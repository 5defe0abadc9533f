"""The least time the chip could take for a window's merge, from the bytes
and operations the window NEEDS — whatever implements it.

A window of R rows (one op each through the door) needs each touched
document's row of every state plane read once and written once, plus its
op buffer; a zamboni needs the rows touched since the last one, on the
same footing. The program's kernel today passes over every row of the
store for each window; that is its cost, not the window's need, and is
why the share reads low.
"""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
#: bytes of one op on the host→device buffer (kind/client, a0, span, lag,
#: table index, seq base and row index: the program's 5 B/op profile
#: rounded up with its per-row words)
OP_BYTES = 16
#: elementwise operations per slot per op in a merge pass (compares,
#: selects, the prefix sums that locate a position): a generous count, and
#: still far below the bytes' time
OPS_PER_SLOT = 64


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it "
                       "to perfbench/peaks.json with its source")
    return table[device_kind]


def row_bytes(state, n_docs: int) -> float:
    """Bytes of one document's row across all state planes."""
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(state)) / n_docs


def window_need(rows: int, ops: int, zamboni_rows: int, row_b: float,
                slots: int):
    """(bytes, operations) one window needs."""
    touched = rows + zamboni_rows
    return (2.0 * touched * row_b + ops * OP_BYTES,
            float(ops * slots * OPS_PER_SLOT + zamboni_rows * slots))


def least_seconds(windows, state, n_docs: int, device_kind: str) -> dict:
    """``windows``: (when, rows, ops, fused zamboni, rows since the last
    zamboni) for each window dispatched in the traced span."""
    if not windows:
        return {}
    pk = peaks(device_kind)
    rb = row_bytes(state, n_docs)
    slots = state.seq.shape[1]
    least = by_bytes = 0.0
    for _when, rows, ops, _fused, zrows in windows:
        b, o = window_need(rows, ops, zrows, rb, slots)
        tb, to = b / pk["hbm_bytes_per_s"], o / pk["bf16_flops_per_s"]
        least += max(tb, to)
        by_bytes += tb >= to
    return {"roofline.least_s": least,
            "roofline.windows": len(windows),
            "roofline.bound_by_bytes_share": by_bytes / len(windows),
            "roofline.rows_touched": sum(w[1] for w in windows),
            "roofline.row_bytes": rb}
