"""The least time the chip could take for a window's merge, from the bytes
and operations the window NEEDS — whatever implements it.

A window of R rows (one to four ops each through the door) needs each
touched document's row of every state plane read once and written once,
plus its op buffer; a zamboni needs the rows touched since the last one,
on the same footing. The program's kernel since PR 33 visits only the
tiles of 64 rows that hold one of the window's rows (a plain merge; the
fused zamboni still passes over every row of the store), and inside a
tile it is bound by arithmetic, a column at a time; a launch also pays a
tile list and a grid step for every tile it skips. Those are its costs,
not the window's need, and are why the share reads low (2-14%).

On several chips the store's rows lie in contiguous blocks, one a chip
(``parallel/sharded.py:shard_of_rows``), the chips work side by side, and
a window is done when its fullest shard is: its least time is the largest
over the shards of what each needs. One shard is the one-chip arithmetic.
"""

import json
import os

import numpy as np

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


def by_shard(rows, n_docs: int, chips: int, ops_per_row):
    """A window's rows and ops counted by the shard that holds each row:
    two lists of ``chips`` whole numbers."""
    shard = np.asarray(rows, np.int64) // (n_docs // chips)
    return (np.bincount(shard, minlength=chips).tolist(),
            np.bincount(shard, np.asarray(ops_per_row, np.int64),
                        minlength=chips).astype(np.int64).tolist())


def least_seconds(windows, state, n_docs: int, device_kind: str) -> dict:
    """``windows``: (when, rows, ops, fused zamboni, rows since the last
    zamboni) for each window dispatched in the traced span, the three
    counts as lists by shard (:func:`by_shard`)."""
    if not windows:
        return {}
    pk = peaks(device_kind)
    rb = row_bytes(state, n_docs)
    slots = state.seq.shape[1]
    least = by_bytes = 0.0
    for _when, rows, ops, _fused, zrows in windows:
        need = (window_need(r, o, z, rb, slots)
                for r, o, z in zip(rows, ops, zrows, strict=True))
        # the window's fullest shard
        tb, to = max(((b / pk["hbm_bytes_per_s"], o / pk["bf16_flops_per_s"])
                      for b, o in need), key=max)
        least += max(tb, to)
        by_bytes += tb >= to
    return {"roofline.least_s": least,
            "roofline.windows": len(windows),
            "roofline.bound_by_bytes_share": by_bytes / len(windows),
            "roofline.rows_touched": sum(sum(w[1]) for w in windows),
            "roofline.row_bytes": rb}
