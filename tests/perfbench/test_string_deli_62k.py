"""``string-deli-62k``: the committed configuration held to what makes it
one door filled to the rows its wire can name, and its frame shape (a
frame many windows long, ending in half a window) rehearsed tiny on the
CPU through the whole command. No test here gives a device number."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_perfbench import BENCH, _data, _rehearse  # noqa: E402

from perfbench import wire  # noqa: E402
from perfbench.traffic import (Layout, heights, load_json,  # noqa: E402
                               select_metrics)

CELL = "string-deli-62k.replay"
BIG, SMALL = (load_json("configs", n) for n in ("string-deli-62k",
                                                "string-deli-10k"))
REPLAY = load_json("traffic", "replay-62k")


# ------------------------------------------------------ the committed file

def test_population_fits_the_wires_row_field():
    """A document's row travels in ``OP_DTYPE['row']``: the population is
    held to what that field can name, read from the dtype."""
    rows = 1 << (8 * wire.OP_DTYPE["row"].itemsize)
    n = BIG["deployment"]["n_docs"]
    assert SMALL["deployment"]["n_docs"] < n <= rows
    # and no larger population of this frame shape fits under it
    per = n // REPLAY["connections"]
    W = BIG["deployment"]["door"]["window_min_rows"]
    assert (per + W) * REPLAY["connections"] > rows


def test_a_frame_is_a_whole_number_of_half_windows():
    """The documents a connection owns are half a window more than whole
    windows, as in the accepted cells (whose heights
    ``test_perfbench.py`` holds every closed-loop cell to)."""
    dep = BIG["deployment"]
    W, C = dep["door"]["window_min_rows"], REPLAY["connections"]
    assert dep["n_docs"] % C == 0
    assert (dep["n_docs"] // C) % W == W // 2


def test_its_traffic_is_replay_but_for_the_frames_in_flight():
    """``replay-62k.json`` is ``replay.json`` with another count of frames
    in flight (each population sits on its own plateau: PERF.md, section
    4) and nothing else: same family, connections, mix and warm-up."""
    ten, big = dict(load_json("traffic", "replay")), dict(REPLAY)
    assert (ten.pop("name"), big.pop("name")) == ("replay", "replay-62k")
    assert ten.pop("frames_in_flight") > big.pop("frames_in_flight") >= 4
    assert ten == big and big["family"] == "replay"


@pytest.mark.parametrize("group", ["deployment", "guarantees", "wire"])
def test_differs_from_string_deli_10k_by_the_population_alone(group):
    big, small = dict(BIG[group]), dict(SMALL[group])
    if group == "deployment":
        assert big.pop("n_docs") != small.pop("n_docs")
    assert big == small


def test_manifest_states_the_file():
    entry = next(c for c in BENCH["configs"] if c["name"] == BIG["name"])
    assert entry["reduced"] == list(BIG["reduced"]) \
        == list(SMALL["reduced"])
    assert entry["source"] == BIG["source"] and len(entry["source"]) <= 200
    assert set(SMALL["assumed"]) | {"population"} == set(BIG["assumed"])
    cell = BENCH["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, BIG["name"], "replay-62k", 1)
    # it reports what the 10k replay cell reports, under the same names
    # (so ``_rehearse``, which picks the first replay cell, reports it too)
    assert select_metrics(BENCH, CELL) == select_metrics(
        BENCH, "string-deli-10k.replay")


def test_rows_past_the_sign_bit_cross_the_wire():
    """Six rows in ten of this population lie past 32,767: a row is
    unsigned on the wire and comes out of the door's decoders as sent."""
    from fluidframework_tpu.server import columnar_ingress as door
    n = BIG["deployment"]["n_docs"]
    ops = np.zeros(5, wire.OP_DTYPE)
    ops["row"] = [0, 32767, 32768, n - 1, 65535]
    ops["cseq"] = np.arange(5) + 1
    frame = wire.encode_ops(wire.table_prefix(["a"]), ops, False)
    (ftype, payload), = wire.split_frames(bytearray(frame))
    _t, _p, got = door.reference_decode_op_frame(payload, False)
    assert got["row"].tolist() == [0, 32767, 32768, n - 1, 65535]


def test_the_last_row_the_wire_names_is_served():
    """The premise of the population: a door over as many rows as the
    wire's row field counts serves the document on the last of them
    (narrow rows and the XLA scan here: the CPU's test of the door, the
    sequencer and the store's row scatter, not of the kernel)."""
    from fluidframework_tpu.server.columnar_ingress import (
        ColumnarAlfred, ColumnarClient)
    from fluidframework_tpu.server.serving import StringServingEngine
    rows = 1 << (8 * wire.OP_DTYPE["row"].itemsize)
    eng = StringServingEngine(n_docs=rows, capacity=16,
                              batch_window=10 ** 9, sequencer="native")
    for i in range(rows - 2):
        eng.doc_row(f"filler-{i}")
    srv = ColumnarAlfred(eng, window_min_rows=4, window_ms=2.0,
                         decode="native").start_in_thread()
    try:
        cl = ColumnarClient("127.0.0.1", srv.port)
        got = cl.join(["near-last", "last"])
        assert got == {"near-last": rows - 2, "last": rows - 1}
        ops = np.zeros(2, wire.OP_DTYPE)
        ops["row"], ops["tidx"] = [rows - 2, rows - 1], [0, 1]
        ops["cseq"] = ops["ref"] = 1
        cl.send_ops(["x", "yz"], ops)
        acks = cl.recv_json()
        assert acks["t"] == "acks" and acks["rows"] == [rows - 2, rows - 1]
        assert all(seq > 0 for _cseq, seq in acks["acks"])
        cl.close()
        assert (eng.read_text("near-last"), eng.read_text("last"),
                eng.read_text("filler-0")) == ("x", "yz", "")
    finally:
        srv.stop()


# --------------------------------------------- the frame shape, rehearsed

def _half_window_heights(W, S):
    """What a frame of whole windows and a half can be cut into: the
    multi-writer extras alone, the half with and without them, a whole;
    and, since the door cuts a pass's rows before it carves them in rounds
    (PR 31) and every later connection's rows begin ``S`` off the grid,
    the half and the whole less the extras."""
    return {S // 2, S, W // 2, W // 2 + S // 2, W // 2 + S, W,
            W // 2 - S // 2, W // 2 - S, W - S // 2, W - S} - {0}


def test_tiny_wide_has_the_cells_frame_shape():
    dep, tr = _data("tiny-wide")["deployment"], _data("tiny-replay")
    W, C = dep["door"]["window_min_rows"], tr["connections"]
    per = dep["n_docs"] // C
    assert per % W == W // 2 and per // W >= 3      # many windows, and a half
    assert dep["n_docs"] % 8 == 0 and dep["capacity"] % 128 == 0   # a tile
    # the same kinds of window at both sizes
    for d, t in ((dep, tr), (BIG["deployment"], REPLAY)):
        w, s = d["door"]["window_min_rows"], t["multi_writer_docs"]
        lay = Layout(d["n_docs"], t["connections"], s)
        assert set(heights(lay, t, w)) == _half_window_heights(w, s)


def test_rehearsal_of_a_frame_many_windows_long():
    r = _rehearse("tiny-wide", "tiny-replay", False)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert all(v["value"] <= v["limit"] for v in r["compared"].values())
    assert set(r["metrics"]) == {"setup_s", "acked_ops_per_s"}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    json.dumps(r)


def test_unapplied_window_is_not_correct_in_a_long_frame():
    r = _rehearse("tiny-wide", "tiny-replay", False,
                  plant="unapplied_window")
    assert r["correct"] is False
    over = {n for n, v in r["compared"].items() if v["value"] > v["limit"]}
    assert over & {"lengths_differ", "docs_text_differs"}, r["compared"]
