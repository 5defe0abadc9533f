"""PR 36: the closed loop's generator keeps its record in arrays reserved
before the window (no copy while clients wait), the replay cells read the
gaps in their acks and the merge module by kernel, and the props form has
its cell at saturation, ``richtext-marks-10k.replay``. No test here gives
a device number."""

import collections
import hashlib
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_perfbench import (BENCH, _cell, _data, _rehearse,  # noqa: E402
                            _rows_in_join_order)

from perfbench import gen, reduce, trace  # noqa: E402
from perfbench.traffic import (Vocabulary, heights, load_json,  # noqa: E402
                               programs, select_metrics, shapes)

CELL = "richtext-marks-10k.replay"
FIVE = ["door.ack_gap_share.replay",
        "kernel.zamboni_merge_ms_per_window.replay",
        "kernel.zamboni_merge_ms_per_window.typing",
        "kernel.merge_outside_kernel_share.replay",
        "kernel.merge_outside_kernel_share.typing"]


# ------------------------------------------------ the generator's record

class _Sock:
    """A door that takes a frame's bytes and says nothing."""

    def sendall(self, data):
        pass


def _scripted(config, traffic, seed, rounds=300):
    """A generator driven without a door, the same way every time: each
    round every connection sends a closed-loop frame, then the door (a
    counter a row) acks each connection's oldest frame, so that one frame
    a connection stays in flight and the multi-writer rows' ops cross."""
    g = gen.Generator(_data(config), _data(traffic), seed)
    lay = g.lay
    row = _rows_in_join_order(lay)
    for c in g.conns:
        c.sock, c.client_id = _Sock(), c.idx + 1
    g.place(row)
    seq = collections.Counter({row[d]: lay.n_joins(d) for d in row})
    unacked = {c.idx: collections.deque() for c in g.conns}
    for r in range(rounds):
        for c in g.conns:
            n0 = g.n_ops
            g.send_frame(c, np.arange(c.n), c.sh)
            unacked[c.idx].append((n0, g.n_ops))
        if r == 0:
            continue
        for c in g.conns:
            lo, hi = unacked[c.idx].popleft()
            ops, pairs = g.ops[lo:hi], []
            for o in ops:
                seq[int(o["row"])] += 1
                pairs.append([int(o["cseq"]), seq[int(o["row"])]])
            g._on_frame(c, {"t": "acks", "acks": pairs,
                            "rows": ops["row"].tolist()}, 0.01 * r)
    return g


def _digest(g) -> str:
    """Every field of the record that the seed decides: the ops but for
    ``ref`` (patched at send time) and when they were due (the clock)."""
    n = g.n_ops
    h = hashlib.sha256()
    for f in ("row", "kind", "a0", "a1", "tidx", "cseq"):
        h.update(np.ascontiguousarray(g.ops[f][:n]).tobytes())
    for a in (g.op_seq, g.op_trecv, g.op_fid, g.op_conn):
        h.update(a[:n].tobytes())
    return h.hexdigest()


#: what the parent's generator (``gen.py`` at PR 34: the record begun at
#: ``1 << 16`` entries and doubled) books for the same script, read off its
#: own code by the same ``_scripted`` and ``_digest``
PARENTS = {
    ("tiny-string", 2_147_483_659): (
        10500, "1f0012480be2873172dcfb138165397980"
               "fc89bc5813195e0042dbc64dde47a3"),
    ("tiny-rich", 3_600_000_011): (
        10500, "49f67eeb8ecfec17e135c810deba1a9a04"
               "1332fc4fb586ad0bb50160ce640a12")}


@pytest.mark.parametrize("config,seed", sorted(PARENTS))
def test_generator_draws_the_parents_records(config, seed, monkeypatch):
    """The record reserved at start holds what the parent's doubled one
    held, field for field and in the same order, and no array of it is
    copied on the way; a record begun small (the parent's policy, which
    ``_grow`` keeps for a run that outlasts the reserve) holds the same."""
    g = _scripted(config, "tiny-replay", seed)
    arrays = [getattr(g, a) for a in gen.RECORD_ARRAYS]
    assert all(len(a) == gen.RECORD for a in arrays)
    # room for a window four times BENCHMARK.json's at 200k ops/s after
    # 1.5M ops of set-up
    assert 4 * BENCH["run_seconds"] * 200_000 + 1_500_000 <= gen.RECORD
    assert (g.n_ops, _digest(g)) == PARENTS[config, seed]
    assert (g.op_seq[:g.n_ops] > 0).sum() > g.n_ops * 0.9
    assert bool(g.rich) == (int((g.ops["kind"][:g.n_ops] == 2).sum()) > 0)
    assert not g.failures or set(g.failures.values()) == {0}
    monkeypatch.setattr(gen, "RECORD", 64)
    small = _scripted(config, "tiny-replay", seed)
    assert len(small.ops) > 64 and small.grew_in_window == 0
    assert (small.n_ops, _digest(small)) == PARENTS[config, seed]
    assert _digest(_scripted(config, "tiny-replay", seed + 1)) \
        != _digest(g)


def test_a_copy_inside_the_window_is_counted(monkeypatch):
    """``_grow`` outside the window is set-up's; inside it the clients
    wait for the copy, and the result says how often."""
    monkeypatch.setattr(gen, "RECORD", 8)
    g = gen.Generator(_data("tiny-string"), _data("tiny-replay"), 5)
    g._grow(9)
    assert len(g.ops) == 16 and g.grew_in_window == 0
    g.t0, g.t1 = gen.now() - 1.0, gen.now() + 60.0
    g._grow(9)                      # fits: nothing copied
    assert len(g.ops) == 16 and g.grew_in_window == 0
    g.n_ops = 16
    g._grow(1)
    assert [len(getattr(g, a)) for a in gen.RECORD_ARRAYS] == [32] * 6
    assert g.grew_in_window == 1
    g.t0, g.t1 = gen.now() - 9.0, gen.now() - 1.0     # the window is over
    g.n_ops = 32
    g._grow(1)
    assert len(g.ops) == 64 and g.grew_in_window == 1


# ------------------------------------------------------ the two readings

def test_ack_gap_share_on_a_planted_gap():
    """A window of 10 s in bins of 0.1 s, 100 acks a bin, evenly: no gap.
    Then nothing for 0.5 s (the clients hear nothing) and the burst that
    follows it in one bin: five bins of a hundred under a quarter of the
    median, and the burst is no gap."""
    t0, t1 = 50.0, 60.0
    even = t0 + (np.arange(10_000) + 0.5) / 1000.0
    assert gen.ack_gap_share(even, t0, t1) == 0.0
    gap = (even >= 53.0) & (even < 53.5)
    planted = np.where(gap, 53.55, even)
    assert gen.ack_gap_share(planted, t0, t1) == pytest.approx(5.0)
    # a bin at 24 of a median of 100 is a gap, one at 25 is not
    thin = np.concatenate([even[~gap], even[gap][:24], even[gap][100:125]])
    assert gen.ack_gap_share(thin, t0, t1) == pytest.approx(4.0)
    # an ack stamped at the window's last instant falls in its last bin
    assert gen.ack_gap_share(np.append(even, np.nextafter(t1, 0)), t0,
                             t1) == 0.0
    assert gen.ack_gap_share(np.zeros(0), t0, t1) is None
    assert gen.ack_gap_share(even, t0, t0) is None
    assert reduce.read_metric("door.ack_gap_share.replay",
                              {"gen.ack_gap_share": 5.0}) == 5.0
    assert reduce.read_metric("door.ack_gap_share.replay", {}) is None


def test_merge_is_read_by_kernel_on_the_recorded_events():
    """``trace_kernels.json``: two chips, on each the merge module twice
    (a plain window and a fused one) and the kernels under the four names
    the program gives them; chip 1 runs the plain form, chip 0 the props
    form. Per chip, as the module's time is."""
    ev = [tuple(e) for e in _data("trace_kernels")["events"]]
    one = trace.reduce_events(ev, devices=[0])["raw"]
    assert one["trace.kernel_s.plain"] == pytest.approx(0.2e-3)
    assert one["trace.kernel_s.zamboni"] == pytest.approx(0.6e-3)
    assert one["trace.kernel_n.plain"] == one["trace.kernel_n.zamboni"] == 1
    mod = "trace.module_s.jit__sharded_columnar_merge"
    assert one[mod] == pytest.approx(2.0e-3)
    for family in ("replay", "typing"):
        assert reduce.read_metric(
            f"kernel.zamboni_merge_ms_per_window.{family}",
            one) == pytest.approx(0.6)
        # 0.8 ms of the module's 2.0 are the kernels'
        assert reduce.read_metric(
            f"kernel.merge_outside_kernel_share.{family}",
            one) == pytest.approx(60.0)
    two = trace.reduce_events(ev, devices=[0, 1])["raw"]
    # chip 1: plain 0.1, zamboni 0.3, module 1.0: the means over two chips
    assert two["trace.kernel_s.plain"] == pytest.approx(0.15e-3)
    assert two["trace.kernel_s.zamboni"] == pytest.approx(0.45e-3)
    assert two["trace.kernel_n.zamboni"] == 1 and two[mod] \
        == pytest.approx(1.5e-3)
    assert reduce.read_metric("kernel.merge_outside_kernel_share.replay",
                              two) == pytest.approx(60.0)
    # the sums by op name, before they are shared out over the chips
    assert trace.kernel_raw(
        {"string_merge.1": 0.1, "string_merge_props.1": 0.2, "fusion.4": 9.0,
         "string_merge_zamboni.1": 0.3, "string_merge_zamboni_props.1": 0.6},
        {"string_merge.1": 1, "string_merge_props.1": 1, "fusion.4": 4,
         "string_merge_zamboni.1": 1, "string_merge_zamboni_props.1": 1}) == {
        "trace.kernel_s.plain": pytest.approx(0.3),
        "trace.kernel_n.plain": 2,
        "trace.kernel_s.zamboni": pytest.approx(0.9),
        "trace.kernel_n.zamboni": 2}
    # a trace with plain windows alone still says so of the fused kind,
    # and the share reads; one with no kernel by that name reads nothing
    plain = [e for e in ev if "zamboni" not in e[2]]
    raw = trace.reduce_events(plain, devices=[0])["raw"]
    assert raw["trace.kernel_n.zamboni"] == 0
    assert reduce.read_metric("kernel.zamboni_merge_ms_per_window.replay",
                              raw) is None
    assert reduce.read_metric("kernel.merge_outside_kernel_share.replay",
                              raw) == pytest.approx(90.0)
    none = [e for e in ev if "string_merge" not in e[2]]
    raw = trace.reduce_events(none, devices=[0])["raw"]
    assert not any(k.startswith("trace.kernel_") for k in raw)
    assert reduce.read_metric("kernel.merge_outside_kernel_share.replay",
                              raw) is None
    assert trace.kernel_raw({"fusion.4": 9.0}, {"fusion.4": 4}) == {}


@pytest.mark.parametrize("name", FIVE)
def test_the_five_new_readings_are_the_manifests(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    family = name.rsplit(".", 1)[1]
    # every replay cell reports it; of the typing cells the one-chip one
    # (a test outside the benchmark's directories holds the mesh typing
    # cell's traced line to the fourteen names it had: PERF.md, section 7)
    assert entry.get("workloads") == {
        "replay": None, "typing": ["richtext-marks-10k.typing"]}[family]
    assert entry["moves"] == {"replay": "acked_ops_per_s",
                              "typing": "ack_p50_ms"}[family]
    assert entry["better"] == "lower"
    assert (entry["unit"], entry["source"]) == {
        "door.ack_gap_share": ("%", "host_clock"),
        "kernel.zamboni_merge_ms_per_window": ("ms", "device_trace"),
        "kernel.merge_outside_kernel_share": ("%", "device_trace")}[
            name.rsplit(".", 1)[0]]
    for w in BENCH["workloads"]:
        tr = load_json("traffic", w["traffic"])
        assert (entry in select_metrics(BENCH, w["name"])[1]) \
            == (tr["family"] == family
                and w["name"] in entry.get("workloads", [w["name"]]))
    # and the program-trace list no longer names it
    with open(os.path.join(os.path.dirname(trace.__file__),
                           "progtrace_per_layer.json")) as f:
        assert name not in {m["name"] for m in json.load(f)}


# -------------------------------------------------------------- the cell

def test_manifest_states_the_cell():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("richtext-marks-10k", "replay", 1)
    assert len(cell["why"]) <= 200
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) == 5 and len(names) == len(set(names))
    # its configuration and its traffic are files other cells run as well
    assert {w["name"] for w in BENCH["workloads"]
            if w["config"] == cell["config"]} \
        == {"richtext-marks-10k.typing", CELL}
    assert "string-deli-10k.replay" in {
        w["name"] for w in BENCH["workloads"]
        if w["traffic"] == cell["traffic"]}
    acked = next(m for m in BENCH["end_to_end"]
                 if m["name"] == "acked_ops_per_s")
    # the bound is the manifest's to state (0.06 until the check of PR 36
    # read the 62k cell wider than half of it): no literal holds it here
    assert acked["workloads"][-1] == CELL and 0.01 <= acked["bound"] <= 0.25
    assert BENCH["run_seconds"] == 30
    # it reports what the string cell under the same traffic reports
    assert select_metrics(BENCH, CELL) == select_metrics(
        BENCH, "string-deli-10k.replay")
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) \
        <= len(BENCH["workloads"]) // 2


def test_its_windows_and_programs_are_swept():
    """The wire is the rich one, so the mix's annotate share applies: the
    frames are ``R`` frames and set-up sweeps the tables that marks and
    characters together pad to, at the string cell's shapes."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    cfg, tr, dep, lay, W = _cell(cell)
    assert cfg["wire"]["frames"] == "R" and len(cfg["wire"]["props"]) == 5
    assert tr["loop"] == "closed" and tr["mix"]["annotate_share"] == 0.25
    assert (tr["connections"], tr["frames_in_flight"],
            tr["multi_writer_docs"]) == (8, 16, 16)
    hs = heights(lay, tr, W)
    assert hs == [8, 16, 240, 248, 256, 264, 272, 496, 504, 512]
    assert len(hs) <= 24
    plain = _cell(next(w for w in BENCH["workloads"]
                       if w["name"] == "string-deli-10k.replay"))
    assert shapes(lay, tr, W) == shapes(plain[3], plain[1], plain[4]) \
        == [(h, 1) for h in hs] + [(512, 4)]
    v = Vocabulary(cfg)
    progs = programs(lay, tr, W, v, rich=True)
    assert {(h, c) for h, c, _ in progs} == set(shapes(lay, tr, W))
    assert len(progs) <= 24 and len(progs) == len(set(progs))
    # the marks take table entries of their own: no height's tables lie
    # under the plain form's
    base = programs(plain[3], plain[1], plain[4], Vocabulary(plain[0]),
                    rich=False)
    for h, c in shapes(lay, tr, W):
        mine = [t for hh, cc, t in progs if (hh, cc) == (h, c)]
        theirs = [t for hh, cc, t in base if (hh, cc) == (h, c)]
        assert min(mine) >= min(theirs) and max(mine) >= max(theirs)


def test_rehearsal_of_the_rich_cell_under_the_closed_loop():
    """The tiny rich configuration under the tiny closed loop, traced:
    the props form through the whole command, every comparison at its
    limit, the new readings in the line where a CPU can give them."""
    r = _rehearse("tiny-rich", "tiny-replay", True)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert all(v["value"] <= v["limit"] for v in r["compared"].values())
    assert r["compared"]["props_differ"] == {"value": 0, "limit": 0}
    assert r["programs"]["swept"] > 0
    assert r["programs"]["swept_not_met"] == r["programs"][
        "new_in_window"] == []
    assert r["notes"] == {"grew_in_window": 0}
    assert r["metrics"]["store.compiles_in_window.replay"]["value"] == 0
    assert 0 <= r["metrics"]["door.ack_gap_share.replay"]["value"] < 100
    assert list(r)[-1] == "compared"
    json.dumps(r)
