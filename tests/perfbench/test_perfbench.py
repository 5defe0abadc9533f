"""The benchmark's own tests: the whole command rehearsed tiny on the CPU
(Pallas interpreted), the controls (a planted fault has to come out not
correct), and the yardstick's arithmetic checked against hand-worked cases.
No test here gives a device number."""

import itertools
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

from perfbench import faults, reduce, roofline, trace, wire  # noqa: E402
from perfbench.refdoc import RefDoc  # noqa: E402
from perfbench.traffic import (WINDOW_COLUMNS, Layout, OpMaker,  # noqa: E402
                               Vocabulary, carve, heights, load_json,
                               programs, select_metrics, shapes, table_size)

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _data(name):
    with open(os.path.join(DATA, name + ".json")) as f:
        return json.load(f)


def _rehearse(config, traffic, trace_on, plant="", seed=2_147_483_659,
              chips=1):
    """The rest of a run, without the harness's look for a chip (on
    several chips: the CPU's virtual devices, which conftest.py makes)."""
    from perfbench import harness
    # the tiny cell reports what the committed cell of its mix and its
    # chips reports, picked by the rule the command itself uses
    like = next(w["name"] for w in BENCH["workloads"]
                if "tiny-" + w["traffic"] == traffic
                and (w["chips"] > 1) == (chips > 1))
    end_to_end, per_layer = select_metrics(BENCH, like)
    return harness.run_cell(
        {"name": f"{config}.{traffic}", "chips": chips}, _data(config),
        _data(traffic),
        {"config": os.path.join(DATA, config + ".json"),
         "traffic": os.path.join(DATA, traffic + ".json")},
        seed=seed, seconds=1.0, trace_on=trace_on, t_start=time.monotonic(),
        end_to_end=end_to_end, per_layer=per_layer, require_tpu=False,
        plant=plant)


# ------------------------------------------------------ the whole command

# what a CPU's trace cannot give: it has no line of modules, the roofline
# needs the chip's peaks, and the CPU reports no memory
# (nor an op by the Pallas kernel's name: the interpreter runs it as XLA ops)
CHIP_ONLY = {"kernel.merge_ms_per_window.replay", "merge_roofline.replay",
             "device.peak_hbm_bytes.replay",
             "kernel.zamboni_merge_ms_per_window.replay",
             "kernel.merge_outside_kernel_share.replay"}


@pytest.mark.parametrize("config,traffic,trace_on,chips", [
    ("tiny-string", "tiny-replay", False, 1),
    ("tiny-rich", "tiny-typing", True, 1),
    ("tiny-string", "tiny-replay", False, 4),
    ("tiny-string", "tiny-replay", True, 4)])
def test_rehearsal_of_a_cell(config, traffic, trace_on, chips):
    r = _rehearse(config, traffic, trace_on, chips=chips)
    assert list(r)[:5] == RESULT_KEYS and list(r)[-1] == "compared"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert all(v["value"] <= v["limit"] for v in r["compared"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes", "memory"} \
        <= set(r["device"])
    assert [m["id"] for m in r["device"]["memory"]] == list(range(chips))
    # set-up met every program it was sent for, and the window none new
    assert r["programs"]["swept"] > 0
    assert r["programs"]["swept_not_met"] == r["programs"][
        "new_in_window"] == []
    # and the generator copied no array of its record inside the window
    assert r["notes"] == {"grew_in_window": 0}
    family = traffic.split("-")[1]
    names = set(r["metrics"])
    if trace_on:
        assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
        assert r["metrics"][f"store.compiles_in_window.{family}"][
            "value"] == 0
        assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
        assert not any("roofline" in n for n in names)   # no CPU roofline
        if chips == 4:
            # what the committed mesh replay cell reports, by the command's
            # own rule: the entries that list it or list no cell
            want = {m["name"] for m in select_metrics(
                BENCH, "string-deli-10k-mesh4.replay")[1]} - CHIP_ONLY
            assert names == want and want
            assert 0 < r["metrics"]["device.busy_min_over_max.replay"][
                "value"] <= 1
            assert r["metrics"]["device.chip0_busy_over_mean.replay"][
                "value"] > 0
    else:
        assert names == {"setup_s", "acked_ops_per_s"}
        assert all(v["value"] > 0 for v in r["metrics"].values())
    json.dumps(r)


def test_population_divides_over_the_cells_chips():
    """A population that does not divide over the cell's chips is refused
    as the server is built, before any traffic."""
    with pytest.raises(ValueError, match="do not divide"):
        _rehearse("tiny-string", "tiny-replay", False, chips=3)


# each fault, the cell it is planted in, and the numbers of which one has to
# catch it (a window of the documents written alone shows in their lengths,
# one of the multi-writer documents in their text)
MERGED = ("lengths_differ", "docs_text_differs")
FAULTS = {"unapplied_window": ("tiny-string", "tiny-replay", MERGED),
          "half_window": ("tiny-string", "tiny-replay", MERGED),
          "skipped_append": ("tiny-string", "tiny-replay", ("log_differs",)),
          "altered_ack": ("tiny-string", "tiny-replay", ("acks_failed",)),
          "dropped_annotates": ("tiny-rich", "tiny-typing",
                                ("props_differ",)),
          "unsharded_state": ("tiny-string", "tiny-replay",
                              ("guarantees_weakened",))}
# a fault only a cell on a mesh can have, and two of the one-chip cell's
# that it can have as well
MESH_FAULTS = ("unsharded_state", "unapplied_window", "skipped_append")


@pytest.mark.parametrize("fault,chips", [
    (f, 1) for f in sorted(set(faults.PLANTS) - {"unsharded_state"})] + [
    (f, 4) for f in MESH_FAULTS])
def test_planted_fault_is_not_correct(fault, chips):
    config, traffic, numbers = FAULTS[fault]
    r = _rehearse(config, traffic, False, plant=fault, chips=chips)
    assert r["correct"] is False
    over = {n for n, v in r["compared"].items() if v["value"] > v["limit"]}
    assert over & set(numbers), r["compared"]
    if fault == "unsharded_state":
        # every answer stays right: the placement alone is gone
        assert over == {"guarantees_weakened"}, r["compared"]
        assert r["compared"]["guarantees_weakened"]["value"] == 1


def test_command_refuses_without_a_chip():
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


# ------------------------------------------------ manifest, found by name

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_manifest_finds_every_file_by_name():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert load_json("configs", c["name"])["name"] == c["name"]
        assert c["file"] == f"perfbench/configs/{c['name']}.json"
        assert set(c["reduced"]) == set(
            load_json("configs", c["name"])["reduced"])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and all(0.01 <= m["bound"] <= 0.25
                                    for m in e2e.values())
    cells = {w["name"] for w in BENCH["workloads"]}
    for w in BENCH["workloads"]:
        assert w["config"] in configs and NAME.match(w["name"])
        tr = load_json("traffic", w["traffic"])
        assert tr["name"] == w["traffic"] and len(w["why"]) <= 200
        reported = {m["name"] for m in BENCH["end_to_end"]
                    if w["name"] in m.get("workloads", cells)}
        assert "setup_s" in reported and len(reported) >= 2
        assert any(w["name"] in m.get("workloads", cells)
                   for m in BENCH["per_layer"])


@pytest.mark.parametrize("metric", BENCH["per_layer"] + BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_metric_has_a_reader_of_its_own(metric):
    spec = load_json("metrics", metric["name"])
    assert set(spec) <= {"reduce", "key", "num", "den", "scale", "module",
                         "num_n", "den_n"}
    assert NAME.match(metric["name"])
    if "moves" in metric:
        moved = next(m for m in BENCH["end_to_end"]
                     if m["name"] == metric["moves"])
        every = [w["name"] for w in BENCH["workloads"]]
        cells = metric.get("workloads", moved.get("workloads", every))
        # each cell it lists reports the metric it moves, and reports it
        assert set(cells) <= set(moved.get("workloads", every))
        for cell in cells:
            assert metric in select_metrics(BENCH, cell)[1]
        # the suffix names the traffic family of the cells that report it
        assert {metric["name"].rsplit(".", 1)[1]} == {
            load_json("traffic", w["traffic"])["family"]
            for w in BENCH["workloads"] if w["name"] in cells}
    assert reduce.read_metric(metric["name"], {}) is None   # nothing read


def test_every_traced_cell_line_has_its_metrics():
    """A cell reports the per-layer entries that list it, or that list
    nothing and move a metric it reports: the mesh's replay cell reports
    every metric of the one-chip replay cell, under the same names, and
    the two that exist only across chips."""
    def listed(cell):
        return {m["name"] for m in BENCH["per_layer"]
                if m["name"].endswith(".replay")
                and cell in m.get("workloads", [cell])}
    one = {m["name"] for m in select_metrics(
        BENCH, "string-deli-10k.replay")[1]}
    four = {m["name"] for m in select_metrics(
        BENCH, "string-deli-10k-mesh4.replay")[1]}
    # the rule and the manifest agree: an entry with no list is every
    # replay cell's, one with a list its cells' alone (a later PR's
    # metric that lists its own cell changes neither of these two)
    assert one == listed("string-deli-10k.replay") and one
    assert four == listed("string-deli-10k-mesh4.replay")
    assert {"device.chip0_busy_over_mean.replay",
            "device.busy_min_over_max.replay"} <= four - one
    shared = {m["name"] for m in BENCH["per_layer"]
              if m["name"].endswith(".replay") and "workloads" not in m}
    assert shared <= one and shared <= four
    e2e = select_metrics(BENCH, "string-deli-10k-mesh4.replay")[0]
    assert [m["name"] for m in e2e] == ["acked_ops_per_s", "setup_s"]


def test_end_to_end_metrics_are_read_from_files_too():
    raw = {"acked": 90_000, "window_s": 2.0, "setup_s": 21.5,
           "gen.ack_p50_ms": 29.0}
    assert reduce.read_metric("acked_ops_per_s", raw) == 45_000.0
    assert reduce.read_metric("ack_p50_ms", raw) == 29.0
    assert reduce.read_metric("setup_s", raw) == 21.5


def test_reducers():
    assert reduce.read_metric("door.ops_per_window.replay",
                              {"d.ops": 850.0, "d.windows": 2.0}) == 425.0
    assert reduce.read_metric(
        "executor.busiest_stage_occupancy.replay",
        {"d.stage_busy_ms.pack": 100.0, "d.stage_busy_ms.seq_dispatch": 600.0,
         "d.stage_busy_ms.log": 50.0, "window_ms": 1000.0}) == 0.6
    assert reduce.read_metric(
        "device.idle_share.replay",
        {"trace.busy_s": 0.5, "trace.window_s": 4.0}) == 87.5
    assert reduce.read_metric("device.idle_share.replay",
                              {"trace.busy_s": 0.5}) is None
    # one kernel's module under the name it has on one chip, on a mesh
    # today, or as a later PR may name it there: the first that the trace
    # has is read, and never two summed
    for mod in ("jit__columnar_merge_jit", "jit__sharded_columnar_merge",
                "jit_fn"):
        raw = {f"trace.module_s.{mod}": 0.9, f"trace.module_n.{mod}": 2000.0,
               "trace.module_s.jit_other": 5.0,
               "trace.module_n.jit_other": 10.0,
               "roofline.least_s": 0.0225, "roofline.windows": 1000.0}
        for family in ("replay", "typing"):
            assert reduce.read_metric(
                f"kernel.merge_ms_per_window.{family}",
                raw) == pytest.approx(0.45)
            # a window's mean need over the module's mean run: the host
            # counted 1,000 windows where the device ran 2,000
            assert reduce.read_metric(f"merge_roofline.{family}",
                                      raw) == pytest.approx(5.0)
        raw["trace.module_s.jit_fn"], raw["trace.module_n.jit_fn"] = 9.0, 1.0
        if mod != "jit_fn":
            assert reduce.read_metric("kernel.merge_ms_per_window.replay",
                                      raw) == pytest.approx(0.45)
        del raw["roofline.windows"]
        assert reduce.read_metric("merge_roofline.replay", raw) is None
    assert reduce.read_metric(
        "kernel.merge_ms_per_window.replay",
        {"trace.module_s.jit_other": 1.0,
         "trace.module_n.jit_other": 9.0}) is None
    busy = {"trace.busy_s": 0.5, "trace.busy_s.0": 0.8, "trace.busy_s.1": 0.4,
            "trace.busy_s.2": 0.4, "trace.busy_s.3": 0.4,
            "trace.busy_s.max": 0.8, "trace.busy_s.min": 0.4}
    assert reduce.read_metric("device.chip0_busy_over_mean.replay",
                              busy) == 1.6
    assert reduce.read_metric("device.busy_min_over_max.replay", busy) == 0.5


# ------------------------------------------------------------- generator

MIX = load_json("traffic", "replay")["mix"]
VOCAB = {rich: Vocabulary(load_json("configs", name)) for rich, name in (
    (False, "string-deli-10k"), (True, "richtext-marks-10k"))}


def _maker(seed, rich=False):
    return OpMaker(seed, 3, 200, MIX, rich, VOCAB[rich])


@pytest.mark.parametrize("rich", [False, True])
def test_generator_is_a_function_of_the_seed(rich):
    li, rows = np.arange(200), np.arange(200) + 1000
    a, b, c = _maker(2**31 + 11, rich), _maker(2**31 + 11, rich), \
        _maker(12, rich)
    for _ in range(30):
        x, y, z = (m.make(li, rows) for m in (a, b, c))
        assert x.tobytes() == y.tobytes()
    assert x.tobytes() != z.tobytes()
    assert (not rich) == (int((x["kind"] == wire.ANN).sum()) == 0)


@pytest.mark.parametrize("rich", [False, True])
def test_documents_grow_as_the_source_and_settle_under_the_cap(rich):
    m = _maker(5, rich)
    li, rows = np.arange(200), np.arange(200)
    for _ in range(MIX["fill_rounds"]):
        m.make(li, rows, fill=True)
    start = float(m.length.mean())
    assert start == MIX["fill_rounds"] * 24
    kinds = np.zeros(3, np.int64)
    for i in range(3000):
        ops = m.make(li, rows)
        if i < 100:                 # still below the band: the source's mix
            kinds += np.bincount(ops["kind"], minlength=3)[:3]
        cut = ops["kind"] != wire.INS
        assert (ops["a0"][cut] < ops["a1"][cut]).all()
        assert (ops["a1"][ops["kind"] == wire.REM]
                - ops["a0"][ops["kind"] == wire.REM] == 1).all()
        assert int(m.length.max()) <= MIX["cap_len"] < 256
    text = kinds[wire.INS] + kinds[wire.REM]
    assert abs(kinds[wire.INS] / text - MIX["insert_share"]) < 0.02
    assert (kinds[wire.ANN] > 0) == rich
    if rich:
        assert abs(kinds[wire.ANN] / kinds.sum() - 0.25) < 0.02
    assert abs(float(m.length.mean()) - MIX["target_len"]) < 8


def test_frames_carry_the_tables_they_use():
    v = VOCAB[True]
    ops = _maker(7, True).make(np.arange(200), np.arange(200))
    for _ in range(20):
        ops = _maker(7, True).make(np.arange(200), np.arange(200))
    prefix, recs = wire.frame_tables(ops, v.texts, v.props)
    ins, ann = ops["kind"] == wire.INS, ops["kind"] == wire.ANN
    n_t, n_p = len(set(ops["tidx"][ins])), len(set(ops["tidx"][ann]))
    assert prefix[0] == n_t and 8 < n_t < 60
    assert int(recs["tidx"][ins].max()) == n_t - 1
    assert int(recs["tidx"][ann].max(initial=0)) == max(n_p - 1, 0)
    # the same characters, found through the frame's own table
    from fluidframework_tpu.server import columnar_ingress as door
    frame = wire.encode_ops(prefix, recs, True)
    (ftype, payload), = wire.split_frames(bytearray(frame))
    t, p, got = door.reference_decode_op_frame(payload, True)
    assert [t[i] for i in got["tidx"][ins]] \
        == [v.texts[i] for i in ops["tidx"][ins]]
    assert [p[i] for i in got["tidx"][ann]] \
        == [v.props[i] for i in ops["tidx"][ann]]


def _cell(cell):
    cfg = load_json("configs", cell["config"])
    tr = load_json("traffic", cell["traffic"])
    dep = cfg["deployment"]
    lay = Layout(dep["n_docs"], tr["connections"], tr["multi_writer_docs"])
    return cfg, tr, dep, lay, dep["door"]["window_min_rows"]


def _table_of(frames):
    ops = np.concatenate(frames)
    return table_size(len(set(ops["tidx"][ops["kind"] == wire.INS]))
                      + len(set(ops["tidx"][ops["kind"] == wire.ANN])))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_programs_cover_the_table_sizes_a_window_meets(cell):
    """Draw windows of every shape from the mix itself (a column is a
    frame's op on each of the window's rows) and see that the
    payload-table size each pads to is one set-up dispatches on purpose."""
    cfg, tr, dep, lay, W = _cell(cell)
    rich, v = bool(cfg["wire"]["props"]), Vocabulary(cfg)
    progs = programs(lay, tr, W, v, rich)
    assert len(progs) <= 64
    assert {(h, c) for h, c, _ in progs} == set(shapes(lay, tr, W))
    assert table_size(0) == table_size(8) == 8 and table_size(9) == 16
    mk = OpMaker(3, 0, 1280, tr["mix"], rich, v)
    li = np.arange(1280)
    for _ in range(tr["mix"]["fill_rounds"]):
        mk.make(li, li, fill=True)
    for h, cols in shapes(lay, tr, W):
        for _ in range(40):
            tab = _table_of([mk.make(li[:h], li[:h]) for _ in range(cols)])
            assert (h, cols, tab) in progs, (h, cols, tab)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_setup_frames_fill_the_table_they_are_sent_for(cell):
    """Every window of a program's compaction cycle in set-up has to pad
    to that program's table size, the small heights too: the fused zamboni
    falls on one window of the sixteen, whichever it is. A window's
    columns are as many frames on the same rows."""
    from perfbench.gen import Generator
    cfg, tr, dep, lay, W = _cell(cell)
    rich = bool(cfg["wire"]["props"])

    class Stub:
        vocab = Vocabulary(cfg)
    mk = OpMaker(11, 0, 1280, tr["mix"], rich, Stub.vocab)
    li = np.arange(1280)
    for _ in range(tr["mix"]["fill_rounds"]):
        mk.make(li, li, fill=True)
    for h, cols, tab in programs(lay, tr, W, Stub.vocab, rich):
        for _ in range(dep["engine"]["compact_every"]):
            frames, skip = [], 0
            for _ in range(cols):
                ops = mk.make(li[:h], li[:h],
                              inserts=tab // 2 + 1 if tab > 8 else 0)
                skip = Generator._fill_table(Stub, ops, tab, skip)
                frames.append(ops)
            assert _table_of(frames) == tab, (h, cols, tab)


def _door_windows(rows, window_rows, fused=()):
    """A drain pass's rows (one entry an op) carved as the door carves
    since PR 31, row by row: [(the window's rows, its columns)]. The
    shapes are ``traffic.carve``'s, which keeps no rows."""
    uniq, pending = np.unique(rows, return_counts=True)
    out = []
    for s in range(0, len(uniq), window_rows):
        at, todo = uniq[s:s + window_rows], pending[s:s + window_rows]
        while len(at):
            cols = 1
            if len(at) == window_rows and len(out) not in fused:
                cols = next(c for c in WINDOW_COLUMNS if c <= todo.min())
            out.append((at, cols))
            at, todo = at[todo > cols], todo[todo > cols] - cols
    assert [(len(r), c) for r, c in out] == carve(pending, window_rows, fused)
    return out


def _rows_in_join_order(lay):
    """Row of every document as the door gives them out: in the order the
    connections join (``Layout.doc_names``), so the multi-writer rows
    follow connection 0's own."""
    row = {}
    for c in range(lay.C):
        for d in lay.doc_names(c):
            row.setdefault(d, len(row))
    return row


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_window_heights_come_from_the_closed_set(cell):
    """Carve random drain passes the way the door does (rows sorted, cut
    every window_min_rows, a full chunk whose every row has four pending
    four wide unless the zamboni is fused into it, what is left one wide)
    and see that no shape outside ``shapes`` can come up: closed loop or
    open, whatever the connections' frames in the pass."""
    cfg, tr, dep, lay, W = _cell(cell)
    hs = heights(lay, tr, W)
    ok = set(shapes(lay, tr, W))
    assert {h for h, c in ok if c == 1} == set(hs) and max(hs) == W
    assert len(hs) <= 24 and len(ok) <= len(hs) + len(WINDOW_COLUMNS) - 1
    # the runs Layout states are the rows in join order
    row = _rows_in_join_order(lay)
    wrote = [set(lay.doc_names(c)[:lay.P]) | set(lay.writes_shared(c))
             for c in range(lay.C)]
    writers = [frozenset(c for c in range(lay.C) if d in wrote[c])
               for d in sorted(row, key=row.get)]
    runs = [(len(list(g)), w) for w, g in itertools.groupby(writers)]
    assert runs == lay.runs()
    rng = np.random.default_rng(0)
    closed = tr["loop"] == "closed"
    per = lay.P if closed else tr["ops_per_frame"]
    most = tr["frames_in_flight"] if closed else 2
    shared = set(lay.shared_names())
    solo = [np.asarray([row[d] for d in lay.doc_names(c)[:lay.P]
                        if d not in shared]) for c in range(lay.C)]
    co = [np.asarray([row[d] for d in lay.writes_shared(c)], np.int64)
          for c in range(lay.C)]
    seen = set()
    for _ in range(300):
        rows = []
        # few frames a connection tell the shapes apart; more add none
        deep = most if rng.random() < 0.3 else min(most, 4)
        for c in range(lay.C):
            # a closed loop's frame is every document it writes; an open
            # loop's the next ``per`` (no frame twice in a pass: no stall
            # wraps the cycle), its first with the shared
            q0 = 0 if closed or rng.random() < 0.2 else \
                int(rng.integers(1, lay.P // per - most))
            for f in range(int(rng.integers(0, deep + 1))):
                q = q0 if closed else q0 + f
                lo = q * per - (lay.S if c == lay.owner and q else 0)
                hi = (q + 1) * per - (lay.S if c == lay.owner else 0)
                rows += [solo[c][lo:hi]] + ([co[c]] if q == 0 else [])
        if not rows:
            continue
        fused = {int(rng.integers(0, 16))} if rng.random() < 0.5 else ()
        got = {(len(r), c) for r, c in _door_windows(
            np.concatenate(rows), W, fused)}
        assert got <= ok, (sorted(got - ok), sorted(ok))
        seen |= got
    if closed:
        # and the closed loop meets every one of them
        assert seen == ok, sorted(ok - seen)


@pytest.mark.parametrize("handed,every", [(0, 16), (13, 16), (2, 3),
                                          (0, 0)])
def test_carve_is_the_doors_carving(handed, every):
    """``traffic.carve`` is a copy of the door's rule: the door's own
    ``_build_windows`` cuts random passes into the same shapes, whichever
    windows the engine (``every``-th it is handed, ``handed`` so far)
    fuses its zamboni into."""
    from fluidframework_tpu.server.columnar_ingress import ColumnarAlfred
    from fluidframework_tpu.utils import tracing
    W, n_rows = 8, 50
    rng = np.random.default_rng([handed, every])
    eng = type("Eng", (), {"n_docs": n_rows, "compact_every": every})()
    for most in (1, 3, 4, 9):
        door = ColumnarAlfred(eng, window_min_rows=W)
        door._pass_tl = tracing.new_record(pid=0, frames=0, ops=0,
                                           admit_ms=0.0)
        door._windows_to = [handed]
        pending = rng.integers(0, most + 1, n_rows)
        pending[:W] = most                  # a chunk that can widen
        rows = rng.permutation(np.repeat(np.arange(n_rows), pending))
        door._texts, door._props = ["t"], []
        zeros = np.zeros(rows.size, np.int32)
        door._parts = [dict(
            {k: zeros for k in ("kind", "gidx", "a0", "a1", "ref")},
            sess=object(), row=rows.astype(np.int32),
            cseq=np.arange(rows.size, dtype=np.int32),
            client=np.full(rows.size, 7, np.int32))]
        got = [w["kind"].shape for w in door._build_windows()]
        fused = {i for i in range(len(got))
                 if every > 1 and (handed + i + 1) % every == 0}
        assert got == carve(pending[pending > 0], W, fused)
        # wide only where a chunk's every row had four, and then surely
        # once a fused window no longer stands in its way
        wide = max(c for _, c in got) > 1
        assert wide <= (most >= 4) and (wide or most < 9)


def test_wire_copy_speaks_the_doors_protocol():
    from fluidframework_tpu.server import columnar_ingress as door
    assert wire.OP_DTYPE == door._OP_DTYPE
    assert WINDOW_COLUMNS == door._WINDOW_COLUMNS
    texts, props = ["a", "bee"], [{"bold": True}, {"bold": None}]
    ops = np.zeros(3, wire.OP_DTYPE)
    ops["row"], ops["tidx"] = [1, 2, 3], [1, 0, 1]
    for pr in (None, props):
        ops["kind"] = [0, 1, 2 if pr else 1]
        frame = wire.encode_ops(wire.table_prefix(texts, pr), ops,
                                pr is not None)
        assert frame == door.encode_op_batch(texts, ops, props=pr)
        buf = bytearray(frame + frame[:7])
        (ftype, payload), = wire.split_frames(buf)
        assert len(buf) == 7 and ftype == ord("R" if pr else "B")
        t, p, got = door.reference_decode_op_frame(payload, pr is not None)
        assert t == texts and p == (pr or []) and (got == ops).all()


# ------------------------------------------------------- plain reference

def test_reference_semantics_hand_worked():
    d = RefDoc()
    d.apply(1, 1, 0, wire.INS, 0, 0, "abcd")
    # two clients insert at the same place of the same view: the later
    # sequenced run goes in front (directly after the view's position)
    d.apply(2, 2, 1, wire.INS, 2, 0, "XX")
    d.apply(3, 3, 1, wire.INS, 2, 0, "YY")
    assert d.text() == "abYYXXcd"
    # a remove speaks of its own view: text it never saw survives
    d.apply(4, 1, 1, wire.REM, 1, 3, None)
    assert d.text() == "aYYXXd"
    # overlapping removes: the earlier one counts, the later is a no-op
    d.apply(5, 2, 3, wire.REM, 0, 3, None)   # view "abYYXXcd": removes abY
    assert d.text() == "YXXd" and d.live == 4
    # annotate: last sequenced writer wins per key; None deletes the key
    d.apply(6, 1, 5, wire.ANN, 0, 2, {"bold": True})
    d.apply(7, 2, 5, wire.ANN, 1, 3, {"bold": None})
    assert d.props() == [{"bold": True}, {}, {}, {}]
    with pytest.raises(ValueError):
        d.apply(9, 1, 7, wire.INS, 0, 0, "z")
    with pytest.raises(IndexError):
        d.apply(8, 1, 7, wire.INS, 99, 0, "z")


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_agrees_with_the_programs_oracle_on_crossing_ops(seed):
    """Not a dependency (the reference imports nothing of the program):
    a second witness that the two state the same semantics."""
    from fluidframework_tpu.core.protocol import (
        MessageType, SequencedDocumentMessage)
    from fluidframework_tpu.models.merge_tree_client import SequenceClient
    rng = np.random.default_rng(seed)
    reps = [SequenceClient(i + 1) for i in range(3)]
    observer, ref = SequenceClient(99), RefDoc()
    marks = [{"bold": True}, {"color": "red"}, {"bold": None}]
    seq, pending = 0, []
    for step in range(400):
        w = int(rng.integers(0, 3))
        rep, n = reps[w], reps[w].get_length()
        roll = rng.random()
        if n < 4 or roll < 0.45:
            text = "abcdefg"[: int(rng.integers(1, 5))]
            op = rep.insert_text_local(int(rng.integers(0, n + 1)), text)
            rec = (wire.INS, op["pos"], 0, text)
        else:
            a = int(rng.integers(0, n - 2))
            b = a + int(rng.integers(1, 3))
            if roll < 0.75:
                rep.remove_range_local(a, b)
                rec = (wire.REM, a, b, None)
            else:
                m = marks[int(rng.integers(0, 3))]
                rep.annotate_range_local(a, b, m)
                rec = (wire.ANN, a, b, m)
        pending.append((w, rep.client_seq, rep.last_processed_seq, rec))
        # sequence some of what is in flight: ops cross
        while pending and rng.random() < 0.6:
            w2, cseq, ref_seq, (k, a0, a1, pay) = pending.pop(0)
            seq += 1
            c = {"mt": "insert", "kind": 0, "pos": a0, "text": pay} \
                if k == wire.INS else \
                {"mt": "remove", "start": a0, "end": a1} if k == wire.REM \
                else {"mt": "annotate", "start": a0, "end": a1, "props": pay}
            c["clientSeq"] = cseq
            msg = SequencedDocumentMessage(
                doc_id="d", client_id=w2 + 1, client_seq=cseq,
                ref_seq=ref_seq, seq=seq, min_seq=0, type=MessageType.OP,
                contents=c)
            for r in reps + [observer]:
                r.apply_msg(msg)
            ref.apply(seq, w2 + 1, ref_seq, k, a0, a1, pay)
    text = observer.get_text()
    assert ref.text() == text and seq > 100
    for pos in range(len(text)):
        seg, _ = observer.tree.get_containing_segment(pos)
        assert ref.props()[pos] == dict(seg.props)


# ------------------------------------------- trace reduction and roofline

def test_trace_reduction_on_the_recorded_trace():
    ev = [tuple(e) for e in _data("trace_small")["events"]]
    red = trace.reduce_events(ev)
    assert red["window_s"] == pytest.approx(1.3e-3)
    assert red["busy_s"] == pytest.approx(0.5e-3)     # 0.4 union + 0.1
    raw = red["raw"]
    assert raw["trace.module_s.jit__columnar_merge_jit"] \
        == pytest.approx(0.4e-3)
    assert raw["trace.module_n.jit__columnar_merge_jit"] == 1
    assert dict(red["breakdown"]["device_ops"]) == pytest.approx(
        {"fusion.1": 0.3e-3, "_columnar_merge_jit.1": 0.3e-3})
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({"store.apply_planes": 0.5e-3,
                                  "door.drain": 0.1e-3,
                                  "door.fan_acks": 0.1e-3,
                                  "_no_benchmark_span_": 0.1e-3})
    assert sum(gaps.values()) + red["busy_s"] \
        == pytest.approx(red["window_s"])
    assert reduce.read_metric("device.idle_share.replay", raw) \
        == pytest.approx(100 * 0.8 / 1.3)
    with pytest.raises(ValueError):
        trace.reduce_events([e for e in ev if e[0] == "/host:CPU"])
    assert raw["trace.busy_s.0"] == raw["trace.busy_s.max"] \
        == raw["trace.busy_s.min"] == raw["trace.busy_s"]


def test_trace_reduction_reads_the_cells_chips_alone():
    """A one-chip cell on a four-chip host reads the chip it uses, not
    the idle planes beside it; a cell on several reads each of them."""
    ev = [tuple(e) for e in _data("trace_small")["events"]]
    alone = trace.reduce_events(ev, devices=[0])
    assert alone == trace.reduce_events(ev)
    # a second chip that ran one op of 0.1 ms in the same 1.3 ms
    lo = min(e[3] for e in ev)
    both = ev + [("/device:TPU:1", trace.OPS_LINE, "%fusion.9 = s32[] x",
                  lo + 200_000, 100_000),
                 ("/device:TPU:1", trace.MODULES_LINE, "jit_other(7)",
                  lo + 200_000, 100_000)]
    assert trace.reduce_events(both, devices=[0]) == alone
    two = trace.reduce_events(both, devices=[0, 1])
    assert two == trace.reduce_events(both)
    raw = two["raw"]
    assert raw["trace.busy_s.0"] == pytest.approx(0.5e-3)
    assert raw["trace.busy_s.1"] == raw["trace.busy_s.min"] \
        == pytest.approx(0.1e-3)
    assert raw["trace.busy_s.max"] == raw["trace.busy_s.0"]
    assert two["busy_s"] == raw["trace.busy_s"] == pytest.approx(0.3e-3)
    # a module's time is per chip; the ops stay summed over the chips
    assert raw["trace.module_s.jit__columnar_merge_jit"] \
        == pytest.approx(0.2e-3)
    assert raw["trace.module_n.jit_other"] == 0.5
    assert dict(two["breakdown"]["device_ops"])["fusion.9"] \
        == pytest.approx(0.1e-3)
    # per chip, idle and busy add up to the window
    assert sum(v for _k, v in two["breakdown"]["idle_gaps"]) \
        + two["busy_s"] == pytest.approx(two["window_s"])
    # a chip of the cell that the trace has no plane for did nothing
    three = trace.reduce_events(both, devices=[0, 1, 2])["raw"]
    assert three["trace.busy_s.2"] == three["trace.busy_s.min"] == 0.0
    assert three["trace.busy_s"] == pytest.approx(0.2e-3)
    with pytest.raises(ValueError, match="no device plane"):
        trace.reduce_events(both, devices=[2, 3])


def test_roofline_counts_the_bytes_a_window_needs():
    # a 512-row window of a 10,240 x 512 store whose planes hold 231 MB:
    # 22,559 B a row; read and written: 512 x 22,559 x 2 = 23.1 MB, plus
    # 512 ops x 16 B; at 819 GB/s that is 28.2 us, and the bytes bind
    rb = 231_000_000 / 10_240
    b, o = roofline.window_need(512, 512, 0, rb, 512)
    assert b == pytest.approx(2 * 512 * rb + 512 * 16)
    pk = roofline.peaks("TPU v5 lite")
    assert b / pk["hbm_bytes_per_s"] == pytest.approx(28.2e-6, rel=0.01)
    assert o / pk["bf16_flops_per_s"] < b / pk["hbm_bytes_per_s"]
    # a fused zamboni adds the rows touched since the last one
    b2, _ = roofline.window_need(512, 512, 6800, rb, 512)
    assert b2 - b == pytest.approx(2 * 6800 * rb)

    class State:                         # 10 planes' worth of one leaf
        class seq:
            shape = (10_240, 512)
    import jax.numpy as jnp
    leaf = jnp.zeros((1024, 8), jnp.int32)
    assert roofline.row_bytes({"a": leaf, "b": leaf}, 1024) == 64.0


def test_roofline_of_a_window_is_its_fullest_shards():
    import collections
    import jax.numpy as jnp
    leaf = jnp.zeros((1024, 8), jnp.int32)
    state = collections.namedtuple("State", "seq length")(leaf, leaf)  # 64 B
    pk = roofline.peaks("TPU v5 lite")
    kind = "TPU v5 lite"
    # one shard: the one-chip arithmetic, to the last bit
    wins = [(0.0, [512], [500], False, [0]), (0.1, [256], [256], True, [700])]
    want = sum(max(b / pk["hbm_bytes_per_s"], o / pk["bf16_flops_per_s"])
               for b, o in (roofline.window_need(512, 500, 0, 64.0, 8),
                            roofline.window_need(256, 256, 700, 64.0, 8)))
    got = roofline.least_seconds(wins, state, 1024, kind)
    assert got["roofline.least_s"] == want
    assert got["roofline.rows_touched"] == 768 and got[
        "roofline.windows"] == 2
    # four shards: rows are counted by the block that holds them
    rows = np.concatenate([np.arange(0, 128), np.arange(256, 384),
                           np.arange(512, 640), np.arange(768, 896)])
    assert roofline.by_shard(rows, 1024, 4, np.ones(512, np.int64)) \
        == ([128] * 4, [128] * 4)
    assert roofline.by_shard(np.arange(256), 1024, 4, np.r_[
        np.zeros(6, np.int64), np.ones(250, np.int64)]) \
        == ([256, 0, 0, 0], [250, 0, 0, 0])
    assert roofline.by_shard(rows, 1024, 1, np.ones(512, np.int64)) \
        == ([512], [512])
    # all rows in one shard: the one-chip time; spread evenly: a quarter
    one = roofline.least_seconds([(0.0, [512], [512], True, [700])],
                                 state, 1024, kind)["roofline.least_s"]
    skew = roofline.least_seconds(
        [(0.0, [0, 512, 0, 0], [0, 512, 0, 0], True, [0, 700, 0, 0])],
        state, 1024, kind)
    even = roofline.least_seconds(
        [(0.0, [128] * 4, [128] * 4, True, [175] * 4)], state, 1024, kind)
    assert skew["roofline.least_s"] == one
    assert even["roofline.least_s"] == pytest.approx(one / 4, rel=1e-12)
    assert skew["roofline.rows_touched"] == even[
        "roofline.rows_touched"] == 512
    with pytest.raises(ValueError):         # counts of different shards
        roofline.least_seconds([(0.0, [512], [512], False, [0] * 4)], state,
                               1024, kind)


def test_unknown_device_has_no_peaks():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="peaks.json"):
        roofline.peaks("TPU v9 imaginary")
