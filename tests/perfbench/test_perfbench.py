"""The benchmark's own tests: the whole command rehearsed tiny on the CPU
(Pallas interpreted), the controls (a planted fault has to come out not
correct), and the yardstick's arithmetic checked against hand-worked cases.
No test here gives a device number."""

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
from perfbench.traffic import (Layout, OpMaker, Vocabulary, heights,  # noqa: E402
                               load_json, programs, select_metrics,
                               table_size)

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _data(name):
    with open(os.path.join(DATA, name + ".json")) as f:
        return json.load(f)


def _rehearse(config, traffic, trace_on, plant="", seed=2_147_483_659):
    """The rest of a run, without the harness's look for a chip."""
    from perfbench import harness
    # the tiny cell reports what the committed cell of its mix reports,
    # picked by the rule the command itself uses
    like = next(w["name"] for w in BENCH["workloads"]
                if "tiny-" + w["traffic"] == traffic)
    end_to_end, per_layer = select_metrics(BENCH, like)
    return harness.run_cell(
        {"name": f"{config}.{traffic}", "chips": 1}, _data(config),
        _data(traffic),
        {"config": os.path.join(DATA, config + ".json"),
         "traffic": os.path.join(DATA, traffic + ".json")},
        seed=seed, seconds=1.0, trace_on=trace_on, t_start=time.monotonic(),
        end_to_end=end_to_end, per_layer=per_layer, require_tpu=False,
        plant=plant)


# ------------------------------------------------------ the whole command

@pytest.mark.parametrize("config,traffic,trace_on", [
    ("tiny-string", "tiny-replay", False),
    ("tiny-rich", "tiny-typing", True)])
def test_rehearsal_of_a_cell(config, traffic, trace_on):
    r = _rehearse(config, traffic, trace_on)
    assert list(r)[:5] == RESULT_KEYS and list(r)[-1] == "compared"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert all(v["value"] <= v["limit"] for v in r["compared"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(r["device"])
    names = set(r["metrics"])
    if trace_on:
        assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
        assert "store.compiles_in_window.typing" in names
        assert r["metrics"]["store.compiles_in_window.typing"]["value"] == 0
        assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
        assert not any("roofline" in n for n in names)   # no CPU roofline
    else:
        assert names == {"setup_s", "acked_ops_per_s"}
        assert all(v["value"] > 0 for v in r["metrics"].values())
    json.dumps(r)


# each fault, the cell it is planted in, and the numbers of which one has to
# catch it (a window of the documents written alone shows in their lengths,
# one of the multi-writer documents in their text)
MERGED = ("lengths_differ", "docs_text_differs")
FAULTS = {"unapplied_window": ("tiny-string", "tiny-replay", MERGED),
          "half_window": ("tiny-string", "tiny-replay", MERGED),
          "skipped_append": ("tiny-string", "tiny-replay", ("log_differs",)),
          "altered_ack": ("tiny-string", "tiny-replay", ("acks_failed",)),
          "dropped_annotates": ("tiny-rich", "tiny-typing",
                                ("props_differ",))}


@pytest.mark.parametrize("fault", sorted(faults.PLANTS))
def test_planted_fault_is_not_correct(fault):
    config, traffic, numbers = FAULTS[fault]
    r = _rehearse(config, traffic, False, plant=fault)
    assert r["correct"] is False
    assert any(r["compared"][n]["value"] > r["compared"][n]["limit"]
               for n in numbers), r["compared"]


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
    assert set(spec) <= {"reduce", "key", "num", "den", "scale"}
    assert NAME.match(metric["name"])
    if "moves" in metric:
        moved = next(m for m in BENCH["end_to_end"]
                     if m["name"] == metric["moves"])
        cells = moved.get("workloads", [w["name"] for w in
                                        BENCH["workloads"]])
        # the suffix names the traffic family of the cells that report it
        assert {metric["name"].rsplit(".", 1)[1]} == {
            load_json("traffic", w["traffic"])["family"]
            for w in BENCH["workloads"] if w["name"] in cells}
        for cell in cells:
            assert metric in select_metrics(BENCH, cell)[1]
    assert reduce.read_metric(metric["name"], {}) is None   # nothing read


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


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_programs_cover_the_table_sizes_a_window_meets(cell):
    """Draw windows of every height from the mix itself and see that the
    payload-table size each pads to is one set-up dispatches on purpose."""
    cfg = load_json("configs", cell["config"])
    tr = load_json("traffic", cell["traffic"])
    dep, rich = cfg["deployment"], bool(cfg["wire"]["props"])
    lay = Layout(dep["n_docs"], tr["connections"], tr["multi_writer_docs"])
    v = Vocabulary(cfg)
    progs = programs(lay, tr, dep["door"]["window_min_rows"], v, rich)
    assert len(progs) <= 64
    assert table_size(0) == table_size(8) == 8 and table_size(9) == 16
    mk = OpMaker(3, 0, 1280, tr["mix"], rich, v)
    li = np.arange(1280)
    for _ in range(tr["mix"]["fill_rounds"]):
        mk.make(li, li, fill=True)
    for h in sorted({h for h, _ in progs}):
        for _ in range(40):
            ops = mk.make(li[:h], li[:h])
            n = len(set(ops["tidx"][ops["kind"] == wire.INS])) \
                + len(set(ops["tidx"][ops["kind"] == wire.ANN]))
            assert (h, table_size(n)) in progs, (h, n)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_setup_frames_fill_the_table_they_are_sent_for(cell):
    """Every frame of a program's compaction cycle in set-up has to pad to
    that program's table size, the small heights too: the fused zamboni
    falls on one frame of the sixteen, whichever it is."""
    from perfbench.gen import Generator
    cfg = load_json("configs", cell["config"])
    tr = load_json("traffic", cell["traffic"])
    dep, rich = cfg["deployment"], bool(cfg["wire"]["props"])
    lay = Layout(dep["n_docs"], tr["connections"], tr["multi_writer_docs"])

    class Stub:
        vocab = Vocabulary(cfg)
    mk = OpMaker(11, 0, 1280, tr["mix"], rich, Stub.vocab)
    li = np.arange(1280)
    for _ in range(tr["mix"]["fill_rounds"]):
        mk.make(li, li, fill=True)
    for h, tab in programs(lay, tr, dep["door"]["window_min_rows"],
                           Stub.vocab, rich):
        for _ in range(dep["engine"]["compact_every"]):
            ops = mk.make(li[:h], li[:h],
                          inserts=tab // 2 + 1 if tab > 8 else 0)
            Generator._fill_table(Stub, ops, tab)
            n = len(set(ops["tidx"][ops["kind"] == wire.INS])) \
                + len(set(ops["tidx"][ops["kind"] == wire.ANN]))
            assert table_size(n) == tab, (h, tab, n)


def _carve(level_rows, window_rows):
    n = len(level_rows)
    return [min(window_rows, n - s) for s in range(0, n, window_rows)]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_window_heights_come_from_the_closed_set(cell):
    """Carve random drain passes the way the door does (rows sorted, split
    by per-row occurrence, cut every window_min_rows) and see that no
    height outside ``heights`` can come up."""
    dep = load_json("configs", cell["config"])["deployment"]
    tr = load_json("traffic", cell["traffic"])
    lay = Layout(dep["n_docs"], tr["connections"], tr["multi_writer_docs"])
    W = dep["door"]["window_min_rows"]
    hs = set(heights(lay, tr, W))
    if tr["loop"] == "closed":
        assert hs == {8, 16, 256, 264, 272, 512}
    assert len(hs) <= 24 and max(hs) == W
    rng = np.random.default_rng(0)
    shared = np.arange(lay.owner * lay.P, lay.owner * lay.P + lay.S)
    per = lay.P if tr["loop"] == "closed" else tr["ops_per_frame"]
    for _ in range(300):
        rows = []
        for c in range(lay.C):
            for f in range(int(rng.integers(0, 3))):
                first = tr["loop"] == "closed" or rng.random() < 0.2
                start = 0 if first else per * int(rng.integers(
                    1, lay.P // per))
                own = np.arange(c * lay.P + start, c * lay.P + start + per)
                rows.append(own)
                if first and c == 0:
                    rows.append(shared)
                if first and c == 1:
                    rows.append(shared[1::2])
        if not rows:
            continue
        rows = np.sort(np.concatenate(rows))
        occ = np.arange(len(rows)) - np.searchsorted(rows, rows)
        for lvl in range(int(occ.max()) + 1):
            got = _carve(rows[occ == lvl], W)
            assert set(got) <= hs, (got, sorted(hs))


def test_wire_copy_speaks_the_doors_protocol():
    from fluidframework_tpu.server import columnar_ingress as door
    assert wire.OP_DTYPE == door._OP_DTYPE
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


def test_unknown_device_has_no_peaks():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="peaks.json"):
        roofline.peaks("TPU v9 imaginary")
