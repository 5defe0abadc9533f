"""What a traced run reads off the program's window records
(``perfbench/progtrace.py``): a traced rehearsal on the CPU by the accepted
harness with the readers attached from outside, the idle attribution on a
hand-worked event list, and every metric file that reads the span table,
from hand-made readings. No test here gives a device number.
"""

import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [REPO, HERE]

from perfbench import progtrace, reduce  # noqa: E402
from perfbench.traffic import load_json  # noqa: E402
from test_perfbench import BENCH, DATA, RESULT_KEYS, _data  # noqa: E402

from fluidframework_tpu.utils import tracing  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"
#: the metrics this module's program readings feed: entries in
#: BENCHMARK.json's form, which names none of them (``harness.py`` would
#: have to call the readers)
NEW = list({m["name"]: m for w in BENCH["workloads"]
            for m in progtrace.per_layer(BENCH, w["name"])}.values())


#: a cell name of its own: the run's files (trace, log, records) go under
#: it, and ``test_perfbench.py`` rehearses the same cell in another worker
NEW_NAMES = {m["name"] for m in NEW}
CELL = "tiny-rich.tiny-typing.progtrace"


@pytest.fixture(scope="module")
def traced():
    return progtrace.run_cell(
        {"name": CELL, "chips": 1}, _data("tiny-rich"), _data("tiny-typing"),
        {"config": os.path.join(DATA, "tiny-rich.json"),
         "traffic": os.path.join(DATA, "tiny-typing.json")},
        bench=BENCH, like="richtext-marks-10k.typing", seed=3_000_000_019,
        seconds=1.0, t_start=time.monotonic(), require_tpu=False)


def test_result_keeps_its_keys_and_gains_program_spans(traced):
    r = traced
    assert list(r)[:5] == RESULT_KEYS and list(r)[-1] == "compared"
    assert list(r)[-2] == "program_spans"
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    ps = r["program_spans"]
    assert {"idle_gaps", "long", "clock_drift_us", "spans", "table"} \
        <= set(ps)
    # the table's ``window`` row and the records are the same windows:
    # those closed inside the measured window (logged inside it: the
    # pipeline's depth apart at most)
    windows = ps["table"]["window"][1]
    assert windows == ps["spans"]["window"][0] > 0
    assert abs(ps["table"]["engine.log"][1] - windows) <= 2
    # the program's span lies inside the benchmark's wrapper of the same
    # call: a little less, never more
    for name in ("store.apply_planes", "engine.log", "door.drain"):
        inner, outer = ps["table"][name][0], ps["outside"][name][0]
        assert 0.8 * outer <= inner <= outer, name
    assert ps["table"]["engine.log"][0] * 1e3 == pytest.approx(
        ps["spans"]["engine.log"][1] * windows, rel=0.25)
    assert r["correct"] is True
    # the wide windows a stalled open loop piles up were swept in set-up
    assert r["programs"]["swept_not_met"] == r["programs"][
        "new_in_window"] == []
    json.dumps(r)
    # every metric that reads the span table has a value
    for m in NEW:
        if m["name"].endswith(".typing"):
            assert r["metrics"][m["name"]]["value"] >= 0, m["name"]
    assert r["metrics"]["door.window_rx_to_ack_ms.typing"]["value"] > 0
    # the accepted harness is as it was: its own metrics all there, and
    # what was wrapped from outside put back
    from perfbench import harness, trace
    from perfbench.traffic import select_metrics
    own = select_metrics(BENCH, "richtext-marks-10k.typing")[1]
    # (a rehearsal's CPU has no peaks and no line of modules)
    # (nor an op by the kernel's name)
    assert {m["name"] for m in own} - set(r["metrics"]) <= {
        "merge_roofline.typing", "kernel.merge_ms_per_window.typing",
        "kernel.zamboni_merge_ms_per_window.typing",
        "kernel.merge_outside_kernel_share.typing"}
    assert harness.trace.reduce_dir is trace.reduce_dir
    assert harness.GenProc.send.__name__ == "send"
    assert harness._instrument.__name__ == "_instrument"


def test_stamps_agree_with_the_traces_events(traced):
    ps = traced["program_spans"]
    # two marks, one at each end of the traced slice, on two clocks
    assert abs(ps["clock_drift_us"]) < 1000
    err = ps["stamp_error_us"]
    assert err["n"] > 100 and err["p50"] < 100
    idle = dict(ps["idle_gaps"])
    assert progtrace.UNATTRIBUTED in idle
    assert any(k in idle for k in ("door.tick_wait", "store.pack"))
    # the records were written beside the trace, one line a window
    path = os.path.join(REPO, "perfbench_out", CELL, "windows.jsonl")
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    wids = [r["wid"] for r in recs]
    assert wids == list(range(wids[0], wids[0] + len(wids)))
    assert len(wids) == traced["program_spans"]["spans"]["window"][0]
    assert all(r["pass"]["pid"] == r["pid"] for r in recs)


def test_idle_is_shared_out_over_own_time_and_waits():
    us = 1000
    # device busy 0-100 and 600-700 us: a gap of 500 us, then one to the
    # slice's end, the last event's at 900
    events = [(DEV, "XLA Ops", "string_merge_zamboni.1", 0, 100 * us, None),
              (DEV, "XLA Ops", "string_merge.1", 600 * us, 100 * us, None),
              # engine.log 150-450 holds log.append 200-400 (its own time:
              # 100 us); store.pack 350-550 on another thread overlaps
              (HOST, "fluid-log", "engine.log", 150 * us, 300 * us, 7),
              (HOST, "fluid-log", "log.append", 200 * us, 200 * us, 7),
              (HOST, "fluid-seq", "store.pack", 350 * us, 200 * us, 8),
              # a pass's span, after the second op
              (HOST, "fluid-door", "door.drain", 800 * us, 100 * us, -1)]
    # stamps in perf_counter seconds; the trace's 0 is 50 s on that clock
    offset = (50e9, 0.0, 1.0)
    rec = {"wid": 8, "spans": [("executor.seq_wait", 50.000100, 50.000150),
                               ("store.pack", 50.000350, 50.000550)],
           "pass": {"pid": 3, "spans": [
               ("door.tick_wait", 50.000700, 50.000800)]}}
    gaps, window_s = progtrace.device_gaps(events)
    assert gaps == [(100 * us, 600 * us), (700 * us, 900 * us)]
    assert window_s == pytest.approx(0.9e-3)
    got = dict(progtrace.idle_by_program_span(
        events, [rec, dict(rec, wid=9)], offset, gaps, tracing.PARENTS,
        tracing.WAITS))
    assert got == pytest.approx({
        "log.append": 200e-6, "engine.log._self": 100e-6,
        "store.pack": 200e-6, "executor.seq_wait": 50e-6,
        "door.tick_wait": 100e-6, "door.drain": 100e-6,
        # 100-550 and 700-900 are covered: 550-600 is not
        progtrace.UNATTRIBUTED: 50e-6})
    # a program without marks: the annotated spans alone
    bare = dict(progtrace.idle_by_program_span(
        events, [rec], None, gaps, tracing.PARENTS, tracing.WAITS))
    assert "door.tick_wait" not in bare and bare["store.pack"] == \
        pytest.approx(200e-6)
    assert progtrace.idle_by_program_span(events, [], None, [], {}) == []


def test_clock_map_and_long_spans_hand_worked():
    # the trace's clock runs 2 us fast over one second
    offset, drift = progtrace.clock_map([(1_000, 7_000_000_000),
                                         (1_000_003_000, 8_000_000_000)])
    assert drift == pytest.approx(2.0)
    assert progtrace.to_trace_ns(offset, 7.5) == pytest.approx(500_002_000)
    assert progtrace.clock_map([(1_000, 7_000_000_000)]) == (None, None)
    events = [(HOST, "fluid-log", "log.append", 0, 80_000_000, 4),
              (HOST, "fluid-log", "log.append", 0, 8_000_000, 5),
              (HOST, "fluid-seq", "store.pack", 0, 60_000_000, 5),
              (DEV, "XLA Ops", "string_merge.1", 0, 90_000_000, None)]
    recs = [{"wid": 4, "t_rx": 1.0, "t_ack": 1.3, "long": ["log.append"],
             "spans": [("executor.log_wait", 1.0, 1.07),
                       ("door.capacity_wait", 1.0, 1.2)], "pass": {}},
            {"wid": 5, "t_rx": 1.0, "t_ack": 1.1, "spans": [],
             "pass": {"long": ["door.decode"]}},
            {"wid": 6, "t_rx": 1.0, "t_ack": 1.01, "spans": [], "pass": {}}]
    got = progtrace.long_spans(events, recs, offset, 0.05, tracing.WAITS)
    assert got["by_line"] == {"fluid-log": {"log.append": [1, 0.08]},
                              "fluid-seq": {"store.pack": [1, 0.06]}}
    assert got["waits"] == {
        "executor.log_wait": [1, pytest.approx(0.07)],
        "door.capacity_wait": [1, pytest.approx(0.2)]}
    assert got["windows"] == 2
    assert [h["wid"] for h in got["slowest"]] == [4, 5]


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_metric_reads_the_span_table(metric):
    """Every reading a second of span time or 0.1 s of long time over a
    30 s window of 3,000 windows and 1,500,000 ops, 3,000 instances."""
    # (28 until the two by-kernel readings went into BENCHMARK.json)
    assert len(NEW) == 26
    assert not NEW_NAMES & {m["name"] for m in BENCH["per_layer"]}
    spec = load_json("metrics", metric["name"])
    table = {"prog." + k for k in tracing.SPAN_TABLE.counters}
    raw = {"window_s": 30.0, "d.windows": 3000, "d.ops": 1_500_000}
    for k in spec["num"] + [spec["den"]]:
        if k.startswith("d.prog."):
            assert k[2:] in table, k      # a row the program really keeps
            raw[k] = 3000 if k.endswith(".n") else \
                0.1 if k.endswith(".long_s") else 1.0
    got = reduce.read_metric(metric["name"], raw)
    n = len(spec["num"])
    want = {"ms/s": n * 0.1 / 30.0 * 1e3,
            "ratio": n * 1.0 / 30.0,
            "us": n * 1.0 / 1_500_000 * 1e6,
            "ms": n * 1.0 / 3000 * 1e3}[metric["unit"]]
    assert got == pytest.approx(want)
    assert metric["better"] == "lower"
    assert metric["source"] == "program_span"
    # a program without the table: nothing read, nothing raised
    assert reduce.read_metric(metric["name"], {"window_s": 30.0,
                                               "d.windows": 3000}) is None


def test_readers_return_nothing_without_the_programs_table(monkeypatch):
    monkeypatch.delattr(tracing, "SPAN_TABLE")
    assert progtrace.counters() == {}
    assert progtrace.records() == []
    progtrace.clock_mark()
    out = progtrace.reduce_dir("/nonexistent", "/nonexistent", (0.0, 9.0))
    assert out == {"idle_gaps": [], "long": {}, "clock_drift_us": None}
