"""The window record (``utils.tracing``): a tiny door, Pallas interpreted,
yields one record per acked window, stamped where the work happens; the
table, the stage histograms and the slow-window ring are all read off it.
"""

import os
import re
import time

import numpy as np
import pytest

from fluidframework_tpu.server import native_deli
from fluidframework_tpu.server.columnar_ingress import (
    ColumnarAlfred, ColumnarClient, _OP_DTYPE)
from fluidframework_tpu.server.opsd import (
    STAGES, WORK_STARTS, latency_breakdown)
from fluidframework_tpu.server.serving import StringServingEngine
from fluidframework_tpu.utils import tracing
from fluidframework_tpu.utils.telemetry import REGISTRY

pytestmark = pytest.mark.skipif(not native_deli.available(),
                                reason="native sequencer unavailable")

N_DOCS = 8
#: what a pipelined window with no admission and no interval takes
TAKEN = (set(tracing.SPANS) - {"door.admit", "store.slide_docs"}) \
    | (set(tracing.WAITS) - {"door.tx_wait"})


class Door:
    """An 8-document door and one client that sends a window at a time."""

    def __init__(self):
        self.engine = StringServingEngine(
            n_docs=N_DOCS, capacity=128, batch_window=10 ** 9,
            sequencer="native")
        self.engine.store.pallas = "interpret"
        self.door = ColumnarAlfred(self.engine, window_min_rows=N_DOCS,
                                   window_ms=1.0).start_in_thread()
        self.client = ColumnarClient("127.0.0.1", self.door.port)
        docs = [f"d{i}" for i in range(N_DOCS)]
        self.client.join(docs)
        self.rows = [self.client.rows[d] for d in docs]
        self.cseq = 0

    def window(self):
        """One op on every document; returns when all are acked."""
        self.cseq += 1
        o = np.zeros(N_DOCS, _OP_DTYPE)
        o["row"], o["cseq"] = self.rows, self.cseq
        self.client.send_ops(["x"], o)
        acked = 0
        while acked < N_DOCS:
            resp = self.client.recv_json()
            assert resp["t"] == "acks", resp
            acked += len(resp["acks"])

    def close(self):
        self.client.close()
        self.door.stop()


@pytest.fixture
def door():
    d = Door()
    d.window()      # compiles the one program this height takes
    d.window()
    yield d
    d.close()


def _table():
    return dict(tracing.SPAN_TABLE.counters)


def _delta(after, before, field):
    return {k[:-len(field) - 1]: after[k] - before[k] for k in after
            if k.endswith("." + field) and after[k] != before[k]}


def _hist_sums(reg, prefix):
    return {k: (h.sum_ms, h.n) for k, h in reg.histograms.items()
            if k.startswith(prefix)}


def test_one_record_per_window_with_the_spans_the_path_took(door):
    tracing.RECENT.clear()
    keys, snap = set(_table()), set(REGISTRY.full_snapshot())
    n0 = door.door.windows_flushed
    for _ in range(5):
        door.window()
    recs = list(tracing.RECENT)
    # one record per acked window, identified by the door's counter
    assert [r["wid"] for r in recs] == list(range(n0, n0 + 5))
    assert door.door.windows_flushed == n0 + 5
    for r in recs:
        tl = r["pass"]
        assert r["pid"] == tl["pid"] and tl["windows"] >= 1
        assert tl["ops"] == N_DOCS and tl["frames"] == 1 and tl["bytes"] > 0
        names = [s[0] for s in tl["spans"] + r["spans"]]
        assert set(names) == TAKEN
        assert all(b >= a for _n, a, b in tl["spans"] + r["spans"])
        # children lie inside an instance of their parent, in the record
        # that holds the parent
        for rec in (tl, r):
            for name, a, b in rec["spans"]:
                par = tracing.PARENTS.get(name)
                if par is not None:
                    assert any(n == par and pa <= a and b <= pb
                               for n, pa, pb in rec["spans"]), name
        # the blocking chain: each crossing is a span's or a wait's edge
        by = {n: (a, b) for n, a, b in tl["spans"] + r["spans"]}
        assert by["door.rx_wait"] == (r["t_rx"], tl["t_drain0"])
        assert by["door.drain"] == (tl["t_drain0"], tl["t_ready"])
        assert by["engine.prepare"] == (r["pack0"], r["pack1"])
        assert by["executor.pack_wait"][1] <= r["pack0"]
        assert by["executor.seq_wait"][0] == r["pack1"]
        assert by["executor.seq_wait"][1] <= r["seq0"]
        assert by["engine.sequence"] == (r["seq0"], r["seq1"])
        assert by["engine.dispatch"] == (r["disp0"], r["disp1"])
        assert by["executor.log_wait"][0] == r["disp1"]
        assert by["executor.log_wait"][1] <= r["log0"]
        assert by["engine.log"] == (r["log0"], r["log1"])
        assert by["door.ack_bounce"] == (r["log1"], r["ack0"])
        assert by["door.fan_acks"][1] == r["t_ack"]
        cross = [r["t_rx"], tl["t_drain0"], tl["t_ready"], r["pack0"],
                 r["pack1"], r["seq0"], r["seq1"], r["disp0"], r["disp1"],
                 r["log0"], r["log1"], r["ack0"], r["t_ack"]]
        assert cross == sorted(cross)
    # the table's and the registry's keys are the same before and after
    assert set(_table()) == keys
    assert {f"spans.{k}" for k in keys} <= snap
    assert set(REGISTRY.full_snapshot()) >= snap


def test_waits_and_work_telescope_to_the_end_to_end_time(door):
    tracing.RECENT.clear()
    before = _hist_sums(REGISTRY, "stage_")
    t0 = _table()
    for _ in range(4):
        door.window()
    recs = list(tracing.RECENT)
    after = _hist_sums(REGISTRY, "stage_")
    d = {k: (after[k][0] - before.get(k, (0, 0))[0],
             after[k][1] - before.get(k, (0, 0))[1]) for k in after}
    e2e = sum(r["t_ack"] - r["t_rx"] for r in recs) * 1e3
    assert d["stage_e2e_ack_ms"] == (pytest.approx(e2e, abs=1e-6), 4)
    # the eight segments sum to it, and each is its wait plus its work
    assert sum(d[f"stage_{s}_ms"][0] for s in STAGES) \
        == pytest.approx(e2e, abs=1e-6)
    ends = {"pack": lambda r: r["pass"]["t_ready"], "sequence":
            lambda r: r["pack1"], "dispatch": lambda r: r["seq1"],
            "log": lambda r: r["disp1"], "ack": lambda r: r["log1"]}
    for stage, mark in WORK_STARTS.items():
        wait = sum(r[mark] - ends[stage](r) for r in recs) * 1e3
        assert d[f"stage_{stage}_wait_ms"] == (
            pytest.approx(wait, abs=1e-6), 4)
        assert wait <= d[f"stage_{stage}_ms"][0] + 1e-6
    bd = latency_breakdown(REGISTRY)
    assert all("wait_ms" in row for row in bd["stages"].values())
    assert bd["stages"]["rx"]["wait_ms"] == bd["stages"]["rx"]["mean_ms"]
    assert bd["stages"]["decode"]["wait_ms"] == 0.0
    # the table's window row is the same rx → ack-fanned time
    t1 = _table()
    assert (t1["window.s"] - t0["window.s"]) * 1e3 \
        == pytest.approx(e2e, abs=1e-6)
    assert t1["window.n"] - t0["window.n"] == 4


def test_stage_timings_are_derived_from_the_records_stamps(door):
    tracing.RECENT.clear()
    m = door.engine.metrics
    before = _hist_sums(m, "ingest_")
    for _ in range(4):
        door.window()
    recs = list(tracing.RECENT)
    after = _hist_sums(m, "ingest_")

    def spans_ms(*names):
        return sum(b - a for r in recs for n, a, b in r["spans"]
                   if n in names) * 1e3

    want = {
        "ingest_seq_ms": spans_ms("deli.sequence"),
        "ingest_pack_ms": spans_ms("store.pack"),
        "ingest_dispatch_ms": spans_ms(
            "store.upload", "store.unpack_dispatch", "store.merge_dispatch"),
        "ingest_log_ms": sum(
            dict((n, b) for n, _a, b in r["spans"])["log.append"]
            - r["log0"] for r in recs) * 1e3,
    }
    for name, ms in want.items():
        assert after[name][1] - before[name][1] == 4
        assert after[name][0] - before[name][0] \
            == pytest.approx(ms, abs=1e-6), name
    # prep: the prepare and sequence stages less the table pack and the
    # native call, each of which has a histogram of its own
    prep = spans_ms("engine.prepare", "engine.sequence") \
        - spans_ms("deli.sequence") \
        - (after["ingest_prepack_ms"][0] - before["ingest_prepack_ms"][0])
    assert after["ingest_prep_ms"][0] - before["ingest_prep_ms"][0] \
        == pytest.approx(prep, abs=1e-6)


def test_a_slow_window_is_kept_whole(door, monkeypatch):
    # "nothing else was long" must not be a wager on the wall clock: the
    # threshold goes well above what a busy neighbour costs a span, and
    # the planted sleep above the threshold
    long_s, planted_s = 0.5, 0.55
    monkeypatch.setattr(tracing, "LONG_S", long_s)
    eng = door.engine
    append = eng._append_columnar

    def slow_once(record):
        monkeypatch.setattr(eng, "_append_columnar", append)
        time.sleep(planted_s)
        return append(record)

    door.window()
    tracing.RECENT.clear()
    # the ring is the process's: a long window of an earlier test's door
    # may have left a trace under a ``wid`` this door will use
    tracing.TRACER.clear()
    t0 = _table()
    monkeypatch.setattr(eng, "_append_columnar", slow_once)
    door.window()
    door.window()
    slow, fast = list(tracing.RECENT)
    t1 = _table()
    # long in the table under the span that held it, and nowhere else
    # (the flusher meanwhile waits for traffic: backpressure by design)
    long_n = _delta(t1, t0, "long_n")
    assert long_n["log.append"] == 1 and long_n["window"] >= 1
    assert "engine.log" not in long_n       # its own time stayed short
    assert _delta(t1, t0, "long_s")["log.append"] >= planted_s
    assert slow["long"] == ["log.append"] and "long" not in fast
    # the whole record is one trace in the ring, unsampled
    evs = tracing.TRACER.events(f"w{slow['wid']}")
    assert {e["name"] for e in evs} == TAKEN | {"window"}
    by = {e["name"]: e for e in evs}
    assert by["log.append"]["dur"] >= planted_s * 1e6
    assert by["log.append"]["parent_id"] == by["engine.log"]["span_id"]
    assert by["engine.log"]["parent_id"] == by["window"]["span_id"]
    assert by["window"]["args"]["long"] == "log.append"
    assert by["window"]["dur"] == pytest.approx(
        (slow["t_ack"] - slow["t_rx"]) * 1e6)
    if fast["wid"] % tracing.KEEP_EVERY:
        assert tracing.TRACER.events(f"w{fast['wid']}") == []
    # and the end-to-end histogram's exemplar names it
    worst = REGISTRY.histograms["stage_e2e_ack_ms"].exemplars[-1]
    assert worst[1] == f"w{slow['wid']}"


def test_one_window_in_many_is_kept_whatever_it_held(monkeypatch):
    monkeypatch.setattr(tracing, "KEEP_EVERY", 2)
    rec = tracing.new_record(wid=6, ops=1)
    tl = tracing.new_record(pid=0, t_rx=1.0)
    tracing.wait(rec, "door.ack_bounce", 1.0, 1.001)
    assert tracing.close_window(rec, tl, 1.002).trace_id == "w6"
    rec = tracing.new_record(wid=7, ops=1)
    assert tracing.close_window(rec, tl, 1.002) is None
    # backpressure waits, however long, do not make a window a slow one
    for name in sorted(tracing.BACKPRESSURE):
        tracing.wait(rec, name, 1.0, 2.0)
    assert "long" not in rec
    tracing.wait(rec, "executor.seq_wait", 1.0, 2.0)
    assert rec["long"] == ["executor.seq_wait"]


def test_a_spans_own_time_leaves_its_children_out():
    t0 = _table()
    rec = tracing.new_record(wid=1)
    with tracing.stage(rec, "engine.log", mark="log") as outer:
        with tracing.stage(rec, "log.append") as inner:
            time.sleep(0.06)
    assert rec["long"] == ["log.append"]
    assert (rec["log0"], rec["log1"]) == (outer.t0, outer.t1)
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    d = _delta(_table(), t0, "long_n")
    # (a long collection meanwhile is the collector's row, not a span's)
    assert {k: v for k, v in d.items() if k not in tracing.GC} \
        == {"log.append": 1}
    assert outer.ms >= inner.ms >= 60.0


@pytest.mark.skipif(not os.path.isdir("/proc/self/task"),
                    reason="thread names are read from /proc")
def test_threads_carry_their_names_for_the_os(door):
    names = set()
    for tid in os.listdir("/proc/self/task"):
        try:
            with open(f"/proc/self/task/{tid}/comm") as f:
                names.add(f.read().strip())
        except OSError:         # a thread that ended meanwhile
            pass
    assert set(tracing.THREADS) <= names


def test_the_server_threads_clocks_move_with_their_work(door):
    t0, w0 = _table(), time.perf_counter()
    for _ in range(4):
        door.window()
    t1, w1 = _table(), time.perf_counter()
    # each of the four server threads ran, none longer than the wall
    # clock between the two readings
    cpu = {n: t1[f"thread.{n}.cpu_s"] - t0[f"thread.{n}.cpu_s"]
           for n in tracing.THREADS}
    assert all(0 < v <= w1 - w0 for v in cpu.values()), cpu
    assert t1["window.n"] - t0["window.n"] == 4


@pytest.mark.parametrize("fuse", [False, True])
@pytest.mark.parametrize("props", [False, True])
def test_merge_kernels_are_named_by_variant(fuse, props):
    import jax
    import jax.numpy as jnp
    from fluidframework_tpu.ops.string_store import (
        TensorStringStore, _columnar_merge_jit)
    st = TensorStringStore(16, 128, 4)
    planes = tuple(jnp.zeros((16, 1), jnp.int32) for _ in range(7))
    ms = jnp.zeros((16,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda s, p, m: _columnar_merge_jit.__wrapped__(
            s, p, m, use_pallas=True, tile=8, interpret=True,
            with_props=props, fuse_compact=fuse))(st.state, planes, ms)
    want = "string_merge" + ("_zamboni" if fuse else "") \
        + ("_props" if props else "")
    assert set(re.findall(r"string_merge[a-z_]*", str(jaxpr))) == {want}
    # the XLA fallback's two halves carry scopes of their own, and the
    # jitted function keeps the name the benchmark's module sums read
    text = _columnar_merge_jit.lower(
        st.state, planes, ms, use_pallas=False, tile=8, interpret=False,
        with_props=props, fuse_compact=fuse).as_text(debug_info=True)
    assert "string_merge" in text and ("string_zamboni" in text) == fuse
    assert "jit__columnar_merge_jit" in text
