"""Health plane (ISSUE 4): time-series retention, SLO burn-rate engine
and mesh-aware rollups.

Covers the acceptance criteria end to end: reset-aware counter rates,
fast/slow multi-window burn math, exemplar capture + breach trace
resolution, per-shard/per-replica labels round-tripping through
``render_prometheus()``, a forced replica digest divergence on the
virtual mesh driving ``replica_digest_divergence_total`` and an
SLO-breach flight dump tagged with the breaching trace id.
"""

import importlib.util
import json
import os
import re
import types

import numpy as np
import pytest

from fluidframework_tpu.utils import (
    flight_recorder, slo, telemetry, timeseries, tracing,
)
from fluidframework_tpu.utils.telemetry import (
    BufferSink, Histogram, MetricsCollector, MetricsRegistry,
    TelemetryLogger,
)

pytestmark = pytest.mark.telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tool(name):
    """Load a tools/*.py script as a module (tools/ is not a package)."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ctx(tid, sid="s0"):
    return types.SimpleNamespace(trace_id=tid, span_id=sid)


# ---------------------------------------------------------- TimeSeriesStore


class TestTimeSeriesStore:
    def test_tick_samples_and_ring_bound(self):
        reg = MetricsRegistry()
        store = timeseries.TimeSeriesStore(registry=reg, capacity=64)
        for i in range(100):
            reg.inc("ops_ingested", 5)
            reg.set_gauge("queue_depth", float(i))
            store.tick(now=float(i))
        assert store.n_ticks == 100
        assert len(store.values("ops_ingested")) == 64  # ring-bounded
        assert store.latest("ops_ingested") == 500.0
        assert store.latest("queue_depth") == 99.0
        assert store.kinds["ops_ingested"] == "counter"
        assert store.kinds["queue_depth"] == "gauge"

    def test_bools_sample_as_01_and_nan_skipped(self):
        reg = MetricsRegistry()
        store = timeseries.TimeSeriesStore(registry=reg)
        reg.set_gauge("digest_parity", True)
        reg.set_gauge("broken", float("nan"))
        store.tick(now=0.0)
        assert store.latest("digest_parity") == 1.0
        assert store.latest("broken") is None

    def test_rate_reset_aware(self):
        store = timeseries.TimeSeriesStore(registry=MetricsRegistry())
        # counter restarts between t=1 and t=2 (engine rebuild): the
        # post-reset sample contributes its own value, never a negative
        for t, v in [(0, 10.0), (1, 20.0), (2, 5.0), (3, 15.0)]:
            store.ingest_sample(float(t), {"ops_ingested": v})
        assert store.rate("ops_ingested") == pytest.approx(25.0 / 3.0)
        # trailing 1s window: just the (5 -> 15) delta
        assert store.rate("ops_ingested", window_s=1.0) == \
            pytest.approx(10.0)

    def test_rate_needs_counter_kind_and_history(self):
        store = timeseries.TimeSeriesStore(registry=MetricsRegistry())
        store.ingest_sample(0.0, {"queue_depth": 3.0, "ops_ingested": 1.0})
        assert store.rate("queue_depth") is None      # gauge
        assert store.rate("ops_ingested") is None     # one sample
        assert store.rate("missing") is None

    def test_window_summary_percentiles(self):
        store = timeseries.TimeSeriesStore(registry=MetricsRegistry())
        for t in range(100):
            store.ingest_sample(float(t), {"lag": float(t + 1)})
        s = store.window_summary("lag")
        assert (s["n"], s["min"], s["max"], s["last"]) == (100, 1, 100, 100)
        assert s["p50"] == 51.0
        assert s["p99"] == 99.0
        # clipped window sees only the tail
        s10 = store.window_summary("lag", window_s=9.0)
        assert s10["n"] == 10 and s10["min"] == 91.0

    def test_jsonl_round_trip_and_torn_tail(self, tmp_path):
        path = str(tmp_path / "health.jsonl")
        reg = MetricsRegistry()
        store = timeseries.TimeSeriesStore(registry=reg, jsonl_path=path)
        for i in range(3):
            reg.inc("ops_ingested", 10)
            reg.set_gauge("digest_parity", True)
            store.tick(now=float(i))
        loaded = timeseries.TimeSeriesStore.from_jsonl(path)
        assert loaded.values("ops_ingested") == \
            store.values("ops_ingested")
        assert loaded.kinds["ops_ingested"] == "counter"  # inferred
        assert loaded.latest("digest_parity") == 1.0
        # torn tail (crash mid-append) must not break the re-load
        with open(path, "a") as f:
            f.write('{"t": 99, "metr')
        torn = timeseries.TimeSeriesStore.from_jsonl(path)
        assert len(torn.values("ops_ingested")) == 3

    def test_export_jsonl_matches_incremental(self, tmp_path):
        reg = MetricsRegistry()
        store = timeseries.TimeSeriesStore(registry=reg)
        for i in range(4):
            reg.inc("flushes")
            store.tick(now=float(i))
        out = str(tmp_path / "export.jsonl")
        assert store.export_jsonl(out) == 4
        assert timeseries.TimeSeriesStore.from_jsonl(out).values(
            "flushes") == store.values("flushes")

    def test_sparklines_counters_plot_deltas(self):
        reg = MetricsRegistry()
        store = timeseries.TimeSeriesStore(registry=reg)
        for i, by in enumerate([0, 10, 20, 30]):
            reg.inc("ops_ingested", by)
            reg.set_gauge("idle_gauge", 0.0)
            store.tick(now=float(i))
        text = store.render_sparklines()
        assert "ops_ingested" in text
        assert "rate=" in text                 # counters carry the rate
        assert "idle_gauge" not in text        # all-zero series hidden
        assert "idle_gauge" in store.render_sparklines(active_only=False)
        empty = timeseries.TimeSeriesStore(registry=MetricsRegistry())
        assert "no active series" in empty.render_sparklines()


# ----------------------------------------------------------- SLO burn math


class TestSLOSpec:
    def test_parse_forms(self):
        s = slo.SLOSpec.parse("ack_p99_ms < 200")
        assert (s.metric, s.op, s.threshold, s.kind) == \
            ("ack_p99_ms", "<", 200.0, "value")
        s = slo.SLOSpec.parse("digest_parity == true")
        assert s.threshold == 1.0
        s = slo.SLOSpec.parse("rate(flight_dump_total) == 0")
        assert (s.metric, s.kind) == ("flight_dump_total", "rate")
        # bare *_rate sugar targets the counter behind it
        s = slo.SLOSpec.parse("flight_dump_rate == 0")
        assert (s.metric, s.kind) == ("flight_dump_total", "rate")
        with pytest.raises(ValueError):
            slo.SLOSpec.parse("no operator here")

    def test_multi_window_requires_both_burning(self):
        store = timeseries.TimeSeriesStore(registry=MetricsRegistry())
        spec = slo.SLOSpec.parse("ack_p99_ms < 200", name="ack",
                                 fast_window_s=10.0, slow_window_s=1000.0,
                                 fast_burn=0.5, slow_burn=0.1)
        for t in range(90):                       # healthy history
            store.ingest_sample(float(t), {"ack_p99_ms": 100.0})
        (r,) = spec.evaluate(store, now=89.0)
        assert r["ok"] and r["judged"]
        # a fresh cliff: the fast window burns (6 bad of 11) but the slow
        # window holds (6 of 96 < 10%) — fast-only is noise, no breach
        for t in range(90, 96):
            store.ingest_sample(float(t), {"ack_p99_ms": 500.0})
        (r,) = spec.evaluate(store, now=95.0)
        assert r["ok"]
        assert r["fast_burn"] >= 0.5
        assert r["slow_burn"] < 0.1
        # the cliff persists: slow window reaches 10 bad of 100 — breach
        for t in range(96, 100):
            store.ingest_sample(float(t), {"ack_p99_ms": 500.0})
        (r,) = spec.evaluate(store, now=99.0)
        assert not r["ok"]
        assert r["worst"] == 500.0

    def test_rate_kind_judges_derived_rate(self):
        store = timeseries.TimeSeriesStore(registry=MetricsRegistry())
        spec = slo.SLOSpec.parse("rate(flight_dump_total) == 0",
                                 name="quiet")
        for t, v in enumerate([0.0, 0.0, 0.0]):
            store.ingest_sample(float(t), {"flight_dump_total": v})
        (r,) = spec.evaluate(store)
        assert r["ok"]
        store.ingest_sample(3.0, {"flight_dump_total": 2.0})
        (r,) = spec.evaluate(store)
        assert not r["ok"] and r["worst"] > 0

    def test_insufficient_data_never_pages(self):
        store = timeseries.TimeSeriesStore(registry=MetricsRegistry())
        store.ingest_sample(0.0, {"ack_p99_ms": 9999.0})
        spec = slo.SLOSpec.parse("ack_p99_ms < 200")   # min_samples=2
        (r,) = spec.evaluate(store)
        assert r["ok"] and not r["judged"]


class TestSLOEngine:
    def _engine(self, tmp_path, specs):
        reg = MetricsRegistry()
        store = timeseries.TimeSeriesStore(registry=reg)
        sink = BufferSink()
        eng = slo.SLOEngine(
            store, specs=specs, registry=reg,
            logger=TelemetryLogger(sink, "slo"),
            recorder=flight_recorder.FlightRecorder(
                dump_dir=str(tmp_path)))
        return reg, store, sink, eng

    def test_breach_edge_trigger_and_rearm(self, tmp_path):
        spec = slo.SLOSpec.parse("digest_parity == true", name="parity",
                                 min_samples=1)
        reg, store, sink, eng = self._engine(tmp_path, [spec])
        reg.set_gauge("digest_parity", 1.0)
        store.tick(now=0.0)
        assert eng.check(now=0.0) == []
        reg.set_gauge("digest_parity", 0.0)
        store.tick(now=1.0)
        new = eng.check(now=1.0)
        assert len(new) == 1
        assert reg.counters["slo_breach_total"] == 1.0
        assert os.path.exists(new[0]["dump"])
        header = json.loads(open(new[0]["dump"]).readline())
        assert header["slo"] == "parity"
        # still breaching: edge-triggered, no duplicate side effects
        store.tick(now=2.0)
        assert eng.check(now=2.0) == []
        assert reg.counters["slo_breach_total"] == 1.0
        # recovery re-arms (window far enough ahead to shed bad samples)
        reg.set_gauge("digest_parity", 1.0)
        store.tick(now=1000.0)
        assert eng.check(now=1000.0) == []
        reg.set_gauge("digest_parity", 0.0)
        store.tick(now=1001.0)
        assert len(eng.check(now=1001.0)) == 1
        assert reg.counters["slo_breach_total"] == 2.0
        assert len(sink.named("slo_breach")) == 2

    def test_breach_carries_worst_exemplar_trace(self, tmp_path):
        spec = slo.SLOSpec.parse("ack_ms_p99_ms < 200", name="ack",
                                 min_samples=1)
        reg, store, sink, eng = self._engine(tmp_path, [spec])
        reg.observe("ack_ms", 50.0, exemplar=_ctx("tid-fine", "s-f"))
        reg.observe("ack_ms", 950.0, exemplar=_ctx("tid-worst", "s-w"))
        store.tick(now=0.0)
        store.tick(now=1.0)
        (breach,) = eng.check(now=1.0)
        assert breach["trace_id"] == "tid-worst"
        assert breach["span_id"] == "s-w"
        assert breach["exemplar_value_ms"] == 950.0
        assert "tid-worst" in open(breach["dump"]).readline()

    def test_breach_falls_back_to_current_span(self, tmp_path):
        spec = slo.SLOSpec.parse("digest_parity == true", name="parity",
                                 min_samples=1)
        reg, store, sink, eng = self._engine(tmp_path, [spec])
        reg.set_gauge("digest_parity", 0.0)
        store.tick(now=0.0)
        with tracing.span("health-probe") as sp:
            (breach,) = eng.check(now=0.0)
            assert breach["trace_id"] == sp.ctx.trace_id

    def test_scorecard_surfaces_unmatched_specs(self):
        reg = MetricsRegistry()
        store = timeseries.TimeSeriesStore(registry=reg)
        eng = slo.SLOEngine(store, specs=slo.default_slos(), registry=reg)
        rows = eng.scorecard()
        # nothing sampled yet: every spec reports, none pages
        assert len(rows) >= len(slo.default_slos())
        assert all(r["ok"] for r in rows)
        text = slo.render_scorecard(rows)
        assert "no-data" in text and "ack_latency" in text


# ------------------------------------------------------- exemplar capture


class TestExemplars:
    def test_worst_exemplar_and_bound(self):
        h = Histogram()
        for i in range(40):
            h.observe(float(i), exemplar=_ctx(f"tid-{i}"))
        h.observe(7.0, exemplar=_ctx("tid-late-small"))
        assert len(h.exemplars) <= Histogram.EXEMPLAR_KEEP
        assert h.worst_exemplar == (39.0, "tid-39", "s0")

    def test_exemplar_true_captures_current_span(self):
        h = Histogram()
        with tracing.span("obs") as sp:
            h.observe(5.0, exemplar=True)
        assert h.worst_exemplar[1] == sp.ctx.trace_id
        # no active span: exemplar=True records the value, no exemplar
        h2 = Histogram()
        h2.observe(5.0, exemplar=True)
        assert h2.n == 1 and h2.exemplars == []


# --------------------------------------------------- mesh-labeled rollups


class TestMeshRollups:
    def test_shard_labels_skew_and_prometheus(self):
        parent = MetricsRegistry()
        colls = []
        for s in range(4):
            c = MetricsCollector()
            parent.attach("Engine", c, labels={"shard": s})
            c.inc("ops_applied", 10.0 * (s + 1))
            colls.append(c)
        snap = parent.full_snapshot()
        assert snap["Engine{shard=2}.ops_applied"] == 30.0
        assert snap["Engine.ops_applied_shard_min"] == 10.0
        assert snap["Engine.ops_applied_shard_max"] == 40.0
        assert snap["Engine.ops_applied_shard_skew"] == 30.0
        kinds = parent.full_snapshot_kinds()
        assert kinds["Engine{shard=2}.ops_applied"] == "counter"
        assert kinds["Engine.ops_applied_shard_skew"] == "gauge"
        prom = parent.render_prometheus()
        assert 'ops_applied{component="Engine",shard="3"} 40.0' in prom

    def test_serving_engine_shard_accounting(self):
        from fluidframework_tpu.parallel.sharded import make_doc_mesh
        from fluidframework_tpu.server.serving import StringServingEngine
        from fluidframework_tpu.utils.telemetry import REGISTRY
        mesh = make_doc_mesh(8)
        eng = StringServingEngine(n_docs=16, capacity=64, mesh=mesh)
        eng._ensure_shard_collectors()
        assert len(eng.shard_metrics) == 8    # one per doc shard
        assert eng._rows_per_shard == 2
        # credit two ops on every row, then pile extra load on shard 0
        eng._note_shard_ops(np.arange(16), counts=np.full(16, 2.0))
        eng._note_shard_ops(np.array([0, 1]), counts=np.array([10., 10.]))
        assert eng.shard_metrics[0].counters["ops_applied"] == 24.0
        assert eng.shard_metrics[3].counters["ops_applied"] == 4.0
        snap = REGISTRY.full_snapshot()
        skews = {k: v for k, v in snap.items()
                 if k.startswith("StringServingEngine")
                 and k.endswith(".ops_applied_shard_skew")}
        assert 20.0 in skews.values()
        # per-shard series round-trip through the Prometheus exposition
        prom = REGISTRY.render_prometheus()
        assert re.search(
            r'ops_applied\{component="StringServingEngine\d*",'
            r'shard="3"\} 4\.0', prom)

    def test_partition_collectors_count_appends(self):
        from fluidframework_tpu.core.protocol import (
            MessageType, SequencedDocumentMessage,
        )
        from fluidframework_tpu.server.oplog import partition_of
        from fluidframework_tpu.server.serving import StringServingEngine
        eng = StringServingEngine(n_docs=4, capacity=32, n_partitions=4)
        assert len(eng.partition_metrics) == 4
        msg = SequencedDocumentMessage("doc-0", 1, 1, 0, 1, 0,
                                       MessageType.NOOP)
        eng._log_append("doc-0", msg)
        p = partition_of("doc-0", 4)
        assert eng.partition_metrics[p].counters["appends"] == 1.0
        assert sum(c.counters.get("appends", 0.0)
                   for c in eng.partition_metrics) == 1.0
        prom = eng.partition_metrics[p].render_prometheus()
        assert "appends 1.0" in prom.replace("\n", " ")


# --------------------------------- replicated mesh: forced divergence path


class TestReplicaDivergence:
    def test_injected_divergence_breaks_agreement_and_pages(self, tmp_path):
        import jax.numpy as jnp
        from fluidframework_tpu.ops.merge_tree_kernel import StringState
        from fluidframework_tpu.parallel import (
            make_mesh, make_replicated_step, shard_ops, shard_state,
        )
        from fluidframework_tpu.parallel.replicated import ReplicaSetMetrics
        from fluidframework_tpu.testing.synthetic import typing_storm

        mesh = make_mesh(8)                  # 2 replicas x 4 doc shards
        _, doc_shards = mesh.devices.shape
        n_docs, n_ops, cap = 2 * doc_shards, 8, 64
        planes, _ = typing_storm(n_docs, n_ops, seed=3)
        ops = tuple(jnp.asarray(planes[k]) for k in
                    ("kind", "a0", "a1", "a2", "seq", "client", "ref_seq"))
        step = make_replicated_step(mesh, inject_divergence=True)
        state = shard_state(StringState.create(n_docs, cap), mesh)
        _, _, agree = step(state, *shard_ops(mesh, *ops))
        assert int(agree) == 0               # the chaos hook forced it

        reg = MetricsRegistry()
        sink = BufferSink()
        rsm = ReplicaSetMetrics(mesh, registry=reg,
                                logger=TelemetryLogger(sink, "replicaSet"))
        assert rsm.n_replicas == 2
        assert rsm.on_step(agree, n_ops=n_docs * n_ops) is False
        assert reg.counters["replica_digest_divergence_total"] == 1.0
        assert reg.gauges["digest_parity"] == 0.0
        assert len(sink.named("replica_digest_divergence")) == 1
        prom = reg.render_prometheus()
        assert 'component="ReplicaSet",replica="0"' in prom
        assert 'component="ReplicaSet",replica="1"' in prom

        # the health plane on top: parity SLO breaches, and the flight
        # dump is tagged with the breaching trace id
        store = timeseries.TimeSeriesStore(registry=reg)
        store.tick(now=0.0)
        eng = slo.SLOEngine(
            store,
            specs=[slo.SLOSpec.parse("digest_parity == true",
                                     name="digest_parity",
                                     min_samples=1)],
            registry=reg, logger=TelemetryLogger(BufferSink(), "slo"),
            recorder=flight_recorder.FlightRecorder(
                dump_dir=str(tmp_path)))
        with tracing.span("divergence-probe") as sp:
            (breach,) = eng.check(now=0.0)
        assert breach["slo"] == "digest_parity"
        assert breach["trace_id"] == sp.ctx.trace_id
        assert reg.counters["slo_breach_total"] == 1.0
        header = json.loads(open(breach["dump"]).readline())
        assert header["flight_recorder"] == "slo:digest_parity"
        assert header["trace_id"] == sp.ctx.trace_id


# --------------------------------------------- flight-dump rate limiting


class TestFlightDumpRateLimit:
    def test_same_reason_suppressed_within_window(self, tmp_path):
        from fluidframework_tpu.utils.telemetry import REGISTRY
        rec = flight_recorder.FlightRecorder(dump_dir=str(tmp_path),
                                             dedup_window_s=30.0)
        rec.note("precursor", detail=1)
        before = REGISTRY.counters.get("flight_dump_suppressed_total", 0.0)
        p1 = rec.dump("crash")
        p2 = rec.dump("crash")               # within the window
        assert p2 == p1                      # prior evidence returned
        assert rec.suppressed["crash"] == 1
        assert REGISTRY.counters["flight_dump_suppressed_total"] == \
            before + 1
        assert len(list(tmp_path.glob("flight-*.jsonl"))) == 1
        # a different reason and a forced dump both still write
        p3 = rec.dump("other")
        p4 = rec.dump("crash", force=True)
        assert len({p1, p3, p4}) == 3
        assert len(list(tmp_path.glob("flight-*.jsonl"))) == 3
        # the suppression itself is on the record
        events = flight_recorder.load_dump(p4)
        assert any(e.get("eventName") == "flight_dump_suppressed"
                   for e in events)


# -------------------------------------------------------------- healthz


class TestHealthz:
    def test_demo_dashboard_green(self, capsys):
        hz = _tool("healthz")
        assert hz.main(["--demo"]) == 0
        out = capsys.readouterr().out
        assert "ops_ingested" in out
        assert "ack_latency" in out          # default SLO scorecard

    def test_breaching_extra_slo_fails(self, capsys):
        hz = _tool("healthz")
        rc = hz.main(["--demo", "--slo", "ops_ingested < 0"])
        capsys.readouterr()
        assert rc == 1

    def test_jsonl_input_round_trip(self, tmp_path, capsys):
        path = str(tmp_path / "h.jsonl")
        reg = MetricsRegistry()
        store = timeseries.TimeSeriesStore(registry=reg, jsonl_path=path)
        for i in range(8):
            reg.inc("ops_ingested", 50)
            reg.set_gauge("digest_parity", 1.0)
            store.tick(now=float(i))
        hz = _tool("healthz")
        assert hz.main([path]) == 0
        out = capsys.readouterr().out
        assert "ops_ingested" in out and "digest_parity" in out
