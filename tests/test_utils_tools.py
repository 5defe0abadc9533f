"""Telemetry, config provider, replay/fetch tools.
Reference behaviors per SURVEY.md §2.15, §5.1, §5.6, §2.18."""

from fluidframework_tpu.drivers import LocalDocumentServiceFactory
from fluidframework_tpu.loader import Loader
from fluidframework_tpu.runtime import ContainerRuntime
from fluidframework_tpu.server.tinylicious import LocalService
from fluidframework_tpu.tools import fetch_document, replay_document
from fluidframework_tpu.utils import (
    BufferSink, ConfigProvider, Histogram, MetricsCollector,
    SampledTelemetry, TelemetryLogger,
)


# ---------------------------------------------------------------- telemetry

class TestTelemetry:
    def test_child_logger_namespaces_and_props(self):
        sink = BufferSink()
        root = TelemetryLogger(sink, "fluid", {"docId": "d1"})
        child = root.child("runtime", {"dsId": "default"})
        child.send_event("opApply", seq=7)
        (e,) = sink.events
        assert e["eventName"] == "fluid:runtime:opApply"
        assert e["docId"] == "d1" and e["dsId"] == "default" and e["seq"] == 7

    def test_performance_event_emits_start_end_with_duration(self):
        sink = BufferSink()
        log = TelemetryLogger(sink)
        with log.performance_event("summarize", attempt=1):
            pass
        names = [e["eventName"] for e in sink.events]
        assert names == ["summarize_start", "summarize_end"]
        assert sink.events[1]["duration_ms"] >= 0

    def test_performance_event_cancel_on_error(self):
        sink = BufferSink()
        log = TelemetryLogger(sink)
        try:
            with log.performance_event("load"):
                raise ValueError("boom")
        except ValueError:
            pass
        assert [e["eventName"] for e in sink.events] == \
            ["load_start", "load_cancel"]
        assert "boom" in sink.events[1]["error"]

    def test_sampled_telemetry_aggregates(self):
        sink = BufferSink()
        s = SampledTelemetry(TelemetryLogger(sink), "opApply", rate=10)
        for i in range(25):
            s.record(2.0)
        assert len(sink.events) == 2          # two full windows of 10
        s.flush()
        assert sink.events[-1]["samples"] == 5 and \
            sink.events[-1]["mean"] == 2.0

    def test_error_logger_tags(self):
        sink = BufferSink()
        TelemetryLogger(sink).send_error("containerClose",
                                         RuntimeError("nope"))
        (e,) = sink.events
        assert e["category"] == "error" and e["errorType"] == "RuntimeError"

    def test_histogram_percentiles(self):
        h = Histogram(buckets_ms=[1, 2, 4, 8, 16])
        for v in [0.5] * 98 + [12.0, 12.0]:
            h.record(v)
        assert h.percentile(50) == 1
        assert h.percentile(99) == 16

    def test_metrics_collector_snapshot(self):
        m = MetricsCollector()
        m.inc("ops_merged", 128)
        m.inc("ops_merged", 64)
        m.observe("apply_latency", 1.5)
        snap = m.snapshot()
        assert snap["ops_merged"] == 192
        assert snap["apply_latency_count"] == 1
        assert snap["apply_latency_p99_ms"] >= 1.5


# ------------------------------------------------------------------- config

class TestConfigProvider:
    def test_precedence_override_env_file(self, tmp_path):
        path = tmp_path / "cfg.json"
        path.write_text('{"gc.enabled": false, "batch.max": 7}')
        cfg = ConfigProvider(
            overrides={"batch.max": 9},
            json_path=str(path),
            env={"FLUID_TPU_gc__enabled": "true"})
        assert cfg.get_bool("gc.enabled") is True      # env beats file
        assert cfg.get_int("batch.max") == 9           # override beats env
        assert cfg.get_int("missing", 3) == 3

    def test_typed_getters_coerce_strings(self):
        cfg = ConfigProvider(env={"FLUID_TPU_a": "off", "FLUID_TPU_b": "2.5"})
        assert cfg.get_bool("a", True) is False
        assert cfg.get_float("b") == 2.5
        assert cfg.get_str("a") == "off"

    def test_runtime_set_wins(self):
        cfg = ConfigProvider(env={})
        cfg.set("feature.x", True)
        assert cfg.get_bool("feature.x") is True


# ------------------------------------------------------------ fetch + replay

class TestReplayTool:
    def _make_recorded_doc(self, tmp_path):
        svc = LocalService()
        loader = Loader(LocalDocumentServiceFactory(svc),
                        ContainerRuntime.factory())
        a = loader.resolve("doc")
        m = a.runtime.create_data_store("default").create_channel("r", "map")
        for i in range(20):
            m.set(f"k{i}", i)
        s = a.runtime.get_data_store("default") \
            .create_channel("text", "sharedString")
        s.insert_text(0, "recorded history")
        service = LocalDocumentServiceFactory(svc) \
            .create_document_service("doc")
        out = str(tmp_path / "doc")
        n = fetch_document(service, out)
        assert n > 20
        return out

    def test_fetch_then_replay_full_history(self, tmp_path):
        recorded = self._make_recorded_doc(tmp_path)
        container, stats = replay_document(recorded)
        ds = container.runtime.get_data_store("default")
        assert ds.get_channel("r").get("k19") == 19
        assert ds.get_channel("text").get_text() == "recorded history"
        assert stats.ops_replayed == stats.last_seq  # no summary: full replay
        assert stats.ops_per_sec > 0

    def test_replay_prefix_with_to_seq(self, tmp_path):
        recorded = self._make_recorded_doc(tmp_path)
        full, _ = replay_document(recorded)
        full_text = full.runtime.get_data_store("default") \
            .get_channel("text").get_text()
        partial, stats = replay_document(recorded, to_seq=10)
        assert stats.last_seq == 10
        pds = partial.runtime.get_data_store("default")
        assert pds.get_channel("r").get("k19") is None
        assert full_text == "recorded history"

    def test_cli_main(self, tmp_path, capsys):
        from fluidframework_tpu.tools.replay import main
        recorded = self._make_recorded_doc(tmp_path)
        assert main([recorded]) == 0
        out = capsys.readouterr().out
        assert "ops_per_sec=" in out and "doc=doc" in out


# ------------------------------------------------------------------ devtools

class TestDevtools:
    def test_inspect_container(self):
        from fluidframework_tpu.framework import LocalClient
        from fluidframework_tpu.tools.devtools import inspect_container
        client = LocalClient()
        fc, doc_id = client.create_container(
            {"initialObjects": {"text": "sharedString", "m": "map"}})
        fc.initial_objects["text"].insert_text(0, "hello")
        fc.initial_objects["m"].set("k", 1)
        view = inspect_container(fc.container)
        assert view["state"] in ("LOADED", "CONNECTED")
        assert view["connected"] is True
        assert view["lastSeq"] >= 1
        channels = view["dataStores"]["default"]["channels"]
        assert channels["text"]["type"] == "sharedString"
        assert channels["text"]["length"] == 5
        assert channels["m"]["keys"] == 1
        assert view["pendingOps"] == 0  # local service delivers synchronously

    def test_inspect_engine_metrics(self):
        from fluidframework_tpu.models.merge_tree_client import SequenceClient
        from fluidframework_tpu.server.serving import StringServingEngine
        from fluidframework_tpu.tools.devtools import inspect_engine
        engine = StringServingEngine(n_docs=2, capacity=128, batch_window=4)
        engine.connect("d", 1)
        c = SequenceClient(1)
        for i in range(9):
            op = c.insert_text_local(c.get_length(), "ab")
            msg, _ = engine.submit("d", 1, op["clientSeq"],
                                   c.last_processed_seq, op)
            c.apply_msg(msg)
        # a nack for the metrics counter
        engine.submit("d", 99, 1, 0, {"mt": "remove", "start": 0, "end": 1})
        engine.flush()
        view = inspect_engine(engine)
        assert view["documents"] == ["d"]
        m = view["metrics"]
        assert m["ops_ingested"] == 9
        assert m["nacks"] == 1 and m["nacks_unknown_client"] == 1
        assert m["ops_flushed"] == 9 and m["flushes"] >= 2
        assert m["flush_ms_count"] >= 2 and m["flush_ms_p99_ms"] > 0
        assert view["slotUsage"]["max"] >= 1
        assert view["overflowedDocs"] == []
