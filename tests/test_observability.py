"""Observability suite (ISSUE 2): end-to-end op tracing, the unified
metrics registry, and the crash flight recorder — plus the telemetry
satellites (performance-event cancel, child-logger props, sampled flush
on close, histogram overflow) and a lint-style check that every
``send_warning`` degradation site also counts.
"""

import ast
import json
import os
import pathlib

import pytest

from fluidframework_tpu.testing.chaos import FaultPlan
from fluidframework_tpu.tools import trace_viewer
from fluidframework_tpu.utils import flight_recorder, tracing
from fluidframework_tpu.utils.faultpoints import (
    SITE_SUBMIT_POST_SEQUENCE, CrashInjected, armed, fault_point,
)
from fluidframework_tpu.utils.telemetry import (
    BufferSink, Histogram, MetricsRegistry, REGISTRY, SampledTelemetry,
    TelemetryLogger,
)

pytestmark = pytest.mark.telemetry

PKG_ROOT = pathlib.Path(__file__).resolve().parent.parent \
    / "fluidframework_tpu"


# --------------------------------------------------------------- telemetry

def test_performance_event_cancel_path():
    sink = BufferSink()
    log = TelemetryLogger(sink, "t")
    with pytest.raises(ValueError):
        with log.performance_event("load", doc="d"):
            raise ValueError("boom")
    cancel, = sink.named("load_cancel")
    assert cancel["category"] == "error"
    assert "boom" in cancel["error"]
    assert cancel["duration_ms"] >= 0
    assert cancel["doc"] == "d"
    assert not sink.named("load_end")


def test_child_logger_prop_merging():
    sink = BufferSink()
    root = TelemetryLogger(sink, "svc", {"docId": "d1", "tier": "a"})
    child = root.child("deli", {"tier": "b", "partition": 3})
    child.send_event("seq", n=1)
    ev, = sink.events
    assert ev["eventName"] == "svc:deli:seq"
    assert ev["docId"] == "d1"        # inherited
    assert ev["tier"] == "b"          # child overrides parent
    assert ev["partition"] == 3
    # the parent's own props are untouched by the child
    assert root.props == {"docId": "d1", "tier": "a"}


def test_sampled_telemetry_min_max_and_close_flush():
    sink = BufferSink()
    st = SampledTelemetry(TelemetryLogger(sink), "lat", rate=3)
    for v in (5.0, 1.0, 9.0):
        st.record(v)
    ev, = sink.events                 # auto-flush at rate
    assert (ev["min"], ev["max"], ev["samples"]) == (1.0, 9.0, 3)
    assert ev["mean"] == pytest.approx(5.0)
    # a partial window is NOT lost on shutdown
    st.record(42.0)
    st.close()
    tail = sink.events[-1]
    assert (tail["samples"], tail["min"], tail["max"]) == (1, 42.0, 42.0)
    st.close()                        # idempotent: nothing to flush
    assert len(sink.events) == 2


def test_sampled_telemetry_context_manager_flushes():
    sink = BufferSink()
    with SampledTelemetry(TelemetryLogger(sink), "lat", rate=100) as st:
        st.record(7.0)
    assert sink.events[-1]["samples"] == 1


def test_histogram_overflow_in_snapshot():
    reg = MetricsRegistry()
    reg.observe("lat_ms", 1.0)
    reg.observe("lat_ms", 1e9)        # past the last bucket bound
    snap = reg.snapshot()
    assert snap["lat_ms_count"] == 2
    assert snap["lat_ms_overflow"] == 1
    assert snap["lat_ms_p99_ms"] == float("inf")
    h = Histogram()
    assert h.overflow == 0


# ---------------------------------------------------------------- registry

def test_registry_counters_gauges_prometheus():
    reg = MetricsRegistry()
    reg.inc("ops")
    reg.inc("ops", 2)
    reg.set_gauge("queue_depth", 7)
    reg.observe("apply_ms", 0.5)
    snap = reg.snapshot()
    assert snap["ops"] == 3
    assert snap["queue_depth"] == 7
    text = reg.render_prometheus()
    assert "# TYPE ops counter" in text
    assert "# TYPE queue_depth gauge" in text
    assert "# TYPE apply_ms histogram" in text
    assert 'apply_ms_bucket{le="+Inf"} 1' in text


def test_registry_attach_collision_and_full_snapshot():
    root = MetricsRegistry()
    a, b = MetricsRegistry(), MetricsRegistry()
    a.inc("flushes", 4)
    b.inc("flushes", 9)
    name_a = root.attach("engine", a)
    name_b = root.attach("engine", b)
    assert name_a == "engine" and name_b == "engine2"
    # re-attaching the same registry keeps its name (no suffix churn)
    assert root.attach("engine", a) == "engine"
    full = root.full_snapshot()
    assert full["engine.flushes"] == 4
    assert full["engine2.flushes"] == 9
    labeled = root.render_prometheus()
    assert 'flushes{component="engine"} 4' in labeled
    # dead components are pruned, their name becomes reusable
    del b
    assert "engine2" not in root.components()


def test_global_registry_sees_engine_components():
    from fluidframework_tpu.testing.chaos import make_engine
    engine = make_engine("string")
    engine.connect("d", 1)
    engine.submit("d", 1, 1, 0, {"mt": "insert", "kind": 0, "pos": 0,
                                 "text": "hi"})
    engine.flush()
    comps = REGISTRY.components()
    name = next((n for n, r in comps.items() if r is engine.metrics), None)
    assert name is not None and name.startswith("StringServingEngine")
    assert REGISTRY.full_snapshot()[f"{name}.flushes"] >= 1


# ----------------------------------------------------------------- tracing

def test_span_nesting_and_wire_roundtrip():
    tracer = tracing.Tracer()
    with tracer.span("outer", ops=2) as outer:
        wire = outer.ctx.to_wire()
        with tracer.span("inner") as inner:
            assert inner.ctx.trace_id == outer.ctx.trace_id
    # a wire dict re-attaches across a (simulated) socket hop
    ctx = tracing.TraceContext.from_wire(wire)
    assert (ctx.trace_id, ctx.span_id) == (outer.ctx.trace_id,
                                           outer.ctx.span_id)
    assert tracing.TraceContext.from_wire(None) is None
    assert tracing.TraceContext.from_wire({"x": 1}) is None
    evs = tracer.events(outer.ctx.trace_id)
    by_name = {e["name"]: e for e in evs}
    assert by_name["inner"]["parent_id"] == outer.ctx.span_id
    assert by_name["outer"]["parent_id"] is None
    assert by_name["outer"]["args"] == {"ops": 2}


def test_span_error_recorded_and_stack_unwound():
    tracer = tracing.Tracer()
    with pytest.raises(RuntimeError):
        with tracer.span("doomed"):
            raise RuntimeError("dead")
    e, = tracer.events()
    assert "dead" in e["error"]
    assert tracer.current() is None   # the stack unwound despite the raise


def test_record_complete_and_disabled_tracer():
    tracer = tracing.Tracer()
    ctx = tracer.record_complete("hot.batch", 12.5, ops=64)
    e, = tracer.events(ctx.trace_id)
    assert e["dur"] == pytest.approx(12.5e3)  # µs
    assert e["args"]["ops"] == 64
    tracer.enabled = False
    assert tracer.record_complete("off", 1.0) is None


def test_trace_id_propagation_full_round_trip():
    """A client op batch yields the acceptance span tree: outbox.flush →
    wire.submit → deli.sequence → serving.apply → ack, one trace id,
    correct parent chain."""
    from fluidframework_tpu.framework import LocalClient
    tracing.TRACER.clear()
    client = LocalClient()
    c1, doc_id = client.create_container(
        {"initialObjects": {"text": "sharedString"}})
    c1.initial_objects["text"].insert_text(0, "hello")
    flushes = [e for e in tracing.TRACER.events()
               if e["name"] == "outbox.flush"]
    assert flushes, "no outbox.flush span recorded"
    tid = flushes[-1]["trace_id"]
    evs = tracing.TRACER.events(tid)
    by_name = {e["name"]: e for e in evs}
    for name in ("outbox.flush", "wire.submit", "deli.sequence",
                 "serving.apply", "ack"):
        assert name in by_name, (name, sorted(by_name))
        assert by_name[name]["trace_id"] == tid
    chain = ("outbox.flush", "wire.submit", "deli.sequence",
             "serving.apply", "ack")
    for parent, child in zip(chain, chain[1:]):
        assert by_name[child]["parent_id"] == by_name[parent]["span_id"], \
            (parent, child)
    # the sequenced message carried the context out of band
    assert by_name["deli.sequence"]["args"]["doc"] == doc_id


def test_trace_viewer_renders_chrome_export(tmp_path):
    tracer = tracing.Tracer()
    with tracer.span("root", ops=1):
        with tracer.span("child"):
            pass
    tid = tracer.trace_ids()[0]
    path = str(tmp_path / "trace.json")
    doc = tracer.export_chrome(path, tid)
    assert json.load(open(path)) == doc
    assert all(ev["ph"] == "X" for ev in doc["traceEvents"])
    # viewer loads + renders both forms: dump file and live tracer
    out = trace_viewer.render(trace_viewer.load_events(path))
    lines = out.splitlines()
    assert lines[0].startswith("root") and "ops=1" in lines[0]
    assert lines[1].startswith("  child")
    assert trace_viewer.trace_ids(doc["traceEvents"]) == [tid]
    assert "root" in trace_viewer.render_tracer(tracer)


def test_span_tree_orphan_becomes_root():
    evs = [{"name": "a", "trace_id": "t", "span_id": 1,
            "parent_id": 999, "ts": 0.0, "dur": 1.0}]
    roots = tracing.span_tree(evs)
    assert [r["name"] for r in roots] == ["a"]


# --------------------------------------------------------- flight recorder

def test_flight_recorder_ring_and_dump(tmp_path):
    rec = flight_recorder.FlightRecorder(capacity=4,
                                         dump_dir=str(tmp_path))
    for i in range(6):
        rec.note("tick", i=i)
    events = rec.snapshot()
    assert len(events) == 4           # bounded: oldest two evicted
    assert events[0]["i"] == 2
    path = rec.dump("test", extra={"fh": open(os.devnull)})
    back = flight_recorder.load_dump(path)
    assert back[0]["flight_recorder"] == "test"
    assert back[0]["n_events"] == 4
    assert "TextIOWrapper" in back[0]["fh"]   # non-JSON coerced via repr
    assert [e["i"] for e in back[1:]] == [2, 3, 4, 5]


def test_flight_recorder_dump_rotation(tmp_path):
    rec = flight_recorder.FlightRecorder(dump_dir=str(tmp_path),
                                         max_dumps=2)
    paths = [rec.dump(f"r{i}") for i in range(3)]
    assert paths[0] == paths[2]       # seq rotates mod max_dumps
    assert len(rec.dumps) == 2        # bounded bookkeeping


def test_telemetry_feeds_flight_recorder_without_sink():
    flight_recorder.RECORDER.clear()
    TelemetryLogger(None, "eng").send_warning("overloaded", depth=9)
    ev = flight_recorder.RECORDER.snapshot()[-1]
    assert ev["eventName"] == "eng:overloaded"
    assert ev["depth"] == 9 and "ts" in ev


def test_faultpoint_crash_dumps_flight_recorder(tmp_path, monkeypatch):
    """The acceptance path: a chaos-drill crash leaves a JSONL dump whose
    events include the faultpoint firing."""
    monkeypatch.setenv("FLUID_FLIGHT_DIR", str(tmp_path))
    flight_recorder.RECORDER.clear()
    plan = FaultPlan(crash={SITE_SUBMIT_POST_SEQUENCE: 1})
    with armed(plan):
        with pytest.raises(CrashInjected):
            fault_point(SITE_SUBMIT_POST_SEQUENCE, doc="d0")
    path = flight_recorder.RECORDER.dumps[-1]
    assert path.startswith(str(tmp_path))
    events = flight_recorder.load_dump(path)
    assert events[0]["flight_recorder"] == \
        f"faultpoint:{SITE_SUBMIT_POST_SEQUENCE}"
    fired = [e for e in events if e.get("eventName") == "faultpoint_fired"]
    assert fired and fired[-1]["site"] == SITE_SUBMIT_POST_SEQUENCE
    assert fired[-1]["doc"] == "d0"
    assert "CrashInjected" in fired[-1]["error"]


def test_drill_assertion_failure_dumps(tmp_path, monkeypatch):
    from fluidframework_tpu.testing import chaos
    monkeypatch.setenv("FLUID_FLIGHT_DIR", str(tmp_path))

    @chaos._recorded_drill
    def failing_drill():
        assert False, "invariant violated"

    with pytest.raises(AssertionError):
        failing_drill()
    events = flight_recorder.load_dump(flight_recorder.RECORDER.dumps[-1])
    assert events[0]["flight_recorder"] == "drill:failing_drill"
    assert any(e.get("eventName") == "drill_assertion_failed"
               for e in events)


# ----------------------------------------------------------- lint: warn+count

def _warning_sites_without_counter():
    """AST sweep: every ``send_warning`` call's enclosing function must
    also increment a metrics counter (``.inc(``) — warnings are for
    humans, counters are for rates; a warn-only degradation path is
    invisible to dashboards."""
    offenders = []
    for path in sorted(PKG_ROOT.rglob("*.py")):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            calls = [c.func.attr for c in ast.walk(node)
                     if isinstance(c, ast.Call)
                     and isinstance(c.func, ast.Attribute)]
            if "send_warning" in calls and "inc" not in calls:
                offenders.append(f"{path.relative_to(PKG_ROOT)}:"
                                 f"{node.lineno} {node.name}")
    return offenders


def test_every_send_warning_site_also_counts():
    offenders = _warning_sites_without_counter()
    # telemetry.py itself defines send_warning; definitions have no calls
    assert not offenders, (
        "send_warning without a metrics counter in the same function "
        f"(warn-only degradation): {offenders}")


def _metric_names_in_tree():
    """AST sweep of every ``.inc(`` / ``.set_gauge(`` / ``.observe(``
    call whose first argument names a metric: string literals verbatim,
    f-strings as their literal prefix + ``*`` (the per-reason counter
    families), and both arms of a literal conditional. ``observe`` calls
    with a non-string first arg are ``Histogram.observe(value)`` — not a
    name site. Returns {name: "file:line"}."""
    roots = [PKG_ROOT, PKG_ROOT.parent / "tools"]
    files = []
    for r in roots:
        files += sorted(r.rglob("*.py")) if r.is_dir() else [r]
    kinds = {"inc", "set_gauge", "observe"}
    names = {}

    def literal_names(node):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return [node.value]
        if isinstance(node, ast.JoinedStr) and node.values and \
                isinstance(node.values[0], ast.Constant):
            return [str(node.values[0].value) + "*"]
        if isinstance(node, ast.IfExp):
            return literal_names(node.body) + literal_names(node.orelse)
        return []

    for path in files:
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in kinds and node.args):
                continue
            for name in literal_names(node.args[0]):
                names.setdefault(name, f"{path.name}:{node.lineno}")
    return names


def test_metric_names_all_in_observability_doc():
    """Dark-metric lint (ISSUE 4 satellite): every metric name used in
    the tree must appear, backtick-quoted, in docs/OBSERVABILITY.md's
    registry — a counter nobody documented is a counter nobody reads."""
    doc = (PKG_ROOT.parent / "docs" / "OBSERVABILITY.md").read_text()
    names = _metric_names_in_tree()
    assert names, "AST sweep found no metric call sites — lint is broken"
    assert len(names) > 20, f"sweep saw too few sites: {sorted(names)}"
    missing = [f"{n} ({where})" for n, where in sorted(names.items())
               if f"`{n}`" not in doc]
    assert not missing, (
        "metric names missing from docs/OBSERVABILITY.md's registry "
        f"table: {missing}")
