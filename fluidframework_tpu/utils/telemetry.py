"""Structured telemetry: loggers, performance spans, sampled counters.

Reference counterpart: ``@fluidframework/telemetry-utils`` —
``ITelemetryLogger``/``createChildLogger``, ``PerformanceEvent.timedExec``,
``LoggingError`` tagging, ``sampledTelemetry`` — SURVEY.md §2.15, §5.1
(mount empty). Host-pluggable sink (the reference delivers events to a
host-provided ``ITelemetryBaseLogger``); span taxonomy mirrors the
reference's hot paths: ``load`` / ``catchup`` / ``opApply`` / ``summarize``.

TPU-first addition (§5.5): ``MetricsRegistry`` — a process-wide registry of
counters, gauges, and latency histograms with Prometheus-style text
exposition, the role Prometheus metrics play server-side in the reference.
``MetricsCollector`` (the historical per-engine name) is the same class;
per-component collectors ``attach`` to the global :data:`REGISTRY` so one
``snapshot()``/``render_prometheus()`` covers the whole process (ISSUE 2).

Every event sent through a :class:`TelemetryLogger` — sink or no sink —
is also recorded into the process flight recorder
(``utils.flight_recorder``), so a crash dump carries the recent telemetry
stream of every layer.
"""

from __future__ import annotations

import bisect
import time
import weakref
from typing import Any, Callable, Dict, List, Optional

from . import flight_recorder as _flight

# event categories (reference: ITelemetryBaseEvent.category)
GENERIC = "generic"
PERFORMANCE = "performance"
ERROR = "error"
WARNING = "warning"   # degraded-but-serving conditions (shed load, stalls)

Sink = Callable[[dict], None]


class TelemetryLogger:
    """Namespaced structured logger (reference: ITelemetryLoggerExt).

    Events are flat dicts: ``{category, eventName, ...props}``; namespaces
    chain with ``:`` like the reference's logger namespaces.
    """

    def __init__(self, sink: Optional[Sink] = None, namespace: str = "",
                 props: Optional[Dict[str, Any]] = None):
        self._sink = sink
        self.namespace = namespace
        self.props = dict(props or {})

    def child(self, namespace: str,
              props: Optional[Dict[str, Any]] = None) -> "TelemetryLogger":
        """Reference: createChildLogger — inherits sink + props."""
        ns = f"{self.namespace}:{namespace}" if self.namespace else namespace
        return TelemetryLogger(self._sink, ns, {**self.props, **(props or {})})

    def send(self, category: str, event_name: str, **props) -> None:
        name = f"{self.namespace}:{event_name}" if self.namespace \
            else event_name
        event = {"category": category, "eventName": name,
                 **self.props, **props}
        # every event — sinked or not — feeds the crash flight recorder
        _flight.record(event)
        if self._sink is not None:
            self._sink(event)

    def send_event(self, event_name: str, **props) -> None:
        self.send(GENERIC, event_name, **props)

    def send_error(self, event_name: str, error: Optional[Exception] = None,
                   **props) -> None:
        if error is not None:
            props.setdefault("error", repr(error))
            props.setdefault("errorType", type(error).__name__)
        self.send(ERROR, event_name, **props)

    def send_warning(self, event_name: str, **props) -> None:
        """Degradation events: the system is still serving but shedding
        load or running slow — these must be VISIBLE (replica overflow,
        slow-consumer evictions, apply stalls), never silent."""
        self.send(WARNING, event_name, **props)

    def performance_event(self, event_name: str,
                          **props) -> "PerformanceEvent":
        return PerformanceEvent(self, event_name, props)


class PerformanceEvent:
    """Timed span (reference: PerformanceEvent.timedExec): emits ``_start``
    on enter and ``_end`` (with duration_ms) or ``_cancel`` (with the error)
    on exit. Use as a context manager."""

    def __init__(self, logger: TelemetryLogger, event_name: str,
                 props: Dict[str, Any],
                 clock: Callable[[], float] = time.perf_counter):
        self.logger = logger
        self.event_name = event_name
        self.props = props
        self.clock = clock
        self._t0: Optional[float] = None
        self.duration_ms: Optional[float] = None

    def __enter__(self) -> "PerformanceEvent":
        self._t0 = self.clock()
        self.logger.send(PERFORMANCE, f"{self.event_name}_start",
                         **self.props)
        return self

    def __exit__(self, exc_type, exc, _tb) -> None:
        self.duration_ms = (self.clock() - self._t0) * 1e3
        if exc is None:
            self.logger.send(PERFORMANCE, f"{self.event_name}_end",
                             duration_ms=self.duration_ms, **self.props)
        else:
            self.logger.send(ERROR, f"{self.event_name}_cancel",
                             duration_ms=self.duration_ms, error=repr(exc),
                             **self.props)


class SampledTelemetry:
    """Emit one aggregated event every ``rate`` records (reference:
    sampledTelemetry for hot-loop counters)."""

    def __init__(self, logger: TelemetryLogger, event_name: str,
                 rate: int = 1000):
        self.logger = logger
        self.event_name = event_name
        self.rate = rate
        self.count = 0
        self.total = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None

    def record(self, value: float = 1.0) -> None:
        self.count += 1
        self.total += value
        # track extremes so outliers (a 983 ms stall in a 1000-sample
        # window) survive aggregation instead of vanishing into the mean
        if self.min is None or value < self.min:
            self.min = value
        if self.max is None or value > self.max:
            self.max = value
        if self.count >= self.rate:
            self.flush()

    def flush(self) -> None:
        if self.count:
            self.logger.send(PERFORMANCE, self.event_name,
                             samples=self.count, total=self.total,
                             mean=self.total / self.count,
                             min=self.min, max=self.max)
            self.count = 0
            self.total = 0.0
            self.min = None
            self.max = None

    def close(self) -> None:
        """Flush any partial window (call on shutdown — a tail of
        ``count < rate`` records would otherwise be lost)."""
        self.flush()

    def __enter__(self) -> "SampledTelemetry":
        return self

    def __exit__(self, *_exc) -> None:
        self.close()


class Histogram:
    """Fixed-bucket latency histogram with percentile reads.

    ``observe(value, exemplar=...)`` additionally captures *exemplars* —
    (value, trace context) pairs in the Prometheus-exemplar sense — so an
    SLO breach on a percentile can name the trace id of the worst sample
    instead of just a number (utils.slo tags its flight dumps with it).
    """

    #: recent exemplars retained per histogram (bounded: hot paths observe
    #: millions of samples; only the newest few are diagnostic)
    EXEMPLAR_KEEP = 16

    def __init__(self, buckets_ms: Optional[List[float]] = None):
        # log-spaced defaults covering 10 µs .. 10 s
        self.bounds = buckets_ms if buckets_ms is not None else [
            0.01 * (10 ** (i / 4)) for i in range(25)]
        self.counts = [0] * (len(self.bounds) + 1)
        self.n = 0
        #: running sum of observed values — the Prometheus ``_sum`` sample;
        #: also what latency attribution needs for exact (not
        #: bucket-quantized) per-stage means
        self.sum_ms = 0.0
        #: newest-last (value_ms, trace_id, span_id) triples
        self.exemplars: List[tuple] = []
        #: the exemplar with the largest value ever observed — the sample
        #: an SLO post-mortem wants (the worst, not the latest)
        self.worst_exemplar: Optional[tuple] = None

    def record(self, value_ms: float) -> None:
        self.counts[bisect.bisect_left(self.bounds, value_ms)] += 1
        self.n += 1
        self.sum_ms += value_ms

    @property
    def mean(self) -> float:
        """Exact mean of observed values (0.0 when empty)."""
        return self.sum_ms / self.n if self.n else 0.0

    def observe(self, value_ms: float, exemplar: Any = None) -> None:
        """Record a sample; ``exemplar`` may be a ``TraceContext``-like
        object (``trace_id``/``span_id`` attrs), or ``True`` to capture
        the thread's current trace context (no-op when none is active).
        ``None`` (the default) records with zero exemplar overhead."""
        self.record(value_ms)
        if exemplar is None:
            return
        if exemplar is True:
            from . import tracing  # late: tracing imports telemetry
            exemplar = tracing.current()
            if exemplar is None:
                return
        entry = (value_ms, getattr(exemplar, "trace_id", None),
                 getattr(exemplar, "span_id", None))
        self.exemplars.append(entry)
        del self.exemplars[:-self.EXEMPLAR_KEEP]
        if self.worst_exemplar is None or value_ms >= self.worst_exemplar[0]:
            self.worst_exemplar = entry

    def percentile(self, p: float) -> float:
        """Upper bound of the bucket containing the p-th percentile.
        Returns ``inf`` when the percentile lands in the open-ended
        overflow bucket — check :attr:`overflow` to see how many values
        exceeded the last bound."""
        if self.n == 0:
            return 0.0
        target = p / 100.0 * self.n
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= target:
                return self.bounds[i] if i < len(self.bounds) \
                    else float("inf")
        return float("inf")

    @property
    def overflow(self) -> int:
        """Count of recorded values past the last bucket bound (the
        values ``percentile`` reports as ``inf``)."""
        return self.counts[-1]


#: fine log-spaced buckets (16 per decade vs the default 4) for the
#: per-stage ingest timings: with quarter-decade buckets a p50 read
#: quantizes a real 25 ms to the 31.6 ms bound — too coarse to check a
#: ≤30 ms budget against. 0.1 ms .. ~5.6 s.
_FINE_BOUNDS = [0.1 * (10 ** (i / 16)) for i in range(75)]

#: the stage-attribution grid keeps the fine sub-ms resolution but
#: extends to ~100 s: under a contended storm the rx→ack end-to-end
#: timeline legitimately reaches tens of seconds (windows queue behind
#: the executor), and a p99 that falls off the grid reads as ``inf`` —
#: useless as the sharding signal the breakdown exists to provide
_STAGE_BOUNDS = [0.1 * (10 ** (i / 16)) for i in range(97)]

#: name-prefix → bucket preset applied when ``observe`` lazily creates a
#: histogram; first matching prefix wins
BUCKET_PRESETS: List[tuple] = [
    ("ingest_", _FINE_BOUNDS),
    # latency-attribution stage segments (ISSUE 17): sub-ms segments like
    # the admission fence need the fine grid too
    ("stage_", _STAGE_BOUNDS),
]


def _buckets_for(name: str) -> Optional[List[float]]:
    for prefix, bounds in BUCKET_PRESETS:
        if name.startswith(prefix):
            return list(bounds)
    return None


class MetricsRegistry:
    """Process-wide counters, gauges, and latency histograms (SURVEY.md
    §5.5): the analog of the reference server's per-lambda Prometheus
    metrics (op rate, lag, pending ops), with Prometheus-style text
    exposition. Component-local instances (one per serving engine)
    ``attach`` to the module's global :data:`REGISTRY` so one snapshot
    covers the whole process."""

    def __init__(self):
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.histograms: Dict[str, Histogram] = {}
        # key -> weakref to an attached component registry: engines come
        # and go (tests build hundreds); the global registry must not
        # keep them alive
        self._components: Dict[str, Any] = {}
        # key -> label dict for label-qualified attachments (shard=,
        # replica=, partition= — the mesh rollup scheme, ISSUE 4)
        self._component_labels: Dict[str, Dict[str, str]] = {}

    # ----------------------------------------------------------- recording

    def inc(self, name: str, by: float = 1.0) -> None:
        self.counters[name] = self.counters.get(name, 0.0) + by

    def set_gauge(self, name: str, value: float) -> None:
        self.gauges[name] = value

    def observe(self, name: str, value_ms: float,
                exemplar: Any = None) -> None:
        if name not in self.histograms:
            self.histograms[name] = Histogram(_buckets_for(name))
        self.histograms[name].observe(value_ms, exemplar=exemplar)

    # ---------------------------------------------------------- components

    @staticmethod
    def component_key(name: str, labels: Optional[Dict[str, Any]]) -> str:
        """The snapshot key for an attachment: ``name`` bare, or
        ``name{k=v,...}`` with sorted label keys — two engines of the
        same family with different labels can never shadow each other."""
        if not labels:
            return name
        inner = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
        return f"{name}{{{inner}}}"

    def attach(self, name: str, registry: "MetricsRegistry",
               labels: Optional[Dict[str, Any]] = None) -> str:
        """Register a component-local registry for global exposition.

        ``labels`` qualify the key (``name{shard=0}``): the mesh rollup
        scheme — per-shard / per-replica / per-partition collectors stay
        distinct series in ``full_snapshot()`` and the Prometheus text.
        Unlabeled (or same-label) collisions between *different* live
        registries auto-suffix the name (several engines of the same
        family in one process). Returns the key used."""
        base, i = name, 1
        while True:
            key = self.component_key(name, labels)
            ref = self._components.get(key)
            if ref is None or ref() is None or ref() is registry:
                break
            i += 1
            name = f"{base}{i}"
        self._components[key] = weakref.ref(registry)
        if labels:
            self._component_labels[key] = {
                k: str(v) for k, v in labels.items()}
        return key

    def components(self) -> Dict[str, "MetricsRegistry"]:
        live = {}
        for key, ref in list(self._components.items()):
            reg = ref()
            if reg is None:
                del self._components[key]
                self._component_labels.pop(key, None)
            else:
                live[key] = reg
        return live

    def component_labels(self, key: str) -> Dict[str, str]:
        """Labels a component was attached with (empty for bare names)."""
        return dict(self._component_labels.get(key, {}))

    # ------------------------------------------------------------ snapshot

    def snapshot(self) -> dict:
        """Flat dict: counters verbatim, gauges verbatim, and per-
        histogram ``_p50_ms``/``_p99_ms``/``_count``/``_overflow``."""
        out: Dict[str, Any] = dict(self.counters)
        out.update(self.gauges)
        for name, h in self.histograms.items():
            out[f"{name}_p50_ms"] = h.percentile(50)
            out[f"{name}_p99_ms"] = h.percentile(99)
            out[f"{name}_count"] = h.n
            out[f"{name}_overflow"] = h.overflow
        return out

    def full_snapshot(self) -> dict:
        """Own snapshot + every live attached component's, prefixed
        ``{component}.{metric}`` — the process-wide metric set
        ``TimeSeriesStore`` samples. Sharded attachments (components labeled
        ``shard=``) additionally roll up into computed cross-shard skew
        keys: ``{name}.ops_applied_shard_{min,max,skew}`` — the max/min
        ops-applied imbalance is the load-balance health signal."""
        out = self.snapshot()
        shard_groups: Dict[str, List[float]] = {}
        for key, reg in self.components().items():
            for k, v in reg.snapshot().items():
                out[f"{key}.{k}"] = v
            labels = self._component_labels.get(key)
            if labels and "shard" in labels:
                base = key.split("{", 1)[0]
                shard_groups.setdefault(base, []).append(
                    float(reg.counters.get("ops_applied", 0.0)))
        for base, counts in shard_groups.items():
            if len(counts) >= 2:
                out[f"{base}.ops_applied_shard_min"] = min(counts)
                out[f"{base}.ops_applied_shard_max"] = max(counts)
                out[f"{base}.ops_applied_shard_skew"] = \
                    max(counts) - min(counts)
        return out

    def snapshot_kinds(self) -> Dict[str, str]:
        """Kind of every key ``snapshot()`` emits: ``counter`` | ``gauge``
        | ``quantile`` (histogram percentile reads — point-in-time, never
        rate-derived). Histogram ``_count``/``_overflow`` keys are
        cumulative and classified ``counter``. The time-series layer
        (utils.timeseries) uses this to decide which series get
        counter→rate derivation."""
        kinds: Dict[str, str] = {}
        for k in self.counters:
            kinds[k] = "counter"
        for k in self.gauges:
            kinds[k] = "gauge"
        for name in self.histograms:
            kinds[f"{name}_p50_ms"] = "quantile"
            kinds[f"{name}_p99_ms"] = "quantile"
            kinds[f"{name}_count"] = "counter"
            kinds[f"{name}_overflow"] = "counter"
        return kinds

    def full_snapshot_kinds(self) -> Dict[str, str]:
        """``snapshot_kinds`` over the full (component-prefixed) key set;
        computed skew keys are gauges."""
        kinds = self.snapshot_kinds()
        for key, reg in self.components().items():
            for k, kind in reg.snapshot_kinds().items():
                kinds[f"{key}.{k}"] = kind
            labels = self._component_labels.get(key)
            if labels and "shard" in labels:
                base = key.split("{", 1)[0]
                for suffix in ("min", "max", "skew"):
                    kinds[f"{base}.ops_applied_shard_{suffix}"] = "gauge"
        return kinds

    def find_histogram(self, snapshot_key: str) -> Optional[Histogram]:
        """The Histogram behind a full-snapshot key (e.g.
        ``StringServingEngine.flush_ms_p99_ms`` → that engine's
        ``flush_ms`` histogram), or None — the SLO engine resolves breach
        exemplars through this."""
        comp, _, metric = snapshot_key.rpartition(".")
        reg = self if not comp else self.components().get(comp)
        if reg is None:
            return None
        for suffix in ("_p50_ms", "_p99_ms", "_count", "_overflow"):
            if metric.endswith(suffix):
                metric = metric[:-len(suffix)]
                break
        return reg.histograms.get(metric)

    def render_prometheus(self, include_components: bool = True) -> str:
        """Prometheus text exposition (counters/gauges as single samples,
        histograms as cumulative ``_bucket`` lines plus ``_sum``/``_count``
        — bounds are upper edges in ms, ``+Inf`` is the overflow bucket).
        Labeled attachments carry their labels on every sample
        (``component="StringServingEngine",shard="3"``) — the per-shard /
        per-replica / per-partition series of the mesh rollup scheme.
        Label values are escaped per the text-format spec (backslash,
        double quote, newline); serve with content-type
        :data:`PROM_CONTENT_TYPE`."""
        lines: List[str] = []

        def emit(prefix: str, reg: "MetricsRegistry",
                 labels: Optional[Dict[str, str]] = None) -> None:
            pairs = ([f'component="{_prom_label_value(prefix)}"']
                     if prefix else []) + \
                [f'{k}="{_prom_label_value(v)}"'
                 for k, v in sorted((labels or {}).items())]
            lab = "{" + ",".join(pairs) + "}" if pairs else ""
            comp = ",".join(pairs) + "," if pairs else ""
            counters = reg.counters         # one reading of the registry
            for k in sorted(counters):
                lines.append(f"# TYPE {_prom_name(k)} counter")
                lines.append(f"{_prom_name(k)}{lab} {counters[k]}")
            for k in sorted(reg.gauges):
                lines.append(f"# TYPE {_prom_name(k)} gauge")
                lines.append(f"{_prom_name(k)}{lab} {reg.gauges[k]}")
            for k in sorted(reg.histograms):
                h = reg.histograms[k]
                name = _prom_name(k)
                lines.append(f"# TYPE {name} histogram")
                cum = 0
                for bound, c in zip(h.bounds, h.counts):
                    cum += c
                    lines.append(
                        f'{name}_bucket{{{comp}le="{bound:g}"}} {cum}')
                lines.append(f'{name}_bucket{{{comp}le="+Inf"}} {h.n}')
                lines.append(f"{name}_sum{lab} {h.sum_ms}")
                lines.append(f"{name}_count{lab} {h.n}")

        emit("", self)
        if include_components:
            for key, reg in sorted(self.components().items()):
                emit(key.split("{", 1)[0], reg,
                     self._component_labels.get(key))
        return "\n".join(lines) + "\n"


#: exposition content-type for :meth:`MetricsRegistry.render_prometheus`
PROM_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


def _prom_name(name: str) -> str:
    """Sanitize a metric name for Prometheus exposition."""
    return "".join(ch if ch.isalnum() or ch == "_" else "_"
                   for ch in name)


def _prom_label_value(value: Any) -> str:
    """Escape a label value per the Prometheus text format: backslash,
    double quote, and line feed are the three characters that would
    otherwise break a scraper's line/quote parse."""
    return (str(value).replace("\\", r"\\").replace('"', r'\"')
            .replace("\n", r"\n"))


class StageClock:
    """Per-stage busy-time accounting for a pipelined executor (the
    ingest pipeline's stage-occupancy/overlap instrument).

    Each worker thread adds its stage's busy wall after every unit of
    work; ``occupancy()`` divides per-stage busy time by the clock's open
    wall-span (how loaded each worker is), and ``overlap()`` is the sum
    of all stages' busy time over the span — a value above 1.0 is direct
    evidence that stages genuinely ran concurrently (a serial stage walk
    can never exceed 1.0)."""

    def __init__(self, stages):
        import threading
        self.stages = tuple(stages)
        self.busy_ms: Dict[str, float] = {s: 0.0 for s in self.stages}
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()

    def add(self, stage: str, ms: float) -> None:
        with self._lock:
            self.busy_ms[stage] += ms

    def span_ms(self) -> float:
        return (time.perf_counter() - self._t0) * 1000

    def occupancy(self) -> Dict[str, float]:
        span = self.span_ms() or 1.0
        with self._lock:
            return {s: self.busy_ms[s] / span for s in self.stages}

    def overlap(self) -> float:
        span = self.span_ms() or 1.0
        with self._lock:
            return sum(self.busy_ms.values()) / span


#: back-compat name — per-engine collectors ARE registries
MetricsCollector = MetricsRegistry

#: the process-wide registry: dark-layer instrumentation (oplog,
#: summarizer, container runtime, kernels, ingress) counts here, and
#: component registries attach for unified exposition
REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return REGISTRY


def console_sink(event: dict) -> None:
    """Debug sink: one line per event."""
    print(" ".join(f"{k}={v}" for k, v in event.items()))


class BufferSink:
    """Test/inspection sink: collects events in memory."""

    def __init__(self):
        self.events: List[dict] = []

    def __call__(self, event: dict) -> None:
        self.events.append(event)

    def named(self, suffix: str) -> List[dict]:
        return [e for e in self.events
                if e["eventName"].endswith(suffix)]
