"""Live operations plane (ISSUE 17): scrape endpoint, latency
attribution, hot-doc introspection.

Every other observability surface is post-hoc — a ``full_snapshot()``
taken by the caller, a ``TimeSeriesStore`` the caller ticks,
``tools/healthz.py`` reading JSONL exports after the run. This module
makes a *running* server observable:

* :class:`OpsServer` — a threaded HTTP façade (``utils.ops_http``) over
  the process singletons: ``/metrics`` (Prometheus text exposition with
  correct content-type and label escaping), ``/healthz`` (live SLO
  scorecard JSON), ``/debug/flights`` (flight-recorder ring),
  ``/debug/trace`` (recent spans as Chrome trace-event JSON),
  ``/debug/hotdocs`` (heavy-hitter sketch), ``/debug/latency``
  (per-stage breakdown). A background ticker thread finally runs
  ``TimeSeriesStore`` sampling + ``SLOEngine`` burn checks on live
  servers, the role the reference's Prometheus scrape loop plays behind
  Routerlicious.

* Latency attribution — :func:`observe_window_timeline` turns the
  monotonic crossing stamps the ingress door and the ingest executor
  record onto each window (rx-buffer → drain/decode → admission → pack →
  sequence → dispatch → durable-append → ack) into per-stage
  ``stage_*_ms`` histograms. Stages are *consecutive timeline segments*,
  so they sum to the observed end-to-end ack latency by construction —
  :func:`latency_breakdown` is the "which stage do we shard next" view.

* :class:`SpaceSaving` — the bounded heavy-hitter sketch over
  ``(doc, tenant)`` maintained in the drain pass; ``/debug/hotdocs`` and
  the ``hotdoc_*`` gauges expose the routing/eviction signal ROADMAP
  items 1 and 3 consume.
"""
from __future__ import annotations

import math
import threading
import time
from typing import (Any, Callable, Dict, Iterable, List, Optional,
                    Tuple)

from ..utils import capacity as _capacity
from ..utils import flight_recorder as _flight
from ..utils import slo as _slo
from ..utils import tracing as _tracing
from ..utils.ops_http import OpsHTTPServer, json_body
from ..utils.telemetry import (PROM_CONTENT_TYPE, REGISTRY,
                               MetricsRegistry)
from ..utils.timeseries import TimeSeriesStore

__all__ = ["OpsServer", "SpaceSaving", "STAGES",
           "observe_window_timeline", "latency_breakdown"]


# --------------------------------------------------------------------------
# latency attribution
# --------------------------------------------------------------------------

#: canonical stage order of the ingest path; ``stage_{name}_ms``
#: histograms are consecutive segments of one monotonic timeline
STAGES = ("rx", "decode", "admit", "pack",
          "sequence", "dispatch", "log", "ack")
#: stage → the mark at which its work starts; what precedes it inside
#: the stage's segment is a wait (``stage_{name}_wait_ms``). ``rx`` is a
#: wait whole, ``decode`` and ``admit`` have none.
WORK_STARTS = {"pack": "pack0", "sequence": "seq0", "dispatch": "disp0",
               "log": "log0", "ack": "ack0"}


def observe_window_timeline(tl: dict, marks: dict, t_ack: float,
                            registry: Optional[MetricsRegistry] = None,
                            exemplar: Any = None) -> None:
    """Attribute one window's end-to-end ack latency to stages.

    ``tl`` is the front-door timeline the drain pass stamps
    (``t_rx``/``t_drain0``/``decode_ms``/``admit_ms``/``t_ready``),
    ``marks`` the executor-side crossings the engine's stage methods
    stamp (``pack1``/``seq1``/``disp1``/``log1``, absolute
    ``perf_counter`` seconds), ``t_ack`` the ack-fan time. Segment k is
    ``crossing[k+1] - crossing[k]`` with crossings clamped monotonic, so
    ``sum(stage_*_ms) == stage_e2e_ack_ms`` exactly — queue waits land
    in the stage that absorbed them (pack's segment includes the
    executor hand-off wait; ack's the done-callback bounce). Where
    ``marks`` also holds a stage's start crossing (``pack0``/``seq0``/
    ``disp0``/``log0``, and ``ack0`` where the bounce ended), the part
    of the segment before it is observed as ``stage_{name}_wait_ms``:
    each segment is then a wait plus the stage's work."""
    t_rx = float(tl["t_rx"])
    t_ready = float(tl["t_ready"])
    admit_s = max(0.0, float(tl.get("admit_ms", 0.0))) * 1e-3
    crossings = [
        t_rx,
        float(tl["t_drain0"]),      # rx segment ends: drain pass starts
        t_ready - admit_s,          # decode ends where admission begins
        t_ready,                    # decoded + admitted, awaiting submit
        float(marks.get("pack1", t_ready)),
        float(marks.get("seq1", t_ready)),
        float(marks.get("disp1", t_ready)),
        float(marks.get("log1", t_ready)),
        float(t_ack),
    ]
    for i in range(1, len(crossings)):   # clock skew / missing marks
        if crossings[i] < crossings[i - 1]:
            crossings[i] = crossings[i - 1]
    reg = registry if registry is not None else REGISTRY
    for name, a, b in zip(STAGES, crossings, crossings[1:]):
        reg.observe(f"stage_{name}_ms", (b - a) * 1e3)
        start = marks.get(WORK_STARTS.get(name))
        if start is not None:
            reg.observe(f"stage_{name}_wait_ms",
                        min(max(float(start) - a, 0.0), b - a) * 1e3)
    reg.observe("stage_e2e_ack_ms", (crossings[-1] - crossings[0]) * 1e3,
                exemplar=exemplar)


def latency_breakdown(registry: Optional[MetricsRegistry] = None) -> dict:
    """Per-stage summary of the accumulated attribution histograms.

    ``stage_sum_ms`` (the sum of per-stage means) matches ``e2e_mean_ms``
    within clock-granularity tolerance whenever every observed window
    recorded all stages — the acceptance check for ISSUE 17 and the
    sharding signal: the stage with the largest mean share is the next
    thing to scale out."""
    reg = registry if registry is not None else REGISTRY
    stages: Dict[str, dict] = {}
    stage_sum = 0.0
    for name in STAGES:
        h = reg.histograms.get(f"stage_{name}_ms")
        if h is None or h.n == 0:
            continue
        hw = reg.histograms.get(f"stage_{name}_wait_ms")
        stages[name] = {"mean_ms": h.mean, "p50_ms": h.percentile(50),
                        "p99_ms": h.percentile(99), "count": h.n,
                        # per window, like mean_ms (windows whose marks
                        # lacked the start crossing count as no wait)
                        "wait_ms": h.mean if name == "rx" else
                        hw.sum_ms / h.n if hw is not None else 0.0}
        stage_sum += h.mean
    e2e = reg.histograms.get("stage_e2e_ack_ms")
    e2e_mean = e2e.mean if e2e is not None and e2e.n else 0.0
    for name, row in stages.items():
        row["share"] = row["mean_ms"] / e2e_mean if e2e_mean else 0.0
    return {
        "stages": stages,
        "stage_sum_ms": stage_sum,
        "e2e_mean_ms": e2e_mean,
        "e2e_p99_ms": e2e.percentile(99) if e2e is not None else 0.0,
        "windows": e2e.n if e2e is not None else 0,
        "coverage": stage_sum / e2e_mean if e2e_mean else 0.0,
    }


# --------------------------------------------------------------------------
# heavy-hitter sketch
# --------------------------------------------------------------------------

class _Bucket:
    """The tracked keys that share one estimated count: a node of the
    stream-summary's ascending list."""
    __slots__ = ("count", "keys", "prev", "next")

    def __init__(self, count):
        self.count = count
        #: key -> err, in the order the keys reached this count
        self.keys: Dict[Any, int] = {}
        self.prev = self.next = self


class SpaceSaving:
    """Bounded Space-Saving heavy-hitter sketch (Metwally et al. 2005).

    Tracks at most ``capacity`` keys in O(capacity) memory. Estimated
    counts overestimate the true count by at most the entry's ``err``
    (the evicted minimum it inherited), and any key whose true count
    exceeds ``total / capacity`` is guaranteed to be tracked — exactly
    the guarantee a hot-doc router or eviction policy needs. Thread-safe:
    the drain pass offers from the ingress loop, the ops endpoint reads
    from scrape threads.

    The entries live in the paper's stream-summary: keys grouped in
    buckets by count, the buckets in a ring in ascending order. A miss
    against a full sketch (the ordinary case of a door that serves more
    documents than ``capacity``; ``evictions`` counts them) takes any
    key of the first bucket as its victim, and every offer moves its key
    forward past at most the buckets between ``count`` and
    ``count + n``: O(1) for a unit offer, never a scan of the entries."""

    def __init__(self, capacity: int = 256):
        self.capacity = max(1, int(capacity))
        #: key -> the bucket that holds it
        self._entries: Dict[Any, _Bucket] = {}
        #: the ring's sentinel: ``next`` is the minimum bucket, ``prev``
        #: the maximum; its infinite count ends every forward walk
        self._root = _Bucket(math.inf)
        self.total = 0
        #: misses against a full sketch, each of which replaced a
        #: minimum-count key; beside ``total``, how saturated it is
        self.evictions = 0
        self._lock = threading.Lock()

    def _offer(self, key: Any, n: int) -> None:
        """One offer, the lock held."""
        entries, root = self._entries, self._root
        self.total += n
        src = entries.get(key)
        if src is not None:
            count, err = src.count + n, src.keys.pop(key)
        elif len(entries) < self.capacity:
            src, count, err = root, n, 0
        else:
            # evict a key of the minimum bucket; the newcomer inherits
            # its count as the overestimation bound
            src = root.next
            del entries[src.keys.popitem()[0]]
            count, err = src.count + n, src.count
            self.evictions += 1
        dst = src.next
        while dst.count < count:
            dst = dst.next
        if not src.keys and src is not root:
            if dst is src.next and dst.count != count:
                src.count = count       # alone, the gap free: in place
                dst = src
            else:
                src.prev.next, src.next.prev = src.next, src.prev
        if dst.count != count:
            nxt, dst = dst, _Bucket(count)
            dst.prev, dst.next = nxt.prev, nxt
            nxt.prev.next = nxt.prev = dst
        dst.keys[key] = err
        entries[key] = dst

    def offer(self, key: Any, n: int = 1) -> None:
        with self._lock:
            self._offer(key, n)

    def offer_many(self, keys: Iterable[Any], counts: Iterable[int]
                   ) -> None:
        """``offer(key, n)`` for each pair in order, under one hold of
        the lock: a drain pass's part in one call."""
        offer = self._offer
        with self._lock:
            for key, n in zip(keys, counts):
                offer(key, n)

    def top(self, k: int = 10) -> List[Tuple[Any, int, int]]:
        """``(key, estimated_count, err)`` rows, largest first.
        ``estimated_count - err`` is a guaranteed lower bound."""
        rows: List[Tuple[Any, int, int]] = []
        with self._lock:
            b = self._root.prev
            while b is not self._root and len(rows) < k:
                rows.extend((key, b.count, err)
                            for key, err in b.keys.items())
                b = b.prev
        return rows[:k]

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._root.prev = self._root.next = self._root
            self.total = 0
            self.evictions = 0


def publish_hotdoc_gauges(sketches: List[SpaceSaving],
                          registry: Optional[MetricsRegistry] = None
                          ) -> None:
    """Roll the attached sketches up into the ``hotdoc_*`` gauges: how
    many keys are tracked, how many offers evicted one, the hottest
    key's estimated ops, and its share of all sketched traffic — the
    skew signal at a glance."""
    reg = registry if registry is not None else REGISTRY
    tracked = sum(len(s) for s in sketches)
    total = sum(s.total for s in sketches)
    top = 0
    for s in sketches:
        rows = s.top(1)
        if rows:
            top = max(top, rows[0][1])
    reg.set_gauge("hotdoc_tracked", float(tracked))
    reg.set_gauge("hotdoc_evictions",
                  float(sum(s.evictions for s in sketches)))
    reg.set_gauge("hotdoc_top_count", float(top))
    reg.set_gauge("hotdoc_top_share", top / total if total else 0.0)


# --------------------------------------------------------------------------
# JSON hygiene
# --------------------------------------------------------------------------

def _finite(obj: Any) -> Any:
    """Recursively replace non-finite floats with ``None`` so route
    payloads stay strict JSON (scorecard burn rates are ``inf`` when a
    window has no samples; histogram percentiles can be ``inf``)."""
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    return obj


# --------------------------------------------------------------------------
# the ops server
# --------------------------------------------------------------------------

class OpsServer:
    """The live operations plane of one process.

    Attach it to anything that serves: ``LocalService.start_ops()``,
    ``ColumnarAlfred.start_ops()``, ``AlfredServer.start_ops()``, or the
    tools' ``--ops-port``. It owns (or borrows) a ``TimeSeriesStore`` +
    ``SLOEngine`` pair and a background ticker thread so sampling and
    burn-rate checks run continuously — ``tick_interval_s=0`` disables
    the ticker for hosts that already tick their own control loop
    (tenant_sim)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 registry: Optional[MetricsRegistry] = None,
                 store: Optional[TimeSeriesStore] = None,
                 slo_engine: Optional[Any] = None,
                 specs: Optional[list] = None,
                 recorder: Optional[Any] = None,
                 tracer: Optional[Any] = None,
                 tick_interval_s: float = 1.0):
        self.registry = registry if registry is not None else REGISTRY
        self.store = store if store is not None \
            else TimeSeriesStore(registry=self.registry)
        if slo_engine is not None:
            self.slo_engine = slo_engine
        else:
            self.slo_engine = _slo.SLOEngine(
                self.store, specs=specs if specs is not None
                else _slo.default_slos(), registry=self.registry)
        self.recorder = recorder if recorder is not None \
            else _flight.RECORDER
        self.tracer = tracer if tracer is not None else _tracing.TRACER
        self.tick_interval_s = tick_interval_s
        self.ticks = 0
        self._t_started = time.time()
        self._sketches: List[SpaceSaving] = []
        self._partition_providers: List[Callable[[], List[dict]]] = []
        self._reader_hubs: List[Any] = []
        self._on_tick: List[Callable[[], None]] = []
        self._tick_stop = threading.Event()
        self._ticker: Optional[threading.Thread] = None
        self.http = (OpsHTTPServer(host, port)
                     .route("/metrics", self._r_metrics)
                     .route("/healthz", self._r_healthz)
                     .route("/debug/flights", self._r_flights)
                     .route("/debug/trace", self._r_trace)
                     .route("/debug/hotdocs", self._r_hotdocs)
                     .route("/debug/latency", self._r_latency)
                     .route("/debug/partitions", self._r_partitions)
                     .route("/debug/memory", self._r_memory)
                     .route("/debug/docs", self._r_docs)
                     .route("/debug/readers", self._r_readers))

    # -------------------------------------------------------- attachments

    def add_hotdocs(self, sketch: SpaceSaving) -> "OpsServer":
        """Expose a drain-pass sketch at ``/debug/hotdocs`` and in the
        ``hotdoc_*`` gauges (multiple doors may each attach one)."""
        self._sketches.append(sketch)
        return self

    def add_partitions(self, provider: Callable[[], List[dict]]
                       ) -> "OpsServer":
        """Expose a partitioned door's per-partition rows (occupancy,
        backlog, resident docs — ISSUE 18) at ``/debug/partitions``."""
        self._partition_providers.append(provider)
        return self

    def add_readers(self, hub: Any) -> "OpsServer":
        """Expose an observer hub's per-subscriber rows (window lag,
        delivered volume, shed counts — ISSUE 20) at ``/debug/readers``.
        ``hub`` is anything with ``.readers()`` and ``.stats()``
        (``server.observer.ObserverHub``); multiple doors may each
        attach their own."""
        self._reader_hubs.append(hub)
        return self

    def on_tick(self, fn: Callable[[], None]) -> "OpsServer":
        """Run ``fn()`` on every ticker beat (host gauge publishers —
        e.g. a service exporting replica queue depth). Exceptions are
        swallowed: a bad publisher must not kill sampling."""
        self._on_tick.append(fn)
        return self

    # ------------------------------------------------------------- routes

    def _r_metrics(self, _q: Dict[str, str]) -> Tuple[str, bytes]:
        self.registry.inc("ops_scrapes_total")
        text = self.registry.render_prometheus()
        return (PROM_CONTENT_TYPE, text.encode("utf-8"))

    def _r_healthz(self, _q: Dict[str, str]) -> Tuple[str, bytes]:
        rows = self.slo_engine.scorecard()
        judged = [r for r in rows if r.get("judged")]
        return json_body(_finite({
            "ok": all(r["ok"] for r in judged),
            "judged": len(judged),
            "ticks": self.ticks,
            "uptime_s": time.time() - self._t_started,
            "rows": rows,
        }))

    def _r_flights(self, q: Dict[str, str]) -> Tuple[str, bytes]:
        limit = int(q.get("n", "512"))
        events = self.recorder.snapshot()
        return json_body(_finite({
            "count": len(events),
            "suppressed": dict(self.recorder.suppressed),
            "events": events[-limit:],
        }))

    def _r_trace(self, q: Dict[str, str]) -> Tuple[str, bytes]:
        if q.get("list"):
            return json_body({"trace_ids": self.tracer.trace_ids()})
        limit = int(q.get("n", "2048"))
        events = self.tracer.events(q.get("trace"))[-limit:]
        return json_body(_finite(
            {"traceEvents": [_tracing.chrome_event(e) for e in events]}))

    def _r_hotdocs(self, q: Dict[str, str]) -> Tuple[str, bytes]:
        k = int(q.get("k", "20"))
        merged: List[Tuple[Any, int, int]] = []
        for s in self._sketches:
            merged.extend(s.top(k))
        merged.sort(key=lambda row: row[1], reverse=True)
        return json_body(_finite({
            "capacity": sum(s.capacity for s in self._sketches),
            "tracked": sum(len(s) for s in self._sketches),
            "total_ops": sum(s.total for s in self._sketches),
            "evictions": sum(s.evictions for s in self._sketches),
            "top": [{"doc": key[0], "tenant": key[1],
                     "count": count, "err": err}
                    if isinstance(key, tuple) and len(key) == 2 else
                    {"key": key, "count": count, "err": err}
                    for key, count, err in merged[:k]],
        }))

    def _r_latency(self, q: Dict[str, str]) -> Tuple[str, bytes]:
        part = q.get("partition")
        if part is not None:
            # the partition dimension (ISSUE 18): the door observes the
            # stage timeline a second time into a partition-labeled
            # collector — serve THAT collector's breakdown
            suffix = "{partition=%s}" % part
            for key, reg in self.registry.components().items():
                if key.endswith(suffix) and any(
                        n.startswith("stage_") for n in reg.histograms):
                    out = latency_breakdown(reg)
                    out["partition"] = int(part)
                    return json_body(_finite(out))
            return json_body(_finite({"partition": int(part),
                                      "stages": {}, "windows": 0}))
        return json_body(_finite(latency_breakdown(self.registry)))

    def _r_partitions(self, _q: Dict[str, str]) -> Tuple[str, bytes]:
        rows: List[dict] = []
        for provider in self._partition_providers:
            try:
                rows.extend(provider())
            except Exception as e:   # debug route: never 500 the plane
                rows.append({"error": repr(e)})
        return json_body(_finite({"count": len(rows),
                                  "partitions": rows}))

    def _r_readers(self, _q: Dict[str, str]) -> Tuple[str, bytes]:
        """Read-plane census (ISSUE 20): per-subscriber lag/shed rows
        from every attached observer hub plus the fleet aggregate."""
        rows: List[dict] = []
        agg = {"subscribers": 0, "windows_published": 0,
               "ops_published": 0, "worst_lag_windows": 0,
               "sheds": 0, "parked": 0, "staleness_p99_s": 0.0}
        for hub in self._reader_hubs:
            try:
                rows.extend(hub.readers())
                s = hub.stats()
            except Exception as e:   # debug route: never 500 the plane
                rows.append({"error": repr(e)})
                continue
            for k in ("subscribers", "windows_published",
                      "ops_published", "sheds", "parked"):
                agg[k] += s.get(k, 0)
            agg["worst_lag_windows"] = max(
                agg["worst_lag_windows"], s.get("worst_lag_windows", 0))
            agg["staleness_p99_s"] = max(
                agg["staleness_p99_s"], s.get("staleness_p99_s", 0.0))
        return json_body(_finite({**agg, "count": len(rows),
                                  "readers": rows}))

    def _r_memory(self, q: Dict[str, str]) -> Tuple[str, bytes]:
        """Capacity census (ISSUE 19): host planes by owner/category,
        device buffers by engine, compile-cache stats, budget headroom.
        ``?device=0`` skips the live-array walk; ``?k=N`` sizes the
        heaviest/coldest lists."""
        try:
            census = _capacity.LEDGER.census(
                top_k=int(q.get("k", "8")),
                device=q.get("device", "1") not in ("0", "false"),
                device_ttl_s=5.0)
        except Exception as e:   # debug route: never 500 the plane
            census = {"error": repr(e)}
        return json_body(_finite(census))

    def _r_docs(self, q: Dict[str, str]) -> Tuple[str, bytes]:
        """Doc-level residency view: resident counts by owner, top-K
        heaviest docs, top-K coldest (exact last-touch stamps)."""
        try:
            census = _capacity.LEDGER.census(
                top_k=int(q.get("k", "16")), device=False)
            out = {"docs": census["docs"], "idle": census["idle"],
                   "heaviest": census["top"]["heaviest"],
                   "coldest": census["top"]["coldest"]}
        except Exception as e:
            out = {"error": repr(e)}
        return json_body(_finite(out))

    # ---------------------------------------------------------- lifecycle

    def start(self) -> "OpsServer":
        self.http.start()
        if self.tick_interval_s and self._ticker is None:
            self._tick_stop.clear()
            self._ticker = threading.Thread(
                target=self._tick_loop, name="opsd-ticker", daemon=True)
            self._ticker.start()
        return self

    def stop(self) -> None:
        self._tick_stop.set()
        ticker = self._ticker
        self._ticker = None
        if ticker is not None:
            ticker.join(timeout=5)
        self.http.stop()

    @property
    def port(self) -> int:
        return self.http.port

    @property
    def url(self) -> str:
        return self.http.url

    def __enter__(self) -> "OpsServer":
        return self.start()

    def __exit__(self, *_exc) -> None:
        self.stop()

    # -------------------------------------------------------------- ticker

    def tick_once(self, now: Optional[float] = None) -> None:
        """One sampling beat: time-series sample, SLO burn check, hot-doc
        gauges, host publishers. The ticker thread calls this; hosts
        with their own control loop may call it directly."""
        self.ticks += 1
        self.registry.inc("ops_ticks_total")
        self.registry.set_gauge("ops_ticker_last_unix", time.time())
        self.registry.set_gauge("ops_uptime_s",
                                time.time() - self._t_started)
        if self._sketches:
            publish_hotdoc_gauges(self._sketches, self.registry)
        for fn in list(self._on_tick):
            try:
                fn()
            except Exception:
                pass
        # capacity gauges BEFORE the SLO check so memory_budget_headroom
        # is judged against this beat's census (device walk TTL-cached —
        # the 1 Hz ticker stays within the scrape-overhead bound)
        try:
            _capacity.LEDGER.publish_gauges(self.registry,
                                            device_ttl_s=5.0)
        except Exception:
            pass
        self.store.tick(now=now)
        try:
            self.slo_engine.check(now=now)
        except Exception:
            pass

    def _tick_loop(self) -> None:
        while not self._tick_stop.wait(self.tick_interval_s):
            self.tick_once()
