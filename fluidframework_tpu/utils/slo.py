"""Declarative SLOs with multi-window burn-rate evaluation.

Reference counterpart: the SRE-workbook alerting lineage the reference
service's lag/latency alerts follow — an objective is declared once
(``ack_p99_ms < 200``) and judged over TWO windows: a *fast* window that
catches a cliff within seconds and a *slow* window that keeps one bad
sample from paging. A breach requires both windows to be burning, the
standard multi-window multi-burn-rate shape: fast-only is noise, slow-only
is stale history.

Specs evaluate over a :class:`~fluidframework_tpu.utils.timeseries.\
TimeSeriesStore` (never raw snapshots — an SLO is a statement about a
window, not an instant). Breaches are edge-triggered: the first tick a
spec crosses into breach it (a) increments ``slo_breach_total``, (b)
emits a warning telemetry event, and (c) dumps the flight recorder
tagged with the breaching SLO and the worst sample's trace id — resolved
from the metric's histogram exemplars (``Histogram.observe(exemplar=)``)
when it has one, else the thread's current trace context. Subsequent
ticks in the same breach stay quiet until the spec recovers (re-arm).

``tools/healthz.py`` renders the scorecard and the ops server's
``/healthz`` serves it.
"""

from __future__ import annotations

import fnmatch
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from . import flight_recorder, telemetry
from .timeseries import TimeSeriesStore

#: comparison operators an SLO may declare, longest-first for parsing
_OPS = ("<=", ">=", "==", "!=", "<", ">")


def _compare(value: float, op: str, threshold: float) -> bool:
    """True when ``value`` satisfies the objective."""
    if op == "<":
        return value < threshold
    if op == "<=":
        return value <= threshold
    if op == ">":
        return value > threshold
    if op == ">=":
        return value >= threshold
    if op == "==":
        return value == threshold
    return value != threshold


@dataclass
class SLOSpec:
    """One declarative objective over a metric pattern.

    ``metric`` is an fnmatch pattern against time-series names (so
    ``*.ack_ms_p99_ms`` covers every engine's histogram); ``kind`` is
    ``value`` (judge each sample) or ``rate`` (judge the counter's
    derived per-second rate over each window — ``flight_dump_rate == 0``
    is ``rate`` over ``flight_dump_total``). Burn thresholds are the
    fraction of window samples allowed to violate before that window is
    "burning": fast defaults strict (half the window bad), slow defaults
    lenient (a tenth) per the workbook's fast/slow pairing.
    """

    name: str
    metric: str
    op: str
    threshold: float
    kind: str = "value"            # "value" | "rate"
    fast_window_s: float = 60.0
    slow_window_s: float = 600.0
    fast_burn: float = 0.5
    slow_burn: float = 0.1
    #: samples required in the fast window before judging (a spec with
    #: one sample is opinion, not measurement)
    min_samples: int = 2

    @classmethod
    def parse(cls, text: str, name: Optional[str] = None,
              **overrides: Any) -> "SLOSpec":
        """Build a spec from ``"metric OP threshold"`` — the form the
        docs/ISSUE write SLOs in. ``true``/``false`` thresholds become
        1/0 (parity flags sample as 0/1); ``rate(counter)`` selects rate
        kind; a bare ``*_rate`` metric with no such series is sugar for
        ``rate(*_total)``."""
        for op in _OPS:
            if op in text:
                metric, _, rhs = text.partition(op)
                break
        else:
            raise ValueError(f"no comparison operator in SLO {text!r}")
        metric = metric.strip()
        rhs = rhs.strip().lower()
        threshold = {"true": 1.0, "false": 0.0}.get(rhs)
        if threshold is None:
            threshold = float(rhs)
        kind = "value"
        if metric.startswith("rate(") and metric.endswith(")"):
            metric = metric[5:-1].strip()
            kind = "rate"
        elif metric.endswith("_rate"):
            metric = metric[:-len("_rate")] + "_total"
            kind = "rate"
        return cls(name=name or text.strip(), metric=metric, op=op,
                   threshold=threshold, kind=kind, **overrides)

    # ------------------------------------------------------------ evaluation

    def _window_burn(self, store: TimeSeriesStore, name: str,
                     window_s: float, now: Optional[float]) -> Optional[dict]:
        """Violation fraction of one series over one window, or None when
        the window has too little data to judge."""
        if self.kind == "rate":
            rate = store.rate(name, window_s, now)
            if rate is None:
                return None
            bad = 0.0 if _compare(rate, self.op, self.threshold) else 1.0
            return {"frac": bad, "n": 2, "worst": rate}
        samples = store.values(name, window_s, now)
        if len(samples) < self.min_samples:
            return None
        vals = [v for _, v in samples]
        violations = [v for v in vals
                      if not _compare(v, self.op, self.threshold)]
        # "worst" = the sample farthest past the threshold; for == / !=
        # objectives any violator qualifies
        worst = max(violations, key=lambda v: abs(v - self.threshold)) \
            if violations else vals[-1]
        return {"frac": len(violations) / len(vals), "n": len(vals),
                "worst": worst}

    def evaluate(self, store: TimeSeriesStore,
                 now: Optional[float] = None) -> List[dict]:
        """Judge every series matching ``metric``: one result dict per
        series with fast/slow burn fractions and the multi-window breach
        verdict. Series with insufficient data report ``ok=True,
        judged=False`` — absence of evidence never pages."""
        matched = [n for n in store.names()
                   if fnmatch.fnmatchcase(n, self.metric)]
        out: List[dict] = []
        for name in matched:
            fast = self._window_burn(store, name, self.fast_window_s, now)
            slow = self._window_burn(store, name, self.slow_window_s, now)
            if fast is None:
                out.append({"slo": self.name, "series": name, "ok": True,
                            "judged": False})
                continue
            slow = slow or fast
            breach = fast["frac"] >= self.fast_burn \
                and slow["frac"] >= self.slow_burn
            out.append({
                "slo": self.name, "series": name, "ok": not breach,
                "judged": True, "kind": self.kind,
                "objective": f"{self.metric} {self.op} {_fmt_thresh(self.threshold)}",
                "fast_burn": round(fast["frac"], 4),
                "slow_burn": round(slow["frac"], 4),
                "worst": fast["worst"],
            })
        return out


def _fmt_thresh(v: float) -> str:
    return str(int(v)) if v == int(v) else f"{v:g}"


@dataclass
class SLOEngine:
    """Evaluates a set of specs each :meth:`check`; edge-triggers breach
    side effects (counter + telemetry + tagged flight dump)."""

    store: TimeSeriesStore
    specs: List[SLOSpec] = field(default_factory=list)
    registry: Optional[telemetry.MetricsRegistry] = None
    logger: Optional[telemetry.TelemetryLogger] = None
    recorder: Optional[flight_recorder.FlightRecorder] = None

    def __post_init__(self) -> None:
        if self.registry is None:
            self.registry = self.store.registry
        if self.logger is None:
            self.logger = telemetry.TelemetryLogger(namespace="slo")
        if self.recorder is None:
            self.recorder = flight_recorder.RECORDER
        #: (slo, series) pairs currently in breach (re-arm on recovery)
        self._breached: set = set()
        #: breach records emitted so far, newest last
        self.breaches: List[dict] = []

    # --------------------------------------------------------------- checks

    def _breach_trace(self, series: str) -> Dict[str, Optional[str]]:
        """Trace identity to tag the breach dump with: the WORST exemplar
        of the histogram behind the series when one was captured, else
        whatever trace is live on this thread (counter/gauge SLOs)."""
        hist = self.registry.find_histogram(series)
        if hist is not None and hist.worst_exemplar is not None:
            value, trace_id, span_id = hist.worst_exemplar
            return {"trace_id": trace_id, "span_id": span_id,
                    "exemplar_value_ms": value}
        from . import tracing   # late: tracing imports telemetry
        ctx = tracing.current()
        if ctx is not None:
            return {"trace_id": ctx.trace_id, "span_id": ctx.span_id}
        return {"trace_id": None}

    def check(self, now: Optional[float] = None) -> List[dict]:
        """Evaluate all specs against the store's current history and
        fire side effects for NEW breaches. Returns the new breach
        records (empty on a healthy tick). Call after ``store.tick()`` —
        the engine never samples on its own."""
        new: List[dict] = []
        for spec in self.specs:
            for result in spec.evaluate(self.store, now):
                key = (result["slo"], result["series"])
                if result["ok"]:
                    self._breached.discard(key)
                    continue
                if key in self._breached:
                    continue          # still breaching; already reported
                self._breached.add(key)
                trace = self._breach_trace(result["series"])
                record = {**result, **trace}
                self.registry.inc("slo_breach_total")
                self.logger.send_warning("slo_breach", **record)
                dump_path = self.recorder.dump(
                    f"slo:{spec.name}", extra={"slo": spec.name, **record})
                record["dump"] = dump_path
                self.breaches.append(record)
                new.append(record)
        return new

    def scorecard(self, now: Optional[float] = None) -> List[dict]:
        """Side-effect-free evaluation of every spec: the table healthz
        prints and ``/healthz`` serves (one row per matched series; specs
        matching nothing report a single unjudged row so a typo'd metric
        pattern is visible, not silently green)."""
        rows: List[dict] = []
        for spec in self.specs:
            results = spec.evaluate(self.store, now)
            if not results:
                results = [{"slo": spec.name, "series": None, "ok": True,
                            "judged": False}]
            rows.extend(results)
        return rows


def default_slos() -> List[SLOSpec]:
    """The stack's standing objectives (docs/OBSERVABILITY.md table):
    ack latency under budget, zero apply stalls, digest parity holding,
    a quiet flight recorder, and zero replica-full sheds."""
    return [
        SLOSpec.parse("ack_p99_ms < 200", name="ack_latency"),
        SLOSpec.parse("rate(*apply_stalls) == 0", name="apply_stall_rate"),
        SLOSpec.parse("digest_parity == true", name="digest_parity",
                      min_samples=1),
        SLOSpec.parse("rate(flight_dump_total) == 0",
                      name="flight_dump_rate"),
        # replica-full shedding degrades device serving silently unless
        # it pages: any nonzero shed rate is a breach
        SLOSpec.parse("rate(*replica_sheds_total) == 0",
                      name="replica_shed_rate"),
        # capacity plane (ISSUE 19): the doc-memory budget must keep
        # ≥5% headroom; the gauge reads 1.0 when no budget is set, so
        # this only pages on processes that declared one. The breach
        # dump carries the capacity census (flight-recorder dump
        # context), so forensics see WHICH docs/owners ate the budget.
        SLOSpec.parse("memory_budget_headroom > 0.05",
                      name="memory_budget_headroom"),
        # read plane (ISSUE 20): bounded staleness — a delivered window
        # or replica catch-up must land within 2s of durability at p99.
        # The gauge only moves on processes that serve readers, so
        # write-only deployments never judge it.
        SLOSpec.parse("read_staleness_p99_s < 2",
                      name="read_staleness"),
    ]


def render_scorecard(rows: List[dict]) -> str:
    """Fixed-width text table of :meth:`SLOEngine.scorecard` rows."""
    out = [f"{'SLO':<20s} {'SERIES':<44s} {'STATE':<8s} "
           f"{'FAST':>6s} {'SLOW':>6s}  WORST"]
    for r in rows:
        state = "ok" if r["ok"] else "BREACH"
        if not r.get("judged"):
            state = "no-data"
        worst = r.get("worst")
        out.append(
            f"{r['slo']:<20s} {str(r.get('series')):<44s} {state:<8s} "
            f"{r.get('fast_burn', ''):>6} {r.get('slow_burn', ''):>6}  "
            f"{'' if worst is None else worst}")
    return "\n".join(out) + "\n"
