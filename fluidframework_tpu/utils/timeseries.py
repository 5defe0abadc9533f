"""Time-series retention over the metrics registry: the health plane's memory.

Reference counterpart: the Prometheus scrape loop behind Routerlicious'
lag/latency alerting — a server is healthy not because a counter exists
but because its *trajectory* stays inside a target. PR 2 gave this stack
point-in-time metrics (``telemetry.MetricsRegistry``); this module adds
the notion of time: a :class:`TimeSeriesStore` samples
``REGISTRY.full_snapshot()`` on a clock **the caller ticks** (serving
loops, ``tools/`` drills, tests — this module itself spawns no
thread; on live servers the ``server.opsd.OpsServer`` ticker is the
clock, everywhere else determinism and zero idle cost win),
keeps a bounded ring of history per metric, derives rates from counters
(reset-aware), and answers windowed percentile reads. ``utils.slo``
evaluates burn-rate targets over it; ``tools/healthz.py`` renders it as
a sparkline dashboard from a JSONL export (``jsonl_path=``).

Sampling cost is one ``full_snapshot()`` (dict merges) plus one bounded
``deque.append`` per metric — safe to tick at phase boundaries of a hot
loop, not meant for per-op ticking.
"""

from __future__ import annotations

import json
import math
import time
from collections import deque
from typing import Any, Dict, Iterable, List, Optional, Tuple

from . import telemetry

#: unicode sparkline ramp, low→high
_SPARK = "▁▂▃▄▅▆▇█"


class TimeSeriesStore:
    """Bounded per-metric history sampled from a ``MetricsRegistry``.

    Each :meth:`tick` appends ``(t, value)`` to every metric's ring
    (``capacity`` samples kept). Booleans sample as 0/1 so parity flags
    (``digest_parity``) are SLO-able like any other series. Metrics are
    classified via ``registry.full_snapshot_kinds()``: ``counter`` series
    get :meth:`rate` derivation (monotone deltas; a reset — engine
    restart, test isolation — contributes the post-reset value, never a
    negative), everything else is read as level.
    """

    def __init__(self, registry: Optional[telemetry.MetricsRegistry] = None,
                 capacity: int = 512, jsonl_path: Optional[str] = None):
        self.registry = registry if registry is not None \
            else telemetry.REGISTRY
        self.capacity = capacity
        #: metric -> deque of (t, value), oldest first
        self.series: Dict[str, deque] = {}
        #: metric -> "counter" | "gauge" | "quantile" (from the registry;
        #: frozen at first sight so a metric's class never flips mid-run)
        self.kinds: Dict[str, str] = {}
        self.jsonl_path = jsonl_path
        self.n_ticks = 0

    # ------------------------------------------------------------- sampling

    def tick(self, now: Optional[float] = None) -> float:
        """Sample the registry once; returns the sample time. The caller
        owns the clock — pass ``now`` for deterministic tests."""
        t = time.time() if now is None else float(now)
        snap = self.registry.full_snapshot()
        for k, kind in self.registry.full_snapshot_kinds().items():
            self.kinds.setdefault(k, kind)
        clean: Dict[str, float] = {}
        for k, v in snap.items():
            if isinstance(v, bool):
                v = 1.0 if v else 0.0
            if not isinstance(v, (int, float)):
                continue
            v = float(v)
            if math.isnan(v):
                continue
            clean[k] = v
            ring = self.series.get(k)
            if ring is None:
                ring = self.series[k] = deque(maxlen=self.capacity)
            ring.append((t, v))
        self.n_ticks += 1
        if self.jsonl_path:
            with open(self.jsonl_path, "a") as f:
                f.write(json.dumps(
                    {"t": t, "metrics": {k: clean[k]
                                         for k in sorted(clean)}}) + "\n")
        return t

    def ingest_sample(self, t: float, metrics: Dict[str, float],
                      kinds: Optional[Dict[str, str]] = None) -> None:
        """Append one externally-produced sample (the JSONL re-load path
        of ``tools/healthz.py``); ``kinds`` defaults to suffix inference."""
        for k, v in metrics.items():
            if isinstance(v, bool):
                v = 1.0 if v else 0.0
            if not isinstance(v, (int, float)) or math.isnan(float(v)):
                continue
            ring = self.series.get(k)
            if ring is None:
                ring = self.series[k] = deque(maxlen=self.capacity)
            ring.append((float(t), float(v)))
            if k not in self.kinds:
                self.kinds[k] = (kinds or {}).get(k) or _infer_kind(k)
        self.n_ticks += 1

    # -------------------------------------------------------------- reading

    def names(self) -> List[str]:
        return sorted(self.series)

    def values(self, name: str, window_s: Optional[float] = None,
               now: Optional[float] = None) -> List[Tuple[float, float]]:
        """(t, value) samples, oldest first, optionally clipped to the
        trailing ``window_s`` seconds (measured from ``now`` or the
        newest sample)."""
        ring = self.series.get(name)
        if not ring:
            return []
        samples = list(ring)
        if window_s is None:
            return samples
        end = samples[-1][0] if now is None else now
        return [s for s in samples if s[0] >= end - window_s]

    def latest(self, name: str) -> Optional[float]:
        ring = self.series.get(name)
        return ring[-1][1] if ring else None

    def rate(self, name: str, window_s: Optional[float] = None,
             now: Optional[float] = None) -> Optional[float]:
        """Counter → per-second rate over the window: sum of deltas /
        elapsed. Reset-aware: a sample BELOW its predecessor means the
        counter restarted from zero (engine rebuild, registry swap), so
        that step contributes the post-reset value — never a negative
        delta that would cancel real traffic. Needs >= 2 samples; None
        otherwise or for non-counter series."""
        if self.kinds.get(name, _infer_kind(name)) != "counter":
            return None
        samples = self.values(name, window_s, now)
        if len(samples) < 2:
            return None
        elapsed = samples[-1][0] - samples[0][0]
        if elapsed <= 0:
            return None
        total = 0.0
        for (_, prev), (_, cur) in zip(samples, samples[1:]):
            total += cur - prev if cur >= prev else cur
        return total / elapsed

    def window_summary(self, name: str, window_s: Optional[float] = None,
                       now: Optional[float] = None) -> Optional[dict]:
        """p50/p99/min/max/last/n over the window (levels verbatim;
        counters summarized on their per-step deltas would lie — use
        :meth:`rate` for those)."""
        samples = self.values(name, window_s, now)
        if not samples:
            return None
        vals = sorted(v for _, v in samples)
        n = len(vals)
        return {
            "n": n,
            "min": vals[0],
            "max": vals[-1],
            "p50": vals[n // 2],
            "p99": vals[min(n - 1, int(math.ceil(n * 0.99)) - 1)],
            "last": samples[-1][1],
        }

    # -------------------------------------------------------------- export

    def export_jsonl(self, path: str) -> int:
        """Write the whole retained history: one line per tick-time, the
        union of every metric's sample at that time. Returns the line
        count. (The incremental form is ``jsonl_path=`` at construction —
        one append per tick.)"""
        by_t: Dict[float, Dict[str, float]] = {}
        for name, ring in self.series.items():
            for t, v in ring:
                by_t.setdefault(t, {})[name] = v
        with open(path, "w") as f:
            for t in sorted(by_t):
                f.write(json.dumps(
                    {"t": t, "metrics": {k: by_t[t][k]
                                         for k in sorted(by_t[t])}}) + "\n")
        return len(by_t)

    @classmethod
    def from_jsonl(cls, path_or_lines: Any,
                   capacity: int = 512) -> "TimeSeriesStore":
        """Rebuild a store from an export (path or iterable of lines) —
        the offline half of ``tools/healthz.py``. Tolerates a torn tail
        the way every JSONL reader in this stack does."""
        store = cls(registry=telemetry.MetricsRegistry(), capacity=capacity)
        if isinstance(path_or_lines, str):
            with open(path_or_lines) as f:
                lines: Iterable[str] = f.readlines()
        else:
            lines = path_or_lines
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                break
            if isinstance(rec, dict) and "metrics" in rec:
                store.ingest_sample(rec.get("t", 0.0), rec["metrics"])
        return store

    # ------------------------------------------------------------ dashboard

    def render_sparklines(self, names: Optional[List[str]] = None,
                          width: int = 24, active_only: bool = True,
                          window_s: Optional[float] = None) -> str:
        """The text dashboard: one line per metric — sparkline of the
        last ``width`` samples, the latest value, and the derived rate
        for counters. ``active_only`` hides all-zero flat series (a full
        snapshot carries hundreds; the dashboard is for the ones that
        moved). Sorted by name; counters render their per-step deltas so
        a steadily-increasing total doesn't read as a ramp forever."""
        out: List[str] = []
        for name in (names if names is not None else self.names()):
            samples = self.values(name, window_s)
            if not samples:
                continue
            vals = [v for _, v in samples]
            kind = self.kinds.get(name, _infer_kind(name))
            if kind == "counter":
                deltas = [cur if cur < prev else cur - prev
                          for prev, cur in zip(vals, vals[1:])]
                plot = deltas if deltas else vals
            else:
                plot = vals
            if active_only and all(v == 0 for v in vals):
                continue
            tail = plot[-width:] if plot else [0.0]
            lo, hi = min(tail), max(tail)
            span = hi - lo
            marks = "".join(
                _SPARK[0] if span == 0 else
                _SPARK[min(len(_SPARK) - 1,
                           int((v - lo) / span * (len(_SPARK) - 1)))]
                for v in tail)
            line = f"{name:<48s} {marks:<{width}s} last={_fmt(vals[-1])}"
            r = self.rate(name, window_s)
            if r is not None:
                line += f" rate={_fmt(r)}/s"
            out.append(line)
        if not out:
            return "(no active series)\n"
        return "\n".join(out) + "\n"


def _infer_kind(name: str) -> str:
    """Suffix-based kind inference for series with no registry to ask
    (JSONL re-loads): the registry's naming conventions are stable enough
    to classify by shape."""
    if name.endswith(("_p50_ms", "_p99_ms")):
        return "quantile"
    if name.endswith(("_total", "_count", "_overflow")) or name.endswith(
            ("ops_ingested", "ops_applied", "ops_flushed", "flushes",
             "nacks", "appends", "compactions")):
        return "counter"
    return "gauge"


def _fmt(v: float) -> str:
    if v != v or v in (float("inf"), float("-inf")):
        return str(v)
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return f"{v:.3g}"
