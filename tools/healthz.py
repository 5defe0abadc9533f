#!/usr/bin/env python
"""Text health dashboard: JSONL exports or a live ops endpoint.

The operator-facing face of the health plane (ISSUE 4, live mode ISSUE
17): a serving loop ticking a ``TimeSeriesStore`` with
``jsonl_path=`` leaves a JSONL trail of metric samples; this tool
re-loads it and renders the two things an operator checks first:

- ``render_sparklines()`` — one line per active metric, recent shape +
  latest value + derived rate for counters;
- the SLO scorecard — every standing objective (``utils.slo.
  default_slos()`` plus any ``--slo "metric < threshold"`` extras)
  judged over the export's history with fast/slow burn windows.

With ``--url`` the same dashboard renders against a RUNNING server's
operations plane (``server.opsd.OpsServer``): ``/metrics`` is polled at
``--interval`` for ``--polls`` rounds to build the sparkline history,
and the scorecard comes from the server's own ``/healthz`` (its
SLOEngine has the full in-process history, not just our polls).

Usage::

    python tools/healthz.py health.jsonl              # dashboard + SLOs
    python tools/healthz.py health.jsonl --names '*shard*'
    python tools/healthz.py --demo                    # synthetic sample
    python tools/healthz.py h.jsonl --slo "ops_ingested_rate > 100"
    python tools/healthz.py --url http://127.0.0.1:9321 \
        --interval 1 --polls 10                       # live server
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import os
import re
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from fluidframework_tpu.utils import slo as slo_mod          # noqa: E402
from fluidframework_tpu.utils import telemetry, timeseries   # noqa: E402

#: one exposition sample line: name, optional {labels}, value
_PROM_LINE = re.compile(
    r'^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?\s+([^\s]+)$')
#: one label pair inside the braces, value with text-format escapes
_PROM_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def _unescape(v: str) -> str:
    return (v.replace(r"\n", "\n").replace(r'\"', '"')
            .replace(r"\\", "\\"))


def parse_prometheus(text: str):
    """Parse a ``render_prometheus`` exposition back into the flat
    ``full_snapshot``-style key space: top-level samples keep their
    name, component-labeled samples become ``component.name`` (or
    ``component{k=v,...}.name`` with extra labels — the registry's
    component-key scheme). Histogram ``_bucket`` lines are skipped
    (the ``_sum``/``_count`` pair carries the trend). Returns
    ``(metrics, kinds)``."""
    metrics, kinds, types = {}, {}, {}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) == 4:
                types[parts[2]] = parts[3]
            continue
        if not line or line.startswith("#"):
            continue
        m = _PROM_LINE.match(line)
        if m is None:
            continue
        name, rawlabels, rawvalue = m.groups()
        if name.endswith("_bucket"):
            continue
        try:
            value = float(rawvalue)
        except ValueError:
            continue
        labels = {k: _unescape(v)
                  for k, v in _PROM_LABEL.findall(rawlabels or "")}
        comp = labels.pop("component", None)
        key = name
        if comp is not None:
            if labels:
                inner = ",".join(f"{k}={labels[k]}"
                                 for k in sorted(labels))
                key = f"{comp}{{{inner}}}.{name}"
            else:
                key = f"{comp}.{name}"
        metrics[key] = value
        typ = types.get(name)
        if typ is None and (name.endswith("_sum")
                            or name.endswith("_count")):
            base = name.rsplit("_", 1)[0]
            if types.get(base) == "histogram":
                typ = "counter"   # cumulative histogram accumulators
        if typ in ("counter", "gauge"):
            kinds[key] = typ
        elif typ == "histogram":
            kinds[key] = "counter"
    return metrics, kinds


def _fetch(url: str, timeout: float = 5.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read()


def _demo_store() -> timeseries.TimeSeriesStore:
    """A synthetic ramp so the dashboard can be seen without a bench
    run: a counter ramping up, a latency gauge breaching its SLO."""
    reg = telemetry.MetricsRegistry()
    store = timeseries.TimeSeriesStore(registry=reg)
    for i in range(32):
        reg.inc("ops_ingested", 100 + 10 * i)
        reg.set_gauge("ack_p99_ms", 40 + (0 if i < 24 else 60 * (i - 23)))
        reg.set_gauge("digest_parity", 1.0)
        store.tick(now=float(i))
    return store


def _live_store(base_url: str, interval_s: float, polls: int
                ) -> timeseries.TimeSeriesStore:
    """Build sparkline history by polling a live ``/metrics`` endpoint."""
    store = timeseries.TimeSeriesStore(
        registry=telemetry.MetricsRegistry())
    for i in range(max(1, polls)):
        if i:
            time.sleep(interval_s)
        text = _fetch(base_url + "/metrics").decode("utf-8")
        metrics, kinds = parse_prometheus(text)
        store.ingest_sample(time.time(), metrics, kinds=kinds)
    return store


def _fmt_bytes(n) -> str:
    if n is None:
        return "?"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}GiB"


def render_capacity(census=None, store=None) -> str:
    """Capacity panel (ISSUE 19). Live mode renders the full
    ``/debug/memory`` census (host/device split, headroom, heaviest +
    coldest docs); file/demo mode reconstructs the headline from the
    capacity gauges present in the metric store. Returns "" when the
    export predates the capacity plane."""
    lines = []
    if census is not None and "error" not in census:
        host = census.get("host", {})
        dev = census.get("device", {})
        docs = census.get("docs", {})
        idle = census.get("idle", {})
        lines.append("capacity")
        lines.append(
            f"  host {_fmt_bytes(host.get('total_bytes'))}"
            f"  device {_fmt_bytes(dev.get('total_bytes'))}"
            f"  docs {docs.get('resident', 0)}"
            f"  headroom {census.get('headroom', 1.0):.2f}"
            + (f"  budget {_fmt_bytes(census['budget_bytes'])}"
               if census.get("budget_bytes") else ""))
        by_owner = host.get("by_owner", {})
        for owner in sorted(by_owner, key=by_owner.get, reverse=True)[:6]:
            lines.append(f"    {owner:<32s} {_fmt_bytes(by_owner[owner])}")
        for heavy in (census.get("top", {}).get("heaviest") or [])[:4]:
            lines.append(f"  heavy {heavy.get('doc')}: "
                         f"{_fmt_bytes(heavy.get('bytes'))}")
        for cold in (census.get("top", {}).get("coldest") or [])[:4]:
            lines.append(f"  cold  {cold.get('doc', cold.get('row'))}: "
                         f"idle {cold.get('idle_s', 0):.1f}s")
        for owner, snap in sorted(idle.items()):
            p99 = snap.get("idle_p99_s")
            if p99 is not None:
                lines.append(f"  idle[{owner}] "
                             f"p50 {snap.get('idle_p50_s', 0):.1f}s"
                             f"  p99 {p99:.1f}s"
                             f"  max {snap.get('idle_max_s', 0):.1f}s")
    elif store is not None:
        vals = {n: store.latest(n)
                for n in ("doc_resident_bytes", "device_buffer_bytes",
                          "resident_docs_total", "memory_budget_headroom",
                          "doc_memory_budget_bytes")}
        if any(v is not None for v in vals.values()):
            lines.append("capacity")
            lines.append(
                f"  host {_fmt_bytes(vals['doc_resident_bytes'])}"
                f"  device {_fmt_bytes(vals['device_buffer_bytes'])}"
                f"  docs {int(vals['resident_docs_total'] or 0)}"
                f"  headroom "
                f"{(vals['memory_budget_headroom'] or 1.0):.2f}"
                + (f"  budget "
                   f"{_fmt_bytes(vals['doc_memory_budget_bytes'])}"
                   if vals["doc_memory_budget_bytes"] else ""))
    return "\n".join(lines) + ("\n" if lines else "")


def render_readers(census=None, store=None) -> str:
    """Readers panel (ISSUE 20). Live mode renders ``/debug/readers``
    (subscriber count, worst window lag, shed/park totals, staleness
    p99, the laggiest subscriber rows); file/demo mode reconstructs the
    headline from the read-plane gauges/counters in the metric store.
    Returns "" when the export predates the read plane."""
    lines = []
    if census is not None and "error" not in census:
        rows = census.get("readers") or []
        if census.get("subscribers") or rows:
            lines.append("readers")
            lines.append(
                f"  subscribers {census.get('subscribers', 0)}"
                f"  worst-lag {census.get('worst_lag_windows', 0)}w"
                f"  sheds {census.get('sheds', 0)}"
                f"  parked {census.get('parked', 0)}"
                f"  staleness-p99 "
                f"{census.get('staleness_p99_s', 0.0):.3f}s")
            laggy = sorted((r for r in rows if "sid" in r),
                           key=lambda r: r.get("lag_windows", 0),
                           reverse=True)
            for r in laggy[:6]:
                lines.append(
                    f"    {r.get('name', '?'):<24s}"
                    f" lag {r.get('lag_windows', 0)}w"
                    f"  ops {r.get('delivered_ops', 0)}"
                    f"  sheds {r.get('sheds', 0)}"
                    + ("  PARKED" if r.get("parked") else ""))
    elif store is not None:
        vals = {n: store.latest(n)
                for n in ("observer_subscribers",
                          "observer_delivery_ops_per_sec",
                          "read_staleness_p99_s",
                          "observer_sheds_total",
                          "read_windows_total")}
        if any(v is not None for v in vals.values()):
            lines.append("readers")
            lines.append(
                f"  subscribers {int(vals['observer_subscribers'] or 0)}"
                f"  delivery "
                f"{(vals['observer_delivery_ops_per_sec'] or 0.0):.0f}"
                f" ops/s"
                f"  windows {int(vals['read_windows_total'] or 0)}"
                f"  sheds {int(vals['observer_sheds_total'] or 0)}"
                f"  staleness-p99 "
                f"{(vals['read_staleness_p99_s'] or 0.0):.3f}s")
    return "\n".join(lines) + ("\n" if lines else "")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("jsonl", nargs="?", help="TimeSeriesStore export")
    ap.add_argument("--demo", action="store_true",
                    help="render a synthetic store instead of a file")
    ap.add_argument("--url", default=None, metavar="http://host:port",
                    help="poll a live ops endpoint instead of a file")
    ap.add_argument("--interval", type=float, default=1.0,
                    help="seconds between live polls (with --url)")
    ap.add_argument("--polls", type=int, default=10,
                    help="number of live polls to sample (with --url)")
    ap.add_argument("--names", default=None,
                    help="fnmatch filter on metric names")
    ap.add_argument("--width", type=int, default=24)
    ap.add_argument("--all", action="store_true",
                    help="include all-zero flat series")
    ap.add_argument("--slo", action="append", default=[],
                    metavar="SPEC",
                    help='extra SLO, e.g. "ack_p99_ms < 200" (repeatable)')
    ap.add_argument("--no-slo", action="store_true",
                    help="skip the SLO scorecard")
    args = ap.parse_args(argv)

    live_rows = None
    if args.url:
        base = args.url.rstrip("/")
        store = _live_store(base, args.interval, args.polls)
        if not args.no_slo:
            try:
                live_rows = json.loads(
                    _fetch(base + "/healthz")).get("rows") or []
            except (OSError, ValueError):
                live_rows = []
    elif args.demo:
        store = _demo_store()
    elif args.jsonl:
        store = timeseries.TimeSeriesStore.from_jsonl(args.jsonl)
    else:
        ap.error("a JSONL path, --demo, or --url is required")
    names = None
    if args.names:
        names = [n for n in store.names()
                 if fnmatch.fnmatchcase(n, args.names)]
    print(store.render_sparklines(names=names, width=args.width,
                                  active_only=not args.all), end="")
    census = None
    if args.url:
        try:
            census = json.loads(_fetch(base + "/debug/memory"))
        except (OSError, ValueError):
            census = None
    panel = render_capacity(census=census, store=store)
    if panel:
        print()
        print(panel, end="")
    readers = None
    if args.url:
        try:
            readers = json.loads(_fetch(base + "/debug/readers"))
        except (OSError, ValueError):
            readers = None
    panel = render_readers(census=readers, store=store)
    if panel:
        print()
        print(panel, end="")
    if args.no_slo:
        return 0
    if args.url:
        # the server's own scorecard: its SLOEngine judged the full
        # in-process history, not just the handful of polls we took
        rows = live_rows
    else:
        specs = slo_mod.default_slos() + [slo_mod.SLOSpec.parse(s)
                                          for s in args.slo]
        engine = slo_mod.SLOEngine(store, specs=specs,
                                   registry=store.registry)
        rows = engine.scorecard()
    print()
    print(slo_mod.render_scorecard(rows), end="")
    # the dashboard reports; only an explicitly breaching scorecard row
    # fails the invocation (operators pipe this into CI gates)
    return 1 if any(not r["ok"] for r in rows) else 0


if __name__ == "__main__":
    raise SystemExit(main())
