"""What the program's own window records give a traced run: the span
table as counters, the window's records, the idle gaps shared out over the
program's spans and waits, and the long ones.

    python3 -m perfbench.progtrace --workload <cell> --seed <n> --seconds <s>

is ``run.py``'s traced run of the cell, by the accepted harness as it
stands, with the program's readings taken beside it from outside
(``attached``): the result gains the metrics of ``progtrace_per_layer.json``
(each read by its file under ``metrics/``) and ``program_spans``. The
benchmark's own command does not come here, and ``BENCHMARK.json`` names
none of those metrics: a harness that called the readers itself would be
an edit to ``harness.py``, which is a ``benchmark`` PR's to make.

The program (``fluidframework_tpu.utils.tracing``) keeps one record per
door window: work is stamped and entered as ``TraceAnnotation("fluid.<name>",
wid=...)``, so it lies in the profiler's trace beside the device's ops;
waits are stamped only, in ``time.perf_counter()``. Two
``fluid.clock`` annotations, one at each end of the traced slice, carry
that clock's reading, and map the stamps onto the trace's clock.

Every reader here returns nothing, and does not raise, on a program that
has no such table, records or marks (the parent of the PR that added
them): the metrics that read them are then left out of the line.
"""

import contextlib
import glob
import json
import os
import threading
import time

import numpy as np

from . import trace
from .traffic import HERE, select_metrics

PREFIX = "fluid."
CLOCK = PREFIX + "clock"
UNATTRIBUTED = "_unattributed_"


def _tracing():
    from fluidframework_tpu.utils import tracing
    return tracing if hasattr(tracing, "SPAN_TABLE") else None


def counters() -> dict:
    """The span table as flat ``prog.<span>.s/.n/.long_s/.long_n``: the
    key set is fixed at the program's import, so the harness's difference
    across the window reads every one."""
    tr = _tracing()
    if tr is None:
        return {}
    return {"prog." + k: v for k, v in tr.SPAN_TABLE.counters.items()}


def clock_mark() -> None:
    tr = _tracing()
    if tr is not None:
        tr.clock_mark()


def records(closed=None) -> list:
    """The newest closed window records, each with its drain pass's;
    ``closed``: those alone whose acks were fanned in [start, end) on
    ``time.perf_counter()``'s clock, which are the table's ``window`` row
    over that time."""
    tr = _tracing()
    recs = list(tr.RECENT) if tr is not None else []
    if closed is not None:
        recs = [r for r in recs if closed[0] <= r["t_ack"] < closed[1]]
    return recs


def stamps(recs):
    """Every (name, start, end) of the records: each window's, and each
    drain pass's once, whatever the number of its windows."""
    seen = set()
    for r in recs:
        yield from r["spans"]
        p = r.get("pass") or {}
        if id(p) not in seen:
            seen.add(id(p))
            yield from p.get("spans", [])


def span_stats(recs) -> dict:
    """Per span and wait over the records: [n, mean, p50, p99, max] in
    ms."""
    by = {"window": [(r["t_ack"] - r["t_rx"]) * 1e3 for r in recs]}
    for name, a, b in stamps(recs):
        by.setdefault(name, []).append((b - a) * 1e3)
    return {n: [len(v), float(np.mean(v)), float(np.percentile(v, 50)),
                float(np.percentile(v, 99)), float(np.max(v))]
            for n, v in sorted(by.items())}


def table(raw: dict, prefix: str = "d.prog.") -> dict:
    """The span table's rows that moved across the window, from the
    harness's differences: name → [s, n, long_s, long_n]. With
    ``prefix="d.span."``, the benchmark's own outside spans in the same
    form, to hold the program's against."""
    rows = {}
    for k, v in raw.items():
        if k.startswith(prefix):
            name, field = k[len(prefix):].rsplit(".", 1)
            rows.setdefault(name, {})[field] = v
    return {n: [r.get("s", 0.0), r.get("n", 0), r.get("long_s", 0.0),
                r.get("long_n", 0)] for n, r in sorted(rows.items())
            if r.get("n")}


def dump(path: str, recs: list) -> None:
    """One JSON line per window record (stamps in perf_counter seconds)."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r, default=float) + "\n")


# ------------------------------------------------------------ the trace

def load(path: str):
    """``(events, marks)``: the device's ops and the program's annotated
    spans as ``trace.py``'s events with one more field, the span's ``wid``
    (-1 for a drain pass's spans, None for what is not the program's),
    and the clock marks as (trace ns, perf_counter ns)."""
    from jax.profiler import ProfileData
    events, marks = [], []
    for plane in ProfileData.from_file(path).planes:
        device = bool(trace.DEVICE.match(plane.name))
        for line in plane.lines:
            cpu_ops = line.name.startswith("tf_XLAPjRtCpuClient")
            for ev in line.events:
                name = ev.name
                if name == CLOCK:
                    marks.append((int(ev.start_ns), int(dict(ev.stats)[
                        "perf_counter_ns"])))
                elif name.startswith(PREFIX):
                    events.append((plane.name, line.name, name[len(PREFIX):],
                                   int(ev.start_ns), int(ev.duration_ns),
                                   int(dict(ev.stats).get("wid", -1))))
                elif device or cpu_ops:
                    events.append((plane.name, line.name, name,
                                   int(ev.start_ns), int(ev.duration_ns),
                                   None))
    return events, sorted(marks)


def clock_map(marks):
    """``(perf ns, trace ns, scale)`` from the first and the last mark:
    ``trace = t + (perf - p) * scale``; and the drift between the two
    clocks over the slice in microseconds (the profiler's host clock may
    be slewed). Nothing without two marks."""
    if len(marks) < 2:
        return None, None
    (x0, p0), (x1, p1) = marks[0], marks[-1]
    scale = (x1 - x0) / (p1 - p0)
    return (p0, x0, scale), ((x1 - x0) - (p1 - p0)) / 1e3


def to_trace_ns(offset, perf_s):
    p0, x0, scale = offset
    return x0 + (np.asarray(perf_s, np.float64) * 1e9 - p0) * scale


def device_gaps(events, rehearsal: bool = False):
    """Idle gaps of the (first) device plane and the slice's bounds, as
    ``trace.reduce_events`` finds them; a rehearsal's CPU client threads
    stand in for the device."""
    planes = sorted({e[0] for e in events if trace.DEVICE.match(e[0])})
    if planes:
        ops = [(e[3], e[3] + e[4]) for e in events
               if e[0] == planes[0] and e[1] == trace.OPS_LINE]
    elif rehearsal:
        ops = [(e[3], e[3] + e[4]) for e in events
               if e[1].startswith("tf_XLAPjRtCpuClient")]
    else:
        ops = []
    if not ops:
        return [], 0.0
    lo = min(e[3] for e in events)
    hi = max(e[3] + e[4] for e in events)
    u = trace.union(ops)
    edges = [lo] + [x for se in u for x in se] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] >= trace.MIN_GAP_NS]
    return gaps, (hi - lo) / 1e9


def _covered(iv, g0, g1):
    """Seconds of each gap [g0, g1) that the merged intervals cover."""
    if not iv:
        return np.zeros_like(g0)
    st = np.asarray([i[0] for i in iv], np.float64)
    en = np.asarray([i[1] for i in iv], np.float64)
    cum = np.concatenate([[0.0], np.cumsum(en - st)])

    def before(t):
        k = np.searchsorted(st, t, side="right")
        j = np.maximum(k - 1, 0)
        return np.where(k > 0, cum[j] + np.clip(t - st[j], 0.0,
                                                en[j] - st[j]), 0.0)

    return (before(g1) - before(g0)) / 1e9


def _minus(iv, holes):
    """Merged intervals ``iv`` less the merged intervals ``holes``."""
    out, j = [], 0
    for s, e in iv:
        while j < len(holes) and holes[j][1] <= s:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < e:
            if holes[k][0] > s:
                out.append([s, holes[k][0]])
            s = max(s, holes[k][1])
            k += 1
        if s < e:
            out.append([s, e])
    return out


def idle_by_program_span(events, recs, offset, gaps, parents: dict,
                         waits=()):
    """Seconds of device idle under each of the program's spans and
    waits. A span's share is its own time: what its children (``parents``:
    child → parent) do not cover, under ``<name>._self`` where it has
    any; a leaf's is the whole span. Work comes from the trace's
    ``fluid.*`` events, waits from the records' stamps through
    ``offset``. Threads overlap, so the parts may add up to more than the
    idle; ``_unattributed_`` is exact: the idle that nothing covers."""
    if not gaps:
        return []
    g0 = np.asarray([g[0] for g in gaps], np.float64)
    g1 = np.asarray([g[1] for g in gaps], np.float64)
    by_name = {}
    for e in events:
        if e[5] is not None:
            by_name.setdefault(e[2], []).append((e[3], e[3] + e[4]))
    if offset is not None:
        for name, a, b in stamps(recs):
            if name in waits:
                a, b = to_trace_ns(offset, [a, b])
                by_name.setdefault(name, []).append((a, b))
    merged = {n: trace.union(iv) for n, iv in by_name.items()}
    out = {}
    for name, iv in merged.items():
        kids = trace.union([x for c, par in parents.items() if par == name
                            for x in merged.get(c, [])])
        own = _minus(iv, kids) if kids else iv
        v = float(_covered(own, g0, g1).sum())
        if v > 0:
            out[name + "._self" if kids else name] = v
    everything = trace.union([x for iv in merged.values() for x in iv])
    idle = float((g1 - g0).sum()) / 1e9
    out[UNATTRIBUTED] = max(
        idle - float(_covered(everything, g0, g1).sum()), 0.0)
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])]


def long_spans(events, recs, offset, long_s: float, waits=()):
    """Spans of ``long_s`` or more: the annotated ones by the trace's
    line (the thread's OS name) and span name, the waits by name, each as
    [count, seconds]; and the slowest windows that held one."""
    by_line, by_wait = {}, {}
    for _p, line, name, _s, d, wid in events:
        if wid is not None and d >= long_s * 1e9:
            n = by_line.setdefault(line, {}).setdefault(name, [0, 0.0])
            n[0] += 1
            n[1] += d / 1e9
    held = []
    for r in recs:
        for name, a, b in r["spans"]:
            if name in waits and b - a >= long_s:
                n = by_wait.setdefault(name, [0, 0.0])
                n[0] += 1
                n[1] += b - a
        names = r.get("long", []) + (r.get("pass") or {}).get("long", [])
        if names:
            held.append({"wid": r.get("wid"),
                         "rx_to_ack_ms": (r["t_ack"] - r["t_rx"]) * 1e3,
                         "long": names})
    held.sort(key=lambda h: -h["rx_to_ack_ms"])
    return {"by_line": by_line, "waits": by_wait,
            "windows": len(held), "slowest": held[:5]}


def stamp_error_us(events, recs, offset):
    """How far the records' stamps of annotated spans, mapped through the
    clock marks, lie from the same spans' events in the trace (start
    against start): median and largest, over every span of every window
    whose events the trace holds."""
    if offset is None:
        return None
    seen = {}
    for e in events:
        if e[5] is not None and e[5] >= 0:
            seen.setdefault((e[5], e[2]), []).append(e[3])
    errs = []
    for r in recs:
        mine = {}
        for name, a, _b in r["spans"]:
            mine.setdefault(name, []).append(a)
        for name, starts in mine.items():
            theirs = sorted(seen.get((r.get("wid", -1), name), []))
            if len(theirs) == len(starts):
                errs += list(np.abs(to_trace_ns(offset, sorted(starts))
                                    - np.asarray(theirs)) / 1e3)
    if not errs:
        return None
    return {"n": len(errs), "p50": float(np.median(errs)),
            "max": float(np.max(errs))}


def reduce_dir(trace_dir: str, out_dir: str, closed,
               rehearsal: bool = False):
    """The traced slice read once more for what the program put there:
    the result's ``program_spans``. ``closed``: the measured window's two
    ends on ``time.perf_counter()``'s clock; the records of the windows
    closed between them go to ``out_dir/windows.jsonl``, and ``long`` and
    ``spans`` are theirs."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = {"idle_gaps": [], "long": {}, "clock_drift_us": None}
    tr = _tracing()
    if len(paths) != 1 or tr is None:
        return out
    events, marks = load(paths[0])
    recs = records(closed)
    dump(os.path.join(out_dir, "windows.jsonl"), recs)
    offset, drift = clock_map(marks)
    gaps, _window_s = device_gaps(events, rehearsal)
    out["idle_gaps"] = idle_by_program_span(
        events, recs, offset, gaps, tr.PARENTS, tr.WAITS)
    out["long"] = long_spans(events, recs, offset, tr.LONG_S, tr.WAITS)
    out["spans"] = span_stats(recs)
    out["clock_drift_us"] = drift
    out["stamp_error_us"] = stamp_error_us(events, recs, offset)
    return out


# ------------------------------------- the harness's run, read from outside

def per_layer(bench: dict, cell: str) -> list:
    """The entries of ``progtrace_per_layer.json`` that the cell reports,
    by ``BENCHMARK.json``'s own rule."""
    with open(os.path.join(HERE, "progtrace_per_layer.json")) as f:
        return select_metrics(dict(bench, per_layer=json.load(f)), cell)[1]


@contextlib.contextmanager
def attached(harness, seen: dict):
    """While inside, a traced ``harness.run_cell`` also takes the program's
    readings: the span table and the harness's own outside spans at the
    measured window's two ends (when the harness tells the generator of
    them), a clock mark inside each end of the profile, and, where the
    harness reduces the trace, the program's part of it; the span table's
    differences join the harness's ``raw`` before the metric files are read.
    ``seen["program_spans"]`` is the result's new key. Three names of the
    harness and two of ``jax.profiler`` are wrapped, as ``_instrument``
    wraps the program's, and put back on the way out."""
    import jax
    send, instrument = harness.GenProc.send, harness._instrument
    reduce_trace = harness.trace.reduce_dir
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
    ends = []

    def snapshot():
        sp = seen.get("spans")
        ends.append((time.perf_counter(), counters(),
                     dict(sp.s) if sp else {}, dict(sp.n) if sp else {}))

    def at_both_ends(t0, t1):
        for t in (t0, t1):
            time.sleep(max(t - harness.now(), 0))
            snapshot()

    def spying_send(self, **cmd):
        if cmd.get("cmd") == "window":
            seen["timer"] = threading.Thread(
                target=at_both_ends, args=(cmd["t0"], cmd["t1"]), daemon=True)
            seen["timer"].start()
        send(self, **cmd)

    def keeping_instrument(*a, **k):
        seen["spans"] = instrument(*a, **k)
        return seen["spans"]

    def marked_start(*a, **k):
        start(*a, **k)
        clock_mark()

    def marked_stop(*a, **k):
        clock_mark()
        stop(*a, **k)

    def reduce_both(tr_dir, rehearsal=False, devices=None):
        red = reduce_trace(tr_dir, rehearsal=rehearsal, devices=devices)
        seen["timer"].join(5.0)
        (p0, c0, s0, n0), (p1, c1, s1, n1) = ends
        raw = {f"d.{k}": c1[k] - c0[k] for k in c1}
        outside = {f"d.span.{k}.s": s1[k] - s0.get(k, 0.0) for k in s1}
        outside.update({f"d.span.{k}.n": n1[k] - n0.get(k, 0) for k in n1})
        prog = reduce_dir(tr_dir, os.path.dirname(tr_dir), (p0, p1),
                          rehearsal)
        seen["program_spans"] = dict(
            prog, table=table(raw), outside=table(outside, "d.span."))
        red["raw"].update(raw)
        return red

    harness.GenProc.send, harness._instrument = spying_send, keeping_instrument
    harness.trace.reduce_dir = reduce_both
    jax.profiler.start_trace, jax.profiler.stop_trace = marked_start, marked_stop
    try:
        yield seen
    finally:
        harness.GenProc.send, harness._instrument = send, instrument
        harness.trace.reduce_dir = reduce_trace
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop


def run_cell(cell: dict, config: dict, traffic: dict, files: dict, *,
             bench: dict, like: str, **kw) -> dict:
    """``harness.run_cell``, traced, with the program's readings: the
    metrics of ``progtrace_per_layer.json`` for the cell ``like`` beside
    the cell's own, and ``program_spans`` set before ``compared``."""
    from . import harness
    end_to_end, layer = select_metrics(bench, like)
    with attached(harness, {}) as seen:
        result = harness.run_cell(
            cell, config, traffic, files, trace_on=True,
            end_to_end=end_to_end, per_layer=layer + per_layer(bench, like),
            **kw)
    compared = result.pop("compared")
    result["program_spans"] = seen.get("program_spans")
    result["compared"] = compared
    return result


def main() -> int:
    import argparse
    from .traffic import load_json
    t_start = time.monotonic()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args()
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == a.workload)
    result = run_cell(
        cell, load_json("configs", cell["config"]),
        load_json("traffic", cell["traffic"]),
        {"config": os.path.join(HERE, "configs", cell["config"] + ".json"),
         "traffic": os.path.join(HERE, "traffic", cell["traffic"] + ".json")},
        bench=bench, like=a.workload, seed=a.seed, seconds=a.seconds,
        t_start=t_start)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
