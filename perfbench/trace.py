"""From the profiler's trace to numbers: the device's busy union, device
time by stable program and op name, and the idle gaps by what the host
was doing (the benchmark's own spans, on the same clock).

``load`` turns an ``.xplane.pb`` into plain events; everything else works
on those, so that it can be checked against a small recorded trace.
An event is (plane, line, name, start_ns, duration_ns).
"""

import glob
import os
import re

import numpy as np

DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
KERNEL = "string_merge"          # the Pallas merge's name, by variant
SPAN_NAMES = ("store.apply_planes", "door.drain", "door.build_windows",
              "door.fan_acks", "engine.prepare", "engine.sequence",
              "engine.dispatch", "engine.log")
MIN_GAP_NS = 20_000


def load(path: str):
    from jax.profiler import ProfileData
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                events.append((plane.name, line.name, ev.name,
                               int(ev.start_ns), int(ev.duration_ns)))
    return events


def stable(name: str) -> str:
    """A program's or op's short, stable name: a module without the id
    that changes from one compile to the next
    (``jit__columnar_merge_jit(1234)`` → ``jit__columnar_merge_jit``), an
    op without the HLO text the TPU's trace appends to it
    (``%copy-done.7 = s32[...] copy-done(...)`` → ``copy-done.7``)."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name)


def union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_events(events, rehearsal: bool = False, devices=None) -> dict:
    """``devices``: the ids of the chips the cell runs on; only their
    planes are read (a host's other chips are not the cell's), and one of
    them with no plane in the trace did nothing in it. Without it, every
    device plane the trace has. Times by module are means over those
    chips, ``trace.busy_s`` too, beside each chip's own (``.<i>``, the
    i-th of ``devices``) and the fullest and emptiest.

    ``rehearsal``: a CPU run has no device plane; its XLA client
    threads then stand in, dealt out over the cell's devices, so that the
    whole reduction is exercised (the numbers are never reported as a
    device's)."""
    found = sorted({p for p, *_ in events if DEVICE.match(p)})
    planes = found if devices is None else \
        [f"/device:TPU:{i}" for i in devices]
    if not found and rehearsal:
        planes = planes or ["/device:TPU:0"]
        lines = sorted({ln for _p, ln, *_ in events
                        if ln.startswith("tf_XLAPjRtCpuClient")})
        deal = {ln: planes[k % len(planes)] for k, ln in enumerate(lines)}
        events = [(deal[ln], OPS_LINE, n, s, d) if ln in deal
                  else (p, ln, n, s, d) for p, ln, n, s, d in events]
        found = planes
    if not set(planes) & set(found):
        raise ValueError(f"the trace has no device plane of {planes}: "
                         f"{found}")
    lo = min(e[3] for e in events)
    hi = max(e[3] + e[4] for e in events)
    window_ns = hi - lo
    busy_ns, raw = [], {}
    op_s, op_n, mod_s, mod_n = {}, {}, {}, {}
    gaps = []
    for p in planes:
        ops = [(s, s + d) for pl, ln, _n, s, d in events
               if pl == p and ln == OPS_LINE]
        if not ops:         # a device plane that names its lines otherwise
            ops = [(s, s + d) for pl, ln, _n, s, d in events
                   if pl == p and ln != MODULES_LINE and ln != "Steps"]
        u = union(ops)
        busy_ns.append(sum(e - s for s, e in u))
        edges = [lo] + [x for se in u for x in se] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] - edges[i] >= MIN_GAP_NS]
        for pl, ln, name, _s, d in events:
            if pl != p:
                continue
            if ln == OPS_LINE:
                k = stable(name)
                op_s[k] = op_s.get(k, 0.0) + d / 1e9
                op_n[k] = op_n.get(k, 0) + 1
            elif ln == MODULES_LINE:
                k = stable(name)
                mod_s[k] = mod_s.get(k, 0.0) + d / 1e9
                mod_n[k] = mod_n.get(k, 0) + 1
    n = len(planes)
    for k, v in mod_s.items():
        raw[f"trace.module_s.{k}"] = v / n
        raw[f"trace.module_n.{k}"] = mod_n[k] / n
    for k, v in kernel_raw(op_s, op_n).items():
        raw[k] = v / n
    raw["trace.window_s"] = window_ns / 1e9
    raw["trace.busy_s"] = sum(busy_ns) / n / 1e9
    for i, b in enumerate(busy_ns):
        raw[f"trace.busy_s.{i}"] = b / 1e9
    raw["trace.busy_s.max"] = max(busy_ns) / 1e9
    raw["trace.busy_s.min"] = min(busy_ns) / 1e9
    return {"raw": raw, "busy_s": raw["trace.busy_s"],
            "window_s": raw["trace.window_s"],
            "breakdown": {
                "device_ops": [[k, v] for k, v in sorted(
                    op_s.items(), key=lambda kv: -kv[1])[:10]],
                "idle_gaps": [[k, v / n] for k, v in
                              idle_by_span(events, gaps)[:10]]}}


def kernel_raw(op_s: dict, op_n: dict) -> dict:
    """Device time and count of the Pallas merge's ops, from the sums by
    stable op name: the plain one and the one with the zamboni fused in
    (with or without props), by the names the program gives them
    (``string_merge[_zamboni][_props]``). Both kinds where the trace has
    either, nothing where it has neither."""
    kernels = [k for k in op_s if k.startswith(KERNEL)]
    raw = {}
    for kind in ("plain", "zamboni") if kernels else ():
        mine = [k for k in kernels if ("_zamboni" in k) == (kind == "zamboni")]
        raw[f"trace.kernel_s.{kind}"] = sum(op_s[k] for k in mine)
        raw[f"trace.kernel_n.{kind}"] = sum(op_n[k] for k in mine)
    return raw


def idle_by_span(events, gaps):
    """Seconds of device idle under each benchmark span: every gap is
    shared out by how much of it each span covers (``engine.dispatch``
    less the ``store.apply_planes`` inside it), and what no span covers
    is ``_no_benchmark_span_`` (the host waiting for traffic, or in code
    the benchmark does not wrap). Spans of different threads can cover
    the same instant, so the parts may add up to more than the idle."""
    if not gaps:
        return []
    g0 = np.asarray([g[0] for g in gaps], np.float64)
    g1 = np.asarray([g[1] for g in gaps], np.float64)
    out = {}
    for name in SPAN_NAMES:
        iv = union([(s, s + d) for _p, _l, n, s, d in events if n == name])
        if not iv:
            continue
        st = np.asarray([i[0] for i in iv], np.float64)
        en = np.asarray([i[1] for i in iv], np.float64)
        cum = np.concatenate([[0.0], np.cumsum(en - st)])

        def covered(t):
            """Span time before instant t."""
            k = np.searchsorted(st, t, side="right")
            j = np.maximum(k - 1, 0)
            return np.where(k > 0, cum[j] + np.clip(t - st[j], 0.0,
                                                    en[j] - st[j]), 0.0)

        out[name] = float((covered(g1) - covered(g0)).sum()) / 1e9
    if "store.apply_planes" in out and "engine.dispatch" in out:
        out["engine.dispatch"] = max(
            out["engine.dispatch"] - out["store.apply_planes"], 0.0)
    idle = float((g1 - g0).sum()) / 1e9
    out["_no_benchmark_span_"] = max(idle - sum(out.values()), 0.0)
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])
            if v > 0]


def reduce_dir(trace_dir: str, rehearsal: bool = False,
               devices=None) -> dict:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"one .xplane.pb wanted under {trace_dir},"
                                f" found {paths}")
    return reduce_events(load(paths[0]), rehearsal, devices)
