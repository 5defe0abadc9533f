"""End-to-end op tracing: per-batch span trees across the whole stack.

Reference counterpart: the distributed-tracing discipline behind the
reference service's correlation ids (Alfred stamps a correlation id per
socket message; every lambda logs against it) — here grown into real
spans: a :class:`TraceContext` (trace id + span id) is attached to an op
batch at the client outbox, rides the wire (op frames / raw-log records /
``SequencedDocumentMessage.trace``) through ingress, Deli sequencing,
serving apply, and the broadcast ack, and every layer opens a host-timed
span (built on ``telemetry.PerformanceEvent``) under its parent. The
result is a per-batch span tree — outbox → wire → deli → apply → ack —
exportable as Chrome trace-event JSON (``chrome://tracing`` / Perfetto)
and renderable as text by ``tools.trace_viewer``.

Spans are recorded into a process-wide bounded ring (:data:`TRACER`);
within a process, parentage flows implicitly through a thread-local
context stack, so nested layers need no plumbing; across process/socket
hops the context is serialized with :meth:`TraceContext.to_wire` (a
2-key dict) and re-attached with :func:`attach` on the far side.

Span start/end events also flow through the tracer's
:class:`~fluidframework_tpu.utils.telemetry.TelemetryLogger`, which means
they land in the crash flight recorder — a dump shows the spans in
flight when a faultpoint fired.
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, List, Optional

from .telemetry import REGISTRY, MetricsRegistry, PerformanceEvent, \
    TelemetryLogger

#: every stamp in this module is ``time.perf_counter()``: the clock the
#: window timeline, the executor and the engine's stages already use.
#: The wall-clock anchor is taken once, here, and only ``chrome_event``
#: adds it (Chrome/Perfetto want epoch microseconds).
_EPOCH_US = (time.time() - time.perf_counter()) * 1e6


class TraceContext:
    """One node of a span tree: (trace_id, span_id). Serializes to a
    2-key dict for wire frames and log records."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: int):
        self.trace_id = trace_id
        self.span_id = span_id

    def to_wire(self) -> dict:
        return {"tid": self.trace_id, "sid": self.span_id}

    @staticmethod
    def from_wire(d: Any) -> Optional["TraceContext"]:
        if isinstance(d, dict) and "tid" in d and "sid" in d:
            return TraceContext(d["tid"], d["sid"])
        return None

    def __repr__(self) -> str:
        return f"TraceContext({self.trace_id}, {self.span_id})"


class Span:
    """A timed span, used as a context manager. While entered, it is the
    thread's current context: child spans and ``current_wire()`` parent
    to it. Timing is delegated to ``PerformanceEvent`` (the span emits
    the reference ``_start``/``_end``/``_cancel`` telemetry events)."""

    def __init__(self, tracer: "Tracer", name: str, ctx: TraceContext,
                 parent_id: Optional[int], args: Dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.ctx = ctx
        self.parent_id = parent_id
        self.args = args
        self._pe = PerformanceEvent(
            tracer.logger, name,
            {"trace_id": ctx.trace_id, "span_id": ctx.span_id})
        self._ts_us: Optional[float] = None

    def annotate(self, **args: Any) -> "Span":
        """Attach args after entry (device-dispatch counters measured
        inside the span)."""
        self.args.update(args)
        return self

    def __enter__(self) -> "Span":
        self._ts_us = time.perf_counter() * 1e6
        self._pe.__enter__()
        self.tracer._push(self.ctx)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.tracer._pop()
        self._pe.__exit__(exc_type, exc, tb)
        event = {
            "name": self.name,
            "trace_id": self.ctx.trace_id,
            "span_id": self.ctx.span_id,
            "parent_id": self.parent_id,
            "ts": self._ts_us,
            "dur": (self._pe.duration_ms or 0.0) * 1e3,  # µs
            "tid": threading.get_ident(),
            "args": self.args,
        }
        if exc is not None:
            event["error"] = repr(exc)
        self.tracer._record(event)


class _NullSpan:
    """Disabled-tracer stand-in: same surface, no recording."""

    ctx = None
    args: Dict[str, Any] = {}

    def annotate(self, **_args: Any) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *_exc) -> None:
        pass


_NULL = _NullSpan()


class Tracer:
    """Process-wide span recorder: a bounded ring of completed span
    events plus a thread-local current-context stack."""

    def __init__(self, capacity: int = 65536):
        self.enabled = True
        self.capacity = capacity
        self._events: deque = deque(maxlen=capacity)
        self._span_ids = itertools.count(1)
        self._trace_ids = itertools.count(1)
        self._local = threading.local()
        #: spans mirror their start/end through this logger (no sink by
        #: default — events still reach the flight recorder)
        self.logger = TelemetryLogger(None, "trace")

    # ----------------------------------------------------------- id issue

    def new_trace_id(self) -> str:
        return f"{os.getpid():x}.{next(self._trace_ids):x}"

    # ---------------------------------------------------- context plumbing

    def _stack(self) -> List[TraceContext]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _push(self, ctx: TraceContext) -> None:
        self._stack().append(ctx)

    def _pop(self) -> None:
        stack = self._stack()
        if stack:
            stack.pop()

    def current(self) -> Optional[TraceContext]:
        stack = self._stack()
        return stack[-1] if stack else None

    # ------------------------------------------------------------ spanning

    def span(self, name: str, parent: Optional[Any] = None,
             **args: Any) -> Any:
        """Open a span. ``parent`` may be a :class:`TraceContext`, a wire
        dict (``{"tid", "sid"}``), or None — None parents to the thread's
        current span, or starts a new trace at the root."""
        if not self.enabled:
            return _NULL
        if parent is None:
            parent = self.current()
        elif not isinstance(parent, TraceContext):
            parent = TraceContext.from_wire(parent) or self.current()
        if parent is None:
            ctx = TraceContext(self.new_trace_id(), next(self._span_ids))
            parent_id = None
        else:
            ctx = TraceContext(parent.trace_id, next(self._span_ids))
            parent_id = parent.span_id
        return Span(self, name, ctx, parent_id, args)

    # ----------------------------------------------------------- recording

    def _record(self, event: dict) -> None:
        self._events.append(event)

    def record_complete(self, name: str, dur_ms: float,
                        parent: Optional[Any] = None,
                        **args: Any) -> Optional[TraceContext]:
        """Record an already-measured span (hot batch paths that time
        themselves): one ring append, no context-manager overhead. The
        span is stamped as ending now. Returns its context (or None when
        disabled)."""
        if not self.enabled:
            return None
        if parent is None:
            parent = self.current()
        elif not isinstance(parent, TraceContext):
            parent = TraceContext.from_wire(parent) or self.current()
        if parent is None:
            ctx = TraceContext(self.new_trace_id(), next(self._span_ids))
            parent_id = None
        else:
            ctx = TraceContext(parent.trace_id, next(self._span_ids))
            parent_id = parent.span_id
        now_us = time.perf_counter() * 1e6
        self._record({
            "name": name, "trace_id": ctx.trace_id,
            "span_id": ctx.span_id, "parent_id": parent_id,
            "ts": now_us - dur_ms * 1e3, "dur": dur_ms * 1e3,
            "tid": threading.get_ident(), "args": args,
        })
        return ctx

    def record_window(self, wid: int, t0: float, t1: float,
                      spans: Iterable[tuple], **args: Any
                      ) -> Optional[TraceContext]:
        """Append one door window's whole record as one trace (id
        ``w<wid>``): a root ``window`` span from ``t0`` to ``t1`` and one
        child per ``(name, start, end)`` stamp (``perf_counter`` seconds),
        parented by :data:`PARENTS`. Returns the root's context — the
        window's exemplar."""
        if not self.enabled:
            return None
        tid, trace_id = threading.get_ident(), f"w{wid}"
        root = TraceContext(trace_id, next(self._span_ids))
        self._record({"name": "window", "trace_id": trace_id,
                      "span_id": root.span_id, "parent_id": None,
                      "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6, "tid": tid,
                      "args": dict(args, wid=wid)})
        latest: Dict[str, int] = {}
        # parents open before their children but close after them
        for name, a, b in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
            sid = latest[name] = next(self._span_ids)
            self._record({"name": name, "trace_id": trace_id,
                          "span_id": sid, "tid": tid, "args": {},
                          "parent_id": latest.get(PARENTS.get(name),
                                                  root.span_id),
                          "ts": a * 1e6, "dur": (b - a) * 1e6})
        return root

    def events(self, trace_id: Optional[str] = None) -> List[dict]:
        evs = list(self._events)
        if trace_id is not None:
            evs = [e for e in evs if e["trace_id"] == trace_id]
        return evs

    def trace_ids(self) -> List[str]:
        """Distinct trace ids in the ring, oldest first."""
        seen: Dict[str, None] = {}
        for e in self._events:
            seen.setdefault(e["trace_id"], None)
        return list(seen)

    def clear(self) -> None:
        self._events.clear()

    # ------------------------------------------------------------- export

    def export_chrome(self, path: Optional[str] = None,
                      trace_id: Optional[str] = None) -> dict:
        """Chrome trace-event JSON (``"ph": "X"`` complete events, µs
        timestamps) — loadable in chrome://tracing / Perfetto and by
        ``tools.trace_viewer``. Writes to ``path`` when given."""
        doc = {"traceEvents": [chrome_event(e)
                               for e in self.events(trace_id)]}
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc


def chrome_event(e: dict) -> dict:
    return {
        "ph": "X", "name": e["name"], "cat": "op",
        "ts": e["ts"] + _EPOCH_US, "dur": e["dur"],
        "pid": os.getpid(), "tid": e["tid"],
        "args": {"trace_id": e["trace_id"], "span_id": e["span_id"],
                 "parent_id": e["parent_id"],
                 **{k: _arg(v) for k, v in e.get("args", {}).items()},
                 **({"error": e["error"]} if "error" in e else {})},
    }


def _arg(v: Any) -> Any:
    return v if isinstance(v, (int, float, str, bool, type(None))) \
        else repr(v)


# ---------------------------------------------------------------------
# the window record: one per door window, stamped where the work happens
# ---------------------------------------------------------------------
#
# A record is a plain dict: ids (``wid``, ``pid``) and counts beside
# ``spans``, a list of ``(name, start, end)`` stamps. The door makes one
# per drain pass and one per window; the window's rides the wave through
# the executor as its ``marks`` (the crossings ``observe_window_timeline``
# reads are keys of the same dict). ``stage`` stamps work, ``wait`` stamps
# a wait once it has ended, ``close_window`` files the finished record.

#: a span whose own time (less its children's) reaches this is "long":
#: by the ledger (PR 24) a window's stages take 2-6 ms each in both cells
LONG_S = 0.050
#: 1 window in this many is kept in the ring whatever it held
KEEP_EVERY = 256

#: static nesting, child → parent: a span's self time is its duration
#: less that of the children stamped into the same record meanwhile
PARENTS = {
    "door.decode": "door.drain", "door.admit": "door.decode",
    "deli.sequence": "engine.sequence",
    "store.apply_planes": "engine.dispatch",
    "store.pack": "store.apply_planes",
    "store.upload": "store.apply_planes",
    "store.unpack_dispatch": "store.apply_planes",
    "store.merge_dispatch": "store.apply_planes",
    "store.slide_docs": "store.apply_planes",
    "log.append": "engine.log",
}
#: work, entered as ``TraceAnnotation("fluid.<name>")``
SPANS = ("door.drain", "door.decode", "door.admit", "door.build_windows",
         "door.submit", "engine.prepare", "engine.sequence",
         "deli.sequence", "engine.dispatch", "store.apply_planes",
         "store.pack", "store.upload", "store.unpack_dispatch",
         "store.merge_dispatch", "store.slide_docs", "engine.log",
         "log.append", "door.fan_acks")
#: waits, known only when they end: stamped, never annotated
WAITS = ("door.tick_wait", "door.rx_wait", "door.capacity_wait",
         "executor.pack_wait", "executor.seq_wait", "executor.log_wait",
         "door.ack_bounce", "door.tx_wait")
#: waits that are backpressure by design: however long, they do not make
#: a window a slow one
BACKPRESSURE = frozenset(WAITS[:3])
#: the collector's pauses: generations 0 and 1, and generation 2
GC = ("gc.young", "gc.full")
#: the table's rows; ``window`` is the whole rx → ack-fanned time
TABLE_NAMES = SPANS + WAITS + ("window",) + GC
#: the server's threads, as ``name_os_thread`` names them
THREADS = ("fluid-door", "fluid-pack", "fluid-seq", "fluid-log")


class _Table(MetricsRegistry):
    """The span table's registry: reading its ``counters`` reads each
    named thread's CPU clock into them first, so the thread rows are as
    of that moment and the hot path never touches them."""

    @property
    def counters(self) -> Dict[str, float]:
        _read_threads(self._rows)
        return self._rows

    @counters.setter
    def counters(self, rows: Dict[str, float]) -> None:
        self._rows = rows


#: the process table, one collector of the registry (``/metrics`` and
#: ``full_snapshot()`` carry it): per name seconds ``.s``, instances
#: ``.n``, and ``.long_s``/``.long_n`` of the instances whose own time
#: reached ``LONG_S``; and per thread of ``THREADS`` (and any other that
#: ``name_os_thread`` names) its CPU seconds, ``thread.<name>.cpu_s``.
#: Every key of those names exists from import on.
SPAN_TABLE = _Table()
_KEYS = {n: (f"{n}.s", f"{n}.n", f"{n}.long_s", f"{n}.long_n")
         for n in TABLE_NAMES}
_ROWS = SPAN_TABLE._rows
_ROWS.update({k: 0.0 for ks in _KEYS.values() for k in ks})
_ROWS.update({f"thread.{n}.cpu_s": 0.0 for n in THREADS})
REGISTRY.attach("spans", SPAN_TABLE)
_table_lock = threading.Lock()
_ANN_NAMES = {n: "fluid." + n for n in SPANS}
#: the newest closed window records, whole (what a traced run dumps)
RECENT: deque = deque(maxlen=4096)
_annotation = None      # jax.profiler.TraceAnnotation, on first use
#: the threads ``name_os_thread`` named, each [name, thread, CPU clock,
#: last reading]; and by name the last readings of those that ended
_named: List[list] = []
_ended: Dict[str, float] = {}
_named_lock = threading.Lock()


def _tabulate(rec: Optional[dict], name: str, dur: float,
              own: float) -> None:
    ks, c = _KEYS[name], _ROWS
    with _table_lock:
        c[ks[0]] += dur
        c[ks[1]] += 1
        if own >= LONG_S:
            c[ks[2]] += own
            c[ks[3]] += 1
    if own >= LONG_S and rec is not None and name not in BACKPRESSURE:
        rec.setdefault("long", []).append(name)


def new_record(**ids: Any) -> dict:
    return dict(ids, spans=[])


class stage:
    """``with stage(rec, "store.pack"): ...`` — one span of work: start
    and end stamped into ``rec`` (and, with ``mark="pack"``, as the
    crossings ``rec["pack0"]``/``rec["pack1"]``), the same interval
    entered as a profiler annotation carrying the record's ``wid``, its
    duration added to the table. A few microseconds while no profile is
    being taken (2.0-2.3 on the x86 hosts measured); there is no other
    switch. No CPU clock is read here: on the TPU hosts reading a
    thread's CPU clock is a system call of about 6 µs, and the clock
    ticks in 10 ms, so the table reads each thread's clock when the
    table is read (``_read_threads``), not per span."""

    __slots__ = ("rec", "name", "mark", "t0", "t1", "_first", "_ann")

    def __init__(self, rec: dict, name: str, mark: Optional[str] = None):
        self.rec, self.name, self.mark = rec, name, mark

    def __enter__(self) -> "stage":
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation
            _annotation = TraceAnnotation
        rec = self.rec
        self._first = len(rec["spans"])
        ann = self._ann = _annotation(_ANN_NAMES[self.name],
                                      wid=rec.get("wid", -1))
        ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        t1 = self.t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        rec, name, t0 = self.rec, self.name, self.t0
        spans = rec["spans"]
        own = t1 - t0
        if len(spans) > self._first:
            for child, a, b in spans[self._first:]:
                if PARENTS.get(child) == name:
                    own -= b - a
        spans.append((name, t0, t1))
        if self.mark is not None:
            rec[self.mark + "0"], rec[self.mark + "1"] = t0, t1
        _tabulate(rec, name, t1 - t0, own)

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) * 1e3


def wait(rec: Optional[dict], name: str, t0: float, t1: float,
         mark: Optional[str] = None) -> None:
    """Stamp a wait that has just ended (``rec`` None: the table alone,
    for waits tied to no window)."""
    if rec is not None:
        rec["spans"].append((name, t0, t1))
        if mark is not None:
            rec[mark] = t1
    _tabulate(rec, name, t1 - t0, t1 - t0)


def close_window(rec: dict, tl: dict, t_ack: float
                 ) -> Optional[TraceContext]:
    """File a window whose acks have been fanned: the table's ``window``
    row, the ring of recent records, and, where the window or its drain
    pass held a long span (or for 1 window in ``KEEP_EVERY``), the whole
    record as one trace in the tracer's ring. Returns that trace's
    context, the exemplar of ``stage_e2e_ack_ms``."""
    t_rx = tl["t_rx"]
    _tabulate(None, "window", t_ack - t_rx, t_ack - t_rx)
    rec["t_rx"], rec["t_ack"], rec["pass"] = t_rx, t_ack, tl
    RECENT.append(rec)
    wid = rec.get("wid", 0)
    long = rec.get("long", []) + tl.get("long", [])
    if not long and wid % KEEP_EVERY:
        return None
    return TRACER.record_window(
        wid, t_rx, t_ack, tl.get("spans", []) + rec["spans"],
        ops=rec.get("ops"), pid=tl.get("pid"), long=",".join(long))


def clock_mark() -> None:
    """Emit ``TraceAnnotation("fluid.clock", perf_counter_ns=...)``: a
    reader of the profiler's trace maps this module's stamps onto the
    trace's clock through two of them, one at each end of the trace."""
    from jax.profiler import TraceAnnotation
    with TraceAnnotation("fluid.clock",
                         perf_counter_ns=time.perf_counter_ns()):
        pass


def name_os_thread(name: str) -> None:
    """Name the calling thread for the OS (Linux ``prctl(PR_SET_NAME)``,
    15 characters; silent elsewhere), so that a profiler's host lines
    read ``fluid-seq`` and not ``python3``: ``threading.Thread(name=)``
    does not reach the OS on Python 3.12. The thread's CPU clock is kept
    too: the table reads it as ``thread.<name>.cpu_s``."""
    try:
        import ctypes
        libc = ctypes.CDLL(None, use_errno=True)
        libc.prctl.argtypes = [ctypes.c_int, ctypes.c_char_p,
                               ctypes.c_ulong, ctypes.c_ulong,
                               ctypes.c_ulong]
        libc.prctl.restype = ctypes.c_int
        libc.prctl(15, name.encode()[:15], 0, 0, 0)     # PR_SET_NAME
    except (OSError, AttributeError):
        pass
    try:
        clock = time.pthread_getcpuclockid(threading.get_ident())
    except (OSError, AttributeError):       # not a POSIX thread clock
        return
    with _named_lock:
        _named.append([name, threading.current_thread(), clock, 0.0])
        _ROWS.setdefault(f"thread.{name}.cpu_s", 0.0)


def _read_threads(rows: Dict[str, float]) -> None:
    """Each named thread's CPU seconds, read now, into ``rows``: threads
    that share a name summed, one that has ended at its last reading (its
    clock raises once it is gone)."""
    with _named_lock:
        for t in list(_named):
            try:
                alive = t[1].is_alive()
                if alive:
                    t[3] = time.clock_gettime(t[2])
            except OSError:
                alive = False
            if not alive:
                _named.remove(t)
                _ended[t[0]] = _ended.get(t[0], 0.0) + t[3]
        sums = dict(_ended)
        for name, _thread, _clock, cpu in _named:
            sums[name] = sums.get(name, 0.0) + cpu
    for name, cpu in sums.items():
        rows[f"thread.{name}.cpu_s"] = cpu


_gc_t0 = 0.0


def _on_gc(phase: str, info: dict) -> None:
    """The collector's pauses into the rows ``gc.young`` (generations 0
    and 1) and ``gc.full`` (2), on the thread that set it off; the
    interpreter lock holds every other Python thread meanwhile. No lock
    is taken: collections never overlap, and one may begin while its
    thread holds ``_table_lock``."""
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter()
        return
    dur = time.perf_counter() - _gc_t0
    ks = _KEYS[GC[info["generation"] == 2]]
    _ROWS[ks[0]] += dur
    _ROWS[ks[1]] += 1
    if dur >= LONG_S:
        _ROWS[ks[2]] += dur
        _ROWS[ks[3]] += 1


gc.callbacks.append(_on_gc)


#: the process tracer — all layers record here
TRACER = Tracer()


def span(name: str, parent: Optional[Any] = None, **args: Any) -> Any:
    return TRACER.span(name, parent, **args)


def current() -> Optional[TraceContext]:
    return TRACER.current()


def current_wire() -> Optional[dict]:
    """The current context as a wire dict, or None — what gets stamped
    into frames / raw-log records at a serialization boundary."""
    ctx = TRACER.current()
    return ctx.to_wire() if ctx is not None else None


class attach:
    """``with attach(wire_dict): ...`` — re-establish a deserialized
    context as the thread's current (the receiving side of a process or
    socket hop). A None/invalid dict is a no-op."""

    def __init__(self, wire: Any):
        self.ctx = TraceContext.from_wire(wire)

    def __enter__(self) -> Optional[TraceContext]:
        if self.ctx is not None:
            TRACER._push(self.ctx)
        return self.ctx

    def __exit__(self, *_exc) -> None:
        if self.ctx is not None:
            TRACER._pop()


def set_enabled(flag: bool) -> None:
    TRACER.enabled = flag


def span_tree(events: Iterable[dict], trace_id: Optional[str] = None
              ) -> List[dict]:
    """Nest flat span events into a tree: each node gets a ``children``
    list, roots returned in start order. Accepts tracer events or the
    ``args``-carrying Chrome form (``tools.trace_viewer`` renders both)."""
    nodes: Dict[int, dict] = {}
    flat: List[dict] = []
    for e in events:
        a = e.get("args") or {}
        node = {
            "name": e["name"],
            "trace_id": e.get("trace_id", a.get("trace_id")),
            "span_id": e.get("span_id", a.get("span_id")),
            "parent_id": e.get("parent_id", a.get("parent_id")),
            "ts": e.get("ts", 0.0),
            "dur": e.get("dur", 0.0),
            "args": {k: v for k, v in a.items()
                     if k not in ("trace_id", "span_id", "parent_id")},
            "children": [],
        }
        if trace_id is not None and node["trace_id"] != trace_id:
            continue
        flat.append(node)
        if node["span_id"] is not None:
            nodes[node["span_id"]] = node
    roots: List[dict] = []
    for node in flat:
        parent = nodes.get(node["parent_id"]) \
            if node["parent_id"] is not None else None
        if parent is not None and parent is not node:
            parent["children"].append(node)
        else:
            roots.append(node)
    for n in nodes.values():
        n["children"].sort(key=lambda c: c["ts"])
    roots.sort(key=lambda c: c["ts"])
    return roots
