"""Pipelined columnar-ingest executor: overlap seq/pack/dispatch/log
across waves (docs/INGEST_PIPELINE.md).

``StringServingEngine.ingest_planes`` is a serial walk of four stages —
prepare/pack → sequence → dispatch → log — whose host walls ADD UP
(BENCH r5: ~150–200 ms of stage p50s around a 10 ms device dispatch).
This executor runs the SAME stage methods (serving.py) on three worker
threads so the recorded stage sum becomes a max:

- **pack worker** — ``_ingest_prepare(prepack=True)``: validation + the
  interner/table build (``ops/string_store.prepack_planes`` for string
  waves, ``ops/tree_store.prepack_wire`` for tree record waves), FIFO,
  for wave N+1 while wave N is on the device;
- **seq/dispatch worker** — ``_ingest_sequence`` + ``_ingest_dispatch``:
  the native C++ sequencing call and the async device merge share one
  thread (they share the sequencer and the compaction cursors); the
  dispatch being async means sequencing wave N+1 overlaps the device
  executing wave N;
- **log worker** — ``_ingest_log``: the durable whole-batch append, ack
  metrics, attribution — wave N−1's durability completes in the
  background of wave N's dispatch.

Recovery contract (unchanged): a wave's ticket resolves — and therefore
the front door acks — only AFTER the durable append commits. The
engine's poison sentinel is counter-backed (``_seq_unlogged``): any wave
crashing between sequencing and its append leaves the engine refusing
summaries until rebuilt, exactly as the serial path.

In-flight depth is bounded (default 2): ``submit`` blocks when ``depth``
waves are sequenced-or-packing but not yet logged — backpressure to the
front door instead of unbounded queueing.

Ordering: stages are strictly FIFO per worker, so sequencing order ==
submission order == log order == ack order, and payload-handle
allocation matches the serial path (parity-tested by
tests/test_ingest_pipeline.py). Interval-touching waves cannot prepack
(anchor handles mint post-nack inside the dispatch stage); the pack
worker BARRIERS on such a wave's dispatch before packing the next wave
so handle order stays serial.

Failure is fail-stop: the first stage exception fails that wave's
ticket and every younger wave (already-dispatched OLDER waves still log
— they sequenced first and their ops must stay durable); the executor
then refuses new submits until closed.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, List, Optional

from ..utils import tracing
from ..utils.telemetry import StageClock

_STOP = object()

#: stage names for the occupancy clock / gauges
_STAGES = ("pack", "seq_dispatch", "log")


class IngestTicket:
    """Handle for one submitted wave: resolves with ``ingest_planes``'s
    return dict after the wave's durable append commits, or with the
    stage exception. ``add_done_callback`` runs on the resolving worker
    thread (front doors bounce acks back to their event loop)."""

    __slots__ = ("index", "_event", "_result", "_error", "_callbacks",
                 "_lock", "_dispatched", "wave", "t_submit")

    def __init__(self, index: int):
        self.index = index
        self.wave = None
        #: submit-time crossing: the executor observes submit→durable
        #: wall per wave (``ingest_ticket_wall_ms``) — queue waits
        #: included, unlike the per-stage busy times
        self.t_submit = time.perf_counter()
        self._event = threading.Event()
        self._dispatched = threading.Event()
        self._result: Optional[dict] = None
        self._error: Optional[BaseException] = None
        self._callbacks: List[Callable[["IngestTicket"], None]] = []
        self._lock = threading.Lock()

    def done(self) -> bool:
        return self._event.is_set()

    def error(self) -> Optional[BaseException]:
        return self._error

    def result(self, timeout: Optional[float] = None) -> dict:
        """Block until the wave's durable append commits; raises the
        stage exception on a failed wave."""
        if not self._event.wait(timeout):
            raise TimeoutError(f"wave {self.index} still in flight")
        if self._error is not None:
            raise self._error
        return self._result

    def add_done_callback(self, fn: Callable[["IngestTicket"], None]
                          ) -> None:
        with self._lock:
            if not self._event.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    def _resolve(self, result: Optional[dict] = None,
                 error: Optional[BaseException] = None) -> None:
        with self._lock:
            self._result, self._error = result, error
            self._event.set()
            callbacks, self._callbacks = self._callbacks, []
        for fn in callbacks:
            fn(self)


class PipelinedIngestExecutor:
    """Bounded-depth staged pipeline over an engine's columnar-ingest
    stage methods (StringServingEngine's plane waves and
    TreeServingEngine's record waves both speak the protocol). One
    executor per engine; the serial front doors (``ingest_planes`` /
    ``ingest_records``) stay available for callers that want the
    round-trip (do not interleave the two mid-flight — drain first)."""

    def __init__(self, engine, depth: int = 2):
        if depth < 1:
            raise ValueError("pipeline depth must be >= 1")
        for stage in ("_ingest_prepare", "_ingest_sequence",
                      "_ingest_dispatch", "_ingest_log"):
            if not hasattr(engine, stage):
                raise TypeError(
                    f"engine lacks {stage}; pipelined ingest needs the "
                    "staged columnar protocol (StringServingEngine)")
        self.engine = engine
        self.depth = depth
        self._sem = threading.BoundedSemaphore(depth)
        self._pack_q: "queue.Queue" = queue.Queue()
        self._seq_q: "queue.Queue" = queue.Queue()
        self._log_q: "queue.Queue" = queue.Queue()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._inflight = 0
        self._max_inflight = 0
        self._waves = 0
        self._failed_at: Optional[int] = None
        self._failure: Optional[BaseException] = None
        self._closed = False
        self._last_done: Optional[float] = None
        self.clock = StageClock(_STAGES)
        self._threads = [
            threading.Thread(target=self._pack_worker,
                             name="ingest-pack", daemon=True),
            threading.Thread(target=self._seq_worker,
                             name="ingest-seq-dispatch", daemon=True),
            threading.Thread(target=self._log_worker,
                             name="ingest-log", daemon=True),
        ]
        for t in self._threads:
            t.start()
        engine._ingest_executor = self

    # ------------------------------------------------------------ public

    def submit(self, *args: Any, **kwargs: Any) -> IngestTicket:
        """Enqueue one wave; blocks while ``depth`` waves are in flight
        (backpressure). Returns immediately otherwise — await the ticket
        (or its callback) for the ack-safe result.

        Arguments are handed verbatim to the engine's
        ``_ingest_prepare`` (plus ``prepack=True``): the string engine
        takes its plane wave (``rows, client, client_seq, ref_seq, kind,
        a0, a1, ...``), the tree engine its record wave (``doc_ids,
        clients, client_seqs, ref_seqs, batch, rows=...``) — the
        executor is signature-agnostic across the staged engines."""
        if self._closed:
            raise RuntimeError("pipelined ingest executor is closed")
        if self._failure is not None:
            raise RuntimeError(
                "pipelined ingest executor failed; drain/close and "
                "rebuild the engine") from self._failure
        with self._lock:
            idle = self._inflight == 0
        if idle:
            # only meaningful when nothing is in flight: mid-flight the
            # engine is poisoned BY DESIGN (sequenced-unlogged waves)
            self.engine._check_poisoned()
        self._sem.acquire()
        with self._lock:
            ticket = IngestTicket(self._waves)
            self._waves += 1
            self._inflight += 1
            self._max_inflight = max(self._max_inflight, self._inflight)
        self._pack_q.put((ticket, args, kwargs))
        return ticket

    def drain(self, timeout: Optional[float] = None) -> None:
        """Block until every in-flight wave has logged (or failed); then
        run any overflow recovery the compact tail deferred. Raises the
        first stage failure (the serial path's error surface)."""
        with self._cond:
            if not self._cond.wait_for(lambda: self._inflight == 0,
                                       timeout):
                raise TimeoutError("pipelined ingest drain timed out")
        eng = self.engine
        if self._failure is None and getattr(eng, "_ov_recover_due",
                                             False):
            eng._ov_recover_due = False
            eng.recover_overflowed()
        if self._failure is not None:
            raise RuntimeError(
                f"pipelined ingest failed at wave {self._failed_at}"
            ) from self._failure

    def close(self, timeout: float = 30.0) -> None:
        """Drain (best effort), stop the workers, publish final stats."""
        if self._closed:
            return
        self._closed = True
        try:
            self.drain(timeout=timeout)
        except (RuntimeError, TimeoutError):
            pass
        self._pack_q.put(_STOP)
        for t in self._threads:
            t.join(timeout=timeout)
        self.publish_metrics()
        if getattr(self.engine, "_ingest_executor", None) is self:
            self.engine._ingest_executor = None

    def stats(self) -> dict:
        """Occupancy/overlap evidence: per-stage busy fractions, the
        overlap factor (> 1.0 == stages ran concurrently), depth walls."""
        occ = self.clock.occupancy()
        with self._lock:
            return {
                "waves": self._waves,
                "depth": self.depth,
                "max_inflight": self._max_inflight,
                "stage_busy_ms": dict(self.clock.busy_ms),
                "stage_occupancy": occ,
                "overlap": self.clock.overlap(),
            }

    def publish_metrics(self) -> None:
        """Write the occupancy gauges into the engine's registry (names
        registered in docs/OBSERVABILITY.md)."""
        m = self.engine.metrics
        occ = self.clock.occupancy()
        m.set_gauge("ingest_pack_occupancy", occ["pack"])
        m.set_gauge("ingest_seq_dispatch_occupancy", occ["seq_dispatch"])
        m.set_gauge("ingest_log_occupancy", occ["log"])
        m.set_gauge("ingest_stage_overlap", self.clock.overlap())
        with self._lock:
            m.set_gauge("ingest_inflight_depth", self._max_inflight)

    # ----------------------------------------------------------- workers

    def _skip(self, ticket: IngestTicket) -> bool:
        """True when an older wave already failed: this (younger) wave
        must not run its stages (fail-stop, no out-of-order sequencing)."""
        return self._failed_at is not None and ticket.index > \
            self._failed_at

    def _fail(self, ticket: IngestTicket, error: BaseException) -> None:
        with self._lock:
            if self._failed_at is None or ticket.index < self._failed_at:
                self._failed_at, self._failure = ticket.index, error
        self._finish(ticket, error=error)

    def _finish(self, ticket: IngestTicket,
                result: Optional[dict] = None,
                error: Optional[BaseException] = None) -> None:
        ticket._dispatched.set()   # release any pack-worker barrier
        ticket._resolve(result=result, error=error)
        self._sem.release()
        with self._cond:
            self._inflight -= 1
            self._cond.notify_all()

    @staticmethod
    def _queue_wait(wave, name: str, since, t0: float) -> None:
        """Stamp the wait a wave spent in a stage's queue into its
        record (``marks``: the string engine's waves carry one, a tree
        wave none): from ``since``, a time or the name of the crossing
        the stage before left there, to ``t0``, when this worker took it."""
        marks = getattr(wave, "marks", None)
        if marks is not None:
            since = marks.get(since) if isinstance(since, str) else since
            if since is not None:
                tracing.wait(marks, name, since, t0)

    def _pack_worker(self) -> None:
        tracing.name_os_thread("fluid-pack")
        eng = self.engine
        while True:
            item = self._pack_q.get()
            if item is _STOP:
                self._seq_q.put(_STOP)
                return
            ticket, args, kwargs = item
            if self._skip(ticket):
                self._finish(ticket, error=self._chain_error(ticket))
                continue
            t0 = time.perf_counter()
            try:
                wave = eng._ingest_prepare(*args, prepack=True, **kwargs)
            except BaseException as e:  # noqa: BLE001 — fail-stop
                self._fail(ticket, e)
                continue
            self.clock.add("pack", (time.perf_counter() - t0) * 1000)
            ticket.wave = wave
            self._queue_wait(wave, "executor.pack_wait", ticket.t_submit,
                             t0)
            self._seq_q.put(ticket)
            if wave.prepacked is None:
                # un-prepackable wave (interval batch: anchor handles
                # mint post-nack; tree dense fallback: table handles
                # mint inline) — its interner writes happen inside the
                # dispatch stage, so packing the NEXT wave's tables
                # first would allocate handles out of submission order:
                # barrier until this wave's dispatch completes.
                ticket._dispatched.wait()

    def _seq_worker(self) -> None:
        tracing.name_os_thread("fluid-seq")
        eng = self.engine
        while True:
            item = self._seq_q.get()
            if item is _STOP:
                self._log_q.put(_STOP)
                return
            ticket = item
            if self._skip(ticket):
                self._finish(ticket, error=self._chain_error(ticket))
                continue
            t0 = time.perf_counter()
            self._queue_wait(ticket.wave, "executor.seq_wait", "pack1", t0)
            try:
                eng._ingest_sequence(ticket.wave)
                eng._ingest_dispatch(ticket.wave)
            except BaseException as e:  # noqa: BLE001 — fail-stop
                self._fail(ticket, e)
                continue
            self.clock.add("seq_dispatch",
                           (time.perf_counter() - t0) * 1000)
            ticket._dispatched.set()
            self._log_q.put(ticket)

    def _log_worker(self) -> None:
        tracing.name_os_thread("fluid-log")
        eng = self.engine
        while True:
            item = self._log_q.get()
            if item is _STOP:
                return
            ticket = item
            # NO younger-failure skip here: a wave that reached the log
            # queue sequenced+dispatched BEFORE the failure — its ops
            # must become durable or the poison sentinel never clears
            t0 = time.perf_counter()
            self._queue_wait(ticket.wave, "executor.log_wait", "disp1", t0)
            try:
                result = eng._ingest_log(ticket.wave)
            except BaseException as e:  # noqa: BLE001 — fail-stop
                self._fail(ticket, e)
                continue
            now = time.perf_counter()
            self.clock.add("log", (now - t0) * 1000)
            # inter-completion gap == the pipeline's effective per-wave
            # wall (steady state: max stage, not the sum — the overlap
            # evidence BENCH records)
            if self._last_done is not None:
                eng.metrics.observe("ingest_wave_wall_ms",
                                    (now - self._last_done) * 1000)
            self._last_done = now
            eng.metrics.observe("ingest_ticket_wall_ms",
                                (now - ticket.t_submit) * 1000)
            eng.metrics.inc("ingest_waves")
            self._finish(ticket, result=result)

    def _chain_error(self, ticket: IngestTicket) -> RuntimeError:
        err = RuntimeError(
            f"wave {ticket.index} aborted: wave {self._failed_at} "
            "failed earlier in the pipeline")
        err.__cause__ = self._failure
        return err

    def __enter__(self) -> "PipelinedIngestExecutor":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()
