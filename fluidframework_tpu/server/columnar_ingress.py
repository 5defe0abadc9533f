"""The columnar front door: N client sockets → ONE batched device
dispatch per window.

Reference counterpart: Alfred's ingress + Kafka's batch aggregation in
front of Deli (SURVEY.md §1, §3.5). The framed-JSON ``ingress.AlfredServer``
serves the full per-op protocol; THIS tier is the volume path the
reference gets from Kafka batching: clients speak a width-coded BINARY op
frame (~16 B/op + shared payload tables), the server aggregates ops from
every connection into per-window planes and drives the serving engine's
columnar fast path (``StringServingEngine.ingest_planes``) — socket fan-in
composes with the device fan-out instead of bypassing it (VERDICT r4
missing #5).

Protocol (little-endian, own framing: u8 type + u32 len + payload +
crc32):

- type ``J``: JSON control — {"t": "join", "docs": [...], "tenant"?} →
  {"t": "joined", "client_id", "rows": {doc: row}}; ack frames {"t":
  "acks", "acks": [[client_seq, seq], ...]} (seq < 0 = nack code);
  admission-shed ops answer with {"t": "throttled", "rows": [...],
  "cseqs": [...], "retry_after_ms"} — resubmit the SAME cseqs after the
  hint (see ``server.admission``).
- type ``B``: op batch — u8 n_texts, per text (u16 len + utf-8 bytes),
  then N × 16-byte records ``row u16 | kind u8 | a0 u16 | a1 u16 |
  tidx u8 | cseq u32 | ref u32`` (kind codes:
  ``core.protocol.ColumnarWireKind`` — 0 = insert of texts[tidx] at a0,
  1 = remove [a0, a1)).
- type ``R``: rich op batch — the ``B`` layout with a props table
  between the text table and the records: u8 n_props, per prop (u16
  len + utf-8 JSON of a SINGLE-key {key: value} dict). Adds kind 2 =
  annotate [a0, a1) with props[tidx] — the rich-text/interval op,
  width-coded like everything else (one small shared table per frame,
  u8 indices per op).

Ingest path (ISSUE 15, accumulate-then-drain): per-client readers do NOT
parse frames — they append raw ``recv`` chunks to a per-connection
growable buffer and poke the flusher. A drain pass then decodes EVERY
connection's accumulated bytes at once: frame split + crc verify
(``native/ingress.cpp`` fast tier, numpy/zlib fallback), op records
gathered into contiguous int32 planes, per-frame payload tables interned
across the pass, and the whole backlog carved into unique-row windows
up to four columns wide (stable sort by row, then each row's next 4 or
1 pending ops a round — per-doc FIFO across columns and windows is the
sort's stability) that feed ``ingest_planes`` directly,
through the ``PipelinedIngestExecutor`` when ``pipeline_depth > 0``.
Decode cost scales with bytes drained, not frames seen. Control (``J``)
frames and all resilience contracts (join/resume, epoch, dup_ack via the
durable dedup ledger, torn-frame recovery — a partial frame simply stays
buffered, backpressure) keep their slow-path semantics unchanged; see
docs/INGRESS.md.
"""

from __future__ import annotations

import asyncio
import itertools
import json
import socket
import struct
import threading
import time
import zlib
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..core.protocol import ColumnarWireKind
from ..utils import capacity, tracing
from ..utils.backoff import Backoff, retry
from ..utils.telemetry import MetricsCollector, REGISTRY
from . import native_ingress
from .ingest_pipeline import PipelinedIngestExecutor
from .opsd import SpaceSaving, observe_window_timeline
from .wire import BufferedSocketReader

_HDR = struct.Struct("<BI")
_OP_DTYPE = np.dtype([("row", "<u2"), ("kind", "u1"), ("a0", "<u2"),
                      ("a1", "<u2"), ("tidx", "u1"), ("cseq", "<u4"),
                      ("ref", "<u4")])
assert _OP_DTYPE.itemsize == 16
#: the column counts a window may have, widest first. A window is R
#: unique rows times O columns, dense: column j holds each row's j-th
#: pending op of the drain pass. ``O`` is static in the store's unpack
#: and merge programs, so each count is a set of compiled programs, and
#: a merge program's first use costs seconds of set-up on a mesh: the
#: set stays closed and as small as it can be
_WINDOW_COLUMNS = (4, 1)

_FT_J, _FT_B, _FT_R = ord("J"), ord("B"), ord("R")

#: defensive bound on one frame's payload (matches wire.MAX_FRAME); the
#: accumulate-then-drain door must bound how many bytes a single frame
#: may hold hostage in the rx buffer
MAX_PAYLOAD = native_ingress.MAX_PAYLOAD
SCAN_BAD_CRC = native_ingress.SCAN_BAD_CRC
SCAN_TOO_LARGE = native_ingress.SCAN_TOO_LARGE

_K_INS = int(ColumnarWireKind.INSERT)
_K_ANN = int(ColumnarWireKind.ANNOTATE)


def encode_frame(ftype: bytes, payload: bytes) -> bytes:
    return _HDR.pack(ftype[0], len(payload)) + payload + \
        struct.pack("<I", zlib.crc32(payload))


def encode_json(obj: dict) -> bytes:
    return encode_frame(b"J", json.dumps(obj).encode())


def encode_op_batch(texts: List[str], ops: np.ndarray,
                    props: Optional[List[dict]] = None) -> bytes:
    """ops: structured array of _OP_DTYPE records. ``props`` (a table of
    single-key dicts indexed by annotate tidx) upgrades the frame to the
    rich ``R`` layout; without it the plain ``B`` frame is emitted."""
    parts = [bytes([len(texts)])]
    for t in texts:
        b = t.encode()
        parts.append(struct.pack("<H", len(b)))
        parts.append(b)
    if props is not None:
        parts.append(bytes([len(props)]))
        for p in props:
            b = json.dumps(p).encode()
            parts.append(struct.pack("<H", len(b)))
            parts.append(b)
    parts.append(np.ascontiguousarray(ops).tobytes())
    return encode_frame(b"R" if props is not None else b"B",
                        b"".join(parts))


def read_frame(sock) -> Tuple[int, bytes]:
    hdr = _recv_exact(sock, _HDR.size)
    ftype, length = _HDR.unpack(hdr)
    payload = _recv_exact(sock, length)
    (crc,) = struct.unpack("<I", _recv_exact(sock, 4))
    if crc != zlib.crc32(payload):
        raise IOError("frame CRC mismatch")
    return ftype, payload


def _recv_exact(sock, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed")
        buf += chunk
    return buf


# ------------------------------------------------------- batch decode core
#
# Pure functions shared by the drain pass, the reference decoder, and the
# byte-split fuzz tests. The contract for all of them: no view of the
# input buffer survives the call (the caller trims a live ``bytearray``
# right after — a surviving numpy/memoryview export would make the resize
# raise BufferError).

def _py_split_frames(buf) -> Tuple[List[Tuple[int, int, int]], int, int]:
    """Numpy-tier frame splitter: scan ``buf`` for complete
    ``[u8 type | u32 len | payload | u32 crc32]`` frames. Same contract
    as ``native_ingress.scan`` (see ``split_frames``)."""
    frames: List[Tuple[int, int, int]] = []
    off, n, status = 0, len(buf), 0
    mv = memoryview(buf)
    try:
        # 5 buffered bytes = a full header: enough to vet the length
        # field (oversized frames fault before their payload arrives)
        while n - off >= 5:
            ftype, length = _HDR.unpack_from(buf, off)
            if length > MAX_PAYLOAD:
                status = SCAN_TOO_LARGE
                break
            total = 5 + length + 4
            if n - off < total:
                break  # torn frame: wait for more bytes
            (crc,) = struct.unpack_from("<I", buf, off + 5 + length)
            if zlib.crc32(mv[off + 5:off + 5 + length]) != crc:
                status = SCAN_BAD_CRC
                break
            frames.append((ftype, off + 5, length))
            off += total
    finally:
        mv.release()
    return frames, off, status


def split_frames(buf, native: Optional[bool] = None
                 ) -> Tuple[List[Tuple[int, int, int]], int, int]:
    """Split an accumulated rx buffer into complete CRC-valid frames.

    Returns ``(frames, consumed, status)``: ``frames`` holds
    ``(ftype, payload_off, payload_len)`` per frame, ``consumed`` the
    bytes they cover (a trailing partial frame stays in the buffer for
    the next drain — torn-frame recovery is exactly this), and
    ``status`` is 0 / SCAN_BAD_CRC / SCAN_TOO_LARGE. On a poisoned frame
    the scan stops AT it: the good prefix is still returned so earlier
    frames take effect before the connection is faulted, matching the
    per-frame door's ordering."""
    if native is None:
        native = native_ingress.available()
    if native:
        return native_ingress.scan(buf)
    return _py_split_frames(buf)


def parse_op_tables(payload, rich: bool
                    ) -> Tuple[List[str], List[dict], int]:
    """Parse an op frame's payload tables (text table; props table when
    ``rich``): returns ``(texts, props, rec_off)`` where ``rec_off`` is
    the byte offset of the 16-byte record section. Raises with the
    protocol's established diagnostics on malformed tables or a ragged
    record section. Accepts bytes or memoryview."""
    try:
        n_texts = payload[0]
    except IndexError:
        raise IndexError("index out of range") from None
    off = 1
    texts: List[str] = []
    for _ in range(n_texts):
        (ln,) = struct.unpack_from("<H", payload, off)
        off += 2
        texts.append(bytes(payload[off:off + ln]).decode())
        off += ln
    props: List[dict] = []
    if rich:
        try:
            n_props = payload[off]
        except IndexError:
            raise IndexError("index out of range") from None
        off += 1
        for _ in range(n_props):
            (ln,) = struct.unpack_from("<H", payload, off)
            off += 2
            p = json.loads(bytes(payload[off:off + ln]))
            off += ln
            if not isinstance(p, dict) or len(p) != 1:
                raise ValueError("props entries must be single-key dicts")
            props.append(p)
    if (len(payload) - off) % _OP_DTYPE.itemsize:
        raise ValueError("record section not a whole number "
                         "of op records")
    return texts, props, off


def _validate_op_planes(kind: np.ndarray, tidx: np.ndarray, rich: bool,
                        n_texts: int, n_props: int) -> Optional[str]:
    """One frame's whole-frame validation on its gathered planes — the
    vectorized twin of the per-frame decoder's checks, byte-for-byte the
    same diagnostics. Returns the reject message or None."""
    top = _K_ANN if rich else int(ColumnarWireKind.REMOVE)
    if kind.size and int(kind.max()) > top:
        return "op kind out of range for this frame type"
    ins = kind == _K_INS
    if ins.any() and (n_texts == 0 or int(tidx[ins].max()) >= n_texts):
        return "tidx out of text-table range"
    ann = kind == _K_ANN
    if ann.any() and (n_props == 0 or int(tidx[ann].max()) >= n_props):
        return "tidx out of props-table range"
    return None


def reference_decode_op_frame(payload: bytes, rich: bool
                              ) -> Tuple[List[str], List[dict],
                                         np.ndarray]:
    """The retired per-frame decoder, kept as the batch path's oracle:
    parse + validate ONE op frame exactly like the pre-drain door did
    (whole-frame reject semantics, same diagnostics). Returns
    ``(texts, props, ops)`` or raises. The byte-split fuzz pins the
    drain decoder against this on every cut offset."""
    texts, props, off = parse_op_tables(payload, rich)
    ops = np.frombuffer(payload, dtype=_OP_DTYPE, offset=off)
    bad = _validate_op_planes(ops["kind"].astype(np.int32),
                              ops["tidx"].astype(np.int32), rich,
                              len(texts), len(props))
    if bad is not None:
        raise ValueError(bad)
    return texts, props, ops


#: plane names a drained part carries (all 1-D int32, equal length)
_PLANES = ("row", "kind", "a0", "a1", "gidx", "cseq", "ref", "client")


class _ColSession:
    """One accepted socket. The reader ONLY accumulates: raw recv chunks
    append to ``rx`` and poke the server's flusher — every byte of
    protocol decode happens in the drain pass. Outbound frames ride a
    bounded queue (slow-client policy: evict, as the reference
    Broadcaster does)."""

    def __init__(self, server: "ColumnarAlfred", reader, writer):
        self.server = server
        self.reader = reader
        self.writer = writer
        self.client_id: Optional[int] = None
        self.out: asyncio.Queue = asyncio.Queue(maxsize=4096)
        self.evicted = False
        self.dead = False
        self.rx = bytearray()
        #: perf_counter of the first undrained byte — the rx-buffer
        #: crossing of the latency-attribution timeline (ISSUE 17)
        self.rx_t0: Optional[float] = None
        #: its mirror on the way out: perf_counter of the oldest frame
        #: pushed and not yet written (``door.tx_wait``)
        self.tx_t0: Optional[float] = None
        #: cleared while the rx buffer is over budget — reader
        #: backpressure until a drain trims it
        self._resume = asyncio.Event()
        self._resume.set()

    async def run(self) -> None:
        srv = self.server
        srv._sessions.add(self)
        sender = asyncio.create_task(self._send_loop())
        try:
            while not self.dead:
                try:
                    chunk = await self.reader.read(srv.read_chunk)
                except (ConnectionError, OSError):
                    break
                if not chunk:
                    break
                self.rx += chunk
                srv._note_rx(self, len(chunk))
                if len(self.rx) >= srv.max_rx_bytes:
                    self._resume.clear()
                    # backpressure stall made visible: count every pause
                    # episode, gauge how many readers are parked NOW
                    srv.rx_pauses += 1
                    srv._rx_paused_now += 1
                    REGISTRY.inc("columnar_rx_paused_total")
                    REGISTRY.set_gauge("rx_paused",
                                       float(srv._rx_paused_now))
                    srv._wake_soon()
                    await self._resume.wait()
                    srv._rx_paused_now -= 1
                    REGISTRY.set_gauge("rx_paused",
                                       float(srv._rx_paused_now))
        finally:
            srv._sessions.discard(self)
            # complete frames that arrived before EOF still drain (the
            # per-frame door processed them too); their acks go to a
            # closed socket, which resubmit+dedup absorbs
            sender.cancel()
            self.writer.close()

    async def _send_loop(self) -> None:
        while True:
            frame = await self.out.get()
            t = time.perf_counter()
            if self.tx_t0 is not None:
                # a frame is tied to no one window: the table alone
                tracing.wait(None, "door.tx_wait", self.tx_t0, t)
            self.tx_t0 = None if self.out.empty() else t
            self.writer.write(frame)
            await self.writer.drain()

    def _push(self, frame: bytes) -> None:
        if self.evicted or self.dead:
            return
        try:
            self.out.put_nowait(frame)
            if self.tx_t0 is None:
                self.tx_t0 = time.perf_counter()
        except asyncio.QueueFull:
            # slow-client policy: evict (Broadcaster's slow-consumer
            # disconnect); reconnect resyncs via the JSON front door
            self.evicted = True
            self.server.evictions += 1
            self.writer.close()

    def _push_json(self, obj: dict) -> None:
        self._push(encode_json(obj))

    def _fatal(self, message: Optional[str]) -> None:
        """Protocol-fatal close from the drain pass: flush whatever the
        sender has queued (acks for frames that preceded the poison),
        append the diagnostic, close. ``message=None`` is the orderly
        ``bye`` close (no diagnostic). transport.close() flushes the
        written bytes before tearing down."""
        if self.dead:
            return
        self.dead = True
        try:
            while not self.out.empty():
                self.writer.write(self.out.get_nowait())
            if message is not None:
                self.writer.write(encode_json({"t": "error",
                                               "message": message}))
        except (ConnectionError, OSError, RuntimeError,
                asyncio.QueueEmpty):
            pass
        try:
            self.writer.close()
        except (ConnectionError, OSError, RuntimeError):
            pass
        self._resume.set()   # wake a paused reader so run() can exit

    def _handle_json(self, payload: bytes) -> Optional[str]:
        """One control frame, slow path (join/resume/bye) — semantics
        unchanged from the per-frame door. Returns None to keep serving,
        or a close reason ("" = orderly bye, non-empty = diagnostic)."""
        srv = self.server
        req = json.loads(payload)
        if req.get("t") == "join":
            resume = req.get("client_id")
            if self.client_id is None and resume is not None:
                # session resumption: the client reclaims its prior
                # identity so the sequencer's dedup cursor still
                # applies to its resubmits (a fresh id would turn
                # every resend into a first-time op)
                self.client_id = int(resume)
                srv._next_client = max(srv._next_client,
                                       self.client_id + 1)
                REGISTRY.inc("session_reconnects_total")
            if self.client_id is None:
                self.client_id = srv._next_client
                srv._next_client += 1
            if srv.admission is not None:
                srv.admission.bind(self.client_id, req.get("tenant"))
            rows = {}
            lcs = {}
            for d in req["docs"]:
                if not srv.engine.is_member(d, self.client_id):
                    # re-joining a still-seated client would RESET its
                    # dedup cursor (client_join re-seats): resumed
                    # members keep their seat
                    srv.engine.connect(d, self.client_id)
                rows[d] = srv.engine.doc_row(d)
                lcs[d] = srv.engine.last_client_seq(d, self.client_id)
            self._push_json({"t": "joined",
                             "client_id": self.client_id,
                             "rows": rows, "lcs": lcs,
                             "epoch": srv.epoch})
            return None
        if req.get("t") == "bye":
            return ""
        return f"unknown {req.get('t')!r}"


class ColumnarAlfred:
    """Binary columnar ingress over a ``StringServingEngine``: aggregates
    every connection's ops into per-window planes, one sequencer call +
    one device dispatch per window (the Alfred→Kafka batching role).

    ISSUE 15: sockets accumulate, the flusher drains — see the module
    docstring for the decode pipeline. ``decode`` picks the drain tier:
    ``"auto"`` (native when ``libingress.so`` built, else numpy),
    ``"native"`` (require it), ``"numpy"`` (force the fallback)."""

    def __init__(self, engine, host: str = "127.0.0.1", port: int = 0,
                 window_min_rows: int = 512, window_ms: float = 2.0,
                 pipeline_depth: int = 2, epoch: int = 0,
                 decode: str = "auto", max_rx_bytes: int = 8 << 20,
                 read_chunk: int = 256 << 10, admission=None):
        self.engine = engine
        #: partitioned serving (ISSUE 18): when ``engine`` is a
        #: ``server.partitioned.PartitionedStringServing`` wrapper
        #: (feature-detected by its ``engines`` list), the drain pass
        #: carves PER-PARTITION windows — partition segments are
        #: contiguous after the stable row sort since global row =
        #: partition * docs_per_partition + local — and each partition
        #: gets its own ``PipelinedIngestExecutor``: N concurrent
        #: sequencers behind one door.
        part_engines = getattr(engine, "engines", None)
        self.n_partitions = len(part_engines) if part_engines else 1
        self._dpp = int(getattr(engine, "docs_per_partition", 0) or 0)
        #: per-partition door collectors: the stage-latency timeline is
        #: observed once globally AND once under a partition label, so
        #: ``/debug/latency`` can split the storm by partition
        self._part_colls: List[MetricsCollector] = []
        if self.n_partitions > 1:
            for p in range(self.n_partitions):
                coll = MetricsCollector()
                REGISTRY.attach("columnarDoor", coll,
                                labels={"partition": p})
                self._part_colls.append(coll)
        #: optional ``server.partitioned.ReplicaDigestTap``: every
        #: sequenced window is folded into the replicated shadow state
        #: after its durable append, asserting cross-replica digest
        #: parity per window (ISSUE 18 acceptance; bench partition
        #: scaling attaches one on the virtual CPU mesh)
        self.digest_tap = None
        #: optional server.admission.AdmissionController: decoded op
        #: planes are offered to it in the drain pass, BEFORE windows
        #: reach the executor; shed suffixes get a throttled frame
        self.admission = admission
        #: (client_id, row) → lowest shed-but-unreadmitted cseq (suffix
        #: discipline across drain passes — see _admit_planes)
        self._shed_fence: Dict[Tuple[int, int], int] = {}
        #: highest cseq shed in each (client, row) fence run: a full
        #: readmit of a PREFIX of the run advances the fence instead of
        #: clearing it (retry waves may resend only part of the run)
        self._shed_high: Dict[Tuple[int, int], int] = {}
        self.throttled_ops = 0
        self.rx_pauses = 0
        self._rx_paused_now = 0
        self.host = host
        self.port = port
        # restart generation: bumped by whoever restarts the door after a
        # crash (chaos soak, supervisor); clients compare epochs across
        # rejoins to learn a restart happened and resubmit their pending
        self.epoch = epoch
        self.window_min_rows = window_min_rows
        self.window_ms = window_ms
        # > 0: windows go through a PipelinedIngestExecutor of this depth
        # (submit wave N+1 while wave N packs/dispatches; ack only after
        # the durable append). 0 = the serial one-round-trip-per-window
        # path.
        self.pipeline_depth = pipeline_depth
        self.max_rx_bytes = max_rx_bytes
        self.read_chunk = read_chunk
        if decode == "native":
            native_ingress.require()   # raises the build error
        self._use_native = (native_ingress.available()
                            if decode == "auto" else decode == "native")
        self.evictions = 0
        self.windows_flushed = 0
        #: windows handed to each partition's engine so far: a window's
        #: number there says whether its merge fuses the zamboni
        #: (``_build_windows``)
        self._windows_to = [0] * self.n_partitions
        self.ops_ingested = 0
        self.drain_passes = 0
        self.drained_bytes = 0
        self._drain_ms: deque = deque(maxlen=512)
        self._drain_bytes: deque = deque(maxlen=512)
        self._next_client = 1
        self._sessions: set = set()
        #: sessions with undrained rx bytes (dict = ordered set)
        self._dirty: Dict[_ColSession, None] = {}
        self._rx_backlog = 0
        self._wake_bytes = max(1, window_min_rows) * _OP_DTYPE.itemsize
        #: decoded-but-unwindowed parts from the current drain pass
        self._parts: List[dict] = []
        self._pending_ops = 0
        # pass-scoped payload interners: frame tables dedupe across every
        # connection in the pass; windows re-table compacted slices
        self._texts: List[str] = []
        self._text_of: Dict[str, int] = {}
        self._props: List[dict] = []
        self._prop_of: Dict[Tuple, int] = {}
        self._server: Optional[asyncio.AbstractServer] = None
        self._wake: Optional[asyncio.Event] = None
        #: one executor per partition (a single-entry list when the
        #: engine is unpartitioned); in-flight depth is tracked PER
        #: partition so one saturated sequencer never blocks its peers
        self._executors: List[PipelinedIngestExecutor] = []
        self._waves_inflight = [0] * self.n_partitions
        #: wid → rows of each partition's windows in flight (submitted,
        #: acks not fanned yet): a wide window waits for those that
        #: share a row with it (``_wait_capacity``)
        self._rows_inflight: List[Dict[int, np.ndarray]] = [
            {} for _ in range(self.n_partitions)]
        self._capacity: Optional[asyncio.Event] = None
        self._pipeline_error: Optional[BaseException] = None
        #: heavy-hitter sketch over (doc, tenant), fed by the drain pass
        #: (ISSUE 17) — the hot-doc routing/eviction signal
        self.hotdocs = SpaceSaving(capacity=256)
        #: per-row last-touch clock (capacity plane, ISSUE 19): stamped
        #: from the same ``np.unique`` pass that feeds the hot-doc
        #: sketch — one vectorized scatter per drained part, no per-op
        #: cost. Rows are GLOBAL rows, so one tracker covers the
        #: partitioned engine too.
        self.idle_ages = capacity.IdleAgeTracker()
        capacity.LEDGER.add_idle_tracker(
            "ColumnarAlfred", self.idle_ages, row_doc_id=self._doc_of_row)
        #: the current drain pass's record (``utils.tracing``): its
        #: spans, counts and the rx/drain/admit crossings every window
        #: of the pass inherits (the window's own record, stamped by the
        #: executor and the engine and closed at the ack fan, names it as
        #: parent)
        self._pass_tl: Optional[dict] = None
        #: when the flusher last ran out of work (``door.tick_wait``)
        self._t_idle0 = time.perf_counter()
        self._ops: Optional[object] = None   # attached OpsServer

    # --------------------------------------------------------- partitions

    @property
    def _executor(self) -> Optional[PipelinedIngestExecutor]:
        """Single-executor view (partition 0 / the sole executor) for
        callers predating the partitioned door."""
        return self._executors[0] if self._executors else None

    def _engine_of(self, p: int):
        """Partition ``p``'s live engine — resolved through the wrapper
        on every call so a failover promotion swaps in transparently."""
        engs = getattr(self.engine, "engines", None)
        return engs[p] if engs else self.engine

    def _part_of_rows(self, rows: np.ndarray) -> np.ndarray:
        return rows // self._dpp if self._dpp else \
            np.zeros(np.asarray(rows).shape, np.int64)

    def rebind_executor(self, p: int) -> None:
        """Post-failover hook: partition ``p``'s engine was swapped
        (promotion); close the deposed engine's executor and pipeline
        into the new authority."""
        if not self._executors:
            return
        try:
            self._executors[p].close()
        except (RuntimeError, TimeoutError):
            pass
        self._executors[p] = PipelinedIngestExecutor(
            self._engine_of(p), depth=self.pipeline_depth)
        self._windows_to[p] = 0     # the new engine counts from nothing

    # ------------------------------------------------------------ ingest side

    def _note_rx(self, sess: _ColSession, n: int) -> None:
        """Reader hook: bytes landed on a session. Wake the flusher once
        roughly a window's worth of records is waiting; smaller dribbles
        ride the ``window_ms`` tick (the old enqueue path's pacing)."""
        if sess.rx_t0 is None:
            sess.rx_t0 = time.perf_counter()
        self._dirty[sess] = None
        self._rx_backlog += n
        if self._rx_backlog >= self._wake_bytes and self._wake is not None:
            self._wake.set()

    def _wake_soon(self) -> None:
        if self._wake is not None:
            self._wake.set()

    def _intern_text(self, s: str) -> int:
        h = self._text_of.get(s)
        if h is None:
            h = self._text_of[s] = len(self._texts)
            self._texts.append(s)
        return h

    def _intern_prop(self, p: dict) -> int:
        (key, value), = p.items()
        pk = (key, value if not isinstance(value, (dict, list))
              else json.dumps(value, sort_keys=True))
        h = self._prop_of.get(pk)
        if h is None:
            h = self._prop_of[pk] = len(self._props)
            self._props.append(p)
        return h

    def _drain(self) -> None:
        """One whole-buffer decode pass over every dirty connection:
        split frames, verify CRCs, gather op planes, intern tables —
        cost scales with bytes drained, not frames seen."""
        if not self._dirty:
            return
        rec = self._pass_tl = tracing.new_record(
            pid=self.drain_passes, frames=0, ops=0, admit_ms=0.0)
        sessions = list(self._dirty)
        self._dirty.clear()
        self._rx_backlog = 0
        total = 0
        rx_min: Optional[float] = None
        with tracing.stage(rec, "door.drain") as sp:
            for sess in sessions:
                if sess.dead or not sess.rx:
                    continue
                if sess.rx_t0 is not None and (rx_min is None
                                               or sess.rx_t0 < rx_min):
                    rx_min = sess.rx_t0
                with tracing.stage(rec, "door.decode"):
                    total += self._drain_session(sess)
        if total:
            t0, t1 = sp.t0, sp.t1
            # pass-level timeline crossings: every window carved from
            # this pass inherits them (t_rx = oldest undrained byte —
            # the worst op's wait, which is what an SLO cares about)
            rec.update(t_rx=rx_min if rx_min is not None else t0,
                       t_drain0=t0, t_ready=t1, bytes=total)
            tracing.wait(rec, "door.tick_wait", self._t_idle0, t0)
            tracing.wait(rec, "door.rx_wait", rec["t_rx"], t0)
            self._drain_ms.append((t1 - t0) * 1e3)
            self._drain_bytes.append(total)
            self.drain_passes += 1
            self.drained_bytes += total
            REGISTRY.inc("columnar_drain_passes")
            REGISTRY.inc("columnar_drained_bytes", total)

    def _drain_session(self, sess: _ColSession) -> int:
        rx = sess.rx
        frames, consumed, status = split_frames(rx,
                                                native=self._use_native)
        fatal: Optional[str] = None
        bye = False
        # per op frame: (abs record offset, count, tmap, pmap, rich,
        # client_id, n_texts, n_props) — gathered in ONE pass below
        runs: List[tuple] = []
        mv = memoryview(rx)
        try:
            for ftype, off, ln in frames:
                if ftype == _FT_B or ftype == _FT_R:
                    if sess.client_id is None:
                        fatal = "join first"
                        break
                    rich = ftype == _FT_R
                    try:
                        texts, props, rec_off = parse_op_tables(
                            mv[off:off + ln], rich)
                    except (ValueError, IndexError, struct.error,
                            UnicodeDecodeError) as e:
                        fatal = f"malformed op frame: {e}"
                        break
                    tmap = np.array([self._intern_text(t) for t in texts],
                                    np.int32)
                    pmap = np.array([self._intern_prop(p) for p in props],
                                    np.int32)
                    runs.append((off + rec_off,
                                 (ln - rec_off) // _OP_DTYPE.itemsize,
                                 tmap, pmap, rich, sess.client_id,
                                 len(texts), len(props)))
                elif ftype == _FT_J:
                    reason = sess._handle_json(bytes(mv[off:off + ln]))
                    if reason is not None:
                        bye, fatal = True, (reason or None)
                        break
                else:
                    fatal = "unknown frame type"
                    break
            else:
                if status == SCAN_BAD_CRC:
                    fatal = "bad crc"
                elif status == SCAN_TOO_LARGE:
                    fatal = "frame too large"
        finally:
            mv.release()
        self._pass_tl["frames"] += len(frames)
        if runs:
            self._decode_runs(sess, rx, runs)
        # no view of rx survives _decode_runs (planes are copies): the
        # bytearray is free to resize
        if fatal is not None or bye:
            sess._fatal(fatal)
            rx.clear()
            sess.rx_t0 = None
        else:
            del rx[:consumed]
            # leftover bytes are a torn frame whose tail hasn't arrived:
            # restart its rx clock at the drain (the op isn't waiting on
            # us yet — it is still in flight on the wire)
            sess.rx_t0 = time.perf_counter() if rx else None
            if not sess._resume.is_set() \
                    and len(rx) < self.max_rx_bytes:
                sess._resume.set()
        return consumed

    def _decode_runs(self, sess: _ColSession, rx: bytearray,
                     runs: List[tuple]) -> None:
        """Gather one session's validated op-frame runs into int32
        planes, map per-frame table indices to pass-global interned ids,
        and queue the part for windowing. Whole-frame reject semantics:
        the first invalid frame faults the connection and discards
        itself plus everything after it; earlier frames stand."""
        if self._use_native:
            planes = native_ingress.gather(rx, [(r[0], r[1])
                                                for r in runs])
            row, kind = planes["row"], planes["kind"]
            a0, a1 = planes["a0"], planes["a1"]
            tidx, cseq, ref = planes["tidx"], planes["cseq"], planes["ref"]
        else:
            views = [np.frombuffer(rx, _OP_DTYPE, count=r[1], offset=r[0])
                     for r in runs]
            rec = np.concatenate(views) if len(views) > 1 \
                else views[0].copy()
            del views
            row = rec["row"].astype(np.int32)
            kind = rec["kind"].astype(np.int32)
            a0 = rec["a0"].astype(np.int32)
            a1 = rec["a1"].astype(np.int32)
            tidx = rec["tidx"].astype(np.int32)
            cseq = rec["cseq"].astype(np.int32)
            ref = rec["ref"].astype(np.int32)
        gidx = np.zeros(row.size, np.int32)
        client = np.empty(row.size, np.int32)
        pos = 0
        keep_until = row.size
        fatal = None
        for _ro, cnt, tmap, pmap, rich, cid, n_texts, n_props in runs:
            sl = slice(pos, pos + cnt)
            bad = _validate_op_planes(kind[sl], tidx[sl], rich,
                                      n_texts, n_props)
            if bad is not None:
                fatal = f"malformed op frame: {bad}"
                keep_until = pos
                break
            if tmap.size:
                m = kind[sl] == _K_INS
                if m.any():
                    gidx[sl][m] = tmap[tidx[sl][m]]
            if pmap.size:
                m = kind[sl] == _K_ANN
                if m.any():
                    gidx[sl][m] = pmap[tidx[sl][m]]
            client[sl] = cid
            pos += cnt
        if keep_until < row.size:
            row, kind, a0, a1 = (x[:keep_until]
                                 for x in (row, kind, a0, a1))
            gidx, cseq, ref, client = (x[:keep_until]
                                       for x in (gidx, cseq, ref, client))
        # per-op row bound check: bad rows error individually and drop;
        # the rest of the frame stands (NOT whole-frame — the row space
        # is the server's, not the frame layout's)
        oob = row >= self.engine.n_docs
        if oob.any():
            for r in row[oob].tolist():
                sess._push_json({"t": "error",
                                 "message": f"row {r} out of range"})
            ok = ~oob
            row, kind, a0, a1 = (x[ok] for x in (row, kind, a0, a1))
            gidx, cseq, ref, client = (x[ok] for x in
                                       (gidx, cseq, ref, client))
        if row.size and self.admission is not None:
            with tracing.stage(self._pass_tl, "door.admit") as sp:
                row, kind, a0, a1, gidx, cseq, ref, client = \
                    self._admit_planes(sess, row, kind, a0, a1, gidx,
                                       cseq, ref, client)
            self._pass_tl["admit_ms"] += sp.ms
        if row.size:
            self._pass_tl["ops"] += int(row.size)
            self._note_hotdocs(row, int(client[0]))
            self._parts.append({"sess": sess, "row": row, "kind": kind,
                                "a0": a0, "a1": a1, "gidx": gidx,
                                "cseq": cseq, "ref": ref,
                                "client": client})
            self._pending_ops += int(row.size)
        if fatal is not None:
            sess._fatal(fatal)
            rx.clear()

    def _admit_planes(self, sess: _ColSession, row, kind, a0, a1,
                      gidx, cseq, ref, client):
        """Offer one session's decoded planes to admission, per (client,
        row) group in arrival order; shed suffixes only (the sequencer
        nacks clientSeq gaps) and answer every shed op with ONE
        throttled frame carrying the worst retry hint. A shed fence per
        (client, row) persists across drain passes: higher cseqs keep
        shedding until the fenced cseq itself is readmitted, so the
        client's ordered resubmit can never land behind a gap."""
        adm = self.admission
        keep = np.ones(row.size, bool)
        shed_rows: List[int] = []
        shed_cseqs: List[int] = []
        retry = 0.0
        cid = int(client[0])     # one session = one client per part
        for r in np.unique(row).tolist():
            idx = np.flatnonzero(row == r)
            key = (cid, r)
            fence = self._shed_fence.get(key)
            if fence is not None:
                if int(cseq[idx[0]]) > fence:
                    # the fenced cseq has not been resubmitted yet: the
                    # whole group is behind the gap — shed it all
                    # without offering (tokens stay for the fence's
                    # resubmit)
                    keep[idx] = False
                    shed_rows += [r] * idx.size
                    shed_cseqs += cseq[idx].tolist()
                    self._shed_high[key] = max(
                        self._shed_high.get(key, 0),
                        int(cseq[idx[-1]]))
                    retry = max(retry,
                                adm.retry_after_ms(cid, r, idx.size))
                    continue
                # cseqs below the fence are stale duplicates of already
                # sequenced ops (everything under the fence admitted
                # contiguously): keep them for the dedup ledger
                # UNCHARGED and offer only the fenced suffix. Offering
                # a duplicate could admit it and clear the fence,
                # letting a higher live cseq skip the still-shed
                # fenced op into a clientSeq-gap nack.
                idx = idx[cseq[idx] >= fence]
                if idx.size == 0:
                    continue
            res = adm.admit(cid, r, int(idx.size),
                            backlog=self._pending_ops + len(shed_cseqs))
            k = res.admitted
            if k < idx.size:
                self._shed_fence[key] = int(cseq[idx[k]])
                self._shed_high[key] = max(self._shed_high.get(key, 0),
                                           int(cseq[idx[-1]]))
                shed = idx[k:]
                keep[shed] = False
                shed_rows += row[shed].tolist()
                shed_cseqs += cseq[shed].tolist()
                retry = max(retry, res.retry_after_ms)
            elif fence is not None:
                # whole group admitted — but a retry wave may carry
                # only a PREFIX of the shed run; advance the fence past
                # what just landed until the run's high-water readmits,
                # so a racing live cseq cannot skip the parked rest
                last = int(cseq[idx[-1]])
                if last < self._shed_high.get(key, 0):
                    self._shed_fence[key] = last + 1
                else:
                    del self._shed_fence[key]
                    self._shed_high.pop(key, None)
        if shed_cseqs:
            self.throttled_ops += len(shed_cseqs)
            REGISTRY.inc("columnar_throttled_ops", len(shed_cseqs))
            sess._push_json({"t": "throttled", "rows": shed_rows,
                             "cseqs": shed_cseqs,
                             "retry_after_ms": round(
                                 max(retry, 1.0), 3)})
            row, kind, a0, a1 = (x[keep] for x in (row, kind, a0, a1))
            gidx, cseq, ref, client = (x[keep] for x in
                                       (gidx, cseq, ref, client))
        return row, kind, a0, a1, gidx, cseq, ref, client

    def _doc_of_row(self, r: int):
        """Row index → doc id for the capacity plane's coldest-doc
        census (bound method so the ledger's weak registration never
        pins the door)."""
        docs = getattr(self.engine, "_row_doc_id", None)
        if docs is not None and 0 <= r < len(docs):
            return docs[r]
        return None

    def _note_hotdocs(self, row: np.ndarray, cid: int) -> None:
        """Feed the heavy-hitter sketch from one session's admitted
        planes: one ``offer_many`` per part over its unique
        (doc, tenant) keys with their op counts, not an offer per op.
        An offer is O(1) in the sketch's size, so the cost is that of
        the part's unique rows: about half a microsecond each when
        every one is a miss, as it is whenever the door serves more
        documents than the sketch holds.
        The same unique pass stamps the idle-age clock: one scatter."""
        if self.admission is not None:
            tenant = self.admission.tenant_of(cid)
        else:
            tenant = f"client-{cid}"
        u, counts = np.unique(row, return_counts=True)
        self.idle_ages.touch(u)
        rows = u.tolist()
        docs = getattr(self.engine, "_row_doc_id", None)
        if docs is None:
            names = [f"row-{r}" for r in rows]
        else:
            # rows reach here checked against engine.n_docs, the length
            # of the engine's row → doc table
            names = list(map(docs.__getitem__, rows))
            if None in names:
                names = [f"row-{r}" if d is None else d
                         for r, d in zip(rows, names)]
        self.hotdocs.offer_many(zip(names, itertools.repeat(tenant)),
                                counts.tolist())

    def _build_windows(self) -> List[dict]:
        """Carve the pass's decoded backlog into dense windows of unique
        rows times 4 or 1 columns (``_WINDOW_COLUMNS``): stable sort
        by row, cut the rows every ``window_min_rows`` into chunks, then
        carve each chunk in rounds. A round takes the chunk's rows that
        still have ops pending and gives them the widest column count
        every one of them can fill; what a row has left waits for the
        chunk's next round. Only a full chunk is widened: a window of
        fewer rows stays one column wide, so the heights that vary are
        those of one-column windows and each wide shape is one program.
        And a window whose merge the engine will fuse its zamboni into
        (every ``compact_every``-th it is handed) stays one column wide
        too, so the fused merge is a program at one width only.
        Column j of a row is its j-th pending op (per-doc FIFO is the
        sort's stability, across columns and across rounds), no slot is
        padded, and a pass in which no row is pending twice gives
        one-column windows of its sorted rows. Each window compacts its
        own text/props tables from the pass interner."""
        parts = self._parts
        if not parts:
            return []
        self._parts = []
        with tracing.stage(self._pass_tl, "door.build_windows"):
            tab: List[_ColSession] = []
            idx_of: Dict[int, int] = {}
            sessi_parts = []
            for p in parts:
                s = p["sess"]
                i = idx_of.get(id(s))
                if i is None:
                    i = idx_of[id(s)] = len(tab)
                    tab.append(s)
                sessi_parts.append(np.full(p["row"].size, i, np.int32))
            if len(parts) == 1:
                f = {k: parts[0][k] for k in _PLANES}
                sessi = sessi_parts[0]
            else:
                f = {k: np.concatenate([p[k] for p in parts])
                     for k in _PLANES}
                sessi = np.concatenate(sessi_parts)
            row = f["row"]
            n = row.size
            order = np.argsort(row, kind="stable")
            srow = row[order]
            # partitioned engine: global row = partition * dpp + local, so
            # after the row sort partition runs are CONTIGUOUS — carve at
            # partition boundaries FIRST, then chunks per partition
            # segment (each window then belongs to exactly one
            # partition's sequencer/executor)
            if self.n_partitions > 1:
                pids = srow // self._dpp
                pcuts = np.flatnonzero(np.diff(pids)) + 1
                segs = [(int(pids[seg[0]]), seg)
                        for seg in np.split(np.arange(n), pcuts)]
            else:
                segs = [(0, np.arange(n))]
            # a chunk is an (R, O) matrix of op indices into the pass's
            # planes: row i's columns are its next O ops in arrival order
            chunks: List[Tuple[int, np.ndarray]] = []
            for part, seg in segs:
                so = srow[seg]
                m = so.size
                new = np.empty(m, bool)
                new[0] = True
                new[1:] = so[1:] != so[:-1]
                first = np.flatnonzero(new)     # a row's next pending op
                left = np.diff(np.append(first, m))     # ops it has left
                oseg = order[seg]
                full = self.window_min_rows
                # the engine counts the windows it is handed and fuses
                # its zamboni into every ``compact_every``-th one's merge
                # (``serving.py:_ingest_sequence``); this door hands it
                # every window it gets, so the count is the door's own
                every = getattr(self._engine_of(part), "compact_every", 0)
                nth = self._windows_to[part]
                for s in range(0, first.size, full):
                    at, todo = first[s:s + full], left[s:s + full]
                    while at.size:
                        cols = 1
                        fused = every > 1 and (nth + 1) % every == 0
                        if at.size == full and not fused:
                            least = int(todo.min())
                            cols = next(c for c in _WINDOW_COLUMNS
                                        if c <= least)
                        w = oseg[at[:, None] + np.arange(cols)]
                        if cols > 1:
                            # an op and its resubmit never ride one
                            # window: the second is re-acked from the
                            # dedup ledger, which learns of the first
                            # when its window's acks are fanned
                            key = (f["client"][w].astype(np.int64) << 32) \
                                | f["cseq"][w]
                            if any((key[:, i] == key[:, j]).any()
                                   for i in range(cols)
                                   for j in range(i + 1, cols)):
                                cols, w = 1, w[:, :1]
                        chunks.append((part, w))
                        nth += 1
                        more = todo > cols
                        at, todo = at[more] + cols, todo[more] - cols
            texts_g, props_g = self._texts, self._props
            windows = []
            for part, w in chunks:
                kind_w = f["kind"][w]
                gidx_w = f["gidx"][w]
                tidx_w = np.zeros(w.shape, np.int32)
                ins = kind_w == _K_INS
                texts_w: List[str] = []
                if ins.any():
                    u, inv = np.unique(gidx_w[ins], return_inverse=True)
                    tidx_w[ins] = inv.astype(np.int32)
                    texts_w = [texts_g[i] for i in u.tolist()]
                props_w: List[dict] = []
                ann = kind_w == _K_ANN
                if ann.any():
                    u, inv = np.unique(gidx_w[ann], return_inverse=True)
                    tidx_w[ann] = inv.astype(np.int32)
                    props_w = [props_g[i] for i in u.tolist()]
                windows.append({
                    "rows": row[w[:, 0]], "kind": kind_w,
                    "a0": f["a0"][w], "a1": f["a1"][w], "tidx": tidx_w,
                    "cseq": f["cseq"][w], "ref": f["ref"][w],
                    "client": f["client"][w], "sessi": sessi[w],
                    "texts": texts_w or [""], "props": props_w or None,
                    "tab": tab, "tl": self._pass_tl, "part": part,
                    "rec": tracing.new_record(pid=self._pass_tl["pid"],
                                              ops=int(w.size))})
            # the interners only feed this pass's windows, which now carry
            # their own compacted tables — reset so they stay bounded
            self._texts, self._text_of = [], {}
            self._props, self._prop_of = [], {}
            if self.n_partitions > 1 and len(windows) > 1:
                # interleave submission round-robin across partitions: the
                # per-partition depth wait then parks on the SATURATED
                # partition only after its peers' windows are already in
                # flight (within a partition, the carve's order — per-doc
                # FIFO — is preserved: stable grouping keeps relative order)
                byp: Dict[int, List[dict]] = {}
                for w in windows:
                    byp.setdefault(w["part"], []).append(w)
                queues = list(byp.values())
                windows = []
                i = 0
                while queues:
                    q = queues[i % len(queues)]
                    windows.append(q.pop(0))
                    if q:
                        i += 1
                    else:
                        queues.remove(q)
        self._pass_tl["windows"] = len(windows)
        return windows

    def _submit_window(self, w: dict) -> None:
        n = int(w["kind"].size)             # ops: rows times columns
        cols = w["kind"].shape[1]
        part = w.get("part", 0)
        # the engine stages speak partition-LOCAL rows; the wire (acks,
        # shed fences, hotdocs) keeps the door's global rows
        loc = w["rows"] - part * self._dpp if self.n_partitions > 1 \
            else w["rows"]
        # the window's identity from here on: the engine's stages stamp
        # the same record (``marks=``), the ack fan closes it
        rec = w["rec"]
        rec["wid"] = self.windows_flushed
        if self._executors:
            # pipelined front door: hand the window to its partition's
            # executor and return — the NEXT window aggregates while
            # this one packs/sequences/dispatches; acks fan back from
            # the done callback only after the durable append commits
            # (ack-after-durable)
            with tracing.stage(rec, "door.submit"):
                ticket = self._executors[part].submit(
                    loc, w["client"], w["cseq"], w["ref"],
                    w["kind"], w["a0"], w["a1"], texts=w["texts"],
                    tidx=w["tidx"], props=w["props"], marks=rec)
                self._waves_inflight[part] += 1
                self._rows_inflight[part][rec["wid"]] = w["rows"]
                loop = getattr(self, "_loop", None) or \
                    asyncio.get_running_loop()
                ticket.add_done_callback(
                    lambda t: self._bounce_ack(loop, t, w))
        else:
            res = self._engine_of(part).ingest_planes(
                loc, w["client"], w["cseq"], w["ref"],
                w["kind"], w["a0"], w["a1"], texts=w["texts"],
                tidx=w["tidx"], props=w["props"], marks=rec)
            self._fan_acks(w, np.asarray(res["seq"]).reshape(-1),
                           marks=res.get("marks"))
        self.windows_flushed += 1
        self._windows_to[part] += 1
        self.ops_ingested += n
        self._pending_ops -= n
        REGISTRY.inc("columnar_windows_flushed")
        REGISTRY.inc("columnar_window_columns", cols)
        if cols > 1:
            REGISTRY.inc("columnar_windows_wide")
        REGISTRY.inc("columnar_ops_ingested", n)

    def _fan_acks(self, w: dict, seqs: np.ndarray,
                  marks: Optional[dict] = None) -> None:
        """Fan a window's acks back: for each participating session one
        frame a column, in column order, so a frame never names a row
        twice and a row's acks arrive in its sequence order.

        Runs AFTER the durable append (serial path: ingest_planes
        returned; pipelined path: the ticket resolved past the log
        stage), so recording the ack in the engine's dedup ledger here
        means a ledger hit can vouch that the op is durable — the
        idempotent dup-ack for a resubmit re-serves the original seq.
        The frame carries a parallel ``rows`` list (acks keep their
        2-tuple shape for wire compatibility) so resilient clients can
        attribute each ack to a doc."""
        rec = w["rec"]
        with tracing.stage(rec, "door.fan_acks") as sp:
            rows, cseq = w["rows"], w["cseq"]
            sessi, tab = w["sessi"], w["tab"]
            n_cols = cseq.shape[1]
            seqs = seqs.reshape(cseq.shape)
            # per op, row-major as the planes are: the row of each
            op_rows = np.repeat(rows, n_cols)
            self.engine.note_acked_planes(op_rows, w["client"].reshape(-1),
                                          cseq.reshape(-1),
                                          seqs.reshape(-1))
            if self.digest_tap is not None:
                # fold the sequenced window into the replicated shadow and
                # assert cross-replica digest parity (ISSUE 18): the tap's
                # on_window runs the shard_map step and records agreement
                self.digest_tap.on_window(
                    op_rows, w["kind"], w["a0"], w["a1"], seqs,
                    w["client"], w["ref"])
            if self.admission is not None:
                # service-rate feedback for the deadline estimator: these
                # ops just finished sequencing + durable append
                self.admission.note_served(int(cseq.size))
            for j in range(n_cols):
                sj = sessi[:, j]
                order = np.argsort(sj, kind="stable")
                cuts = np.flatnonzero(np.diff(sj[order])) + 1
                for g in np.split(order, cuts):
                    pairs = np.empty((g.size, 2), np.int64)
                    pairs[:, 0] = cseq[g, j]
                    pairs[:, 1] = seqs[g, j]
                    tab[int(sj[g[0]])]._push_json(
                        {"t": "acks", "acks": pairs.tolist(),
                         "rows": rows[g].tolist()})
        # the ack fan completes the window's record: file it (a slow
        # window is kept whole, and is then the e2e histogram's exemplar)
        # and attribute rx → ack to consecutive stage segments
        tl = w.get("tl")
        if tl is not None and marks:
            t_ack = sp.t1
            ctx = tracing.close_window(rec, tl, t_ack)
            observe_window_timeline(tl, marks, t_ack, exemplar=ctx)
            if self._part_colls:
                # same stage histograms, partition-labeled (ISSUE 18):
                # /debug/latency?partition=p splits the storm by
                # sequencer so a hot partition shows up as ITS stage
                # walls, not a fleet-wide average
                observe_window_timeline(
                    tl, marks, t_ack,
                    registry=self._part_colls[w.get("part", 0)],
                    exemplar=ctx)

    def _bounce_ack(self, loop, ticket, w: dict) -> None:
        """Ticket done-callback: runs on the executor's log worker —
        bounce onto the event loop (session queues are loop-affine)."""
        try:
            loop.call_soon_threadsafe(self._ack_wave, ticket, w)
        except RuntimeError:
            pass   # loop already closed (shutdown race): acks are moot

    def _ack_wave(self, ticket, w: dict) -> None:
        rec = w["rec"]
        if "log1" in rec:   # log done → here, on the loop
            tracing.wait(rec, "door.ack_bounce", rec["log1"],
                         time.perf_counter(), mark="ack0")
        self._waves_inflight[w.get("part", 0)] -= 1
        self._rows_inflight[w.get("part", 0)].pop(rec["wid"], None)
        if self._capacity is not None:
            self._capacity.set()
        err = ticket.error()
        if err is not None:
            if self._pipeline_error is None:
                self._pipeline_error = err
            for i in np.unique(w["sessi"]).tolist():
                w["tab"][i]._push_json(
                    {"t": "error", "message": f"ingest failed: {err}"})
            if self._wake is not None:
                self._wake.set()
            return
        res = ticket.result()
        self._fan_acks(w, np.asarray(res["seq"]).reshape(-1),
                       marks=res.get("marks"))

    async def _wait_capacity(self, w: dict) -> None:
        """Depth backpressure, per partition: park the flusher (event
        loop stays free to accumulate more socket bytes) until one of
        THIS partition's in-flight waves logs — a saturated partition
        never holds back windows already interleaved behind it for its
        peers (they were submitted first by the round-robin order).

        A wide window also waits for every window in flight that shares
        a row with it. One column at a time, a row's j-th pending op was
        sequenced j windows after its first, by when the ack fan had
        told the dedup ledger of every earlier window: a resubmit riding
        in a later column keeps finding its original there."""
        if not self._executors:
            return
        part = w.get("part", 0)
        wide = w["kind"].shape[1] > 1

        def shares_a_row() -> bool:
            return wide and any(
                np.isin(rows, w["rows"], assume_unique=True).any()
                for rows in self._rows_inflight[part].values())

        while (self._waves_inflight[part] >= self._executors[part].depth
               or shares_a_row()) and self._pipeline_error is None:
            self._capacity.clear()
            await self._capacity.wait()

    async def _flusher(self) -> None:
        self._wake = asyncio.Event()
        self._capacity = asyncio.Event()
        while True:
            try:
                await asyncio.wait_for(self._wake.wait(),
                                       timeout=self.window_ms / 1000.0)
            except asyncio.TimeoutError:
                pass
            self._wake.clear()
            try:
                if self._pipeline_error is not None:
                    raise RuntimeError("pipelined ingest failed"
                                       ) from self._pipeline_error
                self._drain()
                for w in self._build_windows():
                    t = time.perf_counter()
                    await self._wait_capacity(w)
                    tracing.wait(w["rec"], "door.capacity_wait", t,
                                 time.perf_counter())
                    if self._pipeline_error is not None:
                        raise RuntimeError("pipelined ingest failed"
                                           ) from self._pipeline_error
                    self._submit_window(w)
                    self._t_idle0 = time.perf_counter()
            except Exception as e:   # poisoned engine / device fault:
                # surface to every connected session, then stop serving
                for sess in list(self._sessions):
                    sess._push_json({"t": "error",
                                     "message": f"ingest failed: {e}"})
                raise

    # ----------------------------------------------------------- lifecycle

    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        if self.pipeline_depth > 0 and not self._executors:
            self._executors = [
                PipelinedIngestExecutor(self._engine_of(p),
                                        depth=self.pipeline_depth)
                for p in range(self.n_partitions)]
        self._server = await asyncio.start_server(
            self._accept, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        self._flush_task = self._loop.create_task(self._flusher())

    async def _accept(self, reader, writer) -> None:
        await _ColSession(self, reader, writer).run()

    def start_in_thread(self) -> "ColumnarAlfred":
        started = threading.Event()

        def _run():
            tracing.name_os_thread("fluid-door")
            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)

            async def _main():
                await self.start()
                started.set()
                async with self._server:
                    await self._server.serve_forever()

            try:
                self._loop.run_until_complete(_main())
            except asyncio.CancelledError:
                pass

        self._thread = threading.Thread(target=_run, name="fluid-door",
                                        daemon=True)
        self._thread.start()
        if not started.wait(timeout=10):
            raise TimeoutError("columnar ingress failed to start")
        return self

    def start_ops(self, host: str = "127.0.0.1", port: int = 0,
                  **kw) -> "object":
        """Attach a live operations plane (``server.opsd.OpsServer``) to
        this door: scrape ``/metrics`` at 1 Hz, read ``/debug/hotdocs``
        from the drain-pass sketch, ``/debug/latency`` from the stage
        attribution. Stopped automatically by :meth:`stop`."""
        from .opsd import OpsServer
        ops = OpsServer(host=host, port=port, **kw)
        ops.add_hotdocs(self.hotdocs)
        ops.add_partitions(self.partition_stats)
        self._ops = ops.start()
        return ops

    def stop(self) -> None:
        ops = self._ops
        if ops is not None:
            self._ops = None
            ops.stop()
        for ex in self._executors:
            # drain first: in-flight waves resolve (acks fan while the
            # loop is still alive), final occupancy gauges publish
            try:
                ex.close()
            except (RuntimeError, TimeoutError):
                pass
        self._executors = []
        loop = getattr(self, "_loop", None)
        if loop is not None:
            loop.call_soon_threadsafe(
                lambda: [t.cancel() for t in asyncio.all_tasks(loop)])
            self._thread.join(timeout=5)

    def pipeline_stats(self) -> Optional[dict]:
        """Occupancy/overlap evidence from the live executor(s) (None
        when serial). Partitioned door: the sole-executor shape plus a
        ``per_partition`` list, with waves summed and occupancy/overlap
        averaged over partitions."""
        if not self._executors:
            return None
        if len(self._executors) == 1:
            return self._executors[0].stats()
        per = [ex.stats() for ex in self._executors]
        stages = per[0]["stage_occupancy"]
        return {
            "waves": sum(s["waves"] for s in per),
            "depth": self.pipeline_depth,
            "max_inflight": max(s["max_inflight"] for s in per),
            "stage_occupancy": {
                k: sum(s["stage_occupancy"][k] for s in per) / len(per)
                for k in stages},
            "overlap": sum(s["overlap"] for s in per) / len(per),
            "per_partition": per,
        }

    def partition_stats(self) -> List[dict]:
        """Per-partition occupancy / backlog / residency for
        ``/debug/partitions`` (ISSUE 18). Backlog counts this pass's
        decoded-but-unwindowed ops plus waves still in flight."""
        backlog = [0] * self.n_partitions
        for part in list(self._parts):
            for p, n in zip(*np.unique(self._part_of_rows(part["row"]),
                                       return_counts=True)):
                backlog[int(p)] += int(n)
        base = getattr(self.engine, "partition_stats", None)
        rows = base() if base is not None else [
            {"partition": p} for p in range(self.n_partitions)]
        for p, r in enumerate(rows):
            r["backlog_ops"] = backlog[p]
            r["waves_inflight"] = self._waves_inflight[p]
            if p < len(self._executors):
                s = self._executors[p].stats()
                r["seq_dispatch_occupancy"] = \
                    s["stage_occupancy"]["seq_dispatch"]
                r["waves"] = s["waves"]
            if "resident_docs" not in r:
                r["resident_docs"] = getattr(self._engine_of(p),
                                             "resident_docs", 0)
        return rows

    def drain_stats(self) -> dict:
        """Decode-stage evidence (``perfbench/`` and the chip smoke read
        its ``tier``): p50 drain pass latency, drained bytes per pass, pass
        count, decode tier."""
        ms = sorted(self._drain_ms)
        by = sorted(self._drain_bytes)
        return {
            "decode_p50_ms": round(ms[len(ms) // 2], 4) if ms else 0.0,
            "bytes_per_pass_p50": int(by[len(by) // 2]) if by else 0,
            "passes": self.drain_passes,
            "drained_bytes": self.drained_bytes,
            "tier": "native" if self._use_native else "numpy"}


def connect_with_backoff(host: str, port: int, attempts: int = 5,
                         base_delay: float = 0.05,
                         timeout: Optional[float] = None) -> socket.socket:
    """``socket.create_connection`` with BOUNDED jittered backoff.

    A server restarting after a crash drill (or still binding) refuses
    connections for a beat; one retry loop here beats N ad-hoc sleeps in
    callers. Bounded: after ``attempts`` failures the last error
    propagates — an ingress that is actually down must fail loudly, not
    hang."""
    bo = Backoff(base=base_delay, cap=2.0,
                 metric="columnar_connect_backoffs")
    try:
        return retry(
            lambda: socket.create_connection((host, port),
                                             timeout=timeout),
            attempts=attempts, exceptions=(OSError,), backoff=bo)
    except OSError as e:
        raise ConnectionError(
            f"columnar ingress {host}:{port} unreachable after "
            f"{attempts} attempts") from e


class ColumnarClient:
    """Blocking-socket client for the columnar ingress (tests/bench).
    Reads go through a ``BufferedSocketReader`` (one large recv refills
    a buffer the 3-read frame parser serves from)."""

    def __init__(self, host: str, port: int, connect_attempts: int = 5):
        self.sock = connect_with_backoff(host, port,
                                         attempts=connect_attempts)
        self._rd = BufferedSocketReader(self.sock)
        self.client_id: Optional[int] = None
        self.rows: Dict[str, int] = {}
        self.lcs: Dict[str, int] = {}   # per-doc last accepted clientSeq
        self.epoch = 0                  # server restart generation

    def join(self, docs: List[str],
             client_id: Optional[int] = None) -> Dict[str, int]:
        """Join (or, with ``client_id``, RESUME) the given docs. A resume
        keeps the server-side dedup cursor; the response's ``lcs`` map
        tells the client where that cursor stands per doc."""
        req = {"t": "join", "docs": docs}
        if client_id is not None:
            req["client_id"] = client_id
        self.sock.sendall(encode_json(req))
        resp = self.recv_json()
        assert resp["t"] == "joined", resp
        self.client_id = resp["client_id"]
        self.rows.update(resp["rows"])
        self.lcs = dict(resp.get("lcs", {}))
        self.epoch = resp.get("epoch", 0)
        return self.rows

    def send_ops(self, texts: List[str], ops: np.ndarray,
                 props: Optional[List[dict]] = None) -> None:
        self.sock.sendall(encode_op_batch(texts, ops, props=props))

    def recv_json(self) -> dict:
        ftype, payload = read_frame(self._rd)
        assert ftype == ord("J"), ftype
        return json.loads(payload)

    def close(self) -> None:
        try:
            self.sock.sendall(encode_json({"t": "bye"}))
        except OSError:
            pass
        self.sock.close()
