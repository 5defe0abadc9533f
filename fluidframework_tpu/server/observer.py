"""Observer fanout: one encoded window, N read-only subscribers.

The transport half of the read plane (``server.read_plane`` is the
encode half). Reference counterpart: Broadcaster → Redis pub/sub →
socket.io rooms in Routerlicious (SURVEY.md §1) — the reference
encodes a sequenced op once and lets the pub/sub tier fan the bytes;
slow consumers are disconnected, not allowed to backpressure the
sequencer.

Two tiers, deliberately split so the fanout economics are benchable
without sockets:

- :class:`ObserverHub` — transport-agnostic multiplexer. Holds a
  retained ring of the last ``ring`` encoded windows (resubscribe
  replay), a per-subscriber byte budget (``server.admission``'s
  :class:`TokenBucket` with whole-window grant semantics), shed
  accounting, and the delivery/staleness gauges. ``publish`` hands the
  SAME bytes object to every subscriber's sink — the marginal cost per
  subscriber is a budget check and a sink call, never a re-encode.
- :class:`ObserverDoor` — the asyncio socket tier (the
  ``ColumnarAlfred`` idiom: own loop thread,
  ``call_soon_threadsafe`` pushes). Wire protocol (the columnar
  framing, ``columnar_ingress``):

  - client → server ``J`` ``{"t": "subscribe", "from_wid"?, "name"?}``
    → server ``J`` ``{"t": "subscribed", "sid", "next_wid",
    "ring_from", "catchup_needed"}``. With ``from_wid`` inside the
    retained ring the gap replays immediately (reconnect = replay, not
    rehydrate); ``catchup_needed`` means the ring no longer reaches
    back that far — run the generation-diff ladder first
    (docs/READ_PLANE.md).
  - server → client: the read plane's window runs verbatim (``J``
    window header, then ``B``/``R``/``T``/``J`` record frames).
  - a shed subscriber gets ``J`` ``{"t": "gap", "wid"}`` (outside the
    budget — the notice must arrive precisely when data could not) and
    is parked until it resubscribes from its last applied window.

Slow-reader policy: a subscriber whose byte budget cannot take a WHOLE
window is shed that window (``observer_sheds_total``) and parked —
never a partial frame, never a stalled publisher. The write plane is
fully decoupled: ``publish`` does no socket I/O (sinks enqueue onto
the asyncio transport) and never blocks on a reader.
"""

from __future__ import annotations

import asyncio
import json
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from ..utils.telemetry import REGISTRY
from .admission import TokenBucket
from .columnar_ingress import encode_json, read_frame

#: delivery-rate gauge window (seconds)
_RATE_WINDOW_S = 5.0


class _Sub:
    __slots__ = ("sid", "name", "sink", "bucket", "last_wid",
                 "delivered_windows", "delivered_ops",
                 "delivered_bytes", "sheds", "parked", "t_subscribed")

    def __init__(self, sid: int, name: str, sink: Callable[[bytes], None],
                 bucket: Optional[TokenBucket], last_wid: int):
        self.sid = sid
        self.name = name
        self.sink = sink
        self.bucket = bucket
        self.last_wid = last_wid
        self.delivered_windows = 0
        self.delivered_ops = 0
        self.delivered_bytes = 0
        self.sheds = 0
        self.parked = False
        self.t_subscribed = time.time()


class ObserverHub:
    """Encode-once fanout hub; see module docstring. ``ring`` windows
    are retained for resubscribe replay; ``byte_rate``/``byte_burst``
    are the DEFAULT per-subscriber budget (bytes/sec; ``None`` = no
    budget — in-process bench sinks)."""

    def __init__(self, ring: int = 256,
                 byte_rate: Optional[float] = None,
                 byte_burst: Optional[float] = None,
                 tracker=None):
        from .read_plane import STALENESS
        self._lock = threading.Lock()
        self._subs: Dict[int, _Sub] = {}
        self._next_sid = 1
        self._wid = 0
        #: (wid, payload bytes, n_ops, t_encoded)
        self._ring: deque = deque(maxlen=ring)
        self.byte_rate = byte_rate
        self.byte_burst = byte_burst
        self.tracker = tracker if tracker is not None else STALENESS
        self._delivered: deque = deque()   # (t, ops) for the rate gauge
        self.windows_published = 0
        self.ops_published = 0

    # ------------------------------------------------------------ windows

    def next_wid(self) -> int:
        with self._lock:
            self._wid += 1
            return self._wid

    def oldest_retained(self) -> Optional[int]:
        with self._lock:
            return self._ring[0][0] if self._ring else None

    def publish(self, wid: int, payload: bytes, n_ops: int) -> int:
        """Fan one encoded window to every live subscriber; returns the
        number of subscribers it was delivered to. The payload bytes
        are shared — no copy, no re-encode, per subscriber."""
        now = time.monotonic()
        t_wall = time.time()
        nbytes = len(payload)
        delivered = 0
        with self._lock:
            self._ring.append((wid, payload, n_ops, t_wall))
            self.windows_published += 1
            self.ops_published += n_ops
            subs = list(self._subs.values())
        for sub in subs:
            if sub.parked:
                continue
            if sub.bucket is not None:
                got = sub.bucket.grant(nbytes, now)
                if got < nbytes:
                    # whole-window semantics: hand back the partial
                    # grant and shed — never a torn window
                    sub.bucket.tokens += got
                    sub.sheds += 1
                    sub.parked = True
                    REGISTRY.inc("observer_sheds_total")
                    try:
                        sub.sink(encode_json({"t": "gap", "wid": wid}))
                    except Exception:
                        pass
                    continue
            try:
                sub.sink(payload)
            except Exception:
                # a dead sink is an unsubscribe, not a publish error
                self.unsubscribe(sub.sid)
                continue
            sub.last_wid = wid
            sub.delivered_windows += 1
            sub.delivered_ops += n_ops
            sub.delivered_bytes += nbytes
            delivered += 1
        self.tracker.observe(time.time() - t_wall)
        self._note_rate(n_ops * delivered)
        return delivered

    def _note_rate(self, ops: int) -> None:
        now = time.monotonic()
        with self._lock:
            self._delivered.append((now, ops))
            while self._delivered and \
                    self._delivered[0][0] < now - _RATE_WINDOW_S:
                self._delivered.popleft()
            total = sum(n for _, n in self._delivered)
            span = _RATE_WINDOW_S if len(self._delivered) > 1 else 1.0
        REGISTRY.set_gauge("observer_delivery_ops_per_sec", total / span)
        REGISTRY.set_gauge("observer_subscribers",
                           float(len(self._subs)))

    # -------------------------------------------------------- subscribers

    def subscribe(self, sink: Callable[[bytes], None],
                  name: str = "", from_wid: Optional[int] = None,
                  byte_rate: Optional[float] = None,
                  byte_burst: Optional[float] = None) -> dict:
        """Register a sink; replay the retained ring from ``from_wid``
        when it still reaches back that far. Returns ``{"sid",
        "next_wid", "ring_from", "catchup_needed"}`` — ``catchup_needed``
        means the caller must run the generation-diff ladder before the
        live stream is gapless."""
        rate = byte_rate if byte_rate is not None else self.byte_rate
        burst = byte_burst if byte_burst is not None else self.byte_burst
        bucket = TokenBucket(rate, burst) if rate else None
        with self._lock:
            sid = self._next_sid
            self._next_sid += 1
            ring = list(self._ring)
            last = from_wid - 1 if from_wid is not None else self._wid
            sub = _Sub(sid, name or f"observer-{sid}", sink, bucket,
                       last)
            self._subs[sid] = sub
            # the replay stays under the lock: a window published
            # meanwhile reaches this sink after the replayed ones
            ring_from = ring[0][0] if ring else None
            catchup_needed = bool(
                from_wid is not None and ring and from_wid < ring_from)
            if from_wid is not None and not catchup_needed:
                for wid, payload, n_ops, _t in ring:
                    if wid < from_wid:
                        continue
                    # replay rides the same budget as live delivery
                    if sub.bucket is not None:
                        got = sub.bucket.grant(len(payload),
                                               time.monotonic())
                        if got < len(payload):
                            sub.bucket.tokens += got
                            sub.sheds += 1
                            sub.parked = True
                            REGISTRY.inc("observer_sheds_total")
                            try:
                                sub.sink(encode_json({"t": "gap",
                                                      "wid": wid}))
                            except Exception:
                                pass
                            break
                    sub.sink(payload)
                    sub.last_wid = wid
                    sub.delivered_windows += 1
                    sub.delivered_ops += n_ops
                    sub.delivered_bytes += len(payload)
        REGISTRY.inc("observer_subscribes_total")
        return {"sid": sid, "next_wid": sub.last_wid + 1,
                "ring_from": ring_from, "catchup_needed": catchup_needed}

    def unsubscribe(self, sid: int) -> None:
        with self._lock:
            self._subs.pop(sid, None)

    def resume(self, sid: int, from_wid: int) -> bool:
        """Un-park a shed subscriber, replaying [from_wid..] from the
        ring; False when the ring no longer reaches (catch-up needed)."""
        with self._lock:    # held through the replay, as in subscribe
            sub = self._subs.get(sid)
            ring = self._ring
            if sub is None:
                return False
            if ring and from_wid < ring[0][0]:
                return False
            for wid, payload, n_ops, _t in ring:
                if wid < from_wid:
                    continue
                sub.sink(payload)
                sub.last_wid = wid
                sub.delivered_windows += 1
                sub.delivered_ops += n_ops
                sub.delivered_bytes += len(payload)
            sub.parked = False
            return True

    # ------------------------------------------------------------- health

    def readers(self) -> List[dict]:
        """Per-subscriber rows for ``/debug/readers`` and healthz: lag
        (windows behind the newest), delivered volume, shed count."""
        with self._lock:
            wid = self._wid
            subs = list(self._subs.values())
        return [{
            "sid": s.sid, "name": s.name,
            "last_wid": s.last_wid, "lag_windows": max(0, wid - s.last_wid),
            "delivered_windows": s.delivered_windows,
            "delivered_ops": s.delivered_ops,
            "delivered_bytes": s.delivered_bytes,
            "sheds": s.sheds, "parked": s.parked,
            "age_s": round(time.time() - s.t_subscribed, 3),
        } for s in subs]

    def stats(self) -> dict:
        rows = self.readers()
        return {
            "subscribers": len(rows),
            "windows_published": self.windows_published,
            "ops_published": self.ops_published,
            "worst_lag_windows": max((r["lag_windows"] for r in rows),
                                     default=0),
            "sheds": sum(r["sheds"] for r in rows),
            "parked": sum(1 for r in rows if r["parked"]),
            "staleness_p99_s": self.tracker.p99(),
        }


# ----------------------------------------------------------------- door

class ObserverDoor:
    """Asyncio socket tier over one :class:`ObserverHub`: each accepted
    connection subscribes with one control frame and then receives the
    hub's window runs verbatim. ``gen_store`` (a
    ``SummaryGenerationStore``) plus ``family`` enable the catch-up
    rung: a ``{"t": "catchup", "from_gen"}`` request answers with a
    ``J`` frame carrying the generation-diff metadata (the diff itself
    travels out-of-band through the store — observers on the same host
    read the ladder directly; remote transports would pickle it)."""

    def __init__(self, hub: Optional[ObserverHub] = None,
                 host: str = "127.0.0.1", port: int = 0,
                 byte_rate: Optional[float] = None,
                 byte_burst: Optional[float] = None,
                 gen_store=None, family: str = "string"):
        self.hub = hub if hub is not None else ObserverHub()
        self.host = host
        self.port = port
        self.byte_rate = byte_rate
        self.byte_burst = byte_burst
        self.gen_store = gen_store
        self.family = family
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._server: Optional[asyncio.AbstractServer] = None
        self._thread: Optional[threading.Thread] = None
        self._ready = threading.Event()
        self.connections = 0

    # ---------------------------------------------------------- lifecycle

    def start_in_thread(self) -> "ObserverDoor":
        self._thread = threading.Thread(target=self._run,
                                        name="observer-door", daemon=True)
        self._thread.start()
        if not self._ready.wait(10):
            raise RuntimeError("observer door failed to start")
        return self

    def _run(self) -> None:
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._loop = loop

        async def boot():
            self._server = await asyncio.start_server(
                self._handle, self.host, self.port)
            self.port = self._server.sockets[0].getsockname()[1]
            self._ready.set()

        loop.run_until_complete(boot())
        try:
            loop.run_forever()
        finally:
            loop.close()

    def stop(self) -> None:
        loop = self._loop
        if loop is None:
            return

        def shutdown():
            if self._server is not None:
                self._server.close()
            loop.stop()

        loop.call_soon_threadsafe(shutdown)
        if self._thread is not None:
            self._thread.join(timeout=5)

    # --------------------------------------------------------- connection

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        self.connections += 1
        loop = asyncio.get_running_loop()
        sid = None
        try:
            req = await self._read_json(reader)
            if req.get("t") != "subscribe":
                writer.write(encode_json(
                    {"t": "error", "reason": "expected subscribe"}))
                await writer.drain()
                return

            def sink(payload: bytes) -> None:
                # publish runs on the engine's flush thread; the write
                # must hop onto the loop (transports are not threadsafe)
                loop.call_soon_threadsafe(self._write, writer, payload)

            ack = self.hub.subscribe(
                sink, name=str(req.get("name", "")),
                from_wid=req.get("from_wid"),
                byte_rate=req.get("byte_rate", self.byte_rate),
                byte_burst=req.get("byte_burst", self.byte_burst))
            sid = ack["sid"]
            writer.write(encode_json({"t": "subscribed", **ack}))
            await writer.drain()
            # the read side only carries control: catchup/resume/close
            while True:
                req = await self._read_json(reader)
                if req.get("t") == "resume":
                    ok = self.hub.resume(sid, int(req["from_wid"]))
                    writer.write(encode_json(
                        {"t": "resumed" if ok else "catchup_needed"}))
                    await writer.drain()
                elif req.get("t") == "catchup":
                    writer.write(encode_json(self._catchup_info(req)))
                    await writer.drain()
                elif req.get("t") == "close":
                    return
        except (asyncio.IncompleteReadError, ConnectionError, OSError,
                ValueError):
            pass
        finally:
            if sid is not None:
                self.hub.unsubscribe(sid)
            try:
                writer.close()
            except Exception:
                pass

    def _catchup_info(self, req: dict) -> dict:
        """Answer the catch-up rung: which generations the ladder holds
        and whether a diff from the client's generation is possible."""
        if self.gen_store is None:
            return {"t": "catchup_info", "available": False,
                    "reason": "no generation store attached"}
        gens = self.gen_store.generations()
        have = req.get("from_gen")
        return {"t": "catchup_info", "available": bool(gens),
                "generations": gens,
                "family": self.family,
                "directory": self.gen_store.directory,
                "diff_ok": bool(gens) and have is not None
                and have in gens and have != gens[-1]}

    @staticmethod
    def _write(writer: asyncio.StreamWriter, payload: bytes) -> None:
        try:
            writer.write(payload)
        except Exception:
            pass

    @staticmethod
    async def _read_json(reader: asyncio.StreamReader) -> dict:
        import struct as _struct
        import zlib as _zlib
        hdr = await reader.readexactly(5)
        ftype, length = _struct.unpack("<BI", hdr)
        payload = await reader.readexactly(length)
        (crc,) = _struct.unpack("<I", await reader.readexactly(4))
        if crc != _zlib.crc32(payload) or ftype != ord("J"):
            raise ValueError("bad control frame")
        return json.loads(payload)


def read_observer_frame(sock):
    """Blocking client-side frame read (the columnar framing)."""
    return read_frame(sock)
