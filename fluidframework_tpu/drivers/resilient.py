"""Resilient clients: reconnect/resubmit wrappers for both front doors.

Reference counterpart: the Fluid client's ``DeltaManager`` reconnect
pipeline (SURVEY.md §2.8) — on socket loss the client reconnects with
backoff, replays its outbound queue, and relies on server-side
``(clientId, clientSequenceNumber)`` dedup to collapse resubmits of ops
that were already sequenced. Two wrappers here:

- :class:`ResilientConnection` — the framed-JSON delta stream
  (``server.ingress``). Tracks unacked ops, reconnects with decorrelated
  jitter, resumes its seat via the ``resync`` frame, applies the
  catch-up tail, **renumbers** still-pending ops contiguously above the
  server's ``last_client_seq`` cursor (an op that was sequenced but
  never became durable — a crash between sequencing and the log append —
  burns its clientSeq; resending under the old number would nack
  forever), and resubmits in order. An op is "acked" when its sequenced
  form comes back on the stream or a ``dup_ack`` frame vouches for the
  original seq of a resubmit.

- :class:`ResilientColumnarClient` — the binary columnar door
  (``server.columnar_ingress``). Rejoins with its prior ``client_id``
  (keeping the server-side dedup cursor), then resubmits every pending
  op per doc in clientSeq order; already-durable ops come back as
  idempotent dup-acks with their original seq. No renumbering needed:
  the columnar engine never leaves a sequenced op un-logged alive (a
  fault between sequencing and the append poisons the engine, and a
  rebuild replays only the durable log).

Both are deterministic under injected ``random.Random`` (reconnect
schedules replay exactly in a seeded chaos soak) and track reconnect
latencies / resubmit counts (``tools/chaos_soak.py`` reports them).

Both also honor the admission plane's ``throttled`` frames
(``server.admission``): a shed op's clientSeq is NOT burned (it was
refused before the sequencer saw it), so the op parks locally and is
resubmitted with the SAME number, in cseq order, after a jittered
``retry_after_ms`` — never a blind instant resubmit, never a silent
drop. Ops submitted while a throttle episode is pending park too and
ride the same ordered resend.
"""

from __future__ import annotations

import itertools
import json
import socket
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..core.protocol import MessageType
from ..server import columnar_ingress as colwire
from ..server import wire
from ..server.deli import NackReason
from ..utils.backoff import Backoff
from ..utils.telemetry import REGISTRY


class ResilientConnection:
    """Reconnecting wrapper for one doc's JSON delta stream.

    ``submit`` records the op as pending *before* writing it to the
    socket, so a send racing a socket death can never lose track of an
    op: whatever the socket's fate, the op is either acked through the
    stream or resubmitted after the next resync. ``op_acks`` maps each
    submit's uid to its sequence number once acked — exactly once, by
    construction of the server's durable dedup ledger.
    """

    def __init__(self, host: str, port: int, doc_id: str,
                 rng=None, attempts: int = 8,
                 base_delay: float = 0.02,
                 on_op: Optional[Callable] = None,
                 tenant: Optional[str] = None,
                 dial_timeout: float = 10.0,
                 recv_timeout: Optional[float] = None,
                 on_ack: Optional[Callable] = None):
        self.host = host
        self.port = port
        self.doc_id = doc_id
        self.attempts = attempts
        #: tenant identity carried on connect/resync so server-side
        #: admission budgets apply (None = per-client default tenant)
        self.tenant = tenant
        #: connect()/dial timeout; also bounds each handshake recv
        self.dial_timeout = dial_timeout
        #: steady-state recv timeout. None = block forever (an idle but
        #: healthy stream is NOT an error); a value turns prolonged
        #: stream silence into a reconnect — opt-in, since any quiet
        #: period longer than this looks like a dead peer
        self.recv_timeout = recv_timeout
        self._backoff = Backoff(base=base_delay, cap=1.0, rng=rng)
        self._lock = threading.RLock()
        self._acked_cv = threading.Condition(self._lock)
        #: serializes op WRITES to the socket: a resend wave (retry
        #: timer / reconnect, on their own threads) must hit the wire
        #: as one ordered run — a concurrent submit interleaving
        #: mid-wave would reorder clientSeqs and gap-nack. Always
        #: acquired while still holding ``_lock`` (released after the
        #: send), so wire order matches registration order.
        self._send_lock = threading.Lock()
        self._uid = itertools.count(1)
        #: cseq → (uid, op fields) — in submission order (OrderedDict so
        #: renumbering preserves it)
        self._pending: "OrderedDict[int, Tuple[int, dict]]" = OrderedDict()
        self.op_acks: Dict[int, int] = {}    # uid → seq (exactly once)
        self.nacks: List[dict] = []          # genuine rejections
        self._client_seq = 0
        self.client_id: Optional[int] = None
        self.epoch = 0
        self.last_seen_seq = 0
        self.reconnects = 0
        self.resubmits = 0
        self.dup_acked = 0
        self.throttled = 0           # throttled frames received
        self.throttle_resubmits = 0  # ops re-sent after a retry_after
        #: cseqs currently parked behind a throttle (resent, in order,
        #: by the retry timer — never renumbered, never silently lost)
        self._throttled: set = set()
        #: uids that were EVER throttled — their ack latency includes
        #: the deliberate backoff, so latency SLO accounting (the tenant
        #: sim's admitted-ack p99) excludes them
        self.throttled_uids: set = set()
        self._retry_timer: Optional[threading.Timer] = None
        self._retry_at = 0.0
        self.reconnect_latencies: List[float] = []
        self._op_listeners: List[Callable] = []
        self._ack_listeners: List[Callable] = []
        self._closed = False
        self._sock: Optional[socket.socket] = None
        if on_op is not None:
            self._op_listeners.append(on_op)
        if on_ack is not None:
            self._ack_listeners.append(on_ack)
        self._connect_first()
        self._reader = threading.Thread(target=self._read_loop,
                                        daemon=True)
        self._reader.start()

    # ------------------------------------------------------------- connect

    def _dial(self) -> socket.socket:
        # the dial timeout also bounds handshake recvs (create_connection
        # leaves it on the socket); _settle() switches to the
        # steady-state recv_timeout once the stream is live
        return socket.create_connection((self.host, self.port),
                                        timeout=self.dial_timeout)

    def _settle(self, sock: socket.socket) -> None:
        try:
            sock.settimeout(self.recv_timeout)
        except OSError:
            pass

    def _connect_first(self) -> None:
        last: Optional[Exception] = None
        self._backoff.reset()
        for i in range(self.attempts):
            try:
                sock = self._dial()
                hello_req = {"t": "connect", "doc": self.doc_id,
                             "resilient": True}
                if self.tenant is not None:
                    hello_req["tenant"] = self.tenant
                wire.send_frame(sock, hello_req)
                hello = wire.recv_frame(sock)
                if hello.get("t") != "connected":
                    raise wire.WireError(f"bad hello: {hello}")
                self.client_id = int(hello["client_id"])
                self.epoch = hello.get("epoch", 0)
                # seed the ref_seq cursor from the hello's current doc
                # seq: the first submit must reference live state, not
                # seq 0 (below the MSN floor on a long-lived doc)
                self.last_seen_seq = max(self.last_seen_seq,
                                         int(hello.get("seq", 0)))
                self._settle(sock)
                self._sock = sock
                return
            except OSError as e:        # noqa: PERF203 — retry loop
                last = e
                if i + 1 < self.attempts:
                    time.sleep(self._backoff.next_delay())
        raise ConnectionError(
            f"ingress {self.host}:{self.port} unreachable") from last

    def _reconnect(self) -> None:
        """Resync loop: new socket, reclaim the seat, absorb the catch-up
        tail, renumber + resubmit whatever is still pending. Runs on the
        reader thread (the only frame consumer, so no frames race it)."""
        t0 = time.perf_counter()
        self._backoff.reset()
        last: Optional[Exception] = None
        for i in range(self.attempts):
            if self._closed:
                return
            time.sleep(self._backoff.next_delay())
            try:
                sock = self._dial()
                resync_req = {
                    "t": "resync", "doc": self.doc_id,
                    "client_id": self.client_id,
                    "from_seq": self.last_seen_seq}
                if self.tenant is not None:
                    resync_req["tenant"] = self.tenant
                wire.send_frame(sock, resync_req)
                # the stream attaches server-side BEFORE the catch-up
                # fetch (no loss window, duplicate delivery possible):
                # live op frames may arrive ahead of the resynced frame
                while True:
                    frame = wire.recv_frame(sock)
                    if frame.get("t") == "resynced":
                        break
                    self._dispatch(frame)
            except (OSError, wire.WireError) as e:  # noqa: PERF203
                last = e
                continue
            # catch-up tail first: every still-durable in-flight op acks
            # here (broadcast is seq-ordered, the tail is complete up to
            # now) — what remains pending is exactly the never-durable set
            self._settle(sock)
            for m in frame.get("msgs", []):
                self._dispatch({"t": "op", "msg": m})
            self.epoch = frame.get("epoch", self.epoch)
            lcs = int(frame.get("last_client_seq", 0))
            with self._lock:
                # a full resubmit supersedes any throttle episode (the
                # renumbered resend below covers every pending op)
                self._throttled.clear()
                # renumber the survivors contiguously past the server's
                # cursor: burned clientSeqs (sequenced-but-never-durable)
                # are skipped, submission order is preserved
                survivors = list(self._pending.values())
                self._pending.clear()
                self._client_seq = lcs
                resend = []
                for uid, op in survivors:
                    self._client_seq += 1
                    op = dict(op, client_seq=self._client_seq)
                    self._pending[self._client_seq] = (uid, op)
                    resend.append(op)
                self._sock = sock
                self._send_lock.acquire()
            try:
                for op in resend:
                    self.resubmits += 1
                    try:
                        wire.send_frame(sock, op)
                    except OSError:
                        break   # died again: next reconnect resubmits
            finally:
                self._send_lock.release()
            self.reconnects += 1
            REGISTRY.inc("session_reconnects_total")
            self.reconnect_latencies.append(time.perf_counter() - t0)
            return
        if not self._closed:
            raise ConnectionError(
                f"resync to {self.host}:{self.port} failed "
                f"after {self.attempts} attempts") from last

    # -------------------------------------------------------------- stream

    def _read_loop(self) -> None:
        while not self._closed:
            try:
                frame = wire.recv_frame(self._sock)
            except (wire.WireError, OSError):
                if self._closed:
                    return
                try:
                    self._reconnect()
                except ConnectionError:
                    self._closed = True
                    with self._acked_cv:
                        self._acked_cv.notify_all()
                    return
                continue
            self._dispatch(frame)

    def _dispatch(self, frame: dict) -> None:
        t = frame.get("t")
        if t == "op":
            m = frame["msg"]
            seq = int(m["seq"])
            with self._acked_cv:
                if seq > self.last_seen_seq:
                    self.last_seen_seq = seq
                if m["client_id"] == self.client_id and \
                        m["type"] not in (int(MessageType.NOOP),
                                          int(MessageType.CLIENT_JOIN),
                                          int(MessageType.CLIENT_LEAVE)):
                    self._ack(int(m["client_seq"]), seq)
            for fn in list(self._op_listeners):
                fn(m)
        elif t == "dup_ack":
            with self._acked_cv:
                self.dup_acked += 1
                self._ack(int(frame["client_seq"]), int(frame["seq"]))
        elif t == "throttled":
            # admission shed: the op never reached the sequencer, its
            # cseq is NOT burned — park it and resubmit the SAME number
            # after a jittered retry_after, in cseq order (blind instant
            # resubmit would just be shed again)
            with self._acked_cv:
                self.throttled += 1
                REGISTRY.inc("client_throttled_total")
                cs = frame.get("client_seq")
                if cs in self._pending:
                    self._throttled.add(cs)
                    self.throttled_uids.add(self._pending[cs][0])
                self._schedule_retry(
                    float(frame.get("retry_after_ms", 50.0)))
        elif t == "nack":
            reason = frame.get("reason")
            seq = frame.get("seq", -1)
            with self._acked_cv:
                if reason == int(NackReason.DUPLICATE) and seq > 0:
                    # engine-tier idempotent dup-ack rides the nack frame
                    self.dup_acked += 1
                    self._ack(int(frame["client_seq"]), int(seq))
                else:
                    self._pending.pop(frame.get("client_seq"), None)
                    self.nacks.append(frame)
                    self._acked_cv.notify_all()

    def _ack(self, client_seq: int, seq: int) -> None:
        ent = self._pending.pop(client_seq, None)
        if ent is not None:
            uid, _op = ent
            self.op_acks[uid] = seq
            self._acked_cv.notify_all()
            for fn in self._ack_listeners:
                fn(uid, seq)

    # ------------------------------------------------------------ throttling

    def _schedule_retry(self, retry_ms: float) -> None:
        """Arm ONE timer per throttle episode (lock held by caller),
        jittered so a fleet of throttled clients does not resubmit in
        lockstep. Retry hints GROW as the server sheds more of the run
        (they cover the whole parked backlog) — a later, larger hint
        extends the armed timer instead of being dropped, so the resend
        fires once, when the budget can actually take the run."""
        if self._closed:
            return
        delay = (max(1.0, retry_ms) / 1000.0) \
            * self._backoff.rng.uniform(1.0, 1.5)
        fire_at = time.monotonic() + delay
        if self._retry_timer is not None:
            if fire_at <= self._retry_at:
                return
            self._retry_timer.cancel()
        self._retry_at = fire_at
        t: Optional[threading.Timer] = None
        t = threading.Timer(delay,
                            lambda: self._resubmit_throttled(t))
        t.daemon = True
        self._retry_timer = t
        t.start()

    def _resubmit_throttled(self, timer) -> None:
        with self._lock:
            if self._retry_timer is not timer:
                return   # superseded by a later re-arm (or shutdown)
            self._retry_timer = None
            if self._closed:
                return
            cseqs = sorted(cs for cs in self._throttled
                           if cs in self._pending)
            self._throttled.clear()
            ops = [self._pending[cs][1] for cs in cseqs]
            sock = self._sock
            self._send_lock.acquire()
        try:
            for op in ops:
                self.throttle_resubmits += 1
                try:
                    wire.send_frame(sock, op)
                except OSError:
                    break   # reader notices the dead socket and resyncs
        finally:
            self._send_lock.release()

    def on_ack(self, fn: Callable) -> None:
        """Register an ack listener ``fn(uid, seq)`` (called with the
        connection lock held — keep it cheap)."""
        self._ack_listeners.append(fn)

    def on_op(self, fn: Callable) -> None:
        self._op_listeners.append(fn)

    # -------------------------------------------------------------- submit

    def submit(self, contents: Any, type: MessageType = MessageType.OP,
               ref_seq: Optional[int] = None,
               address: Optional[str] = None,
               deadline_ms: Optional[float] = None) -> int:
        """Submit one op; returns its uid (stable across renumbering —
        look the ack up in ``op_acks[uid]``). ``deadline_ms`` rides the
        frame as the op's ingress deadline budget (admission sheds work
        it estimates would sequence too late)."""
        if self._closed:
            raise ConnectionError("submit on closed connection")
        with self._lock:
            self._client_seq += 1
            uid = next(self._uid)
            op = {"t": "op", "contents": contents, "type": int(type),
                  "client_seq": self._client_seq,
                  "ref_seq": self.last_seen_seq if ref_seq is None
                  else ref_seq,
                  "address": address}
            if deadline_ms is not None:
                op["deadline_ms"] = deadline_ms
            # pending BEFORE the send: a socket death mid-write still
            # leaves the op tracked for resubmit
            self._pending[self._client_seq] = (uid, op)
            if self._retry_timer is not None:
                # throttle episode in flight: sending now would only be
                # shed behind the fence — park locally, the retry timer
                # resends the whole run in cseq order
                self._throttled.add(self._client_seq)
                self.throttled_uids.add(uid)
                return uid
            sock = self._sock
            self._send_lock.acquire()
        try:
            wire.send_frame(sock, op)
        except OSError:
            pass    # reader notices the dead socket and resyncs
        finally:
            self._send_lock.release()
        return uid

    def wait_idle(self, timeout: float = 30.0) -> bool:
        """Block until every submitted op is acked (or nacked); False on
        timeout or if the connection gave up reconnecting."""
        deadline = time.monotonic() + timeout
        with self._acked_cv:
            while self._pending and not self._closed:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._acked_cv.wait(left)
            return not self._pending

    @property
    def pending_count(self) -> int:
        with self._lock:
            return len(self._pending)

    # ------------------------------------------------------------- chaos

    def kill_socket(self) -> None:
        """Simulate network loss: hard-close the raw socket. The reader
        thread notices and runs the resync path."""
        sock = self._sock
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            sock.close()

    def close(self) -> None:
        self._closed = True
        timer = self._retry_timer
        if timer is not None:
            timer.cancel()
        sock = self._sock
        try:
            wire.send_frame(sock, {"t": "disconnect"})
        except (OSError, AttributeError):
            pass
        if sock is not None:
            sock.close()
        with self._acked_cv:
            self._acked_cv.notify_all()


class ResilientColumnarClient:
    """Reconnecting wrapper for the binary columnar door.

    Per-doc clientSeq spaces (the columnar sequencer dedups per ``(doc,
    client)``); ``submit`` assigns the next cseq for the doc and records
    the op pending before the send. On socket loss the reader redials
    with jitter, re-joins with the SAME ``client_id`` (the server keeps
    the seat and its dedup cursor), and resubmits every pending op in
    cseq order — already-durable ones come back dup-acked with their
    original seq via the engine's ledger.
    """

    def __init__(self, host: str, port: int, docs: List[str],
                 rng=None, attempts: int = 8,
                 base_delay: float = 0.02,
                 tenant: Optional[str] = None,
                 dial_timeout: float = 10.0,
                 recv_timeout: Optional[float] = None,
                 on_ack: Optional[Callable] = None):
        self.host = host
        self.port = port
        self.docs = list(docs)
        self.attempts = attempts
        self.tenant = tenant
        self.dial_timeout = dial_timeout
        #: None = block forever on a quiet stream; a value turns
        #: prolonged silence into a rejoin (opt-in, see
        #: ResilientConnection.recv_timeout)
        self.recv_timeout = recv_timeout
        self._backoff = Backoff(base=base_delay, cap=1.0, rng=rng)
        self._lock = threading.RLock()
        self._acked_cv = threading.Condition(self._lock)
        #: serializes op WRITES to the socket: a resend wave (retry
        #: timer / reconnect, on their own threads) must hit the wire
        #: as one ordered run — a concurrent submit interleaving
        #: mid-wave would reorder clientSeqs and gap-nack. Always
        #: acquired while still holding ``_lock`` (released after the
        #: send), so wire order matches registration order.
        self._send_lock = threading.Lock()
        self._closed = False
        self.client_id: Optional[int] = None
        self.rows: Dict[str, int] = {}
        self.row_doc: Dict[int, str] = {}
        self.lcs: Dict[str, int] = {}
        self.epoch = 0
        self._cseq: Dict[str, int] = {d: 0 for d in self.docs}
        #: doc → OrderedDict[cseq → (kind, a0, a1, payload, ref)]
        self._pending: Dict[str, "OrderedDict[int, tuple]"] = {
            d: OrderedDict() for d in self.docs}
        self.acks: Dict[str, Dict[int, int]] = {d: {} for d in self.docs}
        self.nacks: List[tuple] = []
        self.reconnects = 0
        self.resubmits = 0
        self.dup_acked = 0
        self.throttled = 0
        self.throttle_resubmits = 0
        #: doc → cseqs parked behind a throttle (resent in cseq order
        #: by the retry timer)
        self._throttled: Dict[str, set] = {d: set() for d in self.docs}
        #: doc → cseqs EVER throttled (latency accounting excludes them:
        #: their ack time includes the deliberate backoff)
        self.throttled_cseqs: Dict[str, set] = {d: set()
                                                for d in self.docs}
        self._retry_timer: Optional[threading.Timer] = None
        self._retry_at = 0.0
        self._ack_listeners: List[Callable] = []
        if on_ack is not None:
            self._ack_listeners.append(on_ack)
        self.reconnect_latencies: List[float] = []
        self._sock = self._join(first=True)
        self._reader = threading.Thread(target=self._read_loop,
                                        daemon=True)
        self._reader.start()

    # ------------------------------------------------------------- connect

    def _join(self, first: bool = False) -> socket.socket:
        sock = colwire.connect_with_backoff(
            self.host, self.port, attempts=self.attempts,
            timeout=self.dial_timeout)
        req = {"t": "join", "docs": self.docs}
        if self.tenant is not None:
            req["tenant"] = self.tenant
        if not first:
            req["client_id"] = self.client_id
        sock.sendall(colwire.encode_json(req))
        ftype, payload = colwire.read_frame(sock)
        resp = json.loads(payload)
        if resp.get("t") != "joined":
            raise ConnectionError(f"bad join response: {resp}")
        self.client_id = resp["client_id"]
        self.rows.update(resp["rows"])
        self.row_doc = {r: d for d, r in self.rows.items()}
        self.lcs = dict(resp.get("lcs", {}))
        self.epoch = resp.get("epoch", 0)
        try:
            sock.settimeout(self.recv_timeout)
        except OSError:
            pass
        return sock

    def _reconnect(self) -> None:
        t0 = time.perf_counter()
        self._backoff.reset()
        last: Optional[Exception] = None
        for _ in range(self.attempts):
            if self._closed:
                return
            time.sleep(self._backoff.next_delay())
            try:
                sock = self._join()
            except (OSError, ConnectionError) as e:  # noqa: PERF203
                last = e
                continue
            with self._lock:
                self._sock = sock
                # the full resubmit below supersedes any throttle episode
                for shed in self._throttled.values():
                    shed.clear()
                resend = [(d, list(pend.items()))
                          for d, pend in self._pending.items() if pend]
                self._send_lock.acquire()
            # resubmit per doc in cseq order: durable ones dup-ack with
            # their original seq, the rest sequence fresh — per-doc order
            # is preserved because each doc's resend list is ordered
            try:
                for doc, ops in resend:
                    for cs, (kind, a0, a1, payload, ref) in ops:
                        self.resubmits += 1
                        self._send_one(sock, doc, cs, kind, a0, a1,
                                       payload, ref)
            finally:
                self._send_lock.release()
            self.reconnects += 1
            REGISTRY.inc("session_reconnects_total")
            self.reconnect_latencies.append(time.perf_counter() - t0)
            return
        if not self._closed:
            raise ConnectionError(
                f"columnar rejoin to {self.host}:{self.port} failed "
                f"after {self.attempts} attempts") from last

    # -------------------------------------------------------------- stream

    def _read_loop(self) -> None:
        while not self._closed:
            try:
                ftype, payload = colwire.read_frame(self._sock)
            except (OSError, ConnectionError):
                if self._closed:
                    return
                try:
                    self._reconnect()
                except ConnectionError:
                    self._closed = True
                    with self._acked_cv:
                        self._acked_cv.notify_all()
                    return
                continue
            if ftype != ord("J"):
                continue
            resp = json.loads(payload)
            if resp.get("t") == "acks":
                rows = resp.get("rows") or [None] * len(resp["acks"])
                with self._acked_cv:
                    for (cs, sq), row in zip(resp["acks"], rows):
                        doc = self.row_doc.get(row)
                        if doc is None:
                            continue
                        if sq > 0:
                            if self._pending[doc].pop(cs, None) is None \
                                    and cs in self.acks[doc]:
                                continue   # re-delivered ack
                            self.acks[doc][cs] = sq
                            for fn in self._ack_listeners:
                                fn(doc, cs, sq)
                        else:
                            self._pending[doc].pop(cs, None)
                            self.nacks.append((doc, cs, sq))
                    self._acked_cv.notify_all()
            elif resp.get("t") == "throttled":
                # admission shed an op suffix: cseqs are NOT burned —
                # park them, resubmit the SAME numbers in order after
                # the jittered retry_after
                cseqs = resp.get("cseqs", [])
                with self._acked_cv:
                    for row, cs in zip(resp.get("rows", []), cseqs):
                        doc = self.row_doc.get(row)
                        if doc is not None \
                                and cs in self._pending[doc]:
                            self._throttled[doc].add(cs)
                            self.throttled_cseqs[doc].add(cs)
                    self.throttled += len(cseqs)
                    REGISTRY.inc("client_throttled_total", len(cseqs))
                    self._schedule_retry(
                        float(resp.get("retry_after_ms", 50.0)))

    # ------------------------------------------------------------ throttling

    def _schedule_retry(self, retry_ms: float) -> None:
        """One timer per throttle episode (lock held by caller); a
        later, larger hint extends the armed timer (hints grow with the
        parked backlog — see ResilientConnection._schedule_retry)."""
        if self._closed:
            return
        delay = (max(1.0, retry_ms) / 1000.0) \
            * self._backoff.rng.uniform(1.0, 1.5)
        fire_at = time.monotonic() + delay
        if self._retry_timer is not None:
            if fire_at <= self._retry_at:
                return
            self._retry_timer.cancel()
        self._retry_at = fire_at
        t: Optional[threading.Timer] = None
        t = threading.Timer(delay,
                            lambda: self._resubmit_throttled(t))
        t.daemon = True
        self._retry_timer = t
        t.start()

    def _resubmit_throttled(self, timer) -> None:
        with self._lock:
            if self._retry_timer is not timer:
                return   # superseded by a later re-arm (or shutdown)
            self._retry_timer = None
            if self._closed:
                return
            resend = []
            for doc, shed in self._throttled.items():
                cseqs = sorted(cs for cs in shed
                               if cs in self._pending[doc])
                shed.clear()
                resend.extend((doc, cs, self._pending[doc][cs])
                              for cs in cseqs)
            sock = self._sock
            self._send_lock.acquire()
        try:
            for doc, cs, (kind, a0, a1, payload, ref) in resend:
                self.throttle_resubmits += 1
                self._send_one(sock, doc, cs, kind, a0, a1, payload,
                               ref)
        finally:
            self._send_lock.release()

    def on_ack(self, fn: Callable) -> None:
        """Register an ack listener ``fn(doc, cseq, seq)`` (called with
        the client lock held — keep it cheap)."""
        self._ack_listeners.append(fn)

    # -------------------------------------------------------------- submit

    def _send_one(self, sock, doc: str, cseq: int, kind: int, a0: int,
                  a1: int, payload, ref: int) -> None:
        ops = np.zeros(1, dtype=colwire._OP_DTYPE)
        ops["row"] = self.rows[doc]
        ops["kind"] = kind
        ops["a0"] = a0
        ops["a1"] = a1
        ops["tidx"] = 0
        ops["cseq"] = cseq
        ops["ref"] = ref
        texts = [payload] if kind == 0 else [""]
        props = [payload] if kind == 2 else None
        try:
            sock.sendall(colwire.encode_op_batch(texts, ops,
                                                 props=props))
        except OSError:
            pass    # reader notices and resubmits after rejoin

    def submit(self, doc: str, kind: int, a0: int, a1: int = 0,
               payload: Any = "", ref: int = 0) -> int:
        """Submit one op on ``doc``; returns its clientSeq (stable — the
        columnar space never renumbers)."""
        if self._closed:
            raise ConnectionError("submit on closed client")
        with self._lock:
            self._cseq[doc] += 1
            cs = self._cseq[doc]
            self._pending[doc][cs] = (kind, a0, a1, payload, ref)
            if self._retry_timer is not None:
                # throttle episode in flight: park locally, the retry
                # timer resends the whole run in cseq order
                self._throttled[doc].add(cs)
                self.throttled_cseqs[doc].add(cs)
                return cs
            sock = self._sock
            self._send_lock.acquire()
        try:
            self._send_one(sock, doc, cs, kind, a0, a1, payload, ref)
        finally:
            self._send_lock.release()
        return cs

    def wait_idle(self, timeout: float = 30.0) -> bool:
        deadline = time.monotonic() + timeout
        with self._acked_cv:
            while any(self._pending.values()) and not self._closed:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._acked_cv.wait(left)
            return not any(self._pending.values())

    @property
    def pending_count(self) -> int:
        with self._lock:
            return sum(len(p) for p in self._pending.values())

    # ------------------------------------------------------------- chaos

    def kill_socket(self) -> None:
        sock = self._sock
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            sock.close()

    def close(self) -> None:
        self._closed = True
        timer = self._retry_timer
        if timer is not None:
            timer.cancel()
        sock = self._sock
        try:
            sock.sendall(colwire.encode_json({"t": "bye"}))
        except (OSError, AttributeError):
            pass
        if sock is not None:
            sock.close()
        with self._acked_cv:
            self._acked_cv.notify_all()


class ResilientObserver:
    """Reconnecting read-only client for the observer door
    (``server.observer.ObserverDoor``).

    The read-plane counterpart of the wrappers above: no ops to
    resubmit, so resilience means *resuming the window stream without a
    gap or a dup*. The client tracks the last applied window id and the
    last applied sequenced seq per doc; a reconnect (or a server-side
    shed ``gap`` frame) re-enters with ``from_wid = last_wid + 1`` so
    the hub's retained ring replays exactly the missed windows — a
    resubscribe requests catch-up, never full hydration. When the ring
    no longer reaches back (``catchup_needed``), the client surfaces it
    (``catchup_needed`` counter) for the generation-diff ladder
    (docs/READ_PLANE.md) and rejoins at the live head.

    Exactly-once accounting is structural: window ids are published
    monotonically with no holes, so ``wid <= last_wid`` is a dup
    (skipped whole) and ``wid > last_wid + 1`` is a gap; per-doc
    sequenced seqs back that up at op granularity (``dups`` /
    ``op_gaps``). The window is the unit of both: its ops are held
    until the last of the header's ``n_frames`` has arrived and applied
    together with the cursor's advance, so a run the socket tore leaves
    nothing behind and is replayed whole. The reconnect-storm test pins
    all four counters at zero.
    """

    def __init__(self, host: str, port: int, name: str = "",
                 rng=None, attempts: int = 8,
                 base_delay: float = 0.02,
                 dial_timeout: float = 10.0,
                 on_op: Optional[Callable] = None,
                 byte_rate: Optional[float] = None,
                 byte_burst: Optional[float] = None):
        self.host = host
        self.port = port
        self.name = name or "resilient-observer"
        self.attempts = attempts
        self.dial_timeout = dial_timeout
        self.on_op = on_op
        self.byte_rate = byte_rate
        self.byte_burst = byte_burst
        self._backoff = Backoff(base=base_delay, cap=1.0, rng=rng,
                                metric="observer_reconnect_backoffs_total")
        self._lock = threading.RLock()
        self._cv = threading.Condition(self._lock)
        self._closed = False
        #: set while the client holds a cursor into the window stream
        #: (the door has answered a subscribe): from then on every
        #: window the hub publishes reaches it, across redials
        self._subscribed = threading.Event()
        self._sock: Optional[socket.socket] = None
        #: doc → last applied sequenced seq (the resume cursor)
        self.doc_seqs: Dict[str, int] = {}
        self.last_wid = 0
        self.windows_applied = 0
        self.ops_applied = 0
        self.window_dups = 0     # whole windows skipped (wid replayed)
        self.dups = 0            # per-op dedup drops
        self.gaps = 0            # window-id holes observed
        self.op_gaps = 0         # per-doc seq holes observed
        self.reconnects = 0
        self.sheds = 0           # server-side shed notices received
        self.catchup_needed = 0  # ring could not reach our cursor
        self.gave_up = False
        #: state of the in-flight window run: its id, the frames still
        #: to come, and the ops decoded from it so far
        self._skip = False
        self._open_wid = 0
        self._frames_left = 0
        self._held: List[tuple] = []
        self._cops_docs: List[str] = []
        self._thread = threading.Thread(
            target=self._run, name=f"observer:{self.name}", daemon=True)
        self._thread.start()

    # -------------------------------------------------------------- loop

    def _run(self) -> None:
        attempts_left = self.attempts
        first = True
        while not self._closed and attempts_left > 0:
            try:
                sock = socket.create_connection(
                    (self.host, self.port), timeout=self.dial_timeout)
                sock.settimeout(None)
                self._sock = sock
                sub: Dict[str, Any] = {"t": "subscribe",
                                       "name": self.name}
                if self._subscribed.is_set():
                    # resume, not rehydrate: only the missed windows
                    # (a cursor of 0 is one too: joined before the
                    # first window, killed before it arrived)
                    sub["from_wid"] = self.last_wid + 1
                if self.byte_rate is not None:
                    sub["byte_rate"] = self.byte_rate
                if self.byte_burst is not None:
                    sub["byte_burst"] = self.byte_burst
                sock.sendall(colwire.encode_json(sub))
                if not first:
                    with self._lock:
                        self.reconnects += 1
                    REGISTRY.inc("observer_reconnects_total")
                first = False
                self._backoff.reset()
                attempts_left = self.attempts
                self._recv_loop(sock)
            except (OSError, ConnectionError, ValueError):
                pass
            if self._closed:
                break
            attempts_left -= 1
            if attempts_left > 0:
                time.sleep(self._backoff.next_delay())
        if not self._closed:
            self.gave_up = True
        with self._cv:
            self._cv.notify_all()

    def _recv_loop(self, sock: socket.socket) -> None:
        while not self._closed:
            ftype, payload = colwire.read_frame(sock)
            self._on_frame(ftype, payload, sock)

    # ------------------------------------------------------------ decode

    def _on_frame(self, ftype: int, payload: bytes,
                  sock: socket.socket) -> None:
        msg = json.loads(bytes(payload)) if ftype == ord("J") else None
        if msg is not None and msg.get("t") != "rec":
            self._on_control(msg, sock)
            return
        if self._skip:
            return
        if msg is not None:
            if msg.get("fmt") == "cops":
                self._cops_docs = list(msg["docs"])
            elif msg.get("fmt") == "json":
                self._held.extend(
                    (doc, int(seq), int(client), contents)
                    for doc, seq, client, contents in msg["ops"])
        elif ftype in (ord("B"), ord("R")):
            self._on_op_frame(payload, rich=ftype == ord("R"))
        elif ftype == ord("T"):
            self._on_tree_frame(payload)
        self._frames_left -= 1
        if self._frames_left == 0:
            self._close_window()

    def _close_window(self) -> None:
        """The open run is whole: advance the cursor and apply its
        ops."""
        wid = self._open_wid
        with self._lock:
            if wid > self.last_wid + 1:
                self.gaps += 1
            self.last_wid = wid
            self.windows_applied += 1
        for op in self._held:
            self._apply(*op)

    def _on_control(self, msg: dict, sock: socket.socket) -> None:
        t = msg.get("t")
        if t == "window":
            wid = int(msg["wid"])
            with self._lock:
                # replay overlap: skip the whole run, count the dup
                self._skip = wid <= self.last_wid
                if self._skip:
                    self.window_dups += 1
                    return
            self._open_wid = wid
            self._frames_left = int(msg["n_frames"])
            self._held = []
            if self._frames_left == 0:
                self._close_window()
        elif t == "subscribed":
            with self._lock:
                if msg.get("catchup_needed"):
                    # the ring no longer reaches our cursor: the
                    # generation-diff ladder owns the gap from here;
                    # the stream itself resumes at the live head
                    self.catchup_needed += 1
                if not self._subscribed.is_set():
                    self.last_wid = int(msg["next_wid"]) - 1
            self._subscribed.set()
        elif t == "gap":
            # server shed us a window (byte budget): we are parked;
            # ask for a ring replay from our cursor on this socket
            with self._lock:
                self.sheds += 1
                from_wid = self.last_wid + 1
            sock.sendall(colwire.encode_json(
                {"t": "resume", "from_wid": from_wid}))
        elif t == "catchup_needed":
            # resume refused: ring too short — ladder territory
            with self._lock:
                self.catchup_needed += 1
            self._subscribed.clear()   # rejoin at the live head
            raise ConnectionError("ring behind cursor")

    def _on_op_frame(self, payload: bytes, rich: bool) -> None:
        texts, props, off = colwire.parse_op_tables(payload, rich)
        recs = np.frombuffer(payload, colwire._OP_DTYPE, offset=off)
        docs = self._cops_docs
        for r in recs:
            kind = int(r["kind"])
            op: Dict[str, Any] = {"kind": kind, "a0": int(r["a0"]),
                                  "a1": int(r["a1"])}
            if kind == 0 and texts:              # INSERT
                op["text"] = texts[int(r["tidx"])]
            elif kind == 2 and props:            # ANNOTATE
                op["props"] = props[int(r["tidx"])]
            self._held.append((docs[int(r["row"])], int(r["cseq"]),
                               int(r["ref"]), op))

    def _on_tree_frame(self, payload: bytes) -> None:
        from ..server.read_plane import decode_tree_frame
        header, rec_op, recs = decode_tree_frame(payload)
        docs = header["docs"]
        for i, seq in enumerate(header["seq"]):
            self._held.append((docs[int(header["doc"][i])], int(seq),
                               int(header["client"][i]),
                               {"tree_rec": int(rec_op[i])}))

    def _apply(self, doc: str, seq: int, client: int, op: Any) -> None:
        with self._cv:
            last = self.doc_seqs.get(doc, 0)
            if seq <= last:
                self.dups += 1
                return
            if last and seq > last + 1:
                self.op_gaps += 1
            self.doc_seqs[doc] = seq
            self.ops_applied += 1
            self._cv.notify_all()
        if self.on_op is not None:
            self.on_op(doc, seq, client, op)

    # ------------------------------------------------------------- waits

    def wait_subscribed(self, timeout: float = 30.0) -> bool:
        """Block until the door has acknowledged a subscription. A
        first-time observer joins at the live head, so what the hub
        published before this returns true is not delivered; what it
        publishes afterwards is, whatever happens to the socket."""
        return self._subscribed.wait(timeout)

    def wait_ops(self, n: int, timeout: float = 30.0) -> bool:
        """Block until ``n`` distinct ops have been applied."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while self.ops_applied < n and not self._closed \
                    and not self.gave_up:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(left)
            return self.ops_applied >= n

    # ------------------------------------------------------------- chaos

    def kill_socket(self) -> None:
        """Simulate network loss mid-stream; the loop redials with
        jitter and resubscribes from ``last_wid + 1``."""
        sock = self._sock
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            sock.close()

    def close(self) -> None:
        self._closed = True
        sock = self._sock
        try:
            sock.sendall(colwire.encode_json({"t": "close"}))
        except (OSError, AttributeError):
            pass
        if sock is not None:
            sock.close()
        with self._cv:
            self._cv.notify_all()
        self._thread.join(timeout=5)
