"""ctypes binding for the native C++ Deli sequencer.

Same policies as ``server.deli.DeliSequencer`` (parity-tested); adds a batch
API for the ingest hot path. ``available()`` says whether the library can be
built here; constructing a sequencer without it raises the build error.
"""

from __future__ import annotations

import ctypes
import threading
from typing import Optional, Tuple

import numpy as np

from ..native.build import NativeBuildError, ensure_built
from ..utils.telemetry import REGISTRY
from .deli import NackReason

_NACK_BY_CODE = {
    -1: NackReason.UNKNOWN_CLIENT,
    -2: NackReason.CLIENT_SEQ_GAP,
    -3: NackReason.DUPLICATE,
    -4: NackReason.REF_SEQ_BELOW_MSN,
}

_lib = None


def _load():
    global _lib
    if _lib is not None:
        return _lib
    lib = ctypes.CDLL(ensure_built("libdeli.so"))
    lib.deli_create.restype = ctypes.c_void_p
    lib.deli_destroy.argtypes = [ctypes.c_void_p]
    lib.deli_client_join.restype = ctypes.c_int64
    lib.deli_client_join.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_int32]
    lib.deli_client_leave.restype = ctypes.c_int64
    lib.deli_client_leave.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_int32]
    lib.deli_sequence.restype = ctypes.c_int64
    lib.deli_sequence.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, ctypes.POINTER(ctypes.c_int64)]
    lib.deli_sequence_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64)]
    lib.deli_doc_handle.restype = ctypes.c_int32
    lib.deli_doc_handle.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.deli_sequence_batch_rows.argtypes = [
        ctypes.c_void_p, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64)]
    lib.deli_replay.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32]
    lib.deli_doc_seq.restype = ctypes.c_int64
    lib.deli_doc_seq.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.deli_doc_min_seq.restype = ctypes.c_int64
    lib.deli_doc_min_seq.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.deli_checkpoint.restype = ctypes.c_int64
    lib.deli_checkpoint.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_int64]
    lib.deli_restore.restype = ctypes.c_void_p
    lib.deli_restore.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    _lib = lib
    return lib


def available() -> bool:
    try:
        _load()
    except NativeBuildError:
        return False
    return True


class NativeDeli:
    """C++ sequencer handle with the Python DeliSequencer's surface.

    Thread safety: the C++ state is NOT internally synchronized, and the
    pipelined ingest executor calls ``sequence_batch_rows`` from its own
    worker thread while front-door event loops join/leave clients — one
    Python-side lock serializes every native call (held for the whole C
    call; the batch entry points release the GIL inside ctypes, so the
    lock is the only thing keeping concurrent callers out)."""

    def __init__(self, _handle=None):
        lib = _load()
        self._lib = lib
        self._lock = threading.Lock()
        self._h = _handle if _handle is not None else lib.deli_create()

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.deli_destroy(self._h)
            self._h = None

    def client_join(self, doc_id: str, client: int) -> int:
        with self._lock:
            return self._lib.deli_client_join(self._h, doc_id.encode(),
                                              client)

    def client_leave(self, doc_id: str, client: int) -> int:
        with self._lock:
            return self._lib.deli_client_leave(self._h, doc_id.encode(),
                                               client)

    def sequence(self, doc_id: str, client: int, client_seq: int,
                 ref_seq: int, is_noop: bool = False
                 ) -> Tuple[Optional[int], Optional[int],
                            Optional[NackReason]]:
        """(seq, min_seq, None) on success, (None, None, reason) on nack."""
        out_min = ctypes.c_int64()
        with self._lock:
            seq = self._lib.deli_sequence(
                self._h, doc_id.encode(), client, client_seq, ref_seq,
                int(is_noop), ctypes.byref(out_min))
        if seq < 0:
            REGISTRY.inc("native_deli_nacks")
            return None, None, _NACK_BY_CODE[int(seq)]
        REGISTRY.inc("native_deli_ops")
        return int(seq), int(out_min.value), None

    def sequence_batch(self, doc_id: str, clients, client_seqs, ref_seqs,
                       is_noop=None):
        """Stamp a batch of raw ops for one doc; returns (seqs, min_seqs)
        int64 arrays (negative seq = nack code)."""
        clients = np.ascontiguousarray(clients, np.int32)
        client_seqs = np.ascontiguousarray(client_seqs, np.int32)
        ref_seqs = np.ascontiguousarray(ref_seqs, np.int32)
        n = len(clients)
        if is_noop is None:
            is_noop = np.zeros(n, np.int32)
        is_noop = np.ascontiguousarray(is_noop, np.int32)
        out_seq = np.empty(n, np.int64)
        out_min = np.empty(n, np.int64)
        p = lambda a, t: a.ctypes.data_as(ctypes.POINTER(t))
        with self._lock:
            self._lib.deli_sequence_batch(
                self._h, doc_id.encode(), n,
                p(clients, ctypes.c_int32), p(client_seqs, ctypes.c_int32),
                p(ref_seqs, ctypes.c_int32), p(is_noop, ctypes.c_int32),
                p(out_seq, ctypes.c_int64), p(out_min, ctypes.c_int64))
        nacks = int(np.count_nonzero(out_seq < 0))
        REGISTRY.inc("native_deli_batch_ops", n - nacks)
        if nacks:
            REGISTRY.inc("native_deli_nacks", nacks)
        return out_seq, out_min

    def doc_handle(self, doc_id: str) -> int:
        """Dense row handle (session-local; re-register after restore)."""
        with self._lock:
            return int(self._lib.deli_doc_handle(self._h, doc_id.encode()))

    def sequence_batch_rows(self, handles, clients, client_seqs, ref_seqs,
                            is_noop=None):
        """Columnar multi-doc stamping: one C call for the whole batch.
        Returns (seqs, min_seqs) int64 arrays; negative seq = nack code."""
        handles = np.ascontiguousarray(handles, np.int32)
        clients = np.ascontiguousarray(clients, np.int32)
        client_seqs = np.ascontiguousarray(client_seqs, np.int32)
        ref_seqs = np.ascontiguousarray(ref_seqs, np.int32)
        n = len(handles)
        if is_noop is None:
            is_noop = np.zeros(n, np.int32)
        is_noop = np.ascontiguousarray(is_noop, np.int32)
        out_seq = np.empty(n, np.int64)
        out_min = np.empty(n, np.int64)
        p = lambda a, t: a.ctypes.data_as(ctypes.POINTER(t))
        with self._lock:
            self._lib.deli_sequence_batch_rows(
                self._h, n, p(handles, ctypes.c_int32),
                p(clients, ctypes.c_int32), p(client_seqs, ctypes.c_int32),
                p(ref_seqs, ctypes.c_int32), p(is_noop, ctypes.c_int32),
                p(out_seq, ctypes.c_int64), p(out_min, ctypes.c_int64))
        nacks = int(np.count_nonzero(out_seq < 0))
        REGISTRY.inc("native_deli_batch_ops", n - nacks)
        if nacks:
            REGISTRY.inc("native_deli_nacks", nacks)
        return out_seq, out_min

    def replay(self, doc_id: str, client: int, client_seq: int,
               ref_seq: int, seq: int, min_seq: int, type_: int) -> None:
        with self._lock:
            self._lib.deli_replay(self._h, doc_id.encode(), client,
                                  client_seq, ref_seq, seq, min_seq, type_)

    def doc_seq(self, doc_id: str) -> int:
        with self._lock:
            return int(self._lib.deli_doc_seq(self._h, doc_id.encode()))

    def doc_min_seq(self, doc_id: str) -> int:
        with self._lock:
            return int(self._lib.deli_doc_min_seq(self._h,
                                                  doc_id.encode()))

    def checkpoint(self) -> bytes:
        with self._lock:
            n = self._lib.deli_checkpoint(self._h, None, 0)
            buf = ctypes.create_string_buffer(int(n))
            self._lib.deli_checkpoint(self._h, buf, n)
        return buf.raw[:n]

    @classmethod
    def restore(cls, blob: bytes) -> "NativeDeli":
        lib = _load()
        h = lib.deli_restore(blob, len(blob))
        return cls(_handle=h)


class NativeDeliAdapter:
    """The C++ sequencer behind the Python ``DeliSequencer`` surface, so a
    serving engine can swap it in wholesale (``sequencer="native"``): the
    per-op path pays one ctypes call instead of Python dict bookkeeping, and
    the columnar ingest path (``raw``) stamps whole batches in one C call
    against the SAME state — one source of truth.

    Checkpoint format is the native text blob wrapped as
    ``{"native": <latin1 str>}``; ``restore_sequencer`` (server.serving)
    dispatches on that key, so python-engine summaries keep loading into
    python sequencers and native into native."""

    def __init__(self, clock=None, _native: Optional[NativeDeli] = None):
        import time
        self.raw = _native if _native is not None else NativeDeli()
        self.clock = clock if clock is not None else time.time
        # partition identity, mirroring DeliSequencer (ISSUE 18)
        self.partition = -1

    def client_join(self, doc_id: str, client_id: int):
        from ..core.protocol import MessageType, SequencedDocumentMessage
        seq = self.raw.client_join(doc_id, client_id)
        return SequencedDocumentMessage(
            doc_id=doc_id, client_id=client_id, client_seq=0,
            ref_seq=seq - 1, seq=seq,
            min_seq=self.raw.doc_min_seq(doc_id),
            type=MessageType.CLIENT_JOIN, contents={"clientId": client_id})

    def client_leave(self, doc_id: str, client_id: int):
        from ..core.protocol import MessageType, SequencedDocumentMessage
        seq = self.raw.client_leave(doc_id, client_id)
        if seq == 0:
            return None
        return SequencedDocumentMessage(
            doc_id=doc_id, client_id=client_id, client_seq=0, ref_seq=seq,
            seq=seq, min_seq=self.raw.doc_min_seq(doc_id),
            type=MessageType.CLIENT_LEAVE, contents={"clientId": client_id})

    def sequence(self, doc_id: str, client_id: int, client_seq: int,
                 ref_seq: int, type, contents, address=None):
        from ..core.protocol import MessageType, SequencedDocumentMessage
        from .deli import Nack
        seq, min_seq, reason = self.raw.sequence(
            doc_id, client_id, client_seq, ref_seq,
            is_noop=(type == MessageType.NOOP))
        if reason is not None:
            return None, Nack(doc_id, client_id, client_seq, reason)
        # mirror the C++ clamp so the broadcast message carries what the
        # sequencer actually recorded
        msg = SequencedDocumentMessage(
            doc_id=doc_id, client_id=client_id, client_seq=client_seq,
            ref_seq=min(ref_seq, seq - 1), seq=seq, min_seq=min_seq,
            type=type, contents=contents, address=address,
            timestamp=self.clock())
        return msg, None

    def replay(self, msg) -> None:
        self.raw.replay(msg.doc_id, msg.client_id, msg.client_seq,
                        msg.ref_seq, msg.seq, msg.min_seq, int(msg.type))

    def doc_seq(self, doc_id: str) -> int:
        return self.raw.doc_seq(doc_id)

    def checkpoint(self) -> dict:
        return {"native": self.raw.checkpoint().decode("latin1")}

    @classmethod
    def restore(cls, snapshot: dict, clock=None) -> "NativeDeliAdapter":
        return cls(clock=clock,
                   _native=NativeDeli.restore(
                       snapshot["native"].encode("latin1")))

    def save_checkpoint(self, path: str) -> None:
        """Atomic (tmp + fsync + rename) durable checkpoint — a kill
        mid-write leaves the previous checkpoint file intact."""
        from ..utils.atomicfile import atomic_write_json
        atomic_write_json(path, self.checkpoint())

    @classmethod
    def load_checkpoint(cls, path: str, clock=None) -> "NativeDeliAdapter":
        from ..utils.atomicfile import read_json
        return cls.restore(read_json(path), clock=clock)
