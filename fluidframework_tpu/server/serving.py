"""The replica serving engine: the end-to-end north-star slice as a service.

Reference counterpart: the full Routerlicious pipeline around the op-merge
hot path (SURVEY.md §3.2, §3.5) — Alfred ingress → Deli sequencing → Kafka →
Broadcaster fan-out / Scriptorium persistence, with client containers doing
the merging. Here the merge itself is the batched device kernel, so the
service *is* the replica: raw client ops are stamped by ``DeliSequencer``,
appended to the durable ``PartitionedLog`` (the Kafka role), queued into an
adaptive batch window, and merged for every resident document at once by
``TensorStringStore`` (one ``pjit``'d apply per flush). The sequenced
message returned from ``submit`` is the broadcast/ack.

Recovery is the reference's single primitive (SURVEY.md §5.4): a summary —
device→host gather of the compacted planes plus sequencer checkpoint and
log offsets — and a tail replay of the log through the SAME apply kernels.

Batching vs latency (SURVEY.md §7 risk (c)): ops queue until ``batch_window``
records are waiting, then flush in one device dispatch; ``flush()`` can be
called any time (reads force it). Smaller windows trade throughput for op
latency exactly like the reference's outbox flush policy.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import threading
import time
from typing import Any, Dict, List, Optional, Set, Tuple

import numpy as np

from ..core.protocol import MessageType, SequencedDocumentMessage
from ..runtime.attributor import Attributor
from ..utils.faultpoints import (
    SITE_APPLY_STALL, SITE_FLUSH_MID_BATCH, SITE_INGEST_MID_BATCH,
    SITE_SUBMIT_POST_SEQUENCE, fault_point,
)
from ..utils import capacity, flight_recorder, tracing
from ..utils.telemetry import MetricsCollector, REGISTRY, TelemetryLogger
from ..ops.map_kernel import TensorMapStore
from ..ops.schema import OpKind
from ..ops.string_store import TensorStringStore
from ..ops.tree_kernel import TreeOpKind
from .deli import DeliSequencer, Nack, NackReason
from .oplog import OplogCorruptionError, PartitionedLog, partition_of


class DedupLedger:
    """Host-side durable-dedup ledger: per ``(doc, client)`` the recent
    ``clientSeq → seq`` acks, recorded only AFTER the op's durable append
    committed. Two jobs: (a) idempotent dup-acks — a resubmitted op whose
    original ack was lost is re-acked with its original seq instead of
    nacked/re-sequenced; (b) the resync cursor — ``last()`` tells a
    reconnecting client the highest clientSeq the service durably
    accepted, so it can renumber still-pending ops. Bounded per key (a
    client's in-flight window is far smaller than ``window``); snapshots
    ride the engine summary so the ledger survives restarts, and
    ``_replay_tail`` re-records the tail.

    Two write paths, one read interface. ``record`` (submit, log-tail
    replay) keeps a key's window as an ``OrderedDict``. ``record_planes``
    (the columnar door's ack fan, ISSUE 37) keeps it by ``(row, client)``
    in arrays: a ring of ``window`` slots a key, slot-major, filled in
    insertion order (slot = insertions % window, so the slot written
    next holds the oldest entry — the ``OrderedDict``'s eviction order).
    A key lives in one form at a time: ``record_planes`` adopts a key
    the per-op form holds, and ``record``, ``merge`` and ``release_row``
    hand a ring key back to it under its document, so every read answers
    exactly as one ``OrderedDict`` ledger would.
    """

    def __init__(self, window: int = 512):
        self.window = window
        self._led: Dict[Tuple[str, int], "collections.OrderedDict"] = {}
        self._last: Dict[Tuple[str, int], int] = {}
        # capacity plane (ISSUE 19): total acked rows across all
        # windows, maintained O(1) at every mutation — the census must
        # never walk every (doc, client) window
        self._entries = 0
        # the ack fan records on the ingress event loop while the
        # pipelined executor's sequencing worker looks up dup slots —
        # off the hot path (a record is a few array operations a window,
        # lookups only happen for rare DUPLICATE nacks), so a plain lock
        # is fine
        self._lock = threading.Lock()
        # ---- the array form: key id k names column k of the rings
        # (clients are the sequencer's int32, so a code names one key)
        self._code = np.empty(0, np.int64)      # sorted row << 32 | client
        self._code_kid = np.empty(0, np.int64)  # key id of each code
        self._ring_of: Dict[Tuple[str, int], int] = {}
        self._ring_key: List[Optional[Tuple[str, int]]] = []
        self._free_kids: List[int] = []
        self._cs = np.zeros((window, 0), np.int64)   # client seq
        self._sq = np.zeros((window, 0), np.int64)   # seq
        self._n = np.zeros(0, np.int64)    # insertions into a key's ring
        self._hi = np.zeros(0, np.int64)   # last(): highest client seq
        self._ring_entries = 0

    def record(self, doc_id: str, client_id: int, client_seq: int,
               seq: int) -> None:
        key = (doc_id, int(client_id))
        with self._lock:
            if key in self._ring_of:
                self._spill(key)
            led = self._led.get(key)
            if led is None:
                led = self._led[key] = collections.OrderedDict()
            if int(client_seq) not in led:
                self._entries += 1
            led[int(client_seq)] = int(seq)
            while len(led) > self.window:
                led.popitem(last=False)
                self._entries -= 1
            if client_seq > self._last.get(key, 0):
                self._last[key] = int(client_seq)

    def record_planes(self, rows, clients, client_seqs, seqs,
                      row_doc) -> int:
        """Record a whole ack window by row: the ops' ``rows``,
        ``clients``, ``client_seqs`` and ``seqs`` in record order (a
        row's ops in its sequence order), ``seqs <= 0`` (nacks) left
        out; ``row_doc[row]`` names a row's document and is read only
        for a key met for the first time. A fixed number of array
        operations for each depth, where the depth is a key's most ops
        in the window (a door's window holds at most four ops a row).
        Returns the ops recorded."""
        seqs = np.asarray(seqs, np.int64)
        rows = np.asarray(rows, np.int64)
        clients = np.asarray(clients, np.int64)
        cs = np.asarray(client_seqs, np.int64)
        ok = seqs > 0
        if not ok.all():
            rows, clients, cs, seqs = rows[ok], clients[ok], cs[ok], seqs[ok]
        n = seqs.size
        if not n:
            return 0
        code = (rows << 32) | (clients & 0xFFFFFFFF)
        if not (code[1:] >= code[:-1]).all():
            # group each key's ops (a multi-writer row's clients
            # interleave), every key's in its record order
            order = np.argsort(code, kind="stable")
            code, rows, clients, cs, seqs = (
                x[order] for x in (code, rows, clients, cs, seqs))
        head = np.empty(n, bool)
        head[0] = True
        np.not_equal(code[1:], code[:-1], out=head[1:])
        at = np.flatnonzero(head)       # each key's first op
        deep = np.diff(np.append(at, n))    # and how many it has
        with self._lock:
            kid = self._kids(code[at], rows[at], clients[at], row_doc)
            for d in range(int(deep.max())):
                if d:
                    more = deep > d
                    kid, at, deep = kid[more], at[more], deep[more]
                self._put(kid, cs[at + d], seqs[at + d])
        return n

    def _kids(self, code, rows, clients, row_doc) -> np.ndarray:
        """Key id of each ``(row, client)`` (``code`` ascending, no
        repeats); a key met for the first time gets a ring column."""
        pos = np.searchsorted(self._code, code)
        found = np.zeros(code.size, bool)
        if self._code.size:
            found = self._code[np.minimum(pos, self._code.size - 1)] == code
        if not found.all():
            miss = np.flatnonzero(~found)
            kids = np.array([self._new_kid((row_doc[r], c)) for r, c in zip(
                rows[miss].tolist(), clients[miss].tolist())], np.int64)
            at = np.searchsorted(self._code, code[miss])
            self._code = np.insert(self._code, at, code[miss])
            self._code_kid = np.insert(self._code_kid, at, kids)
            pos = np.searchsorted(self._code, code)
        return self._code_kid[pos]

    def _new_kid(self, key: Tuple[str, int]) -> int:
        """A ring column for ``key``, holding the window the per-op form
        held for it, in its order, if it held one."""
        if self._free_kids:
            k = self._free_kids.pop()
        else:
            k = len(self._ring_key)
            self._ring_key.append(None)
            if k >= self._n.size:
                self._grow(max(64, 2 * self._n.size))
        self._ring_key[k] = key
        self._ring_of[key] = k
        led = self._led.pop(key, None)
        m = 0 if led is None else len(led)
        if m:
            self._cs[:m, k] = np.fromiter(led.keys(), np.int64, m)
            self._sq[:m, k] = np.fromiter(led.values(), np.int64, m)
            self._entries -= m
            self._ring_entries += m
        self._n[k] = m
        self._hi[k] = self._last.pop(key, 0)
        return k

    def _grow(self, cap: int) -> None:
        """Widen the rings to ``cap`` keys. Only the slots some key has
        filled are copied: the rest are zeros never written, pages the
        process has not touched."""
        old = self._n.size
        depth = int(min(self._n.max(initial=0), self.window))
        for name in ("_cs", "_sq"):
            a = np.zeros((self.window, cap), np.int64)
            a[:depth, :old] = getattr(self, name)[:depth]
            setattr(self, name, a)
        for name in ("_n", "_hi"):
            a = np.zeros(cap, np.int64)
            a[:old] = getattr(self, name)
            setattr(self, name, a)

    def _put(self, k, cs, sq) -> None:
        """One op a key (``k`` distinct) into the rings, as an
        ``OrderedDict`` assignment: a client seq still in its key's
        window keeps its place and takes the new seq; any other goes to
        slot ``n % window``, over the oldest entry once the window is
        full."""
        W = self.window
        n_k, hi = self._n[k], self._hi[k]
        self._hi[k] = np.maximum(hi, cs)
        back = np.flatnonzero(cs <= hi)   # may be in its key's window
        if back.size:
            kb = k[back]
            live = np.arange(W)[:, None] < np.minimum(n_k[back], W)
            hit = (self._cs[:, kb] == cs[back]) & live
            found = hit.any(axis=0)
            if found.any():
                self._sq[hit.argmax(axis=0)[found], kb[found]] = \
                    sq[back[found]]
                keep = np.ones(k.size, bool)
                keep[back[found]] = False
                k, cs, sq, n_k = k[keep], cs[keep], sq[keep], n_k[keep]
        slot = n_k % W
        self._cs[slot, k] = cs
        self._sq[slot, k] = sq
        self._n[k] = n_k + 1
        self._ring_entries += int(np.count_nonzero(n_k < W))

    def _window_of(self, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """A ring key's (client seqs, seqs), oldest first."""
        n, W = int(self._n[k]), self.window
        idx = np.arange(n) if n <= W else (n + np.arange(W)) % W
        return self._cs[idx, k], self._sq[idx, k]

    def _spill(self, key: Tuple[str, int]) -> None:
        """Hand a ring key back to the per-op form, under its document:
        its window in the same order, and its ``last``."""
        k = self._ring_of.pop(key)
        cs, sq = self._window_of(k)
        self._led[key] = collections.OrderedDict(
            zip(cs.tolist(), sq.tolist()))
        self._last[key] = int(self._hi[k])
        self._entries += cs.size
        self._ring_entries -= cs.size
        keep = self._code_kid != k
        self._code, self._code_kid = self._code[keep], self._code_kid[keep]
        self._ring_key[k] = None
        self._free_kids.append(k)

    def release_row(self, row: int) -> None:
        """``row`` is about to name another document: every key recorded
        by it goes back to the per-op form, under the document it
        named."""
        with self._lock:
            lo, hi = np.searchsorted(self._code,
                                     [row << 32, (row + 1) << 32])
            for k in self._code_kid[lo:hi].tolist():
                self._spill(self._ring_key[k])

    def lookup(self, doc_id: str, client_id: int,
               client_seq: int) -> Optional[int]:
        key = (doc_id, int(client_id))
        with self._lock:
            k = self._ring_of.get(key)
            if k is not None:
                live = int(min(self._n[k], self.window))
                at = np.flatnonzero(self._cs[:live, k] == int(client_seq))
                return int(self._sq[at[0], k]) if at.size else None
            led = self._led.get(key)
            return None if led is None else led.get(int(client_seq))

    def last(self, doc_id: str, client_id: int) -> int:
        key = (doc_id, int(client_id))
        with self._lock:
            k = self._ring_of.get(key)
            if k is not None:
                return int(self._hi[k])
            return self._last.get(key, 0)

    def snapshot(self, docs=None) -> dict:
        """Full snapshot, or — ``docs`` given — only those docs' entries
        (the O(changed) slice an incremental summary carries)."""
        out: Dict[str, Dict[str, dict]] = {}
        with self._lock:
            for (doc, cid), led in self._led.items():
                if docs is not None and doc not in docs:
                    continue
                out.setdefault(doc, {})[str(cid)] = {
                    "last": self._last.get((doc, cid), 0),
                    "acked": [[cs, sq] for cs, sq in led.items()]}
            for (doc, cid), k in self._ring_of.items():
                if docs is not None and doc not in docs:
                    continue
                out.setdefault(doc, {})[str(cid)] = {
                    "last": int(self._hi[k]),
                    "acked": np.stack(self._window_of(k), 1).tolist()}
        return out

    def merge(self, partial: Optional[dict]) -> None:
        """Overlay a delta-summary slice: each ``(doc, client)`` entry in
        the slice replaces the ledger's (the slice is that key's full
        current window, not an increment)."""
        for doc, clients in (partial or {}).items():
            for cid, ent in clients.items():
                key = (doc, int(cid))
                with self._lock:
                    if key in self._ring_of:
                        self._spill(key)
                    self._last[key] = max(self._last.get(key, 0),
                                          int(ent.get("last", 0)))
                    old = self._led.get(key)
                    self._entries -= len(old) if old is not None else 0
                    led = self._led[key] = collections.OrderedDict()
                    for cs, sq in ent.get("acked", []):
                        led[int(cs)] = int(sq)
                    self._entries += len(led)

    @classmethod
    def load(cls, snapshot: Optional[dict],
             window: int = 512) -> "DedupLedger":
        self = cls(window=window)
        for doc, clients in (snapshot or {}).items():
            for cid, ent in clients.items():
                key = (doc, int(cid))
                self._last[key] = int(ent.get("last", 0))
                led = self._led[key] = collections.OrderedDict()
                for cs, sq in ent.get("acked", []):
                    led[int(cs)] = int(sq)
                self._entries += len(led)
        return self

    # ------------------------------------------------------ capacity plane

    def mem_stats(self) -> dict:
        """O(1) capacity roll-up: acked rows, (doc, client) keys, and
        the host-byte estimate — the per-op form's OrderedDict windows
        of boxed-int entries plus their two key-tuple'd index dicts, and
        the array form's rings and key index as allocated
        (``array_bytes``: a ring slot no key has reached is a page never
        touched, so resident memory may be less)."""
        from ..utils import capacity as _cap
        with self._lock:
            n_keys, ring_keys = len(self._led), len(self._ring_of)
            arrays = sum(a.nbytes for a in (
                self._cs, self._sq, self._n, self._hi, self._code,
                self._code_kid))
            return {
                "keys": n_keys + ring_keys,
                "entries": self._entries + self._ring_entries,
                "bytes": int(
                    self._entries * _cap.ODICT_ENTRY_BYTES
                    + n_keys * (_cap.ODICT_EMPTY_BYTES
                                + 2 * _cap.DICT_ENTRY_BYTES + 120)
                    + arrays
                    + _cap.dict_nbytes(ring_keys, _cap.DICT_ENTRY_BYTES + 120)
                    + _cap.list_nbytes(len(self._ring_key))),
                "array_bytes": int(arrays),
            }

    def per_doc_entries(self) -> Dict[str, int]:
        """Acked-row count per doc (census-time walk of the key space —
        O(keys), used only for the top-K heaviest ranking)."""
        out: Dict[str, int] = {}
        with self._lock:
            for (doc, _cid), led in self._led.items():
                out[doc] = out.get(doc, 0) + len(led)
            live = np.minimum(self._n, self.window).tolist()
            for (doc, _cid), k in self._ring_of.items():
                out[doc] = out.get(doc, 0) + live[k]
        return out


def make_sequencer(kind: str = "python", clock=None):
    """Engine sequencer factory: "python" = the reference-semantics
    DeliSequencer; "native" = the C++ sequencer behind the same surface.
    A caller that asks for native gets native or the build error."""
    if kind == "native":
        from .native_deli import NativeDeliAdapter
        return NativeDeliAdapter(clock=clock)
    if kind != "python":
        raise ValueError(f"unknown sequencer kind {kind!r}")
    return DeliSequencer(clock=clock)


def restore_sequencer(snapshot: dict, clock=None):
    """Checkpoint-format dispatch: native blobs restore into the native
    sequencer, python dicts into the Python one."""
    if "native" in snapshot:
        from .native_deli import NativeDeliAdapter
        return NativeDeliAdapter.restore(snapshot, clock=clock)
    return DeliSequencer.restore(snapshot, clock=clock)


@dataclasses.dataclass
class ColumnarOps:
    """A columnar (struct-of-arrays) run of sequenced string ops in the
    durable log — ONE record per (ingest batch × partition) instead of one
    Python object per op (the Kafka batch-append analog). Replay expands it
    back into per-op messages (recovery is rare; ingest is hot).

    Payload forms: broadcast ``text`` (every insert the same run), or
    per-op payloads via ``texts`` (payload table) + ``tidx`` ((N,) indices
    into it). Annotate slots (kind == STR_ANNOTATE) index the single-key
    ``props`` table through the same ``tidx`` plane."""

    doc_ids: List[str]          # row-local doc-id table
    doc: np.ndarray             # (N,) index into doc_ids
    client: np.ndarray          # (N,)
    client_seq: np.ndarray      # (N,)
    ref_seq: np.ndarray         # (N,)
    seq: np.ndarray             # (N,)
    min_seq: np.ndarray         # (N,)
    kind: np.ndarray            # (N,) OpKind
    a0: np.ndarray              # (N,) str: pos/start; map: key index
    a1: np.ndarray              # (N,) str: len/end; map: value index
    text: str                   # broadcast insert payload (str family)
    timestamp: float = 0.0
    texts: Optional[List[str]] = None      # per-op payload table
    props: Optional[List[dict]] = None     # single-key annotate table
    tidx: Optional[np.ndarray] = None      # (N,) table index per op
    #: which DDS wire dialect ``expand`` rebuilds: "str" (merge-tree
    #: ops), "map" (set/delete/clear over the keys/values tables), or
    #: "ops" (generic op-dict batch riding the values table)
    family: str = "str"
    keys: Optional[List[str]] = None       # map: key table (a0 indexes)
    values: Optional[list] = None          # map: value table (a1 indexes)

    def expand(self, only_doc: Optional[str] = None):
        """Per-op SequencedDocumentMessage stream (log-tail replay).
        ``only_doc`` expands just that document's slice — the per-doc
        rebuild path must not materialize the whole batch."""
        idxs = range(len(self.seq))
        if only_doc is not None:
            if only_doc not in self.doc_ids:
                return []
            want = self.doc_ids.index(only_doc)
            idxs = np.flatnonzero(np.asarray(self.doc) == want)
        out = []
        for i in idxs:
            k = int(self.kind[i])
            if self.family == "tree_flat":
                # flat single-node insert: values[i] = [parent, field,
                # node_id, after, value, type]
                p, f, nid, aft, val, typ = self.values[int(self.a0[i])]
                contents = {"op": "insert", "parent": p, "field": f,
                            "after": aft or None,
                            "nodes": [{"id": nid, "type": typ,
                                       "value": val}]}
            elif self.family in ("ops", "tree"):
                # generic op-dict batch: contents ride the values table
                contents = self.values[int(self.a0[i])]
            elif self.family == "map":
                if k == OpKind.MAP_CLEAR:
                    contents = {"op": "clear"}
                elif k == OpKind.MAP_DELETE:
                    contents = {"op": "delete",
                                "key": self.keys[int(self.a0[i])]}
                else:
                    contents = {"op": "set",
                                "key": self.keys[int(self.a0[i])],
                                "value": self.values[int(self.a1[i])]}
            elif k == OpKind.STR_INSERT:
                text = self.text if self.texts is None \
                    else self.texts[int(self.tidx[i])]
                # clientSeq rides in the contents too: the ORACLE's
                # remote-insert path keys payload handles by it
                contents = {"mt": "insert", "kind": 0, "pos": int(self.a0[i]),
                            "text": text,
                            "clientSeq": int(self.client_seq[i])}
            elif k == OpKind.STR_ANNOTATE:
                contents = {"mt": "annotate", "start": int(self.a0[i]),
                            "end": int(self.a1[i]),
                            "props": self.props[int(self.tidx[i])]}
            else:
                contents = {"mt": "remove", "start": int(self.a0[i]),
                            "end": int(self.a1[i])}
            out.append(SequencedDocumentMessage(
                doc_id=self.doc_ids[int(self.doc[i])],
                client_id=int(self.client[i]),
                client_seq=int(self.client_seq[i]),
                ref_seq=int(self.ref_seq[i]), seq=int(self.seq[i]),
                min_seq=int(self.min_seq[i]), type=MessageType.OP,
                contents=contents, timestamp=self.timestamp))
        return out


@dataclasses.dataclass
class TreeRecordOps:
    """A columnar run of sequenced TREE ops in the durable log: per-op
    sequencing planes plus the RAW kernel record planes and their
    batch-local string/value tables (``server.tree_wire`` documents the
    wire format). Recovery replays the record planes bit-identically
    through the same kernel — no decode on the state path; ``expand``
    decodes op dicts only for audit and oracle replay."""

    doc_ids: List[str]          # row-local doc-id table
    doc: np.ndarray             # (N,) index into doc_ids
    client: np.ndarray          # (N,)
    client_seq: np.ndarray      # (N,)
    ref_seq: np.ndarray         # (N,)
    seq: np.ndarray             # (N,)
    min_seq: np.ndarray         # (N,)
    rec_op: np.ndarray          # (R,) op index per record, ascending
    recs: np.ndarray            # (R, 8) kind,node,parent,after,field,
    #                             value,type_,meta (batch-LOCAL handles)
    ids: List[str]              # 1-based tables (handle h ↔ table[h-1])
    fields: List[str]
    types: List[str]
    values: list
    timestamp: float = 0.0

    def _op_slices(self):
        """(start, end) record-range per op (rec_op is ascending)."""
        n = len(self.seq)
        starts = np.searchsorted(self.rec_op, np.arange(n), side="left")
        ends = np.searchsorted(self.rec_op, np.arange(n), side="right")
        return starts, ends

    def expand(self, only_doc: Optional[str] = None):
        """Per-op messages with DECODED dict contents (oracle replay /
        audit; the recovery state path uses the raw planes instead).
        Decode is one vectorized table-gather pass over the whole run
        (``tree_wire.decode_records``), not a per-record Python loop."""
        from .tree_wire import decode_records
        idxs = range(len(self.seq))
        if only_doc is not None:
            if only_doc not in self.doc_ids:
                return []
            want = self.doc_ids.index(only_doc)
            idxs = np.flatnonzero(np.asarray(self.doc) == want)
        ops = decode_records(self.rec_op, self.recs, self.ids,
                             self.fields, self.types, self.values)
        out = []
        for i in idxs:
            contents = ops[int(i)]
            out.append(SequencedDocumentMessage(
                doc_id=self.doc_ids[int(self.doc[i])],
                client_id=int(self.client[i]),
                client_seq=int(self.client_seq[i]),
                ref_seq=int(self.ref_seq[i]), seq=int(self.seq[i]),
                min_seq=int(self.min_seq[i]), type=MessageType.OP,
                contents=contents, timestamp=self.timestamp))
        return out


class ServingEngineBase:
    """The DDS-agnostic half of a serving engine: Deli sequencing, the
    durable partitioned log, doc-row membership, window-floor tracking, and
    the adaptive batch window. Subclasses own the device store(s): they
    implement ``_enqueue``/``flush``/``compact`` and summary/recovery."""

    def __init__(self, batch_window: int = 64, n_partitions: int = 8,
                 compact_every: int = 16,
                 log: Optional[PartitionedLog] = None,
                 sequencer: str = "python"):
        self.deli = make_sequencer(sequencer)
        self.log = log if log is not None else PartitionedLog(n_partitions)
        # epoch this engine stamps on durable appends (ISSUE 10): reads
        # the log's CURRENT fence word — constructing/loading an engine
        # never bumps the fence (a read-only follower must not depose the
        # leader); takeover goes through acquire_write_authority().
        self.writer_epoch: Optional[int] = getattr(
            self.log, "fence_epoch", None)
        # the sequencer carries the epoch its stream is stamped under
        setattr(self.deli, "epoch", self.writer_epoch or 0)
        self.batch_window = batch_window
        self.compact_every = compact_every
        self._doc_rows: Dict[str, int] = {}
        # row allocator: freed rows (docs that graduated off this tier) are
        # reused before fresh ones
        self._free_rows: List[int] = []
        self._next_row = 0
        self._queue: List[Tuple[int, SequencedDocumentMessage]] = []
        self._flushes_since_compact = 0
        self._min_seq: Dict[str, int] = {}
        # the same floor by flat row, for engines whose compaction reads
        # it as one array (StringServingEngine); every write of a flat
        # row's floor goes through _set_min_seq to keep the two in step
        self._row_floor: Optional[np.ndarray] = None
        # read plane (ISSUE 20): attach_read_plane() hangs a pump here;
        # _after_flush pokes it so observer windows are carved at
        # device-flush pace (encode-once fanout, server/read_plane.py)
        self._read_plane = None
        # opt-in (enable_attribution): ONE attributor per document —
        # Deli seqs are per-doc, so a shared table would collide across docs
        self._attributors: Optional[Dict[str, Any]] = None
        # per-lambda observability (SURVEY.md §5.5: op rate, nacks by
        # reason, flush batch sizes, flush latency percentiles);
        # attached to the process registry for unified exposition
        self.metrics = MetricsCollector()
        REGISTRY.attach(type(self).__name__, self.metrics)
        # health-plane mesh rollups (ISSUE 4): per-partition labeled
        # collectors count durable-log appends per Kafka-partition analog;
        # per-shard collectors attach lazily on the first flush/ingest
        # (self.mesh is set by subclass __init__ AFTER this runs)
        self.partition_metrics: List[MetricsCollector] = []
        for p in range(self.log.n_partitions):
            coll = MetricsCollector()
            REGISTRY.attach(type(self).__name__, coll,
                            labels={"partition": p})
            self.partition_metrics.append(coll)
        self.shard_metrics: List[MetricsCollector] = []
        self._rows_per_shard = 1
        self._shard_rollup_done = False
        # structured events (attach a sink via telemetry._sink or replace
        # the logger); the apply watchdog warns through it
        self.telemetry = TelemetryLogger(None, "serving")
        # apply watchdog: a device apply that takes longer than this is a
        # STALL — counted, recorded (bounded ring), and warned, so a 63 s
        # hiccup shows up in telemetry instead of vanishing into an
        # average (round-5 postmortem: one unattributed 983 ms worst)
        self.stall_threshold_ms = 250.0
        self.stall_events: List[dict] = []   # most recent _STALL_KEEP
        self._STALL_KEEP = 64
        # round-robin partition cursor for whole-batch columnar records
        # (see _append_columnar)
        self._col_part = 0
        # session-resilience state: the durable-dedup ledger (idempotent
        # dup-acks + resync cursors) and the current member set — both
        # rebuilt by _replay_tail and persisted in _base_summary, because
        # the NATIVE sequencer's client_join resets its dedup window (a
        # restarted/rejoined identity must not re-accept old clientSeqs)
        self._dedup = DedupLedger()
        self._members: Set[Tuple[str, int]] = set()
        self._dup_acked_last = 0
        # set when the device state may be AHEAD of the durable log (a
        # log append failed after the merge was dispatched): every ingest
        # and summary refuses until the engine is rebuilt via load() —
        # summarizing now would durably persist never-logged ops.
        # With the pipelined ingest executor several waves can be
        # sequenced-but-not-yet-logged AT ONCE (from different threads),
        # so the sentinel is counter-backed: poison clears only when the
        # LAST in-flight wave's durable append commits
        # (_ingest_mark_logged); the lock covers counter+message together.
        self._poisoned: Optional[str] = None
        self._poison_lock = threading.Lock()
        self._seq_unlogged = 0
        # deferred overflow harvest (set by the compact tail when waves
        # are still in flight; the executor re-checks after a drain)
        self._ov_recover_due = False
        self._ingest_executor = None
        # ---- incremental-summary machinery (shared by every engine) ----
        # last summary + its dirty-detection baselines (doc seqs, row map,
        # interner table lengths — engine-specific extras)
        self._summ_bookkeeping: Optional[dict] = None
        # docs whose device state was rewritten OUTSIDE the op stream
        # (overflow re-upload, adoption): doc seq does not move, so
        # seq-based dirty detection would miss them
        self._dirty_outside_ops: set = set()
        # bound the delta chain: past this depth summarize(incremental=
        # True) produces a full summary instead (load()'s work and the
        # retained base references stay bounded)
        self.max_incremental_chain = 8
        self._chain_depth = 0
        # capacity plane (ISSUE 19): register this engine's pull
        # provider on the process ledger (weakly — engines are born and
        # die by the hundreds in tests; the ledger must not pin them)
        self._capacity_key = capacity.LEDGER.register(
            type(self).__name__, self._capacity_report)

    # ------------------------------------------------------ capacity plane

    def _capacity_report(self) -> dict:
        """Pull-provider for ``utils.capacity.LEDGER``: host/device
        bytes by category across everything this engine owns — its
        stores (each store's ``capacity_stats``), the dedup ledger, the
        oplog's in-memory tails, and the row map — plus a top-K
        heaviest-doc ranking (uniform device row share + that doc's
        dedup window weight)."""
        host: Dict[str, int] = {}
        device: Dict[str, int] = {}
        for attr in ("store", "mega_store", "axis_store"):
            sub = getattr(self, attr, None)
            if sub is None:
                continue
            stats = getattr(sub, "capacity_stats", None)
            if stats is not None:
                rep = stats()
                for cat, v in rep.get("host", {}).items():
                    host[cat] = host.get(cat, 0) + int(v)
                for cat, v in rep.get("device", {}).items():
                    device[cat] = device.get(cat, 0) + int(v)
            elif getattr(sub, "state", None) is not None:
                device["state"] = device.get("state", 0) \
                    + capacity.device_nbytes(sub.state)
        dd = self._dedup.mem_stats()
        host["dedup"] = dd["bytes"]
        log_stats = getattr(self.log, "mem_stats", None)
        if log_stats is not None:
            host["oplog_tail"] = int(log_stats()["total_bytes"])
        host["row_map"] = capacity.dict_nbytes(
            len(self._doc_rows), capacity.INT_DICT_ENTRY_BYTES + 60)
        n_docs = max(1, int(getattr(self, "n_docs", 0) or 0))
        row_share = sum(device.values()) // n_docs
        per_doc = self._dedup.per_doc_entries()
        per_entry = dd["bytes"] // max(1, dd["entries"])
        ranked = sorted(
            ((doc, row_share + per_doc.get(doc, 0) * per_entry)
             for doc in self._doc_rows),
            key=lambda kv: kv[1], reverse=True)[:8]
        return capacity.report(host=host, device=device,
                               docs=self.resident_docs,
                               heaviest=ranked)

    # ------------------------------------------------ incremental summaries
    # The engine-agnostic dirty-row detection behind summarize(
    # incremental=True) (SURVEY.md §2.16 handle reuse): a row is dirty
    # when its doc sequenced an op since the last summary (host-side, no
    # device read), when its doc↔row mapping changed (graduation, row
    # reuse), or when its device state was rewritten outside the op
    # stream (_dirty_outside_ops). Engines call _dirty_rows_since +
    # _note_summary and store per-store deltas; load() resolves the
    # delta chain via resolve_summary_chain.

    def _incremental_ok(self, incremental: bool) -> bool:
        return (incremental and self._summ_bookkeeping is not None
                and self._chain_depth < self.max_incremental_chain)

    def _dirty_rows_since(self, prev: dict):
        """(dirty row set, current doc seqs) vs the previous summary."""
        cur_seqs = {d: self.deli.doc_seq(d) for d in self._doc_rows}
        dirty = {row for d, row in self._doc_rows.items()
                 if cur_seqs[d] != prev["doc_seqs"].get(d)}
        # rows whose mapping changed since the base: their planes may
        # have been cleared or adopted outside the op stream
        dirty |= {row for d, row in prev["row_of"].items()
                  if self._doc_rows.get(d) != row}
        dirty |= {self._doc_rows[d] for d in self._dirty_outside_ops
                  if d in self._doc_rows}
        return dirty, cur_seqs

    def _note_summary(self, summary: dict, cur_seqs: dict,
                      **extra) -> None:
        self._dirty_outside_ops.clear()
        self._summ_bookkeeping = {
            "summary": summary, "doc_seqs": cur_seqs,
            "row_of": dict(self._doc_rows),
            "members": frozenset(self._members), **extra}

    def _mark_delta(self, summary: dict, prev: dict,
                    cur_seqs: dict) -> None:
        """Stamp a ``_base_summary()`` as a delta over ``prev`` and slim
        its resilience state to O(changed): the dedup ledger rides only
        for docs that sequenced an op since the base, membership as a
        join/leave diff — an idle 512-doc mesh must not re-ship the full
        ledger and roster in every delta. ``_restore_base`` resolves the
        chain (base ledger/roster, then each delta's slice)."""
        summary["kind"] = "delta"
        summary["base"] = prev["summary"]
        changed = {d for d, s in cur_seqs.items()
                   if s != prev["doc_seqs"].get(d)}
        summary["dedup"] = self._dedup.snapshot(docs=changed)
        cur = frozenset(self._members)
        base_members = prev.get("members", frozenset())
        del summary["members"]
        summary["members_delta"] = {
            "join": sorted([d, c] for d, c in cur - base_members),
            "leave": sorted([d, c] for d, c in base_members - cur)}

    @staticmethod
    def resolve_summary_chain(summary: dict):
        """(newest full summary, deltas oldest→newest) of an incremental
        chain (identity for a full summary)."""
        chain: List[dict] = []
        full = summary
        while full.get("kind") == "delta":
            chain.append(full)
            full = full["base"]
        return full, chain[::-1]

    def _check_poisoned(self) -> None:
        if self._poisoned:
            raise RuntimeError(
                f"engine poisoned ({self._poisoned}): device state may be "
                "ahead of the durable log; rebuild via load() from the "
                "latest summary + log")

    def enable_attribution(self) -> None:
        """Record (client, timestamp) per sequenced op for serving-side
        attribution queries (reference: @fluid-experimental/attributor)."""
        if self._attributors is None:
            self._attributors = {}

    def _attributor_of(self, doc_id: str):
        if doc_id not in self._attributors:
            self._attributors[doc_id] = Attributor()
        return self._attributors[doc_id]

    def _record_attribution(self, msg: SequencedDocumentMessage) -> None:
        if self._attributors is not None:
            self._attributor_of(msg.doc_id).record(msg)

    # ------------------------------------------------------------ membership

    def doc_row(self, doc_id: str) -> int:
        if doc_id not in self._doc_rows:
            if self._free_rows:
                row = self._free_rows.pop()
            elif self._next_row < self.n_docs:
                row = self._next_row
                self._next_row += 1
            else:
                raise KeyError(f"document capacity {self.n_docs} exhausted")
            self._doc_rows[doc_id] = row
        return self._doc_rows[doc_id]

    @property
    def resident_docs(self) -> int:
        """Documents currently holding a device row (partition
        occupancy: ``/debug/partitions`` reads this per engine)."""
        return len(self._doc_rows)

    # ------------------------------------------- columnar-ingest row caches

    def _init_row_caches(self, n_docs: int) -> None:
        """doc id / native sequencer handle / log partition by row —
        filled as rows are allocated; engines with a columnar ingest path
        call this from __init__ and populate in their ``doc_row``."""
        self._row_doc_id: List[Optional[str]] = [None] * n_docs
        self._row_handle = np.full(n_docs, -1, np.int32)
        self._row_part = np.zeros(n_docs, np.int32)

    def _note_row(self, doc_id: str, row: int) -> None:
        if self._row_doc_id[row] is None:
            self._row_doc_id[row] = doc_id
            self._row_part[row] = partition_of(doc_id, self.log.n_partitions)
            if self._row_floor is not None:
                self._row_floor[row] = self._min_seq.get(doc_id, 0)

    def _set_min_seq(self, doc_id: str, min_seq: int) -> None:
        """Record ``doc_id``'s MSN floor, in the dict and, where the
        engine keeps one and the doc holds a flat row, in ``_row_floor``:
        ``_row_floor[r] == _min_seq.get(doc, 0)`` for every flat row,
        0 on every free one."""
        self._min_seq[doc_id] = min_seq
        if self._row_floor is not None:
            row = self._doc_rows.get(doc_id)
            if row is not None:
                self._row_floor[row] = min_seq

    def _fill_row_handles(self, rows: np.ndarray, raw) -> None:
        if (self._row_handle[rows] < 0).any():
            for r in rows:
                if self._row_handle[r] < 0:
                    if self._row_doc_id[r] is None:
                        raise KeyError(
                            f"row {int(r)} has no document (allocate via "
                            "doc_row before columnar ingest)")
                    self._row_handle[r] = raw.doc_handle(self._row_doc_id[r])

    # ------------------------------------------ shared columnar protocol
    # The sequencing/durability invariants every engine's columnar ingest
    # must uphold, held in ONE place: sequence the raw batch in one native
    # call, then POISON the engine until its whole-batch durable record is
    # appended (any failure in between leaves doc.seq — and possibly
    # device state — ahead of the log; a summary taken then would persist
    # ops the log never recorded).

    def _sequence_columnar(self, raw, handles, client, client_seq,
                           ref_seq, what: str, doc_of=None):
        """One native sequencing call + the poison sentinel + nack
        metrics. Returns (out_seq, out_min, nacked mask, n_ok).

        ``doc_of`` (flat slot index → doc id) arms the idempotent dup-ack
        path: DUPLICATE-nacked slots found in the dedup ledger get their
        ORIGINAL seq patched into ``out_seq`` (positive, so the ack fan
        re-acks them) while staying in the ``nacked`` mask (never
        re-applied, never re-logged). ``self._dup_acked_last`` counts
        them for the caller's result dict."""
        out_seq, out_min = raw.sequence_batch_rows(
            handles, client, client_seq, ref_seq)
        with self._poison_lock:
            self._seq_unlogged += 1
            self._poisoned = f"{what} failed after sequencing"
        # crash here = batch sequenced, nothing durable, nothing acked; a
        # restarted engine (summary + log tail) must never see these seqs
        fault_point(SITE_INGEST_MID_BATCH, what=what)
        nacked = out_seq < 0
        n_ok = int((~nacked).sum())
        n_dup = 0
        if doc_of is not None and nacked.any():
            # -3 = the native DUPLICATE nack code (see _NACK_BY_CODE)
            for i in np.flatnonzero(out_seq == -3):
                orig = self._dedup.lookup(doc_of(int(i)), int(client[i]),
                                          int(client_seq[i]))
                if orig is not None:
                    out_seq[i] = orig
                    n_dup += 1
        self._dup_acked_last = n_dup
        self.metrics.inc("ops_ingested", n_ok)
        if n_dup:
            REGISTRY.inc("resubmit_dups_acked_total", n_dup)
        n_nack = int(nacked.sum()) - n_dup
        if n_nack:
            self.metrics.inc("nacks", n_nack)
        return out_seq, out_min, nacked, n_ok

    @staticmethod
    def _clamped_ref(ref_flat: np.ndarray, out_seq: np.ndarray):
        """The logged ref_seq is the CLAMPED one (min(ref, seq-1), what
        the sequencer recorded): replaying a raw inflated ref would push
        a client's ref past doc.seq and permanently nack later ops."""
        return np.minimum(ref_flat.astype(np.int64),
                          np.maximum(out_seq - 1, 0))

    def _fenced_append(self, partition: int, record: Any) -> int:
        """Durable append stamped with this engine's writer epoch — a
        deposed engine (fence bumped by a promoted follower or a
        recovered service) gets :class:`FencedWriterError` here instead
        of interleaving seqs into the stream it no longer owns."""
        if self.writer_epoch is None:  # log without a fence word
            return self.log.append(partition, record)
        return self.log.append(partition, record,
                               epoch=self.writer_epoch)

    def acquire_write_authority(self) -> Optional[int]:
        """Takeover edge: bump the log's fence and adopt the new epoch —
        every other live engine on this log becomes a fenced zombie.
        Called by ``OplogFollower.promote()``; ``LocalService.recover()``
        does the equivalent on its service-level logs."""
        bump = getattr(self.log, "bump_fence", None)
        if bump is None:
            return None
        self.writer_epoch = bump()
        setattr(self.deli, "epoch", self.writer_epoch)
        return self.writer_epoch

    def _append_columnar(self, record: "ColumnarOps") -> None:
        """Whole-batch durable append (round-robin partition for balance)
        + poison clear: sequence → merge → log completed."""
        p = self._col_part
        self._col_part = (p + 1) % self.log.n_partitions
        self._fenced_append(int(p), record)
        self.partition_metrics[p].inc("appends")
        self._ingest_mark_logged()

    def _ingest_mark_logged(self) -> None:
        """One sequenced wave's durable append committed: poison clears
        only when NO older sequenced-but-unlogged wave remains (pipelined
        ingest keeps several in flight; any of them crashing must leave
        the engine refusing summaries until rebuilt)."""
        with self._poison_lock:
            if self._seq_unlogged > 0:
                self._seq_unlogged -= 1
            if self._seq_unlogged == 0:
                self._poisoned = None

    def _ingest_inflight(self) -> int:
        """Sequenced-but-unlogged wave count (pipelined ingest depth)."""
        with self._poison_lock:
            return self._seq_unlogged

    def connect(self, doc_id: str, client_id: int
                ) -> SequencedDocumentMessage:
        # row allocation is lazy (first op/read), so a JOIN never pins the
        # doc to a tier it should not land on
        msg = self.deli.client_join(doc_id, client_id)
        self._log_append(doc_id, msg)
        self._members.add((doc_id, int(client_id)))
        return msg

    def disconnect(self, doc_id: str, client_id: int
                   ) -> Optional[SequencedDocumentMessage]:
        msg = self.deli.client_leave(doc_id, client_id)
        if msg is not None:
            self._log_append(doc_id, msg)
        self._members.discard((doc_id, int(client_id)))
        return msg

    def is_member(self, doc_id: str, client_id: int) -> bool:
        """Whether this identity already holds a seat (a resuming client
        must NOT re-join: ``client_join`` resets the sequencer's dedup
        window, re-opening it to already-sequenced resubmits). Tracked
        host-side because the native sequencer doesn't expose it."""
        return (doc_id, int(client_id)) in self._members

    def last_client_seq(self, doc_id: str, client_id: int) -> int:
        """Resync cursor: the highest clientSeq durably accepted from
        this identity (dedup-ledger view; the Python sequencer's live
        counter — which also covers sequenced-but-unlogged burns — wins
        when available)."""
        lcs = self._dedup.last(doc_id, client_id)
        live = getattr(self.deli, "last_client_seq", None)
        if callable(live):
            lcs = max(lcs, live(doc_id, client_id))
        return lcs

    def note_acked(self, doc_id: str, client_id: int, client_seq: int,
                   seq: int) -> None:
        """Ack-path ledger hook: the ingress tier records each op at the
        moment it acks (post-durable-append), arming idempotent dup-acks
        for later resubmits of the same op."""
        self._dedup.record(doc_id, client_id, client_seq, seq)

    def note_acked_planes(self, rows, clients, client_seqs, seqs) -> None:
        """Vectorized ``note_acked``: one call (and one ledger lock) per
        ack window, recorded by row in the ledger's arrays. ``seqs <= 0``
        entries are nacks — never recorded."""
        n = self._dedup.record_planes(rows, clients, client_seqs, seqs,
                                      self._row_doc_id)
        if n:
            REGISTRY.inc("dedup_planes_recorded", n)

    # --------------------------------------------------------------- ingress

    def submit(self, doc_id: str, client_id: int, client_seq: int,
               ref_seq: int, contents: Any
               ) -> Tuple[Optional[SequencedDocumentMessage], Optional[Nack]]:
        """Ingest one raw op. Returns (sequenced message, None) — the
        broadcast/ack — or (None, nack). Malformed contents and capacity
        overflows are nacked BEFORE sequencing/logging: an acked-and-logged
        op the flush path cannot apply would poison the engine AND its
        recovery replay (the log is replayed through the same path)."""
        self._check_poisoned()
        if not self._valid_op(contents):
            return self._nacked(Nack(doc_id, client_id, client_seq,
                                     NackReason.MALFORMED))
        try:
            self._admit(doc_id, contents, client_id)
        except KeyError:
            return self._nacked(Nack(doc_id, client_id, client_seq,
                                     NackReason.CAPACITY))
        with tracing.span("serving.submit", doc=doc_id) as sp:
            msg, nack = self.deli.sequence(
                doc_id, client_id, client_seq, ref_seq, MessageType.OP,
                contents)
            if nack is not None:
                self._unadmit(doc_id, contents)
                if nack.reason == NackReason.DUPLICATE:
                    orig = self._dedup.lookup(doc_id, client_id,
                                              client_seq)
                    if orig is not None:
                        # idempotent dup-ack: the resubmit is durable at
                        # ``orig`` — hand the original stamp back instead
                        # of a bare nack (callers check nack.seq >= 0)
                        nack.seq = orig
                        REGISTRY.inc("resubmit_dups_acked_total")
                return self._nacked(nack)
            self.metrics.inc("ops_ingested")
            sp.annotate(seq=msg.seq)
            # the engine's ack (returning msg) closes this span; carry
            # the context on the message so flush — often a later batch
            # on another call — still links to the submitting trace
            if sp.ctx is not None:
                msg.trace = sp.ctx.to_wire()
            # crash here = sequenced but never logged: the op was NOT
            # acked (submit didn't return), so recovery may drop it —
            # but sequencer counters restored from the log must stay
            # monotone regardless
            fault_point(SITE_SUBMIT_POST_SEQUENCE, doc_id=doc_id,
                        seq=msg.seq)
            self._log_append(doc_id, msg)
            # durable now: ledger the ack for idempotent resubmit handling
            self._dedup.record(doc_id, client_id, client_seq, msg.seq)
            self._record_attribution(msg)
            self._enqueue(doc_id, msg)
            self._set_min_seq(doc_id, msg.min_seq)
            if self._queued() >= self.batch_window:
                self.flush()
        return msg, None

    def _nacked(self, nack: Nack) -> Tuple[None, Nack]:
        self.metrics.inc("nacks")
        self.metrics.inc(f"nacks_{nack.reason.name.lower()}")
        return None, nack

    def _valid_op(self, contents: Any) -> bool:
        """Subclasses reject op shapes their flush path cannot apply."""
        return True

    @staticmethod
    def _is_nat(v, lo: int = 0) -> bool:
        return isinstance(v, int) and not isinstance(v, bool) and v >= lo

    def _admit(self, doc_id: str, contents: Any,
               client_id: int = -1) -> None:
        """Reserve the capacity the op will need at flush (doc row here;
        subclasses add store-specific reservations like key/client
        slots). Raises KeyError on exhaustion → the op is nacked before
        it is logged."""
        self.doc_row(doc_id)

    def _unadmit(self, doc_id: str, contents: Any) -> None:
        """Undo ``_admit``'s reservations when the sequencer nacks AFTER
        admission — otherwise a stream of deli-nacked ops (stale ref_seq,
        clientSeq gaps) leaks capacity that was never used."""

    def _log_append(self, doc_id: str, msg: SequencedDocumentMessage) -> None:
        p = partition_of(doc_id, self.log.n_partitions)
        self._fenced_append(p, msg)
        self.partition_metrics[p].inc("appends")

    def _enqueue(self, doc_id: str, msg: SequencedDocumentMessage) -> None:
        self._queue.append((self.doc_row(doc_id), msg))

    def _queued(self) -> int:
        return len(self._queue)

    # ------------------------------------------------- per-shard rollups
    # A meshed engine's planes are row-sharded over the docs axis; the
    # health plane wants per-shard series (ops applied per chip, load
    # imbalance). Rows map to shards by contiguous block — the same
    # row→device placement NamedSharding(P("docs", ...)) uses.

    def _ensure_shard_collectors(self) -> None:
        if self._shard_rollup_done:
            return
        self._shard_rollup_done = True
        mesh = getattr(self, "mesh", None)
        if mesh is None:
            return
        try:
            from ..parallel.sharded import doc_shard_count
            n_shards = doc_shard_count(mesh)
        except ImportError:
            return
        if n_shards < 2:
            return
        self._rows_per_shard = max(1, self.n_docs // n_shards)
        name = type(self).__name__
        for s in range(n_shards):
            coll = MetricsCollector()
            REGISTRY.attach(name, coll, labels={"shard": s})
            self.shard_metrics.append(coll)

    def _note_shard_ops(self, rows, counts=None) -> None:
        """Credit applied ops to their row-block shards: ``rows`` is the
        batch's row plane, ``counts`` an optional per-row op count (the
        columnar path's valid-slot counts; default 1 per row)."""
        if not self.shard_metrics:
            return
        rows = np.asarray(rows, np.int64)
        if rows.size == 0:
            return
        from ..parallel.sharded import shard_of_rows
        shard = shard_of_rows(rows, self.n_docs, len(self.shard_metrics))
        per = np.bincount(shard, weights=counts,
                          minlength=len(self.shard_metrics))
        for coll, c in zip(self.shard_metrics, per):
            if c:
                coll.inc("ops_applied", float(c))
        if counts is not None:
            # a columnar window: the shards it has ops in and the ops of
            # its fullest one; over the windows flushed and over their ops
            # these give a window's spread and its skew over the mesh
            REGISTRY.inc("mesh_window_shards", int(np.count_nonzero(per)))
            REGISTRY.inc("mesh_window_ops_fullest_shard", float(per.max()))

    def flush(self) -> int:
        """Template: time the subclass's device apply, record batch-size
        and latency metrics, drive the compaction cadence."""
        # crash here = the window is logged (submit acked after append)
        # but not yet applied: recovery MUST replay it from the log
        fault_point(SITE_FLUSH_MID_BATCH, queued=self._queued())
        self._ensure_shard_collectors()
        flushed_rows = [r for r, _ in self._queue]
        # flush parents under the newest queued op's submit span when
        # one exists (batch-triggered flush), else under the caller's
        # context (explicit flush inside a traced read)
        parent = None
        if self._queue:
            parent = getattr(self._queue[-1][1], "trace", None)
        with tracing.span("serving.flush", parent=parent,
                          queued=self._queued()) as sp:
            t0 = time.perf_counter()
            # degradation injection: an armed plan may stall here (a
            # device hiccup) — the watchdog below must see it
            fault_point(SITE_APPLY_STALL, what="flush")
            n = self._flush_impl()
            elapsed_ms = (time.perf_counter() - t0) * 1000
            sp.annotate(ops=n, ms=elapsed_ms)
        if n:
            self.metrics.inc("flushes")
            self.metrics.inc("ops_flushed", n)
            # exemplar: a later SLO breach on flush latency names the
            # trace of the worst flush, not just the percentile
            self.metrics.observe("flush_ms", elapsed_ms,
                                 exemplar=sp.ctx)
            self._note_shard_ops(flushed_rows)
        self._watch_apply(elapsed_ms, "flush", n)
        self._after_flush(n)
        return n

    def _watch_apply(self, elapsed_ms: float, what: str, n_ops: int) -> None:
        """Apply watchdog: surface any device apply slower than
        ``stall_threshold_ms`` as a counted, recorded, warned stall."""
        if elapsed_ms <= self.stall_threshold_ms:
            return
        self.metrics.inc("apply_stalls")
        event = {"what": what, "ms": elapsed_ms, "ops": n_ops,
                 "wall": time.time()}
        self.stall_events.append(event)
        del self.stall_events[:-self._STALL_KEEP]
        self.telemetry.send_warning("apply_stall", **event)
        # stall context goes straight into the crash flight recorder:
        # if the NEXT thing that happens is a faultpoint crash or a
        # drill assertion, the dump shows the stall that preceded it
        flight_recorder.note("apply_stall",
                             engine=type(self).__name__, **event)

    def _flush_impl(self) -> int:
        """Apply the queued window on device; returns messages applied."""
        raise NotImplementedError

    def attach_read_plane(self, plane) -> None:
        """Hang a ``read_plane.ReadPlane`` on this engine: every flush
        that applied ops pumps one encoded observer window. Detach with
        ``attach_read_plane(None)``."""
        self._read_plane = plane

    def _after_flush(self, n: int) -> None:
        if n:
            self._flushes_since_compact += 1
            if self._flushes_since_compact >= self.compact_every:
                self.compact()
            plane = self._read_plane
            if plane is not None:
                plane.pump()

    def compact(self) -> None:
        self.metrics.inc("compactions")
        self._flushes_since_compact = 0

    # ----------------------------------------------------- summary / recovery
    # The engine-agnostic half of the single recovery primitive (summary +
    # log-tail replay through the same apply path). Subclass summarize()
    # merges _base_summary() with its store snapshot(s); subclass load()
    # calls _restore_base() then _replay_tail().

    def _base_summary(self) -> dict:
        self._check_poisoned()
        sizes = [self.log.size(p) for p in range(self.log.n_partitions)]
        chain_at = getattr(self.log, "chain_at", None)
        out = {
            "deli": self.deli.checkpoint(),
            "log_offsets": sizes,
            # checksum-chain anchor (ISSUE 10): the chain word at each
            # partition's summary offset; load() verifies the live log
            # still carries these exact bytes before tail replay — a
            # truncated-then-regrown or spliced log fails loudly instead
            # of silently replaying a different history. None per
            # partition when the log has no durable chain (memory-only).
            "chain_heads": [chain_at(p, s) if chain_at is not None
                            else None for p, s in enumerate(sizes)],
            "doc_rows": dict(self._doc_rows),
            "min_seq": dict(self._min_seq),
            "dedup": self._dedup.snapshot(),
            "members": [[d, c] for d, c in sorted(self._members)],
        }
        if self._attributors is not None:
            out["attribution"] = {d: a.summarize()
                                  for d, a in self._attributors.items()}
        return out

    def _restore_base(self, summary: dict) -> None:
        # keep the engine's (possibly injected deterministic) clock
        self.deli = restore_sequencer(summary["deli"],
                                      clock=self.deli.clock)
        setattr(self.deli, "epoch", self.writer_epoch or 0)
        self._doc_rows = dict(summary["doc_rows"])
        used = set(self._doc_rows.values())
        self._next_row = max(used) + 1 if used else 0
        self._free_rows = [r for r in range(self._next_row)
                           if r not in used]
        self._min_seq = dict(summary["min_seq"])
        if self._row_floor is not None:
            self._row_floor[:] = 0
            for doc_id, row in self._doc_rows.items():
                self._row_floor[row] = self._min_seq.get(doc_id, 0)
        # resilience state (absent from pre-resilience summaries): a
        # delta chain carries the full ledger/roster only in its base
        # full summary plus an O(changed) slice per delta — resolve
        # oldest→newest so the restored state matches the live one
        full, deltas = self.resolve_summary_chain(summary)
        self._dedup = DedupLedger.load(full.get("dedup"))
        members = {(d, int(c)) for d, c in full.get("members") or []}
        for d_sum in deltas:
            self._dedup.merge(d_sum.get("dedup"))
            md = d_sum.get("members_delta") or {}
            members |= {(d, int(c)) for d, c in md.get("join", [])}
            members -= {(d, int(c)) for d, c in md.get("leave", [])}
        self._members = members
        if summary.get("attribution") is not None:
            self._attributors = {d: Attributor.load(a)
                                 for d, a in summary["attribution"].items()}

    def _verify_tail_anchor(self, summary: dict) -> None:
        """Anchor the tail replay against the summary's recorded chain
        heads: the live log must (a) still reach every partition's
        summary offset — a shorter log means the durable stream was
        truncated at a record boundary, which no local scan can see —
        and (b) carry the exact chain word the summary saw there, so a
        spliced/regrown prefix fails before a single byte is replayed."""
        offsets = summary.get("log_offsets")
        if offsets is None:
            return
        heads = summary.get("chain_heads")
        chain_at = getattr(self.log, "chain_at", None)
        for p in range(self.log.n_partitions):
            off = int(offsets[p])
            if self.log.size(p) < off:
                REGISTRY.inc("oplog_chain_verify_failures_total")
                raise OplogCorruptionError(
                    f"log p{p} holds {self.log.size(p)} records but the "
                    f"summary was cut at offset {off}: durable stream "
                    f"truncated behind the summary", index=off,
                    reason="log shorter than summary anchor")
            if heads is None or chain_at is None or heads[p] is None:
                continue
            have = chain_at(p, off)
            if have != int(heads[p]):
                REGISTRY.inc("oplog_chain_verify_failures_total")
                raise OplogCorruptionError(
                    f"log p{p} chain word at offset {off} is "
                    f"{'absent' if have is None else hex(have)}, summary "
                    f"anchored {int(heads[p]):#010x}: log bytes diverged "
                    f"from the summarized history", index=off,
                    reason="chain anchor mismatch")

    def _replay_tail(self, summary: dict, control_hook=None) -> None:
        """Replay EVERY tail message through the sequencer state (so
        resumed sequencing continues past the tail, not from the stale
        checkpoint); JOINs re-register clients (a join-only doc must
        survive recovery); OPs queue for the device merge. A
        ``control_hook(msg) -> True`` consumes engine-specific control
        records before they reach the stores."""
        self._verify_tail_anchor(summary)
        tail: List[SequencedDocumentMessage] = []
        for p in range(self.log.n_partitions):
            for rec in self.log.read(p,
                                     from_offset=summary["log_offsets"][p]):
                # columnar batches (ColumnarOps, TreeRecordOps) expand to
                # per-op messages; engines with a raw-record fast path
                # override _replay_tail instead
                tail.extend(rec.expand() if hasattr(rec, "expand")
                            else (rec,))
        # Partition scan order is NOT chronological: whole-batch columnar
        # records round-robin across partitions while JOIN/LEAVE stay in
        # the doc's own partition. Replaying a client's ops before its
        # JOIN would silently skip them in the sequencer and then let the
        # JOIN replay reset ClientState to last_client_seq=0 — the
        # client's next op is CLIENT_SEQ_GAP-nacked forever and resent
        # old clientSeqs are re-accepted (dedupe broken). Sort the whole
        # tail by (doc, seq) — seqs are per-doc, and JOIN/LEAVE carry
        # theirs — so every doc replays in true chronological order.
        tail.sort(key=lambda m: (m.doc_id, m.seq))
        for msg in tail:
            self.deli.replay(msg)
            self._absorb_resilience(msg)
            self._record_attribution(msg)
            if control_hook is not None and control_hook(msg):
                continue
            if msg.type == MessageType.OP:
                self._enqueue(msg.doc_id, msg)
                self._set_min_seq(msg.doc_id, max(
                    self._min_seq.get(msg.doc_id, 0), msg.min_seq))
        self._queue.sort(key=lambda dm: dm[1].seq)

    def _absorb_resilience(self, msg: SequencedDocumentMessage) -> None:
        """Fold one replayed message into the resilience state (member
        set + dedup ledger) — the durable half of (clientId, clientSeq)
        dedup: a rebuilt engine must refuse (and idempotently re-ack)
        clientSeqs it accepted in its previous life."""
        if msg.type == MessageType.CLIENT_JOIN:
            self._members.add((msg.doc_id, int(msg.client_id)))
        elif msg.type == MessageType.CLIENT_LEAVE:
            self._members.discard((msg.doc_id, int(msg.client_id)))
        elif msg.type == MessageType.OP and msg.client_id >= 0:
            self._dedup.record(msg.doc_id, msg.client_id,
                               msg.client_seq, msg.seq)


class _IngestWave:
    """Per-wave carrier threaded through the four columnar-ingest stages
    (``_ingest_prepare`` → ``_ingest_sequence`` → ``_ingest_dispatch`` →
    ``_ingest_log``); the pipelined executor hands one of these from
    worker to worker, the serial ``ingest_planes`` walks it in place."""
    __slots__ = (
        "rows", "R", "O", "kind", "a0", "a1", "client",
        "ref_seq", "text", "texts", "tidx", "props", "flat_client",
        "flat_client_seq", "flat_ref_seq", "handles", "prepacked",
        "pipelined", "prep_ms", "seq_ms", "out_seq", "out_min", "nacked",
        "n_ok", "kind_eff", "seq_rs", "seq_base", "n_valid", "min_rs",
        "compact_due", "ms_arr", "apply_stats", "ov_prev", "dup_acked",
        "marks")

    def __init__(self):
        self.prepacked = None
        self.pipelined = False
        self.prep_ms = 0.0
        self.seq_ms = 0.0
        self.apply_stats = {}
        self.ov_prev = None
        # the window's record (utils.tracing): each stage method stamps
        # its spans and its two crossings here (``pack0``/``pack1`` ...);
        # the front door hands its own record in (``marks=``) and closes
        # it at the ack fan
        self.marks: dict = {}


class StringServingEngine(ServingEngineBase):
    """Sequencer + durable log + batched device merge for many documents."""

    def __init__(self, n_docs: int, capacity: int = 256, n_props: int = 4,
                 batch_window: int = 64, n_partitions: int = 8,
                 compact_every: int = 16,
                 log: Optional[PartitionedLog] = None,
                 store: Optional[TensorStringStore] = None,
                 mega_docs: int = 0, mega_capacity_per_shard: int = 256,
                 mega_store=None, sequencer: str = "python", mesh=None):
        """``mesh``: a 1-D ``docs`` device mesh (``parallel.sharded.
        make_doc_mesh``) shards the store's planes by doc row across chips
        — the scale-out configuration of SURVEY.md §2.14; every flush then
        runs as a collective-free shard_map of the same kernels."""
        super().__init__(batch_window, n_partitions, compact_every, log,
                         sequencer=sequencer)
        self._init_row_caches(n_docs)
        self._row_floor = np.zeros(n_docs, np.int32)
        if store is not None and mesh is not None \
                and getattr(store, "mesh", None) is not mesh:
            raise ValueError("mesh given with a store that is not sharded "
                             "over it; build the store with mesh= or "
                             "restore(snap, mesh=...)")
        self.store = store if store is not None \
            else TensorStringStore(n_docs, capacity, n_props, mesh=mesh)
        self.mesh = getattr(self.store, "mesh", mesh)
        # in-flight async overflow-flag copy (deferred harvest; see
        # ingest_planes' compact-due branch)
        self._ov_pending = None
        # mega tier: documents too long for one chip's slot budget are
        # served by the segment-axis-sharded store (declare with mark_mega
        # BEFORE the doc's first op; capacity here is per shard per doc)
        self.mega_store = mega_store
        if mega_store is None and mega_docs > 0:
            from ..ops.megadoc_store import MegaDocStringStore
            self.mega_store = MegaDocStringStore(mega_docs,
                                                 mega_capacity_per_shard)
        self.n_docs = n_docs
        self._mega_rows: Dict[str, int] = {}
        self._free_mega_rows: List[int] = []
        self._mega_queue: List[Tuple[int, SequencedDocumentMessage]] = []
        # graduated tier: docs whose compacted state outgrew their tier's
        # slot budget are served from their own right-sized store (the
        # terminal stage of the overflow escape hatch)
        self._graduated: Dict[str, TensorStringStore] = {}
        self._grad_queue: List[Tuple[str, SequencedDocumentMessage]] = []
        #: overflow flags are checked (one device→host read) and recovery
        #: runs automatically on the compaction cadence
        self.auto_recover = True

    # ------------------------------------------------------------ membership

    def doc_row(self, doc_id: str) -> int:
        if doc_id in self._mega_rows:
            return self._mega_rows[doc_id]
        row = super().doc_row(doc_id)
        self._note_row(doc_id, row)
        return row

    def mark_mega(self, doc_id: str) -> None:
        """Route this document to the segment-axis-sharded mega tier (must
        happen before its first op; requires mega_docs capacity). The mark
        is appended to the durable log so recovery replays it before the
        doc's ops — membership survives a crash between summaries."""
        if self.mega_store is None:
            raise ValueError("engine created without a mega tier")
        if doc_id in self._doc_rows:
            raise ValueError(f"{doc_id} already has ops on the flat tier")
        if doc_id not in self._mega_rows:
            self._register_mega(doc_id)
            self._log_append(doc_id, SequencedDocumentMessage(
                doc_id=doc_id, client_id=-1, client_seq=0, ref_seq=0,
                seq=0, min_seq=0, type=MessageType.PROPOSAL,
                contents={"markMega": True}))

    def _register_mega(self, doc_id: str) -> None:
        if self._free_mega_rows:
            self._mega_rows[doc_id] = self._free_mega_rows.pop()
            return
        nxt = len(self._mega_rows) + len(self._free_mega_rows)
        if nxt >= self.mega_store.n_docs:
            raise KeyError("mega-doc capacity exhausted")
        self._mega_rows[doc_id] = nxt

    # --------------------------------------------------------------- ingress

    @classmethod
    def _valid_props(cls, props, required: bool) -> bool:
        if props is None:
            return not required
        if not (isinstance(props, dict) and
                all(isinstance(k, str) for k in props)):
            return False
        if required and not props:
            return False
        try:  # flush JSON-interns values: reject unserializable now
            json.dumps(props)
        except (TypeError, ValueError):
            return False
        return True

    def _valid_op(self, contents: Any) -> bool:
        """Full structural validation BEFORE sequencing/logging: a logged op
        the flush path cannot turn into device records would poison the
        engine and its recovery replay (the submit() invariant)."""
        if not isinstance(contents, dict):
            return False
        mt = contents.get("mt")
        if mt == "insert":
            kind = contents.get("kind")
            if not (self._is_nat(kind) and kind in (0, 1)
                    and self._is_nat(contents.get("pos"))):
                return False
            if contents["kind"] == 0 and \
                    not isinstance(contents.get("text"), str):
                return False
            return self._valid_props(contents.get("props"), required=False)
        if mt == "remove":
            return (self._is_nat(contents.get("start"))
                    and self._is_nat(contents.get("end"))
                    and contents["start"] < contents["end"])
        if mt == "annotate":
            return (self._is_nat(contents.get("start"))
                    and self._is_nat(contents.get("end"))
                    and contents["start"] < contents["end"]
                    and self._valid_props(contents.get("props"),
                                          required=True))
        return False

    def _admit(self, doc_id: str, contents: Any,
               client_id: int = -1) -> None:
        """Row + property-interner reservation (KeyError → CAPACITY nack
        before the op is logged): an annotate whose key cannot get a plane
        would otherwise raise at flush. The reservation is transactional —
        ``_unadmit`` refunds it if the sequencer nacks afterwards."""
        if doc_id not in self._graduated:  # graduated docs own their store;
            self.doc_row(doc_id)           # don't re-pin a tier row
        self._admit_token = None
        props = contents.get("props")
        if props:
            store, _ = self._store_of(doc_id)
            self._admit_token = (store, store.reserve_props(props))

    def _unadmit(self, doc_id: str, contents: Any) -> None:
        if getattr(self, "_admit_token", None) is not None:
            store, minted = self._admit_token
            store.release_props(minted)
        self._admit_token = None

    def _enqueue(self, doc_id: str, msg: SequencedDocumentMessage) -> None:
        if doc_id in self._graduated:
            self._grad_queue.append((doc_id, msg))
            return
        row = self.doc_row(doc_id)
        if doc_id in self._mega_rows:
            self._mega_queue.append((row, msg))
        else:
            self._queue.append((row, msg))

    def _queued(self) -> int:
        return len(self._queue) + len(self._mega_queue) + \
            len(self._grad_queue)

    def heartbeat(self, doc_id: str, client_id: int, ref_seq: int) -> None:
        """NOOP: advances the client's refSeq (and the doc's MSN) so zamboni
        can reclaim tombstones; consumes no clientSeq."""
        msg, _ = self.deli.sequence(
            doc_id, client_id, 0, ref_seq, MessageType.NOOP, None)
        if msg is not None:
            self._set_min_seq(doc_id, msg.min_seq)
            # a heartbeat-only MSN advance must still slide interval anchors
            # at the crossing (the op stream won't carry this advance).
            # Only docs that already hold a row can have intervals — looking
            # one up via _store_of would lazily allocate a flat-tier row and
            # wrongly pin a heartbeat-only doc (breaking a later mark_mega).
            if doc_id in self._doc_rows or doc_id in self._mega_rows \
                    or doc_id in self._graduated:
                store, row = self._store_of(doc_id)
                if getattr(store, "_intervals", None) \
                        and store._intervals[row]:
                    self.flush()
                    store.advance_min_seq(row, msg.min_seq)

    # ------------------------------------------------------- columnar ingest

    def ingest_planes(self, rows, client, client_seq, ref_seq, kind, a0, a1,
                      text: str = "", texts=None, tidx=None,
                      props=None, marks: Optional[dict] = None) -> dict:
        """The high-throughput ingest path: a dense (R, O) columnar batch of
        RAW client string ops — sequenced in ONE native C call, bulk-appended
        to the durable log as per-partition ``ColumnarOps`` records, and
        merged in ONE device dispatch. This is the same submit→log→flush
        pipeline as ``submit``, minus per-op Python objects (SURVEY.md §7.5:
        the low-jitter host loop feeding the device batch).

        rows: (R,) flat-tier doc rows (allocate via ``doc_row``; clients must
        have joined via ``connect``). client/client_seq/ref_seq/kind/a0/a1:
        (R, O) int32 planes, ops of each doc in submission order. Removes
        use a0=start, a1=end. Payloads: the broadcast ``text`` (a1 derived),
        or per-op via ``texts`` + ``tidx`` ((R, O) indices). Annotates
        (kind == STR_ANNOTATE) are admitted when ``props`` (single-key-dict
        table, indexed by ``tidx``) is given — the distinct-payload /
        rich-text shapes real workloads produce (VERDICT r2 weak #4).

        Requires ``sequencer="native"``. Returns {"seq": (R, O) int64
        (negative = nack code), "nacked": int}. Nacked slots are skipped
        everywhere (not logged, not applied).

        Pipelining: the device merge is DISPATCHED (async) before the host
        does log packing/append — host log work rides under the device
        apply, so wall time per batch is max(host, device), not the sum.
        Crash-consistency is unaffected: recovery rebuilds from summary +
        log only, and the call returns (acks) after the log append.

        Docs holding intervals take this path too: the per-op min_seq
        plane from the sequencer rides into ``apply_planes`` as
        ``min_ops``, so anchor slides happen at the exact op where the
        window floor crosses a tombstone (see docs/INTERVALS.md) — no
        per-op submit() fallback."""
        self._check_poisoned()
        w = self._ingest_prepare(rows, client, client_seq, ref_seq, kind,
                                 a0, a1, text, texts, tidx, props,
                                 marks=marks)
        self._ingest_sequence(w)
        self._ingest_dispatch(w)
        return self._ingest_log(w)

    # ------------------------------------------- pipelined ingest stages
    # ``ingest_planes`` above is the serial composition of four stage
    # methods over an _IngestWave carrier; the pipelined executor
    # (server.ingest_pipeline) calls the SAME stages from its worker
    # threads so wave N+1's prepare/pack overlaps wave N's dispatch and
    # wave N−1's log append. Thread contract: prepare runs on the pack
    # worker (validation + payload prepack, FIFO), sequence+dispatch run
    # on one thread (they share the sequencer and compaction cursors),
    # log runs on the log worker (pure host I/O; acks fire after it).

    def _ingest_prepare(self, rows, client, client_seq, ref_seq, kind,
                        a0, a1, text="", texts=None, tidx=None,
                        props=None, prepack=False,
                        marks: Optional[dict] = None) -> "_IngestWave":
        """Stage 1 — validation, row-handle fill, plane flattening, and
        (``prepack=True``, pipelined mode) the payload/table pack, all
        independent of sequencing results. ``marks``: the door's record
        of this window (``utils.tracing``); a fresh one without."""
        raw = getattr(self.deli, "raw", None)
        if raw is None:
            raise RuntimeError("columnar ingest requires sequencer='native'")
        w = _IngestWave()
        w.marks = marks if marks is not None else tracing.new_record()
        with tracing.stage(w.marks, "engine.prepare", mark="pack") as sp:
            rows = np.ascontiguousarray(rows, np.int32)
            R, O = kind.shape
            if len(rows) != R or len(np.unique(rows)) != R:
                raise ValueError("rows must be exactly one UNIQUE row per "
                                 "plane row (duplicates would silently drop "
                                 "ops in the device scatter)")
            if self._graduated and any(self._row_doc_id[r] in self._graduated
                                       for r in rows):
                raise ValueError("a targeted doc has graduated off the flat "
                                 "tier; route its ops through submit()")
            kind = np.asarray(kind, np.int32)
            top = int(OpKind.STR_REMOVE)
            if props is not None:
                top = int(OpKind.STR_ANNOTATE)
                if any(len(p) != 1 for p in props):
                    raise ValueError("columnar annotates are single-key; "
                                     "multi-key props go through submit()")
                # reserve prop planes/values BEFORE sequencing: an op the
                # flush path cannot apply must never be acked+logged
                self.store.reserve_prop_tables(
                    {k for p in props for k in p},
                    [v for p in props for v in p.values()])
            # range compares, not np.isin: set membership over a 655k-op plane
            # costs ~8 ms for the same answer (the kind codes are contiguous
            # from STR_INSERT)
            if not bool(((kind >= int(OpKind.STR_INSERT))
                         & (kind <= top)).all()):
                raise ValueError("columnar planes must be dense "
                                 "insert/remove" +
                                 ("/annotate" if props is not None else ""))
            # tidx must be validated BEFORE sequencing: a negative index would
            # silently wrap (numpy fancy indexing) and apply/ack/log the WRONG
            # payload; an out-of-range one would raise only after the native
            # sequencer consumed seqs, leaving doc.seq ahead of the durable log
            if tidx is not None:
                tidx_arr = np.asarray(tidx, np.int32)
                if tidx_arr.shape != kind.shape:
                    raise ValueError("tidx shape must match the op planes")
                if (tidx_arr < 0).any():
                    raise ValueError("negative tidx in columnar batch")
                # masked maxima (initial=-1) instead of boolean extraction:
                # tidx_arr[mask] materializes a copy per check on the hot path
                if texts is not None and int(np.max(
                        tidx_arr, initial=-1,
                        where=kind == int(OpKind.STR_INSERT))) >= len(texts):
                    raise ValueError("insert tidx beyond the payload table")
                if props is not None and int(np.max(
                        tidx_arr, initial=-1,
                        where=kind == int(OpKind.STR_ANNOTATE))) >= len(props):
                    raise ValueError("annotate tidx beyond the props table")
            elif texts is not None or props is not None:
                raise ValueError("payload/props tables require the tidx plane")

            self._fill_row_handles(rows, raw)
            w.rows, w.R, w.O = rows, R, O
            w.kind = kind
            w.a0 = np.ascontiguousarray(np.asarray(a0, np.int32))
            w.a1 = np.ascontiguousarray(np.asarray(a1, np.int32))
            w.client = np.ascontiguousarray(np.asarray(client, np.int32))
            w.ref_seq = np.ascontiguousarray(np.asarray(ref_seq, np.int32))
            w.text, w.texts, w.tidx, w.props = text, texts, tidx, props
            w.flat_client = w.client.reshape(-1)
            w.flat_client_seq = np.ascontiguousarray(
                np.asarray(client_seq, np.int32).reshape(-1))
            w.flat_ref_seq = w.ref_seq.reshape(-1)
            w.handles = np.repeat(self._row_handle[rows], O)
            if prepack:
                w.pipelined = True
                # payload/table pack AHEAD of sequencing (overlaps the
                # previous wave's device dispatch). None = interval batch:
                # the executor barriers and the dispatch stage packs inline.
                w.prepacked = self.store.prepack_planes(
                    rows, kind, w.a0, w.a1, text, texts, tidx, props)
        # validation and flattening; the table pack timed itself
        w.prep_ms = sp.ms - (w.prepacked.prep_ms
                             if w.prepacked is not None else 0.0)
        return w

    def _ingest_sequence(self, w: "_IngestWave") -> None:
        """Stage 2 — ONE native sequencing call + the post-seq plane math
        (nack masking, per-row seq bases, window-floor fold)."""
        raw = self.deli.raw
        with tracing.stage(w.marks, "engine.sequence", mark="seq") as sp:
            self.flush()  # per-op queue first: per-doc seq order must hold
            rdi_rows = w.rows
            with tracing.stage(w.marks, "deli.sequence") as sp_deli:
                out_seq, out_min, nacked, n_ok = self._sequence_columnar(
                    raw, w.handles, w.flat_client, w.flat_client_seq,
                    w.flat_ref_seq, "columnar batch",
                    doc_of=lambda i: self._row_doc_id[rdi_rows[i // w.O]])
            w.out_seq, w.out_min, w.nacked, w.n_ok = out_seq, out_min, \
                nacked, n_ok
            # dup-acked resubmits: nacked (not re-applied/re-logged) but carry
            # their original positive seq in out_seq so the ack fan re-acks
            w.dup_acked = self._dup_acked_last
            R, O = w.R, w.O
            # nacked slots become NOOP (they consumed no seq); the store
            # rebuilds per-op seqs on device from each doc's base — only
            # narrow planes cross the host→device link (ref clamps on device)
            valid_rs = (~nacked).reshape(R, O)
            w.kind_eff = np.where(valid_rs, w.kind, int(OpKind.NOOP))
            w.seq_rs = out_seq.reshape(R, O)
            w.n_valid = valid_rs.sum(axis=1)
            w.seq_base = (np.max(np.where(valid_rs, w.seq_rs, 0), axis=1)
                          - w.n_valid).astype(np.int32)
            # window-floor tracking for zamboni: fold this batch's MSN advance
            # in BEFORE taking the fused compaction floor, so a compaction-due
            # batch zambonis at the post-batch floor (not one batch stale)
            w.min_rs = out_min.reshape(R, O)
            last_min = w.min_rs[:, -1]
            # C-level dict bulk update (zip over plain-int lists), not a
            # 10k-iteration Python loop with an int() per row; the row
            # array takes the same floors in one scatter
            rdi = self._row_doc_id
            self._min_seq.update(zip((rdi[r] for r in w.rows.tolist()),
                                     last_min.tolist()))
            self._row_floor[w.rows] = last_min
            w.compact_due = \
                self._flushes_since_compact + 1 >= self.compact_every
            # a copy: later windows write the array while this one's
            # merge may still be reading its floor
            w.ms_arr = self._row_floor.copy() if w.compact_due else None
        # the native call; the plane math around it counts as prep
        w.seq_ms = sp_deli.ms
        w.prep_ms += sp.ms - sp_deli.ms

    def _ingest_dispatch(self, w: "_IngestWave") -> None:
        """Stage 3 — the async device merge (zamboni fuses into the same
        dispatch on a compaction-due wave) + compaction cadence."""
        with tracing.stage(w.marks, "engine.dispatch", mark="disp"):
            # degradation injection: an armed plan may stall the device apply
            # here; the watchdog must surface it
            fault_point(SITE_APPLY_STALL, what="ingest_planes")
            pp = w.prepacked
            if pp is not None and getattr(self.store, "_iv_docs", None) \
                    and not self.store._iv_docs.isdisjoint(w.rows.tolist()):
                # intervals appeared on a targeted row between prepack and
                # apply (interval mutation racing the pipeline): fall back to
                # the inline pack, which mints the per-op anchor handles
                self.store._tab_release(pp)
                pp = w.prepacked = None
            self.store.apply_planes(
                w.rows, w.kind_eff, w.a0, w.a1, w.seq_base, w.client,
                w.ref_seq, w.text, min_seq=w.ms_arr, texts=w.texts,
                tidx=w.tidx, props=w.props, min_ops=w.min_rs, prepacked=pp,
                rec=w.marks)
            self._ensure_shard_collectors()
            self._note_shard_ops(w.rows, counts=w.n_valid)
            w.apply_stats = dict(getattr(self.store, "last_apply_stats",
                                         None) or {})
            if w.compact_due:
                self._flushes_since_compact = 0
                self.metrics.inc("compactions")
                self.metrics.inc("compaction_floors_from_rows")
                if self.mega_store is not None and self._mega_rows:
                    mms = np.zeros((self.mega_store.n_docs,), np.int32)
                    for doc_id, row in self._mega_rows.items():
                        mms[row] = self._min_seq.get(doc_id, 0)
                    self.mega_store.compact(mms)
                for doc_id, store in self._graduated.items():
                    store.compact(self._min_seq.get(doc_id, 0))
                if self.auto_recover:
                    # DEFERRED overflow harvest: a synchronous flag read here
                    # would drain the dispatch pipeline (a device→host sync)
                    # at every compaction. Instead start an async device→host
                    # copy of the flags now and inspect the PREVIOUS
                    # compaction's copy (already landed) — detection is one
                    # compaction late, which only delays recovery (the log has
                    # every acked op).
                    w.ov_prev = self._ov_pending
                    # jnp.copy: the live overflow buffer is donated away by
                    # the next merge; the stash must own its storage
                    import jax.numpy as jnp
                    self._ov_pending = jnp.copy(self.store.state.overflow)
                    try:
                        self._ov_pending.copy_to_host_async()
                    except (AttributeError, RuntimeError):
                        pass
            else:
                self._flushes_since_compact += 1

    def _ingest_log(self, w: "_IngestWave") -> dict:
        """Stage 4 — the durable whole-batch append (ack barrier: poison
        clears and callers may ack only after this commits), metrics,
        attribution, watchdog."""
        with tracing.stage(w.marks, "engine.log", mark="log") as sp:
            ts = self.deli.clock()
            R, O = w.R, w.O
            rows, kind, nacked = w.rows, w.kind, w.nacked
            out_seq, out_min = w.out_seq, w.out_min
            text, texts, tidx, props = w.text, w.texts, w.tidx, w.props
            rowidx = np.repeat(np.arange(R, dtype=np.int32), O)
            ids = [self._row_doc_id[r] for r in rows]
            flat_client = w.flat_client
            ref_clamped = self._clamped_ref(w.flat_ref_seq, out_seq)
            flat_tidx = None if tidx is None else np.ascontiguousarray(
                np.asarray(tidx, np.int32).reshape(-1))
            if not nacked.any():
                # hot path: the whole batch is ONE ColumnarOps record (the
                # Kafka-batch analog) — no partition sort, no per-field
                # gathers; a doc's columnar history is reassembled seq-ordered
                # at read (_doc_log_messages scans all partitions — recovery
                # only). Copies detach the log from caller-owned planes.
                record = ColumnarOps(
                    ids, rowidx, flat_client.copy(),
                    w.flat_client_seq.copy(), ref_clamped, out_seq, out_min,
                    kind.reshape(-1).copy(), w.a0.reshape(-1).copy(),
                    w.a1.reshape(-1).copy(), text=text, timestamp=ts,
                    texts=texts, props=props,
                    tidx=None if flat_tidx is None else flat_tidx.copy())
                with tracing.stage(w.marks, "log.append") as sp_app:
                    self._append_columnar(record)
            else:
                # nacked slots present (rare): group the survivors by doc
                # partition with ONE stable sort, one record per partition
                parts = np.repeat(self._row_part[rows], O)
                ok_idx = np.flatnonzero(~nacked)
                order = ok_idx[np.argsort(parts[ok_idx], kind="stable")]
                p_sorted = parts[order]
                bounds = np.searchsorted(
                    p_sorted, np.arange(self.log.n_partitions + 1))
                fields = (flat_client, w.flat_client_seq, ref_clamped,
                          out_seq, out_min, kind.reshape(-1),
                          w.a0.reshape(-1), w.a1.reshape(-1))
                gathered = tuple(f[order] for f in fields)
                row_sorted = rowidx[order]
                tidx_flat = None if flat_tidx is None else flat_tidx[order]
                with tracing.stage(w.marks, "log.append") as sp_app:
                    for p in range(self.log.n_partitions):
                        lo, hi = bounds[p], bounds[p + 1]
                        if lo == hi:
                            continue
                        sl = slice(lo, hi)
                        self._fenced_append(int(p), ColumnarOps(
                            ids, row_sorted[sl], *(g[sl] for g in gathered),
                            text=text, timestamp=ts, texts=texts, props=props,
                            tidx=None if tidx_flat is None else tidx_flat[sl]))
                    # sequence → merge → log completed
                    self._ingest_mark_logged()
            # per-stage host wall (the throughput breakdown): C++ sequencing,
            # plane prep + wire packing, async device dispatch, log append —
            # device time itself is covered by the caller's end sync. In
            # pipelined mode ``ingest_prepack_ms`` is the pack work that ran
            # OFF the critical path (pack worker, overlapped with the
            # previous wave's dispatch).
            log_ms = (sp_app.t1 - sp.t0) * 1000
            st = w.apply_stats
            self.metrics.observe("ingest_seq_ms", w.seq_ms)
            self.metrics.observe("ingest_pack_ms", st.get("pack_ms", 0.0))
            self.metrics.observe("ingest_dispatch_ms",
                                 st.get("dispatch_ms", 0.0))
            self.metrics.observe("ingest_prep_ms", w.prep_ms)
            self.metrics.observe("ingest_log_ms", log_ms)
            prepack_ms = st.get("prepack_ms", 0.0)
            if prepack_ms:
                self.metrics.observe("ingest_prepack_ms", prepack_ms)

            if self._attributors is not None:
                ok = ~nacked
                for doc_local, s, c in zip(rowidx[ok], out_seq[ok],
                                           flat_client[ok]):
                    self._attributor_of(ids[int(doc_local)]).record_raw(
                        int(s), int(c), ts)
            self.metrics.inc("flushes")
            self.metrics.inc("ops_flushed", w.n_ok)
            busy_ms = (w.seq_ms + w.prep_ms + st.get("pack_ms", 0.0)
                       + prepack_ms + st.get("dispatch_ms", 0.0) + log_ms)
            # pipelined waves sit in stage queues between workers; wall time
            # since submission would count that waiting as a stall, so the
            # watchdog judges the wave's BUSY time instead
            elapsed_ms = busy_ms if w.pipelined \
                else (time.perf_counter() - w.marks["pack0"]) * 1000
            self.metrics.observe("flush_ms", elapsed_ms)
            self._watch_apply(elapsed_ms, "ingest_planes", w.n_ok)
            # overflow harvest decision rides AFTER the durable append —
            # recovery replays the LOG, so it must see this wave's record.
            # Pipelined: defer to the executor's drain (other waves may still
            # be sequencing on another thread).
            if w.ov_prev is not None and np.asarray(w.ov_prev).any():
                if w.pipelined:
                    self._ov_recover_due = True
                else:
                    self.recover_overflowed()
            n_dup = int(getattr(w, "dup_acked", 0) or 0)
            # read plane (ISSUE 20): the columnar window is durable — pump
            # one encoded observer window at ingest pace (the fast path
            # never passes through flush()/_after_flush)
            plane = self._read_plane
            if plane is not None and w.n_ok:
                plane.pump()
            result = {"seq": w.seq_rs, "nacked": int(nacked.sum()) - n_dup,
                      "dup_acked": n_dup, "marks": w.marks}
        return result

    # ----------------------------------------------------------- device side

    def _flush_impl(self) -> int:
        """Merge the queued window on device in one batched apply per tier."""
        n = self._queued()
        if self._queue:
            self.store.apply_messages(self._queue)
            self._queue.clear()
        if self._mega_queue:
            self.mega_store.apply_messages(self._mega_queue)
            self._mega_queue.clear()
        if self._grad_queue:
            per_doc: Dict[str, list] = {}
            for doc_id, msg in self._grad_queue:
                per_doc.setdefault(doc_id, []).append((0, msg))
            for doc_id, msgs in per_doc.items():
                self._graduated[doc_id].apply_messages(msgs)
            self._grad_queue.clear()
        return n

    def compact(self) -> None:
        """Zamboni at each doc's MSN (collaboration-window floor); checks
        overflow flags and runs recovery on the same cadence."""
        self.store.compact(self._row_floor.copy())
        self.metrics.inc("compaction_floors_from_rows")
        if self.mega_store is not None and self._mega_rows:
            ms = np.zeros((self.mega_store.n_docs,), np.int32)
            for doc_id, row in self._mega_rows.items():
                ms[row] = self._min_seq.get(doc_id, 0)
            self.mega_store.compact(ms)
        for doc_id, store in self._graduated.items():
            store.compact(self._min_seq.get(doc_id, 0))
        super().compact()
        if self.auto_recover:
            self.recover_overflowed()

    # ----------------------------------------------------------------- reads

    def _store_of(self, doc_id: str):
        if doc_id in self._graduated:
            return self._graduated[doc_id], 0
        if doc_id in self._mega_rows:
            return self.mega_store, self._mega_rows[doc_id]
        return self.store, self.doc_row(doc_id)

    def read_text(self, doc_id: str) -> str:
        self.flush()
        store, row = self._store_of(doc_id)
        return store.read_text(row)

    def get_properties(self, doc_id: str, pos: int) -> dict:
        self.flush()
        store, row = self._store_of(doc_id)
        return store.get_properties(row, pos)

    def attribution_at(self, doc_id: str, pos: int):
        """Who wrote the character at ``pos`` (and when): the device seq
        plane resolves to the engine attributor (enable_attribution)."""
        if self._attributors is None:
            raise RuntimeError("call enable_attribution() first")
        self.flush()
        store, row = self._store_of(doc_id)
        return self._attributor_of(doc_id).get(store.seq_at(row, pos))

    def overflowed_docs(self) -> List[str]:
        """Docs whose device capacity overflowed (ops dropped): these must
        be drained through the oracle and re-uploaded (the escape hatch of
        SURVEY.md §7 risk (b)); ``recover_overflowed`` does exactly that."""
        flags = self.store.overflowed()
        out = [d for d, row in self._doc_rows.items() if flags[row]]
        if self.mega_store is not None and self._mega_rows:
            mflags = self.mega_store.overflowed()
            out += [d for d, row in self._mega_rows.items()
                    if mflags[row].any()]
        return out

    # ----------------------------------------------------- overflow recovery

    def recover_overflowed(self, grow_limit: int = 1 << 20) -> Dict[str, str]:
        """The overflow escape hatch, end to end (SURVEY.md §7 risk (b)):
        for every doc whose device row overflowed (the kernel dropped its
        later ops, sticky flag set), drain the doc's FULL op history from
        the durable log through a fresh rebuild at doubled capacity (the
        same apply kernels — recovery stays one primitive), compact at the
        doc's window floor, then either re-upload into the original row
        (fits again) or graduate the doc to its own right-sized store
        (terminal tier). Zero acked ops are lost: the log has every
        sequenced op. Returns {doc_id: "reuploaded" | "graduated"}."""
        self.flush()  # logged-but-queued ops must not double-apply: the
        # rebuild replays the FULL log, so the queues must be empty
        report: Dict[str, str] = {}
        flags = self.store.overflowed()
        flat = [d for d, r in self._doc_rows.items() if flags[r]]
        if flat:
            # BATCHED rebuild: a correlated mass overflow (identical
            # workloads hitting capacity together) rebuilds every doc in
            # ONE multi-doc temp store per capacity doubling — 2 device
            # reads per doubling instead of 2 per doc (each one a sync
            # that drains the dispatch pipeline)
            report.update(self._recover_flat_batch(flat, grow_limit))
        if self.mega_store is not None and self._mega_rows:
            mflags = self.mega_store.overflowed()
            for doc_id in [d for d, r in self._mega_rows.items()
                           if mflags[r].any()]:
                report[doc_id] = self._recover_mega(doc_id, grow_limit)
        # the terminal tier can overflow too (doc kept growing past its
        # rebuild-time capacity): rebuild in place at doubled capacity
        for doc_id, store in list(self._graduated.items()):
            if store.overflowed().any():
                tmp = self._rebuild_doc(doc_id, store.capacity, grow_limit,
                                        store.n_props)
                ivs = store.intervals(0) if store._intervals[0] else {}
                self._graduated[doc_id] = tmp
                self._readd_intervals(tmp, 0, ivs)
                report[doc_id] = "regrown"
        if report:
            self.metrics.inc("overflow_recoveries", len(report))
        return report

    def _doc_log_messages(self, doc_id: str):
        """Every sequenced OP message for one doc, seq-ascending, from the
        durable log. Per-op records live in the doc's own partition;
        whole-batch ColumnarOps records round-robin across partitions, so
        ALL partitions are scanned for them (recovery-only path) and the
        final seq sort restores the doc's total order."""
        p_own = partition_of(doc_id, self.log.n_partitions)
        msgs = []
        for p in range(self.log.n_partitions):
            for rec in self.log.read(p):
                if isinstance(rec, ColumnarOps):
                    msgs.extend(rec.expand(only_doc=doc_id))
                elif p == p_own and rec.doc_id == doc_id \
                        and rec.type == MessageType.OP:
                    msgs.append(rec)
        msgs.sort(key=lambda m: m.seq)
        return msgs

    def _rebuild_doc(self, doc_id: str, start_capacity: int,
                     grow_limit: int,
                     n_props: Optional[int] = None) -> TensorStringStore:
        """Replay a doc's full log history into a fresh single-doc store,
        doubling capacity until it fits, compacted at the window floor.
        ``n_props`` must be the OWNING tier's plane count (tiers differ)."""
        msgs = self._doc_log_messages(doc_id)
        cap = max(start_capacity, 128)
        props = n_props if n_props is not None else self.store.n_props
        while True:
            cap *= 2
            if cap > grow_limit:
                raise MemoryError(
                    f"{doc_id}: rebuild exceeds grow limit {grow_limit}")
            tmp = TensorStringStore(1, cap, props)
            tmp.apply_messages((0, m) for m in msgs)
            if not tmp.overflowed().any():
                break
        tmp.compact(self._min_seq.get(doc_id, 0))
        return tmp

    def _docs_log_messages(self, doc_ids: List[str]
                           ) -> Dict[str, list]:
        """Per-doc seq-ascending OP messages for MANY docs in ONE pass
        over the durable log (per-doc scans would decode every columnar
        record K times in the mass-overflow case)."""
        want = set(doc_ids)
        buckets: Dict[str, list] = {d: [] for d in doc_ids}
        for p in range(self.log.n_partitions):
            for rec in self.log.read(p):
                if isinstance(rec, ColumnarOps):
                    hits = want.intersection(rec.doc_ids)
                    if not hits:
                        continue
                    if len(hits) == 1:
                        d = next(iter(hits))
                        buckets[d].extend(rec.expand(only_doc=d))
                    else:
                        for m in rec.expand():
                            if m.doc_id in want:
                                buckets[m.doc_id].append(m)
                elif rec.doc_id in want and rec.type == MessageType.OP:
                    buckets[rec.doc_id].append(rec)
        for d in buckets:
            buckets[d].sort(key=lambda m: m.seq)
        return buckets

    def _recover_flat_batch(self, doc_ids: List[str],
                            grow_limit: int) -> Dict[str, str]:
        """Rebuild every overflowed flat-tier doc together: one K-doc temp
        store per capacity doubling, one batched apply, one compact, two
        device reads. Docs that fit re-upload into their rows; docs still
        too big graduate to their own right-sized stores."""
        report: Dict[str, str] = {}
        msgs = self._docs_log_messages(doc_ids)
        pending = list(doc_ids)
        cap = max(self.store.capacity, 128)
        while pending:
            cap *= 2
            if cap > grow_limit:
                raise MemoryError(
                    f"{pending[0]}: rebuild exceeds grow limit "
                    f"{grow_limit}")
            tmp = TensorStringStore(len(pending), cap, self.store.n_props)
            tmp.apply_messages([(i, m) for i, d in enumerate(pending)
                                for m in msgs[d]])
            tmp.compact(np.fromiter(
                (self._min_seq.get(d, 0) for d in pending), np.int32,
                count=len(pending)))
            ov = tmp.overflowed()
            counts = np.asarray(tmp.state.count)
            nxt = []
            for i, d in enumerate(pending):
                if ov[i]:
                    nxt.append(d)  # even doubled didn't fit: grow again
                    continue
                row = self._doc_rows[d]
                ivs = self.store.intervals(row) \
                    if self.store._intervals[row] else {}
                if int(counts[i]) <= self.store.capacity:
                    self.store.adopt_doc(row, tmp, src_row=i)
                    self._readd_intervals(self.store, row, ivs)
                    self._dirty_outside_ops.add(d)
                    report[d] = "reuploaded"
                else:
                    single = TensorStringStore(1, cap, self.store.n_props)
                    single.adopt_doc(0, tmp, src_row=i)
                    self.store._intervals[row] = {}
                    self.store.clear_doc(row)
                    self._graduated[d] = single
                    self._readd_intervals(single, 0, ivs)
                    self._release_flat_row(d)
                    report[d] = "graduated"
            pending = nxt
        return report

    def _release_flat_row(self, doc_id: str) -> None:
        """Return a graduated doc's flat row to the allocator (and clear
        the columnar caches so a reused row can't hit a stale handle)."""
        row = self._doc_rows.pop(doc_id)
        self._dedup.release_row(row)
        self._free_rows.append(row)
        self._row_doc_id[row] = None
        self._row_handle[row] = -1
        self._row_floor[row] = 0

    @staticmethod
    def _readd_intervals(store, row: int, ivs: dict) -> None:
        vis = store.visible_length(row)
        for iid, (start, end, props) in ivs.items():
            clamp = lambda p: max(0, min(int(p), max(vis - 1, 0)))
            store._intervals[row][iid] = (
                store._anchor_at(row, clamp(start)),
                store._anchor_at(row, clamp(end)), dict(props))
        if ivs:
            store._seed_tombs(row)

    def _recover_mega(self, doc_id: str, grow_limit: int) -> str:
        row = self._mega_rows[doc_id]
        tmp = self._rebuild_doc(
            doc_id, self.mega_store.capacity_per_shard, grow_limit,
            self.mega_store.n_props)
        n = int(np.asarray(tmp.state.count[0]))
        mega_cap = self.mega_store.capacity_per_shard * \
            self.mega_store.mesh.devices.size
        if n <= mega_cap:
            self.mega_store = self.mega_store.adopt_doc(row, tmp)
            return "reuploaded"
        # too big even for the sharded tier: graduate; adopting an empty
        # rebuild clears the mega row (and its sticky overflow flag), and
        # the row returns to the mega allocator
        self._graduated[doc_id] = tmp
        self.mega_store = self.mega_store.adopt_doc(
            row, TensorStringStore(1, 128, self.mega_store.n_props))
        del self._mega_rows[doc_id]
        self._free_mega_rows.append(row)
        return "graduated"

    # ----------------------------------------------------- summary / recovery

    def summarize(self, incremental: bool = False) -> dict:
        """Flush + compact, then capture the recovery summary: store
        snapshot, sequencer checkpoint, per-partition log offsets, doc map.

        ``incremental=True`` (after at least one full summary this
        session) captures a DELTA instead: only rows whose document
        sequenced an op since the last summary — detected host-side from
        the sequencer, no device read — plus rows whose doc→row mapping
        changed (graduations, row reuse), plus append-only interner
        deltas. Clean rows are carried by REFERENCE to the previous
        summary (``base``) — the handle-reuse summary of SURVEY.md §2.16.
        A mostly-idle store summarizes in O(changed) bytes."""
        self.flush()
        self.compact()
        prev = self._summ_bookkeeping
        if self._incremental_ok(incremental):
            dirty_rows, cur_seqs = self._dirty_rows_since(prev)
            summary = self._base_summary()
            self._mark_delta(summary, prev, cur_seqs)
            summary["store_delta"] = self.store.snapshot_rows(
                sorted(dirty_rows), prev["payloads_len"],
                prev["prop_values_len"])
            # the small/rare tiers snapshot in full (mega stores shard
            # few docs; graduated stores are single-doc)
            summary["mega_store"] = self.mega_store.snapshot() \
                if self.mega_store is not None else None
            summary["mega_rows"] = dict(self._mega_rows)
            summary["graduated"] = {d: s.snapshot()
                                    for d, s in self._graduated.items()}
            self._chain_depth += 1
        else:
            summary = self._base_summary()
            summary["kind"] = "full"
            self._chain_depth = 0
            summary["store"] = self.store.snapshot()
            summary["mega_store"] = self.mega_store.snapshot() \
                if self.mega_store is not None else None
            summary["mega_rows"] = dict(self._mega_rows)
            summary["graduated"] = {d: s.snapshot()
                                    for d, s in self._graduated.items()}
            cur_seqs = {d: self.deli.doc_seq(d) for d in self._doc_rows}
        self._note_summary(summary, cur_seqs,
                           payloads_len=len(self.store._payloads),
                           prop_values_len=len(self.store._prop_values))
        return summary

    @classmethod
    def load(cls, summary: dict, log: PartitionedLog, mesh=None,
             **kwargs) -> "StringServingEngine":
        """Resume from a summary + the durable log: restore the device
        state, restore the sequencer, then replay the log tail through the
        same apply kernels — the single recovery primitive. ``mesh``
        re-shards the restored planes (recovery onto a fresh mesh).
        Incremental summaries resolve their base chain: the newest full
        summary restores, then each delta's dirty rows overwrite."""
        full, deltas = cls.resolve_summary_chain(summary)
        store = TensorStringStore.restore(full["store"], mesh=mesh)
        for delta in deltas:
            store.apply_row_snapshot(delta["store_delta"])
        mega = None
        if summary.get("mega_store") is not None:
            from ..ops.megadoc_store import MegaDocStringStore
            mega = MegaDocStringStore.restore(summary["mega_store"])
        engine = cls(store.n_docs, store.capacity, store.n_props,
                     log=log, store=store, mega_store=mega, **kwargs)
        engine._restore_base(summary)
        engine._mega_rows = dict(summary.get("mega_rows", {}))
        engine._graduated = {
            d: TensorStringStore.restore(s)
            for d, s in summary.get("graduated", {}).items()}

        def mark_mega_hook(msg):
            if msg.type == MessageType.PROPOSAL and \
                    isinstance(msg.contents, dict) and \
                    msg.contents.get("markMega"):
                if msg.doc_id not in engine._mega_rows:
                    engine._register_mega(msg.doc_id)  # no re-log
                return True  # control record: not for the stores
            return False

        engine._replay_tail(summary, control_hook=mark_mega_hook)
        engine._mega_queue.sort(key=lambda dm: dm[1].seq)
        engine._grad_queue.sort(key=lambda dm: dm[1].seq)
        engine.flush()
        return engine


class MapServingEngine(ServingEngineBase):
    """Serving engine for SharedMap documents: same Deli + durable log +
    batch-window pipeline as the string engine, over the batched LWW map
    kernel (BASELINE config #2 as a service). Ops are the SharedMap wire
    dicts: {"op": "set"|"delete"|"clear", "key", "value"}."""

    def __init__(self, n_docs: int, n_keys: int = 64,
                 batch_window: int = 64, n_partitions: int = 8,
                 log: Optional[PartitionedLog] = None,
                 store: Optional[TensorMapStore] = None,
                 sequencer: str = "python", mesh=None):
        """``mesh``: a 1-D ``docs`` device mesh shards the map planes by
        doc row; the columnar merge runs as a collective-free shard_map
        (same scale-out shape as the string engine's)."""
        super().__init__(batch_window, n_partitions, log=log,
                         sequencer=sequencer)
        if store is not None and mesh is not None \
                and getattr(store, "mesh", None) is not mesh:
            raise ValueError("mesh given with a store not sharded over it")
        self.store = store if store is not None \
            else TensorMapStore(n_docs, n_keys, mesh=mesh)
        self.mesh = getattr(self.store, "mesh", mesh)
        self.n_docs = n_docs
        self._init_row_caches(n_docs)
        # per-(rows, key-vocabulary) key-slot lut cache: steady-state
        # ingest with a stable vocabulary pays zero interning dict hits
        self._lut_cache: Optional[tuple] = None

    def doc_row(self, doc_id: str) -> int:
        row = super().doc_row(doc_id)
        self._note_row(doc_id, row)
        return row

    # ------------------------------------------------------- columnar ingest

    def _key_lut(self, rows: np.ndarray, keys: List[str]) -> np.ndarray:
        """(R, K) per-row key→slot table for this batch's key vocabulary
        (mints slots — KeyError on capacity BEFORE anything is sequenced)."""
        ck = (tuple(keys), rows.tobytes())
        if self._lut_cache is not None and self._lut_cache[0] == ck:
            return self._lut_cache[1]
        lut = np.empty((len(rows), len(keys)), np.int32)
        for i, r in enumerate(rows):
            for j, k in enumerate(keys):
                lut[i, j] = self.store.key_slot(int(r), k)
        self._lut_cache = (ck, lut)
        return lut

    def ingest_planes(self, rows, client, client_seq, ref_seq, kind,
                      kidx, keys: List[str], values: Optional[list] = None,
                      vidx=None) -> dict:
        """High-throughput map ingest: a dense (R, O) columnar batch of
        RAW set/delete/clear ops — one native sequencing call, ONE
        whole-batch durable-log record (family "map"), one fused
        unpack+apply device dispatch (~4-7 B/op on the wire).

        kidx: (R, O) indices into ``keys`` (ignored at clear slots).
        values/vidx: value table + (R, O) indices for set slots.
        Same contract as the string engine's ``ingest_planes``: nacked
        slots are skipped everywhere; returns {"seq", "nacked"}."""
        self._check_poisoned()
        raw = getattr(self.deli, "raw", None)
        if raw is None:
            raise RuntimeError("columnar ingest requires sequencer='native'")
        self.flush()
        rows = np.ascontiguousarray(rows, np.int32)
        R, O = kind.shape
        if len(rows) != R or len(np.unique(rows)) != R:
            raise ValueError("rows must be exactly one UNIQUE row per "
                             "plane row")
        kind = np.asarray(kind, np.int32)
        allowed = [int(OpKind.MAP_SET), int(OpKind.MAP_DELETE),
                   int(OpKind.MAP_CLEAR)]
        if not np.isin(kind, allowed).all():
            raise ValueError("columnar map planes must be dense "
                             "set/delete/clear")
        if self.store.n_keys > 256:
            raise ValueError("columnar map ingest packs key slots as u8 "
                             "(store n_keys must be <= 256)")
        kidx = np.asarray(kidx, np.int32)
        keyed = kind != int(OpKind.MAP_CLEAR)
        if keyed.any() and (int(kidx[keyed].min()) < 0
                            or int(kidx[keyed].max()) >= len(keys)):
            raise ValueError("kidx beyond the keys table")
        sets = kind == int(OpKind.MAP_SET)
        if sets.any():
            if values is None or vidx is None:
                raise ValueError("set slots require values + vidx")
            vidx = np.asarray(vidx, np.int32)
            if int(vidx[sets].min()) < 0 or \
                    int(vidx[sets].max()) >= len(values):
                raise ValueError("vidx beyond the values table")
        # mint key slots + value handles BEFORE sequencing (capacity
        # failures must reject the batch with nothing acked)
        lut = self._key_lut(rows, keys)
        kidx_safe = np.where(keyed, kidx, 0)  # ignored slots may carry
        a0 = np.where(keyed,                  # garbage per the contract
                      lut[np.arange(R)[:, None], kidx_safe], 0)
        if sets.any():
            handles_tab = np.fromiter(
                (self.store.value_handle(v) for v in values), np.int32,
                count=len(values))
            a1 = np.where(sets, handles_tab[np.where(sets, vidx, 0)], 0)
        else:
            a1 = np.zeros((R, O), np.int32)

        self._fill_row_handles(rows, raw)
        t0 = time.perf_counter()
        flat = lambda p: np.ascontiguousarray(np.asarray(p, np.int32)
                                              .reshape(-1))
        handles = np.repeat(self._row_handle[rows], O)
        out_seq, out_min, nacked, n_ok = self._sequence_columnar(
            raw, handles, flat(client), flat(client_seq), flat(ref_seq),
            "columnar map batch")
        valid_rs = (~nacked).reshape(R, O)
        kind_eff = np.where(valid_rs, kind, int(OpKind.NOOP))
        seq_rs = out_seq.reshape(R, O)
        n_valid = valid_rs.sum(axis=1)
        seq_base = (np.max(np.where(valid_rs, seq_rs, 0), axis=1)
                    - n_valid).astype(np.int32)

        # device merge (async dispatch): byte-packed single buffer
        def seg_u8(arr):
            b = np.ascontiguousarray(arr, np.uint8).reshape(-1)
            if len(b) % 4:
                b = np.concatenate([b, np.zeros((-len(b)) % 4, np.uint8)])
            return b.view("<i4")

        def seg_u16(arr):
            b = np.ascontiguousarray(arr, "<u2").reshape(-1)
            if len(b) % 2:
                b = np.concatenate([b, np.zeros(1, "<u2")])
            return b.view("<i4")

        wide_vals = bool(int(a1.max(initial=0)) >= (1 << 16))
        buf = np.concatenate([
            seg_u8(kind_eff), seg_u8(a0),
            (np.ascontiguousarray(a1, "<i4").reshape(-1) if wide_vals
             else seg_u16(a1)),
            seq_base.astype("<i4"),
            rows.astype("<i4"),
        ])
        scatter = not (R == self.n_docs
                       and np.array_equal(rows, np.arange(R)))
        fault_point(SITE_APPLY_STALL, what="ingest_planes")
        import jax.numpy as jnp
        if getattr(self.store, "mesh", None) is not None:
            from ..ops.map_kernel import map_columnar_unpack_jit
            from ..parallel.sharded import sharded_map_merge
            planes = map_columnar_unpack_jit(
                jnp.asarray(buf), R=R, O=O, n_docs=self.n_docs,
                scatter_rows=scatter, wide_vals=wide_vals)
            self.store.state = sharded_map_merge(self.store.mesh)(
                self.store.state, planes)
        else:
            from ..ops.map_kernel import map_columnar_apply_jit
            self.store.state = map_columnar_apply_jit(
                self.store.state, jnp.asarray(buf), R=R, O=O,
                n_docs=self.n_docs, scatter_rows=scatter,
                wide_vals=wide_vals)
        self._ensure_shard_collectors()
        self._note_shard_ops(rows, counts=n_valid)

        # whole-batch durable record (host work rides under the device
        # apply); nacked batches fall back to per-partition grouping is
        # unnecessary here: map records carry their tables per record
        ts = self.deli.clock()
        rowidx = np.repeat(np.arange(R, dtype=np.int32), O)
        ids = [self._row_doc_id[r] for r in rows]
        ref_clamped = self._clamped_ref(flat(ref_seq), out_seq)
        ok = ~nacked
        self._append_columnar(ColumnarOps(
            ids, rowidx[ok], flat(client)[ok], flat(client_seq)[ok],
            ref_clamped[ok], out_seq[ok], out_min[ok],
            kind.reshape(-1)[ok], flat(kidx)[ok],
            (flat(vidx) if vidx is not None
             else np.zeros(R * O, np.int32))[ok],
            text="", timestamp=ts, family="map", keys=list(keys),
            values=list(values) if values is not None else []))
        last_min = out_min.reshape(R, O)[:, -1]
        for i, r in enumerate(rows):
            self._min_seq[self._row_doc_id[r]] = int(last_min[i])
        self.metrics.inc("flushes")
        self.metrics.inc("ops_flushed", n_ok)
        elapsed_ms = (time.perf_counter() - t0) * 1000
        self.metrics.observe("flush_ms", elapsed_ms)
        tracing.TRACER.record_complete(
            "serving.ingest_planes", elapsed_ms, ops=int(n_ok),
            nacked=int(nacked.sum()))
        self._watch_apply(elapsed_ms, "ingest_planes", n_ok)
        return {"seq": seq_rs, "nacked": int(nacked.sum())}

    # ----------------------------------------------------------- device side

    _KINDS = {"set": OpKind.MAP_SET, "delete": OpKind.MAP_DELETE,
              "clear": OpKind.MAP_CLEAR}

    def _valid_op(self, contents: Any) -> bool:
        if not (isinstance(contents, dict)
                and contents.get("op") in self._KINDS
                and (contents["op"] == "clear" or
                     isinstance(contents.get("key"), str))):
            return False
        if contents["op"] == "set":
            try:  # the flush path JSON-interns values: reject unserializable
                json.dumps(contents.get("value"))
            except (TypeError, ValueError):
                return False
        return True

    def _admit(self, doc_id: str, contents: Any,
               client_id: int = -1) -> None:
        row = self.doc_row(doc_id)
        if contents["op"] != "clear":
            self.store.key_slot(row, contents["key"])  # reserve (KeyError
            # on key-capacity exhaustion → CAPACITY nack before logging)

    def _flush_impl(self) -> int:
        n = len(self._queue)
        if self._queue:
            self.store.apply_batch(
                (row, self._KINDS[m.contents["op"]],
                 m.contents.get("key"), m.contents.get("value"), m.seq)
                for row, m in self._queue)
            self._queue.clear()
        return n

    # ----------------------------------------------------------------- reads

    def read_doc(self, doc_id: str) -> dict:
        self.flush()
        return self.store.read_doc(self.doc_row(doc_id))

    def get(self, doc_id: str, key: str, default=None):
        return self.read_doc(doc_id).get(key, default)

    # ----------------------------------------------------- summary / recovery

    def summarize(self, incremental: bool = False) -> dict:
        """``incremental=True`` (after one full summary) captures a
        DELTA: only rows whose doc sequenced an op since the base —
        detected host-side from the sequencer, no device read — plus
        rows whose mapping changed, plus the append-only value-interner
        delta; clean rows ride by reference to the base summary
        (SURVEY.md §2.16)."""
        self.flush()
        prev = self._summ_bookkeeping
        if self._incremental_ok(incremental):
            dirty_rows, cur_seqs = self._dirty_rows_since(prev)
            summary = self._base_summary()
            self._mark_delta(summary, prev, cur_seqs)
            summary["store_delta"] = self.store.snapshot_rows(
                sorted(dirty_rows), prev["values_len"])
            self._chain_depth += 1
        else:
            summary = self._base_summary()
            summary["kind"] = "full"
            self._chain_depth = 0
            summary["store"] = self.store.snapshot()
            cur_seqs = {d: self.deli.doc_seq(d) for d in self._doc_rows}
        self._note_summary(summary, cur_seqs,
                           values_len=len(self.store._interner))
        return summary

    @classmethod
    def load(cls, summary: dict, log: PartitionedLog, mesh=None,
             **kwargs) -> "MapServingEngine":
        """Summary + tail replay through the same apply path (the single
        recovery primitive, as in the string engine). ``mesh`` re-shards
        the restored planes. Incremental summaries resolve their base
        chain: the newest full summary restores, then each delta's dirty
        rows overwrite."""
        full, deltas = cls.resolve_summary_chain(summary)
        store = TensorMapStore.restore(full["store"], mesh=mesh)
        for delta in deltas:
            store.apply_row_snapshot(delta["store_delta"])
        engine = cls(store.n_docs, store.n_keys, log=log, store=store,
                     **kwargs)
        engine._restore_base(summary)
        engine._replay_tail(summary)
        engine.flush()
        return engine


class MatrixServingEngine(ServingEngineBase):
    """Serving engine for SharedMatrix documents.

    Division of labor (SURVEY.md §2.4), fully on device as of r4: the
    permutation state (row/col axes) lives in the batched merge-tree
    kernel (``TensorAxisStore``, 2 axis rows per doc), and position→key
    resolution at each op's (ref_seq, client) perspective happens INSIDE
    the same device scan that applies the axis mutations (the
    ``AXIS_RESOLVE`` op) — one dispatch + ONE device→host read per
    flush, instead of a host MergeTree walk per op. The cell-write
    volume merges in the sort-based device cell table, shared across
    documents by interning (doc, rowKey, colKey) identities.

    FWW fidelity: the DDS's first-writer-wins rejects a write only when
    the writer had NOT seen the current value and is not its author —
    unlike the kernel's batch-level "first ever wins" flag. The engine
    tracks per-cell (seq, writer) host-side and filters FWW losers on
    the RESOLVED key stream before the cell apply; the device always
    merges LWW, and the surviving stream's latest write is exactly the
    DDS's answer.
    """

    _MX = {"insRow", "insCol", "rmRow", "rmCol", "setCell", "policy"}

    #: latest-view perspective for reads (every acked op visible)
    _READ_REF = 1 << 30

    def __init__(self, n_docs: int, cell_capacity: int = 1 << 16,
                 batch_window: int = 64, n_partitions: int = 8,
                 log: Optional[PartitionedLog] = None,
                 store=None, axis_capacity: int = 256,
                 axis_store=None, sequencer: str = "python", mesh=None):
        """``mesh``: a 1-D ``docs`` device mesh shards BOTH matrix
        stores by doc block — the axis rows (2 per doc, adjacent) and
        the cell pool (``ShardedMatrixStore``: cells are doc-scoped, so
        each shard sort-merges its own docs' cells) — every apply a
        collective-free shard_map (SURVEY.md §2.14)."""
        from ..ops.axis_kernel import TensorAxisStore
        from ..ops.matrix_kernel import (
            ShardedMatrixStore, TensorMatrixStore)
        super().__init__(batch_window, n_partitions, log=log,
                         sequencer=sequencer)
        if mesh is not None:
            for s in (store, axis_store):
                if s is not None and getattr(s, "mesh", None) is not mesh:
                    raise ValueError(
                        "mesh given with a store not sharded over it")
        if store is not None:
            self.store = store
        elif mesh is not None:
            self.store = ShardedMatrixStore(cell_capacity, mesh, n_docs)
        else:
            self.store = TensorMatrixStore(cell_capacity)
        self.axis_store = axis_store if axis_store is not None \
            else TensorAxisStore(n_docs, axis_capacity, mesh=mesh)
        self.mesh = mesh
        self.n_docs = n_docs
        self._fww: Dict[int, bool] = {}
        # per-doc {cell: (seq, writer)} — the FWW visibility metadata
        self._cell_meta: Dict[int, Dict] = {}
        self._pending_setcells = 0  # queued setCells (capacity reservation)
        # deferred cell-ingest batches awaiting their resolve harvest
        # (the pipelining that removes the per-batch device round trip)
        self._pending_cells: List[dict] = []
        self._pending_cell_count = 0
        self._init_row_caches(n_docs)
        # conservative per-axis slot usage bound (each admitted axis op
        # adds at most 2 slots: an insert, or a remove's two splits);
        # re-based to the measured device counts at every compact()
        self._axis_used = np.zeros(2 * n_docs, np.int64)

    # structural bound on one axis op (an insert allocates count slots on
    # the axis — an unbounded count is a memory-exhaustion vector)
    MAX_AXIS_COUNT = 1 << 20

    def doc_row(self, doc_id: str) -> int:
        row = super().doc_row(doc_id)
        self._note_row(doc_id, row)
        return row

    def _valid_op(self, contents: Any) -> bool:
        """Full structural validation BEFORE sequencing/logging: every field
        the flush path touches must have the type/range it assumes — a
        logged op that raises in flush poisons the engine and its recovery
        replay (the invariant of ServingEngineBase.submit)."""
        if not (isinstance(contents, dict)
                and contents.get("mx") in self._MX):
            return False
        mx = contents["mx"]
        if mx in ("insRow", "insCol"):
            key = contents.get("opKey")
            return (self._is_nat(contents.get("pos"))
                    and self._is_nat(contents.get("count"), 1)
                    and contents["count"] <= self.MAX_AXIS_COUNT
                    and isinstance(key, (list, tuple)) and len(key) == 2
                    and all(self._is_nat(k, -(1 << 62)) for k in key)
                    and self._is_nat(contents.get("off", 0)))
        if mx in ("rmRow", "rmCol"):
            return (self._is_nat(contents.get("start"))
                    and self._is_nat(contents.get("count"), 1))
        if mx == "setCell":
            if not (self._is_nat(contents.get("row"))
                    and self._is_nat(contents.get("col"))):
                return False
            try:
                json.dumps(contents.get("value"))
                return True
            except (TypeError, ValueError):
                return False
        return True  # policy

    def _admit(self, doc_id: str, contents: Any,
               client_id: int = -1) -> None:
        super()._admit(doc_id, contents)
        row = self.doc_row(doc_id)
        if client_id >= 0 and contents["mx"] != "policy":
            # per-axis client capacity (MAX_CLIENTS): mint now so an op
            # that cannot be applied is CAPACITY-nacked, never acked
            self.axis_store.client(2 * row, client_id)
            self.axis_store.client(2 * row + 1, client_id)
        if contents["mx"] in ("insRow", "insCol", "rmRow", "rmCol"):
            # device axis rows are fixed-capacity: an acked axis op the
            # kernel must drop (sticky overflow) would silently corrupt
            # dims/cells — nack at admission when the conservative bound
            # says the axis may not fit it
            axis = 2 * row + (1 if contents["mx"].endswith("Col") else 0)
            if self._axis_used[axis] + 2 > self.axis_store.capacity:
                raise KeyError("axis slot capacity exhausted")
            self._axis_used[axis] += 2
        if contents["mx"] == "setCell":
            # conservative cell-capacity reservation: distinct interned
            # identities never shrink, and each queued setCell may mint one
            # more — past this bound the device table would silently drop
            # ACKED live cells at truncation, so nack before logging
            if not self.store.conservative_room(
                    self._pending_setcells + self._pending_cell_count):
                # deferred columnar batches' identities are not yet
                # interned — count them or an acked op could overflow
                # the table at harvest time
                raise KeyError("cell table capacity exhausted")
            self._pending_setcells += 1

    # ----------------------------------------------------------- device side

    @staticmethod
    def _mixed(op_key) -> int:
        """The oracle's run identity mix (models/shared_matrix.py:55)."""
        return op_key[0] * 1_000_003 + op_key[1]

    def _flush_impl(self) -> int:
        """Batch the window into per-axis-row op planes — axis mutations
        AND setCell position resolves in one scan — then FWW-filter the
        resolved key stream and merge the surviving cell writes. Exactly
        one device dispatch + one device→host read per flush. Deferred
        columnar cell batches harvest FIRST (per-doc seq order: they were
        sequenced before anything in this queue)."""
        self._harvest_cells()
        n = len(self._queue)
        if not n:
            return n
        self._queue.sort(key=lambda dm: dm[1].seq)
        per_axis: Dict[int, list] = {}
        setcells = []  # (row, msg, r_slot, c_slot)
        dropped = set()
        for row, msg in self._queue:
            op = msg.contents
            mx = op["mx"]
            self._fww.setdefault(row, False)
            self._cell_meta.setdefault(row, {})
            ar, ac = 2 * row, 2 * row + 1
            try:
                self.axis_store.client(ar, msg.client_id)
                self.axis_store.client(ac, msg.client_id)
            except KeyError:
                # per-axis client capacity (MAX_CLIENTS): drop the op —
                # the old host-axis path dropped per-op failures too
                dropped.add(id(msg))
                continue
            if mx in ("insRow", "insCol"):
                axis = ar if mx == "insRow" else ac
                run = self.axis_store.run_handle(
                    self._mixed(tuple(op["opKey"])), op.get("off", 0))
                per_axis.setdefault(axis, []).append(
                    (int(OpKind.STR_INSERT), op["pos"], op["count"], run,
                     msg.seq, self.axis_store.client(axis, msg.client_id),
                     msg.ref_seq))
            elif mx in ("rmRow", "rmCol"):
                axis = ar if mx == "rmRow" else ac
                per_axis.setdefault(axis, []).append(
                    (int(OpKind.STR_REMOVE), op["start"],
                     op["start"] + op["count"], 0, msg.seq,
                     self.axis_store.client(axis, msg.client_id),
                     msg.ref_seq))
            elif mx == "setCell":
                rl = per_axis.setdefault(ar, [])
                cl = per_axis.setdefault(ac, [])
                rl.append((int(OpKind.AXIS_RESOLVE), op["row"], 0, 0,
                           msg.seq,
                           self.axis_store.client(ar, msg.client_id),
                           msg.ref_seq))
                cl.append((int(OpKind.AXIS_RESOLVE), op["col"], 0, 0,
                           msg.seq,
                           self.axis_store.client(ac, msg.client_id),
                           msg.ref_seq))
                setcells.append((row, msg, len(rl) - 1, len(cl) - 1))
            # "policy" flips are applied in the seq-ordered filter below
        self._pending_setcells = 0

        rh = ro = None
        if per_axis:
            rh, ro = self._dispatch_axis(per_axis)

        # seq-ordered pass: policy flips + FWW filter on resolved keys
        records = []
        sc_i = 0
        for row, msg in self._queue:
            op = msg.contents
            if id(msg) in dropped:
                continue
            if op["mx"] == "policy":
                self._fww[row] = True
                continue
            if op["mx"] != "setCell":
                continue
            _, _, rs, cs = setcells[sc_i]
            sc_i += 1
            ar, ac = 2 * row, 2 * row + 1
            if rh[ar, rs] < 0 or rh[ac, cs] < 0:
                continue  # position out of range at the op's perspective:
                # protocol violation by the submitter; drop (oracle raises)
            rk = self.axis_store.run_key(int(rh[ar, rs]), int(ro[ar, rs]))
            ck = self.axis_store.run_key(int(rh[ac, cs]), int(ro[ac, cs]))
            meta = self._cell_meta[row]
            cell = (rk, ck)
            if self._fww[row]:
                seq, writer = meta.get(cell, (0, None))
                if seq > msg.ref_seq and writer != msg.client_id:
                    continue  # FWW: unseen concurrent write loses
            meta[cell] = (msg.seq, msg.client_id)
            records.append(((row, rk), ck, op["value"], msg.seq))
        self._queue.clear()
        if records:
            self.store.apply_batch(records)
        return n

    def ingest_cells(self, doc_ids: List[str], clients, client_seqs,
                     ref_seqs, rpos, cpos, values) -> dict:
        """High-throughput setCell ingest: N raw cell writes (op i targets
        ``doc_ids[i]`` at row/col positions ``rpos[i]``/``cpos[i]``) —
        ONE native sequencing call, one device axis-resolve scan (+ read),
        the FWW filter on the resolved key stream, one cell-table merge,
        and ONE whole-batch durable record. The volume op of BASELINE
        config #3 without per-op Python anywhere. Axis mutations
        (ins/rm row/col, policy) go through ``submit`` as before."""
        self._check_poisoned()
        raw = getattr(self.deli, "raw", None)
        if raw is None:
            raise RuntimeError("cell ingest requires sequencer='native'")
        n = len(doc_ids)
        if not (len(clients) == len(client_seqs) == len(ref_seqs)
                == len(rpos) == len(cpos) == len(values) == n):
            raise ValueError("batch fields must have equal length")
        try:  # the log and the value interner both JSON-encode values:
            json.dumps(values)  # reject unserializable BEFORE sequencing
        except (TypeError, ValueError) as e:
            raise ValueError(f"unserializable cell value: {e}") from None
        rpos = np.ascontiguousarray(rpos, np.int32)
        cpos = np.ascontiguousarray(cpos, np.int32)
        if len(rpos) and (int(rpos.min()) < 0 or int(cpos.min()) < 0):
            raise ValueError("negative cell position")
        if self._queue:   # per-op queue first: per-doc seq order holds
            self.flush()  # (also harvests any deferred cell batches)
        rows_l = list(map(self._doc_rows.get, doc_ids))
        if None in rows_l:  # unseen docs: the minting slow path
            rows = np.fromiter((self.doc_row(d) for d in doc_ids),
                               np.int32, count=n)
        else:
            rows = np.asarray(rows_l, np.int32)
        if not self.store.conservative_room(
                n + self._pending_cell_count):
            raise KeyError("cell table capacity exhausted")
        client = np.ascontiguousarray(clients, np.int32)
        # mint axis client slots BEFORE sequencing (capacity failure must
        # reject the batch) — one interner hit per UNIQUE (row, client)
        for p in np.unique(rows.astype(np.int64) * 4294967296
                           + (client.astype(np.int64)
                              & 0xFFFFFFFF)).tolist():
            row = p >> 32
            cid = int(np.uint32(p & 0xFFFFFFFF).astype(np.int32))
            self.axis_store.client(2 * row, cid)
            self.axis_store.client(2 * row + 1, cid)
        self._fill_row_handles(np.unique(rows), raw)
        t0 = time.perf_counter()
        cseq = np.ascontiguousarray(client_seqs, np.int32)
        ref = np.ascontiguousarray(ref_seqs, np.int32)
        out_seq, out_min, nacked, n_ok = self._sequence_columnar(
            raw, self._row_handle[rows], client, cseq, ref, "cell batch")
        ok = np.flatnonzero(~nacked)
        # the CLAMPED ref is what the log records and what recovery
        # replays through _flush_impl — the live resolve perspective and
        # FWW comparison must use the same value, or an inflated raw ref
        # (> doc.seq, accepted-and-clamped by the sequencer) makes live
        # and recovered state silently diverge
        ref_clamped = self._clamped_ref(ref, out_seq)

        # ONE mutation-free resolve dispatch for every accepted op,
        # packed vectorized: op i contributes entry 2j (its row axis)
        # and 2j+1 (its col axis) — per-axis slot order = op order
        pend = None
        if len(ok):
            from ..ops.tree_store import positions_in_doc
            rows_ok = rows[ok].astype(np.int64)
            ar, ac = 2 * rows_ok, 2 * rows_ok + 1
            k2 = len(ok) * 2
            axis_arr = np.empty(k2, np.int64)
            axis_arr[0::2] = ar
            axis_arr[1::2] = ac
            pos_in_axis, widest = positions_in_doc(axis_arr)
            o = 8
            while o < widest:
                o *= 2
            d2 = 2 * self.n_docs
            planes = {name: np.zeros((d2, o), np.int32)
                      for name in ("kind", "a0", "a1", "a2", "seq",
                                   "client", "ref_seq")}
            # client slot LUT: one interner hit per UNIQUE (axis, client)
            slot2 = np.empty(k2, np.int32)
            cl2 = np.empty(k2, np.int64)
            cl2[0::2] = client[ok]
            cl2[1::2] = client[ok]
            pairs = axis_arr * (1 << 32) + cl2
            uniq, inv = np.unique(pairs, return_inverse=True)
            lut = np.fromiter(
                (self.axis_store.client(int(p >> 32),
                                        int(p & 0xFFFFFFFF))
                 for p in uniq), np.int32, count=len(uniq))
            slot2 = lut[inv]
            a0 = np.empty(k2, np.int64)
            a0[0::2] = rpos[ok]
            a0[1::2] = cpos[ok]
            sq2 = np.repeat(out_seq[ok], 2)
            rf2 = np.repeat(ref_clamped[ok], 2)
            planes["kind"][axis_arr, pos_in_axis] = int(
                OpKind.AXIS_RESOLVE)
            planes["a0"][axis_arr, pos_in_axis] = a0
            planes["seq"][axis_arr, pos_in_axis] = sq2
            planes["client"][axis_arr, pos_in_axis] = slot2
            planes["ref_seq"][axis_arr, pos_in_axis] = rf2
            rh_dev, ro_dev = self.axis_store.resolve_async(planes)
            pend = {
                "rh": rh_dev, "ro": ro_dev,
                "axis": axis_arr, "pos": pos_in_axis,
                "rows": rows_ok, "client": client[ok].copy(),
                "ref": ref_clamped[ok].copy(),
                "seq": out_seq[ok].copy(),
                "values": [values[i] for i in ok],
            }

        # whole-batch durable record (family "ops") — appended before the
        # deferred merge harvest (the record holds RAW ops; recovery
        # replays them through the same resolve+filter path)
        ts = self.deli.clock()
        id_tab = sorted(set(doc_ids))
        id_of = {d: i for i, d in enumerate(id_tab)}
        contents_tab = [{"mx": "setCell", "row": int(rpos[i]),
                         "col": int(cpos[i]), "value": values[i]}
                        for i in ok]
        self._append_columnar(ColumnarOps(
            id_tab, np.fromiter((id_of[doc_ids[i]] for i in ok), np.int32,
                                count=len(ok)),
            client[ok], cseq[ok], ref_clamped[ok], out_seq[ok],
            out_min[ok], np.zeros(len(ok), np.int32),
            np.arange(len(ok), dtype=np.int32),
            np.zeros(len(ok), np.int32),
            text="", timestamp=ts, family="ops", values=contents_tab))
        okl = ok.tolist()
        self._min_seq.update(zip(map(doc_ids.__getitem__, okl),
                                 out_min[ok].tolist()))
        if pend is not None:
            self._pending_cells.append(pend)
            self._pending_cell_count += len(pend["rows"])
        # pipeline: harvest every batch but the newest (its resolve —
        # and the async host copy — overlap the caller's next batch)
        self._harvest_cells(keep_newest=True)
        self.metrics.inc("flushes")
        self.metrics.inc("ops_flushed", n_ok)
        self.metrics.observe("flush_ms", (time.perf_counter() - t0) * 1000)
        return {"seq": out_seq, "nacked": int(nacked.sum())}

    def _harvest_cells(self, keep_newest: bool = False) -> None:
        """Finish deferred cell-ingest batches in FIFO order: read the
        (by now usually landed) resolve results, run the FWW filter on
        the resolved keys, and dispatch the cell merge. ``keep_newest``
        leaves the most recent batch in flight — the pipelining that
        removes the blocking per-batch device round-trip (VERDICT r4
        weak #3)."""
        limit = len(self._pending_cells) - (1 if keep_newest else 0)
        for _ in range(max(limit, 0)):
            pend = self._pending_cells.pop(0)
            self._pending_cell_count -= len(pend["rows"])
            try:
                rh = np.asarray(pend["rh"])
                ro = np.asarray(pend["ro"])
            except Exception as e:   # device fault: state may lag log
                self._poisoned = f"cell resolve harvest failed: {e!r}"
                self._pending_cells.clear()
                raise
            axis, pos = pend["axis"], pend["pos"]
            rh2 = rh[axis, pos].astype(np.int64)
            ro2 = ro[axis, pos].astype(np.int64)
            hr, hc = rh2[0::2], rh2[1::2]
            vi = np.flatnonzero((hr >= 0) & (hc >= 0))
            if not len(vi):  # out of range at perspective: drop
                continue
            # resolved run keys: two gathers over the interned run table
            # (no per-op run_key() calls)
            mixed, base = self.axis_store.runs_arrays()
            hr_v, hc_v = hr[vi], hc[vi]
            rkm, rkb = mixed[hr_v], base[hr_v] + ro2[0::2][vi]
            ckm, ckb = mixed[hc_v], base[hc_v] + ro2[1::2][vi]
            rows_v = pend["rows"][vi]
            seq_v = pend["seq"][vi]
            cl_v = pend["client"][vi]
            keep = self._fww_filter_columnar(
                rows_v, rkm, rkb, ckm, ckb, seq_v, cl_v,
                pend["ref"][vi])
            kept = np.flatnonzero(keep)
            if not len(kept):
                continue
            # key tuples materialized ONCE, for survivors only — these
            # feed both the visibility metadata and the columnar merge
            rk_pairs = list(zip(rkm[kept].tolist(), rkb[kept].tolist()))
            ck_pairs = list(zip(ckm[kept].tolist(), ckb[kept].tolist()))
            rows_l = rows_v[kept].tolist()
            seq_l = seq_v[kept].tolist()
            cl_l = cl_v[kept].tolist()
            cells = list(zip(rk_pairs, ck_pairs))
            pairs = list(zip(seq_l, cl_l))
            # per-doc meta write-back in batch order (dict.update is
            # last-wins — exactly the retired loop's final state)
            ri = rows_v[kept]
            order = np.argsort(ri, kind="stable")
            ri_sorted = ri[order]
            urows = np.unique(ri_sorted)
            bounds = np.searchsorted(ri_sorted, urows)
            bounds = np.append(bounds, len(ri_sorted))
            for i, r in enumerate(urows.tolist()):
                idxs = order[bounds[i]:bounds[i + 1]].tolist()
                self._cell_meta[r].update(
                    zip(map(cells.__getitem__, idxs),
                        map(pairs.__getitem__, idxs)))
            vals = pend["values"]
            fi = vi[kept].tolist()
            self.store.apply_batch_columnar(
                list(zip(rows_l, rk_pairs)), ck_pairs,
                list(map(vals.__getitem__, fi)),
                np.asarray(seq_l, np.int32))

    def _fww_filter_columnar(self, rows, rkm, rkb, ckm, ckb, seqs,
                             clients, refs) -> np.ndarray:
        """First-writer-wins pass over one resolved, per-doc
        seq-ascending key stream — columnar, not op-by-op. Returns the
        bool keep mask; semantics are identical to the retired per-op
        loop: an op is dropped when the cell's current meta seq is newer
        than its ref AND held by a different writer, and each surviving
        op installs (seq, client) as the new meta (so within-batch writes
        chain). Cells written once in the batch (the volume case) are
        judged vectorized against the persistent meta; multiply-written
        cells replay the exact chain over just their own ops."""
        k = len(rows)
        urows, row_inv = np.unique(rows, return_inverse=True)
        fww_flags = np.empty(len(urows), bool)
        for i, r in enumerate(urows.tolist()):
            fww_flags[i] = self._fww.setdefault(r, False)
            self._cell_meta.setdefault(r, {})
        keep = np.ones(k, bool)
        fww_op = fww_flags[row_inv]
        if not fww_op.any():
            return keep
        ident = np.empty((k, 5), np.int64)
        ident[:, 0] = rows
        ident[:, 1] = rkm
        ident[:, 2] = rkb
        ident[:, 3] = ckm
        ident[:, 4] = ckb
        _, first, inv, counts = np.unique(
            np.ascontiguousarray(ident).view([("", np.int64)] * 5
                                             ).ravel(),
            return_index=True, return_inverse=True, return_counts=True)
        # persistent meta probed ONCE per unique fww cell
        nu = len(first)
        prev_seq = np.zeros(nu, np.int64)
        prev_writer = np.full(nu, -1, np.int64)  # absent → seq 0 passes
        ufww = np.flatnonzero(fww_op[first])
        for t in ufww.tolist():
            j0 = int(first[t])
            prev = self._cell_meta[int(rows[j0])].get(
                ((int(rkm[j0]), int(rkb[j0])),
                 (int(ckm[j0]), int(ckb[j0]))))
            if prev is not None:
                prev_seq[t], prev_writer[t] = prev
        sing = fww_op & (counts[inv] == 1)
        keep[sing] = ~((prev_seq[inv][sing] > refs[sing])
                       & (prev_writer[inv][sing] != clients[sing]))
        for t in np.intersect1d(ufww, np.flatnonzero(counts > 1)
                                ).tolist():
            cs, cw = int(prev_seq[t]), int(prev_writer[t])
            for j in np.flatnonzero(inv == t).tolist():
                if cs > int(refs[j]) and cw != int(clients[j]):
                    keep[j] = False
                else:
                    cs, cw = int(seqs[j]), int(clients[j])
        return keep

    def _dispatch_axis(self, per_axis: Dict[int, list]):
        """Dense (2·D, O) planes from per-axis op lists → one scan.
        Vectorized packing: one ``np.array`` per axis's record list + one
        slice write per plane, not a per-element Python triple loop."""
        widest = max(len(v) for v in per_axis.values())
        o = 8
        while o < widest:
            o *= 2
        D2 = 2 * self.n_docs
        names = ("kind", "a0", "a1", "a2", "seq", "client", "ref_seq")
        stack = np.zeros((7, D2, o), np.int32)
        stack[0] = int(OpKind.NOOP)
        for axis, recs in per_axis.items():
            arr = np.array(recs, np.int32)          # (k, 7)
            stack[:, axis, :len(recs)] = arr.T
        planes = {name: stack[i] for i, name in enumerate(names)}
        return self.axis_store.apply(planes)

    def overflowed(self) -> bool:
        """Sticky device overflow (cell table or an axis row): True means
        re-bucket with a larger table / axis capacity."""
        self._harvest_cells()
        return bool(self.store.overflowed()) or \
            bool(self.axis_store.overflowed().any())

    def compact(self) -> None:
        """Zamboni the device axes at each doc's window floor; re-base
        the conservative axis-slot bound to the measured counts."""
        self.flush()
        ms = np.zeros((2 * self.n_docs,), np.int32)
        for doc_id, row in self._doc_rows.items():
            ms[2 * row] = ms[2 * row + 1] = self._min_seq.get(doc_id, 0)
        self.axis_store.compact(ms)
        self._axis_used = np.asarray(self.axis_store.state.count,
                                     dtype=np.int64).copy()
        super().compact()

    # ----------------------------------------------------------------- reads

    def _resolve_read(self, queries):
        """Latest-view resolves [(axis_row, pos)] → [(run, off)] in one
        non-mutating device dispatch."""
        per_axis: Dict[int, list] = {}
        slots = []
        for axis, pos in queries:
            lst = per_axis.setdefault(axis, [])
            lst.append((int(OpKind.AXIS_RESOLVE), pos, 0, 0, 0, -1,
                        self._READ_REF))
            slots.append((axis, len(lst) - 1))
        rh, ro = self._dispatch_axis(per_axis)
        return [(int(rh[a, j]), int(ro[a, j])) for a, j in slots]

    def dims(self, doc_id: str):
        self.flush()
        row = self.doc_row(doc_id)
        lens = self.axis_store.visible_lengths()
        return int(lens[2 * row]), int(lens[2 * row + 1])

    def get_cell(self, doc_id: str, r: int, c: int):
        self.flush()
        row = self.doc_row(doc_id)
        (hr, orr), (hc, oc) = self._resolve_read(
            [(2 * row, r), (2 * row + 1, c)])
        if hr < 0 or hc < 0:
            raise IndexError(f"cell ({r}, {c}) out of range")
        return self.store.read_cell(
            ((row, self.axis_store.run_key(hr, orr)),
             self.axis_store.run_key(hc, oc)))

    def to_lists(self, doc_id: str):
        self.flush()
        row = self.doc_row(doc_id)
        nr, nc = self.dims(doc_id)
        res = self._resolve_read(
            [(2 * row, i) for i in range(nr)] +
            [(2 * row + 1, j) for j in range(nc)])
        rkeys = [self.axis_store.run_key(h, off) for h, off in res[:nr]]
        ckeys = [self.axis_store.run_key(h, off) for h, off in res[nr:]]
        cells = self.store.read_cells()
        return [[cells.get(((row, rk), ck)) for ck in ckeys]
                for rk in rkeys]

    # ----------------------------------------------------- summary / recovery

    def summarize(self, incremental: bool = False) -> dict:
        """``incremental=True`` (after one full summary) captures a
        DELTA: dirty docs' axis rows (fused gather) + their FWW/cell
        metadata, plus the cell pool — trimmed to LIVE cells and skipped
        entirely when no doc is dirty (the pool is key-sorted and
        globally re-merged every batch, so its delta granularity is the
        pool, bounded by live cells, not by history). Append-only
        identity/value tables ride as deltas; clean rows by reference to
        the base (SURVEY.md §2.16)."""
        self.flush()
        self.compact()
        prev = self._summ_bookkeeping
        if self._incremental_ok(incremental):
            dirty_rows, cur_seqs = self._dirty_rows_since(prev)
            dirty = sorted(dirty_rows)
            summary = self._base_summary()
            self._mark_delta(summary, prev, cur_seqs)
            summary["cells_delta"] = self.store.snapshot_delta(
                prev["mx_bases"]) if dirty else None
            axis_rows = [a for r in dirty for a in (2 * r, 2 * r + 1)]
            summary["axis_delta"] = self.axis_store.snapshot_rows(
                axis_rows, prev["runs_len"])
            # per-dirty-row host metadata overlays (None = clear)
            summary["fww_delta"] = {r: self._fww.get(r) for r in dirty}
            summary["cell_meta_delta"] = {
                r: (list(self._cell_meta[r].items())
                    if r in self._cell_meta else None) for r in dirty}
            summary["n_docs"] = self.n_docs
            self._chain_depth += 1
        else:
            summary = self._base_summary()
            summary["kind"] = "full"
            self._chain_depth = 0
            summary["store"] = self.store.snapshot()
            summary["axis_store"] = self.axis_store.snapshot()
            summary["fww"] = dict(self._fww)
            summary["cell_meta"] = {row: list(m.items())
                                    for row, m in self._cell_meta.items()}
            summary["n_docs"] = self.n_docs
            cur_seqs = {d: self.deli.doc_seq(d) for d in self._doc_rows}
        self._note_summary(summary, cur_seqs,
                           mx_bases=self.store.table_bases(),
                           runs_len=len(self.axis_store._runs))
        return summary

    @classmethod
    def load(cls, summary: dict, log: PartitionedLog, mesh=None,
             **kwargs) -> "MatrixServingEngine":
        from ..ops.axis_kernel import TensorAxisStore
        from ..ops.matrix_kernel import (
            ShardedMatrixStore, TensorMatrixStore, tuple_key)
        full, deltas = cls.resolve_summary_chain(summary)
        if "sharded_docs" in full["store"]:
            if mesh is None:
                raise ValueError("sharded matrix summary needs mesh=")
            store = ShardedMatrixStore.restore(full["store"], mesh)
        elif mesh is not None:
            raise ValueError("mesh= given for an unsharded matrix "
                             "summary; re-shard by rebuilding the store")
        else:
            store = TensorMatrixStore.restore(full["store"])
        axis = TensorAxisStore.restore(full["axis_store"], mesh=mesh)
        fww = dict(full["fww"])
        cell_meta = {
            row: {tuple_key(cell): tuple(sw) for cell, sw in items}
            for row, items in full["cell_meta"].items()}
        for delta in deltas:
            if delta["cells_delta"] is not None:
                store.apply_delta(delta["cells_delta"])
            axis.apply_row_snapshot(delta["axis_delta"])
            for r, v in delta["fww_delta"].items():
                r = int(r)
                if v is None:
                    fww.pop(r, None)
                else:
                    fww[r] = v
            for r, items in delta["cell_meta_delta"].items():
                r = int(r)
                if items is None:
                    cell_meta.pop(r, None)
                else:
                    cell_meta[r] = {tuple_key(cell): tuple(sw)
                                    for cell, sw in items}
        engine = cls(summary["n_docs"], log=log, store=store,
                     axis_store=axis, mesh=mesh, **kwargs)
        engine._restore_base(summary)
        engine._fww = fww
        engine._cell_meta = cell_meta
        # re-base the axis-slot admission bound from the restored planes
        # (a zeroed bound would admit ops the full axis cannot hold)
        engine._axis_used = np.asarray(axis.state.count,
                                       dtype=np.int64).copy()
        engine._replay_tail(summary)
        engine.flush()
        return engine


class _TreeIngestWave:
    """Per-wave carrier threaded through the tree engine's four
    columnar-ingest stages (``_ingest_prepare`` → ``_ingest_sequence``
    → ``_ingest_dispatch`` → ``_ingest_log``) — the tree analog of
    ``_IngestWave``; the same ``PipelinedIngestExecutor`` hands one of
    these from worker to worker, the serial ``ingest_records`` walks it
    in place."""
    __slots__ = (
        "t_start", "n", "rows", "uniq_rows", "batch", "rec_op", "recs",
        "client", "cseq", "ref", "prepacked", "pipelined", "prep_ms",
        "prepack_ms", "seq_ms", "dispatch_ms", "out_seq", "out_min",
        "nacked", "n_ok", "keep", "ok")

    def __init__(self):
        self.prepacked = None
        self.pipelined = False
        self.prep_ms = 0.0
        self.prepack_ms = 0.0
        self.seq_ms = 0.0
        self.dispatch_ms = 0.0


class TreeServingEngine(ServingEngineBase):
    """Serving engine for SharedTree documents (SURVEY.md §2.6's serving
    half): the same Deli + durable log + batch-window + summary/tail-replay
    pipeline as the string engine, over the batched tree kernel
    (``TensorTreeStore``). Ops are the SharedTree oracle wire dicts
    (insert/remove/move/setValue/transaction — ``models/shared_tree.py``'s
    module docstring is the merge spec; the kernel reproduces it on device).

    Capacity story: node slots are per-doc-row; an insert that finds no
    free slot sets the doc's sticky overflow flag and drops the op
    device-side. ``recover_overflowed`` is the escape hatch — rebuild the
    doc from its full log history at doubled capacity (same apply kernel),
    then re-upload into its row if it fits or graduate it to its own
    right-sized single-doc store (terminal tier), exactly the string
    engine's recovery shape."""

    def __init__(self, n_docs: int, capacity: int = 256,
                 batch_window: int = 64, n_partitions: int = 8,
                 log: Optional[PartitionedLog] = None,
                 store: Optional["TensorTreeStore"] = None,
                 sequencer: str = "python", mesh=None):
        """``mesh``: a 1-D ``docs`` device mesh shards the tree planes by
        doc row; every batched apply runs as a collective-free shard_map
        of the same record scan (SURVEY.md §2.14 doc-DP for the tree
        tier; the compact wire path falls back to dense packed planes,
        which shard row-wise)."""
        from ..ops.tree_store import TensorTreeStore
        super().__init__(batch_window, n_partitions, log=log,
                         sequencer=sequencer)
        if store is not None and mesh is not None \
                and getattr(store, "mesh", None) is not mesh:
            raise ValueError("mesh given with a store not sharded over it")
        self.store = store if store is not None \
            else TensorTreeStore(n_docs, capacity, mesh=mesh)
        self.mesh = getattr(self.store, "mesh", mesh)
        self.n_docs = n_docs
        self.capacity = self.store.capacity
        self._init_row_caches(n_docs)
        # terminal tier: docs too big for the batched store, each in its
        # own single-doc store sharing the main store's interners
        self._graduated: Dict[str, Any] = {}
        self._grad_queue: Dict[str, List[SequencedDocumentMessage]] = {}

    def allocate_node_ids(self, count: int) -> int:
        """Reserve a cluster of ``count`` numeric node ids; returns the
        base handle (ids are the strings ``#<base>``..``#<base+count-1>``,
        never interned). The id-compressor role (SURVEY.md §2.11): the
        columnar hot path ships ids as ints, so serving never touches a
        string table."""
        return self.store._ids.reserve(count)

    def sync(self) -> np.ndarray:
        """Device→host read of the per-row overflow flags — the honest
        end-of-pipeline sync a sequencer ack path does."""
        return np.asarray(self.store.state.overflow)

    # ------------------------------------------------------------ validation

    _EDIT_KINDS = ("insert", "remove", "move", "setValue", "transaction")

    def _valid_spec(self, spec: Any, depth: int = 0) -> bool:
        if depth > 64 or not isinstance(spec, dict) \
                or not isinstance(spec.get("id"), str) or not spec["id"]:
            return False
        if spec.get("type") is not None \
                and not isinstance(spec["type"], str):
            return False
        try:
            json.dumps(spec.get("value"))
        except (TypeError, ValueError):
            return False
        kids = spec.get("children")
        if kids is None:
            return True
        if not isinstance(kids, dict):
            return False
        for field, specs in kids.items():
            if not isinstance(field, str) or not isinstance(specs, list):
                return False
            if not all(self._valid_spec(c, depth + 1) for c in specs):
                return False
        return True

    def _valid_edit(self, op: Any, depth: int = 0) -> bool:
        if depth > 8 or not isinstance(op, dict) \
                or op.get("op") not in self._EDIT_KINDS:
            return False
        kind = op["op"]
        if kind == "insert":
            return (isinstance(op.get("parent"), str)
                    and isinstance(op.get("field"), str)
                    and (op.get("after") is None
                         or isinstance(op["after"], str))
                    and isinstance(op.get("nodes"), list)
                    and len(op["nodes"]) >= 1
                    and all(self._valid_spec(s) for s in op["nodes"]))
        if kind == "remove":
            return isinstance(op.get("id"), str) and bool(op["id"])
        if kind == "move":
            return (isinstance(op.get("id"), str)
                    and isinstance(op.get("parent"), str)
                    and isinstance(op.get("field"), str)
                    and (op.get("after") is None
                         or isinstance(op["after"], str)))
        if kind == "setValue":
            # "value" must be PRESENT (the expand path reads op["value"]):
            # an acked-and-logged op flush cannot apply poisons recovery
            if not isinstance(op.get("id"), str) or "value" not in op:
                return False
            try:
                json.dumps(op["value"])
            except (TypeError, ValueError):
                return False
            return True
        # transaction — top-level only: a nested transaction's constraints
        # cannot share the single device gate (ok_txn), and the client API
        # cannot produce one ("transactions do not nest",
        # models/shared_tree.py) — reject at ingress rather than silently
        # dropping the inner constraints as the old expansion did
        if depth > 0:
            return False
        cons = op.get("constraints", [])
        if not (isinstance(cons, list)
                and all(isinstance(c, dict)
                        and isinstance(c.get("nodeExists"), str)
                        for c in cons)):
            return False
        return (isinstance(op.get("edits"), list) and len(op["edits"]) >= 1
                and all(self._valid_edit(e, depth + 1)
                        for e in op["edits"]))

    def _valid_op(self, contents: Any) -> bool:
        return self._valid_edit(contents)

    # ----------------------------------------------------------- device side

    def doc_row(self, doc_id: str) -> int:
        row = super().doc_row(doc_id)
        self._note_row(doc_id, row)
        return row

    def _admit(self, doc_id: str, contents: Any,
               client_id: int = -1) -> None:
        if doc_id not in self._graduated:
            # graduated docs own their store; don't re-pin a tier row
            self.doc_row(doc_id)

    def _enqueue(self, doc_id: str, msg: SequencedDocumentMessage) -> None:
        if doc_id in self._graduated:
            self._grad_queue.setdefault(doc_id, []).append(msg)
        else:
            self._queue.append((self.doc_row(doc_id), msg))

    def _queued(self) -> int:
        return len(self._queue) + sum(map(len, self._grad_queue.values()))

    def _flush_impl(self) -> int:
        n = len(self._queue)
        if self._queue:
            self.store.apply_messages(self._queue)
            self._queue.clear()
        for doc_id, msgs in self._grad_queue.items():
            if msgs:
                self._graduated[doc_id].apply_messages(
                    (0, m) for m in msgs)
                n += len(msgs)
                msgs.clear()
        return n

    # ------------------------------------------------------- columnar ingest

    def _validate_record_batch(self, batch: dict, n_ops: int):
        """Bounds-validate a wire record batch (tree_wire module
        docstring). Only BOUNDS need checking for state safety: the
        kernel guards every merge rule on device, and recovery replays
        the same raw planes — a weird-but-bounded stream cannot make
        live and recovered state diverge."""
        rec_op = np.ascontiguousarray(batch["rec_op"], np.int64)
        recs = np.ascontiguousarray(batch["recs"], np.int32)
        if recs.ndim != 2 or recs.shape[1] != 8 \
                or recs.shape[0] != len(rec_op):
            raise ValueError("record planes malformed")
        r = len(rec_op)
        if r and (rec_op[0] < 0 or rec_op[-1] >= n_ops
                  or np.any(np.diff(rec_op) < 0)):
            raise ValueError("rec_op must ascend within the op batch")
        # every op owns ≥1 record: a record-less op would be sequenced
        # but invisible to the seq-derivation and decode paths
        if not np.array_equal(np.unique(rec_op), np.arange(n_ops)):
            raise ValueError("rec_op must cover every op in the batch")
        from ..ops.tree_store import ANON_BASE
        # id entries may be ints: pre-compressed numeric handles from the
        # id-compressor namespace (passed through with no interning)
        if not all((isinstance(s, str) and s)
                   or (isinstance(s, int) and not isinstance(s, bool)
                       and ANON_BASE <= s < (1 << 31))
                   for s in batch["ids"]):
            raise ValueError("every id table entry must be a non-empty "
                             "str or a numeric handle in the anonymous "
                             "namespace")
        for tab, what in ((batch["fields"], "field"),
                          (batch["types"], "type")):
            if not all(isinstance(s, str) and s for s in tab):
                raise ValueError(
                    f"every {what} table entry must be a non-empty str")
        try:  # values land in the durable record and the interner
            json.dumps(batch["values"], sort_keys=True)
        except (TypeError, ValueError) as e:
            raise ValueError(f"unserializable value table: {e}") from None
        if r:
            k = recs[:, 0]
            if not ((k >= 1) &
                    (k <= int(TreeOpKind.TXN_BEGIN_EXISTS))).all():
                raise ValueError("record kind out of range")
            for col, size, what in (
                    (1, len(batch["ids"]), "node"),
                    (2, len(batch["ids"]), "parent"),
                    (3, len(batch["ids"]), "after"),
                    (4, len(batch["fields"]), "field"),
                    (5, len(batch["values"]), "value"),
                    (6, len(batch["types"]), "type")):
                c = recs[:, col]
                if not ((c >= 0) & (c <= size)).all():
                    raise ValueError(f"{what} handle out of table bounds")
            me = recs[:, 7]
            if not ((me >= 0) & (me <= 1)).all():
                raise ValueError("record meta out of range")
        return rec_op, recs

    def _map_records(self, recs: np.ndarray, tables: dict) -> np.ndarray:
        """Batch-local table indices → store interner handles: one dict
        hit per UNIQUE string/value, then vectorized gathers."""
        def table_map(items, interner):
            m = np.zeros(len(items) + 1, np.int32)
            if items:
                m[1:] = interner.bulk(items)
            return m

        id_map = table_map(tables["ids"], self.store._ids)
        f_map = table_map(tables["fields"], self.store._fields)
        t_map = table_map(tables["types"], self.store._types)
        v_map = table_map(tables["values"], self.store._values)
        g = np.empty_like(recs)
        g[:, 0] = recs[:, 0]
        g[:, 1] = id_map[recs[:, 1]]
        g[:, 2] = id_map[recs[:, 2]]
        g[:, 3] = id_map[recs[:, 3]]
        g[:, 4] = f_map[recs[:, 4]]
        g[:, 5] = v_map[recs[:, 5]]
        g[:, 6] = t_map[recs[:, 6]]
        g[:, 7] = recs[:, 7]
        return g

    def _wire_eligible(self, batch: dict) -> bool:
        """Can this batch ride the compact width-coded wire? Id/value
        index lanes width-code u16 → u32 (``pack_wire_records``), so
        only the u8 field/type lanes and the u16 row lane bound table
        sizes; mesh stores, whose dense planes shard row-wise, take the
        dense path."""
        return (self.mesh is None
                and len(batch["ids"]) < 0x7FFFFFFF
                and len(batch["fields"]) < 0xFF
                and len(batch["types"]) < 0xFF
                and len(batch["values"]) < 0x7FFFFFFF
                and self.n_docs <= 0x10000)

    _WIRE_R_FLOOR = 256   # pow2 record-padding floor (bounds recompiles)

    def _dispatch_wire(self, batch, recs, rec_op, keep, rows, out_seq,
                       nacked):
        """Pack kept records into width-coded wire buffers and
        dispatch ``apply_tree_wire`` (upload bytes are the bottleneck —
        see tree_kernel). Returns the prep/dispatch split timestamp, or
        None when the dense path must handle the batch (oversized o)."""
        recs_k = recs[keep]
        rec_op_k = rec_op[keep]
        rows_r = rows[rec_op_k].astype(np.int64)
        pp = self.store.prepack_wire(recs_k, rec_op_k, rows_r, batch,
                                     r_floor=self._WIRE_R_FLOOR)
        if pp is None:
            return None
        # per-doc first-op seq (op seqs are consecutive per doc in-batch)
        base = np.zeros(self.n_docs, np.int32)
        ok = np.flatnonzero(~nacked)
        if len(ok):
            rows_ok = rows[ok]
            uniq, firsti = np.unique(rows_ok, return_index=True)
            base[uniq] = out_seq[ok][firsti].astype(np.int32)
        t_prep = time.perf_counter()
        self.store.apply_wire_prepacked(pp, base)
        return t_prep

    def _ingest_prepare(self, doc_ids: Optional[List[str]], clients,
                        client_seqs, ref_seqs, batch: dict,
                        rows: Optional[np.ndarray] = None,
                        prepack: bool = False) -> "_TreeIngestWave":
        """Stage 1 — validation, row resolution, row-handle fill, and
        (``prepack=True``, pipelined mode) the wire pack +
        interner maps, all independent of sequencing results."""
        raw = getattr(self.deli, "raw", None)
        if raw is None:
            raise RuntimeError("batch ingest requires sequencer='native'")
        w = _TreeIngestWave()
        w.t_start = time.perf_counter()
        n = len(doc_ids) if rows is None else len(rows)
        if not (len(clients) == len(client_seqs) == len(ref_seqs) == n):
            raise ValueError("batch fields must have equal length")
        w.rec_op, w.recs = self._validate_record_batch(batch, n)
        if rows is None:
            if self._graduated and any(d in self._graduated
                                       for d in doc_ids):
                raise ValueError("a targeted doc has graduated off the "
                                 "flat tier; route its ops through "
                                 "submit()")
            rows = np.fromiter((self.doc_row(d) for d in doc_ids),
                               np.int32, count=n)
        else:
            rows = np.ascontiguousarray(rows, np.int32)
            if n and not ((rows >= 0) & (rows < self.n_docs)).all():
                raise ValueError("row out of range")
        w.rows, w.n = rows, n
        w.uniq_rows = np.unique(rows)
        # unknown rows fail in _fill_row_handles (no doc → KeyError)
        self._fill_row_handles(w.uniq_rows, raw)
        w.batch = batch
        w.client = np.ascontiguousarray(clients, np.int32)
        w.cseq = np.ascontiguousarray(client_seqs, np.int32)
        w.ref = np.ascontiguousarray(ref_seqs, np.int32)
        w.prep_ms = (time.perf_counter() - w.t_start) * 1000
        if prepack:
            w.pipelined = True
            if self._wire_eligible(batch):
                t0 = time.perf_counter()
                # pack EVERY record AHEAD of sequencing (overlaps the
                # previous wave's dispatch; nacks resolve at dispatch,
                # which discards the prepack on the rare nacked wave).
                # None → dense fallback, which mints interner handles
                # inline: the executor barriers on this wave's dispatch
                # before packing the next wave's tables.
                w.prepacked = self.store.prepack_wire(
                    w.recs, w.rec_op, rows[w.rec_op].astype(np.int64),
                    batch, r_floor=self._WIRE_R_FLOOR)
                w.prepack_ms = (time.perf_counter() - t0) * 1000
        return w

    def _ingest_sequence(self, w: "_TreeIngestWave") -> None:
        """Stage 2 — per-op queue flush + ONE native sequencing call +
        nack masking + the per-doc window-floor fold."""
        self.flush()  # per-op queue first: per-doc seq order must hold
        t0 = time.perf_counter()
        raw = self.deli.raw
        w.out_seq, w.out_min, w.nacked, w.n_ok = self._sequence_columnar(
            raw, self._row_handle[w.rows], w.client, w.cseq, w.ref,
            "tree records batch")
        w.keep = ~w.nacked[w.rec_op] if len(w.rec_op) \
            else np.zeros(0, bool)
        w.ok = np.flatnonzero(~w.nacked)
        if len(w.ok):
            # per-doc window floor: the LAST op of each doc carries its
            # latest min_seq (one dict write per doc, not per op)
            rows_ok = w.rows[w.ok]
            order = np.argsort(rows_ok, kind="stable")
            rs = rows_ok[order]
            ms = w.out_min[w.ok][order]
            starts = np.r_[0, np.flatnonzero(np.diff(rs)) + 1]
            lasts = np.r_[starts[1:] - 1, len(rs) - 1]
            rdi = self._row_doc_id
            self._min_seq.update(
                zip((rdi[int(r)] for r in rs[starts]),
                    (int(m) for m in ms[lasts])))
        w.seq_ms = (time.perf_counter() - t0) * 1000

    def _ingest_dispatch(self, w: "_TreeIngestWave") -> None:
        """Stage 3 — the async device merge: the prepacked wire (base
        derived from this wave's seqs), the inline wire pack, or the
        dense fallback."""
        # degradation injection: an armed plan may stall the device
        # apply here; the watchdog must surface it
        fault_point(SITE_APPLY_STALL, what="ingest_records")
        t0 = time.perf_counter()
        pp = w.prepacked
        if pp is not None and w.nacked.any():
            # rare: the prepack packed EVERY record; drop it and repack
            # inline below with the keep mask
            pp = w.prepacked = None
        t_prep = None
        if pp is not None:
            # no nacks: per-doc first-op seq straight off the full rows
            # (op seqs are consecutive per doc in-batch)
            base = np.zeros(self.n_docs, np.int32)
            if len(w.ok):
                uniq, firsti = np.unique(w.rows, return_index=True)
                base[uniq] = w.out_seq[firsti].astype(np.int32)
            t_prep = time.perf_counter()
            self.store.apply_wire_prepacked(pp, base)
            w.prepacked = None
        elif self._wire_eligible(w.batch):
            t_prep = self._dispatch_wire(w.batch, w.recs, w.rec_op,
                                         w.keep, w.rows, w.out_seq,
                                         w.nacked)
        if t_prep is None:
            # dense fallback: host-side table mapping + int32 planes
            g = self._map_records(w.recs, w.batch)
            rows_r = w.rows[w.rec_op][w.keep]
            g_k = g[w.keep]
            seq_r = w.out_seq[w.rec_op][w.keep]
            t_prep = time.perf_counter()
            # device apply dispatched before the log append (host log
            # work rides under it), exactly the string pipeline's order
            self.store.apply_records(rows_r, g_k, seq_r)
        w.prep_ms += (t_prep - t0) * 1000
        w.dispatch_ms = (time.perf_counter() - t_prep) * 1000

    def _ingest_log(self, w: "_TreeIngestWave") -> dict:
        """Stage 4 — the durable whole-batch append (ack barrier: poison
        clears and callers may ack only after this commits) + metrics."""
        t0 = time.perf_counter()
        ok = w.ok
        ts = self.deli.clock()
        doc_tab = [self._row_doc_id[int(r)] for r in w.uniq_rows]
        doc_plane = np.searchsorted(w.uniq_rows,
                                    w.rows[ok]).astype(np.int32)
        new_idx = np.cumsum(~w.nacked) - 1   # op index among kept ops
        ref_clamped = self._clamped_ref(w.ref, w.out_seq)
        batch = w.batch
        self._append_columnar(TreeRecordOps(
            doc_tab, doc_plane,
            w.client[ok], w.cseq[ok], ref_clamped[ok], w.out_seq[ok],
            w.out_min[ok], new_idx[w.rec_op][w.keep],
            np.ascontiguousarray(w.recs[w.keep]),
            list(batch["ids"]), list(batch["fields"]),
            list(batch["types"]), list(batch["values"]), timestamp=ts))
        log_ms = (time.perf_counter() - t0) * 1000
        self.metrics.inc("flushes")
        self.metrics.inc("ops_flushed", w.n_ok)
        self.metrics.observe("ingest_seq_ms", w.seq_ms)
        self.metrics.observe("ingest_prep_ms", w.prep_ms)
        self.metrics.observe("ingest_dispatch_ms", w.dispatch_ms)
        self.metrics.observe("ingest_log_ms", log_ms)
        if w.prepack_ms:
            # pack work that ran OFF the critical path (pack worker,
            # overlapped with the previous wave's dispatch)
            self.metrics.observe("ingest_prepack_ms", w.prepack_ms)
        busy_ms = w.seq_ms + w.prep_ms + w.dispatch_ms + log_ms
        # pipelined waves sit in stage queues between workers; wall time
        # since submission would count that waiting as a stall, so the
        # recorded wave cost is the BUSY time instead
        elapsed_ms = busy_ms if w.pipelined \
            else (time.perf_counter() - w.t_start) * 1000
        self.metrics.observe("flush_ms", elapsed_ms)
        tracing.TRACER.record_complete(
            "serving.ingest_records", elapsed_ms, ops=int(w.n_ok),
            nacked=int(w.nacked.sum()), seq_ms=w.seq_ms,
            dispatch_ms=w.dispatch_ms, log_ms=log_ms)
        # read plane (ISSUE 20): pump at ingest pace, as in the string
        # fast path — tree records ship as binary T frames
        plane = self._read_plane
        if plane is not None and w.n_ok:
            plane.pump()
        return {"seq": w.out_seq, "nacked": int(w.nacked.sum())}

    def ingest_records(self, doc_ids: Optional[List[str]], clients,
                       client_seqs, ref_seqs, batch: dict,
                       rows: Optional[np.ndarray] = None) -> dict:
        """The tree GENERAL volume path: N edits of any kind (op i
        targets ``doc_ids[i]``; per-doc order = list order) arriving
        PRE-ENCODED in the columnar record wire format
        (``server.tree_wire``) — one native sequencing call, one
        vectorized table→interner mapping, one batched device apply, one
        raw-plane durable record (``TreeRecordOps``). Nacked ops' records
        are dropped everywhere. Callers on the hot path pass cached
        ``rows`` (from ``doc_row``) instead of ``doc_ids``; cached rows
        are invalidated when ``recover_overflowed`` graduates a doc
        (re-resolve after recovery, as with the string engine). Returns
        {"seq": (N,) (negative = nack code), "nacked"}.

        This is the serial walk of the four stage methods above; the
        ``PipelinedIngestExecutor`` runs the SAME stages on its worker
        threads (``ex.submit(None, clients, client_seqs, ref_seqs,
        batch, rows=rows)``), overlapping wire-pack, sequencing, device
        dispatch, and the durable append across waves."""
        self._check_poisoned()
        w = self._ingest_prepare(doc_ids, clients, client_seqs,
                                 ref_seqs, batch, rows=rows)
        self._ingest_sequence(w)
        self._ingest_dispatch(w)
        return self._ingest_log(w)

    def ingest_batch(self, doc_ids: List[str], clients, client_seqs,
                     ref_seqs, ops: List[dict]) -> dict:
        """Dict-op convenience over ``ingest_records``: validate + encode
        each op through the canonical ``RecordEmitter`` (the per-op host
        cost a real client would pay at serialization time), then run the
        columnar record path — no per-op message objects, no queue
        drain. Returns {"seq": (N,), "nacked"}."""
        if len(ops) != len(doc_ids):
            raise ValueError("batch fields must have equal length")
        for op in ops:
            if not self._valid_op(op):
                raise ValueError(f"malformed tree op {op!r}")
        from .tree_wire import encode_tree_batch
        return self.ingest_records(doc_ids, clients, client_seqs, ref_seqs,
                                   encode_tree_batch(ops))

    def ingest_leaves(self, doc_ids: List[str], clients, client_seqs,
                      ref_seqs, parents: List[str], fields: List[str],
                      node_ids: List[str], values: list,
                      types: Optional[List[str]] = None,
                      afters: Optional[List[Optional[str]]] = None
                      ) -> dict:
        """The tree FLAT volume path: N single-node inserts (op i creates
        ``node_ids[i]`` under ``parents[i]``/``fields[i]``), each ONE
        ``INSERT_SOLO`` record. A thin validated front over
        ``tree_wire.encode_leaf_records`` + ``ingest_records`` — flat
        rides the SAME engine path as the general batch, so flat ≥
        general by construction (the old duplicate per-item table
        builder is retired). Hot-path callers pre-encode with
        ``encode_leaf_records`` off the serving thread and drive
        ``ingest_records``/the pipelined executor directly."""
        n = len(node_ids)
        types = types if types is not None else [None] * n
        afters = afters if afters is not None else [None] * n
        if not (len(doc_ids) == len(clients) == len(client_seqs)
                == len(ref_seqs) == len(parents) == len(fields)
                == len(values) == len(types) == len(afters) == n):
            raise ValueError("batch fields must have equal length")
        for lst, what in ((parents, "parent"), (fields, "field"),
                          (node_ids, "node id")):
            if not all(isinstance(x, str) and x for x in lst):
                raise ValueError(f"every {what} must be a non-empty str")
        if not all(t is None or isinstance(t, str) for t in types):
            raise ValueError("every type must be a str or None")
        if not all(a is None or (isinstance(a, str) and a)
                   for a in afters):
            raise ValueError("every after must be a non-empty str or None")
        try:  # values land in the durable record and the interner
            # (sort_keys matches the canonical value encoding — a value
            # only dumps-able unsorted would crash post-sequencing)
            json.dumps(values, sort_keys=True)
        except (TypeError, ValueError) as e:
            raise ValueError(f"unserializable node value: {e}") from None
        from .tree_wire import encode_leaf_records
        return self.ingest_records(
            doc_ids, clients, client_seqs, ref_seqs,
            encode_leaf_records(parents, fields, node_ids, values,
                                types, afters))

    def _store_of(self, doc_id: str):
        """(store, row) owning this doc, post-flush."""
        if doc_id in self._graduated:
            return self._graduated[doc_id], 0
        return self.store, self.doc_row(doc_id)

    # ----------------------------------------------------------------- reads

    def to_dict(self, doc_id: str) -> dict:
        self.flush()
        store, row = self._store_of(doc_id)
        return store.to_dict(row)

    def node_value(self, doc_id: str, node_id: str):
        self.flush()
        store, row = self._store_of(doc_id)
        return store.node_value(row, node_id)

    def has_node(self, doc_id: str, node_id: str) -> bool:
        self.flush()
        store, row = self._store_of(doc_id)
        return store.has_node(row, node_id)

    def node_count(self, doc_id: str) -> int:
        self.flush()
        store, row = self._store_of(doc_id)
        return store.node_count(row)

    # ----------------------------------------------------- overflow recovery

    def overflowed_docs(self) -> List[str]:
        flags = self.store.overflowed()
        out = [d for d, row in self._doc_rows.items() if flags[row]]
        out += [d for d, s in self._graduated.items()
                if s.overflowed().any()]
        return out

    def _doc_log_messages(self, doc_id: str):
        """Every sequenced OP message for one doc, seq-ascending, with
        DECODED dict contents (oracle replay / audit; the state-rebuild
        path uses ``_doc_log_records`` instead). Per-op records live in
        the doc's partition; whole-batch records round-robin across
        partitions (see the string engine)."""
        p_own = partition_of(doc_id, self.log.n_partitions)
        msgs = []
        for p in range(self.log.n_partitions):
            for rec in self.log.read(p):
                if hasattr(rec, "expand"):
                    msgs.extend(rec.expand(only_doc=doc_id))
                elif p == p_own and rec.doc_id == doc_id \
                        and rec.type == MessageType.OP:
                    msgs.append(rec)
        msgs.sort(key=lambda m: m.seq)
        return msgs

    def _doc_log_records(self, doc_id: str):
        """One doc's full RAW record history as seq-ascending per-op
        (seq, records) chunks in store-interner handle space.
        ``TreeRecordOps`` batches contribute their planes bit-identically;
        per-op dict messages (submit path, legacy log families) re-encode
        through the canonical emitter."""
        p_own = partition_of(doc_id, self.log.n_partitions)
        emitter = self.store.emitter
        chunks: List[tuple] = []   # (seq, (k,8) global-handle records)

        def add_msg(m):
            chunks.append((m.seq,
                           np.array(emitter.emit_op(m.contents), np.int32)))

        for p in range(self.log.n_partitions):
            for rec in self.log.read(p):
                if isinstance(rec, TreeRecordOps):
                    if doc_id not in rec.doc_ids:
                        continue
                    want = rec.doc_ids.index(doc_id)
                    sel = np.flatnonzero(np.asarray(rec.doc) == want)
                    if not len(sel):
                        continue
                    g = self._map_records(
                        np.ascontiguousarray(rec.recs, np.int32),
                        {"ids": rec.ids, "fields": rec.fields,
                         "types": rec.types, "values": rec.values})
                    starts, ends = rec._op_slices()
                    for i in sel:
                        chunks.append((int(rec.seq[i]),
                                       g[starts[i]:ends[i]]))
                elif isinstance(rec, ColumnarOps):
                    for m in rec.expand(only_doc=doc_id):
                        add_msg(m)
                elif p == p_own and rec.doc_id == doc_id \
                        and rec.type == MessageType.OP:
                    add_msg(rec)
        chunks.sort(key=lambda c: c[0])
        return chunks

    _REBUILD_CHUNK = 2048   # bounds the packed scan length per dispatch

    @staticmethod
    def _chunked_ops(chunks):
        """Group per-op (seq, recs) chunks into ≤_REBUILD_CHUNK-record
        apply batches WITHOUT splitting an op: the kernel resets the
        group flags per apply call, so a transaction's records must land
        in one batch."""
        batch: List[tuple] = []
        size = 0
        for seq, recs in chunks:
            if batch and size + len(recs) > TreeServingEngine._REBUILD_CHUNK:
                yield batch
                batch, size = [], 0
            batch.append((seq, recs))
            size += len(recs)
        if batch:
            yield batch

    @staticmethod
    def _flatten_ops(batch):
        recs = np.concatenate([c[1] for c in batch])
        seqs = np.concatenate([np.full(len(c[1]), c[0], np.int64)
                               for c in batch])
        return recs, seqs

    def _rebuild_doc(self, doc_id: str, start_capacity: int,
                     grow_limit: int):
        """Replay the doc's full RAW record history into a fresh
        single-doc store (sharing the batched store's interners so its
        planes can be adopted verbatim), doubling capacity until it
        fits. Chunked applies keep the scan length bounded."""
        from ..ops.tree_store import TensorTreeStore
        chunks = self._doc_log_records(doc_id)
        cap = max(start_capacity, 64)
        while True:
            cap *= 2
            if cap > grow_limit:
                raise MemoryError(
                    f"{doc_id}: rebuild exceeds grow limit {grow_limit}")
            tmp = TensorTreeStore(1, cap)
            tmp.share_interners(self.store)
            for batch in self._chunked_ops(chunks):
                recs, seqs = self._flatten_ops(batch)
                tmp.apply_records(np.zeros(len(recs), np.int64), recs,
                                  seqs)
            if not tmp.overflowed().any():
                tmp.repack()   # slot churn must not inflate the fit check
                return tmp

    def _replay_tail(self, summary: dict, control_hook=None) -> None:
        """Tree tail replay: raw ``TreeRecordOps`` planes re-apply
        bit-identically (no decode on the state path); per-op dict
        messages re-encode through the emitter; everything merges per doc
        in seq order — the sequencer replays every message in the same
        order (the r4 partition-scan-order fix)."""
        self._verify_tail_anchor(summary)
        items: List[tuple] = []   # (doc_id, seq, msg, raw recs or None)
        for p in range(self.log.n_partitions):
            for rec in self.log.read(
                    p, from_offset=summary["log_offsets"][p]):
                if isinstance(rec, TreeRecordOps):
                    g = self._map_records(
                        np.ascontiguousarray(rec.recs, np.int32),
                        {"ids": rec.ids, "fields": rec.fields,
                         "types": rec.types, "values": rec.values})
                    starts, ends = rec._op_slices()
                    for i in range(len(rec.seq)):
                        msg = SequencedDocumentMessage(
                            doc_id=rec.doc_ids[int(rec.doc[i])],
                            client_id=int(rec.client[i]),
                            client_seq=int(rec.client_seq[i]),
                            ref_seq=int(rec.ref_seq[i]),
                            seq=int(rec.seq[i]),
                            min_seq=int(rec.min_seq[i]),
                            type=MessageType.OP, contents=None,
                            timestamp=rec.timestamp)
                        items.append((msg.doc_id, msg.seq, msg,
                                      g[starts[i]:ends[i]]))
                elif hasattr(rec, "expand"):
                    for m in rec.expand():
                        items.append((m.doc_id, m.seq, m, None))
                else:
                    items.append((rec.doc_id, rec.seq, rec, None))
        items.sort(key=lambda t: (t[0], t[1]))
        emitter = self.store.emitter
        flat_ops: List[tuple] = []   # (row, seq, recs) whole ops
        grad: Dict[str, List[tuple]] = {}
        for doc_id, seq, msg, raw in items:
            self.deli.replay(msg)
            self._record_attribution(msg)
            if control_hook is not None and control_hook(msg):
                continue
            if msg.type != MessageType.OP:
                continue
            self._min_seq[doc_id] = max(self._min_seq.get(doc_id, 0),
                                        msg.min_seq)
            rl = raw if raw is not None else \
                np.array(emitter.emit_op(msg.contents), np.int32)
            if doc_id in self._graduated:
                grad.setdefault(doc_id, []).append((seq, rl))
            else:
                flat_ops.append((self.doc_row(doc_id), seq, rl))
        # chunked applies at OP boundaries (the kernel resets group flags
        # per call — a split transaction would lose its gate)
        batch: List[tuple] = []
        size = 0

        def apply_flat(batch):
            rows = np.concatenate([np.full(len(r), row, np.int64)
                                   for row, _s, r in batch])
            recs = np.concatenate([r for _row, _s, r in batch])
            seqs = np.concatenate([np.full(len(r), s, np.int64)
                                   for _row, s, r in batch])
            self.store.apply_records(rows, recs, seqs)

        for row, seq, rl in flat_ops:
            if batch and size + len(rl) > self._REBUILD_CHUNK:
                apply_flat(batch)
                batch, size = [], 0
            batch.append((row, seq, rl))
            size += len(rl)
        if batch:
            apply_flat(batch)
        for doc_id, parts in grad.items():
            for gb in self._chunked_ops(parts):
                recs, seqs = self._flatten_ops(gb)
                self._graduated[doc_id].apply_records(
                    np.zeros(len(recs), np.int64), recs, seqs)

    def recover_overflowed(self, grow_limit: int = 1 << 16
                           ) -> Dict[str, str]:
        """Drain every overflowed doc's history through a right-sized
        rebuild; re-upload or graduate. Zero acked ops are lost: the log
        has every sequenced op. {doc_id: "reuploaded"|"graduated"|
        "regrown"}."""
        self.flush()  # queues must be empty: the rebuild replays the log
        report: Dict[str, str] = {}
        flags = self.store.overflowed()
        for doc_id in [d for d, r in self._doc_rows.items() if flags[r]]:
            row = self._doc_rows[doc_id]
            tmp = self._rebuild_doc(doc_id, self.store.capacity, grow_limit)
            if tmp.high_water() <= self.store.capacity:
                self.store.adopt_doc(row, tmp)
                report[doc_id] = "reuploaded"
            else:
                self.store.clear_doc(row)
                self._graduated[doc_id] = tmp
                # return the row AND clear the columnar-ingest caches: a
                # caller-cached row for this doc now fails loudly in
                # _fill_row_handles instead of silently sequencing under
                # a stale doc handle (live vs recovery divergence)
                self._free_rows.append(self._doc_rows.pop(doc_id))
                self._dedup.release_row(row)
                self._row_doc_id[row] = None
                self._row_handle[row] = -1
                report[doc_id] = "graduated"
            # planes rewritten outside the op stream: seq-based dirty
            # detection would miss the row in the next delta summary
            self._dirty_outside_ops.add(doc_id)
        # the terminal tier can overflow too: rebuild in place, doubled
        for doc_id, store in list(self._graduated.items()):
            if store.overflowed().any():
                self._graduated[doc_id] = self._rebuild_doc(
                    doc_id, store.capacity, grow_limit)
                report[doc_id] = "regrown"
        if report:
            self.metrics.inc("overflow_recoveries", len(report))
        return report

    # ----------------------------------------------------- summary / recovery

    def summarize(self, incremental: bool = False) -> dict:
        """``incremental=True`` (after one full summary) captures a
        DELTA: only rows whose doc sequenced an op since the base —
        detected host-side, no device read — plus rows whose mapping
        changed or were rewritten by overflow recovery, plus append-only
        interner deltas. Clean rows ride by reference to the base
        summary (SURVEY.md §2.16). Graduated single-doc stores snapshot
        in full (rare tier)."""
        self.flush()
        prev = self._summ_bookkeeping
        if self._incremental_ok(incremental):
            dirty_rows, cur_seqs = self._dirty_rows_since(prev)
            summary = self._base_summary()
            self._mark_delta(summary, prev, cur_seqs)
            summary["store_delta"] = self.store.snapshot_rows(
                sorted(dirty_rows), prev["interner_bases"])
            summary["graduated"] = {d: s.snapshot()
                                    for d, s in self._graduated.items()}
            self._chain_depth += 1
        else:
            summary = self._base_summary()
            summary["kind"] = "full"
            self._chain_depth = 0
            summary["store"] = self.store.snapshot()
            summary["graduated"] = {d: s.snapshot()
                                    for d, s in self._graduated.items()}
            cur_seqs = {d: self.deli.doc_seq(d) for d in self._doc_rows}
        self._note_summary(summary, cur_seqs,
                           interner_bases=self.store.interner_bases())
        return summary

    @classmethod
    def load(cls, summary: dict, log: PartitionedLog, mesh=None,
             **kwargs) -> "TreeServingEngine":
        from ..ops.tree_store import TensorTreeStore
        full, deltas = cls.resolve_summary_chain(summary)
        store = TensorTreeStore.restore(full["store"], mesh=mesh)
        for delta in deltas:
            store.apply_row_snapshot(delta["store_delta"])
        engine = cls(store.n_docs, store.capacity, log=log, store=store,
                     mesh=mesh, **kwargs)
        engine._restore_base(summary)
        for doc_id, snap in summary["graduated"].items():
            grad = TensorTreeStore.restore(snap)
            # graduated stores alias the batched store's interners at
            # runtime, so their snapshots exported the SAME tables the
            # main snapshot did — re-alias so tail records mapped through
            # the engine's interners mean the same strings here
            grad.share_interners(engine.store)
            engine._graduated[doc_id] = grad
        engine._replay_tail(summary)
        engine.flush()
        return engine
