"""Host facade for the batched tree kernel: many SharedTree documents
resident on device.

Mirrors ``TensorStringStore``'s division of labor: the host interns
variable-size identities (node-id strings, field names, type names, JSON
values) into int32 handles and EXPANDS each oracle op dict into the guard +
record stream of ``tree_kernel`` (its module docstring documents the
grouping protocol); the device does all merge math. Reads reconstruct the
oracle's ``to_dict`` shape by walking the sibling linked lists host-side.

Reference counterpart: the serving half of ``@fluidframework/tree``
(SURVEY.md §2.6); oracle: ``models.shared_tree``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from .schema import ValueInterner
from .tree_kernel import (
    META_NESTED, ROOT_HANDLE, TreeOpKind, TreeState, _TREE_PLANES,
    apply_tree_planes_jit, apply_tree_wire_jit, gather_tree_rows_jit,
    tree_state_digest, write_tree_rows_jit,
)

ROOT = "root"


#: Floor of the numeric-id namespace: handles ≥ ANON_BASE are ANONYMOUS —
#: their name is synthesized as ``#<handle>`` and never interned. This is
#: the id-compressor role (SURVEY.md §2.11: distributed UUID→small-int
#: compression): clients ``reserve()`` numeric clusters and ship ids as
#: ints, so the serving hot path never touches a string table.
ANON_BASE = 1 << 20


class _Interner:
    """str ↔ dense int32 handle (1-based; 0 = none). Handles below
    ``ANON_BASE`` are interned strings; handles at or above it are the
    numeric-id namespace (name ``#<handle>``, no storage)."""

    def __init__(self, reserved=()):
        self._ids: Dict[str, int] = {}
        self._names: List[Optional[str]] = [None]
        self._next_anon = ANON_BASE
        for name in reserved:
            self.handle(name)

    @staticmethod
    def _anon_handle(name: str) -> Optional[int]:
        if name.startswith("#"):
            tail = name[1:]
            if tail.isdigit():
                h = int(tail)
                if h >= ANON_BASE:
                    return h
        return None

    def handle(self, name: str) -> int:
        h = self._anon_handle(name)
        if h is not None:
            return h
        if name not in self._ids:
            h = len(self._names)
            if h >= ANON_BASE:
                raise OverflowError("string-id space exhausted; use "
                                    "numeric ids (reserve/#-names)")
            self._ids[name] = h
            self._names.append(name)
        return self._ids[name]

    def peek(self, name: str) -> Optional[int]:
        """Handle if known (or anonymous), WITHOUT interning."""
        h = self._anon_handle(name)
        return h if h is not None else self._ids.get(name)

    def reserve(self, count: int) -> int:
        """Allocate a cluster of ``count`` anonymous numeric ids;
        returns the base handle (ids = base..base+count-1, names
        ``#<h>``)."""
        base = self._next_anon
        self._next_anon = base + count
        return base

    def bulk(self, items) -> list:
        """Handles for a whole table at once (the columnar-ingest hot
        path: local-var loop, one dict probe per item). Table entries
        may be ints (pre-compressed numeric handles, passed through)."""
        ids = self._ids
        names = self._names
        get = ids.get
        anon = self._anon_handle
        out = []
        append = out.append
        for s in items:
            if type(s) is int:
                append(s)
                continue
            v = get(s)
            if v is None:
                v = anon(s)
                if v is None:
                    v = len(names)
                    if v >= ANON_BASE:
                        raise OverflowError("string-id space exhausted")
                    ids[s] = v
                    names.append(s)
            append(v)
        return out

    def name(self, handle: int) -> Optional[str]:
        return f"#{handle}" if handle >= ANON_BASE \
            else self._names[handle]

    def __len__(self) -> int:
        return len(self._names)

    def export_from(self, base_len: int) -> list:
        """Names appended since ``base_len`` (incremental-summary delta;
        the table is append-only)."""
        return list(self._names[base_len:])

    def extend_from(self, names: list) -> None:
        for n in names:
            self.handle(n)

    def export(self) -> dict:
        return {"names": list(self._names), "next_anon": self._next_anon}

    @classmethod
    def restore(cls, snap) -> "_Interner":
        it = cls()
        names = snap["names"] if isinstance(snap, dict) else snap
        for n in names[1:]:
            it.handle(n)
        if isinstance(snap, dict):
            it._next_anon = snap["next_anon"]
        return it


class RecordEmitter:
    """Canonical op-dict → kernel-record encoding, shared by the store's
    message path (global interners) and the client wire encoder (local
    per-batch tables); ``server.tree_wire.decode_op`` inverts it.

    The encoding is throughput-shaped: a standalone flat edit compresses
    to ONE solo record; the begin/guard group protocol appears only where
    atomicity actually needs it (multi-node inserts, transactions)."""

    def __init__(self, h_id, h_field, h_value, h_type):
        self._id = h_id
        self._field = h_field
        self._value = h_value
        self._type = h_type

    @staticmethod
    def _rec(kind, node=0, parent=0, after=0, field=0, value=0,
             type_=0, meta=0):
        return (int(kind), node, parent, after, field, value, type_, meta)

    def _vh(self, value) -> int:
        return 0 if value is None else self._value(value)

    def _th(self, type_name) -> int:
        return 0 if type_name is None else self._type(type_name)

    def _emit_specs(self, op: dict, out: list, solo: bool) -> None:
        """DFS INSERT records for every spec of an insert op (top-level
        chained by ``after``; nested records carry META_NESTED)."""
        after = self._id(op["after"]) if op.get("after") else 0
        parent = self._id(op["parent"])
        field = self._field(op["field"])
        kind = TreeOpKind.INSERT_SOLO if solo else TreeOpKind.INSERT
        for spec in op["nodes"]:
            self._emit_spec(spec, parent, field, after, kind, nested=False,
                            out=out)
            after = self._id(spec["id"])

    def _emit_spec(self, spec: dict, parent: int, field: int, after: int,
                   kind, nested: bool, out: list) -> None:
        nid = self._id(spec["id"])
        out.append(self._rec(
            kind, node=nid, parent=parent, after=after,
            field=field, value=self._vh(spec.get("value")),
            type_=self._th(spec.get("type")),
            meta=META_NESTED if nested else 0))
        for fname, child_specs in (spec.get("children") or {}).items():
            fh = self._field(fname)
            prev = 0
            for child in child_specs:
                self._emit_spec(child, nid, fh, prev, kind, nested=True,
                                out=out)
                prev = self._id(child["id"])

    def emit_op(self, op: dict) -> list:
        """Record tuples for ONE standalone sequenced op."""
        kind = op["op"]
        out: list = []
        if kind == "insert":
            if len(op["nodes"]) == 1:
                # single top-level spec: the INSERT record's own absent
                # check IS the oracle's guard; nested specs gate on
                # created_seq — no flags involved, so everything is solo
                self._emit_specs(op, out, solo=True)
            else:
                # multi-node all-or-nothing needs the guard group; the
                # TXN_BEGIN resets BOTH flags left over from prior ops
                out.append(self._rec(TreeOpKind.TXN_BEGIN))
                for spec in op["nodes"]:
                    out.append(self._rec(TreeOpKind.INS_GUARD_ABSENT,
                                         node=self._id(spec["id"])))
                self._emit_specs(op, out, solo=False)
        elif kind == "remove":
            out.append(self._rec(TreeOpKind.REMOVE_SOLO,
                                 node=self._id(op["id"])))
        elif kind == "move":
            out.append(self._rec(
                TreeOpKind.MOVE_SOLO, node=self._id(op["id"]),
                parent=self._id(op["parent"]),
                after=self._id(op["after"]) if op.get("after") else 0,
                field=self._field(op["field"])))
        elif kind == "setValue":
            out.append(self._rec(TreeOpKind.SET_SOLO,
                                 node=self._id(op["id"]),
                                 value=self._vh(op["value"])))
        elif kind == "transaction":
            cons = [c["nodeExists"] for c in op.get("constraints", ())
                    if "nodeExists" in c]
            if cons:
                # the first constraint rides the begin record (fused
                # reset+guard — one record less per transaction)
                out.append(self._rec(TreeOpKind.TXN_BEGIN_EXISTS,
                                     node=self._id(cons[0])))
                for cn in cons[1:]:
                    out.append(self._rec(TreeOpKind.TXN_GUARD_EXISTS,
                                         node=self._id(cn)))
            else:
                out.append(self._rec(TreeOpKind.TXN_BEGIN))
            # each edit is flag-gated (ok_txn holds the constraint gate);
            # ok_ins is re-reset (INS_BEGIN) only when a previous edit's
            # guards may have dirtied it — edits are independent
            dirty = False
            for sub in op["edits"]:
                dirty = self._emit_txn_edit(sub, out, dirty)
        else:
            raise ValueError(f"unknown tree op {kind!r}")
        return out

    def _emit_txn_edit(self, op: dict, out: list, dirty: bool) -> bool:
        kind = op["op"]
        if kind == "insert":
            guarded = len(op["nodes"]) > 1
            if dirty:
                out.append(self._rec(TreeOpKind.INS_BEGIN))
            if guarded:
                for spec in op["nodes"]:
                    out.append(self._rec(TreeOpKind.INS_GUARD_ABSENT,
                                         node=self._id(spec["id"])))
            self._emit_specs(op, out, solo=False)
            return guarded
        if dirty:
            out.append(self._rec(TreeOpKind.INS_BEGIN))
        if kind == "remove":
            out.append(self._rec(TreeOpKind.REMOVE,
                                 node=self._id(op["id"])))
        elif kind == "move":
            out.append(self._rec(
                TreeOpKind.MOVE, node=self._id(op["id"]),
                parent=self._id(op["parent"]),
                after=self._id(op["after"]) if op.get("after") else 0,
                field=self._field(op["field"])))
        elif kind == "setValue":
            out.append(self._rec(TreeOpKind.SET_VALUE,
                                 node=self._id(op["id"]),
                                 value=self._vh(op["value"])))
        else:
            # nested transactions cannot share the single ok_txn gate;
            # the serving engine rejects them at ingress (_valid_edit)
            # and the client API cannot produce them ("transactions do
            # not nest" — models/shared_tree.py)
            raise ValueError(f"unsupported edit inside transaction: "
                             f"{kind!r}")
        return False


def _pow2_at_least(n: int, floor: int = 1) -> int:
    o = floor
    while o < n:
        o *= 2
    return o


def pack_wire_records(recs_k: np.ndarray, rec_op_k: np.ndarray,
                      rows_r: np.ndarray, r_floor: int = 256,
                      id_t=np.uint16, val_t=np.uint16):
    """Width-coded wire buffers for kept records — THE upload layout of
    ``tree_kernel.apply_tree_wire`` (cols: kind|meta<<4 + first-of-op
    bit, field, type; u16/u32 local ids/values; u16 row + u8/u16 pos
    with the ``pos == o`` drop sentinel; records pow2-padded to
    ``r_floor`` buckets). Every call allocates its own buffers: once
    one has been handed to ``jnp.asarray`` nothing may write to it (the
    CPU backend takes an aligned numpy array without a copy and runs
    asynchronously; on a TPU the transfer may still be in flight).
    Returns (cols, ids, vals, row, pos, o), or None when the widest doc
    exceeds the u16 pos budget.

    ``id_t``/``val_t``: dtype of the id/value index lanes — u16 by
    default, widened to u32 by the caller when a batch's id or value
    table outgrows 65534 entries (big general waves; still a fraction
    of the dense planes' bytes)."""
    r = len(recs_k)
    pos, widest = positions_in_doc(rows_r)
    o = _pow2_at_least(max(widest, 1))
    if o > 0xFFFF:
        return None
    rb = _pow2_at_least(max(r, 1), floor=r_floor)
    pos_t = np.uint8 if o <= 128 else np.uint16
    cols = np.zeros((rb, 3), np.uint8)
    idsb = np.zeros((rb, 3), id_t)
    valsb = np.zeros(rb, val_t)
    rowb = np.zeros(rb, np.uint16)
    posb = np.full(rb, o, pos_t)   # padding records drop
    if r:
        first = np.empty(r, np.uint8)
        first[0] = 1
        first[1:] = rec_op_k[1:] != rec_op_k[:-1]
        cols[:r, 0] = recs_k[:, 0] | \
            ((recs_k[:, 7] | (first << 1)) << 4)
        cols[:r, 1] = recs_k[:, 4]
        cols[:r, 2] = recs_k[:, 6]
        idsb[:r] = recs_k[:, 1:4]
        valsb[:r] = recs_k[:, 5]
        rowb[:r] = rows_r
        posb[:r] = pos
    return cols, idsb, valsb, rowb, posb, o


def positions_in_doc(rows: np.ndarray):
    """Per-record position among its doc's records (flat order preserved
    per doc); returns (pos, widest_doc_count)."""
    order = np.argsort(rows, kind="stable")
    r_sorted = rows[order]
    starts = np.r_[0, np.flatnonzero(np.diff(r_sorted)) + 1]
    sizes = np.diff(np.r_[starts, len(r_sorted)])
    pos_sorted = np.arange(len(r_sorted)) - np.repeat(starts, sizes)
    pos = np.empty_like(pos_sorted)
    pos[order] = pos_sorted
    return pos, (int(sizes.max()) if len(sizes) else 0)


class PrepackedWire:
    """One tree record wave's wire buffers + interner table maps, packed
    AHEAD of sequencing on the pipeline's pack worker. Every record is
    packed (nacks resolve at dispatch, which discards the prepack on
    the rare nacked wave and repacks inline). The buffers belong to
    this wave alone and are dropped with it: nothing may write to a
    host buffer after it has been handed to ``jnp.asarray``."""

    __slots__ = ("cols", "idsb", "valsb", "rowb", "posb", "o",
                 "id_map", "f_map", "t_map", "v_map")


class TensorTreeStore:
    def __init__(self, n_docs: int, capacity: int = 256, mesh=None):
        """``mesh``: a 1-D ``docs`` device mesh shards the planes by doc
        row; the packed-plane apply runs as a collective-free shard_map
        of the same record scan (tree merge is per-doc math)."""
        self.n_docs = n_docs
        self.capacity = capacity
        self.mesh = mesh
        self.state = TreeState.create(n_docs, capacity)
        if mesh is not None:
            from ..parallel.sharded import shard_tree_store_state
            self.state = shard_tree_store_state(self.state, mesh)
        self._ids = _Interner(reserved=(ROOT,))      # handle 1 == ROOT
        assert self._ids.handle(ROOT) == ROOT_HANDLE
        self._fields = _Interner()
        self._types = _Interner()
        self._values = ValueInterner()

    # --------------------------------------------------------- capacity plane

    def capacity_stats(self) -> dict:
        """Capacity-plane report fragment (ISSUE 19): interner tables
        host-side, tree planes device-side."""
        from ..utils import capacity as _cap
        host = 0
        for it in (self._ids, self._fields, self._types):
            # names list + ids dict; ~24 chars/name payload average
            host += _cap.interner_nbytes(len(it._names),
                                         73 * len(it._names))
        host += _cap.interner_nbytes(len(self._values),
                                     80 * len(self._values))
        return {"host": {"interner": int(host)},
                "device": {"state": _cap.device_nbytes(self.state)}}

    # ----------------------------------------------------------- translation

    @property
    def emitter(self) -> RecordEmitter:
        return RecordEmitter(self._ids.handle, self._fields.handle,
                             self._values.handle, self._types.handle)

    def _records_for(self, msg) -> list:
        """Expanded device records for one sequenced tree message."""
        return self.emitter.emit_op(msg.contents)

    # ----------------------------------------------------------------- apply

    def _apply_planes(self, planes: np.ndarray) -> None:
        """Dispatch a packed (9, D, O) record-plane batch (plane order:
        kind, node, parent, after, field, value, type_, meta, seq) as ONE
        contiguous host→device transfer. On a mesh the SAME scan runs as
        a collective-free shard_map over each chip's doc block."""
        if self.mesh is not None:
            from ..parallel.sharded import sharded_tree_apply
            self.state = sharded_tree_apply(self.mesh)(
                self.state, jnp.asarray(planes))
            return
        self.state = apply_tree_planes_jit(self.state, jnp.asarray(planes))

    def pack_records(self, rows: np.ndarray, recs: np.ndarray,
                     seqs: np.ndarray) -> np.ndarray:
        """Scatter flat records into dense (9, D, O) planes. ``rows`` is
        each record's doc row; per-doc record ORDER is flat order (the
        sequencer's total order); O is the pow2 bucket of the widest doc
        (bounds recompiles)."""
        pos, widest = positions_in_doc(rows)
        o = _pow2_at_least(max(widest, 1))
        planes = np.zeros((9, self.n_docs, o), np.int32)
        for p in range(8):
            planes[p, rows, pos] = recs[:, p]
        planes[8, rows, pos] = seqs
        return planes

    def apply_wire(self, cols, ids, vals, row, pos, base, id_map, f_map,
                   t_map, v_map, o: int) -> None:
        """Dispatch one compact-wire batch (see tree_kernel
        ``apply_tree_wire`` for the buffer contract)."""
        self.state = apply_tree_wire_jit(
            self.state, jnp.asarray(cols), jnp.asarray(ids),
            jnp.asarray(vals), jnp.asarray(row), jnp.asarray(pos),
            jnp.asarray(base), jnp.asarray(id_map), jnp.asarray(f_map),
            jnp.asarray(t_map), jnp.asarray(v_map), o=o)

    # ------------------------------------------------------- prepacked wire

    @staticmethod
    def _pad_map(items, interner) -> np.ndarray:
        """Pow2-padded local-index → interner-handle map (handle 0 ==
        none)."""
        m = np.zeros(_pow2_at_least(len(items) + 1, floor=8), np.int32)
        if items:
            m[1:len(items) + 1] = interner.bulk(items)
        return m

    def prepack_wire(self, recs: np.ndarray, rec_op: np.ndarray,
                     rows_r: np.ndarray, tables: dict,
                     r_floor: int = 256) -> Optional[PrepackedWire]:
        """Pack ALL of a wave's records + interner maps into pow2 wire
        buffers of its own ahead of sequencing (the pipeline's pack
        worker; the ``ops/string_store.prepack_planes`` analog).
        Returns None when the widest doc overflows the u16 pos budget
        (the dense path must take the wave). The id/value index lanes
        widen to u32 when a table outgrows the u16 budget — big general
        waves (one fresh node id per op) stay on the wire instead of
        falling to dense planes."""
        packed = pack_wire_records(
            recs, rec_op, rows_r, r_floor=r_floor,
            id_t=np.uint16 if len(tables["ids"]) < 0xFFFF else np.uint32,
            val_t=(np.uint16 if len(tables["values"]) < 0xFFFF
                   else np.uint32))
        if packed is None:
            return None
        pp = PrepackedWire()
        pp.cols, pp.idsb, pp.valsb, pp.rowb, pp.posb, pp.o = packed
        pp.id_map = self._pad_map(tables["ids"], self._ids)
        pp.f_map = self._pad_map(tables["fields"], self._fields)
        pp.t_map = self._pad_map(tables["types"], self._types)
        pp.v_map = self._pad_map(tables["values"], self._values)
        return pp

    def apply_wire_prepacked(self, pp: PrepackedWire,
                             base: np.ndarray) -> None:
        """Dispatch a prepacked wave (``base`` arrives
        post-sequencing)."""
        self.apply_wire(pp.cols, pp.idsb, pp.valsb, pp.rowb, pp.posb,
                        base, pp.id_map, pp.f_map, pp.t_map, pp.v_map,
                        pp.o)

    def apply_records(self, rows: np.ndarray, recs: np.ndarray,
                      seqs: np.ndarray) -> None:
        """Apply flat (R, 8) record tuples with per-record doc rows and
        seqs — the raw path shared by columnar ingest, recovery replay,
        and the message path below."""
        if len(recs) == 0:
            return
        self._apply_planes(self.pack_records(
            np.asarray(rows, np.int64), np.asarray(recs, np.int32),
            np.asarray(seqs, np.int64)))

    def apply_messages(self, messages) -> None:
        rows: list = []
        recs_all: list = []
        seqs: list = []
        for doc, msg in messages:
            recs = self._records_for(msg)
            recs_all.extend(recs)
            rows.extend([doc] * len(recs))
            seqs.extend([msg.seq] * len(recs))
        if not recs_all:
            return
        self.apply_records(np.asarray(rows, np.int64),
                           np.array(recs_all, np.int32),
                           np.asarray(seqs, np.int64))


    # ----------------------------------------------------------------- reads

    def _pull(self, doc: int) -> dict:
        st = self.state
        return {k: np.asarray(getattr(st, k)[doc]) for k in _TREE_PLANES}

    def to_dict(self, doc: int) -> dict:
        """The oracle's ``to_dict`` shape, rebuilt from the planes."""
        p = self._pull(doc)
        live = p["node_id"] != 0
        by_id = {int(p["node_id"][i]): i for i in range(self.capacity)
                 if live[i]}

        def node_dict(nid: int) -> dict:
            i = by_id[nid]
            out = {"id": self._ids.name(nid),
                   "type": self._types.name(int(p["type_"][i]))
                   if p["type_"][i] else None,
                   "value": self._values.value(int(p["value"][i]))
                   if p["value"][i] else None}
            # group children by field, ordered by the linked list
            fields: Dict[int, list] = {}
            for j in range(self.capacity):
                if live[j] and int(p["parent"][j]) == nid:
                    fields.setdefault(int(p["field"][j]), []).append(j)
            children = {}
            for fh, slots in fields.items():
                ordered = self._chain_order(p, slots)
                children[self._fields.name(fh)] = [
                    node_dict(int(p["node_id"][j])) for j in ordered]
            if children:
                out["children"] = dict(sorted(children.items()))
            return out

        return node_dict(ROOT_HANDLE)

    def _chain_order(self, p, slots: list) -> list:
        """Order sibling slots by their prev/next chain (head: prev == 0)."""
        by_id = {int(p["node_id"][j]): j for j in slots}
        head = [j for j in slots if int(p["prev_sib"][j]) == 0]
        assert len(head) == 1, "broken sibling chain"
        order = [head[0]]
        while True:
            nxt = int(p["next_sib"][order[-1]])
            if nxt == 0:
                break
            order.append(by_id[nxt])
        assert len(order) == len(slots), "sibling chain mismatch"
        return order

    def node_value(self, doc: int, node_id: str):
        p = self._pull(doc)
        nh = self._ids.peek(node_id)
        if nh is None:
            raise KeyError(node_id)
        sel = p["node_id"] == nh
        if not sel.any():
            raise KeyError(node_id)
        return self._values.value(int(p["value"][sel][0])) \
            if p["value"][sel][0] else None

    def has_node(self, doc: int, node_id: str) -> bool:
        nh = self._ids.peek(node_id)
        if nh is None:
            return False
        return bool((self._pull(doc)["node_id"] == nh).any())

    def node_count(self, doc: int) -> int:
        return int((np.asarray(self.state.node_id[doc]) != 0).sum())

    def overflowed(self) -> np.ndarray:
        return np.asarray(self.state.overflow)

    # -------------------------------------------------- overflow recovery ops
    # (the serving engine's escape hatch — mirrors TensorStringStore's
    # clear_doc/adopt_doc so tree recovery stays the same shape)

    def share_interners(self, other: "TensorTreeStore") -> None:
        """Alias ``other``'s interner tables (append-only) so handles in
        this store mean the same strings/values as in ``other`` — the
        precondition for ``other.adopt_doc`` copying our planes verbatim."""
        self._ids = other._ids
        self._fields = other._fields
        self._types = other._types
        self._values = other._values

    def clear_doc(self, row: int) -> None:
        """Reset one row to the empty tree (root only, overflow cleared)."""
        st = self.state
        fresh = TreeState.create(1, self.capacity)
        self.state = dataclasses.replace(
            st,
            **{k: getattr(st, k).at[row].set(getattr(fresh, k)[0])
               for k in _TREE_PLANES},
            overflow=st.overflow.at[row].set(0))

    def high_water(self, doc: int = 0) -> int:
        """1 + highest live slot index (root counts), for fit checks."""
        live = np.asarray(self.state.node_id[doc]) != 0
        return int(np.max(np.nonzero(live)[0])) + 1 if live.any() else 0

    def repack(self, doc: int = 0) -> None:
        """Compact a doc's live slots to the lowest indices. Slot position
        carries NO meaning in this representation (order/attachment are id
        handles — tree_kernel module docstring), so this is a pure
        permutation; it exists so a rebuilt doc whose history churned
        through many slots fits back into a small tier."""
        st = self.state
        p = {k: np.asarray(getattr(st, k)[doc]) for k in _TREE_PLANES}
        live = np.nonzero(p["node_id"] != 0)[0]
        updates = {}
        for k in _TREE_PLANES:
            row = np.zeros((self.capacity,), np.int32)
            row[:len(live)] = p[k][live]
            updates[k] = getattr(st, k).at[doc].set(jnp.asarray(row))
        self.state = dataclasses.replace(st, **updates)

    def adopt_doc(self, row: int, tmp: "TensorTreeStore") -> None:
        """Upload single-doc store ``tmp`` (which MUST share this store's
        interners — see ``share_interners``) into ``row``. Caller checks
        ``tmp.high_water() <= self.capacity`` first."""
        hw = tmp.high_water()
        assert hw <= self.capacity, "doc does not fit this tier"
        st = self.state
        updates = {}
        for k in _TREE_PLANES:
            src = np.zeros((self.capacity,), np.int32)
            src[:hw] = np.asarray(getattr(tmp.state, k)[0, :hw])
            updates[k] = getattr(st, k).at[row].set(jnp.asarray(src))
        self.state = dataclasses.replace(
            st, **updates, overflow=st.overflow.at[row].set(0))

    def digests(self) -> np.ndarray:
        return np.asarray(tree_state_digest(self.state))

    # ----------------------------------------------------- snapshot / resume

    def snapshot(self) -> dict:
        st = self.state
        return {
            "planes": {k: np.asarray(getattr(st, k)).copy()
                       for k in _TREE_PLANES},
            "overflow": np.asarray(st.overflow).copy(),
            "capacity": self.capacity,
            "ids": self._ids.export(),
            "fields": self._fields.export(),
            "types": self._types.export(),
            "values": self._values.export(),
        }

    def interner_bases(self) -> dict:
        """Append-only table lengths (incremental-summary baselines)."""
        return {"ids": len(self._ids), "fields": len(self._fields),
                "types": len(self._types), "values": len(self._values)}

    def snapshot_rows(self, rows, bases: dict) -> dict:
        """Incremental snapshot: only the given doc rows' planes (one
        fused device→host gather) plus the append-only interner DELTAS
        since the base summary (``bases`` = ``interner_bases()`` recorded
        then). Clean rows ride by reference to the base (SURVEY.md
        §2.16 handle reuse)."""
        from .schema import pad_rows_pow2
        rows = np.ascontiguousarray(rows, np.int32)
        if len(rows):
            rows_p, _p2, n = pad_rows_pow2(rows)
            g = gather_tree_rows_jit(self.state, jnp.asarray(rows_p))
            planes = {k: np.asarray(g[i])[:n].copy()
                      for i, k in enumerate(_TREE_PLANES)}
            overflow = np.asarray(g[-1])[:n].copy()
        else:
            planes = {k: np.zeros((0, self.capacity), np.int32)
                      for k in _TREE_PLANES}
            overflow = np.zeros((0,), np.int32)
        return {
            "rows": rows, "planes": planes, "overflow": overflow,
            "ids_delta": self._ids.export_from(bases["ids"]),
            "next_anon": self._ids._next_anon,
            "fields_delta": self._fields.export_from(bases["fields"]),
            "types_delta": self._types.export_from(bases["types"]),
            "values_delta": self._values.export_from(bases["values"]),
        }

    def apply_row_snapshot(self, delta: dict) -> None:
        """Fold one ``snapshot_rows`` delta into this (restored-base)
        store: overwrite the dirty rows' planes in one scatter, extend
        the append-only interner tables."""
        self._ids.extend_from(delta["ids_delta"])
        self._ids._next_anon = max(self._ids._next_anon,
                                   delta["next_anon"])
        self._fields.extend_from(delta["fields_delta"])
        self._types.extend_from(delta["types_delta"])
        self._values.extend_from(delta["values_delta"])
        from .schema import bucket_rows, pad_rows_pow2
        rows = np.asarray(delta["rows"], np.int32)
        if not len(rows):
            return
        rows_p, p2, n = pad_rows_pow2(rows)

        def bucket(a):
            return jnp.asarray(bucket_rows(a, p2, n))

        self.state = write_tree_rows_jit(
            self.state, jnp.asarray(rows_p),
            *(bucket(delta["planes"][k]) for k in _TREE_PLANES),
            bucket(delta["overflow"]))

    @classmethod
    def restore(cls, snap: dict, mesh=None) -> "TensorTreeStore":
        n_docs = snap["overflow"].shape[0]
        store = cls.__new__(cls)
        store.n_docs = n_docs
        store.capacity = snap["capacity"]
        store.mesh = mesh
        store.state = TreeState(
            **{k: jnp.asarray(snap["planes"][k]) for k in _TREE_PLANES},
            overflow=jnp.asarray(snap["overflow"]))
        if mesh is not None:
            from ..parallel.sharded import shard_tree_store_state
            store.state = shard_tree_store_state(store.state, mesh)
        store._ids = _Interner.restore(snap["ids"])
        store._fields = _Interner.restore(snap["fields"])
        store._types = _Interner.restore(snap["types"])
        store._values = ValueInterner.restore(snap["values"])
        return store
