"""Batched SharedMap op-apply kernel: the first end-to-end device slice.

Reference counterpart: the ``MapKernel.tryProcessMessage`` inner loop of
``@fluidframework/map`` (SURVEY.md §2.3) — but where the reference applies one
JSON op at a time per JS object, this kernel applies a (doc × op) batch of
packed records for thousands of documents in one jit'd call (SURVEY.md §7.3:
"the minimum slice").

Layout
------
State per document: ``K`` dense key slots (host interns string keys → slot
ids per doc). Three (D, K) int32 planes:

    present  — 1 if the key currently has a value
    value    — payload handle (host side table holds the actual JSON value)
    last_seq — seq of the write that set it (debug/digest/FWW-style queries)

Op batch: (D, O) planes (kind/a0/a1/seq) — the sequencer lays ops out densely
per doc, padding with NOOP. Total order within a doc = ascending op index.

Because map semantics are last-writer-wins with ``clear`` barriers, a whole
batch collapses without a sequential scan: for each (doc, key) the result
depends only on the LAST relevant op after the LAST clear — a pure reduction
over the op axis (max-index tricks), which vectorizes perfectly on the VPU.
No data-dependent control flow, fully static shapes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .schema import OpKind, ValueInterner


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MapState:
    """Device-resident state for D documents × K key slots."""

    present: jax.Array   # (D, K) int32 0/1
    value: jax.Array     # (D, K) int32 payload handle
    last_seq: jax.Array  # (D, K) int32

    @staticmethod
    def create(n_docs: int, n_keys: int) -> "MapState":
        # three distinct buffers: the apply step donates its input state, and
        # XLA rejects donating one aliased buffer for multiple arguments
        z = lambda: jnp.zeros((n_docs, n_keys), dtype=jnp.int32)
        return MapState(present=z(), value=z(), last_seq=z())


def apply_map_batch(state: MapState, kind: jax.Array, a0: jax.Array,
                    a1: jax.Array, seq: jax.Array) -> MapState:
    """Apply a dense (D, O) batch of sequenced map ops.

    kind/a0/a1/seq: (D, O) int32 — OpKind, key slot, value handle, seq.
    Pure reduction over the op axis; jit/vmap/shard_map-friendly.
    """
    n_keys = state.present.shape[1]
    o_idx = jnp.arange(kind.shape[1], dtype=jnp.int32)          # (O,)

    is_clear = kind == OpKind.MAP_CLEAR
    is_set = kind == OpKind.MAP_SET
    is_del = kind == OpKind.MAP_DELETE

    # index of the last clear per doc (-1 if none)
    last_clear = jnp.max(jnp.where(is_clear, o_idx[None, :], -1), axis=1)  # (D,)

    # last relevant key-op per (doc, key): max op index among set/delete ops
    # targeting that key after the last clear
    key_onehot = a0[:, :, None] == jnp.arange(n_keys)[None, None, :]  # (D,O,K)
    relevant = (is_set | is_del) & (o_idx[None, :] > last_clear[:, None])
    cand = jnp.where(relevant[:, :, None] & key_onehot, o_idx[None, :, None], -1)
    last_op = jnp.max(cand, axis=1)                              # (D, K)

    had_clear = last_clear >= 0                                   # (D,)
    touched = last_op >= 0                                        # (D, K)

    safe_idx = jnp.maximum(last_op, 0)
    g = lambda plane: jnp.take_along_axis(plane, safe_idx, axis=1)
    op_is_set = g(kind) == OpKind.MAP_SET                         # (D, K)
    op_value = g(a1)
    op_seq = g(seq)

    base_present = jnp.where(had_clear[:, None], 0, state.present)
    base_value = jnp.where(had_clear[:, None], 0, state.value)
    base_seq = jnp.where(had_clear[:, None], 0, state.last_seq)

    present = jnp.where(touched, op_is_set.astype(jnp.int32), base_present)
    value = jnp.where(touched & op_is_set, op_value, base_value)
    last_seq = jnp.where(touched, jnp.where(op_is_set, op_seq, 0), base_seq)
    return MapState(present=present, value=value, last_seq=last_seq)


apply_map_batch_jit = jax.jit(apply_map_batch, donate_argnums=0)


@jax.jit
def _gather_map_rows_jit(state: "MapState", rows):
    """Fused device gather of selected doc rows (incremental summary)."""
    return (state.present[rows], state.value[rows], state.last_seq[rows])


@functools.partial(jax.jit, donate_argnums=0)
def _write_map_rows_jit(state: "MapState", rows, present, value, last_seq):
    """Overwrite selected doc rows (delta restore; duplicate padding rows
    scatter identical values — a no-op)."""
    return MapState(present=state.present.at[rows].set(present),
                    value=state.value.at[rows].set(value),
                    last_seq=state.last_seq.at[rows].set(last_seq))


@functools.partial(jax.jit,
                   static_argnames=("R", "O", "n_docs", "scatter_rows",
                                    "wide_vals"))
def map_columnar_unpack_jit(buf, R, O, n_docs, scatter_rows, wide_vals):
    """Unpack half of ``map_columnar_apply_jit`` (used standalone when
    the merge runs as a separate sharded program)."""
    return _map_unpack(buf, R, O, n_docs, scatter_rows, wide_vals)


@functools.partial(jax.jit, donate_argnums=0,
                   static_argnames=("R", "O", "n_docs", "scatter_rows",
                                    "wide_vals"))
def map_columnar_apply_jit(state, buf, R, O, n_docs, scatter_rows,
                           wide_vals):
    """Fused unpack + apply of ONE byte-packed columnar map batch: the
    host ships [kind u8 | key-slot u8 | value-handle u16/i32 | per-row
    seq bases i32 | row indices i32] as a single int32-word buffer
    (~4-7 B/op — each host→device transfer pays a fixed per-transfer
    overhead, so the whole batch rides one copy; see the string store's
    ``_columnar_unpack_jit``). Per-op seqs rebuild on device from each
    row's base (nacked slots are NOOP and consumed no seq); map merge is
    the closed-form reduction of ``apply_map_batch``."""
    return apply_map_batch(
        state, *_map_unpack(buf, R, O, n_docs, scatter_rows, wide_vals))


def _map_unpack(buf, R, O, n_docs, scatter_rows, wide_vals):
    N = R * O

    def take_u8(off, n):
        w = -(-n // 4)
        words = jax.lax.slice_in_dim(buf, off, off + w, axis=0)
        v = jnp.stack([words & 0xFF, (words >> 8) & 0xFF,
                       (words >> 16) & 0xFF, (words >> 24) & 0xFF],
                      axis=1).reshape(4 * w)[:n]
        return v, off + w

    def take_u16(off, n):
        w = -(-n // 2)
        words = jax.lax.slice_in_dim(buf, off, off + w, axis=0)
        v = jnp.stack([words & 0xFFFF, (words >> 16) & 0xFFFF],
                      axis=1).reshape(2 * w)[:n]
        return v, off + w

    def take_i32(off, n):
        return jax.lax.slice_in_dim(buf, off, off + n, axis=0), off + n

    kind, off = take_u8(0, N)
    a0, off = take_u8(off, N)
    a1, off = (take_i32 if wide_vals else take_u16)(off, N)
    base, off = take_i32(off, R)
    rows, off = take_i32(off, R)

    kind = kind.reshape(R, O)
    a0 = a0.reshape(R, O)
    a1 = a1.reshape(R, O)
    valid = kind != int(OpKind.NOOP)
    seq = base[:, None] + jnp.cumsum(valid.astype(jnp.int32), axis=1)
    planes = (kind, a0, a1, seq)
    if scatter_rows:
        def full(p, fill):
            return jnp.full((n_docs, O), fill, jnp.int32).at[rows].set(p)

        planes = (full(kind, int(OpKind.NOOP)), full(a0, 0), full(a1, 0),
                  full(seq, 0))
    return planes


def map_state_digest(state: MapState) -> jax.Array:
    """Per-doc digest of converged state for cross-replica checks (the
    race-detection analog, SURVEY.md §5.2)."""
    k = jnp.arange(state.present.shape[1], dtype=jnp.int32)
    mix = state.present * (k[None, :] * 1103515245 + 12345) \
        + state.value * 40503 + state.last_seq
    return jnp.sum(jnp.where(state.present > 0, mix, 0), axis=1)


class TensorMapStore:
    """Host facade: many SharedMap documents resident on device.

    Interns string keys / JSON values into int32 handles, packs sequenced ops
    into dense (D, O) batches, applies them in one jit'd call, and reads back
    per-doc dicts. This is the serving-side merge engine; interactive
    optimistic editing stays in ``models.SharedMap`` (host).
    """

    def __init__(self, n_docs: int, n_keys: int = 64, mesh=None):
        self.n_docs = n_docs
        self.n_keys = n_keys
        # multi-chip: a 1-D "docs" mesh shards the planes by doc row; the
        # map merge is a per-doc closed-form reduction, so the sharded
        # apply is a collective-free shard_map of the same kernel
        self.mesh = mesh
        if mesh is not None and n_docs % mesh.devices.size != 0:
            raise ValueError(f"n_docs {n_docs} not divisible by mesh size "
                             f"{mesh.devices.size}")
        self.state = MapState.create(n_docs, n_keys)
        if mesh is not None:
            from ..parallel.sharded import shard_map_store_state
            self.state = shard_map_store_state(self.state, mesh)
        self._key_ids: List[Dict[str, int]] = [dict() for _ in range(n_docs)]
        self._interner = ValueInterner()

    # --------------------------------------------------------- capacity plane

    def capacity_stats(self) -> dict:
        """Capacity-plane report fragment (ISSUE 19)."""
        from ..utils import capacity as _cap
        host = _cap.list_nbytes(self.n_docs)
        for ids in self._key_ids:
            host += _cap.dict_nbytes(len(ids),
                                     _cap.INT_DICT_ENTRY_BYTES)
        host += _cap.interner_nbytes(len(self._interner),
                                     80 * len(self._interner))
        return {"host": {"interner": int(host)},
                "device": {"state": _cap.device_nbytes(self.state)}}

    # ------------------------------------------------------------- interning

    def key_slot(self, doc: int, key: str) -> int:
        ids = self._key_ids[doc]
        if key not in ids:
            if len(ids) >= self.n_keys:
                raise KeyError(f"doc {doc}: key capacity {self.n_keys} exhausted")
            ids[key] = len(ids)
        return ids[key]

    def value_handle(self, value) -> int:
        return self._interner.handle(value)

    # ----------------------------------------------------------------- apply

    def apply_batch(self, records) -> None:
        """records: iterable of (doc, kind, key, value, seq) with key=str,
        value=JSON for sets (None otherwise). Sequenced (seq ascending)."""
        per_doc: Dict[int, list] = {}
        for doc, kind, key, value, seq in records:
            slot = self.key_slot(doc, key) if key is not None else 0
            handle = self.value_handle(value) if kind == OpKind.MAP_SET else 0
            per_doc.setdefault(doc, []).append((int(kind), slot, handle, seq))
        if not per_doc:
            return
        # pad the op axis to a power-of-two bucket: a fresh (D, O) shape per
        # call would retrigger XLA compilation on nearly every batch
        widest = max(len(v) for v in per_doc.values())
        o = 8
        while o < widest:
            o *= 2
        kind = np.full((self.n_docs, o), int(OpKind.NOOP), dtype=np.int32)
        a0 = np.zeros((self.n_docs, o), dtype=np.int32)
        a1 = np.zeros((self.n_docs, o), dtype=np.int32)
        seq = np.zeros((self.n_docs, o), dtype=np.int32)
        for doc, ops in per_doc.items():
            for j, (k_, s_, h_, q_) in enumerate(ops):
                kind[doc, j] = k_
                a0[doc, j] = s_
                a1[doc, j] = h_
                seq[doc, j] = q_
        self.state = apply_map_batch_jit(
            self.state, jnp.asarray(kind), jnp.asarray(a0), jnp.asarray(a1),
            jnp.asarray(seq))

    # ----------------------------------------------------------------- reads

    def read_doc(self, doc: int) -> dict:
        present = np.asarray(self.state.present[doc])
        value = np.asarray(self.state.value[doc])
        out = {}
        for key, slot in self._key_ids[doc].items():
            if present[slot]:
                out[key] = self._interner.value(value[slot])
        return out

    def digests(self) -> np.ndarray:
        return np.asarray(map_state_digest(self.state))

    # ----------------------------------------------------- snapshot / resume

    def snapshot(self) -> dict:
        """Device→host gather + host interning tables (channel summarize();
        resume = ``restore`` + tail replay through ``apply_batch``)."""
        return {
            "present": np.asarray(self.state.present).copy(),
            "value": np.asarray(self.state.value).copy(),
            "last_seq": np.asarray(self.state.last_seq).copy(),
            "n_keys": self.n_keys,
            "key_ids": [dict(m) for m in self._key_ids],
            "values": self._interner.export(),
        }

    def snapshot_rows(self, rows, values_base: int) -> dict:
        """Incremental snapshot: only the given doc rows' planes (one
        fused device→host gather) plus the append-only value-interner
        DELTA since the base summary (``values_base`` = its table
        length). Clean rows ride by reference to the base (SURVEY.md
        §2.16 handle reuse)."""
        from .schema import pad_rows_pow2
        rows = np.ascontiguousarray(rows, np.int32)
        if len(rows):
            rows_p, _p2, n = pad_rows_pow2(rows)
            g = _gather_map_rows_jit(self.state, jnp.asarray(rows_p))
            present, value, last_seq = (np.asarray(x)[:n].copy()
                                        for x in g)
        else:
            present = value = last_seq = np.zeros((0, self.n_keys),
                                                  np.int32)
        return {
            "rows": rows,
            "present": present, "value": value, "last_seq": last_seq,
            "key_ids": {int(r): dict(self._key_ids[int(r)])
                        for r in rows},
            "values_delta": self._interner.export_from(values_base),
        }

    def apply_row_snapshot(self, delta: dict) -> None:
        """Fold one ``snapshot_rows`` delta into this (restored-base)
        store: overwrite the dirty rows' planes in one scatter, extend
        the append-only value table, replace the rows' key maps."""
        self._interner.extend_from(delta["values_delta"])
        rows = np.asarray(delta["rows"], np.int32)
        if not len(rows):
            return
        from .schema import bucket_rows, pad_rows_pow2
        for r, m in delta["key_ids"].items():
            self._key_ids[int(r)] = dict(m)
        rows_p, p2, n = pad_rows_pow2(rows)

        def bucket(a):
            return jnp.asarray(bucket_rows(a, p2, n))

        self.state = _write_map_rows_jit(
            self.state, jnp.asarray(rows_p), bucket(delta["present"]),
            bucket(delta["value"]), bucket(delta["last_seq"]))
        if self.mesh is not None:
            from ..parallel.sharded import shard_map_store_state
            self.state = shard_map_store_state(self.state, self.mesh)

    @classmethod
    def restore(cls, snap: dict, mesh=None) -> "TensorMapStore":
        store = cls.__new__(cls)
        store.n_docs = snap["present"].shape[0]
        store.n_keys = snap["n_keys"]
        store.mesh = mesh
        store.state = MapState(
            present=jnp.asarray(snap["present"]),
            value=jnp.asarray(snap["value"]),
            last_seq=jnp.asarray(snap["last_seq"]))
        if mesh is not None:
            from ..parallel.sharded import shard_map_store_state
            store.state = shard_map_store_state(store.state, mesh)
        store._key_ids = [dict(m) for m in snap["key_ids"]]
        store._interner = ValueInterner.restore(snap["values"])
        return store
