"""Host facade for the batched merge-tree kernel: many SharedString documents
resident on device.

This is the serving/replica-side merge engine of the north star (sequenced
ops only); interactive optimistic editing remains in ``models.SharedString``.
The store interns variable-length payloads (text runs, markers) into an int32
handle table — the device does ordering/position math, never string bytes
(SURVEY.md §7.2) — and maps client ids to per-doc indexes for the remover
bitmask.
"""

from __future__ import annotations

import dataclasses
import functools
import heapq
import json
import logging
import sys
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.constants import NOT_REMOVED
from ..utils import tracing
from ..utils.telemetry import REGISTRY
from .merge_tree_kernel import (
    MAX_CLIENTS, PROP_HANDLE_BITS, StringState, _PLANES, apply_string_batch,
    apply_string_batch_jit, compact_string_state_jit, string_state_digest,
)
from .pallas_string_kernel import apply_string_batch_pallas
from .schema import OpKind, ValueInterner

_TEXT = 0
_MARKER = 1

_log = logging.getLogger(__name__)

# ---------------------------------------------------------- dispatch metrics
# The merge-tree/Pallas kernels were a dark layer: dispatches and XLA
# (re)compiles were invisible outside per-store ad-hoc counters. Every
# device dispatch counts into the process registry; compile-cache
# accounting compares the summed jit-cache sizes of this module's entry
# points before/after — growth means the dispatch paid an XLA compile,
# no growth means it hit the compile cache.

_JIT_FN_NAMES = (
    "_write_rows_jit", "_gather_rows_jit", "_write_row_jit",
    "_visible_lengths_jit", "_gather_doc_jit", "_apply_pallas_jit",
    "_columnar_unpack_jit", "_columnar_merge_jit",
    "apply_string_batch_jit", "compact_string_state_jit",
)
_jit_cache_total = 0


def _note_dispatch(kind: str, dispatch_ms: Optional[float] = None) -> None:
    global _jit_cache_total
    REGISTRY.inc("device_dispatches")
    REGISTRY.inc(f"device_dispatches_{kind}")
    if dispatch_ms is not None:
        REGISTRY.observe("device_dispatch_ms", dispatch_ms)
    size = sum(globals()[name]._cache_size() for name in _JIT_FN_NAMES)
    # a store on a mesh dispatches parallel/sharded.py's programs instead
    # of the columnar pair above (the module is loaded only by such a store)
    sharded = sys.modules.get("fluidframework_tpu.parallel.sharded")
    if sharded is not None:
        size += sharded.jit_cache_size()
    if size > _jit_cache_total:
        REGISTRY.inc("jax_compiles", size - _jit_cache_total)
    else:
        REGISTRY.inc("jax_compile_cache_hits")
    # track shrinkage too (jax.clear_caches in tests resets the baseline)
    _jit_cache_total = size


@functools.partial(jax.jit, donate_argnums=0)
def _write_rows_jit(state, rows, seq, client, removed_seq, removers, length,
                    handle_op, handle_off, prop_val, count, overflow):
    """Batched overwrite of a subset of doc rows (incremental-summary
    restore): one scatter per plane, one dispatch total."""
    return StringState(
        seq=state.seq.at[rows].set(seq),
        client=state.client.at[rows].set(client),
        removed_seq=state.removed_seq.at[rows].set(removed_seq),
        removers=state.removers.at[rows].set(removers),
        length=state.length.at[rows].set(length),
        handle_op=state.handle_op.at[rows].set(handle_op),
        handle_off=state.handle_off.at[rows].set(handle_off),
        prop_val=state.prop_val.at[rows].set(prop_val),
        count=state.count.at[rows].set(count),
        overflow=state.overflow.at[rows].set(overflow),
    )


@jax.jit
def _gather_rows_jit(state, rows):
    """(plane subsets for a row list) in ONE device→host round-trip —
    the incremental-summary gather (dirty rows only)."""
    return (state.seq[rows], state.client[rows], state.removed_seq[rows],
            state.removers[rows], state.length[rows],
            state.handle_op[rows], state.handle_off[rows],
            state.prop_val[rows], state.count[rows], state.overflow[rows])


@functools.partial(jax.jit, donate_argnums=0)
def _write_row_jit(state, row, seq, client, removed_seq, removers, length,
                   handle_op, handle_off, prop_val, count):
    """Overwrite one doc row's planes in a single dispatch (overflow
    recovery re-upload); clears the row's sticky overflow flag."""
    return StringState(
        seq=state.seq.at[row].set(seq),
        client=state.client.at[row].set(client),
        removed_seq=state.removed_seq.at[row].set(removed_seq),
        removers=state.removers.at[row].set(removers),
        length=state.length.at[row].set(length),
        handle_op=state.handle_op.at[row].set(handle_op),
        handle_off=state.handle_off.at[row].set(handle_off),
        prop_val=state.prop_val.at[row].set(prop_val),
        count=state.count.at[row].set(count),
        overflow=state.overflow.at[row].set(0),
    )


@jax.jit
def _visible_lengths_jit(state):
    """(D,) visible length per doc — bulk read primitive."""
    S = state.seq.shape[1]
    active = jnp.arange(S)[None, :] < state.count[:, None]
    live = active & (state.removed_seq == NOT_REMOVED)
    return jnp.sum(jnp.where(live, state.length, 0), axis=1)


@jax.jit
def _gather_doc_jit(state, doc):
    """(6, S) stack of one doc's read planes + its slot count (row 5),
    so a read costs ONE device→host transfer."""
    return jnp.stack([
        state.removed_seq[doc], state.handle_op[doc], state.handle_off[doc],
        state.length[doc], state.seq[doc],
        jnp.full((state.seq.shape[1],), state.count[doc]),
    ])

# Pallas doc-axis tiles, widest first (T=128 measures fastest on v5e; smaller
# tiles let stores whose doc count is not 128-divisible still take the fused
# path). int32 sublane width is 8 — narrower tiles cannot compile.
_PALLAS_TILES = (128, 64, 32, 16, 8)

#: Scoped-VMEM model of the fused kernel: bytes per tile×slot (planes +
#: temporaries) against a budget under v5e's 16 MiB scoped limit. Checked
#: against Mosaic under libtpu 0.0.34 on a v5e (PR 21 chip probe: 64-op
#: and 1-op batches, fused zamboni on and off): every shape the model
#: admits compiles — T=128/S=384 no-props, T=64/S=512 and T=64/S=384 in
#: both modes. It is conservative for no-props (T=128/S=512 compiles
#: today, the model still halves it to 64) and right about props
#: (T=128/S=512 props is refused: "Scoped allocation with size 17.10M
#: and limit 16.00M", 17.99M with fused zamboni — 274 B per tile×slot).
#: Mosaic names a size only when it refuses; forcing a refusal with a
#: low vmem_limit_bytes makes it plan differently and under-reports.
_VMEM_BYTES_PER_TILE_SLOT = 300
_VMEM_BUDGET_BYTES = 15_500_000


def pallas_tile_for(n_docs: int, capacity: int) -> Optional[int]:
    """Widest VMEM tile serving this store shape, or None if the fused
    kernel cannot run it (doc count not tile-divisible, or slot capacity
    not lane-aligned)."""
    if capacity % 128 != 0:
        return None
    for t in _PALLAS_TILES:
        if n_docs % t == 0:
            return t
    return None


@functools.partial(jax.jit, donate_argnums=0,
                   static_argnames=("tile", "interpret", "with_props"))
def _apply_pallas_jit(state, kind, a0, a1, a2, seq, client, ref_seq,
                      tile, interpret, with_props=False):
    return apply_string_batch_pallas(state, kind, a0, a1, a2, seq, client,
                                     ref_seq, tile=tile, interpret=interpret,
                                     with_props=with_props)


def decode_columnar(buf, R, O, pos_wide, ref_wide, rich, compact8, tab_n,
                    n_min_seq):
    """The wire's unpack, whatever places its output: ONE byte-packed
    columnar batch (int32 words, the layout ``_columnar_unpack_jit``
    documents) decoded by shift and mask into the seven (R, O) op planes
    ``(kind, a0, a1, a2, seq, client, ref)``, the batch's (R,) row indices
    and the trailing ``n_min_seq`` words of fused min_seq. Traced inside
    ``_columnar_unpack_jit`` (one chip) and inside
    ``parallel.sharded.sharded_unpack`` (each shard of a mesh)."""
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

    if compact8:
        # 5 B/op profile: [kind(2b)|cidx(6b)] u8, a0 u16, span-delta u8
        # (a1 = a0+delta for remove/annotate, payload length for insert),
        # lag u8. NOOP (=12) rides as code 3 in the 2-bit field.
        kc, off = take_u8(0, N)
        kind = kc & 0x3
        kind = jnp.where(kind == 3, int(OpKind.NOOP), kind)
        client = kc >> 2
        a0, off = take_u16(off, N)
        delta, off = take_u8(off, N)
        a1 = jnp.where(kind == int(OpKind.STR_INSERT), delta, a0 + delta)
        ref, off = take_u8(off, N)
    else:
        take_pos = take_i32 if pos_wide else take_u16
        kind, off = take_u8(0, N)
        client, off = take_u8(off, N)
        a0, off = take_pos(off, N)
        a1, off = take_pos(off, N)
        ref, off = (take_i32 if ref_wide else take_u16)(off, N)
    lenv = None
    if rich in (2, 3):
        ti, off = (take_u8 if rich == 2 else take_u16)(off, N)
        a2tab, off = take_i32(off, tab_n)
        lentab, off = take_i32(off, tab_n)
        ti = ti.reshape(R, O)
        a2 = a2tab[ti]
        lenv = lentab[ti]
    else:
        a2, off = take_i32(off, N if rich else 1)
    base, off = take_i32(off, R)
    rows, off = take_i32(off, R)
    min_seq, off = take_i32(off, n_min_seq)

    kind = kind.reshape(R, O)
    valid = kind != int(OpKind.NOOP)
    seq = base[:, None] + jnp.cumsum(valid.astype(jnp.int32), axis=1)
    a0 = a0.reshape(R, O)
    a1 = a1.reshape(R, O)
    client = client.reshape(R, O)
    if lenv is not None:  # table form: insert a1 = payload length
        a1 = jnp.where(kind == int(OpKind.STR_INSERT), lenv, a1)
    if ref_wide and not compact8:
        ref = jnp.minimum(ref.reshape(R, O), seq - 1)
    else:  # lag encoding: ref = seq - lag, lag >= 1 (the Deli clamp)
        ref = seq - jnp.maximum(ref.reshape(R, O), 1)
    if rich == 1:
        a2 = a2.reshape(R, O)
    elif not rich:
        a2 = jnp.broadcast_to(a2, (R, O))
    a2 = jnp.where((kind == int(OpKind.STR_INSERT))
                   | (kind == int(OpKind.STR_ANNOTATE)), a2, 0)
    return (kind, a0, a1, a2, seq, client, ref), rows, min_seq


@functools.partial(jax.jit,
                   static_argnames=("R", "O", "pos_wide", "ref_wide",
                                    "rich", "n_docs", "fuse_compact",
                                    "scatter_rows", "compact8", "tab_n"))
def _columnar_unpack_jit(buf, R, O, pos_wide, ref_wide, rich, n_docs,
                         fuse_compact, scatter_rows, compact8=False,
                         tab_n=0):
    """Device-side unpack of ONE byte-packed columnar batch. The host
    concatenates every op plane into a single uint8 buffer — kind u8,
    client-idx u8, a0/a1 (i16, or i32 when ``pos_wide``), ref (u16 LAG
    behind the op's own seq, or full i32 when ``ref_wide``), a2 (one
    broadcast i32 handle, or an (N,) i32 plane when ``rich``), the
    per-row seq bases, the row indices, and the fused min_seq — because
    EACH host→device transfer pays a fixed per-transfer overhead and its
    own enqueue: one fused buffer at ~8 B/op is one transfer and one sync
    point per batch instead of seven.

    seq = base + running count of non-NOOP slots (nacked ops were
    NOOP-masked host-side and consumed no sequence number); ref clamps to
    seq-1 (mirroring Deli).

    ``rich`` payload modes: 0 = broadcast (one i32 handle), 1 = a full
    (N,) i32 a2 plane, 2/3 = TABLE form — the wire carries a u8 (mode 2)
    or u16 (mode 3) table index per op plus two small i32 tables
    (``tab_n`` entries each, padded to a power of two): the a2 value
    (payload handle / packed property) and the insert length. The device
    gathers a2 and insert a1 from the tables, so rich batches cost ~1-2
    extra wire bytes per op instead of 4 and the host never materializes
    an (R, O) handle plane (the former rich-pack hot spot).

    This is deliberately its OWN jit (not fused into the merge program),
    and the buffer is INT32 WORDS unpacked by shift/mask — not u8 +
    bitcast: both the u8-bitcast form and fusing the unpack into the
    scan/compact body pathologically explode XLA's TPU compile time
    (seconds → many minutes at 10k-doc shapes, measured); this form
    compiles in seconds and the unpacked planes stay on device.

    This is the one-chip placement; a store on a mesh runs the same
    ``decode_columnar`` where its state lives
    (``parallel.sharded.sharded_unpack``)."""
    planes, rows, min_seq = decode_columnar(
        buf, R, O, pos_wide, ref_wide, rich, compact8, tab_n,
        n_docs if fuse_compact else 1)
    if scatter_rows:
        def full(p, fill):
            return jnp.full((n_docs, O), fill, jnp.int32).at[rows].set(p)

        planes = (full(planes[0], int(OpKind.NOOP)),) + \
            tuple(full(p, 0) for p in planes[1:])
    return planes, min_seq


@functools.partial(jax.jit, donate_argnums=0,
                   static_argnames=("use_pallas", "tile", "interpret",
                                    "with_props", "fuse_compact"))
def _columnar_merge_jit(state, planes, min_seq, use_pallas, tile,
                        interpret, with_props, fuse_compact):
    """The merge half of the columnar apply (device-resident planes from
    ``_columnar_unpack_jit``): fused Pallas apply+zamboni when eligible,
    else the XLA scan (+ fused compact)."""
    if use_pallas:
        # fused apply+zamboni: ONE dispatch, planes stay in VMEM (the r1
        # headline configuration, now the product path)
        return apply_string_batch_pallas(
            state, *planes, tile=tile, interpret=interpret,
            min_seq=min_seq if fuse_compact else None,
            with_props=with_props)
    with jax.named_scope("string_merge"):
        out = apply_string_batch(state, *planes, with_props=with_props)
    if fuse_compact:
        from .merge_tree_kernel import compact_string_state
        with jax.named_scope("string_zamboni"):
            out = compact_string_state(out, min_seq, with_props)
    return out


class PrepackedPlanes:
    """The seq-independent half of a columnar apply's host pack: payload/
    props tables interned, wire form chosen, insert lengths resolved —
    everything ``apply_planes`` needs that does NOT depend on sequencing
    results. Produced by ``TensorStringStore.prepack_planes`` (the
    pipelined-ingest pack worker runs it concurrent with the previous
    wave's dispatch) and consumed exactly once, in submission order —
    payload-handle allocation happens at prepack time, so waves must be
    prepacked and applied FIFO or handle numbering diverges from a
    serial execution."""

    __slots__ = ("rich", "rich_mode", "a2_np", "tab_a2", "tab_len",
                 "tab_n", "tidx_eff", "a1", "prep_ms", "pooled")

    def __init__(self):
        self.rich = False
        self.rich_mode = 0
        self.a2_np = None
        self.tab_a2 = None
        self.tab_len = None
        self.tab_n = 0
        self.tidx_eff = None
        self.a1 = None
        self.prep_ms = 0.0
        self.pooled = False


class StringOpInterner:
    """Shared host-side message→op-record translation for the flat and
    mega-doc stores: payload/client/property interning and the
    insert-with-props → insert + same-seq annotate expansion. One
    implementation so the two serving facades cannot drift apart."""

    # every per-slot plane of StringState, derived so a future plane cannot
    # be silently dropped from either store's snapshots
    SNAP_PLANES = tuple(
        f.name for f in dataclasses.fields(StringState)
        if f.name not in ("count", "overflow"))

    def _init_interner(self, n_docs: int, n_props: int) -> None:
        self._payloads: List[Tuple[int, str]] = [(_TEXT, "")]  # handle 0
        # capacity plane (ISSUE 19): payload text chars, maintained O(1)
        # at every growth point so a census never walks the table
        self._payload_chars = 0
        self._client_idx: List[Dict[int, int]] = [dict()
                                                  for _ in range(n_docs)]
        # annotate: property KEYS intern to plane indexes (store-wide),
        # VALUES intern to handles; handle 0 = key unset (None deletes)
        self._prop_planes: Dict[str, int] = {}
        self._prop_values = ValueInterner()
        self._has_props = False
        self.n_props = n_props
        # one interner pass per UNIQUE (key, value): columnar annotate
        # tables re-pack the same few props every batch; the packed plane
        # <<20 | handle word is cached for hashable values (sound: planes
        # and value handles minted on the apply path are never released)
        self._props_pack_cache: Dict[tuple, int] = {}
        # (rows, client-column, lut) of the last single-writer columnar
        # batch: steady serving re-interns the same (row, client) pairs
        # every batch — a 40 KB memcmp replaces R dict hits
        self._cidx_cache: Optional[tuple] = None
        # pow2 payload-table buffer pool, keyed by tab_n: steady rich
        # serving re-packs same-capacity tables every wave; reusing the
        # buffers (zero only the stale tail) drops an alloc+full-zero per
        # wave. list ops are GIL-atomic, so a pipelined pack worker can
        # pop while the dispatch stage returns (see _tab_buffers).
        self._tab_pool: Dict[int, list] = {}

    def _client(self, doc: int, client_id: int) -> int:
        m = self._client_idx[doc]
        if client_id not in m:
            if len(m) >= MAX_CLIENTS:
                raise KeyError(f"doc {doc}: client capacity {MAX_CLIENTS}")
            m[client_id] = len(m)
        return m[client_id]

    def _payload(self, kind: int, text: str) -> int:
        self._payloads.append((kind, text))
        self._payload_chars += len(text)
        return len(self._payloads) - 1

    def _prop_plane(self, key: str) -> int:
        if key not in self._prop_planes:
            if len(self._prop_planes) >= self.n_props:
                raise KeyError(
                    f"property key capacity {self.n_props} exhausted "
                    f"(recreate the store with a larger n_props)")
            self._prop_planes[key] = len(self._prop_planes)
        return self._prop_planes[key]

    def _prop_handle(self, value) -> int:
        if value is None:
            return 0
        h = self._prop_values.handle(value)
        if h >= (1 << PROP_HANDLE_BITS):
            raise OverflowError("property value table exceeded 2^20 entries")
        return h

    def remap_payload_handles(self, src: "StringOpInterner",
                              handles: np.ndarray) -> np.ndarray:
        """Re-intern ``src``'s payloads referenced by ``handles`` into THIS
        store's table; returns the remapped handle array (dedup per distinct
        source handle). Used by the overflow-recovery re-upload."""
        hmap: Dict[int, int] = {}
        out = np.empty_like(handles)
        for i, h in enumerate(handles):
            h = int(h)
            if h not in hmap:
                kind, text = src._payloads[h]
                hmap[h] = self._payload(kind, text)
            out[i] = hmap[h]
        return out

    def remap_props(self, src: "StringOpInterner", tprop: np.ndarray,
                    out: np.ndarray) -> None:
        """Remap ``src``'s (n, K_src) per-slot property-value handles into
        ``out`` (n+, K_self) under THIS store's key planes and value table
        (overflow-recovery re-upload)."""
        n = tprop.shape[0]
        for key, tplane in src._prop_planes.items():
            mplane = self._prop_plane(key)
            col = tprop[:, tplane]
            vmap = {int(h): (0 if h == 0 else self._prop_values.handle(
                src._prop_values.value(int(h))))
                    for h in np.unique(col)}
            out[:n, mplane] = [vmap[int(h)] for h in col]

    def reserve_props(self, props: dict) -> list:
        """Admission-time reservation of the interner capacity ``props``
        will need at flush (serving engines call this BEFORE the op is
        sequenced/logged): mints planes for every new key now — atomically,
        nothing is minted if any key cannot fit — and checks value-table
        headroom without interning (conservative: values may dedupe at
        flush). Returns a token; pass it to ``release_props`` if the op is
        subsequently nacked, else the mint would leak the tiny plane table.
        Raises KeyError when capacity is exhausted."""
        new_keys = [k for k in props if k not in self._prop_planes]
        if len(self._prop_planes) + len(new_keys) > self.n_props:
            raise KeyError(
                f"property key capacity {self.n_props} exhausted")
        n_vals = sum(1 for v in props.values() if v is not None)
        if len(self._prop_values) + n_vals > (1 << PROP_HANDLE_BITS):
            raise KeyError("property value table exhausted")
        for k in new_keys:
            self._prop_plane(k)
        return new_keys

    def reserve_prop_tables(self, keys, values) -> None:
        """Columnar-ingest admission: reserve planes for every key in
        ``keys`` (atomic, as ``reserve_props``) and check value-table
        headroom for the DISTINCT uninterned values in ``values`` — the
        whole batch is admitted or none of it, before sequencing."""
        new_keys = [k for k in keys if k not in self._prop_planes]
        if len(self._prop_planes) + len(new_keys) > self.n_props:
            raise KeyError(
                f"property key capacity {self.n_props} exhausted")
        uniq = {json.dumps(v, sort_keys=True) for v in values
                if v is not None}
        uniq -= set(self._prop_values._ids)
        if len(self._prop_values) + len(uniq) > (1 << PROP_HANDLE_BITS):
            raise KeyError("property value table exhausted")
        for k in new_keys:
            self._prop_plane(k)

    def release_props(self, minted: list) -> None:
        """Undo ``reserve_props`` after a post-admission nack. Sound only
        within the submit's own synchronous window (no interleaved mint):
        planes are popped in reverse mint order, so indexes stay dense."""
        for k in reversed(minted):
            idx = self._prop_planes.pop(k)
            assert idx == len(self._prop_planes), "interleaved mint"

    def _annotate_rec(self, key, value, start, end, seq, cl, ref_seq):
        self._has_props = True
        packed = (self._prop_plane(key) << PROP_HANDLE_BITS) | \
            self._prop_handle(value)
        return (int(OpKind.STR_ANNOTATE), start, end, packed, seq, cl,
                ref_seq)

    def _records_for(self, doc: int, msg) -> list:
        """Device op records (7-tuples) for one sequenced message."""
        op = msg.contents
        cl = self._client(doc, msg.client_id)
        if op["mt"] == "insert":
            if op["kind"] == 1:  # marker
                handle = self._payload(_MARKER, "")
                length = 1
            else:
                if not op["text"]:
                    return []  # empty insert: no segment anywhere
                handle = self._payload(_TEXT, op["text"])
                length = len(op["text"])
            recs = [(int(OpKind.STR_INSERT), op["pos"], length, handle,
                     msg.seq, cl, msg.ref_seq)]
            # insert-with-props = insert + same-seq annotate of the new
            # segment: in the op's own perspective the inserted run occupies
            # exactly [pos, pos+len) and nothing else visible moved, so the
            # annotate targets only it
            for key in sorted(op.get("props") or {}):
                recs.append(self._annotate_rec(
                    key, op["props"][key], op["pos"], op["pos"] + length,
                    msg.seq, cl, msg.ref_seq))
            return recs
        if op["mt"] == "remove":
            return [(int(OpKind.STR_REMOVE), op["start"], op["end"], 0,
                     msg.seq, cl, msg.ref_seq)]
        if op["mt"] == "annotate":
            # one device record per property key (the kernel's per-key LWW
            # planes); all records share the message's seq
            return [self._annotate_rec(key, op["props"][key], op["start"],
                                       op["end"], msg.seq, cl, msg.ref_seq)
                    for key in sorted(op["props"])]
        raise ValueError(f"unknown op {op['mt']!r}")

    # ------------------------------------------------------ capacity plane

    def interner_host_bytes(self) -> int:
        """Host-byte estimate of the interner tables (capacity plane,
        ISSUE 19). Payload chars are a counter maintained at every
        growth point, so this is a cheap roll-up — never a table walk.
        Per-payload constant: tuple(2) 56 + str header 49 + list slot 8
        (the kind ints are shared small-int singletons)."""
        from ..utils import capacity as _cap
        n_pay = len(self._payloads)
        total = getattr(self, "_payload_chars", 0) + n_pay * (56 + 49 + 8)
        total += _cap.list_nbytes(len(self._client_idx))
        for m in self._client_idx:          # n_docs small dicts: ~1ms/10k
            total += _cap.dict_nbytes(len(m), _cap.INT_DICT_ENTRY_BYTES)
        total += _cap.dict_nbytes(len(self._prop_planes))
        # value interner: JSON-encoded key strings + value objects; a
        # flat per-entry constant (values are small scalars/strings)
        total += _cap.interner_nbytes(len(self._prop_values),
                                      80 * len(self._prop_values))
        total += _cap.dict_nbytes(
            len(getattr(self, "_props_pack_cache", ())),
            _cap.INT_DICT_ENTRY_BYTES)
        return int(total)


class TensorStringStore(StringOpInterner):
    #: Pallas dispatch policy — "auto": fused VMEM kernel on TPU for
    #: annotate-free stores with a compatible shape, XLA scan otherwise;
    #: "interpret": force the Pallas path through its interpreter (CPU
    #: parity tests); "off": always the XLA scan.
    pallas = "auto"
    #: set once this store has warned that a TPU dispatch took the XLA scan
    _scan_warned = False

    def __init__(self, n_docs: int, capacity: int = 256, n_props: int = 4,
                 mesh=None):
        self.n_docs = n_docs
        self.capacity = capacity
        # multi-chip: a 1-D "docs" mesh shards the planes by doc row; every
        # apply/compact runs as a shard_map of the SAME kernels (zero
        # cross-chip collectives on the hot path — parallel/sharded.py)
        self.mesh = mesh
        if mesh is not None and n_docs % mesh.devices.size != 0:
            raise ValueError(f"n_docs {n_docs} not divisible by mesh size "
                             f"{mesh.devices.size}")
        # until the first annotate arrives the kernels run in the no-props
        # mode (all-zero planes are permutation-invariant; skipping their
        # movement saves ~35% HBM traffic on the hot path)
        self.state = StringState.create(n_docs, capacity, n_props)
        if mesh is not None:
            from ..parallel.sharded import shard_store_state
            self.state = shard_store_state(self.state, mesh)
        self._init_interner(n_docs, n_props)
        # serving-side intervals: anchors are (handle_op, handle_off) POINTS
        # — position-independent, stable under splits, tombstone-tolerant —
        # so op application never touches them (reference: local references;
        # the oracle's lazy-slide-at-resolve / re-anchor-at-zamboni split)
        self._intervals: List[Dict[str, tuple]] = [dict()
                                                   for _ in range(n_docs)]
        self._interval_counter = 0
        #: wire profile of the last columnar batch (None before the first)
        self.last_profile: Optional[tuple] = None
        #: rich payload wire form of the last batch: "plane"/"tab8"/"tab16"
        self.last_rich_wire: Optional[str] = None
        #: every static-argument tuple this store has handed the columnar
        #: unpack jit, window height R first — each is one XLA program
        self.unpack_variants: set = set()
        #: fused device→host gathers served (the read path's sync budget)
        self.device_reads = 0
        # highest collaboration-window floor seen per doc (anchor slides
        # trigger at its advances, matching the oracle's zamboni timing)
        self._iv_min_seq = np.zeros((self.n_docs,), np.int64)
        # per-doc min-heap of uncompacted tombstone seqs, maintained ONLY
        # for interval-holding docs (seeded from the device planes when a
        # doc gains its first interval; pushed per remove; pruned as the
        # floor passes). Lets the apply path tell host-side whether a
        # window-floor advance actually dooms a tombstone — only then do
        # interval anchors need sliding at the crossing.
        self._iv_tombs: List[list] = [[] for _ in range(n_docs)]
        # rows currently holding intervals: the columnar hot path's "does
        # this batch need crossing bookkeeping at all" check must be O(1),
        # not a scan of n_docs dicts
        self._iv_docs: set = set()

    # --------------------------------------------------------- capacity plane

    def capacity_stats(self) -> dict:
        """Capacity-plane report fragment (ISSUE 19): host interner +
        interval bookkeeping, device plane bytes (sums to what
        ``jax.live_arrays()`` sees for this store's state)."""
        from ..utils import capacity as _cap
        n_iv = sum(len(d) for d in self._intervals)
        host = {
            "interner": self.interner_host_bytes(),
            # interval records: dict entry + 2 anchor tuples + props
            "intervals": (_cap.dict_nbytes(n_iv, 250)
                          + _cap.list_nbytes(self.n_docs) * 2
                          + _cap.ndarray_nbytes(self._iv_min_seq)),
        }
        return {"host": host,
                "device": {"state": _cap.device_nbytes(self.state)}}

    # ----------------------------------------------------------------- apply

    def apply_messages(self, messages) -> None:
        """messages: iterable of (doc, SequencedDocumentMessage) carrying
        merge-tree op contents (the ``mt`` dicts of SequenceClient).

        Documents holding intervals need anchor slides at the exact message
        where min_seq crosses a tombstone (the oracle slides per message as
        the window advances; sliding once per batch can pick a different
        target — e.g. a segment that was live at the crossing but tombstoned
        by batch end). The batch is split at such a crossing — and only
        there: the per-doc tombstone-seq heap tells us host-side whether an
        advance dooms anything, so interval-holding docs in an active
        collaboration (where MSN advances on nearly every message) still
        take large batched dispatches."""
        msgs = list(messages)
        iv_docs = self._iv_docs
        if not iv_docs:
            self._apply_batch(msgs)
            return
        group: list = []
        for doc, msg in msgs:
            group.append((doc, msg))
            if doc in iv_docs:
                if msg.min_seq > self._iv_min_seq[doc]:
                    self._iv_min_seq[doc] = msg.min_seq
                    if self._floor_dooms_tombstone(doc):
                        self._apply_batch(group)
                        group = []
                        self._slide_anchors_at_floor(doc)
                if msg.contents["mt"] == "remove":
                    heapq.heappush(self._iv_tombs[doc], msg.seq)
        if group:
            self._apply_batch(group)

    def _apply_batch(self, msgs) -> None:
        per_doc: Dict[int, list] = {}
        for doc, msg in msgs:
            recs = self._records_for(doc, msg)
            if recs:
                per_doc.setdefault(doc, []).extend(recs)
        if not per_doc:
            return
        # power-of-two op-axis buckets keep jit cache hits (static shapes)
        widest = max(len(v) for v in per_doc.values())
        o = 8
        while o < widest:
            o *= 2
        planes = {
            "kind": np.full((self.n_docs, o), int(OpKind.NOOP), np.int32),
            "a0": np.zeros((self.n_docs, o), np.int32),
            "a1": np.zeros((self.n_docs, o), np.int32),
            "a2": np.zeros((self.n_docs, o), np.int32),
            "seq": np.zeros((self.n_docs, o), np.int32),
            "client": np.zeros((self.n_docs, o), np.int32),
            "ref_seq": np.zeros((self.n_docs, o), np.int32),
        }
        for doc, recs in per_doc.items():
            for j, (k, x0, x1, x2, sq, cl, rs) in enumerate(recs):
                planes["kind"][doc, j] = k
                planes["a0"][doc, j] = x0
                planes["a1"][doc, j] = x1
                planes["a2"][doc, j] = x2
                planes["seq"][doc, j] = sq
                planes["client"][doc, j] = cl
                planes["ref_seq"][doc, j] = rs
        self._dispatch_apply(tuple(
            jnp.asarray(planes[k]) for k in
            ("kind", "a0", "a1", "a2", "seq", "client", "ref_seq")))

    def _tab_buffers(self, tab_n: int, T: int, P: int):
        """A (tab_a2, tab_len) pair of ``tab_n`` int32 buffers — reused
        from the pow2 pool when available (only the region past the live
        entries is re-zeroed; callers overwrite ``[:T+P]`` / ``[:T]``)."""
        pool = self._tab_pool.get(tab_n)
        if pool:
            tab_a2, tab_len = pool.pop()
            tab_a2[T + P:] = 0
            tab_len[T:] = 0
            return tab_a2, tab_len, True
        return (np.zeros((tab_n,), np.int32),
                np.zeros((tab_n,), np.int32), True)

    def _tab_release(self, pp: PrepackedPlanes) -> None:
        """Return a prepack's table buffers to the pow2 pool once the
        wire buffer has been built (np.concatenate copied them)."""
        if pp.pooled and pp.tab_a2 is not None:
            pool = self._tab_pool.setdefault(pp.tab_n, [])
            if len(pool) < 4:   # depth-bounded pipeline: tiny pool suffices
                pool.append((pp.tab_a2, pp.tab_len))
        pp.tab_a2 = pp.tab_len = None

    def _pack_payload_tables(self, rows, kind, a0, a1, text, texts, tidx,
                             props) -> PrepackedPlanes:
        """Build the payload/props side of a columnar apply's wire form:
        intern payloads, pack props, choose the rich wire mode, resolve
        insert lengths. Depends only on the RAW op planes — never on
        sequencing results — so the pipelined executor runs it on a pack
        worker concurrent with the previous wave's dispatch. Mutates the
        interner (payload handles allocate here): call in submission
        order, consume each result exactly once."""
        _t0 = time.perf_counter()
        pp = PrepackedPlanes()
        R, O = kind.shape
        ins = kind == int(OpKind.STR_INSERT)
        ann = kind == int(OpKind.STR_ANNOTATE)
        if ann.any() and props is None:
            raise ValueError("annotate slots require the props table")
        # interval anchors key by (payload handle, offset): two same-text
        # inserts in one doc must NOT share a handle or the anchor becomes
        # ambiguous (the per-message path mints one handle per op). A
        # batch touching any interval-holding row therefore mints per-op
        # handles and ships the resolved a2 plane; the dedup'd-table fast
        # wire stays reserved for interval-free batches.
        iv_handles = bool(self._iv_docs) and bool(ins.any()) \
            and not self._iv_docs.isdisjoint(
                np.asarray(rows).reshape(-1).tolist())
        pp.rich = not (texts is None and props is None) or iv_handles
        if not pp.rich:
            # broadcast payload: a2 is one scalar handle
            pp.a2_np = np.array([self._payload(_TEXT, text)], np.int32)
            pp.a1 = np.where(ins, len(text), a1)
            pp.prep_ms = (time.perf_counter() - _t0) * 1000
            return pp
        if tidx is not None:
            tidx = np.asarray(tidx, np.int32)
        packed_tab = np.zeros((0,), np.int32)
        if props is not None and ann.any():
            self._has_props = True
            packed_tab = np.empty((len(props),), np.int32)
            cache = self._props_pack_cache
            for j, p in enumerate(props):
                (key, value), = p.items()  # single-key by contract
                try:
                    packed = cache.get((key, value))
                except TypeError:   # unhashable value: intern directly
                    packed = None
                if packed is None:
                    packed = (self._prop_plane(key)
                              << PROP_HANDLE_BITS) \
                        | self._prop_handle(value)
                    try:
                        cache[(key, value)] = packed
                    except TypeError:
                        pass
                packed_tab[j] = packed
        if iv_handles:
            # per-op handle mint (anchor identity), resolved a2 plane
            pp.rich_mode = 1
            base_h = len(self._payloads)
            flat_ins = np.flatnonzero(ins.reshape(-1))
            if texts is not None:
                t_list = [texts[j] for j in
                          map(int, tidx.reshape(-1)[flat_ins])]
            else:
                t_list = [text] * len(flat_ins)
            self._payloads.extend((_TEXT, t) for t in t_list)
            self._payload_chars += sum(map(len, t_list))
            a2_np = np.zeros((R, O), np.int32)
            a2_np.reshape(-1)[flat_ins] = np.arange(
                base_h, base_h + len(flat_ins), dtype=np.int32)
            lens = np.zeros((R, O), np.int32)
            lens.reshape(-1)[flat_ins] = np.fromiter(
                map(len, t_list), np.int32, count=len(t_list))
            pp.a1 = np.where(ins, lens, a1)
            if len(packed_tab):
                a2_np[ann] = packed_tab[tidx[ann]]
            pp.a2_np = a2_np
            pp.prep_ms = (time.perf_counter() - _t0) * 1000
            return pp
        # ONE interner pass per unique payload/props entry: handles
        # resolve into small per-batch TABLES (texts first, packed
        # props after), and when the combined table fits a narrow
        # index the wire ships u8/u16 indices + the tables instead
        # of a resolved (R, O) i32 plane — the device gathers a2
        # and insert lengths itself (rich-pack vectorization
        # tentpole)
        if texts is not None:
            base_h = len(self._payloads)
            self._payloads.extend((_TEXT, t) for t in texts)
            handles_tab = np.arange(base_h, base_h + len(texts),
                                    dtype=np.int32)
            lens_tab = np.fromiter(map(len, texts), np.int32,
                                   count=len(texts))
            self._payload_chars += int(lens_tab.sum())
        elif ins.any():
            handles_tab = np.array([self._payload(_TEXT, text)],
                                   np.int32)
            lens_tab = np.array([len(text)], np.int32)
        else:
            handles_tab = np.zeros((1,), np.int32)
            lens_tab = np.zeros((1,), np.int32)
        T, P = len(handles_tab), len(packed_tab)
        if T + P <= 256:
            pp.rich_mode = 2
        elif T + P <= 65536:
            pp.rich_mode = 3
        else:
            pp.rich_mode = 1
        if pp.rich_mode != 1:
            # annotate indices shift past the text region; indices at
            # remove/NOOP slots are never validated NOR used (the
            # device zeroes a2 for those kinds and the gather clamps),
            # so they ride as-is
            tidx_eff = np.where(ann, tidx + T, tidx)
            if texts is None and ins.any():
                # broadcast-insert + props form: tidx only indexes the
                # props table; inserts all take table entry 0
                tidx_eff = np.where(ins, 0, tidx_eff)
            pp.tidx_eff = tidx_eff
            pp.tab_n = max(8, 1 << (T + P - 1).bit_length())
            pp.tab_a2, pp.tab_len, pp.pooled = \
                self._tab_buffers(pp.tab_n, T, P)
            pp.tab_a2[:T] = handles_tab
            pp.tab_a2[T:T + P] = packed_tab
            pp.tab_len[:T] = lens_tab
            # wire a1 for inserts is a placeholder (= a0, so spans stay
            # 0 and positions stay narrow); the device substitutes the
            # table length — the host never builds the lens plane
            pp.a1 = np.where(ins, a0, a1)
        else:               # huge tables: resolved i32 a2 plane
            a2_np = np.zeros((R, O), np.int32)
            a1_out = a1
            if texts is not None:
                a2_np[ins] = handles_tab[tidx[ins]]
                a1_out = np.where(ins, lens_tab.take(tidx, mode="clip"),
                                  a1)
            elif ins.any():
                a2_np[ins] = handles_tab[0]
                a1_out = np.where(ins, lens_tab[0], a1)
            if P:
                a2_np[ann] = packed_tab[tidx[ann]]
            pp.a2_np = a2_np
            pp.a1 = a1_out
        pp.prep_ms = (time.perf_counter() - _t0) * 1000
        return pp

    def prepack_planes(self, rows, kind, a0, a1, text: str = "",
                       texts=None, tidx=None,
                       props=None) -> Optional[PrepackedPlanes]:
        """Pipelined-ingest hook: run the seq-independent pack work for a
        wave AHEAD of its sequencing (concurrent with the previous wave's
        dispatch) and hand the result to ``apply_planes(prepacked=...)``.

        Returns ``None`` when the batch touches interval-holding rows:
        that path mints one payload handle per ACKED op (anchor
        identity), which depends on post-sequencing nack knowledge — the
        caller must fall back to the inline pack (and, in a pipeline,
        barrier until this wave's dispatch completes so handle order
        stays serial). The raw ``kind`` plane is assumed all-acked;
        nacked slots only affect unused table entries (exactly as the
        inline path, which interns whole tables regardless of nacks)."""
        kind = np.asarray(kind, np.int32)
        ins = kind == int(OpKind.STR_INSERT)
        if bool(self._iv_docs) and bool(ins.any()) \
                and not self._iv_docs.isdisjoint(
                    np.asarray(rows).reshape(-1).tolist()):
            return None
        return self._pack_payload_tables(
            np.asarray(rows), kind, np.asarray(a0, np.int32),
            np.asarray(a1, np.int32), text, texts, tidx, props)

    def apply_planes(self, rows, kind, a0, a1, seq_base, client_id, ref_seq,
                     text: str = "", min_seq=None, texts=None, tidx=None,
                     props=None, min_ops=None, prepacked=None,
                     rec: Optional[dict] = None) -> None:
        """Columnar apply: dense (R, O) already-sequenced op planes for the
        subset of doc rows ``rows`` (R,) — the ingest hot path (no per-op
        Python objects anywhere). Ops per doc apply in column order (the
        sequencer's per-doc total order); NOOP slots (nacked ops) are
        skipped and consumed no seq, so per-op seqs are reconstructed ON
        DEVICE from the per-row ``seq_base`` (the doc's seq before the
        batch).

        Payloads: either the broadcast ``text`` (every insert inserts the
        same run — the typing-storm shape) or per-op payloads via
        ``texts`` (a payload table) + ``tidx`` ((R, O) int32 indices into
        it) — the distinct-payload shape real text produces. Insert a1 is
        derived from the payload either way.

        Annotates (kind == STR_ANNOTATE) are admitted when ``props`` (a
        table of SINGLE-key {key: value} dicts, indexed by ``tidx``) is
        given: one columnar slot = one (key, value) range annotate =
        one sequence number. Multi-key annotates and insert-with-props
        expand to several same-seq records and must go through
        ``apply_messages``.

        ``min_seq`` (n_docs,) fuses zamboni into the same dispatch (the
        apply+compact single-HBM-round-trip configuration); if any doc in
        the store holds intervals, compaction falls back to ``compact``
        (which re-anchors before dropping tombstones).

        Docs holding intervals ride this path too: pass ``min_ops`` — the
        (R, O) per-op min_seq plane the sequencer stamped — and the batch
        is split at the exact column where a doc's window floor crosses a
        pending tombstone (the oracle slides refs per message as the
        window advances; sliding once per batch can pick a different
        target). Between segments the doomed docs' anchors re-anchor off
        the device state AT the crossing, via one fused gather for every
        crossing doc. Without ``min_ops`` the floor is assumed not to
        advance inside the batch (removes still feed the tombstone heaps,
        so a later ``advance_min_seq``/``compact`` slides correctly).

        ``rec``: the window's record (``utils.tracing``) that the spans
        of this call are stamped into; a fresh one without."""
        if rec is None:
            rec = tracing.new_record()
        with tracing.stage(rec, "store.apply_planes"):
            # host pack: from here to the first upload, then once more for
            # each later segment (entered by hand: the span ends inside the
            # loop below; an exception in between ends the whole apply)
            pack = tracing.stage(rec, "store.pack")
            pack.__enter__()
            rows = np.ascontiguousarray(rows, np.int32)
            R, O = kind.shape
            if len(np.unique(rows)) != R:
                raise ValueError("duplicate rows in columnar batch (the device "
                                 "scatter would silently drop ops)")
            kind = np.asarray(kind, np.int32)
            ins = kind == int(OpKind.STR_INSERT)
            a0 = np.asarray(a0, np.int32)
            a1 = np.asarray(a1, np.int32)
            # payload/props side of the pack: either handed in by the
            # pipelined executor's pack worker (``prepacked``, built
            # concurrent with the previous wave's dispatch) or built inline
            # right here — identical code either way (_pack_payload_tables)
            pp = prepacked
            if pp is None:
                pp = self._pack_payload_tables(rows, kind, a0, a1, text,
                                               texts, tidx, props)
            rich = pp.rich
            rich_mode = pp.rich_mode
            a2_np = pp.a2_np
            tab_a2, tab_len, tab_n = pp.tab_a2, pp.tab_len, pp.tab_n
            tidx_eff = pp.tidx_eff
            a1 = pp.a1

            # vectorized client interning. Fast path: one writer per doc row in
            # this batch (the common live-collaboration window) — R dict hits,
            # no materialized (R·O) key array — with a one-entry cache: steady
            # serving re-presents the SAME (rows, client) pairing every batch,
            # which a memcmp detects without touching the dicts. General path:
            # one dict hit per UNIQUE (row, client) pair via a packed int64 key
            # (np.unique on a 1-D int key is ~10× faster than axis=0 row
            # dedup); nacked/NOOP slots never mint an index there.
            valid = kind != int(OpKind.NOOP)
            cidx = np.zeros((R, O), np.int32)
            cid = np.asarray(client_id, np.int32)
            cmax = 0
            if (cid == cid[:, :1]).all():
                cid0 = np.ascontiguousarray(cid[:, 0])
                rkey, ckey = rows.tobytes(), cid0.tobytes()
                cached = self._cidx_cache
                rows_any = valid.any(axis=1)
                all_rows_valid = bool(rows_any.all())
                if cached is not None and all_rows_valid \
                        and cached[0] == rkey and cached[1] == ckey:
                    lut = cached[2]
                else:
                    # mint only for rows with at least one acked op (an
                    # all-NOOP row must not consume one of the doc's
                    # MAX_CLIENTS slots — and must match what a log rebuild
                    # would intern)
                    lut = np.zeros(R, np.int32)
                    mint = self._client
                    rows_l, cid_l = rows.tolist(), cid0.tolist()
                    for i in map(int, np.flatnonzero(rows_any)):
                        lut[i] = mint(rows_l[i], cid_l[i])
                    if all_rows_valid:
                        self._cidx_cache = (rkey, ckey, lut)
                cidx[:] = lut[:, None]
                cmax = int(lut.max(initial=0))
            elif valid.any():
                rr = np.broadcast_to(rows[:, None], (R, O))[valid]
                cc = cid.astype(np.int64)[valid]
                key = (rr.astype(np.int64) << 32) | (cc & 0xFFFFFFFF)
                uniq, inv = np.unique(key, return_inverse=True)
                lut = np.array(
                    [self._client(int(k >> 32), int(np.int32(k & 0xFFFFFFFF)))
                     for k in uniq], np.int32)
                cidx[valid] = lut[inv]
                cmax = int(lut.max(initial=0))

            # unsigned u16 packing would alias a (malformed) negative position
            # to ~65535 — minima force such inputs onto the sign-preserving
            # wide path, where they behave exactly like the per-op path
            narrow = int(a0.max(initial=0)) < 32767 and \
                int(a1.max(initial=0)) < 32767 and \
                int(a0.min(initial=0)) >= 0 and int(a1.min(initial=0)) >= 0
            seq_base = np.asarray(seq_base, np.int32)
            seq = seq_base[:, None] + np.cumsum(valid, axis=1, dtype=np.int32)
            lag = np.subtract(seq, np.asarray(ref_seq, np.int32))
            np.maximum(lag, 1, out=lag)
            ref_wide = bool((lag > 65535).any())
            use_pallas, tile, interpret = self._pallas_choice()
            # the tiles the plain merge walks (pallas_string_kernel: those
            # holding one of the window's rows), from the rows held here
            tiles_total = self.n_docs // tile
            tiles_touched = np.unique(rows // tile).size
            scatter_rows = not (R == self.n_docs
                                and np.array_equal(rows, np.arange(R)))
            fuse = min_seq is not None and not self._iv_docs
            ms = np.asarray(min_seq, np.int32) if fuse \
                else np.zeros((1,), np.int32)
            # tightest profile first: 5 B/op when spans, lags and client
            # indexes all fit a byte (the live-collaboration common case —
            # see _columnar_unpack_jit on why wire bytes are the ceiling).
            # (kind-set membership via compares, not np.isin — isin costs ~8 ms
            # at 655k ops for the same answer)
            span = np.where(ins, a1, a1 - a0) if rich_mode < 2 \
                else np.where(ins, 0, a1 - a0)
            kinds_ok = bool(((kind >= 0) & ((kind <= int(OpKind.STR_ANNOTATE))
                                            | ~valid)).all())
            compact8 = bool(
                narrow and not ref_wide and kinds_ok
                and cmax < 64
                and int(lag.max(initial=0)) < 256
                and int(span.max(initial=0)) < 256
                and int(span.min(initial=0)) >= 0)
            # observability: which wire profile this batch took (head encoding,
            # position width, payload form) — tests pin each branch by name;
            # the rich payload's wire form (plane vs table) rides separately
            self.last_profile = (
                "compact8" if compact8 else
                "ref_wide" if ref_wide else "lag16",
                "pos16" if narrow else "pos32",
                "rich" if rich else "broadcast")
            self.last_rich_wire = (None if not rich else
                                   {1: "plane", 2: "tab8", 3: "tab16"}
                                   [rich_mode])

            # interval crossing scan: split the batch at every column where a
            # doc's window floor crosses a pending tombstone (mirrors the
            # apply_messages per-message bookkeeping; mutates the heaps/floors)
            segments = [(0, O, ())]
            if self._iv_docs:
                if min_ops is not None:
                    min_ops = np.asarray(min_ops)
                splits = self._interval_scan(rows, kind, seq, min_ops)
                if splits:
                    segs, prev = [], 0
                    for b in sorted(splits):
                        segs.append((prev, b, splits[b]))
                        prev = b
                    if prev < O:
                        segs.append((prev, O, ()))
                    segments = segs

            # word-pack EVERYTHING into one int32 buffer: each transfer pays
            # a fixed per-transfer overhead, so the whole batch (planes +
            # rows + seq bases + fused min_seq) rides ONE host→device copy
            # at ~8 B/op (see _columnar_unpack_jit)
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

            seg_pos = (lambda a: np.ascontiguousarray(a, "<i4").reshape(-1)) \
                if not narrow else seg_u16

            def pad_cols(arr, c0, c1, wp, fill=0):
                """Column slice padded to the wp bucket (NOOP-filled pads
                consume no seq and touch no state)."""
                w = c1 - c0
                if c0 == 0 and c1 == O and wp == O:
                    return arr
                out = np.full((R, wp), fill, np.int32)
                out[:, :w] = arr[:, c0:c1]
                return out

            ref_i32 = None
            if ref_wide:
                ref_i32 = np.ascontiguousarray(ref_seq, "<i4")

            if self.mesh is not None:
                from ..parallel import sharded
            pack_ms = 0.0
            dispatch_ms = 0.0
            for si, (c0, c1, slides) in enumerate(segments):
                if si:
                    pack.__enter__()
                last_seg = si == len(segments) - 1
                fuse_seg = fuse and last_seg
                ms_seg = ms if fuse_seg else np.zeros((1,), np.int32)
                w = c1 - c0
                # power-of-two column buckets keep the jit cache warm when a
                # crossing splits the batch (the no-split common case keeps
                # the exact original shape)
                wp = O if w == O else max(8, 1 << (w - 1).bit_length())
                k_s = pad_cols(kind, c0, c1, wp, fill=int(OpKind.NOOP))
                a0_s = pad_cols(a0, c0, c1, wp)
                lag_s = pad_cols(lag, c0, c1, wp, fill=1)
                cidx_s = pad_cols(cidx, c0, c1, wp)
                base_s = seq_base if c0 == 0 else \
                    np.ascontiguousarray(seq[:, c0 - 1])
                if compact8:
                    span_s = pad_cols(span, c0, c1, wp)
                    kc = np.where(k_s == int(OpKind.NOOP), 3, k_s) \
                        | (cidx_s << 2)
                    head = [seg_u8(kc), seg_u16(a0_s), seg_u8(span_s),
                            seg_u8(lag_s)]
                elif ref_wide:
                    head = [seg_u8(k_s), seg_u8(cidx_s), seg_pos(a0_s),
                            seg_pos(pad_cols(a1, c0, c1, wp)),
                            pad_cols(ref_i32, c0, c1, wp).reshape(-1)
                            .astype("<i4", copy=False)]
                else:  # ship the (u16) lag; device reconstructs ref=seq-lag
                    head = [seg_u8(k_s), seg_u8(cidx_s), seg_pos(a0_s),
                            seg_pos(pad_cols(a1, c0, c1, wp)),
                            seg_u16(lag_s)]
                if rich_mode >= 2:
                    tail = [(seg_u8 if rich_mode == 2 else seg_u16)(
                                pad_cols(tidx_eff, c0, c1, wp)),
                            tab_a2.astype("<i4", copy=False),
                            tab_len.astype("<i4", copy=False)]
                elif rich_mode == 1:
                    tail = [np.ascontiguousarray(
                        pad_cols(a2_np, c0, c1, wp), "<i4").reshape(-1)]
                else:
                    tail = [a2_np.astype("<i4", copy=False)]
                buf = np.concatenate(head + tail + [
                    base_s.astype("<i4", copy=False),
                    rows.astype("<i4", copy=False),
                    ms_seg.astype("<i4", copy=False),
                ])
                variant = dict(R=R, O=wp, pos_wide=not narrow,
                               ref_wide=ref_wide, rich=rich_mode,
                               n_docs=self.n_docs, fuse_compact=fuse_seg,
                               scatter_rows=scatter_rows, compact8=compact8,
                               tab_n=tab_n)
                self.unpack_variants.add(tuple(variant.values()))
                pack.__exit__()
                # two placements of the same three steps, chosen by where
                # the state lives: one chip takes one upload and plain jits;
                # a mesh takes the buffer on every chip and unpacks in each
                # shard, so the planes are born with the state's sharding
                # and the merge launch moves nothing between chips
                with tracing.stage(rec, "store.upload") as sp_up:
                    if self.mesh is None:
                        dev = jnp.asarray(buf)
                    else:
                        dev = sharded.replicated(buf, self.mesh)
                with tracing.stage(rec, "store.unpack_dispatch") as sp_unp:
                    if self.mesh is None:
                        planes, ms_dev = _columnar_unpack_jit(dev, **variant)
                    else:
                        planes, ms_dev = sharded.sharded_unpack(
                            self.mesh, **variant)(dev)
                with tracing.stage(rec, "store.merge_dispatch") as sp_mrg:
                    if self.mesh is not None:
                        # planes are (n_docs, O) either way: subset batches
                        # scattered by the unpack, full-store batches already
                        # in row order
                        REGISTRY.inc(
                            "mesh_windows_resident"
                            if planes[0].sharding.is_equivalent_to(
                                self.state.seq.sharding, 2)
                            else "mesh_windows_resharded")
                        fn = sharded.sharded_merge(
                            self.mesh, use_pallas, tile, interpret,
                            self._has_props, fuse_seg)
                        self.state = fn(self.state, planes, ms_dev) \
                            if fuse_seg else fn(self.state, planes)
                    else:
                        self.state = _columnar_merge_jit(
                            self.state, planes, ms_dev, use_pallas=use_pallas,
                            tile=tile, interpret=interpret,
                            with_props=self._has_props, fuse_compact=fuse_seg)
                if use_pallas:  # a fused zamboni walks every tile
                    REGISTRY.inc("merge_tiles_visited",
                                 tiles_total if fuse_seg else tiles_touched)
                    REGISTRY.inc("merge_tiles_total", tiles_total)
                pack_ms += pack.ms
                dispatch_ms += sp_up.ms + sp_unp.ms + sp_mrg.ms
                # drop the segment's device buffers here, inside the
                # span, not at the frame's teardown after it: releasing
                # the upload and the seven planes is part of the apply
                del dev, planes, ms_dev
                if slides:
                    # re-anchor the crossing docs off the device state AS OF
                    # this segment's end — one fused gather for all of them
                    # (the gather also drains the dispatch pipeline, so the
                    # planes it returns include this segment's ops)
                    with tracing.stage(rec, "store.slide_docs"):
                        self._slide_docs(slides)
            self._tab_release(pp)
            #: host-packing vs device-dispatch wall per columnar apply — the
            #: breakdown behind the serving throughput number (dispatches are
            #: async; device time is measured by the caller's end sync).
            #: ``prepack_ms`` is the payload/table build wall: when the wave
            #: came through the pipelined executor that work ran OFF the
            #: critical path (concurrent with the previous wave's dispatch)
            #: and pack_ms counts only the inline remainder.
            self.last_apply_stats = {
                "pack_ms": pack_ms,
                "prepack_ms": pp.prep_ms if prepacked is not None else 0.0,
                "dispatch_ms": dispatch_ms,
                "segments": len(segments),
            }
            _note_dispatch("columnar", dispatch_ms)
            if min_seq is not None and not fuse:
                self.compact(np.asarray(min_seq))

    def _pallas_choice(self):
        """(use_pallas, tile, interpret) for this store's dispatch policy.
        Annotate-bearing stores run the props specialization (K property
        planes in VMEM) at a halved tile — the extra planes eat VMEM.
        On a mesh, the tile must divide each shard's LOCAL doc block."""
        local_docs = self.n_docs if self.mesh is None \
            else self.n_docs // self.mesh.devices.size
        tile = pallas_tile_for(local_docs, self.capacity)
        mode = self.pallas
        use_pallas = (tile is not None and
                      (mode == "interpret" or
                       (mode == "auto" and
                        jax.default_backend() == "tpu")))
        if use_pallas and self._has_props and tile > 64:
            # props mode carries K extra planes + their temporaries in
            # VMEM: T=64 at K=4 fits at S=384 and S=512; at T=128 Mosaic
            # refuses S=512 (see _VMEM_BUDGET_BYTES)
            for smaller in (64, 32, 16, 8):
                if smaller <= tile and local_docs % smaller == 0:
                    tile = smaller
                    break
        # VMEM need scales with tile×capacity: halve the tile until the
        # model says it fits.
        over = lambda t: t * self.capacity * _VMEM_BYTES_PER_TILE_SLOT \
            > _VMEM_BUDGET_BYTES
        while tile is not None and tile > 8 and over(tile):
            nxt = tile // 2
            if local_docs % nxt != 0:
                break
            tile = nxt
        if use_pallas and tile is not None and over(tile):
            # no smaller dividing tile fits the scoped-VMEM budget (odd
            # doc factors, or large capacity even at T=8): an over-budget
            # Pallas launch fails compilation on a real TPU — take the
            # XLA scan path instead
            use_pallas = False
        if not use_pallas and mode == "auto" and not self._scan_warned \
                and jax.default_backend() == "tpu":
            # on a TPU the scan is a slower program than the one asked
            # for: say so, once per store
            self._scan_warned = True
            _log.warning(
                "TensorStringStore(%d docs per device, capacity %d, "
                "props=%s): no Pallas tile fits (tile=%s); dispatching "
                "the XLA scan", local_docs, self.capacity,
                self._has_props, tile)
        return use_pallas, (tile if tile is not None else 8), \
            (mode == "interpret")

    def _dispatch_apply(self, op_planes: tuple) -> None:
        """One device apply of dense (D, O) op planes, on the fused Pallas
        kernel when eligible (VERDICT r1 #1: the serving path runs the same
        kernel the headline measures), else the XLA scan."""
        use_pallas, tile, interpret = self._pallas_choice()
        t0 = time.perf_counter()
        if self.mesh is not None:
            from ..parallel.sharded import sharded_merge
            self.state = sharded_merge(
                self.mesh, use_pallas, tile, interpret, self._has_props,
                fuse_compact=False)(self.state, tuple(op_planes))
        elif use_pallas:
            self.state = _apply_pallas_jit(
                self.state, *op_planes, tile=tile, interpret=interpret,
                with_props=self._has_props)
        else:
            self.state = apply_string_batch_jit(
                self.state, *op_planes, with_props=self._has_props)
        _note_dispatch("pallas" if use_pallas else "batch",
                       (time.perf_counter() - t0) * 1000)

    def compact(self, min_seq) -> None:
        """Zamboni: free tombstones below the collaboration window."""
        # host array first: np.asarray on a device array is a device→host
        # read that would sync the whole dispatch pipeline
        ms_host = np.full((self.n_docs,), int(min_seq), np.int32) \
            if np.isscalar(min_seq) else np.asarray(min_seq, np.int32)
        ms = jnp.asarray(ms_host)
        self._reanchor_for_compact(ms_host)
        if self.mesh is not None:
            from ..parallel.sharded import sharded_compact
            self.state = sharded_compact(self.mesh, self._has_props)(
                self.state, ms)
        else:
            self.state = compact_string_state_jit(
                self.state, ms, with_props=self._has_props)
        for doc in self._iv_docs:
            self._prune_tombs(doc, int(ms_host[doc]))

    # ----------------------------------------------------------------- reads

    def _pull_doc(self, doc: int):
        """One fused device→host gather of a doc's read planes (each
        separate plane pull is its own dispatch and sync):
        (removed_seq, handle_op, handle_off, length, seq)
        trimmed to the doc's slot count. ``device_reads`` counts these —
        the read path's round-trip budget is asserted from it."""
        self.device_reads = getattr(self, "device_reads", 0) + 1
        REGISTRY.inc("device_reads")
        # (getattr: restore() builds stores via __new__)
        arr = np.asarray(_gather_doc_jit(self.state, doc))
        n = int(arr[5, 0])
        return tuple(arr[i, :n] for i in range(5))

    def read_text(self, doc: int) -> str:
        rem, hop, hoff, length, _ = self._pull_doc(doc)
        parts = []
        for i in range(len(rem)):
            if rem[i] != NOT_REMOVED:
                continue
            kind, text = self._payloads[hop[i]]
            if kind == _TEXT:
                parts.append(text[hoff[i]:hoff[i] + length[i]])
        return "".join(parts)

    def visible_length(self, doc: int) -> int:
        rem, _, _, length, _ = self._pull_doc(doc)
        return int(length[rem == NOT_REMOVED].sum())

    def visible_lengths(self) -> np.ndarray:
        """(D,) visible lengths of EVERY doc in one device round-trip (a
        per-doc loop pays D dispatches and syncs)."""
        return np.asarray(_visible_lengths_jit(self.state))

    @staticmethod
    def _slot_in_planes(rem, length, pos: int) -> int:
        """Slot index holding visible position ``pos`` in pulled planes
        (skip tombstones, accumulate live lengths) — the ONE visible-
        position resolver shared by every read."""
        at = 0
        for i in range(len(rem)):
            if rem[i] != NOT_REMOVED:
                continue
            if at <= pos < at + length[i]:
                return i
            at += length[i]
        raise IndexError(f"position {pos} beyond visible length {at}")

    def _slot_at(self, doc: int, pos: int) -> int:
        rem, _, _, length, _ = self._pull_doc(doc)
        return self._slot_in_planes(rem, length, pos)

    def seq_at(self, doc: int, pos: int) -> int:
        """Insert seq of the slot holding visible position ``pos`` — the
        attribution key (reference: merge-tree segments carry their seq;
        the device seq plane stores the same)."""
        rem, _, _, length, seqp = self._pull_doc(doc)
        return int(seqp[self._slot_in_planes(rem, length, pos)])

    def get_properties(self, doc: int, pos: int) -> dict:
        """Properties of the character at visible position pos (reference:
        ``SharedString.getPropertiesAtPosition``)."""
        i = self._slot_at(doc, pos)
        pv = np.asarray(self.state.prop_val[doc][i])
        return {key: self._prop_values.value(int(pv[plane]))
                for key, plane in self._prop_planes.items()
                if pv[plane] != 0}

    # -------------------------------------------------------- intervals
    # Anchored ranges over the served text (reference: IntervalCollection /
    # SequenceInterval with SlideOnRemove endpoints).

    def _doc_slots(self, doc: int):
        """(handle_op, handle_off, length, live) of active slots, host-side."""
        rem, hop, hoff, length, _ = self._pull_doc(doc)
        return hop, hoff, length, rem == NOT_REMOVED

    def _anchor_at(self, doc: int, pos: int):
        """Anchor of the visible character at pos (doc end → last visible
        char; empty doc → detached None), mirroring the oracle's _anchor."""
        hop, hoff, length, live = self._doc_slots(doc)
        at = 0
        last = None
        for i in range(len(hop)):
            if not live[i]:
                continue
            if at <= pos < at + length[i]:
                return (int(hop[i]), int(hoff[i]) + (pos - at))
            at += length[i]
            last = (int(hop[i]), int(hoff[i]) + int(length[i]) - 1)
        return last  # pos at/after doc end → last char; None if empty

    def _anchor_position(self, doc: int, anchor, slots=None) -> int:
        """Resolve an anchor with SLIDE semantics: a tombstoned anchor
        resolves to the nearest following live position (the live prefix at
        its slot), like the oracle's get_position. ``slots`` lets a caller
        resolving many anchors fetch the doc's planes once."""
        if anchor is None:
            return 0  # detached parks at document start
        h, off = anchor
        hop, hoff, length, live = slots if slots is not None \
            else self._doc_slots(doc)
        at = 0
        for i in range(len(hop)):
            if hop[i] == h and hoff[i] <= off < hoff[i] + length[i]:
                return at + (off - int(hoff[i])) if live[i] else at
            if live[i]:
                at += length[i]
        return at  # anchor's slot gone (shouldn't outlive compact re-anchor)

    def _floor_dooms_tombstone(self, doc: int) -> bool:
        """Does the current window floor reach a pending tombstone (so
        anchors must slide before more ops land)?"""
        tombs = self._iv_tombs[doc]
        return bool(tombs) and tombs[0] <= self._iv_min_seq[doc]

    def _slide_anchors_at_floor(self, doc: int) -> None:
        """Slide anchors off slots doomed by the current floor, then drop
        those tombstones from the heap (an already-slid tombstone never
        needs another slide)."""
        self._reanchor_for_compact(self._iv_min_seq, only_doc=doc)
        self._prune_tombs(doc, int(self._iv_min_seq[doc]))

    def _prune_tombs(self, doc: int, floor: int) -> None:
        tombs = self._iv_tombs[doc]
        while tombs and tombs[0] <= floor:
            heapq.heappop(tombs)

    def _seed_tombs(self, doc: int) -> None:
        """Rebuild the doc's tombstone heap from the device planes (on the
        first interval, or after restore): any resident removed_seq above
        the floor is a tombstone a future floor advance could doom."""
        st = self.state
        n = int(st.count[doc])
        removed = np.asarray(st.removed_seq[doc][:n])
        floor = self._iv_min_seq[doc]
        tombs = [int(s) for s in removed[removed != NOT_REMOVED]
                 if s > floor]
        heapq.heapify(tombs)
        self._iv_tombs[doc] = tombs

    def add_intervals_bulk(self, spans: Dict[int, list]
                           ) -> Dict[int, List[str]]:
        """Anchor many intervals across many docs with ONE fused device
        gather: ``spans`` maps doc row → [(start, end, props)].
        ``add_interval`` pays ≥2 device round trips per call (tomb seed +
        anchor pulls) — too many syncs for mass setup (e.g. loading an
        annotated corpus); this path pulls every target row's
        read planes in one dispatch and anchors host-side."""
        rows = np.asarray(sorted(spans), np.int32)
        if not len(rows):
            return {}
        n = len(rows)
        p2 = 1 << (n - 1).bit_length()
        rows_p = np.concatenate([rows, np.full(p2 - n, rows[0],
                                               np.int32)])
        g = [np.asarray(x)[:n] for x in
             _gather_rows_jit(self.state, jnp.asarray(rows_p))]
        self.device_reads = getattr(self, "device_reads", 0) + 1
        REGISTRY.inc("device_reads")
        removed_g, length_g = g[2], g[4]
        hop_g, hoff_g, count_g = g[5], g[6], g[8]
        out: Dict[int, List[str]] = {}
        for j, row in enumerate(map(int, rows)):
            cnt = int(count_g[j])
            removed = removed_g[j, :cnt]
            hop, hoff = hop_g[j, :cnt], hoff_g[j, :cnt]
            length = length_g[j, :cnt]
            live = removed == NOT_REMOVED
            if not self._intervals[row]:
                # seed tombs from the pulled planes (no extra read)
                floor = self._iv_min_seq[row]
                tombs = [int(s) for s in removed[removed != NOT_REMOVED]
                         if s > floor]
                heapq.heapify(tombs)
                self._iv_tombs[row] = tombs

            def anchor(pos: int):
                at = 0
                last = None
                for i in range(cnt):
                    if not live[i]:
                        continue
                    if at <= pos < at + length[i]:
                        return (int(hop[i]), int(hoff[i]) + (pos - at))
                    at += int(length[i])
                    last = (int(hop[i]),
                            int(hoff[i]) + int(length[i]) - 1)
                return last

            ids = []
            for start, end, props in spans[row]:
                self._interval_counter += 1
                iid = f"iv{self._interval_counter}"
                self._intervals[row][iid] = (anchor(start), anchor(end),
                                             dict(props or {}))
                ids.append(iid)
            self._iv_docs.add(row)
            out[row] = ids
        return out

    def add_interval(self, doc: int, start: int, end: int,
                     props: Optional[dict] = None) -> str:
        if not self._intervals[doc]:
            self._seed_tombs(doc)  # bookkeeping starts at the first interval
        self._interval_counter += 1
        iid = f"iv{self._interval_counter}"
        self._intervals[doc][iid] = (self._anchor_at(doc, start),
                                     self._anchor_at(doc, end),
                                     dict(props or {}))
        self._iv_docs.add(doc)
        return iid

    def remove_interval(self, doc: int, iid: str) -> None:
        del self._intervals[doc][iid]
        if not self._intervals[doc]:
            self._iv_docs.discard(doc)

    def interval_endpoints(self, doc: int, iid: str):
        a, b, _props = self._intervals[doc][iid]
        slots = self._doc_slots(doc)
        return (self._anchor_position(doc, a, slots),
                self._anchor_position(doc, b, slots))

    def intervals(self, doc: int) -> dict:
        slots = self._doc_slots(doc)
        return {iid: (self._anchor_position(doc, a, slots),
                      self._anchor_position(doc, b, slots), dict(props))
                for iid, (a, b, props) in self._intervals[doc].items()}

    def advance_min_seq(self, doc: int, min_seq: int) -> None:
        """Window-floor advance that arrived outside the op stream (NOOP
        heartbeats at the serving engine): slide this doc's anchors now, at
        the crossing, exactly as an in-stream advance would."""
        if not self._intervals[doc] or min_seq <= self._iv_min_seq[doc]:
            return
        self._iv_min_seq[doc] = min_seq
        if self._floor_dooms_tombstone(doc):
            self._slide_anchors_at_floor(doc)

    def _interval_scan(self, rows, kind, seq, min_ops):
        """Host-side crossing scan for a columnar batch (mirrors
        ``apply_messages``'s per-message bookkeeping, vectorized): walk
        each interval-holding row's op columns, advance the doc's window
        floor from the per-op ``min_ops`` plane, and whenever the floor
        crosses a pending tombstone record a segment boundary AFTER that
        column (the crossing op itself lands before the slide, exactly as
        the oracle applies the crossing message before sliding). Removes
        feed the tombstone heap AFTER the crossing check (a remove's own
        seq can never be ≤ the floor it ships with).

        Returns {boundary_col: ((doc, floor_at_crossing), ...)}; mutates
        the heaps and floors. With ``min_ops=None`` only the heaps are
        fed (floor advances arrive via advance_min_seq/compact)."""
        splits: Dict[int, list] = {}
        rem_k = int(OpKind.STR_REMOVE)
        noop_k = int(OpKind.NOOP)
        iv = self._iv_docs
        for i, d in enumerate(map(int, rows)):
            if d not in iv:
                continue
            krow = kind[i]
            rem_mask = krow == rem_k
            if min_ops is None:
                tombs = self._iv_tombs[d]
                for j in map(int, np.flatnonzero(rem_mask)):
                    heapq.heappush(tombs, int(seq[i, j]))
                continue
            mrow = min_ops[i]
            floor = self._iv_min_seq[d]
            cand = np.flatnonzero(rem_mask
                                  | ((krow != noop_k) & (mrow > floor)))
            if not len(cand):
                continue
            tombs = self._iv_tombs[d]
            for j in map(int, cand):
                m = int(mrow[j])
                if m > floor:
                    floor = m
                    if tombs and tombs[0] <= floor:
                        splits.setdefault(j + 1, []).append((d, floor))
                        while tombs and tombs[0] <= floor:
                            heapq.heappop(tombs)
                if rem_mask[j]:
                    heapq.heappush(tombs, int(seq[i, j]))
            self._iv_min_seq[d] = floor
        return {b: tuple(v) for b, v in splits.items()}

    def _slide_docs(self, pairs) -> None:
        """Re-anchor a set of (doc, floor) crossings off the CURRENT
        device state with ONE fused gather (a per-doc plane pull is a
        sync each — this is the batched device apply's slide step, so it
        must not undo the columnar path's one-sync-per-batch design)."""
        if not pairs:
            return
        docs = np.asarray([d for d, _ in pairs], np.int32)
        n = len(docs)
        p2 = 1 << (n - 1).bit_length() if n > 1 else 1
        rows_p = np.concatenate([docs, np.full(p2 - n, docs[0], np.int32)])
        g = [np.asarray(x)[:n] for x in
             _gather_rows_jit(self.state, jnp.asarray(rows_p))]
        self.device_reads = getattr(self, "device_reads", 0) + 1
        REGISTRY.inc("device_reads")
        removed_g, length_g = g[2], g[4]
        hop_g, hoff_g, count_g = g[5], g[6], g[8]
        for j, (d, floor) in enumerate(pairs):
            cnt = int(count_g[j])
            self._reanchor_arrays(d, floor, removed_g[j, :cnt],
                                  hop_g[j, :cnt], hoff_g[j, :cnt],
                                  length_g[j, :cnt])

    def _reanchor_arrays(self, doc: int, floor: int, removed, hop, hoff,
                         length) -> None:
        """Slide this doc's anchors off slots doomed at ``floor`` using
        already-pulled planes: to the first following live char, else the
        last preceding live char, else detach (oracle _slide_refs
        rules). Locates are vectorized compares, not Python slot walks."""
        doomed = removed <= floor
        if not doomed.any():
            return
        live_idx = np.flatnonzero(removed == NOT_REMOVED)
        hi = hoff + length

        def slide(i):
            k = np.searchsorted(live_idx, i + 1)
            if k < len(live_idx):           # first following live char
                j = live_idx[k]
                return (int(hop[j]), int(hoff[j]))
            k = np.searchsorted(live_idx, i) - 1
            if k >= 0:                      # last preceding live char
                j = live_idx[k]
                return (int(hop[j]), int(hi[j]) - 1)
            return None                     # no live text: detach

        for iid, (a, b, props) in list(self._intervals[doc].items()):
            new = []
            for anchor in (a, b):
                if anchor is not None:
                    h, off = anchor
                    hit = np.flatnonzero((hop == h) & (hoff <= off)
                                         & (off < hi))
                    if len(hit) and doomed[hit[0]]:
                        anchor = slide(int(hit[0]))
                new.append(anchor)
            self._intervals[doc][iid] = (new[0], new[1], props)

    def _reanchor_for_compact(self, min_seq: np.ndarray,
                              only_doc: Optional[int] = None) -> None:
        """Before zamboni drops tombstones at or below min_seq, move anchors
        off doomed slots (oracle _slide_refs rules). Only docs whose
        tombstone heap is actually doomed by the new floor pull device
        planes — and all of them share ONE fused gather."""
        docs = self._iv_docs if only_doc is None else (only_doc,)
        pairs = []
        for doc in docs:
            if not self._intervals[doc]:
                continue
            floor = int(min_seq[doc])
            tombs = self._iv_tombs[doc]
            if tombs and tombs[0] <= floor:
                pairs.append((doc, floor))
        self._slide_docs(pairs)

    # ------------------------------------------------- overflow recovery

    def adopt_doc(self, row: int, tmp: "TensorStringStore",
                  src_row: int = 0) -> None:
        """Adopt row ``src_row`` of ``tmp``'s rebuilt state into ``row`` —
        the re-upload step of the overflow escape hatch (SURVEY.md §7
        risk (b)): payload handles re-intern into this store's table, the
        per-doc client map transfers wholesale (client indexes are
        doc-local, so client/removers planes carry over bit-exact),
        property planes remap by key, and the row's device planes are
        overwritten in one jitted update that also clears the sticky
        overflow flag. The source row must fit: count ≤ capacity and no
        overflow."""
        n = int(np.asarray(tmp.state.count[src_row]))
        assert n <= self.capacity and not tmp.overflowed()[src_row]
        planes = {k: np.asarray(getattr(tmp.state, k)[src_row][:n]).copy()
                  for k in _PLANES}
        planes["handle_op"] = self.remap_payload_handles(
            tmp, planes["handle_op"])
        self._client_idx[row] = dict(tmp._client_idx[src_row])
        self._cidx_cache = None  # the row's client-index map changed

        prop = np.zeros((self.capacity, self.n_props), np.int32)
        if tmp._has_props:
            self._has_props = True
            self.remap_props(tmp,
                             np.asarray(tmp.state.prop_val[src_row][:n]),
                             prop)

        def pad(a, fill=0):
            out = np.full((self.capacity,) + a.shape[1:], fill, np.int32)
            out[:n] = a
            return out

        self.state = _write_row_jit(
            self.state, jnp.int32(row),
            *(jnp.asarray(pad(planes[k],
                              NOT_REMOVED if k == "removed_seq" else 0))
              for k in _PLANES),
            jnp.asarray(prop), jnp.int32(n))
        # interval bookkeeping restarts from the rebuilt planes
        if self._intervals[row]:
            self._seed_tombs(row)

    def clear_doc(self, row: int) -> None:
        """Empty a row (used when a doc graduates off this store): planes
        zero, overflow flag cleared."""
        z = np.zeros((self.capacity,), np.int32)
        self.state = _write_row_jit(
            self.state, jnp.int32(row),
            *(jnp.asarray(np.full_like(z, NOT_REMOVED)
                          if k == "removed_seq" else z) for k in _PLANES),
            jnp.asarray(np.zeros((self.capacity, self.n_props), np.int32)),
            jnp.int32(0))

    def overflowed(self) -> np.ndarray:
        return np.asarray(self.state.overflow)

    def slot_usage(self) -> np.ndarray:
        return np.asarray(self.state.count)

    def digests(self) -> np.ndarray:
        return np.asarray(string_state_digest(self.state))

    # ----------------------------------------------------- snapshot / resume

    _SNAP_PLANES = StringOpInterner.SNAP_PLANES

    def snapshot(self) -> dict:
        """Device→host gather of the merged state plus the host interning
        tables (reference: channel ``summarize()``; SURVEY.md §7.7 — the
        Summarizer reuses the same kernels: resume = ``restore`` + tail
        replay through ``apply_messages``). Compact first for a minimal
        snapshot. Planes are trimmed to the widest doc's slot count."""
        st = self.state
        counts = np.asarray(st.count)
        n = max(int(counts.max()), 1)
        return {
            "planes": {k: np.asarray(getattr(st, k))[:, :n].copy()
                       for k in self._SNAP_PLANES},
            "count": counts.copy(),
            "overflow": np.asarray(st.overflow).copy(),
            "capacity": self.capacity,
            "n_props": self.n_props,
            "payloads": list(self._payloads),
            "client_idx": [dict(m) for m in self._client_idx],
            "prop_planes": dict(self._prop_planes),
            "prop_values": self._prop_values.export(),
            "has_props": self._has_props,
            "intervals": [{iid: [list(a) if a else None,
                                 list(b) if b else None, props]
                           for iid, (a, b, props) in per_doc.items()}
                          for per_doc in self._intervals],
            "interval_counter": self._interval_counter,
            "iv_min_seq": self._iv_min_seq.tolist(),
        }

    def snapshot_rows(self, rows, payloads_base: int,
                      prop_values_base: int) -> dict:
        """Incremental snapshot: ONLY the given doc rows' planes (one
        fused device→host gather) plus the append-only interner DELTAS
        since the last summary (``payloads_base`` / ``prop_values_base``
        are the table lengths recorded then). Clean rows are represented
        by reference to the previous summary — the handle-reuse half of
        SURVEY.md §2.16. Intervals ride in full (they mutate outside the
        op stream, so cheap full inclusion beats tracking)."""
        rows = np.ascontiguousarray(rows, np.int32)
        if len(rows):
            # pad the row list to a power of two (repeating row 0) so the
            # gather jit compiles one program per BUCKET, not one per
            # distinct dirty-row count
            n = len(rows)
            p2 = 1 << (n - 1).bit_length()
            rows_p = np.concatenate(
                [rows, np.full(p2 - n, rows[0], np.int32)])
            g = [np.asarray(x)[:n] for x in
                 _gather_rows_jit(self.state, jnp.asarray(rows_p))]
            w = max(int(g[8].max()), 1)
            planes = {k: g[i][:, :w].copy()
                      for i, k in enumerate(self._SNAP_PLANES)}
            counts, overflow = g[8].copy(), g[9].copy()
        else:
            planes = {k: np.zeros((0, 1), np.int32)
                      for k in self._SNAP_PLANES}
            counts = overflow = np.zeros((0,), np.int32)
        return {
            "rows": rows,
            "planes": planes,
            "count": counts,
            "overflow": overflow,
            "payloads_delta": list(self._payloads[payloads_base:]),
            "client_idx": {int(r): dict(self._client_idx[int(r)])
                           for r in rows},
            "prop_planes": dict(self._prop_planes),
            "prop_values_delta":
                self._prop_values.export_from(prop_values_base),
            "has_props": self._has_props,
            "intervals": [{iid: [list(a) if a else None,
                                 list(b) if b else None, props]
                           for iid, (a, b, props) in per_doc.items()}
                          for per_doc in self._intervals],
            "interval_counter": self._interval_counter,
            "iv_min_seq": self._iv_min_seq.tolist(),
        }

    def apply_row_snapshot(self, delta: dict) -> None:
        """Fold one ``snapshot_rows`` delta into this (restored-base)
        store: overwrite the dirty rows' device planes in one dispatch,
        extend the append-only interner tables, replace interval state."""
        self._payloads.extend(tuple(p) for p in delta["payloads_delta"])
        self._payload_chars += sum(
            len(p[1]) for p in delta["payloads_delta"])
        self._prop_planes = dict(delta["prop_planes"])
        self._prop_values.extend_from(delta["prop_values_delta"])
        self._has_props = self._has_props or delta["has_props"]
        # the plane map was replaced wholesale and dirty rows get new
        # client maps below — packed-props and client-lut caches are stale
        self._props_pack_cache = {}
        self._cidx_cache = None
        rows = np.asarray(delta["rows"], np.int32)
        if len(rows):
            for r, m in delta["client_idx"].items():
                self._client_idx[int(r)] = dict(m)
            w = delta["planes"]["seq"].shape[1]
            # power-of-two row bucket (repeat row 0 with its own values —
            # a duplicate scatter of identical values is a no-op): one
            # compiled scatter per bucket, not per dirty-row count
            n = len(rows)
            p2 = 1 << (n - 1).bit_length()
            rows_p = np.concatenate(
                [rows, np.full(p2 - n, rows[0], np.int32)])

            def bucket(a):
                return np.concatenate(
                    [a, np.repeat(a[:1], p2 - n, axis=0)]) if p2 > n else a

            def pad(a, fill=0):
                out = np.full((p2, self.capacity) + a.shape[2:],
                              fill, np.int32)
                out[:n, :w] = a
                out[n:] = out[:1]
                return jnp.asarray(out)

            prop = np.zeros((p2, self.capacity, self.n_props), np.int32)
            if "prop_val" in delta["planes"]:
                pv = delta["planes"]["prop_val"]
                prop[:n, :pv.shape[1]] = pv
                prop[n:] = prop[:1]
            self.state = _write_rows_jit(
                self.state, jnp.asarray(rows_p),
                *(pad(delta["planes"][k],
                      NOT_REMOVED if k == "removed_seq" else 0)
                  for k in _PLANES),
                jnp.asarray(prop), jnp.asarray(bucket(delta["count"])),
                jnp.asarray(bucket(delta["overflow"])))
        self._intervals = [
            {iid: (tuple(a) if a else None, tuple(b) if b else None,
                   dict(props))
             for iid, (a, b, props) in per_doc.items()}
            for per_doc in delta["intervals"]]
        self._interval_counter = delta["interval_counter"]
        self._iv_min_seq = np.asarray(delta["iv_min_seq"], np.int64)
        self._iv_docs = {d for d in range(self.n_docs)
                         if self._intervals[d]}
        for d in self._iv_docs:
            self._seed_tombs(d)

    @classmethod
    def restore(cls, snap: dict, mesh=None) -> "TensorStringStore":
        """Rebuild a store from ``snapshot()`` output: planes are padded
        back to capacity and re-uploaded; merging resumes mid-stream.
        Skips __init__'s device allocation (the snapshot fully replaces it)."""
        n_docs = snap["count"].shape[0]
        store = cls.__new__(cls)
        store.n_docs = n_docs
        store.capacity = snap["capacity"]
        store.n_props = snap["n_props"]
        store.mesh = mesh
        cap = snap["capacity"]
        full = {}
        for k in cls._SNAP_PLANES:
            small = np.asarray(snap["planes"][k])
            shape = (n_docs, cap) + small.shape[2:]
            fill = NOT_REMOVED if k == "removed_seq" else 0
            plane = np.full(shape, fill, np.int32)
            plane[:, :small.shape[1]] = small
            full[k] = jnp.asarray(plane)
        store.state = StringState(
            **full, count=jnp.asarray(snap["count"]),
            overflow=jnp.asarray(snap["overflow"]))
        if mesh is not None:
            from ..parallel.sharded import shard_store_state
            store.state = shard_store_state(store.state, mesh)
        store._payloads = [tuple(p) for p in snap["payloads"]]
        store._payload_chars = sum(len(p[1]) for p in store._payloads)
        store._client_idx = [dict(m) for m in snap["client_idx"]]
        store._prop_planes = dict(snap["prop_planes"])
        store._prop_values = ValueInterner.restore(snap["prop_values"])
        store._has_props = snap["has_props"]
        store._intervals = [
            {iid: (tuple(a) if a else None, tuple(b) if b else None,
                   dict(props))
             for iid, (a, b, props) in per_doc.items()}
            for per_doc in snap.get("intervals",
                                    [{} for _ in range(n_docs)])]
        store._interval_counter = snap.get("interval_counter", 0)
        store.last_profile = None
        store.last_rich_wire = None
        store.unpack_variants = set()
        store._props_pack_cache = {}
        store._cidx_cache = None
        store._tab_pool = {}
        store.device_reads = 0
        store._iv_min_seq = np.asarray(
            snap.get("iv_min_seq", [0] * n_docs), np.int64)
        store._iv_tombs = [[] for _ in range(n_docs)]
        store._iv_docs = {d for d in range(n_docs)
                          if store._intervals[d]}
        for d in store._iv_docs:
            store._seed_tombs(d)
        return store
