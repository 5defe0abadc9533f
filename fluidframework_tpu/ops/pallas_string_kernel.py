"""Pallas TPU kernel for the batched merge-tree apply: VMEM-resident op loop.

The XLA path (``apply_string_batch``) scans the op axis with the state planes
round-tripping through HBM on every op: one 64-op batch moves the whole
(D, S) state 128 times. This kernel tiles the doc axis, loads one tile's
planes into VMEM ONCE, applies the entire op batch with a ``fori_loop``
inside the kernel, and writes the planes back ONCE — turning O(ops) HBM
traffic into O(1) per batch. The per-op math is literally the same
``_insert_one`` / ``_range_one`` helpers as the XLA path (vmapped over the
tile's docs), so semantics are shared by construction, not re-derived.

Which tiles: the grid has one step a tile, but the plain merge visits only
the tiles that hold an op. The pass is bound by arithmetic (every column is
applied to every row of a visited tile, op or no op), so its cost is tiles ×
columns, and a door's window touches a few hundred neighbouring rows of a
store of tens of thousands. ``_touched_tiles`` derives the list on the
device from the ``kind`` plane the call already gets (no host work, nothing
new on the wire, shard-local under ``shard_map``); it is scalar-prefetched,
the block index maps read ``tiles[i]``, and the steps past the list's end
skip the body and name the block of the step before them, so the pipeline
moves nothing for them. The state planes are aliased in place: a tile no
step visits is neither read nor written. The grid's length stays static
(``D // tile``), so the program's key is what it was. A batch with an op in
every tile visits every tile, as before. The variants with the zamboni
fused in walk every tile (their list is the identity): compaction rewrites
tiles that have no op.

Two specializations: no-props (stores that have never seen an annotate —
``TensorStringStore._has_props`` False, the mode the north-star benchmark
measures; property planes thread through untouched host-side) and props
(``with_props=True``: the K property planes ride along in VMEM, so
annotate-heavy workloads — rich text, config #5 — stay on the fused path).

VMEM budget per tile: 7 planes × T×S int32 + op planes × T×O + live
temporaries — which tiles Mosaic accepts at which capacity is recorded at
``string_store._VMEM_BUDGET_BYTES``; how fast each runs is not measured on
the current code and machine.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .merge_tree_kernel import (
    _PLANES, StringState, _cumsum, _insert_one, _range_one,
)
from .schema import OpKind

_OPS = 7      # kind, a0, a1, a2, seq, client, ref_seq
_NP = len(_PLANES)


def _compact(c, min_seq, keys=_PLANES):
    """In-VMEM zamboni: stable stream compaction by bit-decomposed shifts.

    Drop slots whose removal is acked at or below min_seq. Each surviving
    slot must move left by d = (dropped slots before it) — non-decreasing
    in slot index, and any two kept slots with displacement difference δ
    are at least δ+1 apart, so shifting every slot whose d has bit b by
    2^b (LSB→MSB) never collides. log2(S) roll+select passes, no sort, no
    gather. Vacated slots are zeroed (removed_seq=NOT_REMOVED) — like the
    XLA sort path, slots at or beyond count are semantically ignored.
    ``keys`` lists the 2-D (T, S) planes to move (props mode adds the
    unstacked property planes)."""
    from ..core.constants import NOT_REMOVED
    S = c["seq"].shape[-1]
    active = _iota2(c["seq"].shape) < c["count"][:, None]
    keep = active & ~(c["removed_seq"] <= min_seq[:, None])
    # dropped-before count: exclusive prefix sum of ~keep over active slots
    dropped = jnp.where(active & ~keep, 1, 0)
    d = _excl_cumsum_last(dropped)

    occ = keep
    planes = {k: c[k] for k in keys}
    idx = _iota2(c["seq"].shape)
    step = 1
    while step < S:
        b_set = occ & (((d // step) % 2) == 1)
        # mask the roll's wraparound: position p receives from p+step only
        # when p+step is in range (the head wrapping to the tail must not
        # masquerade as an incoming element). Roll an int32 mask — Mosaic
        # cannot roll i1 vectors.
        b_set_i = jnp.where(b_set, 1, 0)
        moves_in = (jnp.roll(b_set_i, -step, axis=-1) == 1) & \
            (idx < S - step)
        stays = occ & ~b_set
        for k in keys:
            incoming = jnp.roll(planes[k], -step, axis=-1)
            planes[k] = jnp.where(moves_in, incoming,
                                  jnp.where(stays, planes[k], 0))
        d = jnp.where(moves_in, jnp.roll(d, -step, axis=-1), d)
        occ = moves_in | stays
        step *= 2
    planes["removed_seq"] = jnp.where(occ, planes["removed_seq"],
                                      NOT_REMOVED)
    out = dict(c)
    out.update(planes)
    out["count"] = jnp.sum(keep.astype(jnp.int32), axis=-1)
    return out


def _iota2(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def _excl_cumsum_last(x):
    """Exclusive prefix sum along the last axis: the shared Hillis-Steele
    inclusive scan, shifted right by one."""
    c = _cumsum(x)
    return jnp.where(_iota2(x.shape) == 0, 0, jnp.roll(c, 1, axis=-1))


def _touched_tiles(kind, tile: int):
    """(tiles, n_active) of a (D, O) ``kind`` plane: the indices of the
    tiles holding a non-NOOP op, ascending, padded to ``D // tile`` with the
    last of them (a padded step then names the block its predecessor named),
    and their number, at least 1: a plane without a valid op — three shards
    in four of a mesh whose window lies in one — runs tile 0, whose NOOPs
    leave it as it was (``pick``'s last branch), so that step 0's output
    block is written back from VMEM the body filled."""
    n_tiles = kind.shape[0] // tile
    has = (kind != int(OpKind.NOOP)).reshape(n_tiles, -1).any(axis=1)
    n_active = jnp.maximum(jnp.sum(has, dtype=jnp.int32), 1)
    found, = jnp.nonzero(has, size=n_tiles, fill_value=0)
    found = found.astype(jnp.int32)
    step = jnp.arange(n_tiles, dtype=jnp.int32)
    return jnp.where(step < n_active, found, found[n_active - 1]), \
        n_active.reshape(1)


def _kernel(tiles_ref, n_ref, *refs, compact: bool, n_props: int):
    """The body of one grid step, run while the step lies inside the tile
    list (``n_ref``; the index maps read ``tiles_ref``).

    n_props=0: the no-props specialization (property planes untouched
    host-side). n_props=K: the K property planes ride along in VMEM as K
    extra (T, S) refs, moved by the same split/shift/compact passes."""
    del tiles_ref
    pl.when(pl.program_id(0) < n_ref[0])(
        functools.partial(_merge_tile, refs, compact, n_props))


def _merge_tile(refs, compact: bool, n_props: int):
    if compact:
        ms_ref, refs = refs[0], refs[1:]
    np_ = _NP + n_props
    op_refs = refs[:_OPS]
    plane_refs = refs[_OPS:_OPS + np_]
    cnt_ref, ovf_ref = refs[_OPS + np_:_OPS + np_ + 2]
    out_plane_refs = refs[_OPS + np_ + 2:_OPS + 2 * np_ + 2]
    out_cnt_ref, out_ovf_ref = refs[_OPS + 2 * np_ + 2:]
    with_props = n_props > 0

    n_ops = op_refs[0].shape[1]
    ops = tuple(r[:] for r in op_refs)              # each (T, O), VMEM
    lane = jax.lax.broadcasted_iota(jnp.int32, ops[0].shape, 1)
    carry = dict(zip(_PLANES, (r[:] for r in plane_refs[:_NP])))
    if with_props:
        # K separate (T, S) planes — a stacked (T, S, K) would lane-pad
        # the minor dim to 128 in VMEM (~32× bloat); see _prop_keys
        for i in range(n_props):
            carry[f"prop{i}"] = plane_refs[_NP + i][:]
    else:
        # dummy 1-wide prop plane: with_props=False helpers pass it through
        carry["prop_val"] = jnp.zeros(carry["seq"].shape + (1,), jnp.int32)
    carry["count"] = cnt_ref[:, 0]
    carry["overflow"] = ovf_ref[:, 0]

    def body(o, c):
        # one-hot column extraction: Mosaic supports neither dynamic_slice
        # on values nor unaligned dynamic lane indexing on refs
        take = lambda x: jnp.sum(jnp.where(lane == o, x, 0), axis=1)
        k, p0, p1, p2, sq, cl, rs = (take(x) for x in ops)
        ins = jax.vmap(functools.partial(_insert_one, with_props=with_props)
                       )(c, p0, p1, p2, sq, cl, rs)
        rng = jax.vmap(functools.partial(_range_one, with_props=with_props)
                       )(c, k, p0, p1, p2, sq, cl, rs)

        def pick(key):
            tail = (1,) * (c[key].ndim - 1)
            is_ins = (k == int(OpKind.STR_INSERT)).reshape((-1,) + tail)
            is_rng = ((k == int(OpKind.STR_REMOVE)) |
                      (k == int(OpKind.STR_ANNOTATE))).reshape((-1,) + tail)
            return jnp.where(is_ins, ins[key],
                             jnp.where(is_rng, rng[key], c[key]))

        return {key: pick(key) for key in c}

    out = jax.lax.fori_loop(0, n_ops, body, carry)
    prop_keys = tuple(f"prop{i}" for i in range(n_props))
    if compact:
        out = _compact(out, ms_ref[:, 0], keys=_PLANES + prop_keys)
    for name, ref in zip(_PLANES + prop_keys, out_plane_refs):
        ref[:] = out[name]
    out_cnt_ref[:, 0] = out["count"]
    out_ovf_ref[:, 0] = out["overflow"]


def apply_string_batch_pallas(state: StringState, kind, a0, a1, a2, seq,
                              client, ref_seq, min_seq=None, tile: int = 128,
                              interpret: bool = False,
                              with_props: bool = False) -> StringState:
    """Drop-in equivalent of ``apply_string_batch``, optionally fused with
    zamboni: pass ``min_seq`` (D,) to compact each doc inside the kernel
    epilogue while the planes are still in VMEM — one dispatch, one HBM
    round-trip for apply + compact.

    ``with_props=False`` is the annotate-free specialization (property
    planes thread through untouched host-side); ``with_props=True`` loads
    the K property planes into VMEM alongside the rest, so annotate-bearing
    stores stay on the fused path too.

    Without ``min_seq`` only the tiles that hold a non-NOOP op are visited
    (module docstring): every other row comes out bit for bit as it went
    in, in every slot. A batch without any valid op runs one tile of NOOPs.

    D must divide by ``tile``; S should be a multiple of 128 (lane width).
    ``interpret=True`` runs the Pallas interpreter (CPU tests)."""
    D, S = state.seq.shape
    O = kind.shape[1]
    assert D % tile == 0, f"doc count {D} not divisible by tile {tile}"
    compact = min_seq is not None
    K = state.prop_val.shape[2] if with_props else 0
    np_ = _NP + K

    n_tiles = D // tile
    if compact:
        tiles = jnp.arange(n_tiles, dtype=jnp.int32)
        n_active = jnp.full((1,), n_tiles, jnp.int32)
    else:
        tiles, n_active = _touched_tiles(kind, tile)

    def block(width):
        return pl.BlockSpec((tile, width), lambda i, tiles, n: (tiles[i], 0),
                            memory_space=pltpu.VMEM)

    op_spec, plane_spec, col_spec = block(O), block(S), block(1)
    n_lead = 1 if compact else 0
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles,),
        in_specs=[col_spec] * n_lead + [op_spec] * _OPS
        + [plane_spec] * np_ + [col_spec] * 2,
        out_specs=tuple([plane_spec] * np_ + [col_spec] * 2),
    )
    out_shape = tuple(
        [jax.ShapeDtypeStruct((D, S), jnp.int32)] * np_
        + [jax.ShapeDtypeStruct((D, 1), jnp.int32)] * 2)

    # donate the state planes into the outputs (in-place update in HBM);
    # operand numbers count the two prefetched scalars
    aliases = {2 + n_lead + _OPS + i: i for i in range(np_ + 2)}
    lead = (jnp.asarray(min_seq, jnp.int32)[:, None],) if compact else ()
    prop_in = tuple(state.prop_val[:, :, i] for i in range(K))
    # a stable name by variant, so that a device trace tells the plain
    # merge from the one with the zamboni fused in
    name = "string_merge" + ("_zamboni" if compact else "") \
        + ("_props" if with_props else "")
    outs = pl.pallas_call(
        functools.partial(_kernel, compact=compact, n_props=K),
        grid_spec=grid_spec, out_shape=out_shape,
        input_output_aliases=aliases, interpret=interpret, name=name,
    )(tiles, n_active, *lead, kind, a0, a1, a2, seq, client, ref_seq,
      *(getattr(state, k) for k in _PLANES), *prop_in,
      state.count[:, None], state.overflow[:, None])

    planes = dict(zip(_PLANES, outs[:_NP]))
    prop_val = jnp.stack(outs[_NP:np_], axis=-1) if with_props \
        else state.prop_val
    return StringState(**planes, prop_val=prop_val,
                       count=outs[np_][:, 0], overflow=outs[np_ + 1][:, 0])
