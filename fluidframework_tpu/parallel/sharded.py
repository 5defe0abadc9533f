"""Doc-axis sharding of the serving store: the product's multi-chip path.

Reference counterpart: Routerlicious scales by partitioning DOCUMENTS
across Kafka partitions and lambda instances (SURVEY.md §2.13/§2.14) —
documents are independent, so the TPU-native mapping is a 1-D ``docs``
mesh axis with every chip owning ``n_docs / n_chips`` rows of the
serving store's planes.

The merge kernel is per-doc math (vmap over docs, scan over ops, rolls
along the slot axis), so the sharded apply is expressed as a
``shard_map`` whose body is the SAME ``apply_string_batch`` /
``apply_string_batch_pallas`` the single-chip path runs — by
construction there is **zero cross-chip communication** on the apply
path (the dryrun asserts this from the compiled HLO). What does cross
chips: the host→device op buffer (5-8 B/op, broadcast), rare row
writes (overflow re-upload), and per-doc reads — all off the hot path.

``parallel/replicated.py`` layers the REPLICA axis (redundant copies +
digest agreement) on top; this module is the scale-out axis.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.merge_tree_kernel import (
    StringState, apply_string_batch, compact_string_state,
)
from ..ops.pallas_string_kernel import apply_string_batch_pallas
from .mesh import DOC_AXIS


def make_doc_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D ``docs`` mesh: each device owns a contiguous block of doc rows."""
    devices = jax.devices()
    n = n_devices or len(devices)
    return Mesh(np.array(devices[:n]), (DOC_AXIS,))


def doc_shard_count(mesh) -> int:
    """Doc-axis shard count of ``mesh`` (0 when it has no docs axis) —
    how many per-shard labeled collectors the health plane attaches."""
    try:
        return int(mesh.shape.get(DOC_AXIS, 0))
    except (AttributeError, TypeError):
        return 0


def shard_of_rows(rows, n_docs: int, n_shards: int):
    """Row → doc-shard index by contiguous block: the same row→device
    placement ``NamedSharding(P(DOC_AXIS, ...))`` produces, so the
    per-shard ``ops_applied`` rollups (ISSUE 4) credit the device that
    actually applied the op."""
    rows_per = max(1, n_docs // n_shards)
    return np.minimum(np.asarray(rows, np.int64) // rows_per,
                      n_shards - 1)


def doc_state_specs() -> StringState:
    """PartitionSpecs of every StringState plane on a docs-only mesh."""
    row = P(DOC_AXIS, None)
    return StringState(
        seq=row, client=row, removed_seq=row, removers=row, length=row,
        handle_op=row, handle_off=row, prop_val=P(DOC_AXIS, None, None),
        count=P(DOC_AXIS), overflow=P(DOC_AXIS),
    )


def shard_store_state(state: StringState, mesh: Mesh) -> StringState:
    """Place a store's planes onto the mesh, doc-row sharded."""
    if state.seq.shape[0] % mesh.devices.size != 0:
        raise ValueError(f"n_docs {state.seq.shape[0]} not divisible by "
                         f"mesh size {mesh.devices.size}")
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        state, doc_state_specs())


# jitted sharded programs, cached per (mesh, static flags) — the serving
# engine dispatches thousands of batches through the same few programs
_CACHE: dict = {}


def jit_cache_size() -> int:
    """Programs compiled so far by every sharded entry point built: what
    the store's dispatch accounting adds to its own jits' (a growth
    between two dispatches is an XLA compile paid on the hot path)."""
    return sum(fn._cache_size() for fn in _CACHE.values())


def replicated(buf, mesh: Mesh):
    """A host buffer put whole on every chip of the mesh (one
    host→device copy a chip): the placement ``sharded_unpack`` reads."""
    return jax.device_put(buf, NamedSharding(mesh, P()))


def sharded_unpack(mesh: Mesh, R: int, O: int, pos_wide: bool,
                   ref_wide: bool, rich: int, n_docs: int,
                   fuse_compact: bool, scatter_rows: bool,
                   compact8: bool = False, tab_n: int = 0):
    """The mesh placement of the columnar unpack: replicated packed buffer
    → the seven op planes as ``(n_docs, O)`` arrays sharded like the state
    (``P(docs, None)``), and the fused min_seq ``P(docs)`` (``None``
    without ``fuse_compact``), so the merge launch moves nothing. The
    arguments are ``string_store._columnar_unpack_jit``'s statics, the
    decode is the same ``decode_columnar``; every shard decodes the whole
    (small) window and keeps the rows of its own block, so no plane of the
    store's height exists on one chip and no collective appears."""
    key = ("unpack", mesh, R, O, pos_wide, ref_wide, rich, n_docs,
           fuse_compact, scatter_rows, compact8, tab_n)
    if key not in _CACHE:
        from ..ops.schema import OpKind
        from ..ops.string_store import decode_columnar
        local = n_docs // mesh.devices.size
        planes_spec = (P(DOC_AXIS, None),) * 7

        def body(buf):
            planes, rows, min_seq = decode_columnar(
                buf, R, O, pos_wide, ref_wide, rich, compact8, tab_n,
                n_docs if fuse_compact else 0)
            first = jax.lax.axis_index(DOC_AXIS) * local
            if scatter_rows:
                # another shard's rows go past this block's end, where
                # mode="drop" leaves them out (a negative index would wrap)
                at = rows - first
                at = jnp.where((at >= 0) & (at < local), at, local)

                def block(p, fill):
                    return jnp.full((local, O), fill, jnp.int32) \
                        .at[at].set(p, mode="drop")

                planes = (block(planes[0], int(OpKind.NOOP)),) + \
                    tuple(block(p, 0) for p in planes[1:])
            else:  # a full-store batch in row order: this shard's slice
                planes = tuple(
                    jax.lax.dynamic_slice_in_dim(p, first, local, axis=0)
                    for p in planes)
            if not fuse_compact:
                return planes
            return planes, jax.lax.dynamic_slice_in_dim(min_seq, first, local)

        @jax.jit
        def _sharded_columnar_unpack(buf):
            out = jax.shard_map(
                body, mesh=mesh, in_specs=P(),
                out_specs=(planes_spec, P(DOC_AXIS)) if fuse_compact
                else planes_spec)(buf)
            return out if fuse_compact else (out, None)
        _CACHE[key] = _sharded_columnar_unpack
    return _CACHE[key]


def sharded_merge(mesh: Mesh, use_pallas: bool, tile: int, interpret: bool,
                  with_props: bool, fuse_compact: bool):
    """The sharded columnar/message merge: (state, 7×(D,O) planes[, min_seq])
    → state. Body = the single-chip kernel on each shard's doc block."""
    key = ("merge", mesh, use_pallas, tile, interpret, with_props,
           fuse_compact)
    if key not in _CACHE:
        specs = doc_state_specs()
        planes_spec = (P(DOC_AXIS, None),) * 7

        if fuse_compact:
            @functools.partial(jax.jit, donate_argnums=0)
            def _sharded_columnar_merge(state, planes, ms):
                def body(state, planes, ms):
                    if use_pallas:
                        return apply_string_batch_pallas(
                            state, *planes, tile=tile, interpret=interpret,
                            min_seq=ms, with_props=with_props)
                    out = apply_string_batch(state, *planes,
                                             with_props=with_props)
                    return compact_string_state(out, ms, with_props)
                # check_vma=False: the Pallas body's output aval carries
                # no vma annotation (same setting as parallel/replicated.py)
                return jax.shard_map(
                    body, mesh=mesh,
                    in_specs=(specs, planes_spec, P(DOC_AXIS)),
                    out_specs=specs, check_vma=False)(state, planes, ms)
        else:
            @functools.partial(jax.jit, donate_argnums=0)
            def _sharded_columnar_merge(state, planes):
                def body(state, planes):
                    if use_pallas:
                        return apply_string_batch_pallas(
                            state, *planes, tile=tile, interpret=interpret,
                            with_props=with_props)
                    return apply_string_batch(state, *planes,
                                              with_props=with_props)
                return jax.shard_map(
                    body, mesh=mesh, in_specs=(specs, planes_spec),
                    out_specs=specs, check_vma=False)(state, planes)
        _CACHE[key] = _sharded_columnar_merge
    return _CACHE[key]


def sharded_compact(mesh: Mesh, with_props: bool):
    """Sharded zamboni: (state, (D,) min_seq) → state, per-shard compact."""
    key = ("compact", mesh, with_props)
    if key not in _CACHE:
        specs = doc_state_specs()

        @functools.partial(jax.jit, donate_argnums=0)
        def _sharded_compact(state, ms):
            return jax.shard_map(
                lambda s, m: compact_string_state(s, m, with_props),
                mesh=mesh, in_specs=(specs, P(DOC_AXIS)),
                out_specs=specs, check_vma=False)(state, ms)
        _CACHE[key] = _sharded_compact
    return _CACHE[key]


def map_state_specs():
    """PartitionSpecs of every MapState plane on a docs-only mesh."""
    from ..ops.map_kernel import MapState
    row = P(DOC_AXIS, None)
    return MapState(present=row, value=row, last_seq=row)


def shard_map_store_state(state, mesh: Mesh):
    """Place a map store's planes onto the mesh, doc-row sharded."""
    if state.present.shape[0] % mesh.devices.size != 0:
        raise ValueError(f"n_docs {state.present.shape[0]} not divisible "
                         f"by mesh size {mesh.devices.size}")
    return jax.tree.map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
        state, map_state_specs())


def sharded_map_merge(mesh: Mesh):
    """The doc-sharded columnar map apply (collective-free shard_map of
    the per-doc LWW reduction); one program per mesh — jit specializes
    on plane shapes."""
    key = ("map_merge", mesh)
    if key not in _CACHE:
        from ..ops.map_kernel import apply_map_batch
        specs = map_state_specs()

        @functools.partial(jax.jit, donate_argnums=0)
        def fn(state, planes):
            return jax.shard_map(
                apply_map_batch, mesh=mesh,
                in_specs=(specs,) + (P(DOC_AXIS, None),) * 4,
                out_specs=specs, check_vma=False)(state, *planes)
        _CACHE[key] = fn
    return _CACHE[key]


def tree_state_specs():
    """PartitionSpecs of every TreeState plane on a docs-only mesh."""
    from ..ops.tree_kernel import TreeState
    row = P(DOC_AXIS, None)
    return TreeState(node_id=row, parent=row, field=row, value=row,
                     type_=row, prev_sib=row, next_sib=row,
                     created_seq=row, overflow=P(DOC_AXIS))


def shard_tree_store_state(state, mesh: Mesh):
    """Place a tree store's planes onto the mesh, doc-row sharded."""
    if state.node_id.shape[0] % mesh.devices.size != 0:
        raise ValueError(f"n_docs {state.node_id.shape[0]} not divisible "
                         f"by mesh size {mesh.devices.size}")
    return jax.tree.map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
        state, tree_state_specs())


def sharded_tree_apply(mesh: Mesh):
    """The doc-sharded packed-plane tree apply: shard_map of the SAME
    single-chip record scan over each shard's doc block (tree merge is
    per-doc math — collective-free by construction)."""
    key = ("tree_apply", mesh)
    if key not in _CACHE:
        from ..ops.tree_kernel import apply_tree_planes
        specs = tree_state_specs()

        @functools.partial(jax.jit, donate_argnums=0)
        def fn(state, planes):
            return jax.shard_map(
                apply_tree_planes, mesh=mesh,
                in_specs=(specs, P(None, DOC_AXIS, None)),
                out_specs=specs, check_vma=False)(state, planes)
        _CACHE[key] = fn
    return _CACHE[key]


def axis_state_specs():
    """PartitionSpecs of the matrix AXIS store's StringState (2 axis rows
    per doc, adjacent, so doc-block sharding keeps a doc's row+col axes
    on one chip; shard blocks are even by construction)."""
    return doc_state_specs()


def shard_axis_store_state(state: StringState, mesh: Mesh) -> StringState:
    n_rows = state.seq.shape[0]
    if n_rows % (2 * mesh.devices.size) != 0:
        raise ValueError(f"axis rows {n_rows} not divisible by "
                         f"2×mesh size {2 * mesh.devices.size}")
    return shard_store_state(state, mesh)


def sharded_axis_apply(mesh: Mesh):
    """The doc-sharded axis scan (mutations + in-scan position
    resolves): shard_map of apply_axis_batch over each shard's axis-row
    block; resolve outputs come back row-sharded."""
    key = ("axis_apply", mesh)
    if key not in _CACHE:
        from ..ops.axis_kernel import apply_axis_batch
        specs = axis_state_specs()
        row = P(DOC_AXIS, None)

        @functools.partial(jax.jit, donate_argnums=0)
        def fn(state, planes):
            return jax.shard_map(
                apply_axis_batch, mesh=mesh,
                in_specs=(specs,) + (row,) * 7,
                out_specs=(specs, row, row), check_vma=False)(
                    state, *planes)
        _CACHE[key] = fn
    return _CACHE[key]


def sharded_cells_apply(mesh: Mesh, fww: bool):
    """The doc-sharded cell merge: each shard owns the cell POOL SLICE of
    its doc block (cells are doc-scoped, so routing by owning doc keeps
    the sort-merge shard-local — collective-free)."""
    key = ("cells_apply", mesh, fww)
    if key not in _CACHE:
        from ..ops.matrix_kernel import apply_cells_batch

        @functools.partial(jax.jit, donate_argnums=0)
        def fn(state, key_p, seq_p, val_p):
            def body(st, k, s, v):
                return jax.vmap(
                    functools.partial(apply_cells_batch, fww=fww))(
                        st, k, s, v)
            from ..ops.matrix_kernel import MatrixCellState
            specs = MatrixCellState(
                key=P(DOC_AXIS, None), seq=P(DOC_AXIS, None),
                value=P(DOC_AXIS, None), count=P(DOC_AXIS),
                overflow=P(DOC_AXIS))
            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(specs, P(DOC_AXIS, None), P(DOC_AXIS, None),
                          P(DOC_AXIS, None)),
                out_specs=specs, check_vma=False)(
                    state, key_p, seq_p, val_p)
        _CACHE[key] = fn
    return _CACHE[key]


_COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
                "collective-permute", "reduce-scatter",
                "collective-broadcast")


def assert_collective_free(mesh: Mesh, n_docs: int, capacity: int,
                           n_ops: int) -> str:
    """Compile the sharded apply at the given shape and prove it needs NO
    cross-chip communication: the optimized HLO of the merge and of both
    forms of the unpack (rows scattered, and a full-store batch) must
    contain zero collective ops. Returns the (empty) list rendered as
    evidence."""
    state = shard_store_state(StringState.create(n_docs, capacity), mesh)
    planes = tuple(jnp.zeros((n_docs, n_ops), jnp.int32) for _ in range(7))
    ms = jnp.zeros((n_docs,), jnp.int32)
    fn = sharded_merge(mesh, use_pallas=False, tile=8, interpret=False,
                       with_props=False, fuse_compact=True)
    hlos = {"merge": fn.lower(state, planes, ms).compile().as_text()}
    # any buffer at least as long as the wire's layout lowers: 8 B an op
    # is the widest head, then seq bases, rows and the fused min_seq
    for scatter, R in ((True, n_docs // 2), (False, n_docs)):
        buf = jax.ShapeDtypeStruct((2 * R * n_ops + 2 * R + n_docs + 8,),
                                   jnp.int32)
        fn = sharded_unpack(mesh, R, n_ops, pos_wide=False, ref_wide=False,
                            rich=0, n_docs=n_docs, fuse_compact=True,
                            scatter_rows=scatter)
        hlos[f"unpack(scatter_rows={scatter})"] = \
            fn.lower(buf).compile().as_text()
    bad = {name: [op for op in _COLLECTIVES if op in hlo]
           for name, hlo in hlos.items()}
    assert not any(bad.values()), \
        f"sharded apply HLO contains collectives: {bad}"
    return "collective-free"
