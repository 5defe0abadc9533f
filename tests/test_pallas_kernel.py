"""Pallas VMEM-resident merge kernel vs the XLA scan path (interpret mode on
the CPU mesh; the compiled kernel is checked against the scan on the chip
by ``chip_smoke.py``)."""

import dataclasses
import functools

import jax
import numpy as np
import pytest
import jax.numpy as jnp

from fluidframework_tpu.ops.merge_tree_kernel import (
    StringState, apply_string_batch,
)
from fluidframework_tpu.ops.pallas_string_kernel import (
    apply_string_batch_pallas,
)
from fluidframework_tpu.testing.synthetic import typing_storm

ORDER = ("kind", "a0", "a1", "a2", "seq", "client", "ref_seq")
CHECK = ("seq", "client", "removed_seq", "removers", "length", "handle_op",
         "handle_off", "count", "overflow")


def _assert_equal(a: StringState, b: StringState):
    for k in CHECK:
        assert np.array_equal(np.asarray(getattr(a, k)),
                              np.asarray(getattr(b, k))), k


@pytest.mark.parametrize("seed", range(3))
def test_pallas_matches_xla_single_batch(seed):
    planes, _ = typing_storm(16, 32, seed=seed)
    ops = tuple(jnp.asarray(planes[k]) for k in ORDER)
    ref = apply_string_batch(StringState.create(16, 256), *ops)
    out = apply_string_batch_pallas(StringState.create(16, 256), *ops,
                                    tile=8, interpret=True)
    _assert_equal(ref, out)


def test_pallas_matches_xla_multiclient_stream():
    """Real multi-client concurrency (lagging ref_seq) through the Pallas
    op loop."""
    from tests.test_megadoc import _planes_from_msgs
    from tests.test_merge_tree_kernel import collab_stream
    _, _, msgs = collab_stream(4, n_rounds=12)
    ops = _planes_from_msgs(msgs)
    ref = apply_string_batch(StringState.create(1, 512), *ops)
    out = apply_string_batch_pallas(StringState.create(1, 512), *ops,
                                    tile=1, interpret=True)
    _assert_equal(ref, out)


def test_pallas_threads_state_across_batches():
    state_p = StringState.create(8, 128)
    state_x = StringState.create(8, 128)
    seq = 1
    for r in range(3):
        planes, seq = typing_storm(8, 16, seed=r, start_seq=seq)
        ops = tuple(jnp.asarray(planes[k]) for k in ORDER)
        state_p = apply_string_batch_pallas(state_p, *ops, tile=8,
                                            interpret=True)
        state_x = apply_string_batch(state_x, *ops)
        _assert_equal(state_x, state_p)


def test_pallas_overflow_flag_not_corruption():
    planes, _ = typing_storm(8, 64, seed=5)
    ops = tuple(jnp.asarray(planes[k]) for k in ORDER)
    ref = apply_string_batch(StringState.create(8, 16), *ops)
    out = apply_string_batch_pallas(StringState.create(8, 16), *ops,
                                    tile=8, interpret=True)
    _assert_equal(ref, out)
    assert np.asarray(out.overflow).any()


def test_pallas_fused_compaction_matches_xla_apply_then_compact():
    """min_seq fused into the kernel epilogue (bit-shift stream compaction
    in VMEM) must match XLA apply + sort-based compact exactly on the
    active region, including stability (kept-slot order)."""
    from fluidframework_tpu.ops.merge_tree_kernel import (
        compact_string_state, string_state_digest,
    )
    for seed in range(3):
        sp = StringState.create(8, 128)
        sx = StringState.create(8, 128)
        seq = 1
        for r in range(3):
            planes, seq = typing_storm(8, 16, seed=seed * 10 + r,
                                       start_seq=seq)
            ops = tuple(jnp.asarray(planes[k]) for k in ORDER)
            ms = np.full((8,), max(seq - 17, 0), np.int32)  # partial window
            sp = apply_string_batch_pallas(sp, *ops, min_seq=ms, tile=8,
                                           interpret=True)
            sx = compact_string_state(apply_string_batch(sx, *ops),
                                      jnp.asarray(ms))
            cnt = np.asarray(sp.count)
            assert np.array_equal(cnt, np.asarray(sx.count)), (seed, r)
            for k in ("seq", "client", "removed_seq", "removers", "length",
                      "handle_op", "handle_off"):
                a = np.asarray(getattr(sp, k))
                b = np.asarray(getattr(sx, k))
                for d in range(8):
                    assert np.array_equal(a[d, :cnt[d]], b[d, :cnt[d]]), \
                        (k, seed, r, d)
            assert np.array_equal(np.asarray(string_state_digest(sp)),
                                  np.asarray(string_state_digest(sx)))


def test_store_product_path_runs_pallas():
    """The PRODUCT path (TensorStringStore._dispatch_apply, VERDICT r1 #1):
    the same multi-client message stream through the Pallas-interpret store
    and the XLA store must converge to identical text and digests."""
    from fluidframework_tpu.ops.string_store import (
        TensorStringStore, pallas_tile_for,
    )
    from tests.test_merge_tree_kernel import collab_stream

    assert pallas_tile_for(8, 256) == 8
    assert pallas_tile_for(10240, 384) == 128
    assert pallas_tile_for(7, 256) is None      # doc count not tileable
    assert pallas_tile_for(8, 200) is None      # capacity not lane-aligned

    text, length, msgs = collab_stream(7, n_rounds=10)
    a = TensorStringStore(n_docs=8, capacity=256)
    a.pallas = "interpret"
    b = TensorStringStore(n_docs=8, capacity=256)
    b.pallas = "off"
    for store in (a, b):
        store.apply_messages((3, m) for m in msgs)
    assert a.read_text(3) == text == b.read_text(3)
    assert a.visible_length(3) == length
    assert np.array_equal(a.digests(), b.digests())


def test_store_pallas_falls_back_on_annotate():
    """A store that sees an annotate must leave the fused no-props kernel
    and still converge (the one-way _has_props transition)."""
    from fluidframework_tpu.ops.string_store import TensorStringStore
    from tests.test_merge_tree_kernel import collab_stream

    text, _, msgs = collab_stream(11, n_rounds=10, with_annotates=True)
    store = TensorStringStore(n_docs=8, capacity=512)
    store.pallas = "interpret"
    store.apply_messages((0, m) for m in msgs)
    assert store.read_text(0) == text


def test_replicated_step_pallas_matches_xla():
    """Multi-chip step on the fused kernel (VERDICT r1 #1): per-shard Pallas
    apply under shard_map agrees with the single-device XLA scan."""
    from fluidframework_tpu.ops.merge_tree_kernel import string_state_digest
    from fluidframework_tpu.parallel import (
        make_mesh, make_replicated_step, shard_state, shard_ops,
    )

    mesh = make_mesh(8)
    _, doc_shards = mesh.devices.shape
    n_docs, n_ops, cap = 8 * doc_shards, 8, 128
    planes, _ = typing_storm(n_docs, n_ops, seed=5)
    ops = tuple(jnp.asarray(planes[k]) for k in ORDER)

    single = apply_string_batch(StringState.create(n_docs, cap), *ops)
    step = make_replicated_step(mesh, with_props=False, use_pallas=True,
                                pallas_tile=8, pallas_interpret=True)
    state = shard_state(StringState.create(n_docs, cap), mesh)
    new_state, digest, agree = step(state, *shard_ops(mesh, *ops))
    assert int(agree) == 1
    assert np.array_equal(np.asarray(digest),
                          np.asarray(string_state_digest(single)))


def _annotate_ops(seed, n_docs=8, n_ops=24):
    """Raw op planes with interleaved annotates (packed key<<20|value)."""
    import numpy as np
    from fluidframework_tpu.ops.merge_tree_kernel import PROP_HANDLE_BITS
    from fluidframework_tpu.ops.schema import OpKind
    rng = np.random.default_rng(seed)
    planes, _ = typing_storm(n_docs, n_ops, seed=seed)
    kind, a0, a1, a2 = (planes[k] for k in ("kind", "a0", "a1", "a2"))
    # turn ~1/3 of removes into annotates over the same range
    ann = (kind == OpKind.STR_REMOVE) & (rng.random(kind.shape) < 0.5)
    kind = np.where(ann, OpKind.STR_ANNOTATE, kind)
    key = rng.integers(0, 4, kind.shape).astype(np.int32)
    val = rng.integers(0, 7, kind.shape).astype(np.int32)  # 0 = delete key
    a2 = np.where(ann, (key << PROP_HANDLE_BITS) | val, a2)
    planes.update(kind=kind, a2=a2)
    return tuple(jnp.asarray(planes[k]) for k in ORDER)


def _assert_equal_with_props(a: StringState, b: StringState):
    _assert_equal(a, b)
    assert np.array_equal(np.asarray(a.prop_val), np.asarray(b.prop_val))


@pytest.mark.parametrize("seed", range(3))
def test_pallas_props_matches_xla(seed):
    """The props specialization: annotate-bearing batches through the VMEM
    kernel agree with the XLA scan, property planes included."""
    ops = _annotate_ops(seed)
    ref = apply_string_batch(StringState.create(8, 256), *ops,
                             with_props=True)
    out = apply_string_batch_pallas(StringState.create(8, 256), *ops,
                                    tile=8, interpret=True, with_props=True)
    _assert_equal_with_props(ref, out)


def test_pallas_props_fused_compact_matches_xla():
    """Active-region parity (beyond count the sort path parks dropped
    slots, the shift path zeroes — both semantically ignored)."""
    from fluidframework_tpu.ops.merge_tree_kernel import (
        compact_string_state, string_state_digest,
    )
    ops = _annotate_ops(7)
    ms = jnp.full((8,), 40, jnp.int32)
    ref = compact_string_state(
        apply_string_batch(StringState.create(8, 256), *ops,
                           with_props=True), ms, True)
    out = apply_string_batch_pallas(StringState.create(8, 256), *ops,
                                    tile=8, interpret=True, with_props=True,
                                    min_seq=ms)
    cnt = np.asarray(out.count)
    assert np.array_equal(cnt, np.asarray(ref.count))
    for k in CHECK[:-2] + ("prop_val",):
        a, b = np.asarray(getattr(out, k)), np.asarray(getattr(ref, k))
        for d in range(8):
            assert np.array_equal(a[d, :cnt[d]], b[d, :cnt[d]]), (k, d)
    assert np.array_equal(np.asarray(string_state_digest(out)),
                          np.asarray(string_state_digest(ref)))


def test_store_annotate_stream_stays_on_pallas():
    """An annotate-bearing store now KEEPS the fused path (props kernel)
    and still converges with the oracle (the r1 one-way fall-off, fixed)."""
    from fluidframework_tpu.ops.string_store import TensorStringStore
    from tests.test_merge_tree_kernel import collab_stream

    text, _, msgs = collab_stream(13, n_rounds=12, with_annotates=True)
    store = TensorStringStore(n_docs=8, capacity=512)
    store.pallas = "interpret"
    store.apply_messages((2, m) for m in msgs)
    assert store._has_props
    use_pallas, _, _ = store._pallas_choice()
    assert use_pallas  # props no longer kicks the store off the kernel
    assert store.read_text(2) == text


@pytest.mark.parametrize("seed", range(3))
def test_conflict_storm_pallas_matches_xla(seed):
    """The conflict-heavy corpus (divergent ref_seq, overlapping removes,
    annotates) through BOTH kernels, multi-batch with fused compaction."""
    from fluidframework_tpu.ops.merge_tree_kernel import (
        compact_string_state, string_state_digest,
    )
    from fluidframework_tpu.testing.synthetic import conflict_storm

    sp = StringState.create(8, 512)
    sx = StringState.create(8, 512)
    seq = 1
    for r in range(3):
        planes, seq = conflict_storm(8, 48, seed=seed * 10 + r,
                                     start_seq=seq)
        ops = tuple(jnp.asarray(planes[k]) for k in ORDER)
        ms = np.full((8,), max(seq - 8 * 50, 0), np.int32)
        sp = apply_string_batch_pallas(sp, *ops, tile=8, interpret=True,
                                       with_props=True, min_seq=ms)
        sx = compact_string_state(
            apply_string_batch(sx, *ops, with_props=True),
            jnp.asarray(ms), True)
        cnt = np.asarray(sp.count)
        assert np.array_equal(cnt, np.asarray(sx.count)), (seed, r)
        for k in CHECK[:-2] + ("prop_val",):
            a, b = np.asarray(getattr(sp, k)), np.asarray(getattr(sx, k))
            for d in range(8):
                assert np.array_equal(a[d, :cnt[d]], b[d, :cnt[d]]), \
                    (k, seed, r, d)
        assert np.array_equal(np.asarray(string_state_digest(sp)),
                              np.asarray(string_state_digest(sx)))
    assert not np.asarray(sp.overflow).any()


# -- the touched-tile walk (PR 33): the plain merge visits only the tiles
# -- that hold an op; every other row has to come out as it went in

WALK_TILE, WALK_DOCS, WALK_CAP = 8, 64, 128     # a store of 8 tiles
_WALK_ROWS = {
    "one_tile": np.arange(16, 24),
    "off_the_grid_straddling": np.arange(12, 36),
    "two_tiles_not_neighbours": np.r_[8:16, 50:54],
    "every_tile": np.arange(WALK_DOCS),
    "last_tile_only": np.arange(56, 64),
    "no_valid_op": np.arange(0),
}
_scan = jax.jit(apply_string_batch, static_argnames=("with_props",))
_walk = jax.jit(functools.partial(apply_string_batch_pallas, tile=WALK_TILE,
                                  interpret=True),
                static_argnames=("with_props",))


def walk_case(rows, with_props):
    """(state, ops, touched): a prefilled store of ``WALK_DOCS`` rows and a
    four-column window with ops on ``rows`` alone. The store's slots at or
    beyond ``count`` (semantically ignored) hold a pattern, so that a
    rewritten row shows."""
    from fluidframework_tpu.ops.schema import OpKind

    def storm(n_ops, seed):
        if with_props:
            return dict(zip(ORDER, _annotate_ops(seed, WALK_DOCS, n_ops)))
        planes, _ = typing_storm(WALK_DOCS, n_ops, seed=seed)
        return {k: jnp.asarray(planes[k]) for k in ORDER}

    state = _scan(StringState.create(WALK_DOCS, WALK_CAP),
                  *storm(12, 3).values(), with_props=with_props)
    slot = np.arange(WALK_CAP)[None, :]
    dead = slot >= np.asarray(state.count)[:, None]
    fill = {}
    for i, k in enumerate(CHECK[:-2] + ("prop_val",)):
        junk = (slot * 7 + i + np.arange(WALK_DOCS)[:, None]) % 5 + 1
        was = np.asarray(getattr(state, k))
        if was.ndim == 3:
            junk, hole = junk[..., None], dead[..., None]
        else:
            hole = dead
        fill[k] = jnp.asarray(np.where(hole, junk, was).astype(np.int32))
    state = dataclasses.replace(state, **fill)

    touched = np.zeros(WALK_DOCS, bool)
    touched[rows] = True
    ops = storm(4, 11)
    ops["seq"] = ops["seq"] + 12 * WALK_DOCS    # after the fill's
    ops["ref_seq"] = ops["ref_seq"] + 12 * WALK_DOCS
    ops["kind"] = jnp.where(touched[:, None], ops["kind"], int(OpKind.NOOP))
    return state, tuple(ops.values()), touched


def assert_walked(before, after, expected, touched):
    """``after`` equals ``expected`` in every plane, and the rows without
    an op equal ``before`` bit for bit, beyond ``count`` too."""
    _assert_equal_with_props(expected, after)
    for k in CHECK + ("prop_val",):
        was, got = np.asarray(getattr(before, k)), np.asarray(
            getattr(after, k))
        assert np.array_equal(was[~touched], got[~touched]), k
    if touched.any():   # the window did something
        assert not np.array_equal(np.asarray(before.seq),
                                  np.asarray(after.seq))


@pytest.mark.parametrize("with_props", (False, True),
                         ids=("no_props", "props"))
@pytest.mark.parametrize("where", list(_WALK_ROWS))
def test_touched_tile_walk_matches_xla_and_leaves_the_rest(where, with_props):
    state, ops, touched = walk_case(_WALK_ROWS[where], with_props)
    assert_walked(state, _walk(state, *ops, with_props=with_props),
                  _scan(state, *ops, with_props=with_props), touched)
