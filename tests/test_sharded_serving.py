"""The doc-sharded serving engine (parallel/sharded.py): the product's
multi-chip path on the virtual 8-device CPU mesh — parity with the
unsharded engine, recovery onto the mesh, and the collective-free proof.
"""

import dataclasses

import numpy as np
import pytest

from fluidframework_tpu.parallel.sharded import (
    assert_collective_free, make_doc_mesh,
)
from fluidframework_tpu.server import native_deli
from fluidframework_tpu.server.serving import StringServingEngine

pytestmark = pytest.mark.skipif(not native_deli.available(),
                                reason="native sequencer unavailable")

TEXT = "abcd"


def _pair(R=64, cap=256):
    mesh = make_doc_mesh(8)
    eng = StringServingEngine(n_docs=R, capacity=cap, batch_window=10 ** 9,
                              sequencer="native", mesh=mesh, compact_every=2)
    ora = StringServingEngine(n_docs=R, capacity=cap, batch_window=10 ** 9,
                              sequencer="native", compact_every=2)
    docs = [f"doc-{i}" for i in range(R)]
    for e in (eng, ora):
        for d in docs:
            e.connect(d, 1)
            e.doc_row(d)
    rows = np.array([eng.doc_row(d) for d in docs], np.int32)
    return mesh, eng, ora, docs, rows


def test_sharded_engine_matches_unsharded():
    R, O = 64, 16
    mesh, eng, ora, docs, rows = _pair(R)
    client = np.ones((R, O), np.int32)
    ref = np.zeros((R, O), np.int32)
    kind = np.zeros((R, O), np.int32)
    z = np.zeros((R, O), np.int32)
    from fluidframework_tpu.testing.synthetic import typing_storm
    for b in range(3):
        planes, _ = typing_storm(R, O, seed=b)
        cseq = np.broadcast_to(
            np.arange(b * O + 1, (b + 1) * O + 1, dtype=np.int32), (R, O))
        for e in (eng, ora):
            assert e.ingest_planes(rows, client, cseq, ref, planes["kind"],
                                   planes["a0"], planes["a1"],
                                   TEXT)["nacked"] == 0
    assert np.array_equal(eng.store.digests(), ora.store.digests())
    for d in docs[::13]:
        assert eng.read_text(d) == ora.read_text(d)
    assert "docs" in str(eng.store.state.seq.sharding.spec)


def test_sharded_rich_and_recovery_onto_mesh():
    R, O = 64, 8
    mesh, eng, ora, docs, rows = _pair(R)
    client = np.ones((R, O), np.int32)
    ref = np.zeros((R, O), np.int32)
    texts = [f"t{k}" for k in range(O)]
    props = [{"b": 1}, {"c": "x"}]
    kind = np.zeros((R, O), np.int32)
    kind[:, O // 2:] = 2  # annotate
    a0 = np.zeros((R, O), np.int32)
    a1 = np.zeros((R, O), np.int32)
    a1[:, O // 2:] = 2
    tidx = np.zeros((R, O), np.int32)
    tidx[:, :O // 2] = np.arange(O // 2, dtype=np.int32)
    tidx[:, O // 2:] = np.arange(O // 2, dtype=np.int32) % 2
    cseq = np.broadcast_to(np.arange(1, O + 1, dtype=np.int32), (R, O))
    for e in (eng, ora):
        assert e.ingest_planes(rows, client, cseq, ref, kind, a0, a1,
                               texts=texts, tidx=tidx,
                               props=props)["nacked"] == 0
    assert np.array_equal(eng.store.digests(), ora.store.digests())
    assert eng.get_properties(docs[0], 0) == ora.get_properties(docs[0], 0)

    summary = eng.summarize()
    revived = StringServingEngine.load(summary, eng.log, mesh=mesh)
    assert np.array_equal(revived.store.digests(), eng.store.digests())
    assert "docs" in str(revived.store.state.seq.sharding.spec)
    # restored engine keeps serving, sharded
    msg, nack = revived.submit(
        docs[0], 1, O + 1, 0,
        {"mt": "insert", "kind": 0, "pos": 0, "text": "Z"})
    assert nack is None
    assert revived.read_text(docs[0]) == "Z" + eng.read_text(docs[0])


def test_sharded_apply_hlo_is_collective_free():
    mesh = make_doc_mesh(8)
    assert assert_collective_free(mesh, 64, 128, 16) == "collective-free"


def test_mesh_requires_divisible_docs():
    mesh = make_doc_mesh(8)
    from fluidframework_tpu.ops.string_store import TensorStringStore
    with pytest.raises(ValueError, match="divisible"):
        TensorStringStore(30, 128, mesh=mesh)


def test_sharded_incremental_summary_roundtrip():
    """Incremental summaries of a SHARDED store: the dirty-row gather and
    the delta-restore scatter must work over the mesh, and load(mesh=...)
    must resolve the chain back onto it."""
    R, O = 64, 8
    mesh, eng, ora, docs, rows = _pair(R)
    client = np.ones((R, O), np.int32)
    z = np.zeros((R, O), np.int32)
    kind = np.zeros((R, O), np.int32)
    cseq = np.broadcast_to(np.arange(1, O + 1, dtype=np.int32), (R, O))
    assert eng.ingest_planes(rows, client, cseq, z, kind, z, z,
                             TEXT)["nacked"] == 0
    eng.summarize()
    # touch 3 docs, delta-summarize, touch 2 more, delta again (chain)
    sub = rows[:3]
    cseq2 = np.broadcast_to(np.arange(O + 1, 2 * O + 1, dtype=np.int32),
                            (3, O))
    assert eng.ingest_planes(sub, client[:3], cseq2, z[:3], kind[:3],
                             z[:3], z[:3], TEXT)["nacked"] == 0
    s1 = eng.summarize(incremental=True)
    assert len(s1["store_delta"]["rows"]) == 3
    sub2 = rows[10:12]
    cseq3 = np.broadcast_to(np.arange(O + 1, 2 * O + 1, dtype=np.int32),
                            (2, O))
    assert eng.ingest_planes(sub2, client[:2], cseq3, z[:2], kind[:2],
                             z[:2], z[:2], TEXT)["nacked"] == 0
    s2 = eng.summarize(incremental=True)
    want = {d: eng.read_text(d) for d in docs}
    revived = StringServingEngine.load(s2, eng.log, mesh=mesh)
    assert {d: revived.read_text(d) for d in docs} == want
    assert "docs" in str(revived.store.state.seq.sharding.spec)


def test_sharded_map_engine_matches_unsharded():
    """MapServingEngine(mesh=...): columnar merge as a collective-free
    shard_map; parity with the unsharded engine + recovery onto mesh."""
    from fluidframework_tpu.ops.schema import OpKind
    from fluidframework_tpu.server.serving import MapServingEngine
    mesh = make_doc_mesh(8)
    R, O = 64, 12
    a = MapServingEngine(n_docs=R, batch_window=10 ** 9,
                         sequencer="native", mesh=mesh)
    b = MapServingEngine(n_docs=R, batch_window=10 ** 9,
                         sequencer="native")
    docs = [f"sm-{i}" for i in range(R)]
    for e in (a, b):
        for d in docs:
            e.connect(d, 1)
            e.doc_row(d)
    rows = np.array([a.doc_row(d) for d in docs], np.int32)
    rng = np.random.default_rng(3)
    keys = [f"k{j}" for j in range(6)]
    values = [f"v{j}" for j in range(5)]
    client = np.ones((R, O), np.int32)
    ref = np.zeros((R, O), np.int32)
    for bi in range(3):
        kind = rng.choice([int(OpKind.MAP_SET), int(OpKind.MAP_DELETE),
                           int(OpKind.MAP_CLEAR)],
                          p=[0.8, 0.15, 0.05], size=(R, O)).astype(np.int32)
        kidx = rng.integers(0, len(keys), size=(R, O)).astype(np.int32)
        vidx = rng.integers(0, len(values), size=(R, O)).astype(np.int32)
        cseq = np.broadcast_to(
            np.arange(bi * O + 1, (bi + 1) * O + 1, dtype=np.int32), (R, O))
        for e in (a, b):
            assert e.ingest_planes(rows, client, cseq, ref, kind, kidx,
                                   keys, values, vidx)["nacked"] == 0
    assert np.array_equal(a.store.digests(), b.store.digests())
    for d in docs[::11]:
        assert a.read_doc(d) == b.read_doc(d), d
    assert "docs" in str(a.store.state.present.sharding.spec)

    summary = a.summarize()
    revived = MapServingEngine.load(summary, a.log, mesh=mesh)
    assert {d: revived.read_doc(d) for d in docs} == \
        {d: a.read_doc(d) for d in docs}
    assert "docs" in str(revived.store.state.present.sharding.spec)


# ------------------------------------------------ the columnar apply on a mesh
# (ISSUE 29) A window's op planes are unpacked where the state lives: the
# mesh store against a one-chip store on the same windows, plane for plane.

N_DOCS, CAP, CHIPS = 2048, 128, 4   # a shard is 512 rows: the door's tallest
HEIGHTS = (8, 16, 256, 264, 272, 512)   # window fits inside one
FORMS = {   # form → (payload-table size, annotates, the wire it must take)
    "B": (20, False, "tab8"),
    "R.tab8": (20, True, "tab8"),
    "R.tab16": (300, True, "tab16"),
}
PROPS = [{"bold": True}, {"color": "red"}, {"size": 12}]


def _window_rows(height, where):
    if where == "full":
        return np.arange(N_DOCS, dtype=np.int32)
    shard = N_DOCS // CHIPS
    first = shard if where == "inside" else shard - height // 2
    # the door's windows are row-sorted; the scatter may not lean on it
    return np.random.default_rng(height).permutation(
        np.arange(first, first + height, dtype=np.int32))


def _windows(rows, O, form, compact8, n=4):
    """``n`` windows on the same rows, fused zamboni off and on by turns:
    inserts first, then removes and (props forms) annotates among them.
    ``compact8`` off: refs lag their op by 300, past the profile's byte."""
    from fluidframework_tpu.ops.schema import OpKind
    n_texts, annotates, _ = FORMS[form]
    texts = [f"t{k}" for k in range(n_texts)]
    rng = np.random.default_rng(len(rows) * 31 + O)
    R = len(rows)
    base0 = 0 if compact8 else 1000
    for w in range(n):
        kind = np.full((R, O), int(OpKind.STR_INSERT), np.int32)
        tidx = rng.integers(0, n_texts, (R, O)).astype(np.int32)
        if w >= 2:      # every document holds two runs or more by now
            edit = rng.random(R) < 0.5
            kind[edit, 0] = int(OpKind.STR_REMOVE)
            if annotates:
                ann = edit & (rng.random(R) < 0.5)
                kind[ann, 0] = int(OpKind.STR_ANNOTATE)
                tidx[ann, 0] = rng.integers(0, len(PROPS), int(ann.sum()))
        a0 = np.zeros((R, O), np.int32)
        a1 = np.where(kind == int(OpKind.STR_INSERT), 0, 1).astype(np.int32)
        seq_base = np.full(R, base0 + w * O, np.int32)
        floor = np.zeros(N_DOCS, np.int32)
        floor[rows] = seq_base if compact8 else seq_base - 300
        yield dict(
            rows=rows, kind=kind, a0=a0, a1=a1, seq_base=seq_base,
            client_id=np.ones((R, O), np.int32),
            ref_seq=np.broadcast_to(floor[rows][:, None], (R, O)),
            texts=texts, tidx=tidx, props=PROPS if annotates else None,
            min_seq=floor if w % 2 else None)


def _parity_cases():
    for form in FORMS:
        for height in HEIGHTS:
            for where in ("inside", "border"):
                yield height, where, form, True
    for form in ("B", "R.tab8"):
        for height in (8, 264, 512):
            for where in ("inside", "border"):
                yield height, where, form, False
    for form in FORMS:
        for compact8 in (True, False):
            yield N_DOCS, "full", form, compact8


@pytest.mark.parametrize(
    "height,where,form,compact8", list(_parity_cases()),
    ids=lambda v: str(v))
def test_mesh_apply_planes_matches_one_chip(height, where, form, compact8):
    """Heights of the door's closed set with their rows inside one shard
    and across two shards' border, and a full-store batch in row order
    (``scatter_rows=False``); the B form and the props form on both table
    wires; compact8 where the store chooses it and where it cannot; the
    fused zamboni off and on: every plane equal after every window."""
    import jax
    from fluidframework_tpu.ops.string_store import TensorStringStore
    on_mesh = TensorStringStore(N_DOCS, CAP, mesh=make_doc_mesh(CHIPS))
    one_chip = TensorStringStore(N_DOCS, CAP)
    rows = _window_rows(height, where)
    O = 2 if where == "full" else 1     # the door's windows are one op deep
    for w, win in enumerate(_windows(rows, O, form, compact8)):
        for store in (on_mesh, one_chip):
            store.apply_planes(**win)
            assert store.last_profile[0] == \
                ("compact8" if compact8 else "lag16")
            assert store.last_rich_wire == FORMS[form][2]
        for f in dataclasses.fields(on_mesh.state):
            assert np.array_equal(
                np.asarray(getattr(on_mesh.state, f.name)),
                np.asarray(getattr(one_chip.state, f.name))), (w, f.name)
    assert on_mesh._has_props == FORMS[form][1]
    assert on_mesh.unpack_variants == one_chip.unpack_variants
    assert all((v[7] is False) == (where == "full")      # scatter_rows
               for v in on_mesh.unpack_variants)
    for x in jax.tree.leaves(on_mesh.state):
        assert len(x.sharding.device_set) == CHIPS


def test_mesh_windows_are_born_on_the_mesh(monkeypatch):
    """The mechanism: the planes handed to the merge carry the state's
    sharding, so the launch moves nothing between chips — counted by the
    store, and refused by JAX's own transfer guard when it is not so."""
    import functools
    import jax
    import jax.numpy as jnp
    from fluidframework_tpu.ops import string_store
    from fluidframework_tpu.parallel import sharded
    from fluidframework_tpu.utils.telemetry import REGISTRY
    store = string_store.TensorStringStore(N_DOCS, CAP,
                                           mesh=make_doc_mesh(CHIPS))
    wins = list(_windows(_window_rows(264, "border"), 1, "B", True, n=8))

    def counted():
        return tuple(REGISTRY.counters.get(f"mesh_windows_{k}", 0)
                     for k in ("resident", "resharded"))

    for win in wins[:2]:        # both programs compile outside the guard
        store.apply_planes(**win)
    before = counted()
    with jax.transfer_guard_device_to_device("disallow"):
        for win in wins[2:6]:
            store.apply_planes(**win)
    resident, resharded = counted()
    assert (resident - before[0], resharded - before[1]) == (4, 0)

    # the parent's placement (upload to one chip, unpack there) is what
    # the guard and the second counter exist to catch
    monkeypatch.setattr(sharded, "replicated",
                        lambda buf, mesh: jnp.asarray(buf))
    monkeypatch.setattr(
        sharded, "sharded_unpack", lambda mesh, **variant: functools.partial(
            string_store._columnar_unpack_jit, **variant))
    store.apply_planes(**wins[6])
    assert counted() == (resident, resharded + 1)
    with jax.transfer_guard_device_to_device("disallow"), \
            pytest.raises(Exception, match="device-to-device"):
        store.apply_planes(**wins[7])


@pytest.mark.parametrize("program", [
    "_sharded_columnar_merge.fused", "_sharded_columnar_merge.plain",
    "_sharded_columnar_unpack", "_sharded_compact"])
def test_mesh_programs_carry_their_names(program):
    """The kernel readers (``perfbench/metrics/kernel.merge_ms_per_window.*``,
    ``merge_roofline.*``) pick the merge's module out of a trace by name;
    an anonymous ``jit_fn`` would be summed with every other one."""
    import jax
    import jax.numpy as jnp
    from fluidframework_tpu.ops.merge_tree_kernel import StringState
    from fluidframework_tpu.parallel import sharded
    mesh = make_doc_mesh(CHIPS)
    n_docs, O = 64, 1
    state = sharded.shard_store_state(StringState.create(n_docs, CAP), mesh)
    planes = tuple(jnp.zeros((n_docs, O), jnp.int32) for _ in range(7))
    ms = jnp.zeros((n_docs,), jnp.int32)
    name, _, variant = program.partition(".")
    if name == "_sharded_columnar_merge":
        fused = variant == "fused"
        fn = sharded.sharded_merge(mesh, False, 8, False, False, fused)
        args = (state, planes, ms) if fused else (state, planes)
    elif name == "_sharded_columnar_unpack":
        fn = sharded.sharded_unpack(
            mesh, 8, O, pos_wide=False, ref_wide=False, rich=0,
            n_docs=n_docs, fuse_compact=True, scatter_rows=True)
        args = (jax.ShapeDtypeStruct((n_docs + 64,), jnp.int32),)
    else:
        fn = sharded.sharded_compact(mesh, False)
        args = (state, ms)
    assert fn.__name__ == name
    assert f"@jit_{name} " in fn.lower(*args).as_text()


# ------------------------------------------- the touched-tile walk on a mesh
# (ISSUE 33) Each shard derives its own tile list from its own op planes: a
# shard the window has no row in runs one tile of NOOPs and keeps its state.

_WALK_SHARD_ROWS = {   # of 64 rows: 4 shards of 16, tiles of 8
    "one_shard_of_four": np.arange(18, 30),
    "all_four_shards": np.r_[4:8, 20:24, 36:41, 52:57],
}


@pytest.mark.parametrize("with_props", (False, True),
                         ids=("no_props", "props"))
@pytest.mark.parametrize("where", list(_WALK_SHARD_ROWS))
def test_sharded_merge_walks_each_shards_own_tiles(where, with_props):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from fluidframework_tpu.parallel import sharded
    from tests.test_pallas_kernel import (
        WALK_TILE, _scan, assert_walked, walk_case,
    )
    before, ops, touched = walk_case(_WALK_SHARD_ROWS[where], with_props)
    mesh = make_doc_mesh(CHIPS)
    merge = sharded.sharded_merge(mesh, True, WALK_TILE, True, with_props,
                                  False)
    by_row = NamedSharding(mesh, PartitionSpec("docs", None))
    # the merge donates its state: give it a copy of its own
    on_mesh = merge(
        sharded.shard_store_state(jax.tree.map(jnp.copy, before), mesh),
        tuple(jax.device_put(p, by_row) for p in ops))
    assert_walked(before, on_mesh,
                  _scan(before, *ops, with_props=with_props), touched)
    for x in jax.tree.leaves(on_mesh):
        assert len(x.sharding.device_set) == CHIPS


@pytest.mark.parametrize("placement", ("one_chip", "mesh"))
def test_apply_planes_counts_the_tiles_its_merge_walks(placement):
    """A 512-row window 16 off the grid at tile 64 lies in 9 tiles of the
    store's 32; the window with the zamboni fused in walks all of them; a
    full-store batch too; the XLA scan has no tiles and counts nothing."""
    from fluidframework_tpu.ops.string_store import TensorStringStore
    from fluidframework_tpu.utils.telemetry import REGISTRY
    mesh = make_doc_mesh(CHIPS) if placement == "mesh" else None
    store = TensorStringStore(N_DOCS, 512, mesh=mesh)
    store.pallas = "interpret"
    assert store._pallas_choice()[:2] == (True, 64)

    def counted():
        return tuple(REGISTRY.counters.get(f"merge_tiles_{k}", 0)
                     for k in ("visited", "total"))

    rows = np.arange(528, 1040, dtype=np.int32)
    plain, fused = _windows(rows, 1, "B", True, n=2)
    for win, walked in ((plain, 9), (fused, 32)):
        before = counted()
        store.apply_planes(**win)
        after = counted()
        assert (after[0] - before[0], after[1] - before[1]) == (walked, 32)
    before = counted()
    store.apply_planes(**next(_windows(_window_rows(N_DOCS, "full"), 1, "B",
                                       True, n=1)))
    assert counted() == (before[0] + 32, before[1] + 32)
    store.pallas = "off"
    store.apply_planes(**plain)
    assert counted() == (before[0] + 32, before[1] + 32)
