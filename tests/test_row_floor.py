"""The string engine's MSN floor by flat row (``_row_floor``): the array
the fused zamboni takes its compaction floor from must equal, row for
row, the floor rebuilt by a walk over every flat document's entry in
``_min_seq`` — through columnar windows, per-op submits, heartbeats,
graduation and row reuse, a summary reload and a follower's catch-up.

docs/INGEST_PIPELINE.md says where the compaction floor comes from."""

import random

import numpy as np
import pytest

from fluidframework_tpu.parallel.replicated import OplogFollower
from fluidframework_tpu.parallel.sharded import make_doc_mesh
from fluidframework_tpu.server import native_deli
from fluidframework_tpu.server.ingest_pipeline import (
    PipelinedIngestExecutor,
)
from fluidframework_tpu.server.serving import StringServingEngine
from fluidframework_tpu.testing.synthetic import typing_storm

pytestmark = pytest.mark.skipif(not native_deli.available(),
                                reason="native sequencer unavailable")

N_DOCS, R, O = 8, 4, 4   # rows; documents a window targets; ops a row
DOCS = [f"d{i}" for i in range(R)]


def _walk(eng):
    """The compaction floor rebuilt from the dict: one entry per flat
    row, 0 on every row no document holds."""
    ms = np.zeros((eng.n_docs,), np.int32)
    for doc_id, row in eng._doc_rows.items():
        ms[row] = eng._min_seq.get(doc_id, 0)
    return ms


def _check(eng, step):
    assert eng._row_floor.dtype == np.int32, step
    assert np.array_equal(eng._row_floor, _walk(eng)), (
        step, eng._row_floor.tolist(), _walk(eng).tolist())


class _Waves:
    """Seeded typing windows over DOCS by client 1, whose client seqs
    continue from window to window."""

    def __init__(self, seed):
        self.seed, self.n = seed, 0

    def next(self, eng, k):
        out = []
        for _ in range(k):
            planes, _ = typing_storm(R, O, seed=self.seed * 100 + self.n)
            cs = np.broadcast_to(np.arange(self.n * O + 1,
                                           (self.n + 1) * O + 1,
                                           dtype=np.int32), (R, O))
            ref = np.minimum(cs, min(eng.deli.doc_seq(d) for d in DOCS))
            out.append(dict(client=np.ones((R, O), np.int32),
                            client_seq=cs, ref_seq=ref, kind=planes["kind"],
                            a0=planes["a0"], a1=planes["a1"], text="abcd"))
            self.n += 1
        return out


def _rows(eng):
    return np.array([eng.doc_row(d) for d in DOCS], np.int32)


def _pipelined(eng, waves):
    with PipelinedIngestExecutor(eng, depth=3) as ex:
        tickets = [ex.submit(_rows(eng), **w) for w in waves]
        ex.drain()
        assert all(t.result()["nacked"] == 0 for t in tickets)


def _mk_engine(**kw):
    eng = StringServingEngine(n_docs=N_DOCS, capacity=64,
                              batch_window=10 ** 9, compact_every=3,
                              sequencer="native", **kw)
    for d in DOCS:
        eng.connect(d, 1)
        eng.doc_row(d)
    return eng


@pytest.mark.parametrize("seed", [0, 5])
def test_row_floor_follows_the_dict_through_a_lifecycle(seed):
    rng = random.Random(seed)
    waves = _Waves(seed)
    eng = _mk_engine()
    eng.auto_recover = False     # graduation below is driven by hand
    _check(eng, "rows allocated")
    # a heartbeat on a document that holds no row yet: the dict alone
    eng.connect("late", 5)
    eng.heartbeat("late", 5, eng.deli.doc_seq("late"))
    assert eng._min_seq["late"] > 0
    _check(eng, "row-less heartbeat")

    _pipelined(eng, waves.next(eng, 4))
    _check(eng, "pipelined windows")
    assert eng.metrics.counters.get("compactions", 0) >= 1

    # a second writer on d0: its per-op submits and heartbeats move d0's
    # floor off the columnar path
    eng.connect("d0", 2)
    for cseq in range(1, 4):
        _, nack = eng.submit("d0", 2, cseq, eng.deli.doc_seq("d0"),
                             {"mt": "insert", "kind": 0, "pos": 0,
                              "text": "xy"})
        assert nack is None
        _check(eng, f"per-op submit {cseq}")
        eng.heartbeat("d0", 2, eng.deli.doc_seq("d0"))
        _check(eng, f"heartbeat {cseq}")

    # a document outgrows its row and graduates: the row is freed
    eng.connect("g", 3)
    text = ""
    for i in range(80):
        pos = rng.randint(0, len(text))
        _, nack = eng.submit("g", 3, i + 1, eng.deli.doc_seq("g"),
                             {"mt": "insert", "kind": 0, "pos": pos,
                              "text": f"w{i}"})
        assert nack is None
        text = text[:pos] + f"w{i}" + text[pos:]
    g_row = eng._doc_rows["g"]
    eng.flush()
    _check(eng, "before graduation")
    assert eng.recover_overflowed() == {"g": "graduated"}
    assert "g" not in eng._doc_rows and eng._row_floor[g_row] == 0
    _check(eng, "graduated")
    assert eng.read_text("g") == text

    # the joiner takes the freed row, seeded from its heartbeat's floor
    assert eng.doc_row("late") == g_row
    assert eng._row_floor[g_row] == eng._min_seq["late"] > 0
    _check(eng, "row reused")
    _, nack = eng.submit("late", 5, 1, eng.deli.doc_seq("late"),
                         {"mt": "insert", "kind": 0, "pos": 0, "text": "ok"})
    assert nack is None
    _check(eng, "joiner's op")

    _pipelined(eng, waves.next(eng, 4))
    _check(eng, "more windows")

    # a summary, a tail behind it, and the engine rebuilt from both
    summary = eng.summarize()
    _check(eng, "summarized")
    _pipelined(eng, waves.next(eng, 2))
    _check(eng, "tail")
    back = StringServingEngine.load(summary, eng.log, sequencer="native")
    _check(back, "reloaded")
    for d in DOCS + ["late"]:
        back.doc_row(d)
        assert back._row_floor[back._doc_rows[d]] == \
            eng._row_floor[eng._doc_rows[d]], d
    _check(back, "reloaded rows noted")

    # a follower trailing the engine's log
    fol = OplogFollower(eng)
    _check(fol.engine, "follower loaded")
    _pipelined(eng, waves.next(eng, 3))
    assert fol.catch_up() > 0
    _check(fol.engine, "follower caught up")
    _check(eng, "leader")
    for d in DOCS + ["late"]:
        assert fol.engine._min_seq[d] == eng._min_seq[d], d
        assert fol.engine._row_floor[fol.engine._doc_rows[d]] == \
            eng._row_floor[eng._doc_rows[d]], d


@pytest.mark.parametrize("n_devices", [1, 2])
def test_compaction_window_hands_the_merge_the_walks_floor(
        monkeypatch, n_devices):
    """Each compaction-due window's ``apply_planes(min_seq=...)`` is the
    walk's floor element for element, on one device and sharded over a
    2-device doc mesh; the engine's later writes do not reach it."""
    mesh = make_doc_mesh(n_devices) if n_devices > 1 else None
    eng = _mk_engine(mesh=mesh)
    eng.connect("d1", 2)
    store, real = eng.store, eng.store.apply_planes
    seen = []

    def spy(*args, min_seq=None, **kw):
        if min_seq is not None:
            seen.append((min_seq, _walk(eng)))
        return real(*args, min_seq=min_seq, **kw)

    monkeypatch.setattr(store, "apply_planes", spy)
    waves = _Waves(3)
    for _ in range(7):
        w, = waves.next(eng, 1)
        assert eng.ingest_planes(_rows(eng), **w)["nacked"] == 0
        eng.heartbeat("d1", 2, eng.deli.doc_seq("d1"))
    assert len(seen) == 2
    for got, want in seen:
        assert got.dtype == np.int32 and got.shape == (N_DOCS,)
        assert np.array_equal(got, want)
    assert seen[-1][0].any() and not seen[-1][0][R:].any()
    assert not np.array_equal(seen[0][0], seen[1][0])
    assert not np.shares_memory(seen[-1][0], eng._row_floor)
    _check(eng, "after the windows")


def test_every_compaction_takes_its_floor_from_the_rows():
    eng = _mk_engine()
    _pipelined(eng, _Waves(1).next(eng, 7))
    eng.summarize()      # the per-op path's compaction
    c = eng.metrics.counters
    assert c["compactions"] >= 3
    assert c["compaction_floors_from_rows"] == c["compactions"]
