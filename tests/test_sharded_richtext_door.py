"""Rich text on a doc mesh, through the door (ISSUE 32): ``R`` frames over
sockets into ``ColumnarAlfred`` in front of an engine whose planes are
sharded over four (virtual) chips, carved the way typing's turns carve
them, so that every full window holds rows in every shard. Compared
document by document, text and every mark, with the ``models/`` oracle,
digest by digest with the same session through a one-chip engine, and
with a reload from a summary and the log's tail onto the same mesh.
"""

import dataclasses
import threading
import time

import numpy as np
import pytest

from fluidframework_tpu.core.protocol import MessageType
from fluidframework_tpu.models.merge_tree_client import SequenceClient
from fluidframework_tpu.parallel.sharded import make_doc_mesh, shard_of_rows
from fluidframework_tpu.server import native_deli
from fluidframework_tpu.server.columnar_ingress import (
    ColumnarAlfred, ColumnarClient, _OP_DTYPE)
from fluidframework_tpu.server.native_oplog import NativePartitionedLog
from fluidframework_tpu.server.serving import StringServingEngine
from fluidframework_tpu.utils.telemetry import REGISTRY

pytestmark = pytest.mark.skipif(not native_deli.available(),
                                reason="native sequencer unavailable")

# the cell's layout, small: 8 relays own 8 neighbouring rows each, a chip
# holds two relays', a frame is 4 of a relay's rows, and the relays send in
# two turns (0, 2, 4, 6 then 1, 3, 5, 7): a turn's 16 rows are one window
# with 4 rows in each of the 4 shards. Relay 7's first two documents are
# multi-writer: relay 0 co-writes both, relay 1 the second.
N_DOCS, CAP, CHIPS, RELAYS, FRAME = 64, 128, 4, 8, 4
OWN = N_DOCS // RELAYS
SHARED = [f"doc-{(RELAYS - 1) * OWN + i}" for i in range(2)]
MARKS = [{"bold": True}, {"color": "red"}, {"color": "blue"}, {"size": 12},
         {"bold": None}]
FILL = "the quick brown fox jump"
INS, REM, ANN = 0, 1, 2
COUNTERS = ("mesh_window_shards", "mesh_window_ops_fullest_shard",
            "mesh_windows_resident", "mesh_windows_resharded")


def _co_written(r):
    """The multi-writer documents relay ``r`` writes beside its own."""
    return SHARED if r == 0 else SHARED[1:] if r == 1 else []


def _writes(r):
    """The documents relay ``r`` writes in each frame of its cycle: half
    of its own, the multi-writer ones with the first half."""
    own = [f"doc-{r * OWN + i}" for i in range(OWN)]
    return [own[:FRAME] + _co_written(r), own[FRAME:]]


def _session(log_dir, mesh, rounds=8, summary_after=5):
    """The whole session on one engine: two rounds of fill, then inserts,
    removes and annotates drawn from the seed by each writer's own replica
    of the document (the oracle's client), whose view lags the other
    writers' ops of the round: on the multi-writer documents ops cross."""
    counted0 = {k: REGISTRY.counters.get(k, 0) for k in COUNTERS}
    log = NativePartitionedLog(str(log_dir), 8)
    eng = StringServingEngine(n_docs=N_DOCS, capacity=CAP, n_props=4,
                              sequencer="native", log=log, mesh=mesh,
                              compact_every=4)
    eng.store.pallas = "interpret"
    srv = ColumnarAlfred(eng, window_min_rows=RELAYS // 2 * FRAME,
                         window_ms=2.0, decode="native").start_in_thread()
    hold = threading.Event()
    drain = srv._drain
    srv._drain = lambda: None if hold.is_set() else drain()
    shapes = []                         # per window: its ops by shard
    note = eng._note_shard_ops

    def noted(rows, counts=None):
        shapes.append(np.bincount(
            shard_of_rows(np.asarray(rows, np.int64), N_DOCS, CHIPS),
            weights=counts, minlength=CHIPS).astype(int).tolist())
        return note(rows, counts=counts)

    eng._note_shard_ops = noted
    rng = np.random.default_rng(32)
    try:
        cl = [ColumnarClient("127.0.0.1", srv.port) for _ in range(RELAYS)]
        for r, c in enumerate(cl):
            # as the generator's connections join: its own documents,
            # then those it co-writes. Rows are handed out as documents
            # arrive, so the multi-writer rows follow relay 0's and every
            # later relay's rows lie two off the grid, as the cell's lie 16
            c.join([f"doc-{r * OWN + i}" for i in range(OWN)]
                   + _co_written(r))
        sess = [next(s for s in srv._sessions if s.client_id == c.client_id)
                for c in cl]
        rows = {}
        for c in cl:
            rows.update(c.rows)
        assert sorted(rows.values()) == list(range(N_DOCS))
        assert [rows[d] for d in SHARED] == [OWN, OWN + 1]
        reps = {(r, d): SequenceClient(cl[r].client_id)
                for r in range(RELAYS) for d in sum(_writes(r), [])}

        def one_op(rep, fill):
            n = rep.get_length()
            roll = rng.random()
            if fill or n < 12:
                text = FILL if fill else "abcdefgh"[int(rng.integers(8))]
                op = rep.insert_text_local(int(rng.integers(0, n + 1)), text)
                return INS, op["pos"], 0, text
            a = int(rng.integers(0, n - 9))
            if roll < 0.25:
                m = MARKS[int(rng.integers(len(MARKS)))]
                b = a + int(rng.integers(1, 9))
                rep.annotate_range_local(a, b, m)
                return ANN, a, b, m
            if roll < 0.5:
                rep.remove_range_local(a, a + 1)
                return REM, a, a + 1, None
            text = "abcdefgh"[int(rng.integers(8))]
            rep.insert_text_local(a, text)
            return INS, a, 0, text

        def send(r, docs, fill):
            texts, marks = [], []
            ops = np.zeros(len(docs), _OP_DTYPE)
            for i, d in enumerate(docs):
                rep = reps[r, d]
                ref = rep.last_processed_seq
                kind, a0, a1, pay = one_op(rep, fill)
                table = texts if kind == INS else marks
                if kind != REM and pay not in table:
                    table.append(pay)
                ops[i] = (rows[d], kind, a0, a1,
                          0 if kind == REM else table.index(pay),
                          rep.client_seq, ref)
            cl[r].send_ops(texts, ops, props=marks)
            return len(docs)

        def landed(r):
            size, t_end = -1, time.monotonic() + 30
            while len(sess[r].rx) != size or not size:
                size = len(sess[r].rx)
                time.sleep(0.02)
                assert time.monotonic() < t_end

        def turn(relays, frame, fill):
            hold.set()
            expect = {r: send(r, _writes(r)[frame], fill) for r in relays}
            for r in relays:
                landed(r)
            hold.clear()
            for r, n in expect.items():
                while n:
                    resp = cl[r].recv_json()
                    assert resp["t"] == "acks", resp
                    assert all(seq > 0 for _cseq, seq in resp["acks"])
                    n -= len(resp["acks"])

        def catch_up():
            srv._executor.drain(60.0)
            # the oracle's client acks its own op by the ``clientSeq`` in
            # the contents; the log's removes and annotates carry it on
            # the message alone
            logged = {d: [dataclasses.replace(m, contents=dict(
                m.contents, clientSeq=m.client_seq)) for m in msgs]
                for d, msgs in eng._docs_log_messages(sorted(rows)).items()}
            for (r, d), rep in reps.items():
                for m in logged[d]:
                    if m.seq > rep.last_processed_seq:
                        rep.apply_msg(m)
            return logged

        summary = None
        for rnd in range(rounds):
            # after the summary only each relay's first frame is written:
            # the other half of the documents the log's tail never touches
            for frame in (0, 1) if summary is None else (0,):
                for relays in (range(0, RELAYS, 2), range(1, RELAYS, 2)):
                    turn(list(relays), frame, fill=rnd < 2)
            logged = catch_up()
            if rnd + 1 == summary_after:
                summary = eng.summarize()

        # the oracle: an observer that replays each document's sequenced
        # stream, as the durable log holds it
        texts, marks = {}, {}
        for d in sorted(rows):
            obs = SequenceClient(10 ** 6)
            for m in logged[d]:
                assert m.type == MessageType.OP
                obs.apply_msg(m)
            texts[d] = obs.get_text()
            marks[d] = [dict(obs.tree.get_containing_segment(p)[0].props
                             or {}) for p in range(len(texts[d]))]
            for (r, d2), rep in reps.items():
                if d2 == d:
                    assert rep.get_text() == texts[d] and not rep.pending
        revived = StringServingEngine.load(summary, log, mesh=mesh,
                                           sequencer="native")
        revived.store.pallas = "interpret"
        out = {
            "oracle_texts": texts, "oracle_marks": marks,
            "texts": {d: eng.read_text(d) for d in rows},
            "marks": {d: [eng.get_properties(d, p)
                          for p in range(len(texts[d]))] for d in rows},
            "digests": np.asarray(eng.store.digests()).tolist(),
            "reload_digests": np.asarray(revived.store.digests()).tolist(),
            "reload_texts": {d: revived.read_text(d) for d in rows},
            "reload_marks": {d: [revived.get_properties(d, p)
                                 for p in range(len(texts[d]))]
                             for d in SHARED + sorted(rows)[::8]},
            "untouched_by_tail": sorted(
                rows[d] for r in range(RELAYS) for d in _writes(r)[1]),
            "state_devices": [
                sorted(dev.id for dev in x.sharding.device_set)
                for x in (eng.store.state.seq, revived.store.state.seq)],
            "has_props": bool(eng.store._has_props),
            "rich_wire": eng.store.last_rich_wire,
            "shapes": shapes,
            "windows": srv.windows_flushed, "ops": srv.ops_ingested,
            "counters": {k: REGISTRY.counters.get(k, 0) - v
                         for k, v in counted0.items()},
        }
        for c in cl:
            c.close()
        return out
    finally:
        srv.stop()
        log.close()


@pytest.fixture(scope="module")
def sessions(tmp_path_factory):
    return {name: _session(tmp_path_factory.mktemp(name), mesh)
            for name, mesh in (("mesh", make_doc_mesh(CHIPS)),
                               ("one_chip", None))}


def test_every_full_window_lies_in_every_shard(sessions):
    """The mechanism the cell exists for: a turn's window holds the same
    number of rows in each of the four shards; only the multi-writer rows'
    small windows lie in one. The two counters read it off the per-shard
    counts the engine credits anyway."""
    got = sessions["mesh"]
    full = [s for s in got["shapes"] if sum(s) >= RELAYS // 2 * FRAME]
    small = [s for s in got["shapes"] if sum(s) < RELAYS // 2 * FRAME]
    # a round's four turns: the multi-writer rows ride in relay 0's shard
    # (its first frame's turn, and relay 7's), the last frame of the odd
    # relays straddles a border by the same two rows
    assert full == [[6, 4, 4, 2], [6, 4, 4, 2], [4, 4, 4, 4],
                    [2, 4, 4, 6]] * 5 + [[6, 4, 4, 2]] * 6
    assert all(min(s) >= FRAME - len(SHARED) for s in full)
    # what a turn holds past a window's rows, and the multi-writer row
    # that two relays of one turn both write
    assert small == [[0, 0, 0, 2], [1, 0, 0, 0]] * 8
    assert all(sorted(s)[:CHIPS - 1] == [0] * (CHIPS - 1) for s in small)
    assert got["windows"] == len(got["shapes"]) == len(full) + len(small)
    c = got["counters"]
    assert c["mesh_window_shards"] == CHIPS * len(full) + len(small)
    assert c["mesh_window_ops_fullest_shard"] \
        == sum(max(s) for s in got["shapes"])
    assert c["mesh_windows_resident"] == got["windows"]
    assert c["mesh_windows_resharded"] == 0
    assert got["state_devices"] == [list(range(CHIPS))] * 2


def test_one_chip_engine_counts_no_shards(sessions):
    c = sessions["one_chip"]["counters"]
    assert c == dict.fromkeys(COUNTERS, 0)
    assert sessions["one_chip"]["state_devices"] == [[0], [0]]


@pytest.mark.parametrize("where", ["mesh", "one_chip"])
def test_served_documents_match_the_oracle(sessions, where):
    """Text and every character's marks of every document, the crossing
    multi-writer ones among them, against ``models/``'s replay of the
    sequenced stream the log holds."""
    got = sessions[where]
    assert got["has_props"] and got["rich_wire"] == "tab8"
    assert got["texts"] == got["oracle_texts"]
    assert got["marks"] == got["oracle_marks"]
    assert any(m for d in SHARED for m in got["oracle_marks"][d])
    assert sum(bool(m) for ms in got["oracle_marks"].values()
               for m in ms) > 200
    assert got["ops"] == 5 * (N_DOCS + 3) + 3 * (N_DOCS // 2 + 3)


def test_mesh_matches_one_chip_digest_by_digest(sessions):
    mesh, one = sessions["mesh"], sessions["one_chip"]
    assert mesh["digests"] == one["digests"]
    assert mesh["texts"] == one["texts"] and mesh["marks"] == one["marks"]
    assert mesh["shapes"] == one["shapes"]


@pytest.mark.parametrize("where", ["mesh", "one_chip"])
def test_reload_from_summary_and_log_tail_reproduces_every_digest(
        sessions, where):
    """The summary was taken three rounds before the end: what follows it
    only the log holds. Documents the tail did not touch come back bit for
    bit; those it touched are merged again from the log, by another path
    than the door's, and are held to the oracle (as the benchmark's reload
    check holds them to its reference). On the mesh the reload lands on
    the same four devices (``state_devices``, above)."""
    got = sessions[where]
    same = np.asarray(got["reload_digests"]) == np.asarray(got["digests"])
    assert len(got["untouched_by_tail"]) == N_DOCS // 2
    assert same[got["untouched_by_tail"]].all()
    assert got["reload_texts"] == got["oracle_texts"]
    assert len(got["reload_marks"]) >= N_DOCS // 8 and all(
        marks == got["oracle_marks"][d]
        for d, marks in got["reload_marks"].items())
