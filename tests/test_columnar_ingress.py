"""Socket→columnar composition (VERDICT r4 missing #5): N real client
sockets aggregate into batched ``ingest_planes`` dispatches through the
binary columnar front door, with oracle parity from the durable log."""

import numpy as np
import pytest

from fluidframework_tpu.server import native_deli
from fluidframework_tpu.server.columnar_ingress import (
    ColumnarAlfred, ColumnarClient, _OP_DTYPE,
)
from fluidframework_tpu.server.serving import StringServingEngine

pytestmark = pytest.mark.skipif(not native_deli.available(),
                                reason="native sequencer unavailable")


def _mk(n_docs=32, window_min_rows=8, window_ms=5.0):
    eng = StringServingEngine(n_docs=n_docs, capacity=256,
                              batch_window=10 ** 9, sequencer="native")
    srv = ColumnarAlfred(eng, window_min_rows=window_min_rows,
                         window_ms=window_ms).start_in_thread()
    return eng, srv


def _ops(rows, kinds, a0s, a1s, tidxs, cseqs, refs):
    ops = np.zeros(len(rows), _OP_DTYPE)
    ops["row"] = rows
    ops["kind"] = kinds
    ops["a0"] = a0s
    ops["a1"] = a1s
    ops["tidx"] = tidxs
    ops["cseq"] = cseqs
    ops["ref"] = refs
    return ops


def test_sockets_compose_into_columnar_windows():
    eng, srv = _mk()
    try:
        n_clients, docs_per, waves = 3, 4, 6
        clients = []
        for c in range(n_clients):
            cl = ColumnarClient("127.0.0.1", srv.port)
            docs = [f"c{c}-d{j}" for j in range(docs_per)]
            cl.join(docs)
            clients.append((cl, docs))
        for w in range(waves):
            for cl, docs in clients:
                rows = [cl.rows[d] for d in docs]
                ops = _ops(rows, [0] * docs_per, [0] * docs_per,
                           [0] * docs_per, [0] * docs_per,
                           [w + 1] * docs_per, [0] * docs_per)
                cl.send_ops([f"t{w}."], ops)
        # every op acks with a positive seq
        for cl, docs in clients:
            acked = 0
            while acked < docs_per * waves:
                resp = cl.recv_json()
                assert resp["t"] == "acks", resp
                for cs, seq in resp["acks"]:
                    assert seq > 0, (cs, seq)
                    acked += 1
        assert srv.ops_ingested == n_clients * docs_per * waves
        # aggregation happened: far fewer windows than ops
        assert srv.windows_flushed <= waves * n_clients
        # oracle parity from the durable log on sampled docs
        from fluidframework_tpu.models.shared_string import SharedString
        for cl, docs in clients[:2]:
            d = docs[1]
            oracle = SharedString(d, 999)
            for m in eng._doc_log_messages(d):
                oracle.process_core(m, local=False)
            assert eng.read_text(d) == oracle.get_text(), d
        for cl, _ in clients:
            cl.close()
    finally:
        srv.stop()


def test_mixed_inserts_and_removes_share_one_doc():
    eng, srv = _mk(window_min_rows=1, window_ms=2.0)
    try:
        a = ColumnarClient("127.0.0.1", srv.port)
        b = ColumnarClient("127.0.0.1", srv.port)
        a.join(["shared"])
        b.join(["shared"])
        row = a.rows["shared"]
        a.send_ops(["hello"], _ops([row], [0], [0], [0], [0], [1], [0]))
        s1 = a.recv_json()["acks"][0][1]
        assert s1 > 0
        # b inserts at pos 2 AT THE PERSPECTIVE of a's op (ref = its seq)
        b.send_ops(["XY"], _ops([row], [0], [2], [0], [0], [1], [s1]))
        s2 = b.recv_json()["acks"][0][1]
        assert s2 > 0
        a.send_ops([], _ops([row], [1], [0], [1], [0], [2], [s2]))
        assert a.recv_json()["acks"][0][1] > 0
        from fluidframework_tpu.models.shared_string import SharedString
        oracle = SharedString("shared", 999)
        for m in eng._doc_log_messages("shared"):
            oracle.process_core(m, local=False)
        assert eng.read_text("shared") == oracle.get_text()
        a.close()
        b.close()
    finally:
        srv.stop()


def test_malformed_op_frames_rejected_whole():
    """tidx out of table range / ragged record sections reject the WHOLE
    frame with an error frame (no half-enqueued batch)."""
    from fluidframework_tpu.server.columnar_ingress import encode_frame
    eng, srv = _mk()
    try:
        cl = ColumnarClient("127.0.0.1", srv.port)
        cl.join(["d0"])
        row = cl.rows["d0"]
        cl.send_ops(["only-one"], _ops([row, row], [0, 0], [0, 0],
                                       [0, 0], [0, 7], [1, 2], [0, 0]))
        resp = cl.recv_json()
        assert resp["t"] == "error" and "tidx" in resp["message"]
        cl.close()
        c2 = ColumnarClient("127.0.0.1", srv.port)
        c2.join(["d1"])
        c2.sock.sendall(encode_frame(b"B", bytes([0]) + b"\x01" * 17))
        resp = c2.recv_json()
        assert resp["t"] == "error" and "record" in resp["message"]
        c2.close()
        assert srv.ops_ingested == 0 and srv._pending_ops == 0
    finally:
        srv.stop()


def test_bad_row_and_bad_crc_handling():
    eng, srv = _mk()
    try:
        cl = ColumnarClient("127.0.0.1", srv.port)
        cl.join(["d0"])
        cl.send_ops(["x"], _ops([999], [0], [0], [0], [0], [1], [0]))
        resp = cl.recv_json()
        assert resp["t"] == "error" and "out of range" in resp["message"]
        cl.close()
        # a second client still works after the first one's bad frame
        c2 = ColumnarClient("127.0.0.1", srv.port)
        c2.join(["d1"])
        row = c2.rows["d1"]
        c2.send_ops(["ok"], _ops([row], [0], [0], [0], [0], [1], [0]))
        while True:
            resp = c2.recv_json()
            if resp["t"] == "acks":
                break
        assert resp["acks"][0][1] > 0
        c2.close()
    finally:
        srv.stop()


def test_pipelined_front_door_parity_and_stats():
    """The depth-3 pipelined front door must produce the same final doc
    texts as a depth-0 (serial round-trip per window) server on the same
    deterministic op stream, while actually engaging the executor
    (waves flushed through it, acks only after durable append)."""
    def _run_stream(pipeline_depth):
        eng = StringServingEngine(n_docs=32, capacity=256,
                                  batch_window=10 ** 9,
                                  sequencer="native")
        srv = ColumnarAlfred(eng, window_min_rows=4, window_ms=1.0,
                             pipeline_depth=pipeline_depth
                             ).start_in_thread()
        texts = {}
        try:
            n_clients, docs_per, waves = 2, 3, 12
            clients = []
            for c in range(n_clients):
                cl = ColumnarClient("127.0.0.1", srv.port)
                docs = [f"c{c}-d{j}" for j in range(docs_per)]
                cl.join(docs)
                clients.append((cl, docs))
            for w in range(waves):
                for ci, (cl, docs) in enumerate(clients):
                    rows = [cl.rows[d] for d in docs]
                    # deterministic per-doc content: each doc's final
                    # text is independent of cross-client interleaving
                    cl.send_ops([f"w{w}c{ci}."],
                                _ops(rows, [0] * docs_per, [0] * docs_per,
                                     [0] * docs_per, [0] * docs_per,
                                     [w + 1] * docs_per, [0] * docs_per))
            for cl, docs in clients:
                acked = 0
                while acked < docs_per * waves:
                    resp = cl.recv_json()
                    assert resp["t"] == "acks", resp
                    for _cs, seq in resp["acks"]:
                        assert seq > 0
                        acked += 1
            stats = srv.pipeline_stats()
            windows = srv.windows_flushed
            for cl, docs in clients:
                for d in docs:
                    texts[d] = eng.read_text(d)
                cl.close()
        finally:
            srv.stop()
        return texts, stats, windows

    serial_texts, serial_stats, _ = _run_stream(0)
    pipe_texts, pipe_stats, pipe_windows = _run_stream(3)
    assert serial_stats is None           # depth 0 = no executor
    assert pipe_texts == serial_texts     # front doors agree op-for-op
    assert pipe_stats is not None
    assert pipe_stats["depth"] == 3
    assert pipe_stats["waves"] == pipe_windows  # every window pipelined
    assert pipe_stats["waves"] > 0
    assert pipe_stats["max_inflight"] >= 1


# ---------------------------------------------------------------- carving
#
# ``_build_windows`` without sockets: a door that is never started, its
# decoded parts filled in by hand. An op's ``cseq`` is its index in the
# pass (parts in arrival order), so it names the op wherever it lands.

class _Sess:
    """Stands in for a ``_ColSession``: the carving only names it."""


def _door_with_pass(rng, n_partitions, window_min_rows, max_pending,
                    dpp=40, n_sessions=4, compact_every=0):
    """A door holding one decoded drain pass: every row of every
    partition has 0..``max_pending`` ops pending, dealt over the
    sessions at random (so rows have several writers), each session's
    ops one part in arrival order. Returns the door, the pass's
    concatenated planes as ``_build_windows`` will see them, each op's
    part and the parts' sessions."""
    from fluidframework_tpu.server import columnar_ingress as ci
    from fluidframework_tpu.utils import tracing
    n_rows = n_partitions * dpp
    stub = type("Eng", (), {})()
    stub.n_docs = n_rows
    stub.compact_every = compact_every
    if n_partitions > 1:
        stub.engines = [stub] * n_partitions
        stub.docs_per_partition = dpp
    door = ColumnarAlfred(stub, window_min_rows=window_min_rows)
    door._pass_tl = tracing.new_record(pid=0, frames=0, ops=0,
                                       admit_ms=0.0)
    pending = rng.integers(0, max_pending + 1, n_rows)
    # the first chunk full, and (where rows may be pending twice) every
    # row of it pending enough to fill the widest window: one chunk that
    # does widen
    pending[:window_min_rows] = np.maximum(
        pending[:window_min_rows], min(max_pending, ci._WINDOW_COLUMNS[0]))
    rows = rng.permutation(np.repeat(np.arange(n_rows), pending))
    writer = rng.integers(0, n_sessions, rows.size)
    door._texts = [f"t{i}" for i in range(6)]
    door._props = [{"k": i} for i in range(3)]
    parts, at = [], 0
    for s in range(n_sessions):
        r = rows[writer == s].astype(np.int32)
        if not r.size:
            continue
        kind = rng.integers(0, 3, r.size).astype(np.int32)
        gidx = np.where(kind == 0, rng.integers(0, 6, r.size),
                        np.where(kind == 2, rng.integers(0, 3, r.size), 0)
                        ).astype(np.int32)
        parts.append({
            "sess": _Sess(), "row": r, "kind": kind, "gidx": gidx,
            "a0": rng.integers(0, 50, r.size).astype(np.int32),
            "a1": rng.integers(0, 50, r.size).astype(np.int32),
            "cseq": np.arange(at, at + r.size, dtype=np.int32),
            "ref": rng.integers(0, 9, r.size).astype(np.int32),
            "client": np.full(r.size, 100 + s, np.int32)})
        at += r.size
    door._parts = list(parts)
    planes = {k: np.concatenate([p[k] for p in parts]) for k in ci._PLANES}
    sess_of = np.concatenate([np.full(p["row"].size, i)
                              for i, p in enumerate(parts)])
    return door, planes, sess_of, [p["sess"] for p in parts]


def _parents_carving(door, planes):
    """The carving this door had until it learned to widen, kept as the
    reference: stable sort by row, split by per-row occurrence level,
    cut every ``window_min_rows``; every window one column wide."""
    row = planes["row"]
    n = row.size
    order = np.argsort(row, kind="stable")
    srow = row[order]
    if door.n_partitions > 1:
        pids = srow // door._dpp
        pcuts = np.flatnonzero(np.diff(pids)) + 1
        segs = [(int(pids[seg[0]]), seg)
                for seg in np.split(np.arange(n), pcuts)]
    else:
        segs = [(0, np.arange(n))]
    chunks = []
    for part, seg in segs:
        so = srow[seg]
        m = so.size
        new = np.empty(m, bool)
        new[0] = True
        new[1:] = so[1:] != so[:-1]
        starts = np.flatnonzero(new)
        occ = np.arange(m) - np.repeat(starts,
                                       np.diff(np.append(starts, m)))
        lvl_order = np.argsort(occ, kind="stable")
        cuts = np.flatnonzero(np.diff(occ[lvl_order])) + 1
        oseg = order[seg]
        for lvl in np.split(oseg[lvl_order], cuts):
            for s in range(0, lvl.size, door.window_min_rows):
                chunks.append((part, lvl[s:s + door.window_min_rows]))
    return chunks


def _by_partition(windows):
    out = {}
    for w in windows:
        out.setdefault(w["part"], []).append(w)
    return out


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("compact_every", [0, 3])
@pytest.mark.parametrize("window_min_rows", [4, 16])
@pytest.mark.parametrize("n_partitions", [1, 3])
def test_carving_is_dense_ordered_and_holds_every_op_once(
        n_partitions, window_min_rows, compact_every, seed):
    from fluidframework_tpu.server.columnar_ingress import _WINDOW_COLUMNS
    rng = np.random.default_rng([seed, n_partitions, window_min_rows])
    door, planes, sess_of, sessions = _door_with_pass(
        rng, n_partitions, window_min_rows, max_pending=12,
        compact_every=compact_every)
    door._windows_to = [6] * n_partitions   # the engines' counts so far
    texts_g, props_g = list(door._texts), list(door._props)
    n = planes["row"].size
    windows = door._build_windows()
    assert door._parts == [] and door._pass_tl["windows"] == len(windows)
    seen = []
    served = {}                     # row → its ops in submission order
    widths = set()
    for w in windows:
        R, O = w["kind"].shape
        widths.add(O)
        assert O in _WINDOW_COLUMNS and 1 <= R <= window_min_rows
        assert O == 1 or R == window_min_rows   # only a full chunk widens
        assert w["rows"].shape == (R,) and len(set(w["rows"])) == R
        for k in ("a0", "a1", "tidx", "cseq", "ref", "client", "sessi"):
            assert w[k].shape == (R, O), k
        assert w["rec"]["ops"] == R * O
        ids = w["cseq"]                     # the ops' indices in the pass
        seen.append(ids.reshape(-1))
        # dense: every slot is a real op of the window's row, as it came
        assert (planes["row"][ids] == w["rows"][:, None]).all()
        for k in ("kind", "a0", "a1", "ref", "client"):
            assert (w[k] == planes[k][ids]).all(), k
        assert (w["rows"] // door._dpp == w["part"]).all() \
            if n_partitions > 1 else w["part"] == 0
        for i, j in np.ndindex(R, O):
            assert w["tab"][w["sessi"][i, j]] is sessions[sess_of[ids[i, j]]]
            g = planes["gidx"][ids[i, j]]
            if w["kind"][i, j] == 0:
                assert w["texts"][w["tidx"][i, j]] == texts_g[g]
            elif w["kind"][i, j] == 2:
                assert w["props"][w["tidx"][i, j]] == props_g[g]
        for r, row_ids in zip(w["rows"].tolist(), ids.tolist()):
            served.setdefault(r, []).extend(row_ids)
    # every op in exactly one window
    assert np.array_equal(np.sort(np.concatenate(seen)), np.arange(n))
    # a row's ops in arrival order, across columns and across windows
    for r, got in served.items():
        assert got == np.flatnonzero(planes["row"] == r).tolist(), r
    assert widths > {1}             # the pass did carve wide somewhere
    # partitions interleave, a partition's windows keep their order
    if n_partitions > 1:
        assert len(_by_partition(windows)) > 1
    # the window an engine will fuse its zamboni into (every
    # ``compact_every``-th it is handed) is never a wide one
    for part, ws in _by_partition(windows).items():
        for nth, w in enumerate(ws, start=6):
            if compact_every and (nth + 1) % compact_every == 0:
                assert w["kind"].shape[1] == 1, (part, nth)


@pytest.mark.parametrize("seed", range(2))
@pytest.mark.parametrize("n_partitions", [1, 3])
def test_a_pass_with_no_row_pending_twice_is_carved_as_before(
        n_partitions, seed):
    rng = np.random.default_rng([7, seed, n_partitions])
    door, planes, sess_of, sessions = _door_with_pass(
        rng, n_partitions, window_min_rows=5, max_pending=1)
    texts_g, props_g = list(door._texts), list(door._props)
    want = _by_partition(
        [{"part": p, "w": w} for p, w in _parents_carving(door, planes)])
    got = _by_partition(door._build_windows())
    assert sorted(got) == sorted(want)
    for part in want:
        assert len(got[part]) == len(want[part])
        for g, ref in zip(got[part], want[part]):
            w = ref["w"]
            assert np.array_equal(g["rows"], planes["row"][w])
            for k in ("kind", "a0", "a1", "cseq", "ref", "client"):
                assert np.array_equal(g[k], planes[k][w].reshape(-1, 1)), k
            assert np.array_equal(g["sessi"].reshape(-1), sess_of[w])
            # and its own payload tables, compacted from the pass's
            tidx = np.zeros(w.size, np.int32)
            tables = {}
            for name, code, table in (("texts", 0, texts_g),
                                      ("props", 2, props_g)):
                m = planes["kind"][w] == code
                u, inv = np.unique(planes["gidx"][w][m],
                                   return_inverse=True)
                tidx[m] = inv
                tables[name] = [table[i] for i in u.tolist()]
            assert np.array_equal(g["tidx"], tidx.reshape(-1, 1))
            assert g["texts"] == (tables["texts"] or [""])
            assert g["props"] == (tables["props"] or None)


# ------------------------------------- wide windows, end to end on sockets

def _serve_two_held_passes(log_dir, columns, monkeypatch):
    """Three writers through a real door on the interpreted Pallas
    kernel, their frames held back until a pass holds them all: pass 1
    keeps four frames in flight a connection (three on one, so a chunk's
    rows differ in what they have pending), with two documents written
    by two and three connections; a summary; pass 2, which only the log
    holds, resubmits an acked op, skips a ``client_seq`` (a nack) and
    goes on. The door carves with ``columns`` as its column counts."""
    import threading
    import time
    from fluidframework_tpu.server import columnar_ingress as ci
    from fluidframework_tpu.server.native_oplog import NativePartitionedLog
    from fluidframework_tpu.utils.telemetry import REGISTRY
    monkeypatch.setattr(ci, "_WINDOW_COLUMNS", columns)
    count0 = {k: REGISTRY.counters.get(k, 0) for k in (
        "columnar_windows_flushed", "columnar_window_columns",
        "columnar_windows_wide")}
    log = NativePartitionedLog(str(log_dir), 8)
    eng = StringServingEngine(n_docs=32, capacity=128, sequencer="native",
                              log=log)
    eng.store.pallas = "interpret"
    srv = ColumnarAlfred(eng, window_min_rows=8, window_ms=2.0,
                         decode="native").start_in_thread()
    hold = threading.Event()
    drain = srv._drain
    srv._drain = lambda: None if hold.is_set() else drain()
    acks = {}                           # (writer, row, cseq) → seqs, in order
    try:
        own = {w: [f"{w}{i}" for i in range(10)] for w in "abc"}
        writes = {"a": own["a"] + ["c0", "c1"], "b": own["b"] + ["c1"],
                  "c": own["c"]}
        cl = {}
        for w in "abc":
            cl[w] = ColumnarClient("127.0.0.1", srv.port)
            cl[w].join(writes[w])
        sess = {w: next(s for s in srv._sessions
                        if s.client_id == cl[w].client_id) for w in cl}
        expect = dict.fromkeys(cl, 0)

        def send(w, cseq_of, f, docs=None):
            """One frame: an op on every document ``w`` writes (or on
            ``docs``); a document's third op removes its first
            character."""
            docs = docs or writes[w]
            rows = [cl[w].rows[d] for d in docs]
            cseqs = [cseq_of(d) for d in docs]
            kinds = [1 if c == 3 and d in own[w] else 0
                     for c, d in zip(cseqs, docs)]
            cl[w].send_ops([f"{w}{f}"], _ops(
                rows, kinds, [0] * len(docs), kinds, [0] * len(docs),
                cseqs, [0] * len(docs)))
            expect[w] += len(docs)

        def landed(w):
            # held, a session's buffer only grows: once it stands still
            # with every frame sent, the frames are all there
            size, t_end = -1, time.monotonic() + 30
            while len(sess[w].rx) != size or not size:
                size = len(sess[w].rx)
                time.sleep(0.05)
                assert time.monotonic() < t_end
            return size

        def collect():
            for w in cl:
                while expect[w]:
                    resp = cl[w].recv_json()
                    assert resp["t"] == "acks", resp
                    assert len(set(resp["rows"])) == len(resp["rows"])
                    for (cs, seq), row in zip(resp["acks"], resp["rows"]):
                        acks.setdefault((w, row, cs), []).append(seq)
                    expect[w] -= len(resp["acks"])

        # ---- pass 1: a's frames land before b's, b's before c's -------
        hold.set()
        for w, frames in (("a", 4), ("b", 3), ("c", 4)):
            for f in range(frames):
                send(w, lambda d, f=f: f + 1, f)
            landed(w)
        hold.clear()
        collect()
        srv._executor.drain(60.0)
        summary = eng.summarize()

        # ---- pass 2: a duplicate, a nack, and on ----------------------
        hold.set()
        send("a", lambda d: {"a0": 4, "a1": 9}.get(d, 5), 4)
        send("a", lambda d: {"a0": 5, "a1": 5}.get(d, 6), 5)
        landed("a")
        send("b", lambda d: 4, 4)
        send("b", lambda d: 5, 5)
        landed("b")
        send("c", lambda d: 5, 4, docs=own["c"][:5])
        landed("c")
        hold.clear()
        collect()
        srv._executor.drain(60.0)

        docs = sorted(set(sum(writes.values(), [])))
        revived = StringServingEngine.load(summary, log,
                                           sequencer="native")
        revived.store.pallas = "interpret"
        out = {
            "acks": acks,
            "texts": {d: eng.read_text(d) for d in docs},
            "digests": eng.store.digests().tolist(),
            "untouched_by_tail": [cl["c"].rows[d] for d in own["c"][5:]],
            "log": {d: [(m.seq, m.client_id, m.client_seq, m.ref_seq,
                         m.contents) for m in eng._doc_log_messages(d)]
                    for d in docs},
            "reload_digests": revived.store.digests().tolist(),
            "reload_texts": {d: revived.read_text(d) for d in docs},
            "ops": srv.ops_ingested,
            "counters": {k: REGISTRY.counters.get(k, 0) - v
                         for k, v in count0.items()},
        }
        for c in cl.values():
            c.close()
        return out
    finally:
        srv.stop()
        log.close()


def test_wide_and_one_column_carving_serve_the_same_session(
        tmp_path, monkeypatch):
    from fluidframework_tpu.server import native_ingress, native_oplog
    from fluidframework_tpu.server.columnar_ingress import _WINDOW_COLUMNS
    if not (native_oplog.available() and native_ingress.available()):
        pytest.skip("native log or decode unavailable")
    wide = _serve_two_held_passes(tmp_path / "wide", _WINDOW_COLUMNS,
                                  monkeypatch)
    ref = _serve_two_held_passes(tmp_path / "ref", (1,), monkeypatch)
    # the wide door did carve wide, the reference never
    cw, cr = wide["counters"], ref["counters"]
    assert cr["columnar_windows_wide"] == 0
    assert cr["columnar_window_columns"] == cr["columnar_windows_flushed"]
    assert cw["columnar_windows_wide"] > 0
    assert cw["columnar_window_columns"] > cw["columnar_windows_flushed"]
    assert cw["columnar_windows_flushed"] < cr["columnar_windows_flushed"]
    assert wide["ops"] == ref["ops"] == 48 + 33 + 40 + 24 + 22 + 5
    # exactly one ack an op, the same sequence number from either door;
    # the resubmitted op is acked again under the number it has, the
    # skipped client_seq is nacked
    assert wide["acks"] == ref["acks"]
    dup = {k: s for k, s in wide["acks"].items() if len(s) != 1}
    assert [(k[0], k[2]) for k in dup] == [("a", 4)]
    (first, again), = dup.values()
    assert first == again > 0
    nacked = {k: s for k, s in wide["acks"].items() if s[0] < 0}
    assert [k[2] for k in nacked] == [9]
    # per document: gapless numbers, the same text, log and reload
    for d, msgs in wide["log"].items():
        seqs = [m[0] for m in msgs]
        assert seqs == list(range(seqs[0], seqs[0] + len(seqs))), d
    for k in ("texts", "log", "reload_texts"):
        assert wide[k] == ref[k], k
    # a digest mixes in the payloads' handles, which are numbered as the
    # tables that brought them come: it is equal between a store and its
    # reload where the summary alone holds the document, and neither
    # between two carvings nor where the reload merged the log's tail
    # again by its own path; there the text decides
    for run in (wide, ref):
        rows = run["untouched_by_tail"]
        assert len(rows) == 5
        assert np.array_equal(np.asarray(run["reload_digests"])[rows],
                              np.asarray(run["digests"])[rows])
        assert run["reload_texts"] == run["texts"]
    assert all(wide["texts"].values())


def test_an_op_and_its_resubmit_never_ride_one_window():
    """A resubmitted op is re-acked from the dedup ledger, which learns of
    the original when its window's acks are fanned: so the two lie in
    different windows, the original's first, however wide the rest go."""
    from fluidframework_tpu.utils import tracing
    stub = type("Eng", (), {"n_docs": 4})()
    door = ColumnarAlfred(stub, window_min_rows=2)
    door._pass_tl = tracing.new_record(pid=0, frames=0, ops=0, admit_ms=0.0)
    door._texts = ["x"]
    # both rows hold cseq 3 (sent before a reconnect), then 1..4 resubmitted
    cseq = np.array([3, 3, 1, 1, 2, 2, 3, 3, 4, 4], np.int32)
    row = np.array([0, 1] * 5, np.int32)
    zeros = np.zeros(10, np.int32)
    door._parts = [{"sess": _Sess(), "row": row, "kind": zeros, "a0": zeros,
                    "a1": zeros, "gidx": zeros, "cseq": cseq, "ref": zeros,
                    "client": np.full(10, 7, np.int32)}]
    windows = door._build_windows()
    seen = []
    for w in windows:
        assert (w["cseq"] == w["cseq"][:1]).all()       # rows move together
        per_row = w["cseq"][0].tolist()
        assert len(set(per_row)) == len(per_row), per_row
        seen += per_row
    assert seen == [3, 1, 2, 3, 4]
    assert max(w["kind"].shape[1] for w in windows) > 1  # and still widens
