"""The tree columnar record wire format (server/tree_wire.py):
encode→decode round-trips, ingest_records vs per-op submit parity,
durable TreeRecordOps codec, raw-plane recovery, and bounds rejection."""

import random

import numpy as np
import pytest

from fluidframework_tpu.models.shared_tree import SharedTree
from fluidframework_tpu.server.serving import (
    TreeRecordOps, TreeServingEngine,
)
from fluidframework_tpu.server.tree_wire import (
    TreeBatchEncoder, decode_op, encode_tree_batch,
)

from tests.test_tree_kernel import tree_session


def _normalize(op):
    """Encoder-canonical form of an op dict: every spec carries explicit
    type/value keys; a constraint-free one-edit transaction is its edit."""
    kind = op["op"]
    if kind == "insert":
        def norm_spec(s):
            out = {"id": s["id"], "type": s.get("type"),
                   "value": s.get("value")}
            kids = {f: [norm_spec(c) for c in cs]
                    for f, cs in (s.get("children") or {}).items() if cs}
            if kids:
                out["children"] = kids
            return out
        return {"op": "insert", "parent": op["parent"],
                "field": op["field"], "after": op.get("after"),
                "nodes": [norm_spec(s) for s in op["nodes"]]}
    if kind == "transaction":
        cons = [c for c in op.get("constraints", ())]
        edits = [_normalize(e) for e in op["edits"]]
        if not cons and len(edits) == 1 and edits[0]["op"] == "insert":
            return edits[0]
        out = {"op": "transaction", "edits": edits}
        if cons:
            out["constraints"] = cons
        return out
    if kind == "move":
        return {"op": "move", "id": op["id"], "parent": op["parent"],
                "field": op["field"], "after": op.get("after")}
    return dict(op)


@pytest.mark.parametrize("seed", range(4))
def test_encode_decode_round_trip_fuzz(seed):
    """decode(encode(op)) ≡ op (canonical form) over the fuzz corpus."""
    _, msgs = tree_session(seed)
    ops = [m.contents for m in msgs]
    enc = TreeBatchEncoder()
    for op in ops:
        enc.add(op)
    b = enc.batch()
    rec_op = np.asarray(b["rec_op"])
    for i, op in enumerate(ops):
        sel = np.flatnonzero(rec_op == i)
        recs = [tuple(int(v) for v in b["recs"][j]) for j in sel]
        got = decode_op(recs, b["ids"], b["fields"], b["types"],
                        b["values"])
        assert _normalize(got) == _normalize(op), f"op {i}"


def test_decode_preserves_multinode_and_nested():
    op = {"op": "insert", "parent": "root", "field": "kids",
          "after": "anchor",
          "nodes": [
              {"id": "a", "type": "t", "value": 1,
               "children": {"f1": [{"id": "a1", "type": None,
                                    "value": None},
                                   {"id": "a2", "type": "u",
                                    "value": [1, 2]}],
                            "f2": [{"id": "a3", "type": None,
                                    "value": "x"}]}},
              {"id": "b", "type": None, "value": None}]}
    b = encode_tree_batch([op, {"op": "insert", "parent": "root",
                                "field": "kids", "after": "anchor",
                                "nodes": [{"id": "c"}]}])
    rec_op = np.asarray(b["rec_op"])
    sel = np.flatnonzero(rec_op == 0)
    recs = [tuple(int(v) for v in b["recs"][j]) for j in sel]
    got = decode_op(recs, b["ids"], b["fields"], b["types"], b["values"])
    assert _normalize(got) == _normalize(op)


def _mk(n_docs=6):
    eng = TreeServingEngine(n_docs=n_docs, capacity=256,
                            batch_window=10 ** 9, sequencer="native")
    docs = [f"d{i}" for i in range(n_docs)]
    for d in docs:
        eng.connect(d, 1)
    return eng, docs


def _fuzz_waves(docs, seeds):
    """Per-doc fuzz sessions re-cut into cross-doc ingest waves."""
    per_doc = {d: [m.contents for m in tree_session(s, n_rounds=6)[1]]
               for d, s in zip(docs, seeds)}
    waves = []
    w = 0
    while any(per_doc.values()):
        ids, ops = [], []
        for d in docs:
            if per_doc[d]:
                ids.append(d)
                ops.append(per_doc[d].pop(0))
        waves.append((ids, ops))
        w += 1
    return waves


#: the fuzz comparisons below each run over two sets of six sessions
SEED_SETS = (0, 100)


@pytest.mark.parametrize("seed0", SEED_SETS)
def test_ingest_records_matches_per_op_submit(seed0):
    """The columnar record path and the per-op submit path produce the
    same trees for the same op streams (fuzz corpus incl. transactions,
    nested inserts, moves, removes)."""
    eng_a, docs = _mk()
    eng_b, _ = _mk()
    waves = _fuzz_waves(docs, range(seed0 + 10, seed0 + 16))
    for w, (ids, ops) in enumerate(waves):
        cseq = [w + 1] * len(ids)
        res = eng_a.ingest_batch(ids, [1] * len(ids), cseq,
                                 [0] * len(ids), ops)
        assert res["nacked"] == 0
        for d, op in zip(ids, ops):
            _, nack = eng_b.submit(d, 1, w + 1, 0, op)
            assert nack is None
    for d in docs:
        assert eng_a.to_dict(d) == eng_b.to_dict(d), d


def _assert_oracle_parity(eng, docs):
    for d in docs:
        oracle = SharedTree(d, 999)
        for m in eng._doc_log_messages(d):
            oracle.process_core(m, local=False)
        assert eng.to_dict(d) == oracle.to_dict(), d


@pytest.mark.parametrize("seed0", SEED_SETS)
def test_ingest_records_oracle_parity_and_log_replay(seed0):
    eng, docs = _mk()
    waves = _fuzz_waves(docs, range(seed0 + 20, seed0 + 26))
    for w, (ids, ops) in enumerate(waves):
        eng.ingest_batch(ids, [1] * len(ids), [w + 1] * len(ids),
                         [0] * len(ids), ops)
    _assert_oracle_parity(eng, docs[:3])


def test_back_to_back_waves_of_one_shape_lose_no_record():
    """Many small waves of ONE bucket shape (six one-record ops, a
    256-record wire bucket) pushed through ``ingest_batch`` with no
    device sync between them: a wave's wire buffers and table maps must
    still hold its records when the device reads them, however many
    waves the host has packed since. Compared at the end with the
    per-op engine and the oracle."""
    eng_a, docs = _mk()
    eng_b, _ = _mk()
    rng = random.Random(5)
    live = {d: [] for d in docs}
    n_waves = 150
    for w in range(n_waves):
        ops = []
        for d in docs:
            nodes = live[d]
            k = rng.random() if len(nodes) >= 4 else 0.0
            if k < 0.6:
                after = rng.choice(nodes) if nodes and rng.random() < 0.7 \
                    else None
                nid = f"{d}-n{w}"
                ops.append({"op": "insert", "parent": "root",
                            "field": "kids", "after": after,
                            "nodes": [{"id": nid, "type": None,
                                       "value": w}]})
                nodes.append(nid)
            elif k < 0.8:
                ops.append({"op": "setValue", "id": rng.choice(nodes),
                            "value": f"v{w}"})
            elif k < 0.9:
                nid, after = rng.sample(nodes, 2)
                ops.append({"op": "move", "id": nid, "parent": "root",
                            "field": "kids", "after": after})
            else:
                ops.append({"op": "remove",
                            "id": nodes.pop(rng.randrange(len(nodes)))})
        n = len(docs)
        res = eng_a.ingest_batch(docs, [1] * n, [w + 1] * n, [0] * n, ops)
        assert res["nacked"] == 0
        for d, op in zip(docs, ops):
            _, nack = eng_b.submit(d, 1, w + 1, 0, op)
            assert nack is None
    for d in docs:
        assert eng_a.to_dict(d) == eng_b.to_dict(d), d
    _assert_oracle_parity(eng_a, docs)


@pytest.mark.parametrize("seed0", SEED_SETS)
def test_tree_records_summary_tail_recovery(seed0):
    """Raw-plane tail replay: summary mid-stream, more record batches,
    then load() rebuilds the same trees (and sequencing continues)."""
    eng, docs = _mk()
    waves = _fuzz_waves(docs, range(seed0 + 30, seed0 + 36))
    cut = len(waves) // 2
    for w, (ids, ops) in enumerate(waves[:cut]):
        eng.ingest_batch(ids, [1] * len(ids), [w + 1] * len(ids),
                         [0] * len(ids), ops)
    summary = eng.summarize()
    for w, (ids, ops) in enumerate(waves[cut:]):
        eng.ingest_batch(ids, [1] * len(ids), [cut + w + 1] * len(ids),
                         [0] * len(ids), ops)
    want = {d: eng.to_dict(d) for d in docs}
    revived = TreeServingEngine.load(summary, eng.log)
    assert {d: revived.to_dict(d) for d in docs} == want
    # sequencing resumes past the tail: a fresh op lands, same on both
    n_sent = sum(1 for ids, _ in waves if docs[0] in ids)
    op = {"op": "insert", "parent": "root", "field": "kids",
          "after": None, "nodes": [{"id": "fresh", "type": None,
                                    "value": 7}]}
    for e in (eng, revived):
        r = e.ingest_batch([docs[0]], [1], [n_sent + 1], [0], [op])
        assert r["nacked"] == 0
    assert revived.to_dict(docs[0]) == eng.to_dict(docs[0])


def test_tree_records_nacks_drop_records_everywhere():
    eng, docs = _mk()
    d0, d1 = docs[0], docs[1]
    # clientSeq gap on the middle op: its records must not apply nor log
    res = eng.ingest_batch(
        [d0, d0, d1], [1] * 3, [1, 99, 1], [0] * 3,
        [{"op": "insert", "parent": "root", "field": "kids",
          "after": None, "nodes": [{"id": "x0"}]},
         {"op": "insert", "parent": "root", "field": "kids",
          "after": None, "nodes": [{"id": "x1"}]},
         {"op": "insert", "parent": "root", "field": "kids",
          "after": None, "nodes": [{"id": "y0"}]}])
    assert res["nacked"] == 1 and res["seq"][1] < 0
    assert eng.has_node(d0, "x0") and not eng.has_node(d0, "x1")
    assert eng.has_node(d1, "y0")
    # the durable record kept only the acked ops
    msgs = eng._doc_log_messages(d0)
    assert [m.contents["nodes"][0]["id"] for m in msgs] == ["x0"]
    # and recovery agrees
    revived = TreeServingEngine.load(eng.summarize(), eng.log)
    assert revived.to_dict(d0) == eng.to_dict(d0)


def test_malformed_record_batches_rejected_before_sequencing():
    eng, docs = _mk()
    d = docs[0]
    seq_before = eng.deli.doc_seq(d)
    base = {"rec_op": np.zeros(1, np.int64),
            "recs": np.zeros((1, 8), np.int32),
            "ids": ["n"], "fields": ["f"], "types": [], "values": []}

    def bad(**kw):
        b = dict(base)
        b.update(kw)
        return b

    recs_badkind = np.zeros((1, 8), np.int32)
    recs_badkind[0, 0] = 99
    with pytest.raises(ValueError, match="kind out of range"):
        eng.ingest_records([d], [1], [1], [0], bad(recs=recs_badkind))
    recs_badnode = np.zeros((1, 8), np.int32)
    recs_badnode[0, 0] = 9   # INSERT_SOLO
    recs_badnode[0, 1] = 5   # out of ids table
    with pytest.raises(ValueError, match="node handle"):
        eng.ingest_records([d], [1], [1], [0], bad(recs=recs_badnode))
    with pytest.raises(ValueError, match="rec_op"):
        eng.ingest_records([d], [1], [1], [0],
                           bad(rec_op=np.asarray([3], np.int64)))
    with pytest.raises(ValueError, match="non-empty str"):
        eng.ingest_records([d], [1], [1], [0], bad(ids=[""]))
    with pytest.raises(ValueError, match="unserializable"):
        eng.ingest_records([d], [1], [1], [0], bad(values=[set()]))
    assert eng.deli.doc_seq(d) == seq_before
    eng.summarize()   # not poisoned


def test_tree_records_native_log_round_trip(tmp_path):
    from fluidframework_tpu.server import native_oplog
    if not native_oplog.available():
        pytest.skip("native oplog unavailable")
    rec = TreeRecordOps(
        doc_ids=["a", "b"], doc=np.array([0, 1, 0], np.int64),
        client=np.array([1, 2, 1], np.int64),
        client_seq=np.array([1, 1, 2], np.int64),
        ref_seq=np.array([0, 0, 1], np.int64),
        seq=np.array([2, 2, 3], np.int64),
        min_seq=np.array([0, 0, 0], np.int64),
        rec_op=np.array([0, 1, 1, 2], np.int64),
        recs=np.array([[9, 1, 2, 0, 1, 0, 0, 0],
                       [3, 0, 0, 0, 0, 0, 0, 0],
                       [8, 1, 0, 0, 0, 1, 0, 0],
                       [10, 1, 0, 0, 0, 0, 0, 0]], np.int32),
        ids=["n1", "root"], fields=["kids"], types=[],
        values=[{"deep": [1, None]}], timestamp=123.5)
    log = native_oplog.NativePartitionedLog(str(tmp_path), 2)
    log.append(1, rec)
    got = next(iter(log.read(1)))
    log.close()
    assert isinstance(got, TreeRecordOps)
    assert got.doc_ids == rec.doc_ids and got.ids == rec.ids
    assert got.fields == rec.fields and got.values == rec.values
    assert got.timestamp == rec.timestamp
    for f in ("doc", "client", "client_seq", "ref_seq", "seq", "min_seq",
              "rec_op"):
        assert np.array_equal(getattr(got, f), getattr(rec, f)), f
    assert np.array_equal(got.recs, rec.recs)


def _batch_equal(a, b):
    """Byte-level batch identity: same record planes AND same tables
    (handle order included) — the vectorized encoder is a drop-in."""
    return (np.array_equal(np.asarray(a["rec_op"]),
                           np.asarray(b["rec_op"]))
            and np.array_equal(np.asarray(a["recs"]),
                               np.asarray(b["recs"]))
            and list(a["ids"]) == list(b["ids"])
            and list(a["fields"]) == list(b["fields"])
            and list(a["types"]) == list(b["types"])
            and list(a["values"]) == list(b["values"]))


#: deterministic corpus touching every record kind the encoder emits:
#: guarded multi-node insert, nested children, solo insert/remove/
#: set/move, and a constrained transaction (TXN_BEGIN_EXISTS + guards)
ALL_KINDS_OPS = [
    {"op": "insert", "parent": "root", "field": "kids", "after": None,
     "nodes": [{"id": "a", "type": "t", "value": 1},
               {"id": "b", "type": None, "value": None}]},
    {"op": "insert", "parent": "a", "field": "sub", "after": None,
     "nodes": [{"id": "c", "type": "u", "value": [1, {"k": None}],
                "children": {"f1": [{"id": "c1", "value": "x"}],
                             "f2": [{"id": "c2", "type": "v"}]}}]},
    {"op": "insert", "parent": "root", "field": "kids", "after": "a",
     "nodes": [{"id": "solo", "value": 7}]},
    {"op": "setValue", "id": "a", "value": {"deep": [None, 2.5]}},
    {"op": "move", "id": "b", "parent": "a", "field": "sub",
     "after": "c"},
    {"op": "remove", "id": "solo"},
    {"op": "transaction",
     "constraints": [{"nodeExists": "a"}, {"nodeExists": "c"}],
     "edits": [{"op": "insert", "parent": "a", "field": "sub",
                "after": "c", "nodes": [{"id": "d", "value": 9}]},
               {"op": "setValue", "id": "c", "value": 10},
               {"op": "move", "id": "d", "parent": "c", "field": "f1",
                "after": None},
               {"op": "remove", "id": "b"}]},
]


def test_vectorized_encoder_matches_reference_all_kinds():
    """The vectorized TreeBatchEncoder (one interner pass per table,
    numpy-packed records) is byte-identical to the per-op reference
    encoder on a corpus covering every record kind."""
    from fluidframework_tpu.server.tree_wire import (
        ReferenceTreeBatchEncoder,
    )
    vec, ref = TreeBatchEncoder(), ReferenceTreeBatchEncoder()
    for op in ALL_KINDS_OPS:
        assert vec.add(op) == ref.add(op)
    assert _batch_equal(vec.batch(), ref.batch())


@pytest.mark.parametrize("seed", range(4))
def test_vectorized_encoder_matches_reference_fuzz(seed):
    """Seeded parity over the oracle fuzz corpus (numeric ``#N`` ids ride
    the int fast path; tables must still come out handle-identical)."""
    from fluidframework_tpu.server.tree_wire import (
        ReferenceTreeBatchEncoder,
    )
    _, msgs = tree_session(seed)
    vec, ref = TreeBatchEncoder(), ReferenceTreeBatchEncoder()
    for m in msgs:
        vec.add(m.contents)
        ref.add(m.contents)
    assert _batch_equal(vec.batch(), ref.batch())


def test_leaf_builder_matches_general_encoder():
    """encode_leaf_records (the unified flat path) emits the same
    INSERT_SOLO ops as the general encoder fed the equivalent one-node
    inserts — flat is the same wire, not a parallel format. (Table
    stream order differs — the flat builder resolves ids column-wise —
    so the comparison is decoded-op identity, not byte identity.)"""
    from fluidframework_tpu.server.tree_wire import (decode_records,
                                                     encode_leaf_records)
    n = 9
    parents = ["root" if i % 3 else f"n{i - 1}" for i in range(n)]
    parents[0] = "root"
    fields = [f"f{i % 2}" for i in range(n)]
    nids = [f"n{i}" for i in range(n)]
    values = [None if i % 4 == 3 else {"i": i} for i in range(n)]
    types = [None if i % 2 else "leaf" for i in range(n)]
    afters = [None if i % 3 != 1 else f"n{i - 1}" for i in range(n)]
    flat = encode_leaf_records(parents, fields, nids, values, types,
                               afters)
    general = encode_tree_batch(
        [{"op": "insert", "parent": p, "field": f, "after": a,
          "nodes": [{"id": i, "type": t, "value": v}]}
         for p, f, i, v, t, a in zip(parents, fields, nids, values,
                                     types, afters)])
    def decoded(b):
        return [_normalize(op) for op in decode_records(
            b["rec_op"], b["recs"], b["ids"], b["fields"], b["types"],
            b["values"])]

    assert decoded(flat) == decoded(general)
    assert (np.asarray(flat["recs"])[:, 0] == 9).all()  # INSERT_SOLO


def test_ingest_leaves_is_records_path():
    """Flat-via-records parity: ingest_leaves ≡ encode_leaf_records +
    ingest_records — same seqs, same trees, same durable log (the thin
    builder really did retire the duplicate pipeline)."""
    from fluidframework_tpu.server.tree_wire import encode_leaf_records
    eng_a, docs = _mk()
    eng_b, _ = _mk()
    for wave in range(3):
        parents = ["root"] * len(docs) if wave == 0 \
            else [f"{d}-L0" for d in docs]
        nids = [f"{d}-L{wave}" for d in docs]
        values = [{"w": wave}] * len(docs)
        types = ["leaf"] * len(docs)
        afters = [None if wave < 2 else f"{d}-L1" for d in docs]
        cs = [wave + 1] * len(docs)
        zeros = [0] * len(docs)
        res_a = eng_a.ingest_leaves(docs, [1] * len(docs), cs, zeros,
                                    parents, ["kids"] * len(docs), nids,
                                    values, types, afters)
        batch = encode_leaf_records(parents, ["kids"] * len(docs), nids,
                                    values, types, afters)
        res_b = eng_b.ingest_records(docs, [1] * len(docs), cs, zeros,
                                     batch)
        assert np.array_equal(np.asarray(res_a["seq"]),
                              np.asarray(res_b["seq"]))
        assert res_a["nacked"] == res_b["nacked"] == 0
    for d in docs:
        assert eng_a.to_dict(d) == eng_b.to_dict(d), d
    la = [(m.doc_id, m.seq, m.contents) for m in
          (m for d in docs for m in eng_a._doc_log_messages(d))]
    lb = [(m.doc_id, m.seq, m.contents) for m in
          (m for d in docs for m in eng_b._doc_log_messages(d))]
    assert la == lb


def test_wire_width_coding_u32_parity():
    """The id/value index lanes widen u16 → u32 past 64k table entries;
    a batch whose tables cross the boundary (padded with unused ids and
    values) must still be wire-eligible and merge identically to the
    unpadded ingest."""
    eng_a, docs = _mk()
    eng_b, _ = _mk()
    ops = [{"op": "insert", "parent": "root", "field": "kids",
            "after": None, "nodes": [{"id": f"{d}-n", "type": "t",
                                      "value": 5}]} for d in docs]
    batch = encode_tree_batch(ops)
    padded = dict(batch)
    padded["ids"] = list(batch["ids"]) + \
        [f"pad{i}" for i in range(0x10000)]
    padded["values"] = list(batch["values"]) + list(range(0x10000))
    assert eng_a._wire_eligible(padded)
    ones, cs, zeros = [1] * len(docs), [1] * len(docs), [0] * len(docs)
    res_a = eng_a.ingest_records(docs, ones, cs, zeros, padded)
    res_b = eng_b.ingest_records(docs, ones, cs, zeros, batch)
    assert res_a["nacked"] == res_b["nacked"] == 0
    assert np.array_equal(np.asarray(res_a["seq"]),
                          np.asarray(res_b["seq"]))
    for d in docs:
        assert eng_a.to_dict(d) == eng_b.to_dict(d), d


def test_pack_wire_records_width_parameters():
    """pack_wire_records' u16 and u32 packings carry identical indices —
    the width is a wire-size knob, not a semantic one — and prepack_wire
    picks the width from the table sizes."""
    from fluidframework_tpu.ops.tree_store import pack_wire_records
    ops = [{"op": "insert", "parent": "root", "field": "kids",
            "after": None, "nodes": [{"id": f"m{i}", "value": i}]}
           for i in range(6)]
    b = encode_tree_batch(ops)
    recs = np.asarray(b["recs"])
    rec_op = np.asarray(b["rec_op"])
    rows_r = np.arange(len(rec_op), dtype=np.int64)
    p16 = pack_wire_records(recs, rec_op, rows_r)
    p32 = pack_wire_records(recs, rec_op, rows_r,
                            id_t=np.uint32, val_t=np.uint32)
    k16, ids16, vals16, row16, pos16 = p16[:5]
    k32, ids32, vals32, row32, pos32 = p32[:5]
    assert ids16.dtype == np.uint16 and vals16.dtype == np.uint16
    assert ids32.dtype == np.uint32 and vals32.dtype == np.uint32
    assert np.array_equal(ids16.astype(np.uint32), ids32)
    assert np.array_equal(vals16.astype(np.uint32), vals32)
    assert np.array_equal(k16, k32) and np.array_equal(row16, row32)
    assert np.array_equal(pos16, pos32)


def test_nested_transaction_rejected():
    eng, docs = _mk()
    nested = {"op": "transaction", "edits": [
        {"op": "transaction", "edits": [
            {"op": "setValue", "id": "root", "value": 1}]}]}
    _, nack = eng.submit(docs[0], 1, 1, 0, nested)
    assert nack is not None
    with pytest.raises(ValueError, match="malformed"):
        eng.ingest_batch([docs[0]], [1], [1], [0], [nested])
