"""The dedup ledger's two write paths (ISSUE 37): the columnar door's
``record_planes`` (a window's acks by row, in arrays) held to the per-op
``record`` path as the oracle, on seeded random streams.

Each case drives one ledger through ``record_planes`` a window at a time
and an oracle through ``record`` an op at a time, with the same ops in
the same order, and then asks both every read: ``lookup`` of every
client seq a key has used (and one past it), ``last``, ``snapshot``
(whole and by docs), ``mem_stats``' keys and entries, and
``per_doc_entries``.
"""

import random

import numpy as np
import pytest

from fluidframework_tpu.server.partitioned import PartitionedStringServing
from fluidframework_tpu.server.serving import DedupLedger


class _Stream:
    """Windows of acks as the door fans them: rows of up to ``depth`` ops
    in sequence order, per (row, client) client seqs ascending."""

    def __init__(self, seed, n_rows=12, writers=(1,), depth=4,
                 window_rows=8, gaps=False, nacks=0.0, resubmits=0.0,
                 reseq=0.0):
        self.rng = random.Random(seed)
        self.n_rows, self.writers, self.depth = n_rows, writers, depth
        self.window_rows, self.gaps = window_rows, gaps
        self.nacks, self.resubmits, self.reseq = nacks, resubmits, reseq
        self.clients = {r: [100 + r * 4 + i for i in range(
            self.rng.choice(writers))] for r in range(n_rows)}
        self.hi = {}            # (row, client) -> highest cseq acked
        self.acked = {}         # (row, client) -> [(cseq, seq)]
        self.seq = {r: 0 for r in range(n_rows)}

    def window(self):
        rows = self.rng.sample(range(self.n_rows),
                               min(self.window_rows, self.n_rows))
        out = []
        for r in sorted(rows):
            for _ in range(self.rng.randint(1, self.depth)):
                c = self.rng.choice(self.clients[r])
                key = (r, c)
                old = self.acked.get(key)
                u = self.rng.random()
                if old and u < self.resubmits:
                    # a resubmit re-acked with its original seq
                    cs, sq = self.rng.choice(old[-6:])
                elif old and u < self.resubmits + self.reseq:
                    # any earlier cseq again, under a new seq
                    cs = self.rng.choice(old)[0]
                    self.seq[r] += 1
                    sq = self.seq[r]
                else:
                    cs = self.hi.get(key, 0) + (
                        self.rng.randint(1, 3) if self.gaps else 1)
                    if self.rng.random() < self.nacks:
                        out.append((r, c, cs, -self.rng.randint(0, 3)))
                        continue
                    self.hi[key] = cs
                    self.seq[r] += 1
                    sq = self.seq[r]
                    self.acked.setdefault(key, []).append((cs, sq))
                out.append((r, c, cs, sq))
        return out


def _feed(planes, oracle, ops, row_doc):
    if ops:
        r, c, cs, sq = (np.array(x, np.int64) for x in zip(*ops))
        planes.record_planes(r, c, cs, sq, row_doc)
    for r, c, cs, sq in ops:
        if sq > 0:
            oracle.record(row_doc[r], c, cs, sq)


def _same_answers(planes, oracle, keys):
    for doc, c in keys:
        assert planes.last(doc, c) == oracle.last(doc, c), (doc, c)
        for cs in range(0, oracle.last(doc, c) + 2):
            assert planes.lookup(doc, c, cs) == oracle.lookup(doc, c, cs), \
                (doc, c, cs)
    assert planes.snapshot() == oracle.snapshot()
    docs = {d for d, _ in keys}
    some = set(sorted(docs)[::2])
    assert planes.snapshot(docs=some) == oracle.snapshot(docs=some)
    a, b = planes.mem_stats(), oracle.mem_stats()
    assert (a["keys"], a["entries"]) == (b["keys"], b["entries"])
    assert planes.per_doc_entries() == oracle.per_doc_entries()


def _keys(stream, row_doc):
    return [(row_doc[r], c) for r in range(stream.n_rows)
            for c in stream.clients[r]]


CASES = {
    "one_writer": dict(),
    "multi_writer": dict(writers=(2, 3)),
    "nacks": dict(writers=(1, 2), nacks=0.3),
    "resubmits": dict(writers=(1, 2), resubmits=0.25),
    "gaps_and_new_seqs": dict(writers=(1, 2), gaps=True, reseq=0.15,
                              resubmits=0.1),
    "past_the_window": dict(n_rows=4, window_rows=4, depth=4,
                            writers=(1, 2), resubmits=0.05, reseq=0.1),
}


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
@pytest.mark.parametrize("case", sorted(CASES))
def test_planes_match_the_per_op_oracle(case, seed):
    window = 16 if case == "past_the_window" else 512
    s = _Stream(seed, **CASES[case])
    row_doc = [f"doc-{r}" for r in range(s.n_rows)]
    planes, oracle = DedupLedger(window), DedupLedger(window)
    for w in range(60):
        _feed(planes, oracle, s.window(), row_doc)
        if w % 7 == 0:
            _same_answers(planes, oracle, _keys(s, row_doc))
    _same_answers(planes, oracle, _keys(s, row_doc))
    if case == "past_the_window":
        widths = [len(ent["acked"]) for clients in
                  planes.snapshot().values() for ent in clients.values()]
        assert max(widths) == window
        assert planes.mem_stats()["entries"] <= window * len(
            _keys(s, row_doc))


def test_a_resubmit_finds_its_original_while_the_door_records():
    """The sequencing worker looks a DUPLICATE up by document; the ack
    fan recorded the original by row, a window earlier."""
    led = DedupLedger()
    led.record_planes([0, 0, 1], [7, 7, 8], [1, 2, 1], [10, 11, 12],
                      ["a", "b"])
    assert led.lookup("a", 7, 2) == 11 and led.lookup("b", 8, 1) == 12
    assert led.lookup("a", 7, 3) is None and led.lookup("a", 8, 1) is None
    # the dup-ack re-records the original: nothing moves
    led.record_planes([0, 0], [7, 7], [2, 3], [11, 13], ["a", "b"])
    assert led.snapshot()["a"]["7"] == {"last": 3,
                                        "acked": [[1, 10], [2, 11],
                                                  [3, 13]]}


def test_a_reused_row_keeps_each_documents_acks():
    """A row released (a document graduated off the flat tier) and then
    given to another document: the first document's acks stay under its
    name, the second's start empty."""
    s = _Stream(5, n_rows=6, writers=(1, 2))
    row_doc = [f"first-{r}" for r in range(s.n_rows)]
    planes, oracle = DedupLedger(), DedupLedger()
    for _ in range(10):
        _feed(planes, oracle, s.window(), row_doc)
    before = _keys(s, row_doc)
    planes.release_row(2)
    row_doc[2] = "second-2"
    s.hi = {k: v for k, v in s.hi.items() if k[0] != 2}
    s.acked = {k: v for k, v in s.acked.items() if k[0] != 2}
    for _ in range(10):
        _feed(planes, oracle, s.window(), row_doc)
    _same_answers(planes, oracle, before + _keys(s, row_doc))


def test_both_paths_on_one_key_and_the_summary_round_trip():
    """Per-op records (log-tail replay, submit) and window records on the
    same keys, and a ledger rebuilt from ``snapshot`` by ``load`` and a
    delta ``merge`` answering as the live one."""
    s = _Stream(9, n_rows=8, writers=(1, 2), resubmits=0.1)
    row_doc = [f"mix-{r}" for r in range(s.n_rows)]
    planes, oracle = DedupLedger(), DedupLedger()
    for w in range(40):
        ops = s.window()
        if w % 3 == 0:      # this window through the per-op path
            for r, c, cs, sq in ops:
                if sq > 0:
                    planes.record(row_doc[r], c, cs, sq)
                    oracle.record(row_doc[r], c, cs, sq)
        else:
            _feed(planes, oracle, ops, row_doc)
        if w == 20:
            base = planes.snapshot()
            base_oracle = oracle.snapshot()
            assert base == base_oracle
            touched = set()
        elif w > 20:
            touched |= {row_doc[r] for r, *_ in ops}
    keys = _keys(s, row_doc)
    _same_answers(planes, oracle, keys)
    # base summary + the changed docs' slice, as _restore_base resolves it
    rebuilt = DedupLedger.load(base)
    rebuilt.merge(planes.snapshot(docs=touched))
    _same_answers(rebuilt, oracle, keys)
    # and the window path goes on recording into the rebuilt ledger
    ops = s.window()
    _feed(rebuilt, oracle, ops, row_doc)
    _same_answers(rebuilt, oracle, keys)
    # merge over keys the array form holds
    planes.merge(oracle.snapshot(docs=touched))
    _same_answers(planes, oracle, keys)


def test_mem_stats_describes_the_arrays():
    led = DedupLedger()
    empty = led.mem_stats()["bytes"]
    led.record_planes(np.arange(100), np.full(100, 3), np.ones(100),
                      np.arange(1, 101), [f"m{r}" for r in range(100)])
    ms = led.mem_stats()
    assert ms["keys"] == 100 and ms["entries"] == 100
    # two int64 rings of 512 slots for each of 128 key columns, and more
    assert ms["array_bytes"] >= 2 * 8 * 512 * 128
    assert ms["bytes"] - empty >= ms["array_bytes"]


def test_partitioned_forwarding_matches_the_oracle():
    """``PartitionedStringServing.note_acked_planes`` splits a window of
    global rows by partition; each partition's ledger answers as the
    per-op oracle fed the same acks."""
    svc = PartitionedStringServing(n_partitions=2, docs_per_partition=8)
    docs = [f"pf-{i}" for i in range(10)]
    rows = {d: svc.doc_row(d) for d in docs}
    s = _Stream(17, n_rows=len(docs), writers=(1, 2), resubmits=0.1,
                nacks=0.1)
    row_doc = docs
    oracle = {p: DedupLedger() for p in range(2)}
    for _ in range(25):
        ops = s.window()
        if not ops:
            continue
        r, c, cs, sq = (np.array(x, np.int64) for x in zip(*ops))
        grows = np.array([rows[row_doc[i]] for i in r])
        svc.note_acked_planes(grows, c, cs, sq)
        for i, cl, cseq, seq in ops:
            if seq > 0:
                d = row_doc[i]
                oracle[svc.partition_of_doc(d)].record(d, cl, cseq, seq)
    for p, eng in enumerate(svc.engines):
        keys = [(d, c) for i, d in enumerate(docs)
                for c in s.clients[i] if svc.partition_of_doc(d) == p]
        _same_answers(eng._dedup, oracle[p], keys)
        for d, c in keys:
            assert svc.last_client_seq(d, c) == oracle[p].last(d, c)


def test_threads_recording_and_looking_up_lose_no_update():
    """The ack fan records while the sequencing worker looks DUPLICATEs
    up and the census reads: with the switch interval shortened, every
    thread's windows land (each thread owns its rows, so the serial
    oracle is order-free) and no reader sees a torn ring."""
    import os
    import sys
    import threading

    n_threads = (os.cpu_count() or 2) + 2
    streams = [_Stream(100 + t, n_rows=6, writers=(1, 2), resubmits=0.1)
               for t in range(n_threads)]
    row_doc = [f"st-{r}" for r in range(6 * n_threads)]
    windows = [[[(r + 6 * t, c + 1000 * t, cs, sq) for r, c, cs, sq in
                 s.window()] for _ in range(30)]
               for t, s in enumerate(streams)]
    led, oracle = DedupLedger(), DedupLedger()
    for per_thread in windows:
        for ops in per_thread:
            for r, c, cs, sq in ops:
                if sq > 0:
                    oracle.record(row_doc[r], c, cs, sq)
    stop = threading.Event()
    errors = []

    def writer(per_thread):
        try:
            for ops in per_thread:
                r, c, cs, sq = (np.array(x, np.int64) for x in zip(*ops))
                led.record_planes(r, c, cs, sq, row_doc)
        except Exception as e:      # surfaced by the assert below
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                ms = led.mem_stats()
                assert ms["entries"] <= oracle.mem_stats()["entries"]
                # thread 0's row 0 and thread 1's row 1, first writer
                for doc, c in ((row_doc[0], 100), (row_doc[7], 1104)):
                    seq = led.lookup(doc, c, 1)
                    assert seq is None or seq == oracle.lookup(doc, c, 1)
        except Exception as e:
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=writer, args=(w,))
                   for w in windows]
        readers = [threading.Thread(target=reader) for _ in range(2)]
        for t in threads + readers:
            t.start()
        for t in threads:
            t.join(timeout=60)
        stop.set()
        for t in readers:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads + readers)
    assert not errors, errors
    assert led.snapshot() == oracle.snapshot()
    assert led.mem_stats()["entries"] == oracle.mem_stats()["entries"]
