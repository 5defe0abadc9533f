"""The main path, once, end to end: sockets → ``ColumnarAlfred`` door → C++
sequencer → durable native log → device merge → ack → read → summary and
reload. ``chip_smoke.py`` runs :func:`run_door_smoke` at deployment size
on the chip; tier-1 runs it tiny on the CPU with the Pallas interpreter.

What it holds the system to, as far as one run can show: every op is
acked exactly once with a positive seq, per-doc seqs are gapless, no
error/nack frame arrives, the served text and properties of a seeded
sample of docs (plus every multi-writer doc) equal a replay of the acked
stream through the Python oracle in ``models/``, a reload from
full + incremental summary reproduces every doc's digest, and each Pallas
specialization the store can pick matches the XLA scan on the same
device. Every phase ends in a device→host read and nothing is caught: an
exception anywhere is a failed smoke.

The returned dict is a set of smoke OBSERVATIONS (counts, wall seconds
of one cold run, compile counts) — not benchmark metrics.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.protocol import (
    ColumnarWireKind, MessageType, SequencedDocumentMessage,
)
from ..models.merge_tree_client import SequenceClient
from ..native.build import TARGETS, ensure_built
from ..ops.merge_tree_kernel import StringState, string_state_digest
from ..ops.string_store import TensorStringStore, _columnar_merge_jit
from ..server.columnar_ingress import (
    _OP_DTYPE, ColumnarAlfred, ColumnarClient,
)
from ..server.native_oplog import NativePartitionedLog
from ..server.serving import StringServingEngine
from ..utils.telemetry import REGISTRY
from .synthetic import conflict_storm, typing_storm

_INS, _REM, _ANN = (int(ColumnarWireKind.INSERT),
                    int(ColumnarWireKind.REMOVE),
                    int(ColumnarWireKind.ANNOTATE))

#: the annotate table every rich frame carries (3 keys ≤ the store's 4
#: property planes; a None value deletes the key)
PROPS = [{"bold": True}, {"color": "red"}, {"color": "blue"},
         {"size": 12}, {"bold": None}]

#: (capacity, with_props) → the tile ``_pallas_choice`` is documented to
#: pick at 10,240 docs: the parity block compiles exactly these
DOCUMENTED_SHAPES = ((384, False, 128), (384, True, 64),
                     (512, False, 64), (512, True, 64))

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class _CompileWatch:
    """Sums JAX's own compile-time events and persistent-cache hit/miss
    events for the life of the smoke (dispatch threads report too)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.backend_compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name: str, secs: float, **_kw) -> None:
        if name in _COMPILE_EVENTS:
            with self._lock:
                self.seconds += secs
                self.backend_compiles += name == _COMPILE_EVENTS[2]

    def _event(self, name: str, **_kw) -> None:
        with self._lock:
            self.cache_hits += name == "/jax/compilation_cache/cache_hits"
            self.cache_misses += \
                name == "/jax/compilation_cache/cache_misses"

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._dur)
        jax.monitoring.unregister_event_listener(self._event)


def _cache_entries() -> Tuple[Optional[str], int]:
    d = jax.config.jax_compilation_cache_dir
    n = len(os.listdir(d)) if d and os.path.isdir(d) else 0
    return d, n


class _Writer:
    """One ``ColumnarClient`` and everything it needs to write its docs:
    per-row visible length / clientSeq / last seen seq for the docs it
    writes alone (generated vectorized), and an oracle replica per
    multi-writer doc it shares (positions valid in ITS view, ref behind
    seq whenever a co-writer's op was sequenced first)."""

    def __init__(self, idx: int, port: int, docs: List[str],
                 shared: List[str], timeout: float):
        self.idx = idx
        self.cl = ColumnarClient("127.0.0.1", port)
        self.cl.sock.settimeout(timeout)
        self.cl.join(docs + shared)
        self.solo_rows = np.asarray([self.cl.rows[d] for d in docs],
                                    np.int64)
        n = len(docs)
        self.length = np.zeros(n, np.int64)
        self.cseq = np.zeros(n, np.int64)
        self.ref = np.zeros(n, np.int64)
        self.replicas: Dict[int, SequenceClient] = {
            self.cl.rows[d]: SequenceClient(self.cl.client_id)
            for d in shared}
        #: every frame sent: (ops records, texts, props|None, seqs filled
        #: from acks) — the acked stream the oracle replays
        self.sent: List[tuple] = []
        self._pending: Dict[Tuple[int, int], Tuple[np.ndarray, int]] = {}

    # ---------------------------------------------------------- generate
    def _solo_ops(self, rng, texts: List[str], rich: bool) -> np.ndarray:
        n = len(self.solo_rows)
        tlen = np.asarray([len(t) for t in texts], np.int64)
        roll = rng.random(n)
        span = rng.integers(1, 5, n)
        can_cut = self.length >= span + 4
        kind = np.where(can_cut & (roll < 0.30), _REM, _INS)
        if rich:
            kind = np.where(can_cut & (roll >= 0.30) & (roll < 0.60),
                            _ANN, kind)
        ins = kind == _INS
        tidx = np.where(ins, rng.integers(0, len(texts), n),
                        rng.integers(0, len(PROPS), n))
        a0 = np.where(ins, rng.integers(0, self.length + 1),
                      rng.integers(0, np.maximum(self.length - span, 0)
                                   + 1))
        a1 = np.where(ins, 0, a0 + span)
        self.length += np.where(ins, tlen[np.where(ins, tidx, 0)],
                                np.where(kind == _REM, -span, 0))
        self.cseq += 1
        ops = np.zeros(n, _OP_DTYPE)
        ops["row"], ops["kind"] = self.solo_rows, kind
        ops["a0"], ops["a1"], ops["tidx"] = a0, a1, tidx
        ops["cseq"], ops["ref"] = self.cseq, self.ref
        return ops

    def _shared_ops(self, rng, texts: List[str], rich: bool,
                    wave: int) -> np.ndarray:
        """1–2 local edits per shared doc through its oracle replica (so
        the op is well-formed in this writer's own view)."""
        recs = []
        for row, rep in self.replicas.items():
            for k in range(int(rng.integers(1, 3))):
                n = rep.get_length()
                roll = rng.random()
                if n < 6 or roll < 0.5:
                    text = f"<{self.idx}.{wave}.{k}>"
                    op = rep.insert_text_local(int(rng.integers(0, n + 1)),
                                               text)
                    texts.append(text)
                    rec = (row, _INS, op["pos"], 0, len(texts) - 1)
                else:
                    start = int(rng.integers(0, n - 3))
                    end = start + int(rng.integers(1, 4))
                    if rich and roll < 0.75:
                        t = int(rng.integers(0, len(PROPS)))
                        rep.annotate_range_local(start, end, PROPS[t])
                        rec = (row, _ANN, start, end, t)
                    else:
                        rep.remove_range_local(start, end)
                        rec = (row, _REM, start, end, 0)
                recs.append(rec + (rep.client_seq, rep.last_processed_seq))
        return np.asarray([tuple(r) for r in recs], _OP_DTYPE) \
            if recs else np.zeros(0, _OP_DTYPE)

    def send_wave(self, seed: int, wave: int, rich: bool) -> int:
        rng = np.random.default_rng([seed, self.idx, wave])
        texts = ["".join(chr(97 + (self.idx + wave + i + j) % 26)
                         for j in range(1 + i % 6)) for i in range(16)]
        ops = np.concatenate([self._solo_ops(rng, texts, rich),
                              self._shared_ops(rng, texts, rich, wave)])
        seqs = np.zeros(len(ops), np.int64)
        for i, (r, c) in enumerate(zip(ops["row"].tolist(),
                                       ops["cseq"].tolist())):
            self._pending[r, c] = (seqs, i)
        self.sent.append((ops, texts, PROPS if rich else None, seqs))
        self.cl.send_ops(texts, ops, props=PROPS if rich else None)
        return len(ops)

    # ------------------------------------------------------------- acks
    def drain_acks(self) -> None:
        """Block until every op of the last wave is acked. Anything but
        an ``acks`` frame with positive seqs for ops still pending — an
        error frame, a throttle, a nack, a second ack — fails the smoke."""
        while self._pending:
            resp = self.cl.recv_json()
            if resp.get("t") != "acks":
                raise AssertionError(f"writer {self.idx}: {resp}")
            for (cs, sq), r in zip(resp["acks"], resp["rows"]):
                if sq <= 0:
                    raise AssertionError(
                        f"writer {self.idx}: nack {sq} row {r} cseq {cs}")
                seqs, i = self._pending.pop((r, cs))   # KeyError = dup ack
                seqs[i] = sq
        # a solo writer has seen its own ack (solo ops lead the frame)
        self.ref = self.sent[-1][3][:len(self.solo_rows)].copy()


def _messages(writers: Sequence[_Writer], doc_id: str, row: int
              ) -> List[SequencedDocumentMessage]:
    """The acked stream of one doc, rebuilt from what the writers sent and
    the seqs their acks carried — independent of the server's log."""
    out = []
    for w in writers:
        for ops, texts, props, seqs in w.sent:
            for i in np.flatnonzero(ops["row"] == row).tolist():
                o = ops[i]
                k, a0, a1, t = (int(o["kind"]), int(o["a0"]),
                                int(o["a1"]), int(o["tidx"]))
                if k == _INS:
                    c = {"mt": "insert", "kind": 0, "pos": a0,
                         "text": texts[t]}
                elif k == _REM:
                    c = {"mt": "remove", "start": a0, "end": a1}
                else:
                    c = {"mt": "annotate", "start": a0, "end": a1,
                         "props": props[t]}
                c["clientSeq"] = int(o["cseq"])
                out.append(SequencedDocumentMessage(
                    doc_id=doc_id, client_id=w.cl.client_id,
                    client_seq=int(o["cseq"]), ref_seq=int(o["ref"]),
                    seq=int(seqs[i]), min_seq=0, type=MessageType.OP,
                    contents=c))
    out.sort(key=lambda m: m.seq)
    return out


def _check_against_oracle(engine, writers, doc_id: str, rng) -> int:
    """Served text + properties of one doc == the oracle's replay of its
    acked stream. Returns the doc's visible length."""
    row = engine.doc_row(doc_id)
    oracle = SequenceClient(10 ** 6)        # a pure observer
    for m in _messages(writers, doc_id, row):
        oracle.apply_msg(m)
    text = engine.read_text(doc_id)
    assert text == oracle.get_text(), f"{doc_id}: served text != oracle"
    for pos in rng.integers(0, max(len(text), 1), 4).tolist():
        if pos < len(text):
            seg, _ = oracle.tree.get_containing_segment(pos)
            assert engine.get_properties(doc_id, pos) == dict(seg.props), \
                f"{doc_id}@{pos}: served properties != oracle"
    for w in writers:       # co-writers' replicas converged on it too
        rep = w.replicas.get(row)
        assert rep is None or rep.get_text() == text, \
            f"{doc_id}: writer {w.idx}'s replica diverged"
    return len(text)


def _check_gapless(writers: Sequence[_Writer], joins: np.ndarray) -> int:
    """Per doc, the acked seqs are exactly joins+1 … joins+n_ops."""
    rows = np.concatenate([s[0]["row"] for w in writers for s in w.sent])
    seqs = np.concatenate([s[3] for w in writers for s in w.sent])
    assert (seqs > 0).all(), "an op was never acked"
    order = np.lexsort((seqs, rows))
    rows, seqs = rows[order].astype(np.int64), seqs[order]
    first = np.ones(len(rows), bool)
    first[1:] = rows[1:] != rows[:-1]
    assert (seqs[first] == joins[rows[first]] + 1).all(), \
        "a doc's first op seq does not follow its joins"
    assert (np.diff(seqs)[~first[1:]] == 1).all(), \
        "gap or duplicate in a doc's acked seqs"
    return len(seqs)


def pallas_parity(n_docs: int, shapes, ops_per_doc: int = 64,
                  interpret: bool = False) -> List[dict]:
    """Compile, run once and compare with the XLA scan — on whatever
    device JAX runs on — every Pallas specialization in ``shapes``
    ((capacity, with_props, expected tile) triples), fused zamboni off
    and on, through the store's own merge program."""
    out = []
    order = ("kind", "a0", "a1", "a2", "seq", "client", "ref_seq")
    for cap, with_props, want_tile in shapes:
        store = TensorStringStore(n_docs, cap)
        store._has_props = with_props
        store.pallas = "interpret" if interpret else "auto"
        use, tile, interp = store._pallas_choice()
        assert (use, tile, interp) == (True, want_tile, interpret), \
            f"S={cap} props={with_props}: _pallas_choice gave " \
            f"{(use, tile, interp)}, documented tile {want_tile}"
        gen = conflict_storm if with_props else typing_storm
        planes, next_seq = gen(n_docs, ops_per_doc, seed=cap)
        planes = tuple(jnp.asarray(planes[k]) for k in order)
        ms = jnp.full((n_docs,), next_seq // 2, jnp.int32)
        for fuse in (False, True):
            got = [_columnar_merge_jit(
                StringState.create(n_docs, cap), planes, ms,
                use_pallas=p, tile=tile, interpret=interpret,
                with_props=with_props, fuse_compact=fuse)
                for p in (True, False)]
            # slots at or beyond count are semantically ignored (the two
            # zambonis leave different debris there)
            props = [np.where((np.arange(cap)[None, :]
                               < np.asarray(s.count)[:, None])[..., None],
                              np.asarray(s.prop_val), 0) for s in got]
            same = np.array_equal(*props) and all(
                np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in ((string_state_digest(got[0]),
                              string_state_digest(got[1])),
                             (got[0].count, got[1].count),
                             (got[0].overflow, got[1].overflow)))
            assert same, f"Pallas != XLA scan at S={cap} T={tile} " \
                         f"props={with_props} fused={fuse}"
            out.append({"capacity": cap, "tile": tile,
                        "props": with_props, "fused_zamboni": fuse,
                        "parity": True})
    return out


def run_door_smoke(log_dir: str, *, n_docs: int = 10240,
                   capacity: int = 512, n_clients: int = 8,
                   waves: Tuple[int, int, int] = (14, 6, 10),
                   n_shared: int = 16, n_sampled: int = 64, seed: int = 0,
                   pallas: str = "auto", mesh=None,
                   parity_shapes=DOCUMENTED_SHAPES, parity_ops: int = 64,
                   ack_timeout_s: float = 600.0) -> dict:
    """Drive the string deployment once through the door and check it.

    ``waves`` = (plain insert/remove waves in ``B`` frames, annotate-
    bearing waves in ``R`` frames, further plain waves). ``pallas`` is
    the store's dispatch policy: "auto" must resolve to the compiled
    kernel (the chip run), "interpret" is the tier-1 CPU form."""
    assert n_docs % n_clients == 0
    interpret = pallas == "interpret"
    watch = _CompileWatch()
    compiles0 = REGISTRY.counters.get("jax_compiles", 0)
    cache_dir, cache_before = _cache_entries()
    t_start = time.perf_counter()

    # ---- set-up: build, serve, connect, join --------------------------
    for target in TARGETS:      # from the committed .cpp; raises with the
        ensure_built(target)    # compiler's stderr when it cannot
    log = NativePartitionedLog(log_dir, 8)
    engine = StringServingEngine(n_docs=n_docs, capacity=capacity,
                                 sequencer="native", log=log, mesh=mesh)
    engine.store.pallas = pallas
    door = ColumnarAlfred(engine, decode="native").start_in_thread()
    assert door.pipeline_depth > 0
    assert type(engine.deli).__name__ == "NativeDeliAdapter"
    assert isinstance(engine.log, NativePartitionedLog)
    assert door.drain_stats()["tier"] == "native"

    per = n_docs // n_clients
    docs = [f"doc-{i}" for i in range(n_docs)]
    shared_idx = [j * (n_docs // n_shared) for j in range(n_shared)] \
        if n_shared else []
    co_writers = collections.defaultdict(list)   # writer → shared docs
    joins = np.zeros(n_docs, np.int64)
    for j, i in enumerate(shared_idx):
        for k in range(2 + j % 2):               # 2 or 3 writers
            co_writers[(i // per + k) % n_clients].append(docs[i])
    shared_set = {docs[i] for i in shared_idx}
    writers = [
        _Writer(c, door.port,
                [d for d in docs[c * per:(c + 1) * per]
                 if d not in shared_set],
                co_writers[c], ack_timeout_s)
        for c in range(n_clients)]
    for w in writers:
        joins[w.solo_rows] += 1
        for row in w.replicas:
            joins[row] += 1
    assert int(np.asarray(engine.store.state.count).sum()) == 0
    t_setup = time.perf_counter()

    # ---- serve --------------------------------------------------------
    wave_s: List[float] = []
    wave_compiled: List[bool] = []
    ops_sent = 0
    choice = {}

    def serve(n_waves: int, rich: bool) -> None:
        nonlocal ops_sent
        for _ in range(n_waves):
            t0, c0 = time.perf_counter(), watch.backend_compiles
            wave = len(wave_s)
            for w in writers:
                ops_sent += w.send_wave(seed, wave, rich)
            for w in writers:
                w.drain_acks()
            # what the other writers of a shared doc had sequenced ahead
            # of us reaches our replica only now: this wave's ops crossed
            for i in shared_idx:
                msgs = _messages(writers, docs[i], engine.doc_row(docs[i]))
                for w in writers:
                    rep = w.replicas.get(engine.doc_row(docs[i]))
                    for m in msgs if rep is not None else ():
                        if m.seq > rep.last_processed_seq:
                            rep.apply_msg(m)
            wave_s.append(time.perf_counter() - t0)
            wave_compiled.append(watch.backend_compiles > c0)
        door._executor.drain(ack_timeout_s)    # acked ⇒ logged; now idle

    def pallas_choice(mode: str) -> None:
        use, tile, interp = engine.store._pallas_choice()
        assert (use, interp) == (True, interpret), \
            f"{mode}: the door is not on the " \
            f"{'interpreted' if interpret else 'compiled'} Pallas kernel:" \
            f" _pallas_choice() = {(use, tile, interp)}"
        choice[mode] = {"tile": tile, "interpret": interp}

    pallas_choice("no_props")
    serve(waves[0], rich=False)
    assert not engine.store._has_props
    t0 = time.perf_counter()
    full_summary = engine.summarize()      # the chain's base, mid-run
    t_full_summary = time.perf_counter() - t0
    serve(waves[1], rich=True)
    assert engine.store._has_props
    pallas_choice("props")
    serve(waves[2], rich=False)
    assert not engine.store.overflowed().any(), "a doc overflowed"
    t_served = time.perf_counter()

    # ---- guarantees + queries -----------------------------------------
    assert _check_gapless(writers, joins) == ops_sent == door.ops_ingested
    rng = np.random.default_rng([seed, 99])
    sampled = sorted({docs[i] for i in shared_idx}
                     | {docs[i] for i in rng.choice(
                         n_docs, min(n_sampled, n_docs), replace=False)})
    chars = sum(_check_against_oracle(engine, writers, d, rng)
                for d in sampled)
    t_queried = time.perf_counter()

    # ---- recovery: full + incremental summary, reload, same digests ----
    summary = engine.summarize(incremental=True)
    assert (full_summary["kind"], summary["kind"]) == ("full", "delta")
    for w in writers:
        w.cl.close()
    door.stop()
    revived = StringServingEngine.load(summary, log, mesh=mesh,
                                       sequencer="native")
    revived.store.pallas = pallas
    assert np.array_equal(revived.store.digests(), engine.store.digests()), \
        "digests differ after summary + log reload"
    assert revived.read_text(sampled[0]) == engine.read_text(sampled[0])
    t_recovered = time.perf_counter()

    # ---- the kernel: every specialization vs the XLA scan -------------
    parity = pallas_parity(n_docs, parity_shapes, parity_ops, interpret)
    t_parity = time.perf_counter()

    sharding = engine.store.state.seq.sharding
    mem = [d.memory_stats() or {} for d in sorted(sharding.device_set,
                                                  key=lambda d: d.id)]
    unpack_keys = engine.store.unpack_variants
    log.close()
    watch.close()
    steady = [s for s, c in zip(wave_s, wave_compiled) if not c]
    return {
        "sizes": {"n_docs": n_docs, "capacity": capacity,
                  "clients": n_clients, "waves": list(waves),
                  "shared_docs": len(shared_idx),
                  "state_plane_bytes": int(sum(
                      x.nbytes for x in jax.tree.leaves(
                          engine.store.state)))},
        "native": {"sequencer": type(engine.deli).__name__,
                   "log": type(log).__name__,
                   "decode": door.drain_stats()["tier"]},
        "ops_acked": ops_sent,
        "windows": door.windows_flushed,
        "error_or_nack_frames": 0,
        "oracle_parity": {"docs": len(sampled), "shared": len(shared_idx),
                          "chars": chars},
        "reload_digest_equal": True,
        "pallas": choice,
        "pallas_parity": parity,
        "sharding_devices": len(sharding.device_set),
        "wall_s": {"setup": round(t_setup - t_start, 3),
                   "serve": round(t_served - t_setup - t_full_summary, 3),
                   "mid_run_full_summary": round(t_full_summary, 3),
                   "serve_waves_that_compiled": int(sum(wave_compiled)),
                   "serve_steady_waves": len(steady),
                   "serve_steady": round(sum(steady), 3),
                   "queries": round(t_queried - t_served, 3),
                   "recovery": round(t_recovered - t_queried, 3),
                   "kernel_parity": round(t_parity - t_recovered, 3),
                   "total": round(t_parity - t_start, 3)},
        "compile": {
            "jax_compile_event_s": round(watch.seconds, 3),
            "backend_compiles": watch.backend_compiles,
            "store_jax_compiles": int(
                REGISTRY.counters.get("jax_compiles", 0) - compiles0),
            "unpack_programs": len(unpack_keys),
            "unpack_distinct_R": len({k[0] for k in unpack_keys}),
            "unpack_R_heights": sorted({k[0] for k in unpack_keys}),
            "cache_dir": cache_dir,
            "cache_entries_before": cache_before,
            "cache_entries_after": _cache_entries()[1],
            "persistent_cache_hits": watch.cache_hits,
            "persistent_cache_misses": watch.cache_misses},
        "device_memory": [
            {k: m.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                   "bytes_limit")} for m in mem],
    }
