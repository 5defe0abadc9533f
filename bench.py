"""Headline benchmark: SharedString ops/sec merged across a 10k-doc batch.

BASELINE.md config #4 (Deli replay across many docs, the north-star metric):
a synthetic multi-doc typing storm is sequenced round-robin and merged by the
batched merge-tree kernel on the real chip, with zamboni compaction between
batches. Prints ONE JSON line; vs_baseline is against the 1M ops/sec target
(no published reference numbers exist — BASELINE.md).

Measurement honesty: dispatch is asynchronous, so timed sections end with a
device→host read of the per-doc overflow flags — the same read a real
sequencer ack path would do, and one that surfaces device faults. Latency
metric: ``apply_window_worst_ms`` is the WORST of 8 individually-synced
64-op-scan dispatches divided by the 64 sequential windows each dispatch
applies — an upper bound on per-window device apply latency, and therefore
on its p99. It is NOT the latency of dispatching one 1-op batch from this
host.

``run()`` refuses to start unless JAX's first device is a TPU. The workload
runs in a child process so that the parent never touches JAX (a chip belongs
to one process); the parent makes ONE attempt, re-prints the child's final
JSON line, and passes the child's exit code and stderr tail through.

``--phases "serving broadcast,ack latency"`` re-runs a subset of phases
(plus their recorded dependencies) without the full multi-hour sweep;
skipped phases keep zero/skipped defaults in the record, the record's
``phases_run``/``phases_skipped`` say which ran, and the perf sentinel
only judges full sweeps. Every phase boundary also takes a capacity
census (ISSUE 19): per-phase ``census_ms`` + resident/device bytes ride
in ``phase_capacity``.
"""

import json
import subprocess
import sys
import time


#: every phase marker in run(), in execution order. --phases selects a
#: comma-separated subset; _PHASE_DEPS pulls in what a phase needs from
#: earlier ones (corpora, engines) so any single phase can re-run alone
#: without the full 2-3h sweep. The scorecard phase always runs.
ALL_PHASES = (
    "throughput", "conflict", "serving broadcast", "serving rich",
    "serving durable", "serving tree", "tree kernel", "serving intervals",
    "matrix serving", "columnar ingress", "partition scaling",
    "read_fanout",
    "small-window ack", "ack latency", "apply-window latency",
    "reconnect_storm", "overload_storm", "durability",
)

#: phase → phases it reads state from (engines/corpora defined there)
_PHASE_DEPS = {
    "serving rich": ("serving broadcast",),
    "serving durable": ("serving broadcast",),
    "ack latency": ("serving broadcast", "serving rich"),
    "tree kernel": ("serving tree",),
}


def select_phases(spec):
    """``--phases`` spec → the closed set of phases to run (requested +
    transitive deps). ``None``/empty → all phases."""
    if not spec:
        return set(ALL_PHASES)
    want = [p.strip() for p in spec.split(",") if p.strip()]
    unknown = sorted(set(want) - set(ALL_PHASES))
    if unknown:
        raise SystemExit(
            f"unknown phases {unknown}; known: {', '.join(ALL_PHASES)}")
    selected = set(want)
    frontier = list(selected)
    while frontier:
        for dep in _PHASE_DEPS.get(frontier.pop(), ()):
            if dep not in selected:
                selected.add(dep)
                frontier.append(dep)
    return selected


def run(phases=None):
    import numpy as np
    import jax
    import jax.numpy as jnp

    _run_t0 = time.perf_counter()

    _dev = jax.devices()[0]
    if _dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the chip: jax.devices()[0] is "
            f"{_dev.platform!r} ({_dev.device_kind}), not a TPU")

    _selected = select_phases(phases)

    def _want(name):
        return name in _selected

    # health plane (ISSUE 4): a caller-ticked time-series over the process
    # registry, sampled at every phase boundary, judged by the standing
    # SLOs; the scorecard + perf-sentinel verdict ride in the bench record.
    # Guarded throughout — the health plane must never kill a bench run.
    from fluidframework_tpu.utils import slo as _slo
    from fluidframework_tpu.utils import timeseries as _timeseries
    from fluidframework_tpu.utils.telemetry import REGISTRY as _registry
    _health = _timeseries.TimeSeriesStore(registry=_registry)
    _slo_engine = _slo.SLOEngine(_health, specs=_slo.default_slos(),
                                 registry=_registry)

    # capacity plane (ISSUE 19): one full census per phase boundary —
    # census_ms + resident-doc/device bytes per phase land in the record;
    # entering phase N+1 closes phase N (its peak = max of entry/exit).
    from fluidframework_tpu.utils import capacity as _capacity
    _phase_capacity: dict = {}
    _phase_order: list = []

    def _phase(name):
        # stderr progress marks: the driver keeps stdout to the one JSON
        # line, but when an attempt times out the stderr tail says WHERE
        sys.stderr.write(
            f"[bench +{time.perf_counter() - _run_t0:7.1f}s] {name}\n")
        sys.stderr.flush()
        try:
            _health.tick()
            _slo_engine.check()
        except Exception as e:   # noqa: BLE001 — observability only
            sys.stderr.write(f"[bench] health tick failed: {e!r}\n")
        try:
            _c = _capacity.LEDGER.census(top_k=4)
            snap = {"census_ms": round(_c["census_ms"], 2),
                    "doc_resident_bytes": _c["host"]["total_bytes"],
                    "device_buffer_bytes": _c["device"]["total_bytes"]}
            if _phase_order:
                prev = _phase_capacity[_phase_order[-1]]
                prev["doc_resident_bytes_peak"] = max(
                    prev["doc_resident_bytes"],
                    snap["doc_resident_bytes"])
            _phase_order.append(name)
            _phase_capacity[name] = snap
        except Exception as e:   # noqa: BLE001 — observability only
            sys.stderr.write(f"[bench] capacity census failed: {e!r}\n")

    from fluidframework_tpu.ops.merge_tree_kernel import (
        StringState, apply_string_batch,
    )
    from fluidframework_tpu.testing.synthetic import typing_storm
    # shared across several gated phases (broadcast, durable, intervals,
    # small-window ack, ack latency): hoisted so a phase subset that
    # skips "serving broadcast" still resolves them
    from fluidframework_tpu.server.ingest_pipeline import (
        PipelinedIngestExecutor,
    )
    from fluidframework_tpu.server.serving import StringServingEngine

    n_docs = 10240
    capacity = 384
    ops_per_batch = 64
    n_batches = 4        # kernel-phase corpus (chained seq/ref planes)
    n_serve_batches = 5  # serving corpus: 4 measured after the warmup batch
    serve_capacity = 512  # the 5-batch serving corpus peaks past 384 slots;
    n_suites = 4          # the Pallas tile auto-halves to fit VMEM at S=512
    n_ops = n_docs * ops_per_batch * n_batches * n_suites
    order = ("kind", "a0", "a1", "a2", "seq", "client", "ref_seq")

    batches = []
    seq = 1
    for b in range(n_batches):
        planes, seq = typing_storm(n_docs, ops_per_batch, seed=b,
                                   start_seq=seq)
        batches.append(tuple(jnp.asarray(planes[k]) for k in order))

    # no-props mode: the typing corpus carries no annotates, so the store
    # runs the annotate-free kernel variant (the mode a production store is
    # in until its first annotate; see TensorStringStore._has_props).
    # The Pallas VMEM-resident kernel applies the whole 64-op batch with
    # one HBM round-trip of the state.
    import functools
    from fluidframework_tpu.ops.pallas_string_kernel import (
        apply_string_batch_pallas,
    )
    # fused apply+zamboni: ONE dispatch per batch, planes stay in VMEM
    apply_fn = jax.jit(apply_string_batch_pallas, donate_argnums=0)
    step_fn = apply_fn

    # warmup / compile on a throwaway state (BOTH variants: the fused
    # apply+compact used in the throughput loop and the plain apply used in
    # the latency phase — compiling inside a timed section would be counted)
    state = StringState.create(n_docs, capacity)
    state = apply_fn(state, *batches[0])
    state = step_fn(state, *batches[1],
                    min_seq=jnp.zeros((n_docs,), jnp.int32))
    _ = np.asarray(state.overflow)  # real sync (see module docstring)

    # on-device digest parity: the Mosaic-compiled kernel must produce the
    # same merged state as the XLA scan ON THE REAL CHIP (the CPU tests only
    # cover the Pallas interpreter; VERDICT r1 weak #2). Full-plane check,
    # not just the digest.
    from fluidframework_tpu.ops.merge_tree_kernel import (
        string_state_digest,
    )
    xla_fn = jax.jit(functools.partial(apply_string_batch,
                                       with_props=False))
    s_x = xla_fn(StringState.create(n_docs, capacity), *batches[0])
    s_p = apply_fn(StringState.create(n_docs, capacity), *batches[0])
    digest_parity = bool(np.array_equal(
        np.asarray(string_state_digest(s_x)),
        np.asarray(string_state_digest(s_p))))
    for plane in ("seq", "client", "removed_seq", "removers", "length",
                  "handle_op", "handle_off", "count", "overflow"):
        digest_parity &= bool(np.array_equal(
            np.asarray(getattr(s_x, plane)),
            np.asarray(getattr(s_p, plane))))
    assert digest_parity, "Pallas/XLA divergence on device"
    del s_x, s_p

    import os as _os
    load_start = _os.getloadavg()[0]

    # defaults for every record field a skipped phase would have filled:
    # a --phases subset still emits the full record shape (zeros/None/
    # skipped markers), so downstream readers never KeyError
    ops_per_sec = 0.0
    headline_trials = []
    headline_band = {}
    conflict_ops_per_sec = 0.0
    conflict_parity = None
    engine = rich_engine = tree_eng = None
    serving_trials, serving_pipe_stats = [], None
    serving_ops_per_sec = serving_ops_per_sec_median = 0.0
    serving_read_ms, read_rtts = 0.0, None
    rich_trials, rich_pipe_stats = [], None
    rich_ops_per_sec = rich_ops_per_sec_median = 0.0
    durable_ops_per_sec = durable_ops_per_sec_median = None
    durable_trials = []
    tree_trials, tree_pipe_stats = [], None
    tree_ops_per_sec = tree_ops_per_sec_median = 0.0
    tree_flat_ops_per_sec, leaf_trials = 0.0, []
    tree_kernel_ops_per_sec, tree_kernel_trials = 0.0, []
    interval_ops_per_sec, iv_seg_waves, interval_wire = 0.0, [], None
    n_iv_docs = iv_ow = iv_waves = 0
    matrix_serving_ops_per_sec, matrix_trials = 0.0, [0.0]
    columnar_ingress_ops_per_sec = 0.0
    ingress_trials, ingress_stats, ingress_windows = [0.0], None, 0
    ingress_drain = {"decode_p50_ms": None, "bytes_per_pass_p50": None,
                     "passes": 0, "tier": None}
    ops_plane = None
    partition_scaling = {"skipped": True}
    partition_columnar_ops_per_sec = None
    read_fanout = {"skipped": True}
    read_delivery_ops_per_sec = None
    small_window_ack = {}
    ack_p50_ms = ack_p99_ms = 0.0
    ack_retries = 0
    worst_ms = apply_window_p50_ms = 0.0
    apply_window_retries, apply_window_stalled = 0, False
    reconnect_storm = {"skipped": True}
    overload_storm = {"skipped": True}
    durability = {"skipped": True}

    if _want("throughput"):
        _phase("throughput")
        # --- throughput phase: 64-op batches, compact per batch -----------------
        # Dispatches are pipelined (as a production sequencer host would); each
        # suite's end sync covers its batches' device work. Every suite is an
        # independent trial: the per-suite rates + variance band make cross-
        # round drift (7.98M -> 7.28M between r4 and r5, unremarked) visible
        # inside a single record instead of only between records.
        headline_trials = []
        t0 = time.perf_counter()
        for _suite in range(n_suites):
            ts = time.perf_counter()
            state = StringState.create(n_docs, capacity)
            done_seq = 0
            for batch in batches:
                done_seq += n_docs * ops_per_batch
                ms = jnp.full((n_docs,), done_seq, jnp.int32)
                state = step_fn(state, *batch, min_seq=ms)
            overflow = np.asarray(state.overflow)  # honest end sync (D2H)
            assert not overflow.any(), "capacity overflow in bench"
            headline_trials.append(
                n_docs * ops_per_batch * n_batches /
                (time.perf_counter() - ts))
        total = time.perf_counter() - t0
        n_ops = n_docs * ops_per_batch * n_batches * n_suites
        ops_per_sec = n_ops / total
        headline_sorted = sorted(headline_trials)
        headline_band = {
            "min": round(headline_sorted[0], 1),
            "median": round(headline_sorted[len(headline_sorted) // 2], 1),
            "max": round(headline_sorted[-1], 1),
            "spread_pct": round(
                100 * (headline_sorted[-1] - headline_sorted[0]) /
                headline_sorted[-1], 1),
        }

    if _want("conflict"):
        _phase("conflict")
        # --- conflict phase: multi-client, annotate-bearing corpus --------------
        # VERDICT r1 weak #3: the typing storm is single-writer and annotate-
        # free. This phase measures the props-mode Pallas kernel on divergent
        # perspectives + overlapping removes + annotates, with on-device digest
        # parity against the XLA props path.
        from fluidframework_tpu.testing.synthetic import conflict_storm
        from fluidframework_tpu.ops.merge_tree_kernel import (
            string_state_digest,
        )

        c_batches = []
        seq = 1
        for b in range(n_batches):
            planes, seq = conflict_storm(n_docs, ops_per_batch, seed=100 + b,
                                         start_seq=seq)
            c_batches.append(tuple(jnp.asarray(planes[k]) for k in order))
        conflict_fn = jax.jit(functools.partial(
            apply_string_batch_pallas, tile=64, with_props=True),
            donate_argnums=0)

        # warmup + digest parity (props kernel vs XLA props scan, on device)
        xla_props = jax.jit(functools.partial(apply_string_batch,
                                              with_props=True))
        s_c = conflict_fn(StringState.create(n_docs, capacity), *c_batches[0])
        s_x = xla_props(StringState.create(n_docs, capacity), *c_batches[0])
        conflict_parity = bool(np.array_equal(
            np.asarray(string_state_digest(s_c)),
            np.asarray(string_state_digest(s_x)))) and bool(np.array_equal(
                np.asarray(s_c.prop_val), np.asarray(s_x.prop_val)))
        assert conflict_parity, "props kernel divergence on device"
        del s_c, s_x

        # warmup the fused apply+zamboni variant
        s_w = conflict_fn(StringState.create(n_docs, capacity),
                          *c_batches[0],
                          min_seq=jnp.zeros((n_docs,), jnp.int32))
        _ = np.asarray(s_w.overflow)
        del s_w

        t0 = time.perf_counter()
        for _suite in range(n_suites):
            state = StringState.create(n_docs, capacity)
            done_seq = 0
            for batch in c_batches:
                done_seq += n_docs * ops_per_batch
                ms = jnp.full((n_docs,), done_seq, jnp.int32)
                # fused apply+zamboni: ONE dispatch (the sort-based props
                # compact costs more than the apply itself)
                state = conflict_fn(state, *batch, min_seq=ms)
            overflow = np.asarray(state.overflow)
            assert not overflow.any(), "conflict bench overflow"
        conflict_s = time.perf_counter() - t0
        conflict_ops_per_sec = n_ops / conflict_s

    if _want("serving broadcast"):
        _phase("serving broadcast")
        # --- serving phase: the FULL engine end-to-end ---------------------------
        # StringServingEngine ingest→sequence(C++ Deli)→durable log→device merge
        # →read, via the columnar pipeline (VERDICT r1 weak #1: the product
        # stack, not a kernel microbench). Same corpus shape; per-doc dense seqs.
        from fluidframework_tpu.server.serving import StringServingEngine

        docs = [f"doc-{i}" for i in range(n_docs)]

        def fresh_string_engine():
            eng = StringServingEngine(
                n_docs=n_docs, capacity=serve_capacity, batch_window=10 ** 9,
                compact_every=1, sequencer="native")
            for d in docs:
                eng.connect(d, 1)
            return eng

        engine = fresh_string_engine()
        assert type(engine.deli).__name__ == "NativeDeliAdapter", \
            "native sequencer must be available for the serving bench"
        serve_batches = []
        for b in range(n_serve_batches):
            planes, _ = typing_storm(n_docs, ops_per_batch, seed=b)
            cseq = np.broadcast_to(
                np.arange(b * ops_per_batch + 1, (b + 1) * ops_per_batch + 1,
                          dtype=np.int32), (n_docs, ops_per_batch))
            # client saw everything sequenced so far: op g sees seq g+1 (join=1)
            ref = cseq  # == global per-doc op count before this op, + 1
            serve_batches.append((planes["kind"], planes["a0"], planes["a1"],
                                  cseq, ref))
        client_plane = np.ones((n_docs, ops_per_batch), np.int32)

        # warmup batch compiles the serving dispatch shape, then measure.
        # THREE independent trials (fresh engine each), best reported: single
        # trials swing ±30% run to run. Waves go
        # through the PipelinedIngestExecutor (the production ingest path):
        # wave N+1 prepacks/sequences while wave N's dispatch is on device
        # and N−1's durable append completes in the background; drain() ends
        # the timed section at the last wave's ack-safe point.
        from fluidframework_tpu.server.ingest_pipeline import (
            PipelinedIngestExecutor,
        )

        def _serving_trial(eng):
            trows = np.array([eng.doc_row(d) for d in docs], np.int32)
            kind, a0, a1, cseq, ref = serve_batches[0]
            eng.ingest_planes(trows, client_plane, cseq, ref, kind, a0, a1,
                              "abcd")
            _ = np.asarray(eng.store.state.overflow)
            ex = PipelinedIngestExecutor(eng, depth=3)
            t0 = time.perf_counter()
            tickets = [ex.submit(trows, client_plane, cseq, ref, kind, a0,
                                 a1, text="abcd")
                       for kind, a0, a1, cseq, ref in serve_batches[1:]]
            ex.drain()
            overflow = np.asarray(eng.store.state.overflow)  # end sync
            elapsed = time.perf_counter() - t0
            n = 0
            for tk in tickets:
                res = tk.result()
                assert res["nacked"] == 0
                n += n_docs * ops_per_batch - res["nacked"]
            pipe_stats = ex.stats()
            ex.close()
            assert not overflow.any(), "serving overflow"
            return n / elapsed, pipe_stats

        serving_trials, serving_pipe_stats = [], None
        for _t in range(3):
            eng_t = engine if _t == 0 else fresh_string_engine()
            rate, pstats = _serving_trial(eng_t)
            serving_trials.append(rate)
            if rate >= max(serving_trials):
                serving_pipe_stats = pstats
            if eng_t is not engine:
                del eng_t   # transient: freed after its trial
        serving_trials.sort()
        serving_ops_per_sec = serving_trials[-1]
        serving_ops_per_sec_median = serving_trials[len(serving_trials) // 2]

        # read path timed separately. A read = flush (no device work when the
        # queue is empty) + ONE fused gather+transfer — a 1-round-trip budget,
        # asserted from the store's device-read counter. The warmup read pays
        # the gather program's compile + the pipeline drain OUTSIDE the timed
        # section (a production server's steady state).
        _ = engine.read_text(docs[1])
        before_reads = engine.store.device_reads
        tr = time.perf_counter()
        _ = [engine.read_text(docs[i])
             for i in (0, n_docs // 2, 7, n_docs - 1)]
        serving_read_ms = (time.perf_counter() - tr) * 1000 / 4
        read_rtts = (engine.store.device_reads - before_reads) / 4
        assert read_rtts == 1.0, read_rtts

    if _want("serving rich"):
        _phase("serving rich")
        # --- serving: distinct payloads + annotates (rich corpus) ---------------
        # The columnar path with per-op payload handles and single-key annotate
        # slots (VERDICT r2 weak #4: real text is not a broadcast payload).
        from fluidframework_tpu.testing.synthetic import rich_storm
        from fluidframework_tpu.core.protocol import (
            MessageType, SequencedDocumentMessage,
        )
        from fluidframework_tpu.ops.string_store import TensorStringStore
        from fluidframework_tpu.ops.schema import OpKind
        rich_engine = fresh_string_engine()
        rich_batches = []
        for b in range(n_serve_batches):
            planes, texts, rprops, _ = rich_storm(n_docs, ops_per_batch, seed=b)
            cseq = np.broadcast_to(
                np.arange(b * ops_per_batch + 1, (b + 1) * ops_per_batch + 1,
                          dtype=np.int32), (n_docs, ops_per_batch))
            rich_batches.append((planes, texts, rprops, cseq))
        def _rich_trial(eng):
            trows = np.array([eng.doc_row(d) for d in docs], np.int32)
            planes, texts, rprops, cseq = rich_batches[0]
            eng.ingest_planes(trows, client_plane, cseq, cseq,
                              planes["kind"], planes["a0"], planes["a1"],
                              texts=texts, tidx=planes["tidx"], props=rprops)
            _ = np.asarray(eng.store.state.overflow)
            # pipelined: the rich interner/table build (the 100ms p50 `pack`
            # VERDICT r5 pinned) prepacks on the pack worker CONCURRENT with
            # the previous wave's device dispatch — off the critical path
            ex = PipelinedIngestExecutor(eng, depth=3)
            t0 = time.perf_counter()
            tickets = [ex.submit(trows, client_plane, cseq, cseq,
                                 planes["kind"], planes["a0"], planes["a1"],
                                 texts=texts, tidx=planes["tidx"],
                                 props=rprops)
                       for planes, texts, rprops, cseq in rich_batches[1:]]
            ex.drain()
            overflow = np.asarray(eng.store.state.overflow)
            elapsed = time.perf_counter() - t0
            for tk in tickets:
                assert tk.result()["nacked"] == 0
            pipe_stats = ex.stats()
            ex.close()
            assert not overflow.any(), "rich serving overflow"
            return (n_docs * ops_per_batch * (n_serve_batches - 1) / elapsed,
                    pipe_stats)

        rich_trials, rich_pipe_stats = [], None
        for _t in range(3):  # rich trials spread the widest
            eng_t = rich_engine if _t == 0 else fresh_string_engine()
            rate, pstats = _rich_trial(eng_t)
            rich_trials.append(rate)
            if rate >= max(rich_trials):
                rich_pipe_stats = pstats
            if eng_t is not rich_engine:
                del eng_t   # transient: freed after its trial
        rich_trials.sort()
        rich_ops_per_sec = rich_trials[-1]
        rich_ops_per_sec_median = rich_trials[len(rich_trials) // 2]
        # parity: per-op message path on a fresh single-doc store
        for check_doc in (1, n_docs - 1):
            ref_store = TensorStringStore(n_docs=1, capacity=serve_capacity)
            msgs = []
            seq = 1
            for planes, texts, rprops, cseq in rich_batches:
                for o in range(ops_per_batch):
                    seq += 1
                    k = planes["kind"][check_doc, o]
                    if k == OpKind.STR_INSERT:
                        contents = {"mt": "insert", "kind": 0,
                                    "pos": int(planes["a0"][check_doc, o]),
                                    "text": texts[int(planes["tidx"]
                                                     [check_doc, o])]}
                    elif k == OpKind.STR_ANNOTATE:
                        contents = {"mt": "annotate",
                                    "start": int(planes["a0"][check_doc, o]),
                                    "end": int(planes["a1"][check_doc, o]),
                                    "props": rprops[int(planes["tidx"]
                                                        [check_doc, o])]}
                    else:
                        contents = {"mt": "remove",
                                    "start": int(planes["a0"][check_doc, o]),
                                    "end": int(planes["a1"][check_doc, o])}
                    msgs.append((0, SequencedDocumentMessage(
                        doc_id="x", client_id=1,
                        client_seq=int(cseq[check_doc, o]),
                        ref_seq=int(cseq[check_doc, o]), seq=seq,
                        min_seq=0, type=MessageType.OP, contents=contents)))
            ref_store.apply_messages(msgs)  # one batched device apply
            assert rich_engine.read_text(docs[check_doc]) == \
                ref_store.read_text(0), f"rich divergence doc {check_doc}"

    if _want("serving durable"):
        _phase("serving durable")
        # --- serving: fsync'd durable log (group commit per batch) --------------
        # Same pipeline with the C++ durable log ON and an fsync barrier after
        # every batch — "durable" is in the measured path (VERDICT r2 weak #3).
        import tempfile
        from fluidframework_tpu.server import native_oplog
        durable_ops_per_sec = None
        durable_ops_per_sec_median = None
        durable_trials = []
        assert native_oplog.available(), \
            "native oplog must be available for the durable bench"

        def _durable_trial():
            with tempfile.TemporaryDirectory() as dlog_dir:
                dlog = native_oplog.NativePartitionedLog(dlog_dir, 8)
                dur_engine = StringServingEngine(
                    n_docs=n_docs, capacity=serve_capacity,
                    batch_window=10 ** 9, compact_every=1,
                    sequencer="native", log=dlog)
                for d in docs:
                    dur_engine.connect(d, 1)
                drows = np.array([dur_engine.doc_row(d) for d in docs],
                                 np.int32)
                kind, a0, a1, cseq, ref = serve_batches[0]
                dur_engine.ingest_planes(drows, client_plane, cseq, ref,
                                         kind, a0, a1, "abcd")
                dlog.sync()
                _ = np.asarray(dur_engine.store.state.overflow)
                t0 = time.perf_counter()
                for kind, a0, a1, cseq, ref in serve_batches[1:]:
                    res = dur_engine.ingest_planes(drows, client_plane,
                                                   cseq, ref, kind, a0,
                                                   a1, "abcd")
                    dlog.sync()  # group commit: ack is durable
                    assert res["nacked"] == 0
                overflow = np.asarray(dur_engine.store.state.overflow)
                durable_s = time.perf_counter() - t0
                assert not overflow.any()
                dlog.close()
                return (n_docs * ops_per_batch * (n_serve_batches - 1) /
                        durable_s)

        # >=3 trials, like the broadcast/rich phases above: a single-trial
        # durable number landing ABOVE broadcast (2.72M vs 2.56M in r5)
        # is noise, not physics — the trials array lets the
        # record say which (compare medians, not bests)
        for _t in range(3):
            durable_trials.append(_durable_trial())
        durable_trials.sort()
        durable_ops_per_sec = durable_trials[-1]
        durable_ops_per_sec_median = durable_trials[len(durable_trials) // 2]

    if _want("serving tree"):
        _phase("serving tree")
        # --- serving: SharedTree columnar records --------------------------------
        # The largest DDS's serving number (VERDICT r4 missing #1): GENERAL
        # tree edits (constrained transactions: insert-after + setValue) in
        # the columnar record wire format (server/tree_wire.py) with numeric
        # ids (the id-compressor hot path) — one C++ sequencing call, one
        # width-coded device upload, one batched apply, one raw-plane durable
        # record per wave. Clients pre-encode (their serialization cost, as
        # with ingest_planes' packing); oracle parity asserted from the log.
        from fluidframework_tpu.server.serving import TreeServingEngine
        from fluidframework_tpu.server.tree_wire import (encode_leaf_records,
                                                         encode_tree_batch)
        n_tree_docs = 8192
        tree_opd = 8            # transactions per doc per wave
        n_tree_waves = 6        # measured waves per trial (after warmup;
        #                         6 waves through a depth-3 pipeline reach
        #                         steady-state overlap — 3 barely fill it)
        tdocs = [f"t-{i}" for i in range(n_tree_docs)]
        tree_n_ops = n_tree_docs * tree_opd

        def fresh_tree_engine():
            eng = TreeServingEngine(n_docs=n_tree_docs, capacity=128,
                                    batch_window=10 ** 9, sequencer="native")
            for d in tdocs:
                eng.connect(d, 1)
            return eng

        def tree_batches(eng):
            """Client-side: encode warmup + measured waves of transactions
            (chained inserts + value updates on the previous node)."""
            base = eng.allocate_node_ids(tree_n_ops * (n_tree_waves + 1))

            def nid(di, k):
                return f"#{base + di * tree_opd * (n_tree_waves + 1) + k}"

            out = []
            for wave in range(n_tree_waves + 1):
                ops = []
                for di in range(n_tree_docs):
                    for j in range(tree_opd):
                        k = wave * tree_opd + j
                        prev = nid(di, k - 1)
                        ops.append(
                            {"op": "transaction",
                             "constraints":
                                 [{"nodeExists": prev}] if k else [],
                             "edits": [
                                 {"op": "insert", "parent": "root",
                                  "field": "kids",
                                  "after": prev if k else None,
                                  "nodes": [{"id": nid(di, k),
                                             "type": "item", "value": k}]},
                                 {"op": "setValue",
                                  "id": prev if k else "root",
                                  "value": k * 10}]})
                out.append(encode_tree_batch(ops))
            return out

        def tree_cseqs(wave):
            return np.repeat(
                np.arange(1, tree_opd + 1)[None, :] + wave * tree_opd,
                n_tree_docs, axis=0).reshape(-1)

        tree_zero = np.zeros(tree_n_ops, np.int32)
        tree_ones = np.ones(tree_n_ops, np.int32)

        def _tree_trial():
            """Pipelined trial (the string serving phases' executor idiom):
            wave N+1's wire prepack + sequencing overlap wave N's device
            dispatch while N−1's durable append completes in the background;
            drain() ends the timed section at the last wave's ack-safe
            point."""
            eng = fresh_tree_engine()
            batches = tree_batches(eng)
            trows = np.repeat(
                np.array([eng.doc_row(d) for d in tdocs], np.int32),
                tree_opd)
            eng.ingest_records(None, tree_ones, tree_cseqs(0), tree_zero,
                               batches[0], rows=trows)   # warmup + compile
            _ = eng.sync()
            ex = PipelinedIngestExecutor(eng, depth=3)
            t0 = time.perf_counter()
            tickets = [ex.submit(None, tree_ones, tree_cseqs(w + 1),
                                 tree_zero, b, rows=trows)
                       for w, b in enumerate(batches[1:])]
            ex.drain()
            ovf = eng.sync()
            rate = n_tree_waves * tree_n_ops / (time.perf_counter() - t0)
            assert not ovf.any(), "tree capacity overflow in bench"
            for tk in tickets:
                assert tk.result()["nacked"] == 0
            pipe_stats = ex.stats()
            ex.close()
            return eng, rate, pipe_stats

        tree_trials = []
        tree_eng = None
        tree_pipe_stats = None
        for _t in range(3):
            eng_t, rate, pstats = _tree_trial()
            tree_trials.append(rate)
            if rate >= max(tree_trials):
                tree_eng = eng_t
                tree_pipe_stats = pstats
            else:
                del eng_t
        tree_trials.sort()
        tree_ops_per_sec = tree_trials[-1]
        tree_ops_per_sec_median = tree_trials[len(tree_trials) // 2]

        # the tree VOLUME path: flat single-node inserts, ONE solo record per
        # op, pre-encoded by clients (``encode_leaf_records`` — their
        # serialization cost, exactly like the general phase's
        # ``encode_tree_batch``) and ingested through the SAME
        # ``ingest_records`` pipeline the general path uses. One record per
        # op instead of the transaction path's three, so flat ≥ general by
        # construction. 8 leaves/doc/wave matches the general phase's op
        # volume (65536 ops/wave).
        n_leaf_docs = n_tree_docs
        leaf_opd = tree_opd
        ldocs = [f"tf-{i}" for i in range(n_leaf_docs)]
        n_leaf_waves = n_tree_waves
        leaf_n_ops = n_leaf_docs * leaf_opd
        leaf_ones = np.ones(leaf_n_ops, np.int32)
        leaf_zero = np.zeros(leaf_n_ops, np.int32)

        def leaf_batches(eng):
            lbase = eng.allocate_node_ids(leaf_n_ops * (n_leaf_waves + 1))

            def lid(i, k):
                return f"#{lbase + i * leaf_opd * (n_leaf_waves + 1) + k}"

            out = []
            for wave in range(n_leaf_waves + 1):
                nids, values, afters = [], [], []
                for i in range(n_leaf_docs):
                    for j in range(leaf_opd):
                        k = wave * leaf_opd + j
                        nids.append(lid(i, k))
                        values.append(k)
                        afters.append(lid(i, k - 1) if k else None)
                out.append(encode_leaf_records(
                    ["root"] * leaf_n_ops, ["kids"] * leaf_n_ops, nids,
                    values, ["leaf"] * leaf_n_ops, afters))
            return out

        def leaf_cseqs(wave):
            return np.repeat(
                np.arange(1, leaf_opd + 1)[None, :] + wave * leaf_opd,
                n_leaf_docs, axis=0).reshape(-1)

        def _leaves_trial():
            eng = TreeServingEngine(n_docs=n_leaf_docs, capacity=128,
                                    batch_window=10 ** 9, sequencer="native")
            for d in ldocs:
                eng.connect(d, 1)
            lbs = leaf_batches(eng)
            lrows = np.repeat(
                np.array([eng.doc_row(d) for d in ldocs], np.int32),
                leaf_opd)
            eng.ingest_records(None, leaf_ones, leaf_cseqs(0), leaf_zero,
                               lbs[0], rows=lrows)   # warmup + compile
            _ = eng.sync()
            ex = PipelinedIngestExecutor(eng, depth=3)
            t0 = time.perf_counter()
            tickets = [ex.submit(None, leaf_ones, leaf_cseqs(w + 1),
                                 leaf_zero, b, rows=lrows)
                       for w, b in enumerate(lbs[1:])]
            ex.drain()
            _ = eng.sync()
            rate = n_leaf_waves * leaf_n_ops / (time.perf_counter() - t0)
            for tk in tickets:
                assert tk.result()["nacked"] == 0
            ex.close()
            return eng, rate

        leaf_trials = []
        leaves_eng = None
        for _t in range(3):
            eng_t, rate = _leaves_trial()
            leaf_trials.append(rate)
            if rate >= max(leaf_trials):
                leaves_eng = eng_t
            else:
                del eng_t
        leaf_trials.sort()
        tree_flat_ops_per_sec = leaf_trials[-1]
        # parity: the flat path's log must rebuild the oracle state too
        from fluidframework_tpu.models.shared_tree import SharedTree
        probe_f = ldocs[7]
        oracle_f = SharedTree(probe_f, 999)
        for m in leaves_eng._doc_log_messages(probe_f):
            oracle_f.process_core(m, local=False)
        assert leaves_eng.to_dict(probe_f) == oracle_f.to_dict(), \
            "tree flat-ingest divergence vs oracle"
        del leaves_eng

        # oracle parity: replay the sampled doc's full log history through the
        # pure-Python SharedTree oracle
        probe = tdocs[n_tree_docs // 2]
        oracle = SharedTree(probe, 999)
        for m in tree_eng._doc_log_messages(probe):
            oracle.process_core(m, local=False)
        assert tree_eng.to_dict(probe) == oracle.to_dict(), \
            "tree serving divergence vs oracle"

    if _want("tree kernel"):
        _phase("tree kernel")
        # --- tree kernel-only: device-resident wire applies ----------------------
        # Splits kernel cost from host/upload cost (VERDICT r4 missing #1:
        # "no tree-kernel-only number is recorded anywhere"): the same wire
        # program, arguments already resident, back-to-back donated applies.
        import jax.numpy as _jnp
        from fluidframework_tpu.ops.tree_kernel import (
            TreeState as _TreeState, apply_tree_wire_jit as _wire_jit)
        from fluidframework_tpu.ops.tree_store import pack_wire_records
        kr = np.repeat(np.arange(n_tree_docs, dtype=np.int64), tree_opd)
        kbatch = tree_batches(fresh_tree_engine())[1]
        krec = kbatch["recs"]
        krec_op = kbatch["rec_op"]
        # the SAME packing the serving dispatch uses (one shared layout,
        # id/value lanes width-coded u16 → u32 when a table outgrows u16 —
        # the old unconditional u16 silently truncated this wave's ~74k-id
        # table, wrapping indices instead of exercising the real layout)
        kcols, kids, kvals, krow, kposb, ko = pack_wire_records(
            krec, krec_op, kr[krec_op],
            id_t=np.uint16 if len(kbatch["ids"]) < 0xFFFF else np.uint32,
            val_t=np.uint16 if len(kbatch["values"]) < 0xFFFF else np.uint32)
        kbase = np.full(n_tree_docs, 2, np.int32)
        kmaps = [np.pad(np.asarray(
            [e if isinstance(e, int) else 1 for e in kbatch["ids"]],
            np.int32), (1, 0)),
            np.arange(len(kbatch["fields"]) + 1, dtype=np.int32),
            np.arange(len(kbatch["types"]) + 1, dtype=np.int32),
            np.arange(len(kbatch["values"]) + 1, dtype=np.int32)]
        kargs = [_jnp.asarray(x) for x in
                 (kcols, kids, kvals, krow, kposb, kbase, *kmaps)]
        kst = _TreeState.create(n_tree_docs, 128)
        kst = _wire_jit(kst, *kargs, o=ko)
        _ = np.asarray(kst.overflow)
        # 3 back-to-back measurements of the same resident dispatch loop: the
        # kernel number's run-to-run variance band lands in the record (drift
        # between rounds was previously indistinguishable from regression)
        k_reps = 6
        tree_kernel_trials = []
        for _t in range(3):
            t0 = time.perf_counter()
            for _i in range(k_reps):
                kst = _wire_jit(kst, *kargs, o=ko)
            _ = np.asarray(kst.overflow)
            tree_kernel_trials.append(
                k_reps * tree_n_ops / (time.perf_counter() - t0))
        tree_kernel_trials.sort()
        tree_kernel_ops_per_sec = tree_kernel_trials[-1]
        del kst, kargs

    if _want("serving intervals"):
        _phase("serving intervals")
        # --- serving: interval-holding docs (config #5's serving form) -----------
        # An interval-heavy corpus (annotates + inserts + removes sliding the
        # anchors) through StringServingEngine at 1k docs ≈ 1k simulated
        # editors (VERDICT r4 missing #4). Interval-holding docs now ride the
        # COLUMNAR fast path: the ingress hands apply_planes the per-op MSN
        # plane, the host scan splits each window at tombstone-crossing
        # boundaries, and anchors slide in ONE fused device gather per
        # boundary (docs/INTERVALS.md). Endpoints are asserted against the
        # oracle IntervalCollection on sampled docs — the same gate the old
        # per-op escape hatch had, minus its ~1000x Python round-trip tax.
        import random as _random
        from fluidframework_tpu.models.merge_tree import LOCAL_VIEW
        from fluidframework_tpu.models.interval_collection import (
            IntervalCollection,
        )
        from fluidframework_tpu.models.shared_string import SharedString
        # 4096-doc batch: each wave costs a near-constant ~2 dispatches + 1
        # slide gather (a device→host sync), so throughput scales with the
        # doc axis
        n_iv_docs = 4096
        iv_ow = 16              # ops per doc per wave (window width)
        iv_warm = 2             # untimed: compiles the split/slide shapes
        iv_waves = 8            # timed waves
        iv_rng = _random.Random(5)
        # compact_every=inf at the ENGINE: zamboni already rides inside the
        # apply itself (interval docs disable the fused min_seq path, so
        # apply_planes compacts after the reanchor scan every window); an
        # engine-cadence compact on top would just dispatch it twice
        iv_eng = StringServingEngine(n_docs=n_iv_docs, capacity=256,
                                     batch_window=10 ** 9,
                                     compact_every=10 ** 9,
                                     sequencer="native")
        iv_docs = [f"iv-{i}" for i in range(n_iv_docs)]
        base_text = "the quick brown fox jumps over the dazed dog"
        for d in iv_docs:
            iv_eng.connect(d, 1)
            _, nack = iv_eng.submit(d, 1, 1, 0, {"mt": "insert", "kind": 0,
                                                 "pos": 0, "text": base_text,
                                                 "clientSeq": 1})
            assert nack is None
        iv_eng.flush()
        req = {}
        for d in iv_docs:
            row = iv_eng.doc_row(d)
            spans = []
            for _k in range(3):
                s = iv_rng.randrange(len(base_text) - 8)
                e = s + 2 + iv_rng.randrange(5)
                spans.append((s, e, None))
            req[row] = spans
        # ONE fused gather anchors the whole corpus (add_interval pays >=2
        # device→host syncs per call)
        iv_ids = iv_eng.store.add_intervals_bulk(req)
        iv_spans = []
        for d in iv_docs:
            row = iv_eng.doc_row(d)
            iv_spans.append([(s, e, sid) for (s, e, _), sid in
                             zip(req[row], iv_ids[row])])
        iv_lengths = [len(base_text)] * n_iv_docs
        # plane-shaped waves: ~50% annotate / 30% insert / 20% remove. Every
        # op is client 1's, so positions are generated against the doc's full
        # evolving text (the client's local perspective sees its own ops).
        iv_texts = ["XY"]
        iv_props = [{"bold": True}, {"bold": False}]
        iv_batches = []
        for w in range(iv_warm + iv_waves):
            kind = np.zeros((n_iv_docs, iv_ow), np.int32)
            a0 = np.zeros((n_iv_docs, iv_ow), np.int32)
            a1 = np.zeros((n_iv_docs, iv_ow), np.int32)
            tix = np.zeros((n_iv_docs, iv_ow), np.int32)
            for di in range(n_iv_docs):
                ln = iv_lengths[di]
                for c in range(iv_ow):
                    roll = iv_rng.random()
                    if roll < 0.5 and ln >= 6:
                        s = iv_rng.randrange(ln - 4)
                        kind[di, c] = OpKind.STR_ANNOTATE
                        a0[di, c], a1[di, c] = s, s + 2
                        tix[di, c] = iv_rng.randrange(2)
                    elif roll < 0.8 or ln < 16:
                        kind[di, c] = OpKind.STR_INSERT
                        a0[di, c], a1[di, c] = iv_rng.randrange(ln + 1), 2
                        ln += 2
                    else:
                        s = iv_rng.randrange(ln - 3)
                        kind[di, c] = OpKind.STR_REMOVE
                        a0[di, c], a1[di, c] = s, s + 2
                        ln -= 2
                iv_lengths[di] = ln
            # clientSeq 1 was the base insert; ref = everything the client has
            # seen sequenced = join(1) + base(1) + all prior waves. The
            # constant-per-wave ref advances the MSN floor past the PREVIOUS
            # wave's tombstones at column 0, so every post-warmup wave
            # exercises a real crossing (segment split + device anchor slide).
            cseq = np.broadcast_to(
                np.arange(2 + w * iv_ow, 2 + (w + 1) * iv_ow, dtype=np.int32),
                (n_iv_docs, iv_ow))
            ref = np.full((n_iv_docs, iv_ow), 2 + w * iv_ow, np.int32)
            iv_batches.append((kind, a0, a1, tix, cseq, ref))
        iv_rows = np.array([iv_eng.doc_row(d) for d in iv_docs], np.int32)
        iv_client = np.ones((n_iv_docs, iv_ow), np.int32)
        iv_seg_waves = []
        t0 = time.perf_counter()
        for w, (kind, a0, a1, tix, cseq, ref) in enumerate(iv_batches):
            if w == iv_warm:     # split/slide/compact shapes compiled; go
                _ = np.asarray(iv_eng.store.state.overflow)
                t0 = time.perf_counter()
            res = iv_eng.ingest_planes(iv_rows, iv_client, cseq, ref,
                                       kind, a0, a1, texts=iv_texts,
                                       tidx=tix, props=iv_props)
            assert res["nacked"] == 0
            iv_seg_waves.append(iv_eng.store.last_apply_stats["segments"])
        _ = np.asarray(iv_eng.store.state.overflow)
        interval_ops_per_sec = n_iv_docs * iv_ow * iv_waves / \
            (time.perf_counter() - t0)
        # regression pin: the waves went through the columnar apply (the old
        # per-op fallback kept no segment accounting) AND the MSN floor really
        # crossed tombstones mid-window (>= 2 segments per post-warmup wave)
        assert all(s >= 2 for s in iv_seg_waves[1:]), iv_seg_waves
        interval_wire = iv_eng.store.last_rich_wire
        # oracle parity: replay sampled docs' sequenced ops through the
        # oracle, anchor the same spans, compare endpoint positions
        for di in (7, n_iv_docs // 2):
            d = iv_docs[di]
            oracle = SharedString(d, 999)
            msgs = [m for m in iv_eng._doc_log_messages(d)]
            base_msgs = [m for m in msgs if m.client_seq == 1]
            tail_msgs = [m for m in msgs if m.client_seq > 1]
            # apply_msg (not bare process_core): the oracle must zamboni at
            # min-seq crossings exactly like the reference client, or slid
            # anchors diverge from the device's crossing-driven slides
            for m in base_msgs:
                oracle.apply_msg(m)
            coll = IntervalCollection("c", oracle.tree)
            row = iv_eng.doc_row(d)
            for k, (s, e, sid) in enumerate(iv_spans[di]):
                coll.apply_add(f"o{k}", s, e, {}, LOCAL_VIEW, 999)
            for m in tail_msgs:
                oracle.apply_msg(m)
            assert iv_eng.read_text(d) == oracle.get_text(), d
            for k, (s, e, sid) in enumerate(iv_spans[di]):
                want = coll.endpoints(coll.get(f"o{k}"))
                got = iv_eng.store.interval_endpoints(row, sid)
                assert got == want, (d, k, got, want)
        del iv_eng

    if _want("matrix serving"):
        _phase("matrix serving")
        # --- matrix serving: folded into THE authoritative record ----------------
        # The config #3 side-bench's serving phase (columnar setCell ingest:
        # one C++ sequencing call + one device axis-resolve scan + FWW filter
        # + one cell-table merge + durable record per batch), re-run here so
        # BENCH_r*.json carries matrix_serving_ops_per_sec with a trials
        # array (VERDICT r5: "claims and the record disagree").
        from fluidframework_tpu.server.serving import MatrixServingEngine

        def _matrix_trial():
            D, G = 64, 32   # docs; each a 32x32 grid, then cell storms
            eng = MatrixServingEngine(n_docs=D, cell_capacity=1 << 17,
                                      batch_window=10 ** 9, axis_capacity=128,
                                      sequencer="native")
            mdocs = [f"mx-{i}" for i in range(D)]
            srng = np.random.default_rng(7)
            mcs = {d: 0 for d in mdocs}
            for d in mdocs:
                eng.connect(d, 7)
                for mx in ("insRow", "insCol"):
                    mcs[d] += 1
                    _, nack = eng.submit(d, 7, mcs[d], 0,
                                         {"mx": mx, "pos": 0, "count": G,
                                          "opKey": (7, mcs[d])})
                    assert nack is None
            eng.flush()

            def storm():
                ids, cseqs, rp, cp, vals = [], [], [], [], []
                for d in mdocs:
                    for _ in range(64):
                        mcs[d] += 1
                        ids.append(d)
                        cseqs.append(mcs[d])
                        rp.append(int(srng.integers(0, G)))
                        cp.append(int(srng.integers(0, G)))
                        vals.append(int(srng.integers(0, 1 << 20)))
                return ids, cseqs, rp, cp, vals

            # storms pre-generated OUTSIDE the timed section: the rng loop
            # is the simulated clients' op authoring, not serving work —
            # the same treatment the string/tree phases give their
            # pre-encoded waves (client serialization happens client-side)
            waves = [storm() for _w in range(7)]
            ids, cseqs, rp, cp, vals = waves[0]  # warmup (compiles the scan)
            eng.ingest_cells(ids, [7] * len(ids), cseqs, [0] * len(ids),
                             rp, cp, vals)
            _ = eng.dims(mdocs[0])
            n_serve = 0
            t0 = time.perf_counter()
            for ids, cseqs, rp, cp, vals in waves[1:]:
                res = eng.ingest_cells(ids, [7] * len(ids), cseqs,
                                       [0] * len(ids), rp, cp, vals)
                assert res["nacked"] == 0
                n_serve += len(ids)
            _ = eng.dims(mdocs[0])               # end sync (device read)
            rate = n_serve / (time.perf_counter() - t0)
            del eng
            return rate

        matrix_trials = sorted(_matrix_trial() for _t in range(3))
        matrix_serving_ops_per_sec = matrix_trials[-1]

    if _want("columnar ingress"):
        _phase("columnar ingress")
        # --- columnar ingress: M TCP clients → the PIPELINED front door ----------
        # benches/columnar_ingress_storm.py folded into the authoritative
        # record: real sockets, width-coded binary frames, windowed
        # aggregation — now feeding the pipelined executor (depth 3), so the
        # flusher aggregates the next window while the previous ones are in
        # flight and acks fan back only after each wave's durable append.
        from fluidframework_tpu.server.columnar_ingress import (
            ColumnarAlfred, ColumnarClient, _OP_DTYPE,
        )

        def _ingress_trial(n_clients=8, docs_per=1024, waves=24,
                           window_rows=4096, with_ops=False):
            ing_eng = StringServingEngine(
                n_docs=n_clients * docs_per, capacity=256,
                batch_window=10 ** 9, compact_every=10 ** 9,
                sequencer="native")
            srv = ColumnarAlfred(ing_eng, window_min_rows=window_rows,
                                 window_ms=2.0,
                                 pipeline_depth=3).start_in_thread()
            # scrape-overhead acceptance (ISSUE 17): attach the live ops
            # plane and hit /metrics at 1 Hz for the whole storm — the
            # scraped trial's rate vs the unscraped median is the overhead
            ops = None
            scrape_stop = threading.Event()
            scrapes = [0]
            if with_ops:
                import urllib.request as _url
                ops = srv.start_ops(tick_interval_s=1.0)

                def _scraper():
                    while not scrape_stop.is_set():
                        with _url.urlopen(ops.url + "/metrics",
                                          timeout=30) as r:
                            r.read()
                        scrapes[0] += 1
                        scrape_stop.wait(1.0)

                threading.Thread(target=_scraper, daemon=True).start()
            total = n_clients * docs_per * waves
            acked = [0] * n_clients
            done = threading.Barrier(n_clients + 1)

            def client_run(ci):
                cl = ColumnarClient("127.0.0.1", srv.port)
                cdocs = [f"c{ci}-d{j}" for j in range(docs_per)]
                crow = np.asarray(list(cl.join(cdocs).values()), np.uint16)

                def sender():
                    for w in range(waves):
                        ops = np.zeros(docs_per, _OP_DTYPE)
                        ops["row"] = crow
                        ops["cseq"] = w + 1
                        cl.send_ops([f"w{w}"], ops)

                st = threading.Thread(target=sender, daemon=True)
                st.start()
                want = docs_per * waves
                while acked[ci] < want:
                    resp = cl.recv_json()
                    assert resp["t"] == "acks", resp
                    for _cs, seq in resp["acks"]:
                        assert seq > 0
                    acked[ci] += len(resp["acks"])
                st.join()
                cl.close()
                done.wait()

            cthreads = [threading.Thread(target=client_run, args=(ci,),
                                         daemon=True)
                        for ci in range(n_clients)]
            t0 = time.perf_counter()
            for t in cthreads:
                t.start()
            done.wait(timeout=600)
            rate = total / (time.perf_counter() - t0)
            pstats = srv.pipeline_stats()
            dstats = srv.drain_stats()
            windows = srv.windows_flushed
            opsinfo = None
            if with_ops:
                import json as _json
                import urllib.request as _url
                scrape_stop.set()
                with _url.urlopen(ops.url + "/debug/latency",
                                  timeout=30) as r:
                    breakdown = _json.loads(r.read())
                opsinfo = {"scrapes": scrapes[0], "breakdown": breakdown}
            srv.stop()
            del ing_eng
            return rate, pstats, dstats, windows, opsinfo

        ingress_trials, ingress_stats, ingress_windows = [], None, 0
        ingress_drain = None
        for _t in range(3):
            rate, pstats, dstats, windows, _ = _ingress_trial()
            ingress_trials.append(rate)
            if rate >= max(ingress_trials):
                ingress_stats, ingress_windows = pstats, windows
                ingress_drain = dstats
        ingress_trials.sort()
        columnar_ingress_ops_per_sec = ingress_trials[-1]
        # three more storms with the ops endpoint attached and scraped at
        # 1 Hz (ISSUE 17 acceptance: < 1% throughput loss vs unscraped, and
        # the per-stage breakdown sums to the observed e2e ack latency).
        # Median-of-3 vs median-of-3: single-trial spread on a contended
        # host is ±5-7%, far above the real scrape cost — one draw against
        # the unscraped median reads noise as overhead.
        scraped_trials, opsinfo = [], None
        for _t in range(3):
            s_rate, _, _, _, s_info = _ingress_trial(with_ops=True)
            scraped_trials.append(s_rate)
            if s_rate >= max(scraped_trials):
                opsinfo = s_info
        scraped_trials.sort()
        scraped_rate = scraped_trials[len(scraped_trials) // 2]
        _unscraped = ingress_trials[len(ingress_trials) // 2]
        _bd = opsinfo["breakdown"]
        ops_plane = {
            "scraped_ops_per_sec": round(scraped_rate, 1),
            "scraped_trials": [round(t, 1) for t in scraped_trials],
            "unscraped_median_ops_per_sec": round(_unscraped, 1),
            "scrape_overhead_pct": round(
                (_unscraped - scraped_rate) / _unscraped * 100.0, 2),
            "scrapes": opsinfo["scrapes"],
            "stage_breakdown_coverage": round(_bd["coverage"], 4),
            "stage_e2e_mean_ms": round(_bd["e2e_mean_ms"], 3),
            # p99 is None when it fell off the histogram grid (the route's
            # JSON hygiene maps inf -> null); keep the record strict-JSON
            "stage_e2e_p99_ms": round(_bd["e2e_p99_ms"], 3)
            if _bd["e2e_p99_ms"] is not None else None,
            "stage_shares": {name: round(row["share"], 4)
                             for name, row in _bd["stages"].items()},
            "windows_attributed": _bd["windows"],
        }

    if _want("partition scaling"):
        _phase("partition scaling")
        # --- partitioned serving (ISSUE 18): shard the sequencer -----------------
        # The same columnar storm against PartitionedStringServing at 1/2/4/8
        # Deli partitions: the door carves per-partition windows in its drain
        # pass and runs one PipelinedIngestExecutor per partition (N
        # concurrent native sequencers). Three trials per width; speedup and
        # scaling efficiency are best-vs-best against the 1-partition
        # baseline. host_cores rides along because the ratio measures the
        # HOST as much as the code: the seq_dispatch stage is CPU-bound, so a
        # 1-core host serializes the partitions (ratio ~1.0) while a TPU-host
        # core budget lets them genuinely overlap. One extra trial at 4
        # partitions attaches a ReplicaDigestTap on the virtual device mesh:
        # every sequenced window is folded into the replicated shadow via the
        # shard_map step and cross-replica digest agreement is asserted
        # per window.
        partition_scaling = {}
        try:
            # re-imported locally: this phase must run standalone under
            # --phases without the "columnar ingress" phase's imports
            from fluidframework_tpu.server.columnar_ingress import (
                ColumnarAlfred, ColumnarClient, _OP_DTYPE,
            )
            from fluidframework_tpu.server.partitioned import (
                PartitionedStringServing, ReplicaDigestTap,
            )

            def _partition_trial(n_parts, tap=None, n_clients=4,
                                 docs_per=256, waves=10, window_rows=1024):
                total_docs = n_clients * docs_per
                # 2x headroom over the even split: hash routing is not
                # perfectly balanced, and a full partition would nack joins
                dpp = -(-total_docs * 2 // n_parts)
                svc = PartitionedStringServing(
                    n_partitions=n_parts, docs_per_partition=dpp,
                    capacity=256, batch_window=10 ** 9,
                    compact_every=10 ** 9, sequencer="native")
                srv = ColumnarAlfred(svc, window_min_rows=window_rows,
                                     window_ms=2.0,
                                     pipeline_depth=3).start_in_thread()
                srv.digest_tap = tap
                total = n_clients * docs_per * waves
                acked = [0] * n_clients
                done = threading.Barrier(n_clients + 1)

                def client_run(ci):
                    cl = ColumnarClient("127.0.0.1", srv.port)
                    cdocs = [f"ps{n_parts}-{ci}-d{j}"
                             for j in range(docs_per)]
                    crow = np.asarray(list(cl.join(cdocs).values()),
                                      np.uint16)

                    def sender():
                        for w in range(waves):
                            pops = np.zeros(docs_per, _OP_DTYPE)
                            pops["row"] = crow
                            pops["cseq"] = w + 1
                            cl.send_ops([f"w{w}"], pops)

                    st = threading.Thread(target=sender, daemon=True)
                    st.start()
                    want = docs_per * waves
                    while acked[ci] < want:
                        resp = cl.recv_json()
                        assert resp["t"] == "acks", resp
                        acked[ci] += len(resp["acks"])
                    st.join()
                    cl.close()
                    done.wait()

                cthreads = [threading.Thread(target=client_run, args=(ci,),
                                             daemon=True)
                            for ci in range(n_clients)]
                pt0 = time.perf_counter()
                for t in cthreads:
                    t.start()
                done.wait(timeout=600)
                rate = total / (time.perf_counter() - pt0)
                occ = srv.pipeline_stats().get("stage_occupancy")
                srv.stop()
                del svc
                return rate, occ

            widths = {}
            best_by_width = {}
            for n_parts in (1, 2, 4, 8):
                p_trials, p_occ = [], None
                for _t in range(3):
                    p_rate, occ = _partition_trial(n_parts)
                    p_trials.append(p_rate)
                    if p_rate >= max(p_trials):
                        p_occ = occ
                p_trials.sort()
                best_by_width[n_parts] = p_trials[-1]
                widths[str(n_parts)] = {
                    "ops_per_sec": round(p_trials[-1], 1),
                    "ops_per_sec_median":
                        round(p_trials[len(p_trials) // 2], 1),
                    "trials": [round(t, 1) for t in p_trials],
                    "seq_dispatch_occupancy":
                        round(p_occ["seq_dispatch"], 4) if p_occ else None,
                }
            base = best_by_width[1]
            # digest-parity trial: the tap needs >= 2 devices for a replica
            # axis (CPU: XLA_FLAGS=--xla_force_host_platform_device_count=8
            # gives the virtual 8-device mesh); fewer devices skip it with
            # the reason on the record
            digest = {"skipped": f"{jax.device_count()} device(s) — "
                                 "replica axis needs >= 2"}
            if jax.device_count() >= 2:
                from fluidframework_tpu.parallel.mesh import make_mesh
                tap = ReplicaDigestTap(make_mesh(jax.device_count()))
                t_rate, _ = _partition_trial(4, tap=tap)
                digest = {
                    "devices": jax.device_count(),
                    "replicas": tap.n_replicas,
                    "windows": tap.windows,
                    "agree_all": bool(tap.agree_all),
                    "tapped_ops_per_sec": round(t_rate, 1),
                }
            partition_scaling = {
                "widths": widths,
                "speedup_4x": round(best_by_width[4] / base, 3),
                "speedup_8x": round(best_by_width[8] / base, 3),
                "scaling_efficiency_4x":
                    round(best_by_width[4] / base / 4, 3),
                "host_cores": _os.cpu_count(),
                "digest": digest,
            }
            partition_columnar_ops_per_sec = max(
                best_by_width[4], best_by_width[8])
        except Exception as e:   # noqa: BLE001 — the record must still emit
            partition_scaling = {"error": repr(e)}
            partition_columnar_ops_per_sec = None

    if _want("read_fanout"):
        _phase("read_fanout")
        # --- read plane (ISSUE 20): encode-once observer fanout ------------------
        # Three measurements, one corpus: (a) delivery ops/s and the
        # encode-once amortization ratio at 1/64/256/1024 in-process
        # subscribers — the window bytes are encoded ONCE and the hub
        # fans the identical object, so the per-subscriber marginal cost
        # must be a vanishing fraction of the single-subscriber
        # encode+deliver cost (acceptance: <= 5% at 1024); (b) catch-up
        # latency — generation diff + short tail vs full-tail replay at
        # 512/2048/4096-op tails (acceptance: diff beats full p50 by >=
        # 5x at 4096); (c) staleness p99 under the write storm itself
        # (the plane pumps inline at ingest pace with 64 live
        # subscribers attached).
        read_fanout = {}
        try:
            from fluidframework_tpu.server.observer import ObserverHub
            from fluidframework_tpu.server.read_plane import (
                ReadPlane, StalenessTracker, apply_generation_diff,
                build_generation_diff, encode_window,
            )
            from fluidframework_tpu.testing.chaos import engine_class

            RF_R, RF_O, RF_WAVES = 64, 8, 24

            def _rf_engine(n_docs=RF_R, capacity=2048):
                eng = StringServingEngine(
                    n_docs=n_docs, capacity=capacity,
                    batch_window=10 ** 9, compact_every=10 ** 9,
                    sequencer="native")
                docs = [f"rf-d{i}" for i in range(n_docs)]
                for d in docs:
                    eng.connect(d, 1)
                rows = np.asarray([eng.doc_row(d) for d in docs],
                                  np.int32)
                return eng, docs, rows

            def _rf_wave(eng, rows, w, o=RF_O):
                r = len(rows)
                shape = (r, o)
                client = np.ones(shape, np.int32)
                cseq = np.broadcast_to(
                    np.arange(o, dtype=np.int32) + np.int32(w * o + 1),
                    shape).copy()
                ref = np.zeros(shape, np.int32)
                kind = np.zeros(shape, np.int32)      # STR_INSERT
                a0 = np.zeros(shape, np.int32)
                a1 = np.zeros(shape, np.int32)
                res = eng.ingest_planes(rows, client, cseq, ref,
                                        kind, a0, a1, text=f"w{w:03d}")
                assert res["nacked"] == 0, res

            # --- (c) staleness under the storm: live plane, 64 subs
            rf_tracker = StalenessTracker()
            rf_hub = ObserverHub(ring=RF_WAVES + 8, tracker=rf_tracker)
            for _i in range(64):
                rf_hub.subscribe(lambda _b: None)
            rf_eng, rf_docs, rf_rows = _rf_engine()
            rf_plane = ReadPlane(rf_eng, rf_hub)
            rf_eng.attach_read_plane(rf_plane)
            rf_log = rf_eng.log
            rf_offsets = [0] * rf_log.n_partitions
            wave_records = []
            for w in range(RF_WAVES):
                _rf_wave(rf_eng, rf_rows, w)
                recs = []
                for p in range(rf_log.n_partitions):
                    size = rf_log.size(p)
                    if size > rf_offsets[p]:
                        recs.extend(rf_log.read(
                            p, from_offset=rf_offsets[p],
                            to_offset=size))
                        rf_offsets[p] = size
                wave_records.append(recs)
            staleness_p99_s = rf_tracker.p99()

            # --- (a) encode once, fan to N: pre-encode the windows,
            # then time publish-only at each width (REPS passes so the
            # per-window publish cost is above timer noise)
            REPS = 5
            t0 = time.perf_counter()
            for _rep in range(REPS):
                windows = [encode_window(recs, i + 1)
                           for i, recs in enumerate(wave_records)]
            encode_s = (time.perf_counter() - t0) / REPS
            total_ops = sum(n for _p, n in windows)
            n_windows = len(windows)

            def _publish_time(n_subs):
                hub = ObserverHub(ring=8,
                                  tracker=StalenessTracker())
                sink = lambda _b: None  # noqa: E731 — shared no-op
                for _i in range(n_subs):
                    hub.subscribe(sink)
                t0 = time.perf_counter()
                for _rep in range(REPS):
                    for payload, n_ops in windows:
                        hub.publish(hub.next_wid(), payload, n_ops)
                return (time.perf_counter() - t0) / REPS

            fanout = {}
            pub_s = {}
            for n_subs in (1, 64, 256, 1024):
                best = min(_publish_time(n_subs) for _t in range(3))
                pub_s[n_subs] = best
                fanout[str(n_subs)] = {
                    "delivery_ops_per_sec":
                        round(total_ops * n_subs / best, 1),
                    "publish_ms_per_window":
                        round(best * 1e3 / n_windows, 4),
                }
            # single-subscriber cost = encode once + deliver to 1;
            # marginal = extra cost per additional subscriber
            single_sub_s = (encode_s + pub_s[1]) / n_windows
            marginal_s = (pub_s[1024] - pub_s[1]) / (1023 * n_windows)
            amortization_ratio = marginal_s / single_sub_s \
                if single_sub_s > 0 else None
            read_delivery_ops_per_sec = \
                fanout["1024"]["delivery_ops_per_sec"]

            # --- (b) catch-up: generation diff vs full-tail replay
            catchup = {}
            for tail in (512, 2048, 4096):
                ce, cdocs, crows = _rf_engine(
                    capacity=max(2048, tail // RF_R + 256))
                _rf_wave(ce, crows, 0)
                s_from = ce.summarize()
                waves = tail // (RF_R * RF_O)
                for w in range(1, waves + 1):
                    _rf_wave(ce, crows, w)
                s_to = ce.summarize()
                t_diff, t_full = [], []
                for _t in range(3):
                    t0 = time.perf_counter()
                    diff = build_generation_diff("string", s_from, s_to)
                    e_diff = apply_generation_diff("string", diff,
                                                   s_from, ce.log)
                    t_diff.append(time.perf_counter() - t0)
                    t0 = time.perf_counter()
                    e_full = engine_class("string").load(s_from, ce.log)
                    t_full.append(time.perf_counter() - t0)
                    # parity spot-check rides every trial
                    d0 = e_diff.read_text(cdocs[0])
                    assert d0 == e_full.read_text(cdocs[0])
                t_diff.sort()
                t_full.sort()
                catchup[str(tail)] = {
                    "tail_ops": waves * RF_R * RF_O,
                    "diff_p50_ms": round(t_diff[1] * 1e3, 2),
                    "full_replay_p50_ms": round(t_full[1] * 1e3, 2),
                    "speedup": round(t_full[1] / t_diff[1], 2),
                }
                del ce

            read_fanout = {
                "windows": n_windows,
                "total_ops": total_ops,
                "fanout": fanout,
                "encode_ms_per_window":
                    round(encode_s * 1e3 / n_windows, 4),
                "marginal_us_per_sub_window_1024":
                    round(marginal_s * 1e6, 4),
                "amortization_ratio_1024":
                    round(amortization_ratio, 5)
                    if amortization_ratio is not None else None,
                "catchup": catchup,
                "catchup_speedup_4096": catchup["4096"]["speedup"],
                "staleness_p99_s": round(staleness_p99_s, 6),
            }
            del rf_eng
        except Exception as e:   # noqa: BLE001 — the record must still emit
            read_fanout = {"error": repr(e)}
            read_delivery_ops_per_sec = None

    if _want("small-window ack"):
        _phase("small-window ack")
        # --- small-window ack latency (VERDICT r4 weak #6) -----------------------
        # ack_p50/p99 at 64- and 256-doc windows with TWO concurrent clients
        # per doc; the explicit budget: an ack blocks on ZERO device reads
        # (sequencing + durable append are host work, the merge dispatches
        # async), so its floor is pure host time.
        small_window_ack = {}
        for nd in (64, 256):
            se = StringServingEngine(n_docs=nd, capacity=256,
                                     batch_window=10 ** 9, compact_every=10 ** 9,
                                     sequencer="native")
            sdocs = [f"sw{nd}-{i}" for i in range(nd)]
            for d in sdocs:
                se.connect(d, 1)
                se.connect(d, 2)
            srows = np.array([se.doc_row(d) for d in sdocs], np.int32)
            OW = 8
            # alternating clients per op column; per-client contiguous cseqs
            cl_plane = np.broadcast_to(
                (np.arange(OW, dtype=np.int32) % 2) + 1, (nd, OW))
            samples = []
            base = np.zeros(2, np.int64)
            for c in range(25):
                cseq = np.empty((nd, OW), np.int32)
                for k in range(OW):
                    cseq[:, k] = base[k % 2] + (k // 2) + 1
                base += OW // 2
                planes, _ = typing_storm(nd, OW, seed=40 + c)
                tb = time.perf_counter()
                res = se.ingest_planes(srows, cl_plane, cseq, cseq,
                                       planes["kind"], planes["a0"],
                                       planes["a1"], "abcd")
                samples.append(time.perf_counter() - tb)
                assert res["nacked"] == 0
            samples = samples[1:]   # first sample compiles the OW shape
            samples.sort()
            snap = se.metrics.snapshot()
            small_window_ack[str(nd)] = {
                "p50_ms": round(samples[len(samples) // 2] * 1000, 2),
                "p99_ms": round(samples[-1] * 1000, 2),
                # WHERE the ack wall goes (stage p50s over this window
                # size's samples): C++ sequencing vs host plane prep/pack
                # vs the async device dispatch vs the durable append — the
                # split that shows whether a regression is sequencer, host
                # packing, or log I/O before anyone stares at a profiler
                "split_p50_ms": {
                    k.replace("ingest_", "").replace("_ms", ""):
                        round(snap.get(f"{k}_p50_ms", 0), 3)
                    for k in ("ingest_seq_ms", "ingest_prep_ms",
                              "ingest_pack_ms", "ingest_dispatch_ms",
                              "ingest_log_ms")},
                # the same p50 wall as a per-op budget across the window
                "per_op_us": round(
                    samples[len(samples) // 2] * 1e6 / (nd * OW), 2),
            }
            del se
        small_window_ack["budget"] = {
            "device_reads": 0, "device_round_trips": 0,
            "note": "ack = C++ sequencing + durable append + async device "
                    "dispatch; floor is host time, no device read in the path"}

        # genuinely CONCURRENT two-submitter variant: the loops above
        # measure an UNCONTENDED ack (one thread, engine idle between
        # windows); production front doors race. Two submitter threads
        # share the 256-doc engine behind one lock (the ingest path is
        # single-writer by design — the lock IS the sequencer front door);
        # each sample is submit-intent → ack wall, so time queued behind
        # the other submitter's window is counted in the percentile.
        se2 = StringServingEngine(n_docs=256, capacity=256,
                                  batch_window=10 ** 9,
                                  compact_every=10 ** 9, sequencer="native")
        s2docs = [f"sw2-{i}" for i in range(256)]
        for d in s2docs:
            se2.connect(d, 1)
            se2.connect(d, 2)
        s2rows = np.array([se2.doc_row(d) for d in s2docs], np.int32)
        OW = 8
        ins_kind = np.full((256, OW), int(OpKind.STR_INSERT), np.int32)
        zeros_p = np.zeros((256, OW), np.int32)
        se2.ingest_planes(  # warmup: compiles this engine's window shape
            s2rows, np.ones((256, OW), np.int32),
            np.broadcast_to(np.arange(1, OW + 1, dtype=np.int32), (256, OW)),
            zeros_p, ins_kind, zeros_p, zeros_p, "abcd")
        front_door = threading.Lock()
        conc_walls: list = []
        conc_lock = threading.Lock()
        conc_start = threading.Barrier(2)
        N_WIN2 = 12

        def _submitter(cid, cseq_base):
            cl_pl = np.full((256, OW), cid, np.int32)
            for c in range(N_WIN2):
                cseq = np.broadcast_to(
                    np.arange(cseq_base + c * OW + 1,
                              cseq_base + c * OW + OW + 1,
                              dtype=np.int32), (256, OW))
                if c == 0:
                    conc_start.wait()
                tb = time.perf_counter()
                with front_door:
                    res = se2.ingest_planes(s2rows, cl_pl, cseq, zeros_p,
                                            ins_kind, zeros_p, zeros_p,
                                            "abcd")
                dt = time.perf_counter() - tb
                assert res["nacked"] == 0
                with conc_lock:
                    conc_walls.append(dt)

        _subs = [threading.Thread(target=_submitter, args=(1, OW)),
                 threading.Thread(target=_submitter, args=(2, 0))]
        for _t2 in _subs:
            _t2.start()
        for _t2 in _subs:
            _t2.join()
        conc_walls.sort()
        small_window_ack["256_two_submitters"] = {
            "p50_ms": round(conc_walls[len(conc_walls) // 2] * 1000, 2),
            "p99_ms": round(conc_walls[-1] * 1000, 2),
            "windows": len(conc_walls),
            "note": "two front-door threads racing one engine lock; each "
                    "wall includes queueing behind the other submitter"}
        del se2

    if _want("ack latency"):
        _phase("ack latency")
        # --- ingest→ack latency distribution ------------------------------------
        # Per-call wall time of ingest_planes (sequencing + durable append +
        # device dispatch — the ack path) on small 8-op windows.
        lat_engine = StringServingEngine(
            n_docs=n_docs, capacity=serve_capacity, batch_window=10 ** 9,
            compact_every=1, sequencer="native")
        for d in docs:
            lat_engine.connect(d, 1)
        lrows = np.array([lat_engine.doc_row(d) for d in docs], np.int32)
        OW = 8
        lat_samples = []
        lcseq_base = 0
        lat_client = np.ones((n_docs, OW), np.int32)
        # unmeasured warmup: the OW-shaped dispatch compiles here, not in a
        # timed sample (a compile in the first sample would masquerade as p99)
        wplanes, _ = typing_storm(n_docs, OW, seed=99)
        lat_engine.ingest_planes(
            lrows, lat_client,
            np.broadcast_to(np.arange(1, OW + 1, dtype=np.int32),
                            (n_docs, OW)),
            np.broadcast_to(np.arange(1, OW + 1, dtype=np.int32),
                            (n_docs, OW)),
            wplanes["kind"], wplanes["a0"], wplanes["a1"], "abcd")
        _ = np.asarray(lat_engine.store.state.overflow)
        lcseq_base = OW
        # stall guard: a window >10x the running median is a host
        # hiccup, not ack latency — re-sample a FRESH window (seqs are
        # consumed; the stalled one stays excluded) and count the retry so
        # the record shows how often the run had to dodge
        ack_retries = 0
        c = 0
        while len(lat_samples) < 24:
            planes, _ = typing_storm(n_docs, OW, seed=c)
            c += 1
            cseq = np.broadcast_to(
                np.arange(lcseq_base + 1, lcseq_base + OW + 1,
                          dtype=np.int32), (n_docs, OW))
            lcseq_base += OW
            tb = time.perf_counter()
            lat_engine.ingest_planes(lrows, lat_client, cseq, cseq,
                                     planes["kind"], planes["a0"],
                                     planes["a1"], "abcd")
            dt = time.perf_counter() - tb
            med = (sorted(lat_samples)[len(lat_samples) // 2]
                   if lat_samples else None)
            if med is not None and dt > 10 * med and ack_retries < 8:
                ack_retries += 1
                continue
            lat_samples.append(dt)
        lat_samples.sort()
        ack_p50_ms = float(lat_samples[len(lat_samples) // 2] * 1000)
        ack_p99_ms = float(lat_samples[-1] * 1000)  # max of 24 ≈ p99 bound

        # honesty check: an independently-merged doc (per-op message path on a
        # fresh store) must read identically to the engine's columnar result
        for check_doc in (0, n_docs // 2):
            ref_store = TensorStringStore(n_docs=1, capacity=serve_capacity)
            msgs = []
            seq = 1  # join consumed seq 1
            for kind, a0, a1, cseq, refp in serve_batches:
                for o in range(ops_per_batch):
                    seq += 1
                    if kind[check_doc, o] == OpKind.STR_INSERT:
                        contents = {"mt": "insert", "kind": 0,
                                    "pos": int(a0[check_doc, o]), "text": "abcd"}
                    else:
                        contents = {"mt": "remove",
                                    "start": int(a0[check_doc, o]),
                                    "end": int(a1[check_doc, o])}
                    msgs.append((0, SequencedDocumentMessage(
                        doc_id="x", client_id=1, client_seq=int(cseq[check_doc, o]),
                        ref_seq=int(refp[check_doc, o]), seq=seq,
                        min_seq=int(refp[check_doc, o]), type=MessageType.OP,
                        contents=contents)))
            ref_store.apply_messages(msgs)  # one batched device apply
            want = ref_store.read_text(0)
            got = engine.read_text(docs[check_doc])
            assert got == want, f"serving divergence doc {check_doc}"

    if _want("apply-window latency"):
        _phase("apply-window latency")
        # --- latency phase: per-window apply latency -----------------------------
        # The op axis is time-sequential: each step of the 64-op scan is one
        # apply window over all 10k docs. Sample individually-synced dispatches;
        # worst sample / windows-per-dispatch bounds per-window device latency
        # from above — and hence its p99 (see module docstring for exactly what
        # this does and does not measure).
        # Stall-proofing (a transient 63 s host stall once printed
        # apply_window_worst_ms: 983 with nothing in the record saying
        # the HOST stalled): unmeasured warmup, each sample is the MEDIAN of 3
        # dispatches, and a sample >10x the running median is re-sampled
        # (bounded) with the retry count recorded. A worst_ms that survives
        # all three layers is device latency, not a scheduler hiccup — and if
        # the stall is persistent the sample is kept but FLAGGED.
        wstate = StringState.create(n_docs, capacity)
        _ = np.asarray(wstate.count)
        wstate = apply_fn(wstate, *batches[0])
        _ = np.asarray(wstate.overflow)
        del wstate
        samples = []
        apply_window_retries = 0
        apply_window_stalled = False
        c = 0
        while len(samples) < 8:
            inner = []
            for _r in range(3):
                state = StringState.create(n_docs, capacity)
                _ = np.asarray(state.count)
                tb = time.perf_counter()
                state = apply_fn(state, *batches[c % n_batches])
                _ = np.asarray(state.overflow)
                inner.append(time.perf_counter() - tb)
            dt = sorted(inner)[1]       # median-of-3: one hiccup never wins
            med = sorted(samples)[len(samples) // 2] if samples else None
            if med is not None and dt > 10 * med:
                if apply_window_retries < 8:
                    apply_window_retries += 1
                    continue
                apply_window_stalled = True
            samples.append(dt)
            c += 1
        worst_ms = float(max(samples) * 1000 / ops_per_batch)
        apply_window_p50_ms = float(
            sorted(samples)[len(samples) // 2] * 1000 / ops_per_batch)

    # -------------------------------------------------- reconnect storm
    # the resilience plane under measured load (ISSUE 9): a seeded soak
    # (socket kills + injected sequencer crashes + service restarts over
    # resilient clients) reported as throughput, reconnect latency
    # percentiles, resubmit/dup-ack counts — and the invariant-violation
    # count the perf sentinel gates on (any nonzero fails --check)
    if _want("reconnect_storm"):
        _phase("reconnect_storm")
        try:
            import importlib.util as _ilu
            _spec = _ilu.spec_from_file_location(
                "chaos_soak", _os.path.join(
                    _os.path.dirname(_os.path.abspath(__file__)),
                    "tools", "chaos_soak.py"))
            _soak = _ilu.module_from_spec(_spec)
            _spec.loader.exec_module(_soak)
            _storm = _soak.run_soak(seed=123, steps=300, n_clients=4,
                                    restarts=3, kill_p=0.02, crash_p=0.005)
            reconnect_storm = {
                "ops_per_sec": round(
                    _storm["ops_acked"] / max(_storm["elapsed_s"], 1e-9), 1),
                "ops_acked": _storm["ops_acked"],
                "reconnects": _storm["reconnects"],
                "reconnect_p50_ms": _storm["reconnect_p50_ms"],
                "reconnect_p99_ms": _storm["reconnect_p99_ms"],
                "resubmits": _storm["resubmits"],
                "dup_acked": _storm["dup_acked"],
                "socket_kills": _storm["socket_kills"],
                "restarts": _storm["restarts"],
                "faultpoint_fires": _storm["faultpoint_fires"],
                "invariant_violations": _storm["violations"],
            }
        except Exception as e:   # noqa: BLE001 — the record must still emit
            reconnect_storm = {"error": repr(e), "invariant_violations": -1}

        # -------------------------------------------------- overload storm
        # the admission plane under 2x-capacity load (ISSUE 16): the
        # multi-tenant simulator's quick profile — one abusive tenant at 5x
        # budget, AIMD policy live — reported as goodput/shed/latency, and
        # the two correctness counts the perf sentinel hard-gates on:
        # invariant_violations (exactly-once/order audits) and silent_drops
    if _want("overload_storm"):
        _phase("overload_storm")
        try:
            import importlib.util as _ilu
            _spec = _ilu.spec_from_file_location(
                "tenant_sim", _os.path.join(
                    _os.path.dirname(_os.path.abspath(__file__)),
                    "tools", "tenant_sim.py"))
            _tsim = _ilu.module_from_spec(_spec)
            # registered BEFORE exec: its dataclasses resolve string
            # annotations through sys.modules[cls.__module__]
            sys.modules["tenant_sim"] = _tsim
            _spec.loader.exec_module(_tsim)
            # lenient latency/goodput floors (shared bench boxes vary);
            # the sentinel gates only the correctness counts
            _rep = _tsim.run_sim(seed=123, duration_s=1.2, slo_ms=1000.0,
                                 goodput_min=0.3, quick=True)
            overload_storm = {
                "goodput_ratio": _rep["goodput_ratio"],
                "admitted_ack_p99_ms": _rep["admitted_ack_p99_ms"],
                "shed_ratio": _rep["shed_ratio"],
                "shed_total": _rep["shed_total"],
                "throttled_frames": _rep["throttled_frames"],
                "throttle_resubmits": _rep["throttle_resubmits"],
                "abusive_throttled": _rep["abusive_throttled"],
                "abusive_shed": _rep["abusive_shed"],
                "ops_offered": _rep["ops_offered"],
                "ops_acked": _rep["ops_acked"],
                "policy_breach_ticks": _rep["policy"]["breach_ticks"],
                "policy_min_scale": _rep["policy"]["min_scale"],
                "silent_drops": _rep["silent_drops"],
                "invariant_violations": _rep["violations"],
                "gate_failures": _rep["gate_failures"],
            }
        except Exception as e:   # noqa: BLE001 — the record must still emit
            overload_storm = {"error": repr(e), "invariant_violations": -1,
                              "silent_drops": -1}

        # ------------------------------------------------------- durability
        # the recovery ladder under the clock (ISSUE 10): summary load + tail
        # replay timed at ladder depth 0 (newest generation verifies) and
        # depth 1 (newest rotted → fall back a rung, replay a longer tail),
        # then an offline scrub of the phase's own spill — chain_breaks is
        # the integrity count the perf sentinel hard-gates on
    if _want("durability"):
        _phase("durability")
        try:
            import random as _random
            import tempfile as _tempfile
            from fluidframework_tpu.runtime.summarizer import (
                SummaryGenerationStore as _GenStore,
            )
            from fluidframework_tpu.server.oplog import PartitionedLog as _PLog
            from fluidframework_tpu.server.serving import (
                StringServingEngine as _StrEngine,
            )
            from fluidframework_tpu.utils.faultpoints import (
                corrupt_bitflip as _corrupt_bitflip,
            )
            import importlib.util as _ilu2
            _spec2 = _ilu2.spec_from_file_location(
                "log_scrub", _os.path.join(
                    _os.path.dirname(_os.path.abspath(__file__)),
                    "tools", "log_scrub.py"))
            _scrub = _ilu2.module_from_spec(_spec2)
            _spec2.loader.exec_module(_scrub)
            with _tempfile.TemporaryDirectory(prefix="bench_dur_") as _dd:
                _spill = _os.path.join(_dd, "spill")
                _gen_dir = _os.path.join(_dd, "gens")
                _os.mkdir(_spill)
                _dlog = _PLog(2, _spill, "deltas")
                _deng = _StrEngine(n_docs=4, capacity=1024, batch_window=16,
                                   n_partitions=2, log=_dlog)
                _store = _GenStore(_gen_dir, keep=3)
                _deng.connect("bench-doc", 1)
                _n_dur = 512
                _seq = 0
                for _i in range(_n_dur):
                    _m, _nk = _deng.submit(
                        "bench-doc", 1, _i + 1, 0,
                        {"mt": "insert", "kind": 0, "pos": 0, "text": "x"})
                    _seq = _m.seq
                    # two generations: mid-run and at 3/4 — depth 1 falls
                    # back to the older one and replays the longer tail
                    if _i in (_n_dur // 2 - 1, _n_dur * 3 // 4 - 1):
                        _deng.flush()
                        _store.save(_deng.summarize(), _seq)
                _deng.flush()
                _dlog.close()

                def _ladder_trial():
                    _t0 = time.perf_counter()
                    _s, _sq, _depth = _store.load_latest()
                    _rlog = _PLog.recover(2, _spill, "deltas")
                    _e2 = _StrEngine.load(_s, _rlog)
                    _e2.flush()
                    _dt = (time.perf_counter() - _t0) * 1000
                    _rlog.close()
                    return _dt, _depth

                _trials0 = [_ladder_trial() for _ in range(5)]
                # scrub the spill while it is pristine: the ladder trials are
                # read-only, so any break here is a writer-path bug
                _dsum = _scrub.summarize_reports(_scrub.scrub_tree(_spill))
                _gens = _store.generations()
                _corrupt_bitflip(
                    _os.path.join(_gen_dir, _store._BLOB.format(_gens[-1])),
                    _random.Random(17))
                _trials1 = [_ladder_trial() for _ in range(5)]
                _p50 = lambda ts: sorted(t for t, _ in ts)[len(ts) // 2]  # noqa: E731,E501
                durability = {
                    "recovery_ladder_ms": {
                        "depth0_p50": round(_p50(_trials0), 2),
                        "depth1_p50": round(_p50(_trials1), 2),
                    },
                    "ladder_depths": [_trials0[0][1], _trials1[0][1]],
                    "ops_replayed": _n_dur,
                    "generations_kept": len(_gens),
                    "chain_breaks": _dsum["chain_breaks"],
                    "records_scrubbed": _dsum["records"],
                }
        except Exception as e:   # noqa: BLE001 — the record must still emit
            durability = {"error": repr(e), "chain_breaks": -1}

    # observability ride-along: the unified registry's process-wide view
    # (device dispatches, jit compiles vs cache hits, oplog appends, ...)
    # plus ONE sampled span timeline from the run's newest trace, so a
    # bench record alone shows where a batch's wall time went
    from fluidframework_tpu.utils import tracing as _tracing
    from fluidframework_tpu.utils.telemetry import REGISTRY as _registry
    _tids = _tracing.TRACER.trace_ids()
    _trace_sample = None
    if _tids:
        _tid = _tids[-1]
        _trace_sample = {
            "trace_id": _tid,
            "spans": [{"name": e["name"], "dur_ms": round(e["dur"] / 1e3, 3),
                       "parent_id": e["parent_id"], "span_id": e["span_id"],
                       "args": {k: v for k, v in e.get("args", {}).items()
                                if isinstance(v, (int, float, str, bool))}}
                      for e in _tracing.TRACER.events(_tid)[:32]],
        }

    record = {
        "metric": "sharedstring_ops_per_sec_merged",
        "value": round(ops_per_sec, 1),
        "unit": "ops/s",
        "vs_baseline": round(ops_per_sec / 1_000_000, 4),
        "docs": n_docs,
        "total_ops": n_ops,
        # headline per-suite trials + band (satellite: drift visibility)
        "headline_trials": [round(t, 1) for t in headline_trials],
        "headline_variance_band": headline_band,
        "apply_window_worst_ms": round(worst_ms, 2),
        "apply_window_p50_ms": round(apply_window_p50_ms, 2),
        # stall/retry accounting: how many samples the >10x-median guard
        # re-drew, and whether a stall persisted past the retry budget
        # (a flagged run's worst_ms is a host event, not device latency)
        "apply_window_retries": apply_window_retries,
        "apply_window_stalled": apply_window_stalled,
        "ack_sample_retries": ack_retries,
        "digest_parity": digest_parity,
        "serving_ops_per_sec": round(serving_ops_per_sec, 1),
        "serving_ops_per_sec_median": round(serving_ops_per_sec_median, 1),
        "serving_trials": [round(t, 1) for t in serving_trials],
        "serving_rich_ops_per_sec": round(rich_ops_per_sec, 1),
        "serving_rich_ops_per_sec_median":
            round(rich_ops_per_sec_median, 1),
        "serving_rich_trials": [round(t, 1) for t in rich_trials],
        "serving_interval_ops_per_sec": round(interval_ops_per_sec, 1),
        # columnar-path proof: >=2 apply segments per post-warmup wave
        # means the MSN floor crossed tombstones mid-window and anchors
        # slid on-device (the old per-op fallback recorded no segments)
        "serving_interval_segments_per_wave": iv_seg_waves,
        "serving_interval_wire": interval_wire,
        "serving_interval_ops": n_iv_docs * iv_ow * iv_waves,
        "ack_small_windows": small_window_ack,
        "host_load_start_end": [round(load_start, 2),
                                round(_os.getloadavg()[0], 2)],
        # host-side wall per ingest batch, by stage (p50; device time is
        # the remainder of the batch wall — it overlaps the next batch's
        # host work): C++ sequencing / plane prep / wire packing /
        # worker-side prepack / async dispatch / durable-log append.
        # wave_wall is the PIPELINE's inter-completion gap: with stages
        # overlapped it tracks the max stage, so sum(stage p50s) >
        # wave_wall p50 is the overlap evidence the record carries.
        "ingest_stage_p50_ms": {
            eng_name: {
                k.replace("ingest_", "").replace("_ms", ""):
                    round(e.metrics.snapshot().get(f"{k}_p50_ms", 0), 1)
                for k in ("ingest_seq_ms", "ingest_prep_ms",
                          "ingest_pack_ms", "ingest_prepack_ms",
                          "ingest_dispatch_ms", "ingest_log_ms")}
            for eng_name, e in (("broadcast", engine),
                                ("rich", rich_engine),
                                ("tree", tree_eng)) if e is not None},
        "ingest_wave_wall_p50_ms": {
            eng_name: round(e.metrics.snapshot().get(
                "ingest_wave_wall_ms_p50_ms", 0), 1)
            for eng_name, e in (("broadcast", engine),
                                ("rich", rich_engine),
                                ("tree", tree_eng)) if e is not None},
        # executor occupancy/overlap from each phase's best trial
        # (overlap > 1.0 == stages genuinely ran concurrently)
        "ingest_pipeline": {"broadcast": serving_pipe_stats,
                            "rich": rich_pipe_stats,
                            "tree": tree_pipe_stats},
        "matrix_serving_ops_per_sec": round(matrix_serving_ops_per_sec, 1),
        "matrix_serving_ops_per_sec_median":
            round(matrix_trials[len(matrix_trials) // 2], 1),
        "matrix_serving_trials": [round(t, 1) for t in matrix_trials],
        "columnar_ingress_ops_per_sec":
            round(columnar_ingress_ops_per_sec, 1),
        "columnar_ingress_ops_per_sec_median":
            round(ingress_trials[len(ingress_trials) // 2], 1),
        "columnar_ingress_trials": [round(t, 1) for t in ingress_trials],
        "columnar_ingress_windows": ingress_windows,
        "columnar_ingress_pipeline": ingress_stats,
        # whole-buffer batch decode evidence (ISSUE 15): decode-stage
        # p50 per drain pass, bytes drained per pass, and which tier
        # (native libingress.so vs numpy fallback) served
        "ingress_decode_p50_ms": ingress_drain["decode_p50_ms"],
        "ingress_drained_bytes_per_pass":
            ingress_drain["bytes_per_pass_p50"],
        "ingress_drain_passes": ingress_drain["passes"],
        "ingress_decode_tier": ingress_drain["tier"],
        # live operations plane (ISSUE 17): scrape overhead of the 1 Hz
        # /metrics poller against the columnar storm, plus the stage
        # attribution's coverage (stage sum / e2e ack — 1.0 = the
        # breakdown fully explains the observed latency)
        "ops_plane": ops_plane,
        # partitioned serving (ISSUE 18): the columnar storm at 1/2/4/8
        # sequencer partitions — speedup/efficiency vs the 1-partition
        # baseline (host_cores qualifies the ratio), the per-window
        # digest-parity tap's verdict, and the declared-floor scalar
        # (best rate at >= 4 partitions) the sentinel judges
        "partition_scaling": partition_scaling,
        "partition_columnar_ops_per_sec":
            round(partition_columnar_ops_per_sec, 1)
            if partition_columnar_ops_per_sec else None,
        # read plane (ISSUE 20): encode-once fanout economics (delivery
        # ops/s at 1/64/256/1024 subscribers, the per-subscriber
        # marginal-cost ratio), generation-diff catch-up vs full-tail
        # replay at three tail lengths, and staleness p99 under the
        # write storm — plus the declared-floor scalar (delivery ops/s
        # at 1024 subscribers) the sentinel judges
        "read_fanout": read_fanout,
        "read_delivery_ops_per_sec":
            round(read_delivery_ops_per_sec, 1)
            if read_delivery_ops_per_sec else None,
        # resilience under load (ISSUE 9): the seeded reconnect storm's
        # throughput/latency plus the invariant-violation count the
        # perf sentinel gates on
        "reconnect_storm": reconnect_storm,
        # overload protection under 2x-capacity multi-tenant load
        # (ISSUE 16): goodput/shed split plus the correctness counts
        # (invariant_violations, silent_drops) the sentinel gates on
        "overload_storm": overload_storm,
        # durable-layer integrity under the clock (ISSUE 10): recovery
        # ladder p50 at depth 0/1 + the scrub's chain-break count the
        # perf sentinel hard-gates on
        "durability": durability,
        "serving_durable_ops_per_sec":
            round(durable_ops_per_sec, 1) if durable_ops_per_sec else None,
        "serving_durable_ops_per_sec_median":
            round(durable_ops_per_sec_median, 1)
            if durable_ops_per_sec_median else None,
        "serving_durable_trials": [round(t, 1) for t in durable_trials],
        "tree_serving_ops_per_sec": round(tree_ops_per_sec, 1),
        "tree_serving_ops_per_sec_median":
            round(tree_ops_per_sec_median, 1),
        "tree_serving_trials": [round(t, 1) for t in tree_trials],
        "tree_flat_serving_ops_per_sec": round(tree_flat_ops_per_sec, 1),
        "tree_flat_trials": [round(t, 1) for t in leaf_trials],
        "tree_kernel_ops_per_sec": round(tree_kernel_ops_per_sec, 1),
        "tree_kernel_trials": [round(t, 1) for t in tree_kernel_trials],
        "ack_p50_ms": round(ack_p50_ms, 1),
        "ack_p99_ms": round(ack_p99_ms, 1),
        "serving_read_ms": round(serving_read_ms, 1),
        # round-trip budgets (VERDICT r3 weak #6/#7): a read is ONE fused
        # gather+transfer (asserted via the store's device-read counter);
        # an ingest ack blocks on ZERO device reads — sequencing + the
        # durable append are host-side, the merge is dispatched async and
        # the overflow check is a deferred async copy
        "read_device_round_trips": read_rtts,
        "ack_device_round_trips": 0,
        "conflict_ops_per_sec": round(conflict_ops_per_sec, 1),
        "conflict_parity": conflict_parity,
        # unified metrics registry snapshot (counters + gauges + histogram
        # percentiles, own + attached components) and one sampled span
        # timeline — see utils.telemetry / utils.tracing
        "metrics": _registry.full_snapshot(),
        "trace_sample": _trace_sample,
        "backend": jax.default_backend(),
        # phase selector (ISSUE 19 satellite): which phases this record
        # actually measured — a --phases subset leaves the rest at their
        # zero/skipped defaults above
        "phases_run": [p for p in ALL_PHASES if p in _selected],
        "phases_skipped": [p for p in ALL_PHASES if p not in _selected],
        # capacity plane (ISSUE 19): per-phase boundary census — census
        # cost, resident host bytes at entry, peak across entry/exit
        "phase_capacity": _phase_capacity,
        "capacity_census_ms": round(max(
            (v["census_ms"] for v in _phase_capacity.values()),
            default=0.0), 2),
        "doc_resident_bytes_peak": max(
            (v.get("doc_resident_bytes_peak", v["doc_resident_bytes"])
             for v in _phase_capacity.values()), default=0),
    }

    # final health sample: feed the record's own headline numbers to the
    # SLO gauges (ack_p99_ms, digest_parity) so the scorecard judges the
    # run the way docs/OBSERVABILITY.md declares the objectives, then
    # embed the scorecard and the sentinel's verdict vs the committed
    # BENCH_r*.json trajectory. All guarded: a broken health plane
    # degrades the record, never the bench.
    _phase("health scorecard + perf sentinel")
    try:
        _registry.set_gauge("ack_p99_ms", ack_p99_ms)
        _registry.set_gauge("digest_parity",
                            1.0 if digest_parity else 0.0)
        _health.tick()
        record["slo_scorecard"] = _slo_engine.scorecard()
        record["slo_breaches"] = [
            {k: b.get(k) for k in ("slo", "series", "worst", "trace_id")}
            for b in _slo_engine.breaches]
    except Exception as e:   # noqa: BLE001
        record["slo_scorecard"] = {"error": repr(e)}
    if record["phases_skipped"]:
        # a --phases subset leaves skipped phases at their zero
        # defaults; the sentinel would read those as regressions, so it
        # only judges full sweeps
        record["sentinel"] = {"skipped": "partial run (--phases)"}
    else:
        try:
            import importlib.util as _ilu
            from pathlib import Path as _Path
            _root = _Path(__file__).resolve().parent
            _spec = _ilu.spec_from_file_location(
                "perf_sentinel", _root / "tools" / "perf_sentinel.py")
            _ps = _ilu.module_from_spec(_spec)
            _spec.loader.exec_module(_ps)
            _rounds = _ps.load_trajectory(_root)
            _rounds.append({**{k: v for k, v in record.items()
                               if isinstance(v, (int, float, bool))},
                            "_round": "current"})
            _verdicts = _ps.judge(_rounds) + _ps.judge_floors(_rounds)
            record["sentinel"] = {
                "rounds": len(_rounds) - 1,
                "regressions": [v["metric"] for v in _verdicts
                                if v["verdict"] == _ps.REGRESS],
                "improvements": [v["metric"] for v in _verdicts
                                 if v["verdict"] == _ps.IMPROVE],
                "verdicts": _verdicts,
            }
        except Exception as e:   # noqa: BLE001
            record["sentinel"] = {"error": repr(e)}

    print(json.dumps(record))


def _phases_arg(argv):
    """Extract a ``--phases LIST`` / ``--phases=LIST`` argument."""
    for i, a in enumerate(argv):
        if a == "--phases" and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith("--phases="):
            return a.split("=", 1)[1]
    return None


def main(phases=None):
    child_argv = [sys.executable, __file__, "--child"]
    if phases:
        select_phases(phases)   # fail fast on unknown names
        child_argv += ["--phases", phases]
    # one attempt, in a child so this process never touches JAX (a chip
    # belongs to one process); its rc and stderr tail pass through
    proc = subprocess.run(child_argv, capture_output=True, text=True)
    sys.stderr.write(proc.stderr[-4000:])
    lines = [l for l in proc.stdout.strip().splitlines()
             if l.startswith("{")]
    if proc.returncode == 0 and lines:
        print(lines[-1])
        return
    sys.exit(proc.returncode or 1)


if __name__ == "__main__":
    if "--child" in sys.argv:
        run(phases=_phases_arg(sys.argv))
    else:
        main(phases=_phases_arg(sys.argv))
