"""One run of one cell: the server in this process (it owns the chip), the
generator in its own, a measured window that is a slice of steady
streaming, and the checks on what that window's path produced.

``run_cell`` is the whole of it; ``run.py`` only parses the command line
and looks the cell's files up by name. The program is imported inside the
functions, after the generator has been started and the process pinned.
"""

import collections
import json
import os
import queue
import shutil
import subprocess
import sys
import threading
import time
import traceback

import numpy as np

from . import faults, reduce, roofline, trace, wire
from .refdoc import RefDoc
from .traffic import Layout, Vocabulary

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "perfbench_out")
now = time.monotonic


def note(*a) -> None:
    print("perfbench:", *a, file=sys.stderr, flush=True)


class GenProc:
    """The generator's process and its line protocol."""

    def __init__(self, config_file, traffic_file, seed, cores):
        self.p = subprocess.Popen(
            [sys.executable, "-m", "perfbench.gen", "--config", config_file,
             "--traffic", traffic_file, "--seed", str(seed)],
            cwd=ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        if cores:
            os.sched_setaffinity(self.p.pid, cores)
        self.q = queue.Queue()
        self.t = threading.Thread(target=self._read, daemon=True)
        self.t.start()

    def _read(self):
        for line in self.p.stdout:
            self.q.put(json.loads(line))
        self.q.put({"ev": "eof"})

    def send(self, **cmd):
        self.p.stdin.write((json.dumps(cmd) + "\n").encode())
        self.p.stdin.flush()

    def wait(self, ev, timeout):
        try:
            msg = self.q.get(timeout=timeout)
        except queue.Empty:
            raise TimeoutError(f"generator: no {ev!r} in {timeout}s")
        if msg["ev"] != ev:
            raise RuntimeError(f"generator: wanted {ev!r}, got {msg}")
        return msg

    def close(self):
        if self.p.poll() is None:
            try:
                self.send(cmd="quit")
                self.p.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                self.p.kill()
                self.p.wait()
        self.p.stdin.close()
        self.p.stdout.close()


class Spans:
    """The benchmark's own spans around the calls into each layer: a
    wall-clock sum and a count per name, and a ``TraceAnnotation`` so that
    the profiler's trace carries them on the device's clock."""

    def __init__(self):
        from jax.profiler import TraceAnnotation
        self._ann = TraceAnnotation
        self.s = collections.defaultdict(float)
        self.n = collections.defaultdict(int)

    def wrap(self, obj, attr, name, after=None):
        fn, ann, s, n = getattr(obj, attr), self._ann, self.s, self.n

        def spanned(*a, **k):
            t = time.perf_counter()
            with ann(name):
                r = fn(*a, **k)
            s[name] += time.perf_counter() - t
            n[name] += 1
            if after is not None:
                after(a, k, r)
            return r

        setattr(obj, attr, spanned)


def pin():
    """Give the generator two cores of its own where the machine has them."""
    cores = sorted(os.sched_getaffinity(0))
    if len(cores) < 4:
        return cores, []
    srv, gen = cores[:-2], cores[-2:]
    os.sched_setaffinity(0, srv)
    return srv, gen


def run_cell(cell: dict, config: dict, traffic: dict, files: dict, *,
             seed: int, seconds: float, trace_on: bool, t_start: float,
             end_to_end: list, per_layer: list, require_tpu: bool = True,
             plant: str = "") -> dict:
    """Run one cell once; returns the result object (the last stdout line
    is the caller's). ``files``: {"config": path, "traffic": path};
    ``end_to_end`` and ``per_layer``: the cell's entries of BENCHMARK.json
    (``traffic.select_metrics``), each read by its file under ``metrics/``.
    ``plant`` names a fault of ``faults.py`` (controls and tests only)."""
    all_cores = os.sched_getaffinity(0)
    srv_cores, gen_cores = pin()
    gen = GenProc(files["config"], files["traffic"], seed, gen_cores)
    try:
        return _run(cell, config, traffic, gen, srv_cores, gen_cores,
                    seed=seed, seconds=seconds, trace_on=trace_on,
                    t_start=t_start, end_to_end=end_to_end,
                    per_layer=per_layer, require_tpu=require_tpu,
                    plant=plant)
    finally:
        gen.close()
        os.sched_setaffinity(0, all_cores)


def sharded_over(state, devs, n_docs: int) -> bool:
    """Whether every plane of a store's state lies on exactly these
    devices, ``n_docs / len(devs)`` document rows on each."""
    import jax
    want, rows = sorted(d.id for d in devs), n_docs // len(devs)
    return all(
        sorted(sh.device.id for sh in x.addressable_shards) == want
        and all(sh.data.shape[0] == rows for sh in x.addressable_shards)
        for x in jax.tree.leaves(state))


def _run(cell, config, traffic, gen, srv_cores, gen_cores, *, seed, seconds,
         trace_on, t_start, end_to_end, per_layer, require_tpu, plant):
    import jax
    # every program of the door compiles in under a second, which JAX's
    # default threshold would keep out of the persistent cache: with it at
    # 0 a cell compiles in its first run only
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    note(f"device {device}; cores: server {srv_cores}, generator "
         f"{gen_cores or 'shared (fewer than 4 cores)'}")
    if (require_tpu and dev.platform != "tpu") \
            or device["count"] < cell["chips"]:
        raise SystemExit(f"perfbench: needs {cell['chips']} TPU chip(s), "
                         f"found {device}")
    ready = gen.wait("ready", 60)
    note(f"generator pid {ready['pid']} on cores {ready['cores']}; jax up "
         f"{now() - t_start:.1f}s since start")

    from fluidframework_tpu.native.build import TARGETS, ensure_built
    from fluidframework_tpu.server.columnar_ingress import ColumnarAlfred
    from fluidframework_tpu.server.native_oplog import NativePartitionedLog
    from fluidframework_tpu.server.serving import StringServingEngine

    dep = config["deployment"]
    # the cell's chips: one, or a doc mesh over the first ``chips`` of them
    mesh = None
    if cell["chips"] > 1:
        if dep["n_docs"] % cell["chips"]:
            raise ValueError(f"{dep['n_docs']} documents do not divide "
                             f"over {cell['chips']} chips")
        from fluidframework_tpu.parallel.sharded import make_doc_mesh
        mesh = make_doc_mesh(cell["chips"])
    devs = [dev] if mesh is None else list(mesh.devices.flat)
    lay = Layout(dep["n_docs"], traffic["connections"],
                 traffic["multi_writer_docs"])
    for target in TARGETS:
        ensure_built(target)
    log_dir = os.path.join(OUT, cell["name"], "oplog")
    shutil.rmtree(log_dir, ignore_errors=True)
    log = NativePartitionedLog(log_dir, dep["engine"]["log_partitions"])
    engine = StringServingEngine(
        n_docs=dep["n_docs"], capacity=dep["capacity"],
        n_props=dep["n_props"], log=log,
        compact_every=dep["engine"]["compact_every"],
        sequencer=dep["engine"]["sequencer"], mesh=mesh)
    interpret = dep["pallas"] == "interpret"
    engine.store.pallas = "interpret" if interpret else "auto"
    door = ColumnarAlfred(engine, decode=dep["decode"], **dep["door"]
                          ).start_in_thread()
    note(f"server up {now() - t_start:.1f}s since start")

    try:
        return _serve(cell, config, traffic, gen, door, engine, log, devs,
                      mesh, device, lay, seed=seed, seconds=seconds,
                      trace_on=trace_on, t_start=t_start,
                      end_to_end=end_to_end, per_layer=per_layer,
                      require_tpu=require_tpu, plant=plant)
    finally:
        door.stop()
        log.close()


def _serve(cell, config, traffic, gen, door, engine, log, devs, mesh, device,
           lay, *, seed, seconds, trace_on, t_start, end_to_end, per_layer,
           require_tpu, plant):
    """Set-up traffic, the window and the checks, on a server that is up."""
    import jax
    from fluidframework_tpu.utils.telemetry import REGISTRY
    dep = config["deployment"]
    interpret = dep["pallas"] == "interpret"
    spans, windows_seen = None, []
    if trace_on:
        spans = _instrument(door, engine, windows_seen, len(devs))
    armed = threading.Event()
    if plant:
        faults.PLANTS[plant](door, engine, log, armed)
        note(f"CONTROL: fault {plant!r} planted")

    def counters():
        ex = door._executor
        hist = engine.metrics.histograms.get("ingest_ticket_wall_ms")
        c = {"windows": door.windows_flushed, "ops": door.ops_ingested,
             "drain_passes": door.drain_passes,
             "drained_bytes": door.drained_bytes,
             "compiles": REGISTRY.counters.get("jax_compiles", 0),
             "unpack_variants": len(engine.store.unpack_variants),
             "ticket_wall_ms.sum": hist.sum_ms if hist else 0.0,
             "ticket_wall_ms.n": hist.n if hist else 0}
        for k, v in ex.stats()["stage_busy_ms"].items():
            c[f"stage_busy_ms.{k}"] = v
        if spans is not None:
            for k in list(spans.s):
                c[f"span.{k}.s"], c[f"span.{k}.n"] = spans.s[k], spans.n[k]
        return c

    # ---- set-up traffic: fill, every height on purpose, then the mix ----
    gen.send(cmd="start", port=door.port)
    joined = gen.wait("joined", 300)
    note(f"joined {dep['n_docs']} documents "
         f"({now() - t_start:.1f}s since start)")
    gen.wait("filled", 600)
    note(f"filled ({now() - t_start:.1f}s)")
    swept = gen.wait("swept", 1200)
    note(f"programs dispatched on purpose (height x columns x payload-table "
         f"size), ms for a compaction cycle of each: {swept['ms']} "
         f"({now() - t_start:.1f}s)")
    # each is an unpack program plain and, one column wide, fused as well:
    # what the sweep was sent for and the store never saw
    met = set(map(_shape, engine.store.unpack_variants))
    missed = [(h, cols, tab, fused) for h, cols, tab in swept["programs"]
              for fused in ((False, True) if cols == 1 else (False,))
              if (h, cols, tab, fused) not in met]
    if missed:
        note(f"WARNING: swept and not met (height, columns, table, fused): "
             f"{missed}")
    gen.wait("streaming", 120)
    warm = traffic["warmup"]
    t_stream = now()
    last = (counters()["compiles"], door.windows_flushed)
    while True:
        time.sleep(0.02)
        c = counters()
        if c["compiles"] != last[0]:
            last = (c["compiles"], c["windows"])
        quiet = c["windows"] - last[1]
        if quiet >= warm["quiet_windows"] \
                and now() - t_stream >= warm["min_stream_s"]:
            break
        if now() - t_stream > warm["cap_s"]:
            note(f"WARNING: warm-up cap reached, {quiet} quiet windows")
            break
    variants0 = set(engine.store.unpack_variants)

    # ---- the window ------------------------------------------------------
    t0 = now() + 0.25
    t1 = t0 + seconds
    gen.send(cmd="window", t0=t0, t1=t1)
    time.sleep(max(t0 - now(), 0))
    armed.set()
    c0, setup_s = counters(), t0 - t_start
    tr_dir, tr_span = os.path.join(OUT, cell["name"], "trace"), None
    if trace_on:
        # a few seconds in the middle: traces are large and tracing slows
        # the host; the counters' deltas span the whole window
        shutil.rmtree(tr_dir, ignore_errors=True)
        tr_len = min(traffic["trace_seconds"], seconds * 0.6)
        time.sleep(max(t0 + (seconds - tr_len) / 2 - now(), 0))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tr_dir, profiler_options=opts)
        # the windows dispatched while the profiler records, and not in
        # the seconds it takes to start and to write its trace out
        # (several, and more on several chips): those the trace's
        # module times are of
        a = now()
        time.sleep(tr_len)
        tr_span = (a, now())
        jax.profiler.stop_trace()
    time.sleep(max(t1 - now(), 0))
    c1 = counters()
    found = gen.wait("drained", seconds + 180)
    # the fullest of the cell's chips is the cell's peak
    mems = [dict(d.memory_stats() or {}, id=d.id) for d in devs]
    peak = max((m["peak_bytes_in_use"] for m in mems
                if m.get("peak_bytes_in_use") is not None), default=None)
    new_variants = sorted(set(engine.store.unpack_variants) - variants0)
    note(f"window {seconds}s: {found['acked_in_window']} ops acked, "
         f"compiles in window {c1['compiles'] - c0['compiles']}"
         + (f", new unpack programs {new_variants}" if new_variants else "")
         + f"; generator cpu share {found.get('cpu_share')}, frames late "
         f"p95 {found.get('late_p95_ms')} ms max {found.get('late_max_ms')}"
         f" ms; latency sample {found['acked_in_window']} ops, p50/p95/p99 "
         f"{found.get('ack_p50_ms')}/{found.get('ack_p95_ms')}/"
         f"{found.get('ack_p99_ms')} ms, mean {found.get('ack_mean_ms')} ms, "
         f"over 100 ms {found.get('ack_over_100ms_share')} %; ops sent and "
         f"unacked at open "
         f"{found['unacked_at_open']}, at close {found['unacked_at_close']}")
    # ---- correct ----------------------------------------------------------
    compared = {}
    try:
        _check(compared, cell, config, traffic, lay, gen, door, engine, log,
               found, joined, seed, interpret, devs, mesh)
    except Exception:
        # a server that falls over under the checks (a failed pipeline, a
        # log that no longer reloads) is not correct; what was compared
        # before it fell stays
        note("the checks did not run to their end:\n"
             + traceback.format_exc())
        compared["checks_aborted"] = (1, 0)
    correct = all(v <= lim for v, lim in compared.values())

    # ---- metrics: raw readings, each metric read by its own file ---------
    result = {"correct": bool(correct),
              "attempted": found["acked_in_window"]
              + sum(found["failures"].values()),
              "failed": sum(found["failures"].values()),
              "metrics": {}, "device": dict(
                  device, memory_peak_bytes=peak, memory=[
                      {k: m.get(k) for k in ("id", "peak_bytes_in_use",
                                             "bytes_in_use")} for m in mems])}
    raw = {f"d.{k}": c1[k] - c0[k] for k in c1}
    raw.update({"setup_s": setup_s, "window_s": found["window_s"],
                "window_ms": found["window_s"] * 1e3,
                "acked": found["acked_in_window"],
                "peak_hbm_bytes": peak})
    raw.update({f"gen.{k}": v for k, v in found.items()
                if isinstance(v, (int, float))})
    if trace_on:
        red = trace.reduce_dir(tr_dir, rehearsal=not require_tpu,
                               devices=[d.id for d in devs])
        raw.update(red["raw"])
        inside = [w for w in windows_seen if tr_span[0] <= w[0] < tr_span[1]]
        if require_tpu:     # a rehearsal's CPU has no peaks, and no share
            raw.update(roofline.least_seconds(
                inside, engine.store.state, dep["n_docs"], device["kind"]))
        result["device"].update(busy_s=red["busy_s"],
                                window_s=red["window_s"])
        result["breakdown"] = red["breakdown"]
    for m in per_layer if trace_on else end_to_end:
        v = reduce.read_metric(m["name"], raw)
        if v is not None:
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    note("end to end: " + ", ".join(
        f"{m['name']}={reduce.read_metric(m['name'], raw)}"
        for m in end_to_end))
    result["programs"] = {"swept": len(swept["programs"]),
                          "swept_not_met": missed,
                          "new_in_window": list(map(_shape, new_variants))}
    # times the generator copied its record of ops inside the window, its
    # clients' acks unread meanwhile: 0 in a sound run
    result["notes"] = {"grew_in_window": found["grew_in_window"]}
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    return result


def _shape(variant) -> tuple:
    """(height, columns, payload-table size, fused) of one of the store's
    ``unpack_variants`` (``string_store.apply_planes``'s static key, in
    its order: R, O, ..., fuse_compact at 6, ..., tab_n last)."""
    return (variant[0], variant[1], variant[-1], bool(variant[6]))


def _instrument(door, engine, windows_seen, chips):
    """Spans from the benchmark's files around the calls into each layer
    (traced runs only), and a record of each window as it is dispatched:
    (when, rows, ops, fused zamboni, distinct rows since the last one),
    the three counts by shard (``roofline.by_shard``)."""
    spans = Spans()
    touched = np.zeros(engine.n_docs, bool)
    rx = {"passes": door.drain_passes}

    def after_drain(_a, _k, _r):
        if door.drain_passes != rx["passes"]:
            rx["passes"] = door.drain_passes
            tl = door._pass_tl
            spans.s["door.rx_wait"] += tl["t_drain0"] - tl["t_rx"]
            spans.n["door.rx_wait"] += 1

    def after_dispatch(a, _k, _r):
        w = a[0]
        touched[w.rows] = True
        fused = bool(w.compact_due)
        rows, ops = roofline.by_shard(w.rows, engine.n_docs, chips,
                                      w.n_valid)
        windows_seen.append((
            now(), rows, ops, fused,
            touched.reshape(chips, -1).sum(axis=1).tolist() if fused
            else [0] * chips))
        if fused:
            touched[:] = False

    spans.wrap(door, "_drain", "door.drain", after_drain)
    spans.wrap(door, "_build_windows", "door.build_windows")
    spans.wrap(door, "_fan_acks", "door.fan_acks")
    spans.wrap(engine, "_ingest_prepare", "engine.prepare")
    spans.wrap(engine, "_ingest_sequence", "engine.sequence")
    spans.wrap(engine, "_ingest_dispatch", "engine.dispatch", after_dispatch)
    spans.wrap(engine, "_ingest_log", "engine.log")
    spans.wrap(engine.store, "apply_planes", "store.apply_planes")
    return spans


def _check(cmp, cell, config, traffic, lay, gen, door, engine, log, found,
           joined, seed, interpret, devs, mesh):
    """Everything compared, into ``cmp``, each number beside its limit
    (all exact: 0).

    The ack stream (door, sequencer) is judged by the generator as the
    acks arrive; the served state (store, kernel) against the plain
    reference's replay of the acked stream; durability by reading every
    acked op back from the log and by a reload from summary + log tail."""
    from fluidframework_tpu.server.native_oplog import NativePartitionedLog
    from fluidframework_tpu.server.serving import StringServingEngine
    dep = config["deployment"]
    fails = dict(found["failures"])
    cmp["acks_failed"] = (sum(fails.values())
                          + (0 if found["all_acked"] else 1), 0)
    if sum(fails.values()):
        note(f"ack failures: {fails} {found['notes']}")

    # the guarantees the configuration states
    use, tile, interp = engine.store._pallas_choice()
    weak = [name for name, ok in (
        ("native sequencer", type(engine.deli).__name__
         == "NativeDeliAdapter"),
        ("native partitioned log", isinstance(engine.log,
                                              NativePartitionedLog)
         and engine.log.n_partitions == dep["engine"]["log_partitions"]),
        ("native decode", door.drain_stats()["tier"] == "native"),
        ("pallas kernel", use and interp == interpret),
        ("props specialisation", bool(engine.store._has_props)
         == bool(config["wire"]["props"])),
        ("pipelined executor", door.pipeline_depth
         == dep["door"]["pipeline_depth"]),
        ("state sharded over the cell's chips", sharded_over(
            engine.store.state, devs, dep["n_docs"])),
    ) if not ok]
    if weak:
        note(f"guarantees weakened: {weak}")
    cmp["guarantees_weakened"] = (len(weak), 0)
    door._executor.drain(120.0)
    cmp["overflowed_docs"] = (int(engine.store.overflowed().sum()), 0)
    note(f"slots in use: max {int(engine.store.slot_usage().max())} of "
         f"{dep['capacity']}")

    # summary, then a tail of frames that only the log holds
    summary = engine.summarize()
    gen.send(cmd="tail")
    tail = gen.wait("tail_done", 300)
    cmp["acks_failed"] = (cmp["acks_failed"][0] + sum(
        tail["failures"].values()) - sum(fails.values()), 0)
    door._executor.drain(120.0)

    rng = np.random.default_rng([seed, 4242])
    shared = list(joined["shared_rows"])
    names = {engine.doc_row(f"doc-{i}"): f"doc-{i}"
             for i in range(dep["n_docs"])}
    tail_rows = sorted(set(tail["rows"]))
    solo = [r for r in rng.permutation(dep["n_docs"]).tolist()
            if r not in shared][:traffic["checked_docs"]]
    # every multi-writer document, a draw from the seed, and some of the
    # documents whose last ops only the log's tail holds
    sample = sorted(set(shared + solo + tail_rows[::max(
        len(tail_rows) // 8, 1)]))
    path = os.path.join(OUT, cell["name"], "acked.npz")
    gen.send(cmd="report", path=path, sample=sample)
    gen.wait("reported", 300)
    rep = np.load(path)
    cmp["door_ops_minus_sent"] = (
        abs(int(door.ops_ingested) - int(rep["all_sum"][0])), 0)

    # every acked op is read back from the log, as it was sent
    cmp["log_differs"] = (_log_differs(log, engine, config, rep["all_sum"]),
                          0)

    # the served documents against the reference's replay
    texts_tab = Vocabulary(config).texts
    props_tab = config["wire"]["props"] or []
    ops, seqs, clients = rep["ops"], rep["seq"], rep["client"]
    order = np.lexsort((seqs, ops["row"]))
    ops, seqs, clients = ops[order], seqs[order], clients[order]
    bounds = np.flatnonzero(np.diff(ops["row"].astype(np.int64))) + 1
    refs = {}
    t_ref = now()
    for chunk in np.split(np.arange(len(ops)), bounds):
        row = int(ops["row"][chunk[0]])
        doc = RefDoc(lay.n_joins(names[row]))
        try:
            for i in chunk.tolist():
                o = ops[i]
                k, t = int(o["kind"]), int(o["tidx"])
                doc.apply(int(seqs[i]), int(clients[i]), int(o["ref"]), k,
                          int(o["a0"]), int(o["a1"]),
                          texts_tab[t] if k == wire.INS else
                          props_tab[t] if k == wire.ANN else None)
        except (ValueError, IndexError) as e:
            # the acked stream itself is broken (a gap, a seq never given)
            note(f"row {row}: no replay of its acked stream: {e}")
            doc = None
        refs[row] = doc
    note(f"reference replayed {len(ops)} ops of {len(refs)} documents in "
         f"{now() - t_ref:.1f}s")

    def served_differs(eng):
        bad_text = bad_props = 0
        for row in sample:
            if row in refs and refs[row] is None:
                bad_text += 1
                continue
            ref = refs.get(row)
            want = ref.text() if ref else ""
            got = eng.read_text(names[row])
            if got != want:
                bad_text += 1
                continue
            rp = ref.props() if ref else []
            # the two ends of every run of like properties, so that a
            # mark missed or misplaced shows; a draw of them from the seed
            ends = [p for p in range(len(rp)) if p == 0 or p == len(rp) - 1
                    or rp[p] != rp[p - 1] or rp[p] != rp[p + 1]]
            if len(ends) > traffic["checked_positions"]:
                ends = rng.choice(ends, traffic["checked_positions"],
                                  replace=False).tolist()
            bad_props += sum(eng.get_properties(names[row], pos) != rp[pos]
                             for pos in ends)
        return bad_text, bad_props

    cmp["docs_text_differs"], cmp["props_differ"] = (
        (v, 0) for v in served_differs(engine))
    served_len = np.asarray(engine.store.visible_lengths())
    cmp["lengths_differ"] = (int((
        served_len[rep["visible_rows"]] != rep["visible_len"]).sum()), 0)

    # what a reload from the summary and the log's tail reproduces
    revived = StringServingEngine.load(
        summary, log, mesh=mesh, sequencer=dep["engine"]["sequencer"])
    revived.store.pallas = engine.store.pallas
    if not sharded_over(revived.store.state, devs, dep["n_docs"]):
        note("guarantees weakened: the reload is not on the cell's chips")
        cmp["guarantees_weakened"] = (cmp["guarantees_weakened"][0] + 1, 0)
    # documents the tail did not touch come back from the summary bit for
    # bit; those it touched were merged again from the log, by another
    # path than the door's, and are held to the reference like the rest
    same = np.asarray(revived.store.digests()) \
        == np.asarray(engine.store.digests())
    same[tail_rows] = True
    cmp["reload_digests_differ"] = (int((~same).sum()), 0)
    cmp["reload_lengths_differ"] = (int((np.asarray(
        revived.store.visible_lengths()) != served_len).sum()), 0)
    bad_text, bad_props = served_differs(revived)
    cmp["reload_docs_differ"] = (bad_text + bad_props, 0)
    note(f"reload: summary + {len(tail['rows'])} tail ops from the log")


def _log_differs(log, engine, config, want) -> int:
    """Read every partition of the durable log back and compare the set
    of sequenced ops it holds with the set the clients saw acked: the
    difference in count, plus one if the contents' checksums differ."""
    text_idx = {t: i for i, t in enumerate(Vocabulary(config).texts)}
    prop_idx = {json.dumps(p, sort_keys=True): i
                for i, p in enumerate(config["wire"]["props"] or [])}
    cols = collections.defaultdict(list)
    for p in range(log.n_partitions):
        for rec in log.read(p):
            if not hasattr(rec, "tidx") or rec.tidx is None:
                continue
            rows = np.asarray([engine.doc_row(d) for d in rec.doc_ids],
                              np.int64)[np.asarray(rec.doc)]
            kind = np.asarray(rec.kind, np.int64)
            tmap = np.asarray([text_idx.get(t, 255)
                               for t in rec.texts or [""]], np.int64)
            pmap = np.asarray([prop_idx.get(json.dumps(p, sort_keys=True),
                                            255)
                               for p in rec.props or []] or [0], np.int64)
            tidx = np.asarray(rec.tidx, np.int64)
            vocab = np.where(kind == wire.INS, tmap[np.minimum(tidx,
                                                          len(tmap) - 1)],
                             np.where(kind == wire.ANN,
                                      pmap[np.minimum(tidx, len(pmap) - 1)],
                                      0))
            for k, v in (("row", rows), ("seq", rec.seq),
                         ("client", rec.client), ("cseq", rec.client_seq),
                         ("kind", kind), ("a0", rec.a0),
                         ("a1", np.where(kind == wire.INS, 0, rec.a1)),
                         ("tidx", vocab)):
                cols[k].append(np.asarray(v, np.int64))
    if not cols:
        return int(want[0]) or 1
    got = wire.stream_checksum(*(np.concatenate(cols[k]) for k in (
        "row", "seq", "client", "cseq", "kind", "a0", "a1", "tidx")))
    return abs(int(got[0]) - int(want[0])) + int(got[1] != want[1])
