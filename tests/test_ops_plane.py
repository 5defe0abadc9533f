"""Live operations plane (ISSUE 17): scrape endpoint under load,
per-stage latency attribution, heavy-hitter sketch accuracy.

Covers the acceptance criteria end to end: every route of the in-process
ops endpoint answers — with bounded latency and no deadlock — while a
real columnar ingress storm is running; the Prometheus exposition
survives a STRICT scraper-grammar parse including label-value escaping
(backslash, double quote, newline) and round-trips through the live
``tools/healthz.py`` parser; the telescoping stage histograms sum to the
observed end-to-end ack latency within the 10% tolerance (exactly, by
construction); and the Space-Saving sketch honors its overestimate/
guaranteed-tracking bounds against exact counts on Zipf traffic.
"""

import importlib.util
import json
import os
import random
import re
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from fluidframework_tpu.server import native_deli, opsd
from fluidframework_tpu.server.opsd import (
    STAGES, OpsServer, SpaceSaving, latency_breakdown,
    observe_window_timeline,
)
from fluidframework_tpu.utils import telemetry
from fluidframework_tpu.utils.telemetry import (
    MetricsCollector, MetricsRegistry, PROM_CONTENT_TYPE,
)

pytestmark = [pytest.mark.opsplane, pytest.mark.telemetry]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tool(name):
    """Load a tools/*.py script as a module (tools/ is not a package)."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _get(url, timeout=10.0):
    """(status, content_type, body_bytes) — the scraper's eye view."""
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.headers.get("Content-Type"), resp.read()


# ----------------------------------------------------- strict exposition

#: the text-format grammar a strict scraper enforces: metric names,
#: label names, and label values where ONLY \\ \" \n escapes may carry
#: backslash / quote / newline
_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_LABEL = r'[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\\\|\\"|\\n)*"'
_SAMPLE = re.compile(
    rf"^({_NAME})(?:\{{{_LABEL}(?:,{_LABEL})*\}})? (\S+)$")
_COMMENT = re.compile(rf"^# (?:TYPE {_NAME} (?:counter|gauge|histogram)"
                      rf"|HELP {_NAME} .*)$")


class TestPrometheusExposition:
    def _nasty_registry(self):
        reg = MetricsRegistry()
        reg.inc("ops_ingested", 41)
        reg.set_gauge("queue_depth", 7.0)
        reg.observe("ack_ms", 3.0)
        reg.observe("ack_ms", 9.0)
        coll = MetricsCollector()
        # every character class the escaper must handle, in one value
        coll.inc("ingress_ops", 5)
        reg.attach("alfred", coll,
                   labels={"door": 'col"umn\\ar\nx', "shard": "3"})
        # attachments are weakrefs: pin the collector to the registry's
        # lifetime or it vanishes from the exposition mid-test
        reg._test_pin = coll
        return reg

    def test_every_line_matches_strict_scraper_grammar(self):
        text = self._nasty_registry().render_prometheus()
        assert text.endswith("\n")
        for line in text.splitlines():
            if line.startswith("#"):
                assert _COMMENT.match(line), line
                continue
            m = _SAMPLE.match(line)
            assert m, f"unparseable sample line: {line!r}"
            float(m.group(2))   # the value must be a number

    def test_label_escaping_is_exactly_the_three_escapes(self):
        text = self._nasty_registry().render_prometheus()
        [line] = [ln for ln in text.splitlines()
                  if ln.startswith("ingress_ops")]
        assert r'door="col\"umn\\ar\nx"' in line
        assert "\n" not in line  # the raw newline never leaks

    def test_histogram_emits_sum_count_and_monotone_buckets(self):
        reg = self._nasty_registry()
        lines = reg.render_prometheus().splitlines()
        assert "ack_ms_sum 12.0" in lines
        assert "ack_ms_count 2" in lines
        cums = [int(ln.rsplit(" ", 1)[1]) for ln in lines
                if ln.startswith("ack_ms_bucket")]
        assert cums == sorted(cums) and cums[-1] == 2

    def test_healthz_parser_round_trips_escaped_labels(self):
        healthz = _tool("healthz")
        text = self._nasty_registry().render_prometheus()
        metrics, kinds = healthz.parse_prometheus(text)
        assert metrics["ops_ingested"] == 41.0
        assert kinds["ops_ingested"] == "counter"
        assert metrics["queue_depth"] == 7.0
        assert kinds["queue_depth"] == "gauge"
        # the component key carries the UNESCAPED label value back
        key = 'alfred{door=col"umn\\ar\nx,shard=3}.ingress_ops'
        assert metrics[key] == 5.0
        # histogram accumulators survive as counters, buckets dropped
        assert metrics["ack_ms_sum"] == 12.0
        assert kinds["ack_ms_sum"] == "counter"
        assert not any(k.endswith("_bucket") for k in metrics)


# --------------------------------------------------- stage attribution

class TestStageAttribution:
    def _observe(self, reg, stage_ms):
        """Observe one synthetic window whose 8 stage durations (ms)
        are exactly ``stage_ms``."""
        t = 100.0
        crossings = [t]
        for ms in stage_ms:
            t += ms * 1e-3
            crossings.append(t)
        tl = {"t_rx": crossings[0], "t_drain0": crossings[1],
              "admit_ms": stage_ms[2], "t_ready": crossings[3]}
        marks = {"pack1": crossings[4], "seq1": crossings[5],
                 "disp1": crossings[6], "log1": crossings[7]}
        observe_window_timeline(tl, marks, crossings[8], registry=reg)

    def test_stages_sum_to_e2e_exactly(self):
        reg = MetricsRegistry()
        rng = random.Random(17)
        for _ in range(50):
            self._observe(reg, [rng.uniform(0.1, 5.0) for _ in STAGES])
        bd = latency_breakdown(reg)
        assert bd["windows"] == 50
        assert set(bd["stages"]) == set(STAGES)
        # the acceptance tolerance is 10%; the construction is exact
        assert bd["e2e_mean_ms"] > 0
        assert abs(bd["stage_sum_ms"] - bd["e2e_mean_ms"]) \
            <= 0.10 * bd["e2e_mean_ms"]
        assert abs(bd["coverage"] - 1.0) < 1e-6
        assert abs(sum(r["share"] for r in bd["stages"].values())
                   - 1.0) < 1e-6

    def test_known_durations_land_in_their_stages(self):
        reg = MetricsRegistry()
        ms = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0]
        self._observe(reg, ms)
        for name, want in zip(STAGES, ms):
            h = reg.histograms[f"stage_{name}_ms"]
            assert h.n == 1
            assert abs(h.mean - want) < 1e-6, name
        assert abs(reg.histograms["stage_e2e_ack_ms"].mean
                   - sum(ms)) < 1e-6

    def test_skewed_marks_clamp_never_negative(self):
        reg = MetricsRegistry()
        tl = {"t_rx": 10.0, "t_drain0": 9.0,       # rx after drain?!
              "admit_ms": 5000.0, "t_ready": 10.001}
        marks = {"pack1": 10.0005, "seq1": 10.2,
                 "disp1": 10.1, "log1": 10.3}      # disp before seq
        observe_window_timeline(tl, marks, 10.25, registry=reg)
        for name in STAGES:
            h = reg.histograms[f"stage_{name}_ms"]
            assert h.n == 1 and h.sum_ms >= 0.0, name
        bd = latency_breakdown(reg)
        assert abs(bd["coverage"] - 1.0) < 1e-6

    def test_missing_marks_degrade_to_zero_width_stages(self):
        reg = MetricsRegistry()
        tl = {"t_rx": 1.0, "t_drain0": 1.001, "t_ready": 1.002}
        observe_window_timeline(tl, {}, 1.010, registry=reg)
        bd = latency_breakdown(reg)
        assert abs(bd["e2e_mean_ms"] - 10.0) < 1e-6
        assert abs(bd["coverage"] - 1.0) < 1e-6
        # everything after t_ready collapses into the ack stage
        assert abs(reg.histograms["stage_ack_ms"].mean - 8.0) < 1e-6


# ------------------------------------------------------- space-saving

def _check_contract(sk, exact):
    """The Space-Saving contract against exact counts, and the sketch's
    own invariants; returns ``{key: (est, err)}``."""
    total = sum(exact.values())
    assert sk.total == total
    rows = {key: (est, err) for key, est, err in sk.top(sk.capacity)}
    assert len(rows) == len(sk) <= sk.capacity
    for key, (est, err) in rows.items():
        true = exact.get(key, 0)
        # est overestimates by <= err
        assert true <= est <= true + err, (key, true, est, err)
    # every key above the total/capacity threshold IS tracked
    for key, true in exact.items():
        if true > total / sk.capacity:
            assert key in rows, (key, true, total / sk.capacity)
    # every offered op sits in exactly one counter, and a full sketch's
    # minimum (the next victim's floor) cannot exceed the mean
    assert sum(est for est, _ in rows.values()) == total
    if len(rows) == sk.capacity:
        assert min(est for est, _ in rows.values()) <= total / sk.capacity
    ests = [est for _, est, _ in sk.top(sk.capacity)]
    assert ests == sorted(ests, reverse=True)
    return rows


class _CountedKey:
    """A key that counts its ``__hash__`` calls: what an offer costs in
    the sketch's own terms, no clock involved."""
    hashes = 0

    def __init__(self, i):
        self.i = i

    def __hash__(self):
        _CountedKey.hashes += 1
        return hash(self.i)

    def __eq__(self, other):
        return self.i == other.i


class TestSpaceSaving:
    @pytest.mark.parametrize("capacity", [8, 64, 256])
    @pytest.mark.parametrize("weight", [1, 4, "mixed"])
    def test_zipf_accuracy_vs_exact_counts(self, weight, capacity):
        rng = random.Random(7)
        n_keys, draws = 400, 30_000
        weights = [1.0 / (k + 1) ** 1.2 for k in range(n_keys)]
        sk = SpaceSaving(capacity=capacity)
        exact = {}
        for _ in range(draws):
            key = rng.choices(range(n_keys), weights=weights)[0]
            n = rng.choice((1, 2, 4, 7)) if weight == "mixed" else weight
            exact[key] = exact.get(key, 0) + n
            sk.offer(key, n)
        assert len(sk) == capacity
        assert sk.evictions > 0
        _check_contract(sk, exact)
        # the sketch's top-10 contains the true top-5 heavy hitters (a
        # sketch of 8 owes only the keys above total/8, held above: the
        # fifth key's share of this stream is below that)
        if capacity >= 64:
            true_top5 = sorted(exact, key=exact.get, reverse=True)[:5]
            sketch_top10 = [key for key, _, _ in sk.top(10)]
            assert set(true_top5) <= set(sketch_top10)

    def test_all_miss_stream_evicts_on_every_offer(self):
        # the door's ordinary case: far more documents than entries
        sk = SpaceSaving(capacity=256)
        keys = [(f"doc-{i}", f"client-{i % 8}") for i in range(10_240)]
        exact = {}
        for _ in range(3):
            for key in keys:
                exact[key] = exact.get(key, 0) + 4
                sk.offer(key, 4)
        assert len(sk) == 256
        assert sk.evictions == 3 * len(keys) - 256
        _check_contract(sk, exact)

    def test_a_miss_costs_a_few_hashes_not_a_scan(self):
        sk = SpaceSaving(capacity=256)
        for i in range(256):
            sk.offer(_CountedKey(i), 1 + i % 3)
        assert len(sk) == 256
        missing = [_CountedKey(1000 + i) for i in range(1000)]
        _CountedKey.hashes = 0
        for key in missing[:500]:
            sk.offer(key)
        sk.offer_many(missing[500:], [1 + i % 4 for i in range(500)])
        assert sk.evictions == 1000 and len(sk) == 256
        # a scan of the entries for their minimum is 256 hashes an offer
        assert _CountedKey.hashes <= 8 * 1000, _CountedKey.hashes

    @pytest.mark.parametrize("capacity", [8, 256])
    def test_offer_many_equals_the_offers_in_sequence(self, capacity):
        rng = random.Random(11)
        keys = [("doc-%d" % int(rng.paretovariate(0.5)), "t%d" % (i % 2))
                for i in range(6000)]
        counts = [rng.choice((1, 1, 3, 8)) for _ in keys]
        one, many = SpaceSaving(capacity), SpaceSaving(capacity)
        exact = {}
        for key, n in zip(keys, counts):
            one.offer(key, n)
            exact[key] = exact.get(key, 0) + n
        for i in range(0, len(keys), 750):       # a part at a time
            many.offer_many(keys[i:i + 750], iter(counts[i:i + 750]))
        assert many.total == one.total == sum(counts)
        assert many.evictions == one.evictions > 0
        assert many.top(capacity) == one.top(capacity)
        _check_contract(many, exact)

    def test_bounded_memory_and_concurrent_offers(self):
        sk = SpaceSaving(capacity=16)
        seen = []

        def pound(seed):
            r = random.Random(seed)
            for i in range(1000):
                sk.offer(("doc-%d" % r.randrange(200), "t"))
                part = [("doc-%d" % r.randrange(200), "t")
                        for _ in range(4)]
                sk.offer_many(part, [1, 2, 3, 4])
                if i % 50 == 0:
                    rows = sk.top(16)
                    seen.append(len(rows) <= 16 and
                                [c for _, c, _ in rows] == sorted(
                                    (c for _, c, _ in rows), reverse=True))
        threads = [threading.Thread(target=pound, args=(s,))
                   for s in range(4)]
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads)
        assert sk.total == 4 * 1000 * 11
        assert len(sk) == 16 and all(seen) and len(seen) == 4 * 20
        # a lost update would leave an op outside every counter
        assert sum(c for _, c, _ in sk.top(16)) == sk.total
        sk.clear()
        assert len(sk) == 0 and sk.total == 0 and sk.evictions == 0
        assert sk.top(16) == []


# ------------------------------------------------------- the endpoint

class TestOpsServerRoutes:
    def test_all_routes_serve_and_ticker_ticks(self):
        reg = MetricsRegistry()
        reg.inc("ops_ingested", 3)
        sk = SpaceSaving(capacity=8)
        sk.offer(("d0", "acme"), 5)
        with OpsServer(registry=reg, tick_interval_s=0.05) as ops:
            ops.add_hotdocs(sk)
            status, ctype, body = _get(ops.url + "/metrics")
            assert status == 200 and ctype == PROM_CONTENT_TYPE
            assert b"ops_ingested 3" in body
            status, ctype, body = _get(ops.url + "/healthz")
            assert status == 200 and "application/json" in ctype
            health = json.loads(body)
            assert {"ok", "rows", "ticks", "uptime_s"} <= set(health)
            hot = json.loads(_get(ops.url + "/debug/hotdocs?k=5")[2])
            assert hot["top"][0] == {"doc": "d0", "tenant": "acme",
                                     "count": 5, "err": 0}
            for route in ("/debug/flights", "/debug/trace",
                          "/debug/latency"):
                status, _, body = _get(ops.url + route)
                assert status == 200
                json.loads(body)
            deadline = time.time() + 5.0
            while ops.ticks < 2 and time.time() < deadline:
                time.sleep(0.02)
            assert ops.ticks >= 2           # the ticker thread is live
            assert ops.store.names()        # ... and sampling
            assert reg.gauges["hotdoc_top_count"] == 5.0

    def test_unknown_route_404s_with_route_list(self):
        with OpsServer(registry=MetricsRegistry(),
                       tick_interval_s=0) as ops:
            try:
                urllib.request.urlopen(ops.url + "/nope", timeout=5)
                raise AssertionError("expected 404")
            except urllib.error.HTTPError as e:
                assert e.code == 404
                assert "/metrics" in json.loads(e.read())["routes"]


# ------------------------------------------- the storm (acceptance)

needs_native = pytest.mark.skipif(not native_deli.available(),
                                  reason="native sequencer unavailable")


@needs_native
class TestScrapeUnderIngestStorm:
    @pytest.mark.parametrize("sketch_capacity", [256, 8])
    def test_live_scrape_during_columnar_storm(self, sketch_capacity):
        from fluidframework_tpu.server.columnar_ingress import (
            ColumnarAlfred, ColumnarClient, _OP_DTYPE,
        )
        from fluidframework_tpu.server.serving import StringServingEngine
        eng = StringServingEngine(n_docs=32, capacity=256,
                                  batch_window=10 ** 9,
                                  sequencer="native")
        srv = ColumnarAlfred(eng, window_min_rows=4,
                             window_ms=2.0)
        # 8: more (doc, tenant) keys than entries, a door's ordinary case
        srv.hotdocs.capacity = sketch_capacity
        srv.start_in_thread()
        ops = srv.start_ops(tick_interval_s=0.1)
        routes = ("/metrics", "/healthz", "/debug/hotdocs",
                  "/debug/latency", "/debug/flights", "/debug/trace")
        stop = threading.Event()
        lat, errors = [], []

        def scraper():
            i = 0
            while not stop.is_set():
                route = routes[i % len(routes)]
                i += 1
                t0 = time.perf_counter()
                try:
                    status, _, _ = _get(ops.url + route)
                    assert status == 200
                except Exception as e:          # noqa: BLE001
                    errors.append((route, repr(e)))
                lat.append(time.perf_counter() - t0)
                time.sleep(0.01)

        threads = [threading.Thread(target=scraper, daemon=True)
                   for _ in range(2)]
        try:
            for t in threads:
                t.start()
            # a door that carves wide needs a quarter of the windows
            # for the same waves: more waves, so the storm still outlasts
            # a few scrapes
            n_clients, docs_per, waves = 3, 4, 30
            clients = []
            for c in range(n_clients):
                cl = ColumnarClient("127.0.0.1", srv.port)
                docs = [f"c{c}-d{j}" for j in range(docs_per)]
                cl.join(docs)
                clients.append((cl, docs))
            for w in range(waves):
                for cl, docs in clients:
                    rows = [cl.rows[d] for d in docs]
                    o = np.zeros(docs_per, _OP_DTYPE)
                    o["row"] = rows
                    o["cseq"] = w + 1
                    cl.send_ops([f"t{w}."], o)
            for cl, docs in clients:
                acked = 0
                while acked < docs_per * waves:
                    resp = cl.recv_json()
                    assert resp["t"] == "acks", resp
                    acked += len(resp["acks"])
                cl.close()
            # how long the storm lasted is the machine's business: the
            # scrapers get their ten samples, of which the storm's are some
            t_end = time.monotonic() + 10
            while len(lat) < 10 and time.monotonic() < t_end:
                time.sleep(0.01)
            stop.set()
            for t in threads:
                t.join(timeout=10)
            # the endpoint never deadlocked and stayed bounded while
            # the ingest loop was storming
            assert not errors, errors[:3]
            assert len(lat) >= 10
            assert max(lat) < 5.0
            # acceptance: the per-stage breakdown sums to the observed
            # e2e ack latency within 10% on the storm workload
            bd = json.loads(_get(ops.url + "/debug/latency")[2])
            assert bd["windows"] > 0
            assert bd["e2e_mean_ms"] > 0
            assert abs(bd["stage_sum_ms"] - bd["e2e_mean_ms"]) \
                <= 0.10 * bd["e2e_mean_ms"]
            assert set(bd["stages"]) == set(STAGES)
            # the drain-pass sketch saw exactly the ingested ops, and
            # every one of them sits in some entry's count
            hot = json.loads(_get(ops.url + "/debug/hotdocs?k=64")[2])
            assert hot["total_ops"] == srv.ops_ingested \
                == n_clients * docs_per * waves
            assert sum(r["count"] for r in hot["top"]) \
                == srv.ops_ingested
            assert hot["capacity"] == sketch_capacity
            n_keys = n_clients * docs_per
            if n_keys <= sketch_capacity:
                # all (doc, tenant) keys fit: no evictions, err == 0
                assert hot["tracked"] == n_keys
                assert hot["evictions"] == 0
                assert all(r["err"] == 0 for r in hot["top"])
            else:
                assert hot["tracked"] == sketch_capacity
                assert hot["evictions"] >= n_keys - sketch_capacity
                assert all(r["count"] - r["err"] <= waves
                           for r in hot["top"])
            # the ticker publishes the counter beside hotdoc_tracked
            want = f"hotdoc_evictions {float(hot['evictions'])}"
            deadline = time.time() + 5.0
            while time.time() < deadline:
                metrics = _get(ops.url + "/metrics")[2].decode()
                if want in metrics.splitlines():
                    break
                time.sleep(0.05)
            assert want in metrics.splitlines(), [
                ln for ln in metrics.splitlines() if "hotdoc" in ln]
        finally:
            stop.set()
            srv.stop()

    def test_healthz_cli_live_mode_against_storm_server(self, capsys):
        from fluidframework_tpu.server.columnar_ingress import (
            ColumnarAlfred, ColumnarClient, _OP_DTYPE,
        )
        from fluidframework_tpu.server.serving import StringServingEngine
        healthz = _tool("healthz")
        eng = StringServingEngine(n_docs=8, capacity=128,
                                  batch_window=10 ** 9,
                                  sequencer="native")
        srv = ColumnarAlfred(eng, window_min_rows=1,
                             window_ms=2.0).start_in_thread()
        ops = srv.start_ops(tick_interval_s=0.05)
        try:
            cl = ColumnarClient("127.0.0.1", srv.port)
            cl.join(["d0"])
            o = np.zeros(1, _OP_DTYPE)
            o["row"] = cl.rows["d0"]
            o["cseq"] = 1
            cl.send_ops(["x"], o)
            assert cl.recv_json()["t"] == "acks"
            cl.close()
            rc = healthz.main(["--url", ops.url,
                               "--interval", "0.05", "--polls", "3"])
            out = capsys.readouterr().out
            assert "SLO" in out            # the scorecard rendered
            assert "ops_" in out           # live sparklines rendered
            assert rc in (0, 1)            # a judged verdict, not a crash
        finally:
            srv.stop()
