"""Read plane (ISSUE 20): encode-once observer fanout + generation-diff
catch-up.

Four surfaces under test:

* catch-up parity fuzz — ``build_generation_diff`` between two summary
  generations, applied over the FROM base plus the TO tail, must
  converge byte-identically with a full summary load across all four
  engine families (the acceptance gate).
* hub semantics — encode-once byte sharing, whole-window byte-budget
  shedding (park + gap notice + resume), retained-ring resubscribe
  replay and the ``catchup_needed`` signal when the ring is too short.
* the wire loop — every family's sequenced windows delivered through
  the real socket door and decoded by the real client
  (``ResilientObserver``): string batches as columnar ``B``/``R``
  frames, tree batches as binary ``T`` frames, map/matrix as JSON.
* reconnect-mid-storm exactly-once — observers killed repeatedly while
  a writer storms; every observer must end with every op applied, zero
  window/op gaps, zero dups.
"""

import json
import random
import socket
import threading
import time

import pytest

from fluidframework_tpu.drivers.resilient import ResilientObserver
from fluidframework_tpu.server.observer import ObserverDoor, ObserverHub
from fluidframework_tpu.server.read_plane import (
    ReadPlane, ReadReplica, StalenessTracker, build_generation_diff,
    apply_generation_diff, encode_window, summary_doc_seqs,
)
from fluidframework_tpu.testing.chaos import (
    OpGen, digest, engine_class, make_engine,
)

pytestmark = pytest.mark.readplane

FAMILIES = ("string", "map", "matrix", "tree")
DOCS = [f"d{i}" for i in range(4)]


def _run_engine(family, seed, n1=40, n2=60, tail=20):
    """One engine lineage with two summary generations and a durable
    tail past the second: returns (engine, s_from, s_to, opgen)."""
    rng = random.Random(seed)
    eng = make_engine(family, n_docs=len(DOCS))
    gen = OpGen(rng, family, DOCS)
    cseq = {d: 0 for d in DOCS}

    def push(n):
        for i in range(n):
            d = DOCS[i % len(DOCS)]
            cseq[d] += 1
            _msg, nack = eng.submit(d, 1, cseq[d], 0, gen.op(d))
            assert not nack, nack
        eng.flush()

    for d in DOCS:
        eng.connect(d, 1)
    push(n1)
    s_from = eng.summarize()
    push(n2)
    s_to = eng.summarize()
    push(tail)            # the short tail both loaders must replay
    return eng, s_from, s_to


# ------------------------------------------------------ catch-up parity

@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("seed", [3, 11])
def test_catchup_parity_fuzz(family, seed):
    """diff(G-1 → G) + tail replay must converge byte-identically with
    a full load of G + tail replay — the device-computed catch-up is a
    perfect substitute for full-tail rehydration."""
    eng, s_from, s_to = _run_engine(family, seed)
    diff = build_generation_diff(family, s_from, s_to)
    e_diff = apply_generation_diff(family, diff, s_from, eng.log)
    e_full = engine_class(family).load(s_to, eng.log)
    d_diff = json.dumps(digest(e_diff, family, DOCS), sort_keys=True)
    d_full = json.dumps(digest(e_full, family, DOCS), sort_keys=True)
    assert d_diff == d_full
    # and both match the live engine (the tail really replayed)
    d_live = json.dumps(digest(eng, family, DOCS), sort_keys=True)
    assert d_diff == d_live


def test_generation_diff_needs_full_generations():
    eng, s_from, s_to = _run_engine("map", 5, tail=0)
    delta = dict(s_to)
    delta["kind"] = "delta"
    with pytest.raises(ValueError, match="FULL generations"):
        build_generation_diff("map", s_from, delta)
    with pytest.raises(ValueError, match="FULL generations"):
        build_generation_diff("map", delta, s_to)


def test_summary_doc_seqs_reads_checkpoint():
    eng, s_from, s_to = _run_engine("string", 7, tail=0)
    seqs_from = summary_doc_seqs(s_from)
    seqs_to = summary_doc_seqs(s_to)
    assert set(seqs_to) == set(DOCS)
    assert all(seqs_to[d] > seqs_from[d] for d in DOCS)


# ------------------------------------------------------- hub semantics

def test_hub_encode_once_shares_bytes():
    """The fanout contract: every subscriber's sink receives the SAME
    bytes object — one encode, N sends, zero per-subscriber copies."""
    hub = ObserverHub(tracker=StalenessTracker())
    got = [[], []]
    hub.subscribe(got[0].append)
    hub.subscribe(got[1].append)
    payload = b"window-bytes"
    wid = hub.next_wid()
    assert hub.publish(wid, payload, 3) == 2
    assert got[0][0] is payload and got[1][0] is payload


def test_hub_shed_park_resume():
    """A subscriber whose byte budget cannot take a WHOLE window is
    shed that window (gap notice, parked) and resumes via ring replay —
    never a torn frame, never a stalled publisher."""
    hub = ObserverHub(tracker=StalenessTracker())
    got = []
    ack = hub.subscribe(got.append, byte_rate=1.0, byte_burst=64.0)
    big = bytes(200)
    wid = hub.next_wid()
    assert hub.publish(wid, big, 1) == 0          # over budget: shed
    rows = hub.readers()
    assert rows[0]["parked"] and rows[0]["sheds"] == 1
    # the gap notice arrived INSTEAD of the window
    assert len(got) == 1 and len(got[0]) != len(big)
    # parked: later windows skip it entirely
    assert hub.publish(hub.next_wid(), b"x", 1) == 0
    assert len(got) == 1
    # resume replays the ring from the cursor, unparked
    assert hub.resume(ack["sid"], wid)
    assert big in got and got[-1] == b"x"
    assert not hub.readers()[0]["parked"]


def test_hub_ring_replay_and_catchup_signal():
    hub = ObserverHub(ring=4, tracker=StalenessTracker())
    payloads = [f"w{i}".encode() for i in range(8)]
    for p in payloads:
        hub.publish(hub.next_wid(), p, 1)
    # ring holds wids 5..8: a joiner at wid 6 replays 6..8
    got = []
    ack = hub.subscribe(got.append, from_wid=6)
    assert not ack["catchup_needed"]
    assert got == payloads[5:]
    # a joiner at wid 2 predates the ring: catch-up ladder territory
    got2 = []
    ack2 = hub.subscribe(got2.append, from_wid=2)
    assert ack2["catchup_needed"] and ack2["ring_from"] == 5
    assert got2 == []


def test_a_window_published_during_a_replay_arrives_after_it():
    """A resubscriber's ring replay and a concurrent publish: the live
    window must reach the sink after the replayed ones (before them the
    client counts a gap and drops the replay as dups)."""
    hub = ObserverHub(ring=8, tracker=StalenessTracker())
    for i in (1, 2, 3):
        hub.publish(hub.next_wid(), b"w%d" % i, 1)
    got, publisher = [], []

    def sink(payload):
        got.append(payload)
        if payload == b"w1":        # mid-replay, another thread publishes
            t = threading.Thread(
                target=lambda: hub.publish(hub.next_wid(), b"w4", 1))
            t.start()
            publisher.append(t)
            t.join(0.2)

    hub.subscribe(sink, from_wid=1)
    publisher[0].join(5)
    assert got == [b"w1", b"w2", b"w3", b"w4"]


def test_hub_dead_sink_unsubscribes():
    hub = ObserverHub(tracker=StalenessTracker())

    def dead(_b):
        raise OSError("gone")

    hub.subscribe(dead)
    assert hub.publish(hub.next_wid(), b"x", 1) == 0
    assert hub.stats()["subscribers"] == 0


# ----------------------------------------------------- wire delivery

def _start_plane(family, **eng_kw):
    eng = make_engine(family, **eng_kw)
    hub = ObserverHub(ring=1024, tracker=StalenessTracker())
    plane = ReadPlane(eng, hub)
    eng.attach_read_plane(plane)
    door = ObserverDoor(hub).start_in_thread()
    return eng, hub, plane, door


@pytest.mark.parametrize("family", FAMILIES)
def test_delivery_all_families(family):
    """Every family's sequenced windows reach a socket observer exactly
    once, decoded by the real client: string rides the columnar B/R
    frames, tree the binary T frames, map/matrix the JSON fallback."""
    eng, hub, plane, door = _start_plane(family)
    obs = ResilientObserver("127.0.0.1", door.port, name=family,
                            rng=random.Random(1))
    try:
        assert obs.wait_subscribed(30)
        rng = random.Random(9)
        gen = OpGen(rng, family, DOCS)
        cseq = {d: 0 for d in DOCS}
        for d in DOCS:
            eng.connect(d, 1)
        n = 24
        for i in range(n):
            d = DOCS[i % len(DOCS)]
            cseq[d] += 1
            _msg, nack = eng.submit(d, 1, cseq[d], 0, gen.op(d))
            assert not nack, nack
        eng.flush()
        assert obs.wait_ops(n, 30), (obs.ops_applied, obs.gave_up)
        assert obs.ops_applied == n
        assert obs.gaps == 0 and obs.op_gaps == 0
        assert obs.dups == 0 and obs.window_dups == 0
        # the client's per-doc cursors match the sequencer's
        for d in DOCS:
            assert obs.doc_seqs[d] == eng.deli.doc_seq(d)
    finally:
        obs.close()
        door.stop()


def test_wait_subscribed_then_every_op_is_delivered():
    """``wait_subscribed`` returns once the hub holds the subscriber:
    every window published from that instant on arrives, the first one
    included, with no sleep to cover the dial."""
    eng, hub, plane, door = _start_plane("string")
    obs = ResilientObserver("127.0.0.1", door.port, name="first",
                            rng=random.Random(2))
    try:
        assert obs.wait_subscribed(30)
        assert hub.stats()["subscribers"] == 1
        for d in DOCS:
            eng.connect(d, 1)
        n = 32
        for i in range(n):          # a window an op: 32 publishes at once
            d = DOCS[i % len(DOCS)]
            _msg, nack = eng.submit(d, 1, i // len(DOCS) + 1, 0,
                                    {"mt": "insert", "kind": 0, "pos": 0,
                                     "text": f"w{i}"})
            assert not nack, nack
            eng.flush()
        assert obs.wait_ops(n, 30), (obs.ops_applied, obs.gave_up)
        assert obs.ops_applied == n == hub.stats()["ops_published"]
        assert obs.gaps == 0 and obs.op_gaps == 0
        assert obs.dups == 0 and obs.window_dups == 0
    finally:
        obs.close()
        door.stop()


def test_wait_subscribed_is_false_on_a_dead_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()                       # nothing listens there now
    obs = ResilientObserver("127.0.0.1", port, attempts=2,
                            base_delay=0.01, dial_timeout=1.0)
    assert not obs.wait_subscribed(0.2)
    obs.close()
    assert not obs.wait_subscribed(0.05)
    assert obs.ops_applied == 0


@pytest.mark.parametrize("first_dial, from_wid", [
    ("no_window_yet", 1), ("a_torn_window", 2)])
def test_a_redial_resumes_from_the_last_whole_window(first_dial,
                                                     from_wid):
    """The socket dies (a) after the subscription and before the first
    window, (b) between a window's header and its last frame. The
    client's cursor names the last WHOLE window, 0 included, so the
    redial asks for what it lacks and no op is lost or applied twice."""
    from fluidframework_tpu.server.columnar_ingress import (
        encode_json, read_frame)

    def window(wid, seqs):
        recs = [encode_json({"t": "rec", "fmt": "json", "wid": wid,
                             "ops": [["d0", s, 1, {"n": s}]]})
                for s in seqs]
        head = encode_json({"t": "window", "wid": wid,
                            "n_ops": len(seqs), "n_frames": len(recs)})
        return head, recs

    def subscribed(next_wid):
        return encode_json({"t": "subscribed", "sid": next_wid,
                            "next_wid": next_wid, "ring_from": 1,
                            "catchup_needed": False})

    stream = ((1, [1, 2]), (2, [3, 4, 5]), (3, [6]))
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(2)
    asked = []

    def door():
        conn, _ = srv.accept()
        asked.append(json.loads(bytes(read_frame(conn)[1])))
        sent = subscribed(1)
        if first_dial == "a_torn_window":
            h1, r1 = window(*stream[0])
            h2, r2 = window(*stream[1])
            sent += h1 + b"".join(r1) + h2 + r2[0]
        conn.sendall(sent)
        conn.close()
        # the redial: the ring replays from the cursor it names
        conn, _ = srv.accept()
        sub = json.loads(bytes(read_frame(conn)[1]))
        asked.append(sub)
        conn.sendall(subscribed(4))
        for wid, seqs in stream:
            if wid >= sub.get("from_wid", 4):
                h, r = window(wid, seqs)
                conn.sendall(h + b"".join(r))
        read_frame(conn)            # the client's close
        conn.close()

    t = threading.Thread(target=door, daemon=True)
    t.start()
    got = []
    obs = ResilientObserver("127.0.0.1", srv.getsockname()[1],
                            rng=random.Random(3), base_delay=0.01,
                            on_op=lambda d, s, c, op: got.append(s))
    try:
        assert obs.wait_ops(6, 30), (obs.ops_applied, asked)
        assert "from_wid" not in asked[0]
        assert asked[1]["from_wid"] == from_wid
        assert got == [1, 2, 3, 4, 5, 6]
        assert obs.windows_applied == 3 and obs.last_wid == 3
        assert obs.gaps == 0 and obs.op_gaps == 0
        assert obs.dups == 0 and obs.window_dups == 0
    finally:
        obs.close()
        t.join(5)
        srv.close()


def test_reconnect_mid_storm_exactly_once():
    """Observers killed repeatedly while a writer storms: each redial
    resubscribes from ``last_wid + 1`` and the hub's ring replays the
    missed windows — every observer ends with every op, no gap, no dup
    (the ISSUE 20 acceptance gate)."""
    eng, hub, plane, door = _start_plane("string")
    obs = [ResilientObserver("127.0.0.1", door.port, name=f"o{i}",
                             rng=random.Random(100 + i),
                             base_delay=0.01)
           for i in range(3)]
    try:
        for d in DOCS:
            eng.connect(d, 1)
        for o in obs:
            assert o.wait_subscribed(30)
        total = 160
        cseq = {d: 0 for d in DOCS}
        stop = threading.Event()

        def storm():
            for i in range(total):
                d = DOCS[i % len(DOCS)]
                cseq[d] += 1
                eng.submit(d, 1, cseq[d], 0,
                           {"mt": "insert", "kind": 0, "pos": 0,
                            "text": f"s{i}"})
                if i % 40 == 0:
                    eng.flush()
                    time.sleep(0.01)
            eng.flush()
            stop.set()

        t = threading.Thread(target=storm)
        t.start()
        # kill every observer's socket a few times mid-storm
        for _round in range(3):
            time.sleep(0.05)
            for o in obs:
                o.kill_socket()
        t.join(30)
        assert stop.is_set()
        for o in obs:
            assert o.wait_ops(total, 30), \
                (o.name, o.ops_applied, o.reconnects, o.gave_up)
            assert o.ops_applied == total
            assert o.gaps == 0 and o.op_gaps == 0, (o.gaps, o.op_gaps)
            assert o.dups == 0 and o.window_dups == 0
            assert o.reconnects >= 1     # the storm actually bit
        assert sum(o.reconnects for o in obs) >= 3
    finally:
        for o in obs:
            o.close()
        door.stop()


def test_encode_window_empty_records():
    payload, n_ops = encode_window([], 1)
    assert n_ops == 0 and payload


# --------------------------------------------------- replica staleness

def test_read_replica_bounded_staleness():
    """A follower-fed replica drains the leader's durable tail and
    samples staleness per poll; reads from the replica then match the
    leader exactly (bounded-stale, currently caught up)."""
    leader = make_engine("string")
    for d in DOCS:
        leader.connect(d, 1)
    cseq = {d: 0 for d in DOCS}

    def push(n0, n1):
        for i in range(n0, n1):
            d = DOCS[i % len(DOCS)]
            cseq[d] += 1
            leader.submit(d, 1, cseq[d], 0,
                          {"mt": "insert", "kind": 0, "pos": 0,
                           "text": f"r{i}"})
        leader.flush()

    push(0, 12)
    s0 = leader.summarize()       # replica anchors a generation behind
    tracker = StalenessTracker()
    rep = ReadReplica(leader, family="string", summary=s0,
                      tracker=tracker)
    push(12, 24)                  # the tail the replica must drain
    n = rep.poll()
    assert n > 0
    assert rep.poll() == 0           # caught up: idle poll is free
    assert tracker.p99() >= 0.0
    d_leader = digest(leader, "string", DOCS)
    d_replica = digest(rep.engine, "string", DOCS)
    assert d_leader == d_replica


def test_default_slos_include_read_staleness():
    from fluidframework_tpu.utils.slo import default_slos
    names = {s.name for s in default_slos()}
    assert "read_staleness" in names


def test_opsd_readers_route():
    """`/debug/readers` aggregates every attached hub's census."""
    from fluidframework_tpu.server.opsd import OpsServer
    hub = ObserverHub(tracker=StalenessTracker())
    hub.subscribe(lambda b: None, name="panel")
    hub.publish(hub.next_wid(), b"w", 2)
    ops = OpsServer(port=0, tick_interval_s=0)
    ops.add_readers(hub)
    _ctype, body = ops._r_readers({})
    out = json.loads(body)
    assert out["subscribers"] == 1 and out["count"] == 1
    assert out["ops_published"] == 2
    assert out["readers"][0]["name"] == "panel"
