"""The load generator: its own process, no JAX, nothing of the program.

    python -m perfbench.gen --config C.json --traffic T.json --seed N

Talks to the door over TCP only, and to the harness over stdin/stdout in
JSON lines. Builds every frame's records from the seed (``OpMaker``),
patches the ``ref`` column at send time, stamps every ack with the time
it was received, and keeps the whole acked stream so that the checks can
be made on what the timed path itself produced.
"""

import argparse
import collections
import json
import os
import resource
import select
import socket
import sys
import time

import numpy as np

from . import wire
from .refdoc import RefDoc
from .traffic import (Layout, OpMaker, Vocabulary, cut_probability,
                      programs)

RING = 16        # the least ops of one document that may be in flight
#: entries of the record of ops (45 bytes each over the six arrays) reserved
#: at start: 200 s of the fastest cell's acks. ``np.zeros`` leaves the pages
#: untouched until an op is booked there, so this is address space, and no
#: array is copied while clients wait for the generator to read their acks
RECORD = 1 << 25
RECORD_ARRAYS = ("ops", "op_seq", "op_trecv", "op_due", "op_fid", "op_conn")
ACK_BIN_S = 0.1  # ``ack_gap_share``: the width of a bin of acks
now = time.monotonic


class Conn:
    def __init__(self, idx: int):
        self.idx = idx
        self.sock = None
        self.rx = bytearray()
        self.client_id = None
        self.inflight = 0           # frames not fully acked
        self.q = 0                  # open loop: position in the cycle


class Generator:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.cfg, self.tr, self.seed = config, traffic, seed
        dep = config["deployment"]
        self.lay = Layout(dep["n_docs"], traffic["connections"],
                          traffic["multi_writer_docs"])
        self.rich = bool(config["wire"]["props"])
        self.vocab = Vocabulary(config)
        self.texts = self.vocab.texts
        self.props = config["wire"]["props"] or None
        self.W = dep["door"]["window_min_rows"]
        # a closed loop has a document's op in every frame in flight
        self.ring = max(RING, traffic.get("frames_in_flight", 0))
        self.conns = [Conn(c) for c in range(self.lay.C)]
        self.failures = collections.Counter()
        self.notes = []
        # every op ever sent, in send order
        self.ops = np.zeros(RECORD, wire.OP_DTYPE)
        self.op_seq = np.zeros(RECORD, np.int64)
        self.op_trecv = np.zeros(RECORD, np.float64)
        self.op_due = np.zeros(RECORD, np.float64)
        self.op_fid = np.zeros(RECORD, np.int32)
        self.op_conn = np.zeros(RECORD, np.int8)
        self.n_ops = 0
        self.grew_in_window = 0     # times ``_grow`` copied the record there
        self.frame_left = []        # per frame: ops not yet acked
        self.frame_conn = []
        self.frame_late = []        # (due, seconds late) per stream frame
        self.rng_sh = np.random.default_rng([seed, 9999])
        self.t0 = self.t1 = None
        self.stop_at = None
        self.cpu = {}
        self._stdin = bytearray()
        # every read of acks lands here: a buffer of 1 MiB asked of the
        # allocator for each ``recv`` is mapped and unmapped each time
        # (glibc keeps a block that large out of its heap until one as
        # large has been freed, which the record's doublings used to do)
        self._rxbuf = memoryview(bytearray(1 << 20))

    # ------------------------------------------------------------ set-up
    def connect(self, port: int) -> None:
        name_row = {}
        for c in self.conns:
            c.sock = socket.create_connection(("127.0.0.1", port))
            c.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            names = self.lay.doc_names(c.idx)
            c.sock.sendall(wire.encode_json({"t": "join", "docs": names}))
            resp = self._recv_json(c, 120.0)
            if resp.get("t") != "joined":
                raise RuntimeError(f"join refused: {resp}")
            c.client_id = resp["client_id"]
            name_row.update(resp["rows"])
        self.place(name_row)

    def place(self, name_row: dict) -> None:
        """The generator's books, from the rows the door gave the joined
        documents."""
        lay = self.lay
        n = lay.n_docs
        self.local = np.full(n, -1, np.int64)     # row → index in its owner
        self.owner_of = np.full(n, -1, np.int64)
        shared = lay.shared_names()
        self.shared_rows = [name_row[d] for d in shared]
        self.docs = {r: RefDoc(lay.n_joins(d))
                     for d, r in zip(shared, self.shared_rows)}
        self.known = {r: {} for r in self.shared_rows}
        self.sh_sent = {}           # (conn, row, cseq) → op index
        self.sh_cseq = collections.Counter()
        self.sh_pend = collections.defaultdict(collections.deque)
        for c in self.conns:
            own = [name_row[f"doc-{c.idx * lay.P + i}"]
                   for i in range(lay.P)]
            solo = own[lay.S:] if c.idx == lay.owner else own
            c.rows = np.asarray(solo, np.int64)
            c.n = len(solo)
            c.sh = [name_row[d] for d in lay.writes_shared(c.idx)]
            self.local[c.rows] = np.arange(c.n)
            self.owner_of[c.rows] = c.idx
            c.mk = OpMaker(self.seed, c.idx, c.n, self.tr["mix"], self.rich,
                           self.vocab)
            c.ref = np.zeros(c.n, np.int64)
            c.last_seq = np.ones(c.n, np.int64)       # the join took seq 1
            c.slot = np.full((c.n, self.ring), -1, np.int64)
        self.name_row = name_row

    def _recv_json(self, c: Conn, timeout: float) -> dict:
        end = now() + timeout
        while True:
            for ftype, payload in wire.split_frames(c.rx):
                return json.loads(payload)
            if not select.select([c.sock], [], [], max(end - now(), 0))[0]:
                raise TimeoutError("no reply from the door")
            chunk = c.sock.recv(1 << 20)
            if not chunk:
                raise ConnectionError("door closed the connection")
            c.rx += chunk

    # ------------------------------------------------------------- frames
    def _grow(self, need: int) -> None:
        """Room for ``need`` more ops: nothing to do within ``RECORD``; a
        run that outlasts it doubles the six arrays, and a doubling that
        falls inside the window (the clients' acks wait for it) is
        counted."""
        if self.n_ops + need <= len(self.ops):
            return
        if self.t0 is not None and self.t0 <= now() < self.t1:
            self.grew_in_window += 1
        cap = max(2 * len(self.ops), self.n_ops + need)
        for name in RECORD_ARRAYS:
            old = getattr(self, name)
            new = np.zeros(cap, old.dtype)
            new[:len(old)] = old
            setattr(self, name, new)

    def _shared_op(self, c: Conn, row: int, fill: bool):
        """One op on a multi-writer document, valid in this writer's view
        whatever is still in flight: positions stay inside a lower bound
        of the view's length (the known sequenced prefix, less the spans
        of this writer's own removes still unacked)."""
        doc, rng, m = self.docs[row], self.rng_sh, self.tr["mix"]
        key = (c.idx, row)
        est = doc.live - sum(s for _, s in self.sh_pend[key])
        roll, roll2, u = rng.random(), rng.random(), rng.random()
        a_span = int(rng.integers(1, m["annotate_span"] + 1))
        t_char = int(self.vocab.draw(np.asarray([rng.random()]))[0])
        t_prop = int(rng.integers(0, max(len(self.props or ()), 1)))
        span = m["remove_span"]
        if fill:
            kind, tidx = wire.INS, self.vocab.fill
        elif self.rich and est >= a_span + 4 and roll < m["annotate_share"]:
            kind, tidx, span = wire.ANN, t_prop, a_span
        elif est >= span + 4 and roll2 < float(cut_probability(est, m)):
            kind, tidx = wire.REM, 0
        else:
            kind, tidx = wire.INS, t_char
        if kind == wire.INS:
            a0, a1 = int(u * (max(est, 0) + 1)), 0
        else:
            a0 = int(u * (est - span + 1))
            a1 = a0 + span
        self.sh_cseq[key] += 1
        cseq = self.sh_cseq[key]
        self.sh_pend[key].append((cseq, span if kind == wire.REM else 0))
        return (row, kind, a0, a1, tidx, cseq, doc.seq)

    def send_frame(self, c: Conn, li: np.ndarray, shared=(), fill=False,
                   due=None, table=0, frames=1) -> None:
        """One frame: an op for each of the connection's own documents
        ``li`` and for each multi-writer row in ``shared``. ``table``
        (set-up only) makes the frame's inserts use as many distinct
        characters as fill a payload table of that size; ``frames``
        (set-up only) writes that many such frames on the same documents
        in one send, so that one drain pass holds them all."""
        data, skip = [], 0
        for _ in range(frames):
            frame, skip = self._frame(c, li, shared, fill, due, table, skip)
            data.append(frame)
        c.sock.sendall(b"".join(data))
        if due is not None:
            self.frame_late.append((due, now() - due))

    def _frame(self, c: Conn, li, shared, fill, due, table, skip):
        """Draw, book and encode one frame; returns its bytes and the
        inserts the send holds so far (``_fill_table``)."""
        rows = c.rows[li]
        # a table above the least size, 8, needs more than half as many
        # distinct entries: a small frame's draw may hold fewer inserts
        solo = c.mk.make(li, rows, fill,
                         inserts=table // 2 + 1 if table > 8 else 0)
        if table:
            skip = self._fill_table(solo, table, skip)
        solo["ref"] = c.ref[li]
        sh = np.asarray([self._shared_op(c, r, fill) for r in shared],
                        wire.OP_DTYPE) if len(shared) \
            else np.zeros(0, wire.OP_DTYPE)
        ops = np.concatenate([solo, sh])
        n = len(ops)
        self._grow(n)
        fid, base = len(self.frame_left), self.n_ops
        g = np.arange(base, base + n)
        self.ops[base:base + n] = ops
        self.op_seq[base:base + n] = 0
        self.op_fid[base:base + n] = fid
        self.op_conn[base:base + n] = c.idx
        c.slot[li, solo["cseq"] % self.ring] = g[:len(solo)]
        for j, o in enumerate(sh):
            self.sh_sent[c.idx, int(o["row"]), int(o["cseq"])] = \
                base + len(solo) + j
        self.n_ops += n
        self.frame_left.append(n)
        self.frame_conn.append(c.idx)
        c.inflight += 1
        prefix, recs = wire.frame_tables(ops, self.texts, self.props)
        self.op_due[base:base + n] = now() if due is None else due
        return wire.encode_ops(prefix, recs, self.rich), skip

    def _fill_table(self, ops: np.ndarray, table: int, skip: int = 0) -> int:
        """Swap the characters of a frame's inserts (every one is a single
        character, so no length changes) for the first ``n`` of the
        alphabet in turn, ``n`` chosen so that the distinct characters and
        marks of the window pad to a payload table of ``table`` entries
        whether it holds every mark or none. ``skip``: the inserts of the
        window's earlier columns (frames on the same rows), which this
        frame's carry on from; returns it with this frame's added."""
        ins = np.flatnonzero(ops["kind"] == wire.INS)
        n = min(table - len(self.vocab.props), self.vocab.fill)
        if n > 0:
            ops["tidx"][ins] = (skip + np.arange(len(ins))) % n
        return skip + len(ins)

    # --------------------------------------------------------------- acks
    def pump(self, timeout: float) -> None:
        """Wait up to ``timeout`` for bytes from any connection (or a
        command), and account every ack that has arrived."""
        fds = [c.sock for c in self.conns] + [sys.stdin.fileno()]
        ready = select.select(fds, [], [], max(timeout, 0))[0]
        for c in self.conns:
            if c.sock not in ready:
                continue
            n = c.sock.recv_into(self._rxbuf)
            if not n:
                raise ConnectionError("door closed a connection")
            t = now()
            c.rx += self._rxbuf[:n]
            for ftype, payload in wire.split_frames(c.rx):
                self._on_frame(c, json.loads(payload), t)
        if sys.stdin.fileno() in ready:
            data = os.read(sys.stdin.fileno(), 1 << 16)
            if not data:
                raise SystemExit(0)      # the harness went away
            self._stdin += data

    def command(self):
        """The next command line from the harness, if one is complete."""
        nl = self._stdin.find(b"\n")
        if nl < 0:
            return None
        line = bytes(self._stdin[:nl])
        del self._stdin[:nl + 1]
        return json.loads(line)

    def _on_frame(self, c: Conn, msg: dict, t: float) -> None:
        if msg.get("t") != "acks":
            self.failures["frame_not_acks"] += 1
            if len(self.notes) < 5:
                self.notes.append(str(msg)[:200])
            return
        pairs = np.asarray(msg["acks"], np.int64).reshape(-1, 2)
        rows = np.asarray(msg["rows"], np.int64)
        cseq, seq = pairs[:, 0], pairs[:, 1]
        mine = self.owner_of[rows] == c.idx
        g = np.full(len(rows), -1, np.int64)
        li = self.local[rows[mine]]
        g[mine] = c.slot[li, cseq[mine] % self.ring]
        for j in np.flatnonzero(~mine).tolist():
            g[j] = self.sh_sent.get((c.idx, int(rows[j]), int(cseq[j])), -1)
        known = g >= 0
        gk = g[known]
        ok = known.copy()
        ok[known] = (self.ops["cseq"][gk] == cseq[known]) \
            & (self.ops["row"][gk] == rows[known]) \
            & (self.op_seq[gk] == 0) & (self.op_conn[gk] == c.idx)
        self.failures["ack_unknown_or_twice"] += int((~ok).sum())
        # answered, whatever the answer: the frame is no longer in flight
        go = g[ok]
        for fid, k in zip(*np.unique(self.op_fid[go], return_counts=True)):
            self.frame_left[fid] -= int(k)
            if self.frame_left[fid] == 0:
                self.conns[self.frame_conn[fid]].inflight -= 1
        self.op_seq[go] = np.where(seq[ok] > 0, seq[ok], -1)
        self.op_trecv[go] = t
        self.failures["nack"] += int((seq[ok] <= 0).sum())
        ok &= seq > 0
        # a document's sequence numbers have no gap
        mo = ok & mine
        lim = self.local[rows[mo]]
        gap = seq[mo] != c.last_seq[lim] + 1
        self.failures["seq_gap"] += int(gap.sum())
        c.last_seq[lim] = seq[mo]
        c.ref[lim] = seq[mo]
        for j in np.flatnonzero(ok & ~mine).tolist():
            self._shared_acked(int(rows[j]), int(seq[j]), int(g[j]))

    def _shared_acked(self, row: int, seq: int, gi: int) -> None:
        """Advance a multi-writer document's known prefix: its ops are
        applied in sequence order as soon as the next one's ack is in."""
        known, doc = self.known[row], self.docs[row]
        known[seq] = gi
        while doc.seq + 1 in known:
            gi = known.pop(doc.seq + 1)
            o = self.ops[gi]
            c = self.conns[int(self.op_conn[gi])]
            kind, tidx = int(o["kind"]), int(o["tidx"])
            payload = self.texts[tidx] if kind == wire.INS else \
                self.props[tidx] if kind == wire.ANN else None
            try:
                doc.apply(doc.seq + 1, c.client_id, int(o["ref"]), kind,
                          int(o["a0"]), int(o["a1"]), payload)
            except IndexError:
                self.failures["generator_op_outside_view"] += 1
                doc.seq += 1
            pend = self.sh_pend[c.idx, row]
            while pend and pend[0][0] <= int(o["cseq"]):
                pend.popleft()

    def wait_frames(self, timeout: float) -> bool:
        end = now() + timeout
        while any(c.inflight for c in self.conns):
            if now() > end:
                return False
            self.pump(min(0.25, end - now()))
        return True

    # ------------------------------------------------------------ phases
    def sweep(self) -> list:
        """Dispatch every (height, columns, payload-table size) the mix
        can meet, each through a whole compaction cycle so that its
        fused-zamboni program is met too: one frame at a time, so that
        each frame is one window. A wide window is one send of as many
        frames on the same rows as it has columns (one drain pass then
        holds that many ops for every row of a full chunk); it has no
        fused form, so it is sent twice with a lone frame between: a send
        that falls on the fused slot goes one column wide, and the lone
        window moves the next off that slot."""
        progs = programs(self.lay, self.tr, self.W, self.vocab, self.rich)
        cyc = self.cfg["deployment"]["engine"]["compact_every"]
        turn = 0
        self.sweep_ms = {}
        for h, cols, tab in progs:
            t = now()
            for frames in [1] * cyc if cols == 1 else [cols, 1, cols]:
                c = self.conns[turn % (self.lay.C - 1)]
                turn += 1
                if h > c.n:
                    raise ValueError(f"height {h} exceeds a connection")
                li = (np.arange(h) + c.q) % c.n
                c.q = (c.q + h) % c.n
                self.send_frame(c, li, table=tab, frames=frames)
                if not self.wait_frames(300.0):
                    raise TimeoutError("a set-up frame was never acked")
            self.sweep_ms[f"{h}x{cols}x{tab}"] = round((now() - t) * 1e3, 1)
        for c in self.conns:
            c.q = 0
        return progs

    def fill(self) -> None:
        """Bring every document to its target length with a few long
        inserts, all connections at once, in frames of at most a window's
        rows (a level of a drain pass is then whole such frames, so the
        heights stay in the closed set)."""
        for _ in range(self.tr["mix"]["fill_rounds"]):
            for c in self.conns:
                sh = c.sh if c.idx == self.lay.owner else ()
                for s in range(0, c.n, self.W):
                    self.send_frame(c, np.arange(s, min(s + self.W, c.n)),
                                    sh if s + self.W >= c.n else (),
                                    fill=True)
        if not self.wait_frames(300.0):
            raise TimeoutError("the fill was never acked")

    def _stream_frame(self, c: Conn, due=None) -> None:
        lay, tr = self.lay, self.tr
        if tr["loop"] == "closed":
            self.send_frame(c, np.arange(c.n), c.sh, due=due)
            return
        per = tr["ops_per_frame"]
        n_q = lay.P // per
        first = c.q == 0
        if c.idx == lay.owner:
            lo = 0 if first else c.q * per - lay.S
            hi = (c.q + 1) * per - lay.S
        else:
            lo, hi = c.q * per, (c.q + 1) * per
        c.q = (c.q + 1) % n_q
        self.send_frame(c, np.arange(lo, hi), c.sh if first else (),
                        due=due)

    def stream(self) -> None:
        """Steady traffic until told to stop: the window is a slice of it."""
        tr = self.tr
        closed = tr["loop"] == "closed"
        tick = None if closed else tr["tick_ms"] / 1000.0
        # open loop: the connections send in ``groups`` turns, a turn every
        # tick / groups, so that a turn's frames make one window
        groups = 1 if closed else tr.get("groups", 1)
        next_due, turn = now(), 0
        said = False
        while True:
            t = now()
            cmd = self.command()
            if cmd is not None:
                if cmd["cmd"] == "window":
                    self.t0, self.t1 = cmd["t0"], cmd["t1"]
                    self.stop_at = self.t1 + 0.25
                elif cmd["cmd"] == "stop":
                    self.stop_at = t
            for name, at in (("t0", self.t0), ("t1", self.t1)):
                if at is not None and t >= at and name not in self.cpu:
                    ru = resource.getrusage(resource.RUSAGE_SELF)
                    self.cpu[name] = (ru.ru_utime + ru.ru_stime, t)
            if self.stop_at is not None and t >= self.stop_at:
                return
            if closed:
                for c in self.conns:
                    while c.inflight < tr["frames_in_flight"]:
                        self._stream_frame(c)
                wait = 0.05
            else:
                while now() >= next_due:
                    for c in self.conns[turn % groups::groups]:
                        self._stream_frame(c, due=next_due)
                    next_due += tick / groups
                    turn += 1
                wait = next_due - now()
            if not said:
                say({"ev": "streaming"})
                said = True
            self.pump(wait)

    def tail(self) -> list:
        """A few frames after the summary: what a reload has to take from
        the log. Every connection, every multi-writer document. Returns
        the rows written."""
        n0 = self.n_ops
        for c in self.conns:
            self.send_frame(c, np.arange(min(self.tr["tail_rows"], c.n)),
                            c.sh)
            if not self.wait_frames(10.0):
                # no answer to a lone frame: a failure, not a reason to
                # hang the checks that follow
                self.failures["never_acked"] += int(
                    (self.op_seq[n0:self.n_ops] == 0).sum())
                for k in self.conns:
                    k.inflight = 0
                break               # the server no longer answers
        return self.ops["row"][n0:self.n_ops].tolist()

    # ------------------------------------------------------------ results
    def results(self) -> dict:
        n = self.n_ops
        seq, trecv = self.op_seq[:n], self.op_trecv[:n]
        sent_by_t1 = self.op_due[:n] <= self.t1
        never = int(((seq == 0) & sent_by_t1).sum())
        # acks of a multi-writer document that never joined its prefix
        self.failures["seq_gap"] += sum(map(len, self.known.values()))
        self.failures["never_acked"] += never
        inw = (seq > 0) & (trecv >= self.t0) & (trecv < self.t1)
        lat = (trecv[inw] - self.op_due[:n][inw]) * 1e3
        out = {"window_s": self.t1 - self.t0,
               "acked_in_window": int(inw.sum()),
               "sent_total": n, "acked_total": int((seq > 0).sum()),
               "failures": dict(self.failures), "notes": self.notes,
               "frames": len(self.frame_left)}
        due, got = self.op_due[:n], np.where(seq != 0, trecv, np.inf)
        for name, at in (("unacked_at_open", self.t0),
                         ("unacked_at_close", self.t1)):
            out[name] = int((due <= at).sum() - (got <= at).sum())
        if len(lat):
            q = np.percentile(lat, [50, 95, 99])
            out.update(ack_p50_ms=float(q[0]), ack_p95_ms=float(q[1]),
                       ack_p99_ms=float(q[2]),
                       ack_mean_ms=float(lat.mean()),
                       ack_over_100ms_share=float((lat > 100.0).mean()
                                                  * 100.0))
        late = np.asarray([l for d, l in self.frame_late
                           if self.t0 <= d < self.t1])
        if len(late):
            out["late_p95_ms"] = float(np.percentile(late, 95) * 1e3)
            out["late_max_ms"] = float(late.max() * 1e3)
            out["frames_in_window"] = len(late)
        if "t0" in self.cpu and "t1" in self.cpu:
            (c0, a), (c1, b) = self.cpu["t0"], self.cpu["t1"]
            out["cpu_share"] = (c1 - c0) / (b - a)
        gaps = ack_gap_share(trecv[inw], self.t0, self.t1)
        if gaps is not None:
            out["ack_gap_share"] = gaps
        out["grew_in_window"] = self.grew_in_window
        return out

    def report(self, path: str, sample) -> None:
        """The acked stream: the whole of it in summary form (a checksum
        of every op the log must hold), and the sampled documents' ops in
        full for the reference's replay."""
        n = self.n_ops
        ops, seq = self.ops[:n], self.op_seq[:n]
        client = np.asarray([c.client_id for c in self.conns],
                            np.int64)[self.op_conn[:n]]
        keep = np.isin(ops["row"], np.asarray(sample, np.int64))
        np.savez(path, ops=ops[keep], seq=seq[keep], client=client[keep],
                 all_sum=wire.stream_checksum(
                     ops["row"], seq, client, ops["cseq"], ops["kind"],
                     ops["a0"], ops["a1"], ops["tidx"]),
                 visible_len=np.concatenate(
                     [c.mk.length for c in self.conns]),
                 visible_rows=np.concatenate([c.rows for c in self.conns]))


def ack_gap_share(trecv: np.ndarray, t0: float, t1: float):
    """The share, in percent, of the window's bins of ``ACK_BIN_S`` that
    hold under a quarter of the median bin's acks: the stretches in which
    the clients heard (next to) nothing, whoever stalled. Nothing where no
    bin or no ack is."""
    n = int(round((t1 - t0) / ACK_BIN_S))
    if n < 1 or not len(trecv):
        return None
    bins = np.bincount(np.minimum(((trecv - t0) / ACK_BIN_S).astype(np.int64),
                                  n - 1), minlength=n)
    return float((bins < np.median(bins) / 4.0).mean() * 100.0)


def say(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    a = ap.parse_args()
    with open(a.config) as f, open(a.traffic) as g:
        gen = Generator(json.load(f), json.load(g), a.seed)
    say({"ev": "ready", "pid": os.getpid(),
         "cores": sorted(os.sched_getaffinity(0))})
    while True:
        cmd = gen.command()
        if cmd is None:
            data = os.read(sys.stdin.fileno(), 1 << 16)
            if not data:
                return 0
            gen._stdin += data
            continue
        what = cmd["cmd"]
        if what == "start":
            gen.connect(cmd["port"])
            say({"ev": "joined", "shared_rows": gen.shared_rows})
            gen.fill()
            say({"ev": "filled", "ops": gen.n_ops})
            hs = gen.sweep()
            say({"ev": "swept", "programs": hs, "ms": gen.sweep_ms})
            gen.stream()
            drained = gen.wait_frames(60.0)
            say({"ev": "drained", "all_acked": drained, **gen.results()})
        elif what == "tail":
            say({"ev": "tail_done", "rows": gen.tail(),
                 "failures": dict(gen.failures)})
        elif what == "report":
            gen.report(cmd["path"], cmd["sample"])
            say({"ev": "reported"})
        elif what == "quit":
            for c in gen.conns:
                if c.sock is not None:
                    c.sock.close()
            return 0


if __name__ == "__main__":
    sys.exit(main())
