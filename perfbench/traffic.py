"""The one general traffic generator's arithmetic: who writes which
document, what a frame holds, which window heights a mix can produce.
JAX-free and program-free; ``gen.py`` drives it over sockets.

Layout (``n_docs`` documents, ``C`` connections, ``S`` multi-writer
documents): every connection owns ``P = n_docs / C`` documents. The last
connection's first ``S`` are the multi-writer ones; connection 0 co-writes
all of them and connection 1 the odd half, so half have two writers and
half three. A frame's rows are unique and a frame holds a fixed number
of them, so the door's windows have shapes from a closed set: :func:`carve`
is the door's rule (a copy), :func:`heights` and :func:`programs` derive
from it every shape a pass of the mix can be carved into, and set-up
dispatches each on purpose.
"""

import itertools
import json
import os

import numpy as np

from .wire import ANN, INS, OP_DTYPE, REM

HERE = os.path.dirname(os.path.abspath(__file__))
#: the door's column counts, widest first (a copy of
#: ``columnar_ingress._WINDOW_COLUMNS``; a test holds the two together)
WINDOW_COLUMNS = (4, 1)
#: the longest stall set-up prepares an open loop's door for: the records'
#: episodes last 0.1-0.4 s, and what is sent meanwhile lands in one pass
STALL_S = 0.5
EVERY = range(1 << 62)      # ``carve``: a pass whose every window is fused


def load_json(kind: str, name: str) -> dict:
    """configs/<name>.json, traffic/<name>.json or metrics/<name>.json —
    a cell's files are found by the names BENCHMARK.json gives."""
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def select_metrics(bench: dict, cell: str):
    """The one rule for what a cell reports: the end-to-end entries that
    list it (or list nothing), and the per-layer entries that move one of
    those and list it (or list nothing)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    moved = {m["name"] for m in e2e}
    return e2e, [m for m in bench["per_layer"] if m["moves"] in moved
                 and cell in m.get("workloads", [cell])]


class Vocabulary:
    """What a configuration's clients type: single characters drawn by
    ``wire.alphabet``'s weights, and ``wire.fill_text``, the run a
    document's prior content is made of. An op's ``tidx`` is an index into
    ``texts`` here; a frame carries only the entries it uses."""

    def __init__(self, config: dict):
        w = config["wire"]
        chars, weights = w["alphabet"]["chars"], w["alphabet"]["weights"]
        if len(chars) != len(weights) or len(set(chars)) != len(chars):
            raise ValueError("alphabet: one weight for each distinct char")
        self.texts = list(chars) + [w["fill_text"]]
        self.fill = len(chars)
        self.text_len = np.asarray([len(t) for t in self.texts], np.int64)
        p = np.asarray(weights, np.float64)
        self.p = p / p.sum()
        self.cum = np.cumsum(self.p)
        self.cum[-1] = 1.0
        self.props = w["props"] or []

    def draw(self, u: np.ndarray) -> np.ndarray:
        """Character indices for uniform draws ``u``."""
        return np.searchsorted(self.cum, u, side="right").astype(np.int64)


def distinct_range(p: np.ndarray, draws: float, share_lo: float,
                   share_hi: float):
    """Between how few and how many distinct entries ``draws`` ops use,
    when a share of them between ``share_lo`` and ``share_hi`` each draws
    one entry with probabilities ``p``: the expected counts at the two
    ends, three standard deviations either side (of the number of draws,
    and of the distinct count given that number)."""
    def at(share, sign):
        n = max(draws * share + sign * 3 * np.sqrt(
            draws * share * (1 - share)), 0.0)
        miss = (1.0 - p) ** n
        d = (1.0 - miss).sum() + sign * 3 * np.sqrt((miss * (1 - miss)).sum())
        return min(max(d, 0.0), n, draws, len(p))
    return int(np.floor(at(share_lo, -1))), int(np.ceil(at(share_hi, +1)))


def table_size(n_entries: int) -> int:
    """The payload table's padded size for a window that uses ``n``
    distinct texts and marks (the store pads to a power of two, 8 at the
    least): each size is a program of its own for each height."""
    return max(8, 1 << max(n_entries - 1, 0).bit_length())


class Layout:
    def __init__(self, n_docs: int, n_conns: int, n_shared: int):
        if n_docs % n_conns:
            raise ValueError("n_docs must divide by the connections")
        if n_shared and (n_conns < 3 or n_shared % 2
                         or n_shared > n_docs // n_conns):
            raise ValueError("multi-writer docs need 3 connections, an "
                             "even count, and room in one connection")
        self.n_docs, self.C, self.S = n_docs, n_conns, n_shared
        self.P = n_docs // n_conns
        self.owner = n_conns - 1

    def doc_names(self, c: int):
        """Documents connection ``c`` joins, in order: those it owns (the
        owner's multi-writer ones first), then those it co-writes."""
        own = [f"doc-{c * self.P + i}" for i in range(self.P)]
        return own + self.co_written(c)

    def shared_names(self):
        return [f"doc-{self.owner * self.P + i}" for i in range(self.S)]

    def co_written(self, c: int):
        sh = self.shared_names()
        if not self.S or c == self.owner:
            return []
        if c == 0:
            return sh
        if c == 1:
            return sh[1::2]
        return []

    def writes_shared(self, c: int):
        return self.shared_names() if c == self.owner and self.S \
            else self.co_written(c)

    def runs(self):
        """The rows in row order, as runs of (how many, the connections
        that write them): the door gives out rows in join order, so
        connection 0's documents come first, then the multi-writer ones
        (it joins them next), then each later connection's own: every
        later connection's rows begin ``S`` off the grid."""
        shared = [frozenset(k for k in range(self.C)
                            if d in self.writes_shared(k))
                  for d in self.shared_names()]
        out = []
        for c in range(self.C):
            out.append((self.P - (self.S if c == self.owner else 0),
                        frozenset([c])))
            if c == 0:
                out += [(1, writers) for writers in shared]
        return out

    def n_joins(self, name: str) -> int:
        if name not in self.shared_names():
            return 1
        return 2 + (self.shared_names().index(name) % 2)


def carve(pending, window_rows: int, fused=()):
    """The door's carving of one drain pass, as it is since PR 31
    (``columnar_ingress._build_windows``), on the pass's rows in row order,
    ``pending[i]`` ops waiting on the i-th: cut the rows every
    ``window_rows`` into chunks, then carve each chunk in rounds. A round
    takes the chunk's rows that still have ops pending; a full chunk goes
    as wide as every one of its rows can fill (``WINDOW_COLUMNS``), unless
    the engine fuses its zamboni into this window (``fused``: the windows,
    counted from 0 in the pass, that it does), what is left goes one wide.
    Returns the windows as (height, columns), in order."""
    left = np.asarray(pending, np.int64)
    out = []
    for s in range(0, left.size, window_rows):
        todo = left[s:s + window_rows]
        while todo.size:
            cols = 1
            if todo.size == window_rows and len(out) not in fused:
                cols = next(c for c in WINDOW_COLUMNS if c <= todo.min())
            out.append((int(todo.size), cols))
            todo = todo[todo > cols] - cols
    return out


def deepest(lay: Layout, traffic: dict) -> int:
    """How many ops a pass can hold for one row that a single connection
    writes. A closed loop: its frames in flight. An open loop writes a row
    once a cycle of ``P / ops_per_frame`` ticks: one, and one more for
    every cycle a stall of ``STALL_S`` spans (none in the committed mixes;
    the tests' tiny cycle of 80 ms wraps six times)."""
    if traffic["loop"] == "closed":
        return traffic["frames_in_flight"]
    cycle_s = lay.P // traffic["ops_per_frame"] * traffic["tick_ms"] / 1e3
    return 1 + int(STALL_S / cycle_s)


def heights(lay: Layout, traffic: dict, window_rows: int):
    """Every height a one-column window of this mix can have, smallest
    first, derived from :func:`carve`'s rule."""
    if traffic["loop"] == "closed":
        return _closed_heights(lay, traffic, window_rows)
    return _open_heights(lay, traffic, window_rows)


def _open_heights(lay: Layout, traffic: dict, W: int):
    """An open loop's pass holds frames of ``ops_per_frame`` rows that are
    all different, and the multi-writer rows once for each writer: chunks
    are full or what is left, a second round the multi-writer rows alone.
    Where a stall can wrap its cycle (:func:`deepest`) frames repeat in a
    pass and the rounds cut them anywhere: every height up to the
    window's, which only a small window can have swept."""
    per, deep = traffic["ops_per_frame"], deepest(lay, traffic)
    if deep > 1:
        if W > 32:
            raise ValueError(
                f"an open loop that writes a row every {lay.P // per} ticks "
                f"piles its rows {deep} deep in a stall of {STALL_S} s: no "
                f"closed set of heights to sweep")
        return list(range(1, W + 1))
    out = set()
    for k in range(0, 2 * max(lay.C, W // per) + 1):
        for e in sorted({0, lay.S // 2, lay.S}):
            n = per * k + e
            if n >= W:
                out.add(W)
            if n % W:
                out.add(n % W)
    return sorted(out)


def _closed_heights(lay: Layout, traffic: dict, W: int):
    """A closed loop's pass holds ``f[c]`` whole frames of connection
    ``c``, 0 to its frames in flight, so a row has the sum of its writers'
    ``f`` pending. A chunk is ``W`` of the present rows: enumerated over
    every set of connections present and, chunk by chunk, carved for every
    count of the few connections that write the chunk's rows (counts up to
    one more than a row's most writers tell every pattern of "more pending
    than" apart, so more frames in flight add no height)."""
    runs, out, seen = lay.runs(), set(), set()
    levels = range(1, min(deepest(lay, traffic),
                          max(len(w) for _, w in runs) + 1) + 1)
    for present in itertools.product((False, True), repeat=lay.C):
        # the present rows' runs, cut into chunks: {writers: rows} each
        chunks, room = [{}], W
        for size, writers in runs:
            here = frozenset(c for c in writers if present[c])
            while here and size:
                n = min(size, room)
                chunks[-1][here] = chunks[-1].get(here, 0) + n
                size, room = size - n, room - n
                if not room:
                    chunks.append({})
                    room = W
        for chunk in chunks:
            key = frozenset(chunk.items())
            if not chunk or key in seen:
                continue
            seen.add(key)
            conns = sorted(frozenset().union(*chunk))
            sizes = list(chunk.values())
            for f in itertools.product(levels, repeat=len(conns)):
                n = dict(zip(conns, f))
                # every window fused: the rounds then stop at every depth,
                # the ones a wide round would skip too
                out.update(h for h, _ in carve(np.repeat(
                    [sum(n[c] for c in w) for w in chunk], sizes), W, EVERY))
    return sorted(out)


def shapes(lay: Layout, traffic: dict, window_rows: int):
    """Every (height, columns) a window of this mix can have: the
    one-column heights, and a full window at each wider column count that
    a row's pending ops can fill."""
    deep = deepest(lay, traffic)
    return [(h, 1) for h in heights(lay, traffic, window_rows)] + [
        (window_rows, c) for c in sorted(WINDOW_COLUMNS) if 1 < c <= deep]


def programs(lay: Layout, traffic: dict, window_rows: int,
             vocab: Vocabulary, rich: bool):
    """Every (height, columns, payload-table size) a window of this mix
    can meet: for each shape, the table sizes that the distinct characters
    of its inserts plus its distinct marks can pad to, between a settled
    document's share of inserts (half of the text ops) and a growing
    one's (the source's)."""
    m = traffic["mix"]
    text = 1.0 - (m["annotate_share"] if rich else 0.0)
    marks = np.full(len(vocab.props), 1.0 / max(len(vocab.props), 1))
    out = []
    for h, cols in shapes(lay, traffic, window_rows):
        lo, hi = distinct_range(vocab.p, h * cols, text * 0.5,
                                text * m["insert_share"])
        if rich:
            m_lo, m_hi = distinct_range(marks, h * cols, m["annotate_share"],
                                        m["annotate_share"])
            lo, hi = lo + m_lo, min(hi + m_hi, h * cols)
        out += [(h, cols, t) for t in sorted({table_size(n) for n in
                                              range(max(lo, 1), hi + 1)})]
    return out


def cut_probability(length, mix: dict):
    """How likely a document of this visible length is to get a remove
    rather than an insert. Below ``target_len - band`` the source's own
    share (a document grows as the trace does); across the band it rises
    to the mirror of that share, so that a document settles at
    ``target_len``; at ``cap_len`` it is certain. Works on a number or an
    array."""
    lo = 1.0 - mix["insert_share"]
    x = np.clip((length - (mix["target_len"] - mix["band"]))
                / (2.0 * mix["band"]), 0.0, 1.0)
    return np.where(length >= mix["cap_len"], 1.0,
                    lo + (mix["insert_share"] - lo) * x)


class OpMaker:
    """Draws one connection's ops for the documents it writes alone, from
    the seed and from nothing else: the same seed gives the same records
    in the same order, whatever the acks' timing (only ``ref`` is patched
    at send time). Inserts and removes are single characters in the
    source's proportion; the document's visible length steers the two only
    near ``target_len`` (:func:`cut_probability`)."""

    def __init__(self, seed: int, conn: int, n: int, mix: dict, rich: bool,
                 vocab: Vocabulary):
        self.rng = np.random.default_rng([seed, conn, 0])
        self.length = np.zeros(n, np.int64)
        self.cseq = np.zeros(n, np.int64)
        self.rich = rich
        self.vocab = vocab
        self.mix = mix

    def make(self, li: np.ndarray, rows: np.ndarray, fill: bool = False,
             inserts: int = 0) -> np.ndarray:
        """``inserts`` (set-up only): that many of the frame's first ops
        are inserts whatever the draw, so that a small frame too has the
        distinct characters its payload table is to hold."""
        n = len(li)
        rng, m, v = self.rng, self.mix, self.vocab
        L = self.length[li]
        roll, roll2 = rng.random(n), rng.random(n)
        a_span = rng.integers(1, m["annotate_span"] + 1, n)
        u_pos, u_char = rng.random(n), rng.random(n)
        t_prop = rng.integers(0, max(len(v.props), 1), n)
        ops = np.zeros(n, OP_DTYPE)
        if fill:
            kind = np.full(n, INS)
            tidx = np.full(n, v.fill)
            span = np.zeros(n, np.int64)
        else:
            ann = (L >= a_span + 4) & (roll < m["annotate_share"]) \
                if self.rich else np.zeros(n, bool)
            cut = (L >= m["remove_span"] + 4) & ~ann \
                & (roll2 < cut_probability(L, m))
            ann[:inserts] = cut[:inserts] = False
            kind = np.where(ann, ANN, np.where(cut, REM, INS))
            tidx = np.where(kind == INS, v.draw(u_char),
                            np.where(kind == ANN, t_prop, 0))
            span = np.where(ann, a_span, m["remove_span"])
        ins = kind == INS
        a0 = np.where(ins, np.floor(u_pos * (L + 1)),
                      np.floor(u_pos * (np.maximum(L - span, 0) + 1))
                      ).astype(np.int64)
        a1 = np.where(ins, 0, a0 + span)
        self.length[li] = L + np.where(
            ins, v.text_len[np.where(ins, tidx, 0)],
            np.where(kind == REM, -span, 0))
        self.cseq[li] += 1
        ops["row"], ops["kind"], ops["a0"], ops["a1"] = rows, kind, a0, a1
        ops["tidx"], ops["cseq"] = tidx, self.cseq[li]
        return ops
