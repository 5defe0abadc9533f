"""``richtext-marks-62k-mesh4``: the committed configuration held to its
three parents (``richtext-marks-10k``'s wire, ``string-deli-62k``'s
population, ``string-deli-10k-mesh4``'s placement) and the carving of
typing's turns at its layout placed on its four shards. The cell's
rehearsal on four virtual chips is in
``tests/test_richtext_marks_62k_mesh4_rehearsal.py``: this directory's
files start together on the driver's six workers, and the traced
rehearsals of ``test_perfbench.py`` wager that nothing compiles in their
one-second windows, which a fourth file of rehearsals beside them made
them lose one run in three. No test here gives a device number."""

import itertools
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_perfbench import BENCH, _door_windows  # noqa: E402

from perfbench.traffic import (Layout, load_json,  # noqa: E402
                               select_metrics)

CELL = "richtext-marks-62k-mesh4.typing"
NEW, RICH, BIG, MESH = (load_json("configs", n) for n in (
    "richtext-marks-62k-mesh4", "richtext-marks-10k", "string-deli-62k",
    "string-deli-10k-mesh4"))
TYPING = load_json("traffic", "typing")
CHIPS = 4
ACROSS_CHIPS = {"device.busy_min_over_max.typing",
                "device.chip0_busy_over_mean.typing",
                "device.peak_hbm_bytes.typing",
                "kernel.unpack_ms_per_window.typing"}


# ------------------------------------------------------ the committed file

@pytest.mark.parametrize("group", ["deployment", "guarantees", "wire"])
def test_differs_from_richtext_marks_10k_by_population_and_placement(group):
    new, old = dict(NEW[group]), dict(RICH[group])
    if group == "deployment":
        assert new.pop("n_docs") != old.pop("n_docs")
    if group == "guarantees":
        assert "15,872" in new.pop("placement") and "placement" not in old
    assert new == old


@pytest.mark.parametrize("group", ["deployment", "guarantees", "wire"])
def test_differs_from_string_deli_62k_by_the_wire_and_placement(group):
    """The population is ``string-deli-62k``'s, to the keyword; the wire
    is the other family's (``R`` frames, marks)."""
    new, old = dict(NEW[group]), dict(BIG[group])
    if group == "guarantees":
        new.pop("placement")
    if group == "wire":
        assert (new.pop("frames"), old.pop("frames")) == ("R", "B")
        assert len(new.pop("props")) == 5 and old.pop("props") is None
    assert new == old


def test_placement_is_the_mesh_cells_at_this_population():
    n = NEW["deployment"]["n_docs"]
    assert n % CHIPS == 0 and f"{n // CHIPS:,}" in NEW["guarantees"][
        "placement"]
    assert NEW["guarantees"]["placement"].replace(
        f"{n // CHIPS:,}", "N") == MESH["guarantees"]["placement"].replace(
        f"{MESH['deployment']['n_docs'] // CHIPS:,}", "N")
    # the state it states: 22,536 B a document at 512 slots
    assert f"{n * 22536 / 1e9:.2f} GB" in NEW["assumed"]["population"]
    assert f"{n // CHIPS * 22536 / 1e6:.1f} MB" in NEW["assumed"][
        "population"]


def test_a_connections_documents_are_whole_frames_and_two_to_a_chip():
    n, C = NEW["deployment"]["n_docs"], TYPING["connections"]
    per = n // C
    assert n % C == 0 and per % TYPING["ops_per_frame"] == 0
    assert per // TYPING["ops_per_frame"] == 62     # ticks a cycle
    assert n // CHIPS == 2 * per
    assert TYPING["groups"] * CHIPS == C    # a turn: one relay of each chip


def test_manifest_states_the_file():
    # looked up by name, as ``perfbench/run.py`` does: a place in the list
    # carries no meaning for the harness
    entry = next(c for c in BENCH["configs"] if c["name"] == NEW["name"])
    assert entry["reduced"] == list(NEW["reduced"]) \
        == list(RICH["reduced"]) + ["chips"]
    assert entry["source"] == NEW["source"] and len(entry["source"]) <= 200
    assert set(RICH["assumed"]) | {"population", "ownership"} \
        == set(NEW["assumed"])
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NEW["name"], "typing", CHIPS)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) \
        <= len(BENCH["workloads"]) // 2
    # it reports what the one-chip typing cell reports, under the same
    # names, and the four that exist only across chips
    e2e, layers = select_metrics(BENCH, CELL)
    one = select_metrics(BENCH, "richtext-marks-10k.typing")
    assert e2e == one[0] and [m["name"] for m in e2e] == ["ack_p50_ms",
                                                          "setup_s"]
    names = {m["name"] for m in layers}
    # (the one-chip cell's two by-kernel readings of PR 36 list it alone:
    # ``tests/test_richtext_marks_62k_mesh4_rehearsal.py`` holds this
    # cell's traced line to fourteen names)
    BY_KERNEL = {"kernel.zamboni_merge_ms_per_window.typing",
                 "kernel.merge_outside_kernel_share.typing"}
    assert len(one[1]) == 16 and {m["name"] for m in one[1]} - names \
        == BY_KERNEL and names - {m["name"] for m in one[1]} \
        == ACROSS_CHIPS and len(names) == 18
    assert all(m["workloads"] == [CELL] for m in layers
               if m["name"] in ACROSS_CHIPS)


def test_the_62k_replay_cell_keeps_its_manifest_entry():
    """What ``test_string_deli_62k.py::test_manifest_states_the_file``
    holds, with its cell looked up by name: that test takes
    ``BENCH["workloads"][-1]``, which this cell's entry, appended last as
    the manifest's contract asks, now is (``tests/conftest.py`` marks it
    as expected to fail until a ``benchmark`` PR looks its cell up)."""
    small = load_json("configs", "string-deli-10k")
    entry = next(c for c in BENCH["configs"] if c["name"] == BIG["name"])
    assert entry["reduced"] == list(BIG["reduced"]) \
        == list(small["reduced"])
    assert entry["source"] == BIG["source"] and len(entry["source"]) <= 200
    assert set(small["assumed"]) | {"population"} == set(BIG["assumed"])
    cell = next(w for w in BENCH["workloads"]
                if w["name"] == "string-deli-62k.replay")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (BIG["name"], "replay-62k", 1)
    assert select_metrics(BENCH, cell["name"]) == select_metrics(
        BENCH, "string-deli-10k.replay")
    # the accepted cell stands where it stood, and this one after it
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(cell["name"]) == 3 < names.index(CELL)


# ------------------------------------ typing's turns, placed on the shards

def _turns(lay, tr):
    """One cycle of the open loop as ``gen.py`` streams it: every turn's
    frames, each the rows of one connection. Rows are the door's, handed
    out in order of arrival: a connection joins the documents it owns,
    then those it co-writes, so the multi-writer rows follow connection
    0's."""
    row, per = {}, tr["ops_per_frame"]
    for c in range(lay.C):
        for d in lay.doc_names(c):
            row.setdefault(d, len(row))
    shared = set(lay.shared_names())
    for q in range(lay.P // per):
        for g in range(tr["groups"]):
            frames = []
            for c in range(g, lay.C, tr["groups"]):
                solo = [d for d in lay.doc_names(c)[:lay.P]
                        if d not in shared]
                lo = q * per - (lay.S if c == lay.owner and q else 0)
                hi = (q + 1) * per - (lay.S if c == lay.owner else 0)
                rows = [row[d] for d in solo[lo:hi]]
                if q == 0:
                    rows += [row[d] for d in lay.writes_shared(c)]
                frames.append(np.asarray(rows, np.int64))
            yield frames


def _windows(rows, dep):
    """A drain pass carved the way the door does (rows sorted, cut every
    ``window_min_rows``, each chunk in rounds; no row of a typing pass has
    four ops pending, so every window is one column wide): each window's
    rows by shard, placed with the program's own ``shard_of_rows``."""
    from fluidframework_tpu.parallel.sharded import shard_of_rows
    for got, cols in _door_windows(rows, dep["door"]["window_min_rows"]):
        assert cols == 1
        yield np.bincount(shard_of_rows(got, dep["n_docs"], CHIPS),
                          minlength=CHIPS).tolist()


def test_every_window_of_a_whole_turn_lies_in_all_four_shards():
    """The upper case: a pass that holds a whole turn. A window of a
    frame's rows or more holds rows in every shard, 128 in each but for
    the multi-writer rows (16, which also push every later connection's
    rows 16 off the grid); what is left of a turn, 8 or 16 rows, lies in
    one. On the chip the door's 2 ms tick falls among a turn's four frames
    two times in three: ``test_a_pass_of_k_frames_lies_in_k_shards`` holds
    those passes, and the distribution (2.62 shards a window measured:
    PERF.md, section 6, PR 32) is the door's clock's, which no CPU test
    reads."""
    from fluidframework_tpu.parallel.sharded import shard_of_rows
    dep = NEW["deployment"]
    lay = Layout(dep["n_docs"], TYPING["connections"],
                 TYPING["multi_writer_docs"])
    W, per, S = dep["door"]["window_min_rows"], TYPING["ops_per_frame"], lay.S
    full, small = [], []
    for frames in _turns(lay, TYPING):
        for by in _windows(np.concatenate(frames), dep):
            (full if sum(by) >= per else small).append(by)
    turns = lay.P // per * TYPING["groups"]
    assert len(full) == turns == 124 and all(sum(s) == W for s in full)
    assert all(min(s) >= per - S and max(s) <= per + S for s in full)
    assert sum(s == [per] * CHIPS for s in full) >= turns - 4
    assert sorted(map(sum, small)) == [S // 2, S]
    assert all(sorted(s)[:-1] == [0] * (CHIPS - 1) for s in small)
    shards = sum(np.count_nonzero(s) for s in full + small)
    # the whole-turn case alone: the door makes it one pass in three
    assert shards / len(full + small) >= 3.9
    # the replay mesh cell's windows, 512 consecutive rows of a 2,560-row
    # block, lie in one shard each: what this cell is the first to leave
    n10 = MESH["deployment"]["n_docs"]
    assert len(set(shard_of_rows(np.arange(W), n10, CHIPS))) == 1


@pytest.mark.parametrize("k", [1, 2, 3, 4])
def test_a_pass_of_k_frames_lies_in_k_shards(k):
    """What the door's free-running tick makes of a turn: a pass of any k
    of its four frames is one window of k x 128 rows that holds ops in
    k shards, a frame's rows in each (the multi-writer rows lay every later
    relay's rows 16 off the grid, so the frame that ends a relay's block
    leaves 16 of its rows in the next shard, one pass in a hundred; the first
    turns' shared ops add a window of 8 or 16 rows in one shard).
    ``mesh_window_shards`` over the windows flushed therefore reads the
    mean frames a pass, 4.0 only when every pass holds a whole turn and
    2.62 on the chip."""
    dep = NEW["deployment"]
    lay = Layout(dep["n_docs"], TYPING["connections"],
                 TYPING["multi_writer_docs"])
    per, S = TYPING["ops_per_frame"], lay.S
    passes = spilt = 0
    for frames in _turns(lay, TYPING):
        assert len(frames) == CHIPS
        for some in itertools.combinations(frames, k):
            main, *rest = sorted(_windows(np.concatenate(some), dep),
                                 key=sum, reverse=True)
            assert sum(n >= per - S for n in main) == k
            assert all(n >= per - S or n in (0, S) for n in main)
            spilt += np.count_nonzero(main) != k
            assert all(sum(r) in (S // 2, S) and np.count_nonzero(r) == 1
                       for r in rest)
            passes += 1
    assert passes == 124 * len(list(itertools.combinations(range(4), k)))
    assert spilt <= passes // 30
