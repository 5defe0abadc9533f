"""Where the program's threads spend the CPU (``utils.tracing``): each
named thread's CPU clock, read when the table is and by no span, and the
collector's pauses, each worked by hand.
"""

import gc
import threading
import time

import pytest

from fluidframework_tpu.utils import tracing
from fluidframework_tpu.utils.telemetry import REGISTRY


def _burn(cpu_s: float) -> None:
    """Run on the CPU until this thread has used ``cpu_s`` more of it."""
    t = time.thread_time()
    while time.thread_time() - t < cpu_s:
        pass


def _row(key: str) -> float:
    return tracing.SPAN_TABLE.counters[key]


def _row_raw(key: str) -> float:
    """A row without reading the threads' clocks."""
    return tracing._ROWS[key]


def test_the_table_has_thread_and_collector_rows_and_no_span_cpu():
    keys = set(tracing.SPAN_TABLE.counters)
    assert {f"thread.{n}.cpu_s" for n in tracing.THREADS} <= keys
    assert {f"{g}.{f}" for g in tracing.GC
            for f in ("s", "n", "long_s", "long_n")} <= keys
    # a span reads no CPU clock: only the named threads have a CPU row
    assert {k for k in keys if k.endswith(".cpu_s")} \
        == {k for k in keys if k.startswith("thread.")}


def test_a_span_reads_no_cpu_clock(monkeypatch):
    def refuse():
        raise AssertionError("a span read a CPU clock")
    monkeypatch.setattr(time, "thread_time", refuse)
    monkeypatch.setattr(time, "clock_gettime", refuse)
    rec = tracing.new_record(wid=1)
    n = _row_raw("store.pack.n")
    with tracing.stage(rec, "store.pack"):
        pass
    assert _row_raw("store.pack.n") == n + 1


def test_a_spinning_threads_clock_runs_and_a_sleeping_ones_does_not():
    named, burnt = threading.Barrier(3), threading.Event()
    go, done = threading.Event(), threading.Event()

    def spin():
        tracing.name_os_thread("t-spin")
        named.wait(5)
        go.wait(5)
        _burn(0.1)
        burnt.set()
        done.wait(5)

    def sleep():
        tracing.name_os_thread("t-sleep")
        named.wait(5)
        done.wait(5)

    threads = [threading.Thread(target=f) for f in (spin, sleep)]
    for t in threads:
        t.start()
    named.wait(5)
    before = dict(tracing.SPAN_TABLE.counters)
    go.set()
    assert burnt.wait(10)
    mid = dict(tracing.SPAN_TABLE.counters)
    done.set()
    for t in threads:
        t.join(5)
        assert not t.is_alive()
    spun = mid["thread.t-spin.cpu_s"] - before["thread.t-spin.cpu_s"]
    slept = mid["thread.t-sleep.cpu_s"] - before["thread.t-sleep.cpu_s"]
    assert spun >= 0.1 and slept < 0.01
    # read again once both have ended: each keeps its last reading
    after = dict(tracing.SPAN_TABLE.counters)
    assert after["thread.t-spin.cpu_s"] >= mid["thread.t-spin.cpu_s"]
    assert dict(tracing.SPAN_TABLE.counters)["thread.t-spin.cpu_s"] \
        == after["thread.t-spin.cpu_s"]
    # and the registry carries the rows, read at that moment
    assert REGISTRY.full_snapshot()["spans.thread.t-spin.cpu_s"] \
        == after["thread.t-spin.cpu_s"]


def test_threads_that_share_a_name_are_summed():
    base = dict(tracing.SPAN_TABLE.counters).get("thread.t-pair.cpu_s", 0.0)
    done = threading.Barrier(3)

    def work():
        tracing.name_os_thread("t-pair")
        _burn(0.03)
        done.wait(5)
        done.wait(5)

    threads = [threading.Thread(target=work) for _ in range(2)]
    for t in threads:
        t.start()
    done.wait(5)            # both burnt, both alive
    got = tracing.SPAN_TABLE.counters["thread.t-pair.cpu_s"] - base
    done.wait(5)
    for t in threads:
        t.join(5)
        assert not t.is_alive()
    assert got >= 0.06
    # ended, each at the last reading taken of it
    assert tracing.SPAN_TABLE.counters["thread.t-pair.cpu_s"] - base \
        == pytest.approx(got, abs=0.01)


def test_a_forced_full_collection_is_one_gc_full_row():
    enabled = gc.isenabled()
    gc.disable()            # no collection but the ones forced here
    try:
        t = dict(tracing.SPAN_TABLE.counters)
        gc.collect(2)
        gc.collect(0)
        gc.collect(1)
        u = dict(tracing.SPAN_TABLE.counters)
    finally:
        if enabled:
            gc.enable()
    assert u["gc.full.n"] - t["gc.full.n"] == 1
    assert u["gc.young.n"] - t["gc.young.n"] == 2
    assert u["gc.full.s"] > t["gc.full.s"]
    assert u["gc.young.s"] > t["gc.young.s"]


def test_a_collection_inside_the_tables_lock_does_not_wait_for_it():
    """A collection can start on any bytecode, also while its thread
    tabulates a span; the hook must not take the table's lock then."""
    n = _row("gc.full.n")
    done = threading.Event()

    def collect_under_the_lock():
        with tracing._table_lock:
            gc.collect(2)
        done.set()

    t = threading.Thread(target=collect_under_the_lock, daemon=True)
    t.start()
    assert done.wait(10), "the collector's hook waited for the table"
    t.join(5)
    assert _row("gc.full.n") >= n + 1


def test_a_long_collection_is_long_in_its_row(monkeypatch):
    monkeypatch.setattr(tracing, "LONG_S", 0.0)
    t = dict(tracing.SPAN_TABLE.counters)
    gc.collect(2)
    u = dict(tracing.SPAN_TABLE.counters)
    assert u["gc.full.long_n"] - t["gc.full.long_n"] >= 1
    assert u["gc.full.long_s"] - t["gc.full.long_s"] \
        == pytest.approx(u["gc.full.s"] - t["gc.full.s"])
