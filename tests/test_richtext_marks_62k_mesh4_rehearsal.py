"""``richtext-marks-62k-mesh4.typing`` rehearsed tiny on four virtual chips
through the benchmark's whole command (``tiny-rich`` x ``tiny-typing``,
Pallas interpreted), traced and not, and its two controls planted there.
It lives beside the benchmark's own tests and not among them (see
``tests/perfbench/test_richtext_marks_62k_mesh4.py``). No test here gives
a device number."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "perfbench"))
from test_perfbench import BENCH, _rehearse  # noqa: E402

from perfbench.traffic import select_metrics  # noqa: E402

CELL = "richtext-marks-62k-mesh4.typing"
CHIPS = 4
# what a CPU's trace cannot give (``test_perfbench.CHIP_ONLY``, for typing:
# the interpreter runs the Pallas kernel as XLA ops, so no op bears the
# kernel's name) and what only the mesh's module line gives
CHIP_ONLY = {"kernel.merge_ms_per_window.typing", "merge_roofline.typing",
             "device.peak_hbm_bytes.typing",
             "kernel.unpack_ms_per_window.typing",
             "kernel.zamboni_merge_ms_per_window.typing",
             "kernel.merge_outside_kernel_share.typing"}


def _mesh_counters():
    from fluidframework_tpu.utils.telemetry import REGISTRY
    return {k: REGISTRY.counters.get(k, 0) for k in (
        "mesh_window_shards", "mesh_window_ops_fullest_shard",
        "mesh_windows_resident", "mesh_windows_resharded",
        "columnar_windows_flushed", "columnar_ops_ingested")}


@pytest.mark.parametrize("trace_on", [False, True])
def test_rehearsal_of_the_cell_on_four_chips(trace_on):
    before = _mesh_counters()
    r = _rehearse("tiny-rich", "tiny-typing", trace_on, chips=CHIPS)
    after = _mesh_counters()
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert all(v["value"] <= v["limit"] for v in r["compared"].values())
    assert {"props_differ", "reload_digests_differ", "reload_lengths_differ",
            "reload_docs_differ", "guarantees_weakened"} <= set(r["compared"])
    assert [m["id"] for m in r["device"]["memory"]] == list(range(CHIPS))
    names = set(r["metrics"])
    if trace_on:
        want = {m["name"] for m in select_metrics(BENCH, CELL)[1]}
        assert names == want - CHIP_ONLY
        assert 0 < r["metrics"]["device.busy_min_over_max.typing"][
            "value"] <= 1
        assert r["metrics"]["device.chip0_busy_over_mean.typing"][
            "value"] > 0
    else:
        assert names == {"setup_s", "ack_p50_ms"}
        assert all(v["value"] > 0 for v in r["metrics"].values())
    # the program's counters of the mesh path, read as the docs say
    d = {k: after[k] - before[k] for k in after}
    assert d["columnar_windows_flushed"] > 0
    assert d["mesh_windows_resident"] >= d["columnar_windows_flushed"]
    assert d["mesh_windows_resharded"] == 0
    assert 1.0 <= d["mesh_window_shards"] / d["columnar_windows_flushed"] \
        <= CHIPS
    assert 1.0 / CHIPS <= d["mesh_window_ops_fullest_shard"] \
        / d["columnar_ops_ingested"] <= 1.0
    json.dumps(r)


@pytest.mark.parametrize("fault,number", [
    ("dropped_annotates", "props_differ"),
    ("unsharded_state", "guarantees_weakened")])
def test_planted_fault_is_caught_on_four_chips(fault, number):
    r = _rehearse("tiny-rich", "tiny-typing", False, plant=fault,
                  chips=CHIPS)
    assert r["correct"] is False
    over = {n for n, v in r["compared"].items() if v["value"] > v["limit"]}
    if fault == "dropped_annotates":
        # the reload merges the tail's annotates again and the summary
        # holds the rest as served: the marks alone go missing
        assert number in over and over <= {number, "reload_docs_differ"}, \
            r["compared"]
    else:
        assert over == {number}, r["compared"]
        assert r["compared"][number]["value"] == 1
