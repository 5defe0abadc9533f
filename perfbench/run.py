"""The benchmark's command:

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json and its files by name (configs/<config>.json,
traffic/<traffic>.json, metrics/<metric>.json), runs it once on the chips
this machine holds, and prints one JSON object as the last line of stdout.
Refuses to run without a TPU. ``--control <fault>`` plants one of
``faults.py`` (the controls; never part of a measured run).
"""

import time
T_START = time.monotonic()      # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--control", default="")
    a = ap.parse_args()

    from perfbench import harness
    from perfbench.traffic import HERE, load_json, select_metrics
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if a.workload not in cells:
        raise SystemExit(f"perfbench: no cell {a.workload!r} in "
                         f"BENCHMARK.json ({sorted(cells)})")
    cell = cells[a.workload]
    end_to_end, per_layer = select_metrics(bench, a.workload)
    result = harness.run_cell(
        cell, load_json("configs", cell["config"]),
        load_json("traffic", cell["traffic"]),
        {"config": os.path.join(HERE, "configs", cell["config"] + ".json"),
         "traffic": os.path.join(HERE, "traffic", cell["traffic"] + ".json")},
        seed=a.seed, seconds=a.seconds, trace_on=bool(a.trace),
        t_start=T_START, end_to_end=end_to_end, per_layer=per_layer,
        plant=a.control)
    for k, v in result["compared"].items():
        print(f"perfbench: compared {k} = {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(f"perfbench: correct = {result['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
