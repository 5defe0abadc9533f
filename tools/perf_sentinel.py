#!/usr/bin/env python
"""Perf-regression sentinel over the BENCH_r*.json trajectory.

The driver records every round's ``python bench.py`` run as
``BENCH_r{NN}.json``; nothing so far READS the whole trajectory — a
regression between rounds is only caught if a human happens to diff two
records. This tool is the mechanical judge (ISSUE 4 tentpole piece 4):

- load every committed round (oldest → newest, via ``bench_report``'s
  shape-tolerant ``load_record``),
- for each scalar metric in the NEWEST round, compare against the median
  of the prior rounds, with a variance band wide enough for the known
  round-to-round noise: ``band = max(rel_band·|median|, k_sigma·stdev(priors))``
  (defaults 10% / 3σ — the committed r01–r05 swings, including the −12%
  conflict-throughput dip, sit inside it; a real cliff does not),
- emit one verdict per metric: ``regress`` / ``improve`` / ``flat``
  (plus ``new`` for metrics without enough history and ``info`` for
  metrics that must never fail the build — worst-case single samples,
  the old records' dispatch round trip, config constants),
- exit nonzero iff any metric regressed beyond its band.

Direction is inferred from the name (``*ops_per_sec*`` up is good,
``*_ms``/``*_retries`` down is good); parity booleans are must-hold.
On top of the relative bands, DECLARED_FLOORS carries absolute
per-metric bars (e.g. ``serving_rich_ops_per_sec >= 2e6``) that arm
once achieved and then fail ``--check`` on any later dip below.
``--write-md`` refreshes the ``## Trajectory`` section in BENCHES.md;
``--check`` is the quiet tier-1 mode (table only on failure). bench.py
imports :func:`judge` to embed a live verdict in its own record.

Usage::

    python tools/perf_sentinel.py              # verdict table, exit 0/1
    python tools/perf_sentinel.py --check      # tier-1 gate
    python tools/perf_sentinel.py --write-md   # refresh BENCHES.md
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_report  # noqa: E402  (tools/ is not a package)

#: verdicts that can fail the build
REGRESS = "regress"
IMPROVE = "improve"
FLAT = "flat"
NEW = "new"       # not enough prior rounds to judge
INFO = "info"     # tracked but never failing
STALE = "stale-record"   # floor declared after the newest committed record

#: metrics where a LOWER newest value is the bad direction
HIGHER_BETTER_HINTS = ("ops_per_sec", "per_sec")
HIGHER_BETTER_EXACT = {"value", "vs_baseline"}
#: metrics where a HIGHER newest value is the bad direction
LOWER_BETTER_SUFFIXES = ("_ms", "_retries", "_round_trips", "_stalled")
#: booleans that must stay truthy once they have held for >=1 prior round
MUST_HOLD = {"digest_parity", "conflict_parity"}
#: never-failing metrics: worst-case single samples are outliers by
#: construction (the committed r05 carries a known 983 ms stall), the
#: r01–r05 records' dispatch round trip was their link's property not
#: the code's, and config constants are inputs
INFO_PATTERNS = ("worst",)
INFO_EXACT = {"dispatch_rtt_ms", "docs", "total_ops", "contended"}

#: declared per-metric floors (ISSUE 6 satellite): absolute bars the
#: roadmap has committed to, judged in --check tier-1 mode alongside the
#: trajectory bands. A floor only ARMS once some prior round achieved it
#: ("once achieved"): a still-climbing metric is never failed
#: retroactively, but any later round dipping back below an armed floor
#: fails the build even if the dip sits inside the variance band.
DECLARED_FLOORS: Dict[str, float] = {
    "serving_rich_ops_per_sec": 2e6,
    "columnar_ingress_ops_per_sec": 45e3,
    # ISSUE 7 floors: tree general waves on the width-coded wire through
    # the pipelined executor; matrix storms on the prefix gather-merge
    # kernel. Armed by the first (TPU) round that achieves them — CPU
    # rounds report them unarmed/info rather than failing.
    "tree_serving_ops_per_sec": 5e5,
    "matrix_serving_ops_per_sec": 1e5,
    # ISSUE 18 floor: the partitioned columnar storm (best rate at >= 4
    # sequencer partitions) must reach 2x the committed single-partition
    # columnar number (BENCHES.md: 8683.4 ops/s on the 1-core dev host).
    # Arms on the first round with the host cores to overlap the
    # partition sequencers; stale-record until BENCH_r06 lands.
    "partition_columnar_ops_per_sec": 17.4e3,
    # ISSUE 20 floor: delivered ops/s at 1024 observer subscribers —
    # the encode-once fanout makes delivery a sink call per subscriber,
    # so even the 1-core dev host should clear millions/s. Arms on the
    # first committed clearing round; stale-record until BENCH_r06.
    "read_delivery_ops_per_sec": 5e6,
}

#: round number each floor was declared in (ISSUE 17 satellite): a
#: floor whose declaration postdates the newest COMMITTED ``BENCH_r*``
#: record has never been verified by a committed run — the sentinel
#: says so explicitly (``stale-record``, info-class: visibility, not a
#: build failure) instead of silently judging it "unarmed". Keep this
#: in sync when adding to DECLARED_FLOORS: the round of the PR that
#: declares the floor.
FLOOR_DECLARED_ROUND: Dict[str, int] = {
    "serving_rich_ops_per_sec": 6,
    "columnar_ingress_ops_per_sec": 6,
    "tree_serving_ops_per_sec": 7,
    "matrix_serving_ops_per_sec": 7,
    "partition_columnar_ops_per_sec": 6,
    "read_delivery_ops_per_sec": 6,
}

#: Known-variance note (headline drift, r04 → r05): the merged-kernel
#: headline moved 7.98M → 7.28M ops/s (−8.8%) with no change on the
#: kernel path. That sits INSIDE the 10% rel_band by design: the
#: per-suite ``headline_trials`` of a single record spread up to ~±15%
#: (see ``headline_variance_band.spread_pct``), so a cross-round drift smaller than one record's own
#: in-run spread is noise, not regression. Compare
#: ``headline_variance_band.median`` across rounds — not the
#: best-of-suite ``value`` — before reading a drift as real.


def classify(name: str) -> Optional[str]:
    """'up' (higher better), 'down' (lower better), 'info', 'hold'
    (boolean must-hold), or None for unjudgeable names."""
    if name in MUST_HOLD:
        return "hold"
    if name in INFO_EXACT or any(p in name for p in INFO_PATTERNS):
        return "info"
    if name in HIGHER_BETTER_EXACT or \
            any(h in name for h in HIGHER_BETTER_HINTS):
        return "up"
    if name.endswith(LOWER_BETTER_SUFFIXES):
        return "down"
    return "info"


def load_trajectory(root: Path) -> List[dict]:
    """Every committed round's parsed bench record, oldest → newest.
    Rounds that fail to parse are skipped with a stderr note (one torn
    record must not blind the sentinel to the rest)."""
    rounds: List[dict] = []
    for path in sorted(root.glob("BENCH_r*.json")):
        try:
            rec = bench_report.load_record(path)
        except (ValueError, json.JSONDecodeError) as e:
            print(f"perf_sentinel: skipping {path.name}: {e}",
                  file=sys.stderr)
            continue
        rec["_round"] = path.stem
        rounds.append(rec)
    return rounds


def _median(vals: List[float]) -> float:
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2.0


def _stdev(vals: List[float]) -> float:
    if len(vals) < 2:
        return 0.0
    mean = sum(vals) / len(vals)
    return math.sqrt(sum((v - mean) ** 2 for v in vals)
                     / (len(vals) - 1))


def judge(rounds: List[dict], rel_band: float = 0.10,
          k_sigma: float = 3.0, min_priors: int = 2) -> List[dict]:
    """Verdict per scalar metric of the newest round vs its history.

    A metric regresses when its newest value falls outside
    ``max(rel_band·|median|, k_sigma·stdev)`` of the prior rounds in the
    bad direction for its class; the same excursion in the good
    direction is ``improve``. Metrics seen in fewer than ``min_priors``
    prior rounds are ``new`` — a metric's first appearance can never
    fail the build."""
    if not rounds:
        return []
    newest, priors = rounds[-1], rounds[:-1]
    verdicts: List[dict] = []
    for name in sorted(newest):
        if name.startswith("_"):
            continue
        val = newest[name]
        direction = classify(name)
        if isinstance(val, bool):
            if direction != "hold":
                continue
            held = [r[name] for r in priors if isinstance(r.get(name), bool)]
            ok = val or not any(held)
            verdicts.append({
                "metric": name, "verdict": FLAT if ok else REGRESS,
                "value": val, "expected": "true (must hold)",
                "delta_pct": None,
                "note": "held" if ok else "parity lost vs prior rounds",
            })
            continue
        if not isinstance(val, (int, float)):
            continue
        hist = [float(r[name]) for r in priors
                if isinstance(r.get(name), (int, float))
                and not isinstance(r.get(name), bool)]
        if len(hist) < min_priors:
            verdicts.append({"metric": name, "verdict": NEW,
                             "value": val, "expected": None,
                             "delta_pct": None,
                             "note": f"{len(hist)} prior round(s)"})
            continue
        med = _median(hist)
        band = max(rel_band * abs(med), k_sigma * _stdev(hist))
        delta = float(val) - med
        delta_pct = (delta / med * 100.0) if med else None
        if abs(delta) <= band:
            verdict = FLAT
        elif direction == "info":
            verdict = INFO
        elif direction == "up":
            verdict = IMPROVE if delta > 0 else REGRESS
        elif direction == "down":
            verdict = IMPROVE if delta < 0 else REGRESS
        else:
            verdict = INFO
        verdicts.append({
            "metric": name, "verdict": verdict, "value": val,
            "expected": f"{med:g} ±{band:g}",
            "delta_pct": None if delta_pct is None
            else round(delta_pct, 2),
            "note": f"n={len(hist)}",
        })
    return verdicts


def judge_floors(rounds: List[dict]) -> List[dict]:
    """Declared-floor verdicts for the newest round (see
    DECLARED_FLOORS). Unarmed floors (never achieved in a prior round)
    report ``info``; armed floors report ``flat`` while they hold and
    ``regress`` the moment a round lands below them."""
    if not rounds:
        return []
    newest, priors = rounds[-1], rounds[:-1]
    out: List[dict] = []
    for name, floor in sorted(DECLARED_FLOORS.items()):
        val = newest.get(name)
        if not isinstance(val, (int, float)) or isinstance(val, bool):
            continue
        armed = any(
            isinstance(r.get(name), (int, float))
            and not isinstance(r.get(name), bool)
            and float(r[name]) >= floor for r in priors)
        if val >= floor:
            verdict = FLAT
            note = "floor holds" if armed else "floor achieved (now armed)"
        elif armed:
            verdict, note = REGRESS, "below an ACHIEVED declared floor"
        else:
            verdict, note = INFO, "floor not yet achieved (unarmed)"
        out.append({"metric": name, "verdict": verdict, "value": val,
                    "expected": f">={floor:g} (declared floor)",
                    "delta_pct": round((float(val) - floor) / floor * 100,
                                       2),
                    "note": note})
    return out


def _round_number(stem: str) -> Optional[int]:
    """``"BENCH_r04"`` → 4; None for stems that don't parse."""
    digits = "".join(c for c in stem.rsplit("r", 1)[-1] if c.isdigit())
    return int(digits) if digits else None


def judge_staleness(rounds: List[dict]) -> List[dict]:
    """``stale-record`` verdicts (ISSUE 17 satellite): one per declared
    floor whose declaration round has NO newer committed ``BENCH_r*``
    record. Info-class — the point is an explicit "this bar has never
    been verified by a committed run", not a build failure (the
    floor-arming logic already refuses to fail unachieved floors)."""
    if not rounds:
        return []
    newest = rounds[-1]
    newest_n = _round_number(newest.get("_round", ""))
    if newest_n is None:
        return []
    out: List[dict] = []
    for name, declared in sorted(FLOOR_DECLARED_ROUND.items()):
        if name not in DECLARED_FLOORS or newest_n > declared:
            continue
        out.append({
            "metric": name, "verdict": STALE,
            "value": newest.get(name),
            "expected": f">={DECLARED_FLOORS[name]:g} (declared floor)",
            "delta_pct": None,
            "note": f"floor declared in round {declared}; newest "
                    f"committed record is {newest['_round']} — no "
                    f"committed run verifies it yet",
        })
    return out


def judge_resilience(rounds: List[dict]) -> List[dict]:
    """Hard gate on the newest round's reconnect-storm phase (ISSUE 9):
    ``invariant_violations`` is a correctness count, not a perf number —
    any nonzero value (or a storm that errored out, recorded as −1)
    regresses regardless of bands or history. Rounds predating the
    phase produce no verdict."""
    if not rounds:
        return []
    storm = rounds[-1].get("reconnect_storm")
    if not isinstance(storm, dict):
        return []
    v = storm.get("invariant_violations")
    if not isinstance(v, (int, float)) or isinstance(v, bool):
        return []
    ok = v == 0
    return [{"metric": "reconnect_storm.invariant_violations",
             "verdict": FLAT if ok else REGRESS, "value": v,
             "expected": "0 (resilience invariant)", "delta_pct": None,
             "note": "acked ops exactly-once under the storm" if ok
             else ("storm errored" if v < 0
                   else "resilience invariant broken — see "
                        "docs/RESILIENCE.md")}]


def judge_overload(rounds: List[dict]) -> List[dict]:
    """Hard gate on the newest round's overload-storm phase (ISSUE 16):
    like the resilience gate, ``invariant_violations`` and
    ``silent_drops`` are correctness counts — any nonzero value (or a
    storm that errored out, recorded as −1) regresses regardless of
    bands or history. Rounds predating the phase produce no verdict."""
    if not rounds:
        return []
    storm = rounds[-1].get("overload_storm")
    if not isinstance(storm, dict):
        return []
    out: List[dict] = []
    for key, note_ok, note_bad in (
            ("invariant_violations",
             "exactly-once held under admission shedding",
             "overload invariant broken — see docs/OVERLOAD.md"),
            ("silent_drops",
             "every shed op explicitly throttled, none dropped",
             "shed work silently dropped — see docs/OVERLOAD.md")):
        v = storm.get(key)
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            continue
        ok = v == 0
        out.append({"metric": f"overload_storm.{key}",
                    "verdict": FLAT if ok else REGRESS, "value": v,
                    "expected": "0 (overload invariant)",
                    "delta_pct": None,
                    "note": note_ok if ok
                    else ("storm errored" if v < 0 else note_bad)})
    return out


def judge_partition(rounds: List[dict]) -> List[dict]:
    """Gate on the newest round's ``partition_scaling`` phase (ISSUE
    18). Two verdict classes:

    - digest parity is a MUST-HOLD: the phase folds every sequenced
      window into the replicated shadow state on the virtual device
      mesh — any cross-replica disagreement (or an errored phase)
      regresses regardless of bands or history;
    - the speedup ratio vs the 1-partition baseline is info-class: it
      measures the host's core budget as much as the code (a 1-core
      host serializes the CPU-bound ``seq_dispatch`` stages, ratio
      ~1.0), so the absolute throughput bar rides the
      ``partition_columnar_ops_per_sec`` declared floor instead —
      armed once achieved, ``stale-record`` until a committed round
      verifies it.

    Rounds predating the phase produce no verdict."""
    if not rounds:
        return []
    ps = rounds[-1].get("partition_scaling")
    if not isinstance(ps, dict) or not ps:
        return []
    if "error" in ps:
        return [{"metric": "partition_scaling", "verdict": REGRESS,
                 "value": None, "expected": "phase completes",
                 "delta_pct": None,
                 "note": f"phase errored: {ps['error']}"}]
    out: List[dict] = []
    digest = ps.get("digest")
    if isinstance(digest, dict):
        if "agree_all" in digest:
            ok = bool(digest["agree_all"])
            out.append({
                "metric": "partition_scaling.digest_agree_all",
                "verdict": FLAT if ok else REGRESS, "value": ok,
                "expected": "true (replica digest parity)",
                "delta_pct": None,
                "note": f"{digest.get('windows', 0)} windows folded on "
                        f"{digest.get('devices', '?')} device(s)" if ok
                        else "cross-replica digest diverged — a replica "
                             "raced; see docs/DISTRIBUTED.md"})
        elif "skipped" in digest:
            out.append({
                "metric": "partition_scaling.digest_agree_all",
                "verdict": INFO, "value": None,
                "expected": "true (replica digest parity)",
                "delta_pct": None,
                "note": f"tap skipped: {digest['skipped']}"})
    speedup = ps.get("speedup_4x")
    if isinstance(speedup, (int, float)) and \
            not isinstance(speedup, bool):
        cores = ps.get("host_cores")
        out.append({
            "metric": "partition_scaling.speedup_4x",
            "verdict": INFO, "value": speedup,
            "expected": ">=2.5 on a multi-core host",
            "delta_pct": None,
            "note": f"4-partition storm vs 1-partition baseline on "
                    f"{cores} host core(s) — the ratio is core-bound, "
                    f"the absolute bar is the declared floor"})
    return out


def judge_read(rounds: List[dict]) -> List[dict]:
    """Gate on the newest round's ``read_fanout`` phase (ISSUE 20).

    Two structural gates — both are properties of the code, not the
    host, so they regress outright:

    - ``amortization_ratio_1024`` must stay <= 0.05: the per-subscriber
      marginal cost at 1024 subscribers as a fraction of the
      single-subscriber encode+deliver cost. Above the bar means the
      fanout is re-doing per-subscriber work the encode-once contract
      forbids;
    - ``catchup_speedup_4096`` must stay >= 5: the generation-diff
      catch-up vs full-tail replay at a 4096-op tail. Below the bar the
      device-computed diff stopped paying for itself.

    Staleness p99 is info-class here (the live SLO judges it against
    its bound); the absolute delivery throughput rides the
    ``read_delivery_ops_per_sec`` declared floor. Rounds predating the
    phase produce no verdict."""
    if not rounds:
        return []
    rf = rounds[-1].get("read_fanout")
    if not isinstance(rf, dict) or not rf or "skipped" in rf:
        return []
    if "error" in rf:
        return [{"metric": "read_fanout", "verdict": REGRESS,
                 "value": None, "expected": "phase completes",
                 "delta_pct": None,
                 "note": f"phase errored: {rf['error']}"}]
    out: List[dict] = []
    ratio = rf.get("amortization_ratio_1024")
    if isinstance(ratio, (int, float)) and not isinstance(ratio, bool):
        ok = ratio <= 0.05
        out.append({
            "metric": "read_fanout.amortization_ratio_1024",
            "verdict": FLAT if ok else REGRESS, "value": ratio,
            "expected": "<= 0.05 (encode-once contract)",
            "delta_pct": None,
            "note": "marginal per-subscriber cost is noise vs the "
                    "one-time encode" if ok else
                    "per-subscriber work crept into the fanout — a "
                    "copy or re-encode on the publish path"})
    speedup = rf.get("catchup_speedup_4096")
    if isinstance(speedup, (int, float)) and \
            not isinstance(speedup, bool):
        ok = speedup >= 5
        out.append({
            "metric": "read_fanout.catchup_speedup_4096",
            "verdict": FLAT if ok else REGRESS, "value": speedup,
            "expected": ">= 5x vs full-tail replay (4096-op tail)",
            "delta_pct": None,
            "note": "generation diff + short tail beats rehydration"
                    if ok else "the diff path lost its edge — gather "
                               "kernels or diff sizing regressed"})
    stale = rf.get("staleness_p99_s")
    if isinstance(stale, (int, float)) and not isinstance(stale, bool):
        out.append({
            "metric": "read_fanout.staleness_p99_s",
            "verdict": INFO, "value": stale,
            "expected": "< 2 s (read_staleness SLO bound)",
            "delta_pct": None,
            "note": "window delivery delay under the write storm with "
                    "64 live subscribers — the live SLO engine judges "
                    "the bound, this is the bench's sample"})
    return out


def judge_durability(rounds: List[dict],
                     spill_dir: Optional[str] = None) -> List[dict]:
    """Hard gate on durable-layer integrity (ISSUE 10): the newest
    round's ``durability`` phase reports ``chain_breaks`` from a scrub
    of its own spill — a correctness count like the resilience gate, so
    any nonzero value (or an errored phase, recorded as −1) regresses
    regardless of bands. With ``spill_dir`` the sentinel additionally
    runs the offline scrubber over that directory right now
    (``log_scrub --check`` semantics) and regresses on any break."""
    out: List[dict] = []
    if rounds:
        dur = rounds[-1].get("durability")
        if isinstance(dur, dict):
            v = dur.get("chain_breaks")
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                ok = v == 0
                out.append({
                    "metric": "durability.chain_breaks",
                    "verdict": FLAT if ok else REGRESS, "value": v,
                    "expected": "0 (integrity invariant)",
                    "delta_pct": None,
                    "note": "bench spill verified clean" if ok
                    else ("durability phase errored" if v < 0
                          else "checksum chain broken — see "
                               "docs/DURABILITY.md")})
    if spill_dir:
        import log_scrub
        summary = log_scrub.summarize_reports(
            log_scrub.scrub_tree(spill_dir))
        ok = summary["chain_breaks"] == 0
        out.append({
            "metric": "scrub.chain_breaks",
            "verdict": FLAT if ok else REGRESS,
            "value": summary["chain_breaks"],
            "expected": "0 (integrity invariant)", "delta_pct": None,
            "note": f"scrubbed {summary['files']} files / "
                    f"{summary['records']} records in {spill_dir}"})
    return out


def has_regression(verdicts: List[dict]) -> bool:
    return any(v["verdict"] == REGRESS for v in verdicts)


def render_table(verdicts: List[dict], rounds: List[dict]) -> str:
    """Fixed-width verdict table, regressions first."""
    order = {REGRESS: 0, IMPROVE: 1, STALE: 2, NEW: 3, INFO: 4, FLAT: 5}
    rows = sorted(verdicts, key=lambda v: (order[v["verdict"]],
                                           v["metric"]))
    newest = rounds[-1]["_round"] if rounds else "?"
    head = (f"perf sentinel: {newest} vs {len(rounds) - 1} prior "
            f"round(s)")
    out = [head, "=" * len(head),
           f"{'METRIC':<36s} {'VERDICT':<8s} {'VALUE':>14s} "
           f"{'Δ%':>8s}  EXPECTED"]
    for v in rows:
        val = v["value"]
        val_s = f"{val:g}" if isinstance(val, float) else str(val)
        d = v["delta_pct"]
        out.append(
            f"{v['metric']:<36s} {v['verdict']:<8s} {val_s:>14s} "
            f"{'' if d is None else format(d, '+.1f'):>8s}  "
            f"{v['expected'] or v['note']}")
    counts: Dict[str, int] = {}
    for v in verdicts:
        counts[v["verdict"]] = counts.get(v["verdict"], 0) + 1
    out.append("-- " + "  ".join(f"{k}:{counts[k]}"
                                 for k in sorted(counts)))
    return "\n".join(out) + "\n"


# --------------------------------------------------------- BENCHES.md

TRAJECTORY_HEADING = "## Trajectory"


def trajectory_block(rounds: List[dict], verdicts: List[dict]) -> str:
    """One-line JSON per round (headline metrics only) + the newest
    round's non-flat verdicts — the fenced block under ## Trajectory."""
    lines = []
    for r in rounds:
        lines.append(json.dumps({
            "round": r["_round"],
            **{k: r[k] for k in ("value", "serving_ops_per_sec",
                                 "ack_p99_ms", "digest_parity")
               if k in r}}))
    notable = [v for v in verdicts if v["verdict"] not in (FLAT, NEW)]
    lines.append(json.dumps({
        "sentinel": {"regressions": [v["metric"] for v in notable
                                     if v["verdict"] == REGRESS],
                     "improvements": [v["metric"] for v in notable
                                      if v["verdict"] == IMPROVE]}}))
    return "\n".join(lines)


def write_md(root: Path, rounds: List[dict],
             verdicts: List[dict]) -> None:
    benches = root / "BENCHES.md"
    md = benches.read_text()
    if TRAJECTORY_HEADING not in md:
        md = md.rstrip("\n") + (
            f"\n\n{TRAJECTORY_HEADING} — sentinel view of all rounds"
            "\n\nRegenerated by `python tools/perf_sentinel.py "
            "--write-md`; one line per round, newest verdicts last.\n\n"
            "```json\n{}\n```\n")
    md = bench_report.update_section(
        md, TRAJECTORY_HEADING, trajectory_block(rounds, verdicts))
    benches.write_text(md)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", type=Path,
                    default=Path(__file__).parent.parent)
    ap.add_argument("--rel-band", type=float, default=0.10,
                    help="relative band around the prior median")
    ap.add_argument("--k-sigma", type=float, default=3.0,
                    help="stdev multiplier for the variance band")
    ap.add_argument("--check", action="store_true",
                    help="quiet tier-1 mode: table only on regression")
    ap.add_argument("--write-md", action="store_true",
                    help="refresh the ## Trajectory section in BENCHES.md")
    ap.add_argument("--json", action="store_true",
                    help="print verdicts as JSON instead of the table")
    ap.add_argument("--spill-dir", default=None,
                    help="also scrub this spill directory now and fail "
                         "on any checksum-chain break")
    args = ap.parse_args(argv)

    rounds = load_trajectory(args.root)
    if len(rounds) < 2:
        print("perf_sentinel: fewer than 2 readable rounds; nothing to "
              "judge", file=sys.stderr)
        return 0
    verdicts = judge(rounds, rel_band=args.rel_band,
                     k_sigma=args.k_sigma)
    verdicts += judge_floors(rounds)
    verdicts += judge_staleness(rounds)
    verdicts += judge_resilience(rounds)
    verdicts += judge_overload(rounds)
    verdicts += judge_partition(rounds)
    verdicts += judge_read(rounds)
    verdicts += judge_durability(rounds, spill_dir=args.spill_dir)
    failed = has_regression(verdicts)
    if args.json:
        print(json.dumps(verdicts, indent=2))
    elif not args.check or failed:
        print(render_table(verdicts, rounds), end="")
    if args.write_md:
        write_md(args.root, rounds, verdicts)
        print(f"BENCHES.md {TRAJECTORY_HEADING!r} refreshed",
              file=sys.stderr)
    if args.check and not failed:
        # stale-record is info-class but must stay VISIBLE in the quiet
        # tier-1 mode: an unverified floor silently passing is the
        # failure mode this verdict exists to prevent
        for v in verdicts:
            if v["verdict"] == STALE:
                print(f"perf_sentinel: {STALE} — {v['metric']}: "
                      f"{v['note']}")
        print(f"perf_sentinel: OK — {len(verdicts)} metrics within band "
              f"across {len(rounds)} rounds")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
