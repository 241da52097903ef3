#!/usr/bin/env python
"""Perf-regression gate over the serving bench's counts.

The serving bench writes one JSON artifact per run,
``benchmarks/artifacts/BENCH_serving.json`` (rows merged by name — see
``benchmarks/common.write_bench_json``); this script diffs it against
the committed ``BENCH_baseline.json`` with per-metric direction +
tolerance budgets and exits 1 on any regression.

The bench runs on the CPU, which can show counts, correctness and
mission-clock latencies, not speed: those are deterministic under the
rows' seeds. Wall-clock metrics are never gated; device speed is the
chip benchmark's (``perfbench/``).

Metric classification (``classify``):

  * **ignored: wall clock** — ``us``, ``req_s`` and ``*_req_s``,
    ``tok_s`` and ``*_tok_s``, ``speedup_*``, ``vs_unsharded``.
  * **lower-better** — mission-clock latencies: any ``*_s``/``*_us``
    metric. Regression when the new value exceeds baseline by more
    than ``--tolerance`` (relative).
  * **higher-better** — quality and counts: SLO/hit/acceptance rates,
    Jain fairness, tokens/step, saved FLOPs/bytes. Regression when the
    new value falls below baseline by more than ``--quality-tolerance``
    (relative).
  * **zero-tolerance** — ``page_leaks``: any nonzero value is a
    regression regardless of baseline or tolerance.
  * **ignored** — run geometry (seeds, sizes, SLOs), fault-schedule
    telemetry pinned by the benches' own asserts, and informational
    counters. Non-numeric values are never compared.

A baseline row missing from the bench is a regression (a mode silently
stopped running); a baseline metric missing from its row likewise. New
rows/metrics are informational until ``--update-baseline`` admits them
(wall-clock metrics are left out of the baseline it writes).

Usage (the CI step, scripts/ci_fast.sh):

    python scripts/perf_gate.py \
        --bench benchmarks/artifacts/BENCH_serving.json \
        --baseline BENCH_baseline.json
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Any, Dict, List, Tuple

DEFAULT_TOLERANCE = 0.50          # lower-better metrics may grow 50%
DEFAULT_QUALITY_TOLERANCE = 0.05  # higher-better metrics may drop 5%

# metrics read off the host's wall clock: never gated
WALL_CLOCK = re.compile(r"us|(\w+_)?req_s|(\w+_)?tok_s|speedup_\w+"
                        r"|vs_unsharded")
HIGHER_BETTER = {
    "delivered_under_slo", "prefix_hit_rate", "jain", "served",
    "acceptance_rate", "tokens_per_step", "kv_bytes_saved",
    "prefill_flops_saved",
}
ZERO_TOLERANCE = {"page_leaks"}
IGNORED = {
    "seed", "uavs", "frames_per_uav", "slo_s", "duration_s", "offered",
    "ops", "k", "draft_layers", "steps", "note", "model_shards",
    "token_exact", "baseline_decode_steps", "draft_prefills",
    "draft_steps", "verify_steps", "retries", "preemptions",
    "rejected_rate_limit", "rejected_queue_full", "resumed_served",
    "tokens_replayed", "downshifts", "flight_dumps",
    "deadline_cancelled", "inflight_cancelled", "stage_faults",
    "blackouts_terminal", "cloud_errors_terminal", "kv_pages_peak",
    "shard_imbalance",
}


def classify(metric: str) -> str:
    """'higher' | 'lower' | 'zero' | 'ignore' for one metric name."""
    if metric in ZERO_TOLERANCE:
        return "zero"
    if metric in IGNORED or WALL_CLOCK.fullmatch(metric):
        return "ignore"
    if metric in HIGHER_BETTER:
        return "higher"
    if metric.endswith("_s") or metric.endswith("_us"):
        return "lower"
    return "ignore"


def load_bench(path: str) -> Dict[str, Dict[str, Any]]:
    with open(path) as f:
        doc = json.load(f)
    records = doc.get("records")
    if not isinstance(records, dict):
        raise ValueError(f"{path}: no 'records' object")
    return records


def compare(bench: Dict[str, Dict[str, Any]],
            baseline: Dict[str, Dict[str, Any]],
            tolerance: float, quality_tolerance: float
            ) -> Tuple[List[str], List[str]]:
    """Diff ``bench`` against ``baseline``; returns (regressions,
    infos). Deterministic order: rows and metrics sorted by name."""
    regressions: List[str] = []
    infos: List[str] = []
    for name in sorted(baseline):
        base_row = baseline[name]
        row = bench.get(name)
        if row is None:
            regressions.append(
                f"{name}: row missing from bench (mode stopped running)")
            continue
        for metric in sorted(base_row):
            old = base_row[metric]
            if not isinstance(old, (int, float)):
                continue
            kind = classify(metric)
            if kind == "ignore":
                continue
            new = row.get(metric)
            if not isinstance(new, (int, float)):
                regressions.append(
                    f"{name}.{metric}: metric missing from bench row")
                continue
            if kind == "zero":
                if new != 0:
                    regressions.append(
                        f"{name}.{metric}: {new:g} != 0 (zero-tolerance)")
                continue
            if kind == "lower":
                limit = old * (1.0 + tolerance)
                if new > limit:
                    regressions.append(
                        f"{name}.{metric}: {new:g} > {old:g} "
                        f"(+{tolerance:.0%} budget -> {limit:g})")
            else:   # higher-better
                limit = old * (1.0 - quality_tolerance)
                if new < limit:
                    regressions.append(
                        f"{name}.{metric}: {new:g} < {old:g} "
                        f"(-{quality_tolerance:.0%} budget -> {limit:g})")
        for metric in sorted(set(row) - set(base_row)):
            if isinstance(row[metric], (int, float)) \
                    and classify(metric) != "ignore":
                infos.append(f"{name}.{metric}: new metric "
                             f"({row[metric]:g}), not yet gated")
    for name in sorted(set(bench) - set(baseline)):
        infos.append(f"{name}: new row, not yet gated")
    return regressions, infos


def main(argv=None) -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(here)
    ap = argparse.ArgumentParser(
        prog="python scripts/perf_gate.py",
        description="diff the serving bench's artifact against the "
                    "committed baseline; exit 1 on regression")
    ap.add_argument("--bench",
                    default=os.path.join(repo, "benchmarks", "artifacts",
                                         "BENCH_serving.json"))
    ap.add_argument("--baseline",
                    default=os.path.join(repo, "BENCH_baseline.json"))
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                    help="relative budget for lower-better (mission-"
                         f"clock latency) metrics (default "
                         f"{DEFAULT_TOLERANCE})")
    ap.add_argument("--quality-tolerance", type=float,
                    default=DEFAULT_QUALITY_TOLERANCE,
                    help="relative budget for higher-better metrics "
                         f"(default {DEFAULT_QUALITY_TOLERANCE})")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from the current bench "
                         "(run after an intentional perf change)")
    args = ap.parse_args(argv)
    tolerance, quality = args.tolerance, args.quality_tolerance

    try:
        bench = load_bench(args.bench)
    except (OSError, ValueError) as e:
        print(f"perf_gate: cannot load bench: {e}", file=sys.stderr)
        return 2

    if args.update_baseline:
        records = {name: {m: v for m, v in row.items()
                          if not WALL_CLOCK.fullmatch(m)}
                   for name, row in bench.items()}
        with open(args.baseline, "w") as f:
            json.dump({"benchmark": "BENCH_baseline",
                       "records": records}, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"perf_gate: baseline updated from {args.bench} "
              f"({len(bench)} rows)")
        return 0

    try:
        baseline = load_bench(args.baseline)
    except (OSError, ValueError) as e:
        print(f"perf_gate: cannot load baseline: {e}", file=sys.stderr)
        return 2

    regressions, infos = compare(bench, baseline, tolerance, quality)

    if args.json:
        print(json.dumps({
            "ok": not regressions,
            "tolerance": tolerance,
            "quality_tolerance": quality,
            "regressions": regressions,
            "infos": infos,
        }, indent=2, sort_keys=True))
    else:
        for line in infos:
            print(f"perf_gate [info] {line}")
        for line in regressions:
            print(f"perf_gate [REGRESSION] {line}")
        n_rows = sum(1 for r in baseline if r in bench)
        if regressions:
            print(f"perf_gate: {len(regressions)} regression(s) across "
                  f"{len(baseline)} baselined rows")
        else:
            print(f"perf_gate: clean ({n_rows}/{len(baseline)} "
                  f"baselined rows checked, +{tolerance:.0%} latency / "
                  f"-{quality:.0%} quality budgets)")
    return 1 if regressions else 0


if __name__ == "__main__":
    raise SystemExit(main())
