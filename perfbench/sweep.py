#!/usr/bin/env python3
"""Find the highest rate an open-loop cell's system sustains.

    python3 perfbench/sweep.py --workload <cell> --seconds 30 \
        --uavs 4 6 8 10 --seed 7

Runs the cell's traffic with its fleet resized to each ``--uavs`` count in
turn (split over the fleet's groups in the proportions the cell's file
gives them), in one process, and prints per size the
offered rate, the end-to-end metrics, how many requests due in the
window were still unanswered at the close and how long after the close
the last answer came. A backlog that grows with the window marks a rate
above what the system sustains. Run it once when a cell is defined; the
cell's traffic file then fixes the rate.
"""
import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import harness, traffic as tr  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--uavs", type=int, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    base = harness.cell_spec(args.workload)
    weights = [g["uavs"] for g in base["traffic"]["fleet"]]
    for n in args.uavs:
        spec = copy.deepcopy(base)
        for g, c in zip(spec["traffic"]["fleet"], tr.shares(n, weights)):
            g["uavs"] = c
        rate = sum(g["uavs"] * g["rate_hz"] for g in spec["traffic"]["fleet"])
        out = harness.run(spec, args.seed, args.seconds, False,
                          time.perf_counter())
        res, info = out["result"], out["info"]
        print(json.dumps({
            "uavs": n, "fleet": [g["uavs"] for g in spec["traffic"]["fleet"]],
            "offered_per_s": rate,
            "metrics": {n: m["value"] for n, m in res["metrics"].items()},
            "attempted": res["attempted"], "failed": res["failed"],
            "backlog_at_close": info["backlog_at_close"],
            "last_answer_after_close_s": info["last_answer_after_close_s"],
            "generator_late_s_max": info["generator_late_s_max"],
            "correct": res["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
