#!/usr/bin/env python3
"""Readings of the correctness numbers for setting a cell's limits.

    python3 perfbench/control.py --workload <cell> --seconds 10 \
        --seeds 1 2 3 ...

For each seed, in one process on the cell's chips: one run of the cell
with a short window at its own load, then, on the same sample of served
requests, the numbers the program's answers read and the numbers the
control reads -- the float32 reference put in the program's place and
computed in fp8 (e4m3), the precision below the bfloat16 the
configuration serves. Prints one JSON line per seed, then the largest
program reading and the smallest control reading of each number: a limit
lies between the two.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import check, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = harness.cell_spec(args.workload)
    rows = []
    for seed in args.seeds:
        out = harness.run(spec, seed, args.seconds, False,
                          time.perf_counter(), control=True)
        row = {"seed": seed, "program": out["info"]["program"],
               "control": out["info"]["control"],
               "sampled": out["info"]["sampled"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        del out
        gc.collect()     # the executor's jitted stages hold its weights
    summary = {k: {"program_max": max(r["program"][k] for r in rows),
                   "control_min": min(r["control"][k] for r in rows)}
               for k in check.NUMBERS}
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "readings": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
