#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (weights from the seed, the frame pool, compiles and warm-up) is
timed as ``setup_s``; then the cell's traffic drives the engine for
``--seconds`` on the wall clock. With ``--trace 0`` the result carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
read from a profiler trace of the window. After the window the served
answers are checked against the float32 reference. Earlier lines, on
standard error, report the device, compiles inside the window, how late
the load generator ran, peak device memory, request counts per intent
and, last, each compared number beside its limit. The last line of
standard output is the result as one JSON object. Without the TPU chips
the cell asks for, the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from perfbench import harness
    spec = harness.cell_spec(args.workload)
    out = harness.run(spec, args.seed, args.seconds, bool(args.trace),
                      T_START)
    res, info = out["result"], out["info"]
    harness.log(f"[device] {json.dumps(res['device'])}")
    for key, value in info.items():
        harness.log(f"[info] {key}: {json.dumps(value)}")
    for name, c in res["checks"].items():
        harness.log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
