#!/usr/bin/env python3
"""The program's own spans and stage modules in a profiler trace.

``trace.py`` reduces a traced window with the benchmark's spans alone
(``HOST_SPANS``: ``drive.Driver``'s ``pump`` and ``submit``, one span per
stage call). The program opens spans of its own (``repro.core.spans``:
``engine.*`` and ``inflight.*``, args as event stats) and jits every
stage under its name, so each stage's device work runs as an XLA module
``jit_<stage>`` on the TPU plane's ``XLA Modules`` line. ``load`` reads
both from the same ``.xplane.pb``; ``reduce`` takes them with
``trace.load``'s output over the window and returns:

- ``decode_device_ms``: device time of the ``jit_cloud_decode_rows``
  modules in the window, per decode step;
- ``step_host_gap_ms``: device idle inside ``inflight.step`` spans (at
  any depth below them), per decode step;
- ``admit_ms``: mean length of the ``inflight.admit`` spans that start
  in the window;
- ``idle_gaps``: idle time named by the innermost span open at each
  gap's middle, the benchmark's and the program's together, as
  ``trace.reduce`` names them; ``idle_split``: idle time cut at span
  boundaries, each piece named by the innermost span open over it (a
  gap that runs from the end of a step's device work through the copy
  back, the sampling and the next launch is split between those);
  ``largest_gaps``: the longest single gaps, with their midpoint's
  name and their start from the window's; and ``program_idle_share``,
  the share of idle time inside a program span;
- ``modules``: device seconds per module in the window.

On a trace of a program without these names each number is None.

Run one cell traced and print the harness's result line with these
numbers added under ``program``::

    python3 perfbench/program_spans.py --workload <cell> --seed <n> \
        --seconds <s>
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional, Sequence, Tuple  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import trace as trace_mod  # noqa: E402

PROGRAM_PREFIXES = ("engine.", "inflight.")
MODULE_LINE = "XLA Modules"
DECODE_MODULE = "jit_cloud_decode_rows"


def is_program_span(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIXES)


def module_name(event: str) -> str:
    """A module event's name without the program id XLA appends."""
    return event.split("(", 1)[0].strip()


def load(log_dir: str) -> Dict[str, object]:
    """Module events per TPU plane, the program's host spans as
    (start_ns, end_ns, name, args), and each TPU plane's line names."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    modules: List[List[trace_mod.Interval]] = []
    spans: List[Tuple[float, float, str, Dict[str, object]]] = []
    lines: Dict[str, List[str]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines[plane.name] = [line.name for line in plane.lines]
            modules.append([(e.start_ns, e.start_ns + e.duration_ns,
                             module_name(e.name))
                            for line in plane.lines
                            if line.name == MODULE_LINE
                            for e in line.events])
        elif plane.name.startswith("/host:"):
            spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                       dict(e.stats))
                      for line in plane.lines for e in line.events
                      if is_program_span(e.name)]
    return {"modules": modules, "spans": spans, "lines": lines}


def overlap(a: Sequence[Tuple[float, float]],
            b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def innermost_segments(spans: Sequence[trace_mod.Interval], lo: float,
                       hi: float) -> List[trace_mod.Interval]:
    """[lo, hi] cut into disjoint pieces, each named by the innermost
    of the nested ``spans`` open over it (``outside_spans`` where none
    is)."""
    out: List[trace_mod.Interval] = []
    stack: List[Tuple[float, str]] = []        # (end, name)
    t = lo

    def emit(upto: float) -> None:
        nonlocal t
        upto = min(upto, hi)
        if upto > t:
            out.append((t, upto, stack[-1][1] if stack
                        else "outside_spans"))
            t = upto

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(max(s, lo))
        stack.append((e, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(hi)
    return out


def reduce(trace: Dict[str, object], prog: Dict[str, object], lo: float,
           hi: float, steps: int, top: int = 10) -> Dict[str, object]:
    """The program's numbers over the window [lo, hi] (ns); ``trace`` is
    ``trace.load``'s output and ``steps`` the decode steps taken in the
    window."""
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    n_dev = len(devices)
    spans = [(s, e, name) for s, e, name, _ in prog["spans"]]
    step_spans = trace_mod.union(
        [sp for sp in spans if sp[2] == "inflight.step"], lo, hi)
    program = trace_mod.union(spans, lo, hi)
    host = sorted(trace["host"] + spans)
    starts = [h[0] for h in host]
    segments = innermost_segments(
        [h for h in host if h[2] != "window"], lo, hi)
    idle, split, largest = {}, {}, []
    idle_s, in_step_s, in_program_s = 0.0, 0.0, 0.0
    for ops in devices:
        gaps = trace_mod.gaps(trace_mod.union(ops, lo, hi), lo, hi)
        idle_s += sum(e - s for s, e in gaps) / 1e9 / n_dev
        in_step_s += overlap(gaps, step_spans) / 1e9 / n_dev
        in_program_s += overlap(gaps, program) / 1e9 / n_dev
        for s, e in gaps:
            who = trace_mod._innermost(host, starts, (s + e) / 2)
            idle[who] = idle.get(who, 0.0) + (e - s) / 1e9 / n_dev
            largest.append([who, (e - s) / 1e9, (s - lo) / 1e9])
        for name in {n for _, _, n in segments}:
            piece = overlap(gaps, [(s, e) for s, e, n in segments
                                   if n == name])
            split[name] = split.get(name, 0.0) + piece / 1e9 / n_dev
    module_s: Dict[str, float] = {}
    for events in prog["modules"]:
        for s, e, name in events:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                module_s[name] = module_s.get(name, 0.0) \
                    + (e - s) / 1e9 / n_dev
    admits = [e - s for s, e, name in spans
              if name == "inflight.admit" and lo <= s < hi]
    decode_s = module_s.get(DECODE_MODULE, 0.0)

    def per_step(seconds: float, seen: bool) -> Optional[float]:
        return 1000.0 * seconds / steps if seen and steps > 0 else None

    return {
        "decode_device_ms": per_step(decode_s, decode_s > 0),
        "step_host_gap_ms": per_step(in_step_s, bool(step_spans)),
        "admit_ms": sum(admits) / len(admits) / 1e6 if admits else None,
        "program_idle_share": 100.0 * in_program_s / idle_s
        if program and idle_s > 0 else None,
        "idle_gaps": [[n, s] for n, s in sorted(
            idle.items(), key=lambda kv: -kv[1])[:top]],
        "idle_split": [[n, s] for n, s in sorted(
            split.items(), key=lambda kv: -kv[1])[:top] if s > 0],
        "largest_gaps": sorted(largest, key=lambda g: -g[1])[:top],
        "modules": [[n, s] for n, s in sorted(
            module_s.items(), key=lambda kv: -kv[1])[:top]],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from perfbench import harness
    # the harness deletes its trace directory once trace.load has read
    # it: read the program's events in the same call
    seen: Dict[str, object] = {}
    base_load = trace_mod.load

    def load_both(log_dir: str, ids=None) -> Dict[str, object]:
        seen["trace"] = base_load(log_dir, ids)
        seen["prog"] = load(log_dir)
        return seen["trace"]

    trace_mod.load = load_both
    spec = harness.cell_spec(args.workload)
    out = harness.run(spec, args.seed, args.seconds, True, T_START)
    res, info = out["result"], out["info"]
    window = [h for h in seen["trace"]["host"] if h[2] == "window"][0]
    prog = reduce(seen["trace"], seen["prog"], window[0], window[1],
                  info["decode_steps"])
    # every answer of the cell's traffic has answer_len tokens
    completed = sum(c["completed"]
                    for c in info["requests_by_intent"].values())
    prog["answer_tokens_per_s"] = \
        completed * int(spec["traffic"]["answer_len"]) / args.seconds
    prog["decode_steps"] = info["decode_steps"]
    prog["tpu_lines"] = seen["prog"]["lines"]
    res["program"] = prog
    harness.log(f"[device] {json.dumps(res['device'])}")
    for name, c in res["checks"].items():
        harness.log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
