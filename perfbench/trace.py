"""Reduction of a profiler trace to the numbers the per-layer readers use.

``load`` reads the ``.xplane.pb`` a traced run wrote, with nothing but
JAX, into plain lists: the device operations of each TPU plane of the
cell's chips (``/device:TPU:<id>``, by JAX's device ids) and the
benchmark's own host spans (``drive.py``: ``pump``, ``submit``,
``generator_wait`` and one per executor stage), all in nanoseconds on one
clock. ``reduce`` takes those lists and the window's bounds and returns
busy time (the union of operation intervals, averaged over chips), the
time in named kernels, the operations that took most time, and the idle
gaps, each named by the innermost benchmark span open at its middle.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float, str]          # (start_ns, end_ns, name)

HOST_SPANS = ("window", "pump", "submit", "generator_wait", "cloud_prefix",
              "pool_write", "cloud_decode_rows", "cloud_sam_feats",
              "cloud_mask")
DEVICE_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"


def load(log_dir: str, ids: Optional[Sequence[int]] = None
         ) -> Dict[str, object]:
    """Device operations of the TPU planes of chips ``ids`` (every TPU
    plane where None) and the benchmark's host spans."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    planes: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            planes[plane.name] = [
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for line in plane.lines if line.name == DEVICE_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                     for line in plane.lines for e in line.events
                     if e.name in HOST_SPANS]
    return {"devices": own_planes(planes, ids), "host": host}


def own_planes(planes: Dict[str, List[Interval]],
               ids: Optional[Sequence[int]]) -> List[List[Interval]]:
    """The operations of the planes of chips ``ids``, in that order (of
    every plane, in the trace's order, where None): a cell that runs on
    some of a host's chips reads none of the others'."""
    if ids is None:
        return list(planes.values())
    missing = [i for i in ids if f"{DEVICE_PLANE}{i}" not in planes]
    if missing:
        raise ValueError(f"the trace holds no plane of chips {missing}; "
                         f"it holds {sorted(planes)}")
    return [planes[f"{DEVICE_PLANE}{i}"] for i in ids]


def union(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e, _ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def short(name: str) -> str:
    """An operation's name without its HLO operands: ``%op.N = type``."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return head[:120]
    kind = "tuple" if rest.startswith("(") else \
        rest.split("{", 1)[0].split("(", 1)[0].split(" ", 1)[0]
    return f"{head} = {kind}"[:120]


def self_times(intervals: Sequence[Interval], lo: float, hi: float
               ) -> List[Tuple[str, float]]:
    """(short name, seconds) of each operation inside [lo, hi], less the
    time of the operations nested in it (a loop holds its body's ops)."""
    out: List[Tuple[str, float]] = []
    stack: List[List] = []              # [end, name, own seconds]
    for s, e, name in sorted(intervals, key=lambda x: (x[0], -x[1])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][0] <= s:
            top = stack.pop()
            out.append((top[1], top[2]))
        if stack:
            stack[-1][2] -= (e - s) / 1e9
        stack.append([e, short(name), (e - s) / 1e9])
    out += [(top[1], top[2]) for top in stack]
    return out


def _innermost(spans: Sequence[Interval], starts: Sequence[float],
               t: float, look_back: int = 64) -> str:
    """The shortest benchmark span holding ``t``. Host spans of the
    driver's thread nest, so it is the latest-starting one that has not
    ended: look back from the last span starting before ``t``."""
    best = None
    i = bisect.bisect_right(starts, t) - 1
    for s, e, name in spans[max(0, i - look_back):i + 1][::-1]:
        if e >= t and name != "window" and (
                best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "outside_spans"


def reduce(trace: Dict[str, object], lo: float, hi: float,
           kernels: Dict[str, str], top: int = 10) -> Dict[str, object]:
    """Busy and kernel seconds, top operations and named idle gaps over
    the window [lo, hi] (ns). ``kernels`` maps a reader's kernel key to
    the substring its device operations carry in their names."""
    devices: List[List[Interval]] = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    window_s = (hi - lo) / 1e9
    busy_s, kernel_s = [], {k: 0.0 for k in kernels}
    ops: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    host = sorted(trace["host"])
    starts = [h[0] for h in host]
    for intervals in devices:
        merged = union(intervals, lo, hi)
        busy_s.append(sum(e - s for s, e in merged) / 1e9)
        for name, dur in self_times(intervals, lo, hi):
            ops[name] = ops.get(name, 0.0) + dur / len(devices)
            for key, pattern in kernels.items():
                if pattern in name:
                    kernel_s[key] += dur / len(devices)
        for s, e in gaps(merged, lo, hi):
            who = _innermost(host, starts, (s + e) / 2)
            idle[who] = idle.get(who, 0.0) + (e - s) / 1e9 / len(devices)
    order = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    idle_order = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": window_s, "busy_s": sum(busy_s) / len(busy_s),
            "kernel_s": kernel_s,
            "device_ops": [[n, s] for n, s in order],
            "idle_gaps": [[n, s] for n, s in idle_order]}
