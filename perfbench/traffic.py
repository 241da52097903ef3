"""The one traffic generator: reads a mix from ``traffic/<name>.json``
and turns it into the seeded work of one run.

Two loops:

* ``closed``: ``clients`` UAV clients, each sending its next request the
  moment its last answer returns. Every seed draws the same kinds of
  request; only the frames and queries differ.
* ``open``: each fleet group's UAVs send on their own schedule whether or
  not earlier answers have returned. Gaps are heavy-tailed: Lomax
  (Pareto II) draws of shape ``gap_shape`` plus ``gap_floor``, the
  arithmetic of the repo's seeded storm schedule, scaled so that a UAV's
  mean rate is ``rate_hz``. So that every seed offers the same work, a
  UAV's gaps are the distribution's quantiles at evenly spaced levels,
  shuffled by the seed and stretched so that one cycle of them spans
  the window: each seed sends the same number of requests per UAV with the
  same set of gaps, in another order.

Every request carries a fresh query drawn from the seed, so the prefix
store never holds its ``[ctx; query]`` prefix and each one pays a prefill.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Uav:
    name: str
    intent: str                  # "context" | "insight"
    tier: Optional[str]          # Insight tier; None for Context


@dataclasses.dataclass
class Due:
    t: float                     # seconds after the window opens
    uav: int                     # index into the fleet


def fleet(mix: Dict[str, Any]) -> List[Uav]:
    """The UAVs of a mix, one session each, in a fixed order."""
    out: List[Uav] = []
    if mix["loop"] == "closed":
        n = int(mix["clients"])
        counts = shares(n, [g["share"] for g in mix["fleet"]])
        groups = [(g, c) for g, c in zip(mix["fleet"], counts)]
    else:
        groups = [(g, int(g["uavs"])) for g in mix["fleet"]]
    for g, count in groups:
        for _ in range(count):
            out.append(Uav(f"uav-{len(out)}", g["intent"], g.get("tier")))
    return out


def shares(n: int, weights: List[float]) -> List[int]:
    """``n`` split in proportion to ``weights``, largest remainders first."""
    raw = np.asarray(weights, np.float64) * n / sum(weights)
    counts = np.floor(raw).astype(int)
    for i in np.argsort(raw - counts)[::-1][:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def lomax_quantiles(n: int, shape: float) -> np.ndarray:
    """The Lomax(shape) quantiles at levels (i + 0.5) / n."""
    u = (np.arange(n) + 0.5) / n
    return (1.0 - u) ** (-1.0 / shape) - 1.0


def open_schedule(mix: Dict[str, Any], seconds: float, seed: int
                  ) -> List[Due]:
    """Due times of every request of an open-loop mix in a window of
    ``seconds``, in order."""
    rng = np.random.default_rng([int(seed), 3])
    uavs = fleet(mix)
    rates = []
    for g in mix["fleet"]:
        rates += [float(g["rate_hz"])] * int(g["uavs"])
    shape, floor = float(mix["gap_shape"]), float(mix["gap_floor"])
    out: List[Due] = []
    for i, (uav, rate) in enumerate(zip(uavs, rates)):
        n = max(1, int(round(rate * seconds)))
        gaps = rng.permutation(floor + lomax_quantiles(n, shape))
        gaps *= seconds / gaps.sum()
        # the window holds one cycle of the gaps: the last one wraps
        # round the close, half before the first request, half after
        # the last
        t = gaps[-1] / 2 + np.concatenate([[0.0], np.cumsum(gaps[:-1])])
        out += [Due(float(x), i) for x in t]
    out.sort(key=lambda d: (d.t, d.uav))
    return out


class RequestSource:
    """Seeded choice of frame and query for each request, per UAV, in the
    order the UAV sends them."""

    def __init__(self, mix: Dict[str, Any], vocab: int, frames: int,
                 seed: int):
        self.qlen = int(mix["query_len"])
        self.vocab = int(vocab)
        self.frames = int(frames)
        self._rng = np.random.default_rng([int(seed), 4])

    def next(self) -> Dict[str, Any]:
        return {"frame": int(self._rng.integers(self.frames)),
                "query": self._rng.integers(
                    0, self.vocab, (1, self.qlen)).astype(np.int32)}
