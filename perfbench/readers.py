"""Arithmetic shared by the per-layer readers in ``metrics/``.

Each reader gets one dict for the traced window: ``trace`` (``trace.reduce``
output, per chip), ``counts`` (the stage proxy's counts), ``steps`` and
``slot_steps`` (the engine's decode-step counters, differenced over the
window), ``pcfg``, ``arch`` (its architecture module, which counts the
trunk's work), ``chips`` (the cell's) and ``peaks`` (one chip's). Work
counts are of the whole model, so each share of a peak divides them by
``chips`` chips' peaks. A reader that finds nothing to read returns None
and its metric is left out of the line.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from perfbench import flops

# substring of the device operation names of each kernel a reader times
KERNELS = {"paged_decode": "paged_decode"}


def idle_share(r: Dict[str, Any]) -> Optional[float]:
    t = r["trace"]
    if t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def paged_decode_roofline(r: Dict[str, Any]) -> Optional[float]:
    """Least time of the window's paged decode attention, spread over the
    cell's chips, over the time the kernel took on a chip, in percent."""
    kernel_s = r["trace"]["kernel_s"]["paged_decode"]
    steps = r["counts"]["decode_ctx"]
    if kernel_s <= 0 or not steps:
        return None
    p, n, least = r["pcfg"], r["chips"], 0.0
    for ctx in steps:
        f, b = r["arch"].paged_attention_cost(p, ctx)
        least += max(f / (n * r["peaks"]["bf16_flops"]),
                     b / (n * r["peaks"]["hbm_bytes_per_s"]))
    return 100.0 * least / kernel_s


def model_flops(r: Dict[str, Any]) -> float:
    """Model operations of the work the window's stage calls did."""
    a, p, c = r["arch"], r["pcfg"], r["counts"]
    total = sum(flops.prefill_flops(a, p, n) for n in c["prefill_len"])
    total += sum(flops.decode_step_flops(a, p, ctx)
                 for ctx in c["decode_ctx"])
    total += sum(flops.sam_tail_flops(p, k) for k in c["sam_rank"])
    return total + c["mask"] * flops.mask_flops(p)


def mfu(r: Dict[str, Any], over: str) -> Optional[float]:
    """Model operations over ``over`` ("window" or "busy") seconds at the
    cell's chips' bf16 peak, in percent."""
    seconds = r["trace"]["window_s" if over == "window" else "busy_s"]
    work = model_flops(r)
    if seconds <= 0 or work <= 0:
        return None
    return 100.0 * work / (seconds * r["chips"] * r["peaks"]["bf16_flops"])


def decode_step_ms(r: Dict[str, Any]) -> Optional[float]:
    if r["steps"] <= 0:
        return None
    return 1000.0 * r["trace"]["window_s"] / r["steps"]


def slots_per_step(r: Dict[str, Any]) -> Optional[float]:
    if r["steps"] <= 0:
        return None
    return r["slot_steps"] / r["steps"]
