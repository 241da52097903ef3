"""Shared benchmark infrastructure: trained-system cache, engine
construction, warmup/timing, and CSV helpers.

The offline phase (lisa-mini original + flood-finetune + three bottleneck
tiers) is trained once and cached under benchmarks/artifacts/checkpoints;
subsequent benchmark runs load it from disk. Serving benchmarks build
their ``AveryEngine`` through ``make_engine`` (loopback transport, shared
weights/LUT) instead of hand-wiring executors.
"""
from __future__ import annotations

import os
import time
from typing import Dict, Tuple

ART = os.path.join(os.path.dirname(__file__), "artifacts")
CKPT = os.path.join(ART, "checkpoints")
DRYRUN_DIR = os.path.join(ART, "dryrun")

RATIOS = (0.25, 0.10, 0.05)

# offline-phase training budget (tuned for the single-CPU container:
# ~0.25 s/step at batch 16 -> the full offline phase takes ~8 minutes)
TRAIN_STEPS = 800
FT_STEPS = 250
BN_STEPS = 250
BATCH = 16


def ensure_trained_system(log=print) -> Tuple[dict, dict, Dict[float, dict]]:
    """Train (or load) the full offline phase."""
    from repro.checkpoint import load_pytree, save_pytree
    from repro.configs.lisa_mini import CONFIG as pcfg
    from repro.core import profile as prof

    paths = {
        "orig": os.path.join(CKPT, "lisa_mini_original"),
        "ft": os.path.join(CKPT, "lisa_mini_finetuned"),
        **{f"bn{r}": os.path.join(CKPT, f"bottleneck_r{r}") for r in RATIOS},
    }
    if all(os.path.exists(os.path.join(p, "arrays.npz"))
           for p in paths.values()):
        log("[bench] loading cached offline-phase checkpoints")
        params = load_pytree(paths["orig"])
        params_ft = load_pytree(paths["ft"])
        bns = {r: load_pytree(paths[f"bn{r}"]) for r in RATIOS}
        return params, params_ft, bns

    log("[bench] training offline phase (cached for later runs)")
    params, params_ft, bns = prof.train_full_system(
        pcfg, ratios=RATIOS, steps=TRAIN_STEPS, bn_steps=BN_STEPS,
        ft_steps=FT_STEPS, batch_size=BATCH, log=log)
    os.makedirs(CKPT, exist_ok=True)
    save_pytree(paths["orig"], params)
    save_pytree(paths["ft"], params_ft)
    for r in RATIOS:
        save_pytree(paths[f"bn{r}"], bns[r])
    return params, params_ft, bns


def ensure_lut(log=print):
    """Build (or load) the measured System LUT."""
    from repro.configs.lisa_mini import CONFIG as pcfg
    from repro.core import profile as prof
    from repro.core.lut import SystemLUT
    path = os.path.join(CKPT, "lut.json")
    if os.path.exists(path):
        return SystemLUT.load(path)
    params, params_ft, bns = ensure_trained_system(log)
    lut = prof.build_lut(pcfg, params, params_ft, bns)
    os.makedirs(CKPT, exist_ok=True)
    lut.save(path)
    return lut


def init_serving_system(pcfg=None):
    """Weights + per-tier bottlenecks + paper LUT for serving benchmarks:
    cached trained checkpoints when present, random init otherwise
    (serving throughput depends on the geometry, not the weight values)."""
    from repro.core import profile as prof

    if pcfg is None:
        from repro.configs.lisa_mini import CONFIG as pcfg
    params = None
    path = os.path.join(CKPT, "lisa_mini_original", "arrays.npz")
    if os.path.exists(path):
        from repro.checkpoint import load_pytree
        params = load_pytree(os.path.dirname(path))
    return prof.random_init_system(pcfg, params=params)


def make_executor(pcfg=None, params=None, bns=None, lut=None, **kw):
    """A ``DualStreamExecutor`` over the shared serving system."""
    from repro.core import DualStreamExecutor

    if pcfg is None:
        from repro.configs.lisa_mini import CONFIG as pcfg
    if params is None:
        params, bns, lut = init_serving_system(pcfg)
    return DualStreamExecutor(pcfg=pcfg, params=params, bottlenecks=bns,
                              lut=lut, **kw)


def make_engine(executor, **engine_kw):
    """The benchmark front door: an ``AveryEngine`` on an in-process
    loopback link (no simulated channel in the measurement)."""
    from repro.engine import AveryEngine, LoopbackTransport

    engine_kw.setdefault("transport", LoopbackTransport())
    return AveryEngine(lut=executor.lut, executor=executor, **engine_kw)


def time_best(fn, reps: int = 2) -> float:
    """Warm up once (absorbing XLA compiles), then best-of-``reps``."""
    fn()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


class Timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.us = (time.time() - self.t0) * 1e6


def emit(name: str, us: float, derived: str) -> str:
    row = f"{name},{us:.0f},{derived}"
    print(row, flush=True)
    return row


def write_bench_json(rows, filename: str = "BENCH_serving.json") -> str:
    """Persist benchmark rows as a machine-readable artifact.

    ``rows`` are the strings ``emit`` returns (``name,us,k=v;k=v;...``);
    they merge by row name into ``benchmarks/artifacts/<filename>``, so
    partial runs (``--paged-smoke``, ``--spec``, ``--sharded``) update
    their rows without clobbering the rest. Nothing outside the
    artifacts directory is written. Returns the artifact path."""
    import json

    path = os.path.join(ART, filename)
    records = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
            if isinstance(prev, dict) and isinstance(prev.get("records"),
                                                     dict):
                records = prev["records"]
        except (json.JSONDecodeError, OSError):
            pass                       # corrupt artifact: regenerate
    for row in rows:
        name, us, derived = row.split(",", 2)
        rec = {"us": float(us)}
        for kv in derived.split(";"):
            if "=" not in kv:
                continue
            k, v = kv.split("=", 1)
            try:
                rec[k] = float(v.rstrip("x"))
            except ValueError:
                rec[k] = v
        records[name] = rec
    os.makedirs(ART, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"benchmark": os.path.splitext(filename)[0],
                   "records": records}, f, indent=2, sort_keys=True)
        f.write("\n")
    return path
