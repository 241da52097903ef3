"""One run of one cell: set-up, the measured window, the correctness
check, and the result.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in the file that entry names, the configuration's trunk
architecture in ``arch/<arch>.py``, its traffic in
``traffic/<traffic>.json``, its limits in ``limits/<cell>.json`` and each
per-layer metric's reader in ``metrics/<metric>.py``. A cell's ``chips``
is honoured: on more than one, the weights are made sharded and the
engine serves through a mesh of those chips.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def cell_spec(name: str, root: str = ROOT) -> Dict[str, Any]:
    """The cell, its configuration, traffic, limits and metric entries."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def mine(metric):
        return name in metric.get("workloads", [name])

    return {
        "cell": cell,
        "config": load_json(os.path.join(root, cfg_entry["file"])),
        "traffic": load_json(os.path.join(HERE, "traffic",
                                          cell["traffic"] + ".json")),
        "limits": load_json(os.path.join(HERE, "limits", name + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def reader(metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + re.sub(r"\W", "_", metric), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices(chips: int, require_tpu: bool):
    """The chips of this run; without enough TPUs, an error naming what
    JAX found."""
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(
            f"perfbench needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s) ({devs[0].device_kind})")
    return devs[:chips]


def _percentile(values: List[float], q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if values else None


def end_to_end(name: str, records, t_open: float, t_close: float,
               seconds: float, setup_s: float) -> Optional[float]:
    """An end-to-end metric by name over the window's requests."""
    if name == "setup_s":
        return setup_s
    if name == "answer_tokens_per_s":
        tokens = sum(int(r.tokens.shape[-1]) for r in records
                     if r.failure is None and r.done is not None
                     and t_open <= r.done < t_close)
        return tokens / seconds
    m = re.fullmatch(r"(context|insight)_latency_p(\d+)_ms", name)
    if m:
        lat = [1000.0 * (r.done - r.due) for r in records
               if r.intent == m.group(1) and t_open <= r.due < t_close
               and r.done is not None and r.failure is None]
        return _percentile(lat, float(m.group(2)))
    raise KeyError(f"no end-to-end metric named {name!r}")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(spec: Dict[str, Any], seed: int, seconds: float, traced: bool,
        t_start: float, on_chip: bool = True,
        wrap: Optional[Callable] = None, control: bool = False
        ) -> Dict[str, Any]:
    """One run. ``on_chip=False`` (tests on the CPU) skips the look for
    the chip, its peaks and the persistent compile cache; ``wrap``
    replaces the stage proxy's class (tests break the timed path with
    it); ``control`` also reads the fp8 control's numbers on the same
    sample."""
    import jax
    from perfbench import arch, check, drive, model, peaks as peaks_mod
    from perfbench import readers
    from perfbench import trace as trace_mod, traffic as tr
    from repro.launch.cache import use_compile_cache

    cell, cfg, mix = spec["cell"], spec["config"], spec["traffic"]
    devs = devices(cell["chips"], on_chip)
    pk = devs[0].device_kind
    peaks = peaks_mod.peaks(pk if on_chip else "TPU v5 lite")
    if on_chip:
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    pcfg = model.pipeline_config(cfg)
    mesh = drive.model_mesh(devs) if len(devs) > 1 else None
    uavs = tr.fleet(mix)
    tiers = sorted({u.tier for u in uavs if u.tier})
    params, bottlenecks = model.make_weights(pcfg, cfg["bottleneck_tiers"],
                                             seed, mesh)
    frames = model.make_frames(pcfg, bottlenecks, tiers,
                               int(mix["frame_pool"]), seed)
    engine, proxy, sessions = drive.build_engine(pcfg, params, bottlenecks,
                                                 mix, uavs, traced, mesh)
    if wrap is not None:
        proxy.__class__ = wrap
    source = tr.RequestSource(mix, pcfg.llm.vocab_size,
                              int(mix["frame_pool"]), seed)
    driver = drive.Driver(engine, sessions, uavs, frames, source, traced,
                          t_base=time.perf_counter())
    drive.warm_up(driver, uavs)
    compiles = drive.CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_") if traced \
        else None
    state: Dict[str, Any] = {}

    def on_open() -> float:
        stats = engine.stats
        state["steps0"] = stats["inflight_steps"]
        state["slots0"] = stats["inflight_steps"] * stats["mean_live_slots"]
        proxy.reset()
        proxy.recording = True
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            state["span"] = jax.profiler.TraceAnnotation("window")
            state["span"].__enter__()
        compiles.on = True
        return time.perf_counter()

    def on_close() -> None:
        compiles.on = False
        proxy.recording = False
        stats = engine.stats
        state["steps"] = stats["inflight_steps"] - state["steps0"]
        state["slot_steps"] = (stats["inflight_steps"]
                               * stats["mean_live_slots"] - state["slots0"])
        state["model_shards"] = stats.get("model_shards", 1)
        if traced:
            state["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()

    if mix["loop"] == "closed":
        t_open, t_close = drive.run_closed(driver, mix, seconds, on_open,
                                           on_close)
    else:
        schedule = tr.open_schedule(mix, seconds, seed)
        t_open, t_close = drive.run_open(driver, schedule, seconds, on_open,
                                         on_close)
    setup_s = t_open - t_start
    records = driver.records
    mem = [d.memory_stats() or {} for d in devs]
    peak = max(m.get("peak_bytes_in_use", 0) for m in mem)

    if mix["loop"] == "closed":
        # a closed loop is judged on what came back in the window; what
        # is still in flight at the close is neither done nor failed
        in_window = [r for r in records if r.done is not None
                     and t_open <= r.done < t_close]
    else:
        in_window = [r for r in records if t_open <= r.due < t_close]
    failed = [r for r in in_window if r.failure is not None or r.done is None]
    late = [r.sent - r.due for r in records if t_open <= r.due < t_close]
    backlog = [r for r in records if r.due < t_close
               and (r.done is None or r.done >= t_close)]
    last = max((r.done for r in records if r.done is not None),
               default=t_close)
    by_intent = {}
    for r in in_window:
        c = by_intent.setdefault(r.intent, [0, 0, 0])
        c[0] += 1
        c[1] += r.failure is None and r.done is not None
        c[2] += r.failure is not None or r.done is None

    result: Dict[str, Any] = {
        "correct": False, "attempted": len(in_window),
        "failed": len(failed), "metrics": {},
        "device": {"platform": devs[0].platform, "kind": pk,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": int(peak)}}
    info = {"compiles_in_window": compiles.n,
            "generator_late_s_p50": _percentile(late, 50),
            "generator_late_s_max": max(late, default=None),
            "requests_by_intent": {k: dict(zip(
                ("attempted", "completed", "failed"), v))
                for k, v in by_intent.items()},
            "backlog_at_close": len(backlog),
            "last_answer_after_close_s": max(0.0, last - t_close),
            "decode_steps": state["steps"],
            "model_shards": state["model_shards"]}

    if traced:
        trace = trace_mod.load(trace_dir, [d.id for d in devs])
        span = [h for h in trace["host"] if h[2] == "window"]
        lo, hi = span[0][0], span[0][1]
        red = trace_mod.reduce(trace, lo, hi, readers.KERNELS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"trace": red, "counts": proxy.counts, "steps": state["steps"],
               "slot_steps": state["slot_steps"], "pcfg": pcfg,
               "arch": arch.resolve(cfg), "chips": len(devs), "peaks": peaks}
        for m in spec["per_layer"]:
            v = reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        for m in spec["end_to_end"]:
            v = end_to_end(m["name"], in_window, t_open, t_close, seconds,
                           setup_s)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}

    # free the program's state before the reference runs: the peak above
    # is the program's alone
    del engine, proxy, sessions, driver, on_open, on_close
    gc.collect()
    t0 = time.perf_counter()
    picked = check.sample(records, mix["sample"], seed)
    values = check.numbers(params, bottlenecks, cfg, frames, picked)
    checks = check.verdict(values, spec["limits"])
    info["check_s"] = time.perf_counter() - t0
    info["sampled"] = len(picked)
    result["correct"] = check.passed(checks)
    if control:
        info["control"] = check.numbers(params, bottlenecks, cfg, frames,
                                        picked, control=True)
        info["program"] = values
    result["checks"] = checks
    return {"result": result, "info": info}
