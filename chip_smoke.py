#!/usr/bin/env python3
"""Chip smoke test: serve LISA-7B-width requests on a TPU.

Drives the system's main path once through the entry points a user
calls -- ``AveryEngine(batching="inflight")``, ``session.submit(...)``,
``drain()`` -- with random weights made from ``--seed``:

* the paper's pipeline at its published widths (SAM ViT-H on 1024-px
  frames, CLIP ViT-B/16, a 4096-wide trunk with vocab 32000), the trunk
  cut in depth to what one v5e chip holds beside SAM, CLIP and the KV
  pool;
* Context requests from two operators whose repeats share a prefix, and
  Insight frames at two tiers, through the paged in-flight engine with
  the compiled decode kernels;
* the same requests through the jnp reference attention, compared with
  the kernel path;
* a speculative phase (paged verify kernel + contiguous draft kernel).

Run on one chip::

    python3 chip_smoke.py [--seed 0]

``--four-chips`` runs only the sharded path on a 1x4 "model" mesh: the
sharded paged decode/verify against the unsharded path at the one-chip
cut, then the full 32-layer trunk, sharded from the start, answering a
few requests.

Earlier lines report each phase's wall time (compile included; set-up
information, not a metric), peak device memory and whether the compiled
steps contain the Mosaic kernels. The last line is one JSON object,
``{"ok": true, "device": {...}}``. Any failed check raises and exits
non-zero; without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import gc
import json
import os
import sys
import time
from typing import Any, Dict, List, Sequence

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.lisa7b import CONFIG as LISA7B  # noqa: E402
from repro.core import DualStreamExecutor, Intent  # noqa: E402
from repro.core import profile as prof  # noqa: E402
from repro.core import vlm  # noqa: E402
from repro.core.paging import TRASH_PAGE, pages_for, prefix_positions  # noqa: E402
from repro.engine import AveryEngine, StaticTierPolicy  # noqa: E402
from repro.engine.speculative import SpeculativeConfig, _draft_fns  # noqa: E402

# trunk layers kept of lisa-7b's 32 on one chip: ~10.1 GB of bf16
# weights with SAM, CLIP, embed and head, leaving room for the KV pool,
# the draft ring and SAM's 4096-token attention (sized from the compiled
# stages' memory_analysis for a v5e)
TRUNK_LAYERS = 20
QUERY_LEN = 8
SLOTS = 8                 # in-flight decode slots (engine max_batch)
KV_PAGES = 128            # pre-sized pool: no growth, no recompiles
DRAFT_TOKENS = 3
# relative L2 error allowed between two bf16 paths' logits / masks
TOLERANCE = 5e-2
# (operator, Insight tier): one session each, so the two operators'
# Insight frames travel at two tiers
OPERATORS = (("uav-1", "High Accuracy"), ("uav-2", "Balanced"))
CONTEXT_PROMPTS = ("how many people are waiting on the rooftops?",
                   "is there a passable road to the shelter?")
INSIGHT_PROMPTS = ("segment the flooded road", "highlight the stranded car")


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def phase(name: str, out: Dict[str, float]):
    """Wall time of one phase, compilation included (set-up information,
    not a metric)."""
    t0 = time.perf_counter()
    yield
    out[name] = time.perf_counter() - t0
    log(f"[phase] {name}: {out[name]:.1f} s wall, compile included")


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def rel_err(a, b) -> float:
    """Relative L2 error of ``a`` against the reference ``b``."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---------------------------------------------------------------------------
# model and requests
# ---------------------------------------------------------------------------


def lisa7b_cut(layers: int):
    """lisa-7b at its published widths with the trunk cut to ``layers``."""
    return dataclasses.replace(
        LISA7B, llm=LISA7B.llm.replace(num_layers=layers))


def build_system(pcfg, seed: int, shardings: Any = None):
    """Random weights from ``seed``, made by one jitted init on the
    device(s) -- with ``shardings`` each chip only ever holds its share.
    Returns (params, bottlenecks by tier name, lut)."""
    init = jax.jit(functools.partial(vlm.init_lisa, pcfg),
                   out_shardings=shardings)
    params = jax.block_until_ready(init(jax.random.PRNGKey(seed)))
    return prof.random_init_system(pcfg, seed=seed, params=params)


def param_bytes(params) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))


@dataclasses.dataclass
class SmokeRequest:
    operator: str
    prompt: str
    intent: Intent
    images: Any
    query: np.ndarray
    time_s: float


def make_requests(pcfg, seed: int) -> List[SmokeRequest]:
    """Two Context requests per operator on one frame and query (the
    repeat shares the operator's prefix), then one Insight frame per
    operator. Frames are made on the device from ``seed``."""
    key = jax.random.PRNGKey(seed + 1)
    rng = np.random.RandomState(seed)
    size = pcfg.image_size

    def frame(i):
        return jax.random.uniform(jax.random.fold_in(key, i),
                                  (1, size, size, 3), jnp.float32)

    def query():
        return rng.randint(0, pcfg.llm.vocab_size,
                           (1, QUERY_LEN)).astype(np.int32)

    reqs, t = [], 0.0
    for i, ((op, _), prompt) in enumerate(zip(OPERATORS, CONTEXT_PROMPTS)):
        img, q = frame(i), query()
        for _ in range(2):
            reqs.append(SmokeRequest(op, prompt, Intent.CONTEXT, img, q, t))
            t += 0.25
    for i, ((op, _), prompt) in enumerate(zip(OPERATORS, INSIGHT_PROMPTS)):
        reqs.append(SmokeRequest(op, prompt, Intent.INSIGHT,
                                 frame(len(OPERATORS) + i), query(), t))
        t += 0.25
    return reqs


def mask_shape(pcfg) -> tuple:
    g = pcfg.image_size // pcfg.patch_size
    s = int(round(max(1, pcfg.mask_pixels_per_patch) ** 0.5))
    return (1, g * s, g * s)


# ---------------------------------------------------------------------------
# serving through the engine's front door
# ---------------------------------------------------------------------------


def serve(executor, lut, reqs: Sequence[SmokeRequest], **engine_kw
          ) -> Dict[str, Any]:
    """Serve ``reqs`` through a fresh in-flight engine and audit its pool:
    every operator's prefixes are released afterwards and no page may
    stay in use."""
    engine = AveryEngine(lut=lut, executor=executor, batching="inflight",
                         max_batch=SLOTS, kv_pages=KV_PAGES, **engine_kw)
    sessions = {op: engine.session(op, policy=StaticTierPolicy(tier))
                for op, tier in OPERATORS}
    futures = [sessions[r.operator].submit(prompt=r.prompt, images=r.images,
                                           query=r.query, time_s=r.time_s)
               for r in reqs]
    engine.drain()
    responses = [f.result() for f in futures]
    stats = engine.stats
    for s in sessions.values():
        s.close()
    pages = engine.kv_pool.check_invariants()
    check(pages["pages_in_use"] == 0,
          f"{pages['pages_in_use']} KV pages leaked after release")
    return {"responses": responses, "stats": stats}


def check_responses(pcfg, reqs: Sequence[SmokeRequest], responses,
                    label: str) -> None:
    """Every future resolved feasible, with finite logits of the right
    shape, a full answer, and (Insight) a finite mask of the right
    shape."""
    V = pcfg.llm.vocab_size
    T = np.asarray(responses[0].tokens).shape[-1]
    for r, resp in zip(reqs, responses):
        tag = f"{label}: request {resp.request_id} ({r.intent.name})"
        check(resp.failure is None and resp.feasible,
              f"{tag} failed: {resp.failure}")
        check(resp.intent is r.intent,
              f"{tag} classified as {resp.intent.name}")
        logits = np.asarray(resp.answer_logits, np.float32)
        check(logits.shape == (1, V), f"{tag} logits shape {logits.shape}")
        check(bool(np.isfinite(logits).all()), f"{tag} non-finite logits")
        tokens = np.asarray(resp.tokens)
        check(tokens.shape == (1, T) and T > 0,
              f"{tag} tokens shape {tokens.shape}")
        if r.intent is Intent.INSIGHT:
            mask = np.asarray(resp.mask_logits, np.float32)
            check(mask.shape == mask_shape(pcfg),
                  f"{tag} mask shape {mask.shape} != {mask_shape(pcfg)}")
            check(bool(np.isfinite(mask).all()), f"{tag} non-finite mask")
    hits = sum(bool(resp.prefix_hit) for resp in responses)
    check(hits >= 1, f"{label}: no request hit a shared prefix")
    log(f"[check] {label}: {len(responses)} requests served feasible, "
        f"finite; {hits} prefix hits")


def token_agreement(a, b) -> float:
    """Fraction of answer positions where two runs emitted one token."""
    same = [np.asarray(x.tokens) == np.asarray(y.tokens) for x, y in zip(a, b)]
    return float(np.mean(np.concatenate([s.ravel() for s in same])))


def compare_paths(reqs, kernel, reference) -> Dict[str, float]:
    """The kernel path against the jnp reference on the same requests:
    first-token logits and, where both emitted the same answer, the
    Insight masks (read from the decode step's final <SEG> state) within
    ``TOLERANCE``; the greedy-token agreement is reported."""
    logit_err = max(rel_err(k.answer_logits, r.answer_logits)
                    for k, r in zip(kernel, reference))
    mask_errs = []
    for q, k, r in zip(reqs, kernel, reference):
        if q.intent is Intent.INSIGHT and np.array_equal(k.tokens, r.tokens):
            mask_errs.append(rel_err(k.mask_logits, r.mask_logits))
    agree = token_agreement(kernel, reference)
    log(f"[compare] kernel vs reference engine: greedy-token agreement "
        f"{agree:.3f}; first-token logits rel err {logit_err:.2e}; "
        f"{len(mask_errs)} Insight masks compared, max rel err "
        f"{max(mask_errs, default=0.0):.2e}")
    check(logit_err <= TOLERANCE,
          f"first-token logits differ: rel err {logit_err:.3e}")
    check(all(e <= TOLERANCE for e in mask_errs),
          f"Insight masks differ: rel errs {mask_errs}")
    return {"engine_token_agreement": agree, "engine_logits_err": logit_err,
            "engine_mask_err": max(mask_errs, default=0.0)}


# ---------------------------------------------------------------------------
# stage-level agreement: the same inputs through two executors
# ---------------------------------------------------------------------------


def _stage_tables(pcfg, page: int, T: int):
    prefix_len = pcfg.clip_tokens + QUERY_LEN
    n_pre, n_priv = pages_for(prefix_len, page), pages_for(T, page)
    pre_ids = list(range(1, 1 + n_pre))
    table = np.full((SLOTS, n_pre + n_priv), TRASH_PAGE, np.int32)
    table[0] = pre_ids + list(range(1 + n_pre, 1 + n_pre + n_priv))
    positions = np.full((SLOTS, (n_pre + n_priv) * page), -1, np.int32)
    positions[0, :n_pre * page] = prefix_positions(prefix_len, n_pre, page)
    return prefix_len, n_pre * page, pre_ids, table, positions


def _prefilled_pool(ex, ctx, query, pre_ids):
    """One prefix prefill written into a fresh ``KV_PAGES`` pool, placed
    the way the executor's engine would place it."""
    logits0, paged = ex.cloud_prefix(ctx, query)
    kv = jax.tree.map(lambda a: jnp.zeros((a.shape[0], KV_PAGES)
                                          + a.shape[2:], a.dtype), paged)
    place = getattr(ex, "place_pool", None)
    if place is not None:
        kv = place(kv)
    return np.asarray(logits0), ex.pool_write(kv, paged, pre_ids)


def paged_agreement(pcfg, reference, test, ctx, query) -> Dict[str, float]:
    """Teacher-forced paged decode steps, then one verify chunk, through
    the executors' stage API (the calls ``InflightDecoder`` makes, at its
    shapes), with the reference's greedy tokens fed to both. Reports the
    first-token logits' error, each stage's worst logits error and its
    greedy-token agreement."""
    T, C = reference.max_new_tokens, DRAFT_TOKENS + 1
    prefix_len, base, pre_ids, table, positions = _stage_tables(
        pcfg, reference.page_size, T)
    l0_ref, kv_ref = _prefilled_pool(reference, ctx, query, pre_ids)
    l0_test, kv_test = _prefilled_pool(test, ctx, query, pre_ids)
    fed = [int(np.argmax(l0_ref[0]))]
    dec_ref, dec_errs, dec_agree = [], [], []
    for i in range(T):
        toks = np.zeros((SLOTS, 1), np.int32)
        pos = np.zeros((SLOTS,), np.int32)
        ws = np.zeros((SLOTS,), np.int32)
        toks[0, 0], pos[0], ws[0] = fed[i], prefix_len + i, base + i
        lr, _, kv_ref = reference.cloud_decode_rows(kv_ref, table, positions,
                                                    toks, pos, ws)
        lt, _, kv_test = test.cloud_decode_rows(kv_test, table, positions,
                                                toks, pos, ws)
        positions[0, ws[0]] = pos[0]
        lr, lt = np.asarray(lr)[0], np.asarray(lt)[0]
        dec_ref.append(lr)
        dec_errs.append(rel_err(lt, lr))
        dec_agree.append(int(np.argmax(lt)) == int(np.argmax(lr)))
        fed.append(int(np.argmax(lr)))
    # verify: the chunk [token 0, greedy continuations] on fresh pools
    _, _, _, table, positions = _stage_tables(pcfg, reference.page_size, T)
    _, kv_ref = _prefilled_pool(reference, ctx, query, pre_ids)
    _, kv_test = _prefilled_pool(test, ctx, query, pre_ids)
    toks = np.zeros((SLOTS, C), np.int32)
    toks[0] = fed[:C]
    pos = np.zeros((SLOTS,), np.int32)
    ws = np.zeros((SLOTS,), np.int32)
    clens = np.ones((SLOTS,), np.int32)
    pos[0], ws[0], clens[0] = prefix_len, base, C
    vr, _, _ = reference.cloud_verify_rows(kv_ref, table, positions, toks,
                                           pos, ws, clens)
    vt, _, _ = test.cloud_verify_rows(kv_test, table, positions, toks, pos,
                                      ws, clens)
    vr, vt = np.asarray(vr)[0], np.asarray(vt)[0]
    out = {
        "first_token_err": rel_err(l0_test, l0_ref),
        "decode_err": max(dec_errs),
        "decode_agreement": float(np.mean(dec_agree)),
        "verify_err": max(rel_err(vt[i], vr[i]) for i in range(C)),
        "verify_agreement": float(np.mean(
            np.argmax(vt, -1) == np.argmax(vr, -1))),
        # the verify chunk re-scores the decode steps' inputs
        "verify_vs_decode_err": max(rel_err(vr[i], dec_ref[i])
                                    for i in range(min(C, T))),
    }
    return out


def draft_agreement(pcfg, params, ctx, query, T: int) -> Dict[str, float]:
    """The contiguous-cache decode step (the draft model's) with the
    kernel against the jnp reference: one prefill, then teacher-forced
    steps at the ``DraftModel``'s shapes, so the kernel side reuses the
    speculative phase's compiled draft step."""
    width = pcfg.clip_tokens + QUERY_LEN + T + DRAFT_TOKENS
    cfgs = [dataclasses.replace(pcfg, llm=pcfg.llm.replace(
        use_flash_decode=flag)) for flag in (True, False)]
    prefill, step_kernel, insert = _draft_fns(cfgs[0], width)
    step_ref = _draft_fns(cfgs[1], width)[1]
    logits0, _, row = prefill(params, jnp.asarray(ctx), jnp.asarray(query))
    cache = {"groups": jax.tree.map(
        lambda a: jnp.zeros((a.shape[0], SLOTS) + a.shape[2:], a.dtype),
        row["groups"]),
        "positions": jnp.full((SLOTS, width), -1, jnp.int32)}
    cache = insert(cache, row, jnp.int32(0))
    ck, cr = cache, cache
    tok = int(np.argmax(np.asarray(logits0)[0]))
    errs, agree = [], []
    for i in range(T):
        toks = np.zeros((SLOTS, 1), np.int32)
        pos = np.full((SLOTS,), width - 1, np.int32)
        toks[0, 0], pos[0] = tok, pcfg.clip_tokens + QUERY_LEN + i
        args = (jnp.asarray(toks), jnp.asarray(pos))
        lk, _, ck = step_kernel(params, ck, *args)
        lr, _, cr = step_ref(params, cr, *args)
        lk, lr = np.asarray(lk)[0], np.asarray(lr)[0]
        errs.append(rel_err(lk, lr))
        agree.append(int(np.argmax(lk)) == int(np.argmax(lr)))
        tok = int(np.argmax(lr))
    has_kernel = "tpu_custom_call" in step_kernel.lower(
        params, ck, *args).compile().as_text()
    return {"draft_err": max(errs), "draft_agreement": float(np.mean(agree)),
            "draft_step_kernel": has_kernel}


def decode_has_kernel(ex, pcfg, ctx, query) -> Dict[str, bool]:
    """Whether the executor's compiled paged decode and verify steps
    contain the Mosaic kernels (``tpu_custom_call``); the shapes are the
    engine's, so the compiled executables are the ones it ran."""
    T, C = ex.max_new_tokens, DRAFT_TOKENS + 1
    prefix_len, base, pre_ids, table, positions = _stage_tables(
        pcfg, ex.page_size, T)
    _, kv = _prefilled_pool(ex, ctx, query, pre_ids)
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    z = np.zeros((SLOTS,), np.int32)
    dec = ex._decode_paged.lower(
        ex.params, kv, i32(table), i32(positions),
        i32(np.zeros((SLOTS, 1))), i32(z), i32(z)).compile()
    ver = ex._verify_paged.lower(
        ex.params, kv, i32(table), i32(positions),
        i32(np.zeros((SLOTS, C))), i32(z), i32(z),
        i32(np.ones((SLOTS,)))).compile()
    return {"decode_step_kernel": "tpu_custom_call" in dec.as_text(),
            "verify_step_kernel": "tpu_custom_call" in ver.as_text()}


def check_stage_errors(res: Dict[str, float], label: str) -> None:
    log(f"[compare] {label}: " + ", ".join(
        f"{k} {v:.3e}" if isinstance(v, float) else f"{k} {v}"
        for k, v in res.items()))
    for k, v in res.items():
        if k.endswith("_err"):
            check(v <= TOLERANCE, f"{label}: {k} {v:.3e} > {TOLERANCE}")


# ---------------------------------------------------------------------------
# the phases
# ---------------------------------------------------------------------------


def serving_phases(pcfg, params, bottlenecks, lut, seed: int,
                   times: Dict[str, float]) -> Dict[str, Any]:
    """The one-chip main path at whatever size ``pcfg`` gives: serve the
    request set with the kernels, again through the jnp reference, then
    speculatively; compare the paths at the engine and at the stage
    level. Raises ``SmokeFailure`` on any failed check."""
    reqs = make_requests(pcfg, seed)
    kernel_ex = DualStreamExecutor(pcfg=pcfg, params=params,
                                   bottlenecks=bottlenecks, lut=lut)
    ref_ex = DualStreamExecutor(pcfg=pcfg, params=params,
                                bottlenecks=bottlenecks, lut=lut,
                                flash_decode=False)
    with phase("serve, kernels", times):
        kern = serve(kernel_ex, lut, reqs)
    check_responses(pcfg, reqs, kern["responses"], "kernel path")
    with phase("serve, jnp reference", times):
        ref = serve(ref_ex, lut, reqs)
    check_responses(pcfg, reqs, ref["responses"], "reference path")
    summary = compare_paths(reqs, kern["responses"], ref["responses"])

    spec_reqs = [next(r for r in reqs if r.intent is intent)
                 for intent in (Intent.CONTEXT, Intent.INSIGHT)]
    with phase("serve, speculative", times):
        spec = serve(kernel_ex, lut, spec_reqs,
                     speculative=SpeculativeConfig(
                         draft_tokens=DRAFT_TOKENS))
    stats = spec["stats"]
    check(stats["spec_drafted"] > 0, "speculative phase drafted nothing")
    plain = [kern["responses"][reqs.index(r)] for r in spec_reqs]
    spec_agree = token_agreement(spec["responses"], plain)
    log(f"[check] speculative: {stats['spec_drafted']:.0f} drafted, "
        f"acceptance {stats['spec_acceptance_rate']:.3f}, "
        f"{stats['spec_tokens_per_step']:.2f} tokens per verify step; "
        f"token agreement with plain decode {spec_agree:.3f}")
    for resp in spec["responses"]:
        check(resp.failure is None and resp.speculative,
              f"speculative request {resp.request_id}: {resp.failure}")
        check(bool(np.isfinite(np.asarray(resp.answer_logits,
                                          np.float32)).all()),
              f"speculative request {resp.request_id}: non-finite logits")

    with phase("compare stages, kernel vs reference", times):
        _, ctx = kernel_ex.edge_context(reqs[0].images, 0, 0.0)
        stages = paged_agreement(pcfg, ref_ex, kernel_ex, ctx, reqs[0].query)
        stages.update(draft_agreement(pcfg, params, ctx, reqs[0].query,
                                      kernel_ex.max_new_tokens))
        stages.update(decode_has_kernel(kernel_ex, pcfg, ctx,
                                        reqs[0].query))
    check_stage_errors(stages, "kernel vs reference stages")
    summary.update(stages)
    summary["spec_token_agreement"] = spec_agree
    summary["prefix_hits"] = kern["stats"]["prefix_hits"]
    return summary


def sharded_phases(seed: int, times: Dict[str, float], cut=None,
                   full=LISA7B) -> Dict[str, Any]:
    """The four-chip path on a 1 x n "model" mesh: (a) at the one-chip
    cut, the sharded paged decode and verify against the unsharded
    executor on one chip; (b) the full trunk, built sharded from the
    start, answering the request set through ``AveryEngine(mesh=...)``.
    The sharded context serves the jnp reference attention (running the
    kernels under ``shard_map`` is a later step)."""
    from repro.launch.mesh import make_local_mesh
    from repro.sharding import specs as sh
    from repro.sharding.serving import ShardedServingContext
    cut = lisa7b_cut(TRUNK_LAYERS) if cut is None else cut
    n = len(jax.devices())
    mesh = make_local_mesh(model=n)
    log(f"[model] mesh {dict(mesh.shape)}; sharded serving runs the jnp "
        f"reference attention, partitioned by XLA over "
        f"{full.llm.num_kv_heads // n} kv heads per chip")
    out: Dict[str, Any] = {}

    def cut_comparison():
        pcfg = cut
        params, bns, lut = build_system(pcfg, seed)
        ex = DualStreamExecutor(pcfg=pcfg, params=params, bottlenecks=bns,
                                lut=lut)
        ctx_sh = ShardedServingContext(ex, mesh)
        key = jax.random.PRNGKey(seed + 2)
        ctx = jax.random.normal(key, (1, pcfg.clip_tokens,
                                      pcfg.llm.d_model), pcfg.llm.adtype)
        query = np.random.RandomState(seed).randint(
            0, pcfg.llm.vocab_size, (1, QUERY_LEN)).astype(np.int32)
        return paged_agreement(pcfg, ex, ctx_sh, ctx, query)

    with phase(f"sharded vs one chip at {cut.llm.num_layers} layers",
               times):
        res = cut_comparison()
    gc.collect()
    log(f"[memory] {sum(a.nbytes for a in jax.live_arrays()) / 1e9:.2f} "
        f"GB of arrays live after the cut comparison")
    check_stage_errors(res, "sharded vs unsharded stages")
    out.update(res)

    with phase(f"sharded serve, {full.llm.num_layers} layers", times):
        abstract = jax.eval_shape(
            lambda: vlm.init_lisa(full, jax.random.PRNGKey(0)))
        shardings = sh.to_shardings(
            mesh, sh.param_specs(full.llm, abstract, mesh))
        params, bns, lut = build_system(full, seed, shardings=shardings)
        log(f"[model] {full.name} full trunk: {full.llm.num_layers} "
            f"layers, {param_bytes(params) / 1e9:.2f} GB of weights "
            f"sharded over {n} chips")
        ex = DualStreamExecutor(pcfg=full, params=params, bottlenecks=bns,
                                lut=lut)
        reqs = make_requests(full, seed)
        served = serve(ex, lut, reqs, mesh=mesh)
    check_responses(full, reqs, served["responses"], "sharded full trunk")
    out["sharded_prefix_hits"] = served["stats"]["prefix_hits"]
    return out


def peak_bytes() -> List[int]:
    return [d.memory_stats().get("peak_bytes_in_use", 0)
            for d in jax.local_devices()]


def main(argv: Sequence[str] = ()) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on a 1x4 mesh")
    args = ap.parse_args(argv)

    from repro.launch.cache import use_compile_cache
    log(f"[setup] compilation cache: {use_compile_cache()}")
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"[setup] no TPU: JAX found {dev.platform} devices; this smoke "
            f"test runs only on the chip")
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        log(f"[setup] --four-chips needs 4 chips, found {len(devices)}")
        return 2
    log(f"[setup] device {dev.device_kind} x {len(devices)}, "
        f"jax {jax.__version__}")
    times: Dict[str, float] = {}

    if args.four_chips:
        summary = sharded_phases(args.seed, times)
    else:
        pcfg = lisa7b_cut(TRUNK_LAYERS)
        log(f"[model] {pcfg.name} published widths: SAM ViT-H "
            f"{pcfg.sam.d_model}d x {pcfg.sam.num_layers} blocks on "
            f"{pcfg.image_size}px frames ({pcfg.sam_tokens} tokens), CLIP "
            f"ViT-B/16 {pcfg.clip.d_model}d, trunk d_model "
            f"{pcfg.llm.d_model}, {pcfg.llm.num_heads} heads, d_ff "
            f"{pcfg.llm.d_ff}, vocab {pcfg.llm.vocab_size}")
        log(f"[model] cut: trunk depth {LISA7B.llm.num_layers} -> "
            f"{TRUNK_LAYERS} layers (nothing else)")
        with phase("build weights", times):
            params, bns, lut = build_system(pcfg, args.seed)
        log(f"[model] {param_bytes(params) / 1e9:.2f} GB of weights")
        summary = serving_phases(pcfg, params, bns, lut, args.seed, times)
        for key in ("decode_step_kernel", "verify_step_kernel",
                    "draft_step_kernel"):
            log(f"[kernels] compiled {key.replace('_kernel', '')} contains "
                f"tpu_custom_call: {summary[key]}")
            check(summary[key], f"{key}: no Mosaic kernel on the TPU")
    peaks = peak_bytes()
    log("[memory] peak_bytes_in_use per chip: "
        + ", ".join(f"{p / 1e9:.2f} GB" for p in peaks))
    log(f"[setup] total wall {sum(times.values()):.1f} s over "
        f"{len(times)} phases")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
