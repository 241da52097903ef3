"""Batched KV-cache serving engine: (a) prefill + flash-decode matches the
full-forward ``llm_reason`` fast path, (b) batched cloud stages match
per-packet calls, (c) the continuous-batching scheduler preserves
per-request results and ordering under mixed intents/tiers."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.lisa_mini import CONFIG as PCFG
from repro.core import DualStreamExecutor, bottleneck as bn, paper_lut, vlm
from repro.core.intent import Intent
from repro.data import floodseg
from repro.runtime.scheduler import MicrobatchScheduler, ServeRequest

# flash-decode kernel on the decode attention hot loop
FLASH_PCFG = dataclasses.replace(
    PCFG, llm=PCFG.llm.replace(use_flash_decode=True))


@pytest.fixture(scope="module")
def params():
    return vlm.init_lisa(PCFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def executor(params):
    lut = paper_lut()
    d = PCFG.sam.d_model
    bns = {t.name: bn.init_bottleneck(
        jax.random.PRNGKey(i), bn.BottleneckSpec(
            d, bn.rank_for_ratio(d, t.ratio, 4), 4))
        for i, t in enumerate(lut.tiers)}
    return DualStreamExecutor(pcfg=PCFG, params=params, bottlenecks=bns,
                              lut=lut)


def _ctx_query(params, batch=3, qlen=8, seed=1):
    ctx = jax.random.normal(
        jax.random.PRNGKey(seed), (batch, PCFG.clip_tokens, PCFG.llm.d_model))
    query = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, qlen), 0,
                               PCFG.llm.vocab_size)
    return ctx, query


# ---- (a) prefill + decode vs llm_reason ----


def test_prefill_plus_decode_matches_reason(params):
    """Prefill over [ctx; query[:-1]] + one flash-decode step of the final
    query token reproduces the single-shot llm_reason logits."""
    ctx, query = _ctx_query(params)
    ref_logits, ref_seg = vlm.llm_reason(params, FLASH_PCFG, ctx, query)
    _, _, cache = vlm.llm_prefill(params, FLASH_PCFG, ctx, query[:, :-1],
                                  width=PCFG.clip_tokens + query.shape[1])
    pos = jnp.int32(PCFG.clip_tokens + query.shape[1] - 1)
    logits, seg, _ = vlm.llm_decode_step(params, FLASH_PCFG, cache,
                                         query[:, -1:], pos)
    scale = float(jnp.max(jnp.abs(ref_logits))) + 1.0
    assert float(jnp.max(jnp.abs(ref_logits - logits))) < 2e-3 * scale
    seg_scale = float(jnp.max(jnp.abs(ref_seg))) + 1.0
    assert float(jnp.max(jnp.abs(ref_seg - seg))) < 2e-3 * seg_scale


def test_prefill_only_matches_reason(params):
    ctx, query = _ctx_query(params, seed=5)
    ref_logits, ref_seg = vlm.llm_reason(params, PCFG, ctx, query)
    logits, seg, cache = vlm.llm_prefill(params, PCFG, ctx, query)
    np.testing.assert_allclose(np.asarray(ref_logits), np.asarray(logits),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref_seg), np.asarray(seg),
                               atol=1e-5)
    assert cache["positions"].shape[1] == PCFG.clip_tokens + query.shape[1]


def test_generate_matches_naive_full_forward(params):
    """Greedy KV-cache generation emits the same tokens as re-running the
    full no-cache forward per new token (the seed serving semantics)."""
    ctx, query = _ctx_query(params, seed=9)
    T = 4
    tokens, logits0, seg = vlm.llm_generate(params, FLASH_PCFG, ctx, query, T)
    cur = query
    for t in range(T):
        logits, _ = vlm.llm_reason(params, PCFG, ctx, cur)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if t == 0:
            np.testing.assert_allclose(np.asarray(logits0),
                                       np.asarray(logits), atol=1e-5)
        assert bool(jnp.all(tokens[:, t] == nxt)), t
        cur = jnp.concatenate([cur, nxt[:, None]], axis=1)
    # <SEG> convention: generate's seg is the hidden state of the final
    # generated token == llm_reason's seg over [ctx; query; answer]
    _, ref_seg = vlm.llm_reason(params, PCFG, ctx, cur)
    scale = float(jnp.max(jnp.abs(ref_seg))) + 1.0
    assert float(jnp.max(jnp.abs(ref_seg - seg))) < 2e-3 * scale


def test_generate_seg_convention_consistent_at_t1(params):
    """T == 1 uses the same final-generated-token seg convention as T > 1."""
    ctx, query = _ctx_query(params, seed=13)
    tokens, _, seg = vlm.llm_generate(params, FLASH_PCFG, ctx, query, 1)
    full = jnp.concatenate([query, tokens], axis=1)
    _, ref_seg = vlm.llm_reason(params, PCFG, ctx, full)
    scale = float(jnp.max(jnp.abs(ref_seg))) + 1.0
    assert float(jnp.max(jnp.abs(ref_seg - seg))) < 2e-3 * scale


# ---- (b) batched cloud stages vs single-packet calls ----


def _make_requests(executor, n, seed=0):
    rng = np.random.RandomState(seed)
    lut = executor.lut
    reqs = []
    for i in range(n):
        kind = ("any" if i % 3 == 0 else "segment")
        b = floodseg.make_batch(rng, 1, kind, augment=False)
        images = jnp.asarray(b["images"])
        if kind == "any":
            pkt, _ = executor.edge_context(images, i, 0.0)
            intent = Intent.CONTEXT
        else:
            pkt = executor.edge_insight(images, lut.tiers[i % 2], i, 0.0)
            intent = Intent.INSIGHT
        reqs.append(ServeRequest(seq_id=i, intent=intent, packet=pkt,
                                 query=b["query"]))
    return reqs


def test_batched_insight_matches_single_calls(executor):
    reqs = [r for r in _make_requests(executor, 12)
            if r.intent is Intent.INSIGHT
            and r.packet.tier_name == executor.lut.tiers[0].name]
    assert len(reqs) >= 3
    packets = [r.packet for r in reqs[:3]]
    queries = [r.query for r in reqs[:3]]
    batched = executor.cloud_insight_batch(packets, queries)  # bucket 4: pads
    for (mask_b, logits_b), pkt, q in zip(batched, packets, queries):
        mask_1, logits_1 = executor.cloud_insight(pkt, q)
        np.testing.assert_allclose(mask_b, mask_1, atol=2e-4)
        np.testing.assert_allclose(logits_b, logits_1, atol=2e-4)


def test_batched_context_matches_single_calls(executor):
    reqs = [r for r in _make_requests(executor, 9)
            if r.intent is Intent.CONTEXT]
    packets = [r.packet for r in reqs]
    queries = [r.query for r in reqs]
    batched = executor.cloud_context_batch(packets, queries)
    for logits_b, pkt, q in zip(batched, packets, queries):
        np.testing.assert_allclose(logits_b, executor.cloud_context(pkt, q),
                                   atol=2e-4)


def test_bucket_compile_cache_reuse(executor):
    """Varying request counts within one bucket hit the same compiled
    stage — no new cache entries."""
    reqs = [r for r in _make_requests(executor, 16, seed=3)
            if r.packet.tier_name == executor.lut.tiers[0].name]
    assert len(reqs) >= 4
    executor.cloud_insight_batch([r.packet for r in reqs[:3]],
                                 [r.query for r in reqs[:3]])
    n0 = executor.num_compiled_stages
    executor.cloud_insight_batch([r.packet for r in reqs[:4]],
                                 [r.query for r in reqs[:4]])
    assert executor.num_compiled_stages == n0      # same (stage, tier, 4) key
    assert executor.bucket_for(3) == 4 and executor.bucket_for(5) == 8


# ---- (c) scheduler: ordering + per-request results under mixed intents ----


def test_scheduler_preserves_results_and_order(executor):
    reqs = _make_requests(executor, 10, seed=7)
    sched = MicrobatchScheduler(executor=executor, max_batch=4)
    results = sched.serve_all(reqs)
    assert [r.seq_id for r in results] == [r.seq_id for r in reqs]
    assert sched.n_requests == len(reqs)
    assert sched.n_microbatches < len(reqs)        # batching actually happened
    for req, res in zip(reqs, results):
        assert res.intent is req.intent
        if req.intent is Intent.INSIGHT:
            mask_1, logits_1 = executor.cloud_insight(req.packet, req.query)
            np.testing.assert_allclose(res.mask_logits, mask_1, atol=2e-4)
            np.testing.assert_allclose(res.answer_logits, logits_1, atol=2e-4)
        else:
            np.testing.assert_allclose(
                res.answer_logits, executor.cloud_context(req.packet,
                                                          req.query),
                atol=2e-4)


def test_scheduler_respects_row_cap_for_multirow_packets(executor):
    """Edge calls may pack several frames into one packet; the scheduler
    must cap microbatches by stacked content rows, not request count."""
    rng = np.random.RandomState(21)
    tier = executor.lut.tiers[0]
    reqs = []
    for i in range(6):
        b = floodseg.make_batch(rng, 4, "segment", augment=False)  # 4 rows
        pkt = executor.edge_insight(jnp.asarray(b["images"]), tier, i, 0.0)
        reqs.append(ServeRequest(seq_id=i, intent=Intent.INSIGHT, packet=pkt,
                                 query=b["query"]))
    sched = MicrobatchScheduler(executor=executor, max_batch=16)
    results = sched.serve_all(reqs)               # 24 rows > bucket cap 16
    assert [r.seq_id for r in results] == [r.seq_id for r in reqs]
    assert sched.n_microbatches >= 2              # row cap forced a split
    for res in results:
        assert res.mask_logits.shape[0] == 4


def test_scheduler_separates_mixed_query_lengths(executor):
    """Queries of different lengths can't stack; they must land in
    separate microbatches, not crash the concatenate."""
    rng = np.random.RandomState(31)
    packets, queries = [], []
    for i in range(4):
        b = floodseg.make_batch(rng, 1, "any", augment=False)
        pkt, _ = executor.edge_context(jnp.asarray(b["images"]), i, 0.0)
        packets.append(pkt)
        q = b["query"] if i % 2 == 0 else b["query"][:, :6]
        queries.append(q)
    reqs = [ServeRequest(seq_id=i, intent=Intent.CONTEXT, packet=p, query=q)
            for i, (p, q) in enumerate(zip(packets, queries))]
    sched = MicrobatchScheduler(executor=executor, max_batch=4)
    results = sched.serve_all(reqs)
    assert [r.seq_id for r in results] == [0, 1, 2, 3]
    assert sched.n_microbatches == 2          # one per query length


def test_oversized_direct_call_rounds_up(executor):
    """Per-packet callers may exceed the largest bucket (seed allowed any
    batch); the executor rounds up instead of failing."""
    rng = np.random.RandomState(41)
    b = floodseg.make_batch(rng, 17, "any", augment=False)
    pkt, _ = executor.edge_context(jnp.asarray(b["images"]), 0, 0.0)
    logits = executor.cloud_context(pkt, b["query"])
    assert logits.shape == (17, PCFG.llm.vocab_size)
    assert executor.bucket_for(17) == 32


def test_mixed_tier_batch_rejected(executor):
    reqs = [r for r in _make_requests(executor, 6, seed=23)
            if r.intent is Intent.INSIGHT]
    assert len({r.packet.tier_name for r in reqs}) == 2
    with pytest.raises(ValueError, match="mixed tiers"):
        executor.cloud_insight_batch([r.packet for r in reqs],
                                     [r.query for r in reqs])


def test_scheduler_generate_mode(executor):
    reqs = _make_requests(executor, 6, seed=11)
    sched = MicrobatchScheduler(executor=executor, max_batch=4, generate=True)
    results = sched.serve_all(reqs)
    assert [r.seq_id for r in results] == [r.seq_id for r in reqs]
    for req, res in zip(reqs, results):
        assert res.tokens is not None
        assert res.tokens.shape == (1, executor.max_new_tokens)
        if req.intent is Intent.INSIGHT:
            assert res.mask_logits is not None


# ---- paged shared-prefix serving bench mode (slow) ----


@pytest.mark.slow
def test_bench_serving_paged_mode_reports_prefix_reuse():
    """The bench's paged mode must show prefix reuse on the repeat-prefix
    per-UAV workload by its counts, on both streams: every frame after a
    UAV's first hits the prefix store (4 frames per UAV: 3 hits of 4),
    and the hits save prefill FLOPs and prefix KV bytes."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_serving", "--paged-smoke"],
        capture_output=True, text=True, timeout=560,
        env={**__import__("os").environ, "PYTHONPATH": "src:."})
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [l for l in out.stdout.splitlines()
            if l.startswith("serving/paged_admit_")]
    assert len(rows) == 2
    for kind in ("context", "insight"):
        row = next(r for r in rows if kind in r)
        fields = dict(f.split("=") for f in row.split(",")[2].split(";"))
        assert float(fields["prefix_hit_rate"]) == 0.75, row
        assert float(fields["prefill_flops_saved"]) > 0, row
        assert float(fields["kv_bytes_saved"]) > 0, row


@pytest.mark.slow
def test_bench_serving_spec_mode_reports_tokens_per_step():
    """The bench's speculative mode must report >= 1.5 tokens per
    verify step on repeat-prefix Context-drafted traffic (the warm
    Context weights draft for themselves, so acceptance is near-total)
    and refresh the machine-readable BENCH_serving.json artifact."""
    import json
    import os
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_serving", "--spec-smoke"],
        capture_output=True, text=True, timeout=560,
        env={**os.environ, "PYTHONPATH": "src:."})
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [l for l in out.stdout.splitlines()
            if l.startswith("serving/spec_insight")]
    assert len(rows) == 1
    fields = dict(f.split("=") for f in rows[0].split(",")[2].split(";"))
    assert float(fields["tokens_per_step"]) >= 1.5
    assert 0.0 < float(fields["acceptance_rate"]) <= 1.0
    assert int(fields["verify_steps"]) < int(fields["baseline_decode_steps"])
    art = os.path.join("benchmarks", "artifacts", "BENCH_serving.json")
    with open(art) as f:
        records = json.load(f)["records"]
    # smoke rows carry their own key so they never clobber full-run rows
    assert records["serving/spec_insight_smoke"]["tokens_per_step"] >= 1.5
