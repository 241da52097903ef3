"""Decode path must reproduce full-sequence forward logits step by step —
validates cache bookkeeping, rotary offsets, ring buffers, SSM recurrence
and MLA absorbed-matmul decode across every attention/mixer family."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.models import (HybridConfig, MLAConfig, MoEConfig, ModelConfig,
                          SSMConfig, decode_step, forward, init_cache,
                          init_params)

B, S = 2, 16

CASES = [
    ModelConfig(name="gqa", arch_type="dense", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=97),
    ModelConfig(name="sw", arch_type="dense", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=97,
                sliding_window=8),
    ModelConfig(name="mla", arch_type="dense", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=97,
                attn_type="mla",
                mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                              qk_nope_head_dim=16, qk_rope_head_dim=8,
                              v_head_dim=16)),
    ModelConfig(name="moe", arch_type="moe", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=97,
                moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=32,
                              capacity_factor=8.0)),
    ModelConfig(name="mamba1", arch_type="ssm", num_layers=2, d_model=64,
                num_heads=1, num_kv_heads=1, d_ff=0, vocab_size=97,
                attn_type="none", rope_style="none",
                ssm=SSMConfig(version=1, state_size=4)),
    ModelConfig(name="mamba2", arch_type="ssm", num_layers=2, d_model=64,
                num_heads=1, num_kv_heads=1, d_ff=0, vocab_size=97,
                attn_type="none", rope_style="none",
                ssm=SSMConfig(version=2, state_size=8, head_dim=16)),
    ModelConfig(name="hybrid", arch_type="hybrid", num_layers=4, d_model=64,
                num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=97,
                ssm=SSMConfig(version=2, state_size=8, head_dim=16),
                hybrid=HybridConfig(attn_every=2)),
]


@pytest.mark.parametrize("cfg", CASES, ids=lambda c: c.name)
def test_decode_matches_forward(cfg):
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    logits_full, *_ = forward(params, cfg, {"tokens": tokens})
    if cfg.sliding_window:
        # full forward masks by window; decode must agree within the window
        pass
    cache = init_cache(cfg, B, S if not cfg.sliding_window
                       else cfg.sliding_window)
    dec = jax.jit(lambda p, c, t, pos: decode_step(p, cfg, c, t, pos))
    outs = []
    for t in range(S):
        lg, cache = dec(params, cache, tokens[:, t:t + 1], jnp.int32(t))
        outs.append(lg[:, 0])
    logits_dec = jnp.stack(outs, axis=1)
    err = float(jnp.max(jnp.abs(logits_full - logits_dec)))
    scale = float(jnp.max(jnp.abs(logits_full))) + 1.0
    assert err < 2e-3 * scale, f"{cfg.name}: decode mismatch {err}"


# ---- paged serving cache vs the contiguous generate path ----


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "flash"])
def test_paged_decode_matches_llm_generate(flash):
    """The paged KV cache (page pool + page tables, the serving engine's
    layout) is token-exact against the contiguous ``llm_generate``: same
    greedy tokens, same first-token logits, same <SEG> embedding. Pages
    are laid out non-contiguously and a second batch row shares the
    prefix pages read-only — the multi-UAV serving configuration."""
    import numpy as np

    from repro.configs.lisa_mini import CONFIG as PCFG
    from repro.core import vlm
    from repro.core.paging import pages_for, prefix_positions

    pcfg = dataclasses.replace(
        PCFG, llm=PCFG.llm.replace(use_flash_decode=flash))
    params = vlm.init_lisa(pcfg, jax.random.PRNGKey(0))
    qlen, T, page = 8, 4, 16
    ctx = jax.random.normal(jax.random.PRNGKey(1),
                            (1, pcfg.clip_tokens, pcfg.llm.d_model))
    query = jax.random.randint(jax.random.PRNGKey(2), (1, qlen), 0,
                               pcfg.llm.vocab_size)
    tokens_ref, logits0_ref, seg_ref = vlm.llm_generate(params, pcfg, ctx,
                                                        query, T)

    S = pcfg.clip_tokens + qlen
    n_prefix, n_private = pages_for(S, page), pages_for(T, page)
    logits0, _, paged = vlm.llm_prefill_paged(params, pcfg, ctx, query, page)
    np.testing.assert_allclose(np.asarray(logits0), np.asarray(logits0_ref),
                               atol=1e-5)

    # pool: trash page 0, then scattered prefix/private pages; two rows
    # share the prefix read-only, each with its own private decode pages
    B = 2
    P = 1 + n_prefix + B * n_private
    prefix_ids = np.arange(1, 1 + n_prefix)
    pool = {"groups": [jax.tree.map(
        lambda a: jnp.zeros((a.shape[0], P) + a.shape[3:], a.dtype)
        .at[:, prefix_ids].set(a[:, 0]), paged["groups"][0])]}
    pt = np.zeros((B, n_prefix + n_private), np.int32)
    positions = np.full((B, (n_prefix + n_private) * page), -1, np.int32)
    for b in range(B):
        priv = 1 + n_prefix + b * n_private
        pt[b] = list(prefix_ids) + list(range(priv, priv + n_private))
        positions[b, :n_prefix * page] = prefix_positions(S, n_prefix, page)

    toks = [int(jnp.argmax(logits0[0]))]
    base = n_prefix * page
    seg = None
    for t in range(T):
        tk = np.full((B, 1), toks[-1], np.int32)
        pos = np.full((B,), S + t, np.int32)
        ws = np.full((B,), base + t, np.int32)
        logits, seg, pool, _ = vlm.llm_decode_step_paged(
            params, pcfg, pool, pt, positions, tk, pos, ws)
        positions[:, base + t] = S + t
        if t < T - 1:
            toks.append(int(jnp.argmax(logits[0])))
    assert np.array_equal(np.asarray(tokens_ref)[0], np.asarray(toks))
    # both rows decoded the same sequence; row 1 through shared prefix
    # pages — identical hidden states prove the pages were untouched
    seg = np.asarray(seg)
    scale = float(jnp.max(jnp.abs(seg_ref))) + 1.0
    assert float(np.max(np.abs(seg[0] - np.asarray(seg_ref)[0]))) \
        < 2e-3 * scale
    np.testing.assert_allclose(seg[0], seg[1], atol=1e-6)


# ---- speculative decoding: multi-token verify + draft/accept/rollback ----


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "flash"])
def test_verify_step_matches_sequential_decode_steps(flash):
    """One multi-token verify pass (``llm_verify_step_paged``) over a
    C-token chunk reproduces C successive single-token paged decode
    steps position by position — including a row whose chunk is shorter
    than C (pad entries write to the trash page and change nothing)."""
    import numpy as np

    from repro.configs.lisa_mini import CONFIG as PCFG
    from repro.core import vlm
    from repro.core.paging import pages_for, prefix_positions

    pcfg = dataclasses.replace(
        PCFG, llm=PCFG.llm.replace(use_flash_decode=flash))
    params = vlm.init_lisa(pcfg, jax.random.PRNGKey(0))
    qlen, T, page = 8, 4, 16
    ctx = jax.random.normal(jax.random.PRNGKey(1),
                            (1, pcfg.clip_tokens, pcfg.llm.d_model))
    query = jax.random.randint(jax.random.PRNGKey(2), (1, qlen), 0,
                               pcfg.llm.vocab_size)
    S = pcfg.clip_tokens + qlen
    n_prefix, n_private = pages_for(S, page), pages_for(T, page)
    logits0, _, paged = vlm.llm_prefill_paged(params, pcfg, ctx, query, page)

    B = 2
    P = 1 + n_prefix + B * n_private
    prefix_ids = np.arange(1, 1 + n_prefix)
    def fresh_pool():
        return {"groups": [jax.tree.map(
            lambda a: jnp.zeros((a.shape[0], P) + a.shape[3:], a.dtype)
            .at[:, prefix_ids].set(a[:, 0]), paged["groups"][0])]}
    pt = np.zeros((B, n_prefix + n_private), np.int32)
    positions = np.full((B, (n_prefix + n_private) * page), -1, np.int32)
    for b in range(B):
        priv = 1 + n_prefix + b * n_private
        pt[b] = list(prefix_ids) + list(range(priv, priv + n_private))
        positions[b, :n_prefix * page] = prefix_positions(S, n_prefix, page)
    base = n_prefix * page

    # oracle: T sequential single-token paged decode steps
    pool = fresh_pool()
    pos_seq = positions.copy()
    toks = [int(jnp.argmax(logits0[0]))]
    seq_logits, seq_seg = [], []
    for t in range(T):
        tk = np.full((B, 1), toks[-1], np.int32)
        lg, sg, pool, _ = vlm.llm_decode_step_paged(
            params, pcfg, pool, pt, pos_seq, tk,
            np.full((B,), S + t, np.int32), np.full((B,), base + t,
                                                    np.int32))
        pos_seq[:, base + t] = S + t
        seq_logits.append(np.asarray(lg))
        seq_seg.append(np.asarray(sg))
        toks.append(int(jnp.argmax(lg[0])))

    # one verify chunk: row 0 carries all T tokens, row 1 only 2 (padded)
    chunk = np.tile(np.asarray(toks[:T], np.int32), (B, 1))
    clens = np.asarray([T, 2], np.int32)
    lgv, segv, _, _ = vlm.llm_verify_step_paged(
        params, pcfg, fresh_pool(), pt, positions, chunk,
        np.full((B,), S, np.int32), np.full((B,), base, np.int32), clens)
    lgv, segv = np.asarray(lgv), np.asarray(segv)
    scale = max(float(np.max(np.abs(l))) for l in seq_logits) + 1.0
    for b in range(B):
        for i in range(int(clens[b])):
            assert float(np.max(np.abs(lgv[b, i] - seq_logits[i][b]))) \
                < 2e-3 * scale, (b, i)
            sscale = float(np.max(np.abs(seq_seg[i][b]))) + 1.0
            assert float(np.max(np.abs(segv[b, i] - seq_seg[i][b]))) \
                < 2e-3 * sscale, (b, i)


@pytest.fixture(scope="module")
def spec_executor():
    """Small serving executor for the speculative-decode pins (tiny
    pages so draft overhangs cross page boundaries and rollback really
    fires)."""
    import numpy as np

    from repro.configs.lisa_mini import CONFIG as PCFG
    from repro.core import DualStreamExecutor, paper_lut, profile as prof
    lut = paper_lut()
    params, bns, _ = prof.random_init_system(PCFG, lut=lut)
    return DualStreamExecutor(pcfg=PCFG, params=params, bottlenecks=bns,
                              lut=lut, max_new_tokens=6,
                              flash_decode=False, page_size=4)


def _spec_requests(executor, n, seed):
    import numpy as np

    from repro.core.intent import Intent
    from repro.data import floodseg
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        kind = "any" if i % 3 == 2 else "segment"
        b = floodseg.make_batch(rng, 1, kind, augment=False)
        img = jnp.asarray(b["images"])
        if kind == "any":
            pkt, _ = executor.edge_context(img, i, 0.0)
            out.append((pkt, b["query"], Intent.CONTEXT))
        else:
            pkt = executor.edge_insight(img, executor.lut.tiers[i % 2], i,
                                        0.0)
            out.append((pkt, b["query"], Intent.INSIGHT))
    return out


def _assert_matches_generate(executor, done, reqs):
    import numpy as np

    from repro.core.intent import Intent
    for i, (pkt, q, it) in enumerate(reqs):
        out = executor.cloud_generate_batch([pkt], [q])[0]
        assert np.array_equal(done[i]["tokens"], out[-1]), i
        if it is Intent.INSIGHT:
            np.testing.assert_allclose(done[i]["mask_logits"], out[0],
                                       atol=3e-4)
        np.testing.assert_allclose(done[i]["answer_logits"], out[-2]
                                   if it is Intent.CONTEXT else out[1],
                                   atol=3e-4)


@pytest.mark.parametrize("shared_draft", [True, False],
                         ids=["context_draft", "divergent_draft"])
def test_speculative_decode_token_exact_with_llm_generate(spec_executor,
                                                          shared_draft):
    """Greedy speculative decode through the in-flight batch is token-
    exact with the one-shot ``llm_generate`` path — with the warm
    Context-stream weights drafting (near-total acceptance) and with a
    divergent random draft (rejections force corrections + page
    rollback), under slot reuse (more requests than slots)."""
    import numpy as np

    from repro.engine.inflight import InflightDecoder
    from repro.engine.speculative import SpeculativeConfig

    if shared_draft:
        spec = SpeculativeConfig(draft_tokens=3)
    else:
        from repro.configs.lisa_mini import CONFIG as PCFG
        from repro.core import vlm
        spec = SpeculativeConfig(
            draft_tokens=4,
            draft_params=vlm.init_lisa(PCFG, jax.random.PRNGKey(99)))
    reqs = _spec_requests(spec_executor, 5, seed=13 if shared_draft else 17)
    dec = InflightDecoder(spec_executor, slots=2, spec=spec)
    done = {}
    for i, (pkt, q, it) in enumerate(reqs):
        dec.submit(i, it, pkt, q,
                   lambda out: done.setdefault(out["seq_id"], out))
    dec.drain()
    assert len(done) == len(reqs)
    _assert_matches_generate(spec_executor, done, reqs)
    st = dec.spec_stats
    assert st.row_steps > 0 and st.drafted > 0
    if shared_draft:
        # the Context model *is* the serving model here: full acceptance
        assert st.acceptance_rate == 1.0
        assert st.tokens_per_step >= 1.5
    else:
        # a divergent draft gets rejected and must roll pages back —
        # output is exact anyway (acceptance only moves the cost)
        assert st.acceptance_rate < 1.0
        assert st.pages_rolled_back > 0
    # every private/draft page returned; only cached prefixes pinned
    from repro.core.paging import pages_for
    qlen = np.asarray(reqs[0][1]).shape[-1]
    per_prefix = pages_for(spec_executor.pcfg.clip_tokens + qlen,
                           spec_executor.page_size)
    assert dec.pool.pages_in_use == len(dec.pool.prefix) * per_prefix


def test_mixed_speculative_and_plain_rows_one_batch(spec_executor):
    """Speculating and plain rows share one in-flight verify batch (the
    plain row rides a chunk of one) — both remain token-exact with the
    one-shot generate path."""
    import numpy as np

    from repro.engine.inflight import InflightDecoder
    from repro.engine.speculative import SpeculativeConfig

    reqs = _spec_requests(spec_executor, 4, seed=23)
    dec = InflightDecoder(spec_executor, slots=4,
                          spec=SpeculativeConfig(draft_tokens=3))
    done = {}
    for i, (pkt, q, it) in enumerate(reqs):
        dec.submit(i, it, pkt, q,
                   lambda out: done.setdefault(out["seq_id"], out),
                   speculative=(i % 2 == 0))   # every other row plain
    dec.drain()
    _assert_matches_generate(spec_executor, done, reqs)
    assert [done[i]["speculative"] for i in range(4)] \
        == [True, False, True, False]
    # speculating rows finished in fewer steps than the plain rows'
    # T+1-step lockstep, so the batch really mixed disciplines
    assert dec.spec_stats.row_steps > 0
    assert dec.spec_stats.tokens_per_step > 1.0


def test_draft_reuses_prefix_prefill_on_repeat_frames(spec_executor):
    """Repeat-prefix frames skip the draft model's prefill too (keyed
    like the target prefix store) — and still serve exact results."""
    import numpy as np

    from repro.core.intent import Intent
    from repro.data import floodseg
    from repro.engine.inflight import InflightDecoder
    from repro.engine.speculative import SpeculativeConfig

    rng = np.random.RandomState(29)
    b = floodseg.make_batch(rng, 1, "segment", augment=False)
    img = jnp.asarray(b["images"])
    dec = InflightDecoder(spec_executor, slots=2,
                          spec=SpeculativeConfig(draft_tokens=3))
    done = {}
    for i in range(3):         # same frame + standing query: same prefix
        pkt = spec_executor.edge_insight(img, spec_executor.lut.tiers[0],
                                         i, 0.0)
        dec.submit(i, Intent.INSIGHT, pkt, b["query"],
                   lambda out: done.setdefault(out["seq_id"], out),
                   operator_id="uav-A")
    dec.drain()
    assert dec.draft.n_prefills == 1          # one draft prefill, 3 frames
    out = spec_executor.cloud_generate_batch([pkt], [b["query"]])[0]
    for i in range(3):
        assert np.array_equal(done[i]["tokens"], out[-1])
    # shared rows survive decoder retirement (the engine passes one dict
    # per engine): a successor decoder skips the prefill entirely
    dec2 = InflightDecoder(spec_executor, slots=2, pool=dec.pool,
                           spec=SpeculativeConfig(draft_tokens=3),
                           spec_prefix_rows=dec.draft._prefix_rows)
    pkt2 = spec_executor.edge_insight(img, spec_executor.lut.tiers[0], 9,
                                      0.0)
    done2 = {}
    dec2.submit(9, Intent.INSIGHT, pkt2, b["query"],
                lambda out: done2.setdefault(out["seq_id"], out),
                operator_id="uav-A")
    dec2.drain()
    assert dec2.draft.n_prefills == 0
    assert np.array_equal(done2[9]["tokens"], out[-1])
