"""DeepSeek-V3's block on the serving path, at a tiny size on the CPU:
latent (MLA) pages in the paged pool, group-limited sigmoid routing with
its correction bias, the dropless expert share, and a trunk of a dense
group followed by an MoE group, each checked against a plain reference
on seeded random weights."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.lisa_mini import CONFIG as MINI
from repro.core import vlm
from repro.core.paging import pages_for, prefix_positions
from repro.models import MLAConfig, MoEConfig, ModelConfig, YarnConfig
from repro.models import attention as attn_lib
from repro.models import common
from repro.models import moe as moe_lib

E, K, G, TOPG = 16, 4, 4, 2


def _llm(**moe_kw) -> ModelConfig:
    moe = dict(num_experts=E, top_k=K, d_ff_expert=32, num_shared_experts=1,
               d_ff_shared=32, first_k_dense=1, d_ff_dense=128,
               scoring="sigmoid", n_group=G,
               topk_group=TOPG, routed_scaling=2.5, dispatch="dropless")
    moe.update(moe_kw)
    return ModelConfig(
        name="dsv3-tiny", arch_type="moe", num_layers=3, d_model=64,
        num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=64,
        attn_type="mla",
        mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16),
        moe=MoEConfig(**moe), param_dtype="float32", act_dtype="float32")


# this chip's share: experts 4..7 of the router's 16
PCFG = dataclasses.replace(MINI, llm=_llm(held_experts=4, held_first=4))
# DeepSeek-V3's published rope_scaling
YARN = YarnConfig(factor=40, original_max_position=4096, beta_fast=32,
                  beta_slow=1, mscale=1, mscale_all_dim=1)


def _params(pcfg, seed=0):
    """Seeded weights with a correction bias large enough to move picks."""
    params = vlm.init_lisa(pcfg, jax.random.PRNGKey(seed))
    moe = params["llm"]["groups"][1]["moe"]
    moe["correction"]["b"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), moe["correction"]["b"].shape)
    return params


def _prompt(pcfg, qlen=8, seed=1):
    ctx = jax.random.normal(jax.random.PRNGKey(seed),
                            (1, pcfg.clip_tokens, pcfg.llm.d_model))
    query = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, qlen), 0,
                               pcfg.llm.vocab_size)
    return ctx, query


def _close(a, b, tol=2e-4):
    a, b = np.asarray(a), np.asarray(b)
    assert float(np.max(np.abs(a - b))) < tol * (float(np.max(np.abs(b)))
                                                 + 1.0)


# ---- (b) routing: a plain loop over tokens ----


def _route_loop(p, cfg, x):
    """DeepSeek-V3's router, one token at a time in float64."""
    m = cfg.moe
    w = np.asarray(p["router"], np.float64)
    bias = np.asarray(p["correction"]["b"], np.float64)
    size = m.num_experts // m.n_group
    idx, gates = [], []
    for xt in np.asarray(x, np.float64):
        s = 1.0 / (1.0 + np.exp(-(xt @ w)))
        c = s + bias
        group = [sum(sorted(c[g * size:(g + 1) * size])[-2:])
                 for g in range(m.n_group)]
        kept = sorted(range(m.n_group), key=lambda g: -group[g])
        cand = [e for g in kept[:m.topk_group]
                for e in range(g * size, (g + 1) * size)]
        pick = sorted(cand, key=lambda e: -c[e])[:m.top_k]
        g = s[pick] / s[pick].sum() * m.routed_scaling
        idx.append(pick)
        gates.append(dict(zip(pick, g)))
    return idx, gates


def test_routing_equals_a_plain_loop_over_tokens():
    cfg = _llm()
    p = moe_lib.init_moe(jax.random.PRNGKey(3), cfg)
    p["correction"]["b"] = 0.3 * jax.random.normal(jax.random.PRNGKey(4),
                                                   (E,))
    x = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.d_model))
    _, gate_vals, gate_idx = moe_lib.route(p, cfg, x)
    want_idx, want_gates = _route_loop(p, cfg, x)
    for t in range(x.shape[0]):
        got = dict(zip(np.asarray(gate_idx[t]).tolist(),
                       np.asarray(gate_vals[t]).tolist()))
        assert set(got) == set(want_idx[t]), t
        for e, g in want_gates[t].items():
            assert abs(got[e] - g) < 1e-5, (t, e)
    # the bias and the group limit each change some token's picks, and
    # the gates stay the unbiased scores of the picks
    no_bias = dict(p, correction={"b": jnp.zeros((E,))})
    _, _, idx_nb = moe_lib.route(no_bias, cfg, x)
    _, _, idx_ng = moe_lib.route(
        p, cfg.replace(moe=dataclasses.replace(cfg.moe, n_group=1,
                                               topk_group=1)), x)
    sets = lambda a: [set(r) for r in np.asarray(a).tolist()]  # noqa: E731
    assert sets(idx_nb) != sets(gate_idx)
    assert sets(idx_ng) != sets(gate_idx)
    np.testing.assert_allclose(np.sum(gate_vals, axis=-1), 2.5, rtol=1e-5)


def test_softmax_routing_is_the_training_layers_as_before():
    """Existing MoE configs keep softmax top-k with normalised gates,
    computed as before: the router's matmul at default precision, the
    normaliser's eps 1e-9, no scaling."""
    cfg = _llm(scoring="softmax", n_group=1,
               topk_group=1, routed_scaling=1.0, dispatch="einsum")
    p = moe_lib.init_moe(jax.random.PRNGKey(6), cfg)
    assert "correction" not in p
    x = jax.random.normal(jax.random.PRNGKey(7), (32, cfg.d_model))
    probs, vals, idx = moe_lib.route(p, cfg, x)
    want_probs = jax.nn.softmax(x.astype(jnp.float32) @ p["router"],
                                axis=-1)
    want_vals, want_idx = jax.lax.top_k(want_probs, K)
    want_vals = want_vals / (jnp.sum(want_vals, -1, keepdims=True) + 1e-9)
    for got, want in ((probs, want_probs), (vals, want_vals),
                      (idx, want_idx)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # on a TPU the precision decides the router's numerics: the default
    # here, the highest for the sigmoid router alone
    def precisions(c, params):
        jaxpr = jax.make_jaxpr(lambda v: moe_lib.route(params, c, v))(x)
        return [e.params["precision"] for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "dot_general"]
    assert precisions(cfg, p) == [None]
    sig = _llm()
    hp = jax.lax.Precision.HIGHEST
    assert precisions(sig, moe_lib.init_moe(jax.random.PRNGKey(6), sig)) \
        == [(hp, hp)]


def test_group_limited_routing_needs_sigmoid_scoring():
    cfg = _llm(scoring="softmax", dispatch="einsum")
    p = moe_lib.init_moe(jax.random.PRNGKey(6), cfg)
    with pytest.raises(ValueError, match="sigmoid"):
        moe_lib.route(p, cfg, jnp.ones((2, cfg.d_model)))


# ---- (c) the shares add up to the uncut layer ----


def _layer_loop(p, cfg, x):
    """The whole layer (every expert, the shared one once), token by
    token: the uncut reference."""
    idx, gates = _route_loop(p, cfg, x)
    silu = lambda v: v / (1.0 + np.exp(-v))  # noqa: E731
    f = lambda w, v: v @ np.asarray(w, np.float64)  # noqa: E731
    out = []
    for t, xt in enumerate(np.asarray(x, np.float64)):
        sh = p["shared"]
        y = f(sh["w_down"], silu(f(sh["w_gate"], xt)) * f(sh["w_up"], xt))
        for e in idx[t]:
            h = silu(f(p["w_gate"][e], xt)) * f(p["w_up"][e], xt)
            y = y + gates[t][e] * f(p["w_down"][e], h)
        out.append(y)
    return np.stack(out)


def test_four_shares_add_up_to_the_uncut_layer():
    whole = _llm()
    p = moe_lib.init_moe(jax.random.PRNGKey(8), whole)
    p["correction"]["b"] = 0.3 * jax.random.normal(jax.random.PRNGKey(9),
                                                   (E,))
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 24, whole.d_model))
    held = E // 4
    total, picks = 0.0, 0
    for share in range(4):
        cfg = whole.replace(moe=dataclasses.replace(
            whole.moe, held_experts=held, held_first=share * held))
        lo = share * held
        ps = dict(p, **{k: p[k][lo:lo + held]
                        for k in ("w_gate", "w_up", "w_down")})
        out, counts = moe_lib.moe_held(ps, cfg, x)
        total = total + np.asarray(out, np.float64)
        picks += int(counts["expert_picks"])
        assert 0 < int(counts["experts_hit"]) <= held
    shared = moe_lib.dense_mlp(p["shared"], whole, x.reshape(-1, 64))
    total = total - 3 * np.asarray(shared, np.float64).reshape(total.shape)
    want = _layer_loop(p, whole, x.reshape(-1, 64)).reshape(total.shape)
    _close(total, want, 1e-5)
    assert picks == x.shape[0] * x.shape[1] * K
    uncut, counts = moe_lib.moe_held(p, whole, x)
    _close(uncut, want, 1e-5)
    assert int(counts["expert_picks"]) == picks


def test_an_expert_no_token_picked_is_skipped_and_counts_nothing():
    cfg = _llm(held_experts=4, held_first=0)
    p = moe_lib.init_moe(jax.random.PRNGKey(11), cfg)
    # experts 0..3 can never win against a bias this large elsewhere
    p["correction"]["b"] = jnp.where(jnp.arange(E) < 4, -10.0, 0.0)
    x = jax.random.normal(jax.random.PRNGKey(12), (1, 8, cfg.d_model))
    out, counts = moe_lib.moe_held(p, cfg, x)
    assert int(counts["expert_picks"]) == int(counts["experts_hit"]) == 0
    shared = moe_lib.dense_mlp(p["shared"], cfg, x.reshape(8, -1))
    _close(out.reshape(8, -1), shared, 1e-6)


# ---- (a) latent pages: paged prefill, decode and verify ----


def _paged_pool(paged, n_prefix, n_private, rows):
    """Trash page 0, the prefix pages, then each row's private pages."""
    P = 1 + n_prefix + rows * n_private
    ids = np.arange(1, 1 + n_prefix)
    return {"groups": [jax.tree.map(
        lambda a: jnp.zeros((a.shape[0], P) + a.shape[3:], a.dtype)
        .at[:, ids].set(a[:, 0]), g) for g in paged["groups"]]}


def test_latent_pages_decode_and_verify_equal_the_full_forward():
    _check_latent_pages(PCFG)


def test_latent_pages_with_yarn_equal_the_full_forward():
    llm = PCFG.llm
    _check_latent_pages(dataclasses.replace(PCFG, llm=dataclasses.replace(
        llm, mla=dataclasses.replace(llm.mla, yarn=YARN))))


def _check_latent_pages(PCFG):
    params = _params(PCFG)
    ctx, query = _prompt(PCFG)
    T, page, B = 4, 4, 2
    S = PCFG.clip_tokens + query.shape[1]
    n_prefix, n_private = pages_for(S, page), pages_for(T, page)
    logits0, _, paged = vlm.llm_prefill_paged(params, PCFG, ctx, query, page)
    assert [sorted(g) for g in paged["groups"]] == [["ckv", "krope"]] * 2
    assert paged["groups"][1]["ckv"].shape == (2, 1, n_prefix, page, 16)
    ref0, _ = vlm.llm_reason(params, PCFG, ctx, query)
    _close(logits0, ref0)

    pt = np.zeros((B, n_prefix + n_private), np.int32)
    positions = np.full((B, (n_prefix + n_private) * page), -1, np.int32)
    for b in range(B):
        priv = 1 + n_prefix + b * n_private
        pt[b] = list(range(1, 1 + n_prefix)) + list(range(priv,
                                                          priv + n_private))
        positions[b, :n_prefix * page] = prefix_positions(S, n_prefix, page)
    base = n_prefix * page
    pool = _paged_pool(paged, n_prefix, n_private, B)
    toks = [int(jnp.argmax(logits0[0]))]
    seq, picks = [], 0
    for t in range(T):
        lg, _, pool, counts = vlm.llm_decode_step_paged(
            params, PCFG, pool, pt, positions,
            np.full((B, 1), toks[-1], np.int32), np.full((B,), S + t),
            np.full((B,), base + t))
        positions[:, base + t] = S + t
        seq.append(np.asarray(lg))
        picks += int(counts["expert_picks"])
        assert int(counts["experts_hit"]) <= 4 * 2   # held x MoE layers
        full = jnp.concatenate([query, jnp.asarray([toks])], axis=1)
        ref, _ = vlm.llm_reason(params, PCFG, ctx, full)
        _close(lg[0], ref[0])
        _close(lg[1], ref[0])
        toks.append(int(jnp.argmax(lg[0])))
    assert picks > 0

    positions[:, base:] = -1
    chunk = np.tile(np.asarray(toks[:T], np.int32), (B, 1))
    lgv, _, _, counts = vlm.llm_verify_step_paged(
        params, PCFG, _paged_pool(paged, n_prefix, n_private, B), pt,
        positions, chunk, np.full((B,), S), np.full((B,), base),
        np.asarray([T, 2], np.int32))
    for b, n in ((0, T), (1, 2)):
        for i in range(n):
            _close(lgv[b, i], seq[i][b])
    assert set(counts) == {"expert_picks", "experts_hit"}


def test_idle_rows_and_pad_entries_are_not_counted():
    """A decode step's idle row (parked on the trash page) and a verify
    chunk's pad entries get no routed expert: the counters read what the
    live tokens alone read, and the live rows' logits do not move."""
    params = _params(PCFG)
    ctx, query = _prompt(PCFG, seed=5)
    page, T = 4, 4
    S = PCFG.clip_tokens + query.shape[1]
    n_prefix, n_private = pages_for(S, page), pages_for(T, page)
    _, _, paged = vlm.llm_prefill_paged(params, PCFG, ctx, query, page)
    n = n_prefix + n_private
    row = np.asarray([list(range(1, 1 + n_prefix))
                      + list(range(1 + n_prefix, 1 + n))], np.int32)
    pos_row = np.full((1, n * page), -1, np.int32)
    pos_row[0, :n_prefix * page] = prefix_positions(S, n_prefix, page)
    base = n_prefix * page
    toks = np.asarray([[3, 17, 29, 41]], np.int32)

    def decode(rows):
        pt = np.concatenate([row, np.zeros((rows - 1, n), np.int32)])
        ps = np.concatenate([pos_row, np.full((rows - 1, n * page), -1,
                                              np.int32)])
        lg, _, _, counts = vlm.llm_decode_step_paged(
            params, PCFG, _paged_pool(paged, n_prefix, n_private, 1), pt,
            ps, np.repeat(toks[:, :1], rows, 0), np.full((rows,), S),
            np.asarray([base] + [0] * (rows - 1)))
        return lg[0], {k: int(v) for k, v in counts.items()}

    lg1, alone = decode(1)
    lg3, beside_idle = decode(3)
    assert alone == beside_idle and alone["expert_picks"] > 0
    _close(lg3, lg1, 1e-6)

    def verify(chunk):
        _, _, _, counts = vlm.llm_verify_step_paged(
            params, PCFG, _paged_pool(paged, n_prefix, n_private, 1), row,
            pos_row, toks[:, :chunk], np.asarray([S]), np.asarray([base]),
            np.asarray([2], np.int32))
        return {k: int(v) for k, v in counts.items()}

    assert verify(4) == verify(2)


def test_mla_ring_decode_takes_per_row_slots():
    """The contiguous cache's in-flight path: rows at their own depths."""
    pcfg = dataclasses.replace(MINI, llm=_llm())
    params = _params(pcfg)
    ctx, query = _prompt(pcfg, seed=3)
    W = pcfg.clip_tokens + query.shape[1] + 2
    logits0, _, cache = vlm.llm_prefill(params, pcfg, ctx, query, width=W)
    tok = jnp.argmax(logits0, -1).astype(jnp.int32)[:, None]
    S = pcfg.clip_tokens + query.shape[1]
    lg, _, _ = vlm.llm_decode_step(params, pcfg, cache, tok,
                                   jnp.asarray([S], jnp.int32))
    ref, _ = vlm.llm_reason(params, pcfg, ctx,
                            jnp.concatenate([query, tok], axis=1))
    _close(lg, ref)


# ---- (d) the in-flight engine over a dense group and an MoE group ----


def test_inflight_engine_serves_every_layer_group():
    from repro.core import DualStreamExecutor, packets as pk, paper_lut
    from repro.core import profile as prof
    from repro.core.intent import Intent
    from repro.engine import AveryEngine
    lut = paper_lut()
    params = _params(PCFG)
    _, bns, _ = prof.random_init_system(PCFG, lut=lut, params=params)
    ex = DualStreamExecutor(pcfg=PCFG, params=params, bottlenecks=bns,
                            lut=lut, max_new_tokens=4, flash_decode=False,
                            page_size=4)
    engine = AveryEngine(lut=lut, executor=ex, batching="inflight",
                         max_batch=4)
    reqs = [_prompt(PCFG, seed=20 + 2 * i) for i in range(3)]
    futs = [engine.submit_packet(
        pk.make_context_packet(i, float(i), np.asarray(c)), np.asarray(q),
        Intent.CONTEXT, time_s=float(i)) for i, (c, q) in enumerate(reqs)]
    engine.drain()
    # group 1 (the MoE layers) changes the answer: a trunk that dropped
    # it could not match
    cut = dict(params, llm=dict(params["llm"], groups=[
        params["llm"]["groups"][0],
        jax.tree.map(jnp.zeros_like, params["llm"]["groups"][1])]))
    for fut, (ctx, query) in zip(futs, reqs):
        res = fut.result()
        toks = np.asarray(res.tokens)[0].tolist()
        ref0, _ = vlm.llm_reason(params, PCFG, ctx, query)
        _close(res.answer_logits, ref0)
        assert float(jnp.max(jnp.abs(
            vlm.llm_reason(cut, PCFG, ctx, query)[0] - ref0))) > 1e-2
        for i in range(1, len(toks)):
            full = jnp.concatenate([query, jnp.asarray([toks[:i]])], axis=1)
            ref, _ = vlm.llm_reason(params, PCFG, ctx, full)
            assert toks[i] == int(jnp.argmax(ref[0])), i
    stats = engine.stats
    assert stats["inflight_expert_picks"] > 0
    assert 0 < stats["inflight_experts_hit"] <= stats["inflight_expert_picks"]


def test_published_routing_of_the_listed_model():
    from repro.configs import get_config
    m = get_config("deepseek-v3-671b").moe
    assert (m.scoring, m.n_group, m.topk_group, m.routed_scaling) == \
        ("sigmoid", 8, 4, 2.5)


# ---- YaRN on the rotary part ----


def test_yarn_at_the_published_widths():
    """DeepSeek-V3's 64 rotary dims: pairs 0-9 keep their frequency,
    10-22 ramp down, 23-31 turn 40 times slower; the softmax scale is
    (0.1 ln 40 + 1)^2 / sqrt(192); cos/sin keep their size."""
    from repro.configs import get_config
    llm = get_config("deepseek-v3-671b")
    assert llm.mla.yarn == YARN
    inv = np.asarray(common.yarn_inv_freq(64, 10000.0, YARN), np.float64)
    plain = 10000.0 ** (-np.arange(32) / 32)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)    # low 10, high 23
    np.testing.assert_allclose(inv, plain / 40 * ramp + plain * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    assert float(attn_lib._mla_scale(llm)) == pytest.approx(
        (0.1 * math.log(40) + 1) ** 2 / math.sqrt(192), rel=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 3, 2, 64))
    pos = jnp.asarray([[0, 7, 250]])
    y = common.apply_yarn_rope(x, pos, 10000.0, YARN)
    _close(jnp.linalg.norm(y, axis=-1), jnp.linalg.norm(x, axis=-1), 1e-5)
    _close(y[:, 0], x[:, 0], 1e-6)                 # position 0 unturned
    louder = dataclasses.replace(YARN, mscale=2.0)
    _close(common.apply_yarn_rope(x, pos, 10000.0, louder),
           y * (0.2 * math.log(40) + 1) / (0.1 * math.log(40) + 1), 1e-5)


def test_yarn_changes_latent_attention():
    """The scaled softmax and the slowed frequencies reach the logits."""
    llm = PCFG.llm
    pcfg = dataclasses.replace(PCFG, llm=dataclasses.replace(
        llm, mla=dataclasses.replace(llm.mla, yarn=YARN)))
    params = _params(PCFG)
    ctx, query = _prompt(PCFG)
    plain, _ = vlm.llm_reason(params, PCFG, ctx, query)
    yarn, _ = vlm.llm_reason(params, pcfg, ctx, query)
    assert float(jnp.max(jnp.abs(yarn - plain))) > 1e-3
