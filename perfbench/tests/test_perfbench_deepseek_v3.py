"""The DeepSeek-V3 trunk module: found by name, mapped to the program's
expert share, served and checked at a tiny size against its reference,
counted from shapes, and strict about the keys it is given."""
import copy
import os

import pytest

from perfbench import arch, check, harness, model
from perfbench.tests import tiny

TRUNK = {"num_hidden_layers": 3, "hidden_size": 64,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "intermediate_size": 128, "moe_intermediate_size": 32,
         "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000,
         "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
         "qk_rope_head_dim": 8, "v_head_dim": 16,
         "first_k_dense_replace": 1, "n_routed_experts": 4,
         "n_shared_experts": 1, "num_experts_per_tok": 4, "n_group": 4,
         "topk_group": 2, "norm_topk_prob": True,
         "routed_scaling_factor": 2.5, "hidden_act": "silu",
         "scoring_func": "sigmoid", "topk_method": "noaux_tc",
         "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                          "mscale": 1, "mscale_all_dim": 1,
                          "original_max_position_embeddings": 4096,
                          "type": "yarn"},
         "num_nextn_predict_layers": 0,
         "tie_word_embeddings": False, "attention_bias": False}
# a tiny avery-dsv3: 16 experts over 4 chips, this one holding 4..7
CONFIG = dict(tiny.CONFIG, name="tiny-dsv3", arch="deepseek_v3",
              trunk=TRUNK, deployment={"router_experts": 16,
                                       "chips_per_layer": 4,
                                       "held_first": 4})
LIMITS = harness.load_json(os.path.join(harness.HERE, "limits",
                                        "dsv3-context-closed.json"))


def _config(**trunk):
    cfg = copy.deepcopy(CONFIG)
    cfg["trunk"].update(trunk)
    return cfg


def test_the_committed_configuration_keeps_every_published_width():
    spec = harness.cell_spec("dsv3-context-closed")
    cfg = spec["config"]
    assert arch.resolve(cfg).__name__.endswith(".deepseek_v3")
    llm = model.pipeline_config(cfg).llm
    assert (llm.d_model, llm.num_heads, llm.vocab_size, llm.d_ff) == \
        (7168, 128, 129280, 18432)
    m, e = llm.mla, llm.moe
    assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (m.yarn.factor, m.yarn.original_max_position, m.yarn.beta_fast,
            m.yarn.beta_slow, m.yarn.mscale, m.yarn.mscale_all_dim) == \
        (40, 4096, 32, 1, 1, 1)
    assert (e.num_experts, e.top_k, e.n_group, e.topk_group,
            e.d_ff_expert, e.routed_scaling, e.held, e.held_first,
            e.first_k_dense, llm.num_layers) == \
        (256, 8, 8, 4, 2048, 2.5, 8, 0, 1, 5)
    # the published keys stand at the top level too, as served
    assert all(cfg[k] == v for k, v in cfg["trunk"].items() if k in cfg)


def test_tiny_share_is_correct_and_its_fp8_control_is_not():
    out = tiny.run(tiny.CLOSED, LIMITS, config=CONFIG, control=True)
    assert out["result"]["correct"] is True, out["result"]["checks"]
    ctrl = check.verdict(out["info"]["control"], LIMITS)
    assert not check.passed(ctrl), ctrl


@pytest.mark.parametrize("cfg,named", [
    (_config(sliding_window=64), "'sliding_window'"),
    (_config(head_dim=16), "'head_dim'"),
    (_config(scoring_func="softmax"), "'scoring_func'"),
    (_config(num_nextn_predict_layers=1), "'num_nextn_predict_layers'"),
    (_config(rope_scaling={"type": "linear", "factor": 2.0}),
     "rope_scaling"),
    (dict(CONFIG, deployment={"router_experts": 16, "held_first": 4}),
     "'chips_per_layer'"),
    (dict(CONFIG, hidden_size=128), "'hidden_size'"),
    (dict(CONFIG, deployment=dict(CONFIG["deployment"], held_first=14)),
     "from 14"),
])
def test_what_the_module_does_not_serve_fails_by_name(cfg, named):
    with pytest.raises(ValueError, match=named):
        model.pipeline_config(cfg)


def test_a_read_key_left_out_fails_by_name():
    cfg = copy.deepcopy(CONFIG)
    del cfg["trunk"]["topk_group"]
    with pytest.raises(ValueError, match="'topk_group'"):
        model.pipeline_config(cfg)


def test_token_flops_count_the_held_share_as_an_expectation():
    pcfg = model.pipeline_config(CONFIG)
    mod = arch.resolve(CONFIG)
    d, H = 64, 4
    proj = 2 * (d * 32 + 32 * H * 24 + d * 24 + 16 * H * 32 + H * 16 * d)
    expert = 2 * 3 * d * 32
    moe = 2 * d * 16 + expert + 4 * 4 / 16 * expert
    want = 3 * (proj + 2 * 10 * H * 40) + 2 * 3 * d * 128 + 2 * moe
    assert mod.token_flops(pcfg, 10) == pytest.approx(want)
    f, b = mod.paged_attention_cost(pcfg, [10, 20])
    assert f == 3 * 2 * 30 * H * (24 + 16)
    assert b == 3 * 2 * (30 * 24 + 2 * H * 40)


def test_the_reference_takes_the_programs_picks_only_near_ties():
    """Forced with its own picks the reference routes as unforced; a
    forced pick it is sure against makes every gate NaN, and so does a
    pick it is sure of left out."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    mod = arch.resolve(CONFIG)
    t = dict(TRUNK)
    E, k = 16, t["num_experts_per_tok"]
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    moe = {"router": jax.random.normal(keys[0], (64, E)),
           "correction": {"b": 0.3 * jax.random.normal(keys[1], (E,))}}
    h = jax.random.normal(keys[2], (2, 6, 64))
    own = mod.route(t, "ref", h, moe)
    idx = jax.lax.top_k(own, k)[1]
    np.testing.assert_array_equal(mod.route(t, "ref", h, moe, idx), own)
    # the token's lowest-scored expert in place of its best pick
    worst = jnp.argmin(jax.nn.sigmoid(h @ moe["router"])
                       + moe["correction"]["b"], axis=-1)
    bad = idx.at[0, 0, 0].set(worst[0, 0])
    assert bool(jnp.all(jnp.isnan(mod.route(t, "ref", h, moe, bad))))


def _moe_cfg(c, **kw):
    import dataclasses
    return c.replace(moe=dataclasses.replace(c.moe, **kw))


@pytest.mark.parametrize("fault", ["no_group_limit", "held_first_shifted"])
def test_a_routing_fault_in_the_program_is_not_correct(fault, monkeypatch):
    """Planted in the program's router or its share, under the harness:
    picks from groups the reference drops, or the held experts' weights
    taken for the next chip's, read not correct."""
    from repro.models import moe as moe_lib
    mod = arch.resolve(CONFIG)
    if fault == "no_group_limit":
        route = moe_lib.route
        monkeypatch.setattr(moe_lib, "route", lambda p, c, xt: route(
            p, _moe_cfg(c, n_group=1, topk_group=1), xt))
    else:
        llm_config = mod.llm_config
        monkeypatch.setattr(mod, "llm_config", lambda cfg: _moe_cfg(
            llm_config(cfg), held_first=8))
    out = tiny.run(tiny.CLOSED, LIMITS, config=CONFIG)
    assert out["result"]["correct"] is False, out["result"]["checks"]


def test_rope_without_yarn_in_the_program_is_not_correct(monkeypatch):
    """The reference applies YaRN from ``rope_scaling``; a program that
    served plain RoPE and the plain softmax scale reads not correct."""
    import dataclasses
    mod = arch.resolve(CONFIG)
    llm_config = mod.llm_config

    def plain(cfg):
        c = llm_config(cfg)
        return c.replace(mla=dataclasses.replace(c.mla, yarn=None))

    monkeypatch.setattr(mod, "llm_config", plain)
    out = tiny.run(tiny.CLOSED, LIMITS, config=CONFIG)
    assert out["result"]["correct"] is False, out["result"]["checks"]


def test_the_replay_serves_what_the_engine_served():
    """``served_picks`` replays requests the in-flight engine served
    through the program's own stages: its tokens are the served tokens,
    and its picks on held experts at the decoded positions add up to the
    engine's own device counter."""
    import numpy as np

    from repro.core import DualStreamExecutor, packets as pk, paper_lut
    from repro.core.intent import Intent
    from repro.engine import AveryEngine

    pcfg = model.pipeline_config(CONFIG)
    params, bns = model.make_weights(pcfg, CONFIG["bottleneck_tiers"],
                                     2**31 + 3)
    T, lut = 4, paper_lut()
    ex = DualStreamExecutor(pcfg, params, bns, lut, max_new_tokens=T)
    engine = AveryEngine(lut=lut, executor=ex, batching="inflight",
                         max_batch=8)
    rng = np.random.default_rng(7)
    d = pcfg.llm.d_model
    ctx = rng.standard_normal((5, pcfg.clip_tokens, d)).astype(np.float32)
    ctx = ctx.astype(pcfg.llm.adtype)
    query = rng.integers(0, pcfg.llm.vocab_size, (5, 4)).astype(np.int32)
    futs = [engine.submit_packet(
        pk.make_context_packet(i, float(i), ctx[i:i + 1]),
        query[i:i + 1], Intent.CONTEXT, time_s=float(i)) for i in range(5)]
    engine.drain()
    tokens = np.concatenate([np.asarray(f.result().tokens) for f in futs])
    picks, greedy = arch.resolve(CONFIG).served_picks(params, CONFIG, ctx,
                                                      query, tokens)
    np.testing.assert_array_equal(greedy, tokens)
    S = pcfg.clip_tokens + query.shape[1]
    # the engine decodes every answer token, the last one too (its
    # <SEG> state): the positions S..S+T-1
    local = np.asarray(picks)[:, :, S:] - 4               # held 4..7
    assert engine.stats["inflight_expert_picks"] == \
        int(((local >= 0) & (local < 4)).sum()) > 0
