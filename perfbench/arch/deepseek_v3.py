"""DeepSeek-V3's trunk: latent attention (MLA) in every layer, dense MLPs
in the first ``first_k_dense_replace`` layers, then mixture-of-experts
layers with one shared expert and sigmoid, group-limited routing.

Pre-norm decoder blocks (RMSNorm). Attention: the query through a
``q_lora_rank`` bottleneck (RMSNorm between the two projections), keys
and values from a ``kv_lora_rank`` latent (RMSNorm) plus one
``qk_rope_head_dim`` rotary key shared by every head; each head scores
its ``qk_nope_head_dim`` part against the up-projected latent and its
rotary part against the shared key, softmax scale
1/sqrt(qk_nope_head_dim + qk_rope_head_dim). YaRN (``rope_scaling``) as
published: the rotary frequencies between the dims that turn
``beta_fast`` and ``beta_slow`` times over
``original_max_position_embeddings`` ramp from the plain ones to the
plain ones over ``factor`` (those below kept, those above divided),
cos/sin times mscale(``mscale``) / mscale(``mscale_all_dim``) and the
softmax scale times mscale(``mscale_all_dim``)^2, where mscale(m) =
0.1 m ln(factor) + 1. MoE layers: scores
s = sigmoid(x W_r) over the router's experts, the choice made on
s + b (the correction bias) within the ``topk_group`` of ``n_group``
groups whose two best biased scores sum highest, top
``num_experts_per_tok``; gates s of the picks, normalised
(``norm_topk_prob``), times ``routed_scaling_factor``; SiLU-gated
experts and one shared expert.

The deployment (the configuration's ``deployment`` group): each MoE
layer's ``router_experts`` experts spread over ``chips_per_layer`` chips
(expert parallelism), attention whole on every chip, and this chip
holding ``n_routed_experts`` of them from ``held_first`` on. The router
keeps its published width and routes over every expert; the program and
this reference alike compute only the held experts' part of the routed
sum (what the absent experts would add comes over an exchange that one
chip does not have), and add the shared expert whole.

Departures from the published model, each with its reason:

* RoPE rotates the two halves of the rotary part (rotate-half), where
  the published model rotates interleaved pairs: one fixed permutation
  of the rotary columns of ``wq_b`` and ``wkv_a`` maps one onto the
  other, so under seeded random weights the two are the same model.
* Multi-token prediction (``num_nextn_predict_layers``) is left out:
  serving emits one token a step.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import HIGHEST, VOCAB_BLOCK, f32, mm, rms

READS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
         "num_key_value_heads", "intermediate_size",
         "moe_intermediate_size", "vocab_size", "rms_norm_eps",
         "rope_theta", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "first_k_dense_replace",
         "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
         "n_group", "topk_group", "routed_scaling_factor", "rope_scaling")
# what the program's trunk and the reference below compute
FIXED = {"hidden_act": "silu", "scoring_func": "sigmoid",
         "topk_method": "noaux_tc", "num_nextn_predict_layers": 0, "tie_word_embeddings": False,
         "attention_bias": False, "moe_layer_freq": 1,
         "norm_topk_prob": True}
INFO = ("max_position_embeddings", "torch_dtype", "model_type", "ep_size")
DEPLOYMENT = ("router_experts", "chips_per_layer", "held_first")
YARN = ("type", "factor", "original_max_position_embeddings", "beta_fast",
        "beta_slow", "mscale", "mscale_all_dim")
# how far the program's biased router scores may lie from the
# reference's (``route``): nearly twice the largest gap read on a v5e at
# the cell's widths with the reference teacher-forced, 0.0166 at the
# last MoE layer
TIE = 0.03


def _deployment(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The expert share this chip holds, checked against the trunk."""
    t, dep = cfg["trunk"], cfg.get("deployment", {})
    for key in DEPLOYMENT:
        if key not in dep:
            raise ValueError(f"deployment key {key!r} is missing")
    for key, value in cfg.items():
        if key in t and value != t[key]:
            raise ValueError(f"top-level {key!r} is {value!r}, the trunk "
                             f"serves {t[key]!r}")
    E, held, first = (dep["router_experts"], t["n_routed_experts"],
                      dep["held_first"])
    if held * dep["chips_per_layer"] != E or not 0 <= first <= E - held:
        raise ValueError(
            f"{dep['chips_per_layer']} chips holding {held} experts each "
            f"from {first} do not divide the router's {E}")
    if E % t["n_group"] or t["num_key_value_heads"] != \
            t["num_attention_heads"]:
        raise ValueError("n_group must divide the router's experts and "
                         "num_key_value_heads equal num_attention_heads")
    return {k: dep[k] for k in DEPLOYMENT}


def _yarn(t: Dict[str, Any]):
    """``rope_scaling`` as the program's ``YarnConfig``: null or YaRN,
    every key given."""
    from repro.models import YarnConfig
    rs = t["rope_scaling"]
    if rs is None:
        return None
    if not isinstance(rs, dict) or set(rs) != set(YARN) \
            or rs["type"] != "yarn":
        raise ValueError(f"rope_scaling {rs!r}: this architecture serves "
                         f"null or YaRN with the keys {list(YARN)}")
    return YarnConfig(factor=rs["factor"],
                      original_max_position=rs[
                          "original_max_position_embeddings"],
                      beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
                      mscale=rs["mscale"],
                      mscale_all_dim=rs["mscale_all_dim"])


def llm_config(cfg: Dict[str, Any]):
    """The trunk block as the program's ``ModelConfig``: the dropless
    expert share (``moe.moe_held``) in every MoE layer."""
    from repro.models import MLAConfig, MoEConfig, ModelConfig
    t, dtype = cfg["trunk"], cfg["dtype"]
    dep = _deployment(cfg)
    return ModelConfig(
        name=cfg["name"], arch_type="moe",
        num_layers=t["num_hidden_layers"], d_model=t["hidden_size"],
        num_heads=t["num_attention_heads"],
        num_kv_heads=t["num_key_value_heads"], d_ff=t["intermediate_size"],
        vocab_size=t["vocab_size"], attn_type="mla",
        mla=MLAConfig(q_lora_rank=t["q_lora_rank"],
                      kv_lora_rank=t["kv_lora_rank"],
                      qk_nope_head_dim=t["qk_nope_head_dim"],
                      qk_rope_head_dim=t["qk_rope_head_dim"],
                      v_head_dim=t["v_head_dim"], yarn=_yarn(t)),
        moe=MoEConfig(
            num_experts=dep["router_experts"],
            top_k=t["num_experts_per_tok"],
            d_ff_expert=t["moe_intermediate_size"],
            num_shared_experts=t["n_shared_experts"],
            d_ff_shared=t["moe_intermediate_size"],
            first_k_dense=t["first_k_dense_replace"],
            d_ff_dense=t["intermediate_size"], dispatch="dropless",
            scoring="sigmoid", n_group=t["n_group"],
            topk_group=t["topk_group"],
            routed_scaling=t["routed_scaling_factor"],
            held_experts=t["n_routed_experts"],
            held_first=dep["held_first"]),
        rope_theta=t["rope_theta"], norm_eps=t["rms_norm_eps"],
        param_dtype=dtype, act_dtype=dtype)


# ---- the float32 reference ----

def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def _yarn_freq(dim, theta, rs):
    """YaRN's inverse frequencies (dim//2,), as the published model's
    ``DeepseekV3YarnRotaryEmbedding`` computes them."""
    def correction_dim(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(theta))

    lo = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    hi = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - lo) / (hi - lo),
                   0, 1)
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / rs["factor"]
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def _rope(x, pos, theta, rs):
    """x (B, N, h, r); rotate-half pairs over the rotary part, YaRN's
    frequencies and cos/sin factor where ``rs`` (``rope_scaling``) is
    given."""
    half = x.shape[-1] // 2
    if rs is None:
        inv, amp = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                    / half)), 1.0
    else:
        inv = jnp.asarray(_yarn_freq(x.shape[-1], theta, rs), jnp.float32)
        amp = _mscale(rs["factor"], rs["mscale"]) \
            / _mscale(rs["factor"], rs["mscale_all_dim"])
    ang = pos.astype(jnp.float32)[:, None] * inv          # (N, half)
    cos = amp * jnp.cos(ang)[None, :, None]
    sin = amp * jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(t, mode, h, a, pos):
    """Latent attention over the whole causal sequence, the keys and
    values up-projected from the latent as the published model does."""
    B, N, _ = h.shape
    H, n, r, dv = (t["num_attention_heads"], t["qk_nope_head_dim"],
                   t["qk_rope_head_dim"], t["v_head_dim"])
    eps, theta, rank = t["rms_norm_eps"], t["rope_theta"], t["kv_lora_rank"]
    rs = dict(t["rope_scaling"]) if t["rope_scaling"] else None
    scale = 1.0 / math.sqrt(n + r)
    if rs is not None and rs["mscale_all_dim"]:
        scale *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    cq = rms(mm("bnd,dq->bnq", h, a["wq_a"], mode), a["q_norm"]["w"], eps)
    q = mm("bnq,qe->bne", cq, a["wq_b"], mode).reshape(B, N, H, n + r)
    q_nope, q_rope = q[..., :n], _rope(q[..., n:], pos, theta, rs)
    kv_a = mm("bnd,dk->bnk", h, a["wkv_a"], mode)
    ckv = rms(kv_a[..., :rank], a["kv_norm"]["w"], eps)
    k_rope = _rope(kv_a[..., None, rank:], pos, theta, rs)[:, :, 0]
    kv = mm("bnc,ce->bne", ckv, a["wkv_b"], mode).reshape(B, N, H, n + dv)
    k_nope, v = kv[..., :n], kv[..., n:]
    s = (mm("bqhn,bkhn->bhqk", q_nope, k_nope, mode)
         + mm("bqhr,bkr->bhqk", q_rope, k_rope, mode)) * scale
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    o = mm("bhqk,bkhv->bqhv", jax.nn.softmax(s, axis=-1), v, mode)
    return mm("bne,ed->bnd", o.reshape(B, N, H * dv), a["wo"], mode)


def _mlp(mode, h, m):
    g = mm("bnd,df->bnf", h, m["w_gate"], mode)
    u = mm("bnd,df->bnf", h, m["w_up"], mode)
    return mm("bnf,fd->bnd", jax.nn.silu(g) * u, m["w_down"], mode)


def _kth(x, k):
    """The k-th largest along the last axis, kept as an axis of 1."""
    return jax.lax.top_k(x, k)[0][..., k - 1:k]


def route(t, mode, h, moe, forced=None):
    """Gates (B, N, E) over the router's experts: zero but at each
    token's picks, from the unbiased scores.

    ``forced`` (B, N, k), the program's own picks: the reference then
    takes them wherever its own choice is within ``TIE`` of a tie, and
    requires every pick it is sure of. With scores within ``TIE`` of
    its own, a router must keep each group whose score (a sum of two)
    beats the first group left out by more than 4 ``TIE``, keep only
    groups within 4 ``TIE`` of the last one kept, pick each expert of a
    kept group that beats the (k+1)-th candidate of the possible groups
    by more than 2 ``TIE``, and pick nothing 2 ``TIE`` below the k-th
    expert of the groups it must keep. A forced pick outside those
    bounds makes every gate NaN."""
    B, N, _ = h.shape
    E = moe["router"].shape[-1]
    G, k, kg = t["n_group"], t["num_experts_per_tok"], t["topk_group"]
    s = jax.nn.sigmoid(mm("bnd,de->bne", h, moe["router"], mode))
    c = s + f32(moe["correction"]["b"])
    g = jnp.sum(jax.lax.top_k(c.reshape(B, N, G, E // G), 2)[0], axis=-1)

    def experts(groups):                          # (B,N,G) -> (B,N,E)
        return jnp.repeat(groups, E // G, axis=-1)

    kept = jnp.where(experts(g >= _kth(g, kg)), c, -jnp.inf)
    picked = kept >= _kth(kept, k)
    if forced is not None:
        sure = experts(g - _kth(g, kg + 1) > 4 * TIE)
        possible = jnp.where(experts(g >= _kth(g, kg) - 4 * TIE), c,
                             -jnp.inf)
        must = sure & (c - _kth(possible, k + 1) > 2 * TIE)
        may = (possible >= _kth(jnp.where(sure, c, -jnp.inf), k)
               - 2 * TIE)
        picked = jnp.sum(jax.nn.one_hot(forced, E, dtype=jnp.int32),
                         axis=-2) > 0
        ok = jnp.all((picked | ~must) & (may | ~picked))
        s = jnp.where(ok, s, jnp.nan)
    w = jnp.where(picked, s, 0.0)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * t["routed_scaling_factor"]


def _moe(t, mode, h, moe, first, forced=None):
    """The held experts' part of the routed sum plus the shared expert."""
    gates = route(t, mode, h, moe, forced)
    held = moe["w_gate"].shape[0]
    gates = jax.lax.dynamic_slice_in_dim(gates, first, held, axis=-1)
    g = mm("bnd,edf->benf", h, moe["w_gate"], mode)
    u = mm("bnd,edf->benf", h, moe["w_up"], mode)
    y = mm("benf,efd->bend", jax.nn.silu(g) * u, moe["w_down"], mode)
    return jnp.einsum("bend,bne->bnd", y, gates, precision=HIGHEST) \
        + _mlp(mode, h, moe["shared"])


def _layer(t, mode, first, x, lp, pos, forced=None):
    eps = t["rms_norm_eps"]
    x = x + _attention(t, mode, rms(x, lp["norm1"]["w"], eps), lp["attn"],
                       pos)
    h = rms(x, lp["norm2"]["w"], eps)
    return x + (_mlp(mode, h, lp["mlp"]) if "mlp" in lp
                else _moe(t, mode, h, lp["moe"], first, forced))


@functools.partial(jax.jit, static_argnames=("cfg_items", "first", "n_out",
                                             "mode"))
def _trunk(llm, seg_proj, ctx, ids, forced, *, cfg_items, first, n_out,
           mode):
    t = dict(cfg_items)
    emb = f32(jnp.take(llm["embed"], ids, axis=0))
    x = jnp.concatenate([f32(ctx), emb], axis=1)
    pos = jnp.arange(x.shape[1])
    dense, moe = llm["groups"]
    x, _ = jax.lax.scan(
        lambda x, lp: (_layer(t, mode, first, x, lp, pos), None), x, dense)
    x, _ = jax.lax.scan(
        lambda x, inp: (_layer(t, mode, first, x, inp[0], pos, inp[1]),
                        None), x, (moe, forced))
    x = rms(x, llm["norm"]["w"], t["rms_norm_eps"])
    start = x.shape[1] - 1 - n_out       # predicts the first answer token
    xo = x[:, start:start + n_out]
    head = llm["answer_head"]
    V = head.shape[1]
    logits = jnp.concatenate(
        [mm("bnd,dv->bnv", xo, head[:, lo:min(V, lo + VOCAB_BLOCK)], mode)
         for lo in range(0, V, VOCAB_BLOCK)], axis=-1)
    seg = mm("bd,de->be", x[:, -1], seg_proj, mode)
    return logits, seg


def trunk(params, cfg: Dict[str, Any], ctx, query, tokens, mode="ref"):
    """Teacher-forced trunk over ``[ctx; query; tokens]``, a layer at a
    time (each layer's weights upcast inside the layer scan).

    ctx (B, C, d); query (B, q) and tokens (B, T) int32. Returns
    (logits (B, T, V) float32: row i predicts ``tokens[:, i]``,
    seg (B, d_sam): the <SEG> embedding read at the last token).

    Routing is discontinuous: a pick near a tie goes one way in the
    program's bfloat16 and may go the other in float32, and with random
    weights one routed expert moves a token's state by a large share. So
    the reference is teacher-forced on the program's picks as it is on
    its tokens: ``served_picks`` replays the sequences through the
    program's own stages, and ``route`` takes those picks where its own
    choice is within ``TIE`` of a tie and turns the gates NaN if a pick
    it is sure of is missing or one it rules out is made. The fp8
    control (``mode="fp8"``) stands in the program's place: it takes the
    same picks through the same check on its own scores, so neither
    comparison reads a routing flip the other is spared. Where the
    replay does not reproduce every served token, its picks are not the
    ones the tokens were served with, and the logits are NaN."""
    ids = jnp.concatenate([jnp.asarray(query, jnp.int32),
                           jnp.asarray(tokens, jnp.int32)], axis=1)
    forced, greedy = served_picks(params, cfg, ctx, query, tokens)
    items = tuple(sorted(
        (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
        for k, v in cfg["trunk"].items() if not isinstance(v, list)))
    logits, seg = _trunk(params["llm"], params["seg_proj"],
                         jnp.asarray(ctx), ids, forced, cfg_items=items,
                         first=_deployment(cfg)["held_first"],
                         n_out=int(tokens.shape[1]), mode=mode)
    if not np.array_equal(greedy, np.asarray(tokens)):
        logits = jnp.full_like(logits, jnp.nan)
    return logits, seg


def served_picks(params, cfg: Dict[str, Any], ctx, query, tokens,
                 slots: int = 8):
    """Every MoE layer's routing picks (L, B, N, k) over ``[ctx; query;
    tokens]`` as the program serves them, and the tokens the replay
    serves (B, T): each prefix through the program's prefix stage, one
    sequence a call, then the answers through its paged decode stage,
    ``slots`` sequences to a step (the engine's slots), each step
    feeding the served token. The pool and page tables have the shapes
    the harness gives the engine (``drive.build_engine``), so the decode
    stage is the timed path's program; no computation mixes rows, so a
    row's picks do not depend on its neighbours or its pages. The picks
    are read from the program's router (``moe.route``) by a debug
    callback; the replay's own greedy tokens must equal the served ones
    (``trunk``)."""
    from unittest import mock

    from perfbench import model
    from repro.core import DualStreamExecutor
    from repro.core.lut import paper_lut
    from repro.core.paging import pages_for, prefix_positions
    from repro.models import moe as moe_lib

    ctx, query = np.asarray(ctx), np.asarray(query)
    tokens = np.asarray(tokens, np.int32)
    (B, T), S = tokens.shape, ctx.shape[1] + query.shape[1]
    ex = DualStreamExecutor(model.pipeline_config(cfg), params, {},
                            paper_lut(), max_new_tokens=T)
    page = ex.page_size
    n_pre, n_ans = pages_for(S, page), pages_for(T, page)
    n_row, n_pool = n_pre + n_ans, 1 + (2 * slots + 1) * n_pre \
        + slots * n_ans
    seen, route = [], moe_lib.route
    layers = cfg["trunk"]["num_hidden_layers"] - \
        cfg["trunk"]["first_k_dense_replace"]

    def recording(p, c, xt):
        out = route(p, c, xt)
        jax.debug.callback(lambda i: seen.append(np.asarray(i)), out[2],
                           ordered=True)
        return out

    picks = np.zeros((layers, B, S + T, cfg["trunk"]["num_experts_per_tok"]),
                     np.int32)
    greedy = np.zeros((B, T), np.int32)
    with mock.patch.object(moe_lib, "route", recording):
        for lo in range(0, B, slots):
            rows = range(lo, min(B, lo + slots))
            prefixes = []
            for b in rows:
                logits0, paged = ex.cloud_prefix(ctx[b:b + 1],
                                                 query[b:b + 1])
                greedy[b, 0] = np.argmax(np.asarray(logits0)[0])
                prefixes.append(paged)
            pool = jax.tree.map(lambda a: jnp.zeros(
                (a.shape[0], n_pool) + a.shape[2:], a.dtype), prefixes[0])
            table = np.zeros((slots, n_row), np.int32)
            positions = np.full((slots, n_row * page), -1, np.int32)
            for i, paged in enumerate(prefixes):
                table[i] = 1 + i * n_row + np.arange(n_row)
                pool = ex.pool_write(pool, paged, table[i, :n_pre])
                positions[i, :n_pre * page] = prefix_positions(S, n_pre,
                                                               page)
            feed = np.zeros((slots, 3), np.int32)      # token, pos, slot
            for j in range(T):
                feed[:len(rows), 0] = tokens[lo:lo + len(rows), j]
                feed[:len(rows), 1] = S + j
                feed[:len(rows), 2] = n_pre * page + j
                logits, _, pool = ex.cloud_decode_rows(
                    pool, table, positions, feed[:, :1], feed[:, 1],
                    feed[:, 2])
                positions[:len(rows), n_pre * page + j] = S + j
                if j + 1 < T:
                    greedy[lo:lo + len(rows), j + 1] = np.argmax(
                        np.asarray(logits)[:len(rows)], axis=-1)
            jax.effects_barrier()
            n = len(rows)
            pre = np.stack(seen[:n * layers]).reshape(n, layers, S, -1)
            dec = np.stack(seen[n * layers:]).reshape(T, layers, slots, -1)
            picks[:, lo:lo + n, :S] = pre.transpose(1, 0, 2, 3)
            picks[:, lo:lo + n, S:] = dec[:, :, :n].transpose(1, 2, 0, 3)
            seen.clear()
    return jnp.asarray(picks), greedy


# ---- counts from shapes ----

def token_flops(pcfg, ctx: int) -> float:
    """One token through the trunk attending ``ctx`` positions (itself
    included): latent attention as published (keys and values
    up-projected, each head scoring its nope and rope parts, reading its
    values), the dense MLP in the leading layers, and in each MoE layer
    the router, the shared expert and the routed experts this chip holds
    as an expectation: ``top_k * held / router`` picks a token."""
    llm = pcfg.llm
    m, e = llm.mla, llm.moe
    d, H = llm.d_model, llm.num_heads
    n, r, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    proj = 2 * (d * m.q_lora_rank + m.q_lora_rank * H * (n + r)
                + d * (m.kv_lora_rank + r) + m.kv_lora_rank * H * (n + dv)
                + H * dv * d)
    attn = 2 * ctx * H * (n + r + dv)
    expert = 2 * 3 * d * e.d_ff_expert
    moe = (2 * d * e.num_experts + e.num_shared_experts * expert
           + e.top_k * e.held / e.num_experts * expert)
    dense = 2 * 3 * d * e.d_ff_dense
    return float(llm.num_layers * (proj + attn)
                 + e.first_k_dense * dense
                 + (llm.num_layers - e.first_k_dense) * moe)


def paged_attention_cost(pcfg, ctx_lens: Sequence[int],
                         bytes_per_el: int = 2):
    """(operations, bytes) the paged latent attention needs in one decode
    step, all layers, with the up-projections absorbed as served: each
    live row's heads score the ``kv_lora_rank + qk_rope_head_dim``
    latent of every cached position and read its ``kv_lora_rank`` part
    back; the latent pages are read once, plus the queries and outputs."""
    llm = pcfg.llm
    m, H = llm.mla, llm.num_heads
    lat = m.kv_lora_rank + m.qk_rope_head_dim
    flops = bytes_ = 0.0
    for c in ctx_lens:
        flops += 2 * c * H * (lat + m.kv_lora_rank)
        bytes_ += (c * lat + H * (lat + m.kv_lora_rank)) * bytes_per_el
    return llm.num_layers * flops, llm.num_layers * bytes_
