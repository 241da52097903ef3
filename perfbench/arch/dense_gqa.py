"""Dense grouped-query-attention trunk (Phi-4-mini, Qwen2-VL's decoder).

Pre-norm decoder blocks: RMSNorm, grouped-query attention with
rotate-half RoPE over the whole head on one position per token, optional
q/k/v bias, SiLU-gated MLP; the final norm's output feeds an untied
answer head and the <SEG> projection.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from perfbench.reference import VOCAB_BLOCK, attention, f32, mm, rms

READS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
         "num_key_value_heads", "head_dim", "intermediate_size",
         "vocab_size", "rms_norm_eps", "rope_theta", "attention_bias")
# what the program's dense trunk and the reference below compute
FIXED = {"hidden_act": "silu", "partial_rotary_factor": 1.0,
         "rope_scaling": None, "tie_word_embeddings": False}
INFO = ("max_position_embeddings", "torch_dtype")


def llm_config(cfg: Dict[str, Any]):
    """The trunk block as the program's ``ModelConfig``."""
    from repro.models import ModelConfig
    t, dtype = cfg["trunk"], cfg["dtype"]
    return ModelConfig(
        name=cfg["name"], arch_type="dense",
        num_layers=t["num_hidden_layers"], d_model=t["hidden_size"],
        num_heads=t["num_attention_heads"],
        num_kv_heads=t["num_key_value_heads"], d_ff=t["intermediate_size"],
        vocab_size=t["vocab_size"], head_dim=t["head_dim"],
        qkv_bias=bool(t["attention_bias"]), rope_theta=t["rope_theta"],
        norm_eps=t["rms_norm_eps"], param_dtype=dtype, act_dtype=dtype)


# ---- the float32 reference ----

def _rope(x, pos, theta):
    """x (B, N, h, hd); rotate-half pairs over the whole head."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv          # (N, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _trunk_layer(t: Dict[str, Any], mode: str, x, lp, pos, mask):
    H, K, hd = (t["num_attention_heads"], t["num_key_value_heads"],
                t["head_dim"])
    B, N, _ = x.shape
    a = lp["attn"]
    h = rms(x, lp["norm1"]["w"], t["rms_norm_eps"])
    q = mm("bnd,de->bne", h, a["wq"], mode)
    k = mm("bnd,de->bne", h, a["wk"], mode)
    v = mm("bnd,de->bne", h, a["wv"], mode)
    if t["attention_bias"]:
        q, k, v = q + f32(a["bq"]), k + f32(a["bk"]), v + f32(a["bv"])
    q = _rope(q.reshape(B, N, H, hd), pos, t["rope_theta"])
    k = _rope(k.reshape(B, N, K, hd), pos, t["rope_theta"])
    v = v.reshape(B, N, K, hd)
    x = x + mm("bne,ed->bnd", attention(q, k, v, mask, mode), a["wo"],
               mode)
    m = lp["mlp"]
    h = rms(x, lp["norm2"]["w"], t["rms_norm_eps"])
    g = mm("bnd,df->bnf", h, m["w_gate"], mode)
    u = mm("bnd,df->bnf", h, m["w_up"], mode)
    return x + mm("bnf,fd->bnd", jax.nn.silu(g) * u, m["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("cfg_items", "n_out", "mode"))
def _trunk(llm, seg_proj, ctx, ids, *, cfg_items, n_out, mode):
    t = dict(cfg_items)
    emb = f32(jnp.take(llm["embed"], ids, axis=0))
    x = jnp.concatenate([f32(ctx), emb], axis=1)
    N = x.shape[1]
    pos = jnp.arange(N)
    mask = (pos[None, :] <= pos[:, None])[None, None, None]

    def body(x, lp):
        return _trunk_layer(t, mode, x, lp, pos, mask), None

    x, _ = jax.lax.scan(body, x, llm["groups"][0])
    x = rms(x, llm["norm"]["w"], t["rms_norm_eps"])
    first = N - 1 - n_out            # predicts the first answer token
    xo = x[:, first:first + n_out]
    head = llm["answer_head"]
    V = head.shape[1]
    logits = jnp.concatenate(
        [mm("bnd,dv->bnv", xo, head[:, lo:min(V, lo + VOCAB_BLOCK)], mode)
         for lo in range(0, V, VOCAB_BLOCK)], axis=-1)
    seg = mm("bd,de->be", x[:, -1], seg_proj, mode)
    return logits, seg


def trunk(params, cfg: Dict[str, Any], ctx, query, tokens, mode="ref"):
    """Teacher-forced trunk over ``[ctx; query; tokens]``.

    ctx (B, C, d); query (B, q) and tokens (B, T) int32. Returns
    (logits (B, T, V) float32: row i predicts ``tokens[:, i]``,
    seg (B, d_sam): the <SEG> embedding read at the last token)."""
    ids = jnp.concatenate([jnp.asarray(query, jnp.int32),
                           jnp.asarray(tokens, jnp.int32)], axis=1)
    items = tuple(sorted((k, v) for k, v in cfg["trunk"].items()
                         if not isinstance(v, (dict, list))))
    return _trunk(params["llm"], params["seg_proj"], jnp.asarray(ctx), ids,
                  cfg_items=items, n_out=int(tokens.shape[1]), mode=mode)


# ---- counts from shapes ----

def _shape(pcfg):
    llm = pcfg.llm
    return (llm.num_layers, llm.d_model, llm.num_heads, llm.num_kv_heads,
            llm.resolved_head_dim, llm.d_ff, llm.vocab_size)


def token_flops(pcfg, ctx: int) -> float:
    """One token through the trunk attending ``ctx`` positions (itself
    included): projections, attention and the gated MLP in every layer."""
    L, d, H, K, hd, f, _ = _shape(pcfg)
    proj = 2 * d * (2 * H * hd + 2 * K * hd)
    attn = 2 * 2 * ctx * H * hd
    mlp = 2 * 3 * d * f
    return float(L * (proj + attn + mlp))


def paged_attention_cost(pcfg, ctx_lens: Sequence[int],
                         bytes_per_el: int = 2):
    """(operations, bytes) the paged decode attention needs in one step,
    all layers: each live row's query heads against its cached keys and
    values, each kv head read once, plus its queries and outputs."""
    L, _, H, K, hd, _, _ = _shape(pcfg)
    flops = bytes_ = 0.0
    for c in ctx_lens:
        flops += 2 * 2 * c * H * hd
        bytes_ += (2 * c * K * hd + 2 * H * hd) * bytes_per_el
    return L * flops, L * bytes_
