"""Plain float32 reference of the AVERY cloud pipeline.

Written from the architecture the configuration states, in
straightforward ``jax.numpy`` at ``highest`` matmul precision; it imports
nothing of the program. It reads the weights the benchmark made, by name,
and upcasts them one layer (or one block of the vocabulary) at a time, so
that it fits beside the served weights.

* Trunk: pre-norm decoder blocks, RMSNorm, grouped-query attention with
  rotate-half RoPE on one position per token, optional q/k/v bias, SiLU
  gated MLP; the answer head and the <SEG> projection read the final
  norm's output.
* SAM tail: int8 codes times their scales through the bottleneck's
  decoder, then the encoder blocks after the split (LayerNorm,
  bidirectional multi-head attention without position terms, tanh-GELU
  MLP) and the final LayerNorm.
* Mask head: the features gated by the <SEG> embedding, a tanh-GELU
  layer, one logit per patch.

``mode="fp8"`` is the control: every matmul operand rounded to float8
e4m3 with a per-tensor scale, the precision below the bfloat16 the
configuration serves.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
VOCAB_BLOCK = 16384


def _q8(x):
    s = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, a, b, mode: str):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(w)


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(w) + _f32(b)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _rope(x, pos, theta):
    """x (B, N, h, hd); rotate-half pairs over the whole head."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv          # (N, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, mask, mode):
    """q (B, N, H, hd), k/v (B, N, K, hd); head h reads kv head h // G."""
    B, N, H, hd = q.shape
    K = k.shape[2]
    q = q.reshape(B, N, K, H // K, hd)
    s = _mm("bqkgh,btkh->bkgqt", q, k, mode) / math.sqrt(hd)
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("bkgqt,btkh->bqkgh", p, v, mode)
    return o.reshape(B, N, H * hd)


def _trunk_layer(t: Dict[str, Any], mode: str, x, lp, pos, mask):
    H, K, hd = (t["num_attention_heads"], t["num_key_value_heads"],
                t["head_dim"])
    B, N, _ = x.shape
    a = lp["attn"]
    h = _rms(x, lp["norm1"]["w"], t["rms_norm_eps"])
    q = _mm("bnd,de->bne", h, a["wq"], mode)
    k = _mm("bnd,de->bne", h, a["wk"], mode)
    v = _mm("bnd,de->bne", h, a["wv"], mode)
    if t["attention_bias"]:
        q, k, v = q + _f32(a["bq"]), k + _f32(a["bk"]), v + _f32(a["bv"])
    q = _rope(q.reshape(B, N, H, hd), pos, t["rope_theta"])
    k = _rope(k.reshape(B, N, K, hd), pos, t["rope_theta"])
    v = v.reshape(B, N, K, hd)
    x = x + _mm("bne,ed->bnd", _attention(q, k, v, mask, mode), a["wo"],
                mode)
    m = lp["mlp"]
    h = _rms(x, lp["norm2"]["w"], t["rms_norm_eps"])
    g = _mm("bnd,df->bnf", h, m["w_gate"], mode)
    u = _mm("bnd,df->bnf", h, m["w_up"], mode)
    return x + _mm("bnf,fd->bnd", jax.nn.silu(g) * u, m["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("cfg_items", "n_out", "mode"))
def _trunk(llm, seg_proj, ctx, ids, *, cfg_items, n_out, mode):
    t = dict(cfg_items)
    emb = _f32(jnp.take(llm["embed"], ids, axis=0))
    x = jnp.concatenate([_f32(ctx), emb], axis=1)
    N = x.shape[1]
    pos = jnp.arange(N)
    mask = (pos[None, :] <= pos[:, None])[None, None, None]

    def body(x, lp):
        return _trunk_layer(t, mode, x, lp, pos, mask), None

    x, _ = jax.lax.scan(body, x, llm["groups"][0])
    x = _rms(x, llm["norm"]["w"], t["rms_norm_eps"])
    first = N - 1 - n_out            # predicts the first answer token
    xo = x[:, first:first + n_out]
    head = llm["answer_head"]
    V = head.shape[1]
    logits = jnp.concatenate(
        [_mm("bnd,dv->bnv", xo, head[:, lo:min(V, lo + VOCAB_BLOCK)], mode)
         for lo in range(0, V, VOCAB_BLOCK)], axis=-1)
    seg = _mm("bd,de->be", x[:, -1], seg_proj, mode)
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


def _enc_layer(e: Dict[str, Any], mode: str, x, lp):
    H = e["num_heads"]
    B, N, d = x.shape
    a = lp["attn"]
    h = _ln(x, lp["norm1"]["w"], lp["norm1"]["b"], e["layer_norm_eps"])
    q = _mm("bnd,de->bne", h, a["wq"], mode).reshape(B, N, H, d // H)
    k = _mm("bnd,de->bne", h, a["wk"], mode).reshape(B, N, H, d // H)
    v = _mm("bnd,de->bne", h, a["wv"], mode).reshape(B, N, H, d // H)
    x = x + _mm("bne,ed->bnd", _attention(q, k, v, None, mode), a["wo"],
                mode)
    m = lp["mlp"]
    h = _ln(x, lp["norm2"]["w"], lp["norm2"]["b"], e["layer_norm_eps"])
    return x + _mm("bnf,fd->bnd",
                   _gelu(_mm("bnd,df->bnf", h, m["w_up"], mode)),
                   m["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode"))
def _mask(sam, mask_head, dec, codes, scales, seg, *, cfg_items, mode):
    e = dict(cfg_items)
    z = codes.astype(jnp.float32) * scales
    x = _mm("bnr,rd->bnd", z, dec, mode)
    layers = jax.tree.map(lambda a: a[e["split_layer"]:], sam["groups"][0])
    x, _ = jax.lax.scan(lambda x, lp: (_enc_layer(e, mode, x, lp), None),
                        x, layers)
    feats = _ln(x, sam["norm"]["w"], sam["norm"]["b"], e["layer_norm_eps"])
    fused = feats * _f32(seg)[:, None, :]
    h = _gelu(_mm("bnd,de->bne", fused, mask_head["w1"], mode)
              + _f32(mask_head["b1"]))
    pix = _mm("bne,ep->bnp", h, mask_head["w2"], mode)
    g = e["image_size"] // e["patch_size"]
    return pix.reshape(pix.shape[0], g, g)


def mask(params, bottleneck, cfg: Dict[str, Any], codes, scales, seg,
         mode="ref"):
    """Mask logits (B, g, g) of one Insight frame per row."""
    items = tuple(sorted(cfg["sam"].items()))
    return _mask(params["sam"], params["mask_head"], bottleneck["dec"],
                 jnp.asarray(codes), jnp.asarray(scales), jnp.asarray(seg),
                 cfg_items=items, mode=mode)
