"""Plain float32 reference of the AVERY cloud pipeline: its shared parts.

Written from the architecture the configuration states, in
straightforward ``jax.numpy`` at ``highest`` matmul precision; it imports
nothing of the program. It reads the weights the benchmark made, by name,
and upcasts them one layer (or one block of the vocabulary) at a time, so
that it fits beside the served weights.

* Trunk: in the configuration's architecture module (``arch/<arch>.py``,
  ``trunk``), built from ``mm``, ``f32``, ``rms`` and ``attention`` here.
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


def mm(spec: str, a, b, mode: str):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def f32(x):
    return x.astype(jnp.float32)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * f32(w)


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * f32(w) + f32(b)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def attention(q, k, v, mask, mode):
    """q (B, N, H, hd), k/v (B, N, K, hd); head h reads kv head h // G."""
    B, N, H, hd = q.shape
    K = k.shape[2]
    q = q.reshape(B, N, K, H // K, hd)
    s = mm("bqkgh,btkh->bkgqt", q, k, mode) / math.sqrt(hd)
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("bkgqt,btkh->bqkgh", p, v, mode)
    return o.reshape(B, N, H * hd)


def _enc_layer(e: Dict[str, Any], mode: str, x, lp):
    H = e["num_heads"]
    B, N, d = x.shape
    a = lp["attn"]
    h = _ln(x, lp["norm1"]["w"], lp["norm1"]["b"], e["layer_norm_eps"])
    q = mm("bnd,de->bne", h, a["wq"], mode).reshape(B, N, H, d // H)
    k = mm("bnd,de->bne", h, a["wk"], mode).reshape(B, N, H, d // H)
    v = mm("bnd,de->bne", h, a["wv"], mode).reshape(B, N, H, d // H)
    x = x + mm("bne,ed->bnd", attention(q, k, v, None, mode), a["wo"],
                mode)
    m = lp["mlp"]
    h = _ln(x, lp["norm2"]["w"], lp["norm2"]["b"], e["layer_norm_eps"])
    return x + mm("bnf,fd->bnd",
                   _gelu(mm("bnd,df->bnf", h, m["w_up"], mode)),
                   m["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode"))
def _mask(sam, mask_head, dec, codes, scales, seg, *, cfg_items, mode):
    e = dict(cfg_items)
    z = codes.astype(jnp.float32) * scales
    x = mm("bnr,rd->bnd", z, dec, mode)
    layers = jax.tree.map(lambda a: a[e["split_layer"]:], sam["groups"][0])
    x, _ = jax.lax.scan(lambda x, lp: (_enc_layer(e, mode, x, lp), None),
                        x, layers)
    feats = _ln(x, sam["norm"]["w"], sam["norm"]["b"], e["layer_norm_eps"])
    fused = feats * f32(seg)[:, None, :]
    h = _gelu(mm("bnd,de->bne", fused, mask_head["w1"], mode)
              + f32(mask_head["b1"]))
    pix = mm("bne,ep->bnp", h, mask_head["w2"], mode)
    g = e["image_size"] // e["patch_size"]
    return pix.reshape(pix.shape[0], g, g)


def mask(params, bottleneck, cfg: Dict[str, Any], codes, scales, seg,
         mode="ref"):
    """Mask logits (B, g, g) of one Insight frame per row."""
    items = tuple(sorted(cfg["sam"].items()))
    return _mask(params["sam"], params["mask_head"], bottleneck["dec"],
                 jnp.asarray(codes), jnp.asarray(scales), jnp.asarray(seg),
                 cfg_items=items, mode=mode)
