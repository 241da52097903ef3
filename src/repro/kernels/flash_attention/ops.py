"""Jit'd wrapper: (B,S,H,hd) layout -> kernel layout, GQA head grouping,
sequence padding."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _k


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128) -> jax.Array:
    """q (B,S,H,hd), k/v (B,S,K,hd) with H % K == 0. Returns (B,S,H,hd).

    Heads are laid out kv-major (B, K, G, S, hd) so that query row p maps
    to kv row p // G in the kernel's index space.
    """
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    pad = (-S) % max(block_q, block_k)
    if pad:
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        qp, kp, vp = q, k, v
    Sp = S + pad
    # (B,S,H,hd) -> (B*H, S, hd) with H = K*G laid out kv-major
    qh = qp.reshape(B, Sp, K, G, hd).transpose(0, 2, 3, 1, 4) \
        .reshape(B * H, Sp, hd)
    kh = kp.transpose(0, 2, 1, 3).reshape(B * K, Sp, hd)
    vh = vp.transpose(0, 2, 1, 3).reshape(B * K, Sp, hd)
    out = _k.flash_call(qh, kh, vh, causal=causal, block_q=block_q,
                        block_k=block_k, valid_len=S)
    out = out.reshape(B, K, G, Sp, hd).transpose(0, 3, 1, 2, 4) \
        .reshape(B, Sp, H, hd)
    return out[:, :S]
