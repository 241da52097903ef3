"""Jit'd wrapper: (B,H,hd) / (B,W,K,hd) layouts, cache-length padding.

Per-shard head counts (sharded serving): under tensor parallelism the
paged pool shards kv-heads over the mesh's "model" axis, so inside a
``shard_map`` each shard calls these wrappers with
``K = n_kv_heads / model_shards`` (and ``H = num_heads / model_shards``)
— the ``group``/``heads_per_batch`` grid math is derived from the
per-shard shapes, so the kernel bodies run unchanged on the smaller K.
A ``pallas_call`` is not partitioned by GSPMD, so until the kernels run
under ``shard_map`` ``ShardedServingContext`` serves the jnp reference
attention instead (XLA partitions it over the head-sharded operands).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import decode_attention as _k

NEG_INF = -1e30


@functools.partial(jax.jit, static_argnames=("block_k",))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     bias: jax.Array, block_k: int = 512) -> jax.Array:
    """q (B,H,hd); k/v (B,W,K,hd); bias (B,W) additive. -> (B,H,hd)."""
    B, H, hd = q.shape
    W, K = k.shape[1], k.shape[2]
    G = H // K
    block_k = min(block_k, W)
    pad = (-W) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        bias = jnp.pad(bias, ((0, 0), (0, pad)), constant_values=NEG_INF)
    qh = q.reshape(B, K, G, hd).reshape(B * H, 1, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(B * K, W + pad, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(B * K, W + pad, hd)
    out = _k.decode_call(qh, kh, vh, bias, group=G, block_k=block_k)
    return out.reshape(B, K, G, hd).reshape(B, H, hd)


@jax.jit
def paged_decode_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           page_table: jax.Array,
                           bias: jax.Array) -> jax.Array:
    """Flash decode against a paged KV cache.

    q (B,H,hd); k_pool/v_pool (P, page, K, hd) — the shared page pool;
    page_table (B, n_pages) i32 page ids (all entries must be valid —
    point unused rows at the reserved trash page); bias
    (B, n_pages*page) additive over the gathered virtual sequence.
    Returns (B,H,hd). One program per row and block of whole pages; the
    pool is read in its stored layout: XLA tiles the pool's (K, hd) rows
    contiguously (in (2, 128) tiles at K 2, (8, 128) at K 8 and 32), so
    merging its page and head dims is a bitcast, and nothing is
    transposed. The page table is resolved via scalar prefetch.
    """
    B, H, hd = q.shape
    P, page, K, _ = k_pool.shape
    out = _k.paged_decode_call(q.reshape(B, K, H // K, hd),
                               k_pool.reshape(P, page * K, hd),
                               v_pool.reshape(P, page * K, hd),
                               jnp.asarray(page_table, jnp.int32), bias)
    return out.reshape(B, H, hd)


@jax.jit
def paged_verify_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, page_table: jax.Array,
                           bias: jax.Array) -> jax.Array:
    """Multi-query flash attention against a paged KV cache — the
    speculative-decode verify step.

    q (B, C, H, hd) — C chunk tokens (last accepted token + drafts) per
    row; k_pool/v_pool (P, page, K, hd); page_table (B, n_pages) i32
    (all entries valid); bias (B, C, n_pages*page) additive per query
    position (slot validity + causal-within-chunk). Returns
    (B, C, H, hd). One kv block per page, page table resolved via scalar
    prefetch; column 0 of a C=1 call matches ``paged_decode_attention``.
    """
    B, C, H, hd = q.shape
    K = k_pool.shape[2]
    G = H // K
    # kv-major head layout: program h reads kv head (h % H) // G
    qh = q.transpose(0, 2, 1, 3).reshape(B, K, G, C, hd) \
          .reshape(B * H, C, hd)
    kh = k_pool.transpose(2, 0, 1, 3)                  # (K, P, page, hd)
    vh = v_pool.transpose(2, 0, 1, 3)
    out = _k.paged_verify_call(qh, kh, vh,
                               jnp.asarray(page_table, jnp.int32), bias,
                               group=G)
    return out.reshape(B, K, G, C, hd).reshape(B, H, C, hd) \
              .transpose(0, 2, 1, 3)
