"""Flash-decode kernel: one query token against a long KV cache.

The decode serving hot loop (Insight-stream token generation on the
cloud/pod side). Grid (B*H, kv_blocks) with the cache dimension innermost
and sequential: k/v blocks stream HBM->VMEM once, the online-softmax
running statistics (m, l, acc) stay in VMEM scratch, and the (1, hd)
output tile is written on the last block. HBM traffic is exactly one read
of the cache — the roofline floor the Pair-2 §Perf hillclimb drove decode
to.

``paged_decode_call`` is the page-table-aware variant for the paged KV
cache: k/v live in a shared page pool and each row's blocks are gathered
through its page table (scalar-prefetched, so the indirection is resolved
in the BlockSpec index maps — same one-pass cache traffic).

``paged_verify_call`` is the multi-query variant for speculative
decoding: a q-block of C chunk tokens (the last accepted token plus the
drafted continuations) scores against the row's paged cache in one
pass, with the per-query bias carrying the causal-within-chunk mask.
The online-softmax running statistics simply grow a leading C axis —
cache traffic stays one read per (row, head), amortised over all C
verify positions (the whole point of multi-token verification).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, num_kv_blocks: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                   # (1, hd)
    k = k_ref[0].astype(jnp.float32)                   # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (1, bk)
    s = s + bias_ref[0].astype(jnp.float32)            # (1, bk)
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

    @pl.when(ki == num_kv_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_call(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      page_table: jax.Array, bias: jax.Array, *, group: int,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Page-table-aware gather path: the KV cache lives in a shared page
    pool and each batch row addresses it through its page table.

    q (BH, 1, hd) laid out kv-major as in ``decode_call``; k_pool/v_pool
    (K, P, page, hd) — the shared pool, transposed kv-head-major so one
    (page, hd) tile is one block; page_table (B, n_pages) i32 page ids
    (every entry must be valid — unused rows point at the reserved trash
    page); bias (B, n_pages*page) additive over the row's gathered
    virtual sequence.

    The page table rides in as a scalar-prefetch operand, so the k/v
    BlockSpec index maps dereference it *before* the kernel body runs —
    each page streams HBM->VMEM exactly once per (row, head) program,
    the same online-softmax traffic floor as the contiguous kernel; only
    the addressing is indirect. The bias is viewed as
    (B, n_pages, 1, page) so each block's last two dims span the array's
    (the TPU tiling rule for a page narrower than 128 lanes).
    """
    BH, _, hd = q.shape
    page = k_pool.shape[2]
    B, n_pages = page_table.shape
    heads_per_batch = BH // B
    scale = 1.0 / (hd ** 0.5)
    bias = bias.reshape(B, n_pages, 1, page)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda h, ki, pt: (h, 0, 0)),
            pl.BlockSpec(
                (1, 1, page, hd),
                lambda h, ki, pt: ((h % heads_per_batch) // group,
                                   pt[h // heads_per_batch, ki], 0, 0)),
            pl.BlockSpec(
                (1, 1, page, hd),
                lambda h, ki, pt: ((h % heads_per_batch) // group,
                                   pt[h // heads_per_batch, ki], 0, 0)),
            pl.BlockSpec((1, 1, 1, page),
                         lambda h, ki, pt: (h // heads_per_batch, ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda h, ki, pt: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               num_kv_blocks=n_pages)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, 1, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(page_table, q, k_pool, v_pool, bias)


def _paged_decode_kernel(pt_ref, q_ref, k_ref, v_ref, bias_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, scale: float,
                         num_kv_blocks: int):
    """Online-softmax body of the paged path. Identical running-statistics
    scheme to ``_decode_kernel``; the only differences are the (consumed
    by the index maps) scalar-prefetch page-table ref and the extra pool
    axis on the k/v blocks."""
    del pt_ref                                         # used by index maps
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                   # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)                # (page, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    s = s + bias_ref[0, 0].astype(jnp.float32)         # (1, page)
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

    @pl.when(ki == num_kv_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_verify_call(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      page_table: jax.Array, bias: jax.Array, *, group: int,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Multi-query paged attention for the speculative verify step.

    q (BH, C, hd) — C chunk tokens per (row, head) program, laid out
    kv-major as in ``paged_decode_call``; k_pool/v_pool (K, P, page, hd);
    page_table (B, n_pages) i32 (every entry valid — idle rows park on
    the reserved trash page); bias (B, C, n_pages*page) additive per
    query position over the row's gathered virtual sequence — the caller
    encodes both slot validity and causal-within-chunk there.

    Grid (BH, n_pages), cache-innermost: each page streams HBM->VMEM
    once per (row, head) and all C verify positions score against it
    before the next page loads — the (C, 1)/(C, hd) running statistics
    live in VMEM scratch exactly like the single-query kernel's. The
    bias is laid out page-major, (B, n_pages, C, page), so each block's
    last two dims span the array's.
    """
    BH, C, hd = q.shape
    page = k_pool.shape[2]
    B, n_pages = page_table.shape
    heads_per_batch = BH // B
    scale = 1.0 / (hd ** 0.5)
    bias = bias.reshape(B, C, n_pages, page).transpose(0, 2, 1, 3)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, n_pages),
        in_specs=[
            pl.BlockSpec((1, C, hd), lambda h, ki, pt: (h, 0, 0)),
            pl.BlockSpec(
                (1, 1, page, hd),
                lambda h, ki, pt: ((h % heads_per_batch) // group,
                                   pt[h // heads_per_batch, ki], 0, 0)),
            pl.BlockSpec(
                (1, 1, page, hd),
                lambda h, ki, pt: ((h % heads_per_batch) // group,
                                   pt[h // heads_per_batch, ki], 0, 0)),
            pl.BlockSpec((1, 1, C, page),
                         lambda h, ki, pt: (h // heads_per_batch, ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, C, hd), lambda h, ki, pt: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((C, 1), jnp.float32),
            pltpu.VMEM((C, 1), jnp.float32),
            pltpu.VMEM((C, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_verify_kernel, scale=scale,
                               num_kv_blocks=n_pages)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, C, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(page_table, q, k_pool, v_pool, bias)


def _paged_verify_kernel(pt_ref, q_ref, k_ref, v_ref, bias_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, scale: float,
                         num_kv_blocks: int):
    """Online-softmax body of the multi-query verify path: the decode
    kernel's running statistics with a leading C (chunk) axis."""
    del pt_ref                                         # used by index maps
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                   # (C, hd)
    k = k_ref[0, 0].astype(jnp.float32)                # (page, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    s = s + bias_ref[0, 0].astype(jnp.float32)         # (C, page)
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

    @pl.when(ki == num_kv_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_call(q: jax.Array, k: jax.Array, v: jax.Array, bias: jax.Array,
                *, group: int, block_k: int = 512,
                interpret: Optional[bool] = None) -> jax.Array:
    """q (BH, 1, hd); k/v (BK, W, hd); bias (B, W). BH = B*H laid out
    kv-major so query row p reads kv row p // group and bias row
    p // (H) — H passed implicitly via bias grid math below. The bias is
    viewed as (B, 1, W) so a (1, 1, block_k) block spans the array's
    second-to-last dim."""
    BH, _, hd = q.shape
    BK, W, _ = k.shape
    assert W % block_k == 0, (W, block_k)
    nk = W // block_k
    B = bias.shape[0]
    heads_per_batch = BH // B
    scale = 1.0 / (hd ** 0.5)
    bias = bias.reshape(B, 1, W)
    kernel = functools.partial(_decode_kernel, scale=scale, num_kv_blocks=nk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nk),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda h, ki: (h, 0, 0)),
            pl.BlockSpec((1, block_k, hd), lambda h, ki: (h // group, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda h, ki: (h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda h, ki: (h // heads_per_batch, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda h, ki: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v, bias)
