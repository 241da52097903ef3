"""Flash-decode kernel: one query token against a long KV cache.

The decode serving hot loop (Insight-stream token generation on the
cloud/pod side). Grid (B*H, kv_blocks) with the cache dimension innermost
and sequential: k/v blocks stream HBM->VMEM once, the online-softmax
running statistics (m, l, acc) stay in VMEM scratch, and the (1, hd)
output tile is written on the last block. HBM traffic is exactly one read
of the cache — the roofline floor the Pair-2 §Perf hillclimb drove decode
to.

``paged_decode_call`` is the page-table-aware variant for the paged KV
cache: k/v live in a shared page pool, read in its stored token-major
layout, and each row's pages are gathered through its page table
(scalar-prefetched) by the kernel's own async copies. One program covers
a batch row and a block of whole pages with all of the row's heads, so
each page is read once per row.

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
# VMEM the pages of one ``paged_decode_call`` program may take, K and V
# together, whatever the pool's width: with the kernel's other blocks this
# stays inside the compiler's default scoped VMEM on a v5e.
_PAGED_VMEM_BYTES = 8 << 20


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


def pages_per_block(n_pages: int, page_elems: int, itemsize: int) -> int:
    """Pages one program of ``paged_decode_call`` holds: as many as keep
    its K and V buffers (two slots each in the pool's dtype, plus one
    float32 copy) within ``_PAGED_VMEM_BYTES``, spread evenly over the
    fewest blocks that cover ``n_pages`` so the last block carries little
    padding. ``page_elems`` is one page's elements (page * K * hd)."""
    per_page = 2 * page_elems * (2 * itemsize + 4)
    most = max(1, _PAGED_VMEM_BYTES // per_page)
    return pl.cdiv(n_pages, pl.cdiv(n_pages, most))


def paged_decode_call(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      page_table: jax.Array, bias: jax.Array, *,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Page-table-aware gather path: the KV cache lives in a shared page
    pool and each batch row addresses it through its page table.

    q (B, K, G, hd) — each row's query heads grouped under their kv head;
    k_pool/v_pool (P, page*K, hd) — the stored (P, page, K, hd) pool with
    its page and head dims merged, so row ``t*K + kv`` of a page is token
    t's kv head ``kv``; page_table (B, n_pages) i32 page ids (every entry
    must be valid — unused rows point at the reserved trash page); bias
    (B, n_pages*page) additive over the row's gathered virtual sequence.
    Returns (B, K, G, hd).

    Grid (B, n_blocks), rows outer and blocks of ``pages_per_block``
    whole pages inner, both sequential. The pool stays in HBM: each page
    of a block is one contiguous (page*K, hd) async copy into a VMEM
    buffer, double-buffered so that the next block's pages (the next
    row's, after a row's last block) stream in while this block computes.
    The block is then widened to float32 once, and kv head ``kv``'s keys
    and values are its rows ``kv::K``; the head's G query heads score
    them in one (G, hd) x (hd, T) dot, so every page is read from HBM
    once per row. The online-softmax statistics of all H heads stay in
    VMEM scratch across blocks and the (K, G, hd) output is written on
    the last. A table whose width is not a multiple of the block is
    padded with page 0 under a masked bias.
    """
    B, K, G, hd = q.shape
    page = k_pool.shape[1] // K
    n_pages = page_table.shape[1]
    ppb = pages_per_block(n_pages, page * K * hd, k_pool.dtype.itemsize)
    n_blocks = pl.cdiv(n_pages, ppb)
    pad = n_blocks * ppb - n_pages
    if pad:
        page_table = jnp.pad(page_table, ((0, 0), (0, pad)))
        bias = jnp.pad(bias, ((0, 0), (0, pad * page)),
                       constant_values=NEG_INF)
    T = ppb * page
    # the flat table keeps SMEM small; the bias is viewed as
    # (B, n_blocks, 1, T) so each block's last two dims span the array's
    table = page_table.reshape(-1)
    bias = bias.reshape(B, n_blocks, 1, T)
    rows = (T * K, hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_blocks),
        in_specs=[
            pl.BlockSpec((1, K, G, hd), lambda b, j, pt: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1, T), lambda b, j, pt: (b, j, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, K, G, hd), lambda b, j, pt: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2,) + rows, k_pool.dtype),
            pltpu.VMEM((2,) + rows, v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM(rows, jnp.float32),
            pltpu.VMEM(rows, jnp.float32),
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_decode_kernel, scale=1.0 / (hd ** 0.5),
                               pages_per_block=ppb)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="paged_decode_attention",
        interpret=resolve_interpret(interpret),
    )(table, q, bias, k_pool, v_pool)


def _paged_decode_kernel(pt_ref, q_ref, bias_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, k_f32, v_f32, m_scr, l_scr,
                         acc_scr, *, scale: float, pages_per_block: int):
    """Online-softmax body of the paged path: one batch row against one
    block of whole pages, all of the row's query heads at once. The
    running statistics are ``_decode_kernel``'s, per head; the strided
    reads of a head's rows need 32-bit data, hence the float32 copy."""
    b, j = pl.program_id(0), pl.program_id(1)
    n_blocks = pl.num_programs(1)
    step = b * n_blocks + j
    slot = step % 2
    _, K, _, _ = q_ref.shape
    page_rows = k_hbm.shape[1]
    T = k_f32.shape[0] // K

    def copies(row, blk, slot):
        first = (row * n_blocks + blk) * pages_per_block
        out = []
        for i in range(pages_per_block):
            page_id = pt_ref[first + i]
            dst = pl.ds(i * page_rows, page_rows)
            out.append(pltpu.make_async_copy(
                k_hbm.at[page_id], k_buf.at[slot, dst], sems.at[0, slot]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[page_id], v_buf.at[slot, dst], sems.at[1, slot]))
        return out

    @pl.when(step == 0)
    def _fetch_first():
        for c in copies(b, j, slot):
            c.start()

    @pl.when(step + 1 < pl.num_programs(0) * n_blocks)
    def _prefetch_next():
        row_done = j == n_blocks - 1
        for c in copies(jnp.where(row_done, b + 1, b),
                        jnp.where(row_done, 0, j + 1), 1 - slot):
            c.start()

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    for c in copies(b, j, slot):
        c.wait()
    k_f32[...] = k_buf[slot].astype(jnp.float32)
    v_f32[...] = v_buf[slot].astype(jnp.float32)
    bias = bias_ref[0, 0].astype(jnp.float32)          # (1, T)
    for kv in range(K):
        q = q_ref[0, kv].astype(jnp.float32)           # (G, hd)
        head = pl.ds(kv, T, stride=K)
        k = k_f32[head, :]                             # (T, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale + bias                           # (G, T)
        m_prev, l_prev = m_scr[kv], l_scr[kv]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        acc_scr[kv] = acc_scr[kv] * alpha + jnp.dot(
            p, v_f32[head, :], preferred_element_type=jnp.float32)
        m_scr[kv] = m_new
        l_scr[kv] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

    @pl.when(j == n_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_verify_call(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      page_table: jax.Array, bias: jax.Array, *, group: int,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Multi-query paged attention for the speculative verify step.

    q (BH, C, hd) — C chunk tokens per (row, head) program, laid out
    kv-major as in ``decode_call``; k_pool/v_pool (K, P, page, hd);
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
