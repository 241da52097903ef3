"""Blocked online-softmax attention (FlashAttention) for TPU.

Grid (batch*q_heads, q_blocks, kv_blocks) with the kv dimension innermost
and sequential; running (m, l, acc) statistics live in VMEM scratch and
the output tile is written on the last kv step. K/V are streamed
block-by-block HBM->VMEM by the BlockSpec pipeline — the TPU-native
shape of the algorithm (no shared-memory/warp semantics; DESIGN.md §4.3).

GQA is handled in the k/v index_map: query-head program p attends to
kv-head p % H // group. Causal masking uses global block offsets; fully
masked kv blocks are skipped via pl.when on the block index.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  num_kv_blocks: int, valid_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # with causal masking, blocks strictly above the diagonal contribute 0
    live = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)              # (bq, hd)
        k = k_ref[0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0].astype(jnp.float32)              # (bk, hd)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        ok = cols < valid_len
        if causal:
            ok &= cols <= rows
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[...]                           # (bq, 1)
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_call(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
               block_q: int = 128, block_k: int = 128,
               valid_len: int = -1,
               interpret: Optional[bool] = None) -> jax.Array:
    """q (BH, S, hd), k/v (BK, S, hd), BH % BK == 0 (grouped heads laid out
    so that query row p maps to kv row p // group)."""
    BH, S, hd = q.shape
    BK = k.shape[0]
    group = BH // BK
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    nq, nk = S // block_q, S // block_k
    scale = 1.0 / (hd ** 0.5)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_kv_blocks=nk,
        valid_len=S if valid_len < 0 else valid_len)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda h, qi, ki: (h // group, ki, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda h, qi, ki: (h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda h, qi, ki: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
