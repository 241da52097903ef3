"""Fused bottleneck kernels: low-rank projection + int8 quantisation.

TPU adaptation of the paper's learned compression (DESIGN.md §4.3): the
projection runs on the MXU with the quantisation fused into the epilogue,
so the full-width boundary activation is consumed tile-by-tile from VMEM
and only int8 codes + fp16-able scales are written back to HBM. The
decode kernel dequantises in VMEM and feeds the MXU directly.

Grid: one program per row-tile of tokens; the projection weight is small
(d x r with r << d) and resident in VMEM for every program.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


DEFAULT_BLOCK_T = 128


def _encode_kernel(x_ref, w_ref, codes_ref, scales_ref):
    z = jnp.dot(x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    s = jnp.max(jnp.abs(z), axis=-1, keepdims=True) / 127.0 + 1e-8
    codes_ref[...] = jnp.clip(jnp.round(z / s), -127, 127).astype(jnp.int8)
    scales_ref[...] = s


def encode_call(x: jax.Array, w_enc: jax.Array, *, block_t: int = DEFAULT_BLOCK_T,
                interpret: Optional[bool] = None):
    """x (T, d) [T % block_t == 0], w_enc (d, r)."""
    T, d = x.shape
    r = w_enc.shape[1]
    grid = (T // block_t,)
    return pl.pallas_call(
        _encode_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
            pl.BlockSpec((d, r), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, r), lambda i: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, r), jnp.int8),
            jax.ShapeDtypeStruct((T, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(x, w_enc)


def _decode_kernel(codes_ref, scales_ref, w_ref, out_ref, *, out_dtype):
    z = codes_ref[...].astype(jnp.float32) * scales_ref[...]
    out_ref[...] = jnp.dot(z, w_ref[...].astype(jnp.float32),
                           preferred_element_type=jnp.float32).astype(out_dtype)


def decode_call(codes: jax.Array, scales: jax.Array, w_dec: jax.Array,
                out_dtype=jnp.float32, *, block_t: int = DEFAULT_BLOCK_T,
                interpret: Optional[bool] = None):
    """codes (T, r) int8, scales (T, 1), w_dec (r, d)."""
    T, r = codes.shape
    d = w_dec.shape[1]
    grid = (T // block_t,)
    return pl.pallas_call(
        functools.partial(_decode_kernel, out_dtype=jnp.dtype(out_dtype)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, r), lambda i: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i: (i, 0)),
            pl.BlockSpec((r, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, d), jnp.dtype(out_dtype)),
        interpret=resolve_interpret(interpret),
    )(codes, scales, w_dec)
