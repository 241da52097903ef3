"""Jit'd wrappers for the bottleneck kernels: handle (B, S, d) batching
and token-count padding to the row-tile."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.bottleneck import bottleneck as _k


def _flatten(x):
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def _pad_rows(x, block):
    T = x.shape[0]
    pad = (-T) % block
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x, T


@functools.partial(jax.jit, static_argnames=("block_t",))
def bottleneck_encode(x: jax.Array, w_enc: jax.Array,
                      block_t: int = _k.DEFAULT_BLOCK_T
                      ) -> Tuple[jax.Array, jax.Array]:
    """x (..., d) -> (codes int8 (..., r), scales f32 (..., 1))."""
    flat, lead = _flatten(x)
    flat, T = _pad_rows(flat, block_t)
    codes, scales = _k.encode_call(flat, w_enc, block_t=block_t)
    r = w_enc.shape[1]
    return (codes[:T].reshape(*lead, r),
            scales[:T].reshape(*lead, 1))


@functools.partial(jax.jit, static_argnames=("out_dtype", "block_t"))
def bottleneck_decode(codes: jax.Array, scales: jax.Array, w_dec: jax.Array,
                      out_dtype=jnp.float32,
                      block_t: int = _k.DEFAULT_BLOCK_T) -> jax.Array:
    flat, lead = _flatten(codes)
    sflat = scales.reshape(-1, 1)
    flat, T = _pad_rows(flat, block_t)
    sflat, _ = _pad_rows(sflat, block_t)
    out = _k.decode_call(flat, sflat, w_dec, out_dtype=out_dtype,
                         block_t=block_t)
    return out[:T].reshape(*lead, w_dec.shape[1])
