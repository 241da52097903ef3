"""Jit'd wrapper for the chunked selective scan: pads channels/sequence to
block multiples and restores the original shape."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssm_scan import ssm_scan as _k


@functools.partial(jax.jit, static_argnames=("chunk", "block_c"))
def chunked_scan(decay: jax.Array, drive: jax.Array, chunk: int = 64,
                 block_c: int = 128) -> jax.Array:
    """h_t = decay_t * h_{t-1} + drive_t over axis 1. (B, S, C, N) in,
    (B, S, C, N) f32 out."""
    B, S, C, N = decay.shape
    chunk = min(chunk, S)
    block_c = min(block_c, C)
    pad_s = (-S) % chunk
    pad_c = (-C) % block_c
    if pad_s or pad_c:
        pads = ((0, 0), (0, pad_s), (0, pad_c), (0, 0))
        decay = jnp.pad(decay, pads)
        drive = jnp.pad(drive, pads)
    out = _k.scan_call(decay, drive, chunk=chunk, block_c=block_c)
    return out[:, :S, :C]
