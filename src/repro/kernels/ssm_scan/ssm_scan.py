"""Chunked selective-scan kernel (Mamba recurrence) for TPU.

h_t = decay_t * h_{t-1} + drive_t, scanned over the sequence axis.

Grid (B, channel_blocks, seq_chunks) with the sequence dimension innermost
and sequential; the running state h (bc, N) is carried in VMEM scratch
across chunks, so HBM traffic is exactly one read of (decay, drive) and
one write of h — the TPU-native adaptation of Mamba's CUDA scan: instead
of warp-level prefix products, the VPU iterates the small in-chunk
recurrence over lanes of (channels x state) held in vector registers
(DESIGN.md §4.3).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _scan_kernel(decay_ref, drive_ref, h_ref, state_scr, *, chunk: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    dec = decay_ref[0].astype(jnp.float32)     # (chunk, bc, N)
    drv = drive_ref[0].astype(jnp.float32)

    def step(t, h):
        h = dec[t] * h + drv[t]
        h_ref[0, pl.dslice(t, 1)] = h[None].astype(h_ref.dtype)
        return h

    state_scr[...] = jax.lax.fori_loop(0, chunk, step, state_scr[...])


def scan_call(decay: jax.Array, drive: jax.Array, *, chunk: int = 64,
              block_c: int = 128,
              interpret: Optional[bool] = None) -> jax.Array:
    """decay/drive (B, S, C, N); S % chunk == 0, C % block_c == 0."""
    B, S, C, N = decay.shape
    grid = (B, C // block_c, S // chunk)
    spec = pl.BlockSpec((1, chunk, block_c, N),
                        lambda b, ci, si: (b, si, ci, 0))
    return pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, S, C, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_c, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(decay, drive)
