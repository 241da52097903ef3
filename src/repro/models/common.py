"""Shared NN primitives: norms, activations, RoPE (incl. M-RoPE), inits.

Pure-functional JAX. Parameters are pytrees (nested dicts of jnp arrays);
every function takes params explicitly. No flax/haiku dependency.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# initialisation
# ---------------------------------------------------------------------------


def normal_init(rng: jax.Array, shape: Sequence[int], scale: float,
                dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(rng, shape, dtype=jnp.float32) * scale).astype(dtype)


def fan_in_init(rng: jax.Array, shape: Sequence[int], dtype=jnp.float32) -> jax.Array:
    """LeCun-style init for a (fan_in, fan_out) weight matrix."""
    scale = 1.0 / math.sqrt(max(1, shape[0]))
    return normal_init(rng, shape, scale, dtype)


def zeros_init(shape: Sequence[int], dtype=jnp.float32) -> jax.Array:
    return jnp.zeros(shape, dtype)


def ones_init(shape: Sequence[int], dtype=jnp.float32) -> jax.Array:
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# norms / activations
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def silu(x: jax.Array) -> jax.Array:
    return x * jax.nn.sigmoid(x)


def relu2(x: jax.Array) -> jax.Array:
    """Squared ReLU (Nemotron-4)."""
    r = jax.nn.relu(x)
    return r * r


def gelu(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x, approximate=True)


ACTIVATIONS = {"silu": silu, "relu2": relu2, "gelu": gelu}


def linear(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None) -> jax.Array:
    y = x @ w.astype(x.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def _rope_angles(positions: jax.Array, dim: int, theta: float) -> jax.Array:
    """positions (...,) -> angles (..., dim//2) in float32."""
    half = dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    return positions.astype(jnp.float32)[..., None] * inv_freq


def _apply_angles(x: jax.Array, angles: jax.Array) -> jax.Array:
    """Rotate pairs (even, odd interleaved as two halves).

    x: (B, S, H, D); angles: (B, S, D//2) broadcast over heads.
    Uses the 'rotate_half' (contiguous halves) convention.
    """
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = xf[..., :half], xf[..., half:]
    cos = jnp.cos(angles)[..., None, :]  # (B, S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Standard 1-D RoPE. x: (B, S, H, D), positions: (B, S)."""
    angles = _rope_angles(positions, x.shape[-1], theta)
    return _apply_angles(x, angles)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor: 0.1 * mscale * ln(factor) + 1 (1 at
    factor <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn) -> jax.Array:
    """The rotary inverse frequencies (dim//2,) under YaRN
    (``config.YarnConfig``): the band of dims between the ones that turn
    ``beta_fast`` and ``beta_slow`` times over the original context goes
    linearly from the plain frequency to the plain one over ``factor``."""
    half = dim // 2

    def turns(n):                       # the dim that turns n times
        return dim * math.log(yarn.original_max_position
                              / (n * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns(yarn.beta_fast)), 0)
    high = min(math.ceil(turns(yarn.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    plain = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    return plain / yarn.factor * ramp + plain * (1.0 - ramp)


def apply_yarn_rope(x: jax.Array, positions: jax.Array, theta: float,
                    yarn) -> jax.Array:
    """``apply_rope`` with YaRN's frequencies and cos/sin factor."""
    angles = positions.astype(jnp.float32)[..., None] \
        * yarn_inv_freq(x.shape[-1], theta, yarn)
    out = _apply_angles(x, angles)
    amp = yarn_mscale(yarn.factor, yarn.mscale) \
        / yarn_mscale(yarn.factor, yarn.mscale_all_dim)
    return out if amp == 1.0 else (out.astype(jnp.float32)
                                   * amp).astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array,
                sections: Sequence[int], theta: float = 10000.0) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL). positions: (3, B, S) = (t, h, w) streams.

    ``sections`` partitions the half-dim; section i uses position stream i.
    sum(sections) must equal D // 2.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    parts = []
    for i, sec in enumerate(sections):
        lo = sum(sections[:i])
        inv_freq = 1.0 / (theta ** (jnp.arange(lo, lo + sec, dtype=jnp.float32) / half))
        parts.append(positions[i].astype(jnp.float32)[..., None] * inv_freq)
    angles = jnp.concatenate(parts, axis=-1)  # (B, S, half)
    return _apply_angles(x, angles)


def sinusoid_positions(seq_len: int, dim: int, dtype=jnp.float32) -> jax.Array:
    """Additive sinusoidal position table (encoder-only models)."""
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    i = jnp.arange(dim // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2 * i / dim)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1).astype(dtype)


# ---------------------------------------------------------------------------
# masking helpers
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def causal_mask(seq_len: int, window: Optional[int] = None) -> jax.Array:
    """(S, S) additive mask. window=None -> full causal; else sliding window."""
    i = jnp.arange(seq_len)[:, None]
    j = jnp.arange(seq_len)[None, :]
    ok = j <= i
    if window is not None:
        ok = ok & (j > i - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def cache_mask(cache_positions: jax.Array, pos: jax.Array,
               window: Optional[int] = None) -> jax.Array:
    """Additive mask over cache slots for single-token decode.

    cache_positions: (B, W) absolute position stored in each slot (-1 = empty).
    pos: int32 position of the token being decoded — scalar, or (B, 1)
    for per-row positions (in-flight batching); both broadcast against
    the (B, W) slot positions.
    """
    ok = (cache_positions >= 0) & (cache_positions <= pos)
    if window is not None:
        ok = ok & (cache_positions > pos - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def wsc(x, *spec_axes):
    """with_sharding_constraint if a mesh context with a "model" axis is
    active; no-op otherwise. "BATCH" resolves to the mesh's batch axes.
    Under a mesh, a constraint that cannot apply raises."""
    from jax._src import mesh as mesh_lib
    m = mesh_lib.thread_resources.env.physical_mesh
    if m.empty or "model" not in m.axis_names:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    batch = tuple(a for a in ("pod", "data") if a in m.axis_names)
    axes = tuple(batch if a == "BATCH" else a for a in spec_axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(m, P(*axes)))
