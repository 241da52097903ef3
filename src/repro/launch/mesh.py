"""Production mesh factory (DESIGN.md §7).

A function — not a module-level constant — so importing this module never
touches jax device state. The dry-run entrypoint sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax
import; smoke tests and benchmarks see the real single CPU device.

Single pod : (16, 16)      axes ("data", "model")   = 256 chips (v5e pod)
Multi-pod  : (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

In the AVERY mapping, the "pod" axis doubles as the edge/cloud
disaggregation boundary for split serving (launch/serve.py): pod 0 runs
the head + bottleneck encoder, pod 1 the decoder + tail, and the
inter-pod link carries exactly the compressed boundary payload.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with ``Auto`` axes: sharding propagates through
    GSPMD as the key-path rules and ``in/out_shardings`` direct, rather
    than being part of every array's type (JAX's ``Explicit`` default)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Single-host mesh for tests and sharded serving: uses however many
    devices exist. ``model`` (the tensor-parallel axis size) is clamped
    to the device count — asking for more shards than devices degrades
    to whatever the host has instead of building an empty ``(0, k)``
    mesh — and must divide the remaining device count."""
    n = len(jax.devices())
    if model < 1:
        raise ValueError(f"model axis must be >= 1, got {model}")
    model = min(model, n)
    if n % model:
        raise ValueError(
            f"model={model} does not divide the {n} local devices; pick a "
            f"divisor of {n} (or force more host devices via "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return _mesh((n // model, model), ("data", "model"))
