"""Pallas TPU kernels for the framework's compute hot-spots.

  bottleneck      — fused low-rank projection + int8 quantisation at the
                    split boundary (the paper's per-frame edge hot-spot)
  flash_attention — blocked online-softmax causal GQA attention (prefill)
  ssm_scan        — chunked selective-scan recurrence (Mamba prefill)
  decode_attention— flash-decode: one token vs a long KV cache (the
                    Insight-serving decode hot loop; HBM traffic = one
                    cache read, the Pair-2 roofline floor)

Each package: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper), ref.py (pure-jnp oracle used by tests). The backend picks how
a kernel runs (``resolve_interpret``): compiled by Mosaic on TPU,
interpreted on CPU.
"""
from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether a ``pallas_call`` runs interpreted: an explicit value wins
    (compile tests pass ``False`` to lower for a described TPU from a CPU
    host); otherwise the default backend decides — compiled on TPU,
    interpreted on CPU. Any other backend has no kernel path and raises
    rather than silently interpreting."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on TPU or interpreted on CPU; "
        f"backend {backend!r} has neither")
