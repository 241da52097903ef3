"""Operations and bytes of the served work, from shapes alone.

Counted by the 2-per-multiply-add convention. These are the operations
the model needs, not what a kernel happens to do: idle decode rows parked
on the trash page and a kernel's repeated reads are never counted, so a
kernel that skips them cannot read above its roofline. The trunk's
counts are its architecture module's (``arch/<arch>.py``: ``token_flops``
and ``paged_attention_cost``); the head, the SAM tail and the mask are
counted here.
"""
from __future__ import annotations

from typing import Sequence


def head_flops(pcfg) -> float:
    """Answer logits and the <SEG> projection at one position."""
    d, V = pcfg.llm.d_model, pcfg.llm.vocab_size
    return float(2 * d * V + 2 * d * pcfg.sam.d_model)


def prefill_flops(arch, pcfg, prefix_len: int) -> float:
    """A causal prefill of ``prefix_len`` positions through ``arch``'s
    trunk, then the head at the last one."""
    return sum(arch.token_flops(pcfg, i + 1)
               for i in range(prefix_len)) + head_flops(pcfg)


def decode_step_flops(arch, pcfg, ctx_lens: Sequence[int]) -> float:
    """One decode step: each live row feeds one token attending its
    cached positions plus itself."""
    return sum(arch.token_flops(pcfg, c) + head_flops(pcfg)
               for c in ctx_lens)


def sam_tail_flops(pcfg, rank: int) -> float:
    """Bottleneck decode and the SAM blocks after the split, one frame."""
    s = pcfg.sam
    T, d, f = pcfg.sam_tokens, s.d_model, s.d_ff
    block = 2 * T * d * 4 * d + 2 * 2 * T * T * d + 2 * 2 * T * d * f
    return float(2 * T * rank * d + (s.num_layers - pcfg.split_layer) * block)


def mask_flops(pcfg) -> float:
    s = pcfg.sam
    T, d = pcfg.sam_tokens, s.d_model
    pp = max(1, pcfg.mask_pixels_per_patch)
    return float(2 * T * d * d + 2 * T * d * pp)
