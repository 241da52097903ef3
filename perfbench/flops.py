"""Operations and bytes of the served work, from shapes alone.

Counted by the 2-per-multiply-add convention. These are the operations
the model needs, not what a kernel happens to do: idle decode rows parked
on the trash page and a kernel's repeated reads are never counted, so a
kernel that skips them cannot read above its roofline.
"""
from __future__ import annotations

from typing import Sequence


def _trunk(pcfg):
    llm = pcfg.llm
    return (llm.num_layers, llm.d_model, llm.num_heads, llm.num_kv_heads,
            llm.resolved_head_dim, llm.d_ff, llm.vocab_size)


def trunk_token_flops(pcfg, ctx: int) -> float:
    """One token through the trunk attending ``ctx`` positions (itself
    included): projections, attention and the gated MLP in every layer."""
    L, d, H, K, hd, f, _ = _trunk(pcfg)
    proj = 2 * d * (2 * H * hd + 2 * K * hd)
    attn = 2 * 2 * ctx * H * hd
    mlp = 2 * 3 * d * f
    return float(L * (proj + attn + mlp))


def head_flops(pcfg) -> float:
    """Answer logits and the <SEG> projection at one position."""
    d, V = pcfg.llm.d_model, pcfg.llm.vocab_size
    return float(2 * d * V + 2 * d * pcfg.sam.d_model)


def prefill_flops(pcfg, prefix_len: int) -> float:
    """A causal prefill of ``prefix_len`` positions, then the head at the
    last one."""
    return sum(trunk_token_flops(pcfg, i + 1)
               for i in range(prefix_len)) + head_flops(pcfg)


def decode_step_flops(pcfg, ctx_lens: Sequence[int]) -> float:
    """One decode step: each live row feeds one token attending its
    cached positions plus itself."""
    return sum(trunk_token_flops(pcfg, c) + head_flops(pcfg)
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


def paged_attention_cost(pcfg, ctx_lens: Sequence[int],
                         bytes_per_el: int = 2):
    """(operations, bytes) the paged decode attention needs in one step,
    all layers: each live row's query heads against its cached keys and
    values, each kv head read once, plus its queries and outputs."""
    L, _, H, K, hd, _, _ = _trunk(pcfg)
    flops = bytes_ = 0.0
    for c in ctx_lens:
        flops += 2 * 2 * c * H * hd
        bytes_ += (2 * c * K * hd + 2 * H * hd) * bytes_per_el
    return L * flops, L * bytes_
