"""Analytic compute/energy device models (DESIGN.md §4.2).

This container is CPU-only, so latency/energy numbers in the benchmarks
are *derived*, not timed: FLOPs come from analytic per-block formulas
(cross-checked against ``compiled.cost_analysis()`` in the dry-run), and
device constants below convert them to seconds / joules.

Constants:
  * Edge (paper's UAV computer): NVIDIA Jetson AGX Xavier, MODE_30W_ALL.
    Peak is ~16 TOPS fp16, but the *effective* ViT throughput implied by
    the paper's Fig. 8 (split@1 = patch-embed + 1 SAM block + CLIP ≈
    0.232 s) is ~2 TFLOP/s; average active SoC power implied by
    3.12 J / 0.232 s ≈ 13.5 W — we use 2 TFLOP/s and 15 W. With these,
    our analytic model lands within ~10% of every Fig. 8 point we can
    check (see EXPERIMENTS.md §Paper-claims).
  * Radio: long-range uplink ~ 120 nJ/bit transmit energy.
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.models.config import ModelConfig

# --- hardware constants ---
JETSON_FLOPS = 1.28e12          # effective fp16 FLOP/s (Fig. 8 calibrated:
                                # split@1 edge latency == 0.2318 s)
JETSON_POWER_W = 15.0           # average active power in MODE_30W_ALL
RADIO_J_PER_BIT = 120e-9


@dataclass(frozen=True)
class EdgeDevice:
    flops_per_sec: float = JETSON_FLOPS
    power_watts: float = JETSON_POWER_W

    def latency_s(self, flops: float) -> float:
        return flops / self.flops_per_sec

    def compute_energy_j(self, flops: float) -> float:
        return self.latency_s(flops) * self.power_watts

    def tx_energy_j(self, payload_bytes: float) -> float:
        return payload_bytes * 8 * RADIO_J_PER_BIT


# ---------------------------------------------------------------------------
# analytic FLOPs (2 * MACs convention, matching XLA cost_analysis)
# ---------------------------------------------------------------------------


def attn_block_flops(d: int, d_ff: int, seq: int, heads: int,
                     kv_heads: int, head_dim: int, gated: bool) -> float:
    """One transformer block, full-sequence, per batch element."""
    qkvo = 2 * seq * d * (heads * head_dim + 2 * kv_heads * head_dim
                          + heads * head_dim)
    scores = 2 * seq * seq * heads * head_dim * 2   # QK^T and PV
    mlp = 2 * seq * d * d_ff * (3 if gated else 2)
    return float(qkvo + scores + mlp)


def encoder_flops(cfg: ModelConfig, seq: int, num_blocks: int = -1) -> float:
    """Encoder prefix of ``num_blocks`` blocks (-1 = all), per image."""
    n = cfg.num_layers if num_blocks < 0 else num_blocks
    return n * attn_block_flops(cfg.d_model, cfg.d_ff, seq, cfg.num_heads,
                                cfg.num_kv_heads, cfg.resolved_head_dim,
                                cfg.gated_mlp)


def bottleneck_flops(d: int, rank: int, seq: int) -> float:
    return float(2 * seq * d * rank)


def patch_embed_flops(d: int, patch: int, seq: int, in_ch: int = 3) -> float:
    return float(2 * seq * patch * patch * in_ch * d)


# ---------------------------------------------------------------------------
# per-frame edge cost at a deployment geometry (used by the engine's
# profiled mission path; previously lived in runtime.mission)
# ---------------------------------------------------------------------------


def edge_insight_flops(deploy, ratio: float) -> float:
    """Edge-side FLOPs per Insight frame at the deployment geometry:
    patch embed + SAM blocks [0, k) + bottleneck encode + CLIP encoder.
    ``deploy`` is a ``LISAPipelineConfig``."""
    from repro.core import bottleneck as bn
    d = deploy.sam.d_model
    orig_bytes = 2 if deploy.sam.param_dtype == "bfloat16" else 4
    rank = bn.rank_for_ratio(d, ratio, orig_bytes)
    return (patch_embed_flops(d, deploy.patch_size, deploy.sam_tokens)
            + encoder_flops(deploy.sam, deploy.sam_tokens,
                            deploy.split_layer)
            + bottleneck_flops(d, rank, deploy.sam_tokens)
            + patch_embed_flops(deploy.clip.d_model,
                                deploy.context_patch_size, deploy.clip_tokens)
            + encoder_flops(deploy.clip, deploy.clip_tokens))


def full_edge_flops(deploy) -> float:
    """Full onboard execution of the Insight segmentation backbone."""
    d = deploy.sam.d_model
    return (patch_embed_flops(d, deploy.patch_size, deploy.sam_tokens)
            + encoder_flops(deploy.sam, deploy.sam_tokens))
