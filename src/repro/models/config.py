"""Model configuration dataclasses.

A single ``ModelConfig`` describes every architecture family the framework
supports (dense GQA / MLA, MoE, Mamba-1/2 SSM, hybrid, encoder-only audio,
VLM). Architecture configs in ``repro/configs`` instantiate these with the
exact assigned hyperparameters.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp


@dataclass(frozen=True)
class YarnConfig:
    """YaRN's extension of RoPE (DeepSeek-V3's ``rope_scaling``): rotary
    frequencies that turn fewer than ``beta_slow`` times over
    ``original_max_position`` positions are divided by ``factor``, those
    that turn more than ``beta_fast`` times are kept, a linear ramp lies
    between; cos/sin are scaled by mscale(``mscale``) / mscale(
    ``mscale_all_dim``) and the softmax by mscale(``mscale_all_dim``)^2,
    with mscale(m) = 0.1 m ln(factor) + 1."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2/V3, MiniCPM3)."""
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    # YaRN on the rotary part (None: plain RoPE)
    yarn: Optional[YarnConfig] = None


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    num_shared_experts: int = 0
    d_ff_shared: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # "einsum" = GShard-faithful one-hot dispatch (baseline);
    # "scatter" = sort-based dispatch that avoids the (T,E,C) temp (§Perf);
    # "dropless" = the serving share: no capacity, only the held experts
    # computed (``held_experts`` below)
    dispatch: str = "einsum"
    # layers [0, first_k_dense) use a plain dense FFN (DeepSeek-V3 style)
    first_k_dense: int = 0
    d_ff_dense: int = 0
    # routing: "softmax" over every expert, or "sigmoid" per expert with
    # a per-expert correction bias used only to choose (DeepSeek-V3's
    # noaux_tc); experts fall in ``n_group`` groups, each scored by the
    # sum of its top-2, and picks come from the ``topk_group`` best; the
    # normalised gates are scaled by ``routed_scaling``
    scoring: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling: float = 1.0
    # expert parallelism: this layer holds experts [held_first,
    # held_first + held_experts) of the router's ``num_experts`` (0 holds
    # them all); picks on the others contribute nothing here
    held_experts: int = 0
    held_first: int = 0

    @property
    def held(self) -> int:
        return self.held_experts or self.num_experts


@dataclass(frozen=True)
class SSMConfig:
    version: int              # 1 = Mamba-1 selective scan, 2 = Mamba-2 SSD
    state_size: int           # N
    expand: int = 2           # d_inner = expand * d_model
    conv_kernel: int = 4
    head_dim: int = 64        # mamba2 only (P)
    dt_rank: int = 0          # mamba1 only; 0 -> ceil(d_model / 16)


@dataclass(frozen=True)
class HybridConfig:
    """Zamba2-style: shared attention block invoked every ``attn_every``
    SSM layers. The attention block's parameters are shared across all
    invocations (true to Zamba2's shared-block design)."""
    attn_every: int = 9


@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str            # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0         # 0 -> d_model // num_heads
    mlp_act: str = "silu"     # silu (=SwiGLU), relu2 (single-proj), gelu
    gated_mlp: bool = True    # SwiGLU-style gate; False for relu2/gelu single
    attn_type: str = "gqa"    # gqa | mla | none
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    rope_style: str = "rope"  # rope | mrope | none (sinusoid for encoders)
    mrope_sections: Tuple[int, ...] = ()
    sliding_window: Optional[int] = None   # if set, attention is windowed
    causal: bool = True       # False -> encoder-only (bidirectional)
    norm: str = "rmsnorm"     # rmsnorm | layernorm
    norm_eps: float = 1e-6
    mla: Optional[MLAConfig] = None
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    hybrid: Optional[HybridConfig] = None
    # multi-token prediction (DeepSeek-V3): one extra block predicting t+2
    mtp: bool = False
    mtp_weight: float = 0.3
    # modality frontend (stubbed per assignment carve-out)
    modality: str = "text"    # text | audio | vlm
    frontend_dim: int = 0     # raw feature dim produced by the stub frontend
    num_vision_tokens: int = 0
    # numerics / execution
    param_dtype: str = "float32"
    act_dtype: str = "float32"
    remat: bool = False       # activation checkpointing around each block
    use_flash: bool = False   # route full-seq attention through Pallas kernel
    # route single-token GQA decode attention through the flash-decode
    # Pallas kernel (kernels/decode_attention): one streaming read of the
    # KV cache per step — the serving decode hot loop (MLA decode keeps
    # the absorbed-matmul path)
    use_flash_decode: bool = False
    # query-chunked attention (§Perf lever): lax.scan over q blocks of this
    # size so only a (chunk x S) score tile is ever materialised — the
    # flash-attention access pattern expressed at the XLA level
    attn_chunk: Optional[int] = None
    # perf-analysis ONLY (never for real compute): replace the
    # score/softmax/PV stage with a pass-through so its HLO cost can be
    # isolated; the flash-kernel-adjusted roofline = this + the kernel's
    # analytic VMEM-resident traffic (q,k,v read + o write once)
    attn_scores_stub: bool = False
    use_ssm_kernel: bool = False  # route SSM scan through Pallas kernel
    # fully unroll layer scans (dry-run cost extraction: XLA counts a while
    # body once, so per-layer costs are measured on small unrolled variants
    # and extrapolated linearly — see launch/dryrun.py)
    scan_unroll: bool = False
    # Megatron-style sequence parallelism (§Perf lever): constrain the
    # residual stream to be sequence-sharded over "model" between blocks,
    # turning per-layer all-reduces into reduce-scatter + all-gather pairs
    seq_shard: bool = False
    # shard decode KV caches on head_dim instead of kv-heads (§Perf lever:
    # kv-head counts like 8 or 40 don't divide the model axis, which leaves
    # the cache replicated and decode collective-bound)
    shard_cache_hd: bool = False
    tie_embeddings: bool = False
    # citation for the assigned config
    source: str = ""

    # ----- derived -----
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def adtype(self):
        return jnp.dtype(self.act_dtype)

    @property
    def is_encoder_only(self) -> bool:
        return not self.causal

    @property
    def supports_decode(self) -> bool:
        return self.causal

    @property
    def subquadratic(self) -> bool:
        """True if a 500k-token decode context is feasible: SSM/hybrid state
        is O(1); windowed attention caches only ``sliding_window`` slots."""
        if self.arch_type in ("ssm", "hybrid"):
            return True
        return self.sliding_window is not None

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def with_sliding_window(self, window: int = 8192) -> "ModelConfig":
        """Beyond-paper variant used for long_500k on dense archs."""
        return self.replace(sliding_window=window)

    def param_count(self) -> int:
        """Analytic parameter count (exact for our layouts)."""
        from repro.models import stack
        return stack.count_params(self)

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: shared + top_k experts)."""
        from repro.models import stack
        return stack.count_params(self, active_only=True)
