"""Attention variants: GQA (optionally biased / sliding-window) and MLA.

Two execution paths per variant:
  * full-sequence (training / prefill) — optionally emits cache contents;
  * single-token decode against a ring-buffer KV cache.

Cache layout (per layer, stacked along a leading layer axis by the stack):
  GQA: {"k": (B, W, K, hd), "v": (B, W, K, hd)}      — k stored post-RoPE
  MLA: {"ckv": (B, W, r_kv), "krope": (B, W, d_r)}   — the latent cache that
       makes DeepSeek-style decode memory-light (this *is* MLA's bottleneck
       affinity noted in DESIGN.md); the paged pool holds the same two
       leaves a page at a time, (P, page, r_kv) and (P, page, d_r).
Slot-position bookkeeping ((B?, W) absolute positions) lives at the model
level and arrives here as a pre-computed additive mask.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (NEG_INF, apply_mrope, apply_rope,
                                 apply_yarn_rope, fan_in_init, linear,
                                 yarn_mscale, zeros_init)
from repro.models.config import ModelConfig

# ---------------------------------------------------------------------------
# parameter construction
# ---------------------------------------------------------------------------


def init_gqa(rng: jax.Array, cfg: ModelConfig) -> dict:
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(rng, 4)
    dt = cfg.pdtype
    p = {
        "wq": fan_in_init(ks[0], (d, H * hd), dt),
        "wk": fan_in_init(ks[1], (d, K * hd), dt),
        "wv": fan_in_init(ks[2], (d, K * hd), dt),
        "wo": fan_in_init(ks[3], (H * hd, d), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros_init((H * hd,), dt)
        p["bk"] = zeros_init((K * hd,), dt)
        p["bv"] = zeros_init((K * hd,), dt)
    return p


def init_mla(rng: jax.Array, cfg: ModelConfig) -> dict:
    m = cfg.mla
    assert m is not None
    d, H = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(rng, 5)
    dt = cfg.pdtype
    return {
        "wq_a": fan_in_init(ks[0], (d, m.q_lora_rank), dt),
        "q_norm": {"w": jnp.ones((m.q_lora_rank,), dt)},
        "wq_b": fan_in_init(ks[1], (m.q_lora_rank, H * qk), dt),
        "wkv_a": fan_in_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), dt),
        "kv_norm": {"w": jnp.ones((m.kv_lora_rank,), dt)},
        "wkv_b": fan_in_init(ks[3], (m.kv_lora_rank,
                                     H * (m.qk_nope_head_dim + m.v_head_dim)), dt),
        "wo": fan_in_init(ks[4], (H * m.v_head_dim, d), dt),
    }


def init_attention(rng: jax.Array, cfg: ModelConfig) -> dict:
    return init_mla(rng, cfg) if cfg.attn_type == "mla" else init_gqa(rng, cfg)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def _rope_q_or_k(cfg: ModelConfig, x: jax.Array, positions: jax.Array) -> jax.Array:
    if cfg.rope_style == "none":
        return x
    if cfg.rope_style == "mrope":
        return apply_mrope(x, positions, cfg.mrope_sections, cfg.rope_theta)
    return apply_rope(x, positions, cfg.rope_theta)


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array,
          scale: float) -> jax.Array:
    """q (B,S,H,hd) k/v (B,T,K,hd) grouped attention, fp32 softmax.

    mask: additive, broadcastable to (B, 1, S, T). Matmuls run on the
    native (bf16) operands with fp32 accumulation (preferred_element_type)
    — the MXU idiom; no materialised fp32 copies of q/k/v (§Perf).
    """
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    scores = scores + mask.reshape(mask.shape[0], 1, 1, *mask.shape[1:])
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, hd).astype(q.dtype)


def _sdpa_chunked(cfg: ModelConfig, q: jax.Array, k: jax.Array, v: jax.Array,
                  mask: jax.Array, scale: float, chunk: int) -> jax.Array:
    """Query-chunked attention: lax.scan over q blocks so only a
    (chunk, S) score tile is live at once — the flash-attention access
    pattern at the XLA level (§Perf memory lever)."""
    B, S, H, hd = q.shape
    nc = S // chunk
    qc = q.reshape(B, nc, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    Bm = mask.shape[0]
    mc = mask.reshape(Bm, nc, chunk, mask.shape[-1]).transpose(1, 0, 2, 3)

    def body(_, xs):
        qb, mb = xs
        return None, _sdpa(qb, k, v, mb, scale)

    _, out = jax.lax.scan(body, None, (qc, mc), unroll=cfg.scan_unroll)
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd)


def gqa_full(p: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
             mask: jax.Array) -> Tuple[jax.Array, dict]:
    """Full-sequence GQA. Returns (out, cache_contents)."""
    B, S, d = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = linear(x, p["wq"], p.get("bq")).reshape(B, S, H, hd)
    k = linear(x, p["wk"], p.get("bk")).reshape(B, S, K, hd)
    v = linear(x, p["wv"], p.get("bv")).reshape(B, S, K, hd)
    pos1d = positions if cfg.rope_style != "mrope" else positions
    q = _rope_q_or_k(cfg, q, pos1d)
    k = _rope_q_or_k(cfg, k, pos1d)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    if cfg.attn_scores_stub:
        # perf-analysis stub: keep q/k/v projections alive, skip the
        # score/softmax/PV stage (see config docstring)
        out = q + 1e-6 * (jnp.mean(k) + jnp.mean(v))
    elif cfg.use_flash and cfg.causal and cfg.sliding_window is None:
        from repro.kernels.flash_attention import ops as flash_ops
        out = flash_ops.flash_attention(q, k, v, causal=True)
    elif cfg.attn_chunk and S > cfg.attn_chunk and S % cfg.attn_chunk == 0:
        out = _sdpa_chunked(cfg, q, k, v, mask, scale, cfg.attn_chunk)
    else:
        out = _sdpa(q, k, v, mask, scale)
    out = linear(out.reshape(B, S, H * hd), p["wo"])
    return out, {"k": k, "v": v}


def gqa_decode(p: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
               cache: dict, slot: jax.Array, mask: jax.Array) -> Tuple[jax.Array, dict]:
    """Single-token decode. x (B,1,d); cache k/v (B,W,K,hd); slot scalar
    (shared ring slot) or (B,) vector (per-row slots, in-flight batching);
    mask (B,W) additive over cache slots (already includes the new token's
    slot as valid)."""
    B, S, d = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = linear(x, p["wq"], p.get("bq")).reshape(B, S, H, hd)
    k = linear(x, p["wk"], p.get("bk")).reshape(B, S, K, hd)
    v = linear(x, p["wv"], p.get("bv")).reshape(B, S, K, hd)
    q = _rope_q_or_k(cfg, q, positions)
    k = _rope_q_or_k(cfg, k, positions)
    if cfg.shard_cache_hd:
        # align the fresh k/v (and q) with the head_dim-sharded cache at the
        # source, so the cache update and attention reads stay local and the
        # only collective left is the small score partial-sum (§Perf)
        from repro.models.common import wsc
        q = wsc(q, "BATCH", None, None, "model")
        k = wsc(k, "BATCH", None, None, "model")
        v = wsc(v, "BATCH", None, None, "model")
    if jnp.ndim(slot) == 0:
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot,
                                                      axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot,
                                                      axis=1)
    else:                               # per-row scatter into the ring
        rows = jnp.arange(B)
        k_cache = cache["k"].at[rows, slot].set(k[:, 0])
        v_cache = cache["v"].at[rows, slot].set(v[:, 0])
    if cfg.use_flash_decode and S == 1 and not cfg.shard_cache_hd:
        from repro.kernels.decode_attention import ops as decode_ops
        out = decode_ops.decode_attention(q[:, 0], k_cache, v_cache,
                                          mask)[:, None]
    else:
        scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
        out = _sdpa(q, k_cache, v_cache, mask[:, None, :], scale)
    out = linear(out.reshape(B, S, H * hd), p["wo"])
    return out, {"k": k_cache, "v": v_cache}


def gqa_decode_paged(p: dict, cfg: ModelConfig, x: jax.Array,
                     positions: jax.Array, pool: dict, page_table: jax.Array,
                     write_page: jax.Array, write_off: jax.Array,
                     mask: jax.Array) -> Tuple[jax.Array, dict]:
    """Single-token decode against a shared KV *page pool*.

    x (B,1,d); pool k/v (P, page, K, hd) — pages shared by every live
    row; page_table (B, n_pages) i32, every entry a valid page id (idle
    rows point at the reserved trash page); write_page/write_off (B,)
    page slot receiving the new token's k/v (idle rows may collide on
    the trash page — their outputs are discarded); mask (B, n_pages*page)
    additive over the row's gathered virtual sequence. Returns
    (out, new pool). Gathered virtual order preserves ascending
    positions and masked slots contribute exactly zero, so outputs match
    the contiguous ring cache bit-for-bit up to reduction order.

    Sharded serving (``sharding/serving.py``) runs this body under a
    mesh with kv-heads sharded over "model": the page gather and both
    einsums stay shard-local per head slice (each shard sees
    K / model_shards kv heads) and the only collective is the
    all-reduce after the row-parallel ``wo``. The flash kernel path is
    per-shard-head-count-ready but needs ``shard_map`` (GSPMD does not
    partition a ``pallas_call``), so sharded contexts pin
    ``use_flash_decode=False`` — see ``kernels/decode_attention``.
    """
    B, S, d = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    if cfg.shard_cache_hd:
        raise NotImplementedError(
            "paged decode does not support the head_dim-sharded cache")
    q = linear(x, p["wq"], p.get("bq")).reshape(B, S, H, hd)
    k = linear(x, p["wk"], p.get("bk")).reshape(B, S, K, hd)
    v = linear(x, p["wv"], p.get("bv")).reshape(B, S, K, hd)
    q = _rope_q_or_k(cfg, q, positions)
    k = _rope_q_or_k(cfg, k, positions)
    k_pool = pool["k"].at[write_page, write_off].set(k[:, 0])
    v_pool = pool["v"].at[write_page, write_off].set(v[:, 0])
    if cfg.use_flash_decode and S == 1:
        from repro.kernels.decode_attention import ops as decode_ops
        out = decode_ops.paged_decode_attention(q[:, 0], k_pool, v_pool,
                                                page_table, mask)[:, None]
    else:
        n, page = page_table.shape[1], k_pool.shape[1]
        kg = k_pool[page_table].reshape(B, n * page, K, hd)
        vg = v_pool[page_table].reshape(B, n * page, K, hd)
        scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
        out = _sdpa(q, kg, vg, mask[:, None, :], scale)
    out = linear(out.reshape(B, S, H * hd), p["wo"])
    return out, {"k": k_pool, "v": v_pool}


def gqa_verify_paged(p: dict, cfg: ModelConfig, x: jax.Array,
                     positions: jax.Array, pool: dict, page_table: jax.Array,
                     write_page: jax.Array, write_off: jax.Array,
                     mask: jax.Array) -> Tuple[jax.Array, dict]:
    """Multi-token decode against the shared KV page pool — the
    speculative verify step.

    x (B, C, d) — each row's chunk of C tokens (last accepted token +
    drafted continuations, ascending positions); positions (B, C);
    write_page/write_off (B, C) per-token page slots receiving the new
    k/v (pad tokens target the reserved trash page — collisions there
    are harmless because trash slots never carry a valid position);
    mask (B, C, n_pages*page) additive per query position, carrying
    both slot validity and causal-within-chunk. The chunk's k/v scatter
    lands *before* attention, so chunk token i attends chunk tokens
    <= i through the pool exactly like C successive decode steps would
    — a C=1 call reproduces ``gqa_decode_paged``."""
    B, C, d = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    if cfg.shard_cache_hd:
        raise NotImplementedError(
            "paged verify does not support the head_dim-sharded cache")
    q = linear(x, p["wq"], p.get("bq")).reshape(B, C, H, hd)
    k = linear(x, p["wk"], p.get("bk")).reshape(B, C, K, hd)
    v = linear(x, p["wv"], p.get("bv")).reshape(B, C, K, hd)
    q = _rope_q_or_k(cfg, q, positions)
    k = _rope_q_or_k(cfg, k, positions)
    k_pool = pool["k"].at[write_page, write_off].set(k)
    v_pool = pool["v"].at[write_page, write_off].set(v)
    if cfg.use_flash_decode:
        from repro.kernels.decode_attention import ops as decode_ops
        out = decode_ops.paged_verify_attention(q, k_pool, v_pool,
                                                page_table, mask)
    else:
        n, page = page_table.shape[1], k_pool.shape[1]
        kg = k_pool[page_table].reshape(B, n * page, K, hd)
        vg = v_pool[page_table].reshape(B, n * page, K, hd)
        scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
        out = _sdpa(q, kg, vg, mask, scale)
    out = linear(out.reshape(B, C, H * hd), p["wo"])
    return out, {"k": k_pool, "v": v_pool}


def gqa_empty_cache(cfg: ModelConfig, batch: int, width: int) -> dict:
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    dt = cfg.adtype
    return {
        "k": jnp.zeros((batch, width, K, hd), dt),
        "v": jnp.zeros((batch, width, K, hd), dt),
    }


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 / MiniCPM3)
# ---------------------------------------------------------------------------


def _mla_rope(cfg: ModelConfig, x: jax.Array,
              positions: jax.Array) -> jax.Array:
    y = cfg.mla.yarn
    if y is None:
        return apply_rope(x, positions, cfg.rope_theta)
    return apply_yarn_rope(x, positions, cfg.rope_theta, y)


def _mla_scale(cfg: ModelConfig) -> jax.Array:
    """1/sqrt(qk_nope + qk_rope), times YaRN's mscale(mscale_all_dim)^2."""
    m = cfg.mla
    scale = 1.0 / jnp.sqrt(float(m.qk_nope_head_dim + m.qk_rope_head_dim))
    if m.yarn is not None and m.yarn.mscale_all_dim:
        scale = scale * yarn_mscale(m.yarn.factor,
                                    m.yarn.mscale_all_dim) ** 2
    return scale


def _mla_qkv_full(p: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    from repro.models.common import rms_norm
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    qk_n, qk_r, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    q = linear(rms_norm(linear(x, p["wq_a"]), p["q_norm"]["w"], cfg.norm_eps),
               p["wq_b"])
    q = q.reshape(B, S, H, qk_n + qk_r)
    q_nope, q_rope = q[..., :qk_n], q[..., qk_n:]
    q_rope = _mla_rope(cfg, q_rope, positions)

    kv_a = linear(x, p["wkv_a"])
    ckv = rms_norm(kv_a[..., :m.kv_lora_rank], p["kv_norm"]["w"],
                   cfg.norm_eps)
    k_rope = kv_a[..., m.kv_lora_rank:].reshape(B, S, 1, qk_r)
    k_rope = _mla_rope(cfg, k_rope, positions)[:, :, 0, :]
    return q_nope, q_rope, ckv, k_rope


def mla_full(p: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
             mask: jax.Array) -> Tuple[jax.Array, dict]:
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    qk_n, qk_r, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    q_nope, q_rope, ckv, k_rope = _mla_qkv_full(p, cfg, x, positions)
    kv = linear(ckv, p["wkv_b"]).reshape(B, S, H, qk_n + dv)
    k_nope, v = kv[..., :qk_n], kv[..., qk_n:]
    scale = _mla_scale(cfg)

    def attend(qn, qr, mb):
        scores = (jnp.einsum("bshn,bthn->bhst", qn.astype(jnp.float32),
                             k_nope.astype(jnp.float32))
                  + jnp.einsum("bshr,btr->bhst", qr.astype(jnp.float32),
                               k_rope.astype(jnp.float32))) * scale
        scores = scores + mb.reshape(mb.shape[0], 1, *mb.shape[1:])
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhst,bthv->bshv", probs,
                          v.astype(jnp.float32)).astype(x.dtype)

    c = cfg.attn_chunk
    if c and S > c and S % c == 0:
        nc = S // c
        qn_c = q_nope.reshape(B, nc, c, H, qk_n).transpose(1, 0, 2, 3, 4)
        qr_c = q_rope.reshape(B, nc, c, H, qk_r).transpose(1, 0, 2, 3, 4)
        Bm = mask.shape[0]
        m_c = mask.reshape(Bm, nc, c, mask.shape[-1]).transpose(1, 0, 2, 3)

        def body(_, xs):
            return None, attend(*xs)

        _, out = jax.lax.scan(body, None, (qn_c, qr_c, m_c),
                              unroll=cfg.scan_unroll)
        out = out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, dv)
    else:
        out = attend(q_nope, q_rope, mask)
    out = linear(out.reshape(B, S, H * dv), p["wo"])
    return out, {"ckv": ckv, "krope": k_rope}


def _mla_absorbed(p: dict, cfg: ModelConfig, q_nope: jax.Array,
                  q_rope: jax.Array, ckv: jax.Array, krope: jax.Array,
                  mask: jax.Array) -> jax.Array:
    """Latent attention with the up-projections absorbed: ``W_uk`` goes
    into the query and ``W_uv`` after the latent output, so the keys and
    values are the cached latents themselves. q_nope (B,S,H,n), q_rope
    (B,S,H,r'); ckv (B,W,r), krope (B,W,r'); mask (B,S,W) additive.
    Operands stay in their stored type, products accumulate in float32.
    Returns (B, S, H, dv)."""
    m = cfg.mla
    H = cfg.num_heads
    qk_n, dv = m.qk_nope_head_dim, m.v_head_dim
    f32 = jnp.float32
    wkv_b = p["wkv_b"].reshape(m.kv_lora_rank, H, qk_n + dv)
    w_uk = wkv_b[..., :qk_n].astype(q_nope.dtype)          # (r, H, qk_n)
    w_uv = wkv_b[..., qk_n:].astype(q_nope.dtype)          # (r, H, dv)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk,
                       preferred_element_type=f32).astype(ckv.dtype)
    scale = _mla_scale(cfg)
    scores = (jnp.einsum("bshr,bwr->bhsw", q_lat, ckv,
                         preferred_element_type=f32)
              + jnp.einsum("bshr,bwr->bhsw", q_rope.astype(krope.dtype),
                           krope, preferred_element_type=f32)) * scale
    probs = jax.nn.softmax(scores + mask[:, None], axis=-1)
    o_lat = jnp.einsum("bwr,bhsw->bshr", ckv, probs.astype(ckv.dtype),
                       preferred_element_type=f32).astype(q_nope.dtype)
    return jnp.einsum("bshr,rhv->bshv", o_lat, w_uv,
                      preferred_element_type=f32).astype(q_nope.dtype)


def mla_decode(p: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
               cache: dict, slot: jax.Array, mask: jax.Array) -> Tuple[jax.Array, dict]:
    """Absorbed-matmul MLA decode: scores are computed in the latent space so
    the cache stays (r_kv + d_r) per token — the memory win of MLA. slot is
    a scalar (shared ring slot) or (B,) per-row slots; mask (B, W)."""
    B, S, _ = x.shape
    q_nope, q_rope, ckv_new, krope_new = _mla_qkv_full(p, cfg, x, positions)
    if jnp.ndim(slot) == 0:
        ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv_new,
                                                  slot, axis=1)
        krope = jax.lax.dynamic_update_slice_in_dim(cache["krope"],
                                                    krope_new, slot, axis=1)
    else:                               # per-row scatter into the ring
        rows = jnp.arange(B)
        ckv = cache["ckv"].at[rows, slot].set(ckv_new[:, 0])
        krope = cache["krope"].at[rows, slot].set(krope_new[:, 0])
    out = _mla_absorbed(p, cfg, q_nope, q_rope, ckv, krope, mask[:, None, :])
    out = linear(out.reshape(B, S, -1), p["wo"])
    return out, {"ckv": ckv, "krope": krope}


def mla_paged(p: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
              pool: dict, page_table: jax.Array, write_page: jax.Array,
              write_off: jax.Array, mask: jax.Array) -> Tuple[jax.Array, dict]:
    """MLA against the shared page pool, for decode and speculative
    verify alike: the latent ``ckv`` (P, page, r) and ``krope``
    (P, page, r') pages are what the pool holds, (r + r') values a token
    a layer. x (B, S, d) — one token a row (decode: write_page/write_off
    (B,), mask (B, n_pages*page)) or a chunk of S (verify: (B, S) and
    (B, S, n_pages*page)). The new latents land in the pool before
    attention, as ``gqa_decode_paged``/``gqa_verify_paged`` do, and the
    absorbed attention reads each row's pages through its page table.
    Returns (out, new pool)."""
    B, S, _ = x.shape
    q_nope, q_rope, ckv_new, krope_new = _mla_qkv_full(p, cfg, x, positions)
    if write_page.ndim == 1:
        ckv_new, krope_new, mask = ckv_new[:, 0], krope_new[:, 0], \
            mask[:, None, :]
    ckv_pool = pool["ckv"].at[write_page, write_off].set(ckv_new)
    krope_pool = pool["krope"].at[write_page, write_off].set(krope_new)
    n, page = page_table.shape[1], ckv_pool.shape[1]
    ckv = ckv_pool[page_table].reshape(B, n * page, -1)
    krope = krope_pool[page_table].reshape(B, n * page, -1)
    out = _mla_absorbed(p, cfg, q_nope, q_rope, ckv, krope, mask)
    out = linear(out.reshape(B, S, -1), p["wo"])
    return out, {"ckv": ckv_pool, "krope": krope_pool}


def mla_empty_cache(cfg: ModelConfig, batch: int, width: int) -> dict:
    m = cfg.mla
    dt = cfg.adtype
    return {
        "ckv": jnp.zeros((batch, width, m.kv_lora_rank), dt),
        "krope": jnp.zeros((batch, width, m.qk_rope_head_dim), dt),
    }


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def attn_full(p, cfg: ModelConfig, x, positions, mask):
    if cfg.attn_type == "mla":
        return mla_full(p, cfg, x, positions, mask)
    return gqa_full(p, cfg, x, positions, mask)


def attn_decode(p, cfg: ModelConfig, x, positions, cache, slot, mask):
    if cfg.attn_type == "mla":
        return mla_decode(p, cfg, x, positions, cache, slot, mask)
    return gqa_decode(p, cfg, x, positions, cache, slot, mask)


def attn_decode_paged(p, cfg: ModelConfig, x, positions, pool, page_table,
                      write_page, write_off, mask):
    if cfg.attn_type == "mla":
        return mla_paged(p, cfg, x, positions, pool, page_table, write_page,
                         write_off, mask)
    return gqa_decode_paged(p, cfg, x, positions, pool, page_table,
                            write_page, write_off, mask)


def attn_verify_paged(p, cfg: ModelConfig, x, positions, pool, page_table,
                      write_page, write_off, mask):
    if cfg.attn_type == "mla":
        return mla_paged(p, cfg, x, positions, pool, page_table, write_page,
                         write_off, mask)
    return gqa_verify_paged(p, cfg, x, positions, pool, page_table,
                            write_page, write_off, mask)


def empty_cache(cfg: ModelConfig, batch: int, width: int) -> dict:
    if cfg.attn_type == "mla":
        return mla_empty_cache(cfg, batch, width)
    return gqa_empty_cache(cfg, batch, width)
