"""LISA-style grounded VLM pipeline (paper Fig. 4), built from the stack
substrate: SAM vision backbone + CLIP context encoder + multi-modal LLM +
<SEG>-conditioned mask decoder.

Instantiated at two scales (repro.configs.lisa7b / lisa_mini — DESIGN.md
§6). All pipeline stages are pure functions so the split-computing runtime
can place them on either side of the channel:

  EDGE : patchify -> SAM blocks [0,k)        (Insight head, split@k)
         patchify_lowres -> CLIP encoder     (Context stream)
  LINK : bottleneck codes (+ CLIP features)
  CLOUD: bottleneck decode -> SAM blocks [k,L) -> mask features
         LLM([ctx tokens; query]) -> answer logits + <SEG> embedding
         mask decoder(SAM feats, <SEG>) -> segmentation mask logits
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.lisa7b import LISAPipelineConfig
from repro.core import bottleneck as bn
from repro.models import stack
from repro.models.common import (cache_mask, causal_mask, fan_in_init, gelu,
                                 linear, normal_init)
from repro.models.config import ModelConfig


# ---------------------------------------------------------------------------
# patch embedding
# ---------------------------------------------------------------------------


def patchify(images: jax.Array, patch: int) -> jax.Array:
    """(B, H, W, C) -> (B, T, patch*patch*C), row-major patches."""
    B, H, W, C = images.shape
    gh, gw = H // patch, W // patch
    x = images.reshape(B, gh, patch, gw, patch, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, gh * gw, patch * patch * C)


def _init_encoder(rng: jax.Array, cfg: ModelConfig, patch: int,
                  num_tokens: int, in_ch: int = 3) -> dict:
    ks = jax.random.split(rng, 3)
    spec = stack.layer_groups(cfg)[0]
    return {
        "patch_w": fan_in_init(ks[0], (patch * patch * in_ch, cfg.d_model),
                               cfg.pdtype),
        "patch_b": jnp.zeros((cfg.d_model,), cfg.pdtype),
        "pos": normal_init(ks[1], (num_tokens, cfg.d_model), 0.02, cfg.pdtype),
        "groups": [stack.init_group(ks[2], cfg, spec)],
        "norm": stack.init_norm(cfg),
    }


def _encoder_embed(p: dict, cfg: ModelConfig, images: jax.Array,
                   patch: int) -> jax.Array:
    x = linear(patchify(images, patch).astype(cfg.adtype),
               p["patch_w"], p["patch_b"])
    return x + p["pos"][None].astype(cfg.adtype)


def _encoder_blocks(p_groups, cfg: ModelConfig, x: jax.Array,
                    lo: int = 0, hi: Optional[int] = None) -> jax.Array:
    """Run encoder blocks [lo, hi) — supports the depth-wise split."""
    import dataclasses
    full = stack.layer_groups(cfg)[0]
    hi = full.count if hi is None else hi
    if lo == hi:
        return x
    gp = jax.tree.map(lambda a: a[lo:hi], p_groups[0])
    spec = dataclasses.replace(full, count=hi - lo)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    mask = jnp.zeros((1, S, S), jnp.float32)
    x, _, _ = stack.group_forward(gp, cfg, spec, x, positions, mask)
    return x


# ---------------------------------------------------------------------------
# LISA model
# ---------------------------------------------------------------------------


def init_lisa(pcfg: LISAPipelineConfig, rng: jax.Array) -> dict:
    ks = jax.random.split(rng, 8)
    llm = pcfg.llm
    d_sam, d_clip, d_llm = pcfg.sam.d_model, pcfg.clip.d_model, llm.d_model
    return {
        "sam": _init_encoder(ks[0], pcfg.sam, pcfg.patch_size, pcfg.sam_tokens),
        "clip": _init_encoder(ks[1], pcfg.clip, pcfg.context_patch_size,
                              pcfg.clip_tokens),
        "clip_proj": fan_in_init(ks[2], (d_clip, d_llm), llm.pdtype),
        "llm": {
            "embed": normal_init(ks[3], (llm.vocab_size, d_llm), 0.02,
                                 llm.pdtype),
            "groups": [stack.init_group(
                jax.random.fold_in(ks[4], i) if i else ks[4], llm, spec)
                for i, spec in enumerate(stack.layer_groups(llm))],
            "norm": stack.init_norm(llm),
            "answer_head": fan_in_init(ks[5], (d_llm, llm.vocab_size),
                                       llm.pdtype),
        },
        "seg_proj": fan_in_init(ks[6], (d_llm, d_sam), llm.pdtype),
        "mask_head": {
            "w1": fan_in_init(ks[7], (d_sam, d_sam), pcfg.sam.pdtype),
            "b1": jnp.zeros((d_sam,), pcfg.sam.pdtype),
            "w2": fan_in_init(jax.random.fold_in(ks[7], 1),
                              (d_sam, max(1, pcfg.mask_pixels_per_patch)),
                              pcfg.sam.pdtype),
        },
    }


# ----- edge-side stages -----


def sam_head(params: dict, pcfg: LISAPipelineConfig, images: jax.Array,
             split_k: Optional[int] = None) -> jax.Array:
    """Edge prefix of the SAM backbone: patchify + blocks [0, k)."""
    k = pcfg.split_layer if split_k is None else split_k
    p = params["sam"]
    x = _encoder_embed(p, pcfg.sam, images, pcfg.patch_size)
    return _encoder_blocks(p["groups"], pcfg.sam, x, 0, k)


def clip_encode(params: dict, pcfg: LISAPipelineConfig,
                images: jax.Array) -> jax.Array:
    """Context stream: low-res CLIP features, projected to LLM width.
    Returns (B, clip_tokens, d_llm). Images are resized down to the
    context resolution first (the low-res pathway, paper §4.1)."""
    p = params["clip"]
    if images.shape[1] != pcfg.context_image_size:
        B = images.shape[0]
        images = jax.image.resize(
            images.astype(jnp.float32),
            (B, pcfg.context_image_size, pcfg.context_image_size, 3),
            method="linear").astype(images.dtype)
    x = _encoder_embed(p, pcfg.clip, images, pcfg.context_patch_size)
    x = _encoder_blocks(p["groups"], pcfg.clip, x)
    x = stack.apply_norm(x, p["norm"], pcfg.clip)
    return linear(x, params["clip_proj"])


# ----- cloud-side stages -----


def sam_tail(params: dict, pcfg: LISAPipelineConfig, x: jax.Array,
             split_k: Optional[int] = None) -> jax.Array:
    """Cloud suffix: blocks [k, L) + final norm -> mask features."""
    k = pcfg.split_layer if split_k is None else split_k
    p = params["sam"]
    x = _encoder_blocks(p["groups"], pcfg.sam, x, k, None)
    return stack.apply_norm(x, p["norm"], pcfg.sam)


def _llm_trunk(params: dict, pcfg: LISAPipelineConfig, ctx_tokens: jax.Array,
               query_tokens: jax.Array, want_cache: bool = False):
    """Shared full-sequence LLM trunk over [ctx; query]: embed, causal
    attention stack (every layer group in turn), final norm. Returns
    (x (B,S,d), per-group kv caches or None) — the single source of
    truth for both ``llm_reason`` and ``llm_prefill`` so the fast path
    and the serving prefill can't diverge."""
    llm = pcfg.llm
    p = params["llm"]
    x_q = jnp.take(p["embed"], query_tokens, axis=0).astype(llm.adtype)
    x = jnp.concatenate([ctx_tokens.astype(llm.adtype), x_q], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    mask = causal_mask(S)[None]
    kvs = []
    for spec, gp in zip(stack.layer_groups(llm), p["groups"],
                        strict=True):
        x, _, kv = stack.group_forward(gp, llm, spec, x, positions, mask,
                                       want_cache=want_cache)
        kvs.append(kv)
    return stack.apply_norm(x, p["norm"], llm), \
        (kvs if want_cache else None)


def llm_reason(params: dict, pcfg: LISAPipelineConfig, ctx_tokens: jax.Array,
               query_tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Multi-modal LLM over [ctx; query]. Returns (answer_logits (B,V),
    seg_embedding (B, d_sam)) taken at the final (<SEG>) position."""
    x, _ = _llm_trunk(params, pcfg, ctx_tokens, query_tokens)
    last = x[:, -1]                                   # <SEG> position
    answer_logits = linear(last, params["llm"]["answer_head"])
    seg = linear(last, params["seg_proj"])
    return answer_logits, seg


def _llm_outputs(params: dict, x_last: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
    """Answer logits + <SEG> embedding from the hidden state at one
    position (B, d_llm)."""
    answer_logits = linear(x_last, params["llm"]["answer_head"])
    seg = linear(x_last, params["seg_proj"])
    return answer_logits, seg


def llm_prefill(params: dict, pcfg: LISAPipelineConfig, ctx_tokens: jax.Array,
                query_tokens: jax.Array, width: Optional[int] = None
                ) -> Tuple[jax.Array, jax.Array, Dict]:
    """Full-sequence forward over [ctx; query] that also materialises the
    per-layer KV cache (the serving prefill stage). Returns
    (answer_logits (B,V), seg (B,d_sam), cache).

    The cache is laid out for ``llm_decode_step``: ring-buffer slots of
    ``width`` (>= S; defaults to S) with per-slot absolute positions, the
    same contract as ``models.model.init_cache``. Equivalent to
    ``llm_reason`` at the last position.
    """
    llm = pcfg.llm
    x, kv = _llm_trunk(params, pcfg, ctx_tokens, query_tokens,
                       want_cache=True)
    B, S, _ = x.shape
    answer_logits, seg = _llm_outputs(params, x[:, -1])

    W = S if width is None else width
    assert W >= S, (W, S)
    if W > S:
        kv = jax.tree.map(
            lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, W - S)]
                              + [(0, 0)] * (a.ndim - 3)), kv)
    pos_arr = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S)),
         jnp.full((B, W - S), -1, jnp.int32)], axis=1)
    cache = {"groups": kv, "positions": pos_arr}
    return answer_logits, seg, cache


def llm_prefill_paged(params: dict, pcfg: LISAPipelineConfig,
                      ctx_tokens: jax.Array, query_tokens: jax.Array,
                      page_size: int) -> Tuple[jax.Array, jax.Array, Dict]:
    """Prefill over [ctx; query] that emits the KV cache chunked into
    fixed-size pages — the serving path's shared-prefix unit. Returns
    (answer_logits (B,V), seg (B,d_sam), paged_kv) with paged_kv leaves
    (L, B, n_pages, page_size, ...); the zero-padded tail of the last
    page carries no position and is masked by the caller's bookkeeping
    (``paging.prefix_positions``). Equivalent to ``llm_prefill`` with
    ``width = n_pages * page_size`` up to the page reshape."""
    x, kv = _llm_trunk(params, pcfg, ctx_tokens, query_tokens,
                       want_cache=True)
    B, S, _ = x.shape
    answer_logits, seg = _llm_outputs(params, x[:, -1])
    n_pages = -(-S // page_size)
    W = n_pages * page_size
    if W > S:
        kv = jax.tree.map(
            lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, W - S)]
                              + [(0, 0)] * (a.ndim - 3)), kv)
    paged = jax.tree.map(
        lambda a: a.reshape(a.shape[:2] + (n_pages, page_size)
                            + a.shape[3:]), kv)
    return answer_logits, seg, {"groups": paged}


def _trunk_paged(p: dict, llm: ModelConfig, group_fn, x: jax.Array,
                 positions: jax.Array, pool: Dict, page_table: jax.Array,
                 write_page: jax.Array, write_off: jax.Array,
                 mask: jax.Array) -> Tuple[jax.Array, Dict, Dict]:
    """Every layer group in turn against its part of the page pool
    (``group_fn``: ``stack.group_decode_paged`` or ``group_verify_paged``).
    Returns (x normed, new pool, the groups' counters added up)."""
    kvs, counts = [], {}
    for spec, gp, kv in zip(stack.layer_groups(llm), p["groups"],
                            pool["groups"], strict=True):
        x, kv, c = group_fn(gp, llm, spec, x, positions, kv, page_table,
                            write_page, write_off, mask)
        kvs.append(kv)
        for k, v in c.items():
            counts[k] = counts[k] + v if k in counts else v
    return stack.apply_norm(x, p["norm"], llm), {"groups": kvs}, counts


def llm_decode_step_paged(params: dict, pcfg: LISAPipelineConfig, pool: Dict,
                          page_table: jax.Array, positions: jax.Array,
                          tokens: jax.Array, pos: jax.Array,
                          write_slot: jax.Array
                          ) -> Tuple[jax.Array, jax.Array, Dict, Dict]:
    """One in-flight decode step against the shared KV page pool.

    pool {"groups": [kv, ...]}, one entry per layer group, with leaves
    (L, P, page, ...) — pages shared across every live request;
    page_table (B, n_pages) i32, every entry a valid page id (idle rows
    park on the reserved trash page);
    positions (B, n_pages*page) i32 absolute position stored in each
    virtual slot (-1 empty — the caller owns this bookkeeping, it is
    append-only and deterministic); tokens (B,1) i32; pos (B,) i32
    absolute positions of the new tokens; write_slot (B,) i32 virtual
    slot receiving each row's token. Returns (answer_logits (B,V),
    seg (B,d_sam), new pool, counts) — ``counts`` the step's routed-expert
    counters (``stack.group_decode_paged``), empty for a trunk without a
    dropless MoE share. Token-exact with the contiguous
    ``llm_decode_step``: the gathered virtual sequence preserves
    ascending position order and masked slots contribute exactly zero.
    """
    llm = pcfg.llm
    p = params["llm"]
    B = tokens.shape[0]
    page = jax.tree.leaves(pool)[0].shape[2]
    x = jnp.take(p["embed"], tokens, axis=0).astype(llm.adtype)
    pos = jnp.asarray(pos, jnp.int32)
    write_slot = jnp.asarray(write_slot, jnp.int32)
    rows = jnp.arange(B)
    pos_arr = jnp.asarray(positions, jnp.int32).at[rows, write_slot].set(pos)
    mask = cache_mask(pos_arr, pos[:, None], llm.sliding_window)
    page_table = jnp.asarray(page_table, jnp.int32)
    write_page = page_table[rows, write_slot // page]
    write_off = write_slot % page
    x, pool, counts = _trunk_paged(p, llm, stack.group_decode_paged, x,
                                   pos[:, None], pool, page_table,
                                   write_page, write_off, mask)
    answer_logits, seg = _llm_outputs(params, x[:, -1])
    return answer_logits, seg, pool, counts


def llm_verify_step_paged(params: dict, pcfg: LISAPipelineConfig, pool: Dict,
                          page_table: jax.Array, positions: jax.Array,
                          tokens: jax.Array, pos: jax.Array,
                          write_slot: jax.Array, chunk_len: jax.Array
                          ) -> Tuple[jax.Array, jax.Array, Dict, Dict]:
    """One speculative *verify* step: a chunk of C tokens per row — the
    row's last accepted token followed by drafted continuations — scored
    through the serving model in a single paged multi-token pass.

    pool/page_table/positions as in ``llm_decode_step_paged``; tokens
    (B, C) i32 chunk tokens occupying consecutive virtual slots
    ``write_slot .. write_slot+C-1`` at absolute positions
    ``pos .. pos+C-1`` (both (B,) i32 starts); chunk_len (B,) i32 marks
    how many leading chunk entries are real — pad entries scatter their
    k/v to the reserved trash page, record no position, and their
    logits are garbage the caller ignores (this is what lets plain
    C=1-style rows ride the same jitted call as speculating rows).

    Causal within the chunk: the chunk's k/v land in the pool before
    attention and the position mask admits slots with position <= the
    query's, so chunk token i attends [cache; chunk tokens <= i] —
    exactly the context C successive ``llm_decode_step_paged`` calls
    would give it. Returns (answer_logits (B, C, V), seg (B, C, d_sam),
    new pool): logits[:, i] is the model's next-token distribution
    after consuming chunk token i (column 0 of a chunk_len=1 call
    matches ``llm_decode_step_paged`` on the same token), and seg[:, i]
    is the <SEG> read at chunk position i (the final accepted position
    supplies ``llm_generate``'s end-of-answer embedding); counts as in
    ``llm_decode_step_paged``."""
    from repro.core.paging import TRASH_PAGE
    llm = pcfg.llm
    p = params["llm"]
    B, C = tokens.shape
    page = jax.tree.leaves(pool)[0].shape[2]
    n_slots = positions.shape[1]
    x = jnp.take(p["embed"], tokens, axis=0).astype(llm.adtype)
    rows = jnp.arange(B)[:, None]
    offs = jnp.arange(C, dtype=jnp.int32)[None, :]
    valid = offs < jnp.asarray(chunk_len, jnp.int32)[:, None]
    pos_c = jnp.asarray(pos, jnp.int32)[:, None] + offs          # (B, C)
    ws = jnp.asarray(write_slot, jnp.int32)[:, None] + offs      # (B, C)
    # pad entries scatter out of bounds -> dropped (their positions stay
    # unset, so their trash-page writes can never be attended as valid)
    ws_sc = jnp.where(valid, ws, n_slots)
    pos_arr = jnp.asarray(positions, jnp.int32).at[rows, ws_sc].set(
        pos_c, mode="drop")
    mask = cache_mask(pos_arr[:, None, :], pos_c[:, :, None],
                      llm.sliding_window)                        # (B, C, W)
    page_table = jnp.asarray(page_table, jnp.int32)
    ws_in = jnp.minimum(ws, n_slots - 1)
    write_page = jnp.where(valid, page_table[rows, ws_in // page],
                           TRASH_PAGE)
    write_off = ws_in % page
    x, pool, counts = _trunk_paged(p, llm, stack.group_verify_paged, x,
                                   pos_c, pool, page_table, write_page,
                                   write_off, mask)
    answer_logits, seg = _llm_outputs(params, x)
    return answer_logits, seg, pool, counts


def llm_decode_step(params: dict, pcfg: LISAPipelineConfig, cache: Dict,
                    tokens: jax.Array, pos: jax.Array
                    ) -> Tuple[jax.Array, jax.Array, Dict]:
    """One autoregressive decode step against the KV cache. tokens (B,1)
    i32; pos i32 — either a scalar (whole batch at the same absolute
    position) or a (B,) vector of per-row positions (the in-flight
    batching path, where requests join a running decode mid-stream and
    each slot sits at its own depth). Returns (answer_logits (B,V),
    seg (B,d_sam), new_cache). The attention hot loop routes through the
    flash-decode Pallas kernel when ``pcfg.llm.use_flash_decode`` is
    set."""
    llm = pcfg.llm
    p = params["llm"]
    B = tokens.shape[0]
    x = jnp.take(p["embed"], tokens, axis=0).astype(llm.adtype)
    W = cache["positions"].shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    slot = pos % W
    if pos.ndim == 0:
        pos_arr = jax.lax.dynamic_update_slice(
            cache["positions"],
            jnp.broadcast_to(pos, (B, 1)), (0, slot))
        mask = cache_mask(pos_arr, pos, llm.sliding_window)
        positions = jnp.broadcast_to(pos, (B, 1))
    else:                               # per-row ring slots + masks
        pos_arr = cache["positions"].at[jnp.arange(B), slot].set(pos)
        mask = cache_mask(pos_arr, pos[:, None], llm.sliding_window)
        positions = pos[:, None]
    kvs = []
    for spec, gp, kv in zip(stack.layer_groups(llm), p["groups"],
                            cache["groups"], strict=True):
        x, kv = stack.group_decode(gp, llm, spec, x, positions, kv, slot,
                                   mask)
        kvs.append(kv)
    x = stack.apply_norm(x, p["norm"], llm)
    answer_logits, seg = _llm_outputs(params, x[:, -1])
    return answer_logits, seg, {"groups": kvs, "positions": pos_arr}


def llm_generate(params: dict, pcfg: LISAPipelineConfig, ctx_tokens: jax.Array,
                 query_tokens: jax.Array, max_new_tokens: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Greedy multi-token answer generation: one prefill over [ctx; query]
    then flash-decode steps (the first answer token comes from the prefill
    logits). Returns (tokens (B, T) i32, first_answer_logits (B, V),
    seg (B, d_sam)). The seg embedding is always read from the hidden
    state of the *final generated* token — the answer's trailing <SEG>
    position — for every T, so mask conditioning doesn't change
    convention between T == 1 and T > 1. jit-able with static
    ``max_new_tokens``."""
    S = ctx_tokens.shape[1] + query_tokens.shape[1]
    W = S + max_new_tokens
    logits0, _, cache = llm_prefill(params, pcfg, ctx_tokens, query_tokens,
                                    width=W)
    tok0 = jnp.argmax(logits0, axis=-1).astype(jnp.int32)
    if max_new_tokens > 1:
        def step(carry, pos):
            tok, c = carry
            logits, _, c2 = llm_decode_step(params, pcfg, c, tok[:, None],
                                            pos)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, c2), nxt

        (last, cache), toks = jax.lax.scan(
            step, (tok0, cache), jnp.arange(S, S + max_new_tokens - 1,
                                            dtype=jnp.int32))
        tokens = jnp.concatenate([tok0[:, None], toks.T], axis=1)
    else:
        last, tokens = tok0, tok0[:, None]
    # one more decode step to read the <SEG> hidden state at the last
    # generated token itself (its logits predict beyond the answer and
    # are discarded)
    _, seg, _ = llm_decode_step(params, pcfg, cache, last[:, None],
                                jnp.int32(S + max_new_tokens - 1))
    return tokens, logits0, seg


def mask_decode(params: dict, pcfg: LISAPipelineConfig, sam_feats: jax.Array,
                seg: jax.Array) -> jax.Array:
    """<SEG>-conditioned mask decoder: (B, T, d_sam) x (B, d_sam) ->
    per-pixel logits (B, H, W)."""
    mh = params["mask_head"]
    fused = sam_feats * seg[:, None, :].astype(sam_feats.dtype)
    h = gelu(linear(fused, mh["w1"], mh["b1"]))
    pix = linear(h, mh["w2"])                         # (B, T, pp)
    B, T, pp = pix.shape
    g = pcfg.image_size // pcfg.patch_size
    if pp == 1:
        return pix.reshape(B, g, g)
    s = int(round(pp ** 0.5))
    pix = pix.reshape(B, g, g, s, s)
    pix = pix.transpose(0, 1, 3, 2, 4)
    return pix.reshape(B, g * s, g * s)


# ----- end-to-end pipelines -----


def insight_forward(params: dict, pcfg: LISAPipelineConfig,
                    images: jax.Array, query_tokens: jax.Array,
                    bn_params: Optional[dict] = None,
                    split_k: Optional[int] = None,
                    ) -> Tuple[jax.Array, jax.Array]:
    """Full Insight pipeline; with ``bn_params`` the boundary activation is
    compressed with the straight-through bottleneck (training/eval path).
    Returns (mask_logits (B,H,W), answer_logits (B,V))."""
    a = sam_head(params, pcfg, images, split_k)
    if bn_params is not None:
        a = bn.roundtrip_st(bn_params, a)
    feats = sam_tail(params, pcfg, a, split_k)
    ctx = clip_encode(params, pcfg, images)
    answer_logits, seg = llm_reason(params, pcfg, ctx, query_tokens)
    mask_logits = mask_decode(params, pcfg, feats, seg)
    return mask_logits, answer_logits


def context_forward(params: dict, pcfg: LISAPipelineConfig,
                    images: jax.Array, query_tokens: jax.Array) -> jax.Array:
    """Context pipeline: CLIP-only features -> LLM -> text answer logits."""
    ctx = clip_encode(params, pcfg, images)
    answer_logits, _ = llm_reason(params, pcfg, ctx, query_tokens)
    return answer_logits


# ----- losses / metrics -----


def insight_loss(params: dict, pcfg: LISAPipelineConfig, batch: Dict,
                 bn_params: Optional[dict] = None,
                 pos_weight: float = 25.0) -> Tuple[jax.Array, Dict]:
    mask_logits, answer_logits = insight_forward(
        params, pcfg, batch["images"], batch["query"], bn_params)
    m = batch["mask"].astype(jnp.float32)
    ml = mask_logits.astype(jnp.float32)
    # positive-class weighting: targets cover ~2% of pixels, so unweighted
    # BCE collapses to the empty-mask optimum
    w = 1.0 + (pos_weight - 1.0) * m
    bce = jnp.mean(w * (jnp.maximum(ml, 0) - ml * m
                        + jnp.log1p(jnp.exp(-jnp.abs(ml)))))
    # dice loss stabilises IoU on small targets
    p = jax.nn.sigmoid(ml)
    inter = jnp.sum(p * m, axis=(1, 2))
    dice = 1 - jnp.mean((2 * inter + 1) /
                        (jnp.sum(p, axis=(1, 2)) + jnp.sum(m, axis=(1, 2)) + 1))
    ans = _answer_ce(answer_logits, batch["answer"])
    loss = bce + dice + 0.5 * ans
    return loss, {"bce": bce, "dice": dice, "answer_ce": ans}


def context_loss(params: dict, pcfg: LISAPipelineConfig,
                 batch: Dict) -> Tuple[jax.Array, Dict]:
    logits = context_forward(params, pcfg, batch["images"], batch["query"])
    ce = _answer_ce(logits, batch["answer"])
    return ce, {"answer_ce": ce}


def _answer_ce(logits: jax.Array, answer: jax.Array) -> jax.Array:
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, answer[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def iou_metrics(mask_logits: jax.Array, gt: jax.Array) -> Dict[str, jax.Array]:
    """gIoU (mean per-image IoU), cIoU (cumulative), and their mean —
    the paper's 'Average IoU' (Table 3 note)."""
    pred = (mask_logits > 0).astype(jnp.float32)
    gt = gt.astype(jnp.float32)
    inter = jnp.sum(pred * gt, axis=(1, 2))
    union = jnp.sum(jnp.maximum(pred, gt), axis=(1, 2))
    giou = jnp.mean(inter / (union + 1e-6))
    ciou = jnp.sum(inter) / (jnp.sum(union) + 1e-6)
    return {"giou": giou, "ciou": ciou, "avg_iou": 0.5 * (giou + ciou)}
