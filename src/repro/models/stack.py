"""Layer-stack machinery: homogeneous layer groups scanned with ``lax.scan``.

Every architecture is expressed as a sequence of *groups*; each group is a
stack of identical blocks whose parameters carry a leading layer axis, so a
96-layer model lowers to a single scanned HLO body (essential for compile
time on the 512-device dry-run; see DESIGN.md §7).

Group kinds:
  dense   — attention + dense FFN             (dense / audio / vlm archs)
  moe     — attention + MoE FFN               (granite, deepseek)
  mamba1  — Mamba-1 mixer                     (falcon-mamba)
  mamba2  — Mamba-2 mixer                     (zamba2 backbone)
  hybrid  — Zamba2 super-group: one *shared* attention block (parameters
            shared across all invocations) followed by ``attn_every``
            Mamba-2 layers.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention, moe as moe_lib, ssm
from repro.models.common import layer_norm, rms_norm
from repro.models.config import ModelConfig


@dataclass(frozen=True)
class GroupSpec:
    kind: str
    count: int
    d_ff: int = 0   # dense FFN width for dense/moe-dense groups


def layer_groups(cfg: ModelConfig) -> List[GroupSpec]:
    if cfg.arch_type == "hybrid":
        ae = cfg.hybrid.attn_every
        assert cfg.num_layers % ae == 0, (cfg.num_layers, ae)
        return [GroupSpec("hybrid", cfg.num_layers // ae)]
    if cfg.arch_type == "ssm":
        return [GroupSpec(f"mamba{cfg.ssm.version}", cfg.num_layers)]
    if cfg.moe is not None:
        out = []
        fk = cfg.moe.first_k_dense
        if fk:
            out.append(GroupSpec("dense", fk, cfg.moe.d_ff_dense or cfg.d_ff))
        out.append(GroupSpec("moe", cfg.num_layers - fk))
        return out
    return [GroupSpec("dense", cfg.num_layers, cfg.d_ff)]


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(cfg: ModelConfig) -> dict:
    if cfg.norm == "layernorm":
        return {"w": jnp.ones((cfg.d_model,), cfg.pdtype),
                "b": jnp.zeros((cfg.d_model,), cfg.pdtype)}
    return {"w": jnp.ones((cfg.d_model,), cfg.pdtype)}


def apply_norm(x: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    if cfg.norm == "layernorm":
        return layer_norm(x, p["w"], p["b"], cfg.norm_eps)
    return rms_norm(x, p["w"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------


def _init_dense_layer(rng: jax.Array, cfg: ModelConfig, d_ff: int) -> dict:
    k1, k2 = jax.random.split(rng)
    return {
        "norm1": init_norm(cfg),
        "attn": attention.init_attention(k1, cfg),
        "norm2": init_norm(cfg),
        "mlp": moe_lib.init_dense_mlp(k2, cfg, d_ff),
    }


def _init_moe_layer(rng: jax.Array, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(rng)
    return {
        "norm1": init_norm(cfg),
        "attn": attention.init_attention(k1, cfg),
        "norm2": init_norm(cfg),
        "moe": moe_lib.init_moe(k2, cfg),
    }


def _init_mamba_layer(rng: jax.Array, cfg: ModelConfig) -> dict:
    init = ssm.init_mamba1 if cfg.ssm.version == 1 else ssm.init_mamba2
    return {"norm": init_norm(cfg), "mixer": init(rng, cfg)}


def _init_hybrid_group(rng: jax.Array, cfg: ModelConfig) -> dict:
    """Only the per-group Mamba-2 layers; the shared attention block lives
    once at the top level (params['shared_attn'])."""
    ae = cfg.hybrid.attn_every
    ks = jax.random.split(rng, ae)
    return jax.vmap(lambda k: _init_mamba_layer(k, cfg))(ks)


def init_group(rng: jax.Array, cfg: ModelConfig, spec: GroupSpec) -> Any:
    ks = jax.random.split(rng, spec.count)
    if spec.kind == "dense":
        return jax.vmap(lambda k: _init_dense_layer(k, cfg, spec.d_ff))(ks)
    if spec.kind == "moe":
        return jax.vmap(lambda k: _init_moe_layer(k, cfg))(ks)
    if spec.kind in ("mamba1", "mamba2"):
        return jax.vmap(lambda k: _init_mamba_layer(k, cfg))(ks)
    if spec.kind == "hybrid":
        return jax.vmap(lambda k: _init_hybrid_group(k, cfg))(ks)
    raise ValueError(spec.kind)


# ---------------------------------------------------------------------------
# block bodies (full sequence)
# ---------------------------------------------------------------------------


def _seq_wsc(cfg, x):
    if not cfg.seq_shard:
        return x
    from repro.models.common import wsc
    return wsc(x, "BATCH", "model", None)


def _dense_block_full(p, cfg, x, positions, mask):
    x = _seq_wsc(cfg, x)
    h, kv = attention.attn_full(p["attn"], cfg, apply_norm(x, p["norm1"], cfg),
                                positions, mask)
    x = _seq_wsc(cfg, x + h)
    x = x + moe_lib.dense_mlp(p["mlp"], cfg, apply_norm(x, p["norm2"], cfg))
    return x, kv


def _held_stacks(params: Any, cfg: ModelConfig, spec: GroupSpec):
    """A dropless MoE group's expert weights, kept stacked outside its
    layer scan (``moe.moe_held``'s ``layer``): (params without them,
    the stacks), or (params, None) for any other group."""
    if spec.kind != "moe" or cfg.moe.dispatch != "dropless":
        return params, None
    moe = dict(params["moe"])
    stacks = {k: moe.pop(k) for k in moe_lib.EXPERT_WEIGHTS}
    return dict(params, moe=moe), stacks


def _moe_ffn(p, cfg, x, stacks=None, layer=None, live=None):
    """(out, aux, counts) of an MoE layer's FFN; ``stacks``/``layer`` as
    ``_held_stacks`` gives them (the dropless share, no aux loss), and
    ``live`` as ``moe.moe_held`` takes it."""
    if stacks is None:
        y, aux = moe_lib.moe_mlp(p["moe"], cfg, x)
        return y, aux, {}
    y, counts = moe_lib.moe_held(dict(p["moe"], **stacks), cfg, x, layer,
                                 live)
    return y, jnp.float32(0.0), counts


def _moe_block_full(p, cfg, x, positions, mask, stacks=None, layer=None):
    h, kv = attention.attn_full(p["attn"], cfg, apply_norm(x, p["norm1"], cfg),
                                positions, mask)
    x = x + h
    y, aux, _ = _moe_ffn(p, cfg, apply_norm(x, p["norm2"], cfg), stacks,
                         layer)
    return x + y, kv, aux


def _mamba_block_full(p, cfg, x):
    full = ssm.mamba1_full if cfg.ssm.version == 1 else ssm.mamba2_full
    return x + full(p["mixer"], cfg, apply_norm(x, p["norm"], cfg))


def group_forward(params: Any, cfg: ModelConfig, spec: GroupSpec, x: jax.Array,
                  positions: jax.Array, mask: jax.Array,
                  shared_attn: Optional[dict] = None,
                  want_cache: bool = False):
    """Run one group full-sequence. Returns (x, aux_loss, cache_or_None)."""
    if spec.kind == "dense":
        def body(h, lp):
            h2, kv = _dense_block_full(lp, cfg, h, positions, mask)
            return h2, (kv if want_cache else 0)
        body = jax.checkpoint(body) if cfg.remat else body
        x, kvs = jax.lax.scan(body, x, params, unroll=cfg.scan_unroll)
        return x, 0.0, (kvs if want_cache else None)

    if spec.kind == "moe":
        params, stacks = _held_stacks(params, cfg, spec)

        def body(carry, inp):
            h, aux = carry
            lp, i = inp
            h2, kv, a = _moe_block_full(lp, cfg, h, positions, mask, stacks,
                                        i)
            return (h2, aux + a), (kv if want_cache else 0)
        body = jax.checkpoint(body) if cfg.remat else body
        (x, aux), kvs = jax.lax.scan(
            body, (x, jnp.float32(0.0)),
            (params, jnp.arange(spec.count) if stacks else None),
            unroll=cfg.scan_unroll)
        return x, aux, (kvs if want_cache else None)

    if spec.kind in ("mamba1", "mamba2"):
        def body(h, lp):
            return _mamba_block_full(lp, cfg, h), 0
        body = jax.checkpoint(body) if cfg.remat else body
        x, _ = jax.lax.scan(body, x, params, unroll=cfg.scan_unroll)
        return x, 0.0, None

    if spec.kind == "hybrid":
        sa = shared_attn
        def body(h, gp):
            h2, kv = _dense_block_full(sa, cfg, h, positions, mask)
            def mbody(hh, lp):
                return _mamba_block_full(lp, cfg, hh), 0
            h3, _ = jax.lax.scan(mbody, h2, gp, unroll=cfg.scan_unroll)
            return h3, (kv if want_cache else 0)
        body = jax.checkpoint(body) if cfg.remat else body
        x, kvs = jax.lax.scan(body, x, params, unroll=cfg.scan_unroll)
        return x, 0.0, (kvs if want_cache else None)

    raise ValueError(spec.kind)


# ---------------------------------------------------------------------------
# block bodies (single-token decode)
# ---------------------------------------------------------------------------


def _dense_block_decode(p, cfg, x, positions, cache, slot, mask):
    h, c2 = attention.attn_decode(p["attn"], cfg, apply_norm(x, p["norm1"], cfg),
                                  positions, cache, slot, mask)
    x = x + h
    x = x + moe_lib.dense_mlp(p["mlp"], cfg, apply_norm(x, p["norm2"], cfg))
    return x, c2


def _moe_block_decode(p, cfg, x, positions, cache, slot, mask):
    h, c2 = attention.attn_decode(p["attn"], cfg, apply_norm(x, p["norm1"], cfg),
                                  positions, cache, slot, mask)
    x = x + h
    y, _ = moe_lib.moe_mlp(p["moe"], cfg, apply_norm(x, p["norm2"], cfg))
    return x + y, c2


def _block_paged(p, cfg, x, positions, pool, page_table, write_page,
                 write_off, mask, attn_fn, stacks=None, layer=None):
    """One attention block against the page pool, its FFN dense or MoE.
    Returns (x, new pool, counts): the dropless MoE share's counters
    (``moe.moe_held``) over the tokens someone is served, those that
    write into a page of their own (idle rows and pad entries write into
    the trash page); no leaves for any other FFN."""
    h, c2 = attn_fn(
        p["attn"], cfg, apply_norm(x, p["norm1"], cfg), positions, pool,
        page_table, write_page, write_off, mask)
    x = x + h
    hn = apply_norm(x, p["norm2"], cfg)
    if "mlp" in p:
        return x + moe_lib.dense_mlp(p["mlp"], cfg, hn), c2, {}
    from repro.core.paging import TRASH_PAGE
    live = (write_page != TRASH_PAGE).reshape(x.shape[:2])
    y, _, counts = _moe_ffn(p, cfg, hn, stacks, layer, live)
    return x + y, c2, counts


def _mamba_block_decode(p, cfg, x, state):
    step = ssm.mamba1_step if cfg.ssm.version == 1 else ssm.mamba2_step
    h, s2 = step(p["mixer"], cfg, apply_norm(x, p["norm"], cfg), state)
    return x + h, s2


def group_decode(params: Any, cfg: ModelConfig, spec: GroupSpec, x: jax.Array,
                 positions: jax.Array, cache: Any, slot: jax.Array,
                 mask: jax.Array, shared_attn: Optional[dict] = None):
    """Single-token decode through one group. Returns (x, new_cache)."""
    if spec.kind == "dense":
        def body(h, inp):
            lp, c = inp
            return _dense_block_decode(lp, cfg, h, positions, c, slot, mask)
        return jax.lax.scan(body, x, (params, cache),
                            unroll=cfg.scan_unroll)

    if spec.kind == "moe":
        def body(h, inp):
            lp, c = inp
            return _moe_block_decode(lp, cfg, h, positions, c, slot, mask)
        return jax.lax.scan(body, x, (params, cache),
                            unroll=cfg.scan_unroll)

    if spec.kind in ("mamba1", "mamba2"):
        def body(h, inp):
            lp, s = inp
            return _mamba_block_decode(lp, cfg, h, s)
        return jax.lax.scan(body, x, (params, cache),
                            unroll=cfg.scan_unroll)

    if spec.kind == "hybrid":
        sa = shared_attn
        def body(h, inp):
            gp, c = inp
            h2, kv2 = _dense_block_decode(sa, cfg, h, positions, c["attn"],
                                          slot, mask)
            def mbody(hh, minp):
                lp, s = minp
                return _mamba_block_decode(lp, cfg, hh, s)
            h3, s2 = jax.lax.scan(mbody, h2, (gp, c["mamba"]),
                                  unroll=cfg.scan_unroll)
            return h3, {"attn": kv2, "mamba": s2}
        return jax.lax.scan(body, x, (params, cache),
                            unroll=cfg.scan_unroll)

    raise ValueError(spec.kind)


def _group_paged(params: Any, cfg: ModelConfig, spec: GroupSpec,
                 x: jax.Array, positions: jax.Array, pool: Any,
                 page_table: jax.Array, write_page: jax.Array,
                 write_off: jax.Array, mask: jax.Array, attn_fn):
    if spec.kind not in ("dense", "moe"):
        raise NotImplementedError(
            f"the paged pool covers attention stacks only, not {spec.kind}")
    params, stacks = _held_stacks(params, cfg, spec)

    def body(h, inp):
        lp, c, i = inp
        h, c, counts = _block_paged(lp, cfg, h, positions, c, page_table,
                                    write_page, write_off, mask, attn_fn,
                                    stacks, i)
        return h, (c, counts)

    x, (pool, counts) = jax.lax.scan(
        body, x, (params, pool, jnp.arange(spec.count) if stacks else None),
        unroll=cfg.scan_unroll)
    return x, pool, jax.tree.map(lambda a: jnp.sum(a, axis=0), counts)


def group_decode_paged(params: Any, cfg: ModelConfig, spec: GroupSpec,
                       x: jax.Array, positions: jax.Array, pool: Any,
                       page_table: jax.Array, write_page: jax.Array,
                       write_off: jax.Array, mask: jax.Array):
    """Single-token decode through one group against a shared KV page
    pool (leaves (L, P, page, ...)). Attention-cache stacks only — SSM
    recurrent state has no sequence axis to page. Returns
    (x, new pool, counts): the group's dropless-MoE counters summed over
    its layers (``_block_paged``), an empty dict for other groups."""
    return _group_paged(params, cfg, spec, x, positions, pool, page_table,
                        write_page, write_off, mask,
                        attention.attn_decode_paged)


def group_verify_paged(params: Any, cfg: ModelConfig, spec: GroupSpec,
                       x: jax.Array, positions: jax.Array, pool: Any,
                       page_table: jax.Array, write_page: jax.Array,
                       write_off: jax.Array, mask: jax.Array):
    """Multi-token (speculative verify) decode through one group against
    the shared KV page pool: x (B, C, d) chunk tokens, positions /
    write_page / write_off (B, C), mask (B, C, n_pages*page). Same layer
    scan as ``group_decode_paged`` with the multi-query attention body.
    Returns (x, new pool, counts)."""
    return _group_paged(params, cfg, spec, x, positions, pool, page_table,
                        write_page, write_off, mask,
                        attention.attn_verify_paged)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def group_empty_cache(cfg: ModelConfig, spec: GroupSpec, batch: int,
                      width: int) -> Any:
    def stack(tree, n):
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), tree)

    if spec.kind in ("dense", "moe"):
        return stack(attention.empty_cache(cfg, batch, width), spec.count)
    if spec.kind in ("mamba1", "mamba2"):
        empty = (ssm.mamba1_empty_state if cfg.ssm.version == 1
                 else ssm.mamba2_empty_state)
        return stack(empty(cfg, batch), spec.count)
    if spec.kind == "hybrid":
        return {
            "attn": stack(attention.empty_cache(cfg, batch, width), spec.count),
            "mamba": stack(stack(ssm.mamba2_empty_state(cfg, batch),
                                 cfg.hybrid.attn_every), spec.count),
        }
    raise ValueError(spec.kind)


# ---------------------------------------------------------------------------
# parameter counting
# ---------------------------------------------------------------------------


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    """Exact parameter count via abstract init (no allocation)."""
    from repro.models import model as model_lib
    shapes = jax.eval_shape(
        lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))
    import math
    total = sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))
    if active_only and cfg.moe is not None:
        m = cfg.moe
        per_expert = (3 if cfg.gated_mlp else 2) * cfg.d_model * m.d_ff_expert
        n_moe_layers = cfg.num_layers - m.first_k_dense
        total -= n_moe_layers * max(0, m.held - m.top_k) * per_expert
    return total
