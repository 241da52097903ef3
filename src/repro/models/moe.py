"""Mixture-of-Experts FFN: top-k router + capacity-based dispatch.

GShard/Switch-style one-hot dispatch (einsum) is the *paper-faithful
baseline* formulation — it is fully shardable under GSPMD (experts or
expert-internal d_ff on the "model" axis; tokens on ("pod","data")).
The §Perf hillclimb iterates on its dispatch-FLOPs overhead.

Supports DeepSeek-V3 topology: ``num_shared_experts`` always-on experts,
``first_k_dense`` leading dense layers, normalized top-k gates, and a
load-balance auxiliary loss; and its routing (``route``): sigmoid scores,
a correction bias used only to choose, group-limited top-k, scaled gates.

``dispatch="dropless"`` is the serving layer of expert parallelism
(``moe_held``): the layer holds some of the router's experts, routes over
all of them, drops nothing and computes its own experts' part. The
capacity dispatches above hold every expert.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import fan_in_init, linear, silu
from repro.models.config import ModelConfig


def init_dense_mlp(rng: jax.Array, cfg: ModelConfig, d_ff: int) -> dict:
    d = cfg.d_model
    ks = jax.random.split(rng, 3)
    dt = cfg.pdtype
    if cfg.gated_mlp:
        return {
            "w_gate": fan_in_init(ks[0], (d, d_ff), dt),
            "w_up": fan_in_init(ks[1], (d, d_ff), dt),
            "w_down": fan_in_init(ks[2], (d_ff, d), dt),
        }
    return {
        "w_up": fan_in_init(ks[0], (d, d_ff), dt),
        "w_down": fan_in_init(ks[1], (d_ff, d), dt),
    }


def dense_mlp(p: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    from repro.models.common import ACTIVATIONS
    act = ACTIVATIONS[cfg.mlp_act]
    if cfg.gated_mlp:
        return linear(act(linear(x, p["w_gate"])) * linear(x, p["w_up"]), p["w_down"])
    return linear(act(linear(x, p["w_up"])), p["w_down"])


# ---------------------------------------------------------------------------
# MoE layer
# ---------------------------------------------------------------------------


def init_moe(rng: jax.Array, cfg: ModelConfig) -> dict:
    m = cfg.moe
    d = cfg.d_model
    E, f = m.num_experts, m.d_ff_expert
    ks = jax.random.split(rng, 5)
    dt = cfg.pdtype
    p = {
        "router": fan_in_init(ks[0], (d, E), jnp.float32),
        "w_gate": fan_in_init(ks[1], (m.held, d, f), dt),
        "w_up": fan_in_init(ks[2], (m.held, d, f), dt),
        "w_down": fan_in_init(ks[3], (m.held, f, d), dt),
    }
    if m.scoring == "sigmoid":
        # the correction bias (``e_score_correction_bias``), a bias of
        # the router's choice only
        p["correction"] = {"b": jnp.zeros((E,), jnp.float32)}
    if m.num_shared_experts:
        p["shared"] = init_dense_mlp(
            ks[4], cfg, m.d_ff_shared * m.num_shared_experts)
    return p


def capacity(cfg: ModelConfig, num_tokens: int) -> int:
    m = cfg.moe
    c = int(math.ceil(num_tokens * m.top_k / m.num_experts * m.capacity_factor))
    return max(4, min(num_tokens, c))


def route(p: dict, cfg: ModelConfig, xt: jax.Array):
    """Top-k routing over every expert of the router, in float32.
    Returns (scores (T, E), gate_vals (T, k), gate_idx (T, k)).

    Softmax scoring (the training configs) normalises over all experts
    and takes the top-k of one group. Sigmoid scoring (DeepSeek-V3)
    scores each expert alone at full precision; the correction bias and
    the group limit shape only the choice: groups are ranked by the sum
    of their two best biased scores and picks come from the
    ``topk_group`` best groups; the gates are the unbiased scores of the
    picks, normalised and scaled by ``routed_scaling``."""
    m = cfg.moe
    E, k, G = m.num_experts, m.top_k, m.n_group
    if m.scoring == "softmax":
        if G > 1:
            raise ValueError("group-limited routing needs sigmoid scoring")
        scores = jax.nn.softmax(xt.astype(jnp.float32) @ p["router"],
                                axis=-1)                          # (T, E)
        gate_vals, gate_idx = jax.lax.top_k(scores, k)            # (T, k)
        gate_vals = gate_vals / (jnp.sum(gate_vals, axis=-1, keepdims=True)
                                 + 1e-9)
        return scores, gate_vals, gate_idx
    scores = jax.nn.sigmoid(jnp.dot(xt.astype(jnp.float32), p["router"],
                                    precision=jax.lax.Precision.HIGHEST))
    choice = scores + p["correction"]["b"]
    if G > 1:
        grouped = choice.reshape(-1, G, E // G)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, keep = jax.lax.top_k(group_score, m.topk_group)        # (T, g)
        kept = jnp.sum(jax.nn.one_hot(keep, G, dtype=jnp.bool_), axis=1)
        choice = jnp.where(kept[:, :, None], grouped,
                           -jnp.inf).reshape(-1, E)
    _, gate_idx = jax.lax.top_k(choice, k)                        # (T, k)
    gate_vals = jnp.take_along_axis(scores, gate_idx, axis=-1)
    gate_vals = gate_vals / (jnp.sum(gate_vals, axis=-1, keepdims=True)
                             + 1e-20)
    return scores, gate_vals * m.routed_scaling, gate_idx


def _router(p: dict, cfg: ModelConfig, xt: jax.Array):
    """Shared routing: returns (gate_vals (T,k), gate_idx (T,k), aux)."""
    m = cfg.moe
    E = m.num_experts
    probs, gate_vals, gate_idx = route(p, cfg, xt)
    # load-balance auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)                                  # (E,)
    onehot_any = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)   # (T,k,E)
    ce = jnp.mean(jnp.sum(onehot_any, axis=1), axis=0)            # (E,)
    aux = m.router_aux_weight * E * jnp.sum(me * ce)
    return gate_vals, gate_idx, onehot_any, aux


def _expert_ffn(p: dict, cfg: ModelConfig, xe: jax.Array) -> jax.Array:
    """xe: (E, C, d) -> (E, C, d)."""
    if cfg.gated_mlp:
        h = silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(xe.dtype))) \
            * jnp.einsum("ecd,edf->ecf", xe, p["w_up"].astype(xe.dtype))
    else:
        h = silu(jnp.einsum("ecd,edf->ecf", xe, p["w_up"].astype(xe.dtype)))
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(xe.dtype))


def _moe_einsum(p: dict, cfg: ModelConfig, xt: jax.Array, gate_vals, gate_idx,
                onehot_any, C: int) -> jax.Array:
    """GShard-faithful one-hot dispatch. Materialises a (T, E, C) dispatch
    tensor — the §Perf baseline whose memory/FLOPs blow-up motivates the
    scatter path below."""
    m = cfg.moe
    T, d = xt.shape
    E, k = m.num_experts, m.top_k
    # capacity assignment: position of each (token, slot) within its expert
    sel = onehot_any.reshape(T * k, E)                            # token-major
    pos_in_e = (jnp.cumsum(sel, axis=0) - sel)                    # (T*k, E)
    pos = jnp.sum(pos_in_e * sel, axis=-1).reshape(T, k)          # (T, k)
    keep = (pos < C).astype(jnp.float32)
    gate_vals = gate_vals * keep
    pos_oh = jax.nn.one_hot(jnp.where(keep > 0, pos, C).astype(jnp.int32),
                            C + 1, dtype=jnp.float32)[..., :C]    # (T,k,C)
    dispatch = jnp.einsum("tke,tkc->tec", onehot_any, pos_oh)     # (T,E,C)
    combine = jnp.einsum("tke,tkc,tk->tec", onehot_any, pos_oh, gate_vals)
    xe = jnp.einsum("tec,td->ecd", dispatch.astype(xt.dtype), xt)  # (E,C,d)
    ye = _expert_ffn(p, cfg, xe)
    return jnp.einsum("tec,ecd->td", combine.astype(xt.dtype), ye)


from repro.models.common import wsc as _wsc


def _positions_in_expert(flat_e: jax.Array) -> jax.Array:
    """Rank of each slot within its expert, via sort — no (T·k, E) temp."""
    n = flat_e.shape[0]
    order = jnp.argsort(flat_e)                                   # stable
    sorted_e = flat_e[order]
    idx = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                sorted_e[1:] != sorted_e[:-1]])
    start = jax.lax.associative_scan(jnp.maximum,
                                     jnp.where(is_start, idx, 0))
    pos_sorted = idx - start
    pos = jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)
    return pos


def _buf_spec(cfg: ModelConfig, E: int, C: int, model_size_hint: int = 16):
    """Shard the expert buffer on E when divisible (expert parallel,
    deepseek 256e) else on the capacity dim (granite 40e)."""
    if E % model_size_hint == 0:
        return ("model", None, None)
    return (None, "model", None)


def _moe_scatter(p: dict, cfg: ModelConfig, xt: jax.Array, gate_vals, gate_idx,
                 C: int) -> jax.Array:
    """Sort-based dispatch (beyond-baseline, §Perf): scatter tokens straight
    into (E, C, d) expert buffers. Dropped slots keep their dest but their
    payload is zeroed (capacity semantics identical to the einsum path).
    The buffer carries an explicit sharding constraint so GSPMD exchanges
    token payloads instead of all-reducing a replicated buffer."""
    m = cfg.moe
    T, d = xt.shape
    E, k = m.num_experts, m.top_k
    flat_e = gate_idx.reshape(T * k).astype(jnp.int32)
    pos = _positions_in_expert(flat_e)                            # (T*k,)
    keep = pos < C
    dest = jnp.where(keep, flat_e * C + pos, E * C - 1)
    src = jnp.repeat(xt, k, axis=0) * keep[:, None].astype(xt.dtype)
    # .add: valid destinations are unique (pos is a rank within the expert);
    # dropped slots all alias E*C-1 but contribute zeros
    buf = jnp.zeros((E * C, d), xt.dtype).at[dest].add(src)
    buf = _wsc(buf.reshape(E, C, d), *_buf_spec(cfg, E, C))
    ye = _wsc(_expert_ffn(p, cfg, buf), *_buf_spec(cfg, E, C))
    ye = ye.reshape(E * C, d)
    gathered = ye[dest] * (gate_vals.reshape(T * k, 1).astype(ye.dtype)
                           * keep[:, None].astype(ye.dtype))
    return jnp.sum(gathered.reshape(T, k, d), axis=1)


def _moe_grouped(p: dict, cfg: ModelConfig, x: jax.Array, gate_vals, gate_idx
                 ) -> jax.Array:
    """GShard-style group-local dispatch (§Perf): groups are batch rows,
    already sharded over the data axes, and capacity is per-group — so the
    scatter/gather never crosses a shard boundary and dispatch is
    collective-free. Expert weights are replicated w.r.t. data (sharded on
    d_ff/E over "model"), so the expert matmul reduces over "model" only."""
    m = cfg.moe
    B, S, d = x.shape
    E, k = m.num_experts, m.top_k
    C = capacity(cfg, S)

    def local(xg, gv, gi):                       # (S,d), (S,k), (S,k)
        flat_e = gi.reshape(S * k).astype(jnp.int32)
        pos = _positions_in_expert(flat_e)
        keep = pos < C
        dest = jnp.where(keep, flat_e * C + pos, E * C - 1)
        src = jnp.repeat(xg, k, axis=0) * keep[:, None].astype(xg.dtype)
        buf = jnp.zeros((E * C, d), xg.dtype).at[dest].add(src)
        return buf.reshape(E, C, d), dest, keep

    buf, dest, keep = jax.vmap(local)(x, gate_vals.reshape(B, S, k),
                                      gate_idx.reshape(B, S, k))
    buf = _wsc(buf, "BATCH", None, None, None)   # (B, E, C, d)
    if cfg.gated_mlp:
        h = silu(jnp.einsum("becd,edf->becf", buf,
                            p["w_gate"].astype(buf.dtype))) \
            * jnp.einsum("becd,edf->becf", buf, p["w_up"].astype(buf.dtype))
    else:
        h = silu(jnp.einsum("becd,edf->becf", buf,
                            p["w_up"].astype(buf.dtype)))
    ye = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(buf.dtype))
    ye = _wsc(ye, "BATCH", None, None, None).reshape(B, E * C, d)

    def combine(yg, dg, kg, gv):                 # (E*C,d), (S*k,), ...
        g = yg[dg] * (gv.reshape(S * k, 1).astype(yg.dtype)
                      * kg[:, None].astype(yg.dtype))
        return jnp.sum(g.reshape(S, k, d), axis=1)

    out = jax.vmap(combine)(ye, dest, keep, gate_vals.reshape(B, S, k))
    return out.reshape(B * S, d)


EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def moe_held(p: dict, cfg: ModelConfig, x: jax.Array,
             layer: Optional[jax.Array] = None,
             live: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The serving share of an MoE layer: x (B, S, d) -> (out, counts).

    Routes every token over all of the router's experts, then computes
    the part of the result that this layer's held experts give, for
    every token routed to them: dropless, no capacity. Picks on experts
    held elsewhere contribute nothing here (on one chip the exchange that
    would carry them is absent); the shared expert is added whole. A held
    expert no token picked is skipped, its weights unread. ``counts``:
    ``expert_picks``, the picks that landed on held experts, and
    ``experts_hit``, the held experts with at least one pick (int32).

    With ``layer``, the expert weights in ``p`` are a group's stacks
    (L, H, ...) and each expert is sliced by (layer, expert) inside the
    branch that computes it: a layer scan that sliced the stacks itself
    would copy a layer's every expert before the branches run.

    ``live`` (B, S) bool marks the tokens someone is served: the others
    (a decode step's idle rows, a verify chunk's pad entries) get the
    shared expert alone, and neither count nor wake a held expert."""
    m = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    _, gate_vals, gate_idx = route(p, cfg, xt)
    local = gate_idx - m.held_first
    local = jnp.where((local >= 0) & (local < m.held), local, -1)
    if live is not None:
        local = jnp.where(live.reshape(B * S, 1), local, -1)
    onehot = jax.nn.one_hot(local, m.held, dtype=jnp.float32)    # (T,k,H)
    gates = jnp.einsum("tk,tkh->th", gate_vals, onehot)          # (T, H)
    picks = jnp.sum(onehot, axis=(0, 1)).astype(jnp.int32)       # (H,)

    def expert(acc, e):
        def run(acc):
            w = {k: p[k][e] if layer is None else p[k][layer, e]
                 for k in EXPERT_WEIGHTS}
            h = silu(linear(xt, w["w_gate"])) * linear(xt, w["w_up"])
            y = linear(h, w["w_down"]).astype(jnp.float32)
            return acc + y * gates[:, e, None]
        return jax.lax.cond(picks[e] > 0, run, lambda a: a, acc), None

    out, _ = jax.lax.scan(expert, jnp.zeros((B * S, d), jnp.float32),
                          jnp.arange(m.held))
    out = out.astype(x.dtype)
    if m.num_shared_experts:
        out = out + dense_mlp(p["shared"], cfg, xt)
    counts = {"expert_picks": jnp.sum(picks),
              "experts_hit": jnp.sum(picks > 0).astype(jnp.int32)}
    return out.reshape(B, S, d), counts


def moe_mlp(p: dict, cfg: ModelConfig, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss). Capacity-dropped tokens fall back to
    the shared expert (if any) / residual. The dropless serving share
    (``moe_held``) has no auxiliary loss."""
    m = cfg.moe
    if m.dispatch == "dropless":
        return moe_held(p, cfg, x)[0], jnp.float32(0.0)
    B, S, d = x.shape
    T = B * S
    C = capacity(cfg, T)
    xt = x.reshape(T, d)
    gate_vals, gate_idx, onehot_any, aux = _router(p, cfg, xt)
    if m.dispatch == "grouped" and B > 1:
        out = _moe_grouped(p, cfg, x, gate_vals, gate_idx)
    elif m.dispatch == "scatter" or (m.dispatch == "grouped" and B == 1):
        out = _moe_scatter(p, cfg, xt, gate_vals, gate_idx, C)
    else:
        out = _moe_einsum(p, cfg, xt, gate_vals, gate_idx, onehot_any, C)
    if m.num_shared_experts:
        out = out + dense_mlp(p["shared"], cfg, xt)
    return out.reshape(B, S, d), aux
