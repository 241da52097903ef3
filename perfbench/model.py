"""The configuration as the program serves it, and the inputs the
benchmark makes for it from the seed.

``pipeline_config`` turns a configuration file (``configs/<name>.json``)
into the program's ``LISAPipelineConfig``. ``make_weights`` makes every
weight the program serves, in the program's layout and types, on the
device (or, sharded, on the devices of a mesh) in one jitted call from
the seed: the program's own init is asked only for the layout
(``jax.eval_shape``), never for values. ``make_frames``
makes the pool of edge payloads a request can carry: CLIP context
features for the trunk's prefix and, per Insight tier, int8 bottleneck
codes with their per-token scales, as a UAV would send them.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def _encoder_cfg(name: str, enc: Dict[str, Any], dtype: str):
    from repro.models import ModelConfig
    return ModelConfig(
        name=name, arch_type="dense", num_layers=enc["depth"],
        d_model=enc["embed_dim"], num_heads=enc["num_heads"],
        num_kv_heads=enc["num_heads"], d_ff=enc["mlp_dim"], vocab_size=1,
        causal=False, rope_style="none", norm="layernorm", mlp_act="gelu",
        gated_mlp=False, norm_eps=enc["layer_norm_eps"], param_dtype=dtype,
        act_dtype=dtype)


def pipeline_config(cfg: Dict[str, Any]):
    """The program's pipeline config for one configuration file: the
    trunk as its architecture module maps it (``arch.resolve``), SAM and
    CLIP as every configuration has them."""
    from perfbench import arch
    from repro.configs.lisa7b import LISAPipelineConfig
    dtype = cfg["dtype"]
    llm = arch.resolve(cfg).llm_config(cfg)
    sam, clip = cfg["sam"], cfg["clip"]
    return LISAPipelineConfig(
        name=cfg["name"], sam=_encoder_cfg("sam", sam, dtype),
        clip=_encoder_cfg("clip", clip, dtype), llm=llm,
        image_size=sam["image_size"], patch_size=sam["patch_size"],
        context_image_size=clip["image_size"],
        context_patch_size=clip["patch_size"],
        split_layer=sam["split_layer"])


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole seed up to 64 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _leaf_value(key, path: str, shape, dtype):
    """Seeded values by the leaf's role: norm scales near 1, biases and
    embedding tables small, every matrix scaled by its fan-in."""
    name = path.rsplit("/", 1)[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if "norm" in path and name == "w":
        v = 1.0 + 0.05 * z
    elif name in ("b", "bq", "bk", "bv", "b1", "patch_b"):
        v = 0.02 * z
    elif name in ("embed", "pos"):
        v = 0.02 * z
    else:
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        v = z / math.sqrt(fan_in)
    return v.astype(dtype)


def _paths(tree) -> List[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, _ in flat:
        out.append("/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                            for p in path))
    return out


def weight_layout(pcfg, tiers: Sequence[str]):
    """Shapes and types of everything the program serves: the pipeline's
    params and one bottleneck pair per tier (``(params, bottlenecks)`` of
    ``jax.ShapeDtypeStruct``)."""
    from repro.core import profile as prof
    from repro.core import vlm
    params = jax.eval_shape(lambda: vlm.init_lisa(pcfg,
                                                  jax.random.PRNGKey(0)))
    bns = jax.eval_shape(
        lambda: prof.random_init_system(pcfg, seed=0, params=0)[1])
    return params, {t: bns[t] for t in tiers}


def weight_shardings(pcfg, layout, mesh):
    """Where each weight lives on ``mesh``: the program's key-path rules
    (``sharding.specs.param_specs``), the ones its sharded serving
    context places the weights by."""
    from repro.sharding import specs
    return specs.to_shardings(mesh, specs.param_specs(pcfg.llm, layout,
                                                      mesh))


def make_weights(pcfg, tiers: Sequence[str], seed: int, mesh=None):
    """(params, bottlenecks by tier) from ``seed``, made on the device in
    one jitted call. With ``mesh`` each weight is made where the program
    keeps it, so no chip ever holds the whole trunk; the values do not
    depend on the mesh."""
    layout = weight_layout(pcfg, tiers)
    leaves, treedef = jax.tree.flatten(layout)
    paths = _paths(layout)

    def init(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            _leaf_value(k, p, l.shape, l.dtype)
            for k, p, l in zip(keys, paths, leaves)])

    jitted = jax.jit(init) if mesh is None else jax.jit(
        init, out_shardings=weight_shardings(pcfg, layout, mesh))
    out = jitted(jax.random.fold_in(seed_key(seed), 1))
    return jax.block_until_ready(out)


def make_frames(pcfg, bottlenecks, tiers: Sequence[str], n: int, seed: int
                ) -> Dict[str, List[Dict[str, np.ndarray]]]:
    """``n`` distinct edge payloads per stream, on the host, from the seed:
    ``{"context": [{"ctx"}], tier: [{"codes", "scales", "clip"}]}``.
    Codes are int8 with a per-token absmax scale, as the edge's bottleneck
    encode makes them."""
    rng = np.random.default_rng([int(seed), 2])
    adt = jnp.dtype(pcfg.llm.act_dtype)
    shape_ctx = (1, pcfg.clip_tokens, pcfg.llm.d_model)
    out: Dict[str, List[Dict[str, np.ndarray]]] = {
        "context": [{"ctx": rng.standard_normal(shape_ctx, np.float32)
                     .astype(adt)} for _ in range(n)]}
    for tier in tiers:
        rank = bottlenecks[tier]["enc"].shape[-1]
        frames = []
        for _ in range(n):
            z = rng.standard_normal((1, pcfg.sam_tokens, rank), np.float32)
            s = np.abs(z).max(-1, keepdims=True) / 127.0 + 1e-8
            codes = np.clip(np.round(z / s), -127, 127).astype(np.int8)
            frames.append({
                "codes": codes, "scales": s.astype(np.float32),
                "clip": rng.standard_normal(shape_ctx, np.float32)
                .astype(adt)})
        out[tier] = frames
    return out
