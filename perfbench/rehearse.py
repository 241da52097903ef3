#!/usr/bin/env python3
"""Compile each configuration's serving stages for described TPU v5e
chips, without the chips, and print their memory.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse.py [--chips 1|4] \
        [--traffic <mix>] [config ...]

For each configuration (default: every one in ``BENCHMARK.json``; a
configuration file's path, relative to the repository, takes the traffic
``--traffic`` names unless a cell of its name has one) it
lowers the executor stages a cell's window drives -- prefix prefill,
pool write, paged decode, SAM tail per tier and mask decode -- at the
cells' shapes (8 decode slots, the harness's page pool) and prints each
one's ``memory_analysis()`` and whether the decode step holds the Pallas
kernel (``tpu_custom_call``). With ``--chips 4`` the weights carry the
shardings a four-chip cell makes them with, on a "model" mesh of a
described 2x2 v5e, and the prefill, pool write and decode are the
engine's sharded serving stages (``ShardedServingContext``); every
number is then per chip. Nothing runs: it says what the chips' compiler
accepts and how much memory each program needs beside the weights.
"""
import argparse
import importlib
import json
import os
import sys
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import AxisType, SingleDeviceSharding  # noqa: E402

from perfbench import harness, model  # noqa: E402

SLOTS = 8


def _sds(tree, shardings):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, shardings)


def _per_chip(tree) -> int:
    """Bytes of ``tree``'s shapes that one chip holds."""
    return sum(int(np.prod(a.sharding.shard_shape(a.shape)))
               * a.dtype.itemsize for a in jax.tree.leaves(tree))


def _sharded_stages(ex, mesh, params, pool, paged, page_ids, feats,
                    decode_args, qlen):
    """The engine's sharded stages (``ShardedServingContext``), lowered:
    each is the context's own method inside a jit whose arguments carry
    the mesh's shardings, so what compiles is the context's stage with
    its in and out shardings. The context places the weights with
    ``jax.device_put``, which a described chip cannot take; they already
    carry those shardings, so placing them is the identity. Returns the
    pool as the context shards it and the lowered stages."""
    from repro.sharding.serving import ShardedServingContext
    with mock.patch.object(jax, "device_put", lambda x, _: x):
        ctx = ShardedServingContext(ex, mesh)
    pool = _sds(pool, ctx._kv_sh(pool))
    query = np.zeros((1, qlen), np.int32)   # the context reads it on host

    def lower(method, *args, concrete=()):
        def call(p, *a):
            ctx.params = p
            return getattr(ctx, method)(*a, *concrete)
        return jax.jit(call).lower(params, *args)

    return pool, {
        "cloud_prefix": lower("cloud_prefix", feats, concrete=(query,)),
        "pool_write": lower("pool_write", pool,
                            _sds(paged, ctx._kv_sh(paged)), page_ids),
        "cloud_decode_rows": lower("cloud_decode_rows", pool, *decode_args),
    }


def rehearse(cfg, qlen: int, answer_len: int, chips: int, topo) -> dict:
    from repro.core import DualStreamExecutor
    from repro.core.lut import paper_lut
    kern = importlib.import_module(
        "repro.kernels.decode_attention.decode_attention")
    kern.resolve_interpret = lambda interpret=None: False   # compile Mosaic
    pcfg = model.pipeline_config(cfg)
    tiers = cfg["bottleneck_tiers"]
    layout = model.weight_layout(pcfg, tiers)
    if chips == 1:
        mesh, chip = None, SingleDeviceSharding(topo.devices[0])
        params, bns = _sds(layout, jax.tree.map(lambda _: chip, layout))
    else:
        mesh = jax.make_mesh((1, chips), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2,
                             devices=topo.devices[:chips])
        params, bns = _sds(layout, model.weight_shardings(pcfg, layout,
                                                          mesh))
        chip = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    ex = DualStreamExecutor(pcfg, params, bns, paper_lut(),
                            max_new_tokens=answer_len)
    llm, page = pcfg.llm, ex.page_size
    n_pre = -(-(pcfg.clip_tokens + qlen) // page)
    n_ans = -(-answer_len // page)
    pages = 1 + (2 * SLOTS + 1) * n_pre + SLOTS * n_ans
    kv = (llm.num_layers, pages, page, llm.num_kv_heads,
          llm.resolved_head_dim)
    one = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=chip)
    pool = {"groups": [{"k": one(kv, llm.adtype), "v": one(kv, llm.adtype)}]}
    paged = {"groups": [{k: one((kv[0], n_pre) + kv[2:], llm.adtype)
                         for k in ("k", "v")}]}
    width = (n_pre + n_ans) * page
    i32 = jnp.int32
    ctx_feats = one((1, pcfg.clip_tokens, llm.d_model), llm.adtype)
    decode_args = (one((SLOTS, n_pre + n_ans), i32),
                   one((SLOTS, width), i32), one((SLOTS, 1), i32),
                   one((SLOTS,), i32), one((SLOTS,), i32))
    if mesh is None:
        lowered = {
            "cloud_prefix": ex._jitted("cloud_prefix", None, 1, qlen).lower(
                params, ctx_feats, one((1, qlen), i32)),
            "pool_write": ex._pool_write.lower(pool, paged,
                                               one((n_pre,), i32)),
            "cloud_decode_rows": ex._decode_paged.lower(params, pool,
                                                        *decode_args),
        }
    else:
        pool, lowered = _sharded_stages(ex, mesh, params, pool, paged,
                                        one((n_pre,), i32), ctx_feats,
                                        decode_args, qlen)
    lowered["cloud_mask"] = ex._mask_decode.lower(
        params, one((1, pcfg.sam_tokens, pcfg.sam.d_model), pcfg.sam.adtype),
        one((1, pcfg.sam.d_model), llm.adtype))
    for t in tiers:
        rank = bns[t]["enc"].shape[-1]
        lowered[f"cloud_sam_feats[{t}]"] = ex._jitted(
            "cloud_sam_feats", t, 1, 0).lower(
            params, bns[t], one((1, pcfg.sam_tokens, rank), jnp.int8),
            one((1, pcfg.sam_tokens, 1), jnp.float32))
    out = {"chips": chips, "weights_bytes_per_chip": _per_chip((params, bns)),
           "pool_bytes_per_chip": _per_chip(pool), "pool_pages": pages,
           "stages": {}}
    for name, low in lowered.items():
        compiled = low.compile()
        m = compiled.memory_analysis()
        row = {"argument_bytes": m.argument_size_in_bytes,
               "output_bytes": m.output_size_in_bytes,
               "temp_bytes": m.temp_size_in_bytes,
               "alias_bytes": m.alias_size_in_bytes}
        if name == "cloud_decode_rows":
            text = compiled.as_text()
            row["tpu_custom_call"] = "tpu_custom_call" in text
            row["all_reduce"] = text.count("all-reduce-start") \
                + text.count(" all-reduce(")
        out["stages"][name] = row
        print(f"{cfg['name']} {name}: {json.dumps(row)}", flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--traffic", default="context-closed",
                    help="traffic of a configuration given as a file")
    ap.add_argument("configs", nargs="*",
                    help="names in BENCHMARK.json or configuration files")
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = args.configs or [c["name"] for c in bench["configs"]]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    traffic = {w["config"]: w["traffic"] for w in bench["workloads"]}
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        cfg = harness.load_json(os.path.join(ROOT, files.get(name, name)))
        mix = harness.load_json(os.path.join(
            harness.HERE, "traffic",
            traffic.get(cfg["name"], args.traffic) + ".json"))
        res = rehearse(cfg, int(mix["query_len"]), int(mix["answer_len"]),
                       args.chips, topo)
        print(json.dumps({"config": cfg["name"], **res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
