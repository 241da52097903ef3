#!/usr/bin/env python3
"""Compile each configuration's serving stages for a described TPU v5e
chip, without the chip, and print their memory.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse.py [config ...]

For each configuration (default: every one in ``BENCHMARK.json``) it
lowers the executor stages a cell's window drives -- prefix prefill,
pool write, paged decode, SAM tail per tier and mask decode -- at the
cells' shapes (8 decode slots, the harness's page pool) and prints each
one's ``memory_analysis()`` and whether the decode step holds the Pallas
kernel (``tpu_custom_call``). Nothing runs: it says what the chip's
compiler accepts and how much memory each program needs beside the
weights.
"""
import importlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from perfbench import harness, model  # noqa: E402

SLOTS = 8


def _sds(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def rehearse(cfg, qlen: int, answer_len: int, sharding) -> dict:
    from repro.core import DualStreamExecutor
    from repro.core.lut import paper_lut
    kern = importlib.import_module(
        "repro.kernels.decode_attention.decode_attention")
    kern.resolve_interpret = lambda interpret=None: False   # compile Mosaic
    pcfg = model.pipeline_config(cfg)
    tiers = cfg["bottleneck_tiers"]
    params, bns = model.weight_layout(pcfg, tiers)
    params, bns = _sds(params, sharding), _sds(bns, sharding)
    ex = DualStreamExecutor(pcfg, params, bns, paper_lut(),
                            max_new_tokens=answer_len)
    llm, page = pcfg.llm, ex.page_size
    n_pre = -(-(pcfg.clip_tokens + qlen) // page)
    n_ans = -(-answer_len // page)
    pages = 1 + (2 * SLOTS + 1) * n_pre + SLOTS * n_ans
    kv = (llm.num_layers, pages, page, llm.num_kv_heads,
          llm.resolved_head_dim)
    one = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=sharding)
    pool = {"groups": [{"k": one(kv, llm.adtype), "v": one(kv, llm.adtype)}]}
    width = (n_pre + n_ans) * page
    i32 = jnp.int32
    stages = {
        "cloud_prefix": (ex._jitted("cloud_prefix", None, 1, qlen),
                         (params, one((1, pcfg.clip_tokens, llm.d_model),
                                      llm.adtype), one((1, qlen), i32))),
        "pool_write": (ex._pool_write, (
            pool, {"groups": [{k: one((kv[0], n_pre) + kv[2:], llm.adtype)
                               for k in ("k", "v")}]}, one((n_pre,), i32))),
        "cloud_decode_rows": (ex._decode_paged, (
            params, pool, one((SLOTS, n_pre + n_ans), i32),
            one((SLOTS, width), i32), one((SLOTS, 1), i32),
            one((SLOTS,), i32), one((SLOTS,), i32))),
        "cloud_mask": (ex._mask_decode, (
            params, one((1, pcfg.sam_tokens, pcfg.sam.d_model),
                        pcfg.sam.adtype), one((1, pcfg.sam.d_model),
                                              llm.adtype))),
    }
    for t in tiers:
        rank = bns[t]["enc"].shape[-1]
        stages[f"cloud_sam_feats[{t}]"] = (
            ex._jitted("cloud_sam_feats", t, 1, 0),
            (params, bns[t], one((1, pcfg.sam_tokens, rank), jnp.int8),
             one((1, pcfg.sam_tokens, 1), jnp.float32)))
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves((params, bns)))
    out = {"weights_bytes": weights, "pool_pages": pages, "stages": {}}
    for name, (fn, args) in stages.items():
        compiled = fn.lower(*args).compile()
        m = compiled.memory_analysis()
        row = {"argument_bytes": m.argument_size_in_bytes,
               "output_bytes": m.output_size_in_bytes,
               "temp_bytes": m.temp_size_in_bytes,
               "alias_bytes": m.alias_size_in_bytes}
        if name == "cloud_decode_rows":
            row["tpu_custom_call"] = "tpu_custom_call" in compiled.as_text()
        out["stages"][name] = row
        print(f"{cfg['name']} {name}: {json.dumps(row)}", flush=True)
    return out


def main(argv) -> int:
    jax.config.update("jax_enable_compilation_cache", False)
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = argv or [c["name"] for c in bench["configs"]]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    traffic = {w["config"]: w["traffic"] for w in bench["workloads"]}
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    for name in names:
        cfg = harness.load_json(os.path.join(ROOT, files[name]))
        mix = harness.load_json(os.path.join(
            harness.HERE, "traffic", traffic[name] + ".json"))
        res = rehearse(cfg, int(mix["query_len"]), int(mix["answer_len"]),
                       chip)
        print(json.dumps({"config": name, **res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
