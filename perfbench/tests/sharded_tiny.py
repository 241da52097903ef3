"""The tiny cell on four CPU devices, in a process of its own.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 -m perfbench.tests.sharded_tiny

The trunk has 4 kv heads, one per device. Makes the weights sharded as a
four-chip cell does and compares them with the one-device weights of the
same seed, then runs the closed mix through the harness with ``chips``
4 and the fp8 control. Prints one JSON line of what it found.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench.tests import tiny  # noqa: E402

CONFIG = dict(tiny.CONFIG, trunk=dict(tiny.CONFIG["trunk"],
                                      num_key_value_heads=4))
SEED = 2**31 + 91


def weights(chips: int):
    """What each leaf's shard holds of it, per axis, and whether the
    sharded weights equal the one-device weights of the same seed."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from perfbench import drive, model
    from repro.sharding import specs
    pcfg = model.pipeline_config(CONFIG)
    tiers = CONFIG["bottleneck_tiers"]
    mesh = drive.model_mesh(jax.devices()[:chips])
    sharded = model.make_weights(pcfg, tiers, SEED, mesh)
    one = model.make_weights(pcfg, tiers, SEED)
    rules = specs.param_specs(pcfg.llm, model.weight_layout(pcfg, tiers),
                              mesh)
    flat = jax.tree_util.tree_flatten_with_path(sharded)[0]
    leaves = []
    for (path, a), b, rule in zip(flat, jax.tree.leaves(one),
                                  jax.tree.leaves(rules, is_leaf=lambda x:
                                                  isinstance(x, P))):
        shard = a.addressable_shards[0].data.shape
        leaves.append({
            "path": jax.tree_util.keystr(path),
            "model_axes": [i for i, ax in enumerate(rule) if ax == "model"],
            "share": [s / n for s, n in zip(shard, a.shape)],
            "equal": bool(np.array_equal(np.asarray(a), np.asarray(b)))})
    trunk = jax.tree.leaves(sharded[0]["llm"])
    return {"leaves": leaves, "trunk_bytes": sum(x.nbytes for x in trunk),
            "trunk_bytes_on_device_0": sum(
                x.addressable_shards[0].data.nbytes for x in trunk)}


def main() -> int:
    import jax
    from perfbench import harness
    chips = len(jax.devices())
    found = weights(chips)
    limits = harness.load_json(os.path.join(
        harness.HERE, "limits", "phi4mini-context-closed.json"))
    mix = dict(tiny.CLOSED, clients=8, answer_len=8, preroll_answers=4)
    out = tiny.run(mix, limits, seed=SEED, chips=chips, config=CONFIG,
                   control=True)
    found.update(chips=chips, correct=out["result"]["correct"],
                 checks=out["result"]["checks"],
                 control=out["info"]["control"],
                 attempted=out["result"]["attempted"],
                 model_shards=out["info"]["model_shards"],
                 compiles_in_window=out["info"]["compiles_in_window"])
    print(json.dumps(found), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
