"""A configuration's trunk is mapped, served and checked through the
architecture module it names, and nothing it states is dropped."""
import copy
import dataclasses
import os
import shutil
import sys

import jax
import numpy as np
import pytest

from perfbench import arch, harness, model
from perfbench.tests import tiny

# the program's ModelConfig of each committed configuration's trunk, as
# model.pipeline_config built it before the architecture modules
PARENT_LLM = {
    "avery-phi4mini": dict(
        name="avery-phi4mini", arch_type="dense", num_layers=32,
        d_model=3072, num_heads=24, num_kv_heads=8, d_ff=8192,
        vocab_size=200064, head_dim=128, qkv_bias=False, rope_theta=10000.0,
        norm_eps=1e-05, param_dtype="bfloat16", act_dtype="bfloat16"),
    "avery-qwen2vl2b": dict(
        name="avery-qwen2vl2b", arch_type="dense", num_layers=28,
        d_model=1536, num_heads=12, num_kv_heads=2, d_ff=8960,
        vocab_size=151936, head_dim=128, qkv_bias=True,
        rope_theta=1000000.0, norm_eps=1e-06, param_dtype="bfloat16",
        act_dtype="bfloat16"),
}


def _config(name):
    return harness.load_json(os.path.join(harness.HERE, "configs",
                                          name + ".json"))


@pytest.mark.parametrize("name", sorted(PARENT_LLM))
def test_committed_configs_map_to_the_same_model_config(name):
    from repro.models import ModelConfig
    cfg = _config(name)
    assert "arch" not in cfg and arch.resolve(cfg).__name__.endswith(
        ".dense_gqa")
    got = model.pipeline_config(cfg).llm
    want = ModelConfig(**PARENT_LLM[name])
    for field in dataclasses.fields(ModelConfig):
        assert getattr(got, field.name) == getattr(want, field.name), \
            field.name


def test_weights_at_one_chip_are_the_unsharded_recipe_bit_for_bit():
    """Without a mesh the weights are one plain jit of the seeded
    per-leaf values, as before the sharded set-up existed."""
    pcfg = model.pipeline_config(tiny.CONFIG)
    tiers, seed = tiny.CONFIG["bottleneck_tiers"], 2**31 + 5
    layout = model.weight_layout(pcfg, tiers)
    leaves, treedef = jax.tree.flatten(layout)
    paths = model._paths(layout)

    def init(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            model._leaf_value(k, p, l.shape, l.dtype)
            for k, p, l in zip(keys, paths, leaves)])

    want = jax.jit(init)(jax.random.fold_in(model.seed_key(seed), 1))
    got = model.make_weights(pcfg, tiers, seed)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _with_trunk(**changes):
    cfg = copy.deepcopy(tiny.CONFIG)
    cfg["trunk"].update(changes)
    return cfg


@pytest.mark.parametrize("cfg,named", [
    (_with_trunk(sliding_window=4096), "'sliding_window'"),
    (_with_trunk(num_experts=64), "'num_experts'"),
    (_with_trunk(partial_rotary_factor=0.75), "'partial_rotary_factor'"),
    (_with_trunk(tie_word_embeddings=True), "'tie_word_embeddings'"),
    (_with_trunk(hidden_act="gelu"), "'hidden_act'"),
    (dict(tiny.CONFIG, arch="mla_moe"), "dense_gqa"),
    (dict(tiny.CONFIG, arch="../model"), "dense_gqa"),
])
def test_what_no_module_serves_fails_at_set_up_and_is_named(cfg, named):
    with pytest.raises(ValueError, match=named):
        model.pipeline_config(cfg)


def test_a_read_key_left_out_fails_and_is_named():
    cfg = copy.deepcopy(tiny.CONFIG)
    del cfg["trunk"]["rope_theta"]
    with pytest.raises(ValueError, match="'rope_theta'"):
        model.pipeline_config(cfg)


def test_the_fixed_and_informational_keys_pass_at_their_values():
    cfg = _with_trunk(partial_rotary_factor=1.0, rope_scaling=None,
                      tie_word_embeddings=False, hidden_act="silu",
                      max_position_embeddings=4096, torch_dtype="bfloat16")
    assert model.pipeline_config(cfg) == model.pipeline_config(tiny.CONFIG)


def test_a_new_architecture_is_one_new_file(tmp_path, monkeypatch):
    """A module dropped into the package's path is found by name and
    serves, references and counts the configuration that names it."""
    shutil.copy(os.path.join(arch.__path__[0], "dense_gqa.py"),
                tmp_path / "second_arch.py")
    monkeypatch.setattr(arch, "__path__", list(arch.__path__)
                        + [str(tmp_path)])
    try:
        _serve_through_second_arch(tmp_path)
    finally:
        sys.modules.pop("perfbench.arch.second_arch", None)
        if hasattr(arch, "second_arch"):
            delattr(arch, "second_arch")


def _serve_through_second_arch(tmp_path):
    assert "second_arch" in arch.names()
    cfg = dict(tiny.CONFIG, arch="second_arch")
    mod = arch.resolve(cfg)
    assert mod.__file__ == str(tmp_path / "second_arch.py")
    pcfg = model.pipeline_config(cfg)
    assert pcfg == model.pipeline_config(tiny.CONFIG)
    base = arch.resolve(tiny.CONFIG)
    assert mod.token_flops(pcfg, 7) == base.token_flops(pcfg, 7)
    params, _ = model.make_weights(pcfg, cfg["bottleneck_tiers"], 3)
    ctx = np.zeros((1, pcfg.clip_tokens, pcfg.llm.d_model), np.float32)
    q, t = np.ones((1, 3), np.int32), np.ones((1, 2), np.int32)
    got = mod.trunk(params, cfg, ctx, q, t)[0]
    want = base.trunk(params, tiny.CONFIG, ctx, q, t)[0]
    assert np.array_equal(np.asarray(got), np.asarray(want))
