"""The §Perf optimization levers must be numerically equivalent to the
paper-faithful baselines (they change layout/scheduling, not math)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_local_mesh
from repro.models import (ModelConfig, MoEConfig, forward, init_cache,
                          decode_step, init_params)


@pytest.fixture(scope="module")
def moe_setup():
    cfg = ModelConfig(
        name="t", arch_type="moe", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=97,
        moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=32,
                      capacity_factor=8.0))
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16),
                                          0, 97)}
    base, *_ = forward(params, cfg, batch)
    return cfg, params, batch, base


@pytest.mark.parametrize("dispatch", ["scatter", "grouped"])
def test_moe_dispatch_equivalence(moe_setup, dispatch):
    cfg, params, batch, base = moe_setup
    c2 = cfg.replace(moe=dataclasses.replace(cfg.moe, dispatch=dispatch))
    out, *_ = forward(params, c2, batch)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=1e-4, atol=1e-4)


def test_attn_chunk_equivalence():
    cfg = ModelConfig(name="t", arch_type="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=97)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32),
                                          0, 97)}
    base, *_ = forward(params, cfg, batch)
    out, *_ = forward(params, cfg.replace(attn_chunk=8), batch)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=1e-4, atol=1e-4)


def test_attn_chunk_equivalence_mla():
    from repro.models import MLAConfig
    cfg = ModelConfig(name="t", arch_type="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=97,
                      attn_type="mla",
                      mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                                    qk_nope_head_dim=16, qk_rope_head_dim=8,
                                    v_head_dim=16))
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32),
                                          0, 97)}
    base, *_ = forward(params, cfg, batch)
    out, *_ = forward(params, cfg.replace(attn_chunk=8), batch)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=1e-4, atol=1e-4)


def test_seq_shard_and_kvhd_are_noops_without_mesh():
    """wsc-based levers are identity off-mesh (single-device tests/serving)."""
    cfg = ModelConfig(name="t", arch_type="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=97)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                          0, 97)}
    base, *_ = forward(params, cfg, batch)
    out, *_ = forward(params, cfg.replace(seq_shard=True), batch)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))

    cache = init_cache(cfg, 2, 16)
    tok = jnp.zeros((2, 1), jnp.int32)
    l1, _ = decode_step(params, cfg, cache, tok, jnp.int32(0))
    l2, _ = decode_step(params, cfg.replace(shard_cache_hd=True), cache, tok,
                        jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_kvhd_decode_consistency_with_mesh():
    """shard_cache_hd decode on a (1,1) mesh matches the unsharded path."""
    cfg = ModelConfig(name="t", arch_type="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=97,
                      shard_cache_hd=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 97)
    base, *_ = forward(params, cfg, {"tokens": tokens})
    mesh = make_local_mesh(model=1)
    cache = init_cache(cfg, 2, 8)
    outs = []
    with mesh:
        for t in range(8):
            lg, cache = decode_step(params, cfg, cache, tokens[:, t:t + 1],
                                    jnp.int32(t))
            outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(base),
                               rtol=1e-4, atol=1e-4)
