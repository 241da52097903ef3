"""Operations and bytes from shapes: against hand counts and against
XLA's own count of the reference at a tiny size."""
import jax
import jax.numpy as jnp
import pytest

from perfbench import flops, model
from perfbench.tests import tiny


@pytest.fixture(scope="module")
def pcfg():
    return model.pipeline_config(tiny.CONFIG)


def test_trunk_token_is_twice_the_weights_plus_attention(pcfg):
    # 2 layers: q/o 64x64 each, k/v 64x32 each, 3 MLP 64x128
    weights = 2 * (2 * 64 * 64 + 2 * 64 * 32 + 3 * 64 * 128)
    assert flops.trunk_token_flops(pcfg, 10) == \
        2 * weights + 2 * (2 * 2 * 10 * 4 * 16)


def test_prefill_sums_a_causal_triangle(pcfg):
    n = 7
    want = sum(flops.trunk_token_flops(pcfg, i) for i in range(1, n + 1))
    assert flops.prefill_flops(pcfg, n) == want + flops.head_flops(pcfg)
    assert flops.head_flops(pcfg) == 2 * 64 * 256 + 2 * 64 * 32


def test_paged_attention_counts_live_rows_once(pcfg):
    f, b = flops.paged_attention_cost(pcfg, [10, 20])
    assert f == 2 * (2 * 2 * 30 * 4 * 16)
    assert b == 2 * ((2 * 30 * 2 * 16 + 2 * 2 * 4 * 16) * 2)
    assert flops.paged_attention_cost(pcfg, []) == (0.0, 0.0)
    assert flops.decode_step_flops(pcfg, [10, 20]) == (
        flops.trunk_token_flops(pcfg, 10) + flops.trunk_token_flops(pcfg, 20)
        + 2 * flops.head_flops(pcfg))


def test_sam_tail_matches_xla_count_of_the_same_matmuls(pcfg):
    """The SAM tail's matmuls, counted by XLA for one frame."""
    T, d, f, rank = pcfg.sam_tokens, pcfg.sam.d_model, pcfg.sam.d_ff, 5

    def tail(z, dec, w4, wu, wd):
        x = z @ dec
        for _ in range(pcfg.sam.num_layers - pcfg.split_layer):
            h = x @ w4
            q, k, v = h[:, :d], h[:, d:2 * d], h[:, 2 * d:3 * d]
            x = x + (q @ k.T) @ v
            x = x + (x @ wu) @ wd
        return x

    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in
            [(T, rank), (rank, d), (d, 4 * d), (d, f), (f, d)]]
    # the stand-in's q/k/v/o is one (d, 4d) product; its attention is
    # two (T, T, d) products, as the model's heads together make
    cost = jax.jit(tail).lower(*args).compile().cost_analysis()
    xla = cost["flops"] if isinstance(cost, dict) else cost[0]["flops"]
    mine = flops.sam_tail_flops(pcfg, rank)
    assert abs(xla - mine) / mine < 0.02
