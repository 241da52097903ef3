"""Operations and bytes from shapes: against hand counts and against
XLA's own count of the reference at a tiny size."""
import jax
import jax.numpy as jnp
import pytest

from perfbench import flops, model
from perfbench.arch import dense_gqa
from perfbench.tests import tiny


@pytest.fixture(scope="module")
def pcfg():
    return model.pipeline_config(tiny.CONFIG)


def test_trunk_token_is_twice_the_weights_plus_attention(pcfg):
    # 2 layers: q/o 64x64 each, k/v 64x32 each, 3 MLP 64x128
    weights = 2 * (2 * 64 * 64 + 2 * 64 * 32 + 3 * 64 * 128)
    assert dense_gqa.token_flops(pcfg, 10) == \
        2 * weights + 2 * (2 * 2 * 10 * 4 * 16)


def test_prefill_sums_a_causal_triangle(pcfg):
    n = 7
    want = sum(dense_gqa.token_flops(pcfg, i) for i in range(1, n + 1))
    assert flops.prefill_flops(dense_gqa, pcfg, n) == \
        want + flops.head_flops(pcfg)
    assert flops.head_flops(pcfg) == 2 * 64 * 256 + 2 * 64 * 32


def test_paged_attention_counts_live_rows_once(pcfg):
    f, b = dense_gqa.paged_attention_cost(pcfg, [10, 20])
    assert f == 2 * (2 * 2 * 30 * 4 * 16)
    assert b == 2 * ((2 * 30 * 2 * 16 + 2 * 2 * 4 * 16) * 2)
    assert dense_gqa.paged_attention_cost(pcfg, []) == (0.0, 0.0)
    assert flops.decode_step_flops(dense_gqa, pcfg, [10, 20]) == (
        dense_gqa.token_flops(pcfg, 10) + dense_gqa.token_flops(pcfg, 20)
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


@pytest.mark.parametrize("metric", ["mfu", "paged_decode_roofline.throughput"])
def test_a_four_chip_cell_reads_a_quarter_of_one_chips_share(pcfg, metric):
    """The same window's work over four chips' peaks: the readers divide
    whole-model counts by the cell's chips, and at one chip read what
    one chip's peaks give."""
    from perfbench import harness, peaks, readers
    from perfbench.arch import dense_gqa
    ctx = {"trace": {"window_s": 2.0, "busy_s": 1.5,
                     "kernel_s": {"paged_decode": 0.004}},
           "counts": {"decode_ctx": [[213, 240, 99], [214, 241]],
                      "prefill_len": [212, 212], "sam_rank": [5],
                      "mask": 1},
           "steps": 2, "slot_steps": 5, "pcfg": pcfg, "arch": dense_gqa,
           "peaks": peaks.peaks("TPU v5 lite")}
    read = harness.reader(metric)
    one, four = read(dict(ctx, chips=1)), read(dict(ctx, chips=4))
    assert one > 0 and four == pytest.approx(one / 4, rel=1e-12)
    p = ctx["peaks"]
    if metric == "mfu":
        assert one == 100.0 * readers.model_flops(ctx) / (
            2.0 * p["bf16_flops"])
    else:
        least = sum(max(f / p["bf16_flops"], b / p["hbm_bytes_per_s"])
                    for f, b in (dense_gqa.paged_attention_cost(pcfg, c)
                                 for c in ctx["counts"]["decode_ctx"]))
        assert one == 100.0 * least / 0.004
