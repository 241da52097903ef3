"""Per-kernel validation: sweep shapes/dtypes, assert_allclose against the
pure-jnp ref.py oracles (assignment deliverable c)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bottleneck import ops as bops
from repro.kernels.bottleneck import ref as bref
from repro.kernels.flash_attention import ops as fops
from repro.kernels.flash_attention import ref as fref
from repro.kernels.ssm_scan import ops as sops
from repro.kernels.ssm_scan import ref as sref


# --------------------------- bottleneck -----------------------------------


@pytest.mark.parametrize("T,d,r", [(128, 128, 32), (64, 256, 100),
                                   (100, 64, 16), (256, 1280, 638)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bottleneck_encode(T, d, r, dtype):
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (T, d), dtype)
    w = (jax.random.normal(jax.random.fold_in(rng, 1), (d, r)) * 0.05
         ).astype(dtype)
    codes, scales = bops.bottleneck_encode(x, w)
    codes_r, scales_r = bref.encode_ref(x, w)
    assert codes.dtype == jnp.int8
    # matmul accumulation-order differences can flip a round() at .5:
    # codes agree within +-1 and scales to fp tolerance
    np.testing.assert_allclose(np.asarray(scales), np.asarray(scales_r),
                               rtol=1e-5, atol=1e-7)
    diff = np.abs(np.asarray(codes, np.int32) - np.asarray(codes_r, np.int32))
    assert diff.max() <= 1
    assert (diff > 0).mean() < 1e-3


@pytest.mark.parametrize("T,d,r", [(128, 128, 32), (64, 256, 100)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bottleneck_decode(T, d, r, dtype):
    rng = jax.random.PRNGKey(0)
    codes = jax.random.randint(rng, (T, r), -127, 128).astype(jnp.int8)
    scales = jax.random.uniform(rng, (T, 1), minval=0.01, maxval=0.1)
    w = (jax.random.normal(rng, (r, d)) * 0.05).astype(dtype)
    out = bops.bottleneck_decode(codes, scales, w, out_dtype=jnp.float32)
    out_r = bref.decode_ref(codes, scales, w, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-4)


def test_bottleneck_batched_shapes():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 37, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 16)) * 0.1
    codes, scales = bops.bottleneck_encode(x, w)
    assert codes.shape == (2, 37, 16) and scales.shape == (2, 37, 1)
    wd = jax.random.normal(jax.random.PRNGKey(2), (16, 64)) * 0.1
    y = bops.bottleneck_decode(codes, scales, wd)
    assert y.shape == (2, 37, 64)


# ------------------------- flash attention --------------------------------


@pytest.mark.parametrize("B,S,H,K,hd", [(2, 128, 4, 2, 64), (1, 200, 4, 4, 32),
                                        (2, 64, 8, 2, 64), (1, 256, 4, 1, 128),
                                        (1, 96, 6, 3, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(B, S, H, K, hd, causal):
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, K, hd))
    v = jax.random.normal(jax.random.fold_in(rng, 3), (B, S, K, hd))
    out = fops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = fref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (1, 128, 4, 64), dtype)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (1, 128, 2, 64), dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (1, 128, 2, 64), dtype)
    out = fops.flash_attention(q, k, v, causal=True)
    ref = fref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


# ---------------------------- ssm scan ------------------------------------


@pytest.mark.parametrize("B,S,C,N", [(2, 64, 128, 16), (1, 100, 60, 8),
                                     (2, 128, 256, 4), (1, 33, 16, 16)])
def test_ssm_scan_matches_ref(B, S, C, N):
    rng = jax.random.PRNGKey(0)
    decay = jax.random.uniform(jax.random.fold_in(rng, 1), (B, S, C, N),
                               minval=0.5, maxval=1.0)
    drive = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, C, N)) * 0.1
    h = sops.chunked_scan(decay, drive, chunk=32, block_c=64)
    h_ref = sref.scan_ref(decay, drive)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-6)


def test_ssm_scan_long_decay_stability():
    """Long-sequence stability: products of 512 decays stay finite and match
    the associative-scan oracle."""
    rng = jax.random.PRNGKey(7)
    decay = jax.random.uniform(rng, (1, 512, 32, 8), minval=0.9, maxval=0.999)
    drive = jax.random.normal(jax.random.fold_in(rng, 1), (1, 512, 32, 8))
    h = sops.chunked_scan(decay, drive, chunk=64, block_c=32)
    h_ref = sref.scan_ref(decay, drive)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=2e-4, atol=2e-4)


# ------------------------- decode attention --------------------------------


@pytest.mark.parametrize("B,H,K,hd,W", [(2, 4, 2, 64, 128), (1, 8, 8, 32, 200),
                                        (2, 8, 1, 128, 96), (4, 4, 4, 64, 512)])
def test_decode_attention_matches_ref(B, H, K, hd, W):
    from repro.kernels.decode_attention import ops as dops
    from repro.kernels.decode_attention import ref as dref
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(jax.random.fold_in(rng, 1), (B, H, hd))
    k = jax.random.normal(jax.random.fold_in(rng, 2), (B, W, K, hd))
    v = jax.random.normal(jax.random.fold_in(rng, 3), (B, W, K, hd))
    # slot-validity mask: ragged per-batch lengths (ring-buffer semantics)
    lens = np.linspace(W // 2, W, B).astype(int)
    bias = np.zeros((B, W), np.float32)
    for i, L in enumerate(lens):
        bias[i, L:] = -1e30
    bias = jnp.asarray(bias)
    out = dops.decode_attention(q, k, v, bias, block_k=64)
    ref = dref.decode_attention_ref(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=2e-5)


def test_decode_attention_bf16():
    from repro.kernels.decode_attention import ops as dops
    from repro.kernels.decode_attention import ref as dref
    rng = jax.random.PRNGKey(1)
    q = jax.random.normal(rng, (2, 4, 64), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (2, 128, 2, 64),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (2, 128, 2, 64),
                          jnp.bfloat16)
    bias = jnp.zeros((2, 128), jnp.float32)
    out = dops.decode_attention(q, k, v, bias)
    ref = dref.decode_attention_ref(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def _serving_rows(rng, B, P, page, n):
    """Page table and bias laid out as the in-flight engine lays out its
    rows: a prefix shared by every row whose last page is part-filled (a
    masked hole mid-row), private answer pages, and unused trailing pages
    parked on the trash page 0."""
    n_prefix = n // 2
    ids = rng.permutation(np.arange(1, P))
    prefix, private = ids[:n_prefix], ids[n_prefix:]
    table = np.zeros((B, n), np.int32)
    bias = np.full((B, n * page), -1e30, np.float32)
    for b in range(B):
        table[b, :n_prefix] = prefix
        n_answer = 1 + b % (n - n_prefix - 1)          # at least one trash page
        table[b, n_prefix:n_prefix + n_answer] = rng.choice(private, n_answer)
        bias[b, :n_prefix * page - page // 2 - b] = 0  # the prefix, then a hole
        answer = n_prefix * page
        bias[b, answer:answer + (n_answer - 1) * page + 1 + b] = 0
    return jnp.asarray(table), jnp.asarray(bias)


@pytest.mark.parametrize("B,H,K,hd,P,page,n,rows", [
    (2, 4, 2, 64, 9, 16, 3, "ragged"), (1, 8, 8, 32, 5, 8, 4, "ragged"),
    (3, 4, 1, 128, 12, 32, 2, "ragged"),
    # G = 1, 3, 6 at hd 128, rows as the engine serves them
    (3, 8, 8, 128, 20, 16, 6, "serving"), (3, 24, 8, 128, 20, 16, 6, "serving"),
    (3, 12, 2, 128, 20, 16, 6, "serving"),
    # more pages than one block holds, and not a multiple of the block
    (2, 32, 32, 128, 12, 16, 11, "serving"),
    (2, 24, 8, 128, 30, 16, 23, "serving")])
def test_paged_decode_attention_matches_ref(B, H, K, hd, P, page, n, rows):
    """Page-table gather path == dense oracle over the gathered layout."""
    from repro.kernels.decode_attention import ops as dops
    from repro.kernels.decode_attention import ref as dref
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, hd), jnp.float32)
    kp = jnp.asarray(rng.randn(P, page, K, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(P, page, K, hd), jnp.float32)
    if rows == "serving":
        pt, bias = _serving_rows(rng, B, P, page, n)
    else:
        pt = jnp.asarray(rng.randint(0, P, (B, n)), jnp.int32)
        # ragged validity: tail of each row's virtual sequence masked, as
        # the paged serving cache does for empty slots
        bias = np.zeros((B, n * page), np.float32)
        for i, L in enumerate(np.linspace(page, n * page, B).astype(int)):
            bias[i, L:] = -1e30
        bias = jnp.asarray(bias)
    out = dops.paged_decode_attention(q, kp, vp, pt, bias)
    ref = dref.paged_decode_attention_ref(q, kp, vp, pt, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("n_pages,K,itemsize,expect", [
    (16, 8, 2, 16),     # Phi-4-mini, a Context row: the whole row at once
    (14, 32, 2, 7),     # LISA-7B widths: two blocks of seven pages
    (80, 8, 2, 27),     # a long answer: three blocks, the last one short
    (11, 32, 4, 4),     # the f32 multi-block test case: 3 blocks, 1 padded
    (23, 8, 4, 12)])    # two blocks, one padded
def test_paged_decode_pages_per_block(n_pages, K, itemsize, expect):
    """The block of pages comes from the operands' shapes: as many pages
    as the VMEM budget holds, spread evenly over the fewest blocks."""
    import importlib
    kmod = importlib.import_module(
        "repro.kernels.decode_attention.decode_attention")
    ppb = kmod.pages_per_block(n_pages, 16 * K * 128, itemsize)
    assert ppb == expect
    per_page = 2 * 16 * K * 128 * (2 * itemsize + 4)
    assert ppb * per_page <= kmod._PAGED_VMEM_BYTES


def test_paged_decode_attention_lowers_without_pool_transpose(monkeypatch):
    """Lowered for a TPU from this host, the jitted wrapper reads the
    pool through a reshape alone (no transpose anywhere), and the Pallas
    call carries the name the benchmark's trace reader times."""
    import importlib
    from repro.kernels.decode_attention import ops as dops
    kmod = importlib.import_module(
        "repro.kernels.decode_attention.decode_attention")
    monkeypatch.setattr(kmod, "resolve_interpret",
                        lambda interpret=None: False)
    S = jax.ShapeDtypeStruct
    pool = S((255, 16, 8, 128), jnp.bfloat16)
    args = (S((8, 24, 128), jnp.bfloat16), pool, pool,
            S((8, 16), jnp.int32), S((8, 256), jnp.float32))
    # a fresh jit, so that no interpreted trace is reused or left behind
    fn = jax.jit(dops.paged_decode_attention.__wrapped__)
    text = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert "transpose" not in text
    assert "tensor<255x16x8x128xbf16>) -> tensor<255x128x128xbf16>" in text
    names = re.findall(r'kernel_name = "([^"]*)"', text)
    assert len(names) == 1 and "paged_decode" in names[0]


@pytest.mark.parametrize("B,C,H,K,hd,P,page,n",
                         [(2, 4, 4, 2, 64, 9, 16, 3),
                          (1, 3, 8, 8, 32, 5, 8, 4),
                          (3, 2, 4, 1, 128, 12, 32, 2)])
def test_paged_verify_attention_matches_ref(B, C, H, K, hd, P, page, n):
    """Multi-query (speculative verify) paged kernel == dense oracle,
    with per-query ragged validity (the causal-within-chunk + empty-slot
    bias the serving path feeds it)."""
    from repro.kernels.decode_attention import ops as dops
    from repro.kernels.decode_attention import ref as dref
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, C, H, hd), jnp.float32)
    kp = jnp.asarray(rng.randn(P, page, K, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(P, page, K, hd), jnp.float32)
    pt = jnp.asarray(rng.randint(0, P, (B, n)), jnp.int32)
    bias = np.zeros((B, C, n * page), np.float32)
    for b in range(B):
        for c in range(C):
            bias[b, c, rng.randint(page, n * page + 1):] = -1e30
    out = dops.paged_verify_attention(q, kp, vp, pt, jnp.asarray(bias))
    ref = dref.paged_verify_attention_ref(q, kp, vp, pt, jnp.asarray(bias))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=2e-5)


def test_paged_verify_single_token_matches_decode_kernel():
    """A one-token verify chunk is exactly the single-query paged decode
    kernel — the C axis degenerates cleanly."""
    from repro.kernels.decode_attention import ops as dops
    B, H, K, hd, P, page, n = 2, 4, 2, 64, 7, 16, 3
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(B, 1, H, hd), jnp.float32)
    kp = jnp.asarray(rng.randn(P, page, K, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(P, page, K, hd), jnp.float32)
    pt = jnp.asarray(rng.randint(0, P, (B, n)), jnp.int32)
    bias = np.zeros((B, 1, n * page), np.float32)
    bias[:, :, -page:] = -1e30
    out_v = dops.paged_verify_attention(q, kp, vp, pt, jnp.asarray(bias))
    out_d = dops.paged_decode_attention(q[:, 0], kp, vp, pt,
                                        jnp.asarray(bias[:, 0]))
    np.testing.assert_allclose(np.asarray(out_v[:, 0]), np.asarray(out_d),
                               rtol=1e-6, atol=1e-6)


def test_paged_decode_attention_matches_contiguous():
    """A page table that lays pages out contiguously reproduces the
    contiguous flash-decode kernel on the same cache bytes."""
    from repro.kernels.decode_attention import ops as dops
    B, H, K, hd, page, n = 2, 4, 2, 64, 16, 4
    W = n * page
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(B, H, hd), jnp.float32)
    k = jnp.asarray(rng.randn(B, W, K, hd), jnp.float32)
    v = jnp.asarray(rng.randn(B, W, K, hd), jnp.float32)
    bias = np.zeros((B, W), np.float32)
    bias[:, -page:] = -1e30
    bias = jnp.asarray(bias)
    # pool rows b*n + i hold row b's i-th page
    kp = k.reshape(B * n, page, K, hd)
    vp = v.reshape(B * n, page, K, hd)
    pt = jnp.arange(B * n, dtype=jnp.int32).reshape(B, n)
    out_p = dops.paged_decode_attention(q, kp, vp, pt, bias)
    out_c = dops.decode_attention(q, k, v, bias, block_k=page)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_c),
                               rtol=1e-5, atol=1e-5)
