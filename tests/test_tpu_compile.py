"""The serving path's Pallas kernels compile for a TPU v5e at lisa-7b
widths (hd 128, 32 kv heads, page 16, bf16 pool, up to 16 slots, a
4-token verify chunk), and the paged decode kernel at Phi-4-mini widths
too (24 query heads over 8 kv heads, 8 slots, 16 or 80 pages a row).

The chip is described, not attached: the TPU compiler lowers each kernel
with ``interpret=False`` for one device of a ``v5e:2x2`` topology, which
refuses what Mosaic would refuse on the chip (block shapes off the
tiling, VMEM overuse). The topology is described inside a module-scoped
fixture only -- never at import -- so every pytest worker collects the
same tests and only the worker that runs them loads the TPU library."""
import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import pytest

HD, KV_HEADS, PAGE, SLOTS, CHUNK = 128, 32, 16, 16, 4
POOL_PAGES, TABLE_PAGES = 128, 14          # 13 prefix pages + 1 decode
DRAFT_WIDTH = 211                          # 196 CLIP + 8 query + 4 + 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _kernels(name):
    return importlib.import_module(f"repro.kernels.{name}.{name}")


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _cases():
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    bh = SLOTS * KV_HEADS                  # MHA: one query head per kv head
    pool = (KV_HEADS, POOL_PAGES, PAGE, HD)
    # the paged decode kernel reads the stored pool, page and head merged
    rows_pool = (POOL_PAGES, PAGE * KV_HEADS, HD)
    da = "decode_attention"
    return {
        "paged_decode_call": (da, "paged_decode_call", {}, [
            ((SLOTS, KV_HEADS, 1, HD), bf), (rows_pool, bf),
            (rows_pool, bf), ((SLOTS, TABLE_PAGES), i32),
            ((SLOTS, TABLE_PAGES * PAGE), f32)]),
        "paged_verify_call": (da, "paged_verify_call", {"group": 1}, [
            ((bh, CHUNK, HD), bf), (pool, bf), (pool, bf),
            ((SLOTS, TABLE_PAGES), i32),
            ((SLOTS, CHUNK, TABLE_PAGES * PAGE), f32)]),
        "decode_call": (da, "decode_call", {"group": 1,
                                            "block_k": DRAFT_WIDTH}, [
            ((bh, 1, HD), bf), ((bh, DRAFT_WIDTH, HD), bf),
            ((bh, DRAFT_WIDTH, HD), bf), ((SLOTS, DRAFT_WIDTH), f32)]),
        "flash_call": ("flash_attention", "flash_call", {
            "causal": True, "block_q": 128, "block_k": 128,
            "valid_len": 204}, [
            ((KV_HEADS, 256, HD), bf), ((KV_HEADS, 256, HD), bf),
            ((KV_HEADS, 256, HD), bf)]),
    }


@pytest.mark.parametrize("kernel", ["paged_decode_call", "paged_verify_call",
                                    "decode_call", "flash_call"])
def test_kernel_compiles_for_v5e_at_lisa7b_widths(one_chip, kernel):
    package, fn_name, kwargs, shapes = _cases()[kernel]
    fn = functools.partial(getattr(_kernels(package), fn_name),
                           interpret=False, **kwargs)
    compiled = _compile(one_chip, fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("pages,pool_pages", [(16, 255), (80, 641)])
def test_paged_decode_compiles_for_v5e_at_phi4mini_widths(one_chip, pages,
                                                          pool_pages):
    """8 slots, 24 query heads over 8 kv heads, a bf16 pool. 16 pages a
    row (14 of prefix, 2 of answer) in a 255-page pool: the whole row is
    one block of pages, double-buffered in VMEM. 80 pages a row, a long
    answer: three blocks of 27, the largest the VMEM budget gives, with
    their float32 copies and per-head temporaries."""
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    slots, heads, kv_heads = 8, 24, 8
    pool = ((pool_pages, PAGE * kv_heads, HD), bf)
    fn = functools.partial(_kernels("decode_attention").paged_decode_call,
                           interpret=False)
    compiled = _compile(one_chip, fn,
                        ((slots, kv_heads, heads // kv_heads, HD), bf),
                        pool, pool, ((slots, pages), i32),
                        ((slots, pages * PAGE), f32))
    assert "tpu_custom_call" in compiled.as_text()


def test_latent_expert_share_decode_compiles_for_v5e_at_deepseek_v3_widths(
        one_chip):
    """Two MoE layers of DeepSeek-V3's widths against a latent page pool
    (8 slots, 16 pages a row, 255 pages): latent attention, 256-way
    group-limited routing and 8 held experts of 2048. A held expert is
    sliced by (layer, expert) inside its own branch, so no program
    copies a layer's whole stack of experts (704 MB) before the
    branches run."""
    from repro.configs import get_config
    from repro.models import stack
    dsv3 = get_config("deepseek-v3-671b")
    llm = dsv3.replace(num_layers=2, moe=dataclasses.replace(
        dsv3.moe, first_k_dense=0, dispatch="dropless", held_experts=8))
    spec = stack.layer_groups(llm)[0]
    slots, pages, pool_pages = 8, 16, 255
    m = llm.mla
    params = jax.eval_shape(lambda: stack.init_group(
        jax.random.PRNGKey(0), llm, spec))
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    pool = {"ckv": jax.ShapeDtypeStruct(
                (2, pool_pages, PAGE, m.kv_lora_rank), bf),
            "krope": jax.ShapeDtypeStruct(
                (2, pool_pages, PAGE, m.qk_rope_head_dim), bf)}

    def step(p, pool, x, pos, pt, wp, wo, mask):
        return stack.group_decode_paged(p, llm, spec, x, pos, pool, pt, wp,
                                        wo, mask)

    shard = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    args = [shard(params), shard(pool)] + [
        jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in [
            ((slots, 1, llm.d_model), bf), ((slots, 1), i32),
            ((slots, pages), i32), ((slots,), i32), ((slots,), i32),
            ((slots, pages * PAGE), f32)]]
    compiled = jax.jit(step).lower(*args).compile()
    expert_stack = 3 * 8 * llm.d_model * llm.moe.d_ff_expert * 2
    assert compiled.memory_analysis().temp_size_in_bytes < expert_stack / 4
    assert " conditional(" in compiled.as_text()
