"""Runtime sanitizers as hard budgets: zero steady-state recompiles and
zero implicit device↔host transfers on the in-flight decode pump."""
import numpy as np
import pytest

from repro.analysis.sanitizers import (RecompileBudgetError,
                                       RecompileSanitizer, jit_roots,
                                       transfer_guard_ctx)
from repro.core import paper_lut
from repro.core.intent import Intent
from repro.engine import AveryEngine

LUT = paper_lut()


def _build_executor():
    from repro.configs.lisa_mini import CONFIG as PCFG
    from repro.core import DualStreamExecutor, profile as prof
    params, bns, _ = prof.random_init_system(PCFG, lut=LUT)
    return DualStreamExecutor(pcfg=PCFG, params=params, bottlenecks=bns,
                              lut=LUT, max_new_tokens=3,
                              flash_decode=False)


@pytest.fixture(scope="module")
def executor():
    return _build_executor()


@pytest.fixture()
def cold_executor():
    # the module-scoped executor's jit caches stay warm across tests;
    # cold-start compile behaviour needs its own
    return _build_executor()


def _engine(executor, **kw):
    # kv_pages pre-sizes the pool: growth mid-decode reallocates the KV
    # buffer and recompiles every paged stage (the churn class the
    # compile-budget test exists to pin)
    kw.setdefault("kv_pages", 64)
    kw.setdefault("max_prefixes", 8)
    return AveryEngine(lut=LUT, executor=executor, batching="inflight",
                       max_batch=4, **kw)


def _submit(engine, executor, k, sid, t):
    """Mixed-intent (Context/Insight) and mixed-tier traffic."""
    from repro.data import floodseg
    rng = np.random.RandomState(1000 + sid)
    kind = "any" if k % 3 == 2 else "segment"
    b = floodseg.make_batch(rng, 1, kind, augment=False)
    if kind == "any":
        pkt, _ = executor.edge_context(b["images"], sid, t)
        return engine.submit_packet(pkt, b["query"], Intent.CONTEXT,
                                    time_s=t)
    pkt = executor.edge_insight(b["images"], LUT.tiers[k % 2], sid, t)
    return engine.submit_packet(pkt, b["query"], Intent.INSIGHT, time_s=t)


# ---- compile budget: zero steady-state recompiles ----


def test_steady_state_compile_budget_is_zero(executor):
    """Warm a mixed-tier/mixed-intent in-flight batch, arm, then pump a
    second mixed batch for N steps: not one new jit trace."""
    engine = _engine(executor, debug_recompiles=True)
    futs = [_submit(engine, executor, i, i, float(i)) for i in range(6)]
    engine.drain()
    armed = engine.arm_sanitizers()
    assert armed and armed > 0              # warmup actually compiled

    futs = [_submit(engine, executor, i, 100 + i, 100.0 + i)
            for i in range(6)]
    for _ in range(20):
        engine.pump()                       # raises on any new compile
    engine.drain()
    assert all(f.done() for f in futs)
    assert engine.stats["new_compiles_since_arm"] == 0


def test_recompile_sanitizer_detects_churn(cold_executor):
    """Negative control: arm *before* warmup and the first real request
    must trip the budget — proving the census actually counts."""
    engine = _engine(cold_executor, debug_recompiles=True)
    engine.arm_sanitizers()
    with pytest.raises(RecompileBudgetError):
        _submit(engine, cold_executor, 0, 0, 0.0)
        for _ in range(50):
            engine.pump()
        engine.drain()


def test_pool_growth_after_arm_trips_the_budget(cold_executor):
    """A pool too small for its traffic grows mid-flight; the decode
    shapes change and the paged stages recompile. Armed after warm-up,
    the sanitizer turns that churn into ``RecompileBudgetError``."""
    import jax.numpy as jnp

    from repro.data import floodseg
    rng = np.random.RandomState(311)

    def submit(engine, i):
        b = floodseg.make_batch(rng, 1, "segment", augment=False)
        pkt = cold_executor.edge_insight(jnp.asarray(b["images"]),
                                         LUT.tiers[0], i, 0.0)
        return engine.submit_packet(pkt, b["query"], Intent.INSIGHT,
                                    time_s=float(i),
                                    session=engine.session(f"uav-{i}"))

    engine = _engine(cold_executor, debug_recompiles=True, kv_pages=2)
    submit(engine, 0)
    engine.drain()
    pages0 = engine.stats["kv_pages_total"]
    assert engine.arm_sanitizers() > 0
    # enough distinct prefixes to outgrow the first prefill's capacity
    # hint: the pool doubles and the paged stages recompile
    with pytest.raises(RecompileBudgetError):
        for i in range(1, 8):
            submit(engine, i)
        engine.drain()
    assert engine.stats["kv_pages_total"] > pages0   # the pool really grew


def test_jit_roots_discovery(cold_executor):
    """The census walks the executor's fixed jits and its keyed compile
    cache (dict values)."""
    engine = _engine(cold_executor)
    roots = jit_roots(engine)
    assert len(roots) >= 5                  # the executor's fixed jits
    assert all(callable(getattr(r, "_cache_size", None)) for r in roots)
    san = RecompileSanitizer(engine)
    before = san.compile_count()
    _submit(engine, cold_executor, 0, 0, 0.0)
    engine.drain()
    assert san.compile_count() > before     # first traffic compiles


def test_census_counts_the_decoders_greedy_pick(executor):
    """A live decoder's jitted greedy pick is a census root, and it
    stays counted after a drain retires the decoder: compiles of it
    after arm are new, a later decoder bringing it back is not."""
    from repro.analysis.sanitizers import named_jit_roots
    engine = _engine(executor, debug_recompiles=True)
    _submit(engine, executor, 2, 0, 0.0)
    (qlen, dec), = engine._inflight.items()
    roots = named_jit_roots(engine)
    assert roots[f"decoder[{qlen}].greedy_pick"] is dec.greedy_pick
    engine.drain()
    assert not engine._inflight
    engine.arm_sanitizers()
    _submit(engine, executor, 2, 1, 1.0)
    engine.drain()
    assert engine.stats["new_compiles_since_arm"] == 0
    # a logits shape no step has had compiles one more trace of the pick
    import jax.numpy as jnp
    dec.greedy_pick(jnp.zeros((3, 5), jnp.float32))
    assert engine.stats["new_compiles_since_arm"] == 1


def test_sanitizer_stats_and_noop_paths():
    """Host-only engine: sanitizer knobs are inert but well-formed."""
    class StubExecutor:
        buckets = (1,)
        max_new_tokens = 1
        num_compiled_stages = 0
    engine = AveryEngine(lut=LUT, executor=StubExecutor(),
                         debug_recompiles=True)
    assert engine.arm_sanitizers() == 0
    engine.check_sanitizers()               # no roots, no violation
    assert engine.stats["new_compiles_since_arm"] == 0
    plain = AveryEngine(lut=LUT, executor=StubExecutor())
    assert plain.arm_sanitizers() is None
    assert "new_compiles_since_arm" not in plain.stats


# ---- transfer guard: zero implicit transfers on the decode pump ----


def test_transfer_guard_actually_guards():
    """Sanity-check the guard semantics this jax provides: raw numpy
    into a jitted fn is an implicit h2d transfer and raises; an
    explicit jnp.asarray is allowed."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda v: v * 2)
    x = np.ones((4,), np.float32)
    f(jnp.asarray(x))                       # warm the trace
    with transfer_guard_ctx(True):
        f(jnp.asarray(x))                   # explicit: fine
        with pytest.raises(Exception):
            f(x)                            # implicit h2d: raises


def test_decode_pump_has_zero_implicit_transfers(executor):
    """The post-warmup pump runs entirely under
    jax.transfer_guard('disallow'): every device boundary crossing on
    the decode path is explicit."""
    engine = _engine(executor, debug_transfers=True)
    futs = [_submit(engine, executor, i, i, float(i)) for i in range(6)]
    for _ in range(20):
        engine.pump()                       # guarded: implicit raises
    engine.drain()                          # guarded drain
    assert all(f.done() for f in futs)
    # steady state stays clean too (fresh mixed batch, same guard)
    futs = [_submit(engine, executor, i, 50 + i, 50.0 + i)
            for i in range(4)]
    for _ in range(20):
        engine.pump()
    engine.drain()
    assert all(f.done() for f in futs)


def test_transfer_guard_with_speculation(executor):
    """The speculative path (draft prefill + paged verify) is also
    transfer-clean under the guard."""
    engine = _engine(executor, debug_transfers=True, speculative=True)
    futs = [_submit(engine, executor, 0, i, float(i)) for i in range(3)]
    for _ in range(30):
        engine.pump()
    engine.drain()
    assert all(f.done() for f in futs)
