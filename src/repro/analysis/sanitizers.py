"""Runtime sanitizers: the budgets the static pass can't prove.

averylint's recompile/hostsync checkers catch the *patterns* that cause
compile churn and implicit transfers; these sanitizers measure the
*fact*, on the live engine, and turn it into a hard budget:

  * :class:`RecompileSanitizer` — walks the engine's jit roots (the
    executor's fixed jits, its keyed ``_compiled`` cache, every live
    decoder's draft-model jits) and sums ``_cache_size()`` over them:
    the total number of distinct traces XLA has compiled. ``arm()``
    after warmup snapshots the count; ``check(budget=0)`` raises
    :class:`RecompileBudgetError` if steady state compiled anything new.
  * ``transfer_guard_ctx()`` — ``jax.transfer_guard("disallow")`` as a
    nullable context manager. Under it, any *implicit* device↔host
    transfer in the guarded region raises; explicit ``jnp.asarray`` /
    ``device_get`` stay allowed, which is exactly the engine's
    discipline (the executor jnp-wraps every numpy operand at the stage
    boundary).

Both are engine knobs — ``AveryEngine(debug_recompiles=True)`` arms a
sanitizer the engine checks on every pump after ``arm_sanitizers()``;
``debug_transfers=True`` wraps each decode pump/drain in the guard.
``python -m repro.analysis.sanitizers --smoke`` runs both against a
real in-flight engine (CI's averylint step).
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Iterable, List, Tuple


class RecompileBudgetError(AssertionError):
    """Steady-state decode compiled something new."""


def _unwrap(executor: Any) -> Any:
    """Chase fault-injection wrappers down to the real executor."""
    seen = set()
    while id(executor) not in seen:
        seen.add(id(executor))
        inner = getattr(executor, "_inner", None)
        if inner is None:
            break
        executor = inner
    return executor


def _is_jitted(obj: Any) -> bool:
    return callable(getattr(obj, "_cache_size", None))


def named_jit_roots(engine: Any) -> "Dict[str, Any]":
    """Every jitted callable reachable from the engine, labelled by
    where it hangs: ``executor.<attr>`` for the executor's fixed jits,
    ``executor.<attr>[<key>]`` for keyed compile-cache entries, and
    ``decoder[<qlen>].<attr>`` / ``draft[<qlen>].<attr>`` for each live
    decoder's jits. Re-discovered on every count so jits that appear
    *after* arming (a new cache entry, a new decoder's draft) are
    counted — that is the point. The labels are what the compile
    observatory attributes compile events to."""
    objs: List[Any] = [("executor", _unwrap(engine.executor))]
    for qlen, dec in getattr(engine, "_inflight", {}).items():
        objs.append((f"decoder[{qlen}]", dec))
        draft = _unwrap(getattr(dec, "draft", None))
        if draft is not None:
            objs.append((f"draft[{qlen}]", draft))
    roots: "Dict[str, Any]" = {}
    seen = set()

    def add(label: str, val: Any) -> None:
        if _is_jitted(val) and id(val) not in seen:
            seen.add(id(val))
            roots[label] = val

    for prefix, obj in objs:
        if obj is None:
            continue
        for name, val in vars(obj).items():
            add(f"{prefix}.{name}", val)
            if isinstance(val, dict):
                for k, v in val.items():
                    add(f"{prefix}.{name}[{k}]", v)
            elif isinstance(val, (list, tuple)):
                for i, v in enumerate(val):
                    add(f"{prefix}.{name}[{i}]", v)
    return roots


def jit_roots(engine: Any) -> List[Any]:
    """The engine's jit roots, unlabelled (see :func:`named_jit_roots`)."""
    return list(named_jit_roots(engine).values())


class RecompileSanitizer:
    """Counts distinct compiled traces across the engine's jit roots.

    Once armed it keeps every root it has seen, with the count it had
    when first seen: a retired decoder's jits stay counted, and a jit
    that later decoders share (the greedy pick, the draft stages) is not
    new again each time a fresh decoder brings it back into reach."""

    def __init__(self, engine: Any):
        self.engine = engine
        self.armed_at: "int | None" = None
        self._base: Dict[int, Tuple[Any, int]] = {}   # id -> (jit, count)

    def compile_count(self) -> int:
        return sum(int(f._cache_size()) for f in jit_roots(self.engine))

    def arm(self) -> int:
        """Snapshot after warmup; subsequent compiles are violations."""
        self._base = {}
        self.adopt()
        self.armed_at = self.compile_count()
        return self.armed_at

    def adopt(self) -> None:
        """Take roots not seen yet at their present count. The engine
        calls this when a decoder comes into reach, before it runs."""
        for f in jit_roots(self.engine):
            self._base.setdefault(id(f), (f, int(f._cache_size())))

    def new_compiles(self) -> int:
        if self.armed_at is None:
            return 0
        # a root first seen here has run unseen: all its traces are new
        for f in jit_roots(self.engine):
            self._base.setdefault(id(f), (f, 0))
        return sum(int(f._cache_size()) - n for f, n in self._base.values())

    def check(self, budget: int = 0) -> None:
        n = self.new_compiles()
        if n > budget:
            raise RecompileBudgetError(
                f"steady-state decode compiled {n} new trace(s) "
                f"(budget {budget}); a per-request shape or captured "
                "scalar is churning the jit cache")


def transfer_guard_ctx(enabled: bool = True):
    """``jax.transfer_guard('disallow')`` or a no-op context."""
    if not enabled:
        return contextlib.nullcontext()
    import jax
    return jax.transfer_guard("disallow")


# ---------------------------------------------------------------------------
# CI smoke: both sanitizers against a real in-flight engine
# ---------------------------------------------------------------------------


def _smoke() -> int:
    import numpy as np

    from repro.configs.lisa_mini import CONFIG as PCFG
    from repro.core import DualStreamExecutor, paper_lut, profile as prof
    from repro.core.intent import Intent
    from repro.data import floodseg
    from repro.engine import AveryEngine

    lut = paper_lut()
    params, bns, _ = prof.random_init_system(PCFG, lut=lut)
    execu = DualStreamExecutor(pcfg=PCFG, params=params, bottlenecks=bns,
                               lut=lut, max_new_tokens=3,
                               flash_decode=False)
    # kv_pages pre-sizes the pool and max_prefixes bounds the prefix
    # store: an under-sized pool doubles its backing buffer mid-decode,
    # recompiling every paged stage for the new shape — the first churn
    # class this sanitizer caught (see docs/analysis.md)
    engine = AveryEngine(lut=lut, executor=execu, batching="inflight",
                         max_batch=4, kv_pages=64, max_prefixes=8,
                         debug_recompiles=True, debug_transfers=True)

    rng = np.random.RandomState(7)

    def submit(k: int, sid: int, t: float) -> Any:
        kind = "any" if k % 3 == 2 else "segment"
        b = floodseg.make_batch(rng, 1, kind, augment=False)
        if kind == "any":
            pkt, _ = execu.edge_context(b["images"], sid, t)
            intent = Intent.CONTEXT
        else:
            pkt = execu.edge_insight(b["images"], lut.tiers[k % 2], sid, t)
            intent = Intent.INSIGHT
        return engine.submit_packet(pkt, b["query"], intent, time_s=t)

    # warmup: mixed-intent/mixed-tier traffic through every stage shape
    futs = [submit(i, i, float(i)) for i in range(6)]
    engine.drain()
    warm = engine.arm_sanitizers()

    # steady state: same shape mix; pump with the transfer guard live
    futs = [submit(i, 100 + i, 100.0 + i) for i in range(6)]
    for _ in range(16):
        engine.pump()
    engine.drain()
    assert all(f.done() for f in futs)
    engine.check_sanitizers()           # raises on any new compile
    print(f"[sanitizers] smoke ok: {warm} traces at arm, "
          "0 new compiles, 0 implicit transfers in steady state")
    return 0


def main(argv: "Iterable[str] | None" = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.sanitizers")
    ap.add_argument("--smoke", action="store_true",
                    help="run both sanitizers against a real in-flight "
                         "engine (used by scripts/ci_fast.sh)")
    args = ap.parse_args(list(argv) if argv is not None else None)
    if args.smoke:
        return _smoke()
    ap.print_help()
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
