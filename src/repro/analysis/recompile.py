"""Recompile lint (AV1xx): compile-cache churn at review time.

PR 1's explicit ``(stage, tier, bucket, qlen)`` compile cache exists
because one stray ``jax.jit`` in a per-request path turns steady-state
serving into a recompile loop. This checker enforces the discipline the
executor follows:

  * **AV101** — ``jax.jit`` / ``jax.pmap`` / ``pl.pallas_call`` invoked
    inside a function body without landing in a cache. Allowed homes:
    module level, a constructor (``__init__`` / ``__post_init__`` — one
    build per object), a memoised function (``functools.lru_cache`` /
    ``cache``), or a call whose result is stored into an attribute /
    subscript slot (``self._fn = jax.jit(...)``,
    ``self._compiled[key] = jax.jit(...)``) directly or through a local
    (``fn = jax.jit(...); cache[key] = fn``). Everything else builds a
    fresh traced wrapper per call — compile churn.
  * **AV102** — a jitted closure (``jax.jit(lambda ...)`` or
    ``jax.jit(local_fn)``) capturing a per-call-varying Python value: a
    parameter or loop variable of the enclosing (non-constructor,
    non-memoised) function. The captured scalar bakes into the trace,
    so every new value is a new compile — the exact churn class the
    executor's keyed cache prevents by putting such values in the key.
"""
from __future__ import annotations

import ast
from typing import List, Optional, Set, Tuple

from repro.analysis.model import (Finding, FunctionInfo, ModuleInfo,
                                  RepoModel, is_jit_callee,
                                  is_pallas_callee, named_body)

CHECKER = "recompile"


def _enclosing_chain(mod: ModuleInfo, fn: FunctionInfo
                     ) -> List[FunctionInfo]:
    """``fn`` plus every enclosing function, outermost last."""
    chain = [fn]
    qual = fn.qualname
    while "." in qual:
        qual = qual.rsplit(".", 1)[0]
        parent = mod.functions.get(qual)
        if parent is not None:
            chain.append(parent)
    return chain


def _stored_names(fn: FunctionInfo) -> Set[str]:
    """Local names whose value is stored into an attribute/subscript or
    returned — the 'this escapes into a cache the caller owns' set."""
    out: Set[str] = set()
    for node in fn.body_nodes():
        if isinstance(node, ast.Assign):
            if any(isinstance(t, (ast.Attribute, ast.Subscript))
                   for t in node.targets) and isinstance(node.value,
                                                         ast.Name):
                out.add(node.value.id)
        elif isinstance(node, ast.Return) and node.value is not None:
            # ``return fn, (...)`` escapes fn to the caller;
            # ``return fn(x)`` returns a result — fn stays per-call
            called = {c.func.id for c in ast.walk(node.value)
                      if isinstance(c, ast.Call)
                      and isinstance(c.func, ast.Name)}
            out |= {n.id for n in ast.walk(node.value)
                    if isinstance(n, ast.Name)} - called
    return out


def _loop_called_names(fn: FunctionInfo) -> Set[str]:
    """Names invoked inside a loop body — a jit bound to one of these is
    amortized over the loop (the training-driver idiom:
    ``step = jax.jit(step_fn); for ...: step(...)``)."""
    out: Set[str] = set()
    for node in fn.body_nodes():
        if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call) and isinstance(sub.func,
                                                            ast.Name):
                    out.add(sub.func.id)
    return out


def _loop_targets(fn: FunctionInfo) -> Set[str]:
    out: Set[str] = set()
    for node in fn.body_nodes():
        if isinstance(node, (ast.For, ast.AsyncFor)):
            for t in ast.walk(node.target):
                if isinstance(t, ast.Name):
                    out.add(t.id)
        elif isinstance(node, (ast.comprehension,)):
            for t in ast.walk(node.target):
                if isinstance(t, ast.Name):
                    out.add(t.id)
    return out


def _free_names(node: ast.AST) -> Set[str]:
    """Names a lambda/def body reads that it does not bind itself."""
    bound: Set[str] = set()
    if isinstance(node, (ast.Lambda, ast.FunctionDef,
                         ast.AsyncFunctionDef)):
        a = node.args
        bound = {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs}
        if a.vararg:
            bound.add(a.vararg.arg)
        if a.kwarg:
            bound.add(a.kwarg.arg)
        body = node.body if isinstance(node.body, list) else [node.body]
    else:
        body = [node]
    reads: Set[str] = set()
    for stmt in body:
        for n in ast.walk(stmt):
            if isinstance(n, ast.Name):
                if isinstance(n.ctx, ast.Load):
                    reads.add(n.id)
                else:
                    bound.add(n.id)
    return reads - bound


def check(mod: ModuleInfo, repo: RepoModel) -> List[Finding]:
    findings: List[Finding] = []
    # map: every Call node -> enclosing function (None = module level)
    for fn, call in _jit_calls(mod):
        kind = ("pl.pallas_call"
                if is_pallas_callee(call.func, mod) else "jax.jit")
        if fn is None:
            continue                       # module level: compiled once
        chain = _enclosing_chain(mod, fn)
        if any(f.is_cached or f.is_constructor for f in chain):
            continue                       # memoised or built-once
        if kind == "pl.pallas_call" and repo.is_traced(mod, fn.qualname):
            # a pallas_call inside a traced function compiles with its
            # enclosing jit — the supported kernel idiom
            continue
        if _is_aot(mod, call):
            continue                       # jax.jit(f).lower(...): AOT
        how = _holding(mod, fn, call)
        if how is None:
            findings.append(Finding(
                code="AV101", checker=CHECKER, path=mod.rel,
                line=call.lineno, col=call.col_offset,
                symbol=fn.qualname,
                message=(f"{kind} built inside a per-call code path; hoist "
                         "to module level, a constructor, or a keyed "
                         "compile cache (see DualStreamExecutor._jitted)")))
            continue
        if how == "attr":
            # a single attribute slot is an unkeyed cache: a captured
            # per-call-varying value churns it
            _check_captured_scalars(mod, fn, call, findings)
    return findings


def _is_aot(mod: ModuleInfo, call: ast.Call) -> bool:
    """``jax.jit(f).lower(...)`` — deliberate ahead-of-time compile."""
    for node in ast.walk(mod.tree):
        if (isinstance(node, ast.Attribute) and node.value is call
                and node.attr in ("lower", "trace", "eval_shape")):
            return True
    return False


def _jit_calls(mod: ModuleInfo):
    """(enclosing FunctionInfo | None, Call) for every jit-like call."""
    nodes_to_fn = {}
    for qual, fn in mod.functions.items():
        for node in fn.body_nodes():
            nodes_to_fn[id(node)] = fn
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and (
                is_jit_callee(node.func, mod)
                or is_pallas_callee(node.func, mod)):
            yield nodes_to_fn.get(id(node)), node


def _loop_spans(fn: FunctionInfo) -> List[Tuple[int, int]]:
    return [(n.lineno, getattr(n, "end_lineno", n.lineno))
            for n in fn.body_nodes()
            if isinstance(n, (ast.For, ast.AsyncFor, ast.While))]


def _holding(mod: ModuleInfo, fn: FunctionInfo, call: ast.Call
             ) -> Optional[str]:
    """How this in-body jit's result is legitimately held: 'attr' /
    'subscript' (cache slot), 'return' (caller owns it), 'local'
    (bound once outside any loop and amortized over a loop), or None —
    nothing holds it, it's per-call churn."""
    stored = _stored_names(fn)
    loop_called = _loop_called_names(fn)
    spans = _loop_spans(fn)
    in_loop = any(lo <= call.lineno <= hi for lo, hi in spans)
    for node in fn.body_nodes():
        if isinstance(node, ast.Assign) and _contains(node.value, call):
            for t in node.targets:
                if isinstance(t, ast.Subscript):
                    return "subscript"     # cache[key] = jax.jit(...)
                if isinstance(t, ast.Attribute):
                    return "attr"          # self._fn = jax.jit(...)
                if isinstance(t, ast.Name) and not in_loop:
                    if t.id in stored:
                        return "return"    # escapes to the caller
                    if t.id in loop_called:
                        return "local"     # built once, looped over
        elif isinstance(node, ast.Return) and node.value is not None \
                and _contains(node.value, call):
            return "return"
    return None


def _contains(tree: ast.AST, needle: ast.AST) -> bool:
    return any(n is needle for n in ast.walk(tree))


def _check_captured_scalars(mod: ModuleInfo, fn: FunctionInfo,
                            call: ast.Call,
                            findings: List[Finding]) -> None:
    """AV102: the jitted closure captures a per-call-varying local."""
    if not call.args:
        return
    arg = named_body(call.args[0])
    target: Optional[ast.AST] = None
    if isinstance(arg, ast.Lambda):
        target = arg
    elif isinstance(arg, ast.Name):
        nested = mod.functions.get(f"{fn.qualname}.{arg.id}")
        if nested is not None:
            target = nested.node
    if target is None:
        return
    varying = fn.param_names | _loop_targets(fn)
    captured = sorted(_free_names(target) & varying)
    if captured:
        findings.append(Finding(
            code="AV102", checker=CHECKER, path=mod.rel,
            line=call.lineno, col=call.col_offset, symbol=fn.qualname,
            message=(f"jitted closure captures per-call-varying "
                     f"value(s) {captured} from {fn.name}(); each new "
                     "value bakes a new trace — key the compile cache "
                     "on them instead")))
