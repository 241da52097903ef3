"""The repo model under ``averylint``: parsed modules, resolved
imports, a function table, and the traced-region closure.

Every checker consumes the same picture of the tree, built once by the
driver (``repro.analysis.lint``):

  * ``ModuleInfo`` — one parsed file: its AST, dotted module name, the
    local-name -> module import map, and every function/lambda with a
    stable qualname (``Class.method``, ``outer.inner``,
    ``f.<lambda@L12>``).
  * ``RepoModel`` — the whole lint target. Its one non-trivial product
    is the **traced set**: the transitive closure of functions that
    execute under ``jax.jit`` tracing. Seeds are jit decorators, direct
    ``jax.jit(fn)`` / ``jax.jit(lambda ...)`` wraps, the stage-factory
    idiom (``jax.jit(self._stage_fn(...))`` marks the factory's
    returned closures), and the function handed to a stage-naming
    wrapper (``named_stage(name, fn)``, which exists only to be
    jitted); the closure propagates through
    resolvable call edges — same-module calls, ``self.method`` calls,
    and cross-module ``alias.fn`` calls through the import map. The
    host-sync checker asks "is this ``.item()`` inside traced code?"
    against that set instead of guessing from file names.

The model is purely syntactic — nothing is imported or executed, so the
linter runs on a tree that doesn't even have its dependencies
installed.
"""
from __future__ import annotations

import ast
import hashlib
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set, Tuple, Union

FuncNode = Union[ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda]

# decorator / wrapper spellings that put a function under jax tracing
JIT_NAMES = {"jit", "pmap"}
JIT_MODULES = {"jax"}
PALLAS_CALL_NAMES = {"pallas_call"}
# memoisation decorators: a jit built under one of these is built once
# per distinct key, not per call
CACHE_DECORATORS = {"lru_cache", "cache", "cached_property"}
# wrappers that name a function for the jit that takes it
# (``core.spans.named_stage(name, fn)``): ``fn`` is the traced body
NAME_WRAPPERS = {"named_stage"}


@dataclass(frozen=True)
class Finding:
    """One lint finding. The ``fingerprint`` identifies it across line
    drift (baselines key on it): path + code + enclosing symbol + a
    hash of the message, but not the line number."""
    code: str          # e.g. "AV101"
    checker: str       # e.g. "recompile"
    path: str          # lint-root-relative posix path
    line: int
    col: int
    symbol: str        # enclosing qualname, or "<module>"
    message: str

    @property
    def fingerprint(self) -> str:
        digest = hashlib.sha1(self.message.encode()).hexdigest()[:10]
        return f"{self.code}:{self.path}:{self.symbol}:{digest}"

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.code} "
                f"[{self.checker}] {self.message} (in {self.symbol})")

    def as_dict(self) -> Dict[str, object]:
        return {
            "code": self.code, "checker": self.checker, "path": self.path,
            "line": self.line, "col": self.col, "symbol": self.symbol,
            "message": self.message, "fingerprint": self.fingerprint,
        }


@dataclass
class FunctionInfo:
    qualname: str
    node: FuncNode
    module: "ModuleInfo"
    class_name: Optional[str] = None   # nearest enclosing class, if any

    @property
    def name(self) -> str:
        return getattr(self.node, "name", "<lambda>")

    @property
    def is_constructor(self) -> bool:
        return self.name in ("__init__", "__post_init__", "__new__")

    @property
    def is_cached(self) -> bool:
        """Decorated with a memoiser (functools.lru_cache / cache)."""
        for dec in getattr(self.node, "decorator_list", []):
            target = dec.func if isinstance(dec, ast.Call) else dec
            if decorator_name(target) in CACHE_DECORATORS:
                return True
        return False

    def body_nodes(self, include_nested: bool = False
                   ) -> Iterable[ast.AST]:
        """Walk this function's own statements, not those of nested
        function/lambda definitions (each is its own FunctionInfo)."""
        body = (self.node.body if isinstance(self.node.body, list)
                else [self.node.body])
        stack: List[ast.AST] = list(body)
        while stack:
            node = stack.pop()
            yield node
            if not include_nested and isinstance(
                    node, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.Lambda)):
                # still yield decorators/defaults, which run in this scope
                for dec in getattr(node, "decorator_list", []):
                    stack.append(dec)
                continue
            stack.extend(ast.iter_child_nodes(node))

    @property
    def param_names(self) -> Set[str]:
        a = self.node.args
        names = [p.arg for p in
                 a.posonlyargs + a.args + a.kwonlyargs]
        if a.vararg:
            names.append(a.vararg.arg)
        if a.kwarg:
            names.append(a.kwarg.arg)
        return set(names)


def decorator_name(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


def dotted(node: ast.AST) -> Optional[str]:
    """Render a Name/Attribute chain as ``a.b.c``; None otherwise."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


@dataclass
class ModuleInfo:
    path: Path                      # absolute
    rel: str                        # posix path relative to the lint root
    modname: str                    # dotted module name (best effort)
    tree: ast.Module
    functions: Dict[str, FunctionInfo] = field(default_factory=dict)
    # local alias -> dotted module ("jnp" -> "jax.numpy")
    import_alias: Dict[str, str] = field(default_factory=dict)
    # local name -> (module, attr) for from-imports
    from_imports: Dict[str, Tuple[str, str]] = field(default_factory=dict)

    def jax_aliases(self) -> Set[str]:
        """Local names bound to the jax package or its submodules."""
        out = {a for a, m in self.import_alias.items()
               if m == "jax" or m.startswith("jax.")}
        out |= {a for a, (m, _) in self.from_imports.items()
                if m == "jax" or m.startswith("jax.")}
        return out

    def numpy_aliases(self) -> Set[str]:
        return {a for a, m in self.import_alias.items() if m == "numpy"}

    def resolves_to(self, local: str, full: str) -> bool:
        """Does the local name ``local`` refer to ``full`` (e.g.
        ``jit`` -> ``jax.jit``) via a from-import?"""
        got = self.from_imports.get(local)
        return got is not None and f"{got[0]}.{got[1]}" == full


def _modname_for(rel: str) -> str:
    parts = list(Path(rel).with_suffix("").parts)
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    if "repro" in parts:                  # anchor on the package root
        parts = parts[parts.index("repro"):]
    return ".".join(parts) if parts else "<root>"


class _Indexer(ast.NodeVisitor):
    """Collects imports and the function table with qualnames."""

    def __init__(self, mod: ModuleInfo):
        self.mod = mod
        self.scope: List[str] = []      # qualname parts
        self.class_stack: List[str] = []

    def _register(self, node: FuncNode, name: str) -> FunctionInfo:
        qualname = ".".join(self.scope + [name]) if self.scope else name
        info = FunctionInfo(
            qualname=qualname, node=node, module=self.mod,
            class_name=self.class_stack[-1] if self.class_stack else None)
        self.mod.functions[qualname] = info
        return info

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            self.mod.import_alias[alias.asname
                                  or alias.name.split(".")[0]] = (
                alias.name if alias.asname else alias.name.split(".")[0])
            if alias.asname:
                self.mod.import_alias[alias.asname] = alias.name

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module is None:
            return
        for alias in node.names:
            self.mod.from_imports[alias.asname or alias.name] = (
                node.module, alias.name)

    def _visit_func(self, node, name: str) -> None:
        self._register(node, name)
        self.scope.append(name)
        self.generic_visit(node)
        self.scope.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._visit_func(node, node.name)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._visit_func(node, node.name)

    def visit_Lambda(self, node: ast.Lambda) -> None:
        self._visit_func(node, f"<lambda@{node.lineno}>")

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.scope.append(node.name)
        self.class_stack.append(node.name)
        self.generic_visit(node)
        self.class_stack.pop()
        self.scope.pop()


def parse_module(path: Path, rel: str) -> Optional[ModuleInfo]:
    try:
        tree = ast.parse(path.read_text(), filename=str(path))
    except (SyntaxError, UnicodeDecodeError, OSError):
        return None
    mod = ModuleInfo(path=path, rel=rel, modname=_modname_for(rel),
                     tree=tree)
    _Indexer(mod).visit(tree)
    return mod


# ---------------------------------------------------------------------------
# jit-wrap recognition
# ---------------------------------------------------------------------------


def is_jit_callee(func: ast.AST, mod: ModuleInfo) -> bool:
    """Is this Call's ``func`` one of jax's tracing wrappers
    (``jax.jit`` / ``jax.pmap``, a from-imported ``jit``, or
    ``functools.partial(jax.jit, ...)``)?"""
    if isinstance(func, ast.Attribute) and func.attr in JIT_NAMES:
        base = dotted(func.value)
        return base is not None and (
            base in JIT_MODULES
            or mod.import_alias.get(base, "") in JIT_MODULES)
    if isinstance(func, ast.Name):
        return any(mod.resolves_to(func.id, f"jax.{n}") for n in JIT_NAMES)
    if isinstance(func, ast.Call):        # functools.partial(jax.jit, ...)
        name = decorator_name(func.func)
        if name == "partial" and func.args:
            return is_jit_callee(func.args[0], mod)
    return False


def is_pallas_callee(func: ast.AST, mod: ModuleInfo) -> bool:
    if isinstance(func, ast.Attribute) and func.attr in PALLAS_CALL_NAMES:
        return True
    if isinstance(func, ast.Name):
        return (func.id in PALLAS_CALL_NAMES
                or any(mod.resolves_to(func.id, f"jax.experimental.pallas."
                                                f"{n}")
                       for n in PALLAS_CALL_NAMES))
    if isinstance(func, ast.Call):
        name = decorator_name(func.func)
        if name == "partial" and func.args:
            return is_pallas_callee(func.args[0], mod)
    return False


def named_body(node: ast.AST) -> ast.AST:
    """The function a stage-naming wrapper call wraps
    (``named_stage(name, fn)`` -> ``fn``); any other node as it is."""
    while (isinstance(node, ast.Call) and len(node.args) == 2
           and decorator_name(node.func) in NAME_WRAPPERS):
        node = node.args[1]
    return node


def has_jit_decorator(node: FuncNode, mod: ModuleInfo) -> bool:
    for dec in getattr(node, "decorator_list", []):
        if is_jit_callee(dec, mod):               # @jax.jit / @jit
            return True
        if isinstance(dec, ast.Call) and is_jit_callee(dec.func, mod):
            return True                           # @jax.jit(...) form
        if isinstance(dec, ast.Call) and is_jit_callee(dec, mod):
            return True                           # @partial(jax.jit, ...)
    return False


# ---------------------------------------------------------------------------
# the repo model + traced closure
# ---------------------------------------------------------------------------

FuncKey = Tuple[str, str]            # (module rel path, qualname)


class RepoModel:
    def __init__(self, modules: List[ModuleInfo]):
        self.modules: Dict[str, ModuleInfo] = {m.rel: m for m in modules}
        self.by_modname: Dict[str, ModuleInfo] = {}
        for m in modules:
            self.by_modname.setdefault(m.modname, m)
        self._edges: Dict[FuncKey, Set[FuncKey]] = {}
        self._traced: Set[FuncKey] = set()
        self._build()

    # ---- public queries ----

    def is_traced(self, mod: ModuleInfo, qualname: str) -> bool:
        return (mod.rel, qualname) in self._traced

    def traced_functions(self, mod: ModuleInfo) -> List[FunctionInfo]:
        return [f for q, f in sorted(mod.functions.items())
                if (mod.rel, q) in self._traced]

    # ---- construction ----

    def _build(self) -> None:
        seeds: Set[FuncKey] = set()
        for mod in self.modules.values():
            seeds |= self._module_seeds(mod)
            for qual, fn in mod.functions.items():
                self._edges[(mod.rel, qual)] = self._call_edges(mod, fn)
        # propagate: traced functions trace everything they call
        work = list(seeds)
        self._traced = set(seeds)
        while work:
            key = work.pop()
            for callee in self._edges.get(key, ()):
                if callee not in self._traced:
                    self._traced.add(callee)
                    work.append(callee)

    def _module_seeds(self, mod: ModuleInfo) -> Set[FuncKey]:
        seeds: Set[FuncKey] = set()
        for qual, fn in mod.functions.items():
            if has_jit_decorator(fn.node, mod):
                seeds.add((mod.rel, qual))
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if named_body(node) is not node:
                seeds |= self._resolve_jit_arg(mod, node)
                continue
            if not (is_jit_callee(node.func, mod)
                    or is_pallas_callee(node.func, mod)):
                continue
            if not node.args:
                continue
            seeds |= self._resolve_jit_arg(mod, node.args[0])
        return seeds

    def _resolve_jit_arg(self, mod: ModuleInfo, arg: ast.AST
                         ) -> Set[FuncKey]:
        """Functions put under tracing by ``jax.jit(<arg>)``."""
        arg = named_body(arg)
        if isinstance(arg, ast.Lambda):
            key = self._lambda_key(mod, arg)
            return {key} if key else set()
        target = self._resolve_callable(mod, arg)
        if target is not None:
            return {target}
        if isinstance(arg, ast.Call):
            # the stage-factory idiom: jax.jit(self._stage_fn(...)) —
            # whatever closures the factory returns run under tracing
            factory = self._resolve_callable(mod, arg.func)
            if factory is not None:
                return self._factory_returns(factory)
        return set()

    def _lambda_key(self, mod: ModuleInfo, node: ast.Lambda
                    ) -> Optional[FuncKey]:
        for qual, fn in mod.functions.items():
            if fn.node is node:
                return (mod.rel, qual)
        return None

    def _resolve_callable(self, mod: ModuleInfo, node: ast.AST
                          ) -> Optional[FuncKey]:
        """Resolve a Name/Attribute callable reference to a function in
        the model (same module, ``self.method``, ``Class.method``, or a
        cross-module ``alias.fn``)."""
        if isinstance(node, ast.Name):
            hit = self._lookup(mod, node.id)
            if hit:
                return hit
            imp = mod.from_imports.get(node.id)
            if imp:
                other = self.by_modname.get(imp[0])
                if other:
                    return self._lookup(other, imp[1])
            return None
        d = dotted(node)
        if d is None:
            return None
        head, _, tail = d.partition(".")
        if head == "self" and tail and "." not in tail:
            # self.method: try every Class.method match in this module
            for qual in mod.functions:
                if qual.endswith(f".{tail}"):
                    return (mod.rel, qual)
            return None
        if tail:
            # Class.method in this module
            hit = self._lookup(mod, d)
            if hit:
                return hit
            # alias.fn / alias.Class.method through the import map
            imp = mod.from_imports.get(head)
            target_mod = None
            if imp is not None:
                target_mod = self.by_modname.get(f"{imp[0]}.{imp[1]}")
            if target_mod is None and head in mod.import_alias:
                target_mod = self.by_modname.get(mod.import_alias[head])
            if target_mod is not None:
                return self._lookup(target_mod, tail)
        return None

    def _lookup(self, mod: ModuleInfo, qualname: str
                ) -> Optional[FuncKey]:
        if qualname in mod.functions:
            return (mod.rel, qualname)
        # a bare function name may live nested (outer.inner) — prefer
        # the top-level match only
        return None

    def _factory_returns(self, factory: FuncKey) -> Set[FuncKey]:
        mod = self.modules[factory[0]]
        fn = mod.functions[factory[1]]
        out: Set[FuncKey] = set()
        for node in fn.body_nodes():
            if not isinstance(node, ast.Return) or node.value is None:
                continue
            for ref in ast.walk(node.value):
                if isinstance(ref, ast.Name):
                    nested = f"{fn.qualname}.{ref.id}"
                    if nested in mod.functions:
                        out.add((mod.rel, nested))
                elif isinstance(ref, ast.Lambda):
                    key = self._lambda_key(mod, ref)
                    if key:
                        out.add(key)
        return out

    def _call_edges(self, mod: ModuleInfo, fn: FunctionInfo
                    ) -> Set[FuncKey]:
        edges: Set[FuncKey] = set()
        for node in fn.body_nodes():
            if isinstance(node, ast.Call):
                target = self._resolve_callable(mod, node.func)
                if target is not None and target != (mod.rel, fn.qualname):
                    edges.add(target)
                # nested local call: outer.inner
                if isinstance(node.func, ast.Name):
                    nested = f"{fn.qualname}.{node.func.id}"
                    if nested in mod.functions:
                        edges.add((mod.rel, nested))
        return edges
