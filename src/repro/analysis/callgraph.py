"""Import-aware call graph over the scanned modules, rooted at jit entry
points.

The **traced set** — every function that can run under a JAX trace — is the
reachability closure of:

* functions decorated with ``jax.jit`` (directly or via
  ``functools.partial(jax.jit, ...)``),
* function references passed to a tracing higher-order primitive
  (``jax.jit``, ``jax.vmap`` / ``pmap``, ``lax.scan`` / ``while_loop`` /
  ``fori_loop`` / ``cond`` / ``switch``, ``shard_map``, ``jax.checkpoint`` /
  ``remat``, ``jax.grad`` / ``value_and_grad``), including lambdas,

followed through ordinary call edges, ``functools.partial`` bindings, and
function references passed as plain arguments (higher-order use). Name
resolution walks lexical scopes (nested defs), ``self.``/``cls.`` methods of
the enclosing class, module-level names, then imports.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .astutil import SourceModule

TRACING_HOFS = frozenset(
    {
        "jax.jit",
        "jax.vmap",
        "jax.pmap",
        "jax.lax.scan",
        "jax.lax.while_loop",
        "jax.lax.fori_loop",
        "jax.lax.cond",
        "jax.lax.switch",
        "jax.lax.map",
        "jax.lax.associative_scan",
        "jax.checkpoint",
        "jax.remat",
        "jax.grad",
        "jax.value_and_grad",
        "jax.shard_map",
        "jax.experimental.shard_map.shard_map",
        "jax.experimental.pallas.pallas_call",
    }
)

_PARTIAL = "functools.partial"


class FunctionInfo:
    """One function/lambda definition found in a scanned module."""

    def __init__(
        self,
        qualname: str,
        node: ast.AST,
        module: SourceModule,
        scope_chain: List[str],
        class_qualname: Optional[str] = None,
    ):
        self.qualname = qualname
        self.node = node
        self.module = module
        # enclosing function qualnames, outermost first (for bare-name lookup)
        self.scope_chain = scope_chain
        self.class_qualname = class_qualname
        self.is_jit_root = False
        self.root_cause: Optional[str] = None

    @property
    def line(self) -> int:
        return self.node.lineno


class CallGraph:
    def __init__(self, modules: List[SourceModule]):
        self.modules = modules
        self.functions: Dict[str, FunctionInfo] = {}
        # scope qualname -> {bare name -> member qualname}
        self._members: Dict[str, Dict[str, str]] = {}
        self.edges: Dict[str, Set[str]] = {}
        self._index()
        self._build_edges_and_roots()
        self.traced: Set[str] = self._reach()

    # -- indexing ----------------------------------------------------------
    def _index(self) -> None:
        for mod in self.modules:
            self._index_scope(mod, mod.tree.body, mod.modname, [], None)

    def _index_scope(
        self,
        mod: SourceModule,
        body: List[ast.stmt],
        scope: str,
        chain: List[str],
        class_qual: Optional[str],
    ) -> None:
        members = self._members.setdefault(scope, {})
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{scope}.{stmt.name}"
                members[stmt.name] = qual
                self.functions[qual] = FunctionInfo(
                    qual, stmt, mod, chain + [scope], class_qual
                )
                self._index_scope(mod, stmt.body, qual, chain + [scope], None)
            elif isinstance(stmt, ast.ClassDef):
                qual = f"{scope}.{stmt.name}"
                members[stmt.name] = qual
                self._index_scope(mod, stmt.body, qual, chain + [scope], qual)

    # -- resolution --------------------------------------------------------
    def _lookup(self, info: FunctionInfo, expr: ast.expr) -> Optional[str]:
        """Resolve a function-reference expression to an indexed qualname."""
        if isinstance(expr, ast.Name):
            for scope in reversed(info.scope_chain + [info.qualname]):
                qual = self._members.get(scope, {}).get(expr.id)
                if qual in self.functions:
                    return qual
            dotted = info.module.imports.get(expr.id)
            if dotted in self.functions:
                return dotted
            return None
        if isinstance(expr, ast.Attribute):
            if isinstance(expr.value, ast.Name) and expr.value.id in (
                "self",
                "cls",
            ):
                # method body: self.foo -> a member of the owning class (the
                # method's own class, or an enclosing one for nested defs)
                owners = [info.class_qualname] + [
                    f.class_qualname
                    for f in (
                        self.functions.get(s)
                        for s in reversed(info.scope_chain)
                    )
                    if f is not None
                ]
                for owner in owners:
                    if not owner:
                        continue
                    qual = self._members.get(owner, {}).get(expr.attr)
                    if qual in self.functions:
                        return qual
            dotted = info.module.resolve_name(expr)
            if dotted in self.functions:
                return dotted
            return None
        return None

    def resolve_dotted(self, info: FunctionInfo, expr: ast.expr) -> Optional[str]:
        return info.module.resolve_name(expr)

    # -- edges + roots -----------------------------------------------------
    def _mark_root(self, qual: Optional[str], cause: str) -> None:
        if qual is not None and qual in self.functions:
            f = self.functions[qual]
            f.is_jit_root = True
            f.root_cause = f.root_cause or cause

    def _func_args(self, info: FunctionInfo, call: ast.Call) -> List[str]:
        """Indexed functions referenced by this call's arguments (lambdas
        included via their synthetic qualnames)."""
        out = []
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            if isinstance(arg, (ast.Name, ast.Attribute)):
                qual = self._lookup(info, arg)
                if qual is not None:
                    out.append(qual)
            elif isinstance(arg, ast.Lambda):
                out.append(self._lambda_qual(info, arg))
            elif isinstance(arg, ast.Call):
                # functools.partial(f, ...) used as a function argument
                dotted = self.resolve_dotted(info, arg)
                if dotted == _PARTIAL and arg.args:
                    inner = arg.args[0]
                    if isinstance(inner, (ast.Name, ast.Attribute)):
                        qual = self._lookup(info, inner)
                        if qual is not None:
                            out.append(qual)
        return out

    def _lambda_qual(self, info: FunctionInfo, node: ast.Lambda) -> str:
        qual = f"{info.qualname}.<lambda:{node.lineno}:{node.col_offset}>"
        if qual not in self.functions:
            self.functions[qual] = FunctionInfo(
                qual, node, info.module, info.scope_chain + [info.qualname]
            )
            self._visit_function(self.functions[qual], [node.body])
        return qual

    def _decorator_jits(self, info: FunctionInfo) -> Optional[str]:
        node = info.node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return None
        for dec in node.decorator_list:
            if isinstance(dec, (ast.Name, ast.Attribute)):
                if self.resolve_dotted(info, dec) in TRACING_HOFS:
                    return "decorator"
            elif isinstance(dec, ast.Call):
                dotted = self.resolve_dotted(info, dec)
                if dotted in TRACING_HOFS:
                    return "decorator"
                if dotted == _PARTIAL and dec.args:
                    first = dec.args[0]
                    if (
                        isinstance(first, (ast.Name, ast.Attribute))
                        and self.resolve_dotted(info, first) in TRACING_HOFS
                    ):
                        return "decorator"
        return None

    def _build_edges_and_roots(self) -> None:
        for qual in list(self.functions):
            info = self.functions[qual]
            if isinstance(info.node, ast.Lambda):
                continue  # visited at creation
            if self._decorator_jits(info):
                self._mark_root(qual, "jit decorator")
            self._visit_function(info, info.node.body)

    def _visit_function(self, info: FunctionInfo, body) -> None:
        edges = self.edges.setdefault(info.qualname, set())
        for node in ast.walk(ast.Module(body=list(body), type_ignores=[])):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # nested defs are indexed separately; still record the
                # lexical edge so closures stay reachable from their parent
                qual = f"{info.qualname}.{node.name}"
                if qual in self.functions:
                    edges.add(qual)
                continue
            if not isinstance(node, ast.Call):
                continue
            callee = self._lookup(info, node.func)
            if callee is not None:
                edges.add(callee)
            for qual in self._func_args(info, node):
                edges.add(qual)
            dotted = self.resolve_dotted(info, node.func)
            if dotted in TRACING_HOFS:
                for qual in self._func_args(info, node):
                    self._mark_root(qual, f"passed to {dotted}")
            elif dotted == _PARTIAL and node.args:
                first = node.args[0]
                if (
                    isinstance(first, (ast.Name, ast.Attribute))
                    and self.resolve_dotted(info, first) in TRACING_HOFS
                    and len(node.args) > 1
                ):
                    arg1 = node.args[1]
                    if isinstance(arg1, (ast.Name, ast.Attribute)):
                        self._mark_root(
                            self._lookup(info, arg1), "partial(jit, fn)"
                        )

    # -- reachability ------------------------------------------------------
    def _reach(self) -> Set[str]:
        roots = [q for q, f in self.functions.items() if f.is_jit_root]
        seen: Set[str] = set()
        stack = list(roots)
        while stack:
            q = stack.pop()
            if q in seen:
                continue
            seen.add(q)
            stack.extend(self.edges.get(q, ()))
        return seen

    def is_traced(self, qualname: str) -> bool:
        return qualname in self.traced

    def traced_functions(self) -> List[Tuple[str, FunctionInfo]]:
        return sorted(
            (q, f) for q, f in self.functions.items() if q in self.traced
        )
