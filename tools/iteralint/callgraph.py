"""Cross-module call graph + trace-root detection over the project.

Functions are keyed `module:Qual.Name` (methods include the class,
lambdas get synthetic `<lambda L<line>>` names under their enclosing
function). Edges are best-effort static resolution of call sites:

  * bare names -> sibling/module-level defs, or `from repro.x import y`
    imports;
  * `alias.attr(...)` -> first-party module functions via the import
    table (`from repro.models import transformer` -> transformer.prefill);
  * `self.attr(...)` -> methods of the enclosing class.

Trace roots are functions handed to jax tracing machinery: `jax.jit` /
`jax.pmap` (kind "jit", with any static_argnums/static_argnames
captured for the recompile analyzer), `shard_map` / `tp_shard_map`
(kind "shard_map"), `pl.pallas_call` kernels (kind "pallas"), and the
`jax.lax` control-flow / `jax.vmap`-family combinators whose function
arguments are always traced (kind "trace"). Decorator and call-site
forms both count, including `partial(jax.jit, ...)`.

Nested defs and lambdas are conservatively assumed to execute when
their enclosing function does (they are closure helpers in this
codebase), so tracing propagates into them.
"""
from __future__ import annotations

import ast
import dataclasses

from tools.iteralint.framework import dotted_name, import_table

JIT_TARGETS = {"jax.jit", "jax.pmap"}
SHARD_TARGETS = {
    "jax.shard_map",
    "jax.experimental.shard_map.shard_map",
    "jax.sharding.shard_map",
    "repro.runtime.shardctx.tp_shard_map",
}
PALLAS_TARGETS = {"jax.experimental.pallas.pallas_call"}
TRACE_TARGETS = {
    "jax.lax.scan", "jax.lax.while_loop", "jax.lax.cond",
    "jax.lax.fori_loop", "jax.lax.switch", "jax.lax.map",
    "jax.lax.associative_scan", "jax.vmap", "jax.grad",
    "jax.value_and_grad", "jax.checkpoint", "jax.remat",
}
PARTIAL_TARGETS = {"functools.partial"}


@dataclasses.dataclass
class FuncInfo:
    qual: str                   # module:Qual.Name
    sf: object                  # SourceFile
    node: ast.AST               # FunctionDef / Lambda
    cls: str | None             # enclosing class name, if a method
    parent: str | None          # enclosing function qual, if nested


@dataclasses.dataclass
class JitSite:
    sf: object
    call: ast.AST               # the jax.jit(...) call or decorated def
    wrapped_qual: str | None    # graph node for the wrapped function
    wrapped_ast: ast.AST | None  # Lambda / FunctionDef when in-file
    static_argnums: list[int]
    static_argnames: list[str]
    enclosing: str | None       # qual of the function containing the site


def _const_list(node, typ):
    if node is None:
        return []
    if isinstance(node, ast.Constant) and isinstance(node.value, typ):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, typ)]
    return []


class CallGraph:

    def __init__(self, project):
        self.project = project
        self.functions: dict[str, FuncInfo] = {}
        self.edges: dict[str, set[str]] = {}
        self.roots: dict[str, set[str]] = {}    # qual -> wrapper kinds
        self.jit_sites: list[JitSite] = []
        for sf in project.files.values():
            self._index(sf)
        for sf in project.files.values():
            self._scan(sf)
        self._traced = None

    # -- indexing ----------------------------------------------------------

    def _index(self, sf):
        mod = sf.module

        def visit(node, quals, cls, parent):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    visit(child, quals + [child.name], child.name, parent)
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    q = f"{mod}:" + ".".join(quals + [child.name])
                    self.functions[q] = FuncInfo(q, sf, child, cls, parent)
                    if parent is not None:      # nested def runs w/ parent
                        self._edge(parent, q)
                    visit(child, quals + [child.name], cls, q)
                elif isinstance(child, ast.Lambda):
                    q = (f"{mod}:" + ".".join(
                        quals + [f"<lambda L{child.lineno}>"]))
                    self.functions[q] = FuncInfo(q, sf, child, cls, parent)
                    if parent is not None:
                        self._edge(parent, q)
                    visit(child, quals + [f"<lambda L{child.lineno}>"],
                          cls, q)
                else:
                    visit(child, quals, cls, parent)

        visit(sf.tree, [], None, None)

    def _edge(self, a, b):
        self.edges.setdefault(a, set()).add(b)

    # -- call resolution ---------------------------------------------------

    def _resolve(self, sf, caller: FuncInfo | None, func: ast.AST):
        mod = sf.module
        table = sf.imports
        if isinstance(func, ast.Name):
            n = func.id
            if caller is not None:              # sibling nested def
                q = f"{caller.qual}.{n}"
                if q in self.functions:
                    return q
            if f"{mod}:{n}" in self.functions:
                return f"{mod}:{n}"
            tgt = table.get(n)
            if tgt and tgt.startswith("repro."):
                m, _, sym = tgt.rpartition(".")
                if f"{m}:{sym}" in self.functions:
                    return f"{m}:{sym}"
            return None
        if isinstance(func, ast.Attribute):
            if isinstance(func.value, ast.Name) and func.value.id == "self" \
                    and caller is not None and caller.cls:
                q = f"{mod}:{caller.cls}.{func.attr}"
                if q in self.functions:
                    return q
            dn = dotted_name(func)
            if dn is None:
                return None
            head, _, rest = dn.partition(".")
            tgt = table.get(head)
            if tgt and rest:
                full = f"{tgt}.{rest}"
                if full.startswith("repro."):
                    m, _, sym = full.rpartition(".")
                    if f"{m}:{sym}" in self.functions:
                        return f"{m}:{sym}"
        return None

    def resolve_target(self, sf, node: ast.AST) -> str | None:
        """Fully-qualified dotted target of a Name/Attribute through the
        module's import table ('jax.jit' for `jit` imported from jax)."""
        dn = dotted_name(node)
        if dn is None:
            return None
        head, _, rest = dn.partition(".")
        tgt = sf.imports.get(head)
        if tgt is None:
            return None
        return tgt + ("." + rest if rest else "")

    # -- scanning ----------------------------------------------------------

    def _scan(self, sf):
        if not hasattr(sf, "imports"):
            sf.imports = import_table(sf.tree)
        by_node = {id(fi.node): fi for fi in self.functions.values()
                   if fi.sf is sf}

        def enclosing(stack):
            for n in reversed(stack):
                fi = by_node.get(id(n))
                if fi is not None:
                    return fi
            return None

        stack = []

        def walk(node):
            stack.append(node)
            caller = enclosing(stack)
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.Call):
                    self._handle_call(sf, caller, child)
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    self._handle_decorators(sf, caller, child)
                walk(child)
            stack.pop()

        # `sf.imports` must exist before resolve calls below
        walk(sf.tree)

    def _mark_root(self, qual, kind):
        if qual is not None:
            self.roots.setdefault(qual, set()).add(kind)

    def _fn_arg_qual(self, sf, caller, arg):
        """Graph node for a function-valued argument (Name or Lambda)."""
        if isinstance(arg, ast.Lambda):
            fi = next((f for f in self.functions.values()
                       if f.sf is sf and f.node is arg), None)
            return fi.qual if fi else None
        if isinstance(arg, (ast.Name, ast.Attribute)):
            return self._resolve(sf, caller, arg)
        if isinstance(arg, ast.Call):        # partial(f, ...) etc.
            tgt = self.resolve_target(sf, arg.func)
            if tgt in PARTIAL_TARGETS and arg.args:
                return self._fn_arg_qual(sf, caller, arg.args[0])
        return None

    def _handle_call(self, sf, caller, call: ast.Call):
        tgt = self.resolve_target(sf, call.func)
        if tgt in JIT_TARGETS:
            self._record_jit(sf, caller, call, call.args[0]
                             if call.args else None, call.keywords)
            return
        if tgt in PARTIAL_TARGETS and call.args:
            inner = self.resolve_target(sf, call.args[0])
            if inner in JIT_TARGETS:
                # partial(jax.jit, static_argnames=...) used as decorator
                # or wrapper factory; statics come from the partial.
                self._record_jit(sf, caller, call,
                                 call.args[1] if len(call.args) > 1
                                 else None, call.keywords)
                return
        if tgt in SHARD_TARGETS or (tgt is None and isinstance(
                call.func, ast.Name) and call.func.id == "shard_map"):
            for a in list(call.args[:1]) + [k.value for k in call.keywords
                                            if k.arg == "f"]:
                self._mark_root(self._fn_arg_qual(sf, caller, a),
                                "shard_map")
            return
        if tgt in PALLAS_TARGETS:
            if call.args:
                self._mark_root(self._fn_arg_qual(sf, caller,
                                                  call.args[0]), "pallas")
            return
        if tgt in TRACE_TARGETS:
            for a in call.args:
                q = self._fn_arg_qual(sf, caller, a)
                if q is not None:
                    self._mark_root(q, "trace")
            return
        if caller is not None:
            q = self._resolve(sf, caller, call.func)
            if q is not None:
                self._edge(caller.qual, q)

    def _record_jit(self, sf, caller, call, fn_arg, keywords):
        nums = names = None
        for kw in keywords:
            if kw.arg == "static_argnums":
                nums = _const_list(kw.value, int)
            elif kw.arg == "static_argnames":
                names = _const_list(kw.value, str)
        qual = self._fn_arg_qual(sf, caller, fn_arg) \
            if fn_arg is not None else None
        wrapped_ast = None
        if isinstance(fn_arg, ast.Lambda):
            wrapped_ast = fn_arg
        elif qual in self.functions:
            wrapped_ast = self.functions[qual].node
        self._mark_root(qual, "jit")
        self.jit_sites.append(JitSite(
            sf, call, qual, wrapped_ast, nums or [], names or [],
            caller.qual if caller else None))

    def _handle_decorators(self, sf, caller, fn: ast.FunctionDef):
        fi = next((f for f in self.functions.values()
                   if f.sf is sf and f.node is fn), None)
        if fi is None:
            return
        for dec in fn.decorator_list:
            if isinstance(dec, ast.Call):
                tgt = self.resolve_target(sf, dec.func)
                if tgt in JIT_TARGETS:
                    self._mark_root(fi.qual, "jit")
                    self.jit_sites.append(JitSite(
                        sf, fn, fi.qual, fn,
                        *self._statics(dec.keywords), fi.qual))
                elif tgt in PARTIAL_TARGETS and dec.args and \
                        self.resolve_target(sf, dec.args[0]) in JIT_TARGETS:
                    self._mark_root(fi.qual, "jit")
                    self.jit_sites.append(JitSite(
                        sf, fn, fi.qual, fn,
                        *self._statics(dec.keywords), fi.qual))
                elif tgt in SHARD_TARGETS:
                    self._mark_root(fi.qual, "shard_map")
            else:
                tgt = self.resolve_target(sf, dec)
                if tgt in JIT_TARGETS:
                    self._mark_root(fi.qual, "jit")
                    self.jit_sites.append(JitSite(
                        sf, fn, fi.qual, fn, [], [], fi.qual))

    @staticmethod
    def _statics(keywords):
        nums = names = None
        for kw in keywords:
            if kw.arg == "static_argnums":
                nums = _const_list(kw.value, int)
            elif kw.arg == "static_argnames":
                names = _const_list(kw.value, str)
        return (nums or [], names or [])

    # -- reachability ------------------------------------------------------

    def reachable_from(self, seeds) -> set[str]:
        seen = set()
        work = [s for s in seeds if s in self.functions]
        while work:
            q = work.pop()
            if q in seen:
                continue
            seen.add(q)
            work.extend(self.edges.get(q, ()))
        return seen

    def traced(self) -> set[str]:
        """Functions reachable from any trace root (jit / shard_map /
        pallas / lax combinators)."""
        if self._traced is None:
            self._traced = self.reachable_from(self.roots)
        return self._traced

    def roots_of_kind(self, kind: str) -> set[str]:
        return {q for q, kinds in self.roots.items() if kind in kinds}
