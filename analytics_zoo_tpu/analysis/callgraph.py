"""deepcheck layer 1: a project-wide call graph with context propagation.

zoolint's PR-4 rule families and the PR-6 dataflow layer are strictly
*intraprocedural*: a ``.item()`` inside a jitted function fires, the
same ``.item()`` one helper-call deep is invisible. Every XLA-shaped
property this repo cares about crosses function boundaries -- whether a
helper reached from a jitted function concretizes a tracer, whether the
decode->dispatch->finalize serving hot path blocks on a host sync,
whether an f32 constant flows into a bf16 kernel -- so this module
builds the missing piece: a call graph over the one-parse
:class:`~analytics_zoo_tpu.analysis.core.Project`, with **contexts**
propagated along its edges.

Resolution (all same-parse, no imports executed). A call site resolves
when its callee is

- a function/method defined in an enclosing lexical scope or at module
  level of the same file (``helper(x)``);
- ``self.method(...)`` / ``cls.method(...)`` on the enclosing class
  (single definition; ambiguous names never resolve);
- ``mod.fn(...)`` where ``mod`` is an intra-package import of a scanned
  module (``from analytics_zoo_tpu.serving import worker`` /
  ``import ... as w`` / relative forms), or a symbol imported from one
  (``from .queues import _encode``);
- one level of **alias indirection** through the
  :mod:`~analytics_zoo_tpu.analysis.dataflow` scope machinery:
  ``f = helper`` / ``f = jax.jit(helper)`` / ``self._step =
  jax.jit(step)`` followed by ``f(...)`` / ``self._step(...)``
  (jit/pmap/shard_map/partial wrappers are unwrapped).

Anything else -- dict dispatch, ``*args`` forwarding, attribute calls on
arbitrary objects, names assigned more than once -- is **conservatively
unknown and never produces a finding**.

Contexts propagated caller -> callee along resolved edges:

``jit`` / ``collective``
    Roots are the PR-4 jitted-function detection
    (:func:`~analytics_zoo_tpu.analysis.trace_hazards.jitted_functions`;
    ``shard_map`` roots also carry ``collective``). Alongside the
    context, per-parameter *tracer taint* flows: a callee parameter is
    traced iff some resolved jit-context call site passes it a
    tracer-derived argument.

``hotpath``
    The serving hot path. Roots are the worker pipeline stages
    (methods of ``ServingWorker`` in the decode/dispatch seams) and
    ``InferenceModel.predict_async``; a module may declare extra roots
    with ``ZOOLINT_HOT_PATH = ("fn", "Class.method", ...)``. The
    finalize seam (``_finalize_*`` / ``finalize_loop``) is a *barrier*:
    hotpath context never enters it -- materializing results there is
    the engine's one sanctioned host sync. Per-parameter *device taint*
    flows along hotpath edges (arguments proven device-derived:
    ``predict_async`` results, jit-wrapped call results, ``jnp`` ops,
    ``device_put``).

Nested defs inherit their enclosing function's contexts (the enclosing
body can call them through trampolines the resolver cannot see), and
their tracer walk sees enclosing traced parameters as free variables.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from analytics_zoo_tpu.analysis.core import Project, SourceFile
from analytics_zoo_tpu.analysis.dataflow import Scope
from analytics_zoo_tpu.analysis.trace_hazards import (
    _STATIC_ATTRS, _is_tracer_expr, _static_params, jitted_functions)

CTX_JIT = "jit"
CTX_COLLECTIVE = "collective"
CTX_HOTPATH = "hotpath"

# structural hot-path roots: the serving worker's decode/dispatch
# stages (the threads that must never stall on device results) and the
# inference engine's async dispatch entry
_HOT_STAGE_METHODS = {
    "ServingWorker": {"_decode_stage", "_dispatch_group", "run"},
    "InferenceModel": {"predict_async"},
}
# the finalize seam: materializing device results here is the design
# (the engine's third stage exists to absorb that sync)
_FINALIZE_SEAM = {"_finalize_record", "_finalize_inner", "finalize_loop"}
_HOT_DECL = "ZOOLINT_HOT_PATH"

_JIT_WRAPPERS = {"jit", "pmap", "shard_map", "partial"}
_SCOPE_FNS = (ast.FunctionDef, ast.AsyncFunctionDef)


def _attr_root(expr: ast.expr) -> Optional[str]:
    node = expr
    while isinstance(node, ast.Attribute):
        node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return None


def _unwrap_wrapper(expr: ast.expr, depth: int = 0,
                    stripped: Optional[List[str]] = None) -> ast.expr:
    """Strip ``jax.jit(fn, ...)`` / ``partial(fn, ...)`` layers so an
    alias of a wrapped function still resolves to the def; appends
    each stripped wrapper's name to ``stripped`` (a ``partial`` layer
    shifts positional binding, which callers must know)."""
    if depth > 2:
        return expr
    if isinstance(expr, ast.Call) and expr.args:
        name = None
        if isinstance(expr.func, ast.Name):
            name = expr.func.id
        elif isinstance(expr.func, ast.Attribute):
            name = expr.func.attr
        if name in _JIT_WRAPPERS:
            if stripped is not None:
                stripped.append(name)
            return _unwrap_wrapper(expr.args[0], depth + 1, stripped)
    return expr


class FnNode:
    """One function/method definition in the graph."""

    def __init__(self, src: SourceFile, node: ast.AST, qname: str,
                 cls_name: Optional[str], parent: Optional["FnNode"]):
        self.src = src
        self.node = node
        self.qname = qname                  # "<rel>::Class.method"
        self.name = getattr(node, "name", "<lambda>")
        self.cls_name = cls_name
        self.parent = parent                # enclosing FnNode, if any
        self.children: List["FnNode"] = []
        args = getattr(node, "args", None)
        self.pos_params: List[str] = []
        self.all_params: Set[str] = set()
        if args is not None:
            self.pos_params = [a.arg for a in
                               (list(args.posonlyargs) + list(args.args))]
            self.all_params = set(self.pos_params) | {
                a.arg for a in args.kwonlyargs}
        # propagation state
        self.contexts: Set[str] = set()
        self.jit_direct = False
        self.jit_kind: Optional[str] = None
        self.tracer_params: Set[str] = set()
        self.device_params: Set[str] = set()
        # one representative (root qname, caller qname) per context, so
        # finding messages can name HOW the context arrived
        self.via: Dict[str, Tuple[str, str]] = {}
        self.edges_out: List["CallEdge"] = []
        self.edges_in: List["CallEdge"] = []
        self._scope: Optional[Scope] = None

    @property
    def is_method(self) -> bool:
        return self.cls_name is not None

    def owning_class(self) -> Optional[str]:
        """The class whose ``self`` is in scope: this method's class,
        or -- for a def nested inside a method (the jitted-step idiom:
        ``def step(...)`` closing over ``self``) -- the enclosing
        method's class."""
        node: Optional["FnNode"] = self
        while node is not None:
            if node.cls_name is not None:
                return node.cls_name
            node = node.parent
        return None

    def scope(self) -> Scope:
        if self._scope is None:
            self._scope = Scope(self.node)
        return self._scope

    def effective_tracer_params(self) -> Set[str]:
        """Own traced params plus enclosing functions' traced params
        visible as closure free variables (minus shadowed names)."""
        out = set(self.tracer_params)
        node, shadow = self.parent, set(self.all_params)
        while node is not None:
            out |= node.tracer_params - shadow
            shadow |= node.all_params
            node = node.parent
        return out

    def root_of(self, ctx: str) -> str:
        return self.via.get(ctx, (self.qname, self.qname))[0]


def own_nodes(fn: FnNode) -> Iterable[ast.AST]:
    """Every AST node in ``fn``'s OWN body, pruning nested-def
    subtrees (each nested def is its own FnNode and scans itself --
    ``ast.walk`` + a skip of the def node alone would still descend
    into its body and double-report every finding there)."""
    nested = {id(c.node) for c in fn.children}

    def walk(node: ast.AST) -> Iterable[ast.AST]:
        for child in ast.iter_child_nodes(node):
            if id(child) in nested:
                continue
            yield child
            yield from walk(child)

    body = fn.node.body
    for stmt in (body if isinstance(body, list) else [body]):
        if id(stmt) in nested:
            continue  # a nested def IS a top-level body statement
        yield stmt
        yield from walk(stmt)


class CallEdge:
    def __init__(self, caller: FnNode, callee: FnNode, call: ast.Call,
                 bindings: List[Tuple[str, ast.expr]]):
        self.caller = caller
        self.callee = callee
        self.call = call
        self.bindings = bindings  # (callee param name, arg expression)


class CallGraph:
    """The built graph: nodes, edges, per-file unresolved counts."""

    def __init__(self, project: Project):
        self.project = project
        self.nodes: List[FnNode] = []
        self.by_node_id: Dict[int, FnNode] = {}
        # (rel, fn name) -> [module-level FnNodes]
        self._module_fns: Dict[Tuple[str, str], List[FnNode]] = {}
        # (rel, class, method) -> [FnNodes]
        self._methods: Dict[Tuple[str, str, str], List[FnNode]] = {}
        # rel -> {alias: ("module", rel2) | ("symbol", rel2, name)}
        self._imports: Dict[str, Dict[str, Tuple]] = {}
        # (rel, class) -> {attr: [value exprs]} from self.<attr> = ...
        self._self_attrs: Dict[Tuple[str, str],
                               Dict[str, List[ast.expr]]] = {}
        self._module_scopes: Dict[str, Scope] = {}
        self.unresolved: Dict[str, int] = {}
        self._build()
        self._mark_roots()
        self._mark_wrapper_call_roots()
        self._propagate()

    # ------------------------------------------------------ indexing --
    def _module_rel(self, dotted: str) -> Optional[str]:
        """rel path of a dotted module among the scanned files."""
        base = dotted.replace(".", "/")
        for cand in (base + ".py", base + "/__init__.py"):
            if self.project.file(cand) is not None:
                return cand
        # paths are repo-root-relative; a lint of a subtree may carry a
        # prefix (e.g. "analytics_zoo_tpu/...") -- try suffix match
        for f in self.project.files:
            if f.rel.endswith("/" + base + ".py"):
                return f.rel
        return None

    def _collect_imports(self, src: SourceFile) -> None:
        imp: Dict[str, Tuple] = {}
        pkg_parts = src.rel.split("/")[:-1]
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    rel2 = self._module_rel(alias.name)
                    if rel2 is not None:
                        imp[alias.asname
                            or alias.name.split(".")[0]] = (
                            ("module", rel2) if alias.asname
                            else ("module_root", alias.name, rel2))
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    base = pkg_parts[:len(pkg_parts) - (node.level - 1)]
                    mod = ".".join(base + (node.module.split(".")
                                           if node.module else []))
                else:
                    mod = node.module or ""
                rel2 = self._module_rel(mod) if mod else None
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    bound = alias.asname or alias.name
                    # "from pkg import worker" (submodule) vs
                    # "from pkg.mod import fn" (symbol)
                    sub = self._module_rel(
                        (mod + "." if mod else "") + alias.name)
                    if sub is not None:
                        imp[bound] = ("module", sub)
                    elif rel2 is not None:
                        imp[bound] = ("symbol", rel2, alias.name)
        self._imports[src.rel] = imp

    def _collect_defs(self, src: SourceFile) -> None:
        graph = self

        class V(ast.NodeVisitor):
            def __init__(self):
                self.stack: List[Tuple[str, object]] = []  # (kind, x)

            def _fn_parent(self) -> Optional[FnNode]:
                for kind, x in reversed(self.stack):
                    if kind == "fn":
                        return x
                return None

            def visit_ClassDef(self, node):
                self.stack.append(("cls", node.name))
                self.generic_visit(node)
                self.stack.pop()

            def _def(self, node):
                parent = self._fn_parent()
                cls = None
                if (self.stack and self.stack[-1][0] == "cls"):
                    cls = self.stack[-1][1]
                qname = "::".join((src.rel, ".".join(
                    [x if k == "cls" else x.name
                     for k, x in self.stack] + [node.name])))
                fn = FnNode(src, node, qname, cls, parent)
                graph.nodes.append(fn)
                graph.by_node_id[id(node)] = fn
                if parent is not None:
                    parent.children.append(fn)
                if cls is not None:
                    graph._methods.setdefault(
                        (src.rel, cls, node.name), []).append(fn)
                elif parent is None:
                    graph._module_fns.setdefault(
                        (src.rel, node.name), []).append(fn)
                else:  # nested def: findable from enclosing scopes too
                    graph._module_fns.setdefault(
                        (src.rel, node.name), []).append(fn)
                self.stack.append(("fn", fn))
                self.generic_visit(node)
                self.stack.pop()

            visit_FunctionDef = _def
            visit_AsyncFunctionDef = _def

        V().visit(src.tree)

        # self.<attr> = <expr> assignments per class (alias one level)
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            attrs: Dict[str, List[ast.expr]] = {}
            for sub in ast.walk(node):
                if (isinstance(sub, ast.Assign)
                        and len(sub.targets) == 1
                        and isinstance(sub.targets[0], ast.Attribute)
                        and isinstance(sub.targets[0].value, ast.Name)
                        and sub.targets[0].value.id == "self"):
                    attrs.setdefault(sub.targets[0].attr,
                                     []).append(sub.value)
            self._self_attrs[(src.rel, node.name)] = attrs

    # ---------------------------------------------------- resolution --
    def _module_scope(self, rel: str) -> Scope:
        if rel not in self._module_scopes:
            src = self.project.file(rel)
            self._module_scopes[rel] = Scope(src.tree)
        return self._module_scopes[rel]

    def _lookup_local(self, caller: FnNode,
                      name: str) -> Optional[FnNode]:
        """A def LEXICALLY visible from ``caller`` by bare name:
        module level, or nested inside the caller's enclosing-function
        chain (a def nested in an unrelated function is not in scope
        and must not make an edge). Unique or nothing."""
        ancestors = {None}
        node: Optional[FnNode] = caller
        while node is not None:
            ancestors.add(node)
            node = node.parent
        hits = [n for n in self._module_fns.get(
            (caller.src.rel, name), [])
            if n.cls_name is None and n.parent in ancestors]
        if len(hits) == 1:
            return hits[0]
        return None

    def _resolve_ref(self, caller: FnNode, expr: ast.expr,
                     depth: int = 0,
                     stripped: Optional[List[str]] = None
                     ) -> Optional[FnNode]:
        if depth > 1:  # one level of alias indirection, by contract
            return None
        expr = _unwrap_wrapper(expr, stripped=stripped)
        if isinstance(expr, ast.Name):
            hit = self._lookup_local(caller, expr.id)
            if hit is not None:
                return hit
            imp = self._imports.get(caller.src.rel, {}).get(expr.id)
            if imp is not None and imp[0] == "symbol":
                return self._foreign_fn(imp[1], imp[2])
            # alias: unique simple assignment in the caller's own
            # scope, else the module scope (dataflow's Scope machinery)
            for scope in (caller.scope(),
                          self._module_scope(caller.src.rel)):
                if expr.id in scope.tainted:
                    return None
                assigns = scope.assigns.get(expr.id, [])
                if len(assigns) == 1:
                    return self._resolve_ref(caller, assigns[0],
                                             depth + 1, stripped)
                if assigns:
                    return None
            return None
        if isinstance(expr, ast.Attribute):
            base = expr.value
            own_cls = caller.owning_class()
            if (isinstance(base, ast.Name)
                    and base.id in ("self", "cls")
                    and own_cls is not None):
                hits = self._methods.get(
                    (caller.src.rel, own_cls, expr.attr), [])
                if len(hits) == 1:
                    return hits[0]
                if hits:
                    return None
                # self-attribute alias: self._step = jax.jit(step)
                attrs = self._self_attrs.get(
                    (caller.src.rel, own_cls), {})
                exprs = attrs.get(expr.attr, [])
                if len(exprs) == 1:
                    return self._resolve_ref(caller, exprs[0],
                                             depth + 1, stripped)
                return None
            if isinstance(base, ast.Name):
                imp = self._imports.get(caller.src.rel,
                                        {}).get(base.id)
                if imp is not None and imp[0] == "module":
                    return self._foreign_fn(imp[1], expr.attr)
            # "import analytics_zoo_tpu.serving.worker" root form:
            # worker.fn via full dotted attribute chain
            root = _attr_root(expr.value)
            if root is not None:
                imp = self._imports.get(caller.src.rel,
                                        {}).get(root)
                if imp is not None and imp[0] == "module_root":
                    dotted = self._dotted(expr.value)
                    if dotted is not None:
                        rel2 = self._module_rel(dotted)
                        if rel2 is not None:
                            return self._foreign_fn(rel2, expr.attr)
        return None

    @staticmethod
    def _dotted(expr: ast.expr) -> Optional[str]:
        parts: List[str] = []
        node = expr
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if isinstance(node, ast.Name):
            parts.append(node.id)
            return ".".join(reversed(parts))
        return None

    def _foreign_fn(self, rel: str, name: str) -> Optional[FnNode]:
        hits = [n for n in self._module_fns.get((rel, name), [])
                if n.cls_name is None and n.parent is None]
        if len(hits) == 1:
            return hits[0]
        return None

    # -------------------------------------------------------- edges --
    @staticmethod
    def _bind(call: ast.Call, callee: FnNode,
              bound_method: bool) -> List[Tuple[str, ast.expr]]:
        params = list(callee.pos_params)
        if bound_method and params and params[0] in ("self", "cls"):
            params = params[1:]
        out: List[Tuple[str, ast.expr]] = []
        for i, a in enumerate(call.args):
            if isinstance(a, ast.Starred):
                break
            if i < len(params):
                out.append((params[i], a))
        for kw in call.keywords:
            if kw.arg and kw.arg in callee.all_params:
                out.append((kw.arg, kw.value))
        return out

    def _collect_calls(self, fn: FnNode) -> None:
        for child in own_nodes(fn):
            if not isinstance(child, ast.Call):
                continue
            stripped: List[str] = []
            callee = self._resolve_ref(fn, child.func,
                                       stripped=stripped)
            if callee is None:
                self.unresolved[fn.src.rel] = (
                    self.unresolved.get(fn.src.rel, 0) + 1)
            elif callee.node is not fn.node:
                bound = (isinstance(child.func, ast.Attribute)
                         and callee.is_method)
                # an alias through partial pre-binds params, shifting
                # the positional map in a way this resolver does not
                # model: keep the edge (the call DOES happen --
                # contexts must flow) but claim no argument bindings
                bindings = ([] if "partial" in stripped
                            else self._bind(child, callee, bound))
                edge = CallEdge(fn, callee, child, bindings)
                fn.edges_out.append(edge)
                callee.edges_in.append(edge)

    def _build(self) -> None:
        for src in self.project.files:
            self._collect_imports(src)
            self._collect_defs(src)
        for fn in self.nodes:
            self._collect_calls(fn)

    # -------------------------------------------------------- roots --
    def _hot_declared(self, src: SourceFile) -> Set[Tuple[str, str]]:
        """(class-or-'', name) pairs from a module-level
        ``ZOOLINT_HOT_PATH = ("fn", "Class.method")`` declaration."""
        out: Set[Tuple[str, str]] = set()
        for node in src.tree.body:
            if (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == _HOT_DECL
                    and isinstance(node.value, (ast.Tuple, ast.List))):
                for e in node.value.elts:
                    if (isinstance(e, ast.Constant)
                            and isinstance(e.value, str)):
                        cls, _, name = e.value.rpartition(".")
                        out.add((cls, name))
        return out

    def _mark_roots(self) -> None:
        for src in self.project.files:
            for jf in jitted_functions(src):
                fn = self.by_node_id.get(id(jf.fn))
                if fn is None:
                    continue  # inline lambda: PR 4 covers its body
                fn.jit_direct = True
                fn.jit_kind = jf.kind
                fn.tracer_params |= jf.params
                fn.contexts.add(CTX_JIT)
                fn.via.setdefault(CTX_JIT, (fn.qname, fn.qname))
                if jf.kind == "shard_map":
                    fn.contexts.add(CTX_COLLECTIVE)
                    fn.via.setdefault(CTX_COLLECTIVE,
                                      (fn.qname, fn.qname))
        declared_by_rel = {src.rel: self._hot_declared(src)
                           for src in self.project.files}
        for fn in self.nodes:
            stages = _HOT_STAGE_METHODS.get(fn.cls_name or "", set())
            declared = declared_by_rel.get(fn.src.rel, set())
            hot = (fn.name in stages
                   or (fn.cls_name or "", fn.name) in declared)
            if hot and fn.name not in _FINALIZE_SEAM:
                fn.contexts.add(CTX_HOTPATH)
                fn.via.setdefault(CTX_HOTPATH, (fn.qname, fn.qname))

    # ------------------------------------- wrapper-call root marking --
    def _wrap_target(self, caller: FnNode, expr: ast.expr,
                     depth: int = 0
                     ) -> Optional[Tuple[FnNode, int, Set[str]]]:
        """Resolve the function being traced in ``shard_map(X, ...)`` /
        ``jit(X)``, carrying partial-binding info the plain
        :meth:`_resolve_ref` discards: returns ``(fn, n_positional
        pre-bound, kw names pre-bound)`` through ``partial`` layers,
        nested wrappers, and one alias hop (``body = partial(f, ...)``;
        ``self._step = jit(step)``). None when unresolvable.

        The Name/self-attr/import branches mirror ``_resolve_ref``
        minus the dotted ``module_root`` form -- a resolution-rule
        change there must land here too, or the two walks drift."""
        if depth > 3:
            return None
        if isinstance(expr, ast.Call):
            name = None
            if isinstance(expr.func, ast.Name):
                name = expr.func.id
            elif isinstance(expr.func, ast.Attribute):
                name = expr.func.attr
            if name == "partial" and expr.args:
                inner = self._wrap_target(caller, expr.args[0],
                                          depth + 1)
                if inner is None:
                    return None
                fn, pos, kws = inner
                kws = kws | {kw.arg for kw in expr.keywords if kw.arg}
                if (any(kw.arg is None for kw in expr.keywords)
                        or any(isinstance(a, ast.Starred)
                               for a in expr.args[1:])):
                    # a *args/**kwargs splat can bind ANY parameter --
                    # which ones is unknowable, so no param may claim
                    # tracer taint (contexts still propagate)
                    kws = kws | {"*"}
                return fn, pos + len(expr.args) - 1, kws
            if name in _JIT_WRAPPERS and expr.args:
                return self._wrap_target(caller, expr.args[0],
                                         depth + 1)
            return None
        if isinstance(expr, ast.Name):
            if expr.id in caller.all_params:
                # a function passed IN (params lexically shadow outer
                # defs): resolvable one level up, at the caller's own
                # call sites (the _ring_shard_call idiom) -- hand back
                # a marker for the deferred pass
                return ("param", expr.id), 0, set()
            hit = self._lookup_local(caller, expr.id)
            if hit is not None:
                return hit, 0, set()
            imp = self._imports.get(caller.src.rel, {}).get(expr.id)
            if imp is not None and imp[0] == "symbol":
                fn = self._foreign_fn(imp[1], imp[2])
                return None if fn is None else (fn, 0, set())
            for scope in (caller.scope(),
                          self._module_scope(caller.src.rel)):
                if expr.id in scope.tainted:
                    return None
                assigns = scope.assigns.get(expr.id, [])
                if len(assigns) == 1:
                    return self._wrap_target(caller, assigns[0],
                                             depth + 1)
                if assigns:
                    return None
            return None
        if isinstance(expr, ast.Attribute):
            base = expr.value
            own_cls = caller.owning_class()
            if (isinstance(base, ast.Name) and base.id in ("self",
                                                           "cls")
                    and own_cls is not None):
                hits = self._methods.get(
                    (caller.src.rel, own_cls, expr.attr), [])
                if len(hits) == 1:
                    return hits[0], 0, set()
                if hits:
                    return None
                exprs = self._self_attrs.get(
                    (caller.src.rel, own_cls), {}).get(expr.attr, [])
                if len(exprs) == 1:
                    return self._wrap_target(caller, exprs[0],
                                             depth + 1)
                return None
            if isinstance(base, ast.Name):
                imp = self._imports.get(caller.src.rel,
                                        {}).get(base.id)
                if imp is not None and imp[0] == "module":
                    fn = self._foreign_fn(imp[1], expr.attr)
                    return None if fn is None else (fn, 0, set())
        return None

    def _mark_wrapper_call_roots(self) -> None:
        """Mark functions traced through a wrapper CALL (not a
        decorator): ``shard_map(body, mesh, ...)`` where ``body =
        partial(_pipeline_local, stage_fn=...)`` -- the pipeline /
        ring-attention / zouwu idiom. The PR-4 detection only sees
        decorators, ``jit(name)`` by direct name, and inline lambdas,
        so these bodies carried no collective context at all; this is
        THE resolution gap that hid the jax-0.4.x ``lax.axis_size``
        crashes (collective-version-api in deep_rules)."""
        deferred: List[Tuple[FnNode, str, ast.Call, str, int,
                             Set[str]]] = []
        for fn in self.nodes:
            for child in own_nodes(fn):
                if isinstance(child, ast.Call):
                    self._mark_one_wrapper_call(fn, child, deferred)
        # higher-order, one level: ``fn`` wraps its own PARAMETER
        # (``_ring_shard_call(local_fn, ...)`` -> ``shard_map(
        # partial(local_fn, ...), ...)``); the wrapped function is
        # whatever fn's resolved call sites pass for that parameter
        for fn, wname, call, pname, pos, kws in deferred:
            for edge in fn.edges_in:
                for bname, aexpr in edge.bindings:
                    if bname != pname:
                        continue
                    info = self._wrap_target(edge.caller, aexpr)
                    if info is None or not isinstance(info[0], FnNode):
                        continue
                    self._mark_root_fn(info[0], wname, call,
                                       edge.caller,
                                       pos + info[1], kws | info[2])

    def _mark_one_wrapper_call(
            self, caller: FnNode, call: ast.Call,
            deferred: List[Tuple[FnNode, str, ast.Call, str, int,
                                 Set[str]]]) -> None:
        func = call.func
        if isinstance(func, ast.Name):
            name = func.id
        elif isinstance(func, ast.Attribute):
            name = func.attr
            if _attr_root(func) != "jax":
                return  # jax.jit / jax.experimental...shard_map only
        else:
            return
        if name not in ("jit", "pmap", "shard_map") or not call.args:
            return
        info = self._wrap_target(caller, call.args[0])
        if info is None:
            return
        target, pos_bound, kw_bound = info
        if isinstance(target, FnNode):
            self._mark_root_fn(target, name, call, caller, pos_bound,
                               kw_bound)
        else:  # ("param", pname): resolve at caller's call sites
            deferred.append((caller, name, call, target[1], pos_bound,
                             kw_bound))

    def _mark_root_fn(self, callee: FnNode, wrapper: str,
                      call: ast.Call, caller: FnNode, pos_bound: int,
                      kw_bound: Set[str]) -> None:
        if callee.jit_direct:
            return  # PR-4 saw it; its static_argnums params stand
        callee.contexts.add(CTX_JIT)
        callee.via.setdefault(CTX_JIT, (callee.qname, caller.qname))
        if wrapper == "shard_map":
            callee.contexts.add(CTX_COLLECTIVE)
            callee.via.setdefault(CTX_COLLECTIVE,
                                  (callee.qname, caller.qname))
        if "*" in kw_bound:
            return  # a splat layer: param binding unknowable, no taint
        static = _static_params(call, callee.node)
        for pname in callee.pos_params[pos_bound:]:
            if pname in ("self", "cls") or pname in kw_bound \
                    or pname in static:
                continue
            callee.tracer_params.add(pname)

    # -------------------------------------------------- propagation --
    def _propagate(self) -> None:
        changed = True
        guard = 0
        while changed and guard < 100:
            changed = False
            guard += 1
            for fn in self.nodes:
                # containment: nested defs inherit enclosing contexts
                for child in fn.children:
                    for ctx in fn.contexts:
                        if ctx == CTX_HOTPATH and (
                                child.name in _FINALIZE_SEAM):
                            continue
                        if ctx not in child.contexts:
                            child.contexts.add(ctx)
                            child.via.setdefault(
                                ctx, (fn.root_of(ctx), fn.qname))
                            changed = True
                for edge in fn.edges_out:
                    callee = edge.callee
                    for ctx in fn.contexts:
                        if ctx == CTX_HOTPATH and (
                                callee.name in _FINALIZE_SEAM):
                            continue  # the sanctioned sync barrier
                        if ctx not in callee.contexts:
                            callee.contexts.add(ctx)
                            callee.via.setdefault(
                                ctx, (fn.root_of(ctx), fn.qname))
                            changed = True
                    if (CTX_JIT in fn.contexts
                            or CTX_COLLECTIVE in fn.contexts):
                        params = fn.effective_tracer_params()
                        for pname, aexpr in edge.bindings:
                            if (pname not in callee.tracer_params
                                    and _is_tracer_expr(aexpr, params)):
                                callee.tracer_params.add(pname)
                                changed = True
                    if CTX_HOTPATH in fn.contexts:
                        for pname, aexpr in edge.bindings:
                            if (pname not in callee.device_params
                                    and is_device_expr(aexpr, fn)):
                                callee.device_params.add(pname)
                                changed = True

    # ------------------------------------------------------- export --
    def to_dict(self) -> Dict:
        """The ``--graph`` debug dump: what resolved, what contexts
        propagated where, which params carry taint."""
        fns = []
        for fn in sorted(self.nodes, key=lambda n: n.qname):
            if not (fn.contexts or fn.edges_out or fn.edges_in):
                continue
            fns.append({
                "qname": fn.qname,
                "contexts": sorted(fn.contexts),
                "jit_direct": fn.jit_direct,
                "tracer_params": sorted(fn.tracer_params),
                "device_params": sorted(fn.device_params),
                "via": {k: list(v) for k, v in sorted(fn.via.items())},
                "calls": sorted({e.callee.qname
                                 for e in fn.edges_out}),
            })
        return {
            "functions": fns,
            "unresolved_calls": dict(sorted(self.unresolved.items())),
            "counts": {
                "functions": len(self.nodes),
                "edges": sum(len(f.edges_out) for f in self.nodes),
                "unresolved": sum(self.unresolved.values()),
            },
        }


# --------------------------------------------------------------------- #
# device-derivation walk (shared with deep_rules' hot-path family)       #
# --------------------------------------------------------------------- #
_DEVICE_ATTRS = {"predict_async"}
_DEVICE_MODULES = {"jnp"}


def _device_call(call: ast.Call, fn: FnNode,
                 _seen: Optional[Set[str]] = None) -> bool:
    func = call.func
    name = func.attr if isinstance(func, ast.Attribute) else (
        func.id if isinstance(func, ast.Name) else None)
    if name in _DEVICE_ATTRS:
        return True
    if name == "device_put":
        return True
    root = _attr_root(func) if isinstance(func, ast.Attribute) else None
    if root in _DEVICE_MODULES:
        # jnp ops produce device arrays (jnp.asarray of host data is
        # itself the transfer, so it is a device source too)
        return True
    if name in ("tree_map", "tree_leaves"):
        return any(is_device_expr(a, fn, _seen) for a in call.args)
    # a call to a jit-wrapped function in the same graph
    graph = getattr(fn, "_graph", None)
    if graph is not None:
        callee = graph._resolve_ref(fn, func)
        if callee is not None and callee.jit_direct:
            return True
    return False


def is_device_expr(expr: ast.AST, fn: FnNode,
                   _seen: Optional[Set[str]] = None) -> bool:
    """Proven device-derived: a value the walk can trace to an async
    dispatch (``predict_async``), a jit-wrapped call, a ``jnp`` op,
    ``jax.device_put``, or a parameter that inherited device taint.
    Unknown derivations return False -- the caller must not claim."""
    if _seen is None:
        _seen = set()
    if isinstance(expr, ast.Name):
        if expr.id in fn.device_params:
            return True
        if expr.id in _seen:
            # self-referential assignment (``acc = acc + ...``): the
            # cycle itself proves nothing -- the OTHER operands decide
            return False
        _seen = _seen | {expr.id}
        scope = fn.scope()
        if expr.id in scope.tainted:
            # tuple-unpack of a device-producing call is the worker
            # idiom (``preds, n = model.predict_async(x)``); Scope
            # taints those, so look for the unpack assignment directly
            return _unpack_device(expr.id, fn, _seen)
        assigns = scope.assigns.get(expr.id, [])
        return bool(assigns) and all(
            is_device_expr(a, fn, _seen) for a in assigns)
    if isinstance(expr, ast.Call):
        return _device_call(expr, fn, _seen)
    if isinstance(expr, ast.Attribute):
        if expr.attr in _STATIC_ATTRS:
            # x.shape / x.dtype / x.ndim on a device array is host
            # metadata -- reading it costs no d2h sync
            return False
        return is_device_expr(expr.value, fn, _seen)
    if isinstance(expr, ast.Subscript):
        return is_device_expr(expr.value, fn, _seen)
    if isinstance(expr, ast.BinOp):
        return (is_device_expr(expr.left, fn, _seen)
                or is_device_expr(expr.right, fn, _seen))
    return False


def _unpack_device(name: str, fn: FnNode,
                   _seen: Optional[Set[str]] = None) -> bool:
    """True when every ``a, b = <call>`` binding of ``name`` in this
    function unpacks a device-producing call."""
    found = False
    for stmt in ast.walk(fn.node):
        if not isinstance(stmt, ast.Assign) or len(stmt.targets) != 1:
            continue
        t = stmt.targets[0]
        if not isinstance(t, (ast.Tuple, ast.List)):
            continue
        names = [e.id for e in t.elts if isinstance(e, ast.Name)]
        if name not in names:
            continue
        if not (isinstance(stmt.value, ast.Call)
                and _device_call(stmt.value, fn, _seen)):
            return False
        found = True
    return found


def build_call_graph(project: Project) -> CallGraph:
    graph = CallGraph(project)
    for fn in graph.nodes:
        fn._graph = graph  # backref for the device walk
    return graph
