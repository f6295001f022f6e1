"""deepcheck layer 2: interprocedural rule families over the call graph.

Three families, all riding :mod:`analytics_zoo_tpu.analysis.callgraph`'s
context propagation. Everything here is conservative by construction:
an unresolved call, an unknown value, an untainted parameter is never a
finding.

**Transitive trace hazards.** The PR-4 rules (``jit-numpy-call`` /
``jit-concretize`` / ``jit-tracer-branch``) re-run inside every
function that *inherits* jit/collective context through the graph, with
the tracer-ness walk seeded by the propagated per-parameter taint -- a
helper extracted out of a jitted step keeps its guardrails. Findings
PR 4 already reports (directly jitted functions) are deduplicated, so
each hazard fires exactly once. ``jit-host-callback-undeclared`` flags
``pure_callback`` / ``io_callback`` / ``host_callback.call`` /
``py_func``-style trace escapes reached from jit context: each one is a
host round-trip per dispatch, fine only when somebody wrote down why
(suppress inline with the reason).

**Hot-path host syncs.** ``hotpath-block-on-device`` fires on
``.block_until_ready()`` / ``jax.device_get`` anywhere in propagated
serving-hot-path context, and on ``.item()`` / ``float()`` / ``int()``
/ ``np.asarray`` / ``np.array`` whose operand is *proven*
device-derived. The decode->dispatch stages exist to overlap host work
with device compute (docs/serving.md); one synchronous materialization
there stalls the whole pipeline for a device round-trip -- the recurring
TPU-serving-throughput lesson. The finalize seam is exempt (that stage
exists to absorb the sync), as is anything in jit context (a host sync
inside a trace is a *trace* hazard, reported by the jit family).

**Version-fragile collective API.** ``jax.shard_map`` and
``lax.axis_size`` have moved and changed keywords across jax lines,
so each is spelled in exactly ONE wrapper and the next move is a
one-line change. ``shard-map-direct`` flags any ``jax.shard_map`` use
outside its wrapper (``parallel/mesh.py``). ``collective-version-api``
flags ``lax.axis_size`` outside its wrapper
(``parallel/collectives.py``) in **propagated collective context** --
the interprocedural part: the pipeline/ring-attention local bodies
are plain module functions whose collective-ness is only provable by
resolving ``shard_map(partial(body, ...), ...)`` through the call
graph. Dogfooding this pair on the pre-deepcheck tree found 10 real
direct uses (7 ``jax.shard_map``, 3 ``lax.axis_size`` bodies) -- see
docs/zoolint.md.

**Dtype drift.** ``dtype-upcast-f32`` flags an argument with a
provable float32/float64 dtype flowing into a parameter whose
default/annotation declares bf16/f16 at a resolved call edge -- the
static twin of the r4 ResNet-50 profile where f32 batch-norm constants
upcast bf16 activations into convert+reduce fusions worth 31% of step
time (BENCH_NOTES.md). ``dtype-mixed-collective`` flags a collective
whose operand expression mixes two provable float dtypes: the operand
is silently computed (and shipped cross-chip) at the wider one.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from analytics_zoo_tpu.analysis.callgraph import (
    CTX_COLLECTIVE, CTX_HOTPATH, CTX_JIT, FnNode, build_call_graph,
    is_device_expr, own_nodes)
from analytics_zoo_tpu.analysis.core import (
    Checker, Finding, Project, register)
from analytics_zoo_tpu.analysis.mesh_rules import _COLLECTIVES
from analytics_zoo_tpu.analysis.trace_hazards import (
    TraceHazardChecker, _is_tracer_expr, _np_root)

# py_func-style trace escapes: each is a host callback per dispatch
_HOST_CALLBACKS = {"pure_callback", "io_callback", "py_func"}
_HOST_CALLBACK_MODULES = {"host_callback", "hcb"}

# host-numpy functions that only read array METADATA -- safe on a
# tracer (shape/dtype are concrete at trace time), so they are never
# a jit-numpy-call finding
_NP_METADATA = {"ndim", "shape", "size", "result_type", "dtype",
                "isscalar", "iterable"}

_F32_TOKENS = {"float32", "float64"}
_BF16_TOKENS = {"bfloat16", "float16"}
_DTYPE_TOKENS = _F32_TOKENS | _BF16_TOKENS
_ARRAY_CTORS = {"array", "asarray", "zeros", "ones", "full", "empty",
                "arange", "linspace", "eye", "full_like", "zeros_like",
                "ones_like"}
_FLOAT_MODULES = {"np", "numpy", "onp", "jnp"}


def _call_name(func: ast.expr) -> Optional[str]:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _chain_root(func: ast.expr) -> Optional[str]:
    node = func
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


# --------------------------------------------------------------------- #
# literal dtype inference (one level of Name indirection via Scope)      #
# --------------------------------------------------------------------- #
def dtype_token(expr: ast.AST, fn: Optional[FnNode] = None,
                _depth: int = 0) -> Optional[str]:
    """The provable dtype of an expression, as a canonical token
    ("float32", "bfloat16", ...), or None when unknown. Plain python
    float literals are weakly typed under jax and never claim."""
    if _depth > 2:
        return None
    if isinstance(expr, ast.Constant):
        if isinstance(expr.value, str) and expr.value in _DTYPE_TOKENS:
            return expr.value
        return None
    if isinstance(expr, ast.Attribute):
        # np.float32 / jnp.bfloat16 as a dtype object
        if (expr.attr in _DTYPE_TOKENS
                and _chain_root(expr) in _FLOAT_MODULES):
            return expr.attr
        return None
    if isinstance(expr, ast.Name):
        if fn is None:
            return None
        for scope in (fn.scope(),):
            if expr.id in scope.tainted:
                return None
            assigns = scope.assigns.get(expr.id, [])
            if len(assigns) == 1:
                return dtype_token(assigns[0], fn, _depth + 1)
        return None
    if isinstance(expr, ast.Call):
        name = _call_name(expr.func)
        root = _chain_root(expr.func)
        if name in _DTYPE_TOKENS and root in _FLOAT_MODULES:
            return name  # np.float32(1.0) / jnp.bfloat16(x)
        if name == "astype" and isinstance(expr.func, ast.Attribute):
            if expr.args:
                return dtype_token(expr.args[0], fn, _depth + 1)
            return None
        if name in _ARRAY_CTORS and root in _FLOAT_MODULES:
            for kw in expr.keywords:
                if kw.arg == "dtype":
                    return dtype_token(kw.value, fn, _depth + 1)
            if len(expr.args) >= 2:
                return dtype_token(expr.args[1], fn, _depth + 1)
            return None
    return None


def _is_dtype_selector(expr: ast.AST) -> bool:
    """A bare dtype OBJECT (``jnp.bfloat16``, ``"float32"``) rather
    than a value carrying that dtype: a selector parameter/argument.
    An explicit ``dtype=np.float32`` is the caller *choosing* f32 --
    the opposite of the silent-upcast pattern the rule hunts."""
    if isinstance(expr, ast.Attribute):
        return (expr.attr in _DTYPE_TOKENS
                and _chain_root(expr) in _FLOAT_MODULES)
    if isinstance(expr, ast.Constant):
        return isinstance(expr.value, str) and expr.value in \
            _DTYPE_TOKENS
    return False


def _param_decl_dtypes(fn: FnNode) -> Dict[str, str]:
    """Declared dtypes of parameters: a VALUE default or annotation
    with a provable dtype token (``eps=jnp.bfloat16(1e-3)``,
    ``x: jnp.bfloat16``). A bare dtype-object default
    (``dtype=jnp.bfloat16``) declares a selector parameter, not a
    bf16 value, and is excluded."""
    args = getattr(fn.node, "args", None)
    if args is None:
        return {}
    out: Dict[str, str] = {}
    pos = list(args.posonlyargs) + list(args.args)
    defaults = list(args.defaults)
    for a, d in zip(pos[len(pos) - len(defaults):], defaults):
        tok = dtype_token(d, fn)
        if tok is not None and not _is_dtype_selector(d):
            out[a.arg] = tok
    for a, d in zip(args.kwonlyargs, args.kw_defaults):
        if d is not None:
            tok = dtype_token(d, fn)
            if tok is not None and not _is_dtype_selector(d):
                out[a.arg] = tok
    for a in pos + list(args.kwonlyargs):
        if a.annotation is not None:
            tok = dtype_token(a.annotation, fn)
            if tok is not None:
                out.setdefault(a.arg, tok)
    return out


def _augmented_tracer_names(fn: FnNode, params: Set[str]) -> Set[str]:
    """Tainted params plus locals provably derived from them: a name
    whose every simple assignment is a tracer expression w.r.t. the
    growing set (``l = jnp.sum(x)`` with ``x`` traced taints ``l``).
    Tainted-any-other-way names (unpacking, loop targets) stay out --
    conservative, like everything here."""
    scope = fn.scope()
    names = set(params)
    changed = True
    while changed:
        changed = False
        for name, exprs in scope.assigns.items():
            if name in names or name in scope.tainted:
                continue
            if exprs and all(_is_tracer_expr(e, names) for e in exprs):
                names.add(name)
                changed = True
    return names


def _short(qname: str) -> str:
    """'pkg/mod.py::Class.fn' -> 'Class.fn' (messages stay symbolic
    and path-independent; the finding's own path column has the file)."""
    return qname.split("::", 1)[-1]


@register
class DeepChecker(Checker):
    """deepcheck: the interprocedural families (docs/zoolint.md)."""

    name = "deep"
    rules = {
        "jit-numpy-call": "host numpy call on a traced value inside a "
                          "jitted function (use jnp/lax)",
        "jit-concretize": ".item()/float()/int()/bool() on a traced "
                          "value inside a jitted function",
        "jit-tracer-branch": "Python if/while on a traced value inside "
                             "a jitted function (retrace or trace "
                             "error; use lax.cond/jnp.where)",
        "jit-host-callback-undeclared": "pure_callback/io_callback/"
                                        "host_callback/py_func escape "
                                        "reached from jit context -- a "
                                        "host round-trip per dispatch; "
                                        "suppress inline with the "
                                        "reason if intentional",
        "hotpath-block-on-device": "host sync (.item()/float()/"
                                   "np.asarray/device_get/"
                                   ".block_until_ready) on a device "
                                   "value reached from a serving "
                                   "pipeline stage outside the "
                                   "finalize seam (stalls the decode/"
                                   "dispatch overlap)",
        "shard-map-direct": "direct jax.shard_map use outside the "
                            "parallel/mesh.py wrapper (its location "
                            "and keywords move across jax lines; use "
                            "parallel.mesh.shard_map)",
        "collective-version-api": "lax.axis_size in propagated "
                                  "collective context outside its one "
                                  "wrapper (use "
                                  "parallel.collectives.axis_size)",
        "dtype-upcast-f32": "f32/f64 value flowing into a parameter "
                            "declared/defaulted bf16 or f16 (the "
                            "convert-fusion upcast pattern behind the "
                            "r4 BN profile)",
        "dtype-mixed-collective": "collective operand mixes two "
                                  "provable float dtypes (computed "
                                  "and shipped at the wider one)",
    }

    # ------------------------------------------------------- driver --
    def check_project(self, project: Project) -> Iterable[Finding]:
        graph = build_call_graph(project)
        # (rel, rule, line) PR 4 already reports: dedup so a directly
        # jitted function's hazards fire exactly once, from one family
        base = TraceHazardChecker()
        seen: Set[Tuple[str, str, int]] = set()
        for src in project.files:
            for f in base.check_file(src):
                seen.add((f.path, f.rule, f.line))
        for fn in graph.nodes:
            yield from self._check_trace(fn, seen)
            yield from self._check_host_callbacks(fn)
            yield from self._check_hotpath(fn)
            yield from self._check_dtype_edges(fn)
            yield from self._check_version_api(fn)
        for fn in graph.nodes:
            yield from self._check_mixed_collectives(fn)
        for src in project.files:
            yield from self._check_shard_map_direct(src)

    # ------------------------------------- transitive trace hazards --
    def _check_trace(self, fn: FnNode,
                     seen: Set[Tuple[str, str, int]]
                     ) -> Iterable[Finding]:
        if fn.jit_direct:
            return  # PR 4's per-file scan owns directly jitted bodies
        if not ({CTX_JIT, CTX_COLLECTIVE} & fn.contexts):
            return
        params = fn.effective_tracer_params()
        if not params:
            return
        params = _augmented_tracer_names(fn, params)
        root, caller = fn.via.get(
            CTX_JIT, fn.via.get(CTX_COLLECTIVE, (fn.qname, fn.qname)))
        reach = (f"'{fn.name}' (reached from jit-traced "
                 f"'{_short(root)}' via '{_short(caller)}')")
        for node in own_nodes(fn):
                if isinstance(node, ast.Call):
                    key = (fn.src.rel, "jit-numpy-call", node.lineno)
                    np_mod = _np_root(node.func)
                    if _call_name(node.func) in _NP_METADATA:
                        np_mod = None  # shape/dtype probes are static
                    if (np_mod is not None and key not in seen
                            and any(_is_tracer_expr(a, params)
                                    for a in list(node.args)
                                    + [kw.value
                                       for kw in node.keywords])):
                        seen.add(key)
                        yield Finding(
                            "jit-numpy-call", "error", fn.src.rel,
                            node.lineno,
                            f"helper {reach} calls host numpy "
                            f"({np_mod}.{_call_name(node.func)}) on a "
                            "transitively traced value; use jnp/lax")
                        continue
                    key = (fn.src.rel, "jit-concretize", node.lineno)
                    if (isinstance(node.func, ast.Attribute)
                            and node.func.attr == "item"
                            and not node.args and key not in seen
                            and _is_tracer_expr(node.func.value,
                                                params)):
                        seen.add(key)
                        yield Finding(
                            "jit-concretize", "error", fn.src.rel,
                            node.lineno,
                            f"helper {reach} calls .item() on a "
                            "transitively traced value (host sync "
                            "inside the trace)")
                        continue
                    if (isinstance(node.func, ast.Name)
                            and node.func.id in ("float", "int",
                                                 "bool")
                            and len(node.args) == 1
                            and key not in seen
                            and _is_tracer_expr(node.args[0], params)):
                        seen.add(key)
                        yield Finding(
                            "jit-concretize", "error", fn.src.rel,
                            node.lineno,
                            f"helper {reach} applies "
                            f"{node.func.id}() to a transitively "
                            "traced value (ConcretizationTypeError "
                            "under jit)")
                elif isinstance(node, (ast.If, ast.While)):
                    key = (fn.src.rel, "jit-tracer-branch",
                           node.lineno)
                    if (key not in seen
                            and _is_tracer_expr(node.test, params)):
                        seen.add(key)
                        kw = "if" if isinstance(node, ast.If) else \
                            "while"
                        yield Finding(
                            "jit-tracer-branch", "error", fn.src.rel,
                            node.lineno,
                            f"helper {reach} branches with Python "
                            f"'{kw}' on a transitively traced value; "
                            "use lax.cond/lax.while_loop or "
                            "jnp.where")

    def _check_host_callbacks(self, fn: FnNode) -> Iterable[Finding]:
        if not ({CTX_JIT, CTX_COLLECTIVE} & fn.contexts):
            return
        root = _short(fn.root_of(CTX_JIT if CTX_JIT in fn.contexts
                                 else CTX_COLLECTIVE))
        for node in own_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = _call_name(node.func)
                is_cb = name in _HOST_CALLBACKS or (
                    name == "call"
                    and isinstance(node.func, ast.Attribute)
                    and _chain_root(node.func)
                    in _HOST_CALLBACK_MODULES)
                if is_cb:
                    yield Finding(
                        "jit-host-callback-undeclared", "warning",
                        fn.src.rel, node.lineno,
                        f"'{fn.name}' (jit context from "
                        f"'{root}') escapes the trace through "
                        f"{name}; each dispatch pays a host "
                        "round-trip -- suppress inline with the "
                        "reason if intentional")

    # ------------------------------------------- hot-path host syncs --
    def _check_hotpath(self, fn: FnNode) -> Iterable[Finding]:
        if CTX_HOTPATH not in fn.contexts:
            return
        if {CTX_JIT, CTX_COLLECTIVE} & fn.contexts or fn.jit_direct:
            return  # inside a trace a sync is a trace hazard instead
        root, caller = fn.via.get(CTX_HOTPATH, (fn.qname, fn.qname))
        reach = (f"'{fn.name}' (hot path from '{_short(root)}'"
                 + ("" if caller == fn.qname
                    else f" via '{_short(caller)}'") + ")")
        for node in own_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                msg = self._sync_message(node, fn)
                if msg is not None:
                    yield Finding(
                        "hotpath-block-on-device", "warning",
                        fn.src.rel, node.lineno,
                        f"serving stage helper {reach} {msg}; the "
                        "decode/dispatch stages must stay "
                        "non-blocking -- move the materialization to "
                        "the finalize seam (or suppress with the "
                        "reason)")

    @staticmethod
    def _sync_message(node: ast.Call, fn: FnNode) -> Optional[str]:
        func = node.func
        name = _call_name(func)
        if name == "block_until_ready":
            return "blocks on .block_until_ready()"
        if name == "device_get":
            return "synchronously fetches with jax.device_get"
        if (name == "item" and isinstance(func, ast.Attribute)
                and not node.args
                and is_device_expr(func.value, fn)):
            return ".item()s a device value (one host round-trip)"
        if (name in ("asarray", "array")
                and _chain_root(func) in ("np", "numpy", "onp")
                and node.args and is_device_expr(node.args[0], fn)):
            return (f"materializes a device value with np.{name} "
                    "(synchronous d2h copy)")
        if (isinstance(func, ast.Name) and func.id in ("float", "int")
                and len(node.args) == 1
                and is_device_expr(node.args[0], fn)):
            return (f"concretizes a device value with {func.id}() "
                    "(one host round-trip)")
        return None

    # -------------------------------- version-fragile collective API --
    def _check_version_api(self, fn: FnNode) -> Iterable[Finding]:
        if CTX_COLLECTIVE not in fn.contexts:
            return  # axis_size outside a mapped body is its own error
        if fn.src.rel.endswith("parallel/collectives.py"):
            return  # the one wrapper, by contract
        caller = fn.via.get(CTX_COLLECTIVE, (fn.qname, fn.qname))[1]
        for node in own_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                if (isinstance(func, ast.Attribute)
                        and func.attr == "axis_size"
                        and _chain_root(func) in ("lax", "jax")):
                    yield Finding(
                        "collective-version-api", "error", fn.src.rel,
                        node.lineno,
                        f"'{fn.name}' (collective body, traced via "
                        f"'{_short(caller)}') calls lax.axis_size -- "
                        "spelled in one wrapper only; use "
                        "parallel.collectives.axis_size")

    def _check_shard_map_direct(self, src) -> Iterable[Finding]:
        if src.rel.endswith("parallel/mesh.py"):
            return  # the one compat wrapper, by contract
        seen_lines: Set[int] = set()
        for node in ast.walk(src.tree):
            hit = None
            if (isinstance(node, ast.ImportFrom) and node.module
                    and node.module.startswith("jax")
                    and any(a.name == "shard_map"
                            for a in node.names)):
                hit = f"imports shard_map from {node.module}"
            elif (isinstance(node, ast.Attribute)
                    and node.attr == "shard_map"
                    and _chain_root(node) == "jax"):
                hit = "uses jax.shard_map directly"
            if hit is not None and node.lineno not in seen_lines:
                seen_lines.add(node.lineno)
                yield Finding(
                    "shard-map-direct", "error", src.rel, node.lineno,
                    f"{hit}: spelled in one wrapper only -- route "
                    "through parallel.mesh.shard_map")

    # ------------------------------------------------- dtype drift --
    def _check_dtype_edges(self, fn: FnNode) -> Iterable[Finding]:
        for edge in fn.edges_out:
            decl = _param_decl_dtypes(edge.callee)
            if not decl:
                continue
            for pname, aexpr in edge.bindings:
                want = decl.get(pname)
                if want not in _BF16_TOKENS:
                    continue
                if _is_dtype_selector(aexpr):
                    continue  # explicit dtype= choice, not a leak
                got = dtype_token(aexpr, fn)
                if got in _F32_TOKENS:
                    yield Finding(
                        "dtype-upcast-f32", "warning", fn.src.rel,
                        aexpr.lineno,
                        f"'{fn.name}' passes a {got} value to "
                        f"'{edge.callee.name}' parameter "
                        f"'{pname}' declared {want}; the math runs "
                        f"(and buffers convert) at {got} -- the BN "
                        "convert-fusion upcast pattern")

    def _check_mixed_collectives(self, fn: FnNode) -> Iterable[Finding]:
        for node in own_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                cname = _call_name(node.func)
                if cname not in _COLLECTIVES or not node.args:
                    continue
                toks: Set[str] = set()
                for sub in ast.walk(node.args[0]):
                    tok = dtype_token(sub, fn)
                    if tok is not None:
                        toks.add(tok)
                floats = toks & _DTYPE_TOKENS
                if len(floats) >= 2:
                    yield Finding(
                        "dtype-mixed-collective", "warning",
                        fn.src.rel, node.lineno,
                        f"collective '{cname}' in '{fn.name}' mixes "
                        f"operand dtypes {sorted(floats)}; the "
                        "reduction computes (and the wire carries) "
                        "the widest one -- cast to one dtype first")
