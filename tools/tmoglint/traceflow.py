"""Traced-vs-static value lattice over jitgraph's reachability facts.

jitgraph answers *which* functions can run under a JAX trace; traceflow
answers *what the names inside and around them hold*. Three abstract
interpretations share one ModuleGraph and one ancestor-annotated walk:

* **traced-value states** (TRC002): inside every trace-reachable
  function, each local name is ``TRACED`` (may hold a tracer) or
  ``STATIC`` (a python value the trace pins). Params start from the
  jit's ``static_argnums/argnames`` declaration plus scalar
  annotations; *helper* params get their states from the arguments the
  traced call sites actually pass — the same interprocedural threading
  shardflow does for ``axis_name=``. Assignments propagate states
  forward; ``.shape``/``.ndim``/``len()``/``is None``/``isinstance``
  reads are static under trace and sanitize.

* **host shape flow** (TRC003): inside *host* functions of hot-path
  files, each scalar is ``VARYING`` (derived from ``len(arg)`` /
  ``arg.shape[i]`` — a different number every call, i.e. a fresh XLA
  program every call), ``CHOKED`` (routed through a bucket-ladder
  choke point, the only shapes the zero-recompile contract
  allows), or ``STATIC``. A scalar *parameter* inherits the join of
  what its intra-module call sites pass, so a ``bucket`` threaded from
  ``pick_bucket`` stays proven-choked through helper calls.

* **jit-construction sites** (TRC001): every non-decorator
  ``jax.jit``/``pjit``/``partial(jit, ...)`` call, annotated with its
  enclosing function, loop ancestry, assignment target and whether the
  fresh callable is invoked inline or inside the same loop.

Everything is stdlib-``ast`` only and cached per file on the ctx (like
``module_graph``): the walk is the expensive part, the five TRC
rules are queries. ``TraceFlow.stats`` counts what was actually
interpreted so tests can assert the analysis SAW the hot paths rather
than silently skipping them (the SHD non-vacuity discipline).
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .core import LintContext, dotted_name
from .jitgraph import FuncInfo, ModuleGraph, jnp_aliases, module_graph

# -- lattice values ----------------------------------------------------------
TRACED = "traced"
STATIC = "static"
VARYING = "varying"
CHOKED = "choked"

# host calls that return a *bucketed* size — the only values the
# zero-recompile contract lets into a shape position on a hot path.
# Matched on the last dotted component so `self.pick_bucket(...)` and
# `TP.tile_rows_for(...)` both count.
CHOKE_TAILS = {
    "pick_bucket", "bucket_ladder", "tile_rows_for", "stats_row_block",
    "stream_tile_rows_default", "score_tile_rows_default",
    "tile_budget_bytes", "tile_prefetch_depth", "ingest_workers",
}

# accessors whose result is a static python value under trace
_STATIC_ACCESSORS = {"shape", "ndim", "dtype", "size", "itemsize"}
# builtins that are static under trace regardless of their argument
_STATIC_CALLS = {"len", "isinstance", "callable", "type", "range",
                 "enumerate", "zip", "hasattr", "getattr"}
# jax.* host introspection that returns plain python values, not tracers
# (`use_matmul = jax.default_backend() == "tpu"` is a static route pick)
_STATIC_JAX_CALLS = {"default_backend", "device_count",
                     "local_device_count", "devices", "local_devices",
                     "process_index", "process_count"}
_SCALAR_ANN_TOKENS = ("int", "float", "bool", "str", "bytes")
_ARRAY_ANN_TOKENS = ("Array", "ndarray")

# -- path scoping ------------------------------------------------------------
# per-request hot paths: one XLA program total is the contract
_REQUEST_DIRS = {"serve", "fleet"}
# per-tile hot paths: one program per fixed tile SHAPE is the contract.
# Named files, not whole dirs: readers/readers.py and monitor/offline.py
# are fit-time/offline code where one compile per dataset is the design.
_TILE_FILES = {"tileplane.py", "ingest.py", "streaming.py", "window.py"}
_TILE_DIRS = {"parallel", "readers", "monitor"}


def hot_path_kind(path: str) -> Optional[str]:
    """'request' / 'tile' when `path` is a production hot-path module,
    None otherwise. Tests and bench deliberately provoke retraces (that
    is how RecompileTracker is tested) so they are never hot paths."""
    if is_test_path(path):
        return None
    parts = path.split("/")
    dirs = set(parts[:-1])
    if "tools" in dirs:
        return None
    if dirs & _REQUEST_DIRS:
        return "request"
    if parts[-1] in _TILE_FILES and dirs & _TILE_DIRS:
        return "tile"
    return None


def is_test_path(path: str) -> bool:
    """Out of scope for the whole TRC family: tests deliberately
    provoke retraces (that is how RecompileTracker is proven) and bench
    deliberately constructs jits inline (it measures the compile)."""
    parts = path.split("/")
    return "tests" in parts[:-1] or parts[-1].startswith("test_") \
        or parts[-1].startswith("bench")


# -- shared AST plumbing -----------------------------------------------------

def _ann_of(arg: ast.arg) -> str:
    return ast.unparse(arg.annotation) if arg.annotation is not None else ""


def _scalar_annotated(ann: str) -> bool:
    if not ann or any(t in ann for t in _ARRAY_ANN_TOKENS):
        return False
    return any(t in ann.replace("Optional", "").replace("[", " ")
               .replace("]", " ").replace(",", " ").split()
               for t in _SCALAR_ANN_TOKENS)


def _positional_params(call: ast.Call, params: List[str]) -> List[str]:
    """The positional-binding view of `params` for this call site: a
    bound-method call (`self.helper(x)`) supplies the receiver
    implicitly, so positional args bind from the second param on —
    without the shift, `self._assemble(padded, bucket)` would bind
    `padded` to `self` and `bucket` to `records`, and the poison/trace
    threading would silently miss the real `bucket` param."""
    if params and params[0] in ("self", "cls") and \
            isinstance(call.func, ast.Attribute):
        return params[1:]
    return params


def _is_none_check(node: ast.AST) -> bool:
    return (isinstance(node, ast.Compare)
            and all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops)
            and all(isinstance(c, ast.Constant) and c.value is None
                    for c in node.comparators))


def _param_names(node: ast.AST) -> List[str]:
    args = node.args
    return [a.arg for a in getattr(args, "posonlyargs", [])
            + args.args + args.kwonlyargs]


class JitSite:
    """One non-decorator jit/pjit construction call."""

    def __init__(self, node: ast.Call, scope: Optional[FuncInfo],
                 loop: Optional[ast.AST], assigned: Optional[str],
                 store_subscript: bool, invoked_inline: bool):
        self.node = node
        self.scope = scope              # enclosing function, None = module
        self.loop = loop                # innermost for/while ancestor
        self.assigned = assigned        # `x = jax.jit(...)` target name
        self.store_subscript = store_subscript  # `cache[k] = jax.jit(...)`
        self.invoked_inline = invoked_inline    # `jax.jit(f)(...)`
        self.called_in_loop = False     # assigned name called in same loop


class TraceFlow:
    """All three analyses for one parsed module."""

    def __init__(self, ctx: LintContext):
        self.ctx = ctx
        self.graph: ModuleGraph = module_graph(ctx)
        self.jnp = jnp_aliases(ctx) | {"jnp", "jax", "lax"}
        self.stats: Dict[str, int] = {
            "traced_funcs": 0, "call_bindings": 0, "jit_sites": 0,
            "host_funcs": 0, "shape_sites": 0,
        }
        # names assigned from jax.jit(...)/pjit(...) anywhere in the file
        # (module level or local) — TRC005's dispatch-taint sources
        self.jit_names: Set[str] = set()
        self.jit_sites: List[JitSite] = []
        #: traced-value states per traced function, name -> TRACED|STATIC
        self._traced_env: Dict[FuncInfo, Dict[str, str]] = {}
        #: interprocedural param states observed at traced call sites
        self._helper_params: Dict[FuncInfo, Dict[str, str]] = {}
        #: host shape states per hot-path host function
        self._shape_env: Dict[FuncInfo, Dict[str, str]] = {}
        #: every interpreted shape-position argument:
        #: (host fn, arg node, lattice state)
        self.shape_sites: List[Tuple[FuncInfo, ast.AST, str]] = []
        # decorator calls must not register as constructions
        self._decorator_nodes: Set[ast.AST] = set()
        for fi in self.graph.all_funcs:
            if isinstance(fi.node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in fi.node.decorator_list:
                    for sub in ast.walk(dec):
                        self._decorator_nodes.add(sub)
        self._collect_jit_sites()
        self._bind_helper_params()
        for fi in self.graph.traced_funcs():
            self._traced_env[fi] = self._interpret_traced(fi)
            self.stats["traced_funcs"] += 1
        if hot_path_kind(ctx.path):
            self._interpret_shapes()

    # -- jit constructions (TRC001) -----------------------------------------

    def _is_jit_construction(self, call: ast.Call) -> bool:
        d = dotted_name(call.func)
        if d and d.split(".")[-1] in {"jit", "pjit"}:
            return True
        # partial(jax.jit, ...) builds a jit factory; calling jit through
        # it is still a construction
        if d and d.split(".")[-1] == "partial" and call.args:
            inner = dotted_name(call.args[0])
            return bool(inner and inner.split(".")[-1] in {"jit", "pjit"})
        return False

    def _collect_jit_sites(self) -> None:
        scope_by_node = {fi.node: fi for fi in self.graph.all_funcs}

        def walk(node: ast.AST, scope: Optional[FuncInfo],
                 loop: Optional[ast.AST], stmt: Optional[ast.stmt]):
            for child in ast.iter_child_nodes(node):
                c_scope = scope_by_node.get(child, scope)
                c_loop = loop
                if child in scope_by_node:
                    c_loop = None    # loops do not cross function bodies
                elif isinstance(child, (ast.For, ast.While)):
                    c_loop = child
                c_stmt = child if isinstance(child, ast.stmt) else stmt
                if isinstance(child, ast.Call) and \
                        child not in self._decorator_nodes and \
                        self._is_jit_construction(child):
                    assigned = None
                    store_sub = False
                    if isinstance(c_stmt, ast.Assign) and \
                            c_stmt.value is child:
                        for t in c_stmt.targets:
                            if isinstance(t, ast.Name):
                                assigned = t.id
                                self.jit_names.add(t.id)
                            elif isinstance(t, ast.Subscript):
                                store_sub = True
                    invoked = isinstance(node, ast.Call) and \
                        node.func is child
                    self.jit_sites.append(JitSite(
                        child, c_scope, c_loop, assigned, store_sub,
                        invoked))
                    self.stats["jit_sites"] += 1
                walk(child, c_scope, c_loop, c_stmt)

        walk(self.ctx.tree, None, None, None)
        # second pass: is a loop-constructed callable invoked in its loop?
        for site in self.jit_sites:
            if site.loop is None or site.assigned is None:
                continue
            for sub in ast.walk(site.loop):
                if isinstance(sub, ast.Call) and \
                        isinstance(sub.func, ast.Name) and \
                        sub.func.id == site.assigned:
                    site.called_in_loop = True
                    break

    # -- traced-value interpretation (TRC002) -------------------------------

    def _bind_helper_params(self) -> None:
        """Thread tracedness through calls: when a traced function calls a
        lexically-resolved helper, the helper's params take the state of
        the argument expressions (join over call sites: any traced call
        site makes the param traced)."""
        # iterate to a fixpoint: bindings can make a helper's locals
        # traced, which can make ITS callees' params traced
        for _ in range(3):
            changed = False
            for fi in self.graph.traced_funcs():
                env = self._interpret_traced(fi)
                for node in self.graph._own_nodes(fi):
                    if not isinstance(node, ast.Call):
                        continue
                    targets = self.graph._func_args_of(node.func, fi)
                    if not targets:
                        continue
                    for target in targets:
                        if not target.traced:
                            continue
                        params = _param_names(target.node) \
                            if not isinstance(target.node, ast.Lambda) \
                            else [a.arg for a in target.node.args.args]
                        bound = self._helper_params.setdefault(target, {})
                        pos = _positional_params(node, params)
                        for i, arg in enumerate(node.args):
                            if i >= len(pos):
                                break
                            st = self._expr_traced(arg, env)
                            prev = bound.get(pos[i], STATIC)
                            if st == TRACED and prev != TRACED:
                                bound[pos[i]] = TRACED
                                changed = True
                            else:
                                bound.setdefault(pos[i], prev)
                        for kw in node.keywords:
                            if kw.arg is None or kw.arg not in params:
                                continue
                            st = self._expr_traced(kw.value, env)
                            prev = bound.get(kw.arg, STATIC)
                            if st == TRACED and prev != TRACED:
                                bound[kw.arg] = TRACED
                                changed = True
                            else:
                                bound.setdefault(kw.arg, prev)
                        self.stats["call_bindings"] += 1
            if not changed:
                break

    def _interpret_traced(self, fi: FuncInfo) -> Dict[str, str]:
        env: Dict[str, str] = {}
        node = fi.node
        if isinstance(node, ast.Lambda):
            params = [a.arg for a in node.args.args]
            anns: Dict[str, str] = {}
        else:
            params = _param_names(node)
            args = node.args
            anns = {a.arg: _ann_of(a) for a in
                    getattr(args, "posonlyargs", []) + args.args
                    + args.kwonlyargs}
        bound = self._helper_params.get(fi, {})
        for p in params:
            if p == "self" or p in fi.static_params:
                env[p] = STATIC
            elif _scalar_annotated(anns.get(p, "")):
                env[p] = STATIC
            elif fi.is_direct_jit:
                env[p] = TRACED
            elif p in bound:
                env[p] = bound[p]
            else:
                # helper never called from interpreted code: stay silent
                # rather than guess TRACED (precision over recall — the
                # direct-jit entry still covers the real hazard)
                env[p] = STATIC
        if not isinstance(node, ast.Lambda):
            if node.args.vararg is not None:
                env[node.args.vararg.arg] = TRACED if fi.is_direct_jit \
                    else STATIC
            if node.args.kwarg is not None:
                env[node.args.kwarg.arg] = STATIC
        # forward propagation over assignments, two passes so a name
        # assigned below its first use in a loop still converges
        for _ in range(2):
            for sub in self.graph._own_nodes(fi):
                if isinstance(sub, ast.Assign):
                    st = self._expr_traced(sub.value, env)
                    for t in sub.targets:
                        for el in (t.elts if isinstance(
                                t, (ast.Tuple, ast.List)) else [t]):
                            if isinstance(el, ast.Name):
                                if env.get(el.id) != TRACED:
                                    env[el.id] = st
                elif isinstance(sub, ast.AugAssign) and \
                        isinstance(sub.target, ast.Name):
                    st = self._expr_traced(sub.value, env)
                    if st == TRACED:
                        env[sub.target.id] = TRACED
        return env

    def _expr_traced(self, expr: ast.AST, env: Dict[str, str]) -> str:
        """TRACED iff `expr` may evaluate to a tracer given `env`."""
        if _is_none_check(expr):
            return STATIC
        if isinstance(expr, ast.Constant):
            return STATIC
        if isinstance(expr, ast.Name):
            return env.get(expr.id, STATIC)
        if isinstance(expr, ast.Attribute):
            if expr.attr in _STATIC_ACCESSORS:
                return STATIC
            return self._expr_traced(expr.value, env)
        if isinstance(expr, ast.Subscript):
            # x.shape[0] stays static; tracer[i] stays traced
            return self._expr_traced(expr.value, env)
        if isinstance(expr, ast.Call):
            d = dotted_name(expr.func)
            tail = d.split(".")[-1] if d else ""
            if d in _STATIC_CALLS or tail in _STATIC_CALLS:
                return STATIC
            if tail in _STATIC_JAX_CALLS:
                return STATIC
            root = d.split(".")[0] if d else ""
            if root in self.jnp or root in self.jit_names:
                return TRACED
            if any(self._expr_traced(a, env) == TRACED
                   for a in list(expr.args)
                   + [k.value for k in expr.keywords]):
                return TRACED
            if isinstance(expr.func, ast.Attribute):
                # method on a traced value (x.sum(), x.astype(...))
                return self._expr_traced(expr.func.value, env)
            return STATIC
        if isinstance(expr, (ast.BinOp, ast.UnaryOp, ast.BoolOp,
                             ast.Compare, ast.IfExp)):
            return TRACED if any(
                self._expr_traced(c, env) == TRACED
                for c in ast.iter_child_nodes(expr)
                if isinstance(c, ast.expr)) else STATIC
        if isinstance(expr, (ast.Tuple, ast.List)):
            return TRACED if any(
                self._expr_traced(e, env) == TRACED for e in expr.elts) \
                else STATIC
        return STATIC

    def traced_env(self, fi: FuncInfo) -> Dict[str, str]:
        return self._traced_env.get(fi, {})

    def helper_param_states(self, fi: FuncInfo) -> Dict[str, str]:
        return self._helper_params.get(fi, {})

    # -- host shape flow (TRC003) -------------------------------------------

    def _interpret_shapes(self) -> None:
        host = [fi for fi in self.graph.all_funcs
                if not fi.traced
                and not isinstance(fi.node, ast.Lambda)]
        # pass 1: per-function envs; params start unpoisoned (a param is
        # presumed shape-safe until some caller passes a varying value)
        param_join: Dict[FuncInfo, Dict[str, str]] = {}
        for fi in host:
            self._shape_env[fi] = self._shape_env_of(fi, {})
            self.stats["host_funcs"] += 1
        # poison params from intra-module call sites TO A FIXPOINT: a
        # `bucket` param is proven choked only because every caller
        # passes a choked value; one varying call site poisons it, and
        # the poison must ride through helper chains (score_batch ->
        # _assemble -> _bucket_columns is two hops in the real engine)
        for _ in range(len(host) + 1):
            changed = False
            for fi in host:
                env = self._shape_env[fi]
                for node in self.graph._own_nodes(fi):
                    if not isinstance(node, ast.Call):
                        continue
                    for target in self.graph._func_args_of(node.func, fi):
                        params = _param_names(target.node) \
                            if not isinstance(target.node, ast.Lambda) \
                            else []
                        bound = param_join.setdefault(target, {})
                        pos = _positional_params(node, params)
                        for i, arg in enumerate(node.args):
                            if i >= len(pos):
                                break
                            if self._shape_state(arg, env) == VARYING \
                                    and bound.get(pos[i]) != VARYING:
                                bound[pos[i]] = VARYING
                                changed = True
                        for kw in node.keywords:
                            if kw.arg in params and self._shape_state(
                                    kw.value, env) == VARYING and \
                                    bound.get(kw.arg) != VARYING:
                                bound[kw.arg] = VARYING
                                changed = True
            if not changed:
                break
            for fi in host:
                if fi in param_join:
                    self._shape_env[fi] = self._shape_env_of(
                        fi, param_join[fi])

    def _shape_env_of(self, fi: FuncInfo,
                      param_seed: Dict[str, str]) -> Dict[str, str]:
        env: Dict[str, str] = dict(param_seed)
        for _ in range(2):
            for sub in self.graph._own_nodes(fi):
                if isinstance(sub, ast.Assign):
                    st = self._shape_state(sub.value, env)
                    for t in sub.targets:
                        for el in (t.elts if isinstance(
                                t, (ast.Tuple, ast.List)) else [t]):
                            if isinstance(el, ast.Name):
                                if env.get(el.id) != VARYING:
                                    env[el.id] = st
                elif isinstance(sub, ast.AugAssign) and \
                        isinstance(sub.target, ast.Name):
                    if self._shape_state(sub.value, env) == VARYING:
                        env[sub.target.id] = VARYING
        return env

    def _is_choke_call(self, call: ast.Call) -> bool:
        d = dotted_name(call.func)
        if not d:
            return False
        tail = d.split(".")[-1]
        return tail in CHOKE_TAILS

    def _shape_state(self, expr: ast.AST, env: Dict[str, str]) -> str:
        """VARYING iff `expr` is a call-varying host scalar; CHOKED when
        it provably went through a bucket choke point."""
        if isinstance(expr, ast.Constant):
            return STATIC
        if isinstance(expr, ast.Name):
            return env.get(expr.id, STATIC)
        if isinstance(expr, ast.Call):
            if self._is_choke_call(expr):
                return CHOKED
            d = dotted_name(expr.func)
            tail = d.split(".")[-1] if d else ""
            if tail == "len":
                # len() of a live argument varies per call; len() of a
                # self-attribute or module constant does not (schemas
                # are fixed at model load, not per request)
                arg = expr.args[0] if expr.args else None
                if isinstance(arg, ast.Name):
                    return VARYING
                return STATIC
            if tail in ("min", "max", "sum"):
                states = [self._shape_state(a, env) for a in expr.args]
                if VARYING in states:
                    return VARYING
                if CHOKED in states:
                    return CHOKED
                return STATIC
            return STATIC
        if isinstance(expr, ast.Subscript):
            # x.shape[i] of a live argument varies per call
            if isinstance(expr.value, ast.Attribute) and \
                    expr.value.attr == "shape" and \
                    isinstance(expr.value.value, ast.Name):
                return VARYING
            return self._shape_state(expr.value, env)
        if isinstance(expr, ast.Attribute):
            if expr.attr == "shape" and isinstance(expr.value, ast.Name):
                return VARYING
            return STATIC
        if isinstance(expr, (ast.BinOp, ast.UnaryOp, ast.IfExp)):
            states = [self._shape_state(c, env)
                      for c in ast.iter_child_nodes(expr)
                      if isinstance(c, ast.expr)]
            if VARYING in states:
                return VARYING
            if CHOKED in states:
                return CHOKED
            return STATIC
        if isinstance(expr, (ast.Tuple, ast.List)):
            states = [self._shape_state(e, env) for e in expr.elts]
            if VARYING in states:
                return VARYING
            if CHOKED in states:
                return CHOKED
            return STATIC
        return STATIC

    def shape_env(self, fi: FuncInfo) -> Dict[str, str]:
        return self._shape_env.get(fi, {})

    def record_shape_site(self, fi: FuncInfo, node: ast.AST,
                          state: str) -> None:
        self.shape_sites.append((fi, node, state))
        self.stats["shape_sites"] += 1


def trace_flow(ctx: LintContext) -> TraceFlow:
    """One TraceFlow per file, shared by the TRC rules (the lattice walk
    is the expensive part; the rules are queries)."""
    tf = getattr(ctx, "_trace_flow", None)
    if tf is None:
        tf = TraceFlow(ctx)
        ctx._trace_flow = tf
    return tf
