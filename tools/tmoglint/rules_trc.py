"""TRC001-TRC005 + PLN001 — the trace-contract and plan-precedence rules.

The production loop rests on two contracts that were only ever checked
*after the fact* (RecompileTracker counters at smoke time, planner event
logs): the zero-recompile serving contract and the PR 15 plan precedence
(explicit env > TMOG_PLAN=0 > measured model > hand default). These
rules prove both statically, over the traced-vs-static lattice in
traceflow.py. The framing is the same N=1-correct/N>1-wrong story as
SHD: every one of these bugs is invisible on a warm 2-CPU test box and
catastrophic on hardware where one Mosaic compile costs minutes.

* TRC001 — jitted-callable construction per call: `jax.jit(f)` minted
  inside a loop and invoked there, or constructed-and-called inline, or
  constructed at all inside a per-request module (serve/, fleet/). A
  fresh wrapper carries a fresh compile cache — the silent retrace
  storm. Module-level jits, decorator jits, `lru_cache`d factories and
  cache-fill stores (`cache[k] = jax.jit(...)`) are the blessed forms.
* TRC002 — python control flow on a traced value where TPU002 cannot
  see it: a *derived* traced local (`s = x.sum(); if s > 0:`) or a
  helper param that a traced call site positively binds to a tracer
  (interprocedural threading, like shardflow's `axis_name=`). Branches
  on direct nonstatic params of a jit entry stay TPU002's.
* TRC003 — call-varying host scalars (`len(batch)`, `x.shape[0]`
  arithmetic) flowing into a shape position in a hot-path module
  without passing a bucket-ladder/planner choke point — the exact bug
  the serving ladder exists to prevent.
* TRC004 — pytree structure built from unordered set iteration feeding
  a jitted/jax call: treedef order varies across processes, so the
  *shared* fleet compile cache fragments (each process compiles its own
  permutation of the same program).
* TRC005 — host-sync (`.item()`, `np.asarray`, `block_until_ready`,
  `float()`) on a jit-produced value inside a loop in a hot-path
  module: a per-tile/per-request pipeline stall, generalizing THR002
  beyond under-lock sites. Taint is positive (the value came from a
  known-jitted callable), so the tileplane's *designed* span fences
  (which sync device_put results, not jit outputs) stay silent.
* PLN001 — a read of a plan-governed TMOG_* knob (planner/plan.py's
  `_ENV_FOR` table) that bypasses `plan_fit`/`plan_serving`: the raw
  env read silently re-inverts the measured-model precedence. The two
  blessed shapes are a module-level read (an import-time pin, itself a
  hand setting) and the repo-wide fallback idiom — the env read lives
  in the `except` handler of a `try` whose body consults the planner.

Tests and bench files are out of scope for the whole family: they
deliberately provoke retraces (that is how RecompileTracker is proven)
and pin knobs directly.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .core import Finding, LintContext, dotted_name, file_rule, project_rule
from .jitgraph import jnp_aliases, numpy_aliases
from .rules_env import _env_read_name
from .traceflow import (
    CHOKED, TRACED, VARYING, hot_path_kind, is_test_path, trace_flow,
)

# -- TRC001: jitted-callable construction per call ---------------------------


@file_rule("TRC001", "jax.jit/pjit constructed per call (in a loop or a "
                     "per-request path) — fresh compile cache every time")
def check_trc001(ctx: LintContext) -> List[Finding]:
    if is_test_path(ctx.path):
        return []
    flow = trace_flow(ctx)
    kind = hot_path_kind(ctx.path)
    findings: List[Finding] = []
    for site in flow.jit_sites:
        f: Optional[Finding] = None
        if site.invoked_inline:
            f = ctx.finding(
                "TRC001", site.node,
                "`jax.jit(f)(...)` constructs and calls a fresh jitted "
                "wrapper in one expression — its compile cache dies with "
                "the expression, so EVERY call retraces; bind the jit "
                "once (module level / lru_cache factory) and call that")
        elif site.loop is not None and site.called_in_loop and \
                not site.store_subscript:
            f = ctx.finding(
                "TRC001", site.node,
                f"`{site.assigned} = jax.jit(...)` is minted and invoked "
                f"inside the same loop — a fresh wrapper (and a fresh, "
                f"empty compile cache) every iteration is the silent "
                f"retrace storm; hoist the construction out of the loop "
                f"or cache it keyed on its statics")
        elif kind == "request" and site.scope is not None:
            f = ctx.finding(
                "TRC001", site.node,
                f"jit construction inside `{site.scope.name}` in a "
                f"per-request module — serving code must only CALL "
                f"prebuilt programs (module-level jit or cached factory); "
                f"constructing here rebuilds the cache per request")
        if f is not None:
            findings.append(f)
    return findings


# -- TRC002: python branch on a derived/threaded traced value ----------------

_BRANCH_SANITIZED_CALLS = {"len", "isinstance", "callable", "hasattr"}


def _live_names(test: ast.AST) -> Set[str]:
    """Names in `test` used where a tracer would concretize: skips
    None-checks, static accessors (.shape/.ndim/...), and len()/
    isinstance() arguments — those are static under trace."""
    from .traceflow import _STATIC_ACCESSORS, _is_none_check

    out: Set[str] = set()

    def walk(node):
        if _is_none_check(node):
            return
        if isinstance(node, ast.Attribute) and \
                node.attr in _STATIC_ACCESSORS:
            return
        if isinstance(node, ast.Call):
            d = dotted_name(node.func)
            if d and d.split(".")[-1] in _BRANCH_SANITIZED_CALLS:
                return
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            out.add(node.id)
        for child in ast.iter_child_nodes(node):
            walk(child)

    walk(test)
    return out


@file_rule("TRC002", "python control flow on a derived or interprocedurally "
                     "traced value inside a jit body")
def check_trc002(ctx: LintContext) -> List[Finding]:
    if is_test_path(ctx.path):
        return []
    flow = trace_flow(ctx)
    findings: List[Finding] = []
    for fi in flow.graph.traced_funcs():
        env = flow.traced_env(fi)
        if isinstance(fi.node, ast.Lambda):
            continue
        direct_params = set()
        if fi.is_direct_jit:
            # branches directly on a nonstatic param of the jit entry are
            # TPU002's finding; TRC002 only adds what the lattice proves
            # beyond it (derived locals, threaded helper params)
            from .traceflow import _param_names
            direct_params = {p for p in _param_names(fi.node)
                             if p not in fi.static_params and p != "self"}
        for sub in flow.graph._own_nodes(fi):
            if not isinstance(sub, (ast.If, ast.While)):
                continue
            hit = sorted(n for n in _live_names(sub.test)
                         if env.get(n) == TRACED and n not in direct_params)
            if not hit:
                continue
            threaded = set(hit) & set(flow.helper_param_states(fi))
            how = ("bound to a tracer by a traced call site"
                   if threaded else "derived from traced values")
            f = ctx.finding(
                "TRC002", sub,
                f"python `{type(sub).__name__.lower()}` on {hit} in "
                f"trace-reachable `{fi.name}` — the value is {how}, so "
                f"this branch concretizes under jit (trace error) or "
                f"forces a retrace per value; use lax.cond/jnp.where or "
                f"hoist the decision to a static arg")
            if f is not None:
                findings.append(f)
    return findings


# -- TRC003: unbucketed call-varying shapes in hot paths ---------------------

# array creators whose FIRST positional arg (all args for arange) is a
# shape: a varying value here is a fresh XLA program per call
_SHAPE_CREATORS = {"zeros", "ones", "empty", "full", "arange"}


@file_rule("TRC003", "call-varying scalar reaches a shape position in a "
                     "hot path without a bucket-ladder/planner choke point")
def check_trc003(ctx: LintContext) -> List[Finding]:
    if hot_path_kind(ctx.path) is None:
        return []
    flow = trace_flow(ctx)
    num_alias = numpy_aliases(ctx) | jnp_aliases(ctx) | {"np", "jnp"}
    findings: List[Finding] = []
    for fi in flow.graph.all_funcs:
        if fi.traced or isinstance(fi.node, ast.Lambda):
            continue
        env = flow.shape_env(fi)
        for node in flow.graph._own_nodes(fi):
            if not isinstance(node, ast.Call):
                continue
            shape_args: List[ast.AST] = []
            d = dotted_name(node.func)
            if d:
                parts = d.split(".")
                if parts[0] in num_alias and \
                        parts[-1] in _SHAPE_CREATORS and node.args:
                    shape_args = list(node.args) \
                        if parts[-1] == "arange" else [node.args[0]]
            if not shape_args and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "reshape":
                shape_args = list(node.args)
            if not shape_args:
                continue
            state = "static"
            for a in shape_args:
                st = flow._shape_state(a, env)
                if st == VARYING:
                    state = VARYING
                    break
                if st == CHOKED:
                    state = CHOKED
            flow.record_shape_site(fi, node, state)
            if state != VARYING:
                continue
            f = ctx.finding(
                "TRC003", node,
                f"call-varying scalar reaches the shape of `{d or 'reshape'}"
                f"()` in hot-path `{fi.name}` — every distinct size is a "
                f"fresh XLA program (minutes of Mosaic compile on "
                f"hardware, invisible on a warm test box); route the size "
                f"through pick_bucket/bucket_ladder or a planned_* getter "
                f"and pad to the bucket")
            if f is not None:
                findings.append(f)
    return findings


# -- TRC004: treedef nondeterminism from unordered iteration -----------------

_SET_METHOD_TAILS = {"intersection", "union", "difference",
                     "symmetric_difference"}


def _is_unordered(expr: ast.AST) -> bool:
    if isinstance(expr, (ast.Set, ast.SetComp)):
        return True
    if isinstance(expr, ast.Call):
        d = dotted_name(expr.func)
        if d and d.split(".")[-1] in {"set", "frozenset"}:
            return True
        if isinstance(expr.func, ast.Attribute) and \
                expr.func.attr in _SET_METHOD_TAILS:
            return True
    return False


@file_rule("TRC004", "pytree built from unordered set iteration feeds a "
                     "jitted call — treedef order fragments the shared "
                     "compile cache across processes")
def check_trc004(ctx: LintContext) -> List[Finding]:
    if is_test_path(ctx.path):
        return []
    flow = trace_flow(ctx)
    jaxish = jnp_aliases(ctx) | {"jnp", "jax", "lax"}
    jit_callables = set(flow.jit_names)
    for fi in flow.graph.all_funcs:
        if fi.is_direct_jit and not isinstance(fi.node, ast.Lambda):
            jit_callables.add(fi.name)

    def feeds_jit(call: ast.Call) -> bool:
        d = dotted_name(call.func)
        if not d:
            return False
        return d.split(".")[0] in jaxish or d.split(".")[0] in \
            jit_callables

    findings: List[Finding] = []
    scopes: List[Tuple[object, ast.AST]] = [(None, ctx.tree)]
    for fi in flow.graph.all_funcs:
        if not isinstance(fi.node, ast.Lambda):
            scopes.append((fi, fi.node))
    func_nodes = {f.node for f in flow.graph.all_funcs}

    def module_own(tree: ast.AST) -> List[ast.AST]:
        out: List[ast.AST] = []

        def w(n):
            for c in ast.iter_child_nodes(n):
                if c in func_nodes:
                    continue
                out.append(c)
                w(c)

        w(tree)
        return out

    for fi, root in scopes:
        own = list(flow.graph._own_nodes(fi)) if fi is not None \
            else module_own(root)
        # names whose contents came from unordered iteration
        tainted: Set[str] = set()
        comp_nodes: Dict[ast.AST, ast.AST] = {}
        for node in own:
            if isinstance(node, (ast.ListComp, ast.DictComp,
                                 ast.GeneratorExp)):
                if any(_is_unordered(g.iter) for g in node.generators):
                    comp_nodes[node] = node
            elif isinstance(node, ast.Assign) and \
                    isinstance(node.value, (ast.ListComp, ast.DictComp,
                                            ast.GeneratorExp)):
                if any(_is_unordered(g.iter)
                       for g in node.value.generators):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            tainted.add(t.id)
            elif isinstance(node, ast.For) and _is_unordered(node.iter):
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Call) and \
                            isinstance(sub.func, ast.Attribute) and \
                            sub.func.attr in ("append", "add", "update") \
                            and isinstance(sub.func.value, ast.Name):
                        tainted.add(sub.func.value.id)
                    elif isinstance(sub, ast.Subscript) and \
                            isinstance(sub.ctx, ast.Store) and \
                            isinstance(sub.value, ast.Name):
                        tainted.add(sub.value.id)
        if not tainted and not comp_nodes:
            continue
        for node in own:
            if not (isinstance(node, ast.Call) and feeds_jit(node)):
                continue
            for arg in list(node.args) + [k.value for k in node.keywords]:
                culprit = None
                for sub in ast.walk(arg):
                    if sub in comp_nodes:
                        culprit = sub
                        break
                    if isinstance(sub, ast.Name) and sub.id in tainted:
                        culprit = sub
                        break
                if culprit is None:
                    continue
                what = f"`{culprit.id}`" if isinstance(
                    culprit, ast.Name) else "a comprehension"
                f = ctx.finding(
                    "TRC004", node,
                    f"{what} built from unordered set iteration feeds "
                    f"jax call `{dotted_name(node.func)}` — set order "
                    f"varies across processes, so each fleet process "
                    f"compiles its own treedef permutation of the same "
                    f"program; wrap the iteration in sorted()")
                if f is not None:
                    findings.append(f)
                break
    return findings


# -- TRC005: host-sync on jit outputs in hot-path loops ----------------------

_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_SYNC_CASTS = {"float", "int", "bool"}
_NP_SYNC = {"asarray", "array"}


@file_rule("TRC005", "host-sync on a jit-produced value inside a hot-path "
                     "loop (per-tile/per-request pipeline stall)")
def check_trc005(ctx: LintContext) -> List[Finding]:
    if hot_path_kind(ctx.path) is None:
        return []
    flow = trace_flow(ctx)
    np_alias = numpy_aliases(ctx) | {"np"}
    # callables whose results are device values produced by a jitted
    # program THIS module owns: names bound from jax.jit(...) plus
    # decorator-jitted defs. Positive taint only — syncing a
    # device_put result or a cross-module value is the caller's design.
    jit_callables = set(flow.jit_names)
    for fi in flow.graph.all_funcs:
        if fi.is_direct_jit and not isinstance(fi.node, ast.Lambda):
            jit_callables.add(fi.name)
    if not jit_callables:
        return []
    findings: List[Finding] = []
    for fi in flow.graph.all_funcs:
        if fi.traced or isinstance(fi.node, ast.Lambda):
            continue
        own = list(flow.graph._own_nodes(fi))
        tainted: Set[str] = set()
        for node in own:
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call):
                d = dotted_name(node.value.func)
                if d and d.split(".")[0] in jit_callables:
                    for t in node.targets:
                        for el in (t.elts if isinstance(
                                t, (ast.Tuple, ast.List)) else [t]):
                            if isinstance(el, ast.Name):
                                tainted.add(el.id)
        if not tainted:
            continue
        loops = [n for n in own if isinstance(n, (ast.For, ast.While))]
        for loop in loops:
            for node in ast.walk(loop):
                hit: Optional[str] = None
                if isinstance(node, ast.Call):
                    d = dotted_name(node.func)
                    arg0 = node.args[0] if node.args else None
                    if isinstance(node.func, ast.Attribute) and \
                            node.func.attr in _SYNC_METHODS and \
                            isinstance(node.func.value, ast.Name) and \
                            node.func.value.id in tainted:
                        hit = f".{node.func.attr}()"
                    elif d and isinstance(arg0, ast.Name) and \
                            arg0.id in tainted:
                        parts = d.split(".")
                        if parts[-1] == "block_until_ready" or \
                                (parts[0] in np_alias
                                 and parts[-1] in _NP_SYNC) or \
                                d in _SYNC_CASTS:
                            hit = f"{d}()"
                if hit is None:
                    continue
                f = ctx.finding(
                    "TRC005", node,
                    f"`{hit}` blocks on a jitted result inside a loop in "
                    f"hot-path `{fi.name}` — the host stalls the "
                    f"per-tile/per-request pipeline every iteration "
                    f"(async dispatch exists so the next step can "
                    f"overlap); sync once after the loop, or keep the "
                    f"reduction on device")
                if f is not None:
                    findings.append(f)
    return findings


# -- PLN001: plan-precedence bypass ------------------------------------------

#: snapshot of planner/plan.py's _ENV_FOR values — the fallback when the
#: scan does not include the planner (fixture scans); a scanned
#: planner/plan.py always wins so the governed set cannot drift
_GOVERNED_FALLBACK = frozenset({
    "TMOG_GRID_FUSE", "TMOG_GRID_FUSE_HBM_LANES", "TMOG_GRID_FUSE_OUT_MB",
    "TMOG_TILE_MB", "TMOG_STATS_TILE_ROWS", "TMOG_SCORE_TILE_ROWS",
    "TMOG_TILE_PREFETCH", "TMOG_INGEST_WORKERS",
})

_PLANNER_GETTER_TAILS = {"plan_serving", "plan_fit", "grid_fuse_enabled",
                         "glm_streamed_min_rows"}


def _governed_knobs(ctxs: Sequence[LintContext]) -> Set[str]:
    """The plan-governed knob set: string values of the module-level
    `_ENV_FOR = {...}` literal in any scanned planner/plan.py."""
    out: Set[str] = set()
    for ctx in ctxs:
        if not ctx.path.endswith("planner/plan.py"):
            continue
        for node in ctx.tree.body:
            if not (isinstance(node, ast.Assign)
                    and any(isinstance(t, ast.Name) and t.id == "_ENV_FOR"
                            for t in node.targets)
                    and isinstance(node.value, ast.Dict)):
                continue
            for v in node.value.values:
                if isinstance(v, ast.Constant) and \
                        isinstance(v.value, str) and \
                        v.value.startswith("TMOG_"):
                    out.add(v.value)
    return out or set(_GOVERNED_FALLBACK)


def _consults_planner(try_node: ast.Try) -> bool:
    """Does the TRY BODY (not its handlers) reach for the planner? The
    fallback idiom is only blessed when the primary path really was the
    precedence ladder."""
    for stmt in try_node.body:
        for sub in ast.walk(stmt):
            if isinstance(sub, ast.ImportFrom) and sub.module and \
                    "planner" in sub.module:
                return True
            if isinstance(sub, ast.Call):
                d = dotted_name(sub.func)
                tail = d.split(".")[-1] if d else ""
                if tail in _PLANNER_GETTER_TAILS or \
                        tail.startswith("planned_"):
                    return True
    return False


def _pln001_scoped(path: str) -> bool:
    parts = path.split("/")
    base = parts[-1]
    if base.startswith("test_") or base.startswith("bench") or \
            base == "conftest.py":
        return False
    dirs = set(parts[:-1])
    if dirs & {"tests", "tools", "planner"}:
        # the planner itself OWNS the governed reads (that is where the
        # precedence ladder lives); tests/bench pin knobs by design
        return False
    return True


@project_rule("PLN001", "plan-governed TMOG_* knob read outside the "
                        "planner precedence ladder (raw env bypasses the "
                        "measured model)")
def check_pln001(ctxs: Sequence[LintContext]) -> List[Finding]:
    governed = _governed_knobs(ctxs)
    findings: List[Finding] = []
    for ctx in ctxs:
        if not _pln001_scoped(ctx.path) or "TMOG_" not in ctx.source:
            continue

        def walk(node: ast.AST, in_func: bool,
                 handler_tries: List[ast.Try]) -> None:
            hit = _env_read_name(node)
            if hit is not None and not (
                    isinstance(node, ast.Subscript)
                    and not isinstance(node.ctx, ast.Load)):
                anchor, name = hit
                if name in governed:
                    if not in_func:
                        pass  # module-level read: an import-time pin is
                        #       itself a hand setting (ops/trees.py)
                    elif any(_consults_planner(t)
                             for t in handler_tries):
                        pass  # the blessed fallback idiom: env read in
                        #       the except arm of a planner consult
                    else:
                        f = ctx.finding(
                            "PLN001", anchor,
                            f"`{name}` is plan-governed (planner/plan.py "
                            f"_ENV_FOR) but read here outside the "
                            f"precedence ladder — a raw env read beats "
                            f"the measured model even when the user "
                            f"never set the knob; call the planned_* "
                            f"getter (its except-fallback may read the "
                            f"env) or read at module level")
                        if f is not None:
                            findings.append(f)
            for child in ast.iter_child_nodes(node):
                c_in_func = in_func or isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda))
                c_tries = handler_tries
                if isinstance(node, ast.Try) and \
                        isinstance(child, ast.ExceptHandler):
                    c_tries = handler_tries + [node]
                walk(child, c_in_func, c_tries)

        walk(ctx.tree, False, [])
    return findings
