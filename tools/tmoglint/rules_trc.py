"""TRC001-TRC005 — the trace-contract rules.

The production loop rests on a contract that was only ever checked
*after the fact* (RecompileTracker counters at smoke time): the
zero-recompile serving contract. These rules prove it statically, over
the traced-vs-static lattice in traceflow.py. The framing is the same
N=1-correct/N>1-wrong story as SHD: every one of these bugs is invisible
on a warm 2-CPU test box and catastrophic on hardware where one Mosaic
compile costs minutes.

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
  without passing a bucket-ladder choke point — the exact bug
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

Tests and bench files are out of scope for the whole family: they
deliberately provoke retraces (that is how RecompileTracker is proven).
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .core import Finding, LintContext, dotted_name, file_rule
from .jitgraph import jnp_aliases, numpy_aliases
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
                     "hot path without a bucket-ladder choke point")
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
                f"through pick_bucket/bucket_ladder and pad to the bucket")
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
