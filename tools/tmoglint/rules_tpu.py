"""TPU discipline rules TPU001-TPU005.

Each rule only fires inside *trace-reachable* code (see jitgraph.py), except
TPU003 which is path-scoped to kernel directories and TPU005 which inspects
HOST functions (timing code is host code by definition). Rationale for each
rule is in docs/static_analysis.md, tied to the measured rooflines in
docs/performance.md.
"""
from __future__ import annotations

import ast
from typing import List, Optional, Set, Tuple

from .core import Finding, LintContext, dotted_name, file_rule
from .jitgraph import jnp_aliases, module_graph, numpy_aliases

# -- shared precision helpers ------------------------------------------------
# A cast/branch/format only fires when it can actually see a *tracer*: a
# parameter of the traced function that is neither static, nor annotated as a
# plain python scalar, nor used solely through static accessors
# (.shape/.ndim/.dtype/.size/len()). `x is None` checks are static under
# trace (None never traces) and are ignored wholesale.

_SCALAR_ANN_TOKENS = ("int", "float", "bool", "str", "bytes")
_ARRAY_ANN_TOKENS = ("Array", "ndarray")
_STATIC_ACCESSORS = {"shape", "ndim", "dtype", "size", "itemsize"}


def _param_annotations(fi) -> dict:
    node = fi.node
    if isinstance(node, ast.Lambda):
        return {a.arg: "" for a in node.args.args}
    out = {}
    args = node.args
    for a in args.args + args.kwonlyargs + getattr(args, "posonlyargs", []):
        out[a.arg] = ast.unparse(a.annotation) if a.annotation else ""
    return out


def _scalar_annotated(ann: str) -> bool:
    if not ann or any(t in ann for t in _ARRAY_ANN_TOKENS):
        return False
    return any(t in ann.replace("Optional", "").replace("[", " ").
               replace("]", " ").replace(",", " ").split()
               for t in _SCALAR_ANN_TOKENS)


def _is_none_check(node: ast.AST) -> bool:
    return (isinstance(node, ast.Compare)
            and all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops)
            and all(isinstance(c, ast.Constant) and c.value is None
                    for c in node.comparators))


def _traced_name_uses(expr: ast.AST, fi) -> Set[str]:
    """Names inside `expr` that may hold a tracer in traced function `fi`:
    non-static, non-scalar-annotated params of `fi` (or an enclosing traced
    fn), counted only where used outside static accessors / None-checks."""
    candidates: Set[str] = set()
    scope = fi
    while scope is not None:
        anns = _param_annotations(scope)
        for name, ann in anns.items():
            if name == "self" or name in scope.static_params:
                continue
            if _scalar_annotated(ann):
                continue
            candidates.add(name)
        scope = scope.parent

    used: Set[str] = set()

    def walk(node):
        if _is_none_check(node):
            return
        if isinstance(node, ast.Attribute) and \
                node.attr in _STATIC_ACCESSORS:
            return  # x.shape[...] etc is static under trace
        if isinstance(node, ast.Call):
            d = dotted_name(node.func)
            if d == "len":
                return
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load) \
                and node.id in candidates:
            used.add(node.id)
        for child in ast.iter_child_nodes(node):
            walk(child)

    walk(expr)
    return used


# -- TPU001: host sync in hot path ------------------------------------------

_SYNC_METHODS = {"item", "tolist", "block_until_ready", "copy_to_host_async"}
_CAST_BUILTINS = {"float", "int", "bool", "complex"}
_NP_SYNC_FUNCS = {"asarray", "array", "save", "savez", "copyto"}


@file_rule("TPU001", "host-sync inside trace-reachable code")
def check_tpu001(ctx: LintContext) -> List[Finding]:
    graph = module_graph(ctx)
    np_alias = numpy_aliases(ctx)
    findings: List[Finding] = []
    for fi, node in graph.iter_traced_nodes():
        if not isinstance(node, ast.Call):
            continue
        f: Optional[Finding] = None
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in _SYNC_METHODS:
            f = ctx.finding(
                "TPU001", node,
                f"`.{node.func.attr}()` forces a device->host sync inside "
                f"trace-reachable `{fi.name}`; keep reductions on device "
                f"and sync once outside the jitted region")
        else:
            d = dotted_name(node.func)
            if d:
                parts = d.split(".")
                if parts[0] in np_alias and parts[-1] in _NP_SYNC_FUNCS:
                    f = ctx.finding(
                        "TPU001", node,
                        f"`{d}()` materialises a host ndarray inside "
                        f"trace-reachable `{fi.name}` — use jnp so the op "
                        f"stays in the XLA program")
                elif d in ("jax.device_get",):
                    f = ctx.finding(
                        "TPU001", node,
                        f"`{d}()` is an explicit host transfer inside "
                        f"trace-reachable `{fi.name}`")
                elif d in _CAST_BUILTINS and node.args and \
                        _traced_name_uses(node.args[0], fi):
                    f = ctx.finding(
                        "TPU001", node,
                        f"`{d}()` on a traced value blocks on the device "
                        f"inside trace-reachable `{fi.name}` (ConcretizationError "
                        f"under jit; silent sync under eager)")
        if f is not None:
            findings.append(f)
    return findings


# -- TPU002: recompile hazards ----------------------------------------------

_ARRAYISH_ANNOTATIONS = ("Array", "ndarray")
_STRINGIFIERS = {"str", "repr", "format"}


def _nonstatic_params(fi) -> Set[str]:
    node = fi.node
    args = node.args
    names = [a.arg for a in args.args + args.kwonlyargs
             + getattr(args, "posonlyargs", [])]
    return {n for n in names if n not in fi.static_params and n != "self"}


@file_rule("TPU002", "python control flow / stringification of traced values; "
                     "unsound static args")
def check_tpu002(ctx: LintContext) -> List[Finding]:
    graph = module_graph(ctx)
    findings: List[Finding] = []

    for fi in graph.traced_funcs():
        if not fi.is_direct_jit:
            continue
        node = fi.node
        nonstatic = _nonstatic_params(fi)
        # (a) declared static names that do not exist in the signature
        sig_names = {a.arg for a in node.args.args + node.args.kwonlyargs
                     + getattr(node.args, "posonlyargs", [])}
        for s in sorted(fi.static_params - sig_names):
            f = ctx.finding(
                "TPU002", node,
                f"static arg `{s}` is not a parameter of `{fi.name}` — "
                f"typo'd static_argnames silently trace the arg instead")
            if f:
                findings.append(f)
        # (b) static params that are array-shaped or unhashable by default
        for a in node.args.args + node.args.kwonlyargs:
            if a.arg not in fi.static_params:
                continue
            ann = ast.unparse(a.annotation) if a.annotation is not None else ""
            if any(t in ann for t in _ARRAYISH_ANNOTATIONS):
                f = ctx.finding(
                    "TPU002", a,
                    f"static arg `{a.arg}` of `{fi.name}` is annotated "
                    f"`{ann}` — arrays are unhashable as static args and "
                    f"recompile per value")
                if f:
                    findings.append(f)
        # (c) python branches on non-static (traced) params of the jit entry
        for sub in graph._own_nodes(fi):
            if isinstance(sub, (ast.If, ast.While)):
                hit = _traced_name_uses(sub.test, fi) & nonstatic
                if hit:
                    f = ctx.finding(
                        "TPU002", sub,
                        f"python `{type(sub).__name__.lower()}` on traced "
                        f"value(s) {sorted(hit)} in jitted `{fi.name}` — "
                        f"use lax.cond/jnp.where or declare the arg static")
                    if f:
                        findings.append(f)
            # (d) f-strings / str() of traced params: every distinct value
            # stringifies (and under jit, concretizes) -> recompile per call
            elif isinstance(sub, ast.JoinedStr):
                hit = set()
                for v in sub.values:
                    if isinstance(v, ast.FormattedValue):
                        hit |= _traced_name_uses(v.value, fi) & nonstatic
                if hit:
                    f = ctx.finding(
                        "TPU002", sub,
                        f"f-string formats traced value(s) {sorted(hit)} in "
                        f"jitted `{fi.name}`")
                    if f:
                        findings.append(f)
            elif isinstance(sub, ast.Call):
                d = dotted_name(sub.func)
                if d in _STRINGIFIERS and sub.args and \
                        (_traced_name_uses(sub.args[0], fi) & nonstatic):
                    f = ctx.finding(
                        "TPU002", sub,
                        f"`{d}()` of traced value in jitted `{fi.name}`")
                    if f:
                        findings.append(f)

    # (e) debug prints anywhere trace-reachable: they concretize and force
    # retrace-per-value; jax.debug.print is the supported spelling
    for fi, node in graph.iter_traced_nodes():
        if isinstance(node, ast.Call) and dotted_name(node.func) == "print":
            f = ctx.finding(
                "TPU002", node,
                f"`print()` inside trace-reachable `{fi.name}` — use "
                f"jax.debug.print (traced) or log outside the jitted region")
            if f:
                findings.append(f)
    return findings


# -- TPU003: dtype drift in kernel paths ------------------------------------

# path components that make a file a kernel path for TPU003
DTYPE_SCOPES = ("ops",)
# creator -> 0-based positional index of dtype. (`asarray` is deliberately
# absent: it is a cast that preserves its input dtype, not a creation with
# an ambient default.)
_CREATORS_DTYPE_POS = {
    "array": 1, "zeros": 1, "ones": 1, "empty": 1, "full": 2,
}


def _in_dtype_scope(path: str) -> bool:
    parts = path.split("/")
    return any(p in DTYPE_SCOPES for p in parts[:-1])


@file_rule("TPU003", "float64 literals / dtype-less jnp creation in kernel "
                     "paths (bf16/f32 discipline)")
def check_tpu003(ctx: LintContext) -> List[Finding]:
    if not _in_dtype_scope(ctx.path):
        return []
    np_alias = numpy_aliases(ctx)
    jnp_alias = jnp_aliases(ctx)
    num_alias = np_alias | jnp_alias
    findings: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Attribute) and node.attr in (
                "float64", "complex128"):
            base = dotted_name(node.value)
            if base and base.split(".")[0] in num_alias:
                f = ctx.finding(
                    "TPU003", node,
                    f"`{base}.{node.attr}` in a kernel path — TPU has no "
                    f"f64 ALU; keep accumulators f32 (or bf16 data + f32 "
                    f"accumulate)")
                if f:
                    findings.append(f)
        elif isinstance(node, ast.Constant) and node.value == "float64":
            f = ctx.finding(
                "TPU003", node, "'float64' dtype string in a kernel path")
            if f:
                findings.append(f)
        elif isinstance(node, ast.Call):
            d = dotted_name(node.func)
            if not d:
                continue
            parts = d.split(".")
            if parts[0] in jnp_alias and parts[-1] in _CREATORS_DTYPE_POS:
                pos = _CREATORS_DTYPE_POS[parts[-1]]
                has_dtype = any(kw.arg == "dtype" for kw in node.keywords) \
                    or len(node.args) > pos
                if not has_dtype:
                    f = ctx.finding(
                        "TPU003", node,
                        f"dtype-less `{d}()` in a kernel path — the default "
                        f"float dtype is ambient (x64 flag) and silently "
                        f"promotes; pass dtype= explicitly")
                    if f:
                        findings.append(f)
    return findings


# -- TPU005: unsynced wall timing --------------------------------------------

# time functions whose subtraction is a wall-clock delta (bare names
# cover `from time import time/perf_counter/monotonic`)
_TIME_FUNCS = {"time.time", "time.perf_counter", "time.monotonic",
               "time", "perf_counter", "monotonic"}
# jax async dispatch returns before the device finishes; a wall delta
# around a dispatching call without a block_until_ready in the same
# function times the ENQUEUE, not the kernel. Dispatch-ish calls are:
# jax/lax/jax.numpy-aliased dotted calls (aliases resolved per file via
# jnp_aliases, like TPU003), names bound from jax.jit(...), locally
# jitted/traced functions (jitgraph), and the repo's known device-sweep
# drivers (they dispatch jitted programs internally).
_JAXISH_ROOTS = {"jax", "lax"}
_DISPATCH_HINTS = {
    # validator sweep entries (dispatch chunked XLA programs)
    "validate", "fit_arrays", "predict_arrays",
    # ops-level sweep/fit drivers
    "fit_gbt", "fit_gbt_folds", "fit_gbt_softmax", "fit_forest",
    "grow_tree", "sweep_glm_streamed_rounds", "sweep_glm_round",
    "sweep_glm_squared_gram", "route_hist",
    "hist_folds", "knockout_deltas",
}
_SYNC_NAMES = {"block_until_ready"}


def _is_time_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    d = dotted_name(node.func)
    return d in _TIME_FUNCS if d else False


def _module_jit_names(ctx: LintContext) -> Set[str]:
    """Names assigned from jax.jit(...) / pjit(...) anywhere in the file."""
    out: Set[str] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            d = dotted_name(node.value.func)
            if d and d.split(".")[-1] in {"jit", "pjit"}:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
    return out


def _has_sync(fi, graph) -> bool:
    for node in graph._own_nodes(fi):
        if isinstance(node, ast.Call):
            d = dotted_name(node.func)
            if d and d.split(".")[-1] in _SYNC_NAMES:
                return True
        elif isinstance(node, ast.Attribute) and node.attr in _SYNC_NAMES:
            return True
    return False


def _dispatchish(call: ast.Call, fi, graph, jit_names: Set[str],
                 jaxish: Set[str]) -> Optional[str]:
    """Name of the device-dispatching callee, or None."""
    d = dotted_name(call.func)
    if not d:
        return None
    parts = d.split(".")
    if parts[-1] in _SYNC_NAMES or d in _TIME_FUNCS:
        return None
    if parts[0] in jaxish and len(parts) > 1:
        return d
    if parts[-1] in _DISPATCH_HINTS:
        return d
    if parts[0] in jit_names:
        return d
    if len(parts) == 1:
        target = fi.resolve(parts[0]) if fi else None
        if target is None:
            target = graph.module_funcs.get(parts[0])
        if target is not None and target.traced:
            return d
    return None


@file_rule("TPU005", "unsynced-wall-timing: time deltas around jitted "
                     "dispatch with no block_until_ready")
def check_tpu005(ctx: LintContext) -> List[Finding]:
    graph = module_graph(ctx)
    jit_names = _module_jit_names(ctx)
    # resolve jax.numpy import aliases per file (TPU003 does the same):
    # `import jax.numpy as jnumpy` must dispatch like `jnp`
    jaxish = _JAXISH_ROOTS | jnp_aliases(ctx)
    findings: List[Finding] = []
    for fi in graph.all_funcs:
        if isinstance(fi.node, ast.Lambda):
            continue
        if _has_sync(fi, graph):
            # the function synchronizes somewhere — its walls are the
            # author's responsibility, not a static lie
            continue
        # anchor assignments per name, in line order: each delta pairs
        # with the LATEST prior assignment of ITS anchor name, so two
        # disjoint host-only timed windows never merge into one giant
        # window that swallows an untimed dispatch call between them
        anchor_lines: dict = {}
        deltas: List[Tuple[ast.BinOp, int]] = []
        nodes = list(graph._own_nodes(fi))
        for node in nodes:
            if isinstance(node, ast.Assign) and _is_time_call(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        anchor_lines.setdefault(t.id, []).append(
                            node.lineno)
            elif isinstance(node, ast.BinOp) and \
                    isinstance(node.op, ast.Sub):
                names = [n.id for n in (node.left, node.right)
                         if isinstance(n, ast.Name)
                         and n.id in anchor_lines]
                times = [n for n in (node.left, node.right)
                         if _is_time_call(n)]
                if not names or len(names) + len(times) < 2:
                    continue
                # per anchor NAME take its latest assignment before the
                # delta (re-assignment starts a new window), then span
                # from the EARLIEST such anchor: `t0=..; work; t1=..;
                # dt = t1 - t0` must cover the work between t0 and t1
                starts = [max((ln for ln in anchor_lines[nm]
                               if ln <= node.lineno), default=None)
                          for nm in names]
                starts = [s for s in starts if s is not None]
                if starts:
                    deltas.append((node, min(starts)))
        # EVERY offending delta gets its own finding (anchored at its own
        # line): a suppression on one window must not blind the rule to
        # later windows in the same function
        for delta, start in deltas:
            hit = None
            for node in nodes:
                if isinstance(node, ast.Call) and \
                        start <= node.lineno <= delta.lineno:
                    hit = _dispatchish(node, fi, graph, jit_names, jaxish)
                    if hit:
                        break
            if not hit:
                continue
            f = ctx.finding(
                "TPU005", delta,
                f"wall-clock delta in `{fi.name}` times dispatching call "
                f"`{hit}` with no block_until_ready in the same function "
                f"— jax dispatch is async, so the wall measures the "
                f"enqueue, not the device work; block on the result (or "
                f"justify: host-side conversion already syncs)")
            if f:
                findings.append(f)
    return findings


# -- TPU004: tracer leak -----------------------------------------------------

@file_rule("TPU004", "traced values escaping the trace via self./globals")
def check_tpu004(ctx: LintContext) -> List[Finding]:
    graph = module_graph(ctx)
    findings: List[Finding] = []
    for fi, node in graph.iter_traced_nodes():
        targets: List[ast.expr] = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        elif isinstance(node, ast.Global):
            f = ctx.finding(
                "TPU004", node,
                f"`global {', '.join(node.names)}` inside trace-reachable "
                f"`{fi.name}` — a tracer stored in module state outlives the "
                f"trace (jax leaked-tracer error at best, stale constant at "
                f"worst)")
            if f:
                findings.append(f)
            continue
        for t in targets:
            # unwrap tuple targets
            elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
            for el in elts:
                if isinstance(el, ast.Attribute) and \
                        isinstance(el.value, ast.Name) and \
                        el.value.id == "self":
                    f = ctx.finding(
                        "TPU004", node,
                        f"assignment to `self.{el.attr}` inside "
                        f"trace-reachable `{fi.name}` — the traced value "
                        f"escapes the trace; return it instead")
                    if f:
                        findings.append(f)
    return findings
