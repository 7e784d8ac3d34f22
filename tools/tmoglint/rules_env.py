"""ENV001 — TMOG_* knob-registry contract.

The env-knob surface is the library's de-facto config API: 30+ ``TMOG_*``
variables route kill switches, tile sizes and sampling rates, and every
one of them is load-bearing in some CI smoke or bench recipe. Their only
ledger used to be prose, and it drifted (three knobs were read by code
that no doc file named). ENV001 checks the machine-readable registry
(tools/tmoglint/knobs.py) both ways:

* an ``os.environ.get``/``os.getenv``/``os.environ[...]`` or
  ``env_on``/``env_int``/``env_float`` (utils/env.py) access of a
  ``TMOG_*`` name with no registry row — an undeclared knob;
* a registry row whose ``doc`` file does not mention the knob — the
  human-facing contract dropped it (checked only when the registry file
  itself is in the scan, so partial scans of unrelated trees stay
  quiet);
* a structurally broken registry row (missing ``name``/``doc``).

The registry is resolved from the scanned files first (a module-level
``KNOBS = [...]`` literal — this is what fixture tests exercise) and
falls back to importing the committed ``tools.tmoglint.knobs`` so scans
that do not include tools/ still know the declared set.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .core import Finding, LintContext, dotted_name, project_rule

_TMOG = re.compile(r"^TMOG_[A-Z0-9_]+$")


def _env_read_name(node: ast.AST) -> Optional[Tuple[ast.AST, str]]:
    """(anchor, name) when `node` reads/writes a TMOG_* env var —
    environ.get/getenv, env_on/env_int/env_float, environ[...],
    environ.setdefault/pop, and `"TMOG_X" in os.environ` membership
    tests all establish knob-dependent behavior."""
    if isinstance(node, ast.Call):
        d = dotted_name(node.func)
        if not d:
            return None
        tail = d.split(".")[-1]
        parts = d.split(".")
        envish = (tail in ("get", "setdefault", "pop")
                  and len(parts) >= 2 and parts[-2] == "environ") or \
            tail in ("getenv", "env_on", "env_int", "env_float")
        if envish and node.args and isinstance(node.args[0],
                                               ast.Constant) and \
                isinstance(node.args[0].value, str) and \
                _TMOG.match(node.args[0].value):
            return node, node.args[0].value
    elif isinstance(node, ast.Subscript):
        d = dotted_name(node.value)
        if d and d.split(".")[-1] == "environ" and \
                isinstance(node.slice, ast.Constant) and \
                isinstance(node.slice.value, str) and \
                _TMOG.match(node.slice.value):
            return node, node.slice.value
    elif isinstance(node, ast.Compare) and len(node.ops) == 1 and \
            isinstance(node.ops[0], (ast.In, ast.NotIn)) and \
            isinstance(node.left, ast.Constant) and \
            isinstance(node.left.value, str) and \
            _TMOG.match(node.left.value):
        d = dotted_name(node.comparators[0])
        if d and d.split(".")[-1] == "environ":
            return node, node.left.value
    return None


def _scanned_registries(ctxs: Sequence[LintContext]
                        ) -> List[Tuple[LintContext, ast.AST, List[dict],
                                        List[ast.AST]]]:
    """(ctx, assign node, entries, per-entry nodes) for every scanned
    module-level ``KNOBS = [...]`` literal."""
    out = []
    for ctx in ctxs:
        for node in ctx.tree.body:
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AnnAssign):
                targets = [node.target]
            else:
                continue
            if not (node.value is not None
                    and any(isinstance(t, ast.Name) and t.id == "KNOBS"
                            for t in targets)
                    and isinstance(node.value, (ast.List, ast.Tuple))):
                continue
            entries: List[dict] = []
            entry_nodes: List[ast.AST] = []
            for el in node.value.elts:
                try:
                    val = ast.literal_eval(el)
                except (ValueError, SyntaxError):
                    val = None
                entries.append(val if isinstance(val, dict) else {})
                entry_nodes.append(el)
            out.append((ctx, node, entries, entry_nodes))
    return out


def _builtin_names() -> Set[str]:
    try:
        from .knobs import declared_names
        return set(declared_names())
    except Exception:  # pragma: no cover - broken tree mid-edit
        return set()


@project_rule("ENV001", "TMOG_* env knob read with no registry row, or "
                        "registry row its doc file does not mention")
def check_env001(ctxs: Sequence[LintContext]) -> List[Finding]:
    findings: List[Finding] = []
    registries = _scanned_registries(ctxs)
    declared: Set[str] = set()
    for _ctx, _node, entries, _nodes in registries:
        declared |= {e.get("name") for e in entries if e.get("name")}
    if not registries:
        declared = _builtin_names()

    # direction 1: undeclared reads
    for ctx in ctxs:
        if "TMOG_" not in ctx.source:
            continue
        for node in ast.walk(ctx.tree):
            hit = _env_read_name(node)
            if hit is None:
                continue
            anchor, name = hit
            if name in declared:
                continue
            f = ctx.finding(
                "ENV001", anchor,
                f"`{name}` is read here but has no row in the TMOG_* "
                f"knob registry (tools/tmoglint/knobs.py) — undeclared "
                f"knobs are exactly how the docs drifted; register it "
                f"with name/default/doc, then document it in the doc "
                f"file the row names")
            if f is not None:
                findings.append(f)

    # direction 2: registry rows vs their doc files (scanned registry
    # only — the doc check needs a lint root to resolve files against)
    doc_cache: Dict[str, Optional[str]] = {}
    for ctx, _node, entries, entry_nodes in registries:
        if ctx.root is None:
            continue
        for entry, el in zip(entries, entry_nodes):
            name = entry.get("name")
            doc = entry.get("doc")
            if not name or not doc:
                f = ctx.finding(
                    "ENV001", el,
                    "malformed knob-registry row: every entry needs at "
                    "least `name` and `doc`")
                if f is not None:
                    findings.append(f)
                continue
            if doc not in doc_cache:
                p = os.path.join(ctx.root, doc)
                try:
                    with open(p, "r", encoding="utf-8") as fh:
                        doc_cache[doc] = fh.read()
                except OSError:
                    doc_cache[doc] = None
            text = doc_cache[doc]
            if text is None:
                f = ctx.finding(
                    "ENV001", el,
                    f"knob `{name}` names doc file `{doc}` which does "
                    f"not exist under the lint root")
                if f is not None:
                    findings.append(f)
            # boundary-aware: a knob TMOG_X must not pass on the
            # strength of TMOG_X_DIR mentions
            elif not re.search(re.escape(name) + r"(?![A-Z0-9_])",
                               text):
                f = ctx.finding(
                    "ENV001", el,
                    f"knob `{name}` is registered but `{doc}` never "
                    f"mentions it — document the knob (name, default, "
                    f"effect) or point the row at the doc that does")
                if f is not None:
                    findings.append(f)
    return findings
