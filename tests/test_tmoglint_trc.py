"""tmoglint v4: trace-contract (TRC001-005).

The contract these rules prove — zero recompiles in steady state —
fails in the one way tier-1 cannot
catch: correct on the warm CPU test box, wrong on hardware. So the
tests here are adversarial about vacuity: every rule has known-bad
fixtures that MUST fire and known-good fixtures that MUST stay silent,
the repo-hot-paths-clean claim is asserted against the abstract
interpreter's own site counters (a scan that interpreted nothing does
not count as clean), and the canonical contract breaks are driven as
MUTATIONS of the real serve engine through the real CLI — the mutated
copy must go red, the restored copy green.
"""
import json
import os
import subprocess
import sys
import textwrap

from tools.tmoglint.core import (
    LintContext, expand_rule_selection, run_rules, scan_paths,
)
from tools.tmoglint.traceflow import (
    CHOKED, VARYING, hot_path_kind, is_test_path, trace_flow,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRC_ALL = ["TRC001", "TRC002", "TRC003", "TRC004", "TRC005"]


def lint(src: str, path: str = "ops/mod.py", rules=None):
    ctx = LintContext(path, textwrap.dedent(src))
    return run_rules([ctx], only=rules)


def lint_many(named_srcs, rules=None):
    ctxs = [LintContext(p, textwrap.dedent(s)) for p, s in named_srcs]
    return run_rules(ctxs, only=rules)


def rule_lines(findings, rule):
    return sorted(f.line for f in findings if f.rule == rule)


# -- path scoping shared by the family ---------------------------------------

class TestScoping:
    def test_hot_path_kinds(self):
        assert hot_path_kind("serve/engine.py") == "request"
        assert hot_path_kind("fleet/router.py") == "request"
        assert hot_path_kind("parallel/tileplane.py") == "tile"
        assert hot_path_kind("readers/streaming.py") == "tile"
        # fit-time/offline neighbours are NOT hot paths: one compile per
        # dataset is the design there
        assert hot_path_kind("readers/readers.py") is None
        assert hot_path_kind("monitor/offline.py") is None
        assert hot_path_kind("ops/trees.py") is None
        assert hot_path_kind("tools/tmoglint/core.py") is None

    def test_tests_and_bench_excluded(self):
        assert is_test_path("tests/test_serve.py")
        assert is_test_path("bench.py")
        assert is_test_path("bench_serving.py")
        assert not is_test_path("serve/engine.py")


# -- TRC001: jit construction per call ---------------------------------------

class TestTRC001:
    def test_jit_minted_and_called_in_loop(self):
        out = lint("""
            import jax

            def sweep(fns, xs):
                for fn in fns:
                    g = jax.jit(fn)
                    xs = g(xs)
                return xs
        """, rules=["TRC001"])
        assert len(rule_lines(out, "TRC001")) == 1
        assert "inside the same loop" in out[0].message

    def test_inline_jit_call(self):
        out = lint("""
            import jax

            def apply(fn, x):
                return jax.jit(fn)(x)
        """, rules=["TRC001"])
        assert len(out) == 1
        assert "fresh jitted" in out[0].message

    def test_any_construction_in_request_path_function(self):
        out = lint("""
            import jax

            def score(self, x):
                g = jax.jit(lambda v: v + 1)
                return g(x)
        """, path="serve/engine.py", rules=["TRC001"])
        assert len(out) == 1
        assert "per-request" in out[0].message

    def test_module_level_jit_silent(self):
        out = lint("""
            import jax

            def _kernel(x):
                return x * 2

            kernel = jax.jit(_kernel)
        """, path="serve/engine.py", rules=["TRC001"])
        assert out == []

    def test_warmup_cache_store_in_loop_silent(self):
        # the prewarm idiom: minting per bucket into a cache is the
        # POINT of warmup — the program outlives the loop
        out = lint("""
            import jax

            def prewarm(self, fn, buckets):
                for b in buckets:
                    self._cache[b] = jax.jit(fn)
        """, rules=["TRC001"])
        assert out == []

    def test_test_paths_excluded(self):
        out = lint("""
            import jax

            def test_retrace_counter(fn, x):
                return jax.jit(fn)(x)
        """, path="tests/test_tracing.py", rules=["TRC001"])
        assert out == []


# -- TRC002: branch on derived/threaded traced values ------------------------

class TestTRC002:
    def test_branch_on_derived_local(self):
        out = lint("""
            import jax

            @jax.jit
            def f(x):
                y = x * 2
                if y:
                    return y
                return x
        """, rules=["TRC002"])
        assert len(out) == 1
        assert "derived from traced values" in out[0].message

    def test_branch_on_threaded_helper_param(self):
        # the interprocedural case TPU002 cannot see: `v` is only a
        # tracer because f's call site passed one
        out = lint("""
            import jax

            def helper(v):
                if v:
                    return v
                return v + 1

            @jax.jit
            def f(x):
                return helper(x)
        """, rules=["TRC002"])
        assert len(out) == 1
        assert "bound to a tracer by a traced call site" in out[0].message

    def test_branch_through_bound_method_self_shift(self):
        # regression for the positional-binding bug the mutation drives
        # surfaced: `self.helper(x)` supplies the receiver implicitly,
        # so `x` binds to `v`, NOT to `self` — without the shift the
        # tracer binding lands on the wrong param and this goes silent
        out = lint("""
            import jax

            class Stage:
                def helper(self, v):
                    if v:
                        return v
                    return v + 1

                @jax.jit
                def f(self, x):
                    return self.helper(x)
        """, rules=["TRC002"])
        assert len(out) == 1
        assert "bound to a tracer" in out[0].message

    def test_static_argnames_param_silent(self):
        out = lint("""
            import functools

            import jax

            @functools.partial(jax.jit, static_argnames=("mode",))
            def f(x, mode):
                if mode:
                    return x
                return -x
        """, rules=["TRC002"])
        assert out == []

    def test_backend_probe_silent(self):
        # jax.default_backend() is host introspection, not a tracer
        out = lint("""
            import jax

            @jax.jit
            def f(x):
                use_matmul = jax.default_backend() == "tpu"
                if use_matmul:
                    return x @ x
                return x
        """, rules=["TRC002"])
        assert out == []


# -- TRC003: call-varying shapes without a choke -----------------------------

class TestTRC003:
    def test_len_reaches_shape_in_request_path(self):
        out = lint("""
            import numpy as np

            def assemble(records):
                n = len(records)
                return np.zeros(n, np.float32)
        """, path="serve/engine.py", rules=["TRC003"])
        assert len(out) == 1
        assert "fresh XLA program" in out[0].message

    def test_two_hop_poison_through_helper(self):
        # the size crosses two plain python calls before the creator —
        # the call-site poisoning must ride the chain to a fixpoint
        out = lint("""
            import numpy as np

            def outer(records):
                n = len(records)
                return mid(n)

            def mid(n):
                return inner(n)

            def inner(n):
                return np.full(n, 0.0, np.float32)
        """, path="parallel/tileplane.py", rules=["TRC003"])
        assert len(out) == 1
        assert "np.full" in out[0].message

    def test_bound_method_two_hop_poison(self):
        # regression (pre-fix-failing): the engine's real chain is
        # score_batch -> self._assemble -> self._bucket_columns; the
        # receiver shift must hold or `bucket` never poisons
        out = lint("""
            import numpy as np

            class Engine:
                def score(self, records):
                    n = len(records)
                    return self._assemble(records, n)

                def _assemble(self, records, bucket):
                    return self._columns(bucket)

                def _columns(self, bucket):
                    return np.full(bucket, np.nan, np.float64)
        """, path="serve/engine.py", rules=["TRC003"])
        assert len(out) == 1

    def test_choked_through_bucket_ladder_silent(self):
        out = lint("""
            import numpy as np

            class Engine:
                def assemble(self, records):
                    n = self.pick_bucket(len(records))
                    return np.zeros(n, np.float32)
        """, path="serve/engine.py", rules=["TRC003"])
        assert out == []

    def test_non_hot_path_silent(self):
        # fit-time code: one compile per dataset is the design
        out = lint("""
            import numpy as np

            def assemble(records):
                return np.zeros(len(records), np.float32)
        """, path="readers/readers.py", rules=["TRC003"])
        assert out == []


# -- TRC004: pytrees from unordered iteration --------------------------------

class TestTRC004:
    def test_comp_over_set_feeds_stack(self):
        out = lint("""
            import jax.numpy as jnp

            def pack(d):
                cols = [d[k] for k in set(d)]
                return jnp.stack(cols)
        """, rules=["TRC004"])
        assert len(out) == 1
        assert "sorted()" in out[0].message

    def test_loop_over_intersection_feeds_device_put(self):
        out = lint("""
            import jax

            def pack(d, wanted):
                vals = []
                for k in d.keys().intersection(wanted):
                    vals.append(d[k])
                return jax.device_put(vals)
        """, rules=["TRC004"])
        assert len(out) == 1

    def test_inline_comp_argument(self):
        out = lint("""
            import jax.numpy as jnp

            def pack(d):
                return jnp.stack([d[k] for k in set(d)])
        """, rules=["TRC004"])
        assert len(out) == 1

    def test_sorted_iteration_silent(self):
        out = lint("""
            import jax.numpy as jnp

            def pack(d):
                cols = [d[k] for k in sorted(set(d))]
                return jnp.stack(cols)
        """, rules=["TRC004"])
        assert out == []

    def test_host_only_consumer_silent(self):
        out = lint("""
            def total(d):
                return sum(d[k] for k in set(d))
        """, rules=["TRC004"])
        assert out == []


# -- TRC005: host sync on jit outputs in hot-path loops ----------------------

class TestTRC005:
    def test_item_in_tile_loop(self):
        out = lint("""
            import jax

            step = jax.jit(lambda c, x: c + x)

            def drain(tiles):
                total = 0.0
                for t in tiles:
                    r = step(total, t)
                    total = r.item()
                return total
        """, path="parallel/tileplane.py", rules=["TRC005"])
        assert len(out) == 1
        assert ".item()" in out[0].message

    def test_np_asarray_in_request_loop(self):
        out = lint("""
            import jax
            import numpy as np

            score = jax.jit(lambda x: x * 2)

            def serve(batches):
                outs = []
                for b in batches:
                    y = score(b)
                    outs.append(np.asarray(y))
                return outs
        """, path="serve/engine.py", rules=["TRC005"])
        assert len(out) == 1

    def test_sync_after_loop_silent(self):
        out = lint("""
            import jax

            step = jax.jit(lambda c, x: c + x)

            def drain(tiles):
                acc = 0.0
                for t in tiles:
                    acc = step(acc, t)
                return acc.item()
        """, path="parallel/tileplane.py", rules=["TRC005"])
        assert out == []

    def test_non_jit_value_silent(self):
        # device_put results are transfers, not jitted programs — the
        # tileplane's designed sync fences must stay silent
        out = lint("""
            import jax

            def feed(tiles):
                for t in tiles:
                    buf = jax.device_put(t)
                    buf.block_until_ready()
        """, path="parallel/tileplane.py", rules=["TRC005"])
        assert out == []

    def test_non_hot_path_silent(self):
        out = lint("""
            import jax

            step = jax.jit(lambda c, x: c + x)

            def fit(tiles):
                for t in tiles:
                    r = step(0.0, t)
                    print(r.item())
        """, path="ops/stats_engine.py", rules=["TRC005"])
        assert out == []


# -- suppression + family selection ------------------------------------------

class TestSuppressionAndSelection:
    def test_inline_disable_suppresses_trc(self):
        out = lint("""
            import jax

            def apply(fn, x):
                # tmoglint: disable=TRC001  one-shot tool, compile measured
                return jax.jit(fn)(x)
        """, rules=["TRC001"])
        assert out == []

    def test_disable_all_with_justification(self):
        out = lint("""
            import numpy as np

            def assemble(records):
                return np.zeros(len(records))  # tmoglint: disable=TRC003  boot probe
        """, path="serve/engine.py", rules=["TRC003"])
        assert out == []

    def test_family_prefix_expansion(self):
        assert expand_rule_selection(["TRC"]) == set(TRC_ALL)
        assert expand_rule_selection(["ENV"]) == {"ENV001"}
        got = expand_rule_selection(["TRC", "ENV"])
        assert got == set(TRC_ALL) | {"ENV001"}

    def test_list_rules_names_new_families(self):
        env = dict(os.environ, PYTHONPATH=REPO_ROOT)
        proc = subprocess.run(
            [sys.executable, "-m", "tools.tmoglint", "--list-rules"],
            cwd=REPO_ROOT, env=env, capture_output=True, text=True)
        assert proc.returncode == 0
        for rid in TRC_ALL:
            assert rid in proc.stdout, rid

    def test_family_scope_composes_with_baseline_guard(self, tmp_path):
        """--rules TRC scopes the stale-entry check: another family's
        grandfathered entry is neither new nor stale, and a fixed TRC
        entry only goes stale under a TRC-selecting scan."""
        serve = tmp_path / "serve"
        serve.mkdir()
        (serve / "eng.py").write_text(textwrap.dedent("""
            import numpy as np

            def assemble(records):
                return np.zeros(len(records), np.float32)
        """))
        env = dict(os.environ, PYTHONPATH=REPO_ROOT)
        base = tmp_path / "base.json"
        wrote = subprocess.run(
            [sys.executable, "-m", "tools.tmoglint", "serve",
             "--root", str(tmp_path), "--baseline", str(base),
             "--write-baseline"],
            cwd=REPO_ROOT, env=env, capture_output=True, text=True)
        assert wrote.returncode == 0, wrote.stdout + wrote.stderr
        entries = json.load(open(base))["findings"]
        assert any(e["rule"] == "TRC003" for e in entries), entries
        # ENV-scoped scan: the TRC003 entry is out of scope, not stale
        other = subprocess.run(
            [sys.executable, "-m", "tools.tmoglint", "serve",
             "--root", str(tmp_path), "--baseline", str(base),
             "--rules", "ENV"],
            cwd=REPO_ROOT, env=env, capture_output=True, text=True)
        assert other.returncode == 0, other.stdout + other.stderr
        # TRC-scoped scan sees it baselined: green
        trc = subprocess.run(
            [sys.executable, "-m", "tools.tmoglint", "serve",
             "--root", str(tmp_path), "--baseline", str(base),
             "--rules", "TRC"],
            cwd=REPO_ROOT, env=env, capture_output=True, text=True)
        assert trc.returncode == 0, trc.stdout + trc.stderr
        # fix the debt without regenerating: TRC-scoped scan goes stale
        (serve / "eng.py").write_text("x = 1\n")
        stale = subprocess.run(
            [sys.executable, "-m", "tools.tmoglint", "serve",
             "--root", str(tmp_path), "--baseline", str(base),
             "--rules", "TRC"],
            cwd=REPO_ROOT, env=env, capture_output=True, text=True)
        assert stale.returncode == 1 and "stale" in stale.stdout


# -- CLI: parallel parity, SARIF, TMOG_LINT_JOBS -----------------------------

def _fixture_tree(tmp_path):
    """One TRC003 + one ENV001 finding, plus clean neighbours."""
    serve = tmp_path / "serve"
    serve.mkdir()
    (serve / "eng.py").write_text(textwrap.dedent("""
        import numpy as np

        def assemble(records):
            return np.zeros(len(records), np.float32)
    """))
    (tmp_path / "ops").mkdir()
    (tmp_path / "ops" / "knob.py").write_text(textwrap.dedent("""
        import os

        def tile_budget():
            return int(os.environ.get("TMOG_NOT_A_KNOB", "32"))
    """))
    (tmp_path / "clean.py").write_text("x = 1\n")


def _scan_json(tmp_path, *extra, env_extra=None):
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    if env_extra:
        env.update(env_extra)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.tmoglint", ".",
         "--root", str(tmp_path), "--no-baseline", *extra],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True)
    return proc


class TestCLI:
    def test_parallel_and_serial_reports_identical(self, tmp_path):
        _fixture_tree(tmp_path)
        outs = []
        for jobs in ("1", "2"):
            proc = _scan_json(tmp_path, "--jobs", jobs, "--format", "json",
                              "--rules", "TRC,ENV")
            assert proc.returncode == 1, proc.stdout + proc.stderr
            rep = json.loads(proc.stdout)
            outs.append([(f["rule"], f["path"], f["fingerprint"])
                         for f in rep["new"]])
        assert outs[0] == outs[1]
        assert {r for r, _, _ in outs[0]} == {"TRC003", "ENV001"}

    def test_sarif_round_trips_against_json_report(self, tmp_path):
        _fixture_tree(tmp_path)
        jproc = _scan_json(tmp_path, "--format", "json")
        sproc = _scan_json(tmp_path, "--format", "sarif")
        # same scan, same verdict, same exit code
        assert jproc.returncode == 1 and sproc.returncode == 1
        rep = json.loads(jproc.stdout)
        doc = json.loads(sproc.stdout)
        assert doc["version"] == "2.1.0"
        [run] = doc["runs"]
        # results are exactly the report's NEW findings
        assert [(r["ruleId"], r["fingerprints"]["tmoglint/v1"])
                for r in run["results"]] == \
            [(f["rule"], f["fingerprint"]) for f in rep["new"]]
        [loc] = run["results"][0]["locations"]
        f0 = rep["new"][0]
        phys = loc["physicalLocation"]
        assert phys["artifactLocation"]["uri"] == f0["path"]
        assert phys["region"]["startLine"] == f0["line"]
        assert phys["region"]["startColumn"] == f0["col"] + 1
        # every used rule is declared with its registered doc line
        assert {r["id"] for r in run["tool"]["driver"]["rules"]} == \
            {f["rule"] for f in rep["new"]}
        # the rest of the JSON report rides the property bag verbatim
        props = run["properties"]
        for key in ("paths", "rules", "total_findings", "counts_by_rule",
                    "baselined", "stale_baseline_entries", "ok"):
            assert props[key] == rep[key], key
        # stats are per-run wall timings — two scans can't match on the
        # seconds, so round-trip the structure and the scan facts
        assert set(props["stats"]) == set(rep["stats"])
        assert props["stats"]["files"] == rep["stats"]["files"]
        assert props["stats"]["jobs"] == rep["stats"]["jobs"]

    def test_sarif_clean_scan_exits_zero(self, tmp_path):
        (tmp_path / "clean.py").write_text("x = 1\n")
        proc = _scan_json(tmp_path, "--format", "sarif")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        [run] = json.loads(proc.stdout)["runs"]
        assert run["results"] == []
        assert run["properties"]["ok"] is True

    def test_lint_jobs_env_knob(self, tmp_path):
        # >= 4 files: below that the pool is not worth starting and the
        # scan goes serial regardless of the requested width
        for i in range(5):
            (tmp_path / f"clean{i}.py").write_text("x = 1\n")
        # the knob pins the default pool width...
        proc = _scan_json(tmp_path, "--format", "json",
                          env_extra={"TMOG_LINT_JOBS": "2"})
        assert json.loads(proc.stdout)["stats"]["jobs"] == 2
        # ...an explicit --jobs still wins...
        proc = _scan_json(tmp_path, "--format", "json", "--jobs", "1",
                          env_extra={"TMOG_LINT_JOBS": "2"})
        assert json.loads(proc.stdout)["stats"]["jobs"] == 1
        # ...and an unparseable pin falls back to the cpu heuristic
        proc = _scan_json(tmp_path, "--format", "json",
                          env_extra={"TMOG_LINT_JOBS": "many"})
        assert proc.returncode == 0
        assert json.loads(proc.stdout)["stats"]["jobs"] >= 1


# -- the repo's own hot paths: clean, and NON-vacuously ----------------------

class TestRepoScan:
    def test_repo_hot_paths_clean_nonvacuously(self):
        ctxs, errors = scan_paths(
            [os.path.join(REPO_ROOT, "transmogrifai_tpu")], REPO_ROOT)
        assert not errors
        findings = run_rules(ctxs, only=TRC_ALL)
        assert findings == [], [(f.rule, f.path, f.line) for f in findings]
        # ...and the interpreter actually interpreted: the clean verdict
        # is backed by discovered-and-analysed sites, not empty scans
        by_path = {c.path: c for c in ctxs}
        eng = by_path["transmogrifai_tpu/serve/engine.py"]
        eng_flow = trace_flow(eng)
        states = [st for _, _, st in eng_flow.shape_sites]
        assert eng_flow.stats["shape_sites"] >= 3, eng_flow.stats
        assert VARYING not in states, states
        # the choke is SEEN: score_batch's `bucket` is choked by
        # pick_bucket in the interpreted env (that is WHY the creator
        # sites downstream stay un-poisoned)
        score_batch = next(fi for fi in eng_flow.graph.all_funcs
                           if fi.name == "score_batch")
        assert eng_flow.shape_env(score_batch).get("bucket") == CHOKED
        totals = {"traced_funcs": 0, "jit_sites": 0, "call_bindings": 0,
                  "host_funcs": 0}
        for c in ctxs:
            fl = getattr(c, "_trace_flow", None)
            if fl is None:
                continue
            for k in totals:
                totals[k] += fl.stats[k]
        assert totals["traced_funcs"] > 20, totals
        assert totals["jit_sites"] > 5, totals
        assert totals["call_bindings"] > 50, totals
        assert totals["host_funcs"] > 10, totals


# -- mutation drives: the canonical contract breaks, through the CLI ---------

def _drive(tmp_path, rule, family, mutate):
    """Copy the real serve engine aside, scan clean, apply `mutate`
    (old, new) to the copy, assert the CLI goes red naming `rule`, then
    restore and assert green again."""
    src = open(os.path.join(REPO_ROOT, "transmogrifai_tpu", "serve",
                            "engine.py")).read()
    serve = tmp_path / "serve"
    serve.mkdir(exist_ok=True)
    dst = serve / "engine.py"
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)

    def scan():
        return subprocess.run(
            [sys.executable, "-m", "tools.tmoglint", "serve/engine.py",
             "--root", str(tmp_path), "--no-baseline", "--rules", family],
            cwd=REPO_ROOT, env=env, capture_output=True, text=True)

    dst.write_text(src)
    clean = scan()
    assert clean.returncode == 0, (rule, clean.stdout, clean.stderr)
    old, new = mutate
    assert src.count(old) == 1, f"engine anchor drifted: {old!r}"
    dst.write_text(src.replace(old, new))
    hit = scan()
    assert hit.returncode == 1, (rule, hit.stdout, hit.stderr)
    assert rule in hit.stdout, (rule, hit.stdout)
    dst.write_text(src)  # deleting the mutation restores the clean scan
    again = scan()
    assert again.returncode == 0, (rule, again.stdout, again.stderr)


class TestMutationDrives:
    ANCHOR = "        records = list(records)\n"

    def test_jit_into_score_batch_fires_trc001(self, tmp_path):
        _drive(tmp_path, "TRC001", "TRC",
               (self.ANCHOR,
                self.ANCHOR + "        _g = jax.jit(lambda v: v)\n"))

    def test_ladder_bypass_fires_trc003(self, tmp_path):
        # the ISSUE's canonical break: replace the bucket-ladder lookup
        # with the raw batch size — every distinct batch size becomes
        # its own XLA program, two helper hops away from the creator
        _drive(tmp_path, "TRC003", "TRC",
               ("        bucket = self.pick_bucket(n)\n",
                "        bucket = n\n"))
