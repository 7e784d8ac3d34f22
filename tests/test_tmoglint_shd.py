"""tmoglint v3: SHD (SPMD/collective correctness) + ENV/EVT (contract
drift) rule tests.

Every rule gets known-bad fixtures (must be caught) and known-good
fixtures (must stay silent), the `fit_gbt_folds_sharded` subsample bar
is pinned at BOTH layers (lint-time SHD003 + the trace-time raise), and
the real repo's sharded modules are asserted clean — the acceptance
contract that the baseline stays EMPTY with the new families on.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from tools.tmoglint.core import LintContext, run_rules, scan_paths

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SHARD_PRELUDE = textwrap.dedent("""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map
""")


def shard_src(body: str) -> str:
    """Prelude + dedented fixture body (dedent cannot handle the two
    indentation levels once concatenated)."""
    return SHARD_PRELUDE + textwrap.dedent(body)


def lint(src: str, path: str = "pkg/mod.py", rules=None):
    ctx = LintContext(path, textwrap.dedent(src))
    return run_rules([ctx], only=rules)


def lint_many(named_srcs, rules=None):
    ctxs = [LintContext(p, textwrap.dedent(s)) for p, s in named_srcs]
    return run_rules(ctxs, only=rules)


def lint_tree(tmp_path, files, paths=("."), rules=None):
    """Write `files` under tmp_path and lint via scan_paths so ctxs
    carry a real lint root (the ENV/EVT doc checks need one)."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    ctxs, errors = scan_paths(list(paths), str(tmp_path))
    return errors + run_rules(ctxs, only=rules)


def rules_of(findings):
    return sorted(f.rule for f in findings)


# -- SHD001: unreduced cross-shard output ------------------------------------

class TestSHD001:
    def test_forgot_the_psum(self):
        """The motivating bug: replicated out_spec, body never reduces —
        correct at 1 device, silently wrong at N>1."""
        out = lint(shard_src("""
            def build(mesh):
                def core(x):
                    return x.sum(axis=0)
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None),),
                                 out_specs=P())
        """), rules=["SHD001"])
        assert len(out) == 1 and out[0].rule == "SHD001"
        assert "psum" in out[0].message

    def test_one_of_two_outputs_unreduced(self):
        """Tuple out_specs: the reduced output passes, the forgotten
        one flags — findings are per-position."""
        out = lint(shard_src("""
            def build(mesh):
                def core(x):
                    hist = x.sum(axis=0)
                    merged = jax.lax.psum(hist, "batch")
                    return merged, hist
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None),),
                                 out_specs=(P(), P()))
        """), rules=["SHD001"])
        assert len(out) == 1
        assert "output 1" in out[0].message

    def test_negative_psum_through_threaded_helper(self):
        """The repo idiom: an `_allreduce(v, axis_name)` helper with the
        axis threaded through a kwarg — the reduction is seen
        interprocedurally."""
        out = lint(shard_src("""
            def _allreduce(v, axis_name):
                return jax.lax.psum(v, axis_name) \\
                    if axis_name is not None else v

            def _impl(x, axis_name=None):
                acc = x.sum(axis=0)
                return _allreduce(acc, axis_name)

            def build(mesh):
                def core(x):
                    return _impl(x, axis_name="batch")
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None),),
                                 out_specs=P())
        """), rules=["SHD001"])
        assert out == []

    def test_negative_sharded_out_spec_needs_no_reduction(self):
        out = lint(shard_src("""
            def build(mesh):
                def core(x):
                    return x * 2.0
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None),),
                                 out_specs=P("batch", None))
        """), rules=["SHD001"])
        assert out == []

    def test_negative_scan_carry_accumulator_psummed(self):
        """lax.scan-accumulated partial sums + one psum at the end: the
        stats-engine shape."""
        out = lint(shard_src("""
            def build(mesh):
                def core(x):
                    def body(acc, row):
                        return acc + row, None
                    acc0 = jnp.zeros(x.shape[1])
                    acc, _ = jax.lax.scan(body, acc0, x)
                    return jax.lax.psum(acc, "batch")
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None),),
                                 out_specs=P())
        """), rules=["SHD001"])
        assert out == []

    def test_scan_carry_without_psum_flags(self):
        out = lint(shard_src("""
            def build(mesh):
                def core(x):
                    def body(acc, row):
                        return acc + row, None
                    acc0 = jnp.zeros(x.shape[1])
                    acc, _ = jax.lax.scan(body, acc0, x)
                    return acc
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None),),
                                 out_specs=P())
        """), rules=["SHD001"])
        assert len(out) == 1

    def test_suppression_with_justification(self):
        out = lint(shard_src("""
            def build(mesh):
                def core(x):
                    return x.sum(axis=0)
                return shard_map(
                    core, mesh, in_specs=(P("batch", None),),
                    # tmoglint: disable=SHD001  single-device by design
                    out_specs=P())
        """), rules=["SHD001"])
        assert out == []


# -- SHD002: axis mismatch / unbound axis ------------------------------------

class TestSHD002:
    def test_axis_name_mismatch(self):
        out = lint(shard_src("""
            def build(mesh):
                def core(x):
                    return jax.lax.psum(x.sum(axis=0), "data")
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None),),
                                 out_specs=P())
        """), rules=["SHD002"])
        assert len(out) == 1
        assert "'data'" in out[0].message and "batch" in out[0].message

    def test_unbound_axis_outside_shard_map(self):
        out = lint("""
            import jax

            @jax.jit
            def f(x):
                return jax.lax.psum(x, "batch")
        """, rules=["SHD002"])
        assert len(out) == 1
        assert "outside any shard_map" in out[0].message

    def test_axis_none_reaching_the_trace(self):
        out = lint(shard_src("""
            def build(mesh):
                def core(x):
                    return jax.lax.psum(x.sum(axis=0), None)
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None),),
                                 out_specs=P())
        """), rules=["SHD002"])
        assert any("axis_name=None" in f.message for f in out)

    def test_negative_guarded_degenerate_path(self):
        """`psum(v, axis) if axis is not None else v` called with None
        folds to the identity branch — the single-device path must stay
        legal."""
        out = lint(shard_src("""
            def _allreduce(v, axis_name):
                return jax.lax.psum(v, axis_name) \\
                    if axis_name is not None else v

            def run_local(x):
                return _allreduce(x.sum(axis=0), None)

            def build(mesh):
                def core(x):
                    return _allreduce(x.sum(axis=0), "batch")
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None),),
                                 out_specs=P())
        """), rules=["SHD002"])
        assert out == []

    def test_axis_judged_per_site_when_mesh_resolves(self):
        """With the site's Mesh construction statically resolvable, a
        collective naming an axis THAT mesh does not bind flags — even
        though another site in the project binds it (per-site judgment,
        not the global union)."""
        out = lint(shard_src("""
            from jax.sharding import Mesh

            def build_model(mesh):
                def core_m(x):
                    return jax.lax.psum(x.sum(axis=0), "model")
                return shard_map(core_m, mesh,
                                 in_specs=(P("model", None),),
                                 out_specs=P())

            def build_batch(devs):
                mesh = Mesh(devs, ("batch",))
                def core_b(x):
                    return jax.lax.psum(x.sum(axis=0), "model")
                return shard_map(core_b, mesh,
                                 in_specs=(P("batch", None),),
                                 out_specs=P())
        """), rules=["SHD002"])
        assert len(out) == 1
        assert "'model'" in out[0].message and "batch" in out[0].message

    def test_negative_unresolved_mesh_binds_all_declared_axes(self):
        """When the mesh is a parameter (statically opaque), a
        collective over a project-declared axis absent from the specs
        stays legal — shard_map binds EVERY mesh axis, not just the
        spec-listed ones (the 2-D batch x model case)."""
        out = lint(shard_src("""
            BATCH_AXIS = "batch"
            MODEL_AXIS = "model"

            def build(mesh):
                def core(x):
                    w = jax.lax.psum(jnp.ones(()), MODEL_AXIS)
                    return jax.lax.psum(x.sum(axis=0), BATCH_AXIS) / w
                return shard_map(core, mesh,
                                 in_specs=(P(BATCH_AXIS, None),),
                                 out_specs=P())
        """), rules=["SHD002"])
        assert out == []

    def test_negative_tuple_axis_reduction(self):
        """psum over a TUPLE of axes — the 2-D mesh idiom — reduces
        every named axis and must satisfy SHD001's replicated claim."""
        out = lint(shard_src("""
            def build(mesh):
                def core(x):
                    return jax.lax.psum(x.sum(axis=0),
                                        ("batch", "model"))
                return shard_map(core, mesh,
                                 in_specs=(P(("batch", "model"), None),),
                                 out_specs=P())
        """), rules=["SHD001", "SHD002"])
        assert out == []

    def test_negative_module_constant_axis_cross_module(self):
        """BATCH_AXIS imported from another module resolves to its
        string value — the ops/ <- parallel/mesh.py idiom."""
        out = lint_many([
            ("pkg/mesh.py", """
                BATCH_AXIS = "batch"
            """),
            ("pkg/kern.py", shard_src("""
                from pkg.mesh import BATCH_AXIS

                def build(mesh):
                    def core(x):
                        return jax.lax.psum(x.sum(axis=0), BATCH_AXIS)
                    return shard_map(core, mesh,
                                     in_specs=(P(BATCH_AXIS, None),),
                                     out_specs=P())
            """))], rules=["SHD002"])
        assert out == []

    def test_negative_none_constant_spec_entry_cross_module(self):
        """An imported constant whose value is None parses as a
        replicated spec entry, not an unknown (sharded) one."""
        out = lint_many([
            ("pkg/mesh.py", """
                BATCH_AXIS = "batch"
                LANE_AXIS = None
            """),
            ("pkg/kern.py", shard_src("""
                from pkg.mesh import BATCH_AXIS, LANE_AXIS

                def build(mesh):
                    def core(x, tbl):
                        return jax.lax.psum(
                            (x * tbl[None, :]).sum(axis=0), BATCH_AXIS)
                    return shard_map(
                        core, mesh,
                        in_specs=(P(BATCH_AXIS, None), P(LANE_AXIS)),
                        out_specs=P())
            """))], rules=["SHD"])
        assert out == []

    def test_same_basename_module_resolves_to_sibling(self):
        """`from pkg.models.mesh import AXIS` with both ops/mesh.py and
        models/mesh.py present resolves the IMPORTING module's sibling
        (path-boundary + nearest-directory match), so the axis constant
        comes from the right file."""
        out = lint_many([
            ("pkg/ops/mesh.py", """
                AXIS = "batch"
            """),
            ("pkg/models/mesh.py", """
                AXIS = "lane"
            """),
            ("pkg/models/kern.py", shard_src("""
                from .mesh import AXIS

                def build(mesh):
                    def core(x):
                        return jax.lax.psum(x.sum(axis=0), AXIS)
                    return shard_map(core, mesh,
                                     in_specs=(P("lane", None),),
                                     out_specs=P())
            """))], rules=["SHD002"])
        assert out == []

    def test_constant_axis_mismatch_cross_module(self):
        """A mesh built by a cross-module factory (make_mesh) resolves
        its axis tuple; a collective naming a different constant's
        axis flags."""
        out = lint_many([
            ("pkg/mesh.py", """
                from jax.sharding import Mesh

                BATCH_AXIS = "batch"
                MODEL_AXIS = "model"

                def make_mesh(devs):
                    return Mesh(devs, (BATCH_AXIS,))
            """),
            ("pkg/kern.py", shard_src("""
                from pkg.mesh import BATCH_AXIS, MODEL_AXIS, make_mesh

                def build(devs):
                    mesh = make_mesh(devs)
                    def core(x):
                        return jax.lax.psum(x.sum(axis=0), MODEL_AXIS)
                    return shard_map(core, mesh,
                                     in_specs=(P(BATCH_AXIS, None),),
                                     out_specs=P())
            """))], rules=["SHD002"])
        assert len(out) == 1 and "'model'" in out[0].message


# -- SHD003: shard-variant nondeterminism ------------------------------------

class TestSHD003:
    def test_index_local_draw_mixing_with_sharded_rows(self):
        out = lint(shard_src("""
            def build(mesh):
                def core(x, key):
                    g = x * 2.0
                    rw = (jax.random.uniform(key, (128,)) < 0.5)
                    g = g * rw[:, None]
                    return jax.lax.psum(g.sum(axis=0), "batch")
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None), P()),
                                 out_specs=P())
        """), rules=["SHD003"])
        assert len(out) == 1
        assert "index-local" in out[0].message

    def test_host_branch_on_shard_variant_value(self):
        out = lint(shard_src("""
            def build(mesh):
                def core(x):
                    s = x.sum()
                    if s > 0:
                        s = s * 2.0
                    return jax.lax.psum(s, "batch")
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None),),
                                 out_specs=P())
        """), rules=["SHD003"])
        assert len(out) == 1
        assert "host control flow" in out[0].message

    def test_negative_trace_time_raise_bars_the_draw(self):
        """The promoted subsample pattern: the `raise` under the axis
        guard is a recorded path condition that kills the draw branch —
        the guarded repo shape scans clean."""
        out = lint(shard_src("""
            def impl(x, key, subsample, axis_name):
                if subsample < 1.0 and axis_name is not None:
                    raise ValueError("no sharded subsample")
                g = x * 2.0
                if subsample < 1.0:
                    rw = (jax.random.uniform(key, (128,)) < subsample)
                    g = g * rw[:, None]
                return jax.lax.psum(g.sum(axis=0), axis_name)

            def build(mesh, subsample):
                def core(x, key):
                    return impl(x, key, subsample, axis_name="batch")
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None), P()),
                                 out_specs=P())
        """), rules=["SHD003"])
        assert out == []

    def test_removing_the_raise_reintroduces_the_finding(self):
        """Same shape minus the trace-time bar: SHD003 catches in CI
        what used to only raise at trace time."""
        out = lint(shard_src("""
            def impl(x, key, subsample, axis_name):
                g = x * 2.0
                if subsample < 1.0:
                    rw = (jax.random.uniform(key, (128,)) < subsample)
                    g = g * rw[:, None]
                return jax.lax.psum(g.sum(axis=0), axis_name)

            def build(mesh, subsample):
                def core(x, key):
                    return impl(x, key, subsample, axis_name="batch")
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None), P()),
                                 out_specs=P())
        """), rules=["SHD003"])
        assert len(out) == 1

    def test_where_mask_application_also_flags(self):
        """The canonical jnp.where mask application is the same
        index-local bug as `x * mask` and must not hide behind the
        generic call join."""
        out = lint(shard_src("""
            def build(mesh):
                def core(x, key):
                    mask = jax.random.uniform(key, (128,)) < 0.5
                    w = jnp.where(mask[:, None], x, 0.0)
                    return jax.lax.psum(w.sum(axis=0), "batch")
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None), P()),
                                 out_specs=P())
        """), rules=["SHD003"])
        assert len(out) == 1
        assert "jnp.where" in out[0].message

    def test_negative_replicated_feature_draw(self):
        """A draw that only ever combines with replicated data (the
        colsample feature-mask shape) is shard-consistent — same key,
        same subset on every shard."""
        out = lint(shard_src("""
            def build(mesh):
                def core(x, key):
                    hist = jax.lax.psum(x.sum(axis=0), "batch")
                    fmask = jax.random.uniform(key, (16,)) < 0.5
                    gain = jnp.where(fmask, hist, -jnp.inf)
                    return gain
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None), P()),
                                 out_specs=P())
        """), rules=["SHD003"])
        assert out == []

    def test_negative_pytree_none_check_is_static(self):
        """`x.gzz is None` structure checks are trace-time static and
        must not count as host branching."""
        out = lint(shard_src("""
            def build(mesh):
                def core(x):
                    extra = None
                    if extra is None:
                        y = x.sum(axis=0)
                    else:
                        y = x.sum(axis=0) + extra
                    return jax.lax.psum(y, "batch")
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None),),
                                 out_specs=P())
        """), rules=["SHD003"])
        assert out == []


# -- SHD004: spec arity/rank mismatch ----------------------------------------

class TestSHD004:
    def test_in_specs_arity_mismatch(self):
        out = lint(shard_src("""
            def build(mesh):
                def core(x, y):
                    return jax.lax.psum(x + y, "batch")
                return shard_map(core, mesh,
                                 in_specs=(P("batch"),),
                                 out_specs=P())
        """), rules=["SHD004"])
        assert len(out) == 1
        assert "1 entry" in out[0].message and "2" in out[0].message

    def test_out_specs_count_mismatch(self):
        out = lint(shard_src("""
            def build(mesh):
                def core(x):
                    s = jax.lax.psum(x.sum(axis=0), "batch")
                    return s, s, s
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None),),
                                 out_specs=(P(), P()))
        """), rules=["SHD004"])
        assert len(out) == 1
        assert "out_specs has 2" in out[0].message

    def test_rank_mismatch_against_shape_unpack(self):
        out = lint(shard_src("""
            def build(mesh):
                def core(x):
                    n, d = x.shape
                    return jax.lax.psum(x.sum(axis=0), "batch")
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None, None),),
                                 out_specs=P())
        """), rules=["SHD004"])
        assert len(out) == 1
        assert "rank-2" in out[0].message

    def test_negative_vararg_core_with_repeated_specs(self):
        """The stats-engine `core(X, y, w, *extras)` shape with
        `(P(...),)*n` repeated specs has no static arity to violate."""
        out = lint(shard_src("""
            def build(mesh, n_extras):
                def core(x, y, *extras):
                    return jax.lax.psum((x * y[:, None]).sum(axis=0),
                                        "batch")
                return shard_map(
                    core, mesh,
                    in_specs=(P("batch", None), P("batch"))
                    + (P(),) * n_extras,
                    out_specs=P())
        """), rules=["SHD004"])
        assert out == []

    def test_negative_defaulted_param_may_go_unmapped(self):
        """shard_map specs match the CALL's argument pytree, not the
        signature — a trailing defaulted param with no spec is legal."""
        out = lint(shard_src("""
            def build(mesh):
                def core(x, scale=1.0):
                    return jax.lax.psum((x * scale).sum(axis=0),
                                        "batch")
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None),),
                                 out_specs=P())
        """), rules=["SHD004"])
        assert out == []

    def test_negative_exact_arity(self):
        out = lint(shard_src("""
            def build(mesh):
                def core(x, y):
                    return jax.lax.psum(x + y, "batch")
                return shard_map(core, mesh,
                                 in_specs=(P("batch"), P("batch")),
                                 out_specs=P())
        """), rules=["SHD004"])
        assert out == []


# -- SHD005: host merge without the cross-process fold -----------------------

class TestSHD005:
    def test_np_sum_over_fetched_sharded_array(self):
        out = lint("""
            import numpy as np
            from pkg.parallel import multihost

            def run(local, n):
                mesh = multihost.global_mesh()
                arr = multihost.host_local_rows(local, mesh, n)
                rows = np.asarray(arr)
                return np.sum(rows)
        """, rules=["SHD005"])
        assert len(out) == 1
        assert "addressable shards" in out[0].message

    def test_method_sum_on_fetched_value(self):
        out = lint("""
            import numpy as np
            from pkg.parallel import multihost

            def run(X, mesh):
                multihost.initialize()
                arr, n = multihost_put(X)
                fetched = np.asarray(fit_stats_sharded(mesh, arr))
                return fetched.sum()

            def multihost_put(X):
                return multihost.host_local_rows(X, None, 4), 4
        """, rules=["SHD005"])
        assert len(out) == 1

    def test_branch_assigned_producer_still_caught(self):
        """A sharded producer assigned inside an if-branch is seen by an
        outer-level fetch (the taint pass iterates to a fixpoint —
        ast.walk order must not matter)."""
        out = lint("""
            import numpy as np
            from pkg.parallel import multihost

            def run(local, n, small):
                mesh = multihost.global_mesh()
                if small:
                    arr = multihost.host_local_rows(local[:n], mesh, n)
                else:
                    arr = multihost.host_local_rows(local, mesh, n)
                rows = np.asarray(arr)
                return np.sum(rows)
        """, rules=["SHD005"])
        assert len(out) == 1

    def test_negative_reduce_on_device_before_fetch(self):
        """psum inside the sharded program, host just reads the already
        -global scalar: the documented-correct shape."""
        out = lint("""
            import numpy as np
            from pkg.parallel import multihost

            def run(local, n, fitted):
                mesh = multihost.global_mesh()
                arr = multihost.host_local_rows(local, mesh, n)
                total = np.asarray(device_total(arr))
                return total
        """, rules=["SHD005"])
        assert out == []

    def test_negative_single_process_module_untouched(self):
        out = lint("""
            import numpy as np

            def run(x):
                rows = np.asarray(x)
                return np.sum(rows)
        """, rules=["SHD005"])
        assert out == []


# -- ENV001: knob registry ---------------------------------------------------

class TestENV001:
    def test_unregistered_knob_read(self):
        out = lint("""
            import os

            def f():
                return os.environ.get("TMOG_TOTALLY_NEW_KNOB", "1")
        """, rules=["ENV001"])
        assert len(out) == 1
        assert "TMOG_TOTALLY_NEW_KNOB" in out[0].message

    def test_env_on_and_subscript_reads_also_checked(self):
        out = lint("""
            import os

            def f():
                a = env_on("TMOG_NOT_REGISTERED_A")
                b = os.environ["TMOG_NOT_REGISTERED_B"]
                return a, b
        """, rules=["ENV001"])
        assert sorted("TMOG_NOT_REGISTERED" in f.message
                      for f in out) == [True, True]

    def test_setdefault_and_membership_reads_also_checked(self):
        """environ.setdefault and `"TMOG_X" in os.environ` establish
        knob-dependent behavior just like .get — same registry
        contract."""
        out = lint("""
            import os

            def f():
                os.environ.setdefault("TMOG_NOT_REGISTERED_C", "1")
                if "TMOG_NOT_REGISTERED_D" in os.environ:
                    return True
                return False
        """, rules=["ENV001"])
        assert len(out) == 2

    def test_negative_registered_knob(self):
        out = lint("""
            import os

            def f():
                return os.environ.get("TMOG_TREE_SHARD", "")
        """, rules=["ENV001"])
        assert out == []

    def test_registry_row_missing_from_doc(self, tmp_path):
        out = lint_tree(tmp_path, {
            "docs/perf.md": "Only `TMOG_DOCUMENTED` is described here.",
            "knobs.py": """
                KNOBS = [
                    {"name": "TMOG_DOCUMENTED", "default": "1",
                     "doc": "docs/perf.md", "desc": "fine"},
                    {"name": "TMOG_FORGOTTEN", "default": "1",
                     "doc": "docs/perf.md", "desc": "drifted"},
                ]
            """,
            "mod.py": """
                import os
                x = os.environ.get("TMOG_DOCUMENTED", "")
            """,
        }, rules=["ENV001"])
        assert len(out) == 1
        assert "TMOG_FORGOTTEN" in out[0].message
        assert out[0].path == "knobs.py"

    def test_doc_mention_is_boundary_aware(self, tmp_path):
        """A knob that is a PREFIX of a documented knob must not pass
        on the longer name's mentions (a TMOG_X / TMOG_X_DIR pair)."""
        out = lint_tree(tmp_path, {
            "docs/perf.md": "Set `TMOG_CACHE_DIR` to a directory.",
            "knobs.py": """
                KNOBS = [
                    {"name": "TMOG_CACHE_DIR", "default": "",
                     "doc": "docs/perf.md", "desc": "fine"},
                    {"name": "TMOG_CACHE", "default": "",
                     "doc": "docs/perf.md", "desc": "prefix of above"},
                ]
            """,
        }, rules=["ENV001"])
        assert len(out) == 1 and "TMOG_CACHE" in out[0].message

    def test_registry_row_with_missing_doc_file(self, tmp_path):
        out = lint_tree(tmp_path, {
            "knobs.py": """
                KNOBS = [
                    {"name": "TMOG_X", "default": "1",
                     "doc": "docs/nope.md", "desc": "orphan"},
                ]
            """,
        }, rules=["ENV001"])
        assert len(out) == 1 and "does not exist" in out[0].message

    def test_real_registry_matches_real_code_and_docs(self):
        """The committed registry covers every TMOG_* read in the repo
        and every row's doc file mentions its knob — scanned exactly as
        ci.sh step 2 does."""
        ctxs, errors = scan_paths(
            ["transmogrifai_tpu", "tests", "bench.py", "tools"],
            REPO_ROOT)
        out = [f for f in errors + run_rules(ctxs, only=["ENV001"])
               if f.rule == "ENV001"]
        assert out == [], "\n".join(f.render() for f in out)


# -- EVT001: event schema ----------------------------------------------------

EVT_DOC = """
    # Observability

    ## The event log (`events.jsonl`)

    | event | source | fields |
    |---|---|---|
    | `alpha_done` / `alpha_start` | pkg/mod.py | `n` |
    | `beta_tick` | pkg/mod.py | `t` |
"""


class TestEVT001:
    def test_unlisted_event_name(self, tmp_path):
        out = lint_tree(tmp_path, {
            "docs/observability.md": EVT_DOC,
            "pkg/__init__.py": "",
            "pkg/mod.py": """
                def f(log):
                    log.event("alpha_done", n=1)
                    log.event("alpha_start", n=1)
                    log.event("beta_tick", t=0.0)
                    log.event("gamma_unlisted", x=2)
            """,
        }, rules=["EVT001"])
        assert len(out) == 1
        assert "gamma_unlisted" in out[0].message

    def test_stale_table_row(self, tmp_path):
        out = lint_tree(tmp_path, {
            "docs/observability.md": EVT_DOC,
            "pkg/__init__.py": "",
            "pkg/mod.py": """
                def f(log):
                    log.event("alpha_done", n=1)
                    log.event("alpha_start", n=1)
            """,
        }, rules=["EVT001"])
        assert len(out) == 1
        assert "beta_tick" in out[0].message
        assert out[0].path == "docs/observability.md"

    def test_subtree_scan_still_checks_call_sites(self, tmp_path):
        """Scanning a package SUBDIRECTORY (its own __init__.py in the
        scan, the top-level one absent) still runs the unlisted-name
        direction — only the stale direction needs the whole package."""
        out = lint_tree(tmp_path, {
            "docs/observability.md": EVT_DOC,
            "pkg/__init__.py": "",
            "pkg/serve/__init__.py": "",
            "pkg/serve/mod.py": """
                def f(log):
                    log.event("serve_new_thing", x=1)
            """,
        }, paths=("pkg/serve",), rules=["EVT001"])
        assert len(out) == 1
        assert "serve_new_thing" in out[0].message
        assert all("stale" not in f.message for f in out)

    def test_stale_scoping_needs_full_package_view(self, tmp_path):
        """Without the package __init__.py in the scan, unmatched table
        rows are NOT stale — a single-file scan cannot judge the
        package's full emitter set."""
        out = lint_tree(tmp_path, {
            "docs/observability.md": EVT_DOC,
            "pkg/__init__.py": "",
            "pkg/mod.py": """
                def f(log):
                    log.event("alpha_done", n=1)
            """,
        }, paths=("pkg/mod.py",), rules=["EVT001"])
        assert out == []

    def test_negative_all_listed_and_emitted(self, tmp_path):
        out = lint_tree(tmp_path, {
            "docs/observability.md": EVT_DOC,
            "pkg/__init__.py": "",
            "pkg/mod.py": """
                def f(log):
                    log.event("alpha_done", n=1)
                    log.event("alpha_start", n=1)
                    log.event("beta_tick", t=0.0)
            """,
        }, rules=["EVT001"])
        assert out == []

    def test_stale_needs_an_emitting_package_not_any_package(self,
                                                             tmp_path):
        """Scanning a package that emits NO events (the tools/ case)
        must not declare the event table stale, even though its
        __init__.py is in the scan."""
        out = lint_tree(tmp_path, {
            "docs/observability.md": EVT_DOC,
            "toolpkg/__init__.py": "",
            "toolpkg/util.py": "def f():\n    return 1\n",
        }, paths=("toolpkg",), rules=["EVT001"])
        assert out == []

    def test_negative_tests_and_scripts_out_of_scope(self, tmp_path):
        """Only package files (top dir with a scanned __init__.py) are
        checked: tests may emit fixture events freely."""
        out = lint_tree(tmp_path, {
            "docs/observability.md": EVT_DOC,
            "pkg/__init__.py": "",
            "pkg/mod.py": """
                def f(log):
                    log.event("alpha_done", n=1)
                    log.event("alpha_start", n=1)
                    log.event("beta_tick", t=0.0)
            """,
            "tests/test_mod.py": """
                def test_f(log):
                    log.event("made_up_fixture_event")
            """,
        }, rules=["EVT001"])
        assert out == []

    def test_real_event_table_matches_real_emitters(self):
        """Code <-> docs/observability.md table, both directions, on
        the real repo."""
        ctxs, errors = scan_paths(["transmogrifai_tpu", "tests"],
                                  REPO_ROOT)
        out = [f for f in errors + run_rules(ctxs, only=["EVT001"])
               if f.rule == "EVT001"]
        assert out == [], "\n".join(f.render() for f in out)


# -- the repo's own sharded modules scan clean -------------------------------

class TestRepoShardedModulesClean:
    @pytest.fixture(scope="class")
    def shd_findings(self):
        ctxs, errors = scan_paths(["transmogrifai_tpu"], REPO_ROOT)
        return errors + run_rules(ctxs, only=["SHD"])

    def test_all_sharded_ops_modules_clean(self, shd_findings):
        """Every shard_map site in ops/stats_engine, ops/trees,
        ops/glm_sweep, parallel/* proves its out_spec claims — the
        acceptance pin that the baseline stays EMPTY with SHD on."""
        assert shd_findings == [], \
            "\n".join(f.render() for f in shd_findings)

    def test_sites_actually_discovered(self):
        """The clean scan must not be vacuous: the analysis resolves
        the repo's real shard_map sites and proves replicated outputs
        reduced (not 'skipped')."""
        from tools.tmoglint.shardflow import ShardAnalysis
        ctxs, _ = scan_paths(["transmogrifai_tpu"], REPO_ROOT)
        sa = ShardAnalysis(ctxs)
        paths = {s.mod.path for s in sa.sites}
        for expected in ("transmogrifai_tpu/ops/stats_engine.py",
                         "transmogrifai_tpu/ops/glm_sweep.py",
                         "transmogrifai_tpu/ops/trees.py"):
            assert expected in paths, sorted(paths)
        assert len(sa.sites) >= 7
        assert not sa.any_incomplete
        # the collective observations bind the real mesh axis
        axes = set()
        for _mod, _node, _tail, per_site in sa.collectives.values():
            for vals in per_site.values():
                for v in vals:
                    if isinstance(v, frozenset):
                        axes |= v
        assert "batch" in axes


# -- the subsample bar: both layers pinned -----------------------------------

class TestSubsampleBarBothLayers:
    def test_trace_time_raise_still_fires(self):
        """Layer 1 (backstop): the sharded fused fit refuses
        subsample<1 at trace time."""
        from transmogrifai_tpu.ops.trees import _fit_gbt_folds_impl
        Xb = np.zeros((8, 3), np.int8)
        y = np.zeros(8, np.float32)
        W = np.ones((2, 8), np.float32)
        with pytest.raises(ValueError, match="subsample"):
            _fit_gbt_folds_impl(Xb, y, W, None, n_rounds=1, depth=2,
                                n_bins=4, subsample=0.5,
                                axis_name="batch")

    def test_lint_time_layer_catches_it_first(self):
        """Layer 2 (SHD003): the real ops/trees.py guard is recognized
        (clean scan, asserted above); the fixture in
        TestSHD003.test_removing_the_raise_reintroduces_the_finding
        proves removing the guard flags at lint time, before any sweep
        runs. Here: the real module, scanned alone with its imports,
        stays clean under SHD003."""
        ctxs, _ = scan_paths(["transmogrifai_tpu/ops",
                              "transmogrifai_tpu/parallel"], REPO_ROOT)
        out = [f for f in run_rules(ctxs, only=["SHD003"])]
        assert out == [], "\n".join(f.render() for f in out)


# -- CLI: family selection, scoping, parallel parity -------------------------

class TestCLIFamilies:
    def _run(self, args, cwd=REPO_ROOT):
        env = dict(os.environ, PYTHONPATH=REPO_ROOT)
        return subprocess.run(
            [sys.executable, "-m", "tools.tmoglint"] + args,
            cwd=cwd, env=env, capture_output=True, text=True)

    def test_family_selection_shd_env_evt(self, tmp_path):
        (tmp_path / "mod.py").write_text(textwrap.dedent("""
            import os
            import jax

            @jax.jit
            def f(x):
                return jax.lax.psum(x, "batch")

            FLAG = os.environ.get("TMOG_NOT_A_REAL_KNOB", "")
        """))
        proc = self._run(["mod.py", "--root", str(tmp_path),
                          "--no-baseline", "--rules", "SHD,ENV,EVT",
                          "--format", "json"])
        assert proc.returncode == 1, proc.stdout + proc.stderr
        report = json.loads(proc.stdout)
        assert report["rules"] == ["ENV001", "EVT001", "SHD001",
                                   "SHD002", "SHD003", "SHD004",
                                   "SHD005"]
        assert report["counts_by_rule"] == {"ENV001": 1, "SHD002": 1}

    def test_scoping_guard_composes_with_new_families(self, tmp_path):
        """A baselined TPU entry is out of scope for a SHD-only scan:
        neither new nor stale."""
        (tmp_path / "clean.py").write_text("x = 1\n")
        base = tmp_path / "base.json"
        base.write_text(json.dumps({"version": 1, "findings": [
            {"fingerprint": "feedfeedfeedfeed", "rule": "TPU003",
             "path": "other.py", "line": 1, "col": 0,
             "message": "unrelated grandfathered debt", "snippet": ""}]}))
        proc = self._run(["clean.py", "--root", str(tmp_path),
                          "--baseline", str(base), "--rules", "SHD",
                          "--format", "json"])
        assert proc.returncode == 0, proc.stdout + proc.stderr
        report = json.loads(proc.stdout)
        assert report["stale_baseline_entries"] == []

    def test_parallel_jobs_match_serial_with_new_families(self, tmp_path):
        """--jobs 1 and --jobs 2 produce identical reports with SHD/
        ENV/EVT findings present (they are project rules — the pool
        split must not change them)."""
        (tmp_path / "kern.py").write_text(textwrap.dedent("""
            import jax
            from jax.sharding import PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def build(mesh):
                def core(x):
                    return x.sum(axis=0)
                return shard_map(core, mesh,
                                 in_specs=(P("batch", None),),
                                 out_specs=P())
        """))
        (tmp_path / "knob.py").write_text(textwrap.dedent("""
            import os
            FLAG = os.environ.get("TMOG_NOT_A_REAL_KNOB_2", "")
        """))
        for i in range(4):
            (tmp_path / f"filler{i}.py").write_text(f"x = {i}\n")
        outs = []
        for jobs in ("1", "2"):
            proc = self._run([".", "--root", str(tmp_path),
                              "--no-baseline", "--jobs", jobs,
                              "--format", "json"])
            assert proc.returncode == 1, proc.stdout + proc.stderr
            report = json.loads(proc.stdout)
            outs.append([(f["rule"], f["path"], f["fingerprint"])
                         for f in report["new"]])
        assert outs[0] == outs[1]
        assert {r for r, _, _ in outs[0]} >= {"SHD001", "ENV001"}

    def test_list_rules_includes_new_families(self):
        proc = self._run(["--list-rules"])
        assert proc.returncode == 0
        for rid in ("SHD001", "SHD002", "SHD003", "SHD004", "SHD005",
                    "ENV001", "EVT001"):
            assert rid in proc.stdout
