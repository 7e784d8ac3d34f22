"""The start-up ledger (utils/tracing.RecompileTracker, PR 33): always on,
a true compile told from a persistent-cache load, six seconds that add up
to first contact, nested and concurrent intervals counted once, `te` set
by the first root job span alone, counts that `activate()` no longer
resets. (`ServingEngine.prewarm()` and the recompile watch with the
collector off ride tests/test_serving.py's fitted model:
TestWatchWithCollectionOff.)"""
import json
import os
import subprocess
import sys

import pytest

from transmogrifai_tpu.utils import platform, tracing
from transmogrifai_tpu.utils.metrics import MetricsCollector
from transmogrifai_tpu.utils.tracing import (
    _CACHE_HIT_EVENT, _COMPILE_EVENT, _LOWER_EVENT, _TRACE_EVENT,
    RecompileTracker, TraceTree)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SECONDS = ("startup_import_s", "startup_reach_device_s",
           "startup_trace_lower_s", "startup_cache_load_s",
           "startup_compile_s", "startup_run_s")

# one user-style process: collection is NEVER enabled; a validate nested
# in a workflow span runs one jitted program; the record goes to stdout
CHILD = """
import json, jax, jax.numpy as jnp
import transmogrifai_tpu
from transmogrifai_tpu.utils import platform, tracing
from transmogrifai_tpu.utils.metrics import collector
f = jax.jit(lambda x: (x * 3.0).sum())
with collector.trace_span("Workflow.train", kind="workflow"):
    with collector.trace_span("CrossValidation", kind="validate"):
        f(jnp.ones(7)).block_until_ready()
    inner_te = tracing.tracker.te
rec = platform.startup_record()
marks = [tracing.tracker.t0, tracing.tracker.t1, tracing.tracker.te]
jax.jit(lambda x: x - 1.0)(jnp.ones(7)).block_until_ready()
print(json.dumps({"inner_te": inner_te, "enabled": collector.enabled,
                  "true": tracing.tracker.true_compiles,
                  "hits": tracing.tracker.total_cache_hits, "rec": rec,
                  "marks": marks,
                  "later": platform.startup_record()}))
"""


def _child(cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               TMOG_COMPILE_CACHE_DIR=str(cache_dir))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", CHILD], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def cold_and_warm(tmp_path_factory):
    """The same process twice over one fresh cache directory."""
    cache = tmp_path_factory.mktemp("startup_cache")
    return _child(cache), _child(cache)


def _row(rec, name, key="programs"):
    return next(r for r in rec[key] if r["fun_name"] == name)


class TestAlwaysOn:
    def test_counts_with_collection_never_enabled(self, cold_and_warm):
        cold, warm = cold_and_warm
        assert cold["enabled"] is False and warm["enabled"] is False
        assert cold["true"] >= 2 and cold["hits"] == 0
        assert warm["true"] == 0 and warm["hits"] == cold["true"]

    def test_a_true_compile_and_a_cache_load_are_told_apart(
            self, cold_and_warm):
        cold, warm = (r["rec"] for r in cold_and_warm)
        assert cold["startup_compile_s"] > 0
        assert cold["startup_cache_load_s"] == 0.0
        assert warm["startup_compile_s"] == 0.0   # 0.0, not None
        assert warm["startup_cache_load_s"] > 0
        assert cold["startup_programs"] == warm["startup_programs"] >= 1
        for rec, hit in ((cold, False), (warm, True)):
            row = _row(rec, "<lambda>")
            assert row["cache_hit"] is hit
            assert row["trace_s"] > 0 and row["lower_s"] > 0
            assert (row["load_s"] > 0) is hit
            assert (row["compile_s"] > 0) is not hit
            assert row["loads"] + row["compiles"] == 1
        rows = cold["programs"]
        totals = [r["trace_s"] + r["lower_s"] + r["load_s"] + r["compile_s"]
                  for r in rows]
        assert totals == sorted(totals, reverse=True)   # slowest first

    def test_the_six_seconds_add_up_to_first_contact(self, cold_and_warm):
        for run in cold_and_warm:
            rec = run["rec"]
            assert rec["complete"] is True
            assert all(rec[k] >= 0 for k in SECONDS), rec
            assert sum(rec[k] for k in SECONDS) == pytest.approx(
                rec["first_contact_s"], abs=1e-6)
            t0, t1, te = run["marks"]   # the tracker's own marks
            assert rec["first_contact_s"] == pytest.approx(te - t0, abs=1e-9)
            assert rec["startup_import_s"] == pytest.approx(t1 - t0)
            assert t0 < t1 < te
            if rec["before_import_s"] is not None:   # /proc can be read
                assert 0 < rec["before_import_s"] < 120
            assert 0 <= rec["listener_s"] < 0.1
            assert rec["events_dropped"] == 0

    def test_the_nested_validate_does_not_end_start_up_early(
            self, cold_and_warm):
        for run in cold_and_warm:
            assert run["inner_te"] is None
            # what ran after the first result is not start-up's: the
            # record is frozen, the later program named beside it
            later = run["later"]
            assert later["first_contact_s"] == run["rec"]["first_contact_s"]
            assert later["startup_programs"] == \
                run["rec"]["startup_programs"]
            assert _row(later, "<lambda>", "later_programs")["loads"] \
                + _row(later, "<lambda>", "later_programs")["compiles"] == 1


def _ledger(events, t0=100.0, t1=103.0, te=120.0):
    tr = RecompileTracker()
    tr.mark_import(t0, t1)
    # tmoglint: disable=THR001  a hand-made ledger, before any thread
    tr._events, tr.te = list(events), te
    return tr.startup_record()


class TestTheArithmetic:
    def test_nested_and_concurrent_intervals_are_counted_once(self):
        rec = _ledger([
            ("trace", 105.0, 109.0, "outer"),
            ("trace", 106.0, 107.0, "inner"),       # nested in outer's
            ("compile", 108.0, 108.5, "eager_op"),  # an eager op, mid-trace
            ("lower", 109.0, 110.0, "outer"),
            ("cache_load", 110.0, 112.0, "outer"),
            ("compile", 111.0, 113.0, "helper"),    # a helper thread's
            ("trace", 119.0, 125.0, "late")])       # straddles te
        assert rec["startup_import_s"] == 3.0
        assert rec["startup_reach_device_s"] == 2.0
        # [105, 110) less the eager compile, and [119, 120)
        assert rec["startup_trace_lower_s"] == pytest.approx(5.5)
        assert rec["startup_cache_load_s"] == pytest.approx(1.0)
        assert rec["startup_compile_s"] == pytest.approx(2.5)
        assert rec["startup_run_s"] == pytest.approx(6.0)
        assert sum(rec[k] for k in SECONDS) == pytest.approx(20.0, abs=1e-9)
        assert rec["first_contact_s"] == 20.0
        assert rec["startup_programs"] == 3
        assert _row(rec, "outer") == {
            "fun_name": "outer", "trace_s": 4.0, "lower_s": 1.0,
            "load_s": 2.0, "compile_s": 0.0, "loads": 1, "compiles": 0,
            "cache_hit": True}
        assert rec["later_programs"] == []

    def test_a_process_with_no_finished_job_runs_to_the_moment_read(self):
        import time
        now = time.time()
        rec = _ledger([("compile", now - 5.0, now - 4.0, "f")],
                      t0=now - 10.0, t1=now - 8.0, te=None)
        assert rec["complete"] is False
        assert rec["first_contact_s"] == pytest.approx(10.0, abs=0.5)
        assert sum(rec[k] for k in SECONDS) == pytest.approx(
            rec["first_contact_s"], abs=1e-6)
        assert rec["startup_compile_s"] == pytest.approx(1.0)

    def test_no_program_at_all_is_all_reach_device(self):
        rec = _ledger([])
        assert rec["startup_reach_device_s"] == 17.0
        assert rec["startup_run_s"] == rec["startup_compile_s"] == 0.0
        assert rec["startup_programs"] == 0 and rec["programs"] == []

    def test_program_names_lose_their_wrapper(self):
        tr = RecompileTracker()
        tr._on_event(_TRACE_EVENT, 0.25, fun_name="f")
        tr._on_event(_LOWER_EVENT, 0.5, fun_name="jit(f)")
        tr._on_event(_CACHE_HIT_EVENT, 0.01)
        tr._on_event(_COMPILE_EVENT, 1.0, fun_name="jit(f)")
        tr._on_event("/jax/some/other_duration", 9.0, fun_name="g")
        (row,) = tr.startup_record()["programs"]
        assert row == {"fun_name": "f", "trace_s": pytest.approx(0.25),
                       "lower_s": pytest.approx(0.5),
                       "load_s": pytest.approx(1.0), "compile_s": 0.0,
                       "loads": 1, "compiles": 0, "cache_hit": True}

    def test_past_the_cap_only_the_counters_run(self, monkeypatch):
        monkeypatch.setattr(tracing, "_MAX_EVENTS", 3)
        tr = RecompileTracker()
        for _ in range(5):
            tr._on_event(_COMPILE_EVENT, 0.001, fun_name="jit(f)")
        rec = tr.startup_record()
        assert tr.true_compiles == 5 and rec["events_dropped"] == 2
        assert rec["startup_programs"] == 3


class TestLifecycle:
    def test_activate_no_longer_resets_the_counts(self):
        tr = RecompileTracker()
        tr._on_event(_COMPILE_EVENT, 0.01, fun_name="jit(f)")
        tr._on_event(_CACHE_HIT_EVENT, 0.0)
        tr._on_event(_COMPILE_EVENT, 0.01, fun_name="jit(g)")
        tr.activate(TraceTree())
        assert (tr.total_compiles, tr.true_compiles,
                tr.total_cache_hits) == (2, 1, 1)
        tr.deactivate()
        tr._on_event(_COMPILE_EVENT, 0.01, fun_name="jit(h)")
        assert tr.true_compiles == 2    # off or on, it counts

    def test_booking_to_a_span_is_a_view_while_a_tree_is_active(self):
        tr, tree = RecompileTracker(), TraceTree()
        sp = tree.open("stage", "stage")
        tr._on_event(_COMPILE_EVENT, 0.5)       # no tree yet: not booked
        tr.activate(tree)
        tr._on_event(_CACHE_HIT_EVENT, 0.0)
        tr._on_event(_COMPILE_EVENT, 0.25)
        assert sp.attrs == {"compiles": 1, "compile_seconds": 0.25,
                            "cache_hits": 1}
        assert tr.total_compiles == 2

    @pytest.fixture()
    def fresh(self, monkeypatch):
        tr = RecompileTracker()
        monkeypatch.setattr(tracing, "tracker", tr)
        return tr, MetricsCollector()

    def test_te_is_the_first_root_job_span_alone(self, fresh):
        tr, c = fresh
        with c.trace_span("prep", kind="host_step"):
            pass
        assert tr.te is None                    # not a job's span
        with pytest.raises(RuntimeError):
            with c.trace_span("CV", kind="validate"):
                raise RuntimeError("no result")
        assert tr.te is None                    # a failed job is no result
        with c.trace_span("W.train", kind="workflow"):
            with c.trace_span("CV", kind="validate"):
                pass
            assert tr.te is None                # nested: not yet
        te = tr.te
        assert te is not None and platform.startup_record()["complete"]
        with c.trace_span("CV", kind="validate"):
            pass
        assert tr.te == te                      # the first one only

    def test_a_job_closed_on_another_thread_does_not_raise(self):
        """An abandoned span generator finalised by another thread: no
        depth there, and the close sits in a `finally`."""
        import threading
        tr = RecompileTracker()
        tr.job_enter()
        t = threading.Thread(target=tr.job_exit, args=(False,))
        t.start()
        t.join()
        assert tr.te is None
        tr.job_exit(True)
        assert tr.te is not None

    def test_the_listeners_cost_counts_retrievals_too(self):
        tr = RecompileTracker()
        tr._on_event(_CACHE_HIT_EVENT, 0.0)
        first = tr.listener_seconds
        assert first > 0
        tr._on_event(_COMPILE_EVENT, 0.01, fun_name="jit(f)")
        assert tr.listener_seconds > first and tr.total_cache_hits == 1

    def test_the_package_installed_the_one_listener(self):
        from jax._src import monitoring
        mine = [fn for fn in monitoring.get_event_duration_listeners()
                if getattr(fn, "__self__", None) is tracing.tracker]
        assert len(mine) == 1
        assert not [fn for fn in monitoring.get_event_time_span_listeners()
                    if isinstance(getattr(fn, "__self__", None),
                                  RecompileTracker)]


@pytest.mark.parametrize("pinned,kernels_on,libtpu,starts", [
    ("cpu", True, True, False),     # the tests, CPU serving
    ("", True, True, True),         # a TPU host: nothing pinned
    ("tpu", True, True, True),
    ("tpu,cpu", True, True, True),
    ("", False, True, False),       # TMOG_NO_PALLAS
    ("", True, False, False),       # a laptop, a GPU host: no libtpu
], ids=["cpu", "unpinned", "tpu", "tpu-then-cpu", "kernels-off",
        "no-libtpu"])
def test_kernel_modules_are_imported_on_a_thread_where_kernels_may_run(
        monkeypatch, pinned, kernels_on, libtpu, starts):
    """platform.prefetch_kernel_modules: jax's Pallas modules, on a daemon
    thread whose interval the ledger keeps, unless JAX is pinned off the
    TPU, the host has no libtpu or the kernels are off; a failing import
    stays in the thread."""
    import jax
    from transmogrifai_tpu.ops import pallas_hist
    from transmogrifai_tpu.utils.tracing import tracker
    monkeypatch.setattr(type(jax.config), "jax_platforms",
                        property(lambda self: pinned))
    monkeypatch.setattr(pallas_hist, "_enabled", kernels_on)
    monkeypatch.setattr(platform.importlib.util, "find_spec",
                        lambda name: object() if libtpu else None)
    monkeypatch.setattr(tracker, "kernel_import", None)
    seen = []

    def fake_import(name):
        seen.append(name)
        if name.endswith(".tpu"):
            raise ImportError("a broken install")
    monkeypatch.setattr(platform.importlib, "import_module", fake_import)
    thread = platform.prefetch_kernel_modules()
    assert (thread is not None) == starts
    if starts:
        thread.join(10)
        assert thread.daemon and not thread.is_alive()
        assert seen == ["jax.experimental.pallas",
                        "jax.experimental.pallas.tpu"]
        assert platform.startup_record()["kernel_import_s"] >= 0.0
    else:
        assert seen == []
        assert platform.startup_record()["kernel_import_s"] is None
