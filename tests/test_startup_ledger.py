"""The start-up ledger (utils/tracing.RecompileTracker, PR 33): always on,
a true compile told from a persistent-cache load, six seconds that add up
to first contact, nested and concurrent intervals counted once, `te` set
by the first root job span alone, counts that `activate()` no longer
resets; since PR 49 the instant the backend came up (a one-shot timer on
jax's backend factories) and reaching the device in two by it.
(`ServingEngine.prewarm()` and the recompile watch with the collector off
ride tests/test_serving.py's fitted model: TestWatchWithCollectionOff.)"""
import json
import os
import subprocess
import sys
import types

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
import json, threading, jax, jax.numpy as jnp
import transmogrifai_tpu
from transmogrifai_tpu.utils import platform, tracing
from transmogrifai_tpu.utils.metrics import collector
from jax._src import xla_bridge
def timed():
    return sorted(k for k, r in xla_bridge._backend_factories.items()
                  if "_timed_factory" in getattr(r.factory, "__qualname__", ""))
before = platform.startup_record()      # no backend is up yet
timed_before = timed()
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
                  "marks": marks, "before": before,
                  "timed": [timed_before, timed()],
                  "threads": [t.name for t in threading.enumerate()],
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


SPLIT = ("startup_backend_up_s", "startup_first_dispatch_s",
         "startup_backend_up_cpu_s")


class TestTheBackendsInstant:
    """One user-style process on the CPU: the package imported before any
    backend is up, the CPU's factory timed once, the TPU's never called."""

    def test_the_three_are_none_while_no_backend_is_up(self, cold_and_warm):
        for run in cold_and_warm:
            before = run["before"]
            assert [before[k] for k in SPLIT] == [None] * 3
            assert before["backend_up_before_import"] is False
            assert before["complete"] is False and before["backend_inits"] == []
            assert before["ledger_version"] == 2

    def test_the_two_seconds_add_up_to_reaching_the_device(
            self, cold_and_warm):
        for run in cold_and_warm:
            rec = run["rec"]
            assert rec["ledger_version"] == 2
            assert rec["backend_up_before_import"] is False
            assert all(rec[k] >= 0 for k in SPLIT), rec
            assert rec["startup_backend_up_s"] \
                + rec["startup_first_dispatch_s"] == pytest.approx(
                    rec["startup_reach_device_s"], abs=1e-9)
            assert rec["startup_backend_up_s"] > 0
            (row,) = rec["backend_inits"]
            assert row["platform"] == "cpu" and row["ok"] is True
            # this process's first jax call is an operation, so its
            # backend came up INSIDE the first program event: the instant
            # is held to the interval it splits
            assert row["begin_s"] + row["init_s"] \
                >= rec["startup_backend_up_s"]
            # CPU seconds of every thread: at most the interval on each core
            assert rec["startup_backend_up_cpu_s"] \
                <= rec["startup_backend_up_s"] * os.cpu_count() + 0.05
            assert rec["kernel_import_cpu_s"] is None   # no thread on a CPU pin
            assert run["later"]["startup_backend_up_s"] \
                == rec["startup_backend_up_s"]

    def test_nothing_is_left_timed_or_running_after_the_first_job(
            self, cold_and_warm):
        for run in cold_and_warm:
            timed_before, timed_after = run["timed"]
            assert "cpu" in timed_before and timed_after == []
            assert not [t for t in run["threads"] if t.startswith("tmog")]


def _ledger(events, t0=100.0, t1=103.0, te=120.0, inits=(),
            up_at_install=False, t1_cpu=1.0):
    tr = RecompileTracker()
    tr.mark_import(t0, t1)
    # tmoglint: disable=THR001  a hand-made ledger, before any thread
    tr._events, tr.te = list(events), te
    tr.backend_inits, tr.t1_cpu = list(inits), t1_cpu
    tr.backend_up_at_install = up_at_install
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


REPLAYED = [
    ("trace", 105.0, 109.0, "outer"), ("trace", 106.0, 107.0, "inner"),
    ("compile", 108.0, 108.5, "eager_op"), ("lower", 109.0, 110.0, "outer"),
    ("cache_load", 110.0, 112.0, "outer"),
    ("compile", 111.0, 113.0, "helper"), ("trace", 119.0, 125.0, "late")]
OLD_SIX = (3.0, 2.0, 5.5, 1.0, 2.5, 6.0)


@pytest.mark.parametrize("inits,up_at_install,want,flag", [
    # (platform, begin, end, process CPU at end, came up); t1 103, first
    # program event 105, the process's CPU at t1 1.0
    ([("cpu", 103.5, 104.5, 1.25, True)], False, (1.5, 0.5, 0.25), False),
    ([("tpu", 103.0, 104.0, 1.5, True), ("cpu", 104.0, 104.25, 1.75, True)],
     False, (1.25, 0.75, 0.75), False),
    ([("tpu", 103.25, 103.5, 1.5, False), ("cpu", 103.5, 104.0, 2.0, True)],
     False, (1.0, 1.0, 1.0), False),                 # no chip: the CPU's
    ([("cpu", 105.5, 106.0, 1.5, True)], False, (2.0, 0.0, 0.5), False),
    ([("cpu", 102.0, 102.5, 0.5, True)], False, (0.0, 2.0, 0.0), True),
    ([], True, (0.0, 2.0, 0.0), True),               # jax came up first
    ([], False, (None, None, None), False),          # no backend yet
    ([("tpu", 103.5, 104.5, 1.25, False)], False, (None, None, None), False),
    ([], None, (None, None, None), None),            # another jax
], ids=["one-platform", "tpu-then-cpu", "tpu-failed", "traced-before-up",
        "up-during-import", "up-before-import", "not-up", "none-came-up",
        "names-missing"])
def test_reaching_the_device_in_two_on_a_replayed_event_list(
        inits, up_at_install, want, flag):
    """t_up is the end of the last factory that came up; the two seconds
    stay inside [0, startup_reach_device_s] and add up to it; the six old
    fields are what they were without any of it."""
    rec = _ledger(REPLAYED, inits=inits, up_at_install=up_at_install)
    assert tuple(rec[k] for k in SECONDS) == pytest.approx(OLD_SIX)
    assert rec["first_contact_s"] == 20.0 and rec["startup_programs"] == 3
    assert tuple(rec[k] for k in SPLIT) == want
    assert rec["backend_up_before_import"] is flag
    assert rec["ledger_version"] == 2 and rec["complete"] is True
    assert [r["platform"] for r in rec["backend_inits"]] \
        == [row[0] for row in inits]
    if want[0] is not None:
        assert want[0] + want[1] == rec["startup_reach_device_s"]
        assert min(want) >= 0


def _fake_bridge(monkeypatch, up=False, frozen=False, **missing):
    """jax's xla_bridge as `_watch_backends` sees it: two registrations
    with a `factory` each, the lock's question, nothing else."""
    import jax._src

    class Registration:
        def __init__(self, factory):
            object.__setattr__(self, "factory", factory)

        def __setattr__(self, key, value):
            if frozen:
                raise AttributeError("cannot assign to field 'factory'")
            object.__setattr__(self, key, value)

    def no_chip():
        raise RuntimeError("no chip here")
    fake = types.SimpleNamespace(
        backends_are_initialized=lambda: up,
        _backend_factories={"tpu": Registration(no_chip),
                            "cpu": Registration(lambda: "a client")})
    for name in missing:
        delattr(fake, name)
    monkeypatch.setattr(jax._src, "xla_bridge", fake)
    own = {k: r.factory for k, r in fake._backend_factories.items()} \
        if "_backend_factories" not in missing else {}
    return fake, own


class TestTheFactoryTimers:
    def test_a_timer_runs_once_and_the_first_job_takes_the_rest_off(
            self, monkeypatch):
        fake, own = _fake_bridge(monkeypatch)
        regs = fake._backend_factories
        tr = RecompileTracker()
        tr.install()
        assert tr.backend_up_at_install is False
        assert regs["cpu"].factory is not own["cpu"]
        assert regs["tpu"].factory is not own["tpu"]
        assert regs["cpu"].factory() == "a client"      # what jax calls
        assert regs["cpu"].factory is own["cpu"]        # and it is gone
        assert regs["tpu"].factory is not own["tpu"]    # never called
        ((platform_, begin, end, cpu, ok),) = tr.backend_inits
        assert (platform_, ok) == ("cpu", True) and begin <= end
        rec = tr.startup_record()
        assert rec["startup_backend_up_s"] is not None
        tr.install()                                    # once
        assert regs["tpu"].factory is not own["tpu"]
        tr.job_enter()
        tr.job_exit(True)
        assert regs["tpu"].factory is own["tpu"]
        assert tr._timed_factories == {}

    def test_a_factory_that_raises_is_no_backend(self, monkeypatch):
        fake, own = _fake_bridge(monkeypatch)
        tr = RecompileTracker()
        tr.install()
        with pytest.raises(RuntimeError, match="no chip here"):
            fake._backend_factories["tpu"].factory()
        assert fake._backend_factories["tpu"].factory is own["tpu"]
        assert [row[4] for row in tr.backend_inits] == [False]
        assert tr.startup_record()["startup_backend_up_s"] is None

    def test_a_backend_that_is_up_is_left_alone(self, monkeypatch):
        fake, own = _fake_bridge(monkeypatch, up=True)
        tr = RecompileTracker()
        tr.install()
        assert tr.backend_up_at_install is True
        assert {k: r.factory for k, r in
                fake._backend_factories.items()} == own
        rec = tr.startup_record()
        assert rec["backend_up_before_import"] is True
        assert rec["startup_backend_up_s"] == 0.0
        assert rec["startup_first_dispatch_s"] \
            == rec["startup_reach_device_s"]

    @pytest.mark.parametrize("kw", [
        {"_backend_factories": True}, {"backends_are_initialized": True},
        {"frozen": True}], ids=["no-table", "no-question", "frozen-field"])
    def test_another_jax_reads_none_and_nothing_raises(self, monkeypatch, kw):
        frozen = kw.pop("frozen", False)
        fake, own = _fake_bridge(monkeypatch, frozen=frozen, **kw)
        tr = RecompileTracker()
        tr.install()
        assert tr.backend_up_at_install is None
        assert tr._timed_factories == {} and tr.backend_inits == []
        if own:
            assert {k: r.factory for k, r in
                    fake._backend_factories.items()} == own
        rec = tr.startup_record()
        assert [rec[k] for k in SPLIT] == [None] * 3
        assert rec["backend_up_before_import"] is None
        assert rec["ledger_version"] == 2

    def test_this_process_came_up_before_or_was_timed(self):
        """The process's own tracker: jax up before the package's import
        (0, all to the first dispatch), or its CPU factory timed once."""
        import jax
        jax.devices()
        rec = platform.startup_record()
        assert tracing.tracker.backend_up_at_install is not None
        assert all(rec[k] is not None and rec[k] >= 0 for k in SPLIT)
        assert rec["startup_backend_up_s"] + rec["startup_first_dispatch_s"] \
            == pytest.approx(rec["startup_reach_device_s"], abs=1e-9)
        if tracing.tracker.backend_up_at_install:
            assert rec["backend_up_before_import"] is True
            assert rec["startup_backend_up_s"] == 0.0


FORCED = """
import json, threading, jax
import transmogrifai_tpu
from transmogrifai_tpu.utils import platform
from transmogrifai_tpu.utils.metrics import collector
from jax._src import xla_bridge
platform.force_cpu(4)
devices = [d.platform for d in jax.devices()]
for t in threading.enumerate():
    if t.name.startswith("tmog"):
        t.join(60)
with collector.trace_span("CV", kind="validate"):
    jax.jit(lambda x: x + 1.0)(jax.numpy.ones(3)).block_until_ready()
rec = platform.startup_record()
print(json.dumps({"devices": devices, "rec": rec,
    "threads": [t.name for t in threading.enumerate()],
    "timed": [k for k, r in xla_bridge._backend_factories.items()
              if "_timed_factory" in getattr(r.factory, "__qualname__", "")]}))
"""


def test_force_cpu_after_the_import_still_works(tmp_path):
    """Nothing pinned when the package is imported (a TPU host's way in:
    every factory timed, the kernel modules' thread started where libtpu
    is installed), then `force_cpu`: jax calls the CPU's factory alone,
    and the first job hands the TPU's back unused."""
    env = dict(os.environ, PYTHONPATH=REPO,
               TMOG_COMPILE_CACHE_DIR=str(tmp_path))
    for key in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS"):
        env.pop(key, None)
    r = subprocess.run([sys.executable, "-c", FORCED], env=env,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == ["cpu"] * 4
    rec = out["rec"]
    assert [row["platform"] for row in rec["backend_inits"]] == ["cpu"]
    assert rec["backend_up_before_import"] is False
    assert rec["startup_backend_up_s"] + rec["startup_first_dispatch_s"] \
        == pytest.approx(rec["startup_reach_device_s"], abs=1e-9)
    assert out["timed"] == []
    assert not [t for t in out["threads"] if t.startswith("tmog")]
    if rec["kernel_import_s"] is not None:      # libtpu is installed here
        assert 0 <= rec["kernel_import_cpu_s"] <= rec["kernel_import_s"] + 0.05


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
        rec = platform.startup_record()
        assert rec["kernel_import_s"] >= 0.0
        assert rec["kernel_import_cpu_s"] >= 0.0    # the thread's own CPU
    else:
        assert seen == []
        rec = platform.startup_record()
        assert rec["kernel_import_s"] is None
        assert rec["kernel_import_cpu_s"] is None
