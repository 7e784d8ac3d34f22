"""Hierarchical run tracing (utils/tracing + utils/metrics integration).

Covers the ISSUE-4 acceptance list: span-tree nesting and parent-id
integrity under exceptions, Perfetto/Chrome trace_event schema, the
recompile counter seeing exactly the bucket-ladder's compile count on CPU,
event-log validity + monotone timestamps, and backward compatibility of
AppMetrics.to_json() against a golden of the pre-tracing writer.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import transmogrifai_tpu.utils.tracing as T
from transmogrifai_tpu.utils.metrics import (
    AppMetrics, MetricsCollector, StageMetric, collector)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spans_by_name(c):
    return {s.name: s for s in c.trace.spans}


# -- span tree ---------------------------------------------------------------

class TestSpanTree:
    def test_nesting_and_parent_ids(self):
        c = MetricsCollector()
        c.enable("app")
        with c.trace_span("outer", kind="workflow"):
            with c.span("stageA", "u1", "fit", n_rows=4):
                pass
            with c.trace_span("inner", kind="layer"):
                with c.span("stageB", "u2", "transform"):
                    pass
        c.finish()
        by = spans_by_name(c)
        root = by["app"]
        assert root.parent_id is None and root.kind == "run"
        assert by["outer"].parent_id == root.span_id
        assert by["stageA"].parent_id == by["outer"].span_id
        assert by["inner"].parent_id == by["outer"].span_id
        assert by["stageB"].parent_id == by["inner"].span_id
        # every span closed, children inside parents
        for s in c.trace.spans:
            assert s.t_end is not None
            if s.parent_id is not None:
                parent = next(p for p in c.trace.spans
                              if p.span_id == s.parent_id)
                assert s.t_start >= parent.t_start - 1e-6
                assert s.t_end <= parent.t_end + 1e-6

    def test_parent_integrity_under_exception(self):
        """An exception unwinding through nested spans must close them,
        mark the failing one, and leave the stack consistent so later
        spans attach at the right depth."""
        c = MetricsCollector()
        c.enable("app")
        with pytest.raises(ValueError):
            with c.trace_span("outer", kind="workflow"):
                with c.span("bad_stage", "u", "fit"):
                    raise ValueError("boom")
        with c.trace_span("after", kind="workflow"):
            pass
        c.finish()
        by = spans_by_name(c)
        assert by["bad_stage"].error and \
            by["bad_stage"].error_type == "ValueError"
        assert by["outer"].error and by["outer"].error_type == "ValueError"
        # the new span parents to the ROOT, not to a leaked open span
        assert by["after"].parent_id == by["app"].span_id
        assert not by["after"].error
        # the StageMetric satellite: error propagated onto the flat record
        m = [m for m in c.current.stage_metrics
             if m.stage_name == "bad_stage"][0]
        assert m.error is True and m.error_type == "ValueError"

    def test_double_close_keeps_first_t_end(self):
        """save()'s close_all racing a still-open context manager: the
        second close must not rewrite t_end (which would inflate the span
        past its already-closed parent and break trace containment)."""
        import time as _time
        c = MetricsCollector()
        c.enable("app")
        with c.trace_span("outer", kind="workflow") as sp:
            c.finish()          # closes everything, including sp
            end1 = sp.t_end
            _time.sleep(0.02)   # the with-exit close happens later
        assert sp.t_end == end1
        root = spans_by_name(c)["app"]
        assert sp.t_end <= root.t_end

    def test_enable_is_reentrancy_safe(self):
        """A nested enable (runner.run inside an outer traced run) must
        join the outer tree, not reset it mid-run."""
        c = MetricsCollector()
        c.enable("outer_app")
        with c.trace_span("outer_work", kind="workflow"):
            c.enable("nested_app")  # e.g. runner.run collect_stage_metrics
            with c.span("nested_stage", "u", "fit"):
                pass
        c.finish()
        c.disable()
        by = spans_by_name(c)
        assert "outer_app" in by and "nested_app" not in by
        assert by["nested_stage"].parent_id == by["outer_work"].span_id
        # after finish(), enable() re-arms a FRESH run
        c.enable("second_app")
        assert c.current.app_name == "second_app"
        assert c.current.end_time == 0.0
        c.finish()
        c.disable()

    def test_span_records_error_but_still_measures(self):
        c = MetricsCollector()
        c.enable("app")
        with pytest.raises(RuntimeError):
            with c.span("s", "u", "fit"):
                raise RuntimeError("x")
        m = c.current.stage_metrics[0]
        assert m.error and m.error_type == "RuntimeError"
        assert m.wall_seconds >= 0.0


# -- finish()/save() idempotency (satellite) ---------------------------------

class TestFinishIdempotent:
    def test_second_finish_keeps_end_time(self, tmp_path):
        c = MetricsCollector()
        c.enable("app")
        with c.span("s", "u", "fit"):
            pass
        c.save(str(tmp_path / "m.json"))  # calls finish()
        end1 = c.current.end_time
        dur1 = c.current.duration_seconds
        import time
        time.sleep(0.02)
        app = c.finish()  # the runner's second call
        assert app.end_time == end1
        assert app.duration_seconds == dur1
        # enable() re-arms
        c.enable("app2")
        assert c.current.end_time == 0.0
        c.finish()
        assert c.current.end_time != 0.0


# -- Chrome trace export -----------------------------------------------------

class TestChromeExport:
    def _traced_collector(self):
        c = MetricsCollector()
        c.enable("app")
        with c.trace_span("outer", kind="workflow"):
            with c.span("stage", "u", "fit", n_rows=2):
                pass
            c.kernel("kern", 0.01, 1e6, cold=False)
        c.finish()
        return c

    def test_schema_fields(self, tmp_path):
        c = self._traced_collector()
        path = str(tmp_path / "train_trace.json")
        c.save_chrome_trace(path)
        doc = json.loads(open(path).read())
        events = doc["traceEvents"]
        xs = [e for e in events if e.get("ph") == "X"]
        assert len(xs) == 4  # app, outer, stage, kern
        for e in events:
            assert "ph" in e
        for e in xs:
            for k in ("ts", "dur", "pid", "tid", "name", "args"):
                assert k in e, (k, e)
            assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        ids = [e["args"]["span_id"] for e in xs]
        assert len(ids) == len(set(ids))
        # kernel span carries the roofline attrs into args
        kern = next(e for e in xs if e["name"] == "kern")
        assert kern["cat"] == "kernel"
        assert kern["args"]["bytes_hbm"] == 1e6

    def test_trace_report_check_passes(self, tmp_path):
        c = self._traced_collector()
        c.save_chrome_trace(str(tmp_path / "train_trace.json"))
        c.save(str(tmp_path / "train_stage_metrics.json"))
        text, ok = T.trace_report(str(tmp_path), check=True)
        assert ok, text
        text, ok = T.trace_report(str(tmp_path))
        assert ok
        assert "Top spans by self-time" in text
        assert "Kernel roofline" in text

    def test_report_self_time_isolated_per_trace_file(self, tmp_path):
        """Span ids restart per trace file; a multi-trace dir (the ci.sh
        smoke layout) must not subtract one file's children from another
        file's spans when computing self-time."""
        import time as _time
        c1 = MetricsCollector()
        c1.enable("appA")
        with c1.trace_span("childA", kind="stage"):
            _time.sleep(0.05)
        c1.finish()
        c1.save_chrome_trace(str(tmp_path / "a_trace.json"))
        c2 = MetricsCollector()
        c2.enable("appB")  # root with NO children: full self-time
        _time.sleep(0.03)
        c2.finish()
        c2.save_chrome_trace(str(tmp_path / "b_trace.json"))
        text, ok = T.trace_report(str(tmp_path))
        assert ok
        row = next(ln for ln in text.splitlines()
                   if ln.startswith("appB"))
        self_s = float(row.split()[3])
        # with colliding ids, appA's 0.05s child would clamp this to 0
        assert self_s >= 0.02, row

    def test_trace_report_check_catches_corruption(self, tmp_path):
        c = self._traced_collector()
        path = tmp_path / "train_trace.json"
        c.save_chrome_trace(str(path))
        doc = json.loads(path.read_text())
        for e in doc["traceEvents"]:
            e.pop("ph", None)
        path.write_text(json.dumps(doc))
        text, ok = T.trace_report(str(tmp_path), check=True)
        assert not ok
        assert "missing 'ph'" in text

    def test_trace_report_survives_non_numeric_ts(self, tmp_path):
        """The validator must FLAG malformed ts/dur, not crash on the
        containment arithmetic downstream of it."""
        c = self._traced_collector()
        path = tmp_path / "train_trace.json"
        c.save_chrome_trace(str(path))
        doc = json.loads(path.read_text())
        xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        xs[1]["ts"] = "oops"
        path.write_text(json.dumps(doc))
        text, ok = T.trace_report(str(tmp_path), check=True)
        assert not ok
        assert "non-numeric" in text
        text, ok = T.trace_report(str(tmp_path))  # report mode too
        assert not ok and "non-numeric" in text


# -- recompile attribution ---------------------------------------------------

class TestRecompileTracker:
    def test_exact_compile_count_per_shape(self):
        """A jitted function called on N fresh shapes inside a span books
        exactly N compiles there; re-calling the same shapes books none."""
        f = jax.jit(lambda x: (x * 2.0).sum())
        # pre-create inputs AND warm one shape outside any span: array
        # creation / first-touch helpers compile their own tiny programs
        xs = [jnp.zeros(n, jnp.float32) for n in (3, 4, 5)]
        jax.block_until_ready(f(xs[0]))
        def in_ledger():
            # the always-on ledger, by program name (before or after the
            # worker's first finished job)
            rec = T.tracker.startup_record()
            return sum(r["loads"] + r["compiles"]
                       for r in rec["programs"] + rec["later_programs"]
                       if r["fun_name"] == "<lambda>")
        booked0 = in_ledger()
        c = MetricsCollector()
        c.enable("app")
        with c.trace_span("warmshape", kind="stage"):
            jax.block_until_ready(f(xs[0]))
        with c.trace_span("freshshapes", kind="stage"):
            jax.block_until_ready(f(xs[1]))
            jax.block_until_ready(f(xs[2]))
        with c.trace_span("rerun", kind="stage"):
            jax.block_until_ready(f(xs[1]))
            jax.block_until_ready(f(xs[2]))
        c.finish()
        c.disable()
        by = spans_by_name(c)
        assert by["warmshape"].attrs.get("compiles", 0) == 0
        assert by["freshshapes"].attrs.get("compiles", 0) == 2
        assert by["rerun"].attrs.get("compiles", 0) == 0
        assert in_ledger() - booked0 == 2

    def test_bucket_ladder_bounded_recompiles(self):
        """Runtime verification of PR 3's claim: each power-of-two lane
        bucket compiles its round program ONCE; a sweep whose lane count
        maps to an already-compiled bucket recompiles nothing
        (tests/test_glm_convergence.py asserts the same via jit cache
        size — here it is visible in any traced run)."""
        from transmogrifai_tpu.ops.glm_sweep import sweep_glm_streamed_rounds

        rng = np.random.default_rng(0)
        X = rng.normal(size=(300, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        w = np.ones_like(y)
        masks = np.ones((2, len(y)), np.float32)
        masks[0, ::3] = 0.0
        masks[1, 1::3] = 0.0

        def run(n_grid, max_iter=2):
            regs = np.linspace(0.01, 0.5, n_grid).astype(np.float32)
            return sweep_glm_streamed_rounds(
                jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
                jnp.asarray(masks), regs, np.zeros(n_grid, np.float32),
                loss="logistic", max_iter=max_iter, tol=1e-12,
                standardize=False, round_iters=2, warm_start=False)

        # warm constant helpers (zeros/ones of d, scalar transfers) and
        # the 8-bucket program with an untraced run: 2 grids x 2 folds =
        # 4 lanes -> bucket 8
        run(2)
        c = collector
        c.enable("ladder")
        try:
            with c.trace_span("sweep32", kind="sweep_fit"):
                run(10)   # 20 lanes -> bucket 32: ONE fresh program
            with c.trace_span("sweep16", kind="sweep_fit"):
                run(5)    # 10 lanes -> bucket 16: ONE fresh program
            with c.trace_span("sweep16_reuse", kind="sweep_fit"):
                run(6)    # 12 lanes -> bucket 16 again: cache hit
            c.finish()
        finally:
            c.disable()
        by = spans_by_name(c)

        def booked(root_name):
            root = by[root_name]
            ids = {root.span_id}
            total = 0
            # sum over the subtree (compiles are booked on the innermost
            # glm_round spans the driver opens)
            changed = True
            while changed:
                changed = False
                for s in c.trace.spans:
                    if s.parent_id in ids and s.span_id not in ids:
                        ids.add(s.span_id)
                        changed = True
            for s in c.trace.spans:
                if s.span_id in ids:
                    total += int(s.attrs.get("compiles", 0))
            return total

        assert booked("sweep32") == 1, [
            (s.name, s.attrs.get("compiles")) for s in c.trace.spans]
        assert booked("sweep16") == 1
        assert booked("sweep16_reuse") == 0
        # the round spans carry the ladder geometry
        buckets = [s.attrs["bucket"] for s in c.trace.spans
                   if s.kind == "sweep_round"]
        assert set(buckets) <= {8, 16, 32}


# -- event log ---------------------------------------------------------------

class TestEventLog:
    def test_lines_valid_and_monotone(self, tmp_path):
        c = MetricsCollector()
        path = str(tmp_path / "events.jsonl")
        c.attach_event_log(path)
        c.enable("app")
        c.event("run_start", run_type="Train")
        with c.span("s1", "u1", "fit", n_rows=5):
            pass
        with c.span("s2", "u2", "transform"):
            pass
        c.event("sweep_cell_landed", model="M", grid_index=0,
                mean_metric=0.5)
        c.event("run_end", run_type="Train")
        c.finish()
        c.detach_event_log()
        c.disable()
        lines = [ln for ln in open(path).read().splitlines() if ln.strip()]
        assert len(lines) >= 7  # run_start + 2x(start,end) + cell + run_end
        recs = [json.loads(ln) for ln in lines]  # every line valid JSON
        ts = [r["t"] for r in recs]
        assert all(isinstance(t, float) for t in ts)
        assert ts == sorted(ts), "monotone timestamps"
        seqs = [r["seq"] for r in recs]
        assert seqs == list(range(len(recs))), "strictly increasing seq"
        events = [r["event"] for r in recs]
        assert events[0] == "run_start" and events[-1] == "run_end"
        assert "stage_start" in events and "stage_end" in events
        stage_end = next(r for r in recs if r["event"] == "stage_end")
        assert stage_end["wall_seconds"] >= 0.0

    def test_runner_keeps_caller_attached_log(self, tmp_path):
        """runner.run must not close a log it did not attach (the
        BENCH_TRACE_DIR pattern: one log spanning several runs)."""
        from transmogrifai_tpu import FeatureBuilder
        from transmogrifai_tpu.automl.transmogrifier import transmogrify
        from transmogrifai_tpu.readers.readers import ListReader
        from transmogrifai_tpu.workflow import (
            OpParams, OpWorkflowRunner, Workflow)
        rows = [{"x": float(i % 5)} for i in range(40)]
        fx = FeatureBuilder.Real("x").extract(
            lambda r: r.get("x")).as_predictor()
        wf = Workflow().set_result_features(transmogrify([fx]))
        runner = OpWorkflowRunner(wf, train_reader=ListReader(rows))
        path = str(tmp_path / "outer_events.jsonl")
        collector.attach_event_log(path)
        try:
            runner.run(OpWorkflowRunner.TRAIN, OpParams())
            assert collector.has_event_log  # still attached
            collector.event("after_run")    # still flows
        finally:
            collector.detach_event_log()
            collector.disable()
        events = [json.loads(ln)["event"]
                  for ln in open(path).read().splitlines()]
        assert "run_start" in events and "run_end" in events
        assert events[-1] == "after_run"

    def test_failed_attach_keeps_working_log(self, tmp_path):
        """attach_event_log(bad path) must raise with the previous log
        still attached and functional — not leave a closed log installed
        that silently swallows every later event."""
        c = MetricsCollector()
        good = str(tmp_path / "good.jsonl")
        c.attach_event_log(good)
        bad_dir = tmp_path / "blocked"
        bad_dir.write_text("a file, not a dir")
        with pytest.raises(OSError):
            c.attach_event_log(str(bad_dir / "sub" / "events.jsonl"))
        c.event("survived")
        c.detach_event_log()
        events = [json.loads(ln)["event"]
                  for ln in open(good).read().splitlines()]
        assert events == ["survived"]

    def test_events_flow_without_span_collection(self, tmp_path):
        """The log is the liveness channel: it works with enabled=False
        (collect_stage_metrics off) for runner/validator events."""
        c = MetricsCollector()
        path = str(tmp_path / "events.jsonl")
        c.attach_event_log(path)
        c.event("run_start", run_type="Score")
        with c.span("s", "u", "fit"):  # span no-ops while disabled
            pass
        c.event("run_end", run_type="Score")
        c.detach_event_log()
        recs = [json.loads(ln) for ln in open(path).read().splitlines()]
        assert [r["event"] for r in recs] == ["run_start", "run_end"]


# -- AppMetrics.to_json() backward compatibility -----------------------------

# golden captured from the PRE-TRACING writer (utils/metrics.py at PR 3):
# these exact keys and values must keep coming out of to_json()
GOLDEN = {
    "app_name": "golden",
    "duration_seconds": 2.0,
    "total_stage_seconds": 1.5,
    "stage_metrics": [
        {"stage_name": "s", "uid": "u", "phase": "fit",
         "wall_seconds": 1.5, "n_rows": 3, "n_stages_fused": 1},
    ],
}


class TestAppMetricsGolden:
    def test_to_json_backward_compatible(self):
        app = AppMetrics(app_name="golden", start_time=10.0, end_time=12.0,
                         stage_metrics=[StageMetric(
                             stage_name="s", uid="u", phase="fit",
                             wall_seconds=1.5, n_rows=3)])
        doc = app.to_json()
        for key, val in GOLDEN.items():
            assert key in doc
            if key != "stage_metrics":
                assert doc[key] == val
        for old, new in zip(GOLDEN["stage_metrics"], doc["stage_metrics"]):
            for k, v in old.items():
                assert new[k] == v, k
        # empty kernel/sweep lists stay OMITTED (old writer behavior)
        assert "kernel_metrics" not in doc
        assert "sweep_metrics" not in doc

    def test_save_adds_spans_key_only(self, tmp_path):
        c = MetricsCollector()
        c.enable("golden")
        with c.span("s", "u", "fit", n_rows=3):
            pass
        path = str(tmp_path / "m.json")
        c.save(path)
        c.disable()
        doc = json.loads(open(path).read())
        for key in GOLDEN:
            assert key in doc
        assert "spans" in doc  # the one addition
        sp = doc["spans"]
        assert sp[0]["parent_id"] is None
        assert any(s["kind"] == "stage" for s in sp)


# -- end to end through the runner + CLI -------------------------------------

class TestRunnerIntegration:
    def _run_train(self, tmp_path):
        from transmogrifai_tpu import FeatureBuilder
        from transmogrifai_tpu.automl.transmogrifier import transmogrify
        from transmogrifai_tpu.readers.readers import ListReader
        from transmogrifai_tpu.workflow import (
            OpParams, OpWorkflowRunner, Workflow)
        rows = [{"x": float(i % 7), "y": float(i % 3)} for i in range(80)]
        fx = FeatureBuilder.Real("x").extract(
            lambda r: r.get("x")).as_predictor()
        fy = FeatureBuilder.Real("y").extract(
            lambda r: r.get("y")).as_predictor()
        wf = Workflow().set_result_features(transmogrify([fx, fy]))
        runner = OpWorkflowRunner(wf, train_reader=ListReader(rows))
        params = OpParams(collect_stage_metrics=True,
                          metrics_location=str(tmp_path))
        runner.run(OpWorkflowRunner.TRAIN, params)
        collector.disable()

    def test_traced_run_writes_all_artifacts(self, tmp_path):
        self._run_train(tmp_path)
        assert (tmp_path / "train_stage_metrics.json").exists()
        assert (tmp_path / "train_trace.json").exists()
        assert (tmp_path / "events.jsonl").exists()
        doc = json.loads((tmp_path / "train_trace.json").read_text())
        names = [e["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "X"]
        # full hierarchy: run -> Train -> workflow -> layer -> stage
        assert "Train" in names
        assert any(n.endswith(".train") for n in names)
        assert any(n.startswith("layer_") for n in names)
        cats = {e.get("cat") for e in doc["traceEvents"]
                if e.get("ph") == "X"}
        assert {"run", "workflow", "layer", "stage"} <= cats
        recs = [json.loads(ln) for ln in
                (tmp_path / "events.jsonl").read_text().splitlines()]
        events = [r["event"] for r in recs]
        assert events[0] == "run_start" and events[-1] == "run_end"

    def test_joined_run_leaves_outer_collection_open(self, tmp_path):
        """runner.run with metrics_location inside an OUTER enable(): its
        artifact writes must snapshot, not finish — the outer span tree
        stays open and later outer spans still nest under the root."""
        from transmogrifai_tpu import FeatureBuilder
        from transmogrifai_tpu.automl.transmogrifier import transmogrify
        from transmogrifai_tpu.readers.readers import ListReader
        from transmogrifai_tpu.workflow import (
            OpParams, OpWorkflowRunner, Workflow)
        rows = [{"x": float(i % 5)} for i in range(30)]
        fx = FeatureBuilder.Real("x").extract(
            lambda r: r.get("x")).as_predictor()
        wf = Workflow().set_result_features(transmogrify([fx]))
        runner = OpWorkflowRunner(wf, train_reader=ListReader(rows))
        collector.enable("outer_bench")
        try:
            with collector.trace_span("outer_phase", kind="workflow"):
                runner.run(OpWorkflowRunner.TRAIN, OpParams(
                    collect_stage_metrics=True,
                    metrics_location=str(tmp_path)))
            assert collector.collecting  # NOT finished by the inner run
            with collector.trace_span("outer_after", kind="workflow"):
                pass
            collector.finish()
        finally:
            collector.disable()
        by = spans_by_name(collector)
        root = by["outer_bench"]
        assert by["outer_after"].parent_id == root.span_id
        assert by["outer_phase"].t_end <= root.t_end
        # the inner run's snapshot artifact still validates
        text, ok = T.trace_report(str(tmp_path), check=True)
        assert ok, text

    def test_sequential_runs_do_not_accumulate(self, tmp_path):
        """Two runner runs WITHOUT a metrics_location: the run that
        started a collection also ends it, so the second run gets a fresh
        tree instead of appending to the first's."""
        from transmogrifai_tpu import FeatureBuilder
        from transmogrifai_tpu.automl.transmogrifier import transmogrify
        from transmogrifai_tpu.readers.readers import ListReader
        from transmogrifai_tpu.workflow import (
            OpParams, OpWorkflowRunner, Workflow)
        rows = [{"x": float(i % 5)} for i in range(30)]
        fx = FeatureBuilder.Real("x").extract(
            lambda r: r.get("x")).as_predictor()
        wf = Workflow().set_result_features(transmogrify([fx]))
        runner = OpWorkflowRunner(wf, train_reader=ListReader(rows))
        runner.run(OpWorkflowRunner.TRAIN,
                   OpParams(collect_stage_metrics=True))
        n1 = len(collector.current.stage_metrics)
        t1 = collector.current.start_time
        runner.run(OpWorkflowRunner.TRAIN,
                   OpParams(collect_stage_metrics=True))
        assert len(collector.current.stage_metrics) == n1  # not n1 * 2
        assert collector.current.start_time > t1  # a FRESH run
        assert not collector.collecting  # ended by the run that began it
        collector.disable()

    def test_trace_report_cli(self, tmp_path):
        self._run_train(tmp_path)
        env = dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "transmogrifai_tpu", "trace-report",
             str(tmp_path), "--check"],
            cwd=REPO_ROOT, env=env, capture_output=True, text=True)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "OK" in proc.stdout
        proc = subprocess.run(
            [sys.executable, "-m", "transmogrifai_tpu", "trace-report",
             str(tmp_path)],
            cwd=REPO_ROOT, env=env, capture_output=True, text=True)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "Top spans by self-time" in proc.stdout
        # corrupt the event log -> --check goes red
        with open(tmp_path / "events.jsonl", "a") as f:
            f.write("{not json\n")
        proc = subprocess.run(
            [sys.executable, "-m", "transmogrifai_tpu", "trace-report",
             str(tmp_path), "--check"],
            cwd=REPO_ROOT, env=env, capture_output=True, text=True)
        assert proc.returncode == 1
        assert "invalid JSON" in proc.stdout

    def test_exit_codes_match_lint_table(self, tmp_path):
        """The project-wide exit-code table (docs/static_analysis.md):
        0 clean, 1 validation problems, 2 usage error — trace-report
        and the tmoglint CLI must agree so CI failures are attributable
        at a glance. An empty/non-run directory is a USAGE error (2),
        not a passing check and not a schema failure."""
        empty = tmp_path / "not_a_run_dir"
        empty.mkdir()
        text, rc = T.trace_report_rc(str(empty), check=True)
        assert rc == 2 and "nothing to read" in text
        env = dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "transmogrifai_tpu", "trace-report",
             str(empty), "--check"],
            cwd=REPO_ROOT, env=env, capture_output=True, text=True)
        assert proc.returncode == 2, proc.stdout + proc.stderr
        # a VALID run dir: rc 0; a corrupted one: rc 1
        good = tmp_path / "run"
        good.mkdir()
        c = MetricsCollector()
        c.enable("rc-test")
        with c.trace_span("s", kind="stage"):
            pass
        c.save_chrome_trace(str(good / "run_trace.json"))
        c.disable()
        _text, rc = T.trace_report_rc(str(good), check=True)
        assert rc == 0
        (good / "events.jsonl").write_text("{broken\n")
        _text, rc = T.trace_report_rc(str(good), check=True)
        assert rc == 1


# -- device memory watermark -------------------------------------------------

class TestMemoryWatermark:
    def test_none_safe_on_cpu(self):
        """CPU devices return memory_stats() == None; the sampler must
        yield {} (and never initialize a backend by itself)."""
        attrs = T.device_memory_attrs()
        assert isinstance(attrs, dict)
        for v in attrs.values():
            assert isinstance(v, int)

    def test_spans_close_fine_without_stats(self):
        c = MetricsCollector()
        c.enable("app")
        with c.trace_span("s", kind="stage"):
            pass
        c.finish()
        c.disable()
        sp = spans_by_name(c)["s"]
        assert sp.t_end is not None
