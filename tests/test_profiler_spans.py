"""The span primitive's second sink: `collector.trace_span` / `collector.span`
write `tmog.<kind>:<name>` host annotations into a jax.profiler trace,
collection on or off, and `validate()` is spanned phase by phase on every
route (docs/observability.md "Spans on the profiler's clock").

Each test records a real profiler session on the CPU and reads the
`.xplane.pb` back with jax.profiler.ProfileData, as the benchmark does."""
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from transmogrifai_tpu.automl import CrossValidation
from transmogrifai_tpu.automl.tuning import validators as V
from transmogrifai_tpu.evaluators.evaluators import Evaluators
from transmogrifai_tpu.models.glm import OpLogisticRegression, OpNaiveBayes
from transmogrifai_tpu.models.trees import (
    OpXGBoostClassifier, _TreeEstimator)
from transmogrifai_tpu.stages.params import param_grid
from transmogrifai_tpu.utils.metrics import MetricsCollector, collector


@pytest.fixture(autouse=True)
def collection_off():
    """test_serving, test_monitor_serving and test_ingest leave the
    process-wide collector enabled; whichever file shares their worker
    must not inherit that."""
    collector.disable()


def profiled(tmp_path, body):
    """Run body() inside a profiler session (Python tracer off, as the
    benchmark's); returns (body's value, the `tmog.` host events as dicts
    with name / start / end / stats / line, in start order)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        value = body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("tmog."):
                    events.append({"name": e.name, "start": e.start_ns,
                                   "end": e.end_ns, "line": i,
                                   "stats": dict(e.stats)})
    return value, sorted(events, key=lambda e: (e["start"], -e["end"]))


def named(events, name):
    return [e for e in events if e["name"] == name]


def inside(inner, outer):
    return (outer["start"] <= inner["start"] and inner["end"] <= outer["end"]
            and inner["line"] == outer["line"])


# -- the primitive ------------------------------------------------------------

class TestAnnotationSink:
    def test_collector_off_leaves_a_host_event_with_its_attrs(self, tmp_path):
        c = MetricsCollector()

        def body():
            with c.trace_span("fit[32]", kind="sweep_round", bucket=32,
                              share=0.5, warm=True, label="a,b#c=d",
                              arr=np.ones(2)) as sp:
                return sp
        sp, events = profiled(tmp_path, body)
        assert sp is None and not c.enabled and c.trace.spans == []
        ev, = named(events, "tmog.sweep_round:fit[32]")
        # scalars ride as metadata; the characters TraceMe's packing
        # reserves are replaced; a non-scalar attr is left out
        assert ev["stats"]["bucket"] == 32
        assert float(ev["stats"]["share"]) == 0.5
        assert ev["stats"]["label"] == "a_b_c_d"
        assert "warm" in ev["stats"] and "arr" not in ev["stats"]
        assert ev["end"] > ev["start"]

    def test_spans_nest_and_an_exception_still_records(self, tmp_path):
        c = MetricsCollector()

        def body():
            with c.trace_span("outer", kind="k"):
                with c.trace_span("inner", kind="k"):
                    pass
                with pytest.raises(KeyError):
                    with c.trace_span("boom", kind="k"):
                        raise KeyError("x")
        _, events = profiled(tmp_path, body)
        outer, = named(events, "tmog.k:outer")
        inner, = named(events, "tmog.k:inner")
        boom, = named(events, "tmog.k:boom")
        assert inside(inner, outer) and inside(boom, outer)
        assert inner["end"] <= boom["start"]

    def test_collector_on_feeds_both_sinks(self, tmp_path):
        c = MetricsCollector()

        def body():
            c.enable("both")
            try:
                with c.trace_span("work", kind="layer", rows=7) as sp:
                    with c.span("stageA", "uid_1", "fit", n_rows=7):
                        pass
                return sp
            finally:
                c.finish()
                c.disable()
        sp, events = profiled(tmp_path, body)
        tree = {s.name: s for s in c.trace.spans}
        assert sp is tree["work"] and tree["work"].attrs["rows"] == 7
        assert tree["stageA"].parent_id == tree["work"].span_id
        assert [m.stage_name for m in c.current.stage_metrics] == ["stageA"]
        work, = named(events, "tmog.layer:work")
        stage, = named(events, "tmog.stage:stageA")
        assert inside(stage, work) and work["stats"]["rows"] == 7
        assert stage["stats"]["uid"] == "uid_1"
        assert stage["stats"]["phase"] == "fit"
        assert stage["stats"]["n_rows"] == 7

    def test_with_neither_sink_nothing_is_recorded(self):
        c = MetricsCollector()
        with c.trace_span("quiet", kind="k", rows=1) as sp:
            with c.span("stage", "u", "fit"):
                pass
        assert sp is None
        assert c.trace.spans == [] and c.current.stage_metrics == []

    def test_tree_fit_is_spanned_but_not_fenced_with_collection_off(
            self, tmp_path):
        """_timed_fused_fit used to return before its span when the
        collector was off: the measured window had no tree_fused span."""
        assert not collector.enabled
        Xb = jnp.zeros((8, 2), jnp.uint8)
        before = len(collector.current.kernel_metrics)
        out, events = profiled(
            tmp_path, lambda: _TreeEstimator._timed_fused_fit(
                "tree_sweep_fold_fused", Xb, 6, 3, 2, lambda: "ran"))
        assert out == "ran"     # not a jax value: no fence touched it
        ev, = named(events, "tmog.tree_fused:tree_levels")
        assert ev["stats"]["lanes"] == 6 and ev["stats"]["depth"] == 3
        assert ev["stats"]["slot_passes"] == 1 + 2
        assert len(collector.current.kernel_metrics) == before


# -- validate(), route by route -----------------------------------------------

TOP_KINDS = ("tmog.validate_phase:", "tmog.sweep_fit:", "tmog.sweep_eval:")
SPAN_CAP = 200     # rounds x 3 + folds x chunks + a dozen, at test size


def _data(n=400, d=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    beta = np.linspace(1.0, -1.0, d).astype(np.float32)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ beta)))) \
        .astype(np.float32)
    return X, y


def _route_case(route, monkeypatch):
    """(estimator, grids, expected count of every top-level span name)."""
    common = {"fold_assign": 1, "bookkeeping": 1, "winner": 1}
    lr_grid = param_grid(reg_param=[0.01, 0.1], elastic_net_param=[0.0])
    if route == "streamed":
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
        return OpLogisticRegression(max_iter=10), lr_grid, dict(
            common, device_place=1, record=1,
            **{"sweep_fit:glm_streamed:OpLogisticRegression": 1,
               "sweep_eval:glm_streamed_eval:OpLogisticRegression": 1})
    if route == "streamed_multiclass":
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
        return OpLogisticRegression(max_iter=10), lr_grid, dict(
            common, label_classes=1, device_place=1, record=1,
            **{"sweep_fit:glm_streamed:OpLogisticRegression": 1,
               "sweep_eval:glm_streamed_eval:OpLogisticRegression": 1})
    if route == "vmapped":
        return OpLogisticRegression(max_iter=10), lr_grid, dict(
            common, device_place=1, record=1,
            **{"sweep_fit:glm_vmapped:OpLogisticRegression": 1})
    if route == "mask_folds":
        # the binned rank metric at any size: fold_metrics' lane route
        monkeypatch.setattr(V, "BINNED_RANK_METRIC_MIN_ROWS", 0)
        grids = param_grid(eta=[0.1, 0.3])
        return OpXGBoostClassifier(num_round=3, max_depth=2, max_bins=8), \
            grids, dict(common, device_place=1, tree_bin=1, tree_fit=2,
                        fold_metrics=2, record=2)
    grids = param_grid(smoothing=[0.5, 1.0])
    return OpNaiveBayes(), grids, dict(
        common, record=2, **{"sweep_fit:sequential:OpNaiveBayes": 2})


@pytest.mark.parametrize("route", ["streamed", "streamed_multiclass",
                                   "vmapped", "mask_folds", "sequential"])
def test_validate_is_spanned_phase_by_phase(route, tmp_path, monkeypatch):
    est, grids, expect = _route_case(route, monkeypatch)
    X, y = _data()
    if route == "sequential":
        X = np.abs(X)       # naive Bayes wants non-negative features
    evaluator, problem = Evaluators.BinaryClassification.au_roc(), "binary"
    if route == "streamed_multiclass":
        y = y + (X[:, 0] > 0.5)         # three classes
        evaluator = Evaluators.MultiClassification.error()
        problem = "multiclass"
    cv = CrossValidation(evaluator, num_folds=3, seed=5)
    best, events = profiled(
        tmp_path, lambda: cv.validate([(est, grids)], X, y,
                                      problem_type=problem))
    assert {v.route.split(":")[0] for v in best.validated} \
        == {"streamed" if route.startswith("streamed") else route}

    root, = [e for e in events if e["name"].startswith("tmog.validate:")]
    assert root["name"] == "tmog.validate:CrossValidation"
    assert root["stats"]["rows"] == 400 and root["stats"]["folds"] == 3
    assert root["stats"]["models"] == 1
    assert root["stats"]["grid_points"] == len(grids)
    rest = [e for e in events if e is not root]
    assert all(inside(e, root) for e in rest)
    assert len(events) <= SPAN_CAP

    # every top-level phase the stated number of times, and no other
    top = [e for e in rest if e["name"].startswith(TOP_KINDS)]
    counts = {}
    for e in top:
        key = e["name"][len("tmog."):].replace("validate_phase:", "")
        counts[key] = counts.get(key, 0) + 1
    assert counts == expect
    # ... disjoint, in time order
    for a, b in zip(top, top[1:]):
        assert a["end"] <= b["start"], (a["name"], b["name"])
    # every other span sits inside exactly one of them
    for e in rest:
        if e not in top:
            assert sum(inside(e, t) for t in top) == 1, e["name"]

    if route in ("streamed", "streamed_multiclass"):
        classes = 3 if route == "streamed_multiclass" else 2
        fit, = named(events,
                     "tmog.sweep_fit:glm_streamed:OpLogisticRegression")
        assert fit["stats"]["classes"] == classes
        rounds = [e for e in events if e["name"].startswith(
            "tmog.sweep_round:" + ("mlr" if classes == 3 else "glm")
            + "_round[")]
        assert rounds and all(inside(r, fit) for r in rounds)
        for r in rounds:    # one prep and one fetch a round
            assert r["stats"]["bucket"] >= r["stats"]["active"] >= 1
            for step in ("round_prep", "round_fetch"):
                assert sum(inside(e, r) for e in
                           named(events, f"tmog.host_step:{step}")) == 1
        gram = named(events, "tmog.host_step:gram_factor")
        if classes == 3:
            # 10 iterations in rounds of 5, no lane retired; the Gram and
            # factor step once a sweep, before the first round
            assert len(rounds) == 2
            assert all(r["stats"]["classes"] == 3
                       and r["stats"]["iters_budget"] == 5 for r in rounds)
            assert len(gram) == 1 and inside(gram[0], fit)
            assert gram[0]["end"] <= rounds[0]["start"]
            assert gram[0]["stats"]["folds"] == 3
            lc, = named(events, "tmog.validate_phase:label_classes")
            fa, = named(events, "tmog.validate_phase:fold_assign")
            assert lc["end"] <= fa["start"]
        else:
            assert not gram
        ev, = named(events,
                    "tmog.sweep_eval:glm_streamed_eval:OpLogisticRegression")
        assert ev["stats"]["classes"] == classes
        # 400 rows: the exact (sorted) metric, and the multiclass counts,
        # keep the fold-by-fold loop and its fetch a fold and grid chunk
        assert ev["stats"]["eval_route"] == "per_fold"
        assert ev["stats"]["passes"] == 3
        fetches = named(events, "tmog.host_step:metric_fetch")
        assert len(fetches) == 3    # folds x one chunk of two grid points
        assert all(inside(f, ev) for f in fetches)
        place, = named(events, "tmog.validate_phase:device_place")
        # X and y came from the host; the default weights and the masks
        # were made on the device (folds.assign_fold_masks)
        assert place["stats"]["h2d_bytes"] == X.nbytes + y.nbytes
        fa, = named(events, "tmog.validate_phase:fold_assign")
        assert fa["stats"]["route"] == "device"
        assert fa["stats"]["rows"] == 400 and fa["stats"]["folds"] == 3
        assert "stratify" in fa["stats"]
    if route == "mask_folds":
        for e in named(events, "tmog.validate_phase:tree_fit"):
            assert e["stats"]["lanes"] == 3 and e["stats"]["depth"] == 2
        tb, = named(events, "tmog.validate_phase:tree_bin")
        assert tb["stats"]["bins"] == 8 and tb["stats"]["configs"] == 2
        # the lane-batched binned counts say which histogram body ran (off
        # the TPU the scatter twins) and the parts of their payload: no
        # sample weights, the validator's own masks
        for e in named(events, "tmog.validate_phase:fold_metrics"):
            assert e["stats"]["lanes"] == 3 and e["stats"]["depth"] == 2
            assert e["stats"]["hist_body"] == "scatter"
            assert e["stats"]["payload_parts"] == 1


@pytest.mark.parametrize("case,n_grid,route,passes", [
    ("device_masks", 2, "heldout_once", 1),
    ("device_masks", 11, "heldout_once", 2),    # two chunks, ragged tail
    ("external_partition", 2, "heldout_once", 1),
    ("external_overlap", 2, "per_fold", 3),
])
def test_binned_sweep_eval_says_its_route_and_fetches_once(
        case, n_grid, route, passes, tmp_path, monkeypatch):
    """A binned rank metric over folds whose held-out sets are disjoint
    runs ONE metric program a grid chunk and ONE fetch a sweep, and the
    sweep_eval span and last_streamed_telemetry say so; a row held out by
    two folds keeps the loop and its fetch a fold."""
    monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
    monkeypatch.setattr(V, "BINNED_RANK_METRIC_MIN_ROWS", 0)
    X, y = _data()
    cv = CrossValidation(Evaluators.BinaryClassification.au_pr(),
                         num_folds=3, seed=5)
    masks = None
    if case != "device_masks":
        masks = np.array(cv.fold_masks(y))      # writable
        if case == "external_overlap":
            masks[1, np.flatnonzero(masks[0] == 0)[0]] = 0.0
    grids = param_grid(reg_param=list(np.geomspace(1e-3, 1.0, n_grid)))
    best, events = profiled(
        tmp_path, lambda: cv.validate(
            [(OpLogisticRegression(max_iter=5), grids)], X, y, masks=masks))
    assert {v.route for v in best.validated} == {"streamed"}
    ev, = named(events,
                "tmog.sweep_eval:glm_streamed_eval:OpLogisticRegression")
    assert ev["stats"]["eval_route"] == route
    assert ev["stats"]["passes"] == passes
    assert ev["stats"]["cells"] == n_grid
    tele = cv.last_streamed_telemetry
    assert (tele["eval_route"], tele["passes"]) == (route, passes)
    # the histogram body (off the TPU the scatter twins) and the parts its
    # payload goes in: one only where validate() made the masks itself
    parts = 1 if case == "device_masks" else 3
    for said in (ev["stats"], tele):
        assert said["hist_body"] == "scatter"
        assert said["payload_parts"] == parts
    fetches = named(events, "tmog.host_step:metric_fetch")
    assert len(fetches) == (1 if route == "heldout_once" else passes)
    assert all(inside(f, ev) for f in fetches)
    fa, = named(events, "tmog.validate_phase:fold_assign")
    assert fa["stats"]["route"] == ("device" if masks is None
                                    else "external")
    assert all(len(v.fold_metrics) == 3 and np.all(np.isfinite(
        v.fold_metrics)) for v in best.validated)
