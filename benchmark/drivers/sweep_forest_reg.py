"""Closed loop of one client over a REGRESSION random-forest
`CrossValidation.validate()`: the call a RegressionModelSelector makes for
OpRandomForestRegressor — two of that pool's three default families are
trees — feature matrix resident on the device -> every grid point's trees x
folds grown as lanes of the fused histogram passes from a REAL-VALUED
label, RMSE computed in the sweep, winner (the lower mean RMSE) on the host.

A sibling of drivers/sweep_forest.py and drivers/sweep.py, whose spies,
route check and loop it runs by import. What differs: the program is asked
BEFORE any data is made how this estimator's payload is carried into the
bfloat16 contraction (`models/trees.forest_payload_body`) and is refused if
it has no such word — a program without it rounds weight x label once to
bfloat16, at 2^-9 of a value that lies five deviations from zero; the data
comes from benchmark/datagen_forest_reg.py; the evaluator is RMSE and the
folds are not stratified; the word, the rows a (lane, slot) and the columns
a node are read from the warm-up job's own spans and telemetry; and the
answer is held to benchmark/reference_forest_reg.py.
"""
from __future__ import annotations

import importlib
import json
import operator

import numpy as np

from benchmark import datagen_forest_reg, harness, reference, \
    reference_forest_reg

sweep = harness.load_module("drivers", "sweep")
sweep_forest = harness.load_module("drivers", "sweep_forest")

METRIC = "rmse"


def _require_payload(ctx, cls, params, grids) -> None:
    from transmogrifai_tpu.models import trees as MT
    ask = getattr(MT, "forest_payload_body", None)
    want = ctx.cell["expect"]["forest_lanes"]["payload_body"]
    for g in grids:
        body = ask and ask(cls(**params).copy(**g))
        ctx.notes["payload_body_declared"] = body
        if body != want:
            raise harness.BenchFailure(
                f"models/trees.forest_payload_body({cls.__name__} {g}) "
                f"names {body!r}, not {want!r}: this program hands the "
                f"fused passes weight x label rounded ONCE to bfloat16; "
                f"nothing was made or measured")


class VoteSpy:
    """Around ForestLaneSpy's own hook of ops/trees.fit_forest_lanes: keep,
    a grid point at a time, the votes of ALL folds as the last lane group
    left them, and what the call said of its payload (`payload`, and
    `centre`: the label's centre and the payload's scale). Entered after
    ForestLaneSpy, left before it."""

    def __init__(self, lane_spy):
        self.lane_spy, self.votes, self.said = lane_spy, [], []

    def __enter__(self):
        from transmogrifai_tpu.ops import trees as T
        self._T, self._inner = T, T.fit_forest_lanes
        spy = self

        def fit(*args, **kw):
            out = spy._inner(*args, **kw)
            n = len(spy.lane_spy.points)
            del spy.votes[n - 1:], spy.said[n - 1:]
            spy.votes.append(out[0])
            spy.said.append({"payload": kw.get("payload"),
                             "centre": None if kw.get("centre") is None
                             else np.asarray(kw["centre"], np.float64)
                             .ravel().tolist()})
            return out
        T.fit_forest_lanes = fit
        return self

    def __exit__(self, *exc):
        self._T.fit_forest_lanes = self._inner


def _job(ctx, st):
    import jax.numpy as jnp
    from transmogrifai_tpu.automl.tuning.validators import CrossValidation
    from transmogrifai_tpu.evaluators.evaluators import Evaluators

    sz = ctx.sizes
    val = CrossValidation(getattr(Evaluators.Regression, METRIC)(),
                          num_folds=sz["folds"], seed=sz["cv_seed"],
                          sweep_dtype=jnp.dtype(sz["dtype"]), mesh=None)
    models = [(cls(**params), [dict(g) for g in grids])
              for _, cls, params, grids in st.pool]
    # validate() returns host floats reduced from every device result of
    # the sweep, so the wall ends after the last of them: no fence needed
    best = val.validate(models, st.X, st.y, problem_type="regression")
    st.last_best, st.last_val = best, val
    return (best.name, json.dumps(best.best_grid, sort_keys=True),
            tuple(np.asarray(v.fold_metrics, np.float64).tobytes()
                  for v in best.validated))


def setup(ctx):
    sz = ctx.sizes
    if ctx.rehearse:
        for target, value in ctx.cell["rehearsal"].get(
                "program_globals", {}).items():
            mod, _, name = target.partition(":")
            setattr(importlib.import_module(mod), name, value)
    pool = []
    for fam, spec in ctx.cell["families"].items():
        base = ctx.config["pool"][fam]
        cls, params, grids = harness.pool_entry(base, spec["grid"],
                                                ctx.rehearse)
        if len(grids) != ctx.config[base["grid_key"]]:
            raise harness.BenchFailure(
                f"{fam}: {len(grids)} grid points, the configuration's "
                f"{base['grid_key']} says {ctx.config[base['grid_key']]}")
        _require_payload(ctx, cls, params, grids)
        sweep_forest._require_route(cls, params, grids, sz)
        pool.append((fam, cls, params, grids))
    X, y = datagen_forest_reg.device_matrix(
        sz["rows"], sz["cols"], sz["dtype"], ctx.seed, **ctx.config["label"])
    harness.log(f"data {X.shape} {X.dtype}, real label, on the device")
    st = sweep.State(X, y, pool)
    c = _checks(ctx)["forest_answer"]

    def watched():
        with reference.DispatcherSpy() as spy, \
                sweep_forest.ForestLaneSpy(
                    c["fold"], c["replay_trees"],
                    min(c["bootstrap_prefix"], sz["rows"])) as lanes, \
                VoteSpy(lanes) as votes:
            answer = _job(ctx, st)
        st.spy_calls = spy.calls
        st.forest_points, st.votes = lanes.finished(), votes
        return answer
    answer, events, spans = harness.watched_warmup(ctx, watched)
    st.warm_answer = answer
    sweep._check_routes(ctx, st, events, spans)
    _check_program(ctx, st)
    best = st.last_best
    ctx.notes["winner"] = {"name": answer[0], "grid": json.loads(answer[1]),
                           METRIC: float(best.best_metric)}
    # predicting the label's mean alone errs by its deviation
    spread = float(np.asarray(y[:1 << 16]).std())
    ctx.require(np.isfinite(best.best_metric)
                and 0.0 < best.best_metric < spread,
                f"winner RMSE {best.best_metric}, the label's deviation "
                f"is {spread}")
    for v in best.validated:
        ctx.require(len(v.fold_metrics) == sz["folds"]
                    and bool(np.all(np.isfinite(v.fold_metrics))),
                    f"fold metrics of {v.model_name} {v.grid}")
    return st


def _check_program(ctx, st) -> None:
    """What the warm-up job ran, from its own record: the lanes it counted,
    and the predicate's word, the rows a (lane, slot) and the columns a
    node alike in the telemetry, on every forest_group span and in the
    calls themselves."""
    from transmogrifai_tpu.utils.metrics import collector
    expect = ctx.cell["expect"]["forest_lanes"]
    tele = dict(getattr(st.last_val, "last_tree_telemetry", None) or {})
    ctx.notes["forest_lanes"] = tele
    spans = {}
    for s in collector.trace.spans:
        spans.setdefault(f"{s.kind}:{s.name}", []).append(dict(s.attrs))
    groups = spans.get("tree_fused:forest_group", [])
    metric_spans = spans.get("validate_phase:fold_metrics", [])
    said = {(s["payload"], s["centre"] is not None) for s in st.votes.said}
    ctx.notes["program"] = {
        "forest_group_spans": groups[:2], "groups": len(groups),
        "fold_metrics_spans": metric_spans[:1], "calls_said": st.votes.said}
    n_points = sum(len(g) for *_, g in st.pool)
    ctx.require(len(st.forest_points) == n_points,
                f"{len(st.forest_points)} grid points ran as forest lanes, "
                f"not {n_points}")
    word = expect["payload_body"]
    ctx.require(said == {(word, True)},
                f"the lane groups were called with {sorted(map(str, said))},"
                f" not payload {word!r} and its centre")
    keys = ("payload_body", "payload_rows", "features_per_node")
    if ctx.rehearse:   # the toy matrix: other counts, the same words
        expect = {"payload_body": word,
                  **ctx.cell["rehearsal"]["forest_lanes"]}
    for key, want in expect.items():
        ctx.require(tele.get(key) == want,
                    f"the sweep counted {key} = {tele.get(key)!r}, "
                    f"not {want!r}")
    ctx.require(bool(groups) and all(
        g.get(k) == tele.get(k) for g in groups for k in keys),
        f"forest_group spans carry {[{k: g.get(k) for k in keys} for g in groups[:1]]},"
        f" the telemetry {[tele.get(k) for k in keys]}")
    ctx.require(bool(metric_spans) and all(
        m.get("metric") == METRIC
        and m.get("metric_body") == ctx.cell["expect"]["metric_body"]
        for m in metric_spans),
        f"fold_metrics spans: {metric_spans[:1]!r}")
    centre, scale = tele.get("label_centre"), tele.get("payload_scale")
    ctx.require(isinstance(centre, float) and abs(
        centre - ctx.config["label"]["mu"]) < 0.1,
        f"label_centre {centre!r}: the label's mean is "
        f"{ctx.config['label']['mu']}")
    ctx.require(isinstance(scale, float) and scale > 0
                and np.log2(scale) == round(np.log2(scale))
                and all(s["centre"] == [centre, scale]
                        for s in st.votes.said),
                f"payload_scale {scale!r} is no power of two, or the lane "
                f"groups were handed another pair than [{centre}, {scale}]: "
                f"{st.votes.said[:1]}")


def _checks(ctx) -> dict:
    return {k: dict(c, **(c.get("rehearsal", {}) if ctx.rehearse else {}))
            for k, c in ctx.cell.get("checks", {}).items()}


def run_window(ctx, st) -> harness.Result:
    with harness.profiler(ctx):
        done = harness.closed_loop(
            lambda: _job(ctx, st), ctx.seconds, "bench.validate",
            max_jobs=ctx.param("trace_jobs") if ctx.trace else None)
    tele = getattr(st.last_val, "last_tree_telemetry", None) or {}
    # the program's own counts, under the names the layer files read
    for key in ("tree_lanes", "lane_groups", "lanes_per_group",
                "bootstrap_draws", "payload_rows", "features_per_node"):
        if key in tele:
            ctx.counters["rfr_" + key] = tele[key]
    # what opcount_forest takes, flat, so that a layer file names them
    import jax.numpy as jnp
    ctx.counters.update(
        rows=ctx.sizes["rows"], cols=ctx.sizes["cols"],
        folds=ctx.sizes["folds"],
        x_itemsize=jnp.dtype(ctx.sizes["dtype"]).itemsize,
        grid_points=[g for *_, grids in st.pool for g in grids])
    if done:
        ctx.require(done[0][1] == st.warm_answer,
                    "the window's jobs answered unlike the warm-up job, "
                    "whose trees and votes were read")
    return harness.job_result(ctx, done, ctx.cell["metric"], operator.eq)


def verify(ctx, st) -> None:
    """The checks that need a reference, outside the window: the routing
    and lookup kernels as drivers/sweep.py replays them, the histogram
    kernels under a REAL payload, then the forest's own checks against
    benchmark/reference_forest_reg.py. Every reading lands in the notes
    before its bound is applied."""
    import jax.numpy as jnp
    checks = _checks(ctx)
    n = st.X.shape[0]
    masks = st.last_val.fold_masks(np.zeros(n))      # [folds, n], 1 = train
    c = checks["forest_answer"]
    fam, _, params, grids = next(p for p in st.pool if p[0] == c["family"])
    grid0 = dict(params, **grids[0])
    ans = ctx.notes["forest_answer"] = {}
    twins = ctx.notes["real_payload_twins"] = []
    k = checks["kernel_twins"]
    m = min(k["rows"], n)
    Xb_t = reference.binned_sample(st.X[:m], k["bins"], ctx.seed)

    def kernels():
        ctx.notes["kernel_twins"] = reference.kernel_checks(
            [call for call in st.spy_calls
             if call["kernel"] in ("route", "table_lookup")],
            Xb_t, st.y[:m], jnp.asarray(masks[:, :m]),
            st.X[:m, 0].astype(jnp.float32), interpret=ctx.rehearse,
            binned_tol=k["tol"])
        reference_forest_reg.real_payload_twins(
            st.spy_calls, Xb_t, st.y[:m], masks[:, :m], into=twins,
            seed=ctx.seed, interpret=ctx.rehearse, tol=k["tol"])

    def forest():
        reference.require(bool(st.forest_points),
                          "the program handed over no forest lanes: "
                          "nothing to hold to the reference")
        points = [dict(p, min_info_gain=float(dict(params, **g)[
            "min_info_gain"])) for p, g in zip(st.forest_points, grids)]
        reference_forest_reg.forest_reg_answer(
            st.last_best, points, st.votes.votes, masks, st.X, st.y,
            into=ans, fold=c["fold"], replay_trees=c["replay_trees"],
            depth=grid0["max_depth"], bins=grid0["max_bins"],
            trees=grid0["num_trees"], subsample=grid0["subsampling_rate"],
            features_per_node=c["features_per_node"],
            train_rows=c["train_rows"], tol_gain=c["tol_gain"],
            tol_leaf=c["tol_leaf"], tol_vote=c["tol_vote"],
            tol_metric=c["tol_metric"], tol_moment=c["tol_moment"],
            tol_corr=c["tol_corr"], tol_plain=c["tol_plain"],
            order_gap=c["order_gap"])
    for check in (kernels, forest):   # a failed one does not stop the next
        try:
            check()
        except reference.CheckFailure as e:
            ctx.require(False, f"reference check failed: {e}")
