"""Closed loop of one client over a MULTICLASS random-forest
`CrossValidation.validate()`: the call a MultiClassificationModelSelector
makes for OpRandomForestClassifier — half of that pool's default families —
feature matrix resident on the device -> every grid point's trees x folds
grown as lanes of the fused histogram passes under K class channels a
(lane, slot), the error computed in the sweep, winner (the lower mean
error) on the host.

A sibling of drivers/sweep_forest.py and drivers/sweep.py, whose lane
spy, route check and loop it runs by import.
What differs: the program is asked BEFORE any data is made whether a
multiclass forest at this shape AND class count takes the lane route
(`models/trees.forest_lane_route_ok` with the class count) and under which
payload word (`models/trees.payload_body` of a multiclass sweep), and is
refused if it does not — a program without the route grows every tree of
every fold one after another under a [rows, K] payload; the data comes from
benchmark/datagen_softmax.py at the configuration's class count; the
evaluator is the multiclass error and the folds are the validator's default
fold program on a class label; the word, the rows a (lane, slot), the
classes and the columns a node are read from the warm-up job's own spans
and telemetry; and the answer is held to benchmark/reference_forest_mc.py.
"""
from __future__ import annotations

import importlib
import json
import operator

import numpy as np

from benchmark import datagen_softmax, harness, reference, \
    reference_forest_mc

sweep = harness.load_module("drivers", "sweep")
sweep_forest = harness.load_module("drivers", "sweep_forest")

METRIC = "error"


def _require_route(ctx, cls, params, grids, sz) -> None:
    """The two questions, before any data: the lane route at this shape and
    class count, and the payload word of a multiclass sweep."""
    import inspect
    from transmogrifai_tpu.models import trees as MT
    K = ctx.sizes["classes"]
    want = ctx.cell["expect"]["forest_lanes"]["payload_body"]
    ok = getattr(MT, "forest_lane_route_ok", None)
    ask = getattr(MT, "payload_body", None)
    knows_k = ok is not None \
        and "n_classes" in inspect.signature(ok).parameters
    for g in grids:
        est = cls(**params).copy(**g)
        route = knows_k and ok(est, sz["rows"], sz["cols"], sz["folds"],
                               multiclass=True, n_classes=K)
        body = route and ask and ask(est, multiclass=True, n_classes=K)
        ctx.notes["payload_body_declared"] = body
        if not route or body != want:
            raise harness.BenchFailure(
                f"{cls.__name__} {g}: models/trees.forest_lane_route_ok("
                f"{sz['rows']}, {sz['cols']}, {sz['folds']}, multiclass, "
                f"n_classes={K}) is {bool(route)} and the payload word "
                f"{body!r}, not {want!r}: this program grows a multiclass "
                f"forest's {sz['folds']} folds x every tree one after "
                f"another under a [rows, {K}] payload; nothing was made or "
                f"measured")


class ClassLaneSpy(sweep_forest.ForestLaneSpy):
    """ForestLaneSpy whose joined trees keep every leaf's K values: leaf
    [trees, folds, leaves, K]."""

    def finished(self) -> list:
        out = []
        for pt in self.points:
            if not pt["trees"]:
                continue
            trees = {k: np.concatenate([g[k] for g in pt["trees"]])
                     for k in ("feat", "thresh", "miss", "leaf")}
            out.append(dict(
                pt, trees=trees, subsets=np.concatenate(pt["subsets"]),
                boot_head=np.stack(pt["boot_head"]),
                boot_prefix=np.stack(pt["boot_prefix"])))
        return out


class ClassVoteSpy:
    """drivers/sweep_forest_reg.VoteSpy for votes [folds, K, rows]: keep, a
    grid point at a time, the votes of ALL folds as the last lane group
    left them and the payload word each call was given — a finished
    point's votes on the HOST (1.4 GB a point at the cell's size would
    otherwise stay on the device through the next point's fit and count
    into the run's peak memory). Entered after the lane spy, left before
    it."""

    def __init__(self, lane_spy):
        self.lane_spy, self.votes, self.said = lane_spy, [], []

    def __enter__(self):
        from transmogrifai_tpu.ops import trees as T
        self._T, self._inner = T, T.fit_forest_lanes
        spy = self

        def fit(*args, **kw):
            n = len(spy.lane_spy.points)
            if len(spy.votes) == n - 1 and spy.votes:   # a new point begins
                spy.votes[-1] = np.asarray(spy.votes[-1])
            out = spy._inner(*args, **kw)
            del spy.votes[n - 1:], spy.said[n - 1:]
            spy.votes.append(out[0])
            spy.said.append({"payload": kw.get("payload"),
                             "classes": kw.get("classes")})
            return out
        T.fit_forest_lanes = fit
        return self

    def __exit__(self, *exc):
        self._T.fit_forest_lanes = self._inner
        self.votes = [np.asarray(v) for v in self.votes]


def _job(ctx, st):
    import jax.numpy as jnp
    from transmogrifai_tpu.automl.tuning.validators import CrossValidation
    from transmogrifai_tpu.evaluators.evaluators import Evaluators

    sz = ctx.sizes
    val = CrossValidation(getattr(Evaluators.MultiClassification, METRIC)(),
                          num_folds=sz["folds"], seed=sz["cv_seed"],
                          sweep_dtype=jnp.dtype(sz["dtype"]), mesh=None)
    models = [(cls(**params), [dict(g) for g in grids])
              for _, cls, params, grids in st.pool]
    # validate() returns host floats reduced from every device result of
    # the sweep, so the wall ends after the last of them: no fence needed
    best = val.validate(models, st.X, st.y, problem_type="multiclass")
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
        _require_route(ctx, cls, params, grids, sz)
        pool.append((fam, cls, params, grids))
    X, y = datagen_softmax.device_matrix(
        sz["rows"], sz["cols"], sz["classes"], sz["dtype"], ctx.seed,
        ctx.config["truth_scale"])
    harness.log(f"data {X.shape} {X.dtype}, {sz['classes']} classes, on "
                f"the device")
    st = sweep.State(X, y, pool)
    c = sweep_forest._checks(ctx)["forest_answer"]

    def watched():
        with reference.DispatcherSpy() as spy, \
                ClassLaneSpy(c["fold"], c["replay_trees"],
                             min(c["bootstrap_prefix"], sz["rows"])) \
                as lanes, ClassVoteSpy(lanes) as votes:
            answer = _job(ctx, st)
        st.spy_calls = spy.calls
        st.forest_points, st.votes = lanes.finished(), votes
        return answer
    answer, events, spans = harness.watched_warmup(ctx, watched)
    st.warm_answer = answer
    sweep._check_routes(ctx, st, events, spans)
    ctx.require(not any(e.get("event") == "forest_lane_route_declined"
                        for e in events), "forest_lane_route_declined fired")
    _check_program(ctx, st)
    best = st.last_best
    ctx.notes["winner"] = {"name": answer[0], "grid": json.loads(answer[1]),
                           METRIC: float(best.best_metric)}
    # predicting the largest class alone errs by 1 - its prior
    prior = float(np.bincount(np.asarray(y[:1 << 16]).astype(np.int64))
                  .max()) / min(1 << 16, sz["rows"])
    ctx.notes["largest_prior"] = prior
    ctx.require(np.isfinite(best.best_metric)
                and 0.0 < best.best_metric < 1.0 - prior,
                f"winner error {best.best_metric}, the largest class alone "
                f"errs by {1.0 - prior}")
    for v in best.validated:
        ctx.require(len(v.fold_metrics) == sz["folds"]
                    and bool(np.all(np.isfinite(v.fold_metrics))),
                    f"fold metrics of {v.model_name} {v.grid}")
    return st


def _check_program(ctx, st) -> None:
    """(a) What the warm-up job ran, from its own record: the lanes it
    counted, and the word, the rows a (lane, slot), the classes and the
    columns a node alike in the telemetry, on every forest_group span and
    in the calls themselves."""
    from transmogrifai_tpu.utils.metrics import collector
    K = ctx.sizes["classes"]
    expect = dict(ctx.cell["expect"]["forest_lanes"])
    tele = dict(getattr(st.last_val, "last_tree_telemetry", None) or {})
    ctx.notes["forest_lanes"] = tele
    spans = {}
    for s in collector.trace.spans:
        spans.setdefault(f"{s.kind}:{s.name}", []).append(dict(s.attrs))
    groups = spans.get("tree_fused:forest_group", [])
    metric_spans = spans.get("validate_phase:fold_metrics", [])
    said = {(s["payload"], s["classes"]) for s in st.votes.said}
    hist_calls = [c for c in st.spy_calls
                  if c["kernel"] in ("hist_folds", "route_hist")]
    ctx.notes["program"] = {
        "forest_group_spans": groups[:2], "groups": len(groups),
        "fold_metrics_spans": metric_spans[:1], "calls_said": st.votes.said,
        "hist_calls_classes": sorted({c["static"].get("classes")
                                      for c in hist_calls}, key=str)}
    n_points = sum(len(g) for *_, g in st.pool)
    ctx.require(len(st.forest_points) == n_points,
                f"{len(st.forest_points)} grid points ran as forest lanes, "
                f"not {n_points}")
    word = expect["payload_body"]
    ctx.require(said == {(word, K)},
                f"the lane groups were called with payload and classes "
                f"{sorted(map(str, said))}, not {(word, K)}")
    ctx.require(bool(hist_calls) and all(
        c["static"].get("classes") == K for c in hist_calls),
        f"the histogram dispatchers were called with classes "
        f"{ctx.notes['program']['hist_calls_classes']}, not {K}")
    keys = ("payload_body", "payload_rows", "classes", "features_per_node")
    if ctx.rehearse:   # the toy matrix: other counts, the same words
        expect = {"payload_body": word, "classes": K, "payload_rows": K + 1,
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
        m.get("metric") == METRIC and m.get("classes") == K
        and m.get("metric_body") == ctx.cell["expect"]["metric_body"]
        for m in metric_spans),
        f"fold_metrics spans: {metric_spans[:1]!r}")


def run_window(ctx, st) -> harness.Result:
    with harness.profiler(ctx):
        done = harness.closed_loop(
            lambda: _job(ctx, st), ctx.seconds, "bench.validate",
            max_jobs=ctx.param("trace_jobs") if ctx.trace else None)
    tele = getattr(st.last_val, "last_tree_telemetry", None) or {}
    # the program's own counts, under the names the layer files read
    for key in ("tree_lanes", "lane_groups", "lanes_per_group",
                "bootstrap_draws", "payload_rows", "features_per_node",
                "classes"):
        if key in tele:
            ctx.counters["rfm_" + key] = tele[key]
    # what opcount_forest_mc takes, flat, so that a layer file names them
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
    """The checks that need a reference, outside the window: (f) the
    routing and lookup kernels as drivers/sweep.py replays them and the
    histogram kernels under class channels, then (b)-(e), (g) against
    benchmark/reference_forest_mc.py. Every reading lands in the notes
    before its bound is applied."""
    import jax.numpy as jnp
    checks = sweep_forest._checks(ctx)
    K = ctx.sizes["classes"]
    n = st.X.shape[0]
    masks = st.last_val.fold_masks(np.zeros(n))      # [folds, n], 1 = train
    c = checks["forest_answer"]
    fam, _, params, grids = next(p for p in st.pool if p[0] == c["family"])
    grid0 = dict(params, **grids[0])
    ans = ctx.notes["forest_answer"] = {}
    twins = ctx.notes["class_channel_twins"] = []
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
        reference_forest_mc.class_kernel_twins(
            st.spy_calls, Xb_t, st.y[:m], masks[:, :m], into=twins,
            classes=K, seed=ctx.seed, interpret=ctx.rehearse)

    def forest():
        reference.require(bool(st.forest_points),
                          "the program handed over no forest lanes: "
                          "nothing to hold to the reference")
        points = [dict(p, min_info_gain=float(dict(params, **g)[
            "min_info_gain"])) for p, g in zip(st.forest_points, grids)]
        reference_forest_mc.forest_mc_answer(
            st.last_best, points, st.votes.votes, masks, st.X, st.y,
            into=ans, classes=K, fold=c["fold"],
            replay_trees=c["replay_trees"], depth=grid0["max_depth"],
            bins=grid0["max_bins"], trees=grid0["num_trees"],
            subsample=grid0["subsampling_rate"],
            features_per_node=c["features_per_node"],
            train_rows=c["train_rows"], tol_gain=c["tol_gain"],
            tol_leaf=c["tol_leaf"], tol_vote=c["tol_vote"],
            tol_metric=c["tol_metric"], tol_moment=c["tol_moment"],
            tol_corr=c["tol_corr"], tol_plain=c["tol_plain"],
            order_gap=c["order_gap"],
            threshold_binds=c["threshold_binds"])
    for check in (kernels, forest):   # a failed one does not stop the next
        try:
            check()
        except reference.CheckFailure as e:
            ctx.require(False, f"reference check failed: {e}")
