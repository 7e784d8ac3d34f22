"""Closed loop of one client over a REGRESSION boosted-tree
`CrossValidation.validate()`: the call a RegressionModelSelector makes for
OpGBTRegressor, the third family of that pool — feature matrix resident on
the device -> every grid point's boosting rounds grown for all folds as
lanes of the fused histogram passes, each round's REAL-VALUED residual
handed to the bfloat16 contraction as three exact parts over that round's
own scale, RMSE computed in the sweep, winner (the lower mean RMSE) on the
host.

A sibling of drivers/sweep.py and drivers/sweep_forest_reg.py, whose job,
spies, route check and loop it runs by import. What differs: the program is
asked BEFORE any data is made how this estimator's payload is carried into
the bfloat16 contraction (`models/trees.payload_body`) and is refused if it
has no such word — a program without it rounds every round's residual once
to bfloat16, at 2^-9 of each value; the word, the rows a (lane, slot), the
rounds and the scale reductions are read from the warm-up job's own spans,
telemetry and calls; and the answer is held to
benchmark/reference_gbt_reg.py.
"""
from __future__ import annotations

import importlib
import json

import numpy as np

from benchmark import datagen_forest_reg, harness, reference, \
    reference_gbt_reg

sweep = harness.load_module("drivers", "sweep")
sweep_forest_reg = harness.load_module("drivers", "sweep_forest_reg")

METRIC = sweep_forest_reg.METRIC
_checks = sweep_forest_reg._checks


def _require_payload(ctx, cls, params, grids) -> None:
    from transmogrifai_tpu.models import trees as MT
    ask = getattr(MT, "payload_body", None)
    want = ctx.cell["expect"]["booster"]["payload_body"]
    for g in grids:
        body = ask and ask(cls(**params).copy(**g))
        ctx.notes["payload_body_declared"] = body
        if body != want:
            raise harness.BenchFailure(
                f"models/trees.payload_body({cls.__name__} {g}) names "
                f"{body!r}, not {want!r}: this program hands the fused "
                f"passes every round's residual rounded ONCE to bfloat16; "
                f"nothing was made or measured")


class BoosterSpy:
    """Keep what the boosters' fold-fused fits of the warm-up job produced,
    a grid point at a time: the program's bin edges and (the first point's:
    they bin one matrix alike) binned matrix, every round's tree of every
    fold lane, the lanes' base scores, the margins of lane `fold` and of
    every lane's first `head` rows, and what the call said of its rule and
    its payload. No more is held through the window than the checks read."""

    def __init__(self, fold: int, head: int):
        self.fold, self.head = fold, head
        self.points, self._edges = [], None

    def __enter__(self):
        from transmogrifai_tpu.models import trees as MT
        from transmogrifai_tpu.ops import trees as T
        self._T, self._cls = T, MT._TreeEstimator
        self._fit, self._bin = T.fit_gbt_folds, MT._TreeEstimator._bin
        spy = self

        def bin_(est, X, n_valid=None):
            out = spy._bin(est, X, n_valid=n_valid)
            spy._edges = np.asarray(out[1], np.float32)
            return out

        def fit(Xb, y, W, key, **kw):
            out = spy._fit(Xb, y, W, key, **kw)
            trees, base, margins = out
            pt = {"Xb": None if spy.points else Xb, "edges": spy._edges,
                  "margins_fold": margins[spy.fold],
                  "margins_head": np.asarray(margins[:, :spy.head]),
                  "base": np.asarray(base, np.float64),
                  "lanes": int(W.shape[0]),
                  "min_instances": float(kw.get("min_instances", 1.0)),
                  "min_info_gain": float(kw.get("min_info_gain", 0.0)),
                  "said": {k: kw.get(k) for k in (
                      "payload", "normalize_gain", "n_rounds", "depth",
                      "learning_rate", "reg_lambda", "loss", "subsample")},
                  "trees": {k: np.asarray(getattr(trees, k))
                            for k in ("feat", "thresh", "miss", "leaf")}}
            pt["trees"]["leaf"] = pt["trees"]["leaf"][..., 0]
            spy.points.append(pt)
            return out
        MT._TreeEstimator._bin, T.fit_gbt_folds = bin_, fit
        return self

    def __exit__(self, *exc):
        self._cls._bin, self._T.fit_gbt_folds = self._bin, self._fit


def setup(ctx):
    sz = ctx.sizes
    if ctx.rehearse:
        for target, value in ctx.cell["rehearsal"].get(
                "program_globals", {}).items():
            mod, _, name = target.partition(":")
            setattr(importlib.import_module(mod), name, value)
    pool = []
    # the toy matrix's gains are not the cell's: its own two thresholds
    for fam, spec in ctx.param("families").items():
        base = ctx.config["pool"][fam]
        cls, params, grids = harness.pool_entry(base, spec["grid"],
                                                ctx.rehearse)
        if len(grids) != ctx.config[base["grid_key"]]:
            raise harness.BenchFailure(
                f"{fam}: {len(grids)} grid points, the configuration's "
                f"{base['grid_key']} says {ctx.config[base['grid_key']]}")
        _require_payload(ctx, cls, params, grids)
        pool.append((fam, cls, params, grids))
    X, y = datagen_forest_reg.device_matrix(
        sz["rows"], sz["cols"], sz["dtype"], ctx.seed, **ctx.config["label"])
    harness.log(f"data {X.shape} {X.dtype}, real label, on the device")
    st = sweep.State(X, y, pool)

    checks = _checks(ctx)

    def watched():
        with reference.DispatcherSpy() as spy, BoosterSpy(
                checks["gbt_answer"]["fold"],
                checks["kernel_twins"]["rows"]) as fits:
            answer = sweep_forest_reg._job(ctx, st)
        st.spy_calls, st.booster_points = spy.calls, fits.points
        return answer
    answer, events, spans = harness.watched_warmup(ctx, watched)
    st.warm_answer = answer
    declined = [e for e in events
                if e.get("event") == "booster_parts_route_declined"]
    ctx.notes["declined"] = declined
    ctx.require(not declined, f"the parts route was declined: {declined[:2]}")
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


def _expect(ctx) -> dict:
    """The booster's counts the cell states, under --rehearse those of its
    toy grid (other counts, the same words)."""
    want = dict(ctx.cell["expect"]["booster"])
    if ctx.rehearse:
        want.update(ctx.cell["rehearsal"]["booster"])
    return want


def _check_program(ctx, st) -> None:
    """What the warm-up job ran, from its own record: the counts, and the
    payload's word and its rows alike in the telemetry, on every
    tree_fused span and in the calls themselves."""
    from transmogrifai_tpu.utils.metrics import collector
    want = _expect(ctx)
    tele = dict(getattr(st.last_val, "last_tree_telemetry", None) or {})
    ctx.notes["booster"] = tele
    fits = [dict(s.attrs) for s in collector.trace.spans
            if s.kind == "tree_fused" and s.name == "tree_levels"]
    metric_spans = [dict(s.attrs) for s in collector.trace.spans
                    if s.kind == "validate_phase"
                    and s.name == "fold_metrics"]
    said = [p["said"] for p in st.booster_points]
    ctx.notes["program"] = {"tree_levels_spans": fits[:2],
                            "fits": len(fits), "calls_said": said,
                            "fold_metrics_spans": metric_spans[:1]}
    n_points = sum(len(g) for *_, g in st.pool)
    ctx.require(len(st.booster_points) == n_points,
                f"{len(st.booster_points)} grid points ran as fold-fused "
                f"booster fits, not {n_points}")
    for key, value in want.items():
        ctx.require(tele.get(key) == value,
                    f"the sweep counted {key} = {tele.get(key)!r}, "
                    f"not {value!r}")
    word, rows = want["payload_body"], want["payload_rows"]
    ctx.require(bool(said) and all(
        s["payload"] == word and s["normalize_gain"] is True
        and s["loss"] == "squared" for s in said),
        f"the fits were called with {said[:1]}, not payload {word!r} under "
        f"the gain a weighted row")
    ctx.require(bool(fits) and all(
        f.get("payload_body") == word and f.get("payload_rows") == rows
        and f.get("rounds") == s["n_rounds"] and f.get("lanes") == p["lanes"]
        for f, s, p in zip(fits, said, st.booster_points)),
        f"tree_levels spans carry {fits[:1]}, the calls {said[:1]}")
    ctx.require(bool(metric_spans) and all(
        m.get("metric") == METRIC
        and m.get("metric_body") == ctx.cell["expect"]["metric_body"]
        for m in metric_spans),
        f"fold_metrics spans: {metric_spans[:1]!r}")


def run_window(ctx, st) -> harness.Result:
    """drivers/sweep_forest_reg.py's window (the loop, the shapes the work
    models take, every job against the warm-up job's answer), and the
    booster's own counts under the names its layer files read."""
    result = sweep_forest_reg.run_window(ctx, st)
    tele = getattr(st.last_val, "last_tree_telemetry", None) or {}
    for key, name in (("payload_rows", "gbr_payload_rows"),
                      ("rounds", "gbr_tree_rounds")):
        if key in tele:
            ctx.counters[name] = tele[key]
    return result


def verify(ctx, st) -> None:
    """The checks that need a reference, outside the window: the routing
    and lookup kernels as drivers/sweep.py replays them, the histogram
    kernels in the booster's call shape under a round's scaled residual,
    then the booster's own checks against benchmark/reference_gbt_reg.py.
    Every reading lands in the notes before its bound is applied."""
    import jax.numpy as jnp
    checks = _checks(ctx)
    n = st.X.shape[0]
    masks = st.last_val.fold_masks(np.zeros(n))      # [folds, n], 1 = train
    c = checks["gbt_answer"]
    fam, _, params, grids = next(p for p in st.pool if p[0] == c["family"])
    grid0 = dict(params, **grids[0])
    ans = ctx.notes["gbt_answer"] = {}
    twins = ctx.notes["residual_twins"] = []
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
        reference.require(bool(st.booster_points),
                          "the program handed over no fold-fused fits")
        ys = np.asarray(st.y[:m], np.float32)
        first, last = st.booster_points[0], st.booster_points[-1]
        reference_gbt_reg.residual_twins(
            st.spy_calls, Xb_t, {
                "round_1": ys[None, :] - first["base"].astype(
                    np.float32)[:, None],
                "after_the_last_round": ys[None, :]
                - last["margins_head"][:, :m]},
            masks[:, :m], into=twins, seed=ctx.seed,
            interpret=ctx.rehearse, tol=k["tol"])

    def booster():
        reference.require(bool(st.booster_points),
                          "the program handed over no fold-fused fits: "
                          "nothing to hold to the reference")
        reference_gbt_reg.gbt_reg_answer(
            st.last_best, st.booster_points, masks, st.X, st.y, into=ans,
            fold=c["fold"], rounds=grid0["max_iter"],
            depth=grid0["max_depth"], bins=grid0["max_bins"],
            step=grid0["step_size"], lam=c["reg_lambda"],
            train_rows=c["train_rows"], tol_gain=c["tol_gain"],
            tol_leaf=c["tol_leaf"], tol_margin=c["tol_margin"],
            tol_metric=c["tol_metric"], tol_plain=c["tol_plain"])
    for check in (kernels, booster):   # a failed one does not stop the next
        try:
            check()
        except reference.CheckFailure as e:
            ctx.require(False, f"reference check failed: {e}")
