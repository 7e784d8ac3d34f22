"""Closed loop of one client over a MULTICLASS `CrossValidation.validate()`:
the call a MultiClassificationModelSelector makes, feature matrix resident
on the device -> every LR grid point x fold fitted by the streamed
multinomial rounds, the multiclass error computed in the sweep, winner on
the host.

A sibling of drivers/sweep.py (whose State, route check and loop it
reuses): the evaluator, the label, the counters and the checks differ. A
program that does not declare the streamed multiclass route is refused
before any data is made: its vmapped route would attempt [rows, classes]
float32 arrays per lane at this size.
"""
from __future__ import annotations

import importlib
import json
import operator

import numpy as np

from benchmark import datagen_softmax, harness, reference, reference_softmax

sweep = harness.load_module("drivers", "sweep")


def _job(ctx, st):
    import jax.numpy as jnp
    from transmogrifai_tpu.automl.tuning.validators import CrossValidation
    from transmogrifai_tpu.evaluators.evaluators import Evaluators

    sz = ctx.sizes
    val = CrossValidation(Evaluators.MultiClassification.error(),
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
        if getattr(cls, "streamed_multiclass_loss", None) is None:
            raise harness.BenchFailure(
                f"{cls.__name__} declares no streamed multiclass route "
                f"(streamed_multiclass_loss): this program would fit "
                f"{sz['rows']} x {sz['classes']} float32 arrays per lane; "
                f"nothing was made or measured")
        pool.append((fam, cls, params, grids))
    X, y = datagen_softmax.device_matrix(
        sz["rows"], sz["cols"], sz["classes"], sz["dtype"], ctx.seed,
        ctx.config["truth_scale"])
    harness.log(f"data {X.shape} {X.dtype}, {sz['classes']} classes, on "
                f"the device")
    st = sweep.State(X, y, pool)

    def watched():
        with reference.StreamedFitSpy() as fits:
            answer = _job(ctx, st)
        st.streamed_fits = fits.fits
        return answer
    answer, events, spans = harness.watched_warmup(ctx, watched)
    st.warm_answer = answer
    sweep._check_routes(ctx, st, events, spans)
    best = st.last_best
    ctx.notes["winner"] = {"name": answer[0], "grid": json.loads(answer[1]),
                           "error": float(best.best_metric)}
    # predicting the largest class alone errs by 1 - its share
    share = float(np.bincount(np.asarray(y[:1 << 16]).astype(np.int64),
                              minlength=sz["classes"]).max()) / min(
        1 << 16, sz["rows"])
    ctx.require(np.isfinite(best.best_metric)
                and 0.0 < best.best_metric < 1.0 - share,
                f"winner error {best.best_metric}, the largest class "
                f"alone gives {1.0 - share}")
    for v in best.validated:
        ctx.require(len(v.fold_metrics) == sz["folds"]
                    and bool(np.all(np.isfinite(v.fold_metrics))),
                    f"fold metrics of {v.model_name} {v.grid}")
    return st


def run_window(ctx, st) -> harness.Result:
    with harness.profiler(ctx):
        done = harness.closed_loop(
            lambda: _job(ctx, st), ctx.seconds, "bench.validate",
            max_jobs=ctx.param("trace_jobs") if ctx.trace else None)
    tele = st.last_val.last_streamed_telemetry or {}
    # the program's own counts, under the names the layer files read
    for key, name in (("padded_lane_passes", "mlr_padded_lane_passes"),
                      ("lane_passes", "mlr_lane_passes"),
                      ("data_passes", "mlr_data_passes"),
                      ("gram_passes", "mlr_gram_passes"),
                      ("glm_rounds", "mlr_rounds"), ("classes", "classes")):
        if key in tele:
            ctx.counters[name] = tele[key]
    import jax.numpy as jnp
    ctx.counters.update(
        rows=ctx.sizes["rows"], cols=ctx.sizes["cols"],
        folds=ctx.sizes["folds"],
        x_itemsize=jnp.dtype(ctx.sizes["dtype"]).itemsize,
        grid_points=[g for *_, grids in st.pool for g in grids])
    if done:
        ctx.require(done[0][1] == st.warm_answer,
                    "the window's jobs answered unlike the warm-up job, "
                    "whose routes and coefficients were read")
    return harness.job_result(ctx, done, ctx.cell["metric"], operator.eq)


def verify(ctx, st) -> None:
    """The checks that need a reference, outside the window: blocks of the
    cell file's `checks`; a `rehearsal` block wins under --rehearse."""
    checks = {k: dict(c, **(c.get("rehearsal", {}) if ctx.rehearse else {}))
              for k, c in ctx.cell.get("checks", {}).items()}
    n, K = st.X.shape[0], ctx.sizes["classes"]
    masks = st.last_val.fold_masks(np.zeros(n))      # [folds, n], 1 = train
    try:
        if "mlr_answer" in checks:
            c = checks["mlr_answer"]
            fam, _, params, grids = next(p for p in st.pool
                                         if p[0] == c["family"])
            ctx.notes["mlr_answer"] = reference_softmax.mlr_sweep_answer(
                st.last_best, st.streamed_fits, masks, grids, st.X, st.y,
                n_classes=K,
                fit_params={"max_iter": params["max_iter"],
                            "tol": params["tol"],
                            "fit_intercept": params["fit_intercept"],
                            "standardize": params["standardization"]},
                reference_fold=c["reference_fold"],
                reference_rows=c["reference_rows"],
                tol_metric=c["tol_metric"],
                tol_coefficients=c["tol_coefficients"],
                tol_logloss=c["tol_logloss"])
        if "confusion_twin" in checks:
            ctx.notes["confusion_twin"] = _confusion_twin(
                st, masks, min(checks["confusion_twin"]["rows"], n), K,
                ctx.seed)
    except reference.CheckFailure as e:
        ctx.require(False, f"reference check failed: {e}")


def _confusion_twin(st, masks, m: int, K: int, seed: int) -> dict:
    """The program's lane-batched confusion count and the metrics it
    derives, replayed on the first `m` rows against the numpy twin: one
    lane per fold of the sweep's own first-grid-point coefficients, the
    fold's held-out rows under seeded NON-unit weights (a count that
    rounded its weights to bf16 would show)."""
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import metrics_ops as M

    B, b0 = st.streamed_fits[0]
    y = np.asarray(st.y[:m])
    w = np.random.default_rng(seed).uniform(0.5, 1.5, m).astype(np.float32)
    out = {"rows": m, "lanes": int(B.shape[0]), "worst_count": 0.0,
           "worst_metric": 0.0}
    for f in range(B.shape[0]):
        pred, _ = reference_softmax.scores(st.X[:m], y, B[f, 0], b0[f, 0])
        wf = w * (1.0 - masks[f, :m])
        got = np.asarray(M.confusion_lanes(
            jnp.asarray(pred)[None, :], jnp.asarray(y), jnp.asarray(wf),
            K))[0]
        ref = reference_softmax.confusion_plain(pred, y, wf, K)
        out["worst_count"] = max(out["worst_count"],
                                 float(np.abs(got - ref).max()))
        mets = M.multiclass_metrics_from_confusion(jnp.asarray(got))
        plain = reference_softmax.metrics_plain(ref)
        out["worst_metric"] = max(out["worst_metric"], *(
            abs(float(getattr(mets, k)) - plain[k]) for k in plain))
    # float32 sums of <= m weights of size ~1: 1e-3 is 3e-8 of the mass
    reference.require(out["worst_count"] <= 1e-3,
                      f"a confusion cell is {out['worst_count']:.2e} off "
                      f"the plain count")
    reference.require(out["worst_metric"] <= 1e-5,
                      f"a class metric is {out['worst_metric']:.2e} off "
                      f"the plain one")
    harness.log(f"confusion twin: cells within {out['worst_count']:.2e}, "
                f"metrics within {out['worst_metric']:.2e}")
    return out
