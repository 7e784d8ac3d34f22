"""Closed loop of one client over a REGRESSION `CrossValidation.validate()`:
the call a RegressionModelSelector makes for OpLinearRegression, on the
matrix `transmogrify()` makes of a table of numeric fields with holes
(`binary-25m-64-nulls`' table, block for block) under a real-valued label:
upstream's whole default grid, 4 x 2 = 8 points x 5 folds = 40 lanes,
standardised, max_iter 50, tol 1e-6, evaluator RMSE.

A sibling of drivers/sweep.py, whose State, route check and loop it runs by
import. What differs: the program is asked BEFORE any data is made whether
it declares the held-out-once route for a regression metric
(`validators.heldout_metric_body`), and is refused if not — without it the
sweep scores all rows once a FOLD with four fifths of them at weight zero,
and its Gram pass holds a second copy of the matrix; the data comes from
benchmark/datagen_regression.py; what the warm-up job ran is read from its
own telemetry and spans (the route, the passes over X, the moment-space
solves' own convergence counters, the Gram program's temporaries); and the
answer is held to benchmark/reference_regression.py.
"""
from __future__ import annotations

import importlib
import json
import operator

import numpy as np

from benchmark import datagen_regression, harness, reference, \
    reference_regression

sweep = harness.load_module("drivers", "sweep")

METRIC = "rmse"


def _require_route(ctx) -> None:
    from transmogrifai_tpu.automl.tuning import validators as V
    ask = getattr(V, "heldout_metric_body", None)
    body = ask and ask(METRIC, "regression", V.RANK_METRIC_BINS)
    ctx.notes["metric_body_declared"] = body
    want = ctx.cell["expect"]["telemetry"]["metric_body"]
    if body != want:
        raise harness.BenchFailure(
            f"automl/tuning/validators.heldout_metric_body({METRIC!r}, "
            f"'regression', ...) names {body!r}, not {want!r}: this "
            f"program scores every row once a fold for a regression "
            f"metric; nothing was made or measured")


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
    if sz["cols"] != 2 * sz["raw_cols"]:
        raise harness.BenchFailure(
            f"{sz['cols']} columns are not 2 x {sz['raw_cols']} fields")
    _require_route(ctx)
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
        pool.append((fam, cls, params, grids))
    X, y, _ = datagen_regression.device_matrix(
        sz["rows"], sz["raw_cols"], sz["dtype"], ctx.seed,
        **ctx.config["label"])
    harness.log(f"data {X.shape} {X.dtype}, real label, on the device")
    st = sweep.State(X, y, pool)

    def watched():
        with reference.StreamedFitSpy() as fits:
            answer = _job(ctx, st)
        st.streamed_fits = fits.fits
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
    """What the warm-up job ran, from its own record: the telemetry (the
    route, the passes, the solves' convergence counters), the spans inside
    the fit, and the compiled Gram pass's temporaries."""
    from transmogrifai_tpu.ops import glm_sweep as GS
    from transmogrifai_tpu.utils.metrics import collector
    expect, sz = ctx.cell["expect"], ctx.sizes
    tele = dict(st.last_val.last_streamed_telemetry or {})
    spans = {}
    for s in collector.trace.spans:
        spans.setdefault(f"{s.kind}:{s.name}", []).append(dict(s.attrs))
    max_iter = next(p for *_, p, _ in st.pool)["max_iter"]
    got = ctx.notes["program"] = {
        "telemetry": {k: tele.get(k) for k in (
            *expect["telemetry"], "gram_solve_iters", "gram_body",
            "lanes_total", "lanes_retired")},
        "gram_pass_spans": spans.get("host_step:gram_pass", []),
        "gram_solve_spans": spans.get("host_step:gram_solve", []),
        "eval_spans": [a for k, v in spans.items()
                       if k.startswith("sweep_eval:") for a in v]}
    temp = getattr(GS, "gram_temp_bytes", None)
    if temp is not None:
        import jax
        import jax.numpy as jnp
        n, f32 = st.X.shape[0], jnp.float32
        got["gram_temp_bytes"] = ctx.counters["lin_gram_temp_bytes"] = temp(
            st.X, st.y, jax.ShapeDtypeStruct((n,), f32),
            jax.ShapeDtypeStruct((sz["folds"], n), f32))
    if "x_passes" in tele:
        ctx.counters["lin_x_passes"] = tele["x_passes"]
    x_bytes = st.X.size * st.X.dtype.itemsize
    for key, want in expect["telemetry"].items():
        ctx.require(tele.get(key) == want,
                    f"telemetry {key}: {tele.get(key)!r}, not {want!r}")
    iters = tele.get("gram_solve_iters")
    ctx.require(isinstance(iters, int) and 0 < iters < max_iter,
                f"gram_solve_iters {iters!r}: the moment-space solves did "
                f"not stop under max_iter {max_iter}")
    lanes = sz["folds"] * sum(len(g) for *_, g in st.pool)
    for what, have, want in (
            ("host_step:gram_pass", got["gram_pass_spans"],
             [{"folds": sz["folds"], "cols": sz["cols"],
               "body": tele.get("gram_body"),
               "x_tile": GS.glm_x_tile(sz["cols"])}]),
            ("host_step:gram_solve", got["gram_solve_spans"],
             [{"lanes": lanes, "iters": iters, "lanes_at_cap": 0}])):
        have = [{k: a.get(k) for k in want[0]} for a in have]
        ctx.require(have == want, f"{what} spans: {have!r}, not {want!r}")
    ev = got["eval_spans"]
    ctx.require(
        len(ev) == 1 and all(
            ev[0].get(k) == expect["telemetry"][k]
            for k in ("eval_route", "passes", "metric_body")),
        f"sweep_eval spans: {ev!r}")
    if not ctx.rehearse:
        ctx.require(
            got.get("gram_temp_bytes", x_bytes)
            <= expect["gram_temp_share"] * x_bytes,
            f"the Gram program holds {got.get('gram_temp_bytes')} bytes of "
            f"temporaries, over {expect['gram_temp_share']:.0%} of the "
            f"matrix's {x_bytes}")


def run_window(ctx, st) -> harness.Result:
    with harness.profiler(ctx):
        done = harness.closed_loop(
            lambda: _job(ctx, st), ctx.seconds, "bench.validate",
            max_jobs=ctx.param("trace_jobs") if ctx.trace else None)
    # what opcount_gram takes, flat, so that a layer file names them
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


def _checks(ctx) -> dict:
    return {k: dict(c, **(c.get("rehearsal", {}) if ctx.rehearse else {}))
            for k, c in ctx.cell.get("checks", {}).items()}


def verify(ctx, st) -> None:
    """The checks that need a reference, outside the window: blocks of the
    cell file's `checks`; a `rehearsal` block wins under --rehearse. Every
    reading of every check lands in the notes before any bound is applied,
    so a run that fails one still reports them all."""
    checks = _checks(ctx)
    n = st.X.shape[0]
    masks = st.last_val.fold_masks(np.zeros(n))      # [folds, n], 1 = train
    c = checks["linreg_answer"]
    _, _, params, grids = next(p for p in st.pool if p[0] == c["family"])
    ans = ctx.notes["linreg_answer"] = {}
    try:
        twin = ctx.notes["moments_twin"] = _moments_twin(
            ctx, st, masks, min(checks["moments_twin"]["rows"], n))
        harness.log(f"moments twin: {twin}")
        reference_regression.linreg_answer(
            st.last_best, st.streamed_fits, masks, grids, st.X, st.y,
            into=ans, fit_params={"max_iter": params["max_iter"],
                                  "tol": params["tol"]},
            reference_fold=c["reference_fold"],
            reference_rows=c["reference_rows"])
    except reference.CheckFailure as e:
        ctx.require(False, f"reference check failed: {e}")
        return
    iters = ctx.notes["program"]["telemetry"].get("gram_solve_iters") or 0
    ctx.require(iters >= ans["replay_iters_max"],
                f"the moment-space solves stopped after {iters} iterations; "
                f"the float64 replay of the documented iteration needs "
                f"{ans['replay_iters_max']}")
    wrong = reference_regression.misordered(
        ans["order"], c["order_apart"] * c["tol_metric"])
    ans["order"]["misordered"] = wrong
    ctx.require(not wrong,
                f"grid points the sweep's report orders unlike the exact "
                f"mean RMSEs of its own coefficients: {wrong}")
    for got, tol, what in (
            (ans["metric_worst_delta"], c["tol_metric"],
             "a fold RMSE of the sweep, off the exact RMSE of its own "
             "coefficients"),
            (ans["replay_delta_worst"], c["tol_replay"],
             "the sweep's standardised coefficients, off the float64 "
             "replay of the documented iteration on the reference's "
             "moments of all the fold's training rows"),
            (ans["intercept_delta_worst"], c["tol_intercept"],
             "the sweep's intercept on the standardised columns, off that "
             "replay's"),
            (ans["kkt_worst"], c["tol_kkt"],
             "the KKT residual of the sweep's coefficients over the fold's "
             "training rows"),
            (ans["coefficients_worst"], c["tol_coefficients"],
             "the sweep's standardised coefficients, off the plain fit on "
             "the sample"),
            (ans["mse_delta_worst"], c["tol_mse"],
             "the sweep's held-out MSE, off the plain fit's"),
            (twin["worst"], checks["moments_twin"]["tol"],
             "a per-fold moment of the program's Gram pass, off its "
             "float64 twin, of the largest")):
        ctx.require(got <= tol, f"{what}: {got:.3e} (bound {tol})")


def _moments_twin(ctx, st, masks, m: int) -> dict:
    """The program's Gram pass (`glm_sweep.sweep_gram_moments`) replayed on
    the first `m` rows under the sweep's own column moments, the folds'
    masks and seeded row weights from 0.5 to 2 (a pass that rounded a
    weighted operand would show), against the numpy float64 twin; beside
    it what the twin reads with its standardised rows rounded once to
    bfloat16 (the matrix unit's default), and with the same sums
    accumulated in bfloat16 (rounded after every 1 024 rows). Errors are
    relative to each sum's largest entry; the worst of the five is
    held."""
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import glm_sweep as GS
    R = reference_regression
    X, y = st.X[:m], st.y[:m]
    w = np.random.default_rng(ctx.seed).uniform(0.5, 2.0, m) \
        .astype(np.float32)
    mean, std = GS.glm_standardize_stats(
        st.X, jnp.ones(st.X.shape[0], jnp.float32))
    got = GS.sweep_gram_moments(X, y, jnp.asarray(w),
                                jnp.asarray(masks[:, :m], jnp.float32),
                                mean, std)
    args = (np.asarray(X.astype(jnp.float32)), np.asarray(y), w,
            masks[:, :m], np.asarray(mean), np.asarray(std))
    ref = R.moments_twin(*args)
    rounded = R.moments_twin(*args, rounded=True)
    low = [np.zeros_like(r, dtype=np.float32) for r in ref]
    for s in range(0, m, 1024):
        part = R.moments_twin(args[0][s:s + 1024], args[1][s:s + 1024],
                              args[2][s:s + 1024], args[3][:, s:s + 1024],
                              *args[4:])
        low = [R.as_bf16(a + R.as_bf16(p)) for a, p in zip(low, part)]

    def off(vals):
        return [float(np.abs(np.asarray(v, np.float64) - r).max()
                      / np.abs(r).max()) for v, r in zip(vals, ref)]
    return {"rows": m, "cols": int(X.shape[1]), "folds": int(masks.shape[0]),
            "by_sum": off(got), "worst": max(off(got)),
            "once_rounded_operands": max(off(rounded)),
            "bf16_accumulation": max(off(low))}
