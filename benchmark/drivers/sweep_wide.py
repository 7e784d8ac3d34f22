"""Closed loop of one client over a WIDE binary `CrossValidation.validate()`:
the call a BinaryClassificationModelSelector makes after `transmogrify()`
hashed a table's free-text columns — thousands of term-count columns
resident on the device -> every LR grid point x fold fitted by the streamed
wide rounds, the exact AuPR computed in the sweep, winner on the host.

A sibling of drivers/sweep.py (whose State, job, route check and loop it
reuses): the data, the counters and the checks differ. A program that does
not declare the wide streamed route is refused before any data is made: at
this width its other routes form a [cols, cols] Hessian a lane an
iteration, or a copy of the matrix a lane.
"""
from __future__ import annotations

import importlib
import json
import operator

import numpy as np

from benchmark import datagen_hashed, harness, reference, reference_wide

sweep = harness.load_module("drivers", "sweep")

def _require_route(cls, cols: int, lanes: int) -> None:
    from transmogrifai_tpu.automl.tuning import validators as V
    from transmogrifai_tpu.ops import glm_sweep as GS
    ok = getattr(GS, "streamed_wide_route_ok", None)
    if getattr(cls, "streamed_loss", None) != "logistic" or ok is None \
            or not ok(cols, lanes, V.SWEEP_LANE_BUDGET_BYTES):
        raise harness.BenchFailure(
            f"{cls.__name__} declares no wide streamed route "
            f"(ops/glm_sweep.streamed_wide_route_ok({cols}, {lanes}, ...)): "
            f"this program would form a [{cols}, {cols}] Hessian a lane an "
            f"iteration; nothing was made or measured")


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
        _require_route(cls, sz["cols"], sz["folds"] * len(grids))
        pool.append((fam, cls, params, grids))
    if sz["cols"] != sz["text_columns"] * (sz["buckets"] + 1):
        raise harness.BenchFailure(
            f"{sz['cols']} columns are not {sz['text_columns']} x "
            f"({sz['buckets']} + 1)")
    X, y = datagen_hashed.device_matrix(
        sz["rows"], sz["text_columns"], sz["buckets"], sz["dtype"], ctx.seed,
        truth_nonzero=sz["truth_nonzero"],
        truth_scale=ctx.config["truth_scale"],
        truth_intercept=ctx.config["truth_intercept"])
    harness.log(f"data {X.shape} {X.dtype} on the device")
    st = sweep.State(X, y, pool)

    def watched():
        with reference.StreamedFitSpy() as fits:
            answer = sweep._job(ctx, st)
        st.streamed_fits = fits.fits
        return answer
    answer, events, spans = harness.watched_warmup(ctx, watched)
    st.warm_answer = answer
    sweep._check_routes(ctx, st, events, spans)
    tele = st.last_val.last_streamed_telemetry or {}
    ctx.require(tele.get("kernel") == "wide_rounds",
                f"the sweep ran kernel {tele.get('kernel')!r}, not the wide "
                f"rounds")
    best = st.last_best
    rate = float(np.asarray(y[:1 << 16]).mean())
    ctx.notes["winner"] = {"name": answer[0], "grid": json.loads(answer[1]),
                           "au_pr": float(best.best_metric),
                           "positive_rate": rate}
    ctx.require(np.isfinite(best.best_metric)
                and 2.0 * rate < best.best_metric <= 1.0,
                f"winner AuPR {best.best_metric} at positive rate {rate}")
    for v in best.validated:
        ctx.require(len(v.fold_metrics) == sz["folds"]
                    and bool(np.all(np.isfinite(v.fold_metrics))),
                    f"fold metrics of {v.model_name} {v.grid}")
    return st


def run_window(ctx, st) -> harness.Result:
    with harness.profiler(ctx):
        done = harness.closed_loop(
            lambda: sweep._job(ctx, st), ctx.seconds, "bench.validate",
            max_jobs=ctx.param("trace_jobs") if ctx.trace else None)
    tele = st.last_val.last_streamed_telemetry or {}
    # the program's own counts, under the names the layer files read
    for key in ("padded_lane_passes", "lane_passes", "data_passes",
                "x_passes", "gram_passes", "factorizations", "glm_rounds",
                "inner_steps", "padded_cols"):
        if key in tele:
            ctx.counters["wglm_" + key.replace("glm_", "")] = tele[key]
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
    n = st.X.shape[0]
    masks = st.last_val.fold_masks(np.zeros(n))      # [folds, n], 1 = train
    try:
        if "wide_answer" in checks:
            c = checks["wide_answer"]
            fam, _, params, grids = next(p for p in st.pool
                                         if p[0] == c["family"])
            ctx.notes["wide_answer"] = {}
            reference_wide.wide_sweep_answer(
                st.last_best, st.streamed_fits, masks, grids, st.X, st.y,
                into=ctx.notes["wide_answer"],
                fit_params={"max_iter": params["max_iter"],
                            "tol": params["tol"],
                            "fit_intercept": params["fit_intercept"],
                            "standardize": params["standardization"]},
                reference_fold=c["reference_fold"],
                tol_metric=c["tol_metric"],
                tol_coefficients=c["tol_coefficients"],
                tol_logloss=c["tol_logloss"],
                tol_objective=c["tol_objective"])
        if "gram_twin" in checks:
            c = checks["gram_twin"]
            ctx.notes["gram_twin"] = _gram_twin(st, min(c["rows"], n),
                                                ctx.seed, c["tol"])
    except reference.CheckFailure as e:
        ctx.require(False, f"reference check failed: {e}")


def _gram_twin(st, m: int, seed: int, tol: float) -> dict:
    """The program's Gram step replayed on the first `m` rows, under seeded
    0/1 weights (a fold's mask), against the numpy float64 twin; beside it
    what the same Gram accumulated in bfloat16 (rounded after every 512
    rows) reads. Errors are relative to the largest entry."""
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import glm_sweep as GS

    X = st.X[:m]
    w = (np.random.default_rng(seed).uniform(size=m) < 0.8) \
        .astype(np.float32)
    mean, std = GS.glm_standardize_stats(X, jnp.asarray(w))
    inv_std = 1.0 / std
    got, lam = GS.wide_gram(X, jnp.asarray(w), mean, inv_std)
    Xh = np.asarray(X.astype(jnp.float32), np.float64)
    ref = reference_wide.gram_twin(Xh, w, mean, inv_std)
    top = float(np.abs(ref).max())
    low = np.zeros_like(ref, np.float32)
    for s in range(0, m, 512):
        blk = (Xh[s:s + 512] * w[s:s + 512, None]).T @ Xh[s:s + 512]
        low = reference_wide._as_bf16(low + reference_wide._as_bf16(blk))
    D = np.asarray(inv_std, np.float64)
    low = (low - w.sum() * np.outer(mean, mean)) * D[:, None] * D[None, :]
    eig = float(np.linalg.eigvalsh(ref)[-1])
    out = {"rows": m, "cols": int(X.shape[1]),
           "worst": float(np.abs(np.asarray(got) - ref).max()) / top,
           "bf16_accumulation": float(np.abs(low - ref).max()) / top,
           "lam_over_top_eigenvalue": float(lam) / eig}
    harness.log(f"Gram twin: entries within {out['worst']:.2e} of the "
                f"largest (a bfloat16 accumulation: "
                f"{out['bf16_accumulation']:.2e}); lam / top eigenvalue "
                f"{out['lam_over_top_eigenvalue']:.4f}")
    reference.require(out["worst"] <= tol,
                      f"a Gram entry is {out['worst']:.2e} of the largest "
                      f"off the float64 twin (bound {tol})")
    reference.require(1.0 <= out["lam_over_top_eigenvalue"] <= 1.06,
                      f"lam is {out['lam_over_top_eigenvalue']:.4f} x the "
                      f"Gram's top eigenvalue: the inner step would be "
                      f"too long or needlessly short")
    return out
