"""Closed loop of one client over `CrossValidation.validate()`: the call a
ModelSelector makes, feature matrix resident on the device -> every grid
point x fold fitted, in-sweep metric computed, winner on the host.

The cell file names the families, their grids and the end-to-end metric
its median sweep wall is reported as; the configuration file the matrix,
the folds and each family's fixed parameters. A new sweep
cell (another family, another grid) is a new cell file and nothing else.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import operator

import numpy as np

from benchmark import datagen, harness, reference


@dataclasses.dataclass
class State:
    X: object
    y: object
    pool: list              # (family key, estimator class, params, grids)
    spy_calls: list = dataclasses.field(default_factory=list)
    streamed_fits: list = dataclasses.field(default_factory=list)
    warm_answer: tuple = None
    last_best: object = None
    last_val: object = None


def _job(ctx, st: State):
    import jax.numpy as jnp
    from transmogrifai_tpu.automl.tuning.validators import CrossValidation
    from transmogrifai_tpu.evaluators.evaluators import Evaluators

    sz = ctx.sizes
    val = CrossValidation(Evaluators.BinaryClassification.au_pr(),
                          num_folds=sz["folds"], seed=sz["cv_seed"],
                          sweep_dtype=jnp.dtype(sz["dtype"]), mesh=None)
    models = [(cls(**params), [dict(g) for g in grids])
              for _, cls, params, grids in st.pool]
    # validate() returns host floats reduced from every device result of
    # the sweep, so the wall ends after the last of them: no fence needed
    best = val.validate(models, st.X, st.y)
    st.last_best, st.last_val = best, val
    return (best.name, json.dumps(best.best_grid, sort_keys=True),
            tuple(np.asarray(v.fold_metrics, np.float64).tobytes()
                  for v in best.validated))


def setup(ctx) -> State:
    sz = ctx.sizes
    if ctx.rehearse:
        # the program picks its routes by size; a cell's rehearsal block
        # may lower the floors it documents as hand overrides, so that the
        # toy size takes the routes the chip takes and the checks run
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
    X, y = datagen.device_matrix(sz["rows"], sz["cols"], sz["dtype"],
                                 ctx.seed)
    harness.log(f"data {X.shape} {X.dtype} on the device")
    st = State(X, y, pool)

    def watched():
        with reference.DispatcherSpy() as spy, \
                reference.StreamedFitSpy() as fits:
            answer = _job(ctx, st)
        st.spy_calls, st.streamed_fits = spy.calls, fits.fits
        return answer
    answer, events, spans = harness.watched_warmup(ctx, watched)
    st.warm_answer = answer
    _check_routes(ctx, st, events, spans)
    best = st.last_best
    ctx.notes["winner"] = {"name": answer[0], "grid": json.loads(answer[1]),
                           "au_pr": float(best.best_metric)}
    ctx.require(np.isfinite(best.best_metric)
                and 0.5 < best.best_metric <= 1.0,
                f"winner AuPR {best.best_metric}")
    for v in best.validated:
        ctx.require(len(v.fold_metrics) == sz["folds"]
                    and bool(np.all(np.isfinite(v.fold_metrics))),
                    f"fold metrics of {v.model_name} {v.grid}")
    return st


def _check_routes(ctx, st, events, spans) -> None:
    """Routes, fallbacks and kernel dispatchers, each read from the
    warm-up job's own record. On the CPU (--rehearse) the program takes
    other routes by design: reported, not enforced."""
    cells = [e for e in events if e.get("event") == "sweep_cell_landed"]
    routes = sorted({(e["model"], e["route"]) for e in cells})
    seen = sorted({(c["kernel"], c["interpret"], c["available"])
                   for c in st.spy_calls})
    ctx.notes["routes"] = {"cells": routes, "n_cells": len(cells),
                           "kernel_spans": sorted(set(spans)),
                           "dispatchers": seen}
    if ctx.rehearse:
        return
    expect = ctx.cell["expect"]
    n_points = sum(len(g) for *_, g in st.pool)
    ctx.require(dict(routes) == expect["routes"],
                f"routes {routes} != {expect['routes']}")
    ctx.require(len(cells) == n_points,
                f"{len(cells)} cells landed for {n_points} grid points")
    ctx.require(not any(e.get("event") == "fused_route_fallback"
                        for e in events), "fused_route_fallback fired")
    for kernel, count in expect.get("kernel_spans", {}).items():
        ctx.require(spans.count(kernel) == count,
                    f"{spans.count(kernel)} {kernel} spans, not {count}")
    names = {c["kernel"] for c in st.spy_calls}
    for d in expect["dispatchers"]:
        ctx.require(d in names, f"dispatcher {d} never called")
    for c in st.spy_calls:
        ctx.require(c["available"] and not c["interpret"],
                    f"{c['kernel']} ran with available={c['available']} "
                    f"interpret={c['interpret']}")


def run_window(ctx, st: State) -> harness.Result:
    with harness.profiler(ctx):
        done = harness.closed_loop(
            lambda: _job(ctx, st), ctx.seconds, "bench.validate",
            max_jobs=ctx.param("trace_jobs") if ctx.trace else None)
    tele = st.last_val.last_streamed_telemetry or {}
    for key in ("padded_lane_passes", "lane_passes", "data_passes",
                "glm_rounds"):
        if key in tele:
            ctx.counters["glm_" + key.replace("glm_", "")] = tele[key]
    # what the opcount models take, flat, so that a layer file names them
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


def verify(ctx, st: State) -> None:
    """The checks that need a reference, outside the window. Each is a
    block of the cell file's `checks`; its `rehearsal` block wins under
    --rehearse."""
    import jax.numpy as jnp
    checks = {k: dict(c, **(c.get("rehearsal", {}) if ctx.rehearse else {}))
              for k, c in ctx.cell.get("checks", {}).items()}
    n = st.X.shape[0]
    masks = st.last_val.fold_masks(np.zeros(n))      # [folds, n], 1 = train
    try:
        if "glm_answer" in checks:
            c = checks["glm_answer"]
            grids = next(g for fam, _, _, g in st.pool
                         if fam == c["family"])
            ctx.notes["glm_answer"] = reference.glm_sweep_answer(
                st.last_best, st.streamed_fits, masks, grids, st.X, st.y,
                reference_fold=c["reference_fold"],
                reference_rows=c["reference_rows"],
                tol_metric=c["tol_metric"],
                tol_reference=c["tol_reference"])
        if "gbt_answer" in checks:
            c = checks["gbt_answer"]
            ctx.notes["gbt_answer"] = reference.gbt_sweep_answer(
                st.last_best, masks, st.X, st.y, fold=c["fold"],
                train_rows=c["train_rows"], tol=c["tol"])
        if "kernel_twins" in checks:
            c = checks["kernel_twins"]
            m = min(c["rows"], n)
            Xb_t = reference.binned_sample(st.X[:m], c["bins"], ctx.seed)
            ctx.notes["kernel_twins"] = reference.kernel_checks(
                st.spy_calls, Xb_t, st.y[:m], jnp.asarray(masks[:, :m]),
                st.X[:m, 0].astype(jnp.float32), interpret=ctx.rehearse,
                binned_tol=c["binned_tol"])
    except reference.CheckFailure as e:
        ctx.require(False, f"reference check failed: {e}")
