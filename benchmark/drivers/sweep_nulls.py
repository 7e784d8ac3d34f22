"""Closed loop of one client over `CrossValidation.validate()` on the matrix
`transmogrify()` makes of a table of numeric fields with holes: every field
mean-imputed and followed by its null indicator (RealVectorizer,
TrackNulls), so 64 fields reach the selector as 128 columns, half of them
sparse 0/1 columns at rates from 0.001 to 0.5, the other half with a point
mass at their mean and scales from 2^-4 to 2^4 — upstream's LR grid WHOLE,
standardised, max_iter 50, tol 1e-6.

A sibling of drivers/sweep.py, whose set-up, job, route check and loop it
runs by import. What differs: the program is asked BEFORE any data is made
whether the rounds' pass over a matrix of this width is the fused body on
this backend (`ops/glm_sweep.glm_round_kernel`), and is refused if not — at
128 columns the other body holds a second, padded copy of the matrix and
runs each step of the pass as a fusion of its own; the data comes from
benchmark/datagen_nulls.py; what the warm-up job ran is read from its own
spans, telemetry and the kernel's dispatcher; and the answer is held to
benchmark/reference_nulls.py and to the program's own vectoriser.
"""
from __future__ import annotations

import contextlib

import numpy as np

from benchmark import datagen_nulls, harness, reference, reference_nulls

sweep = harness.load_module("drivers", "sweep")


def _lanes(ctx) -> int:
    return ctx.sizes["folds"] * sum(
        ctx.config[ctx.config["pool"][fam]["grid_key"]]
        for fam in ctx.cell["families"])


def _require_kernel(ctx) -> None:
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import glm_sweep as GS
    sz = ctx.sizes
    choose = getattr(GS, "glm_round_kernel", None)
    bucket = GS.bucket_lanes(_lanes(ctx))
    body = choose and choose(sz["cols"], jnp.dtype(sz["dtype"]), bucket)
    ctx.notes["round_body_declared"] = body
    if body != ctx.cell["expect"]["round_kernel"] and not ctx.rehearse:
        raise harness.BenchFailure(
            f"ops/glm_sweep.glm_round_kernel({sz['cols']}, {sz['dtype']}, "
            f"{bucket}) names {body!r}, not "
            f"{ctx.cell['expect']['round_kernel']!r}: on this backend the "
            f"rounds would run the XLA row blocks over a padded copy of the "
            f"matrix; nothing was made or measured")


class _MomentsSpy:
    """Every call the sweep makes into ops/pallas_glm.glm_moments (at trace
    time, once a compiled round program): the matrix's shape, the tile form
    and whether it was interpreted."""

    def __init__(self):
        self.calls = []

    def __enter__(self):
        from transmogrifai_tpu.ops import pallas_glm as PG
        self._PG, self._orig = PG, PG.glm_moments

        def wrapped(XT, *args, **kw):
            self.calls.append({
                "x_shape": tuple(int(s) for s in XT.shape),
                "lanes": int(args[3].shape[1]),
                "x_tile": kw.get("x_tile", "rows_minor"),
                "interpret": bool(kw.get("interpret", False))})
            return self._orig(XT, *args, **kw)
        PG.glm_moments = wrapped
        return self

    def __exit__(self, *exc):
        self._PG.glm_moments = self._orig


@contextlib.contextmanager
def _data(ctx, fills: list):
    """drivers/sweep.py's set-up with its matrix made by datagen_nulls; the
    fills the device used land in `fills`."""
    class Nulls:
        @staticmethod
        def device_matrix(rows, cols, dtype, seed):
            X, y, f = datagen_nulls.device_matrix(
                rows, cols // 2, dtype, seed,
                truth_scale=ctx.config["truth_scale"],
                truth_intercept=ctx.config["truth_intercept"])
            fills.append(f)
            return X, y
    plain, sweep.datagen = sweep.datagen, Nulls
    try:
        yield
    finally:
        sweep.datagen = plain


def setup(ctx):
    sz = ctx.sizes
    if sz["cols"] != 2 * sz["raw_cols"]:
        raise harness.BenchFailure(
            f"{sz['cols']} columns are not 2 x {sz['raw_cols']} fields")
    _require_kernel(ctx)
    fills = []
    with _MomentsSpy() as spy, _data(ctx, fills):
        st = sweep.setup(ctx)
    st.fills, st.moments_calls = fills[0], spy.calls
    _check_body(ctx, st)
    return st


def _check_body(ctx, st) -> None:
    """Which body ran the rounds' pass over X and in which tile form, from
    the warm-up job's own record: the telemetry, every round's span, the
    fit's span, the kernel's dispatcher, and the compiled round program's
    temporaries."""
    from transmogrifai_tpu.ops import glm_sweep as GS
    from transmogrifai_tpu.utils.metrics import collector
    expect, sz = ctx.cell["expect"], ctx.sizes
    tele = dict(st.last_val.last_streamed_telemetry or {})
    spans = [(s.kind, dict(s.attrs)) for s in collector.trace.spans]
    rounds = [a for k, a in spans if k == "sweep_round"]
    fit = next((a for k, a in spans if k == "sweep_fit"), {})
    bucket = GS.bucket_lanes(_lanes(ctx))
    got = ctx.notes["round_body"] = {
        "telemetry": tele.get("round_kernel"),
        "round_spans": sorted({(a.get("body"), a.get("x_tile"))
                               for a in rounds}),
        "fit_span": {k: fit.get(k) for k in ("cols", "lanes", "bucket",
                                             "standardize")},
        "moments_calls": st.moments_calls}
    temp = getattr(GS, "glm_round_temp_bytes", None)
    if temp is not None:
        import jax
        import jax.numpy as jnp
        n, f32 = st.X.shape[0], jnp.float32
        got["round_temp_bytes"] = ctx.counters["glm_round_temp_bytes"] = \
            temp(st.X, st.y, jax.ShapeDtypeStruct((n,), f32),
                 jax.ShapeDtypeStruct((sz["folds"], n), f32), bucket,
                 loss="logistic")
    x_bytes = st.X.size * st.X.dtype.itemsize
    if ctx.rehearse:
        return
    for what, have, want in (
            ("telemetry round_kernel", got["telemetry"],
             expect["round_kernel"]),
            ("sweep_round spans (body, x_tile)", got["round_spans"],
             [(expect["round_kernel"], expect["x_tile"])]),
            ("sweep_fit span", got["fit_span"],
             {"cols": sz["cols"], "lanes": _lanes(ctx), "bucket": bucket,
              "standardize": True})):
        ctx.require(have == want, f"{what}: {have!r}, not {want!r}")
    ctx.require(
        bool(st.moments_calls) and all(
            c["x_shape"] == tuple(st.X.shape)
            and c["x_tile"] == expect["x_tile"] and not c["interpret"]
            for c in st.moments_calls),
        f"glm_moments was dispatched as {st.moments_calls}, not on the "
        f"{tuple(st.X.shape)} matrix in {expect['x_tile']} tiles")
    ctx.require(
        got.get("round_temp_bytes", x_bytes)
        <= expect["round_temp_share"] * x_bytes,
        f"the round program holds {got.get('round_temp_bytes')} bytes of "
        f"temporaries, over {expect['round_temp_share']:.0%} of the "
        f"matrix's {x_bytes}")


run_window = sweep.run_window


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
    c = checks["nulls_answer"]
    _, _, params, grids = next(p for p in st.pool if p[0] == c["family"])
    ans = ctx.notes["nulls_answer"] = {}
    try:
        tie = ctx.notes["vectoriser_tie"] = _vectoriser_tie(
            ctx, st, min(checks["vectoriser_tie"]["rows"], n))
        harness.log(f"vectoriser tie: {tie}")
        twin = ctx.notes["moments_twin"] = _moments_twin(
            ctx, st, masks, min(checks["moments_twin"]["rows"], n),
            checks["moments_twin"]["lanes"])
        harness.log(f"moments twin: {twin}")
        reference_nulls.nulls_sweep_answer(
            st.last_best, st.streamed_fits, masks, grids, st.X, st.y,
            into=ans, fit_params={"max_iter": params["max_iter"],
                                  "tol": params["tol"]},
            reference_fold=c["reference_fold"],
            reference_rows=c["reference_rows"])
    except reference.CheckFailure as e:
        ctx.require(False, f"reference check failed: {e}")
        return
    t_tie, t_twin = checks["vectoriser_tie"], checks["moments_twin"]
    for got, tol, what in (
            (ans["metric_worst_delta"], c["tol_metric"],
             "a fold metric of the sweep, off the exact AuPR of its own "
             "coefficients"),
            (ans["kkt_worst"], c["tol_kkt"],
             "the KKT residual of the sweep's coefficients over the fold's "
             "training rows"),
            (ans["coefficients_worst"], c["tol_coefficients"],
             "the sweep's standardised coefficients, off the plain fit on "
             "the sample"),
            (ans["logloss_delta_worst"], c["tol_logloss"],
             "the sweep's held-out log-loss, off the plain fit's"),
            (tie["reference_vs_device"], 0,
             "entries of the device matrix unlike the reference's "
             "impute-and-indicate of the raw rows"),
            (tie["program_vs_device"], 0,
             "entries of the device matrix unlike the program's "
             "NumericVectorizerModel of the raw rows"),
            (tie["fills_worst_sd"], t_tie["tol_fills_sd"],
             "a fill, off the mean of its field's observed entries, in "
             "standard deviations"),
            (twin["worst"], t_twin["tol"],
             "a sum of glm_moments, off its float64 twin, of the largest")):
        ctx.require(got <= tol, f"{what}: {got:.3e} (bound {tol})")


def _vectoriser_tie(ctx, st, m: int) -> dict:
    """The first `m` raw rows, made again on the host, through the
    program's own NumericVectorizerModel with the device's fills and
    through the reference's impute-and-indicate, against the device
    matrix's rows."""
    import jax.numpy as jnp
    from transmogrifai_tpu.automl.vectorizers.numeric import \
        NumericVectorizerModel
    from transmogrifai_tpu.data.dataset import Column
    sz = ctx.sizes
    raw = datagen_nulls.raw_rows(sz["rows"], sz["raw_cols"], ctx.seed, 0, m)
    model = NumericVectorizerModel(fills=st.fills, track_nulls=True)
    program = model.transform_block(
        [Column(kind="float", data=raw[:, j]) for j in range(raw.shape[1])])
    return reference_nulls.vectoriser_tie(
        raw, st.fills, np.asarray(st.X[:m].astype(jnp.float32)), program)


def _moments_twin(ctx, st, masks, m: int, bucket: int) -> dict:
    """pallas_glm.glm_moments replayed on the first `m` rows, in the tile
    form the width takes, at the sweep's own lanes (its RAW-unit
    coefficients and intercepts, fold by fold, in a bucket of `bucket`),
    against the numpy float64 twin; beside it what the same sums
    accumulated in bfloat16 (rounded after every 1 024 rows) read. Errors
    are relative to each sum's largest entry; the worst of the four is
    held. The replay hands the kernel mean 0 and std 1, so that the
    standardised row IS the matrix's row and no operand is rounded before
    the twin sees it: with the columns' own moments a quotient one float32
    digit apart (the chip's division is not numpy's) lands a value that
    many rows share — an indicator's 0 or 1 — on the other side of a
    bfloat16 tie in all of them at once (my chip runs, PR 37, seed
    3700000311: hA 4.07e-3 = 2^-8 off, 1.1e-3 with std a power of two),
    which is the operand's rounding and not the kernel's sums. For the
    same reason the rows carry seeded weights from 0.5 to 2 and not the
    sweep's ones: a lane that the penalty shrank to its intercept gives
    EVERY row one curvature, and then curvature x value rounds alike in
    every row that shares the value (half the rows hold a field's fill):
    the same seed read hA 3.0e-4 off where eight others read 1e-6. The
    standardisation itself is held by the whole sweep's checks (`std` not
    applied reads KKT 0.021) and by the CPU tests at std 0.03 to 16."""
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import glm_sweep as GS
    from transmogrifai_tpu.ops import pallas_glm as PG
    R = reference_nulls
    X = st.X[:m]
    d, F = X.shape[1], masks.shape[0]
    Braw, b0raw = st.streamed_fits[0]
    live = F * Braw.shape[1]
    reference.require(live <= bucket,
                      f"{live} lanes in a bucket of {bucket}")
    B = np.zeros((bucket, d), np.float32)
    b0 = np.zeros(bucket, np.float32)
    B[:live], b0[:live] = Braw.reshape(live, d), b0raw.reshape(live)
    sel = np.zeros((F, bucket), np.float32)
    sel[np.repeat(np.arange(F), Braw.shape[1]), np.arange(live)] = 1.0
    Bt = jnp.asarray(B).astype(X.dtype)
    mean, std = np.zeros(d, np.float32), np.ones(d, np.float32)
    w = np.random.default_rng(ctx.seed).uniform(0.5, 2.0, m) \
        .astype(np.float32)
    y = st.y[:m]
    x_tile = GS.glm_x_tile(d)
    got = PG.glm_moments(
        X if x_tile == "cols_minor" else X.T, PG.dense_rows(y),
        PG.dense_rows(jnp.asarray(w)),
        jnp.asarray(masks[:, :m], jnp.float32),
        jnp.asarray(sel), Bt, jnp.asarray(b0), jnp.asarray(mean),
        jnp.asarray(std), loss="logistic", x_tile=x_tile,
        interpret=ctx.rehearse)
    Xh = np.asarray(X.astype(jnp.float32))
    args = (np.asarray(y), w, masks[:, :m], sel,
            np.asarray(Bt.astype(jnp.float32)), b0, mean, std)
    ref = R.moments_twin(Xh, *args)
    low = [np.zeros_like(r, dtype=np.float32) for r in ref]
    for s in range(0, m, 1024):
        part = R.moments_twin(Xh[s:s + 1024], args[0][s:s + 1024],
                              args[1][s:s + 1024], args[2][:, s:s + 1024],
                              *args[3:])
        low = [R.as_bf16(a + R.as_bf16(p)) for a, p in zip(low, part)]

    def off(vals):
        return [float(np.abs(np.asarray(v, np.float64) - r).max()
                      / np.abs(r).max()) for v, r in zip(vals, ref)]
    return {"rows": m, "cols": int(d), "lanes": bucket, "live": int(live),
            "x_tile": x_tile, "by_sum": off(got), "worst": max(off(got)),
            "bf16_accumulation": max(off(low))}
