"""The plain references of the wide binary sweep: what decides `correct` in
`sweep-glm-wide4k`. Nothing here imports the program.

- `fit`: binary logistic regression of ONE fold and ONE grid point in
  float32 at `highest` matmul precision, the matrix read a block of rows at
  a time (a float32 copy of it would not fit beside it), no lanes, no
  buckets, no retirement. The objective is Spark ML's, as upstream's
  OpLogisticRegression wraps it:

      sum_i t_i logloss_i / sum_i t_i
        + reg * (alpha * |B|_1 + (1 - alpha) / 2 * |B|_2^2)

  over the fold's training weights t = mask * w, B penalised on the
  standardised scale, the intercept unpenalised, coefficients returned in
  raw units. The solver is the one the program documents
  (ops/glm_sweep.py, "streamed wide route"), written again from that
  description. Columns are centred and scaled by the moments of ALL rows
  (weights w). Boehning's bound p (1 - p) <= 1/4, and training rows being a
  subset of all rows, bound every lane's Hessian by kappa [[Gs, 0], [0, W]],
  kappa = 1 / (4 sum t), Gs = sum_i w_i xs_i xs_i' the all-rows Gram of the
  standardised columns, W = sum w. One iteration: the exact gradient g of
  the data term (one pass over the rows); INNER_STEPS FISTA steps from
  z = v = B, theta = 1 on g'(z - B) + kappa/2 (z - B)' Gs (z - B)
  + l2/2 |z|^2 + l1 |z|_1 with step 1 / (kappa lam + l2), lam = LAM_MARGIN x
  the Rayleigh quotient after POWER_ITERS power-iteration steps on Gs from
  the constant vector; b0 <- b0 - 4 sum r / W. From zero, until max |dB| +
  |db0| <= tol or max_iter iterations are done. The departure from upstream
  is the solver (Spark runs L-BFGS / OWL-QN): the prox is exact, so the
  fixed point is the elastic-net optimum, but at max_iter 50 the iterate
  is still on its way there, and WHICH iterate a sweep reports is part of
  its answer. tests/benchmark/test_benchmark_wide.py holds this file to a
  numpy loop and its long-run limit to the optimality conditions.
- `margins`: float32 margins of given coefficients over a device matrix.
- `objective`: the penalised objective above, of given raw coefficients.
- `gram_twin`: numpy float64 twin of the program's Gram step.
- `wide_sweep_answer`: the comparisons themselves.
"""
from __future__ import annotations

import functools

import numpy as np

from benchmark.harness import log
from benchmark.reference import numpy_au_pr, require

INNER_STEPS = 16
POWER_ITERS = 32
LAM_MARGIN = 1.05
BLOCK_ROWS = 1 << 14


def _blocks(n: int):
    c = min(BLOCK_ROWS, n)
    return c, [min(i, n - c) for i in range(0, n, c)], \
        [max(i - min(i, n - c), 0) for i in range(0, n, c)]


@functools.lru_cache(maxsize=None)
def _programs(c: int):
    """The block programs, compiled a block size: every one takes the whole
    device matrix and a start row, and works on rows [start, start + c) in
    float32 (`skip` leading rows of the block belong to the block before:
    the last block starts early)."""
    import jax
    import jax.numpy as jnp
    hp = jax.lax.Precision.HIGHEST

    def cut(X, start, skip, *rows):
        x = jax.lax.dynamic_slice_in_dim(X, start, c, axis=0) \
            .astype(jnp.float32)
        fresh = (jnp.arange(c) >= skip).astype(jnp.float32)
        return (x, fresh) + tuple(
            jax.lax.dynamic_slice_in_dim(jnp.asarray(r, jnp.float32),
                                         start, c) for r in rows)

    @jax.jit
    def sums(X, w, start, skip):
        x, fresh, wb = cut(X, start, skip, w)
        wb = wb * fresh
        return jnp.matmul(wb, x, precision=hp), wb.sum()

    @jax.jit
    def centred(X, w, mean, start, skip):
        x, fresh, wb = cut(X, start, skip, w)
        return jnp.matmul(wb * fresh, (x - mean) ** 2, precision=hp)

    @jax.jit
    def gram(X, w, mean, inv_std, start, skip):
        x, fresh, wb = cut(X, start, skip, w)
        xs = (x - mean) * inv_std
        return jnp.matmul((xs * (wb * fresh)[:, None]).T, xs, precision=hp)

    @jax.jit
    def grad(X, y, t, mean, inv_std, B, b0, start, skip):
        x, fresh, yb, tb = cut(X, start, skip, y, t)
        xs = (x - mean) * inv_std
        eta = jnp.matmul(xs, B, precision=hp) + b0
        r = (jax.nn.sigmoid(eta) - yb) * tb * fresh
        return jnp.matmul(r, xs, precision=hp), r.sum()

    @jax.jit
    def margin(X, beta, b0, start):
        x = jax.lax.dynamic_slice_in_dim(X, start, c, axis=0) \
            .astype(jnp.float32)
        return jnp.matmul(x, beta, precision=hp) + b0

    @jax.jit
    def lam_of(Gs):
        def power(_, v):
            u = jnp.matmul(Gs, v, precision=hp)
            return u / jnp.maximum(jnp.linalg.norm(u), 1e-12)
        d = Gs.shape[0]
        v = jax.lax.fori_loop(0, POWER_ITERS, power,
                              jnp.full((d,), d ** -0.5, jnp.float32))
        return LAM_MARGIN * jnp.vdot(v, jnp.matmul(Gs, v, precision=hp))

    @jax.jit
    def inner(g, B, Gs, kappa, l1, l2, step):
        def one(_, s):
            z, v, th = s
            gr = g + kappa * jnp.matmul(Gs, v - B, precision=hp) + l2 * v
            u = v - step * gr
            zn = jnp.sign(u) * jnp.maximum(jnp.abs(u) - step * l1, 0.0)
            thn = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * th * th))
            return zn, zn + ((th - 1.0) / thn) * (zn - z), thn
        return jax.lax.fori_loop(0, INNER_STEPS, one,
                                 (B, B, jnp.float32(1.0)))[0]

    return dict(sums=sums, centred=centred, gram=gram, grad=grad,
                margin=margin, lam_of=lam_of, inner=inner)


def moments(X, w) -> tuple:
    """(mean [d], std [d], W) float32 of the rows under weights w, two
    passes."""
    import jax.numpy as jnp
    n = X.shape[0]
    c, starts, skips = _blocks(n)
    P = _programs(c)
    wd = jnp.asarray(w, jnp.float32)
    s, W = 0.0, 0.0
    for st, sk in zip(starts, skips):
        a, b = P["sums"](X, wd, st, sk)
        s, W = s + a, W + b
    mean = s / W
    v = 0.0
    for st, sk in zip(starts, skips):
        v = v + P["centred"](X, wd, mean, st, sk)
    return mean, jnp.sqrt(jnp.maximum(v / W, 1e-12)), W


def fit(X, y, w, t, reg: float, alpha: float, *, max_iter: int, tol: float,
        fit_intercept: bool = True, standardize: bool = True,
        iterations=None) -> dict:
    """The documented iteration (module docstring) for training weights `t`
    (mask * w) inside all-rows weights `w`. X [n, d] is a device matrix of
    any float dtype. Returns raw-unit `beta` [d], `b0`, `iters`, and the
    standardised-space state (`B`, `mean`, `inv_std`). `iterations` stops
    after exactly that many (for the one-step-fewer reading)."""
    import jax.numpy as jnp
    n, d = X.shape
    c, starts, skips = _blocks(n)
    P = _programs(c)
    yd, wd, td = (jnp.asarray(a, jnp.float32) for a in (y, w, t))
    f32 = jnp.float32
    mean, inv_std = jnp.zeros(d, f32), jnp.ones(d, f32)
    if standardize or fit_intercept:
        mean, std, _ = moments(X, wd)
        if standardize:
            inv_std = 1.0 / std
    W, T = wd.sum(), td.sum()
    Gs = 0.0
    for st, sk in zip(starts, skips):
        Gs = Gs + P["gram"](X, wd, mean, inv_std, st, sk)
    Gs = 0.5 * (Gs + Gs.T)
    lam = P["lam_of"](Gs)
    l1, l2 = f32(reg * alpha), f32(reg * (1.0 - alpha))
    kappa = 0.25 / T
    step = 1.0 / (kappa * lam + l2)
    B, b0, it = jnp.zeros(d, f32), f32(0.0), 0
    stop = max_iter if iterations is None else iterations
    while it < stop:
        g, g0 = 0.0, 0.0
        for st, sk in zip(starts, skips):
            a, b = P["grad"](X, yd, td, mean, inv_std, B, b0, st, sk)
            g, g0 = g + a, g0 + b
        Bn = P["inner"](g / T, B, Gs, kappa, l1, l2, step)
        b0n = b0 - 4.0 * g0 / W if fit_intercept else b0
        delta = float(jnp.abs(Bn - B).max() + jnp.abs(b0n - b0))
        B, b0, it = Bn, b0n, it + 1
        if iterations is None and delta <= tol:
            break
    beta = B * inv_std
    return {"beta": np.asarray(beta),
            "b0": float(b0 - (beta * mean).sum()), "iters": it,
            "B": np.asarray(B), "mean": np.asarray(mean),
            "inv_std": np.asarray(inv_std)}


def margins(X, beta, b0) -> np.ndarray:
    """[n] float32 margins x . beta + b0 of every row of the device matrix:
    float32 products at `highest` precision, a block of rows at a time."""
    import jax.numpy as jnp
    n = X.shape[0]
    c, starts, _ = _blocks(n)
    P = _programs(c)
    bd = jnp.asarray(beta, jnp.float32)
    out = np.empty(n, np.float32)
    for st in starts:
        out[st:st + c] = np.asarray(P["margin"](X, bd, jnp.float32(b0), st))
    return out


def logloss(margin, y, w) -> float:
    """Weighted mean log-loss of margins, numpy float64."""
    m = np.asarray(margin, np.float64)
    yy, ww = np.asarray(y, np.float64), np.asarray(w, np.float64)
    return float(((np.logaddexp(0.0, m) - yy * m) * ww).sum() / ww.sum())


def objective(margin, y, t, beta, inv_std, reg: float, alpha: float) -> float:
    """The penalised training objective of raw coefficients `beta` whose
    margins are `margin`: log-loss over weights t plus the elastic-net
    penalty of the standardised coefficients beta / inv_std."""
    Bs = np.asarray(beta, np.float64) / np.asarray(inv_std, np.float64)
    return logloss(margin, y, t) + reg * (
        alpha * np.abs(Bs).sum() + 0.5 * (1.0 - alpha) * (Bs * Bs).sum())


def gram_twin(X, w, mean, inv_std) -> np.ndarray:
    """numpy float64: Gs = D^-1 (X' diag(w) X - W mean mean') D^-1 of host
    rows X [m, d]."""
    X = np.asarray(X, np.float64)
    w = np.asarray(w, np.float64)
    mean = np.asarray(mean, np.float64)
    D = np.asarray(inv_std, np.float64)
    G = (X * w[:, None]).T @ X - w.sum() * np.outer(mean, mean)
    return G * D[:, None] * D[None, :]


def _as_bf16(a) -> np.ndarray:
    import jax.numpy as jnp
    return np.asarray(jnp.asarray(a, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32))


def wide_sweep_answer(best, fits, masks, grids, X, y, *, fit_params: dict,
                      reference_fold: int, tol_metric: float,
                      tol_coefficients: float, tol_logloss: float,
                      tol_objective: float, into: dict = None) -> dict:
    """Hold the wide LR sweep that ran to its own answer. For the best grid
    point and EVERY fold: the exact AuPR (float32 margins at `highest`
    precision, full sort, all held-out rows) of the coefficients the sweep's
    own fit handed to its metric pass, against the fold metric it reported.
    For `reference_fold`: `fit` at the same grid point on the same rows,
    against the sweep's coefficients (largest difference over the
    STANDARDISED coefficients and the intercept), their held-out log-loss
    and their training objective. Beside each, what a wrong computation
    would have read, for the tolerances' second readings. `into`, when
    given, receives the readings before any bound is applied, so a run
    that fails still reports them."""
    lr = [v for v in best.validated if v.route == "streamed"]
    require(bool(lr) and len(fits) == 1,
            f"{len(lr)} streamed grid points, {len(fits)} streamed fits "
            f"seen: the sweep's coefficients cannot be read")
    top = max(lr, key=lambda v: v.mean_metric)
    j = grids.index(dict(top.grid))
    B, b0 = fits[0]
    n, d = X.shape
    F = masks.shape[0]
    require(B.shape == (F, B.shape[1], d) and B.shape[1] >= len(grids)
            and b0.shape == B.shape[:2],
            f"fold coefficients of shape {B.shape}, intercepts {b0.shape}")
    yh = np.asarray(y)
    ones = np.ones(n, np.float32)
    reg = float(top.grid["reg_param"])
    alpha = float(top.grid["elastic_net_param"])
    out = {"grid": dict(top.grid), "folds": []}
    worst = worst_low = 0.0
    for f in range(F):
        held = 1.0 - masks[f]
        m = margins(X, B[f, j], b0[f, j])
        exact = numpy_au_pr(m, yh, held)
        got = float(top.fold_metrics[f])
        # the nearest precision below float32 coefficients: the same rows
        # scored with the coefficients rounded to bfloat16
        low = numpy_au_pr(margins(X, _as_bf16(B[f, j]), b0[f, j]), yh, held)
        out["folds"].append({"sweep": got, "exact": exact,
                             "bf16_coefficients": low})
        worst = max(worst, abs(got - exact))
        worst_low = max(worst_low, abs(low - exact))
        if f != reference_fold:
            continue
        ref = fit(X, yh, ones, masks[f], reg, alpha, **fit_params)
        scale = 1.0 / ref["inv_std"]

        def against(beta, b0_):
            """(standardised-coefficient distance, held-out log-loss,
            training objective) of raw coefficients, measured like the
            sweep's."""
            mm = margins(X, beta, b0_)
            return (float(max(np.abs((beta - B[f, j]) * scale).max(),
                              abs(b0_ - b0[f, j]))),
                    logloss(mm, yh, held),
                    objective(mm, yh, masks[f], beta, ref["inv_std"], reg,
                              alpha))
        _, ll_sweep, obj_sweep = against(B[f, j], float(b0[f, j]))
        d_ref, ll_ref, obj_ref = against(ref["beta"], ref["b0"])
        out.update(reference_fold=f, reference_iters=ref["iters"],
                   coefficient_delta=d_ref,
                   logloss_sweep=ll_sweep, logloss_reference=ll_ref,
                   logloss_delta=abs(ll_sweep - ll_ref),
                   objective_sweep=obj_sweep, objective_reference=obj_ref,
                   objective_delta=abs(obj_sweep - obj_ref))
        # second readings: what each named wrong computation reads in the
        # same three comparisons (reference side, so the sweep is not rerun)
        wrong = {}
        fewer = fit(X, yh, ones, masks[f], reg, alpha,
                    iterations=ref["iters"] - 1, **fit_params)
        wrong["one_iteration_fewer"] = against(fewer["beta"], fewer["b0"])
        raw = fit(X, yh, ones, masks[f], reg, alpha,
                  **dict(fit_params, standardize=False))
        wrong["standardisation_dropped"] = against(raw["beta"], raw["b0"])
        wrong["bf16_coefficients"] = against(_as_bf16(B[f, j]),
                                             float(b0[f, j]))
        out["wrong"] = {k: {"coefficients": v[0],
                            "logloss_delta": abs(v[1] - ll_ref),
                            "objective_delta": abs(v[2] - obj_ref)}
                        for k, v in wrong.items()}
    out["metric_worst_delta"] = worst
    out["bf16_coefficients_metric_delta"] = worst_low
    if into is not None:
        into.update(out)
    log(f"wide answer: sweep AuPR vs exact worst {worst:.2e} (bfloat16 "
        f"coefficients would move it by {worst_low:.2e}); sweep vs reference "
        f"fit ({out['reference_iters']} iterations): coefficients "
        f"{out['coefficient_delta']:.2e}, held-out log-loss "
        f"{out['logloss_delta']:.2e}, objective "
        f"{out['objective_delta']:.2e}; wrong computations: {out['wrong']}")
    require(worst <= tol_metric,
            f"a fold metric of the sweep is {worst:.2e} off the exact AuPR "
            f"of its own coefficients (bound {tol_metric})")
    require(out["coefficient_delta"] <= tol_coefficients,
            f"the sweep's coefficients are {out['coefficient_delta']:.2e} "
            f"off the plain reference fit (bound {tol_coefficients})")
    require(out["logloss_delta"] <= tol_logloss,
            f"the sweep's coefficients score {out['logloss_delta']:.2e} off "
            f"the plain reference fit in held-out log-loss (bound "
            f"{tol_logloss})")
    require(out["objective_delta"] <= tol_objective,
            f"the sweep's coefficients reach an objective "
            f"{out['objective_delta']:.2e} off the plain reference fit's "
            f"(bound {tol_objective})")
    return out
