"""The plain references of the null-tracked binary sweep: what decides
`correct` in `sweep-glm-nulls128`. Nothing here imports the program.

- `impute_indicate`, `fills_of`: `RealVectorizer` as upstream describes it
  (fillWithMean, TrackNulls): a missing entry takes the mean of its field's
  observed entries, and every field is followed by its 0/1 null indicator —
  value, indicator, value, indicator ... numpy float64.
- the standardised elastic-net logistic objective, Spark ML's as upstream's
  OpLogisticRegression wraps it:

      sum_i t_i logloss_i / sum_i t_i
        + reg * (alpha * |B|_1 + (1 - alpha) / 2 * |B|_2^2)

  over a fold's training weights t, B on the columns standardised by the
  moments of ALL rows, the intercept unpenalised. `moments`, `gradient`
  (float32 at `highest` matmul precision, the device matrix read a block of
  rows at a time: a float32 copy of it would not fit beside it),
  `kkt_residual` (how far given coefficients are from the optimum, with no
  refit) and `fit` (a plain accelerated proximal-gradient fit on a sample
  that fits as float32; its step from the Gram's top eigenvalue; no lanes,
  no buckets, no Newton step).
- `newton_replay`: the iteration the program documents for its rounds
  (ops/glm_sweep.py, `_newton_prox_update`), written again from that
  description on the same sample: a damped-Newton step on the data term and
  the ridge, a soft threshold by l1 over the Hessian's diagonal, a Newton
  step of the intercept; until max |dB| + |db0| <= tol. Its fixed point is
  NOT the elastic-net optimum where columns correlate under the curvature
  weights (the threshold takes the diagonal for the Hessian); how far it
  lies from `fit`'s optimum is reported, and what a sweep that stopped one
  iteration, or half its iterations, early would read.
- `margins`, `logloss`, the exact AuPR by full sort (`benchmark.reference.
  numpy_au_pr`).
- `moments_twin`: numpy float64 twin of `pallas_glm.glm_moments`, from its
  docstring.
- `nulls_sweep_answer`, `vectoriser_tie`: the comparisons themselves.
"""
from __future__ import annotations

import functools

import numpy as np

from benchmark.harness import log
from benchmark.reference import numpy_au_pr, require

BLOCK_ROWS = 1 << 16
FIT_ITERS = 3000
FIT_TOL = 1e-8


# -- RealVectorizer, plainly ----------------------------------------------------

def fills_of(raw) -> np.ndarray:
    """[fields] float64: the mean of each field's observed entries."""
    return np.nanmean(np.asarray(raw, np.float64), axis=0)


def impute_indicate(raw, fills) -> np.ndarray:
    """[rows, 2 fields] float64 of a raw table [rows, fields] with NaN for
    missing: field j's entries with fills[j] where missing, then 1.0 where
    it was missing and 0.0 where not."""
    raw = np.asarray(raw, np.float64)
    gone = np.isnan(raw)
    out = np.empty((raw.shape[0], 2 * raw.shape[1]), np.float64)
    out[:, 0::2] = np.where(gone, np.asarray(fills, np.float64)[None, :],
                            raw)
    out[:, 1::2] = gone
    return out


def as_bf16(a) -> np.ndarray:
    """float32 values of `a` rounded to bfloat16."""
    import jax.numpy as jnp
    return np.asarray(jnp.asarray(a, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32))


# -- the objective, in blocks over the device matrix -----------------------------

def _blocks(n: int):
    c = min(BLOCK_ROWS, n)
    return c, [(min(i, n - c), max(i - min(i, n - c), 0))
               for i in range(0, n, c)]


@functools.lru_cache(maxsize=None)
def _programs(c: int):
    """Block programs, compiled a block size: each takes the whole device
    matrix and a start row and works on rows [start, start + c) in float32;
    `skip` leading rows belong to the block before (the last block starts
    early)."""
    import jax
    import jax.numpy as jnp
    hp = jax.lax.Precision.HIGHEST

    def cut(X, start, skip, *rows):
        x = jax.lax.dynamic_slice_in_dim(X, start, c, axis=0) \
            .astype(jnp.float32)
        fresh = (jnp.arange(c) >= skip).astype(jnp.float32)
        return (x, fresh) + tuple(
            jax.lax.dynamic_slice_in_dim(r, start, c) for r in rows)

    @jax.jit
    def sums(X, start, skip):
        x, fresh = cut(X, start, skip)
        return jnp.matmul(fresh, x, precision=hp)

    @jax.jit
    def centred(X, mean, start, skip):
        x, fresh = cut(X, start, skip)
        return jnp.matmul(fresh, (x - mean) ** 2, precision=hp)

    @jax.jit
    def grad(X, y, t, mean, inv_std, B, b0, start, skip):
        """B [d, K], b0 [K]: K coefficient vectors in one read of the
        block. (sum_i r_i xs_i [K, d], sum_i r_i [K]), r = (p - y) t."""
        x, fresh, yb, tb = cut(X, start, skip, y, t)
        xs = (x - mean) * inv_std
        eta = jnp.matmul(xs, B, precision=hp) + b0
        r = (jax.nn.sigmoid(eta) - yb[:, None]) * (tb * fresh)[:, None]
        return jnp.matmul(r.T, xs, precision=hp), r.sum(0)

    @jax.jit
    def margin(X, beta, b0, start):
        x = jax.lax.dynamic_slice_in_dim(X, start, c, axis=0) \
            .astype(jnp.float32)
        return jnp.matmul(x, beta, precision=hp) + b0

    return dict(sums=sums, centred=centred, grad=grad, margin=margin)


def moments(X) -> tuple:
    """(mean [d], std [d]) float32 of all rows of the device matrix, two
    passes (population variance, floored at 1e-12)."""
    import jax.numpy as jnp
    n = X.shape[0]
    c, blocks = _blocks(n)
    P = _programs(c)
    mean = sum(P["sums"](X, st, sk) for st, sk in blocks) / n
    var = sum(P["centred"](X, mean, st, sk) for st, sk in blocks) / n
    return mean, jnp.sqrt(jnp.maximum(var, 1e-12))


def gradient(X, y, t, mean, inv_std, B, b0) -> tuple:
    """(g [K, d], g0 [K]) float64: the gradient of the data term, sum_i t_i
    logloss_i / sum_i t_i, in the standardised coefficients B [K, d] and
    the intercepts b0 [K], over all rows of the device matrix."""
    import jax.numpy as jnp
    c, blocks = _blocks(X.shape[0])
    P = _programs(c)
    yd, td = jnp.asarray(y, jnp.float32), jnp.asarray(t, jnp.float32)
    Bd = jnp.asarray(np.asarray(B, np.float32).T)
    b0d = jnp.asarray(b0, jnp.float32)
    g = g0 = 0.0
    for st, sk in blocks:
        a, b = P["grad"](X, yd, td, mean, inv_std, Bd, b0d, st, sk)
        g, g0 = g + np.asarray(a, np.float64), g0 + np.asarray(b, np.float64)
    T = float(np.asarray(t, np.float64).sum())
    return g / T, g0 / T


def kkt_residual(g, g0, B, reg: float, alpha: float) -> float:
    """The largest violation of the optimality conditions of the objective
    (module docstring) at standardised coefficients B [d] with data
    gradient g [d], g0: |g + l2 B + l1 sign(B)| where B is non-zero, what
    |g + l2 B| exceeds l1 by where it is zero, and |g0|."""
    B = np.asarray(B, np.float64)
    l1, l2 = reg * alpha, reg * (1.0 - alpha)
    s = np.asarray(g, np.float64) + l2 * B
    viol = np.where(B != 0.0, np.abs(s + l1 * np.sign(B)),
                    np.maximum(np.abs(s) - l1, 0.0))
    return float(max(viol.max(), abs(float(g0))))


def margins(X, beta, b0) -> np.ndarray:
    """float32 margins x . beta + b0 of every row of the device matrix,
    float32 products at `highest` precision, a block at a time: [n] for
    one coefficient vector beta [d], [K, n] for K of them (beta [K, d], b0
    [K]) in ONE read of the matrix."""
    import jax.numpy as jnp
    n = X.shape[0]
    c, blocks = _blocks(n)
    P = _programs(c)
    bd = jnp.asarray(np.asarray(beta, np.float32).T)
    b0d = jnp.asarray(b0, jnp.float32)
    out = np.empty((n,) + bd.shape[1:], np.float32)
    for st, _ in blocks:
        out[st:st + c] = np.asarray(P["margin"](X, bd, b0d, st))
    return out.T


def logloss(margin, y, w) -> float:
    """Weighted mean log-loss of margins, numpy float64."""
    m = np.asarray(margin, np.float64)
    yy, ww = np.asarray(y, np.float64), np.asarray(w, np.float64)
    return float(((np.logaddexp(0.0, m) - yy * m) * ww).sum() / ww.sum())


# -- fits on a sample that fits as float32 ----------------------------------------

def standardised(X, mean, inv_std, rows: int):
    """The first `rows` rows, standardised, as ONE float32 device array."""
    import jax.numpy as jnp
    return (X[:rows].astype(jnp.float32) - mean) * inv_std


@functools.lru_cache(maxsize=None)
def _fit_programs():
    import jax
    import jax.numpy as jnp
    hp = jax.lax.Precision.HIGHEST

    def data_grad(xs, y, t, T, B, b0):
        r = (jax.nn.sigmoid(jnp.matmul(xs, B, precision=hp) + b0) - y) * t
        return jnp.matmul(r, xs, precision=hp) / T, r.sum() / T

    @jax.jit
    def top_eigenvalue(xs, t, T):
        """Of [xs, 1]' diag(t) [xs, 1] / T: 64 power-iteration steps."""
        def mv(v, v0):
            u = (jnp.matmul(xs, v, precision=hp) + v0) * t
            return jnp.matmul(u, xs, precision=hp) / T, u.sum() / T

        def step(_, s):
            v, v0 = mv(*s)
            nrm = jnp.sqrt((v * v).sum() + v0 * v0)
            return v / nrm, v0 / nrm
        d = xs.shape[1]
        v, v0 = jax.lax.fori_loop(
            0, 64, step, (jnp.full((d,), (d + 1) ** -0.5), (d + 1) ** -0.5))
        u, u0 = mv(v, v0)
        return (u * v).sum() + u0 * v0

    @jax.jit
    def fista(xs, y, t, l1, l2, lam):
        """Accelerated proximal gradient from zero, step 1 / (lam / 4 +
        l2); the intercept is one more, unpenalised, coordinate."""
        T = t.sum()
        step = 1.0 / (0.25 * lam + l2)

        def cond(s):
            return (s[0] < FIT_ITERS) & (s[-1] > FIT_TOL)

        def body(s):
            i, B, b0, V, v0, th, _ = s
            g, g0 = data_grad(xs, y, t, T, V, v0)
            u = V - step * (g + l2 * V)
            Bn = jnp.sign(u) * jnp.maximum(jnp.abs(u) - step * l1, 0.0)
            b0n = v0 - step * g0
            thn = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * th * th))
            m = (th - 1.0) / thn
            delta = jnp.abs(Bn - B).max() + jnp.abs(b0n - b0)
            return (i + 1, Bn, b0n, Bn + m * (Bn - B), b0n + m * (b0n - b0),
                    thn, delta)
        z, f = jnp.zeros(xs.shape[1], jnp.float32), jnp.float32
        out = jax.lax.while_loop(cond, body, (0, z, f(0), z, f(0), f(1),
                                              f(jnp.inf)))
        return out[1], out[2], out[0]

    @jax.jit
    def newton_step(xs, y, t, l1, l2, B, b0):
        """One iteration as the program documents it."""
        T = t.sum()
        p = jax.nn.sigmoid(jnp.matmul(xs, B, precision=hp) + b0)
        r, s = (p - y) * t, jnp.maximum(p * (1.0 - p), 1e-6) * t
        g = jnp.matmul(r, xs, precision=hp) / T + l2 * B
        H = jnp.matmul((xs * s[:, None]).T, xs, precision=hp) / T \
            + (l2 + 1e-6) * jnp.eye(xs.shape[1], dtype=jnp.float32)
        Bn = B - jnp.linalg.solve(H, g)
        Bn = jnp.sign(Bn) * jnp.maximum(
            jnp.abs(Bn) - l1 / jnp.maximum(jnp.diagonal(H), 1e-12), 0.0)
        b0n = b0 - (r.sum() / T) / jnp.maximum(s.sum() / T, 1e-12)
        return Bn, b0n, jnp.abs(Bn - B).max() + jnp.abs(b0n - b0)

    return dict(top=top_eigenvalue, fista=fista, newton=newton_step)


def fit(xs, y, t, reg: float, alpha: float) -> dict:
    """The optimum of the objective over the standardised float32 sample
    `xs` [m, d] under training weights t [m]: standardised `B` [d], `b0`,
    `iters`."""
    import jax.numpy as jnp
    P = _fit_programs()
    yd, td = jnp.asarray(y, jnp.float32), jnp.asarray(t, jnp.float32)
    lam = P["top"](xs, td, td.sum())
    B, b0, it = P["fista"](xs, yd, td, jnp.float32(reg * alpha),
                           jnp.float32(reg * (1.0 - alpha)), lam)
    return {"B": np.asarray(B, np.float64), "b0": float(b0),
            "iters": int(it)}


def newton_replay(xs, y, t, reg: float, alpha: float, *, max_iter: int,
                  tol: float) -> dict:
    """The documented rounds' iteration from zero on the sample: every
    iterate (`B` [k, d], `b0` [k]) and its `deltas` [k], stopped as the
    program stops (the first delta <= tol, or max_iter)."""
    import jax
    import jax.numpy as jnp
    P = _fit_programs()
    yd, td = jnp.asarray(y, jnp.float32), jnp.asarray(t, jnp.float32)
    B, b0 = jnp.zeros(xs.shape[1], jnp.float32), jnp.float32(0.0)
    Bs, b0s, deltas = [], [], []
    with jax.default_matmul_precision("highest"):
        for _ in range(max_iter):
            B, b0, delta = P["newton"](
                xs, yd, td, jnp.float32(reg * alpha),
                jnp.float32(reg * (1.0 - alpha)), B, b0)
            Bs.append(np.asarray(B, np.float64))
            b0s.append(float(b0))
            deltas.append(float(delta))
            if deltas[-1] <= tol:
                break
    return {"B": np.stack(Bs), "b0": np.asarray(b0s), "deltas": deltas}


# -- the kernel's twin --------------------------------------------------------------

def moments_twin(X, y, w, fold_masks, sel, Bt, b0, mean, std) -> tuple:
    """numpy float64, from `ops/pallas_glm.py`'s docstrings: (gA [lanes,
    d], hA [lanes, d, d], g0A [lanes], h0A [lanes]), the sums over the rows
    of R xs', S xs xs', R and S — xs the standardised row, R and S the
    logistic residual p - y and curvature max(p (1 - p), 1e-6) at xs' B +
    b0 times the lane's fold weight (fold_masks' w) sel. The matrix unit's
    operands are rounded to the matrix's dtype (bfloat16) as the module
    states: xs, R, and a lane's weighted block S xs (hA[lane, i, j] sums
    rounded(S xs_i) xs_j); the intercept's sums take R and S unrounded.
    Every sum is float64 here and float32 there."""
    f64 = np.float64
    x32 = np.asarray(X, np.float32)
    xs = as_bf16((x32 - np.asarray(mean, np.float32)[None, :])
                 / np.asarray(std, np.float32)[None, :]).astype(f64)
    eta = xs @ np.asarray(Bt, f64).T + np.asarray(b0, f64)[None, :]
    p = 1.0 / (1.0 + np.exp(-eta))
    wl = (np.asarray(fold_masks, f64).T * np.asarray(w, f64)[:, None]) \
        @ np.asarray(sel, f64)
    R = (p - np.asarray(y, f64)[:, None]) * wl
    S = np.maximum(p * (1.0 - p), 1e-6) * wl
    hA = np.stack([as_bf16(xs * S[:, k:k + 1]).astype(f64).T @ xs
                   for k in range(S.shape[1])])
    return as_bf16(R).astype(f64).T @ xs, hA, R.sum(0), S.sum(0)


# -- the comparisons ----------------------------------------------------------------

def vectoriser_tie(raw, fills, device_rows, program_rows) -> dict:
    """Raw rows (float64, NaN for missing) through this file's
    impute-and-indicate with the device's `fills`, against the same rows of
    the device matrix (`device_rows`, float32 values of its dtype) and
    against what the program's own vectoriser made of them
    (`program_rows`): entries that differ after the bfloat16 cast, which
    must be none. Beside them, what filling with 0 would differ by, and
    how far the device's fills (means over ALL rows) lie from the float64
    mean of the observed entries of the rows given, in standard deviations
    of the field (the reading includes the sample's own error)."""
    ref = as_bf16(impute_indicate(raw, fills))
    zero = as_bf16(impute_indicate(raw, np.zeros_like(fills)))
    dev = np.asarray(device_rows, np.float32)
    prog = as_bf16(program_rows)
    seen = fills_of(raw)
    spread = np.nanstd(np.asarray(raw, np.float64), axis=0)
    return {"rows": int(dev.shape[0]),
            "reference_vs_device": int((ref != dev).sum()),
            "program_vs_device": int((prog != dev).sum()),
            "fill_zero_vs_device": int((zero != dev).sum()),
            "fills_worst_sd": float((np.abs(fills - seen) / spread).max()),
            "fill_zero_worst_sd": float((np.abs(seen) / spread).min())}


def nulls_sweep_answer(best, fits, masks, grids, X, y, *, fit_params: dict,
                       reference_fold: int, reference_rows: int,
                       into: dict) -> dict:
    """The readings of the null-tracked LR sweep that ran, before any
    bound (the driver applies the cell file's): for the best grid point
    and EVERY fold, the exact AuPR of the coefficients the sweep's fit
    handed to its metric pass against the fold metric it reported; for
    `reference_fold`, the best and the least-regularised point: the KKT
    residual over ALL its training rows, and coefficients and held-out
    log-loss against `fit` on the first `reference_rows` rows. Beside
    each, what the named wrong builds read."""
    import jax.numpy as jnp
    lr = [v for v in best.validated if v.route == "streamed"]
    require(bool(lr) and len(fits) == 1,
            f"{len(lr)} streamed grid points, {len(fits)} streamed fits "
            f"seen: the sweep's coefficients cannot be read")
    top = max(lr, key=lambda v: v.mean_metric)
    j_top = grids.index(dict(top.grid))
    j_low = min(range(len(grids)), key=lambda j: (
        grids[j]["reg_param"], grids[j]["elastic_net_param"]))
    Braw, b0raw = fits[0]
    n, d = X.shape
    F = masks.shape[0]
    require(Braw.shape == (F, Braw.shape[1], d)
            and Braw.shape[1] >= len(grids)
            and b0raw.shape == Braw.shape[:2],
            f"fold coefficients of shape {Braw.shape}, intercepts "
            f"{b0raw.shape}")
    yh = np.asarray(y)
    value_cols = np.arange(d) % 2 == 0
    out = into
    out.update(grid=dict(top.grid), least_regularised=dict(grids[j_low]),
               folds=[])
    worst = worst_low = worst_noind = 0.0
    for f in range(F):
        held = masks[f] == 0
        yf, ones = yh[held], np.ones(int(held.sum()), np.float32)
        # the sweep's coefficients; the nearest precision below float32
        # ones; a metric pass that left the indicator columns out
        beta = Braw[f, j_top]
        exact, low, noind = (
            numpy_au_pr(m[held], yf, ones) for m in margins(
                X, np.stack([beta, as_bf16(beta), beta * value_cols]),
                np.full(3, b0raw[f, j_top])))
        got = float(top.fold_metrics[f])
        out["folds"].append({"sweep": got, "exact": exact,
                             "bf16_coefficients": low,
                             "indicators_left_out": noind})
        worst = max(worst, abs(got - exact))
        worst_low = max(worst_low, abs(low - exact))
        worst_noind = max(worst_noind, abs(noind - exact))
    out.update(metric_worst_delta=worst,
               bf16_coefficients_metric_delta=worst_low,
               indicators_left_out_metric_delta=worst_noind)
    log(f"nulls answer: sweep AuPR vs exact worst {worst:.2e} (bfloat16 "
        f"coefficients {worst_low:.2e}, indicators left out "
        f"{worst_noind:.2e})")

    f = reference_fold
    t, held = masks[f], 1.0 - masks[f]
    mean, std = moments(X)
    inv_std = 1.0 / std
    mean_h, std_h = (np.asarray(v, np.float64) for v in (mean, std))

    def standard(beta, b0_):
        """Raw-unit coefficients on the standardised scale."""
        beta = np.asarray(beta, np.float64)
        return beta * std_h, float(b0_) + float((beta * mean_h).sum())
    m = min(reference_rows, n)
    xs = standardised(X, mean, inv_std, m)
    points = {}
    for name, j in (("best", j_top), ("least_regularised", j_low)):
        if name != "best" and j == j_top:
            points[name] = points["best"]   # one point is both
            continue
        reg = float(grids[j]["reg_param"])
        alpha = float(grids[j]["elastic_net_param"])
        Bs, b0s = standard(Braw[f, j], b0raw[f, j])
        plain = fit(xs, yh[:m], t[:m], reg, alpha)
        replay = newton_replay(xs, yh[:m], t[:m], reg, alpha,
                               max_iter=fit_params["max_iter"],
                               tol=fit_params["tol"])
        # wrong builds, reference side: the same sample fitted without the
        # indicator columns, and with `std` not applied (the penalty on the
        # centred raw columns), each on the standardised scale
        noind = fit(xs * jnp.asarray(value_cols, jnp.float32), yh[:m],
                    t[:m], reg, alpha)
        nostd = fit(xs * std, yh[:m], t[:m], reg, alpha)
        def stopped_at(k):
            """The sweep's coefficients moved back by what the replay's
            iterate k lies before its last."""
            return (Bs + replay["B"][k] - replay["B"][-1],
                    b0s + replay["b0"][k] - replay["b0"][-1])
        done = len(replay["deltas"])
        cand = {"sweep": (Bs, b0s),
                "indicators_left_out": (noind["B"] * value_cols,
                                        noind["b0"]),
                "std_not_applied": (nostd["B"] * std_h, nostd["b0"]),
                "one_newton_iteration_fewer": stopped_at(max(done - 2, 0)),
                "half_the_newton_iterations": stopped_at((done - 1) // 2)}
        Bc = np.stack([c[0] for c in cand.values()] + [plain["B"]])
        b0c = np.asarray([c[1] for c in cand.values()] + [plain["b0"]])
        g, g0 = gradient(X, yh, t, mean, inv_std, Bc, b0c)
        # every candidate's held-out log-loss, and the plain fit's (last),
        # in one read of the matrix: raw-unit coefficients
        ll = [logloss(m, yh, held) for m in margins(
            X, Bc / std_h, b0c - (Bc / std_h * mean_h).sum(1))]
        reads = {name_: {
            "kkt": kkt_residual(g[k], g0[k], Bc[k], reg, alpha),
            "coefficients": float(max(np.abs(Bc[k] - plain["B"]).max(),
                                      abs(b0c[k] - plain["b0"]))),
            "logloss_delta": abs(ll[k] - ll[-1])}
            for k, name_ in enumerate(cand)}
        points[name] = {
            "grid": dict(grids[j]), **reads.pop("sweep"), "wrong": reads,
            "plain_iters": plain["iters"],
            "newton_replay_deltas": replay["deltas"],
            "newton_fixed_point_vs_optimum": float(max(
                np.abs(replay["B"][-1] - plain["B"]).max(),
                abs(replay["b0"][-1] - plain["b0"])))}
        log(f"nulls answer, fold {f}, {name} {grids[j]}: {points[name]}")
    out.update(reference_fold=f, reference_rows=int(m), points=points)
    for key in ("kkt", "coefficients", "logloss_delta"):
        out[key + "_worst"] = max(p[key] for p in points.values())
    return out
