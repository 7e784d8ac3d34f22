"""The plain references of the regression sweep: what decides `correct` in
`sweep-linreg-nulls128`. Nothing here imports the program.

The objective is the one `ops/glm.py` documents for `OpLinearRegression`:
over a fold's training weights t,

    (1 / 2 sum t) sum_i t_i (y_i - xs_i . B - b)^2
        + reg * (alpha * |B|_1 + (1 - alpha) / 2 * |B|_2^2)

B on the columns standardised by the moments of ALL rows, the intercept b
unpenalised. Departures from Spark's `LinearRegression`, which upstream's
OpLinearRegression wraps: Spark also divides the label by its deviation
before it applies `regParam` (so its penalty is relative to the label's
scale; here `reg` is absolute, on the label's own scale), standardises with
the unbiased (n - 1) deviation (here the population's) and solves by
OWL-QN / normal equations (here the documented iteration below).

The squared loss is a function of second moments alone, so everything past
one read of the matrix is a [d + 1, d + 1] problem on the host in numpy
float64:

- `fold_moments`: sum t xs xs', sum t xs, sum t y xs, sum t y, sum t over
  all rows of the device matrix, a block of rows at a time: float32
  products at `highest` matmul precision inside a block of 8 192 rows, the
  blocks added up in float64 on the host (a float64 copy of the matrix
  would not fit beside it; thousands of independent float32 block sums add
  up to well under float32's own error). `rounded` rounds the standardised
  block to bfloat16 first: the wrong build whose matrix unit saw
  once-rounded operands.
- `ridge`, `soft`, `kkt_residual`, `fista` (a plain accelerated proximal
  gradient fit in moment space: the optimum, by another algorithm than the
  program's), and `replay`: the iteration the program documents
  (`ops/glm.prox_newton_gram`), written again from that description — the
  ridge closed form of the same l2 as the seed (intercept eliminated, a
  1e-6 jitter), then beta <- soft(beta - H^-1 g, l1 / diag H) with H = G /
  sum t + (l2 + 1e-6) I, g the gradient at (beta, b), and b <- b - g0 with
  g0 the intercept's gradient at the OLD beta; until max |d beta| + |d b|
  <= tol or max_iter. Its fixed point is NOT the elastic-net optimum where
  columns correlate (the threshold takes H's diagonal for H); `linreg_
  answer` reports how far it lies from `fista`'s.
- `residual_sums`, `metrics_from_sums`: the exact weighted RMSE / MSE /
  MAE / R2 of given coefficients over given rows: float32 `highest`
  predictions, float64 residuals' sums.
- `moments_twin`: the numpy float64 twin of the program's per-fold moments.
- `linreg_answer`: the comparisons themselves, every reading before any
  bound (the driver applies the cell file's).
"""
from __future__ import annotations

import functools

import numpy as np

from benchmark.harness import log
from benchmark.reference import require
from benchmark.reference_nulls import as_bf16
from benchmark.reference_nulls import moments as column_moments

BLOCK_ROWS = 1 << 13
FIT_ITERS = 20000
FIT_TOL = 1e-13


def _blocks(n: int):
    """(block size, [(start, rows of the block that the one before already
    had)]): the last block starts early."""
    c = min(BLOCK_ROWS, n)
    return c, [(min(i, n - c), max(i - min(i, n - c), 0))
               for i in range(0, n, c)]


@functools.lru_cache(maxsize=None)
def _programs(c: int):
    import jax
    import jax.numpy as jnp
    hp = jax.lax.Precision.HIGHEST
    f32 = jnp.float32

    def cut(X, start, skip, *rows):
        x = jax.lax.dynamic_slice_in_dim(X, start, c, axis=0).astype(f32)
        fresh = (jnp.arange(c) >= skip).astype(f32)
        return (x, fresh) + tuple(
            jax.lax.dynamic_slice_in_dim(r, start, c) for r in rows)

    @functools.partial(jax.jit, static_argnames=("rounded",))
    def moments(X, y, t, mean, inv_std, start, skip, rounded=False):
        """[d + 1, d + 2]: [xs, 1]' diag(t) [xs, 1, y] of the block."""
        x, fresh, yb, tb = cut(X, start, skip, y, t)
        xs = (x - mean) * inv_std
        if rounded:
            # (a float32 -> bfloat16 -> float32 round trip inside a fusion
            # comes back unrounded on the chip: PERF.md, PR 29)
            xs = jax.lax.reduce_precision(xs, exponent_bits=8,
                                          mantissa_bits=7)
        one = jnp.ones((c, 1), f32)
        left = jnp.concatenate([xs, one], axis=1) * (tb * fresh)[:, None]
        right = jnp.concatenate([xs, one, yb[:, None]], axis=1)
        return jnp.matmul(left.T, right, precision=hp)

    @jax.jit
    def sums(X, y, v, beta, b0, pivot, start, skip):
        """beta [d, K], b0 [K]: K coefficient vectors in one read of the
        block: (sum v r^2 [K], sum v |r| [K], [sum v, sum v (y - pivot),
        sum v (y - pivot)^2]), r = x . beta + b0 - y."""
        x, fresh, yb, vb = cut(X, start, skip, y, v)
        vb = vb * fresh
        r = jnp.matmul(x, beta, precision=hp) + b0 - yb[:, None]
        yc = yb - pivot
        return ((r * r * vb[:, None]).sum(0), (jnp.abs(r) * vb[:, None])
                .sum(0), jnp.stack([vb.sum(), (vb * yc).sum(),
                                    (vb * yc * yc).sum()]))

    return dict(moments=moments, sums=sums)


def _sum_blocks(results, ahead: int = 64) -> list:
    """The float64 sums, leaf by leaf, of an iterator of per-block device
    results (tuples of arrays): dispatched `ahead` blocks before the first
    is fetched."""
    total, pending = None, []

    def drain():
        nonlocal total
        for res in pending:
            vals = [np.asarray(v, np.float64) for v in res]
            total = vals if total is None else [
                t + v for t, v in zip(total, vals)]
        pending.clear()
    for res in results:
        pending.append(res)
        if len(pending) == ahead:
            drain()
    drain()
    return total


# -- the sufficient statistics -------------------------------------------------

def fold_moments(X, y, t, mean, inv_std, rows=None, rounded=False) -> dict:
    """float64 `G` [d, d] = sum t xs xs', `sx` [d] = sum t xs, `c` [d] =
    sum t y xs, `sy`, `sw` over the first `rows` rows (default: all) of the
    device matrix under weights t."""
    import jax.numpy as jnp
    n = X.shape[0] if rows is None else min(int(rows), X.shape[0])
    c, blocks = _blocks(n)
    P = _programs(c)
    yd, td = jnp.asarray(y, jnp.float32), jnp.asarray(t, jnp.float32)
    d = X.shape[1]
    total, = _sum_blocks(
        (P["moments"](X, yd, td, mean, inv_std, st, sk, rounded=rounded),)
        for st, sk in blocks)
    return {"G": total[:d, :d], "sx": total[d, :d], "c": total[:d, d + 1],
            "sy": float(total[d, d + 1]), "sw": float(total[d, d])}


def restrict(m: dict, keep) -> dict:
    """The moments of the columns `keep` (a boolean [d]) alone."""
    return dict(m, G=m["G"][np.ix_(keep, keep)], sx=m["sx"][keep],
                c=m["c"][keep])


def rescale(m: dict, scale) -> dict:
    """The moments of the columns multiplied by `scale` [d]."""
    s = np.asarray(scale, np.float64)
    return dict(m, G=m["G"] * s[:, None] * s[None, :], sx=m["sx"] * s,
                c=m["c"] * s)


# -- the objective in moment space, numpy float64 --------------------------------

def soft(u, thr):
    return np.sign(u) * np.maximum(np.abs(u) - thr, 0.0)


def gradient(m: dict, B, b0: float) -> tuple:
    """(g [d], g0): the data term's gradient in B and in the intercept."""
    B = np.asarray(B, np.float64)
    return ((m["G"] @ B + b0 * m["sx"] - m["c"]) / m["sw"],
            (m["sx"] @ B + b0 * m["sw"] - m["sy"]) / m["sw"])


def kkt_residual(m: dict, B, b0: float, reg: float, alpha: float) -> float:
    """The largest violation of the objective's optimality conditions at
    (B, b0): |g + l2 B + l1 sign(B)| where B is non-zero, what |g + l2 B|
    exceeds l1 by where it is zero, and |g0|."""
    B = np.asarray(B, np.float64)
    g, g0 = gradient(m, B, b0)
    l1, l2 = reg * alpha, reg * (1.0 - alpha)
    s = g + l2 * B
    viol = np.where(B != 0.0, np.abs(s + l1 * np.sign(B)),
                    np.maximum(np.abs(s) - l1, 0.0))
    return float(max(viol.max(), abs(g0)))


def ridge(m: dict, l2: float, fit_intercept: bool = True,
          jitter: float = 0.0) -> tuple:
    """The ridge optimum in closed form, the intercept eliminated:
    (G / sw - xbar xbar' + (l2 + jitter) I) B = c / sw - xbar ybar,
    b = ybar - xbar . B."""
    d = m["G"].shape[0]
    eye = np.eye(d)
    if not fit_intercept:
        return np.linalg.solve(m["G"] / m["sw"] + (l2 + jitter) * eye,
                               m["c"] / m["sw"]), 0.0
    xbar, ybar = m["sx"] / m["sw"], m["sy"] / m["sw"]
    B = np.linalg.solve(
        m["G"] / m["sw"] - np.outer(xbar, xbar) + (l2 + jitter) * eye,
        m["c"] / m["sw"] - xbar * ybar)
    return B, float(ybar - xbar @ B)


def replay(m: dict, reg: float, alpha: float, *, max_iter: int, tol: float,
           fit_intercept: bool = True) -> dict:
    """The documented iteration (module docstring) from its documented
    seed: `B`, `b0` where it stops, `iters`, every `delta`, and the seed."""
    l1, l2 = reg * alpha, reg * (1.0 - alpha)
    d = m["G"].shape[0]
    B, b0 = ridge(m, l2, fit_intercept, jitter=1e-6)
    seed = (B.copy(), b0)
    H = m["G"] / m["sw"] + (l2 + 1e-6) * np.eye(d)
    hdiag = np.maximum(np.diagonal(H), 1e-12)
    deltas = []
    for _ in range(int(max_iter)):
        g, g0 = gradient(m, B, b0)
        Bn = soft(B - np.linalg.solve(H, g + l2 * B), l1 / hdiag)
        b0n = b0 - g0 if fit_intercept else b0
        deltas.append(float(np.abs(Bn - B).max() + abs(b0n - b0)))
        B, b0 = Bn, b0n
        if deltas[-1] <= tol:
            break
    return {"B": B, "b0": float(b0), "iters": len(deltas),
            "deltas": deltas, "seed": seed}


def fista(m: dict, reg: float, alpha: float) -> dict:
    """The optimum of the objective by accelerated proximal gradient on
    the centred moments (the intercept eliminated exactly: b = ybar -
    xbar . B at every B), step 1 / (top eigenvalue + l2): `B`, `b0`,
    `iters`."""
    l1, l2 = reg * alpha, reg * (1.0 - alpha)
    xbar, ybar = m["sx"] / m["sw"], m["sy"] / m["sw"]
    A = m["G"] / m["sw"] - np.outer(xbar, xbar)
    rhs = m["c"] / m["sw"] - xbar * ybar
    step = 1.0 / (float(np.linalg.eigvalsh(A)[-1]) + l2)
    B = V = np.zeros_like(rhs)
    th, it = 1.0, 0
    for it in range(1, FIT_ITERS + 1):
        Bn = soft(V - step * (A @ V - rhs + l2 * V), step * l1)
        thn = 0.5 * (1.0 + np.sqrt(1.0 + 4.0 * th * th))
        V = Bn + (th - 1.0) / thn * (Bn - B)
        done = np.abs(Bn - B).max() <= FIT_TOL
        B, th = Bn, thn
        if done:
            break
    return {"B": B, "b0": float(ybar - xbar @ B), "iters": it}


# -- exact metrics of given coefficients -------------------------------------------

def residual_sums(X, y, v, beta, b0, pivot: float) -> dict:
    """float64 sums over all rows of the device matrix under weights v [n]
    of the residuals r = x . beta + b0 - y of K RAW-unit coefficient
    vectors (beta [K, d], b0 [K]) in ONE read: `r2` [K] = sum v r^2, `abs`
    [K] = sum v |r|, and the label's `w`, `y`, `y2` = sum v, sum v (y -
    pivot), sum v (y - pivot)^2."""
    import jax.numpy as jnp
    c, blocks = _blocks(X.shape[0])
    P = _programs(c)
    yd, vd = jnp.asarray(y, jnp.float32), jnp.asarray(v, jnp.float32)
    bd = jnp.asarray(np.asarray(beta, np.float32).T)
    b0d = jnp.asarray(b0, jnp.float32)
    r2, ab, lab = _sum_blocks(
        P["sums"](X, yd, vd, bd, b0d, jnp.float32(pivot), st, sk)
        for st, sk in blocks)
    return {"r2": r2, "abs": ab, "w": float(lab[0]), "y": float(lab[1]),
            "y2": float(lab[2])}


def metrics_from_sums(s: dict) -> dict:
    """RMSE, MSE, MAE, R2 [K] float64 of `residual_sums`' sums."""
    mse = s["r2"] / s["w"]
    ss_tot = s["y2"] - s["y"] ** 2 / s["w"]
    return {"rmse": np.sqrt(mse), "mse": mse, "mae": s["abs"] / s["w"],
            "r2": 1.0 - s["r2"] / ss_tot}


# -- the program's per-fold moments, twinned -----------------------------------------

def moments_twin(X, y, w, fold_masks, mean, std, rounded=False) -> tuple:
    """numpy float64 twin of the program's Gram pass, from its docstring:
    (Gm [F, d, d], cA [F, d], sxA [F, d], syA [F], wsum_f [F]) = the sums
    over the rows of w_f xs xs', w_f y xs, w_f xs, w_f y, w_f with w_f =
    fold_masks[f] * w and xs = (x - mean) / std taken in float32, as the
    program takes it, and summed in float64. `rounded` rounds xs to
    bfloat16: what a matrix unit at default precision would be handed."""
    f64 = np.float64
    xs = ((np.asarray(X, np.float32) - np.asarray(mean, np.float32)[None])
          / np.asarray(std, np.float32)[None])
    xs = (as_bf16(xs) if rounded else xs).astype(f64)
    wf = np.asarray(fold_masks, f64) * np.asarray(w, f64)[None, :]   # [F, n]
    yy = np.asarray(y, f64)
    return (np.einsum("fn,nd,ne->fde", wf, xs, xs, optimize=True),
            (wf * yy[None, :]) @ xs, wf @ xs, wf @ yy, wf.sum(1))


# -- the comparisons ------------------------------------------------------------------

def _lowest(grids) -> int:
    return min(range(len(grids)), key=lambda j: (
        grids[j]["reg_param"], grids[j]["elastic_net_param"]))


def linreg_answer(best, fits, masks, grids, X, y, *, fit_params: dict,
                  reference_fold: int, reference_rows: int, into: dict
                  ) -> dict:
    """The readings of the regression sweep that ran (`best`: validate()'s
    answer, RMSE; `fits`: the RAW fold coefficients its fit handed its
    metric pass), before any bound:

    - every grid point and EVERY fold: the exact metrics of the sweep's
      own coefficients over all held-out rows, against the fold RMSE it
      reported; beside the best and the least-regularised point's, what
      the same coefficients rounded to bfloat16 read;
    - `reference_fold`, the best, the least-regularised and the
      largest-l1 point, over ALL its training rows: the objective's KKT
      residual at the sweep's coefficients, how far they (`replay_delta`)
      and their intercept (`intercept_delta`: a float32 intercept near 10
      resolves 1e-6, ten times what a coefficient near 0.1 does, so it is
      held apart) lie from the float64 replay of the documented iteration
      on this file's own float64 moments, and what each named wrong build
      reads there;
    - the same fold against `fista` on the first `reference_rows` rows:
      coefficients and held-out MSE;
    - the pairs of grid points the sweep's report orders unlike the exact
      mean RMSEs of its own coefficients."""
    lr = [v for v in best.validated if v.route == "streamed"]
    require(bool(lr) and len(fits) == 1,
            f"{len(lr)} streamed grid points, {len(fits)} streamed fits "
            f"seen: the sweep's coefficients cannot be read")
    Braw, b0raw = fits[0]
    n, d = X.shape
    F, G = masks.shape[0], len(grids)
    require(Braw.shape == (F, G, d) and b0raw.shape == (F, G),
            f"fold coefficients of shape {Braw.shape}, intercepts "
            f"{b0raw.shape}, for {F} folds x {G} grid points")
    by_grid = {tuple(sorted(v.grid.items())): v for v in lr}
    rows = [by_grid[tuple(sorted(g.items()))] for g in grids]
    sweep = np.asarray([v.fold_metrics for v in rows], np.float64).T  # [F, G]
    j_top = int(np.argmin(sweep.mean(0)))
    j_low = _lowest(grids)
    # the lane the proximal iteration works hardest for: the largest l1
    j_l1 = max(range(G), key=lambda j: (
        grids[j]["reg_param"] * grids[j]["elastic_net_param"]))
    yh = np.asarray(y)
    pivot = float(yh[:1 << 16].mean())
    out = into
    out.update(grid=dict(grids[j_top]), least_regularised=dict(grids[j_low]))

    mean, std = column_moments(X)
    inv_std = 1.0 / std
    mean_h, std_h = (np.asarray(v, np.float64) for v in (mean, std))

    def standard(beta, b0_):
        """Raw-unit coefficients on the standardised scale."""
        beta = np.asarray(beta, np.float64)
        return beta * std_h, float(b0_) + float((beta * mean_h).sum())

    def raw(Bs, b0s):
        Bs = np.asarray(Bs, np.float64)
        return Bs / std_h, float(b0s) - float((Bs / std_h * mean_h).sum())

    # -- the plain fit on the sample, whose held-out MSE rides fold f's pass
    f = reference_fold
    m_rows = min(reference_rows, n)
    sample = fold_moments(X, yh, masks[f], mean, inv_std, rows=m_rows)
    plain = {j: fista(sample, float(grids[j]["reg_param"]),
                      float(grids[j]["elastic_net_param"]))
             for j in {j_top, j_low, j_l1}}

    # -- (b), (f): exact metrics of the sweep's own coefficients
    three = (j_top, j_low, j_l1)
    exact = np.empty((F, G))
    low = np.empty((F, 2))
    full = {}
    plain_mse = {}
    for k in range(F):
        cand = [Braw[k], as_bf16(Braw[k][[j_top, j_low]])]
        cand0 = [b0raw[k], b0raw[k][[j_top, j_low]]]
        if k == f:
            pr = [raw(plain[j]["B"], plain[j]["b0"]) for j in three]
            cand.append(np.stack([p[0] for p in pr]))
            cand0.append(np.asarray([p[1] for p in pr]))
        mets = metrics_from_sums(residual_sums(
            X, yh, 1.0 - masks[k], np.concatenate(cand),
            np.concatenate(cand0), pivot))
        exact[k] = mets["rmse"][:G]
        low[k] = mets["rmse"][G:G + 2]
        if k == f:
            full = {key: v[:G].tolist() for key, v in mets.items()}
            plain_mse = dict(zip(three, mets["mse"][G + 2:]))
            sweep_mse = mets["mse"][:G]
    two = [j_top, j_low]
    out["metric"] = {
        "sweep": sweep.tolist(), "exact": exact.tolist(),
        "worst_delta": float(np.abs(sweep - exact)[:, two].max()),
        "worst_delta_all_points": float(np.abs(sweep - exact).max()),
        "bf16_coefficients_delta": float(np.abs(low - exact[:, two]).max()),
        "bf16_coefficients_delta_least": float(np.abs(
            low - exact[:, two]).max(0).min()),
        "fold_metrics_exact": full}
    out["metric_worst_delta"] = out["metric"]["worst_delta"]
    log(f"linreg answer: sweep RMSE vs exact worst "
        f"{out['metric_worst_delta']:.3e} (all points "
        f"{out['metric']['worst_delta_all_points']:.3e}; bfloat16 "
        f"coefficients {out['metric']['bf16_coefficients_delta']:.3e})")

    # -- (c): fold f, ALL training rows, in moment space
    m = fold_moments(X, yh, masks[f], mean, inv_std)
    m_rounded = fold_moments(X, yh, masks[f], mean, inv_std, rounded=True)
    value_cols = np.arange(d) % 2 == 0
    kw = dict(max_iter=fit_params["max_iter"], tol=fit_params["tol"])
    points = {}
    for name, j in (("best", j_top), ("least_regularised", j_low),
                    ("largest_l1", j_l1)):
        same = [p for p in points.values() if p["grid"] == grids[j]]
        if same:
            points[name] = same[0]          # one point is both
            continue
        reg = float(grids[j]["reg_param"])
        alpha = float(grids[j]["elastic_net_param"])
        Bs, b0s = standard(Braw[f, j], b0raw[f, j])
        doc = replay(m, reg, alpha, **kw)
        opt = fista(m, reg, alpha)
        # the named wrong builds, reference side, each on the
        # standardised scale of ALL columns
        rnd = replay(m_rounded, reg, alpha, **kw)
        nostd = replay(rescale(m, std_h), reg, alpha, **kw)
        noind = replay(restrict(m, value_cols), reg, alpha, **kw)
        noint = replay(m, reg, alpha, fit_intercept=False, **kw)
        one = replay(m, reg, alpha, max_iter=1, tol=kw["tol"])
        wide = np.zeros(d)
        wide[value_cols] = noind["B"]
        cand = {"sweep": (Bs, b0s),
                "once_rounded_operands": (rnd["B"], rnd["b0"]),
                "std_not_applied": (nostd["B"] * std_h, nostd["b0"]),
                "indicators_left_out": (wide, noind["b0"]),
                "intercept_dropped": (noint["B"], noint["b0"]),
                "ridge_for_elastic_net": doc["seed"],
                "one_prox_iteration": (one["B"], one["b0"])}
        reads = {who: {
            "kkt": kkt_residual(m, B_, b_, reg, alpha),
            "replay_delta": float(np.abs(B_ - doc["B"]).max()),
            "intercept_delta": float(abs(b_ - doc["b0"])),
            "coefficients": float(max(
                np.abs(B_ - plain[j]["B"]).max(),
                abs(b_ - plain[j]["b0"])))} for who, (B_, b_) in cand.items()}
        points[name] = {
            "grid": dict(grids[j]), **reads.pop("sweep"), "wrong": reads,
            "mse_delta": float(abs(sweep_mse[j] - plain_mse[j])),
            "replay_iters": doc["iters"], "replay_deltas": doc["deltas"],
            "replay_kkt": kkt_residual(m, doc["B"], doc["b0"], reg, alpha),
            "replay_vs_optimum": float(max(
                np.abs(doc["B"] - opt["B"]).max(),
                abs(doc["b0"] - opt["b0"]))),
            "optimum_kkt": kkt_residual(m, opt["B"], opt["b0"], reg, alpha),
            "plain_iters": plain[j]["iters"]}
        log(f"linreg answer, fold {f}, {name} {grids[j]}: {points[name]}")
    out.update(reference_fold=f, reference_rows=int(m_rows), points=points)
    for key in ("kkt", "replay_delta", "intercept_delta", "coefficients",
                "mse_delta"):
        out[key + "_worst"] = max(p[key] for p in points.values())
    # the longest of the replays: the program's solves, which iterate all
    # lanes together, cannot have stopped sooner
    out["replay_iters_max"] = max(p["replay_iters"] for p in points.values())
    # a wrong build is refused where ANY point reads past a bound
    out["wrong_worst"] = {
        who: {key: max(p["wrong"][who][key] for p in points.values())
              for key in ("kkt", "replay_delta", "intercept_delta",
                          "coefficients")}
        for who in next(iter(points.values()))["wrong"]}

    # -- (f): the order of the grid points
    mean_sweep, mean_exact = sweep.mean(0), exact.mean(0)
    out["order"] = {"mean_rmse_sweep": mean_sweep.tolist(),
                    "mean_rmse_exact": mean_exact.tolist()}
    return out


def misordered(order: dict, apart: float) -> list:
    """Pairs (i, j) of grid points whose exact mean RMSEs lie more than
    `apart` apart and which the sweep's report orders the other way."""
    a, b = (np.asarray(order[k]) for k in ("mean_rmse_exact",
                                           "mean_rmse_sweep"))
    return [(i, j) for i in range(len(a)) for j in range(i + 1, len(a))
            if abs(a[i] - a[j]) > apart
            and (a[i] - a[j]) * (b[i] - b[j]) <= 0.0]
