"""The plain references of the multiclass sweep: what decides `correct` in
`sweep-mlr-k32`. Nothing here imports the program.

- `fit`: full-batch multinomial logistic regression in float32 at `highest`
  matmul precision, one fold and one grid point at a time, no tiles, no
  lanes, no retirement. The objective is Spark ML's, as upstream's
  OpLogisticRegression wraps it:

      sum_i w_i * logloss_i / sum_i w_i
        + reg * (alpha * |B|_1 + (1 - alpha) / 2 * |B|_2^2)

  with B penalised on the standardised scale, intercepts unpenalised and
  coefficients returned in raw units. The solver is the one the program
  documents (ops/glm.fit_softmax), written again from its description:
  Boehning's (1992) bound H <= 0.5 (1 - 1/K) Xs' W Xs makes the curvature a
  constant matrix A; each step is B <- soft(B - A^-1 G, l1 / diag A),
  b0 <- b0 - mean residual / (0.5 (1 - 1/K)), from zero, until the largest
  change is <= tol or max_iter steps are done. The departure from upstream
  is the solver (Spark runs L-BFGS / OWL-QN): bound optimisation is
  monotone but slow, so at max_iter 50 the iterate is still on its way to
  the optimum, and WHICH iterate a sweep reports is part of its answer.
  tests/benchmark/test_benchmark_reference_softmax.py holds this file to a
  numpy loop, and its long-run limit to the objective's optimality
  conditions.
- `scores`: float32 logits of given coefficients over a device matrix, a
  chunk of rows at a time: predicted class and log-loss of every row.
- `confusion_plain`, `metrics_plain`: the exact weighted confusion count
  and the class metrics from it, numpy float64.
- `mlr_sweep_answer`: the comparisons themselves.
"""
from __future__ import annotations

import functools

import numpy as np

from benchmark.harness import log
from benchmark.reference import require


def fit(X, y, w, reg: float, alpha: float, n_classes: int, *,
        max_iter: int, tol: float, fit_intercept: bool = True,
        standardize: bool = True):
    """(B [d, K], b0 [K]) float32, raw units; X [n, d] float32, y class ids,
    w row weights (0 = the row is absent). See the module docstring."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        B, b0, _ = _fit_program(int(n_classes), int(max_iter),
                                bool(fit_intercept), bool(standardize))(
            jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32),
            jnp.asarray(w, jnp.float32), jnp.float32(reg),
            jnp.float32(alpha), jnp.float32(tol))
    return np.asarray(B), np.asarray(b0)


@functools.lru_cache(maxsize=None)
def _fit_program(K: int, max_iter: int, fit_intercept: bool,
                 standardize: bool):
    import jax
    import jax.numpy as jnp

    def run(X, y, w, reg, alpha, tol):
        n, d = X.shape
        wsum = w.sum()
        if standardize:
            mean = (X * w[:, None]).sum(0) / wsum
            std = jnp.sqrt(jnp.maximum(
                (((X - mean) ** 2) * w[:, None]).sum(0) / wsum, 1e-12))
        else:
            mean, std = jnp.zeros(d), jnp.ones(d)
        Xs = (X - mean) / std
        Y = (y[:, None] == jnp.arange(K, dtype=jnp.float32)[None, :]) \
            .astype(jnp.float32)
        l1, l2 = reg * alpha, reg * (1.0 - alpha)
        coef = 0.5 * (1.0 - 1.0 / K)
        A = coef * (Xs * w[:, None]).T @ Xs / wsum \
            + (l2 + 1e-6) * jnp.eye(d)
        adiag = jnp.diag(A)

        def step(s):
            i, B, b0, _ = s
            R = (jax.nn.softmax(Xs @ B + b0, axis=1) - Y) * w[:, None]
            G = Xs.T @ R / wsum + l2 * B
            Bn = B - jnp.linalg.solve(A, G)
            Bn = jnp.sign(Bn) * jnp.maximum(
                jnp.abs(Bn) - l1 / adiag[:, None], 0.0)
            b0n = b0 - R.sum(0) / wsum / coef if fit_intercept else b0
            return (i + 1, Bn, b0n,
                    jnp.abs(Bn - B).max() + jnp.abs(b0n - b0).max())

        i, B, b0, _ = jax.lax.while_loop(
            lambda s: (s[0] < max_iter) & (s[3] > tol), step,
            (0, jnp.zeros((d, K)), jnp.zeros(K), jnp.float32(jnp.inf)))
        B = B / std[:, None]
        return B, b0 - (B * mean[:, None]).sum(0), i
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _score_part(chunk: int):
    import jax
    import jax.numpy as jnp

    def part(X, y, B, b0, start):
        x = jax.lax.dynamic_slice_in_dim(X, start, chunk) \
            .astype(jnp.float32)
        yc = jax.lax.dynamic_slice_in_dim(y, start, chunk).astype(jnp.int32)
        z = jnp.matmul(x, B, precision=jax.lax.Precision.HIGHEST) + b0
        lse = jax.scipy.special.logsumexp(z, axis=1)
        own = jnp.take_along_axis(
            z, jnp.clip(yc, 0, z.shape[1] - 1)[:, None], axis=1)[:, 0]
        return jnp.argmax(z, axis=1).astype(jnp.int32), lse - own
    return jax.jit(part)


def scores(X, y, B, b0, chunk: int = 1 << 21) -> tuple:
    """(predicted class [n] int32, log-loss [n] float32) of every row of
    the device matrix under coefficients B [d, K], b0 [K]: float32 logits
    at `highest` precision, a chunk of rows at a time, so that neither a
    float32 copy of the matrix nor an [n, K] array is made (the last chunk
    starts early and overlaps)."""
    import jax.numpy as jnp
    n = X.shape[0]
    chunk = min(chunk, n)
    part = _score_part(chunk)
    Bd, bd = jnp.asarray(B, jnp.float32), jnp.asarray(b0, jnp.float32)
    yd = jnp.asarray(y, jnp.float32)
    pred, loss = np.empty(n, np.int32), np.empty(n, np.float32)
    for i in range(0, n, chunk):
        start = min(i, n - chunk)
        p, ls = part(X, yd, Bd, bd, start)
        pred[start:start + chunk] = np.asarray(p)
        loss[start:start + chunk] = np.asarray(ls)
    return pred, loss


def _as_bf16(a) -> np.ndarray:
    import jax.numpy as jnp
    return np.asarray(jnp.asarray(a, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32))


def confusion_plain(pred, y, w, n_classes: int) -> np.ndarray:
    """conf[true, pred] = the weight of the rows with that label and that
    prediction, float64. A label or prediction outside 0..K-1 counts
    nowhere."""
    pred = np.asarray(pred).astype(np.int64)
    y = np.asarray(y).astype(np.int64)
    ok = (pred >= 0) & (pred < n_classes) & (y >= 0) & (y < n_classes)
    return np.bincount(
        y[ok] * n_classes + pred[ok], np.asarray(w, np.float64)[ok],
        n_classes * n_classes).reshape(n_classes, n_classes)


def metrics_plain(conf: np.ndarray) -> dict:
    """error, and precision / recall / F1 weighted by the true classes'
    shares (Spark's MulticlassMetrics, as upstream's evaluator reports)."""
    tp, per_true, per_pred = np.diag(conf), conf.sum(1), conf.sum(0)
    with np.errstate(divide="ignore", invalid="ignore"):
        prec = np.where(per_pred > 0, tp / per_pred, 0.0)
        rec = np.where(per_true > 0, tp / per_true, 0.0)
        f1 = np.where(prec + rec > 0, 2 * prec * rec / (prec + rec), 0.0)
    share = per_true / max(per_true.sum(), 1e-12)
    return {"error": 1.0 - tp.sum() / max(conf.sum(), 1e-12),
            "precision": float((prec * share).sum()),
            "recall": float((rec * share).sum()),
            "f1": float((f1 * share).sum())}


def mlr_sweep_answer(best, fits, masks, grids, X, y, *, n_classes: int,
                     fit_params: dict, reference_fold: int,
                     reference_rows: int, tol_metric: float,
                     tol_coefficients: float, tol_logloss: float) -> dict:
    """Hold the multiclass LR sweep that ran to its own answer. For the
    best grid point and EVERY fold: the exact error (float32 logits, all
    held-out rows) of the coefficients the sweep's own fit handed to its
    metric pass, against the fold metric it reported. For
    `reference_fold`: `fit` on the first `reference_rows` rows of that
    fold's training part at the same grid point, against the sweep's
    coefficients (largest difference over all of B and b0, raw units) and
    against their held-out log-loss."""
    import jax.numpy as jnp

    lr = [v for v in best.validated if v.route == "streamed"]
    require(bool(lr) and len(fits) == 1,
            f"{len(lr)} streamed grid points, {len(fits)} streamed fits "
            f"seen: the sweep's coefficients cannot be read")
    top = min(lr, key=lambda v: v.mean_metric)
    j = grids.index(dict(top.grid))
    B, b0 = fits[0]
    d = X.shape[1]
    require(B.shape == (masks.shape[0], B.shape[1], d, n_classes)
            and B.shape[1] >= len(grids) and b0.shape == B.shape[:2]
            + (n_classes,), f"fold coefficients of shape {B.shape}, "
                            f"intercepts {b0.shape}")
    yh = np.asarray(y)
    out = {"grid": dict(top.grid), "folds": []}
    worst = worst_low = 0.0
    for f in range(masks.shape[0]):
        held = 1.0 - masks[f]
        pred, loss = scores(X, yh, B[f, j], b0[f, j])
        exact = metrics_plain(confusion_plain(pred, yh, held, n_classes))
        got = float(top.fold_metrics[f])
        # the nearest precision below the float32 coefficients, which
        # tol_metric has to refuse (PERF.md gives both readings): the
        # same rows scored with the coefficients rounded to bfloat16
        low, _ = scores(X, yh, _as_bf16(B[f, j]), _as_bf16(b0[f, j]))
        low = metrics_plain(confusion_plain(low, yh, held, n_classes))
        out["folds"].append({"sweep": got, "exact": exact["error"],
                             "bf16_coefficients": low["error"]})
        worst = max(worst, abs(got - exact["error"]))
        worst_low = max(worst_low, abs(low["error"] - exact["error"]))
        if f == reference_fold:
            n = min(reference_rows, X.shape[0])
            rB, rb0 = fit(X[:n].astype(jnp.float32), yh[:n], masks[f, :n],
                          float(top.grid["reg_param"]),
                          float(top.grid["elastic_net_param"]), n_classes,
                          **fit_params)
            _, rloss = scores(X, yh, rB, rb0)
            hsum = held.sum()
            out.update(
                reference_fold=f, reference_rows=int(n),
                coefficient_delta=float(max(
                    np.abs(rB - B[f, j]).max(),
                    np.abs(rb0 - b0[f, j]).max())),
                logloss_sweep_coefficients=float((loss * held).sum() / hsum),
                logloss_reference_fit=float((rloss * held).sum() / hsum))
            out["logloss_delta"] = abs(out["logloss_sweep_coefficients"]
                                       - out["logloss_reference_fit"])
    out["metric_worst_delta"] = worst
    out["bf16_coefficients_delta"] = worst_low
    log(f"MLR answer: sweep error vs exact worst {worst:.2e}; sweep vs "
        f"reference fit: coefficients {out['coefficient_delta']:.2e}, "
        f"held-out log-loss {out['logloss_delta']:.2e}; bfloat16 "
        f"coefficients would move a fold's error by "
        f"{out['bf16_coefficients_delta']:.2e}")
    require(worst <= tol_metric,
            f"a fold metric of the sweep is {worst:.2e} off the exact "
            f"error of its own coefficients (bound {tol_metric})")
    require(out["coefficient_delta"] <= tol_coefficients,
            f"the sweep's coefficients are {out['coefficient_delta']:.2e} "
            f"off the plain reference fit (bound {tol_coefficients})")
    require(out["logloss_delta"] <= tol_logloss,
            f"the sweep's coefficients score {out['logloss_delta']:.2e} "
            f"off the plain reference fit in held-out log-loss (bound "
            f"{tol_logloss})")
    return out
