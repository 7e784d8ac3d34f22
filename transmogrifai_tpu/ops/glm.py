"""Generalized-linear-model solvers as pure JAX programs.

These replace Spark MLlib's LBFGS/OWLQN/IRLS optimizers (used by the
reference's OpLogisticRegression / OpLinearRegression / OpLinearSVC /
OpGeneralizedLinearRegression wrappers, core/.../impl/{classification,
regression}/). Design goals:

* full-batch second-order steps — X^T W X is one MXU matmul; on a
  row-sharded X the Gram matrix reduction becomes an ICI psum inserted by
  XLA, so the same code scales from 1 chip to a pod;
* everything fixed-iteration (`lax.fori_loop`) and shape-static so the
  model-selector can `vmap` the whole fit over the hyperparameter grid and
  CV folds (grid x fold axes replace the reference's 8-thread pool,
  OpValidator.scala:318);
* elastic-net via proximal (FISTA-style) steps on the smooth Newton
  direction, matching Spark's OWLQN behavior closely enough for metric
  parity.

Weights: every solver takes per-row weights `w` — fold masks, balancing
weights and padding masks all enter here, so no data movement is needed
between folds.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

EPS = 1e-12


class GLMParams(NamedTuple):
    """Static-shape hyperparameters (vmappable leaves)."""
    reg: jax.Array          # total regularization strength (lambda)
    elastic_net: jax.Array  # alpha in [0,1]: 0 = ridge, 1 = lasso


def _solver_dtype(X: jax.Array):
    """Solver-state dtype: never below f32 even when X is bf16.

    Mixed precision, TPU-first: callers may ship the feature matrix in
    bfloat16 (halving HBM per vmapped sweep lane — the MXU consumes bf16
    natively), while beta/Hessian/solves stay float32. f32 inputs are
    byte-for-byte unaffected."""
    return jnp.promote_types(X.dtype, jnp.float32)


def _mm(a: jax.Array, b: jax.Array) -> jax.Array:
    """Matmul that keeps a low-precision left operand low-precision (no
    [n, d] f32 materialization of a bf16 X) and accumulates in f32."""
    return jnp.matmul(a, b.astype(a.dtype),
                      preferred_element_type=jnp.float32)


def _standardize(X: jax.Array, w: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Weighted column standardization; returns (Xs, mean, std).

    Xs keeps X's dtype (bf16 stays bf16 — centering in bf16 is safe for
    data of moderate dynamic range; pre-center on host otherwise); the
    mean/std statistics accumulate in f32."""
    f32 = jnp.float32
    wsum = jnp.maximum(w.sum().astype(f32), EPS)
    wx = w.astype(X.dtype)
    mean = jnp.sum(X * wx[:, None], axis=0, dtype=f32) / wsum
    centered = X - mean.astype(X.dtype)
    var = jnp.sum(centered * centered * wx[:, None], axis=0, dtype=f32) / wsum
    std = jnp.sqrt(jnp.maximum(var, EPS))
    return centered / std.astype(X.dtype), mean, std


def _unstandardize_beta(beta: jax.Array, intercept: jax.Array,
                        mean: jax.Array, std: jax.Array) -> Tuple[jax.Array, jax.Array]:
    b = beta / std
    return b, intercept - (b * mean).sum()


def _soft_threshold(x: jax.Array, t: jax.Array) -> jax.Array:
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


def _newton_prox_fit(grad_hess_fn, d: int, reg: jax.Array, alpha: jax.Array,
                     max_iter: int, tol: float, dtype=jnp.float32):
    """Damped-Newton with L2 in the Hessian and L1 via proximal step.

    grad_hess_fn(beta, b0) -> (g, H, g0, h0) for the unpenalized loss
    (beta: coefficients, b0: intercept handled separately, unregularized).
    """
    l1 = reg * alpha
    l2 = reg * (1.0 - alpha)

    def cond(state):
        i, _, _, delta = state
        return (i < max_iter) & (delta > tol)

    def body(state):
        i, beta, b0, _ = state
        g, H, g0, h0 = grad_hess_fn(beta, b0)
        g = g + l2 * beta
        H = H + l2 * jnp.eye(d, dtype=dtype)
        # solve with jitter for safety
        step = jnp.linalg.solve(H + 1e-6 * jnp.eye(d, dtype=dtype), g)
        beta_new = beta - step
        # proximal L1 using diagonal curvature as scaling
        hdiag = jnp.maximum(jnp.diag(H), EPS)
        beta_new = _soft_threshold(beta_new, l1 / hdiag)
        b0_new = b0 - g0 / jnp.maximum(h0, EPS)
        delta = jnp.abs(beta_new - beta).max() + jnp.abs(b0_new - b0)
        return i + 1, beta_new, b0_new, delta

    beta0 = jnp.zeros((d,), dtype)
    b00 = jnp.asarray(0.0, dtype)
    _, beta, b0, _ = jax.lax.while_loop(
        cond, body, (jnp.asarray(0, jnp.int32), beta0, b00,
                     jnp.asarray(jnp.inf, dtype)))
    return beta, b0


# -- sufficient-statistics (Gram) solvers for the squared loss ---------------
#
# For loss="squared" the IRLS curvature is identically 1, so every lane
# Hessian collapses to the per-fold weighted Gram X^T diag(w) X —
# iteration-invariant. ops/glm_sweep streams those moments in ONE pass over
# X; the two solvers below then replay `_newton_prox_fit`'s exact update
# rule in moment space. They live HERE, next to the per-lane solvers whose
# fixed points they share, so the parity contract (pinned by
# tests/test_glm_convergence.py) cannot drift from the reference math.


def ridge_gram_solve(Gm: jax.Array, cm: jax.Array, sx: jax.Array,
                     sy: jax.Array, sw: jax.Array, l2: jax.Array,
                     fit_intercept: bool = True
                     ) -> Tuple[jax.Array, jax.Array]:
    """Closed-form weighted ridge from per-lane sufficient statistics.

    Gm [L, d, d] = X^T W_l X, cm [L, d] = X^T W_l y, sx [L, d] = X^T W_l 1,
    sy [L] = 1^T W_l y, sw [L] = 1^T W_l 1, l2 [L]. Solves the stationary
    point of `_newton_prox_fit(loss=squared, l1=0)` with the intercept
    eliminated: (G/sw - xbar xbar^T + l2 I) beta = c/sw - xbar ybar and
    b0 = ybar - xbar.beta — i.e. the point the per-lane Newton iteration
    converges toward, reached in one batched solve. The 1e-6 jitter matches
    the iterative Hessian's conditioning. Returns (beta [L, d], b0 [L])."""
    f32 = jnp.float32
    d = Gm.shape[-1]
    eye = jnp.eye(d, dtype=f32)
    sw_ = jnp.maximum(sw, EPS)
    if fit_intercept:
        xbar = sx / sw_[:, None]
        ybar = sy / sw_
        A = (Gm / sw_[:, None, None]
             - xbar[:, :, None] * xbar[:, None, :]
             + (l2 + 1e-6)[:, None, None] * eye[None])
        rhs = cm / sw_[:, None] - xbar * ybar[:, None]
        beta = jnp.linalg.solve(A, rhs[..., None])[..., 0]
        b0 = ybar - (beta * xbar).sum(1)
    else:
        A = Gm / sw_[:, None, None] + (l2 + 1e-6)[:, None, None] * eye[None]
        beta = jnp.linalg.solve(A, (cm / sw_[:, None])[..., None])[..., 0]
        b0 = jnp.zeros_like(sy)
    return beta, b0


def prox_newton_gram(Gm: jax.Array, cm: jax.Array, sx: jax.Array,
                     sy: jax.Array, sw: jax.Array, l1: jax.Array,
                     l2: jax.Array, beta0: jax.Array, b00: jax.Array,
                     max_iter, tol, fit_intercept: bool = True
                     ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Lane-batched proximal Newton on cached squared-loss moments.

    Replays `_newton_prox_fit`'s update rule with every data-dependent term
    reconstructed from the sufficient statistics (curvature == 1, so no
    pass over X per iteration): grad = (G beta + b0 sx - c)/sw + l2 beta,
    H = G/sw + (l2 + 1e-6) I (iteration-invariant, factored once by shape),
    proximal L1 against H's diagonal, intercept step b0 - g0 (h0/wsum == 1
    because wsum IS the lane weight sum). Warm-startable via beta0/b00 —
    the Gram fast path seeds from `ridge_gram_solve` of the same l2
    (pathwise continuation). max_iter/tol are traced scalars.

    The gradient's [L, d, d] x [L, d] product runs at HIGHEST precision: at
    the default the chip's matrix unit rounds G and beta to bfloat16, the
    step then moves beta by beta's own rounding error (2^-9 |beta|) and no
    delta ever clears a tolerance of 1e-6 (the moment-space sibling of
    PERF.md, PR 38); the products are [40, 128, 128], free at any
    precision. With an intercept the iteration runs on the label CENTRED
    by the lane's weighted mean ybar = sy / sw (c - ybar sx for c, b0 -
    ybar for b0: the same iterates, to rounding): the intercept's step
    b0 sw - sy is otherwise a difference of two numbers of the label
    mean's size, and at a label mean of 10 float32 leaves it a noise of
    1e-6 — `tol` itself — so that delta never settles (PERF.md, PR 43).
    The lanes iterate together while ANY delta is over `tol` (nothing to
    retire: an iteration reads no data). Returns (beta [L, d], b0 [L],
    iters executed, delta [L]: each lane's own last step, so that a caller
    can count the lanes the cap stopped)."""
    f32 = jnp.float32
    d = Gm.shape[-1]
    eye = jnp.eye(d, dtype=f32)
    sw_ = jnp.maximum(sw, EPS)
    H = Gm / sw_[:, None, None] + (l2 + 1e-6)[:, None, None] * eye[None]
    hdiag = jnp.maximum(jnp.diagonal(H, axis1=1, axis2=2), EPS)
    ybar = sy / sw_ if fit_intercept else jnp.zeros_like(sy)
    cm = cm - ybar[:, None] * sx

    def cond(state):
        i, _, _, delta = state
        return (i < max_iter) & (delta.max() > tol)

    def body(state):
        i, beta, b0, _ = state
        g = ((jnp.einsum('lde,le->ld', Gm, beta,
                         precision=jax.lax.Precision.HIGHEST)
              + b0[:, None] * sx - cm)
             / sw_[:, None] + l2[:, None] * beta)
        step = jnp.linalg.solve(H, g[..., None])[..., 0]
        beta_new = _soft_threshold(beta - step, l1[:, None] / hdiag)
        if fit_intercept:
            # (sy - ybar sw is zero: the centred label's weighted sum)
            b0_new = b0 - ((sx * beta).sum(1) / sw_ + b0)
        else:
            b0_new = b0
        delta = jnp.abs(beta_new - beta).max(1) + jnp.abs(b0_new - b0)
        return i + 1, beta_new, b0_new, delta

    state = (jnp.asarray(0, jnp.int32), beta0.astype(f32),
             b00.astype(f32) - ybar, jnp.full(sw.shape, jnp.inf, f32))
    i, beta, b0, delta = jax.lax.while_loop(cond, body, state)
    return beta, b0 + ybar, i, delta


def fit_logistic(X: jax.Array, y: jax.Array, w: jax.Array,
                 reg: jax.Array, elastic_net: jax.Array,
                 max_iter: int = 50, tol: float = 1e-6,
                 fit_intercept: bool = True,
                 standardize: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Binary logistic regression via IRLS-Newton (+proximal L1).

    Returns (coefficients [d], intercept). Matches Spark's
    LogisticRegression(standardization=true, family=binomial) closely.
    X may be bfloat16 (see _solver_dtype) — per-row work and the Xs*s
    product then stay bf16 while beta/H/solves run in f32.
    """
    dtype = _solver_dtype(X)
    n, d = X.shape
    Xs, mean, std = _standardize(X, w) if standardize else (X, jnp.zeros(d, dtype), jnp.ones(d, dtype))
    wsum = jnp.maximum(w.sum(), EPS)

    def grad_hess(beta, b0):
        eta = _mm(Xs, beta) + b0
        p = jax.nn.sigmoid(eta)
        r = (p - y) * w
        g = _mm(Xs.T, r) / wsum
        s = jnp.maximum(p * (1 - p), 1e-6) * w
        H = _mm((Xs * s.astype(Xs.dtype)[:, None]).T, Xs) / wsum
        g0 = r.sum() / wsum if fit_intercept else jnp.asarray(0.0, dtype)
        h0 = s.sum() / wsum if fit_intercept else jnp.asarray(1.0, dtype)
        return g, H, g0, h0

    beta, b0 = _newton_prox_fit(grad_hess, d, reg, elastic_net, max_iter, tol, dtype)
    if standardize:
        beta, b0 = _unstandardize_beta(beta, b0, mean, std)
    if not fit_intercept:
        b0 = jnp.asarray(0.0, dtype)
    return beta, b0


def fit_linear(X: jax.Array, y: jax.Array, w: jax.Array,
               reg: jax.Array, elastic_net: jax.Array,
               max_iter: int = 50, tol: float = 1e-6,
               fit_intercept: bool = True,
               standardize: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Weighted linear regression with elastic net (Spark LinearRegression).

    Ridge part closed-form per Newton step; L1 via proximal iterations.
    X may be bfloat16 (see _solver_dtype).
    """
    dtype = _solver_dtype(X)
    n, d = X.shape
    Xs, mean, std = _standardize(X, w) if standardize else (X, jnp.zeros(d, dtype), jnp.ones(d, dtype))
    wsum = jnp.maximum(w.sum(), EPS)

    def grad_hess(beta, b0):
        r = (_mm(Xs, beta) + b0 - y) * w
        g = _mm(Xs.T, r) / wsum
        H = _mm((Xs * w.astype(Xs.dtype)[:, None]).T, Xs) / wsum
        g0 = r.sum() / wsum if fit_intercept else jnp.asarray(0.0, dtype)
        h0 = w.sum() / wsum if fit_intercept else jnp.asarray(1.0, dtype)
        return g, H, g0, h0

    beta, b0 = _newton_prox_fit(grad_hess, d, reg, elastic_net, max_iter, tol, dtype)
    if standardize:
        beta, b0 = _unstandardize_beta(beta, b0, mean, std)
    if not fit_intercept:
        b0 = jnp.asarray(0.0, dtype)
    return beta, b0


def fit_linear_svc(X: jax.Array, y: jax.Array, w: jax.Array,
                   reg: jax.Array,
                   max_iter: int = 50, tol: float = 1e-6,
                   fit_intercept: bool = True,
                   standardize: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Linear SVM with squared-hinge loss + L2 (Spark LinearSVC semantics).

    Squared hinge is differentiable, so Newton steps apply with the
    active-set (margin<1) indicator inside the Hessian. X may be bfloat16
    (see _solver_dtype).
    """
    dtype = _solver_dtype(X)
    n, d = X.shape
    ypm = 2.0 * y - 1.0  # {0,1} -> {-1,+1}
    Xs, mean, std = _standardize(X, w) if standardize else (X, jnp.zeros(d, dtype), jnp.ones(d, dtype))
    wsum = jnp.maximum(w.sum(), EPS)

    def grad_hess(beta, b0):
        margin = ypm * (_mm(Xs, beta) + b0)
        active = (margin < 1.0).astype(dtype) * w
        r = -ypm * jnp.maximum(1.0 - margin, 0.0) * w  # d/d_eta of 0.5*max(0,1-m)^2 * ypm... scaled
        g = _mm(Xs.T, r) / wsum
        H = _mm((Xs * active.astype(Xs.dtype)[:, None]).T, Xs) / wsum
        g0 = r.sum() / wsum if fit_intercept else jnp.asarray(0.0, dtype)
        h0 = jnp.maximum(active.sum() / wsum, 1e-6) if fit_intercept else jnp.asarray(1.0, dtype)
        return g, H, g0, h0

    beta, b0 = _newton_prox_fit(grad_hess, d, reg, jnp.asarray(0.0, dtype),
                                max_iter, tol, dtype)
    if standardize:
        beta, b0 = _unstandardize_beta(beta, b0, mean, std)
    if not fit_intercept:
        b0 = jnp.asarray(0.0, dtype)
    return beta, b0


def fit_softmax(X: jax.Array, Y: jax.Array, w: jax.Array,
                reg: jax.Array, elastic_net: jax.Array,
                max_iter: int = 100, lr: float = 1.0,
                fit_intercept: bool = True,
                standardize: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Multinomial logistic regression; Y is one-hot [n, c].

    Uses Boehning's (1992) curvature bound: the softmax Hessian satisfies
    H <= 0.5 (1 - 1/c) X^T W X per class block, so a CONSTANT preconditioner
    A = 0.5(1-1/c) X^T W X + l2 I can be Cholesky-factored once and every
    iteration is pure matmuls + triangular solves — monotone convergence and
    an ideal TPU profile (no per-iteration d x d solves).
    Returns (B [d, c], b0 [c]). X may be bfloat16 (see _solver_dtype).
    """
    dtype = _solver_dtype(X)
    n, d = X.shape
    c = Y.shape[1]
    Xs, mean, std = _standardize(X, w) if standardize else (X, jnp.zeros(d, dtype), jnp.ones(d, dtype))
    wsum = jnp.maximum(w.sum(), EPS)
    l2 = reg * (1.0 - elastic_net)
    l1 = reg * elastic_net
    I = jnp.eye(d, dtype=dtype)

    coef = 0.5 * (1.0 - 1.0 / c)
    A = coef * _mm((Xs * w.astype(Xs.dtype)[:, None]).T, Xs) / wsum \
        + l2 * I + 1e-6 * I
    chol = jax.scipy.linalg.cho_factor(A)
    hdiag = jnp.maximum(jnp.diag(A), EPS)
    h0 = jnp.maximum(coef * w.sum() / wsum, 1e-6)

    def body(_, state):
        B, b0 = state
        logits = _mm(Xs, B) + b0[None, :]
        P = jax.nn.softmax(logits, axis=1)
        R = (P - Y) * w[:, None]          # [n, c]
        G = _mm(Xs.T, R) / wsum + l2 * B  # [d, c]
        B_new = B - jax.scipy.linalg.cho_solve(chol, G)
        B_new = _soft_threshold(B_new, l1 / hdiag[:, None])
        if fit_intercept:
            b0_new = b0 - (R.sum(0) / wsum) / h0
        else:
            b0_new = b0
        return B_new, b0_new

    B0 = jnp.zeros((d, c), dtype)
    b00 = jnp.zeros((c,), dtype)
    B, b0 = jax.lax.fori_loop(0, max_iter, body, (B0, b00))
    if standardize:
        Bu = B / std[:, None]
        b0 = b0 - (Bu * mean[:, None]).sum(0)
        B = Bu
    return B, b0


def fit_glr(X: jax.Array, y: jax.Array, w: jax.Array,
            reg: jax.Array, family: str = "gaussian",
            max_iter: int = 25, fit_intercept: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Generalized linear regression via IRLS (Spark
    GeneralizedLinearRegression families: gaussian/identity, poisson/log,
    gamma/log, tweedie — gaussian & poisson are the reference's default grid,
    DefaultSelectorParams.DistFamily).
    """
    dtype = _solver_dtype(X)
    n, d = X.shape
    wsum = jnp.maximum(w.sum(), EPS)
    I = jnp.eye(d, dtype=dtype)

    if family == "gaussian":
        link, inv_link, var_fn = (lambda m: m), (lambda e: e), (lambda m: jnp.ones_like(m))
    elif family == "poisson":
        link = lambda m: jnp.log(jnp.maximum(m, EPS))
        inv_link = jnp.exp
        var_fn = lambda m: jnp.maximum(m, EPS)
    elif family == "gamma":
        link = lambda m: jnp.log(jnp.maximum(m, EPS))
        inv_link = jnp.exp
        var_fn = lambda m: jnp.maximum(m * m, EPS)
    else:
        raise ValueError(f"Unsupported GLR family: {family}")

    def body(_, state):
        beta, b0 = state
        eta = X @ beta + b0
        mu = inv_link(eta)
        if family == "gaussian":
            z = y
            s = w
        else:
            # canonical log link: d_mu/d_eta = mu
            z = eta + (y - mu) / jnp.maximum(mu, EPS)
            s = w * jnp.maximum(mu, EPS)  # working weights mu^2/var * ... = mu for poisson
            if family == "gamma":
                s = w  # mu^2/var = 1 for gamma with log link
        A = (X * s[:, None]).T @ X / wsum + reg * I + 1e-6 * I
        rhs = X.T @ (s * (z - b0)) / wsum
        beta_new = jnp.linalg.solve(A, rhs)
        if fit_intercept:
            b0_new = (s * (z - X @ beta_new)).sum() / jnp.maximum(s.sum(), EPS)
        else:
            b0_new = b0
        return beta_new, b0_new

    beta0 = jnp.zeros((d,), dtype)
    b00 = jnp.asarray(0.0, dtype)
    return jax.lax.fori_loop(0, max_iter, body, (beta0, b00))


def fit_naive_bayes(X: jax.Array, Y: jax.Array, w: jax.Array,
                    smoothing: float = 1.0) -> Tuple[jax.Array, jax.Array]:
    """Multinomial naive Bayes (Spark NaiveBayes modelType=multinomial):
    requires nonnegative features. Returns (log_prob [c, d], log_prior [c])."""
    w_ = w[:, None]
    class_count = (Y * w_).sum(0)                      # [c]
    feat_count = Y.T @ (jnp.maximum(X, 0.0) * w_)      # [c, d]
    log_prior = jnp.log(jnp.maximum(class_count, EPS)) - \
        jnp.log(jnp.maximum(class_count.sum(), EPS))
    num = feat_count + smoothing
    den = feat_count.sum(1, keepdims=True) + smoothing * X.shape[1]
    log_prob = jnp.log(num) - jnp.log(den)
    return log_prob, log_prior
