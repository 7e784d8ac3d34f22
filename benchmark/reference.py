"""What decides `correct`: the plain references and the comparisons.

Nothing here is the program's: the kernel twins are numpy written from the
dispatchers' documented contracts, the logistic fit is a plain float32
proximal-gradient solver, the boosted trees a plain histogram GBT, the
AuPR an exact sort. From the program the checks take only what the timed
path itself produced — the fold metrics `validate()` returned, the fold
coefficients its streamed fit handed to its metric pass, the shapes its
kernel dispatchers were called with — so that a change which makes the
timed path answer differently shows as `correct: false`.

(`DispatcherSpy`, `_reference_logistic` and `numpy_au_pr` began as copies
from chip_smoke.py and bench.py; the originals are listed under Open
questions in PERF.md for a later PR to delete.)
"""
from __future__ import annotations

import functools
import time

import numpy as np

from benchmark.harness import log

#: per-cell bound on bf16-input histogram g/h channels, relative to the
#: cell's absolute payload mass (tests/test_hist_batched.py: <= 0.4%)
BF16_HIST_RTOL = 4e-3
F32_HIST_RTOL = 1e-4


class CheckFailure(AssertionError):
    pass


def require(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailure(what)


# -- what the timed path ran, read from the warm-up job ----------------------

class DispatcherSpy:
    """Record every call the sweep makes into ops/pallas_hist's kernel
    dispatchers — shapes, static arguments, interpret flag and whether
    pallas was available at that moment — so routes and tile shapes are
    read from what ran. Calls land at trace time (once per compiled
    program); a persistent-cache hit still traces."""

    NAMES = ("hist_folds", "route", "route_hist", "table_lookup",
             "hist_pallas", "route_pallas")

    def __init__(self):
        self.calls = []
        self._depth = 0

    def __enter__(self):
        from transmogrifai_tpu.ops import pallas_hist as PH
        self._orig = {n: getattr(PH, n) for n in self.NAMES}
        for n, fn in self._orig.items():
            setattr(PH, n, self._wrap(PH, n, fn))
        return self

    def __exit__(self, *exc):
        from transmogrifai_tpu.ops import pallas_hist as PH
        for n, fn in self._orig.items():
            setattr(PH, n, fn)

    def _wrap(self, PH, name, fn):
        def wrapped(*args, **kw):
            if self._depth == 0:  # hist_folds -> hist_pallas is one call
                rec = {"kernel": name,
                       "shapes": [tuple(int(s) for s in a.shape)
                                  for a in args],
                       "xb_dtype": str(args[0].dtype),
                       "interpret": bool(kw.get("interpret", False)),
                       "available": bool(PH.available()),
                       "static": {k: v for k, v in kw.items()
                                  if k != "interpret"}}
                if rec not in self.calls:
                    self.calls.append(rec)
            self._depth += 1
            try:
                return fn(*args, **kw)
            finally:
                self._depth -= 1
        return wrapped


class StreamedFitSpy:
    """Keep what the validator's streamed GLM fit handed to its metric
    pass: the raw fold coefficients B [folds, grid, d] and b0 [folds,
    grid] of the sweep that ran, so that its fold metrics can be
    recomputed exactly from its own coefficients. A validator without
    that seam cannot be checked, and the run says so."""

    def __init__(self):
        self.fits = []      # (B, b0) of every streamed fit, as numpy

    def __enter__(self):
        from transmogrifai_tpu.automl.tuning.validators import Validator
        self._cls = Validator
        self._orig = Validator.__dict__.get("_streamed_fit")
        if self._orig is None:
            return self
        spy = self

        @functools.wraps(self._orig)
        def wrapped(validator, *args, **kw):
            out = spy._orig(validator, *args, **kw)
            spy.fits.append((np.asarray(out[0], np.float32),
                             np.asarray(out[1], np.float32)))
            return out
        Validator._streamed_fit = wrapped
        return self

    def __exit__(self, *exc):
        if self._orig is not None:
            self._cls._streamed_fit = self._orig


# -- plain twins of the kernel dispatchers (numpy, float64) ---------------------

def hist_plain(Xb_t, pay, slot, n_slots: int, n_bins: int,
               derive_count: bool = False) -> np.ndarray:
    """The contract of hist_pallas / hist_folds, summed plainly:
    out[(lane, slot, channel), (feature, bin)] = sum of pay[lane, channel,
    i] over rows i with slot[lane, i] == slot and Xb_t[feature, i] == bin.
    A row whose slot id is >= n_slots is dropped. derive_count appends a
    channel that counts the rows whose last payload channel is > 0."""
    Xb_t = np.asarray(Xb_t).astype(np.int64)
    pay = np.asarray(pay, np.float64)
    slot = np.asarray(slot).astype(np.int64)
    F, N = Xb_t.shape
    lanes = slot.shape[0]
    C = pay.shape[0] // lanes
    cell = np.arange(F)[:, None] * n_bins + Xb_t            # [F, N]
    size = n_slots * F * n_bins
    out = []
    for k in range(lanes):
        p = pay[k * C:(k + 1) * C]
        if derive_count:
            p = np.concatenate([p, (p[C - 1:C] > 0).astype(np.float64)])
        keep = slot[k] < n_slots
        ids = (slot[k, keep][None, :] * (F * n_bins)
               + cell[:, keep]).ravel()
        h = np.stack([np.bincount(ids, np.tile(pc[keep], F), size)
                      for pc in p])                         # [Co, S*F*B]
        out.append(h.reshape(len(p), n_slots, F * n_bins)
                   .transpose(1, 0, 2).reshape(-1, F * n_bins))
    return np.concatenate(out)


def route_plain(Xb_t, node, f_lvl, t_lvl, m_lvl) -> np.ndarray:
    """One level of routing: row i of a lane sits in node[lane, i]; that
    node splits on feature f at bin t; a row goes right when its bin is
    above t, and a missing value (bin 0) by the node's default direction
    m. New id = 2 * node + right."""
    Xb_t = np.asarray(Xb_t).astype(np.int64)
    node = np.asarray(node).astype(np.int64)
    f = np.take_along_axis(np.asarray(f_lvl), node, axis=1)
    t = np.take_along_axis(np.asarray(t_lvl), node, axis=1)
    m = np.take_along_axis(np.asarray(m_lvl), node, axis=1)
    x = Xb_t[f, np.arange(Xb_t.shape[1])[None, :]]          # [lanes, N]
    right = (x > t) | ((x == 0) & (m > 0))
    return (2 * node + right).astype(np.float32)


def route_hist_plain(Xb_t, pay, node, f_lvl, t_lvl, m_lvl, n_nodes: int,
                     n_bins: int, derive_count: bool = False):
    """route_hist's contract: the routed ids, and the histograms of the
    rows that went LEFT, by the node they came from (the right child is
    the parent less the left one, outside the kernel)."""
    new = route_plain(Xb_t, node, f_lvl, t_lvl, m_lvl)
    right = new - 2 * np.asarray(node, np.float32)
    slots = np.where(right > 0, n_nodes, np.asarray(node))   # right: drop
    return hist_plain(Xb_t, pay, slots, n_nodes, n_bins, derive_count), new


def lookup_plain(tbl, idx) -> np.ndarray:
    """table_lookup's contract: out[lane, i] = tbl[lane, idx[lane, i]],
    0 where the id is out of range."""
    tbl = np.asarray(tbl)
    idx = np.asarray(idx).astype(np.int64)
    ok = (idx >= 0) & (idx < tbl.shape[1])
    vals = np.take_along_axis(tbl, np.clip(idx, 0, tbl.shape[1] - 1), 1)
    return np.where(ok, vals, 0.0).astype(np.float32)


def binned_sample(Xs, bins: int, seed: int) -> np.ndarray:
    """[F, rows] bin ids for the kernel checks, made plainly: bin 0 is the
    missing value, a present value sits in 1 + the number of the column's
    `bins - 1` quantile edges at or below it. The data holds no missing
    value, so a seeded 1 % of the entries are made missing: the default
    direction of a split is exercised too."""
    Xs = np.asarray(Xs, np.float32)
    edges = np.quantile(Xs, np.arange(1, bins) / bins, axis=0)   # [B-1, F]
    Xb = 1 + (Xs[None, :, :] >= edges[:, None, :]).sum(axis=0)
    Xb[np.random.default_rng(seed).random(Xb.shape) < 0.01] = 0
    return np.ascontiguousarray(Xb.T)


def _cells_close(got, ref, mass, rtol):
    tol = rtol * mass + 1e-6 * (1.0 + np.abs(ref))
    bad = np.abs(got - ref) > tol
    worst = float(np.max(np.abs(got - ref) / (mass + 1e-6)))
    return not bad.any(), worst


def _compare_hist(got, ref, mass, lanes, co, derive_count, bf16):
    """hist [lanes*slots*co, cols]: count channel exact, g/h per cell
    within the input-rounding bound."""
    got = np.asarray(got, np.float64).reshape(lanes, -1, co, got.shape[-1])
    ref = ref.reshape(got.shape)
    out = {}
    c_in = co - 1 if derive_count else co
    if derive_count:
        out["counts_exact"] = bool(
            np.array_equal(got[:, :, co - 1], ref[:, :, co - 1]))
        require(out["counts_exact"], "derived count channel differs")
    mass = mass.reshape(lanes, -1, c_in, got.shape[-1])
    ok, worst = _cells_close(got[:, :, :c_in], ref[:, :, :c_in], mass,
                             BF16_HIST_RTOL if bf16 else F32_HIST_RTOL)
    out["gh_worst_rel"] = worst
    require(ok, f"g/h cells off by {worst:.2e} relative")
    return out


def kernel_checks(calls, Xb_t, y, masks, margin, *, interpret: bool,
                  binned_tol: float) -> list:
    """Replay every dispatcher call the sweep recorded — same static
    arguments, same tile shape, N cut to a slice — against its plain numpy
    twin on the same arrays."""
    import jax
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import metrics_ops as M
    from transmogrifai_tpu.ops import pallas_hist as PH

    F, N = Xb_t.shape
    Xb_plain = Xb_t
    key = jax.random.PRNGKey(7)
    results = []
    for c in calls:
        name, st = c["kernel"], c["static"]
        Xb_t = jnp.asarray(Xb_plain, c["xb_dtype"])
        k1, k2, k3, k4, key = jax.random.split(key, 5)
        res = {"kernel": name}
        bf16 = bool(st.get("allow_bf16", False))
        t0 = time.perf_counter()
        if name == "hist_pallas" and c["shapes"][0][0] == 1:
            # rank-metric consumer: the binned AuPR of lanes of scores
            # over the same rows, against the exact one
            L, bins = st["n_slots"], st["n_bins"]
            lift = jnp.linspace(0.5, 2.0, L)[:, None] * (y[None, :] - 0.5)
            scores = jax.random.normal(k1, (L, N), jnp.float32) + lift
            wl = jnp.broadcast_to(1.0 - masks[:1], (L, N)) \
                * (jax.random.uniform(k2, (L, N)) < 0.7)
            got = np.asarray(M.au_pr_binned_lanes(scores, y, wl, bins))
            ref = np.array([numpy_au_pr(s, np.asarray(y), w) for s, w in
                            zip(np.asarray(scores), np.asarray(wl))])
            res["binned_au_pr_worst"] = float(np.max(np.abs(got - ref)))
            require(res["binned_au_pr_worst"] <= binned_tol,
                    f"binned AuPR off the exact one by "
                    f"{res['binned_au_pr_worst']:.2e}")
        elif name in ("hist_folds", "hist_pallas"):
            lanes = c["shapes"][2][0]
            C = c["shapes"][1][0] // lanes
            S, B = st["n_slots"], st["n_bins"]
            dc = bool(st.get("derive_count", False))
            pay = _payload(y, masks, margin, lanes, C)
            slot = jax.random.randint(k1, (lanes, N), 0, S + 1) \
                .astype(jnp.float32)          # S = dropped row
            fn = PH.hist_folds if name == "hist_folds" else PH.hist_pallas
            got = fn(Xb_t, pay, slot, n_slots=S, n_bins=B,
                     interpret=interpret, allow_bf16=bf16, derive_count=dc)
            ref = hist_plain(Xb_plain, pay, slot, S, B, dc)
            mass = hist_plain(Xb_plain, np.abs(np.asarray(pay)), slot, S, B)
            res.update(_compare_hist(got, ref, mass, lanes,
                                     C + (1 if dc else 0), dc, bf16))
        elif name in ("route", "route_pallas", "route_hist"):
            node_ix = 2 if name == "route_hist" else 1
            lanes = c["shapes"][node_ix][0]
            n_nodes = st["n_nodes"]
            B = st["n_bins"] if name == "route_hist" \
                else int(Xb_plain.max()) + 1
            node = jax.random.randint(k1, (lanes, N), 0, n_nodes) \
                .astype(jnp.float32)
            f_lvl = jax.random.randint(k2, (lanes, n_nodes), 0, F)
            t_lvl = jax.random.randint(k3, (lanes, n_nodes), 0, B)
            m_lvl = jax.random.randint(k4, (lanes, n_nodes), 0, 2)
            if name == "route_hist":
                C = c["shapes"][1][0] // lanes
                dc = bool(st.get("derive_count", False))
                pay = _payload(y, masks, margin, lanes, C)
                got, got_node = PH.route_hist(
                    Xb_t, pay, node, f_lvl, t_lvl, m_lvl, n_nodes=n_nodes,
                    n_bins=B, interpret=interpret, allow_bf16=bf16,
                    derive_count=dc)
                ref, ref_node = route_hist_plain(
                    Xb_plain, pay, node, f_lvl, t_lvl, m_lvl, n_nodes, B, dc)
                mass, _ = route_hist_plain(
                    Xb_plain, np.abs(np.asarray(pay)), node, f_lvl, t_lvl,
                    m_lvl, n_nodes, B)
                res.update(_compare_hist(got, ref, mass, lanes,
                                         C + (1 if dc else 0), dc, bf16))
            else:
                fn = PH.route if name == "route" else PH.route_pallas
                got_node = fn(Xb_t, node, f_lvl, t_lvl, m_lvl,
                              n_nodes=n_nodes, interpret=interpret)
                ref_node = route_plain(Xb_plain, node, f_lvl, t_lvl,
                                       m_lvl)
            res["routing_identical"] = bool(np.array_equal(
                np.asarray(got_node), ref_node))
            require(res["routing_identical"], "routing decisions differ")
        elif name == "table_lookup":
            lanes, m = c["shapes"][0]
            tbl = jax.random.normal(k1, (lanes, m), jnp.float32)
            idx = jax.random.randint(k2, (lanes, N), -1, m + 1) \
                .astype(jnp.float32)          # -1 and m are out of range
            got = np.asarray(PH.table_lookup(tbl, idx, interpret=interpret))
            res["worst_abs"] = float(np.max(np.abs(
                got - lookup_plain(tbl, idx))))
            require(res["worst_abs"] <= 1e-6,
                    f"table_lookup off by {res['worst_abs']:.2e}")
        else:
            raise CheckFailure(f"no check for dispatcher {name}")
        # every branch above compared on the host, which syncs
        # tmoglint: disable=TPU005  np.asarray of the results blocks
        res["check_s"] = round(time.perf_counter() - t0, 2)
        log(f"kernel check {name} {c['shapes'][0]} ok "
            f"({res['check_s']}s)")
        results.append(res)
    return results


def _payload(y, masks, margin, lanes, C):
    """Fold-major [lanes*C, N] payload: logistic g/h of the sweep's own
    labels under its fold masks at `margin` — values with full f32
    mantissas, so bf16 input rounding is really exercised (channel order
    g..., h)."""
    import jax
    import jax.numpy as jnp
    W = jnp.tile(masks, (-(-lanes // masks.shape[0]), 1))[:lanes]
    p = jax.nn.sigmoid(margin)
    g = W * (p - y)[None, :]
    h = jnp.maximum(W * (p * (1.0 - p))[None, :], 1e-12) * (W > 0)
    chans = [g] * (C - 1) + [h]
    return jnp.stack(chans, axis=1).reshape(lanes * C, -1)


# -- the exact metric ------------------------------------------------------------

def numpy_au_pr(score, y, w):
    """Average precision by an exact sort over the rows with w > 0: the
    sum over distinct scores, from the top, of the recall gained there
    times the precision there. Rows that share a score count together, so
    the order a sort leaves them in does not matter."""
    keep = w > 0
    score, y = score[keep], y[keep]
    order = np.argsort(score)[::-1]
    score, y = score[order], y[order].astype(np.float64)
    last = np.flatnonzero(np.append(score[1:] != score[:-1], True))
    tp = np.cumsum(y)[last]
    prec = tp / (last + 1.0)
    rec = tp / max(tp[-1], 1e-12)
    return float((np.diff(rec, prepend=0.0) * prec).sum())


@functools.lru_cache(maxsize=None)
def _margin_part(chunk: int):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda X, b, start: jnp.matmul(
        jax.lax.dynamic_slice_in_dim(X, start, chunk).astype(jnp.float32),
        b, precision=jax.lax.Precision.HIGHEST))


def _margins(X, beta, b0, chunk: int = 1 << 21) -> np.ndarray:
    """X @ beta + b0 in float32 at `highest` precision, for all rows of the
    device matrix, a chunk of rows at a time so that no float32 copy of
    it is made (one program: the last chunk starts early and overlaps)."""
    import jax.numpy as jnp
    n = X.shape[0]
    chunk = min(chunk, n)
    part, b = _margin_part(chunk), jnp.asarray(beta, jnp.float32)
    out = np.empty(n, np.float32)
    for i in range(0, n, chunk):
        start = min(i, n - chunk)
        out[start:start + chunk] = np.asarray(part(X, b, start))
    return out + np.float32(b0)


# -- the GLM sweep's answer ---------------------------------------------------------

def glm_sweep_answer(best, fits, masks, grids, X, y, *, reference_fold: int,
                     reference_rows: int, tol_metric: float,
                     tol_reference: float) -> dict:
    """Hold the LR sweep that ran to its own answer. For the best LR grid
    point and EVERY fold: the exact AuPR of the sweep's own fold
    coefficients on that fold's held-out rows (float32 margins, exact
    sort) against the fold metric the sweep reported (its binned in-sweep
    pass). For `reference_fold`: a plain float32 proximal-gradient fit on
    `reference_rows` rows of that fold's training part, scored on the same
    held-out rows, against the sweep's coefficients."""
    import jax
    import jax.numpy as jnp

    lr = [v for v in best.validated if v.route == "streamed"]
    require(bool(lr) and len(fits) == 1,
            f"{len(lr)} streamed grid points, {len(fits)} streamed fits "
            f"seen: the sweep's coefficients cannot be read")
    top = max(lr, key=lambda v: v.mean_metric)
    j = grids.index(dict(top.grid))
    B, b0 = fits[0]
    require(B.ndim == 3 and B.shape[0] == masks.shape[0]
            and B.shape[1] >= len(grids) and B.shape[2] == X.shape[1],
            f"fold coefficients of shape {B.shape}")
    yh = np.asarray(y)
    out = {"grid": dict(top.grid), "folds": []}
    worst = 0.0
    for f in range(masks.shape[0]):
        held = 1.0 - masks[f]
        exact = numpy_au_pr(_margins(X, B[f, j], b0[f, j]), yh, held)
        got = float(top.fold_metrics[f])
        out["folds"].append({"sweep": got, "exact": exact})
        worst = max(worst, abs(got - exact))
        if f == reference_fold:
            n = min(reference_rows, X.shape[0])
            with jax.default_matmul_precision("highest"):
                rb, rb0 = _reference_logistic(
                    X[:n].astype(jnp.float32), jnp.asarray(yh[:n]),
                    jnp.asarray(masks[f, :n]),
                    float(top.grid["reg_param"]),
                    float(top.grid["elastic_net_param"]))
            ref = numpy_au_pr(_margins(X, np.asarray(rb), float(rb0)), yh,
                              held)
            out.update(reference_fold=f, reference_rows=int(n),
                       au_pr_sweep_coefficients=exact,
                       au_pr_reference_fit=ref,
                       reference_delta=abs(exact - ref))
    out["metric_worst_delta"] = worst
    log(f"GLM answer: sweep metric vs exact worst {worst:.2e}, sweep vs "
        f"reference fit {out['reference_delta']:.2e}")
    require(worst <= tol_metric,
            f"a fold metric of the sweep is {worst:.2e} off the exact "
            f"AuPR of its own coefficients (bound {tol_metric})")
    require(out["reference_delta"] <= tol_reference,
            f"the sweep's coefficients score {out['reference_delta']:.2e} "
            f"off the plain reference fit (bound {tol_reference})")
    return out


def _reference_logistic(X, y, w, reg, alpha, iters=400):
    """min_b  sum_i w_i logloss_i / sum w + reg(1-alpha)/2 |b|^2
    + reg*alpha |b|_1, intercept unpenalized — plain accelerated
    proximal gradient, nothing shared with ops/glm."""
    import jax
    import jax.numpy as jnp
    n, d = X.shape
    wsum = w.sum()
    l1, l2 = reg * alpha, reg * (1.0 - alpha)
    # Lipschitz bound of the smooth part: sigma' <= 1/4
    lip = 0.25 * jnp.linalg.norm((X * w[:, None]).T @ X / wsum, 2) \
        + 0.25 + l2

    def grad(b, b0):
        r = (jax.nn.sigmoid(X @ b + b0) - y) * w
        return X.T @ r / wsum + l2 * b, r.sum() / wsum

    def body(_, s):
        b, b0, zb, z0, t = s
        g, g0 = grad(zb, z0)
        nb = zb - g / lip
        nb = jnp.sign(nb) * jnp.maximum(jnp.abs(nb) - l1 / lip, 0.0)
        n0 = z0 - g0 / lip
        nt = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        m = (t - 1.0) / nt
        return nb, n0, nb + m * (nb - b), n0 + m * (n0 - b0), nt

    z = jnp.zeros(d, jnp.float32)
    b, b0, _, _, _ = jax.lax.fori_loop(
        0, iters, body, (z, jnp.float32(0), z, jnp.float32(0),
                         jnp.float32(1)))
    return b, b0


# -- the tree sweep's answer -------------------------------------------------------

def gbt_sweep_answer(best, masks, X, y, *, fold: int, train_rows: int,
                     tol: float) -> dict:
    """Hold the tree sweep that ran to a plain reference: for every
    boosted-tree grid point, a plain float32 histogram GBT (same rounds,
    depth, bins, eta, lambda) fitted on `train_rows` rows of the fold's
    training part and scored, by exact AuPR, on ALL the fold's held-out
    rows — the rows the sweep's own fold metric was taken on."""
    import jax.numpy as jnp

    held_idx = np.flatnonzero(masks[fold] == 0)
    train_idx = np.flatnonzero(masks[fold] == 1)[:train_rows]
    Xtr = X[jnp.asarray(train_idx)].astype(jnp.float32)
    Xhe = X[jnp.asarray(held_idx)].astype(jnp.float32)
    yh = np.asarray(y)
    ytr = jnp.asarray(yh[train_idx])
    out = {"fold": fold, "train_rows": int(len(train_idx)),
           "held_rows": int(len(held_idx)), "points": []}
    worst = 0.0
    for v in best.validated:
        g = v.grid
        if "max_depth" not in g:
            continue
        t0 = time.perf_counter()
        margin = np.asarray(plain_gbt(
            Xtr, ytr, Xhe, rounds=int(g["num_round"]),
            depth=int(g["max_depth"]), bins=int(g["max_bins"]),
            eta=float(g["eta"]), lam=float(g.get("reg_lambda", 1.0))))
        ref = numpy_au_pr(margin, yh[held_idx], np.ones(len(held_idx)))
        got = float(v.fold_metrics[fold])
        out["points"].append({"grid": dict(g), "sweep": got,
                              "reference": ref,
                              "s": round(time.perf_counter() - t0, 2)})
        worst = max(worst, abs(got - ref))
    require(bool(out["points"]), "no boosted-tree grid point to check")
    out["worst_delta"] = worst
    log(f"tree answer: sweep fold metric vs plain GBT worst {worst:.2e}")
    require(worst <= tol,
            f"a fold metric of the tree sweep is {worst:.2e} off the plain "
            f"reference GBT (bound {tol})")
    return out


def plain_gbt(Xtr, ytr, Xev, *, rounds: int, depth: int, bins: int,
              eta: float, lam: float, min_child_weight: float = 1.0):
    """XGBoost's histogram algorithm, plainly: quantile bins per column,
    logistic loss from the prior's logit, level-wise growth by the best
    gain G_L^2/(H_L+lam) + G_R^2/(H_R+lam) - G^2/(H+lam) with both
    children's hessian >= min_child_weight, leaves -G/(H+lam) * eta.
    Float32 throughout at `highest` matmul precision; sums by one-hot
    products and table reads by one-hot selection, which every backend
    runs well. Returns the margins of Xev's rows."""
    import jax.numpy as jnp
    qs = jnp.arange(1, bins, dtype=jnp.float32) / bins
    sample = Xtr[::max(1, Xtr.shape[0] // 200_000)]
    edges = jnp.quantile(sample, qs, axis=0).T                # [F, bins-1]
    fit = _plain_gbt_program(rounds, depth, bins)
    return fit(Xtr, ytr, Xev, edges, jnp.float32(eta), jnp.float32(lam),
               jnp.float32(min_child_weight))


@functools.lru_cache(maxsize=None)
def _plain_gbt_program(rounds: int, depth: int, bins: int):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def binned(Xm, edges):   # [n, F] -> [F, n]: number of edges <= x
        return jax.vmap(lambda e, col: jnp.searchsorted(
            e, col, side="right", method="compare_all"))(
                edges, Xm.T).astype(jnp.int32)

    def pick(table, node):
        """table[node] for a table of a few entries, by selection."""
        sel = jax.nn.one_hot(node, table.shape[0], dtype=jnp.float32)
        return (sel * table[None, :].astype(jnp.float32)).sum(axis=1)

    def step(Xb_t, node, f, t):
        """One level down: the row's bin in its node's split feature,
        right when above the node's split bin."""
        fn = pick(f, node).astype(jnp.int32)
        x = jnp.where(jnp.arange(Xb_t.shape[0])[:, None] == fn[None, :],
                      Xb_t, 0).sum(axis=0)
        return 2 * node + (x > pick(t, node)).astype(jnp.int32)

    def sums(node, S, gh):
        """[n, 2S]: g and h of each row in its node's two columns."""
        return (jax.nn.one_hot(node, S, dtype=jnp.float32)[:, :, None]
                * gh[:, None, :]).reshape(node.shape[0], 2 * S)

    def grow(Xb_t, g, h, lam, mcw):
        """One tree: per level each node's split (feature, bin), then the
        leaf each row fell in and the leaves' g and h."""
        gh = jnp.stack([g, h], axis=1)
        node = jnp.zeros(g.shape[0], jnp.int32)
        feats, thrs = [], []
        for level in range(depth):
            S = 1 << level
            P = sums(node, S, gh)
            hist = jax.lax.map(
                lambda col: jnp.matmul(
                    jax.nn.one_hot(col, bins, dtype=jnp.float32).T, P,
                    precision=hi), Xb_t).reshape(-1, bins, S, 2)
            left = jnp.cumsum(hist, axis=1)[:, :-1]           # bin <= t
            tot = hist.sum(axis=1)[0]                         # [S, 2]
            right = tot[None, None] - left

            def score(s):
                return s[..., 0] ** 2 / (s[..., 1] + lam)
            gain = score(left) + score(right) - score(tot)[None, None]
            ok = (left[..., 1] >= mcw) & (right[..., 1] >= mcw)
            flat = jnp.where(ok, gain, -jnp.inf) \
                .transpose(2, 0, 1).reshape(S, -1)            # [S, F*(B-1)]
            at = jnp.argmax(flat, axis=1)
            split = jnp.max(flat, axis=1) > 0
            f = jnp.where(split, at // (bins - 1), 0)
            t = jnp.where(split, at % (bins - 1), bins)       # no split:
            feats.append(f)                                   # all go left
            thrs.append(t)
            node = step(Xb_t, node, f, t)
        L = 1 << depth
        leaves = jnp.matmul(jax.nn.one_hot(node, L, dtype=jnp.float32).T,
                            gh, precision=hi)                 # [L, 2]
        return feats, thrs, node, leaves

    @jax.jit
    def fit(Xtr, y, Xev, edges, eta, lam, mcw):
        Xb_tr, Xb_ev = binned(Xtr, edges), binned(Xev, edges)
        prior = jnp.clip(y.mean(), 1e-6, 1 - 1e-6)
        base = jnp.log(prior / (1 - prior))

        def one_round(carry, _):
            m_tr, m_ev = carry
            p = jax.nn.sigmoid(m_tr)
            feats, thrs, node, leaves = grow(Xb_tr, p - y, p * (1 - p),
                                             lam, mcw)
            value = -leaves[:, 0] / (leaves[:, 1] + lam) * eta
            ev = jnp.zeros(Xb_ev.shape[1], jnp.int32)
            for f, t in zip(feats, thrs):
                ev = step(Xb_ev, ev, f, t)
            return (m_tr + pick(value, node), m_ev + pick(value, ev)), None
        (_, m_ev), _ = jax.lax.scan(
            one_round, (jnp.full(y.shape, base), jnp.full(
                Xb_ev.shape[1], base)), None, length=rounds)
        return m_ev
    return fit
