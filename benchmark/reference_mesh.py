"""What decides `correct` for a sweep over a matrix row-sharded over
several chips: the plain reference and its comparisons, computed shard by
shard and block by block so that neither the whole matrix nor a float32
copy of a shard is ever on one chip or on the host.

Nothing here is the program's (the shared pieces — the exact AuPR, the
proximal-gradient logistic fit, `require` — are benchmark/reference.py's).
From the program the checks take what the timed path produced: the fold
metrics `validate()` returned, the fold coefficients its streamed fit
handed to its metric pass, the fold masks it swept on. Float32 at
`highest` matmul precision on the chips, float64 on the host.

(a) for the best grid point and every fold, the exact AuPR (full sort over
    ALL held-out rows of ALL shards) of the sweep's own coefficients
    against the fold metric it reported. The binned in-sweep metric sits a
    steady offset off the exact one, so the comparison is a window on the
    signed difference: a metric pass that saw only some of the rows, or
    whose counts were not summed over the chips, lands outside it.
(b) for one fold, a plain float32 proximal-gradient fit on rows drawn
    equally from every shard, scored by exact AuPR on all that fold's
    held-out rows, against the sweep's coefficients scored the same way.
(c) the fold rule replayed on ONE chip from its documentation
    (automl/tuning/folds.py: row i's key is the Threefry-2x32 block of the
    counters (i, n + i) under the seed's two key words; one stable sort of
    (key, row id); the fold of row i is the id at sorted position i modulo
    the folds): the sweep's masks equal to it bit for bit on every row,
    every row held out exactly once, fold sizes within one of each other
    over the WHOLE matrix.
(d) the gradient of the logistic objective at the sweep's own
    coefficients, every lane on its fold's training rows of ALL shards,
    summed in float64 on the host from per-block float32 partials. HELD
    is its intercept component, the weighted mean of (p - y): the rounds
    update the intercept by an exact scalar Newton step in float32, so at
    their fixed point it is zero to float32's own noise (1e-7), whatever
    the low-precision products did to the coefficients; rows the rounds
    never saw leave it at their own sampling noise, a quarter of
    sqrt(p (1 - p) / rows of a shard) when one shard of four is missing
    (2e-5 at 25.6M rows), fold by fold. So a shard left out of the rounds
    cannot pass. REPORTED beside it, not held: the coefficient part, a
    lane and as a mean over the lanes without an L1 part. It carries the
    rounding of the rounds' bfloat16 products (half a bfloat16 ulp of a
    coefficient is about the sampling error of 100M rows, so a missing
    shard moves it by little more than a factor of two), and a lane with
    an L1 part stops where the rounds' diagonal soft-threshold does, off
    the exact KKT point by more than that.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from benchmark.harness import log
from benchmark.reference import (
    _reference_logistic, numpy_au_pr, require,
)

#: rows of a shard a chip works on at once (float32 copy: 512 MB at 64
#: columns); the largest divisor of the shard's rows under it is taken, so
#: that sums never count a row twice
CHUNK_ROWS = 1 << 21


def row_shards(a, axis: int = 0) -> list:
    """The one-device pieces of an array sharded on `axis`, in row order."""
    return [s.data for s in sorted(
        a.addressable_shards, key=lambda s: s.index[axis].start or 0)]


def _chunk(local: int) -> int:
    return next(c for c in range(min(CHUNK_ROWS, local), 0, -1)
                if local % c == 0)


# -- the fold rule, replayed --------------------------------------------------

_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def threefry2x32(k0, k1, x0, x1):
    """The Threefry-2x32 block cipher, 20 rounds (Salmon et al. 2011), on
    uint32 arrays: written from the paper, not taken from jax."""
    ks = (k0, k1, k0 ^ k1 ^ np.uint32(0x1BD11BDA))
    x0, x1 = x0 + ks[0], x1 + ks[1]
    for r in range(5):
        for rot in _ROTATIONS[r % 2]:
            x0 = x0 + x1
            x1 = ((x1 << np.uint32(rot)) | (x1 >> np.uint32(32 - rot))) ^ x0
        x0 = x0 + ks[(r + 1) % 3]
        x1 = x1 + ks[(r + 2) % 3] + np.uint32(r + 1)
    return x0, x1


@functools.lru_cache(maxsize=None)
def _replay_program(n: int, folds: int):
    import jax
    import jax.numpy as jnp

    def replay(key):
        i = jax.lax.iota(jnp.uint32, n)
        w0, w1 = threefry2x32(key[0], key[1], i, i + jnp.uint32(n))
        ids = jax.lax.sort((w0, w1, jax.lax.iota(jnp.int32, n)),
                           num_keys=2, is_stable=True)[2]
        return (ids % folds).astype(jnp.uint8)
    return jax.jit(replay)


def replayed_fold_of(seed: int, n: int, folds: int, device):
    """uint8[n] on `device`: the fold that holds each row out, by the
    documented k-fold rule."""
    import jax
    key = np.array([(int(seed) >> 32) & 0xFFFFFFFF,
                    int(seed) & 0xFFFFFFFF], np.uint32)
    return _replay_program(n, folds)(jax.device_put(key, device))


# -- one pass over a shard ----------------------------------------------------

@functools.lru_cache(maxsize=None)
def _shard_program(chunk: int, folds: int):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def part(X, y, M, C, c0, B, b0, lane_fold, start):
        """One block of one shard. C [F, d], c0 [F]: the checked grid
        point's coefficients a fold; B [L, d], b0 [L]: every lane's, with
        lane_fold [L] its fold. Unit sample weights."""
        x = jax.lax.dynamic_slice_in_dim(X, start, chunk).astype(jnp.float32)
        yy = jax.lax.dynamic_slice_in_dim(y, start, chunk)
        mm = jax.lax.dynamic_slice_in_dim(M, start, chunk, axis=1)  # [F, c]
        held = mm == 0.0
        fold_of = jnp.argmax(held, axis=0)
        # every row held out exactly once, the mask nothing but 0 and 1
        once = jnp.all(held.sum(axis=0) == 1) & jnp.all(held | (mm == 1.0))
        m_all = jnp.matmul(x, C.T, precision=hi) + c0[None, :]     # [c, F]
        own = jnp.take_along_axis(m_all, fold_of[:, None], axis=1)[:, 0]
        eta = jnp.matmul(x, B.T, precision=hi) + b0[None, :]       # [c, L]
        wl = mm[lane_fold].T                                       # [c, L]
        r = (jax.nn.sigmoid(eta) - yy[:, None]) * wl
        return (own, fold_of.astype(jnp.uint8), once,
                jnp.matmul(x.T, r, precision=hi), r.sum(axis=0),
                wl.sum(axis=0),
                jnp.zeros(folds, jnp.int32).at[fold_of].add(1))
    return jax.jit(part)


@functools.lru_cache(maxsize=None)
def _margin_program(chunk: int):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda X, b, start: jnp.matmul(
        jax.lax.dynamic_slice_in_dim(X, start, chunk).astype(jnp.float32),
        b, precision=jax.lax.Precision.HIGHEST))


def _kkt_residual(g, B, l1):
    """The penalised gradient's distance from zero, coordinate by
    coordinate: g + l1 sign(b) where b is not zero, what of |g| passes l1
    where it is."""
    return np.where(B != 0.0, g + l1 * np.sign(B),
                    np.sign(g) * np.maximum(np.abs(g) - l1, 0.0))


# -- the sweep's answer ---------------------------------------------------------

def mesh_sweep_answer(best, fits, masks, grids, X, y, *, into: dict,
                      cv_seed: int, reference_fold: int,
                      reference_rows: int, metric_offset_lo: float,
                      metric_offset_hi: float, tol_reference: float,
                      tol_intercept_gradient: float) -> None:
    """Hold the LR sweep that ran on the mesh to (a)-(d) of the module's
    docstring. X [n, d], y [n], masks [F, n] are the sharded device arrays
    the sweep ran on; `fits` what its streamed fit handed to its metric
    pass; `grids` the grid points in the sweep's order. Readings go
    `into` as they are taken, so that a failed run still reports them."""
    import jax
    import jax.numpy as jnp

    lr = [v for v in best.validated if v.route == "streamed"]
    require(bool(lr) and len(fits) == 1,
            f"{len(lr)} streamed grid points, {len(fits)} streamed fits "
            f"seen: the sweep's coefficients cannot be read")
    top = max(lr, key=lambda v: v.mean_metric)
    j = grids.index(dict(top.grid))
    B, b0 = fits[0]
    F, n = int(masks.shape[0]), int(X.shape[0])
    G = len(grids)
    require(B.ndim == 3 and B.shape[0] == F and B.shape[1] >= G
            and B.shape[2] == X.shape[1],
            f"fold coefficients of shape {B.shape}")
    Xs, ys, Ms = row_shards(X), row_shards(y), row_shards(masks, axis=1)
    shards, local = len(Xs), int(Xs[0].shape[0])
    require(all(int(x.shape[0]) == local for x in Xs)
            and all(int(m.shape[1]) == local for m in Ms)
            and shards * local == n,
            "the shards do not hold the same number of rows each")
    chunk = _chunk(local)
    into.update(grid=dict(top.grid), shards=shards, rows_per_shard=local,
                block_rows=chunk)

    lanes_B = B[:, :G].reshape(F * G, -1).astype(np.float32)
    lanes_b0 = b0[:, :G].reshape(F * G).astype(np.float32)
    lane_fold = np.repeat(np.arange(F), G).astype(np.int32)
    part = _shard_program(chunk, F)
    own = np.empty(n, np.float32)
    fold_of = np.empty(n, np.uint8)
    grad = np.zeros((X.shape[1], F * G), np.float64)
    grad0 = np.zeros(F * G, np.float64)
    wsum = np.zeros(F * G, np.float64)
    sizes = np.zeros(F, np.int64)
    once = True
    t0 = time.perf_counter()
    for k, (Xk, yk, Mk) in enumerate(zip(Xs, ys, Ms)):
        dev = next(iter(Xk.devices()))
        consts = [jax.device_put(a, dev) for a in (
            B[:, j].astype(np.float32), b0[:, j].astype(np.float32),
            lanes_B, lanes_b0, lane_fold)]
        outs = [part(Xk, yk, Mk, *consts, start)
                for start in range(0, local, chunk)]
        for i, o in enumerate(outs):
            at = k * local + i * chunk
            own[at:at + chunk] = np.asarray(o[0])
            fold_of[at:at + chunk] = np.asarray(o[1])
            once = once and bool(o[2])
            grad += np.asarray(o[3], np.float64)
            grad0 += np.asarray(o[4], np.float64)
            wsum += np.asarray(o[5], np.float64)
            sizes += np.asarray(o[6], np.int64)
    yh = np.concatenate([np.asarray(v) for v in ys])
    # tmoglint: disable=TPU005  every block was fetched to the host above
    took = time.perf_counter() - t0
    log(f"mesh reference: one pass over {shards} shards ({took:.1f}s)")

    # (c) the fold rule
    into["fold_sizes"] = [int(v) for v in sizes]
    require(once, "a row is not held out exactly once, or a mask entry is "
                  "neither 0 nor 1")
    require(int(sizes.sum()) == n and int(sizes.max() - sizes.min()) <= 1,
            f"fold sizes {into['fold_sizes']} over {n} rows")
    dev0 = next(iter(Xs[0].devices()))
    replay = np.asarray(replayed_fold_of(cv_seed, n, F, dev0))
    into["fold_rows_unlike_replay"] = int((replay != fold_of).sum())
    del replay
    require(into["fold_rows_unlike_replay"] == 0,
            f"{into['fold_rows_unlike_replay']} rows sit in another fold "
            f"than the one-device replay of the documented rule gives")

    # (a) every fold's metric against the exact AuPR of its coefficients
    into["folds"] = []
    exact_ref_fold = None
    for f in range(F):
        held = fold_of == f
        exact = numpy_au_pr(own[held], yh[held], np.ones(int(held.sum())))
        got = float(top.fold_metrics[f])
        into["folds"].append({"sweep": got, "exact": exact,
                              "offset": got - exact})
        if f == reference_fold:
            exact_ref_fold = exact
    offsets = [fo["offset"] for fo in into["folds"]]
    into.update(metric_offset_min=min(offsets),
                metric_offset_max=max(offsets))
    log(f"mesh reference: sweep metric less exact AuPR "
        f"{min(offsets):.3e} .. {max(offsets):.3e}")

    # (d) the objective's gradient at the sweep's coefficients
    l1 = np.tile([float(g["reg_param"]) * float(g["elastic_net_param"])
                  for g in grids], F)
    l2 = np.tile([float(g["reg_param"])
                  * (1.0 - float(g["elastic_net_param"])) for g in grids], F)
    g_lanes = (grad / wsum[None, :]).T + l2[:, None] * lanes_B   # [L, d]
    res = _kkt_residual(g_lanes, lanes_B.astype(np.float64), l1[:, None])
    ridge = res[l1 == 0.0].mean(axis=0)
    into.update(
        intercept_gradient_rms=float(np.sqrt(((grad0 / wsum) ** 2).mean())),
        intercept_gradient_by_fold=[float(v) for v in
                                    (grad0 / wsum).reshape(F, G).mean(1)],
        gradient_rms_a_lane=[float(v) for v in
                             np.sqrt((res ** 2).mean(axis=1))],
        gradient_rms_of_ridge_lane_mean=float(np.sqrt((ridge ** 2).mean())),
        train_rows_a_fold=[int(v) for v in wsum[::G]])

    # (b) the plain fit, on rows drawn equally from every shard
    take = min(reference_rows // shards, local)
    g = dict(top.grid)
    Xr = jnp.concatenate([jax.device_put(x[:take], dev0) for x in Xs]) \
        .astype(jnp.float32)
    yr = jnp.concatenate([jax.device_put(v[:take], dev0) for v in ys])
    wr = jnp.concatenate([jax.device_put(m[reference_fold, :take], dev0)
                          for m in Ms])
    with jax.default_matmul_precision("highest"):
        rb, rb0 = _reference_logistic(Xr, yr, wr, float(g["reg_param"]),
                                      float(g["elastic_net_param"]))
    del Xr
    rb, rb0 = np.asarray(rb, np.float32), float(rb0)
    margin = _margin_program(chunk)
    ref = np.empty(n, np.float32)
    for k, Xk in enumerate(Xs):
        bk = jax.device_put(rb, next(iter(Xk.devices())))
        for i, start in enumerate(range(0, local, chunk)):
            at = k * local + i * chunk
            ref[at:at + chunk] = np.asarray(margin(Xk, bk, start))
    held = fold_of == reference_fold
    au_ref = numpy_au_pr(ref[held] + np.float32(rb0), yh[held],
                         np.ones(int(held.sum())))
    into.update(reference_fold=reference_fold,
                reference_rows=int(take * shards),
                au_pr_sweep_coefficients=exact_ref_fold,
                au_pr_reference_fit=au_ref,
                reference_delta=abs(exact_ref_fold - au_ref))
    log(f"mesh reference: sweep vs plain fit {into['reference_delta']:.2e}, "
        f"intercept gradient rms {into['intercept_gradient_rms']:.3e}, "
        f"coefficient gradient rms of the ridge lanes' mean "
        f"{into['gradient_rms_of_ridge_lane_mean']:.3e}")

    require(metric_offset_lo <= min(offsets)
            and max(offsets) <= metric_offset_hi,
            f"a fold metric of the sweep less the exact AuPR of its own "
            f"coefficients over all held-out rows is {min(offsets):.3e} .. "
            f"{max(offsets):.3e}, outside [{metric_offset_lo}, "
            f"{metric_offset_hi}]")
    require(into["intercept_gradient_rms"] <= tol_intercept_gradient,
            f"the weighted mean of (p - y) over ALL training rows at the "
            f"sweep's coefficients has rms {into['intercept_gradient_rms']:.3e}"
            f" over the lanes (bound {tol_intercept_gradient}): the rounds "
            f"did not fit all the rows")
    require(into["reference_delta"] <= tol_reference,
            f"the sweep's coefficients score {into['reference_delta']:.2e} "
            f"off the plain reference fit (bound {tol_reference})")
