"""The plain references of the random-forest sweep: what decides `correct`
in `sweep-rf`. Nothing here imports the program.

The model is Spark ML's RandomForestClassifier as upstream's
OpRandomForestClassifier wraps it (DefaultSelectorParams: gini, maxBins 32,
subsamplingRate 1.0, featureSubsetStrategy auto), on quantile-binned
columns:

- a tree weighs row i by a Poisson(subsamplingRate) draw (bagging with
  replacement) times the row's fold weight;
- every NODE draws its own subset of `features_per_node` columns
  (sqrt(F) for a classifier under `auto`) and splits on the best allowed
  candidate (feature f, bin t; rows with bin <= t go left) by Gini gain a
  unit of the node's weight,

      gain = [ sum_k GL_k^2 / HL + sum_k GR_k^2 / HR - sum_k G_k^2 / H ] / H

  (k the two classes, G the class weights, H their sum), allowed when the
  feature is in the node's subset, both children hold at least
  minInstancesPerNode rows and gain > minInfoGain; a node with no allowed
  candidate sends all its rows left, to a child that draws again;
- a leaf's value is the weighted share of class 1 among its rows; the
  forest's score is the mean over trees, and the metric the exact AuPR.

Departures from Spark, each the program's documented rule
(ops/trees.grow_tree): minInstancesPerNode counts the ROWS of positive
weight in a child (Spark counts bagged copies); minInfoGain is compared
strictly; trees are complete to `depth` (a dead node repeats as
all-rows-left); bins are 1 + the number of the column's quantile edges at
or below the value, bin 0 the missing value.

- `plain_forest`: such a forest fitted in float32 at `highest` matmul
  precision with its OWN generator's bootstrap draws and node subsets, the
  histograms blocked one-hot products; scores of other rows by traversal.
- `binned`, `level_histograms`, `traverse`: the pieces, also used to
  replay a tree somebody else grew.
- `split_replay`: every node of a given tree, under given row weights and
  node subsets, held to the rule above on exact (integer) histograms of
  ALL the rows: the chosen split is allowed, its gain is within a relative
  tolerance of the best allowed one, every leaf is the exact weighted mean.
- `forest_sweep_answer`: the comparisons of `sweep-rf` themselves.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from benchmark.harness import log
from benchmark.reference import numpy_au_pr, require
from benchmark.reference_wide import _as_bf16

BLOCK_ROWS = 1 << 16


# -- the pieces ------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _binned_program():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(X, edges):
        """[n, F] values -> [F, n] bins (int8 while they fit): 1 + the
        number of the column's edges at or below the value, 0 for a
        missing one."""
        def one(col, e):
            x = col.astype(jnp.float32)
            b = 1 + (x[:, None] >= e[None, :]).sum(axis=1)
            return jnp.where(jnp.isnan(x), 0, b).astype(
                jnp.int8 if edges.shape[1] < 127 else jnp.int32)
        return jax.lax.map(lambda a: one(*a), (X.T, edges))
    return run


def binned(X, edges):
    """Bin ids [F, n] of the device matrix X [n, F] under the given
    per-column edges [F, bins - 1]."""
    import jax.numpy as jnp
    return _binned_program()(X, jnp.asarray(edges, jnp.float32))


@functools.lru_cache(maxsize=None)
def _histogram_program(n_nodes: int, bins: int, block: int):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def run(Xb_t, node, pay):
        """sum over rows i of pay[c, i] into cell (node[i], c, f,
        Xb_t[f, i]): [n_nodes, C, F, bins] float32, a block of rows at a
        time as one-hot products at `highest` (exact for integer pay
        below 2^24 a cell). Rows past the last whole block are padded
        with zero pay."""
        F, n = Xb_t.shape
        C = pay.shape[0]
        pad = (-n) % block
        Xp = jnp.pad(Xb_t, ((0, 0), (0, pad)))
        npad = jnp.pad(node, (0, pad))
        ppad = jnp.pad(pay, ((0, 0), (0, pad)))
        nb = (n + pad) // block

        def body(acc, i):
            xb = jax.lax.dynamic_slice(Xp, (0, i * block), (F, block))
            nd = jax.lax.dynamic_slice(npad, (i * block,), (block,))
            pb = jax.lax.dynamic_slice(ppad, (0, i * block), (C, block))
            oh = (xb[:, None, :] == jnp.arange(bins, dtype=xb.dtype)[
                None, :, None]).astype(jnp.float32)           # [F, B, blk]
            q = (jax.nn.one_hot(nd, n_nodes, dtype=jnp.float32).T[:, None]
                 * pb[None]).reshape(n_nodes * C, block)
            return acc + jnp.einsum("qi,fbi->qfb", q, oh, precision=hi), \
                None
        acc0 = jnp.zeros((n_nodes * C, F, bins), jnp.float32)
        acc, _ = jax.lax.scan(body, acc0, jnp.arange(nb))
        return acc.reshape(n_nodes, C, F, bins)
    return run


def level_histograms(Xb_t, node, pay, n_nodes: int, bins: int) -> np.ndarray:
    """[n_nodes, C, F, bins] float64: the payload sums of each (node,
    feature, bin) cell over all rows."""
    block = min(BLOCK_ROWS, int(Xb_t.shape[1]))
    out = _histogram_program(n_nodes, bins, block)(Xb_t, node, pay)
    return np.asarray(out, np.float64)


@functools.lru_cache(maxsize=None)
def _step_program():
    import jax
    import jax.numpy as jnp

    def pick(table, node):
        sel = jax.nn.one_hot(node, table.shape[0], dtype=jnp.float32)
        return (sel * table[None, :].astype(jnp.float32)).sum(axis=1)

    @jax.jit
    def step(Xb_t, node, f, t, m):
        """One level down: a row goes right when the bin of its node's
        split feature is above the node's split bin, a missing value
        (bin 0) when the node says so."""
        fn = pick(f, node).astype(jnp.int32)
        x = jnp.where(jnp.arange(Xb_t.shape[0])[:, None] == fn[None, :],
                      Xb_t, 0).sum(axis=0)
        right = (x > pick(t, node)) | ((x == 0) & (pick(m, node) > 0.5))
        return 2 * node + right.astype(jnp.int32)

    @jax.jit
    def value(leaf, node):   # a read, not a product: the value bit for bit
        return leaf[node]
    return step, value


def traverse(Xb_t, feat, thresh, miss, depth: int):
    """The leaf [n] int32 each row of Xb_t lands on, walking one tree in
    heap layout (level d's nodes at 2^d - 1 ...)."""
    import jax.numpy as jnp
    step, _ = _step_program()
    node = jnp.zeros(Xb_t.shape[1], jnp.int32)
    for d in range(depth):
        lo, n = (1 << d) - 1, 1 << d
        node = step(Xb_t, node, jnp.asarray(feat[lo:lo + n]),
                    jnp.asarray(thresh[lo:lo + n]),
                    jnp.asarray(miss[lo:lo + n]))
    return node


def tree_values(Xb_t, tree: dict, depth: int):
    """The leaf value [n] float32 of every row under one tree (dict of
    feat, thresh, miss [2^depth - 1] and leaf [2^depth])."""
    import jax.numpy as jnp
    _, value = _step_program()
    node = traverse(Xb_t, tree["feat"], tree["thresh"], tree["miss"], depth)
    return value(jnp.asarray(tree["leaf"], jnp.float32), node)


# -- the split rule ---------------------------------------------------------------

def candidate_gains(hist: np.ndarray):
    """From one level's histograms [nodes, 3, F, B] of (class-1 weight,
    weight, rows): the two-class Gini gain a unit of node weight of every
    candidate [nodes, F, B] (rows with bin <= t left), and the rows on
    each side."""
    left = np.cumsum(hist, axis=3)
    tot = left[:, :, :1, -1:]
    right = tot - left

    def score(s):     # sum_k G_k^2 / H over the two classes
        g1, h = s[:, 0], s[:, 1]
        with np.errstate(divide="ignore", invalid="ignore"):
            return np.where(h > 0, (g1 * g1 + (h - g1) ** 2) / h, 0.0)
    h_tot = tot[:, 1]
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = np.where(h_tot > 0, (score(left) + score(right) - score(tot))
                        / h_tot, -np.inf)
    return gain, left[:, 2], right[:, 2]


def split_replay(Xb_t, y, weight, tree: dict, subsets: np.ndarray, *,
                 depth: int, bins: int, min_instances: float,
                 min_info_gain: float) -> dict:
    """Hold one grown tree to the split rule along its OWN routing.

    Xb_t [F, n] bins, y [n] 0/1, weight [n] the tree's row weights (fold
    mask x bootstrap draw: small integers, so every histogram is exact),
    tree its feat / thresh / miss [2^depth - 1] and leaf [2^depth], subsets
    [2^depth - 1, F] bool the columns each node drew. Returns what was
    found, judged by nobody: gain_shortfall (the largest relative
    shortfall of a chosen split's gain under the best allowed one),
    the counts of chosen splits that were not allowed and of dead nodes
    that had an allowed candidate (both outside a 1e-4 relative band of
    minInfoGain, inside which float32 may fall either way), leaf_worst
    (largest |leaf - exact weighted mean|), and what the named wrong
    builds would have made of the same nodes."""
    import jax.numpy as jnp
    step, _ = _step_program()
    pay = jnp.stack([weight * y, weight,
                     (weight > 0).astype(jnp.float32)])
    node = jnp.zeros(Xb_t.shape[1], jnp.int32)
    out = {"nodes": 0, "live_nodes": 0, "splits": 0, "dead_with_rows": 0,
           "gain_shortfall": 0.0, "not_allowed": [], "dead_but_allowed": [],
           "subset_sizes": sorted({int(s.sum()) for s in subsets}),
           "distinct_subsets": len({s.tobytes() for s in subsets}),
           "trap_between_1x_2x": 0, "dead_between_half_1x": 0,
           "min_gain_margin": np.inf}
    band = 1e-4 * max(min_info_gain, 1e-12)
    last_bin = bins - 1
    for d in range(depth):
        lo, n = (1 << d) - 1, 1 << d
        hist = level_histograms(Xb_t, node, pay, n, bins)
        gain, c_left, c_right = candidate_gains(hist)
        sub = subsets[lo:lo + n]
        counts_ok = (c_left >= min_instances) & (c_right >= min_instances)
        allowed = counts_ok & sub[:, :, None] & (gain > min_info_gain)
        firm = counts_ok & sub[:, :, None] & (gain > min_info_gain + band)
        for k in range(n):
            f, t = int(tree["feat"][lo + k]), int(tree["thresh"][lo + k])
            rows = float(hist[k, 2, 0].sum())
            out["nodes"] += 1
            out["live_nodes"] += rows > 0
            best = float(gain[k][allowed[k]].max()) if allowed[k].any() \
                else None
            if t >= last_bin:                       # the program: dead
                out["dead_with_rows"] += rows > 0
                if firm[k].any():
                    out["dead_but_allowed"].append(
                        [d, k, float(gain[k][firm[k]].max())])
                loose = counts_ok[k] & sub[k][:, None]
                top = float(gain[k][loose].max()) if loose.any() else 0.0
                out["dead_between_half_1x"] += \
                    0.5 * min_info_gain < top <= min_info_gain
                continue
            out["splits"] += 1
            g = float(gain[k, f, t])
            ok = bool(counts_ok[k, f, t] and sub[k, f]
                      and g > min_info_gain - band)
            if not ok:
                out["not_allowed"].append(
                    [d, k, f, t, g, bool(sub[k, f]),
                     float(c_left[k, f, t]), float(c_right[k, f, t])])
                continue
            out["gain_shortfall"] = max(
                out["gain_shortfall"], (best - g) / best if best else 0.0)
            out["trap_between_1x_2x"] += g <= 2.0 * min_info_gain
            out["min_gain_margin"] = min(out["min_gain_margin"],
                                         g / min_info_gain
                                         if min_info_gain > 0 else np.inf)
        node = step(Xb_t, node, jnp.asarray(tree["feat"][lo:lo + n]),
                    jnp.asarray(tree["thresh"][lo:lo + n]),
                    jnp.asarray(tree["miss"][lo:lo + n]))
    # the leaves: exact sums of the rows each one holds
    L = 1 << depth
    sums = level_histograms(Xb_t[:1], node, pay, L, bins).sum(axis=3)[:, :, 0]
    with np.errstate(divide="ignore", invalid="ignore"):
        exact = np.where(sums[:, 2] > 0, sums[:, 0] / sums[:, 1], 0.0)
    leaf = np.asarray(tree["leaf"], np.float64)
    out["leaf_worst"] = float(np.abs(leaf - exact).max())
    out["leaf_worst_if_bf16"] = float(np.abs(_as_bf16(exact) - exact).max())
    out["leaves_with_rows"] = int((sums[:, 2] > 0).sum())
    out["min_gain_margin"] = float(out["min_gain_margin"])
    return out


# -- the plain forest ----------------------------------------------------------------

def node_subsets(rng: np.random.Generator, n_nodes: int, n_feat: int,
                 k: int) -> np.ndarray:
    """[n_nodes, F] bool: k columns a node, drawn without replacement."""
    order = np.argsort(rng.random((n_nodes, n_feat)), axis=1)
    sub = np.zeros((n_nodes, n_feat), bool)
    np.put_along_axis(sub, order[:, :k], True, axis=1)
    return sub


def quantile_edges(X, bins: int) -> np.ndarray:
    """[F, bins - 1] quantile edges of the columns, from at most 200 000
    evenly spaced rows."""
    import jax.numpy as jnp
    sample = X[::max(1, X.shape[0] // 200_000)].astype(jnp.float32)
    qs = jnp.arange(1, bins, dtype=jnp.float32) / bins
    return np.asarray(jnp.quantile(sample, qs, axis=0).T, np.float32)


def grow_plain_tree(Xb_t, y, weight, rng, *, depth: int, bins: int,
                    min_instances: float, min_info_gain: float,
                    features_per_node: int) -> dict:
    """One tree by the rule at the head of this file (bins counts the
    missing-value bin). Returns feat, thresh, miss [2^depth - 1] and leaf
    [2^depth]."""
    import jax.numpy as jnp
    step, _ = _step_program()
    F = int(Xb_t.shape[0])
    pay = jnp.stack([weight * y, weight, (weight > 0).astype(jnp.float32)])
    node = jnp.zeros(Xb_t.shape[1], jnp.int32)
    feats, thrs = [], []
    for d in range(depth):
        n = 1 << d
        hist = level_histograms(Xb_t, node, pay, n, bins)
        gain, c_left, c_right = candidate_gains(hist)
        sub = node_subsets(rng, n, F, features_per_node)
        ok = ((c_left >= min_instances) & (c_right >= min_instances)
              & sub[:, :, None] & (gain > min_info_gain))
        flat = np.where(ok, gain, -np.inf).reshape(n, -1)
        at = flat.argmax(axis=1)
        split = np.isfinite(flat.max(axis=1))
        f = np.where(split, at // bins, 0).astype(np.int32)
        t = np.where(split, at % bins, bins - 1).astype(np.int32)
        feats.append(f)
        thrs.append(t)
        node = step(Xb_t, node, jnp.asarray(f), jnp.asarray(t),
                    jnp.zeros(n, jnp.int32))
    L = 1 << depth
    sums = level_histograms(Xb_t[:1], node, pay, L, bins).sum(axis=3)[:, :, 0]
    with np.errstate(divide="ignore", invalid="ignore"):
        leaf = np.where(sums[:, 2] > 0, sums[:, 0] / sums[:, 1], 0.0)
    feat = np.concatenate(feats)
    return {"feat": feat, "thresh": np.concatenate(thrs),
            "miss": np.zeros_like(feat), "leaf": leaf.astype(np.float32)}


def plain_forest(Xtr, ytr, Xev, *, trees: int, depth: int, bins: int,
                 min_instances: float, min_info_gain: float,
                 features_per_node: int, subsample: float = 1.0,
                 seed: int = 0) -> np.ndarray:
    """The mean class-1 vote [n_ev] of a plain forest fitted on (Xtr, ytr)
    for the rows of Xev; `bins` is maxBins (the missing-value bin is added
    here). Bootstrap draws and node subsets come from numpy's generator
    seeded with `seed`."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    edges = quantile_edges(Xtr, bins)
    Xb_tr, Xb_ev = binned(Xtr, edges), binned(Xev, edges)
    y = jnp.asarray(ytr, jnp.float32)
    votes = jnp.zeros(Xb_ev.shape[1], jnp.float32)
    for _ in range(trees):
        w = jnp.asarray(rng.poisson(subsample, Xb_tr.shape[1]), jnp.float32)
        tree = grow_plain_tree(
            Xb_tr, y, w, rng, depth=depth, bins=bins + 1,
            min_instances=min_instances, min_info_gain=min_info_gain,
            features_per_node=features_per_node)
        votes = votes + tree_values(Xb_ev, tree, depth)
    return np.asarray(votes, np.float64) / trees


# -- the comparisons ---------------------------------------------------------------

def bootstrap_answer(stats: list, prefixes: np.ndarray, *, rate: float,
                     rows: int, tol_moment: float, tol_corr: float) -> dict:
    """The bootstrap draws of one grid point's trees: `stats` the (mean,
    variance) of every tree's whole vector, `prefixes` [trees, m] the
    first m draws of each. Poisson(rate): mean and variance `rate`, and
    no two trees' vectors equal or correlated."""
    means = np.array([s[0] for s in stats])
    vars_ = np.array([s[1] for s in stats])
    p = np.asarray(prefixes, np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        c = np.corrcoef(p) if p.shape[0] > 1 else np.ones((1, 1))
    # a constant vector has no correlation to give: counted as 1
    off = np.nan_to_num(np.abs(c - np.eye(len(c))), nan=1.0)
    equal = sum(bool(np.array_equal(p[i], p[j]))
                for i in range(len(p)) for j in range(i))
    out = {"trees": len(stats), "rows": rows, "prefix": int(p.shape[1]),
           "mean_worst": float(np.abs(means - rate).max()),
           "variance_worst": float(np.abs(vars_ - rate).max()),
           "largest_draw": float(p.max()),
           "correlation_worst": float(off.max()), "equal_pairs": equal}
    require(out["mean_worst"] <= tol_moment
            and out["variance_worst"] <= tol_moment,
            f"a tree's bootstrap draws have mean off {rate} by "
            f"{out['mean_worst']:.2e}, variance by "
            f"{out['variance_worst']:.2e} (bound {tol_moment})")
    # the largest draw anybody should see among this many: P(X > m) x
    # draws under 1e-3
    pmf, m, draws = np.exp(-rate), 0, p.size
    tail = 1.0 - pmf
    while tail * draws >= 1e-3:
        m += 1
        pmf *= rate / m
        tail -= pmf
    out["largest_plausible_draw"] = m
    require(out["largest_draw"] <= m,
            f"a bootstrap draw of {out['largest_draw']:.0f} among {draws} "
            f"Poisson({rate}) draws: nothing over {m} is plausible")
    require(equal == 0 and out["correlation_worst"] <= tol_corr,
            f"{equal} pairs of trees share a bootstrap vector; largest "
            f"correlation {out['correlation_worst']:.2e} (bound {tol_corr})")
    return out


def forest_sweep_answer(best, points: list, masks, X, y, *, into: dict,
                        fold: int, replay_trees: int, depth: int, bins: int,
                        trees: int, min_info_gain: float, subsample: float,
                        features_per_node: int, train_rows: int,
                        tol_gain: float, tol_leaf: float, tol_vote: float,
                        tol_metric: float, tol_moment: float,
                        tol_corr: float, tol_plain: float) -> dict:
    """Hold the forest sweep that ran to the plain rule. `points` is what
    the timed path itself produced, one dict a grid point in grid order:
    `edges` the program's bin edges, `trees` the (feat, thresh, miss, leaf)
    of every (tree, fold) it grew ([trees, folds, ...]), `subsets` every
    tree's per-node columns [trees, nodes, F], `boot_head` the whole
    bootstrap vectors of its first trees [k, n], `boot_stats` /
    `boot_prefix` of all of them, `votes_fold` the summed leaf values it
    accumulated for `fold` [n], `min_instances`. Fills `into` as it goes
    (a failed check leaves what was read) and raises CheckFailure."""
    import jax.numpy as jnp
    yh = np.asarray(y)
    held_idx = np.flatnonzero(masks[fold] == 0)
    train_idx = np.flatnonzero(masks[fold] == 1)
    at = next(i for i, v in enumerate(best.validated)
              if v.grid == best.best_grid)
    pt = points[at]
    into.update(fold=fold, best_point=at, points=[])

    # the program's bins, made again from its edges
    Xb_t = binned(X, pt["edges"])
    same = bool(jnp.array_equal(Xb_t, pt["Xb"].T.astype(Xb_t.dtype)))
    into["bins_identical"] = same
    require(same, "the program's binned matrix is not 1 + the number of its "
                  "own edges at or below each value")
    yd = jnp.asarray(yh, jnp.float32)
    mask = jnp.asarray(masks[fold], jnp.float32)

    # (b) split replay of the best point's first trees, fold `fold`
    t0 = time.perf_counter()
    into["replay"] = []
    for t in range(replay_trees):
        tree = {k: np.asarray(v[t, fold]) for k, v in pt["trees"].items()}
        w = mask * jnp.asarray(pt["boot_head"][t], jnp.float32)
        r = split_replay(Xb_t, yd, w, tree, np.asarray(pt["subsets"][t]),
                         depth=depth, bins=bins + 1,
                         min_instances=pt["min_instances"],
                         min_info_gain=min_info_gain)
        r["tree"] = t
        into["replay"].append(r)
        log(f"split replay tree {t}: {r['splits']} splits of "
            f"{r['live_nodes']} live nodes, gain shortfall "
            f"{r['gain_shortfall']:.2e}, leaves within {r['leaf_worst']:.2e} "
            f"(bf16 leaves: {r['leaf_worst_if_bf16']:.2e}); "
            f"{r['trap_between_1x_2x']} splits between 1x and 2x "
            f"minInfoGain, {r['dead_between_half_1x']} dead nodes between "
            f"0.5x and 1x")
        require(not r["not_allowed"],
                f"tree {t}: chosen splits the rule does not allow "
                f"{r['not_allowed'][:3]}")
        require(not r["dead_but_allowed"],
                f"tree {t}: nodes left unsplit that had an allowed "
                f"candidate {r['dead_but_allowed'][:3]}")
        require(r["gain_shortfall"] <= tol_gain,
                f"tree {t}: a chosen split's gain is {r['gain_shortfall']:.2e}"
                f" under the best allowed (bound {tol_gain})")
        require(r["leaf_worst"] <= tol_leaf,
                f"tree {t}: a leaf is {r['leaf_worst']:.2e} off its exact "
                f"weighted mean (bound {tol_leaf})")
        require(r["subset_sizes"] == [features_per_node]
                and 2 * r["distinct_subsets"] > r["nodes"],
                f"tree {t}: node subsets of sizes {r['subset_sizes']}, "
                f"{r['distinct_subsets']} distinct among {r['nodes']} nodes")
    into["replay_s"] = round(time.perf_counter() - t0, 2)

    # (c) the fold's votes by plain traversal of every tree it returned,
    # and their exact AuPR against the fold metric the sweep reported
    Xb_held = Xb_t[:, jnp.asarray(held_idx)]
    votes = jnp.zeros(len(held_idx), jnp.float32)
    votes_bf16 = votes
    for t in range(trees):
        tree = {k: np.asarray(v[t, fold]) for k, v in pt["trees"].items()}
        votes = votes + tree_values(Xb_held, tree, depth)
        votes_bf16 = votes_bf16 + tree_values(
            Xb_held, dict(tree, leaf=_as_bf16(tree["leaf"])), depth)
    votes = np.asarray(votes, np.float64)
    got_votes = np.asarray(pt["votes_fold"])[held_idx].astype(np.float64)
    ones = np.ones(len(held_idx))
    exact = numpy_au_pr(votes, yh[held_idx], ones)
    got = float(best.validated[at].fold_metrics[fold])
    into["votes"] = {
        "held_rows": int(len(held_idx)),
        "vote_worst": float(np.abs(votes - got_votes).max()),
        "vote_worst_if_bf16_leaves": float(np.abs(
            np.asarray(votes_bf16, np.float64) - got_votes).max()),
        "exact_au_pr": exact, "sweep_fold_metric": got,
        "metric_delta": abs(got - exact),
        "metric_delta_if_bf16_leaves": abs(got - numpy_au_pr(
            np.asarray(votes_bf16, np.float64), yh[held_idx], ones)),
        "metric_delta_if_a_tree_were_missing": abs(got - numpy_au_pr(
            votes - np.asarray(tree_values(Xb_held, tree, depth)),
            yh[held_idx], ones))}
    log(f"votes: traversal within {into['votes']['vote_worst']:.2e} of the "
        f"sweep's sums; exact AuPR {exact:.6f} vs fold metric {got:.6f}")
    require(into["votes"]["vote_worst"] <= tol_vote,
            f"a held-out row's summed vote is "
            f"{into['votes']['vote_worst']:.2e} off the plain traversal of "
            f"the returned trees (bound {tol_vote})")
    require(into["votes"]["metric_delta"] <= tol_metric,
            f"the sweep's fold metric is {into['votes']['metric_delta']:.2e}"
            f" off the exact AuPR of its own trees' votes (bound "
            f"{tol_metric})")

    # (d) the bootstrap draws, (e) the plain forest, every point
    tr = jnp.asarray(train_idx[:train_rows])
    Xtr, Xhe = X[tr].astype(jnp.float32), \
        X[jnp.asarray(held_idx)].astype(jnp.float32)
    worst = 0.0
    for i, (p, v) in enumerate(zip(points, best.validated)):
        rec = {"grid": dict(v.grid)}
        into["points"].append(rec)
        rec["bootstrap"] = bootstrap_answer(
            p["boot_stats"], p["boot_prefix"], rate=subsample,
            rows=int(X.shape[0]), tol_moment=tol_moment, tol_corr=tol_corr)
        t0 = time.perf_counter()
        ref_votes = plain_forest(
            Xtr, yh[train_idx[:train_rows]], Xhe, trees=trees, depth=depth,
            bins=bins, min_instances=p["min_instances"],
            min_info_gain=min_info_gain,
            features_per_node=features_per_node, subsample=subsample,
            seed=1 + i)
        ref = numpy_au_pr(ref_votes, yh[held_idx], ones)
        got_i = float(v.fold_metrics[fold])
        rec.update(sweep=got_i, reference=ref,
                   s=round(time.perf_counter() - t0, 2))
        worst = max(worst, abs(got_i - ref))
        log(f"plain forest, point {i}: sweep {got_i:.6f} vs plain "
            f"{ref:.6f} on {len(tr)} training rows ({rec['s']} s)")
    into["plain_worst_delta"] = worst
    require(worst <= tol_plain,
            f"a fold metric of the forest sweep is {worst:.2e} off the "
            f"plain forest (bound {tol_plain})")
    return into
