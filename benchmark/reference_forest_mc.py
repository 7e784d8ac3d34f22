"""The plain references of the MULTICLASS random-forest sweep: what decides
`correct` in `sweep-rf-multiclass`. Nothing here imports the program (the
kernel replays call the program's dispatchers, as every cell's do).

The model is Spark ML's RandomForestClassifier as upstream's
OpRandomForestClassifier wraps it inside MultiClassificationModelSelector
(DefaultSelectorParams: gini, maxBins 32, subsamplingRate 1.0,
featureSubsetStrategy auto), on quantile-binned columns, under a label of K
classes:

- a tree weighs row i by a Poisson(subsamplingRate) draw (bagging with
  replacement) times the row's fold weight;
- every NODE draws its own subset of `features_per_node` columns (sqrt(F)
  for a classifier under `auto`: 8 of 64) and splits on the best allowed
  candidate (feature f, bin t; rows with bin <= t go left) by the K-class
  Gini gain a unit of the node's weight,

      gain = imp(node) - HL / H imp(L) - HR / H imp(R),   imp = 1 - sum_k p_k^2
           = [ sum_k GL_k^2 / HL + sum_k GR_k^2 / HR - sum_k G_k^2 / H ] / H

  (G_k the weight of class k, H their sum), allowed when the feature is in
  the node's subset, both children hold at least minInstancesPerNode rows
  and gain > minInfoGain — the threshold as upstream's grid states it, NOT
  halved (the binary cell's one-channel payload halves it;
  reference_forest.py); a node with no allowed candidate sends all its rows
  left, to a child that draws again;
- a leaf's value is the weighted class distribution of its rows; a row's
  votes are the sum over trees of its leaves' distributions, its class the
  largest vote, and the metric the error: the share of held-out rows whose
  class is not the label.

Departures from Spark's RandomForestClassifier, each the program's
documented rule (ops/trees.grow_tree), as in reference_forest.py:
minInstancesPerNode counts the ROWS of positive weight in a child (Spark
counts bagged copies); minInfoGain is compared strictly; trees are complete
to `depth` (a dead node repeats as all-rows-left); bins are 1 + the number
of the column's quantile edges (this repo's sketch, not Spark's) at or
below the value, bin 0 the missing value; the bootstrap draws come from
JAX's generator (the program's own vectors are replayed, and held to
Poisson(1)'s moments); a tie between candidates of equal gain goes to the
first in (feature, bin) order, a tie between classes of equal vote to the
lower class.

EXACT SUMS. Every payload here is a whole number (bootstrap draw x 0/1
fold weight x class indicator), so the blocked float32 one-hot products of
reference_forest.level_histograms at `highest` are exact integers (under
2^24 a cell a block, blocks added exactly), and every gain and leaf is
float64 arithmetic on exact sums of ALL the training rows.

- `class_gains`, `split_replay`: every node of a given tree, under given
  row weights and node subsets, held to the rule above along its own
  routing; beside it what the named wrong builds would have made of the
  same nodes (a halved threshold, a class channel left out, one-vs-rest
  gains, unnormalised or bfloat16 leaves).
- `plain_forest_mc`: such a forest grown here, own bootstrap and subsets.
- `class_kernel_twins`: the program's histogram dispatchers replayed under
  [class id, weight] planes against float64 sums, and its lookup at K
  values a leaf.
- `forest_mc_answer`: the comparisons of `sweep-rf-multiclass` themselves.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import reference
from benchmark.harness import log
from benchmark.reference_forest import (
    _step_program, binned, bootstrap_answer, level_histograms, node_subsets,
    quantile_edges, traverse)
from benchmark.reference_forest_reg import Held
from benchmark.reference_wide import _as_bf16


# -- the split rule ---------------------------------------------------------------

def class_payload(weight, y, classes: int):
    """[K + 2, n] float32 on the device: weight x (y == k) for every class,
    the weight, and the rows of positive weight."""
    import jax.numpy as jnp
    ind = (jnp.asarray(y, jnp.float32)[None, :]
           == jnp.arange(classes, dtype=jnp.float32)[:, None])
    return jnp.concatenate([ind.astype(jnp.float32) * weight[None, :],
                            weight[None, :],
                            (weight > 0).astype(jnp.float32)[None, :]])


def class_gains(hist: np.ndarray, channels=None):
    """From one level's exact histograms [nodes, K + 2, F, B] of (class
    weights, weight, rows): the K-class Gini gain a unit of node weight of
    every candidate [nodes, F, B] (rows with bin <= t left), and the rows
    on each side. `channels` restricts the sum of squares to those classes
    (a wrong build: a channel left out)."""
    K = hist.shape[1] - 2
    left = np.cumsum(hist, axis=3)
    tot = left[:, :, :1, -1:]
    right = tot - left
    ks = list(range(K)) if channels is None else list(channels)

    def score(s):     # sum_k G_k^2 / H
        h = s[:, K]
        with np.errstate(divide="ignore", invalid="ignore"):
            return np.where(h > 0, (s[:, ks] ** 2).sum(axis=1) / h, 0.0)
    h_tot = tot[:, K]
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = np.where(h_tot > 0, (score(left) + score(right) - score(tot))
                        / h_tot, -np.inf)
    return gain, left[:, K + 1], right[:, K + 1]


def one_vs_rest_gains(hist: np.ndarray, k: int):
    """The two-class Gini gain of class k against the rest, from the same
    histograms: what a one-vs-rest tree for class k would maximise."""
    K = hist.shape[1] - 2
    two = np.stack([hist[:, k], hist[:, K] - hist[:, k], hist[:, K],
                    hist[:, K + 1]], axis=1)
    return class_gains(two)[0]


def _argbest(gain, ok):
    flat = np.where(ok, gain, -np.inf)
    return np.unravel_index(np.argmax(flat), flat.shape)


def split_replay(Xb_t, y, weight, tree: dict, subsets: np.ndarray, *,
                 depth: int, bins: int, classes: int, min_instances: float,
                 min_info_gain: float) -> dict:
    """Hold one grown tree to the split rule along its OWN routing.

    Xb_t [F, n] bins, y [n] class ids, weight [n] the tree's row weights
    (fold mask x bootstrap draw: whole numbers), tree its feat / thresh /
    miss [2^depth - 1] and leaf [2^depth, K], subsets [2^depth - 1, F] bool
    the columns each node drew. Returns what was found, judged by nobody:
    gain_shortfall (the largest relative shortfall of a chosen split's gain
    under the best allowed one), splits_off_best, the chosen splits that
    were not allowed and the dead nodes that had an allowed candidate (both
    outside a 1e-4 relative band of minInfoGain), leaf_worst (largest
    |leaf_k - exact weighted share of class k|), and what the named wrong
    builds would have made of the same nodes."""
    import jax.numpy as jnp
    step, _ = _step_program()
    K = classes
    pay = class_payload(weight, y, K)
    node = jnp.zeros(Xb_t.shape[1], jnp.int32)
    out = {"nodes": 0, "live_nodes": 0, "splits": 0, "dead_with_rows": 0,
           "gain_shortfall": 0.0, "splits_off_best": 0, "not_allowed": [],
           "dead_but_allowed": [],
           "subset_sizes": sorted({int(s.sum()) for s in subsets}),
           "distinct_subsets": len({s.tobytes() for s in subsets}),
           "dead_between_half_1x": 0, "splits_between_1x_2x": 0,
           "min_gain_margin": np.inf, "best_root_gain": None,
           "stopped_by_threshold": 0,
           "left_out_splits_differ": 0, "left_out_gain_shortfall": 0.0,
           "ovr_splits_differ": 0, "ovr_gain_shortfall": 0.0}
    band = 1e-4 * max(min_info_gain, 1e-12)
    last_bin = bins - 1
    for d in range(depth):
        lo, n = (1 << d) - 1, 1 << d
        hist = level_histograms(Xb_t, node, pay, n, bins)
        gain, c_left, c_right = class_gains(hist)
        gain_out, _, _ = class_gains(hist, channels=range(K - 1))
        big = int(hist[:, :K, 0].sum(axis=(0, 2)).argmax())   # largest class
        gain_ovr = one_vs_rest_gains(hist, big)
        sub = subsets[lo:lo + n]
        loose = (c_left >= min_instances) & (c_right >= min_instances) \
            & sub[:, :, None]
        allowed = loose & (gain > min_info_gain)
        firm = loose & (gain > min_info_gain + band)
        for k in range(n):
            f, t = int(tree["feat"][lo + k]), int(tree["thresh"][lo + k])
            rows = float(hist[k, K + 1, 0].sum())
            out["nodes"] += 1
            out["live_nodes"] += rows > 0
            best = float(gain[k][allowed[k]].max()) if allowed[k].any() \
                else None
            top = float(gain[k][loose[k]].max()) if loose[k].any() else 0.0
            if d == 0:
                out["best_root_gain"] = top
            if best is not None:
                at = _argbest(gain[k], allowed[k])
                # the wrong builds' own best candidates at this node
                for name, g_wrong in (("left_out", gain_out[k]),
                                      ("ovr", gain_ovr[k])):
                    ok_w = loose[k] & (g_wrong > min_info_gain)
                    if ok_w.any():
                        at_w = _argbest(g_wrong, ok_w)
                        out[name + "_splits_differ"] += at_w != at
                        out[name + "_gain_shortfall"] = max(
                            out[name + "_gain_shortfall"],
                            (best - float(gain[k][at_w])) / best)
                    else:
                        out[name + "_splits_differ"] += 1
            if t >= last_bin:                       # the program: dead
                out["dead_with_rows"] += rows > 0
                if firm[k].any():
                    out["dead_but_allowed"].append(
                        [d, k, float(gain[k][firm[k]].max())])
                out["dead_between_half_1x"] += \
                    0.5 * min_info_gain < top <= min_info_gain
                # rows a side were there: the threshold alone stopped it
                out["stopped_by_threshold"] += bool(
                    rows > 0 and loose[k].any() and top <= min_info_gain)
                continue
            out["splits"] += 1
            g = float(gain[k, f, t])
            ok = bool(loose[k, f, t] and g > min_info_gain - band)
            if not ok:
                out["not_allowed"].append(
                    [d, k, f, t, g, bool(sub[k, f]),
                     float(c_left[k, f, t]), float(c_right[k, f, t])])
                continue
            out["gain_shortfall"] = max(
                out["gain_shortfall"], (best - g) / best if best else 0.0)
            out["splits_off_best"] += bool(best) and g < best
            out["splits_between_1x_2x"] += g <= 2.0 * min_info_gain
            out["min_gain_margin"] = min(out["min_gain_margin"],
                                         g / min_info_gain
                                         if min_info_gain > 0 else np.inf)
        node = step(Xb_t, node, jnp.asarray(tree["feat"][lo:lo + n]),
                    jnp.asarray(tree["thresh"][lo:lo + n]),
                    jnp.asarray(tree["miss"][lo:lo + n]))
    exact, sums = leaf_distributions(Xb_t, node, pay, 1 << depth, bins, K)
    leaf = np.asarray(tree["leaf"], np.float64)              # [leaves, K]
    out["leaf_worst"] = float(np.abs(leaf - exact).max())
    out["leaf_worst_if_bf16"] = float(np.abs(_as_bf16(exact) - exact).max())
    out["leaf_worst_if_unnormalised"] = float(
        np.abs(sums[:, :K] - exact).max())
    out["leaf_sums_off_one"] = float(np.abs(
        leaf.sum(axis=1) - (sums[:, K + 1] > 0)).max())
    out["leaves_with_rows"] = int((sums[:, K + 1] > 0).sum())
    out["smallest_leaf_rows"] = int(sums[:, K + 1][sums[:, K + 1] > 0].min())
    out["min_gain_margin"] = float(out["min_gain_margin"]) \
        if np.isfinite(out["min_gain_margin"]) else None   # no split at all
    for key in ("splits_off_best", "dead_between_half_1x", "live_nodes",
                "dead_with_rows", "splits_between_1x_2x",
                "stopped_by_threshold", "left_out_splits_differ",
                "ovr_splits_differ"):
        out[key] = int(out[key])
    return out


def leaf_distributions(Xb_t, node, pay, n_leaves: int, bins: int,
                       classes: int) -> tuple:
    """(exact weighted class distribution [n_leaves, K], 0 where a leaf
    holds no row; the leaves' exact sums [n_leaves, K + 2])."""
    sums = level_histograms(Xb_t[:1], node, pay, n_leaves, bins) \
        .sum(axis=3)[:, :, 0]
    with np.errstate(divide="ignore", invalid="ignore"):
        dist = np.where(sums[:, classes + 1:] > 0,
                        sums[:, :classes] / sums[:, classes:classes + 1], 0.0)
    return dist, sums


# -- the plain forest ----------------------------------------------------------------

def grow_plain_tree(Xb_t, y, weight, rng, *, depth: int, bins: int,
                    classes: int, min_instances: float, min_info_gain: float,
                    features_per_node: int) -> dict:
    """One tree by the rule at the head of this file (bins counts the
    missing-value bin), on exact sums and float64 gains. Returns feat,
    thresh, miss [2^depth - 1] and leaf [2^depth, K]."""
    import jax.numpy as jnp
    step, _ = _step_program()
    F = int(Xb_t.shape[0])
    pay = class_payload(weight, y, classes)
    node = jnp.zeros(Xb_t.shape[1], jnp.int32)
    feats, thrs = [], []
    for d in range(depth):
        n = 1 << d
        gain, c_left, c_right = class_gains(
            level_histograms(Xb_t, node, pay, n, bins))
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
    leaf, _ = leaf_distributions(Xb_t, node, pay, 1 << depth, bins, classes)
    feat = np.concatenate(feats)
    return {"feat": feat, "thresh": np.concatenate(thrs),
            "miss": np.zeros_like(feat), "leaf": leaf.astype(np.float32)}


def tree_votes(Xb_t, tree: dict, depth: int) -> np.ndarray:
    """[K, n] float64: the leaf distribution every row lands on under one
    tree, by plain traversal (a read of the leaf table, bit for bit)."""
    node = np.asarray(traverse(Xb_t, tree["feat"], tree["thresh"],
                               tree["miss"], depth))
    return np.asarray(tree["leaf"], np.float64)[node].T


def plain_forest_mc(Xtr, ytr, Xev, *, classes: int, trees: int, depth: int,
                    bins: int, min_instances: float, min_info_gain: float,
                    features_per_node: int, subsample: float = 1.0,
                    seed: int = 0) -> np.ndarray:
    """The summed class votes [K, n_ev] (float64) of a plain forest fitted
    on (Xtr, ytr) for the rows of Xev; `bins` is maxBins (the missing-value
    bin is added here). Bootstrap draws and node subsets come from numpy's
    generator seeded with `seed`."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    edges = quantile_edges(Xtr, bins)
    Xb_tr, Xb_ev = binned(Xtr, edges), binned(Xev, edges)
    y = jnp.asarray(ytr, jnp.float32)
    total = np.zeros((classes, Xb_ev.shape[1]))
    for _ in range(trees):
        w = jnp.asarray(rng.poisson(subsample, Xb_tr.shape[1]), jnp.float32)
        tree = grow_plain_tree(
            Xb_tr, y, w, rng, depth=depth, bins=bins + 1, classes=classes,
            min_instances=min_instances, min_info_gain=min_info_gain,
            features_per_node=features_per_node)
        total += tree_votes(Xb_ev, tree, depth)
    return total


def vote_error(votes, y) -> float:
    """The exact error of class votes [K, n] against labels [n]: the share
    of rows whose largest vote (the lower class on a tie) is not the
    label."""
    pred = np.argmax(np.asarray(votes), axis=0)
    return float((pred != np.asarray(y).astype(np.int64)).mean())


# -- the kernels under class channels --------------------------------------------------

def class_kernel_twins(calls, Xb_t, y, masks, *, into: list, classes: int,
                       seed: int, interpret: bool) -> list:
    """Replay every histogram dispatcher call the sweep recorded (hist_folds
    and route_hist: same static arguments, the cell's lanes, N cut to a
    slice) under [class id, weight] planes — the sweep's own labels, w a
    seeded Poisson(1) draw under the folds' masks — against
    reference.hist_plain (float64) of the K class channels and the count
    laid out plainly; then the lookup at K values a leaf. Every sum is a
    whole number: the kernels must be EXACT. Fills `into`; raises after the
    last call."""
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import pallas_hist as PH
    rng = np.random.default_rng(seed)
    F, N = Xb_t.shape
    K = classes
    ids = np.asarray(y, np.float32)
    mk = np.asarray(masks, np.float32)
    out, hold = into, Held()
    for c in calls:
        name, st = c["kernel"], dict(c["static"])
        if name not in ("hist_folds", "route_hist"):
            continue
        lanes = c["shapes"][2][0]
        C = c["shapes"][1][0] // lanes
        hold(C == 2 and st.get("classes") == K,
             f"{name}: {C} planes a lane under classes={st.get('classes')},"
             f" not [class id, weight] under classes={K}")
        w = rng.poisson(1.0, (lanes, N)).astype(np.float32) \
            * mk[np.arange(lanes) % mk.shape[0]]
        pay = np.stack([np.broadcast_to(ids, w.shape), w],
                       axis=1).reshape(2 * lanes, N)
        ind = (ids[None, :] == np.arange(K, dtype=np.float32)[:, None])
        plain = np.concatenate(
            [ind[None] * w[:, None, :], (w > 0)[:, None, :]],
            axis=1).astype(np.float64).reshape(lanes * (K + 1), N)
        Xb = jnp.asarray(Xb_t, c["xb_dtype"])
        B = st["n_bins"]
        t0 = time.perf_counter()
        if name == "hist_folds":
            S = st["n_slots"]
            slot = rng.integers(0, S + 1, (lanes, N)).astype(np.float32)
            got = PH.hist_folds(Xb, jnp.asarray(pay), jnp.asarray(slot),
                                interpret=interpret, **st)
            ref, routed, nodes = reference.hist_plain(
                Xb_t, plain, slot, S, B), None, None
        else:
            S = st["n_nodes"]
            node = rng.integers(0, S, (lanes, N)).astype(np.float32)
            tables = [rng.integers(0, hi, (lanes, S)).astype(np.int32)
                      for hi in (F, B, 2)]
            got, nodes = PH.route_hist(
                Xb, jnp.asarray(pay), jnp.asarray(node),
                *map(jnp.asarray, tables), interpret=interpret, **st)
            ref, routed = reference.route_hist_plain(
                Xb_t, plain, node, *tables, S, B)
        g = np.asarray(got, np.float64)
        res = {"kernel": name, "lanes": lanes, "slots": S, "classes": K,
               "rows_a_slot": int(g.shape[0] // (lanes * S)),
               "worst_abs": float(np.abs(g - ref.reshape(g.shape)).max()),
               # tmoglint: disable=TPU005  compared on the host: synced
               "check_s": round(time.perf_counter() - t0, 2)}
        if routed is not None:
            res["routing_identical"] = bool(
                np.array_equal(np.asarray(nodes), routed))
        out.append(res)
        log(f"class-channel twin {name} lanes {lanes} slots {S}: "
            f"{res['rows_a_slot']} rows a slot, worst |sum - float64 sum| "
            f"{res['worst_abs']}, {res['check_s']} s")
        hold(res["rows_a_slot"] == K + 1,
             f"{name}: {res['rows_a_slot']} rows a (lane, slot), not K + 1")
        hold(res["worst_abs"] == 0.0,
             f"{name} at {S} slots: a class sum or a count is "
             f"{res['worst_abs']} off the float64 sum of whole numbers")
        hold(res.get("routing_identical", True),
             f"{name}: routing decisions differ")
    hold({t["kernel"] for t in out} == {"hist_folds", "route_hist"},
         f"the histogram dispatchers replayed: "
         f"{sorted({t['kernel'] for t in out})}")
    # the lookup at K values a leaf: one call a class, the same ids
    look = next((c for c in calls if c["kernel"] == "table_lookup"), None)
    hold(look is not None, "the sweep recorded no table_lookup call")
    if look is not None:
        lanes, m = look["shapes"][0]
        tbl = rng.random((lanes, m, K)).astype(np.float32)
        idx = rng.integers(-1, m + 1, (lanes, N)).astype(np.float32)
        worst = max(float(np.abs(np.asarray(PH.table_lookup(
            jnp.asarray(tbl[:, :, k]), jnp.asarray(idx),
            interpret=interpret)) - reference.lookup_plain(
                tbl[:, :, k], idx)).max()) for k in range(K))
        out.append({"kernel": "table_lookup", "lanes": lanes, "leaves": m,
                    "classes": K, "worst_abs": worst})
        hold(worst == 0.0, f"table_lookup at K = {K} values a leaf is "
                           f"{worst} off the plain read")
    hold.settle()
    return out


# -- the comparisons ---------------------------------------------------------------

def forest_mc_answer(best, points: list, all_votes: list, masks, X, y, *,
                     into: dict, classes: int, fold: int, replay_trees: int,
                     depth: int, bins: int, trees: int, subsample: float,
                     features_per_node: int, train_rows: int,
                     tol_gain: float, tol_leaf: float, tol_vote: float,
                     tol_metric: float, tol_moment: float, tol_corr: float,
                     tol_plain: float, order_gap: float,
                     threshold_binds: bool = True) -> dict:
    """Hold the multiclass forest sweep that ran to the plain rule.
    `points` is what the timed path itself produced, one dict a grid point
    in grid order (the driver's spy: the program's bin edges and binned
    matrix, every lane's tree with leaf [trees, folds, leaves, K], every
    tree's node subsets, the first trees' bootstrap vectors, all trees'
    moments and prefixes, `min_instances`) with `min_info_gain` beside it;
    `all_votes` the summed leaf distributions [folds, K, n] each point
    accumulated. `threshold_binds`: every later grid point's replayed
    tree must hold a node its threshold alone stops (the cell's points grow
    different trees; a toy rehearsal's depth-3 trees need not). Fills
    `into` as it goes (a failed check leaves what was read) and raises
    CheckFailure."""
    import jax.numpy as jnp
    K = classes
    yh = np.asarray(y, np.float32)
    folds = int(masks.shape[0])
    held_idx = np.flatnonzero(masks[fold] == 0)
    train_idx = np.flatnonzero(masks[fold] == 1)
    first = points[0]
    into.update(fold=fold, classes=K, points=[])
    hold = Held()

    Xb_t = binned(X, first["edges"])
    same = bool(jnp.array_equal(Xb_t, first["Xb"].T.astype(Xb_t.dtype)))
    into["bins_identical"] = same
    hold(same, "the program's binned matrix is not 1 + the number of its "
               "own edges at or below each value")
    yd = jnp.asarray(yh)
    mask = jnp.asarray(masks[fold], jnp.float32)

    # (b) split replay, fold `fold`: the first trees of the first point,
    # and the first tree of every other point (whose threshold must bind)
    t0 = time.perf_counter()
    into["replay"] = []
    jobs = [(0, t) for t in range(replay_trees)] \
        + [(i, 0) for i in range(1, len(points))]
    for i, t in jobs:
        p = points[i]
        tree = {k: np.asarray(v[t, fold]) for k, v in p["trees"].items()}
        w = mask * jnp.asarray(p["boot_head"][t], jnp.float32)
        r = split_replay(Xb_t, yd, w, tree, np.asarray(p["subsets"][t]),
                         depth=depth, bins=bins + 1, classes=K,
                         min_instances=p["min_instances"],
                         min_info_gain=p["min_info_gain"])
        r.update(point=i, tree=t, min_info_gain=p["min_info_gain"])
        into["replay"].append(r)
        log(f"split replay point {i} tree {t}: {r['splits']} splits of "
            f"{r['live_nodes']} live nodes (root gain "
            f"{r['best_root_gain']:.4f}), gain shortfall "
            f"{r['gain_shortfall']:.2e} ({r['splits_off_best']} off the "
            f"exact best), leaves within {r['leaf_worst']:.2e}; a channel "
            f"left out: {r['left_out_splits_differ']} splits differ, "
            f"shortfall {r['left_out_gain_shortfall']:.2e}; one-vs-rest: "
            f"{r['ovr_splits_differ']} differ, {r['ovr_gain_shortfall']:.2e}"
            f"; bf16 leaves {r['leaf_worst_if_bf16']:.2e}; "
            f"{r['stopped_by_threshold']} nodes stopped by the threshold, "
            f"{r['dead_between_half_1x']} of them above half of it")
        hold(not r["not_allowed"],
             f"point {i} tree {t}: chosen splits the rule does not allow "
             f"{r['not_allowed'][:3]}")
        hold(not r["dead_but_allowed"],
             f"point {i} tree {t}: nodes left unsplit that had an "
             f"allowed candidate {r['dead_but_allowed'][:3]}")
        hold(r["gain_shortfall"] <= tol_gain,
             f"point {i} tree {t}: a chosen split's gain is "
             f"{r['gain_shortfall']:.2e} under the best allowed (bound "
             f"{tol_gain})")
        hold(r["leaf_worst"] <= tol_leaf,
             f"point {i} tree {t}: a leaf's class share is "
             f"{r['leaf_worst']:.2e} off the exact distribution (bound "
             f"{tol_leaf})")
        hold(r["subset_sizes"] == [features_per_node]
             and 2 * r["distinct_subsets"] > r["nodes"],
             f"point {i} tree {t}: node subsets of sizes "
             f"{r['subset_sizes']} (the configuration: "
             f"{features_per_node}), {r['distinct_subsets']} distinct "
             f"among {r['nodes']} nodes")
    # the grid's points must grow different trees: the later points'
    # threshold stops a node the first point's lets split
    binds = [r["stopped_by_threshold"] for r in into["replay"]
             if r["point"] > 0]
    into["threshold_binds"] = binds
    hold(not threshold_binds or all(b > 0 for b in binds),
         f"a later grid point's replayed tree has no node its threshold "
         f"stops: {binds}")
    # tmoglint: disable=TPU005  every replay's sums came to the host
    into["replay_s"] = round(time.perf_counter() - t0, 2)

    # (c) fold `fold`'s vote sums by plain traversal of every tree of the
    # first point against the sweep's; then EVERY fold's exact error of the
    # sweep's own votes against its reported metric
    Xb_held = Xb_t[:, jnp.asarray(held_idx)]
    total = np.zeros((K, len(held_idx)))
    total_bf16 = np.zeros_like(total)
    for t in range(trees):
        tree = {k: np.asarray(v[t, fold]) for k, v in first["trees"].items()}
        last = tree_votes(Xb_held, tree, depth)
        total += last
        total_bf16 += tree_votes(
            Xb_held, dict(tree, leaf=_as_bf16(tree["leaf"])), depth)
    got_votes = np.asarray(all_votes[0][fold])[:, held_idx] \
        .astype(np.float64)
    y_held = yh[held_idx]
    exact = vote_error(got_votes, y_held)
    got = float(best.validated[0].fold_metrics[fold])
    into["votes"] = {
        "held_rows": int(len(held_idx)),
        "vote_worst": float(np.abs(total - got_votes).max()),
        "vote_worst_if_bf16_leaves": float(
            np.abs(total_bf16 - got_votes).max()),
        "vote_worst_if_a_tree_were_missing": float(
            np.abs(total - last - got_votes).max()),
        "exact_error": exact, "traversal_error": vote_error(total, y_held),
        "sweep_fold_metric": got, "metric_delta": abs(got - exact),
        "metric_delta_if_bf16_leaves": abs(
            got - vote_error(total_bf16, y_held)),
        "metric_delta_if_a_tree_were_missing": abs(
            got - vote_error(total - last, y_held))}
    log(f"votes: traversal within {into['votes']['vote_worst']:.2e} of the "
        f"sweep's sums; exact error {exact:.7f} vs fold metric {got:.7f}")
    hold(into["votes"]["vote_worst"] <= tol_vote,
         f"a held-out row's vote for a class is "
         f"{into['votes']['vote_worst']:.2e} off the plain traversal of "
         f"the returned trees (bound {tol_vote})")
    hold(into["votes"]["metric_delta"] <= tol_metric,
         f"the sweep's fold metric is {into['votes']['metric_delta']:.2e}"
         f" off the exact error of its own votes (bound {tol_metric})")
    worst, exact_means = 0.0, []
    for i, v in enumerate(best.validated):
        per_fold = []
        for f in range(folds):
            idx = np.flatnonzero(masks[f] == 0)
            per_fold.append(vote_error(
                np.asarray(all_votes[i][f])[:, idx], yh[idx]))
            worst = max(worst, abs(per_fold[-1] - float(v.fold_metrics[f])))
        exact_means.append(float(np.mean(per_fold)))
    into["every_fold_metric_delta"] = worst
    hold(worst <= tol_metric,
         f"a fold metric of the sweep is {worst:.2e} off the exact error "
         f"of its own votes (bound {tol_metric})")

    # (g) the grid points' order
    sweep_means = [float(np.mean(v.fold_metrics)) for v in best.validated]
    at = int(np.argmin(sweep_means))
    wrong = [[i, j] for i in range(len(exact_means))
             for j in range(len(exact_means))
             if exact_means[i] + order_gap < exact_means[j]
             and not sweep_means[i] < sweep_means[j]]
    into["order"] = {"exact_means": exact_means, "sweep_means": sweep_means,
                     "gap": order_gap, "misordered": wrong}
    hold(not wrong, f"grid points the sweep orders unlike the exact mean "
                    f"errors of its own votes: {wrong}")
    hold(best.validated[at].grid == best.best_grid,
         f"the winner is {best.best_grid}, not the lowest mean error of "
         f"{sweep_means}")

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
        ref = vote_error(plain_forest_mc(
            Xtr, yh[train_idx[:train_rows]], Xhe, classes=K, trees=trees,
            depth=depth, bins=bins, min_instances=p["min_instances"],
            min_info_gain=p["min_info_gain"],
            features_per_node=features_per_node, subsample=subsample,
            seed=1 + i), y_held)
        got_i = float(v.fold_metrics[fold])
        rec.update(sweep=got_i, reference=ref,
                   s=round(time.perf_counter() - t0, 2))
        worst = max(worst, abs(got_i - ref))
        log(f"plain forest, point {i}: sweep {got_i:.6f} vs plain "
            f"{ref:.6f} on {len(tr)} training rows ({rec['s']} s)")
    into["plain_worst_delta"] = worst
    hold(worst <= tol_plain,
         f"a fold error of the forest sweep is {worst:.2e} off the plain "
         f"forest (bound {tol_plain})")
    hold.settle()
    return into
