"""The plain references of the regression-forest sweep: what decides
`correct` in `sweep-rf-regression`. Nothing here imports the program.

The model is Spark ML's RandomForestRegressor as upstream's
OpRandomForestRegressor wraps it (DefaultSelectorParams: variance impurity,
maxBins 32, subsamplingRate 1.0, featureSubsetStrategy auto), on
quantile-binned columns, under a REAL-VALUED label:

- a tree weighs row i by a Poisson(subsamplingRate) draw (bagging with
  replacement) times the row's fold weight;
- every NODE draws its own subset of ceil(F / 3) columns (`auto` for a
  regression forest: Spark's DecisionTreeMetadata takes the ceiling, 22 of
  64) and splits on the best allowed candidate (feature f, bin t; rows with
  bin <= t go left) by Spark's variance gain a unit of the node's weight,

      gain = imp(node) - HL / H imp(L) - HR / H imp(R)
           = [ GL^2 / HL + GR^2 / HR - G^2 / H ] / H

  (imp the weighted variance of the label, G the weighted label sums, H the
  weights), allowed when the feature is in the node's subset, both children
  hold at least minInstancesPerNode rows and gain > minInfoGain — the
  threshold as upstream's grid states it, in label^2 units, NOT halved (the
  classifier's one-channel payload halves it; reference_forest.py);
- a leaf's value is the weighted mean of the label of its rows; the
  forest's prediction is the mean over trees, and the metric the RMSE over
  the held-out rows.

Departures from Spark, each the program's documented rule
(ops/trees.grow_tree), as in reference_forest.py: minInstancesPerNode
counts the ROWS of positive weight in a child, minInfoGain is compared
strictly, trees are complete to `depth`, bin 0 is the missing value. The
node subset's size is Spark's own since PR 46 (no departure).

EXACT SUMS. A float32 one-hot product summed over 8M rows is itself rounded
~1e-6, which is what the program is to be held to. So the sums here are
integers: the label is read once as a fixed-point number, yq = round(y x
2^21) (exact for |y| >= 4, where a float32's last bit is 2^-21 or more;
off by at most 2^-22 below), the weights are whole draws, the payload w x
(yq + 16 x 2^21) is a non-negative int32 cut into four 8-bit limbs, each
limb's one-hot product over a block of 65 536 rows is an integer under 2^24
(exact in float32 at any precision) and the blocks are added in uint32. The
limbs are put together in float64 on the host, where the 16 x H comes off
again: every G here is the exact sum of w x yq / 2^21.

- `exact_level_sums`, `variance_gains`: the pieces.
- `split_replay`: every node of a given tree, under given row weights and
  node subsets, held to the rule above along its own routing; beside it
  what a payload rounded ONCE to bfloat16 (the program before PR 46) would
  have made of the same nodes.
- `plain_forest_reg`: such a forest grown here, own bootstrap and subsets.
- `real_payload_twins`: the program's histogram dispatchers replayed under
  a real payload against reference.hist_plain (float64).
- `forest_reg_answer`: the comparisons of `sweep-rf-regression` themselves.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from benchmark import reference
from benchmark.harness import log
from benchmark.reference import require
from benchmark.reference_forest import (
    BLOCK_ROWS, _step_program, binned, bootstrap_answer, node_subsets,
    quantile_edges, tree_values)
from benchmark.reference_wide import _as_bf16

class Held:
    """Bounds applied after every reading is taken: a failed one is kept
    and the checks go on, so that a run that fails reports all it read;
    `settle` raises the lot."""

    def __init__(self):
        self.failed = []

    def __call__(self, cond: bool, what: str) -> None:
        if not cond:
            log(f"HELD: {what}")
            self.failed.append(what)

    def settle(self) -> None:
        require(not self.failed, "; ".join(self.failed))


SCALE_BITS = 21
OFFSET = 16          # added to the label so that every payload is >= 0
LIMBS = 4            # 8 bits each: payloads under 2^31


# -- exact sums ---------------------------------------------------------------------

def fixed_point(y):
    """round(y x 2^21) as int32 [n] on the device (|y| < 16)."""
    import jax.numpy as jnp
    return jnp.round(jnp.asarray(y, jnp.float32)
                     * float(1 << SCALE_BITS)).astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def _exact_program(n_nodes: int, bins: int, block: int, payloads: int):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def run(Xb_t, node, w, vq):
        """uint32 [n_nodes, payloads * LIMBS + 2, F, bins]: per (node,
        feature, bin) cell the sums of each 8-bit limb of each int32
        payload row of vq [payloads, n], of the whole weights w and of the
        rows of positive weight. Rows past the last whole block are padded
        with nothing."""
        F, n = Xb_t.shape
        pad = (-n) % block
        Xp = jnp.pad(Xb_t, ((0, 0), (0, pad)))
        npad = jnp.pad(node, (0, pad))
        wpad = jnp.pad(w, (0, pad))
        vpad = jnp.pad(vq, ((0, 0), (0, pad)))
        C = payloads * LIMBS + 2

        def body(acc, i):
            xb = jax.lax.dynamic_slice(Xp, (0, i * block), (F, block))
            nd = jax.lax.dynamic_slice(npad, (i * block,), (block,))
            wb = jax.lax.dynamic_slice(wpad, (i * block,), (block,))
            vb = jax.lax.dynamic_slice(vpad, (0, i * block),
                                       (payloads, block))
            limbs = [((vb >> (8 * k)) & 255).astype(jnp.float32)
                     for k in range(LIMBS)]                  # [P, blk] each
            pay = jnp.concatenate(
                [jnp.stack(limbs, axis=1).reshape(payloads * LIMBS, block),
                 wb[None, :], (wb > 0).astype(jnp.float32)[None, :]])
            oh = (xb[:, None, :] == jnp.arange(bins, dtype=xb.dtype)[
                None, :, None]).astype(jnp.float32)           # [F, B, blk]
            q = (jax.nn.one_hot(nd, n_nodes, dtype=jnp.float32).T[:, None]
                 * pay[None]).reshape(n_nodes * C, block)
            part = jnp.einsum("qi,fbi->qfb", q, oh, precision=hi)
            return acc + part.astype(jnp.uint32), None
        acc0 = jnp.zeros((n_nodes * C, F, bins), jnp.uint32)
        acc, _ = jax.lax.scan(body, acc0, jnp.arange((n + pad) // block))
        return acc.reshape(n_nodes, C, F, bins)
    return run


def exact_level_sums(Xb_t, node, w, vq, n_nodes: int, bins: int) -> tuple:
    """(G [payloads, n_nodes, F, bins], H, C [n_nodes, F, bins]) float64:
    per cell the exact sums of each fixed-point payload row of vq
    [payloads, n] int32 (>= 0, under 2^31) in the label's units with the
    OFFSET still in, of the weights (whole numbers under 256) and of the
    rows of positive weight."""
    payloads = int(vq.shape[0])
    block = min(BLOCK_ROWS, int(Xb_t.shape[1]))
    out = np.asarray(_exact_program(n_nodes, bins, block, payloads)(
        Xb_t, node, w, vq)).astype(np.float64)
    G = np.zeros((payloads,) + out[:, 0].shape)
    for p in range(payloads):
        for k in range(LIMBS):
            G[p] += out[:, p * LIMBS + k] * float(1 << (8 * k))
    return G / float(1 << SCALE_BITS), out[:, -2], out[:, -1]


def payload_rows(w, yq, y):
    """The two int32 payload rows of a tree [2, n]: w x (yq + OFFSET) in
    fixed point — exact — and beside it what ONE bfloat16 part carries:
    bfloat16(w x y), as the program before PR 46 handed it to the
    contraction, plus the same offset."""
    import jax.numpy as jnp
    wi = w.astype(jnp.int32)
    off = jnp.int32(OFFSET << SCALE_BITS)
    once = (w * y).astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.stack([wi * (yq + off),
                      fixed_point(once) + wi * off])


def variance_gains(G, H, C):
    """From one level's exact sums [nodes, F, B] (G with the offset taken
    off): Spark's variance gain a unit of node weight of every candidate
    [nodes, F, B] (rows with bin <= t left), and the rows on each side."""
    GL, HL, CL = (np.cumsum(a, axis=2) for a in (G, H, C))
    Gt, Ht, Ct = (a[:, :1, -1:] for a in (GL, HL, CL))

    def score(g, h):
        with np.errstate(divide="ignore", invalid="ignore"):
            return np.where(h > 0, g * g / h, 0.0)
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = np.where(Ht > 0, (score(GL, HL) + score(Gt - GL, Ht - HL)
                                 - score(Gt, Ht)) / Ht, -np.inf)
    return gain, CL, Ct - CL


def _level(Xb_t, node, w, vq, n_nodes, bins):
    """(exact gains, one-part gains, rows left, rows right, rows a node)."""
    G, H, C = exact_level_sums(Xb_t, node, w, vq, n_nodes, bins)
    gain, c_left, c_right = variance_gains(G[0] - OFFSET * H, H, C)
    gain1, _, _ = variance_gains(G[1] - OFFSET * H, H, C)
    return gain, gain1, c_left, c_right, C[:, 0].sum(axis=1)


def leaf_means(Xb_t, node, w, vq, n_leaves: int, bins: int) -> tuple:
    """(exact weighted mean [n_leaves], the same from the one-part payload,
    rows of positive weight) of the rows each leaf holds; 0 where none."""
    G, H, C = exact_level_sums(Xb_t[:1], node, w, vq, n_leaves, bins)
    G, H, C = G.sum(axis=(2, 3)), H.sum(axis=(1, 2)), C.sum(axis=(1, 2))
    with np.errstate(divide="ignore", invalid="ignore"):
        mean = np.where(C > 0, G / H - OFFSET, 0.0)
    return mean[0], mean[-1], C


def split_replay(Xb_t, y, weight, tree: dict, subsets: np.ndarray, *,
                 depth: int, bins: int, min_instances: float,
                 min_info_gain: float) -> dict:
    """Hold one grown tree to the split rule along its OWN routing.

    Xb_t [F, n] bins, y [n] float32, weight [n] the tree's row weights
    (fold mask x bootstrap draw: whole numbers), tree its feat / thresh /
    miss [2^depth - 1] and leaf [2^depth], subsets [2^depth - 1, F] bool
    the columns each node drew. Returns what was found, judged by nobody:
    gain_shortfall (the largest relative shortfall of a chosen split's gain
    under the best allowed one), splits_off_best (chosen splits that are
    not the exact best allowed candidate), the chosen splits that were not
    allowed and the dead nodes that had an allowed candidate (both outside
    a 1e-4 relative band of minInfoGain), leaf_worst (largest |leaf - exact
    weighted mean|), and what the named wrong builds would have made of the
    same nodes: a payload of ONE bfloat16 part (its own best candidate's
    exact shortfall, how many of its splits differ, its leaves), leaves
    rounded to bfloat16, a threshold halved."""
    import jax.numpy as jnp
    step, _ = _step_program()
    vq = payload_rows(weight, fixed_point(y), y)
    node = jnp.zeros(Xb_t.shape[1], jnp.int32)
    out = {"nodes": 0, "live_nodes": 0, "splits": 0, "dead_with_rows": 0,
           "gain_shortfall": 0.0, "splits_off_best": 0, "not_allowed": [],
           "dead_but_allowed": [],
           "subset_sizes": sorted({int(s.sum()) for s in subsets}),
           "distinct_subsets": len({s.tobytes() for s in subsets}),
           "dead_between_half_1x": 0, "min_gain_margin": np.inf,
           "one_part_gain_shortfall": 0.0, "one_part_splits_differ": 0,
           "one_part_dead_flips": 0, "best_root_gain": None}
    band = 1e-4 * max(min_info_gain, 1e-12)
    last_bin = bins - 1
    for d in range(depth):
        lo, n = (1 << d) - 1, 1 << d
        gain, gain1, c_left, c_right, rows_of = _level(
            Xb_t, node, weight, vq, n, bins)
        sub = subsets[lo:lo + n]
        loose = (c_left >= min_instances) & (c_right >= min_instances) \
            & sub[:, :, None]
        allowed = loose & (gain > min_info_gain)
        firm = loose & (gain > min_info_gain + band)
        for k in range(n):
            f, t = int(tree["feat"][lo + k]), int(tree["thresh"][lo + k])
            rows = float(rows_of[k])
            out["nodes"] += 1
            out["live_nodes"] += rows > 0
            best = float(gain[k][allowed[k]].max()) if allowed[k].any() \
                else None
            if d == 0:
                out["best_root_gain"] = float(gain[0][loose[0]].max()) \
                    if loose[0].any() else None
            # the one-part build at this node: its own allowed best
            ok1 = loose[k] & (gain1[k] > min_info_gain)
            if ok1.any():
                at1 = np.unravel_index(
                    np.argmax(np.where(ok1, gain1[k], -np.inf)), ok1.shape)
                if best is not None:
                    at = np.unravel_index(np.argmax(
                        np.where(allowed[k], gain[k], -np.inf)), ok1.shape)
                    out["one_part_splits_differ"] += at != at1
                    out["one_part_gain_shortfall"] = max(
                        out["one_part_gain_shortfall"],
                        (best - float(gain[k][at1])) / best)
            out["one_part_dead_flips"] += bool(ok1.any()) != (
                best is not None)
            if t >= last_bin:                       # the program: dead
                out["dead_with_rows"] += rows > 0
                if firm[k].any():
                    out["dead_but_allowed"].append(
                        [d, k, float(gain[k][firm[k]].max())])
                top = float(gain[k][loose[k]].max()) if loose[k].any() \
                    else 0.0
                out["dead_between_half_1x"] += \
                    0.5 * min_info_gain < top <= min_info_gain
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
            out["min_gain_margin"] = min(out["min_gain_margin"],
                                         g / min_info_gain
                                         if min_info_gain > 0 else np.inf)
        node = step(Xb_t, node, jnp.asarray(tree["feat"][lo:lo + n]),
                    jnp.asarray(tree["thresh"][lo:lo + n]),
                    jnp.asarray(tree["miss"][lo:lo + n]))
    exact, once, rows = leaf_means(Xb_t, node, weight, vq, 1 << depth, bins)
    leaf = np.asarray(tree["leaf"], np.float64)
    out["leaf_worst"] = float(np.abs(leaf - exact).max())
    out["leaf_worst_if_bf16"] = float(np.abs(_as_bf16(exact) - exact).max())
    out["leaf_worst_if_one_part"] = float(np.abs(once - exact).max())
    out["leaves_with_rows"] = int((rows > 0).sum())
    out["smallest_leaf_rows"] = int(rows[rows > 0].min())
    out["min_gain_margin"] = float(out["min_gain_margin"]) \
        if np.isfinite(out["min_gain_margin"]) else None   # no split at all
    for key in ("splits_off_best", "one_part_splits_differ",
                "one_part_dead_flips", "dead_between_half_1x",
                "live_nodes", "dead_with_rows"):
        out[key] = int(out[key])
    return out


# -- the plain forest ----------------------------------------------------------------

def grow_plain_tree(Xb_t, y, weight, rng, *, depth: int, bins: int,
                    min_instances: float, min_info_gain: float,
                    features_per_node: int) -> dict:
    """One tree by the rule at the head of this file (bins counts the
    missing-value bin), on exact sums and float64 gains. Returns feat,
    thresh, miss [2^depth - 1] and leaf [2^depth]."""
    import jax.numpy as jnp
    step, _ = _step_program()
    F = int(Xb_t.shape[0])
    vq = payload_rows(weight, fixed_point(y), y)[:1]
    node = jnp.zeros(Xb_t.shape[1], jnp.int32)
    feats, thrs = [], []
    for d in range(depth):
        n = 1 << d
        G, H, C = exact_level_sums(Xb_t, node, weight, vq, n, bins)
        gain, c_left, c_right = variance_gains(G[0] - OFFSET * H, H, C)
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
    leaf, _, _ = leaf_means(Xb_t, node, weight, vq, 1 << depth, bins)
    feat = np.concatenate(feats)
    return {"feat": feat, "thresh": np.concatenate(thrs),
            "miss": np.zeros_like(feat), "leaf": leaf.astype(np.float32)}


def plain_forest_reg(Xtr, ytr, Xev, *, trees: int, depth: int, bins: int,
                     min_instances: float, min_info_gain: float,
                     features_per_node: int, subsample: float = 1.0,
                     seed: int = 0) -> np.ndarray:
    """The mean prediction [n_ev] (float64) of a plain regression forest
    fitted on (Xtr, ytr) for the rows of Xev; `bins` is maxBins (the
    missing-value bin is added here). Bootstrap draws and node subsets come
    from numpy's generator seeded with `seed`."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    edges = quantile_edges(Xtr, bins)
    Xb_tr, Xb_ev = binned(Xtr, edges), binned(Xev, edges)
    y = jnp.asarray(ytr, jnp.float32)
    total = np.zeros(Xb_ev.shape[1])
    for _ in range(trees):
        w = jnp.asarray(rng.poisson(subsample, Xb_tr.shape[1]), jnp.float32)
        tree = grow_plain_tree(
            Xb_tr, y, w, rng, depth=depth, bins=bins + 1,
            min_instances=min_instances, min_info_gain=min_info_gain,
            features_per_node=features_per_node)
        total += np.asarray(tree_values(Xb_ev, tree, depth), np.float64)
    return total / trees


def rmse(pred, y) -> float:
    d = np.asarray(pred, np.float64) - np.asarray(y, np.float64)
    return float(np.sqrt((d * d).mean()))


# -- the kernels under a real payload --------------------------------------------------

def real_payload_twins(calls, Xb_t, y, masks, *, into: list, seed: int,
                       interpret: bool, tol: float) -> list:
    """Replay every histogram dispatcher call the sweep recorded (hist_folds
    and route_hist: same static arguments, the cell's lanes, N cut to a
    slice) under a REAL payload — g = w x y / 512 with y the label as it
    stands, mean ~10, uncentred, w a seeded draw of small whole numbers
    under the folds' masks, the power of two the one that brings g into
    the [-1, 1] the kernels' contract asks of a three-part payload —
    against reference.hist_plain in float64. An error is
    held as a share of the cell's own mass, sum |g|; the counts and the
    weight sums are whole numbers and exact. Beside each call what the
    same kernel gives with the payload as ONE bfloat16 part (the call
    without `payload_parts`). Fills `into`; raises after the last call."""
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import pallas_hist as PH
    rng = np.random.default_rng(seed)
    F, N = Xb_t.shape
    yh = np.asarray(y, np.float32)
    mk = np.asarray(masks, np.float32)
    out, hold = into, Held()
    for c in calls:
        name, st = c["kernel"], dict(c["static"])
        if name not in ("hist_folds", "route_hist"):
            continue
        lanes = c["shapes"][2][0]
        C = c["shapes"][1][0] // lanes
        hold(C == 2, f"{name}: {C} payload channels, not g and h")
        w = rng.poisson(1.0, (lanes, N)).astype(np.float32) \
            * mk[np.arange(lanes) % mk.shape[0]]
        pay = np.stack([w * yh[None, :] / 512.0, w],
                       axis=1).reshape(2 * lanes, N)
        Xb = jnp.asarray(Xb_t, c["xb_dtype"])
        dc = bool(st.get("derive_count", False))
        B = st["n_bins"]
        t0 = time.perf_counter()
        if name == "hist_folds":
            S = st["n_slots"]
            slot = rng.integers(0, S + 1, (lanes, N)).astype(np.float32)
            args = (Xb, jnp.asarray(pay), jnp.asarray(slot))
            run = PH.hist_folds
            ref = reference.hist_plain(Xb_t, pay, slot, S, B, dc)
            mass = reference.hist_plain(Xb_t, np.abs(pay), slot, S, B, dc)
            routed = None
        else:
            S = st["n_nodes"]
            node = rng.integers(0, S, (lanes, N)).astype(np.float32)
            tables = [rng.integers(0, hi, (lanes, S)).astype(np.int32)
                      for hi in (F, B, 2)]
            args = (Xb, jnp.asarray(pay), jnp.asarray(node),
                    *map(jnp.asarray, tables))
            run = PH.route_hist
            ref, routed = reference.route_hist_plain(
                Xb_t, pay, node, *tables, S, B, dc)
            mass, _ = reference.route_hist_plain(
                Xb_t, np.abs(pay), node, *tables, S, B, dc)

        def worst(static):
            got = run(*args, interpret=interpret, **static)
            nodes = None
            if routed is not None:
                got, nodes = got
            co = C + (1 if dc else 0)
            g = np.asarray(got, np.float64).reshape(lanes, S, co, -1)
            r = ref.reshape(g.shape)
            m = mass.reshape(g.shape)
            exact = bool(np.array_equal(g[:, :, 1:], r[:, :, 1:]))
            share = np.abs(g[:, :, 0] - r[:, :, 0]) / (m[:, :, 0] + 1e-30)
            return float(share[m[:, :, 0] > 0].max()), exact, nodes
        got_worst, exact, nodes = worst(st)
        one = dict(st)
        one.pop("payload_parts", None)
        res = {"kernel": name, "lanes": lanes, "slots": S,
               "payload_parts": st.get("payload_parts", 1),
               "g_worst_share": got_worst, "h_and_counts_exact": exact,
               "g_worst_share_if_one_part": worst(one)[0],
               # tmoglint: disable=TPU005  compared on the host: synced
               "check_s": round(time.perf_counter() - t0, 2)}
        if routed is not None:
            res["routing_identical"] = bool(
                np.array_equal(np.asarray(nodes), routed))
        out.append(res)
        log(f"real-payload twin {name} lanes {lanes} slots {S}: g off by "
            f"{got_worst:.2e} of a cell's mass (one part: "
            f"{res['g_worst_share_if_one_part']:.2e}), {res['check_s']} s")
        hold(exact, f"{name}: the weight sums or the counts differ")
        hold(res.get("routing_identical", True),
                f"{name}: routing decisions differ")
        hold(got_worst <= tol,
             f"{name} at {S} slots: a histogram sum of w x y is "
             f"{got_worst:.2e} of its cell's mass off the float64 sum "
             f"(bound {tol})")
    hold({t["kernel"] for t in out} == {"hist_folds", "route_hist"},
         f"the histogram dispatchers replayed: "
         f"{sorted({t['kernel'] for t in out})}")
    hold.settle()
    return out


# -- the comparisons ---------------------------------------------------------------

def forest_reg_answer(best, points: list, all_votes: list, masks, X, y, *,
                      into: dict, fold: int, replay_trees: int, depth: int,
                      bins: int, trees: int, subsample: float,
                      features_per_node: int, train_rows: int,
                      tol_gain: float, tol_leaf: float, tol_vote: float,
                      tol_metric: float, tol_moment: float, tol_corr: float,
                      tol_plain: float, order_gap: float) -> dict:
    """Hold the regression-forest sweep that ran to the plain rule.
    `points` is what the timed path itself produced, one dict a grid point
    in grid order (drivers/sweep_forest.ForestLaneSpy: the program's bin
    edges and binned matrix, every lane's tree, every tree's node subsets,
    the first trees' bootstrap vectors, all trees' moments and prefixes,
    `min_instances`) with `min_info_gain` beside it; `all_votes` the summed
    leaf values [folds, n] each point accumulated. Fills `into` as it goes
    (a failed check leaves what was read) and raises CheckFailure."""
    import jax.numpy as jnp
    yh = np.asarray(y, np.float32)
    folds = int(masks.shape[0])
    held_idx = np.flatnonzero(masks[fold] == 0)
    train_idx = np.flatnonzero(masks[fold] == 1)
    at = next(i for i, v in enumerate(best.validated)
              if v.grid == best.best_grid)
    pt = points[at]
    into.update(fold=fold, best_point=at, points=[])
    hold = Held()

    Xb_t = binned(X, pt["edges"])
    same = bool(jnp.array_equal(Xb_t, pt["Xb"].T.astype(Xb_t.dtype)))
    into["bins_identical"] = same
    hold(same, "the program's binned matrix is not 1 + the number of its "
                  "own edges at or below each value")
    yd = jnp.asarray(yh)
    mask = jnp.asarray(masks[fold], jnp.float32)

    # (b) split replay of the best point's first trees, fold `fold`, and
    # the first tree of every other point (how many nodes its threshold
    # lets split)
    t0 = time.perf_counter()
    into["replay"] = []
    jobs = [(at, t) for t in range(replay_trees)] \
        + [(i, 0) for i in range(len(points)) if i != at]
    for i, t in jobs:
        p = points[i]
        tree = {k: np.asarray(v[t, fold]) for k, v in p["trees"].items()}
        w = mask * jnp.asarray(p["boot_head"][t], jnp.float32)
        r = split_replay(Xb_t, yd, w, tree, np.asarray(p["subsets"][t]),
                         depth=depth, bins=bins + 1,
                         min_instances=p["min_instances"],
                         min_info_gain=p["min_info_gain"])
        r.update(point=i, tree=t, min_info_gain=p["min_info_gain"])
        into["replay"].append(r)
        log(f"split replay point {i} tree {t}: {r['splits']} splits of "
            f"{r['live_nodes']} live nodes (root gain "
            f"{r['best_root_gain']:.4f}), gain shortfall "
            f"{r['gain_shortfall']:.2e} ({r['splits_off_best']} off the "
            f"exact best), leaves within {r['leaf_worst']:.2e}; ONE bf16 "
            f"part: shortfall {r['one_part_gain_shortfall']:.2e}, "
            f"{r['one_part_splits_differ']} splits differ, leaves "
            f"{r['leaf_worst_if_one_part']:.2e}; bf16 leaves "
            f"{r['leaf_worst_if_bf16']:.2e}; {r['dead_between_half_1x']} "
            f"dead nodes between 0.5x and 1x minInfoGain")
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
                f"point {i} tree {t}: a leaf is {r['leaf_worst']:.2e} off "
                f"its exact weighted mean (bound {tol_leaf})")
        hold(r["subset_sizes"] == [features_per_node]
                and 2 * r["distinct_subsets"] > r["nodes"],
                f"point {i} tree {t}: node subsets of sizes "
                f"{r['subset_sizes']} (the configuration: "
                f"{features_per_node}), {r['distinct_subsets']} distinct "
                f"among {r['nodes']} nodes")
    # tmoglint: disable=TPU005  every replay's sums came to the host
    into["replay_s"] = round(time.perf_counter() - t0, 2)

    # (c) the fold's summed leaves by plain traversal of every tree the
    # best point returned, against the sweep's votes; then every fold's
    # EXACT RMSE of the sweep's own votes against its reported metric
    Xb_held = Xb_t[:, jnp.asarray(held_idx)]
    total = np.zeros(len(held_idx))
    total_bf16 = np.zeros(len(held_idx))
    for t in range(trees):
        tree = {k: np.asarray(v[t, fold]) for k, v in pt["trees"].items()}
        last = np.asarray(tree_values(Xb_held, tree, depth), np.float64)
        total += last
        total_bf16 += np.asarray(tree_values(
            Xb_held, dict(tree, leaf=_as_bf16(tree["leaf"])), depth),
            np.float64)
    got_votes = np.asarray(all_votes[at][fold])[held_idx].astype(np.float64)
    exact = rmse(total / trees, yh[held_idx])
    got = float(best.validated[at].fold_metrics[fold])
    into["votes"] = {
        "held_rows": int(len(held_idx)),
        "vote_worst": float(np.abs(total - got_votes).max()),
        "vote_worst_if_bf16_leaves": float(
            np.abs(total_bf16 - got_votes).max()),
        "exact_rmse": exact, "sweep_fold_metric": got,
        "metric_delta": abs(got - exact),
        "metric_delta_if_bf16_leaves": abs(
            got - rmse(total_bf16 / trees, yh[held_idx])),
        "metric_delta_if_a_tree_were_missing": abs(
            got - rmse((total - last) / (trees - 1), yh[held_idx]))}
    log(f"votes: traversal within {into['votes']['vote_worst']:.2e} of the "
        f"sweep's sums; exact RMSE {exact:.7f} vs fold metric {got:.7f}")
    hold(into["votes"]["vote_worst"] <= tol_vote,
            f"a held-out row's summed leaves are "
            f"{into['votes']['vote_worst']:.2e} off the plain traversal of "
            f"the returned trees (bound {tol_vote})")
    hold(into["votes"]["metric_delta"] <= tol_metric,
            f"the sweep's fold metric is {into['votes']['metric_delta']:.2e}"
            f" off the exact RMSE of its own trees (bound {tol_metric})")
    worst, exact_means = 0.0, []
    for i, v in enumerate(best.validated):
        per_fold = []
        for f in range(folds):
            idx = np.flatnonzero(masks[f] == 0)
            per_fold.append(rmse(
                np.asarray(all_votes[i][f])[idx].astype(np.float64)
                / trees, yh[idx]))
            worst = max(worst, abs(per_fold[-1] - float(v.fold_metrics[f])))
        exact_means.append(float(np.mean(per_fold)))
    into["every_fold_metric_delta"] = worst
    hold(worst <= tol_metric,
            f"a fold metric of the sweep is {worst:.2e} off the exact RMSE "
            f"of its own votes (bound {tol_metric})")

    # (g) the grid points' order
    sweep_means = [float(np.mean(v.fold_metrics)) for v in best.validated]
    wrong = [[i, j] for i in range(len(exact_means))
             for j in range(len(exact_means))
             if exact_means[i] + order_gap < exact_means[j]
             and not sweep_means[i] < sweep_means[j]]
    into["order"] = {"exact_means": exact_means, "sweep_means": sweep_means,
                     "gap": order_gap, "misordered": wrong}
    hold(not wrong, f"grid points the sweep orders unlike the exact "
                       f"mean RMSEs of its own votes: {wrong}")
    hold(sweep_means[at] == min(sweep_means),
            f"the winner is point {at}, not the lowest mean RMSE of "
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
        ref = rmse(plain_forest_reg(
            Xtr, yh[train_idx[:train_rows]], Xhe, trees=trees, depth=depth,
            bins=bins, min_instances=p["min_instances"],
            min_info_gain=p["min_info_gain"],
            features_per_node=features_per_node, subsample=subsample,
            seed=1 + i), yh[held_idx])
        got_i = float(v.fold_metrics[fold])
        rec.update(sweep=got_i, reference=ref,
                   s=round(time.perf_counter() - t0, 2))
        worst = max(worst, abs(got_i - ref))
        log(f"plain forest, point {i}: sweep {got_i:.6f} vs plain "
            f"{ref:.6f} on {len(tr)} training rows ({rec['s']} s)")
    into["plain_worst_delta"] = worst
    hold(worst <= tol_plain,
            f"a fold RMSE of the forest sweep is {worst:.2e} off the plain "
            f"forest (bound {tol_plain})")
    hold.settle()
    return into
