"""The plain references of the regression booster sweep: what decides
`correct` in `sweep-gbt-regression`. Nothing here imports the program.

The model is the boosting rule the configuration `regression-10m-64-gbt`
states under `guarantees`, on quantile-binned columns, under a REAL-VALUED
label and the squared loss:

- the base score of a fold is the weighted mean of the label over its
  training rows; F starts there;
- round r grows one tree on the residual y - F of the rounds before it.
  A node splits on the best allowed candidate (feature f, bin t; rows with
  bin <= t go left) by the Newton gain A WEIGHTED ROW of the node,

      gain = [ GL^2 / (HL + lam) + GR^2 / (HR + lam) - G^2 / (H + lam) ]
             / max(H, 1)

  (G the sums of w x (y - F), H of the weights w, lam = reg_lambda = 1),
  allowed when both children hold at least minInstancesPerNode rows of
  positive weight and gain > minInfoGain — Spark's threshold, compared
  with a gain a row as Spark compares it (`normalize_gain`), and not with
  the gain summed over the node's rows (XGBoost's rule: RULE_SUMMED below
  is that wrong build);
- a leaf is step_size x G / (H + lam) — a Newton step, on EVERY tree — and
  F grows by the leaf each row lands on; the fold metric is the RMSE of F
  over the held-out rows.

That is this library's boosting rule, not Spark 2.3's
`GradientBoostedTrees.boost`, which the configuration lists as departures
under `assumed`: Spark fits its first tree to the label itself at weight 1
(no base score), later trees to the pseudo-residual 2 (y - F) at weight
stepSize, with plain-mean leaves (no L2) and the variance gain of what the
tree is fitted to (4 x the residual's from the second tree on).
`plain_gbt_reg(rule="spark")` grows that forest on the same rows, so that
each run reports the departure's size beside its checks.

EXACT SUMS, as benchmark/reference_forest_reg.py makes them and by its
programs: the residual is read once as a fixed-point number, round((y - F)
x 2^21) from float64, the weights are 0 or 1, every one-hot product over a
block of rows is a whole number float32 holds exactly and the blocks are
added in uint32: no sum a replay reads is rounded at all.

- `newton_gains`: the split rule's pieces.
- `replay_round`: every node of one of the program's OWN trees held to the
  rule along its own routing, from the residual rebuilt in float64 from the
  program's own earlier trees; beside it what each named wrong build would
  have made of the same nodes and leaves.
- `plain_gbt_reg`: a booster grown here, by the rule above or by Spark's.
- `residual_twins`: the program's histogram dispatchers replayed in the
  booster's call shape under a round's scaled residual against
  reference.hist_plain (float64); one part and two parts beside three.
- `gbt_reg_answer`: the comparisons of `sweep-gbt-regression` themselves.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import reference
from benchmark.harness import log
from benchmark.reference import require
from benchmark.reference_forest import (
    _step_program, binned, quantile_edges, tree_values)
from benchmark.reference_forest_reg import (
    OFFSET, SCALE_BITS, Held, exact_level_sums, rmse)
from benchmark.reference_wide import _as_bf16

RULE_ROW, RULE_SUMMED = "a_weighted_row", "summed_over_the_node"


# -- the split rule -----------------------------------------------------------------

def newton_gains(G, H, C, lam: float, rule: str = RULE_ROW):
    """From one level's exact sums [nodes, F, B] (G of w x (y - F)): the
    gain of every candidate [nodes, F, B] (rows with bin <= t left) under
    `rule`, and the rows of positive weight on each side."""
    GL, HL, CL = (np.cumsum(a, axis=2) for a in (G, H, C))
    Gt, Ht, Ct = (a[:, :1, -1:] for a in (GL, HL, CL))

    def score(g, h):   # a side of no weight scores 0 (lam = 0)
        with np.errstate(divide="ignore", invalid="ignore"):
            return np.where(h + lam > 0, g * g / (h + lam), 0.0)
    gain = score(GL, HL) + score(Gt - GL, Ht - HL) - score(Gt, Ht)
    if rule == RULE_ROW:
        gain = gain / np.maximum(Ht, 1.0)
    return gain, CL, Ct - CL


def fixed_rows(w, r64, r_once=None):
    """int32 payload rows [1 or 2, n] for exact_level_sums: w x (round(r x
    2^21) + OFFSET x 2^21), w 0 or 1 and |r| < 16 — the residual EXACTLY —
    and under it the same of `r_once`, what another build would have
    summed in its place (a residual rounded once to bfloat16)."""
    import jax.numpy as jnp
    wi = np.asarray(w).astype(np.int64)
    out = np.stack([
        wi * (np.rint(np.asarray(r, np.float64) * float(1 << SCALE_BITS))
              .astype(np.int64) + (OFFSET << SCALE_BITS))
        for r in (r64, r_once) if r is not None])
    require(bool((out >= 0).all() and (out < 2 ** 31).all()),
            "a residual outside (-16, 16): the fixed-point payload of the "
            "exact sums does not hold it")
    return jnp.asarray(out.astype(np.int32))


def _sums(Xb_t, node, w, vq, n_nodes, bins):
    """(G [payloads, nodes, F, B] of w x r with the offset off, H, C)."""
    G, H, C = exact_level_sums(Xb_t, node, w, vq, n_nodes, bins)
    return G - OFFSET * H[None], H, C


def leaf_sums(Xb_t, node, w, vq, n_leaves: int, bins: int) -> tuple:
    """(G [payloads, leaves], H, C [leaves]) of the rows each leaf holds."""
    G, H, C = _sums(Xb_t[:1], node, w, vq, n_leaves, bins)
    return G.sum(axis=(2, 3)), H.sum(axis=(1, 2)), C.sum(axis=(1, 2))


def replay_round(Xb_t, r64, weight, tree: dict, *, depth: int, bins: int,
                 min_instances: float, min_info_gain: float, lam: float,
                 step: float) -> dict:
    """Hold one of the program's trees to the rule along its OWN routing.

    Xb_t [F, n] bins, r64 [n] float64 the residual y - F before this round
    (rebuilt by the caller from the program's own earlier trees), weight
    [n] the lane's row weights (the fold's mask: 0 or 1), tree its feat /
    thresh / miss [2^depth - 1] and leaf [2^depth]. Returns what was found,
    judged by nobody: gain_shortfall (the largest relative shortfall of a
    chosen split's gain under the best allowed one), splits_off_best, the
    chosen splits that were not allowed and the dead nodes that had an
    allowed candidate (both outside a 1e-4 relative band of minInfoGain),
    leaf_worst (largest |leaf - step x G / (H + lam)|), and what the named
    wrong builds would have made of the same nodes: the residual as ONE
    bfloat16 part; the gain summed over the node (how many of the
    program's dead nodes that rule would have split, how many of its
    splits it would have refused: none, its threshold is the looser);
    step_size applied twice or not at all, reg_lambda 0, the weight sums
    rounded to bfloat16, the leaves rounded to bfloat16."""
    import jax.numpy as jnp
    stepper, _ = _step_program()
    r32 = np.asarray(r64, np.float32)
    vq = fixed_rows(weight, r64, _as_bf16(np.asarray(weight) * r32))
    w = jnp.asarray(weight, jnp.float32)
    node = jnp.zeros(Xb_t.shape[1], jnp.int32)
    out = {"nodes": 0, "live_nodes": 0, "splits": 0, "dead_with_rows": 0,
           "gain_shortfall": 0.0, "splits_off_best": 0, "not_allowed": [],
           "dead_but_allowed": [], "min_gain_margin": np.inf,
           "best_root_gain": None, "dead_between_quarter_1x": 0,
           "one_part_gain_shortfall": 0.0, "one_part_splits_differ": 0,
           "one_part_dead_flips": 0, "summed_rule_would_split": 0,
           "summed_rule_root_gain": None}
    band = 1e-4 * max(min_info_gain, 1e-12)
    last_bin = bins - 1
    for d in range(depth):
        lo, n = (1 << d) - 1, 1 << d
        G, H, C = _sums(Xb_t, node, w, vq, n, bins)
        gain, c_left, c_right = newton_gains(G[0], H, C, lam)
        gain1, _, _ = newton_gains(G[1], H, C, lam)
        summed, _, _ = newton_gains(G[0], H, C, lam, RULE_SUMMED)
        rows_of = C[:, 0].sum(axis=1)
        loose = (c_left >= min_instances) & (c_right >= min_instances) \
            & (gain > 0)
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
                out["summed_rule_root_gain"] = float(
                    summed[0][loose[0]].max()) if loose[0].any() else None
            ok1 = loose[k] & (gain1[k] > min_info_gain)
            if ok1.any() and best is not None:
                at1 = np.unravel_index(
                    np.argmax(np.where(ok1, gain1[k], -np.inf)), ok1.shape)
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
                # Spark compares 4 x this gain from its second tree on
                out["dead_between_quarter_1x"] += \
                    0.25 * min_info_gain < top <= min_info_gain
                out["summed_rule_would_split"] += bool(
                    (loose[k] & (summed[k] > min_info_gain + band)).any())
                continue
            out["splits"] += 1
            g = float(gain[k, f, t])
            ok = bool(loose[k, f, t] and g > min_info_gain - band)
            if not ok:
                out["not_allowed"].append(
                    [d, k, f, t, g, float(c_left[k, f, t]),
                     float(c_right[k, f, t])])
                continue
            out["gain_shortfall"] = max(
                out["gain_shortfall"], (best - g) / best if best else 0.0)
            out["splits_off_best"] += bool(best) and g < best
            out["min_gain_margin"] = min(
                out["min_gain_margin"],
                g / min_info_gain if min_info_gain > 0 else np.inf)
        node = stepper(Xb_t, node, jnp.asarray(tree["feat"][lo:lo + n]),
                       jnp.asarray(tree["thresh"][lo:lo + n]),
                       jnp.asarray(tree["miss"][lo:lo + n]))
    G, H, C = leaf_sums(Xb_t, node, w, vq, 1 << depth, bins)
    live = C > 0

    def leaves(g, h, lr=step, l2=lam):
        with np.errstate(divide="ignore", invalid="ignore"):
            return np.where(live, lr * g / (h + l2), 0.0)
    exact = leaves(G[0], H)
    leaf = np.asarray(tree["leaf"], np.float64)

    def off(other):
        return float(np.abs(other - exact).max())
    out.update(
        leaf_worst=off(leaf), leaf_largest=float(np.abs(exact).max()),
        leaf_worst_if_one_part=off(leaves(G[1], H)),
        leaf_worst_if_bf16=off(_as_bf16(exact)),
        leaf_worst_if_step_twice=off(leaves(G[0], H, lr=step * step)),
        leaf_worst_if_no_step=off(leaves(G[0], H, lr=1.0)),
        leaf_worst_if_lambda_0=off(leaves(G[0], H, l2=0.0)),
        leaf_worst_if_h_bf16=off(leaves(G[0], _as_bf16(H))),
        leaves_with_rows=int(live.sum()),
        smallest_leaf_rows=int(C[live].min()),
        residual_largest=float(np.abs(
            np.asarray(r64)[np.asarray(weight) > 0]).max()))
    out["min_gain_margin"] = float(out["min_gain_margin"]) \
        if np.isfinite(out["min_gain_margin"]) else None   # no split at all
    for key in ("splits_off_best", "one_part_splits_differ",
                "one_part_dead_flips", "dead_between_quarter_1x",
                "summed_rule_would_split", "live_nodes", "dead_with_rows"):
        out[key] = int(out[key])
    return out


def power_of_two_over(x: float) -> float:
    """The power of two just over x > 0: the scale a round's residual is
    divided by, so that it lies inside (-1, 1)."""
    return float(2.0 ** (np.floor(np.log2(x)) + 1))


# -- the plain booster ----------------------------------------------------------------

def grow_plain_tree(Xb_t, r64, *, depth: int, bins: int,
                    min_instances: float, min_info_gain: float, lam: float,
                    gain_times: float = 1.0) -> tuple:
    """One tree on the residual r64 at unit weights by the rule at the
    head of this file (bins counts the missing-value bin), on exact sums
    and float64 gains; `gain_times` multiplies the gain before the
    threshold (Spark's pseudo-residual is 2 x r: 4). Returns (feat, thresh
    [2^depth - 1], the leaf each row fell in [n], G, H, C of the leaves)."""
    import jax.numpy as jnp
    stepper, _ = _step_program()
    n_rows = int(Xb_t.shape[1])
    w = jnp.ones(n_rows, jnp.float32)
    vq = fixed_rows(np.ones(n_rows), r64)
    node = jnp.zeros(n_rows, jnp.int32)
    feats, thrs = [], []
    for d in range(depth):
        n = 1 << d
        G, H, C = _sums(Xb_t, node, w, vq, n, bins)
        gain, c_left, c_right = newton_gains(G[0], H, C, lam)
        gain = gain * gain_times
        ok = ((c_left >= min_instances) & (c_right >= min_instances)
              & (gain > min_info_gain) & (gain > 0))
        flat = np.where(ok, gain, -np.inf).reshape(n, -1)
        at = flat.argmax(axis=1)
        split = np.isfinite(flat.max(axis=1))
        f = np.where(split, at // bins, 0).astype(np.int32)
        t = np.where(split, at % bins, bins - 1).astype(np.int32)
        feats.append(f)
        thrs.append(t)
        node = stepper(Xb_t, node, jnp.asarray(f), jnp.asarray(t),
                       jnp.zeros(n, jnp.int32))
    G, H, C = leaf_sums(Xb_t, node, w, vq, 1 << depth, bins)
    return np.concatenate(feats), np.concatenate(thrs), node, G[0], H, C


def plain_gbt_reg(Xtr, ytr, Xev, *, rounds: int, depth: int, bins: int,
                  step: float, lam: float, min_instances: float,
                  min_info_gain: float, rule: str = "program") -> np.ndarray:
    """The prediction [n_ev] (float64) of a plain booster fitted on (Xtr,
    ytr) at unit weights for the rows of Xev; `bins` is maxBins (the
    missing-value bin is added here). `rule` "program": the rule at the
    head of this file. "spark": Spark 2.3's — the first tree on the label
    itself at weight 1 with no base score, later trees on 2 (y - F) at
    weight `step`, plain-mean leaves (no L2), the variance gain of what
    the tree is fitted to."""
    import jax.numpy as jnp
    require(rule in ("program", "spark"), f"unknown boosting rule {rule!r}")
    edges = quantile_edges(Xtr, bins)
    Xb_tr, Xb_ev = binned(Xtr, edges), binned(Xev, edges)
    y = np.asarray(ytr, np.float64)
    spark = rule == "spark"
    base = 0.0 if spark else float(y.mean())
    F_tr = np.full(len(y), base)
    F_ev = np.full(int(Xb_ev.shape[1]), base)
    for r in range(rounds):
        first = spark and r == 0
        feat, thr, node, G, H, C = grow_plain_tree(
            Xb_tr, y - F_tr, depth=depth, bins=bins + 1,
            min_instances=min_instances, min_info_gain=min_info_gain,
            lam=0.0 if spark else lam,
            gain_times=4.0 if spark and not first else 1.0)
        if spark:   # the mean of the label, then of 2 (y - F) at `step`
            leaf = np.where(C > 0, G / np.maximum(H, 1.0), 0.0) \
                * (1.0 if first else 2.0 * step)
        else:
            leaf = np.where(C > 0, step * G / (H + lam), 0.0)
        tree = {"feat": feat, "thresh": thr, "miss": np.zeros_like(feat),
                "leaf": leaf.astype(np.float32)}
        F_tr = F_tr + np.asarray(leaf.astype(np.float32), np.float64)[
            np.asarray(node)]
        F_ev = F_ev + np.asarray(tree_values(Xb_ev, tree, depth), np.float64)
    return F_ev


# -- the kernels under a round's residual ----------------------------------------------

def two_parts(x) -> np.ndarray:
    """What the first TWO of the kernels' three fixed-point parts hold of
    float32 x in [-1, 1]: the nearest multiple of 2^-7, and of what is
    left the nearest multiple of 2^-15 (the third, under 2^-16, dropped)."""
    x = np.asarray(x, np.float32)
    hi = np.floor(x * np.float32(128.0) + np.float32(0.5)) / np.float32(128)
    rest = x - hi
    mid = np.floor(rest * np.float32(32768.0) + np.float32(0.5)) \
        / np.float32(32768)
    return (hi + mid).astype(np.float64)


def residual_twins(calls, Xb_t, residuals: dict, masks, *, into: list,
                   seed: int, interpret: bool, tol: float) -> list:
    """Replay every histogram dispatcher call the sweep recorded
    (hist_folds and route_hist: same static arguments, the cell's lanes, N
    cut to a slice) under a ROUND's payload — g = w x r / scale with r the
    lanes' residual of that round as `residuals` names it ({name: [lanes,
    N] float32}, from the sweep's own base scores and margins), w the
    folds' masks and scale the power of two just over each lane's largest
    |w x r|, the booster's own call — against reference.hist_plain in
    float64. An error is held as a share of the cell's own mass, sum |g|;
    the counts and the weight sums are whole numbers and exact. Beside each
    call what the same kernel gives with the payload as ONE bfloat16 part
    (the call without `payload_parts`) and what TWO of the three parts
    would sum to. Fills `into`; raises after the last call."""
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import pallas_hist as PH
    rng = np.random.default_rng(seed)
    F, N = Xb_t.shape
    mk = np.asarray(masks, np.float32)
    out, hold = into, Held()
    for c in calls:
        name, st = c["kernel"], dict(c["static"])
        if name not in ("hist_folds", "route_hist"):
            continue
        lanes = c["shapes"][2][0]
        C = c["shapes"][1][0] // lanes
        hold(C == 2, f"{name}: {C} payload channels, not g and h")
        w = mk[np.arange(lanes) % mk.shape[0]]
        Xb = jnp.asarray(Xb_t, c["xb_dtype"])
        dc = bool(st.get("derive_count", False))
        B = st["n_bins"]
        if name == "hist_folds":
            S = st["n_slots"]
            slot = rng.integers(0, S + 1, (lanes, N)).astype(np.float32)
            tables = None
        else:
            S = st["n_nodes"]
            slot = rng.integers(0, S, (lanes, N)).astype(np.float32)
            tables = [rng.integers(0, hi, (lanes, S)).astype(np.int32)
                      for hi in (F, B, 2)]
        for which, r in residuals.items():
            t0 = time.perf_counter()
            g = (w * np.asarray(r, np.float32)[
                np.arange(lanes) % len(r)]).astype(np.float32)
            scale = np.asarray([power_of_two_over(max(float(
                np.abs(row).max()), 2.0 ** -100)) for row in g], np.float32)
            g = g / scale[:, None]
            pay = np.stack([g, w], axis=1).reshape(2 * lanes, N)
            pay2 = np.stack([two_parts(g), w], axis=1).reshape(2 * lanes, N)

            def plain(p):
                if tables is None:
                    return reference.hist_plain(Xb_t, p, slot, S, B, dc), \
                        None
                return reference.route_hist_plain(Xb_t, p, slot, *tables,
                                                  S, B, dc)
            ref, routed = plain(pay)
            mass, _ = plain(np.abs(pay))
            co = C + (1 if dc else 0)

            def shares(got):
                a = np.asarray(got, np.float64).reshape(lanes, S, co, -1)
                r_, m_ = ref.reshape(a.shape), mass.reshape(a.shape)
                exact = bool(np.array_equal(a[:, :, 1:], r_[:, :, 1:]))
                share = np.abs(a[:, :, 0] - r_[:, :, 0]) \
                    / (m_[:, :, 0] + 1e-30)
                return float(share[m_[:, :, 0] > 0].max()), exact

            def run(static):
                args = (Xb, jnp.asarray(pay), jnp.asarray(slot))
                if tables is None:
                    return PH.hist_folds(*args, interpret=interpret,
                                         **static), None
                return PH.route_hist(*args, *map(jnp.asarray, tables),
                                     interpret=interpret, **static)
            got, nodes = run(st)
            got_worst, exact = shares(got)
            one = dict(st)
            one.pop("payload_parts", None)
            res = {"kernel": name, "residual": which, "lanes": lanes,
                   "slots": S, "payload_parts": st.get("payload_parts", 1),
                   "scales": sorted(set(scale.tolist())),
                   "g_worst_share": got_worst, "h_and_counts_exact": exact,
                   "g_worst_share_if_one_part": shares(run(one)[0])[0],
                   "g_worst_share_if_two_parts": shares(plain(pay2)[0])[0],
                   # tmoglint: disable=TPU005  compared on the host: synced
                   "check_s": round(time.perf_counter() - t0, 2)}
            if routed is not None:
                res["routing_identical"] = bool(
                    np.array_equal(np.asarray(nodes), routed))
            out.append(res)
            log(f"residual twin {name} ({which}) lanes {lanes} slots {S}: "
                f"g off by {got_worst:.2e} of a cell's mass (one part "
                f"{res['g_worst_share_if_one_part']:.2e}, two parts "
                f"{res['g_worst_share_if_two_parts']:.2e}), "
                f"{res['check_s']} s")
            hold(res["payload_parts"] == 3,
                 f"{name}: the sweep called it with payload_parts "
                 f"{res['payload_parts']}, not 3")
            hold(exact, f"{name}: the weight sums or the counts differ")
            hold(res.get("routing_identical", True),
                 f"{name}: routing decisions differ")
            hold(got_worst <= tol,
                 f"{name} at {S} slots ({which}): a histogram sum of the "
                 f"residual is {got_worst:.2e} of its cell's mass off the "
                 f"float64 sum (bound {tol})")
    hold({t["kernel"] for t in out} == {"hist_folds", "route_hist"},
         f"the histogram dispatchers replayed: "
         f"{sorted({t['kernel'] for t in out})}")
    hold.settle()
    return out


# -- the comparisons ---------------------------------------------------------------

def _tree(trees: dict, r: int, lane: int) -> dict:
    return {k: np.asarray(v[r, lane]) for k, v in trees.items()}


def gbt_reg_answer(best, points: list, masks, X, y, *, into: dict,
                   fold: int, rounds: int, depth: int, bins: int,
                   step: float, lam: float, train_rows: int,
                   tol_gain: float, tol_leaf: float, tol_margin: float,
                   tol_metric: float, tol_plain: float) -> dict:
    """Hold the regression booster sweep that ran to the plain rule.
    `points` is what the timed path itself produced, one dict a grid point
    in grid order (drivers/sweep_gbt_reg.BoosterSpy: the program's bin
    edges and, at the first point, binned matrix, every round's tree of
    every fold lane, the lanes' base scores, fold `fold`'s margins,
    `min_instances`, `min_info_gain`).
    Fills `into` as it goes (a failed check leaves what was read) and
    raises CheckFailure."""
    import jax.numpy as jnp
    yh = np.asarray(y, np.float32)
    y64 = yh.astype(np.float64)
    folds = int(masks.shape[0])
    into.update(fold=fold, points=[], replay=[])
    hold = Held()

    pt0 = points[0]
    Xb_t = binned(X, pt0["edges"])
    same = bool(jnp.array_equal(Xb_t, pt0["Xb"].T.astype(Xb_t.dtype)))
    into["bins_identical"] = same
    hold(same, "the program's binned matrix is not 1 + the number of its "
               "own edges at or below each value")
    mask = np.asarray(masks[fold], np.float32)

    # (b) split replay, fold `fold`: the FIRST tree and the LAST round's
    # tree of every point, the residual before a round rebuilt in float64
    # from the program's own earlier trees
    t0 = time.perf_counter()
    differ = None
    for i, p in enumerate(points):
        require(p["trees"]["feat"].shape[:2] == (rounds, folds),
                f"point {i}: trees of shape {p['trees']['feat'].shape[:2]}, "
                f"not ({rounds}, {folds})")
        F = np.full(len(y64), float(p["base"][fold]), np.float64)
        for r in range(rounds):
            tree = _tree(p["trees"], r, fold)
            if r in (0, rounds - 1):
                rep = replay_round(
                    Xb_t, y64 - F, mask, tree, depth=depth, bins=bins + 1,
                    min_instances=p["min_instances"],
                    min_info_gain=p["min_info_gain"], lam=lam, step=step)
                rep.update(point=i, round=r,
                           min_info_gain=p["min_info_gain"],
                           scale=power_of_two_over(rep["residual_largest"]))
                into["replay"].append(rep)
                log(f"replay point {i} round {r}: {rep['splits']} splits of "
                    f"{rep['live_nodes']} live nodes (root gain "
                    f"{rep['best_root_gain']:.4f}, residual up to "
                    f"{rep['residual_largest']:.3f}), gain shortfall "
                    f"{rep['gain_shortfall']:.2e} "
                    f"({rep['splits_off_best']} off the exact best), "
                    f"leaves within {rep['leaf_worst']:.2e}; ONE part: "
                    f"shortfall {rep['one_part_gain_shortfall']:.2e}, "
                    f"{rep['one_part_splits_differ']} splits differ, "
                    f"leaves {rep['leaf_worst_if_one_part']:.2e}; lambda 0 "
                    f"{rep['leaf_worst_if_lambda_0']:.2e}, step twice "
                    f"{rep['leaf_worst_if_step_twice']:.2e}, h bf16 "
                    f"{rep['leaf_worst_if_h_bf16']:.2e}; the summed gain "
                    f"would split {rep['summed_rule_would_split']} of "
                    f"{rep['dead_with_rows']} dead nodes")
                hold(not rep["not_allowed"],
                     f"point {i} round {r}: chosen splits the rule does not "
                     f"allow {rep['not_allowed'][:3]}")
                hold(not rep["dead_but_allowed"],
                     f"point {i} round {r}: nodes left unsplit that had an "
                     f"allowed candidate {rep['dead_but_allowed'][:3]}")
                hold(rep["gain_shortfall"] <= tol_gain,
                     f"point {i} round {r}: a chosen split's gain is "
                     f"{rep['gain_shortfall']:.2e} under the best allowed "
                     f"(bound {tol_gain})")
                hold(rep["leaf_worst"] <= tol_leaf,
                     f"point {i} round {r}: a leaf is {rep['leaf_worst']:.2e}"
                     f" off step x G / (H + lambda) of its rows (bound "
                     f"{tol_leaf})")
            F += np.asarray(tree_values(Xb_t, tree, depth), np.float64)
        # the margins the program kept against the plain traversal of its
        # own trees, every row of the fold's lane
        got = np.asarray(p["margins_fold"]).astype(np.float64)
        p_off = float(np.abs(got - F).max())
        into["points"].append({
            "margin_worst": p_off,
            # rounds (from 0) whose tree is a root that does not split
            "rounds_of_a_dead_root": [
                r for r in range(rounds)
                if int(p["trees"]["thresh"][r, fold, 0]) >= bins]})
        hold(p_off <= tol_margin,
             f"point {i}: a row's margin is {p_off:.2e} off the base score "
             f"plus the plain traversal of the returned trees (bound "
             f"{tol_margin})")
        this = np.concatenate([p["trees"][k][:, fold].ravel()
                               for k in ("feat", "thresh")])
        if differ is None:
            differ, first = False, this
        else:
            differ = differ or not np.array_equal(first, this)
    # tmoglint: disable=TPU005  every replay's sums came to the host
    into["replay_s"] = round(time.perf_counter() - t0, 2)
    into["points_grow_different_trees"] = bool(differ)
    hold(len(points) < 2 or bool(differ),
         "the grid points grew the SAME trees: minInfoGain binds nowhere "
         "(as it would compared with a gain summed over the node's rows)")
    binds = [r["dead_with_rows"] for r in into["replay"]]
    into["threshold_binds_in_replayed_trees"] = binds
    hold(any(binds), "minInfoGain stops no node of any replayed tree")

    # (c) every fold's EXACT RMSE of the program's own trees over its
    # held-out rows, against the sweep's reported metric
    worst = 0.0
    for i, (p, v) in enumerate(zip(points, best.validated)):
        per_fold = []
        for f in range(folds):
            idx = np.flatnonzero(masks[f] == 0)
            Xb_held = Xb_t[:, jnp.asarray(idx)]
            F = np.full(len(idx), float(p["base"][f]), np.float64)
            for r in range(rounds):
                F += np.asarray(tree_values(
                    Xb_held, _tree(p["trees"], r, f), depth), np.float64)
            per_fold.append(rmse(F, yh[idx]))
            worst = max(worst, abs(per_fold[-1] - float(v.fold_metrics[f])))
        into["points"][i].update(
            grid=dict(v.grid), exact_fold_rmse=per_fold,
            sweep_fold_rmse=[float(m) for m in v.fold_metrics])
    into["every_fold_metric_delta"] = worst
    log(f"fold metrics: every fold of every point within {worst:.2e} of the "
        f"exact RMSE of its own trees")
    hold(worst <= tol_metric,
         f"a fold metric of the sweep is {worst:.2e} off the exact RMSE of "
         f"its own trees (bound {tol_metric})")
    sweep_means = [float(np.mean(v.fold_metrics)) for v in best.validated]
    at = next(i for i, v in enumerate(best.validated)
              if v.grid == best.best_grid)
    hold(sweep_means[at] == min(sweep_means),
         f"the winner is point {at}, not the lowest mean RMSE of "
         f"{sweep_means}")

    # (a) the plain booster on a sample of the fold's training rows,
    # every point; beside it Spark's own rule on the same rows
    held_idx = np.flatnonzero(masks[fold] == 0)
    train_idx = np.flatnonzero(masks[fold] == 1)[:train_rows]
    Xtr = X[jnp.asarray(train_idx)].astype(jnp.float32)
    Xhe = X[jnp.asarray(held_idx)].astype(jnp.float32)
    worst = 0.0
    for i, (p, v) in enumerate(zip(points, best.validated)):
        rec = into["points"][i]
        for rule in ("program", "spark"):
            t0 = time.perf_counter()
            rec[f"plain_{rule}_rmse"] = rmse(plain_gbt_reg(
                Xtr, yh[train_idx], Xhe, rounds=rounds, depth=depth,
                bins=bins, step=step, lam=lam,
                min_instances=p["min_instances"],
                min_info_gain=p["min_info_gain"], rule=rule), yh[held_idx])
            # tmoglint: disable=TPU005  the prediction came to the host
            rec[f"plain_{rule}_s"] = round(time.perf_counter() - t0, 2)
        got_i = float(v.fold_metrics[fold])
        worst = max(worst, abs(got_i - rec["plain_program_rmse"]))
        log(f"plain booster, point {i}: sweep {got_i:.6f} vs plain "
            f"{rec['plain_program_rmse']:.6f} on {len(train_idx)} training "
            f"rows; Spark's rule {rec['plain_spark_rmse']:.6f}")
    into["plain_worst_delta"] = worst
    hold(worst <= tol_plain,
         f"a fold RMSE of the booster sweep is {worst:.2e} off the plain "
         f"booster (bound {tol_plain})")
    hold.settle()
    return into
