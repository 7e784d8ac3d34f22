"""Closed loop of one client over a RANDOM-FOREST `CrossValidation.validate()`:
the call a Binary / MultiClassification / Regression ModelSelector makes with
upstream's default model types — the random forest is in all three pools —
feature matrix resident on the device -> every grid point's trees x folds
grown as lanes of the fused histogram passes, in-sweep metric computed,
winner on the host.

A sibling of drivers/sweep.py, whose set-up, job, route check, loop and
kernel twins it runs by import: what differs is asked BEFORE any data is
made — a program that does not declare the forest lane route grows every
tree of every fold one after another (~100 s a grid point at this size) and
is refused — and what is watched and held to benchmark/reference_forest.py:
the trees, bootstrap vectors and node subsets of the warm-up job.
"""
from __future__ import annotations

import importlib

import numpy as np

from benchmark import harness, reference, reference_forest

sweep = harness.load_module("drivers", "sweep")


def _require_route(cls, params, grids, sz) -> None:
    from transmogrifai_tpu.models import trees as MT
    ok = getattr(MT, "forest_lane_route_ok", None)
    for g in grids:
        if ok is None or not ok(cls(**params).copy(**g), sz["rows"],
                                sz["cols"], sz["folds"]):
            raise harness.BenchFailure(
                f"{cls.__name__} {g} declares no forest lane route "
                f"(models/trees.forest_lane_route_ok({sz['rows']}, "
                f"{sz['cols']}, {sz['folds']})): this program would grow "
                f"{sz['folds']} folds x every tree one after another, each "
                f"level its own pass over the matrix; nothing was made or "
                f"measured")


class ForestLaneSpy:
    """Keep what the forest lane route of the warm-up job produced, a grid
    point at a time: the program's bin edges and binned matrix, every
    (tree, fold) lane's tree, every tree's node subsets, the whole
    bootstrap vectors of the first `head` trees, the mean, variance and
    first `prefix` draws of all of them, and the votes it summed for
    `fold`. A program without that seam leaves `points` empty, and the
    run says so."""

    def __init__(self, fold: int, head: int, prefix: int):
        self.fold, self.head, self.prefix = fold, head, prefix
        self.points = []

    def __enter__(self):
        from transmogrifai_tpu.models import trees as MT
        from transmogrifai_tpu.ops import trees as T
        self._T, self._cls = T, MT._ForestBase
        self._orig = {n: getattr(T, n, None)
                      for n in ("forest_bootstrap", "fit_forest_lanes")}
        self._hook = MT._ForestBase.__dict__.get("_mask_scores_fused")
        if self._hook is None or None in self._orig.values():
            return self
        spy = self

        def hook(est, ctx, *args, **kw):
            spy.points.append({
                "Xb": ctx[0], "edges": np.asarray(ctx[1], np.float32),
                "min_instances": float(
                    est.get_param("min_instances_per_node")),
                "trees": [], "subsets": [], "boot_head": [],
                "boot_stats": [], "boot_prefix": [], "votes_fold": None})
            return spy._hook(est, ctx, *args, **kw)

        def bootstrap(key, start, subsample, **kw):
            rw, keys = spy._orig["forest_bootstrap"](key, start, subsample,
                                                     **kw)
            pt = spy.points[-1]
            live = max(min(kw["group"], kw["n_trees"] - int(start)), 0)
            pt["live"] = live
            counts = rw[:live].astype("int32")   # exact sums, not f32's
            n, s1, s2 = rw.shape[1], counts.sum(axis=1), \
                (counts * counts).sum(axis=1)
            for t in range(live):
                mean = int(s1[t]) / n
                pt["boot_stats"].append((mean, int(s2[t]) / n - mean ** 2))
                pt["boot_prefix"].append(
                    np.asarray(rw[t, :spy.prefix]).astype(np.uint8))
                if len(pt["boot_head"]) < spy.head:
                    pt["boot_head"].append(
                        np.asarray(rw[t]).astype(np.uint8))
            return rw, keys

        def fit(Xb, y, W, rw, node_keys, votes, **kw):
            out = spy._orig["fit_forest_lanes"](Xb, y, W, rw, node_keys,
                                                votes, **kw)
            new_votes, trees, subsets = out
            pt = spy.points[-1]
            folds, live = int(W.shape[0]), pt["live"]
            pt["trees"].append({
                k: np.asarray(getattr(trees, k)).reshape(
                    (-1, folds) + getattr(trees, k).shape[1:])[:live]
                for k in ("feat", "thresh", "miss", "leaf")})
            n_feat = int(Xb.shape[1])
            pt["subsets"].append(
                np.ones((live, (1 << kw["depth"]) - 1, n_feat), bool)
                if subsets is None else np.asarray(subsets)[:live])
            pt["votes_fold"] = new_votes[spy.fold]
            return out

        MT._ForestBase._mask_scores_fused = hook
        T.forest_bootstrap, T.fit_forest_lanes = bootstrap, fit
        return self

    def __exit__(self, *exc):
        if self._hook is not None and None not in self._orig.values():
            self._cls._mask_scores_fused = self._hook
            for n, fn in self._orig.items():
                setattr(self._T, n, fn)

    def finished(self) -> list:
        """The points with their groups joined: trees [trees, folds, ...]
        (leaf [trees, folds, leaves]), subsets [trees, nodes, F]."""
        out = []
        for pt in self.points:
            if not pt["trees"]:
                continue
            trees = {k: np.concatenate([g[k] for g in pt["trees"]])
                     for k in ("feat", "thresh", "miss", "leaf")}
            trees["leaf"] = trees["leaf"][..., 0]
            out.append(dict(
                pt, trees=trees, subsets=np.concatenate(pt["subsets"]),
                boot_head=np.stack(pt["boot_head"]),
                boot_prefix=np.stack(pt["boot_prefix"])))
        return out


def setup(ctx):
    sz = ctx.sizes
    if ctx.rehearse:
        for target, value in ctx.cell["rehearsal"].get(
                "program_globals", {}).items():
            mod, _, name = target.partition(":")
            setattr(importlib.import_module(mod), name, value)
    for fam, spec in ctx.cell["families"].items():
        cls, params, grids = harness.pool_entry(
            ctx.config["pool"][fam], spec["grid"], ctx.rehearse)
        _require_route(cls, params, grids, sz)
    c = _checks(ctx)["forest_answer"]
    with ForestLaneSpy(c["fold"], c["replay_trees"],
                       min(c["bootstrap_prefix"], sz["rows"])) as spy:
        st = sweep.setup(ctx)
    st.forest_points = spy.finished()
    tele = getattr(st.last_val, "last_tree_telemetry", None) or {}
    ctx.notes["forest_lanes"] = dict(tele)
    expect = ctx.cell["expect"]["forest_lanes"]
    n_points = sum(len(g) for *_, g in st.pool)
    ctx.require(len(st.forest_points) == n_points,
                f"{len(st.forest_points)} grid points ran as forest lanes, "
                f"not {n_points}")
    if not ctx.rehearse:
        for key, want in expect.items():
            ctx.require(tele.get(key) == want,
                        f"the sweep counted {key} = {tele.get(key)}, "
                        f"not {want}")
    return st


def _checks(ctx) -> dict:
    return {k: dict(c, **(c.get("rehearsal", {}) if ctx.rehearse else {}))
            for k, c in ctx.cell.get("checks", {}).items()}


def run_window(ctx, st) -> harness.Result:
    result = sweep.run_window(ctx, st)
    tele = getattr(st.last_val, "last_tree_telemetry", None) or {}
    # the program's own counts, under the names the layer files read
    for key in ("tree_lanes", "lane_groups", "lanes_per_group",
                "bootstrap_draws"):
        if key in tele:
            ctx.counters["rf_" + key] = tele[key]
    return result


def verify(ctx, st) -> None:
    """The kernel twins as drivers/sweep.py replays them (at this cell's
    lane count: the dispatcher calls are the warm-up job's), then the
    forest's own checks against benchmark/reference_forest.py."""
    sweep.verify(ctx, st)
    c = _checks(ctx)["forest_answer"]
    fam, _, params, grids = next(p for p in st.pool
                                 if p[0] == c["family"])
    n = st.X.shape[0]
    masks = st.last_val.fold_masks(np.zeros(n))      # [folds, n], 1 = train
    grid0 = dict(params, **grids[0])
    ctx.notes["forest_answer"] = {}
    try:
        reference.require(bool(st.forest_points),
                          "the program handed over no forest lanes: "
                          "nothing to hold to the reference")
        reference_forest.forest_sweep_answer(
            st.last_best, st.forest_points, masks, st.X, st.y,
            into=ctx.notes["forest_answer"], fold=c["fold"],
            replay_trees=c["replay_trees"], depth=grid0["max_depth"],
            bins=grid0["max_bins"], trees=grid0["num_trees"],
            min_info_gain=grid0["min_info_gain"],
            subsample=grid0["subsampling_rate"],
            features_per_node=c["features_per_node"],
            train_rows=c["train_rows"], tol_gain=c["tol_gain"],
            tol_leaf=c["tol_leaf"], tol_vote=c["tol_vote"],
            tol_metric=c["tol_metric"], tol_moment=c["tol_moment"],
            tol_corr=c["tol_corr"], tol_plain=c["tol_plain"])
    except reference.CheckFailure as e:
        ctx.require(False, f"reference check failed: {e}")
