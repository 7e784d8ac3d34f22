"""``plan calibrate`` — a bounded micro-bench grid that seeds the corpus.

A cold corpus pins every decision to the hand defaults; calibration
buys the planner its first measured evidence on the CURRENT backend in
minutes. Each family below times a small, deterministic workload per
candidate knob value (or per route) with honest device syncs
(``block_until_ready`` before every clock read), writing warm-wall
records — and cold/compile records where the compile cost IS the
decision input (tree growth forms, the fused sweep).

The workloads are the repo's own kernels where that is cheap (the
streamed GLM round driver, the fused tree fit) and tiny shape-faithful
proxies where a real run would blow the minutes budget (the tileplane
copy/reduce loop, bucketized scoring). Every record is labeled
``src="calibrate"``; harvested hardware spans land beside them and the
model blends both.

Budget discipline: families run in priority order and each checks the
remaining wall budget before starting — a tight budget yields a
partial (still useful) corpus, never an overrun.
"""
from __future__ import annotations

import json
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .corpus import Corpus, PlanRecord
from .model import CANDIDATES
from .plan import corpus_dir as _default_corpus_dir

_SEED = 20260803


def _records_for_values(family: str, backend: str, values, measure,
                        shape: Dict[str, float], work: float
                        ) -> List[PlanRecord]:
    out = []
    for v in values:
        wall = measure(v)
        if wall is None:
            continue
        out.append(PlanRecord(
            family=family, backend=backend, knobs={"value": v},
            shape=dict(shape), wall_s=float(wall), work=float(work),
            src="calibrate"))
    return out


def _cal_tileplane_tile(backend: str, scale: float) -> List[PlanRecord]:
    """Host->device tile copy + reduce per TMOG_TILE_MB candidate over a
    fixed total byte count — the tileplane's per-tile cost shape."""
    import jax
    import jax.numpy as jnp

    row_bytes = 256 * 4                        # 1 KB/row, 64 MB total
    total_rows = max(int((1 << 16) * scale), 1024)
    rng = np.random.default_rng(_SEED)
    host = rng.normal(size=(total_rows, 256)).astype(np.float32)

    @jax.jit
    def reduce_tile(t):
        return jnp.sum(t)

    def measure(tile_mb: int) -> Optional[float]:
        tile_rows = max((int(tile_mb) << 20) // row_bytes, 256)
        # warm the program shapes first so the measured pass is copies
        # + dispatch, not compiles
        for start in range(0, total_rows, tile_rows):
            jax.block_until_ready(reduce_tile(
                jnp.asarray(host[start:start + tile_rows])))
        t0 = time.perf_counter()
        acc = []
        for start in range(0, total_rows, tile_rows):
            acc.append(reduce_tile(
                jnp.asarray(host[start:start + tile_rows])))
        jax.block_until_ready(acc)
        return time.perf_counter() - t0

    return _records_for_values(
        "tileplane_tile", backend, CANDIDATES["tile_mb"], measure,
        {"rows": float(total_rows), "feat": 256.0},
        work=float(total_rows * row_bytes))


def _cal_tile_rows(family: str, backend: str, candidates, n_feat: int,
                   total_rows: int, step_builder) -> List[PlanRecord]:
    """Shared fixed-tile-shape pass timer for the stats/score tile-row
    knobs: one jitted per-tile program per candidate shape, warmed,
    then one full measured pass over the same total row count."""
    import jax

    rng = np.random.default_rng(_SEED)
    host = rng.normal(size=(total_rows, n_feat)).astype(np.float32)

    def measure(tile_rows: int) -> Optional[float]:
        tile_rows = int(tile_rows)
        if tile_rows > total_rows:
            return None
        step = step_builder()
        import jax.numpy as jnp
        tile0 = jnp.asarray(host[:tile_rows])
        jax.block_until_ready(step(tile0))  # compile outside the clock
        t0 = time.perf_counter()
        outs = []
        for start in range(0, total_rows - tile_rows + 1, tile_rows):
            outs.append(step(jnp.asarray(host[start:start + tile_rows])))
        jax.block_until_ready(outs)
        return time.perf_counter() - t0

    return _records_for_values(
        family, backend, candidates, measure,
        {"rows": float(total_rows), "feat": float(n_feat)},
        work=float(total_rows))


def _cal_stats_tile(backend: str, scale: float) -> List[PlanRecord]:
    import jax
    import jax.numpy as jnp

    def build():
        @jax.jit
        def step(t):  # the stats engine's per-tile moment shape
            return jnp.sum(t, 0), jnp.sum(t * t, 0), jnp.sum(t > 0, 0)
        return step

    total = max(int((1 << 19) * scale), 1 << 16)
    return _cal_tile_rows("stats_tile", backend,
                          [c for c in CANDIDATES["stats_tile_rows"]],
                          16, total, build)


def _cal_score_tile(backend: str, scale: float) -> List[PlanRecord]:
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(_SEED + 1)
    wv = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))

    def build():
        @jax.jit
        def step(t):  # a bulk-scoring stage program's shape
            return jax.nn.sigmoid(t @ wv[:t.shape[1]])
        return step

    total = max(int((1 << 17) * scale), 1 << 14)
    return _cal_tile_rows("score_tile", backend,
                          [c for c in CANDIDATES["score_tile_rows"]],
                          64, total, build)


def _cal_glm_routes(backend: str, scale: float) -> List[PlanRecord]:
    """The real streamed round driver vs a vmapped per-lane IRLS fit at
    two row scales — the evidence behind the streamed-vs-materialized
    crossover."""
    import jax
    import jax.numpy as jnp
    from ..ops import glm as G
    from ..ops import glm_sweep as GS

    d, folds = 16, 2
    regs = np.asarray([1e-3, 1e-2, 1e-1, 0.3], np.float32)
    alphas = np.zeros_like(regs)
    lanes = folds * len(regs)
    out: List[PlanRecord] = []
    for rows in (max(int(20_000 * scale), 2_000),
                 max(int(60_000 * scale), 6_000)):
        rng = np.random.default_rng(_SEED + rows)
        Xd = jnp.asarray(rng.normal(size=(rows, d)).astype(np.float32))
        yd = jnp.asarray(
            (rng.uniform(size=rows) < 0.5).astype(np.float32))
        masks = (rng.integers(0, folds, size=rows)[None, :]
                 != np.arange(folds)[:, None]).astype(np.float32)
        shape = {"rows": float(rows), "feat": float(d),
                 "lanes": float(lanes)}
        work = float(rows) * d * lanes

        # calibration compiles one program per measured shape ON PURPOSE
        # (the lambda closes over this shape's Xd/yd) and the warmup call
        # below keeps the compile out of the clocked window
        # tmoglint: disable=TRC001  per-shape compile IS the measurement
        vfit = jax.jit(jax.vmap(
            lambda wl, r: G.fit_logistic(Xd, yd, wl, r, 0.0,
                                         max_iter=10),
            in_axes=(0, 0)))
        w_lanes = jnp.asarray(
            np.repeat(masks, len(regs), axis=0))       # [lanes, rows]
        r_lanes = jnp.asarray(np.tile(regs, folds))
        jax.block_until_ready(vfit(w_lanes, r_lanes))  # compile
        t0 = time.perf_counter()
        jax.block_until_ready(vfit(w_lanes, r_lanes))
        out.append(PlanRecord(
            family="glm_sweep", backend=backend, route="vmapped",
            shape=shape, wall_s=time.perf_counter() - t0, work=work,
            src="calibrate"))

        def run_streamed():
            # returns host arrays: the call is device-synced by its own
            # final fetch, so the clock reads below are honest
            return GS.sweep_glm_streamed_rounds(
                Xd, yd, jnp.ones(rows, jnp.float32), jnp.asarray(masks),
                regs, alphas, loss="logistic", max_iter=10)
        B, b0, _info = run_streamed()             # compile + warm caches
        jax.block_until_ready((jnp.asarray(B), jnp.asarray(b0)))
        t0 = time.perf_counter()
        B, b0, _info = run_streamed()
        jax.block_until_ready((jnp.asarray(B), jnp.asarray(b0)))
        out.append(PlanRecord(
            family="glm_sweep", backend=backend, route="streamed",
            shape=shape, wall_s=time.perf_counter() - t0, work=work,
            src="calibrate"))
    return out


def _cal_tree_routes(backend: str, scale: float) -> List[PlanRecord]:
    """Grid-fused-vs-per-config lane batching on the real fused fit,
    with the compile wall recorded from the cold call (the knee term's
    measured companion)."""
    import jax
    import jax.numpy as jnp
    from ..ops import trees as T

    rows = max(int(20_000 * scale), 2_000)
    F, bins, depth, rounds = 16, 16, 5, 2
    rng = np.random.default_rng(_SEED + 7)
    Xb = jnp.asarray(rng.integers(0, bins + 1, size=(rows, F)), jnp.int8)
    y = jnp.asarray((rng.uniform(size=rows) < 0.4), jnp.float32)
    key = jax.random.PRNGKey(0)

    def fit(lanes: int):
        W = jnp.asarray(
            (rng.integers(0, 2, size=(lanes, rows)) > 0), jnp.float32)

        def run():
            return T.fit_gbt_folds(Xb, y, W, key, n_rounds=rounds,
                                   depth=depth, n_bins=bins)
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        warm = time.perf_counter() - t0
        return warm, max(cold - warm, 0.0)

    out: List[PlanRecord] = []
    # grid fusion: 4 configs x 2 folds as ONE 8-lane program vs 4
    # sequential 2-lane programs (identical total work)
    warm8, compile8 = fit(lanes=8)
    t_seq = 0.0
    for _ in range(4):
        warm2, _ = fit(lanes=2)
        t_seq += warm2
    shape = {"rows": float(rows), "feat": float(F), "lanes": 8.0,
             "depth": float(depth)}
    work = float(rows) * F * 8 * depth
    out.append(PlanRecord(
        family="tree_sweep", backend=backend, route="grid_fused",
        shape=shape, wall_s=warm8, work=work, src="calibrate"))
    out.append(PlanRecord(
        family="tree_sweep", backend=backend, route="grid_fused",
        shape=shape, compile_s=compile8, work=work, cold=True,
        src="calibrate"))
    out.append(PlanRecord(
        family="tree_sweep", backend=backend, route="per_config",
        shape=shape, wall_s=t_seq, work=work, src="calibrate"))
    return out


def _expected_ladder_cost(walls: Dict[int, float], floor: int,
                          top: int, req_sizes) -> float:
    """Expected per-request wall under a power-of-two ladder with this
    floor: each request pays the smallest rung >= its size."""
    def rung(s: int) -> int:
        if s <= 1:
            return 1
        b = floor
        while b < s and b < top:
            b *= 2
        return b
    return float(np.mean([walls[rung(s)] for s in req_sizes]))


def _cal_bucket_floors(backend: str, scale: float) -> List[PlanRecord]:
    """Bucketized dispatch walls -> expected per-request cost per floor
    candidate, for BOTH power-of-two ladders (the serving bucket ladder
    and the GLM lane-retirement compaction ladder)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(_SEED + 11)
    wv = jnp.asarray(rng.normal(size=(64, 1)).astype(np.float32))
    sizes = [1, 2, 4, 8, 16, 32]

    @jax.jit
    def score(t):
        return jax.nn.sigmoid(t @ wv)

    walls: Dict[int, float] = {}
    for s in sizes:
        batch = jnp.asarray(rng.normal(size=(s, 64)).astype(np.float32))
        jax.block_until_ready(score(batch))  # compile outside the clock
        reps = 50
        t0 = time.perf_counter()
        outs = [score(batch) for _ in range(reps)]
        jax.block_until_ready(outs)
        walls[s] = (time.perf_counter() - t0) / reps

    out: List[PlanRecord] = []
    req = rng.integers(1, 9, size=256)     # serving: small requests
    for floor in CANDIDATES["serve_bucket_floor"]:
        out.append(PlanRecord(
            family="serve_bucket", backend=backend,
            knobs={"value": int(floor)},
            shape={"max_batch": 32.0},
            wall_s=_expected_ladder_cost(walls, int(floor), 32, req),
            work=1.0, src="calibrate"))
    # GLM lane retirement: active-lane counts decay geometrically
    decay = [32, 17, 9, 4, 2, 1]
    for floor in CANDIDATES["glm_bucket_floor"]:
        cost = sum(_expected_ladder_cost(walls, int(floor), 32, [a])
                   for a in decay)
        out.append(PlanRecord(
            family="glm_bucket", backend=backend,
            knobs={"value": int(floor)}, shape={"lanes": 32.0},
            wall_s=cost, work=1.0, src="calibrate"))
    return out


def _cal_grid_caps(backend: str, scale: float) -> List[PlanRecord]:
    """Measured walls for the fused-sweep chunk caps on the repo's own
    route+hist pass: lane-chunk size (family ``tree_sweep_lanes`` — the
    TMOG_GRID_FUSE_HBM_LANES candidates, one fixed lane total processed
    in candidate-sized chunks, so fewer bigger passes race more smaller
    ones) and out-block size (family ``tree_sweep_out`` — node counts
    chosen so the fused histogram block lands near each candidate MB).
    These are the records that let ``planned_grid_fuse_caps`` leave its
    priors; the out-MB argmin is still knee-filtered at plan time, so a
    fast-measured 16MB block can never bust the compile budget."""
    import jax
    import jax.numpy as jnp
    from ..ops import pallas_hist as PH

    rows = max(int(20_000 * scale), 2_000)
    F, B = 16, 17
    rng = np.random.default_rng(_SEED + 13)
    Xb_t = jnp.asarray(rng.integers(0, B, size=(F, rows)), jnp.int8)

    def pass_wall(lanes: int, n_nodes: int) -> float:
        pay = jnp.asarray(
            rng.normal(size=(2 * lanes, rows)).astype(np.float32))
        node = jnp.asarray(
            rng.integers(0, n_nodes, size=(lanes, rows))
            .astype(np.float32))
        f_lvl = jnp.asarray(
            rng.integers(0, F, size=(lanes, n_nodes)), jnp.int32)
        t_lvl = jnp.full((lanes, n_nodes), B // 2, jnp.int32)
        m_lvl = jnp.zeros((lanes, n_nodes), jnp.int32)

        def one():
            return PH.route_hist(Xb_t, pay, node, f_lvl, t_lvl, m_lvl,
                                 n_nodes=n_nodes, n_bins=B,
                                 allow_bf16=True, derive_count=True)
        jax.block_until_ready(one())  # compile outside the clock
        t0 = time.perf_counter()
        jax.block_until_ready(one())
        return time.perf_counter() - t0

    out: List[PlanRecord] = []
    # the lane pool must be at least the largest candidate or every
    # chunk degenerates to the same one-pass program and the argmin
    # would select on timer noise alone
    total_lanes = max(CANDIDATES["grid_fuse_hbm_lanes"])
    for cand in CANDIDATES["grid_fuse_hbm_lanes"]:
        chunk = min(int(cand), total_lanes)
        passes = -(-total_lanes // chunk)
        out.append(PlanRecord(
            family="tree_sweep_lanes", backend=backend,
            knobs={"value": int(cand)},
            shape={"rows": float(rows), "feat": float(F),
                   "lanes": float(total_lanes)},
            wall_s=pass_wall(chunk, 4) * passes,
            work=float(rows) * total_lanes, src="calibrate"))
    lanes = 8
    per_node_bytes = lanes * 3 * B * 4  # the fused hist block row cost
    for cand in CANDIDATES["grid_fuse_out_mb"]:
        n_nodes = max(int((float(cand) * 1e6) // per_node_bytes), 2)
        out.append(PlanRecord(
            family="tree_sweep_out", backend=backend,
            knobs={"value": float(cand)},
            shape={"rows": float(rows), "feat": float(F),
                   "lanes": float(lanes), "nodes": float(n_nodes)},
            wall_s=pass_wall(lanes, n_nodes),
            work=float(rows) * lanes, src="calibrate"))
    return out


_FAMILIES: List = [
    ("tileplane_tile", _cal_tileplane_tile),
    ("stats_tile", _cal_stats_tile),
    ("score_tile", _cal_score_tile),
    ("bucket_floors", _cal_bucket_floors),
    ("glm_routes", _cal_glm_routes),
    ("tree_routes", _cal_tree_routes),
    ("grid_caps", _cal_grid_caps),
]


def run_calibration(corpus_path: Optional[str] = None, *,
                    budget_s: float = 180.0,
                    scale: float = 1.0) -> Dict[str, Any]:
    """Run every calibration family within the wall budget and append
    the records to the corpus. Families are fault-isolated: one failing
    micro-bench logs and skips, the rest still land. Returns the
    summary the CLI prints (and emits a ``plan_calibrated`` event)."""
    import jax

    t0 = time.perf_counter()
    backend = jax.default_backend()
    corpus = Corpus(corpus_path or _default_corpus_dir())
    counts: Dict[str, int] = {}
    errors: Dict[str, str] = {}
    for name, fn in _FAMILIES:
        # each family syncs its own measurements; this clock only
        # enforces the overall budget
        # tmoglint: disable=TPU005  budget clock, not a kernel wall
        if time.perf_counter() - t0 > budget_s:
            errors[name] = "skipped: budget"
            continue
        try:
            recs = fn(backend, scale)
            counts[name] = corpus.append(recs)
        except Exception as e:  # fault-isolated by contract
            errors[name] = f"{type(e).__name__}: {str(e)[:160]}"
    summary = {"backend": backend, "corpus": corpus.path,
               "records": counts,
               "total_records": sum(counts.values()),
               # tmoglint: disable=TPU005  budget clock, not a kernel wall
               "wall_s": round(time.perf_counter() - t0, 2)}
    if errors:
        summary["errors"] = errors
    try:
        from ..utils.metrics import collector
        collector.event("plan_calibrated", backend=backend,
                        records=sum(counts.values()),
                        wall_seconds=summary["wall_s"])
    except Exception:
        pass
    return summary


# -- CLI (python -m transmogrifai_tpu plan ...) ------------------------------

def run_plan_cli(args) -> int:
    """Dispatch for the ``plan`` subcommand: calibrate | show |
    explain."""
    from . import plan as P
    path = args.corpus_dir or P.corpus_dir()
    if args.action == "calibrate":
        summary = run_calibration(path, budget_s=args.budget_s,
                                  scale=args.scale)
        print(json.dumps(summary, sort_keys=True))
        return 0
    if args.action == "show":
        print(json.dumps(Corpus(path).summary(), indent=2,
                         sort_keys=True))
        return 0
    # explain: resolve a plan for the given shape and print each
    # decision with its provenance and alternatives. The resolved path
    # OVERRIDES any pre-set TMOG_PLAN_CORPUS_DIR: an explicit
    # --corpus-dir must be the corpus the printed decisions came from
    import os
    os.environ["TMOG_PLAN_CORPUS_DIR"] = path
    fit = P.plan_fit(n_rows=args.rows, n_feat=args.feat,
                     n_folds=args.folds, n_grids=args.grids,
                     depth=args.depth, n_bins=args.bins,
                     n_shards=getattr(args, "shards", 1))
    serving = P.plan_serving(args.max_batch)
    if args.json:
        print(json.dumps({"fit": fit.to_json(),
                          "serving": serving.to_json()}, sort_keys=True))
        return 0
    print(f"plan explain  backend={fit.backend}  corpus={path}")
    print(f"shape: rows={args.rows} feat={args.feat} folds={args.folds} "
          f"grids={args.grids} depth={args.depth} bins={args.bins}")
    print(f"{'decision':<24}{'value':>12}  {'source':<9} alternatives")
    for name, d in fit.decisions.items():
        alts = ", ".join(
            f"{k}={v:.3g}" if isinstance(v, float) else f"{k}=?"
            for k, v in list(d.alternatives.items())[:6]) or "-"
        print(f"{name:<24}{str(d.value):>12}  {d.source:<9} {alts}")
    d = serving.decisions["serve_bucket_floor"]
    print(f"{'serve_bucket_floor':<24}{str(d.value):>12}  {d.source:<9} "
          f"ladder={list(serving.buckets)}")
    return 0
