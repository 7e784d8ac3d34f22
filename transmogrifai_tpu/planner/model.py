"""The plan cost model: analytic priors blended with measured evidence.

Two ingredients, in strict priority order:

* **Priors.** The hand-tuned defaults that shipped every PR so far
  (``HAND_DEFAULTS`` — the same numbers the knobs' own modules carry)
  plus the analytic cost terms the kernels already publish: the HBM
  traffic models in ``ops/pallas_hist`` / ``ops/stats_engine`` and a
  **compile-cost knee term** fit to the round-5 compile-knee
  measurements (r5 session 2: ~75 s Mosaic compiles at the 8 MB fused
  out-block cap, 20+ minutes at a 16 MB block). A cold corpus yields
  exactly the priors, so a cold planner reproduces today's hand plan
  bit for bit.

* **Measurements.** Corpus records blend in as nearest-shape
  observations in log-shape space: a route/knob cost at a query shape
  is the median *unit* cost (wall per work unit) of the k nearest
  measured shapes, scaled by the query's analytic work. A knob
  candidate only beats the hand default when BOTH have been measured —
  one stray observation of an alternative can never outvote an
  unmeasured default.

Decisions are per (backend): TPU evidence never informs CPU plans and
vice versa (corpora are per-backend files for the same reason).
"""
from __future__ import annotations

import math
import statistics
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

from .corpus import Corpus, PlanRecord

#: Today's hand plan — one row per retired hand knob / constant, each
#: matching the owning module's shipped default (docs/planning.md maps
#: every row to its planner decision). The cold-corpus no-op guarantee
#: is an equality test against this table.
HAND_DEFAULTS: Dict[str, Any] = {
    # automl/tuning/validators.STREAMED_SWEEP_MIN_ROWS
    "glm_streamed_min_rows": 200_000,
    # validators TMOG_GRID_FUSE default (opt-in because of the knee)
    "grid_fuse": False,
    # ops/pallas_hist TMOG_GRID_FUSE_HBM_LANES / _OUT_MB defaults
    "grid_fuse_hbm_lanes": 64,
    "grid_fuse_out_mb": 8.0,
    # parallel/tileplane TMOG_TILE_MB default
    "tile_mb": 32,
    # ops/stats_engine TMOG_STATS_TILE_ROWS default
    "stats_tile_rows": 1 << 18,
    # readers/streaming TMOG_SCORE_TILE_ROWS default
    "score_tile_rows": 1024,
    # ops/glm_sweep._BUCKET_MIN (lane-retirement compaction ladder floor)
    "glm_bucket_floor": 8,
    # serve/engine._BUCKET_FLOOR (serving bucket ladder floor)
    "serve_bucket_floor": 8,
    # parallel/tileplane TMOG_TILE_PREFETCH default (prefetch-ring
    # depth; 1 = the classic two-in-flight double buffering)
    "tile_prefetch": 1,
    # parallel/ingest TMOG_INGEST_WORKERS default (parse-worker pool)
    "ingest_workers": 1,
}

#: candidate grids the measured argmin searches over (the default is
#: always a member, so "default measured + candidate measured" is the
#: only way a knob moves)
CANDIDATES: Dict[str, Tuple] = {
    "tile_mb": (8, 16, 32, 64, 128),
    "stats_tile_rows": (1 << 15, 1 << 16, 1 << 17, 1 << 18, 1 << 19),
    "score_tile_rows": (256, 512, 1024, 2048, 4096),
    "glm_bucket_floor": (4, 8, 16),
    "serve_bucket_floor": (2, 4, 8),
    "grid_fuse_hbm_lanes": (32, 64, 128),
    "grid_fuse_out_mb": (2.0, 4.0, 8.0, 12.0, 16.0),
    "tile_prefetch": (1, 2, 3, 4),
    "ingest_workers": (1, 2, 4, 8),
}

#: Mosaic compile budget a planned program must clear; anything past it
#: is rejected at plan time instead of discovered 20 minutes into a
#: compile (the r5 failure mode that keeps TMOG_GRID_FUSE opt-in).
COMPILE_BUDGET_S = 180.0

_KNN = 3


def compile_knee_s(out_mb: float, backend: str = "tpu") -> float:
    """Predicted whole-program compile wall (seconds) vs the fused
    out-block size in MB.

    TPU: an exponential fit through the two anchors the knee harness
    measured — ~75 s at the 8 MB TMOG_GRID_FUSE_OUT_MB default cap and
    ~21 min at the 16 MB block of r5 session 2 (Mosaic's layout search
    explodes as the out block nears the scoped-VMEM boundary) —
    ``4.3 * exp(0.356 * out_mb)``. Other backends run plain XLA with no
    Mosaic layout search: compile cost is small and near-flat in the
    out-block size."""
    mb = max(float(out_mb), 0.0)
    if backend == "tpu":
        return 4.3 * math.exp(0.356 * mb)
    return 1.0 + 0.05 * mb


def compile_ok(out_mb: float, backend: str = "tpu",
               budget_s: float = COMPILE_BUDGET_S) -> bool:
    """Does the knee term clear the compile budget at this out-block
    size? The 16 MB shape r5 measured at 20+ minutes is rejected here
    at plan time (test-pinned)."""
    return compile_knee_s(out_mb, backend) <= budget_s


def _log_distance(a: Mapping[str, float], b: Mapping[str, float]) -> float:
    """Euclidean distance in log1p-shape space over the union of keys
    (a key one side lacks reads as 0 — absent geometry is small
    geometry, keeping sparse harvest records usable)."""
    keys = set(a) | set(b)
    if not keys:
        return 0.0
    return math.sqrt(sum(
        (math.log1p(max(float(a.get(k, 0.0)), 0.0))
         - math.log1p(max(float(b.get(k, 0.0)), 0.0))) ** 2
        for k in keys))


def _default_work(shape: Mapping[str, float]) -> float:
    """Fallback analytic work proxy: rows x feat x lanes x depth over
    whatever geometry the shape names (missing axes count 1)."""
    w = 1.0
    for k in ("rows", "feat", "lanes", "depth"):
        v = float(shape.get(k, 0.0) or 0.0)
        if v > 0:
            w *= v
    return max(w, 1.0)


class CostModel:
    """Measured-cost queries over one backend's corpus slice."""

    def __init__(self, corpus: Corpus, backend: str) -> None:
        self.backend = backend
        self._records = [r for r in corpus.load(backend)
                         if r.backend == backend]

    # -- raw access ---------------------------------------------------------
    def obs(self, family: str, route: Optional[str] = None,
            knob_value: Any = None, warm: bool = True
            ) -> List[PlanRecord]:
        out = []
        for r in self._records:
            if r.family != family:
                continue
            if route is not None and r.route != route:
                continue
            if knob_value is not None \
                    and r.knobs.get("value") != knob_value:
                continue
            if warm and r.wall_s <= 0.0:
                continue
            if not warm and r.compile_s <= 0.0:
                continue
            out.append(r)
        return out

    @staticmethod
    def _unit_cost(r: PlanRecord,
                   work_fn: Callable[[Mapping[str, float]], float]
                   ) -> float:
        work = r.work if r.work > 0 else work_fn(r.shape)
        return r.wall_s / max(work, 1.0)

    def predict_wall(self, family: str, route: str,
                     shape: Mapping[str, float],
                     work_fn: Optional[Callable] = None
                     ) -> Optional[float]:
        """Predicted warm wall at ``shape``: median unit cost of the k
        nearest measured shapes x the query's analytic work. None when
        the (family, route) has no warm observations — the caller must
        then fall back to its prior."""
        work_fn = work_fn or _default_work
        recs = self.obs(family, route)
        if not recs:
            return None
        recs.sort(key=lambda r: _log_distance(r.shape, shape))
        unit = statistics.median(
            self._unit_cost(r, work_fn) for r in recs[:_KNN])
        return unit * max(work_fn(shape), 1.0)

    def predict_compile(self, family: str, route: str,
                        shape: Mapping[str, float]) -> float:
        """Predicted compile wall: the nearest cold observations when
        any exist, else 0 (the knee term is applied separately where an
        out-block size is known)."""
        recs = self.obs(family, route, warm=False)
        if not recs:
            return 0.0
        recs.sort(key=lambda r: _log_distance(r.shape, shape))
        return statistics.median(r.compile_s for r in recs[:_KNN])

    # -- decisions ----------------------------------------------------------
    def choose_value(self, name: str, family: str, default: Any,
                     candidates: Optional[Sequence] = None
                     ) -> Tuple[Any, str, Dict[Any, Optional[float]]]:
        """Measured argmin over a knob's candidate grid.

        Returns ``(value, source, alternatives)`` where alternatives
        maps candidate -> median unit cost (None = unmeasured). The
        default only loses to a candidate when BOTH are measured
        (source "measured"); a cold family keeps the default
        ("prior"). The comparison is PER HOST: absolute unit costs are
        not comparable across machines, so a candidate is judged by its
        median cost RATIO to the default on hosts that measured both —
        a merged corpus where a fast box happened to measure one
        candidate and a slow box another must not move the knob on
        hardware identity."""
        candidates = list(candidates if candidates is not None
                          else CANDIDATES.get(name, (default,)))
        if default not in candidates:
            candidates.append(default)
        alts: Dict[Any, Optional[float]] = {}
        by_host: Dict[str, Dict[Any, float]] = {}
        for cand in candidates:
            recs = self.obs(family, knob_value=cand)
            alts[cand] = (statistics.median(
                self._unit_cost(r, _default_work) for r in recs)
                if recs else None)
            hosts: Dict[str, List[float]] = {}
            for r in recs:
                hosts.setdefault(r.host, []).append(
                    self._unit_cost(r, _default_work))
            for host, costs in hosts.items():
                by_host.setdefault(host, {})[cand] = \
                    statistics.median(costs)
        ratios: Dict[Any, float] = {}
        for cand in candidates:
            if cand == default:
                continue
            rs = [cmap[cand] / max(cmap[default], 1e-12)
                  for cmap in by_host.values()
                  if cand in cmap and default in cmap]
            if rs:
                ratios[cand] = statistics.median(rs)
        winners = {c: r for c, r in ratios.items() if r < 1.0}
        if not winners:
            return default, "prior", alts
        best = min(winners, key=lambda c: winners[c])
        return best, "measured", alts

    def feed_compute_ratio(self) -> Optional[float]:
        """Median (tile_parse + tile_copy) / tile_compute unit-cost
        ratio over the harvested tileplane tile spans — how many times
        slower the FEED side (host parse + H2D copy) runs than the
        device step. The prefetch-depth decision sizes the ring from
        this: a feed k x slower than compute needs ~k tiles in flight
        before the device stops starving.

        Per host, like choose_value: absolute unit costs are not
        comparable across machines, so the ratio is formed only on
        hosts that measured the compute side, and the cross-host median
        is returned. None when no host measured tile_compute, or no
        host measured any feed-side family — cold stays cold."""
        def per_host(family: str) -> Dict[str, float]:
            hosts: Dict[str, List[float]] = {}
            for r in self.obs(family):
                hosts.setdefault(r.host, []).append(
                    self._unit_cost(r, _default_work))
            return {h: statistics.median(v) for h, v in hosts.items()}

        compute = per_host("tileplane_compute")
        parse = per_host("ingest_parse")
        copy = per_host("tileplane_copy")
        ratios = []
        for host, c in compute.items():
            if c <= 0:
                continue
            feed = parse.get(host, 0.0) + copy.get(host, 0.0)
            if feed > 0:
                ratios.append(feed / c)
        return statistics.median(ratios) if ratios else None

    def choose_route(self, family: str, routes: Sequence[str],
                     default: str, shape: Mapping[str, float],
                     work_fn: Optional[Callable] = None,
                     amortize: int = 1
                     ) -> Tuple[str, str, Dict[str, Optional[float]]]:
        """Measured argmin over route labels at a shape, charging each
        route its predicted compile wall amortized over ``amortize``
        expected reuses. Every route must be measured or the default
        holds (a route we have never run is not evidence it is slow —
        it is absence of evidence)."""
        alts: Dict[str, Optional[float]] = {}
        for route in routes:
            wall = self.predict_wall(family, route, shape, work_fn)
            if wall is None:
                alts[route] = None
                continue
            alts[route] = wall + self.predict_compile(
                family, route, shape) / max(int(amortize), 1)
        if any(v is None for v in alts.values()):
            return default, "prior", alts
        best = min(alts, key=lambda r: alts[r])  # type: ignore[arg-type]
        return best, ("prior" if best == default else "measured"), alts

    def crossover_rows(self, family: str, small_route: str,
                       big_route: str, shape: Mapping[str, float],
                       default_rows: int,
                       lo: int = 1_000, hi: int = 50_000_000
                       ) -> Tuple[int, str]:
        """Row threshold above which ``big_route`` (the higher-capacity
        kernel) beats ``small_route``, scanned over a geometric row
        grid with the rest of ``shape`` held fixed.

        Monotone by construction: the returned threshold is the
        smallest grid point from which big_route wins at EVERY larger
        grid point, so more rows can never select the smaller-capacity
        route once the threshold is crossed. The scan is bounded to the
        MEASURED row range (min observed row count to 4x the max): the
        kNN unit cost is constant beyond the nearest measurements, so
        an unbounded scan would extrapolate a flat "win" all the way
        down to the grid floor — a route can never be selected at row
        counts smaller than any shape it was actually measured at.
        Falls back to the hand default when either route is unmeasured
        or no consistent crossover exists, and clamps a measured
        threshold to [lo x 4, default x 16] so a few noisy points
        cannot push the route to an absurd extreme."""
        small_obs = self.obs(family, small_route)
        big_obs = self.obs(family, big_route)
        if not (small_obs and big_obs):
            return default_rows, "prior"
        measured = [r.shape.get("rows", 0.0)
                    for r in small_obs + big_obs
                    if r.shape.get("rows", 0.0) > 0]
        if not measured:
            return default_rows, "prior"
        r_lo = max(lo, int(min(measured)))
        r_hi = min(hi, int(max(measured)) * 4)
        grid: List[int] = []
        r = r_lo
        while r <= r_hi:
            grid.append(r)
            r *= 2
        wins = []
        for rows in grid:
            q = dict(shape)
            q["rows"] = float(rows)
            big = self.predict_wall(family, big_route, q)
            small = self.predict_wall(family, small_route, q)
            wins.append(big is not None and small is not None
                        and big <= small)
        threshold = None
        for i, rows in enumerate(grid):
            if all(wins[i:]):
                threshold = rows
                break
        if threshold is None:
            return default_rows, "prior"
        threshold = max(lo * 4, min(threshold, default_rows * 16))
        return threshold, ("prior" if threshold == default_rows
                           else "measured")

    def decide_grid_fuse(self, shape: Mapping[str, float],
                         out_mb: float) -> Tuple[bool, str, Dict]:
        """Fold x config fused sweep on/off: fused must be MEASURED
        faster than the per-config route at the nearest shape AND its
        planned out-block must clear the compile knee (predicted from
        the knee prior and any measured cold compiles, whichever is
        worse). Cold corpus -> off, exactly today's opt-in default."""
        route, source, alts = self.choose_route(
            "tree_sweep", ("grid_fused", "per_config"), "per_config",
            shape)
        knee = max(compile_knee_s(out_mb, self.backend),
                   self.predict_compile("tree_sweep", "grid_fused",
                                        shape))
        info = {"alternatives": alts, "out_mb": out_mb,
                "predicted_compile_s": round(knee, 1)}
        if source == "prior":
            return HAND_DEFAULTS["grid_fuse"], "prior", info
        if route != "grid_fused":
            return False, "measured", info
        if knee > COMPILE_BUDGET_S:
            info["rejected"] = "compile_knee"
            return False, "measured", info
        return True, "measured", info
