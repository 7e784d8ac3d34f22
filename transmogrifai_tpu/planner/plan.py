"""``plan_fit`` / ``plan_serving`` — THE per-shape route/knob choke point.

Every hot-path decision the repo used to read from a hand-set constant
or env var resolves here instead:

==========================  ===========================================
decision                    consumed by
==========================  ===========================================
glm_streamed_min_rows       validators._streamable (streamed-vs-
                            materialized GLM sweep route)
grid_fuse                   validators' config-fused sweep gate
grid_fuse_hbm_lanes/out_mb  ops/pallas_hist.plan_lane_chunk caps
tile_mb                     parallel/tileplane.tile_budget_bytes
stats_tile_rows             ops/stats_engine.stream_tile_rows_default
score_tile_rows             readers/streaming.score_tile_rows_default
glm_bucket_floor            ops/glm_sweep.bucket_lanes (lane-retirement
                            compaction ladder)
serve_bucket_floor          serve/engine bucket ladder (plan_serving)
tile_prefetch               parallel/tileplane.tile_prefetch_depth
                            (prefetch-ring depth; derived from measured
                            tile_parse/tile_copy/tile_compute ratios)
ingest_workers              parallel/ingest.ingest_workers (sharded
                            parse-worker pool size)
==========================  ===========================================

Precedence, strictly: **an explicitly-set TMOG_* env var always wins**
(hand beats model; the override is logged once as a ``plan_override``
event), then the measured model (``TMOG_PLAN=1``, the default), then
the hand default (``TMOG_PLAN=0``, or a cold corpus — in both cases the
plan is bit-identical to today's hand plan). Decision lookups are
cached against the corpus fingerprint and never raise: any planner
fault degrades to the hand default, because a broken corpus must not
break a fit.
"""
from __future__ import annotations

import dataclasses
import os
import threading
from typing import Any, Dict, Mapping, Optional, Tuple

from .corpus import Corpus
from .model import (COMPILE_BUDGET_S, HAND_DEFAULTS, CostModel,
                    compile_ok)

_DEFAULT_CORPUS_DIR = os.path.join("~", ".cache", "transmogrifai_tpu",
                                   "plan-corpus")

#: decision name -> the env knob that hand-overrides it (decisions that
#: were bare constants before this PR have no override knob)
_ENV_FOR: Dict[str, str] = {
    "grid_fuse": "TMOG_GRID_FUSE",
    "grid_fuse_hbm_lanes": "TMOG_GRID_FUSE_HBM_LANES",
    "grid_fuse_out_mb": "TMOG_GRID_FUSE_OUT_MB",
    "tile_mb": "TMOG_TILE_MB",
    "stats_tile_rows": "TMOG_STATS_TILE_ROWS",
    "score_tile_rows": "TMOG_SCORE_TILE_ROWS",
    "tile_prefetch": "TMOG_TILE_PREFETCH",
    "ingest_workers": "TMOG_INGEST_WORKERS",
}

_lock = threading.Lock()
_model_cache: Dict[Tuple, CostModel] = {}
_decision_cache: Dict[Tuple, "PlanDecision"] = {}
_overrides_logged: set = set()
_plans_logged: set = set()


def plan_enabled() -> bool:
    """The kill switch: TMOG_PLAN=0 pins every decision to its hand
    default (env overrides still logged and honored). Parsed through
    glm_sweep.env_on — the one tri-state TMOG_* toggle parse, so the
    accepted falsy spellings cannot drift between modules."""
    from ..ops.glm_sweep import env_on
    return env_on("TMOG_PLAN")


def corpus_dir() -> str:
    """TMOG_PLAN_CORPUS_DIR, defaulting to the per-user cache dir so
    calibration and harvested bench spans persist across runs."""
    return os.path.expanduser(
        os.environ.get("TMOG_PLAN_CORPUS_DIR", "").strip()
        or _DEFAULT_CORPUS_DIR)


def _backend() -> str:
    """Corpus key for this process's measurements. Multi-process pods
    append "-pc<N>": a collective-bearing span's wall includes DCN
    waits, so pod measurements must never steer (or be steered by)
    single-process plans — the suffix keys them into their own
    corpus-<backend>.jsonl file and plan cache (docs/planning.md)."""
    try:
        import jax
        backend = jax.default_backend()
        pc = jax.process_count()
        return f"{backend}-pc{pc}" if pc > 1 else backend
    except Exception:
        return "cpu"


@dataclasses.dataclass(frozen=True)
class PlanDecision:
    """One resolved decision: value + where it came from.

    source: ``prior`` (hand default — cold corpus or default won the
    measured comparison), ``measured`` (the corpus moved it), ``env``
    (an explicitly-set TMOG_* var overrode the planner), ``off``
    (TMOG_PLAN=0). ``alternatives`` maps candidate -> predicted cost
    (None = unmeasured) for `plan explain`."""

    name: str
    value: Any
    source: str
    alternatives: Mapping[Any, Optional[float]] = \
        dataclasses.field(default_factory=dict)
    reason: str = ""


def _note_override(name: str, env_name: str, value: Any) -> None:
    """Log a hand override ONCE per knob per process (the knobs are
    read per tile / per sweep — per-read events would flood the log)."""
    with _lock:
        if env_name in _overrides_logged:
            return
        _overrides_logged.add(env_name)
    try:
        from ..utils.metrics import collector
        collector.event("plan_override", decision=name, env=env_name,
                        value=value)
    except Exception:
        pass


def _env_override(name: str) -> Optional[PlanDecision]:
    """The explicitly-set env var's value, or None when unset/unparsable
    (an unparsable override falls through to the planner rather than
    crashing the read site — matching int() call sites would have
    raised before this PR, but the planner path must not add new crash
    modes)."""
    env_name = _ENV_FOR.get(name)
    if not env_name or env_name not in os.environ:
        return None
    raw = os.environ[env_name].strip()
    default = HAND_DEFAULTS[name]
    try:
        if name == "grid_fuse":
            value: Any = raw.lower() in ("1", "true", "on")
        elif isinstance(default, float):
            value = float(raw)
        else:
            value = int(raw)
    except ValueError:
        return None
    _note_override(name, env_name, value)
    return PlanDecision(name=name, value=value, source="env",
                        reason=f"{env_name} explicitly set")


def _model() -> Optional[CostModel]:
    """The cached per-(backend, corpus fingerprint) cost model; None
    when the corpus is unreadable."""
    try:
        corpus = Corpus(corpus_dir())
        backend = _backend()
        key = (backend, corpus.fingerprint())
        with _lock:
            m = _model_cache.get(key)
            if m is not None:
                return m
        m = CostModel(corpus, backend)
        with _lock:
            _model_cache.clear()  # one fingerprint is ever live
            _decision_cache.clear()
            _model_cache[key] = m
        return m
    except Exception:
        return None


def _decide(name: str, compute, cache_key: Tuple = ()) -> PlanDecision:
    """Shared resolution ladder: env override -> kill switch -> cached
    model decision -> hand default on any fault."""
    env = _env_override(name)
    if env is not None:
        return env
    default = HAND_DEFAULTS[name]
    if not plan_enabled():
        return PlanDecision(name=name, value=default, source="off",
                            reason="TMOG_PLAN=0")
    model = _model()
    if model is None:
        return PlanDecision(name=name, value=default, source="prior",
                            reason="corpus unreadable")
    key = (model.backend, name) + cache_key
    with _lock:
        hit = _decision_cache.get(key)
        if hit is not None:
            return hit
    try:
        decision = compute(model)
    except Exception as e:  # a model fault is never a fit fault
        decision = PlanDecision(name=name, value=default, source="prior",
                                reason=f"model error: {type(e).__name__}")
    with _lock:
        _decision_cache[key] = decision
    return decision


def _value_decision(name: str, family: str):
    def compute(model: CostModel) -> PlanDecision:
        value, source, alts = model.choose_value(
            name, family, HAND_DEFAULTS[name])
        return PlanDecision(name=name, value=value, source=source,
                            alternatives=alts)
    return compute


# -- shape-free knob getters (the scattered low-level consumers) -------------

def planned_tile_mb() -> int:
    """Tileplane tile size (MB) — parallel/tileplane.tile_budget_bytes."""
    return int(_decide("tile_mb",
                       _value_decision("tile_mb", "tileplane_tile")).value)


def planned_stats_tile_rows() -> int:
    """Streamed statistics tile rows — ops/stats_engine."""
    return int(_decide(
        "stats_tile_rows",
        _value_decision("stats_tile_rows", "stats_tile")).value)


def planned_score_tile_rows() -> int:
    """Bulk-scoring tile rows — readers/streaming."""
    return int(_decide(
        "score_tile_rows",
        _value_decision("score_tile_rows", "score_tile")).value)


def _compute_tile_prefetch(model: CostModel) -> PlanDecision:
    """Prefetch-ring depth: the measured knob argmin when the knob
    family carries direct A/B evidence; otherwise DERIVED from the
    measured tile-span ratios the tileplane already publishes — a feed
    side (tile_parse + tile_copy unit cost) running k x slower than the
    device step (tile_compute) needs ~ceil(k) tiles in flight before
    the consumer stops starving, clamped to the candidate range. Cold
    on both -> the depth-1 hand default (classic double buffering)."""
    import math as _math

    default = HAND_DEFAULTS["tile_prefetch"]
    value, source, alts = model.choose_value(
        "tile_prefetch", "tileplane_prefetch", default)
    if source == "measured":
        return PlanDecision(name="tile_prefetch", value=value,
                            source=source, alternatives=alts)
    ratio = model.feed_compute_ratio()
    if ratio is None:
        return PlanDecision(name="tile_prefetch", value=value,
                            source=source, alternatives=alts,
                            reason="no tile-span evidence")
    from .model import CANDIDATES
    cap = max(CANDIDATES["tile_prefetch"])
    depth = max(1, min(cap, int(_math.ceil(ratio))))
    return PlanDecision(
        name="tile_prefetch", value=depth,
        source="prior" if depth == default else "measured",
        alternatives=alts,
        reason=f"feed/compute unit-cost ratio {ratio:.2f}")


def planned_tile_prefetch() -> int:
    """Tileplane prefetch-ring depth —
    parallel/tileplane.tile_prefetch_depth."""
    return max(1, int(_decide("tile_prefetch",
                              _compute_tile_prefetch).value))


def planned_ingest_workers() -> int:
    """Sharded-ingest parse-worker pool size —
    parallel/ingest.ingest_workers. Moves off the serial hand default
    only on direct measured A/B evidence (the ingest_ab bench / a
    calibration run feeding the ingest_parse family with knob
    records)."""
    return max(1, int(_decide(
        "ingest_workers",
        _value_decision("ingest_workers", "ingest_parse")).value))


def planned_glm_bucket_floor() -> int:
    """Smallest lane bucket of the GLM retirement compaction ladder —
    ops/glm_sweep.bucket_lanes."""
    return int(_decide(
        "glm_bucket_floor",
        _value_decision("glm_bucket_floor", "glm_bucket")).value)


def _compute_out_mb(model: CostModel) -> PlanDecision:
    """Out-block cap decision: the measured argmin over KNEE-SAFE
    candidates only, so a corpus can never push the cap to a block
    size whose predicted Mosaic compile busts the budget (the 16 MB /
    20-minute r5 shape stays rejected at plan time)."""
    from .model import CANDIDATES
    safe = [c for c in CANDIDATES["grid_fuse_out_mb"]
            if compile_ok(c, model.backend)]
    if HAND_DEFAULTS["grid_fuse_out_mb"] not in safe:
        safe.append(HAND_DEFAULTS["grid_fuse_out_mb"])
    value, source, alts = model.choose_value(
        "grid_fuse_out_mb", "tree_sweep_out",
        HAND_DEFAULTS["grid_fuse_out_mb"], candidates=safe)
    return PlanDecision(name="grid_fuse_out_mb", value=value,
                        source=source, alternatives=alts,
                        reason=f"knee-safe candidates {safe}")


def _caps_decisions() -> Tuple[PlanDecision, PlanDecision]:
    return (_decide("grid_fuse_hbm_lanes",
                    _value_decision("grid_fuse_hbm_lanes",
                                    "tree_sweep_lanes")),
            _decide("grid_fuse_out_mb", _compute_out_mb))


def planned_grid_fuse_caps() -> Tuple[int, float]:
    """(HBM lane budget, out-block MB cap) for the fused-sweep chunk
    planner — ops/pallas_hist.plan_lane_chunk."""
    lanes, out_mb = _caps_decisions()
    return int(lanes.value), float(out_mb.value)


def _min_rows_decision(n_feat: int, lanes: int) -> PlanDecision:
    shape = {"feat": float(n_feat), "lanes": float(lanes)}

    def compute(model: CostModel) -> PlanDecision:
        rows, source = model.crossover_rows(
            "glm_sweep", "vmapped", "streamed", shape,
            HAND_DEFAULTS["glm_streamed_min_rows"])
        return PlanDecision(name="glm_streamed_min_rows", value=rows,
                            source=source)
    return _decide("glm_streamed_min_rows", compute,
                   cache_key=(n_feat, lanes))


def glm_streamed_min_rows(n_feat: int = 0, lanes: int = 0) -> int:
    """Row floor above which GLM sweeps take the streamed lane-batched
    route — validators._streamable's crossover."""
    return int(_min_rows_decision(n_feat, lanes).value)


def grid_fuse_enabled(n_rows: int = 0, n_feat: int = 0, n_folds: int = 0,
                      n_grids: int = 0, depth: int = 0,
                      n_bins: int = 0, n_shards: int = 1) -> bool:
    """Config-fused sweep route on/off for this sweep shape —
    validators' fused-group gate. Env TMOG_GRID_FUSE wins; otherwise
    fused turns on only when measured faster AND the planned out-block
    clears the compile knee. Cold corpus -> off (today's opt-in).
    ``n_shards`` is the mesh batch-axis size: the chunk planner's lane
    budget scales with it, so the knee must judge the sharded chunk's
    block, not the single-device one."""
    return bool(_grid_fuse_decision(n_rows, n_feat, n_folds, n_grids,
                                    depth, n_bins, n_shards).value)


def _grid_fuse_decision(n_rows: int, n_feat: int, n_folds: int,
                        n_grids: int, depth: int, n_bins: int,
                        n_shards: int) -> PlanDecision:
    shape = {"rows": float(n_rows), "feat": float(n_feat),
             "lanes": float(max(n_folds, 1) * max(n_grids, 1)),
             "depth": float(depth)}

    def compute(model: CostModel) -> PlanDecision:
        out_mb = _planned_out_block_mb(n_feat, n_bins, n_folds,
                                       n_grids, depth, n_shards)
        on, source, info = model.decide_grid_fuse(shape, out_mb)
        return PlanDecision(name="grid_fuse", value=on, source=source,
                            alternatives=info.get("alternatives", {}),
                            reason=str({k: v for k, v in info.items()
                                        if k != "alternatives"}))
    return _decide("grid_fuse", compute,
                   cache_key=(n_rows, n_feat, n_folds, n_grids,
                              depth, n_bins, n_shards))


def _planned_out_block_mb(n_feat: int, n_bins: int, n_folds: int,
                          n_grids: int, depth: int,
                          n_shards: int = 1) -> float:
    """Fused out-block MB at the chunk plan_lane_chunk would pick for
    this shape — the quantity the compile knee judges. Bins are judged
    at ``n_bins + 1``, matching the fused fit's own call (the null
    bin), and the chunk at the caller's shard count — the knee is
    exponential, so judging a smaller block than the one actually
    compiled would let a shape slip past the budget."""
    if not (n_feat and n_folds and depth):
        return HAND_DEFAULTS["grid_fuse_out_mb"]
    from ..ops import pallas_hist
    bins = max(n_bins, 1) + 1
    chunk = pallas_hist.plan_lane_chunk(
        n_feat, bins, n_folds, max(n_grids, 1), depth,
        n_shards=max(int(n_shards), 1))
    if chunk <= 0:
        return HAND_DEFAULTS["grid_fuse_out_mb"]
    plan = pallas_hist.plan_fused_hist(n_feat, bins, chunk * n_folds,
                                       depth)
    return plan.out_bytes / 1e6


# -- the Plan objects --------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FitPlan:
    """Every fit-time decision for one sweep shape, with provenance."""

    backend: str
    shape: Mapping[str, float]
    decisions: Mapping[str, PlanDecision]

    def __getattr__(self, name: str) -> Any:
        d = self.decisions.get(name)
        if d is None:
            raise AttributeError(name)
        return d.value

    def to_json(self) -> Dict[str, Any]:
        return {
            "backend": self.backend,
            "shape": dict(self.shape),
            "decisions": {
                n: {"value": d.value, "source": d.source,
                    **({"reason": d.reason} if d.reason else {}),
                    **({"alternatives": {str(k): v for k, v
                                         in d.alternatives.items()}}
                       if d.alternatives else {})}
                for n, d in self.decisions.items()},
        }


@dataclasses.dataclass(frozen=True)
class ServePlan:
    """Serving-side plan: the bucket ladder + its floor decision."""

    backend: str
    max_batch: int
    buckets: Tuple[int, ...]
    decisions: Mapping[str, PlanDecision]

    def to_json(self) -> Dict[str, Any]:
        return {"backend": self.backend, "max_batch": self.max_batch,
                "buckets": list(self.buckets),
                "decisions": {
                    n: {"value": d.value, "source": d.source}
                    for n, d in self.decisions.items()}}


def _log_plan(kind: str, doc: Dict[str, Any]) -> None:
    """Emit ONE plan_chosen event per distinct plan per process (plans
    resolve per sweep/tile — re-logging identical choices would flood
    the log without adding information)."""
    import json as _json
    sig = _json.dumps(doc, sort_keys=True, default=str)
    with _lock:
        if sig in _plans_logged:
            return
        _plans_logged.add(sig)
    try:
        from ..utils.metrics import collector
        collector.event("plan_chosen", plan=kind, **doc)
    except Exception:
        pass


def plan_fit(n_rows: int, n_feat: int, *, n_folds: int = 1,
             n_grids: int = 1, depth: int = 0,
             n_bins: int = 0, n_shards: int = 1) -> FitPlan:
    """Resolve every fit-time decision for one sweep shape. Cold corpus
    (or TMOG_PLAN=0) reproduces the hand plan bit for bit; explicitly
    set TMOG_* vars override individual decisions. ``n_shards`` is the
    mesh batch-axis size — the grid-fuse knee judges the sharded
    chunk's out-block, so a mesh caller must pass it or the reported
    plan can disagree with the gate the sweep actually used."""
    lanes = max(n_folds, 1) * max(n_grids, 1)
    backend = _backend()
    hbm_lanes_dec, out_mb_dec = _caps_decisions()
    decisions: Dict[str, PlanDecision] = {}

    decisions["glm_streamed_min_rows"] = _min_rows_decision(n_feat,
                                                            lanes)
    decisions["grid_fuse"] = _grid_fuse_decision(
        n_rows, n_feat, n_folds, n_grids, depth, n_bins, n_shards)
    decisions["grid_fuse_hbm_lanes"] = hbm_lanes_dec
    decisions["grid_fuse_out_mb"] = out_mb_dec
    decisions["tile_mb"] = _decide(
        "tile_mb", _value_decision("tile_mb", "tileplane_tile"))
    decisions["stats_tile_rows"] = _decide(
        "stats_tile_rows",
        _value_decision("stats_tile_rows", "stats_tile"))
    decisions["score_tile_rows"] = _decide(
        "score_tile_rows",
        _value_decision("score_tile_rows", "score_tile"))
    decisions["glm_bucket_floor"] = _decide(
        "glm_bucket_floor",
        _value_decision("glm_bucket_floor", "glm_bucket"))
    decisions["tile_prefetch"] = _decide("tile_prefetch",
                                         _compute_tile_prefetch)
    decisions["ingest_workers"] = _decide(
        "ingest_workers",
        _value_decision("ingest_workers", "ingest_parse"))
    shape = {"rows": float(n_rows), "feat": float(n_feat),
             "folds": float(n_folds), "grids": float(n_grids),
             "depth": float(depth), "bins": float(n_bins),
             "shards": float(max(int(n_shards), 1))}
    plan = FitPlan(backend=backend, shape=shape, decisions=decisions)
    _log_plan("fit", {"backend": backend, "shape": shape,
                      "values": {n: d.value
                                 for n, d in decisions.items()},
                      "sources": {n: d.source
                                  for n, d in decisions.items()}})
    return plan


def plan_serving(max_batch: int) -> ServePlan:
    """Resolve the serving bucket ladder for a max batch size. Cold
    corpus -> exactly serve/engine.bucket_ladder's hand ladder (floor
    8); a measured corpus may move the floor rung."""
    floor_dec = _decide(
        "serve_bucket_floor",
        _value_decision("serve_bucket_floor", "serve_bucket"))
    floor = int(floor_dec.value)
    from ..serve.engine import bucket_ladder
    buckets = bucket_ladder(max_batch, floor=floor)
    backend = _backend()
    plan = ServePlan(backend=backend, max_batch=int(max_batch),
                     buckets=buckets,
                     decisions={"serve_bucket_floor": floor_dec})
    _log_plan("serving", {"backend": backend,
                          "max_batch": int(max_batch),
                          "buckets": list(buckets),
                          "sources": {"serve_bucket_floor":
                                      floor_dec.source}})
    return plan
