"""Plan-time autotuning: a measured cost model that picks routes and
tile/lane/ladder knobs per shape (docs/planning.md).

The repo's hot-path route decisions were hand-set constants or env
knobs — ``TMOG_TILE_MB``, ``TMOG_GRID_FUSE`` (+ its lane/out-block
caps), ``TMOG_STATS_TILE_ROWS``/``TMOG_SCORE_TILE_ROWS``, the
``STREAMED_SWEEP_MIN_ROWS`` GLM route floor, the power-of-two bucket
ladders — while BENCH_TPU_R5 measured ~3% GLM MFU on a 197 TFLOP/s
chip: the gap is plan quality, not kernel quality. This package builds
"A Learned Performance Model for TPUs" (arxiv 2008.01040) in
miniature:

* :mod:`corpus` — a persistent, append-only JSONL calibration corpus of
  (backend, family, shape, route, knobs) -> (wall, compile wall, bytes,
  work) records, harvested from the TraceTree span artifacts every
  traced fit/bench/ci run already exports, with dedup'd merge so
  corpora from different runs and boxes compose per backend.
* :mod:`model` — the cost model: analytic roofline priors (delegating
  to the kernels' own traffic models plus a compile-cost term fit to
  the round-5 compile-knee measurements) blended with
  nearest-shape measured observations in log-shape space. A cold
  corpus yields the pure prior, and the prior reproduces today's hand
  defaults — a cold planner is a no-op, not a regression.
* :mod:`plan` — ``plan_fit(...) -> FitPlan`` / ``plan_serving(...) ->
  ServePlan``: ONE choke point for every per-shape route decision.
  Call sites in validators/trees/tileplane/glm_sweep/serve consume the
  plan; an explicitly-set ``TMOG_*`` env var always overrides the
  planner (hand wins, logged as a ``plan_override`` event).
  ``TMOG_PLAN=0`` is the kill switch; ``TMOG_PLAN_CORPUS_DIR`` points
  at the corpus.
* :mod:`calibrate` — ``python -m transmogrifai_tpu plan
  calibrate|show|explain``: a bounded micro-bench grid that seeds a
  cold corpus on the current backend in minutes, and an explainer that
  prints each decision with predicted-vs-alternative costs.
"""
from .corpus import Corpus, PlanRecord, harvest_metrics_doc
from .model import (COMPILE_BUDGET_S, HAND_DEFAULTS, CostModel,
                    compile_knee_s, compile_ok)
from .plan import (FitPlan, PlanDecision, ServePlan, corpus_dir,
                   plan_enabled, plan_fit, plan_serving)

__all__ = [
    "COMPILE_BUDGET_S", "Corpus", "CostModel", "FitPlan", "HAND_DEFAULTS",
    "PlanDecision", "PlanRecord", "ServePlan", "compile_knee_s",
    "compile_ok", "corpus_dir", "harvest_metrics_doc", "plan_enabled",
    "plan_fit", "plan_serving",
]
