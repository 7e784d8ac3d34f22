"""Machine-readable registry of every ``TMOG_*`` environment knob.

The knobs grew one per PR — 30+ of them by now — and the only ledger was
prose scattered over six doc files, which is exactly how the drift ENV001
found happened (code read ``TMOG_SCORE_TILE_ROWS``/``TMOG_STATS_TILE_ROWS``/
``TMOG_DISABLE_NATIVE_TREES`` that no doc named). This table is the single
source of truth the ENV001 rule (rules_env.py) checks both directions
against:

* every ``os.environ``/``env_on`` read of a ``TMOG_*`` name in the scanned
  code must have a row here;
* every row's ``doc`` file must actually mention the knob (the
  human-facing contract cannot silently drop a registered knob).

Knobs read from C++ (``std::getenv`` in ``native/*.cpp``) are outside
ENV001's AST sweep and are registered by hand — the doc-mention
direction still covers them.

Rows are pure literals — the registry is parsed by AST from fixture
copies in tests and imported directly for real scans, so it must stay
import-light (stdlib only, no package imports).

Fields: ``name`` (the env var), ``default`` (informational — what an
unset var behaves like), ``doc`` (repo-relative markdown file owning the
knob's documentation), ``desc`` (one line).
"""
from __future__ import annotations

from typing import Dict, List

KNOBS: List[Dict[str, str]] = [
    # -- compile cache / platform -------------------------------------------
    {"name": "TMOG_COMPILE_CACHE_DIR", "default": "<checkout>/.jax_cache",
     "doc": "docs/serving.md",
     "desc": "persistent XLA compilation cache directory (0/off disables; "
             "a set JAX_COMPILATION_CACHE_DIR wins)"},
    {"name": "TMOG_DISABLE_NATIVE", "default": "",
     "doc": "docs/developer-guide.md",
     "desc": "skip the native C++ kernel build, use numpy fallbacks"},
    {"name": "TMOG_DISABLE_NATIVE_TREES", "default": "",
     "doc": "docs/developer-guide.md",
     "desc": "skip only the native tree kernels (trees.cpp), keep the rest"},
    {"name": "TMOG_NO_HOST_TREES", "default": "",
     "doc": "docs/performance.md",
     "desc": "disable the host-side tree scoring path"},
    # read from C++ (std::getenv in native/trees.cpp) — ENV001's AST
    # sweep only sees Python reads, so native knobs are registered by
    # hand; the doc-mention direction still checks them
    {"name": "TMOG_TREE_HIST_BUDGET_MB", "default": "768",
     "doc": "docs/developer-guide.md",
     "desc": "native tree-kernel histogram byte budget per node group "
             "(tests shrink it to force the grouped multi-sweep path)"},
    {"name": "TMOG_NO_PALLAS", "default": "",
     "doc": "docs/performance.md",
     "desc": "force the pure-jnp twins of every pallas kernel"},
    # -- tree sweep ---------------------------------------------------------
    {"name": "TMOG_TREE_SHARD", "default": "1",
     "doc": "docs/performance.md",
     "desc": "mesh-sharded fused tree sweep route (0 = per-fold fallback)"},
    {"name": "TMOG_GRID_FUSE", "default": "0 (opt-in)",
     "doc": "docs/performance.md",
     "desc": "fold x config fused histogram route for the grid sweep"},
    {"name": "TMOG_GRID_FUSE_HBM_LANES", "default": "64",
     "doc": "docs/performance.md",
     "desc": "HBM lane budget for the fused-route chunk planner"},
    {"name": "TMOG_GRID_FUSE_OUT_MB", "default": "8",
     "doc": "docs/performance.md",
     "desc": "output-block cap for the fused-route chunk planner"},
    {"name": "TMOG_GRID_FUSE_MAX_FAILURES", "default": "3",
     "doc": "docs/performance.md",
     "desc": "fused-route failures tolerated before the sweep raises"},
    # -- statistics engine --------------------------------------------------
    {"name": "TMOG_STATS_FUSED", "default": "1",
     "doc": "docs/performance.md",
     "desc": "one-pass fused statistics engine (0 = legacy multi-pass)"},
    {"name": "TMOG_STATS_STREAM_MB", "default": "4096",
     "doc": "docs/performance.md",
     "desc": "resident-size threshold that auto-routes stats to streaming"},
    {"name": "TMOG_STATS_TILE_ROWS", "default": "262144",
     "doc": "docs/performance.md",
     "desc": "rows per streamed statistics tile (the fixed tile shape)"},
    # -- tileplane / streaming ----------------------------------------------
    {"name": "TMOG_TILEPLANE", "default": "1",
     "doc": "docs/performance.md",
     "desc": "double-buffered host->device tileplane (0 = sync loop)"},
    {"name": "TMOG_TILE_MB", "default": "32",
     "doc": "docs/performance.md",
     "desc": "host/device bytes per tileplane tile"},
    {"name": "TMOG_SCORE_TILE_ROWS", "default": "1024",
     "doc": "docs/performance.md",
     "desc": "records per bulk-scoring tile (0 = legacy per-record path)"},
    {"name": "TMOG_TILE_PREFETCH", "default": "1",
     "doc": "docs/performance.md",
     "desc": "tileplane prefetch ring depth (tiles queued ahead of compute)"},
    {"name": "TMOG_INGEST_WORKERS", "default": "1",
     "doc": "docs/performance.md",
     "desc": "parse-worker pool size for sharded columnar ingest"},
    # -- multi-host pod -----------------------------------------------------
    {"name": "TMOG_MULTIHOST", "default": "",
     "doc": "docs/performance.md",
     "desc": "master opt-in for environment-driven multi-host init and "
             "per-process ingest striping (launch_local_pod sets it)"},
    {"name": "TMOG_COORD_ADDR", "default": "",
     "doc": "docs/performance.md",
     "desc": "host:port of the jax.distributed coordinator (rank 0)"},
    {"name": "TMOG_PROC_COUNT", "default": "",
     "doc": "docs/performance.md",
     "desc": "total process count of the pod multihost.initialize joins"},
    {"name": "TMOG_PROC_ID", "default": "",
     "doc": "docs/performance.md",
     "desc": "this process's rank in the pod (0..TMOG_PROC_COUNT-1)"},
    # -- pod flight recorder ------------------------------------------------
    {"name": "TMOG_PODTRACE", "default": "",
     "doc": "docs/observability.md",
     "desc": "master opt-in for the per-rank pod flight recorder "
             "(launch_local_pod's trace_dir sets it)"},
    {"name": "TMOG_PODTRACE_DIR", "default": "",
     "doc": "docs/observability.md",
     "desc": "pod trace root; each rank writes rank-<k>/ artifacts "
             "(metrics.json, heartbeat.jsonl, events.jsonl, meta.json)"},
    {"name": "TMOG_PODTRACE_HEARTBEAT_S", "default": "0.5",
     "doc": "docs/observability.md",
     "desc": "minimum interval between heartbeat lines (phase "
             "transitions always beat)"},
    {"name": "TMOG_PODTRACE_SPAN_BUDGET", "default": "20000",
     "doc": "docs/observability.md",
     "desc": "pod_* spans recorded per rank before the recorder goes "
             "quiet (heartbeats continue)"},
    {"name": "TMOG_PODTRACE_DEBUG_SLEEP_MS", "default": "0",
     "doc": "docs/observability.md",
     "desc": "chaos hook: per-round stall injected on this rank so the "
             "ci.sh pod stage can assert straggler attribution"},
    # -- serving ------------------------------------------------------------
    {"name": "TMOG_SERVE_SPAN_BUDGET", "default": "10000",
     "doc": "docs/serving.md",
     "desc": "serve_batch spans emitted before span bookkeeping stops"},
    {"name": "TMOG_DEBUG_SLEEP_MAX_MS", "default": "0",
     "doc": "docs/observability.md",
     "desc": "cap for the X-Tmog-Debug-Sleep chaos hook (0 = disabled)"},
    # -- monitor ------------------------------------------------------------
    {"name": "TMOG_MONITOR_PROFILE", "default": "1",
     "doc": "docs/monitoring.md",
     "desc": "build the drift reference profile at model save time"},
    # -- request tracing / telemetry ----------------------------------------
    {"name": "TMOG_REQTRACE", "default": "1",
     "doc": "docs/observability.md",
     "desc": "per-request distributed tracing kill switch"},
    {"name": "TMOG_TRACE_SAMPLE", "default": "0.01",
     "doc": "docs/observability.md",
     "desc": "baseline tail-sampling probability for kept traces"},
    {"name": "TMOG_TRACE_SLO_MIN_COUNT", "default": "200",
     "doc": "docs/observability.md",
     "desc": "e2e histogram count before the slow-SLO keep activates"},
    {"name": "TMOG_REQTRACE_SPAN_BUDGET", "default": "1000",
     "doc": "docs/observability.md",
     "desc": "request-trace lane spans kept in the Chrome trace"},
    {"name": "TMOG_GAUGE_INTERVAL_S", "default": "1.0",
     "doc": "docs/observability.md",
     "desc": "gauge time-series sampling interval"},
    {"name": "TMOG_EVENTLOG_MAX_MB", "default": "256",
     "doc": "docs/observability.md",
     "desc": "events.jsonl size-rotation threshold (0/off disables)"},
    {"name": "TMOG_EVENTLOG_KEEP", "default": "3",
     "doc": "docs/observability.md",
     "desc": "rotated event-log segments kept"},
    # -- static analysis ----------------------------------------------------
    {"name": "TMOG_LINT_JOBS", "default": "min(8, cpus)",
     "doc": "docs/static_analysis.md",
     "desc": "tmoglint worker-pool width for the per-file rules "
             "(--jobs wins; pins the pool on cgroup-limited CI runners)"},
    # -- continuous retraining ----------------------------------------------
    {"name": "TMOG_RETRAIN_FAULT", "default": "",
     "doc": "docs/retraining.md",
     "desc": "fault injection for the retrain loop: fit_crash|fit_hang|"
             "bad_artifact|validation_fail|rollout_reject — tests and "
             "ci.sh prove containment at every stage"},
]


def declared_names() -> frozenset:
    return frozenset(k["name"] for k in KNOBS)
