"""ServingEngine: shape-bucketed, AOT-prewarmed scoring executables.

The batch score path compiles one XLA program per (layer, batch shape);
a server that accepts arbitrary batch sizes would compile on the request
path — exactly the cold-start the ROADMAP flags (11.6s cold vs 2.7s warm
for a 1M-row score, BENCH_TPU_R5). The fix is the same ahead-of-time
lower/compile discipline pjit training uses (PAPERS arxiv 2204.06514):

- a POWER-OF-TWO BUCKET LADDER (1, 8, 16, …, max_batch — the PR 3
  ``bucket_lanes`` idea applied to the batch axis): every request batch
  pads up to the smallest bucket that holds it, so the set of shapes the
  device ever sees is fixed and finite;
- PREWARM compiles every bucket once at startup by scoring a template
  batch through :meth:`WorkflowModel.score_fixed`. With the persistent
  compilation cache active (utils/platform.enable_compilation_cache,
  ``TMOG_COMPILE_CACHE_DIR``) the SECOND process start is all cache
  hits: ``serve --prewarm-only`` at deploy time means production
  restarts perform zero XLA compiles;
- PREALLOCATED INPUT BUFFERS per bucket: the raw-feature columns are
  allocated once and refilled in place per batch (the host-side analogue
  of the tileplane's donated carry — across the H2D boundary XLA owns
  the copy, so reuse on the host side is where allocation can actually
  be saved);
- a RECOMPILE WATCH: after warmup the engine samples the always-on
  RecompileTracker after every batch, span collection on or off; any
  compile that lands post-warmup increments ``post_warmup_compiles`` and
  emits a ``serve_recompile`` event, which ``trace-report --check``
  treats as a failure — "zero recompiles under traffic" is pinned at
  runtime, not asserted.

Observability: per-batch ``batch_assemble``/``device_score`` spans (span
emission stops after TMOG_SERVE_SPAN_BUDGET batches so the in-memory
tree stays bounded under traffic; histograms and events continue),
``serve_batch``/``serve_prewarm``/``serve_recompile`` events, and
streaming-quantile latency histograms (utils/metrics.LatencyHistogram)
that both the ``/metrics`` endpoint and bench.py --serving read.
"""
from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..data.dataset import Column, Dataset, column_from_values
from ..filters.sketches import numeric_value as _numeric_value
from ..local.scoring import record_validator, score_function
from ..local.scoring import _extract as _extract_typed
from ..types import ColumnKind
from ..utils import tracing
from ..utils.metrics import LatencyHistogram, collector
from ..workflow.io import (load_serve_manifest, manifest_stamp,
                           save_serve_manifest, verify_serve_manifest)

Record = Dict[str, Any]

_log = logging.getLogger("transmogrifai_tpu.serve")

DEFAULT_MAX_BATCH = 64
#: first ladder rung above the single-record bucket (PR 3 bucket_lanes
#: floor): buckets 2..7 would each buy <1 row of padding saved per
#: request at the cost of one more compiled program per layer
_BUCKET_FLOOR = 8

_NUMERIC_KINDS = (ColumnKind.FLOAT, ColumnKind.INT, ColumnKind.BOOL)


def bucket_ladder(max_batch: int) -> Tuple[int, ...]:
    """(1, 8, 16, …, 2^ceil(log2(max_batch))): the fixed batch shapes the
    engine compiles. The top rung rounds max_batch UP to a power of two —
    padding a full batch beats compiling an off-power shape."""
    mb = max(int(max_batch), 1)
    rungs = [1]
    if mb == 1:
        return (1,)
    b = _BUCKET_FLOOR
    while b < mb:
        rungs.append(b)
        b *= 2
    rungs.append(b)
    return tuple(rungs)


_TEMPLATE_BY_KIND = {
    ColumnKind.FLOAT: 0.0,
    ColumnKind.INT: 0,
    ColumnKind.BOOL: False,
    ColumnKind.STRING: "",
    ColumnKind.STRING_LIST: [],
    ColumnKind.FLOAT_LIST: [],
    ColumnKind.STRING_SET: [],
    ColumnKind.MAP: {},
    ColumnKind.GEO: None,
    ColumnKind.VECTOR: None,
}


def template_record(raw_features: Sequence[Any]) -> Record:
    """A syntactically-valid record for prewarm batches: one neutral value
    per predictor feature (responses are never extracted at serving
    time). Values only shape the compiled programs — the scores of a
    prewarm batch are discarded."""
    return {f.name: _TEMPLATE_BY_KIND.get(f.feature_type.column_kind)
            for f in raw_features if not f.is_response}


class ServingEngine:
    """Loads (or wraps) a fitted WorkflowModel and serves fixed-shape
    score batches through prewarmed executables.

    `model`: a WorkflowModel or a saved-model directory path.
    `buckets`/`example` default from the model dir's ``serve.json``
    prewarm manifest when present (written by ``serve --prewarm-only``),
    else from `max_batch` / :func:`template_record`.
    `single_record="local"` routes batch-of-one requests through the
    pure-Python ``local/scoring.score_function`` replay instead of the
    bucket-1 executable — for small models the host replay can undercut
    device dispatch latency (tiny/odd-shape fallback; parity between the
    two paths is test-pinned).
    """

    def __init__(self, model: Any, *, max_batch: int = DEFAULT_MAX_BATCH,
                 buckets: Optional[Sequence[int]] = None,
                 example: Optional[Record] = None,
                 single_record: str = "bucket",
                 strict_keys: bool = True,
                 monitor: Optional[Any] = None):
        if isinstance(model, str):
            from ..workflow.workflow import WorkflowModel
            model = WorkflowModel.load(model)
        self.model = model
        manifest = load_serve_manifest(getattr(model, "source_path", None))
        if buckets is None and manifest and manifest.get("buckets"):
            buckets = [int(b) for b in manifest["buckets"]]
        if example is None and manifest and \
                isinstance(manifest.get("example"), dict):
            example = manifest["example"]
        # explicit buckets, then the manifest's ladder, then the default
        self.buckets: Tuple[int, ...] = (
            tuple(sorted({int(b) for b in buckets})) if buckets
            else bucket_ladder(max_batch))
        if self.buckets[0] < 1:
            raise ValueError(f"bucket sizes must be >= 1: {self.buckets}")
        # manifest freshness (docs/fleet.md "The manifest contract"):
        # the stamp written at --prewarm-only time must still describe
        # THIS artifact, or the prewarm silently misses the persistent
        # cache. A mismatch is a warning here; `serve --strict-manifest`
        # (and every fleet replica) refuses to start on it.
        self.manifest_mismatch: List[str] = verify_serve_manifest(
            getattr(model, "source_path", None), manifest)
        if manifest and manifest.get("buckets") and \
                self.buckets != tuple(sorted({int(b) for b
                                              in manifest["buckets"]})):
            self.manifest_mismatch.append(
                f"bucket ladder {list(self.buckets)} != manifest "
                f"{manifest['buckets']} (prewarmed executables cover "
                f"different shapes)")
        if self.manifest_mismatch:
            _log.warning("serve: STALE serve.json manifest — %s. Re-run "
                         "`serve --prewarm-only` after saving the model.",
                         "; ".join(self.manifest_mismatch))
        self.max_batch = self.buckets[-1]
        if single_record not in ("bucket", "local"):
            raise ValueError("single_record must be 'bucket' or 'local'")
        self.single_record = single_record

        self.raw = model.raw_features()
        self._predictors = [(f, f.origin_stage) for f in self.raw
                            if not f.is_response]
        self._result_types = {f.name: f.feature_type
                              for f in model.result_features}
        self.example: Record = (dict(example) if example
                                else template_record(self.raw))
        #: typed 400-class validation (local/scoring.record_validator) —
        #: the batcher runs it BEFORE admission so one bad record can
        #: never poison a batch
        self.validate_record = record_validator(model,
                                                strict_keys=strict_keys)
        self._local_fn: Optional[Callable[[Record], Record]] = (
            score_function(model) if single_record == "local" else None)

        # preallocated per-bucket raw-feature columns (filled in place)
        self._buffers: Dict[int, Dict[str, Column]] = {}
        # serializes device scoring AND buffer reuse: batches from the
        # micro-batcher, bulk submit_many calls and prewarm never
        # interleave on the same buffers
        self._lock = threading.RLock()
        # leaf lock for the shared counters below: note_shed/observe_*
        # fire from every HTTP worker thread and the dispatcher, and
        # /metrics reads from yet another — a bare `+= 1` loses updates
        # under contention. Always acquired AFTER _lock, never around
        # device work (THR003: the global order is _lock -> _stat_lock)
        self._stat_lock = threading.Lock()

        self.hist: Dict[str, LatencyHistogram] = {
            "total": LatencyHistogram("serve_total"),
            "queue_wait": LatencyHistogram("serve_queue_wait"),
            "batch_assemble": LatencyHistogram("serve_batch_assemble"),
            "device_score": LatencyHistogram("serve_device_score"),
            "monitor_observe": LatencyHistogram("serve_monitor_observe"),
        }
        self.n_requests = 0
        self.n_batches = 0
        self.n_rows = 0
        #: pad accounting (the request-tracing segment decomposition,
        #: docs/observability.md): bucket_rows = device rows actually
        #: scored (incl. padding), pad_rows = the padding share — both
        #: plain sums, so the fleet merge is exact and
        #: pad_rows / bucket_rows is the fleet-wide pad fraction
        self.pad_rows = 0
        self.bucket_rows = 0
        self.n_shed = 0
        self.warm = False
        self.post_warmup_compiles = 0
        #: prewarm() summary, re-served under /metrics "prewarm": the
        #: fleet supervisor reads compiles/cache_hits off a restarted
        #: replica to assert the compile-free-rejoin contract from the
        #: RecompileTracker's counters rather than from log lines
        self.prewarm_summary: Optional[Dict[str, Any]] = None
        self._warm_compiles = 0
        self._anchor = None
        self._span_budget = int(os.environ.get("TMOG_SERVE_SPAN_BUDGET",
                                               "10000"))

        # -- drift monitor (monitor/window.ServeMonitor, docs/monitoring.md)
        # Observations run under self._lock after each scored batch: the
        # numeric sketch is an ASYNC device dispatch (nothing fetched
        # until a window rolls over), the hash/prediction paths are
        # host-side sums on the thread that assembled the batch. A
        # monitor whose profile names a feature this model lacks is
        # refused up front — comparing misaligned columns would report
        # garbage drift.
        self.monitor = monitor
        self.monitor_errors = 0
        #: set by _monitor_fault after repeated observation failures:
        #: observation stops, but the monitor object (and its counters,
        #: /metrics block and /drift report) stay visible — evidence of
        #: WHY the drift series stopped must not vanish with it
        self.monitor_disabled = False
        self._gen_by_name = {f.name: gen for f, gen in self._predictors}
        if monitor is not None:
            missing = (set(monitor.numeric_names)
                       | set(monitor.hashed_names)) - set(self._gen_by_name)
            if missing:
                _log.warning("serve: monitor profile names features this "
                             "model lacks (%s); monitoring DISABLED",
                             sorted(missing))
                self.monitor = None

    # -- buckets -----------------------------------------------------------
    def pick_bucket(self, n: int) -> int:
        """Smallest bucket >= n (n must fit the top rung)."""
        for b in self.buckets:
            if b >= n:
                return b
        raise ValueError(f"batch of {n} exceeds max bucket "
                         f"{self.max_batch}")

    # -- assembly ----------------------------------------------------------
    def _bucket_columns(self, bucket: int) -> Dict[str, Column]:
        cols = self._buffers.get(bucket)
        if cols is None:
            cols = {}
            for f in self.raw:
                kind = f.feature_type.column_kind
                if kind == ColumnKind.VECTOR:
                    continue  # rare raw vectors: built fresh per batch
                if kind in _NUMERIC_KINDS:
                    arr = np.full(bucket, np.nan, np.float64)
                else:
                    arr = np.empty(bucket, dtype=object)
                # responses stay all-missing forever (serving records are
                # unlabeled); predictors refill per batch
                cols[f.name] = Column(kind=kind, data=arr)
            self._buffers[bucket] = cols
        return cols

    def _assemble(self, records: List[Record], bucket: int) -> Dataset:
        """Raw-feature Dataset for one padded batch, written into the
        bucket's preallocated buffers. Caller holds self._lock."""
        cols = dict(self._bucket_columns(bucket))
        for f, gen in self._predictors:
            col = cols.get(f.name)
            if col is None:  # vector-kind raw feature: no reusable buffer
                cols[f.name] = column_from_values(
                    f.feature_type, [_extract_typed(gen, r)
                                     for r in records])
                continue
            data = col.data
            if col.kind in _NUMERIC_KINDS:
                for i, rec in enumerate(records):
                    data[i] = _numeric_value(_extract_typed(gen, rec))
            else:
                for i, rec in enumerate(records):
                    data[i] = _extract_typed(gen, rec)
        return Dataset(cols, n_rows=bucket)

    # -- scoring -----------------------------------------------------------
    def score_batch(self, records: Sequence[Record],
                    batch_trace: Optional[Any] = None) -> List[Record]:
        """Score records through the bucket ladder; returns one
        {result_feature: value} dict per record (same row shapes as the
        local per-record path — map-typed predictions unpack to dicts).
        Batches above the top bucket chunk into max-bucket slices.
        `batch_trace` (reqtrace.BatchTrace) receives the batch's shared
        assemble/device/monitor walls + pad accounting; chunked bulk
        accumulates across the slices."""
        records = list(records)
        if not records:
            return []
        if len(records) > self.max_batch:
            out: List[Record] = []
            for s in range(0, len(records), self.max_batch):
                out.extend(self.score_batch(records[s:s + self.max_batch],
                                            batch_trace=batch_trace))
            return out
        with self._stat_lock:
            warm = self.warm
        if len(records) == 1 and self._local_fn is not None and warm:
            t0 = time.perf_counter()
            res = self._local_fn(records[0])  # host replay: no device lock
            row = self._local_row(res)
            t1 = time.perf_counter()
            mon_s = 0.0
            with self._lock:  # counters/histograms share the lock though
                self._observe_batch(1, 1, 0.0, t1 - t0, path="local")
                if self.monitor is not None and not self.monitor_disabled:
                    self._observe_monitor_record(records[0], row)
                    mon_s = time.perf_counter() - t1
                    self._observe_monitor_wall(mon_s)
            if batch_trace is not None:
                batch_trace.add(1, 1, 0.0, t1 - t0, monitor_s=mon_s,
                                path="local")
            return [row]
        n = len(records)
        bucket = self.pick_bucket(n)
        # pad by repeating the last record: real values keep every
        # stage's numerics on the fast path (readers/streaming pads the
        # same way); pad rows are dropped after scoring
        padded = records + [records[-1]] * (bucket - n)
        with self._lock:
            t0 = time.perf_counter()
            ds = self._assemble(padded, bucket)
            t1 = time.perf_counter()
            # the batch lock EXISTS to serialize device scoring +
            # buffer reuse (docs/serving.md "Lock ownership")
            # tmoglint: disable=THR002  serialized scoring IS the design
            scored = self.model.score_fixed(ds)
            from ..readers.streaming import _row_value
            cols = [(nm, scored.column(nm), t)
                    for nm, t in self._result_types.items() if nm in scored]
            out = [{nm: _row_value(col, i, t) for nm, col, t in cols}
                   for i in range(n)]
            t2 = time.perf_counter()
            self._observe_batch(bucket, n, t1 - t0, t2 - t1)
            mon_s = 0.0
            if self.monitor is not None and not self.monitor_disabled:
                # the monitor segment measures what the REQUEST PATH
                # pays for observation — the async sketch dispatch +
                # host hash/score sums, NOT the device wall (that is
                # fetched once per window close, off this path)
                self._observe_monitor(ds, out, n, bucket)
                mon_s = time.perf_counter() - t2  # tmoglint: disable=TPU005  dispatch cost IS the measurement
                self._observe_monitor_wall(mon_s)
            self._check_recompiles()
        if batch_trace is not None:
            batch_trace.add(bucket, n, t1 - t0, t2 - t1, monitor_s=mon_s)
        return out

    def _local_row(self, res: Record) -> Record:
        # the local replay yields FeatureType values; normalize maps to
        # plain dicts like the batch unpack does
        return {k: (dict(v.value) if hasattr(v, "value")
                    and isinstance(v.value, dict) else
                    (v.value if hasattr(v, "value") else v))
                for k, v in res.items()}

    def score_record(self, record: Record) -> Record:
        (out,) = self.score_batch([record])
        return out

    # -- drift monitoring --------------------------------------------------
    def _monitor_scores(self, out_rows: Sequence[Record]):
        pred = self.monitor.profile.prediction
        if pred is None:
            return None
        from ..monitor.profile import score_of
        vals = [score_of(r, pred.feature, pred.field) for r in out_rows]
        return np.asarray([v for v in vals if v is not None], np.float64)

    def _observe_monitor(self, ds: Dataset, out_rows: List[Record],
                         n: int, bucket: int) -> None:
        """Feed one scored batch into the window sketches (caller holds
        self._lock). The numeric matrix copies out of the reusable
        buffers (np.stack-to-f32 decouples it before the next batch
        refills them); the device dispatch is async and nothing syncs
        until a window rolls over. Monitoring must never fail a request:
        errors count, log, and after 20 the monitor shuts itself off."""
        mon = self.monitor
        try:
            X = w = None
            if mon.numeric_names:
                X = np.stack([np.asarray(ds.column(nm).data, np.float32)
                              for nm in mon.numeric_names], axis=1)
                w = np.zeros(bucket, np.float32)
                w[:n] = 1.0
            hashed = {nm: ds.column(nm).data[:n]
                      for nm in mon.hashed_names if nm in ds}
            mon.observe_batch(X, w, hashed, self._monitor_scores(out_rows),
                              n)
        except Exception:
            self._monitor_fault()

    def _observe_monitor_record(self, record: Record, row: Record) -> None:
        """Single-record local route: one [1, K] dispatch through the
        bucket-1 sketch executable + the host paths (caller holds
        self._lock)."""
        mon = self.monitor
        try:
            from ..monitor.offline import observe_raw_records
            observe_raw_records(mon, [record], self._gen_by_name)
            scores = self._monitor_scores([row])
            if scores is not None:
                mon.observe_scores(scores)
        except Exception:
            self._monitor_fault()

    def _monitor_fault(self) -> None:
        """Shared observation-failure accounting (both score routes):
        count, log the first few, self-disable after 20 — monitoring
        must never keep taxing a request path it cannot serve."""
        with self._stat_lock:
            self.monitor_errors += 1
            errs = self.monitor_errors
            disable = errs >= 20 and not self.monitor_disabled
            if disable:
                self.monitor_disabled = True
        if errs <= 3:
            _log.exception("serve: drift-monitor observation failed "
                           "(%d)", errs)
        if disable:
            _log.error("serve: drift monitor disabled after %d errors",
                       errs)

    def monitor_tick(self) -> None:
        """Timer-based window rollover for idle periods (the batcher's
        dispatcher calls this between batches so a `window_seconds`
        boundary closes even with no traffic arriving)."""
        with self._stat_lock:
            disabled = self.monitor_disabled
        if self.monitor is None or disabled:
            return
        with self._lock:
            self.monitor.maybe_rollover()

    def finish_monitor(self) -> None:
        """Force-close any partial window (drain/shutdown path)."""
        if self.monitor is None:
            return
        with self._lock:
            self.monitor.maybe_rollover(force=True)

    # -- prewarm -----------------------------------------------------------
    def prewarm(self) -> Dict[str, Any]:
        """Compile (or cache-load) every bucket's executables by scoring
        one template batch per rung, smallest first. Returns a summary
        dict; afterwards the recompile watch is armed."""
        from ..utils.platform import compile_cache_dir

        with self._lock:
            if collector.enabled:
                with self._stat_lock:
                    self._anchor = collector.trace.current()
            t0 = time.perf_counter()
            compiles0 = tracing.tracker.true_compiles
            hits0 = tracing.tracker.total_cache_hits
            per_bucket: List[Dict[str, Any]] = []
            for b in self.buckets:
                tb = time.perf_counter()
                cb0 = tracing.tracker.true_compiles
                recs = [dict(self.example) for _ in range(b)]
                ds = self._assemble(recs, b)
                # prewarm compiles serially under the batch lock BY
                # DESIGN (no traffic is admitted before warm)
                # tmoglint: disable=THR002  deliberate: prewarm owns the lock
                self.model.score_fixed(ds)
                per_bucket.append({
                    "bucket": b,
                    "wall_s": round(time.perf_counter() - tb, 4),
                    "compiles": tracing.tracker.true_compiles - cb0})
            if self.monitor is not None:
                # compile the per-bucket window sketch programs now:
                # monitoring must not add a single post-warmup compile
                # (the zero-recompile contract holds with monitoring on)
                self.monitor.prewarm(self.buckets)
            wall = time.perf_counter() - t0
            with self._stat_lock:
                self.warm = True
                # the watch counts TRUE compiles: persistent-cache loads
                # are not the cold-start cost the ladder exists to
                # eliminate
                self._warm_compiles = tracing.tracker.true_compiles
                self.post_warmup_compiles = 0
            summary = {"buckets": list(self.buckets),
                       "wall_s": round(wall, 4),
                       "compiles": self._warm_compiles - compiles0,
                       "cache_hits": (tracing.tracker.total_cache_hits
                                      - hits0),
                       "compile_cache_dir": compile_cache_dir(),
                       "per_bucket": per_bucket}
            with self._stat_lock:
                self.prewarm_summary = {
                    "wall_s": summary["wall_s"],
                    "compiles": summary["compiles"],
                    "cache_hits": summary["cache_hits"]}
            collector.event("serve_prewarm", buckets=list(self.buckets),
                            wall_seconds=round(wall, 6),
                            compiles=summary["compiles"],
                            cache_hits=summary["cache_hits"])
            _log.info("serve prewarm: %d bucket(s) %s in %.2fs "
                      "(%s compiles, %s cache hits; cache %s)",
                      len(self.buckets), list(self.buckets), wall,
                      summary["compiles"], summary["cache_hits"],
                      compile_cache_dir() or "inactive")
        return summary

    def write_manifest(self) -> Optional[str]:
        """Persist the prewarm manifest (serve.json) next to the model
        artifact so the next startup prewarms the identical ladder —
        the `serve --prewarm-only` deploy-time contract."""
        src = getattr(self.model, "source_path", None)
        if not src:
            return None
        return save_serve_manifest(src, {
            "buckets": list(self.buckets),
            "max_batch": self.max_batch,
            "single_record": self.single_record,
            "example": self.example,
            # freshness stamp (docs/fleet.md): adoption re-verifies both
            **manifest_stamp(src),
        })

    # -- telemetry ---------------------------------------------------------
    # Counter discipline: every mutable counter below is touched only
    # under _stat_lock — observe_request/note_shed run on HTTP worker
    # threads, _observe_batch on the dispatcher, metrics() on whoever
    # asks. The histograms keep their own internal locks.
    def observe_queue_wait(self, seconds: float) -> None:
        self.hist["queue_wait"].record(seconds)
        collector.latency("serve_queue_wait", seconds)
        with self._stat_lock:
            in_budget = self.n_batches <= self._span_budget
            anchor = self._anchor
        if collector.enabled and in_budget:
            collector.trace.add_complete("queue_wait", "serve", seconds,
                                         parent_span=anchor)

    def observe_request(self, seconds: float, bucket: int) -> None:
        with self._stat_lock:
            self.n_requests += 1
        self.hist["total"].record(seconds)
        collector.latency("serve_total", seconds)
        collector.event("serve_request",
                        wall_ms=round(seconds * 1e3, 3), bucket=bucket)

    def note_shed(self, queue_len: int) -> None:
        with self._stat_lock:
            self.n_shed += 1
            shed_total = self.n_shed
        collector.event("serve_shed", queue_len=queue_len,
                        shed_total=shed_total)

    def _observe_monitor_wall(self, seconds: float) -> None:
        """Book one batch's monitor-observation wall (request-path cost
        of the drift sketches — the `monitor` trace segment)."""
        self.hist["monitor_observe"].record(seconds)
        collector.latency("serve_monitor_observe", seconds)

    def _observe_batch(self, bucket: int, n_valid: int,
                       assemble_s: float, score_s: float,
                       path: str = "bucket") -> None:
        with self._stat_lock:
            self.n_batches += 1
            self.n_rows += n_valid
            self.pad_rows += bucket - n_valid
            self.bucket_rows += bucket
            in_budget = self.n_batches <= self._span_budget
            anchor = self._anchor
        self.hist["batch_assemble"].record(assemble_s)
        self.hist["device_score"].record(score_s)
        collector.latency("serve_batch_assemble", assemble_s)
        collector.latency("serve_device_score", score_s)
        collector.event("serve_batch", bucket=bucket, rows=n_valid,
                        path=path, assemble_ms=round(assemble_s * 1e3, 3),
                        score_ms=round(score_s * 1e3, 3))
        if collector.enabled and in_budget:
            collector.trace.add_complete(
                "batch_assemble", "serve", assemble_s,
                parent_span=anchor, bucket=bucket, rows=n_valid)
            collector.trace.add_complete(
                "device_score", "serve", score_s,
                parent_span=anchor, bucket=bucket, rows=n_valid,
                path=path)

    def _check_recompiles(self) -> None:
        """Post-warmup compile watch: any true XLA compile after prewarm
        is counted and warned about, collection on or off (the tracker is
        always on) — the runtime pin behind the
        zero-recompiles-under-traffic claim. The `serve_recompile` event
        needs an attached event log."""
        with self._stat_lock:
            if not self.warm:
                return
            delta = tracing.tracker.true_compiles - self._warm_compiles
            new = delta - self.post_warmup_compiles
            if new > 0:
                self.post_warmup_compiles = delta
        if new > 0:
            collector.event("serve_recompile", compiles=new,
                            total_post_warmup=delta)
            _log.warning("serve: %d XLA compile(s) landed AFTER warmup "
                         "(total %d) — a request shape escaped the "
                         "bucket ladder", new, delta)

    def metrics(self) -> Dict[str, Any]:
        """Counters + latency quantiles, the /metrics payload (and the
        source bench.py --serving reads instead of re-timing)."""
        with self._stat_lock:
            out = {"warm": self.warm,
                   "buckets": list(self.buckets),
                   "max_batch": self.max_batch,
                   "single_record": self.single_record,
                   "requests": self.n_requests,
                   "batches": self.n_batches,
                   "rows": self.n_rows,
                   "pad_rows": self.pad_rows,
                   "bucket_rows": self.bucket_rows,
                   "shed": self.n_shed,
                   "post_warmup_compiles": self.post_warmup_compiles,
                   "prewarm": self.prewarm_summary,
                   "monitor_disabled": self.monitor_disabled,
                   "monitor_errors": self.monitor_errors}
        out["latency"] = {k: h.to_json() for k, h in self.hist.items()}
        disabled = out.pop("monitor_disabled")
        if self.monitor is not None:
            out["monitor"] = self.monitor.metrics()
            out["monitor"]["disabled"] = disabled
        else:
            out.pop("monitor_errors")
        return out

    def gauge_state(self) -> Dict[str, Any]:
        """One cheap gauge snapshot (counters only, no histogram
        serialization) — the GaugeSampler's per-interval read for the
        ``GET /metrics/history`` ring (docs/observability.md)."""
        with self._stat_lock:
            out: Dict[str, Any] = {
                "requests": self.n_requests,
                "rows": self.n_rows,
                "shed": self.n_shed,
                "post_warmup_compiles": self.post_warmup_compiles,
                "warm": self.warm}
        mon = self.monitor
        if mon is not None:
            out.update(mon.gauge_state())
        return out
