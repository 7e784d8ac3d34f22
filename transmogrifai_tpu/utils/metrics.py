"""Run metrics + tracing registry.

Reference: utils/.../spark/OpSparkListener.scala:56-164 — per-stage/job/app
metrics (durations, GC, shuffle/IO bytes) collected by a Spark listener,
opt-in via OpParams.collectStageMetrics, surfaced at app end. The TPU
equivalents are per-stage wall clock + row counts + XLA compile counts.

Collection is opt-in and process-local: `enable()` (or
OpParams.collect_stage_metrics=True through the runner) turns it on; the
workflow engine reports fit/transform spans here.

Since the hierarchical-tracing PR every record is also a node of a span
TREE (utils/tracing.py): enable() opens a root span and activates the
recompile tracker; span()/trace_span() nest under it; kernel() and
sweep_convergence() attach as child spans. The flat StageMetric /
KernelRoofline / SweepConvergence lists stay exactly as before so
AppMetrics.to_json() remains byte-compatible for existing consumers — the
tree adds a "spans" key in save(), a Chrome-trace export
(save_chrome_trace) and an optional streaming event log
(attach_event_log / event).

Every span()/trace_span() ALSO writes a host annotation `tmog.<kind>:<name>`
into the jax profiler's trace, whether or not collection is on: inside a
`jax.profiler.start_trace` session the program's spans sit on the device
ops' clock (docs/observability.md "Spans on the profiler's clock"); with
no session an annotation records nothing."""
from __future__ import annotations

import collections
import contextlib
import json
import math
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, ContextManager, Dict, Iterator, List, Optional

from . import tracing
from .tracing import EventLog, TraceTree


@dataclass
class StageMetric:
    """One fit/transform span (reference StageMetrics case class).

    error/error_type: a span is recorded even when its body raises (the
    `finally` path), and before the tracing PR it silently dropped that
    fact — a failed fit read exactly like a fast one. Both fields ride
    into to_json()/the trace export; absent errors serialize as
    error=False / error_type=None, which old readers ignore."""

    stage_name: str
    uid: str
    phase: str              # 'fit' | 'transform' | 'fused-transform'
    wall_seconds: float
    n_rows: int = 0
    n_stages_fused: int = 1
    error: bool = False
    error_type: Optional[str] = None

    def to_json(self) -> Dict[str, Any]:
        return dict(self.__dict__)


def roofline_fields(wall_seconds: float, bytes_hbm: float,
                    roof_gbps: Optional[float]) -> Dict[str, Any]:
    """THE achieved-GB/s / %-of-roof arithmetic, shared by every
    consumer (collector.kernel spans in BENCH_*.json and bench.py's
    --hist-roofline micro-bench) so their numbers cannot diverge in
    rounding or clamping. 3-decimal GB/s so tiny CPU-fallback figures
    stay nonzero; roof fields None off-TPU."""
    gbps = bytes_hbm / max(wall_seconds, 1e-9) / 1e9
    return {"achieved_gbps": round(gbps, 3),
            "roof_gbps": roof_gbps,
            "pct_of_roof": (round(100.0 * gbps / roof_gbps, 2)
                            if roof_gbps else None)}


@dataclass
class KernelRoofline:
    """One timed kernel/sweep span with analytic HBM traffic attached.

    bytes_hbm comes from the kernel's own traffic model (e.g.
    ops/pallas_hist.fused_fit_bytes) — analytic by construction, since
    per-invocation byte counters cannot exist inside a jitted program.
    achieved_gbps = bytes_hbm / wall; pct_of_roof is against the device's
    published HBM bandwidth (utils/platform.DEVICE_SPECS; None off-TPU).
    cold=True marks the first run of a program: its wall includes jit
    trace + compile, so only cold=False spans are valid bandwidth
    claims."""

    kernel: str
    wall_seconds: float
    bytes_hbm: float
    achieved_gbps: float = 0.0
    roof_gbps: Optional[float] = None
    pct_of_roof: Optional[float] = None
    cold: Optional[bool] = None

    def to_json(self) -> Dict[str, Any]:
        return dict(self.__dict__)


@dataclass
class SweepConvergence:
    """Round/pass telemetry of one convergence-aware GLM sweep
    (ops/glm_sweep.py). `data_passes` counts executed streaming passes
    over X inside the fit kernels (the one-time standardization stats
    pass is excluded and noted in docs/performance.md); `lane_passes` is
    the USEFUL work — sum over rounds of active_lanes x iterations (the
    corrected FLOP model, bench.py::glm_flops_estimate, bills the
    sweep's `padded_lane_passes`: bucket_size x iterations, what the
    device actually executed). kernel: "gram" (squared-loss sufficient
    statistics, exactly one pass), "rounds" (retirement driver) or
    "mlr_rounds" (the multinomial retirement driver)."""

    family: str
    kernel: str
    rounds: int
    data_passes: int
    lane_passes: int
    lanes_total: int
    lanes_retired: int
    active_per_round: List[int] = field(default_factory=list)
    iters_per_round: List[int] = field(default_factory=list)
    bucket_sizes: List[int] = field(default_factory=list)

    def to_json(self) -> Dict[str, Any]:
        return dict(self.__dict__)


@dataclass
class StatsPass:
    """One pass of the one-pass statistics engine (ops/stats_engine.py).

    `passes` is the number of logical reads of X the driver performed
    (1 by construction — the engine exists so the SanityChecker's
    pre-model statistics stop costing 4+G passes); `tiles` the scan/tile
    count inside that read; `bytes_hbm` the analytic traffic
    (stats_pass_bytes). The wall is fenced with block_until_ready, so a
    companion kernel-roofline span named stats_pass[<driver>] carries
    the achieved-GB/s attribution next to the sweep kernels."""

    driver: str             # 'fused' | 'sharded' | 'streamed'
    rows: int
    cols: int
    tiles: int
    bytes_hbm: float
    wall_seconds: float
    passes: int = 1
    label: str = "stats"
    cold: Optional[bool] = None

    def to_json(self) -> Dict[str, Any]:
        return dict(self.__dict__)


@dataclass
class IngestPass:
    """One parallel-parse pass of the sharded ingest engine
    (parallel/ingest.py ShardedSource).

    `workers` is the parse-worker count the pass actually ran with
    (after the min(workers, shards) clamp), `parse_seconds` the SUM of
    per-worker decode time (compare against `wall_seconds` for the
    overlap factor: parse_seconds > wall_seconds means the pool decoded
    in parallel), `chunks` the columnar chunk count reassembled in shard
    order. Serial degradations (workers <= 1) are recorded too so A/B
    runs land both sides in one metrics doc."""

    label: str
    workers: int
    shards: int
    chunks: int
    rows: int
    parse_seconds: float
    wall_seconds: float

    def to_json(self) -> Dict[str, Any]:
        return dict(self.__dict__)


class LatencyHistogram:
    """Streaming-quantile latency histogram (the serving engine's p50/p95/
    p99 source, docs/serving.md).

    Fixed log-spaced buckets — `_BPD` per decade from 1µs to ~1000s — so
    recording is O(1), memory is constant regardless of request count, and
    quantiles come from the cumulative bucket counts with log-linear
    interpolation inside the winning bucket (relative error bounded by the
    bucket ratio, ~33% of a decade step at 7/decade — tight enough for
    p50-vs-p99 shape, which is what the histogram exists to show).
    Thread-safe: the serving engine records from the batcher thread and
    every HTTP worker thread concurrently."""

    _BPD = 7                     # buckets per decade
    _LO = 1e-6                   # 1µs floor
    _DECADES = 9                 # 1µs .. 1000s
    _N = _BPD * _DECADES

    def __init__(self, name: str = "latency") -> None:
        self.name = name
        self.count = 0
        self.total_seconds = 0.0
        self.max_seconds = 0.0
        self._counts = [0] * (self._N + 1)  # +1 overflow bucket
        self._lock = threading.Lock()

    def _bucket(self, seconds: float) -> int:
        if seconds <= self._LO:
            return 0
        b = int(math.log10(seconds / self._LO) * self._BPD)
        return min(b, self._N)

    #: upper bound of bucket b in seconds
    def _bound(self, b: int) -> float:
        return self._LO * 10.0 ** ((b + 1) / self._BPD)

    def record(self, seconds: float) -> None:
        s = max(float(seconds), 0.0)
        with self._lock:
            self.count += 1
            self.total_seconds += s
            if s > self.max_seconds:
                self.max_seconds = s
            self._counts[self._bucket(s)] += 1

    def quantile(self, q: float) -> float:
        """Latency (seconds) at quantile q in [0, 1]; 0.0 when empty."""
        with self._lock:
            if not self.count:
                return 0.0
            target = q * self.count
            seen = 0
            for b, c in enumerate(self._counts):
                if not c:
                    continue
                if seen + c >= target:
                    lo = self._LO * 10.0 ** (b / self._BPD) \
                        if b else 0.0
                    hi = min(self._bound(b), self.max_seconds)
                    frac = (target - seen) / c
                    return lo + (max(hi, lo) - lo) * frac
                seen += c
            return self.max_seconds

    @property
    def mean_seconds(self) -> float:
        return self.total_seconds / self.count if self.count else 0.0

    def merge(self, other: "LatencyHistogram") -> "LatencyHistogram":
        """Add `other`'s observations into this histogram, in place.

        EXACT bucket-sum semantics: the log-spaced buckets are identical
        across all instances, so merged counts equal the counts of
        recording the union stream, and every quantile of the merge
        equals the union-stream quantile bit-for-bit (quantiles read
        only bucket counts + the max, both of which merge losslessly).
        This is what makes a fleet p99 from summed per-replica buckets
        honest — no histogram re-fitting, no approximation beyond the
        bucket resolution each replica already had. Merging an empty
        histogram is the identity. Locks are taken one at a time
        (snapshot `other`, then apply), never nested."""
        with other._lock:
            counts = list(other._counts)
            count, total = other.count, other.total_seconds
            mx = other.max_seconds
        with self._lock:
            self.count += count
            self.total_seconds += total
            if mx > self.max_seconds:
                self.max_seconds = mx
            for b, c in enumerate(counts):
                if c:
                    self._counts[b] += c
        return self

    #: bucket key (the "buckets_ms" label of to_json) -> bucket index;
    #: built once — from_json must invert the exact formatting record()
    #: and to_json() use, or a merged fleet histogram would misplace mass
    _KEY_TO_BUCKET: Optional[Dict[str, int]] = None

    @classmethod
    def _key_map(cls) -> Dict[str, int]:
        if cls._KEY_TO_BUCKET is None:
            lo = cls._LO
            cls._KEY_TO_BUCKET = {
                f"{lo * 10.0 ** ((b + 1) / cls._BPD) * 1e3:.3g}": b
                for b in range(cls._N + 1)}
        return cls._KEY_TO_BUCKET

    @staticmethod
    def from_json(doc: Dict[str, Any]) -> "LatencyHistogram":
        """Rebuild a histogram from its to_json() payload (the fleet
        telemetry path: each replica serves its histograms over
        /metrics, the fleet merges the parsed copies). Bucket counts and
        the total count round-trip exactly; mean/max carry to_json()'s
        4-decimal-ms rounding, so to_json(from_json(j)) == j."""
        h = LatencyHistogram(str(doc.get("name", "latency")))
        count = int(doc.get("count", 0))
        # factory-local: `h` is unshared until returned (the same
        # happens-before-sharing argument the __init__ exemption makes)
        h.count, h.total_seconds, h.max_seconds = (  # tmoglint: disable=THR001
            count, float(doc.get("mean_ms", 0.0)) * count / 1e3,
            float(doc.get("max_ms", 0.0)) / 1e3)
        key_map = h._key_map()
        for key, c in (doc.get("buckets_ms") or {}).items():
            b = key_map.get(str(key))
            if b is None:
                raise ValueError(f"unknown latency bucket key {key!r}")
            h._counts[b] += int(c)
        return h

    def to_json(self) -> Dict[str, Any]:
        with self._lock:
            count, total = self.count, self.total_seconds
            mx = self.max_seconds
            nonzero = {f"{self._bound(b) * 1e3:.3g}": c
                       for b, c in enumerate(self._counts) if c}
        ms = 1e3
        return {"name": self.name, "count": count,
                "mean_ms": round(total / count * ms, 4) if count else 0.0,
                "p50_ms": round(self.quantile(0.50) * ms, 4),
                "p95_ms": round(self.quantile(0.95) * ms, 4),
                "p99_ms": round(self.quantile(0.99) * ms, 4),
                "max_ms": round(mx * ms, 4),
                "buckets_ms": nonzero}


class GaugeRing:
    """Fixed-length ring of gauge snapshots — the ``GET /metrics/history``
    time-series (docs/observability.md "Request tracing").

    Each sample is one flat JSON-able dict stamped with the ring's
    monotonic `t` (seconds since construction) and wall `ts` (epoch).
    The deque bound makes memory constant under a long-running serve no
    matter the cadence; dropping the oldest snapshot is the design, not
    data loss — the ring is a recent-history window, the mergeable
    aggregates (counters + latency histograms) carry the full run.
    Thread-safe: the sampler thread appends while HTTP workers read."""

    def __init__(self, maxlen: int = 720) -> None:
        self._snaps: "collections.deque[Dict[str, Any]]" = \
            collections.deque(maxlen=int(maxlen))
        self._lock = threading.Lock()
        self._mono0 = time.perf_counter()

    def append(self, **gauges: Any) -> Dict[str, Any]:
        snap: Dict[str, Any] = {
            "t": round(time.perf_counter() - self._mono0, 3),
            "ts": round(time.time(), 3)}
        snap.update(gauges)
        with self._lock:
            self._snaps.append(snap)
        return snap

    def to_json(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(s) for s in self._snaps]

    def __len__(self) -> int:
        with self._lock:
            return len(self._snaps)


@dataclass
class AppMetrics:
    """Whole-run metrics (reference AppMetrics)."""

    app_name: str = "transmogrifai_tpu"
    start_time: float = 0.0
    end_time: float = 0.0
    stage_metrics: List[StageMetric] = field(default_factory=list)
    kernel_metrics: List[KernelRoofline] = field(default_factory=list)
    sweep_metrics: List[SweepConvergence] = field(default_factory=list)
    stats_metrics: List[StatsPass] = field(default_factory=list)
    ingest_metrics: List[IngestPass] = field(default_factory=list)
    latency_metrics: Dict[str, LatencyHistogram] = field(
        default_factory=dict)

    @property
    def duration_seconds(self) -> float:
        return max(self.end_time - self.start_time, 0.0)

    def total_stage_seconds(self) -> float:
        return sum(m.wall_seconds for m in self.stage_metrics)

    def to_json(self) -> Dict[str, Any]:
        out = {"app_name": self.app_name,
               "duration_seconds": self.duration_seconds,
               "total_stage_seconds": self.total_stage_seconds(),
               "stage_metrics": [m.to_json() for m in self.stage_metrics]}
        if self.kernel_metrics:
            out["kernel_metrics"] = [m.to_json()
                                     for m in self.kernel_metrics]
        if self.sweep_metrics:
            out["sweep_metrics"] = [m.to_json()
                                    for m in self.sweep_metrics]
        if self.stats_metrics:
            out["stats_metrics"] = [m.to_json()
                                    for m in self.stats_metrics]
        if self.ingest_metrics:
            out["ingest_metrics"] = [m.to_json()
                                     for m in self.ingest_metrics]
        if self.latency_metrics:
            out["latency_metrics"] = {k: h.to_json() for k, h
                                      in self.latency_metrics.items()}
        return out

    def pretty(self) -> str:
        lines = [f"{'Stage':<42}{'Phase':<18}{'Rows':>9}{'Seconds':>10}"]
        for m in self.stage_metrics:
            lines.append(f"{m.stage_name[:41]:<42}{m.phase:<18}"
                         f"{m.n_rows:>9}{m.wall_seconds:>10.4f}")
        lines.append(f"Total: {self.total_stage_seconds():.4f}s over "
                     f"{len(self.stage_metrics)} spans")
        return "\n".join(lines)


_META_BREAKERS = str.maketrans("#,=", "___")


def _annotation(kind: str, name: str, attrs: Dict[str, Any]
                ) -> ContextManager[Any]:
    """The profiler-trace sink of one span: a host TraceMe
    `tmog.<kind>:<name>` carrying the span's scalar attrs, on the clock
    the device ops are stamped with. Never fences, never reads device
    memory; without a profiler session it records nothing (~1 us). jax
    comes from sys.modules like everywhere in utils/tracing: a host-only
    process that never imported it has no profiler to write to."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    # TraceMe packs metadata as `name#k=v,k=v#`: those three characters
    # inside a name or value would cut the rest off
    meta = {k: v.translate(_META_BREAKERS) if isinstance(v, str) else v
            for k, v in attrs.items()
            if isinstance(v, (bool, int, float, str))}
    return jax.profiler.TraceAnnotation(
        f"tmog.{kind}:{name}".translate(_META_BREAKERS), **meta)


class MetricsCollector:
    """Process-local registry (the listener's slot in this runtime)."""

    def __init__(self) -> None:
        self.enabled = False
        self.current = AppMetrics()
        self.trace = TraceTree()
        self._finished = False
        self._event_log: Optional[EventLog] = None
        # lifecycle lock (tmoglint THR001): enable/finish/attach run on
        # the driving thread while event()/latency()/span checks fire
        # from serving + tileplane threads — the state swap in enable()
        # must never interleave with a half-read (enabled, trace) pair.
        # RLock: save() -> finish() nests. Ordering: _lock may be held
        # while taking TraceTree._lock or EventLog._lock, never the
        # reverse (THR003)
        self._lock = threading.RLock()

    def enable(self, app_name: str = "transmogrifai_tpu") -> None:
        """Start (or join) a collected run. Reentrancy-safe: when a run is
        ALREADY being collected (an outer bench/BENCH_TRACE_DIR trace, a
        library user's own enable) a nested enable — e.g. runner.run with
        collect_stage_metrics inside it — must NOT reset the outer span
        tree mid-run; the nested run's spans simply join the existing
        tree. disable(), or finish() having closed the run, re-arms a
        fresh enable."""
        with self._lock:
            if self.enabled and not self._finished:
                return
            self.enabled = True
            self._finished = False
            self.current = AppMetrics(app_name=app_name,
                                      start_time=time.time())
            self.trace = TraceTree()
            tracing.tracker.activate(self.trace)
            self.trace.open(app_name, "run")

    @property
    def collecting(self) -> bool:
        """True while an UNFINISHED run is being collected — the state a
        nested enable() joins instead of resetting (callers that enable
        conditionally, like runner.run, key their cleanup on this)."""
        with self._lock:
            return self.enabled and not self._finished

    def disable(self) -> None:
        with self._lock:
            self.enabled = False
            tracing.tracker.deactivate()

    def finish(self) -> AppMetrics:
        """Close the run. Idempotent: end_time (and therefore
        duration_seconds) freezes on the FIRST call — save() and
        runner._finish both call here, and the second call used to
        silently rewrite the run's duration."""
        with self._lock:
            if not self._finished:
                self.current.end_time = time.time()
                self.trace.close_all()
                self._finished = True
            return self.current

    # -- event log ---------------------------------------------------------
    @property
    def has_event_log(self) -> bool:
        with self._lock:
            return self._event_log is not None

    def attach_event_log(self, path: str) -> EventLog:
        """Open (append) the streaming JSONL event log. Events flow
        independently of `enabled` — the log is the tail-able liveness
        channel of a long sweep even when span collection is off. The new
        log opens BEFORE the old one closes: a failed open (unwritable
        path) raises with the working log still attached."""
        new_log = EventLog(path)
        with self._lock:
            if self._event_log is not None:
                self._event_log.close()
            self._event_log = new_log
        return new_log

    def detach_event_log(self) -> None:
        with self._lock:
            log = self._event_log
            self._event_log = None
        if log is not None:
            log.close()

    def event(self, event: str, **fields: Any) -> None:
        """Emit one run event to the attached log (no-op without one).
        The reference is taken under the lock, the emit happens outside
        it: a detach racing a serve-thread event sees either the old log
        (which swallows writes after close) or none — never a torn
        state, and the file write never extends the lock hold
        (tmoglint THR002)."""
        with self._lock:
            log = self._event_log
        if log is not None:
            log.emit(event, **fields)

    # -- spans ---------------------------------------------------------------
    _EVENTED_KINDS = ("run", "workflow", "stage")

    @contextlib.contextmanager
    def trace_span(self, name: str, kind: str = "span",
                   **attrs: Any) -> Iterator[Optional[tracing.Span]]:
        """Generic span context: nests under the innermost open span,
        records error/error_type when the body raises, samples the device
        memory watermark and recompile attribution at close. Yields the
        Span (None when collection is off) so callers can add attrs.
        Collection on or off, the span is also a `tmog.<kind>:<name>`
        annotation in the profiler's trace (_annotation; entered outside
        the lock, tmoglint THR002)."""
        with self._lock:
            if not self.enabled:
                sp = trace = None
            else:
                # capture the TREE that opened the span: a concurrent
                # enable() may swap self.trace mid-span, and the close
                # must land on the tree the span belongs to
                trace = self.trace
                sp = trace.open(name, kind, **attrs)
        # a job's root span: its close ends the process's start-up ledger
        # (tracing.tracker.te), collection on or off
        job = kind in tracing.JOB_KINDS
        if job:
            tracing.tracker.job_enter()
        ok = False
        try:
            with _annotation(kind, name, attrs):
                if sp is None:
                    yield None
                else:
                    if kind in self._EVENTED_KINDS:
                        self.event("span_start", name=name, kind=kind)
                    err: Optional[str] = None
                    try:
                        yield sp
                    except BaseException as e:
                        err = type(e).__name__
                        raise
                    finally:
                        trace.close(sp, error_type=err)
                        if kind in self._EVENTED_KINDS:
                            self.event(
                                "span_end", name=name, kind=kind,
                                wall_seconds=round(sp.duration, 6),
                                error=err is not None,
                                **({"error_type": err} if err else {}))
            ok = True
        finally:
            if job:
                tracing.tracker.job_exit(ok)

    @contextlib.contextmanager
    def span(self, stage_name: str, uid: str, phase: str,
             n_rows: int = 0, n_stages_fused: int = 1) -> Iterator[None]:
        attrs = dict(uid=uid, phase=phase, n_rows=n_rows,
                     n_stages_fused=n_stages_fused)
        with self._lock:
            if not self.enabled:
                sp = trace = cur = None
            else:
                t0 = time.time()
                trace = self.trace
                cur = self.current
                sp = trace.open(stage_name, "stage", **attrs)
        with _annotation("stage", stage_name, attrs):
            if sp is None:
                yield
                return
            self.event("stage_start", stage=stage_name, uid=uid,
                       phase=phase)
            err: Optional[str] = None
            try:
                yield
            except BaseException as e:
                # the span records even when the body raises; WITHOUT the
                # error mark a failed fit reads exactly like a fast one
                err = type(e).__name__
                raise
            finally:
                trace.close(sp, error_type=err)
                wall = time.time() - t0
                cur.stage_metrics.append(StageMetric(
                    stage_name=stage_name, uid=uid, phase=phase,
                    wall_seconds=wall, n_rows=n_rows,
                    n_stages_fused=n_stages_fused,
                    error=err is not None, error_type=err))
                self.event("stage_end", stage=stage_name, uid=uid,
                           phase=phase, wall_seconds=round(wall, 6),
                           error=err is not None,
                           **({"error_type": err} if err else {}))

    def kernel(self, name: str, wall_seconds: float, bytes_hbm: float,
               cold: Optional[bool] = None,
               attrs: Optional[Dict[str, Any]] = None
               ) -> Optional[KernelRoofline]:
        """Record one kernel-roofline span (no-op unless enabled). The
        roof is resolved from the default backend's device kind at record
        time; achieved GB/s and %-of-roof are derived here so every
        consumer (bench.py, BENCH_*.json) reports the same arithmetic.
        cold=True flags a span whose wall includes jit trace/compile.
        The record also lands as a `kernel` child span of the innermost
        open span (trace export), with `attrs` merged in."""
        with self._lock:
            if not self.enabled:
                return None
            cur, trace = self.current, self.trace
        from .platform import device_spec
        spec = device_spec()  # None off-TPU; an unknown TPU kind raises
        roof = spec.hbm_bytes_per_s / 1e9 if spec else None
        rec = KernelRoofline(
            kernel=name, wall_seconds=round(wall_seconds, 4),
            bytes_hbm=float(bytes_hbm), cold=cold,
            **roofline_fields(wall_seconds, bytes_hbm, roof))
        cur.kernel_metrics.append(rec)
        trace.add_complete(
            name, "kernel", wall_seconds, bytes_hbm=rec.bytes_hbm,
            achieved_gbps=rec.achieved_gbps, roof_gbps=rec.roof_gbps,
            pct_of_roof=rec.pct_of_roof, cold=rec.cold, **(attrs or {}))
        return rec

    def sweep_convergence(self, family: str, kernel: str, rounds: int,
                          data_passes: int, lane_passes: int,
                          lanes_total: int, lanes_retired: int,
                          active_per_round=(), iters_per_round=(),
                          bucket_sizes=()) -> Optional[SweepConvergence]:
        """Record one sweep's round/pass telemetry (no-op unless enabled).
        The validator reports here after every streamed GLM sweep; bench.py
        reads the same numbers off Validator.last_streamed_telemetry for
        its executed-FLOP accounting."""
        with self._lock:
            if not self.enabled:
                return None
            cur, trace = self.current, self.trace
        rec = SweepConvergence(
            family=family, kernel=kernel, rounds=int(rounds),
            data_passes=int(data_passes), lane_passes=int(lane_passes),
            lanes_total=int(lanes_total), lanes_retired=int(lanes_retired),
            active_per_round=[int(v) for v in active_per_round],
            iters_per_round=[int(v) for v in iters_per_round],
            bucket_sizes=[int(v) for v in bucket_sizes])
        cur.sweep_metrics.append(rec)
        trace.add_complete(
            f"{family}:{kernel}", "sweep", 0.0, **rec.to_json())
        return rec

    def stats_pass(self, driver: str, rows: int, cols: int, tiles: int,
                   bytes_hbm: float, wall_seconds: float,
                   cold: Optional[bool] = None, passes: int = 1,
                   label: str = "stats") -> Optional[StatsPass]:
        """Record one statistics-engine pass (no-op unless enabled).

        Three artifacts from one call, so every consumer sees the same
        numbers: a StatsPass telemetry record (rides AppMetrics JSON as
        "stats_metrics" and attaches under the innermost open span — the
        SanityChecker fit stage when the workflow is traced), a
        kernel-roofline span named stats_pass[<driver>] (bytes/roofline
        attribution in the trace's kernel table and BENCH JSON's
        kernel_roofline list), and a `stats_pass` event on the streaming
        event log."""
        with self._lock:
            if not self.enabled:
                return None
            cur = self.current
        rec = StatsPass(driver=driver, rows=int(rows), cols=int(cols),
                        tiles=int(tiles), bytes_hbm=float(bytes_hbm),
                        wall_seconds=round(wall_seconds, 6),
                        passes=int(passes), label=label, cold=cold)
        cur.stats_metrics.append(rec)
        self.kernel(f"stats_pass[{driver}]", wall_seconds, bytes_hbm,
                    cold=cold, attrs={"rows": int(rows), "cols": int(cols),
                                      "tiles": int(tiles),
                                      "passes": int(passes),
                                      "label": label})
        self.event("stats_pass", driver=driver, rows=int(rows),
                   cols=int(cols), tiles=int(tiles), passes=int(passes),
                   bytes_hbm=float(bytes_hbm),
                   wall_seconds=round(wall_seconds, 6), label=label)
        return rec

    def ingest_pass(self, label: str, workers: int, shards: int,
                    chunks: int, rows: int, parse_seconds: float,
                    wall_seconds: float) -> Optional[IngestPass]:
        """Record one sharded-ingest parse pass (no-op unless enabled).

        Mirrors stats_pass: an IngestPass telemetry record (rides
        AppMetrics JSON as "ingest_metrics") plus an `ingest_pass` event
        on the streaming event log (docs/observability.md). The per-tile
        decode walls themselves ride as `tile_parse` spans emitted by
        the parse workers, one Perfetto lane per worker."""
        with self._lock:
            if not self.enabled:
                return None
            cur = self.current
        rec = IngestPass(label=label, workers=int(workers),
                         shards=int(shards), chunks=int(chunks),
                         rows=int(rows),
                         parse_seconds=round(parse_seconds, 6),
                         wall_seconds=round(wall_seconds, 6))
        cur.ingest_metrics.append(rec)
        self.event("ingest_pass", label=label, workers=int(workers),
                   shards=int(shards), chunks=int(chunks), rows=int(rows),
                   parse_seconds=round(parse_seconds, 6),
                   wall_seconds=round(wall_seconds, 6))
        return rec

    def latency(self, name: str, wall_seconds: float
                ) -> Optional[LatencyHistogram]:
        """Record one latency observation into the named streaming
        histogram (no-op unless enabled). The serving engine reports its
        per-request/per-phase walls here so p50/p95/p99 ride AppMetrics
        JSON under "latency_metrics" next to the kernel/sweep telemetry —
        same numbers the engine's own /metrics endpoint serves."""
        with self._lock:
            if not self.enabled:
                return None
            hist = self.current.latency_metrics.get(name)
            if hist is None:
                hist = self.current.latency_metrics.setdefault(
                    name, LatencyHistogram(name))
        hist.record(wall_seconds)  # the histogram has its own lock
        return hist

    def save(self, path: str, close: bool = True) -> None:
        """AppMetrics JSON + (new) the span tree under "spans" — every
        pre-existing key keeps its exact shape (golden-tested), the tree
        rides along for trace-report.

        close=False writes a SNAPSHOT without finishing: a run that
        JOINED an outer collection (runner.run inside a BENCH_TRACE_DIR
        trace) must not close the outer span tree mid-run — its artifact
        is the enclosing run's state so far, duration up to now."""
        with self._lock:
            # snapshot under the lifecycle lock (latency() inserts into
            # latency_metrics from serving threads mid-iteration
            # otherwise); the file write below happens OUTSIDE it
            if close:
                doc = self.finish().to_json()
            else:
                doc = self.current.to_json()
                if not self._finished:
                    doc["duration_seconds"] = max(
                        time.time() - self.current.start_time, 0.0)
            if self.trace.spans:
                doc["spans"] = self.trace.to_json()
        with open(path, "w") as f:
            json.dump(doc, f, indent=2)

    def save_chrome_trace(self, path: str, close: bool = True) -> None:
        """Chrome trace_event export of the span tree — open the file in
        Perfetto (ui.perfetto.dev) or chrome://tracing. close=False (a
        joined collection, see save) exports with still-open spans drawn
        up to now instead of closing them."""
        if close:
            self.finish()
        with self._lock:
            trace, app_name = self.trace, self.current.app_name
        tracing.write_chrome_trace(path, trace, app_name=app_name)


# the process-wide collector the workflow engine reports to
collector = MetricsCollector()
