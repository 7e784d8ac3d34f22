"""Hierarchical run tracing: span tree, recompile/HBM attribution, exports.

Reference: the OpSparkListener gave every run a per-stage/job/app metrics
story surfaced through the Spark UI and its event log. The TPU equivalent
here is a process-local span TREE (run -> workflow -> layer -> stage ->
kernel / sweep-round) with the two costs that dominate JAX/TPU runs
attributed per span:

- **XLA recompiles** — the process's ONE `jax.monitoring` listener
  (`tracker`, always on) keeps every trace, lowering, cache load and
  compile by program: the start-up ledger behind
  `utils/platform.startup_record()` and the counter the serving engine's
  zero-recompile watch reads. While a tree is active it also books each
  compile to the innermost open span, making claims like PR 3's "bounded
  recompiles on the bucket ladder" runtime-verifiable from any traced
  run;
- **device-memory watermarks** — `Device.memory_stats()` sampled at span
  close (None-safe: CPU hosts report nothing and the attrs are omitted).

Three consumers, one tree:

- Chrome `trace_event` JSON (`chrome_trace`/`write_chrome_trace`) loadable
  in Perfetto / chrome://tracing;
- the existing AppMetrics JSON (`utils/metrics.MetricsCollector.save`
  appends the span list under a new "spans" key, everything else
  byte-compatible);
- a streaming JSONL event log (`EventLog`) of timestamped run events, so a
  preempted multi-hour sweep is monitorable by tailing ONE file.

`trace_report(dir)` renders top-spans-by-self-time, per-program recompile
counts and the kernel roofline table; `trace_report(dir, check=True)` is
the schema validator CI runs (`python -m transmogrifai_tpu trace-report
<dir> --check`).

This module is import-light on purpose: jax is only touched lazily (and
only when it is already imported) so attaching tracing to a host-only run
never initializes a backend.
"""
from __future__ import annotations

import glob as _glob
import json
import os
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "Span", "TraceTree", "RecompileTracker", "tracker", "EventLog",
    "device_memory_attrs", "chrome_trace",
    "write_chrome_trace", "trace_report", "trace_report_rc",
    "event_log_paths", "iter_events", "requests_report",
    "requests_report_rc", "fmt_table",
]

# the monitoring events of one program's way to the device. jax 0.9.0
# reports each as a duration, on the thread that did the work, the moment
# it ends, with `fun_name`: tracing (`f`), lowering to MLIR, Mosaic bodies
# included (`jit(f)`), and the backend compile (`jit(f)`).
# NOTE (measured on this image's jaxlib): a persistent-compilation-cache
# HIT emits the compile event too — but a hit is PRECEDED by the
# cache-retrieval event below, so the tracker classifies the pair and keeps
# a separate total_cache_hits counter (total_compiles keeps counting both;
# true compiles = total_compiles - total_cache_hits, what the serving
# engine's post-warmup recompile watch reads)
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

#: span kinds whose outermost close is a finished job: the first one to
#: close without an error ends the process's start-up (Te)
JOB_KINDS = ("validate", "workflow")

#: program events the ledger keeps with their intervals; past it only the
#: counters run (a process that recompiles without end must not grow)
_MAX_EVENTS = 1 << 16


@dataclass
class Span:
    """One node of the run's span tree.

    t_start/t_end are seconds on the owning TraceTree's monotonic clock
    (perf_counter anchored at tree construction) — wall-time arithmetic
    between spans is exact regardless of system clock steps."""

    span_id: int
    parent_id: Optional[int]
    name: str
    kind: str               # run|workflow|layer|stage|kernel|sweep|
                            # sweep_round|tile|pod_round|pod_compute|
                            # pod_collective|pod_ingest (pod_* families:
                            # parallel/podtrace.py span glossary)
    t_start: float
    t_end: Optional[float] = None
    attrs: Dict[str, Any] = field(default_factory=dict)
    error: bool = False
    error_type: Optional[str] = None

    @property
    def duration(self) -> float:
        end = self.t_end if self.t_end is not None else self.t_start
        return max(end - self.t_start, 0.0)

    def to_json(self) -> Dict[str, Any]:
        out = {"span_id": self.span_id, "parent_id": self.parent_id,
               "name": self.name, "kind": self.kind,
               "t_start": round(self.t_start, 6),
               "t_end": round(self.t_end, 6)
               if self.t_end is not None else None,
               "duration_seconds": round(self.duration, 6),
               "error": self.error}
        if self.error_type:
            out["error_type"] = self.error_type
        if self.attrs:
            out["attrs"] = _jsonable(self.attrs)
        return out


def _jsonable_value(v: Any) -> Any:
    if isinstance(v, (str, int, float, bool, type(None))):
        return v
    if isinstance(v, dict):
        # nested payloads (e.g. a request_trace's per-segment dict) keep
        # their structure instead of stringifying — events.jsonl lines
        # must stay machine-parseable JSON all the way down
        return {str(k): _jsonable_value(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable_value(x) for x in v]
    return str(v)


def _jsonable(d: Dict[str, Any]) -> Dict[str, Any]:
    return {k: _jsonable_value(v) for k, v in d.items()}


class TraceTree:
    """Span registry + open-span stack for one traced run (one enable()).

    Thread note: the tree is driven from the host thread that dispatches
    the run; the lock only exists so the jax.monitoring compile listener
    (which fires synchronously inside compile calls, possibly from helper
    threads in future jax versions) can attribute safely."""

    def __init__(self) -> None:
        self._clock0 = time.perf_counter()
        self._wall0 = time.time()
        self.spans: List[Span] = []
        self._stack: List[Span] = []
        self._next_id = 1
        self._lock = threading.RLock()
        # parent span_id -> children, so subtree walks (self-time) stay
        # O(subtree), not O(all spans)
        self._children: Dict[int, List[Span]] = {}

    # -- clock -------------------------------------------------------------
    def now(self) -> float:
        return time.perf_counter() - self._clock0

    # -- structure ---------------------------------------------------------
    def current(self) -> Optional[Span]:
        with self._lock:
            return self._stack[-1] if self._stack else None

    def open(self, name: str, kind: str, **attrs: Any) -> Span:
        with self._lock:
            parent = self._stack[-1].span_id if self._stack else None
            sp = Span(span_id=self._next_id, parent_id=parent, name=name,
                      kind=kind, t_start=self.now(), attrs=dict(attrs))
            self._next_id += 1
            self.spans.append(sp)
            if parent is not None:
                self._children.setdefault(parent, []).append(sp)
            self._stack.append(sp)
        return sp

    def children_of(self, span_id: int) -> List[Span]:
        with self._lock:
            return list(self._children.get(span_id, ()))

    def close(self, sp: Span, error_type: Optional[str] = None) -> None:
        with self._lock:
            # a double close (e.g. close_all() from save() racing the
            # still-open context manager's exit) must be a no-op: the
            # first close fixed t_end, and rewriting it would inflate the
            # span past its already-closed parent's window
            already_closed = sp.t_end is not None
            if not already_closed:
                sp.t_end = self.now()
            if error_type:
                sp.error = True
                sp.error_type = error_type
            # pop up to and including sp — tolerates children left open by
            # an exception unwinding through several context managers. A
            # close of a span no longer on the stack must not drain it.
            if any(top is sp for top in self._stack):
                while self._stack:
                    top = self._stack.pop()
                    if top is sp:
                        break
                    if top.t_end is None:
                        top.t_end = sp.t_end
        if already_closed:
            return
        mem = device_memory_attrs()
        if mem:
            sp.attrs.update(mem)

    def add_complete(self, name: str, kind: str, duration: float,
                     parent_span: Optional["Span"] = None,
                     **attrs: Any) -> Span:
        """Record an already-measured child span (e.g. a kernel wall that
        was timed by its own block_until_ready window): t_end = now,
        t_start = now - duration, parented to the innermost open span —
        or to `parent_span` when given (a producer THREAD records its
        tile spans under the span that was current when its pass began;
        parenting to the consumer thread's transient stage spans would
        violate the children-inside-parent-window invariant)."""
        with self._lock:
            if parent_span is not None:
                parent = parent_span.span_id
            else:
                parent = self._stack[-1].span_id if self._stack else None
            end = self.now()
            sp = Span(span_id=self._next_id, parent_id=parent, name=name,
                      kind=kind, t_start=max(end - max(duration, 0.0), 0.0),
                      t_end=end, attrs=dict(attrs))
            self._next_id += 1
            self.spans.append(sp)
            if parent is not None:
                self._children.setdefault(parent, []).append(sp)
        return sp

    def add_window(self, name: str, kind: str, t_start: float,
                   t_end: float, parent_span: Optional["Span"] = None,
                   **attrs: Any) -> Span:
        """Record an already-measured span at an EXPLICIT window on the
        tree clock (both ends in tree-clock seconds, i.e. values from
        :meth:`now`). The request-trace exporter uses this to lay a kept
        request's segment chain end-to-end inside its request window —
        add_complete's end-is-now anchoring would stack every segment at
        the same instant."""
        with self._lock:
            parent = (parent_span.span_id if parent_span is not None
                      else (self._stack[-1].span_id if self._stack
                            else None))
            t0 = max(float(t_start), 0.0)
            t1 = max(float(t_end), t0)
            sp = Span(span_id=self._next_id, parent_id=parent, name=name,
                      kind=kind, t_start=t0, t_end=t1, attrs=dict(attrs))
            self._next_id += 1
            self.spans.append(sp)
            if parent is not None:
                self._children.setdefault(parent, []).append(sp)
        return sp

    def close_all(self) -> None:
        # pop-then-close WITHOUT holding the tree lock across close():
        # close() re-enters the lock itself and samples device memory
        # outside it
        while True:
            with self._lock:
                if not self._stack:
                    return
                sp = self._stack[-1]
            self.close(sp)

    # -- derived -----------------------------------------------------------
    def self_seconds(self, sp: Span) -> float:
        child = sum(s.duration for s in self.children_of(sp.span_id))
        return max(sp.duration - child, 0.0)

    def to_json(self) -> List[Dict[str, Any]]:
        return [s.to_json() for s in self.spans]


# -- the start-up ledger and recompile attribution -----------------------------

_EVENT_KINDS = {_TRACE_EVENT: "trace", _LOWER_EVENT: "lower",
                _COMPILE_EVENT: "compile", _CACHE_HIT_EVENT: "hit"}


def _program(fun_name: Any) -> str:
    """One name a program: jax reports tracing under `f` and lowering and
    the backend compile under `jit(f)` (`pmap(f)`)."""
    name = str(fun_name)
    if name.endswith(")") and "(" in name:
        name = name[name.index("(") + 1:-1]
    return name


def union_seconds(intervals: List[Tuple[float, float]]) -> float:
    """Total length of the union of [start, end] intervals: a trace nested
    in a trace, a compile on a helper thread beside another, a tile span
    inside a pod_compute bracket, is counted once."""
    total, hi = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > hi:
            total += max(e - max(s, hi), 0.0)
            hi = e
    return total


def _process_start() -> Optional[float]:
    """The process's own start on the time.time() clock, from
    /proc/self/stat (field 22, ticks since boot) and /proc/uptime; None
    where they cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        return time.time() - age
    except (OSError, ValueError, IndexError):
        return None


def _program_rows(events: List[Tuple[str, float, float, str]]
                  ) -> List[Dict[str, Any]]:
    """Per-program seconds of the events, slowest first. A row holds the
    program's OWN events: a trace nested in another program's trace is in
    both rows (the totals of the record count it once)."""
    rows: Dict[str, Dict[str, Any]] = {}
    for kind, s, e, name in events:
        row = rows.get(name)
        if row is None:
            row = rows[name] = {
                "fun_name": name, "trace_s": 0.0, "lower_s": 0.0,
                "load_s": 0.0, "compile_s": 0.0, "loads": 0, "compiles": 0}
        if kind == "cache_load":
            row["load_s"] += e - s
            row["loads"] += 1
        else:
            row[kind + "_s"] += e - s
            if kind == "compile":
                row["compiles"] += 1
    for row in rows.values():
        row["cache_hit"] = row["loads"] > 0 and row["compiles"] == 0
    return sorted(rows.values(), key=lambda r: -(
        r["trace_s"] + r["lower_s"] + r["load_s"] + r["compile_s"]))


class RecompileTracker:
    """The process's start-up ledger and its one compile counter.

    A `jax.monitoring` duration listener, registered once when the package
    is imported and ALWAYS on (there is no switch: none of its events
    fires in a warm steady state, so the hot path pays nothing). Every
    trace, lowering and backend compile is kept by program name with its
    interval on `time.time()` (the clock jax stamps them with), the
    compile marked a persistent-cache LOAD or a true compile by the
    retrieval event that precedes a load on the same thread. With
    `t0`/`t1` (first and last statement of the package's `__init__`) and
    `te` (the close of the first root job span) that is the whole of
    `startup_record()`. `true_compiles` / `total_cache_hits` run for the
    process's life; callers take differences.

    jax emits no event for its backend's start, so `install()` also puts a
    one-shot timer around each registered backend factory
    (`_watch_backends`): the instant the backend came up, and the process's
    CPU seconds up to it, split `startup_reach_device_s` in two.

    While a collected run's tree is active (`collector.enable()`), each
    compile is ALSO booked to the innermost open span (`compiles`,
    `compile_seconds`, `cache_hits` attrs) — a view, not the record.
    jax is only consulted when something else already imported it (the
    module contract): in a process without it nothing compiles and there
    is nothing to count."""

    def __init__(self) -> None:
        self._tree: Optional[TraceTree] = None
        self._listener_installed = False
        self.total_compiles = 0
        self.total_cache_hits = 0
        # a retrieval event and ITS compile event fire back-to-back on
        # the SAME thread, so the pairing flag is thread-local: compiles
        # interleaving from helper threads cannot steal another thread's
        # pending hit and misclassify a true compile as a cache load
        self._pending = threading.local()
        # nesting of JOB_KINDS spans, a thread: te is the OUTERMOST close
        self._jobs = threading.local()
        self.t0 = self.t1 = time.time()   # mark_import() sets the real ones
        self.te: Optional[float] = None
        self.t1_cpu = time.process_time()   # the process's CPU at t1
        # platform.prefetch_kernel_modules' thread: start, end, and the
        # thread's own CPU seconds (time.thread_time) between them
        self.kernel_import: Optional[Tuple[float, float, float]] = None
        # was a backend up when install() looked: None where it could not
        # look (no jax, or a jax without the names _watch_backends reads)
        self.backend_up_at_install: Optional[bool] = None
        # each platform's initialisation, in the order jax ran them:
        # (platform, begin, end, process CPU at end, came up)
        self.backend_inits: List[Tuple[str, float, float, float, bool]] = []
        # the factories still timed: platform -> (registration, its own
        # factory, the timer in its place)
        self._timed_factories: Dict[str, Tuple[Any, Any, Any]] = {}
        self._events: List[Tuple[str, float, float, str]] = []
        self.events_dropped = 0
        self.listener_seconds = 0.0
        # guards the counters, the ledger and the activation state
        # (tmoglint THR001): the jax.monitoring listener fires on whatever
        # thread compiles — a serving dispatcher and a prewarm can land
        # compiles concurrently, and `total_compiles += 1` unlocked loses
        # updates exactly where the zero-recompile contract reads them.
        # Ordering: _lock may be held while taking the tree's lock,
        # never the reverse
        self._lock = threading.RLock()

    @property
    def true_compiles(self) -> int:
        """Compiles that actually ran XLA (persistent-cache loads
        excluded) — the serving engine's zero-recompile contract counts
        THESE; a prewarmed restart is all cache hits and reads 0."""
        with self._lock:
            return max(self.total_compiles - self.total_cache_hits, 0)

    # -- lifecycle ---------------------------------------------------------
    def install(self) -> None:
        """Register the listener and watch for the backend (once), if jax
        is imported."""
        with self._lock:
            if self._listener_installed or sys.modules.get("jax") is None:
                return
            import jax.monitoring
            jax.monitoring.register_event_duration_secs_listener(
                self._on_event)
            self._listener_installed = True
        # outside the lock: jax's backend lock is taken in there, and a
        # timed factory takes them the other way round
        self._watch_backends()

    def mark_import(self, t0: float, t1: float) -> None:
        """The package's own import, [first, last] statement of its
        `__init__` on time.time(); called AS that last statement, so the
        process's CPU clock is read here for t1."""
        with self._lock:
            self.t0, self.t1 = float(t0), float(t1)
            self.t1_cpu = time.process_time()

    def mark_kernel_import(self, start: float, end: float,
                           cpu_s: float) -> None:
        """The interval of platform.prefetch_kernel_modules' thread and
        the CPU seconds the thread itself spent in it."""
        with self._lock:
            self.kernel_import = (float(start), float(end), float(cpu_s))

    # -- the instant the backend came up --------------------------------------
    def _watch_backends(self) -> None:
        """Put a one-shot timer in place of every backend factory jax has
        registered (`jax._src.xla_bridge._backend_factories`, filled at
        that module's import), unless a backend is up already.

        `xla_bridge.backends()` calls each factory of the platforms it
        wants, once, under its lock; a timer hands the registration its
        own factory back BEFORE running it, notes [begin, end] on
        time.time() and the process's CPU clock at the end, and is gone.
        Nothing initialises a backend here, nothing runs or polls, and a
        factory jax never calls (the TPU's in a process forced to the CPU)
        keeps an idle timer until the first finished job takes the rest
        off (`job_exit`) — in a process that reaches neither, one closure
        a registered platform, never called. The names are private to jax
        0.9.0: where they are missing or cannot be set, nothing is timed,
        `backend_up_at_install` stays None and the record's fields read
        None."""
        t_in = time.perf_counter()
        timed: Dict[str, Tuple[Any, Any, Any]] = {}
        try:
            from jax._src import xla_bridge
            up: Optional[bool] = bool(xla_bridge.backends_are_initialized())
            if not up:
                for platform, reg in list(
                        xla_bridge._backend_factories.items()):
                    factory = reg.factory
                    timer = self._timed_factory(platform, reg, factory)
                    reg.factory = timer
                    timed[platform] = (reg, factory, timer)
        except (ImportError, AttributeError, TypeError):
            up = None
        with self._lock:
            self.backend_up_at_install = up
            self._timed_factories.update(timed)
            self.listener_seconds += time.perf_counter() - t_in
        if up is None:
            self._unwatch_backends()

    def _timed_factory(self, platform: str, registration: Any,
                       factory: Any) -> Any:
        def timer():
            registration.factory = factory
            begin, ok = time.time(), False
            try:
                backend = factory()
                ok = backend is not None
                return backend
            finally:
                end, cpu = time.time(), time.process_time()
                with self._lock:
                    self._timed_factories.pop(platform, None)
                    self.backend_inits.append((platform, begin, end, cpu, ok))
        return timer

    def _unwatch_backends(self) -> None:
        """Hand every factory still timed back to its registration."""
        with self._lock:
            timed, self._timed_factories = self._timed_factories, {}
        for registration, factory, timer in timed.values():
            if registration.factory is timer:
                registration.factory = factory

    def activate(self, tree: TraceTree) -> None:
        """Book compiles to `tree`'s innermost open span from now on. The
        counts are the process's: nothing is reset."""
        with self._lock:
            self._tree = tree
            self.install()

    def deactivate(self) -> None:
        with self._lock:
            self._tree = None

    # -- the first root job: where start-up ends ---------------------------
    def job_enter(self) -> None:
        self._jobs.depth = getattr(self._jobs, "depth", 0) + 1

    def job_exit(self, ok: bool) -> None:
        """Close of a JOB_KINDS span; the first OUTERMOST one that did not
        raise is the process's first result."""
        # a span finalised on another thread than it was entered on (an
        # abandoned generator, an executor hand-off) finds no depth there:
        # it must not raise inside a finally and mask the job's own error
        depth = self._jobs.depth = max(
            getattr(self._jobs, "depth", 1) - 1, 0)
        if ok and depth == 0:
            with self._lock:
                first = self.te is None
                if first:
                    self.te = time.time()
            if first:
                self._unwatch_backends()

    # -- the listener --------------------------------------------------------
    def _on_event(self, event: str, duration: float, **kw: Any) -> None:
        kind = _EVENT_KINDS.get(event)
        if kind is None:
            return
        t_in = time.perf_counter()
        end = time.time()   # the event fires as the work ends
        with self._lock:
            try:
                if kind == "hit":
                    # a persistent-cache retrieval fires immediately
                    # BEFORE its compile event (measured order, same
                    # thread); mark the pair so THIS thread's next compile
                    # books as a cache LOAD, not a true XLA compile
                    self._pending.cache_hit = True
                    return
                if kind == "compile":
                    hit = getattr(self._pending, "cache_hit", False)
                    self._pending.cache_hit = False
                    self.total_compiles += 1
                    if hit:
                        self.total_cache_hits += 1
                        kind = "cache_load"
                    if self._tree is not None:
                        self._book_to_span(self._tree, float(duration), hit)
                if len(self._events) < _MAX_EVENTS:
                    self._events.append((kind, end - float(duration), end,
                                         _program(kw.get("fun_name", "?"))))
                else:
                    self.events_dropped += 1
            finally:
                # every path, the retrieval's too: the listener's own cost
                self.listener_seconds += time.perf_counter() - t_in

    @staticmethod
    def _book_to_span(tree: TraceTree, duration: float, hit: bool) -> None:
        # the whole read-modify-write under BOTH locks (tracker then
        # tree — the documented order): the listener may fire from
        # helper threads, and an unlocked attrs update would race
        # close()'s watermark update
        with tree._lock:
            sp = tree.current()
            if sp is None:
                return
            sp.attrs["compiles"] = int(sp.attrs.get("compiles", 0)) + 1
            sp.attrs["compile_seconds"] = round(
                float(sp.attrs.get("compile_seconds", 0.0)) + duration, 4)
            if hit:
                sp.attrs["cache_hits"] = \
                    int(sp.attrs.get("cache_hits", 0)) + 1

    # -- the record ----------------------------------------------------------
    def startup_record(self) -> Dict[str, Any]:
        """Where the process's start-up went, t0 to its first result (to
        NOW while no root job has closed: `complete` False).

        Six seconds that add up to `first_contact_s` by construction:
        `startup_import_s` (t1 - t0), `startup_reach_device_s` (t1 to the
        first program event: backend initialisation and whatever the
        caller did before its first jitted call), then every instant from
        there on booked ONCE — to `startup_compile_s` if a true backend
        compile was running, else `startup_cache_load_s` if a cache load
        was, else `startup_trace_lower_s` if a trace or a lowering was,
        else `startup_run_s` (data made on the device, the first job's
        execution). `startup_programs` counts the programs loaded or
        compiled, eager one-op programs included. `programs` are the rows
        up to the first result, `later_programs` what traced, loaded or
        compiled after it (a warm server: the recompile's name).

        `startup_reach_device_s` in two (ledger_version 2), by `t_up`, the
        end of the last backend factory that came up (`_watch_backends`):
        `startup_backend_up_s` (t1 to t_up, held inside the interval) and
        `startup_first_dispatch_s` (the rest: the host work between the
        backend and the first program event, the caller's and the
        program's), which add up to it by construction; and
        `startup_backend_up_cpu_s`, the process's CPU seconds of all
        threads inside [t1, t_up] — near the wall figure the interval was
        host compute, far under it a wait on the driver and the chip. A
        backend that was up before the package was imported reads 0, all
        to the first dispatch, with `backend_up_before_import` True; while
        none is up, or where jax hides its factories, the three are None.
        `backend_inits` has each platform's initialisation (`begin_s` from
        t1, `init_s`, `ok`).

        Beside the six, `kernel_import_s`: how long the thread that imports
        jax's Pallas modules took (platform.prefetch_kernel_modules; None
        where none ran or it has not ended), and `kernel_import_cpu_s`, the
        thread's own CPU seconds of it: far under the wall, it was held. It
        starts at t1 and runs beside the main thread, under
        `startup_reach_device_s` where the backend takes longer to come up
        than the import."""
        now = time.time()
        with self._lock:
            t0, t1, te = self.t0, self.t1, self.te
            t1_cpu, up_at_install = self.t1_cpu, self.backend_up_at_install
            inits = list(self.backend_inits)
            kernel_import = self.kernel_import
            events = list(self._events)
            out: Dict[str, Any] = {
                "true_compiles": self.true_compiles,
                "cache_hits": self.total_cache_hits,
                "events_dropped": self.events_dropped,
                "listener_s": self.listener_seconds}
        end = max(te if te is not None else now, t1)
        early = [ev for ev in events if ev[1] < end]
        clipped = [(k, max(s, t1), min(e, end)) for k, s, e, _ in early
                   if e > t1]
        first = min((s for _, s, _ in clipped), default=end)
        compile_s = union_seconds([(s, e) for k, s, e in clipped
                            if k == "compile"])
        load_s = union_seconds([(s, e) for k, s, e in clipped
                         if k in ("compile", "cache_load")]) - compile_s
        busy = union_seconds([(s, e) for _, s, e in clipped])
        start = _process_start()
        reach = first - t1
        came_up = [row for row in inits if row[4]]
        if came_up:
            _, _, t_up, up_cpu, _ = max(came_up, key=lambda row: row[2])
            before_import = t_up <= t1
            backend_up_s = min(max(t_up - t1, 0.0), reach)
            backend_up_cpu_s = max(up_cpu - t1_cpu, 0.0)
        elif up_at_install:
            before_import, backend_up_s, backend_up_cpu_s = True, 0.0, 0.0
        else:
            before_import = up_at_install
            backend_up_s = backend_up_cpu_s = None
        out.update({
            "ledger_version": 2,
            "backend_up_before_import": before_import,
            "startup_backend_up_s": backend_up_s,
            "startup_first_dispatch_s": None if backend_up_s is None
            else reach - backend_up_s,
            "startup_backend_up_cpu_s": backend_up_cpu_s,
            "backend_inits": [
                {"platform": platform, "begin_s": begin - t1,
                 "init_s": end_ - begin, "ok": ok}
                for platform, begin, end_, _, ok in inits],
            "kernel_import_cpu_s": None if kernel_import is None
            else kernel_import[2],
            "complete": te is not None,
            "before_import_s": None if start is None else t0 - start,
            "first_contact_s": end - t0,
            "kernel_import_s": None if kernel_import is None
            else kernel_import[1] - kernel_import[0],
            "startup_import_s": t1 - t0,
            "startup_reach_device_s": first - t1,
            "startup_trace_lower_s": busy - compile_s - load_s,
            "startup_cache_load_s": load_s,
            "startup_compile_s": compile_s,
            "startup_run_s": (end - first) - busy,
            "startup_programs": sum(k in ("compile", "cache_load")
                                    for k, _, _, _ in early),
            "programs": _program_rows(early),
            "later_programs": _program_rows(
                [ev for ev in events if ev[1] >= end])})
        return out


#: THE process-wide tracker: the package's __init__ installs it, the
#: collector activates its span view per enable()
tracker = RecompileTracker()


# -- device-memory watermark -------------------------------------------------

def device_memory_attrs() -> Dict[str, Any]:
    """HBM watermark attrs for the current local devices, or {} when jax is
    not imported / the backend reports nothing (CPU memory_stats() is
    None — the ISSUE's None-safety contract). Never initializes a backend:
    only consults jax when something else already imported it."""
    jmod = sys.modules.get("jax")
    if jmod is None:
        return {}
    try:
        stats = [d.memory_stats() for d in jmod.local_devices()]
    except Exception:
        return {}
    in_use = [s.get("bytes_in_use") for s in stats
              if isinstance(s, dict) and s.get("bytes_in_use") is not None]
    peak = [s.get("peak_bytes_in_use") for s in stats
            if isinstance(s, dict)
            and s.get("peak_bytes_in_use") is not None]
    out: Dict[str, Any] = {}
    if in_use:
        out["hbm_bytes_in_use"] = int(sum(in_use))
    if peak:
        out["hbm_peak_bytes"] = int(max(peak))
    return out


# -- streaming event log -----------------------------------------------------

#: default events.jsonl rotation threshold — generous on purpose: an
#: offline fit/score run never gets near it, while a long-running serve
#: replica (which emits per-request events forever) stays bounded
DEFAULT_EVENTLOG_MAX_MB = 256.0


def _eventlog_max_bytes(max_mb: Optional[float]) -> int:
    """Resolved rotation threshold in bytes; 0 disables rotation."""
    if max_mb is None:
        raw = os.environ.get("TMOG_EVENTLOG_MAX_MB", "").strip().lower()
        if raw in ("", "auto"):
            max_mb = DEFAULT_EVENTLOG_MAX_MB
        elif raw in ("0", "off", "false", "no"):
            max_mb = 0.0
        else:
            try:
                max_mb = float(raw)
            except ValueError:
                max_mb = DEFAULT_EVENTLOG_MAX_MB
    return int(max(float(max_mb), 0.0) * 1e6)


class EventLog:
    """Append-only JSONL of timestamped run events.

    Each line: {"seq": N, "t": monotonic_seconds, "ts": wall_epoch,
    "event": type, ...fields}. `t` is non-decreasing and `seq` strictly
    increasing — the monotonicity contract `trace_report --check`
    validates. Lines are flushed per event so `tail -f events.jsonl`
    follows a live run.

    ROTATION: under a long-running serve a per-request event stream
    grows without bound, so once the live file passes `max_mb`
    (``TMOG_EVENTLOG_MAX_MB``, default 256 — generous enough that
    offline runs never rotate; 0/off disables) it shifts to
    ``events.jsonl.1`` (older segments to ``.2`` … up to `keep`, the
    oldest dropped) and a fresh live file opens. `seq` and the monotonic
    clock CONTINUE across the boundary — concatenating the segments
    oldest-first (:func:`event_log_paths`) reproduces one monotone
    stream, which is exactly what trace-report reads."""

    def __init__(self, path: str, max_mb: Optional[float] = None,
                 keep: Optional[int] = None) -> None:
        self.path = path
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._f = open(path, "a", encoding="utf-8")
        self._lock = threading.Lock()
        self._seq = 0
        self._mono0 = time.perf_counter()
        self._max_bytes = _eventlog_max_bytes(max_mb)
        if keep is None:
            try:
                keep = int(os.environ.get("TMOG_EVENTLOG_KEEP", "3"))
            except ValueError:
                keep = 3
        self.keep = max(int(keep), 1)
        self.rotations = 0

    def emit(self, event: str, **fields: Any) -> None:
        with self._lock:
            rec = {"seq": self._seq, "t": round(
                time.perf_counter() - self._mono0, 6),
                "ts": round(time.time(), 6), "event": event}
            rec.update(_jsonable(fields))
            self._seq += 1
            # this lock EXISTS to serialize the per-event line write +
            # flush: seq/t monotonicity across threads is the file's
            # contract, so the I/O inside the critical section is the
            # design, not an accident
            try:
                # tmoglint: disable=THR002  serialized write IS the lock's job
                self._f.write(json.dumps(rec, default=str) + "\n")
                # tmoglint: disable=THR002  flush pairs with the write
                self._f.flush()
                if self._max_bytes and self._f.tell() >= self._max_bytes:
                    self._rotate()
            except (ValueError, OSError):
                # closed file / full disk / flaky mount: the liveness
                # side channel must never kill the run it is monitoring
                pass

    def _rotate(self) -> None:
        """Shift the full live file to .1 (.1 -> .2 … oldest dropped)
        and reopen. Caller holds the lock; `seq`/`_mono0` deliberately
        survive so the stream stays monotone across segments."""
        try:
            self._f.close()
        except OSError:
            pass
        try:
            oldest = f"{self.path}.{self.keep}"
            if os.path.exists(oldest):
                os.remove(oldest)
            for i in range(self.keep - 1, 0, -1):
                src = f"{self.path}.{i}"
                if os.path.exists(src):
                    os.replace(src, f"{self.path}.{i + 1}")
            os.replace(self.path, f"{self.path}.1")
        except OSError:
            pass  # a failed shift falls through to reopening in place
        # the shift + reopen IS what the lock serializes: an emit racing
        # a half-rotated log would interleave segments
        # tmoglint: disable=THR002  rotation is the lock's job
        self._f = open(self.path, "a", encoding="utf-8")
        self.rotations += 1

    def close(self) -> None:
        with self._lock:
            try:
                self._f.close()
            except OSError:
                pass

    def follow(self, *, stop: Optional[threading.Event] = None,
               poll_s: float = 0.1, from_start: bool = False) -> "Any":
        """Tail-subscribe to THIS log's path (:func:`follow_events`):
        yields parsed events seq-monotone across size-rotation
        boundaries until `stop` is set. Safe from another thread — the
        follower reads the files, never this writer's handle."""
        return follow_events(self.path, stop=stop, poll_s=poll_s,
                             from_start=from_start)


def event_log_paths(path: str) -> List[str]:
    """Every segment of a (possibly rotated) event log, OLDEST first —
    ``events.jsonl.N … events.jsonl.1 events.jsonl``. Reading them in
    this order reproduces one stream with `seq` strictly increasing
    across the rotation boundaries."""
    numbered: List[Tuple[int, str]] = []
    for p in _glob.glob(path + ".*"):
        suffix = p[len(path) + 1:]
        if suffix.isdigit():
            numbered.append((int(suffix), p))
    out = [p for _, p in sorted(numbered, reverse=True)]
    if os.path.exists(path):
        out.append(path)
    return out


def iter_events(path: str) -> "Any":
    """Yield every parsed event record across all rotated segments of
    `path`, oldest first (the tail-across-the-boundary reader).
    Unparseable lines are skipped — validation is trace-report's job."""
    for p in event_log_paths(path):
        try:
            with open(p, encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        yield json.loads(line)
                    except json.JSONDecodeError:
                        continue
        except OSError:
            continue


def follow_events(path: str, *, stop: Optional[threading.Event] = None,
                  poll_s: float = 0.1, from_start: bool = False) -> "Any":
    """Tail-subscribe to a (possibly rotating) event log: yield every
    parsed event with a `seq` STRICTLY greater than the last one seen,
    until `stop` is set (the retrain controller's trigger source;
    :meth:`EventLog.follow` delegates here).

    The steady-state cost is `tail -f`'s: an open handle + byte offset
    on the LIVE file, reading only appended lines per poll. The cursor
    that survives rotation is the EventLog's own monotonicity contract —
    `seq` strictly increases across ``events.jsonl.N`` boundaries — so
    when the live file is REPLACED under the handle (inode change, or
    the file shrank), the follower rescans every segment oldest-first
    (:func:`iter_events`) and emits only records beyond the last seq:
    events appended just before the shift are seen exactly once, from
    the ``.1`` segment they rotated into. A segment dropped past `keep`
    between polls is lost — the same contract tail -f + logrotate gives.
    A torn final line (writer mid-append, or a crash) is held back until
    its newline lands; records without an integer `seq` are skipped
    (they also fail trace-report --check).

    `from_start=False` (default) begins AFTER the current end of the
    log — a subscriber attaching to a long-running serve must not
    replay history as fresh triggers. The log may not exist yet; the
    follower waits for it to appear."""
    last = -1
    # tail-mode attach consumes the first full-segment scan silently
    # (advancing `last` past history instead of pre-scanning AND
    # rescanning — history is parsed exactly once either way)
    primed = from_start

    f = None
    ino = None

    def _close():
        nonlocal f, ino
        if f is not None:
            try:
                f.close()
            except OSError:
                pass
        f, ino = None, None

    def _parse(line: str):
        nonlocal last
        line = line.strip()
        if not line:
            return None
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            return None
        s = rec.get("seq") if isinstance(rec, dict) else None
        if not isinstance(s, int) or s <= last:
            return None
        last = s
        return rec

    try:
        while stop is None or not stop.is_set():
            rotated = False
            try:
                st = os.stat(path)
            except OSError:
                _close()
                st = None
                if not primed:
                    # no LIVE file at attach time: skip whatever
                    # rotated history already exists (a follower
                    # attaching mid-rotation must not replay it);
                    # everything that lands later is fresh
                    for rec in iter_events(path):
                        s = rec.get("seq")
                        if isinstance(s, int) and s > last:
                            last = s
                    primed = True
            if st is not None:
                if f is None or st.st_ino != ino \
                        or st.st_size < f.tell():
                    # fresh file under the path: first attach, a
                    # rotation that shifted the one we were reading to
                    # .1, or a truncate-in-place (logrotate copytruncate
                    # keeps the inode but drops our offset past EOF) —
                    # catch up through ALL segments by seq (on a plain
                    # first attach with from_start=False this pass only
                    # advances `last` past pre-existing history)
                    rotated = True
                    _close()
                    for rec in iter_events(path):
                        s = rec.get("seq")
                        if isinstance(s, int) and s > last:
                            last = s
                            if primed:
                                yield rec
                    primed = True
                    try:
                        # read the fresh live file from byte 0 — lines
                        # the rescan already emitted are dropped by the
                        # seq filter, and a line appended between the
                        # rescan and this open is NOT missed (seeking to
                        # EOF here would skip it)
                        f = open(path, encoding="utf-8")
                        ino = st.st_ino
                    except OSError:
                        _close()
                if f is not None and not rotated:
                    while True:
                        pos = f.tell()
                        line = f.readline()
                        if not line:
                            break
                        if not line.endswith("\n"):
                            # torn tail: the writer is mid-append (or
                            # died mid-line); re-read once it completes
                            f.seek(pos)
                            break
                        rec = _parse(line)
                        if rec is not None:
                            yield rec
            if stop is None:
                time.sleep(poll_s)
            elif stop.wait(poll_s):
                return
    finally:
        _close()


# -- Chrome trace_event export -----------------------------------------------

def chrome_trace(tree: TraceTree, app_name: str = "transmogrifai_tpu"
                 ) -> Dict[str, Any]:
    """Chrome trace_event JSON (the format Perfetto and chrome://tracing
    load): one complete ("ph": "X") event per span, microsecond
    timestamps on the tree's monotonic clock, span/parent ids + attrs in
    `args` so the hierarchy survives round-trips through the viewer."""
    pid = os.getpid()
    events: List[Dict[str, Any]] = [
        {"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
         "args": {"name": app_name}},
        {"ph": "M", "name": "thread_name", "pid": pid, "tid": 1,
         "args": {"name": "run"}},
    ]
    # per-LANE view: spans carrying a `lane` attr (the request-trace
    # exporter stamps one per tracer) render on their own tid row in
    # Perfetto instead of interleaving with the run hierarchy — kept
    # request windows + their segment chains read as swimlanes
    lanes: Dict[str, int] = {}
    for sp in tree.spans:
        lane = sp.attrs.get("lane")
        if isinstance(lane, str) and lane not in lanes:
            lanes[lane] = 2 + len(lanes)
    for lane, tid in lanes.items():
        events.append({"ph": "M", "name": "thread_name", "pid": pid,
                       "tid": tid, "args": {"name": lane}})
    end_default = tree.now()
    for sp in tree.spans:
        end = sp.t_end if sp.t_end is not None else end_default
        args = {"span_id": sp.span_id, "parent_id": sp.parent_id,
                "error": sp.error}
        if sp.error_type:
            args["error_type"] = sp.error_type
        args.update(_jsonable(sp.attrs))
        events.append({
            "ph": "X", "name": sp.name, "cat": sp.kind,
            "ts": round(sp.t_start * 1e6, 3),
            "dur": round(max(end - sp.t_start, 0.0) * 1e6, 3),
            "pid": pid, "tid": lanes.get(sp.attrs.get("lane"), 1),
            "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"app_name": app_name,
                          "trace_wall_start": tree._wall0}}


def write_chrome_trace(path: str, tree: TraceTree,
                       app_name: str = "transmogrifai_tpu") -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(chrome_trace(tree, app_name), f, indent=1)


# -- trace-report ------------------------------------------------------------

def _load_trace_spans(path: str) -> Tuple[List[Dict[str, Any]], List[str]]:
    """(span dicts from a chrome trace file, schema problems)."""
    problems: List[str] = []
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [], [f"{path}: unreadable trace ({e})"]
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        return [], [f"{path}: no traceEvents list"]
    spans = []
    ids = set()
    for i, ev in enumerate(events):
        ph = ev.get("ph")
        if ph is None:
            problems.append(f"{path}: event {i} missing 'ph'")
            continue
        if ph != "X":
            continue
        missing = [k for k in ("ts", "dur", "pid", "tid") if k not in ev]
        if missing:
            problems.append(f"{path}: X event {i} ({ev.get('name')}) "
                            f"missing {missing}")
            continue
        bad_num = [k for k in ("ts", "dur")
                   if not isinstance(ev[k], (int, float))
                   or isinstance(ev[k], bool) or ev[k] < 0]
        if bad_num:
            # flag AND drop: the containment arithmetic below must never
            # crash on the malformed input this validator exists to catch
            problems.append(f"{path}: X event {i} ({ev.get('name')}) "
                            f"non-numeric {bad_num}")
            continue
        args = ev.get("args", {})
        sid = args.get("span_id")
        if sid is not None:
            if sid in ids:
                problems.append(f"{path}: duplicate span_id {sid}")
            ids.add(sid)
        spans.append(ev)
    # parent integrity: every parent_id must be a recorded span_id
    for ev in spans:
        pid_ = ev.get("args", {}).get("parent_id")
        if pid_ is not None and pid_ not in ids:
            problems.append(f"{path}: span {ev.get('name')} has unknown "
                            f"parent_id {pid_}")
    # containment: a child's [ts, ts+dur] must sit inside its parent's
    # window (1ms slack for rounding)
    by_id = {ev["args"].get("span_id"): ev for ev in spans
             if ev.get("args", {}).get("span_id") is not None}
    slack_us = 1000.0
    for ev in spans:
        pid_ = ev.get("args", {}).get("parent_id")
        parent = by_id.get(pid_)
        if parent is None:
            continue
        if ev["ts"] + slack_us < parent["ts"] or \
                ev["ts"] + ev["dur"] > parent["ts"] + parent["dur"] \
                + slack_us:
            problems.append(
                f"{path}: span {ev.get('name')} escapes parent "
                f"{parent.get('name')} window")
    return spans, problems


def _check_event_log(paths: List[str]
                     ) -> Tuple[int, List[str], Dict[str, int]]:
    """(n valid events, schema problems, counts per event type) in ONE
    pass — report mode reuses the counts instead of re-parsing a log
    that can run 10^5+ lines on a long sweep. `paths` is the rotated
    segment chain OLDEST FIRST (event_log_paths): `seq`/`t`
    monotonicity is validated ACROSS rotation boundaries, because the
    EventLog rotation contract is that the concatenated segments are
    one monotone stream."""
    problems: List[str] = []
    counts: Dict[str, int] = {}
    n = 0
    last_t = None
    last_seq = None
    for path in paths:
        with open(path) as f:
            for lineno, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    problems.append(f"{path}:{lineno}: invalid JSON")
                    continue
                n += 1
                ev_name = rec.get("event", "?")
                counts[ev_name] = counts.get(ev_name, 0) + 1
                if "event" not in rec:
                    problems.append(f"{path}:{lineno}: missing 'event'")
                t = rec.get("t")
                if not isinstance(t, (int, float)):
                    problems.append(f"{path}:{lineno}: missing numeric "
                                    f"'t'")
                else:
                    # a re-attached log (resumed run) restarts the
                    # monotonic clock; monotonicity is per seq=0 segment
                    seq = rec.get("seq")
                    if last_t is not None and seq != 0 and t < last_t:
                        problems.append(f"{path}:{lineno}: timestamp "
                                        f"went backwards ({t} < "
                                        f"{last_t})")
                    last_t = t
                seq = rec.get("seq")
                if isinstance(seq, int) and isinstance(last_seq, int) \
                        and seq != 0 and seq <= last_seq:
                    problems.append(f"{path}:{lineno}: seq not "
                                    f"increasing")
                last_seq = seq if isinstance(seq, int) else last_seq
    return n, problems, counts


def fmt_table(rows: List[List[str]], header: List[str]) -> List[str]:
    """Left-justified fixed-width text table — the one formatter every
    report surface shares (trace-report, trace-report --requests,
    trace-report --pod via parallel/podtrace.py, the fleet status
    table) so their column alignment cannot drift apart."""
    if not rows:
        return ["(empty)"]
    widths = [max(len(str(r[i])) for r in [header] + rows)
              for i in range(len(header))]
    out = ["  ".join(str(h).ljust(w) for h, w in zip(header, widths))]
    for r in rows:
        out.append("  ".join(str(c).ljust(w) for c, w in zip(r, widths)))
    return out


_fmt_table = fmt_table  # pre-pod-tracing private spelling, still imported


def trace_report_rc(run_dir: str, check: bool = False,
                    top: int = 15) -> Tuple[str, int]:
    """(report text, exit code) with the project-wide code table
    (docs/static_analysis.md "Exit codes", shared with tmoglint):
    0 = clean, 1 = validation problems found, 2 = usage error (`run_dir`
    is not a traced run directory at all — nothing to validate is a
    caller mistake, not a passing check and not a schema failure)."""
    text, ok = trace_report(run_dir, check=check, top=top)
    if text.startswith("trace-report: nothing to read"):
        return text, 2
    return text, 0 if ok else 1


def trace_report(run_dir: str, check: bool = False,
                 top: int = 15) -> Tuple[str, bool]:
    """Render (report text, ok) for a traced run directory.

    Reads every `*trace.json` (chrome traces), `events.jsonl` and
    `*stage_metrics.json` under `run_dir`. With check=True the text is a
    validation verdict (schema problems listed) and ok=False on any.
    CLI callers want :func:`trace_report_rc`, which distinguishes a
    directory with nothing to read (usage error, exit 2) from real
    schema problems (exit 1)."""
    trace_files = sorted(_glob.glob(os.path.join(run_dir, "*trace.json")))
    event_log = os.path.join(run_dir, "events.jsonl")
    log_paths = event_log_paths(event_log)
    metric_files = sorted(
        _glob.glob(os.path.join(run_dir, "*stage_metrics.json")))
    lines: List[str] = []
    problems: List[str] = []

    if not trace_files and not metric_files and not log_paths:
        return (f"trace-report: nothing to read in {run_dir} (no "
                f"*trace.json, *stage_metrics.json or events.jsonl)", False)

    # span ids restart at 1 in every trace file: key everything by
    # (file index, id) or a multi-trace dir (the ci.sh smoke layout)
    # would subtract one file's children from another file's self-time
    all_spans: List[Tuple[int, Dict[str, Any]]] = []
    for fidx, tf in enumerate(trace_files):
        spans, probs = _load_trace_spans(tf)
        all_spans.extend((fidx, ev) for ev in spans)
        problems.extend(probs)

    n_events = 0
    event_counts: Dict[str, int] = {}
    if log_paths:
        n_events, probs, event_counts = _check_event_log(log_paths)
        problems.extend(probs)
        # serving contract (docs/serving.md): the engine emits one
        # serve_recompile event for every XLA compile that lands AFTER
        # its warmup finished — under the prewarmed bucket ladder there
        # must be none, so any such event fails --check exactly like a
        # schema violation (the ci.sh serving smoke pins this)
        n_rc = event_counts.get("serve_recompile", 0)
        if n_rc:
            problems.append(
                f"{event_log}: {n_rc} serve_recompile event(s) — XLA "
                f"compile(s) landed after serving warmup")
        # drift contract (docs/monitoring.md): every threshold breach
        # the serve-side monitor saw is a drift_alert event; --check
        # surfaces them the same way — a monitored run that drifted is
        # not a clean run
        n_da = event_counts.get("drift_alert", 0)
        if n_da:
            problems.append(
                f"{event_log}: {n_da} drift_alert event(s) — serve-time "
                f"feature/prediction drift exceeded policy thresholds")

    for mf in metric_files:
        try:
            with open(mf) as f:
                doc = json.load(f)
            for key in ("app_name", "duration_seconds",
                        "total_stage_seconds", "stage_metrics"):
                if key not in doc:
                    problems.append(f"{mf}: missing AppMetrics key "
                                    f"'{key}'")
        except (OSError, json.JSONDecodeError) as e:
            problems.append(f"{mf}: unreadable ({e})")

    if check:
        lines.append(f"trace-report --check: {len(trace_files)} trace "
                     f"file(s), {n_events} event(s), "
                     f"{len(metric_files)} metrics file(s)")
        if problems:
            lines.append(f"{len(problems)} problem(s):")
            lines.extend(f"  {p}" for p in problems)
        else:
            lines.append("OK")
        return "\n".join(lines), not problems

    # -- report mode -------------------------------------------------------
    lines.append(f"# trace-report {run_dir}")
    if all_spans:
        # self time = dur - sum(direct children dur)
        child_dur: Dict[Any, float] = {}
        for fidx, ev in all_spans:
            pid_ = ev.get("args", {}).get("parent_id")
            if pid_ is not None:
                key = (fidx, pid_)
                child_dur[key] = child_dur.get(key, 0.0) + ev["dur"]
        rows = []
        for fidx, ev in all_spans:
            sid = (fidx, ev.get("args", {}).get("span_id"))
            self_us = max(ev["dur"] - child_dur.get(sid, 0.0), 0.0)
            rows.append((self_us, ev))
        rows.sort(key=lambda r: -r[0])
        table = [[ev.get("name", "?")[:48], ev.get("cat", ""),
                  f"{ev['dur'] / 1e6:.4f}", f"{self_us / 1e6:.4f}",
                  str(ev.get("args", {}).get("compiles", "")),
                  "ERR" if ev.get("args", {}).get("error") else ""]
                 for self_us, ev in rows[:top]]
        lines.append(f"\n## Top spans by self-time "
                     f"({len(all_spans)} spans)")
        lines.extend(_fmt_table(
            table, ["span", "kind", "total_s", "self_s", "compiles",
                    "err"]))

        # recompiles per program (span name)
        comp: Dict[str, Tuple[int, float]] = {}
        for _, ev in all_spans:
            args = ev.get("args", {})
            c = args.get("compiles")
            if c:
                n, s = comp.get(ev.get("name", "?"), (0, 0.0))
                comp[ev.get("name", "?")] = (
                    n + int(c), s + float(args.get("compile_seconds", 0.0)))
        lines.append("\n## Recompiles per program")
        if comp:
            lines.extend(_fmt_table(
                [[name[:48], str(n), f"{s:.2f}"]
                 for name, (n, s) in
                 sorted(comp.items(), key=lambda kv: -kv[1][0])],
                ["program", "compiles", "compile_s"]))
        else:
            lines.append("(none recorded)")

        # roofline table from kernel spans
        kern = [ev for _, ev in all_spans if ev.get("cat") == "kernel"]
        if kern:
            lines.append("\n## Kernel roofline")
            lines.extend(_fmt_table(
                [[ev.get("name", "?")[:40],
                  f"{ev['dur'] / 1e6:.4f}",
                  str(ev.get("args", {}).get("bytes_hbm", "")),
                  str(ev.get("args", {}).get("achieved_gbps", "")),
                  str(ev.get("args", {}).get("pct_of_roof", "")),
                  str(ev.get("args", {}).get("cold", ""))]
                 for ev in kern],
                ["kernel", "wall_s", "bytes_hbm", "gbps", "pct_roof",
                 "cold"]))

        # HBM watermark
        peaks = [ev.get("args", {}).get("hbm_peak_bytes")
                 for _, ev in all_spans
                 if ev.get("args", {}).get("hbm_peak_bytes") is not None]
        if peaks:
            lines.append(f"\nHBM peak across spans: "
                         f"{max(peaks) / 1e9:.3f} GB")

    if n_events:
        counts = event_counts
        lines.append(f"\n## Event log ({n_events} events)")
        lines.extend(_fmt_table(
            [[k, str(v)] for k, v in
             sorted(counts.items(), key=lambda kv: -kv[1])],
            ["event", "count"]))

    if problems:
        lines.append(f"\n## {len(problems)} schema problem(s)")
        lines.extend(f"  {p}" for p in problems)
    return "\n".join(lines), not problems


# -- trace-report --requests -------------------------------------------------

#: a request is flagged when its UNATTRIBUTED wall (e2e minus the sum of
#: its segments) exceeds BOTH bounds: the fraction catches slow requests
#: hiding real time outside the segment chain, the floor keeps
#: millisecond-scale requests from flagging on scheduler-wake jitter
#: (condition-variable wakeups cost whole milliseconds on a busy CPU
#: host — attributing those would need a segment per context switch)
REQUEST_COVERAGE_TOLERANCE = 0.25
REQUEST_COVERAGE_FLOOR_MS = 25.0


def load_request_traces(run_dir: str) -> List[Dict[str, Any]]:
    """Every `request_trace` event under `run_dir` — the kept traces of
    the tail sampler (docs/observability.md "Request tracing") — read
    across rotated event-log segments, oldest first."""
    path = os.path.join(run_dir, "events.jsonl")
    return [rec for rec in iter_events(path)
            if rec.get("event") == "request_trace"]


def _coverage_problems(recs: List[Dict[str, Any]],
                       tolerance: float, floor_ms: float) -> List[str]:
    problems: List[str] = []
    by_id: Dict[str, Dict[str, float]] = {}
    for rec in recs:
        tid = rec.get("trace_id")
        wall = rec.get("wall_ms")
        segs = rec.get("segments") or {}
        if not isinstance(wall, (int, float)) or isinstance(wall, bool):
            problems.append(f"request {tid}: non-numeric wall_ms")
            continue
        seg_sum = sum(float(v) for v in segs.values()
                      if isinstance(v, (int, float)))
        slack = max(tolerance * wall, floor_ms)
        label = f"{rec.get('origin', '?')} request {tid}"
        if wall - seg_sum > slack:
            problems.append(
                f"{label}: segments cover {seg_sum:.1f}ms of "
                f"{wall:.1f}ms e2e wall ({wall - seg_sum:.1f}ms "
                f"unattributed > {slack:.1f}ms tolerance)")
        elif seg_sum - wall > slack:
            problems.append(
                f"{label}: segments sum to {seg_sum:.1f}ms, OVER the "
                f"{wall:.1f}ms e2e wall by more than {slack:.1f}ms")
        if isinstance(tid, str):
            by_id.setdefault(tid, {})[rec.get("origin", "?")] = \
                float(wall)
    # cross-process sanity — DURATIONS only, never absolute-timestamp
    # arithmetic between two hosts' clocks: the replica's own e2e wall
    # for a traced request must fit inside the router's wall for the
    # same trace id (plus slack for response serialization/transport)
    for tid, origins in by_id.items():
        rep, rout = origins.get("replica"), origins.get("router")
        if rep is None or rout is None:
            continue
        slack = max(tolerance * rout, floor_ms)
        if rep > rout + slack:
            problems.append(
                f"request {tid}: replica-side wall {rep:.1f}ms exceeds "
                f"the router-side wall {rout:.1f}ms for the same trace")
    return problems


def requests_report(run_dir: str, top: int = 15,
                    tolerance: float = REQUEST_COVERAGE_TOLERANCE,
                    floor_ms: float = REQUEST_COVERAGE_FLOOR_MS
                    ) -> Tuple[str, bool]:
    """(report text, ok) over the kept request traces of a run dir: the
    top-`top` slowest kept traces with their segment breakdown, kept
    reasons, and the coverage check — any request whose segments do not
    cover its end-to-end wall within tolerance is flagged (ok=False)."""
    recs = load_request_traces(run_dir)
    if not recs:
        return (f"trace-report --requests: no request_trace events in "
                f"{run_dir} (request tracing off, or no kept traces)",
                False)
    problems = _coverage_problems(recs, tolerance, floor_ms)
    lines = [f"# trace-report --requests {run_dir}",
             f"{len(recs)} kept trace(s)"]
    reasons: Dict[str, int] = {}
    for rec in recs:
        k = str(rec.get("kept", "?"))
        reasons[k] = reasons.get(k, 0) + 1
    lines.append("kept by reason: " + ", ".join(
        f"{k}={v}" for k, v in sorted(reasons.items(), key=lambda kv:
                                      -kv[1])))
    ranked = sorted(
        recs, key=lambda r: -(r.get("wall_ms")
                              if isinstance(r.get("wall_ms"),
                                            (int, float)) else 0.0))
    rows = []
    for rec in ranked[:top]:
        segs = rec.get("segments") or {}
        seg_sum = sum(float(v) for v in segs.values()
                      if isinstance(v, (int, float)))
        wall = rec.get("wall_ms")
        cover = (f"{100.0 * seg_sum / wall:.0f}%"
                 if isinstance(wall, (int, float)) and wall else "?")
        rows.append([str(rec.get("trace_id", "?"))[:16],
                     str(rec.get("origin", "?")),
                     str(rec.get("replica", ""))[:20],
                     str(rec.get("status", "")),
                     str(rec.get("kept", "")),
                     f"{wall:.2f}" if isinstance(wall, (int, float))
                     else "?",
                     cover,
                     " ".join(f"{k}={v:.2f}" for k, v in segs.items()
                              if isinstance(v, (int, float)))[:72]])
    lines.append(f"\n## Top {min(top, len(ranked))} slowest kept traces")
    lines.extend(_fmt_table(rows, ["trace", "origin", "replica",
                                   "status", "kept", "wall_ms", "cover",
                                   "segments_ms"]))
    if problems:
        lines.append(f"\n## {len(problems)} coverage problem(s)")
        lines.extend(f"  {p}" for p in problems)
    else:
        lines.append("\ncoverage OK (every kept trace's segments cover "
                     "its e2e wall within tolerance)")
    return "\n".join(lines), not problems


def requests_report_rc(run_dir: str, top: int = 15) -> Tuple[str, int]:
    """(text, exit code) with the project-wide code table
    (docs/static_analysis.md "Exit codes"): 0 = clean, 1 = coverage
    problems, 2 = nothing to read (no kept request traces at all)."""
    text, ok = requests_report(run_dir, top=top)
    if text.startswith("trace-report --requests: no request_trace"):
        return text, 2
    return text, 0 if ok else 1
