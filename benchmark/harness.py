"""What every cell shares: the run's context, the compile counter, the
closed loop of whole jobs, the profiler window and the device report.

Nothing here knows a cell, a configuration or a per-layer metric: those
are files under workloads/, configs/, drivers/, layers/ and readers/,
found by the names BENCHMARK.json lists.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib.util
import json
import os
import re
import statistics
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class BenchFailure(Exception):
    """The run cannot give a result (thin window, missing file, wrong
    device): exit non-zero, print no last line."""


_T0 = time.time()


def log(msg: str) -> None:
    print(f"[bench +{time.time() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def load_json(*parts: str) -> dict:
    path = os.path.join(HERE, *parts)
    if not os.path.exists(path):
        raise BenchFailure(f"no such benchmark file: {path}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by name: a later PR adds a driver
    or a reader as a new file and edits nothing."""
    if not NAME_RE.match(name):
        raise BenchFailure(f"bad {kind} name {name!r}")
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise BenchFailure(f"no such benchmark file: {path}")
    mod_name = f"benchmark.{kind}.{name}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def layer_files() -> list:
    """Every per-layer metric the benchmark knows: one JSON file each."""
    out = []
    for path in sorted(glob.glob(os.path.join(HERE, "layers", "*.json"))):
        with open(path) as f:
            out.append(json.load(f))
    return out


# -- the run's context --------------------------------------------------------

@dataclasses.dataclass
class Ctx:
    """One run of one cell. Drivers fill `counters` (what the program
    counted), `notes` (the report's earlier line) and `problems` (each one
    makes the run `correct: false`); readers only read."""

    cell: dict
    config: dict
    sizes: dict            # config sizes, shrunk by its rehearsal block
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    out_dir: str
    compile_log: "CompileLog"
    peaks: dict | None = None
    counters: dict = dataclasses.field(default_factory=dict)
    notes: dict = dataclasses.field(default_factory=dict)
    problems: list = dataclasses.field(default_factory=list)
    reduced: object = None   # reduce_trace.Reduced of a traced run

    def param(self, key: str):
        """A traffic parameter of the cell; its `rehearsal` block wins
        under --rehearse."""
        if self.rehearse and key in self.cell.get("rehearsal", {}):
            return self.cell["rehearsal"][key]
        return self.cell[key]

    def require(self, cond: bool, what: str) -> bool:
        if not cond:
            log(f"PROBLEM: {what}")
            self.problems.append(what)
        return bool(cond)


@dataclasses.dataclass
class Result:
    attempted: int
    failed: int
    end_to_end: dict        # metric name -> value, all digits


# -- compiles ----------------------------------------------------------------

class CompileLog:
    """Process-wide compile record from jax.monitoring (copied from
    chip_smoke.py): every backend compile by program name, and how many
    were loads from the persistent cache. A true compile is a backend
    compile that was not a cache load."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _CACHE_HIT = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax.monitoring
        self._lock = threading.Lock()   # the listener fires on whatever
        self.seconds = {}               # thread compiles
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        with self._lock:
            if event == self._CACHE_HIT:
                self.cache_hits += 1
            elif event == self._COMPILE:
                self.programs += 1
                name = str(kw.get("fun_name", "?"))
                self.seconds[name] = self.seconds.get(name, 0.0) \
                    + float(duration)

    def snapshot(self) -> dict:
        with self._lock:
            return {"programs": self.programs, "cache_loads": self.cache_hits,
                    "true_compiles": max(self.programs - self.cache_hits, 0)}

    def slowest(self, k=6) -> list:
        with self._lock:
            top = sorted(self.seconds.items(), key=lambda kv: -kv[1])[:k]
        return [[n, round(s, 2)] for n, s in top]


# -- what the drivers share ---------------------------------------------------------

def pool_entry(base: dict, grid: dict, rehearse: bool) -> tuple:
    """(estimator class, its fixed params, the grid points) of one family:
    `base` is the configuration's pool entry, `grid` a dict of lists whose
    cartesian product (first key slowest) is laid over its `fixed_grid`."""
    import importlib
    import itertools
    mod, _, name = base["estimator"].partition(":")
    cls = getattr(importlib.import_module(mod), name)
    over = base.get("rehearsal_grid", {}) if rehearse else {}
    grids = [dict(base.get("fixed_grid", {}), **dict(zip(grid, vals)), **over)
             for vals in itertools.product(*grid.values())]
    return cls, dict(base.get("params", {})), grids


def watched_warmup(ctx: "Ctx", job) -> tuple:
    """The warm-up job: compiles or loads every program of the cell, with
    the program's collector on (its event log, its kernel spans, which
    fence) so that routes are read from what ran. The collector is off
    again before the window. Returns (answer, events, kernel span names)."""
    from transmogrifai_tpu.utils.metrics import collector
    events_path = os.path.join(ctx.out_dir, "warmup_events.jsonl")
    if os.path.exists(events_path):
        os.remove(events_path)
    collector.enable("benchmark_warmup")
    collector.attach_event_log(events_path)
    try:
        answer = job()
        spans = [k.kernel for k in collector.current.kernel_metrics]
    finally:
        collector.detach_event_log()
        collector.finish()
        collector.disable()
    events = []
    if os.path.exists(events_path):
        with open(events_path) as f:
            events = [json.loads(ln) for ln in f if ln.strip()]
    return answer, events, spans


# -- the closed loop of whole jobs ----------------------------------------------

def closed_loop(job, seconds: float, span: str, max_jobs=None) -> list:
    """One client, next job when the last returned. Returns (wall, output)
    of every WHOLE job that finished inside the window. A job that cannot
    finish by the deadline, going by the quickest seen so far, is not
    started; one in flight at the deadline is dropped."""
    import jax
    done = []
    deadline = time.perf_counter() + seconds
    while max_jobs is None or len(done) < max_jobs:
        t0 = time.perf_counter()
        if t0 + min((w for w, _ in done), default=0.0) >= deadline:
            break
        with jax.profiler.TraceAnnotation(span):
            out = job()
        # a job returns host values (floats, bytes): the wall ends after
        # the device work that made them
        # tmoglint: disable=TPU005  the job's output is on the host
        wall = time.perf_counter() - t0
        if t0 + wall > deadline:
            break
        done.append((wall, out))
    return done


def job_result(ctx: Ctx, done: list, metric: str, same) -> Result:
    """Median wall of a closed loop's jobs; `same(first, other)` says
    whether two jobs gave the same answer (every job of a run must)."""
    need = ctx.param("min_jobs") if not ctx.trace else 1
    if len(done) < need:
        raise BenchFailure(
            f"{len(done)} whole jobs in the window, {need} needed: a "
            f"median over fewer is not printed")
    walls = [w for w, _ in done]
    failed = sum(not same(done[0][1], out) for _, out in done[1:])
    ctx.require(failed == 0, f"{failed} jobs of the run answered unlike "
                             f"the first")
    ctx.notes["job_walls_s"] = walls
    return Result(attempted=len(done), failed=failed,
                  end_to_end={metric: statistics.median(walls)})


# -- the profiler window -------------------------------------------------------

@contextlib.contextmanager
def profiler(ctx: Ctx):
    """jax.profiler around the traced part of a --trace 1 run; a no-op
    in a --trace 0 run. The Python tracer stays off: it slows the host it
    is meant to measure and buries the benchmark's own spans."""
    if not ctx.trace:
        yield
        return
    import shutil

    import jax
    trace_dir = os.path.join(ctx.out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise BenchFailure(f"the profiler wrote no .xplane.pb under "
                           f"{trace_dir}")
    ctx.notes["xplane"] = found[0]
    ctx.notes["xplane_bytes"] = os.path.getsize(found[0])


# -- the device ------------------------------------------------------------------

def device_report(chips: int) -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def load_peaks(device_kind: str) -> dict:
    """The benchmark's own copy of the published peaks, keyed by
    device_kind. A device that is not in the table is an error."""
    table = load_json("peaks.json")
    if device_kind not in table["devices"]:
        raise BenchFailure(
            f"device kind {device_kind!r} is not in benchmark/peaks.json; "
            f"add its published peaks with their source")
    return table["devices"][device_kind]
