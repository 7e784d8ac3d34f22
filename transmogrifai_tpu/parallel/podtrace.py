"""Pod flight recorder: per-rank fit tracing merged into one timeline.

PR 18 put the whole fit pipeline on a multi-process (data x lane) mesh,
but the pod stayed a black box: when the launcher reaped a timeout it
only knew "the stragglers are wedged in a collective" — no rank
timeline, no psum-wait vs compute split, no liveness signal. This
module is that signal path, in three layers:

1. **Per-rank recording** — when ``TMOG_PODTRACE`` is on and
   ``TMOG_PODTRACE_DIR`` names an artifact root, every rank records its
   own TraceTree/EventLog into ``<dir>/rank-<k>/`` (started from
   `multihost.initialize`, saved from `multihost.finalize`). The engine
   call sites bracket each round's **compute**, **collective entry ->
   exit** (the psum/allgather barrier wall, measured as monotonic deltas
   around each cross-host reduction) and **ingest stripe** walls with
   the `pod_round` / `compute` / `collective` / `ingest` context
   managers below. On the fused mesh path the compute and the psum live
   in ONE jitted program, so the bracketed collective window = program
   call + result fetch: a victim rank's collective wall inflates while
   it waits for a straggler, and the straggler itself shows large
   *derived compute* (round wall minus collective wall) — which is
   exactly the attribution the skew table reads.

2. **Heartbeats** — each bracket transition appends one JSON line
   (round, phase, monotonic, wall ts) to ``rank-<k>/heartbeat.jsonl``
   via a single O_APPEND write (atomic on POSIX; a torn final line is
   ignored by readers). `launch_local_pod`'s reaper reads the tails to
   name the wedged rank, round and collective in its timeout error
   (`straggler_table`) instead of the generic wedged message.

3. **Post-hoc merge** — `merge_pod` joins N rank dirs into one Chrome
   trace with rank swimlanes. Rank clocks are NOT synchronized, so the
   merge uses durations only, aligned on shared round boundaries: round
   r of every rank starts at the same merged timestamp and the merged
   round width is the slowest rank's width. Per round it computes the
   straggler rank, the max/median derived-compute ratio and each rank's
   collective-wait share; an MFU pass attributes analytic FLOPs/bytes
   to the measured spans and names the top sinks (`mfu_table`).

Surfaces: ``trace-report --pod <dir>`` (merged timeline + skew table,
exit 1 on undercoverage or broken round alignment), ``bench.py
--multihost`` (skew/collective-wait block), ci.sh's pod stage (asserts
an injected straggler is detected and named).

Telemetry must never break bring-up or a fit: every recorder entry
point is a no-op unless active, and `start`/`finish` swallow their own
failures.
"""
from __future__ import annotations

import contextlib
import glob as _glob
import json
import os
import sys
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

from ..utils.tracing import union_seconds

__all__ = [
    "enabled", "active", "start", "finish", "beat", "pod_round",
    "compute", "collective", "ingest", "note_collective",
    "read_heartbeat", "straggler_table", "rank_dirs", "merge_pod",
    "pod_report", "pod_report_rc", "COVERAGE_MIN",
    "STRAGGLER_RATIO", "HEARTBEAT_NAME", "METRICS_NAME", "META_NAME",
]

HEARTBEAT_NAME = "heartbeat.jsonl"
METRICS_NAME = "metrics.json"
META_NAME = "meta.json"

#: per-round interval-union coverage floor `trace-report --pod` enforces
#: (the acceptance bar: compute + collective + ingest spans must explain
#: at least this share of each rank's round wall)
COVERAGE_MIN = 0.75

#: max/median derived-compute ratio above which a round names a straggler
STRAGGLER_RATIO = 1.5

#: span kinds the recorder emits (merge keys on these)
POD_KINDS = ("pod_round", "pod_compute", "pod_collective", "pod_ingest")

#: span kinds that count toward per-round coverage: the explicit pod
#: brackets plus the tileplane/kernel spans the engines already emit
#: (a streamed stats pass inside a round is covered by its tile spans,
#: not by a redundant pod_compute wrapper)
_COVER_KINDS = ("pod_compute", "pod_collective", "pod_ingest", "tile",
                "kernel")


def _env_on(name: str) -> bool:
    return os.environ.get(name, "").strip().lower() not in (
        "", "0", "false", "off", "no")


def enabled() -> bool:
    """TMOG_PODTRACE: master switch for per-rank pod recording
    (launch_local_pod's `trace_dir` kwarg sets it for every child)."""
    return _env_on("TMOG_PODTRACE")


def _heartbeat_interval_s() -> float:
    """TMOG_PODTRACE_HEARTBEAT_S: min seconds between non-forced beats
    (phase transitions always beat — the rate limit only throttles
    repeats of the same phase)."""
    try:
        return max(float(os.environ.get("TMOG_PODTRACE_HEARTBEAT_S",
                                        "0.5")), 0.0)
    except ValueError:
        return 0.5


def _span_budget() -> int:
    """TMOG_PODTRACE_SPAN_BUDGET: pod spans recorded per rank before
    span bookkeeping stops (heartbeats continue — liveness outlives the
    bounded trace, same shape as TMOG_SERVE_SPAN_BUDGET)."""
    try:
        return max(int(os.environ.get("TMOG_PODTRACE_SPAN_BUDGET",
                                      "20000")), 0)
    except ValueError:
        return 20000


def _debug_sleep_ms() -> float:
    """TMOG_PODTRACE_DEBUG_SLEEP_MS: chaos hook — the rank it is set on
    sleeps this long inside every pod_round, inside an explicit
    pod_compute span (site=debug_sleep), so the skew table must flag it
    as the straggler. 0 = disabled; launch_local_pod's `debug_sleep_ms`
    kwarg sets it on one rank only."""
    try:
        return max(float(os.environ.get("TMOG_PODTRACE_DEBUG_SLEEP_MS",
                                        "0")), 0.0)
    except ValueError:
        return 0.0


class _Recorder:
    """Process-local recorder state. One per rank process; the lock
    serializes beats (the tileplane producer thread and the host
    dispatch thread both cross bracket boundaries) — tmoglint THR001."""

    def __init__(self) -> None:
        self.active = False
        self.rank = 0
        self.dir: Optional[str] = None
        self.hb_fd: Optional[int] = None
        self.owns_collector = False
        self.round: Optional[int] = None
        self.phase = "init"
        self.last_beat = 0.0
        self.spans = 0
        self.lock = threading.RLock()


_rec = _Recorder()


def active() -> bool:
    return _rec.active


def start(process_id: Optional[int] = None,
          processes: Optional[int] = None) -> Optional[str]:
    """Begin per-rank recording (idempotent; returns the rank dir or
    None). Called from `multihost.initialize()` after bring-up; no-op
    unless TMOG_PODTRACE is on and TMOG_PODTRACE_DIR names a root.
    Failures are swallowed: the flight recorder must never break the
    pod it is observing."""
    with _rec.lock:
        if _rec.active or not enabled():
            return _rec.dir
        root = os.environ.get("TMOG_PODTRACE_DIR", "").strip()
        if not root:
            return None
        try:
            if process_id is None:
                process_id = int(os.environ.get("TMOG_PROC_ID", "0") or 0)
            rank_dir = os.path.join(root, f"rank-{int(process_id)}")
            os.makedirs(rank_dir, exist_ok=True)
            from ..utils.metrics import collector
            if not collector.collecting:
                collector.enable(f"pod-rank{int(process_id)}")
                _rec.owns_collector = True
            collector.attach_event_log(
                os.path.join(rank_dir, "events.jsonl"))
            backend = "cpu"
            jmod = sys.modules.get("jax")
            if jmod is not None:
                try:
                    backend = str(jmod.default_backend())
                except Exception:
                    pass
            meta = {"rank": int(process_id), "pid": os.getpid(),
                    "backend": backend, "ts": round(time.time(), 3)}
            if processes is not None:
                meta["processes"] = int(processes)
            with open(os.path.join(rank_dir, META_NAME), "w",
                      encoding="utf-8") as fh:
                json.dump(meta, fh)
            _rec.hb_fd = os.open(
                os.path.join(rank_dir, HEARTBEAT_NAME),
                os.O_APPEND | os.O_CREAT | os.O_WRONLY, 0o644)
            _rec.rank = int(process_id)
            _rec.dir = rank_dir
            _rec.round = None
            _rec.phase = "init"
            _rec.spans = 0
            _rec.active = True
        except Exception:
            _rec.active = False
            return None
    beat("start", force=True)
    return _rec.dir


def finish() -> None:
    """Save this rank's artifacts and stop recording (idempotent).
    Called from `multihost.finalize()` — i.e. while every peer is still
    alive, so a rank killed mid-run simply leaves a torn dir, which
    `merge_pod` degrades to a partial report."""
    with _rec.lock:
        if not _rec.active:
            return
        _rec.active = False
        rank_dir, fd = _rec.dir, _rec.hb_fd
        owns = _rec.owns_collector
        _rec.hb_fd = None
        _rec.owns_collector = False
    try:
        _write_beat(fd, _rec.round, "finish")
    except Exception:
        pass
    try:
        from ..utils.metrics import collector
        if rank_dir is not None:
            # a joined run (caller owns the collector) gets a snapshot
            # save; an owned run closes out — either way metrics.json
            # carries the span tree merge_pod reads
            collector.save(os.path.join(rank_dir, METRICS_NAME),
                           close=owns)
    except Exception:
        pass
    if fd is not None:
        try:
            os.close(fd)
        except OSError:
            pass


def _write_beat(fd: Optional[int], rnd: Optional[int],
                phase: str) -> None:
    if fd is None:
        return
    rec = {"round": rnd, "phase": phase,
           "mono": round(time.perf_counter(), 6),
           "ts": round(time.time(), 6)}
    # ONE os.write of one full line on an O_APPEND fd: atomic on POSIX,
    # so a concurrent reader sees whole lines or a torn tail it ignores
    os.write(fd, (json.dumps(rec) + "\n").encode("utf-8"))


def beat(phase: str, rnd: Optional[int] = None,
         force: bool = False) -> None:
    """Append one heartbeat line (rate-limited unless the phase changed
    or `force`). The launcher's reaper reads the tail to name a wedged
    rank's last (round, phase)."""
    with _rec.lock:
        if not _rec.active:
            return
        if rnd is not None:
            _rec.round = int(rnd)
        now = time.perf_counter()
        if not (force or phase != _rec.phase
                or now - _rec.last_beat >= _heartbeat_interval_s()):
            return
        _rec.phase = phase
        _rec.last_beat = now
        fd, cur = _rec.hb_fd, _rec.round
    try:
        _write_beat(fd, cur, phase)
    except OSError:
        pass  # full disk must not kill the run it is monitoring


def _budget_ok() -> bool:
    with _rec.lock:
        if not _rec.active:
            return False
        _rec.spans += 1
        return _rec.spans <= _span_budget()


@contextlib.contextmanager
def _span(name: str, kind: str, **attrs: Any) -> Iterator[Any]:
    if not _budget_ok():
        yield None
        return
    from ..utils.metrics import collector
    with collector.trace_span(name, kind, **attrs) as sp:
        yield sp


@contextlib.contextmanager
def pod_round(index: Any, **attrs: Any) -> Iterator[Any]:
    """Bracket one engine round (the shared alignment boundary the
    merge keys on: every rank runs the same round indexes). Fires the
    debug-sleep chaos hook inside an explicit pod_compute span so the
    injected straggler's wall is attributed, not mysterious."""
    if not _rec.active:
        yield None
        return
    idx = int(index)
    beat("round", rnd=idx, force=True)
    with _span(f"pod_round[{idx}]", "pod_round", round=idx,
               **attrs) as sp:
        ms = _debug_sleep_ms()
        if ms > 0:
            with _span("pod_compute[debug_sleep]", "pod_compute",
                       site="debug_sleep", sleep_ms=ms):
                time.sleep(ms / 1000.0)
        try:
            yield sp
        finally:
            beat("round_end", force=True)


@contextlib.contextmanager
def compute(site: str, **attrs: Any) -> Iterator[Any]:
    """Bracket host/device compute attributed to `site`."""
    if not _rec.active:
        yield None
        return
    beat(f"compute:{site}")
    with _span(f"pod_compute[{site}]", "pod_compute", site=site,
               **attrs) as sp:
        yield sp


@contextlib.contextmanager
def collective(site: str, **attrs: Any) -> Iterator[Any]:
    """Bracket one cross-host reduction, entry -> exit. The entry beat
    is forced: "last seen entering collective X of round N" is exactly
    what the reaper needs to name a wedge. On the fused mesh path the
    window is program call + fetch (the psum is inside the jitted
    program) — see the module docstring for how skew reads that."""
    if not _rec.active:
        yield None
        return
    beat(f"collective:{site}", force=True)
    try:
        with _span(f"pod_collective[{site}]", "pod_collective",
                   site=site, **attrs) as sp:
            yield sp
    finally:
        beat(f"post:{site}", force=True)


@contextlib.contextmanager
def ingest(site: str, **attrs: Any) -> Iterator[Any]:
    """Bracket one ingest stripe wall (parse + landing of this rank's
    rows)."""
    if not _rec.active:
        yield None
        return
    beat(f"ingest:{site}")
    with _span(f"pod_ingest[{site}]", "pod_ingest", site=site,
               **attrs) as sp:
        yield sp


def note_collective(site: str, dur: float, **attrs: Any) -> None:
    """Record an ALREADY-measured collective wall (e.g. the tileplane
    tile merge, whose blocking device wait is timed by the consumer's
    own block_until_ready window) without re-timing it."""
    if not _rec.active or not _budget_ok():
        return
    try:
        from ..utils.metrics import collector
        if collector.collecting:
            collector.trace.add_complete(
                f"pod_collective[{site}]", "pod_collective",
                max(float(dur), 0.0), site=site, **attrs)
    except Exception:
        pass


# -- heartbeat reading (launcher side) ---------------------------------------

def read_heartbeat(rank_dir: str) -> Optional[Dict[str, Any]]:
    """Last COMPLETE heartbeat record of one rank dir, or None. The
    atomic-append contract: only newline-terminated lines count, so a
    writer killed mid-append (or racing this reader) yields the
    previous beat, never a torn one."""
    path = os.path.join(rank_dir, HEARTBEAT_NAME)
    try:
        with open(path, "rb") as fh:
            raw = fh.read()
    except OSError:
        return None
    nl = raw.rfind(b"\n")
    if nl < 0:
        return None
    for line in reversed(raw[:nl].split(b"\n")):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line.decode("utf-8"))
        except (ValueError, UnicodeDecodeError):
            continue
        if isinstance(rec, dict):
            return rec
    return None


def rank_dirs(pod_dir: str) -> List[Tuple[int, str]]:
    """(rank, path) for every ``rank-<k>/`` under `pod_dir`, rank
    order."""
    out: List[Tuple[int, str]] = []
    for p in _glob.glob(os.path.join(pod_dir, "rank-*")):
        if not os.path.isdir(p):
            continue
        tail = os.path.basename(p)[len("rank-"):]
        if tail.isdigit():
            out.append((int(tail), p))
    return sorted(out)


def straggler_table(pod_dir: str,
                    rcs: Optional[List[Optional[int]]] = None
                    ) -> Tuple[str, List[int]]:
    """(table text, likely straggler ranks) from the per-rank heartbeat
    tails — what the launcher appends to its timeout / dead-coordinator
    error so the operator learns WHICH rank wedged, in which round, in
    which collective, without opening a single artifact.

    Straggler heuristic: a wedged pod is N-1 victims parked inside a
    collective ("collective:<site>" phase, beats stop at entry) plus
    the rank that never arrived — so ranks whose last phase is NOT a
    collective entry are the suspects; among them (or among all, when
    every rank reads "collective:") the oldest beat names the wedge."""
    dirs = rank_dirs(pod_dir)
    if not dirs:
        return ("(no podtrace heartbeats under %s)" % pod_dir, [])
    now = time.time()
    rows: List[Tuple[int, Optional[int], Optional[float],
                     Optional[int], str]] = []
    for rank, path in dirs:
        hb = read_heartbeat(path)
        rc = None
        if rcs is not None and rank < len(rcs):
            rc = rcs[rank]
        if hb is None:
            rows.append((rank, rc, None, None, "(no heartbeat)"))
            continue
        age = max(now - float(hb.get("ts") or now), 0.0)
        rnd = hb.get("round")
        rows.append((rank, rc, age,
                     int(rnd) if isinstance(rnd, int) else None,
                     str(hb.get("phase") or "?")))
    live = [r for r in rows if r[1] is None and r[2] is not None]
    pool = [r for r in live
            if not r[4].startswith("collective:")] or live
    pool = sorted(pool, key=lambda r: -(r[2] or 0.0))
    stragglers = [r[0] for r in pool[:1]]
    lines = ["rank  rc    beat_age_s  round  phase"]
    for rank, rc, age, rnd, phase in rows:
        lines.append(
            f"{rank:<4}  {str(rc):<4}  "
            f"{('%.1f' % age) if age is not None else '?':<10}  "
            f"{str(rnd) if rnd is not None else '?':<5}  {phase}")
    if stragglers:
        r = next(x for x in rows if x[0] == stragglers[0])
        lines.append(
            f"likely straggler: rank {r[0]} (round "
            f"{r[3] if r[3] is not None else '?'}, phase {r[4]}, "
            f"beat {('%.1f' % r[2]) if r[2] is not None else '?'}s ago)")
    return "\n".join(lines), stragglers


# -- post-hoc merge ----------------------------------------------------------

def _load_rank(rank: int, path: str) -> Dict[str, Any]:
    """One rank's artifacts; a killed-mid-write rank yields torn=True
    and empty spans (the partial-report contract), never a raise."""
    out: Dict[str, Any] = {"rank": rank, "path": path, "spans": [],
                           "meta": {}, "torn": False}
    try:
        with open(os.path.join(path, META_NAME), encoding="utf-8") as fh:
            meta = json.load(fh)
        if isinstance(meta, dict):
            out["meta"] = meta
    except (OSError, ValueError):
        pass
    try:
        with open(os.path.join(path, METRICS_NAME),
                  encoding="utf-8") as fh:
            doc = json.load(fh)
        spans = doc.get("spans") if isinstance(doc, dict) else None
        if not isinstance(spans, list):
            raise ValueError("no spans")
        out["spans"] = [s for s in spans if isinstance(s, dict)]
        out["doc"] = doc
    except (OSError, ValueError):
        out["torn"] = True
    return out


def _span_window(s: Dict[str, Any]) -> Optional[Tuple[float, float]]:
    t0, t1 = s.get("t_start"), s.get("t_end")
    if not isinstance(t0, (int, float)) or not isinstance(
            t1, (int, float)) or isinstance(t0, bool):
        return None
    return (float(t0), float(t1))


def _rank_rounds(spans: List[Dict[str, Any]]
                 ) -> Dict[int, Tuple[float, float]]:
    """round index -> (t_start, t_end) on this rank's own clock (first
    occurrence wins: a replayed index cannot stretch the window)."""
    out: Dict[int, Tuple[float, float]] = {}
    for s in spans:
        if s.get("kind") != "pod_round":
            continue
        rnd = (s.get("attrs") or {}).get("round")
        w = _span_window(s)
        if isinstance(rnd, int) and w is not None and rnd not in out:
            out[rnd] = w
    return out


def _median(vals: List[float]) -> float:
    if not vals:
        return 0.0
    v = sorted(vals)
    n = len(v)
    return v[n // 2] if n % 2 else 0.5 * (v[n // 2 - 1] + v[n // 2])


# analytic FLOPs/bytes priors per collective/compute site, from the
# attrs the instrumentation sites stamp (rows/feat/lanes/iters).
def _analytic_cost(name: str, attrs: Dict[str, Any]
                   ) -> Tuple[float, float]:
    """(flops, bytes) attributed to one measured span; (0, 0) when the
    shape attrs are absent (the span still ranks by wall)."""
    def num(*keys: str, default: float = 0.0) -> float:
        for k in keys:
            v = attrs.get(k)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                return float(v)
        return default

    rows = num("rows", "n_rows")
    feat = num("feat", "cols")
    lanes = num("lanes", default=1.0)
    iters = num("iters", "n_iter", default=1.0)
    site = str(attrs.get("site") or name)
    if "glm_round" in site:
        # streamed IRLS round: per iter, eta = X @ B (2*r*f*l), working
        # response + weights (~6*r*l), gram/rhs accumulation
        # (~3*r*f*l) — call it 5*r*f*l*iters with X re-read per iter
        return (5.0 * rows * feat * lanes * iters,
                4.0 * rows * feat * iters)
    if "gram" in site:
        # one-shot X^T X (+ X^T y per lane): r*f*(f+l) MACs
        return (2.0 * rows * feat * (feat + lanes),
                4.0 * rows * feat)
    if "tree" in site:
        depth = num("depth", default=6.0)
        folds = num("folds", default=1.0)
        return (2.0 * rows * feat * depth * max(folds, 1.0),
                4.0 * rows * feat)
    if "stats" in site or "tile" in site:
        cols = feat or num("cols")
        return (8.0 * rows * cols, 4.0 * rows * cols)
    return (0.0, 0.0)


def merge_pod(pod_dir: str, out: Optional[str] = None,
              coverage_min: float = COVERAGE_MIN) -> Dict[str, Any]:
    """Join every ``rank-<k>/`` under `pod_dir` into one report dict +
    merged Chrome trace (written to `out`, default
    ``<pod_dir>/pod_trace.json``).

    Rank clocks are unsynchronized, so only DURATIONS are merged:
    round r starts at one shared merged timestamp for every rank and
    advances by the slowest rank's round wall. Returns::

        {"ranks": [...per-rank summaries...],
         "rounds": [...per-round skew rows...],
         "skew": {straggler_rank, flagged, max_ratio, ...},
         "mfu_table": [...top sinks...],
         "coverage_min_seen": float | None,
         "problems": [...strings...],
         "trace_path": out, "synthetic_rounds": bool}

    A torn rank dir (killed mid-write) degrades to a partial report; a
    rank whose round-index chain differs from its peers is a "broken
    round alignment" problem (exit 1 via `pod_report_rc`)."""
    dirs = rank_dirs(pod_dir)
    ranks = [_load_rank(rank, path) for rank, path in dirs]
    problems: List[str] = []
    for r in ranks:
        if r["torn"]:
            problems.append(
                f"rank {r['rank']}: torn artifact dir (no readable "
                f"{METRICS_NAME}) — partial report")

    live = [r for r in ranks if not r["torn"]]
    per_rank_rounds = {r["rank"]: _rank_rounds(r["spans"]) for r in live}

    # round alignment: every live rank must have run the same rounds
    synthetic = all(not rr for rr in per_rank_rounds.values())
    if synthetic:
        for r in live:
            windows = [w for s in r["spans"]
                       if s.get("kind") in _COVER_KINDS
                       for w in [_span_window(s)] if w is not None]
            if windows:
                per_rank_rounds[r["rank"]] = {
                    0: (min(w[0] for w in windows),
                        max(w[1] for w in windows))}
    else:
        idx_sets = {rank: frozenset(rr)
                    for rank, rr in per_rank_rounds.items() if rr}
        if len(set(idx_sets.values())) > 1:
            detail = "; ".join(
                f"rank {k}: rounds {sorted(v)[:8]}"
                for k, v in sorted(idx_sets.items()))
            problems.append(f"broken round alignment — {detail}")

    all_rounds = sorted({i for rr in per_rank_rounds.values()
                         for i in rr})

    # per (rank, round): wall, collective wall, coverage
    per_cell: Dict[Tuple[int, int], Dict[str, float]] = {}
    for r in live:
        rr = per_rank_rounds.get(r["rank"], {})
        for idx, (r0, r1) in rr.items():
            wall = max(r1 - r0, 0.0)
            coll_ivals: List[Tuple[float, float]] = []
            cover: List[Tuple[float, float]] = []
            for s in r["spans"]:
                kind = s.get("kind")
                if kind == "pod_round":
                    continue
                w = _span_window(s)
                if w is None or w[0] < r0 - 1e-6 or w[1] > r1 + 1e-6:
                    continue
                if kind == "pod_collective":
                    # UNION, not sum: a nested collective bracket (e.g.
                    # row_layout inside a wider window) must not double
                    # count toward the rank's wait share
                    coll_ivals.append(w)
                if kind in _COVER_KINDS:
                    cover.append(w)
            coll = union_seconds(coll_ivals)
            per_cell[(r["rank"], idx)] = {
                "wall": wall, "collective": coll,
                "compute": max(wall - coll, 0.0),
                "coverage": (union_seconds(cover) / wall
                             if wall > 0 else 1.0)}

    # skew per round
    round_rows: List[Dict[str, Any]] = []
    flag_counts: Dict[int, int] = {}
    coverage_min_seen: Optional[float] = None
    for idx in all_rounds:
        cells = {r["rank"]: per_cell[(r["rank"], idx)]
                 for r in live if (r["rank"], idx) in per_cell}
        if not cells:
            continue
        comp = {k: c["compute"] for k, c in cells.items()}
        med = _median(list(comp.values()))
        straggler = max(comp, key=lambda k: comp[k])
        ratio = (comp[straggler] / med) if med > 0 else (
            float("inf") if comp[straggler] > 0 else 1.0)
        flagged = ratio >= STRAGGLER_RATIO
        if flagged:
            flag_counts[straggler] = flag_counts.get(straggler, 0) + 1
        for k, c in cells.items():
            cov = c["coverage"]
            if coverage_min_seen is None or cov < coverage_min_seen:
                coverage_min_seen = cov
            if not synthetic and cov < coverage_min:
                problems.append(
                    f"rank {k} round {idx}: spans cover "
                    f"{100.0 * cov:.0f}% of the round wall "
                    f"(< {100.0 * coverage_min:.0f}%)")
        round_rows.append({
            "round": idx,
            "straggler_rank": straggler,
            "flagged": flagged,
            "compute_ratio": round(min(ratio, 1e9), 3),
            "wall_s": {k: round(c["wall"], 6)
                       for k, c in cells.items()},
            "collective_s": {k: round(c["collective"], 6)
                             for k, c in cells.items()},
            "collective_share": {
                k: round(c["collective"] / c["wall"], 4)
                if c["wall"] > 0 else 0.0 for k, c in cells.items()},
        })

    # merged timeline: shared round starts, slowest rank sets the width
    t_merged: Dict[int, float] = {}
    t_cursor = 0.0
    for idx in all_rounds:
        t_merged[idx] = t_cursor
        t_cursor += max((per_cell[(r["rank"], idx)]["wall"]
                         for r in live
                         if (r["rank"], idx) in per_cell),
                        default=0.0)

    events: List[Dict[str, Any]] = []
    for r in live:
        events.append({"ph": "M", "name": "process_name",
                       "pid": r["rank"], "tid": 0,
                       "args": {"name": f"rank-{r['rank']}"}})
        rr = per_rank_rounds.get(r["rank"], {})
        for s in r["spans"]:
            w = _span_window(s)
            if w is None:
                continue
            home = next((idx for idx, (r0, r1) in rr.items()
                         if w[0] >= r0 - 1e-6 and w[1] <= r1 + 1e-6),
                        None)
            if home is None:
                continue  # outside every round: not alignable
            shift = t_merged[home] - rr[home][0]
            args = dict(s.get("attrs") or {})
            args["rank"] = r["rank"]
            args["span_id"] = s.get("span_id")
            events.append({
                "ph": "X", "name": str(s.get("name", "?")),
                "cat": str(s.get("kind", "span")),
                "ts": round((w[0] + shift) * 1e6, 3),
                "dur": round((w[1] - w[0]) * 1e6, 3),
                "pid": r["rank"], "tid": 1, "args": args})

    if out is None:
        out = os.path.join(pod_dir, "pod_trace.json")
    trace_path: Optional[str] = out
    try:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w", encoding="utf-8") as fh:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms",
                       "otherData": {"pod_dir": pod_dir,
                                     "ranks": len(ranks),
                                     "alignment": "round-boundary, "
                                                  "durations only"}},
                      fh, indent=1)
    except OSError as e:
        problems.append(f"cannot write merged trace {out}: {e}")
        trace_path = None

    # MFU pass: analytic FLOPs/bytes per measured span, summed per site
    mfu_table = _mfu_table(live)

    # pod-level straggler: the rank flagged most often
    skew: Dict[str, Any] = {"flagged": bool(flag_counts)}
    if flag_counts:
        top = max(flag_counts, key=lambda k: flag_counts[k])
        skew["straggler_rank"] = top
        skew["flagged_rounds"] = flag_counts[top]
        skew["max_ratio"] = max(rw["compute_ratio"]
                                for rw in round_rows if rw["flagged"])

    rank_rows = []
    for r in ranks:
        cells = [per_cell[(r["rank"], i)] for i in all_rounds
                 if (r["rank"], i) in per_cell]
        wall = sum(c["wall"] for c in cells)
        coll = sum(c["collective"] for c in cells)
        rank_rows.append({
            "rank": r["rank"], "torn": r["torn"],
            "rounds": len(cells),
            "round_wall_s": round(wall, 6),
            "collective_s": round(coll, 6),
            "collective_share": round(coll / wall, 4) if wall > 0
            else 0.0,
            "min_coverage": round(min((c["coverage"] for c in cells),
                                      default=0.0), 4)})

    report = {"pod_dir": pod_dir, "ranks": rank_rows,
              "rounds": round_rows, "skew": skew,
              "mfu_table": mfu_table,
              "coverage_min_seen": (round(coverage_min_seen, 4)
                                    if coverage_min_seen is not None
                                    else None),
              "synthetic_rounds": synthetic,
              "problems": problems, "trace_path": trace_path}
    try:
        from ..utils.metrics import collector
        collector.event("podtrace_merge", pod_dir=pod_dir,
                        ranks=len(ranks), rounds=len(all_rounds),
                        problems=len(problems),
                        flagged=skew.get("flagged", False))
        if skew.get("flagged"):
            collector.event("pod_straggler",
                            rank=skew.get("straggler_rank"),
                            rounds=skew.get("flagged_rounds"),
                            max_ratio=skew.get("max_ratio"))
        if mfu_table:
            collector.event("mfu_table", sinks=mfu_table[:3])
    except Exception:
        pass
    return report


def _mfu_table(live: List[Dict[str, Any]],
               top: int = 3) -> List[Dict[str, Any]]:
    """Top measured sinks with analytic FLOPs/bytes attributed — the
    "where did the pod's wall go, and how far from the roof was it"
    table every traced fit emits. MFU needs the device's bf16 peak
    (utils/platform.DEVICE_SPECS; an unknown TPU kind raises); off-TPU
    the sinks still rank by wall with mfu omitted."""
    roof_gflops = None
    if sys.modules.get("jax") is not None:
        from ..utils.platform import device_spec
        spec = device_spec()
        roof_gflops = spec.bf16_flops / 1e9 if spec else None
    agg: Dict[str, List[float]] = {}
    total_wall = 0.0
    for r in live:
        for s in r["spans"]:
            if s.get("kind") not in ("pod_collective", "pod_compute",
                                     "pod_ingest", "kernel"):
                continue
            wall = float(s.get("duration_seconds") or 0.0)
            if wall <= 0.0:
                continue
            attrs = s.get("attrs") or {}
            flops, bts = _analytic_cost(str(s.get("name", "")), attrs)
            if not bts:
                b = attrs.get("bytes_hbm")
                if isinstance(b, (int, float)):
                    bts = float(b)
            slot = agg.setdefault(str(s.get("name", "?")),
                                  [0.0, 0.0, 0.0])
            slot[0] += wall
            slot[1] += flops
            slot[2] += bts
            total_wall += wall
    rows = []
    for name, (wall, flops, bts) in sorted(
            agg.items(), key=lambda kv: -kv[1][0]):
        row: Dict[str, Any] = {
            "span": name, "wall_s": round(wall, 6),
            "wall_share": round(wall / total_wall, 4)
            if total_wall > 0 else 0.0,
            "gflops": round(flops / 1e9, 3),
            "gbytes": round(bts / 1e9, 3)}
        if roof_gflops and wall > 0 and flops > 0:
            row["mfu"] = round(flops / wall / (roof_gflops * 1e9), 4)
        rows.append(row)
    return rows[:top]


# -- trace-report --pod ------------------------------------------------------

def _fmt(rows: List[List[str]], header: List[str]) -> List[str]:
    from ..utils.tracing import _fmt_table
    return _fmt_table(rows, header)


def pod_report(pod_dir: str, top: int = 15) -> Tuple[str, bool]:
    """(report text, ok) for a merged pod run dir."""
    report = merge_pod(pod_dir)
    lines = [f"# trace-report --pod {pod_dir}"]
    lines.append(f"\n## Ranks ({len(report['ranks'])})")
    lines.extend(_fmt(
        [[str(r["rank"]), "torn" if r["torn"] else "ok",
          str(r["rounds"]), f"{r['round_wall_s']:.4f}",
          f"{r['collective_s']:.4f}",
          f"{100.0 * r['collective_share']:.1f}%",
          f"{100.0 * r['min_coverage']:.0f}%"]
         for r in report["ranks"]],
        ["rank", "state", "rounds", "round_wall_s", "collective_s",
         "coll_share", "min_cover"]))
    if report["rounds"]:
        lines.append(f"\n## Per-round skew"
                     f" ({len(report['rounds'])} rounds"
                     + (", synthetic boundaries"
                        if report["synthetic_rounds"] else "") + ")")
        lines.extend(_fmt(
            [[str(rw["round"]), str(rw["straggler_rank"]),
              "*" if rw["flagged"] else "",
              f"{rw['compute_ratio']:.2f}",
              " ".join(f"r{k}={v:.3f}"
                       for k, v in sorted(rw["wall_s"].items())),
              " ".join(f"r{k}={100.0 * v:.0f}%"
                       for k, v in
                       sorted(rw["collective_share"].items()))]
             for rw in report["rounds"][:top]],
            ["round", "straggler", "flag", "max/med", "wall_s",
             "coll_share"]))
    skew = report["skew"]
    if skew.get("flagged"):
        lines.append(
            f"\nstraggler: rank {skew['straggler_rank']} flagged in "
            f"{skew['flagged_rounds']} round(s), max compute ratio "
            f"{skew['max_ratio']:.2f}")
    if report["mfu_table"]:
        lines.append("\n## Top sinks (analytic FLOPs/bytes)")
        lines.extend(_fmt(
            [[row["span"][:44], f"{row['wall_s']:.4f}",
              f"{100.0 * row['wall_share']:.1f}%",
              f"{row['gflops']:.2f}", f"{row['gbytes']:.3f}",
              f"{row['mfu']:.4f}" if "mfu" in row else "-"]
             for row in report["mfu_table"]],
            ["span", "wall_s", "share", "gflops", "gbytes", "mfu"]))
    if report["trace_path"]:
        lines.append(f"\nmerged trace: {report['trace_path']}")
    if report["problems"]:
        lines.append(f"\n## {len(report['problems'])} problem(s)")
        lines.extend(f"  {p}" for p in report["problems"])
    return "\n".join(lines), not report["problems"]


def pod_report_rc(pod_dir: str, top: int = 15) -> Tuple[str, int]:
    """(text, exit code), project-wide code table
    (docs/static_analysis.md "Exit codes"): 0 = clean, 1 = problems
    (undercoverage, broken round alignment, torn rank dirs), 2 = usage
    error (no ``rank-<k>/`` dirs at all — nothing to merge)."""
    if not rank_dirs(pod_dir):
        return (f"trace-report --pod: no rank-*/ dirs under {pod_dir} "
                f"(not a podtrace artifact root)", 2)
    text, ok = pod_report(pod_dir, top=top)
    return text, 0 if ok else 1
