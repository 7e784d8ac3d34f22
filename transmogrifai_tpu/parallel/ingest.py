"""Parallel sharded ingest: a parse-worker pool feeding the tileplane.

The tileplane (parallel/tileplane.py) overlaps H2D copy with device
compute, but its feed was still ONE Python thread parsing records
cell-by-cell — at 100M-row scale the device starves on host parse, the
exact input-pipeline bottleneck sharded-host ingest solves for pjit/TPU
training (PAPERS arxiv 2204.06514). This module parallelizes the feed
WITHOUT changing a single downstream bit:

- `ShardedSource` is a RowSource over per-file-shard chunk factories.
  N parse workers each own a striped subset of shards (worker j owns
  shards j, j+N, j+2N, ... — `FileStreamingReader._paths` already fixes
  the shard order) and decode into bounded per-shard queues;
- the consumer side of `chunks()` drains those queues IN SHARD-INDEX
  ORDER — deterministic order-preserving reassembly. The global chunk
  sequence is identical to a serial read of the shards, so the
  tileplane's fixed-tile assembly slices identical tiles and every
  float reduction (stats moments, GLM Gram/score, tree histograms)
  stays BIT-IDENTICAL to serial ingest at any worker count;
- a worker crash/exception lands on the queue of the shard it was
  parsing; reassembly reaches that shard and re-raises on the consumer
  thread — a failed pass, never a hang;
- single-shard or workers<=1 inputs degrade to a serial in-thread loop
  (today's single-producer path, same spans, no threads);
- decode is COLUMNAR: workers pull whole column blocks per chunk
  (readers/readers.csv_columnar_chunks, readers/avro.read_avro_columns)
  and convert each column with ONE vectorized `np.asarray`/`astype`
  (readers/readers.columnar_f32) instead of the per-cell dict walk;
- each worker wraps every decoded chunk in a `tile_parse` span carrying
  a per-worker `lane` attr, so parse/copy/compute overlap renders as
  separate Perfetto swimlanes (docs/observability.md).

TMOG_INGEST_WORKERS sizes the pool (default 1); the pass emits an
`ingest_pass` event + IngestPass telemetry record.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import (Any, Callable, Dict, Iterable, Iterator, List,
                    Optional, Sequence, Tuple)

import numpy as np

from ..utils.env import env_int
from .tileplane import RowSource

#: per-shard queue depth: how many chunks a worker may decode ahead of
#: reassembly on each shard it owns (host buffering is bounded by
#: shards * ahead chunks, independent of file size)
_SHARD_QUEUE_AHEAD = 2


def ingest_workers() -> int:
    """Parse-worker pool size for sharded sources: TMOG_INGEST_WORKERS,
    default 1 (serial parse, the order-preserving baseline), never
    below 1. Per pass the pool is additionally clamped to the shard
    count."""
    return max(1, env_int("TMOG_INGEST_WORKERS", 1))


def _put(q: "queue.Queue", item: Any, stop: threading.Event) -> bool:
    """Bounded put that observes the stop flag (the consumer may abandon
    the pass mid-stream); False = pass abandoned, caller unwinds."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.1)
            return True
        except queue.Full:
            continue
    return False


def _parse_worker(factories: Sequence[Callable[[], Iterable[Tuple[np.ndarray, ...]]]],
                  owned: Sequence[int], qs: Sequence["queue.Queue"],
                  stop: threading.Event, traced: bool, anchor: Any,
                  label: str, worker_idx: int,
                  parse_s: List[float], collector: Any) -> None:
    """Worker body: decode owned shards IN ORDER into their per-shard
    queues. Module-level with explicit args — all pass state lives in
    the consumer's frame, none on shared objects. An exception lands on
    the queue of the shard being parsed: reassembly drains shards in
    index order, and every shard before the failed one either ended
    cleanly or fails first, so the consumer always reaches the error
    (failed pass) instead of blocking on a sentinel that never comes.
    `parse_s[worker_idx]` is a single-writer slot, read by the consumer
    only after join."""
    si = owned[0]
    try:
        for si in owned:
            q = qs[si]
            seq = 0
            t0 = time.perf_counter()
            for chunk in factories[si]():
                chunk = tuple(np.ascontiguousarray(a) for a in chunk)
                dur = time.perf_counter() - t0
                parse_s[worker_idx] += dur
                if traced:
                    collector.trace.add_complete(
                        "tile_parse", "tile", dur, parent_span=anchor,
                        shard=si, chunk=seq, worker=worker_idx,
                        rows=int(chunk[0].shape[0]), label=label,
                        lane=f"ingest-w{worker_idx}")
                if not _put(q, ("chunk", chunk), stop):
                    return
                seq += 1
                t0 = time.perf_counter()
            if not _put(q, ("end", None), stop):
                return
    except BaseException as e:
        _put(qs[si], ("error", e), stop)


class ShardedSource(RowSource):
    """Order-preserving parallel-parse RowSource over file shards.

    `shard_factories[i]()` starts a fresh chunk iteration of shard i
    (tuples of same-leading-dim arrays, the RowSource chunk contract).
    `chunks()` yields shard 0's chunks, then shard 1's, ... — exactly a
    serial concatenated read — while up to `workers` threads decode
    ahead. Re-iterable: every `chunks()` call is a fresh pass with
    fresh threads (GLM rounds re-read disk through the same pool)."""

    def __init__(self, shard_factories: Sequence[
                     Callable[[], Iterable[Tuple[np.ndarray, ...]]]],
                 *, n_rows: Optional[int] = None,
                 workers: Optional[int] = None,
                 ahead: int = _SHARD_QUEUE_AHEAD,
                 label: str = "ingest"):
        self.shard_factories = list(shard_factories)
        self.n_rows = n_rows
        #: None = resolve ingest_workers() (TMOG_INGEST_WORKERS) per pass
        self.workers = workers
        self.ahead = max(1, int(ahead))
        self.label = label
        self._anchor: Any = None

    def set_span_anchor(self, anchor: Any) -> None:
        # caller's thread, BEFORE the pass's threads exist (run_tileplane
        # contract) — workers then receive it by argument
        # tmoglint: disable=THR001  written before pass threads start
        self._anchor = anchor

    def effective_workers(self) -> int:
        """Pool size for the next pass: requested (or planned) workers
        clamped to the shard count — a single shard has no parallelism
        to exploit and degrades to the serial path."""
        w = self.workers if self.workers is not None else ingest_workers()
        return max(1, min(int(w), len(self.shard_factories)))

    def chunks(self) -> Iterator[Tuple[np.ndarray, ...]]:
        n_workers = self.effective_workers()
        if n_workers <= 1 or len(self.shard_factories) <= 1:
            yield from self._serial_pass()
        else:
            yield from self._parallel_pass(n_workers)

    # -- serial degradation (single shard / workers=1 / tiny inputs) --------

    def _serial_pass(self) -> Iterator[Tuple[np.ndarray, ...]]:
        """The single-producer path, in-thread — same chunk sequence,
        same tile_parse spans (worker 0), so serial-vs-parallel A/B
        reads off one trace schema."""
        from ..utils.metrics import collector
        traced = bool(collector.enabled)
        anchor = self._anchor
        parse_s = 0.0
        rows = 0
        n_chunks = 0
        t_pass = time.perf_counter()
        for si, factory in enumerate(self.shard_factories):
            seq = 0
            t0 = time.perf_counter()
            for chunk in factory():
                chunk = tuple(np.ascontiguousarray(a) for a in chunk)
                dur = time.perf_counter() - t0
                parse_s += dur
                if traced:
                    collector.trace.add_complete(
                        "tile_parse", "tile", dur, parent_span=anchor,
                        shard=si, chunk=seq, worker=0,
                        rows=int(chunk[0].shape[0]), label=self.label,
                        lane="ingest-w0")
                rows += int(chunk[0].shape[0])
                n_chunks += 1
                seq += 1
                yield chunk
                t0 = time.perf_counter()
        if traced:
            collector.ingest_pass(
                label=self.label, workers=1,
                shards=len(self.shard_factories), chunks=n_chunks,
                rows=rows, parse_seconds=parse_s,
                wall_seconds=time.perf_counter() - t_pass)

    # -- the worker pool ----------------------------------------------------

    def _parallel_pass(self, n_workers: int
                       ) -> Iterator[Tuple[np.ndarray, ...]]:
        from ..utils.metrics import collector
        traced = bool(collector.enabled)
        anchor = self._anchor
        n_shards = len(self.shard_factories)
        qs = [queue.Queue(maxsize=self.ahead) for _ in range(n_shards)]
        stop = threading.Event()
        parse_s = [0.0] * n_workers
        threads = []
        for w in range(n_workers):
            th = threading.Thread(
                target=_parse_worker,
                args=(self.shard_factories, list(range(w, n_shards,
                                                       n_workers)),
                      qs, stop, traced, anchor, self.label, w, parse_s,
                      collector),
                name=f"ingest-{self.label}-w{w}", daemon=True)
            th.start()
            threads.append(th)
        rows = 0
        n_chunks = 0
        t_pass = time.perf_counter()
        try:
            for si in range(n_shards):
                # reassembly: global order = shard order = serial order
                while True:
                    kind, payload = qs[si].get()
                    if kind == "end":
                        break
                    if kind == "error":
                        raise payload
                    rows += int(payload[0].shape[0])
                    n_chunks += 1
                    yield payload
        finally:
            stop.set()
            # drain every queue so workers blocked on put observe the
            # flag (their _put loops re-check it each timeout)
            for q in qs:
                try:
                    while True:
                        q.get_nowait()
                except queue.Empty:
                    pass
            for th in threads:
                th.join(timeout=30.0)
            if traced:
                # parse_s read happens-after join
                collector.ingest_pass(
                    label=self.label, workers=n_workers,
                    shards=n_shards, chunks=n_chunks, rows=rows,
                    parse_seconds=sum(parse_s),
                    wall_seconds=time.perf_counter() - t_pass)

    def peek(self) -> Tuple[np.ndarray, ...]:
        """Width probe without spinning up the pool: read shard 0's
        first chunk in-thread (falls back to a full-pass probe when
        shard 0 is empty). Cached like the base peek."""
        if self._peek_cache is None:
            if self.shard_factories:
                it = iter(self.shard_factories[0]())
                try:
                    first = next(it)
                except StopIteration:
                    first = None
                finally:
                    close = getattr(it, "close", None)
                    if close is not None:
                        close()
                if first is not None:
                    self._peek_cache = tuple(
                        np.ascontiguousarray(a) for a in first)
                    return self._peek_cache
            return super().peek()
        return self._peek_cache


def sharded_reader_source(paths: Sequence[str],
                          columns_fn: Callable[[Dict[str, np.ndarray]],
                                               Tuple[np.ndarray, ...]],
                          *, columns: Optional[Sequence[str]] = None,
                          batch_records: int = 8192,
                          n_rows: Optional[int] = None,
                          workers: Optional[int] = None,
                          label: str = "ingest",
                          stripe: Optional[bool] = None) -> ShardedSource:
    """ShardedSource over CSV/Avro file shards with COLUMNAR decode.

    Each shard decodes in whole column blocks — one vectorized
    float32 conversion per column per chunk, no per-record dicts —
    and `columns_fn({name -> float32 array})` maps one chunk's columns
    to the source's chunk tuple (e.g. `lambda c: (np.stack([c["x0"],
    c["x1"]], 1), c["y"], c["w"])`): the vectorized replacement for the
    per-record `row_fn` of tileplane.reader_row_source. Format is by
    extension per shard (.avro = container decode, else CSV);
    `columns` restricts decode to the named fields (CSV header names /
    Avro record fields). Shard ORDER is the caller's `paths` order —
    pass FileStreamingReader's deterministic listing for file globs.

    `stripe` (None = auto: TMOG_MULTIHOST set AND >1 jax processes)
    keeps only THIS PROCESS's contiguous stripe of `paths`
    (multihost.stripe_paths): under multi-host SPMD every process calls
    with the SAME deterministic global listing and opens ONLY its own
    files — its parsed rows are its batch-axis block of the global row
    set. When the stripe drops files, a caller-supplied global `n_rows`
    no longer describes the local stream and is reset to None. Pass
    stripe=False when `paths` is already a per-process stripe."""
    paths = [str(p) for p in paths]
    if stripe is None:
        from .multihost import multihost_enabled
        stripe = multihost_enabled()
    if stripe:
        from . import multihost as MH
        if MH.process_count() > 1:
            mine = [str(p) for p in MH.stripe_paths(paths)]
            if len(mine) != len(paths):
                paths = mine
                n_rows = None

    def factory_for(path: str) -> Callable[[], Iterator[Tuple[np.ndarray, ...]]]:
        if path.endswith(".avro"):
            def factory() -> Iterator[Tuple[np.ndarray, ...]]:
                from ..readers.avro import read_avro_columns
                from ..readers.readers import columnar_f32
                for cols in read_avro_columns(
                        path, fields=columns,
                        batch_records=batch_records):
                    yield columns_fn(
                        {k: columnar_f32(v) for k, v in cols.items()})
        else:
            def factory() -> Iterator[Tuple[np.ndarray, ...]]:
                from ..readers.readers import csv_columnar_chunks
                for cols in csv_columnar_chunks(
                        path, columns=columns,
                        batch_records=batch_records):
                    yield columns_fn(cols)
        return factory

    return ShardedSource([factory_for(p) for p in paths], n_rows=n_rows,
                         workers=workers, label=label)
