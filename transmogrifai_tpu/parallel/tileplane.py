"""Unified double-buffered host->device row-tile pipeline.

Every streamed hot path in this repo used to own a private, fully
SYNCHRONOUS tile loop: ops/stats_engine.stream_stats dispatched one tile,
blocked on the state fetch, host-merged, then started the next H2D copy
(zero copy/compute overlap); ops/glm_sweep.sweep_glm_streamed_rounds
re-read X per round through its own loop; tree binning and bulk scoring
required a resident matrix. Large-scale JAX/TPU training gets its
throughput precisely by overlapping the input pipeline's H2D transfers
with device compute behind async dispatch (PAPERS: pjit/TPUv4 training,
arxiv 2204.06514), and external-memory gradient boosting shows tree
workloads stream well when tiles keep a fixed shape (PAPERS: XGBoost GPU,
arxiv 1806.11248).

This module is the ONE pipeline those consumers now share:

- a background PRODUCER thread slices/pads row chunks into fixed-shape
  numpy tiles (ragged tail zero-padded — the repo-wide zero-weight pad
  convention makes padded rows inert in every consumer's math) and
  `device_put`s tile k+1 while the caller's thread runs tile k's jitted
  step — classic double buffering, generalized to a DEPTH-N PREFETCH
  RING: the copy slot carries `TMOG_TILE_PREFETCH` tokens (released
  when the consumer dequeues a tile), so at most depth+1 tiles are ever
  in flight — the one computing plus up to `depth` copied-ahead. The
  default of 1 is exactly the old two-in-flight double buffering. Depth
  NEVER changes tile sizes or boundaries, so results stay bit-identical
  at any depth;
- the feed side itself can parallelize: a RowSource may parse file
  shards on a worker pool (parallel/ingest.ShardedSource) as long as
  `chunks()` yields the same chunk sequence as a serial read — the
  fixed-tile assembly below is order-preserving, which is what keeps
  stats/GLM/tree reductions bit-identical to serial ingest;
- the CARRY (moment state, GLM accumulators) stays device-resident for
  the whole pass and is fetched ONCE at the end, not per tile;
- the consumer's jitted step DONATES the carry (donate_argnums=(0,)),
  so the accumulator updates in place; tile buffers are not
  donate-marked — they have no same-shaped output to alias (XLA would
  warn and copy) and their last host reference dies at dispatch, which
  frees them just as early;
- fixed tile shapes mean ONE executable per (consumer, tile shape): the
  RecompileTracker pins 0 recompiles from tile 2 onward;
- when tracing is enabled (utils/metrics.collector), every tile records a
  `tile_copy` span (producer thread, around device_put + ready fence) and
  a `tile_compute` span (consumer thread, around the step dispatch +
  carry fence), so copy/compute OVERLAP is measurable in the exported
  Perfetto trace rather than asserted;
- an optional shard_map lane: the producer device_puts tiles with the
  caller-supplied shardings (parallel/mesh.batch_sharding) and the
  consumer's step runs under shard_map — stats tiles psum-merge across
  the mesh batch axis exactly like the resident sharded driver (and
  under the same tmoglint SHD collective-correctness gate: the lane's
  replicated carry is only sound because each tile's cross-shard merge
  psums before folding in — see docs/static_analysis.md).

`TMOG_TILEPLANE=0` is the global kill switch: every consumer keeps its
legacy synchronous loop behind it. `TMOG_TILE_MB` sizes tiles (default
32MB of f32 rows, matching the stats engine's scan-tile budget).

Sources are RE-ITERABLE (`RowSource.chunks()` starts a fresh pass), so a
multi-pass consumer (GLM Newton rounds) re-reads disk instead of holding
X: a larger-than-HBM CSV/Avro flow runs fit -> score end-to-end without
ever materializing the matrix.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import (Any, Callable, Dict, Iterable, Iterator, List,
                    NamedTuple, Optional, Sequence, Tuple)

import numpy as np

from ..utils.env import env_int, env_on


def tileplane_enabled() -> bool:
    """THE kill switch: TMOG_TILEPLANE=0 restores every consumer's legacy
    synchronous streamed loop."""
    return env_on("TMOG_TILEPLANE")


def tile_budget_bytes() -> int:
    """Host/device bytes per tile: TMOG_TILE_MB, default 32. It sizes
    every consumer's tile; two tiles in flight + the carry is the
    pipeline's whole device footprint."""
    return env_int("TMOG_TILE_MB", 32) << 20


def tile_prefetch_depth() -> int:
    """Copy-slot tokens in the prefetch ring: how many tiles the
    producer may run AHEAD of the consumer (device footprint is
    depth+1 tiles plus the carry): TMOG_TILE_PREFETCH, default 1 (the
    classic double buffering), never below 1. Depth only changes how
    far the feed side runs ahead, never tile shapes, so any depth is
    bit-identical."""
    return max(1, env_int("TMOG_TILE_PREFETCH", 1))


def tile_rows_for(row_bytes: int, n_rows: Optional[int] = None,
                  multiple: int = 1) -> int:
    """Rows per tile for a given per-row byte width, clamped to [256,
    2^20], rounded UP to `multiple` (mesh batch-axis divisibility)."""
    c = tile_budget_bytes() // max(int(row_bytes), 1)
    c = max(min(c, 1 << 20), 256)
    if n_rows is not None:
        c = max(min(c, int(n_rows)), 1)
    if multiple > 1:
        c = -(-c // multiple) * multiple
    return c


# -- row sources -------------------------------------------------------------

class RowSource:
    """Re-iterable source of host row-chunks.

    `chunks()` starts a FRESH pass and yields tuples of numpy arrays that
    share a leading row dimension (chunk sizes may vary; the pipeline
    re-tiles them). Multi-pass consumers (GLM rounds) call `chunks()` once
    per data pass — for file-backed sources that is a re-read of disk,
    which is the point: X never lives in memory.
    """

    #: row count if known up front (None for tail-follow sources)
    n_rows: Optional[int] = None

    def chunks(self) -> Iterator[Tuple[np.ndarray, ...]]:
        raise NotImplementedError

    _peek_cache: Optional[Tuple[np.ndarray, ...]] = None

    def peek(self) -> Tuple[np.ndarray, ...]:
        """First chunk of a fresh pass (shape/width probe for drivers
        that need d or F before streaming). Cached: repeated probes cost
        one chunk read TOTAL, not one per caller."""
        if self._peek_cache is None:
            it = self.chunks()
            try:
                self._peek_cache = next(it)
            except StopIteration:
                raise ValueError("empty row source") from None
            finally:
                close = getattr(it, "close", None)
                if close is not None:
                    close()
        return self._peek_cache

    def set_span_anchor(self, anchor: Any) -> None:
        """Tile-span parent hook: run_tileplane hands the span current
        at pass START here, on the caller's thread, BEFORE any pipeline
        thread starts — a source that records its own `tile_parse`
        spans from parse workers (parallel/ingest.ShardedSource)
        parents them to the same anchor as tile_copy/tile_compute.
        Default: ignore."""


class ArraySource(RowSource):
    """Chunks sliced off resident host arrays (numpy views — no copies):
    the compatibility shim that lets `stream_stats(X, y, w)`-style callers
    ride the pipeline unchanged."""

    def __init__(self, *arrays: Any, chunk_rows: Optional[int] = None):
        self.arrays = [np.asarray(a) for a in arrays]
        self.n_rows = int(self.arrays[0].shape[0])
        for a in self.arrays:
            if a.shape[0] != self.n_rows:
                raise ValueError("row-count mismatch across source arrays")
        self.chunk_rows = int(chunk_rows) if chunk_rows else None

    def chunks(self) -> Iterator[Tuple[np.ndarray, ...]]:
        c = self.chunk_rows or self.n_rows
        for s in range(0, self.n_rows, c):
            yield tuple(a[s:s + c] for a in self.arrays)


class IterSource(RowSource):
    """Chunks from a factory of fresh iterators (generators over files,
    sockets, record decoders...)."""

    def __init__(self, factory: Callable[[], Iterable[Tuple[np.ndarray, ...]]],
                 n_rows: Optional[int] = None):
        self.factory = factory
        self.n_rows = n_rows

    def chunks(self) -> Iterator[Tuple[np.ndarray, ...]]:
        for chunk in self.factory():
            yield tuple(np.asarray(a) for a in chunk)


class PaddedSource(RowSource):
    """A source padded to exactly `n_target` rows with zero rows.

    The multi-host streamed pass needs every process to emit the SAME
    number of tiles — the tile step's psum is a collective, so a process
    running out of rows one tile early would wedge the whole pod in a
    reduction its peers never join. Each process wraps its (uneven)
    local stripe in a PaddedSource sized to the pod-uniform per-process
    row count (multihost.row_layout): padded rows are zeros, so the
    zero-weight convention keeps them inert in every statistic. The
    inner source must own at least one row (its first chunk is the
    shape template for the padding)."""

    def __init__(self, inner: RowSource, n_target: int):
        self.inner = inner
        self.n_target = int(n_target)
        self.n_rows = int(n_target)

    def chunks(self) -> Iterator[Tuple[np.ndarray, ...]]:
        seen = 0
        template: Optional[Tuple[np.ndarray, ...]] = None
        for chunk in self.inner.chunks():
            if template is None:
                template = chunk
            seen += chunk[0].shape[0]
            if seen > self.n_target:
                raise ValueError(
                    f"PaddedSource: inner source produced {seen} rows, "
                    f"more than the layout's {self.n_target}")
            yield chunk
        if seen < self.n_target:
            if template is None:
                raise ValueError("PaddedSource: empty inner source — "
                                 "every process must own at least one "
                                 "row (one file of its stripe)")
            miss = self.n_target - seen
            yield tuple(np.zeros((miss,) + tuple(a.shape[1:]), a.dtype)
                        for a in template)

    def peek(self) -> Tuple[np.ndarray, ...]:
        return self.inner.peek()

    def set_span_anchor(self, anchor: Any) -> None:
        self.inner.set_span_anchor(anchor)


def reader_row_source(read_records: Callable[[], Iterable[Dict[str, Any]]],
                      row_fn: Callable[[Dict[str, Any]],
                                       Sequence[Sequence[float]]],
                      batch_records: int = 4096,
                      n_rows: Optional[int] = None) -> RowSource:
    """The chunked `row-source -> numpy tile` adapter over the record
    readers (readers/avro.read_avro_file, readers/readers.CSVReader.read,
    streaming readers): `read_records()` starts a fresh record iteration;
    `row_fn(record)` maps one record to a tuple of per-array row values
    (e.g. `(x_row [d], y, w)`). Records buffer `batch_records` at a time
    into float32 chunks — the only host buffering between disk and the
    tile assembly."""

    def factory():
        buf: List[Sequence[Any]] = []

        def flush():
            cols = list(zip(*buf))
            return tuple(np.asarray(np.stack(c) if np.ndim(c[0]) else c,
                                    dtype=np.float32) for c in cols)

        for rec in read_records():
            buf.append(tuple(row_fn(rec)))
            if len(buf) >= batch_records:
                yield flush()
                buf = []
        if buf:
            yield flush()

    return IterSource(factory, n_rows=n_rows)


# -- fixed-shape re-tiling ---------------------------------------------------

def iter_fixed_tiles(source: RowSource, tile_rows: int,
                     track: Optional["TilePlaneStats"] = None
                     ) -> Iterator[Tuple[Tuple[np.ndarray, ...], int]]:
    """Re-slice a chunk stream into fixed `[tile_rows, ...]` numpy tiles,
    zero-padding the ragged tail; yields `(tile_arrays, n_valid)`.

    Synchronous — this is the shared assembly used by the producer thread
    AND by the legacy (TMOG_TILEPLANE=0) loops, so tile content is
    bit-identical on both paths. Zero padding keeps padded rows inert
    under the repo-wide zero-weight convention (w rides the source, so
    padding w with zeros IS the mask). At most one tile of rows is owned
    here at any time (`track.peak_host_rows` proves the <= 2-tile bound
    together with the chunk in hand)."""
    pend: List[Tuple[np.ndarray, ...]] = []
    pend_rows = 0
    narr = None

    def pop_tile() -> Tuple[Tuple[np.ndarray, ...], int]:
        nonlocal pend, pend_rows
        take, have = [], 0
        while pend and have < tile_rows:
            chunk = pend.pop(0)
            r = chunk[0].shape[0]
            if have + r <= tile_rows:
                take.append(chunk)
                have += r
            else:
                cut = tile_rows - have
                take.append(tuple(a[:cut] for a in chunk))
                pend.insert(0, tuple(a[cut:] for a in chunk))
                have = tile_rows
        pend_rows -= have
        parts = list(zip(*take))
        tile = []
        for ai in range(narr):
            arr = parts[ai][0] if len(parts[ai]) == 1 \
                else np.concatenate(parts[ai], axis=0)
            arr = np.ascontiguousarray(arr, dtype=np.float32)
            if arr.shape[0] < tile_rows:
                pad = [(0, tile_rows - arr.shape[0])] \
                    + [(0, 0)] * (arr.ndim - 1)
                arr = np.pad(arr, pad)
            tile.append(arr)
        return tuple(tile), have

    for chunk in source.chunks():
        if narr is None:
            narr = len(chunk)
        pend.append(chunk)
        pend_rows += chunk[0].shape[0]
        if track is not None:
            # single-writer: only the tile assembly (producer thread)
            # writes this; readers run after the producer joined
            # tmoglint: disable=THR001  read happens-after join
            track.peak_host_rows = max(track.peak_host_rows, pend_rows)
        while pend_rows >= tile_rows:
            yield pop_tile()
    while pend_rows > 0:
        yield pop_tile()


# -- the pipeline ------------------------------------------------------------

class TilePlaneStats:
    """Per-pass pipeline telemetry (mutable; filled as the pass runs)."""

    def __init__(self, tile_rows: int, label: str, prefetch: int = 1):
        self.label = label
        self.tile_rows = int(tile_rows)
        self.prefetch_depth = int(prefetch)
        self.tiles = 0
        self.rows = 0
        #: max host rows buffered in the tile assembly at any instant —
        #: the "X never materialized" proof: <= 2 * tile_rows by
        #: construction (one tile being assembled + one chunk in hand)
        self.peak_host_rows = 0
        self.copy_seconds = 0.0
        self.compute_seconds = 0.0
        self.wall_seconds = 0.0
        self.overlapped = None  # True when traced copy/compute windows met

    def to_json(self) -> Dict[str, Any]:
        return {"label": self.label, "tiles": self.tiles, "rows": self.rows,
                "tile_rows": self.tile_rows,
                "prefetch_depth": self.prefetch_depth,
                "peak_host_rows": self.peak_host_rows,
                "copy_seconds": round(self.copy_seconds, 6),
                "compute_seconds": round(self.compute_seconds, 6),
                "wall_seconds": round(self.wall_seconds, 6),
                "overlapped": self.overlapped}


class _Stop(Exception):
    pass


_SENTINEL = object()


def _device_put_tile(tile, shardings):
    """Land one host tile on the mesh. Single-host shardings are a plain
    device_put; a sharding spanning multiple PROCESSES means `tile` holds
    only THIS process's rows of the global tile, so the global array is
    assembled via make_array_from_process_local_data — each host's rows
    land on its own devices and never cross the wire (the cross-host
    traffic is the psum in the step, not the copy). Dims sharded over the
    batch axis scale by the process count; replicated dims do not."""
    import jax

    if shardings is None:
        return tuple(jax.device_put(a) for a in tile)
    out = []
    for a, s in zip(tile, shardings):
        if getattr(s, "is_fully_addressable", True):
            out.append(jax.device_put(a, s))
        else:
            pc = len({d.process_index
                      for d in np.asarray(s.mesh.devices).ravel()})
            gshape = list(a.shape)
            for i, name in enumerate(s.spec):
                if name is not None and i < len(gshape):
                    gshape[i] = gshape[i] * pc
            out.append(jax.make_array_from_process_local_data(
                s, np.ascontiguousarray(a), tuple(gshape)))
    return tuple(out)


def _producer(source: RowSource, tile_rows: int, q: "queue.Queue",
              copy_slot: threading.Semaphore, stop: threading.Event,
              stats: TilePlaneStats, shardings: Optional[Sequence[Any]],
              traced: bool, anchor=None) -> None:
    """Producer-thread body: assemble fixed tiles, device_put tile k+1
    while the consumer computes tile k, record tile_copy spans (anchored
    to the span current at pass START — the consumer thread's transient
    stage spans open and close concurrently and must not adopt them).

    `copy_slot` (prefetch-depth tokens, each released when the consumer
    DEQUEUES a tile) gates each device_put: at most `depth` tiles are
    copied-but-unconsumed while one computes, so in-flight device tiles
    are bounded at depth+1 — the footprint contract the TMOG_TILE_MB
    sizing guidance promises (depth 1 = the classic two-in-flight
    double buffering)."""
    import jax

    from ..utils.metrics import collector
    try:
        k = 0
        for tile, n_valid in iter_fixed_tiles(source, tile_rows, stats):
            acquired = False
            while not stop.is_set():
                if copy_slot.acquire(timeout=0.1):
                    acquired = True
                    break
            if not acquired:
                raise _Stop
            t0 = time.perf_counter()
            dev = _device_put_tile(tile, shardings)
            if traced:
                # fence so the span measures the COPY, not the enqueue;
                # blocks only this producer thread — the consumer keeps
                # computing tile k-1 concurrently, which is exactly the
                # overlap the span pair exists to expose
                jax.block_until_ready(dev)
                dur = time.perf_counter() - t0
                # producer-owned field; read only after th.join()
                # tmoglint: disable=THR001  read happens-after join
                stats.copy_seconds += dur
                collector.trace.add_complete(
                    "tile_copy", "tile", dur, parent_span=anchor,
                    tile=k, rows=int(n_valid), label=stats.label,
                    bytes=int(sum(a.nbytes for a in tile)))
            while not stop.is_set():
                try:
                    q.put((dev, n_valid, k), timeout=0.1)
                    break
                except queue.Full:
                    continue
            k += 1
        q.put(_SENTINEL)
    except _Stop:
        pass
    except BaseException as e:  # surfaced on the consumer thread
        q.put(("__error__", e))


def run_tileplane(source: RowSource, step: Callable[..., Any], carry0: Any,
                  *, tile_rows: int, label: str = "tileplane",
                  first_tile: Optional[Callable[..., Any]] = None,
                  sink: Optional[Callable[[np.ndarray, int], None]] = None,
                  shardings: Optional[Sequence[Any]] = None,
                  prefetch: Optional[int] = None
                  ) -> Tuple[Any, TilePlaneStats]:
    """ONE double-buffered pass of `source` through a fixed-shape jitted
    `step`, returning the final DEVICE carry and the pass stats.

    `prefetch` is the ring depth — how many tiles the producer may copy
    ahead of the consumer (None resolves tile_prefetch_depth():
    TMOG_TILE_PREFETCH, default 1). Depth changes device footprint
    ((depth+1) tiles + carry) and overlap, never tile boundaries, so
    the carry is bit-identical at any depth.

    step(carry, *tile_arrays) -> carry, or -> (carry, out_tile) when
    `sink` is given (out tiles are fetched with a one-tile lag and handed
    to `sink(np_out, n_valid)` so the D2H fetch of tile k overlaps tile
    k+1's compute). The consumer owns the jit and its donate_argnums
    (carry + tile args), which is what keeps "one executable per
    (consumer, tile shape)" under the consumer's control. `first_tile`
    (carry, *tile_arrays) -> carry runs once on tile 0 BEFORE its step —
    e.g. the stats engine derives its Gram shift from the first tile
    there, on device, instead of a separate host pre-pass over the same
    rows."""
    from ..utils.metrics import collector

    traced = bool(collector.enabled)
    anchor = collector.trace.current() if traced else None
    depth = max(1, int(prefetch)) if prefetch else tile_prefetch_depth()
    stats = TilePlaneStats(tile_rows, label, prefetch=depth)
    # anchor handed over BEFORE any pipeline thread exists: a sharded
    # source's parse workers parent their tile_parse spans to the same
    # span the copy/compute spans use
    source.set_span_anchor(anchor)
    t_pass = time.perf_counter()
    multiproc = bool(shardings) and any(
        not getattr(s, "is_fully_addressable", True) for s in shardings)
    if not tileplane_enabled() or multiproc:
        # kill switch: the SAME pass, fully synchronous on the caller's
        # thread — no producer thread, no queue, no copy/compute overlap.
        # Multi-process shardings ALWAYS take this path: landing tile k+1
        # on the producer thread while the step's cross-process gloo
        # collectives run tile k corrupts the CPU client's heap on this
        # jaxlib — the pod pays serialized copy/compute for correctness.
        return _run_sync(source, step, carry0, tile_rows=tile_rows,
                         stats=stats, first_tile=first_tile, sink=sink,
                         shardings=shardings, traced=traced,
                         anchor=anchor, t_pass=t_pass)
    q: "queue.Queue" = queue.Queue(maxsize=depth)
    # `depth` copy slots, each released when a tile is DEQUEUED: while
    # tile k computes, tiles k+1..k+depth may be copied ahead
    copy_slot = threading.Semaphore(depth)
    stop = threading.Event()
    th = threading.Thread(
        target=_producer, args=(source, tile_rows, q, copy_slot, stop,
                                stats, shardings, traced, anchor),
        name=f"tileplane-{label}", daemon=True)
    th.start()

    import jax

    carry = carry0
    consumer = _Consumer(step, first_tile, sink, stats, traced, anchor,
                         carry0)
    try:
        while True:
            item = q.get()
            if item is _SENTINEL:
                break
            if isinstance(item, tuple) and len(item) == 2 \
                    and item[0] == "__error__":
                raise item[1]
            dev, n_valid, k = item
            copy_slot.release()  # tile accepted: producer may copy k+1
            consumer.feed(dev, n_valid, k)
        consumer.flush()
    finally:
        stop.set()
        # drain so a producer blocked on put/acquire observes the flag
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass
        th.join(timeout=30.0)
    return consumer.carry, _finish_pass(stats, traced, t_pass)


class _Consumer:
    """Per-tile step/sink/span logic, shared verbatim by the threaded
    consumer loop and the kill-switch synchronous fallback."""

    def __init__(self, step, first_tile, sink, stats: TilePlaneStats,
                 traced: bool, anchor, carry0, multiproc: bool = False):
        self.step = step
        self.first_tile = first_tile
        self.sink = sink
        self.stats = stats
        self.traced = traced
        self.anchor = anchor
        self.carry = carry0
        self.multiproc = multiproc
        self._pending: Optional[Tuple[Any, int]] = None

    def feed(self, dev, n_valid: int, k: int) -> None:
        import jax

        from ..utils.metrics import collector
        t0 = time.perf_counter()
        if k == 0 and self.first_tile is not None:
            self.carry = self.first_tile(self.carry, *dev)
            # fence: the step below DONATES these tile buffers; the
            # first-tile hook must have consumed them first (once per
            # pass — not a per-tile sync)
            jax.block_until_ready(self.carry)
        out = self.step(self.carry, *dev)
        if self.sink is not None:
            self.carry, out_tile = out
            if self._pending is not None:
                prev, prev_n = self._pending
                self.sink(np.asarray(prev)[:prev_n], prev_n)
            self._pending = (out_tile, n_valid)
        else:
            self.carry = out
        if self.traced:
            jax.block_until_ready(self.carry)
            dur = time.perf_counter() - t0
            # consumer-owned field (caller's thread); the producer
            # never touches compute-side stats
            # tmoglint: disable=THR001  single-owner, read post-join
            self.stats.compute_seconds += dur
            collector.trace.add_complete(
                "tile_compute", "tile", dur, parent_span=self.anchor,
                tile=k, rows=int(n_valid), label=self.stats.label)
            if self.multiproc:
                # the step's cross-process psum merge is inside this
                # already-measured block window — attribute it to the
                # pod collective ledger without a second clock read
                from . import podtrace
                podtrace.note_collective(
                    "tile_merge", dur, tile=k, rows=int(n_valid),
                    label=self.stats.label)
        # tmoglint: disable=THR001  consumer-owned (see compute_seconds)
        self.stats.tiles += 1
        # tmoglint: disable=THR001  consumer-owned (see compute_seconds)
        self.stats.rows += int(n_valid)

    def flush(self) -> None:
        if self._pending is not None:
            prev, prev_n = self._pending
            self.sink(np.asarray(prev)[:prev_n], prev_n)
            self._pending = None


def _finish_pass(stats: TilePlaneStats, traced: bool,
                 t_pass: float) -> TilePlaneStats:
    from ..utils.metrics import collector

    # pass-end bookkeeping: runs on the consumer thread after the
    # producer joined (run_tileplane finally)
    # tmoglint: disable=THR001  single-owner, read post-join
    stats.wall_seconds = time.perf_counter() - t_pass
    if traced:
        # tmoglint: disable=THR001  same happens-after-join ownership
        stats.overlapped = stats.copy_seconds + stats.compute_seconds \
            > stats.wall_seconds * 1.001
        collector.event(
            "tileplane_pass", label=stats.label, tiles=stats.tiles,
            rows=stats.rows, tile_rows=stats.tile_rows,
            prefetch_depth=stats.prefetch_depth,
            peak_host_rows=stats.peak_host_rows,
            copy_seconds=round(stats.copy_seconds, 6),
            compute_seconds=round(stats.compute_seconds, 6),
            wall_seconds=round(stats.wall_seconds, 6))
    return stats


def _run_sync(source: RowSource, step, carry0, *, tile_rows: int,
              stats: TilePlaneStats, first_tile, sink, shardings,
              traced: bool, anchor, t_pass: float
              ) -> Tuple[Any, TilePlaneStats]:
    """TMOG_TILEPLANE=0 fallback: the identical pass on ONE thread —
    same tiles (shared assembly), same step/sink/span semantics, no
    background producer, no copy/compute overlap."""
    import jax

    from ..utils.metrics import collector
    multiproc = bool(shardings) and any(
        not getattr(s, "is_fully_addressable", True) for s in shardings)
    consumer = _Consumer(step, first_tile, sink, stats, traced, anchor,
                         carry0, multiproc=multiproc)
    for k, (tile, n_valid) in enumerate(
            iter_fixed_tiles(source, tile_rows, stats)):
        t0 = time.perf_counter()
        dev = _device_put_tile(tile, shardings)
        if traced:
            jax.block_until_ready(dev)
            dur = time.perf_counter() - t0
            stats.copy_seconds += dur
            collector.trace.add_complete(
                "tile_copy", "tile", dur, parent_span=anchor, tile=k,
                rows=int(n_valid), label=stats.label,
                bytes=int(sum(a.nbytes for a in tile)))
        consumer.feed(dev, n_valid, k)
    consumer.flush()
    return consumer.carry, _finish_pass(stats, traced, t_pass)


# -- generic pipelined producer/consumer (record-batch consumers) ------------

def pipelined(produce: Iterable[Any], *, label: str = "tileplane",
              depth: Optional[int] = None) -> Iterator[Any]:
    """Run `produce` (any host-side iterable — e.g. records -> fixed-size
    Dataset tiles for bulk scoring) on a background thread with a
    `depth`-deep queue, yielding its items on the caller's thread.

    The array pipeline above is for numeric tile math; this is the same
    prefetch ring for consumers whose 'tile' is a host object (the
    scoring path assembles a Dataset per record tile here while the
    device scores the previous one). Items are produced at most `depth`
    ahead (None resolves tile_prefetch_depth(); the default of 1
    is the old one-ahead double buffering)."""
    d = max(1, int(depth)) if depth else tile_prefetch_depth()
    q: "queue.Queue" = queue.Queue(maxsize=d)
    stop = threading.Event()

    def body():
        try:
            for item in produce:
                while not stop.is_set():
                    try:
                        q.put((None, item), timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return
            q.put(_SENTINEL)
        except BaseException as e:
            q.put((e, None))

    th = threading.Thread(target=body, name=f"tileplane-{label}",
                          daemon=True)
    th.start()
    try:
        while True:
            item = q.get()
            if item is _SENTINEL:
                return
            err, value = item
            if err is not None:
                raise err
            yield value
    finally:
        stop.set()
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass
        th.join(timeout=30.0)
