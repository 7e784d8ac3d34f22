"""Multi-host (DCN) scale-out entry points.

The reference delegates cross-machine execution to Spark: the driver ships
closures to executors, readers produce per-partition rows, reduceByKey
shuffles over the cluster network (SURVEY §2.9). The TPU-native analogue
is JAX multi-process SPMD: every host runs this same program, owns a slice
of the global row axis, and XLA inserts the collectives (psum over ICI
within a slice, DCN across slices) wherever a sharded reduction appears —
the Gram matrices, gradient histograms and metric sums of the sweep
kernels need no code changes.

This module holds the process-level plumbing that Spark's driver/executor
split used to provide:

- `initialize()`         — jax.distributed bring-up (coordinator + rank
                           from args, TMOG_COORD_ADDR / TMOG_PROC_COUNT /
                           TMOG_PROC_ID, or the JAX_COORDINATOR_ADDRESS /
                           JAX_NUM_PROCESSES / JAX_PROCESS_ID spellings;
                           CPU pods ride jax's default gloo collectives);
- `global_mesh()`        — a Mesh over ALL processes' devices;
- `padded_global_rows(n)`— the device-count row multiple arrays pad to;
- `process_row_range(n)` — which REAL rows of a global dataset this host
                           loads (the reader-partition analogue: each host
                           reads only its slice; padding is all-tail);
- `host_local_rows(...)` — assemble a GLOBAL row-sharded jax.Array from
                           this host's local rows (jax.make_array_from_
                           process_local_data); padded rows carry
                           pad_value and are masked by `mesh.row_mask`
                           exactly like the single-host sweep padding
                           (zero weight = inert in every reduction);
- `stripe_paths(...)`    — this process's contiguous stripe of the
                           deterministic (mtime, path) file listing, so
                           each host opens ONLY its own shard files;
- `row_layout(...)` /
  `host_local_block(...)`— the uneven-block generalization the file-
                           striped ingest needs: per-process real row
                           counts are allgathered once, every block pads
                           to one uniform per-process length, and the
                           engines' weight vectors zero the padding;
- `fetch_local(x)` /
  `fetch_global(x)`      — the two documented host fetches of a
                           row-sharded global array: local rows only
                           (never crosses a process boundary) vs the
                           all-gathered global view (SHD005's fold).

Single-process use degrades to the local mesh: every helper works
unchanged with one process, which is how the unit tests cover it.
"""
from __future__ import annotations

import os
from typing import NamedTuple, Optional, Sequence, Tuple

import numpy as np

from .mesh import BATCH_AXIS, make_mesh


_initialized = False


def multihost_enabled() -> bool:
    """TMOG_MULTIHOST: master opt-in for environment-driven multi-host
    behavior — reader-level file striping and workflow auto-initialize.
    Explicit API use (the launch helper, the 2proc tests) does not need
    it; the knob exists so a single launch script can flip a whole
    pipeline run without touching call sites."""
    v = os.environ.get("TMOG_MULTIHOST", "").strip().lower()
    return v not in ("", "0", "false", "off", "no")


def _env_first(*names: str) -> str:
    for n in names:
        v = os.environ.get(n)
        if v:
            return v
    return ""


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Bring up jax.distributed; single-process calls are safe no-ops.

    Arguments fall back to TMOG_COORD_ADDR / TMOG_PROC_COUNT /
    TMOG_PROC_ID, then the JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
    JAX_PROCESS_ID spellings. An explicit coordinator with an unknown
    process count raises (silently degrading a requested distributed run
    to one process would compute per-host-only results). Only a REAL
    bring-up latches: an early no-arg call does not block a later
    configured one."""
    global _initialized
    if _initialized:
        return
    explicit = coordinator_address is not None
    coordinator_address = coordinator_address or _env_first(
        "TMOG_COORD_ADDR", "JAX_COORDINATOR_ADDRESS")
    if num_processes is None:
        num_processes = int(_env_first("TMOG_PROC_COUNT",
                                       "JAX_NUM_PROCESSES") or 0)
    if process_id is None:
        process_id = int(_env_first("TMOG_PROC_ID",
                                    "JAX_PROCESS_ID") or 0)
    if not coordinator_address:
        return  # single-process; a later configured call may still init
    if num_processes <= 0:
        raise ValueError(
            "initialize: coordinator_address given but num_processes "
            "unknown — pass it or set TMOG_PROC_COUNT/JAX_NUM_PROCESSES")
    if num_processes == 1 and not explicit:
        return
    import jax
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    _initialized = True
    try:
        # pod flight recorder: per-rank TraceTree/EventLog + heartbeat
        # into TMOG_PODTRACE_DIR/rank-<k>/ (no-op unless TMOG_PODTRACE)
        from . import podtrace
        podtrace.start(process_id=int(process_id),
                       processes=int(num_processes))
    except Exception:
        pass  # telemetry must never break distributed bring-up
    try:
        from ..utils.metrics import collector
        if collector.enabled:
            collector.event(
                "multihost_init", processes=int(num_processes),
                process_id=int(process_id),
                coordinator=str(coordinator_address),
                devices=len(jax.devices()),
                local_devices=int(jax.local_device_count()))
    except Exception:
        pass  # telemetry must never break distributed bring-up


def finalize() -> None:
    """Explicit jax.distributed teardown (idempotent no-op when never
    initialized). Pod children call it before exiting: the atexit-time
    teardown has been observed to race gloo's background threads on
    rare exits and wedge the interpreter — which the launch helper then
    has to SIGKILL. An explicit shutdown while every peer is still
    alive is instant."""
    global _initialized
    if not _initialized:
        return
    try:
        # save this rank's flight-recorder artifacts while every peer
        # is still alive (a rank that dies before here leaves a torn
        # dir, which merge_pod degrades to a partial report)
        from . import podtrace
        podtrace.finish()
    except Exception:
        pass
    import jax
    try:
        jax.distributed.shutdown()
    except Exception:
        pass
    _initialized = False


def process_count() -> int:
    import jax
    return jax.process_count()


def process_index() -> int:
    import jax
    return jax.process_index()


def is_multiprocess() -> bool:
    return process_count() > 1


def global_mesh(n_model: int = 1):
    """(batch, model) Mesh over every device of every process.

    The batch axis spans hosts: row-sharded arrays then reduce over DCN
    between slices exactly where the reference's Spark shuffle sat."""
    import jax

    n_dev = len(jax.devices())
    if n_dev % n_model:
        raise ValueError(f"{n_dev} devices not divisible by "
                         f"model axis {n_model}")
    return make_mesh(n_batch=n_dev // n_model, n_model=n_model)


def padded_global_rows(n_rows: int) -> int:
    """Global row counts pad up to a device-count multiple (row-sharded
    dims must divide the batch axis; mesh.row_mask masks the tail)."""
    import jax
    nd = len(jax.devices())
    return -(-n_rows // nd) * nd


def process_row_range(n_rows: int) -> Tuple[int, int]:
    """[start, stop) of the REAL rows this process loads.

    The padded row space splits uniformly across processes (equal device
    counts per host), so real rows fill processes in order and all padding
    lands on the last process's tail — the global array is real rows
    first, padding last, matching mesh.row_mask."""
    import jax
    per = padded_global_rows(n_rows) // jax.process_count()
    i = jax.process_index()
    return min(i * per, n_rows), min((i + 1) * per, n_rows)


def fetch_global(x) -> np.ndarray:
    """np.ndarray of a GLOBAL (possibly row-sharded) jax.Array, safe
    under multi-process SPMD.

    ``np.asarray(x)`` on a multi-host global array either raises (rows
    living on another host are not addressable) or — worse, via
    addressable-shard paths — silently yields only THIS host's rows, so
    a host-side ``np.sum`` over it computes a per-host total that looks
    global. That is the SHD005 bug class (tmoglint flags it statically:
    docs/static_analysis.md). This helper is the documented cross-process
    fold: single-process it is a plain ``asarray``; multi-process it
    all-gathers the array so every host sees every row. Prefer reducing
    ON DEVICE (psum inside the sharded program) when you only need the
    aggregate — fetching all rows to every host is the expensive path,
    and when only THIS host's rows are needed, `fetch_local` below never
    crosses a process boundary at all.
    """
    if process_count() == 1:
        return np.asarray(x)
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(x, tiled=True))


def fetch_local(x, axis: int = 0) -> np.ndarray:
    """THIS process's rows of a row-sharded global array, as one host
    block — the cheap sibling of `fetch_global` for callers that only
    need host-local rows (per-host previews, telemetry, the local half
    of a two-stage merge). Never moves data across processes: it reads
    only addressable shards, dedupes model-axis replicas by row offset,
    and concatenates in global row order. Single-process (or plain
    numpy input) it is exactly ``asarray``. Contract: the array is
    sharded (or replicated) along `axis` only — axis 0 is the engines'
    row layout; axis 1 is the fold-mask / margins layout [F, n]."""
    import jax
    if not isinstance(x, jax.Array) or process_count() == 1:
        return np.asarray(x)
    by_offset = {}
    for s in x.addressable_shards:
        start = 0
        if len(s.index) > axis and isinstance(s.index[axis], slice):
            start = int(s.index[axis].start or 0)
        by_offset.setdefault(start, s)
    blocks = [np.asarray(by_offset[k].data) for k in sorted(by_offset)]
    if not blocks:
        shape = list(x.shape)
        shape[axis] = 0
        return np.empty(tuple(shape), x.dtype)
    return blocks[0] if len(blocks) == 1 else \
        np.concatenate(blocks, axis)


def stripe_paths(paths: Sequence, index: Optional[int] = None,
                 count: Optional[int] = None) -> list:
    """This process's stripe of a deterministic path listing (readers
    pin (mtime, path) order — readers/streaming.snapshot_paths).

    CONTIGUOUS blocks, not round-robin: the concatenation of the
    stripes in process order preserves the single-process global file
    (and therefore row) order, which keeps the 2-process fit
    bit-comparable with the 1-process fit. The remainder spreads over
    the first processes so block sizes differ by at most one."""
    paths = list(paths)
    if count is None:
        count = process_count()
    if index is None:
        index = process_index()
    base, rem = divmod(len(paths), count)
    start = index * base + min(index, rem)
    stop = start + base + (1 if index < rem else 0)
    return paths[start:stop]


def host_local_rows(local: np.ndarray, mesh, n_rows_global: int,
                    pad_value: float = 0.0):
    """Global row-sharded jax.Array from this host's local block.

    `local` must be exactly this process's `process_row_range(n_rows_global)`
    slice; the block pads to the uniform per-process length with
    `pad_value` rows (weight-0 semantics downstream — give padded rows
    zero sample weight via `mesh.row_mask(padded_global_rows(n), n)`).
    Returns an array of `padded_global_rows(n_rows_global)` rows."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    padded_total = padded_global_rows(n_rows_global)
    per = padded_total // jax.process_count()
    if local.shape[0] < per:
        pad = np.full((per - local.shape[0],) + tuple(local.shape[1:]),
                      pad_value, dtype=local.dtype)
        local = np.concatenate([local, pad], axis=0)
    spec = P(BATCH_AXIS, *([None] * (local.ndim - 1)))
    sharding = NamedSharding(mesh, spec)
    global_shape = (padded_total,) + tuple(local.shape[1:])
    return jax.make_array_from_process_local_data(
        sharding, np.ascontiguousarray(local), global_shape)


class RowLayout(NamedTuple):
    """Global row layout of UNEVEN per-process blocks.

    `process_row_range` assumes the caller can slice a known global
    dataset; the file-striped ingest path cannot — each process parses
    its own files and only then knows its real row count. `row_layout`
    allgathers those counts once, and every process pads its block to
    one uniform `per_process` length (a local-device-count multiple, as
    XLA's even sharding requires). Padded rows are inert downstream via
    `local_weights` (weight 0), exactly like single-host tail padding —
    so the union of real rows, and therefore every psum-merged
    sufficient statistic, matches the single-process fit regardless of
    where the padding sits."""

    counts: Tuple[int, ...]   # real rows per process, process order
    per_process: int          # uniform padded local block length

    @property
    def n_real(self) -> int:
        return int(sum(self.counts))

    @property
    def n_padded(self) -> int:
        return self.per_process * len(self.counts)

    def local_count(self, process: Optional[int] = None) -> int:
        i = process_index() if process is None else process
        return int(self.counts[i])

    def local_weights(self, process: Optional[int] = None) -> np.ndarray:
        """1.0 for this process's real rows, 0.0 for its padding."""
        w = np.zeros((self.per_process,), np.float32)
        w[: self.local_count(process)] = 1.0
        return w


def allgather_counts(n_local: int) -> Tuple[int, ...]:
    """Every process's value of a host integer, in process order (one
    tiny device allgather; single-process: just the value)."""
    if process_count() == 1:
        return (int(n_local),)
    from jax.experimental import multihost_utils
    g = multihost_utils.process_allgather(
        np.asarray([int(n_local)], np.int32))
    return tuple(int(v) for v in np.asarray(g).reshape(-1))


def row_layout(n_local: int, mesh) -> RowLayout:
    """The pod-wide RowLayout for this process's `n_local` real rows.

    COLLECTIVE: every process must call it (it allgathers the counts).
    The uniform block length is the max padded count, rounded up to this
    host's share of the mesh batch axis."""
    pc = process_count()
    from . import podtrace
    with podtrace.collective("row_layout", procs=pc, rows=int(n_local)):
        counts = allgather_counts(n_local)
    try:
        n_batch = int(dict(mesh.shape).get(BATCH_AXIS, 1))
    except Exception:
        n_batch = 1
    local_dev = max(1, n_batch // max(1, pc))
    per = -(-max(max(counts), 1) // local_dev) * local_dev
    return RowLayout(counts=counts, per_process=per)


def host_local_block(local: np.ndarray, mesh, layout: RowLayout,
                     pad_value: Optional[float] = 0.0, axis: int = 0):
    """Global batch-sharded jax.Array from this process's (possibly
    shorter) local block, padded to `layout.per_process` along `axis`
    (the batch-sharded dim; fold masks pass axis=1).

    `pad_value=None` repeats the last real row instead of a constant —
    the tree-binning semantics of `mesh.pad_rows_to_multiple` (synthetic
    values would shift quantile bins; duplicates barely do)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    local = np.asarray(local)
    n = local.shape[axis]
    if n > layout.per_process:
        raise ValueError(f"local block of {n} rows exceeds the layout's "
                         f"per-process length {layout.per_process}")
    if n < layout.per_process:
        pad_n = layout.per_process - n
        if pad_value is None and n > 0:
            pad = np.repeat(np.take(local, [n - 1], axis=axis),
                            pad_n, axis=axis)
        else:
            shape = list(local.shape)
            shape[axis] = pad_n
            pad = np.full(shape, 0.0 if pad_value is None else pad_value,
                          local.dtype)
        local = np.concatenate([local, pad], axis=axis)
    spec = [None] * local.ndim
    spec[axis] = BATCH_AXIS
    sharding = NamedSharding(mesh, P(*spec))
    gshape = list(local.shape)
    gshape[axis] = layout.n_padded
    return jax.make_array_from_process_local_data(
        sharding, np.ascontiguousarray(local), tuple(gshape))


def replicated_global(x, mesh):
    """Fully-replicated global array from an identical host value on
    every process. `jax.device_put` refuses shardings with
    non-addressable devices, so the multi-process path goes through
    make_array_from_process_local_data; single-process it is a plain
    replicated device_put. COLLECTIVE in the sense that every process
    must supply the same value (scalars, regs/alphas grids, fold
    counts) — divergent values would silently diverge the programs."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = np.asarray(x)
    sharding = NamedSharding(mesh, P())
    if process_count() == 1:
        return jax.device_put(x, sharding)
    return jax.make_array_from_process_local_data(
        sharding, x, tuple(x.shape))
