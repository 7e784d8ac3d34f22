"""Device mesh + sharding substrate.

The reference delegates distribution to Spark (partitioned RDDs + shuffle,
SURVEY §2.9). Here the equivalent is a named `jax.sharding.Mesh` with GSPMD
sharding annotations: feature-matrix rows ride the ``batch`` axis (Spark
partitions), CV-fold and hyperparameter-grid replication ride ``model``
(thread-pool parallelism of OpValidator.scala:318), and XLA inserts the
all-reduce/all-gather collectives over ICI/DCN that replace shuffle + Rabit.

Kernels written in pure jnp (the vmapped sweep, the exact metrics, the GLM
solvers' Gram reductions) get their distribution from input shardings alone:
GSPMD partitions them and inserts the collectives. Two kinds do not, and
carry an explicit `shard_map` form with its own psums (`build_shard_map`
below, proved by tmoglint SHD001-SHD005): the streamed row scans (the GLM
rounds and moments, the one-pass stats engine, the fused tree passes, the
fold program, the held-out metric pass), whose accumulators must merge once
a pass and not once a block, and every Pallas kernel, which Mosaic refuses
on a row-sharded operand and which therefore sees a chip's LOCAL rows
inside a `shard_map`.

A matrix that already lives row-sharded on the batch axis names its own
mesh (`resident_row_mesh`): the validators read it from the array and need
no `mesh=` argument.
"""
from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

BATCH_AXIS = "batch"
MODEL_AXIS = "model"

_active_mesh: Optional[Mesh] = None


def shard_vary(tree, axis_name):
    """Under shard_map's varying-manual-axes tracking a scan carry becomes
    batch-varying inside the body; the initial zeros must carry the same
    type. Shared by every sharded streaming kernel (GLM sweep, stats
    engine, trees)."""
    if axis_name is None:
        return tree
    return jax.lax.pcast(tree, axis_name, to="varying")


def build_shard_map(core, mesh, in_specs, out_specs):
    """shard_map with varying-manual-axes checking off: the accumulator
    psums inside the streaming kernels' `while` loops make every carry
    replicated by construction, which the checker cannot see.

    check_vma=False also means NOTHING at runtime verifies a replicated
    out_spec was actually psum-merged — and at 1 device per shard (every
    CI mesh) a forgotten psum is the identity. That contract is enforced
    statically instead: tmoglint SHD001-SHD005 resolve every
    build_shard_map/shard_map call site, bind the P(...) axis names, and
    prove each replicated out_spec reduced through the body's dataflow
    (docs/static_analysis.md)."""
    return jax.shard_map(core, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def resident_row_mesh(x) -> Optional[Mesh]:
    """The mesh a device-resident array is row-sharded over: `x` is a
    jax.Array under a NamedSharding whose rows ride BATCH_AXIS over more
    than one device, every other dimension whole. None for anything else
    (a host array, one device, a replicated or column-sharded array),
    which runs as it always has."""
    sh = getattr(x, "sharding", None)
    if not isinstance(x, jax.Array) or not isinstance(sh, NamedSharding):
        return None
    spec = tuple(sh.spec) + (None,) * (x.ndim - len(sh.spec))
    if not spec or spec[0] not in (BATCH_AXIS, (BATCH_AXIS,)) \
            or any(s is not None for s in spec[1:]):
        return None
    return sh.mesh if mesh_batch_count(sh.mesh) > 1 else None


def mesh_batch_count(mesh) -> int:
    """Devices on the batch axis (1 for None / degenerate meshes) — the
    single predicate sweep drivers use to decide whether a mesh context
    warrants the row-sharded fused route (models/trees)."""
    if mesh is None:
        return 1
    try:
        return int(dict(mesh.shape).get(BATCH_AXIS, 1))
    except Exception:
        return 1


def mesh_process_count(mesh) -> int:
    """Distinct processes owning the mesh's devices (1 for None / local
    meshes). The predicate the engine drivers use to pick the multi-host
    data landing (make_array_from_process_local_data) over the
    single-host one (device_put of the full array)."""
    if mesh is None:
        return 1
    try:
        return len({d.process_index
                    for d in np.asarray(mesh.devices).ravel()})
    except Exception:
        return 1


def mesh_is_multiprocess(mesh) -> bool:
    return mesh_process_count(mesh) > 1


def make_mesh(n_batch: Optional[int] = None, n_model: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    """Create a (batch, model) mesh over available devices.

    With jax.distributed initialized, `jax.devices()` is the GLOBAL
    device list in process order, so the batch axis (the row/data axis)
    spans hosts with each process's devices contiguous along it — the
    per-host device assignment `make_array_from_process_local_data`
    needs for a host's rows to land on its own devices. The model axis
    (the lane axis of the sweep) stays within a host at n_model <=
    local device count; the 2-D (data x lane) pod mesh of
    docs/performance.md is exactly this reshape."""
    devs = list(devices if devices is not None else jax.devices())
    if n_batch is None:
        n_batch = len(devs) // n_model
    use = devs[: n_batch * n_model]
    arr = np.array(use).reshape(n_batch, n_model)
    return Mesh(arr, (BATCH_AXIS, MODEL_AXIS))


def default_mesh() -> Mesh:
    global _active_mesh
    if _active_mesh is None:
        _active_mesh = make_mesh()
    return _active_mesh


@contextmanager
def use_mesh(mesh: Mesh):
    global _active_mesh
    prev = _active_mesh
    _active_mesh = mesh
    try:
        yield mesh
    finally:
        _active_mesh = prev


def batch_sharding(mesh: Optional[Mesh] = None, ndim: int = 2) -> NamedSharding:
    """Rows sharded over the batch axis; all other dims replicated."""
    mesh = mesh or default_mesh()
    spec = P(BATCH_AXIS, *([None] * (ndim - 1)))
    return NamedSharding(mesh, spec)


def replicated(mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh or default_mesh()
    return NamedSharding(mesh, P())


def sharded_along(mesh: Optional[Mesh], dim: int, ndim: int) -> NamedSharding:
    """Shard one dimension over the batch axis, others replicated (e.g.
    fold masks [F, n] shard dim=1)."""
    mesh = mesh or default_mesh()
    spec = [None] * ndim
    spec[dim] = BATCH_AXIS
    return NamedSharding(mesh, P(*spec))


def pad_rows_to_multiple(x: np.ndarray, multiple: int,
                         pad_value: Optional[float] = 0.0
                         ) -> Tuple[np.ndarray, int]:
    """Pad rows so the batch axis divides evenly across devices. Returns the
    padded array and the original row count (callers carry a weight/mask
    vector so padded rows never affect statistics). ``pad_value=None``
    repeats the LAST real row instead — for feature matrices feeding
    unweighted statistics (tree quantile binning), where synthetic values
    would shift the distribution but duplicates barely do."""
    n = x.shape[0]
    rem = n % multiple
    if rem == 0:
        return x, n
    pad = multiple - rem
    if pad_value is None:
        pad_block = np.repeat(np.asarray(x)[-1:], pad, axis=0)
    else:
        pad_block = np.full((pad,) + x.shape[1:], pad_value, dtype=x.dtype)
    return np.concatenate([x, pad_block], axis=0), n


def device_put_batch(x: np.ndarray, mesh: Optional[Mesh] = None,
                     pad: bool = True) -> Tuple[jax.Array, int]:
    """Host -> HBM with rows sharded on the batch axis.

    Returns (device array, true row count). When `pad`, rows are zero-padded
    to a multiple of the batch-axis size (XLA requires even sharding).
    """
    mesh = mesh or default_mesh()
    n_shards = mesh.shape[BATCH_AXIS]
    n = x.shape[0]
    if pad:
        x, n = pad_rows_to_multiple(np.asarray(x), n_shards)
    return jax.device_put(x, batch_sharding(mesh, ndim=x.ndim)), n


def row_mask(n_padded: int, n_true: int) -> np.ndarray:
    """1.0 for real rows, 0.0 for padding."""
    m = np.zeros((n_padded,), dtype=np.float32)
    m[:n_true] = 1.0
    return m
