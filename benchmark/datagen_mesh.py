"""The benchmark's inputs for a cell on several chips, made from --seed:
`datagen.device_matrix`'s population (standard-normal X, a logistic label
on the fixed `truth_beta`) drawn shard by shard ON the chips, each chip its
own rows from `fold_in(PRNGKey(seed), shard)`. The whole matrix is never on
one chip or on the host; the same seed and the same number of shards give
the same data.

The layout is the deployment's, stated in the configuration file: rows
sharded over the mesh axis `batch` of a (`batch`, `model`) mesh whose
`model` axis is 1, every chip the same number of rows.
"""
from __future__ import annotations

import numpy as np

from benchmark.datagen import truth_beta

BATCH_AXIS, MODEL_AXIS = "batch", "model"
#: rows a chip draws at once: the float32 normals of one block are the
#: generator's only temporary (128 MiB at 64 columns)
BLOCK_ROWS = 1 << 19


def row_mesh(devices):
    """The (`batch`, `model`) mesh of the deployment over `devices`."""
    from jax.sharding import Mesh
    return Mesh(np.array(list(devices)).reshape(len(devices), 1),
                (BATCH_AXIS, MODEL_AXIS))


def sharded_matrix(rows: int, cols: int, dtype: str, seed: int, devices):
    """(X [rows, cols] in `dtype`, y [rows] float32), both row-sharded over
    `devices`; `rows` divides by their number (rows are never padded)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    shards = len(devices)
    if rows % shards:
        raise ValueError(f"{rows} rows do not divide over {shards} chips")
    local = rows // shards
    # the largest block that divides a chip's rows: no ragged tail to cut
    block = next(b for b in range(min(BLOCK_ROWS, local), 0, -1)
                 if local % b == 0)
    n_blocks = local // block
    mesh = row_mesh(devices)
    beta = jnp.asarray(truth_beta(cols))

    def one_block(key):
        kx, ku = jax.random.split(key)
        X = jax.random.normal(kx, (block, cols), jnp.float32)
        p = jax.nn.sigmoid(X @ beta)
        y = (jax.random.uniform(ku, (block,)) < p).astype(jnp.float32)
        return X.astype(jnp.dtype(dtype)), y

    def shard(key):
        key = jax.random.fold_in(key, jax.lax.axis_index(BATCH_AXIS))
        keys = jax.vmap(lambda b: jax.random.fold_in(key, b))(
            jnp.arange(n_blocks))
        X, y = jax.lax.map(one_block, keys)
        return X.reshape(local, cols), y.reshape(local)

    # tmoglint: disable=TRC001  called once in a process
    gen = jax.jit(jax.shard_map(
        shard, mesh=mesh, in_specs=P(),
        out_specs=(P(BATCH_AXIS, None), P(BATCH_AXIS)), check_vma=False))
    X, y = gen(jax.random.PRNGKey(seed))
    jax.block_until_ready((X, y))
    assert X.sharding.is_equivalent_to(
        NamedSharding(mesh, P(BATCH_AXIS, None)), 2)
    return X, y
