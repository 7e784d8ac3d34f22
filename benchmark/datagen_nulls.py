"""The null-tracked cell's inputs, made from --seed on the device: what
upstream's `transmogrify()` hands a ModelSelector for a table of numeric
fields with holes. `RealVectorizer` (fillWithMean true,
TransmogrifierDefaults.TrackNulls true; here automl/vectorizers/numeric.py)
imputes a field's mean and appends its null indicator, so `raw_cols` fields
reach the selector as 2 x raw_cols columns: value, indicator, value,
indicator ...

The raw table: field j is LOC[j] + SCALE[j] * N(0, 1), SCALE a power of two
from 2^-4 to 2^4, 0.25 SCALE <= |LOC| <= 2 SCALE (never zero, so "fill with
0" is another answer), and is missing, completely at random, with
probability MISSING[j], log-spaced from 0.001 to 0.5 over the fields (fixed
numpy rng 123 draws and a fixed shuffle: every seed draws from one
population). It is made a block of rows at a time by ONE jitted program
(`_raw_program`: key, block index -> float32 values with NaN), which both
the device matrix and `raw_rows` run, so the host's copy of any raw rows is
the device's bit for bit.

The matrix, two passes over the blocks, nothing but a block on the host or
in float32 on the device: the fills (the mean of a field's observed entries
over ALL rows: per-block float32 sums of value - LOC, added up in float64 on
the host), then every block imputed, indicated, interleaved, cast and
written into the one [rows, 2 raw_cols] result in place.

The label is Bernoulli of a logistic over the 2 raw_cols columns
standardised by their POPULATION moments (closed form), with a fixed dense
truth on value AND indicator columns (numpy rng 123): missingness is
informative.
"""
from __future__ import annotations

import functools

import numpy as np

BLOCK_ROWS = 1 << 16


@functools.lru_cache(maxsize=None)
def population(raw_cols: int) -> dict:
    """LOC, SCALE, MISSING [raw_cols] float64 of the raw fields, and the
    population mean and std [2 raw_cols] of the vectorised columns (a value
    column keeps its mean under mean imputation and loses the missing
    share of its variance; an indicator is Bernoulli(MISSING))."""
    rng = np.random.default_rng(123)
    scale = 2.0 ** ((np.arange(raw_cols) * 5) % 9 - 4)
    loc = scale * rng.uniform(0.25, 2.0, raw_cols) \
        * rng.choice([-1.0, 1.0], raw_cols)
    missing = rng.permutation(
        10.0 ** np.linspace(np.log10(0.001), np.log10(0.5), raw_cols))
    mean = np.stack([loc, missing], axis=1).reshape(-1)
    std = np.sqrt(np.stack([(1.0 - missing) * scale ** 2,
                            missing * (1.0 - missing)], axis=1).reshape(-1))
    return {"loc": loc, "scale": scale, "missing": missing, "mean": mean,
            "std": std}


def truth(raw_cols: int, scale: float) -> np.ndarray:
    """beta [2 raw_cols] float64 on the population-STANDARDISED columns:
    standard-normal weights (numpy rng 123) times scale / sqrt(2 raw_cols),
    value and indicator columns alike."""
    rng = np.random.default_rng(123)
    return rng.normal(size=2 * raw_cols) * scale / np.sqrt(2 * raw_cols)


@functools.lru_cache(maxsize=None)
def _raw_program(raw_cols: int, block: int):
    """key, block index -> [block, raw_cols] float32, NaN where missing:
    the one program both `device_matrix` and `raw_rows` run. SCALE is a
    power of two, so loc + scale * z rounds once however it is fused."""
    import jax
    import jax.numpy as jnp

    pop = population(raw_cols)
    loc, scale, missing = (jnp.asarray(pop[k], jnp.float32)
                           for k in ("loc", "scale", "missing"))

    @jax.jit
    def raw_block(key, i):
        kz, km = jax.random.split(jax.random.fold_in(key, i))
        z = jax.random.normal(kz, (block, raw_cols), jnp.float32)
        gone = jax.random.uniform(km, (block, raw_cols)) < missing[None, :]
        return jnp.where(gone, jnp.nan, loc[None, :] + scale[None, :] * z)
    return raw_block


@functools.lru_cache(maxsize=None)
def _programs(raw_cols: int, block: int, truth_scale: float,
              truth_intercept: float):
    import jax
    import jax.numpy as jnp

    pop = population(raw_cols)
    loc = jnp.asarray(pop["loc"], jnp.float32)
    beta = truth(raw_cols, truth_scale) / pop["std"]
    b0 = truth_intercept - float((beta * pop["mean"]).sum())
    beta = jnp.asarray(beta, jnp.float32)

    @jax.jit
    def sums(raw):
        """Per field: the sum of value - LOC over the block's observed
        entries, and their count."""
        seen = ~jnp.isnan(raw)
        return (jnp.where(seen, raw - loc[None, :], 0.0).sum(0),
                seen.sum(0).astype(jnp.float32))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def write(X, y, raw, fills, key, i):
        """Block i imputed, indicated, interleaved and labelled, written
        into rows [i * block, (i + 1) * block) of X and y in place."""
        gone = jnp.isnan(raw)
        V = jnp.stack([jnp.where(gone, fills[None, :], raw),
                       gone.astype(jnp.float32)],
                      axis=2).reshape(block, 2 * raw_cols)
        p = jax.nn.sigmoid((V * beta[None, :]).sum(1) + b0)
        lab = jax.random.uniform(jax.random.fold_in(key, i), (block,)) < p
        return (jax.lax.dynamic_update_slice_in_dim(
                    X, V.astype(X.dtype), i * block, axis=0),
                jax.lax.dynamic_update_slice_in_dim(
                    y, lab.astype(jnp.float32), i * block, axis=0))

    return sums, write


def _block_rows(rows: int) -> int:
    """Rows a block: the largest divisor of `rows` that is at most
    BLOCK_ROWS (25 000 000 rows: 400 blocks of 62 500), so that the matrix
    is whole blocks and nothing is padded or cut."""
    steps = -(-rows // BLOCK_ROWS)
    while rows % steps:
        steps += 1
    return rows // steps


def _keys(seed: int):
    import jax
    return jax.random.split(jax.random.PRNGKey(seed))


def device_matrix(rows: int, raw_cols: int, dtype: str, seed: int, *,
                  truth_scale: float, truth_intercept: float) -> tuple:
    """(X [rows, 2 raw_cols] in `dtype`, y [rows] float32 0/1, fills
    [raw_cols] float64): the vectorised matrix and its label on the device,
    and the fills the device used (float32 values, as float64). Rows
    [i * block, (i + 1) * block) come from block i of the raw stream."""
    import jax
    import jax.numpy as jnp

    block = _block_rows(rows)
    raw_block = _raw_program(raw_cols, block)
    sums, write = _programs(raw_cols, block, float(truth_scale),
                            float(truth_intercept))
    k_raw, k_lab = _keys(seed)
    parts = [sums(raw_block(k_raw, i)) for i in range(rows // block)]
    total = np.sum([np.asarray(s, np.float64) for s, _ in parts], axis=0)
    count = np.sum([np.asarray(c, np.float64) for _, c in parts], axis=0)
    fills = (population(raw_cols)["loc"]
             + total / np.maximum(count, 1.0)).astype(np.float32)
    fills_d = jnp.asarray(fills)
    X = jnp.zeros((rows, 2 * raw_cols), jnp.dtype(dtype))
    y = jnp.zeros(rows, jnp.float32)
    for i in range(rows // block):
        X, y = write(X, y, raw_block(k_raw, i), fills_d, k_lab, i)
    jax.block_until_ready((X, y))
    return X, y, fills.astype(np.float64)


def raw_rows(rows: int, raw_cols: int, seed: int, start: int, stop: int
             ) -> np.ndarray:
    """Rows [start, stop) of the RAW table of `device_matrix(rows,
    raw_cols, ..., seed)` as the host holds a table: [stop - start,
    raw_cols] float64, NaN where missing. Runs the generator's own block
    program again, so the values are the device's bit for bit."""
    block = _block_rows(rows)
    raw_block = _raw_program(raw_cols, block)
    k_raw, _ = _keys(seed)
    first = start // block
    out = [np.asarray(raw_block(k_raw, i), np.float64)
           for i in range(first, -(-stop // block))]
    return np.concatenate(out)[start - first * block:stop - first * block]
