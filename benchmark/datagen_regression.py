"""The regression cell's inputs, made from --seed on the device: the matrix
of `binary-25m-64-nulls` block for block — benchmark/datagen_nulls.py's raw
block program, its fills, its impute-indicate-interleave, imported and run
again, so that the two configurations sweep ONE table — under a REAL-VALUED
float32 label:

    y = MU + SIGMA * (truth . standardised columns + NOISE * N(0, 1))

with datagen_nulls' dense truth on value AND indicator columns (the
population-standardised columns, closed form), so missingness is
informative here too. MU lies several label deviations from zero and SIGMA
is not 1: a dropped intercept, an uncentred label and a wrong scale each
give another answer. The label's noise is drawn from the label key folded
with the block index, as datagen_nulls draws its Bernoulli label: rows
[i * block, (i + 1) * block) come from block i alone.
"""
from __future__ import annotations

import functools

import numpy as np

from benchmark import datagen_nulls as DN


@functools.lru_cache(maxsize=None)
def _write_program(raw_cols: int, block: int, mu: float, sigma: float,
                   truth_scale: float, noise: float):
    import jax
    import jax.numpy as jnp

    pop = DN.population(raw_cols)
    beta = DN.truth(raw_cols, truth_scale) / pop["std"]
    b0 = -float((beta * pop["mean"]).sum())
    beta = jnp.asarray(beta, jnp.float32)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def write(X, y, raw, fills, key, i):
        """Block i imputed, indicated and interleaved as datagen_nulls
        writes it, and labelled, into rows [i * block, (i + 1) * block) of
        X and y in place."""
        gone = jnp.isnan(raw)
        V = jnp.stack([jnp.where(gone, fills[None, :], raw),
                       gone.astype(jnp.float32)],
                      axis=2).reshape(block, 2 * raw_cols)
        z = (V * beta[None, :]).sum(1) + b0
        eps = jax.random.normal(jax.random.fold_in(key, i), (block,),
                                jnp.float32)
        lab = mu + sigma * (z + noise * eps)
        return (jax.lax.dynamic_update_slice_in_dim(
                    X, V.astype(X.dtype), i * block, axis=0),
                jax.lax.dynamic_update_slice_in_dim(
                    y, lab.astype(jnp.float32), i * block, axis=0))

    return write


def label_moments(raw_cols: int, mu: float, sigma: float,
                  truth_scale: float, noise: float) -> dict:
    """The population's label mean, deviation and the R2 of the true
    coefficients, closed form: the vectorised columns are uncorrelated (a
    mean-imputed value is uncorrelated with its own indicator), so the
    signal's variance is the truth's squared norm."""
    signal = float((DN.truth(raw_cols, truth_scale) ** 2).sum())
    return {"mean": mu, "std": sigma * float(np.sqrt(signal + noise ** 2)),
            "r2_of_truth": signal / (signal + noise ** 2)}


def device_matrix(rows: int, raw_cols: int, dtype: str, seed: int, *,
                  mu: float, sigma: float, truth_scale: float,
                  noise: float) -> tuple:
    """(X [rows, 2 raw_cols] in `dtype`, y [rows] float32, fills [raw_cols]
    float64): datagen_nulls.device_matrix's X and fills for this seed, bit
    for bit, and the real-valued label."""
    import jax
    import jax.numpy as jnp

    block = DN._block_rows(rows)
    raw_block = DN._raw_program(raw_cols, block)
    sums, _ = DN._programs(raw_cols, block, 0.0, 0.0)
    write = _write_program(raw_cols, block, float(mu), float(sigma),
                           float(truth_scale), float(noise))
    k_raw, k_lab = DN._keys(seed)
    parts = [sums(raw_block(k_raw, i)) for i in range(rows // block)]
    total = np.sum([np.asarray(s, np.float64) for s, _ in parts], axis=0)
    count = np.sum([np.asarray(c, np.float64) for _, c in parts], axis=0)
    fills = (DN.population(raw_cols)["loc"]
             + total / np.maximum(count, 1.0)).astype(np.float32)
    fills_d = jnp.asarray(fills)
    X = jnp.zeros((rows, 2 * raw_cols), jnp.dtype(dtype))
    y = jnp.zeros(rows, jnp.float32)
    for i in range(rows // block):
        X, y = write(X, y, raw_block(k_raw, i), fills_d, k_lab, i)
    jax.block_until_ready((X, y))
    return X, y, fills.astype(np.float64)
