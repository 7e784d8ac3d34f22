"""The benchmark's inputs, made from --seed: the same seed gives the same
data. Copied from bench.py (`device_data`, `truth_beta`) with the seed made
an argument; the program receives only what is generated here.
"""
from __future__ import annotations

import numpy as np


def truth_beta(d: int) -> np.ndarray:
    """Ground-truth coefficients of the logistic label: fixed, so every
    seed draws from the same population and the reference fit chases the
    same optimum."""
    rng = np.random.default_rng(123)
    return (rng.normal(size=d) / np.sqrt(d)).astype(np.float32)


def device_matrix(rows: int, cols: int, dtype: str, seed: int):
    """Standard-normal X [rows, cols] in `dtype` and a logistic label on
    truth_beta, made ON THE DEVICE in one jitted call: no host matrix is
    built or copied."""
    import jax
    import jax.numpy as jnp

    beta = truth_beta(cols)

    def gen(key):
        kx, ku = jax.random.split(key)
        X = jax.random.normal(kx, (rows, cols), jnp.float32)
        p = jax.nn.sigmoid(X @ jnp.asarray(beta))
        y = (jax.random.uniform(ku, (rows,)) < p).astype(jnp.float32)
        return X.astype(jnp.dtype(dtype)), y

    # tmoglint: disable=TRC001  called once in a process
    X, y = jax.jit(gen)(jax.random.PRNGKey(seed))
    jax.block_until_ready((X, y))
    return X, y
