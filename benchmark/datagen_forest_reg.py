"""The regression forest's inputs, made from --seed on the device:
`binary-10m-64`'s matrix — benchmark/datagen.device_matrix's key split, its
draw and its cast, so that at a shared seed `sweep-rf` and
`sweep-rf-regression` grow on ONE matrix, bit for bit — under a REAL-VALUED
float32 label on datagen.truth_beta:

    y = MU + SIGMA * (X . truth_beta + NOISE * N(0, 1))

X here is the float32 draw, as the logistic label of datagen sees it, and
the label's noise comes from the key datagen draws its uniforms from. MU
lies several label deviations from zero, as a positive business quantity
does (an amount, days to close): a payload w * y rounded to bfloat16 at its
own size, an uncentred sum and a leaf that forgets its centre each give
another answer.
"""
from __future__ import annotations

import numpy as np

from benchmark import datagen


def label_moments(cols: int, mu: float, sigma: float, noise: float) -> dict:
    """The population's label mean, deviation and the share of its variance
    the columns carry, closed form (the columns are independent standard
    normals)."""
    signal = float((datagen.truth_beta(cols).astype(np.float64) ** 2).sum())
    return {"mean": mu, "std": sigma * float(np.sqrt(signal + noise ** 2)),
            "r2_of_truth": signal / (signal + noise ** 2)}


def device_matrix(rows: int, cols: int, dtype: str, seed: int, *,
                  mu: float, sigma: float, noise: float):
    """(X [rows, cols] in `dtype`, y [rows] float32), made ON THE DEVICE in
    one jitted call: no host matrix is built or copied."""
    import jax
    import jax.numpy as jnp

    beta = datagen.truth_beta(cols)

    def gen(key):
        kx, ku = jax.random.split(key)
        X = jax.random.normal(kx, (rows, cols), jnp.float32)
        eps = jax.random.normal(ku, (rows,), jnp.float32)
        y = mu + sigma * (X @ jnp.asarray(beta) + noise * eps)
        return X.astype(jnp.dtype(dtype)), y.astype(jnp.float32)

    # tmoglint: disable=TRC001  called once in a process
    X, y = jax.jit(gen)(jax.random.PRNGKey(seed))
    jax.block_until_ready((X, y))
    return X, y
