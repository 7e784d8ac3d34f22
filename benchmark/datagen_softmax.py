"""The multiclass cell's inputs, made from --seed on the device: the same
seed gives the same data. X is standard normal as `datagen.device_matrix`
makes it; the label is drawn from softmax(X @ B* + b*) with a FIXED truth,
so every seed draws from the same population and every fit chases the same
optimum.
"""
from __future__ import annotations

import numpy as np

#: rows made by one step of the generator: bounds the [rows, classes]
#: float32 logits and Gumbel draws to 2 x 128 MB at 32 classes
CHUNK_ROWS = 1 << 20


def truth(cols: int, classes: int, scale: float) -> tuple:
    """(B* [cols, classes], b* [classes]): numpy rng 123, B* = scale x
    standard normal / sqrt(cols), b*[k] = -log(k + 1) — skewed priors, so
    that intercepts matter. `scale` sets the Bayes error of the data (the
    configuration records both)."""
    rng = np.random.default_rng(123)
    B = (rng.normal(size=(cols, classes)) / np.sqrt(cols) * scale)
    return B.astype(np.float32), \
        (-np.log(np.arange(classes) + 1.0)).astype(np.float32)


def device_matrix(rows: int, cols: int, classes: int, dtype: str, seed: int,
                  scale: float):
    """X [rows, cols] in `dtype` and y [rows] float32 class ids, made ON
    THE DEVICE in one jitted call, CHUNK_ROWS rows a step (each step its
    own fold of the key): no host matrix, no [rows, classes] array."""
    import jax
    import jax.numpy as jnp

    steps = -(-rows // CHUNK_ROWS)
    while rows % steps:
        steps += 1
    chunk = rows // steps
    B, b = (jnp.asarray(a) for a in truth(cols, classes, scale))

    def gen(key):
        def step(i):
            kx, ky = jax.random.split(jax.random.fold_in(key, i))
            X = jax.random.normal(kx, (chunk, cols), jnp.float32)
            y = jax.random.categorical(ky, X @ B + b, axis=1)
            return X.astype(jnp.dtype(dtype)), y.astype(jnp.float32)
        X, y = jax.lax.map(step, jnp.arange(steps))
        return X.reshape(rows, cols), y.reshape(rows)

    # tmoglint: disable=TRC001  called once in a process
    X, y = jax.jit(gen)(jax.random.PRNGKey(seed))
    jax.block_until_ready((X, y))
    return X, y
