"""The wide cell's inputs, made from --seed on the device: what upstream's
`transmogrify()` hands a ModelSelector for a table of free-text columns.
Each text column is hashed to `buckets` term-count columns in its own hash
space, plus one null-indicator column (Transmogrifier.scala:52-90,
SmartTextVectorizer.scala; DefaultNumOfFeatures 512, TrackNulls true,
binaryFreq false): columns [c * (buckets + 1), (c + 1) * (buckets + 1)) are
text column c's buckets, then its indicator.

The generator is elementwise (no scatter, no host matrix): a row's text
column c holds T tokens, T lognormal (sigma TOKEN_SIGMA) around
TOKEN_MEANS[c]; tokens come from a Zipf (s = ZIPF_S) vocabulary of VOCAB
words, each word assigned to a bucket by a FIXED numpy rng 123 draw a
column, which gives the bucket masses q_c; the count of bucket b is then
Poisson(min(T q_c[b], RATE_CAP)) — the Poissonised multinomial of hashing T
independent tokens — by inverse CDF from ONE uniform a cell, capped at 255
so that bfloat16 holds it exactly. A text column is null with probability
NULL_P: its counts are zero and its indicator one.

The label is Bernoulli of a logistic over the columns standardised by their
POPULATION moments (closed form from the generator, the same for every
seed), with a fixed sparse truth: `truth_nonzero` of the bucket columns and
every indicator (numpy rng 123), so every seed draws from one population.
"""
from __future__ import annotations

import functools

import numpy as np

TOKEN_MEANS = (3.0, 3.0, 6.0, 6.0, 12.0, 12.0, 40.0, 120.0)
TOKEN_SIGMA = 0.6
ZIPF_S = 1.07
VOCAB = 50_000
NULL_P = 0.1
RATE_CAP = 60.0
#: inverse-CDF steps: RATE_CAP + 4.6 standard deviations
CDF_STEPS = 96
#: rows made by one step of the generator: bounds the [rows, cols] float32
#: temporaries of a step to 0.27 GB at 4 104 columns
CHUNK_ROWS = 1 << 14


@functools.lru_cache(maxsize=None)
def bucket_masses(text_columns: int, buckets: int) -> np.ndarray:
    """q [text_columns, buckets] float64: the Zipf word masses summed into
    each column's buckets, one assignment draw a column from rng 123."""
    rng = np.random.default_rng(123)
    p = np.arange(1, VOCAB + 1, dtype=np.float64) ** -ZIPF_S
    p /= p.sum()
    return np.stack([np.bincount(rng.integers(0, buckets, VOCAB), weights=p,
                                 minlength=buckets)
                     for _ in range(text_columns)])


def population_moments(text_columns: int, buckets: int) -> tuple:
    """(mean [cols], std [cols]) of the generated columns, closed form (the
    rate cap and the 255 cap are ignored: they move a moment by under a
    percent): a count is 0 with probability NULL_P and else Poisson(T q),
    E[T] = m, E[T^2] = m^2 exp(sigma^2); an indicator is Bernoulli(NULL_P)."""
    q = bucket_masses(text_columns, buckets)
    m = np.asarray(TOKEN_MEANS[:text_columns])[:, None]
    ex = (1.0 - NULL_P) * m * q
    ex2 = (1.0 - NULL_P) * (m * q + m * m * np.exp(TOKEN_SIGMA ** 2) * q * q)
    mean = np.concatenate([ex, np.full((text_columns, 1), NULL_P)], axis=1)
    var = np.concatenate(
        [ex2 - ex * ex,
         np.full((text_columns, 1), NULL_P * (1.0 - NULL_P))], axis=1)
    return mean.reshape(-1), np.sqrt(np.maximum(var.reshape(-1), 1e-12))


def truth(text_columns: int, buckets: int, nonzero: int, scale: float
          ) -> np.ndarray:
    """beta [cols] float64 on the STANDARDISED columns: `nonzero` bucket
    columns chosen without replacement and every indicator carry a
    standard-normal weight (numpy rng 123), the whole scaled by `scale` /
    sqrt(their number)."""
    rng = np.random.default_rng(123)
    per = buckets + 1
    cols = text_columns * per
    is_bucket = (np.arange(cols) % per) < buckets
    live = np.concatenate([
        rng.choice(np.flatnonzero(is_bucket), size=nonzero, replace=False),
        np.flatnonzero(~is_bucket)])
    beta = np.zeros(cols)
    beta[live] = rng.normal(size=live.size) * scale / np.sqrt(live.size)
    return beta


def generator(rows: int, text_columns: int, buckets: int, dtype: str, *,
              truth_nonzero: int, truth_scale: float,
              truth_intercept: float):
    """The jitted program key -> (X, y) of `device_matrix`."""
    import jax
    import jax.numpy as jnp

    per = buckets + 1
    cols = text_columns * per
    steps = -(-rows // CHUNK_ROWS)
    while rows % steps:
        steps += 1
    chunk = rows // steps
    q = jnp.asarray(bucket_masses(text_columns, buckets), jnp.float32)
    mean, std = population_moments(text_columns, buckets)
    beta = truth(text_columns, buckets, truth_nonzero, truth_scale) / std
    b0 = truth_intercept - float((beta * mean).sum())
    beta = jnp.asarray(beta, jnp.float32)
    means = jnp.asarray(TOKEN_MEANS[:text_columns], jnp.float32)

    def gen(key):
        def block(i):
            kt, kn, ku, ky = jax.random.split(jax.random.fold_in(key, i), 4)
            T = means[None, :] * jnp.exp(
                TOKEN_SIGMA * jax.random.normal(kt, (chunk, text_columns))
                - 0.5 * TOKEN_SIGMA ** 2)
            null = jax.random.uniform(kn, (chunk, text_columns)) < NULL_P
            lam = jnp.minimum(T[:, :, None] * q[None], RATE_CAP)
            u = jax.random.uniform(ku, (chunk, text_columns, buckets))
            # inverse CDF, unrolled so that it stays ONE elementwise pass
            p = jnp.exp(-lam)
            cdf, k = p, jnp.zeros_like(lam)
            for j in range(1, CDF_STEPS + 1):
                k = k + (u > cdf)
                p = p * (lam / j)
                cdf = cdf + p
            counts = jnp.where(null[:, :, None], 0.0, jnp.minimum(k, 255.0))
            X = jnp.concatenate(
                [counts, null[:, :, None].astype(jnp.float32)],
                axis=2).reshape(chunk, cols)
            z = (X * beta[None, :]).sum(1) + b0
            y = jax.random.uniform(ky, (chunk,)) < jax.nn.sigmoid(z)
            return X.astype(jnp.dtype(dtype)), y.astype(jnp.float32)

        # each block is written into the one [rows, cols] result in place:
        # stacked blocks would be copied once more into the chip's layout
        def step(i, out):
            Xb, yb = block(i)
            return (jax.lax.dynamic_update_slice_in_dim(out[0], Xb,
                                                        i * chunk, axis=0),
                    jax.lax.dynamic_update_slice_in_dim(out[1], yb,
                                                        i * chunk, axis=0))
        return jax.lax.fori_loop(
            0, steps, step, (jnp.zeros((rows, cols), jnp.dtype(dtype)),
                             jnp.zeros(rows, jnp.float32)))

    return jax.jit(gen)


def device_matrix(rows: int, text_columns: int, buckets: int, dtype: str,
                  seed: int, **truth_kw):
    """X [rows, text_columns * (buckets + 1)] in `dtype` and y [rows]
    float32 0/1, made ON THE DEVICE in one jitted call, CHUNK_ROWS rows a
    step (each step its own fold of the key). `truth_kw`: truth_nonzero,
    truth_scale, truth_intercept."""
    import jax
    # tmoglint: disable=TRC001  called once in a process
    X, y = generator(rows, text_columns, buckets, dtype, **truth_kw)(
        jax.random.PRNGKey(seed))
    jax.block_until_ready((X, y))
    return X, y
