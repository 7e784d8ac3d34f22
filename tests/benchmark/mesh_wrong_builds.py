"""Named WRONG builds of the sweep on a mesh, as context managers that
patch the program for as long as they are open. benchmark/reference_mesh.py
has to refuse each one: the tests show it at toy size on the CPU's host
devices, `.scratch` scripts of the PR that brought them showed it on the
chip at the cell's size (PERF.md section 4).

- `rounds_drop_shard(k)`: shard k's rows carry weight 0 in the IRLS
  rounds (the fit never sees them); the metric pass is whole.
- `metric_drop_shard(k)`: shard k's rows carry weight 0 in the held-out
  metric pass; the fit is whole.
- `counts_not_summed()`: the metric pass leaves out its psum, so every
  chip takes the metrics from its own rows' counts.
"""
from __future__ import annotations

import contextlib


def _without_shard(w, k: int):
    """w with the rows of shard k zeroed, in w's own layout."""
    import jax
    import jax.numpy as jnp
    local = w.shape[0] // len(w.sharding.device_set)
    # tmoglint: disable=TRC001  a wrong build made once a test
    return jax.jit(
        lambda v: v * (jnp.arange(v.shape[0]) // local != k),
        out_shardings=w.sharding)(w)


@contextlib.contextmanager
def rounds_drop_shard(k: int):
    from transmogrifai_tpu.ops import glm_sweep as GS
    whole = GS.sweep_glm_streamed_rounds

    def rounds(X, y, w, fold_masks, *args, **kw):
        return whole(X, y, _without_shard(w, k), fold_masks, *args, **kw)
    GS.sweep_glm_streamed_rounds = rounds
    try:
        yield
    finally:
        GS.sweep_glm_streamed_rounds = whole


@contextlib.contextmanager
def metric_drop_shard(k: int):
    from transmogrifai_tpu.automl.tuning import validators as V
    whole = V._sharded_eval_heldout_fn

    def build(mesh, metric, rank_bins):
        fn = whole(mesh, metric, rank_bins)
        return lambda X, y, w, *rest: fn(X, y, _without_shard(w, k), *rest)
    V._sharded_eval_heldout_fn = build
    try:
        yield
    finally:
        V._sharded_eval_heldout_fn = whole


@contextlib.contextmanager
def counts_not_summed():
    from transmogrifai_tpu.automl.tuning import validators as V
    summed = V._eval_heldout_core

    def core(*args, axis_name=None, **kw):
        return summed(*args, axis_name=None, **kw)
    V._eval_heldout_core = core
    V._sharded_eval_heldout_fn.cache_clear()    # trace the wrong body
    try:
        yield
    finally:
        V._eval_heldout_core = summed
        V._sharded_eval_heldout_fn.cache_clear()


BUILDS = {"rounds_drop_shard": lambda: rounds_drop_shard(1),
          "metric_drop_shard": lambda: metric_drop_shard(2),
          "counts_not_summed": counts_not_summed}
