"""The fused multinomial pass (ops/pallas_softmax.mlr_gradient) on the CPU in
interpret mode, held to the XLA body it stands in for
(ops/glm_sweep._mlr_gradient_blocks): the two are one arithmetic, so they may
differ by the order of float32 sums and nothing else. Then one whole round
and one whole streamed sweep through each body, and what the telemetry and
the round's span say ran.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.ops import glm_sweep as GS
from transmogrifai_tpu.ops import pallas_hist
from transmogrifai_tpu.ops import pallas_softmax as PS
from transmogrifai_tpu.utils.metrics import collector

FOLDS = 3


def _problem(n, d, K, Lb, dtype, seed=0):
    """Seeded inputs of one pass: shifted, scaled columns (so that
    standardising does something), non-unit weights, complementary fold
    masks, Lb - 1 live lanes and one inert one (all of them at Lb = 1)."""
    rng = np.random.default_rng(seed)
    X = (rng.normal(size=(n, d)) * 2.0 + 0.5).astype(np.float32)
    y = rng.integers(0, K, size=n).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    fold = rng.integers(0, FOLDS, size=n)
    masks = (fold[None, :] != np.arange(FOLDS)[:, None]).astype(np.float32)
    live = max(1, Lb - 1)
    sel = np.zeros((FOLDS, Lb), np.float32)
    sel[rng.integers(0, FOLDS, size=live), np.arange(live)] = 1.0
    B = (rng.normal(size=(Lb, d, K)) * 0.3).astype(np.float32)
    b0 = rng.normal(size=(Lb, K)).astype(np.float32)
    Xd = jnp.asarray(X).astype(dtype)
    hi, lo = GS._mlr_coefficient_parts(jnp.asarray(B), dtype)
    tail = (jnp.asarray(sel), hi, lo, jnp.asarray(b0),
            jnp.asarray(X.mean(0)), jnp.asarray(1.0 / X.std(0)))
    return Xd, jnp.asarray(y), jnp.asarray(w), jnp.asarray(masks), tail


def _nan_past(a, n_pad):
    """`a` with NaN planted in n_pad more rows (its last axis)."""
    width = [(0, 0)] * (a.ndim - 1) + [(0, n_pad)]
    return jnp.pad(a, width, constant_values=jnp.nan)


def _assert_same_sums(got, ref):
    """float32 sums of ~1e3 terms in another order: 1e-5 of the largest."""
    for a, b in zip(got, ref):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and np.isfinite(a).all()
        assert np.abs(a - b).max() <= 1e-5 * np.abs(b).max()


def _fused(X, y, w, masks, tail, n, n_pad):
    """The kernel over buffers that run n_pad rows past n, NaN there."""
    return PS.mlr_gradient(
        _nan_past(X.T, n_pad), PS.dense_rows(_nan_past(y, n_pad), n),
        PS.dense_rows(_nan_past(w, n_pad), n), _nan_past(masks, n_pad),
        *tail, n_rows=n, interpret=True)


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 1 024 rows in two bodies of two chunks (the chip's are 8
    bodies of 6): the test sizes make a whole tile and a ragged one, the
    accumulators revisited, and the interpreted body, whose chunks and
    lanes are unrolled, compiles in a third of the time."""
    monkeypatch.setattr(PS, "_UNROLL", 2)
    monkeypatch.setattr(PS, "_TILE_BODIES", 2)
    gradient = PS.mlr_gradient      # the jitted function, whoever wraps it
    gradient.clear_cache()
    yield
    gradient.clear_cache()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("classes", [2, 3, 32])
@pytest.mark.parametrize("bucket", [1, 4, 16])
def test_fused_pass_equals_the_xla_body(bucket, classes, dtype, small_tiles):
    """(gA, g0A) at 64 columns for every bucket and for class counts that
    pad a sublane tile (2, 3) and fill four (32); 1 300 rows are no multiple
    of the 256-row chunk, and the buffers hold NaN in the 236 rows after
    them, which only the select on the row index keeps out of the sums."""
    n, n_pad = 1300, 236
    X, y, w, masks, tail = _problem(n, 64, classes, bucket, dtype)
    ref = GS._mlr_gradient_blocks(X, y, w, masks, *tail)
    _assert_same_sums(_fused(X, y, w, masks, tail, n, n_pad), ref)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("d", [8, 37])
def test_fused_pass_at_widths_that_fill_no_tile(d, dtype, small_tiles):
    """Columns pad to whole sublane tiles INSIDE the kernel (the block
    reaches past the matrix; a select on the column index zeroes what it
    reads there): 37 is no multiple of 8 or 16, 8 is half a bf16 tile."""
    n = 700
    X, y, w, masks, tail = _problem(n, d, 5, 4, dtype, seed=d)
    ref = GS._mlr_gradient_blocks(X, y, w, masks, *tail)
    _assert_same_sums(_fused(X, y, w, masks, tail, n, 68), ref)


def test_fused_pass_over_more_than_one_grid_step():
    """13 000 rows are two tiles of 12 288, the second partial with nothing
    behind it (no padded buffer: the block itself reaches past the matrix);
    the accumulators are revisited, and a second call repeats bit for bit."""
    n = 13000
    assert -(-n // PS._tile_rows(n)) == 2
    X, y, w, masks, tail = _problem(n, 64, 3, 4, jnp.bfloat16, seed=9)
    ref = GS._mlr_gradient_blocks(X, y, w, masks, *tail)
    args = (X.T, PS.dense_rows(y), PS.dense_rows(w), masks) + tail
    got = PS.mlr_gradient(*args, interpret=True)
    _assert_same_sums(got, ref)
    again = PS.mlr_gradient(*args, interpret=True)
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(got, again))


# -- a whole round, a whole sweep ---------------------------------------------

N, D, K = 2048, 8, 5


def _sweep_data(seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, D)).astype(np.float32)
    logits = X @ rng.normal(size=(D, K)).astype(np.float32) \
        - np.log(np.arange(K) + 1.0)
    y = (logits + rng.gumbel(size=logits.shape)).argmax(1)
    fold = rng.integers(0, FOLDS, size=N)
    masks = (fold[None, :] != np.arange(FOLDS)[:, None]).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=N).astype(np.float32)
    return (jnp.asarray(X).astype(jnp.bfloat16),
            jnp.asarray(y.astype(np.float32)), jnp.asarray(w),
            jnp.asarray(masks))


@pytest.fixture
def backend(monkeypatch, small_tiles):
    """backend(mosaic) makes the program choose as it would on a backend
    with (or without) Mosaic, the fused body interpreted: steered here, not
    by an option of the program. The round program bakes the choice in, so
    its cache goes with every change of it."""
    monkeypatch.setattr(PS, "mlr_gradient", functools.partial(
        PS.mlr_gradient, interpret=True))

    def choose(mosaic: bool):
        monkeypatch.setattr(pallas_hist, "available", lambda: mosaic)
        GS.sweep_mlr_round.clear_cache()
    yield choose
    GS.sweep_mlr_round.clear_cache()


def test_a_whole_round_through_the_kernel(backend):
    """sweep_mlr_round with either body around ONE iteration (cond, body,
    the Cholesky step, the threshold): the same number of iterations, the
    same iterate to float32 rounding, the lanes' deltas in the same order."""
    X, y, w, masks = _sweep_data()
    mean, std = GS.glm_standardize_stats(X, w)
    Lb, live = 4, 3
    lane_fold = np.array([0, 1, 2, 0], np.int32)
    sel = np.zeros((FOLDS, Lb), np.float32)
    sel[lane_fold[:live], np.arange(live)] = 1.0
    l2 = jnp.asarray([1e-3, 1e-2, 1e-1, 1e-3], jnp.float32)
    chol, hdiag = GS.mlr_gram_factor(X, w, masks, mean, std,
                                     jnp.asarray(lane_fold), l2, n_classes=K)
    args = (X, y, w, masks, jnp.asarray(sel), l2 * 0.1, l2,
            jnp.zeros((Lb, D, K), jnp.float32),
            jnp.zeros((Lb, K), jnp.float32), mean, std, chol, hdiag,
            jnp.asarray(8, jnp.int32), jnp.asarray(1e-6, jnp.float32))
    outs = []
    for mosaic in (False, True):
        backend(mosaic)
        outs.append([np.asarray(v) for v in GS.sweep_mlr_round(*args)])
    B, b0, delta, iters = zip(*outs)
    assert int(iters[0]) == int(iters[1]) == 8
    # eight steps of a contraction: rounding differences of 1e-7 do not grow
    np.testing.assert_allclose(B[1], B[0], rtol=0, atol=2e-5)
    np.testing.assert_allclose(b0[1], b0[0], rtol=0, atol=2e-5)
    assert (delta[0][live:] == 0).all() and (delta[1][live:] == 0).all()
    assert (np.argsort(delta[0][:live]) == np.argsort(delta[1][:live])).all()
    np.testing.assert_allclose(delta[1], delta[0], rtol=1e-3, atol=1e-7)


def _streamed(regs=(0.01, 0.1)):
    X, y, w, masks = _sweep_data(seed=5)
    collector.disable()     # whatever an earlier test file left behind
    collector.enable("mlr_round_kernel")
    try:
        B, b0, info = GS.sweep_mlr_streamed_rounds(
            X, y, w, masks, np.float32(regs), np.float32([0.1] * len(regs)),
            n_classes=K, max_iter=10, tol=1e-6, round_iters=5)
        spans = [s for s in collector.trace.spans if s.kind == "sweep_round"]
    finally:
        collector.finish()
        collector.disable()
    return B, b0, info, spans


def test_telemetry_and_span_name_the_xla_body_on_the_cpu():
    assert GS.round_kernel(64) == "xla_blocks"
    _, _, info, spans = _streamed()
    assert info["round_kernel"] == "xla_blocks"
    assert spans and {s.attrs["kernel"] for s in spans} == {"xla_blocks"}
    assert all(s.name.startswith("mlr_round[") for s in spans)


def test_telemetry_and_span_name_the_fused_body_where_it_runs(backend):
    """Where the backend has Mosaic the sweep runs the fused body and says
    so in `round_kernel` and on every round's span; its answer is the XLA
    body's to float32 rounding. 128 columns stay with the blocks."""
    backend(True)
    assert GS.round_kernel(64) == "pallas_fused"
    assert GS.round_kernel(100) == "pallas_fused"
    assert GS.round_kernel(128) == "xla_blocks"
    B, b0, info, spans = _streamed()
    assert info["round_kernel"] == "pallas_fused"
    assert spans and {s.attrs["kernel"] for s in spans} == {"pallas_fused"}
    backend(False)
    B_x, b0_x, info_x, _ = _streamed()
    assert info_x["round_kernel"] == "xla_blocks"
    assert info_x["iters_per_round"] == info["iters_per_round"]
    np.testing.assert_allclose(B, B_x, rtol=0, atol=5e-5)
    np.testing.assert_allclose(b0, b0_x, rtol=0, atol=5e-5)
