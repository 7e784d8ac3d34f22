"""The fused wide pass (ops/pallas_wide.wide_gradient) on the CPU in interpret
mode, held to the XLA body it stands in for
(ops/glm_sweep._wide_gradient_blocks): the two are one arithmetic, so they may
differ by the order of float32 sums and nothing else. Then which body the
program chooses from what it can observe, and one whole round and one whole
streamed sweep through each.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.ops import glm_sweep as GS
from transmogrifai_tpu.ops import pallas_hist
from transmogrifai_tpu.ops import pallas_wide as PW
from transmogrifai_tpu.ops import parts as P
from transmogrifai_tpu.utils.metrics import collector

FOLDS = 3
BF16, F32 = jnp.bfloat16, jnp.float32


def _problem(n, d, Lb, live, dtype, unit_w=False, intercept=True, seed=0):
    """Seeded inputs of one pass: term counts (exact in bf16), non-unit
    weights unless `unit_w`, complementary fold masks, `live` lanes of the
    bucket mapped to folds and the rest inert (no fold), raw-unit
    coefficients and, with `intercept`, intercepts."""
    rng = np.random.default_rng(seed)
    X = rng.poisson(0.7, size=(n, d)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.3).astype(np.float32)
    w = np.ones(n, np.float32) if unit_w \
        else rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    fold = rng.integers(0, FOLDS, size=n)
    masks = (fold[None, :] != np.arange(FOLDS)[:, None]).astype(np.float32)
    sel = np.zeros((FOLDS, Lb), np.float32)
    sel[rng.integers(0, FOLDS, size=live), np.arange(live)] = 1.0
    B = (rng.normal(size=(Lb, d)) * 0.1).astype(np.float32)
    b0 = rng.normal(size=Lb).astype(np.float32) if intercept \
        else np.zeros(Lb, np.float32)
    return (jnp.asarray(X).astype(dtype), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), jnp.asarray(sel), jnp.asarray(B),
            jnp.asarray(b0))


def _assert_same_sums(got, ref):
    """float32 sums of ~1e3 terms in another order: 1e-5 of the largest."""
    for a, b in zip(got, ref):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and np.isfinite(a).all()
        assert np.abs(a - b).max() <= 1e-5 * np.abs(b).max()


def _fused(X, y, w, masks, sel, B, b0):
    return PW.wide_gradient(X.T, PW.side_rows(y, w, masks), sel,
                            *(p.astype(X.dtype) for p in
                              P.float32_parts(B, X.dtype, 2)),
                            b0, interpret=True)


@pytest.fixture
def backend(monkeypatch):
    """backend(mosaic) makes the program choose as it would on a backend
    with (or without) Mosaic, the fused body interpreted: steered here, not
    by an option of the program. The round program bakes the choice in, so
    its cache goes with every change of it."""
    monkeypatch.setattr(PW, "wide_gradient", functools.partial(
        PW.wide_gradient, interpret=True))

    def choose(mosaic: bool):
        monkeypatch.setattr(pallas_hist, "available", lambda: mosaic)
        GS.sweep_glm_wide_round.clear_cache()
    yield choose
    GS.sweep_glm_wide_round.clear_cache()


# (rows, columns, bucket, live lanes, dtype, unit weights, intercept)
CASES = {
    # 264 columns: no multiple of 16, so the block reaches 8 sublanes past
    # the matrix; the main part is 256 columns and the last 128 overlap it
    "width-264": (1024, 264, 8, 8, BF16, False, True),
    # 1 300 rows in one tile of 1 408: the tail reads past n (interpret mode
    # plants NaN there), which only the select on the row index keeps out
    "ragged-rows": (1300, 264, 8, 8, BF16, False, True),
    # whole tiles and a ragged last one, the accumulators revisited
    "three-tiles": (2500, 200, 8, 8, BF16, False, True),
    "inert-lanes": (1024, 264, 16, 10, BF16, False, True),
    "one-live-lane": (1024, 136, 8, 1, BF16, False, True),
    "unit-weights": (1024, 264, 8, 8, BF16, True, True),
    "no-intercept": (1024, 264, 8, 8, BF16, False, False),
    "bucket-64": (640, 520, 64, 40, BF16, False, True),
    # a float32 matrix contracts at HIGHEST, another program: it stays with
    # the blocks whatever the backend has
    "float32": (1024, 264, 8, 8, F32, False, True),
}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_fused_pass_equals_the_xla_body(case, backend, monkeypatch):
    """(gA, g0A) of the body the program chooses where Mosaic is present
    against the XLA body, to float32 rounding; a second run repeats bit for
    bit (one sequential grid axis: every sum has a fixed order)."""
    n, d, Lb, live, dtype, unit_w, intercept = case
    monkeypatch.setattr(PW, "_TILE", 1024)      # three-tiles: 2 500 rows
    backend(True)
    args = _problem(n, d, Lb, live, dtype, unit_w, intercept, seed=n + d)
    ref = GS._wide_gradient_blocks(*args)
    if dtype == F32:
        assert GS.wide_round_kernel(d, dtype) == "xla_blocks"
        with pytest.raises(TypeError):      # two parts: B_lo is None
            _fused(*args)
        return
    assert GS.wide_round_kernel(d, dtype) == "pallas_fused"
    got = _fused(*args)
    _assert_same_sums(got, ref)
    assert (np.asarray(got[0])[live:] == 0).all()       # inert lanes
    again = _fused(*args)
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(got, again))


@pytest.mark.parametrize("mosaic,no_pallas,d,dtype,vmem,says", [
    (False, False, 4104, BF16, None, "xla_blocks"),     # the CPU, Tier-1
    (True, False, 4104, BF16, None, "pallas_fused"),    # sweep-glm-wide4k
    (True, False, 264, BF16, None, "pallas_fused"),
    (True, False, 4096, BF16, None, "xla_blocks"),      # columns-minor: a copy
    (True, False, 256, BF16, None, "xla_blocks"),
    (True, True, 4104, BF16, None, "xla_blocks"),       # TMOG_NO_PALLAS
    (True, False, 4104, F32, None, "xla_blocks"),       # HIGHEST: another program
    (True, False, 4104, jnp.float16, None, "xla_blocks"),     # not its parts
    (True, False, 4104, BF16, 2 << 20, "xla_blocks"),   # no 128-row tile fits
    (True, False, 60_000, BF16, None, "xla_blocks"),    # nor at this width
], ids=["cpu", "wide4k", "264", "4096-columns", "256-columns",
        "TMOG_NO_PALLAS", "float32", "float16", "small-vmem", "too-wide"])
def test_the_body_is_chosen_from_backend_width_dtype_and_vmem(
        monkeypatch, mosaic, no_pallas, d, dtype, vmem, says):
    """wide_round_kernel's table. TMOG_NO_PALLAS reaches it through
    pallas_hist.available() (the switch is read at import, so the case sets
    what it sets); VMEM as a v5e's unless the case gives another."""
    monkeypatch.setattr(jax, "default_backend",
                        lambda: "tpu" if mosaic else "cpu")
    monkeypatch.setattr(pallas_hist, "_enabled", not no_pallas)
    monkeypatch.setattr(pallas_hist, "_vmem_limit",
                        lambda: vmem or (96 << 20))
    assert GS.wide_round_kernel(d, dtype) == says
    # one predicate for both families: where dtype and VMEM do not decide,
    # the multinomial rounds get the same answer from backend and width
    if dtype == BF16 and vmem is None and d < 60_000:
        assert GS.round_kernel(d) == says


def test_the_tile_is_sized_by_the_vmem_it_is_given(monkeypatch):
    monkeypatch.setattr(pallas_hist, "_vmem_limit", lambda: 96 << 20)
    assert PW.tile_rows(4104) == 2048
    assert PW.vmem_bytes(4104, 2048) < 96 << 20
    monkeypatch.setattr(pallas_hist, "_vmem_limit", lambda: 32 << 20)
    assert PW.tile_rows(4104) == 512
    monkeypatch.setattr(pallas_hist, "_vmem_limit", lambda: 16 << 20)
    assert PW.tile_rows(4104) == 0


# -- a whole round, a whole sweep ---------------------------------------------

N, D = 1536, 136


def _sweep_data(seed=3):
    rng = np.random.default_rng(seed)
    X = rng.poisson(0.5, size=(N, D)).astype(np.float32)
    beta = rng.normal(size=D).astype(np.float32) * (rng.uniform(size=D) < 0.2)
    p = 1.0 / (1.0 + np.exp(-((X - 0.5) @ beta - 0.5)))
    y = (rng.uniform(size=N) < p).astype(np.float32)
    fold = rng.integers(0, FOLDS, size=N)
    masks = (fold[None, :] != np.arange(FOLDS)[:, None]).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=N).astype(np.float32)
    return (jnp.asarray(X).astype(BF16), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks))


@pytest.mark.parametrize("fit_intercept", [True, False],
                         ids=["intercept", "no-intercept"])
def test_a_whole_round_through_the_kernel(backend, fit_intercept):
    """sweep_glm_wide_round with either body around ONE iteration (centre
    and scale, the 16 inner steps, the intercept step): the same number of
    iterations, the same iterate to float32 rounding (six steps: 1e-5),
    inert lanes at rest;
    without an intercept b0 stays where it was."""
    X, y, w, masks = _sweep_data()
    mean, std = GS.glm_standardize_stats(X, w)
    inv_std = 1.0 / std
    Gs, lam = GS.wide_gram(X, w, mean, inv_std)
    Lb, live = 8, 5
    sel = np.zeros((FOLDS, Lb), np.float32)
    sel[np.arange(live) % FOLDS, np.arange(live)] = 1.0
    l2 = jnp.asarray([1e-3, 1e-2, 1e-1, 1e-3, 1e-2, 1, 1, 1], F32)
    args = (X, y, w, masks, jnp.asarray(sel), l2 * 0.5, l2,
            jnp.zeros((Lb, D), F32), jnp.zeros(Lb, F32), mean, inv_std, Gs,
            lam, jnp.asarray(6, jnp.int32), jnp.asarray(1e-6, F32))
    outs = []
    for mosaic in (False, True):
        backend(mosaic)
        outs.append([np.asarray(v) for v in GS.sweep_glm_wide_round(
            *args, fit_intercept=fit_intercept)])
    B, b0, delta, iters = zip(*outs)
    assert int(iters[0]) == int(iters[1]) == 6
    assert np.abs(B[0][:live]).max() > 1e-3
    np.testing.assert_allclose(B[1], B[0], rtol=0, atol=1e-5)
    np.testing.assert_allclose(b0[1], b0[0], rtol=0, atol=1e-5)
    assert (b0[1] == 0).all() != fit_intercept
    assert (delta[0][live:] == 0).all() and (delta[1][live:] == 0).all()
    np.testing.assert_allclose(delta[1], delta[0], rtol=1e-3, atol=1e-7)


def _streamed(regs=(0.01, 0.1)):
    X, y, w, masks = _sweep_data(seed=5)
    collector.disable()     # whatever an earlier test file left behind
    collector.enable("wide_round_kernel")
    try:
        B, b0, info = GS.sweep_glm_wide_streamed_rounds(
            X, y, w, masks, np.float32(regs), np.float32([0.5] * len(regs)),
            max_iter=10, tol=1e-6, round_iters=5)
        spans = [s for s in collector.trace.spans if s.kind == "sweep_round"]
    finally:
        collector.finish()
        collector.disable()
    return B, b0, info, spans


def test_telemetry_and_span_name_the_fused_body_where_it_runs(backend):
    """Where the backend has Mosaic the sweep runs the fused body and says
    so in `round_kernel` and on every round's span; its answer is the XLA
    body's to float32 rounding; `kernel` stays the route's name."""
    backend(True)
    B, b0, info, spans = _streamed()
    assert info["round_kernel"] == "pallas_fused"
    assert info["kernel"] == "wide_rounds"
    assert spans and {s.attrs["kernel"] for s in spans} == {"pallas_fused"}
    assert all(s.name.startswith("glm_wide_round[") for s in spans)
    backend(False)
    B_x, b0_x, info_x, spans_x = _streamed()
    assert info_x["round_kernel"] == "xla_blocks"
    assert {s.attrs["kernel"] for s in spans_x} == {"xla_blocks"}
    assert info_x["iters_per_round"] == info["iters_per_round"]
    np.testing.assert_allclose(B, B_x, rtol=0, atol=2e-5)
    np.testing.assert_allclose(b0, b0_x, rtol=0, atol=2e-5)
