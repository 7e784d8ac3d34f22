"""The fused binary GLM pass (ops/pallas_glm.glm_moments) on the CPU in
interpret mode, held to the XLA body it stands in for
(ops/glm_sweep._moments_blocks). On the chip the two round the matrix
unit's operands alike (but the residual: its two leading parts in the
kernel, all of them there); the CPU's XLA body multiplies in float32, so
the pass is held twice: to float32 rounding against a twin that writes the chip's
roundings out, and to the operands' rounding against the XLA body itself.
Then which body the program chooses from what it can observe, one whole
round through each, and what the telemetry and the round's span say ran.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.ops import glm_sweep as GS
from transmogrifai_tpu.ops import pallas_glm as PG
from transmogrifai_tpu.ops import pallas_hist
from transmogrifai_tpu.ops import parts as P
from transmogrifai_tpu.utils.metrics import collector

FOLDS = 3
BF16, F32 = jnp.bfloat16, jnp.float32


def _problem(n, d, Lb, live, seed=0, wide_scales=False):
    """Seeded inputs of one pass: shifted, scaled columns (so that
    standardising does something; `wide_scales`: standard deviations from
    0.03 to 16, a power of two a column, as null-tracked numerics have),
    weights of which a tenth are zero, complementary fold masks (a third of
    the rows held out of each fold), `live` lanes of the bucket mapped to
    folds and the rest inert."""
    rng = np.random.default_rng(seed)
    scale, shift = (2.0 ** ((np.arange(d) * 3) % 10 - 5),) * 2 \
        if wide_scales else (1.5, 0.3)
    X = (rng.normal(size=(n, d)) * scale + shift).astype(np.float32)
    y = (rng.uniform(size=n) < 0.4).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    w[rng.uniform(size=n) < 0.1] = 0.0
    fold = rng.integers(0, FOLDS, size=n)
    masks = (fold[None, :] != np.arange(FOLDS)[:, None]).astype(np.float32)
    sel = np.zeros((FOLDS, Lb), np.float32)
    sel[rng.integers(0, FOLDS, size=live), np.arange(live)] = 1.0
    B = (rng.normal(size=(Lb, d)) * 0.2).astype(np.float32)
    B[live:] = 0.0
    b0 = rng.normal(size=Lb).astype(np.float32)
    return (jnp.asarray(X).astype(BF16), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), jnp.asarray(sel),
            jnp.asarray(B).astype(BF16), jnp.asarray(b0),
            jnp.asarray(X.mean(0)), jnp.asarray(X.std(0)))


def _nan_past(a, n_pad):
    """`a` with NaN planted in n_pad more rows (its last axis)."""
    width = [(0, 0)] * (a.ndim - 1) + [(0, n_pad)]
    return jnp.pad(a, width, constant_values=jnp.nan)


def _fused_raw(X, y, w, masks, sel, Bt, b0, mean, std, loss, n_pad=0):
    """The kernel over buffers that run n_pad rows past n, NaN there, in
    the tile form the width takes (glm_x_tile: X.T, or X as it is): its
    six sums, gA over the rounded residual first, gA_low over what the
    rounding left fifth and cA, the Hessian's border, last."""
    n = X.shape[0]
    x_tile = GS.glm_x_tile(X.shape[1])
    XT = _nan_past(X.T, n_pad)
    return PG.glm_moments(
        XT.T if x_tile == "cols_minor" else XT,
        PG.dense_rows(_nan_past(y, n_pad), n),
        PG.dense_rows(_nan_past(w, n_pad), n), _nan_past(masks, n_pad),
        sel, Bt, b0, mean, std, loss=loss, n_rows=n, interpret=True,
        x_tile=x_tile)


def _fused(*args, **kw):
    """The five sums the round steps on, as `_round_core` makes them of the
    kernel's six: gA + gA_low, hA, g0A, h0A, cA."""
    gA, hA, g0A, h0A, gA_low, cA = _fused_raw(*args, **kw)
    return gA + gA_low, hA, g0A, h0A, cA


@functools.partial(jax.jit, static_argnames="loss")
def _blocks(X, y, w, masks, sel, Bt, b0, mean, std, loss):
    """_round_core's XLA body over the same inputs."""
    c = min(GS._row_block(X.shape[1]), X.shape[0])
    return GS._moments_blocks(GS._blocked(X, y, w, masks, c), sel, Bt, b0,
                              mean, std, loss=loss)


@functools.partial(jax.jit, static_argnames="loss")
def _chip_twin(X, y, w, masks, sel, Bt, b0, mean, std, loss):
    """The pass with the chip's roundings written out, the kernel's six
    sums: what the matrix unit's operands are there (the block and the
    curvature x weight x block one bfloat16 pass, the residual x weight its
    two leading bfloat16 parts — gA over the first, gA_low over the second
    — the curvature x weight one part for cA, float32 sums), and what the
    kernel does by its casts."""
    def low(v):
        return v.astype(X.dtype).astype(F32)
    xf = low((X.astype(F32) - mean) / std)
    eta = xf @ Bt.astype(F32).T + b0
    r0, s0 = PG.residual_curvature(loss)(eta, y[:, None])
    wl = (masks.T * w[:, None]) @ sel
    R, S = r0 * wl, s0 * wl
    hA = jnp.einsum("cld,ce->lde", low(S[:, :, None] * xf[:, None, :]), xf)
    return (low(R).T @ xf, hA, R.sum(0), S.sum(0),
            low(R - low(R)).T @ xf, low(S).T @ xf)


def _rel(a, b, of=None):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and np.isfinite(a).all()
    return np.abs(a - b).max() / np.abs(b if of is None else of).max()


def _off_twin(raw, twin):
    """The kernel's six sums against the twin's, each of its own largest
    entry; gA_low of gA's (a last digit of a margin that sends R to the
    other side of a bfloat16 tie moves a whole step of R from one part to
    the other: only on the gradient's scale is the second part's sum a
    float32 sum in another order); and cA a tenth as closely: S takes
    ONE part, so a margin's last digit that sends one row's S to the other
    side of a bfloat16 tie moves that row's term by 2^-9 of itself, 3e-4 of
    the largest sum over a few hundred rows."""
    assert len(raw) == len(twin) == 6
    return max([_rel(a, b) for a, b in zip(raw[:4], twin[:4])]
               + [_rel(raw[4], twin[4], of=twin[0]),
                  _rel(raw[5], twin[5]) / 10])


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 512 rows in two bodies of two chunks: the test sizes make
    whole tiles and a ragged last one, the accumulators revisited."""
    monkeypatch.setattr(PG, "_CHUNK", 128)
    monkeypatch.setattr(PG, "_UNROLL", 2)
    monkeypatch.setattr(PG, "_TILE_BODIES", 2)
    moments = PG.glm_moments        # the jitted function, whoever wraps it
    moments.clear_cache()
    yield
    moments.clear_cache()


# (rows, columns, bucket, live lanes, loss, NaN rows past n)
CASES = {
    # 1 300 rows: two whole tiles and 276 rows of a third, NaN after them,
    # which only the select on the row index keeps out of the sums
    "ragged-rows": (1300, 64, 8, 6, "logistic", 236),
    "one-live-lane": (1300, 64, 8, 1, "logistic", 236),
    "squared-hinge": (1300, 64, 8, 6, "squared_hinge", 236),
    "squared": (1300, 64, 8, 6, "squared", 236),
    "bucket-32": (1300, 64, 32, 30, "logistic", 0),
    # 100 columns are no whole sublane tile: the block reaches 12 rows past
    # the matrix's width
    "width-100": (700, 100, 32, 30, "squared_hinge", 0),
    # 128 columns: [rows, 128] tiles of X itself, turned over in VMEM
    # (x_tile cols_minor); a ragged last tile with NaN after it, 8 lanes,
    # 40 live lanes of a 64-lane bucket (upstream's whole LR grid x 5
    # folds) and a full one
    "cols-128-ragged": (1300, 128, 8, 6, "logistic", 236),
    "cols-128-40-of-64": (700, 128, 64, 40, "logistic", 0),
    "cols-128-64": (600, 128, 64, 64, "squared_hinge", 0),
}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_fused_pass_equals_the_xla_body(case, small_tiles):
    """The kernel's six sums: float32 sums in another order against the
    twin (1e-4 of the largest: a margin summed in another order can send
    one curvature-weighted entry of the ~1e5 to the other side of a
    bfloat16 tie); the five the round steps on (gA + gA_low, hA, g0A, h0A,
    cA) against the CPU's XLA body by the operands' rounding (the Gram's
    2^-9 a term: 4e-3 of the largest; the gradient takes the residual's two
    parts here and all three there, 2^-17 a term: 4e-6; the intercept's sums
    are unrounded in both: 1e-6; the border rounds the curvature x weight
    to the dtype in both, one part: 1e-3), inert lanes at zero, and a
    second run bit for bit (one sequential grid axis: every sum has a fixed
    order)."""
    n, d, Lb, live, loss, n_pad = case
    args = _problem(n, d, Lb, live, seed=n + d + Lb,
                    wide_scales=d == 128) + (loss,)
    assert GS.glm_x_tile(d) == ("cols_minor" if d == 128 else "rows_minor")
    if d == 128:    # standardize on, std spanning 0.03 to 16
        std = np.asarray(args[8])
        assert std.min() < 0.04 and std.max() > 15
    raw = _fused_raw(*args, n_pad=n_pad)
    assert _off_twin(raw, _chip_twin(*args)) <= 1e-4
    got, blocks = _fused(*args, n_pad=n_pad), _blocks(*args)
    assert len(got) == len(blocks) == 5
    for a, b, tol in zip(got, blocks, (4e-6, 4e-3, 1e-6, 1e-6, 1e-3)):
        assert _rel(a, b) <= tol
    assert all((np.asarray(v)[live:] == 0).all() for v in raw)
    again = _fused_raw(*args, n_pad=n_pad)
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(raw, again))


# -- the coefficients' precision ----------------------------------------------

# the two tile forms: X.T tiles at 64 columns, tiles of X itself at 128
PRECISION_CASES = {"rows-minor": (1300, 64, 8, 6, "logistic", 236),
                   "cols-minor": (700, 128, 64, 40, "logistic", 0)}


def _inexact(args):
    """`_problem`'s inputs with coefficients that bfloat16 does not hold:
    every live one moved by a third of its own bfloat16 step."""
    B = args[5].astype(F32)
    return args[:5] + (B * (1 + 2.0 ** -9 / 3),) + args[6:]


@pytest.mark.parametrize("case", PRECISION_CASES.values(),
                         ids=PRECISION_CASES.keys())
def test_margins_see_float32_coefficients_in_both_bodies(case, small_tiles):
    """A float32 B that is NOT exact in the matrix's dtype reaches the
    margins unrounded in the kernel and in the XLA body alike: the
    intercept's sums (residual and curvature unrounded, so the margins'
    precision is all they carry) agree to float32 rounding between the
    bodies and with the twin, and sit where B's own rounding would not
    leave them — the kernel handed bfloat16(B) reads 1e-4 or more away."""
    n, d, Lb, live, loss, n_pad = case
    args = _inexact(_problem(n, d, Lb, live, seed=n + d + Lb,
                             wide_scales=d == 128)) + (loss,)
    B = np.asarray(args[5])
    assert (B[:live] != np.asarray(args[5].astype(BF16).astype(F32))[:live]) \
        .mean() > 0.9
    assert _off_twin(_fused_raw(*args, n_pad=n_pad),
                     _chip_twin(*args)) <= 1e-4
    got = _fused(*args, n_pad=n_pad)
    for a, b, tol in zip(got, _blocks(*args), (4e-6, 4e-3, 2e-6, 2e-6)):
        assert _rel(a, b) <= tol
    rounded = _fused(*args[:5], args[5].astype(BF16), *args[6:],
                     n_pad=n_pad)
    assert _rel(rounded[2], got[2]) >= 1e-4
    assert all((np.asarray(v)[live:] == 0).all() for v in got)


@pytest.mark.parametrize("case", PRECISION_CASES.values(),
                         ids=PRECISION_CASES.keys())
def test_exact_coefficients_give_the_one_part_sums_to_the_bit(
        case, small_tiles, monkeypatch):
    """Coefficients that ARE exact in the matrix's dtype leave every part
    after the first zero, and the five sums are, bit for bit, those of the
    program whose margins take ONE part (bt = Bt.astype(X.dtype), one slab)
    — the residual's two parts kept, which have no exact case. With the
    residual cut to one part as well, which is the kernel as it was before
    either operand took parts, hA, g0A and h0A are still those to the bit
    and gA to the order of a float32 sum: the second part's sum comes out
    beside it, as gA_low, which is then zero, and otherwise no larger than
    the residual's own rounding."""
    n, d, Lb, live, loss, n_pad = case
    args = _problem(n, d, Lb, live, seed=n + d + Lb,
                    wide_scales=d == 128) + (loss,)
    assert P.n_parts(BF16) == 3 and P.n_parts(F32) == 1
    assert PG.residual_parts(BF16) == 2 and PG.residual_parts(F32) == 1
    parts = np.asarray(P.float32_parts(args[5].astype(F32), BF16))
    assert parts.shape == (3, Lb, d) and (parts[1:] == 0).all()
    got = _fused_raw(*args, n_pad=n_pad)
    got_f32 = _fused_raw(*args[:5], args[5].astype(F32), *args[6:],
                         n_pad=n_pad)
    split = P.float32_parts
    monkeypatch.setattr(
        P, "float32_parts", lambda V, dtype, parts=None, **kw: split(
            V, dtype, 1 if parts is None else parts, **kw))
    monkeypatch.setattr(PG, "residual_parts", lambda dtype: 2)
    PG.glm_moments.clear_cache()
    one_part = _fused_raw(*args, n_pad=n_pad)
    for a, b, c in zip(got, got_f32, one_part):
        assert np.array_equal(np.asarray(a), np.asarray(c))
        assert np.array_equal(np.asarray(b), np.asarray(c))
    monkeypatch.setattr(PG, "residual_parts", lambda dtype: 1)
    PG.glm_moments.clear_cache()
    before = _fused_raw(*args, n_pad=n_pad)
    for a, b in zip(got[1:4], before[1:4]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # (the CPU's matmul orders a sum by its operand's width; the chip's
    # matrix unit does not, and read gA the parent's to the bit)
    assert _rel(got[0], before[0]) <= 1e-6
    assert not np.asarray(before[4]).any()
    assert 0 < np.abs(got[4]).max() <= 2.0 ** -8 * np.abs(got[0]).max()


def test_coefficient_parts_sum_to_the_float32_coefficients():
    """Three bfloat16 parts hold a float32 exactly (8 + 8 + 8 significant
    bits), largest first; a float32 matrix takes B itself."""
    rng = np.random.default_rng(0)
    B = (rng.normal(size=(8, 64)) * 10.0 ** rng.integers(-6, 3, (8, 64))) \
        .astype(np.float32)
    parts = np.asarray(P.float32_parts(jnp.asarray(B), BF16))
    assert parts.shape == (3, 8, 64)
    assert np.array_equal(parts, np.asarray(
        jnp.asarray(parts).astype(BF16).astype(F32)))
    assert np.array_equal(parts[2] + parts[1] + parts[0], B)
    assert np.array_equal(parts[0], np.asarray(
        jnp.asarray(B).astype(BF16).astype(F32)))
    assert (np.abs(parts[1]) <= np.abs(B) * 2.0 ** -8).all()
    (whole,) = P.float32_parts(jnp.asarray(B), F32)
    assert np.array_equal(np.asarray(whole), B)
    stacked = jnp.asarray(parts.reshape(24, 64))
    assert np.array_equal(np.asarray(P.slab_sum(stacked, 8)), B)
    assert np.array_equal(np.asarray(P.slab_sum(stacked.T, 8, axis=1)), B.T)


@pytest.mark.parametrize("in_kernel", [False, True],
                         ids=["reduce-precision", "in-kernel-casts"])
def test_two_parts_hold_the_residual_to_2_to_the_minus_17(in_kernel):
    """The residual x weight as the gradient's contraction takes it: the
    SAME routine cut to two parts, inside one jitted program as both bodies
    run it (a round trip fused away would leave the low part zero: PERF.md,
    PR 29) and by the casts a Mosaic body is left with. The parts sum to R
    within 2^-17 |R| where the first alone leaves up to 2^-9, the low part
    is not zero, and it is the first two of the exact three."""
    rng = np.random.default_rng(1)
    R = (rng.uniform(-1, 1, size=(16, 256))
         * rng.uniform(0.5, 2.0, size=(1, 256))).astype(np.float32)
    assert PG.residual_parts(BF16) == 2 and PG.residual_parts(F32) == 1
    split = jax.jit(lambda v: [p.astype(BF16).astype(F32) for p in
                               P.float32_parts(v, BF16,
                                               PG.residual_parts(BF16),
                                               in_kernel=in_kernel)])
    hi, lo = (np.asarray(p) for p in split(jnp.asarray(R)))
    assert np.array_equal(hi, np.asarray(
        jnp.asarray(R).astype(BF16).astype(F32)))
    assert (lo != 0).mean() > 0.95
    assert (np.abs(lo + hi - R) <= np.abs(R) * 2.0 ** -17).all()
    assert (np.abs(hi - R) > np.abs(R) * 2.0 ** -11).mean() > 0.5
    three = np.asarray(P.float32_parts(jnp.asarray(R), BF16))
    assert np.array_equal(three[:2], np.stack([hi, lo]))
    (whole,) = P.float32_parts(jnp.asarray(R), F32, PG.residual_parts(F32))
    assert np.array_equal(np.asarray(whole), R)


# -- the residual's precision -------------------------------------------------

def _shared_rows(n, d, Lb, live, seed):
    """One pass's inputs in which the residual's roundings cannot average
    out: eight distinct rows, each repeated over an eighth of the table
    with one label and unit weight, so that a lane's R takes a handful of
    values and every row of a group rounds the same way (what a null
    indicator's column does to a thousandth of a real table's rows). No
    standardisation (mean 0, std 1: the block is X itself)."""
    rng = np.random.default_rng(seed)
    group = rng.integers(0, 8, size=n)
    X = jnp.asarray(rng.normal(size=(8, d)).astype(np.float32)[group]) \
        .astype(BF16)
    y = (rng.uniform(size=8) < 0.5).astype(np.float32)[group]
    fold = rng.integers(0, FOLDS, size=n)
    masks = (fold[None, :] != np.arange(FOLDS)[:, None]).astype(np.float32)
    sel = np.zeros((FOLDS, Lb), np.float32)
    sel[np.arange(live) % FOLDS, np.arange(live)] = 1.0
    B = (rng.normal(size=(Lb, d)) * 0.2).astype(np.float32)
    B[live:] = 0.0
    b0 = rng.normal(size=Lb).astype(np.float32) * (np.arange(Lb) < live)
    return (X, jnp.asarray(y), jnp.ones(n, F32), jnp.asarray(masks),
            jnp.asarray(sel), jnp.asarray(B), jnp.asarray(b0),
            jnp.zeros(d, F32), jnp.ones(d, F32))


def _gradient_f64(X, y, w, masks, sel, B, b0):
    """gA of the logistic pass in float64, nothing rounded but the block."""
    X, y, w, masks, sel, B, b0 = (np.asarray(v.astype(F32), np.float64)
                                  for v in (X, y, w, masks, sel, B, b0))
    p = 1 / (1 + np.exp(-(X @ B.T + b0)))
    return ((p - y[:, None]) * ((masks.T * w[:, None]) @ sel)).T @ X


@pytest.mark.parametrize("body", ["kernel", "xla"])
@pytest.mark.parametrize("case", PRECISION_CASES.values(),
                         ids=PRECISION_CASES.keys())
def test_gradient_sees_the_float32_residual_in_both_bodies(
        case, body, small_tiles, monkeypatch):
    """sum_rows R xs' where rows share their residual: the gradient the
    round steps on is the float64 sum to 2^-15 of its largest entry through
    the kernel's two tile forms (gA + gA_low: R's two leading parts) and
    through the XLA body (all three: 2^-20); with ONE part (the program as
    it was: `R.astype(dtype)`, and on the chip the XLA body's float32
    product at default precision) it is 2^-11 or more away, the rounding of
    a shared residual added up over its rows. The kernel's gA alone IS that
    one-part sum (to the order of a float32 sum on the CPU), and hA, g0A,
    h0A are what that program returned, to the bit."""
    n, d, Lb, live, loss, n_pad = case
    args = _shared_rows(n, d, Lb, live, seed=n + d)
    want = _gradient_f64(*args[:7])
    split = P.float32_parts

    def one_part_of_R(V, dtype, parts=None, **kw):
        """The XLA body's R arrives [lanes, rows of a block] with every
        part asked for; B [lanes, d] keeps its own."""
        whole = parts is None and V.shape[1] != d
        return split(V, dtype, 1 if whole else parts, **kw)
    assert min(GS._row_block(d), n) != d

    def run():
        try:    # neither program may outlive the parts it was traced with
            if body == "kernel":
                return _fused_raw(*args, loss, n_pad=n_pad)
            sums = _blocks(*args, loss)
            return sums[:4] + (0.0, sums[4])
        finally:
            PG.glm_moments.clear_cache()
            _blocks.clear_cache()
    got = run()
    with monkeypatch.context() as m:
        m.setattr(P, "float32_parts", one_part_of_R)
        m.setattr(PG, "residual_parts", lambda dtype: 1)
        one_part = run()
    assert _rel(got[0] + got[4], want) <= 2.0 ** (
        -15 if body == "kernel" else -20)
    assert _rel(one_part[0], want) >= 2.0 ** -11
    assert not np.asarray(one_part[4]).any()
    for a, b in zip(got[1:4], one_part[1:4]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    if body == "kernel":
        assert _rel(got[0], one_part[0]) <= 1e-6


# -- the Hessian's border -------------------------------------------------------

BORDER_CASES = {"rows-minor": (1300, 64, 8, 6, BF16, 236),
                "cols-minor": (700, 128, 64, 40, BF16, 0),
                "float32-matrix": (1300, 64, 8, 6, F32, 236)}


@pytest.mark.parametrize("case", BORDER_CASES.values(),
                         ids=BORDER_CASES.keys())
def test_the_sixth_sum_is_the_hessians_border(case, small_tiles):
    """cA = sum_rows S xs', the curvature-weighted column sums that couple
    the coefficients' step to the intercept's, against a numpy float64 twin
    with S rounded to the matrix's dtype once (1e-3 of the largest entry: a
    float32 sum, and the rare tie a last digit of a margin decides, 2^-9 of
    a row's term), in both
    tile forms and for a float32 matrix (which the kernel takes in interpret
    mode though no program routes one to it: one part of each operand, two
    slabs); the XLA body returns the same sum, last of its five; an inert
    lane reads zero; and h0A, the sum of S unrounded, is what cA's twin
    sums to against a column of ones."""
    n, d, Lb, live, dtype, n_pad = case
    X, y, w, masks, sel, Bt, b0, mean, std = _problem(
        n, d, Lb, live, seed=n + d + Lb, wide_scales=d == 128)
    X = X.astype(dtype)
    args = (X, y, w, masks, sel, Bt, b0, mean, std, "logistic")
    raw = _fused_raw(*args, n_pad=n_pad)
    blocks = _blocks(*args)
    assert len(raw) == 6 and len(blocks) == 5
    assert raw[5].shape == blocks[4].shape == (Lb, d)

    def low(v):
        return np.asarray(jnp.asarray(v, F32).astype(dtype).astype(F32),
                          np.float64)
    h = [np.asarray(v.astype(F32), np.float64)
         for v in (X, y, w, masks, sel, Bt, b0, mean, std)]
    xs = low((h[0] - h[7]) / h[8])
    p = 1 / (1 + np.exp(-(xs @ h[5].T + h[6])))
    S = np.maximum(p * (1 - p), 1e-6) * ((h[3].T * h[2][:, None]) @ h[4])
    want = low(S).T @ xs
    assert np.abs(want[:live]).max() > 1.0
    assert _rel(raw[5], want) <= 1e-3
    assert _rel(blocks[4], want) <= 1e-3
    assert (np.asarray(raw[5])[live:] == 0).all()
    assert (np.asarray(blocks[4])[live:] == 0).all()
    assert _rel(raw[3], S.sum(0)) <= 1e-6


def test_the_borders_slab_is_counted():
    """The curvature's slab stands after the residual's parts in the
    gradient's block — three slabs of a bfloat16 matrix, two of a float32
    one, each the lanes in whole sublane tiles — and `vmem_bytes` counts it:
    one more [lanes, chunk] operand a chunk of a body and, where the slabs
    outgrow a 128-column group, one more group of float32 sums (64 lanes:
    192 columns in 256; 32 lanes: 96 in the 128 it already had)."""
    assert PG._gradient_slabs(64, BF16) == (3, 64)
    assert PG._gradient_slabs(8, BF16) == (3, 16)
    assert PG._gradient_slabs(8, F32) == (2, 8)

    def without_border(d, lanes):
        """vmem_bytes with the residual's slabs alone, written out."""
        dp, lp = PG._padded(d, lanes, BF16)
        slabs, slab = PG.residual_parts(BF16), PG._round_up(lp, 16)
        tile = PG._CHUNK * PG._UNROLL * PG._TILE_BODIES
        return PG._UNROLL * (lp * dp + slabs * slab) * PG._CHUNK * 2 \
            + 2 * dp * (lp * dp + PG._round_up(slabs * slab, 128)) * 4 \
            + 2 * tile * (dp * 2 + 8 * 4 + 2 * 4) + 2 * 3 * lp * dp * 2
    operand = PG._UNROLL * PG._CHUNK * 2
    assert PG.vmem_bytes(128, 64) - without_border(128, 64) \
        == 64 * operand + 2 * 128 * 128 * 4
    assert PG.vmem_bytes(64, 32) - without_border(64, 32) == 32 * operand


@pytest.mark.parametrize("mosaic,no_pallas,d,dtype,lanes,vmem,says", [
    (False, False, 64, BF16, 32, None, "xla_blocks"),   # the CPU, Tier-1
    (True, False, 64, BF16, 32, None, "pallas_fused"),  # sweep-glm
    (True, False, 64, BF16, 8, None, "pallas_fused"),
    (True, False, 100, BF16, 128, None, "pallas_fused"),
    (True, False, 120, BF16, 32, None, "pallas_fused"),
    (True, False, 121, BF16, 32, None, "xla_blocks"),   # columns-minor: a copy
    (True, False, 127, BF16, 32, None, "xla_blocks"),
    (True, False, 128, BF16, 32, None, "pallas_fused"),  # cols_minor tiles
    (True, False, 128, BF16, 64, None, "pallas_fused"),  # sweep-glm-nulls128
    (False, False, 128, BF16, 64, None, "xla_blocks"),  # the CPU
    (True, False, 128, BF16, 256, None, "xla_blocks"),  # 170 MiB of VMEM
    (True, False, 128, F32, 64, None, "xla_blocks"),
    (True, False, 136, BF16, 32, None, "xla_blocks"),   # the feature tiles
    (True, False, 4104, BF16, 32, None, "xla_blocks"),
    (True, False, 64, F32, 32, None, "xla_blocks"),     # another precision
    (True, False, 64, jnp.float16, 32, None, "xla_blocks"),
    (True, True, 64, BF16, 32, None, "xla_blocks"),     # TMOG_NO_PALLAS
    (True, False, 64, BF16, 32, 10 << 20, "xla_blocks"),     # they do not fit
    (True, False, 64, BF16, 8, 10 << 20, "pallas_fused"),  # these do
], ids=["cpu", "sweep-glm", "bucket-8", "width-100", "120-columns",
        "121-columns", "127-columns", "128-columns", "nulls128",
        "nulls128-cpu", "128-columns-256-lanes", "128-columns-float32",
        "136-columns", "4104-columns", "float32", "float16",
        "TMOG_NO_PALLAS", "small-vmem", "small-vmem-bucket-8"])
def test_the_body_is_chosen_from_backend_width_dtype_and_vmem(
        monkeypatch, mosaic, no_pallas, d, dtype, lanes, vmem, says):
    """glm_round_kernel's table. TMOG_NO_PALLAS reaches it through
    pallas_hist.available() (the switch is read at import, so the case sets
    what it sets); VMEM as a v5e's unless the case gives another."""
    monkeypatch.setattr(jax, "default_backend",
                        lambda: "tpu" if mosaic else "cpu")
    monkeypatch.setattr(pallas_hist, "_enabled", not no_pallas)
    monkeypatch.setattr(pallas_hist, "_vmem_limit",
                        lambda: vmem or (96 << 20))
    assert GS.glm_round_kernel(d, dtype, lanes) == says
    assert PG.vmem_bytes(64, 32) < 24 << 20 < PG.vmem_bytes(128, 128)
    assert PG.vmem_bytes(128, 64) < 51 << 20
    # the families whose fused body reads X.T still leave 128 columns alone
    assert GS.round_kernel(128) == "xla_blocks"


# -- a whole round, a whole sweep ---------------------------------------------

N, D = 1536, 64


@pytest.fixture
def backend(monkeypatch, small_tiles):
    """backend(mosaic) makes the program choose as it would on a backend
    with (or without) Mosaic, the fused body interpreted: steered here, not
    by an option of the program. The round programs bake the choice in, so
    their caches go with every change of it."""
    monkeypatch.setattr(PG, "glm_moments", functools.partial(
        PG.glm_moments, interpret=True))
    monkeypatch.setattr(pallas_hist, "_vmem_limit", lambda: 96 << 20)

    def choose(mosaic: bool):
        monkeypatch.setattr(pallas_hist, "available", lambda: mosaic)
        GS.sweep_glm_round.clear_cache()
    yield choose
    GS.sweep_glm_round.clear_cache()


def _sweep_data(seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, D)).astype(np.float32)
    beta = rng.normal(size=D).astype(np.float32) / np.sqrt(D)
    y = (rng.uniform(size=N) < 1 / (1 + np.exp(-(X @ beta - 0.3)))) \
        .astype(np.float32)
    fold = rng.integers(0, FOLDS, size=N)
    masks = (fold[None, :] != np.arange(FOLDS)[:, None]).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=N).astype(np.float32)
    return (jnp.asarray(X).astype(BF16), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks))


@pytest.mark.parametrize("loss,fit_intercept", [
    ("logistic", True), ("logistic", False), ("squared_hinge", True)],
    ids=["intercept", "no-intercept", "squared-hinge"])
def test_a_whole_round_through_the_kernel(backend, loss, fit_intercept):
    """sweep_glm_round with either body around ONE iteration (the Newton
    solve, the proximal step, the intercept step): the same number of
    iterations and the same iterate to what the operands' rounding moves a
    Newton step by on the CPU (3e-3), inert lanes at rest; without an
    intercept b0 stays where it was."""
    X, y, w, masks = _sweep_data()
    mean, std = GS.glm_standardize_stats(X, w)
    Lb, live = 8, 5
    sel = np.zeros((FOLDS, Lb), np.float32)
    sel[np.arange(live) % FOLDS, np.arange(live)] = 1.0
    l2 = jnp.asarray([1e-3, 1e-2, 1e-1, 1e-3, 1e-2, 1, 1, 1], F32)
    args = (X, y, w, masks, jnp.asarray(sel), l2 * 0.5, l2,
            jnp.zeros((Lb, D), F32), jnp.zeros(Lb, F32), mean, std,
            jnp.asarray(4, jnp.int32), jnp.asarray(1e-9, F32))
    outs = []
    for mosaic in (False, True):
        backend(mosaic)
        assert GS.glm_round_kernel(D, X.dtype, Lb) \
            == ("pallas_fused" if mosaic else "xla_blocks")
        outs.append([np.asarray(v) for v in GS.sweep_glm_round(
            *args, loss=loss, fit_intercept=fit_intercept)])
    B, b0, delta, iters = zip(*outs)
    assert int(iters[0]) == int(iters[1]) == 4
    assert np.abs(B[0][:live]).max() > 1e-2
    np.testing.assert_allclose(B[1], B[0], rtol=0, atol=3e-3)
    np.testing.assert_allclose(b0[1], b0[0], rtol=0, atol=3e-3)
    assert (b0[1] == 0).all() != fit_intercept
    assert (B[1][live:] == 0).all() and (delta[1][live:] == 0).all()


def _streamed(regs=(0.01, 0.1)):
    X, y, w, masks = _sweep_data(seed=5)
    collector.disable()     # whatever an earlier test file left behind
    collector.enable("glm_round_kernel")
    try:
        B, b0, info = GS.sweep_glm_streamed_rounds(
            X, y, w, masks, np.float32(regs), np.float32([0.5] * len(regs)),
            loss="logistic", max_iter=6, tol=1e-6, round_iters=3,
            standardize=False)
        spans = [s for s in collector.trace.spans if s.kind == "sweep_round"]
    finally:
        collector.finish()
        collector.disable()
    return B, b0, info, spans


def test_telemetry_and_span_name_the_fused_body_where_it_runs(backend):
    """Where the backend has Mosaic the sweep runs the fused body and says
    so in `round_kernel` and on every round's span; its answer is the XLA
    body's to the operands' rounding; `kernel` stays the route's name."""
    backend(True)
    B, b0, info, spans = _streamed()
    assert info["round_kernel"] == "pallas_fused"
    assert info["kernel"] == "rounds"
    assert spans and {s.attrs["kernel"] for s in spans} == {"pallas_fused"}
    assert all(s.name.startswith("glm_round[") for s in spans)
    backend(False)
    B_x, b0_x, info_x, spans_x = _streamed()
    assert info_x["round_kernel"] == "xla_blocks"
    assert {s.attrs["kernel"] for s in spans_x} == {"xla_blocks"}
    assert info_x["iters_per_round"] == info["iters_per_round"]
    assert info_x["bucket_sizes"] == info["bucket_sizes"]
    np.testing.assert_allclose(B, B_x, rtol=0, atol=3e-3)
    np.testing.assert_allclose(b0, b0_x, rtol=0, atol=3e-3)


# -- the rounds converge --------------------------------------------------------

TOL, MAX_ITER = 1e-6, 50


def _null_tracked(n=8000, raw=8, seed=0):
    """A null-tracked table as transmogrify() makes it, bfloat16: `raw`
    fields at scales 2^-4 .. 2^4 and missing rates 0.01 .. 0.5, each
    followed by its 0/1 null indicator (the first at rate 0.01), the
    missing entries filled with the observed mean (a point mass there)."""
    rng = np.random.default_rng(seed)
    rates = np.logspace(-2, np.log10(0.5), raw)
    scale = 2.0 ** np.linspace(-4, 4, raw)
    loc = scale * rng.uniform(0.25, 2.0, raw) * rng.choice([-1, 1], raw)
    v = loc + scale * rng.normal(size=(n, raw))
    miss = rng.uniform(size=(n, raw)) < rates
    v = np.where(miss, np.nanmean(np.where(miss, np.nan, v), 0), v)
    X = np.empty((n, 2 * raw), np.float32)
    X[:, 0::2], X[:, 1::2] = v, miss
    Xs = (X - X.mean(0)) / X.std(0)
    beta = rng.normal(size=2 * raw) * 2.5 / np.sqrt(2 * raw)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(Xs @ beta - 1.5)))) \
        .astype(np.float32)
    fold = rng.integers(0, FOLDS, n)
    masks = (fold[None] != np.arange(FOLDS)[:, None]).astype(np.float32)
    return jnp.asarray(X).astype(BF16), jnp.asarray(y), jnp.asarray(masks)


def _converged_sweep(X, y, masks, standardize):
    regs = np.float32([0.01, 0.1, 0.2])
    st = GS._new_round_state(FOLDS * len(regs), X.shape[1])
    _, _, info = GS.sweep_glm_streamed_rounds(
        X, y, jnp.ones(X.shape[0], F32), masks, regs,
        np.float32([0.1] * len(regs)), loss="logistic", max_iter=MAX_ITER,
        tol=TOL, standardize=standardize, state=st)
    return info, st


@pytest.fixture(scope="module")
def null_tracked_sweeps():
    """The standardised sweep over the bfloat16 table, and the same sweep
    over the float32 copy of the block its passes see — bfloat16((x - mean)
    / std) as float32, not standardised again — whose margins never had a
    cast to lose the coefficients in."""
    X, y, masks = _null_tracked()
    assert (np.asarray(X.astype(F32))[:, 1] != 0).mean() < 0.015
    mean, std = GS.glm_standardize_stats(X, jnp.ones(X.shape[0], F32))
    assert float(std.min()) < 0.1 and float(std.max()) > 10
    block = ((X.astype(F32) - mean) / std).astype(BF16).astype(F32)
    return {"bf16": _converged_sweep(X, y, masks, True),
            "f32": _converged_sweep(block, y, masks, False)}


@pytest.mark.parametrize("which", ["bf16", "f32"])
def test_every_lane_retires_at_tol_and_none_at_the_cap(null_tracked_sweeps,
                                                       which):
    """The step is taken where the gradient was read, so delta falls
    through tol: no lane runs to max_iter, on the bfloat16 matrix as on its
    float32 copy. (With the margins at bfloat16(B) every lane's delta
    stayed at ~3e-3 and all nine ran to the cap, 55 passes.)"""
    info, st = null_tracked_sweeps[which]
    assert info["lanes_at_cap"] == 0
    assert info["lanes_retired"] == info["lanes_total"] == 9
    assert (st["delta"] <= TOL).all() and st["retired"].all()
    assert int(st["iters"].max()) < MAX_ITER
    assert info["data_passes"] < MAX_ITER


def test_the_rounds_on_bfloat16_are_the_rounds_on_the_float32_copy(
        null_tracked_sweeps):
    """The same retirement history and the same coefficients to float32
    noise (the parts' sum is xs' B to float32): the matrix's dtype rounds
    the block, once, and no longer the iterate."""
    (info, st), (info_f, st_f) = (null_tracked_sweeps[k]
                                  for k in ("bf16", "f32"))
    for key in ("data_passes", "iters_per_round", "bucket_sizes",
                "padded_lane_passes"):
        assert info[key] == info_f[key]
    assert np.abs(st_f["B"]).max() > 0.5
    np.testing.assert_allclose(st["B"], st_f["B"], rtol=0, atol=2e-6)
    np.testing.assert_allclose(st["b0"], st_f["b0"], rtol=0, atol=2e-6)


def test_the_benchmarks_twin_holds_the_sums_at_128_columns():
    """The benchmark's float64 twin of the kernel
    (benchmark/reference_nulls.moments_twin, written from the module's
    docstring: R rounded to ONE part of the matrix's dtype) on the inputs of
    its own Tier-1 test (tests/benchmark/test_benchmark_nulls.py): gA, hA,
    g0A, h0A within that test's limits still, since the second part's sum
    comes out BESIDE gA and not in it — and gA_low, which that twin does
    not know, against the twin's own lines carried one part further: 1e-4
    of gA's largest entry, the sum of the two within 2^-15 of the float64
    gradient with R unrounded, where gA alone is 2e-4 or more away."""
    from benchmark import reference_nulls as RN
    rng = np.random.default_rng(5)
    n, d, Lb, live, F = 640, 128, 8, 6, 3
    scale = 2.0 ** ((np.arange(d) * 3) % 10 - 5)
    X = jnp.asarray((rng.normal(size=(n, d)) * scale + 0.3 * scale)
                    .astype(np.float32)).astype(BF16)
    Xh = np.asarray(X.astype(F32))
    y = (rng.uniform(size=n) < 0.4).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    fold = rng.integers(0, F, size=n)
    masks = (fold[None, :] != np.arange(F)[:, None]).astype(np.float32)
    sel = np.zeros((F, Lb), np.float32)
    sel[rng.integers(0, F, size=live), np.arange(live)] = 1.0
    B = (rng.normal(size=(Lb, d)) * 0.1).astype(np.float32)
    B[live:] = 0.0
    Bt = jnp.asarray(B).astype(BF16)
    b0 = rng.normal(size=Lb).astype(np.float32)
    mean, std = Xh.mean(0), Xh.std(0)
    got = [np.asarray(v, np.float64) for v in PG.glm_moments(
        X, PG.dense_rows(jnp.asarray(y)), PG.dense_rows(jnp.asarray(w)),
        jnp.asarray(masks), jnp.asarray(sel), Bt, jnp.asarray(b0),
        jnp.asarray(mean), jnp.asarray(std), loss="logistic",
        x_tile="cols_minor", interpret=True)]
    assert len(got) == 6
    Bh = np.asarray(Bt.astype(F32))
    ref = RN.moments_twin(Xh, y, w, masks, sel, Bh, b0, mean, std)

    def off(a, r, of=None):
        assert a.shape == r.shape
        return np.abs(a - r).max() / np.abs(r if of is None else of).max()
    for a, r, tol in zip(got, ref, (1e-4, 1e-4, 1e-6, 1e-6)):
        assert off(a, r) <= tol
    # the twin's own lines, one part further
    xs = RN.as_bf16((Xh - mean) / std).astype(np.float64)
    p = 1.0 / (1.0 + np.exp(-(xs @ Bh.astype(np.float64).T + b0)))
    R = (p - y[:, None]) * ((masks.T * w[:, None]) @ sel)
    low = RN.as_bf16(R - RN.as_bf16(R).astype(np.float64)).astype(np.float64)
    assert off(got[4], low.T @ xs, of=ref[0]) <= 1e-4
    assert off(got[0] + got[4], R.T @ xs) <= 2.0 ** -15
    assert off(got[0], R.T @ xs) >= 2e-4
    # and the sixth, which that twin does not know either: sum S xs', S
    # rounded to one part
    S = np.maximum(p * (1 - p), 1e-6) * ((masks.T * w[:, None]) @ sel)
    assert off(got[5], RN.as_bf16(S).astype(np.float64).T @ xs) <= 1e-3
    assert all((v[live:] == 0).all() for v in got)


# -- the residual's floor -------------------------------------------------------

def _l1_sweep(X, y, masks, regs, alphas):
    """The standardised sweep over a grid of (reg, elastic-net) points;
    per-lane iterations and deltas in the state."""
    regs, alphas = np.float32(regs), np.float32(alphas)
    st = GS._new_round_state(FOLDS * len(regs), X.shape[1])
    _, _, info = GS.sweep_glm_streamed_rounds(
        X, y, jnp.ones(X.shape[0], F32), masks, regs, alphas,
        loss="logistic", max_iter=MAX_ITER, tol=TOL, standardize=True,
        state=st)
    return info, st


def _strong_l1_sweep(X, y, masks):
    """A grid that holds the default grid's strong-L1 point (reg 0.1 x
    elastic-net 0.5: the lane that cycled on the residual's rounding
    floor), a slow ridge-like point and a fast one."""
    return _l1_sweep(X, y, masks, [0.01, 0.1, 0.1], [0.1, 0.1, 0.5])


def test_the_xla_body_takes_the_whole_residual(monkeypatch):
    """The XLA body contracts R in ALL its parts, which is the float32
    product it had on the CPU and more than the chip's default precision
    gave it. Lanes that the penalty shrank to one or two coefficients
    share a handful of residuals over all their rows, and there even two
    parts floor delta over tol (2^-17 |R| does not average out): on 32 768
    rows of a null-tracked table every lane of such a grid retires in 12
    passes, and with R cut to two parts two lanes run to max_iter."""
    X, y, masks = _null_tracked(n=32768, seed=1)
    grid = [0.2, 0.2, 0.3], [0.5, 0.3, 0.5]
    GS.sweep_glm_round.clear_cache()
    info, st = _l1_sweep(X, y, masks, *grid)
    assert info["round_kernel"] == "xla_blocks"
    assert info["lanes_at_cap"] == 0 and info["data_passes"] <= 14
    assert (st["delta"] <= TOL).all()
    assert ((np.asarray(st["B"]) != 0).sum(1) <= 4).all()
    split, d = P.float32_parts, X.shape[1]

    def two_parts_of_R(V, dtype, parts=None, **kw):
        """R arrives [lanes, rows of a block] with every part asked for."""
        whole = parts is None and V.shape[1] != d
        return split(V, dtype, 2 if whole else parts, **kw)
    monkeypatch.setattr(P, "float32_parts", two_parts_of_R)
    GS.sweep_glm_round.clear_cache()
    try:
        info_2, st_2 = _l1_sweep(X, y, masks, *grid)
    finally:
        GS.sweep_glm_round.clear_cache()
    assert info_2["lanes_at_cap"] >= 1 and info_2["data_passes"] == 55
    assert st_2["delta"].max() > TOL


def test_the_kernels_lanes_retire_where_the_xla_bodys_do(backend,
                                                         monkeypatch):
    """The floor itself, through the interpreted kernel: on 32 768 rows of
    a null-tracked table the kernel that casts the residual x weight to ONE
    bfloat16 part (the program as it was) leaves deltas of ~2e-5 and most
    lanes at max_iter, 55 passes; with the two parts every lane retires at
    tol, the strong-L1 point with the others, and round for round and lane
    for lane where the XLA body's lanes do (the coefficients apart by what
    the Gram's own bfloat16 operand moves a thresholded fixed point)."""
    # (seed 1, not 5: there the warm round's lane ends its fifth iteration
    # at 9.1e-7 through the kernel and just over tol through the XLA body,
    # a round more in one history; the one-part kernel runs to the cap on
    # both)
    X, y, masks = _null_tracked(n=32768, seed=1)
    backend(True)
    info, st = _strong_l1_sweep(X, y, masks)
    assert info["round_kernel"] == "pallas_fused"
    backend(False)
    info_x, st_x = _strong_l1_sweep(X, y, masks)
    assert info_x["round_kernel"] == "xla_blocks"
    for got in (info, info_x):
        assert got["lanes_at_cap"] == 0
        assert got["lanes_retired"] == got["lanes_total"] == 9
    for key in ("iters_per_round", "data_passes", "bucket_sizes",
                "active_per_round", "padded_lane_passes"):
        assert info[key] == info_x[key]
    assert info["data_passes"] < 30
    assert np.array_equal(st["iters"], st_x["iters"])
    assert (st["delta"] <= TOL).all() and (st_x["delta"] <= TOL).all()
    np.testing.assert_allclose(st["B"], st_x["B"], rtol=0, atol=1e-4)
    monkeypatch.setattr(PG, "residual_parts", lambda dtype: 1)
    backend(True)
    PG.glm_moments.func.clear_cache()
    info_1, st_1 = _strong_l1_sweep(X, y, masks)
    assert info_1["lanes_at_cap"] >= 6 and info_1["data_passes"] == 55
    assert 5e-6 < st_1["delta"].max() < 1e-3
