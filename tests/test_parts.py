"""ops/parts.py — the ONE loop that cuts a float32 into parts of a dtype and
the fixed-point cut that stands beside it — and utils/platform's rule that a
lowered operation's location is its own frame, not its callers' stack."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.ops import parts as P

F32, BF16 = jnp.float32, jnp.bfloat16


def _through(a, dtype):
    """float32 `a` cast to `dtype` and back, on the host."""
    return np.asarray(jnp.asarray(a).astype(dtype).astype(F32))


def _inputs():
    """(values, exact): float32 values of any size and both signs — normal
    draws over twelve decades, tiny ones, powers of two and their float32
    neighbours on either side — and values that bfloat16 holds exactly."""
    rng = np.random.default_rng(0)
    two_k = 2.0 ** np.arange(-12, 13, dtype=np.float32)
    values = np.concatenate([
        rng.normal(size=256) * 10.0 ** rng.integers(-6, 6, 256),
        rng.normal(size=32) * 1e-30,
        two_k, -two_k,
        np.nextafter(two_k, np.float32(np.inf)),
        np.nextafter(two_k, np.float32(0.0)),
        -np.nextafter(two_k, np.float32(np.inf)),
    ]).astype(np.float32)
    exact = _through(np.concatenate([values[:256], two_k, -two_k, [0.0]])
                     .astype(np.float32), BF16)
    return values, exact


@pytest.mark.parametrize("in_kernel", [False, True],
                         ids=["reduce-precision", "in-kernel-casts"])
@pytest.mark.parametrize("dtype,k", [(BF16, 1), (BF16, 2), (BF16, 3),
                                     (F32, 1)],
                         ids=["bf16-1", "bf16-2", "bf16-3", "f32-1"])
def test_the_loop_cuts_a_float32_into_parts(dtype, k, in_kernel):
    """`k` float32 arrays, largest first, whose float32 sum is V to the bit;
    every part but the last exact in `dtype`, the last too where all the
    parts were taken; cast to `dtype`, two parts of bfloat16 are within
    2^-17 |V| (2^-(8k + 1); one part is the rounding itself, 2^-8 |V|); a V
    that is exact in `dtype` leaves every later part zero; and ONE part is V
    itself, untouched."""
    values, exact = _inputs()
    for V in (values, exact):
        cut = P.float32_parts(jnp.asarray(V), dtype, k, in_kernel=in_kernel)
        assert len(cut) == k
        cut = [np.asarray(p) for p in cut]
        assert all(p.dtype == np.float32 and p.shape == V.shape for p in cut)
        total = cut[-1]
        for p in cut[-2::-1]:
            total = total + p
        assert np.array_equal(total, V)
        whole = k == P.n_parts(dtype)
        for p in cut if whole else cut[:-1]:
            assert np.array_equal(_through(p, dtype), p)
        held = sum(_through(p, dtype).astype(np.float64) for p in cut)
        bound = 0.0 if whole else 2.0 ** -(8 if k == 1 else 8 * k + 1)
        assert (np.abs(held - V) <= bound * np.abs(V)).all()
        if V is exact:
            assert all(not p.any() for p in cut[1:])
    # asked for more parts than the dtype has: a float32 matrix has one
    assert len(P.float32_parts(jnp.asarray(values), dtype, 3)) \
        == P.n_parts(dtype)
    x = jnp.asarray(values)
    assert P.float32_parts(x, dtype, 1, in_kernel=in_kernel)[0] is x


def test_n_parts_and_slab_sum():
    assert P.n_parts(BF16) == 3 and P.n_parts(F32) == 1
    assert P.n_parts(jnp.float16) == 3
    values, _ = _inputs()
    V = jnp.asarray(values[:256].reshape(4, 64))
    stacked = jnp.concatenate(P.float32_parts(V, BF16), axis=0)
    assert np.array_equal(np.asarray(P.slab_sum(stacked, 4)), np.asarray(V))
    assert np.array_equal(np.asarray(P.slab_sum(stacked.T, 4, axis=1)),
                          np.asarray(V.T))


def test_the_parts_of_a_jitted_programs_parameter_keep_their_low_part():
    """What an `astype` round trip could lose (PERF.md §6, PR 29: fused, it
    came back unrounded for a program parameter on the v5e and the low part
    was zero): inside a jitted program the cut is `reduce_precision`, no
    cast and back, and the low part of a parameter is not zero."""
    values, _ = _inputs()
    B = jnp.asarray(values[:256].reshape(4, 64))

    def two(v):
        return [p.astype(BF16) for p in P.float32_parts(v, BF16, 2)]
    hi, lo = (np.asarray(p.astype(F32)) for p in jax.jit(two)(B))
    assert np.array_equal(hi, _through(B, BF16))
    assert (lo != 0).mean() > 0.95
    assert (np.abs(hi + lo - np.asarray(B))
            <= 2.0 ** -17 * np.abs(np.asarray(B))).all()
    prims = [e.primitive.name for e in jax.make_jaxpr(two)(B).eqns]
    assert prims.count("reduce_precision") == 1
    # the casts are the one-way ones of the two results
    assert prims.count("convert_element_type") == 2
    # a Mosaic body has no reduce_precision: there the cast and back is it
    inside = jax.make_jaxpr(lambda v: P.float32_parts(
        v, BF16, 2, in_kernel=True))(B)
    assert "reduce_precision" not in str(inside)


def test_unit_cuts_are_fixed_point_on_the_unit_interval():
    """Three float32 arrays that sum to x to the bit on [-1, 1]: the first
    two whole multiples of 2^-7 and 2^-15 that bfloat16 holds exactly, the
    rest under 2^-16, which the cast to bfloat16 rounds at 2^-25."""
    rng = np.random.default_rng(1)
    x = np.concatenate([
        rng.uniform(-1, 1, 4096), rng.uniform(-1, 1, 256) * 1e-4,
        [0.0, 1.0, -1.0, 0.5, 2.0 ** -7, 2.0 ** -15, 2.0 ** -16,
         1.0 - 2.0 ** -24, -1.0 + 2.0 ** -24, 255.0 / 256, 257.0 / 65536],
    ]).astype(np.float32)
    hi, mid, lo = (np.asarray(p) for p in P.unit_cuts(jnp.asarray(x)))
    for p in (hi, mid):
        assert p.dtype == np.float32
        assert np.array_equal(_through(p, BF16), p)
    assert np.abs(_through(lo, BF16) - lo).max() <= 2.0 ** -25
    assert np.array_equal(lo + mid + hi, x)
    assert np.array_equal(hi * 128.0, np.round(hi * 128.0))
    assert np.array_equal(mid * 32768.0, np.round(mid * 32768.0))
    assert np.abs(lo).max() <= 2.0 ** -16
    # their sums over many rows are whole numbers in their own units
    assert float(np.sum(hi.astype(np.float64)) * 128.0).is_integer()


def _probe(x):
    """A small jitted function: its operations' own frames are these lines."""
    return jnp.tanh(x) * 2.0 + 1.0


def test_a_lowered_location_is_the_operations_own_frame():
    """After `import transmogrifai_tpu` a lowered operation's location is its
    ONE innermost frame, function name and all, and not the stack of its
    callers (utils/platform.enable_compilation_cache: a Mosaic body is
    serialised with its locations, so a caller's line numbers were part of
    every kernel's compile-cache key): the lowered text WITH debug info is
    byte-equal when the caller's source is compiled with and without leading
    blank lines. With jax's default, ten frames, the same two texts differ."""
    import transmogrifai_tpu  # noqa: F401
    flag = "jax_traceback_in_locations_limit"
    assert getattr(jax.config, flag) == 1
    assert jax.config.jax_include_full_tracebacks_in_locations is True

    def lowered_twice():
        texts = []
        for blanks in (0, 3):
            ns = {"jax": jax, "probe": _probe, "x": jnp.ones((8,), F32)}
            exec(compile("\n" * blanks + "def call():\n    return jax.jit("
                         "lambda v: probe(v)).lower(x)\n",
                         "caller_of_test_parts.py", "exec"), ns)
            texts.append(ns["call"]().as_text(debug_info=True))
        return texts
    own, moved = lowered_twice()
    assert own == moved and "test_parts.py" in own and '"_probe"' in own
    assert "caller_of_test_parts.py" not in own
    jax.config.update(flag, 10)
    try:
        own, moved = lowered_twice()
    finally:
        jax.config.update(flag, 1)
    assert own != moved and "caller_of_test_parts.py" in own
