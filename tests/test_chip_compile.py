"""Kernels of the main path compiled for a DESCRIBED TPU v5e at the widths
the benchmark runs them at: the TPU's compiler is installed here and refuses
what the chip's would (a slice off the tiling, too much VMEM, a layout it
cannot take), at no chip time. Nothing runs, so nothing here is a result or
a time. The topology is described inside a fixture and never at import: one
process at a time may load the TPU's library (on-chip-measurement guide).
"""
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.ops import glm_sweep as GS
from transmogrifai_tpu.ops import pallas_hist
from transmogrifai_tpu.utils import platform as P

F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_v5e(monkeypatch):
    """What the program asks of its backend answered as on the chip (the VMEM
    figures the kernels size themselves by; Mosaic is there), and no
    persistent cache: what is compiled for a described chip cannot be read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(P, "device_spec",
                        lambda kind=None: P.DEVICE_SPECS["TPU v5 lite"])
    monkeypatch.setattr(pallas_hist, "available", lambda: True)
    rounds = (GS.sweep_mlr_round, GS.sweep_glm_wide_round,
              GS.sweep_glm_round, GS._sharded_round_fn)
    for fn in rounds:
        fn.clear_cache()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    for fn in rounds:
        fn.clear_cache()
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _mosaic_call_named(compiled, name: str) -> bool:
    """Is a Mosaic custom call of the compiled program an instruction NAMED
    `name` (`%glm_moments.4 = ... custom-call(...)`)? The chip's compiler
    names it after the function of the call's innermost frame, the traces
    show that name and the benchmark's readers match it: with
    `jax_include_full_tracebacks_in_locations` off every one reads
    `tpu_custom_call.N` (PERF.md §6, PR 56)."""
    import re
    return re.search(rf"%?{name}[.\d]* = [^\n]*custom-call\(",
                     compiled.as_text()) is not None


def _round_shapes(one_chip, n, d, K, Lb, F, dtype):
    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return (S((n, d), dtype), S((n,), F32), S((n,), F32), S((F, n), F32),
            S((F, Lb), F32), S((Lb,), F32), S((Lb,), F32), S((Lb, d, K), F32),
            S((Lb, K), F32), S((d,), F32), S((d,), F32), S((Lb, d, d), F32),
            S((Lb, d), F32), S((), jnp.int32), S((), F32))


@pytest.mark.parametrize("n,d,K,Lb,F,dtype", [
    (25_000_000, 64, 32, 16, 5, BF16),      # sweep-mlr-k32's round
    (25_000_000, 64, 32, 1, 5, BF16),       # its smallest bucket
    (1_000_003, 37, 3, 4, 3, BF16),         # ragged rows, columns, classes
    (1_000_003, 100, 2, 2, 3, F32),
], ids=["k32-bucket16", "k32-bucket1", "ragged-bf16", "ragged-f32"])
def test_fused_multinomial_round_compiles_for_a_v5e(
        one_chip, as_v5e, n, d, K, Lb, F, dtype):
    """The whole round program around the fused pass: Mosaic takes the
    kernel, and the program holds no second copy of X (the matrix lives
    rows-minor on the chip, so X.T is the layout it has)."""
    compiled = GS.sweep_mlr_round.lower(
        *_round_shapes(one_chip, n, d, K, Lb, F, dtype),
        fit_intercept=True).compile()
    assert _mosaic_call_named(compiled, "mlr_gradient")
    x_bytes = n * d * jnp.dtype(dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < 0.25 * x_bytes


def test_a_128_column_matrix_would_be_copied(one_chip, as_v5e, monkeypatch):
    """Why round_kernel leaves 128 columns to the XLA blocks: the chip
    keeps such a matrix columns-minor, and the fused round would hold a
    transposed copy of it beside the original."""
    n, d = 4_000_000, 128
    assert GS.round_kernel(d) == "xla_blocks"
    monkeypatch.setattr(GS, "round_kernel", lambda d: "pallas_fused")
    compiled = GS.sweep_mlr_round.lower(
        *_round_shapes(one_chip, n, d, 5, 8, 5, BF16),
        fit_intercept=True).compile()
    assert compiled.memory_analysis().temp_size_in_bytes >= n * d * 2


def _wide_round_shapes(one_chip, n, d, Lb, F, dtype):
    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return (S((n, d), dtype), S((n,), F32), S((n,), F32), S((F, n), F32),
            S((F, Lb), F32), S((Lb,), F32), S((Lb,), F32), S((Lb, d), F32),
            S((Lb,), F32), S((d,), F32), S((d,), F32), S((d, d), F32),
            S((), F32), S((), jnp.int32), S((), F32))


@pytest.mark.parametrize("n,d,Lb,F,tile", [
    (786_432, 4_104, 64, 5, 2_048),     # sweep-glm-wide4k's round
    (786_432, 4_104, 8, 5, 2_048),      # its smallest bucket
    (100_003, 264, 16, 3, 2_048),       # ragged rows, a width off the
                                        # 16-row tile
    (100_003, 8_200, 128, 5, 1_024),    # a width that halves the tile, at
                                        # the bucket the VMEM model is for
], ids=["wide4k-bucket64", "wide4k-bucket8", "ragged", "half-tile"])
def test_fused_wide_round_compiles_for_a_v5e(one_chip, as_v5e, n, d, Lb, F,
                                             tile):
    """The whole wide round program around the fused pass: Mosaic takes the
    kernel at the tile `pallas_wide.tile_rows` chooses for the chip's VMEM,
    and the program holds no second copy of X."""
    from transmogrifai_tpu.ops import pallas_wide
    assert GS.wide_round_kernel(d, BF16) == "pallas_fused"
    assert pallas_wide.tile_rows(d) == tile
    compiled = GS.sweep_glm_wide_round.lower(
        *_wide_round_shapes(one_chip, n, d, Lb, F, BF16),
        fit_intercept=True).compile()
    assert _mosaic_call_named(compiled, "wide_gradient")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.25 * n * d * 2


def _glm_round_shapes(S, n, d, Lb, F, row=None, cols=None):
    """sweep_glm_round's arguments; `row` / `cols` shard the row axis of
    the vectors / the masks on a mesh."""
    return (S((n, d), BF16, row), S((n,), F32, row), S((n,), F32, row),
            S((F, n), F32, cols), S((F, Lb), F32), S((Lb,), F32),
            S((Lb,), F32), S((Lb, d), F32), S((Lb,), F32), S((d,), F32),
            S((d,), F32), S((), jnp.int32), S((), F32))


@pytest.mark.parametrize("n,d,Lb,F,loss", [
    (25_000_000, 64, 32, 5, "logistic"),        # sweep-glm's round
    (25_000_000, 64, 8, 5, "logistic"),         # its smallest bucket
    (1_000_003, 100, 16, 3, "squared_hinge"),   # ragged rows and columns
    (1_000_003, 37, 128, 3, "logistic"),        # the largest bucket
    # sweep-glm-nulls128's round: [rows, 128] tiles of X as the chip keeps
    # it, turned over in VMEM; 64 lanes hold 49 MiB of its 96
    (25_000_000, 128, 64, 5, "logistic"),
], ids=["glm-bucket32", "glm-bucket8", "ragged-hinge", "bucket128",
        "nulls128-bucket64"])
def test_fused_binary_round_compiles_for_a_v5e(one_chip, as_v5e, n, d, Lb, F,
                                               loss):
    """The whole binary round program around the fused pass: Mosaic takes
    the kernel (the [lanes, 1, rows] x [1, d, rows] broadcast, the cast
    and the merge of its leading dimensions among the rest), and the
    program holds no copy of X, padded or transposed: what it keeps beside
    the matrix is y and w laid out for the kernel."""
    def S(shape, dt, _=None):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    assert GS.glm_round_kernel(d, BF16, Lb) == "pallas_fused"
    compiled = GS.sweep_glm_round.lower(
        *_glm_round_shapes(S, n, d, Lb, F), loss=loss,
        fit_intercept=True).compile()
    assert _mosaic_call_named(compiled, "glm_moments")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.25 * n * d * 2
    if d == 128:    # the cell's bound; the XLA body there holds 7.4 GB
        assert GS.glm_x_tile(d) == "cols_minor"
        assert compiled.memory_analysis().temp_size_in_bytes \
            < 0.05 * n * d * 2


def test_a_121_column_matrix_would_be_copied(one_chip, as_v5e, monkeypatch):
    """Why round_kernel leaves 121 to 128 columns to the XLA blocks:
    rows-minor such a matrix pads to the size it has columns-minor, the
    chip keeps it columns-minor, and the fused round would hold a
    transposed copy of it. 120 columns live rows-minor and are read in
    place."""
    def S(shape, dt, _=None):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    n = 4_000_003
    assert GS.round_kernel(120) == "pallas_fused"
    assert GS.round_kernel(121) == GS.round_kernel(127) == "xla_blocks"
    monkeypatch.setattr(GS, "round_kernel", lambda d: "pallas_fused")
    for d, copied in ((120, False), (121, True)):
        compiled = GS.sweep_glm_round.lower(
            *_glm_round_shapes(S, n, d, 8, 5), loss="logistic",
            fit_intercept=True).compile()
        assert "glm_moments" in compiled.as_text()
        assert (compiled.memory_analysis().temp_size_in_bytes
                >= n * d * 2) == copied


def test_fused_binary_round_compiles_for_the_four_chip_mesh(topo, as_v5e):
    """sweep-glm-4chip's round: the kernel inside the shard_map over every
    chip's 32M local rows, the ONE all-reduce an iteration (and the fold
    weight sums') still there, no copy of a chip's rows."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as Ps
    from transmogrifai_tpu.parallel.mesh import BATCH_AXIS, MODEL_AXIS
    mesh = Mesh(np.array(topo.devices).reshape(4, 1),
                (BATCH_AXIS, MODEL_AXIS))

    def S(shape, dt, spec=None):
        return jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(
            mesh, spec or Ps()))
    n, d = 128_000_000, 64
    compiled = GS._sharded_round_fn(mesh, "logistic", True).lower(
        *_glm_round_shapes(S, n, d, 32, 5, Ps(BATCH_AXIS),
                           Ps(None, BATCH_AXIS))).compile()
    text = compiled.as_text()
    assert "glm_moments" in text
    assert text.count(" all-reduce(") + text.count(" all-reduce-start(") == 2
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 0.25 * n // 4 * d * 2


def _heldout_eval_shapes(S, n, d, F, Gc, row=None, cols=None):
    """_streamed_eval_heldout's arguments; `row` / `cols` as above."""
    return (S((n, d), BF16, row), S((n,), F32, row), S((n,), F32, row),
            S((F, n), F32, cols), S((F, Gc, d), F32), S((F, Gc), F32))


@pytest.fixture
def metric_programs(as_v5e, monkeypatch):
    """The lane-batched binned counts take the pallas route, as on the
    chip, and no metric program traced under another answer survives."""
    from transmogrifai_tpu.automl.tuning import validators as V
    from transmogrifai_tpu.ops import metrics_ops as M
    monkeypatch.setattr(M, "_pallas_route", lambda: True)
    fns = (V._streamed_eval_heldout, V._sharded_eval_heldout_fn)
    for fn in fns:
        fn.clear_cache()
    yield V
    for fn in fns:
        fn.clear_cache()


@pytest.mark.parametrize("n,Gc,unit", [
    (25_000_000, 6, True),      # sweep-glm's metric pass: one payload part
    (25_000_000, 6, False),     # sample weights handed in: three
    (25_000_000, 8, True),      # sweep-glm-nulls128's chunk of 8 points
    (1_000_003, 3, False),      # ragged rows
], ids=["glm-unit", "glm-weights", "nulls-chunk8", "ragged"])
def test_heldout_metric_pass_compiles_for_a_v5e(one_chip, metric_programs,
                                                n, Gc, unit):
    """The whole held-out-once metric program around the two-level
    histogram body (ops/pallas_rank_hist.py): Mosaic takes the kernel —
    the integer shifts and masks of the bins, the payload's cuts, the
    [rows, 1, blk] x [1, hi, blk] broadcast and its merge, the contraction
    over the block's rows — at the block the chip's VMEM is asked for."""
    def S(shape, dt, _=None):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = metric_programs._streamed_eval_heldout.lower(
        *_heldout_eval_shapes(S, n, 64, 5, Gc), unit, metric="au_pr",
        rank_bins=4096).compile()
    assert _mosaic_call_named(compiled, "_hist_two_level_jit")
    assert "_hist_pallas_jit" not in compiled.as_text()


@pytest.mark.parametrize("unit", [True, False], ids=["unit", "weights"])
def test_lane_metric_call_compiles_for_a_v5e(one_chip, metric_programs,
                                             unit):
    """The tree route's fold_metrics form at sweep-gbt's shape: F = 1, the
    ten lanes as slots over 100M flattened elements, 640 left rows a
    payload part."""
    from transmogrifai_tpu.ops import metrics_ops as M
    L, n = 10, 10_000_000
    fn = jax.jit(lambda s, y, wl: M.au_pr_binned_lanes(
        s, y, wl, 4096, unit_payload=unit))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((L, n), F32, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), F32, sharding=one_chip),
        jax.ShapeDtypeStruct((L, n), F32, sharding=one_chip)).compile()
    assert "_hist_two_level_jit" in compiled.as_text()


@pytest.mark.slow     # three sorts: ~100 s of the TPU's compiler
def test_fold_program_compiles_for_the_four_chip_mesh(topo, as_v5e):
    """sweep-glm-4chip's fold program: a chip sorts its own 32M keys and
    the places it receives, ONE all-to-all and ONE all-reduce between
    them; the 128M-key sort is there once, under the conditional that
    answers an overflow, and the program's temporaries stay within a run's
    places of that sort's own (3.58 GB at the parent, by this compiler)."""
    import re
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as Ps
    from transmogrifai_tpu.automl.tuning import folds
    from transmogrifai_tpu.parallel.mesh import BATCH_AXIS, MODEL_AXIS
    mesh = Mesh(np.array(topo.devices).reshape(4, 1),
                (BATCH_AXIS, MODEL_AXIS))
    n = 128_000_000
    _, capacity = folds._partition_plan(n, 4)
    compiled = folds._sharded_fold_masks_fn(mesh, n, 5, None, False).lower(
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=NamedSharding(
            mesh, Ps()))).compile()
    text = compiled.as_text()
    sorts = sorted(int(k) for k in re.findall(
        r"= \(u32\[(\d+)\][^=]* sort\(", text))
    assert sorts == [n // 4, 4 * capacity, n]
    assert len(re.findall(r" all-to-all(-start)?\(", text)) == 1
    assert len(re.findall(r" all-reduce(-start)?\(", text)) == 1
    assert " conditional(" in text and "all-gather" not in text
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 3.6e9 + 4 * (n // 4 + capacity)


def test_heldout_metric_pass_compiles_for_the_four_chip_mesh(
        topo, metric_programs):
    """sweep-glm-4chip's metric pass: the two-level body inside the
    shard_map over every chip's 32M local rows, ONE all-reduce of the
    counts."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as Ps
    from transmogrifai_tpu.parallel.mesh import BATCH_AXIS, MODEL_AXIS
    mesh = Mesh(np.array(topo.devices).reshape(4, 1),
                (BATCH_AXIS, MODEL_AXIS))

    def S(shape, dt, spec=None):
        return jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(
            mesh, spec or Ps()))
    compiled = metric_programs._sharded_eval_heldout_fn(
        mesh, "au_pr", 4096).lower(*_heldout_eval_shapes(
            S, 128_000_000, 64, 5, 6, Ps(BATCH_AXIS),
            Ps(None, BATCH_AXIS)), True).compile()
    text = compiled.as_text()
    assert "_hist_two_level_jit" in text
    assert text.count(" all-reduce(") + text.count(" all-reduce-start(") == 1


@pytest.mark.parametrize("d", [128, 64], ids=["linreg-128", "cols-64"])
def test_regression_sweep_programs_compile_for_a_v5e(one_chip,
                                                     metric_programs, d):
    """sweep-linreg-nulls128's programs at 25M rows, and the same at the
    width the chip keeps rows-minor: the Gram pass reads X in place (a
    block's temporaries, a few MB: a padded, transposed or float32 copy of
    X would be 6.4 GB or more), its two raw branches contract bfloat16
    operands at the default precision (the block against 1 and against 3
    parts of the fold-weighted block, and each one's first-order sums) and
    `highest` is the float32 branch's, under the guard; the held-out pass
    of sums holds a few [rows] vectors (under 5 % of the 128-column X),
    neither a [Gc, rows] array of scores; the moment-space solves hold
    nothing of the rows' size."""
    import re
    n, F, Gc = 25_000_000, 5, 8
    x_bytes = n * d * 2

    def S(shape, dt=F32, _=None):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    gram = GS.sweep_gram_moments.lower(
        S((n, d), BF16), S((n,)), S((n,)), S((F, n)), S((d,)), S((d,))
    ).compile()
    assert gram.memory_analysis().temp_size_in_bytes <= 16 << 20
    text = gram.as_text()
    dots = [ln for ln in text.splitlines() if " convolution(" in ln]
    blocks = [ln for ln in dots if "operand_precision={highest,highest}" in ln]
    raw = [ln for ln in dots if "highest" not in ln]
    assert len(blocks) == 3 and len(raw) == 4 and " conditional(" in text
    for ln in raw:
        for operand in re.search(r" convolution\((%[\w.-]+), (%[\w.-]+)\)",
                                 ln).groups():
            assert re.search(re.escape(operand) + r" = bf16\[", text), ln
    for parts in (1, 3):
        assert any(f"= f32[{d},{parts * F * d}]" in ln for ln in raw)
    solve = GS.sweep_gram_solve.lower(
        S((F, d, d)), S((F, d)), S((F, d)), S((F,)), S((F,)), S((d,)),
        S((d,)), S((Gc,)), S((Gc,)), S((), jnp.int32), S(()),
        fit_intercept=True).compile()
    assert solve.memory_analysis().temp_size_in_bytes < 64 << 20
    metric = metric_programs._streamed_eval_heldout.lower(
        *_heldout_eval_shapes(S, n, d, F, Gc), False, metric="rmse",
        rank_bins=4096).compile()
    assert metric.memory_analysis().temp_size_in_bytes \
        <= 0.06 * n * 128 * 2
    assert "_hist_two_level_jit" not in metric.as_text()


def test_three_part_histogram_kernels_compile_for_a_v5e(one_chip, as_v5e):
    """sweep-rf-regression's histogram kernels at 10M rows x 64 columns, 33
    bins, the 15 lanes plan_forest_group gives five payload rows a (lane,
    slot): the root pass and the deepest fused route-and-histogram pass
    (16 slots: a [1 200, 2 112] float32 output block, 10.1 MB of VMEM) with
    weight x (label - centre) cut into three bfloat16 parts in the kernel
    (bit masks: Mosaic lowers no reduce_precision)."""
    n, F, B, lanes, nodes = 10_002_432, 64, 33, 15, 16
    assert pallas_hist.plan_forest_group(10_000_000, F, B, 5, 10, 6, 5) * 5 \
        == lanes

    def S(shape, dt=F32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    kw = dict(n_bins=B, interpret=False, use_bf16=True, derive_count=True,
              parts=3)
    root = pallas_hist._hist_pallas_jit.lower(
        S((F, n), jnp.int8), S((2 * lanes, n)), S((lanes, n)), n_slots=1,
        **kw).compile()
    deep = pallas_hist._route_hist_pallas_jit.lower(
        S((F, n), jnp.int8), S((2 * lanes, n)), S((lanes, n)),
        *[S((lanes, nodes), jnp.int32)] * 3, n_nodes=nodes, **kw).compile()
    for compiled in (root, deep):
        assert "tpu_custom_call" in compiled.as_text()


def test_class_channel_histogram_kernels_compile_for_a_v5e(one_chip, as_v5e):
    """sweep-rf-multiclass's histogram kernels at 10M rows x 64 columns, 33
    bins, K = 7 classes: the 10 lanes plan_forest_group gives K + 1 = 8 rows
    a (lane, slot) — the root pass and the deepest fused pass (16 slots: a
    [1 280, 2 112] float32 output block, 10.8 MB of VMEM), the class
    channels weight x (id == k) built in the kernel from [class id,
    weight]."""
    n, F, B, K, lanes, nodes = 10_002_432, 64, 33, 7, 10, 16
    rows = pallas_hist.payload_rows(2, 1, True, K)
    assert rows == K + 1 and pallas_hist.plan_forest_group(
        10_000_000, F, B, 5, 10, 6, rows, classes=K) * 5 == lanes

    def S(shape, dt=F32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    kw = dict(n_bins=B, interpret=False, use_bf16=True, derive_count=True,
              classes=K)
    root = pallas_hist._hist_pallas_jit.lower(
        S((F, n), jnp.int8), S((2 * lanes, n)), S((lanes, n)), n_slots=1,
        **kw).compile()
    deep = pallas_hist._route_hist_pallas_jit.lower(
        S((F, n), jnp.int8), S((2 * lanes, n)), S((lanes, n)),
        *[S((lanes, nodes), jnp.int32)] * 3, n_nodes=nodes, **kw).compile()
    for compiled in (root, deep):
        assert "tpu_custom_call" in compiled.as_text()
    assert f"f32[{lanes * nodes * rows},{F * B}]" in deep.as_text()


def test_a_mosaic_bodys_bytes_do_not_hold_its_callers_lines():
    """The serialised Mosaic body inside the custom call (lowered for the TPU
    here, nothing compiled) is byte-equal when the CALLER's source is
    compiled with and without three leading blank lines: one frame of
    traceback a location (utils/platform.enable_compilation_cache). With
    jax's ten frames the same two bodies differ, which moved the kernel's
    compile-cache key with every edit above its call site (PERF.md §6,
    PR 56)."""
    import re
    from transmogrifai_tpu.ops import pallas_glm as PG
    n, d, F, L = 8192, 64, 5, 8
    S = jax.ShapeDtypeStruct
    args = (S((d, n), BF16), S((n,), F32), S((n,), F32), S((F, n), F32),
            S((F, L), F32), S((L, d), F32), S((L,), F32), S((d,), F32),
            S((d,), F32))
    src = ("def caller(XT, y, w, m, sel, B, b0, mean, std):\n"
           "    return PG.glm_moments(XT, PG.dense_rows(y), PG.dense_rows(w),"
           " m, sel, B, b0, mean, std, loss='logistic')\n")

    def bodies():
        out = []
        for blanks in (0, 3):
            ns = {"PG": PG}
            exec(compile("\n" * blanks + src, "caller_of_glm_moments.py",
                         "exec"), ns)
            PG.glm_moments.clear_cache()
            text = jax.jit(ns["caller"]).trace(*args).lower(
                lowering_platforms=("tpu",)).as_text(debug_info=True)
            out.append(re.findall(r'backend_config = "([^"]*)"', text))
        PG.glm_moments.clear_cache()
        return out
    own, moved = bodies()
    assert len(own) == 1 and own == moved
    jax.config.update("jax_traceback_in_locations_limit", 10)
    try:
        own, moved = bodies()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", 1)
    assert own != moved
