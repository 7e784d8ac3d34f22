"""A sweep over a matrix that lives ROW-SHARDED on the device: validate()
reads the mesh from where X lives (no `mesh=`), the fold masks are the
one-device program's bit for bit, the rounds and the held-out metric pass
run on the mesh, nothing passes through the host, and the spans and the
telemetry say which layout ran. On 4 of conftest's 8 host devices."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.automl.tuning import folds
from transmogrifai_tpu.automl.tuning import validators as V
from transmogrifai_tpu.automl.tuning.folds import (
    FOLD_ASSIGNMENT_VERSION, assign_fold_masks, assign_fold_masks_sharded,
    fold_key,
)
from transmogrifai_tpu.automl.tuning.validators import (
    CrossValidation, TrainValidationSplit,
)
from transmogrifai_tpu.evaluators.evaluators import Evaluators
from transmogrifai_tpu.models.glm import OpLogisticRegression
from transmogrifai_tpu.ops import glm_sweep as GS
from transmogrifai_tpu.parallel.mesh import (
    batch_sharding, make_mesh, replicated, resident_row_mesh, sharded_along,
)
from transmogrifai_tpu.utils.metrics import collector

N, D, FOLDS = 4096, 8, 3
GRIDS = [dict(reg_param=r, elastic_net_param=a)
         for r in (1e-4, 1e-2, 0.3) for a in (0.0, 0.5)]
# fold metrics of the mesh sweep against the one-device sweep: the same
# rows, products and bins, float32 sums in another order
TOL_LAYOUT = 1e-5


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(n_batch=4, n_model=1, devices=jax.devices()[:4])


@pytest.fixture(scope="module")
def data(mesh):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(N, D)).astype(np.float32)
    beta = rng.normal(size=D) / np.sqrt(D)
    y = (rng.random(N) < 1 / (1 + np.exp(-X @ beta))).astype(np.float32)
    return {"X": X, "y": y,
            "X1": jnp.asarray(X, jnp.bfloat16), "y1": jnp.asarray(y),
            "Xs": jax.device_put(jnp.asarray(X, jnp.bfloat16),
                                 batch_sharding(mesh, 2)),
            "ys": jax.device_put(y, batch_sharding(mesh, 1))}


@pytest.fixture
def small_routes(monkeypatch):
    """The toy size takes the routes the chip takes at 128M rows."""
    monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
    monkeypatch.setattr(V, "BINNED_RANK_METRIC_MIN_ROWS", 0)


def _sweep(X, y, spans=None, **kw):
    val = CrossValidation(Evaluators.BinaryClassification.au_pr(),
                          num_folds=FOLDS, seed=42,
                          sweep_dtype=jnp.bfloat16, **kw)
    models = [(OpLogisticRegression(max_iter=15, standardization=False),
               [dict(g) for g in GRIDS])]
    if spans is None:
        return val, val.validate(models, X, y)
    collector.enable("mesh_test")
    try:
        best = val.validate(models, X, y)
        spans.extend((s.kind, s.name, dict(s.attrs))
                     for s in collector.trace.spans)
    finally:
        collector.finish()
        collector.disable()
    return val, best


# -- the way in ---------------------------------------------------------------

def test_resident_row_mesh_reads_only_a_row_sharded_device_array(mesh, data):
    assert resident_row_mesh(data["Xs"]) == mesh
    assert resident_row_mesh(data["ys"]) == mesh
    assert resident_row_mesh(data["X"]) is None          # host
    assert resident_row_mesh(data["X1"]) is None         # one device
    assert resident_row_mesh(jax.device_put(
        data["X"], replicated(mesh))) is None
    assert resident_row_mesh(jax.device_put(
        data["X"], sharded_along(mesh, 1, 2))) is None   # columns
    one = make_mesh(n_batch=1, n_model=1, devices=jax.devices()[:1])
    assert resident_row_mesh(jax.device_put(
        data["X"], batch_sharding(one, 2))) is None


# -- folds ----------------------------------------------------------------------

# a seed, a row count and whether a run is given too few places. At 4 096
# rows the program's own places for a run are all of a chip's rows; at
# 200 000 (a shard's rows no power of two) a third of them, the runs cut
# out of the sorted rows; rows / shards**2 places hold a run without its
# window's margins, so a run overflows
FOLD_CASES = {"n4096": (7, N, False),
              "seed_past_2_32": (2 ** 40 + 5, N, False),
              "rows_no_power_of_two": (7, 200_000, False),
              "capacity_overflows": (7, N, True)}


@pytest.mark.parametrize("case", list(FOLD_CASES))
@pytest.mark.parametrize("shards", [2, 4])
@pytest.mark.parametrize("stratify", [False, True])
@pytest.mark.parametrize("spec", [dict(n_folds=5),
                                  dict(n_folds=1, val_fraction=0.25)],
                         ids=["kfold", "split"])
def test_fold_masks_on_a_mesh_are_the_one_device_masks(shards, stratify, spec,
                                                       case):
    seed, n, too_few = FOLD_CASES[case]
    m = make_mesh(n_batch=shards, n_model=1, devices=jax.devices()[:shards])
    y = (np.random.default_rng(1).random(n) < 0.3).astype(np.float32)
    y1 = jnp.asarray(y) if stratify else None
    ys = jax.device_put(y, batch_sharding(m, 1)) if stratify else None
    one = assign_fold_masks(fold_key(seed), y1, n=n, stratify=stratify,
                            **spec)
    if too_few:
        on_mesh, overflow = folds._sharded_fold_masks_fn(
            m, n, spec["n_folds"], spec.get("val_fraction"), stratify,
            n // shards ** 2)(fold_key(seed), *([ys] if stratify else []))
    else:
        on_mesh, overflow = assign_fold_masks_sharded(
            m, fold_key(seed), ys, n=n, stratify=stratify, **spec)
    assert FOLD_ASSIGNMENT_VERSION == 2
    # which body answered: the partitioned one unless a run overflowed
    # (the stratified rule has none to overflow)
    route = folds.sharded_fold_route(m, n, stratify)
    assert route["route"] == ("replicated" if stratify else "partitioned")
    assert route["sort_keys"] == (4 if stratify else 3)
    assert route["sort_places"] == (n + -n % 2048 if stratify
                                    else shards * route["capacity"])
    assert route["exchange_bytes"] == 12 * shards * route["capacity"]
    if case == "rows_no_power_of_two" and not stratify:
        assert route["capacity"] < n // shards and route["sort_places"] < n
    assert bool(overflow) == (too_few and not stratify)
    assert on_mesh.sharding.is_equivalent_to(sharded_along(m, 1, 2), 2)
    # no chip holds the whole [F, n] block
    assert {s.data.shape for s in on_mesh.addressable_shards} == \
        {(one.shape[0], n // shards)}
    assert np.array_equal(np.asarray(one), np.asarray(on_mesh))


def test_partitioned_order_breaks_ties_by_row_id(mesh):
    """Equal 64-bit keys on different shards come out in row-id order, as
    the one stable sort gives them: across the positions where one chip's
    slice of the order ends and the next begins, at the first and last
    word of a bucket and of a window, and for a key that equals the
    padding. Threefry hands a test no tie, so the core takes the words."""
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from transmogrifai_tpu.parallel.mesh import BATCH_AXIS, build_shard_map
    n, shards = 1 << 16, 4     # a run's places: 3/8 of a chip's rows
    n_local = n // shards
    margin, capacity = folds._partition_plan(n, shards)
    assert capacity < n_local
    rng = np.random.default_rng(3)
    w = rng.integers(0, 2 ** 32, size=(2, n), dtype=np.uint64) \
        .astype(np.uint32)
    order = np.lexsort((np.arange(n), w[1], w[0]))
    for c in range(1, shards):
        rows = order[c * n_local - 8:c * n_local + 8]
        assert len(set(rows // n_local)) > 1        # on several shards
        w[:, rows] = w[:, rows[:1]]
    edges = [-(-c * 2 ** 32 // shards) for c in range(shards + 1)]
    for w0, w1 in ((edges[1], 5), (edges[1] - 1, 5), (edges[2] - margin, 5),
                   (edges[2] - margin - 1, 5), (edges[1] + margin, 5),
                   (edges[1] + margin - 1, 5), (0, 0),
                   (2 ** 32 - 1, 2 ** 32 - 1)):
        rows = rng.choice(n, 6, replace=False)
        assert len(set(rows // n_local)) > 1
        w[0, rows], w[1, rows] = w0, w1
    want = lax.sort((jnp.asarray(w[0]), jnp.asarray(w[1]),
                     lax.iota(jnp.int32, n)), num_keys=2, is_stable=True)[2]
    core = jax.jit(build_shard_map(
        lambda w0, w1: folds._partitioned_ranks(w0, w1, n, BATCH_AXIS),
        mesh, in_specs=(P(BATCH_AXIS), P(BATCH_AXIS)),
        out_specs=(P(BATCH_AXIS), P())))
    ranks, overflow = core(*(jax.device_put(x, batch_sharding(mesh, 1))
                             for x in w))
    assert not bool(overflow)
    assert np.array_equal(np.asarray(ranks), np.asarray(want))


@pytest.mark.parametrize("cls,kw", [
    (CrossValidation, dict(num_folds=5)),
    (TrainValidationSplit, dict(train_ratio=0.75))])
@pytest.mark.parametrize("stratify", [True, False])
def test_validator_masks_do_not_depend_on_the_layout(mesh, data, cls, kw,
                                                     stratify):
    val = cls(Evaluators.BinaryClassification.au_pr(), seed=11,
              stratify=stratify, **kw)
    one = np.asarray(val.device_fold_masks(data["y1"]))
    assert val.last_fold_overflow is None       # no mesh, no flag
    assert np.array_equal(
        one, np.asarray(val.device_fold_masks(data["ys"], mesh=mesh)))
    assert not bool(val.last_fold_overflow)


# -- the sweep ------------------------------------------------------------------

@pytest.fixture(scope="module")
def sweeps(data):
    """The same sweep on one device and on the resident sharded matrix."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
        mp.setattr(V, "BINNED_RANK_METRIC_MIN_ROWS", 0)
        spans1, spans4 = [], []
        one = _sweep(data["X1"], data["y1"], spans1)
        four = _sweep(data["Xs"], data["ys"], spans4)
    return {"one": one, "four": four, "spans1": spans1, "spans4": spans4}


def test_mesh_sweep_answers_as_the_one_device_sweep(sweeps):
    (_, b1), (_, b4) = sweeps["one"], sweeps["four"]
    assert b1.best_grid == b4.best_grid
    for a, b in zip(b1.validated, b4.validated):
        assert a.grid == b.grid and a.route == b.route == "streamed"
        assert np.max(np.abs(np.array(a.fold_metrics)
                             - np.array(b.fold_metrics))) <= TOL_LAYOUT


def test_telemetry_says_which_layout_ran(sweeps):
    t1 = sweeps["one"][0].last_streamed_telemetry
    t4 = sweeps["four"][0].last_streamed_telemetry
    assert (t1["shards"], t1["psums"], t1["psum_bytes"],
            t1["rows_per_shard"]) == (1, 0, 0, N)
    assert t4["shards"] == 4 and t4["rows_per_shard"] == N // 4
    assert t4["eval_route"] == t1["eval_route"] == "heldout_once"
    # one collective an iteration and one a round program, one a chunk of
    # the metric pass
    assert t4["psums"] == t4["data_passes"] + t4["glm_rounds"] + t4["passes"]
    rounds = sum(it * GS.round_psum_bytes(Lb, D) for it, Lb in
                 zip(t4["iters_per_round"], t4["bucket_sizes"]))
    assert t4["psum_bytes"] == rounds + 2 * FOLDS * 6 * V.RANK_METRIC_BINS * 4
    for key in ("glm_rounds", "data_passes", "padded_lane_passes",
                "bucket_sizes"):
        assert t1[key] == t4[key]       # the same retirement history


def _attrs(spans, kind, name):
    return [a for k, n, a in spans if k == kind and n.startswith(name)]


@pytest.mark.parametrize("which,shards,route", [
    ("spans1", 1, "one_device"), ("spans4", 4, "resident_sharded")])
def test_spans_name_the_layout(sweeps, which, shards, route):
    spans = sweeps[which]
    assert _attrs(spans, "validate", "CrossValidation")[0]["shards"] == shards
    assign = _attrs(spans, "validate_phase", "fold_assign")[0]
    assert assign["shards"] == shards
    if shards == 1:
        assert {k: assign[k] for k in ("route", "sort_keys", "sort_places",
                                       "pad_places")} == dict(
            route="device", **folds.fold_sort_shape(N, False))
        assert "capacity" not in assign
    else:
        # the fold program's body on the mesh, as planned; the flag says
        # that body answered, and nothing fetched it
        _, capacity = folds._partition_plan(N, shards)
        assert {k: assign[k] for k in ("route", "sort_keys", "sort_places",
                                       "capacity", "exchange_bytes")} == dict(
            route="partitioned", sort_keys=3, sort_places=shards * capacity,
            capacity=capacity, exchange_bytes=12 * shards * capacity)
        assert not bool(sweeps["four"][0].last_fold_overflow)
    place = _attrs(spans, "validate_phase", "device_place")[0]
    assert place["route"] == route and place["h2d_bytes"] == 0
    assert _attrs(spans, "sweep_fit", "glm_streamed")[0]["shards"] == shards
    rounds = _attrs(spans, "sweep_round", "glm_round")
    assert rounds and all(r["shards"] == shards for r in rounds)
    assert all(r["psums"] == int(shards > 1) for r in rounds)
    assert all(r["psum_bytes"] == int(shards > 1)
               * GS.round_psum_bytes(r["bucket"], D) for r in rounds)
    ev = _attrs(spans, "sweep_eval", "glm_streamed_eval")[0]
    assert ev["shards"] == shards and ev["eval_route"] == "heldout_once"


def test_validate_never_brings_the_rows_to_the_host(data, small_routes,
                                                    monkeypatch):
    """np.asarray of a sharded array reads `ArrayImpl._value` (and
    `__array__` where Python sees the call): neither may be asked for
    anything with a row dimension."""
    from jax._src.array import ArrayImpl
    seen = []
    value, array = ArrayImpl.__dict__["_value"], ArrayImpl.__array__

    def spy_value(self):
        seen.append(tuple(self.shape))
        return value.fget(self)

    def spy_array(self, *a, **kw):
        seen.append(tuple(self.shape))
        return array(self, *a, **kw)
    monkeypatch.setattr(ArrayImpl, "_value", property(spy_value))
    monkeypatch.setattr(ArrayImpl, "__array__", spy_array)
    _sweep(data["Xs"], data["ys"])
    assert seen, "the spy saw no fetch at all"
    assert not [s for s in seen if N in s or N // 4 in s], seen


def test_host_weights_and_masks_join_the_resident_matrix(data, mesh,
                                                         small_routes):
    val = CrossValidation(Evaluators.BinaryClassification.au_pr(),
                          num_folds=FOLDS, seed=42, sweep_dtype=jnp.bfloat16)
    masks = val.fold_masks(data["y"])
    spans = []
    collector.enable("mesh_test")
    try:
        best = val.validate(
            [(OpLogisticRegression(max_iter=15, standardization=False),
              [dict(g) for g in GRIDS])], data["Xs"], data["ys"],
            w=np.ones(N, np.float32), masks=masks)
        spans.extend((s.kind, s.name, dict(s.attrs))
                     for s in collector.trace.spans)
    finally:
        collector.finish()
        collector.disable()
    place = _attrs(spans, "validate_phase", "device_place")[0]
    assert place["route"] == "resident_sharded"
    assert place["h2d_bytes"] == masks.nbytes + 4 * N
    assert val.last_streamed_telemetry["shards"] == 4
    assert all(v.route == "streamed" for v in best.validated)


def test_host_arrays_with_a_mesh_keep_the_host_put_branch(data, mesh, sweeps,
                                                          small_routes):
    spans = []
    val, best = _sweep(data["X"], data["y"], spans, mesh=mesh)
    place = _attrs(spans, "validate_phase", "device_place")[0]
    assert place["route"] == "host_put" and place["h2d_bytes"] > 0
    assert _attrs(spans, "validate_phase", "fold_assign")[0]["shards"] == 1
    assert val.last_streamed_telemetry["shards"] == 4
    assert val.last_streamed_telemetry["eval_route"] == "heldout_once"
    for a, b in zip(sweeps["one"][1].validated, best.validated):
        assert np.max(np.abs(np.array(a.fold_metrics)
                             - np.array(b.fold_metrics))) <= TOL_LAYOUT


def test_a_sharded_matrix_sweeps_on_its_mesh_through_the_selector(
        data, small_routes):
    from transmogrifai_tpu.automl.selector import ModelSelector
    val = CrossValidation(Evaluators.BinaryClassification.au_pr(),
                          num_folds=FOLDS, seed=42, sweep_dtype=jnp.bfloat16)
    sel = ModelSelector(val, None, [
        (OpLogisticRegression(max_iter=15, standardization=False),
         [dict(g) for g in GRIDS[:2]])])
    sel.fit_arrays(data["Xs"], data["ys"])
    assert val.last_streamed_telemetry["shards"] == 4
    assert val.last_streamed_telemetry["rows_per_shard"] == N // 4


# -- the rounds converge ----------------------------------------------------------

LAYOUTS = ("one_device", "mesh", "source")


@pytest.fixture(scope="module")
def converged(mesh, data):
    """sweep_glm_streamed_rounds over the bfloat16 matrix on one device,
    row-sharded on the mesh (the shard_map form of the round), and as a
    RowSource (the tileplane steps): (info, state) of each."""
    from transmogrifai_tpu.parallel import tileplane as TP
    rng = np.random.default_rng(1)
    fold = rng.integers(0, FOLDS, size=N)
    masks = (fold[None, :] != np.arange(FOLDS)[:, None]).astype(np.float32)
    w = np.ones(N, np.float32)
    regs = np.float32([g["reg_param"] for g in GRIDS])
    alphas = np.float32([g["elastic_net_param"] for g in GRIDS])
    inputs = {
        "one_device": (data["X1"], data["y1"], jnp.asarray(w),
                       jnp.asarray(masks), {}),
        "mesh": (data["Xs"], data["ys"],
                 jax.device_put(w, batch_sharding(mesh, 1)),
                 jax.device_put(masks, sharded_along(mesh, 1, 2)),
                 {"mesh": mesh}),
        "source": (TP.ArraySource(np.asarray(data["X1"]), data["y"], w,
                                  masks.T.copy(), chunk_rows=1000),
                   None, None, None, {})}
    out = {}
    for name, (X, y, w_, m, kw) in inputs.items():
        st = GS._new_round_state(FOLDS * len(GRIDS), D)
        _, _, info = GS.sweep_glm_streamed_rounds(
            X, y, w_, m, regs, alphas, loss="logistic", max_iter=15,
            tol=1e-6, standardize=False, state=st, **kw)
        out[name] = (info, st)
    return out


@pytest.mark.parametrize("layout", LAYOUTS)
def test_lanes_retire_at_tol_in_every_layout(converged, layout):
    """The margins see the float32 coefficients in the shard_map form and
    in the source rounds as on one device: every lane's delta clears tol
    inside max_iter 15 (with the margins at bfloat16(B) 17 of the 18 lanes
    ran to the cap on one device and on the mesh, delta 3e-3), the same
    retirement history in all three, one psum an iteration on the mesh."""
    info, st = converged[layout]
    assert info["driver"] == ("tileplane" if layout == "source"
                              else "resident")
    assert info["lanes_at_cap"] == 0
    assert info["lanes_retired"] == info["lanes_total"] == 18
    assert (st["delta"] <= 1e-6).all()
    assert info["data_passes"] < 15
    one = converged["one_device"][0]
    for key in ("data_passes", "iters_per_round", "bucket_sizes"):
        assert info[key] == one[key]
    assert info["psums"] == (layout == "mesh") * (
        info["data_passes"] + info["glm_rounds"])
    np.testing.assert_allclose(st["B"], converged["one_device"][1]["B"],
                               rtol=0, atol=1e-5)


# -- the programs -----------------------------------------------------------------

@pytest.mark.parametrize("bucket", [8, 32])
def test_round_program_issues_the_collectives_it_declares(mesh, bucket):
    """ONE psum call inside the iteration loop, of the five accumulators
    together (the gradient, the Gram, the two intercept sums and, since the
    intercept steps with the coefficients, the Gram's border: 4 x bucket x
    D more bytes, no further collective), and one outside it (the fold
    weight sums). jax lowers a psum of five arrays to five all_reduce ops
    side by side, which the TPU's compiler merges into one (compiled for a
    described v5e:2x2 the program's text holds 2 all-reduces: PERF.md
    section 6, PR 35)."""
    S = jax.ShapeDtypeStruct
    args = (S((N, D), jnp.bfloat16), S((N,), jnp.float32),
            S((N,), jnp.float32), S((FOLDS, N), jnp.float32),
            S((FOLDS, bucket), jnp.float32), S((bucket,), jnp.float32),
            S((bucket,), jnp.float32), S((bucket, D), jnp.float32),
            S((bucket,), jnp.float32), S((D,), jnp.float32),
            S((D,), jnp.float32), S((), jnp.int32), S((), jnp.float32))
    text = GS._sharded_round_fn(mesh, "logistic", True).lower(*args).as_text()
    assert text.count("all_reduce") == 1 + 5
    assert GS.round_psum_bytes(bucket, D) \
        == 4 * bucket * (D + D * D + 2 + D)


def test_sharded_round_with_the_fused_pass_is_the_one_device_round(
        mesh, data, monkeypatch):
    """Where the backend has Mosaic every chip runs the fused pass
    (ops/pallas_glm.glm_moments, interpreted here) over its LOCAL rows
    inside the shard_map, and the five accumulators still merge in the ONE
    psum an iteration: the same program text around another body, the same
    answer as the fused round on one device to float32 rounding."""
    import functools

    from transmogrifai_tpu.ops import pallas_glm, pallas_hist
    monkeypatch.setattr(pallas_glm, "glm_moments", functools.partial(
        pallas_glm.glm_moments, interpret=True))
    monkeypatch.setattr(pallas_hist, "available", lambda: True)
    monkeypatch.setattr(pallas_hist, "_vmem_limit", lambda: 96 << 20)
    bucket = 8
    assert GS.glm_round_kernel(D, jnp.bfloat16, bucket) == "pallas_fused"
    rng = np.random.default_rng(1)
    fold = rng.integers(0, FOLDS, size=N)
    masks = (fold[None, :] != np.arange(FOLDS)[:, None]).astype(np.float32)
    sel = np.zeros((FOLDS, bucket), np.float32)
    sel[np.arange(6) % FOLDS, np.arange(6)] = 1.0
    tail = (jnp.asarray(sel), jnp.zeros(bucket), jnp.full(bucket, 1e-2),
            jnp.zeros((bucket, D)), jnp.zeros(bucket), jnp.zeros(D),
            jnp.ones(D), jnp.asarray(3, jnp.int32),
            jnp.asarray(0.0, jnp.float32))
    GS.sweep_glm_round.clear_cache()
    GS._sharded_round_fn.cache_clear()
    try:
        one = GS.sweep_glm_round(
            data["X1"], data["y1"], jnp.ones(N), jnp.asarray(masks), *tail,
            loss="logistic", fit_intercept=True)
        rounds = GS._sharded_round_fn(mesh, "logistic", True)
        on_mesh = (data["Xs"], data["ys"],
                   jax.device_put(np.ones(N, np.float32),
                                  batch_sharding(mesh, 1)),
                   jax.device_put(masks, sharded_along(mesh, 1, 2))) + tail
        four = rounds(*on_mesh)
        text = rounds.lower(*on_mesh).as_text()
    finally:
        GS.sweep_glm_round.clear_cache()
        GS._sharded_round_fn.cache_clear()
    assert int(one[3]) == int(four[3]) == 3
    assert np.abs(np.asarray(one[0])[:6]).max() > 1e-2
    for a, b in zip(one[:3], four[:3]):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=0,
                                   atol=1e-5)
    assert text.count("all_reduce") == 1 + 5
    assert GS.round_psum_bytes(bucket, D) \
        == 4 * bucket * (D + D * D + 2 + D)


def _sort_lengths(text):
    """Operand lengths of the sorts in a lowered program's text, in order."""
    import re
    return [int(re.search(r"\}\) : \(tensor<(\d+)x", text[m.end():]).group(1))
            for m in re.finditer(r'"stablehlo\.sort"', text)]


def test_sharded_programs_keep_the_names_traces_find_them_by(mesh):
    S = jax.ShapeDtypeStruct
    ev = V._sharded_eval_heldout_fn(mesh, "au_pr", 64)
    text = ev.lower(S((N, D), jnp.bfloat16), S((N,), jnp.float32),
                    S((N,), jnp.float32), S((FOLDS, N), jnp.float32),
                    S((FOLDS, 6, D), jnp.float32),
                    S((FOLDS, 6), jnp.float32)).as_text()
    assert "jit__streamed_eval_heldout_sharded" in text
    # ONE psum call of the two classes' counts (two ops side by side in
    # jax's lowering, one all-reduce once the TPU's compiler is done)
    assert text.count("all_reduce") == 2
    # the fold program at the four-chip cell's size (lowered, never run):
    # ONE exchange of the runs and ONE psum of their lengths, no gather of
    # anything, and outside the branch that answers an overflow no sort
    # longer than a chip's rows and one run's places
    n = 128_000_000
    _, capacity = folds._partition_plan(n, 4)
    text = folds._sharded_fold_masks_fn(mesh, n, 5, None, False).lower(
        S((2,), jnp.uint32)).as_text()
    assert "jit_assign_fold_masks_sharded" in text
    assert text.count("stablehlo.all_to_all") == 1
    assert text.count("stablehlo.all_reduce") == 1
    assert "all_gather" not in text
    partitioned, _, fallback = text.partition('"stablehlo.case"')
    assert _sort_lengths(partitioned) == [n // 4, 4 * capacity]
    assert 4 * capacity <= n // 4 + capacity
    assert _sort_lengths(fallback) == [n]
    # the stratified rule keeps the replicated sort behind an all-gather
    text = folds._sharded_fold_masks_fn(mesh, N, 5, None, True).lower(
        S((2,), jnp.uint32), S((N,), jnp.float32)).as_text()
    assert "jit_assign_fold_masks_sharded" in text
    assert "all_gather" in text and "all_to_all" not in text
    assert set(_sort_lengths(text)) == {N}
    rounds = GS._sharded_round_fn(mesh, "logistic", True)
    assert rounds.__wrapped__.__name__ == "sweep_glm_round_sharded" \
        or "sweep_glm_round_sharded" in repr(rounds)
