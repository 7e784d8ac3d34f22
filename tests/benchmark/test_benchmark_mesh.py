"""The four-chip cell `sweep-glm-4chip` (PR 35): its manifest entries and
files, the configuration's arithmetic, every `x4_*` layer file, the plain
reference of benchmark/reference_mesh.py against the program and against
each named wrong build, the one new reader, and one rehearsal each of
`--trace 0` and `--trace 1` on 4 host devices."""
import glob
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
CELL, CONFIG = "sweep-glm-4chip", "binary-128m-64-x4"
X4 = ("x4_rounds_device_s", "x4_rounds_roofline", "x4_metric_device_s",
      "x4_fold_assign_device_s", "x4_collective_device_s",
      "x4_collectives_per_job", "x4_chip_skew_s", "x4_host_gap_s",
      "x4_fit_host_s", "x4_eval_host_s", "x4_fold_assign_host_s",
      "x4_device_place_host_s", "x4_host_fetches")


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return _load(REPO, "BENCHMARK.json")


@pytest.fixture(scope="module")
def cell():
    return _load(BENCH, "workloads", CELL + ".json")


@pytest.fixture(scope="module")
def config():
    return _load(BENCH, "configs", CONFIG + ".json")


# -- the manifest -----------------------------------------------------------------

def test_manifest_holds_the_cell_and_its_configuration(manifest, cell, config):
    w = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert w == {"name": CELL, "config": CONFIG,
                 "traffic": "glm-mesh4-closed-1", "chips": 4,
                 "why": cell["why"]}
    c = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert c["file"] == f"benchmark/configs/{CONFIG}.json"
    assert c["reduced"] == ["glm_grid"] == list(config["reduced"])
    assert c["source"] == config["source"] and len(c["source"]) <= 200
    glm = next(m for m in manifest["end_to_end"] if m["name"] == "glm_sweep_s")
    assert CELL in glm["workloads"] and glm["bound"] == 0.02
    assert cell["metric"] == "glm_sweep_s"
    assert set(cell["units"]) == {"glm_sweep_s", "setup_s"}
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert four == [CELL]            # the first, and the one allowed


def test_configuration_arithmetic(config, cell):
    """Rows a chip, bytes a chip past a quarter of its memory, the whole
    past one chip: the numbers the configuration file states."""
    peaks = _load(BENCH, "peaks.json")["devices"]["TPU v5 lite"]
    sz, mesh = config["sizes"], config["mesh"]
    assert mesh["chips"] == cell["chips"] == mesh["batch"] == 4
    assert mesh["model"] == 1
    assert sz["rows"] % mesh["chips"] == 0          # never padded
    local = sz["rows"] // mesh["chips"]
    assert local == mesh["rows_per_chip"] == 32_000_000
    x = local * sz["cols"] * 2
    resident = x + sz["folds"] * local * 4 + 2 * local * 4
    assert resident == 4_992_000_000
    assert resident > 0.25 * peaks["hbm_bytes"]
    assert mesh["chips"] * x > peaks["hbm_bytes"]   # one chip cannot hold X
    lanes = config["glm_grid"] * sz["folds"]
    grid = cell["families"]["lr"]["grid"]
    assert lanes == 30 and config["glm_grid"] == \
        len(grid["reg_param"]) * len(grid["elastic_net_param"])
    assert config["pool"]["lr"]["params"] == {"max_iter": 15,
                                              "standardization": False}
    assert cell["expect"]["shards"] == 4 and cell["expect"]["h2d_bytes"] == 0


@pytest.mark.parametrize("name", X4)
def test_layer_file_against_the_manifest(manifest, name):
    spec = _load(BENCH, "layers", name + ".json")
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": spec["unit"],
                     "better": spec["better"], "source": spec["source"],
                     "layer": spec["layer"], "moves": "glm_sweep_s",
                     "workloads": [CELL]}
    assert spec["cells"] == [CELL]
    assert os.path.exists(os.path.join(BENCH, "readers",
                                       spec["reader"] + ".py"))
    for mod in spec["modules"]:
        assert os.path.exists(os.path.join(REPO, mod)), mod


def test_no_other_layer_file_is_the_cells(manifest):
    mine = {os.path.basename(p)[:-5]
            for p in glob.glob(os.path.join(BENCH, "layers", "*.json"))
            if CELL in _load(p).get("cells", [])}
    assert mine == set(X4)
    layers = {m["layer"] for m in manifest["per_layer"]
              if m["name"] in mine}
    assert layers == {"GLM round driver", "metric kernels",
                      "validator and sweep driver", "mesh and collectives"}


# -- the reference, against the program and the wrong builds --------------------------

def test_reference_threefry_is_the_programs_hash():
    import jax.numpy as jnp
    from jax.extend.random import threefry_2x32

    from benchmark import reference_mesh
    n = 1000
    key = np.array([0x9E3779B9, 42], np.uint32)
    words = threefry_2x32((jnp.uint32(key[0]), jnp.uint32(key[1])),
                          jnp.arange(2 * n, dtype=jnp.uint32).reshape(2, n))
    i = np.arange(n, dtype=np.uint32)
    with np.errstate(over="ignore"):
        w0, w1 = reference_mesh.threefry2x32(key[0], key[1], i,
                                             i + np.uint32(n))
    assert np.array_equal(np.asarray(words[0]), w0)
    assert np.array_equal(np.asarray(words[1]), w1)


@pytest.mark.parametrize("seed,folds", [(42, 5), (2**33 + 7, 3)])
def test_reference_replays_the_fold_rule(seed, folds):
    import jax

    from benchmark import reference_mesh
    from transmogrifai_tpu.automl.tuning.folds import (
        assign_fold_masks, fold_key,
    )
    n = 4099
    masks = np.asarray(assign_fold_masks(fold_key(seed), None, n=n,
                                         n_folds=folds))
    replay = np.asarray(reference_mesh.replayed_fold_of(
        seed, n, folds, jax.devices()[0]))
    assert np.array_equal(np.argmin(masks, axis=0), replay)


@pytest.fixture(scope="module")
def rehearsed():
    """The cell's driver at rehearsal size in THIS process (4 of
    conftest's 8 host devices): set up once, then verified under each
    build."""
    from benchmark import harness
    cell = harness.load_json("workloads", CELL + ".json")
    config = harness.load_json("configs", CONFIG + ".json")
    driver = harness.load_module("drivers", cell["driver"])
    import tempfile
    ctx = harness.Ctx(
        cell=cell, config=config,
        sizes=dict(config["sizes"], **config["rehearsal"]), seed=35,
        seconds=1.0, trace=False, rehearse=True,
        out_dir=tempfile.mkdtemp(prefix="bench-mesh-"),
        compile_log=harness.CompileLog())
    with pytest.MonkeyPatch.context() as mp:
        from transmogrifai_tpu.automl.tuning import validators as V
        for name in ("STREAMED_SWEEP_MIN_ROWS",
                     "BINNED_RANK_METRIC_MIN_ROWS"):
            mp.setattr(V, name, getattr(V, name))   # restored on exit
        st = driver.setup(ctx)
        yield ctx, st, driver


def test_driver_reads_the_layout_from_the_warm_up_job(rehearsed):
    ctx, st, _ = rehearsed
    assert ctx.problems == []
    lay = ctx.notes["layout"]
    assert lay["validate_shards"] == lay["fold_assign_shards"] == 4
    assert lay["device_place"] == {"route": "resident_sharded",
                                   "h2d_bytes": 0}
    assert lay["round_shards"] == [4] and lay["round_psums"] == [1]
    assert lay["eval"] == {"eval_route": "heldout_once", "shards": 4}
    assert len(st.X.sharding.device_set) == 4
    assert {s.data.shape for s in st.X.addressable_shards} == {(1024, 8)}


def test_right_build_passes_the_reference(rehearsed):
    ctx, st, driver = rehearsed
    ctx.problems.clear()
    driver.verify(ctx, st)
    assert ctx.problems == []
    r = ctx.notes["mesh_answer"]
    assert r["fold_rows_unlike_replay"] == 0
    assert sum(r["fold_sizes"]) == 4096
    assert max(r["fold_sizes"]) - min(r["fold_sizes"]) <= 1
    assert r["shards"] == 4 and r["rows_per_shard"] == 1024


@pytest.mark.parametrize("build,check", [
    ("rounds_drop_shard", "did not fit all the rows"),
    ("metric_drop_shard", "over all held-out rows"),
    ("counts_not_summed", "over all held-out rows")])
def test_wrong_build_fails_the_reference(rehearsed, build, check):
    import mesh_wrong_builds as WB

    from benchmark import reference
    ctx, st, driver = rehearsed
    right = st.streamed_fits, st.last_best
    try:
        with WB.BUILDS[build](), reference.StreamedFitSpy() as fits:
            driver.sweep._job(ctx, st)
        st.streamed_fits = fits.fits
        ctx.problems.clear()
        driver.verify(ctx, st)
        assert len(ctx.problems) == 1 and check in ctx.problems[0], \
            ctx.problems
    finally:
        st.streamed_fits, st.last_best = right
        ctx.problems.clear()


def test_a_program_without_the_route_is_refused_before_any_data(monkeypatch):
    from benchmark import harness
    from transmogrifai_tpu.parallel import mesh
    driver = harness.load_module("drivers", "sweep_mesh")
    monkeypatch.delattr(mesh, "resident_row_mesh")
    made = []
    monkeypatch.setattr(driver.datagen_mesh, "sharded_matrix",
                        lambda *a, **k: made.append(a))
    with pytest.raises(harness.BenchFailure, match="sharded-resident"):
        driver.setup(types.SimpleNamespace(cell={"chips": 4}))
    assert made == []


def test_too_few_devices_are_refused(monkeypatch):
    import jax

    from benchmark import harness
    driver = harness.load_module("drivers", "sweep_mesh")
    monkeypatch.setattr(jax, "devices", lambda *a: [object()] * 3)
    with pytest.raises(harness.BenchFailure, match="JAX reports 3"):
        driver.setup(types.SimpleNamespace(cell={"chips": 4}))


def test_sharded_data_is_a_function_of_seed_and_shards():
    import jax

    from benchmark import datagen_mesh
    devs = jax.devices()[:4]
    X, y = datagen_mesh.sharded_matrix(4096, 8, "bfloat16", 3500000102, devs)
    X2, y2 = datagen_mesh.sharded_matrix(4096, 8, "bfloat16", 3500000102,
                                         devs)
    X3, _ = datagen_mesh.sharded_matrix(4096, 8, "bfloat16", 3500000103,
                                        devs)
    assert np.array_equal(np.asarray(X, np.float32),
                          np.asarray(X2, np.float32))
    assert np.array_equal(np.asarray(y), np.asarray(y2))
    assert not np.array_equal(np.asarray(X, np.float32),
                              np.asarray(X3, np.float32))
    assert [s.data.shape for s in X.addressable_shards] == [(1024, 8)] * 4
    assert [next(iter(s.data.devices())) for s in X.addressable_shards] \
        == devs
    # no two shards hold the same rows
    parts = [np.asarray(s.data, np.float32) for s in X.addressable_shards]
    assert not np.array_equal(parts[0], parts[1])
    assert 0.3 < float(np.asarray(y).mean()) < 0.7
    with pytest.raises(ValueError, match="do not divide"):
        datagen_mesh.sharded_matrix(4098, 8, "bfloat16", 1, devs)


# -- the one new reader ----------------------------------------------------------------

def test_chip_skew_reads_busiest_less_idlest():
    from benchmark import harness
    from benchmark.reduce_trace import Op, Reduced, Span
    reader = harness.load_module("readers", "chip_skew")
    ops = [Op(0.0, 4e9, "a", "jit_m", 0, "%a = x"),
           Op(0.0, 3e9, "a", "jit_m", 1, "%a = x"),
           Op(5e9, 6e9, "all-reduce.1", "jit_m", 1, "%all-reduce.1 = x"),
           Op(10e9, 12e9, "a", "jit_m", 0, "%a = x"),
           Op(10e9, 12.5e9, "a", "jit_m", 1, "%a = x")]
    spans = [Span(0.0, 9e9, "bench.validate", "t#0"),
             Span(10e9, 19e9, "bench.validate", "t#0")]
    ctx = types.SimpleNamespace(
        reduced=Reduced(ops, spans, {}, on_device=True),
        cell={"job_span": "bench.validate"}, notes={})
    # job 0: chip 0 busy 4 s, chip 1 3 + 1; job 1: 2 s against 2.5
    assert reader.read(ctx, {}) == pytest.approx((0.0 + 0.5) / 2)
    assert reader.read(ctx, {"op": "all-reduce"}) == pytest.approx(0.5)
    one = types.SimpleNamespace(
        reduced=Reduced(ops[:1], spans, {}, on_device=True),
        cell={"job_span": "bench.validate"}, notes={})
    assert reader.read(one, {}) is None        # one chip: no skew to read


# -- the rehearsals -------------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_on_four_host_devices(trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3500000177", "--seconds", "2", "--trace", str(trace),
         "--rehearse", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    line, report = json.loads(lines[-1]), json.loads(lines[0])
    assert line["correct"] is True, report["problems"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 4
    if trace:
        # the roofline needs the chip's peaks and the skew four device
        # planes: the CPU's stand-in trace has neither
        want = set(X4) - {"x4_rounds_roofline", "x4_chip_skew_s"}
        assert set(line["metrics"]) == want | {"programs_compiled",
                                               "window_compiles"}
        # nothing is placed: what is left is the span's own few calls
        assert line["metrics"]["x4_device_place_host_s"]["value"] < 0.01
        assert line["metrics"]["x4_collectives_per_job"]["value"] == \
            report["notes"]["layout"]["telemetry"]["psums"]
    else:
        assert set(line["metrics"]) == {"glm_sweep_s", "setup_s"}
    assert report["notes"]["layout"]["device_place"] == {
        "route": "resident_sharded", "h2d_bytes": 0}
    assert report["notes"]["mesh_answer"]["fold_rows_unlike_replay"] == 0
