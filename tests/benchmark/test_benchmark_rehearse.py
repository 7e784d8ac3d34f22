"""Every driver rehearsed end to end on the CPU at toy size (--rehearse:
sizes from the files' `rehearsal` blocks; nothing printed is a device
figure), the last line held to the driver's contract, the refusal to
measure off the chip, and the proof that the harness is driven by data: a
cell, a driver, a layer metric and a reader added as NEW files in a copy
run without an edit to any file that was there."""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(root, *args, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)   # conftest's 8 virtual devices
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        env=env, capture_output=True, text=True, timeout=timeout)


def _last_line(r):
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert len(lines) == 2, "the report line, then the result line"
    return json.loads(lines[-1]), json.loads(lines[0])


def _load(*parts):
    with open(os.path.join(REPO, "benchmark", *parts)) as f:
        return json.load(f)


def _per_layer_of(cell):
    """The cell's per-layer metrics, from the layer files themselves (the
    harness reads those, not BENCHMARK.json)."""
    names = set()
    for f in os.listdir(os.path.join(REPO, "benchmark", "layers")):
        spec = _load("layers", f)
        if cell in spec.get("cells", [cell]):
            names.add(spec["name"])
    return names


@pytest.mark.parametrize("cell,trace", [
    ("sweep-glm", 0), ("sweep-glm", 1), ("sweep-gbt", 0), ("sweep-gbt", 1)])
def test_rehearsal_prints_the_contracts_last_line(cell, trace, tmp_path):
    r = _run(REPO, "--workload", cell, "--seed", "3", "--seconds", "3",
             "--trace", str(trace), "--rehearse", "--out", str(tmp_path))
    line, report = _last_line(r)
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == want | ({"breakdown"} if trace else set())
    assert line["correct"] is True, report["problems"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"      # labelled, never tpu
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], (int, float))
    if trace:
        # only per-layer metrics of this cell; the trace-read ones need
        # the TPU's module names, the counters are all there
        assert set(line["metrics"]) <= _per_layer_of(cell)
        assert {"programs_compiled", "window_compiles"} \
            <= set(line["metrics"])
        assert line["metrics"]["window_compiles"]["value"] == 0
        assert line["device"]["busy_s"] > 0
        assert line["device"]["window_s"] >= line["device"]["busy_s"]
        bd = line["breakdown"]
        assert set(bd) == {"device_ops", "idle_gaps"}
        assert 1 <= len(bd["device_ops"]) <= 10
        assert len(bd["idle_gaps"]) <= 10
    else:
        assert set(line["metrics"]) == \
            set(_load("workloads", cell + ".json")["units"])
        assert all(m["value"] > 0 for m in line["metrics"].values())
    assert report["samples"] >= 1 and report["workload"] == cell
    # the timed path's own answer was held to the plain references
    answer = report["notes"]["glm_answer" if cell == "sweep-glm"
                             else "gbt_answer"]
    if cell == "sweep-glm":
        assert report["notes"]["routes"]["cells"] == \
            [["OpLogisticRegression", "streamed"]]
        assert len(answer["folds"]) == 3
        assert answer["metric_worst_delta"] < 1e-2
        assert answer["reference_delta"] < 1e-2
    else:
        assert len(answer["points"]) == 2 and answer["worst_delta"] < 0.1


def test_without_a_chip_it_measures_nothing():
    """No --rehearse on a machine without a TPU: non-zero, no result."""
    r = _run(REPO, "--workload", "sweep-glm", "--seed", "1", "--seconds",
             "1", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_without_the_program_it_fails(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    files: non-zero, no result."""
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "sweep-glm",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


DUMMY_DRIVER = '''
"""A cell that is nothing but new files."""
from benchmark import harness


def setup(ctx):
    import jax
    import jax.numpy as jnp
    step = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
    x = jnp.ones((ctx.param("width"), 64))
    step(x).block_until_ready()
    return {"step": step, "x": x}


def run_window(ctx, st):
    with harness.profiler(ctx):
        done = harness.closed_loop(
            lambda: float(st["step"](st["x"])), ctx.seconds, "bench.dummy",
            max_jobs=ctx.param("trace_jobs") if ctx.trace else 50)
    ctx.counters["dummy_jobs"] = len(done)
    return harness.job_result(ctx, done, "dummy_s", lambda a, b: a == b)


def verify(ctx, st):
    ctx.require(float(st["step"](st["x"])) > 0, "dummy answer")
'''


def test_a_new_cell_and_metric_are_new_files_only(tmp_path):
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    bench = root / "benchmark"
    (bench / "drivers" / "dummy.py").write_text(DUMMY_DRIVER)
    (bench / "workloads" / "dummy-cell.json").write_text(json.dumps({
        "name": "dummy-cell", "config": "binary-10m-64", "driver": "dummy",
        "traffic": "dummy", "chips": 1, "why": "test",
        "units": {"dummy_s": "s", "setup_s": "s"}, "job_span": "bench.dummy",
        "width": 128, "min_jobs": 3, "trace_jobs": 3}))
    (bench / "readers" / "twice.py").write_text(
        "def read(ctx, args):\n"
        "    return 2 * ctx.counters[args['key']]\n")
    (bench / "layers" / "dummy_jobs_twice.json").write_text(json.dumps({
        "name": "dummy_jobs_twice", "layer": "dummy", "unit": "count",
        "better": "higher", "source": "program_counter", "moves": "dummy_s",
        "cells": ["dummy-cell"], "reader": "twice",
        "args": {"key": "dummy_jobs"}}))
    r = _run(str(root), "--workload", "dummy-cell", "--seed", "1",
             "--seconds", "2", "--trace", "1", "--rehearse",
             "--out", str(tmp_path / "out"))
    line, _ = _last_line(r)
    assert line["correct"] is True
    assert line["metrics"]["dummy_jobs_twice"] == {"value": 6,
                                                   "unit": "count"}
    assert "sweep_host_gap_s" not in line["metrics"]   # not this cell's
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"
