"""benchmark/readers/startup.py on a hand-made record (every key a number,
never None; a program older than its ledger; a field that has gone), the
seven layer files, and ONE traced rehearsal of `sweep-glm` whose line holds
the seven `startup_*` metrics adding up to the record's first contact."""
import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402

# the cells whose accepted tests take a line with more metrics than their
# PR brought (tests/benchmark/test_benchmark_rehearse.py: `<=`); the other
# three pin their line's set (PERF.md §7 (h)) and wait for a `benchmark` PR
CELLS = {"sweep-glm", "sweep-gbt"}
SECONDS = ["startup_import_s", "startup_reach_device_s",
           "startup_trace_lower_s", "startup_cache_load_s",
           "startup_compile_s", "startup_run_s"]
KEYS = SECONDS + ["startup_programs"]


def _layer(name):
    with open(os.path.join(REPO, "benchmark", "layers", name + ".json")) as f:
        return json.load(f)


def _read(ctx, key):
    return harness.load_module("readers", "startup").read(ctx, {"key": key})


def _ctx():
    return types.SimpleNamespace(notes={})


RECORD = {
    "complete": True, "before_import_s": None,
    "first_contact_s": 20.0, "startup_import_s": 3.5,
    "startup_reach_device_s": 6.5, "startup_trace_lower_s": 2.0,
    "startup_cache_load_s": 1.0, "startup_compile_s": 0.0,
    "startup_run_s": 7.0, "startup_programs": 41, "true_compiles": 0,
    "cache_hits": 41, "events_dropped": 0, "listener_s": 0.001,
    "programs": [{"fun_name": f"p{i}", "trace_s": 0.1, "lower_s": 0.1,
                  "load_s": 0.1, "compile_s": 0.0, "loads": 1,
                  "compiles": 0, "cache_hit": True} for i in range(60)],
    "later_programs": []}


# -- the reader ------------------------------------------------------------------

@pytest.mark.parametrize("key", KEYS)
def test_every_key_reads_a_number_never_none(key, monkeypatch):
    from transmogrifai_tpu.utils import platform
    monkeypatch.setattr(platform, "startup_record", lambda: dict(RECORD))
    ctx = _ctx()
    value = _read(ctx, key)
    assert value == RECORD[key] and value is not None
    assert isinstance(value, int if key == "startup_programs" else float)
    # a warm run's compile seconds are 0.0, a number
    assert _read(ctx, "startup_compile_s") == 0.0
    # read once a run, kept whole for the report, the rows cut
    kept = ctx.notes["startup_record"]
    assert kept["first_contact_s"] == 20.0 and len(kept["programs"]) == 40
    assert sum(_read(ctx, k) for k in SECONDS) == kept["first_contact_s"]


def test_a_program_older_than_its_ledger_reads_zero(monkeypatch):
    """What the parent commit gives: run.py fails a run on the chip whose
    reader finds nothing, so the reader answers what that program holds."""
    from transmogrifai_tpu.utils import platform
    monkeypatch.delattr(platform, "startup_record")
    ctx = _ctx()
    assert [_read(ctx, k) for k in KEYS] == [0] * 7
    assert ctx.notes["startup_record"] is None


def test_a_field_that_has_gone_reads_nothing(monkeypatch):
    from transmogrifai_tpu.utils import platform
    gone = {k: v for k, v in RECORD.items() if k != "startup_run_s"}
    monkeypatch.setattr(platform, "startup_record",
                        lambda: dict(gone, startup_import_s=None))
    ctx = _ctx()
    assert _read(ctx, "startup_run_s") is None
    assert _read(ctx, "startup_import_s") is None
    assert _read(ctx, "complete") is None      # a flag is not a metric
    assert _read(ctx, "startup_programs") == 41


@pytest.mark.parametrize("key", KEYS)
def test_the_layer_files_are_the_issues(key):
    spec = _layer(key)
    assert spec["layer"] == "start-up and compile cache"
    assert spec["source"] == "program_counter" and spec["moves"] == "setup_s"
    assert spec["reader"] == "startup" and spec["args"] == {"key": key}
    # membership, not equality: a `benchmark` PR that admits the other
    # cells appends to both lists and leaves this file alone
    assert CELLS <= set(spec["cells"]) and spec["better"] == "lower"
    assert "reads 0" in spec["what"]   # the parent's side is no measurement
    assert spec["unit"] == ("count" if key == "startup_programs" else "s")
    assert spec["modules"] == ["transmogrifai_tpu/__init__.py",
                               "transmogrifai_tpu/utils/platform.py",
                               "transmogrifai_tpu/utils/tracing.py"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == key]
    assert CELLS <= set(entry["workloads"])
    assert sorted(entry["workloads"]) == sorted(spec["cells"])


# -- one cell, rehearsed ------------------------------------------------------------

def test_rehearsed_sweep_glm_prints_the_split_of_its_start_up(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)   # conftest's 8 virtual devices
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "sweep-glm", "--seed", "3300000005", "--seconds", "3",
         "--trace", "1", "--rehearse", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    report, line = map(json.loads, r.stdout.strip().splitlines())
    assert line["correct"] is True, report["problems"]
    metrics = line["metrics"]
    for key in KEYS:
        assert metrics[key]["unit"] == _layer(key)["unit"], key
        assert isinstance(metrics[key]["value"], (int, float)), key
        assert metrics[key]["value"] >= 0, key
    assert metrics["startup_compile_s"]["value"] \
        + metrics["startup_cache_load_s"]["value"] > 0
    assert 0 < metrics["startup_programs"]["value"] \
        <= report["compiles"]["at_window"]["programs"]
    rec = report["notes"]["startup_record"]
    assert rec["complete"] is True
    assert sum(metrics[k]["value"] for k in SECONDS) == pytest.approx(
        rec["first_contact_s"], abs=0.05)
    # first contact is inside set-up: what is left over is Python's start,
    # `import jax` and the spies after the warm-up job
    setup_s = report["notes"]["traced_end_to_end"]["setup_s"]
    assert 0 < rec["first_contact_s"] < setup_s
    assert rec["programs"] and rec["programs"][0]["fun_name"]
