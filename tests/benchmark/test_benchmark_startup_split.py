"""benchmark/readers/startup_split.py on hand-made records (a program older
than `ledger_version`: 0; one that has it: the value; one that has it and
lost the key: nothing), the four layer files of PR 49 and their manifest
entries, and ONE traced rehearsal of `sweep-gbt` whose line holds the four
metrics, the two seconds adding up to the record's
`startup_reach_device_s`."""
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

# the two cells whose accepted tests admit a listed metric (`<=` in
# test_benchmark_rehearse.py:68). `sweep-glm-nulls128` and
# `sweep-linreg-nulls128` admit one on their rehearsed line (`<= listed`,
# test_benchmark_nulls.py:261, test_benchmark_linreg.py:301) but hold the
# manifest's set for the cell EQUAL (`mine == {...}`, :343 and :402), and
# the other five pin their line's set: a `benchmark` PR's to re-aim
CELLS = ["sweep-glm", "sweep-gbt"]
SPLIT = ["startup_backend_up_s", "startup_first_dispatch_s",
         "startup_backend_up_cpu_s"]
# metric -> (reader, the record's key)
FILES = dict({k: ("startup_split", k) for k in SPLIT},
             startup_before_import_s=("startup", "before_import_s"))

V2 = {
    "ledger_version": 2, "complete": True, "before_import_s": 2.75,
    "backend_up_before_import": False, "first_contact_s": 20.0,
    "startup_import_s": 0.5, "startup_reach_device_s": 6.5,
    "startup_backend_up_s": 6.25, "startup_first_dispatch_s": 0.25,
    "startup_backend_up_cpu_s": 1.5, "kernel_import_s": 1.75,
    "kernel_import_cpu_s": 1.25, "programs": [], "later_programs": []}
# what the parent of PR 49 holds: the six, `before_import_s`, no version
V1 = {k: v for k, v in V2.items()
      if k not in SPLIT + ["ledger_version", "backend_up_before_import",
                           "kernel_import_cpu_s"]}


def _layer(name):
    with open(os.path.join(REPO, "benchmark", "layers", name + ".json")) as f:
        return json.load(f)


def _read(record, name):
    from transmogrifai_tpu.utils import platform
    reader, key = FILES[name]
    ctx = types.SimpleNamespace(notes={})
    with pytest.MonkeyPatch.context() as mp:
        if record is None:
            mp.delattr(platform, "startup_record")
        else:
            mp.setattr(platform, "startup_record", lambda: dict(record))
        return harness.load_module("readers", reader).read(ctx, {"key": key})


# -- the reader ------------------------------------------------------------------

@pytest.mark.parametrize("name", SPLIT)
def test_a_record_with_the_version_reads_its_value(name):
    assert _read(V2, name) == V2[name]
    assert _read(dict(V2, **{name: 0.0}), name) == 0.0   # 0.0 is a number


@pytest.mark.parametrize("name", SPLIT)
@pytest.mark.parametrize("record", [V1, None],
                         ids=["no-version", "no-record"])
def test_a_program_older_than_the_fields_reads_zero(name, record):
    """The parent of PR 49 under this PR's benchmark files: run.py fails a
    chip run whose reader finds nothing, so the reader answers what that
    program holds."""
    assert _read(record, name) == 0


@pytest.mark.parametrize("name", SPLIT)
def test_the_version_without_the_key_reads_nothing(name):
    gone = {k: v for k, v in V2.items() if k != name}
    assert _read(gone, name) is None
    assert _read(dict(V2, **{name: None}), name) is None   # no backend timed
    assert _read(dict(V2, **{name: True}), name) is None   # a flag: no metric


def test_before_import_is_read_on_both_sides():
    """The existing reader, a key the parent holds: both sides measure."""
    assert _read(V2, "startup_before_import_s") == 2.75
    assert _read(V1, "startup_before_import_s") == 2.75
    assert _read(None, "startup_before_import_s") == 0
    assert _read(dict(V2, before_import_s=None),
                 "startup_before_import_s") is None      # /proc unreadable


def test_the_record_is_shared_with_the_startup_reader():
    from transmogrifai_tpu.utils import platform
    calls = []
    ctx = types.SimpleNamespace(notes={})
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(platform, "startup_record",
                   lambda: calls.append(1) or dict(V2))
        for name, (reader, key) in FILES.items():
            harness.load_module("readers", reader).read(ctx, {"key": key})
    assert len(calls) == 1 and ctx.notes["startup_record"]["ledger_version"] == 2


# -- the files -------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(FILES))
def test_the_layer_files_are_the_issues(name):
    spec = _layer(name)
    reader, key = FILES[name]
    assert spec["name"] == name
    assert spec["layer"] == "start-up and compile cache"
    assert spec["source"] == "program_counter" and spec["moves"] == "setup_s"
    assert spec["unit"] == "s" and spec["better"] == "lower"
    assert spec["reader"] == reader and spec["args"] == {"key": key}
    assert spec["cells"] == CELLS
    assert "reads 0" in spec["what"]   # the parent's side is no measurement
    assert spec["modules"] == ["transmogrifai_tpu/__init__.py",
                               "transmogrifai_tpu/utils/platform.py",
                               "transmogrifai_tpu/utils/tracing.py"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == name]
    assert entry == {"name": name, "unit": "s", "better": "lower",
                     "source": "program_counter",
                     "layer": "start-up and compile cache",
                     "moves": "setup_s", "workloads": CELLS}


def test_no_cell_that_pins_its_manifest_set_is_listed():
    """Every `startup_*` file, the seven of PR 33 too, lists the two cells
    alone: the other seven cells' accepted tests would fail on an entry."""
    seen = 0
    for path in os.listdir(os.path.join(REPO, "benchmark", "layers")):
        if path.startswith("startup_"):
            assert _layer(path[:-5])["cells"] == CELLS, path
            seen += 1
    assert seen == 11


# -- one cell, rehearsed ------------------------------------------------------------

def test_rehearsed_sweep_gbt_prints_the_four(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)   # conftest's 8 virtual devices
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "sweep-gbt", "--seed", "4900000007",
         "--seconds", "3", "--trace", "1", "--rehearse",
         "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    report, line = map(json.loads, r.stdout.strip().splitlines())
    assert line["correct"] is True, report["problems"]
    metrics = line["metrics"]
    for name in FILES:
        assert metrics[name]["unit"] == "s", name
        assert isinstance(metrics[name]["value"], float), name
        assert metrics[name]["value"] >= 0, name
    rec = report["notes"]["startup_record"]
    assert rec["ledger_version"] == 2 and rec["complete"] is True
    assert rec["backend_up_before_import"] is False
    # run.py reaches the device itself, straight after the import: the
    # backend's instant lies inside the interval, before the first program
    assert metrics["startup_backend_up_s"]["value"] > 0
    assert metrics["startup_backend_up_s"]["value"] \
        + metrics["startup_first_dispatch_s"]["value"] == pytest.approx(
            rec["startup_reach_device_s"], abs=1e-3)
    assert [row["platform"] for row in rec["backend_inits"]] == ["cpu"]
    assert metrics["startup_backend_up_cpu_s"]["value"] \
        <= metrics["startup_backend_up_s"]["value"] * os.cpu_count() + 0.05
    assert metrics["startup_before_import_s"]["value"] \
        == rec["before_import_s"] > 0
    # the six old seconds still add up to first contact, and with the
    # seconds before the import they stay inside set-up
    six = ["startup_import_s", "startup_reach_device_s",
           "startup_trace_lower_s", "startup_cache_load_s",
           "startup_compile_s", "startup_run_s"]
    assert sum(rec[k] for k in six) == pytest.approx(
        rec["first_contact_s"], abs=1e-6)
    setup_s = report["notes"]["traced_end_to_end"]["setup_s"]
    assert rec["first_contact_s"] < setup_s
