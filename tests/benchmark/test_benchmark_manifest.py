"""BENCHMARK.json against the driver's contract and against the files it
names: every name and unit in the allowed characters, every cell's config,
driver and layer files present, every `moves` an end-to-end metric of the
cells that report it, the run length inside the check's budget, and the
benchmark's peaks equal to the program's."""
import glob
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return _load(REPO, "BENCHMARK.json")


@pytest.fixture(scope="module")
def layers():
    return {os.path.basename(p)[:-5]: _load(p)
            for p in glob.glob(os.path.join(BENCH, "layers", "*.json"))}


def test_top_level_keys_and_command(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert len(json.dumps(manifest)) < 64 << 10
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word


def test_run_length_fits_the_full_check(manifest):
    """2 + 14 x 24 runs of run_seconds + 60 s, 2 x 90 s per cell to
    compile and 1200 s spare must fit 43200 s with all 24 cells."""
    rs = manifest["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_entry_keys(manifest):
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        names.append(w["name"])
    for kind in ("end_to_end", "per_layer"):
        for m in manifest[kind]:
            want = {"name", "unit", "better", "source"}
            want |= {"bound"} if kind == "end_to_end" else {"layer", "moves"}
            assert set(m) - {"workloads"} == want, m
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
            names.append(m["name"])
    assert all(NAME.match(n) for n in names), names
    assert len(names) == len(set(names))
    for entry in manifest["configs"] + manifest["workloads"]:
        for key in ("why", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200, (entry["name"], key)
                assert "\n" not in entry[key] and "\t" not in entry[key]
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(len(manifest["workloads"]) // 4, 1)


def test_end_to_end_bounds(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


def test_every_cell_has_its_files(manifest, layers):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    for w in manifest["workloads"]:
        cell = _load(BENCH, "workloads", w["name"] + ".json")
        for key in ("name", "config", "traffic", "chips", "why"):
            assert cell[key] == w[key], (w["name"], key)
        assert os.path.exists(os.path.join(BENCH, "drivers",
                                           cell["driver"] + ".py"))
        conf = configs[w["config"]]
        used.add(w["config"])
        assert conf["file"] == f"benchmark/configs/{w['config']}.json"
        on_disk = _load(REPO, conf["file"])
        assert on_disk["name"] == conf["name"]
        assert on_disk["source"] == conf["source"]
        assert sorted(on_disk["reduced"]) == sorted(conf["reduced"])
        e2e = {m["name"] for m in manifest["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert set(cell["units"]) == e2e
        mine = [m for m in manifest["per_layer"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert mine, f"{w['name']} reports no per-layer metric"
        for m in mine:
            assert m["moves"] in e2e, (w["name"], m["name"])
    assert used == set(configs)


def test_per_layer_entries_are_their_layer_files(manifest, layers):
    """Every listed metric is a layer file, word for word. A layer file
    that is not listed belongs to cells that are not listed either (their
    files wait under benchmark/ for the PR that can admit them)."""
    listed = {m["name"]: m for m in manifest["per_layer"]}
    assert set(listed) <= set(layers)
    cells = {w["name"] for w in manifest["workloads"]}
    by_layer = {}
    for name, spec in layers.items():
        assert spec["name"] == name
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
        assert len(spec["layer"]) <= 200 and "\n" not in spec["layer"]
        if name.endswith("_roofline"):
            assert spec["unit"] == "%"
        by_layer.setdefault(spec["layer"].lower(), set()).add(spec["layer"])
        if name not in listed:
            assert not set(spec["cells"]) & cells, name
            continue
        m = listed[name]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (name, key)
        assert sorted(set(spec.get("cells", cells)) & cells) == \
            sorted(m.get("workloads", cells))
    # metrics of one layer give the same `layer`, letter for letter
    assert all(len(v) == 1 for v in by_layer.values()), by_layer


def test_files_under_paths_are_named_from_name_characters(manifest):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for root in manifest["paths"]:
        for d, dirs, files in os.walk(os.path.join(REPO, root)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), REPO)
                assert ok.match(rel), rel


def test_peaks_equal_the_programs():
    from transmogrifai_tpu.utils.platform import DEVICE_SPECS
    peaks = _load(BENCH, "peaks.json")["devices"]
    assert set(peaks) == set(DEVICE_SPECS)
    for kind, spec in DEVICE_SPECS.items():
        for key, val in peaks[kind].items():
            assert getattr(spec, key) == val, (kind, key)


def test_unknown_device_kind_is_an_error():
    import sys
    sys.path.insert(0, REPO)
    from benchmark import harness
    assert harness.load_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(harness.BenchFailure, match="not in benchmark/peaks"):
        harness.load_peaks("TPU v9 imaginary")
