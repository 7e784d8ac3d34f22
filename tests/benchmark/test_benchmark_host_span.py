"""benchmark/readers/host_span.py on a hand-made trace against numbers
worked out on paper (synthetic_host_spans.py), on a trace that has no
`tmog.` span (a program older than its annotations) and on one whose
spans the pattern does not match; and each sweep cell rehearsed with
--trace 1: every new metric printed, and the phases' exposed seconds plus
the uncovered ones adding up to the host gap of the same run."""
import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

from benchmark import harness  # noqa: E402
from benchmark.reduce_trace import Reduced  # noqa: E402
import synthetic_host_spans  # noqa: E402
import synthetic_trace  # noqa: E402

US = 1e-6
TOP_LEVEL = r"^tmog\.(validate_phase|sweep_fit|sweep_eval):"
ROOT = r"^tmog\.validate:"


def _ctx(text_proto):
    from jax.profiler import ProfileData
    red = Reduced.from_profile(ProfileData.from_text_proto(text_proto))
    return types.SimpleNamespace(reduced=red, cell={"job_span": "bench.job"})


def _read(ctx, name, stat):
    reader = harness.load_module("readers", "host_span")
    return reader.read(ctx, {"name": name, "stat": stat})


@pytest.fixture(scope="module")
def spans():
    return _ctx(synthetic_host_spans.text_proto())


@pytest.mark.parametrize("name,micros", [
    (r"^tmog\.validate_phase:fold_assign$", 130),   # no device work inside
    (r"^tmog\.validate_phase:device_place$", 10),   # op A overlaps it
    (r"^tmog\.sweep_fit:", 100),    # its share of the gap [400, 600)
    (r"^tmog\.sweep_eval:", 145),   # the gap's other side, and op B inside
    (r"^tmog\.validate_phase:winner$", 15),
    (TOP_LEVEL, 400),               # disjoint phases add up
    # nested spans count once: sweep_fit's subtree adds nothing to it; the
    # metric fetches add [520, 710) less op B and [800, 850) of job 1:
    # (100 + 90 + 50 + 100) / 2
    (r"^tmog\.(sweep_fit|sweep_round|host_step):", 170),
])
def test_exposed_seconds_are_idle_time_under_the_union(spans, name, micros):
    assert _read(spans, name, "exposed_s") == pytest.approx(micros * US)


def test_uncovered_and_phases_add_up_to_the_host_gap(spans):
    gap = harness.load_module("readers", "host_gap").read(spans, {})
    assert gap == pytest.approx(500 * US)
    uncovered = _read(spans, ROOT, "uncovered_s")
    assert uncovered == pytest.approx(100 * US)
    assert _read(spans, TOP_LEVEL, "exposed_s") + uncovered == \
        pytest.approx(gap)


def test_count_is_a_mean_over_the_jobs_on_the_jobs_thread(spans):
    fetches = r"^tmog\.host_step:(metric_fetch|round_fetch)$"
    assert _read(spans, fetches, "count") == 2          # (3 + 1) / 2
    assert _read(spans, r"^tmog\.host_step:round_prep$", "count") == 0.5
    # thread B's span is over both jobs, and is none of theirs
    assert _read(spans, r"^tmog\.stage:", "count") is None


def test_a_span_the_pattern_misses_reads_nothing(spans):
    for stat in ("exposed_s", "count", "uncovered_s"):
        assert _read(spans, r"^tmog\.validate_phase:renamed$", stat) is None
    with pytest.raises(ValueError, match="no such stat"):
        _read(spans, ROOT, "self_s")


def test_a_program_without_annotations_reads_zero_and_all_uncovered():
    """PR 22's hand-made trace has no `tmog.` span: what a parent commit
    gives. The reader must answer (run.py fails a run on the chip whose
    reader finds nothing) and say what that trace shows."""
    old = _ctx(synthetic_trace.text_proto())
    assert _read(old, r"^tmog\.sweep_fit:", "exposed_s") == 0.0
    assert _read(old, r"^tmog\.host_step:", "count") == 0
    gap = harness.load_module("readers", "host_gap").read(old, {})
    assert _read(old, ROOT, "uncovered_s") == pytest.approx(gap)
    none = types.SimpleNamespace(reduced=None, cell={"job_span": "bench.job"})
    assert _read(none, ROOT, "uncovered_s") is None


# -- each cell, rehearsed -----------------------------------------------------

def _new_metrics(cell):
    out = {}
    for f in os.listdir(os.path.join(REPO, "benchmark", "layers")):
        with open(os.path.join(REPO, "benchmark", "layers", f)) as fh:
            spec = json.load(fh)
        if spec["reader"] == "host_span" and cell in spec["cells"]:
            out[spec["name"]] = spec
    return out


@pytest.mark.parametrize("cell,gap,n_new", [
    ("sweep-glm", "glm_host_gap_s", 6), ("sweep-gbt", "gbt_host_gap_s", 4)])
def test_rehearsed_cell_prints_the_split_of_its_host_gap(cell, gap, n_new,
                                                         tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)   # conftest's 8 virtual devices
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", cell, "--seed", "5", "--seconds", "3", "--trace", "1",
         "--rehearse", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    report, line = map(json.loads, r.stdout.strip().splitlines())
    specs = _new_metrics(cell)
    assert len(specs) == n_new
    metrics = line["metrics"]
    for name, spec in specs.items():
        assert metrics[name]["unit"] == spec["unit"], name
        assert metrics[name]["value"] >= 0, name
    # the same trace, read once more: all top-level phases (three of them
    # have no metric of their own) and the rest add up to the host gap
    ctx = types.SimpleNamespace(
        reduced=Reduced.from_file(report["notes"]["xplane"]),
        cell={"job_span": "bench.validate"})
    prefix = cell.split("-")[1]
    uncovered = metrics[f"{prefix}_unspanned_host_s"]["value"]
    assert _read(ctx, ROOT, "uncovered_s") == pytest.approx(uncovered)
    phases = _read(ctx, TOP_LEVEL, "exposed_s")
    assert phases + uncovered == pytest.approx(metrics[gap]["value"],
                                               rel=1e-6)
    assert phases > 0
    listed = sum(metrics[n]["value"] for n, s in specs.items()
                 if s["args"]["stat"] == "exposed_s")
    assert listed <= phases * (1 + 1e-9)
    # the breakdown names the program's spans under the benchmark's
    assert any(">tmog." in name for name, _ in
               line["breakdown"]["idle_gaps"])
