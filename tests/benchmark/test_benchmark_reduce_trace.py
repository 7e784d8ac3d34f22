"""benchmark/reduce_trace.py: on a hand-made trace against numbers worked
out on paper (synthetic_trace.py), and on a small trace recorded on the
TPU v5e (recorded_v5e.xplane.pb: two jitted programs, three `bench.job`
spans; chip run of PR 22) for what the planes and lines are really
called."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

from benchmark.reduce_trace import Reduced, union_ns  # noqa: E402
import synthetic_trace  # noqa: E402


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData
    return Reduced.from_profile(
        ProfileData.from_text_proto(synthetic_trace.text_proto()))


def test_union_counts_overlap_once():
    assert union_ns([(0, 10), (5, 20), (30, 40), (32, 35)]) == 30
    assert union_ns([]) == 0


def test_window_is_the_hull_of_the_benchmark_spans(red):
    assert red.on_device and red.chips == [0]
    assert red.window() == (50_000.0, 1_000_000.0)
    assert len(red.jobs("bench.job")) == 2


def test_busy_is_the_union_of_op_intervals(red):
    # while.1 covers its nested ops: 300 us; job 2: 100 + 100 us; the op
    # after the last span is outside the window
    assert red.busy_ns() == 500_000.0
    assert red.busy_ns(0.0, 2_000_000.0) == 600_000.0


def test_ops_belong_to_the_module_that_contains_them(red):
    mods = {o.name: o.module for o in red.ops}
    assert mods["custom-call.3"] == "jit_alpha"
    assert mods["_hist_pallas_jit.7"] == "jit_beta"
    assert mods["fusion.9"] == "jit_alpha"
    alpha = red.select(module="alpha")
    # per job: 300 us in job 1, 0 in job 2 -> mean 150 us
    assert red.per_job_s(alpha, "bench.job") == pytest.approx(150e-6)
    # `op` sees the whole HLO line: the opcode finds both kernels, the
    # jitted wrapper's name one of them
    calls = red.select(op="custom-call")
    assert red.per_job_s(calls, "bench.job") == pytest.approx(100e-6)
    hist = red.select(module="beta", op=r"^%_hist_pallas_jit")
    assert [o.name for o in hist] == ["_hist_pallas_jit.7"]
    assert red.select(module="gamma") == []
    assert red.per_job_s(alpha, "bench.nothing") is None


def test_top_ops_are_by_self_time(red):
    top = dict(red.top_ops())
    # while.1: 300 us less its two nested 100 us ops
    assert top["jit_alpha:while.1"] == pytest.approx(100e-6)
    assert top["jit_alpha:fusion.2"] == pytest.approx(100e-6)
    assert top["jit_beta:sort.5"] == pytest.approx(100e-6)
    assert "jit_alpha:fusion.9" not in top       # outside the window
    assert sum(top.values()) == pytest.approx(500e-6)


def test_idle_gaps_are_named_by_what_the_host_did(red):
    gaps = dict(red.idle_gaps())
    # [50,100) mid 75 -> Dispatch(alpha); [400,500) mid 450 -> the span
    # alone; [500,550) between spans; [550,600) mid 575 -> HostPrep;
    # [700,750) mid 725 -> Dispatch(beta); [850,1000) mid 925 -> the span
    assert gaps["bench.job>Dispatch(alpha)"] == pytest.approx(50e-6)
    assert gaps["bench.job>HostPrep"] == pytest.approx(50e-6)
    assert gaps["bench.job>Dispatch(beta)"] == pytest.approx(50e-6)
    assert gaps["bench.job"] == pytest.approx(250e-6)
    assert gaps["outside_spans"] == pytest.approx(50e-6)
    assert sum(gaps.values()) == pytest.approx(950e-6 - 500e-6)


def test_a_trace_that_lost_device_events_says_so(red):
    assert red.lost_dispatches() == []
    # the same trace, had the host dispatched a program at 900 us: the
    # last device op it holds inside the window ends at 850 us
    host = dict(red.host)
    line = red.spans[0].line
    host[line] = sorted(host[line] + [
        (855_000.0, 856_000.0, "PjitFunction(soon_after)"),   # within slack
        (900_000.0, 910_000.0, "PjitFunction(fit_gbt)"),
        (1_200_000.0, 1_210_000.0, "PjitFunction(outside_window)")])
    cut = Reduced(red.ops, red.spans, host, on_device=True)
    assert cut.lost_dispatches(slack_ns=10_000.0) == ["fit_gbt"]
    # a dispatch in the last 2 % of the window is a job's own tail
    host[line] = sorted(red.host[line] + [
        (990_000.0, 991_000.0, "PjitFunction(less)")])
    ops = red.ops + [red.ops[0].__class__(960_000.0, 985_000.0, "fusion.1",
                                          "jit_beta", 0, "fusion.1")]
    assert Reduced(ops, red.spans, host, True).lost_dispatches(1_000.0) == []


def test_host_gap_and_trace_time_readers(red):
    import types
    from benchmark import harness
    ctx = types.SimpleNamespace(reduced=red, cell={"job_span": "bench.job"},
                                counters={"k": 7}, peaks=None, notes={})
    gap = harness.load_module("readers", "host_gap").read(ctx, {})
    # job 1: 450 - 300; job 2: 450 - 200 -> mean 200 us
    assert gap == pytest.approx(200e-6)
    tt = harness.load_module("readers", "trace_time")
    assert tt.read(ctx, {"module": "beta", "op": "sort"}) == \
        pytest.approx(50e-6)
    assert tt.read(ctx, {"module": "nowhere"}) is None
    ctx.reduced = None
    assert tt.read(ctx, {"module": "beta"}) is None
    counter = harness.load_module("readers", "counter")
    assert counter.read(ctx, {"key": "k"}) == 7
    assert counter.read(ctx, {"key": "absent"}) is None


def test_roofline_reader_takes_its_work_from_the_layer_file(red):
    import types
    from benchmark import harness, opcount
    roof = harness.load_module("readers", "roofline")
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    ctx = types.SimpleNamespace(
        reduced=red, cell={"job_span": "bench.job"}, peaks=peaks, notes={},
        counters={"n": 1000, "d": 8, "lp": 3, "dp": 2, "isz": 2})
    args = {"module": "alpha", "work": "glm_sweep", "counters": {
        "rows": "n", "cols": "d", "padded_lane_passes": "lp",
        "data_passes": "dp", "itemsize": "isz"}}
    flops, byts = opcount.glm_sweep(1000, 8, 3, 2, 2)
    least = max(flops / 1e12, byts / 1e9)
    # alpha runs 150 us a job (above)
    assert roof.read(ctx, args) == pytest.approx(100 * least / 150e-6)
    assert ctx.notes["rooflines"]["glm_sweep"]["roof"] == "bytes"
    del ctx.counters["lp"]                  # the run lacks a count
    assert roof.read(ctx, args) is None
    ctx.peaks = None                        # a rehearsal has no peaks
    assert roof.read(ctx, args) is None


def test_on_the_chip_a_metric_that_reads_nothing_fails_the_run():
    import types
    import importlib.util
    from benchmark import harness
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(REPO, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    layers = [{"name": "there", "unit": "count", "source": "program_counter",
               "reader": "counter", "args": {"key": "k"}},
              {"name": "gone", "unit": "s", "source": "device_trace",
               "cells": ["c"], "reader": "counter", "args": {"key": "x"}},
              {"name": "other_cells", "unit": "s", "source": "device_trace",
               "cells": ["d"], "reader": "counter", "args": {"key": "x"}}]
    fake = types.SimpleNamespace(
        layer_files=lambda: layers, load_module=harness.load_module,
        BenchFailure=harness.BenchFailure)
    ctx = types.SimpleNamespace(cell={"name": "c"}, counters={"k": 1},
                                rehearse=False)
    with pytest.raises(harness.BenchFailure, match="gone"):
        run.per_layer(ctx, fake, True)
    # a trace that lost device events explains its device-trace metrics
    assert run.per_layer(ctx, fake, False) == \
        {"there": {"value": 1, "unit": "count"}}
    ctx.rehearse = True                     # the CPU has no such names
    assert set(run.per_layer(ctx, fake, True)) == {"there"}


def test_recorded_v5e_trace():
    path = os.path.join(HERE, "recorded_v5e.xplane.pb")
    red = Reduced.from_file(path)
    assert red.on_device and red.chips == [0]
    assert len(red.jobs("bench.job")) == 3
    lo, hi = red.window()
    assert 0 < red.busy_ns() < hi - lo
    mods = {o.module for o in red.ops}
    assert any("gram_step" in m for m in mods), mods
    assert any("colsum" in m for m in mods), mods
    per_job = red.per_job_s(red.select(module="gram_step"), "bench.job")
    assert 0 < per_job < (hi - lo) / 1e9
    bd = red.breakdown()
    assert bd["device_ops"] and bd["idle_gaps"]
    assert all(name.startswith(("bench.job", "outside_spans",
                                "shorter_gaps"))
               for name, _ in bd["idle_gaps"])
