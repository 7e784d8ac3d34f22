"""chip_smoke.py off the chip: its kernel-vs-twin checks at toy size with
the pallas kernels in interpret mode, its route assertions on recorded
runs, and the contract that without a chip (and without the test-only
argument) it fails and prints no result. The compile-heavy runs are marked
slow (ci.sh's full pytest runs them; Tier-1 keeps the seconds-long ones)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as CS  # noqa: E402


def _run(*args, **env_overrides):
    env = dict(os.environ, PYTHONPATH=REPO, **env_overrides)
    env.pop("XLA_FLAGS", None)   # conftest's 8 virtual devices
    return subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py"),
                           *args], env=env, capture_output=True, text=True,
                          timeout=600)


@pytest.mark.parametrize("platforms", [
    "cpu", pytest.param("", marks=pytest.mark.slow)])  # "": libtpu probes
def test_without_a_chip_it_fails_and_prints_no_result(platforms):
    r = _run(JAX_PLATFORMS=platforms)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


@pytest.mark.slow
def test_kernel_checks_at_toy_size_in_interpret_mode():
    import jax
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import trees as T

    cfg = CS.sizes(toy=True, chips=1)
    n = cfg["check_rows"]
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    X = jax.random.normal(k1, (n, cfg["n_cols"]), jnp.float32)
    y = (jax.random.uniform(k2, (n,)) < 0.5).astype(jnp.float32)
    masks = (jax.random.randint(k3, (n,), 0, cfg["folds"])[None, :]
             != jnp.arange(cfg["folds"])[:, None]).astype(jnp.float32)
    Xb_t = T.bin_matrix(X, T.quantile_edges(X, cfg["gbt_bins"])).T
    calls = CS._toy_dispatcher_calls(cfg)
    res = CS.kernel_checks(calls, Xb_t, y, masks, X[:, 0], toy=True)
    assert [r["kernel"] for r in res] == [c["kernel"] for c in calls]
    assert all(r["ok"] for r in res)
    hist = [r for r in res if "gh_worst_rel" in r]
    # bf16 input rounding is really exercised, and stays inside the bound
    assert all(0.0 < r["gh_worst_rel"] <= CS.BF16_HIST_RTOL for r in hist)

    # a kernel that returns garbage must fail the check
    from transmogrifai_tpu.ops import pallas_hist as PH
    real = PH.route
    PH.route = lambda *a, **kw: real(*a, **kw) + 1.0
    try:
        with pytest.raises(CS.SmokeFailure, match="routing"):
            CS.kernel_checks([c for c in calls if c["kernel"] == "route"],
                             Xb_t, y, masks, X[:, 0], toy=True)
    finally:
        PH.route = real


def _recorded_run(cfg, **changes):
    """What run_sweep records for a healthy one-chip sweep."""
    def call(kernel, f=64, bf16=False):
        return {"kernel": kernel, "shapes": [(f, 8)], "interpret": False,
                "available": True, "bf16_inputs": bf16, "static": {}}
    run = {
        "cell_routes": [("OpLogisticRegression", "streamed"),
                        ("OpXGBoostClassifier", "mask_folds")],
        "n_cells": len(cfg["glm_grids"]) + len(cfg["gbt_grids"]),
        "fused_route_fallbacks": 0,
        "kernel_spans": [{"kernel": "tree_sweep_fold_fused"}
                         for _ in cfg["gbt_grids"]],
        "dispatcher_calls": [call("hist_folds", bf16=True),
                             call("route_hist", bf16=True), call("route"),
                             call("table_lookup", f=5),
                             call("hist_pallas", f=1)],
    }
    run.update(changes)
    return run


def test_route_checks_read_the_record_not_the_flags():
    cfg = CS.sizes(toy=False, chips=1)
    CS.check_routes_one_chip(_recorded_run(cfg), cfg)
    bad = [
        dict(cell_routes=[("OpLogisticRegression", "vmapped"),
                          ("OpXGBoostClassifier", "mask_folds")]),
        dict(kernel_spans=[]),                 # never reached the kernel
        dict(fused_route_fallbacks=1),
        dict(dispatcher_calls=[dict(c, interpret=True) for c in
                               _recorded_run(cfg)["dispatcher_calls"]]),
        dict(dispatcher_calls=[dict(c, available=False) for c in
                               _recorded_run(cfg)["dispatcher_calls"]]),
        dict(dispatcher_calls=[c for c in
                               _recorded_run(cfg)["dispatcher_calls"]
                               if c["kernel"] != "hist_pallas"]),  # exact
        #                                       metric instead of binned
        dict(dispatcher_calls=[dict(c, bf16_inputs=False) for c in
                               _recorded_run(cfg)["dispatcher_calls"]]),
    ]
    for change in bad:
        with pytest.raises(CS.SmokeFailure):
            CS.check_routes_one_chip(_recorded_run(cfg, **change), cfg)


@pytest.mark.slow
def test_toy_run_end_to_end(tmp_path):
    r = _run("--toy", "--out", str(tmp_path), JAX_PLATFORMS="cpu")
    assert r.returncode == 0, r.stderr[-2000:]
    report_line, last = r.stdout.strip().splitlines()[-2:]
    # the driver's contract: the last line is the verdict and nothing else
    verdict = json.loads(last)
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    out = json.loads(report_line)
    with open(tmp_path / "report.json") as f:
        assert json.load(f)["leg_b"] == out["leg_b"]
    assert out["ok"] and out["toy"] and out["device"] == verdict["device"]
    assert out["device"]["platform"] == "cpu"
    assert out["leg_a"]["warm"]["true_compiles"] == 0
    assert out["leg_b"]["post_warmup_compiles"] == 0
    assert np.isfinite(out["leg_a"]["aupr_parity"]["delta"])
