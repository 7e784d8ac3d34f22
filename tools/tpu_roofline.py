"""Tree-sweep roofline measurement (VERDICT r4 #4).

Bytes-moved and FLOP models for the fold-fused tree sweep's two hot
kernels — the gradient histogram (pallas one-hot MXU contraction) and
the level routing pass — measured warm on the live backend at the
BASELINE shape (10M x 64, 5 folds, 32 bins), then compared against the
device's attainable HBM bandwidth and MXU peak. Prints ONE JSON line.

Per histogram pass (depth-d level, all folds fused):
  reads:  Xb_t [F, N] int8  +  pay_t [folds*3, N] (bf16|f32)
          + slot_t [folds, N] f32
  writes: hist [folds*slots*3, F*B] f32 (tiny)
  FLOPs:  2 * N * (folds*3) * (F*B)   (dense one-hot contraction on MXU)
Per routing pass: reads Xb_t + node ids [folds, N] i32, writes new ids.

Reference anchor: XGBoost's hist method is the reference's only native
tree path (SURVEY §2.9, XGBoostParams.scala:62); its CUDA hist kernel is
the moral equivalent of hist_pallas here.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main():
    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.ops import pallas_hist
    from transmogrifai_tpu.ops.trees import bin_matrix, quantile_edges

    n = int(os.environ.get("ROOFLINE_ROWS", "10000000"))
    F = int(os.environ.get("ROOFLINE_COLS", "64"))
    folds = 5
    n_bins = 32
    depth = 6
    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", str(dev))
    backend = jax.default_backend()

    # attainable numbers from the one peaks table (an unknown TPU kind
    # raises; off-TPU there is no roof and nothing to measure)
    from transmogrifai_tpu.utils.platform import device_spec
    spec = device_spec()
    if spec is None:
        print(json.dumps({"metric": "tree_sweep_roofline",
                          "backend": backend,
                          "error": "needs a TPU backend"}))
        sys.exit(1)
    hbm_gbs, peak_bf16 = spec.hbm_bytes_per_s / 1e9, spec.bf16_flops

    key = jax.random.PRNGKey(0)
    X = jax.random.normal(key, (n, F), jnp.float32)
    edges = quantile_edges(X, n_bins)
    Xb = bin_matrix(X, edges)
    Xb_t = jnp.asarray(Xb.T)                      # [F, N] int8
    del X
    bf16 = True  # the tree fits' histogram inputs (pallas_hist._HIST_BF16)
    pay_np = np.random.default_rng(1).normal(
        size=(folds * 3, n)).astype(np.float32)
    pay_t = jnp.asarray(pay_np)
    # deepest level: 2^(depth-1) slots — the widest histogram of a fit
    n_slots = 1 << (depth - 1)
    slot_t = jnp.asarray(
        np.random.default_rng(2).integers(0, n_slots, size=(folds, n)),
        jnp.float32)

    def timed(fn, *args, reps=3, **kw):
        jax.block_until_ready(fn(*args, **kw))   # warm (compile)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args, **kw))
            best = min(best, time.perf_counter() - t0)
        return best

    result = {"metric": "tree_sweep_roofline", "backend": backend,
              "device_kind": kind, "rows": n, "cols": F, "folds": folds,
              "n_bins": n_bins, "n_slots": n_slots, "hist_bf16": bf16,
              "attainable": {"hbm_gbs": hbm_gbs,
                             "peak_bf16_tflops": peak_bf16 / 1e12}}

    if pallas_hist.available():
        hist_s = timed(pallas_hist.hist_pallas, Xb_t, pay_t, slot_t,
                       n_slots=n_slots, n_bins=n_bins, allow_bf16=bf16)
        pay_bytes = 2 if bf16 else 4
        hist_read = n * F * 1 + folds * 3 * n * pay_bytes + folds * n * 4
        hist_write = folds * n_slots * 3 * F * n_bins * 4
        hist_flops = 2.0 * n * (folds * 3) * (F * n_bins)
        result["hist"] = {
            "s": round(hist_s, 4),
            "bytes_moved_gb": round((hist_read + hist_write) / 1e9, 3),
            "achieved_gbs": round((hist_read + hist_write) / hist_s / 1e9, 1),
            "pct_hbm_roof": round(
                100 * (hist_read + hist_write) / hist_s / 1e9 / hbm_gbs, 1),
            "flops_tf": round(hist_flops / 1e12, 3),
            "achieved_tfs": round(hist_flops / hist_s / 1e12, 2),
            "pct_mxu_roof": round(
                100 * hist_flops / hist_s / peak_bf16, 1),
        }

        # routing pass at the same level
        node_t = jnp.asarray(
            np.random.default_rng(3).integers(0, n_slots, (folds, n)),
            jnp.float32)
        f_lvl = jnp.asarray(
            np.random.default_rng(4).integers(0, F, (folds, n_slots)),
            jnp.int32)
        t_lvl = jnp.asarray(
            np.random.default_rng(5).integers(1, n_bins, (folds, n_slots)),
            jnp.int32)
        d_lvl = jnp.zeros((folds, n_slots), jnp.int32)
        try:
            route_s = timed(pallas_hist.route_pallas, Xb_t, node_t,
                            f_lvl, t_lvl, d_lvl, n_nodes=n_slots, reps=5)
            route_bytes = n * F * 1 + folds * n * 4 * 2
            result["route"] = {
                "s": round(route_s, 4),
                "bytes_moved_gb": round(route_bytes / 1e9, 3),
                "achieved_gbs": round(route_bytes / route_s / 1e9, 1),
                "pct_hbm_roof": round(
                    100 * route_bytes / route_s / 1e9 / hbm_gbs, 1),
            }
        except Exception as e:  # signature drift: report, don't die
            result["route"] = {"error": str(e)[:200]}

        # whole-fit extrapolation: levels x rounds x the 16-config grid
        if "hist" in result and "s" in result["hist"]:
            per_level = result["hist"]["s"] + result.get("route", {}).get(
                "s", 0.0)
            est = per_level * depth * 10 * 16
            result["sweep_extrapolation"] = {
                "per_level_s": round(per_level, 4),
                "est_16cfg_10round_s": round(est, 1),
                "note": "upper bound: every level priced at the deepest "
                        "level's slot count",
            }
    else:
        result["error"] = "pallas unavailable on this backend"

    print(json.dumps(result))


if __name__ == "__main__":
    main()
