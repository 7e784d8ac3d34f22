"""SHA-256 of the lowered text (`.lower(...).as_text()`, no locations) of
the programs the benchmark's cells run OUTSIDE the squared loss's Gram
pass and the boosters' fused tree growth, at small fixed shapes: run it
from the root of two checkouts and compare, to rule a change out of a
program it says it does not touch.

    JAX_PLATFORMS=cpu python tools/lowered_digests.py [checkout]

Prints one JSON object, {program: digest}. The CPU's lowering (a Mosaic
pass lowers here as its XLA twin; PERF.md, PR 48)."""
import hashlib
import json
import os
import sys


def digests(root: str) -> dict:
    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.automl.tuning import folds
    from transmogrifai_tpu.ops import glm_sweep as GS
    from transmogrifai_tpu.ops import trees as T

    if not GS.__file__.startswith(root):
        raise SystemExit(f"{GS.__file__} is not of the checkout {root}")
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    S = jax.ShapeDtypeStruct
    n, d, F, L, K = 4096, 128, 5, 8, 4

    def rows(dt=f32):
        return S((n, d), dt), S((n,), f32), S((n,), f32), S((F, n), f32)
    X, y, w, masks = rows(bf16)
    col, lane, lanes = S((d,), f32), S((L,), f32), S((L, d), f32)
    budget, tol = S((), i32), S((), f32)
    round_args = (S((F, L), f32), lane, lane, lanes, lane, col, col)
    # the fused tree growth where a node or a level draws its features
    # (the forests' cells; a booster under colsample_bylevel): the programs
    # a dead level does not end (PR 52)
    Xb, trees, fo, bins = S((n, 16), jnp.int8), 2, 10, 32
    out = {
        "fit_forest_lanes[22 of 64 a node]": T.fit_forest_lanes.lower(
            Xb, y, masks, S((trees, n), f32), S((trees, 2), jnp.uint32),
            S((F, n), f32), depth=4, n_bins=bins, feature_frac=22 / 64,
            payload="centred_parts", centre=S((2,), f32)),
        "fit_gbt_folds[colsample_bylevel 0.5]": T.fit_gbt_folds.lower(
            Xb, y, S((fo, n), f32), S((2,), jnp.uint32), n_rounds=2,
            depth=4, n_bins=bins, colsample_bylevel=0.5),
        "glm_standardize_stats": GS.glm_standardize_stats.lower(X, w),
        "sweep_glm_round": GS.sweep_glm_round.lower(
            X, y, w, masks, *round_args, budget, tol, loss="logistic"),
        "mlr_gram_factor": GS.mlr_gram_factor.lower(
            X, w, masks, col, col, S((L,), i32), lane, n_classes=K),
        "sweep_mlr_round": GS.sweep_mlr_round.lower(
            X, y, w, masks, S((F, L), f32), lane, lane, S((L, d, K), f32),
            S((L, K), f32), col, col, S((L, d, d), f32), lanes, budget,
            tol),
        "wide_gram": GS.wide_gram.lower(X, w, col, col),
        "sweep_gram_solve": GS.sweep_gram_solve.lower(
            S((F, d, d), f32), S((F, d), f32), S((F, d), f32), S((F,), f32),
            S((F,), f32), col, col, lane, lane, budget, tol),
        "assign_fold_masks": folds.assign_fold_masks.lower(
            S((2,), jnp.uint32), y, n=n, n_folds=F),
        # and the Gram pass itself where this PR says it is today's body
        "sweep_gram_moments[float32]": GS.sweep_gram_moments.lower(
            *rows(f32), col, col),
    }
    return {k: hashlib.sha256(v.as_text().encode()).hexdigest()
            for k, v in out.items()}


if __name__ == "__main__":
    root = os.path.abspath(sys.argv[1] if len(sys.argv) > 1 else ".")
    sys.path.insert(0, root)
    print(json.dumps(digests(root), indent=1))
