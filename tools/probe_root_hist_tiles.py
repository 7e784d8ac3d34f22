"""The root histogram kernel alone at a growing count of left-operand rows
(PR 52's probe; no code a cell runs): pallas_hist.hist_folds on
10 002 432 x 64 int8 bins (33 slots a column, the boosted cells' matrix) at
5 / 21 / 42 / 43 lanes of three rows (g, h and the derived count: M = 15 /
63 / 126 / 129 rows of the [M, blk] x [blk, F * B] contraction). If a pass
costs one turn of the [F * B, N] one-hot through the matrix unit a 128-row
tile of M, the first three read alike and the fourth steps up by a tile.

    chiprun --chips 1 -- python tools/probe_root_hist_tiles.py

Prints one JSON object and writes it to chiprun_out/probe_root_hist_tiles.json;
milliseconds a pass on the host's clock around block_until_ready, the best
and the median of `--repeats` warm calls. Needs the chip: the twins of a CPU
run time nothing the question is about."""
import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=10_002_432)
    ap.add_argument("--features", type=int, default=64)
    ap.add_argument("--bins", type=int, default=32)
    ap.add_argument("--lanes", type=int, nargs="+", default=[5, 21, 42, 43])
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.ops import pallas_hist as PH

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"the probe times the kernel on the chip, not on "
                         f"{dev.platform}")
    n, f, b = args.rows, args.features, args.bins + 1
    kx, kp = jax.random.split(jax.random.PRNGKey(52))
    Xb_t = jax.random.randint(kx, (f, n), 0, b, jnp.int32).astype(jnp.int8)
    out = {"device": dev.device_kind, "rows": n, "features": f, "slots": b,
           "passes": []}
    @functools.partial(jax.jit, static_argnames="lanes")
    def payload(key, *, lanes):
        # [g, h] a lane, fold-major, as _grow_tree_folds lays them out, in
        # ONE buffer: h 0 or 1 (a fifth of the rows weigh nothing, as a
        # fold's held-out rows do), g uniform in (-1, 1)
        u = jax.random.uniform(key, (2 * lanes, n), jnp.float32)
        is_h = (jnp.arange(2 * lanes) % 2 == 1)[:, None]
        return jnp.where(is_h, (u < 0.8).astype(jnp.float32), 2.0 * u - 1.0)

    for lanes in args.lanes:
        pay = jax.block_until_ready(payload(kp, lanes=lanes))
        node = jnp.zeros((lanes, n), jnp.float32)

        def root():
            return PH.hist_folds(Xb_t, pay, node, n_slots=1, n_bins=b,
                                 allow_bf16=True, derive_count=True,
                                 payload_parts=1)
        jax.block_until_ready(root())           # compile and warm
        walls = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(root())
            walls.append(1e3 * (time.perf_counter() - t0))
        out["passes"].append({
            "lanes": lanes, "m_rows": 3 * lanes,
            "tiles_of_128": -(-3 * lanes // 128),
            "best_ms": min(walls), "median_ms": statistics.median(walls),
            "walls_ms": walls})
        del pay, node       # the next, larger pair is made after these go
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_root_hist_tiles.json", "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
