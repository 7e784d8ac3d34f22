"""Liveness on the chip: the flagship sweep and train -> save -> serve.

Drives the system's normal entry points once on the accelerator JAX finds,
at the width of the one configuration that has run on a chip (bench.py
TPU_CFG: 10M x 64 bf16, 5 folds, LR + depth-6/32-bin XGBoost), checks what
came out by the repo's own means, and prints two JSON lines on stdout: the
full report (also written to <out>/report.json), then, last, the verdict
alone: {"ok": ..., "device": {"platform", "kind", "count"}}. Any failed leg
or check exits non-zero; so does a host where JAX finds no TPU, which
prints no result. One process runs everything: a chip belongs to one
process.

    python chip_smoke.py              # one chip: leg A, then leg B
    python chip_smoke.py --leg a      # leg A only (the compile-cache pair)
    python chip_smoke.py --chips 4    # leg A on device 0, then on a mesh

`--toy` is for tests/test_chip_smoke.py only: tiny sizes on the CPU, pallas
kernels in interpret mode, route expectations reported but not enforced.
Nothing it prints is a device figure.

The walls printed here are a liveness record, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time

import numpy as np

_T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))

#: what was cut from bench.py's TPU_CFG to fit the 1200 s contract —
#: grid points only; rows, columns, folds, depth, bins, rounds stay
CUTS = {"glm_grid": "6 of 48 points (3 reg_param x 2 elastic_net_param)",
        "gbt_grid": "2 of 16 configs (depth 6 only; eta 0.1 and 0.3)",
        "four_chip_gbt_grid": "1 config on --chips 4 (both runs)"}

#: per-cell bound on bf16-input histogram g/h channels, relative to the
#: cell's absolute payload mass (tests/test_hist_batched.py: <= 0.4%)
BF16_HIST_RTOL = 4e-3
F32_HIST_RTOL = 1e-4
AUPR_PARITY_TOL = 1e-3      # BASELINE.md tier-one contract
MESH_METRIC_ATOL = 5e-3     # __graft_entry__.dryrun_multichip's tolerance
SERVE_ATOL = 1e-6


def log(msg: str) -> None:
    print(f"[smoke +{time.time() - _T0:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


class SmokeFailure(AssertionError):
    pass


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- sizes ------------------------------------------------------------------

def sizes(toy: bool, chips: int) -> dict:
    from bench import TPU_CFG
    cfg = dict(TPU_CFG, check_rows=32_768, parity_rows=500_000,
               serve_rows=4096, serve_singles=32)
    if toy:
        cfg.update(n_rows=4096, n_cols=8, folds=3, gbt_rounds=2,
                   gbt_depth=3, gbt_bins=8, wide_rows=1500,
                   check_rows=8192, parity_rows=4096, serve_rows=64,
                   serve_singles=4)
    cfg["glm_grids"] = [{"reg_param": r, "elastic_net_param": a}
                        for r in (1e-4, 1e-2, 0.3) for a in (0.0, 0.5)]
    cfg["gbt_grids"] = [
        {"num_round": cfg["gbt_rounds"], "max_depth": cfg["gbt_depth"],
         "eta": e, "reg_lambda": 1.0, "max_bins": cfg["gbt_bins"]}
        for e in ((0.1,) if chips > 1 else (0.1, 0.3))]
    return cfg


# -- observing the run ------------------------------------------------------

class DispatcherSpy:
    """Record every call the sweep makes into ops/pallas_hist's kernel
    dispatchers — shapes, static arguments, interpret flag and whether
    pallas was available at that moment — so routes and tile shapes are
    read from what ran. Calls land at trace time (once per compiled
    program); a persistent-cache hit still traces."""

    NAMES = ("hist_folds", "route", "route_hist", "table_lookup",
             "hist_pallas", "route_pallas")

    def __init__(self):
        self.calls = []
        self._depth = 0

    def __enter__(self):
        from transmogrifai_tpu.ops import pallas_hist as PH
        self._orig = {n: getattr(PH, n) for n in self.NAMES}
        for n, fn in self._orig.items():
            setattr(PH, n, self._wrap(PH, n, fn))
        return self

    def __exit__(self, *exc):
        from transmogrifai_tpu.ops import pallas_hist as PH
        for n, fn in self._orig.items():
            setattr(PH, n, fn)

    def _wrap(self, PH, name, fn):
        def wrapped(*args, **kw):
            if self._depth == 0:  # hist_folds -> hist_pallas is one call
                rec = {"kernel": name,
                       "shapes": [tuple(int(s) for s in a.shape)
                                  for a in args],
                       "xb_dtype": str(args[0].dtype),
                       "interpret": bool(kw.get("interpret", False)),
                       "available": bool(PH.available()),
                       "bf16_inputs": bool(kw.get("allow_bf16", False)
                                           and PH._HIST_BF16),
                       "static": {k: v for k, v in kw.items()
                                  if k != "interpret"}}
                if rec not in self.calls:
                    self.calls.append(rec)
            self._depth += 1
            try:
                return fn(*args, **kw)
            finally:
                self._depth -= 1
        return wrapped


class CompileLog:
    """Process-wide compile record from jax.monitoring: seconds of every
    backend compile by program name, and how many of them were
    persistent-cache loads (the RecompileTracker keeps the same counts
    per collected run; this one spans the legs)."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _CACHE_HIT = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax.monitoring
        self._lock = threading.Lock()   # the listener fires on whatever
        self.seconds = {}               # thread compiles
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        with self._lock:
            if event == self._CACHE_HIT:
                self.cache_hits += 1
            elif event == self._COMPILE:
                self.programs += 1
                name = str(kw.get("fun_name", "?"))
                self.seconds[name] = self.seconds.get(name, 0.0) \
                    + float(duration)

    def summary(self, k=8):
        with self._lock:
            secs = dict(self.seconds)
            programs, hits = self.programs, self.cache_hits
        top = sorted(secs.items(), key=lambda kv: -kv[1])[:k]
        return {"programs": programs, "cache_hits": hits,
                "compile_s_total": round(sum(secs.values()), 2),
                "slowest": [{"program": n, "compile_s": round(s, 2)}
                            for n, s in top]}


def read_events(path: str):
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def device_report():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def bytes_in_use():
    import jax
    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append(int(stats.get("bytes_in_use", -1)))
    return out


# -- leg A: the ModelSelector sweep -----------------------------------------

def run_sweep(cfg, X, y, mesh, events_path):
    """One CrossValidation.validate over both families — the call
    ModelSelector makes — with the run's events, kernel spans, dispatcher
    calls and compile counts captured."""
    import jax
    import jax.numpy as jnp
    from transmogrifai_tpu.automl.tuning.validators import CrossValidation
    from transmogrifai_tpu.evaluators.evaluators import Evaluators
    from transmogrifai_tpu.models.glm import OpLogisticRegression
    from transmogrifai_tpu.models.trees import OpXGBoostClassifier
    from transmogrifai_tpu.utils import tracing
    from transmogrifai_tpu.utils.metrics import collector

    val = CrossValidation(Evaluators.BinaryClassification.au_pr(),
                          num_folds=cfg["folds"], seed=42,
                          sweep_dtype=jnp.bfloat16, mesh=mesh)
    # synthetic standard-normal columns: standardization is a no-op
    models = [(OpLogisticRegression(max_iter=15, standardization=False),
               [dict(g) for g in cfg["glm_grids"]]),
              (OpXGBoostClassifier(), [dict(g) for g in cfg["gbt_grids"]])]
    n_kernels0 = len(collector.current.kernel_metrics)
    n_events0 = len(read_events(events_path))
    c0 = tracing.tracker.true_compiles
    h0 = tracing.tracker.total_cache_hits
    with DispatcherSpy() as spy:
        t0 = time.perf_counter()
        best = val.validate(models, X, y)
        # validate() returns host floats reduced from every device
        # result, so the fence is a formality — kept so the wall is
        # taken around block_until_ready by construction
        jax.block_until_ready(X)
        wall = time.perf_counter() - t0
    events = read_events(events_path)[n_events0:]
    cells = [e for e in events if e.get("event") == "sweep_cell_landed"]
    return {
        "best": best, "val": val, "wall_s": round(wall, 2),
        "true_compiles": tracing.tracker.true_compiles - c0,
        "cache_hits": tracing.tracker.total_cache_hits - h0,
        "cell_routes": sorted({(e["model"], e["route"]) for e in cells}),
        "n_cells": len(cells),
        "fused_route_fallbacks": sum(
            e.get("event") == "fused_route_fallback" for e in events),
        "kernel_spans": [k.to_json() for k in
                         collector.current.kernel_metrics[n_kernels0:]],
        "dispatcher_calls": spy.calls,
        "glm_telemetry": val.last_streamed_telemetry,
    }


def summarize_sweep(run) -> dict:
    best = run["best"]
    spans = {}
    for k in run["kernel_spans"]:
        spans[k["kernel"]] = spans.get(k["kernel"], 0) + 1
    return {
        "wall_s": run["wall_s"], "true_compiles": run["true_compiles"],
        "cache_hits": run["cache_hits"],
        "winner": best.name, "winner_grid": best.best_grid,
        "winner_au_pr": round(float(best.best_metric), 6),
        "cell_routes": [list(c) for c in run["cell_routes"]],
        "n_cells": run["n_cells"],
        "fused_route_fallbacks": run["fused_route_fallbacks"],
        "kernel_spans": spans,
        "dispatchers": sorted({(c["kernel"], c["interpret"],
                                c["available"], c["bf16_inputs"])
                               for c in run["dispatcher_calls"]}),
        "glm_kernel": (run["glm_telemetry"] or {}).get("kernel"),
    }


def check_routes_one_chip(run, cfg) -> None:
    """The one-chip routes, each read from the run's own record."""
    routes = dict(run["cell_routes"])
    require(len(run["cell_routes"]) == 2, f"cell routes {run['cell_routes']}")
    require(routes.get("OpLogisticRegression") == "streamed",
            f"GLM route {routes.get('OpLogisticRegression')!r} != streamed")
    require(routes.get("OpXGBoostClassifier") == "mask_folds",
            f"tree route {routes.get('OpXGBoostClassifier')!r}")
    require(run["n_cells"] == len(cfg["glm_grids"]) + len(cfg["gbt_grids"]),
            f"{run['n_cells']} cells landed")
    require(run["fused_route_fallbacks"] == 0, "fused_route_fallback fired")
    fused = [k for k in run["kernel_spans"]
             if k["kernel"] == "tree_sweep_fold_fused"]
    require(len(fused) == len(cfg["gbt_grids"]),
            f"{len(fused)} tree_sweep_fold_fused spans for "
            f"{len(cfg['gbt_grids'])} tree configs")
    calls = run["dispatcher_calls"]
    seen = {c["kernel"] for c in calls}
    for name in ("hist_folds", "route_hist", "route", "table_lookup",
                 "hist_pallas"):
        require(name in seen, f"dispatcher {name} never called")
    for c in calls:
        require(c["available"] and not c["interpret"],
                f"{c['kernel']} ran with available={c['available']} "
                f"interpret={c['interpret']}")
        if c["kernel"] in ("hist_folds", "route_hist"):
            require(c["bf16_inputs"], f"{c['kernel']} without bf16 inputs")
    # the rank-metric consumer is the one direct hist_pallas caller: one
    # "feature" whose bins are the score buckets
    rank = [c for c in calls if c["kernel"] == "hist_pallas"
            and c["shapes"][0][0] == 1]
    require(bool(rank), "in-sweep metric did not take the binned pallas path")


# -- kernel-vs-twin checks, at the shapes the sweep ran ----------------------

def _cells_close(got, ref, mass, rtol):
    tol = rtol * mass + 1e-6 * (1.0 + np.abs(ref))
    bad = np.abs(got - ref) > tol
    worst = float(np.max(np.abs(got - ref) / (mass + 1e-6)))
    return not bad.any(), worst


def _compare_hist(got, ref, mass, lanes, co, derive_count, bf16):
    """hist [lanes*slots*co, cols]: count channel exact, g/h per cell
    within the input-rounding bound."""
    got = np.asarray(got).reshape(lanes, -1, co, got.shape[-1])
    ref = np.asarray(ref).reshape(got.shape)
    out = {}
    c_in = co - 1 if derive_count else co
    if derive_count:
        out["counts_exact"] = bool(
            np.array_equal(got[:, :, co - 1], ref[:, :, co - 1]))
        require(out["counts_exact"], "derived count channel differs")
    mass = np.asarray(mass).reshape(lanes, -1, c_in, got.shape[-1])
    ok, worst = _cells_close(got[:, :, :c_in], ref[:, :, :c_in], mass,
                             BF16_HIST_RTOL if bf16 else F32_HIST_RTOL)
    out["gh_worst_rel"] = worst
    require(ok, f"g/h cells off by {worst:.2e} relative")
    return out


def kernel_checks(calls, Xb_t, y, masks, margin, *, toy: bool) -> list:
    """Replay every dispatcher call the sweep recorded — same static
    arguments, same tile shape, N cut to a slice — against its jax.numpy
    twin on the same device arrays."""
    import jax
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import metrics_ops as M
    from transmogrifai_tpu.ops import pallas_hist as PH

    interpret = toy
    F, N = Xb_t.shape
    key = jax.random.PRNGKey(7)
    results = []
    for c in calls:
        name, st = c["kernel"], c["static"]
        k1, k2, k3, k4, key = jax.random.split(key, 5)
        res = {"kernel": name, "shapes": c["shapes"], "static": dict(st)}
        t0 = time.perf_counter()
        if name == "hist_pallas" and c["shapes"][0][0] == 1:
            # rank-metric consumer: lanes of scores over the same rows
            L, bins = st["n_slots"], st["n_bins"]
            scores = jax.random.normal(k1, (L, N), jnp.float32) * 2.0
            wl = jnp.broadcast_to(masks[:1], (L, N)) \
                * (jax.random.uniform(k2, (L, N)) < 0.7)
            # (wl holds zeros and ones: the body and the payload parts the
            # sweep's call ran are the ones replayed)
            got = M._binned_cum_counts_lanes_pallas(
                scores, y, wl, bins, interpret=interpret,
                unit_payload=bool(st.get("unit_payload", False)))
            ref = M._binned_cum_counts_lanes_jnp(scores, y, wl, bins)
            res["counts_exact"] = bool(all(
                np.array_equal(np.asarray(g), np.asarray(r))
                for g, r in zip(got, ref)))
            require(res["counts_exact"], "rank-metric bin counts differ")
        elif name in ("hist_folds", "hist_pallas"):
            lanes = c["shapes"][2][0]
            C = c["shapes"][1][0] // lanes
            S, B = st["n_slots"], st["n_bins"]
            dc = bool(st.get("derive_count", False))
            pay = _payload(y, masks, margin, lanes, C)
            slot = jax.random.randint(k1, (lanes, N), 0, S + 1) \
                .astype(jnp.float32)          # S = dropped row
            fn = PH.hist_folds if name == "hist_folds" else PH.hist_pallas
            got = fn(Xb_t, pay, slot, n_slots=S, n_bins=B,
                     interpret=interpret,
                     allow_bf16=bool(st.get("allow_bf16", False)),
                     derive_count=dc)
            ref = _per_lane(lanes, lambda k: PH._hist_segment_jnp(
                Xb_t, pay[k * C:(k + 1) * C], slot[k:k + 1], n_slots=S,
                n_bins=B, derive_count=dc))
            mass = _per_lane(lanes, lambda k: PH._hist_segment_jnp(
                Xb_t, jnp.abs(pay[k * C:(k + 1) * C]), slot[k:k + 1],
                n_slots=S, n_bins=B))
            res.update(_compare_hist(got, ref, mass, lanes,
                                     C + (1 if dc else 0), dc,
                                     c["bf16_inputs"]))
        elif name in ("route", "route_pallas", "route_hist"):
            node_ix = 2 if name == "route_hist" else 1
            lanes = c["shapes"][node_ix][0]
            n_nodes = st["n_nodes"]
            B = st["n_bins"] if name == "route_hist" \
                else int(jnp.max(Xb_t)) + 1
            node = jax.random.randint(k1, (lanes, N), 0, n_nodes) \
                .astype(jnp.float32)
            f_lvl = jax.random.randint(k2, (lanes, n_nodes), 0, F)
            t_lvl = jax.random.randint(k3, (lanes, n_nodes), 0, B)
            m_lvl = jax.random.randint(k4, (lanes, n_nodes), 0, 2)
            ref_node = PH._route_level_jnp(Xb_t, node, f_lvl, t_lvl, m_lvl)
            if name == "route_hist":
                C = c["shapes"][1][0] // lanes
                dc = bool(st.get("derive_count", False))
                pay = _payload(y, masks, margin, lanes, C)
                got, got_node = PH.route_hist(
                    Xb_t, pay, node, f_lvl, t_lvl, m_lvl, n_nodes=n_nodes,
                    n_bins=B, interpret=interpret,
                    allow_bf16=bool(st.get("allow_bf16", False)),
                    derive_count=dc)
                def twin(k, p, derive):
                    return PH._route_hist_jnp(
                        Xb_t, p[k * C:(k + 1) * C], node[k:k + 1],
                        f_lvl[k:k + 1], t_lvl[k:k + 1], m_lvl[k:k + 1],
                        n_nodes=n_nodes, n_bins=B, derive_count=derive)[0]
                ref = _per_lane(lanes, lambda k: twin(k, pay, dc))
                mass = _per_lane(lanes,
                                 lambda k: twin(k, jnp.abs(pay), False))
                res.update(_compare_hist(got, ref, mass, lanes,
                                         C + (1 if dc else 0), dc,
                                         c["bf16_inputs"]))
            else:
                fn = PH.route if name == "route" else PH.route_pallas
                got_node = fn(Xb_t, node, f_lvl, t_lvl, m_lvl,
                              n_nodes=n_nodes, interpret=interpret)
            res["routing_identical"] = bool(np.array_equal(
                np.asarray(got_node), np.asarray(ref_node)))
            require(res["routing_identical"], "routing decisions differ")
        elif name == "table_lookup":
            lanes, m = c["shapes"][0]
            tbl = jax.random.normal(k1, (lanes, m), jnp.float32)
            idx = jax.random.randint(k2, (lanes, N), 0, m) \
                .astype(jnp.float32)
            got = np.asarray(PH.table_lookup(tbl, idx, interpret=interpret))
            ref = np.asarray(PH._table_lookup_jnp(tbl, idx))
            res["worst_abs"] = float(np.max(np.abs(got - ref)))
            require(res["worst_abs"] <= 1e-6,
                    f"table_lookup off by {res['worst_abs']:.2e}")
        else:
            raise SmokeFailure(f"no check for dispatcher {name}")
        # every branch above compared on the host, which syncs
        # tmoglint: disable=TPU005  np.asarray of the results blocks
        res["check_s"] = round(time.perf_counter() - t0, 2)
        res["ok"] = True
        log(f"kernel check {name} {c['shapes'][0]} ok "
            f"({res['check_s']}s)")
        results.append(res)
    return results


def _per_lane(lanes, twin):
    """The segment-sum twins lay [rows, channels] operands out
    channel-minor; on the TPU that pads 3 channels to 128 lanes, so they
    run one fold lane at a time."""
    import jax.numpy as jnp
    return jnp.concatenate([twin(k) for k in range(lanes)], axis=0)


def _payload(y, masks, margin, lanes, C):
    """Fold-major [lanes*C, N] payload: logistic g/h of the sweep's own
    labels under its fold masks at `margin` — values with full f32
    mantissas, so bf16 input rounding is really exercised (channel order
    g..., h)."""
    import jax
    import jax.numpy as jnp
    W = jnp.tile(masks, (-(-lanes // masks.shape[0]), 1))[:lanes]
    p = jax.nn.sigmoid(margin)
    g = W * (p - y)[None, :]
    h = jnp.maximum(W * (p * (1.0 - p))[None, :], 1e-12) * (W > 0)
    chans = [g] * (C - 1) + [h]
    return jnp.stack(chans, axis=1).reshape(lanes * C, -1)


# -- AuPR parity of the winning LR grid point --------------------------------

def aupr_parity(cfg, best, X, y) -> dict:
    """Refit the best LR grid point two ways on one row slice pulled from
    the device matrix — the system's estimator (the device fit) and a
    plain float32 proximal-gradient solver at `highest` matmul precision —
    and compare exact AuPR on the slice's held-out fifth."""
    import jax
    import jax.numpy as jnp
    from bench import numpy_au_pr
    from transmogrifai_tpu.models.glm import OpLogisticRegression

    lr = [v for v in best.validated
          if v.model_name == "OpLogisticRegression"]
    top = max(lr, key=lambda v: v.mean_metric)
    reg = float(top.grid["reg_param"])
    alpha = float(top.grid["elastic_net_param"])
    n = min(cfg["parity_rows"], X.shape[0])
    Xs_dev, ys_dev = X[:n], y[:n]
    Xs = np.asarray(Xs_dev.astype(jnp.float32))
    ys = np.asarray(ys_dev)
    w = (np.arange(n) % 5 != 0).astype(np.float32)   # train 4/5

    est = OpLogisticRegression(max_iter=15, standardization=False,
                               reg_param=reg, elastic_net_param=alpha)
    model = est.fit_arrays(Xs_dev, ys_dev, w=w)
    dev_beta = np.asarray(model.beta, np.float64)
    dev_b0 = float(model.intercept)

    with jax.default_matmul_precision("highest"):
        ref_beta, ref_b0 = _reference_logistic(
            jnp.asarray(Xs), jnp.asarray(ys), jnp.asarray(w), reg, alpha)
    ref_beta = np.asarray(ref_beta, np.float64)
    a_dev = numpy_au_pr(Xs @ dev_beta + dev_b0, ys, 1.0 - w)
    a_ref = numpy_au_pr(Xs @ ref_beta + float(ref_b0), ys, 1.0 - w)
    out = {"grid": dict(top.grid), "rows": int(n),
           "au_pr_device_fit": round(a_dev, 6),
           "au_pr_reference_fit": round(a_ref, 6),
           "delta": round(abs(a_dev - a_ref), 6)}
    require(np.isfinite(a_dev) and np.isfinite(a_ref),
            f"non-finite AuPR {out}")
    require(out["delta"] <= AUPR_PARITY_TOL, f"AuPR parity {out}")
    return out


def _reference_logistic(X, y, w, reg, alpha, iters=400):
    """min_b  sum_i w_i logloss_i / sum w + reg(1-alpha)/2 |b|^2
    + reg*alpha |b|_1, intercept unpenalized — plain accelerated
    proximal gradient, nothing shared with ops/glm."""
    import jax
    import jax.numpy as jnp
    n, d = X.shape
    wsum = w.sum()
    l1, l2 = reg * alpha, reg * (1.0 - alpha)
    # Lipschitz bound of the smooth part: sigma' <= 1/4
    lip = 0.25 * jnp.linalg.norm((X * w[:, None]).T @ X / wsum, 2) \
        + 0.25 + l2

    def grad(b, b0):
        r = (jax.nn.sigmoid(X @ b + b0) - y) * w
        return X.T @ r / wsum + l2 * b, r.sum() / wsum

    def body(_, s):
        b, b0, zb, z0, t = s
        g, g0 = grad(zb, z0)
        nb = zb - g / lip
        nb = jnp.sign(nb) * jnp.maximum(jnp.abs(nb) - l1 / lip, 0.0)
        n0 = z0 - g0 / lip
        nt = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        m = (t - 1.0) / nt
        return nb, n0, nb + m * (nb - b), n0 + m * (n0 - b0), nt

    z = jnp.zeros(d, jnp.float32)
    b, b0, _, _, _ = jax.lax.fori_loop(
        0, iters, body, (z, jnp.float32(0), z, jnp.float32(0),
                         jnp.float32(1)))
    return b, b0


def leg_a(cfg, *, toy: bool, out_dir: str, compile_log) -> dict:
    import jax
    import jax.numpy as jnp
    from bench import device_data
    from transmogrifai_tpu.ops import trees as T
    from transmogrifai_tpu.utils.metrics import collector
    from transmogrifai_tpu.utils.platform import device_spec

    report = {"rows": cfg["n_rows"], "cols": cfg["n_cols"],
              "folds": cfg["folds"], "depth": cfg["gbt_depth"],
              "bins": cfg["gbt_bins"], "rounds": cfg["gbt_rounds"],
              "glm_grid": len(cfg["glm_grids"]),
              "gbt_grid": len(cfg["gbt_grids"])}
    spec = device_spec()   # raises on a TPU the peaks table does not know
    report["device_spec_known"] = spec is not None
    t0 = time.perf_counter()
    X, y, _ = device_data(cfg["n_rows"], cfg["n_cols"], cfg["folds"],
                          jnp.bfloat16)
    report["datagen_s"] = round(time.perf_counter() - t0, 2)
    log(f"leg A: data {X.shape} {X.dtype} on device "
        f"({report['datagen_s']}s)")

    events_path = os.path.join(out_dir, "leg_a_events.jsonl")
    collector.enable("chip_smoke_leg_a")
    collector.attach_event_log(events_path)
    try:
        cold = run_sweep(cfg, X, y, None, events_path)
        report["cold"] = summarize_sweep(cold)
        report["compiles_after_cold_call"] = compile_log.summary()
        log(f"leg A cold call: {report['cold']}")
        warm = run_sweep(cfg, X, y, None, events_path)
        report["warm"] = summarize_sweep(warm)
        log(f"leg A warm call: {report['warm']}")
    finally:
        collector.detach_event_log()
        collector.finish()
        collector.disable()
    report["bytes_in_use_per_device"] = bytes_in_use()

    require(warm["true_compiles"] == 0,
            f"warm call compiled {warm['true_compiles']} programs")
    require(warm["best"].name == cold["best"].name
            and warm["best"].best_grid == cold["best"].best_grid,
            "warm call picked another winner")
    require(np.isfinite(cold["best"].best_metric)
            and 0.5 < cold["best"].best_metric <= 1.0,
            f"winner AuPR {cold['best'].best_metric}")
    for v in cold["best"].validated:
        require(len(v.fold_metrics) == cfg["folds"]
                and bool(np.all(np.isfinite(v.fold_metrics))),
                f"fold metrics of {v.model_name} {v.grid}: {v.fold_metrics}")
    if toy:
        report["routes_enforced"] = False
        calls = _toy_dispatcher_calls(cfg)
    else:
        check_routes_one_chip(cold, cfg)
        report["routes_enforced"] = True
        calls = cold["dispatcher_calls"]

    # the sweep's own binning rule on the sweep's own matrix, cut to a
    # slice; fold masks as the validator drew them
    n = min(cfg["check_rows"], X.shape[0])
    edges = T.quantile_edges(X, cfg["gbt_bins"])
    Xb_t = T.bin_matrix(X[:n], edges).T
    masks = jnp.asarray(cold["val"].fold_masks(np.zeros(X.shape[0]))[:, :n])
    report["kernel_checks"] = kernel_checks(
        calls, Xb_t, y[:n], masks, X[:n, 0].astype(jnp.float32), toy=toy)
    report["aupr_parity"] = aupr_parity(cfg, cold["best"], X, y)
    log(f"leg A AuPR parity: {report['aupr_parity']}")
    report["ok"] = True
    return report


def _toy_dispatcher_calls(cfg):
    """The dispatcher calls of a one-chip sweep at toy shape, for the CPU
    test: there the sweep itself takes the host tree builder, so the
    shapes are written down instead of recorded."""
    F, fo, B = cfg["n_cols"], cfg["folds"], cfg["gbt_bins"] + 1
    S = 1 << (cfg["gbt_depth"] - 2)
    n = cfg["check_rows"]
    kw = {"allow_bf16": True, "derive_count": True}

    def rec(kernel, shapes, bf16=False, **static):
        return {"kernel": kernel, "shapes": shapes, "static": static,
                "bf16_inputs": bf16, "interpret": True, "available": False}
    return [
        rec("hist_folds", [(F, n), (2 * fo, n), (fo, n)], True,
            n_slots=1, n_bins=B, **kw),
        rec("route_hist", [(F, n), (2 * fo, n), (fo, n), (fo, S)], True,
            n_nodes=S, n_bins=B, **kw),
        rec("route", [(F, n), (fo, n), (fo, 2 * S)], n_nodes=2 * S),
        rec("table_lookup", [(fo, 4 * S), (fo, n)]),
        rec("hist_pallas", [(1, fo * n), (2, fo * n), (1, fo * n)],
            n_slots=fo, n_bins=256),
    ]


# -- four chips: default path, then the mesh ---------------------------------

def leg_a_four_chips(cfg, *, toy: bool, out_dir: str) -> dict:
    import jax
    import jax.numpy as jnp
    from bench import device_data
    from transmogrifai_tpu.parallel.mesh import make_mesh
    from transmogrifai_tpu.utils.metrics import collector

    report = {"rows": cfg["n_rows"], "cols": cfg["n_cols"],
              "gbt_grid": len(cfg["gbt_grids"]),
              "glm_grid": len(cfg["glm_grids"])}
    X, y, _ = device_data(cfg["n_rows"], cfg["n_cols"], cfg["folds"],
                          jnp.bfloat16)
    events_path = os.path.join(out_dir, "leg_a4_events.jsonl")
    collector.enable("chip_smoke_leg_a4")
    collector.attach_event_log(events_path)
    try:
        flat = run_sweep(cfg, X, y, None, events_path)
        report["default_path"] = summarize_sweep(flat)
        report["default_path"]["bytes_in_use_per_device"] = bytes_in_use()
        report["default_path"]["x_devices"] = len(X.sharding.device_set)
        log(f"4 chips, default path: {report['default_path']}")
        if not toy:
            check_routes_one_chip(flat, cfg)

        mesh = make_mesh()
        t0 = time.perf_counter()
        sharded = _SeenArrays()
        with sharded:
            meshed = run_sweep(cfg, X, y, mesh, events_path)
        report["mesh"] = summarize_sweep(meshed)
        report["mesh"]["mesh_shape"] = {
            k: int(v) for k, v in dict(mesh.shape).items()}
        report["mesh"]["bytes_in_use_per_device"] = bytes_in_use()
        report["mesh"]["x_sharding"] = sharded.report
        report["mesh"]["total_s"] = round(time.perf_counter() - t0, 2)
        log(f"4 chips, mesh: {report['mesh']}")
    finally:
        collector.detach_event_log()
        collector.finish()
        collector.disable()

    n_dev = len(jax.devices())
    xs = sharded.report
    require(bool(xs), "the mesh sweep never placed the matrix")
    for s in xs:
        require(s["devices"] == n_dev,
                f"X on {s['devices']} of {n_dev} devices")
        share = [b / max(s["nbytes"], 1) for b in s["shard_bytes"]]
        require(all(abs(f - 1.0 / n_dev) < 0.02 for f in share),
                f"uneven shards {share}")
    require(meshed["fused_route_fallbacks"] == 0,
            "fused_route_fallback fired on the mesh")
    require(not meshed["dispatcher_calls"],
            f"a pallas kernel met a sharded operand: "
            f"{[c['kernel'] for c in meshed['dispatcher_calls']]}")
    require(meshed["best"].name == flat["best"].name
            and meshed["best"].best_grid == flat["best"].best_grid,
            f"mesh winner {meshed['best'].name} {meshed['best'].best_grid} "
            f"!= one-chip {flat['best'].name} {flat['best'].best_grid}")
    deltas = {}
    for a, b in zip(meshed["best"].validated, flat["best"].validated):
        d = float(np.max(np.abs(np.asarray(a.fold_metrics)
                                - np.asarray(b.fold_metrics))))
        deltas[a.model_name] = max(deltas.get(a.model_name, 0.0), d)
        require(d <= MESH_METRIC_ATOL,
                f"mesh vs one-chip fold metrics of {a.model_name} "
                f"{a.grid} differ by {d:.2e}")
    report["mesh"]["fold_metric_max_delta"] = deltas
    report["ok"] = True
    return report


class _SeenArrays:
    """Watch Validator._device_arrays during the mesh sweep: where the
    matrix landed and how long the placement (a host round trip for a
    device-generated matrix) took."""

    def __enter__(self):
        from transmogrifai_tpu.automl.tuning import validators as V
        self.report = []
        self._V = V
        self._orig = V.Validator._device_arrays
        seen = self

        def wrapped(self_v, X, y, w, masks, dtype):
            t0 = time.perf_counter()
            out = seen._orig(self_v, X, y, w, masks, dtype)
            import jax
            jax.block_until_ready(out)
            Xd = out[0]
            seen.report.append({
                "place_s": round(time.perf_counter() - t0, 2),
                "devices": len(Xd.sharding.device_set),
                "nbytes": int(Xd.nbytes),
                "shard_bytes": [int(s.data.nbytes)
                                for s in Xd.addressable_shards]})
            return out
        V.Validator._device_arrays = wrapped
        return self

    def __exit__(self, *exc):
        self._V.Validator._device_arrays = self._orig


# -- leg B: train -> save -> serve through the public API --------------------

def leg_b(cfg, *, toy: bool, out_dir: str) -> dict:
    import jax
    from transmogrifai_tpu import Column, Dataset, FeatureBuilder
    from transmogrifai_tpu.automl import (BinaryClassificationModelSelector,
                                          SanityChecker)
    from transmogrifai_tpu.automl.transmogrifier import transmogrify
    from transmogrifai_tpu.models.glm import OpLogisticRegression
    from transmogrifai_tpu.models.prediction import probability_of
    from transmogrifai_tpu.models.trees import OpXGBoostClassifier
    from transmogrifai_tpu.serve import ServingEngine
    from transmogrifai_tpu.types import ColumnKind
    from transmogrifai_tpu.utils import tracing
    from transmogrifai_tpu.utils.metrics import collector
    from transmogrifai_tpu.workflow import Workflow

    n, d = cfg["wide_rows"], cfg["n_cols"]
    report = {"rows": n, "raw_predictors": d, "folds": cfg["folds"]}
    rng = np.random.default_rng(11)
    Xh = rng.normal(size=(n, d))
    beta = rng.normal(size=d) / np.sqrt(d)
    yh = (rng.uniform(size=n) < 1 / (1 + np.exp(-(Xh @ beta)))) \
        .astype(np.float64)
    Xh[rng.uniform(size=(n, d)) < 0.03] = np.nan   # a few percent missing
    cols = {f"x{j}": Column(ColumnKind.FLOAT, Xh[:, j].copy())
            for j in range(d)}
    cols["y"] = Column(ColumnKind.FLOAT, yh)
    ds = Dataset(cols)

    preds = [FeatureBuilder.Real(f"x{j}").as_predictor() for j in range(d)]
    label = FeatureBuilder.RealNN("y").as_response()
    # one derived math feature: a jitted stage in the scoring DAG, so the
    # serve path touches the device and compile counting is real
    derived = (preds[0] + preds[1]) + 1.0
    checked = SanityChecker(check_sample=1.0, remove_bad_features=True) \
        .set_input(label, transmogrify(preds + [derived])).get_output()
    xgb = {"num_round": cfg["gbt_rounds"], "max_depth": cfg["gbt_depth"],
           "max_bins": cfg["gbt_bins"], "reg_lambda": 1.0}
    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=cfg["folds"], seed=42, models_and_parameters=[
            (OpLogisticRegression(max_iter=15),
             [{"reg_param": r} for r in (1e-3, 1e-2)]),
            (OpXGBoostClassifier(),
             [dict(xgb, eta=e) for e in (0.1, 0.3)])])
    prediction = selector.set_input(label, checked).get_output()

    events_path = os.path.join(out_dir, "leg_b_events.jsonl")
    collector.enable("chip_smoke_leg_b")
    collector.attach_event_log(events_path)
    model_dir = os.path.join(out_dir, "leg_b_model")
    shutil.rmtree(model_dir, ignore_errors=True)
    try:
        c0 = tracing.tracker.true_compiles
        t0 = time.perf_counter()
        model = Workflow().set_input_dataset(ds) \
            .set_result_features(prediction).train()
        report["train_s"] = round(time.perf_counter() - t0, 2)
        report["train_true_compiles"] = tracing.tracker.true_compiles - c0
        report["fit_backend"] = jax.default_backend()
        events = read_events(events_path)
        report["cell_routes"] = sorted(
            {(e["model"], e["route"]) for e in events
             if e.get("event") == "sweep_cell_landed"})
        report["kernel_spans"] = sorted(
            {k.kernel for k in collector.current.kernel_metrics})
        report["host_tree_route"] = bool(OpXGBoostClassifier._host_route())
        log(f"leg B trained in {report['train_s']}s: "
            f"{report['cell_routes']} spans {report['kernel_spans']}")
        require(any(k.startswith("stats_pass") for k in
                    report["kernel_spans"]),
                "no stats-engine pass was recorded")
        if not toy:
            require(report["fit_backend"] == "tpu", "fit not on TPU")
            require(not report["host_tree_route"],
                    "trees took the host builder")
            routes = dict(report["cell_routes"])
            require(routes.get("OpLogisticRegression") == "streamed"
                    and routes.get("OpXGBoostClassifier") == "mask_folds",
                    f"leg B routes {report['cell_routes']}")

        t0 = time.perf_counter()
        model.save(model_dir)
        report["save_s"] = round(time.perf_counter() - t0, 2)

        eng = ServingEngine(model_dir)
        warm = eng.prewarm()
        report["prewarm"] = {k: warm[k] for k in
                             ("buckets", "wall_s", "compiles", "cache_hits")}
        names = [f"x{j}" for j in range(d)]
        idx = np.arange(cfg["serve_rows"])

        def record(i):
            return {nm: (None if np.isnan(Xh[i, j]) else float(Xh[i, j]))
                    for j, nm in enumerate(names)}
        t0 = time.perf_counter()
        singles = [eng.score_record(record(i))
                   for i in idx[:cfg["serve_singles"]]]
        report["singles_s"] = round(time.perf_counter() - t0, 3)
        t0 = time.perf_counter()
        bulk = eng.score_batch([record(i) for i in idx])
        report["bulk_s"] = round(time.perf_counter() - t0, 3)

        scored = model.score(ds.take(idx))
        probs = np.asarray(probability_of(scored.column(prediction.name)))
        served = np.array([r[prediction.name]["probability_1"]
                           for r in bulk])
        one_by_one = np.array([r[prediction.name]["probability_1"]
                               for r in singles])
        report["served_rows"] = int(len(served))
        report["bulk_max_abs_delta"] = float(
            np.max(np.abs(served - probs[:, 1])))
        report["singles_max_abs_delta"] = float(np.max(np.abs(
            one_by_one - probs[:len(one_by_one), 1])))
        report["post_warmup_compiles"] = int(
            eng.metrics()["post_warmup_compiles"])
        summary = model.selector_summary()
        report["winner"] = summary.best_model_name
        log(f"leg B served: {report}")
        require(np.all(np.isfinite(served)) and len(served) == len(idx),
                "served predictions not finite / wrong count")
        require(report["bulk_max_abs_delta"] <= SERVE_ATOL
                and report["singles_max_abs_delta"] <= SERVE_ATOL,
                f"served != model.score: {report['bulk_max_abs_delta']} / "
                f"{report['singles_max_abs_delta']}")
        require(report["post_warmup_compiles"] == 0,
                f"{report['post_warmup_compiles']} post-warmup compiles")
    finally:
        collector.detach_event_log()
        collector.finish()
        collector.disable()
    report["ok"] = True
    return report


def native_report() -> dict:
    """Leg B's host transforms and serving are what the C++ exists for:
    both libraries must be built from the sources in this checkout."""
    from transmogrifai_tpu.native.build import build, build_pyext
    lib, ext = build(), build_pyext()
    require(lib is not None and ext is not None,
            f"native build failed (lib={lib}, pyext={ext})")
    from transmogrifai_tpu.ops import native_bridge, pyext_bridge
    require(native_bridge._load() is not None, "native library not loaded")
    require(pyext_bridge.module() is not None, "pyext module not loaded")
    return {"native": "built", "lib": os.path.basename(lib),
            "pyext": os.path.basename(ext)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--leg", choices=("a", "b", "all"), default="all")
    ap.add_argument("--chips", type=int, default=1,
                    help="devices this run must find (1, or 4 for the "
                         "default-path + mesh run of leg A)")
    ap.add_argument("--toy", action="store_true",
                    help="test-only: tiny sizes on the CPU, interpret mode")
    ap.add_argument("--out", default=None,
                    help="directory for events, the saved model and the "
                         "full report (default chiprun_out/chip_smoke)")
    args = ap.parse_args()

    import jax
    import transmogrifai_tpu  # noqa: F401 — settles the compile cache
    from transmogrifai_tpu.utils.platform import compile_cache_dir

    device = device_report()
    if args.toy:
        if device["platform"] != "cpu":
            print("--toy is the CPU test mode", file=sys.stderr)
            return 1
    elif device["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices: {device}); "
              f"nothing was run", file=sys.stderr)
        return 1
    if device["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX reports "
              f"{device['count']} device(s)", file=sys.stderr)
        return 1
    log(f"device {device}")

    out_dir = args.out or os.path.join(HERE, "chiprun_out", "chip_smoke")
    os.makedirs(out_dir, exist_ok=True)
    cfg = sizes(args.toy, args.chips)
    compile_log = CompileLog()
    result = {"ok": False, "device": device, "toy": bool(args.toy),
              "cuts": CUTS, "compile_cache_dir": compile_cache_dir()}
    failed = None
    try:
        result.update(native_report())
        if args.chips > 1:
            result["leg_a_four_chips"] = leg_a_four_chips(
                cfg, toy=args.toy, out_dir=out_dir)
        else:
            if args.leg in ("a", "all"):
                result["leg_a"] = leg_a(cfg, toy=args.toy, out_dir=out_dir,
                                        compile_log=compile_log)
            if args.leg in ("b", "all"):
                result["leg_b"] = leg_b(cfg, toy=args.toy, out_dir=out_dir)
        result["ok"] = True
    except Exception as e:  # report what failed, then exit non-zero
        import traceback
        traceback.print_exc()
        failed = f"{type(e).__name__}: {e}"
    result["compiles"] = compile_log.summary()
    result["total_s"] = round(time.time() - _T0, 1)
    if failed:
        result["failed"] = failed
    with open(os.path.join(out_dir, "report.json"), "w") as f:
        json.dump(result, f, indent=1, default=str)
    if failed:
        print(f"chip_smoke FAILED: {failed}", file=sys.stderr)
    # the full report is the line before; the LAST stdout line is the
    # verdict alone, exactly {"ok", "device": {"platform","kind","count"}}
    print(json.dumps(result, default=str), flush=True)
    print(json.dumps({"ok": result["ok"], "device": device}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
