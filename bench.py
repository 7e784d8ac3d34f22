"""Headline benchmark: the BASELINE.json workloads, measured end to end.

North-star (BASELINE.json config 5): a BinaryClassificationModelSelector
sweep — 5-fold CV x 64 model configurations (48 logistic-regression grid
points + 16 XGBoost-style histogram-GBT configs) over a 10M x 64 feature
matrix. Reference inner loop: core/.../impl/tuning/OpValidator.scala:270-312
(one Spark fit per (model, grid, fold) on 8 driver threads).

Device path = the framework's own validator: the GLM grid runs as chunked
vmapped XLA programs (bf16 X, f32 solver state), trees run mask-fold fits
against a once-binned matrix. The host baseline is MEASURED at the full row
count (per-config cost x config count — configs within a family are
cost-identical by construction), not extrapolated from a subsample; numpy's
multithreaded BLAS makes it a GENEROUS stand-in for the reference's
Spark-local path (which adds JVM/DataFrame overhead on top of the same
BLAS). vs_baseline_8thread additionally divides by the reference's
8-thread pool for the most conservative comparison.

Also measured: MFU from XLA's own cost analysis, an AuPR parity delta
between the device sweep winner and the same config fit on host, the
wide-transmogrify config (vectorized host transforms vs a reference-shaped
per-row loop), and the three helloworld example flows.

The flagship runs on the TPU JAX finds; with no TPU it fails. A caller
that pins `JAX_PLATFORMS=cpu` gets the reduced CPU_CFG liveness run,
labelled `backend: "cpu"` — never a device figure. One process runs every
device phase: a chip belongs to one process.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...} and
exits non-zero when any phase failed or was skipped. A watchdog emits the
partial JSON if the time budget expires mid-phase.
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "2400"))

TPU_CFG = dict(n_rows=10_000_000, n_cols=64, folds=5, glm_grid=48,
               gbt_grid=16, gbt_rounds=10, gbt_depth=6, gbt_bins=32,
               wide_rows=1_000_000)
# what an explicit JAX_PLATFORMS=cpu run sweeps: liveness, not a perf
# claim — sized so the whole bench finishes in a few minutes
CPU_CFG = dict(n_rows=200_000, n_cols=64, folds=5, glm_grid=12,
               gbt_grid=4, gbt_rounds=5, gbt_depth=4, gbt_bins=32,
               wide_rows=60_000)

RESULT: dict = {"metric": "cv_sweep_wall", "value": -1.0, "unit": "s",
                "vs_baseline": 0.0}
_T0 = time.time()

# Incremental persistence: every completed phase snapshots RESULT to disk,
# so a killed process cannot erase the evidence already gathered.
PARTIAL_PATH = os.environ.get(
    "BENCH_PARTIAL_PATH",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "bench_partial.json"))


def persist_partial(phase: str) -> None:
    try:
        RESULT["last_phase"] = phase
        with open(PARTIAL_PATH + ".tmp", "w") as f:
            json.dump(RESULT, f)
        os.replace(PARTIAL_PATH + ".tmp", PARTIAL_PATH)
    except OSError:
        pass


TRACE_DIR = os.environ.get("BENCH_TRACE_DIR")


def save_trace_artifacts() -> None:
    """Flush the BENCH_TRACE_DIR span tree to disk. Called from the
    happy path AND the budget-alarm/fatal paths: the preempted long run
    is exactly the run the trace exists to make inspectable, so dying
    must not lose it (events.jsonl already streamed)."""
    if not TRACE_DIR:
        return
    try:
        from transmogrifai_tpu.utils.metrics import collector
        if not collector.enabled:
            return
        collector.save(os.path.join(TRACE_DIR, "bench_stage_metrics.json"))
        collector.save_chrome_trace(
            os.path.join(TRACE_DIR, "bench_trace.json"))
    except Exception:
        pass  # best-effort: never block the JSON emit on trace IO


def emit_and_exit(signum=None, frame=None):
    RESULT.setdefault("errors", []).append("time budget expired; partial run")
    persist_partial("budget_expired")
    save_trace_artifacts()
    print(json.dumps(RESULT), flush=True)
    os._exit(1)


def remaining() -> float:
    return BUDGET_S - (time.time() - _T0)


def log(msg: str) -> None:
    print(f"[bench +{time.time() - _T0:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


def pinned_backend():
    """The backend this run must come up on: "cpu" only when the caller
    pinned JAX_PLATFORMS=cpu, else "tpu". Read from the environment so a
    parent that only launches children never initializes a backend."""
    pinned = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    return "cpu" if pinned == "cpu" else "tpu"


def require_backend():
    """(backend, device_kind) of the device JAX found; exits non-zero
    when it is not the one the caller asked for — a missing chip is a
    failed run, never a smaller one."""
    import jax
    want = pinned_backend()
    dev = jax.devices()[0]
    if dev.platform != want:
        print(f"bench: expected a {want} backend, JAX found "
              f"{dev.platform} ({dev.device_kind}); nothing was run",
              file=sys.stderr)
        sys.exit(1)
    return dev.platform, dev.device_kind


# -- data -------------------------------------------------------------------

def truth_beta(d):
    """Ground-truth coefficients shared by the device draw and the host
    twin, so both fits chase the SAME population optimum (the AuPR parity
    probe depends on this)."""
    rng = np.random.default_rng(123)
    return (rng.normal(size=d) / np.sqrt(d)).astype(np.float32)


def make_data(n, d, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    logits = X @ truth_beta(d)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-logits))).astype(np.float32)
    return X, y


def device_data(n, d, folds, dtype):
    """Generate the sweep data ON DEVICE (one XLA program): no multi-GB
    host matrix is built or copied; the host baseline uses an
    independently drawn twin of the same distribution (its cost is
    data-independent: fixed-iteration solvers).
    Same key + static dtype means X can be regenerated bit-identically in
    another precision later."""
    import jax
    import jax.numpy as jnp

    beta_np = truth_beta(d)

    def gen(key):
        kx, _, ku, kf = jax.random.split(key, 4)
        X = jax.random.normal(kx, (n, d), jnp.float32)
        p = jax.nn.sigmoid(X @ jnp.asarray(beta_np))
        y = (jax.random.uniform(ku, (n,)) < p).astype(jnp.float32)
        fold = jax.random.randint(kf, (n,), 0, folds)
        masks = (fold[None, :]
                 != jnp.arange(folds)[:, None]).astype(jnp.float32)
        return X.astype(dtype), y, masks

    X, y, masks = jax.jit(gen)(jax.random.PRNGKey(0))
    jax.block_until_ready((X, y, masks))
    return X, y, masks


def glm_grids(g):
    regs = np.logspace(-4, -0.5, max(g // 3, 1))
    out = [{"reg_param": float(r), "elastic_net_param": a}
           for r in regs for a in (0.0, 0.25, 0.5)]
    return out[:g]


def gbt_grids(cfg):
    out = [{"num_round": cfg["gbt_rounds"], "max_depth": d, "eta": e,
            "reg_lambda": l, "max_bins": cfg["gbt_bins"]}
           for d in (cfg["gbt_depth"] - 2, cfg["gbt_depth"])
           for e in (0.05, 0.1, 0.2, 0.3) for l in (1.0, 5.0)]
    return out[:cfg["gbt_grid"]]


# -- device sweeps (the framework's own validator paths) --------------------

def device_sweeps(X, y, cfg, sweep_dtype, errors):
    """GLM + tree sweeps through the framework validator, both in THIS
    process. Each family is independently fault-isolated: a failure
    (e.g. an OOM, a Mosaic refusal) records an error — which fails the
    run's exit code — and zeroes that family, so the other family's
    evidence is still gathered."""
    from transmogrifai_tpu.automl.tuning.validators import CrossValidation
    from transmogrifai_tpu.evaluators.evaluators import Evaluators
    from transmogrifai_tpu.models.glm import OpLogisticRegression
    from transmogrifai_tpu.models.trees import OpXGBoostClassifier
    from transmogrifai_tpu.utils.metrics import collector as _mc

    ev = Evaluators.BinaryClassification.au_pr()
    val = CrossValidation(ev, num_folds=cfg["folds"], seed=42,
                          sweep_dtype=sweep_dtype)
    # synthetic standard-normal features: standardization is a statistical
    # no-op; skipping it avoids a per-lane [n, d] standardized copy
    lr = OpLogisticRegression(max_iter=15, standardization=False)
    ggrids = glm_grids(cfg["glm_grid"])
    tgrids = gbt_grids(cfg)

    best_glm = best_tree = None
    glm_s = tree_s = 0.0
    glm_warm_s = None
    glm_route = None
    glm_info = None  # round/pass telemetry of the streamed route
    log(f"GLM sweep: {len(ggrids)} grids x {cfg['folds']} folds")
    try:
        t0 = time.perf_counter()
        best_glm = val.validate([(lr, [dict(g) for g in ggrids])], X, y)
        # every validate() route np.asarray()s its fold metrics to
        # host floats before returning, so this wall is device-synced
        # tmoglint: disable=TPU005  validate() blocks via np.asarray
        glm_s = time.perf_counter() - t0
        glm_route = best_glm.validated[0].route
        glm_info = val.last_streamed_telemetry
        log(f"GLM sweep done in {glm_s:.2f}s (incl. compile, "
            f"route={glm_route}, telemetry={glm_info})")
        # steady state: the re-run hits the jit cache, isolating XLA
        # compile time (reported separately; the headline keeps cold)
        t0 = time.perf_counter()
        val.validate([(lr, [dict(g) for g in ggrids])], X, y)
        # tmoglint: disable=TPU005  validate blocks via np.asarray
        glm_warm_s = time.perf_counter() - t0
        log(f"GLM sweep warm: {glm_warm_s:.2f}s")
    except Exception as e:
        errors.append(f"glm sweep: {type(e).__name__}: {str(e)[:200]}")

    log(f"tree sweep: {len(tgrids)} configs x {cfg['folds']} folds")
    kernel_roofline = []
    # a BENCH_TRACE_DIR run already enabled the collector in main();
    # re-enabling here would reset its span tree mid-run
    mc_was_enabled = _mc.enabled
    try:
        # stage-metric collection ON so the fused tree fits record
        # per-kernel roofline spans (achieved GB/s vs the HBM roof)
        if not mc_was_enabled:
            _mc.enable("bench_tree_sweep")
        t0 = time.perf_counter()
        best_tree = val.validate([(OpXGBoostClassifier(),
                                   [dict(g) for g in tgrids])], X, y)
        # tmoglint: disable=TPU005  validate() blocks via np.asarray
        tree_s = time.perf_counter() - t0
        kernel_roofline = [k.to_json()
                           for k in _mc.current.kernel_metrics]
        log(f"tree sweep done in {tree_s:.2f}s")
    except Exception as e:
        errors.append(f"tree sweep: {type(e).__name__}: {str(e)[:200]}")
    finally:
        if not mc_was_enabled:
            _mc.disable()

    candidates = [b for b in (best_glm, best_tree) if b is not None]
    if not candidates:
        raise RuntimeError("both sweep families failed: " + "; ".join(errors))
    best = max(candidates, key=lambda b: b.best_metric)
    out = dict(glm_s=glm_s, tree_s=tree_s, glm_route=glm_route,
               tree_route=tree_route_ran(best_tree, kernel_roofline),
               glm_fits=len(ggrids) * cfg["folds"] if best_glm else 0,
               tree_fits=len(tgrids) * cfg["folds"] if best_tree else 0,
               best_name=best.name, best_grid=best.best_grid,
               best_au_pr=float(best.best_metric))
    if glm_route == "streamed" and glm_info:
        # convergence telemetry: the executed-FLOP model and the
        # acceptance gates read these (monotone active-lane shrink,
        # one-pass squared sweeps). Emit only the keys that exist rather
        # than JSON nulls that break numeric consumers.
        out["glm_telemetry"] = glm_info
        for k in ("glm_rounds", "lanes_retired", "data_passes"):
            if glm_info.get(k) is not None:
                out[k] = glm_info[k]
    if kernel_roofline:
        out["kernel_roofline"] = kernel_roofline
    if best_tree is not None:
        # the fused tree fit's compile-wall proxy
        tts = tree_trace_seconds(kernel_roofline)
        if tts:
            out["tree_trace_s"] = tts
    if glm_warm_s is not None:
        out["glm_warm_s"] = round(glm_warm_s, 3)
    return out


def tree_trace_seconds(kernel_roofline):
    """Cold-minus-warm compile proxy from the tree sweep's own roofline
    spans: a cold span's wall includes jit trace + Mosaic compile, so
    subtracting the median warm wall of the same kernel label leaves the
    trace+compile share. Labels with no warm twin contribute their full
    cold wall (an upper bound). The fused fit's program count grows with
    depth (one route_hist program a level), so BENCH JSON carries the
    number as `tree_trace_s` (docs/performance.md). Spans group by
    (kernel, bytes_hbm):
    analytic bytes are a pure function of the program shape (rows,
    lanes, depth, rounds, itemsize), so a grid sweep whose chunking
    emits several lane counts under one label never mixes one shape's
    warm walls into another shape's cold baseline."""
    by = {}
    for k in kernel_roofline or []:
        by.setdefault((k.get("kernel"), k.get("bytes_hbm")), []).append(k)
    total = 0.0
    for spans in by.values():
        colds = [float(s.get("wall_seconds", 0.0)) for s in spans
                 if s.get("cold")]
        warms = sorted(float(s.get("wall_seconds", 0.0)) for s in spans
                       if not s.get("cold"))
        if not colds:
            continue
        warm_med = warms[len(warms) // 2] if warms else 0.0
        total += sum(max(c - warm_med, 0.0) for c in colds)
    return round(total, 3)


def tree_route_ran(best_tree, kernel_roofline):
    """The tree route the sweep TOOK, read from its own record: the fused
    fits leave kernel spans named for their program
    (models/trees._timed_fused_fit); without one the validator's route
    label stands. Never inferred from flags — a fit that did not reach
    the kernel must not be reported as if it had."""
    if best_tree is None:
        return None
    spans = sorted({k["kernel"] for k in kernel_roofline
                    if str(k.get("kernel", "")).startswith("tree_sweep")})
    return "+".join(spans) or best_tree.validated[0].route


def glm_flops_estimate(cfg, route, telemetry=None):
    """Executed FLOPs for the GLM sweep, matched to the route that actually
    ran (ADVICE r2: attributing vmapped timings to the streamed FLOP model
    misstates MFU) AND to the convergence telemetry the sweep recorded.

    Streamed (ops/glm_sweep.py): per executed lane-pass — eta 2nd +
    gradient 2nd + FULL symmetric per-lane Gram einsum 2nd^2. (The old
    model billed the compressed-triangle Gram 2nT, T = d(d+1)/2, which the
    kernel retired when the triangle's column gather proved to be the TPU
    wall — _hessian_blocks moved to the full einsum — and it hard-coded 15
    iterations.) Executed lane-passes come from the sweep's own telemetry
    — `padded_lane_passes` (sum over rounds of bucket_size x iterations:
    the device runs the padded power-of-two bucket, so that is what MFU
    must bill; `lane_passes` is the USEFUL active-lane work) with the
    logical count as fallback; folds for the one-pass squared-loss Gram
    path. `glm_rounds`/`lanes_retired`/`data_passes` land in the sweep
    JSON alongside. Only when telemetry is absent entirely does it fall
    back to the legacy 15-iterations x all-lanes assumption.

    Vmapped (ops/glm.py per lane): eta 2nd + gradient 2nd + full weighted
    Gram 2nd^2 + the [n, d] scale nd; 15 iterations x lanes."""
    n, d = cfg["n_rows"], cfg["n_cols"]
    fits = cfg["glm_grid"] * cfg["folds"]
    if route == "streamed":
        per_lane_pass = 4 * n * d + 2 * n * d * d
        t = telemetry or {}
        lane_passes = t.get("padded_lane_passes") or t.get("lane_passes")
        if lane_passes:
            return per_lane_pass * lane_passes
        return per_lane_pass * 15 * fits
    # vmapped / sequential per-lane solve
    per_iter_lane = 4 * n * d + 2 * n * d * d + n * d
    return per_iter_lane * 15 * fits


def tree_flops_cost_analysis(cfg, sweep_dtype):
    """Ask XLA itself for the per-fit FLOPs of one GBT config (AOT lowering
    hits the jit cache when shapes match the sweep's)."""
    try:
        import jax
        import jax.numpy as jnp
        from transmogrifai_tpu.ops import trees as T
        n, d = cfg["n_rows"], cfg["n_cols"]
        Xb = jax.ShapeDtypeStruct((n, d), jnp.int32)
        y = jax.ShapeDtypeStruct((n,), jnp.float32)
        w = jax.ShapeDtypeStruct((n,), jnp.float32)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        # lower the XLA-only variant: a pallas custom call's FLOPs are
        # invisible to cost analysis
        pallas_was = T.pallas_enabled()
        T.set_pallas_enabled(False)
        try:
            lowered = T.fit_gbt.lower(
                Xb, y, w, key, n_rounds=cfg["gbt_rounds"],
                depth=cfg["gbt_depth"], n_bins=cfg["gbt_bins"])
            cost = lowered.compile().cost_analysis()
        finally:
            T.set_pallas_enabled(pallas_was)
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return float(cost.get("flops", 0.0))
    except Exception as e:  # cost analysis is best-effort
        log(f"tree cost_analysis unavailable: {e}")
        return 0.0


# -- host baselines (measured at FULL size) ---------------------------------

def numpy_fit_logistic(X, y, w, reg, iters=15):
    """Newton IRLS with f32 BLAS matmuls (f64 d x d solve). f32 sgemm is
    ~2x dgemm throughput, making this baseline FASTER — i.e. the
    vs_baseline ratio more conservative — than the reference's netlib
    path, and halving host RAM at the 10M-row config."""
    n, d = X.shape
    beta = np.zeros(d, np.float32)
    b0 = 0.0
    Xw = np.ascontiguousarray(X, np.float32)
    for _ in range(iters):
        m = Xw @ beta + b0
        p = 1 / (1 + np.exp(-np.clip(m, -30, 30)))
        g = (w * (p - y)).astype(np.float32)
        h = np.maximum(w * p * (1 - p), 1e-6).astype(np.float32)
        Xh = Xw * h[:, None]
        H = (Xw.T @ Xh).astype(np.float64) + reg * np.sum(w) * np.eye(d)
        gb = (Xw.T @ g).astype(np.float64) + reg * np.sum(w) * beta
        beta = (beta - np.linalg.solve(H, gb)).astype(np.float32)
        b0 -= g.sum() / h.sum()
    return beta.astype(np.float64), float(b0)


def numpy_au_pr(score, y, w):
    keep = w > 0
    score, y = score[keep], y[keep]
    order = np.argsort(-score)
    y = y[order]
    tp = np.cumsum(y)
    fp = np.cumsum(1 - y)
    prec = tp / np.maximum(tp + fp, 1e-12)
    rec = tp / max(tp[-1], 1e-12)
    dr = np.diff(rec, prepend=0.0)
    return float((dr * prec).sum())


def baseline_glm(X, y, masks, cfg, n_measure=2):
    """Per-fit cost measured at full rows (configs in the logistic grid are
    cost-identical: same matmuls, fixed iterations); total = cost x fits."""
    w = masks[0]
    times = []
    for i in range(n_measure):
        t0 = time.perf_counter()
        numpy_fit_logistic(X, y, w, 0.01)
        times.append(time.perf_counter() - t0)
        log(f"baseline GLM fit {i}: {times[-1]:.2f}s")
    per_fit = float(np.median(times))
    fits = cfg["glm_grid"] * cfg["folds"]
    return per_fit, per_fit * fits


def numpy_gbt_round(Xb, g, h, depth, n_bins):
    """One boosting round of histogram GBT in numpy (reference-shaped host
    compute): level-wise, per-feature bincount histograms, best-gain split."""
    n, F = Xb.shape
    node = np.zeros(n, np.int32)
    feats = []
    threshs = []
    for lvl in range(depth):
        n_nodes = 1 << lvl
        best_gain = np.full(n_nodes, -np.inf)
        best_f = np.zeros(n_nodes, np.int32)
        best_t = np.zeros(n_nodes, np.int32)
        for f in range(F):
            idx = node * n_bins + Xb[:, f]
            gh = np.bincount(idx, weights=g, minlength=n_nodes * n_bins)
            hh = np.bincount(idx, weights=h, minlength=n_nodes * n_bins)
            gh = gh.reshape(n_nodes, n_bins)
            hh = hh.reshape(n_nodes, n_bins)
            gl = np.cumsum(gh, axis=1)
            hl = np.cumsum(hh, axis=1)
            gt = gl[:, -1:]
            ht = hl[:, -1:]
            gain = (gl ** 2 / np.maximum(hl + 1.0, 1e-6)
                    + (gt - gl) ** 2 / np.maximum(ht - hl + 1.0, 1e-6)
                    - gt ** 2 / np.maximum(ht + 1.0, 1e-6))
            fb = np.argmax(gain, axis=1)
            fg = np.take_along_axis(gain, fb[:, None], 1)[:, 0]
            upd = fg > best_gain
            best_gain = np.where(upd, fg, best_gain)
            best_f = np.where(upd, f, best_f)
            best_t = np.where(upd, fb, best_t)
        feats.append(best_f)
        threshs.append(best_t)
        node = 2 * node + (Xb[np.arange(n), best_f[node]]
                           > best_t[node]).astype(np.int32)
    leaves = 1 << depth
    gl = np.bincount(node, weights=g, minlength=leaves)
    hl = np.bincount(node, weights=h, minlength=leaves)
    return -gl / (hl + 1.0 + 1e-6), node


def baseline_gbt(X, y, masks, cfg):
    """One full boosting ROUND measured at full rows (rounds are
    cost-identical); total = round cost x rounds x configs x folds, plus the
    one-time binning cost per (config, fold)."""
    t0 = time.perf_counter()
    edges = np.quantile(X[:: max(1, len(X) // 200_000)],
                        np.linspace(0, 1, cfg["gbt_bins"] + 1)[1:-1], axis=0)
    Xb = np.empty(X.shape, np.int32)
    for f in range(X.shape[1]):
        Xb[:, f] = np.searchsorted(edges[:, f], X[:, f], side="right")
    bin_s = time.perf_counter() - t0
    log(f"baseline GBT binning: {bin_s:.2f}s")

    w = masks[0]
    margin = np.zeros(len(y), np.float64)
    p = 1 / (1 + np.exp(-margin))
    g = w * (p - y)
    h = np.maximum(w * p * (1 - p), 1e-6)
    t0 = time.perf_counter()
    numpy_gbt_round(Xb, g, h, cfg["gbt_depth"], cfg["gbt_bins"])
    round_s = time.perf_counter() - t0
    log(f"baseline GBT round: {round_s:.2f}s")
    fits = cfg["gbt_grid"] * cfg["folds"]
    total = (round_s * cfg["gbt_rounds"] + bin_s) * fits
    return round_s, total


def aupr_parity(Xh, yh, masks_h, best_grid, Xd, yd):
    """Statistical-parity probe: fit the winning config on device (its own
    10M draw) AND on host (the host twin) with the SAME fold-0 training
    mask as weights, then score the SAME host data with both coefficient
    vectors and compare exact AuPR. Both fits see the same fraction of the
    same distribution, so the betas converge to the same population
    optimum; the delta isolates solver disagreement."""
    from transmogrifai_tpu.models.glm import OpLogisticRegression

    w = masks_h[0]
    reg = float(best_grid.get("reg_param", 0.01))
    alpha = float(best_grid.get("elastic_net_param", 0.0))
    est = OpLogisticRegression(max_iter=15, standardization=False,
                               reg_param=reg, elastic_net_param=alpha)
    model = est.fit_arrays(Xd, yd, w=w)  # device fit, fold-0 train mask
    dev_beta = np.asarray(model.beta, np.float64)
    dev_b0 = float(model.intercept)
    host_beta, host_b0 = numpy_fit_logistic(Xh, yh, w, reg)
    val_w = 1.0 - w
    a_dev = numpy_au_pr(Xh @ dev_beta + dev_b0, yh, val_w)
    a_host = numpy_au_pr(Xh @ host_beta + host_b0, yh, val_w)
    return abs(a_dev - a_host), a_host, a_dev


# -- wide transmogrify ------------------------------------------------------

def make_wide_rows(n, seed=2):
    rng = np.random.default_rng(seed)
    cats_a = [f"cat{i}" for i in range(50)]
    cats_b = [f"seg{i}" for i in range(12)]
    words = ["alpha", "beta", "gamma", "delta", "omega", "sigma", "tau"]
    cols = {
        "plA": rng.choice(cats_a, size=n),
        "plB": rng.choice(cats_b, size=n),
        "txt": np.array([" ".join(rng.choice(words, size=5))
                         for _ in range(n // 100)])[
                             rng.integers(0, max(n // 100, 1), size=n)],
        "r1": rng.normal(size=n),
        "r2": np.where(rng.uniform(size=n) < 0.1, np.nan, rng.normal(size=n)),
        "dt": (1_500_000_000_000
               + rng.integers(0, 10**9, size=n)).astype(np.int64),
        "m1": rng.normal(size=n),  # map keys k0/k1 assembled below
        "m2": rng.normal(size=n),
    }
    return cols


def wide_transmogrify(n):
    from transmogrifai_tpu.automl.transmogrifier import transmogrify
    from transmogrifai_tpu.data.dataset import Dataset
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.types import (
        Date, Integral, PickList, Real, RealMap, Text,
    )
    from transmogrifai_tpu.workflow.workflow import Workflow

    cols = make_wide_rows(n)
    maps = np.empty(n, dtype=object)
    for i in range(n):
        maps[i] = {"k0": cols["m1"][i], "k1": cols["m2"][i]}
    ds = Dataset.from_features([
        ("plA", PickList, cols["plA"].tolist()),
        ("plB", PickList, cols["plB"].tolist()),
        ("txt", Text, cols["txt"].tolist()),
        ("r1", Real, cols["r1"].tolist()),
        ("r2", Real, [None if np.isnan(v) else float(v)
                      for v in cols["r2"]]),
        ("dt", Date, cols["dt"].tolist()),
        ("mp", RealMap, list(maps)),
    ])
    feats = [
        FeatureBuilder.PickList("plA").extract(lambda r: r.get("plA")).as_predictor(),
        FeatureBuilder.PickList("plB").extract(lambda r: r.get("plB")).as_predictor(),
        FeatureBuilder.Text("txt").extract(lambda r: r.get("txt")).as_predictor(),
        FeatureBuilder.Real("r1").extract(lambda r: r.get("r1")).as_predictor(),
        FeatureBuilder.Real("r2").extract(lambda r: r.get("r2")).as_predictor(),
        FeatureBuilder.Date("dt").extract(lambda r: r.get("dt")).as_predictor(),
        FeatureBuilder.RealMap("mp").extract(lambda r: r.get("mp")).as_predictor(),
    ]
    vec = transmogrify(feats)
    wf = Workflow().set_input_dataset(ds).set_result_features(vec)
    t0 = time.perf_counter()
    model = wf.train()
    fit_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    scored = model.score(ds)
    score_cold_s = time.perf_counter() - t0
    # serving throughput is a warm-path number: the cold pass pays one-time
    # page-fault/allocator costs for the [n, width] output blocks. Best of
    # 3 passes: single-shot timings on a contended 1-core box swing +-30%
    # (the r2 driver artifact recorded a noise spike as the result).
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        scored = model.score(ds)
        times.append(time.perf_counter() - t0)
    score_s = min(times)
    width = scored.column(vec.name).data.shape[1]

    # reference-shaped baseline: per-row python closure loop (the fused
    # rdd.map of FitStagesUtil.applyOpTransformations:96) producing the
    # SAME output width — 512-dim text hashing, one-hot + null columns,
    # circular date features, per-key map expansion. Measured on the same
    # rows with a time cap; per-row cost is constant so the cap-scale is
    # exact arithmetic, and measured_rows is reported.
    import math
    vocab_a = {c: i for i, c in enumerate(sorted(set(cols["plA"])))}
    vocab_b = {c: i for i, c in enumerate(sorted(set(cols["plB"])))}
    two_pi = 2 * math.pi

    def row_loop_pass(cap):
        t0 = time.perf_counter()
        done = 0
        for i in range(n):
            row = []
            oh = [0.0] * (len(vocab_a) + 2)  # topK + OTHER + null
            oh[vocab_a.get(cols["plA"][i], len(vocab_a))] = 1.0
            row += oh
            oh = [0.0] * (len(vocab_b) + 2)
            oh[vocab_b.get(cols["plB"][i], len(vocab_b))] = 1.0
            row += oh
            toks = cols["txt"][i].lower().split()
            hv = [0.0] * 512  # TransmogrifierDefaults.DefaultNumOfFeatures
            for t in toks:
                hv[hash(t) % 512] += 1.0
            row += hv
            row += [cols["r1"][i], 0.0]
            v = cols["r2"][i]
            isnan = v != v
            row += [0.0 if isnan else v, 1.0 if isnan else 0.0]
            ts = cols["dt"][i] / 86_400_000.0
            for period in (1.0, 7.0, 30.4375, 365.25):
                row += [math.sin(two_pi * ts / period),
                        math.cos(two_pi * ts / period)]
            row += [cols["m1"][i], 0.0, cols["m2"][i], 0.0]
            done = i + 1
            if (i & 1023) == 0 and time.perf_counter() - t0 > cap:
                break
        return (time.perf_counter() - t0) * (n / done), done

    # best of 2 passes, same contention-noise defense as score_s (the
    # baseline must not be inflated by a noise spike either)
    cap = min(120.0, max(remaining() - 60.0, 10.0)) / 2
    (l1, d1), (l2, d2) = row_loop_pass(cap), row_loop_pass(cap)
    loop_s, done = ((l1, d1) if l1 <= l2 else (l2, d2))
    return dict(rows=n, fit_s=round(fit_s, 3), score_s=round(score_s, 3),
                score_cold_s=round(score_cold_s, 3),
                vector_width=int(width),
                rows_per_s=int(n / max(score_s, 1e-9)),
                row_loop_s=round(loop_s, 3),
                row_loop_measured_rows=done,
                vs_row_loop=round(loop_s / max(score_s, 1e-9), 2))


# -- histogram roofline micro-bench (--hist-roofline) -----------------------

def hist_roofline_bench(n_rows=None):
    """Micro-bench for the fused multi-(fold x lane) histogram pipeline:
    analytic bytes-moved per sweep-level for the r5 per-fold baseline vs
    the batched route+hist kernel (one residency of the binned matrix for
    all lanes, count channel derived in VMEM, routing fused into the same
    pass), plus a MEASURED deepest-level pass with achieved GB/s against
    the device's HBM roof. Runs on any backend — on CPU the jnp fallback
    path is what gets timed (a liveness number, not a perf claim); the
    analytic reduction factor is backend-independent. One JSON line."""
    import jax
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import pallas_hist as PH
    from transmogrifai_tpu.utils.metrics import roofline_fields
    from transmogrifai_tpu.utils.platform import device_spec

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    folds, F, n_bins, depth = 5, 64, 32, 6
    B = n_bins + 1
    n = int(n_rows) if n_rows else (10_000_000 if on_tpu else 200_000)
    per_fold = PH.sweep_level_bytes(n, F, folds, fused="per_fold")
    r5 = PH.sweep_level_bytes(n, F, folds, fused="r5")
    fused = PH.sweep_level_bytes(n, F, folds, fused=True)
    out = {"metric": "hist_level_roofline", "backend": backend,
           "n_rows": n, "n_cols": F, "folds": folds, "depth": depth,
           "bytes_per_level_per_fold_route": int(per_fold),
           "bytes_per_level_r5_fold_fused": int(r5),
           "bytes_per_level_fused": int(fused),
           # vs the sequential per-lane route (the fallback when fold
           # fusion is gated off) AND vs what r5's production fold-fused
           # TPU route actually streamed — both, so neither number can
           # be mistaken for the other
           "bytes_reduction_x_vs_per_fold": round(per_fold / fused, 2),
           "bytes_reduction_x_vs_r5_fold_fused": round(r5 / fused, 2)}

    # measured deepest routed level (2^(depth-2) nodes); only
    # route_hist sits in the timed window
    n_nodes = 1 << (depth - 2)
    key = jax.random.PRNGKey(0)
    kx, kp, kn, kf = jax.random.split(key, 4)
    Xb_t = jax.random.randint(kx, (F, n), 0, B, jnp.int32).astype(jnp.int8)
    pay = jax.random.normal(kp, (2 * folds, n), jnp.float32)
    node = jax.random.randint(kn, (folds, n), 0, n_nodes,
                              jnp.int32).astype(jnp.float32)
    f_lvl = jax.random.randint(kf, (folds, n_nodes), 0, F, jnp.int32)
    t_lvl = jnp.full((folds, n_nodes), B // 2, jnp.int32)
    m_lvl = jnp.zeros((folds, n_nodes), jnp.int32)
    jax.block_until_ready((Xb_t, pay, node))

    def one():
        return PH.route_hist(Xb_t, pay, node, f_lvl, t_lvl, m_lvl,
                             n_nodes=n_nodes, n_bins=B,
                             allow_bf16=True, derive_count=True)

    jax.block_until_ready(one())  # warm/compile
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(one())
        times.append(time.perf_counter() - t0)
    wall = min(times)
    spec = device_spec()
    roof = spec.hbm_bytes_per_s / 1e9 if spec else None
    rf = roofline_fields(wall, fused, roof)  # shared arithmetic: the
    # micro-bench must report the same numbers a collector.kernel span
    # of the identical pass would
    out.update(level_wall_s=round(wall, 4),
               achieved_gbps=rf["achieved_gbps"])
    if roof:
        out.update(hbm_roof_gbps=rf["roof_gbps"],
                   pct_of_hbm_roof=rf["pct_of_roof"])
    return out


# -- statistics-engine roofline micro-bench (--stats-roofline) --------------

def stats_roofline_bench(n_rows=None):
    """Micro-bench for the one-pass statistics engine (ops/stats_engine):
    analytic bytes-moved and pass counts for the legacy multi-pass
    SanityChecker statistics vs the fused single scan, plus a MEASURED
    fused pass with achieved GB/s against the device's HBM roof. Runs on
    any backend — on CPU the numbers are liveness, not perf claims; the
    pass-count reduction is backend-independent. One JSON line."""
    import jax
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import stats as S
    from transmogrifai_tpu.ops import stats_engine as SE
    from transmogrifai_tpu.utils.metrics import roofline_fields
    from transmogrifai_tpu.utils.platform import device_spec

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    n = int(n_rows) if n_rows else (10_000_000 if on_tpu else 200_000)
    d, n_classes, n_groups = 64, 4, 8
    bytes_pass = SE.stats_pass_bytes(n, d)
    legacy_passes = SE.legacy_pass_count(corr_matrix=True,
                                         n_groups=n_groups)
    out = {"metric": "stats_roofline", "backend": backend,
           "n_rows": n, "n_cols": d, "n_groups": n_groups,
           "bytes_per_pass": int(bytes_pass),
           "legacy_passes": int(legacy_passes), "fused_passes": 1,
           "traffic_reduction_x": float(legacy_passes)}

    key = jax.random.PRNGKey(0)
    kx, ky = jax.random.split(key)
    X = jax.random.normal(kx, (n, d), jnp.float32)
    y = jax.random.randint(ky, (n,), 0, n_classes,
                           jnp.int32).astype(jnp.float32)
    distinct = jnp.arange(n_classes, dtype=jnp.float32)
    clip = jnp.zeros(d, bool)
    w = jnp.ones(n, jnp.float32)

    def fused_one(xv):
        st, _ = SE.fused_stats(xv, y, w, distinct=distinct, clip=clip,
                               corr_matrix=True)
        return st

    jax.block_until_ready(fused_one(X))  # warm/compile
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fused_one(X))
        times.append(time.perf_counter() - t0)
    wall = min(times)
    spec = device_spec()
    roof = spec.hbm_bytes_per_s / 1e9 if spec else None
    rf = roofline_fields(wall, bytes_pass, roof)  # shared arithmetic:
    # this line must report the same numbers a collector stats_pass span
    # of the identical pass would
    out.update(fused_wall_s=round(wall, 4),
               achieved_gbps=rf["achieved_gbps"])
    if roof:
        out.update(hbm_roof_gbps=rf["roof_gbps"],
                   pct_of_hbm_roof=rf["pct_of_roof"])
    # the StatsPass telemetry shape (utils/metrics.StatsPass), verbatim,
    # so BENCH JSON consumers see the same struct a traced run records
    # next to kernel_roofline
    out["stats_pass"] = {
        "driver": "fused", "rows": n, "cols": d,
        "tiles": -(-n // SE.stats_row_block(d, n)),
        "bytes_hbm": int(bytes_pass), "wall_seconds": round(wall, 6),
        "passes": 1}

    # measured legacy route at the same shape: the separate reductions +
    # one contingency matmul per categorical group (what the pre-engine
    # SanityChecker dispatched)
    def legacy_one(xv):
        outs = [S.col_stats(xv), S.pearson_with_label(xv, y),
                S.pearson_matrix(xv), S.col_stats(y[:, None])]
        yoh = (y[:, None] == distinct[None, :]).astype(jnp.float32)
        for g in range(n_groups):
            cols = xv[:, 2 * g:2 * g + 2]
            outs.append(S.contingency_table(cols, yoh))
        return outs

    jax.block_until_ready(legacy_one(X))
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(legacy_one(X))
        times.append(time.perf_counter() - t0)
    out.update(legacy_wall_s=round(min(times), 4),
               speedup_x=round(min(times) / max(wall, 1e-9), 2))
    return out


# -- streaming data plane scenario (--streaming) ----------------------------

def streaming_bench(n_rows=None):
    """Scenario config for the tileplane streaming data plane
    (docs/performance.md "Streaming data plane"): an Avro file on disk is
    the ONLY copy of X; the bench streams it through every consumer —
    stats fit, GLM round fit, quantile binning + binned-matrix emission,
    and bulk scoring through a fitted workflow — reporting rows/s per
    phase plus the measured copy/compute overlap ratio, so the bench
    trajectory tracks this path like the flagship sweep. One JSON line;
    on CPU the numbers are liveness, not perf claims."""
    import shutil
    import tempfile

    import jax
    from transmogrifai_tpu.ops import glm_sweep as GS
    from transmogrifai_tpu.ops import stats_engine as SE
    from transmogrifai_tpu.ops import trees as TR
    from transmogrifai_tpu.parallel import tileplane as TP
    from transmogrifai_tpu.readers.avro import read_avro_file, \
        write_avro_file
    from transmogrifai_tpu.utils.metrics import collector

    backend = jax.default_backend()
    n = int(n_rows) if n_rows else (2_000_000 if backend == "tpu"
                                    else 50_000)
    d, F = 16, 3
    out = {"metric": "streaming_plane", "backend": backend,
           "n_rows": n, "n_cols": d, "tile_mb": TP.tile_budget_bytes() >> 20}

    tmp = tempfile.mkdtemp(prefix="bench_stream_")
    try:
        rng = np.random.default_rng(0)
        beta = rng.normal(size=d)
        schema = {"type": "record", "name": "Row", "fields": (
            [{"name": f"x{j}", "type": "float"} for j in range(d)]
            + [{"name": "y", "type": "float"},
               {"name": "id", "type": "long"}])}
        t0 = time.perf_counter()
        # written in SLABS of separate container files so the writer
        # holds at most one slab of records — the Avro directory really
        # is the only full copy of X
        slab = 250_000
        paths = []
        i = 0
        while i < n:
            rows = min(slab, n - i)
            recs = []
            for r_i in range(i, i + rows):
                x = rng.normal(size=d).astype(np.float32)
                recs.append({**{f"x{j}": float(x[j]) for j in range(d)},
                             "y": float(x @ beta > 0), "id": r_i})
            p = os.path.join(tmp, f"rows_{len(paths):04d}.avro")
            write_avro_file(p, schema, recs)
            paths.append(p)
            del recs
            i += rows
        out["write_s"] = round(time.perf_counter() - t0, 2)
        out["slabs"] = len(paths)

        def read_all():
            for p in paths:
                yield from read_avro_file(p)

        def stats_row(r):
            return ([r[f"x{j}"] for j in range(d)], r["y"], 1.0)

        def glm_row(r):
            m = [1.0] * F
            m[r["id"] % F] = 0.0
            return ([r[f"x{j}"] for j in range(d)], r["y"], 1.0, m)

        def src(fn):
            return TP.reader_row_source(read_all, fn,
                                        batch_records=8192, n_rows=n)

        # timed phases run UNTRACED: tracing inserts per-tile
        # block_until_ready fences the production path does not pay
        # (docs/observability.md "Tile spans"), so traced rows/s would
        # systematically understate the async pipeline
        t0 = time.perf_counter()
        SE.run_stats(src(stats_row), corr_matrix=True, label="bench")
        wall = time.perf_counter() - t0
        ps = SE._last_stream_stats
        out["stats_fit"] = {
            "wall_s": round(wall, 3),
            "rows_per_s": round(n / max(wall, 1e-9))}
        if ps is not None:  # None on the TMOG_TILEPLANE=0 legacy loop
            out["stats_fit"].update(tiles=ps.tiles,
                                    peak_host_rows=ps.peak_host_rows)

        t0 = time.perf_counter()
        _, _, info = GS.sweep_glm_streamed_rounds(
            src(glm_row), None, None, None,
            np.asarray([0.01, 0.1], np.float32),
            np.zeros(2, np.float32), loss="logistic", max_iter=15,
            tol=1e-5, warm_start=True)
        # the round driver returns HOST numpy coefficients — every
        # streamed pass already fenced on its delta fetch
        wall = time.perf_counter() - t0  # tmoglint: disable=TPU005
        out["glm_fit"] = {
            "wall_s": round(wall, 3),
            "data_passes": info["data_passes"],
            "rows_per_s_effective": round(
                n * max(info["data_passes"], 1) / max(wall, 1e-9)),
            "rounds": info["glm_rounds"]}

        t0 = time.perf_counter()
        edges = TR.stream_quantile_edges(src(stats_row), 32,
                                         hist_bins=512)
        # stats source yields (x, y, w); binning reads x only
        xonly = TP.IterSource(
            lambda: ((c[0],) for c in src(stats_row).chunks()),
            n_rows=n)
        binned = TR.stream_bin_matrix(xonly, edges)
        wall = time.perf_counter() - t0
        out["tree_binning"] = {
            "wall_s": round(wall, 3),
            "rows_per_s": round(n / max(wall, 1e-9)),
            "binned_mb": round(binned.nbytes / (1 << 20), 1)}
        del binned

        # separate TRACED probe pass just for the overlap ratio (its
        # wall is not the headline number)
        collector.enable("bench_streaming")
        try:
            SE.run_stats(src(stats_row), corr_matrix=True,
                         label="overlap_probe")
            ps = SE._last_stream_stats
            if ps is not None and ps.wall_seconds:
                out["overlap_probe"] = {
                    "overlap_ratio": round(
                        (ps.copy_seconds + ps.compute_seconds)
                        / max(ps.wall_seconds, 1e-9), 3),
                    "copy_s": round(ps.copy_seconds, 3),
                    "compute_s": round(ps.compute_seconds, 3),
                    "wall_s": round(ps.wall_seconds, 3)}
        finally:
            collector.finish()
            collector.disable()

        out["score"] = _streaming_score_phase(
            os.path.join(tmp, "rows_*.avro"), paths[0], d, n)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _streaming_score_phase(avro_pattern, train_path, d, n):
    """Train a tiny transmogrified workflow, then bulk-score the Avro
    stream through the tileplane scoring path (fixed record tiles,
    producer-thread Dataset assembly)."""
    import contextlib
    import io as _io

    from transmogrifai_tpu import FeatureBuilder
    from transmogrifai_tpu.automl import BinaryClassificationModelSelector
    from transmogrifai_tpu.automl.transmogrifier import transmogrify
    from transmogrifai_tpu.models.glm import OpLogisticRegression
    from transmogrifai_tpu.readers import AvroStreamingReader, score_stream
    from transmogrifai_tpu.readers.avro import read_avro_file
    from transmogrifai_tpu.readers.readers import ListReader
    from transmogrifai_tpu.stages.params import param_grid
    from transmogrifai_tpu.workflow import Workflow

    train_rows = []
    for r in read_avro_file(train_path):
        train_rows.append(r)
        if len(train_rows) >= 5000:
            break
    preds = [FeatureBuilder.Real(f"x{j}").extract(
        lambda r, j=j: r.get(f"x{j}")).as_predictor() for j in range(d)]
    fy = FeatureBuilder.RealNN("y").extract(
        lambda r: r.get("y")).as_response()
    vec = transmogrify(preds)
    pred = BinaryClassificationModelSelector.with_train_validation_split(
        models_and_parameters=[(OpLogisticRegression(),
                                param_grid(reg_param=[0.01]))],
    ).set_input(fy, vec).get_output()
    with contextlib.redirect_stdout(_io.StringIO()):
        model = Workflow().set_reader(ListReader(train_rows)) \
            .set_result_features(pred).train()
    reader = AvroStreamingReader(avro_pattern)
    t0 = time.perf_counter()
    scored = sum(len(b) for b in score_stream(model, reader,
                                              tile_rows=4096))
    wall = time.perf_counter() - t0
    return {"wall_s": round(wall, 3), "rows_scored": int(scored),
            "rows_per_s": round(scored / max(wall, 1e-9))}


# -- sharded ingest A/B (--ingest-ab) ---------------------------------------

def ingest_ab_bench(n_rows=None):
    """Serial-vs-parallel ingest A/B over a multi-shard CSV input
    (docs/performance.md "Parallel sharded ingest"): three arms feed
    the SAME streamed stats fit — the legacy per-record reader source,
    the columnar sharded source at workers=1, and at workers=2 — and
    the bench reports pure parse rows/s (source drained with no device
    work), end-to-end fit wall + rows/s, a traced-probe device idle
    share (1 - compute/wall on the tileplane consumer), and a
    bit-identical check on the resulting moments. One JSON line; on CPU
    the numbers are liveness + speedup shape, not absolute perf."""
    import shutil
    import tempfile

    import jax
    from transmogrifai_tpu.ops import stats_engine as SE
    from transmogrifai_tpu.parallel import ingest as ING
    from transmogrifai_tpu.parallel import tileplane as TP
    from transmogrifai_tpu.readers.readers import CSVReader

    backend = jax.default_backend()
    n = int(n_rows) if n_rows else (2_000_000 if backend == "tpu"
                                    else 120_000)
    d, shards = 8, 8
    out = {"metric": "ingest_ab", "backend": backend, "n_rows": n,
           "n_cols": d, "shards": shards}

    tmp = tempfile.mkdtemp(prefix="bench_ingest_")
    try:
        rng = np.random.default_rng(0)
        per = -(-n // shards)
        t0 = time.perf_counter()
        paths = []
        for s in range(shards):
            rows = min(per, n - s * per)
            p = os.path.join(tmp, f"part-{s:03d}.csv")
            with open(p, "w") as fh:
                fh.write(",".join(f"x{j}" for j in range(d))
                         + ",y\n")
                block = rng.normal(size=(rows, d + 1))
                for r in block:
                    fh.write(",".join(f"{v:.6f}" for v in r) + "\n")
            paths.append(p)
        out["write_s"] = round(time.perf_counter() - t0, 2)

        def stats_cols(c):
            return (np.stack([c[f"x{j}"] for j in range(d)], 1),
                    c["y"], np.ones_like(c["y"]))

        def stats_row(r):
            return ([r[f"x{j}"] for j in range(d)], r["y"], 1.0)

        def legacy_source():
            def read_all():
                for p in paths:
                    yield from CSVReader(p).read()
            return TP.reader_row_source(read_all, stats_row,
                                        batch_records=8192, n_rows=n)

        def columnar_source(workers):
            return ING.sharded_reader_source(
                paths, stats_cols, batch_records=8192, n_rows=n,
                workers=workers, label=f"ab_w{workers}")

        arms = [("legacy_per_record", legacy_source),
                ("columnar_w1", lambda: columnar_source(1)),
                ("columnar_w2", lambda: columnar_source(2))]
        # warmup: compile the stats step once (same tile shape for all
        # arms) so no arm's fit wall carries the cold compile
        SE.run_stats(columnar_source(1), label="ab_warmup")
        means = {}
        for name, mk in arms:
            # pure parse: drain the chunk stream, no device in the loop
            t0 = time.perf_counter()
            rows = sum(int(c[0].shape[0]) for c in mk().chunks())
            parse_wall = time.perf_counter() - t0
            assert rows == n
            # end-to-end: the streamed stats fit (untraced — tracing
            # fences each tile and would understate the async pipeline)
            t0 = time.perf_counter()
            res = SE.run_stats(mk(), label=f"ab_{name}")
            fit_wall = time.perf_counter() - t0
            means[name] = (np.asarray(res.mean), np.asarray(res.m2))
            ps = SE._last_stream_stats
            arm = {"parse_wall_s": round(parse_wall, 3),
                   "parse_rows_per_s": round(n / max(parse_wall, 1e-9)),
                   "fit_wall_s": round(fit_wall, 3),
                   "fit_rows_per_s": round(n / max(fit_wall, 1e-9))}
            if ps is not None:
                arm["tiles"] = ps.tiles
            # separate TRACED probe for the idle share (compute-side
            # timings only accumulate under tracing): the fraction of
            # the pass wall the consumer spent NOT computing —
            # feed-starved headroom
            from transmogrifai_tpu.utils.metrics import collector
            collector.enable(f"bench_ingest_{name}")
            try:
                SE.run_stats(mk(), label=f"ab_probe_{name}")
                ps = SE._last_stream_stats
                if ps is not None and ps.wall_seconds:
                    arm["device_idle_share"] = round(
                        1.0 - ps.compute_seconds
                        / max(ps.wall_seconds, 1e-9), 3)
            finally:
                collector.finish()
                collector.disable()
            out[name] = arm

        ref_mean, ref_m2 = means["legacy_per_record"]
        out["bit_identical"] = bool(all(
            np.array_equal(m, ref_mean) and np.array_equal(q, ref_m2)
            for m, q in means.values()))
        legacy, w2 = out["legacy_per_record"], out["columnar_w2"]
        out["parse_speedup_w2_vs_legacy"] = round(
            w2["parse_rows_per_s"] / max(legacy["parse_rows_per_s"], 1),
            2)
        out["parse_speedup_w2_vs_w1"] = round(
            w2["parse_rows_per_s"]
            / max(out["columnar_w1"]["parse_rows_per_s"], 1), 2)
        out["fit_speedup_w2_vs_legacy"] = round(
            legacy["fit_wall_s"] / max(w2["fit_wall_s"], 1e-9), 2)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# -- multi-host pod A/B (--multihost) ---------------------------------------

# Child payload for the pod arms: runs inside launch_local_pod children,
# one per process. Each process opens ONLY its stripe of the shared CSV
# shard listing (multihost.stripe_paths via the ingest auto-stripe),
# drains it once for a pure-parse rate, then runs the streamed stats fit
# and a GLM gram sweep THROUGH the pod mesh — every psum a cross-process
# gloo collective when n_procs > 1. Rank 0 also reports a psum inventory
# (trace-time `psum` counts per sharded step program, via make_jaxpr —
# no execution) and a recompile probe (jax_log_compiles over a second
# identical stream pass; any count > 0 is a shape leak).
_MULTIHOST_CHILD = r"""
import glob, logging, os, re, time
import numpy as np
from transmogrifai_tpu.parallel import multihost as MH
MH.initialize()
import jax
pc = jax.process_count()
pid = jax.process_index()
mesh = MH.global_mesh(n_model=2)
d = int(os.environ["BENCH_MH_D"])
paths = sorted(glob.glob(os.path.join(os.environ["BENCH_MH_DIR"],
                                      "part-*.csv")))
from transmogrifai_tpu.ops import glm_sweep as GS
from transmogrifai_tpu.ops import stats_engine as SE
from transmogrifai_tpu.ops import trees as TR
from transmogrifai_tpu.parallel import ingest as ING

def stats_cols(c):
    return (np.stack([c["x%d" % j] for j in range(d)], 1), c["y"],
            np.ones_like(c["y"]))

mine = [str(p) for p in MH.stripe_paths(paths)]

def mk(n_rows=None, tag="parse"):
    # stripe=False: `mine` is already this process's stripe
    return ING.sharded_reader_source(mine, stats_cols, batch_records=8192,
                                     n_rows=n_rows, workers=1,
                                     stripe=False, label="mh_" + tag)

# pure parse: drain the local stripe, no device work in the loop
t0 = time.perf_counter()
chunks = list(mk().chunks())
n_local = sum(int(c[0].shape[0]) for c in chunks)
parse_wall = time.perf_counter() - t0

# streamed stats fit through the pod mesh: warm (compile) then timed
SE.stream_stats(mk(n_local, "warm"), mesh=mesh, corr_matrix=True)
t0 = time.perf_counter()
st, shift = SE.stream_stats(mk(n_local, "fit"), mesh=mesh,
                            corr_matrix=True)
stream_wall = time.perf_counter() - t0
ps = SE._last_stream_stats
tiles = ps.tiles if ps is not None else 0

# GLM gram sweep over the resident local rows, same mesh
Xl = np.concatenate([c[0] for c in chunks])
yl = (Xl[:, 0] > 0).astype(np.float32)
wl = np.ones(n_local, np.float32)
masks = np.zeros((2, n_local), np.float32)
masks[0, ::2] = 1.0
masks[1, 1::2] = 1.0
regs = np.asarray([1.0, 0.1, 0.01, 0.001], np.float32)
alphas = np.zeros(4, np.float32)
# block on the warm result: the gram program's gloo collectives must
# drain before the timed call's row_layout allgather, or two programs'
# collectives interleave on the pod's gloo context (size-mismatch abort)
jax.block_until_ready(GS.sweep_glm_squared_gram_sharded(
    mesh, Xl, yl, wl, masks, regs, alphas, max_iter=8))
t0 = time.perf_counter()
B, b0, iters, *_ = GS.sweep_glm_squared_gram_sharded(
    mesh, Xl, yl, wl, masks, regs, alphas, max_iter=8)
jax.block_until_ready(B)
glm_wall = time.perf_counter() - t0

# recompile probe: a second identical stream pass must hit the jit cache
class _Count(logging.Handler):
    def __init__(self):
        logging.Handler.__init__(self)
        self.n = 0
    def emit(self, r):
        if "ompil" in r.getMessage():
            self.n += 1

h = _Count()
jax.config.update("jax_log_compiles", True)
lg = logging.getLogger("jax")
lg.addHandler(h)
try:
    SE.stream_stats(mk(n_local, "re"), mesh=mesh, corr_matrix=True)
finally:
    jax.config.update("jax_log_compiles", False)
    lg.removeHandler(h)

out = {"pid": pid, "pc": pc, "n_local": n_local,
       "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
       "parse_wall_s": round(parse_wall, 3),
       "stream_wall_s": round(stream_wall, 3),
       "glm_wall_s": round(glm_wall, 3), "tiles": tiles,
       "recompiles_second_pass": h.n,
       "stats_mean0": float(np.asarray(st.mean)[0])}

if pid == 0:
    # psum inventory: trace-time collective count per sharded step
    def psums(fn, *args):
        return len(re.findall(r"\bpsum\b",
                              str(jax.make_jaxpr(fn)(*args))))
    nb = mesh.devices.shape[0]
    ns = 8 * nb
    Xs = np.zeros((ns, d), np.float32)
    ys = np.zeros(ns, np.float32)
    ws = np.ones(ns, np.float32)
    ms = np.ones((2, ns), np.float32)
    r2 = np.asarray([0.1, 0.01], np.float32)
    a2 = np.zeros(2, np.float32)
    inv = {"stats_fused_step": psums(
        SE._sharded_stats_fn(mesh, 0, True, False, False, False, False),
        Xs, ys, ws)}
    inv["glm_gram_sweep"] = psums(
        GS._sharded_gram_fn(mesh, True, True),
        Xs, ys, ws, ms, r2, a2, 8, 1e-6)
    static_kw = (("n_rounds", 2), ("depth", 2), ("n_bins", 8),
                 ("min_instances", 1.0), ("min_info_gain", 0.0),
                 ("subsample", 1.0), ("feature_frac", 1.0),
                 ("loss", "logistic"), ("interpret", False),
                 ("alpha", 0.0), ("max_delta_step", 0.0),
                 ("colsample_bylevel", 1.0), ("base_score", None))
    lane = np.full(2, 0.1, np.float32)
    inv["gbt_fit"] = psums(
        TR._sharded_gbt_fn(mesh, static_kw),
        np.zeros((ns, d), np.int32), ys, ms, jax.random.PRNGKey(0),
        lane, lane, lane, lane)
    out["psum_inventory"] = inv
    out["stream_psums_per_pass"] = tiles * inv["stats_fused_step"]

import json
print("RESULT|" + json.dumps(out), flush=True)
MH.finalize()
"""


def multihost_bench(n_rows=None):
    """Multi-host pod scaling A/B (docs/performance.md "Multi-host pod
    scaling"): the SAME 2x2 (data x lane) global mesh run as one
    process owning all 4 devices vs TWO processes owning 2 each
    (launch_local_pod, real jax.distributed children on localhost,
    cross-process psums over gloo). Each arm stripes the shared CSV
    shard listing per process, reports pure-parse rows/s, streamed
    stats + GLM gram fit walls, a per-step psum inventory, a recompile
    probe (second identical pass, expect 0), and a stats checksum that
    must agree across arms. On this box every process shares ONE core,
    so the parse "speedup" is a liveness + correctness measurement, not
    a perf claim — see liveness_note in the output."""
    import shutil
    import tempfile

    from transmogrifai_tpu.parallel.launch import launch_local_pod

    n = int(n_rows) if n_rows else 60_000
    d, shards = 8, 4
    out = {"metric": "multihost_ab", "n_rows": n, "n_cols": d,
           "shards": shards}

    tmp = tempfile.mkdtemp(prefix="bench_mh_")
    try:
        rng = np.random.default_rng(0)
        per = -(-n // shards)
        for s in range(shards):
            rows = min(per, n - s * per)
            with open(os.path.join(tmp, f"part-{s:03d}.csv"), "w") as fh:
                fh.write(",".join(f"x{j}" for j in range(d)) + ",y\n")
                for r in rng.normal(size=(rows, d + 1)):
                    fh.write(",".join(f"{v:.6f}" for v in r) + "\n")

        env = {"BENCH_MH_DIR": tmp, "BENCH_MH_D": str(d)}
        trace_dir = os.path.join(tmp, "podtrace")
        arms = {}
        for name, n_procs, dev in (("one_proc", 1, 4), ("two_proc", 2, 2)):
            # flight-record the real pod arm only: the recorder's value
            # is cross-process skew/collective-wait, meaningless at pc=1
            pod = launch_local_pod(_MULTIHOST_CHILD, n_procs=n_procs,
                                   devices_per_proc=dev, timeout=420.0,
                                   extra_env=env,
                                   trace_dir=(trace_dir if n_procs > 1
                                              else None))
            if not pod.ok:
                arms[name] = {"ok": False, "error": pod.error,
                              "stderr_tail": [c.stderr_tail[-400:]
                                              for c in pod.children]}
                continue
            res = [pod.result(i) for i in range(n_procs)]
            arm = {"ok": True, "n_procs": n_procs,
                   "devices_per_proc": dev, "mesh": res[0]["mesh"],
                   "rows_parsed": sum(r["n_local"] for r in res),
                   # the pod parses shard stripes concurrently: the pod
                   # rate is total rows over the SLOWEST stripe's wall
                   "parse_wall_s": max(r["parse_wall_s"] for r in res),
                   "stream_fit_wall_s": max(r["stream_wall_s"]
                                            for r in res),
                   "glm_fit_wall_s": max(r["glm_wall_s"] for r in res),
                   "tiles": res[0]["tiles"],
                   "recompiles_second_pass": sum(
                       r["recompiles_second_pass"] for r in res),
                   "stats_mean0": res[0]["stats_mean0"],
                   "pod_wall_s": round(pod.wall_s, 2)}
            arm["parse_rows_per_s"] = round(
                arm["rows_parsed"] / max(arm["parse_wall_s"], 1e-9))
            arm["psum_inventory"] = res[0].get("psum_inventory")
            arm["stream_psums_per_pass"] = res[0].get(
                "stream_psums_per_pass")
            arms[name] = arm
        out.update(arms)

        one, two = arms.get("one_proc"), arms.get("two_proc")
        if one and two and one.get("ok") and two.get("ok"):
            out["parse_speedup_2proc"] = round(
                two["parse_rows_per_s"] / max(one["parse_rows_per_s"], 1),
                2)
            out["stats_mean0_delta"] = abs(two["stats_mean0"]
                                           - one["stats_mean0"])
            out["liveness_note"] = (
                "both pod arms share one physical CPU core, so 2 "
                "processes cannot parse faster than 1 here — this A/B "
                "is a liveness and cross-arm-agreement measurement "
                "(real cross-process gloo psums, 0 recompiles, "
                "identical stats); per-host parse scaling needs "
                "per-host cores")

        # pod flight recorder on the real pod arm: merge the per-rank
        # artifact dirs into skew / collective-wait / MFU columns
        # (docs/observability.md "Pod tracing"). This child runs
        # one-shot sharded entry points, no engine rounds, so the merge
        # aligns on one synthetic round — collective_share and the MFU
        # sinks are still exact (measured durations, analytic costs).
        if two and two.get("ok"):
            from transmogrifai_tpu.parallel import podtrace as PT
            rep = PT.merge_pod(trace_dir)
            out["pod_trace"] = {
                "rounds": len(rep["rounds"]),
                "synthetic_rounds": rep["synthetic_rounds"],
                "coverage_min_seen": rep["coverage_min_seen"],
                "collective_share": {
                    r["rank"]: r["collective_share"]
                    for r in rep["ranks"]},
                "collective_wait_s": {
                    r["rank"]: r["collective_s"]
                    for r in rep["ranks"]},
                "skew": rep["skew"],
                "mfu_top_sinks": rep["mfu_table"][:3],
                "problems": rep["problems"],
            }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# -- serving scenario (--serving) -------------------------------------------

def serving_bench(n_rows=None):
    """Scenario config for the production serving engine (serve/,
    docs/serving.md): a fitted workflow served through the bucket
    ladder — sustained bulk throughput through the top bucket, plus
    single-record p50/p99 through the micro-batching queue, BOTH read
    from the engine's own streaming latency histograms (the bench does
    not re-time what the engine already measures). One JSON line; on CPU
    the numbers are liveness, not perf claims."""
    import threading

    import jax
    from transmogrifai_tpu import FeatureBuilder
    from transmogrifai_tpu.automl import BinaryClassificationModelSelector
    from transmogrifai_tpu.automl.transmogrifier import transmogrify
    from transmogrifai_tpu.models.glm import OpLogisticRegression
    from transmogrifai_tpu.readers.readers import ListReader
    from transmogrifai_tpu.serve import MicroBatcher, ServingEngine
    from transmogrifai_tpu.stages.params import param_grid
    from transmogrifai_tpu.utils import tracing
    from transmogrifai_tpu.utils.metrics import collector
    from transmogrifai_tpu.workflow import Workflow

    backend = jax.default_backend()
    n_bulk = int(n_rows) if n_rows else (1_000_000 if backend == "tpu"
                                         else 100_000)
    d = 16
    out = {"metric": "serving", "backend": backend, "n_bulk": n_bulk,
           "n_cols": d}

    rng = np.random.default_rng(0)
    beta = rng.normal(size=d)

    def rec(i):
        x = rng.normal(size=d)
        return {**{f"x{j}": float(x[j]) for j in range(d)},
                "y": float(x @ beta > 0)}

    train_rows = [rec(i) for i in range(5000)]
    preds = [FeatureBuilder.Real(f"x{j}").extract(
        lambda r, j=j: r.get(f"x{j}")).as_predictor() for j in range(d)]
    fy = FeatureBuilder.RealNN("y").extract(
        lambda r: r.get("y")).as_response()
    # a derived jitted feature so the prewarm/compile accounting is real
    fsum = (preds[0] + preds[1]) + 1.0
    pred = BinaryClassificationModelSelector.with_train_validation_split(
        models_and_parameters=[(OpLogisticRegression(),
                                param_grid(reg_param=[0.01]))],
    ).set_input(fy, transmogrify(preds + [fsum])).get_output()
    with contextlib.redirect_stdout(io.StringIO()):
        model = Workflow().set_reader(ListReader(train_rows)) \
            .set_result_features(pred).train()

    collector.enable("bench_serving")
    try:
        engine = ServingEngine(model, max_batch=4096, strict_keys=False)
        t0 = time.perf_counter()
        warm = engine.prewarm()
        out["prewarm"] = {"wall_s": warm["wall_s"],
                          "buckets": warm["buckets"],
                          "compiles": warm["compiles"],
                          "cache_hits": warm["cache_hits"]}
        base_compiles = tracing.tracker.true_compiles

        # bulk sustained throughput through the bucket ladder (the
        # engine chunks into top-bucket batches internally)
        bulk = [{k: v for k, v in rec(i).items() if k != "y"}
                for i in range(n_bulk)]
        t0 = time.perf_counter()
        scored = engine.score_batch(bulk)
        # score_batch returns host dicts — already synced
        wall = time.perf_counter() - t0  # tmoglint: disable=TPU005
        assert len(scored) == n_bulk
        out["bulk"] = {"wall_s": round(wall, 3),
                       "rows_per_s": round(n_bulk / max(wall, 1e-9)),
                       "bucket": engine.max_batch}
        del scored

        # the COLUMNAR bulk lane (readers/streaming.score_stream over the
        # tileplane): producer-thread Dataset assembly overlapped with
        # device scoring — the sustained-throughput path for row floods,
        # vs the request-shaped per-record ladder above
        from transmogrifai_tpu.readers import ListStreamingReader
        from transmogrifai_tpu.readers.streaming import score_stream
        t0 = time.perf_counter()
        n2 = sum(len(b) for b in score_stream(
            model, ListStreamingReader(bulk, batch_size=8192),
            tile_rows=4096))
        wall = time.perf_counter() - t0  # tmoglint: disable=TPU005
        assert n2 == n_bulk
        out["bulk_stream"] = {"wall_s": round(wall, 3),
                              "rows_per_s": round(n_bulk / max(wall, 1e-9)),
                              "tile_rows": 4096}
        del bulk

        # single-record latency through the micro-batcher, engine's own
        # histograms as the source of truth
        batcher = MicroBatcher(engine, max_wait_ms=1.0, max_queue=4096)
        singles = [{k: v for k, v in rec(i).items() if k != "y"}
                   for i in range(400)]
        for r in singles[:200]:  # sequential: isolated-request latency
            batcher.submit(r)
        errs = []

        def fire(rs):
            for r in rs:
                try:
                    batcher.submit(r)
                except Exception as e:  # noqa: BLE001 - recorded below
                    errs.append(repr(e))

        ths = [threading.Thread(target=fire,
                                args=(singles[200 + 25 * k:
                                              200 + 25 * (k + 1)],))
               for k in range(8)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(120)
        batcher.shutdown(drain=True)
        m = engine.metrics()
        out["single_record"] = {
            "requests": m["requests"],
            "p50_ms": m["latency"]["total"]["p50_ms"],
            "p99_ms": m["latency"]["total"]["p99_ms"],
            "queue_wait_p95_ms": m["latency"]["queue_wait"]["p95_ms"],
            "device_score_p50_ms": m["latency"]["device_score"]["p50_ms"],
        }
        out["post_warmup_compiles"] = \
            tracing.tracker.true_compiles - base_compiles
        out["shed"] = m["shed"]
        if errs:
            out["errors"] = errs[:5]

        # segment decomposition (docs/observability.md "Request
        # tracing"): where a request's wall actually goes, from the
        # engine's own per-segment histograms + pad accounting — the
        # numbers the fleet /requests endpoint merges across replicas
        lat = m["latency"]
        eng_hists = engine.hist
        total_s = eng_hists["total"].total_seconds
        out["segments"] = {
            "queue_wait_p50_ms": lat["queue_wait"]["p50_ms"],
            "queue_wait_p99_ms": lat["queue_wait"]["p99_ms"],
            "batch_assemble_p50_ms": lat["batch_assemble"]["p50_ms"],
            "device_score_p50_ms": lat["device_score"]["p50_ms"],
            "device_score_p99_ms": lat["device_score"]["p99_ms"],
            # padding share of all device rows (bulk + singles)
            "pad_fraction_mean": round(
                m["pad_rows"] / max(m["bucket_rows"], 1), 4),
            # device wall (batch walls counted once) over summed
            # request walls: the device share of the e2e latency mass
            "device_share": round(
                eng_hists["device_score"].total_seconds
                / max(total_s, 1e-9), 4),
        }

        # request-tracing on/off A/B (the tail-sampling layer's
        # request-path overhead pin): the IDENTICAL single-record mix
        # through fresh batchers on the SAME warm engine, submit walls
        # timed identically into bench-local histograms — tracing adds
        # one slotted record + a few perf_counter reads per request,
        # and this shows what that costs at p99
        from transmogrifai_tpu.serve import ReqTracer
        from transmogrifai_tpu.utils.metrics import LatencyHistogram

        def _drive_mix(trace_tracer):
            b = MicroBatcher(engine, max_wait_ms=1.0, max_queue=4096)
            h = LatencyHistogram("ab")
            errs_ab = []

            def one(r):
                t0 = time.perf_counter()
                rt = (trace_tracer.start(None)
                      if trace_tracer is not None else None)
                try:
                    b.submit(dict(r), trace=rt)
                    wall = time.perf_counter() - t0
                    if trace_tracer is not None:
                        trace_tracer.finish(rt, wall, status=200)
                    h.record(wall)
                except Exception as e:  # noqa: BLE001 - recorded
                    errs_ab.append(repr(e))

            for r in singles[:200]:
                one(r)
            ths = [threading.Thread(
                target=lambda k=k: [one(r) for r in
                                    singles[200 + 25 * k:
                                            200 + 25 * (k + 1)]])
                for k in range(8)]
            for t in ths:
                t.start()
            for t in ths:
                t.join(120)
            b.shutdown(drain=True)
            return h, errs_ab

        h_off, e1 = _drive_mix(None)
        ab_tracer = ReqTracer("bench", sample_rate=0.05)
        h_on, e2 = _drive_mix(ab_tracer)
        j_off, j_on = h_off.to_json(), h_on.to_json()
        out["reqtrace_ab"] = {
            "p50_ms_off": j_off["p50_ms"], "p50_ms_on": j_on["p50_ms"],
            "p99_ms_off": j_off["p99_ms"], "p99_ms_on": j_on["p99_ms"],
            "p50_delta_ms": round(j_on["p50_ms"] - j_off["p50_ms"], 4),
            "p99_delta_ms": round(j_on["p99_ms"] - j_off["p99_ms"], 4),
            "traces": ab_tracer.n_traces,
            "kept": ab_tracer.n_kept,
        }
        if e1 or e2:
            out.setdefault("errors", []).extend((e1 + e2)[:5])

        # monitoring on/off A/B (docs/monitoring.md): the same single-
        # record + bulk traffic through a SECOND engine with the drift
        # monitor attached — p50/p99 delta and bulk rows/s overhead of
        # the per-bucket sketch program, sourced from the engines' own
        # histograms, so the drift tax rides the bench trajectory
        from transmogrifai_tpu.monitor import ServeMonitor, build_profile
        profile = build_profile(model)
        mon = ServeMonitor(profile, window_rows=4096, window_seconds=1e9)
        eng_on = ServingEngine(model, max_batch=4096, strict_keys=False,
                               monitor=mon)
        eng_on.prewarm()
        base_on = tracing.tracker.true_compiles
        bulk = [{k: v for k, v in rec(i).items() if k != "y"}
                for i in range(n_bulk)]
        t0 = time.perf_counter()
        assert len(eng_on.score_batch(bulk)) == n_bulk
        # score_batch returns host dicts — already synced
        wall_on = time.perf_counter() - t0  # tmoglint: disable=TPU005
        del bulk
        # IDENTICAL single-record mix to the baseline phase (200
        # sequential + 8x25 concurrent): the p50/p99 delta must isolate
        # the sketch overhead, not a different queue-wait profile
        b_on = MicroBatcher(eng_on, max_wait_ms=1.0, max_queue=4096)
        for r in singles[:200]:
            b_on.submit(dict(r))
        errs_on = []

        def fire_on(rs):
            for r in rs:
                try:
                    b_on.submit(dict(r))
                except Exception as e:  # noqa: BLE001 - recorded below
                    errs_on.append(repr(e))

        ths = [threading.Thread(target=fire_on,
                                args=(singles[200 + 25 * k:
                                              200 + 25 * (k + 1)],))
               for k in range(8)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(120)
        b_on.shutdown(drain=True)
        eng_on.finish_monitor()
        if errs_on:
            out.setdefault("errors", []).extend(errs_on[:5])
        m_on = eng_on.metrics()
        rows_s_off = out["bulk"]["rows_per_s"]
        rows_s_on = round(n_bulk / max(wall_on, 1e-9))
        out["monitor_ab"] = {
            "windows": m_on["monitor"]["windows"],
            "alerts": m_on["monitor"]["alerts_total"],
            "post_warmup_compiles_on": (tracing.tracker.true_compiles
                                        - base_on),
            "single_p50_ms_off": out["single_record"]["p50_ms"],
            "single_p50_ms_on": m_on["latency"]["total"]["p50_ms"],
            "single_p99_ms_off": out["single_record"]["p99_ms"],
            "single_p99_ms_on": m_on["latency"]["total"]["p99_ms"],
            "p50_delta_ms": round(m_on["latency"]["total"]["p50_ms"]
                                  - out["single_record"]["p50_ms"], 4),
            "p99_delta_ms": round(m_on["latency"]["total"]["p99_ms"]
                                  - out["single_record"]["p99_ms"], 4),
            "bulk_rows_per_s_off": rows_s_off,
            "bulk_rows_per_s_on": rows_s_on,
            "bulk_overhead_pct": round(
                100.0 * (rows_s_off - rows_s_on) / max(rows_s_off, 1),
                2),
        }
    finally:
        collector.finish()
        collector.disable()
    return out


# -- fleet scenario (--fleet) ------------------------------------------------

def fleet_bench(n_requests=None):
    """Scenario config for the serving fleet (fleet/, docs/fleet.md):
    the same tiny model served DIRECT (in-process engine + batcher),
    then behind the front router with 1 and with 2 real replica
    subprocesses — per-config rows/s and p50/p99 from the router's own
    histogram, plus the router-overhead decomposition (fleet p50 minus
    the replica-reported engine p50: HTTP hop + routing). Replica
    children run on the CPU backend (the overhead being measured is
    host-side); one JSON line."""
    import shutil
    import tempfile
    import threading

    from transmogrifai_tpu import FeatureBuilder
    from transmogrifai_tpu.automl import BinaryClassificationModelSelector
    from transmogrifai_tpu.automl.transmogrifier import transmogrify
    from transmogrifai_tpu.fleet import (HealthProber, Router, Supervisor)
    from transmogrifai_tpu.fleet.frontend import FleetFrontend
    from transmogrifai_tpu.models.glm import OpLogisticRegression
    from transmogrifai_tpu.readers.readers import ListReader
    from transmogrifai_tpu.serve import MicroBatcher, ServingEngine
    from transmogrifai_tpu.stages.params import param_grid
    from transmogrifai_tpu.workflow import Workflow

    n_req = int(n_requests) if n_requests else 300
    d = 8
    rng = np.random.default_rng(0)
    beta = rng.normal(size=d)

    def rec(i):
        x = rng.normal(size=d)
        return {**{f"x{j}": float(x[j]) for j in range(d)},
                "y": float(x @ beta > 0)}

    train_rows = [rec(i) for i in range(2000)]
    preds = [FeatureBuilder.Real(f"x{j}").extract(
        lambda r, j=j: r.get(f"x{j}")).as_predictor() for j in range(d)]
    fy = FeatureBuilder.RealNN("y").extract(
        lambda r: r.get("y")).as_response()
    fsum = (preds[0] + preds[1]) + 1.0
    pred = BinaryClassificationModelSelector.with_train_validation_split(
        models_and_parameters=[(OpLogisticRegression(),
                                param_grid(reg_param=[0.01]))],
    ).set_input(fy, transmogrify(preds + [fsum])).get_output()
    with contextlib.redirect_stdout(io.StringIO()):
        model = Workflow().set_reader(ListReader(train_rows)) \
            .set_result_features(pred).train()

    tmp = tempfile.mkdtemp(prefix="bench_fleet_")
    out = {"metric": "fleet", "n_requests": n_req}
    try:
        mdir = os.path.join(tmp, "model")
        model.save(mdir)
        records = [{k: v for k, v in rec(i).items() if k != "y"}
                   for i in range(n_req)]

        # DIRECT baseline: in-process engine + micro-batcher
        engine = ServingEngine(mdir, max_batch=16, strict_keys=False)
        engine.prewarm()
        batcher = MicroBatcher(engine, max_wait_ms=1.0, max_queue=4096)
        t0 = time.perf_counter()
        for r in records:
            batcher.submit(r)
        # submit blocks per record: wall is the sequential total
        wall = time.perf_counter() - t0  # tmoglint: disable=TPU005
        batcher.shutdown(drain=True)
        md = engine.metrics()
        out["direct"] = {
            "rows_per_s": round(n_req / max(wall, 1e-9)),
            "p50_ms": md["latency"]["total"]["p50_ms"],
            "p99_ms": md["latency"]["total"]["p99_ms"]}

        env = {"JAX_PLATFORMS": "cpu",
               "TMOG_COMPILE_CACHE_DIR": os.path.join(tmp, "cache"),
               "PYTHONPATH": os.path.dirname(os.path.abspath(__file__))}
        for n_replicas in (1, 2):
            lock = threading.RLock()
            sup = Supervisor(
                mdir, replicas=n_replicas, lock=lock,
                metrics_root=os.path.join(tmp, f"fleet{n_replicas}"),
                serve_args=["--max-batch", "16", "--max-wait-ms", "1",
                            "--monitor", "off"],
                env=env, startup_timeout_s=300.0)
            router = Router(lock, request_timeout=60.0)
            prober = None
            try:
                router.set_champions(sup.start())
                prober = HealthProber(router, interval_s=0.25).start()
                fe = FleetFrontend(sup, router)
                errs = []

                def fire(rs):
                    for r in rs:
                        try:
                            fe.submit(r)
                        except Exception as e:  # noqa: BLE001
                            errs.append(repr(e))

                chunk = max(n_req // 4, 1)
                t0 = time.perf_counter()
                ths = [threading.Thread(
                    target=fire, args=(records[k * chunk:
                                               (k + 1) * chunk],))
                    for k in range(4)]
                for t in ths:
                    t.start()
                for t in ths:
                    t.join(600)
                # fe.submit returns parsed responses: all synced
                wall = time.perf_counter() - t0  # tmoglint: disable=TPU005
                served = router.n_requests
                fm = fe.metrics()
                rj = router.hist.to_json()
                engine_p50 = fm["latency"].get("total", {}).get("p50_ms")
                cfg = {
                    "rows_per_s": round(served / max(wall, 1e-9)),
                    "p50_ms": rj["p50_ms"], "p99_ms": rj["p99_ms"],
                    "engine_p50_ms": engine_p50,
                    "router_overhead_p50_ms": (
                        round(rj["p50_ms"] - engine_p50, 4)
                        if engine_p50 is not None else None),
                    "retries": router.n_retries, "shed": router.n_shed,
                    "post_warmup_compiles": fm["post_warmup_compiles"],
                }
                if errs:
                    cfg["errors"] = errs[:5]
                out[f"replicas_{n_replicas}"] = cfg
            finally:
                if prober is not None:
                    prober.stop()
                sup.stop(router=router)
        r1 = out.get("replicas_1", {}).get("rows_per_s") or 1
        r2 = out.get("replicas_2", {}).get("rows_per_s")
        if r2:
            out["scaling_2_over_1"] = round(r2 / r1, 3)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# -- cpu-subprocess phases --------------------------------------------------
# The example flows and the host-transform-dominated wide bench measure
# host work; they run in CPU-pinned child processes (which never want
# the chip the parent holds) with hard timeouts so no phase can starve
# the headline metric.

#: the cold -> warm example pair's cache: a FIXED path (the path is part
#: of the cache key), emptied before the pair runs
EXAMPLE_CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 ".bench_xla_cache")


def run_subprocess_phase(args, timeout_s, compile_cache=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.abspath(__file__))
    # cold numbers must stay cold across bench reruns: an inherited or
    # default persistent compile cache would warm them invisibly, so
    # each phase gets an explicit cache dir ("0" disables)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["TMOG_COMPILE_CACHE_DIR"] = compile_cache or "0"
    r = subprocess.run([sys.executable, os.path.abspath(__file__)] + args,
                       capture_output=True, text=True, timeout=timeout_s,
                       env=env, cwd=os.path.dirname(os.path.abspath(__file__)))
    if r.returncode != 0:
        raise RuntimeError(f"phase {args} rc={r.returncode}: "
                           f"{r.stderr.strip()[-300:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def run_example(mod_name):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "examples"))
    sys.argv = sys.argv[:1]  # examples parse argv (CSV path arg)
    import importlib
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(io.StringIO()):
        mod = importlib.import_module(mod_name)
        mod.main()
    return time.perf_counter() - t0


#: reference checkout's canonical Titanic training file (headerless)
REF_TITANIC = ("/root/reference/helloworld/src/main/resources/"
               "TitanicDataset/TitanicPassengersTrainData.csv")
#: the reference's published holdout metrics for this flow
#: (/root/reference/README.md:84-96)
TITANIC_PUBLISHED = {"au_roc": 0.8822, "au_pr": 0.8225}


def titanic_quality():
    """Model-quality parity on the canonical real dataset: train the full
    OpTitanicSimple flow on the reference's own CSV and report holdout
    AuROC/AuPR against its published run — quality evidence that lands in
    the artifact on ANY backend, not just when the TPU sweep runs."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "examples"))
    import op_titanic_simple as t
    from transmogrifai_tpu.readers.readers import CSVReader

    with contextlib.redirect_stdout(io.StringIO()):
        wf, _ = t.build_workflow()
        model = wf.set_reader(
            CSVReader(REF_TITANIC, columns=t.PASSENGER_COLUMNS)).train()
    hold = model.selector_summary().holdout_evaluation
    out = {"holdout_au_roc": round(float(hold["au_roc"]), 4),
           "holdout_au_pr": round(float(hold["au_pr"]), 4)}
    for k, pub in TITANIC_PUBLISHED.items():
        out[f"published_{k}"] = pub
        out[f"delta_{k}"] = round(float(hold[k]) - pub, 4)
    return out


# -- main -------------------------------------------------------------------

def main():
    # subcommands executed in CPU child processes
    if len(sys.argv) > 2 and sys.argv[1] == "--wide":
        print(json.dumps(wide_transmogrify(int(sys.argv[2]))))
        return
    if len(sys.argv) > 2 and sys.argv[1] == "--example":
        print(json.dumps({"s": round(run_example(sys.argv[2]), 2)}))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--quality":
        print(json.dumps(titanic_quality()))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--hist-roofline":
        print(json.dumps(hist_roofline_bench(
            sys.argv[2] if len(sys.argv) > 2 else None)))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--stats-roofline":
        print(json.dumps(stats_roofline_bench(
            sys.argv[2] if len(sys.argv) > 2 else None)))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--streaming":
        print(json.dumps(streaming_bench(
            sys.argv[2] if len(sys.argv) > 2 else None)))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--ingest-ab":
        print(json.dumps(ingest_ab_bench(
            sys.argv[2] if len(sys.argv) > 2 else None)))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--multihost":
        res = multihost_bench(sys.argv[2] if len(sys.argv) > 2 else None)
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "MULTICHIP_r07.json")
        with open(path, "w") as fh:
            json.dump(res, fh, indent=2)
        print(json.dumps(res))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--serving":
        print(json.dumps(serving_bench(
            sys.argv[2] if len(sys.argv) > 2 else None)))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--fleet":
        print(json.dumps(fleet_bench(
            sys.argv[2] if len(sys.argv) > 2 else None)))
        return

    signal.signal(signal.SIGALRM, emit_and_exit)
    signal.alarm(max(int(BUDGET_S) - 30, 60))

    backend, kind = require_backend()
    errors = []
    RESULT["errors"] = errors
    # optional hierarchical trace of the whole bench (docs/observability.md):
    # BENCH_TRACE_DIR=<dir> writes bench_trace.json (Perfetto), the span-tree
    # stage-metrics JSON and a streaming events.jsonl there; inspect with
    # `python -m transmogrifai_tpu trace-report <dir>`
    trace_dir = TRACE_DIR
    if trace_dir:
        from transmogrifai_tpu.utils.metrics import collector as _coll
        os.makedirs(trace_dir, exist_ok=True)
        _coll.enable("bench")
        _coll.attach_event_log(os.path.join(trace_dir, "events.jsonl"))
        _coll.event("run_start", run_type="bench")
    if backend == "cpu":  # the caller pinned it: liveness sizes
        cfg = dict(CPU_CFG)
        sweep_dtype = None  # f32 — CPU matmuls have no bf16 units
    else:
        cfg = dict(TPU_CFG)
        import jax.numpy as jnp
        sweep_dtype = jnp.bfloat16
    RESULT.update(backend=backend, device_kind=kind, n_rows=cfg["n_rows"],
                  config=f"{cfg['glm_grid']}+{cfg['gbt_grid']} models x "
                         f"{cfg['folds']} folds")
    log(f"backend={backend} kind={kind} cfg={cfg}")
    persist_partial("backend_probe")

    # 1. headline sweep — data generated ON DEVICE
    import jax.numpy as jnp
    t0 = time.perf_counter()
    Xd, yd, _ = device_data(cfg["n_rows"], cfg["n_cols"],
                            cfg["folds"], sweep_dtype or jnp.float32)
    log(f"device data gen: {time.perf_counter() - t0:.2f}s")

    sweep = device_sweeps(Xd, yd, cfg, sweep_dtype, errors)
    device_s = max(sweep["glm_s"] + sweep["tree_s"], 1e-9)
    RESULT.update(metric=f"cv_sweep_{cfg['n_rows'] / 1e6:g}m_rows_"
                         f"{cfg['glm_grid'] + cfg['gbt_grid']}"
                         f"model_{cfg['folds']}fold_wall",
                  value=round(device_s, 3), sweep=sweep)
    if sweep.get("kernel_roofline"):
        RESULT["kernel_roofline"] = sweep["kernel_roofline"]
    persist_partial("device_sweeps")

    # 2. MFU — count only families whose device sweep actually ran, with
    # the FLOP model matched to the route that produced the timing and to
    # the sweep's own executed-pass telemetry
    glm_flops = (glm_flops_estimate(cfg, sweep.get("glm_route"),
                                    sweep.get("glm_telemetry"))
                 if sweep["glm_fits"] else 0.0)
    per_fit = (tree_flops_cost_analysis(cfg, sweep_dtype)
               if sweep["tree_fits"] else 0.0)
    tree_flops = per_fit * cfg["gbt_grid"] * cfg["folds"] \
        if sweep["tree_fits"] else 0.0
    from transmogrifai_tpu.utils.platform import device_spec
    spec = device_spec()  # None on the CPU; an unknown TPU kind raises
    peak = spec.bf16_flops if spec else None
    mfu = {"glm_tflops_analytic": round(glm_flops / 1e12, 2),
           "tree_tflops_xla": round(tree_flops / 1e12, 2),
           "achieved_tflops_per_s": round(
               (glm_flops + tree_flops) / device_s / 1e12, 2)}
    glm_warm = sweep.get("glm_warm_s")
    if glm_warm:
        mfu["glm_achieved_tflops_warm"] = round(
            glm_flops / glm_warm / 1e12, 2)
    if peak:
        mfu["peak_bf16_tflops"] = peak / 1e12
        mfu["mfu"] = round((glm_flops + tree_flops) / device_s / peak, 4)
        if glm_warm:
            mfu["glm_mfu_warm"] = round(glm_flops / glm_warm / peak, 4)
    mfu["glm_flop_model"] = (sweep.get("glm_route") or "n/a") + (
        ":measured_passes"
        if (sweep.get("glm_telemetry") or {}).get("lane_passes")
        else (":assumed_15it" if sweep.get("glm_route") else ""))
    RESULT["mfu"] = mfu
    persist_partial("mfu")

    # 3. measured host baseline (independent same-distribution twin; fixed
    # iteration counts make the cost data-independent)
    log(f"host twin gen {cfg['n_rows']} x {cfg['n_cols']}")
    Xh, yh = make_data(cfg["n_rows"], cfg["n_cols"], seed=1)
    rng = np.random.default_rng(7)
    fold = rng.integers(0, cfg["folds"], size=cfg["n_rows"])
    masks_h = np.stack([(fold != k).astype(np.float32)
                        for k in range(cfg["folds"])])
    glm_fit_s, glm_total = (baseline_glm(Xh, yh, masks_h, cfg)
                            if sweep["glm_fits"] else (0.0, 0.0))
    gbt_round_s, gbt_total = (baseline_gbt(Xh, yh, masks_h, cfg)
                              if sweep["tree_fits"] else (0.0, 0.0))
    # compare like with like: only count baseline families whose device
    # sweep actually ran (a family zeroed by a device failure would
    # otherwise inflate the ratio)
    base_total = (glm_total if sweep["glm_fits"] else 0.0) \
        + (gbt_total if sweep["tree_fits"] else 0.0)
    RESULT["baseline"] = {
        "total_s": round(base_total, 1),
        "glm_fit_s_measured": round(glm_fit_s, 2),
        "gbt_round_s_measured": round(gbt_round_s, 2),
        "method": ("sequential host numpy/BLAS (multithreaded); per-fit / "
                   "per-round cost measured at the FULL row count, totals "
                   "are cost x config x fold counts (configs within a "
                   "family are cost-identical). Generous vs Spark-local: "
                   "no JVM/DataFrame overhead counted."),
    }
    RESULT["vs_baseline"] = round(base_total / device_s, 2)
    RESULT["vs_baseline_8thread"] = round(base_total / 8 / device_s, 2)
    persist_partial("host_baseline")

    # 4. AuPR parity: device-trained vs host-trained winner coefficients
    # scored on the SAME host data
    try:
        if "reg_param" in sweep["best_grid"] and remaining() > 120:
            delta, a_host, a_dev = aupr_parity(
                Xh, yh, masks_h, sweep["best_grid"], Xd, yd)
            RESULT["sweep"]["au_pr_host_fit"] = round(a_host, 4)
            RESULT["sweep"]["au_pr_device_fit"] = round(a_dev, 4)
            RESULT["sweep"]["au_pr_parity_delta"] = round(delta, 4)
    except Exception as e:
        errors.append(f"parity: {type(e).__name__}: {e}")
    persist_partial("aupr_parity")
    del Xh, Xd  # free 2 x [n, d] before the host-heavy phases

    # 5. wide transmogrify + example configs, in CPU children
    configs = {}
    RESULT["configs"] = configs
    try:
        if remaining() > 240:
            configs["wide_transmogrify"] = run_subprocess_phase(
                ["--wide", str(cfg["wide_rows"])],
                min(remaining() - 120, 600))
        else:
            errors.append("wide_transmogrify skipped: budget")
    except Exception as e:
        errors.append(f"wide: {type(e).__name__}: {str(e)[:200]}")
    persist_partial("wide_transmogrify")
    import shutil
    cache_dir = EXAMPLE_CACHE_DIR
    shutil.rmtree(cache_dir, ignore_errors=True)
    for key, mod in (("titanic_s", "op_titanic_simple"),
                     ("iris_s", "op_iris"), ("boston_s", "op_boston")):
        try:
            if remaining() > 90:
                configs[key] = run_subprocess_phase(
                    ["--example", mod], min(remaining() - 40, 240),
                    compile_cache=cache_dir)["s"]
                log(f"{mod}: {configs[key]}s")
            else:
                errors.append(f"{mod} skipped: budget")
        except Exception as e:
            errors.append(f"{mod}: {type(e).__name__}: {str(e)[:200]}")
        persist_partial(f"example_{key}")
    # model-quality parity on the canonical real dataset (skipped when the
    # reference checkout is absent)
    try:
        if os.path.isfile(REF_TITANIC) and remaining() > 90:
            configs["titanic_quality"] = run_subprocess_phase(
                ["--quality"], min(remaining() - 40, 240),
                compile_cache=cache_dir)
            log(f"titanic quality: {configs['titanic_quality']}")
    except Exception as e:
        errors.append(f"titanic quality: {type(e).__name__}: {str(e)[:200]}")
    persist_partial("titanic_quality")
    # cold-vs-warm XLA-compile-cache effect: a SECOND cold process of the
    # same example pays tracing but loads compiles from the per-run cache
    # dir the first run just populated (a controlled pair — the user-level
    # cache is excluded from both)
    try:
        if "titanic_s" in configs and remaining() > 90:
            configs["titanic_s_cached_process"] = run_subprocess_phase(
                ["--example", "op_titanic_simple"],
                min(remaining() - 40, 240), compile_cache=cache_dir)["s"]
            log(f"titanic cached-process: "
                f"{configs['titanic_s_cached_process']}s")
    except Exception as e:
        errors.append(f"titanic warm: {type(e).__name__}: {str(e)[:200]}")
    persist_partial("example_warm")

    if trace_dir:
        from transmogrifai_tpu.utils.metrics import collector as _coll
        _coll.event("run_end", run_type="bench")
        save_trace_artifacts()
        _coll.detach_event_log()
        _coll.disable()
    if not errors:
        RESULT.pop("errors", None)
    signal.alarm(0)
    persist_partial("complete")
    print(json.dumps(RESULT), flush=True)
    if errors:
        sys.exit(1)  # a failed or skipped phase is a failed run


def _silence_broken_stdout():
    """Point stdout at devnull so the interpreter-shutdown flush of a
    broken pipe can't flip the exit status to 120 (python docs pattern)."""
    os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())


if __name__ == "__main__":
    try:
        main()
    except BrokenPipeError:
        _silence_broken_stdout()
        sys.exit(0)  # consumer closed stdout; nothing left to say
    except Exception as e:  # never exit without a parseable JSON line
        RESULT.setdefault("errors", []).append(
            f"{type(e).__name__}: {e}")
        persist_partial("fatal_error")
        save_trace_artifacts()
        try:
            print(json.dumps(RESULT), flush=True)
        except BrokenPipeError:
            _silence_broken_stdout()
        sys.exit(1)
