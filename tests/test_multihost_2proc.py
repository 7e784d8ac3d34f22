"""REAL two-process jax.distributed runs (CPU backend, localhost
coordinator): the multi-host story executed across process boundaries,
not just the single-process degradation the unit tests cover.

Both tests launch through parallel/launch.launch_local_pod — the same
harness ci.sh's multihost smoke and bench.py --multihost use — so the
children get the full pod environment (gloo collectives flag, virtual
device count, TMOG_* topology knobs) and deadline/containment for free.

`test_two_process_distributed_matches_numpy` keeps the original story: a
hand-rolled Gram + logistic fit whose psums cross the process boundary,
checked against single-process numpy.

`test_two_process_fit_pipeline_parity` is the PR's acceptance run: the
ACTUAL engines (fused + streamed stats, GLM Gram/IRLS sweeps, sharded
fold-fused GBT) on an UNEVEN contiguous row split (12 + 11), each child
holding only its stripe, every merge a cross-host collective. Tree
structure and integer histogram counts must match the single-device
reference EXACTLY; float statistics to documented f32-psum tolerance.
"""
import numpy as np
import pytest

from transmogrifai_tpu.parallel.launch import launch_local_pod

_GRAM_CHILD = r"""
import json, os
import numpy as np
from transmogrifai_tpu.parallel import multihost as MH

MH.initialize()
import jax
assert jax.process_count() == 2, jax.process_count()
mesh = MH.global_mesh(n_model=1)

n, d = 50, 4  # 50 rows over 8 devices -> padded to 56, tail masked
rng = np.random.default_rng(0)
X_global = rng.normal(size=(n, d)).astype(np.float32)
y_global = (rng.uniform(size=n) < 0.5).astype(np.float32)

start, stop = MH.process_row_range(n)
X = MH.host_local_rows(X_global[start:stop], mesh, n)
y = MH.host_local_rows(y_global[start:stop], mesh, n)
w = MH.host_local_rows(
    np.ones(stop - start, np.float32), mesh, n)  # pad rows -> weight 0

@jax.jit
def gram_and_fit(X, y, w):
    g = (X * w[:, None]).T @ X          # psum over the process boundary
    from transmogrifai_tpu.ops.glm import fit_logistic
    beta, b0 = fit_logistic(X, y, w, 0.1, 0.0)
    return g, beta, b0

with mesh:
    g, beta, b0 = gram_and_fit(X, y, w)
    out = dict(pid=jax.process_index(), ospid=os.getpid(),
               rows=[int(start), int(stop)],
               gram=np.asarray(g).tolist(),
               beta=np.asarray(beta).tolist(), b0=float(b0))
print("RESULT|" + json.dumps(out), flush=True)
MH.finalize()
"""

# The whole fit pipeline: each child holds ONLY its contiguous stripe of
# the 23-row dataset (12 + 11 — deliberately uneven so the row_layout
# padding path is exercised), and every engine's merge is a pod psum.
_PIPELINE_CHILD = r"""
import json, os
import numpy as np
from transmogrifai_tpu.parallel import multihost as MH

MH.initialize()
import jax, jax.numpy as jnp
pc = jax.process_count(); pid = jax.process_index()
mesh = MH.global_mesh(n_model=2)

rng = np.random.default_rng(0)
n, d = 23, 3
X = rng.normal(size=(n, d)).astype(np.float32)
# structured label: tree split gains well separated from zero, so the
# psum reduction order cannot flip a gain>0 guard (degenerate gain==0
# nodes are order-sensitive by construction — docs/performance.md)
y = (X[:, 0] + 0.5 * X[:, 1] + 0.1 * rng.normal(size=n) > 0
     ).astype(np.float32)
w = (0.5 + rng.random(n)).astype(np.float32)
masks = np.zeros((2, n), np.float32)
masks[0, ::2] = 1.0
masks[1, 1::2] = 1.0
bounds = [0, 12, n] if pc == 2 else [0, n]
lo, hi = bounds[pid], bounds[pid + 1]

def err(a, b):
    a = np.asarray(a, np.float64); b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b))) if a.size else 0.0

out = {"pc": pc, "pid": pid, "ospid": os.getpid()}

from transmogrifai_tpu.ops import stats_engine as SE
st, _ = SE.fused_stats_sharded(mesh, X[lo:hi], y[lo:hi], w[lo:hi],
                               corr_matrix=True)
ref, _ = SE.fused_stats(jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
                        corr_matrix=True)
out["stats_mean_err"] = err(st.mean, ref.mean)
out["stats_m2_err"] = err(st.m2, ref.m2)
out["stats_cnt_err"] = err(st.cnt, ref.cnt)

# integer histogram counts (unit weights): EXACT equality required —
# integer sums are reduction-order invariant below 2**24
ones = np.ones(n, np.float32)
lo_v = np.full(d, -3.0, np.float32); hi_v = np.full(d, 3.0, np.float32)
sth, _ = SE.fused_stats_sharded(mesh, X[lo:hi], y[lo:hi], ones[lo:hi],
                                lo=lo_v, hi=hi_v, bins=8)
refh, _ = SE.fused_stats(jnp.asarray(X), jnp.asarray(y),
                         jnp.asarray(ones), lo=jnp.asarray(lo_v),
                         hi=jnp.asarray(hi_v), bins=8)
out["hist_err"] = err(sth.hist, refh.hist)
out["hist_total"] = float(np.sum(np.asarray(sth.hist)))

from transmogrifai_tpu.parallel import tileplane as TP
src = TP.ArraySource(X[lo:hi], y[lo:hi], w[lo:hi], chunk_rows=5)
st2, _ = SE.stream_stats(src, None, None, tile_rows=8, mesh=mesh)
out["stream_mean_err"] = err(st2.mean, ref.mean)
out["stream_cnt_err"] = err(st2.cnt, ref.cnt)

from transmogrifai_tpu.ops import glm_sweep as GS
regs = np.asarray([0.1, 1.0], np.float32)
alphas = np.asarray([0.0, 0.5], np.float32)
B2, b02, *_ = GS.sweep_glm_squared_gram_sharded(
    mesh, X[lo:hi], y[lo:hi], w[lo:hi], masks[:, lo:hi], regs, alphas)
B1, b01, *_ = GS.sweep_glm_squared_gram(
    jnp.asarray(X), jnp.asarray(y), jnp.asarray(w), jnp.asarray(masks),
    jnp.asarray(regs), jnp.asarray(alphas))
out["glm_gram_err"] = max(err(B2, B1), err(b02, b01))
B4, b04, _ = GS.sweep_glm_streamed_rounds(
    X[lo:hi], y[lo:hi], w[lo:hi], masks[:, lo:hi], regs, alphas,
    loss="logistic", mesh=mesh)
B3, b03, _ = GS.sweep_glm_streamed_rounds(
    jnp.asarray(X), jnp.asarray(y), jnp.asarray(w), jnp.asarray(masks),
    regs, alphas, loss="logistic")
out["glm_irls_err"] = max(err(B4, B3), err(b04, b03))

from transmogrifai_tpu.ops import trees as T
edges = T.quantile_edges(jnp.asarray(X), 16)
Xb = np.asarray(T.bin_matrix(jnp.asarray(X), edges))
W = masks * w[None, :]
key = jax.random.PRNGKey(0)
trees2, base2, marg2 = T.fit_gbt_folds_sharded(
    Xb[lo:hi], y[lo:hi], W[:, lo:hi], key, mesh=mesh, n_rounds=3,
    depth=2, n_bins=16, learning_rate=0.3, loss="logistic")
trees1, base1, marg1 = T.fit_gbt_folds(
    jnp.asarray(Xb), jnp.asarray(y), jnp.asarray(W), key, n_rounds=3,
    depth=2, n_bins=16, learning_rate=0.3, loss="logistic")
out["tree_feat_exact"] = bool(
    np.array_equal(np.asarray(trees2.feat), np.asarray(trees1.feat)))
out["tree_thresh_exact"] = bool(
    np.array_equal(np.asarray(trees2.thresh), np.asarray(trees1.thresh)))
out["tree_leaf_err"] = err(trees2.leaf, trees1.leaf)
out["tree_margin_err"] = err(marg2, np.asarray(marg1)[:, lo:hi])
out["base_err"] = err(base2, base1)

print("RESULT|" + json.dumps(out), flush=True)
MH.finalize()
"""


# some jaxlib builds ship a CPU client without cross-process collective
# support at all — the children die in the first psum. That is an
# environment limit, not a repo regression: skip (the single-process
# mesh degradation tests still run everywhere). The message wording
# has drifted across jaxlib releases, so match a family of known
# phrasings rather than one exact string — a new wording must still
# SKIP here, not fail the tier.
_BACKEND_UNSUPPORTED_MARKERS = (
    # <= 0.4.x wording (exact message this test originally pinned)
    "Multiprocess computations aren't implemented on the CPU backend",
    # variants observed across releases / XLA error surfaces
    "not implemented on the CPU backend",
    "not supported on the CPU backend",
    "multi-process computations are not supported",
    "cross-host collectives are not implemented",
    "UNIMPLEMENTED: CollectivePermute",
    "UNIMPLEMENTED: AllReduce",
)


def _backend_unsupported(pod) -> bool:
    text = " ".join(c.stderr_tail for c in pod.children).lower()
    return any(m.lower() in text for m in _BACKEND_UNSUPPORTED_MARKERS)


def _run_pod(payload, **kw):
    """launch_local_pod with one retry on a fresh port (free_port closes
    its probe socket before the coordinator binds, so a busy host can
    steal the port in the window) and the backend-unsupported skip."""
    kw.setdefault("n_procs", 2)
    kw.setdefault("devices_per_proc", 4)
    kw.setdefault("timeout", 420.0)
    pod = launch_local_pod(payload, **kw)
    if not pod.ok and not _backend_unsupported(pod):
        pod = launch_local_pod(payload, **kw)
    if not pod.ok and _backend_unsupported(pod):
        pytest.skip("this jaxlib's CPU backend does not implement "
                    "multiprocess computations (environment limit, "
                    "not a repo regression): " + (pod.error or "")[:200])
    assert pod.ok, pod.error
    outs = [pod.result(i) for i in range(kw["n_procs"])]
    assert all(o is not None for o in outs), \
        "child exited 0 without a RESULT| payload"
    # the pod really was two OS processes, each claiming its own rank
    assert len({o["ospid"] for o in outs}) == kw["n_procs"]
    assert sorted(o["pid"] for o in outs) == list(range(kw["n_procs"]))
    return outs


@pytest.mark.slow
def test_two_process_distributed_matches_numpy():
    outs = _run_pod(_GRAM_CHILD, n_procs=2, devices_per_proc=4)

    # both processes computed the SAME replicated results
    np.testing.assert_allclose(outs[0]["gram"], outs[1]["gram"], rtol=1e-5)
    np.testing.assert_allclose(outs[0]["beta"], outs[1]["beta"], rtol=1e-5)

    # and they match single-process numpy ground truth
    n, d = 50, 4
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    np.testing.assert_allclose(outs[0]["gram"], X.T @ X, rtol=1e-4)

    # row ranges partition the real rows exactly (process 0 first)
    outs.sort(key=lambda o: o["pid"])
    assert outs[0]["rows"][0] == 0
    assert outs[0]["rows"][1] == outs[1]["rows"][0]
    assert outs[1]["rows"][1] == n

    # beta sanity vs an unsharded device fit
    from transmogrifai_tpu.ops.glm import fit_logistic
    import jax.numpy as jnp
    beta1, b01 = fit_logistic(jnp.asarray(X), jnp.asarray(y),
                              jnp.ones(n, jnp.float32), 0.1, 0.0)
    np.testing.assert_allclose(outs[0]["beta"], np.asarray(beta1),
                               atol=2e-3)


@pytest.mark.slow
def test_two_process_fit_pipeline_parity():
    """Acceptance run: every fit engine on a real 2-process pod, uneven
    row stripes, vs in-child single-device full-data references."""
    outs = _run_pod(_PIPELINE_CHILD, n_procs=2, devices_per_proc=4)
    for o in outs:
        assert o["pc"] == 2

    # SPMD: both ranks fetched the SAME replicated global results, so
    # every error magnitude must agree bit-for-bit across ranks
    a, b = sorted(outs, key=lambda o: o["pid"])
    for k in ("stats_mean_err", "stats_m2_err", "stats_cnt_err",
              "hist_err", "hist_total", "stream_mean_err",
              "stream_cnt_err", "glm_gram_err", "glm_irls_err",
              "tree_leaf_err", "base_err", "tree_feat_exact",
              "tree_thresh_exact"):
        assert a[k] == b[k], (k, a[k], b[k])

    for o in outs:
        # integer accumulations: exact (reduction-order invariant)
        assert o["hist_err"] == 0.0, o
        assert o["hist_total"] == 23.0 * 3, o  # every (row, col) binned
        assert o["stats_cnt_err"] == 0.0, o
        assert o["stream_cnt_err"] == 0.0, o
        # tree STRUCTURE: exactly the single-device trees
        assert o["tree_feat_exact"], o
        assert o["tree_thresh_exact"], o
        # float sufficient statistics: f32 psum-order tolerance
        assert o["stats_mean_err"] < 1e-6, o
        assert o["stats_m2_err"] < 1e-4, o
        assert o["stream_mean_err"] < 1e-6, o
        assert o["glm_gram_err"] < 1e-4, o
        assert o["glm_irls_err"] < 1e-4, o
        assert o["tree_leaf_err"] < 1e-5, o
        assert o["tree_margin_err"] < 1e-5, o
        assert o["base_err"] < 1e-5, o
