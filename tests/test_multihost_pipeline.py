"""Single-process unit coverage for the multi-host pipeline pieces.

The REAL cross-process behavior lives in test_multihost_2proc.py (slow:
it launches actual OS processes). Everything here runs in-process on the
8-virtual-device CPU mesh: the 1-process degradation contract (a mesh
that spans one process must take exactly the pre-pod code paths), the
row-layout/landing round trips, the file striping arithmetic, the
padded stream source, and the launch
helper's containment guarantees (which spawn trivial children that
never build a jax pod, so they stay fast)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.parallel import mesh as M
from transmogrifai_tpu.parallel import multihost as MH
from transmogrifai_tpu.parallel import tileplane as TP


@pytest.fixture
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual CPU devices")
    return M.make_mesh(4, 2)


# -- 1-process degradation: the pod landing paths must stay dormant ----------

def test_single_process_mesh_is_not_multiprocess(mesh):
    assert M.mesh_process_count(mesh) == 1
    assert not M.mesh_is_multiprocess(mesh)
    assert MH.process_count() == 1
    assert not MH.is_multiprocess()


def test_single_process_engines_never_touch_multihost_landing(
        mesh, monkeypatch, rng):
    """With a 1-process mesh the sharded engines must take the exact
    pre-pod code path: poison every multihost landing helper and run
    stats + GLM + trees end to end through the mesh entry points."""
    def bomb(*a, **k):
        raise AssertionError("multihost landing called on a 1-process mesh")

    monkeypatch.setattr(MH, "host_local_block", bomb)
    monkeypatch.setattr(MH, "replicated_global", bomb)
    monkeypatch.setattr(MH, "row_layout", bomb)

    n, d = 32, 3
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    w = np.ones(n, np.float32)
    masks = np.zeros((2, n), np.float32)
    masks[0, ::2] = 1.0
    masks[1, 1::2] = 1.0

    from transmogrifai_tpu.ops import glm_sweep as GS
    from transmogrifai_tpu.ops import stats_engine as SE
    from transmogrifai_tpu.ops import trees as T

    st, _ = SE.fused_stats_sharded(mesh, X, y, w, corr_matrix=True)
    ref, _ = SE.fused_stats(jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
                            corr_matrix=True)
    np.testing.assert_allclose(np.asarray(st.mean), np.asarray(ref.mean),
                               atol=1e-6)

    st2, _ = SE.stream_stats(TP.ArraySource(X, y, w, chunk_rows=8),
                             None, None, tile_rows=8, mesh=mesh)
    np.testing.assert_allclose(np.asarray(st2.mean), np.asarray(ref.mean),
                               atol=1e-6)

    regs = np.asarray([0.5], np.float32)
    alphas = np.asarray([0.0], np.float32)
    B, b0, *_ = GS.sweep_glm_squared_gram_sharded(mesh, X, y, w, masks,
                                                 regs, alphas)
    B1, b01, *_ = GS.sweep_glm_squared_gram(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(w), jnp.asarray(masks),
        jnp.asarray(regs), jnp.asarray(alphas))
    np.testing.assert_allclose(np.asarray(B), np.asarray(B1), atol=1e-5)

    edges = T.quantile_edges(jnp.asarray(X), 8)
    Xb = np.asarray(T.bin_matrix(jnp.asarray(X), edges))
    W = masks * w[None, :]
    t2, _, _ = T.fit_gbt_folds_sharded(
        jnp.asarray(Xb), jnp.asarray(y), jnp.asarray(W),
        jax.random.PRNGKey(0), mesh=mesh, n_rounds=2, depth=2, n_bins=8,
        learning_rate=0.3, loss="logistic")
    t1, _, _ = T.fit_gbt_folds(
        jnp.asarray(Xb), jnp.asarray(y), jnp.asarray(W),
        jax.random.PRNGKey(0), n_rounds=2, depth=2, n_bins=8,
        learning_rate=0.3, loss="logistic")
    assert np.array_equal(np.asarray(t2.feat), np.asarray(t1.feat))
    assert np.array_equal(np.asarray(t2.thresh), np.asarray(t1.thresh))


# -- row layout + landing round trips ----------------------------------------

def test_row_layout_single_process(mesh):
    layout = MH.row_layout(23, mesh)
    assert layout.counts == (23,)
    assert layout.n_real == 23
    # 1 process owns the whole 4-wide batch axis: pad to a multiple of 4
    assert layout.per_process == 24
    assert layout.n_padded == 24
    w = layout.local_weights()
    assert w.shape == (24,)
    assert w[:23].sum() == 23.0 and w[23:].sum() == 0.0


def test_row_layout_uneven_counts_weights():
    layout = MH.RowLayout(counts=(5, 3), per_process=6)
    assert layout.n_real == 8
    assert layout.n_padded == 12
    assert layout.local_count(0) == 5 and layout.local_count(1) == 3
    np.testing.assert_array_equal(
        layout.local_weights(1),
        np.asarray([1, 1, 1, 0, 0, 0], np.float32))


def test_host_local_block_round_trip(mesh, rng):
    n, d = 23, 3
    X = rng.normal(size=(n, d)).astype(np.float32)
    layout = MH.row_layout(n, mesh)
    blk = MH.host_local_block(X, mesh, layout)
    assert blk.shape == (layout.n_padded, d)
    got = np.asarray(blk)
    np.testing.assert_array_equal(got[:n], X)
    assert np.all(got[n:] == 0.0)          # constant zero padding
    np.testing.assert_array_equal(MH.fetch_local(blk)[:n], X)

    # pad_value=None repeats the last real row (tree-binning semantics)
    blk2 = np.asarray(MH.host_local_block(X, mesh, layout,
                                          pad_value=None))
    np.testing.assert_array_equal(blk2[n:],
                                  np.repeat(X[-1:], layout.n_padded - n,
                                            axis=0))

    # axis=1: the fold-mask [F, n] layout, padded along columns
    masks = rng.random((2, n)).astype(np.float32)
    blk3 = MH.host_local_block(masks, mesh, layout, pad_value=1.0, axis=1)
    assert blk3.shape == (2, layout.n_padded)
    got3 = np.asarray(blk3)
    np.testing.assert_array_equal(got3[:, :n], masks)
    assert np.all(got3[:, n:] == 1.0)
    np.testing.assert_array_equal(MH.fetch_local(blk3, axis=1)[:, :n],
                                  masks)

    # oversized local block is a hard error, not silent truncation
    with pytest.raises(ValueError):
        MH.host_local_block(np.zeros((layout.per_process + 1, d),
                                     np.float32), mesh, layout)


def test_replicated_global_round_trip(mesh):
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    g = MH.replicated_global(x, mesh)
    np.testing.assert_array_equal(np.asarray(g), x)
    # scalars land as 0-d arrays usable as traced jit operands
    s = MH.replicated_global(np.asarray(7, np.int32), mesh)
    assert int(s) == 7


def test_fetch_local_never_allgathers(mesh, monkeypatch, rng):
    """fetch_local must stay on-host even at N processes: poison the
    allgather and pretend the process count is 2 — the shard walk alone
    must reproduce this host's rows (on a single host, ALL rows)."""
    n, d = 24, 3
    X = rng.normal(size=(n, d)).astype(np.float32)
    blk = jax.device_put(X, M.batch_sharding(mesh, ndim=2))

    from jax.experimental import multihost_utils

    def bomb(*a, **k):
        raise AssertionError("fetch_local crossed a process boundary")

    monkeypatch.setattr(multihost_utils, "process_allgather", bomb)
    monkeypatch.setattr(MH, "process_count", lambda: 2)
    np.testing.assert_array_equal(MH.fetch_local(blk), X)
    # model-axis replicas dedupe by row offset: 4 batch shards x 2
    # model replicas must yield 24 rows once, not 48
    assert MH.fetch_local(blk).shape == (n, d)
    # axis=1 layout ([F, n] fold masks / margins)
    masks = rng.random((2, n)).astype(np.float32)
    blk2 = jax.device_put(
        masks, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, M.BATCH_AXIS)))
    np.testing.assert_array_equal(MH.fetch_local(blk2, axis=1), masks)
    # plain numpy passes through untouched
    np.testing.assert_array_equal(MH.fetch_local(X), X)


# -- file striping -----------------------------------------------------------

def test_stripe_paths_partition_and_order():
    paths = [f"/data/part-{i:03d}.avro" for i in range(7)]
    stripes = [MH.stripe_paths(paths, index=i, count=3) for i in range(3)]
    # a partition: disjoint, complete, in order
    flat = [p for s in stripes for p in s]
    assert flat == paths                   # contiguous striping preserves
    assert [len(s) for s in stripes] == [3, 2, 2]  # remainder spreads left

    # single process: identity
    assert MH.stripe_paths(paths, index=0, count=1) == paths
    # more processes than files: tail processes get empty stripes
    stripes = [MH.stripe_paths(paths[:2], index=i, count=3)
               for i in range(3)]
    assert [len(s) for s in stripes] == [1, 1, 0]


# -- the padded stream source ------------------------------------------------

def test_padded_source_pads_to_target(rng):
    n, d = 11, 3
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.random(n).astype(np.float32)
    w = np.ones(n, np.float32)
    src = TP.PaddedSource(TP.ArraySource(X, y, w, chunk_rows=4), 16)
    assert src.n_rows == 16
    chunks = list(src.chunks())
    got = np.concatenate([c[0] for c in chunks])
    np.testing.assert_array_equal(got[:n], X)
    assert got.shape == (16, d)
    assert np.all(got[n:] == 0.0)          # zero rows, zero weights
    wg = np.concatenate([c[2] for c in chunks])
    assert np.all(wg[n:] == 0.0)
    # dtypes/shapes of the pad chunk mirror the real chunks
    assert chunks[-1][0].dtype == X.dtype
    # peek passes through to the inner source
    assert src.peek()[0].shape[1] == d


def test_padded_source_rejects_overflow_and_empty(rng):
    X = rng.normal(size=(5, 2)).astype(np.float32)
    y = np.zeros(5, np.float32)
    w = np.ones(5, np.float32)
    over = TP.PaddedSource(TP.ArraySource(X, y, w, chunk_rows=5), 3)
    with pytest.raises(ValueError):
        list(over.chunks())
    empty = TP.PaddedSource(
        TP.ArraySource(X[:0], y[:0], w[:0], chunk_rows=5), 4)
    with pytest.raises(ValueError):
        list(empty.chunks())


def test_stream_stats_multiprocess_requires_known_rows(mesh, monkeypatch,
                                                       rng):
    """The pod stream path sizes its uniform tile plan from the local
    stripe's row count — a countless source must fail loudly, not hang
    the pod in a mismatched collective."""
    from transmogrifai_tpu.ops import stats_engine as SE

    monkeypatch.setattr(M, "mesh_process_count", lambda m: 2)

    def gen():
        yield (rng.normal(size=(4, 3)).astype(np.float32),
               np.zeros(4, np.float32), np.ones(4, np.float32))

    src = TP.IterSource(gen, n_rows=None)
    with pytest.raises(ValueError, match="n_rows"):
        SE.stream_stats(src, None, None, tile_rows=4, mesh=mesh)


def test_run_tileplane_multiprocess_shardings_run_synchronously(
        monkeypatch):
    """A sharding that spans processes must never reach the producer
    thread (its landing races the step's gloo collectives): poison the
    threaded producer and drive a pass with a fake non-addressable
    sharding — the synchronous path handles it, the producer never
    runs."""
    def bomb(*a, **k):
        raise AssertionError("threaded producer used for a pod sharding")

    monkeypatch.setattr(TP, "_producer", bomb)
    monkeypatch.setattr(TP, "_device_put_tile",
                        lambda tile, shardings: tuple(
                            jnp.asarray(a) for a in tile))

    class FakePodSharding:
        is_fully_addressable = False

    n, d = 8, 2
    X = np.arange(n * d, dtype=np.float32).reshape(n, d)
    y = np.zeros(n, np.float32)
    w = np.ones(n, np.float32)
    carry, _ = TP.run_tileplane(
        TP.ArraySource(X, y, w, chunk_rows=4),
        lambda carry, xt, yt, wt: carry + xt.sum(),
        jnp.asarray(0.0), tile_rows=4,
        shardings=(FakePodSharding(),) * 3)
    assert float(carry) == float(X.sum())


# -- launch helper containment (no jax in the children: fast) ----------------

def test_launch_timeout_kills_and_reaps_everyone():
    from transmogrifai_tpu.parallel.launch import launch_local_pod

    pod = launch_local_pod("import time; time.sleep(600)", n_procs=2,
                           devices_per_proc=1, timeout=3.0)
    assert not pod.ok
    assert "timeout" in pod.error
    assert pod.wall_s < 60.0
    for c in pod.children:
        assert c.returncode is not None    # reaped, not abandoned
        assert c.killed


def test_launch_dead_coordinator_contains_stragglers():
    """Rank 0 (the coordinator) dies before serving; the straggler would
    block in distributed init forever — the launcher must grace-kill it
    and report the root-cause child."""
    from transmogrifai_tpu.parallel.launch import launch_local_pod

    payload = (
        "import os, sys, time\n"
        "if os.environ['TMOG_PROC_ID'] == '0':\n"
        "    sys.exit(3)\n"
        "time.sleep(600)\n")
    pod = launch_local_pod(payload, n_procs=2, devices_per_proc=1,
                           timeout=120.0, grace_s=1.0)
    assert not pod.ok
    assert "child 0" in pod.error and "rc=3" in pod.error
    assert pod.wall_s < 60.0               # grace, not the full timeout
    for c in pod.children:
        assert c.returncode is not None
    assert pod.children[1].killed


def test_launch_chaos_hook_kills_target_on_marker():
    from transmogrifai_tpu.parallel.launch import launch_local_pod

    payload = (
        "import os, sys, time\n"
        "print('ROUND 1 done', flush=True)\n"
        "time.sleep(600)\n")
    pod = launch_local_pod(payload, n_procs=2, devices_per_proc=1,
                           timeout=120.0, grace_s=1.0,
                           kill_on="ROUND 1 done", kill_target=1)
    assert not pod.ok
    assert "chaos-killed" in pod.error
    assert pod.children[1].killed
    assert pod.wall_s < 60.0


def test_launch_timeout_error_names_straggler_from_heartbeats(tmp_path):
    """With a trace dir the reaper is not blind: the timeout error names
    the rank whose heartbeat shows it still computing while its peer is
    parked in a collective — rank, round, phase, beat age. The children
    write flight-recorder heartbeats with stdlib json only (the
    launcher's env plumbing is what's under test, not the recorder —
    tests/test_podtrace.py owns that)."""
    from transmogrifai_tpu.parallel.launch import launch_local_pod

    payload = (
        "import json, os, time\n"
        "assert os.environ['TMOG_PODTRACE'] == '1'\n"
        "root = os.environ['TMOG_PODTRACE_DIR']\n"
        "rank = os.environ['TMOG_PROC_ID']\n"
        "d = os.path.join(root, 'rank-' + rank)\n"
        "os.makedirs(d, exist_ok=True)\n"
        "phase = ('collective:glm_round' if rank == '0'\n"
        "         else 'compute:glm_prep')\n"
        "with open(os.path.join(d, 'heartbeat.jsonl'), 'a') as fh:\n"
        "    fh.write(json.dumps({'round': 4, 'phase': phase,\n"
        "                         'mono': time.monotonic(),\n"
        "                         'ts': time.time()}) + '\\n')\n"
        "time.sleep(600)\n")
    pod = launch_local_pod(payload, n_procs=2, devices_per_proc=1,
                           timeout=4.0, trace_dir=str(tmp_path))
    assert not pod.ok and "timeout" in pod.error
    assert "likely straggler: rank 1" in pod.error
    assert "round 4" in pod.error
    assert "compute:glm_prep" in pod.error


def test_launch_debug_sleep_env_targets_one_rank(tmp_path):
    """debug_sleep_ms reaches ONLY the target rank's environment —
    the chaos-straggler injection the ci.sh pod stage asserts on."""
    from transmogrifai_tpu.parallel.launch import launch_local_pod

    payload = (
        "import json, os\n"
        "print('RESULT|' + json.dumps(\n"
        "    {'rank': os.environ['TMOG_PROC_ID'],\n"
        "     'sleep': os.environ.get('TMOG_PODTRACE_DEBUG_SLEEP_MS')}),\n"
        "    flush=True)\n")
    pod = launch_local_pod(payload, n_procs=2, devices_per_proc=1,
                           timeout=60.0, trace_dir=str(tmp_path),
                           debug_sleep_ms=150, debug_sleep_target=1)
    assert pod.ok, pod.error
    by_rank = {r["rank"]: r["sleep"]
               for r in (pod.result(i) for i in range(2))}
    assert by_rank == {"0": None, "1": "150"}


def test_pod_env_shapes_child_topology():
    from transmogrifai_tpu.parallel.launch import pod_env

    env = pod_env(12345, 1, 2, 4, {"TMOG_EXTRA": "x"})
    assert env["TMOG_MULTIHOST"] == "1"
    assert env["TMOG_COORD_ADDR"] == "127.0.0.1:12345"
    assert env["TMOG_PROC_COUNT"] == "2"
    assert env["TMOG_PROC_ID"] == "1"
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    assert env["TMOG_EXTRA"] == "x"
    # stale JAX_* topology spellings must not leak into the child
    for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
              "JAX_PROCESS_ID"):
        assert k not in env
