"""Batched multi-(fold x lane) histogram pipeline: property tests.

The fused sweep reads the binned matrix ONCE per level for every
(fold x config) lane (ops/pallas_hist.hist_folds / route_hist); its
correctness contract is that batching must not change a result:

  1. the batched kernel == per-fold hist_pallas calls BIT-FOR-BIT in f32
     (each lane's contraction rows are disjoint — fusion is pure layout),
     across odd shapes: rows not divisible by the tile, n_slots 1,
     single fold, single lane;
  2. in bf16 contraction mode the batched and per-fold legs quantize
     identically (equal to each other bit-for-bit) and stay within the
     established 1e-3-AuPR-impact tolerance of the f32 leg;
  3. the fused route+hist pass == the separate route_pallas pass + the
     plain histogram of the surviving left children, bit-for-bit;
  4. the pure-jnp CPU fallback matches interpret-mode pallas up to f32
     summation order;
  4b. the routing and lookup kernels are sized by the level's own node
     count (PH.node_rows) and stay EXACT at every size: routed ids
     bitwise equal to the gather twin from 1 node to 2 048 with feature
     ids past 300 and thresholds up to 256, looked-up values bitwise
     equal to the gather for full-mantissa f32 tables 2 to 4 096 wide;
  5. the planner (plan_lane_chunk) honors every budget and the CPU
     fallback smoke runs on a tiny matrix — the tier-1 liveness check
     ci.sh exercises on every run (no TPU required).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from transmogrifai_tpu.ops import pallas_hist as PH


def _lanes_inputs(n, f, b, folds, n_slots, seed=0, channels=2,
                  integral=False):
    """integral=True draws small-integer payloads: every partial sum is
    exactly representable in f32 (and bf16), so equality assertions stay
    BIT-FOR-BIT no matter how the backend's gemm blocking associates the
    reduction — what's under test is lane/slot layout, not the backend's
    f32 rounding at different contraction shapes."""
    rng = np.random.default_rng(seed)
    Xb_t = jnp.asarray(rng.integers(0, b, size=(f, n)), jnp.int8)
    pay = (rng.integers(-8, 9, size=(folds * channels, n)) if integral
           else rng.normal(size=(folds * channels, n)))
    pay = jnp.asarray(pay, jnp.float32)
    # slot == n_slots exercises the dropped-row encoding in every shape
    slot = jnp.asarray(rng.integers(0, n_slots + 1, size=(folds, n)),
                       jnp.float32)
    return Xb_t, pay, slot


# odd shapes on purpose: ragged rows (n % blk != 0, multi-grid-step),
# n_slots 1, single fold, single lane, and a multi-lane fold-major stack
ODD_SHAPES = [
    pytest.param(PH._BLK + 17, 5, 8, 3, 4, id="ragged-rows"),
    pytest.param(257, 3, 4, 1, 1, id="single-fold-single-slot"),
    pytest.param(515, 6, 8, 5, 1, id="n-slots-1"),
    pytest.param(64, 2, 4, 1, 2, id="single-lane-tiny"),
    pytest.param(130, 4, 6, 6, 2, id="fold-x-config-lanes"),
]


@pytest.mark.parametrize("n,f,b,folds,n_slots", ODD_SHAPES)
def test_batched_matches_per_fold_f32_bitwise(n, f, b, folds, n_slots):
    Xb_t, pay, slot = _lanes_inputs(n, f, b, folds, n_slots,
                                    integral=True)
    C = pay.shape[0] // folds
    fused = PH.hist_pallas(Xb_t, pay, slot, n_slots=n_slots, n_bins=b,
                           interpret=True)
    for k in range(folds):
        one = PH.hist_pallas(Xb_t, pay[C * k:C * (k + 1)], slot[k:k + 1],
                             n_slots=n_slots, n_bins=b, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(fused[k * n_slots * C:(k + 1) * n_slots * C]),
            np.asarray(one))


@pytest.mark.parametrize("n,f,b,folds,n_slots", ODD_SHAPES)
def test_batched_matches_per_fold_f32_continuous(n, f, b, folds, n_slots):
    """Continuous payloads: same parity up to the backend's f32 gemm
    association (catches accumulation-scale bugs the exact-integer
    construction can't)."""
    Xb_t, pay, slot = _lanes_inputs(n, f, b, folds, n_slots)
    C = pay.shape[0] // folds
    fused = PH.hist_pallas(Xb_t, pay, slot, n_slots=n_slots, n_bins=b,
                           interpret=True)
    for k in range(folds):
        one = PH.hist_pallas(Xb_t, pay[C * k:C * (k + 1)], slot[k:k + 1],
                             n_slots=n_slots, n_bins=b, interpret=True)
        assert np.allclose(
            np.asarray(fused[k * n_slots * C:(k + 1) * n_slots * C]),
            np.asarray(one), atol=1e-4)


@pytest.mark.parametrize("n,f,b,folds,n_slots", ODD_SHAPES)
def test_batched_matches_per_fold_bf16(n, f, b, folds, n_slots):
    """bf16 contraction inputs: batched == per-fold bitwise (the lanes
    quantize independently), and both stay within the 1e-3-AuPR-impact
    tolerance of the f32 leg (BENCH_NOTES r4: <=0.4% relative on g/h)."""
    Xb_t, payi, slot = _lanes_inputs(n, f, b, folds, n_slots, seed=1,
                                     integral=True)
    _, payc, _ = _lanes_inputs(n, f, b, folds, n_slots, seed=1)
    C = payi.shape[0] // folds
    prev = PH._HIST_BF16
    try:
        PH.set_hist_bf16(True)
        fused = PH.hist_pallas(Xb_t, payi, slot, n_slots=n_slots,
                               n_bins=b, interpret=True, allow_bf16=True)
        for k in range(folds):
            one = PH.hist_pallas(Xb_t, payi[C * k:C * (k + 1)],
                                 slot[k:k + 1], n_slots=n_slots, n_bins=b,
                                 interpret=True, allow_bf16=True)
            np.testing.assert_array_equal(
                np.asarray(fused[k * n_slots * C:(k + 1) * n_slots * C]),
                np.asarray(one))
        quant = PH.hist_pallas(Xb_t, payc, slot, n_slots=n_slots,
                               n_bins=b, interpret=True, allow_bf16=True)
    finally:
        PH.set_hist_bf16(prev)
    f32 = PH.hist_pallas(Xb_t, payc, slot, n_slots=n_slots, n_bins=b,
                         interpret=True)
    ref = np.asarray(f32)
    scale = np.abs(ref).max() + 1.0
    assert np.allclose(np.asarray(quant), ref, atol=1e-2 * scale)


@pytest.mark.parametrize("n,f,b,folds,n_slots", ODD_SHAPES[:3])
def test_cpu_fallback_matches_interpret(n, f, b, folds, n_slots):
    """_hist_segment_jnp (the hist_folds CPU route) == interpret-mode
    pallas up to f32 summation order. (First three shapes only: the
    vmapped segment-sum's CPU compile is ~25s per novel fold count, and
    the dropped shapes add no new fallback code path.)"""
    Xb_t, pay, slot = _lanes_inputs(n, f, b, folds, n_slots, seed=2)
    want = PH.hist_pallas(Xb_t, pay, slot, n_slots=n_slots, n_bins=b,
                          interpret=True)
    got = PH._hist_segment_jnp(Xb_t, pay, slot, n_slots=n_slots, n_bins=b)
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("derive_count", [False, True])
def test_derive_count_matches_streamed_channel(derive_count):
    """derive_count appends IN VMEM exactly the channel the tree path
    used to stream from HBM: count = (hessian > 0)."""
    n, f, b, folds, n_slots = 515, 4, 8, 3, 4
    rng = np.random.default_rng(3)
    Xb_t = jnp.asarray(rng.integers(0, b, size=(f, n)), jnp.int8)
    g = rng.normal(size=(folds, n)).astype(np.float32)
    h = np.where(rng.uniform(size=(folds, n)) < 0.3, 0.0,
                 rng.uniform(0.1, 1.0, size=(folds, n))).astype(np.float32)
    slot = jnp.asarray(rng.integers(0, n_slots, size=(folds, n)),
                       jnp.float32)
    pay2 = jnp.asarray(np.stack([g, h], axis=1).reshape(2 * folds, n))
    cnt = (h > 0).astype(np.float32)
    pay3 = jnp.asarray(np.stack([g, h, cnt], axis=1).reshape(3 * folds, n))
    if derive_count:
        got = PH.hist_pallas(Xb_t, pay2, slot, n_slots=n_slots, n_bins=b,
                             interpret=True, derive_count=True)
    else:
        got = PH._hist_segment_jnp(Xb_t, pay2, slot, n_slots=n_slots,
                                   n_bins=b, derive_count=True)
    want = PH.hist_pallas(Xb_t, pay3, slot, n_slots=n_slots, n_bins=b,
                          interpret=True)
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("folds,n", [(3, 517), (1, 130)])
def test_route_hist_matches_separate_passes(folds, n):
    """One fused route+hist pass == route_pallas THEN hist_pallas of the
    left children, bit-for-bit on both outputs."""
    f, b, n_nodes = 5, 8, 4
    rng = np.random.default_rng(4)
    Xb_t = jnp.asarray(rng.integers(0, b, size=(f, n)), jnp.int8)
    pay = jnp.asarray(rng.normal(size=(2 * folds, n)), jnp.float32)
    node = jnp.asarray(rng.integers(0, n_nodes, size=(folds, n)),
                       jnp.float32)
    f_lvl = jnp.asarray(rng.integers(0, f, size=(folds, n_nodes)),
                        jnp.int32)
    t_lvl = jnp.asarray(rng.integers(0, b, size=(folds, n_nodes)),
                        jnp.int32)
    m_lvl = jnp.asarray(rng.integers(0, 2, size=(folds, n_nodes)),
                        jnp.int32)
    hist, new_node = PH.route_hist(Xb_t, pay, node, f_lvl, t_lvl, m_lvl,
                                   n_nodes=n_nodes, n_bins=b,
                                   interpret=True, derive_count=True)
    want_node = PH.route_pallas(Xb_t, node, f_lvl, t_lvl, m_lvl,
                                n_nodes=n_nodes, interpret=True)
    np.testing.assert_array_equal(np.asarray(new_node),
                                  np.asarray(want_node))
    # left rows keep their old node id as the next level's slot; right
    # rows drop (slot >= n_slots), same encoding hist_pallas pads with
    right = want_node - 2.0 * node
    slots = node + float(n_nodes) * right
    want_hist = PH.hist_pallas(Xb_t, pay, slots, n_slots=n_nodes,
                               n_bins=b, interpret=True, derive_count=True)
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(want_hist))


def test_route_hist_cpu_fallback_decisions_match():
    """The jnp fallback of route_hist routes bitwise like interpret-mode
    pallas and its histogram matches within summation order."""
    f, b, n_nodes, folds, n = 4, 6, 2, 2, 261
    rng = np.random.default_rng(5)
    Xb_t = jnp.asarray(rng.integers(0, b, size=(f, n)), jnp.int8)
    pay = jnp.asarray(rng.normal(size=(2 * folds, n)), jnp.float32)
    node = jnp.asarray(rng.integers(0, n_nodes, size=(folds, n)),
                       jnp.float32)
    f_lvl = jnp.asarray(rng.integers(0, f, size=(folds, n_nodes)),
                        jnp.int32)
    t_lvl = jnp.asarray(rng.integers(0, b, size=(folds, n_nodes)),
                        jnp.int32)
    m_lvl = jnp.asarray(rng.integers(0, 2, size=(folds, n_nodes)),
                        jnp.int32)
    hist_i, node_i = PH.route_hist(Xb_t, pay, node, f_lvl, t_lvl, m_lvl,
                                   n_nodes=n_nodes, n_bins=b,
                                   interpret=True, derive_count=True)
    node_c = PH._route_level_jnp(Xb_t, node, f_lvl, t_lvl, m_lvl)
    np.testing.assert_array_equal(np.asarray(node_c), np.asarray(node_i))
    right = node_c - 2.0 * node
    hist_c = PH._hist_segment_jnp(Xb_t, pay,
                                  node + float(n_nodes) * right,
                                  n_slots=n_nodes, n_bins=b,
                                  derive_count=True)
    assert np.allclose(np.asarray(hist_c), np.asarray(hist_i), atol=1e-4)


# -- routing and lookup at the level's own node count ----------------------

NODE_COUNTS = [1, 2, 4, 8, 16, 32, 64, 256, 2048]


def _route_inputs(n_nodes, lanes, n, seed, wide):
    """A level's tables and rows. wide: int32 bins up to 256 over 310
    features, so feature ids pass 300 and thresholds 2^8 (one default
    bf16 pass would round both); else int8 bins over 40 features. Bin 0
    (missing) is present and both directions are drawn; every 7th row
    carries a node id past the table."""
    rng = np.random.default_rng(seed)
    f, top, dt = (310, 256, jnp.int32) if wide else (40, 31, jnp.int8)
    Xb_t = jnp.asarray(rng.integers(0, top + 1, size=(f, n)), dt)
    node = rng.integers(0, n_nodes, size=(lanes, n))
    stray = np.arange(n) % 7 == 3
    node[:, stray] = n_nodes + rng.integers(0, 3, size=(lanes, stray.sum()))
    f_lvl = rng.integers(0, f, size=(lanes, n_nodes))
    t_lvl = rng.integers(0, top + 1, size=(lanes, n_nodes))
    f_lvl[:, 0], t_lvl[:, 0] = f - 1, top       # the largest of each
    if n_nodes > 1:
        f_lvl[:, 1], t_lvl[:, 1] = f - 9, top - 1
    m_lvl = rng.integers(0, 2, size=(lanes, n_nodes))
    return (Xb_t, jnp.asarray(node, jnp.float32),
            jnp.asarray(f_lvl, jnp.int32), jnp.asarray(t_lvl, jnp.int32),
            jnp.asarray(m_lvl, jnp.int32), stray)


def _assert_routed_like_twin(got, Xb_t, node, f_lvl, t_lvl, m_lvl, stray):
    got = np.asarray(got)
    keep = ~stray
    want = PH._route_level_jnp(Xb_t[:, keep], node[:, keep], f_lvl, t_lvl,
                               m_lvl)
    np.testing.assert_array_equal(got[:, keep], np.asarray(want))
    # a node id past the table owns no entry: the row goes left
    np.testing.assert_array_equal(got[:, stray],
                                  2.0 * np.asarray(node)[:, stray])


@pytest.mark.parametrize("lanes", [1, 10])
@pytest.mark.parametrize("n_nodes", NODE_COUNTS)
def test_route_pallas_exact_at_every_node_count(n_nodes, lanes):
    wide = n_nodes <= 256
    n = PH._ROUTE_BLK + 37 if n_nodes <= 64 else 517    # ragged either way
    Xb_t, node, f_lvl, t_lvl, m_lvl, stray = _route_inputs(
        n_nodes, lanes, n, seed=n_nodes + lanes, wide=wide)
    got = PH.route_pallas(Xb_t, node, f_lvl, t_lvl, m_lvl,
                          n_nodes=n_nodes, interpret=True)
    _assert_routed_like_twin(got, Xb_t, node, f_lvl, t_lvl, m_lvl, stray)


@pytest.mark.parametrize("lanes", [1, 10])
@pytest.mark.parametrize("n_nodes", NODE_COUNTS)
def test_route_hist_exact_at_every_node_count(n_nodes, lanes):
    """The fused pass routes like the twin and its histogram is bitwise
    route_pallas THEN hist_pallas, at every level width. Wide bins (ids
    past 300, thresholds to 256) ride the narrow levels; the 2 048-node
    level keeps two lanes (its histogram block is lanes x 6 144 rows);
    517 rows are two ragged grid steps at 40 x 32 one-hot columns, and
    261 rows three at the wide levels' 310 x 257 (whose interpreted grid
    steps are most of this file's time: 517 rows there were five)."""
    wide = n_nodes <= 2
    lanes = min(lanes, 2) if n_nodes > 256 else lanes
    b = 257 if wide else 32
    n = 261 if wide else 517
    Xb_t, node, f_lvl, t_lvl, m_lvl, stray = _route_inputs(
        n_nodes, lanes, n, seed=3 * n_nodes + lanes, wide=wide)
    rng = np.random.default_rng(n_nodes)
    pay = jnp.asarray(rng.integers(-8, 9, size=(2 * lanes, n)),
                      jnp.float32)
    hist, new_node = PH.route_hist(Xb_t, pay, node, f_lvl, t_lvl, m_lvl,
                                   n_nodes=n_nodes, n_bins=b,
                                   interpret=True, allow_bf16=True,
                                   derive_count=True)
    _assert_routed_like_twin(new_node, Xb_t, node, f_lvl, t_lvl, m_lvl,
                             stray)
    want_node = PH.route_pallas(Xb_t, node, f_lvl, t_lvl, m_lvl,
                                n_nodes=n_nodes, interpret=True)
    np.testing.assert_array_equal(np.asarray(new_node),
                                  np.asarray(want_node))
    slots = node + float(n_nodes) * (want_node - 2.0 * node)
    want_hist = PH.hist_pallas(Xb_t, pay, slots, n_slots=n_nodes, n_bins=b,
                               interpret=True, allow_bf16=True,
                               derive_count=True)
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(want_hist))


@pytest.mark.parametrize("lanes", [1, 10])
@pytest.mark.parametrize("width", [2, 64, 100, 4096])
def test_table_lookup_bitwise_at_every_width(width, lanes):
    """Full 24-bit mantissas over many binades come back bit for bit
    (one bf16 pass would round them to 8 bits); ids outside the table
    read 0."""
    rng = np.random.default_rng(width + lanes)
    mant = rng.integers(1 << 23, 1 << 24, size=(lanes, width)) | 1
    tbl = (mant * rng.choice([-1.0, 1.0], size=mant.shape)
           * 2.0 ** rng.integers(-40, 17, size=mant.shape))
    tbl = jnp.asarray(tbl, jnp.float32)
    n = PH._ROUTE_BLK + 37 if width <= 100 else 517
    idx = rng.integers(-1, width + 1, size=(lanes, n))
    idx[:, ::11] = PH.node_rows(width, 2) + 5
    idx = jnp.asarray(idx, jnp.float32)
    got = PH.table_lookup_pallas(tbl, idx, interpret=True)
    want = PH._table_lookup_jnp(tbl, idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(want)[:, ::11].max() == 0.0


@pytest.mark.parametrize("n,f32_rows,bf16_rows", [
    (1, 8, 16), (2, 8, 16), (8, 8, 16), (9, 16, 16), (32, 32, 32),
    (64, 64, 64), (100, 104, 112), (128, 128, 128), (129, 256, 256),
    (2048, 2048, 2048)])
def test_node_rows_is_the_table_at_one_sublane_tile(n, f32_rows, bf16_rows):
    assert PH.node_rows(n) == f32_rows
    assert PH.node_rows(n, 2) == bf16_rows


class TestPlanner:
    """plan_lane_chunk: the single place tile/lane budgets are decided."""

    def test_respects_hbm_lane_budget(self, monkeypatch):
        monkeypatch.setenv("TMOG_GRID_FUSE_HBM_LANES", "20")
        monkeypatch.setenv("TMOG_GRID_FUSE_OUT_MB", "1000")
        # 16 configs x 5 folds = 80 lanes > 20: halve to 4 x 5 = 20
        assert PH.plan_lane_chunk(8, 9, 5, 16, 3) == 4

    def test_out_block_cap_halves_chunk(self, monkeypatch):
        monkeypatch.setenv("TMOG_GRID_FUSE_HBM_LANES", "4096")
        monkeypatch.setenv("TMOG_GRID_FUSE_OUT_MB", "8")
        full = PH.plan_fused_hist(64, 33, 16 * 5, 6).out_bytes / 1e6
        assert full > 8.0  # the cap must actually bind at 16 configs
        chunk = PH.plan_lane_chunk(64, 33, 5, 16, 6)
        assert 0 < chunk < 16
        assert PH.plan_fused_hist(64, 33, chunk * 5, 6).out_bytes / 1e6 \
            <= 8.0

    def test_zero_when_single_config_busts_caps(self, monkeypatch):
        # even ONE config's fold lanes violate the HBM budget -> 0, the
        # caller must take the per-config route (ADVICE r5: chunk==1
        # used to skip these caps entirely)
        monkeypatch.setenv("TMOG_GRID_FUSE_HBM_LANES", "3")
        assert PH.plan_lane_chunk(8, 9, 5, 16, 3) == 0

    def test_vmem_gate_matches_fused_hist_fits(self):
        for shape in [(64, 33, 5, 6), (300, 257, 5, 6), (8, 9, 1, 0)]:
            assert PH.plan_fused_hist(*shape).fits == \
                PH.fused_hist_fits(*shape)


def test_planner_cpu_smoke():
    """Tier-1 smoke (ci.sh runs this on every CPU pass): plan a tiny
    matrix, then drive hist_folds — which dispatches to the pure-jnp
    segment-sum fallback off-TPU — through the planned lane count."""
    n, f, b, folds, configs, depth = 96, 4, 7, 2, 3, 3
    chunk = PH.plan_lane_chunk(f, b, folds, configs, depth)
    assert chunk >= 1
    lanes = chunk * folds
    Xb_t, pay, slot = _lanes_inputs(n, f, b, lanes, 2, seed=6)
    out = PH.hist_folds(Xb_t, pay, slot, n_slots=2, n_bins=b,
                        derive_count=True)
    assert out.shape == (lanes * 2 * 3, f * b)
    assert bool(jnp.isfinite(out).all())
