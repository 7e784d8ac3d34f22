"""Pallas TPU kernel for tree gradient histograms.

The XLA chunked histogram path (ops/trees._histograms_matmul) materializes
its [chunk, F*B] one-hot block in HBM every scan step — ~1GB of write+read
traffic per 64K-row chunk, ~150GB per level at the 10M-row BASELINE
config, which dominates the tree sweep's wall clock. This kernel builds
the one-hot tiles directly in VMEM (they never exist in HBM) and leaves
one MXU contraction per row block:

    out[slot*C + c, f*B + b] += sum_i  1[slot_i = slot] * P[c, i]
                                     * 1[Xb[f, i] = b]

- inputs arrive TRANSPOSED ([F, N] / [C, N] / [1, N]) so the huge axis is
  minor: TPU tiling pads the minor axis to 128 lanes, and feeding [N, C]
  with C=4 would inflate HBM 32x (the round-2 fold-vmap OOM was exactly
  this padding on [5, 10M] arrays);
- the (feature, bin) one-hot is a VPU broadcast-compare reshaped
  [F, B, blk] -> [F*B, blk] (leading-dim merge, layout-free);
- slot one-hots drop out-of-range ids (slot = n_slots encodes "row
  contributes nothing" — how histogram subtraction or padded rows enter);
- grid steps run sequentially on the core, accumulating into the same
  VMEM output block (zeroed at step 0).

Reference workload: XGBoost's hist-method gradient histograms, the C++
path behind the reference's OpXGBoost* wrappers (SURVEY §2.9).
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.env import env_float, env_int
from . import parts as _parts

_BLK = 4096


# Budgets off-TPU (interpret-mode tests): small enough to exercise the
# tile-shrinking and gate logic, unrelated to any chip.
_CPU_TILE_BUDGET = 4 << 20
_CPU_VMEM_LIMIT = 12 << 20


def _tile_budget() -> int:
    """Size budget for the nominal [cols, blk] f32 one-hot tile: 3/16 of
    the device's VMEM (24 MiB on v5e — 4x fewer grid steps than a 4 MiB
    tile; at 10M rows the per-step overhead and the skinny matmuls were
    the tree sweep's wall). Nominal: Mosaic builds the one-hot in
    pieces and never holds it whole (a 128 MiB tile compiled on v5e,
    PERF.md PR 21), so this sets the grid-step count, not a VMEM bound."""
    from ..utils.platform import device_spec
    spec = device_spec()
    return _CPU_TILE_BUDGET if spec is None else spec.vmem_bytes * 3 // 16


def block_rows(n_onehot_cols: int) -> int:
    """Rows per grid step of the one-level bodies, sized so the [cols, blk]
    f32 one-hot tile stays within the tile budget (v5e trees: F*B ~ 2048 ->
    2048 rows; 4096 bins -> 1024, which pallas_rank_hist takes instead)."""
    blk = _BLK
    budget = _tile_budget()
    while blk > 128 and n_onehot_cols * blk * 4 > budget:
        blk //= 2
    return blk


def _vmem_limit() -> int:
    """VMEM one kernel may claim: the device's physical VMEM less a
    quarter held back for the compiler's own scratch (96 of 128 MiB on
    v5e). ONE figure with two readers: plan_fused_hist gates kernel
    forms whose residents scale with problem shape (the fused output
    block) against it, and every pallas_call passes it as Mosaic's
    `vmem_limit_bytes`, so a shape the gate admits is not refused for
    the compiler's smaller default scoped limit. (The flagship shape
    compiles under the default too — PERF.md, PR 21.)"""
    from ..utils.platform import device_spec
    spec = device_spec()
    return _CPU_VMEM_LIMIT if spec is None else spec.vmem_bytes * 3 // 4


def _compiler_params():
    """Mosaic params shared by every kernel here (see _vmem_limit)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit())


@dataclasses.dataclass(frozen=True)
class HistPlan:
    """Tile/residency plan for one fused multi-(fold x config-lane)
    histogram program — the single place tile shapes are derived from
    (rows, cols, slots, lanes). Produced by plan_fused_hist; consumed by
    the sweep chunker (plan_lane_chunk / models/trees) and the VMEM gate
    (fused_hist_fits)."""

    lanes: int        # fold x config lanes resident in one program
    n_slots: int      # worst-level slot count budgeted (2^(depth-2))
    blk: int          # rows per grid step (the HBM->VMEM tile height)
    out_bytes: int    # fused output block, fully VMEM-resident
    vmem_bytes: int   # estimated total VMEM residents
    fits: bool        # vmem_bytes within the device budget


def plan_fused_hist(n_feat: int, n_bins: int, lanes: int, depth: int,
                    channels: int = 3) -> HistPlan:
    """Plan VMEM residency for the fused histogram kernel at this shape.

    The fused output block [lanes * n_slots * channels, F * B] f32 is
    fully VMEM-resident and scales with every one of those factors;
    block_rows only budgets the one-hot tile, so XGB-shaped configs
    (256 bins, depth 6, a few hundred features, 3-5 folds) would sail
    past a Mosaic compile failure with no library-level fallback. Worst
    level is the deepest histogram pass: sibling subtraction halves the
    slot count, so n_slots = 2^(depth-2) for depth >= 2. The fused fit
    (ops/trees._grow_tree_folds) runs level d at its own 1 << d slots,
    one Mosaic route_hist program a level, so this is the LARGEST of a
    fit's programs and not the shape of each: what fits here fits at
    every shallower level.
    Residents:
    output block + the [F*B, blk] f32 one-hot tile (+ a bf16 copy when
    the bf16 input mode is on) + the f32 Xb/payload/slot tiles + the
    routing half's (lane, node) rows: the selected bins of one lane
    group and their decisions (_route_right), f32 [rows, blk] each.
    """
    cols = n_feat * n_bins
    n_slots = 1 << max(depth - 2, 0)
    out_b = lanes * n_slots * channels * cols * 4
    blk = block_rows(cols)
    onehot_b = cols * blk * 4
    if _HIST_BF16:
        onehot_b += cols * blk * 2
    minor_b = (n_feat + lanes * channels + lanes) * blk * 8
    # worst routed level has 2^(depth-2) nodes, laid out at node_rows
    # a lane, one lane group at a time (the final level routes through
    # the standalone route kernel: twice the nodes, no histogram operands)
    route_b = 2 * route_group_rows(n_slots, lanes) * blk * 4
    vmem = out_b + onehot_b + minor_b + route_b
    return HistPlan(lanes=lanes, n_slots=n_slots, blk=blk, out_bytes=out_b,
                    vmem_bytes=vmem, fits=vmem <= _vmem_limit())


def fused_hist_fits(n_feat: int, n_bins: int, n_folds: int, depth: int,
                    channels: int = 3) -> bool:
    """Will the fold-fused histogram kernel's VMEM residents fit? (Thin
    gate over plan_fused_hist; callers — models/trees._fused_route_ok —
    fall back to the sequential per-fold path when this returns False.)"""
    return plan_fused_hist(n_feat, n_bins, n_folds, depth, channels).fits


def plan_lane_chunk(n_feat: int, n_bins: int, n_folds: int, n_configs: int,
                    depth: int, channels: int = 3,
                    n_shards: int = 1) -> int:
    """Configs per fused sweep program, honoring every budget at once.

    The single planner for the config-fused sweep: lanes = configs x
    folds share one residency of the binned matrix, but three budgets cap
    how many fit one program — the VMEM plan (plan_fused_hist), the HBM
    lane budget (TMOG_GRID_FUSE_HBM_LANES: each lane carries 4 lane-sized
    f32 planes — W, g, h, margins), and the fused output block cap
    (TMOG_GRID_FUSE_OUT_MB: Mosaic's layout search explodes when the out
    block nears the scoped-VMEM boundary; r5 session 2 saw 20+ min
    compiles at a 16MB block). Returns the largest config chunk (halving
    from n_configs) that clears ALL THREE, and 0 when even a single
    config's fold lanes violate any cap — callers must then fall back to
    the per-config route (a chunk of 1 that only cleared the VMEM gate
    used to sail past the HBM/out-block caps; ADVICE round 5).

    `n_shards` is the lane-shard budget of the mesh route
    (fit_gbt_folds_sharded): the 4 row-planes every lane carries shard
    over the mesh batch axis, so per-device HBM pressure divides by the
    shard count and the lane budget multiplies by it. VMEM and
    out-block caps are PER DEVICE and do not scale — the fused output
    block is replicated on every shard (psum-merged)."""
    lane_cap = env_int("TMOG_GRID_FUSE_HBM_LANES", 64)
    out_mb_cap = env_float("TMOG_GRID_FUSE_OUT_MB", 8.0)
    hbm_lane_budget = lane_cap * max(int(n_shards), 1)

    def ok(chunk: int) -> bool:
        lanes = chunk * n_folds
        plan = plan_fused_hist(n_feat, n_bins, lanes, depth, channels)
        return (plan.fits and lanes <= hbm_lane_budget
                and plan.out_bytes / 1e6 <= out_mb_cap)

    chunk = max(n_configs, 1)
    while chunk > 1 and not ok(chunk):
        chunk = (chunk + 1) // 2
    if chunk == 1 and not ok(1):
        return 0
    return chunk


# -- analytic HBM traffic (roofline accounting) -----------------------------

def sweep_level_bytes(n_rows: int, n_feat: int, lanes: int, *,
                      channels: int = 2, xb_itemsize: int = 1,
                      fused=True) -> int:
    """Analytic HBM bytes moved for ONE mid-sweep tree level.

    Three routes, honest about what each actually streamed:

    fused='per_fold' (or False): the sequential per-lane route (r5's
    fallback when fold fusion was gated off) — every lane re-streams the
    binned matrix for its histogram pass AND again for its routing pass,
    plus per-lane payload (g/h, `channels` f32 planes), the slot plane
    and the node read+write.

    fused='r5' models what the r5 production TPU route ACTUALLY moved
    per config: the fold axis was already fused (one hist_pallas + one
    route_pallas per level for all `lanes` folds, so Xb streams twice
    per level total), but the count channel was its own HBM plane and
    routing was a separate pass.

    fused='fused' (or True): the batched route+hist kernel — ONE
    residency of the binned matrix serves every (fold x config) lane,
    the count channel is derived in VMEM from the hessian (no HBM
    plane), and routing rides the same pass (node read + next-level node
    write per lane).

    The bench/tools roofline reports are computed from this single model
    so the numbers cannot drift from the kernels they describe.
    """
    mode = {True: "fused", False: "per_fold"}.get(fused, fused)
    xb = n_rows * n_feat * xb_itemsize
    pay = channels * 4 * n_rows            # g/h f32 planes per lane
    node = 4 * n_rows                      # f32 slot/node plane
    if mode == "per_fold":
        # hist pass: Xb + payload + count plane + slot ids; route pass:
        # Xb again + node read + node write
        per_lane = 2 * xb + pay + 2 * node + 2 * node
        return lanes * per_lane
    if mode == "r5":
        # fold-fused hist pass (payload + streamed count + slot ids per
        # lane) + separate fold-fused route pass (node read + write)
        return 2 * xb + lanes * (pay + 2 * node + 2 * node)
    if mode != "fused":
        raise ValueError(f"unknown traffic mode {fused!r}")
    return xb + lanes * (pay + 2 * node)   # node read + new-node write


def fused_fit_bytes(n_rows: int, n_feat: int, lanes: int, depth: int,
                    n_rounds: int, *, xb_itemsize: int = 1,
                    payload_rows: int = 3) -> int:
    """Analytic HBM bytes for one whole fused-sweep GBT fit (all rounds).

    Per round: the level-0 histogram pass (Xb + per-lane payload + slot),
    depth-1 fused route+hist passes (_grow_tree_folds calls route_hist
    for every d in 0..depth-2; sweep_level_bytes each), the final
    standalone route (Xb + node read/write per lane) and the leaf lookup
    + margin update (3 lane planes). `payload_rows` = 5 (a payload in
    three parts, ops/trees.PAYLOAD_PARTS): the kernels cut the parts in
    VMEM from the same float32 planes, so a pass moves what it moved; what
    a round adds is the scale — one more read of the gradient plane for
    its max-reduction and the write of the scaled plane (2 lane planes).
    Used by the sweep's roofline spans (utils/metrics collector) — analytic
    by construction since the whole fit is one jitted program."""
    xb = n_rows * n_feat * xb_itemsize
    plane = 4 * n_rows
    level0 = xb + lanes * (2 * plane + plane)      # g/h + slot ids
    mid = max(depth - 1, 0) * sweep_level_bytes(
        n_rows, n_feat, lanes, xb_itemsize=xb_itemsize, fused=True)
    final_route = (xb + lanes * 2 * plane) if depth >= 1 else 0
    leaf_margin = lanes * 3 * plane
    scaled = lanes * 2 * plane if payload_rows > 3 else 0
    return n_rounds * (level0 + mid + final_route + leaf_margin + scaled)


# THE pallas kill switch — single flag for every consumer (tree
# histograms, lane-batched metrics). Env default: TMOG_NO_PALLAS truthy
# (not "0"/"false"/"") disables; set_enabled() is the runtime toggle.
_enabled = os.environ.get("TMOG_NO_PALLAS", "").strip().lower() \
    in ("", "0", "false")

# jitted functions whose compiled executables bake the pallas choice in;
# cleared on toggle so a cached program cannot pin the previous choice
_cache_consumers = []


def register_cache_consumer(fn) -> None:
    """Register a jitted function that traces through available()."""
    _cache_consumers.append(fn)


def enabled() -> bool:
    return _enabled


def set_enabled(enabled: bool) -> None:
    global _enabled
    if _enabled == bool(enabled):
        return
    _enabled = bool(enabled)
    for fn in _cache_consumers:
        fn.clear_cache()


def available() -> bool:
    """Pallas path usable? (enabled + TPU backend.) On the TPU backend a
    pallas that cannot be imported raises here: a broken install must
    not turn into the jnp twin at 10M rows."""
    if not _enabled or jax.default_backend() != "tpu":
        return False
    from jax.experimental import pallas  # noqa: F401
    from jax.experimental.pallas import tpu  # noqa: F401
    return True


# Histogram contraction input dtype. bf16 doubles the MXU ceiling (the
# fused fold fit runs near the f32 matmul peak); the one-hot operand is
# EXACT in bf16 (0/1), counts stay integer-exact (1.0 payloads, f32
# accumulation) and so does every payload row whose values are integers
# under 256: the weight row h under unit sample weights (bootstrap draw x
# fold mask), and g = h x a 0/1 label — what a forest classifier's lanes
# carry. A real-valued g row is rounded ONCE to bfloat16 (2^-9 of each
# value's size) unless the CALLER asks for `payload_parts` = 3: then the
# kernels cut the g rows into three bfloat16 parts whose sum is the
# float32 value (parts.unit_cuts: every product exact) and issue 5 rows a
# (lane, slot) where they issue 3. The cuts are FIXED-POINT for a payload
# the caller has scaled into [-1, 1] (a power of two: exact): whole
# multiples of 2^-7 and of 2^-15, whose float32 sums over millions of rows
# are themselves exact, and a rest under 2^-16 — so a histogram sum is
# the float64 sum to ~2^-25 of the scale a row, not float32's rounding
# of a long accumulation. Who decides: the word models/trees.payload_body
# gives the estimator (ops/trees.PAYLOAD_PARTS), handed to
# ops/trees.fit_forest_lanes and fit_gbt_folds as their `payload` argument:
# a regression forest's centred, scaled label takes three parts, and so
# does a squared-loss booster's residual, each round over that round's own
# scale; the logistic boosters' gradients (under 1 in size) and a weight
# row under real-valued sample weights still go as one part.
# set_hist_bf16(False) is the lever of the float32 parity tests.
_HIST_BF16 = True


def set_hist_bf16(enabled: bool) -> None:
    """Toggle bf16 histogram inputs. hist_pallas itself resolves the flag
    OUTSIDE its jit (it becomes the use_bf16 cache key), so only the
    registered consumer jits — which bake the flag into their traces —
    need their caches cleared."""
    global _HIST_BF16
    if _HIST_BF16 == bool(enabled):
        return
    _HIST_BF16 = bool(enabled)
    for fn in _cache_consumers:
        fn.clear_cache()


def _feature_onehot(xf, *, F, B, blk, use_bf16):
    """(feature, bin) one-hot tile [F*B, blk] — the shared VPU expansion
    both histogram kernels contract against. Comparisons must run in f32
    (Mosaic rejects bf16 cmpf vectors, like the f32-iota restriction
    below); bf16 mode therefore builds the one-hot feature-by-feature,
    casting each [B, blk] slice down immediately — one full-size f32
    one-hot next to its bf16 copy would blow the 16MB scoped-VMEM stack.
    f32 mode (parity tests, narrow metric calls) builds a 3D broadcast-compare
    reshaped [F, B, blk] -> [F*B, blk], a leading-dim merge.
    Mosaic's tpu.iota only produces integer vectors; build int32 and cast
    (f32 iota verified fine in interpret mode but fails TPU lowering)."""
    if use_bf16:
        bins2 = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0) \
            .astype(jnp.float32)                            # [B, 1]
        return jnp.concatenate(
            [(xf[f:f + 1, :] == bins2).astype(jnp.bfloat16)  # [B, blk]
             for f in range(F)], axis=0)                    # [F*B, blk]
    bins = jax.lax.broadcasted_iota(jnp.int32, (1, B, 1), 1) \
        .astype(jnp.float32)
    oh = (xf[:, None, :] == bins).astype(jnp.float32)       # [F, B, blk]
    return oh.reshape(F * B, blk)


def _fold_payload(pay_ref, k, C, mxu_dtype, derive_count, parts=1,
                  classes=0):
    """Fold k's payload rows, with the unit-count channel derived in VMEM
    when derive_count: count = (h > 0) on the LAST input channel (the
    hessian) — exactly grow_tree's count_unit, computed on the VPU
    instead of streamed as its own HBM plane. `parts` = 3 (a real-valued
    payload in bf16 mode): the channels before the last go as three
    bfloat16 parts each (parts.unit_cuts), part-major, ahead of the last
    channel and the count (payload_rows(C, parts, derive_count) rows).
    `classes` = K (a class label): the lane's two planes are [class id,
    weight] and the K class channels weight x (id == k) are built HERE,
    beside the count — no K planes a lane in HBM, and no weight row: the
    weight is the channels' sum, taken outside (K + 1 rows)."""
    pay = pay_ref[k * C:(k + 1) * C, :]                     # [C, blk] f32
    if classes:
        ids = jax.lax.broadcasted_iota(
            jnp.int32, (classes, pay.shape[1]), 0).astype(jnp.float32)
        chans = (ids == pay[0:1, :]).astype(jnp.float32) * pay[1:2, :]
        cnt = (pay[1:2, :] > 0.0).astype(jnp.float32)
        return jnp.concatenate([chans, cnt], axis=0).astype(mxu_dtype)
    if derive_count:
        cnt = (pay[C - 1:C, :] > 0.0).astype(jnp.float32)
        pay = jnp.concatenate([pay, cnt], axis=0)           # [C+1, blk]
    if parts == 3:
        pay = jnp.concatenate(
            _parts.unit_cuts(pay[:C - 1, :]) + [pay[C - 1:, :]], axis=0)
    return pay.astype(mxu_dtype)


def payload_rows(C: int, parts: int, derive_count: bool,
                 classes: int = 0) -> int:
    """Rows a (lane, slot) of the contraction's left operand: the C input
    channels, the derived count, and parts - 1 more for each channel
    before the last. `parts` is 1 or 3: what _fold_payload cuts. Under
    `classes` = K the two input planes [class id, weight] become the K
    class channels and the count: K + 1."""
    if parts not in (1, 3):
        raise ValueError(f"payload_parts {parts}: one part or three")
    if classes:
        if (C, parts, derive_count) != (2, 1, True):
            raise ValueError(
                f"{classes} class channels are built from [class id, "
                f"weight] in one part beside the derived count, not from "
                f"{C} channels in {parts} part(s)")
        return classes + 1
    return C + (1 if derive_count else 0) + (parts - 1) * (C - 1)


def _sum_parts(hist, *, C, parts, derive_count):
    """[lanes * slots * payload_rows, F * B] as the kernels leave it under
    `parts` > 1 -> the [lanes * slots * Co, F * B] of one part: each cut
    channel's parts added, the smallest first."""
    if parts == 1:
        return hist
    h = hist.reshape(-1, payload_rows(C, parts, derive_count),
                     hist.shape[1])
    cut = h[:, :parts * (C - 1)].reshape(-1, parts, C - 1, hist.shape[1])
    g = cut[:, -1]
    for p in range(parts - 2, -1, -1):
        g = g + cut[:, p]
    return jnp.concatenate([g, h[:, parts * (C - 1):]], axis=1) \
        .reshape(-1, hist.shape[1])


def _kernel(xb_ref, pay_ref, slot_ref, out_ref, *, F, B, C, n_slots,
            n_folds, use_bf16=False, derive_count=False, parts=1,
            classes=0):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    blk = xb_ref.shape[1]
    mxu_dtype = jnp.bfloat16 if use_bf16 else jnp.float32
    xf = xb_ref[:].astype(jnp.float32)                      # [F, blk]
    oh = _feature_onehot(xf, F=F, B=B, blk=blk, use_bf16=use_bf16)

    # fold-fused: each fold contributes its own slot one-hot x payload
    # rows to ONE contraction, so the (feature, bin) one-hot above — the
    # dominant VPU cost — and the Xb traffic are built once for all folds,
    # and the matmul M dim grows n_folds x (the single-fold M of S*C rows
    # is far below the 128-row MXU tile; see BENCH_NOTES round-4 session 2)
    Co = payload_rows(C, parts, derive_count, classes)
    slots = jax.lax.broadcasted_iota(jnp.int32, (n_slots, blk), 0) \
        .astype(jnp.float32)
    qs = []
    for k in range(n_folds):
        slot = slot_ref[k:k + 1, :]                         # [1, blk]
        slot_oh = (slots == slot).astype(mxu_dtype)         # [n_slots, blk]
        pay = _fold_payload(pay_ref, k, C, mxu_dtype, derive_count, parts,
                            classes)
        qs.append((slot_oh[:, None, :] * pay[None, :, :])
                  .reshape(n_slots * Co, blk))
    q = qs[0] if n_folds == 1 else jnp.concatenate(qs, axis=0)

    out_ref[:] += jax.lax.dot_general(
        q, oh, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # [Fo*S*Co, F*B]


def hist_pallas(Xb_t: jax.Array, pay_t: jax.Array, slot_t: jax.Array,
                *, n_slots: int, n_bins: int, interpret: bool = False,
                allow_bf16: bool = False, derive_count: bool = False,
                unit_payload: bool = False,
                payload_parts: int = 1, classes: int = 0) -> jax.Array:
    """Histograms [n_folds * n_slots * Co, F * n_bins] (f32) of payload sums.

    Xb_t [F, N] int bins; pay_t [n_folds * C, N] f32 payload channels;
    slot_t [n_folds, N] f32 slot ids (n_slots drops the row). The fold
    axis batches independent slot assignments over the SAME binned matrix
    (CV fold masks AND fused config lanes in the tree sweep): one one-hot
    serves every lane and the contraction's M scales with n_folds. Ragged
    N pads with dropped-slot rows; the grid double-buffers the tiles.

    Two bodies, chosen from the arguments (pallas_rank_hist.hist_body):
    f32 mode over whole 128-bin groups, 1 024 bins and up (the rank
    metrics), factors the bin one-hot in two and takes the f32 payload as
    three exact bf16 parts — one where `unit_payload` vouches that every
    payload value is 0 or 1; all else builds a one-hot row a bin (_kernel).

    derive_count: append a unit-count channel computed IN VMEM as (last
    channel > 0): grow_tree's count_unit without an HBM plane (Co = C + 1).

    allow_bf16: bf16 contraction INPUTS (f32 accumulation) when the module
    flag agrees (_HIST_BF16) — the tree fits take it: one-hots, counts and
    integer payload rows under 256 exact, a real-valued row rounded once to
    bfloat16 unless `payload_parts` = 3 cuts the channels before the last
    into three exact bfloat16 parts (parts.unit_cuts: fixed-point for values
    the caller scaled into [-1, 1], their sums exact; the parts are summed
    here, the layout stays Co rows a slot); the rank metrics keep f32
    weights. Resolved OUTSIDE the jit, so set_hist_bf16 cannot serve
    stale-dtype programs.

    classes: K > 0 reads a lane's two planes as [class id, weight] and
    sums weight x (id == k) for k < K, then the count (_fold_payload):
    Co = K + 1, whole numbers under 256 exact in bfloat16 as a 0/1 label's.
    """
    use_bf16 = allow_bf16 and _HIST_BF16
    kw = dict(n_slots=n_slots, n_bins=n_bins, interpret=interpret,
              derive_count=derive_count)
    # imported here: pallas_rank_hist imports this module for its helpers
    from . import pallas_rank_hist as two
    if two.hist_body(n_bins, use_bf16) == "two_level":
        return two._hist_two_level_jit(
            Xb_t, pay_t, slot_t, parts=two.payload_parts(unit_payload), **kw)
    return _hist_pallas_jit(Xb_t, pay_t, slot_t, use_bf16=use_bf16,
                            parts=payload_parts if use_bf16 else 1,
                            classes=classes, **kw)


@functools.partial(jax.jit,
                   static_argnames=("n_slots", "n_bins", "interpret",
                                    "use_bf16", "derive_count", "parts",
                                    "classes"))
def _hist_pallas_jit(Xb_t, pay_t, slot_t, *, n_slots, n_bins,
                     interpret, use_bf16, derive_count=False, parts=1,
                     classes=0):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    F, N = Xb_t.shape
    n_folds = slot_t.shape[0]
    if pay_t.shape[0] % n_folds:
        raise ValueError(f"pay_t channels {pay_t.shape[0]} not a multiple "
                         f"of slot_t folds {n_folds}")
    C = pay_t.shape[0] // n_folds
    Co = payload_rows(C, parts, derive_count, classes)
    B = n_bins
    blk = block_rows(F * B)
    pad = (-N) % blk
    if pad:
        Xb_t = jnp.pad(Xb_t, ((0, 0), (0, pad)))
        pay_t = jnp.pad(pay_t, ((0, 0), (0, pad)))
        slot_t = jnp.pad(slot_t, ((0, 0), (0, pad)),
                         constant_values=float(n_slots))  # dropped
        N += pad

    kernel = functools.partial(_kernel, F=F, B=B, C=C, n_slots=n_slots,
                               n_folds=n_folds, use_bf16=use_bf16,
                               derive_count=derive_count, parts=parts,
                               classes=classes)
    return _sum_parts(pl.pallas_call(
        kernel,
        grid=(N // blk,),
        in_specs=[
            pl.BlockSpec((F, blk), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((n_folds * C, blk), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((n_folds, blk), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (n_folds * n_slots * Co, F * B), lambda i: (0, 0),
            memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (n_folds * n_slots * Co, F * B), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(Xb_t, pay_t, slot_t), C=C, parts=parts, derive_count=derive_count)


def _class_channels(pay_k, classes: int):
    """[class id, weight] [2, N] -> the K class channels weight x (id ==
    k) and the count [K + 1, N]: _fold_payload's `classes` rows, in jnp."""
    ids = jnp.arange(classes, dtype=jnp.float32)[:, None]
    return jnp.concatenate(
        [(ids == pay_k[0:1, :]).astype(jnp.float32) * pay_k[1:2, :],
         (pay_k[1:2, :] > 0.0).astype(jnp.float32)], axis=0)


def _hist_segment_jnp(Xb_t, pay_t, slot_t, *, n_slots, n_bins,
                      derive_count=False, classes=0):
    """Pure-jnp twin of hist_pallas (CPU/GPU fallback): one fused
    segment-sum per fold lane over (slot, feature, bin) cells, same
    [n_folds * n_slots * Co, F * B] output layout. Out-of-range slot ids
    (>= n_slots — padding / sibling-subtraction drops) land in a spill
    segment that is sliced away."""
    F, N = Xb_t.shape
    n_folds = slot_t.shape[0]
    C = pay_t.shape[0] // n_folds
    B = n_bins
    fb = (jnp.arange(F, dtype=jnp.int32)[:, None] * B
          + Xb_t.astype(jnp.int32))                          # [F, N]
    seg = n_slots * F * B

    def one_fold(slot_k, pay_k):
        if classes:
            pay_k = _class_channels(pay_k, classes)
        elif derive_count:
            cnt = (pay_k[C - 1:C, :] > 0.0).astype(jnp.float32)
            pay_k = jnp.concatenate([pay_k, cnt], axis=0)
        Co = pay_k.shape[0]
        slot_i = slot_k.astype(jnp.int32)                    # [N]
        ids = jnp.where(slot_i[None, :] >= n_slots, seg,
                        slot_i[None, :] * (F * B) + fb)      # [F, N]
        data = jnp.broadcast_to(pay_k[:, None, :], (Co, F, N))
        hist = jax.ops.segment_sum(
            data.reshape(Co, F * N).T, ids.reshape(-1),
            num_segments=seg + 1)[:seg]                      # [seg, Co]
        return hist.reshape(n_slots, F, B, Co) \
            .transpose(0, 3, 1, 2).reshape(n_slots * Co, F * B)

    pay_f = pay_t.reshape(n_folds, C, N)
    out = jax.vmap(one_fold)(slot_t, pay_f)                  # [Fo, S*Co, FB]
    return out.reshape(-1, F * B)


def hist_folds(Xb_t: jax.Array, pay_t: jax.Array, slot_t: jax.Array, *,
               n_slots: int, n_bins: int, interpret: bool = False,
               allow_bf16: bool = False, derive_count: bool = False,
               payload_parts: int = 1, classes: int = 0) -> jax.Array:
    """Batched multi-(fold x lane) histogram dispatcher: the VMEM pallas
    kernel on a live TPU (or in interpret mode for tests), the pure-jnp
    segment-sum fallback everywhere else — same signature and output
    layout as hist_pallas, so CPU CI exercises the exact call shape the
    TPU sweep runs. The twin sums float32 payloads as they are, which is
    what `payload_parts` = 3 gives the kernel. `classes` as in
    hist_pallas."""
    if interpret or available():
        return hist_pallas(Xb_t, pay_t, slot_t, n_slots=n_slots,
                           n_bins=n_bins, interpret=interpret,
                           allow_bf16=allow_bf16,
                           derive_count=derive_count,
                           payload_parts=payload_parts, classes=classes)
    return _hist_segment_jnp(Xb_t, pay_t, slot_t, n_slots=n_slots,
                             n_bins=n_bins, derive_count=derive_count,
                             classes=classes)


# -- level routing ----------------------------------------------------------
# Training-time routing (rel' = 2*rel + go_right) is one read of the binned
# matrix per level, but the XLA gather-free form (trees._onehot_route_step)
# materializes [chunk, F] f32 selection products in HBM — 48ms/level at the
# 10M-row config vs ~1ms of Xb traffic. Here everything lives in VMEM, and
# (like the histograms) a fold axis shares the Xb read across every CV
# fold's tree. The work is sized by the level's OWN node count, in node
# space: a level's tables name one feature a (lane, node), so ONE selection
# contraction [lanes * nodes, F] x [F, blk] picks every node's bin of every
# row for all lanes at once (0/1 against bin ids: exact in bf16), the
# decision is a compare a (lane, node) row against that node's threshold
# column, and a row takes the decision of its own node by an [nodes, blk]
# compare. No per-row table lookup is left: no node one-hot goes to the
# MXU, nothing is padded to 128 nodes, no [F, blk] mask a lane (PERF.md §6,
# PR 30: what a fused level adds to the histogram alone fell from 27 ms to
# 3 ms a 10M-row pass at 10 lanes on the v5e).

_ROUTE_BLK = 4096

# (lane, node) rows one selection contraction holds: lanes are taken in
# groups of this many rows, so the routing residents do not grow with the
# lane count (a 2 048-node level is one lane a group)
_ROUTE_GROUP_ROWS = 512


def node_rows(n: int, itemsize: int = 4) -> int:
    """Rows the routing and lookup kernels lay out for a table of `n`
    entries: `n` rounded up to one sublane tile of the array that carries
    the node axis (8 rows of f32, 16 of bf16) — 8 at the root's children,
    32 at depth 6's last level, 64 leaves — and to a multiple of 128 past
    128, where the axis is also a contraction's minor one. The ONE place
    the node axis is sized: the kernels, plan_fused_hist and the
    `route_node_rows` counter of the tree_fused span all read it."""
    tile = 32 // itemsize if n <= 128 else 128
    return -(-n // tile) * tile


def _group_lanes(n_r: int) -> int:
    """Whole lanes of `n_r` node rows in one selection contraction."""
    return max(_ROUTE_GROUP_ROWS // n_r, 1)


def route_group_rows(n_nodes: int, lanes: int) -> int:
    """(lane, node) rows of one selection contraction of the routing
    kernels at this level: whole lanes, up to _ROUTE_GROUP_ROWS."""
    n_r = node_rows(n_nodes)
    return min(_group_lanes(n_r), lanes) * n_r


def route_node_rows(depth: int) -> int:
    """Node rows the kernels lay out a lane for ONE depth-`depth` tree of
    the fused fit: its route_hist passes (levels 0..depth-2), the last
    level's route and the leaf lookup — 144 at depth 6, where padding
    every table to 128 was 896."""
    return sum(node_rows(1 << d) for d in range(depth)) \
        + node_rows(1 << depth, 2)


def _route_tables(f_lvl, t_lvl, m_lvl, *, n_nodes, n_feat):
    """A level's split tables in node space, (lane, node) rows lane-major
    with each lane's nodes padded to node_rows: `sel` [Fo * n_r, F] bf16,
    the one-hot of the row's split feature (padded rows select nothing),
    and `tm` [Fo * n_r, 2] f32, its threshold and missing direction."""
    Fo = f_lvl.shape[0]
    n_r = node_rows(n_nodes)
    pad = ((0, 0), (0, n_r - n_nodes))
    f = jnp.pad(f_lvl.astype(jnp.int32), pad, constant_values=-1)
    sel = (f[:, :, None] == jnp.arange(n_feat, dtype=jnp.int32)) \
        .astype(jnp.bfloat16).reshape(Fo * n_r, n_feat)
    tm = jnp.stack([jnp.pad(t_lvl.astype(jnp.float32), pad).reshape(-1),
                    jnp.pad(m_lvl.astype(jnp.float32), pad).reshape(-1)],
                   axis=1)
    return sel, tm, n_r


def _route_right(xf, node_ref, sel_ref, tm_ref, *, one_byte, n_r, n_folds):
    """Every lane's routing decision [1, blk] (f32 0/1) for one row block.

    xf [F, blk] f32 bins. Bin ids reach the MXU as bf16, exact up to 256:
    a one-byte matrix goes whole, a wider one as x = 256 * hi + lo (exact
    below 2^16). Each output of the selection contraction is one selected
    term, so `xs` holds the bin itself. A node id outside [0, n_nodes)
    owns no row of `dec` and goes left."""
    blk = xf.shape[1]
    if one_byte:
        lo, hi = xf.astype(jnp.bfloat16), None
    else:
        hi = jnp.floor(xf * (1.0 / 256.0))
        lo = (xf - 256.0 * hi).astype(jnp.bfloat16)
        hi = hi.astype(jnp.bfloat16)

    def select(sel, xp):                                    # [R, blk] f32
        return jax.lax.dot_general(sel, xp, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    ni = jax.lax.broadcasted_iota(jnp.int32, (n_r, blk), 0) \
        .astype(jnp.float32)
    group = _group_lanes(n_r)
    rights = []
    for k0 in range(0, n_folds, group):
        k1 = min(k0 + group, n_folds)
        rows = slice(k0 * n_r, k1 * n_r)
        sel = sel_ref[rows, :]                              # [R, F] bf16
        xs = select(sel, lo)
        if hi is not None:
            xs = 256.0 * select(sel, hi) + xs
        dec = jnp.logical_or(
            xs > tm_ref[rows, 0:1],
            jnp.logical_and(xs == 0.0, tm_ref[rows, 1:2] > 0.5))
        for k in range(k0, k1):
            own = jnp.logical_and(
                ni == node_ref[k:k + 1, :],
                dec[(k - k0) * n_r:(k - k0 + 1) * n_r, :])  # [n_r, blk]
            rights.append(jnp.max(own.astype(jnp.float32), axis=0,
                                  keepdims=True))
    return rights


def _route_kernel(xb_ref, node_ref, sel_ref, tm_ref, out_ref, *, n_r,
                  n_folds):
    xf = xb_ref[:].astype(jnp.float32)                      # [F, blk]
    rights = _route_right(xf, node_ref, sel_ref, tm_ref,
                          one_byte=xb_ref.dtype.itemsize == 1, n_r=n_r,
                          n_folds=n_folds)
    rows = [2.0 * node_ref[k:k + 1, :] + r for k, r in enumerate(rights)]
    out_ref[:] = rows[0] if n_folds == 1 else \
        jnp.concatenate(rows, axis=0)


@functools.partial(jax.jit, static_argnames=("n_nodes", "interpret"))
def route_pallas(Xb_t: jax.Array, node_t: jax.Array, f_lvl: jax.Array,
                 t_lvl: jax.Array, m_lvl: jax.Array, *, n_nodes: int,
                 interpret: bool = False) -> jax.Array:
    """One level of tree routing for every fold in one Xb pass.

    Xb_t [F, N] int bins; node_t [n_folds, N] f32 in-level node ids;
    f_lvl/t_lvl/m_lvl [n_folds, n_nodes] split tables. Returns the next
    level's ids [n_folds, N] f32 (2*node + right; right uses the learned
    missing direction for bin 0 — same decision as trees._onehot_route_step
    and the serving traversals). Out-of-range node ids (e.g. row padding)
    own no table entry and go left (2*node) — the caller slices padded
    rows away.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    F, N = Xb_t.shape
    n_orig = N
    Fo = node_t.shape[0]
    sel, tm, n_r = _route_tables(f_lvl, t_lvl, m_lvl, n_nodes=n_nodes,
                                 n_feat=F)
    blk = _ROUTE_BLK
    pad = (-N) % blk
    if pad:
        Xb_t = jnp.pad(Xb_t, ((0, 0), (0, pad)))
        node_t = jnp.pad(node_t, ((0, 0), (0, pad)),
                         constant_values=float(n_r))        # inert
        N += pad
    kernel = functools.partial(_route_kernel, n_r=n_r, n_folds=Fo)
    out = pl.pallas_call(
        kernel,
        grid=(N // blk,),
        in_specs=[
            pl.BlockSpec((F, blk), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((Fo, blk), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(sel.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(tm.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((Fo, blk), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Fo, N), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(Xb_t, node_t, sel, tm)
    return out[:, :n_orig]


def _route_level_jnp(Xb_t, node_t, f_lvl, t_lvl, m_lvl):
    """Gather-form twin of route_pallas's decision (CPU fallback). Node
    ids must be in-range [0, n_nodes) — true for every caller (routing
    always starts at node 0 and doubles)."""
    node_i = node_t.astype(jnp.int32)                        # [Fo, N]
    f = jnp.take_along_axis(f_lvl, node_i, axis=1)           # [Fo, N]
    t = jnp.take_along_axis(t_lvl, node_i, axis=1)
    mdir = jnp.take_along_axis(m_lvl, node_i, axis=1)
    xsel = jnp.take_along_axis(Xb_t.astype(jnp.int32), f, axis=0)
    right = (xsel > t) | ((xsel == 0) & (mdir > 0))
    return node_t * 2.0 + right.astype(jnp.float32)


def route(Xb_t: jax.Array, node_t: jax.Array, f_lvl: jax.Array,
          t_lvl: jax.Array, m_lvl: jax.Array, *, n_nodes: int,
          interpret: bool = False) -> jax.Array:
    """Level-routing dispatcher: route_pallas on a live TPU / in
    interpret mode, the gather form on CPU (identical decisions — the
    pallas selected-bin is a single exact one-hot term)."""
    if interpret or available():
        return route_pallas(Xb_t, node_t, f_lvl, t_lvl, m_lvl,
                            n_nodes=n_nodes, interpret=interpret)
    return _route_level_jnp(Xb_t, node_t, f_lvl, t_lvl, m_lvl)


# -- fused route + histogram ------------------------------------------------
# One pass of the binned matrix per level instead of two: the level-d
# split tables route every row IN VMEM (_route_right: the routing above,
# at this level's node count) and the surviving (left-child) slot ids
# feed the level-(d+1) histogram contraction in the same grid step — the
# route pass's separate HBM read of Xb disappears. Works because
# new_node = 2*node + right is even exactly when the row goes left, and
# sibling subtraction histograms LEFT children only: the level-(d+1) slot
# id of a left row is its OLD node id, known the moment `right` is
# computed. Fold lanes (CV folds x fused config lanes) share the Xb read
# and the (feature, bin) one-hot exactly as in _kernel.


def _route_hist_kernel(xb_ref, pay_ref, node_ref, sel_ref, tm_ref, hist_ref,
                       node_out_ref, *, F: int, B: int, C: int, n_nodes: int,
                       n_r: int, n_folds: int,
                       use_bf16=False, derive_count=False, parts=1,
                       classes=0):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _():
        hist_ref[:] = jnp.zeros_like(hist_ref)

    blk = xb_ref.shape[1]
    mxu_dtype = jnp.bfloat16 if use_bf16 else jnp.float32
    xf = xb_ref[:].astype(jnp.float32)                      # [F, blk]
    oh = _feature_onehot(xf, F=F, B=B, blk=blk, use_bf16=use_bf16)
    rights = _route_right(xf, node_ref, sel_ref, tm_ref,
                          one_byte=xb_ref.dtype.itemsize == 1, n_r=n_r,
                          n_folds=n_folds)
    slots = jax.lax.broadcasted_iota(jnp.int32, (n_nodes, blk), 0) \
        .astype(jnp.float32)
    Co = payload_rows(C, parts, derive_count, classes)
    rows, qs = [], []
    for k in range(n_folds):
        node = node_ref[k:k + 1, :]                         # [1, blk]
        rightf = rights[k]                                  # [1, blk]
        rows.append(2.0 * node + rightf)
        # next level's LEFT-child slot id = old node for left rows; right
        # rows shift past the iota range (node + n_nodes >= n_nodes) —
        # the same dropped-slot encoding hist_pallas uses for padding
        slot_oh = (slots == node + float(n_nodes) * rightf) \
            .astype(mxu_dtype)                              # [n_nodes, blk]
        pay = _fold_payload(pay_ref, k, C, mxu_dtype, derive_count, parts,
                            classes)
        qs.append((slot_oh[:, None, :] * pay[None, :, :])
                  .reshape(n_nodes * Co, blk))
    q = qs[0] if n_folds == 1 else jnp.concatenate(qs, axis=0)
    hist_ref[:] += jax.lax.dot_general(
        q, oh, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # [Fo*S*Co, F*B]
    node_out_ref[:] = rows[0] if n_folds == 1 else \
        jnp.concatenate(rows, axis=0)


@functools.partial(jax.jit,
                   static_argnames=("n_nodes", "n_bins", "interpret",
                                    "use_bf16", "derive_count", "parts",
                                    "classes"))
def _route_hist_pallas_jit(Xb_t, pay_t, node_t, f_lvl, t_lvl, m_lvl, *,
                           n_nodes, n_bins, interpret, use_bf16,
                           derive_count=False, parts=1, classes=0):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    F, N = Xb_t.shape
    n_orig = N
    Fo = node_t.shape[0]
    if pay_t.shape[0] % Fo:
        raise ValueError(f"pay_t channels {pay_t.shape[0]} not a multiple "
                         f"of node_t folds {Fo}")
    C = pay_t.shape[0] // Fo
    Co = payload_rows(C, parts, derive_count, classes)
    B = n_bins
    sel, tm, n_r = _route_tables(f_lvl, t_lvl, m_lvl, n_nodes=n_nodes,
                                 n_feat=F)
    blk = block_rows(F * B)
    pad = (-N) % blk
    if pad:
        Xb_t = jnp.pad(Xb_t, ((0, 0), (0, pad)))
        pay_t = jnp.pad(pay_t, ((0, 0), (0, pad)))
        # padded rows carry node id n_r: they own no table entry (go
        # left, then are sliced away) and can never match a histogram
        # slot (payload is zero anyway)
        node_t = jnp.pad(node_t, ((0, 0), (0, pad)),
                         constant_values=float(n_r))
        N += pad

    kernel = functools.partial(_route_hist_kernel, F=F, B=B, C=C,
                               n_nodes=n_nodes, n_r=n_r, n_folds=Fo,
                               use_bf16=use_bf16, derive_count=derive_count,
                               parts=parts, classes=classes)
    hist, node_out = pl.pallas_call(
        kernel,
        grid=(N // blk,),
        in_specs=[
            pl.BlockSpec((F, blk), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((Fo * C, blk), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((Fo, blk), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(sel.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(tm.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((Fo * n_nodes * Co, F * B), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((Fo, blk), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Fo * n_nodes * Co, F * B), jnp.float32),
            jax.ShapeDtypeStruct((Fo, N), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(Xb_t, pay_t, node_t, sel, tm)
    return _sum_parts(hist, C=C, parts=parts, derive_count=derive_count), \
        node_out[:, :n_orig]


def route_hist(Xb_t: jax.Array, pay_t: jax.Array, node_t: jax.Array,
               f_lvl: jax.Array, t_lvl: jax.Array, m_lvl: jax.Array, *,
               n_nodes: int, n_bins: int, interpret: bool = False,
               allow_bf16: bool = False, derive_count: bool = False,
               payload_parts: int = 1, classes: int = 0):
    """Route one level AND histogram the next level's left children in a
    single pass over the binned matrix, for every (fold x config) lane.

    Xb_t [F, N] int bins; pay_t [Fo * C, N] f32 payload channels (g/h per
    lane, fold-major; derive_count appends the in-VMEM unit-count
    channel); node_t [Fo, N] f32 in-level node ids; f_lvl/t_lvl/m_lvl
    [Fo, n_nodes] the level's split tables. Returns (hist, new_node):
    hist [Fo * n_nodes * Co, F * n_bins] — the level-(d+1) LEFT-child
    histograms (n_slots = this level's n_nodes, sibling-subtraction
    layout) — and new_node [Fo, N] = 2*node + right, bitwise what
    route_pallas returns. On CPU the jnp fallback chains the gather-form
    route with the segment-sum histogram (identical decisions; histogram
    equal up to f32 summation order). `payload_parts` and `classes` as in
    hist_pallas.
    """
    if interpret or available():
        use_bf16 = allow_bf16 and _HIST_BF16
        return _route_hist_pallas_jit(
            Xb_t, pay_t, node_t, f_lvl, t_lvl, m_lvl, n_nodes=n_nodes,
            n_bins=n_bins, interpret=interpret, use_bf16=use_bf16,
            derive_count=derive_count,
            parts=payload_parts if use_bf16 else 1, classes=classes)
    return _route_hist_jnp(Xb_t, pay_t, node_t, f_lvl, t_lvl, m_lvl,
                           n_nodes=n_nodes, n_bins=n_bins,
                           derive_count=derive_count, classes=classes)


def _route_hist_jnp(Xb_t, pay_t, node_t, f_lvl, t_lvl, m_lvl, *, n_nodes,
                    n_bins, derive_count=False, classes=0):
    """Pure-jnp twin of the fused route+hist kernel: the gather-form
    route chained with the segment-sum histogram."""
    new_node = _route_level_jnp(Xb_t, node_t, f_lvl, t_lvl, m_lvl)
    right = new_node - 2.0 * node_t                          # 0/1
    slots = node_t + float(n_nodes) * right                  # left keeps id
    hist = _hist_segment_jnp(Xb_t, pay_t, slots, n_slots=n_nodes,
                             n_bins=n_bins, derive_count=derive_count,
                             classes=classes)
    return hist, new_node


# -- leaf lookup ------------------------------------------------------------
# out[k, i] = tbl[k, idx[k, i]] keeps a per-row one-hot (the value IS a
# table entry), but the one-hot goes to the MXU as bf16 (0/1: exact) at
# the table's own width, and it is the tiny TABLE that is cut into three
# bf16 parts, once, outside the row loop: hi + mid + lo restores the f32
# bit for bit, and each part's output is a single selected term. One bf16
# pass a lane with the three parts as three rows of its lhs tile.

_LOOKUP_ROWS = 16   # a lane's lhs tile: one bf16 sublane tile, 3 rows used


def _lookup_kernel(tbl_ref, idx_ref, out_ref, *, m_r, n_folds):
    blk = idx_ref.shape[1]
    mi = jax.lax.broadcasted_iota(jnp.int32, (m_r, blk), 0) \
        .astype(jnp.float32)
    rows = []
    for k in range(n_folds):
        idx = idx_ref[k:k + 1, :]                           # [1, blk]
        noh = (mi == idx).astype(jnp.bfloat16)              # [m_r, blk]
        p = jax.lax.dot_general(
            tbl_ref[_LOOKUP_ROWS * k:_LOOKUP_ROWS * (k + 1), :], noh,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [16, blk]
        rows.append(p[0:1, :] + p[1:2, :] + p[2:3, :])
    out_ref[:] = rows[0] if n_folds == 1 else \
        jnp.concatenate(rows, axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def table_lookup_pallas(tbl: jax.Array, idx_t: jax.Array, *,
                        interpret: bool = False) -> jax.Array:
    """Per-fold small-table lookup out[k, i] = tbl[k, idx[k, i]].

    tbl [n_folds, M] f32 (e.g. leaf payloads); idx_t [n_folds, N] f32 ids.
    Out-of-range ids (>= M, e.g. row padding) return 0. TPU gathers from
    tiny tables by huge index vectors serialize; the one-hot contraction
    here stays on the MXU/VPU and reads idx_t exactly once. Values come
    back bit for bit (finite f32 whose lowest part is not subnormal).
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Fo, M = tbl.shape
    N = idx_t.shape[1]
    n_orig = N
    m_r = node_rows(M, 2)
    parts = jnp.stack([p.astype(jnp.bfloat16) for p in
                       _parts.float32_parts(tbl, jnp.bfloat16)], axis=1)
    tblp = jnp.pad(parts, ((0, 0), (0, _LOOKUP_ROWS - 3), (0, m_r - M))) \
        .reshape(Fo * _LOOKUP_ROWS, m_r)                    # [16 Fo, m_r]
    blk = _ROUTE_BLK
    pad = (-N) % blk
    if pad:
        idx_t = jnp.pad(idx_t, ((0, 0), (0, pad)),
                        constant_values=float(m_r))         # -> 0
        N += pad
    kernel = functools.partial(_lookup_kernel, m_r=m_r, n_folds=Fo)
    return pl.pallas_call(
        kernel,
        grid=(N // blk,),
        in_specs=[
            pl.BlockSpec(tblp.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((Fo, blk), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((Fo, blk), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Fo, N), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(tblp, idx_t)[:, :n_orig]


def table_lookup(tbl: jax.Array, idx_t: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """Per-fold table-lookup dispatcher: the one-hot contraction kernel
    on a live TPU / in interpret mode, a plain gather on CPU (same
    out-of-range -> 0 contract)."""
    if interpret or available():
        return table_lookup_pallas(tbl, idx_t, interpret=interpret)
    return _table_lookup_jnp(tbl, idx_t)


def _table_lookup_jnp(tbl: jax.Array, idx_t: jax.Array) -> jax.Array:
    """Gather twin of table_lookup_pallas (out-of-range ids -> 0)."""
    M = tbl.shape[1]
    idx = idx_t.astype(jnp.int32)
    vals = jnp.take_along_axis(tbl, jnp.clip(idx, 0, M - 1), axis=1)
    return jnp.where((idx >= 0) & (idx < M), vals, 0.0)


# -- forest lane groups -------------------------------------------------------
# A forest's (tree, fold) lanes go through the fused passes a GROUP at a
# time (ops/trees.fit_forest_lanes). What bounds a group is what bounds
# the boosters' lanes — the VMEM plan, the fused output block, the lane
# planes in HBM — at other figures: a forest lane carries five row planes
# (two payload channels, node ids in and out, leaf rows) where a booster
# lane carries four, and the planes' sublane axis pads to 8 in HBM. A
# K-class group lays out the same five a lane (its two payload planes are
# [class id, weight]: the K channels are built in VMEM) and, a GROUP, the
# votes it takes and hands back: [folds, K, rows] twice, K padded to 8.

#: cap of the deepest level's output block [lanes * slots * 3, F * B] f32.
#: Three quarters of the 16 MB at which r5 saw 20-minute Mosaic compiles;
#: PERF.md §6 (PR 31) has the compile and pass times that were measured
#: under it.
_FOREST_OUT_BLOCK_BYTES = 12 << 20
_FOREST_LANE_PLANES = 5


def _pad8(n: int) -> int:
    return -(-n // 8) * 8


def forest_group_planes(lanes: int, n_folds: int, classes: int = 0) -> int:
    """float32 row planes a lane group of the forest route lays out in
    HBM: _FOREST_LANE_PLANES a lane (the lane axis padded to 8) and, under
    K class channels, the votes in and out, [n_folds, K -> 8, rows] each.
    THE count plan_forest_group budgets."""
    votes = 2 * n_folds * _pad8(classes) if classes else 0
    return _FOREST_LANE_PLANES * _pad8(lanes) + votes


def plan_forest_group(n_rows: int, n_feat: int, n_bins: int, n_folds: int,
                      n_trees: int, depth: int, payload_rows: int = 3,
                      classes: int = 0) -> int:
    """Trees a lane group of the forest route: the most whose (tree x
    fold) lanes clear plan_fused_hist, the output-block cap and 7/16 of
    the device's HBM in row planes (forest_group_planes; rows count here:
    the planes are what grows with them), then evened out over the groups
    the forest needs —
    20 trees at a most of 6 a group make 4 groups of 5, not 3 of 6 and a
    2. 0: not even one tree's fold lanes fit (depth 12: the slot-dense
    output block alone is 130 MB; K class channels past ~17 at five folds
    and depth 6), and the caller keeps its sequential
    path. `n_bins` counts the missing-value bin; `payload_rows` the rows a
    (lane, slot) the kernels issue (3, 5 under a three-part payload, K + 1
    under `classes` = K class channels: the output block the kernel holds
    grows with them, the lanes a group shrink)."""
    from ..utils.platform import device_spec
    spec = device_spec()

    def ok(trees: int) -> bool:
        lanes = trees * n_folds
        plan = plan_fused_hist(n_feat, n_bins, lanes, depth, payload_rows)
        planes = forest_group_planes(lanes, n_folds, classes) * n_rows * 4
        return (plan.fits and plan.out_bytes <= _FOREST_OUT_BLOCK_BYTES
                and (spec is None or planes <= spec.hbm_bytes * 7 // 16))

    most = 0
    while most < n_trees and ok(most + 1):
        most += 1
    if most == 0:
        return 0
    groups = -(-n_trees // most)
    return -(-n_trees // groups)
