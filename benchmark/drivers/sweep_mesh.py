"""Closed loop of one client over `CrossValidation.validate()` on a matrix
that lives ROW-SHARDED over the chips of one host: the call a ModelSelector
makes when the table outgrows one chip — upstream's only deployment, a
row-partitioned table over several executors. The driver passes no mesh:
the program reads the layout from where the matrix lives.

A sibling of drivers/sweep.py, whose set-up, job, route check and loop it
runs by import. What differs: the program is asked BEFORE any data is made
whether it declares the sharded-resident route (a program without it would
gather a 16 GB matrix through the host, or sweep on chip 0 alone) and is
refused if not; the data is made shard by shard on the chips
(benchmark/datagen_mesh.py) over the first `chips` devices JAX reports; the
layout the warm-up job ran on is read from its own spans and telemetry;
and the answer is held to benchmark/reference_mesh.py.
"""
from __future__ import annotations

import contextlib

from benchmark import datagen_mesh, harness, reference, reference_mesh

sweep = harness.load_module("drivers", "sweep")


def _require_route() -> None:
    from transmogrifai_tpu.automl.tuning import folds
    from transmogrifai_tpu.parallel import mesh
    if not (hasattr(mesh, "resident_row_mesh")
            and hasattr(folds, "assign_fold_masks_sharded")):
        raise harness.BenchFailure(
            "the program declares no sharded-resident route "
            "(parallel/mesh.resident_row_mesh, automl/tuning/folds."
            "assign_fold_masks_sharded): validate() would fetch the "
            "sharded matrix through the host or sweep on one chip; "
            "nothing was made or measured")


@contextlib.contextmanager
def _data_on(devices):
    """drivers/sweep.py's set-up with its matrix made over `devices`."""
    class Sharded:
        @staticmethod
        def device_matrix(rows, cols, dtype, seed):
            return datagen_mesh.sharded_matrix(rows, cols, dtype, seed,
                                               devices)
    plain, sweep.datagen = sweep.datagen, Sharded
    try:
        yield
    finally:
        sweep.datagen = plain


def _checks(ctx) -> dict:
    return {k: dict(c, **(c.get("rehearsal", {}) if ctx.rehearse else {}))
            for k, c in ctx.cell.get("checks", {}).items()}


def _warmup_spans() -> list:
    """(kind, name, attrs) of the warm-up job's spans: the collector keeps
    its last tree after it is switched off."""
    from transmogrifai_tpu.utils.metrics import collector
    return [(s.kind, s.name, dict(s.attrs)) for s in collector.trace.spans]


def setup(ctx):
    import jax
    _require_route()
    chips = ctx.cell["chips"]
    if len(jax.devices()) < chips:
        raise harness.BenchFailure(
            f"the cell shards over {chips} devices, JAX reports "
            f"{len(jax.devices())}")
    devices = jax.devices()[:chips]
    with _data_on(devices):
        st = sweep.setup(ctx)
    st.devices = devices
    _check_layout(ctx, st, _warmup_spans())
    return st


def _check_layout(ctx, st, spans) -> None:
    """Where the warm-up job ran, from its own record: every span that
    names a layout and the streamed telemetry must say the same thing."""
    expect = ctx.cell["expect"]
    shards = expect["shards"]
    tele = dict(st.last_val.last_streamed_telemetry or {})
    by = {}
    for kind, name, attrs in spans:
        by.setdefault((kind, name.split("[")[0].split(":")[0]), []) \
            .append(attrs)
    root = by.get(("validate", "CrossValidation"), [{}])[0]
    place = by.get(("validate_phase", "device_place"), [{}])[0]
    folds = by.get(("validate_phase", "fold_assign"), [{}])[0]
    rounds = by.get(("sweep_round", "glm_round"), [])
    evals = by.get(("sweep_eval", "glm_streamed_eval"), [{}])[0]
    lay = ctx.notes["layout"] = {
        "validate_shards": root.get("shards"),
        "fold_assign_shards": folds.get("shards"),
        "device_place": {k: place.get(k) for k in ("route", "h2d_bytes")},
        "round_shards": sorted({r.get("shards") for r in rounds}),
        "round_psums": sorted({r.get("psums") for r in rounds}),
        "eval": {k: evals.get(k) for k in ("eval_route", "shards")},
        "telemetry": {k: tele.get(k) for k in (
            "shards", "rows_per_shard", "psums", "psum_bytes", "eval_route",
            "passes", "glm_rounds", "data_passes")}}
    rows = ctx.sizes["rows"]
    for what, got, want in (
            ("validate shards", lay["validate_shards"], shards),
            ("fold_assign shards", lay["fold_assign_shards"], shards),
            ("device_place", lay["device_place"],
             {"route": expect["place_route"],
              "h2d_bytes": expect["h2d_bytes"]}),
            ("sweep_round shards", lay["round_shards"], [shards]),
            ("sweep_eval", lay["eval"],
             {"eval_route": expect["eval_route"], "shards": shards}),
            ("eval_route", tele.get("eval_route"), expect["eval_route"]),
            ("telemetry shards", tele.get("shards"), shards),
            ("telemetry rows_per_shard", tele.get("rows_per_shard"),
             rows // shards),
            # one collective an iteration and one a round program, one a
            # chunk of the metric pass
            ("telemetry psums", tele.get("psums"),
             tele.get("data_passes", 0) + tele.get("glm_rounds", 0)
             + tele.get("passes", 0))):
        ctx.require(got == want, f"{what}: {got!r}, not {want!r}")
    if not ctx.rehearse:
        # the Pallas histogram kernel saw a chip's LOCAL rows
        local = [c for c in st.spy_calls if c["kernel"] == "hist_pallas"]
        ctx.require(bool(local) and all(
            c["shapes"][0][-1] == rows // shards for c in local),
            f"hist_pallas was called on {[c['shapes'][0] for c in local]}, "
            f"not on a chip's {rows // shards} rows")


def run_window(ctx, st) -> harness.Result:
    result = sweep.run_window(ctx, st)
    tele = st.last_val.last_streamed_telemetry or {}
    shards = len(st.devices)
    ctx.counters.update(
        shards=shards, rows_per_chip=ctx.sizes["rows"] // shards,
        x4_collectives_per_job=tele.get("psums"),
        x4_psum_bytes_per_job=tele.get("psum_bytes"))
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in st.devices]
    ctx.notes["memory_peak_bytes_by_chip"] = peaks
    if not ctx.rehearse:
        ctx.require(
            min(peaks) >= (1.0 - ctx.cell["expect"]["memory_balance"])
            * max(peaks),
            f"memory peaks {peaks}: a chip holds less than "
            f"{1.0 - ctx.cell['expect']['memory_balance']:.0%} of the "
            f"fullest")
    return result


def verify(ctx, st) -> None:
    """The sweep that ran, held to benchmark/reference_mesh.py on the very
    arrays it ran on; the masks are the program's own, made again on the
    mesh (a function of the seed and the row count alone)."""
    from transmogrifai_tpu.parallel.mesh import resident_row_mesh
    c = _checks(ctx)["mesh_answer"]
    grids = next(g for fam, _, _, g in st.pool if fam == c["family"])
    masks = st.last_val.device_fold_masks(st.y,
                                          mesh=resident_row_mesh(st.X))
    ctx.notes["mesh_answer"] = {}
    try:
        reference_mesh.mesh_sweep_answer(
            st.last_best, st.streamed_fits, masks, grids, st.X, st.y,
            into=ctx.notes["mesh_answer"], cv_seed=ctx.sizes["cv_seed"],
            reference_fold=c["reference_fold"],
            reference_rows=c["reference_rows"],
            metric_offset_lo=c["metric_offset_lo"],
            metric_offset_hi=c["metric_offset_hi"],
            tol_reference=c["tol_reference"],
            tol_intercept_gradient=c["tol_intercept_gradient"])
    except reference.CheckFailure as e:
        ctx.require(False, f"reference check failed: {e}")
