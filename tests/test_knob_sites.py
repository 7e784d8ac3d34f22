"""Each hot-path decision reads ONE default (and at most one TMOG_* knob)
at the site that uses it.

Per site: the default through the site's own function, the knob's
override honoured, an unparsable value read as the default without a
raise, the two clamps, the TMOG_GRID_FUSE whitelist, the validator's row
floor, and the direction of the package's imports (ops/ and parallel/
reach for nothing above them; nothing imports a planner).
"""
import ast
import functools
import os

import jax
import jax.numpy as jnp
import pytest

import transmogrifai_tpu
from transmogrifai_tpu.automl.tuning import validators as V
from transmogrifai_tpu.automl.tuning.validators import CrossValidation
from transmogrifai_tpu.evaluators.evaluators import Evaluators
from transmogrifai_tpu.models.glm import OpLogisticRegression
from transmogrifai_tpu.ops import glm_sweep as GS
from transmogrifai_tpu.ops import pallas_hist as PH
from transmogrifai_tpu.ops import stats_engine as SE
from transmogrifai_tpu.parallel import ingest as ING
from transmogrifai_tpu.parallel import tileplane as TP
from transmogrifai_tpu.readers import streaming as RS
from transmogrifai_tpu.serve.engine import bucket_ladder
from transmogrifai_tpu.utils import env as E

KNOB_NAMES = ("TMOG_GRID_FUSE", "TMOG_GRID_FUSE_HBM_LANES",
              "TMOG_GRID_FUSE_OUT_MB", "TMOG_TILE_MB", "TMOG_TILE_PREFETCH",
              "TMOG_INGEST_WORKERS", "TMOG_STATS_TILE_ROWS",
              "TMOG_SCORE_TILE_ROWS")


@pytest.fixture(autouse=True)
def _no_ambient_knobs(monkeypatch):
    for name in KNOB_NAMES:
        monkeypatch.delenv(name, raising=False)
    # the chunker's two caps are read apart from its VMEM gate, whose
    # off-TPU budget is far below either
    monkeypatch.setattr(PH, "_vmem_limit", lambda: 1 << 40)


def _lane_capped_chunk():
    """4 folds x 64 configs of a tiny histogram: only the HBM lane
    budget binds (16 configs x 4 folds = 64 lanes)."""
    return PH.plan_lane_chunk(4, 5, 4, 64, 2)


def _out_capped_chunk():
    """1 fold x 64 configs, 64 columns x 33 bins at depth 6: only the
    out-block cap binds (16 configs = 6.49 MB, 32 = 12.98 MB)."""
    return PH.plan_lane_chunk(64, 33, 1, 64, 6)


# site name -> (read through the site's own function, the default)
SITES = {
    "glm_streamed_min_rows": (lambda: V.STREAMED_SWEEP_MIN_ROWS, 200_000),
    "grid_fuse": (V.grid_fuse_on, False),
    "grid_fuse_hbm_lanes": (_lane_capped_chunk, 16),
    "grid_fuse_out_mb": (_out_capped_chunk, 16),
    "glm_bucket_floor": (lambda: GS.bucket_lanes(1), 8),
    "serve_bucket_floor": (lambda: bucket_ladder(64), (1, 8, 16, 32, 64)),
    "tile_mb": (TP.tile_budget_bytes, 32 << 20),
    "tile_prefetch": (TP.tile_prefetch_depth, 1),
    "ingest_workers": (ING.ingest_workers, 1),
    "stats_tile_rows": (SE.stream_tile_rows_default, 1 << 18),
    "score_tile_rows": (RS.score_tile_rows_default, 1024),
}

# knob -> (site, a value to set, what the site then returns)
OVERRIDES = {
    "TMOG_GRID_FUSE": ("grid_fuse", "1", True),
    "TMOG_GRID_FUSE_HBM_LANES": ("grid_fuse_hbm_lanes", "63", 8),
    "TMOG_GRID_FUSE_OUT_MB": ("grid_fuse_out_mb", "6.4", 8),
    "TMOG_TILE_MB": ("tile_mb", "8", 8 << 20),
    "TMOG_TILE_PREFETCH": ("tile_prefetch", "3", 3),
    "TMOG_INGEST_WORKERS": ("ingest_workers", "4", 4),
    "TMOG_STATS_TILE_ROWS": ("stats_tile_rows", "4096", 4096),
    "TMOG_SCORE_TILE_ROWS": ("score_tile_rows", "0", 0),
}


@pytest.mark.parametrize("site", sorted(SITES))
def test_default(site):
    read, default = SITES[site]
    assert read() == default


@pytest.mark.parametrize("knob", KNOB_NAMES)
def test_override_honoured(knob, monkeypatch):
    site, raw, expected = OVERRIDES[knob]
    monkeypatch.setenv(knob, raw)
    assert SITES[site][0]() == expected


@pytest.mark.parametrize("knob", KNOB_NAMES)
def test_unparsable_reads_as_default(knob, monkeypatch):
    read, default = SITES[OVERRIDES[knob][0]]
    monkeypatch.setenv(knob, "3 tiles, please")
    assert read() == default
    monkeypatch.setenv(knob, "")
    assert read() == default


@pytest.mark.parametrize("knob,read", [
    ("TMOG_TILE_PREFETCH", TP.tile_prefetch_depth),
    ("TMOG_INGEST_WORKERS", ING.ingest_workers)])
def test_clamped_at_one(knob, read, monkeypatch):
    monkeypatch.setenv(knob, "0")
    assert read() == 1
    monkeypatch.setenv(knob, "-3")
    assert read() == 1


@pytest.mark.parametrize("raw,on", [
    ("1", True), ("true", True), ("on", True), (" ON ", True),
    ("yes", False), ("0", False), ("", False)])
def test_grid_fuse_is_a_whitelist(raw, on, monkeypatch):
    monkeypatch.setenv("TMOG_GRID_FUSE", raw)
    assert V.grid_fuse_on() is on


def test_env_on_keeps_its_falsy_list(monkeypatch):
    """env_on is the other parse: on unless 0 / false / off, so a kill
    switch left unset, or set to anything else, leaves the path on."""
    monkeypatch.delenv("TMOG_TILEPLANE", raising=False)
    assert E.env_on("TMOG_TILEPLANE")
    for raw, on in (("0", False), ("False", False), (" off ", False),
                    ("yes", True), ("1", True), ("", True)):
        monkeypatch.setenv("TMOG_TILEPLANE", raw)
        assert E.env_on("TMOG_TILEPLANE") is on
        assert TP.tileplane_enabled() is on


# -- the validator's row floor -----------------------------------------------

def _streamable(rows, warm_seed=None):
    val = CrossValidation(Evaluators.BinaryClassification.au_pr(),
                          num_folds=5, seed=42)
    val.warm_seed = warm_seed
    X = jax.ShapeDtypeStruct((rows, 64), jnp.bfloat16)
    grids = [{"reg_param": r} for r in (0.001, 0.01, 0.1)]
    return val._streamable(OpLogisticRegression(), grids, "binary", X, 5)


# only the streamed rounds consume a warm seed, so a seeded refit takes
# them at any size
_SEED = {"beta": [0.0] * 64}


@pytest.mark.parametrize("rows,floor,warm_seed,streamed", [
    (199_999, None, None, False),
    (200_000, None, None, True),
    (1_000, 1_000, None, True),        # a reassigned floor is honoured
    (10 ** 9, 10 ** 15, None, False),  # in both directions
    (100, None, _SEED, True),
])
def test_row_floor(rows, floor, warm_seed, streamed, monkeypatch):
    if floor is not None:
        monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", floor)
    assert _streamable(rows, warm_seed) is streamed


# -- import direction --------------------------------------------------------

_PKG = os.path.dirname(transmogrifai_tpu.__file__)


def _package_imports(path):
    """Sub-packages of transmogrifai_tpu that the file at `path` imports,
    function-level imports included."""
    here = ["transmogrifai_tpu"] + \
        os.path.relpath(path, _PKG).split(os.sep)[:-1]
    found = set()
    with open(path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read())
    for node in ast.walk(tree):
        targets = []
        if isinstance(node, ast.Import):
            targets = [a.name.split(".") for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = here[:len(here) - (node.level - 1)] if node.level else []
            mod = base + (node.module.split(".") if node.module else [])
            # `from . import x` / `from transmogrifai_tpu import x`
            targets = [mod + [a.name] for a in node.names] \
                if len(mod) == 1 else [mod]
        for t in targets:
            if t[0] == "transmogrifai_tpu" and len(t) > 1:
                found.add(t[1])
    return found


@functools.lru_cache(maxsize=None)
def _imports_by_subpackage():
    by = {}
    for d, _, files in os.walk(_PKG):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(d, f)
                top = os.path.relpath(path, _PKG).split(os.sep)[0]
                by.setdefault(top, set()).update(_package_imports(path))
    return by


@pytest.mark.parametrize("layer,allowed", [
    ("ops", {"native", "parallel", "utils"}),
    ("parallel", {"readers", "utils"})])
def test_layer_imports_nothing_above_it(layer, allowed):
    got = _imports_by_subpackage()[layer] - {layer}
    assert got <= allowed, sorted(got - allowed)


def test_nothing_imports_a_planner():
    assert not os.path.exists(os.path.join(_PKG, "planner"))
    by = _imports_by_subpackage()
    assert len(by) > 20 and by["ops"], "the walk saw no imports"
    assert all("planner" not in subs for subs in by.values())
    # the knob parse sits below every layer: it imports nothing of ours
    assert _package_imports(os.path.join(_PKG, "utils", "env.py")) == set()
