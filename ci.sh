#!/usr/bin/env bash
# CI recipe (reference: .circleci/config.yml:35-62 — style -> compile ->
# parallel test). The TPU-native equivalents:
#   1. lint-ish import check (no compile step in pure Python; the native
#      kernel library builds on demand and must compile cleanly)
#   2. full pytest on an 8-device virtual CPU mesh (tests/conftest.py sets
#      XLA_FLAGS=--xla_force_host_platform_device_count=8 — the analogue of
#      the reference testing distribution on local[2] Spark)
#   3. the three helloworld example flows
#   4. driver-contract smoke: dryrun_multichip + a reduced-size bench that
#      must emit one parseable JSON line
set -euo pipefail
cd "$(dirname "$0")"

echo "== 1/7 import + native kernel build =="
python - <<'PY'
import transmogrifai_tpu
from transmogrifai_tpu.ops import native_bridge
print("package import ok; native kernels:",
      "built" if native_bridge.available() else "UNAVAILABLE (numpy fallbacks)")
PY

echo "== 2/7 tmoglint (static JAX/TPU discipline + stage contracts) =="
# fails fast on findings not in tools/tmoglint/baseline.json and on stale
# baseline entries (docs/static_analysis.md); runs before the test tiers
# because it needs no imports and catches contract breaks in seconds.
# bench.py + tools/ are in scope since TPU005 (unsynced-wall-timing);
# the v2 concurrency (THR001-004) + buffer-lifetime (BUF001-003)
# families, the v3 SPMD/collective-correctness (SHD001-005) +
# contract-drift (ENV001/EVT001) families and the v4 trace-contract
# (TRC001-005) family all run in the same scan with the SAME empty
# baseline — SHD is the pre-hardware gate for the multi-host GSPMD push
# (correct-at-N=1/wrong-at-N>1 bugs the CPU-mesh tiers cannot see),
# ENV/EVT keep the knob registry and the event table honest, TRC
# statically proves the zero-recompile contract no CPU tier can time-out
# on (correct on the warm test box, wrong on hardware). The --format
# json report is saved as a CI artifact so finding counts per rule ride
# the build outputs next to the BENCH_*.json
# series, and the documented 10s full-scan budget is asserted from its
# --stats block.
ARTIFACTS_DIR="${TMOG_CI_ARTIFACTS:-$(mktemp -d)}"
mkdir -p "$ARTIFACTS_DIR"
# one gating scan, captured as the JSON artifact (it carries ok/new/
# stale + the --stats timings the assert below surfaces); a nonzero rc
# stops CI right here under `set -e`
python -m tools.tmoglint transmogrifai_tpu/ tests/ bench.py tools/ \
  --format json > "$ARTIFACTS_DIR/tmoglint_report.json"
python - "$ARTIFACTS_DIR/tmoglint_report.json" <<'PY'
import json, subprocess, sys
rep = json.load(open(sys.argv[1]))
assert rep["ok"], rep
assert "stats" in rep and rep["stats"]["files"] > 150, rep.get("stats")
# the documented budget (docs/static_analysis.md "Running"): a full-repo
# --jobs scan, every family on, stays under 10s. Wall time on a shared
# runner is noisy, so a miss gets ONE quiet re-measure before failing —
# the budget gates linter regressions, not runner load spikes.
total = rep["stats"]["total_s"]
rerun = None
if total >= 10.0:
    out = subprocess.run(
        [sys.executable, "-m", "tools.tmoglint", "transmogrifai_tpu/",
         "tests/", "bench.py", "tools/", "--format", "json"],
        capture_output=True, text=True)
    if out.returncode == 0 and out.stdout.strip():
        rerun = json.loads(out.stdout)["stats"]["total_s"]
        total = min(total, rerun)
    else:
        print(f"  budget re-measure itself failed "
              f"(rc {out.returncode}): {out.stderr[-500:]}",
              file=sys.stderr)
assert total < 10.0, \
    f"tmoglint full scan blew the 10s budget twice: first " \
    f"{rep['stats']['total_s']}s, re-measure {rerun}s ({rep['stats']})"
print(f"  tmoglint JSON artifact ok: {rep['total_findings']} finding(s), "
      f"stats={rep['stats']}")
PY
# family selection must run clean against the SAME baseline with the
# stale-entry scoping guard active — v2 (concurrency + buffer lifetime),
# v3 (SPMD/collective correctness + contract drift) and v4
# (trace-contract) each alone, no TPU/DAG noise
python -m tools.tmoglint transmogrifai_tpu/ tests/ bench.py tools/ \
  --rules THR,BUF
python -m tools.tmoglint transmogrifai_tpu/ tests/ bench.py tools/ \
  --rules SHD,ENV,EVT
python -m tools.tmoglint transmogrifai_tpu/ tests/ bench.py tools/ \
  --rules TRC
# mutation drive for the v4 family: the clean scan above is only
# meaningful if the rules FIRE when the contract actually breaks. The
# drive copies the real serve hot path aside, scans the copy clean,
# seeds the canonical contract break (a per-request jit construction
# for TRC001), asserts the real CLI exits 1 naming the rule, then deletes
# the mutation and asserts the scan is clean again — through
# `python -m tools.tmoglint`, not library calls.
MUT_TMP=$(mktemp -d)
python - "$MUT_TMP" <<'PY'
import os
import shutil
import subprocess
import sys

mut = sys.argv[1]
src = "transmogrifai_tpu/serve/engine.py"
dst = os.path.join(mut, "serve", "engine.py")
os.makedirs(os.path.dirname(dst), exist_ok=True)
# a unique single-line statement inside ServingEngine.score_batch — the
# mutation lands directly on the per-request path the rules scope to
ANCHOR = "        records = list(records)\n"


def scan(rules):
    return subprocess.run(
        [sys.executable, "-m", "tools.tmoglint", "serve/engine.py",
         "--root", mut, "--no-baseline", "--rules", rules],
        capture_output=True, text=True)


def drive(rule, family, mutation):
    text = open(src).read()
    assert text.count(ANCHOR) == 1, "score_batch anchor drifted"
    shutil.copyfile(src, dst)
    clean = scan(family)
    assert clean.returncode == 0, (rule, clean.stdout, clean.stderr)
    with open(dst, "w") as f:
        f.write(text.replace(ANCHOR, ANCHOR + mutation))
    hit = scan(family)
    assert hit.returncode == 1 and rule in hit.stdout, \
        (rule, hit.returncode, hit.stdout, hit.stderr)
    shutil.copyfile(src, dst)  # deleting the mutation restores clean
    again = scan(family)
    assert again.returncode == 0, (rule, again.stdout)
    print(f"  mutation drive: {rule} fires on the seeded serve-path "
          f"break and clears on restore")


drive("TRC001", "TRC",
      "        _mut = jax.jit(lambda x: x)  # seeded: per-request jit\n")
PY
rm -rf "$MUT_TMP"
echo "  tmoglint: full scan (<10s) + THR,BUF + SHD,ENV,EVT + TRC family scans clean, v4 mutation drive fires (artifact: $ARTIFACTS_DIR/tmoglint_report.json)"

echo "== 3/7 test suite (8-device virtual CPU mesh) =="
# fused histogram planner + CPU-fallback smoke first, explicitly under
# JAX_PLATFORMS=cpu: the tier-1 guarantee that the pure-jnp twin of the
# batched sweep kernel stays live on hosts with no TPU
JAX_PLATFORMS=cpu python -m pytest \
  tests/test_hist_batched.py::test_planner_cpu_smoke -q -m 'not slow'
# convergence-aware GLM sweep smoke (tier-1-safe, small shapes): the
# squared-loss Gram fast path must stay one-pass and the retirement
# round driver must keep matching the legacy streamed route on CPU
JAX_PLATFORMS=cpu python -m pytest \
  "tests/test_glm_convergence.py::TestGramFastPath::test_single_pass_telemetry" \
  "tests/test_glm_convergence.py::TestRoundDriver::test_matches_legacy_streamed_logistic" \
  -q -m 'not slow'
python -m pytest tests/ -q

echo "== 4/7 examples =="
for ex in op_titanic_simple op_titanic_mini op_iris op_boston; do
  JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python "examples/${ex}.py" > /dev/null
  echo "  ${ex} ok"
done
REF_RES=/root/reference/helloworld/src/main/resources
if [ -f "$REF_RES/EmailDataset/Clicks.csv" ]; then
  JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python examples/op_dataprep.py \
    "$REF_RES/EmailDataset/Clicks.csv" "$REF_RES/EmailDataset/Sends.csv" \
    "$REF_RES/WebVisitsDataset/WebVisits.csv" > /dev/null
  echo "  op_dataprep ok"
fi

echo "== 5/7 observability smoke (traced workflow + GLM sweep) =="
# a tiny traced run must produce a loadable span hierarchy: Chrome trace +
# AppMetrics-with-spans + streaming events.jsonl, all validated by the
# schema checks in `trace-report --check` (docs/observability.md)
TRACE_DIR=$(mktemp -d)
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python - "$TRACE_DIR" <<'PY'
import sys

import numpy as np

out = sys.argv[1]
from transmogrifai_tpu import FeatureBuilder
from transmogrifai_tpu.automl.transmogrifier import transmogrify
from transmogrifai_tpu.readers.readers import ListReader
from transmogrifai_tpu.workflow import OpParams, OpWorkflowRunner, Workflow

rows = [{"x": float(i % 7), "y": float(i % 3)} for i in range(120)]
fx = FeatureBuilder.Real("x").extract(lambda r: r.get("x")).as_predictor()
fy = FeatureBuilder.Real("y").extract(lambda r: r.get("y")).as_predictor()
wf = Workflow().set_result_features(transmogrify([fx, fy]))
runner = OpWorkflowRunner(wf, train_reader=ListReader(rows))
runner.run(OpWorkflowRunner.TRAIN,
           OpParams(collect_stage_metrics=True, metrics_location=out))

# tiny traced GLM round sweep: the glm_round spans + event log entries
import jax.numpy as jnp
from transmogrifai_tpu.ops.glm_sweep import sweep_glm_streamed_rounds
from transmogrifai_tpu.utils.metrics import collector

rng = np.random.default_rng(0)
X = rng.normal(size=(400, 4)).astype(np.float32)
y = (X[:, 0] > 0).astype(np.float32)
masks = np.ones((2, 400), np.float32)
masks[0, ::3] = 0.0
masks[1, 1::3] = 0.0
collector.enable("ci_glm_sweep")
collector.attach_event_log(out + "/events.jsonl")
with collector.trace_span("glm_sweep", kind="sweep_fit"):
    sweep_glm_streamed_rounds(
        jnp.asarray(X), jnp.asarray(y), jnp.ones(400, jnp.float32),
        jnp.asarray(masks), np.asarray([0.05, 0.2], np.float32),
        np.zeros(2, np.float32), loss="logistic", max_iter=4, tol=1e-8,
        standardize=False, round_iters=2, warm_start=False)
collector.save(out + "/glm_stage_metrics.json")
collector.save_chrome_trace(out + "/glm_trace.json")
collector.detach_event_log()
collector.disable()
print("traced workflow + GLM sweep ok:", out)
PY
# one-pass statistics engine smoke: the sharded (2-device CPU mesh, psum
# merge) and streamed (host tile merge) drivers must agree with the fused
# single program, and a traced pearson SanityChecker fit must land exactly
# ONE stats_pass span (docs/performance.md "One-pass statistics engine")
PYTHONPATH="$PWD" python - "$TRACE_DIR" <<'PY'
import sys

out = sys.argv[1]
from transmogrifai_tpu.utils.platform import force_cpu

force_cpu(2)
import numpy as np

from transmogrifai_tpu.automl import SanityChecker
from transmogrifai_tpu.data.dataset import Column, column_from_values
from transmogrifai_tpu.ops import stats_engine as SE
from transmogrifai_tpu.parallel.mesh import make_mesh
from transmogrifai_tpu.types import ColumnKind, RealNN
from transmogrifai_tpu.utils.metrics import collector

rng = np.random.default_rng(0)
X = rng.normal(size=(4000, 6)).astype(np.float32)
X[rng.uniform(size=X.shape) < 0.1] = np.nan
y = rng.integers(0, 2, size=4000).astype(np.float32)

collector.enable("ci_stats_engine")
collector.attach_event_log(out + "/events.jsonl")
fused = SE.run_stats(X, y, corr_matrix=True, label="ci_fused")
sharded = SE.run_stats(X, y, corr_matrix=True, mesh=make_mesh(n_batch=2),
                       label="ci_sharded")
streamed = SE.run_stats(X, y, corr_matrix=True, driver="streamed",
                        tile_rows=1000, label="ci_streamed")
for other, nm in ((sharded, "sharded"), (streamed, "streamed")):
    for f in ("count", "mean", "variance", "corr_label"):
        np.testing.assert_allclose(getattr(other, f), getattr(fused, f),
                                   rtol=2e-4, atol=2e-5, err_msg=nm)
label = column_from_values(RealNN, [float(v) for v in y])
vec = Column(kind=ColumnKind.VECTOR, data=np.where(np.isfinite(X), X, 0.0))
before = sum(1 for s in collector.trace.spans
             if s.name.startswith("stats_pass"))
SanityChecker().fit_columns(label, vec)
fit_spans = sum(1 for s in collector.trace.spans
                if s.name.startswith("stats_pass")) - before
assert fit_spans == 1, f"pearson fit made {fit_spans} stats passes, not 1"
collector.save(out + "/stats_stage_metrics.json")
collector.save_chrome_trace(out + "/stats_trace.json")
collector.detach_event_log()
collector.disable()
print("stats engine smoke ok: sharded+streamed parity, 1-pass fit")
PY
# streaming data plane smoke (docs/performance.md "Streaming data plane"):
# an Avro file is the ONLY copy of X — tileplane stats fit (sharded tile
# lane on the 2-device CPU mesh) + streamed GLM fit + streamed score, with
# the bounded-host-buffer and overlap claims checked from the artifacts
PYTHONPATH="$PWD" python - "$TRACE_DIR" <<'PY'
import sys

out = sys.argv[1]
from transmogrifai_tpu.utils.platform import force_cpu

force_cpu(2)
import os
import tempfile

import numpy as np
import jax.numpy as jnp

from transmogrifai_tpu.ops import glm_sweep as GS
from transmogrifai_tpu.ops import stats_engine as SE
from transmogrifai_tpu.parallel import tileplane as TP
from transmogrifai_tpu.parallel.mesh import make_mesh
from transmogrifai_tpu.readers.avro import read_avro_file, write_avro_file
from transmogrifai_tpu.utils.metrics import collector

collector.enable("ci_streaming")
collector.attach_event_log(out + "/events.jsonl")

n, d, F = 6000, 8, 2
rng = np.random.default_rng(0)
X = rng.normal(size=(n, d)).astype(np.float32)
beta = rng.normal(size=d)
y = (X @ beta > 0).astype(np.float32)
tmp = tempfile.mkdtemp(prefix="ci_stream_")
path = os.path.join(tmp, "rows.avro")
schema = {"type": "record", "name": "Row", "fields": (
    [{"name": f"x{j}", "type": "float"} for j in range(d)]
    + [{"name": "y", "type": "float"}, {"name": "id", "type": "long"}])}
write_avro_file(path, schema, [
    {**{f"x{j}": float(X[i, j]) for j in range(d)},
     "y": float(y[i]), "id": i} for i in range(n)])


def src(fn):
    return TP.reader_row_source(lambda: read_avro_file(path), fn,
                                batch_records=512, n_rows=n)


fused = SE.run_stats(X, y, corr_matrix=True, label="ci_resident")
# Avro-served fit, sharded tile lane on the 2-device mesh
res = SE.run_stats(
    src(lambda r: ([r[f"x{j}"] for j in range(d)], r["y"], 1.0)),
    corr_matrix=True, tile_rows=1000, mesh=make_mesh(n_batch=2),
    label="ci_tileplane")
np.testing.assert_allclose(res.mean, fused.mean, rtol=2e-4, atol=2e-5)
np.testing.assert_allclose(res.corr_matrix, fused.corr_matrix,
                           rtol=2e-3, atol=2e-4)
ps = SE._last_stream_stats
assert ps.rows == n and ps.peak_host_rows <= 2 * ps.tile_rows, \
    (ps.rows, ps.peak_host_rows, ps.tile_rows)

# streamed GLM fit from the same file
mask = np.stack([(np.arange(n) % F != k).astype(np.float32)
                 for k in range(F)])
regs = np.asarray([0.05], np.float32)
B_src, _, info = GS.sweep_glm_streamed_rounds(
    src(lambda r: ([r[f"x{j}"] for j in range(d)], r["y"], 1.0,
                   [float(r["id"] % F != k) for k in range(F)])),
    None, None, None, regs, np.zeros(1, np.float32), loss="logistic",
    max_iter=10, tol=1e-6, warm_start=False)
B_dev, _, _ = GS.sweep_glm_streamed_rounds(
    jnp.asarray(X), jnp.asarray(y), jnp.ones(n, jnp.float32),
    jnp.asarray(mask), regs, np.zeros(1, np.float32), loss="logistic",
    max_iter=10, tol=1e-6, warm_start=False)
assert info["driver"] == "tileplane"
np.testing.assert_allclose(B_src, B_dev, rtol=5e-3, atol=7e-4)

# compute-heavy traced pass: the per-tile tile_copy/tile_compute spans
# whose OVERLAP the post-export check below asserts
Xb = rng.normal(size=(16000, 96)).astype(np.float32)


def gram_step(carry, xt):
    import jax
    g = jnp.matmul(xt.T, xt, preferred_element_type=jnp.float32)
    return carry + jnp.matmul(g, g, preferred_element_type=jnp.float32)


import jax
TP.run_tileplane(TP.ArraySource(Xb, chunk_rows=2000),
                 jax.jit(gram_step), jnp.zeros((96, 96), jnp.float32),
                 tile_rows=2000, label="ci_overlap")

# streamed score through the tileplane scoring path
from transmogrifai_tpu import FeatureBuilder
from transmogrifai_tpu.automl import BinaryClassificationModelSelector
from transmogrifai_tpu.automl.transmogrifier import transmogrify
from transmogrifai_tpu.models.glm import OpLogisticRegression
from transmogrifai_tpu.readers import AvroStreamingReader, score_stream
from transmogrifai_tpu.readers.readers import ListReader
from transmogrifai_tpu.stages.params import param_grid
from transmogrifai_tpu.workflow import Workflow

rows = [{**{f"x{j}": float(X[i, j]) for j in range(d)}, "y": float(y[i])}
        for i in range(1500)]
preds = [FeatureBuilder.Real(f"x{j}").extract(
    lambda r, j=j: r.get(f"x{j}")).as_predictor() for j in range(d)]
fy = FeatureBuilder.RealNN("y").extract(lambda r: r.get("y")).as_response()
pred = BinaryClassificationModelSelector.with_train_validation_split(
    models_and_parameters=[(OpLogisticRegression(),
                            param_grid(reg_param=[0.01]))],
).set_input(fy, transmogrify(preds)).get_output()
model = Workflow().set_reader(ListReader(rows)) \
    .set_result_features(pred).train()
scored = sum(len(b) for b in score_stream(model, AvroStreamingReader(path),
                                          tile_rows=1024))
assert scored == n, scored

collector.save(out + "/stream_stage_metrics.json")
collector.save_chrome_trace(out + "/stream_trace.json")
collector.detach_event_log()
collector.disable()
import shutil
shutil.rmtree(tmp, ignore_errors=True)
print("streaming smoke ok: avro fit parity, bounded host buffer, "
      f"{scored} rows scored")
PY
# sharded ingest smoke (docs/performance.md "Parallel sharded ingest"):
# a multi-shard CSV streams through the parse-worker pool at
# TMOG_INGEST_WORKERS=2 — stats moments must be BIT-IDENTICAL to the
# workers=1 serial pass, the parallel pass must add 0 compiles after
# the serial warmup (same tile shapes => same executables), and the
# exported trace must carry tile_parse spans from >=2 distinct workers
# on their own ingest-w<j> lanes (trace-report --check below also
# validates the ingest_pass events on the shared log)
PYTHONPATH="$PWD" python - "$TRACE_DIR" <<'PY'
import sys

out = sys.argv[1]
from transmogrifai_tpu.utils.platform import force_cpu

force_cpu(2)
import json
import os
import tempfile

import numpy as np

from transmogrifai_tpu.ops import stats_engine as SE
from transmogrifai_tpu.parallel import ingest as ING
from transmogrifai_tpu.utils import tracing
from transmogrifai_tpu.utils.metrics import collector

collector.enable("ci_ingest")
collector.attach_event_log(out + "/events.jsonl")

n_shards, rows, d = 4, 900, 6
rng = np.random.default_rng(0)
tmp = tempfile.mkdtemp(prefix="ci_ingest_")
paths = []
for s in range(n_shards):
    p = os.path.join(tmp, f"part-{s:03d}.csv")
    with open(p, "w") as fh:
        fh.write(",".join(f"x{j}" for j in range(d)) + ",y\n")
        for r in rng.normal(size=(rows, d + 1)):
            fh.write(",".join(f"{v:.6f}" for v in r) + "\n")
    paths.append(p)


def src(workers):
    return ING.sharded_reader_source(
        paths, lambda c: (np.stack([c[f"x{j}"] for j in range(d)], 1),
                          c["y"], np.ones_like(c["y"])),
        batch_records=256, n_rows=n_shards * rows, workers=workers,
        label=f"ci_w{workers}")


serial = SE.run_stats(src(1), tile_rows=1024, label="ci_ingest_serial")
base = tracing.tracker.true_compiles
parallel = SE.run_stats(src(2), tile_rows=1024, label="ci_ingest_par")
compiles = tracing.tracker.true_compiles - base
assert compiles == 0, f"parallel ingest pass compiled: {compiles}"
for f in ("count", "mean", "variance", "m2", "min", "max"):
    a, b = np.asarray(getattr(serial, f)), np.asarray(getattr(parallel, f))
    assert np.array_equal(a, b), f"stats field {f} not bit-identical"

spans = [s for s in collector.trace.spans if s.name == "tile_parse"]
par_workers = {s.attrs["worker"] for s in spans
               if s.attrs["label"] == "ci_w2"}
assert len(par_workers) >= 2, f"parse workers seen: {par_workers}"
lanes = {s.attrs["lane"] for s in spans}
assert {"ingest-w0", "ingest-w1"} <= lanes, lanes
[ingest_ev] = [r for r in collector.current.ingest_metrics
               if r.workers == 2]
assert ingest_ev.shards == n_shards and ingest_ev.rows == n_shards * rows

collector.save(out + "/ingest_stage_metrics.json")
collector.save_chrome_trace(out + "/ingest_trace.json")
collector.detach_event_log()
collector.disable()
import shutil
shutil.rmtree(tmp, ignore_errors=True)
print(f"ingest smoke ok: bit-identical at workers=2, 0 compiles, "
      f"{len(par_workers)} parse lanes")
PY
# serving smoke (docs/serving.md): fit + save a model, `serve
# --prewarm-only` via the real CLI (populates the persistent compile
# cache + writes the serve.json manifest), then a FRESH process starts
# the engine in-process — prewarm must be all cache hits (0 true XLA
# compiles) — and fires concurrent mixed-size traffic: p50 sanity, zero
# post-warmup recompiles (also re-checked from the artifact by the
# trace-report --check below, which fails on any serve_recompile event),
# and a clean drain on shutdown.
SERVE_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python - "$SERVE_TMP" <<'PY'
import sys

import numpy as np

out = sys.argv[1]
from transmogrifai_tpu import FeatureBuilder
from transmogrifai_tpu.automl import BinaryClassificationModelSelector
from transmogrifai_tpu.automl.transmogrifier import transmogrify
from transmogrifai_tpu.models.glm import OpLogisticRegression
from transmogrifai_tpu.readers.readers import ListReader
from transmogrifai_tpu.stages.params import param_grid
from transmogrifai_tpu.workflow import Workflow

rng = np.random.default_rng(0)
rows = [{"a": float(rng.normal()), "b": float(rng.normal()),
         "y": float(rng.integers(0, 2))} for _ in range(400)]
fa = FeatureBuilder.Real("a").extract(lambda r: r.get("a")).as_predictor()
fb = FeatureBuilder.Real("b").extract(lambda r: r.get("b")).as_predictor()
fy = FeatureBuilder.RealNN("y").extract(lambda r: r.get("y")).as_response()
fsum = (fa + fb) + 1.0  # a jitted stage, so compile accounting is real
pred = BinaryClassificationModelSelector.with_train_validation_split(
    models_and_parameters=[(OpLogisticRegression(),
                            param_grid(reg_param=[0.01]))],
).set_input(fy, transmogrify([fa, fb, fsum])).get_output()
Workflow().set_reader(ListReader(rows)) \
    .set_result_features(pred).train().save(out + "/model")
print("serving smoke: model saved")
PY
JAX_PLATFORMS=cpu TMOG_COMPILE_CACHE_DIR="$SERVE_TMP/cache" \
  PYTHONPATH="$PWD" python -m transmogrifai_tpu serve "$SERVE_TMP/model" \
  --prewarm-only --max-batch 16
JAX_PLATFORMS=cpu TMOG_COMPILE_CACHE_DIR="$SERVE_TMP/cache" \
  PYTHONPATH="$PWD" python - "$SERVE_TMP" "$TRACE_DIR" <<'PY'
import sys
import threading

import numpy as np

model_dir, trace = sys.argv[1] + "/model", sys.argv[2]
from transmogrifai_tpu.serve import MicroBatcher, ServingEngine
from transmogrifai_tpu.utils import tracing
from transmogrifai_tpu.utils.metrics import collector

collector.enable("ci_serve")
collector.attach_event_log(trace + "/events.jsonl")
eng = ServingEngine(model_dir)
assert eng.buckets == (1, 8, 16), eng.buckets  # the prewarm manifest
warm = eng.prewarm()
assert warm["compiles"] == 0, \
    f"fresh-process prewarm compiled: {warm['compiles']}"
assert warm["cache_hits"] > 0, warm  # executables really loaded
base = tracing.tracker.true_compiles
batcher = MicroBatcher(eng, max_wait_ms=2.0, max_queue=256)
rng = np.random.default_rng(1)
errors = []


def single(i):
    try:
        out = batcher.submit({"a": float(rng.normal()),
                              "b": float(rng.normal())})
        assert out
    except Exception as e:
        errors.append(repr(e))


def bulk(k):
    try:
        recs = [{"a": float(i), "b": 0.5} for i in range(k)]
        assert len(eng.score_batch(recs)) == k
    except Exception as e:
        errors.append(repr(e))


threads = [threading.Thread(target=single, args=(i,)) for i in range(20)]
threads += [threading.Thread(target=bulk, args=(k,))
            for k in (1, 3, 8, 16, 5, 11)]
for t in threads:
    t.start()
for t in threads:
    t.join(60)
batcher.shutdown(drain=True)  # graceful drain
assert not errors, errors[:3]
assert tracing.tracker.true_compiles == base, "recompile under traffic"
assert eng.post_warmup_compiles == 0
m = eng.metrics()
assert m["requests"] >= 20 and m["shed"] == 0, m
p50 = m["latency"]["total"]["p50_ms"]
assert 0.0 < p50 < 2000.0, p50  # sanity, not a perf claim on CPU
collector.save(trace + "/serve_stage_metrics.json")
collector.save_chrome_trace(trace + "/serve_trace.json")
collector.detach_event_log()
collector.disable()
print(f"serving smoke ok: 0 prewarm compiles ({warm['cache_hits']} cache "
      f"hits), {m['requests']} requests, p50 {p50}ms, clean drain")
PY
rm -rf "$SERVE_TMP"
# drift-monitor smoke (docs/monitoring.md): fit+save writes the
# monitor.json reference profile; a monitored engine serving traffic
# from a deliberately SHIFTED distribution raises drift_alert within ONE
# window (with 0 true XLA compiles after warmup), trace-report --check
# on that run dir SURFACES the drift (fails + names drift_alert), while
# identical-distribution traffic stays quiet across 3 windows and
# passes --check; finally the offline `monitor` CLI over the same
# shifted file agrees with the serve-side verdict (exit 3 under
# --fail-on-drift) and stays green on the quiet file.
MON_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python - "$MON_TMP" <<'PY'
import csv
import os
import sys

import numpy as np

out = sys.argv[1]
from transmogrifai_tpu import FeatureBuilder
from transmogrifai_tpu.automl import BinaryClassificationModelSelector
from transmogrifai_tpu.automl.transmogrifier import transmogrify
from transmogrifai_tpu.models.glm import OpLogisticRegression
from transmogrifai_tpu.readers.readers import CSVReader, ListReader
from transmogrifai_tpu.stages.params import param_grid
from transmogrifai_tpu.workflow import Workflow

rng = np.random.default_rng(0)


def make_rows(n, shift=0.0, cat=("u", "v", "w")):
    rows = []
    for _ in range(n):
        a, b = float(rng.normal(shift)), float(rng.normal())
        rows.append({"a": a, "b": b, "c": str(rng.choice(list(cat))),
                     "y": float(a + 0.5 * b > shift)})
    return rows


fa = FeatureBuilder.Real("a").extract(lambda r: r.get("a")).as_predictor()
fb = FeatureBuilder.Real("b").extract(lambda r: r.get("b")).as_predictor()
fc = FeatureBuilder.PickList("c").extract(lambda r: r.get("c")).as_predictor()
fy = FeatureBuilder.RealNN("y").extract(lambda r: r.get("y")).as_response()
pred = BinaryClassificationModelSelector.with_train_validation_split(
    models_and_parameters=[(OpLogisticRegression(),
                            param_grid(reg_param=[0.01]))],
).set_input(fy, transmogrify([fa, fb, fc])).get_output()
model = Workflow().set_reader(ListReader(make_rows(500))) \
    .set_result_features(pred).train()
model.save(out + "/model")
assert os.path.exists(out + "/model/monitor.json"), \
    "fit+save must write the reference profile"

# the shifted and quiet bulk files (the offline CLI scores these next)
for name, shift, cat in (("shifted", 9.0, ("q",)),
                         ("quiet", 0.0, ("u", "v", "w"))):
    with open(f"{out}/{name}.csv", "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=["a", "b", "c"])
        w.writeheader()
        for r in make_rows(384, shift=shift, cat=cat):
            w.writerow({k: r[k] for k in ("a", "b", "c")})

from transmogrifai_tpu.monitor import ReferenceProfile, ServeMonitor
from transmogrifai_tpu.serve import ServingEngine
from transmogrifai_tpu.utils import tracing
from transmogrifai_tpu.utils.metrics import collector
from transmogrifai_tpu.workflow.io import load_monitor_profile
from transmogrifai_tpu.workflow.workflow import WorkflowModel

m2 = WorkflowModel.load(out + "/model")
prof = ReferenceProfile.from_json(load_monitor_profile(out + "/model"))
os.makedirs(out + "/drifted")
os.makedirs(out + "/quiet_run")
collector.enable("ci_monitor")

# drifted: serve the SAME shifted file the offline CLI will read
collector.attach_event_log(out + "/drifted/events.jsonl")
mon = ServeMonitor(prof, window_rows=128, window_seconds=1e9)
eng = ServingEngine(m2, max_batch=16, monitor=mon)
eng.prewarm()
base = tracing.tracker.true_compiles
eng.score_batch(CSVReader(out + "/shifted.csv").read()[:128])
assert mon.n_windows == 1, mon.n_windows
assert mon.alerts_total > 0, "shifted traffic must alert within 1 window"
assert tracing.tracker.true_compiles == base, \
    "monitoring must not compile after warmup"
rep = mon.report()
assert rep["alerting"] and rep["last"]["alerts"], rep
targets = {al["target"] for al in rep["last"]["alerts"]}
assert {"a", "c"} <= targets, targets
collector.detach_event_log()

# quiet: identical-distribution traffic across 3 windows stays silent
collector.attach_event_log(out + "/quiet_run/events.jsonl")
mon2 = ServeMonitor(prof, window_rows=128, window_seconds=1e9)
eng2 = ServingEngine(m2, max_batch=16, monitor=mon2)
eng2.prewarm()
base2 = tracing.tracker.true_compiles
eng2.score_batch([{k: r[k] for k in ("a", "b", "c")}
                  for r in make_rows(3 * 128)])
assert mon2.n_windows == 3 and mon2.alerts_total == 0, \
    (mon2.n_windows, mon2.alerts_total)
assert tracing.tracker.true_compiles == base2
collector.detach_event_log()
collector.disable()
print(f"monitor serve smoke ok: drifted window alerted on {sorted(targets)}"
      f", quiet 3 windows silent, 0 post-warmup compiles")
PY
# trace-report --check must FAIL on the drifted run and NAME drift_alert
if PYTHONPATH="$PWD" python -m transmogrifai_tpu trace-report \
    "$MON_TMP/drifted" --check > "$MON_TMP/check_drifted.out" 2>&1; then
  echo "trace-report --check unexpectedly PASSED on the drifted run"
  exit 1
fi
grep -q "drift_alert" "$MON_TMP/check_drifted.out"
echo "  trace-report surfaced the drift_alert"
# ... and stay green on the quiet run
PYTHONPATH="$PWD" python -m transmogrifai_tpu trace-report \
  "$MON_TMP/quiet_run" --check > /dev/null
# offline CLI over the same shifted file agrees with the serve verdict
set +e
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python -m transmogrifai_tpu monitor \
  "$MON_TMP/model" "$MON_TMP/shifted.csv" --fail-on-drift \
  --tile-rows 128 > "$MON_TMP/offline_drifted.json"
MON_RC=$?
set -e
[ "$MON_RC" -eq 3 ] || {
  echo "offline monitor CLI missed the drift (rc=$MON_RC)"; exit 1; }
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python -m transmogrifai_tpu monitor \
  "$MON_TMP/model" "$MON_TMP/quiet.csv" --fail-on-drift \
  --tile-rows 128 > "$MON_TMP/offline_quiet.json"
python - "$MON_TMP" <<'PY'
import json
import sys

out = sys.argv[1]
drifted = json.load(open(out + "/offline_drifted.json"))
quiet = json.load(open(out + "/offline_quiet.json"))
assert drifted["verdict"] == "drift" and drifted["alerts_total"] > 0
assert {a["target"] for a in drifted["last"]["alerts"]} >= {"a", "c"}
assert quiet["verdict"] == "ok" and quiet["alerts_total"] == 0
print(f"monitor offline smoke ok: shifted file -> drift "
      f"({drifted['alerts_total']} alerts), quiet file -> ok")
PY
rm -rf "$MON_TMP"
# fleet smoke (docs/fleet.md): fit+save -> REAL CLI --prewarm-only into a
# shared compile cache -> 2-replica fleet of real serve subprocesses ->
# concurrent traffic -> kill -9 one replica mid-traffic (zero failed
# requests; the router retries onto the survivor) -> the supervisor
# restarts it and the REJOIN performs 0 true XLA compiles, asserted from
# the restarted incarnation's SAVED event artifact (serve_prewarm
# carries the RecompileTracker counters) -> shadow-rollout a
# byte-identical v2 -> clean verdict -> atomic swap under traffic ->
# trace-report --check green on the fleet log and on the restarted
# replica's artifacts.
FLEET_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python - "$FLEET_TMP" <<'PY'
import sys

import numpy as np

out = sys.argv[1]
from transmogrifai_tpu import FeatureBuilder
from transmogrifai_tpu.automl import BinaryClassificationModelSelector
from transmogrifai_tpu.automl.transmogrifier import transmogrify
from transmogrifai_tpu.models.glm import OpLogisticRegression
from transmogrifai_tpu.readers.readers import ListReader
from transmogrifai_tpu.stages.params import param_grid
from transmogrifai_tpu.workflow import Workflow

rng = np.random.default_rng(0)
rows = [{"a": float(rng.normal()), "b": float(rng.normal()),
         "y": float(rng.integers(0, 2))} for _ in range(400)]
fa = FeatureBuilder.Real("a").extract(lambda r: r.get("a")).as_predictor()
fb = FeatureBuilder.Real("b").extract(lambda r: r.get("b")).as_predictor()
fy = FeatureBuilder.RealNN("y").extract(lambda r: r.get("y")).as_response()
fsum = (fa + fb) + 1.0  # a jitted stage: compile accounting is real
pred = BinaryClassificationModelSelector.with_train_validation_split(
    models_and_parameters=[(OpLogisticRegression(),
                            param_grid(reg_param=[0.01]))],
).set_input(fy, transmogrify([fa, fb, fsum])).get_output()
Workflow().set_reader(ListReader(rows)) \
    .set_result_features(pred).train().save(out + "/model")
print("fleet smoke: model saved")
PY
JAX_PLATFORMS=cpu TMOG_COMPILE_CACHE_DIR="$FLEET_TMP/cache" \
  PYTHONPATH="$PWD" python -m transmogrifai_tpu serve "$FLEET_TMP/model" \
  --prewarm-only --max-batch 16
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python - "$FLEET_TMP" <<'PY'
import json
import os
import shutil
import sys
import threading
import time

tmp = sys.argv[1]
from transmogrifai_tpu.fleet import (HealthProber, RolloutManager, Router,
                                     Supervisor)
from transmogrifai_tpu.fleet.frontend import FleetFrontend
from transmogrifai_tpu.utils.metrics import collector

v1 = tmp + "/model"
v2 = tmp + "/model_v2"
shutil.copytree(v1, v2)
os.remove(v2 + "/serve.json")  # v2 gets its OWN stamped manifest

env = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": os.getcwd(),
       "TMOG_COMPILE_CACHE_DIR": tmp + "/cache"}
collector.enable("ci_fleet")
collector.attach_event_log(tmp + "/fleet_events.jsonl")
lock = threading.RLock()
sup = Supervisor(v1, replicas=2, lock=lock, metrics_root=tmp + "/fleet",
                 serve_args=["--max-batch", "16", "--max-wait-ms", "2",
                             "--monitor", "off"],
                 env=env, backoff_base_s=0.2, startup_timeout_s=300.0)
router = Router(lock, request_timeout=60.0)
router.set_champions(sup.start())
prober = HealthProber(router, interval_s=0.25).start()
rollout = RolloutManager(sup, router, lock=lock)
fe = FleetFrontend(sup, router, rollout)

errors = []
rng_rec = [{"a": 0.1 * i, "b": -0.05 * i} for i in range(50)]


def fire(n, sleep=0.01):
    for i in range(n):
        try:
            assert fe.submit(rng_rec[i % len(rng_rec)])
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))
        time.sleep(sleep)


# concurrent traffic, then kill -9 one replica mid-flight
threads = [threading.Thread(target=fire, args=(30,)) for _ in range(4)]
for t in threads:
    t.start()
time.sleep(0.3)
victim = router.champions[0]
inc0 = victim.incarnation
pid = sup.kill_replica(victim)
print(f"fleet smoke: kill -9 {victim.name} pid={pid} mid-traffic")
for t in threads:
    t.join(120)
assert not errors, errors[:5]  # ZERO failed requests past the kill
deadline = time.monotonic() + 240
while time.monotonic() < deadline:
    if victim.incarnation > inc0 and victim.healthy:
        break
    time.sleep(0.1)
assert victim.healthy and victim.incarnation > inc0, "no rejoin"
assert sup.rejoin_violations == 0, "rejoin compiled"
restarted_dir = victim.metrics_dir  # the NEW incarnation's artifacts
p99 = router.hist.to_json()["p99_ms"]
assert 0 < p99 < 60000, p99

# shadow-rollout the byte-identical v2: clean verdict -> atomic swap,
# all under continued traffic
stopper = threading.Event()


def pump():
    i = 0
    while not stopper.is_set():
        try:
            fe.submit(rng_rec[i % len(rng_rec)])
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))
        i += 1
        time.sleep(0.01)


pumps = [threading.Thread(target=pump) for _ in range(2)]
for t in pumps:
    t.start()
try:
    rollout.start(v2, replicas=1, fraction=1.0, min_shadow=16)
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline and rollout.state == "shadow":
        time.sleep(0.1)
finally:
    stopper.set()
    for t in pumps:
        t.join(60)
assert rollout.state == "swapped", rollout.status()
assert not errors, errors[:5]  # zero dropped requests through the swap
assert all(h.model_dir == v2 for h in router.champions)
assert fe.submit(rng_rec[0])  # v2 serves
m = fe.metrics()
assert m["post_warmup_compiles"] == 0, m
prober.stop()
sup.stop(router=router)
collector.detach_event_log()
collector.disable()

# the compile-free REJOIN, from the SAVED artifact (not process state):
# the restarted incarnation's serve_prewarm event carries the
# RecompileTracker counters it booked at startup
ev = [json.loads(l) for l in open(restarted_dir + "/events.jsonl")]
pw = [e for e in ev if e["event"] == "serve_prewarm"]
assert pw and pw[0]["compiles"] == 0 and pw[0]["cache_hits"] > 0, pw
with open(tmp + "/restarted_dir.txt", "w") as f:
    f.write(restarted_dir)
fl = [json.loads(l) for l in open(tmp + "/fleet_events.jsonl")]
names = {e["event"] for e in fl}
assert {"fleet_replica_down", "fleet_replica_up", "fleet_rollout_started",
        "fleet_rollout_swapped"} <= names, names
print(f"fleet smoke ok: kill -9 survived with 0 errors (p99 {p99}ms), "
      f"rejoin 0 compiles ({pw[0]['cache_hits']} cache hits, from the "
      f"artifact), v2 swapped under traffic")
PY
# trace-report --check green on the fleet event log AND the restarted
# replica's own artifacts
PYTHONPATH="$PWD" python -m transmogrifai_tpu trace-report \
  "$(cat "$FLEET_TMP/restarted_dir.txt")" --check > /dev/null
echo "  fleet trace-report: restarted replica artifacts clean"
# request-tracing smoke (docs/observability.md "Request tracing"): a
# fresh 2-replica fleet with tracing ON under mixed traffic; ONE
# artificially slow request (X-Tmog-Debug-Sleep, gated by
# TMOG_DEBUG_SLEEP_MAX_MS in the replica env) and ONE invalid request
# injected -> both TAIL-KEPT with full segment chains naming the serving
# replica, the slow request's router+replica segments sum to within 10%
# of its measured e2e wall, fleet /requests serves both, trace-report
# --requests exits green on the router's event log, and the
# zero-post-warmup-recompile contract holds with tracing ON
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python - "$FLEET_TMP" <<'PY'
import json
import os
import sys
import threading
import time

tmp = sys.argv[1]
from transmogrifai_tpu.fleet import (HealthProber, Router, Supervisor)
from transmogrifai_tpu.fleet.frontend import FleetFrontend
from transmogrifai_tpu.utils.metrics import collector

v1 = tmp + "/model"
env = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": os.getcwd(),
       "TMOG_COMPILE_CACHE_DIR": tmp + "/cache",
       # the chaos hook + a tail threshold reachable at smoke volume
       "TMOG_DEBUG_SLEEP_MAX_MS": "1000",
       "TMOG_TRACE_SLO_MIN_COUNT": "20"}
os.environ["TMOG_TRACE_SLO_MIN_COUNT"] = "20"
trace_dir = tmp + "/reqtrace"
os.makedirs(trace_dir, exist_ok=True)
collector.enable("ci_reqtrace")
collector.attach_event_log(trace_dir + "/events.jsonl")
lock = threading.RLock()
sup = Supervisor(v1, replicas=2, lock=lock,
                 metrics_root=tmp + "/reqtrace_fleet",
                 serve_args=["--max-batch", "16", "--max-wait-ms", "2",
                             "--monitor", "off"],
                 env=env, backoff_base_s=0.2, startup_timeout_s=300.0)
router = Router(lock, request_timeout=60.0)
router.set_champions(sup.start())
prober = HealthProber(router, interval_s=0.25).start()
fe = FleetFrontend(sup, router)
assert fe.tracer.enabled

recs = [{"a": 0.1 * i, "b": -0.05 * i} for i in range(40)]
# mixed warm traffic: singles through the queue + one bulk body
for i in range(120):
    assert fe.submit(recs[i % len(recs)])
status, _ = fe.forward_score(json.dumps(recs[:12]).encode())
assert status == 200

# the SLOW request: 600ms injected in the replica frontend, its own
# debug_sleep segment
rt = fe.tracer.start(None)
t0 = time.perf_counter()
status, _ = fe.forward_score(json.dumps(recs[0]).encode(), trace=rt,
                             headers={"X-Tmog-Debug-Sleep": "600"})
e2e_ms = (time.perf_counter() - t0) * 1e3
fe.tracer.finish(rt, e2e_ms / 1e3, status=status)
assert status == 200
slow_id = rt.trace_id

# the INVALID request: unknown key under strict validation -> 400
rt2 = fe.tracer.start(None)
status, _ = fe.forward_score(
    json.dumps({"a": 1.0, "b": 2.0, "nope": 3.0}).encode(), trace=rt2)
fe.tracer.finish(rt2, status=status)
assert status == 400, status
bad_id = rt2.trace_id

time.sleep(1.2)  # let replica gauge samplers tick
req = fe.requests()
kept = {(k["trace_id"], k["origin"]): k for k in req["kept"]}
slow_rep = kept.get((slow_id, "replica"))
slow_rout = kept.get((slow_id, "router"))
assert slow_rep is not None and slow_rout is not None, sorted(kept)
assert slow_rep["kept"] == "slow" and slow_rep["replica"], slow_rep
assert slow_rep["replica"].startswith("champion-"), slow_rep
bad_rep = kept.get((bad_id, "replica"))
bad_rout = kept.get((bad_id, "router"))
assert bad_rep is not None and bad_rout is not None, sorted(kept)
assert bad_rep["kept"] == "error" and bad_rout["status"] == 400
assert bad_rep["replica"].startswith("champion-"), bad_rep

# the acceptance pin: router+replica segments (>= 5: route, queue,
# batch, device, respond) sum to within 10% of the measured e2e wall.
# The router's `upstream` wall CONTAINS the replica's whole chain, so
# the non-overlapping sum is router(route) + every replica segment —
# upstream itself is excluded or the replica time would count twice
segs = dict(slow_rep["segments"])
segs_rout = dict(slow_rout["segments"])
assert {"route", "queue", "batch", "device", "respond"} <= \
    (set(segs) | set(segs_rout)), (segs, segs_rout)
total = segs_rout.get("route", 0.0) + sum(segs.values())
assert abs(total - e2e_ms) <= 0.10 * e2e_ms, (segs, total, e2e_ms)

# merged segment histograms cover the fleet's traffic
assert req["segments"]["queue"]["count"] >= 120, req["segments"].keys()
assert req["segments"]["device"]["count"] >= 120
assert req["joined_traces"] >= 2, req["joined_traces"]

# gauge time-series: both replicas + the router report rings
hist = fe.history()
assert len(hist["replicas"]) == 2 and all(
    len(g) > 0 for g in hist["replicas"].values()), hist["replicas"]

# /debugz answers on a live replica
from transmogrifai_tpu.fleet.router import get_json
h0 = router.champions[0]
dz = get_json(h0.host, h0.port, "/debugz")
assert dz and dz["batcher_alive"] and dz["dispatcher_beat_age_s"] < 5.0
assert any("serve-batcher" in k for k in dz["threads"]), dz["threads"]

# tracing ON added zero post-warmup compiles
m = fe.metrics()
assert m["post_warmup_compiles"] == 0, m["post_warmup_compiles"]

prober.stop()
sup.stop(router=router)
collector.detach_event_log()
collector.disable()
print(f"reqtrace smoke ok: slow {slow_id} kept ({total:.1f}ms of "
      f"{e2e_ms:.1f}ms e2e covered), invalid {bad_id} kept as error, "
      f"0 post-warmup compiles with tracing ON")
PY
# trace-report --requests green (segment sums cover every kept trace's
# e2e wall) on the router-side event log
PYTHONPATH="$PWD" python -m transmogrifai_tpu trace-report \
  "$FLEET_TMP/reqtrace" --requests > /dev/null
echo "  trace-report --requests: kept traces cover their e2e walls"
rm -rf "$FLEET_TMP"
# retrain smoke (docs/retraining.md): the loop CLOSED end-to-end — fit v1
# on distribution A, serve it as a monitored 1-replica fleet, pump
# SHIFTED traffic -> the pooled /drift verdict alerts -> the controller
# auto-triggers -> a sandboxed retrain-worker subprocess refits over the
# labeled history (mostly the shifted slab) with the champion-config
# narrowing + warm-seed shortcuts -> the validation gate passes (artifact
# loads, profile rebuilt, holdout within tolerance, offline monitor CLI
# green on a replay of the tapped triggering window) -> shadow-validate
# -> atomic swap, all with ZERO failed requests and 0 post-warmup
# compiles on champions -> more shifted traffic against the NEW champion
# and the pooled drift verdict CLEARS. Then the containment pass: a
# second (manual) cycle under TMOG_RETRAIN_FAULT=bad_artifact ends
# QUARANTINED with its evidence while the serving champion never blinks.
RETRAIN_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python - "$RETRAIN_TMP" <<'PY'
import csv
import json
import sys

import numpy as np

out = sys.argv[1]
from transmogrifai_tpu import FeatureBuilder
from transmogrifai_tpu.automl import BinaryClassificationModelSelector
from transmogrifai_tpu.automl.transmogrifier import transmogrify
from transmogrifai_tpu.models.glm import OpLogisticRegression
from transmogrifai_tpu.readers.readers import ListReader
from transmogrifai_tpu.stages.params import param_grid
from transmogrifai_tpu.workflow import Workflow

rng = np.random.default_rng(0)

SHIFT = 4.0


def make_rows(n, shift=0.0):
    rows = []
    for _ in range(n):
        a, b = float(rng.normal(shift)), float(rng.normal())
        rows.append({"a": a, "b": b, "y": float(a + 0.5 * b > shift)})
    return rows


fa = FeatureBuilder.Real("a").extract(lambda r: r.get("a")).as_predictor()
fb = FeatureBuilder.Real("b").extract(lambda r: r.get("b")).as_predictor()
fy = FeatureBuilder.RealNN("y").extract(lambda r: r.get("y")).as_response()
pred = BinaryClassificationModelSelector.with_train_validation_split(
    models_and_parameters=[(OpLogisticRegression(max_iter=10),
                            param_grid(reg_param=[0.01]))],
).set_input(fy, transmogrify([fa, fb])).get_output()
Workflow().set_reader(ListReader(make_rows(400))) \
    .set_result_features(pred).train().save(out + "/model")

# labeled history for the refit: a thin slab of the ORIGINAL
# distribution plus a thick slab of the SHIFTED one (the label feed
# caught up with the new world) — the candidate's rebuilt profile must
# cover the shifted traffic or the replay gate will refuse it
with open(out + "/history.csv", "w", newline="") as f:
    w = csv.DictWriter(f, fieldnames=["a", "b", "y"])
    w.writeheader()
    for r in make_rows(40) + make_rows(600, shift=SHIFT):
        w.writerow(r)

# the refit recipe next to the model: the builder module + retrain.json
with open(out + "/retrain_builder_ci.py", "w") as f:
    f.write('''
from transmogrifai_tpu import FeatureBuilder
from transmogrifai_tpu.automl import BinaryClassificationModelSelector
from transmogrifai_tpu.automl.transmogrifier import transmogrify
from transmogrifai_tpu.models.glm import OpLogisticRegression
from transmogrifai_tpu.stages.params import param_grid
from transmogrifai_tpu.workflow import Workflow


def build():
    fa = FeatureBuilder.Real("a").extract(
        lambda r: r.get("a")).as_predictor()
    fb = FeatureBuilder.Real("b").extract(
        lambda r: r.get("b")).as_predictor()
    fy = FeatureBuilder.RealNN("y").extract(
        lambda r: r.get("y")).as_response()
    pred = BinaryClassificationModelSelector.with_train_validation_split(
        models_and_parameters=[(OpLogisticRegression(max_iter=10),
                                param_grid(reg_param=[0.01, 0.1]))],
    ).set_input(fy, transmogrify([fa, fb])).get_output()
    return Workflow().set_result_features(pred)
''')
with open(out + "/model/retrain.json", "w") as f:
    json.dump({"builder": "retrain_builder_ci:build",
               "builder_path": out,
               "history": [out + "/history.csv"],
               "holdout_fraction": 0.2, "seed": 7,
               "fraction": 1.0, "min_shadow": 12, "replicas": 1}, f)
print("retrain smoke: v1 + history + recipe ready")
PY
JAX_PLATFORMS=cpu TMOG_COMPILE_CACHE_DIR="$RETRAIN_TMP/cache" \
  PYTHONPATH="$PWD" python -m transmogrifai_tpu serve "$RETRAIN_TMP/model" \
  --prewarm-only --max-batch 16
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" python - "$RETRAIN_TMP" <<'PY'
import json
import os
import sys
import threading
import time

import numpy as np

tmp = sys.argv[1]
from transmogrifai_tpu.fleet import (HealthProber, RolloutManager, Router,
                                     Supervisor)
from transmogrifai_tpu.fleet.frontend import FleetFrontend
from transmogrifai_tpu.monitor.alerts import DriftPolicy
from transmogrifai_tpu.monitor.profile import ReferenceProfile
from transmogrifai_tpu.retrain import RetrainController, RetrainPolicy
from transmogrifai_tpu.utils.metrics import collector
from transmogrifai_tpu.workflow.io import (load_monitor_profile,
                                           model_content_hash)

v1 = tmp + "/model"
v1_hash = model_content_hash(v1)
env = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": os.getcwd(),
       "TMOG_COMPILE_CACHE_DIR": tmp + "/cache"}
collector.enable("ci_retrain")
collector.attach_event_log(tmp + "/retrain_events.jsonl")
lock = threading.RLock()
sup = Supervisor(v1, replicas=1, lock=lock, metrics_root=tmp + "/fleet",
                 serve_args=["--max-batch", "16", "--max-wait-ms", "2",
                             "--monitor", "auto",
                             "--monitor-window-rows", "256"],
                 env=env, backoff_base_s=0.2, startup_timeout_s=300.0)
router = Router(lock, request_timeout=60.0)
router.set_champions(sup.start())
prober = HealthProber(router, interval_s=0.25).start()
# RELAXED shadow-verdict comparison: a candidate that LEARNED the shift
# scores the shifted traffic differently from the stale champion BY
# DESIGN (docs/retraining.md — the recipe's rollout_* overrides are the
# production spelling of exactly this). max_pred_js sits ABOVE the JS
# saturation point (1.0 on disjoint support): the stale champion scores
# every shifted row ~1.0 while the adapted candidate spreads, so with a
# small min_shadow the two calibration histograms can be fully disjoint
# and any threshold < 1 would flake on shadow-pair timing.
rollout = RolloutManager(sup, router, lock=lock, max_pred_js=1.5,
                         max_psi=50.0, max_score_shift=0.95)
profile = ReferenceProfile.from_json(load_monitor_profile(v1))
assert profile.model_hash == v1_hash, "profile must stamp the model hash"
fe = FleetFrontend(sup, router, rollout, profile=profile,
                   policy=DriftPolicy())
ctl = RetrainController(
    lambda: router.champions[0].model_dir if router.champions else None,
    root=tmp + "/retrain", rollout=rollout,
    policy=RetrainPolicy(min_interval_s=1.0, fit_attempts=2,
                         fit_timeout_s=420.0, rollout_timeout_s=300.0,
                         rollout_fraction=1.0, rollout_min_shadow=12,
                         require_monitor_green=True),
    drift_poll=fe.drift, drift_poll_interval_s=1.0, env=env)
fe.retrain = ctl
ctl.start()

rng = np.random.default_rng(7)
errors = []
stop_pump = threading.Event()


def pump():
    while not stop_pump.is_set():
        rec = {"a": float(rng.normal(4.0)), "b": float(rng.normal())}
        try:
            fe.submit(rec)
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))
        time.sleep(0.01)


pumps = [threading.Thread(target=pump, daemon=True) for _ in range(3)]
for t in pumps:
    t.start()

# shifted traffic -> pooled alert -> trigger -> refit -> gate -> shadow
# -> swap. Generous deadline: the worker is a REAL subprocess fit.
deadline = time.monotonic() + 600
while time.monotonic() < deadline and ctl.swapped_total == 0:
    if ctl.quarantined_total:
        raise AssertionError(f"cycle quarantined instead of swapping: "
                             f"{ctl.last_verdict}")
    time.sleep(0.5)
assert ctl.swapped_total == 1, \
    f"no swap within deadline: {ctl.status()}"
assert not errors, errors[:5]  # zero failed requests through the cycle

new_champ = router.champions[0].model_dir
assert new_champ != v1, "champion dir did not change"
assert model_content_hash(new_champ) != v1_hash
report = (ctl.last_verdict or {}).get("report") or {}
assert report.get("narrowed") and report.get("warm_seeded"), report
m = fe.metrics()
assert m["post_warmup_compiles"] == 0, m["post_warmup_compiles"]

# drift CLEARS on the new champion: more shifted traffic, judged
# against the NEW champion's own rebuilt profile (window size 256 keeps
# the pooled sample big enough that JS sampling noise cannot alert)
t_clear = time.monotonic() + 90
cleared = None
while time.monotonic() < t_clear:
    d = fe.drift()
    if d and d["rows_pooled"] >= 128:
        cleared = d
        break
    time.sleep(0.5)
assert cleared is not None, "no pooled window on the new champion"
assert not cleared["alerting"], cleared["pooled"]["alerts"]
assert cleared["pooled"]["model_content_hash"] == \
    model_content_hash(new_champ)
print(f"retrain smoke: auto cycle swapped ({report['metric']} "
      f"candidate={report['candidate_metric']:.3f} vs champion="
      f"{report['champion_metric']:.3f}), drift cleared on the new "
      f"champion over {cleared['rows_pooled']:.0f} pooled rows")

# ---- containment pass: bad_artifact fault, champion never blinks ----
os.environ["TMOG_RETRAIN_FAULT"] = "bad_artifact"
ctl2 = RetrainController(
    lambda: router.champions[0].model_dir if router.champions else None,
    root=tmp + "/retrain_fault", rollout=rollout,
    policy=RetrainPolicy(min_interval_s=0.0, fit_attempts=2,
                         fit_timeout_s=420.0,
                         require_monitor_green=True),
    recipe={"builder": "retrain_builder_ci:build", "builder_path": tmp,
            "history": [tmp + "/history.csv"]},
    env=dict(env, TMOG_RETRAIN_FAULT="bad_artifact"))
champ_before = router.champions[0].model_dir
n_req_before = router.n_requests
ctl2.trigger(reason="manual")
deadline = time.monotonic() + 600
while time.monotonic() < deadline and ctl2.quarantined_total == 0:
    assert ctl2.swapped_total == 0, "corrupt artifact must NEVER swap"
    time.sleep(0.5)
assert ctl2.quarantined_total == 1, ctl2.status()
q = ctl2.quarantine_list()
assert len(q) == 1 and "unloadable" in q[0]["reason"], q
assert os.path.isdir(q[0]["dir"]), "quarantine evidence missing"
assert os.path.exists(os.path.join(q[0]["dir"], "candidate",
                                   "op-model.json")), "evidence lost"
assert router.champions[0].model_dir == champ_before, \
    "fault pass touched the champion"
stop_pump.set()
for t in pumps:
    t.join(30)
assert not errors, errors[:5]  # zero failed requests through the fault
assert router.n_requests > n_req_before, "traffic kept flowing"
m = fe.metrics()
assert m["post_warmup_compiles"] == 0, m["post_warmup_compiles"]
ctl2.close()
ctl.close()
prober.stop()
sup.stop(router=router)
fe.close()
collector.detach_event_log()
collector.disable()

ev = [json.loads(l) for l in open(tmp + "/retrain_events.jsonl")]
names = [e["event"] for e in ev]
for needed in ("retrain_triggered", "retrain_fit_started",
               "retrain_candidate_ready", "retrain_rollout_started",
               "retrain_swapped", "fleet_rollout_swapped",
               "retrain_validation_failed", "retrain_quarantined"):
    assert needed in names, (needed, sorted(set(names)))
print("retrain smoke ok: drift->refit->gate->shadow->swap with 0 failed "
      "requests, then bad_artifact QUARANTINED with evidence while the "
      "champion served on")
PY
rm -rf "$RETRAIN_TMP"
# tree-sweep smoke on the 2-device CPU mesh: the mesh-sharded fused sweep
# (TMOG_GRID_FUSE=1 + a mesh validator) must take the
# mask_folds:grid_fused_sharded route, match the meshless fused kernel's
# margins at the metric level, and — the one-executable contract — a re-sweep
# at the same (shape, depth) must book ZERO true compiles, asserted from
# the saved span artifact (not just in-process state)
TMOG_GRID_FUSE=1 PYTHONPATH="$PWD" python - "$TRACE_DIR" <<'PY'
import json
import sys

out = sys.argv[1]
from transmogrifai_tpu.utils.platform import force_cpu

force_cpu(2)
import numpy as np
import jax.numpy as jnp

from transmogrifai_tpu.automl.tuning.validators import CrossValidation
from transmogrifai_tpu.evaluators.evaluators import Evaluators
from transmogrifai_tpu.models.trees import OpXGBoostClassifier
from transmogrifai_tpu.ops import trees as T
from transmogrifai_tpu.parallel.mesh import make_mesh
from transmogrifai_tpu.utils.metrics import collector

rng = np.random.default_rng(0)
n, d = 900, 6
X = rng.normal(size=(n, d)).astype(np.float32)
y = (X[:, 0] + 0.5 * X[:, 1]
     + rng.normal(scale=0.5, size=n) > 0).astype(np.float32)
grids = [{"eta": 0.1, "reg_lambda": 1.0}, {"eta": 0.3, "reg_lambda": 5.0}]
mesh = make_mesh(n_batch=2, n_model=1)
ev = Evaluators.BinaryClassification.au_pr()

collector.enable("ci_tree_mesh_sweep")
collector.attach_event_log(out + "/events.jsonl")
with collector.trace_span("tree_sweep_cold", kind="sweep_fit"):
    val = CrossValidation(ev, num_folds=2, seed=42, mesh=mesh)
    best = val.validate([(OpXGBoostClassifier(
        num_round=4, max_depth=3, max_bins=15),
        [dict(g) for g in grids])], X, y)
routes = [v.route for v in best.validated]
assert all(r == "mask_folds:grid_fused_sharded" for r in routes), routes
with collector.trace_span("tree_sweep_warm", kind="sweep_fit"):
    best2 = CrossValidation(ev, num_folds=2, seed=42, mesh=mesh).validate(
        [(OpXGBoostClassifier(num_round=4, max_depth=3, max_bins=15),
          [dict(g) for g in grids])], X, y)
for v1, v2 in zip(best.validated, best2.validated):
    np.testing.assert_allclose(v1.fold_metrics, v2.fold_metrics, rtol=1e-6)

# meshless reference: the same lanes through the single-device fused
# kernel — sharded psum-merged margins must agree at the metric level
vs = CrossValidation(ev, num_folds=2, seed=42).validate(
    [(OpXGBoostClassifier(num_round=4, max_depth=3, max_bins=15),
      [dict(g) for g in grids])], X, y)
for vm, vx in zip(best.validated, vs.validated):
    np.testing.assert_allclose(vm.fold_metrics, vx.fold_metrics,
                               rtol=1e-3, atol=1e-4)
collector.finish()
collector.save(out + "/tree_mesh_stage_metrics.json")
collector.save_chrome_trace(out + "/tree_mesh_trace.json")
collector.detach_event_log()
collector.disable()

# compile count FROM THE ARTIFACT: the warm re-sweep's tree_shard_merge
# spans must book 0 compiles (the fused-fit program for this (shape,
# depth) already exists), while the cold sweep compiled at least one
doc = json.load(open(out + "/tree_mesh_stage_metrics.json"))
spans = doc["spans"]


def subtree_ids(root_name):
    ids = {s["span_id"] for s in spans if s["name"] == root_name}
    assert ids, root_name
    grew = True
    while grew:
        grew = False
        for s in spans:
            if s.get("parent_id") in ids and s["span_id"] not in ids:
                ids.add(s["span_id"])
                grew = True
    return ids


def compiles_in(ids, name=None):
    return sum(int(s.get("attrs", {}).get("compiles", 0))
               for s in spans if s["span_id"] in ids
               and (name is None or s["name"] == name))


merge_spans = [s for s in spans if s["name"] == "tree_shard_merge"]
assert merge_spans, "sharded sweep must record tree_shard_merge spans"
cold = compiles_in(subtree_ids("tree_sweep_cold"))
# the warm sweep may re-jit validator-local helpers (fresh fold_metrics
# closure per validate); the one-executable contract is about the FUSED FIT:
# its tree_shard_merge spans must book zero compiles on the re-sweep
warm_merge = compiles_in(subtree_ids("tree_sweep_warm"),
                         name="tree_shard_merge")
print(f"tree mesh sweep smoke ok: routes={routes[0]}, cold compiles="
      f"{cold}, warm fused-fit compiles={warm_merge}")
assert cold >= 1, f"cold sweep booked {cold} compiles"
assert warm_merge == 0, f"warm re-sweep recompiled: {warm_merge}"
PY
PYTHONPATH="$PWD" python -m transmogrifai_tpu trace-report "$TRACE_DIR" --check
# the stats_pass spans must be visible to trace tooling (not just the
# in-process assert above): grep the exported chrome trace
python - "$TRACE_DIR" <<'PY'
import json
import sys

with open(sys.argv[1] + "/stats_trace.json") as f:
    doc = json.load(f)
names = [ev.get("name", "") for ev in doc["traceEvents"]]
n = sum(1 for nm in names if nm.startswith("stats_pass"))
assert n >= 4, f"expected >=4 stats_pass spans in the trace, saw {n}"
print(f"trace stats_pass spans ok ({n})")
PY
# double-buffering, checked from the ARTIFACT: tile_copy spans for later
# tiles must overlap tile_compute spans for earlier ones in the exported
# trace of the compute-heavy pass (docs/observability.md "Tile spans")
python - "$TRACE_DIR" <<'PY'
import json
import sys

with open(sys.argv[1] + "/stream_trace.json") as f:
    doc = json.load(f)
evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"
       and e.get("args", {}).get("label") == "ci_overlap"]


def spans(name):
    return [(e["ts"], e["ts"] + e["dur"], e["args"]["tile"])
            for e in evs if e["name"] == name]


copies, computes = spans("tile_copy"), spans("tile_compute")
assert len(copies) == 8 and len(computes) == 8, (len(copies),
                                                 len(computes))
overlap = any(ct > mt and cs < me and ms < ce
              for cs, ce, ct in copies for ms, me, mt in computes)
assert overlap, "no tile_copy overlapped an earlier tile_compute"
print("tileplane copy/compute overlap ok")
PY
rm -rf "$TRACE_DIR"

echo "== 6/7 driver-contract smoke =="
python - <<'PY'
import __graft_entry__ as g
g.dryrun_multichip(8)
PY
# NOTE: `python - <<HEREDOC` would clobber the piped stdin with the
# heredoc — the checker must use -c so the pipe stays on stdin
JAX_PLATFORMS=cpu BENCH_BUDGET_S=600 python bench.py | python -c '
import json, sys
lines = sys.stdin.read().strip().splitlines()
assert lines, "bench produced no output"
out = json.loads(lines[-1])
assert {"metric", "value", "unit", "vs_baseline"} <= set(out), out
print("bench JSON ok:", out["metric"], out["value"], out["unit"])
'

# multihost pod smoke: a REAL 2-process jax.distributed pod on localhost
# (gloo cross-process psums) — clean-run parity vs the single-process
# sweep, then a chaos kill of child 1 at the first GLM round boundary
# and a full-pod relaunch that resumes from the rank-0 RoundCheckpoint
# bit-identically (docs/performance.md "Multi-host pod scaling")
echo "== 7/7 multihost pod smoke =="
JAX_PLATFORMS=cpu python - <<'PY'
import os, shutil, tempfile
import numpy as np
from transmogrifai_tpu.parallel.launch import launch_local_pod

PAYLOAD = r"""
import json, os
import numpy as np
from transmogrifai_tpu.parallel import multihost as MH
MH.initialize()
import jax
pc = jax.process_count(); pid = jax.process_index()
mesh = MH.global_mesh(n_model=1)
rng = np.random.default_rng(1)
n, d = 40, 4
X = rng.normal(size=(n, d)).astype(np.float32)
y = (X[:, 0] - X[:, 2] + 0.3 * rng.normal(size=n) > 0).astype(np.float32)
w = np.ones(n, np.float32)
masks = np.zeros((2, n), np.float32)
masks[0, ::2] = 1.0
masks[1, 1::2] = 1.0
bounds = [0, 20, n] if pc == 2 else [0, n]
lo, hi = bounds[pid], bounds[pid + 1]
from transmogrifai_tpu.ops import glm_sweep as GS
from transmogrifai_tpu.automl.tuning.checkpoint import RoundCheckpoint
regs = np.asarray([1.0, 0.3, 0.1, 0.03], np.float32)
alphas = np.zeros(4, np.float32)
# rank-0-owned checkpoint: every rank LOADS the same file (the round
# state is replicated, so resume decisions stay SPMD-consistent), only
# rank 0 writes it
rc = RoundCheckpoint(os.path.join(os.environ["SMOKE_CK_DIR"], "rc.npz"))
KEY = "multihost-resume-smoke"
state = rc.load(KEY)
resumed = state is not None

def on_round(s):
    if pid == 0:
        rc.save(KEY, s)
    print("ROUND %d retired" % s["rounds"], flush=True)

B, b0, info = GS.sweep_glm_streamed_rounds(
    X[lo:hi], y[lo:hi], w[lo:hi], masks[:, lo:hi], regs, alphas,
    loss="logistic", mesh=mesh, round_iters=2, state=state,
    on_round=on_round)
out = dict(pid=pid, resumed=bool(resumed), rounds=int(info["glm_rounds"]),
           B=np.asarray(B).tolist(), b0=np.asarray(b0).tolist())
print("RESULT|" + json.dumps(out), flush=True)
MH.finalize()
"""

tmp = tempfile.mkdtemp(prefix="ci_mh_")
try:
    clean = os.path.join(tmp, "clean"); os.makedirs(clean)
    chaos = os.path.join(tmp, "chaos"); os.makedirs(chaos)

    # 1. clean 2-process pod run
    pod = launch_local_pod(PAYLOAD, n_procs=2, devices_per_proc=2,
                           timeout=300.0, extra_env={"SMOKE_CK_DIR": clean})
    assert pod.ok, (pod.error, [c.stderr_tail[-300:] for c in pod.children])
    ref = pod.result(0)
    assert not ref["resumed"]
    assert ref["B"] == pod.result(1)["B"], "pod ranks disagree"

    # single-process reference parity (same global data, no mesh)
    rng = np.random.default_rng(1)
    n, d = 40, 4
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] - X[:, 2]
         + 0.3 * rng.normal(size=n) > 0).astype(np.float32)
    w = np.ones(n, np.float32)
    masks = np.zeros((2, n), np.float32)
    masks[0, ::2] = 1.0
    masks[1, 1::2] = 1.0
    from transmogrifai_tpu.ops import glm_sweep as GS
    regs = np.asarray([1.0, 0.3, 0.1, 0.03], np.float32)
    alphas = np.zeros(4, np.float32)
    B1, _, _ = GS.sweep_glm_streamed_rounds(
        X, y, w, masks, regs, alphas, loss="logistic", round_iters=2)
    pd = float(np.max(np.abs(np.asarray(ref["B"]) - np.asarray(B1))))
    assert pd <= 1e-4, pd

    # 2. chaos: kill child 1 at the first retirement boundary
    pod = launch_local_pod(PAYLOAD, n_procs=2, devices_per_proc=2,
                           timeout=300.0, grace_s=2.0,
                           kill_on="retired", kill_target=1,
                           extra_env={"SMOKE_CK_DIR": chaos})
    assert not pod.ok and "chaos-killed" in (pod.error or ""), pod.error
    assert os.path.exists(os.path.join(chaos, "rc.npz")), \
        "no checkpoint written before the kill"

    # 3. relaunch the pod; every rank resumes from rank 0's checkpoint
    pod = launch_local_pod(PAYLOAD, n_procs=2, devices_per_proc=2,
                           timeout=300.0, extra_env={"SMOKE_CK_DIR": chaos})
    assert pod.ok, (pod.error, [c.stderr_tail[-300:] for c in pod.children])
    res = pod.result(0)
    assert res["resumed"], "resume run did not load the checkpoint"
    err = float(np.max(np.abs(np.asarray(res["B"])
                              - np.asarray(ref["B"]))))
    assert err == 0.0, err
    print("multihost smoke ok: pod parity %.1e, chaos kill + "
          "checkpoint resume bit-identical" % pd)
finally:
    shutil.rmtree(tmp, ignore_errors=True)
PY

# pod flight recorder (docs/observability.md "Pod tracing"): a clean
# traced 2-process pod must merge green (round-aligned swimlanes,
# >= 75% span coverage of every rank's round wall, 0 post-warmup
# recompiles); a chaos pod with a debug-sleep stall injected on rank 1
# must be NAMED by trace-report --pod; a wedged pod's timeout error must
# name the straggler's rank/round/phase from heartbeats
echo "== 7/7b pod flight recorder =="
JAX_PLATFORMS=cpu python - <<'PY'
import json, os, shutil, subprocess, sys, tempfile
import numpy as np
from transmogrifai_tpu.parallel import podtrace as PT
from transmogrifai_tpu.parallel.launch import launch_local_pod

PAYLOAD = r"""
import json, os
import numpy as np
from transmogrifai_tpu.parallel import multihost as MH
MH.initialize()
import jax
pc = jax.process_count(); pid = jax.process_index()
mesh = MH.global_mesh(n_model=1)
rng = np.random.default_rng(1)
n, d = 40, 4
X = rng.normal(size=(n, d)).astype(np.float32)
y = (X[:, 0] - X[:, 2] + 0.3 * rng.normal(size=n) > 0).astype(np.float32)
w = np.ones(n, np.float32)
masks = np.zeros((2, n), np.float32)
masks[0, ::2] = 1.0
masks[1, 1::2] = 1.0
bounds = [0, 20, n] if pc == 2 else [0, n]
lo, hi = bounds[pid], bounds[pid + 1]
from transmogrifai_tpu.ops import glm_sweep as GS
regs = np.asarray([1.0, 0.3, 0.1, 0.03], np.float32)
alphas = np.zeros(4, np.float32)
B, b0, info = GS.sweep_glm_streamed_rounds(
    X[lo:hi], y[lo:hi], w[lo:hi], masks[:, lo:hi], regs, alphas,
    loss="logistic", mesh=mesh, round_iters=2)
print("RESULT|" + json.dumps({"pid": pid,
                              "rounds": int(info["glm_rounds"])}),
      flush=True)
MH.finalize()
"""

WEDGE = r"""
import time
import numpy as np
from transmogrifai_tpu.parallel import multihost as MH
MH.initialize()
import jax
pid = jax.process_index()
mesh = MH.global_mesh(n_model=1)
from transmogrifai_tpu.parallel import podtrace
with podtrace.pod_round(0):
    if pid == 1:
        podtrace.beat("compute:wedged", rnd=0, force=True)
        time.sleep(600)
    from transmogrifai_tpu.ops import stats_engine as SE
    SE.fused_stats_sharded(mesh, np.ones((8, 2), np.float32),
                           np.ones(8, np.float32),
                           np.ones(8, np.float32))
MH.finalize()
"""


def run(trace_dir, **kw):
    # one retry on a fresh port (free_port's close-then-rebind race)
    pod = launch_local_pod(PAYLOAD, n_procs=2, devices_per_proc=2,
                           timeout=300.0, trace_dir=trace_dir, **kw)
    if not pod.ok:
        shutil.rmtree(trace_dir, ignore_errors=True)
        pod = launch_local_pod(PAYLOAD, n_procs=2, devices_per_proc=2,
                               timeout=300.0, trace_dir=trace_dir, **kw)
    assert pod.ok, (pod.error,
                    [c.stderr_tail[-300:] for c in pod.children])
    return pod


def round_compiles(rank_dir):
    """Per-rank [(round, bucket, compiles-in-window)] from the span
    tree — the post-warmup recompile gate's raw data."""
    doc = json.load(open(os.path.join(rank_dir, PT.METRICS_NAME)))
    spans = doc["spans"]
    rounds = sorted(
        ((s["attrs"]["round"], s["attrs"].get("bucket"),
          s["t_start"], s["t_end"])
         for s in spans if s["kind"] == "pod_round"),
        key=lambda r: r[0])
    out = []
    for rnd, bucket, t0, t1 in rounds:
        n = sum(int(s.get("attrs", {}).get("compiles") or 0) for s in spans
                if s["kind"] != "pod_round"
                and s.get("t_start") is not None
                and s.get("t_end") is not None
                and s["t_start"] >= t0 - 1e-6
                and s["t_end"] <= t1 + 1e-6)
        out.append((rnd, bucket, n))
    return out


tmp = tempfile.mkdtemp(prefix="ci_podtrace_")
try:
    # 1. clean traced pod -> merged timeline green
    clean = os.path.join(tmp, "clean")
    run(clean)
    rep = PT.merge_pod(clean)
    assert rep["problems"] == [], rep["problems"]
    assert not rep["synthetic_rounds"] and len(rep["rounds"]) >= 2
    assert rep["coverage_min_seen"] >= 0.75, rep["coverage_min_seen"]
    assert os.path.exists(rep["trace_path"])
    text, rc = PT.pod_report_rc(clean)
    assert rc == 0, text

    # 0 post-warmup recompiles: a round at an already-seen bucket shape
    # must compile nothing (the bucket-ladder contract, now visible per
    # rank in the flight recorder)
    for rank, rd in PT.rank_dirs(clean):
        seen, bad = set(), []
        for rnd, bucket, n in round_compiles(rd):
            if bucket in seen and n > 0:
                bad.append((rnd, bucket, n))
            seen.add(bucket)
        assert not bad, f"rank {rank}: post-warmup recompiles {bad}"

    # 2. chaos straggler: injected debug-sleep on rank 1 must be named,
    # through the CLI surface
    chaos = os.path.join(tmp, "chaos")
    run(chaos, debug_sleep_ms=200, debug_sleep_target=1)
    rep = PT.merge_pod(chaos)
    assert rep["skew"]["flagged"], rep["skew"]
    assert rep["skew"]["straggler_rank"] == 1, rep["skew"]
    r = subprocess.run(
        [sys.executable, "-m", "transmogrifai_tpu", "trace-report",
         "--pod", chaos], capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "straggler: rank 1" in r.stdout, r.stdout[-2000:]

    # 3. wedged pod: the reaper names rank/round/phase from heartbeats
    wedged = os.path.join(tmp, "wedged")
    pod = launch_local_pod(WEDGE, n_procs=2, devices_per_proc=2,
                           timeout=30.0, trace_dir=wedged)
    assert not pod.ok and "timeout" in (pod.error or ""), pod.error
    assert "likely straggler: rank 1" in pod.error, pod.error
    assert "compute:wedged" in pod.error, pod.error
    print("pod flight recorder ok: %d rounds merged, coverage %.0f%%, "
          "chaos straggler + wedge both named rank 1"
          % (len(rep["rounds"]), 100.0 * rep["coverage_min_seen"]))
finally:
    shutil.rmtree(tmp, ignore_errors=True)
PY

echo "CI GREEN"
