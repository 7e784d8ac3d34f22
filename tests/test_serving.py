"""Production serving engine (serve/): shape-bucketed micro-batching with
AOT-prewarmed executables (docs/serving.md).

Pins the subsystem's contracts: bucket-ladder shapes, request/batch parity
with the batch score path AND the local per-record replay, typed 400-class
validation errors, micro-batch coalescing + Overloaded load-shed +
graceful drain, the HTTP frontend's status-code mapping, the
streaming-quantile latency histogram, ZERO true XLA compiles after warmup
under concurrent mixed-batch-size traffic (RecompileTracker), and the
deploy-time prewarm: `serve --prewarm-only` followed by a fresh-process
start performs 0 true compiles (persistent-cache hits only).
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from transmogrifai_tpu import FeatureBuilder
from transmogrifai_tpu.automl import BinaryClassificationModelSelector
from transmogrifai_tpu.automl.transmogrifier import transmogrify
from transmogrifai_tpu.local.scoring import (InvalidFeatureError,
                                             MissingFeatureError,
                                             UnknownFeatureError)
from transmogrifai_tpu.models.glm import OpLogisticRegression
from transmogrifai_tpu.readers.readers import ListReader
from transmogrifai_tpu.serve import (MicroBatcher, Overloaded, ServeFrontend,
                                     ServingEngine, bucket_ladder,
                                     make_http_server, template_record)
from transmogrifai_tpu.stages.params import param_grid
from transmogrifai_tpu.utils import tracing
from transmogrifai_tpu.utils.metrics import LatencyHistogram, collector
from transmogrifai_tpu.workflow import Workflow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_rows(n=400, seed=3):
    rng = np.random.default_rng(seed)
    rows = []
    for _ in range(n):
        a = float(rng.normal())
        b = float(rng.normal())
        rows.append({"a": a, "b": b, "c": str(rng.choice(["x", "y", "z"])),
                     "y": float(a + 0.5 * b > 0)})
    return rows


def _fit_model(rows):
    """Workflow whose scoring DAG contains JITTED stages (the derived
    math features) — compile counting must measure something real."""
    fa = FeatureBuilder.Real("a").extract(lambda r: r.get("a")).as_predictor()
    fb = FeatureBuilder.Real("b").extract(lambda r: r.get("b")).as_predictor()
    fc = FeatureBuilder.PickList("c").extract(
        lambda r: r.get("c")).as_predictor()
    fy = FeatureBuilder.RealNN("y").extract(
        lambda r: r.get("y")).as_response()
    fsum = (fa + fb) + 1.0
    fnorm = fa.fill_missing_with_mean().z_normalize()
    pred = BinaryClassificationModelSelector.with_train_validation_split(
        models_and_parameters=[(OpLogisticRegression(max_iter=15),
                                param_grid(reg_param=[0.01]))],
    ).set_input(fy, transmogrify([fa, fb, fc, fsum, fnorm])).get_output()
    model = Workflow().set_reader(ListReader(rows)) \
        .set_result_features(pred).train()
    return model, pred


@pytest.fixture(scope="module")
def fitted():
    rows = _make_rows()
    model, pred = _fit_model(rows)
    return model, rows, pred


@pytest.fixture()
def collected():
    """Span collection + active RecompileTracker around one test."""
    collector.enable("test_serving")
    try:
        yield collector
    finally:
        collector.finish()
        collector.disable()


class TestBucketLadder:
    def test_ladder_shapes(self):
        assert bucket_ladder(64) == (1, 8, 16, 32, 64)
        assert bucket_ladder(1) == (1,)
        assert bucket_ladder(8) == (1, 8)
        # top rung rounds UP to a power of two
        assert bucket_ladder(100) == (1, 8, 16, 32, 64, 128)
        assert bucket_ladder(5) == (1, 8)

    def test_pick_bucket(self, fitted):
        model, _, _ = fitted
        eng = ServingEngine(model, max_batch=64)
        assert eng.pick_bucket(1) == 1
        assert eng.pick_bucket(2) == 8
        assert eng.pick_bucket(8) == 8
        assert eng.pick_bucket(9) == 16
        assert eng.pick_bucket(64) == 64
        with pytest.raises(ValueError, match="exceeds max bucket"):
            eng.pick_bucket(65)

    def test_explicit_buckets_and_validation(self, fitted):
        model, _, _ = fitted
        eng = ServingEngine(model, buckets=[4, 1, 32])
        assert eng.buckets == (1, 4, 32)
        assert eng.max_batch == 32
        with pytest.raises(ValueError, match="bucket sizes"):
            ServingEngine(model, buckets=[0, 4])
        with pytest.raises(ValueError, match="single_record"):
            ServingEngine(model, single_record="nope")

    def test_template_record(self, fitted):
        model, _, _ = fitted
        t = template_record(model.raw_features())
        assert set(t) == {"a", "b", "c"}  # responses excluded
        assert t["a"] == 0.0 and t["c"] == ""


class TestLatencyHistogram:
    def test_quantiles_track_percentiles(self):
        rng = np.random.default_rng(0)
        vals = rng.lognormal(mean=-5.0, sigma=1.0, size=5000)  # ~ms scale
        h = LatencyHistogram("t")
        for v in vals:
            h.record(float(v))
        for q in (0.5, 0.95, 0.99):
            est = h.quantile(q)
            true = float(np.quantile(vals, q))
            # log-bucketed: relative error bounded by the bucket ratio
            assert true / 1.6 <= est <= true * 1.6, (q, est, true)
        assert h.count == 5000
        assert h.max_seconds == pytest.approx(float(vals.max()))

    def test_json_fields_and_empty(self):
        h = LatencyHistogram("x")
        doc = h.to_json()
        assert doc["count"] == 0 and doc["p50_ms"] == 0.0
        h.record(0.010)
        doc = h.to_json()
        assert doc["count"] == 1 and doc["max_ms"] == 10.0
        assert doc["buckets_ms"]
        assert 2.0 < doc["p50_ms"] < 15.0

    def test_monotone_quantiles(self):
        h = LatencyHistogram("m")
        for v in (0.001, 0.002, 0.01, 0.2, 1.5):
            h.record(v)
        qs = [h.quantile(q) for q in (0.1, 0.5, 0.9, 0.99, 1.0)]
        assert qs == sorted(qs)

    def test_collector_latency_rides_appmetrics(self, collected):
        collected.latency("serve_total", 0.005)
        collected.latency("serve_total", 0.007)
        doc = collected.current.to_json()
        assert doc["latency_metrics"]["serve_total"]["count"] == 2

    def test_appmetrics_json_unchanged_without_latency(self):
        from transmogrifai_tpu.utils.metrics import AppMetrics
        assert "latency_metrics" not in AppMetrics().to_json()


class TestEngineScoring:
    def test_parity_with_batch_and_local(self, fitted):
        model, rows, pred = fitted
        eng = ServingEngine(model, max_batch=16)
        eng.prewarm()
        recs = [{k: v for k, v in r.items() if k != "y"}
                for r in rows[:10]]
        served = eng.score_batch(recs)
        scored = model.score()
        col = scored.column(pred.name)
        fn = model.score_function()
        from transmogrifai_tpu.models.prediction import probability_of
        probs = probability_of(col)
        for i, out in enumerate(served):
            rv = out[pred.name]
            assert isinstance(rv, dict)
            assert rv["probability_1"] == pytest.approx(
                float(probs[i, 1]), abs=1e-5)
            loc = fn(dict(recs[i]))[pred.name]
            loc = dict(loc.value if hasattr(loc, "value") else loc)
            assert rv["prediction"] == pytest.approx(
                float(loc["prediction"]), abs=1e-5)

    def test_padding_does_not_leak_into_results(self, fitted):
        model, rows, pred = fitted
        eng = ServingEngine(model, max_batch=16)
        recs = [{k: v for k, v in r.items() if k != "y"}
                for r in rows[:3]]
        out = eng.score_batch(recs)  # bucket 8, 5 pad rows
        assert len(out) == 3
        # one-at-a-time scores agree with the padded-batch scores
        for r, o in zip(recs, out):
            single = eng.score_batch([dict(r)])[0]
            assert single[pred.name]["prediction"] == \
                pytest.approx(o[pred.name]["prediction"], abs=1e-5)

    def test_bulk_chunks_above_max_batch(self, fitted):
        model, rows, _ = fitted
        eng = ServingEngine(model, buckets=[1, 8])
        recs = [{k: v for k, v in r.items() if k != "y"}
                for r in rows[:20]]
        assert len(eng.score_batch(recs)) == 20

    def test_single_record_local_route_parity(self, fitted):
        model, rows, pred = fitted
        bucket = ServingEngine(model, max_batch=8)
        local = ServingEngine(model, max_batch=8, single_record="local")
        bucket.prewarm()
        local.prewarm()
        rec = {k: v for k, v in rows[5].items() if k != "y"}
        b = bucket.score_record(dict(rec))[pred.name]
        l = local.score_record(dict(rec))[pred.name]
        assert l["prediction"] == pytest.approx(b["prediction"], abs=1e-5)
        assert l["probability_1"] == pytest.approx(b["probability_1"],
                                                   abs=1e-5)

    def test_missing_optional_key_scores(self, fitted):
        model, _, pred = fitted
        eng = ServingEngine(model, max_batch=8)
        out = eng.score_batch([{"a": 0.5}])  # b, c absent -> None/missing
        assert pred.name in out[0]

    def test_metrics_counters(self, fitted):
        model, rows, _ = fitted
        eng = ServingEngine(model, max_batch=8)
        eng.prewarm()
        eng.score_batch([{k: v for k, v in rows[0].items() if k != "y"}])
        m = eng.metrics()
        assert m["warm"] and m["rows"] >= 1 and m["batches"] >= 1
        assert m["latency"]["device_score"]["count"] >= 1
        assert m["post_warmup_compiles"] == 0


class TestRecordValidation:
    def test_unknown_key_typed_error(self, fitted):
        model, _, _ = fitted
        eng = ServingEngine(model)
        with pytest.raises(UnknownFeatureError, match="bogus"):
            eng.validate_record({"a": 1.0, "bogus": 2.0})

    def test_non_strict_allows_extra_keys(self, fitted):
        model, _, _ = fitted
        eng = ServingEngine(model, strict_keys=False)
        eng.validate_record({"a": 1.0, "row_id": "r1"})  # no raise

    def test_missing_feature_named(self):
        rows = _make_rows(200)
        # hard [] access: a missing key used to KeyError deep in a stage
        fa = FeatureBuilder.Real("a").extract(
            lambda r: r["a"]).as_predictor()
        fy = FeatureBuilder.RealNN("y").extract(
            lambda r: r.get("y")).as_response()
        pred = BinaryClassificationModelSelector \
            .with_train_validation_split(
                models_and_parameters=[(OpLogisticRegression(),
                                        param_grid(reg_param=[0.01]))],
            ).set_input(fy, transmogrify([fa])).get_output()
        model = Workflow().set_reader(ListReader(rows)) \
            .set_result_features(pred).train()
        eng = ServingEngine(model, strict_keys=False)
        with pytest.raises(MissingFeatureError, match="'a'"):
            eng.validate_record({"b": 1.0})
        # the per-record replay raises the SAME typed error
        with pytest.raises(MissingFeatureError, match="'a'"):
            model.score_function()({"b": 1.0})
        # MissingFeatureError still satisfies a legacy KeyError handler
        assert issubclass(MissingFeatureError, KeyError)

    def test_invalid_value_typed_error(self, fitted):
        model, _, _ = fitted
        eng = ServingEngine(model)
        with pytest.raises(InvalidFeatureError, match="'a'"):
            eng.validate_record({"a": "not-a-number"})

    def test_record_must_be_dict(self, fitted):
        model, _, _ = fitted
        eng = ServingEngine(model)
        with pytest.raises(InvalidFeatureError):
            eng.validate_record([1, 2, 3])


class TestZeroRecompilesUnderTraffic:
    def test_concurrent_mixed_batch_sizes(self, fitted, collected):
        """THE acceptance pin: after prewarm, concurrent traffic at every
        batch size in [1, max_batch] performs zero true XLA compiles —
        every shape the device sees is a prewarmed bucket."""
        model, rows, pred = fitted
        eng = ServingEngine(model, max_batch=16)
        eng.prewarm()
        base = tracing.tracker.true_compiles
        batcher = MicroBatcher(eng, max_wait_ms=3.0, max_queue=256)
        recs = [{k: v for k, v in r.items() if k != "y"} for r in rows]
        errors = []

        def single(i):
            try:
                out = batcher.submit(dict(recs[i % len(recs)]))
                assert pred.name in out
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append(e)

        def bulk(k):
            try:
                out = eng.score_batch(
                    [dict(r) for r in recs[:k]])
                assert len(out) == k
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=single, args=(i,))
                   for i in range(24)]
        threads += [threading.Thread(target=bulk, args=(k,))
                    for k in (1, 2, 5, 8, 11, 16, 3, 13)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        batcher.shutdown(drain=True)
        assert not errors, errors[:3]
        assert tracing.tracker.true_compiles == base
        assert eng.post_warmup_compiles == 0
        m = eng.metrics()
        assert m["requests"] >= 24
        assert m["latency"]["total"]["count"] >= 24


class TestWatchWithCollectionOff:
    """The tracker is always on (PR 33): the prewarm summary and the
    post-warm-up recompile watch no longer need collector.enable()."""

    def test_prewarm_counts_and_the_watch_fires(self, fitted):
        import jax
        import jax.numpy as jnp
        assert not collector.enabled
        model, rows, _ = fitted
        x = jnp.ones(3)   # its own one-op program, before the warm-up
        eng = ServingEngine(model, max_batch=8)
        summary = eng.prewarm()
        for key in ("compiles", "cache_hits"):
            assert isinstance(summary[key], int) and summary[key] >= 0, key
            assert eng.metrics()["prewarm"][key] == summary[key]
        assert all(isinstance(b["compiles"], int)
                   for b in summary["per_bucket"])
        recs = [{k: v for k, v in r.items() if k != "y"} for r in rows[:5]]
        eng.score_batch(recs)
        assert eng.post_warmup_compiles == 0
        # a shape that escaped the ladder: a program no cache can hold
        # (its constant is this moment), compiled after warm-up
        jax.jit(lambda v: v * time.time())(x).block_until_ready()
        eng.score_batch(recs)
        assert eng.post_warmup_compiles == 1
        assert eng.metrics()["post_warmup_compiles"] == 1


class TestMicroBatcher:
    def _engine_stub(self, fitted, delay=0.0):
        model, _, _ = fitted
        eng = ServingEngine(model, max_batch=8)
        eng.prewarm()
        calls = []
        real = eng.score_batch

        def spy(records):
            calls.append(len(records))
            if delay:
                time.sleep(delay)
            return real(records)

        # tmoglint: disable=THR001  test fixture patches BEFORE threads
        eng.score_batch = spy
        return eng, calls

    def test_coalesces_concurrent_submits(self, fitted):
        eng, calls = self._engine_stub(fitted, delay=0.05)
        b = MicroBatcher(eng, max_wait_ms=100.0, max_queue=64)
        results = []
        ths = [threading.Thread(
            target=lambda i=i: results.append(
                b.submit({"a": 0.1 * i, "b": 0.0, "c": "x"})))
            for i in range(6)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(30)
        b.shutdown()
        assert len(results) == 6
        # 6 near-simultaneous submits must NOT make 6 device batches
        # (first dispatch may race ahead with fewer; never one-per-request)
        assert len(calls) < 6
        assert sum(calls) == 6

    def test_overload_sheds_typed(self, fitted):
        eng, _ = self._engine_stub(fitted, delay=0.3)
        b = MicroBatcher(eng, max_wait_ms=0.0, max_queue=2)

        def sub():
            try:
                b.submit({"a": 1.0, "b": 0.0, "c": "x"})
            except Overloaded:
                pass  # racing threads may be shed too — that's the point

        ths = [threading.Thread(target=sub) for _ in range(4)]
        for t in ths:
            t.start()
        time.sleep(0.1)  # dispatcher busy on batch 1, queue refills
        with b._cond:
            while len(b._q) < b.max_queue:  # fill whatever room is left
                from transmogrifai_tpu.serve.batcher import _Pending
                b._q.append(_Pending({"a": 0.0, "b": 0.0, "c": "x"}))
        with pytest.raises(Overloaded):
            b.submit({"a": 2.0, "b": 0.0, "c": "x"})
        assert eng.n_shed >= 1
        b.shutdown(drain=True)
        for t in ths:
            t.join(30)

    def test_graceful_drain_scores_everything(self, fitted):
        eng, calls = self._engine_stub(fitted, delay=0.05)
        b = MicroBatcher(eng, max_wait_ms=50.0, max_queue=64)
        results, errs = [], []

        def sub(i):
            try:
                results.append(b.submit({"a": float(i), "b": 0.0,
                                         "c": "y"}))
            except Exception as e:
                errs.append(e)

        ths = [threading.Thread(target=sub, args=(i,)) for i in range(10)]
        for t in ths:
            t.start()
        time.sleep(0.02)
        b.shutdown(drain=True)  # refuse new, score queued
        for t in ths:
            t.join(30)
        assert not errs
        assert len(results) == 10
        assert sum(calls) == 10

    def test_timeout_withdraws_queued_request(self, fitted):
        """A timed-out submit must pull its request back OUT of the
        queue: it is neither scored nor counted, and stops holding
        queue capacity (review finding)."""
        eng, calls = self._engine_stub(fitted, delay=0.4)
        b = MicroBatcher(eng, max_wait_ms=0.0, max_queue=8)
        # occupy the dispatcher so the next submit stays queued
        t1 = threading.Thread(
            target=lambda: b.submit({"a": 1.0, "b": 0.0, "c": "x"}))
        t1.start()
        time.sleep(0.1)
        n_req0 = eng.n_requests
        with pytest.raises(TimeoutError):
            b.submit({"a": 2.0, "b": 0.0, "c": "x"}, timeout=0.05)
        t1.join(30)
        b.shutdown(drain=True)
        # the withdrawn record never reached the engine
        assert sum(calls) == 1
        assert eng.n_requests == n_req0 + 1  # only the live request

    def test_submit_after_shutdown_raises(self, fitted):
        eng, _ = self._engine_stub(fitted)
        b = MicroBatcher(eng)
        b.shutdown()
        with pytest.raises(RuntimeError, match="shut down"):
            b.submit({"a": 1.0, "b": 0.0, "c": "x"})

    def test_systemic_error_propagates_to_waiters(self, fitted):
        model, _, _ = fitted
        eng = ServingEngine(model, max_batch=8)
        eng.prewarm()

        def boom(records):
            raise RuntimeError("device on fire")

        eng.score_batch = boom
        b = MicroBatcher(eng, max_wait_ms=1.0)
        with pytest.raises(RuntimeError, match="device on fire"):
            b.submit({"a": 1.0, "b": 0.0, "c": "x"}, timeout=30)
        b.shutdown()

    def test_validation_rejected_before_admission(self, fitted):
        eng, calls = self._engine_stub(fitted)
        b = MicroBatcher(eng)
        with pytest.raises(UnknownFeatureError):
            b.submit({"a": 1.0, "nope": 1.0})
        b.shutdown()
        assert sum(calls) == 0  # never reached the engine


class TestHTTPFrontend:
    @pytest.fixture()
    def server(self, fitted):
        model, _, pred = fitted
        eng = ServingEngine(model, max_batch=8)
        eng.prewarm()
        batcher = MicroBatcher(eng, max_wait_ms=2.0)
        fe = ServeFrontend(eng, batcher)
        httpd = make_http_server(fe)
        th = threading.Thread(target=httpd.serve_forever,
                              kwargs={"poll_interval": 0.05}, daemon=True)
        th.start()
        yield httpd.server_address[1], pred
        httpd.shutdown()
        httpd.server_close()
        batcher.shutdown()

    def _req(self, port, path, payload=None):
        import urllib.error
        import urllib.request
        url = f"http://127.0.0.1:{port}{path}"
        if payload is None:
            req = urllib.request.Request(url)
        else:
            req = urllib.request.Request(
                url, data=json.dumps(payload).encode(), method="POST")
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def test_score_single_and_bulk(self, server):
        port, pred = server
        code, out = self._req(port, "/score",
                              {"a": 0.3, "b": -0.1, "c": "x"})
        assert code == 200 and pred.name in out
        code, out = self._req(port, "/score",
                              [{"a": 0.1, "b": 0.0, "c": "y"},
                               {"a": -0.4, "b": 1.0, "c": "z"}])
        assert code == 200 and len(out) == 2

    def test_client_errors_are_400(self, server):
        port, _ = server
        code, out = self._req(port, "/score", {"a": 1.0, "junk": 1})
        assert code == 400 and out["error_type"] == "UnknownFeatureError"
        code, out = self._req(port, "/score", 42)
        assert code == 400

    def test_healthz_and_metrics(self, server):
        port, _ = server
        code, h = self._req(port, "/healthz")
        assert code == 200 and h["warm"] is True
        self._req(port, "/score", {"a": 0.0, "b": 0.0, "c": "x"})
        code, m = self._req(port, "/metrics")
        assert code == 200
        assert m["requests"] >= 1
        assert "p99_ms" in m["latency"]["total"]

    def test_unknown_path_404(self, server):
        port, _ = server
        code, _ = self._req(port, "/nope")
        assert code == 404

    def test_drain_flips_healthz_and_drops_nothing(self, server):
        """The /drain satellite (docs/fleet.md): GET /drain flips
        /healthz to draining-503 so a router/LB rotates the replica out
        BEFORE SIGTERM — while every in-flight and still-arriving
        request keeps scoring (the no-dropped-requests pin)."""
        port, pred = server
        errors, oks = [], []

        def fire(n):
            for _ in range(n):
                try:
                    code, out = self._req(port, "/score",
                                          {"a": 0.1, "b": 0.2, "c": "x"})
                    assert code == 200 and pred.name in out, (code, out)
                    oks.append(1)
                except Exception as e:  # noqa: BLE001 - tallied below
                    errors.append(repr(e))

        threads = [threading.Thread(target=fire, args=(8,))
                   for _ in range(4)]
        for t in threads:
            t.start()
        # flip the drain mid-traffic
        code, d = self._req(port, "/drain")
        assert code == 200 and d["draining"] is True
        assert d["status"] == "draining"
        for t in threads:
            t.join(60)
        assert not errors, errors[:3]
        assert len(oks) == 32  # nothing dropped
        # the LB view: healthz is 503/draining, idempotently
        code, h = self._req(port, "/healthz")
        assert code == 503 and h["status"] == "draining"
        code, h = self._req(port, "/drain")
        assert code == 200 and h["status"] == "draining"
        # ... and scoring STILL works (drain is rotation, not refusal)
        code, out = self._req(port, "/score",
                              {"a": 0.0, "b": 0.0, "c": "y"})
        assert code == 200 and pred.name in out

    def test_bulk_above_max_bulk_is_413(self, fitted):
        model, _, _ = fitted
        eng = ServingEngine(model, max_batch=8)
        batcher = MicroBatcher(eng, max_wait_ms=1.0)
        fe = ServeFrontend(eng, batcher, max_bulk=3)
        httpd = make_http_server(fe)
        th = threading.Thread(target=httpd.serve_forever,
                              kwargs={"poll_interval": 0.05}, daemon=True)
        th.start()
        try:
            code, out = self._req(
                httpd.server_address[1], "/score",
                [{"a": 0.0, "b": 0.0, "c": "x"}] * 4)
            assert code == 413 and "max_bulk" in out["error"]
        finally:
            httpd.shutdown()
            httpd.server_close()
            batcher.shutdown()


class TestServeEvents:
    def test_events_and_trace_check(self, fitted, collected, tmp_path):
        model, rows, _ = fitted
        collected.attach_event_log(str(tmp_path / "events.jsonl"))
        try:
            eng = ServingEngine(model, max_batch=8)
            eng.prewarm()
            b = MicroBatcher(eng, max_wait_ms=1.0)
            b.submit({k: v for k, v in rows[0].items() if k != "y"})
            eng.note_shed(queue_len=5)  # the shed path's event
            b.shutdown(drain=True)
            collected.save_chrome_trace(str(tmp_path / "serve_trace.json"),
                                        close=False)
        finally:
            collected.detach_event_log()
        events = [json.loads(l) for l in
                  (tmp_path / "events.jsonl").read_text().splitlines()]
        kinds = {e["event"] for e in events}
        assert {"serve_prewarm", "serve_batch", "serve_request",
                "serve_shed"} <= kinds
        assert "serve_recompile" not in kinds
        from transmogrifai_tpu.utils.tracing import trace_report
        text, ok = trace_report(str(tmp_path), check=True)
        assert ok, text
        # serve spans land in the exported trace
        doc = json.loads((tmp_path / "serve_trace.json").read_text())
        names = {e.get("name") for e in doc["traceEvents"]}
        assert {"batch_assemble", "device_score", "queue_wait"} <= names

    def test_trace_check_fails_on_post_warmup_recompile(self, tmp_path):
        (tmp_path / "events.jsonl").write_text(
            json.dumps({"seq": 0, "t": 0.0, "ts": 0.0,
                        "event": "serve_recompile", "compiles": 1}) + "\n")
        from transmogrifai_tpu.utils.tracing import trace_report
        text, ok = trace_report(str(tmp_path), check=True)
        assert not ok
        assert "serve_recompile" in text


class TestManifestFreshness:
    """The serve.json freshness stamp (docs/fleet.md "The manifest
    contract"): --prewarm-only stamps model hash + monitor presence;
    adoption verifies both — warning by default, rc 2 under
    --strict-manifest (how a fleet replica refuses to join)."""

    def _saved(self, fitted, tmp_path):
        from transmogrifai_tpu.workflow.workflow import WorkflowModel
        model, _, _ = fitted
        mdir = str(tmp_path / "model")
        model.save(mdir)
        m2 = WorkflowModel.load(mdir)
        eng = ServingEngine(m2, buckets=[1, 4])
        eng.write_manifest()
        return mdir

    def test_fresh_manifest_verifies_clean(self, fitted, tmp_path):
        from transmogrifai_tpu.workflow.io import load_serve_manifest
        from transmogrifai_tpu.workflow.workflow import WorkflowModel
        mdir = self._saved(fitted, tmp_path)
        manifest = load_serve_manifest(mdir)
        assert manifest["model_hash"] and len(manifest["model_hash"]) == 16
        assert isinstance(manifest["monitor_profile"], bool)
        eng = ServingEngine(WorkflowModel.load(mdir))
        assert eng.manifest_mismatch == []

    def test_stale_hash_warns_and_strict_refuses(self, fitted, tmp_path):
        import argparse
        from transmogrifai_tpu.serve.frontend import run_serve
        from transmogrifai_tpu.workflow.workflow import WorkflowModel
        mdir = self._saved(fitted, tmp_path)
        # the model is re-saved/modified AFTER the prewarm stamped it
        with open(os.path.join(mdir, "arrays.npz"), "ab") as f:
            f.write(b"drift")
        eng = ServingEngine(WorkflowModel.load(mdir))
        assert eng.manifest_mismatch  # adoption NOTICED (warning path)
        assert any("model_hash" in p for p in eng.manifest_mismatch)
        # --strict-manifest: the same staleness is a startup refusal
        args = argparse.Namespace(
            model_dir=mdir, max_batch=8, buckets=None, example=None,
            single_record="bucket", monitor="off", metrics_location=None,
            strict_manifest=True, prewarm_only=True)
        assert run_serve(args) == 2

    def test_explicit_bucket_disagreement_is_flagged(self, fitted,
                                                     tmp_path):
        from transmogrifai_tpu.workflow.workflow import WorkflowModel
        mdir = self._saved(fitted, tmp_path)
        eng = ServingEngine(WorkflowModel.load(mdir), buckets=[1, 8, 16])
        assert any("bucket ladder" in p for p in eng.manifest_mismatch)

    def test_monitor_profile_change_is_flagged(self, fitted, tmp_path):
        from transmogrifai_tpu.workflow.workflow import WorkflowModel
        mdir = self._saved(fitted, tmp_path)
        mon = os.path.join(mdir, "monitor.json")
        if os.path.exists(mon):
            os.remove(mon)  # profile vanished since the stamp
        else:
            with open(mon, "w") as f:
                json.dump({"features": []}, f)  # profile appeared
        eng = ServingEngine(WorkflowModel.load(mdir))
        assert any("monitor.json" in p for p in eng.manifest_mismatch)


class TestPrewarmManifestAndPersistentCache:
    def test_manifest_roundtrip(self, fitted, tmp_path):
        model, _, _ = fitted
        mdir = str(tmp_path / "model")
        model.save(mdir)
        from transmogrifai_tpu.workflow.workflow import WorkflowModel
        m2 = WorkflowModel.load(mdir)
        assert m2.source_path == mdir
        eng = ServingEngine(m2, buckets=[1, 4])
        assert eng.write_manifest() == os.path.join(mdir, "serve.json")
        # a fresh engine over the same dir adopts the manifest ladder
        eng2 = ServingEngine(WorkflowModel.load(mdir))
        assert eng2.buckets == (1, 4)
        # corrupt manifest: startup must not crash, defaults win
        with open(os.path.join(mdir, "serve.json"), "w") as f:
            f.write("{broken")
        eng3 = ServingEngine(WorkflowModel.load(mdir), max_batch=8)
        assert eng3.buckets == (1, 8)

    def test_prewarm_only_then_fresh_process_zero_compiles(self, fitted,
                                                           tmp_path):
        """THE deploy-time acceptance pin: `serve --prewarm-only`
        populates the persistent compilation cache; a fresh process
        serving the same artifact performs 0 true XLA compiles — every
        bucket executable is a cache hit."""
        model, _, _ = fitted
        mdir = str(tmp_path / "model")
        model.save(mdir)
        cache = str(tmp_path / "xla-cache")
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
                   TMOG_COMPILE_CACHE_DIR=cache)
        env.pop("PYTHONSTARTUP", None)
        r1 = subprocess.run(
            [sys.executable, "-m", "transmogrifai_tpu", "serve", mdir,
             "--prewarm-only", "--max-batch", "8"],
            env=env, capture_output=True, text=True, timeout=300)
        assert r1.returncode == 0, r1.stderr[-2000:]
        doc = json.loads(r1.stdout.strip().splitlines()[-1])
        assert doc["prewarm"]["buckets"] == [1, 8]
        assert doc["prewarm"]["manifest"] == os.path.join(mdir,
                                                          "serve.json")
        assert os.listdir(cache), "prewarm populated no cache entries"
        probe = (
            "import os\n"
            "from transmogrifai_tpu.utils.metrics import collector\n"
            "from transmogrifai_tpu.utils import tracing\n"
            "from transmogrifai_tpu.serve import ServingEngine\n"
            "collector.enable('probe')\n"
            f"eng = ServingEngine({mdir!r})\n"
            "s = eng.prewarm()\n"
            "assert eng.buckets == (1, 8), eng.buckets  # manifest ladder\n"
            "print('TRUE_COMPILES=%d CACHE_HITS=%d'\n"
            "      % (tracing.tracker.true_compiles,\n"
            "         tracing.tracker.total_cache_hits))\n"
        )
        r2 = subprocess.run([sys.executable, "-c", probe], env=env,
                            capture_output=True, text=True, timeout=300)
        assert r2.returncode == 0, r2.stderr[-2000:]
        line = [l for l in r2.stdout.splitlines()
                if l.startswith("TRUE_COMPILES=")][0]
        true_c = int(line.split()[0].split("=")[1])
        hits = int(line.split()[1].split("=")[1])
        assert true_c == 0, f"fresh-process prewarm compiled: {line}"
        # the jitted math stages really exist AND all loaded from cache
        assert hits > 0, line
