"""Unified double-buffered streaming data plane (parallel/tileplane.py).

Covers the pipeline core (fixed-shape re-tiling, bounded host buffer,
error propagation, tile_copy/tile_compute spans + overlap), the four
rewired consumers (stats engine, GLM rounds, tree binning, bulk scoring:
streamed-via-tileplane == resident parity, TMOG_TILEPLANE=0 legacy
parity), the RecompileTracker pins (one tile executable per consumer
shape, 0 recompiles from tile 2 onward), the first-tile Gram-shift
satellite (every row of the source read exactly ONCE even with
corr_matrix), the reader mid-write stability satellite, and the
larger-than-memory contract: an Avro-served fit with X never
materialized and the peak tileplane host buffer <= 2 tiles.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.ops import glm_sweep as GS
from transmogrifai_tpu.ops import stats_engine as SE
from transmogrifai_tpu.ops import trees as T
from transmogrifai_tpu.parallel import tileplane as TP
from transmogrifai_tpu.utils.metrics import collector


@pytest.fixture
def traced():
    collector.enable("test_tileplane")
    try:
        yield collector
    finally:
        collector.finish()
        collector.disable()


def _counting_source(X, y, w, chunk_rows):
    """ArraySource that counts every row handed out — the single-read
    pin: corr_matrix must NOT re-read the first tile."""

    class Counting(TP.ArraySource):
        rows_yielded = 0
        passes = 0

        def chunks(self):
            Counting.passes += 1
            for chunk in super().chunks():
                Counting.rows_yielded += chunk[0].shape[0]
                yield chunk

    return Counting(X, y, w, chunk_rows=chunk_rows)


class TestPipelineCore:
    def test_sum_parity_and_ragged_tail(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(1013, 3)).astype(np.float32)
        w = rng.uniform(0.5, 1.5, size=1013).astype(np.float32)
        src = TP.ArraySource(X, w, chunk_rows=97)

        @jax.jit
        def step(carry, xt, wt):
            return carry + (xt * wt[:, None]).sum(0)

        carry, stats = TP.run_tileplane(
            src, step, jnp.zeros(3, jnp.float32), tile_rows=128,
            label="core")
        np.testing.assert_allclose(np.asarray(carry),
                                   (X * w[:, None]).sum(0), rtol=1e-5)
        assert stats.tiles == -(-1013 // 128)
        assert stats.rows == 1013

    def test_peak_host_buffer_under_two_tiles(self):
        X = np.ones((5000, 4), np.float32)
        src = TP.ArraySource(X, chunk_rows=256)

        @jax.jit
        def step(carry, xt):
            return carry + xt.sum()

        _, stats = TP.run_tileplane(src, step, jnp.zeros((), jnp.float32),
                                    tile_rows=512, label="peak")
        # one tile being assembled + at most one chunk in hand
        assert stats.peak_host_rows <= 2 * 512

    def test_producer_error_propagates(self):
        def factory():
            yield (np.ones((10, 2), np.float32),)
            raise RuntimeError("reader died")

        src = TP.IterSource(factory)

        @jax.jit
        def step(carry, xt):
            return carry + xt.sum()

        with pytest.raises(RuntimeError, match="reader died"):
            TP.run_tileplane(src, step, jnp.zeros((), jnp.float32),
                             tile_rows=8, label="err")

    def test_sink_order_and_valid_rows(self):
        X = np.arange(130, dtype=np.float32).reshape(-1, 1)
        src = TP.ArraySource(X, chunk_rows=40)
        got = []

        @jax.jit
        def step(carry, xt):
            return carry, xt * 2.0

        TP.run_tileplane(src, step, jnp.zeros((), jnp.float32),
                         tile_rows=32, label="sink",
                         sink=lambda t, n: got.append(t[:n]))
        np.testing.assert_allclose(np.concatenate(got), X * 2.0)

    def test_tile_spans_and_overlap(self, traced):
        # compute-heavy step (Gram per 2000x96 tile) so each tile_compute
        # window comfortably contains the producer's next tile_copy
        X = np.random.default_rng(1).normal(
            size=(16000, 96)).astype(np.float32)
        src = TP.ArraySource(X, chunk_rows=2000)

        @jax.jit
        def step(carry, xt):
            g = jnp.matmul(xt.T, xt, preferred_element_type=jnp.float32)
            return carry + jnp.matmul(g, g,
                                      preferred_element_type=jnp.float32)

        with collector.trace_span("pass", kind="span"):
            _, stats = TP.run_tileplane(
                src, step, jnp.zeros((96, 96), jnp.float32),
                tile_rows=2000, label="spans")
        copies = [s for s in collector.trace.spans if s.name == "tile_copy"]
        computes = [s for s in collector.trace.spans
                    if s.name == "tile_compute"]
        assert len(copies) == stats.tiles == 8
        assert len(computes) == 8
        # double buffering: some tile k+1 copy window must intersect an
        # earlier tile's compute window
        overlap = any(
            c.attrs["tile"] > m.attrs["tile"]
            and c.t_start < m.t_end and m.t_start < c.t_end
            for c in copies for m in computes)
        assert overlap, "producer copies never overlapped compute"

    def test_tile_rows_for_env(self, monkeypatch):
        monkeypatch.setenv("TMOG_TILE_MB", "1")
        assert TP.tile_rows_for(1024) == (1 << 20) // 1024
        assert TP.tile_rows_for(4, multiple=3) % 3 == 0

    def test_pipelined_propagates_and_orders(self):
        def gen():
            for i in range(5):
                yield i

        assert list(TP.pipelined(gen(), label="t")) == list(range(5))

        def bad():
            yield 1
            raise ValueError("boom")

        with pytest.raises(ValueError, match="boom"):
            list(TP.pipelined(bad(), label="t"))


class TestStatsConsumer:
    def _data(self, n=3000, d=6, seed=0):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, d)).astype(np.float32) + 50.0
        X[rng.uniform(size=X.shape) < 0.08] = np.nan
        y = rng.integers(0, 2, size=n).astype(np.float32)
        return X, y

    def test_streamed_matches_fused_full_stats(self):
        X, y = self._data()
        lo = np.nanmin(X, 0).astype(np.float32)
        hi = np.nanmax(X, 0).astype(np.float32)
        kw = dict(corr_matrix=True, lo=lo, hi=hi, bins=12,
                  distinct=np.asarray([0.0, 1.0], np.float32))
        fused = SE.run_stats(X, y, **kw)
        streamed = SE.run_stats(X, y, driver="streamed", tile_rows=400,
                                **kw)
        for f in ("count", "mean", "variance", "min", "max", "fill_rate",
                  "corr_label", "num_non_zeros"):
            np.testing.assert_allclose(getattr(streamed, f),
                                       getattr(fused, f), rtol=2e-4,
                                       atol=2e-5, err_msg=f)
        np.testing.assert_allclose(streamed.corr_matrix, fused.corr_matrix,
                                   rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(streamed.hist, fused.hist, rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(streamed.contingency, fused.contingency,
                                   rtol=2e-4, atol=1e-4)

    def test_kill_switch_legacy_parity(self, monkeypatch):
        X, y = self._data(seed=3)
        fused = SE.run_stats(X, y, corr_matrix=True)
        monkeypatch.setenv("TMOG_TILEPLANE", "0")
        legacy = SE.run_stats(X, y, corr_matrix=True, driver="streamed",
                              tile_rows=500)
        np.testing.assert_allclose(legacy.mean, fused.mean, rtol=2e-4,
                                   atol=2e-5)
        np.testing.assert_allclose(legacy.corr_matrix, fused.corr_matrix,
                                   rtol=2e-3, atol=2e-4)

    @pytest.mark.parametrize("tileplane", ["1", "0"])
    def test_single_read_even_with_corr_matrix(self, monkeypatch,
                                               tileplane):
        """The Gram-shift satellite: the first tile's rows flow into the
        pipeline ONCE (the old host pre-pass re-read rows 0:c)."""
        monkeypatch.setenv("TMOG_TILEPLANE", tileplane)
        X, y = self._data(n=2000, seed=5)
        src = _counting_source(X, y, np.ones(2000, np.float32),
                               chunk_rows=250)
        res = SE.run_stats(src, corr_matrix=True, tile_rows=500)
        # one DATA pass + the cached one-chunk shape probe: no row of
        # the first tile flows through the pipeline twice (the old host
        # shift pre-pass re-read rows 0:c)
        assert type(src).passes <= 2
        assert type(src).rows_yielded <= 2000 + 250
        fused = SE.run_stats(X, y, corr_matrix=True)
        np.testing.assert_allclose(res.corr_matrix, fused.corr_matrix,
                                   rtol=2e-3, atol=2e-4)

    def test_one_tile_executable_zero_recompiles_after_tile2(self, traced):
        """RecompileTracker pin: the streamed pass compiles its tile
        program at most twice (shift + merge step) on the FIRST tiles;
        a whole second pass over the same shape books 0 compiles."""
        X, y = self._data(n=2500, d=5, seed=7)
        SE.run_stats(X, y, corr_matrix=True, driver="streamed",
                     tile_rows=500)  # warm: compiles land here
        with collector.trace_span("pinned", kind="span") as sp:
            SE.run_stats(X, y, corr_matrix=True, driver="streamed",
                         tile_rows=500)
        subtree = [s for s in collector.trace.spans
                   if s.span_id == sp.span_id
                   or s.parent_id == sp.span_id]
        assert sum(int(s.attrs.get("compiles", 0)) for s in subtree) == 0

    def test_sharded_tileplane_lane(self):
        from transmogrifai_tpu.parallel.mesh import make_mesh
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        X, y = self._data(n=2200, d=5, seed=9)
        fused = SE.run_stats(X, y, corr_matrix=True)
        sh = SE.run_stats(X, y, corr_matrix=True, driver="streamed",
                          mesh=make_mesh(n_batch=2), tile_rows=512)
        np.testing.assert_allclose(sh.mean, fused.mean, rtol=2e-4,
                                   atol=2e-5)
        np.testing.assert_allclose(sh.corr_matrix, fused.corr_matrix,
                                   rtol=2e-3, atol=2e-4)


class TestGLMConsumer:
    def _problem(self, n=1600, d=5, F=3, seed=11):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, d)).astype(np.float32)
        X[:, 1] += 30.0
        beta = rng.normal(size=d)
        y = (X @ beta + 0.2 * rng.normal(size=n)
             > np.median(X @ beta)).astype(np.float32)
        w = np.ones(n, np.float32)
        fold = rng.integers(0, F, size=n)
        masks = np.stack([(fold != k).astype(np.float32)
                          for k in range(F)])
        return X, y, w, masks

    def test_source_rounds_match_device_rounds(self, monkeypatch):
        """Both drivers obey one retire rule (delta <= tol), so they take
        the same rounds — at a tolerance float32 can resolve. 1e-7 cannot
        be asserted: it is under one float32 ulp of the update (2^-23 ~
        1.19e-7), and after 10 iterations both drivers' deltas ARE that
        rounding noise; the order in which 400-row tiles are summed then
        decides whether a lane reads 6.0e-8 or 1.27e-7 and takes a third
        round."""
        monkeypatch.setattr(
            "transmogrifai_tpu.parallel.tileplane.tile_rows_for",
            lambda *a, **k: 400)  # force a multi-tile pass
        X, y, w, masks = self._problem()
        regs = np.asarray([0.02, 0.2], np.float32)
        alphas = np.asarray([0.0, 0.5], np.float32)
        B_dev, b0_dev, info_dev = GS.sweep_glm_streamed_rounds(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), regs, alphas, loss="logistic",
            max_iter=25, tol=1e-6, warm_start=False)
        src = TP.ArraySource(X, y, w, masks.T.copy(), chunk_rows=300)
        B_src, b0_src, info_src = GS.sweep_glm_streamed_rounds(
            src, None, None, None, regs, alphas, loss="logistic",
            max_iter=25, tol=1e-6, warm_start=False)
        assert info_src["driver"] == "tileplane"
        assert info_src["glm_rounds"] == info_dev["glm_rounds"]
        np.testing.assert_allclose(B_src, B_dev, rtol=5e-3, atol=5e-4)
        np.testing.assert_allclose(b0_src, b0_dev, rtol=5e-3, atol=5e-4)

    def test_source_warm_start_and_retirement(self):
        X, y, w, masks = self._problem(seed=13)
        regs = np.asarray([0.01, 0.1, 0.5], np.float32)
        alphas = np.zeros(3, np.float32)
        src = TP.ArraySource(X, y, w, masks.T.copy())
        B, b0, info = GS.sweep_glm_streamed_rounds(
            src, None, None, None, regs, alphas, loss="logistic",
            max_iter=30, tol=1e-6, warm_start=True)
        assert info["warm_start"]
        assert info["lanes_retired"] == info["lanes_total"]
        B_dev, _, _ = GS.sweep_glm_streamed_rounds(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(masks), regs, alphas, loss="logistic",
            max_iter=30, tol=1e-6, warm_start=True)
        np.testing.assert_allclose(B, B_dev, rtol=5e-3, atol=7e-4)

    def test_source_kill_switch_sync_parity(self, monkeypatch):
        """TMOG_TILEPLANE=0 must shed the producer thread for the GLM
        source sweep too: run_tileplane degrades to its synchronous
        loop, results unchanged."""
        monkeypatch.setenv("TMOG_TILEPLANE", "0")
        monkeypatch.setattr(
            "transmogrifai_tpu.parallel.tileplane.tile_rows_for",
            lambda *a, **k: 400)
        X, y, w, masks = self._problem(seed=47)
        regs = np.asarray([0.05], np.float32)
        alphas = np.zeros(1, np.float32)
        src = TP.ArraySource(X, y, w, masks.T.copy(), chunk_rows=300)
        B_sync, b0_sync, info = GS.sweep_glm_streamed_rounds(
            src, None, None, None, regs, alphas, loss="logistic",
            max_iter=15, tol=1e-6, warm_start=False)
        monkeypatch.setenv("TMOG_TILEPLANE", "1")
        B_tp, b0_tp, _ = GS.sweep_glm_streamed_rounds(
            src, None, None, None, regs, alphas, loss="logistic",
            max_iter=15, tol=1e-6, warm_start=False)
        np.testing.assert_allclose(B_sync, B_tp, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(b0_sync, b0_tp, rtol=1e-6, atol=1e-7)

    def test_source_round_single_executable(self, monkeypatch):
        monkeypatch.setattr(
            "transmogrifai_tpu.parallel.tileplane.tile_rows_for",
            lambda *a, **k: 397)
        X, y, w, masks = self._problem(n=1200, seed=17)
        src = TP.ArraySource(X, y, w, masks.T.copy(), chunk_rows=397)
        regs = np.asarray([0.05], np.float32)
        alphas = np.zeros(1, np.float32)
        before_step = GS._source_round_step._cache_size()
        GS.sweep_glm_streamed_rounds(src, None, None, None, regs, alphas,
                                     loss="logistic", max_iter=10,
                                     tol=1e-6, warm_start=False)
        grew = GS._source_round_step._cache_size() - before_step
        assert grew <= 1  # ONE executable for every tile of every round

    def test_source_rejects_mesh_and_stray_args(self):
        src = TP.ArraySource(np.ones((8, 2), np.float32),
                             np.ones(8, np.float32),
                             np.ones(8, np.float32),
                             np.ones((8, 2), np.float32))
        with pytest.raises(ValueError, match="ride the source"):
            GS.sweep_glm_streamed_rounds(
                src, np.ones(8), None, None,
                np.asarray([0.1], np.float32),
                np.zeros(1, np.float32), loss="logistic")


class TestTreesConsumer:
    def _X(self, n=4000, d=4, seed=19):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, d)).astype(np.float32)
        X[:, 1] *= 40.0
        X[rng.uniform(size=X.shape) < 0.05] = np.nan
        return X

    def test_stream_bin_matrix_exact_parity(self):
        X = self._X()
        edges = np.asarray(T.quantile_edges(jnp.asarray(X), 16))
        resident = np.asarray(T.bin_matrix(jnp.asarray(X),
                                           jnp.asarray(edges)))
        streamed = T.stream_bin_matrix(
            TP.ArraySource(X, chunk_rows=600), edges, tile_rows=640)
        assert streamed.dtype == resident.dtype
        np.testing.assert_array_equal(streamed, resident)

    def test_stream_bin_matrix_kill_switch(self, monkeypatch):
        monkeypatch.setenv("TMOG_TILEPLANE", "0")
        X = self._X(n=900, seed=23)
        edges = np.asarray(T.quantile_edges(jnp.asarray(X), 8))
        resident = np.asarray(T.bin_matrix(jnp.asarray(X),
                                           jnp.asarray(edges)))
        streamed = T.stream_bin_matrix(TP.ArraySource(X, chunk_rows=200),
                                       edges, tile_rows=256)
        np.testing.assert_array_equal(streamed, resident)

    def test_stream_quantile_edges_quality(self):
        X = self._X(n=6000, d=3, seed=29)
        X[:, 2] = 5.0  # constant column
        src = TP.ArraySource(X, chunk_rows=700)
        edges = T.stream_quantile_edges(src, 16, hist_bins=512)
        assert edges.shape == (3, 15)
        for j in range(2):
            col = X[:, j]
            fin = np.isfinite(col)
            true_q = np.quantile(col[fin], np.arange(1, 16) / 16)
            bw = (col[fin].max() - col[fin].min()) / 512
            assert np.abs(edges[j] - true_q).max() < 3 * bw
            assert np.all(np.diff(edges[j]) >= 0)
        assert np.all(edges[2] == 5.0)

    def test_stream_quantile_edges_all_nan_column(self):
        X = self._X(n=800, d=2, seed=31)
        X[:, 1] = np.nan
        edges = T.stream_quantile_edges(TP.ArraySource(X, chunk_rows=200),
                                        8, hist_bins=64)
        assert np.all(np.isnan(edges[1]))
        # all-NaN edges bin every present value to 1 — same as resident
        binned = T.stream_bin_matrix(TP.ArraySource(X, chunk_rows=200),
                                     edges)
        assert set(np.unique(binned[:, 1])) <= {0}

    def test_bin_tile_single_executable(self):
        X = self._X(n=2000, d=3, seed=37)
        edges = np.asarray(T.quantile_edges(jnp.asarray(X), 8))
        before = T._bin_tile_jit._cache_size()
        T.stream_bin_matrix(TP.ArraySource(X, chunk_rows=333), edges,
                            tile_rows=512)
        assert T._bin_tile_jit._cache_size() - before <= 1


class TestScoringConsumer:
    def _model(self):
        from transmogrifai_tpu import FeatureBuilder
        from transmogrifai_tpu.automl import (
            BinaryClassificationModelSelector)
        from transmogrifai_tpu.automl.transmogrifier import transmogrify
        from transmogrifai_tpu.models.glm import OpLogisticRegression
        from transmogrifai_tpu.readers.readers import ListReader
        from transmogrifai_tpu.stages.params import param_grid
        from transmogrifai_tpu.workflow import Workflow

        rng = np.random.default_rng(41)
        rows = [{"a": float(rng.normal()), "b": float(rng.normal()),
                 "label": 0.0} for _ in range(250)]
        for r in rows:
            r["label"] = float(r["a"] + 0.5 * r["b"] > 0)
        fa = FeatureBuilder.Real("a").extract(
            lambda r: r.get("a")).as_predictor()
        fb = FeatureBuilder.Real("b").extract(
            lambda r: r.get("b")).as_predictor()
        fy = FeatureBuilder.RealNN("label").extract(
            lambda r: r.get("label")).as_response()
        vec = transmogrify([fa, fb])
        pred = BinaryClassificationModelSelector \
            .with_train_validation_split(models_and_parameters=[
                (OpLogisticRegression(), param_grid(reg_param=[0.01]))]) \
            .set_input(fy, vec).get_output()
        model = Workflow().set_reader(ListReader(rows)) \
            .set_result_features(pred).train()
        return model, rows

    def test_tileplane_scores_match_per_record(self):
        from transmogrifai_tpu.readers import (ListStreamingReader,
                                               score_stream)
        model, rows = self._model()
        unlabeled = [{"a": r["a"], "b": r["b"]} for r in rows[:53]]
        tiled = [s for b in score_stream(
            model, ListStreamingReader(unlabeled, batch_size=9),
            tile_rows=16) for s in b]
        fn = model.score_function()
        legacy = [fn(r) for r in unlabeled]
        assert len(tiled) == len(legacy) == 53
        for got, want in zip(tiled, legacy):
            g = list(got.values())[0]
            w = list(want.values())[0]
            assert g["prediction"] == w["prediction"]
            assert g["probability_1"] == pytest.approx(
                w["probability_1"], abs=1e-5)

    def test_kill_switch_restores_per_record_batches(self, monkeypatch):
        from transmogrifai_tpu.readers import (ListStreamingReader,
                                               score_stream)
        monkeypatch.setenv("TMOG_TILEPLANE", "0")
        model, rows = self._model()
        unlabeled = [{"a": r["a"], "b": r["b"]} for r in rows[:20]]
        batches = list(score_stream(
            model, ListStreamingReader(unlabeled, batch_size=7)))
        # legacy semantics: one list per READER batch
        assert [len(b) for b in batches] == [7, 7, 6]

    def test_scoring_zero_recompiles_after_warm_pass(self, traced):
        """RecompileTracker pin for the scoring consumer: fixed record
        tiles mean the workflow's stage programs compile on the first
        tile only — a whole second streamed pass books 0 compiles."""
        from transmogrifai_tpu.readers import (ListStreamingReader,
                                               score_stream)
        model, rows = self._model()
        unlabeled = [{"a": r["a"], "b": r["b"]} for r in rows[:48]]

        def run():
            return list(score_stream(
                model, ListStreamingReader(unlabeled, batch_size=12),
                tile_rows=16))

        run()  # warm: the fixed tile shape compiles here
        n_before = len(collector.trace.spans)
        with collector.trace_span("pinned", kind="span") as sp:
            run()
        fresh = collector.trace.spans[n_before:]
        assert sum(int(s.attrs.get("compiles", 0))
                   for s in fresh + [sp]) == 0

    def test_scoring_tile_spans(self, traced):
        from transmogrifai_tpu.readers import (ListStreamingReader,
                                               score_stream)
        model, rows = self._model()
        unlabeled = [{"a": r["a"], "b": r["b"]} for r in rows[:40]]
        list(score_stream(model, ListStreamingReader(unlabeled,
                                                     batch_size=10),
                          tile_rows=16))
        names = [s.name for s in collector.trace.spans]
        assert names.count("tile_copy") == 3
        assert names.count("tile_compute") == 3


class TestReaderStability:
    def test_midwrite_file_deferred_until_stable(self, tmp_path):
        from transmogrifai_tpu.readers import CSVStreamingReader
        (tmp_path / "done.csv").write_text("x\n1\n2\n")
        partial = tmp_path / "partial.csv"
        partial.write_text("x\n3\n")
        r = CSVStreamingReader(str(tmp_path / "*.csv"))
        # simulate an active writer: partial.csv grows between stats
        sizes = {str(partial): iter([10, 14, 18, 22])}
        real_size = type(r)._size

        def fake_size(self, p):
            it = sizes.get(p)
            return next(it) if it is not None else real_size(self, p)

        r._size = fake_size.__get__(r)
        first = r.poll()
        assert len(first) == 1 and first[0][0]["x"] == 1  # done.csv only
        assert str(partial) in r._pending
        # writer finished: size stable across the next poll
        del sizes[str(partial)]
        partial.write_text("x\n3\n4\n")
        r._pending[str(partial)] = os.path.getsize(str(partial))
        again = r.poll()
        assert len(again) == 1 and [row["x"] for row in again[0]] == [3, 4]
        assert r.poll() == []

    def test_stable_files_yield_first_poll(self, tmp_path):
        from transmogrifai_tpu.readers import CSVStreamingReader
        for i in range(2):
            (tmp_path / f"f{i}.csv").write_text("x\n1\n")
        r = CSVStreamingReader(str(tmp_path / "*.csv"))
        assert len(r.poll()) == 2

    def test_vanished_file_skipped(self, tmp_path):
        from transmogrifai_tpu.readers import CSVStreamingReader
        (tmp_path / "a.csv").write_text("x\n1\n")
        r = CSVStreamingReader(str(tmp_path / "*.csv"))
        r._size = (lambda self, p: -1).__get__(r)
        assert r.poll() == []


class TestAvroEndToEnd:
    """A fit on data served from disk, X never materialized as one
    array: the substrate claim of the whole data plane."""

    def _write_avro(self, path, n=1800, d=4, F=2, seed=43):
        from transmogrifai_tpu.readers.avro import write_avro_file
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, d)).astype(np.float32)
        beta = rng.normal(size=d)
        y = (X @ beta > 0).astype(np.float32)
        schema = {"type": "record", "name": "Row", "fields": (
            [{"name": f"x{j}", "type": "float"} for j in range(d)]
            + [{"name": "y", "type": "float"},
               {"name": "id", "type": "long"}])}
        recs = [{**{f"x{j}": float(X[i, j]) for j in range(d)},
                 "y": float(y[i]), "id": i} for i in range(n)]
        write_avro_file(str(path), schema, recs)
        return X, y

    def _sources(self, path, d, F):
        from transmogrifai_tpu.readers.avro import read_avro_file

        def stats_row(r):
            return ([r[f"x{j}"] for j in range(d)], r["y"], 1.0)

        def glm_row(r):
            m = [1.0] * F
            m[r["id"] % F] = 0.0
            return ([r[f"x{j}"] for j in range(d)], r["y"], 1.0, m)

        mk = lambda fn: TP.reader_row_source(  # noqa: E731
            lambda: read_avro_file(str(path)), fn, batch_records=256)
        return mk(stats_row), mk(glm_row)

    def test_avro_fit_never_materializes_x(self, tmp_path):
        d, F = 4, 2
        X, y = self._write_avro(tmp_path / "rows.avro", d=d, F=F)
        stats_src, glm_src = self._sources(tmp_path / "rows.avro", d, F)

        res = SE.run_stats(stats_src, corr_matrix=True, tile_rows=256)
        fused = SE.run_stats(X, y, corr_matrix=True)
        np.testing.assert_allclose(res.mean, fused.mean, rtol=2e-4,
                                   atol=2e-5)
        np.testing.assert_allclose(res.corr_matrix, fused.corr_matrix,
                                   rtol=2e-3, atol=2e-4)
        ps = SE._last_stream_stats
        # peak tileplane host buffer <= 2 tiles (+ the merged state,
        # which is [d]/[d,d]-shaped — not row-proportional)
        assert ps.peak_host_rows <= 2 * ps.tile_rows
        assert ps.rows == X.shape[0]

        mask = np.stack([(np.arange(X.shape[0]) % F != k)
                         .astype(np.float32) for k in range(F)])
        regs = np.asarray([0.05, 0.2], np.float32)
        alphas = np.zeros(2, np.float32)
        B_src, b0_src, info = GS.sweep_glm_streamed_rounds(
            glm_src, None, None, None, regs, alphas, loss="logistic",
            max_iter=20, tol=1e-6, warm_start=False)
        B_dev, b0_dev, _ = GS.sweep_glm_streamed_rounds(
            jnp.asarray(X), jnp.asarray(y),
            jnp.ones(X.shape[0], jnp.float32), jnp.asarray(mask),
            regs, alphas, loss="logistic", max_iter=20, tol=1e-6,
            warm_start=False)
        assert info["driver"] == "tileplane"
        np.testing.assert_allclose(B_src, B_dev, rtol=5e-3, atol=7e-4)
        np.testing.assert_allclose(b0_src, b0_dev, rtol=5e-3, atol=7e-4)
