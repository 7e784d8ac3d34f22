"""Parallel sharded ingest (parallel/ingest.py + the columnar readers).

Covers the columnar decode parity pins (csv_columnar_chunks /
read_avro_columns == the per-record readers, cell for cell), the
ShardedSource reassembly contract (serial == parallel chunk stream,
bit for bit, at any worker count; worker crash => failed pass, never a
hang; single-shard / workers=1 degradation), the depth-N prefetch ring
(bit-identical results at any depth, the env knob), the
end-to-end bit-identity matrix (stats Summary / GLM fit / tree binning
across workers {1,2,4} x prefetch {1,3}), the ingest_pass/tile_parse
telemetry, and the FileStreamingReader shard-order determinism the
worker assignment builds on (equal mtimes -> lexicographic; one stat
pair per candidate per scan; snapshot_paths does not consume).
"""
import glob
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.ops import glm_sweep as GS
from transmogrifai_tpu.ops import stats_engine as SE
from transmogrifai_tpu.ops import trees as T
from transmogrifai_tpu.parallel import ingest as ING
from transmogrifai_tpu.parallel import tileplane as TP
from transmogrifai_tpu.readers.avro import (AvroDecodeError,
                                            read_avro_columns,
                                            read_avro_file,
                                            write_avro_file)
from transmogrifai_tpu.readers.readers import (CSVReader, columnar_f32,
                                               csv_columnar_chunks)
from transmogrifai_tpu.readers.streaming import FileStreamingReader
from transmogrifai_tpu.utils.metrics import collector


@pytest.fixture(autouse=True)
def _clean_knobs(monkeypatch):
    """Isolate every test from ambient ingest knobs."""
    monkeypatch.delenv("TMOG_INGEST_WORKERS", raising=False)
    monkeypatch.delenv("TMOG_TILE_PREFETCH", raising=False)


@pytest.fixture
def traced():
    collector.enable("test_ingest")
    try:
        yield collector
    finally:
        collector.finish()
        collector.disable()


def _write_csv_shards(dirpath, n_shards=3, rows=(400, 257, 311), d=4,
                      seed=0):
    """Uneven CSV shards with x0..x{d-1}, y, w, fold columns + some
    string nulls, deterministic content."""
    rng = np.random.default_rng(seed)
    paths = []
    os.makedirs(dirpath, exist_ok=True)
    for s in range(n_shards):
        p = os.path.join(str(dirpath), f"part-{s:03d}.csv")
        with open(p, "w") as fh:
            fh.write(",".join([f"x{j}" for j in range(d)]
                              + ["y", "w", "fold"]) + "\n")
            for i in range(rows[s % len(rows)]):
                cells = [f"{rng.normal():.6f}" for _ in range(d)]
                if i % 37 == 0:
                    cells[1] = "NA"  # string null -> NaN, vectorized
                fh.write(",".join(
                    cells + [str(int(rng.integers(0, 2))), "1.0",
                             str(i % 2)]) + "\n")
        paths.append(p)
    return paths


# -- columnar decode parity --------------------------------------------------

class TestColumnarReaders:
    def test_csv_columnar_matches_per_record(self, tmp_path):
        [p] = _write_csv_shards(tmp_path, n_shards=1, rows=(403,))
        recs = CSVReader(p).read()
        ref = {k: columnar_f32([r[k] for r in recs])
               for k in recs[0]}
        chunks = list(csv_columnar_chunks(p, batch_records=100))
        assert len(chunks) == -(-403 // 100)
        for k in ref:
            got = np.concatenate([c[k] for c in chunks])
            assert got.dtype == np.float32
            # NaNs from the "NA" cells must land in the same rows
            np.testing.assert_array_equal(np.isnan(got),
                                          np.isnan(ref[k]))
            m = ~np.isnan(got)
            np.testing.assert_array_equal(got[m], ref[k][m])

    def test_csv_columnar_column_subset_and_width_check(self, tmp_path):
        [p] = _write_csv_shards(tmp_path, n_shards=1, rows=(50,))
        chunks = list(csv_columnar_chunks(p, columns=("y", "w")))
        assert set(chunks[0]) == {"y", "w"}
        with open(p, "a") as fh:
            fh.write("1.0,2.0\n")  # short row
        with pytest.raises(ValueError):
            list(csv_columnar_chunks(p))

    def test_csv_columnar_headerless_fields(self, tmp_path):
        p = tmp_path / "raw.csv"
        p.write_text("1.0,2.0\n3.0,4.0\n")
        chunks = list(csv_columnar_chunks(str(p), fields=("a", "b")))
        np.testing.assert_array_equal(
            np.concatenate([c["a"] for c in chunks]), [1.0, 3.0])

    def test_columnar_f32_dtype_paths(self):
        np.testing.assert_array_equal(
            columnar_f32(np.asarray([1, 2], np.int64)), [1.0, 2.0])
        got = columnar_f32(["1.5", "NA", "", "2.5"])
        assert got.dtype == np.float32
        np.testing.assert_array_equal(np.isnan(got),
                                      [False, True, True, False])
        got = columnar_f32([1.0, None, 3.0])
        np.testing.assert_array_equal(np.isnan(got),
                                      [False, True, False])

    def test_avro_columnar_matches_per_record(self, tmp_path):
        p = str(tmp_path / "rows.avro")
        schema = {"type": "record", "name": "r", "fields": [
            {"name": "x", "type": "double"},
            {"name": "y", "type": ["null", "double"]},
            {"name": "tag", "type": "string"}]}
        recs = [{"x": i / 7.0, "y": None if i % 5 == 0 else float(i),
                 "tag": f"t{i}"} for i in range(300)]
        write_avro_file(p, schema, recs)
        ref = list(read_avro_file(p))
        chunks = list(read_avro_columns(p, batch_records=128))
        assert [len(c["x"]) for c in chunks] == [128, 128, 44]
        flat = {k: [v for c in chunks for v in c[k]] for k in chunks[0]}
        assert flat["x"] == [r["x"] for r in ref]
        assert flat["y"] == [r["y"] for r in ref]
        assert flat["tag"] == [r["tag"] for r in ref]

    def test_avro_columnar_field_subset(self, tmp_path):
        p = str(tmp_path / "rows.avro")
        schema = {"type": "record", "name": "r", "fields": [
            {"name": "x", "type": "double"},
            {"name": "y", "type": "double"}]}
        write_avro_file(p, schema,
                        [{"x": 1.0, "y": 2.0}, {"x": 3.0, "y": 4.0}])
        chunks = list(read_avro_columns(p, fields=("y",)))
        assert set(chunks[0]) == {"y"}
        assert chunks[0]["y"] == [2.0, 4.0]

    def test_avro_columnar_requires_record_schema(self, tmp_path):
        p = str(tmp_path / "prim.avro")
        write_avro_file(p, "double", [1.0, 2.0])
        with pytest.raises(AvroDecodeError):
            list(read_avro_columns(p))


# -- ShardedSource reassembly ------------------------------------------------

def _chunk_factories(n_shards=3, chunk_rows=64, seed=0):
    rng = np.random.default_rng(seed)
    shards = [rng.normal(size=(n, 3)).astype(np.float32)
              for n in (400, 257, 311, 123)[:n_shards]]

    def factory_for(X):
        def factory():
            for s in range(0, X.shape[0], chunk_rows):
                yield (X[s:s + chunk_rows],)
        return factory

    return [factory_for(X) for X in shards], shards


class TestShardedSource:
    @pytest.mark.parametrize("workers", [2, 4])
    def test_parallel_stream_bitwise_equals_serial(self, workers):
        factories, _ = _chunk_factories()
        serial = list(ING.ShardedSource(factories, workers=1).chunks())
        par = list(ING.ShardedSource(factories,
                                     workers=workers).chunks())
        assert len(par) == len(serial)
        for (a,), (b,) in zip(serial, par):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(a, b)

    def test_reiterable_fresh_pass(self):
        factories, _ = _chunk_factories()
        src = ING.ShardedSource(factories, workers=2)
        first = [c[0].sum() for c in src.chunks()]
        second = [c[0].sum() for c in src.chunks()]
        assert first == second

    def test_worker_exception_is_failed_pass_not_hang(self):
        def bad():
            yield (np.ones((4, 2), np.float32),)
            raise RuntimeError("shard decode blew up")

        def good():
            for _ in range(5):
                yield (np.ones((4, 2), np.float32),)

        before = threading.active_count()
        src = ING.ShardedSource([good, bad, good], workers=2)
        with pytest.raises(RuntimeError, match="blew up"):
            list(src.chunks())
        # every pool thread joined on the way out
        assert threading.active_count() == before

    def test_consumer_abandon_unblocks_workers(self):
        def big():
            for _ in range(50):
                yield (np.ones((8, 2), np.float32),)

        before = threading.active_count()
        src = ING.ShardedSource([big, big], workers=2, ahead=1)
        it = src.chunks()
        next(it)
        it.close()  # abandon mid-pass: workers blocked on put must exit
        assert threading.active_count() == before

    def test_single_shard_degrades_to_serial(self):
        factories, _ = _chunk_factories(n_shards=1)
        src = ING.ShardedSource(factories, workers=8)
        assert src.effective_workers() == 1
        assert len(list(src.chunks())) == -(-400 // 64)

    def test_env_knob_and_explicit_workers_precedence(self, monkeypatch):
        factories, _ = _chunk_factories()
        monkeypatch.setenv("TMOG_INGEST_WORKERS", "2")
        assert ING.ShardedSource(factories).effective_workers() == 2
        # an explicit workers= beats the env knob
        assert ING.ShardedSource(
            factories, workers=1).effective_workers() == 1
        monkeypatch.setenv("TMOG_INGEST_WORKERS", "not-a-number")
        assert ING.ShardedSource(factories).effective_workers() == 1

    def test_peek_does_not_spin_up_pool_or_consume(self):
        factories, shards = _chunk_factories()
        src = ING.ShardedSource(factories, workers=4)
        before = threading.active_count()
        first = src.peek()
        assert threading.active_count() == before
        np.testing.assert_array_equal(first[0], shards[0][:64])
        assert len(list(src.chunks())) == sum(
            -(-X.shape[0] // 64) for X in shards)

    def test_ingest_pass_record_and_per_worker_spans(self, traced,
                                                     tmp_path):
        import json
        log = tmp_path / "events.jsonl"
        traced.attach_event_log(str(log))
        try:
            factories, _ = _chunk_factories()
            src = ING.ShardedSource(factories, workers=2, label="t")
            list(src.chunks())
        finally:
            traced.detach_event_log()
        [rec] = traced.current.ingest_metrics
        assert rec.workers == 2 and rec.shards == 3
        assert rec.rows == 400 + 257 + 311
        evs = [json.loads(l) for l in log.read_text().splitlines()]
        [ev] = [e for e in evs if e["event"] == "ingest_pass"]
        assert ev["workers"] == 2 and ev["rows"] == rec.rows
        spans = [s for s in traced.trace.spans
                 if s.name == "tile_parse"]
        assert spans and all(s.kind == "tile" for s in spans)
        assert {s.attrs["worker"] for s in spans} == {0, 1}
        assert {s.attrs["lane"] for s in spans} == {"ingest-w0",
                                                    "ingest-w1"}

    def test_serial_pass_emits_same_telemetry_schema(self, traced):
        factories, _ = _chunk_factories(n_shards=1)
        list(ING.ShardedSource(factories, label="t1").chunks())
        [rec] = traced.current.ingest_metrics
        assert rec.workers == 1
        assert all(s.attrs["lane"] == "ingest-w0"
                   for s in traced.trace.spans
                   if s.name == "tile_parse")


# -- depth-N prefetch ring ---------------------------------------------------

class TestPrefetchRing:
    def test_env_knob_precedence(self, monkeypatch):
        assert TP.tile_prefetch_depth() == 1  # the default
        monkeypatch.setenv("TMOG_TILE_PREFETCH", "3")
        assert TP.tile_prefetch_depth() == 3
        monkeypatch.setenv("TMOG_TILE_PREFETCH", "garbage")
        assert TP.tile_prefetch_depth() == 1

    @pytest.mark.parametrize("depth", [1, 3])
    def test_depth_never_changes_results(self, depth):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(1013, 3)).astype(np.float32)
        src = TP.ArraySource(X, chunk_rows=97)

        @jax.jit
        def step(carry, xt):
            return carry + xt.sum(0)

        carry, stats = TP.run_tileplane(
            src, step, jnp.zeros(3, jnp.float32), tile_rows=128,
            label="ring", prefetch=depth)
        assert stats.prefetch_depth == depth
        ref, _ = TP.run_tileplane(
            src, step, jnp.zeros(3, jnp.float32), tile_rows=128,
            label="ring", prefetch=1)
        np.testing.assert_array_equal(np.asarray(carry),
                                      np.asarray(ref))

    def test_tileplane_pass_event_carries_depth(self, traced,
                                                tmp_path):
        import json
        X = np.ones((500, 2), np.float32)

        @jax.jit
        def step(carry, xt):
            return carry + xt.sum()

        log = tmp_path / "events.jsonl"
        traced.attach_event_log(str(log))
        try:
            TP.run_tileplane(TP.ArraySource(X, chunk_rows=100), step,
                             jnp.zeros((), jnp.float32), tile_rows=128,
                             label="ev", prefetch=2)
        finally:
            traced.detach_event_log()
        evs = [json.loads(l) for l in log.read_text().splitlines()]
        [ev] = [e for e in evs if e["event"] == "tileplane_pass"]
        assert ev["prefetch_depth"] == 2


# -- end-to-end bit-identity matrix ------------------------------------------

class TestEndToEndParity:
    """stats Summary / GLM fit / tree binning, bit for bit, across
    workers {1,2,4} x prefetch {1,3} on a 3-shard CSV input."""

    D = 4

    def _sources(self, dirpath, workers):
        d = self.D

        def stats_cols(c):
            return (np.stack([c[f"x{j}"] for j in range(d)], 1),
                    c["y"], c["w"])

        def glm_cols(c):
            masks = np.stack([(c["fold"] != k).astype(np.float32)
                              for k in range(2)], 1)
            return (np.stack([c[f"x{j}"] for j in range(d)], 1),
                    c["y"], c["w"], masks)

        def tree_cols(c):
            return (np.stack([c[f"x{j}"] for j in range(d)], 1),)

        paths = sorted(glob.glob(os.path.join(str(dirpath), "*.csv")))
        mk = lambda fn: ING.sharded_reader_source(  # noqa: E731
            paths, fn, batch_records=256, workers=workers)
        return mk(stats_cols), mk(glm_cols), mk(tree_cols)

    def _fingerprint(self, dirpath, workers, prefetch, monkeypatch):
        monkeypatch.setenv("TMOG_TILE_PREFETCH", str(prefetch))
        stats_src, glm_src, tree_src = self._sources(dirpath, workers)
        res = SE.run_stats(stats_src, tile_rows=256)
        regs = np.asarray([0.05, 0.2], np.float32)
        alphas = np.asarray([0.0, 0.5], np.float32)
        B, b0, info = GS.sweep_glm_streamed_rounds(
            glm_src, None, None, None, regs, alphas, loss="logistic",
            max_iter=8, tol=1e-6, warm_start=False)
        assert info["driver"] == "tileplane"
        edges = T.stream_quantile_edges(tree_src, 8, hist_bins=128)
        binned = T.stream_bin_matrix(tree_src, edges, tile_rows=256)
        return (np.asarray(res.mean), np.asarray(res.m2),
                np.asarray(B), np.asarray(b0), np.asarray(edges),
                np.asarray(binned))

    def test_bit_identical_across_workers_and_prefetch(self, tmp_path,
                                                       monkeypatch):
        _write_csv_shards(tmp_path / "shards", d=self.D)
        ref = self._fingerprint(tmp_path / "shards", 1, 1, monkeypatch)
        for workers, prefetch in [(2, 1), (2, 3), (4, 1), (4, 3),
                                  (1, 3)]:
            got = self._fingerprint(tmp_path / "shards", workers,
                                    prefetch, monkeypatch)
            for a, b in zip(ref, got):
                np.testing.assert_array_equal(
                    a, b, err_msg=f"workers={workers} "
                                  f"prefetch={prefetch}")


# -- shard-order determinism (FileStreamingReader) ---------------------------

class TestShardOrderDeterminism:
    def _mk(self, dirpath, names, mtime=1_700_000_000):
        paths = []
        for n in names:
            p = os.path.join(str(dirpath), n)
            with open(p, "w") as fh:
                fh.write("c\n1\n")
            os.utime(p, (mtime, mtime))
            paths.append(p)
        return paths

    def test_equal_mtimes_sort_lexicographic(self, tmp_path):
        # created in shuffled order, identical mtimes
        self._mk(tmp_path, ["part-002.csv", "part-000.csv",
                            "part-001.csv"])
        r = FileStreamingReader(str(tmp_path / "*.csv"),
                                lambda p: CSVReader(p))
        got = [os.path.basename(p) for p in r.snapshot_paths()]
        assert got == ["part-000.csv", "part-001.csv", "part-002.csv"]

    def test_mtime_order_beats_name_order(self, tmp_path):
        self._mk(tmp_path, ["part-000.csv"], mtime=1_700_000_100)
        self._mk(tmp_path, ["part-001.csv"], mtime=1_700_000_000)
        r = FileStreamingReader(str(tmp_path / "*.csv"),
                                lambda p: CSVReader(p))
        got = [os.path.basename(p) for p in r.snapshot_paths()]
        assert got == ["part-001.csv", "part-000.csv"]

    def test_snapshot_paths_does_not_consume(self, tmp_path):
        self._mk(tmp_path, ["a.csv", "b.csv"])
        r = FileStreamingReader(str(tmp_path / "*.csv"),
                                lambda p: CSVReader(p))
        assert r.snapshot_paths() == r.snapshot_paths()
        assert len(r.poll()) == 2  # stream still yields everything

    def test_one_stat_pair_per_candidate_per_scan(self, tmp_path,
                                                  monkeypatch):
        self._mk(tmp_path, ["a.csv", "b.csv", "c.csv"])
        r = FileStreamingReader(str(tmp_path / "*.csv"),
                                lambda p: CSVReader(p))
        calls = []
        real = os.stat

        def counting_stat(p, *a, **k):
            if str(p).endswith(".csv"):
                calls.append(str(p))
            return real(p, *a, **k)

        monkeypatch.setattr(
            "transmogrifai_tpu.readers.streaming.os.stat",
            counting_stat)
        paths = r.snapshot_paths()
        assert len(paths) == 3
        # exactly the s1/s2 stability pair per candidate: mtime ordering
        # reads the cached stat, never a third os.stat
        assert sorted(calls) == sorted(paths * 2)
