"""Native C++ host kernels vs pure-Python reference implementations.

Parity gates: murmur3 test vectors, batch hashing == python hashing,
fused tokenize+hash == tokenize_text + hash_tokens_to_counts, CSV scan ==
python csv module. Skipped only if the baked-in g++ somehow fails.
"""
import csv as pycsv
import io

import numpy as np
import pytest

from transmogrifai_tpu.ops import native_bridge as NB
from transmogrifai_tpu.ops.hashing import (
    hash_string, hash_tokens_to_counts, murmur3_32)

pytestmark = pytest.mark.skipif(not NB.available(),
                                reason="native library unavailable")


class TestMurmur:
    def test_reference_vectors(self):
        # canonical MurmurHash3_x86_32 test vectors
        assert NB.native_murmur3(b"", 0) == 0
        assert NB.native_murmur3(b"", 1) == 0x514E28B7
        assert NB.native_murmur3(b"abc", 0) == 0xB3DD93FA
        assert NB.native_murmur3(b"Hello, world!", 1234) == 0xFAF6CDB3

    def test_matches_python(self):
        rng = np.random.default_rng(0)
        for _ in range(50):
            n = int(rng.integers(0, 40))
            data = bytes(rng.integers(0, 256, size=n, dtype=np.uint8))
            seed = int(rng.integers(0, 2**31))
            assert NB.native_murmur3(data, seed) == murmur3_32(data, seed)


class TestBatchHashing:
    def test_hash_strings_matches(self):
        strings = ["hello", "world", "", "héllo ünïcode", "a" * 100]
        out = NB.native_hash_strings(strings, seed=7)
        for s, h in zip(strings, out):
            assert int(h) == murmur3_32(s.encode("utf-8"), 7)

    def test_hash_tokens_matches_python_fallback(self):
        token_lists = [["the", "cat"], None, [], ["cat", "cat", "dog"]]
        import os
        native = NB.native_hash_tokens(token_lists, 32, seed=3)
        # pure python path
        py = np.zeros((4, 32))
        for i, toks in enumerate(token_lists):
            for t in (toks or []):
                py[i, hash_string(t, 32, 3)] += 1
        np.testing.assert_array_equal(native, py)

    def test_fused_tokenizer_matches_python_pipeline(self):
        # contract: the byte-level C tokenizer equals the unicode python
        # analyzer on ASCII documents (the only inputs it is routed)
        from transmogrifai_tpu.transformers.text import tokenize_text
        docs = ["The CAT sat on the mat!", None, "", "123's it's-fine",
                "a,b;c  d\te", "under_score splits"]
        fused = NB.native_tokenize_hash_counts(docs, 64, seed=1, min_len=1)
        py = np.zeros((len(docs), 64))
        for i, d in enumerate(docs):
            for t in tokenize_text(d, 1, True, False):
                py[i, hash_string(t, 64, 1)] += 1
        np.testing.assert_array_equal(fused, py)

    def test_non_ascii_docs_route_to_unicode_python_path(self):
        from transmogrifai_tpu.automl.vectorizers.text import (
            tokenize, tokenize_hash_counts)
        docs = ["naïve café crème", "北京 大学", None]
        out = tokenize_hash_counts(docs, 32, seed=2)
        py = np.zeros((len(docs), 32))
        for i, d in enumerate(docs):
            for t in tokenize(d):
                py[i, hash_string(t, 32, 2)] += 1
        np.testing.assert_array_equal(out, py)
        assert out[1].sum() == 2.0  # unicode tokens kept, not dropped


class TestCSV:
    def test_csv_scan_matches_csv_module(self):
        text = ('a,b,c\n1,"two, with comma",3\r\n'
                '"quoted ""inner"" text",5,\n,,\n')
        native = NB.native_csv_parse(text.encode("utf-8"))
        expected = list(pycsv.reader(io.StringIO(text)))
        assert native == expected

    def test_csv_non_ascii_utf8(self):
        # regression: field bounds are BYTE offsets; multi-byte characters
        # must not shift later fields (José is 5 bytes / 4 chars)
        text = ('name,city,score\nJosé,Köln,1.5\n"Fran ""çois""",東京,2\n'
                'plain,row,3\n')
        native = NB.native_csv_parse(text.encode("utf-8"))
        expected = list(pycsv.reader(io.StringIO(text)))
        assert native == expected

    def test_parse_floats(self):
        data = b"1.5,-2e3, ,abc,42"
        bounds = np.array([0, 3, 4, 8, 9, 10, 11, 14, 15, 17], np.int64)
        out = NB.native_parse_floats(data, bounds)
        assert out[0] == 1.5 and out[1] == -2000.0 and out[4] == 42.0
        assert np.isnan(out[2]) and np.isnan(out[3])


class TestIntegration:
    def test_hashing_vectorizer_uses_native(self):
        # hash_tokens_to_counts routes through native when available and
        # must equal the pure python result
        token_lists = [["x", "y"], ["x"], None]
        out = hash_tokens_to_counts(token_lists, 16, seed=0)
        py = np.zeros((3, 16))
        for i, toks in enumerate(token_lists):
            for t in (toks or []):
                py[i, hash_string(t, 16, 0)] += 1
        np.testing.assert_array_equal(out, py)


def test_native_dict_encode_matches_numpy_unique():
    from transmogrifai_tpu.ops.native_bridge import native_dict_encode
    import numpy as np
    rng = np.random.default_rng(3)
    strs = [f"v{int(i)}" for i in rng.integers(0, 37, size=5000)]
    out = native_dict_encode(strs)
    if out is None:
        import pytest
        pytest.skip("native library unavailable")
    codes, uniques = out
    # exact decode round-trip
    assert [uniques[c] for c in codes] == strs
    # same unique SET as np.unique (order differs by design)
    arr = np.empty(len(strs), object); arr[:] = strs
    assert set(uniques) == set(np.unique(arr))
    # unicode + empties + collisions in one table
    c, u = native_dict_encode(["", "ü", "", "a" * 300, "ü"])
    assert list(c) == [0, 1, 0, 2, 1] and u == ["", "ü", "a" * 300]


def test_factorize_native_and_fallback_agree(monkeypatch):
    import numpy as np
    from transmogrifai_tpu.automl.vectorizers import encoding as E
    data = ["b", None, "a", "b", 7, None, "a"]
    u1, inv1, nm1 = E.factorize(data)
    # force the numpy fallback
    import transmogrifai_tpu.ops.native_bridge as NB
    monkeypatch.setattr(NB, "native_dict_encode", lambda s: None)
    u2, inv2, nm2 = E.factorize(data)
    # decode both: identical value streams regardless of unique order
    assert [u1[i] for i in inv1] == [u2[i] for i in inv2]
    np.testing.assert_array_equal(nm1, nm2)


class TestBuildStaleness:
    """native/build.py trusts a binary by the digest of its sources and
    compile command, never by mtime (a copied tree scrambles mtimes)."""

    def test_rebuilds_when_source_content_changes_under_same_mtime(
            self, tmp_path):
        import ctypes
        import importlib
        import os

        # (the package re-exports the build() function under this name)
        B = importlib.import_module("transmogrifai_tpu.native.build")

        src = tmp_path / "k.cpp"
        lib = str(tmp_path / "k.so")
        src.write_text('extern "C" int answer() { return 1; }\n')
        mtime = os.stat(src).st_mtime_ns
        assert B._build(lib, [str(src)], B._FLAGS, False) == lib
        lib_mtime = os.stat(lib).st_mtime_ns
        # unchanged content: trusted even though the binary looks OLDER
        # than its source
        os.utime(lib, ns=(0, 0))
        assert B._build(lib, [str(src)], B._FLAGS, False) == lib
        assert os.stat(lib).st_mtime_ns == 0
        # changed content, mtime restored to the original: must rebuild
        src.write_text('extern "C" int answer() { return 2; }\n')
        os.utime(src, ns=(mtime, mtime))
        os.utime(lib, ns=(lib_mtime + 10 ** 12, lib_mtime + 10 ** 12))
        assert B._build(lib, [str(src)], B._FLAGS, False) == lib
        assert ctypes.CDLL(lib).answer() == 2
        # a binary without its stamp is not trusted either
        os.unlink(lib + ".sha256")
        before = os.stat(lib).st_mtime_ns
        assert B._build(lib, [str(src)], B._FLAGS, False) == lib
        assert os.stat(lib).st_mtime_ns != before
