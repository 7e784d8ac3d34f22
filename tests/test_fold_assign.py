"""Fold assignment is ONE device program (automl/tuning/folds.py): exact
k-fold / single-split partitions from a seed, the same on every backend
and for host or device labels, and `validate()` hands its result to the
sweep routes without a host copy."""
import hashlib
import json
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.automl import CrossValidation, TrainValidationSplit
from transmogrifai_tpu.automl.tuning import folds as F
from transmogrifai_tpu.automl.tuning import validators as V
from transmogrifai_tpu.automl.tuning.checkpoint import (
    SweepCheckpoint, data_fingerprint)
from transmogrifai_tpu.evaluators.evaluators import Evaluators
from transmogrifai_tpu.models.glm import OpLogisticRegression, OpNaiveBayes
from transmogrifai_tpu.stages.params import param_grid
from transmogrifai_tpu.utils.metrics import collector

EV = Evaluators.BinaryClassification.au_roc


def fold_ids(masks):
    """Per-row held-out fold of a k-fold mask, after checking that every
    row is held out exactly once."""
    held = 1.0 - np.asarray(masks)
    assert set(np.unique(held)) <= {0.0, 1.0}
    assert (held.sum(axis=0) == 1.0).all()
    return held.argmax(axis=0)


def labels(n, k, seed=0):
    """k classes of unequal sizes, shuffled."""
    rng = np.random.default_rng(seed)
    p = np.arange(1, k + 1, dtype=np.float64)
    return rng.choice(k, size=n, p=p / p.sum()).astype(np.float32)


# -- k-fold -------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 42, 2 ** 31 + 7])
@pytest.mark.parametrize("n,folds", [(100, 4), (103, 4), (1000, 5),
                                     (1001, 5), (7, 3), (64, 2)])
def test_kfold_is_an_exact_balanced_partition(n, folds, seed):
    cv = CrossValidation(EV(), num_folds=folds, seed=seed)
    masks = cv.fold_masks(np.zeros(n))
    assert isinstance(masks, np.ndarray)
    assert masks.shape == (folds, n) and masks.dtype == np.float32
    sizes = np.bincount(fold_ids(masks), minlength=folds)
    assert sizes.sum() == n and sizes.max() - sizes.min() <= 1
    # deterministic in the seed, and in nothing else
    again = CrossValidation(EV(), num_folds=folds, seed=seed)
    assert np.array_equal(again.fold_masks(np.ones(n)), masks)
    other = CrossValidation(EV(), num_folds=folds, seed=seed + 1)
    assert not np.array_equal(other.fold_masks(np.zeros(n)), masks)


@pytest.mark.parametrize("k", [2, 3])
@pytest.mark.parametrize("n,folds", [(120, 3), (1001, 5)])
def test_stratified_kfold_balances_every_class(n, folds, k):
    y = labels(n, k, seed=n + k)
    cv = CrossValidation(EV(), num_folds=folds, seed=9, stratify=True)
    masks = cv.fold_masks(y)
    ids = fold_ids(masks)
    for c in range(k):
        per_fold = np.bincount(ids[y == c], minlength=folds)
        assert per_fold.sum() == (y == c).sum()
        assert per_fold.max() - per_fold.min() <= 1, (c, per_fold)
    # the labels decide: other labels, other folds
    assert not np.array_equal(cv.fold_masks(labels(n, k, seed=1)), masks)


def test_fold_of_a_row_is_uniform_over_seeds():
    hits = np.zeros(5)
    for seed in range(400):
        m = CrossValidation(EV(), num_folds=5, seed=seed).fold_masks(
            np.zeros(53))
        hits[fold_ids(m)[0]] += 1
    # 400 draws of a fair 5-sided die: each side 80 +- 4 sd of 8
    assert hits.min() > 48 and hits.max() < 112, hits


# -- single split -------------------------------------------------------------

@pytest.mark.parametrize("ratio", [0.75, 0.8, 0.9, 0.5, 0.7])
@pytest.mark.parametrize("n", [100, 103, 1001])
def test_split_holds_out_the_rounded_share(n, ratio):
    tvs = TrainValidationSplit(EV(), train_ratio=ratio, seed=3)
    masks = tvs.fold_masks(np.zeros(n))
    assert masks.shape == (1, n) and masks.dtype == np.float32
    assert int((masks[0] == 0).sum()) == int(round(n * (1.0 - ratio)))
    assert np.array_equal(
        TrainValidationSplit(EV(), train_ratio=ratio, seed=3)
        .fold_masks(np.zeros(n)), masks)
    assert not np.array_equal(
        TrainValidationSplit(EV(), train_ratio=ratio, seed=4)
        .fold_masks(np.zeros(n)), masks)


@pytest.mark.parametrize("ratio", [0.75, 0.9, 0.7])
@pytest.mark.parametrize("k", [2, 3])
def test_stratified_split_holds_out_the_rounded_share_of_each_class(k, ratio):
    y = labels(1003, k, seed=k)
    tvs = TrainValidationSplit(EV(), train_ratio=ratio, seed=3,
                               stratify=True)
    held = tvs.fold_masks(y)[0] == 0
    for c in range(k):
        n_c = int((y == c).sum())
        assert int((held & (y == c)).sum()) == \
            int(round(n_c * (1.0 - ratio))), c


@pytest.mark.parametrize("fraction", [
    0.25, 0.5, 0.375, 0.1, 1.0 - 0.9, 1.0 - 0.8, 1.0 - 0.7, 1 / 3, 0.999999,
    1e-9, 1e-30, 5e-324])
def test_round_count_share_is_pythons_round(fraction):
    """Class counts times the held-out share, rounded as Python rounds the
    float64 product (halves to even), at counts float32 cannot hold."""
    rnd = random.Random(fraction.hex())
    counts = [0, 1, 2, 3, 5, 15, 25, 35, 2 ** 31 - 1, 2 ** 30, 25_000_000,
              12_500_001] + list(range(200))
    counts += [rnd.randint(0, 2 ** 31 - 1) for _ in range(500)]
    for m in range(0, 600, 7):      # the counts next to every half
        c = int((m + 0.5) / fraction) if fraction > 1e-8 else 0
        counts += [c + d for d in (-1, 0, 1) if 0 <= c + d < 2 ** 31]
    want = [round(c * fraction) for c in counts]
    got = jax.jit(lambda c: F._round_count_share(c, fraction))(
        jnp.asarray(np.array(counts, np.int64).astype(np.int32)))
    assert np.asarray(got).tolist() == want


# -- one function of the seed, everywhere -------------------------------------

def test_key_is_the_named_threefry_key():
    for seed in (0, 42, 2 ** 32 - 1):
        assert np.array_equal(
            F.fold_key(seed),
            jax.random.key_data(jax.random.key(seed, impl="threefry2x32")))
    # and past what jax.random.key takes without x64
    assert F.fold_key(2 ** 32 + 5).tolist() == [1, 5]


def _sha(masks):
    return hashlib.sha256(
        fold_ids(masks).astype(np.int32).tobytes()).hexdigest()


GOLDEN_PLAIN = \
    "0b1d46564963970989c0a45160e24913050b2909579d1da5e69a911953453faf"
GOLDEN_STRATIFIED = \
    "e1ea8b98f804b9ee42f443972c0ab78b567a9a83a2d11c1a02f39bc8f04237fe"


def golden_hashes():
    """(plain, stratified): fold ids for (n=1000, folds=5, seed=42), and
    for the same with 3 classes `arange(1000) % 7 % 3`, stratified. Also
    run on the chip (PERF.md, PR 24), where it must give the same two."""
    plain = CrossValidation(EV(), num_folds=5, seed=42).fold_masks(
        np.zeros(1000))
    y = (np.arange(1000) % 7 % 3).astype(np.float32)
    strat = CrossValidation(EV(), num_folds=5, seed=42,
                            stratify=True).fold_masks(y)
    return _sha(plain), _sha(strat)


def test_golden_fold_ids():
    assert golden_hashes() == (GOLDEN_PLAIN, GOLDEN_STRATIFIED)


def test_folds_ignore_the_global_prng_flags():
    want = golden_hashes()
    flag = jax.config.jax_threefry_partitionable
    impl = jax.config.jax_default_prng_impl
    try:
        jax.config.update("jax_threefry_partitionable", not flag)
        jax.config.update("jax_default_prng_impl", "rbg")
        F.assign_fold_masks.clear_cache()   # retrace under the other flags
        assert golden_hashes() == want
    finally:
        jax.config.update("jax_threefry_partitionable", flag)
        jax.config.update("jax_default_prng_impl", impl)
        F.assign_fold_masks.clear_cache()


def test_a_new_seed_does_not_recompile():
    CrossValidation(EV(), num_folds=3, seed=1).fold_masks(np.zeros(211))
    before = F.assign_fold_masks._cache_size()
    for seed in (2, 3, 2 ** 40):
        CrossValidation(EV(), num_folds=3, seed=seed).fold_masks(
            np.zeros(211))
    assert F.assign_fold_masks._cache_size() == before


# -- version 2, stated plainly -------------------------------------------------

def version2_masks(seed, y, n, folds, val_fraction, stratify,
                   word_mask=np.uint32(0)):
    """FOLD_ASSIGNMENT_VERSION 2 in numpy: the rows ordered by ([label,]
    word 0, word 1, row id) — row i's words the Threefry block of (i,
    n + i) — and the fold from the rank in that order (within the class
    when stratified), as assign_fold_masks' docstring says it."""
    from jax.extend.random import threefry_2x32
    i = np.arange(n, dtype=np.uint32)
    key = F.fold_key(seed)
    w0, w1 = np.asarray(threefry_2x32(
        (key[0], key[1]), np.stack([i, np.uint32(n) + i]))) | word_mask
    ids = np.arange(n)
    if not stratify:
        # the sorted ids, read as "row i has rank ids[i]"
        rank = np.lexsort((ids, w1, w0))
        n_val = None if val_fraction is None else int(round(n * val_fraction))
    else:
        order = np.lexsort((ids, w1, w0, y))
        cls = y[order]
        first = np.r_[True, cls[1:] != cls[:-1]]
        start = np.maximum.accumulate(np.where(first, ids, 0))
        size = np.diff(np.r_[np.flatnonzero(first), n])[np.cumsum(first) - 1]
        rank = np.empty(n, np.int64)
        rank[order] = ids - start
        if val_fraction is not None:
            n_val = np.empty(n, np.int64)
            n_val[order] = [round(int(c) * val_fraction) for c in size]
    fold_of = rank % folds if val_fraction is None else rank >= n_val
    held_out = np.arange(1 if val_fraction is not None else folds)
    return (fold_of[None, :] != held_out[:, None]).astype(np.float32)


# all but a word's two lowest bits forced to one: four values a word,
# all-ones (the padding's word) among them, so that hundreds of rows share
# all 64 bits and the row id decides
FEW_WORDS = np.uint32(0xFFFFFFFC)


@pytest.mark.parametrize("stratify", [False, True], ids=["plain", "strat"])
@pytest.mark.parametrize("val_fraction", [None, 0.3], ids=["kfold", "split"])
@pytest.mark.parametrize("n", [1, 5, 2047, 2048, 2049, 10_000, 65_537,
                               "10_000-collisions"])
def test_masks_are_version_2_bit_for_bit(n, val_fraction, stratify,
                                         monkeypatch):
    word_mask = np.uint32(0)
    if isinstance(n, str):
        n, word_mask, row_words = 10_000, FEW_WORDS, F._row_words
        monkeypatch.setattr(F, "_row_words", lambda *a: [
            w | FEW_WORDS for w in row_words(*a)])
    y = labels(n, 3, seed=n)
    try:
        F.assign_fold_masks.clear_cache()   # the words are traced in
        for seed in (7, 2 ** 31 + 11):
            got = F.assign_fold_masks(
                F.fold_key(seed), jnp.asarray(y) if stratify else None, n=n,
                n_folds=4, val_fraction=val_fraction, stratify=stratify)
            want = version2_masks(seed, y, n, 4, val_fraction, stratify,
                                  word_mask)
            assert got.dtype == jnp.float32
            assert np.array_equal(np.asarray(got), want), seed
    finally:
        monkeypatch.undo()
        F.assign_fold_masks.clear_cache()


@pytest.mark.parametrize("n", [5, 2048, 25_000_000])
def test_the_program_is_one_unstable_sort_of_keys_alone(n):
    """The unstratified program as lowered: ONE sort, unstable, three
    operands and every one a key (a stable sort keeps an index of its own
    on the chip), over a length that is a multiple of 2 048 (any other
    costs 3 % more a key there: PERF.md, PR 50)."""
    import re
    text = F.assign_fold_masks.lower(
        jax.ShapeDtypeStruct((2,), jnp.uint32), None, n=n,
        n_folds=5).as_text()
    sort, = re.findall(r'"stablehlo\.sort"\((.*?)\) <\{(.*?)\}> \(\{'
                       r'(.*?)\n    \}\) : \((.*?)\) ->', text, re.S)
    operands, attrs, comparator, types = sort
    assert "is_stable = false" in attrs
    assert len(operands.split(",")) == 3
    shape = F.fold_sort_shape(n, False)
    assert shape["sort_keys"] == 3 and shape["sort_places"] % 2048 == 0
    assert 0 <= shape["pad_places"] == shape["sort_places"] - n < 2048
    assert types.split(", ") == [
        f"tensor<{shape['sort_places']}x{t}>" for t in ("ui32", "ui32",
                                                        "i32")]
    # every operand decides: the comparator reads all six of its arguments
    args = re.findall(r"(%arg\d+): tensor", comparator.split("\n")[1])
    assert len(args) == 6
    assert all(re.search(rf"compare .*{a}\b", comparator) for a in args)


# -- validate() ---------------------------------------------------------------

def _data(n=300, d=5, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    beta = np.linspace(1.0, -1.0, d).astype(np.float32)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ beta)))) \
        .astype(np.float32)
    return X, y


@pytest.fixture
def placed(monkeypatch):
    """Spy on Validator._device_arrays: the (y, w, masks) each call got."""
    seen = []
    real = V.Validator._device_arrays

    def spy(self, X, y, w, masks, dtype):
        seen.append((y, w, masks))
        return real(self, X, y, w, masks, dtype)
    monkeypatch.setattr(V.Validator, "_device_arrays", spy)
    return seen


@pytest.mark.parametrize("stratify", [False, True])
@pytest.mark.parametrize("make", [
    lambda s: CrossValidation(EV(), num_folds=3, seed=5, stratify=s),
    lambda s: TrainValidationSplit(EV(), train_ratio=0.75, seed=5,
                                   stratify=s)], ids=["cv", "split"])
def test_validate_runs_on_the_masks_fold_masks_reports(make, stratify,
                                                       placed):
    X, y = _data()
    val = make(stratify)
    val.validate([(OpLogisticRegression(max_iter=5),
                   param_grid(reg_param=[0.01, 0.1]))], X, y)
    (_, w, masks), = placed
    # made on the device: nothing for device_place to copy
    assert isinstance(masks, jax.Array) and isinstance(w, jax.Array)
    assert masks.dtype == jnp.float32 and w.dtype == jnp.float32
    assert np.array_equal(np.asarray(w), np.ones(len(y), np.float32))
    host = val.fold_masks(y)
    assert np.array_equal(np.asarray(masks), host)
    # host labels and device labels draw the same folds
    assert np.array_equal(val.fold_masks(jnp.asarray(y)), host)
    assert np.array_equal(val.fold_masks(y.astype(np.float64)), host)
    dev = val.device_fold_masks(jnp.asarray(y))
    assert isinstance(dev, jax.Array) and np.array_equal(np.asarray(dev),
                                                         host)


def test_external_masks_and_weights_pass_through(placed):
    X, y = _data()
    cv = CrossValidation(EV(), num_folds=3, seed=5)
    one = cv.fold_masks(y)[1:2]
    w = np.full(len(y), 2.0, np.float32)
    cv.validate([(OpLogisticRegression(max_iter=5),
                  param_grid(reg_param=[0.01]))], X, y, w=w, masks=one)
    (_, w_seen, m_seen), = placed
    assert w_seen is w and m_seen is one
    assert cv._external_mask_tag != ""


def test_sequential_route_indexes_host_copies():
    """The one route that slices rows on the host converts the device
    masks and weights itself."""
    X, y = _data()
    cv = CrossValidation(EV(), num_folds=3, seed=5)
    best = cv.validate([(OpNaiveBayes(), param_grid(smoothing=[0.5, 1.0]))],
                       np.abs(X), y)
    assert {v.route for v in best.validated} == {"sequential"}
    assert all(np.isfinite(v.fold_metrics).all() for v in best.validated)


@pytest.mark.parametrize("external", [False, True])
def test_fold_assign_span_says_how_the_masks_were_made(external):
    X, y = _data()
    cv = CrossValidation(EV(), num_folds=3, seed=5, stratify=True)
    masks = cv.fold_masks(y)[:2] if external else None
    collector.disable()     # whatever an earlier test file left behind
    collector.enable("fold_assign_span")
    try:
        cv.validate([(OpLogisticRegression(max_iter=5),
                      param_grid(reg_param=[0.01]))], X, y, masks=masks)
        spans = list(collector.trace.spans)
    finally:
        collector.finish()
        collector.disable()
    sp, = [s for s in spans
           if s.kind == "validate_phase" and s.name == "fold_assign"]
    assert sp.attrs["route"] == ("external" if external else "device")
    assert sp.attrs["rows"] == len(y)
    assert sp.attrs["folds"] == (2 if external else 3)
    assert sp.attrs["stratify"] is True


# -- checkpoints --------------------------------------------------------------

def test_a_record_keyed_before_the_version_is_not_replayed(tmp_path):
    """A checkpoint written under the numpy assignment ran on other folds:
    its key (no `fold_assignment`) no longer matches, so the cell refits."""
    X, y = _data()
    est = OpLogisticRegression(max_iter=5)
    grid = {"reg_param": 0.01}
    cv = CrossValidation(EV(), num_folds=3, seed=5)
    cv.checkpoint_path = str(tmp_path / "sweep.jsonl")
    _, (key,), _ = cv._cell_bookkeeping(est, [grid], X, y, "au_roc", 3,
                                        path="vmapped:float32")

    def old_key(**kw):     # sweep_key as PR 23 wrote it
        base = est.param_values()
        payload = json.dumps(
            {"model": "OpLogisticRegression", "grid": grid, "folds": 3,
             "seed": 5, "stratify": False, "metric": "au_roc",
             "data": data_fingerprint(X, y), "path": "vmapped:float32",
             "base": {k: base[k] for k in sorted(base)}, **kw},
            sort_keys=True, default=str)
        return hashlib.sha256(payload.encode()).hexdigest()[:24]
    # the helper reproduces today's key once the version is added ...
    assert old_key(fold_assignment=F.FOLD_ASSIGNMENT_VERSION) == key
    stale = old_key()
    assert stale != key
    # ... and a record under yesterday's key is left alone
    SweepCheckpoint(cv.checkpoint_path).record(
        stale, "OpLogisticRegression", grid, [9.0, 9.0, 9.0], "au_roc")
    best = cv.validate([(est, [grid])], X, y)
    assert all(m < 1.5 for m in best.validated[0].fold_metrics)
    assert SweepCheckpoint(cv.checkpoint_path).get(key) is not None
