"""The two-level one-hot histogram body (ops/pallas_rank_hist.py) that
pallas_hist.hist_pallas runs for the rank metrics' wide bin axis: against
the jnp twin and float64 sums in the sweep's two call forms, the dispatch
rule, the public metric functions through it, and the one static fact —
`unit_payload` — on its way from validate() to the kernel. Everything runs
on the CPU through interpret=True at small shapes.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from benchmark.reference import hist_plain    # the contract, in float64
from transmogrifai_tpu.automl.tuning import validators as V
from transmogrifai_tpu.evaluators.evaluators import Evaluators
from transmogrifai_tpu.models.glm import OpLogisticRegression
from transmogrifai_tpu.ops import metrics_ops as M
from transmogrifai_tpu.ops import pallas_hist as PH
from transmogrifai_tpu.ops import pallas_rank_hist as RH
from transmogrifai_tpu.ops import parts as P


def _operands(rng, F, n_folds, n_slots, C, n_bins, N, unit):
    Xb = jnp.asarray(rng.integers(0, n_bins, size=(F, N)), jnp.int32)
    pay = rng.integers(0, 2, size=(n_folds * C, N)) if unit else \
        rng.uniform(0.2, 2.0, size=(n_folds * C, N))   # bf16 cannot hold
    # slot n_slots: a dropped row
    slot = rng.integers(0, n_slots + 1, size=(n_folds, N))
    return Xb, jnp.asarray(pay, jnp.float32), jnp.asarray(slot, jnp.float32)


# (F, n_folds, n_slots, C, n_bins, N): the grid points as features with the
# fold as slot (validators._streamed_eval_heldout), F = 1 with the lane as
# slot (fold_metrics), two fold lanes; N ragged against the 4 096-row
# block, one to three grid steps
FORMS = [
    pytest.param(6, 1, 5, 2, 4096, 9000, id="heldout-4096"),
    pytest.param(3, 1, 3, 2, 1024, 1100, id="heldout-1024"),
    pytest.param(1, 1, 10, 2, 4096, 5000, id="lanes-4096"),
    pytest.param(1, 1, 4, 2, 1024, 777, id="lanes-1024"),
    pytest.param(2, 2, 3, 2, 1152, 4100, id="two-folds-9-hi-rows"),
]


@pytest.mark.parametrize("F,n_folds,n_slots,C,n_bins,N", FORMS)
def test_weights_bf16_cannot_hold_take_three_parts(F, n_folds, n_slots, C,
                                                   n_bins, N):
    rng = np.random.default_rng(N)
    Xb, pay, slot = _operands(rng, F, n_folds, n_slots, C, n_bins, N, False)
    assert RH.hist_body(n_bins, False) == "two_level"
    got = np.asarray(PH.hist_pallas(Xb, pay, slot, n_slots=n_slots,
                                    n_bins=n_bins, interpret=True))
    twin = PH._hist_segment_jnp(Xb, pay, slot, n_slots=n_slots,
                                n_bins=n_bins)
    assert got.shape == (n_folds * n_slots * C, F * n_bins)
    np.testing.assert_allclose(got, np.asarray(twin), rtol=0, atol=1e-3)
    np.testing.assert_allclose(
        got, hist_plain(Xb, pay, slot, n_slots, n_bins), rtol=0,
        atol=1e-3)
    # one part of such weights is their bfloat16 truncation: the caller's
    # word is what keeps it off this payload
    one = np.asarray(PH.hist_pallas(Xb, pay, slot, n_slots=n_slots,
                                    n_bins=n_bins, interpret=True,
                                    unit_payload=True))
    assert np.abs(one - got).max() > 1e-3


@pytest.mark.parametrize("F,n_folds,n_slots,C,n_bins,N", FORMS)
def test_a_payload_of_zeros_and_ones_is_its_one_part(F, n_folds, n_slots,
                                                     C, n_bins, N):
    rng = np.random.default_rng(N + 1)
    Xb, pay, slot = _operands(rng, F, n_folds, n_slots, C, n_bins, N, True)
    kw = dict(n_slots=n_slots, n_bins=n_bins, interpret=True)
    three = np.asarray(PH.hist_pallas(Xb, pay, slot, **kw))
    one = np.asarray(PH.hist_pallas(Xb, pay, slot, unit_payload=True, **kw))
    np.testing.assert_array_equal(one, three)          # to the bit
    np.testing.assert_array_equal(
        one, hist_plain(Xb, pay, slot, n_slots, n_bins))  # counts


def test_derived_count_channel():
    rng = np.random.default_rng(3)
    Xb, pay, slot = _operands(rng, 2, 1, 3, 2, 1024, 600, False)
    pay = pay * (jnp.asarray(rng.uniform(size=pay.shape)) < 0.6)
    kw = dict(n_slots=3, n_bins=1024, derive_count=True)
    got = PH.hist_pallas(Xb, pay, slot, interpret=True, **kw)
    twin = PH._hist_segment_jnp(Xb, pay, slot, **kw)
    assert got.shape == (3 * 3, 2 * 1024)
    np.testing.assert_allclose(np.asarray(got), np.asarray(twin), rtol=0,
                               atol=1e-3)


def test_bf16_cuts_sum_to_the_float32():
    """The payload's cuts as the kernel's body makes them (ops/parts.py,
    `in_kernel`); with ONE part the payload itself: no operation."""
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 256))
                    * np.logspace(-6, 6, 256), jnp.float32)
    cuts = P.float32_parts(x, jnp.bfloat16, 3, in_kernel=True)
    for c in cuts:      # each part survives bfloat16 unchanged
        np.testing.assert_array_equal(
            np.asarray(c.astype(jnp.bfloat16).astype(jnp.float32)),
            np.asarray(c))
    np.testing.assert_array_equal(np.asarray(cuts[0] + cuts[1] + cuts[2]),
                                  np.asarray(x))
    assert P.float32_parts(x, jnp.bfloat16, 1, in_kernel=True)[0] is x


@pytest.mark.parametrize("allow_bf16,flag,n_bins,body", [
    (False, True, 4096, "two_level"),     # the rank metrics
    (False, True, 1024, "two_level"),
    (False, True, 1152, "two_level"),     # nine 128-bin groups
    (False, True, 8192, "two_level"),
    (False, True, 512, "one_level"),      # narrow metric calls
    (False, True, 1000, "one_level"),     # no whole 128-bin groups
    (False, True, 33, "one_level"),
    (True, True, 33, "one_level"),        # the tree histograms
    (True, True, 4096, "one_level"),      # bfloat16 mode, whatever the width
    (True, False, 4096, "two_level"),     # set_hist_bf16(False): float32
])
def test_dispatch_rule(monkeypatch, allow_bf16, flag, n_bins, body):
    """hist_pallas chooses from the dtype mode and the bin axis alone, and
    hist_body is the function that says which."""
    monkeypatch.setattr(PH, "_HIST_BF16", flag)
    assert RH.hist_body(n_bins, allow_bf16 and flag) == body
    ran = []
    monkeypatch.setattr(
        RH, "_hist_two_level_jit",
        lambda *a, **k: ran.append(("two_level", k)))
    monkeypatch.setattr(
        PH, "_hist_pallas_jit",
        lambda *a, **k: ran.append(("one_level", k)))
    z = jnp.zeros((1, 8), jnp.float32)
    PH.hist_pallas(z.astype(jnp.int32), z, z, n_slots=2, n_bins=n_bins,
                   allow_bf16=allow_bf16)
    (name, kw), = ran
    assert name == body
    if body == "two_level":
        assert kw["parts"] == 3      # nobody vouched
    else:
        assert kw["use_bf16"] == (allow_bf16 and flag)


def test_payload_parts():
    assert RH.payload_parts(True) == 1 and RH.payload_parts(False) == 3


@pytest.fixture
def through_the_kernel(monkeypatch):
    """The public metric functions take the pallas route, interpreted; the
    list holds the keywords of every hist_pallas call."""
    calls = []
    orig = PH.hist_pallas

    def interpreted(*a, **kw):
        calls.append(kw)
        return orig(*a, **dict(kw, interpret=True))
    monkeypatch.setattr(M, "_pallas_route", lambda: True)
    monkeypatch.setattr(PH, "hist_pallas", interpreted)
    return calls


@pytest.mark.parametrize("unit", [False, True], ids=["weights", "unit"])
@pytest.mark.parametrize("n_folds,Gc,n", [(5, 6, 2300), (1, 3, 777)])
def test_heldout_metrics_equal_the_jnp_twins(through_the_kernel, n_folds,
                                             Gc, n, unit):
    rng = np.random.default_rng(n)
    y = jnp.asarray(rng.uniform(size=n) < 0.4, jnp.float32)
    scores = jnp.asarray(rng.normal(size=(Gc, n)) + 1.5 * (np.asarray(y) - .5),
                         jnp.float32)
    w = jnp.ones(n, jnp.float32) if unit else \
        jnp.asarray(rng.uniform(0.2, 2.0, size=n), jnp.float32)
    fold_of = jnp.asarray(rng.integers(0, n_folds + 1, size=n), jnp.int32)
    assert M.rank_hist_kernel(4096, unit) == {
        "hist_body": "two_level", "payload_parts": 1 if unit else 3}
    for fn, from_counts in ((M.au_pr_heldout_lanes, M._au_pr_from_counts),
                            (M.au_roc_heldout_lanes, M._au_roc_from_counts)):
        got = fn(scores, y, w, fold_of, n_folds, 4096, unit_payload=unit)
        twin = from_counts(*M._heldout_cum_counts_lanes_jnp(
            scores, y, w, fold_of, n_folds, 4096))
        assert got.shape == (n_folds, Gc)
        np.testing.assert_allclose(np.asarray(got), np.asarray(twin),
                                   rtol=0, atol=1e-6)
    assert through_the_kernel and all(
        kw["unit_payload"] is unit for kw in through_the_kernel)


def test_lanes_metrics_keep_their_four_positional_arguments(
        through_the_kernel):
    """The benchmark's kernel_twins check calls au_pr_binned_lanes(scores,
    y, wl, bins): the F = 1 form, three parts unless vouched for."""
    rng = np.random.default_rng(5)
    L, n = 4, 900
    y = jnp.asarray(rng.uniform(size=n) < 0.5, jnp.float32)
    scores = jnp.asarray(rng.normal(size=(L, n)), jnp.float32)
    wl = jnp.asarray(rng.uniform(size=(L, n)) < 0.7, jnp.float32)
    twin = M._au_pr_from_counts(
        *M._binned_cum_counts_lanes_jnp(scores, y, wl, 4096))
    got = M.au_pr_binned_lanes(scores, y, wl, 4096)
    vouched = M.au_pr_binned_lanes(scores, y, wl, 4096, unit_payload=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(twin), rtol=0,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(vouched), np.asarray(got))
    assert [kw["unit_payload"] for kw in through_the_kernel] == [False, True]


def test_off_the_tpu_the_counts_are_the_scatter_twins():
    assert M.rank_hist_kernel(4096, True) == {
        "hist_body": "scatter", "payload_parts": 1}


def _binary(n=1200, d=5, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    p = 1 / (1 + np.exp(-(X @ np.linspace(1.5, -1.5, d))))
    return X, (rng.uniform(size=n) < p).astype(np.float32)


@pytest.mark.parametrize("case,parts", [
    ("default", 1),             # no sample weights, the validator's masks
    ("sample_weights", 3),
    ("external_masks", 3),      # masks handed in may hold anything
])
def test_validate_says_what_it_can_vouch_for(monkeypatch, case, parts):
    """The one static fact, from where validate() sees it to the counts'
    dispatcher (a jit key on the way) and into the telemetry."""
    monkeypatch.setattr(V, "STREAMED_SWEEP_MIN_ROWS", 0)
    monkeypatch.setattr(V, "BINNED_RANK_METRIC_MIN_ROWS", 0)
    seen = []
    orig = M.heldout_cum_counts_lanes

    def spy(*a, **kw):
        seen.append(kw["unit_payload"])
        return orig(*a, **kw)
    monkeypatch.setattr(M, "heldout_cum_counts_lanes", spy)
    V._streamed_eval_heldout.clear_cache()
    X, y = _binary()
    cv = V.CrossValidation(Evaluators.BinaryClassification.au_pr(),
                           num_folds=3, seed=5)
    w = masks = None
    if case == "sample_weights":
        w = np.random.default_rng(1).uniform(0.5, 2.0, len(y)) \
            .astype(np.float32)
    if case == "external_masks":
        masks = np.asarray(cv.fold_masks(y))
    best = cv.validate([(OpLogisticRegression(max_iter=4),
                         [{"reg_param": 0.01}, {"reg_param": 0.1}])],
                       X, y, w=w, masks=masks)
    V._streamed_eval_heldout.clear_cache()
    assert {v.route for v in best.validated} == {"streamed"}
    tele = cv.last_streamed_telemetry
    assert tele["eval_route"] == "heldout_once"
    assert tele["payload_parts"] == parts
    assert tele["hist_body"] == "scatter"       # the CPU's route
    assert seen == [parts == 1]
