"""reference_rank.py against a hand-computed case, against the literal
per-query loop of the repo's tier-1 tests (tests/test_ranking.py), against the library's NDCG
metric, and the job's root check against the system; tiny, on the CPU."""

import math
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT, os.path.join(ROOT, "tests")]

import reference          # noqa: E402
import reference_rank     # noqa: E402


def test_lambdas_equal_a_hand_computed_two_query_case():
    """Query A: labels (2, 0, 1), scores (0.5, 1.0, 0.0), sigmoid 1, no
    normalisation. Sorted by score: doc 1 (rank 0), doc 0 (rank 1), doc 2
    (rank 2). Query B: two documents of one label, so nothing."""
    d = [1.0, 1.0 / math.log2(3.0), 0.5]            # discount by rank
    inv = 1.0 / (3.0 * d[0] + 1.0 * d[1])           # gains 3, 1, 0 in order
    lam, hess = [0.0, 0.0, 0.0], [0.0, 0.0, 0.0]
    # (high doc, low doc, high rank, low rank, gain gap)
    for hi, lo, r_hi, r_lo, gap in ((0, 1, 1, 0, 3.0), (2, 1, 2, 0, 1.0),
                                    (0, 2, 1, 2, 2.0)):
        ds = (0.5, 1.0, 0.0)[hi] - (0.5, 1.0, 0.0)[lo]
        dndcg = gap * abs(d[r_hi] - d[r_lo]) * inv
        p = 1.0 / (1.0 + math.exp(ds))
        lam[hi] += -dndcg * p
        lam[lo] -= -dndcg * p
        hess[hi] += dndcg * p * (1.0 - p)
        hess[lo] += dndcg * p * (1.0 - p)
    g, h = reference_rank.lambdarank(
        [2, 0, 1, 1, 1], [0.5, 1.0, 0.0, 0.3, -0.2], [3, 2], sigmoid=1.0,
        norm=False)
    np.testing.assert_allclose(g, lam + [0.0, 0.0], rtol=1e-12, atol=0)
    np.testing.assert_allclose(h, hess + [0.0, 0.0], rtol=1e-12, atol=0)
    # the document the labels rank first is pushed up (negative gradient)
    assert g[0] < 0 < g[1]
    # with normalisation the same query is scaled by 1/(0.01+|ds|) a pair
    # and log2(1+S)/S overall: one number, checked by hand
    gn, _ = reference_rank.lambdarank([2, 0, 1], [0.5, 1.0, 0.0], [3],
                                      sigmoid=1.0, norm=True)
    pl = [-3.0 * abs(d[1] - d[0]) * inv / 0.51 / (1 + math.exp(-0.5)),
          -1.0 * abs(d[2] - d[0]) * inv / 1.01 / (1 + math.exp(-1.0)),
          -2.0 * abs(d[1] - d[2]) * inv / 0.51 / (1 + math.exp(0.5))]
    s = -2.0 * sum(pl)
    assert gn[0] == pytest.approx((pl[0] + pl[2]) * math.log2(1 + s) / s,
                                  rel=1e-12)


@pytest.mark.parametrize("trunc,norm,sigmoid,decimals",
                         [(30, True, 1.0, 6), (5, True, 2.0, 6),
                          (30, False, 1.0, 6), (30, True, 1.0, 0)])
def test_lambdas_equal_the_literal_loop(trunc, norm, sigmoid, decimals):
    from test_ranking import _lambdarank_loop
    rng = np.random.default_rng(2)
    sizes = np.array([1, 2, 7, 31, 45, 4, 30])
    n = int(sizes.sum())
    y = rng.integers(0, 5, size=n).astype(np.float64)
    score = np.round(rng.standard_normal(n), decimals) + 0.0
    g, h = reference_rank.lambdarank(y, score, sizes, sigmoid=sigmoid,
                                     norm=norm, truncation_level=trunc)
    g_ref, h_ref = _lambdarank_loop(y, score, sizes, sigmoid=sigmoid,
                                    trunc=trunc, norm=norm)
    np.testing.assert_allclose(g, g_ref, rtol=1e-10, atol=1e-13)
    np.testing.assert_allclose(h, h_ref, rtol=1e-10, atol=1e-13)
    err = reference_rank.per_query_error(g * (1 + 1e-6), g, sizes)
    assert err.shape == (len(sizes),) and err.max() <= 1.01e-6
    # a bf16 pair stage is no float32 one
    import ml_dtypes
    low, _ = reference_rank.lambdarank(
        y, score, sizes, sigmoid=sigmoid, norm=norm, truncation_level=trunc,
        pair_round=lambda x: x.astype(ml_dtypes.bfloat16).astype(np.float64))
    assert reference_rank.per_query_error(low, g, sizes).max() > 1e-4


@pytest.mark.parametrize("k", (1, 5, 10))
def test_ndcg_equals_the_library_metric(k):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ranking import NDCGMetric
    rng = np.random.default_rng(3)
    sizes = np.array([1, 3, 12, 40, 8, 5])
    n = int(sizes.sum())
    y = rng.integers(0, 5, size=n).astype(np.float64)
    y[4:16] = 0.0                      # a query without a relevant document
    score = np.round(rng.standard_normal(n), 1)
    m = NDCGMetric(Config.from_params({"eval_at": [k]}))
    m.init(y, None, sizes)
    assert reference_rank.ndcg_at_k(y, score, sizes, k) == \
        pytest.approx(m.eval(score)[0], rel=1e-12)
    assert reference_rank.ndcg_at_k(y, -y * 0.0 + y, sizes, k) == \
        pytest.approx(1.0)


def test_root_check_against_the_system_as_the_job_makes_it():
    """jobs/rank_train.py check (a) and (b) at a tiny size: the root of
    tree 0 over the reference's lambdas at zero scores, and the library's
    objective on every query at the trained scores."""
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(5)
    sizes = rng.integers(1, 90, size=120)
    n = int(sizes.sum())
    X = rng.standard_normal((n, 6)).astype(np.float32)
    y = np.clip(np.round(X[:, 0] + 0.5 * X[:, 3] + 1.5
                         + 0.5 * rng.standard_normal(n)), 0, 4) \
        .astype(np.float32)
    params = {"objective": "lambdarank", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3}
    ds = lgb.Dataset(X, label=y, group=sizes, params=params)
    b = lgb.train(params, ds, 4, keep_training_booster=True)
    g0, h0 = reference_rank.lambdarank(y, np.zeros(n), sizes)
    bins = np.asarray(ds.bins)[:n]
    gain, f_np, _t, left_np = reference_rank.root_split(
        bins, g0, h0, int(ds.max_num_bins), 20, 1e-3)
    tree = reference.parse_model(b.model_to_string(num_iteration=1))[0]
    f_sys = int(tree["split_feature"][0])
    gain_sys, left_raw = reference_rank.gain_of_raw_split(
        X[:, f_sys], g0, h0, float(tree["threshold"][0]), 20, 1e-3)
    assert (gain - gain_sys) / gain <= 1e-3
    assert reference.child_count(tree, int(tree["left_child"][0])) == left_raw
    assert (f_sys, left_raw) == (f_np, left_np)
    gb = b._boosting
    score = np.asarray(gb.train_score, np.float32).reshape(-1)
    g_sys, h_sys = gb.objective.get_grad_hess(gb.train_score)
    g_ref, h_ref = reference_rank.lambdarank(y, score, sizes)
    assert reference_rank.per_query_error(
        np.asarray(g_sys), g_ref, sizes).max() <= 1e-5
    assert reference_rank.per_query_error(
        np.asarray(h_sys), h_ref, sizes).max() <= 1e-5
