"""reference.py against the repo's own oracle and against the system, at a
tiny size on the CPU."""

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT, os.path.join(ROOT, "tests")]

import reference  # noqa: E402


def _data(n=4000, f=6, seed=1):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 3] + rng.logistic(size=n) > 0)
    return X, y.astype(np.float32)


def test_root_split_equals_the_brute_force_oracle():
    import reference_impl as oracle           # tests/reference_impl.py
    X, y = _data(1500)
    nb = 16
    bins = np.stack([np.searchsorted(np.quantile(X[:, j], np.linspace(
        0, 1, nb + 1)[1:-1]), X[:, j]) for j in range(X.shape[1])],
        axis=1).astype(np.uint8)
    p0, h0 = reference.binary_root_stats(y)
    grad, hess = p0 - y.astype(np.float64), np.full(len(y), h0)
    best = None
    for j in range(bins.shape[1]):
        hist = np.zeros((nb, 3))
        np.add.at(hist, bins[:, j], np.stack(
            [grad, hess, np.ones(len(y))], axis=1))
        r = oracle.best_split_feature(
            hist, grad.sum(), hess.sum(), float(len(y)), nb,
            oracle.MISSING_NONE, 0, 0.0, 0.0, 20, 1e-3, 0.0)
        if r is not None and (best is None or r[0] > best[0]):
            best = r + (j,)
    gain, f, t, left = reference.root_split(bins, y, nb, 20, 1e-3)
    shift = oracle.leaf_gain(grad.sum(), hess.sum(), 0.0, 0.0)
    assert (f, t, left) == (best[-1], best[1], int(best[5]))
    assert abs((gain - shift) - best[0]) <= 1e-9 * abs(gain)


def test_model_text_traversal_and_root_check_against_the_system():
    import lightgbm_tpu as lgb
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3}
    ds = lgb.Dataset(X, label=y, params=params)
    b = lgb.train(params, ds, 6, keep_training_booster=True)
    trees = reference.parse_model(b.model_to_string())
    assert len(trees) == 6
    got = reference.predict_raw(trees, X[:500])
    want = b.predict(X[:500], raw_score=True)
    assert np.abs(got - want).max() <= 1e-9
    # the root of tree 0, as jobs/train.py checks it
    bins = np.asarray(ds.bins)[:ds.num_data]
    gain, f, t, left = reference.root_split(bins, y, int(ds.max_num_bins),
                                            20, 1e-3)
    t0 = trees[0]
    g_sys, left_raw = reference.gain_of_raw_split(
        X[:, int(t0["split_feature"][0])], y, float(t0["threshold"][0]),
        20, 1e-3)
    assert (gain - g_sys) / gain <= 1e-3
    assert left_raw == reference.child_count(t0, int(t0["left_child"][0]))
    assert len(y) - left_raw == reference.child_count(
        t0, int(t0["right_child"][0]))


def test_midrank_auc_handles_ties():
    y = np.array([0, 0, 1, 1], dtype=np.float32)
    assert reference.midrank_auc(y, np.array([0.1, 0.2, 0.3, 0.4])) == 1.0
    assert reference.midrank_auc(y, np.array([0.5, 0.5, 0.5, 0.5])) == 0.5
