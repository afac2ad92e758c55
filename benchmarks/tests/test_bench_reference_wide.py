"""reference_wide.py against a dense float64 brute force on a few hundred
rows, the probe's construction, and the Epsilon generator's contract. Run
by hand: ``JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q``."""

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import reference            # noqa: E402
import reference_wide       # noqa: E402
from data import epsilon    # noqa: E402

SPEC = {"features": 24, "sample_seed": 3}


def _data(rows=600):
    X, y = epsilon.make(SPEC, 5, rows, rows)
    bounds = []
    for j in range(X.shape[1]):
        q = np.quantile(X[:, j].astype(np.float64), np.linspace(0, 1, 9)[1:-1])
        bounds.append(np.unique(q))
    return X, y, bounds


def _brute_root(X, y, bounds, min_data, min_hess):
    n = len(y)
    p0, h0 = reference.binary_root_stats(y)
    g = p0 - y.astype(np.float64)
    best = (-np.inf, -1, -1, -1)
    for j in range(X.shape[1]):
        for t, b in enumerate(bounds[j]):
            left = X[:, j].astype(np.float64) <= b
            cl, cr = int(left.sum()), int(n - left.sum())
            hl, hr = h0 * cl + 1e-15, h0 * cr
            if min(cl, cr) < min_data or min(hl, hr) < min_hess:
                continue
            gain = g[left].sum() ** 2 / hl + g[~left].sum() ** 2 / hr
            if gain > best[0]:
                best = (gain, j, t, cl)
    return best


def test_root_split_is_the_brute_force_over_all_columns():
    X, y, bounds = _data()
    _, cnt, ysum = reference_wide.whole_histograms(X, y, bounds, 8, 2)
    got = reference_wide.root_split(cnt, ysum, y, bounds, 5.0, 1.0)
    want = _brute_root(X, y, bounds, 5.0, 1.0)
    assert got[1:] == want[1:] and abs(got[0] - want[0]) < 1e-9 * want[0]
    gain, left = reference_wide.gain_of_raw_split(
        X[:, got[1]], y, bounds[got[1]][got[2]], 5.0, 1.0)
    assert left == got[3] and abs(gain - got[0]) < 1e-9 * got[0]


def test_leaf_index_walks_the_raw_values():
    X, _, _ = _data()
    tree = {"num_leaves": 3, "split_feature": np.array([2, 5]),
            "threshold": np.array([0.0, 0.01]),
            "left_child": np.array([1, -1]), "right_child": np.array([-2, -3])}
    leaf = reference_wide.leaf_index(tree, X, 2)
    x2, x5 = X[:, 2].astype(np.float64), X[:, 5].astype(np.float64)
    want = np.where(x2 <= 0.0, np.where(x5 <= 0.01, 0, 2), 1)
    assert np.array_equal(leaf, want)


def test_the_decision_list_takes_each_columns_top_bin_of_what_is_left():
    X, y, bounds = _data(4000)
    binsT, _, _ = reference_wide.whole_histograms(X, y, bounds, 8, 2)
    cols = reference_wide.designated_columns(24, 8)
    assert cols.tolist() == [0, 3, 7, 8, 11, 15, 16, 19, 23]
    top = [len(bounds[c]) for c in cols]
    y2, steps = reference_wide.decision_list(binsT, cols, top, 9)
    assert sorted(c for c, _, _ in steps) == cols.tolist()
    assert [m for _, _, m in steps][:2] == [reference_wide.PROBE_HIGH,
                                            reference_wide.PROBE_LOW]
    free = np.ones(len(y), bool)
    for c, rows, mean in steps:
        take = free & (binsT[c] == len(bounds[c]))
        assert rows == take.sum() > 0
        assert abs(y2[take].mean() - mean) < 0.1
        free &= ~take
    assert abs(y2[free].mean() - 0.5) < 0.05
    # a second seed draws another order and another label
    y3, steps3 = reference_wide.decision_list(binsT, cols, top, 10)
    assert [c for c, _, _ in steps3] != [c for c, _, _ in steps]


def test_planted_found_reads_thresholds_by_bin():
    bounds = [np.array([-1.0, 0.0, 1.0])] * 4
    tree = {"split_feature": np.array([0, 1, 1, 3]),
            "threshold": np.array([1.0, 0.0, 0.9, 1.0])}
    # top bin 3: the planted split is at bound 1.0 (bin 2); 0.9 lies in it
    assert reference_wide.planted_found(tree, [0, 1, 2, 3], [3] * 4,
                                        bounds) == [True, True, False, True]


def test_generator_is_fixed_by_sample_seed_and_balanced():
    Xa, ya = epsilon.make(SPEC, 1, 5000, 4000)
    Xb, yb = epsilon.make(SPEC, 2, 5000, 4000)
    assert Xa.dtype == np.float32 and ya.dtype == np.float32
    assert np.array_equal(Xa[4000:], Xb[4000:])
    assert np.allclose((Xa.astype(np.float64) ** 2).sum(axis=1), 1.0,
                       atol=1e-5)
    assert 0.45 < ya.mean() < 0.55
    assert sorted(map(tuple, Xa[:4000])) == sorted(map(tuple, Xb[:4000]))
    t = epsilon.task(2000)
    assert t["cols"][0] == 0 and t["cols"][-1] == 1999
    assert len(t["cols"]) == 401
