"""reference_missing.py against a float64 brute force on a few hundred rows
with NaN: the root split with NaN tried on both sides, the traversal that
sends a NaN by the printed direction, and the probe's construction. Run
by hand: ``JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q``."""

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import reference            # noqa: E402
import reference_dp         # noqa: E402
import reference_missing    # noqa: E402
from data import bosch      # noqa: E402


class _Mapper:
    """What ``tables`` reads of a fitted bin mapper."""

    def __init__(self, bounds, has_nan):
        self.bin_upper_bound = list(bounds) + [np.inf]
        self.num_bin = len(bounds) + 1 + (1 if has_nan else 0)
        self.missing_type = 2 if has_nan else 0


def _data(rows=500, f=12):
    rng = np.random.default_rng(4)
    X = np.round(rng.standard_normal((rows, f)), 1).astype(np.float32)
    X[rng.random((rows, f)) < np.linspace(0.0, 0.9, f)] = np.nan
    y = ((np.nan_to_num(X[:, 2], nan=1.5) + np.isnan(X[:, 7])
          + rng.logistic(size=rows)) > 1.2).astype(np.float32)
    mappers = []
    for j in range(f):
        col = X[:, j][~np.isnan(X[:, j])].astype(np.float64)
        bounds = np.unique(np.quantile(col, np.linspace(0, 1, 8)[1:-1]))
        mappers.append(_Mapper(bounds, bool(np.isnan(X[:, j]).any())))
    return X, y, mappers


def _brute_root(X, y, bounds, min_data, min_hess):
    """Every column, every bound, NaN on each side, from the raw rows."""
    n = len(y)
    p0, h0 = reference.binary_root_stats(y)
    g = p0 - y.astype(np.float64)
    best = (-np.inf, -1, -1, False, -1)
    for j in range(X.shape[1]):
        x = X[:, j].astype(np.float64)
        nan = np.isnan(x)
        for t, b in enumerate(list(bounds[j]) + [np.inf]):
            for nan_left in ((False, True) if nan.any() else (False,)):
                with np.errstate(invalid="ignore"):
                    left = np.where(nan, nan_left, x <= b)
                cl = int(left.sum())
                if t == len(bounds[j]) and (nan_left or not nan.any()):
                    continue        # no cut between values
                hl, hr = h0 * cl + 1e-15, h0 * (n - cl)
                if min(cl, n - cl) < min_data or min(hl, hr) < min_hess:
                    continue
                gain = g[left].sum() ** 2 / hl + g[~left].sum() ** 2 / hr
                if gain > best[0] * (1 + 1e-12):
                    best = (gain, j, t, nan_left, cl)
    return best


def test_root_split_is_the_brute_force_with_nan_both_ways():
    X, y, mappers = _data()
    bounds, real, nan_bin = reference_missing.tables(mappers)
    assert real == [len(b) + 1 for b in bounds]
    _, cnt, ysum = reference_missing.whole_histograms(
        X, y, bounds, nan_bin, max(m.num_bin for m in mappers), 2)
    assert (cnt.sum(axis=1) == len(y)).all()
    got = reference_dp.root_split(cnt, ysum, y, real, nan_bin, 5.0, 0.5)
    want = _brute_root(X, y, bounds, 5.0, 0.5)
    assert got[1:] == want[1:] and abs(got[0] - want[0]) < 1e-9 * want[0]
    j, t, nan_left = got[1], got[2], got[3]
    (g_left, c_left), (g_right, c_right) = reference_missing.direction_gains(
        X[:, j], y, bounds[j][t], 5.0, 0.5)
    gain, count = (g_left, c_left) if nan_left else (g_right, c_right)
    assert count == got[4] and abs(gain - got[0]) < 1e-9 * got[0]


def test_traversal_sends_nan_by_the_printed_direction():
    X, _, _ = _data()
    left = reference_missing.NAN_LEFT
    right = reference_missing.NAN_RIGHT
    tree = {"num_leaves": 3, "split_feature": np.array([7, 3]),
            "threshold": np.array([0.0, 0.5]),
            "left_child": np.array([1, -2]), "right_child": np.array([-1,
                                                                       -3])}
    leaf = reference_dp.leaf_index(tree, np.array([left, right]), X, 2)
    x7, x3 = X[:, 7].astype(np.float64), X[:, 3].astype(np.float64)
    with np.errstate(invalid="ignore"):
        go7 = np.isnan(x7) | (x7 <= 0.0)
        go3 = ~np.isnan(x3) & (x3 <= 0.5)
    want = np.where(~go7, 0, np.where(go3, 1, 2))
    assert np.array_equal(leaf, want)
    rows = reference_missing.node_rows(tree, np.array([left, right]), X, 1)
    assert np.array_equal(rows, np.flatnonzero(go7))


def test_the_planted_list_cuts_present_rows_and_leaves_nan_free():
    """A step takes present rows only, from the top or the bottom of its
    column, at least ``slice_rows`` of them where the column has them;
    ``planted_found`` reads the bound and the direction back."""
    X, _, mappers = _data(rows=2000)
    bounds, real, nan_bin = reference_missing.tables(mappers)
    binsT = reference_missing.host_bins(X, bounds, nan_bin, 2)
    cols = np.array([2, 5, 9])
    y, steps = reference_missing.planted_list(binsT, cols, real, 60, 11)
    assert sorted(s[0] for s in steps) == cols.tolist()
    assert 0.3 < y.mean() < 0.7
    for c, rows, mean, t, top in steps:
        assert top == (int(np.flatnonzero(cols == c)[0]) % 2 == 0)
        assert rows >= 60 or rows == (binsT[c] < real[c]).sum()
        assert mean == (reference_missing.PROBE_HIGH if top
                        else reference_missing.PROBE_LOW)
        assert 0 <= t < real[c] - 1
    c, _, _, t, top = steps[0]
    thr = bounds[c][t]
    tree = {"num_leaves": 2, "split_feature": np.array([c]),
            "threshold": np.array([thr]), "left_child": np.array([-1]),
            "right_child": np.array([-2])}
    dt = np.array([reference_missing.NAN_LEFT if top
                   else reference_missing.NAN_RIGHT])
    found = reference_missing.planted_found(tree, dt, steps[:1], bounds, X)
    assert found == [(0, True)]
    flipped = reference_missing.planted_found(
        tree, dt ^ 2, steps[:1], bounds, X)
    assert flipped == [(0, False)]


def test_the_planted_direction_is_read_where_the_most_rows_meet_the_cut():
    """Where the tree cuts a designated column at its bound twice, the
    direction is read at the node the most rows reach, not at the first:
    a small node inside another step's slice may rightly send NaN the
    other way."""
    X, _, mappers = _data(rows=2000)
    bounds, real, nan_bin = reference_missing.tables(mappers)
    binsT = reference_missing.host_bins(X, bounds, nan_bin, 2)
    _, steps = reference_missing.planted_list(binsT, np.array([9]), real,
                                              60, 11)
    c, _, _, t, top = steps[0]
    planted = reference_missing.NAN_LEFT if top else reference_missing.NAN_RIGHT
    # node 0 parts a few rows (the lowest values of column 0) from the
    # rest; both children cut column c at the planted bound
    tree = {"num_leaves": 4, "split_feature": np.array([0, c, c]),
            "threshold": np.array([bounds[0][0], bounds[c][t],
                                   bounds[c][t]]),
            "left_child": np.array([1, -1, -3]),
            "right_child": np.array([2, -2, -4])}
    small = reference_missing.node_rows(
        tree, np.array([reference_missing.NAN_RIGHT] * 3), X, 1)
    assert 0 < len(small) < len(X) // 4
    for first, second, want in ((planted ^ 2, planted, True),
                                (planted, planted ^ 2, False)):
        dt = np.array([reference_missing.NAN_RIGHT, first, second])
        assert reference_missing.planted_found(
            tree, dt, steps, bounds, X) == [(2, want)]


def test_the_bosch_layout_is_the_source_shape():
    """968 columns over 4 lines of 24, 2, 3 and 23 stations, about 81%
    NaN, a station's columns present together; ``--seed`` draws the order
    only, and the kept positions stay."""
    t = bosch.layout()
    assert len(t["station"]) == 968 and len(t["line"]) == 52
    assert np.bincount(t["line"]).tolist() == [24, 2, 3, 23]
    assert np.bincount(t["line"][t["station"]]).tolist() == [168, 513, 42,
                                                               245]
    spec = {"features": 968, "sample_seed": 0}
    keep = np.array([3, 50, 777])
    Xa, ya = bosch.make(spec, 5, 3000, 2500, keep=keep)
    Xb, yb = bosch.make(spec, 6, 3000, 2500, keep=keep)
    assert 0.78 < np.isnan(Xa).mean() < 0.84
    assert np.array_equal(Xa[keep], Xb[keep], equal_nan=True)
    assert np.array_equal(Xa[2500:], Xb[2500:], equal_nan=True)
    assert not np.array_equal(Xa[:2500], Xb[:2500], equal_nan=True)
    nan = np.isnan(Xa)
    for s in range(52):
        cols = np.flatnonzero(t["station"] == s)
        assert (nan[:, cols] == nan[:, cols[:1]]).all()
