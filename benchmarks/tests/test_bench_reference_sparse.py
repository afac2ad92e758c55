"""reference_sparse.py against a dense float64 brute force on a few hundred
rows, and the Expo generator's contract: fixed by ``sample_seed``, ``--seed``
permutes rows only, no dense [rows, features] array. Run by hand:
``JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q``."""

import os
import sys
import tracemalloc

import numpy as np
import pytest
import scipy.sparse as sp

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import reference            # noqa: E402
import reference_sparse     # noqa: E402
from data import expo       # noqa: E402

SPEC = {
    "features": 60, "sample_seed": 3, "label_bias": -1.5,
    "time_effect": 1.6, "distance_effect": 0.1,
    "groups": [
        {"name": "month", "size": 12, "exponent": 0.05, "effect": 0.3},
        {"name": "carrier", "size": 16, "exponent": 1.0, "offset": 2.0,
         "effect": 0.5},
        {"name": "origin", "size": 30, "exponent": 1.5, "offset": 2.0,
         "effect": 0.5}]}


def _bounds(X):
    """Bin upper bounds a column: midpoints between its distinct values
    (zero among them), the last +inf."""
    out = []
    for j in range(X.shape[1]):
        v = np.unique(np.append(X[:, j], 0.0))
        out.append(None if len(v) < 2
                   else np.append((v[:-1] + v[1:]) / 2.0, np.inf))
    return out


def _brute_root(X, y, bounds, min_data, min_hess):
    """Every (column, bound) tried on the dense matrix."""
    n = len(y)
    p0, h0 = reference.binary_root_stats(y)
    g, h = p0 - y.astype(np.float64), np.full(n, h0)
    best = (-np.inf, -1, np.nan, -1)
    for j in range(X.shape[1]):
        if bounds[j] is None:
            continue
        for t in bounds[j][:-1]:
            left = X[:, j] <= t
            cl, cr = left.sum(), n - left.sum()
            hl, hr = h[left].sum() + reference_sparse.K_EPSILON, \
                h[~left].sum()
            if min(cl, cr) < min_data or min(hl, hr) < min_hess:
                continue
            gain = g[left].sum() ** 2 / hl + g[~left].sum() ** 2 / hr
            if gain > best[0]:
                best = (float(gain), j, float(t), int(cl))
    return best


def test_root_split_equals_a_dense_brute_force():
    Xs, y = expo.make(SPEC, 0, 400, 400)
    X = Xs.toarray().astype(np.float64)
    bounds = _bounds(X)
    got = reference_sparse.root_split(Xs.tocsc(), y, bounds, 5, 1e-3)
    want = _brute_root(X, y, bounds, 5, 1e-3)
    assert got[1:] == want[1:]
    assert abs(got[0] - want[0]) <= 1e-9 * want[0]
    gain, left = reference_sparse.gain_of_raw_split(
        Xs.tocsc(), got[1], y, got[2], 5, 1e-3)
    assert left == got[3] and abs(gain - got[0]) <= 1e-9 * got[0]


def test_leaf_counts_equal_a_dense_traversal():
    Xs, _y = expo.make(SPEC, 0, 500, 500)
    X = Xs.toarray().astype(np.float64)
    # a hand-made tree over a numeric, a common and a rare column
    tree = {"num_leaves": 5,
            "split_feature": np.array([58, 0, 30, 59]),
            "threshold": np.array([900.0, 0.5, 0.5, 500.0]),
            "left_child": np.array([1, -1, -3, -4]),
            "right_child": np.array([2, -2, 3, -5])}
    got = reference_sparse.leaf_counts(tree, Xs.tocsc())
    leaf = reference_sparse.leaf_index(tree, Xs.tocsc())
    node = np.zeros(len(X), dtype=np.int64)
    while (node >= 0).any():
        live = node >= 0
        nd = node[live]
        left = X[live, tree["split_feature"][nd]] <= tree["threshold"][nd]
        node[live] = np.where(left, tree["left_child"][nd],
                              tree["right_child"][nd])
    want = np.bincount(~node, minlength=5)
    assert got.sum() == 500 and (got == want).all()
    assert (got > 0).sum() >= 4
    assert (leaf == ~node).all()
    # tree 0's leaf values: the average's log-odds plus lr x -G / H over
    # the leaf's rows, by a loop
    p0 = float(np.mean(_y, dtype=np.float64))
    values = reference_sparse.leaf_values(tree, leaf, _y, 0.1)
    for k in range(5):
        yk = _y[leaf == k].astype(np.float64)
        g, h = (p0 - yk).sum(), p0 * (1 - p0) * len(yk)
        assert abs(values[k] - (np.log(p0 / (1 - p0)) - 0.1 * g / h)) < 1e-12


def test_tree_field_reads_the_lines_the_parser_leaves_out():
    text = ("tree\nversion=v3\n\nTree=0\nnum_leaves=2\nsplit_gain=12.5\n"
            "leaf_weight=3 4.5\nshrinkage=0.1\n\nTree=1\nnum_leaves=2\n"
            "split_gain=7\nshrinkage=0.1\n\nend of trees\n")
    assert reference_sparse.tree_field(text, 0, "leaf_weight").tolist() \
        == [3.0, 4.5]
    assert reference_sparse.tree_field(text, 1, "split_gain").tolist() == [7.0]
    with pytest.raises(KeyError):
        reference_sparse.tree_field(text, 1, "leaf_weight")


def test_kept_positions_keep_their_rows():
    keep = np.array([3, 40, 41, 499])
    plain, yp = expo.make(SPEC, 11, 700, 0)
    a, ya = expo.make(SPEC, 11, 700, 500, keep=keep)
    b, yb = expo.make(SPEC, 2 ** 31 + 5, 700, 500, keep=keep)
    for m, y in ((a, ya), (b, yb)):
        assert (m[keep] != plain[keep]).nnz == 0 and (y[keep] == yp[keep]).all()
        assert (m[500:] != plain[500:]).nnz == 0
        assert sorted(y[:500].tolist()) == sorted(yp[:500].tolist())
    assert (a[:500] != b[:500]).nnz > 0


def test_generator_is_fixed_by_sample_seed_and_permuted_by_seed():
    a, ya = expo.make(SPEC, 11, 700, 500)
    b, yb = expo.make(SPEC, 2 ** 31 + 5, 700, 500)
    assert sp.issparse(a) and a.format == "csr" and a.dtype == np.float32
    assert a.shape == (700, 60) and (a.getnnz(axis=1) == 5).all()
    # the held-out rows keep their place; the shuffled rows are the same
    # multiset in another order
    assert (a[500:] != b[500:]).nnz == 0 and (ya[500:] == yb[500:]).all()
    assert (a[:500] != b[:500]).nnz > 0
    key = lambda m, y: sorted(map(tuple, np.column_stack(      # noqa: E731
        [m.toarray(), y]).tolist()))
    assert key(a[:500], ya[:500]) == key(b[:500], yb[:500])
    c, yc = expo.make({**SPEC, "sample_seed": 4}, 11, 700, 500)
    assert (a != c).nnz > 0
    a2, ya2 = expo.make(SPEC, 11, 700, 500)
    assert (a != a2).nnz == 0 and (ya == ya2).all()


def test_generator_allocates_no_dense_matrix():
    rows, feats = 200_000, 700
    spec = {**SPEC, "features": feats, "groups": SPEC["groups"] + [
        {"name": "destination", "size": feats - 60, "exponent": 1.5,
         "offset": 2.0, "effect": 0.2}]}
    tracemalloc.start()
    X, _y = expo.make(spec, 1, rows, rows)
    peak = tracemalloc.get_traced_memory()[1]
    tracemalloc.stop()
    assert X.shape == (rows, feats)
    # a dense float32 matrix would be 560 MB; the CSR arrays and their
    # shuffled copies are 6 non-zeros x 8 bytes x 2 a row
    assert peak < rows * feats * 4 / 8, peak
