"""Differential tests: jitted grower vs brute-force numpy oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
from lightgbm_tpu.models.grower import grow_tree
from lightgbm_tpu.models.tree import predict_leaf_bins

from reference_impl import grow_tree_reference


def _make_params(l1=0.0, l2=0.0, min_data=1, min_hess=1e-3, min_gain=0.0):
    f32 = jnp.float32
    return SplitParams(
        lambda_l1=f32(l1), lambda_l2=f32(l2), max_delta_step=f32(0.0),
        path_smooth=f32(0.0), min_data_in_leaf=f32(min_data),
        min_sum_hessian_in_leaf=f32(min_hess), min_gain_to_split=f32(min_gain),
        cat_l2=f32(10.0), cat_smooth=f32(10.0),
        max_cat_threshold=jnp.int32(32), min_data_per_group=f32(100.0),
        max_cat_to_onehot=jnp.int32(4), monotone_penalty=f32(0.0),
        cegb_tradeoff=f32(1.0), cegb_penalty_split=f32(0.0))


def _make_meta(num_bins, missing_types=None, default_bins=None):
    f = len(num_bins)
    nb = np.asarray(num_bins, dtype=np.int32)
    mt = np.asarray(missing_types if missing_types is not None else np.zeros(f),
                    dtype=np.int32)
    db = np.asarray(default_bins if default_bins is not None else np.zeros(f),
                    dtype=np.int32)
    mode_a = (nb > 2) & (mt != 0)
    missing_bin = np.where(mode_a & (mt == 2), nb - 1,
                           np.where(mode_a & (mt == 1), db, -1)).astype(np.int32)
    meta = FeatureMeta(
        num_bins=jnp.asarray(nb), missing_type=jnp.asarray(mt),
        default_bin=jnp.asarray(db),
        is_categorical=jnp.zeros((f,), dtype=bool),
        monotone=jnp.zeros((f,), dtype=jnp.int8),
        penalty=jnp.ones((f,), dtype=jnp.float32))
    return meta, missing_bin


def _run_both(bins, grad, hess, num_bins_per_feat, num_leaves, seed_missing=None,
              l1=0.0, l2=0.0, min_data=1, min_hess=1e-3, min_gain=0.0,
              hist_method="scatter", exact=True):
    """exact=True matches the oracle's strict best-first order even when the
    num_leaves budget binds (the batched mode deliberately deviates there)."""
    n, f = bins.shape
    mt = seed_missing if seed_missing is not None else np.zeros(f, dtype=np.int32)
    meta, missing_bin = _make_meta(num_bins_per_feat, mt)
    params = _make_params(l1, l2, min_data, min_hess, min_gain)
    B = int(max(num_bins_per_feat))
    tree, leaf_id, _aux = grow_tree(
        jnp.asarray(bins.astype(np.uint8)), jnp.asarray(grad, dtype=jnp.float32),
        jnp.asarray(hess, dtype=jnp.float32), jnp.ones((n,), dtype=jnp.float32),
        meta, params, jnp.ones((f,), dtype=jnp.float32),
        jnp.asarray(missing_bin),
        max_leaves=num_leaves, num_bins=B, hist_method=hist_method, exact=exact)
    ref_leaf, ref_values, ref_splits = grow_tree_reference(
        bins, grad.astype(np.float64), hess.astype(np.float64),
        num_bins_per_feat, mt, np.zeros(f, dtype=np.int64), missing_bin,
        num_leaves, l1, l2, min_data, min_hess, min_gain)
    return tree, np.asarray(leaf_id), ref_leaf, ref_values, ref_splits


def _partition_signature(leaf_id):
    """Order-independent signature: map rows -> canonical leaf label."""
    _, canon = np.unique(leaf_id, return_inverse=True)
    # canonicalize by first occurrence order
    first_seen = {}
    out = np.empty_like(leaf_id)
    nxt = 0
    for i, l in enumerate(leaf_id):
        if l not in first_seen:
            first_seen[l] = nxt
            nxt += 1
        out[i] = first_seen[l]
    return out


@pytest.mark.parametrize("hist_method", ["scatter", "binloop"])
def test_single_split_exact(hist_method):
    rng = np.random.RandomState(0)
    n = 200
    bins = rng.randint(0, 8, size=(n, 3))
    # target correlated with feature 0
    grad = (bins[:, 0] < 4).astype(np.float64) * 2 - 1
    hess = np.ones(n)
    tree, leaf_id, ref_leaf, ref_values, ref_splits = _run_both(
        bins, grad, hess, [8, 8, 8], num_leaves=2, hist_method=hist_method)
    assert int(tree.num_leaves) == 2
    assert len(ref_splits) == 1
    assert int(tree.node_feature[0]) == ref_splits[0][1]
    assert int(tree.node_threshold_bin[0]) == ref_splits[0][2]
    np.testing.assert_array_equal(_partition_signature(leaf_id),
                                  _partition_signature(ref_leaf))


@pytest.mark.parametrize("num_leaves", [4, 8, 16])
def test_multi_split_partition_matches_oracle(num_leaves):
    rng = np.random.RandomState(1)
    n, f = 500, 5
    bins = rng.randint(0, 16, size=(n, f))
    grad = rng.normal(size=n)
    hess = np.ones(n)
    tree, leaf_id, ref_leaf, ref_values, _ = _run_both(
        bins, grad, hess, [16] * f, num_leaves=num_leaves)
    assert int(tree.num_leaves) == len(ref_values)
    np.testing.assert_array_equal(_partition_signature(leaf_id),
                                  _partition_signature(ref_leaf))


def test_leaf_values_match_oracle():
    rng = np.random.RandomState(2)
    n, f = 400, 4
    bins = rng.randint(0, 10, size=(n, f))
    grad = rng.normal(size=n)
    hess = np.ones(n) + rng.uniform(size=n)
    tree, leaf_id, ref_leaf, ref_values, _ = _run_both(
        bins, grad, hess, [10] * f, num_leaves=6, l2=1.0)
    # match leaf values by row partition: for each jit leaf, find ref leaf of
    # its rows and compare values
    lv = np.asarray(tree.leaf_value)
    for leaf in np.unique(leaf_id):
        rows = leaf_id == leaf
        ref_leaves = np.unique(ref_leaf[rows])
        assert len(ref_leaves) == 1
        np.testing.assert_allclose(lv[leaf], ref_values[int(ref_leaves[0])],
                                   rtol=2e-4, atol=1e-6)


def test_min_data_in_leaf_respected():
    rng = np.random.RandomState(3)
    n = 300
    bins = rng.randint(0, 16, size=(n, 3))
    grad = rng.normal(size=n)
    hess = np.ones(n)
    min_data = 30
    tree, leaf_id, ref_leaf, ref_values, _ = _run_both(
        bins, grad, hess, [16] * 3, num_leaves=16, min_data=min_data)
    counts = np.bincount(leaf_id, minlength=int(tree.num_leaves))
    active = counts[:int(tree.num_leaves)]
    assert active.min() >= min_data
    assert int(tree.num_leaves) == len(ref_values)


def test_lambda_l1_l2_match_oracle():
    rng = np.random.RandomState(4)
    n = 400
    bins = rng.randint(0, 12, size=(n, 4))
    grad = rng.normal(size=n)
    hess = np.ones(n)
    tree, leaf_id, ref_leaf, ref_values, _ = _run_both(
        bins, grad, hess, [12] * 4, num_leaves=8, l1=0.5, l2=2.0, min_data=10)
    np.testing.assert_array_equal(_partition_signature(leaf_id),
                                  _partition_signature(ref_leaf))


def test_nan_missing_routing():
    rng = np.random.RandomState(5)
    n = 400
    nb = 10  # last bin (9) is the NaN bin
    bins = rng.randint(0, 9, size=(n, 2))
    nan_rows = rng.uniform(size=n) < 0.2
    bins[nan_rows, 0] = 9
    # make NaN rows strongly negative-gradient so routing matters
    grad = rng.normal(size=n)
    grad[nan_rows] -= 3.0
    hess = np.ones(n)
    mt = np.array([2, 0], dtype=np.int32)  # feature 0 has NaN missing
    tree, leaf_id, ref_leaf, ref_values, ref_splits = _run_both(
        bins, grad, hess, [nb, 9], num_leaves=4, seed_missing=mt)
    np.testing.assert_array_equal(_partition_signature(leaf_id),
                                  _partition_signature(ref_leaf))


def test_predict_leaf_consistency():
    """Traversal on the tree must reproduce the training partition."""
    rng = np.random.RandomState(6)
    n = 500
    bins = rng.randint(0, 16, size=(n, 4)).astype(np.uint8)
    grad = rng.normal(size=n)
    hess = np.ones(n)
    meta, missing_bin = _make_meta([16] * 4)
    params = _make_params(min_data=5)
    tree, leaf_id, _aux = grow_tree(
        jnp.asarray(bins), jnp.asarray(grad, dtype=jnp.float32),
        jnp.asarray(hess, dtype=jnp.float32), jnp.ones((n,), jnp.float32),
        meta, params, jnp.ones((4,), jnp.float32), jnp.asarray(missing_bin),
        max_leaves=8, num_bins=16)
    leaves = predict_leaf_bins(tree, jnp.asarray(bins), jnp.asarray(missing_bin))
    np.testing.assert_array_equal(np.asarray(leaves), np.asarray(leaf_id))


def test_batched_equals_exact_when_budget_not_binding():
    """Batched-round growth produces the identical tree when every positive-
    gain split fits in the budget (order independence; grower docstring)."""
    rng = np.random.RandomState(8)
    n = 300
    bins = rng.randint(0, 8, size=(n, 3))
    grad = rng.normal(size=n)
    hess = np.ones(n)
    # min_data large => tree terminates naturally well below num_leaves
    te, le, rl, rv, _ = _run_both(bins, grad, hess, [8] * 3, num_leaves=64,
                                  min_data=40, exact=True)
    tb, lb, _, _, _ = _run_both(bins, grad, hess, [8] * 3, num_leaves=64,
                                min_data=40, exact=False)
    assert int(te.num_leaves) == int(tb.num_leaves) == len(rv)
    np.testing.assert_array_equal(_partition_signature(le),
                                  _partition_signature(lb))
    np.testing.assert_array_equal(_partition_signature(le),
                                  _partition_signature(rl))


def test_no_split_when_constant_gradient_zero():
    n = 100
    bins = np.random.RandomState(7).randint(0, 8, size=(n, 2))
    grad = np.zeros(n)
    hess = np.ones(n)
    tree, leaf_id, ref_leaf, ref_values, _ = _run_both(
        bins, grad, hess, [8, 8], num_leaves=8)
    assert int(tree.num_leaves) == 1
    assert np.all(leaf_id == 0)


def test_bagging_subset_matches_mask():
    """grow_tree with a compacted bagging subset (sub_idx/sub_bins) must
    grow the identical tree as the mask formulation over the same selected
    rows (gbdt.cpp:810-818 subset copy semantics)."""
    rng = np.random.RandomState(23)
    n, f, b = 1200, 5, 16
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    hess = np.ones(n, dtype=np.float32)
    sel = rng.uniform(size=n) < 0.4
    sub_idx = np.nonzero(sel)[0].astype(np.int32)
    meta, missing_bin = _make_meta([b] * f)
    params = _make_params(min_data=5)

    common = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess))
    tree_m, leaf_m, _ = grow_tree(
        *common, jnp.asarray(sel.astype(np.float32)), meta, params,
        jnp.ones((f,), jnp.float32), jnp.asarray(missing_bin),
        max_leaves=8, num_bins=b)
    sub_bins = jnp.asarray(bins[sub_idx])
    tree_s, leaf_s, _ = grow_tree(
        *common, jnp.ones((n,), jnp.float32), meta, params,
        jnp.ones((f,), jnp.float32), jnp.asarray(missing_bin),
        max_leaves=8, num_bins=b,
        sub_idx=jnp.asarray(sub_idx), sub_bins=sub_bins,
        sub_binsT=jnp.asarray(np.ascontiguousarray(bins[sub_idx].T)))
    assert int(tree_m.num_leaves) == int(tree_s.num_leaves)
    np.testing.assert_array_equal(np.asarray(tree_m.node_feature),
                                  np.asarray(tree_s.node_feature))
    np.testing.assert_array_equal(np.asarray(tree_m.node_threshold_bin),
                                  np.asarray(tree_s.node_threshold_bin))
    np.testing.assert_allclose(np.asarray(tree_m.leaf_value),
                               np.asarray(tree_s.leaf_value),
                               rtol=1e-5, atol=1e-7)
    # full-row routing agrees (out-of-bag rows included in the score update)
    np.testing.assert_array_equal(np.asarray(leaf_m), np.asarray(leaf_s))


# ----------------------------------------------------------------- routing

def _np_goes_left(col, thr, dleft, mb, is_cat, bitset, seg_lo, seg_hi):
    """The general route of one split, in numpy: missing / threshold, the
    EFB segment test, the categorical bitset."""
    left = np.where((col == mb) & (mb >= 0), dleft, col <= thr)
    if seg_lo >= 0:
        left = np.where((col >= seg_lo) & (col <= seg_hi), col <= thr, dleft)
    if is_cat:
        left = ((bitset[col >> 5] >> (col & 31).astype(np.uint32)) & 1) == 1
    return left


ROUTE_CASES = [
    # what the data set has -> the statics the grower hands _apply_split
    pytest.param(dict(), id="numerical-only"),
    pytest.param(dict(bundle=True), id="bundle"),
    pytest.param(dict(categorical=True), id="categorical-and-numerical"),
    pytest.param(dict(bundle=True, categorical=True), id="bundle-categorical"),
    pytest.param(dict(sparse=True), id="sparse-columns"),
    pytest.param(dict(subset=True), id="bagging-subset"),
    pytest.param(dict(row_major=True), id="row-major-bins"),
]


@pytest.mark.parametrize("exact", [False, True], ids=["loop", "exact"])
@pytest.mark.parametrize("case", ROUTE_CASES)
def test_specialised_route_equals_the_general_route(monkeypatch, case, exact):
    """``_apply_split`` traces only the routing tests the data set can
    need (``with_categorical``, ``with_bundle``; the split loop carries
    the leaf ids as [1, N]). For every combination the rows land where the
    general route, forced through the same phase, puts them, and where a
    numpy transcription of it does: random bins, thresholds, missing bins
    present and absent, ``default_left`` both ways, five leaves split in
    one phase (categorical and numerical splits mixed in one tree)."""
    import jax
    from lightgbm_tpu.models import grower
    from lightgbm_tpu.ops.split import BundleMeta

    rng = np.random.RandomState(11)
    n, f, B, L, k = 2500, 6, 64, 16, 5
    bins = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    bins[rng.rand(n, f) < 0.2] = B - 1            # rows in the NaN bin
    meta, _ = _make_meta([B] * f)
    missing_bin = np.asarray([-1, B - 1, 0, -1, B - 1, 7], np.int32)
    leaf_id = rng.randint(0, k, size=n).astype(np.int32)

    categorical, bundle = case.get("categorical"), case.get("bundle")
    zf = np.zeros((L,), np.float32)
    gain = np.full((L,), -np.inf, np.float32)
    gain[:k] = rng.permutation(k) + 1.0
    feature = rng.randint(0, f, size=L).astype(np.int32)
    feature[:k] = [1, 0, 4, 2, 5]        # both sparse columns, every missing kind
    threshold = rng.randint(0, B - 1, size=L).astype(np.int32)
    default_left = rng.rand(L) < 0.5
    default_left[:2] = [True, False]
    is_cat = np.zeros((L,), bool)
    bitset = np.zeros((L, 2), np.uint32)
    seg_lo = np.full((L,), -1, np.int32)
    seg_hi = np.full((L,), -1, np.int32)
    if categorical:
        is_cat[[0, 2, 3]] = True
        bitset[is_cat] = rng.randint(0, 2 ** 32, size=(3, 2), dtype=np.uint64)
    if bundle:
        seg_lo[[1, 3, 4]] = [5, 20, 0]
        seg_hi[[1, 3, 4]] = [30, 41, 63]
        threshold[[1, 3, 4]] = [17, 33, 12]
    best = grower.SplitInfo(
        gain=gain, feature=feature, threshold=threshold,
        default_left=default_left, left_sum_g=zf, left_sum_h=zf,
        left_count=zf, right_sum_g=zf, right_sum_h=zf, right_count=zf,
        left_output=zf, right_output=zf, is_cat=is_cat, cat_bitset=bitset,
        seg_lo=seg_lo, seg_hi=seg_hi)

    kw = dict(max_leaves=L, num_bins=B, hist_method="scatter", exact=exact,
              with_categorical=bool(categorical))
    dense = bins
    if bundle:
        fb = np.zeros((f, B), np.int32)
        kw["bundle_meta"] = BundleMeta(fb, fb, np.zeros((f,), bool),
                                       fb > 0, fb > 0, fb, fb)
    if case.get("sparse"):
        # the layout of Dataset._maybe_extract_sparse, by hand: one
        # concatenation, stream i the entries [offsets[i], offsets[i + 1])
        # with cell = i * B + bin, the widest stream last
        sp_cols = (4, 1)
        dense = np.delete(bins, sp_cols, axis=1)
        sp_default = np.asarray([B - 1, 3], np.int32)
        sp_rows, sp_cell = [], []
        for j, (c, s) in enumerate(zip(sp_cols, (900, 1400))):
            rows = np.flatnonzero(bins[:, c] != sp_default[j])[:s]
            bins[:, c] = sp_default[j]
            bins[rows, c] = rng.randint(0, B, size=rows.size)
            sp_rows.append(rows)
            sp_cell.append(j * B + bins[rows, c].astype(np.int32))
        kw.update(sp_cols=sp_cols, sp_offsets=(0, 900, 2300),
                  sp_rows=jnp.asarray(np.concatenate(sp_rows), jnp.int32),
                  sp_cell=jnp.asarray(np.concatenate(sp_cell), jnp.int32),
                  sp_default=jnp.asarray(sp_default))
    if not case.get("row_major"):
        kw["binsT"] = np.ascontiguousarray(dense.T)
    sub_idx = None
    if case.get("subset"):
        sub_idx = np.sort(rng.choice(n, size=900, replace=False)).astype(
            np.int32)
        kw.update(sub_idx=sub_idx, sub_bins=dense[sub_idx],
                  sub_binsT=np.ascontiguousarray(dense[sub_idx].T))

    def phase(leaf, leaf_sub, best):
        fns = grower._grower_fns(
            jnp.asarray(dense), jnp.ones((n,)), jnp.ones((n,)),
            jnp.ones((n,)), meta, _make_params(), jnp.ones((f,)),
            jnp.asarray(missing_bin), **kw)
        st = fns["init_state"]()._replace(
            leaf_id=leaf, leaf_id_sub=leaf_sub, num_leaves=jnp.int32(k),
            hist_valid=jnp.arange(L) < k,
            best=grower.SplitInfo(*(jnp.asarray(a) for a in best)))
        out = fns["split_apply"](st)
        return (out.leaf_id, out.leaf_id_sub, out.num_leaves,
                out.tree.node_feature, out.tree.node_cat,
                out.tree.node_seg_lo)

    leaf_sub = leaf_id[sub_idx] if sub_idx is not None else np.zeros(
        (1,), np.int32)
    got = jax.jit(phase)(leaf_id, leaf_sub, best)

    seen = []
    real = grower._apply_split

    def general(*a, **akw):
        seen.append((akw["with_categorical"], akw["with_bundle"]))
        return real(*a, **{**akw, "with_categorical": True,
                           "with_bundle": True})

    monkeypatch.setattr(grower, "_apply_split", general)
    # a new callable: jit would answer for ``phase`` from its cache
    want = jax.jit(lambda *a: phase(*a))(leaf_id, leaf_sub, best)
    assert set(seen) == {(bool(categorical), bool(bundle))}
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    # and the numpy transcription, split by split in gain order
    ref, new_leaf = leaf_id.copy(), k
    for l in (np.argsort(-gain[:k])[:1] if exact else np.argsort(-gain[:k])):
        left = _np_goes_left(
            bins[:, feature[l]].astype(np.int32), threshold[l],
            default_left[l], missing_bin[feature[l]], is_cat[l], bitset[l],
            seg_lo[l], seg_hi[l])
        ref = np.where((ref == l) & ~left, new_leaf, ref)
        new_leaf += 1
    assert int(got[2]) == new_leaf
    np.testing.assert_array_equal(np.asarray(got[0]), ref)
    if sub_idx is not None:
        np.testing.assert_array_equal(np.asarray(got[1]), ref[sub_idx])
