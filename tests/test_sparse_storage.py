"""Sparse device storage (reference: sparse_bin.hpp SparseBin chosen at
sparse_rate > kSparseThreshold, bin.h:39; most_freq elision reconstructed by
FixHistogram, dataset.h:506). Here a >=90%-concentrated device column drops
out of the dense [N, F] matrix into a (row, bin) stream, all streams in one
concatenation without padding; histogram planes scatter O(nnz) entries and
reconstruct the elided default bin from per-leaf totals.

Parity model: counts are EXACT and the column reconstruction is bit-exact
(asserted at unit level below); grad/hess sums differ from the dense path
only by f32 accumulation ORDER (the default-bin cell is total minus
non-default instead of a direct sum), so near-tied split gains can resolve
differently — exactly the tolerance the reference accepts between its own
dense/sparse and CPU/GPU paths (test_dual.py score-parity, not bit-parity).
End-to-end tests therefore assert quality parity, unit tests exactness."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.models import grower

from test_grower import _make_meta, _make_params, _np_goes_left


def _sparse_frame(rng, n=2000, dense_f=4, sparse_f=3, nnz_frac=0.04):
    """dense continuous columns + heavily-concentrated columns whose
    non-default entries are informative."""
    X = rng.normal(size=(n, dense_f + sparse_f)).astype(np.float64)
    for j in range(dense_f, dense_f + sparse_f):
        col = np.zeros(n)
        nz = rng.choice(n, int(n * nnz_frac), replace=False)
        col[nz] = rng.normal(size=len(nz)) + 2.0
        X[:, j] = col
    y = ((X[:, 0] + 3.0 * (X[:, dense_f] > 0) + 0.5 * X[:, 1]) > 0.5)
    return X, y.astype(np.float64)


def _fit(X, y, enable_sparse, extra=None, rounds=8):
    params = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
              "enable_sparse": enable_sparse, "enable_bundle": False,
              "histogram_method": "scatter", "verbosity": -1}
    params.update(extra or {})
    ds = lgb.Dataset(X, label=y, params=params)
    booster = lgb.train(params, ds, rounds)
    return ds, booster


def _acc(b, X, y):
    return float(np.mean((b.predict(X) > 0.5) == (y > 0.5)))


def _stream_kw(ds):
    """A data set's streams as ``grower._grower_fns`` takes them."""
    return dict(sp_cols=tuple(int(c) for c in ds.sp_cols),
                sp_offsets=tuple(int(o) for o in ds.sp_offsets),
                sp_rows=ds.sp_rows, sp_cell=ds.sp_cell,
                sp_default=ds.sp_default)


def _check_planes(bins_d, dense, kw, B, rng, L=8):
    """One histogram pass of the grower over five leaves (``hist_phase``:
    the dense planes, then ``combine_sparse``) against a float64
    ``np.bincount`` of every RAW column by leaf: the count channel exact,
    gradients and hessians to the float32 accumulation order (the default
    cell is the leaf's total less the stream's entries). A third of the
    rows is out of the bag: ``stats`` carry the mask. The tile of the
    scatter backend has a slot a leaf of the budget ``L``: up to 64 slots
    an entry finds its own by compares, past that by a lookup table."""
    n, f = bins_d.shape
    k = 5
    leaf = rng.randint(0, k, n).astype(np.int32)
    grad = rng.normal(size=n).astype(np.float32)
    hess = np.abs(rng.normal(size=n)).astype(np.float32)
    mask = (rng.uniform(size=n) < 0.67).astype(np.float32)
    meta, missing_bin = _make_meta([B] * f)

    def phase(leaf):
        fns = grower._grower_fns(
            jnp.asarray(dense), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask), meta, _make_params(), jnp.ones((f,)),
            jnp.asarray(missing_bin), max_leaves=L, num_bins=B,
            hist_method="scatter", **kw)
        st = fns["init_state"]()._replace(leaf_id=leaf,
                                          num_leaves=jnp.int32(k))
        return fns["hist_phase"](st).hist

    got = np.asarray(jax.jit(phase)(leaf))[:k]
    for c in range(f):
        key = leaf.astype(np.int64) * B + bins_d[:, c]
        for ch, w in enumerate((grad * mask, hess * mask, mask)):
            want = np.bincount(key, weights=w.astype(np.float64),
                               minlength=k * B).reshape(k, B)
            if ch == 2:
                np.testing.assert_array_equal(got[:, c, :, ch], want)
            else:
                np.testing.assert_allclose(got[:, c, :, ch], want,
                                           atol=5e-4, rtol=1e-5)


def test_sparse_reconstruction_and_histogram_exactness(rng):
    """Unit anchors: (a) every sparse column reconstructs bit-exactly from
    its stream; (b) the grower's planes for the stream columns match a
    float64 count of the dense columns exactly on counts and to f32
    accumulation-order tolerance on grads."""
    X, y = _sparse_frame(rng)
    common = {"objective": "binary", "enable_bundle": False,
              "verbosity": -1}
    ds_d = lgb.Dataset(X, label=y, params={**common,
                                           "enable_sparse": False})
    ds_d.construct()
    ds_s = lgb.Dataset(X, label=y, params={**common, "enable_sparse": True})
    ds_s.construct()
    assert ds_s.has_sparse_cols and len(ds_s.sp_cols) >= 2
    n = len(X)
    bins_d = np.asarray(ds_d.bins)
    sp_def = np.asarray(ds_s.sp_default)
    for i, c in enumerate(ds_s.sp_cols):
        col = np.full(n, sp_def[i], np.int64)
        rows, vals = ds_s.stream_column(i)
        col[rows] = vals
        np.testing.assert_array_equal(col, bins_d[:, c].astype(np.int64))
    _check_planes(bins_d.astype(np.int64), np.asarray(ds_s.bins),
                  _stream_kw(ds_s), ds_d.max_num_bins, rng, L=80)


# entries of each stream column, in COLUMN order (the columns after two
# dense ones): what a slice clamped at the arrays' end, a mask one entry
# off or a stream out of its place would get wrong
STREAM_WIDTHS = [
    pytest.param((3, 30, 300), id="1-10-100"),
    pytest.param((1, 40, 200), id="one-entry"),
    pytest.param((300, 30, 3), id="widest-first"),
    pytest.param((50, 200, 50), id="equal-width"),
]


def _stream_set(widths, rng, n=3000, B=16):
    """A bin matrix of two dense columns and one stream column a width,
    with the storage ``Dataset._maybe_extract_sparse`` gives it. A
    stream's last entry holds the top bin, above every default."""
    bins = rng.randint(0, B, size=(n, 2 + len(widths))).astype(np.uint8)
    for j, w in enumerate(widths):
        default = (5 * j + 2) % B
        bins[:, 2 + j] = default
        rows = rng.choice(n, w, replace=False)
        bins[rows, 2 + j] = (default + rng.randint(1, B, size=w)) % B
        bins[rows.max(), 2 + j] = B - 1
    ds = lgb.Dataset(np.zeros((1, 1)))
    ds.max_num_bins = B
    dense = ds._maybe_extract_sparse(bins, Config())
    return bins, dense, ds


@pytest.mark.parametrize("widths", STREAM_WIDTHS)
def test_stream_layout_holds_real_entries_only(widths, rng):
    """The streams lie in one concatenation, ascending by length with the
    widest last, every stream's rows ascending, no slot without an entry;
    ``stream_column`` gives each column back."""
    bins, dense, ds = _stream_set(widths, rng)
    n = len(bins)
    assert sorted(ds.sp_cols.tolist()) == [2 + j for j in range(len(widths))]
    lengths = np.diff(ds.sp_offsets)
    assert lengths.tolist() == sorted(widths)
    assert [widths[c - 2] for c in ds.sp_cols] == lengths.tolist()
    assert ds.sp_offsets[0] == 0 and ds.sp_offsets[-1] == sum(widths) \
        == ds.sp_rows.shape[0] == ds.sp_cell.shape[0]
    np.testing.assert_array_equal(dense, bins[:, :2])
    for i, c in enumerate(ds.sp_cols):
        rows, vals = ds.stream_column(i)
        assert (np.diff(rows) > 0).all() and 0 <= rows.min() \
            and rows.max() < n
        col = np.full(n, int(ds.sp_default[i]))
        col[rows] = vals
        np.testing.assert_array_equal(col, bins[:, c])


@pytest.mark.parametrize("widths", STREAM_WIDTHS)
def test_stream_planes_equal_a_float64_count(widths, rng):
    """``combine_sparse``'s planes for streams of very unequal width, of
    one entry, with the widest first in column order and of equal width."""
    bins, dense, ds = _stream_set(widths, rng)
    _check_planes(bins.astype(np.int64), dense, _stream_kw(ds), 16, rng)


@pytest.mark.parametrize("widths", STREAM_WIDTHS)
def test_every_stream_column_routes_as_its_dense_column(widths, rng):
    """A split on EVERY stream column (and one on a dense column between
    them) sends the rows where the raw column sends them: the narrowest
    stream is the one a clamped slice or a mask that lets the next
    stream's entries through would misroute."""
    B, L = 16, 16
    bins, dense, ds = _stream_set(widths, rng)
    n, f = bins.shape
    k = f - 1                       # one leaf a stream column + a dense one
    feature = np.zeros((L,), np.int32)
    feature[:k] = [2 + j for j in range(len(widths))] + [1]
    gain = np.full((L,), -np.inf, np.float32)
    gain[:k] = rng.permutation(k) + 1.0
    threshold = rng.randint(0, B - 1, size=L).astype(np.int32)
    default_left = rng.rand(L) < 0.5
    zf = np.zeros((L,), np.float32)
    none = np.full((L,), -1, np.int32)
    best = grower.SplitInfo(
        gain=gain, feature=feature, threshold=threshold,
        default_left=default_left, left_sum_g=zf, left_sum_h=zf,
        left_count=zf, right_sum_g=zf, right_sum_h=zf, right_count=zf,
        left_output=zf, right_output=zf, is_cat=np.zeros((L,), bool),
        cat_bitset=np.zeros((L, 1), np.uint32), seg_lo=none, seg_hi=none)
    meta, _ = _make_meta([B] * f)
    missing_bin = np.full((f,), -1, np.int32)
    missing_bin[[2, f - 1]] = [int(ds.sp_default[list(ds.sp_cols).index(2)]),
                               3]
    leaf = rng.randint(0, k, size=n).astype(np.int32)
    # the two rows a mask one position off gets wrong, put where it shows:
    # stream i's leaf splits at the default bin (the default goes left, the
    # top bin right) and holds the stream's LAST entry and the FIRST entry
    # of the stream stored behind it, which is no entry of column i
    splits_on = {int(feature[l]): l for l in range(k)}
    ends = [ds.stream_column(i)[0][[0, -1]] for i in range(len(widths))]
    for i, c in enumerate(ds.sp_cols):
        threshold[splits_on[int(c)]] = int(ds.sp_default[i])
        if i + 1 < len(widths):
            leaf[ends[i + 1][0]] = splits_on[int(c)]
    for i, c in enumerate(ds.sp_cols):
        leaf[ends[i][1]] = splits_on[int(c)]

    def phase(leaf, best):
        fns = grower._grower_fns(
            jnp.asarray(dense), jnp.ones((n,)), jnp.ones((n,)),
            jnp.ones((n,)), meta, _make_params(), jnp.ones((f,)),
            jnp.asarray(missing_bin), max_leaves=L, num_bins=B,
            hist_method="scatter", binsT=np.ascontiguousarray(dense.T),
            **_stream_kw(ds))
        st = fns["init_state"]()._replace(
            leaf_id=leaf, num_leaves=jnp.int32(k),
            hist_valid=jnp.arange(L) < k,
            best=grower.SplitInfo(*(jnp.asarray(a) for a in best)))
        out = fns["split_apply"](st)
        return out.leaf_id, out.num_leaves

    got, leaves = jax.jit(phase)(leaf, best)
    ref, new_leaf = leaf.copy(), k
    for l in np.argsort(-gain[:k]):
        left = _np_goes_left(
            bins[:, feature[l]].astype(np.int32), threshold[l],
            default_left[l], missing_bin[feature[l]], False, None, -1, -1)
        ref = np.where((ref == l) & ~left, new_leaf, ref)
        new_leaf += 1
    assert int(leaves) == new_leaf
    np.testing.assert_array_equal(np.asarray(got), ref)


@pytest.mark.slow
def test_sparse_end_to_end_quality_parity(rng):
    """(Slow tier: a quality-parity spelling — the sparse-vs-dense
    MECHANICS stay tier-1 via test_sparse_all_columns_sparse,
    test_sparse_reconstruction_and_histogram_exactness and the sparse
    eval/predict regressions in test_advisor_fixes.py.)"""
    X, y = _sparse_frame(rng)
    ds_d, b_dense = _fit(X, y, enable_sparse=False)
    ds_s, b_sparse = _fit(X, y, enable_sparse=True)
    assert not ds_d.has_sparse_cols
    assert ds_s.has_sparse_cols
    # the concentrated columns left the dense matrix
    assert ds_s.bins.shape[1] == ds_d.bins.shape[1] - len(ds_s.sp_cols)
    a_d, a_s = _acc(b_dense, X, y), _acc(b_sparse, X, y)
    assert a_s > 0.9 and abs(a_s - a_d) < 0.02, (a_s, a_d)
    # the sparse columns actually split (their streams carry the signal)
    imp = b_sparse._boosting.feature_importance("split")
    assert imp[4] > 0
    # model round-trips through text
    b2 = lgb.Booster(model_str=b_sparse.model_to_string())
    np.testing.assert_allclose(b2.predict(X[:64]), b_sparse.predict(X[:64]),
                               rtol=1e-6)


@pytest.mark.slow
def test_sparse_parity_with_bagging_and_categorical(rng):
    """(Slow tier: the bagging×categorical×sparse COMBINATION cell —
    sparse training/eval mechanics stay tier-1 via
    test_sparse_all_columns_sparse and the sparse eval/predict
    regressions in test_advisor_fixes.py; bagging and categorical parity
    each have their own tier-1 files.)"""
    X, y = _sparse_frame(rng, sparse_f=2)
    # a concentrated CATEGORICAL column (mode category >= 90%)
    cat = np.where(rng.uniform(size=len(X)) < 0.93, 0.0,
                   rng.randint(1, 5, size=len(X)).astype(np.float64))
    X = np.column_stack([X, cat])
    extra = {"categorical_feature": [X.shape[1] - 1],
             # mask-path bagging (fraction > 0.5 keeps the subset copy off)
             "bagging_fraction": 0.8, "bagging_freq": 1, "bagging_seed": 7}

    def fit(enable):
        params = {"objective": "binary", "num_leaves": 15,
                  "min_data_in_leaf": 5, "enable_sparse": enable,
                  "enable_bundle": False, "histogram_method": "scatter",
                  "verbosity": -1, **extra}
        ds = lgb.Dataset(X, label=y, params=params,
                         categorical_feature=[X.shape[1] - 1])
        return ds, lgb.train(params, ds, 6)

    ds_s, b_s = fit(True)
    ds_d, b_d = fit(False)
    assert ds_s.has_sparse_cols
    a_s, a_d = _acc(b_s, X, y), _acc(b_d, X, y)
    assert a_s > 0.85 and abs(a_s - a_d) < 0.03, (a_s, a_d)


def test_sparse_subset_copy_stays_off(rng):
    """bagging_fraction <= 0.5 normally takes the subset-copy path; sparse
    streams index ORIGINAL rows, so the mask path must be forced — and the
    model still trains healthy."""
    X, y = _sparse_frame(rng)
    extra = {"bagging_fraction": 0.4, "bagging_freq": 1}
    ds_s, b_s = _fit(X, y, True, extra)
    assert ds_s.has_sparse_cols
    assert b_s._boosting._bag_sub is None      # mask path forced
    assert _acc(b_s, X, y) > 0.8


def test_sparse_gates(rng):
    X, y = _sparse_frame(rng)
    # parallel learner requested at Dataset construct time -> no extraction
    params = {"objective": "binary", "tree_learner": "data",
              "enable_sparse": True, "verbosity": -1}
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    assert not ds.has_sparse_cols
    # tiny data -> no extraction (path-flip guard for small tests)
    Xs, ys = _sparse_frame(rng, n=300)
    ds2 = lgb.Dataset(Xs, label=ys, params={"enable_sparse": True,
                                            "verbosity": -1})
    ds2.construct()
    assert not ds2.has_sparse_cols
    # rollback is gated with a clean error
    from lightgbm_tpu.utils.log import LightGBMError
    ds3, b3 = _fit(X, y, True)
    with pytest.raises(LightGBMError):
        b3._boosting.rollback_one_iter()


def test_sparse_all_columns_sparse(rng):
    """Every device column sparse: the dense matrix is [N, 0] and per-leaf
    totals come from the direct per-slot reduction."""
    n = 1500
    X = np.zeros((n, 3))
    for j in range(3):
        nz = rng.choice(n, 60, replace=False)
        X[nz, j] = rng.normal(size=60) + 1.0 + j
    y = (X[:, 0] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
              "enable_sparse": True, "enable_bundle": False,
              "histogram_method": "scatter", "verbosity": -1}
    ds = lgb.Dataset(X, label=y, params=params)
    b = lgb.train(params, ds, 5)
    assert ds.has_sparse_cols and ds.bins.shape[1] == 0
    params_d = {**params, "enable_sparse": False}
    ds_d = lgb.Dataset(X, label=y, params=params_d)
    b_d = lgb.train(params_d, ds_d, 5)
    assert abs(_acc(b, X, y) - _acc(b_d, X, y)) < 0.02
    assert _acc(b, X, y) > 0.95


def _gathers_of(jaxpr, shape, in_loop=False):
    """``[in a while body?]`` of every gather in a jaxpr, its loops,
    branches and calls, whose operand has ``shape``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather" \
                and tuple(eqn.invars[0].aval.shape) == shape:
            found.append(in_loop)
        inner = in_loop or eqn.primitive.name == "while"
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _gathers_of(sub, shape, inner)
    return found


def test_the_statistics_by_entry_are_gathered_once_a_tree(rng):
    """``stats[sp_rows]`` is the same in every pass of a tree: the grow
    program gathers from the [N, 3] statistics ONCE, before its loop, and
    no pass does (eleven passes a tree paid 0.37 s an iteration for it at
    11M rows and 2.4M slots). The leaf ids are gathered by entry in the
    loop, so the walker does see into it."""
    bins, dense, ds = _stream_set((3, 30, 300), rng)
    n, f = bins.shape
    meta, missing_bin = _make_meta([16] * f)
    jaxpr = jax.make_jaxpr(lambda g, h: grower.grow_tree(
        jnp.asarray(dense), g, h, jnp.ones((n,)), meta, _make_params(),
        jnp.ones((f,)), jnp.asarray(missing_bin), max_leaves=8, num_bins=16,
        hist_method="scatter", **_stream_kw(ds)))(
            jnp.ones((n,)), jnp.ones((n,))).jaxpr
    assert _gathers_of(jaxpr, (n, 3)) == [False]
    by_entry = _gathers_of(jaxpr, (n,))
    assert by_entry and all(by_entry)
