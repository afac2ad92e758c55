"""Distributed data-parallel tests on the virtual 8-device CPU mesh
(the analog of the reference testing multi-node with an in-process Dask
LocalCluster, test_dask.py — here: real shard_map + psum over 8 XLA host
devices)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow

from lightgbm_tpu.parallel.data_parallel import grow_tree_dp, make_mesh
from lightgbm_tpu.models.grower import grow_tree

from test_grower import _make_meta, _make_params, _partition_signature


@pytest.fixture(scope="module")
def mesh8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return make_mesh(8)


def _data(seed, n=512, f=4, b=16):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    hess = np.ones(n, dtype=np.float32)
    return bins, grad, hess


@pytest.mark.parametrize("exact", [True, False])
def test_dp_matches_single_device(mesh8, exact):
    """Distributed growth must produce the same tree as single-device growth
    (the analog of test_dask.py's distributed ~= local assertions, but exact:
    psum of f32 partial histograms is deterministic)."""
    bins, grad, hess = _data(0)
    n, f = bins.shape
    meta, missing_bin = _make_meta([16] * f)
    params = _make_params(min_data=5)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.ones((n,), jnp.float32), meta, params,
            jnp.ones((f,), jnp.float32), jnp.asarray(missing_bin))
    tree_s, leaf_s, _aux = grow_tree(*args, max_leaves=16, num_bins=16, exact=exact)
    tree_d, leaf_d = grow_tree_dp(mesh8, *args, max_leaves=16, num_bins=16,
                                  exact=exact)
    assert int(tree_s.num_leaves) == int(tree_d.num_leaves)
    np.testing.assert_array_equal(np.asarray(tree_s.node_feature),
                                  np.asarray(tree_d.node_feature))
    np.testing.assert_array_equal(np.asarray(tree_s.node_threshold_bin),
                                  np.asarray(tree_d.node_threshold_bin))
    np.testing.assert_array_equal(np.asarray(leaf_s), np.asarray(leaf_d))
    np.testing.assert_allclose(np.asarray(tree_s.leaf_value),
                               np.asarray(tree_d.leaf_value), rtol=1e-5,
                               atol=1e-7)


def test_dp_rows_not_divisible(mesh8):
    """Row counts not divisible by the mesh size are padded with zero-mass
    rows and must not change the result."""
    bins, grad, hess = _data(1, n=509)
    n, f = bins.shape
    meta, missing_bin = _make_meta([16] * f)
    params = _make_params(min_data=5)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.ones((n,), jnp.float32), meta, params,
            jnp.ones((f,), jnp.float32), jnp.asarray(missing_bin))
    tree_s, leaf_s, _aux = grow_tree(*args, max_leaves=8, num_bins=16)
    tree_d, leaf_d = grow_tree_dp(mesh8, *args, max_leaves=8, num_bins=16)
    assert leaf_d.shape[0] == n
    np.testing.assert_array_equal(np.asarray(tree_s.node_feature)[:int(tree_s.num_leaves) - 1],
                                  np.asarray(tree_d.node_feature)[:int(tree_d.num_leaves) - 1])
    np.testing.assert_array_equal(np.asarray(leaf_s), np.asarray(leaf_d))


def test_dp_bagging_mask(mesh8):
    bins, grad, hess = _data(2)
    n, f = bins.shape
    rng = np.random.RandomState(3)
    mask = (rng.uniform(size=n) < 0.7).astype(np.float32)
    meta, missing_bin = _make_meta([16] * f)
    params = _make_params(min_data=5)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask), meta, params,
            jnp.ones((f,), jnp.float32), jnp.asarray(missing_bin))
    tree_s, leaf_s, _aux = grow_tree(*args, max_leaves=8, num_bins=16)
    tree_d, leaf_d = grow_tree_dp(mesh8, *args, max_leaves=8, num_bins=16)
    np.testing.assert_array_equal(np.asarray(leaf_s), np.asarray(leaf_d))


# ---------------------------------------------------------------- learners
@pytest.mark.parametrize("mode,kwargs", [
    ("data", {}),                       # psum_scatter + owner search + sync
    ("feature", {}),                    # feature slices + sync_best_splits
    ("voting", {"vote_top_k": 3}),      # 2*top_k == F: full electorate ==
                                        # serial exactly
])
def test_parallel_learner_kernels_match_serial(mesh8, mode, kwargs):
    """All three parallel learner modes reproduce the serial tree on the
    8-device mesh (reference analog: test_dask.py's distributed ~= local
    matrix over data/voting learners)."""
    from lightgbm_tpu.parallel.learners import ParallelGrower
    bins, grad, hess = _data(4, n=512, f=6)
    n, f = bins.shape
    meta, missing_bin = _make_meta([16] * f)
    params = _make_params(min_data=5)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.ones((n,), jnp.float32), meta, params,
            jnp.ones((f,), jnp.float32), jnp.asarray(missing_bin))
    tree_s, leaf_s, _aux = grow_tree(*args, max_leaves=8, num_bins=16)
    pg = ParallelGrower(mode, mesh8, axis="data")
    tree_d, leaf_d, _aux2 = pg(*args, max_leaves=8, num_bins=16, **kwargs)
    assert int(tree_s.num_leaves) == int(tree_d.num_leaves)
    np.testing.assert_array_equal(np.asarray(tree_s.node_feature),
                                  np.asarray(tree_d.node_feature))
    np.testing.assert_array_equal(np.asarray(tree_s.node_threshold_bin),
                                  np.asarray(tree_d.node_threshold_bin))
    np.testing.assert_array_equal(np.asarray(leaf_s), np.asarray(leaf_d))
    np.testing.assert_allclose(np.asarray(tree_s.leaf_value),
                               np.asarray(tree_d.leaf_value), rtol=1e-5,
                               atol=1e-7)


def test_voting_restricts_to_electorate(mesh8):
    """With a tiny electorate the voting learner must only split on elected
    features (PV-tree semantics) while still producing a usable tree."""
    from lightgbm_tpu.parallel.learners import ParallelGrower
    bins, grad, hess = _data(5, n=512, f=6)
    n, f = bins.shape
    meta, missing_bin = _make_meta([16] * f)
    params = _make_params(min_data=5)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.ones((n,), jnp.float32), meta, params,
            jnp.ones((f,), jnp.float32), jnp.asarray(missing_bin))
    pg = ParallelGrower("voting", mesh8, axis="data")
    tree_v, leaf_v, _aux = pg(*args, max_leaves=8, num_bins=16, vote_top_k=1)
    assert int(tree_v.num_leaves) >= 2


@pytest.mark.parametrize("mode", ["data", "feature", "voting"])
def test_tree_learner_public_api_matches_serial(mode):
    """lgb.train({"tree_learner": ...}) routes through the parallel grower
    and matches serial training end-to-end (the config must not be
    silently ignored)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(7)
    n, f = 600, 8
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.1 * rng.normal(size=n) > 0).astype(
        np.float64)

    def fit(extra):
        ds = lgb.Dataset(X, label=y, params={"min_data_in_leaf": 5,
                                             "verbosity": -1})
        booster = lgb.train({"objective": "binary", "num_leaves": 8,
                             "min_data_in_leaf": 5, "verbosity": -1, **extra},
                            ds, num_boost_round=5)
        return booster.predict(X, raw_score=True)

    extra = {"tree_learner": mode}
    if mode == "voting":
        extra["top_k"] = 4   # 2*top_k == F: full electorate
    np.testing.assert_allclose(fit({}), fit(extra), rtol=1e-4, atol=1e-6)


def test_voting_election_confines_splits(mesh8):
    """Discriminative PV-tree election check (voting_parallel_tree_learner
    .cpp:151-182 GlobalVoting): a feature with the highest GLOBAL gain but
    support on only one shard (1 vote) must lose the election to features
    that win votes across shards — the root split must come from the
    elected set, while serial growth picks the unelected global-best."""
    from lightgbm_tpu.parallel.learners import ParallelGrower
    rng = np.random.RandomState(11)
    n, f, b = 512, 6, 16
    shard_rows = n // 8
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    grad = 0.05 * rng.normal(size=n).astype(np.float32)
    hess = np.ones(n, dtype=np.float32)
    # f0: moderate signal on EVERY shard (wins most shards' top-1 vote)
    grad += 0.5 * np.where(bins[:, 0] < b // 2, -1.0, 1.0).astype(np.float32)
    # f1: strong signal only on shard 0 (1 vote)
    s0 = slice(0, shard_rows)
    grad[s0] += 2.0 * np.where(bins[s0, 1] < b // 2, -1.0, 1.0)
    # f5: HUGE signal only on shard 1 -> highest global gain, but 1 vote and
    # the highest feature index (loses the tie-break to f1)
    s1 = slice(shard_rows, 2 * shard_rows)
    grad[s1] += 20.0 * np.where(bins[s1, 5] < b // 2, -1.0, 1.0)

    meta, missing_bin = _make_meta([b] * f)
    params = _make_params(min_data=5)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.ones((n,), jnp.float32), meta, params,
            jnp.ones((f,), jnp.float32), jnp.asarray(missing_bin))
    tree_s, _, _aux = grow_tree(*args, max_leaves=2, num_bins=b)
    assert int(np.asarray(tree_s.node_feature)[0]) == 5  # serial: global best
    pg = ParallelGrower("voting", mesh8, axis="data")
    tree_v, _, _aux2 = pg(*args, max_leaves=2, num_bins=b, vote_top_k=1)
    root_feat = int(np.asarray(tree_v.node_feature)[0])
    # electorate = top-2 by votes: f0 (6 votes) + f1 (tie-break by index)
    assert root_feat in (0, 1), root_feat


def test_voting_quality_near_serial():
    """PV-tree quality claim (voting_parallel_tree_learner.cpp): a
    RESTRICTED electorate (2*top_k < F) still trains nearly as well as
    serial when the informative features win votes."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(13)
    n, f = 2000, 10
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.7 * X[:, 1] + 0.15 * rng.normal(size=n) > 0).astype(
        np.float64)

    def fit(extra):
        ds = lgb.Dataset(X, label=y, params={"min_data_in_leaf": 5,
                                             "verbosity": -1})
        booster = lgb.train({"objective": "binary", "num_leaves": 15,
                             "min_data_in_leaf": 5, "verbosity": -1, **extra},
                            ds, num_boost_round=10)
        p = booster.predict(X)
        return float(np.mean((p > 0.5) == (y > 0.5)))

    acc_serial = fit({})
    acc_voting = fit({"tree_learner": "voting", "top_k": 2})  # electorate 4 < 10
    assert acc_voting >= acc_serial - 0.02, (acc_serial, acc_voting)


@pytest.mark.parametrize("mode,params_extra,data_kind", [
    ("data", {}, "sparse_efb"),            # EFB bundles under data-parallel
    ("feature", {}, "sparse_efb"),         # ... and feature-parallel
    ("voting", {"top_k": 4}, "categorical"),  # categorical under voting
    ("data", {"extra_trees": True}, "dense"),
])
def test_lifted_learner_restrictions_match_serial(mode, params_extra,
                                                  data_kind):
    """Round-4 lifted combos: EFB-bundled datasets, categorical x voting,
    and extra_trees now run under the parallel learners and must match
    serial training (the reference's distributed learners have no such
    restrictions, data_parallel_tree_learner.cpp)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(17)
    n, f = 800, 8
    if data_kind == "sparse_efb":
        import scipy.sparse as sp
        X = rng.normal(size=(n, f)) * (rng.uniform(size=(n, f)) < 0.15)
        y = X[:, 0] - X[:, 3] + 0.05 * rng.normal(size=n)
        make_X = lambda: sp.csr_matrix(X)
        obj = {"objective": "regression"}
        cats = {}
    elif data_kind == "categorical":
        X = rng.normal(size=(n, f))
        X[:, 2] = rng.randint(0, 5, size=n)
        y = (X[:, 0] + (X[:, 2] == 3) > 0.5).astype(np.float64)
        make_X = lambda: X.copy()
        obj = {"objective": "binary"}
        cats = {"categorical_feature": [2]}
    else:
        X = rng.normal(size=(n, f))
        y = X[:, 0] + np.sin(X[:, 1])
        make_X = lambda: X.copy()
        obj = {"objective": "regression"}
        cats = {}

    def fit(extra):
        ds = lgb.Dataset(make_X(), label=y,
                         params={"min_data_in_leaf": 5, "verbosity": -1},
                         **cats)
        booster = lgb.train({**obj, "num_leaves": 8, "min_data_in_leaf": 5,
                             "verbosity": -1, **extra},
                            ds, num_boost_round=4)
        return booster.predict(make_X(), raw_score=True)

    extra = {"tree_learner": mode, **params_extra}
    base = {k: v for k, v in params_extra.items()}
    p_base, p_dist = fit(base), fit(extra)
    if data_kind == "categorical":
        # the categorical many-vs-many scan sorts bins by grad/hess ratio,
        # where f32 psum reduction-order differences can flip ties in later
        # trees — assert quality parity, the reference's own distributed
        # test contract (test_dask.py distributed ~= local)
        acc_b = np.mean((p_base > 0) == (y > 0.5))
        acc_d = np.mean((p_dist > 0) == (y > 0.5))
        assert abs(acc_b - acc_d) < 0.01, (acc_b, acc_d)
        assert np.mean(np.abs(p_base - p_dist) > 1e-3) < 0.15
    else:
        np.testing.assert_allclose(p_base, p_dist, rtol=1e-4, atol=1e-6)


def test_forced_splits_under_data_parallel(tmp_path):
    """Forced splits now run under the data-parallel learner and match
    serial (ff holds global feature indices; owner search + sync)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    import json
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(19)
    n, f = 800, 6
    X = rng.normal(size=(n, f))
    y = X[:, 0] + np.sin(2 * X[:, 4]) + 0.05 * rng.normal(size=n)
    forced = {"feature": 4, "threshold": 0.0,
              "left": {"feature": 2, "threshold": -0.5}}
    p = tmp_path / "forced.json"
    p.write_text(json.dumps(forced))

    def fit(extra):
        ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
        booster = lgb.train({"objective": "regression", "num_leaves": 8,
                             "forcedsplits_filename": str(p),
                             "verbosity": -1, **extra},
                            ds, num_boost_round=3)
        feats = {int(v) for ht in booster._boosting.host_trees
                 for v in np.asarray(ht.split_feature)}
        return booster.predict(X, raw_score=True), feats

    p_s, feats_s = fit({})
    p_d, feats_d = fit({"tree_learner": "data"})
    assert 4 in feats_d        # the forced root split happened
    np.testing.assert_allclose(p_d, p_s, rtol=1e-4, atol=1e-6)
