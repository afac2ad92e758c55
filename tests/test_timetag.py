"""TIMETAG (``profiling.enable``) only observes training: a run with it on
grows the same trees as a run with it off, and its ``grow_tree`` timer
counts the trees it timed. One case per configuration the serial grower
takes: the unfused iteration with and without split fusion, a bagging
subset, a kept compaction rung, categorical columns, EFB with a stream
column, forced splits, and the default fused iteration."""

import json

import numpy as np
import pytest
import scipy.sparse

import lightgbm_tpu as lgb
from lightgbm_tpu.utils import profiling

UNFUSED = {"fused_iteration": False, "histogram_method": "scatter"}

CASES = {
    "unfused": UNFUSED,
    "split_fusion": {**UNFUSED, "split_fusion": "on"},
    "bagging_subset": {**UNFUSED, "bagging_fraction": 0.5,
                       "bagging_freq": 1},
    "compaction_rung": {**UNFUSED, "hist_compaction": True},
    "categorical": UNFUSED,
    "efb_streams": UNFUSED,
    "forced_splits": UNFUSED,
    "fused": {},
}


def _dense(rng, n=2000):
    X = rng.normal(size=(n, 6))
    X[:, 3] = rng.randint(0, 8, size=n)
    y = X[:, 0] + 0.5 * X[:, 1] + (X[:, 3] > 4) + 0.1 * rng.normal(size=n)
    return X, (y > 0.5).astype(np.float64)


def _one_hot(rng, n=4000):
    """Four dense columns and a one-hot group handed over as a CSR matrix:
    127 common members (90% of the rows) that EFB bundles into one column,
    and 30 rare ones (8%) bundled into a second column that sits over 90%
    on bin 0, a stream column."""
    r = rng.rand(n)
    code = np.where(r < 0.9, rng.randint(0, 127, n),
                    np.where(r < 0.98, 127 + rng.randint(0, 30, n), -1))
    X = np.zeros((n, 4 + 157))
    X[:, :4] = rng.normal(size=(n, 4))
    rows = np.nonzero(code >= 0)[0]
    X[rows, 4 + code[rows]] = 1.0
    y = X[:, 0] + 1.5 * (code % 3 == 0) + 0.1 * rng.normal(size=n)
    return scipy.sparse.csr_matrix(X), (y > 0.5).astype(np.float64)


def _train(X, y, params, on, categorical):
    profiling.reset()
    profiling.enable(on)
    try:
        ds = lgb.Dataset(X, label=y, params=params,
                         categorical_feature=categorical)
        booster = lgb.train(params, ds, num_boost_round=3,
                            keep_training_booster=True)
        return booster, booster.model_to_string(), profiling.scopes()
    finally:
        profiling.enable(False)
        profiling.reset()


@pytest.mark.parametrize("case", sorted(CASES))
def test_timetag_leaves_the_trees_alone(case, tmp_path):
    rng = np.random.RandomState(7)
    X, y = _one_hot(rng) if case == "efb_streams" else _dense(rng)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbosity": -1, **CASES[case]}
    if case == "forced_splits":
        path = tmp_path / "forced.json"
        path.write_text(json.dumps({
            "feature": 2, "threshold": 0.0,
            "left": {"feature": 4, "threshold": 0.0}}))
        params["forcedsplits_filename"] = str(path)

    categorical = [3] if case == "categorical" else "auto"
    booster, plain, _ = _train(X, y, params, False, categorical)
    _, timed, scopes = _train(X, y, params, True, categorical)
    assert timed == plain
    assert scopes.get("grow_tree", {}).get("calls", 0) >= 3, scopes

    # the case reaches the configuration it names
    gb = booster._boosting
    statics = gb._serial_grow_statics(gb._hist_method())
    if case == "split_fusion":
        assert statics["split_fusion"]
    elif case == "compaction_rung":
        assert statics["compaction_ladder"]
    elif case == "bagging_subset":
        assert gb._bag_sub is not None
    elif case == "categorical":
        assert "cat_threshold=" in plain
    elif case == "efb_streams":
        assert gb.train_set.bundles is not None
        assert gb.train_set.has_sparse_cols
    elif case == "forced_splits":
        assert gb._forced_splits is not None
        assert "\nsplit_feature=2 " in plain
    elif case == "fused":
        assert gb._fused_ok(None)
