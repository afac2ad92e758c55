"""Missing values at width: every column with a NaN bin, the histogram
kernel's split epilogue scanning both NaN directions in feature blocks
whose last one is padded, held to the plain reference the benchmark's
``bosch.train`` cell runs (benchmarks/reference_missing.py).

The kernels run interpreted (the TPU compiler's side of the 968-column
epilogue is tests/test_chip_compile.py). Each case trains the fused
search (``split_fusion`` auto) and the classic one (off): the model text
has to be the same. Tree 0 then has to be the reference's over the raw
values: the root split over all columns with its default direction, every
leaf count a raw traversal's, every leaf value the float64 sums. The
TIMETAG split counters the benchmark reads have to count what the model
text holds.
"""

import os
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models import gbdt
from lightgbm_tpu.utils import profiling

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")

pytestmark = pytest.mark.pallas


@pytest.fixture(scope="module")
def ref():
    """benchmarks/ on the path, as run.py puts it: the reference and the
    model-text reader it shares with the other cells."""
    sys.path.insert(0, BENCH)
    try:
        import reference
        import reference_missing
        import reference_sparse
        yield reference_missing, reference, reference_sparse
    finally:
        sys.path.remove(BENCH)


def _text_counts(reference, reference_sparse, text):
    """What the TIMETAG counters must read over a model text's trees."""
    want = dict.fromkeys(gbdt.TREE_COUNTERS, 0)
    for k, t in enumerate(reference.parse_model(text)):
        s = t["num_leaves"] - 1
        want["trees"] += 1
        want["tree_leaves"] += t["num_leaves"]
        want["splits"] += s
        if s:
            dt = reference_sparse.tree_field(text, k, "decision_type") \
                .astype(int)
            nan = (dt >> 2) & 3 == 2
            want["nan_splits"] += int(nan.sum())
            want["nan_left_splits"] += int((nan & ((dt & 2) != 0)).sum())
    return want


def _data(n, f, positives, seed):
    """Columns whose NaN share runs from 0% to 95% and whose values are
    rounded to 1 to 3 decimals (8 to 255 bins); a label at about
    ``positives`` from a few columns' values and NaN patterns. The columns
    that carry the label lie in every feature block."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    decimals = rng.integers(1, 4, size=f)
    scale = rng.uniform(0.05, 1.0, size=f)
    for j in range(f):
        X[:, j] = np.round(X[:, j] * scale[j], decimals[j])
    nan_share = np.linspace(0.0, 0.95, f)[rng.permutation(f)]
    X[rng.random((n, f)) < nan_share] = np.nan
    cols = np.linspace(0, f - 1, 7).astype(int)
    logit = np.zeros(n)
    for k, j in enumerate(cols):
        present = ~np.isnan(X[:, j])
        logit += np.where(present, X[:, j] / scale[j], 0.8 * (-1) ** k)
    logit += np.log(positives / (1 - positives)) - 0.5
    y = (logit + rng.logistic(size=n) > 0).astype(np.float32)
    return X, y


# (rows, columns, positive share, max_bin): 450 columns are three feature
# blocks of 152, the last with 6 of padding; 170 are two of 88
CASES = [
    pytest.param(4096, 450, 0.02, 63, id="450x63-2pct"),
    pytest.param(4096, 170, 0.005, 255, id="170x255-half-pct"),
    pytest.param(3000, 450, 0.05, 15, id="450x15-5pct"),
]


@pytest.mark.parametrize("n,f,positives,max_bin", CASES)
def test_nan_bins_in_the_fused_epilogue_are_the_reference(ref, n, f,
                                                          positives,
                                                          max_bin):
    X, y = _data(n, f, positives, seed=f + max_bin)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": max_bin,
              "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 0.5,
              "is_enable_sparse": False, "verbosity": -1,
              "hist_pallas_interpret": True}
    texts, plans = {}, {}
    for fusion in ("auto", "off"):
        p = {**params, "split_fusion": fusion}
        ds = lgb.Dataset(X, label=y, params=p)
        was = profiling.enabled()
        before = profiling.counters()
        profiling.enable(True)
        try:
            booster = lgb.train(p, ds, 2, keep_training_booster=True)
            # the trees; the parameters that follow name the fusion
            texts[fusion] = booster.model_to_string().split(
                "end of trees")[0]
        finally:
            profiling.enable(was)
        after = profiling.counters()
        counted = {k: after.get(k, 0.0) - before.get(k, 0.0)
                   for k in gbdt.TREE_COUNTERS}
        plans[fusion] = booster.hist_plan()
    assert ds.num_used_features() == f
    assert [m.missing_type == 2 for m in ds.mappers] \
        == np.isnan(X).any(axis=0).tolist()
    auto = plans["auto"]
    assert auto["split_fusion"] and not plans["off"]["split_fusion"]
    assert auto["feature_blocks"] == (3 if f == 450 else 2), auto
    assert auto["feature_block"] * auto["feature_blocks"] > f
    assert texts["auto"] == texts["off"]

    # tree 0 against the reference over the raw values
    rm, reference, reference_sparse = ref
    text = texts["auto"]
    bounds, real, nan_bin = rm.tables(ds.mappers)
    _, cnt, ysum = rm.whole_histograms(X, y, bounds, nan_bin,
                                       ds.max_num_bins, 2)
    tree = reference.parse_model(text)[0]
    dtype = reference_sparse.tree_field(text, 0, "decision_type").astype(int)
    gain = rm.reference_dp.root_split(cnt, ysum, y, real, nan_bin, 1.0,
                                      0.5)[0]
    f_sys, thr = int(tree["split_feature"][0]), float(tree["threshold"][0])
    (g_left, c_left), (g_right, c_right) = rm.direction_gains(
        X[:, f_sys], y, thr, 1.0, 0.5)
    sys_left = bool(dtype[0] & rm.reference_dp.DEFAULT_LEFT)
    gain_sys, left_raw = (g_left, c_left) if sys_left else (g_right,
                                                            c_right)
    assert (gain - gain_sys) / gain <= 1e-3
    if abs(g_left - g_right) > 1e-3 * gain:
        assert sys_left == (g_left > g_right)
    assert reference.child_count(tree, int(tree["left_child"][0])) \
        == left_raw
    leaf = rm.reference_dp.leaf_index(tree, dtype, X, 2)
    assert np.array_equal(np.bincount(leaf, minlength=tree["num_leaves"]),
                          tree["leaf_count"])
    values = reference_sparse.leaf_values(
        tree, leaf, y, float(reference_sparse.tree_field(text, 0,
                                                         "shrinkage")[0]))
    assert np.median(np.abs(tree["leaf_value"] - values)) <= 2.5e-6

    # the counters of the last training (split_fusion off: the same
    # trees), and NaN sent both ways somewhere in the model
    want = _text_counts(reference, reference_sparse, text)
    assert {k: int(v) for k, v in counted.items()} == want
    assert 0 < want["nan_splits"] <= want["splits"]
    assert 0 < want["nan_left_splits"] < want["nan_splits"]


def test_splits_are_counted_once_with_categorical_and_nan_columns(ref):
    """A set with a categorical column AND columns with a NaN bin: the
    TIMETAG ``splits`` counter counts every split once, ``cat_splits`` and
    the host mirrors ``cat_splits_total`` / ``splits_total`` (what
    ``cat_split_share`` reads) are what they were, and ``nan_splits``
    counts the splits on a column with a NaN bin."""
    reference_sparse = ref[2]
    rng = np.random.default_rng(5)
    n = 2000
    X = np.round(rng.standard_normal((n, 4)), 2).astype(np.float32)
    X[:, 0] = rng.integers(0, 6, size=n)
    X[rng.random(n) < 0.4, 1] = np.nan
    X[rng.random(n) < 0.7, 2] = np.nan
    y = ((X[:, 0] % 2 == 1) ^ np.isnan(X[:, 1])
         ^ (np.nan_to_num(X[:, 3]) > 0.3)).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 8, "verbosity": -1,
              "min_data_in_leaf": 5}
    ds = lgb.Dataset(X, label=y, categorical_feature=[0], params=params)
    was = profiling.enabled()
    before = profiling.counters()
    profiling.enable(True)
    try:
        booster = lgb.train(params, ds, 3, keep_training_booster=True)
        text = booster.model_to_string()
    finally:
        profiling.enable(was)
    after = profiling.counters()
    got = {k: int(after.get(k, 0) - before.get(k, 0))
           for k in gbdt.TREE_COUNTERS}
    gb = booster._boosting
    trees = len(text.split("\nTree=")) - 1
    splits = cats = nans = 0
    for k in range(trees):
        dt = reference_sparse.tree_field(text, k, "decision_type").astype(int)
        splits += len(dt)
        cats += int((dt & 1).sum())
        nans += int((((dt >> 2) & 3) == 2).sum())
    assert got["splits"] == gb.splits_total == splits > 0
    assert got["cat_splits"] == gb.cat_splits_total == cats > 0
    assert got["nan_splits"] == nans > 0
    assert got["trees"] == trees == 3


def test_the_job_refuses_a_library_without_the_counters(monkeypatch):
    """The parent library has no ``TREE_COUNTERS``: the job's first step
    raises, before any data is made."""
    sys.path.insert(0, BENCH)
    try:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "bench_jobs_missing_train",
            os.path.join(BENCH, "jobs", "missing_train.py"))
        job = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(job)
        job._surface()
        monkeypatch.delattr(gbdt, "TREE_COUNTERS")
        with pytest.raises(RuntimeError, match="TREE_COUNTERS"):
            job._surface()
    finally:
        sys.path.remove(BENCH)


def test_the_cell_entries_fit_the_form():
    """bosch.train in BENCHMARK.json: one chip, job ``missing_train``, in
    the two end-to-end lists it reports, and the five ``missing`` metrics
    listed for it alone, as their files say."""
    import json
    root = os.path.dirname(BENCH)
    doc = json.load(open(os.path.join(root, "BENCHMARK.json")))
    cell = next(w for w in doc["workloads"] if w["name"] == "bosch.train")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "bosch-1m", "missing_train", 1)
    assert 1 <= len(cell["why"]) <= 200
    lists = {m["name"]: m for m in doc["end_to_end"] + doc["per_layer"]}
    for name in ("train_s_per_iter", "valid_auc"):
        assert "bosch.train" in lists[name]["workloads"]
    files = sorted(f[:-5] for f in os.listdir(os.path.join(
        BENCH, "layer_metrics")) if "missing_train" in json.load(open(
            os.path.join(BENCH, "layer_metrics", f)))["jobs"])
    assert files == sorted(["missing_split_share", "missing_left_share",
                            "leaves_per_tree",
                            "missing_hist_kernel_s_per_iter",
                            "missing_hist_tiles_roofline"])
    for name in files:
        spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                           name + ".json")))
        assert lists[name]["workloads"] == ["bosch.train"]
        assert {k: lists[name][k] for k in ("unit", "better", "source",
                                            "layer", "moves")} \
            == {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")}
    cfg = json.load(open(os.path.join(BENCH, "configs", "bosch-1m.json")))
    assert (cfg["data"]["rows"], cfg["data"]["features"]) == (1_000_000,
                                                              968)
    assert sorted(cfg["reduced"]) == ["data", "num_boost_round"]
    assert {k: cfg["params"][k] for k in ("max_bin", "num_leaves",
                                          "min_sum_hessian_in_leaf",
                                          "min_data_in_leaf")} \
        == {"max_bin": 255, "num_leaves": 255,
            "min_sum_hessian_in_leaf": 100.0, "min_data_in_leaf": 1}
