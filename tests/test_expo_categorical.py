"""The categorical Expo job at a few thousand rows, on the CPU: the flights
of benchmarks/data/expo.py handed over as six integer-coded categorical
columns and two numeric ones (benchmarks/data/expo_cat.py). The default
path (the categorical search, bitset routing, bitsets of raw codes in the
model text) against the plain reference over the RAW codes
(benchmarks/reference_cat.py, loaded by path); the device quantiser
against the host's on codes it has to send to the other bin; the scopes
and counters the cell reads; the rehearsal cell through the benchmark's
own job; and, for the five numerical cells, the lowered step text of
before this PR.
"""

import copy
import hashlib
import importlib.util
import json
import os
import re
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import binning, telemetry
from lightgbm_tpu.utils import profiling

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")


def _load(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, *parts))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


expo = _load("bench_data_expo_for_cat_tests", "data", "expo.py")
expo_cat = _load("bench_data_expo_cat_tests", "data", "expo_cat.py")
ref = _load("bench_reference_cat_tests", "reference_cat.py")

ROWS = 6000
CATS = [0, 1, 2, 3, 4, 5]
# 200 origins: more than 32 of them get a bin of their own and the rarest
# fold into the other bin; 40 destinations
GROUPS = [
    {"name": "month", "size": 12, "exponent": 0.05, "effect": 0.15},
    {"name": "day", "size": 31, "exponent": 0.02, "effect": 0.05},
    {"name": "weekday", "size": 7, "exponent": 0.05, "effect": 0.12},
    {"name": "carrier", "size": 22, "exponent": 1.0, "offset": 2.0,
     "effect": 0.25},
    {"name": "origin", "size": 200, "exponent": 1.6, "offset": 4.0,
     "effect": 0.3},
    {"name": "destination", "size": 40, "exponent": 1.6, "offset": 4.0,
     "effect": 0.2}]
SPEC = {"features": 8, "sample_seed": 1, "label_bias": -1.9,
        "time_effect": 1.6, "distance_effect": 0.1, "groups": GROUPS}
PARAMS = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 3,
          "verbosity": -1}
# what reference_cat reads: the library's defaults, resolved
SPLIT = {"min_data_in_leaf": 3.0, "min_sum_hessian_in_leaf": 1e-3,
         "lambda_l2": 0.0, "max_cat_to_onehot": 4.0, "cat_smooth": 10.0,
         "cat_l2": 10.0, "max_cat_threshold": 32.0,
         "min_data_per_group": 100.0}


@pytest.fixture(scope="module")
def data():
    return expo_cat.make(SPEC, 1, ROWS, ROWS)


@pytest.fixture(scope="module")
def trained(data):
    X, y = data
    ds = lgb.Dataset(X, label=y, params=PARAMS, categorical_feature=CATS)
    booster = lgb.train(PARAMS, ds, 3, keep_training_booster=True)
    return ds, booster


def _tree0(booster):
    return ref.parse_model(booster.model_to_string(num_iteration=1))[0]


# ------------------------------------------------------------------ data
def test_the_flights_are_the_one_hot_cells_row_for_row(data):
    """Codes are the one-hot group's column less its first, the numbers and
    the label are the same: the same flights in the same order."""
    X, y = data
    csr, y1 = expo.make({**SPEC, "features": 314}, 1, ROWS, ROWS)
    first = np.concatenate([[0], np.cumsum([g["size"] for g in GROUPS])])
    idx = csr.indices.reshape(ROWS, 8)
    np.testing.assert_array_equal(X[:, :6], idx[:, :6] - first[:6])
    np.testing.assert_array_equal(X[:, 6:], csr.data.reshape(ROWS, 8)[:, 6:])
    np.testing.assert_array_equal(y, y1)
    assert X.dtype == np.float32 and X.shape == (ROWS, 8)


def test_the_construct_reports_its_categorical_columns(trained, data):
    ds, _ = trained
    X, _ = data
    stats = ds.construct_stats
    assert stats["cat_columns"] == CATS
    assert stats["cat_bins"] == [ds.mappers[j].num_bin for j in CATS]
    other = [int(np.isin(X[:, j], ds.mappers[j].bin_2_categorical[1:],
                         invert=True).sum()) for j in CATS]
    assert stats["cat_other_rows"] == other and other[4] > 0
    assert stats["binned_on_device"] is False       # the CPU's host loop
    plain = lgb.Dataset(X[:, 6:], label=data[1], params=PARAMS).construct()
    assert plain.construct_stats is None


# ------------------------------------------------------- the reference
def test_first_tree_equals_the_reference_over_the_raw_codes(trained, data):
    """Tree 0's root (column and left set of raw codes) is the reference's
    best, its child counts and all its leaf counts a raw traversal's, its
    leaf values the float64 sums over the leaf's rows."""
    ds, booster = trained
    X, y = data
    cols = ref.columns_of(ds.mappers)
    binsT = ref.column_bins(X, cols)
    _, _, g, h = ref.binary_root_stats(y)
    best = ref.best_split(*ref.histograms(binsT, g, h, ds.max_num_bins),
                          cols, SPLIT)
    tree = _tree0(booster)
    assert int(tree["split_feature"][0]) == best["column"]
    if best["kind"] == "cat":
        assert ref.is_cat(tree, 0)
        assert ref.bins_of_codes(ref.left_codes(tree, 0),
                                 cols[best["column"]][1]) \
            == best["left_bins"]
    leaf = ref.leaf_index(tree, X)
    np.testing.assert_array_equal(
        np.bincount(leaf, minlength=tree["num_leaves"]), tree["leaf_count"])
    values = ref.leaf_values(tree, leaf, y, ref.leaf_l2(tree, cols, SPLIT))
    np.testing.assert_allclose(tree["leaf_value"], values, atol=2e-5)
    assert sum(ref.is_cat(tree, i) for i in range(14)) >= 5


def test_the_reference_search_on_a_small_column():
    """One-vs-rest under max_cat_to_onehot, the sorted scan above it: the
    left set is the bins whose label mean is high, bin 0 never in it, and
    the group counter holds a set of fewer than min_data_per_group rows
    back."""
    cnt = np.array([[50., 100, 100, 100, 100, 100, 100]])
    ymean = np.array([[0.9, 0.9, 0.1, 0.1, 0.9, 0.1, 0.1]])
    p0 = float((cnt * ymean).sum() / cnt.sum())
    gs = cnt * (p0 - ymean)
    hs = cnt * p0 * (1 - p0)
    cats = [("cat", np.arange(6))]
    best = ref.best_split(cnt, gs, hs, cats, SPLIT)
    assert best["left_bins"] == [1, 4] and best["l2"] == 10.0
    onehot = ref.best_split(cnt[:, :4], gs[:, :4], hs[:, :4],
                            [("cat", np.arange(3))], SPLIT)
    assert onehot["left_bins"] == [1] and onehot["l2"] == 0.0
    held = ref.best_split(cnt, gs, hs, cats,
                          {**SPLIT, "min_data_per_group": 250.0})
    assert held is None or len(held["left_bins"]) >= 3


# ---------------------------------------------------- device quantiser
def test_the_device_quantiser_equals_the_host_on_categorical_codes(data):
    """NaN, negative codes, codes in (-1, 0), unseen and folded codes,
    exact float32 integers beyond the category list and infinities: the
    device bins equal ``values_to_bins`` bit for bit, and a matrix without
    categorical columns keeps its tables (no category table)."""
    X, y = data
    ds = lgb.Dataset(X, label=y, params=PARAMS,
                     categorical_feature=CATS).construct()
    used = [ds.mappers[j] for j in ds.used_features]
    Z = X[:2000].copy()
    folded = sorted(set(range(200)) - set(ds.mappers[4].bin_2_categorical))
    assert folded
    special = [np.nan, -1.0, -0.5, -7.0, 1000.0, 16777216.0, 3.7, np.inf,
               -np.inf, float(folded[0]), 3e9]
    for k, v in enumerate(special):
        Z[k * 20:(k + 1) * 20, [0, 3, 4, 5]] = v
    Z[300:320, 6] = np.nan
    dev = np.asarray(binning.bin_data_device(Z, used, block=512))
    with np.errstate(invalid="ignore"):
        host = binning.bin_data(Z.astype(np.float64), used)
    np.testing.assert_array_equal(dev.astype(np.int32), host)
    # all but -0.5 and 3.7 (categories 0 and 3) take the other bin
    assert (host[:220, 4] == 0).sum() == 180
    assert binning.device_quantisable(used)
    assert binning.device_cat_table(used[6:]) is None
    # rows over a mesh of four (the construct of a row-sharded set)
    import jax
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("shard",))
    got = np.asarray(binning.bin_data_device(Z[:1999], used, block=128,
                                             mesh=mesh))
    np.testing.assert_array_equal(got[:1999].astype(np.int32), host[:1999])
    assert not got[1999:].any()
    wide = copy.deepcopy(used[4])
    wide.bin_2_categorical = wide.bin_2_categorical[:-1] + [(1 << 24) + 1]
    assert not binning.device_quantisable([wide])


# --------------------------------------------- bitset words past the first
def test_a_left_set_past_bitset_word_0_routes_alike_in_train_and_predict(
        trained, data):
    """The probe label of the benchmark's job: one tree splits with a left
    set holding bins of 32 and more; its leaf counts, the device predict's
    leaves, the host traversal of the model text and the reference's raw
    traversal all agree row for row."""
    sys.path.insert(0, BENCH)
    try:
        job = _load("bench_jobs_cat_train_tests", "jobs", "cat_train.py")
    finally:
        sys.path.remove(BENCH)
    ds, _ = trained
    X, _ = data
    cols = ref.columns_of(ds.mappers)
    y2, chosen = job.probe_label(ref.column_bins(X, cols), cols, 5)
    assert 4 in chosen and min(chosen[4]) >= 32
    probe_ds = lgb.Dataset(X, label=y2, params=PARAMS,
                           categorical_feature=CATS)
    booster = lgb.train(PARAMS, probe_ds, 1, keep_training_booster=True)
    tree = _tree0(booster)
    high = [i for i in range(tree["num_leaves"] - 1)
            if ref.is_cat(tree, i) and max(ref.bins_of_codes(
                ref.left_codes(tree, i),
                cols[int(tree["split_feature"][i])][1]) or [0]) >= 32]
    assert high
    leaf = ref.leaf_index(tree, X)
    np.testing.assert_array_equal(
        np.bincount(leaf, minlength=tree["num_leaves"]), tree["leaf_count"])
    np.testing.assert_array_equal(
        booster.predict(X, pred_leaf=True).reshape(len(X), -1)[:, 0], leaf)
    loaded = lgb.Booster(model_str=booster.model_to_string())
    np.testing.assert_allclose(loaded.predict(X, raw_score=True),
                               booster.predict(X, raw_score=True),
                               rtol=0, atol=1e-12)


def test_the_host_traversal_truncates_codes_toward_zero(trained, data):
    """A code in (-1, 0) is category 0 to the quantiser, the device predict
    and the model text's host traversal alike; an infinite one goes right
    without an error."""
    _, booster = trained
    X, _ = data
    Z = X[:64].copy()
    Z[:32, :6] = -0.5
    Z[32:40, :6] = np.inf
    Zero = Z.copy()
    Zero[:32, :6] = 0.0
    loaded = lgb.Booster(model_str=booster.model_to_string())
    np.testing.assert_allclose(loaded.predict(Z[:32], raw_score=True),
                               loaded.predict(Zero[:32], raw_score=True),
                               atol=0)
    np.testing.assert_allclose(booster.predict(Z, raw_score=True),
                               loaded.predict(Z, raw_score=True), atol=1e-12)


# ------------------------------------------------- scopes and counters
def _step_text(booster):
    gb = booster._boosting
    (step, bind), = gb._fused_cache.values()
    return step.lower(*gb._fused_call_args(None, bind)).compile().as_text()


def test_the_categorical_step_names_its_scopes_and_a_numerical_one_not(
        trained, data):
    _, booster = trained
    text = _step_text(booster)
    assert re.search(r'op_name="[^"]*/split_search/cat_search/', text)
    assert re.search(r'op_name="[^"]*/apply_split/cat_route/', text)
    assert {"cat_search", "cat_route"} <= set(
        telemetry.scope_table()["jit__fused_step"].values())
    X, y = data
    plain = lgb.train(PARAMS, lgb.Dataset(X, label=y, params=PARAMS), 1,
                      keep_training_booster=True)
    text = _step_text(plain)
    assert "/cat_search/" not in text and "/cat_route/" not in text


@pytest.fixture
def timetag():
    was = profiling.enabled()
    profiling.enable(True)
    profiling.reset()
    yield
    profiling.reset()
    profiling.enable(was)


def test_the_splits_are_counted_where_the_tree_reaches_the_host(
        data, timetag, tmp_path):
    """``cat_splits`` / ``splits`` (TIMETAG counters and the booster's
    always-on totals) against the model text's ``decision_type``; the
    flight recorder's iteration record carries both; a numerical set
    counts neither."""
    X, y = data
    p = {**PARAMS, "telemetry_dir": str(tmp_path)}
    booster = lgb.train(p, lgb.Dataset(X, label=y, params=p,
                                       categorical_feature=CATS), 3,
                        keep_training_booster=True)
    trees = ref.parse_model(booster.model_to_string())
    counts = profiling.counters()
    booster._boosting._flush_flight("test")
    cats = sum(int((t["decision_type"] & 1).sum()) for t in trees)
    splits = sum(t["num_leaves"] - 1 for t in trees)
    assert 0 < cats < splits
    assert counts["cat_splits"] == cats and counts["splits"] == splits
    gb = booster._boosting
    assert (gb.cat_splits_total, gb.splits_total) == (cats, splits)
    recs, errors = telemetry.validate_flight_jsonl(
        os.path.join(str(tmp_path), "flight_rank0.jsonl"))
    assert errors == []
    last = [r for r in recs if r["type"] == "iter"][-1]
    assert 0 < last["cat_splits"] <= cats and last["splits"] <= splits

    profiling.reset()
    lgb.train(PARAMS, lgb.Dataset(X[:, 6:], label=y, params=PARAMS), 2,
              keep_training_booster=True)._boosting.host_trees
    assert not {"cat_splits", "splits"} & set(profiling.counters())


# -------------------------------------------------------- the benchmark
@pytest.fixture(scope="module")
def bench():
    """benchmarks/ on the path as run.py puts it, and run.py itself."""
    sys.path.insert(0, BENCH)
    try:
        yield _load("bench_run_for_cat", "run.py")
    finally:
        sys.path.remove(BENCH)


def test_the_rehearsal_cell_is_correct(bench):
    """The cell's own job over the rehearsal files, as ``run.py
    --rehearse`` drives it: tree 0 and the probe against the reference,
    the counters against the model text."""
    cell = bench.load_json("workloads", "tiny-expo-cat.train.json")
    cfg = bench.load_json("configs", cell["config"] + ".json")
    ctx = bench.Context(cell, cfg, 2147483659,
                        {"hist_pallas_interpret": True}, None)
    lines = []
    ctx.log = lines.append
    job = bench.load_module("jobs", cell["job"])
    st = job.setup(ctx)
    res = job.window(ctx, st, 0.5, None)
    reasons = job.check(ctx, st)
    said = "\n".join(lines)
    assert reasons == [], said
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert 0 < ctx.counters["cat_splits"] <= ctx.counters["splits"]
    assert "sum |model - raw traversal| = 0" in said
    assert "with a left bin in bitset word 1 or beyond" in said


def test_the_job_refuses_a_library_without_its_surface(bench, monkeypatch):
    """The parent of this PR has neither scope: the job says so before any
    data is made, so the parent exits within seconds."""
    job = bench.load_module("jobs", "cat_train")
    monkeypatch.setattr(profiling, "SCOPES", tuple(
        s for s in profiling.SCOPES if s not in ("cat_search", "cat_route")))
    with pytest.raises(RuntimeError, match="cat_search"):
        job._surface()
    monkeypatch.undo()
    monkeypatch.delattr(binning, "device_cat_table")
    with pytest.raises(RuntimeError, match="device quantiser"):
        job._surface()


def test_the_cell_s_entries_fit_the_form():
    bench_json = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell, = [w for w in bench_json["workloads"]
             if w["name"] == "expo-cat.train"]
    cfg, = [c for c in bench_json["configs"] if c["name"] == "expo-cat-11m"]
    assert cell["config"] == "expo-cat-11m" and cell["chips"] == 1
    assert len(cell["why"]) <= 200 and len(cfg["source"]) <= 200
    assert cfg["reduced"] == ["num_boost_round", "data"]
    with open(os.path.join(REPO, cfg["file"])) as f:
        conf = json.load(f)
    assert set(conf["reduced"]) == set(cfg["reduced"])
    assert conf["data"]["rows"] == 11_000_000
    assert conf["data"]["categorical_feature"] == CATS
    assert [g["size"] for g in conf["data"]["groups"]] == \
        [12, 31, 7, 22, 313, 313]
    for k, v in {"num_leaves": 255, "max_bin": 255, "min_data_in_leaf": 100,
                 "max_cat_to_onehot": 4, "cat_smooth": 10.0, "cat_l2": 10.0,
                 "max_cat_threshold": 32, "min_data_per_group": 100}.items():
        assert conf["params"][k] == v, k
    with open(os.path.join(BENCH, "workloads", "expo-cat.train.json")) as f:
        wl = json.load(f)
    assert wl["job"] == "cat_train" and wl["why"] == cell["why"]
    new = ["cat_search_s_per_iter", "cat_route_s_per_iter",
           "cat_split_share", "cat_setup_bin_rows_s",
           "cat_hist_kernel_s_per_iter", "cat_hist_tiles_roofline"]
    listed = {m["name"]: m for m in bench_json["per_layer"]}
    for name in new:
        assert listed[name]["workloads"] == ["expo-cat.train"], name
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            assert json.load(f)["jobs"] == ["cat_train"], name
    # appended after the cells before it (later cells come after it)
    cells = [w["name"] for w in bench_json["workloads"]]
    for m in bench_json["end_to_end"]:
        if m["name"] in ("train_s_per_iter", "valid_auc"):
            listed_cells = m["workloads"]
            at = listed_cells.index("expo-cat.train")
            assert listed_cells[at - 1] == "epsilon.train"
    assert cells[cells.index("expo-cat.train") - 1] == "epsilon.train"
    names = [m["name"] for m in bench_json["per_layer"]]
    at = names.index(new[0])
    assert names[at:at + 6] == new


# -------------------------------------------- the numerical cells' steps
# sha256[:16] of the lowered fused step (StableHLO text, no locations) of
# three small numerical data sets, taken by the function below when the
# histogram statistics became channel-major (models/grower.py row_stats;
# the digests of before were taken on 1eb6aa2, before the categorical
# scopes, and held until then): the dense default path of higgs.train,
# msltr.train and epsilon.train, the bundled classic search of expo.train
# and the data-parallel step of criteo.train-dp4. The categorical scopes
# and the cat_route branch are traced only where a data set has a
# categorical column, so these programs hold none of them
_BEFORE = {"dense": "85631512b20ac0f8", "bundled": "11a04afc66700219",
           "data_parallel": "6ff41b063f3f7add"}


def _step_digest(kind):
    rng = np.random.default_rng(0)
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbosity": -1}
    if kind == "bundled":
        X, y = expo.make({**SPEC, "features": 314}, 1, 3000, 3000)
        p["bin_construct_sample_cnt"] = 1500
    else:
        X = rng.standard_normal((2048, 8)).astype(np.float32)
        y = (X[:, 0] + rng.logistic(size=2048) > 0).astype(np.float32)
        if kind == "data_parallel":
            p["tree_learner"] = "data"
    ds = lgb.Dataset(X, label=y, params=p).construct()
    gb = lgb.Booster(params=p, train_set=ds)._boosting
    step, bind = gb._fused_step_fn(gb._hist_method(), False)
    text = step.lower(*gb._fused_call_args(None, bind)).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("kind", sorted(_BEFORE))
def test_a_numerical_cell_s_step_is_the_program_of_before(kind):
    """A digest that moves with a jax upgrade is taken again; one that
    moves with an edit to the step is that edit's to justify."""
    assert _step_digest(kind) == _BEFORE[kind]
