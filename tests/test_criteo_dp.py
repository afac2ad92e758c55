"""The data-parallel learner on a row-sharded training set, on the virtual
CPU devices of conftest.py: a tiny draw of the benchmark's Criteo-shaped
data (67 columns, NaNs in the integer ones), rows no multiple of the mesh.

What the cell ``criteo.train-dp4`` holds on the chip at 24,000,000 rows
(benchmarks/jobs/dp_train.py), here at a few thousand: the construct bins
each shard of rows on the device that owns it and keeps no whole matrix,
the model is the serial learner's, the chips' own rows are a partition of
the data (benchmarks/reference_dp.py), the collective counters equal their
arithmetic from shapes, and the step's scope table names the collectives.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import binning, telemetry
from lightgbm_tpu.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    path = os.path.join(REPO, "benchmarks", *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


criteo = _load("data", "criteo.py")
reference = _load("reference.py")
reference_dp = _load("reference_dp.py")
reference_sparse = _load("reference_sparse.py")

ROWS = 3001
SPEC = {"features": 67, "sample_seed": 3, "missing_share": [0.01, 0.45],
        "categories": [300, 5000], "label_bias": -2.0,
        "label_effect_sd": 0.5, "label_ctr_columns": 6,
        "label_int_columns": [2, 7], "label_int_weight": 0.25,
        "label_missing_weight": -0.3}
PARAMS = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
DP = {**PARAMS, "tree_learner": "data"}

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs four virtual devices")


@pytest.fixture(scope="module")
def data():
    return criteo.make(SPEC, 11, ROWS, ROWS)


@pytest.fixture(scope="module")
def trained(data):
    X, y = data
    ds = lgb.Dataset(X, label=y)
    booster = lgb.train(DP, ds, 4, keep_training_booster=True)
    return ds, booster


def _tables(ds):
    bounds, real, nan_bin = [], [], []
    for j in ds.used_features:
        m = ds.mappers[int(j)]
        has_nan = m.missing_type == binning.MISSING_NAN
        r = m.num_bin - (1 if has_nan else 0)
        bounds.append(np.asarray(m.bin_upper_bound[:r - 1], np.float64))
        real.append(r)
        nan_bin.append(m.num_bin - 1 if has_nan else -1)
    return bounds, real, nan_bin


# ------------------------------------------------------------ the data
def test_criteo_draw_is_the_configurations_shape(data):
    X, y = data
    assert X.shape == (ROWS, 67) and X.dtype == np.float32
    gone = np.isnan(X).mean(axis=0)
    assert (gone[:13] > 0.002).all() and (gone[:13] < 0.5).all()
    assert not gone[13:].any()
    assert 0.0 <= X[:, 13:39].min() and X[:, 13:39].max() <= 1.0
    assert 0.02 < y.mean() < 0.5
    # a CTR column and its count column are one draw
    for j in (0, 7, 25):
        pairs = {(a, b) for a, b in zip(X[:, 13 + j], X[:, 39 + j])}
        assert len(pairs) == len(set(X[:, 13 + j]))


def test_criteo_seed_draws_the_order_of_the_training_rows_only():
    a, ya = criteo.make(SPEC, 1, 2000, 1500)
    b, yb = criteo.make(SPEC, 2, 2000, 1500)
    np.testing.assert_array_equal(a[1500:], b[1500:])
    np.testing.assert_array_equal(ya[1500:], yb[1500:])
    assert not np.array_equal(a[:1500], b[:1500], equal_nan=True)
    key = lambda X: np.sort(np.nan_to_num(X, nan=-1.0).sum(axis=1))  # noqa: E731
    np.testing.assert_allclose(key(a[:1500]), key(b[:1500]))
    assert ya[:1500].sum() == yb[:1500].sum()


# ------------------------------------------------------- the construct
def test_construct_keeps_one_shard_a_device_and_no_whole_matrix(trained):
    ds, booster = trained
    d = len(jax.devices())
    s = -(-ROWS // d)
    assert ds._bins is None, "something gathered the whole bin matrix"
    rb = ds.row_bins
    assert rb.shape == (s * d, 67) and rb.dtype == jnp.uint8
    assert len(rb.sharding.device_set) == d
    assert all(sh.data.shape == (s, 67) for sh in rb.addressable_shards)
    stats = ds.construct_stats
    assert stats["shard_rows_max"] == s
    assert stats["shard_rows_min"] == ROWS - (d - 1) * s
    assert stats["shard_place_s"] >= 0
    # the step's own constants: padded to the mesh, still one shard each
    gb = booster._boosting
    pb = gb._fused_parallel_bindings(gb._hist_method())
    f_pad = -67 % d
    assert pb["n_pad"] == s * d - ROWS and pb["f_pad"] == f_pad
    assert all(sh.data.shape == (s, 67 + f_pad)
               for sh in pb["bins"].addressable_shards)
    # no device holds more bin bytes than its shards of the matrix, its
    # padded copy and (where the method reads one) the transpose
    per_device, seen = {}, set()
    for a in jax.live_arrays():
        if a.dtype == jnp.uint8 and a.ndim == 2 and a.size >= ROWS:
            for sh in a.addressable_shards:
                # two arrays may share one buffer (a device_put in place)
                at = sh.data.unsafe_buffer_pointer()
                if at not in seen:
                    seen.add(at)
                    per_device[sh.device] = per_device.get(sh.device, 0) \
                        + sh.data.size
    assert per_device and max(per_device.values()) <= 3 * s * (67 + f_pad)


def test_padding_rows_are_bin_zero_and_the_rest_the_host_quantiser(trained,
                                                                   data):
    ds, _ = trained
    X, _y = data
    used = [ds.mappers[j] for j in ds.used_features]
    host = binning.bin_data(X[:, ds.used_features].astype(np.float64), used)
    whole = np.asarray(ds.row_bins)
    np.testing.assert_array_equal(whole[:ROWS], host)
    assert not whole[ROWS:].any()


@pytest.mark.parametrize("rows", [ROWS, 3000, 5])
def test_device_quantiser_bins_each_shard_where_it_lies(data, rows):
    """The chip's construct (float32 in, quantized on the devices), which
    the CPU's construct never takes: equal to the host quantiser, padding
    rows bin 0, one shard a device."""
    X, y = data
    ds = lgb.Dataset(X, label=y, params=PARAMS).construct()
    used = [ds.mappers[j] for j in ds.used_features]
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("shard",))
    Xu = np.ascontiguousarray(X[:rows, ds.used_features])
    got = binning.bin_data_device(Xu, used, block=256, mesh=mesh)
    s = -(-rows // 4)
    assert got.shape == (4 * s, len(used)) and got.dtype == jnp.uint8
    assert all(sh.data.shape == (s, len(used))
               for sh in got.addressable_shards)
    whole = np.asarray(got)
    np.testing.assert_array_equal(
        whole[:rows], binning.bin_data(Xu.astype(np.float64), used))
    assert not whole[rows:].any()
    np.testing.assert_array_equal(
        whole[:rows], np.asarray(binning.bin_data_device(Xu, used)))


def test_whole_matrix_is_gathered_only_when_asked(data):
    X, y = data
    ds = lgb.Dataset(X, label=y, params=DP).construct()
    assert ds._bins is None and ds.num_dense_columns() == 67
    whole = ds.bins
    assert whole.shape == (ROWS, 67)
    np.testing.assert_array_equal(np.asarray(whole),
                                  np.asarray(ds.row_bins)[:ROWS])
    ds.bins = None
    assert ds.row_bins is None and ds._bins is None


def test_a_validation_set_and_a_serial_learner_stay_whole(data):
    X, y = data
    serial = lgb.Dataset(X, label=y, params=PARAMS).construct()
    assert serial.row_bins is None and serial.bins.shape == (ROWS, 67)
    train = lgb.Dataset(X, label=y, params=DP)
    valid = lgb.Dataset(X[:500], label=y[:500], reference=train,
                        params=DP).construct()
    assert valid.row_bins is None and valid.bins.shape == (500, 67)


# ----------------------------------------------------------- the model
def _trees(booster):
    text = booster.model_to_string()
    return text, reference.parse_model(text)


def test_model_is_the_serial_learners(trained, data):
    """Tree for tree the same splits and the same rows a leaf; the values
    agree to float32 rounding: the mesh sums each histogram cell from one
    partial sum a device, the serial learner from one sum."""
    X, y = data
    _ds, booster = trained
    serial = lgb.train(PARAMS, lgb.Dataset(X, label=y), 4)
    text_d, trees_d = _trees(booster)
    text_s, trees_s = _trees(serial)
    assert len(trees_d) == len(trees_s) == 4
    for k, (a, b) in enumerate(zip(trees_d, trees_s)):
        for key in ("split_feature", "threshold", "left_child",
                    "right_child", "leaf_count", "internal_count"):
            np.testing.assert_array_equal(a[key], b[key], err_msg=key)
        np.testing.assert_array_equal(
            reference_sparse.tree_field(text_d, k, "decision_type"),
            reference_sparse.tree_field(text_s, k, "decision_type"))
        np.testing.assert_allclose(a["leaf_value"], b["leaf_value"],
                                   rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(booster.predict(X[:200], raw_score=True),
                               serial.predict(X[:200], raw_score=True),
                               rtol=2e-4, atol=1e-6)


def test_unfused_learner_reads_the_same_shards(trained, data):
    """A custom objective takes the per-phase path: the same constants,
    the same trees' structure, and still no whole matrix."""
    X, y = data

    def fobj(preds, dtrain):
        p = 1.0 / (1.0 + np.exp(-preds))
        return p - dtrain.get_label(), p * (1.0 - p)

    ds = lgb.Dataset(X, label=y)
    custom = lgb.train({**DP, "objective": "none",
                        "boost_from_average": False}, ds, 2, fobj=fobj)
    plain = lgb.train({**PARAMS, "boost_from_average": False},
                      lgb.Dataset(X, label=y), 2)
    assert ds._bins is None
    for a, b in zip(_trees(custom)[1], _trees(plain)[1]):
        np.testing.assert_array_equal(a["split_feature"], b["split_feature"])
        np.testing.assert_array_equal(a["leaf_count"], b["leaf_count"])


# ------------------------------------------------------- the reference
def test_chips_own_rows_are_a_partition_of_the_data(trained, data):
    ds, _ = trained
    X, y = data
    bounds, real, nan_bin = _tables(ds)
    B = int(ds.max_num_bins)
    hostT = reference_dp.host_bins(X[:, ds.used_features], bounds, nan_bin, 2)
    cnt, ysum = reference_dp.column_histograms(hostT, y, B, 2)
    shard_cnt, rows = [], 0
    for sh in ds.row_bins.addressable_shards:
        a = sh.index[0].start or 0
        own = np.asarray(sh.data)[:max(0, min(sh.data.shape[0], ROWS - a))]
        c, _ = reference_dp.column_histograms(
            np.ascontiguousarray(own.T), y[a:a + len(own)], B, 2)
        shard_cnt.append(c)
        rows += len(own)
    assert rows == ROWS
    assert reference_dp.partition_faults(shard_cnt, cnt) == 0
    assert (sum(shard_cnt).sum(axis=1) == ROWS).all()
    # a shard left out, or counted twice, is that many rows off
    assert reference_dp.partition_faults(shard_cnt[1:], cnt) \
        == shard_cnt[0][0].sum()
    assert reference_dp.partition_faults(shard_cnt + shard_cnt[:1], cnt) \
        == shard_cnt[0][0].sum()


def test_tree_zero_against_the_reference(trained, data):
    ds, booster = trained
    X, y = data
    conf = Config.from_params(DP)
    bounds, real, nan_bin = _tables(ds)
    hostT = reference_dp.host_bins(X[:, ds.used_features], bounds, nan_bin, 2)
    cnt, ysum = reference_dp.column_histograms(hostT, y,
                                               int(ds.max_num_bins), 2)
    gain, _f, _t, _left, _cl = reference_dp.root_split(
        cnt, ysum, y, real, nan_bin, conf.min_data_in_leaf,
        conf.min_sum_hessian_in_leaf)
    text = booster.model_to_string(num_iteration=1)
    tree = reference.parse_model(text)[0]
    dtype = reference_sparse.tree_field(text, 0, "decision_type").astype(int)
    gain_sys, left = reference_dp.gain_of_raw_split(
        X[:, int(tree["split_feature"][0])], y, float(tree["threshold"][0]),
        dtype[0], conf.min_data_in_leaf, conf.min_sum_hessian_in_leaf)
    assert (gain - gain_sys) / gain <= 1e-3
    assert left == reference.child_count(tree, int(tree["left_child"][0]))
    leaf = reference_dp.leaf_index(tree, dtype, X, 2)
    np.testing.assert_array_equal(
        np.bincount(leaf, minlength=tree["num_leaves"]), tree["leaf_count"])
    want = reference_sparse.leaf_values(
        tree, leaf, y, float(reference_sparse.tree_field(text, 0,
                                                          "shrinkage")[0]))
    assert np.median(np.abs(tree["leaf_value"] - want)) <= 1e-5


def test_reference_root_split_tries_missing_values_on_both_sides():
    """Against a brute force over every (threshold, direction)."""
    rng = np.random.default_rng(5)
    n = 400
    x = rng.integers(0, 6, n).astype(np.float64)
    x[rng.random(n) < 0.3] = np.nan
    y = ((np.isnan(x) | (x > 3)) ^ (rng.random(n) < 0.1)).astype(np.float32)
    bounds, nan_bin = [np.arange(5) + 0.5], [6]
    binsT = reference_dp.host_bins(x[:, None].astype(np.float32), bounds,
                                   nan_bin, 1)
    cnt, ysum = reference_dp.column_histograms(binsT, y, 7, 1)
    gain, f, t, nan_left, cl = reference_dp.root_split(
        cnt, ysum, y, [6], nan_bin, 1.0, 1e-3)
    best = max(
        (reference_dp.gain_of_raw_split(x, y, thr, 8 | (2 if left else 0),
                                        1.0, 1e-3)[0], thr, left)
        for thr in np.arange(6) + 0.5 for left in (False, True))
    assert f == 0 and gain == pytest.approx(best[0], rel=1e-12)
    assert (t + 0.5, nan_left) == (best[1], best[2]) or gain == best[0]
    # a NaN follows the printed direction only where the node says NaN
    v = np.array([np.nan, 0.0, 2.0])
    assert reference_dp.go_left(v, 1.0, 8 | 2).tolist() == [True, True, False]
    assert reference_dp.go_left(v, 1.0, 8).tolist() == [False, True, False]
    assert reference_dp.go_left(v, 1.0, 2).tolist() == [True, True, False]
    assert reference_dp.go_left(v, -1.0, 0).tolist() == [False, False, False]


# -------------------------------------------------- counters and scopes
def test_collective_counters_equal_their_arithmetic_from_shapes(trained):
    ds, booster = trained
    gb = booster._boosting
    d = len(jax.devices())
    hm = gb._hist_method()
    kw = gb._parallel_grow_statics(hm)
    pb = gb._fused_parallel_bindings(hm)
    n_pad_rows, f_pad = pb["bins"].shape
    slots = min(kw["tile_leaves"], kw["max_leaves"]) \
        if hm.startswith(("onehot", "pallas")) else kw["max_leaves"]
    # every pass streams all the mesh's rows, and moves one tile's planes
    # to their owners: [slots, F_pad, bins, 3] float32 over d
    passes = gb.rows_streamed_total / n_pad_rows
    assert passes == int(passes) and passes >= 4
    tile_bytes = slots * f_pad * kw["num_bins"] * 3 * 4
    assert gb.coll_bytes_total == passes * tile_bytes / d
    # one best-split sync a search round, one round a pass here
    assert gb.split_sync_calls_total == passes
    serial = lgb.train(PARAMS, lgb.Dataset(np.asarray(ds.row_bins)[:500, :5]
                                           .astype(np.float32),
                                           label=np.arange(500) % 2), 2,
                       keep_training_booster=True)
    assert serial._boosting.split_sync_calls_total == 0
    assert serial._boosting.coll_bytes_total == 0


def test_collective_scopes_are_in_the_steps_scope_table(trained):
    _ds, booster = trained
    assert booster is not None
    scopes = {scope for table in telemetry.scope_table().values()
              for scope in table.values()}
    assert {"hist_allreduce", "split_sync", "hist_pass",
            "apply_split"} <= scopes
