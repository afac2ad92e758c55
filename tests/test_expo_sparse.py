"""The Expo-shaped job at a few thousand rows, on the CPU: one-hot groups
with Zipf member frequencies and two numeric columns, handed over as a scipy
CSR matrix. The default path (EFB bundles, a (row, bin) stream column, the
classic bundled search) against the plain reference over the RAW columns
(tests/reference_sparse.py) and against the same data trained dense and
unbundled; the construct's counters against direct counts; the device
storage decoded back to the host quantiser's bins.

The data come from the benchmark's own generator (benchmarks/data/expo.py)
and the model text is read by the benchmark's own parser
(benchmarks/reference.py), loaded by path.
"""

import hashlib
import importlib.util
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import binning, telemetry
from lightgbm_tpu.config import Config
from lightgbm_tpu.utils import profiling

import hlo_text
import reference_sparse

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    path = os.path.join(REPO, "benchmarks", *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


expo = _load("data", "expo.py")
reference = _load("reference.py")

ROWS = 6000
# 200 origins: the commonest 127 fill one bundle (255 bins) and the rare
# rest a second one that sits over 90% on bin 0, the stream column; 40
# destinations bundle beside each other
SPEC = {
    "features": 314, "sample_seed": 1, "label_bias": -1.9,
    "time_effect": 1.6, "distance_effect": 0.1,
    "groups": [
        {"name": "month", "size": 12, "exponent": 0.05, "effect": 0.15},
        {"name": "day", "size": 31, "exponent": 0.02, "effect": 0.05},
        {"name": "weekday", "size": 7, "exponent": 0.05, "effect": 0.12},
        {"name": "carrier", "size": 22, "exponent": 1.0, "offset": 2.0,
         "effect": 0.25},
        {"name": "origin", "size": 200, "exponent": 1.6, "offset": 4.0,
         "effect": 0.3},
        {"name": "destination", "size": 40, "exponent": 1.6, "offset": 4.0,
         "effect": 0.2}]}
PARAMS = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 3,
          "verbosity": -1}
MIN_HESS = 1e-3          # the default min_sum_hessian_in_leaf
# bundles are found on a sample: with one of 1,500 of the 6,000 rows, two
# members that never met in the sample meet in the rest
SAMPLED = {"bin_construct_sample_cnt": 1500}


@pytest.fixture(scope="module")
def data():
    X, y = expo.make(SPEC, 1, ROWS, ROWS)
    return X, y


def _dataset(data, extra=None):
    X, y = data
    ds = lgb.Dataset(X, label=y, params={**PARAMS, **(extra or {})})
    return ds.construct()


@pytest.fixture(scope="module")
def exact(data):
    """The default path with every row in the sample: no conflict row."""
    return _dataset(data)


@pytest.fixture(scope="module")
def sampled(data):
    return _dataset(data, SAMPLED)


def _host_bins(ds, X):
    """The host quantiser's bins, one column a used feature."""
    used = [ds.mappers[int(j)] for j in ds.used_features]
    return binning.bin_data(
        X.toarray()[:, ds.used_features].astype(np.float64), used)


def _conflict_rows(ds, host):
    """Rows in which two members of one bundle are both off their
    most-frequent bin, counted straight from the unbundled bins."""
    mode = np.array([ds.mappers[int(j)].most_freq_bin
                     for j in ds.used_features])
    off = host != mode[None, :]
    bad = np.zeros(len(host), dtype=bool)
    for bd in ds.bundles:
        if len(bd.members) > 1:
            bad |= off[:, bd.members].sum(axis=1) > 1
    return bad


def _trees(booster):
    return reference.parse_model(booster.model_to_string())


def _structure(tree):
    return {k: tree[k].tolist() for k in
            ("split_feature", "threshold", "left_child", "right_child",
             "leaf_count")}


def test_the_data_set_has_bundles_a_stream_and_no_conflict(exact):
    stats = exact.construct_stats
    assert set(stats) == {
        "efb_used_features", "efb_columns", "efb_bundle_bins",
        "efb_conflict_rows", "sparse_stream_columns",
        "sparse_stream_entries", "sparse_stream_slots", "efb_fit_mappers_s",
        "efb_find_bundles_s", "efb_place_s", "sparse_extract_s"}
    multi = [b for b in exact.bundles if len(b.members) > 1]
    assert len(multi) >= 6 and stats["efb_columns"] == len(exact.bundles)
    assert stats["efb_columns"] < stats["efb_used_features"] / 20
    assert stats["efb_bundle_bins"] == sum(b.num_bin for b in multi)
    assert stats["sparse_stream_columns"] == len(exact.sp_cols) >= 1
    # the layout keeps no padding and no tail: a slot an entry
    assert stats["sparse_stream_slots"] == stats["sparse_stream_entries"] \
        == exact.sp_rows.shape[0] == exact.sp_cell.shape[0] \
        == sum(len(exact.stream_column(i)[0])
               for i in range(len(exact.sp_cols)))
    assert stats["efb_conflict_rows"] == 0
    assert all(stats[k] >= 0.0 for k in stats if k.endswith("_s"))


def test_a_dense_construct_reports_nothing(data):
    X, y = data
    ds = lgb.Dataset(X[:600].toarray(), label=y[:600], params=PARAMS)
    assert ds.construct_stats is None
    assert ds.construct().construct_stats is None


def test_first_tree_equals_the_reference_over_the_raw_columns(data, exact):
    """Root split and every leaf count of tree 0: the default path
    (bundles, a stream, the classic bundled search) against float64 numpy
    over the 314 original columns."""
    X, y = data
    booster = lgb.train(PARAMS, exact, 1)
    tree = _trees(booster)[0]
    Xc = X.tocsc()
    bounds = [None if m.is_trivial else m.bin_upper_bound
              for m in exact.mappers]
    gain, f_ref, t_ref, left_ref = reference_sparse.root_split(
        Xc, y, bounds, PARAMS["min_data_in_leaf"], MIN_HESS)
    assert int(tree["split_feature"][0]) == f_ref
    assert float(tree["threshold"][0]) == t_ref
    gain_sys, left_raw = reference_sparse.gain_of_raw_split(
        Xc, f_ref, y, t_ref, PARAMS["min_data_in_leaf"], MIN_HESS)
    assert gain_sys == pytest.approx(gain, rel=1e-12)
    assert reference.child_count(tree, int(tree["left_child"][0])) \
        == left_raw == left_ref
    assert tree["num_leaves"] == 15
    np.testing.assert_array_equal(reference_sparse.leaf_counts(tree, Xc),
                                  tree["leaf_count"])


def test_default_path_equals_dense_unbundled_tree_for_tree(data, exact):
    """No conflict row: bundling and stream storage change no tree. The
    same data trained from a dense matrix with both switched off grows the
    same splits, thresholds, children and leaf counts. The draw matters as
    in tests/test_efb.py: a stream's default bin is the leaf total less
    the entries, a float32 sum in another order, and with leaves of three
    rows a near-tie may swap (``sample_seed`` 0 swaps one split of 42)."""
    X, y = data
    plain = {**PARAMS, "enable_bundle": False, "is_enable_sparse": False}
    ds = lgb.Dataset(X.toarray(), label=y, params=plain).construct()
    assert ds.bundles is None and not ds.has_sparse_cols
    a = _trees(lgb.train(PARAMS, exact, 3))
    b = _trees(lgb.train(plain, ds, 3))
    assert len(a) == len(b) == 3
    for ta, tb in zip(a, b):
        assert _structure(ta) == _structure(tb)
        np.testing.assert_allclose(ta["leaf_value"], tb["leaf_value"],
                                   rtol=1e-4, atol=2e-5)


def test_conflict_rows_are_counted_and_bound_the_leaf_counts(data, sampled):
    """Bundles found on a sample: the counter equals a direct count over
    ALL rows, and the first tree's leaf counts are off from the raw
    traversal by no more than that many rows."""
    X, _y = data
    conflicts = int(_conflict_rows(sampled, _host_bins(sampled, X)).sum())
    assert conflicts > 0
    assert sampled.construct_stats["efb_conflict_rows"] == conflicts
    tree = _trees(lgb.train({**PARAMS, **SAMPLED}, sampled, 1))[0]
    ref = reference_sparse.leaf_counts(tree, X.tocsc())
    off = int(np.abs(ref - tree["leaf_count"]).sum())
    assert ref.sum() == tree["leaf_count"].sum() == ROWS
    assert off <= 2 * conflicts


@pytest.mark.parametrize("start,stop", [(0, ROWS), (1000, 2500)])
def test_device_storage_decodes_to_the_host_quantiser(data, exact, start,
                                                      stop):
    X, _y = data
    assert exact.has_sparse_cols and exact.bins.shape[1] \
        == exact.construct_stats["efb_columns"] - len(exact.sp_cols)
    np.testing.assert_array_equal(exact.unbundled_bins(start, stop),
                                  _host_bins(exact, X[start:stop]))


def test_decoded_storage_differs_only_in_conflict_rows(data, sampled):
    X, _y = data
    host = _host_bins(sampled, X)
    differ = (sampled.unbundled_bins(0, ROWS) != host).any(axis=1)
    assert differ.any()
    assert not (differ & ~_conflict_rows(sampled, host)).any()


@pytest.fixture(scope="module")
def fused_step(exact, tmp_path_factory):
    """Two iterations on the default path with the flight recorder on:
    (the compiled fused step's text, the telemetry directory)."""
    directory = str(tmp_path_factory.mktemp("telemetry"))
    params = {**PARAMS, "telemetry_dir": directory}
    gb = lgb.train(params, exact, 2, keep_training_booster=True)._boosting
    assert not gb._split_fusion_on(gb._hist_method())
    (step, bind), = gb._fused_cache.values()
    text = step.lower(*gb._fused_call_args(None, bind)).compile().as_text()
    # the scope table weakly holds the booster's programs: read it here
    scopes = set(telemetry.scope_table()["jit__fused_step"].values())
    return text, directory, scopes


def test_the_step_names_the_sparse_scopes_and_the_header_the_counts(
        exact, fused_step):
    """``sparse_hist`` (under ``hist_pass``) and ``sparse_route`` (under
    ``apply_split``) are in the compiled fused step and in the scope
    table; the flight recorder's header carries the construct's counts."""
    text, directory, scopes = fused_step
    assert 'hist_pass/sparse_hist/' in text
    assert 'apply_split/sparse_route/' in text
    assert {"sparse_hist", "sparse_route"} <= scopes
    recs, errors = telemetry.validate_flight_jsonl(
        os.path.join(directory, "flight_rank0.jsonl"))
    assert errors == [] and recs[0]["type"] == "run"
    header = recs[0]["context"]["construct"]
    assert header["efb_columns"] == exact.construct_stats["efb_columns"]
    assert header["sparse_stream_entries"] > 0


def test_only_a_split_on_a_stream_column_rebuilds_the_column(fused_step):
    """The routing is a conditional on the split's own column. Its stream
    branch holds the scatter, under ``apply_split/sparse_route/``; its
    dense branch reads the dense matrix and holds nothing of the stream;
    nothing of ``sparse_route`` is outside that branch, so a split on a
    dense column pays nothing for the streams; and no index is sorted
    (a stream's rows ascend)."""
    text = fused_step[0]
    dense, stream = hlo_text.route_branches(text)
    assert any("apply_split/sparse_route/" in ln and " scatter(" in ln
               for ln in stream)
    assert not any("sparse_route" in ln or " scatter(" in ln for ln in dense)
    assert any("dynamic-slice(" in ln or " gather(" in ln for ln in dense)
    everywhere = [ln for ln in text.splitlines() if "sparse_route" in ln]
    assert len(everywhere) == len([ln for ln in stream
                                   if "sparse_route" in ln])
    assert not any(" sort(" in ln for ln in everywhere)


def test_leaf_values_of_the_first_tree_equal_float64_sums(data, exact):
    """Every leaf value of tree 0 against the reference's float64 sums of
    the gradients and hessians of the rows a raw traversal sends there:
    what the benchmark reads the histograms' precision from."""
    X, y = data
    text = lgb.train(PARAMS, exact, 1).model_to_string()
    tree = reference.parse_model(text)[0]
    leaf = reference_sparse.leaf_index(tree, X.tocsc())
    np.testing.assert_array_equal(
        reference_sparse.leaf_counts(tree, X.tocsc(), leaf),
        tree["leaf_count"])
    shrinkage = reference_sparse.tree_field(text, 0, "shrinkage")
    assert shrinkage == pytest.approx([0.1])
    want = reference_sparse.leaf_values(tree, leaf, y, float(shrinkage[0]))
    np.testing.assert_allclose(tree["leaf_value"], want, rtol=0, atol=2e-5)
    gains = reference_sparse.tree_field(text, 0, "split_gain")
    assert len(gains) == tree["num_leaves"] - 1 and (gains > 0).all()


def _stream_members(ds):
    return [np.array(sorted(int(ds.used_features[m])
                            for m in ds.bundles[int(c)].members))
            for c in ds.sp_cols]


def _stream_label(data, ds):
    """The benchmark's probe label (jobs/sparse_train._probe_label) at a
    small size: only members of the stream column explain it (every second
    member, its share of ones falling with its rows so that all offer the
    same gain; a quarter of the other rows)."""
    Xc = data[0].tocsc()
    (cols,) = _stream_members(ds)
    chosen = cols[::2]
    rows = np.diff(Xc.indptr)[chosen]
    q = np.full(ROWS, 0.25)
    for j, k in sorted(zip(chosen, rows), key=lambda jk: -jk[1]):
        q[Xc.indices[Xc.indptr[j]:Xc.indptr[j + 1]]] = \
            0.25 + 0.75 * np.sqrt(rows.min() / k)
    return (np.random.default_rng(5).random(ROWS) < q).astype(np.float32)


def test_a_label_of_stream_members_is_split_on_the_stream(data, exact):
    """A label that only members of the stream column explain puts a
    stream member at the root and at several splits, so the stream's
    planes decide the search and its side of ``_apply_split`` routes the
    rows; root and every leaf count equal the reference's over the raw
    columns."""
    X, _y = data
    Xc = X.tocsc()
    (cols,) = _stream_members(exact)
    y2 = _stream_label(data, exact)
    exact.set_label(y2)
    try:
        tree = _trees(lgb.train(PARAMS, exact, 1))[0]
    finally:
        exact.set_label(data[1])
    bounds = [None if m.is_trivial else m.bin_upper_bound
              for m in exact.mappers]
    _gain, f_ref, t_ref, left_ref = reference_sparse.root_split(
        Xc, y2, bounds, PARAMS["min_data_in_leaf"], MIN_HESS)
    assert f_ref in cols
    assert (int(tree["split_feature"][0]), float(tree["threshold"][0])) \
        == (f_ref, t_ref)
    assert reference.child_count(tree, int(tree["left_child"][0])) \
        == left_ref
    assert np.isin(tree["split_feature"], cols).sum() >= 4
    np.testing.assert_array_equal(reference_sparse.leaf_counts(tree, Xc),
                                  tree["leaf_count"])


def _trees_sha(booster):
    """sha256 of the model text's trees: all of it before the parameter
    block, which names the storage asked for."""
    text = booster.model_to_string()
    return hashlib.sha256(
        text[:text.index("\nparameters:")].encode()).hexdigest()


@pytest.mark.parametrize("label", ["real", "stream-members"])
@pytest.mark.parametrize("extra", [
    {}, {"tree_growth_mode": "exact"}, {"enable_bundle": False}],
    ids=["round-loop", "exact", "unbundled"])
def test_stream_storage_grows_the_all_dense_trees(data, exact, extra, label):
    """Both branches of the routing give the row the leaf the dense column
    gives it. On the real label the trees split on dense columns; the
    stream members' label splits on the stream. In the round loop and in
    the ``exact`` mode's ``lax.cond``, three trees equal, by the hash of
    their text, those of the same data with every column dense
    (``is_enable_sparse=false``): a bundle's bin 0 is never searched, so
    the streams' planes give the very sums. Without bundles (258
    single-feature streams) the search reads a stream's default bin, the
    leaf total less the entries in float32, and near-ties may swap: there
    every leaf count of every tree equals a traversal of the raw values."""
    X, y = data
    if label == "stream-members":
        y = _stream_label(data, exact)
    boosters = []
    for storage in ({}, {"is_enable_sparse": False}):
        params = {**PARAMS, **extra, **storage}
        ds = lgb.Dataset(X, label=y, params=params).construct()
        assert ds.has_sparse_cols == (not storage)
        boosters.append(lgb.train(params, ds, 3))
    if "enable_bundle" not in extra:
        assert _trees_sha(boosters[0]) == _trees_sha(boosters[1])
        return
    Xc = X.tocsc()
    for tree in _trees(boosters[0]):
        np.testing.assert_array_equal(
            reference_sparse.leaf_counts(tree, Xc), tree["leaf_count"])


def test_stream_rows_ascend(data, exact):
    """What the routing's scatter counts on (``indices_are_sorted``): a
    stream's rows ascend strictly, and the widest stream is stored last,
    so a slice of its length from any stream's start stays inside the
    arrays. One stream, and 258 of very unequal length in one
    concatenation: no slot without an entry."""
    for ds in (exact, _dataset(data, {"enable_bundle": False})):
        lengths = np.diff(ds.sp_offsets)
        assert lengths.min() >= 1 and (np.diff(lengths) >= 0).all()
        assert ds.sp_offsets[0] == 0 \
            and ds.sp_offsets[-1] == ds.sp_rows.shape[0]
        assert ds.construct_stats["sparse_stream_slots"] \
            == ds.construct_stats["sparse_stream_entries"] \
            == int(lengths.sum())
        for i in range(len(ds.sp_cols)):
            rows, vals = ds.stream_column(i)
            assert (np.diff(rows) > 0).all() and rows[-1] < ROWS
            assert (vals != int(ds.sp_default[i])).all()
    assert len(lengths) == 258 and lengths[-1] >= 20 * lengths[0]


@pytest.mark.parametrize("extra", [{}, {"enable_bundle": False}],
                         ids=["bundled", "unbundled"])
def test_the_host_readers_rebuild_the_all_dense_matrix(data, extra):
    """``Dataset.unbundled_bins`` and the booster's traversal bins read the
    streams through ``Dataset.stream_column``: both give the matrix of the
    same data stored all dense (``is_enable_sparse=false``), whole and by
    slice, with one stream and with 258."""
    ds = _dataset(data, extra)
    plain = _dataset(data, {**extra, "is_enable_sparse": False})
    assert ds.has_sparse_cols and not plain.has_sparse_cols
    np.testing.assert_array_equal(ds.unbundled_bins(0, ROWS),
                                  plain.unbundled_bins(0, ROWS))
    np.testing.assert_array_equal(ds.unbundled_bins(1234, 1300),
                                  plain.unbundled_bins(0, ROWS)[1234:1300])
    gb = lgb.Booster(params={**PARAMS, **extra}, train_set=ds)._boosting
    np.testing.assert_array_equal(np.asarray(gb._traversal_bins(ds)),
                                  np.asarray(plain.bins))


@pytest.fixture
def timetag():
    was = profiling.enabled()
    profiling.enable(True)
    profiling.reset()
    yield
    profiling.reset()
    profiling.enable(was)


def test_the_stream_splits_are_counted_where_the_tree_reaches_the_host(
        data, exact, timetag, tmp_path):
    """``sparse_route_stream_splits`` is the number of splits that took
    the routing's stream branch, ``sparse_route_splits`` the number in
    all: against the model text's ``split_feature`` entries, after
    training on the stream members' label; the flight recorder's last
    iteration record carries both. A data set without stream columns
    counts neither."""
    (cols,) = _stream_members(exact)
    exact.set_label(_stream_label(data, exact))
    try:
        booster = lgb.train({**PARAMS, "telemetry_dir": str(tmp_path)},
                            exact, 3, keep_training_booster=True)
        booster._boosting.host_trees          # fetch what is pending
        counts = profiling.counters()
        booster._boosting._flush_flight("test")
    finally:
        exact.set_label(data[1])
    trees = _trees(booster)
    on_stream = sum(int(np.isin(t["split_feature"], cols).sum())
                    for t in trees)
    assert on_stream >= 4
    assert counts["sparse_route_stream_splits"] == on_stream
    assert counts["sparse_route_splits"] == sum(
        len(t["split_feature"]) for t in trees)
    recs, errors = telemetry.validate_flight_jsonl(
        os.path.join(str(tmp_path), "flight_rank0.jsonl"))
    assert errors == []
    last = [r for r in recs if r["type"] == "iter"][-1]
    assert 0 < last["route_stream_splits"] <= on_stream
    assert last["route_splits"] >= last["route_stream_splits"]

    profiling.reset()
    X, y = data
    plain = {**PARAMS, "is_enable_sparse": False}
    lgb.train(plain, lgb.Dataset(X, label=y, params=plain), 2,
              keep_training_booster=True)._boosting.host_trees
    assert not {"sparse_route_stream_splits", "sparse_route_splits"} \
        & set(profiling.counters())


def test_kept_positions_hold_their_rows_and_with_them_the_storage():
    """``make(..., keep=positions)``: the rows at the positions the library
    samples stay in place on every seed and the others are permuted among
    themselves, so bundles, streams and conflict rows, found on the
    sample, are the data set's and not the row order's."""
    keep = binning.sample_indices(ROWS, SAMPLED["bin_construct_sample_cnt"],
                                  Config().data_random_seed)
    plain, yp = expo.make(SPEC, 3, ROWS, 0)
    sets = []
    for seed in (3, 2 ** 31 + 7):
        X, y = expo.make(SPEC, seed, ROWS, ROWS, keep=keep)
        assert (X[keep] != plain[keep]).nnz == 0 and (y[keep] == yp[keep]).all()
        assert (X != plain).nnz > 0
        assert sorted(y.tolist()) == sorted(yp.tolist())
        np.testing.assert_array_equal(
            np.sort(np.asarray(X.sum(axis=0)).ravel()),
            np.sort(np.asarray(plain.sum(axis=0)).ravel()))
        ds = lgb.Dataset(X, label=y, params={**PARAMS, **SAMPLED}).construct()
        sets.append({k: v for k, v in ds.construct_stats.items()
                     if not k.endswith("_s")}
                    | {"members": [b.members for b in ds.bundles],
                       "streams": None if ds.sp_cols is None
                       else ds.sp_cols.tolist()})
    assert sets[0] == sets[1] and sets[0]["efb_conflict_rows"] > 0
    free, _y = expo.make(SPEC, 2 ** 31 + 7, ROWS, ROWS)
    moved = lgb.Dataset(free, label=_y,
                        params={**PARAMS, **SAMPLED}).construct()
    assert moved.construct_stats["efb_conflict_rows"] \
        != sets[0]["efb_conflict_rows"]
