"""Fused split-finding epilogue + level-batched frontier growth (ISSUE 12).

The fusion contract under test, at three levels:

- UNIT: numerical_candidates + candidates_to_splitinfo reproduce
  find_best_splits bit-for-bit on the numerical non-bundled search, and
  the Pallas epilogue kernel (interpret) matches the XLA twin bit-for-bit
  — including in-pass sibling derivation (parent - computed), exact on
  representable sums.
- E2E: split_fusion=on model text is BIT-IDENTICAL to split_fusion=off
  across the split-semantics edge-config matrix (monotone, missing both
  directions, min_data/min_hessian, l1/path-smooth/max-delta, subset
  bagging, interactions, exact mode, q8), on both the XLA twin (scatter)
  and the in-kernel path (pallas interpret).
- GATING: "auto" falls back to the classic phase for the configurations
  whose semantics stay in find_best_splits (categorical, EFB, forced
  splits, CEGB, extra_trees) — still training correctly — while "on"
  refuses them loudly; a trainer state from a run that timed its kernels
  restores onto the rule's plan; the phased grower is bit-identical and
  launches one histogram pass per frontier LEVEL, not per leaf.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.binning import MISSING_NONE
from lightgbm_tpu.config import Config
from lightgbm_tpu.ops import pallas_hist
from lightgbm_tpu.ops.histogram import (histogram_tiles,
                                        histogram_tiles_with_candidates)
from lightgbm_tpu.ops.split import (CAND_CHANNELS, FeatureMeta, SplitParams,
                                    candidates_to_splitinfo,
                                    find_best_splits, numerical_candidates)

pytestmark = pytest.mark.pallas


# ------------------------------------------------------------------- unit

def _rand_hist(rng, L, F, B):
    h = rng.rand(L, F, B, 3).astype(np.float32)
    h[..., 2] = rng.randint(0, 50, size=(L, F, B)).astype(np.float32)
    h[..., 1] = np.abs(h[..., 1]) * h[..., 2]
    return jnp.asarray(h)


def _meta(F, B, missing=MISSING_NONE, monotone=None):
    from lightgbm_tpu.binning import MISSING_NAN, MISSING_ZERO
    mt = {"none": MISSING_NONE, "nan": MISSING_NAN,
          "zero": MISSING_ZERO}[missing] if isinstance(missing, str) \
        else missing
    return FeatureMeta(
        num_bins=jnp.full((F,), B, jnp.int32),
        missing_type=jnp.full((F,), mt, jnp.int32),
        default_bin=jnp.full((F,), 1, jnp.int32),
        is_categorical=jnp.zeros((F,), bool),
        monotone=(jnp.zeros((F,), jnp.int8) if monotone is None
                  else jnp.asarray(monotone, jnp.int8)),
        penalty=jnp.ones((F,), jnp.float32))


@pytest.mark.parametrize("missing", ["none", "nan", "zero"])
@pytest.mark.parametrize("mono", [None, [1, -1, 0, 1]])
def test_candidates_match_find_best_splits(missing, mono):
    """The shared scan + table consumer == find_best_splits, field by
    field, bit for bit — the factored code paths cannot drift."""
    rng = np.random.RandomState(3)
    L, F, B = 6, 4, 17
    hist = _rand_hist(rng, L, F, B)
    sum_g = jnp.asarray(hist[:, 0, :, 0].sum(axis=1))
    sum_h = jnp.asarray(hist[:, 0, :, 1].sum(axis=1))
    cnt = jnp.asarray(hist[:, 0, :, 2].sum(axis=1))
    out = jnp.asarray(rng.randn(L).astype(np.float32) * 0.1)
    depth = jnp.asarray(rng.randint(0, 3, L).astype(np.int32))
    meta = _meta(F, B, missing, mono)
    p = SplitParams.from_config(Config.from_params(
        {"min_data_in_leaf": 5, "min_sum_hessian_in_leaf": 1e-3,
         "lambda_l1": 0.1, "lambda_l2": 0.3, "path_smooth": 1.5,
         "max_delta_step": 0.8}))
    with_mono = mono is not None
    lmin = (jnp.full((L,), -0.5) if with_mono else None)
    lmax = (jnp.full((L,), 0.5) if with_mono else None)
    fmask = jnp.ones((L, F), jnp.float32)

    ref = find_best_splits(hist, sum_g, sum_h, cnt, out, depth, meta, p,
                           fmask, max_depth=4,
                           leaf_min=lmin, leaf_max=lmax)
    cand = numerical_candidates(
        hist, sum_g, sum_h, cnt, out, meta.num_bins, meta.missing_type,
        meta.default_bin, meta.monotone.astype(jnp.int32), p,
        with_monotone=with_mono, leaf_min=lmin, leaf_max=lmax)
    assert cand.shape == (L, F, CAND_CHANNELS)
    got = candidates_to_splitinfo(
        cand, sum_g, sum_h, cnt, out, depth, meta, p, fmask, max_depth=4,
        with_monotone=with_mono, leaf_min=lmin, leaf_max=lmax)
    for name in ("gain", "feature", "threshold", "default_left",
                 "left_sum_g", "left_sum_h", "left_count", "right_sum_g",
                 "right_sum_h", "right_count", "left_output",
                 "right_output"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)), np.asarray(getattr(ref, name)),
            err_msg=name)


def _epi_inputs(n=3001, f=5, b=63, seed=0, int8=False):
    """Representable (or int8) stats with a POSITIVE hessian channel —
    real training stats, so every leaf has valid split candidates."""
    rng = np.random.RandomState(seed)
    binsT = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    if int8:
        stats = rng.randint(-127, 128, size=(n, 3)).astype(np.int8)
        stats[:, 1] = rng.randint(1, 128, size=n)
        stats[:, 2] = 1
    else:
        stats = (rng.randint(-1023, 1024, size=(n, 3)) / 1024.0
                 ).astype(np.float32)
        stats[:, 1] = rng.randint(1, 1024, size=n) / 1024.0
        stats[:, 2] = 1.0
    leaf = rng.randint(0, 3, n).astype(np.int32)
    return binsT, np.ascontiguousarray(binsT.T), stats, leaf


@pytest.mark.parametrize("mode,method,with_mono", [
    ("highest", "pallas", False),
    ("highest", "pallas", True),
    ("q8", "pallas_q8", False)])
def test_epilogue_kernel_matches_xla_twin_and_derives_exactly(mode, method,
                                                              with_mono):
    """The in-kernel epilogue == the XLA twin bit-for-bit on representable
    sums, AND the derived sibling's plane (parent - computed, the second
    lane group on the slot's own lanes) equals its directly-built
    histogram exactly."""
    n, f, b = 3001, 5, 63
    P = 6
    binsT, bins, stats, leaf = _epi_inputs(int8=(mode == "q8"))
    jb, jbT = jnp.asarray(bins), jnp.asarray(binsT)
    jst, jl = jnp.asarray(stats), jnp.asarray(leaf)
    # slot 0: leaf 0 computed, its sibling leaf 1 derived from it; slot 1:
    # leaf 2 computed alone (nothing to derive); the other slots idle
    sel = jnp.asarray(np.array([0, 2, -1, -1, -1, -1], np.int32))
    sel_derived = jnp.asarray(np.array([1, -1, -1, -1, -1, -1], np.int32))
    # the parent's plane (leaves 0+1 merged) — f32, as resident in the
    # grower's state after dequantization
    parent_leaf = jnp.asarray(np.where(np.isin(leaf, [0, 1]), 0, 2)
                              .astype(np.int32))
    st_f = jnp.asarray(stats.astype(np.float32))
    hp = histogram_tiles(jb, st_f, parent_leaf, jnp.asarray([0], jnp.int32),
                         b, method="scatter")
    parent = jnp.zeros((P, f, b, 3), jnp.float32).at[0].set(hp[0])

    # leaf aggregates by group: computed slots, then their derived leaves
    sums = np.zeros((2, P, 3), np.float32)
    for (g, q), lv in {(0, 0): 0, (0, 1): 2, (1, 0): 1}.items():
        sums[g, q] = stats[leaf == lv].astype(np.float64).sum(0)
    la = jnp.stack([pallas_hist.pack_leaf_aux(
        *(jnp.asarray(sums[g, :, i]) for i in range(3)), jnp.zeros((P,)),
        leaf_min=jnp.full((P,), -0.4) if with_mono else None,
        leaf_max=jnp.full((P,), 0.4) if with_mono else None)
        for g in range(2)])
    fmeta = pallas_hist.pack_feature_meta(
        jnp.full((f,), b, jnp.int32), jnp.zeros((f,), jnp.int32),
        jnp.zeros((f,), jnp.int32),
        (jnp.asarray([1, -1, 0, 1, -1], jnp.int32) if with_mono
         else jnp.zeros((f,), jnp.int32)))
    pvec = pallas_hist.pack_scan_params(
        SplitParams.from_config(Config.from_params({})))
    qsc = jnp.ones((3,), jnp.float32) if mode == "q8" else None

    # both arms jitted: the grower always runs them inside one compiled
    # program, and eager-vs-jit would differ in FMA contraction, not in
    # the math under test
    kw = dict(num_bins=b, block=512, with_monotone=with_mono, q_scale=qsc)
    run_k = jax.jit(lambda *a: histogram_tiles_with_candidates(
        *a, method=method, binsT=jbT, interpret=True, **kw))
    xla_m = "onehot_q8" if mode == "q8" else "scatter"
    run_x = jax.jit(lambda *a: histogram_tiles_with_candidates(
        *a, method=xla_m, binsT=jbT, **kw))
    tile_k, cand_k = run_k(jb, jst, jl, sel, sel_derived, parent, la, fmeta,
                           pvec)
    tile_x, cand_x = run_x(jb, jst, jl, sel, sel_derived, parent, la, fmeta,
                           pvec)
    assert tile_k.shape == (2 * P, f, b, 3)
    assert cand_k.shape == (2 * P, f, CAND_CHANNELS)
    np.testing.assert_array_equal(np.asarray(tile_k), np.asarray(tile_x))
    np.testing.assert_array_equal(np.asarray(cand_k), np.asarray(cand_x))
    # sibling-derivation exactness: the derived plane (group 1, slot 0)
    # == leaf 1's directly-built histogram (representable/integer sums ->
    # exact subtraction); a slot with nothing to derive stays zero
    direct = histogram_tiles(jb, st_f, jl, jnp.asarray([1], jnp.int32), b,
                             method="scatter")
    np.testing.assert_array_equal(np.asarray(tile_k[P]),
                                  np.asarray(direct[0]))
    assert not np.asarray(tile_k[P + 1:]).any()
    # and the candidate table for the derived leaf is populated
    assert np.isfinite(np.asarray(cand_k)[P, :, 0]).any()
    # acceptance floor from the REAL buffers: per-leaf plane bytes the
    # classic search streams vs the candidate row the fused search reads
    plane_per_leaf = tile_k.nbytes / tile_k.shape[0]
    cand_per_leaf = cand_k.nbytes / cand_k.shape[0]
    assert plane_per_leaf / cand_per_leaf >= b / 4, (
        plane_per_leaf, cand_per_leaf, b)


def _leaf_sums(stats, leaf, leaves):
    """[len(leaves), 3] float32 sums of ``stats`` over each leaf's rows
    (-1 = no leaf: zeros)."""
    out = np.zeros((len(leaves), 3), np.float32)
    for q, lv in enumerate(leaves):
        if lv >= 0:
            out[q] = stats[leaf == lv].astype(np.float64).sum(0)
    return out


@pytest.mark.parametrize("mode,method,xla_m", [
    ("hilo", "pallas_hilo", "onehot_hilo"),
    ("highest", "pallas", "scatter"),
    ("q8", "pallas_q8", "onehot_q8")])
def test_fused_pass_kernel_twin_and_classic_agree(mode, method, xla_m):
    """One fused pass, three ways, bit for bit: the two-group kernel
    (interpret), the XLA twin, and the classic phase (histogram_tiles,
    parent - computed, find_best_splits over the planes). The tile's P
    slots are all computed; two bring a derived sibling along, one is a
    root-like leaf with no sibling, one a lone leaf whose sibling is not
    pending, one is idle."""
    n, f, b, P = 4001, 5, 63, 5
    rng = np.random.RandomState(11)
    binsT, bins, stats, _ = _epi_inputs(n=n, f=f, b=b, seed=11,
                                        int8=(mode == "q8"))
    leaf = rng.randint(0, 7, n).astype(np.int32)
    jb, jbT = jnp.asarray(bins), jnp.asarray(binsT)
    jst, jl = jnp.asarray(stats), jnp.asarray(leaf)
    st_f = jnp.asarray(stats.astype(np.float32))
    # pairs (0, 1) and (3, 4): the first of each is computed and derives
    # the second; 2 has no sibling; 5's sibling (6) is not in the pass
    sel_np = np.array([0, 2, 3, 5, -1], np.int32)
    der_np = np.array([1, -1, 4, -1, -1], np.int32)
    sel, sel_derived = jnp.asarray(sel_np), jnp.asarray(der_np)

    def direct(leaves):
        return histogram_tiles(jb, st_f, jl, jnp.asarray(leaves, jnp.int32),
                               b, method="scatter")

    parent = jnp.zeros((P, f, b, 3), jnp.float32)
    parent = parent.at[0].set(direct([0])[0] + direct([1])[0])
    parent = parent.at[2].set(direct([3])[0] + direct([4])[0])
    sums = np.stack([_leaf_sums(stats, leaf, sel_np),
                     _leaf_sums(stats, leaf, der_np)])       # [2, P, 3]
    la = jnp.stack([pallas_hist.pack_leaf_aux(
        *(jnp.asarray(sums[g, :, i]) for i in range(3)), jnp.zeros((P,)))
        for g in range(2)])
    meta = _meta(f, b)
    fmeta = pallas_hist.pack_feature_meta(
        meta.num_bins, meta.missing_type, meta.default_bin,
        meta.monotone.astype(jnp.int32))
    sp = SplitParams.from_config(Config.from_params(
        {"min_data_in_leaf": 5, "min_sum_hessian_in_leaf": 1e-3}))
    pvec = pallas_hist.pack_scan_params(sp)
    qsc = jnp.ones((3,), jnp.float32) if mode == "q8" else None
    kw = dict(num_bins=b, block=512, q_scale=qsc)
    args = (jb, jst, jl, sel, sel_derived, parent, la, fmeta, pvec)
    tile_k, cand_k = jax.jit(lambda *a: histogram_tiles_with_candidates(
        *a, method=method, binsT=jbT, interpret=True, **kw))(*args)
    tile_x, cand_x = jax.jit(lambda *a: histogram_tiles_with_candidates(
        *a, method=xla_m, binsT=jbT, **kw))(*args)
    np.testing.assert_array_equal(np.asarray(tile_k), np.asarray(tile_x))
    np.testing.assert_array_equal(np.asarray(cand_k), np.asarray(cand_x))

    # the classic phase: the computed planes, each derived sibling as
    # parent - computed, and the plane-reading search over all of them
    computed = histogram_tiles(jb, jst, jl, sel, b, method=xla_m)
    computed = computed.astype(jnp.float32)
    classic = jnp.concatenate([
        computed,
        jnp.where((sel_derived >= 0)[:, None, None, None],
                  parent - computed, 0.0)])
    np.testing.assert_array_equal(np.asarray(tile_k), np.asarray(classic))
    for q, lv in enumerate(der_np):           # a derived plane is the leaf's
        if lv >= 0:
            np.testing.assert_array_equal(np.asarray(tile_k[P + q]),
                                          np.asarray(direct([lv])[0]))
    live = np.concatenate([sel_np, der_np]) >= 0
    flat = jnp.asarray(sums.reshape(2 * P, 3))
    zeros, depth = jnp.zeros((2 * P,)), jnp.zeros((2 * P,), jnp.int32)
    fmask = jnp.ones((2 * P, f), jnp.float32)
    ref = find_best_splits(classic, flat[:, 0], flat[:, 1], flat[:, 2],
                           zeros, depth, meta, sp, fmask, max_depth=-1)
    got = candidates_to_splitinfo(cand_k, flat[:, 0], flat[:, 1],
                                  flat[:, 2], zeros, depth, meta, sp, fmask,
                                  max_depth=-1)
    assert np.isfinite(np.asarray(got.gain)[live]).all()
    for name in ("gain", "feature", "threshold", "default_left",
                 "left_sum_g", "left_sum_h", "left_count", "right_sum_g",
                 "right_sum_h", "right_count"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name))[live],
            np.asarray(getattr(ref, name))[live], err_msg=name)


def test_search_bytes_floor():
    """Acceptance: split-search consumer bytes reduced >= B/4x — per-leaf
    [F, B, 4] planes vs the [F, CAND_CHANNELS] candidate row."""
    for b in (63, 255):
        t = pallas_hist.traffic_model(500_000, 28, b, 42, 3)
        ratio = t["search_in_planes"] / t["search_in_cand"]
        assert ratio >= b / 4, (b, ratio)


# ------------------------------------------------------------------- e2e

def _tree_text(booster):
    return "\n".join(l for l in booster.model_to_string().splitlines()
                     if not l.startswith("[") and l != "end of parameters")


def _data(seed=4, n=1400, f=5, with_nan=False, with_zero=False):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f))
    if with_zero:
        X[rng.rand(n, f) < 0.3] = 0.0
    y = (2.0 * (X[:, 0] > 0.3) + 1.0 * (X[:, 1] > -0.2)
         + 0.5 * (X[:, 2] > 0.5) + 0.01 * rng.normal(size=n))
    if with_nan:
        X[rng.rand(n, f) < 0.15] = np.nan
    return X, y


def _train_text(X, y, params, rounds=3):
    # fused_iteration off: the parity under test lives in the GROWER, and
    # the unfused path dispatches the module-level grow_tree jit — its
    # cache is shared across every config in this file that maps to the
    # same statics, so the matrix costs compiles only where the statics
    # actually differ (the fused-step program is per-booster and would
    # recompile for every single cell)
    ds = lgb.Dataset(X, label=y, params={"verbosity": -1, **{
        k: params[k] for k in ("max_bin", "zero_as_missing")
        if k in params}})
    booster = lgb.train({"objective": "regression", "num_leaves": 8,
                         "verbosity": -1, "fused_iteration": False,
                         **params}, ds, num_boost_round=rounds)
    return _tree_text(booster)


EDGE_CONFIGS = [
    pytest.param({}, {}, id="default"),
    pytest.param({"monotone_constraints": [1, -1, 0, 0, 0]}, {},
                 id="monotone-basic"),
    pytest.param({}, {"with_nan": True}, id="missing-nan"),
    pytest.param({"zero_as_missing": True}, {"with_zero": True},
                 id="missing-zero"),
    pytest.param({"min_data_in_leaf": 60,
                  "min_sum_hessian_in_leaf": 5.0}, {}, id="min-data-hess"),
    pytest.param({"lambda_l1": 0.5, "lambda_l2": 1.3, "path_smooth": 2.0,
                  "max_delta_step": 0.3}, {}, id="l1-smooth-delta"),
    pytest.param({"max_depth": 3}, {}, id="max-depth"),
    pytest.param({"tree_growth_mode": "exact"}, {}, id="exact"),
    pytest.param({"bagging_fraction": 0.4, "bagging_freq": 1}, {},
                 id="subset-bagging"),
    pytest.param({"feature_fraction": 0.6}, {}, id="col-sampling"),
    pytest.param({"interaction_constraints": [[0, 1], [2, 3, 4]]}, {},
                 id="interactions"),
    pytest.param({"quantized_grad": True}, {}, id="q8"),
]


_XLA_TEXTS = {}


def _train_xla(params, dkw, fusion):
    X, y = _data(**dkw)
    return _train_text(X, y, {"histogram_method": "scatter", **params,
                              "split_fusion": fusion})


def _xla_text(params, dkw, fusion):
    """Model text of one edge config on the XLA twin (scatter backend),
    trained once a process: the fusion parity and the routing parity
    below read the same run."""
    key = repr((sorted(params.items()), sorted(dkw.items()), fusion))
    if key not in _XLA_TEXTS:
        _XLA_TEXTS[key] = _train_xla(params, dkw, fusion)
    return _XLA_TEXTS[key]


@pytest.mark.parametrize("params,dkw", EDGE_CONFIGS)
def test_e2e_fusion_bit_parity_xla(params, dkw):
    """split_fusion on == off, model text bit-identical, on the XLA twin
    (scatter backend) across the split-semantics edge-config matrix."""
    assert _xla_text(params, dkw, "on") == _xla_text(params, dkw, "off")


def _route_generally(monkeypatch):
    """From here on every split is routed by the GENERAL route (segment
    test and bitset lookup traced whatever the data set holds): the
    program of the commit before the routing's cases became statics.
    ``grow_tree`` is jitted anew over a new callable (jit's trace cache is
    keyed by the function), so no program traced with the specialised
    route answers. Returns the statics each traced split was handed."""
    from lightgbm_tpu.models import gbdt, grower
    real, traced = grower._apply_split, []

    def general(*a, **kw):
        traced.append((kw["with_categorical"], kw["with_bundle"]))
        return real(*a, **{**kw, "with_categorical": True,
                           "with_bundle": True})

    monkeypatch.setattr(grower, "_apply_split", general)
    monkeypatch.setattr(gbdt, "grow_tree", jax.jit(
        lambda *a, **kw: grower.grow_tree.__wrapped__(*a, **kw),
        static_argnames=grower._GROW_STATICS))
    return traced


@pytest.mark.parametrize("fusion", ["on", "off"])
@pytest.mark.parametrize("params,dkw", EDGE_CONFIGS)
def test_model_text_equals_the_general_routes(monkeypatch, params, dkw,
                                              fusion):
    """A split routed with only the tests its data set can need grows the
    trees the general route grows, fused search and classic, over the
    edge-config matrix (its bagging-subset job routes two row sets)."""
    special = _xla_text(params, dkw, fusion)
    traced = _route_generally(monkeypatch)
    assert _train_xla(params, dkw, fusion) == special
    assert set(traced) == {(False, False)}


def _categorical_job():
    rng = np.random.RandomState(5)
    X = rng.normal(size=(1400, 5))
    X[:, 0] = rng.randint(0, 12, size=1400)
    y = (1.5 * np.isin(X[:, 0], [1, 4, 7, 10]) + (X[:, 1] > 0.1)
         + 0.5 * (X[:, 2] > -0.3) + 0.01 * rng.normal(size=1400))
    return X, y, {"categorical_feature": [0]}, {
        "min_data_per_group": 10, "cat_smooth": 1.0}


def _bundled_job():
    sp = pytest.importorskip("scipy.sparse")
    rng = np.random.RandomState(6)
    X = sp.random(1400, 40, density=0.02, random_state=rng, format="csr",
                  data_rvs=lambda k: rng.uniform(0.5, 2.0, k))
    y = (np.asarray(X[:, :8].sum(axis=1)).ravel()
         + 0.01 * rng.normal(size=1400))
    return X, y, {}, {"min_data_in_leaf": 5}


def _sparse_column_job():
    rng = np.random.RandomState(7)
    X = rng.normal(size=(1400, 6))
    for j in (3, 4, 5):
        col = np.zeros(1400)
        nz = rng.choice(1400, 60, replace=False)
        col[nz] = rng.normal(size=60) + 2.0
        X[:, j] = col
    y = X[:, 0] + 3.0 * (X[:, 3] > 0) + 0.5 * X[:, 1]
    return X, y, {}, {"enable_sparse": True, "enable_bundle": False,
                      "min_data_in_leaf": 5}


@pytest.mark.parametrize("job,has", [
    (_categorical_job, "has_categorical"), (_bundled_job, "bundles"),
    (_sparse_column_job, "has_sparse_cols")],
    ids=["categorical", "bundled", "sparse-columns"])
def test_model_text_equals_the_general_routes_beyond_the_matrix(
        monkeypatch, job, has):
    """The data sets the general route exists for (a categorical feature,
    an EFB bundle) and the sparse-column reconstruction: each trains to
    the general route's model text (classic search: the fused one takes
    none of them)."""
    X, y, dkw, params = job()
    params = {"objective": "regression", "num_leaves": 8, "verbosity": -1,
              "fused_iteration": False, "histogram_method": "scatter",
              **params}

    def text():
        ds = lgb.Dataset(X, label=y, params=params, **dkw)
        t = _tree_text(lgb.train(params, ds, num_boost_round=3))
        assert getattr(ds, has), has
        return t

    special = text()
    assert special.count("split_feature=") == 3
    if has == "has_categorical":
        # both split kinds in the trees: decision_type bit 0 set and clear
        kinds = {int(d) & 1 for ln in special.splitlines()
                 if ln.startswith("decision_type=")
                 for d in ln.split("=")[1].split()}
        assert kinds == {0, 1}, kinds
    traced = _route_generally(monkeypatch)
    assert text() == special
    assert set(traced) == {(has == "has_categorical", has == "bundles")}


@pytest.mark.parametrize("params,dkw", [
    pytest.param({}, {}, id="default"),
    # q8 rides slow: its in-kernel dequant is pinned tier-1 by the q8
    # epilogue unit parity above and e2e by the XLA-twin q8 case (same
    # scan function), and scripts/kernel_bench.py --fast --interpret
    # runs the q8 kernel mode on every CI pass; the interpret-kernel
    # LAUNCH mechanics stay tier-1 via the default case below
    pytest.param({"quantized_grad": True}, {}, id="q8",
                 marks=pytest.mark.slow),
])
def test_e2e_fusion_bit_parity_kernel(params, dkw):
    """split_fusion on == off through the IN-KERNEL epilogue (pallas
    interpret, compaction ladder on so the epilogue kernel also runs over
    gathered rows inside the rung dispatch). The
    missing-direction/monotone/etc edge matrix is covered bit-for-bit on the XLA twin above — the kernel
    runs the SAME scan function, and its plane assembly + monotone aux
    are pinned by the kernel-vs-twin unit test — so this matrix only
    needs the configs that change the KERNEL's own launch shape (the
    default pass and q8's in-kernel dequant)."""
    X, y = _data(**dkw)
    base = {"histogram_method": "pallas", "hist_pallas_interpret": True,
            **params}
    t_on = _train_text(X, y, {**base, "split_fusion": "on"}, rounds=2)
    t_off = _train_text(X, y, {**base, "split_fusion": "off"}, rounds=2)
    assert t_on == t_off


def test_degenerate_shapes():
    """All-leaves-dead (root fails the 2x min_data guard -> splitless
    tree) and the single-pending-leaf launch shape (num_leaves=2) — both
    fused == classic."""
    X, y = _data(n=600)
    dead = {"histogram_method": "scatter", "min_data_in_leaf": 2000}
    t_on = _train_text(X, y, {**dead, "split_fusion": "on"}, rounds=2)
    t_off = _train_text(X, y, {**dead, "split_fusion": "off"}, rounds=2)
    assert t_on == t_off
    assert "num_leaves=1" in t_on
    two = {"histogram_method": "scatter", "num_leaves": 2}
    t_on = _train_text(X, y, {**two, "split_fusion": "on",
                              "num_leaves": 2}, rounds=2)
    t_off = _train_text(X, y, {**two, "split_fusion": "off",
                               "num_leaves": 2}, rounds=2)
    assert t_on == t_off


# ------------------------------------------------- pass count and the fill

def _grow_args(n=6000, f=4, B=32, seed=9):
    """Operands of a direct grow_tree / _grower_fns call: a regression
    problem whose tree keeps splitting down to small leaves."""
    rng = np.random.RandomState(seed)
    bins = jnp.asarray(rng.randint(0, B, size=(n, f)).astype(np.uint8))
    grad = jnp.asarray(rng.normal(size=n).astype(np.float32))
    p = SplitParams.from_config(Config.from_params(
        {"min_data_in_leaf": 5, "min_sum_hessian_in_leaf": 1e-3}))
    return (bins, grad, jnp.ones((n,), jnp.float32),
            jnp.ones((n,), jnp.float32), _meta(f, B), p,
            jnp.ones((f,), jnp.float32), jnp.full((f,), -1, jnp.int32))


def _grow_passes(args, **kw):
    """Drive the grower's phases from the host, each jitted, in the order
    grow_tree's while_loop runs them; returns (final state, [(state
    before, state after)] of every histogram pass)."""
    from lightgbm_tpu.models.grower import _grower_fns
    fns = _grower_fns(*args, **kw)
    hist_phase = jax.jit(fns["hist_phase"])
    split_phase = jax.jit(
        lambda st: fns["split_apply"](fns["split_search"](st)))
    state, passes = fns["init_state"](), []
    while bool(fns["outer_cond"](state)):
        state = fns["dead_guard"](state)
        if bool(jnp.any(fns["pending_mask"](state))):
            after = hist_phase(state)
            passes.append((state, after))
            state = after
        else:
            state = split_phase(state)
    return state, passes


@pytest.mark.parametrize("tile_leaves,max_leaves", [(4, 24), (3, 16),
                                                    (8, 40)])
def test_fused_grower_takes_the_classic_growers_passes(tile_leaves,
                                                       max_leaves):
    """The pass-count guard: with a tile smaller than the frontier, the
    fused grower takes exactly the passes of the classic one — a slot of
    the tile is never spent on a leaf that reads no rows — streams the
    same rows and grows the same tree. (A fused tile that pairs siblings
    on its slots computes half as many leaves a pass and fails this.)"""
    args = _grow_args()
    kw = dict(max_leaves=max_leaves, num_bins=32, hist_method="onehot",
              tile_leaves=tile_leaves)
    classic, passes_c = _grow_passes(args, **kw)
    fused, passes_f = _grow_passes(args, split_fusion=True, **kw)
    assert int(fused.num_leaves) == max_leaves
    assert len(passes_f) == len(passes_c)
    assert int(fused.rounds) == int(classic.rounds)
    assert float(fused.rows_streamed) == float(classic.rows_streamed)
    assert float(fused.leaves_resolved) == float(classic.leaves_resolved)
    # every leaf that was split had been resolved once, computed or
    # derived (the children of the last split phase never are)
    assert (max_leaves - 1 <= float(fused.leaves_resolved)
            <= 2 * max_leaves - 1)
    # more than tile_leaves a pass: the derived siblings ride along
    assert max(float(b.leaves_resolved - a.leaves_resolved)
               for a, b in passes_f) > tile_leaves
    # the same tree: structure and row routing exactly; floats to the
    # last bits only (the phases are jitted apart here, so the two
    # searches round their gains in programs of their own; model text
    # parity is test_small_tile_model_text_matches_classic's)
    for a, b in zip(jax.tree_util.tree_leaves(classic.tree),
                    jax.tree_util.tree_leaves(fused.tree)):
        if jnp.issubdtype(a.dtype, jnp.floating):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(classic.leaf_id),
                                  np.asarray(fused.leaf_id))


def test_small_tile_model_text_matches_classic():
    """The same guard through the library: model text of a split_fusion
    run equals the classic phase's at a tile far below the frontier, and
    with no ladder (under one the fused fill is bounded by rows) both
    stream the same rows."""
    X, y = _data(n=2000)

    def run(sf):
        ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
        b = lgb.train({"objective": "regression", "num_leaves": 24,
                       "verbosity": -1, "fused_iteration": False,
                       "histogram_method": "onehot", "tile_leaves": 3,
                       "min_data_in_leaf": 5, "hist_compaction": False,
                       "split_fusion": sf},
                      ds, num_boost_round=2)
        return _tree_text(b), b._boosting.rows_streamed_total

    (t_on, rows_on), (t_off, rows_off) = run("on"), run("off")
    assert t_on == t_off
    assert rows_on == rows_off


@pytest.mark.parametrize("rows,ladder,want", [
    # first half (2 + 3) fits the 8-rung: leaves are added while the sum
    # stays within 8
    ([2, 3, 1, 2, 5, 1], (8, 64), [1, 1, 1, 1, 0, 0]),
    # first half (20 + 30) fits only the 64-rung: fill to 64
    ([20, 30, 10, 4, 1, 1], (8, 64), [1, 1, 1, 1, 0, 0]),
    ([20, 30, 5, 4, 1, 1], (8, 64), [1, 1, 1, 1, 1, 1]),
    # first half fits no rung: the pass is a full one whatever it holds
    ([50, 30, 40, 40, 9, 9], (8, 64), [1, 1, 1, 1, 1, 1]),
    # the first half is always taken, and exactly on the rung still fits
    ([4, 4, 0, 0, 0, 0], (8,), [1, 1, 1, 1, 1, 1]),
    ([4, 3, 1, 1, 0, 0], (8,), [1, 1, 1, 0, 0, 0]),
    # a single-slot tile takes its one candidate
    ([70], (8, 64), [1]),
])
def test_tile_fill_stays_within_the_first_halfs_rung(rows, ladder, want):
    """tile_fill: under a ladder a pass takes its first P // 2 candidates
    and then only those that keep the running row count within the rung
    the first half fits."""
    from lightgbm_tpu.models.grower import tile_fill
    keep = np.asarray(tile_fill(jnp.asarray(rows, jnp.float32), ladder))
    np.testing.assert_array_equal(keep, np.asarray(want, bool))
    assert keep[:max(len(rows) // 2, 1)].all()
    # a prefix: a leaf is never taken past one that was left out
    assert not (np.diff(keep.astype(int)) > 0).any()


def _pass_candidates(state, P):
    """The leaves a fused pass may compute, in tile order, from the state
    before it (the smaller of each derivable pair, every other pending
    leaf) — the test's own reading of tile_pass_fused's choice."""
    n_l = int(state.num_leaves)
    pending = (np.arange(state.hist_valid.shape[0]) < n_l) \
        & ~np.asarray(state.hist_valid) & ~np.asarray(state.leaf_dead)
    sib, cnt = np.asarray(state.sib), np.asarray(state.leaf_cnt)
    parent_hist = np.asarray(state.parent_hist)
    out = []
    for l in np.nonzero(pending)[0]:
        s_ = sib[l]
        derivable = s_ >= 0 and pending[s_] and parent_hist[min(l, s_)]
        if derivable and not (cnt[l] < cnt[s_]
                              or (cnt[l] == cnt[s_] and l < s_)):
            continue
        out.append(int(l))
    return out[:P], cnt


@pytest.mark.parametrize("ladder", [(), (400, 1500)])
def test_fused_fill_with_and_without_a_ladder(ladder):
    """Without a ladder every fused pass fills its tile to P computed
    leaves (or takes all there are); with one, a pass goes beyond its
    first P // 2 only while the rows stay within the rung that half fits
    — and the rung then taken is that one. The tree is the same."""
    P, n = 8, 6000
    args = _grow_args(n=n)
    # (the scatter backend ignores tile_leaves: one pass serves every
    # leaf; the onehot backend honours it)
    kw = dict(max_leaves=48, num_bins=32, hist_method="onehot",
              split_fusion=True)
    state, passes = _grow_passes(args, tile_leaves=P,
                                 compaction_ladder=ladder, **kw)
    base, _ = _grow_passes(args, tile_leaves=P, **kw)
    np.testing.assert_array_equal(np.asarray(state.tree.node_feature),
                                  np.asarray(base.tree.node_feature))
    np.testing.assert_array_equal(
        np.asarray(state.tree.node_threshold_bin),
        np.asarray(base.tree.node_threshold_bin))
    short = 0
    for before, after in passes:
        cands, cnt = _pass_candidates(before, P)
        newly = np.asarray(after.hist_valid) & ~np.asarray(before.hist_valid)
        taken = [l for l in cands if newly[l]]
        assert taken == cands[:len(taken)]
        streamed = float(after.rows_streamed - before.rows_streamed)
        if not ladder:
            assert taken == cands and streamed == n
            continue
        half = cands[:P // 2]
        assert taken[:len(half)] == half
        fits = [m for m in ladder if cnt[half].sum() <= m]
        cap = min(fits) if fits else np.inf
        assert len(taken) == len(half) or cnt[taken].sum() <= cap
        # the next candidate was left out only because it would not fit
        if len(taken) < len(cands):
            short += 1
            assert cnt[cands[:len(taken) + 1]].sum() > cap
        assert streamed == (cap if fits else n)
    if ladder:
        assert short > 0, "no pass of this tree exercised the guard"


# ---------------------------------------------------------------- gating

def _cat_data(seed=5, n=1200):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, 4))
    X[:, 3] = rng.randint(0, 6, n)
    y = (X[:, 0] > 0) * 1.0 + (X[:, 3] == 2) * 2.0
    return X, y


def test_auto_falls_back_and_on_refuses():
    """The configurations whose split semantics stay in find_best_splits:
    'auto' silently keeps the classic phase (training equals explicit
    'off'), 'on' raises naming the blocker."""
    X, y = _cat_data()

    def train(params, sf):
        ds = lgb.Dataset(X, label=y, params={"verbosity": -1},
                         categorical_feature=[3])
        return lgb.train({"objective": "regression", "num_leaves": 8,
                          "verbosity": -1, "split_fusion": sf,
                          "fused_iteration": False, **params},
                         ds, num_boost_round=2)

    t_auto = _tree_text(train({}, "auto"))
    t_off = _tree_text(train({}, "off"))
    assert t_auto == t_off
    with pytest.raises(ValueError, match="split_fusion=on is unsupported"):
        train({}, "on").model_to_string()

    # extra_trees / CEGB / non-positive feature_contri blockers,
    # numerical data (the contri multiplier only commutes with the
    # fused per-feature argmax when positive — see
    # candidates_to_splitinfo)
    Xn, yn = _data()
    for blocker in ({"extra_trees": True},
                    {"cegb_tradeoff": 0.5, "cegb_penalty_split": 0.1},
                    {"feature_contri": [1.0, 0.0, 1.0, 1.0, 1.0]}):
        ds = lgb.Dataset(Xn, label=yn, params={"verbosity": -1})
        with pytest.raises(ValueError,
                           match="split_fusion=on is unsupported"):
            lgb.train({"objective": "regression", "verbosity": -1,
                       "split_fusion": "on", **blocker}, ds,
                      num_boost_round=1)
    # and 'auto' with a non-positive contri entry falls back to the
    # classic phase (same trees as explicit off)
    contri = {"feature_contri": [1.0, -0.5, 1.0, 1.0, 1.0],
              "histogram_method": "scatter"}
    t_auto = _train_text(Xn, yn, {**contri, "split_fusion": "auto"},
                         rounds=2)
    t_off2 = _train_text(Xn, yn, {**contri, "split_fusion": "off"},
                         rounds=2)
    assert t_auto == t_off2


@pytest.mark.parametrize("fusion", ["auto", "off"])
def test_old_trainer_state_takes_the_rules_plan(fusion):
    """A trainer state written while runs still timed their kernels
    carries ``measured_hm`` and ``hist_tuned``; it restores, the keys are
    ignored and the plan is the rule's, whatever the kernel form — and a
    state written now holds neither key."""
    from lightgbm_tpu.ops import pallas_hist
    X, y = _data(n=600)
    params = {"objective": "regression", "verbosity": -1, "num_leaves": 8,
              "hist_pallas_interpret": True, "split_fusion": fusion}
    ds = lgb.Dataset(X, label=y, params=params)
    booster = lgb.train(params, ds, num_boost_round=2,
                        keep_training_booster=True)
    state = booster._boosting.get_trainer_state()
    assert "measured_hm" not in state and "hist_tuned" not in state
    old = dict(state, measured_hm="onehot_hilo",
               hist_tuned={"block": 8192, "tile_leaves": 42,
                           "epilogue": fusion != "auto"})
    resumed = lgb.Booster(params=params, train_set=ds)
    gb = resumed._boosting
    gb.set_trainer_state(old)
    assert gb.iter == 2
    hm = gb._hist_method()
    st = gb._serial_grow_statics(hm)
    assert (hm, st["hist_block"], st["tile_leaves"]) == (
        "pallas_hilo", pallas_hist.DEFAULT_BLOCK,
        pallas_hist.structural_tile_leaves())
    assert st["split_fusion"] is (fusion == "auto")
    resumed.update()
    booster.update()
    assert _tree_text(resumed) == _tree_text(booster)


# ------------------------------------------------------- phased profiling

def test_phased_grower_bit_parity_and_frontier_launches():
    """TIMETAG profiling routes growth through the host-phased grower:
    bit-identical model text, hist_pass/split_search/apply_split scopes
    recorded, and the dispatch-count regression — histogram launches per
    tree track frontier LEVELS (well under one per leaf/split)."""
    from lightgbm_tpu.utils import profiling
    X, y = _data()
    params = {"objective": "regression", "num_leaves": 16,
              "verbosity": -1, "histogram_method": "scatter",
              "fused_iteration": False}
    rounds = 2

    def run(profile):
        profiling.reset()
        profiling.enable(profile)
        try:
            ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
            booster = lgb.train(params, ds, num_boost_round=rounds)
            return _tree_text(booster), profiling.scopes()
        finally:
            profiling.enable(False)
            profiling.reset()

    t_plain, _ = run(False)
    t_phased, scopes = run(True)
    assert t_phased == t_plain
    for name in ("hist_pass", "split_search", "apply_split"):
        assert scopes.get(name, {}).get("calls", 0) > 0, (name, scopes)
    # one histogram launch per frontier level: far fewer than one per
    # split (15 splits/tree at 16 leaves)
    hist_launches_per_tree = scopes["hist_pass"]["calls"] / rounds
    assert hist_launches_per_tree < 15, scopes["hist_pass"]
    assert hist_launches_per_tree >= 1


@pytest.mark.slow
def test_phased_equals_monolithic_under_fusion():
    """Phased + split_fusion: same trees as the monolithic fused grower
    (the phased programs run the same _grower_fns phases).

    Slow: a combination spelling of two contracts that each stay
    tier-1 — phased-vs-monolithic bit parity
    (test_phased_grower_bit_parity_and_frontier_launches) and
    fusion-on == fusion-off e2e bit parity
    (test_e2e_fusion_bit_parity_xla matrix); the phased driver runs the
    SAME _grower_fns phase programs either way, so the cross term has
    no mechanics of its own."""
    from lightgbm_tpu.utils import profiling
    X, y = _data(n=900)
    params = {"objective": "regression", "num_leaves": 8, "verbosity": -1,
              "histogram_method": "scatter", "split_fusion": "on",
              "fused_iteration": False}

    def run(profile):
        profiling.reset()
        profiling.enable(profile)
        try:
            ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
            return _tree_text(lgb.train(params, ds, num_boost_round=2))
        finally:
            profiling.enable(False)
            profiling.reset()

    assert run(True) == run(False)
