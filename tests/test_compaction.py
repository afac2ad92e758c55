"""Leaf-partitioned row compaction (the DataPartition analog,
data_partition.hpp:21-60): parity, ladder dispatch, and the rows-streamed
telemetry.

Parity model: ``compact_rows`` preserves the kept rows' ORIGINAL order, so
the scatter backend (the CPU production default) accumulates every
histogram cell's contributions in exactly the full-pass order — training
with and without compaction is asserted BIT-IDENTICAL (model-text
equality) there. The matmul backends (onehot/binloop) regroup partial sums
when the scan-block partition changes, so compaction perturbs grad/hess
sums at f32 accumulation-order level — the same tolerance the repo accepts
between its own dense/sparse and CPU/TPU paths (test_sparse_storage's
parity model): those cells assert identical STRUCTURE (split features,
thresholds, counts) and prediction parity."""

import re

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb


def _data(rng, n=4000, f=5, cat_col=None):
    X = rng.normal(size=(n, f)).astype(np.float64)
    if cat_col is not None:
        X[:, cat_col] = rng.randint(0, 8, size=n)
        y = (X[:, 0] + (X[:, cat_col] > 3) + 0.1 * rng.normal(size=n) > 0.5)
    else:
        y = (X[:, 0] + 0.5 * X[:, 1] + 0.1 * rng.normal(size=n) > 0.3)
    return X, y.astype(np.float64)


def _train(X, y, extra, rounds=4):
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
    params.update(extra)
    ds = lgb.Dataset(X, label=y, params=params)
    b = lgb.Booster(params=params, train_set=ds)
    for _ in range(rounds):
        b.update()
    return b


def _tree_text(b):
    """Model text up to the parameters block (the trees — the parameters
    section records hist_compaction itself, which differs by design)."""
    return b.model_to_string().split("\nparameters:")[0]


def _structure_text(b):
    """Tree text with the f32-accumulated value lines stripped (gains,
    leaf values/weights, internal values) — split features, thresholds,
    counts and topology remain."""
    txt = _tree_text(b)
    drop = ("split_gain=", "leaf_value=", "leaf_weight=",
            "internal_value=", "internal_weight=", "tree_sizes=",
            "shrinkage=")
    return "\n".join(l for l in txt.splitlines()
                     if not l.startswith(drop))


BIT_EXACT_CELLS = {
    "scatter": {"histogram_method": "scatter"},
    "scatter_nosub": {"histogram_method": "scatter",
                      "hist_subtraction": False},
    # the subset cell rides the slow tier below: the subset-copy machinery
    # is tier-1 in test_gbdt's bagging tests and the compaction rungs it
    # exercises are shared with the tier-1 scatter/nosub/categorical cells
    "scatter_bag_subset": {"histogram_method": "scatter",
                           "bagging_fraction": 0.4, "bagging_freq": 1},
    "scatter_categorical": {"histogram_method": "scatter",
                            "categorical_feature": [3]},
    "scatter_exact_mode": {"histogram_method": "scatter",
                           "tree_growth_mode": "exact"},
}


@pytest.mark.parametrize("cell", [
    # the exact-growth-mode and bagging-subset cells ride the slow tier:
    # exact-mode growth has its own tier-1 coverage (test_grower), the
    # subset copy has test_gbdt's bagging tier-1 coverage, and the
    # compaction machinery both share stays tier-1 via the other cells
    pytest.param(c, marks=pytest.mark.slow)
    if c in ("scatter_exact_mode", "scatter_bag_subset") else c
    for c in sorted(BIT_EXACT_CELLS)])
def test_compaction_parity_bit_exact(rng, cell):
    """Compacted and full-pass training yield IDENTICAL model text on the
    scatter backend across subtraction x bagging-subset x categorical x
    growth mode."""
    extra = BIT_EXACT_CELLS[cell]
    cat = extra.get("categorical_feature", [None])[0]
    X, y = _data(rng, cat_col=cat)
    b_on = _train(X, y, {**extra, "hist_compaction": True})
    b_off = _train(X, y, {**extra, "hist_compaction": False})
    assert _tree_text(b_on) == _tree_text(b_off)
    # and compaction actually engaged (fewer rows streamed) except in the
    # no-subtraction cell, where both children of every split stay pending
    # so non-root passes still cover ~all rows
    if "hist_subtraction" not in extra:
        assert (b_on._boosting.rows_streamed_per_tree
                < b_off._boosting.rows_streamed_per_tree)


@pytest.mark.parametrize("method", [
    "onehot",
    # binloop rides the slow tier: its grower-level parity stays tier-1
    # (test_grower's scatter/binloop matrix) and the compaction
    # structural-parity machinery stays tier-1 via the onehot cell
    pytest.param("binloop", marks=pytest.mark.slow)])
def test_compaction_parity_matmul_structural(rng, method):
    """The matmul backends: identical tree structure + prediction parity
    (accumulation-order tolerance on the value fields — see the module
    docstring)."""
    X, y = _data(rng)
    b_on = _train(X, y, {"histogram_method": method,
                         "hist_compaction": True})
    b_off = _train(X, y, {"histogram_method": method,
                          "hist_compaction": False})
    assert _structure_text(b_on) == _structure_text(b_off)
    np.testing.assert_allclose(b_on.predict(X), b_off.predict(X),
                               rtol=1e-3, atol=1e-3)
    assert (b_on._boosting.rows_streamed_per_tree
            < b_off._boosting.rows_streamed_per_tree)


def test_ladder_fallback_rung(rng):
    """A ladder whose rungs are all smaller than any pending tile must
    take the full-N fallback every round — identical model text AND the
    uncompacted rows-streamed count — and stay correct."""
    X, y = _data(rng)
    b_tiny = _train(X, y, {"histogram_method": "scatter",
                           "hist_compaction": True,
                           "hist_compaction_ladder": [0.001]})
    b_off = _train(X, y, {"histogram_method": "scatter",
                          "hist_compaction": False})
    assert _tree_text(b_tiny) == _tree_text(b_off)
    assert (b_tiny._boosting.rows_streamed_per_tree
            == b_off._boosting.rows_streamed_per_tree)


def test_compact_rows_unit(rng):
    """compact_rows: stable order, padded slots inert, scatter-backend
    tile bitwise-equal to the full pass, onehot allclose."""
    from lightgbm_tpu.ops.histogram import compact_rows, histogram_tiles

    n, f, b_bins, L = 1500, 4, 16, 8
    bins = jnp.asarray(rng.randint(0, b_bins, size=(n, f)).astype(np.uint8))
    binsT = jnp.asarray(np.asarray(bins).T)
    stats = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    leaf_ids = jnp.asarray(rng.randint(0, L, size=n).astype(np.int32))
    sel = jnp.asarray(np.asarray([2, 5, -1, -1], np.int32))
    keep = np.isin(np.asarray(leaf_ids), [2, 5])
    size = 1024
    assert keep.sum() <= size

    bc, btc, sc, lc = compact_rows(bins, binsT, stats, jnp.asarray(leaf_ids),
                                   jnp.asarray(keep), size)
    k = int(keep.sum())
    # stable original order of the kept rows
    np.testing.assert_array_equal(np.asarray(bc)[:k],
                                  np.asarray(bins)[keep])
    np.testing.assert_array_equal(np.asarray(btc)[:, :k],
                                  np.asarray(binsT)[:, keep])
    np.testing.assert_array_equal(np.asarray(lc)[:k],
                                  np.asarray(leaf_ids)[keep])
    # padding: zero stats, leaf id -2 (matches no sel entry)
    assert np.all(np.asarray(sc)[k:] == 0.0)
    assert np.all(np.asarray(lc)[k:] == -2)

    full = histogram_tiles(bins, stats, leaf_ids, sel, b_bins,
                           method="scatter")
    comp = histogram_tiles(bc, sc, lc, sel, b_bins, method="scatter")
    np.testing.assert_array_equal(np.asarray(full), np.asarray(comp))

    full_o = histogram_tiles(bins, stats, leaf_ids, sel, b_bins,
                             method="onehot")
    comp_o = histogram_tiles(bc, sc, lc, sel, b_bins, method="onehot")
    np.testing.assert_allclose(np.asarray(full_o), np.asarray(comp_o),
                               rtol=1e-5, atol=1e-5)


def test_grower_ladder_fallback_direct(rng):
    """Direct grow_tree: a mixed ladder where only SOME rungs can ever fit
    produces the same tree as no ladder (fallback + engaged rungs are both
    correct), on the scatter backend bit-exactly."""
    import jax
    from lightgbm_tpu.models.grower import grow_tree
    from lightgbm_tpu.ops.split import FeatureMeta, SplitParams

    n, f, B = 3000, 4, 32
    bins = jnp.asarray(rng.randint(0, B, size=(n, f)).astype(np.uint8))
    grad = jnp.asarray(rng.normal(size=n).astype(np.float32))
    hess = jnp.ones((n,), jnp.float32)
    f32 = jnp.float32
    params = SplitParams(
        lambda_l1=f32(0.0), lambda_l2=f32(0.0), max_delta_step=f32(0.0),
        path_smooth=f32(0.0), min_data_in_leaf=f32(5),
        min_sum_hessian_in_leaf=f32(1e-3), min_gain_to_split=f32(0.0),
        cat_l2=f32(10.0), cat_smooth=f32(10.0),
        max_cat_threshold=jnp.int32(32), min_data_per_group=f32(100.0),
        max_cat_to_onehot=jnp.int32(4), monotone_penalty=f32(0.0),
        cegb_tradeoff=f32(1.0), cegb_penalty_split=f32(0.0))
    meta = FeatureMeta(
        num_bins=jnp.full((f,), B, jnp.int32),
        missing_type=jnp.zeros((f,), jnp.int32),
        default_bin=jnp.zeros((f,), jnp.int32),
        is_categorical=jnp.zeros((f,), bool),
        monotone=jnp.zeros((f,), jnp.int8),
        penalty=jnp.ones((f,), jnp.float32))
    common = dict(max_leaves=8, num_bins=B, hist_method="scatter")
    args = (bins, grad, hess, jnp.ones((n,), jnp.float32), meta, params,
            jnp.ones((f,), jnp.float32), jnp.full((f,), -1, jnp.int32))
    t_base, l_base, aux_base = grow_tree(*args, **common)
    # 64 can never hold a pending tile here; 1536 holds every non-root one
    t_lad, l_lad, aux_lad = grow_tree(*args, **common,
                                      compaction_ladder=(64, 1536))
    for a, b in zip(jax.tree_util.tree_leaves(t_base),
                    jax.tree_util.tree_leaves(t_lad)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(l_base), np.asarray(l_lad))
    assert float(aux_lad.rows_streamed) < float(aux_base.rows_streamed)


@pytest.mark.slow
def test_rows_streamed_perf_smoke(rng):
    """CPU perf smoke: on a synthetic 50k-row problem the compaction
    ladder must cut rows streamed per tree well below the uncompacted
    O(N * rounds) count. (Slow tier: a wall-clock smoke — that compaction
    actually engages is asserted per-cell by the tier-1 bit-exact parity
    tests above via their rows_streamed_per_tree checks.)"""
    n, fdim = 50_000, 6
    X = rng.normal(size=(n, fdim)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] + np.sin(2 * X[:, 2])
         + 0.2 * rng.normal(size=n) > 0.2).astype(np.float32)

    def rows_per_tree(compaction):
        b = _train(X, y, {"histogram_method": "scatter",
                          "num_leaves": 31,
                          "hist_compaction": compaction}, rounds=3)
        return b._boosting.rows_streamed_per_tree

    compacted = rows_per_tree(True)
    uncompacted = rows_per_tree(False)
    assert compacted > 0
    # every non-root pass covers only the smaller siblings => well below
    # the full-N-per-round count
    assert compacted < 0.75 * uncompacted, (compacted, uncompacted)


def test_profiling_counter_surface(rng):
    """The rows-streamed telemetry reaches the profiling counter table."""
    from lightgbm_tpu.utils import profiling
    X, y = _data(rng, n=1500)
    profiling.reset()
    profiling.enable(True)
    try:
        _train(X, y, {"histogram_method": "scatter"}, rounds=2)
        counts = profiling.counters()
        assert counts.get("hist_rows_streamed", 0) > 0
        assert re.search(r"hist_rows_streamed", profiling.table())
        # leaves computed or derived by those passes: every leaf that was
        # split had been resolved, and none twice
        trees = 2
        leaves = counts.get("hist_leaves_resolved", 0)
        assert trees <= leaves <= trees * (2 * 15 - 1), counts
    finally:
        profiling.enable(False)
        profiling.reset()


def test_compaction_rejected_for_parallel_learners(rng):
    """The grower refuses a ladder under any parallel mode (the gbdt layer
    never passes one there; the assert is the backstop)."""
    from lightgbm_tpu.models.grower import grow_tree
    with pytest.raises(AssertionError, match="serial-only"):
        grow_tree(
            jnp.zeros((8, 1), jnp.uint8), jnp.zeros((8,), jnp.float32),
            jnp.ones((8,), jnp.float32), jnp.ones((8,), jnp.float32),
            None, None, jnp.ones((1,), jnp.float32),
            jnp.full((1,), -1, jnp.int32),
            max_leaves=2, num_bins=2, axis_name="d",
            compaction_ladder=(64,))


# ------------------------------------------------- which rungs are kept
#
# ops/histogram.prune_compaction_ladder: a candidate rung stays in the
# step only where a pass through it costs less than the full pass it
# replaces, by RUNG_COSTS' per-row constants for the device kind. The
# expectations marked "chip" are readings of scripts/calibrate_compaction.py
# on a TPU v5 lite (PERF.md, Findings, PR 28: the calibration table; PR 36:
# read again with the bins-major kernel body).

V5E = "TPU v5 lite"
HIGGS_ROWS = 10_500_000


def _candidates(rows, fractions=(0.5, 0.125)):
    """GBDT._compaction_ladder's candidate rungs for ``rows`` rows."""
    out = {-(-max(int(round(rows * fr)), 1) // 64) * 64 for fr in fractions}
    return tuple(sorted(m for m in out if 0 < m < rows))


def _kept(kind, method, rows, f, b, fractions=(0.5, 0.125)):
    from lightgbm_tpu.ops.histogram import prune_compaction_ladder
    return prune_compaction_ladder(_candidates(rows, fractions), kind,
                                   method, rows, f, b)


@pytest.mark.parametrize("method,bins", [
    ("pallas_hilo", 255), ("pallas_hilo", 63), ("pallas_q8", 255),
    ("pallas_q8", 63)])
def test_rule_keeps_no_default_rung_at_the_higgs_shape(method, bins):
    """chip: at 10.5M x 28 neither rung of [0.5, 0.125] pays: the half
    rung costs 2.2-2.9 full passes in every mode measured, the eighth
    rung beats the pass it replaces by 6-19% only where it is taken, and
    4 passes of a tree's 12 take it while all 12 pay the count."""
    assert _candidates(HIGGS_ROWS) == (1312512, 5250048)
    assert _kept(V5E, method, HIGGS_ROWS, 28, bins) == ()


# chip (PERF.md, PR 36, the calibration read again with the bins-major
# kernel body; 2,097,152 rows, rungs N/2, N/8, N/32, block 4096):
# (method, features, bins, divisors that paid by more than 15%, divisors
# that lost by more than 15%), counts charged as rung_costs charges them.
# Points inside the band may fall either way and are left out: N/2 at
# 137 x 255 hilo (-11%). Under the rows-major body of PR 28 the N/8 rung
# still paid at 137 x 63 and N/2 and N/8 under q8 at 137 x 255: a kernel
# a third of the price a row leaves a rung's fixed costs nothing to win.
CHIP_POINTS = [
    ("pallas_hilo", 28, 255, (), (2, 8, 32)),
    ("pallas_hilo", 137, 255, (8,), (32,)),
    ("pallas_hilo", 137, 63, (), (2, 8, 32)),
    ("pallas_q8", 137, 255, (), (2, 8, 32)),
    # the widest shape measured (a 700-feature kernel does not fit VMEM):
    # every rung pays
    ("pallas_hilo", 274, 255, (2, 8, 32), ()),
]


@pytest.mark.parametrize("method,f,bins,paid,lost", CHIP_POINTS)
def test_rule_agrees_with_the_chip(method, f, bins, paid, lost):
    rows = 2_097_152
    kept = _kept(V5E, method, rows, f, bins, (1 / 2, 1 / 8, 1 / 32))
    assert {rows // d for d in paid} <= set(kept), kept
    assert not {rows // d for d in lost} & set(kept), kept


@pytest.mark.parametrize("method", ["pallas_hilo", "pallas_q8", "pallas"])
@pytest.mark.parametrize("rows", [262_144, 2_097_152, HIGGS_ROWS])
def test_rule_is_monotone_in_the_kernels_work(method, rows):
    """A rung kept at F features is kept at 2F; a rung pruned at B bins is
    pruned at B/4: more kernel work a row can only help a rung."""
    fractions = (1 / 2, 1 / 8, 1 / 32)
    widths = (7, 14, 28, 56, 112, 224, 448, 896)
    for bins in (255, 63):
        prev = set()
        for f in widths:
            kept = set(_kept(V5E, method, rows, f, bins, fractions))
            assert prev <= kept, (method, rows, bins, f, prev, kept)
            prev = kept
    for f in widths:
        kept_255 = set(_kept(V5E, method, rows, f, 255, fractions))
        kept_63 = set(_kept(V5E, method, rows, f, 63, fractions))
        assert kept_63 <= kept_255, (method, rows, f)


@pytest.mark.parametrize("bins", [63, 255])
def test_rule_q8_never_keeps_a_rung_hilo_prunes(bins):
    """q8's kernel is the cheaper one, so its rungs lose sooner; HIGHEST's
    is the dearest."""
    fractions = (1 / 2, 1 / 8, 1 / 32)
    for rows in (262_144, 2_097_152, HIGGS_ROWS):
        for f in (7, 28, 137, 274, 700, 2000):
            q8 = set(_kept(V5E, "pallas_q8", rows, f, bins, fractions))
            hilo = set(_kept(V5E, "pallas_hilo", rows, f, bins, fractions))
            high = set(_kept(V5E, "pallas", rows, f, bins, fractions))
            assert q8 <= hilo <= high, (rows, f, bins)


@pytest.mark.parametrize("kind,method", [
    ("cpu", "scatter"), ("cpu", "pallas_hilo"), ("NVIDIA H100", "onehot"),
    (V5E, "scatter"), (V5E, "onehot_hilo"), (V5E, "binloop")])
def test_rule_prunes_nothing_without_constants(kind, method):
    """No row for the device kind, or none for the histogram method on it:
    the candidates come back as they are."""
    from lightgbm_tpu.ops.histogram import (prune_compaction_ladder,
                                            rung_costs)
    for rows, f, b in ((4000, 5, 255), (HIGGS_ROWS, 28, 255)):
        cands = _candidates(rows)
        assert rung_costs(kind, method, rows, f, b, cands[0]) is None
        assert prune_compaction_ladder(cands, kind, method, rows, f,
                                       b) == cands


def test_rule_borrows_the_v5e_row_for_another_tpu():
    """A TPU kind without a row of its own is priced with TPU v5 lite's;
    anything else has no price."""
    from lightgbm_tpu.ops.histogram import rung_costs, rung_costs_source
    args = ("pallas_hilo", HIGGS_ROWS, 28, 255, 1312512)
    assert rung_costs("TPU v6 lite", *args) == rung_costs(V5E, *args)
    assert rung_costs_source("TPU v6 lite") == V5E == rung_costs_source(V5E)
    assert rung_costs_source("cpu") is None


def _booster(X, y, extra):
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              **extra}
    return lgb.Booster(params=params,
                       train_set=lgb.Dataset(X, label=y, params=params))


def _as_device(monkeypatch, kind):
    """GBDT._compaction_ladder asks jax for the device kind; answer for
    it (nothing else runs while the patch is on)."""
    import types

    import jax
    monkeypatch.setattr(
        jax, "devices", lambda *a: [types.SimpleNamespace(device_kind=kind)])


@pytest.mark.parametrize("extra,rows,want", [
    # the shapes this file trains at, and what the parent returned for them
    ({"histogram_method": "scatter"}, 4000, (512, 2048)),
    ({"histogram_method": "onehot"}, 4000, (512, 2048)),
    ({"histogram_method": "scatter", "hist_compaction_ladder": [0.001]},
     4000, (64,)),
    ({"histogram_method": "scatter", "bagging_fraction": 0.4,
      "bagging_freq": 1}, 4000, (256, 832)),
    ({"histogram_method": "scatter"}, 1500, (192, 768)),
    # tests/test_trace_scopes.py's parameters: the chip's default path,
    # interpreted
    ({"min_data_in_leaf": 5, "histogram_method": "pallas_hilo",
      "hist_pallas_interpret": True, "hist_compaction": True,
      "split_fusion": "on"}, 4000, (512, 2048)),
])
def test_ladder_unchanged_on_a_backend_without_constants(rng, extra, rows,
                                                         want):
    """On the CPU (no row in RUNG_COSTS) _compaction_ladder returns the
    parent's tuple, and _serial_grow_statics carries it."""
    X, y = _data(rng, n=rows)
    gb = _booster(X, y, extra)._boosting
    hm = gb._hist_method()
    assert gb._compaction_ladder(hm) == want
    assert gb._serial_grow_statics(hm)["compaction_ladder"] == want


@pytest.mark.parametrize("kind", ["cpu", V5E, "TPU v4", "NVIDIA H100"])
def test_hist_compaction_false_means_no_rung_on_every_backend(
        rng, monkeypatch, kind):
    X, y = _data(rng, n=1500)
    gb = _booster(X, y, {"histogram_method": "scatter",
                         "hist_compaction": False})._boosting
    _as_device(monkeypatch, kind)
    for hm in ("scatter", "pallas_hilo", "pallas_q8"):
        assert gb._compaction_ladder(hm) == ()


def test_booster_prunes_by_device_and_says_so(rng, monkeypatch, capsys):
    """The booster hands the rule its device kind, method and shape, and
    logs candidates, kept rungs and inputs once."""
    from lightgbm_tpu.utils import log
    X, y = _data(rng, n=1500)
    gb = _booster(X, y, {"histogram_method": "scatter"})._boosting
    monkeypatch.setattr(log, "_verbosity", 1)
    monkeypatch.setattr(log, "_logger", None)
    _as_device(monkeypatch, V5E)
    # a 1500 x 5 pass is far too cheap for any rung on the chip
    assert gb._compaction_ladder("pallas_hilo") == ()
    assert gb._compaction_ladder("pallas_hilo") == ()
    # a method the table has no kernel rate for: nothing pruned
    assert gb._compaction_ladder("scatter") == (192, 768)
    said = [ln for ln in capsys.readouterr().err.splitlines()
            if "hist compaction:" in ln]
    assert len(said) == 2, said
    assert ("candidates (192, 768) kept () [TPU v5 lite, pallas_hilo, "
            "N=1500 F=5 B=") in said[0]
    assert "kept (192, 768) [TPU v5 lite, scatter," in said[1]
    assert not any("priced with" in ln for ln in said)
    # a TPU kind without a row of its own says whose constants price it
    _as_device(monkeypatch, "TPU v6 lite")
    assert gb._compaction_ladder("pallas_hilo") == ()
    assert "priced with TPU v5 lite's" in capsys.readouterr().err
