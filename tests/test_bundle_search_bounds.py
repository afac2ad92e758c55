"""The bundled search reads a segment's bounds without a gather
(ops/split.segment_prefix_sums): bit for bit what two ``take_along_axis``
of the whole ``[L, F, B, 3]`` plane gave until PR 40, on every table
``basic.Dataset`` writes.

The oracle is that gather form, kept with the microbenchmark that times
the two against each other on the chip (scripts/bundle_search_bench.py:
``gather_bounds``; its contraction form ``onehot_bounds`` is held to the
same bits here, on the CPU's exact float32 product), as is the helper
that has ``Dataset._build_feature_meta_bundled`` itself write the tables
of a described layout.
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.binning import MISSING_ZERO
from lightgbm_tpu.config import Config
from lightgbm_tpu.ops.split import BundleMeta, SplitParams

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "bundle_search_bench",
    os.path.join(REPO, "scripts", "bundle_search_bench.py"))
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)

ONE_HOT = bench.ONE_HOT
# device columns: an int is a plain column of that many bins, a list the
# members (num_bin, most_freq_bin, missing_type) of a bundle column
LAYOUTS = {
    # the plain column is narrower than B: its bins past num_bins - 1 lie
    # beyond their own seg_hi, as a bundle's trailing padding does
    "plain-beside-bundles": [[ONE_HOT] * 4, 7, [(3, 1, 0), ONE_HOT], 16],
    "one-hot-members-of-span-2": [[ONE_HOT] * 7, [ONE_HOT] * 7],
    "spans-3-to-6": [[(3, 1, 0), (4, 2, 0), (5, 0, 0), (6, 3, 0)],
                     [(6, 5, 0), (3, 0, 0), (5, 4, 0), (4, 0, 0)]],
    "most-freq-bin-0-phantom-candidate": [[(4, 0, 0), (3, 0, 0)], 8],
    "zero-as-missing-member": [[(5, 2, MISSING_ZERO), (4, 1, MISSING_ZERO),
                                (3, 0, 0)], 13],
    "last-member-ends-before-B": [[ONE_HOT] * 2, [(3, 1, 0)] * 5],
}
LEAVES = 9


def _csr_dataset_tables():
    sp = pytest.importorskip("scipy.sparse")
    rng = np.random.RandomState(3)
    x = sp.random(3000, 120, density=0.01, random_state=rng, format="csr",
                  data_rvs=lambda k: rng.uniform(0.5, 2.0, k))
    ds = lgb.Dataset(x, label=rng.rand(3000) > 0.5,
                     params={"min_data_in_leaf": 5, "verbosity": -1})
    ds.construct()
    assert ds.bundle_meta is not None and bool(ds.bundle_meta.is_bundle.any())
    return ds.feature_meta, ds.bundle_meta


@pytest.fixture(scope="module", params=[*LAYOUTS, "csr-dataset"])
def tables(request):
    """``(FeatureMeta, BundleMeta)`` of a layout, written by the library."""
    if request.param == "csr-dataset":
        return _csr_dataset_tables()
    return bench.bundle_tables(LAYOUTS[request.param])


def _planes(meta, bundle, every_bin=False):
    """Histograms with empty bins and gradients of both signs; with
    ``every_bin`` also mass where a pass leaves none (past a column's
    ``num_bins``), which the bounds have to copy all the same."""
    bins = bundle.seg_lo.shape[1]
    hist, tot = bench.planes(np.random.default_rng(5), LEAVES, meta, bins)
    if every_bin:
        hist = hist + np.float32(0.5)
    assert (hist[..., 0] < 0).any() and (hist[..., 2] == 0).any() != every_bin
    return jnp.asarray(hist), jnp.asarray(tot)


def _assert_same_bits(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        if g.dtype == np.float32:
            g, w = g.view(np.uint32), w.view(np.uint32)
        np.testing.assert_array_equal(g, w)


def _traced_with(form, fn, *args):
    with bench.bounds_read_by(form):
        return jax.jit(lambda *a: fn(*a))(*args)


@pytest.mark.parametrize("every_bin", [False, True],
                         ids=["planes-of-a-pass", "mass-in-every-bin"])
def test_directional_sums_equal_the_gathers_bits(tables, every_bin):
    """All twelve arrays, at EVERY bin: also those no candidate can sit on
    (a plain column's bins past its last, a bundle's trailing padding),
    where an unsplittable leaf's argmax may still land."""
    meta, bundle = tables
    hist, tot = _planes(meta, bundle, every_bin)
    want = _traced_with(bench.gather_bounds, bench.sums, hist, tot, bundle)
    assert len(want) == 12
    _assert_same_bits(_traced_with(None, bench.sums, hist, tot, bundle), want)
    _assert_same_bits(
        _traced_with(bench.onehot_bounds, bench.sums, hist, tot, bundle),
        want)


def test_find_best_splits_returns_the_gathers_split(tables):
    """Every field of the SplitInfo, ``seg_lo`` / ``seg_hi`` included."""
    meta, bundle = tables
    hist, tot = _planes(meta, bundle)
    params = SplitParams.from_config(Config(min_data_in_leaf=20))
    want = _traced_with(bench.gather_bounds, bench.search, hist, tot, meta,
                        bundle, params)
    got = _traced_with(None, bench.search, hist, tot, meta, bundle, params)
    assert np.isfinite(np.asarray(got.gain)).any()
    _assert_same_bits(got, want)


def test_a_feature_blocks_slice_of_the_tables_reads_the_same_bounds():
    """The feature-blocked search (models/grower.py) hands the search a
    ``[f_block, B]`` slice of every table."""
    meta, bundle = bench.bundle_tables(LAYOUTS["plain-beside-bundles"])
    hist, tot = _planes(meta, bundle)
    part = BundleMeta(*(a[1:3] for a in bundle))
    want = _traced_with(bench.gather_bounds, bench.sums, hist[:, 1:3], tot,
                        part)
    _assert_same_bits(_traced_with(None, bench.sums, hist[:, 1:3], tot, part),
                      want)


# ------------------------------------------------------- the program's text

L, F, B = 255, 11, 255


def _lowered_search(bundled):
    """StableHLO of ``find_best_splits`` at ``expo.train``'s shapes."""
    meta, bundle = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        bench.bundle_tables(bench.EXPO_COLUMNS))
    assert bundle.seg_lo.shape == (F, B)
    params = SplitParams(*(jax.ShapeDtypeStruct((), jnp.float32)
                           for _ in SplitParams._fields))

    def search(hist, tot, meta, bundle, params):
        return bench.search(hist, tot, meta, bundle if bundled else None,
                            params)

    return jax.jit(search).lower(
        jax.ShapeDtypeStruct((L, F, B, 3), jnp.float32),
        jax.ShapeDtypeStruct((L, 3), jnp.float32),
        meta, bundle, params).as_text()


def _gathered_elements(text):
    """Result sizes of every gather in a StableHLO text."""
    sizes = []
    for ln in text.splitlines():
        if "stablehlo.gather" in ln or "stablehlo.dynamic_gather" in ln:
            result = re.findall(r"tensor<([\dx]+)x\w+>", ln)[-1]
            sizes.append(int(np.prod([int(d) for d in result.split("x")])))
    return sizes


def test_the_bundled_search_gathers_no_plane():
    """Two gathers of 2,145,825 elements took 0.51 of ``expo.train``'s 2.51
    s an iteration (10.8 ns an element). What is left are the twelve reads
    of the chosen candidate, ``[L]`` elements each, and the tables' own."""
    sizes = _gathered_elements(_lowered_search(bundled=True))
    assert sizes and max(sizes) < L * F * B, sorted(sizes)[-3:]
    with bench.bounds_read_by(bench.gather_bounds):
        old = _gathered_elements(_lowered_search(bundled=True))
    assert max(old) == L * F * B * 3        # the guard sees the old form's


def test_the_unbundled_search_keeps_its_one_scan():
    """``bundle is None`` lowers to the program it lowered to: the plain
    ``prefix_sum``, no second scan, no latch."""
    assert _lowered_search(bundled=False).count("stablehlo.while") == 1
    assert _lowered_search(bundled=True).count("stablehlo.while") == 2


def test_the_benchmark_script_runs_at_a_tiny_shape(capsys):
    bench.main(["--cpu"])
    rows = [ln for ln in capsys.readouterr().out.splitlines()
            if "bits_differ" in ln]
    assert len(rows) == 3 and all(
        '"sums_bits_differ_from_gather": 0, '
        '"split_bits_differ_from_gather": 0' in ln for ln in rows), rows
