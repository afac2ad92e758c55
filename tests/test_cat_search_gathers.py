"""The categorical search moves its sorted sums and its backward reads
without a gather (ops/split.sort_with_key, read_reversed): bit for bit
what ``argsort`` and seven ``take_along_axis`` of the whole ``[L, F, B]``
plane gave, in every field ``find_best_cat_splits`` returns.

The oracle is that gather form, kept with the microbenchmark that times
the two against each other on the chip (scripts/cat_search_bench.py:
``gather_sort``, ``gather_read_reversed``), as are the planes: counts
from a few values, so ratios tie and some bins fall under ``cat_smooth``.
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.ops.split import SplitParams

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "cat_search_bench", os.path.join(REPO, "scripts", "cat_search_bench.py"))
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)

GATHER = bench.FORMS["gather"]
LEAVES = 9
PARAMS = {
    "defaults": Config(min_data_in_leaf=20),
    # few bins a side, every group and leaf size admitted, so the cap
    # decides where the candidates end
    "max-cat-threshold-2": Config(max_cat_threshold=2, min_data_per_group=1,
                                  min_data_in_leaf=1,
                                  min_sum_hessian_in_leaf=0.0, cat_l2=1.0),
}


def _columns(bins):
    """One-hot columns (``num_bins <= max_cat_to_onehot``) beside sorted
    ones, one of them ``B`` bins wide, and a numerical column last."""
    return (3, 4, bins // 2 + 1, bins, bins, bins), 5


def _planes(bins, seed=5):
    """The bench's planes, with the widest sorted column (feature 3) of
    leaves 0-3 holding 0, 1, 2 and ``B - 1`` bins of ``cat_smooth`` rows
    or more."""
    num_bins, categorical = _columns(bins)
    rng = np.random.default_rng(seed)
    hist, tot = bench.planes(rng, LEAVES, num_bins, bins)
    c = hist[:4, 3, :, 2]
    c[:3] = np.where(c[:3] >= 10, 3.0, c[:3])
    c[1, 4], c[2, 2], c[2, bins - 1] = 250, 100, 100
    c[3, 1:] = np.where(c[3, 1:] >= 10, c[3, 1:], 100)
    hist[:4, 3, :, 1] = 0.25 * c
    hist[:4, 3, :, 0] = c * rng.choice([-0.5, 0.25, 0.5], c.shape)
    valid = (hist[..., 2] >= 10) & (np.arange(bins) >= 1)
    assert list(valid[:4, 3].sum(axis=1)) == [0, 1, 2, bins - 1]
    ratio = hist[..., 0] / (hist[..., 1] + 10)
    assert any(len(set(r[v])) < v.sum() for r, v in
               zip(ratio.reshape(-1, bins), valid.reshape(-1, bins)))
    return (jnp.asarray(hist), jnp.asarray(tot),
            bench.feature_meta(num_bins, categorical))


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
    with bench.reads_by(form):
        return jax.jit(lambda *a: fn(*a))(*args)


@pytest.mark.parametrize("bins", [255, 16])
@pytest.mark.parametrize("params", sorted(PARAMS))
@pytest.mark.parametrize("mask", ["1-d", "2-d"])
def test_find_best_cat_splits_returns_the_gathers_split(bins, params, mask):
    """Gain, feature, left g / h / count, bitset words and l2."""
    hist, tot, meta = _planes(bins)
    p = SplitParams.from_config(PARAMS[params])
    f = hist.shape[1]
    fmask = jnp.ones((f,), bool)
    if mask == "2-d":
        fmask = jnp.asarray(np.random.default_rng(1).random((LEAVES, f)) < 0.7)
    want = _traced_with(GATHER, bench.search, hist, tot, meta, p, fmask)
    got = _traced_with(None, bench.search, hist, tot, meta, p, fmask)
    gain, feature = np.asarray(got[0]), np.asarray(got[1])
    assert np.isfinite(gain).sum() >= LEAVES - 1
    assert len(set(feature[np.isfinite(gain)])) >= 2
    _assert_same_bits(got, want)


@pytest.mark.parametrize("bins", [255, 256, 16, 15, 3])
def test_the_helpers_copy_the_gathers_elements(bins):
    """Every element, also where no candidate sits: the sort's order and
    its three planes, and the reversed reads from every start in
    ``[-2, B - 1]`` (the reads past ``start`` are 0)."""
    num_bins = (bins,) * 4
    hist, _ = bench.planes(np.random.default_rng(2), 72, num_bins, bins)
    hist = jnp.asarray(hist)
    _assert_same_bits(_traced_with(None, bench.reads, hist),
                      _traced_with(GATHER, bench.reads, hist))


# ------------------------------------------------------- the program's text

L, F, B = 255, 8, 255


def _lowered_search():
    """StableHLO of ``find_best_cat_splits`` at ``expo-cat.train``'s
    shapes."""
    meta = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        bench.feature_meta(bench.EXPO_CAT_BINS,
                                           bench.EXPO_CAT_COLUMNS))
    params = SplitParams(*(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in
                           SplitParams.from_config(Config())))
    return jax.jit(lambda *a: bench.search(*a)).lower(
        jax.ShapeDtypeStruct((L, F, B, 3), jnp.float32),
        jax.ShapeDtypeStruct((L, 3), jnp.float32),
        meta, params, jax.ShapeDtypeStruct((F,), jnp.bool_)).as_text()


def _gathered_elements(text):
    """Result sizes of every gather in a StableHLO text."""
    sizes = []
    for ln in text.splitlines():
        if "stablehlo.gather" in ln or "stablehlo.dynamic_gather" in ln:
            result = re.findall(r"tensor<([\dx]+)x\w+>", ln)[-1]
            sizes.append(int(np.prod([int(d) for d in result.split("x")])))
    return sizes


def test_the_categorical_search_gathers_no_plane():
    """Seven gathers of 520,200 elements took 0.306 of ``expo-cat.train``'s
    2.23 s an iteration (4.855 ms each a search round). What is left are
    the reads of the chosen candidate, ``[L]`` or ``[L, B]`` elements.
    (StableHLO holds ``take_along_axis`` once, as a function called seven
    times: the compiled text of the benchmark's test below counts them.)"""
    sizes = _gathered_elements(_lowered_search())
    assert sizes and max(sizes) < L * F * B, sorted(sizes)[-3:]
    with bench.reads_by(GATHER):
        old = _gathered_elements(_lowered_search())
    assert max(old) == L * F * B            # the guard sees the old form's


def test_one_sort_carries_the_sums():
    """The plane's sort has five operands, the key, the order and the
    three sums; the only other sort is the chosen rows' ``[L, B]`` rank,
    a key and its order, as both sorts were in the gather form."""
    def results(text):
        return sorted(re.findall(r'%\w+(?::(\d+))? = "stablehlo.sort"', text))

    assert results(_lowered_search()) == ["2", "5"]
    with bench.reads_by(GATHER):
        assert results(_lowered_search()) == ["2", "2"]


def test_the_benchmark_script_runs_at_a_tiny_shape(capsys):
    bench.main(["--cpu"])
    rows = [ln for ln in capsys.readouterr().out.splitlines()
            if "bits_differ" in ln]
    assert len(rows) == 2 and all(
        '"reads_bits_differ_from_gather": 0, "bits_differ": 0' in ln
        for ln in rows), rows
    assert '"plane_gathers": 7' in rows[0] and '"plane_gathers": 0' in rows[1]
