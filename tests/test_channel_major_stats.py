"""The histogram statistics travel channel-major, ``[S, rows]``, from the
gradient phase that makes them to the kernel that reads them, padded once
to the kernel's row blocks (models/grower.py row_stats,
ops/pallas_hist.py stat_rows). These tests hold that the sums are the
numbers they were when the statistics travelled rows-major, ``[N, S]``:

- the kernel's leaf-channel operand, built from an ``[S, C]`` tile, equals
  bit for bit the one built from the ``[C, S]`` tile (the formulation the
  kernel had before, copied below as the reference);
- the interpreted kernel's planes equal its XLA twin's bit for bit at 8
  and 28 columns and in feature blocks of 200, in every mode, at a row
  count that is no multiple of the block;
- a compaction rung's gathered copies are born at the kernel's row
  count, and sum what the plain reference sums over the kept rows;
- trained models are the trees the rows-major statistics grew, on the
  grower's paths: the plain pass, the kernel with and without the fused
  search, the bagging subset and mask, q8, and stream columns.
"""

import hashlib
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import pallas_hist
from lightgbm_tpu.ops.histogram import (_KERNEL_MODE, compact_indices,
                                        histogram_tiles)

pytestmark = pytest.mark.pallas

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_METHOD = {mode: m for m, mode in _KERNEL_MODE.items()}
_TWIN = {"hilo": "onehot_hilo", "highest": "onehot", "q8": "onehot_q8"}


def _rows_major_rhs(stats_rows, leaf, chan_leaf, s, mode):
    """The leaf-channel operand as the kernel built it from a rows-major
    ``[C, S]`` tile: the statistics tiled across the 128 lanes by lane
    concatenation, selected by the leaf match, split into hi || lo."""
    pad = pallas_hist._PAD
    reps = -(-pad // s)
    stat_chan = jnp.concatenate([stats_rows] * reps, axis=1)[:, :pad]
    match = leaf[:, None] == chan_leaf[None, :]
    if mode == "q8":
        return jnp.where(match, stat_chan, jnp.int8(0))
    rhs = jnp.where(match, stat_chan.astype(jnp.float32), jnp.float32(0.0))
    return pallas_hist.split_hilo(rhs) if mode == "hilo" else rhs


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for x in (v if isinstance(v, (list, tuple)) else (v,)):
                x = getattr(x, "jaxpr", x)
                if hasattr(x, "eqns"):
                    yield from _equations(x)


def _bits(x):
    x = np.asarray(x)
    return x.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[x.itemsize])


@pytest.mark.parametrize("p", [42, 5])
@pytest.mark.parametrize("mode", ["hilo", "highest", "q8"])
def test_leaf_channels_equal_the_rows_major_build(mode, p):
    """The operand the kernel contracts, bit for bit, from either tile
    orientation: every live lane (a selected leaf's channel), the lanes of
    inactive slots and the dead lanes past P*S."""
    rng = np.random.RandomState(p)
    c, s = 1024, 3
    if mode == "q8":
        st = rng.randint(-127, 128, size=(c, s)).astype(np.int8)
    else:
        st = rng.randn(c, s).astype(np.float32)
        st[rng.rand(c) < 0.05, 0] = -0.0
    leaf = jnp.asarray(rng.randint(-2, 2 * p, size=c).astype(np.int32))
    sel = np.arange(0, 2 * p, 2, dtype=np.int32)
    sel[1] = -1                                       # an inactive slot
    chan = pallas_hist.chan_leaf_table(jnp.asarray(sel), s)
    assert chan.shape == (pallas_hist._PAD, 2)
    got = pallas_hist._leaf_channels(jnp.asarray(st.T), leaf, chan, s=s,
                                     mode=mode)
    want = _rows_major_rhs(jnp.asarray(st), leaf, chan[:, 0], s, mode)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(_bits(got), _bits(want))


def _kernel_stats(st, block):
    return pallas_hist.pad_stats(jnp.asarray(st.T), st.shape[0], block)


# (mode, features, feature block): 8 and 28 columns in one block in every
# mode, and 400 in blocks of 200, the widest block the rule gives, in hilo
# and q8 (highest differs from hilo in the contraction only)
CASES = [pytest.param(mode, f, None, id=f"{mode}-f{f}")
         for mode in ("hilo", "highest", "q8") for f in (8, 28)] + [
    pytest.param(mode, 400, 200, id=f"{mode}-f400-blocks200")
    for mode in ("hilo", "q8")]


@pytest.mark.parametrize("mode,f,fblock", CASES)
def test_kernel_planes_equal_the_twin(mode, f, fblock):
    """1,500 rows (one 1,536-row body, 36 padding rows) through the
    interpreted kernel and through the XLA twin that sums over the same
    row partition: the float32 planes bit for bit, q8's integers exactly."""
    rng = np.random.RandomState(f)
    n, b, block = 1500, 255, 4096
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    if mode == "q8":
        st = rng.randint(-127, 128, size=(n, 3)).astype(np.int8)
    else:
        st = rng.randn(n, 3).astype(np.float32)
    st[:, 2] = 1
    leaf = jnp.asarray(rng.randint(0, 10, n).astype(np.int32))
    sel = jnp.asarray(np.array([0, 2, 3, 5, 7, 8, -1, 9], np.int32))
    binsT = jnp.asarray(np.ascontiguousarray(bins.T))
    h = pallas_hist.histogram_tiles_pallas_mode(
        binsT, _kernel_stats(st, block), leaf, sel, b, block=block,
        mode=mode, interpret=True, fblock=fblock)
    c = pallas_hist.row_block(n, block)
    twin = histogram_tiles(jnp.asarray(bins), jnp.asarray(st.T), leaf, sel,
                           b, method=_TWIN[mode], block=c)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(twin))


@pytest.mark.parametrize("mode", ["hilo", "q8"])
def test_a_rung_is_born_at_its_block_multiple(mode):
    """A compaction rung of 800 rows, no multiple of the block: the index
    buffer is padded to the kernel's row count before the gather, so the
    statistics come out of the gather as the kernel reads them, [3, 1024],
    and nothing pads them after; the tile sums what the plain reference
    sums over the kept rows (representable statistics: bit for bit)."""
    rng = np.random.RandomState(3)
    n, f, b, m = 2881, 5, 63, 800
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    if mode == "q8":
        st = rng.randint(-127, 128, size=(3, n)).astype(np.int8)
    else:
        st = (rng.randint(-1023, 1024, size=(3, n)) / 1024.0).astype(
            np.float32)
    leaf = jnp.asarray(rng.randint(0, 12, n).astype(np.int32))
    sel = jnp.asarray(np.array([0, 2, 5, -1], np.int32))
    keep = jnp.isin(leaf, jnp.asarray([0, 2, 5]))
    assert int(keep.sum()) <= m
    idx = compact_indices(keep, m)
    binsT = jnp.asarray(np.ascontiguousarray(bins.T))

    def rung(st_):
        return histogram_tiles(jnp.asarray(bins), st_, leaf, sel, b,
                               method=_METHOD[mode], block=4096,
                               binsT=binsT, gather_idx=idx, interpret=True)

    eqns = list(_equations(jax.make_jaxpr(rung)(jnp.asarray(st)).jaxpr))
    rows = pallas_hist.stat_rows(m, 4096)
    assert rows == 1024
    shapes = [tuple(v.aval.shape) for e in eqns
              for v in e.outvars if e.primitive.name == "gather"]
    assert (3, rows) in shapes, shapes
    pads = [tuple(e.invars[0].aval.shape) for e in eqns
            if e.primitive.name == "pad"]
    assert not [sh for sh in pads if sh[0] == 3], pads
    h = np.asarray(rung(jnp.asarray(st)))
    masked = jnp.where(keep[None, :], jnp.asarray(st), 0).astype(st.dtype)
    ref = np.asarray(histogram_tiles(jnp.asarray(bins), masked, leaf, sel, b,
                                     method=_TWIN["q8"] if mode == "q8"
                                     else "scatter"))
    np.testing.assert_array_equal(h[:3], ref[:3])
    assert np.all(h[3] == 0)


def _load_bench(*parts):
    path = os.path.join(REPO, "benchmarks", *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _trees(booster):
    """sha256[:16] of the model text up to its parameter dump."""
    text = booster.model_to_string()
    return hashlib.sha256(
        text[:text.index("\nparameters:")].encode()).hexdigest()[:16]


def _dense():
    rng = np.random.RandomState(4)
    n = 1500
    X = rng.normal(size=(n, 8))
    y = ((2.0 * (X[:, 0] > 0.3) + 1.0 * (X[:, 1] > -0.2)
          + 0.5 * (X[:, 2] > 0.5) + 0.3 * rng.normal(size=n)) > 1.0)
    return X, y.astype(float), {"objective": "binary", "num_leaves": 15,
                                "verbosity": -1, "min_data_in_leaf": 5}, 6


def _streams():
    """6,000 one-hot flights of 314 columns (tests/test_expo_sparse.py's
    groups): bundles and one stream column."""
    expo = _load_bench("data", "expo.py")
    spec = {"features": 314, "sample_seed": 1, "label_bias": -1.9,
            "time_effect": 1.6, "distance_effect": 0.1,
            "groups": [
                {"name": "month", "size": 12, "exponent": 0.05,
                 "effect": 0.15},
                {"name": "day", "size": 31, "exponent": 0.02,
                 "effect": 0.05},
                {"name": "weekday", "size": 7, "exponent": 0.05,
                 "effect": 0.12},
                {"name": "carrier", "size": 22, "exponent": 1.0,
                 "offset": 2.0, "effect": 0.25},
                {"name": "origin", "size": 200, "exponent": 1.6,
                 "offset": 4.0, "effect": 0.3},
                {"name": "destination", "size": 40, "exponent": 1.6,
                 "offset": 4.0, "effect": 0.2}]}
    X, y = expo.make(spec, 1, 6000, 6000)
    return X, y, {"objective": "binary", "num_leaves": 15,
                  "min_data_in_leaf": 3, "verbosity": -1}, 4


_KERNEL = {"histogram_method": "pallas", "hist_pallas_interpret": True}
_HILO = {"histogram_method": "pallas_hilo", "hist_pallas_interpret": True}
_SUBSET = {"bagging_fraction": 0.4, "bagging_freq": 1}

# sha256[:16] of the trees, taken by _trees on the commit whose statistics
# travelled rows-major (7a8e8a2), the CPU backend: the kernel cases run
# the interpreted kernel with its compaction rungs (every candidate rung
# is kept off the chip), the others the scatter-add
BEFORE = [
    pytest.param(_dense, {}, "cdf692446e641e2e", id="scatter"),
    pytest.param(_dense, _KERNEL, "a808b00e5ffaaeb4", id="kernel-highest"),
    pytest.param(_dense, _HILO, "af8830b8a548046d", id="kernel-hilo"),
    pytest.param(_dense, {**_HILO, "split_fusion": "off"},
                 "af8830b8a548046d", id="kernel-hilo-classic-search"),
    pytest.param(_dense, _SUBSET, "a65a5ff40855a1c5", id="subset-scatter"),
    pytest.param(_dense, {**_HILO, **_SUBSET}, "a6805f3b9633f2a3",
                 id="subset-kernel-hilo"),
    pytest.param(_dense, {**_HILO, "bagging_fraction": 0.7,
                          "bagging_freq": 1}, "a2b966b2b5353da3",
                 id="mask-kernel-hilo"),
    pytest.param(_streams, {}, "f4468dbd0f202807", id="streams-scatter"),
    pytest.param(_streams, _HILO, "1868fd60d6b7e5f1",
                 id="streams-kernel-hilo"),
]


@pytest.mark.parametrize("data,extra,want", BEFORE)
def test_the_trees_are_those_the_rows_major_statistics_grew(data, extra,
                                                            want):
    """Every path that makes or reads the statistics trains the same
    trees, to the bit, as when they travelled rows-major: the root sums,
    the kernel and its twin, a rung's gather, the bagging subset's and
    the mask's statistics, the stream entries' gather."""
    X, y, params, rounds = data()
    params = {**params, **extra}
    ds = lgb.Dataset(X, label=y, params=params)
    booster = lgb.train(params, ds, rounds)
    if data is _streams:
        assert ds.construct_stats["sparse_stream_columns"] >= 1
    assert _trees(booster) == want


def test_q8_trains_the_same_trees_through_kernel_and_twin():
    """q8 draws its stochastic rounding in the statistics' channel-major
    shape, so its trees are not the rows-major draw's; the interpreted
    int8 kernel and the XLA int8 twin still grow them alike."""
    X, y, params, rounds = _dense()
    got = []
    for extra in ({"quantized_grad": True},
                  {"quantized_grad": True, "hist_pallas_interpret": True}):
        p = {**params, **extra}
        got.append(_trees(lgb.train(p, lgb.Dataset(X, label=y, params=p),
                                    rounds)))
    assert got[0] == got[1]
