"""Fused Pallas histogram kernel vs the XLA one-hot backend
(ops/pallas_hist.py). Runs in Pallas interpret mode so the parity check
works on CPU hosts; tests/test_chip_compile.py hands the kernels to the TPU
compiler and chip_smoke.py runs them on the chip."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def test_pallas_hist_matches_onehot(monkeypatch):
    from lightgbm_tpu.ops import pallas_hist
    from lightgbm_tpu.ops.histogram import histogram_tiles

    # interpret mode: emulate the kernel on CPU
    from jax.experimental import pallas as pl
    orig_call = pl.pallas_call

    def interp_call(*args, **kwargs):
        kwargs.pop("compiler_params", None)
        kwargs["interpret"] = True
        return orig_call(*args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", interp_call)

    rng = np.random.RandomState(0)
    n, f, b, p = 5000, 6, 16, 8
    binsT = jnp.asarray(rng.randint(0, b, size=(f, n)).astype(np.int8))
    bins = jnp.asarray(np.ascontiguousarray(np.asarray(binsT).T))
    stats = jnp.asarray(rng.rand(n, 3).astype(np.float32))
    leaf = jnp.asarray(rng.randint(0, 12, n).astype(np.int32))
    sel = jnp.asarray(np.array([0, 2, 5, 7, 9, 11, -1, -1], np.int32))

    h_pl = pallas_hist.histogram_tiles_pallas_mode(
        binsT, stats, leaf, sel, b, block=512, mode="highest")
    h_ref = histogram_tiles(bins, stats, leaf, sel, b, method="scatter")
    np.testing.assert_allclose(np.asarray(h_pl), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-4)


def test_pallas_hilo_matches_scatter(monkeypatch):
    """hi/lo bf16 kernel parity (coarser input rounding: ~2^-17 relative)."""
    from lightgbm_tpu.ops import pallas_hist
    from lightgbm_tpu.ops.histogram import histogram_tiles

    from jax.experimental import pallas as pl
    orig_call = pl.pallas_call

    def interp_call(*args, **kwargs):
        kwargs.pop("compiler_params", None)
        kwargs["interpret"] = True
        return orig_call(*args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", interp_call)

    rng = np.random.RandomState(1)
    n, f, b, p = 5000, 6, 16, 8
    binsT = jnp.asarray(rng.randint(0, b, size=(f, n)).astype(np.int8))
    bins = jnp.asarray(np.ascontiguousarray(np.asarray(binsT).T))
    stats_np = rng.randn(n, 3).astype(np.float32)
    stats_np[:, 2] = 1.0          # count channel is 0/1 in production
    stats = jnp.asarray(stats_np)
    leaf = jnp.asarray(rng.randint(0, 12, n).astype(np.int32))
    sel = jnp.asarray(np.array([0, 2, 5, 7, 9, 11, -1, -1], np.int32))

    h_pl = pallas_hist.histogram_tiles_pallas_mode(
        binsT, stats, leaf, sel, b, block=512, mode="hilo")
    h_ref = histogram_tiles(bins, stats, leaf, sel, b, method="scatter")
    ref = np.asarray(h_ref)
    # hi/lo bf16 input rounding is ~2^-16 per element; signed-sum
    # cancellation amplifies the relative error on small cells
    np.testing.assert_allclose(np.asarray(h_pl), ref,
                               rtol=1e-3, atol=1e-3 * np.abs(ref).max())
    # count channel is exact (0/1 one-hot x 0/1 bf16)
    np.testing.assert_array_equal(np.asarray(h_pl)[..., 2], ref[..., 2])


def test_onehot_hilo_matches_scatter():
    from lightgbm_tpu.ops.histogram import histogram_tiles
    rng = np.random.RandomState(2)
    n, f, b = 4000, 5, 32
    bins = jnp.asarray(rng.randint(0, b, size=(n, f)).astype(np.int8))
    stats_np = rng.randn(n, 3).astype(np.float32)
    stats_np[:, 2] = 1.0          # count channel is 0/1 in production
    stats = jnp.asarray(stats_np)
    leaf = jnp.asarray(rng.randint(0, 10, n).astype(np.int32))
    sel = jnp.asarray(np.array([0, 3, 6, 9, -1], np.int32))
    h = histogram_tiles(bins, stats, leaf, sel, b, method="onehot_hilo")
    ref = np.asarray(histogram_tiles(bins, stats, leaf, sel, b,
                                     method="scatter"))
    np.testing.assert_allclose(np.asarray(h), ref,
                               rtol=3e-3, atol=1e-3 * np.abs(ref).max())
    np.testing.assert_array_equal(np.asarray(h)[..., 2], ref[..., 2])


def test_pallas_method_fallback_off_tpu():
    """histogram_tiles(method='pallas_hilo') on a CPU backend must fall back
    to the XLA onehot formulation and still be correct (the production
    'auto' resolution path for non-TPU hosts never selects pallas, but an
    explicit config choice must not crash)."""
    from lightgbm_tpu.ops.histogram import histogram_tiles
    rng = np.random.RandomState(3)
    n, f, b = 3000, 4, 16
    bins_np = rng.randint(0, b, size=(n, f)).astype(np.int8)
    bins = jnp.asarray(bins_np)
    binsT = jnp.asarray(np.ascontiguousarray(bins_np.T))
    stats = jnp.asarray(rng.randn(n, 3).astype(np.float32))
    leaf = jnp.asarray(rng.randint(0, 6, n).astype(np.int32))
    sel = jnp.asarray(np.array([0, 1, 2, 5], np.int32))
    h = histogram_tiles(bins, stats, leaf, sel, b, method="pallas_hilo",
                        binsT=binsT)
    ref = np.asarray(histogram_tiles(bins, stats, leaf, sel, b,
                                     method="scatter"))
    np.testing.assert_allclose(np.asarray(h), ref,
                               rtol=1e-3, atol=1e-3 * np.abs(ref).max())


@pytest.mark.slow
def test_grower_pallas_hilo_end_to_end():
    """grow_tree with hist_method='pallas_hilo' (CPU fallback path) grows
    the same tree as the scatter backend on well-separated data.

    Slow: the hilo kernel's histogram parity stays tier-1 via the unit
    kernel-vs-reference cases above, an end-to-end interpret-kernel
    training runs tier-1 in
    test_split_fusion.py::test_e2e_fusion_bit_parity_kernel[default],
    and scripts/kernel_bench.py --fast --interpret exercises the hilo
    mode on every CI pass (tests/run_suite.sh)."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(4)
    n = 2000
    X = rng.normal(size=(n, 5))
    y = (X[:, 0] > 0.3).astype(float) + 0.01 * rng.normal(size=n)
    preds = {}
    for hm in ("scatter", "pallas_hilo"):
        ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
        booster = lgb.train({"objective": "regression", "num_leaves": 15,
                             "histogram_method": hm, "verbosity": -1},
                            ds, num_boost_round=5)
        preds[hm] = booster.predict(X)
    # leaf outputs inherit the ~1e-3 relative histogram rounding of the
    # hi/lo fast path (a few rows reach ~7e-3 on the CPU interpret path);
    # structure-level agreement is what matters here — a wrong split
    # shows up as O(0.1) prediction jumps, far above this tolerance
    np.testing.assert_allclose(preds["pallas_hilo"], preds["scatter"],
                               rtol=1e-2, atol=1e-4)


def test_onehot_q8_integer_parity():
    """The int8 contraction produces EXACT integer histograms: parity vs a
    numpy integer reference."""
    from lightgbm_tpu.ops.histogram import histogram_tiles
    rng = np.random.RandomState(5)
    n, f, b = 3000, 4, 16
    bins_np = rng.randint(0, b, size=(n, f)).astype(np.int8)
    stats_np = rng.randint(-127, 128, size=(n, 3)).astype(np.int8)
    leaf_np = rng.randint(0, 6, n).astype(np.int32)
    sel_np = np.array([0, 2, 4, 5], np.int32)
    h = np.asarray(histogram_tiles(
        jnp.asarray(bins_np), jnp.asarray(stats_np), jnp.asarray(leaf_np),
        jnp.asarray(sel_np), b, method="onehot_q8"))
    ref = np.zeros((4, f, b, 3), np.int64)
    for p_i, leaf in enumerate(sel_np):
        rows = np.nonzero(leaf_np == leaf)[0]
        for j in range(f):
            for r in rows:
                ref[p_i, j, bins_np[r, j]] += stats_np[r]
    np.testing.assert_array_equal(h.astype(np.int64), ref)


def test_pallas_q8_matches_onehot_q8(monkeypatch):
    from lightgbm_tpu.ops import pallas_hist
    from lightgbm_tpu.ops.histogram import histogram_tiles
    from jax.experimental import pallas as pl
    orig_call = pl.pallas_call

    def interp_call(*args, **kwargs):
        kwargs.pop("compiler_params", None)
        kwargs["interpret"] = True
        return orig_call(*args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", interp_call)
    rng = np.random.RandomState(6)
    n, f, b = 4000, 5, 16
    binsT_np = rng.randint(0, b, size=(f, n)).astype(np.int8)
    stats_np = rng.randint(-127, 128, size=(n, 3)).astype(np.int8)
    leaf_np = rng.randint(0, 8, n).astype(np.int32)
    sel_np = np.array([0, 1, 3, 5, 7], np.int32)
    h_pl = np.asarray(pallas_hist.histogram_tiles_pallas_mode(
        jnp.asarray(binsT_np), jnp.asarray(stats_np), jnp.asarray(leaf_np),
        jnp.asarray(sel_np), b, block=512, mode="q8"))
    h_ref = np.asarray(histogram_tiles(
        jnp.asarray(np.ascontiguousarray(binsT_np.T)), jnp.asarray(stats_np),
        jnp.asarray(leaf_np), jnp.asarray(sel_np), b, method="onehot_q8"))
    np.testing.assert_array_equal(h_pl, h_ref)


@pytest.mark.slow
def test_quantized_training_quality():
    # ~14 s: end-to-end quality check of the OPT-IN q8 mode (tier-1 keeps
    # the q8 kernel-correctness tests in this file; quality rides slow)
    """End-to-end training with histogram_method=pallas_q8 (CPU fallback:
    onehot_q8 + the grower's int8 quantization) stays close to full
    precision — the quantized-gradient mode's quality contract."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(7)
    n = 4000
    X = rng.normal(size=(n, 6))
    y = (X[:, 0] + 0.6 * X[:, 1] + 0.2 * rng.normal(size=n) > 0).astype(
        np.float64)

    def acc(hm):
        ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
        booster = lgb.train({"objective": "binary", "num_leaves": 31,
                             "histogram_method": hm, "verbosity": -1},
                            ds, num_boost_round=20)
        return float(np.mean((booster.predict(X) > 0.5) == (y > 0.5)))

    a_full = acc("scatter")
    a_q8 = acc("pallas_q8")
    assert a_q8 >= a_full - 0.01, (a_full, a_q8)


def _sub_jaxprs(jaxpr):
    """``jaxpr`` and every jaxpr nested in its equations' parameters (the
    pallas_call's kernel body, the chunk loop's, ...)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for x in (v if isinstance(v, (list, tuple)) else (v,)):
                x = getattr(x, "jaxpr", x)
                if hasattr(x, "eqns"):
                    yield from _sub_jaxprs(x)


@pytest.mark.parametrize("b", [255, 63])
@pytest.mark.parametrize("mode", ["hilo", "highest", "q8"])
def test_accumulate_onehot_is_bins_major(mode, b):
    """The kernel body builds its one-hot bins-major and hands it to the
    MXU as built: every contraction is a plain [M, K] x [K, N] over the
    one-hot's dimension 1, nothing is transposed, and no rows-major
    [C, m*b] one-hot exists — in the packed form (b=63, with a remainder
    group) as in the plain one, and inside the chunk loop."""
    from lightgbm_tpu.ops import pallas_hist
    f, s, n = 5, 3, 4096
    c = pallas_hist._CHUNK
    jaxpr = jax.make_jaxpr(functools.partial(
        pallas_hist._fused_call, num_bins=b, block=2 * c, mode=mode))(
        jnp.zeros((f, n), jnp.uint8), jnp.zeros((1, n), jnp.int32),
        jnp.zeros((n, s), jnp.int8 if mode == "q8" else jnp.float32),
        jnp.zeros((1, pallas_hist._PAD), jnp.int32))
    eqns = [e for j in _sub_jaxprs(jaxpr.jaxpr) for e in j.eqns]
    g = max(1, pallas_hist._PAD // b)
    widths = {min(g, f - j0) * b for j0 in range(0, f, g)}
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == -(-f // g)
    for e in dots:
        assert e.params["dimension_numbers"] == (((1,), (0,)), ((), ()))
        lhs, rhs = (v.aval.shape for v in e.invars)
        assert lhs[0] in widths and lhs[1] == c == rhs[0], (lhs, rhs)
    assert not [e for e in eqns if e.primitive.name == "transpose"]
    rows_major = {(c, w) for w in widths}
    assert not [v.aval.shape for e in eqns for v in e.outvars
                if tuple(v.aval.shape) in rows_major]


@pytest.mark.parametrize("b,f,block", [(63, 5, 1536), (16, 11, 512)])
@pytest.mark.parametrize("mode", ["hilo", "highest", "q8"])
def test_packed_remainder_group_matches_scatter(mode, b, f, block):
    """The packed path (b <= 64: g features share one one-hot) with a
    remainder group (f % g != 0), at a row block that is not a multiple of
    the chunk (1536: one body over the whole block), against the flat
    scatter-add: exact for q8, the float modes to their tolerances."""
    from lightgbm_tpu.ops import pallas_hist
    from lightgbm_tpu.ops.histogram import histogram_scatter
    g = pallas_hist._PAD // b
    chunk = pallas_hist._CHUNK
    assert f % g and (block <= chunk or block % chunk)
    rng = np.random.RandomState(8)
    n = 4000
    binsT = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    if mode == "q8":
        stats_np = rng.randint(-127, 128, size=(n, 3)).astype(np.int8)
    else:
        stats_np = rng.randn(n, 3).astype(np.float32)
    stats_np[:, 2] = 1
    leaf = jnp.asarray(rng.randint(0, 12, n).astype(np.int32))
    sel_np = np.array([0, 2, 5, 7, 9, 11, -1, -1], np.int32)
    h = np.asarray(pallas_hist.histogram_tiles_pallas_mode(
        jnp.asarray(binsT), jnp.asarray(stats_np), leaf, jnp.asarray(sel_np),
        b, block=block, mode=mode, interpret=True))
    ref = np.asarray(histogram_scatter(
        jnp.asarray(np.ascontiguousarray(binsT.T)), jnp.asarray(stats_np),
        leaf, 12, b))[sel_np]
    ref[sel_np < 0] = 0
    if mode == "q8":
        np.testing.assert_array_equal(h, ref.astype(np.int32))
        return
    tol = (dict(rtol=1e-5, atol=1e-4) if mode == "highest"
           else dict(rtol=1e-3, atol=1e-3 * np.abs(ref).max()))
    np.testing.assert_allclose(h, ref, **tol)
    np.testing.assert_array_equal(h[..., 2], ref[..., 2])


@pytest.mark.parametrize("mode", ["highest", "hilo"])
@pytest.mark.parametrize("n,f,b,block", [
    (1500, 5, 255, 4096),     # one 1536-row body, as the e2e parity run
    (5000, 5, 255, 4096),     # two blocks walked in _CHUNK-row chunks
    (3000, 7, 63, 2048)])     # packed one-hot with a remainder group
def test_kernel_bits_equal_xla_twin(mode, n, f, b, block):
    """The interpreted kernel's float32 sums are BIT for bit those of its
    XLA twin (ops/histogram.py ``onehot`` / ``onehot_hilo``) on full-
    mantissa statistics, where the twin's row blocks are the kernel's
    partial sums: the whole block where one body covers it, _CHUNK rows
    where the chunk loop walks it. Both contract a bins-major one-hot with
    a channel matrix a whole lane group wide, so a backend whose summation
    order follows the shapes (the CPU's) sums both alike."""
    from lightgbm_tpu.ops import pallas_hist
    from lightgbm_tpu.ops.histogram import histogram_tiles
    rng = np.random.RandomState(n)
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    args = (jnp.asarray(bins), jnp.asarray(rng.randn(n, 3).astype(np.float32)),
            jnp.asarray(rng.randint(0, 10, n).astype(np.int32)),
            jnp.arange(8, dtype=jnp.int32), b)
    c = pallas_hist._row_operands(jnp.asarray(bins.T), args[2], args[1],
                                  block, mode)[3]
    part = pallas_hist._CHUNK if c % pallas_hist._CHUNK == 0 else c
    kernel = {"highest": "pallas", "hilo": "pallas_hilo"}[mode]
    h = histogram_tiles(*args, method=kernel, block=block,
                        binsT=jnp.asarray(np.ascontiguousarray(bins.T)),
                        interpret=True)
    twin = histogram_tiles(*args, method=kernel.replace("pallas", "onehot"),
                           block=part)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(twin))
