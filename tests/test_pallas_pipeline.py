"""Pallas-first histogram pipeline: parity, traffic accounting, and the
quantized-gradient training mode (ops/pallas_hist.py, the primary TPU path).

Kernel-level checks run the REAL kernels through the Pallas interpreter
(``interpret=True``) so the fused leaf-channel build and the compaction
rungs that feed it are exercised on CPU hosts; end-to-end checks train
through ``hist_pallas_interpret=true``. Precision contracts under test:

- "highest": bit-exact vs the scatter reference whenever the sums are
  exactly representable (the claim a matmul formulation can actually make;
  with full-mantissa inputs the difference is f32 accumulation-order
  rounding, bounded here at the prediction level) — and bit-exact model
  TEXT vs the XLA onehot formulation of the same contraction end to end.
- "hilo": ~2^-17 relative input rounding (documented bound), counts exact.
- "q8": exact int32 accumulation — integer equality vs a numpy reference.

The ``pallas`` marker selects this suite; tests/test_chip_compile.py hands
the same kernels to the TPU compiler.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import pallas_hist
from lightgbm_tpu.ops.histogram import (_KERNEL_MODE, compact_indices,
                                        histogram_tiles, resolve_method)

pytestmark = pytest.mark.pallas


def _mk(n, f, b, n_leaves=12, seed=0, representable=False, int8=False):
    """Synthetic tile-pass inputs. ``representable=True`` draws stats as
    multiples of 2^-10 with |sum| << 2^14, so every partial sum is exactly
    representable in f32 and ANY accumulation grouping gives the same
    bits — the precondition for the highest-mode bit-exactness claim."""
    rng = np.random.RandomState(seed)
    binsT = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    if int8:
        stats = rng.randint(-127, 128, size=(n, 3)).astype(np.int8)
    elif representable:
        stats = (rng.randint(-1023, 1024, size=(n, 3)) / 1024.0
                 ).astype(np.float32)
        stats[:, 2] = 1.0
    else:
        stats = rng.randn(n, 3).astype(np.float32)
        stats[:, 2] = 1.0
    leaf = rng.randint(0, n_leaves, n).astype(np.int32)
    sel = np.array([0, 2, 5, 7, 9, 11, -1, -1], np.int32)
    return (jnp.asarray(binsT),
            jnp.asarray(np.ascontiguousarray(binsT.T)),
            jnp.asarray(np.ascontiguousarray(stats.T)), jnp.asarray(leaf),
            jnp.asarray(sel))


def _kernel_stats(stats, block):
    """Channel-major [S, N] statistics at the columns the kernels read:
    zero-padded to ``pallas_hist.stat_rows(N, block)``."""
    return pallas_hist.pad_stats(stats, stats.shape[1], block)


# adversarial shapes: N not a multiple of the block, F not a multiple of
# the bin-packing group (63 bins -> g=2), bins at both production
# settings, and a block the kernel walks in several chunks
SHAPES = [
    pytest.param(3001, 5, 63, 512, id="n3001-f5-b63"),
    pytest.param(2049, 4, 255, 1024, id="n2049-f4-b255"),
    pytest.param(4100, 3, 255, 2048, id="n4100-f3-b255-chunked"),
]


@pytest.mark.parametrize("n,f,b,blk", SHAPES)
def test_highest_bit_exact_vs_scatter(n, f, b, blk):
    """Full-pass fused kernel, HIGHEST mode: bit-exact vs the scatter
    reference on exactly-representable stats."""
    binsT, bins, stats, leaf, sel = _mk(n, f, b, representable=True)
    h = pallas_hist.histogram_tiles_pallas_mode(
        binsT, _kernel_stats(stats, blk), leaf, sel, b, block=blk,
        mode="highest", interpret=True)
    ref = histogram_tiles(bins, stats, leaf, sel, b, method="scatter")
    np.testing.assert_array_equal(np.asarray(h), np.asarray(ref))


@pytest.mark.parametrize("n,f,b,blk", SHAPES)
def test_hilo_documented_bound(n, f, b, blk):
    """Full-pass fused kernel, hilo mode: values within the documented
    ~2^-17 input-rounding bound (signed-sum cancellation amplifies the
    relative error on small cells, hence the max-scaled atol); the count
    channel is exact."""
    binsT, bins, stats, leaf, sel = _mk(n, f, b, seed=1)
    h = np.asarray(pallas_hist.histogram_tiles_pallas_mode(
        binsT, _kernel_stats(stats, blk), leaf, sel, b, block=blk,
        mode="hilo", interpret=True))
    ref = np.asarray(histogram_tiles(bins, stats, leaf, sel, b,
                                     method="scatter"))
    np.testing.assert_allclose(h, ref, rtol=1e-3,
                               atol=1e-3 * np.abs(ref).max())
    np.testing.assert_array_equal(h[..., 2], ref[..., 2])


@pytest.mark.parametrize("n,f,b,blk", SHAPES)
def test_q8_exact_integer(n, f, b, blk):
    """Full-pass fused kernel, q8 mode: EXACT int32 accumulation — integer
    equality vs a numpy int64 reference."""
    binsT, bins, stats, leaf, sel = _mk(n, f, b, seed=2, int8=True)
    h = np.asarray(pallas_hist.histogram_tiles_pallas_mode(
        binsT, _kernel_stats(stats, blk), leaf, sel, b, block=blk,
        mode="q8", interpret=True))
    bins_np, stats_np, leaf_np = (np.asarray(bins), np.asarray(stats).T,
                                  np.asarray(leaf))
    ref = np.zeros((8, f, b, 3), np.int64)
    for p_i, lv in enumerate(np.asarray(sel)):
        if lv < 0:
            continue
        rows = np.nonzero(leaf_np == lv)[0]
        for j in range(f):
            np.add.at(ref[p_i, j], bins_np[rows, j],
                      stats_np[rows].astype(np.int64))
    np.testing.assert_array_equal(h.astype(np.int64), ref)


_METHOD = {mode: m for m, mode in _KERNEL_MODE.items()}


def _rung_pass(binsT, bins, stats, leaf, sel, b, mode, idx, block):
    """A compaction-rung pass as the grower issues it: the row-index
    buffer goes to histogram_tiles, which gathers the compacted copies
    and streams them through the (interpreted) kernel."""
    return np.asarray(histogram_tiles(
        bins, stats, leaf, sel, b, method=_METHOD[mode], block=block,
        binsT=binsT, gather_idx=idx, interpret=True))


@pytest.mark.parametrize("rung", [1, 2, 8])
@pytest.mark.parametrize("mode", ["highest", "q8"])
def test_gather_kernel_parity_rungs(rung, mode):
    """The kernel over gathered rows at compaction rungs 1/2/8: bit-exact
    (highest on representable stats; q8 integer) vs scatter over the same
    kept rows. The index buffer is built exactly as the grower's ladder
    builds it (compact_indices: stable order, padded with N)."""
    n, f, b = 2881, 5, 63
    binsT, bins, stats, leaf, sel = _mk(
        n, f, b, seed=3 + rung, representable=(mode == "highest"),
        int8=(mode == "q8"))
    # deeper rungs get fewer pending leaves — exactly the grower's regime
    # (subtraction makes deep tiles small) and it keeps every rung's
    # kept-row count under its buffer so the rung would really be chosen
    keep_leaves = {1: [0, 2, 5], 2: [0, 2], 8: [0]}[rung]
    keep = jnp.asarray(np.isin(np.asarray(leaf), keep_leaves))
    m = -(-(n // rung) // 64) * 64
    assert int(jnp.sum(keep)) <= m, "fixture bug: rung must fit kept rows"
    idx = compact_indices(keep, m)
    h = _rung_pass(binsT, bins, stats, leaf, sel, b, mode, idx, 256)
    zero = jnp.int8(0) if mode == "q8" else jnp.float32(0.0)
    masked = jnp.where(keep[None, :], stats, zero)
    ref_m = ("onehot_q8" if mode == "q8" else "scatter")
    ref = np.asarray(histogram_tiles(bins, masked, leaf, sel, b,
                                     method=ref_m))
    n_kept_slots = len(keep_leaves)
    np.testing.assert_array_equal(h[:n_kept_slots], ref[:n_kept_slots])
    # slots whose leaves were NOT kept accumulate nothing from kept rows
    assert np.all(h[n_kept_slots:6] == 0)


def test_gather_all_padding_is_zero():
    """An index buffer of pure padding (idx == N everywhere) must produce
    an all-zero histogram: padding rows clamp to row N-1 for the gather
    but carry zero stats and a leaf id that matches no lane."""
    n, f, b = 700, 3, 16
    binsT, bins, stats, leaf, sel = _mk(n, f, b, seed=9)
    idx = jnp.full((128,), n, jnp.int32)
    h = _rung_pass(binsT, bins, stats, leaf, sel, b, "hilo", idx, 128)
    assert np.all(h == 0)


def test_hilo_gather_matches_full():
    """Gather over an all-rows index buffer == the full pass, bit-for-bit
    (same block size -> same accumulation grouping)."""
    n, f, b = 1024, 4, 32
    binsT, bins, stats, leaf, sel = _mk(n, f, b, seed=5)
    idx = jnp.arange(n, dtype=jnp.int32)
    h_g = _rung_pass(binsT, bins, stats, leaf, sel, b, "hilo", idx, 256)
    h_f = np.asarray(pallas_hist.histogram_tiles_pallas_mode(
        binsT, _kernel_stats(stats, 256), leaf, sel, b, block=256,
        mode="hilo", interpret=True))
    np.testing.assert_array_equal(h_g, h_f)


# ------------------------------------------------------- traffic accounting

def _walk_jaxpr_shapes(jaxpr, skip_primitives=("pallas_call",)):
    """All intermediate (shape, dtype) pairs produced OUTSIDE the skipped
    primitives, recursing through scan/cond/while bodies."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in skip_primitives:
            continue
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.append((tuple(aval.shape), np.dtype(aval.dtype).name))
        for pv in eqn.params.values():
            inner = getattr(pv, "jaxpr", None)
            if inner is not None:
                out.append(_walk_jaxpr_shapes(inner, skip_primitives))
            if isinstance(pv, (list, tuple)):
                for item in pv:
                    inner = getattr(item, "jaxpr", None)
                    if inner is not None:
                        out.append(_walk_jaxpr_shapes(inner,
                                                      skip_primitives))
    flat = []
    for item in out:
        flat.extend(item if isinstance(item, list) else [item])
    return flat


def test_no_rhs_in_jaxpr_and_rungs_feed_compacted_copy():
    """The fusion claim, asserted on the traced program: the Pallas path
    never materializes the [N, 128] leaf-channel RHS outside the kernel.
    A compaction rung does materialize the compacted [M, F] bin-matrix
    copy (XLA gathers it; Mosaic has no sub-tile DMA for an in-kernel row
    gather) — on the Pallas path exactly as on the XLA one."""
    n, f, b, m = 2048, 5, 63, 512
    binsT, bins, stats, leaf, sel = _mk(n, f, b)
    idx = compact_indices(leaf < 3, m)

    def shapes_of(method, **kw):
        def fn(bins, stats, leaf, sel, binsT, idx):
            return histogram_tiles(bins, stats, leaf, sel, b, method=method,
                                   binsT=binsT, gather_idx=idx, block=256,
                                   **kw)
        return _walk_jaxpr_shapes(
            jax.make_jaxpr(fn)(bins, stats, leaf, sel, binsT, idx).jaxpr)

    def has_copy(shapes):
        return any(len(shp) == 2 and dt in ("int8", "uint8")
                   and shp in ((f, m), (m, f)) for shp, dt in shapes)

    shapes = shapes_of("pallas_hilo", interpret=True)
    for shp, dt in shapes:
        assert not (len(shp) == 2 and shp[1] in (128, 256)
                    and shp[0] >= m and dt in ("float32", "bfloat16")), (
            f"leaf-channel RHS materialized outside the kernel: {shp} {dt}")
    assert has_copy(shapes)
    assert has_copy(shapes_of("onehot"))


def test_traffic_model_5x_at_higgs_shape():
    """Acceptance: modeled post-fusion HBM bytes/pass <= bin matrix +
    stats + leaf ids + output, and >= 5x below the XLA onehot path at the
    Higgs0.5M shape (500k x 28 x 255 bins x 42-leaf tile)."""
    n, f, b, p, s = 500_000, 28, 255, 42, 3
    for mode in ("hilo", "highest", "q8"):
        t = pallas_hist.traffic_model(n, f, b, p, s, mode)
        stat_b = 1 if mode == "q8" else 4
        budget = n * f + n * s * stat_b + n * 4 + t["output"]
        assert t["fused"] <= budget, (mode, t)
        assert t["xla_onehot"] / t["fused"] >= 5, (mode, t)
        # and the pre-fusion kernel (XLA-side [N,128] RHS) is also beaten
        assert t["prefusion"] / t["fused"] >= 5, (mode, t)


# ------------------------------------------------------------- end to end

def _tree_text(booster):
    """Model text with the embedded parameter dump stripped (it names the
    histogram method, which legitimately differs between parity runs)."""
    return "\n".join(l for l in booster.model_to_string().splitlines()
                     if not l.startswith("[") and l != "end of parameters")


@pytest.fixture(scope="module")
def e2e_models():
    """One small well-separated training per backend under comparison —
    shared across the e2e parity tests so the interpreter cost is paid
    once. Compaction stays ON (default ladder) so the Pallas run drives
    the kernel over gathered rows inside grow_tree's rung dispatch."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(4)
    n = 1500
    X = rng.normal(size=(n, 5))
    # well-SEPARATED split gains (distinct per-feature step sizes, little
    # noise) so structure comparisons test the backends, not coin flips
    # between near-tied noise splits
    y = (2.0 * (X[:, 0] > 0.3) + 1.0 * (X[:, 1] > -0.2)
         + 0.5 * (X[:, 2] > 0.5) + 0.01 * rng.normal(size=n))
    out = {}
    for name, params in [
        ("scatter", {"histogram_method": "scatter"}),
        ("onehot", {"histogram_method": "onehot"}),
        ("pallas", {"histogram_method": "pallas",
                    "hist_pallas_interpret": True}),
        ("pallas_nocompact", {"histogram_method": "pallas",
                              "hist_pallas_interpret": True,
                              "hist_compaction": False}),
    ]:
        ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
        booster = lgb.train({"objective": "regression", "num_leaves": 8,
                             "verbosity": -1, **params},
                            ds, num_boost_round=4)
        out[name] = (_tree_text(booster), booster.predict(X))
    return out


def test_e2e_text_parity_vs_onehot(e2e_models):
    """hist_method=pallas (HIGHEST) model text is BIT-IDENTICAL to the XLA
    onehot formulation end to end — the kernel is a drop-in replacement
    for its reference formulation, compaction rungs included."""
    assert e2e_models["pallas"][0] == e2e_models["onehot"][0]


def test_e2e_gather_path_is_inert(e2e_models):
    """Compaction ON (the kernel over gathered rows inside the ladder) vs
    OFF (full-pass kernel only): identical split structure, predictions within f32
    accumulation-order rounding. (Not bit-text: the full pass interleaves
    the non-tile rows as zero contributions, which lands the kept rows in
    different SIMD reduction lanes than the compacted pass — the same
    pass-shape tolerance test_compaction documents for the XLA ladder.)"""
    def structure(text):
        return [l for l in text.splitlines()
                if l.startswith(("split_feature", "threshold"))]
    assert structure(e2e_models["pallas"][0]) == \
        structure(e2e_models["pallas_nocompact"][0])
    np.testing.assert_allclose(e2e_models["pallas"][1],
                               e2e_models["pallas_nocompact"][1],
                               rtol=1e-6, atol=1e-6)


def test_e2e_structure_parity_vs_scatter(e2e_models):
    """vs the scatter reference: identical split structure (features +
    thresholds), predictions within f32 accumulation-order rounding (the
    matmul formulations regroup partial sums; same bound test_compaction
    documents for the onehot backend)."""
    def structure(text):
        return [l for l in text.splitlines()
                if l.startswith(("split_feature", "threshold",
                                 "decision_type", "left_child",
                                 "right_child", "num_leaves"))]
    assert structure(e2e_models["pallas"][0]) == \
        structure(e2e_models["scatter"][0])
    np.testing.assert_allclose(e2e_models["pallas"][1],
                               e2e_models["scatter"][1],
                               rtol=1e-6, atol=1e-6)


def test_quantized_grad_resolution():
    """Config.quantized_grad maps every method family onto its q8 twin:
    the Pallas kernel wherever kernels run (TPU, or interpret for tests),
    the XLA int8 contraction elsewhere — never silently non-quantized."""
    on_cpu = jax.default_backend() != "tpu"
    want_plain = "onehot_q8" if on_cpu else "pallas_q8"
    assert resolve_method("auto", quantized=True) == want_plain
    assert resolve_method("auto", quantized=True,
                          interpret=True) == "pallas_q8"
    assert resolve_method("pallas_hilo", quantized=True,
                          interpret=True) == "pallas_q8"
    assert resolve_method("scatter", quantized=True) == "onehot_q8"
    assert resolve_method("onehot_hilo", quantized=True) == "onehot_q8"
    # and without the flag, auto off-TPU keeps the scatter fast path
    # unless interpret asks for the kernel pipeline
    if on_cpu:
        assert resolve_method("auto") == "scatter"
        assert resolve_method("auto", interpret=True) == "pallas_hilo"
        assert resolve_method("auto", deterministic=True,
                              interpret=True) == "pallas"


@pytest.mark.slow
def test_quantized_grad_end_to_end():
    """quantized_grad=true trains end to end (int8 stochastic-rounding
    grad/hess, exact int32 histograms, f32 rescale at split time) with
    accuracy close to full precision.

    Slow: a pure quality claim (two 15-round trainings for an accuracy
    bar). The q8 MECHANICS stay tier-1: end-to-end q8 training via
    test_split_fusion.py::test_e2e_fusion_bit_parity_xla[q8] (both
    fusion legs train q8), the in-kernel dequant via the q8 epilogue
    unit parity there, and the kernel smoke
    (scripts/kernel_bench.py --fast --interpret, every CI pass) runs
    the q8 mode. The refusal contract is tier-1 below
    (test_quantized_grad_refuses_f64_hist)."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(7)
    n = 3000
    X = rng.normal(size=(n, 6))
    y = (X[:, 0] + 0.6 * X[:, 1] + 0.2 * rng.normal(size=n) > 0).astype(
        np.float64)

    def acc(params):
        ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
        booster = lgb.train({"objective": "binary", "num_leaves": 31,
                             "verbosity": -1, **params},
                            ds, num_boost_round=15)
        return float(np.mean((booster.predict(X) > 0.5) == (y > 0.5)))

    a_full = acc({})
    a_q8 = acc({"quantized_grad": True})
    assert a_q8 >= a_full - 0.01, (a_full, a_q8)


def test_quantized_grad_refuses_f64_hist():
    """The contradictory int8-grad + f64-histogram combination is
    refused at train start (extracted from the slow end-to-end quality
    test so the contract stays tier-1)."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(7)
    X = rng.normal(size=(80, 4))
    y = (X[:, 0] > 0).astype(np.float64)
    with pytest.raises(ValueError, match="quantized_grad and gpu_use_dp"):
        ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
        lgb.train({"objective": "binary", "quantized_grad": True,
                   "gpu_use_dp": True, "verbosity": -1}, ds,
                  num_boost_round=1)


@pytest.mark.parametrize("block,tile", [(0, 0), (8192, 0), (0, 16),
                                        (512, 21)])
def test_hist_plan_serial_and_parallel_agree(block, tile):
    """The histogram plan is ONE rule for every learner: the serial and
    the data-parallel grow statics hold the same resolved row block and
    tile width — an explicit ``hist_block`` / ``tile_leaves`` where the
    configuration names one, else DEFAULT_BLOCK and the structural tile."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(9)
    X = rng.normal(size=(400, 5))
    y = X[:, 0] + 0.1 * rng.normal(size=400)
    explicit = {k: v for k, v in (("hist_block", block),
                                  ("tile_leaves", tile)) if v}
    plans = {}
    for learner in ("serial", "data"):
        params = {"objective": "regression", "verbosity": -1,
                  "tree_learner": learner, "hist_pallas_interpret": True,
                  **explicit}
        ds = lgb.Dataset(X, label=y, params=params)
        gb = lgb.Booster(params=params, train_set=ds)._boosting
        hm = gb._hist_method()
        assert hm == "pallas_hilo"
        st = (gb._serial_grow_statics(hm) if learner == "serial"
              else gb._parallel_grow_statics(hm))
        plans[learner] = (st["hist_block"], st["tile_leaves"])
    assert plans["serial"] == plans["data"] == (
        block or pallas_hist.DEFAULT_BLOCK,
        tile or pallas_hist.structural_tile_leaves())
