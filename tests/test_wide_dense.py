"""A dense matrix wider than one kernel body: the histogram kernel blocked
over features, the job that measures it (benchmarks/jobs/wide_train.py) and
the bin finder that no longer takes a Python step a distinct value.

The kernels run interpreted (the TPU compiler's side of the same kernels is
tests/test_chip_compile.py). What the blocked kernel must give is what the
one-block kernel gives, bit for bit: a feature's sums do not depend on
which block holds the feature.
"""

import importlib.util
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import binning
from lightgbm_tpu.config import Config
from lightgbm_tpu.ops import pallas_hist

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")

pytestmark = pytest.mark.pallas


# ------------------------------------------------------- (i) the kernels
def _operands(rng, mode, f, n, b):
    binsT = jnp.asarray(rng.integers(0, b, size=(f, n)), jnp.uint8)
    if mode == "q8":
        stats = jnp.asarray(rng.integers(-100, 100, size=(n, 3)), jnp.int8)
    else:
        stats = jnp.asarray(rng.standard_normal((n, 3)), jnp.float32)
    leaf = jnp.asarray(rng.integers(0, 6, size=(n,)), jnp.int32)
    return binsT, stats, leaf


def _both(mode, epilogue, b, f=21, n=1500, fblock=8):
    """(one-block outputs, blocked outputs) of one pass: 21 features in
    blocks of 8 are three blocks, the last padded."""
    rng = np.random.default_rng(7)
    binsT, stats, leaf = _operands(rng, mode, f, n, b)
    sel = jnp.asarray([0, 2, 3, -1, 5], jnp.int32)
    kw = dict(block=512, mode=mode, interpret=True)
    if not epilogue:
        run = lambda fb: (pallas_hist.histogram_tiles_pallas_mode(   # noqa: E731
            binsT, stats, leaf, sel, b, fblock=fb, **kw),)
    else:
        p = sel.shape[0]
        derived = jnp.asarray([1, -1, 4, -1, -1], jnp.int32)
        parent = jnp.asarray(rng.integers(0, 50, size=(p, f, b, 3)),
                             jnp.float32)
        la = jnp.abs(jnp.asarray(rng.standard_normal((2, p, 8)),
                                 jnp.float32)) * 100
        fm = pallas_hist.pack_feature_meta(
            jnp.full((f,), b), jnp.zeros((f,)), jnp.zeros((f,)),
            jnp.zeros((f,)))
        pv = jnp.asarray([0, 0, 0, 0, 1, 1e-3, 0], jnp.float32)
        qs = jnp.asarray([0.01, 0.02, 1.0], jnp.float32)
        run = lambda fb: pallas_hist.histogram_tiles_pallas_epilogue(  # noqa: E731
            binsT, stats, leaf, sel, derived, parent, la, fm, pv, b,
            q_scale=qs, fblock=fb, **kw)
    return run(f), run(fblock)


@pytest.mark.parametrize("epilogue", [False, True], ids=["plain", "epilogue"])
@pytest.mark.parametrize("mode", ["hilo", "highest", "q8"])
def test_blocked_kernel_equals_the_one_block_kernel(mode, epilogue):
    """A width that is no multiple of the block: the last block's padding
    columns are cut off again, planes and candidates equal bit for bit."""
    one, blocked = _both(mode, epilogue, 255)
    for a, c in zip(one, blocked):
        assert a.shape == c.shape
        assert np.array_equal(np.asarray(a), np.asarray(c), equal_nan=True)


@pytest.mark.parametrize("epilogue", [False, True], ids=["plain", "epilogue"])
def test_blocked_kernel_with_feature_packing(epilogue):
    """63 bins: two features share an MXU tile inside a block (8 features
    a block pack in pairs; 21 leave a block of 5 with an odd one)."""
    one, blocked = _both("hilo", epilogue, 63)
    for a, c in zip(one, blocked):
        assert np.array_equal(np.asarray(a), np.asarray(c), equal_nan=True)


def test_feature_block_rule():
    """One block up to ONE_BLOCK_FEATURES (so at every width a listed cell
    ran before the kernel had blocks); above it blocks of a multiple of 8,
    two at least, as few as MAX_FEATURE_BLOCK allows and as even as they
    come; 2,000 columns are ten blocks of 200 with no padding."""
    one, cap = pallas_hist.ONE_BLOCK_FEATURES, pallas_hist.MAX_FEATURE_BLOCK
    for f in (1, 8, 28, 68, 137, one):
        assert pallas_hist.feature_block(f, 255) == f
        assert pallas_hist.feature_blocks(
            f, pallas_hist.feature_block(f, 255)) == 1
    for f in (one + 1, 200, 274, 400, 700, 968, 2000, 4228):
        fb = pallas_hist.feature_block(f, 255, "hilo", epilogue=True)
        blocks = pallas_hist.feature_blocks(f, fb)
        assert fb % 8 == 0 and fb <= cap
        assert blocks == max(2, math.ceil(f / cap))
        assert (blocks - 1) * fb < f <= blocks * fb
        for mode in ("hilo", "highest", "q8"):
            assert pallas_hist.feature_block(f, 63, mode) == fb
    assert pallas_hist.feature_block(2000, 255) == 200


# ------------------------------------------- (ii), (iii) a model in blocks
F_WIDE, N_WIDE = 500, 3000
PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
          "min_data_in_leaf": 5, "verbosity": -1,
          "hist_pallas_interpret": True}


def _wide_data():
    rng = np.random.default_rng(3)
    X = rng.standard_normal((N_WIDE, F_WIDE)).astype(np.float32)
    w = np.zeros(F_WIDE)
    w[[0, 167, 168, 335, 336, 499]] = [1.0, -1.0, 0.8, 0.9, -0.7, 1.1]
    y = (X @ w + rng.logistic(size=N_WIDE) > 0).astype(np.float32)
    return X, y


def _train(params, rounds=3):
    X, y = _wide_data()
    ds = lgb.Dataset(X, label=y, params=params)
    booster = lgb.train(params, ds, rounds, keep_training_booster=True)
    return booster.hist_plan(), booster.model_to_string()


@pytest.mark.parametrize("learner", ["serial", "data"])
def test_model_in_blocks_is_the_one_block_model(monkeypatch, learner):
    """500 columns are three feature blocks under the rule; with the cap
    lifted they are one. The model text is the same, under the serial
    learner (the fused epilogue kernel) and under ``tree_learner=data`` on
    the test environment's virtual devices (the plain kernel under
    ``shard_map``)."""
    params = {**PARAMS, "tree_learner": learner}
    plan, text = _train(params)
    assert (plan["feature_block"], plan["feature_blocks"]) == (168, 3)
    assert plan["split_fusion"] == (learner == "serial")
    jax.clear_caches()
    monkeypatch.setattr(pallas_hist, "ONE_BLOCK_FEATURES", 10 ** 6)
    try:
        plan1, text1 = _train(params)
    finally:
        jax.clear_caches()
    assert (plan1["feature_block"], plan1["feature_blocks"]) == (F_WIDE, 1)
    assert text == text1


def _bench_module(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, *parts))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    """benchmarks/ on the path as run.py puts it, and run.py itself."""
    sys.path.insert(0, BENCH)
    try:
        yield _bench_module("bench_run_for_wide", "run.py")
    finally:
        sys.path.remove(BENCH)


def test_the_rehearsal_cell_is_correct(bench):
    """The cell's own job over the rehearsal files, as ``run.py
    --rehearse`` drives it: tree 0's root over all 500 columns, its leaf
    counts exactly, its leaf values, and the probe tree that has to split
    three designated columns of each of the three feature blocks at the
    planted bound, each held to benchmarks/reference_wide.py."""
    cell = bench.load_json("workloads", "tiny-epsilon.train.json")
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
    assert st["plan"]["feature_blocks"] == 3, st["plan"]
    assert ctx.counters["hist_feature_blocks"] == 3.0 * ctx.units
    assert "splits 9 of them at the planted bound" in said, said
    assert "sum |model - raw traversal| = 0" in said


def test_the_probe_reads_a_lost_feature_block(bench):
    """The probe's reader against a tree that skips a block: a designated
    column whose planted split is missing is named."""
    ref = bench.load_module(".", "reference_wide")
    cols = ref.designated_columns(400, 136)
    assert cols.tolist() == [0, 67, 135, 136, 203, 271, 272, 335, 399]
    assert ref.designated_columns(2000, 160)[[0, -1]].tolist() == [0, 1999]
    bounds = [np.linspace(-1, 1, 62) for _ in range(400)]
    top = [62] * len(cols)
    tree = {"split_feature": np.array([0, 67, 135, 136, 203, 271, 5]),
            "threshold": np.array([1.0, 1.0, 1.0, 1.0, 1.0, 0.5, 1.0])}
    found = ref.planted_found(tree, cols, top, bounds)
    assert found == [True] * 5 + [False] * 4


def test_the_job_refuses_a_library_without_the_surface(bench, monkeypatch):
    job = bench.load_module("jobs", "wide_train")
    job._surface()
    monkeypatch.delattr(pallas_hist, "feature_block")
    with pytest.raises(RuntimeError, match="not blocked over features"):
        job._surface()


def test_the_generator_draws_the_order_only(bench):
    """``--seed`` permutes the training rows of one fixed data set; rows
    are unit length, the label balanced, the held-out rows in place."""
    gen = bench.load_module("data", "epsilon")
    spec = {"features": 40, "sample_seed": 1}
    Xa, ya = gen.make(spec, 5, 3000, 2000)
    Xb, yb = gen.make(spec, 6, 3000, 2000)
    assert Xa.dtype == np.float32 and Xa.shape == (3000, 40)
    assert np.allclose(np.sqrt((Xa.astype(np.float64) ** 2).sum(axis=1)),
                       1.0, atol=1e-5)
    assert np.array_equal(Xa[2000:], Xb[2000:])
    assert not np.array_equal(Xa[:2000], Xb[:2000])
    key = lambda X, y: sorted(map(tuple, np.c_[X[:2000], y[:2000]]))  # noqa: E731
    assert key(Xa, ya) == key(Xb, yb)
    assert 0.4 < ya.mean() < 0.6


def test_the_cell_entries_fit_the_form(bench):
    """Every line of text in BENCHMARK.json is 1 to 200 printable
    characters (a ``why`` of 208 refused this cell's first check), and the
    cell is listed by the two metrics and the ``wide_*`` entries it reports."""
    doc = bench.load_json("..", "BENCHMARK.json")
    texts = [(e["name"], k, e[k])
             for part in ("configs", "workloads", "end_to_end", "per_layer")
             for e in doc[part] for k in ("why", "source", "layer") if k in e]
    bad = [(n, k, len(t)) for n, k, t in texts
           if not 1 <= len(t) <= 200 or not t.isprintable()]
    assert bad == []
    cell = next(w for w in doc["workloads"] if w["name"] == "epsilon.train")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "epsilon-400k", "wide_train", 1)
    lists = {m["name"]: m.get("workloads", [])
             for m in doc["end_to_end"] + doc["per_layer"]}
    assert "epsilon.train" in lists["train_s_per_iter"]
    assert "epsilon.train" in lists["valid_auc"]
    wide = sorted(n for n in lists if n.startswith("wide_"))
    assert wide == sorted(
        f[:-5] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
        if f.startswith("wide_"))
    assert all(lists[n] == ["epsilon.train"] for n in wide)


# ------------------------------------------------- (iv) the bin finder
def _greedy_find_bin_loop(distinct_values, counts, max_bin, total_cnt,
                          min_data_in_bin):
    """``binning.greedy_find_bin`` as it was before this file existed: one
    Python step a distinct value (reference: bin.cpp:78-155)."""
    up, eq = binning._get_double_upper_bound, \
        binning._check_double_equal_ordered
    num_distinct = len(distinct_values)
    out = []
    if num_distinct <= max_bin:
        cur = 0
        for i in range(num_distinct - 1):
            cur += counts[i]
            if cur >= min_data_in_bin:
                val = up((distinct_values[i] + distinct_values[i + 1]) / 2.0)
                if not out or not eq(out[-1], val):
                    out.append(val)
                    cur = 0
        out.append(math.inf)
        return out
    if min_data_in_bin > 0:
        max_bin = max(min(max_bin, total_cnt // min_data_in_bin), 1)
    mean_bin_size = total_cnt / max_bin
    rest_bin_cnt, rest_sample_cnt = max_bin, int(total_cnt)
    big = counts >= mean_bin_size
    rest_bin_cnt -= int(big.sum())
    rest_sample_cnt -= int(counts[big].sum())
    mean_bin_size = rest_sample_cnt / max(rest_bin_cnt, 1)
    upper, lower = [math.inf] * max_bin, [math.inf] * max_bin
    bin_cnt = 0
    lower[0] = float(distinct_values[0])
    cur = 0
    for i in range(num_distinct - 1):
        if not big[i]:
            rest_sample_cnt -= counts[i]
        cur += counts[i]
        if (big[i] or cur >= mean_bin_size
                or (big[i + 1] and cur >= max(1.0, mean_bin_size * 0.5))):
            upper[bin_cnt] = float(distinct_values[i])
            bin_cnt += 1
            lower[bin_cnt] = float(distinct_values[i + 1])
            if bin_cnt >= max_bin - 1:
                break
            cur = 0
            if not big[i]:
                rest_bin_cnt -= 1
                mean_bin_size = rest_sample_cnt / max(rest_bin_cnt, 1)
    bin_cnt += 1
    for i in range(bin_cnt - 1):
        val = up((upper[i] + lower[i + 1]) / 2.0)
        if not out or not eq(out[-1], val):
            out.append(val)
    out.append(math.inf)
    return out


def _columns():
    rng = np.random.default_rng(0)
    n = 6000
    yield "continuous", rng.standard_normal(n)
    yield "float32", rng.standard_normal(n).astype(np.float32)
    yield "few-values", rng.integers(0, 7, n).astype(float)
    yield "integers", rng.integers(0, 300, n).astype(float)
    yield "dominant", np.where(rng.random(n) < 0.8, 3.0,
                               rng.standard_normal(n))
    yield "two-dominant", np.where(
        rng.random(n) < 0.4, 3.0,
        np.where(rng.random(n) < 0.5, -1.0, rng.standard_normal(n)))
    yield "zeros", np.where(rng.random(n) < 0.6, 0.0,
                            rng.standard_normal(n))
    yield "nan", np.where(rng.random(n) < 0.1, np.nan,
                          rng.standard_normal(n))
    yield "heavy-tail", np.floor(np.exp(rng.normal(2, 2, n)))
    yield "negative-heavy", -np.floor(np.exp(rng.normal(1, 1.5, n)))
    for k in range(6):
        m = int(rng.integers(300, 1500))
        c = np.maximum(1, (rng.pareto(1.0, m)
                           * rng.integers(1, 50)).astype(int))
        yield f"pareto-{k}", np.repeat(np.sort(rng.standard_normal(m)), c)


COLUMNS = dict(_columns())


@pytest.mark.parametrize("kind", sorted(COLUMNS))
def test_find_bin_equals_the_loop_bound_for_bound(monkeypatch, kind):
    """The mapper (bounds, bin counts' consequences: default and most
    frequent bin, sparse rate, missing type) fitted with the searching
    greedy_find_bin against the one fitted with the old loop, over the
    column kinds, ``max_bin`` 255 / 63 / small and ``min_data_in_bin``."""
    x = COLUMNS[kind]
    for max_bin in (255, 63, 15, 2):
        for mdb in (3, 1, 50, 1000):
            for zero_as_missing in (False, True):
                kw = dict(total_sample_cnt=len(x) + (1500 if kind == "zeros"
                                                     else 0),
                          max_bin=max_bin, min_data_in_bin=mdb,
                          zero_as_missing=zero_as_missing)
                new = binning.BinMapper()
                new.find_bin(x, **kw)
                with monkeypatch.context() as mp:
                    mp.setattr(binning, "greedy_find_bin",
                               _greedy_find_bin_loop)
                    old = binning.BinMapper()
                    old.find_bin(x, **kw)
                a, b = new.to_dict(), old.to_dict()
                assert a.keys() == b.keys()
                for key in a:
                    if isinstance(a[key], (list, np.ndarray)):
                        assert np.array_equal(
                            np.asarray(a[key], dtype=float),
                            np.asarray(b[key], dtype=float),
                            equal_nan=True), (kind, kw, key)
                    else:
                        assert a[key] == b[key], (kind, kw, key)


def test_find_bin_mappers_fits_blocks_of_columns_like_single_columns():
    """The sample's rows of a block of columns are gathered once; each
    column's mapper is the one its own values give."""
    rng = np.random.default_rng(1)
    X = np.column_stack([COLUMNS[k][:6000] for k in
                         ("continuous", "few-values", "zeros", "nan",
                          "heavy-tail", "dominant")]
                        + [rng.standard_normal(6000) for _ in range(31)])
    cfg = Config.from_params({"max_bin": 255,
                              "bin_construct_sample_cnt": 4000})
    got = binning.find_bin_mappers(X, cfg)
    idx = binning.sample_indices(len(X), 4000, cfg.data_random_seed)
    cnt = binning.filter_cnt_for_sample(cfg, len(idx), len(X))
    assert len(got) == X.shape[1] == 37
    for j, m in enumerate(got):
        want = binning.fit_mapper_for_column(j, X[idx, j], len(idx), cfg,
                                             set(), cnt)
        assert np.array_equal(m.bin_upper_bound, want.bin_upper_bound,
                              equal_nan=True), j
        assert (m.num_bin, m.default_bin, m.most_freq_bin) == (
            want.num_bin, want.default_bin, want.most_freq_bin), j
