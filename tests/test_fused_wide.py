"""Fused-iteration coverage for the widened one-dispatch fast path (PR 3).

Bit-parity regressions for every config newly admitted to the fused
gradients -> growth -> score-update program (models/gbdt.py _fused_ok):
multiclass (K > 1), the data/feature/voting parallel learners on the
virtual 8-device mesh, the bagging subset copy, CEGB, and forced splits —
each fused run's model text must equal the unfused phase-by-phase run's
bit for bit (``fused_iteration=false`` is the reference side; the dumped
param line itself is the one intended difference).

Plus the telemetry this PR adds: dispatches/host-bytes per iteration
(utils/profiling.py install_dispatch_hook) and the data/voting learners'
collective receive volume (GrowAux.coll_bytes).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils import profiling

from test_grower import _make_meta, _make_params


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(42)
    # deliberately NOT divisible by the 8-device mesh (exercises padding)
    X = rng.normal(size=(900, 8)).astype(np.float64)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    y3 = np.digitize(X[:, 0] + 0.3 * X[:, 2], [-0.5, 0.5]).astype(np.float64)
    return X, y, y3


def _strip(model_text: str) -> str:
    """Drop the one INTENDED difference between the two runs' dumps."""
    return "\n".join(l for l in model_text.splitlines()
                     if not l.startswith("[fused_iteration"))


def _fit(X, y, extra, nround):
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 10,
         "verbosity": -1}
    p.update(extra)
    return lgb.train(p, lgb.Dataset(X, label=y, params=p), nround)


def _assert_parity(X, y, extra, nround=3):
    fused = _fit(X, y, extra, nround)
    plain = _fit(X, y, {**extra, "fused_iteration": False}, nround)
    assert fused._boosting._fused_cache, "fused path did not engage"
    assert not plain._boosting._fused_cache, "unfused run engaged fused"
    assert _strip(fused.model_to_string()) == _strip(plain.model_to_string())
    np.testing.assert_array_equal(fused.predict(X[:64]), plain.predict(X[:64]))
    return fused, plain


# --------------------------------------------------- newly admitted configs
def test_fused_parity_multiclass(data):
    """K > 1: all class trees grow inside ONE program (lax.scan over the
    class axis) — bit-identical to the per-class unfused loop."""
    X, _, y3 = data
    fused, _ = _assert_parity(
        X, y3, {"objective": "multiclass", "num_class": 3})
    assert len(fused._boosting.trees) == 3 * 3   # nround x num_class


@pytest.mark.slow
def test_fused_parity_multiclassova(data):
    X, _, y3 = data
    _assert_parity(X, y3, {"objective": "multiclassova", "num_class": 3})


def test_fused_parity_bagging_subset(data):
    """The bagging subset copy (gbdt.cpp:810-818) drawn in-program from
    the period-start key, vs the host-side _update_bagging draw."""
    X, y, _ = data
    fused, plain = _assert_parity(
        X, y, {"bagging_fraction": 0.4, "bagging_freq": 2})
    assert plain._boosting._bag_sub is not None   # subset path active
    assert fused._boosting._bag_sub is None       # never left the device


@pytest.mark.slow
def test_fused_parity_bagging_mask_posneg(data):
    X, y, _ = data
    _assert_parity(X, y, {"pos_bagging_fraction": 0.7,
                          "neg_bagging_fraction": 0.9, "bagging_freq": 2})


def test_fused_parity_cegb(data):
    """CEGB's cross-iteration used-feature aux as device-resident fused
    loop state (operand in, operand out)."""
    X, y, _ = data
    _assert_parity(X, y, {"cegb_tradeoff": 0.9, "cegb_penalty_split": 0.01,
                          "cegb_penalty_feature_coupled": [0.1] * 8})


@pytest.mark.slow
def test_fused_parity_forced_splits(data, tmp_path):
    X, y, _ = data
    fn = tmp_path / "forced.json"
    fn.write_text(json.dumps({"feature": 0, "threshold": 0.0}))
    _assert_parity(X, y, {"forcedsplits_filename": str(fn)})


@pytest.mark.parametrize("mode,extra", [
    ("data", {}),
    ("feature", {}),
    # the voting cell rides the slow tier: the fused embedding it shares
    # with data/feature stays tier-1 above, and voting-specific behavior
    # is pinned tier-1 by the mesh-8 voting collective-volume regression
    # below (plus the full voting matrix in test_distributed.py, slow)
    pytest.param("voting", {"top_k": 3}, marks=pytest.mark.slow),
])
def test_fused_parity_parallel(data, mode, extra):
    """The parallel learners' fused step embeds the SAME shard_map'd
    grower the unfused path dispatches (ParallelGrower.get_shard_fn) —
    one program per iteration over the virtual mesh."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    X, y, _ = data
    fused, _ = _assert_parity(X, y, {"tree_learner": mode, **extra},
                              nround=3)
    coll = fused._boosting.coll_bytes_total
    if mode == "feature":
        assert coll == 0.0    # only the O(L)-scalar best-split sync
    else:
        assert coll > 0.0     # data/voting move histogram planes


@pytest.mark.slow
def test_fused_parity_data_multiclass(data):
    """Multiclass x data-parallel: the scan over classes wraps the
    shard_map'd grower."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    X, _, y3 = data
    _assert_parity(X, y3, {"tree_learner": "data", "objective": "multiclass",
                           "num_class": 3}, nround=3)


@pytest.mark.slow
def test_fused_resume_unfused_midperiod_bagging(data):
    """Switching fused -> unfused mid-bagging-period re-derives the same
    mask (the period-start key draw): train 2 fused iters, flip the gate,
    continue unfused — identical to the all-unfused run."""
    X, y, _ = data
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 10,
         "bagging_fraction": 0.8, "bagging_freq": 4, "verbosity": -1}
    b = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    for _ in range(2):
        b.update()
    assert b._boosting._fused_cache
    b._boosting.config.fused_iteration = False    # mid-period flip
    for _ in range(2):
        b.update()
    plain = _fit(X, y, {"bagging_fraction": 0.8, "bagging_freq": 4,
                        "fused_iteration": False}, 4)
    assert _strip(b.model_to_string()) == _strip(plain.model_to_string())


@pytest.mark.slow
def test_fused_bynode_reset_parameter_parity(data):
    """A reset_parameter change to feature_fraction_bynode mid-training
    must retrace the fused step (the fraction is a closed-over constant,
    keyed in the fused cache) — review finding: without the key the
    cached program silently kept the old fraction."""
    from lightgbm_tpu import callback
    X, y, _ = data
    sched = [0.9, 0.9, 0.3, 0.3, 0.3]

    def fit(fused):
        p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 10,
             "feature_fraction_bynode": 0.9, "verbosity": -1,
             "fused_iteration": fused}
        cbs = [callback.reset_parameter(feature_fraction_bynode=sched)]
        return lgb.train(p, lgb.Dataset(X, label=y, params=p), len(sched),
                         callbacks=cbs)

    b1, b0 = fit(True), fit(False)
    assert b1._boosting._fused_cache
    assert _strip(b1.model_to_string()) == _strip(b0.model_to_string())


# ----------------------------------------------------- dispatch telemetry
@pytest.fixture
def dispatch_hook():
    """Install the counting hooks for one test, then restore the jax
    fastpath so the rest of the suite doesn't pay the Python round trip."""
    if not profiling.install_dispatch_hook():
        pytest.skip("jax internals hook unavailable on this version")
    yield
    profiling.uninstall_dispatch_hook()


def test_dispatch_telemetry_fused_vs_unfused(data, dispatch_hook):
    """The acceptance numbers: a fused iteration is <= 2 compiled-program
    dispatches (the grow step + the donated score add); the unfused path
    pays 3+ (gradients, growth, finalize/score eager ops). Guards the
    one-dispatch property against regression."""
    X, y, _ = data

    def measure(extra, n_meas=3):
        p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 10,
             "verbosity": -1}
        p.update(extra)
        b = lgb.Booster(params=p,
                        train_set=lgb.Dataset(X, label=y, params=p))
        for _ in range(2):                       # warmup (compile)
            b.update()
        _ = float(np.asarray(b._boosting.train_score).ravel()[0])
        before = profiling.dispatch_stats()
        for _ in range(n_meas):
            b.update()
        # snapshot BEFORE any sync fetch: dispatches count at call time
        delta = profiling.dispatch_delta(before)
        return delta["dispatches"] / n_meas

    assert measure({}) <= 2.0
    assert measure({"fused_iteration": False}) >= 3.0


def test_dispatch_telemetry_counts_transfers(dispatch_hook):
    before = profiling.dispatch_stats()
    arr = jnp.asarray(np.ones((1000,), np.float32))   # host -> device
    _ = jax.device_get(arr)                           # device -> host
    d = profiling.dispatch_delta(before)
    assert d["h2d_bytes"] >= 4000
    assert d["d2h_bytes"] >= 4000
    assert d["device_gets"] >= 1


# ------------------------------------------------- collective volume
def _grow_parallel(mode, d, n, f=8, B=16, top_k=2):
    """One L=2 tree via ParallelGrower on a d-device mesh: exactly one
    histogram tile pass (root) + one split phase, so the expected
    collective volume is a closed formula."""
    from lightgbm_tpu.parallel.data_parallel import make_mesh
    from lightgbm_tpu.parallel.learners import ParallelGrower
    rng = np.random.RandomState(3)
    bins = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    hess = np.ones(n, dtype=np.float32)
    meta, missing_bin = _make_meta([B] * f)
    params = _make_params(min_data=5)
    pg = ParallelGrower(mode, mesh=make_mesh(d), axis="data")
    _tree, _leaf, aux = pg(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.ones((n,), jnp.float32), meta, params,
        jnp.ones((f,), jnp.float32), jnp.asarray(missing_bin),
        max_leaves=2, num_bins=B, hist_method="scatter",
        vote_top_k=top_k)
    return float(aux.coll_bytes)


def _data_volume_expected(d, L=2, f=8, B=16, S=3, itemsize=4):
    """Histogram size / devices — the ReduceScatter design volume."""
    return L * f * B * S * itemsize / d


def _voting_volume_expected(top_k, L=2, f=8, B=16, S=3, itemsize=4):
    """Vote-tally allreduce + elected 2k-column histogram sum."""
    return L * f * 4 + L * min(2 * top_k, f) * B * S * itemsize


@pytest.mark.parametrize("d", [1, 2, 4])
@pytest.mark.slow
def test_collective_volume_data_learner_small_meshes(d):
    """Row-count independence (the n=1024 re-run) + the /d formula at the
    remaining mesh sizes — the slow half of the mesh-1/2/4/8 sweep."""
    if len(jax.devices()) < d:
        pytest.skip(f"needs {d} virtual devices")
    assert _grow_parallel("data", d, n=256) == _data_volume_expected(d)
    assert _grow_parallel("data", d, n=1024) == _data_volume_expected(d)


def test_collective_volume_data_learner(data):
    """Data learner: per-iteration psum_scatter receive volume ==
    histogram size / devices, independent of row count (the reference
    ReduceScatter's bytes, data_parallel_tree_learner.cpp:184-186) —
    the scaling-efficiency evidence. Mesh sizes
    1/2/4 and the row-independence re-runs live in the slow tier (same
    formula, one shard-program compile each)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    assert _grow_parallel("data", 8, n=256) == _data_volume_expected(8)


@pytest.mark.parametrize("d", [1, 2, 4, 8])
@pytest.mark.slow
def test_collective_volume_voting_rows_independent(d):
    if len(jax.devices()) < d:
        pytest.skip(f"needs {d} virtual devices")
    assert _grow_parallel("voting", d, n=1024, top_k=2) == \
        _voting_volume_expected(2)


def test_collective_volume_voting_learner(data):
    """Voting learner: the vote-tally allreduce plus the elected 2k
    columns' histogram sum (GlobalVoting/CopyLocalHistogram,
    voting_parallel_tree_learner.cpp:151-184) — independent of BOTH rows
    and mesh size, the whole point of PV-tree. Mesh sizes 1/2/4 and the
    row-independence re-runs live in the slow tier."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    assert _grow_parallel("voting", 8, n=256, top_k=2) == \
        _voting_volume_expected(2)


def test_collective_volume_zero_for_serial(data):
    """Serial growth moves no histogram bytes between devices (the
    feature learner's zero is asserted where its program is already
    compiled — see test_fused_parity_parallel)."""
    X, y, _ = data
    b = _fit(X, y, {}, 2)
    assert b._boosting.coll_bytes_total == 0.0
