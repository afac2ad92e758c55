"""Compile-wall coverage: K-iterations-per-dispatch scan blocks +
persistent AOT compile cache (ISSUE 10).

The contracts pinned here:

- K-block training (``boost_rounds_per_dispatch`` K >= 4) is BIT-IDENTICAL
  (model text) to K separate fused iterations — for plain gbdt,
  multiclass, mask bagging, subset bagging and GOSS (whose sampling now
  runs in-program, newly admitting it to the fused path at K=1 too);
- a warm K-block costs <= 2 compiled-program dispatches (measured via the
  PR 3 dispatch hook; the block itself is ONE — score carried in-program);
- the traced fused program embeds (almost) NO constants: the dataset
  arrays (objective label/derived tables, feature meta, bins) are
  OPERANDS, so XLA has nothing dataset-sized to constant-fold at compile
  time (>6 s slow-constant-folding alarms at 10.5M rows);
- a checkpoint period that is not a multiple of K is rejected with a
  clear error (a K-block is one atomic dispatch — no mid-block state
  exists to capture), and block-boundary checkpoints resume
  bit-identically;
- a SECOND process with a warm persistent compilation cache
  (``compile_cache_dir``) resumes from a checkpoint with ZERO fused-step
  XLA compiles (cache hits only) — the supervisor/gang-relaunch warm
  path, asserted on the per-module compile counters.
"""

import json
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import callback as callback_mod
from lightgbm_tpu.utils import profiling
from lightgbm_tpu.utils.log import LightGBMError


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(7)
    X = rng.normal(size=(1500, 8)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] + rng.normal(size=1500) * 0.3 > 0)
    y3 = np.digitize(X[:, 0] + 0.3 * X[:, 2], [-0.5, 0.5])
    return X, y.astype(np.float32), y3.astype(np.float32)


def _strip(model_text: str) -> str:
    """Drop the intended param-dump differences between the two runs."""
    drop = ("[boost_rounds_per_dispatch", "[fused_iteration",
            "[compile_cache_dir")
    return "\n".join(l for l in model_text.splitlines()
                     if not l.startswith(drop))


def _fit(X, y, extra, nround=8):
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 10,
         "verbosity": -1}
    p.update(extra)
    return lgb.train(p, lgb.Dataset(X, label=y, params=p), nround)


def _assert_block_parity(X, y, extra, nround=8, K=4):
    blocked = _fit(X, y, {**extra, "boost_rounds_per_dispatch": K}, nround)
    single = _fit(X, y, extra, nround)
    assert _strip(blocked.model_to_string()) == \
        _strip(single.model_to_string())
    return blocked, single


# ------------------------------------------------------- K-scan parity
def test_kscan_parity_gbdt(data):
    X, y, _ = data
    blocked, _ = _assert_block_parity(X, y, {})
    assert blocked._boosting._fused_cache, "block path did not engage"


# slow: class-scan spelling of the same block machinery tier-1's
# test_kscan_parity_gbdt pins (multiclass parity also rides
# test_fused_wide's tier-1 fused coverage)
@pytest.mark.slow
def test_kscan_parity_multiclass(data):
    X, _, y3 = data
    _assert_block_parity(X, y3, {"objective": "multiclass",
                                 "num_class": 3}, nround=6, K=4)


def test_kscan_parity_bagging_mask(data):
    X, y, _ = data
    _assert_block_parity(X, y, {"bagging_freq": 2,
                                "bagging_fraction": 0.7})


# slow: the subset draw is the same in-program fold_in stream the
# tier-1 mask spelling exercises; full parity still runs in the slow
# tier and the manual combo sweep
@pytest.mark.slow
def test_kscan_parity_bagging_subset(data):
    X, y, _ = data
    _assert_block_parity(X, y, {"bagging_freq": 2,
                                "bagging_fraction": 0.4})


def test_kscan_parity_goss(data):
    X, y, _ = data
    # learning_rate 0.3 -> the 1/lr warm-up gate flips INSIDE the run
    # (iteration 3), exercising both cond arms of the in-program sampler
    blocked, single = _assert_block_parity(
        X, y, {"boosting": "goss", "learning_rate": 0.3})
    assert blocked._boosting._fused_cache, "GOSS block did not fuse"


# slow: tier-1's test_kscan_parity_goss already proves the fused
# in-program sampler bit-matches (block == K singles == its model);
# this is the explicit fused-vs-unfused spelling
@pytest.mark.slow
def test_goss_now_fused_and_matches_unfused(data):
    """GOSS's in-program sampling newly admits it to the fused path —
    and the fused run must stay bit-identical to the phase-by-phase
    reference (the same contract every other fused config carries)."""
    X, y, _ = data
    fused = _fit(X, y, {"boosting": "goss", "learning_rate": 0.3})
    plain = _fit(X, y, {"boosting": "goss", "learning_rate": 0.3,
                        "fused_iteration": False})
    assert fused._boosting._fused_cache, "GOSS did not take the fused path"
    assert not plain._boosting._fused_cache
    assert _strip(fused.model_to_string()) == _strip(plain.model_to_string())


# slow: the K-mask pre-draw is exercised by the tier-1 gbdt parity
# via _feature_mask_np order (and the multiclass slow sibling)
@pytest.mark.slow
def test_kscan_parity_feature_fraction(data):
    """Column sampling draws from a stateful host rng: the block must
    pre-draw K masks in the exact per-iteration order."""
    X, y, _ = data
    _assert_block_parity(X, y, {"feature_fraction": 0.6})


# slow: remainder truncation is pinned cheaply by
# test_manual_update_keeps_single_iteration_semantics + the resume
# parity sibling; the full 7-round parity rides the slow tier
@pytest.mark.slow
def test_kscan_remainder_rounds(data):
    """num_boost_round not a multiple of K: the last block truncates
    (never over-trains) and stays bit-identical."""
    X, y, _ = data
    blocked, single = _assert_block_parity(X, y, {}, nround=7, K=4)
    assert len(blocked._boosting.trees) == 7
    assert len(single._boosting.trees) == 7


def test_manual_update_keeps_single_iteration_semantics(data):
    """Only engine.train may drive block consumption: a manual
    Booster.update loop must advance exactly one iteration per call even
    with boost_rounds_per_dispatch set (cv()'s round counting depends on
    it)."""
    X, y, _ = data
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "boost_rounds_per_dispatch": 4}
    b = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    b.update()
    assert b._boosting.iter == 1


# ------------------------------------------------- dispatch-count budget
def test_block_dispatch_budget(data):
    """A warm K-block is <= 2 dispatches (it is ONE: the score add rides
    the scan carry; the per-iteration mode's budget was 2)."""
    X, y, _ = data
    if not profiling.install_dispatch_hook():
        pytest.skip("dispatch hook unavailable on this jax")
    try:
        p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
             "boost_rounds_per_dispatch": 4}
        b = lgb.Booster(params=p,
                        train_set=lgb.Dataset(X, label=y, params=p))
        bo = b._boosting
        bo._block_target = 12
        b.update()                      # block 0-3 (compiles)
        with profiling.dispatch_scope() as d:
            b.update()                  # block 4-7, warm
        assert bo.iter == 8
        assert d["dispatches"] <= 2, d
    finally:
        profiling.uninstall_dispatch_hook()


# ------------------------------------------- constant-folding hoist
def test_fused_program_has_no_dataset_constants(data):
    """The traced fused block must close over (almost) nothing: every
    dataset-sized array — objective label/weight/derived tables, feature
    meta, bundle/forced/CEGB tables — enters as an operand. Closure
    constants become HLO constants whose label-derived subexpressions
    XLA constant-folds at COMPILE time (>6 s per instruction at 10.5M
    rows); this pins the hoist."""
    import jax
    X, y, _ = data
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
    b = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    bo = b._boosting
    step, bind = bo._fused_step_fn(bo._hist_method(), False, k_rounds=4)
    jaxpr = jax.make_jaxpr(step.__wrapped__)(
        *bo._fused_call_args(None, bind))
    const_bytes = sum(np.asarray(c).nbytes for c in jaxpr.consts)
    # a handful of scalars (PRNG keys fold in as pairs) is fine; a single
    # retained [N] array would be 6000 bytes at this shape
    assert const_bytes < 1024, (
        f"{const_bytes} bytes of closure constants in the fused program: "
        f"{[np.asarray(c).shape for c in jaxpr.consts]}")
    # and the objective's device tables really are operands
    assert "label_sign" in bind["obj_consts"]


# ------------------------------------------------- checkpoint alignment
def test_checkpoint_period_not_multiple_of_k_rejected(data, tmp_path):
    X, y, _ = data
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "boost_rounds_per_dispatch": 4}
    cb = callback_mod.checkpoint(str(tmp_path), period=3)
    with pytest.raises(LightGBMError, match="multiple of"):
        lgb.train(p, lgb.Dataset(X, label=y, params=p), 8, callbacks=[cb])


def test_misaligned_period_ok_when_schedule_disables_blocks(data, tmp_path):
    """A reset_parameter schedule disables blocking, making the run
    per-iteration — a checkpoint period that is not a multiple of K must
    then be ACCEPTED (review fix: the rejection used to fire before the
    schedule fallback was decided)."""
    X, y, _ = data
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "boost_rounds_per_dispatch": 4}
    cb = callback_mod.checkpoint(str(tmp_path / "ck"), period=3)
    b = lgb.train(p, lgb.Dataset(X, label=y, params=p), 6,
                  callbacks=[cb], learning_rates=[0.1] * 6)
    assert b._boosting.iter == 6
    assert (tmp_path / "ck").exists()


# slow: boundary resume parity is CI-proven every run by
# scripts/compile_wall_smoke.py (run_suite.sh): resume + zero-
# recompile + bit-identical continuation in two real processes
@pytest.mark.slow
def test_checkpoint_block_boundary_resume_parity(data, tmp_path):
    """Kill-at-boundary + resume under K-blocks reproduces the
    uninterrupted blocked run bit-identically (checkpoints exist only at
    block boundaries, so the resumed run re-enters on a fresh aligned
    block)."""
    X, y, _ = data
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 10,
         "verbosity": -1, "boost_rounds_per_dispatch": 4}
    full = _fit(X, y, p, nround=8)
    ck = str(tmp_path / "ck")
    lgb.train(p, lgb.Dataset(X, label=y, params=p), 4,
              callbacks=[callback_mod.checkpoint(ck, period=4)])
    resumed = lgb.train(p, lgb.Dataset(X, label=y, params=p), 8,
                        callbacks=[callback_mod.checkpoint(ck, period=4)],
                        resume_from=ck)
    assert resumed._boosting.iter == 8
    assert _strip(resumed.model_to_string()) == _strip(full.model_to_string())


# slow: the fallback flag is a one-line engine gate; the parity
# spelling rides the slow tier
@pytest.mark.slow
def test_reset_parameter_schedule_disables_blocks(data):
    """A per-iteration learning_rate schedule cannot ride a block
    dispatch: engine.train falls back to K=1 and the result matches the
    unblocked schedule run exactly."""
    X, y, _ = data
    rates = [0.1 + 0.01 * i for i in range(6)]
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
    a = lgb.train({**p, "boost_rounds_per_dispatch": 4},
                  lgb.Dataset(X, label=y, params=p), 6,
                  learning_rates=rates)
    b = lgb.train(p, lgb.Dataset(X, label=y, params=p), 6,
                  learning_rates=rates)
    assert _strip(a.model_to_string()) == _strip(b.model_to_string())


def test_block_sentinel_names_mid_block_iteration(data):
    """The in-program NaN injection at an iteration INSIDE a block is
    caught by the [K] sentinel flag vector and named exactly."""
    X, y, _ = data
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "boost_rounds_per_dispatch": 4, "check_numerics": True,
         "fault_nan_hist_at_iter": 5}
    with pytest.raises(LightGBMError, match="iteration 5"):
        lgb.train(p, lgb.Dataset(X, label=y, params=p), 8)


# ------------------------------------------------- persistent cache
_CHILD = r"""
import json, os, sys, time
import numpy as np
sys.path.insert(0, {repo!r})
import lightgbm_tpu as lgb
from lightgbm_tpu import callback as callback_mod
from lightgbm_tpu import compile_cache

cfg = json.loads(sys.argv[1])
rng = np.random.RandomState(7)
X = rng.normal(size=(1500, 8)).astype(np.float32)
y = (X[:, 0] + 0.5 * X[:, 1] + rng.normal(size=1500) * 0.3 > 0)
y = y.astype(np.float32)
p = {{"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 10,
     "verbosity": -1, "boost_rounds_per_dispatch": 4,
     "compile_cache_dir": cfg["cache_dir"]}}

if cfg.get("aot"):
    # in-process AOT drill (the reset_cache regression): compile ONCE
    # with NO cache configured (jax pins its cache object at the first
    # compile), then configure the cache, AOT-warm, and train one block.
    # warm_start must WRITE the fused block's entry — which only works
    # if configure() reset jax's pinned (dir-less) cache — and the block
    # that follows must ask for no compile at all (jax 0.9 keeps the AOT
    # compile's lowering and executable for the call). Then the jit
    # caches are dropped, as in a fresh process, and a second booster's
    # block must be served from that entry.
    import jax
    p0 = dict(p); p0.pop("compile_cache_dir")
    lgb.train(p0, lgb.Dataset(X, label=y, params=p0), 4)
    compile_cache.configure(cache_dir=cfg["cache_dir"])
    fused = lambda kind: compile_cache.module_count(kind, "jit(_fused")
    b = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    bo = b._boosting
    assert bo.warm_start(k_rounds=4)
    out = {{"aot_misses": fused("misses")}}
    before = {{k: fused(k) for k in ("misses", "requests")}}
    bo._block_target = 4
    b.update()
    assert bo.iter == 4
    out["warm_miss_delta"] = fused("misses") - before["misses"]
    out["warm_request_delta"] = fused("requests") - before["requests"]
    jax.clear_caches()
    b2 = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    b2._boosting._block_target = 4
    b2.update()
    out["cold_jit_miss_delta"] = fused("misses") - before["misses"]
    out["fused_hits"] = fused("hits")
else:
    cb = callback_mod.checkpoint(cfg["ckpt_dir"], period=4)
    t0 = time.time()
    b = lgb.train(p, lgb.Dataset(X, label=y, params=p), cfg["rounds"],
                  callbacks=[cb],
                  resume_from=cfg["ckpt_dir"] if cfg["resume"] else None)
    out = {{
        "wall_s": time.time() - t0,
        "iter": b._boosting.iter,
        "model": b.model_to_string(),
        "fused_misses": compile_cache.module_count("misses", "jit(_fused"),
        "fused_hits": compile_cache.module_count("hits", "jit(_fused"),
        "total_misses": compile_cache.totals()["misses"],
    }}
with open(cfg["out"], "w") as fh:
    json.dump(out, fh)
"""


def _run_child(cfg):
    import os
    code = _CHILD.format(repo=str(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    # the children place their cache with compile_cache_dir; a directory
    # named from outside would win over it (compile_cache.configure)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run([sys.executable, "-c", code, json.dumps(cfg)],
                       capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    with open(cfg["out"]) as fh:
        return json.load(fh)


# slow: the two-process acceptance drill runs on every CI pass via
# scripts/compile_wall_smoke.py (run_suite.sh); tier-1 keeps the
# one-process AOT child (test_warm_start_aot)
@pytest.mark.slow
def test_warm_process_zero_fused_recompiles(data, tmp_path):
    """The acceptance contract (and the supervisor/gang-relaunch warm
    path): a SECOND process resuming the same-shape training from a
    checkpoint with a warm persistent cache performs ZERO fused-step XLA
    compiles — the restore-time AOT warmup and the first block both hit
    the disk cache — and continues bit-identically to the uninterrupted
    blocked run."""
    X, y, _ = data
    cache = str(tmp_path / "cache")
    ckpt = str(tmp_path / "ckpt")
    cold = _run_child({"cache_dir": cache, "ckpt_dir": ckpt, "rounds": 4,
                       "resume": False, "out": str(tmp_path / "c.json")})
    assert cold["iter"] == 4
    assert cold["fused_misses"] >= 1          # the cold compile, cached
    warm = _run_child({"cache_dir": cache, "ckpt_dir": ckpt, "rounds": 8,
                       "resume": True, "out": str(tmp_path / "w.json")})
    assert warm["iter"] == 8
    assert warm["fused_misses"] == 0, (
        f"warm incarnation recompiled the fused step: {warm}")
    assert warm["fused_hits"] >= 1
    # and the continuation is the uninterrupted run, bit for bit
    full = _fit(X, y, {"boost_rounds_per_dispatch": 4}, nround=8)
    assert _strip(warm["model"]) == _strip(full.model_to_string())


def test_warm_start_aot(tmp_path):
    """warm_start() AOT-compiles the exact program the training loop
    dispatches and writes it to the persistent cache: the first block
    after it asks for NO fused-step compile (jax 0.9 keeps the AOT
    compile's executable for the call), and once the in-memory jit
    caches are gone the same program is a disk hit, not a recompile.
    Runs in a SUBPROCESS because configuring the persistent cache is
    process-global (pointing the whole pytest process at a test-scoped
    dir would tax every later compile) — and the child first compiles
    WITHOUT the cache, pinning jax's dir-less cache object, which
    regression-tests configure()'s reset_cache."""
    out = _run_child({"cache_dir": str(tmp_path / "cache"), "aot": True,
                      "out": str(tmp_path / "aot.json")})
    assert out["aot_misses"] >= 1, out
    assert out["warm_miss_delta"] == 0, out
    assert out["warm_request_delta"] == 0, out
    assert out["cold_jit_miss_delta"] == 0, out
    assert out["fused_hits"] >= 1, out


_PLACEMENT_CHILD = r"""
import json, sys
import numpy as np
sys.path.insert(0, sys.argv[1])
import lightgbm_tpu as lgb
from lightgbm_tpu import compile_cache
# what the entry points (chip_smoke.py, benchmarks/run.py, bench_serve.py) do
d = compile_cache.configure(cache_dir=compile_cache.default_dir())
rng = np.random.RandomState(0)
X = rng.normal(size=(400, 4)).astype(np.float32)
y = (X[:, 0] > 0).astype(np.float32)
p = {"objective": "binary", "num_leaves": 4, "verbosity": -1}
lgb.train(p, lgb.Dataset(X, label=y, params=p), 2)
print(json.dumps({"dir": d, **compile_cache.totals()}))
"""


@pytest.mark.parametrize("placed_from_outside", [True, False])
def test_compile_cache_placement(tmp_path, placed_from_outside):
    """One resolver: with JAX_COMPILATION_CACHE_DIR set, an entry point's
    cache entries go there and nowhere else; unset, to the fixed
    ``.jax_cache`` of the checkout. Either way a second run of the same
    command is served from them. The 'checkout' is a copy of the package
    under tmp_path, so the real tree's cache stays out of it."""
    import os
    import shutil
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    checkout = tmp_path / "checkout"
    shutil.copytree(os.path.join(repo, "lightgbm_tpu"),
                    checkout / "lightgbm_tpu",
                    ignore=shutil.ignore_patterns("__pycache__", "_build"))
    outside, inside = tmp_path / "outside", checkout / ".jax_cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if placed_from_outside:
        env["JAX_COMPILATION_CACHE_DIR"] = str(outside)
    want, other = (outside, inside) if placed_from_outside \
        else (inside, outside)

    def run():
        r = subprocess.run(
            [sys.executable, "-c", _PLACEMENT_CHILD, str(checkout)],
            capture_output=True, text=True, env=env, timeout=300)
        assert r.returncode == 0, r.stderr[-3000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    cold = run()
    assert cold["dir"] == str(want)
    assert cold["misses"] > 0 and cold["hits"] == 0, cold
    assert len(os.listdir(want)) >= cold["misses"]
    assert not other.exists()
    warm = run()
    assert warm["hits"] > 0 and warm["compiles"] == 0, warm
    assert not other.exists()


@pytest.mark.slow
def test_engine_warm_aot(data):
    """PredictEngine.warm_aot compiles the serve bucket's accumulation
    program ahead of traffic (keyed like the bucket cache)."""
    X, y, _ = data
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
    b = _fit(X, y, p, nround=3)
    bo = b._boosting
    eng = bo._predict_engine()
    ts = bo.train_set
    assert eng.warm_aot(4096, ts.num_used_features(), np.int32,
                        ts.missing_bin)
    # the serve variant (donated carry — the program _serve_chunk
    # dispatches; a different HLO module from the plain one)
    assert eng.warm_aot(4096, ts.num_used_features(), np.int32,
                        ts.missing_bin, serve=True)
