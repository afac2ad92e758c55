"""Histogram precision (gpu_use_dp analog) + profiling subsystem."""

import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _auc(pred, y):
    order = np.argsort(pred)
    ranks = np.empty_like(order, dtype=np.float64)
    ranks[order] = np.arange(1, len(pred) + 1)
    pos = y > 0
    n_pos, n_neg = pos.sum(), (~pos).sum()
    return (ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)


def test_f32_hist_auc_parity(binary_example):
    """The float32 histogram path must track the float64 path's AUC closely
    (the reference's documented f32-GPU vs f64-CPU parity,
    docs/GPU-Performance.rst:133-140: identical to 6 digits at 255 bins).
    The f64 run executes in a subprocess with JAX_ENABLE_X64 so the global
    x64 switch cannot leak into this test session."""
    Xtr, ytr, Xte, yte = binary_example
    ds = lgb.Dataset(Xtr, label=ytr, params={"verbosity": -1})
    b32 = lgb.train({"objective": "binary", "num_leaves": 31,
                     "verbosity": -1}, ds, num_boost_round=60)
    auc32 = _auc(b32.predict(Xte, raw_score=True), yte)
    assert auc32 > 0.80, auc32

    code = f"""
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import sys
sys.path.insert(0, {REPO!r})
import lightgbm_tpu as lgb
tr = np.loadtxt("/root/reference/examples/binary_classification/binary.train")
te = np.loadtxt("/root/reference/examples/binary_classification/binary.test")
ds = lgb.Dataset(tr[:, 1:], label=tr[:, 0], params={{"verbosity": -1}})
b = lgb.train({{"objective": "binary", "num_leaves": 31, "verbosity": -1,
               "gpu_use_dp": True}}, ds, num_boost_round=60)
np.save("/tmp/_dp_pred.npy", b.predict(te[:, 1:], raw_score=True))
"""
    env = dict(os.environ, JAX_ENABLE_X64="1", JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env, timeout=900,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]
    auc64 = _auc(np.load("/tmp/_dp_pred.npy"), yte)
    # near-tie splits flip between precisions so trees legitimately diverge
    # (the reference's 6-digit f32/f64 parity is measured on 500k-row test
    # sets; on this 500-row set one flipped split moves AUC ~5e-3)
    assert auc64 > 0.80, auc64
    assert abs(auc32 - auc64) < 1e-2, (auc32, auc64)


def test_gpu_use_dp_without_x64_warns_and_trains(binary_example):
    Xtr, ytr, _, _ = binary_example
    ds = lgb.Dataset(Xtr, label=ytr, params={"verbosity": -1})
    booster = lgb.train({"objective": "binary", "num_leaves": 8,
                         "gpu_use_dp": True, "verbosity": -1},
                        ds, num_boost_round=2)
    assert booster._boosting.host_trees[0].num_leaves > 1


def test_profiling_timer_table(binary_example):
    from lightgbm_tpu.utils import profiling
    Xtr, ytr, _, _ = binary_example
    profiling.reset()
    profiling.enable(True)
    try:
        ds = lgb.Dataset(Xtr[:1000], label=ytr[:1000],
                         params={"verbosity": -1})
        lgb.train({"objective": "binary", "num_leaves": 8, "verbosity": -1},
                  ds, num_boost_round=3)
        tab = profiling.table()
    finally:
        profiling.enable(False)
        profiling.reset()
    # the fused fast path folds the gradients phase INTO grow_tree (one
    # jitted program per iteration, gbdt._fused_step_fn), so the table
    # shows grow/finalize/score scopes; "gradients" only appears on the
    # phase-by-phase path
    assert "grow_tree" in tab
    assert "score_update" in tab and "finalize_tree" in tab
