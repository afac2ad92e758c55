#!/usr/bin/env python
"""Supervisor gang-restart + elastic-shrink + integrity smoke: fast
knobs, ~90 s. A CPU tool: the gangs are local processes, and a chip
belongs to one process at a time.

Three stanzas:
  1. restart — a 2-process localhost gang training with per-iteration
     checkpoints has rank 1 hard-killed at iteration 3 (os._exit 137 via
     the fault harness); the supervisor must relaunch the gang exactly
     once and the final model text must be BIT-IDENTICAL to an
     uninterrupted gang's.
  2. elastic — rank 1's spawn fails outright (exit 96 via
     LGBM_TPU_FAULT_SPAWN_FAIL_RANK); the supervisor must classify the
     rank permanently lost, SHRINK the gang to world size 1, complete
     training there, and record the shrink in the SupervisorReport.
  3. integrity — one score-cache bit is flipped on rank 1 of a 3-rank
     gang (LGBM_TPU_FAULT_FLIP_SCORE_RANK); the cross-rank divergence
     check must name exactly that rank (exit 95 + a divergence
     diagnosis), the supervisor must restore the gang from the last
     valid checkpoint, and the final model text must be BIT-IDENTICAL
     to the fault-free run's.

Usage:  JAX_PLATFORMS=cpu python scripts/supervisor_smoke.py
Exits 0 on success, 1 with a diagnosis otherwise. The same paths run in
tier-1 as tests/test_supervisor.py::test_gang_kill_rank_mid_iter_bit_identical,
::test_gang_shrink_on_spawn_fail and
tests/test_integrity.py::test_supervised_corrupt_rank_restart_bit_identical.
"""
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PARAMS = {"objective": "binary", "num_leaves": 8, "min_data_in_leaf": 5,
          "boost_from_average": False, "histogram_method": "scatter",
          "verbosity": -1, "heartbeat_interval": 0.4,
          "collective_deadline": 10.0}
# the integrity stanza turns the cross-rank divergence check on (every
# iteration — fast knobs; production cadence is coarser)
INTEG_PARAMS = dict(PARAMS, integrity_check_period=1,
                    collective_deadline=12.0)
ROUNDS = 4


def _make_fn(params):
    def train_fn(rank, ckdir):
        import lightgbm_tpu as lgb
        rng = np.random.RandomState(7)
        X = rng.normal(size=(320, 6))
        y = (X[:, 0] - 0.5 * X[:, 2] > 0).astype(np.float64)
        ds = lgb.Dataset(X, label=y, params=dict(params),
                         free_raw_data=False)
        booster = lgb.train(dict(params), ds, ROUNDS,
                            callbacks=[lgb.checkpoint_callback(ckdir,
                                                               period=1)],
                            resume_from=ckdir)
        return booster.model_to_string()
    return train_fn


def train_fn(rank, ckdir):
    return _make_fn(PARAMS)(rank, ckdir)


def integ_train_fn(rank, ckdir):
    return _make_fn(INTEG_PARAMS)(rank, ckdir)


def main() -> int:
    from lightgbm_tpu import supervisor
    t0 = time.time()
    with tempfile.TemporaryDirectory() as td:
        clean = supervisor.run_supervised(
            train_fn, nproc=2, args=(os.path.join(td, "clean"),),
            devices_per_proc=1, timeout=180)
        if clean.restarts != 0:
            print(f"FAIL: clean gang restarted {clean.restarts}x")
            return 1
        ck = os.path.join(td, "ck")
        os.environ["LGBM_TPU_FAULT_KILL_RANK_AT_ITER"] = "1:3"
        try:
            report = supervisor.run_supervised(
                train_fn, nproc=2, args=(ck,), devices_per_proc=1,
                checkpoint_dir=ck, max_restarts=2, timeout=180)
        finally:
            os.environ.pop("LGBM_TPU_FAULT_KILL_RANK_AT_ITER", None)
        if report.restarts != 1:
            print(f"FAIL: expected exactly 1 restart, got {report.restarts}")
            return 1
        if report.result != clean.result:
            print("FAIL: restarted gang's model text differs from the "
                  "uninterrupted run's")
            return 1
        # ---- elastic stanza: rank 1 permanently lost -> gang shrinks
        cke = os.path.join(td, "ck_elastic")
        os.environ["LGBM_TPU_FAULT_SPAWN_FAIL_RANK"] = "1"
        try:
            elastic = supervisor.run_supervised(
                train_fn, nproc=2, args=(cke,), devices_per_proc=1,
                checkpoint_dir=cke, max_restarts=2, timeout=180)
        finally:
            os.environ.pop("LGBM_TPU_FAULT_SPAWN_FAIL_RANK", None)
        if elastic.world_size != 1 or len(elastic.shrinks) != 1 \
                or elastic.shrinks[0].lost_ranks != [1]:
            print(f"FAIL: expected one 2->1 shrink of lost rank 1, got "
                  f"world_size={elastic.world_size} "
                  f"shrinks={elastic.shrinks}")
            return 1
        if elastic.result != clean.result:
            print("FAIL: shrunken gang's model text differs from the "
                  "uninterrupted run's")
            return 1
        # ---- integrity stanza: bit-flip -> divergence detect ->
        # corrupt-rank restart -> complete, bit-identical. The fault-free
        # reference is a single-process run: the gang trains the serial
        # learner on replicated data, so every rank's model equals it.
        import lightgbm_tpu as lgb
        from lightgbm_tpu import distributed
        rng = np.random.RandomState(7)
        X = rng.normal(size=(320, 6))
        y = (X[:, 0] - 0.5 * X[:, 2] > 0).astype(np.float64)
        ds = lgb.Dataset(X, label=y, params=dict(INTEG_PARAMS),
                         free_raw_data=False)
        ref = lgb.train(dict(INTEG_PARAMS), ds, ROUNDS).model_to_string()
        cki = os.path.join(td, "ck_integrity")
        os.environ["LGBM_TPU_FAULT_FLIP_SCORE_RANK"] = "1:2"
        try:
            integ = supervisor.run_supervised(
                integ_train_fn, nproc=3, args=(cki,), devices_per_proc=1,
                checkpoint_dir=cki, max_restarts=2, timeout=240)
        finally:
            os.environ.pop("LGBM_TPU_FAULT_FLIP_SCORE_RANK", None)
        if integ.restarts != 1:
            print(f"FAIL: integrity gang expected exactly 1 restart, got "
                  f"{integ.restarts}")
            return 1
        if integ.failures[0].exit_codes.get(1) \
                != distributed.DIVERGENCE_EXIT_CODE:
            print(f"FAIL: expected rank 1 to exit with the divergence "
                  f"code, got {integ.failures[0].exit_codes}")
            return 1
        divs = [d for f in integ.failures for d in f.watchdog
                if d.get("kind") == "divergence"]
        if not divs or divs[0].get("corrupt_ranks") != [1]:
            print(f"FAIL: divergence diagnosis should name exactly rank "
                  f"1, got {divs}")
            return 1
        if integ.result != ref:
            print("FAIL: restored gang's model text differs from the "
                  "fault-free run's")
            return 1
    print(f"OK: gang killed at iter 3, restarted once, model text "
          f"bit-identical; spawn-failed rank 1 shrank the gang 2->1 and "
          f"training completed; bit-flipped rank 1 of a 3-rank gang named "
          f"by the divergence vote, restored from checkpoint, model text "
          f"bit-identical ({time.time() - t0:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
