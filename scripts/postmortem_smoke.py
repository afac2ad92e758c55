#!/usr/bin/env python
"""Post-mortem pipeline smoke: supervised kill -> the analyzer names the
killed rank (fast knobs, ~40 s). A CPU tool: the gang is local
processes, and a chip belongs to one process at a time.

Drill: a 2-process localhost gang training with per-iteration
checkpoints has rank 1 hard-killed at iteration 2 (os._exit 137 via the
fault harness) with NO restart budget — the supervisor must:

  1. raise ``GangFailedError`` carrying a ``postmortem`` report path it
     generated automatically (the supervisor runs the analyzer on gang
     failure);
  2. the machine report must classify the failure ``kill`` and name
     rank 1 (the exit-137 evidence + rank 1's fault-kill flight flush);
  3. rerunning the analysis offline through ``scripts/postmortem.py``
     over the diag directory must reach the SAME verdict/rank (the
     operator workflow: kill a gang -> run the script -> read the
     verdict) and exit 0 under ``--expect kill``.

Usage:  JAX_PLATFORMS=cpu python scripts/postmortem_smoke.py
Exits 0 on success, 1 with a diagnosis otherwise. Wired into
tests/run_suite.sh; the classification logic itself is covered per
fault class in tests/test_postmortem.py.
"""
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

PARAMS = {"objective": "binary", "num_leaves": 8, "min_data_in_leaf": 5,
          "boost_from_average": False, "histogram_method": "scatter",
          "verbosity": -1, "heartbeat_interval": 0.4,
          "collective_deadline": 10.0}
ROUNDS = 4


def train_fn(rank, ckdir):
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(7)
    X = rng.normal(size=(320, 6))
    y = (X[:, 0] - 0.5 * X[:, 2] > 0).astype(np.float64)
    ds = lgb.Dataset(X, label=y, params=dict(PARAMS), free_raw_data=False)
    booster = lgb.train(dict(PARAMS), ds, ROUNDS,
                        callbacks=[lgb.checkpoint_callback(ckdir, period=1)],
                        resume_from=ckdir)
    return booster.model_to_string()


def main() -> int:
    from lightgbm_tpu import supervisor
    t0 = time.time()
    with tempfile.TemporaryDirectory() as td:
        ck = os.path.join(td, "ck")
        os.environ["LGBM_TPU_FAULT_KILL_RANK_AT_ITER"] = "1:2"
        err = None
        try:
            supervisor.run_supervised(
                train_fn, nproc=2, args=(ck,), devices_per_proc=1,
                checkpoint_dir=ck, max_restarts=0, timeout=180)
        except supervisor.GangFailedError as e:
            err = e
        finally:
            os.environ.pop("LGBM_TPU_FAULT_KILL_RANK_AT_ITER", None)
        if err is None:
            print("FAIL: gang with max_restarts=0 and a killed rank "
                  "did not raise GangFailedError")
            return 1
        if not err.postmortem or not os.path.exists(err.postmortem):
            print(f"FAIL: GangFailedError carries no post-mortem report "
                  f"path (got {err.postmortem!r})")
            return 1
        with open(err.postmortem) as fh:
            report = json.load(fh)
        if report.get("verdict") != "kill" or report.get("rank") != 1:
            print(f"FAIL: expected verdict 'kill' naming rank 1, got "
                  f"{report.get('verdict')!r} rank {report.get('rank')!r}")
            return 1
        if str(err.postmortem) not in str(err):
            print("FAIL: GangFailedError message does not reference the "
                  "report path")
            return 1
        # operator workflow: rerun the analysis offline over the diag dir
        diag_dir = os.path.dirname(err.postmortem)
        r = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "postmortem.py"),
             diag_dir, "--checkpoint-dir", ck, "--expect", "kill"],
            capture_output=True, text=True, timeout=120)
        if r.returncode != 0:
            print(f"FAIL: scripts/postmortem.py exited {r.returncode}:\n"
                  f"{r.stdout[-1500:]}\n{r.stderr[-1500:]}")
            return 1
        if "rank 1" not in r.stdout:
            print(f"FAIL: offline report does not name rank 1:\n"
                  f"{r.stdout[-1500:]}")
            return 1
    print(f"OK: killed rank 1 classified 'kill' by the supervisor's "
          f"auto post-mortem AND by the offline scripts/postmortem.py "
          f"rerun ({time.time() - t0:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
