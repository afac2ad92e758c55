#!/usr/bin/env python
"""Telemetry-layer end-to-end smoke (fast knobs, ~20 s). A CPU tool: it
starts a child process, and a chip belongs to one process at a time.

Drill: (1) a short recorder-on training run under a durable telemetry
dir, killed mid-run by the fault harness — the flushed flight-recorder
JSONL must exist, parse, schema-validate, and name the in-flight
iteration; (2) a clean run whose train-end flush validates and whose
health snapshot references the JSONL by path; (3) with ``--trace``
(default on), a ``telemetry.trace_window`` capture around two boosting
iterations — on backends whose profiler cannot start the contract is a
recorded error, never a crash (the jax.profiler no-op tolerance);
(4) the Prometheus exposition renders and every line parses.

Wired into tests/run_suite.sh. Exit 0 = all stages passed.
"""

import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def log(msg):
    print(f"[telemetry_smoke] {msg}", flush=True)


def check(cond, msg):
    if not cond:
        log(f"FAIL: {msg}")
        sys.exit(1)
    log(f"ok: {msg}")


def stage_kill_flush(tmp):
    """Killed training leaves a valid post-mortem JSONL."""
    from lightgbm_tpu import telemetry
    tele_dir = os.path.join(tmp, "tele_kill")
    code = (
        "import numpy as np, lightgbm_tpu as lgb\n"
        "rng = np.random.RandomState(0)\n"
        "X = rng.normal(size=(3000, 8)).astype(np.float32)\n"
        "y = (X[:, 0] > 0).astype(np.float32)\n"
        "ds = lgb.Dataset(X, label=y, params={'verbosity': -1})\n"
        "lgb.train({'objective': 'binary', 'num_leaves': 15,\n"
        "           'verbosity': -1, 'telemetry_dir': %r,\n"
        "           'fault_kill_at_iter': 4}, ds, 12)\n" % tele_dir)
    r = subprocess.run([sys.executable, "-c", code],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    check(r.returncode == 137,
          f"harness kill exits 137 (got {r.returncode})")
    path = os.path.join(tele_dir, "flight_rank0.jsonl")
    check(os.path.exists(path), "kill flushed a flight-recorder JSONL")
    recs, errors = telemetry.validate_flight_jsonl(path)
    check(not errors, f"JSONL schema-validates ({errors[:3]})")
    flush = recs[-1]
    check(flush["type"] == "flush" and "at iteration 4" in flush["reason"],
          f"last record names the in-flight iteration "
          f"({flush.get('reason')!r})")
    iters = [x for x in recs if x["type"] == "iter"]
    check(iters and iters[-1]["iteration"] == 3,
          "per-iteration records cover every completed iteration")


def stage_clean_run(tmp):
    """Clean training: train-end flush + health reference."""
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu import distributed, telemetry
    tele_dir = os.path.join(tmp, "tele_clean")
    rng = np.random.RandomState(1)
    X = rng.normal(size=(3000, 8)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
    booster = lgb.train({"objective": "binary", "num_leaves": 15,
                         "verbosity": -1, "telemetry_dir": tele_dir},
                        ds, 5)
    path = os.path.join(tele_dir, "flight_rank0.jsonl")
    check(os.path.exists(path), "clean run flushed at train end")
    recs, errors = telemetry.validate_flight_jsonl(path)
    check(not errors, f"clean-run JSONL validates ({errors[:3]})")
    check(recs[-1]["reason"] == "train-end", "final flush is train-end")
    check(distributed.health_snapshot().get("flight_recorder") == path,
          "health snapshot references the JSONL by path")
    return booster


def stage_trace(tmp, booster):
    """Windowed device-trace capture (jax.profiler no-op tolerance)."""
    from lightgbm_tpu import telemetry
    trace_dir = os.path.join(tmp, "trace")
    with telemetry.trace_window(trace_dir, iters=2) as tw:
        for _ in range(2):
            booster.update()
    if tw.ok:
        check(bool(telemetry.trace_files(trace_dir)),
              "trace capture wrote artifact files")
    else:
        # the tolerance contract: no raise, error recorded
        check(bool(tw.error), f"trace failure recorded ({tw.error!r})")


def stage_prometheus():
    from lightgbm_tpu import telemetry
    text = telemetry.prometheus_text()
    bad = [ln for ln in text.splitlines()
           if ln and not ln.startswith("#")
           and not ln.startswith("lightgbm_tpu_")]
    check(not bad, f"every exposition line is namespaced ({bad[:2]})")
    for ln in text.splitlines():
        if ln and not ln.startswith("#"):
            float(ln.rpartition(" ")[2])
    check(True, "every exposition value parses as a number")
    log("snapshot: " + json.dumps(
        {k: type(v).__name__ for k, v in telemetry.snapshot().items()}))


def main():
    trace = "--no-trace" not in sys.argv
    with tempfile.TemporaryDirectory(prefix="lgbm_tele_smoke_") as tmp:
        stage_kill_flush(tmp)
        booster = stage_clean_run(tmp)
        if trace:
            stage_trace(tmp, booster)
        stage_prometheus()
    log("ALL STAGES PASSED")


if __name__ == "__main__":
    main()
