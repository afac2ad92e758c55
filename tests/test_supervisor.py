"""Distributed training supervisor suite: heartbeat liveness, the
collective-deadline watchdog, and gang-restart from checkpoint.

The fault matrix (kill-rank-mid-iter, hang-rank, kill-during-checkpoint-
write, clean-run-no-restart) runs REAL 2-process localhost gangs through
``supervisor.run_supervised`` and asserts the headline property: after the
supervisor relaunches the gang from the latest valid checkpoint, the final
model text is BIT-IDENTICAL to an uninterrupted run's. The gangs train on
replicated data (the reference's ``pre_partition=false`` mode — every
rank's trainer state is identical, which is what makes a rank-0 checkpoint
restore the whole gang exactly; this container's CPU backend cannot run
cross-process XLA collectives, so the cross-process coordination exercised
here is jax.distributed init + the coordination-service barrier + the
heartbeat side-channel, which is also everything the supervisor itself
relies on).

Fast knobs run in tier-1 (clean + kill cases, the single-process watchdog,
and the unit layer); the hang and kill-during-checkpoint-write gangs ride
the slow tier — their detection mechanics (watchdog firing, suspect
naming, stale-.tmp recovery) each have a fast tier-1 sibling below."""

import os
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import distributed, supervisor
from lightgbm_tpu.checkpoint import CheckpointManager
from lightgbm_tpu.distributed import (CollectiveWatchdog,
                                      DistributedTimeoutError,
                                      HeartbeatMonitor, _progress)

pytestmark = pytest.mark.faults


def _data():
    rng = np.random.RandomState(7)
    n, f = 320, 6
    X = rng.normal(size=(n, f))
    y = (X[:, 0] - 0.5 * X[:, 2] > 0).astype(np.float64)
    return X, y


GANG_PARAMS = {"objective": "binary", "num_leaves": 8,
               "min_data_in_leaf": 5, "boost_from_average": False,
               "histogram_method": "scatter", "verbosity": -1,
               # the deadline is judged at every checkpoint barrier: on
               # this loaded 1-core container a 5 s deadline occasionally
               # fired on a HEALTHY slow peer mid-suite, burning a
               # spurious incarnation (restarts==2 flake) — 12 s still
               # detects the hang-rank case in seconds, far under the
               # test timeouts
               "heartbeat_interval": 0.4, "collective_deadline": 12.0}
GANG_ROUNDS = 4


def _gang_train_fn(rank, ckdir):
    """Module-level so distributed.spawn can pickle it: checkpointed,
    resumable training over the full replicated dataset."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(7)
    n, f = 256, 5
    X = rng.normal(size=(n, f))
    y = (X[:, 0] - 0.5 * X[:, 2] > 0).astype(np.float64)
    ds = lgb.Dataset(X, label=y, params=dict(GANG_PARAMS),
                     free_raw_data=False)
    booster = lgb.train(dict(GANG_PARAMS), ds, GANG_ROUNDS,
                        callbacks=[lgb.checkpoint_callback(ckdir, period=1)],
                        resume_from=ckdir)
    return booster.model_to_string()


_CLEAN_CACHE = {}


def _reference_model() -> str:
    """The uninterrupted run's model text. The gang trains the serial
    learner on REPLICATED data, so every rank's model equals a plain
    single-process train of the same params — computed in-process once
    (~3 s) instead of launching a reference gang per test; the slow
    clean-run gang test asserts the gang itself reproduces this text."""
    if "model" not in _CLEAN_CACHE:
        import tempfile
        with tempfile.TemporaryDirectory() as td:
            _CLEAN_CACHE["model"] = _gang_train_fn(
                0, os.path.join(td, "unused_ck"))
    return _CLEAN_CACHE["model"]


def _run_faulted_gang(fault_env: dict, ckdir: str,
                      max_restarts: int = 2) -> supervisor.SupervisorReport:
    saved = {k: os.environ.get(k) for k in fault_env}
    os.environ.update(fault_env)
    try:
        return supervisor.run_supervised(
            _gang_train_fn, nproc=2, args=(ckdir,), devices_per_proc=1,
            checkpoint_dir=ckdir, max_restarts=max_restarts, timeout=180)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# =================================================== gang restart matrix
@pytest.mark.slow
def test_gang_clean_run_no_restart(tmp_path):
    """Clean-run-no-restart case of the matrix: an unfaulted gang runs to
    completion with zero restarts and reproduces the single-process
    reference text. (Slow tier: the kill test below and the tier-1
    integrity gang demo launch the same gang machinery; this case only
    adds the no-fault baseline.)"""
    ckdir = str(tmp_path / "ck")
    report = supervisor.run_supervised(
        _gang_train_fn, nproc=2, args=(ckdir,), devices_per_proc=1,
        checkpoint_dir=ckdir, max_restarts=0, timeout=180)
    assert report.restarts == 0
    assert report.failures == []
    assert report.result.count("Tree=") == GANG_ROUNDS
    assert report.result == _reference_model()


@pytest.mark.slow
def test_gang_kill_rank_mid_iter_bit_identical(tmp_path):
    """PR 5's acceptance bar: rank 1 is hard-killed (os._exit 137) at the
    start of iteration 3; the supervisor reaps the gang, relaunches it
    once with the fault disarmed, the gang resumes from the latest
    checkpoint, and the final model text equals the uninterrupted run's
    byte for byte. Slow: tier-1 siblings cover the machinery —
    test_integrity.py::test_supervised_corrupt_rank_restart_bit_identical
    (the same supervisor restart-from-checkpoint -> bit-identical path on
    a 3-rank gang, driven by a divergence exit instead of a kill) and
    test_gang_shrink_on_spawn_fail (exit-code classification + relaunch);
    the kill-specific 137 classification is one table entry both share."""
    clean = _reference_model()
    ckdir = str(tmp_path / "ck")
    report = _run_faulted_gang(
        {"LGBM_TPU_FAULT_KILL_RANK_AT_ITER": "1:3"}, ckdir)
    assert report.restarts == 1
    assert len(report.failures) == 1
    fl = report.failures[0]
    assert 1 in fl.failed_ranks
    assert fl.exit_codes.get(1) == 137
    assert report.result == clean
    # telemetry: the restart count is on record as a health gauge (the
    # telemetry snapshot's health plane reads it)
    from lightgbm_tpu.utils import profiling
    assert profiling.gauges().get("supervisor_restarts") == 1.0
    # flight recorder (telemetry acceptance): the killed rank flushed
    # its per-iteration ring into the diag dir before os._exit — the
    # JSONL validates and its last record names the in-flight iteration
    # with phase/health state (the relaunched incarnation writes
    # .r1.jsonl files, so the post-mortem survives the restart)
    from lightgbm_tpu import telemetry
    flight = os.path.join(ckdir, "supervisor_diag", "flight_rank1.jsonl")
    assert os.path.exists(flight), "killed rank left no flight recorder"
    recs, errors = telemetry.validate_flight_jsonl(flight)
    assert errors == []
    flush = recs[-1]
    assert flush["type"] == "flush"
    assert "at iteration 3" in flush["reason"]
    assert flush["health"]["last_iteration"] == 2
    iters = [r for r in recs if r["type"] == "iter"]
    assert iters and iters[-1]["iteration"] == 2


@pytest.mark.slow
def test_gang_hang_rank_watchdog_fires_bit_identical(tmp_path):
    """Hung-rank case: rank 1 hangs at iteration 2. Rank 0 proceeds to the
    next checkpoint barrier and its collective_deadline expires there; the
    watchdog diagnosis (written for the supervisor) names the suspect rank
    and the last completed iteration, the gang relaunches, and the final
    model is bit-identical. (Fast tier-1 siblings: the single-process
    watchdog tests + suspect-table unit tests below.)"""
    clean = _reference_model()
    ckdir = str(tmp_path / "ck")
    t0 = time.time()
    report = _run_faulted_gang(
        {"LGBM_TPU_FAULT_HANG_RANK_AT_ITER": "1:2"}, ckdir)
    assert report.restarts == 1
    fl = report.failures[0]
    assert fl.watchdog_fired
    # the watchdog terminated the stall within the deadline (plus launch
    # overheads), not after the supervisor's 180s incarnation timeout
    assert time.time() - t0 < 120
    diags = fl.watchdog
    assert diags, "no watchdog diagnosis written"
    d = diags[0]
    assert d["suspects"] == [1]
    assert d["iteration"] >= 1          # completed iters before the stall
    assert d["deadline"] == GANG_PARAMS["collective_deadline"]
    # the diagnosis references the firing rank's flushed flight-recorder
    # JSONL (telemetry.py): stall verdict + per-iteration post-mortem
    # travel together through the supervisor report
    assert d.get("flight_recorder"), "diagnosis lacks flight_recorder ref"
    assert report.failures[0].flight_recorders
    from lightgbm_tpu import telemetry
    _, errors = telemetry.validate_flight_jsonl(d["flight_recorder"])
    assert errors == []
    assert report.result == clean


@pytest.mark.slow
def test_gang_kill_during_checkpoint_write_bit_identical(tmp_path):
    """Writer killed MID-CHECKPOINT (payload files staged, manifest not):
    the stale ckpt_N.tmp is ignored, the gang restarts from the previous
    valid checkpoint, the next write cleans the staging dir, and the final
    model is bit-identical. (Fast tier-1 sibling: the staging-dir
    recovery tests in test_fault_tolerance.py.)"""
    clean = _reference_model()
    ckdir = str(tmp_path / "ck")
    report = _run_faulted_gang(
        {"LGBM_TPU_FAULT_KILL_IN_CKPT_WRITE": "3"}, ckdir)
    assert report.restarts == 1
    assert report.failures[0].exit_codes.get(0) == 137   # writer = rank 0
    assert report.result == clean
    # no staging junk survived the run
    assert not [e for e in os.listdir(ckdir) if e.endswith(".tmp")]


def _gang_train_fn_always_dies(rank, ckdir):
    """Kill armed through CONFIG, so the supervisor's env-stripping cannot
    disarm it on relaunch — every incarnation dies at iteration 0."""
    import lightgbm_tpu as lgb
    X = np.zeros((100, 3))
    y = np.zeros(100)
    params = {"objective": "regression", "num_leaves": 4, "verbosity": -1,
              "fault_kill_at_iter": 0}
    ds = lgb.Dataset(X, label=y, params=params, free_raw_data=False)
    lgb.train(dict(params), ds, 3)
    return "unreachable"


@pytest.mark.slow
def test_supervisor_gives_up_after_max_restarts(tmp_path):
    """A fault armed through CONFIG (not env, so restart-stripping cannot
    disarm it) kills every incarnation: the supervisor must stop at
    max_restarts and raise with the failure history, not loop forever.
    (Slow tier: the restart loop + exit-code classification it exercises
    also run in the tier-1 kill test above; only the give-up branch is
    unique here.)"""
    ckdir = str(tmp_path / "ck")
    with pytest.raises(supervisor.GangFailedError) as ei:
        supervisor.run_supervised(
            _gang_train_fn_always_dies, nproc=2, args=(ckdir,),
            devices_per_proc=1, checkpoint_dir=ckdir, max_restarts=1,
            timeout=180)
    err = ei.value
    assert len(err.failures) == 2                 # initial + 1 restart
    assert all(137 in f.exit_codes.values() for f in err.failures)
    assert "max_restarts=1" in str(err)
    assert ckdir in str(err)                      # names the resumable dir


# ======================================================= elastic gangs
@pytest.mark.slow
def test_gang_shrink_on_spawn_fail(tmp_path):
    """A rank whose SPAWN fails (exit 96) is classified permanently lost
    on the spot: the supervisor shrinks the gang 2 -> 1, the survivor
    completes training, and the SupervisorReport records the shrink (plus
    the supervisor_world_size health gauge). The final model equals the
    uninterrupted reference — replicated-serial gangs train the same
    model at every world size.

    Slow: the identical drill (permanent spawn-fail of rank 1 -> one
    2->1 shrink recorded in the SupervisorReport -> survivor completes)
    runs on every CI pass as the elastic stanza of
    scripts/supervisor_smoke.py (tests/run_suite.sh), which asserts the
    same world_size / shrinks / lost_ranks fields."""
    clean = _reference_model()
    ckdir = str(tmp_path / "ck")
    report = _run_faulted_gang(
        {"LGBM_TPU_FAULT_SPAWN_FAIL_RANK": "1"}, ckdir)
    assert report.restarts == 1
    assert report.world_size == 1
    assert len(report.shrinks) == 1
    sh = report.shrinks[0]
    assert (sh.from_nproc, sh.to_nproc, sh.lost_ranks) == (2, 1, [1])
    assert "spawn failed" in sh.reason
    fl = report.failures[0]
    assert fl.exit_codes.get(1) == distributed.SPAWN_FAIL_EXIT_CODE
    assert fl.spawn_failed_ranks == [1]
    assert report.result == clean
    from lightgbm_tpu.utils import profiling
    assert profiling.gauges().get("supervisor_world_size") == 1.0
    assert profiling.gauges().get("supervisor_shrinks") == 1.0


def _gang_train_fn_rank1_machine_dead(rank, ckdir):
    """Rank 1's 'machine' is permanently down: it dies whenever it exists,
    across every incarnation (fn-level, so the supervisor's one-shot env
    stripping cannot disarm it) — the budget-exhaustion shrink shape."""
    if rank == 1:
        os._exit(137)
    return _gang_train_fn(rank, ckdir)


@pytest.mark.slow
def test_gang_shrink_on_rank_budget_exhausted(tmp_path):
    """max_restarts accounting ACROSS a shrink: rank 1 dies every
    incarnation; with rank_restart_budget=1 the supervisor burns one
    same-size relaunch (failure 1 <= budget), then classifies rank 1
    permanently lost (failure 2 > budget), shrinks 2 -> 1, and the world-1
    gang completes — 2 restarts total, both counted against max_restarts.
    (Tier-1 sibling: test_gang_shrink_on_spawn_fail covers the shrink
    relaunch machinery; only the budget arithmetic is unique here.)"""
    clean = _reference_model()
    ckdir = str(tmp_path / "ck")
    report = supervisor.run_supervised(
        _gang_train_fn_rank1_machine_dead, nproc=2, args=(ckdir,),
        devices_per_proc=1, checkpoint_dir=ckdir, max_restarts=3,
        timeout=180, rank_restart_budget=1)
    assert report.restarts == 2
    assert report.world_size == 1
    assert len(report.shrinks) == 1
    assert report.shrinks[0].incarnation == 1      # 2nd failure triggered it
    assert "budget 1" in report.shrinks[0].reason
    assert [f.world_size for f in report.failures] == [2, 2]
    assert report.result == clean


@pytest.mark.slow
def test_shrink_respects_min_world_size_and_max_restarts(tmp_path):
    """Accounting edges: with min_world_size=2 a lost rank CANNOT shrink
    a 2-gang, so max_restarts=0 exhausts immediately — the error carries
    the failure (world size recorded, spawn-fail classified) and no
    shrink is recorded. (Slow tier: the shrink relaunch machinery is
    tier-1 via test_gang_shrink_on_spawn_fail; the give-up branch via
    test_supervisor_gives_up_after_max_restarts.)"""
    ckdir = str(tmp_path / "ck")
    os.environ["LGBM_TPU_FAULT_SPAWN_FAIL_RANK"] = "1"
    try:
        with pytest.raises(supervisor.GangFailedError) as ei:
            supervisor.run_supervised(
                _gang_train_fn, nproc=2, args=(ckdir,), devices_per_proc=1,
                checkpoint_dir=ckdir, max_restarts=0, timeout=180,
                min_world_size=2)
    finally:
        os.environ.pop("LGBM_TPU_FAULT_SPAWN_FAIL_RANK", None)
    err = ei.value
    assert len(err.failures) == 1
    assert err.failures[0].world_size == 2
    assert err.failures[0].spawn_failed_ranks == [1]


def test_heartbeat_after_shrink_no_ghost_suspects():
    """After a 3 -> 2 shrink the new gang's monitors are built for
    nproc=2 with renumbered ranks: a fully current 2-rank table implicates
    nobody — the departed rank 3 numbering must NOT resurface as a
    'missing' suspect."""
    hb = HeartbeatMonitor(0, 2, "127.0.0.1:1", interval=0.5)
    now = time.monotonic()
    _progress.reset()
    _progress.begin("step:4", 4)
    try:
        hb._server_table = {
            0: {"iter": 3, "step": 4, "recv": now},
            1: {"iter": 3, "step": 4, "recv": now},
        }
        assert hb.suspects(my_step=4, my_iter=3) == []
    finally:
        _progress.end(4)
        _progress.reset()


def test_suspects_during_relaunch_window():
    """In the window between teardown and the next incarnation's first
    ANSWERED heartbeat a non-zero rank's table is EMPTY: suspects() must
    answer None (unknown), never implicate every rank (including the
    caller). Rank 0's own table always contains at least itself, so a
    freshly relaunched rank 0 names only genuinely absent peers."""
    hb = HeartbeatMonitor(1, 2, "127.0.0.1:1", interval=0.5)
    assert hb.suspects(my_step=0, my_iter=-1) is None


# ============================================ single-process watchdog
def test_watchdog_hang_names_rank_and_iteration():
    """collective_deadline terminates a hang within the deadline and the
    error names the rank and the last completed iteration — the
    single-process shape of the acceptance criterion."""
    X, y = _data()
    params = {"objective": "regression", "num_leaves": 7, "verbosity": -1,
              "collective_deadline": 2.0, "fault_hang_at_iter": 2}
    ds = lgb.Dataset(X, label=y, params=params, free_raw_data=False)
    t0 = time.time()
    with pytest.raises(DistributedTimeoutError) as ei:
        lgb.train(dict(params), ds, num_boost_round=6)
    elapsed = time.time() - t0
    e = ei.value
    assert e.rank == 0
    assert e.iteration == 1                     # completed 0 and 1
    assert "rank 0" in str(e)
    assert "last completed iteration 1" in str(e)
    # fired within the deadline plus compile/monitor slack, not a test
    # timeout later
    assert elapsed < 60, elapsed


def test_watchdog_clean_run_unaffected(tmp_path):
    """An armed watchdog must not perturb training: same trees as a run
    without it (only the echoed parameters block may differ). The watched
    run also checkpoints, covering the manifest health snapshot (restart
    count + progress recorded for postmortems) in the same trainings."""
    import json as _json
    X, y = _data()
    base = {"objective": "regression", "num_leaves": 7, "verbosity": -1}
    ds1 = lgb.Dataset(X, label=y, params=base, free_raw_data=False)
    plain = lgb.train(dict(base), ds1, 4).model_to_string()
    armed = {**base, "collective_deadline": 120.0, "heartbeat_interval": 0.3}
    ckdir = str(tmp_path / "ck")
    ds2 = lgb.Dataset(X, label=y, params=armed, free_raw_data=False)
    watched = lgb.train(dict(armed), ds2, 4,
                        callbacks=[lgb.checkpoint_callback(ckdir, period=2)]
                        ).model_to_string()
    assert plain.split("\nparameters:")[0] == watched.split("\nparameters:")[0]
    health = CheckpointManager(ckdir).load_latest_valid() \
        .manifest.get("health")
    assert health is not None
    assert health["restart_count"] == 0
    assert health["last_iteration"] >= 0
    assert health["collective_deadline"] == 120.0


def test_watchdog_exempts_first_step_compile(monkeypatch):
    """The first boosting step includes jit compile; a deadline shorter
    than compile time must not fire during it (step phases are judged only
    after one completed step). Verified at the unit level: a fresh
    progress state inside a long-running step:0 does not fire."""
    fired = []
    wd = CollectiveWatchdog(0.2, rank=0, supervised=False)
    monkeypatch.setattr(wd, "_fire", lambda snap: fired.append(snap))
    _progress.reset()
    _progress.begin("step:0", 0)
    try:
        wd.start()
        time.sleep(1.0)
        assert fired == []                       # exempt: no completed step
    finally:
        wd.stop()
        _progress.end(0)
    # after one completed step, a stalled step IS judged
    _progress.begin("step:1", 1)
    try:
        wd2 = CollectiveWatchdog(0.2, rank=0, supervised=False)
        monkeypatch.setattr(wd2, "_fire", lambda snap: fired.append(snap))
        wd2.start()
        time.sleep(1.0)
        assert fired and fired[0]["phase"] == "step:1"
    finally:
        wd2.stop()
        _progress.end(1)
        _progress.reset()


def test_barrier_covered_by_watchdog_phase():
    """Barriers register on the progress stack so the watchdog times them
    (the checkpoint barrier is where survivors of a dead rank stall)."""
    _progress.reset()
    with distributed.watchdog_phase("barrier:test"):
        snap = _progress.snapshot()
        assert snap["phase"] == "barrier:test"
        assert snap["phase_elapsed"] >= 0.0
    assert _progress.snapshot()["phase"] is None


# ================================================= heartbeat / suspects
def test_heartbeat_roundtrip_localhost():
    """A rank-0 server and a rank-1 client exchange liveness over the TCP
    side-channel; both ends converge on a 2-rank table."""
    port = distributed.free_port()
    hb0 = HeartbeatMonitor(0, 2, f"127.0.0.1:{port}", interval=0.2)
    hb1 = HeartbeatMonitor(1, 2, f"127.0.0.1:{port}", interval=0.2)
    _progress.reset()
    try:
        hb0.start()
        hb1.start()
        deadline = time.time() + 10
        while time.time() < deadline:
            if set(hb1.table()) == {0, 1} and set(hb0.table()) == {0, 1}:
                break
            time.sleep(0.1)
        assert set(hb0.table()) == {0, 1}
        assert set(hb1.table()) == {0, 1}      # reply carries the table
    finally:
        hb0.stop()
        hb1.stop()


def test_suspects_dead_missing_and_lagging():
    """Suspect classification over a fabricated table: a rank with a stale
    heartbeat, a rank that never reported, and a rank whose progress is
    behind the stalled step are all implicated; current ranks are not."""
    hb = HeartbeatMonitor(0, 4, "127.0.0.1:1", interval=0.5)
    now = time.monotonic()
    _progress.reset()
    _progress.begin("step:5", 5)
    try:
        hb._server_table = {
            0: {"iter": 4, "step": 5, "recv": now},
            1: {"iter": 4, "step": 5, "recv": now},          # current
            2: {"iter": 4, "step": 5, "recv": now - 60.0},   # dead
            3: {"iter": 2, "step": -1, "recv": now},         # lagging/hung
        }
        assert hb.suspects(my_step=5, my_iter=4) == [2, 3]
        # rank 4 missing entirely would also be a suspect
        hb.nproc = 5
        assert hb.suspects(my_step=5, my_iter=4) == [2, 3, 4]
    finally:
        _progress.end(5)
        _progress.reset()


def test_timeout_error_carries_diagnosis():
    e = DistributedTimeoutError(rank=3, iteration=17, suspects=[1, 2],
                                phase="step:18")
    assert e.rank == 3 and e.iteration == 17 and e.suspects == [1, 2]
    s = str(e)
    assert "rank 3" in s and "iteration 17" in s and "1, 2" in s


# ==================================================== health telemetry
def test_health_snapshot_restart_count_env(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_RESTART_COUNT", "3")
    assert distributed.health_snapshot()["restart_count"] == 3


def test_health_gauges_unit():
    """set_gauge/gauges last-value semantics + reset clears them."""
    from lightgbm_tpu.utils import profiling
    profiling.set_gauge("test_gauge", 1)
    profiling.set_gauge("test_gauge", 4.5)
    assert profiling.gauges()["test_gauge"] == 4.5
    was_enabled = profiling.enabled()
    profiling.reset()
    profiling.enable(was_enabled)
    assert "test_gauge" not in profiling.gauges()
