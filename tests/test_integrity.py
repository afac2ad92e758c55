"""Training-integrity layer suite: in-program numerics sentinels,
cross-rank divergence detection, and OOM-aware graceful degradation.

Three properties under test:

1. SENTINELS — ``check_numerics`` now runs WITH ``fused_iteration``: the
   fused step computes a packed NaN/Inf flag word in-program (gradients /
   hessians / histogram plane / leaf outputs / score delta) and the host
   fail-fasts naming iteration + source. Guard off => the grown trees are
   BIT-IDENTICAL to the pre-guard fused path, and the fused iteration
   stays at 2 dispatches with the guard on.
2. DIVERGENCE — every ``integrity_check_period`` iterations ranks
   exchange a model-state fingerprint (tree-structure hash + score-cache
   checksum over the rank's rows) and majority-vote mismatches; a
   bit-flipped rank in a 3-rank gang is named exactly, and the supervisor
   restores it from the last valid checkpoint bit-identically (the
   kill-the-job demo, tier-1 with fast knobs; the unsupervised spawn
   spelling and the budget-exhausted shrink ride the slow tier — their
   verdict mechanics are covered by the unit layer here).
3. OOM DEGRADATION — a RESOURCE_EXHAUSTED from the boosting step walks
   the documented ladder (smaller hist block -> XLA scatter -> chunked
   predict buckets) in order, records every event in health_snapshot()
   and the gauges, and the degraded configuration rides the trainer
   state (bit-identical-restart contract).
"""

import os
import tempfile

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import distributed, supervisor
from lightgbm_tpu.utils import faults, profiling
from lightgbm_tpu.utils.log import LightGBMError

pytestmark = pytest.mark.faults

BASE = {"objective": "binary", "num_leaves": 8, "min_data_in_leaf": 5,
        "verbosity": -1}


def _data(n=400, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.float64)
    return X, y


def _trees(model_text: str) -> str:
    """The tree section of a model dump (the params header legitimately
    records guard flags like check_numerics; the trees must not move)."""
    return model_text.split("end of parameters", 1)[1]


def _fit(params, rounds=6, n=400):
    X, y = _data(n)
    p = dict(BASE, **params)
    return lgb.train(dict(p), lgb.Dataset(X, label=y, params=p), rounds)


# ===================================================== numerics sentinels
def test_sentinel_parity_fused_bit_identical():
    """Guard off => current fused path; guard on => same trees, bit for
    bit (the sentinel reductions ride the program epilogue and must not
    perturb growth), and the fused path is actually taken (the PR 3
    exclusion is lifted)."""
    b_off = _fit({})
    b_on = _fit({"check_numerics": True})
    assert b_on._boosting._fused_cache, \
        "check_numerics unexpectedly unfused the iteration"
    assert _trees(b_off.model_to_string()) == _trees(b_on.model_to_string())


def test_fused_ok_admits_check_numerics():
    X, y = _data()
    p = dict(BASE, check_numerics=True)
    b = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    assert b._boosting._fused_ok(None)


def test_sentinel_catches_in_program_nan_fused():
    """The traced NaN injection (NAN_HIST fault) is invisible to host-side
    checks — only the in-program sentinel word can see it, and the error
    must name the iteration and the source."""
    with pytest.raises(LightGBMError) as ei:
        _fit({"check_numerics": True, "fault_nan_hist_at_iter": 2})
    msg = str(ei.value)
    assert "iteration 2" in msg
    assert "in-program sentinels" in msg
    assert "gradients" in msg


@pytest.mark.slow
def test_sentinel_nan_hist_unfused_host_check():
    """The unfused spelling of the same fault: the host-side counting
    check catches it (the two paths share the fault twin). Slow: tier-1
    siblings cover both halves — test_sentinel_catches_in_program_nan_fused
    (this fault twin, fused) and test_fault_tolerance.py::
    test_check_numerics_names_iteration_and_count (the unfused host-side
    counting check)."""
    with pytest.raises(LightGBMError) as ei:
        _fit({"check_numerics": True, "fused_iteration": False,
              "fault_nan_hist_at_iter": 1})
    assert "iteration 1" in str(ei.value)


@pytest.mark.slow
def test_sentinel_multiclass_fused():
    """Sentinels cover the multiclass lax.scan spelling too (per-class
    aux sentinels are summed into the flag word). Slow: tier-1 siblings
    cover the halves — test_sentinel_catches_in_program_nan_fused (the
    fused in-program catch, binary) and test_fused_wide.py::
    test_fused_parity_multiclass (the multiclass fused-scan growth)."""
    rng = np.random.RandomState(3)
    X = rng.normal(size=(300, 6))
    y = rng.randint(0, 3, size=300).astype(np.float64)
    p = dict(BASE, objective="multiclass", num_class=3,
             check_numerics=True, fault_nan_hist_at_iter=1)
    with pytest.raises(LightGBMError) as ei:
        lgb.train(dict(p), lgb.Dataset(X, label=y, params=p), 4)
    assert "iteration 1" in str(ei.value)


@pytest.fixture
def dispatch_hook():
    if not profiling.install_dispatch_hook():
        pytest.skip("jax internals hook unavailable on this version")
    yield
    profiling.uninstall_dispatch_hook()


def test_sentinel_dispatch_count_stays_two(dispatch_hook):
    """The acceptance number: the sentinel flag word rides the fused
    step's own results — check_numerics must not add a dispatch (still
    grow step + donated score add = 2)."""
    X, y = _data()
    p = dict(BASE, check_numerics=True)
    b = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    for _ in range(2):
        b.update()
    _ = float(np.asarray(b._boosting.train_score).ravel()[0])
    before = profiling.dispatch_stats()
    n_meas = 3
    for _ in range(n_meas):
        b.update()
    delta = profiling.dispatch_delta(before)
    assert delta["dispatches"] / n_meas <= 2.0


def test_sentinel_flag_word_sources():
    """Bit -> source naming used by the fail-fast message."""
    X, y = _data(n=64)
    p = dict(BASE)
    b = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    with pytest.raises(LightGBMError) as ei:
        b._boosting._check_sentinel_flags(0b10001)
    msg = str(ei.value)
    assert "gradients" in msg and "score delta" in msg
    assert "hessians" not in msg
    b._boosting._check_sentinel_flags(0)        # clean word: no raise


# ================================================== divergence: unit layer
def _entry(rank, trees="T", score="S", row_start=0, row_count=100):
    return {"rank": rank, "trees": trees, "score": score,
            "row_start": row_start, "row_count": row_count}


def test_verdict_world3_score_minority():
    """2 honest / 1 flipped at world 3: the minority rank is named, with
    a strict majority (not indeterminate)."""
    entries = [_entry(0), _entry(1, score="S'"), _entry(2)]
    corrupt, indet = distributed.divergence_verdict(entries)
    assert corrupt == [1] and not indet


def test_verdict_world3_tree_minority():
    entries = [_entry(0, trees="T'"), _entry(1), _entry(2)]
    corrupt, indet = distributed.divergence_verdict(entries)
    assert corrupt == [0] and not indet


def test_verdict_world2_indeterminate():
    """A 1:1 split has no majority: both ranks are implicated and the
    verdict is flagged indeterminate (restart the whole gang)."""
    entries = [_entry(0), _entry(1, score="S'")]
    corrupt, indet = distributed.divergence_verdict(entries)
    assert corrupt == [0, 1] and indet


def test_verdict_prepartitioned_disjoint_rows_not_compared():
    """Pre-partitioned ranks hold disjoint row ranges whose score
    checksums differ BY DESIGN — only the (rank-symmetric) tree hash may
    vote across them."""
    entries = [_entry(0, score="A", row_start=0, row_count=50),
               _entry(1, score="B", row_start=50, row_count=50),
               _entry(2, score="C", row_start=100, row_count=50)]
    corrupt, indet = distributed.divergence_verdict(entries)
    assert corrupt == [] and not indet
    entries[1]["trees"] = "T'"                  # but a tree mismatch votes
    corrupt, indet = distributed.divergence_verdict(entries)
    assert corrupt == [1] and not indet


def test_verdict_clean():
    corrupt, indet = distributed.divergence_verdict(
        [_entry(r) for r in range(4)])
    assert corrupt == [] and not indet


def test_flip_score_fault_is_one_bit_involution():
    """The FLIP_SCORE fault moves exactly one bit and undoes itself when
    applied twice (so the test harness can verify placement)."""
    import jax.numpy as jnp
    plan = faults.FaultPlan(flip_score_rank=(0, 3))
    s = jnp.asarray(np.arange(8, dtype=np.float32))
    assert faults.maybe_flip_score(plan, 2, s) is None      # wrong iter
    f1 = faults.maybe_flip_score(plan, 3, s)
    bits = (np.asarray(f1).view(np.uint32)
            ^ np.asarray(s).view(np.uint32))
    assert np.count_nonzero(bits) == 1 and bits.sum() == 1
    f2 = faults.maybe_flip_score(plan, 3, f1)
    assert np.array_equal(np.asarray(f2), np.asarray(s))


def test_model_fingerprint_moves_with_state():
    """The fingerprint is sensitive to both halves it claims to cover:
    score-cache bits and tree structure."""
    b = _fit({}, rounds=2, n=200)
    fp1 = distributed.model_fingerprint(b._boosting)
    import jax.numpy as jnp
    arr = np.array(np.asarray(b._boosting.train_score), copy=True)
    arr.reshape(-1).view(np.uint32)[0] ^= 1
    b._boosting.train_score = jnp.asarray(arr)
    fp2 = distributed.model_fingerprint(b._boosting)
    assert fp1["score"] != fp2["score"] and fp1["trees"] == fp2["trees"]
    b2 = _fit({}, rounds=3, n=200)
    assert distributed.model_fingerprint(b2._boosting)["trees"] \
        != fp1["trees"]


# ======================================== divergence: supervised gang demo
GANG_PARAMS = {"objective": "binary", "num_leaves": 8,
               "min_data_in_leaf": 5, "boost_from_average": False,
               "histogram_method": "scatter", "verbosity": -1,
               "integrity_check_period": 1,
               "heartbeat_interval": 0.4, "collective_deadline": 12.0}
GANG_ROUNDS = 3                     # flip fires after iter 2 (the last
                                    # round): fast knobs, same mechanics


def _gang_data():
    rng = np.random.RandomState(7)
    X = rng.normal(size=(320, 6))
    y = (X[:, 0] - 0.5 * X[:, 2] > 0).astype(np.float64)
    return X, y


def _integrity_gang_fn(rank, ckdir):
    """Module-level so distributed.spawn can pickle it: checkpointed,
    resumable replicated-serial training with the divergence check on."""
    import lightgbm_tpu as lgb
    X, y = _gang_data()
    ds = lgb.Dataset(X, label=y, params=dict(GANG_PARAMS),
                     free_raw_data=False)
    booster = lgb.train(dict(GANG_PARAMS), ds, GANG_ROUNDS,
                        callbacks=[lgb.checkpoint_callback(ckdir, period=1)],
                        resume_from=ckdir)
    return booster.model_to_string()


def _divergence_probe_fn(rank):
    """Unsupervised spelling: every rank must raise RankDivergenceError
    naming the flipped rank."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import distributed as dist
    X, y = _gang_data()
    ds = lgb.Dataset(X, label=y, params=dict(GANG_PARAMS),
                     free_raw_data=False)
    try:
        lgb.train(dict(GANG_PARAMS), ds, GANG_ROUNDS)
        return ("no-error", None)
    except dist.RankDivergenceError as e:
        return ("diverged", (e.iteration, e.corrupt_ranks, e.indeterminate))


def _reference_gang_model() -> str:
    """Fault-free reference: the gang trains the SERIAL learner on
    replicated data, so every rank's model equals a plain single-process
    run with the same params."""
    X, y = _gang_data()
    ds = lgb.Dataset(X, label=y, params=dict(GANG_PARAMS),
                     free_raw_data=False)
    return lgb.train(dict(GANG_PARAMS), ds, GANG_ROUNDS).model_to_string()


@pytest.mark.slow
def test_supervised_corrupt_rank_restart_bit_identical():
    """The kill-the-job demo (fast knobs): one score-cache bit
    flipped on rank 1 of a 3-rank gang -> the divergence check names
    exactly that rank (exit DIVERGENCE_EXIT_CODE + a divergence diagnosis
    naming it), the supervisor restores the gang from the last valid
    checkpoint, and the final model text is BIT-IDENTICAL to the
    fault-free run's.

    Slow (the heaviest single tier-1 test at ~29 s): the identical
    3-rank FLIP_SCORE drill runs on every CI pass as stanza 3 of
    scripts/supervisor_smoke.py (tests/run_suite.sh), the vote logic
    stays tier-1 via the test_verdict_* unit tests above, and the same
    fault's artifact/classification spelling is tier-1 in
    test_postmortem.py::test_classify_flip_score_divergence (with the
    supervised-gang twin riding slow there as
    test_gang_flip_score_postmortem)."""
    ref = _reference_gang_model()
    with tempfile.TemporaryDirectory() as td:
        ck = os.path.join(td, "ck")
        os.environ["LGBM_TPU_FAULT_FLIP_SCORE_RANK"] = "1:2"
        try:
            report = supervisor.run_supervised(
                _integrity_gang_fn, nproc=3, args=(ck,),
                devices_per_proc=1, checkpoint_dir=ck, max_restarts=2,
                timeout=240)
        finally:
            os.environ.pop("LGBM_TPU_FAULT_FLIP_SCORE_RANK", None)
    assert report.restarts == 1
    assert report.failures[0].exit_codes.get(1) \
        == distributed.DIVERGENCE_EXIT_CODE
    assert "diverged" in report.failures[0].reason
    divs = [d for f in report.failures for d in f.watchdog
            if d.get("kind") == "divergence"]
    assert divs and divs[0]["corrupt_ranks"] == [1] \
        and divs[0]["rank"] == 1
    assert report.shrinks == []                 # budget 1: restart, not shrink
    assert report.result == ref


@pytest.mark.slow
def test_divergence_unsupervised_raises_everywhere():
    """Slow subprocess spelling (tier-1 siblings: the verdict unit layer
    + the supervised gang above): without a supervisor, every rank raises
    RankDivergenceError naming the flipped rank."""
    os.environ["LGBM_TPU_FAULT_FLIP_SCORE_RANK"] = "1:2"
    try:
        res = distributed.spawn(_divergence_probe_fn, nproc=3,
                                devices_per_proc=1, timeout=240)
    finally:
        os.environ.pop("LGBM_TPU_FAULT_FLIP_SCORE_RANK", None)
    assert res == ("diverged", (2, [1], False))


@pytest.mark.slow
def test_divergence_shrink_after_budget():
    """Slow subprocess spelling (tier-1 siblings: the supervised restart
    above + the supervisor-shrink suite): with rank_restart_budget=0 a
    single divergence classifies the rank permanently lost and the gang
    SHRINKS 3 -> 2 instead of retrying it."""
    with tempfile.TemporaryDirectory() as td:
        ck = os.path.join(td, "ck")
        os.environ["LGBM_TPU_FAULT_FLIP_SCORE_RANK"] = "1:2"
        try:
            report = supervisor.run_supervised(
                _integrity_gang_fn, nproc=3, args=(ck,),
                devices_per_proc=1, checkpoint_dir=ck, max_restarts=2,
                rank_restart_budget=0, timeout=300)
        finally:
            os.environ.pop("LGBM_TPU_FAULT_FLIP_SCORE_RANK", None)
    assert report.shrinks and report.shrinks[0].lost_ranks == [1]
    assert report.shrinks[0].from_nproc == 3 \
        and report.shrinks[0].to_nproc == 2
    assert report.world_size == 2
    assert report.result is not None


# ==================================================== OOM degradation
def test_oom_ladder_ordering_and_telemetry():
    """count=3 consecutive simulated RESOURCE_EXHAUSTEDs walk the ladder
    in the documented order (block -> scatter -> predict chunk), training
    completes on the 4th attempt, and every event lands in
    health_snapshot()/gauges."""
    b = _fit({"fault_oom_at_iter": 1, "fault_oom_count": 3}, rounds=4)
    bb = b._boosting
    assert bb._oom_level == 3
    # _init_train resets the process-level log, so these are exactly this
    # run's events (an earlier booster's history must not leak into a new
    # run's health snapshots / manifests)
    events = distributed.degradations()
    assert [e["level"] for e in events] == [1, 2, 3]
    assert "hist_block" in events[0]["action"]
    assert "scatter" in events[1]["action"]
    assert "predict_chunk_rows" in events[2]["action"]
    assert all(e["iteration"] == 1 for e in events)
    assert bb._oom_block > 0 and bb._oom_hm == "scatter" \
        and bb._oom_predict_chunk > 0
    assert bb._hist_method() == "scatter"
    health = distributed.health_snapshot()
    assert [e["action"] for e in health["degradations"][-3:]] \
        == [e["action"] for e in events]
    assert profiling.gauges().get("hist_oom_degrade_level") == 3.0
    # the degraded booster still trains and predicts
    X, _ = _data(n=50)
    assert b.predict(X).shape == (50,)


@pytest.mark.slow
def test_oom_ladder_exhausted_reraises():
    """A 4th consecutive OOM after the last rung re-raises: degradation
    is bounded, not an infinite retry loop. Slow: tier-1 siblings —
    test_oom_fallback_gate_off_reraises exercises the same re-raise exit
    and test_oom_ladder_ordering_and_telemetry walks every rung (the
    bound itself is the `_oom_level >= 3` check both paths share)."""
    with pytest.raises(faults.SimulatedResourceExhausted):
        _fit({"fault_oom_at_iter": 1, "fault_oom_count": 5}, rounds=4)


def test_oom_fallback_gate_off_reraises():
    with pytest.raises(faults.SimulatedResourceExhausted):
        _fit({"fault_oom_at_iter": 0, "fault_oom_count": 1,
              "hist_oom_fallback": False}, rounds=2)


def test_oom_classifier_matches_xla_not_everything():
    assert faults.is_resource_exhausted(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating"))
    assert faults.is_resource_exhausted(
        faults.SimulatedResourceExhausted("x"))
    assert not faults.is_resource_exhausted(ValueError("shape mismatch"))


@pytest.mark.slow
def test_oom_degrade_state_rides_trainer_state():
    """The degraded configuration is numerics (block size / method change
    accumulation shape): a resumed incarnation must reuse it — same
    contract as the measured histogram method. Slow: tier-1 sibling
    test_oom_predict_rung_independent_of_training_ladder asserts the same
    oom_degrade dict rides get_trainer_state (predict-rung case; the
    get/set round trip here adds the full-ladder level/block/hm
    fields)."""
    b = _fit({"fault_oom_at_iter": 1, "fault_oom_count": 2}, rounds=2,
             n=200)
    state = b._boosting.get_trainer_state()
    assert state["oom_degrade"]["level"] == 2
    X, y = _data(n=200)
    p = dict(BASE)
    b2 = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    b2._boosting.set_trainer_state(state)
    assert b2._boosting._oom_level == 2
    assert b2._boosting._oom_hm == "scatter"
    assert b2._boosting._oom_block == b._boosting._oom_block
    # an undegraded run records nothing
    b3 = _fit({}, rounds=1, n=200)
    assert b3._boosting.get_trainer_state()["oom_degrade"] is None


@pytest.mark.slow
def test_oom_degraded_run_still_learns():
    """Degrading mid-run keeps the model usable: the scatter-degraded run
    produces the same tree COUNT and a finite, sane model (values differ
    from the undegraded run — accumulation order changed, which is the
    documented price of running degraded). Slow: tier-1 sibling
    test_oom_ladder_ordering_and_telemetry trains through the full
    ladder AND predicts from the degraded booster."""
    b = _fit({"fault_oom_at_iter": 2, "fault_oom_count": 2}, rounds=5)
    assert len(b._boosting.trees) == 5
    X, _ = _data(n=64)
    assert np.isfinite(b.predict(X, raw_score=True)).all()


def test_oom_training_ladder_single_process_only(monkeypatch):
    """Gangs FAIL-STOP on a training OOM: one rank degrading alone would
    change its accumulation numerics and be named corrupt by the
    divergence vote — the supervisor's restart/shrink path owns rank-
    local resource failures."""
    import jax
    b = _fit({}, rounds=1)
    bb = b._boosting
    monkeypatch.setattr(jax, "process_count", lambda: 3)
    exc = faults.SimulatedResourceExhausted("RESOURCE_EXHAUSTED: sim")
    assert bb._maybe_degrade_oom(exc, len(bb.trees)) is False
    assert bb._oom_level == 0


def test_oom_predict_rung_independent_of_training_ladder():
    """A serve-time OOM shrinks the predict chunk WITHOUT consuming the
    training ladder (predict chunking is numerics-exact): a later
    training OOM must still have rungs 1-3 available; and the
    predict-only degraded configuration still rides the trainer state."""
    b = _fit({}, rounds=2)
    bb = b._boosting
    exc = faults.SimulatedResourceExhausted("RESOURCE_EXHAUSTED: sim")
    assert bb._maybe_degrade_predict_oom(exc)
    assert bb._oom_level == 0 and bb._oom_predict_chunk > 0
    state = bb.get_trainer_state()
    assert state["oom_degrade"]["level"] == 0
    assert state["oom_degrade"]["predict_chunk"] == bb._oom_predict_chunk
    # ...and restores on a fresh incarnation (set-side of the contract;
    # the full-ladder fields ride the same dict — slow sibling)
    X, y = _data(n=200)
    p = dict(BASE)
    b2 = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    b2._boosting.set_trainer_state(state)
    assert b2._boosting._oom_predict_chunk == bb._oom_predict_chunk
    # the training ladder starts at rung 1, untouched by the serve OOM
    assert bb._maybe_degrade_oom(exc, len(bb.trees))
    assert bb._oom_level == 1 and bb._oom_block > 0


def test_oom_fallback_method_mapping():
    from lightgbm_tpu.ops.histogram import oom_fallback_method
    assert oom_fallback_method("pallas_hilo") == "scatter"
    assert oom_fallback_method("onehot") == "scatter"
    assert oom_fallback_method("pallas_q8") == "onehot_q8"
    assert oom_fallback_method("onehot_q8") == "onehot_q8"
    from lightgbm_tpu.ops.pallas_hist import DEFAULT_BLOCK, oom_shrink_block
    assert oom_shrink_block(0) == DEFAULT_BLOCK // 4
    assert oom_shrink_block(2048) == 512
    assert oom_shrink_block(600) == 256
    assert oom_shrink_block(100) == 256


# ================================================ review-fix regressions
def test_growaux_unpickles_without_sentinel_field():
    """Pre-sentinel checkpoints pickled a 4-field GrowAux (the CEGB aux in
    state.pkl); the class must keep accepting 4 positional fields, and
    set_trainer_state must normalize the missing sentinel to a real array
    so the fused step's operand structure stays trace-stable."""
    import jax.numpy as jnp
    from lightgbm_tpu.models.grower import GrowAux
    old = GrowAux(jnp.zeros((3,), bool), jnp.zeros((1, 1), bool),
                  jnp.float32(0.0), jnp.float32(0.0))
    assert old.sentinel is None
    b = _fit({"cegb_tradeoff": 0.1}, rounds=2, n=200)
    state = b._boosting.get_trainer_state()
    assert state["cegb_aux"] is not None
    state["cegb_aux"] = type(state["cegb_aux"])(*state["cegb_aux"][:4])
    X, y = _data(n=200)
    p = dict(BASE, cegb_tradeoff=0.1)
    b2 = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    b2._boosting.set_trainer_state(state)
    assert b2._boosting._cegb_aux.sentinel is not None
    assert float(b2._boosting._cegb_aux.sentinel) == 0.0


def test_step_retry_rearms_watchdog_clock():
    """The OOM degrade-and-retry loop re-arms the step clock
    (notify_step_retry): the retry phase carries a fresh timestamp and the
    ``step-retry:`` label the watchdog exempts (the retry recompiles the
    degraded programs), and completion accounting is untouched."""
    import time
    prog = distributed._progress
    prog.reset()
    distributed.notify_step_begin(5)
    time.sleep(0.05)
    distributed.notify_step_retry(5)
    snap = prog.snapshot()
    assert snap["phase"].startswith("step-retry:5")
    assert snap["phase_elapsed"] < 0.05       # fresh clock
    assert snap["steps_done"] == 0            # no phantom completion
    assert snap["step"] == 5                  # still reported in-flight
    distributed.notify_step_end(5)
    snap = prog.snapshot()
    assert snap["phase"] is None and snap["steps_done"] == 1
    prog.reset()


def test_checkpoint_callback_votes_before_save(tmp_path, monkeypatch):
    """A checkpoint written BETWEEN integrity votes must not capture
    uncertified state: with integrity_check_period on, the checkpoint
    callback runs the divergence vote before saving — unless engine.train
    already voted this very iteration (the dedup marker)."""
    from lightgbm_tpu.callback import CallbackEnv
    X, y = _data(n=200)
    p = dict(BASE, integrity_check_period=3)
    ds = lgb.Dataset(X, label=y, params=p)
    b = lgb.train(dict(p), ds, 2, keep_training_booster=True)
    calls = []
    monkeypatch.setattr(distributed, "check_model_integrity",
                        lambda boosting, it, **kw: calls.append(it))
    cb = lgb.checkpoint_callback(str(tmp_path / "ck"), period=1)
    env = CallbackEnv(model=b, params=dict(p), iteration=1,
                      begin_iteration=0, end_iteration=2,
                      evaluation_result_list=[])
    cb(env)
    assert calls == [1]
    # engine.train voted at this iteration already -> no second exchange
    b._boosting._integrity_checked_iter = 1
    cb(env)
    assert calls == [1]
