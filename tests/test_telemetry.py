"""Unified telemetry layer (lightgbm_tpu/telemetry.py): snapshot schema,
flight-recorder ring + crash flushes, trace capture, Prometheus
exposition, and the overhead contract (the recorder reads only
already-fetched host values — zero extra dispatches per iteration).

Crash-flush coverage reuses the utils/faults.py harness: a hard kill at
iteration k (subprocess), a NaN gradient under check_numerics, and an
OOM ladder exhaustion must each leave a flushed JSONL that exists,
parses, schema-validates, and names the faulty iteration."""

import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.utils import profiling
from lightgbm_tpu.utils.log import LightGBMError

pytestmark = pytest.mark.faults


def _data(n=3000, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] + rng.normal(size=n) > 0).astype(np.float32)
    return X, y


def _train(params=None, rounds=6, n=3000, **kwargs):
    X, y = _data(n=n)
    ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "min_data_in_leaf": 20}
    p.update(params or {})
    return lgb.train(p, ds, rounds, **kwargs)


# ------------------------------------------------------------- snapshot

def test_snapshot_schema():
    snap = telemetry.snapshot()
    assert snap["schema"] == telemetry.SCHEMA_VERSION
    for key in ("time", "scopes", "counters", "gauges", "dispatch",
                "health"):
        assert key in snap
    # the dispatch plane carries the four monotonic counters even when
    # the hook is not installed (zeros)
    assert set(snap["dispatch"]) == {"dispatches", "device_gets",
                                     "d2h_bytes", "h2d_bytes"}
    # health embeds progress scalars the Prometheus renderer needs
    assert "restart_count" in snap["health"]


def test_prometheus_text_renders_gauges_and_scopes():
    profiling.set_gauge("serve_p99_ms", 12.5)
    profiling.set_gauge("serve_p50_ms", 3.25)
    # monotonic counters past 1e6 must keep FULL precision ('%g' would
    # freeze them at 6 significant digits and blind rate()/increase())
    profiling.set_gauge("serve_requests", 1234567.0)
    try:
        text = telemetry.prometheus_text()
    finally:
        profiling.reset()
    assert "lightgbm_tpu_serve_p99_ms 12.5" in text
    assert "lightgbm_tpu_serve_p50_ms 3.25" in text
    assert "lightgbm_tpu_serve_requests 1234567" in text
    assert "lightgbm_tpu_dispatches_total" in text
    assert text.startswith("# lightgbm_tpu telemetry schema")
    # every non-comment line is "name[{labels}] value"
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        name, _, value = line.rpartition(" ")
        assert name.startswith("lightgbm_tpu_"), line
        float(value)


def test_gang_snapshot_single_process():
    out = telemetry.gang_snapshot("test_single")
    assert len(out) == 1
    assert out[0]["schema"] == telemetry.SCHEMA_VERSION


def _gang_snapshot_fn(rank):
    """Module-level so spawn can pickle it: each rank tags a gauge with
    its own rank, then allgathers snapshots in lockstep."""
    from lightgbm_tpu import telemetry as tele
    from lightgbm_tpu.utils import profiling as prof
    prof.set_gauge("gang_probe_rank", float(rank))
    snaps = tele.gang_snapshot("tele_gang_test")
    return [(s["schema"], s["gauges"].get("gang_probe_rank"))
            for s in snaps]


@pytest.mark.slow
def test_gang_snapshot_two_process():
    """Rank-0 gang aggregation over the coordination service: a REAL
    2-process gang exchanges snapshots through exchange_host and every
    rank sees both, in rank order. (Tier-1 sibling: the single-process
    spelling above runs the same code path minus the gRPC hop.)"""
    from lightgbm_tpu import distributed
    out = distributed.spawn(_gang_snapshot_fn, nproc=2,
                            devices_per_proc=1, timeout=240)
    assert out == [(telemetry.SCHEMA_VERSION, 0.0),
                   (telemetry.SCHEMA_VERSION, 1.0)]


# ------------------------------------------------------- flight recorder

def test_recorder_rides_training_and_flushes(tmp_path):
    d = str(tmp_path / "tele")
    _train({"telemetry_dir": d, "telemetry_ring_size": 4}, rounds=7)
    rec = telemetry.recorder()
    assert rec is not None
    records = rec.records()
    # ring bounded at 4 despite 7 iterations
    assert len(records) == 4
    assert records[-1]["iteration"] == 6
    assert all(r["completed"] for r in records)
    # resolved run context filled after the first step
    assert rec.has_context
    # clean train end flushed (a durable dir was configured)
    path = os.path.join(d, "flight_rank0.jsonl")
    assert os.path.exists(path)
    recs, errors = telemetry.validate_flight_jsonl(path)
    assert errors == []
    assert recs[0]["type"] == "run"
    assert recs[0]["context"]["backend"] == "cpu"
    assert recs[-1]["type"] == "flush"
    assert recs[-1]["reason"] == "train-end"
    # the manifest/bench embed point: health names the JSONL by reference
    from lightgbm_tpu import distributed
    assert distributed.health_snapshot().get("flight_recorder") == path


def test_recorder_disabled_by_param(tmp_path):
    _train({"telemetry_flight_recorder": False,
            "telemetry_dir": str(tmp_path)}, rounds=3)
    assert telemetry.recorder() is None
    assert not os.path.exists(str(tmp_path / "flight_rank0.jsonl"))


@pytest.mark.slow
def test_recorder_no_flush_without_dir(tmp_path):
    """A clean run with NO durable dir configured leaves no JSONL litter
    (event flushes still would — tested by the fault cases). Slow:
    tier-1 siblings cover both sides of the switch
    (test_recorder_rides_training_and_flushes asserts the WITH-dir
    flush, test_recorder_disabled_by_param the off-param) — this case
    only adds the no-dir/no-litter default."""
    _train(rounds=3)
    rec = telemetry.recorder()
    assert rec is not None and len(rec.records()) == 3
    assert rec.path() is None            # no dir resolved, never flushed


@pytest.mark.slow
def test_kill_fault_flushes_jsonl(tmp_path):
    """A supervised-style hard kill (utils/faults _hard_exit) leaves a
    flushed flight-recorder JSONL that validates and names the in-flight
    iteration — the crashed-gang post-mortem contract. Slow: the tier-1
    sibling test_postmortem.py::test_classify_kill_rank runs the same
    subprocess kill and asserts the same JSONL validation + in-flight
    iteration on top of the analyzer verdict."""
    d = str(tmp_path / "tele")
    code = (
        "import numpy as np, lightgbm_tpu as lgb\n"
        "rng = np.random.RandomState(0)\n"
        "X = rng.normal(size=(2000, 8)).astype(np.float32)\n"
        "y = (X[:, 0] > 0).astype(np.float32)\n"
        "ds = lgb.Dataset(X, label=y, params={'verbosity': -1})\n"
        "lgb.train({'objective': 'binary', 'num_leaves': 15,\n"
        "           'verbosity': -1, 'telemetry_dir': %r,\n"
        "           'fault_kill_at_iter': 3}, ds, 10)\n" % d)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 137, r.stderr[-2000:]
    path = os.path.join(d, "flight_rank0.jsonl")
    assert os.path.exists(path), "kill fault did not flush the recorder"
    recs, errors = telemetry.validate_flight_jsonl(path)
    assert errors == []
    flush = recs[-1]
    assert flush["type"] == "flush"
    # the last record names the in-flight iteration: the kill fired at
    # the start of iteration 3, after 3 completed records
    assert "at iteration 3" in flush["reason"]
    iters = [r for r in recs if r["type"] == "iter"]
    assert iters and iters[-1]["iteration"] == 2
    assert flush["health"]["last_iteration"] == 2


@pytest.mark.slow
def test_nan_grad_error_flush_names_iteration(tmp_path):
    """check_numerics fail-fast: the train-error flush lands with the
    sentinel/NaN verdict in the reason, even without a durable dir.
    Slow: tier-1 siblings exercise the same engine train-error flush
    through the OOM-exhaustion raise (test_oom_exhaustion_flushes) and
    the check_numerics judge through the sentinel back-fill test —
    this case only adds the NaN-specific reason text."""
    with pytest.raises(LightGBMError, match="iteration 2"):
        _train({"check_numerics": True, "fault_nan_grad_at_iter": 2},
               rounds=6)
    rec = telemetry.recorder()
    assert rec is not None
    path = rec.path()                  # created by the event flush
    assert path is not None and os.path.exists(path)
    recs, errors = telemetry.validate_flight_jsonl(path)
    assert errors == []
    reasons = [r["reason"] for r in recs if r["type"] == "flush"]
    assert any("train-error" in r and "iteration 2" in r for r in reasons)


def test_sentinel_verdict_backfills_record():
    """The fused path's lazy sentinel drain back-fills 'ok' verdicts
    into the covering flight records (rides the drain — no extra
    fetches)."""
    booster = _train({"check_numerics": True}, rounds=5)
    booster._boosting._flush_sentinel()
    rec = telemetry.recorder()
    iters = [r for r in rec.records() if r["type"] == "iter"]
    assert iters
    # every verdict judged by now; none may still read "pending"/"off"
    assert all(r["sentinel"] == "ok" for r in iters)


@pytest.mark.slow
def test_oom_exhaustion_flushes(tmp_path):
    """Spending the whole OOM ladder flushes an 'oom-exhausted' event
    before the error unwinds, with the degradation rungs in the ring.
    Slow: the tier-1 sibling
    test_postmortem.py::test_classify_oom_exhaustion drives the same
    exhaustion and asserts the same flush + [1, 2, 3] ladder history on
    top of the analyzer verdict (plus the memory/predicted-bytes
    enrichment)."""
    from lightgbm_tpu.utils.faults import SimulatedResourceExhausted
    with pytest.raises(SimulatedResourceExhausted):
        _train({"telemetry_dir": str(tmp_path / "t"),
                "fault_oom_at_iter": 2, "fault_oom_count": 4}, rounds=6)
    rec = telemetry.recorder()
    path = rec.path()
    assert path is not None and os.path.exists(path)
    recs, errors = telemetry.validate_flight_jsonl(path)
    assert errors == []
    reasons = [r["reason"] for r in recs if r["type"] == "flush"]
    assert any(r.startswith("oom-exhausted") for r in reasons)
    # the exhaustion flush carries the full ladder history in health
    flush = next(r for r in recs if r["type"] == "flush"
                 and r["reason"].startswith("oom-exhausted"))
    degr = flush["health"].get("degradations") or []
    assert [d["level"] for d in degr if d["kind"] == "oom"] == [1, 2, 3]


def test_flush_is_idempotent_and_cumulative(tmp_path):
    """Each flush rewrites the file with the full ring + EVERY flush
    event so far — an early event flush survives into the final one."""
    d = str(tmp_path)
    rec = telemetry.FlightRecorder(capacity=8, directory=d, rank=0)
    rec.record(iteration=0, wall_s=0.1)
    p1 = rec.flush("first-event")
    rec.record(iteration=1, wall_s=0.1)
    p2 = rec.flush("second-event")
    assert p1 == p2
    recs, errors = telemetry.validate_flight_jsonl(p2)
    assert errors == []
    reasons = [r["reason"] for r in recs if r["type"] == "flush"]
    assert reasons == ["first-event", "second-event"]
    assert sum(1 for r in recs if r["type"] == "iter") == 2
    # periodic flushes are TRANSIENT: written into their own file, never
    # retained into later flushes (a long run must not accumulate one
    # permanent event per period — quadratic file growth)
    p3 = rec.flush("periodic", retain_event=False)
    recs, _ = telemetry.validate_flight_jsonl(p3)
    assert [r["reason"] for r in recs if r["type"] == "flush"] \
        == ["first-event", "second-event", "periodic"]
    p4 = rec.flush("third-event")
    recs, _ = telemetry.validate_flight_jsonl(p4)
    assert [r["reason"] for r in recs if r["type"] == "flush"] \
        == ["first-event", "second-event", "third-event"]


def test_periodic_flush_cadence(tmp_path):
    """flush_period=4: no flush at iteration 0 (the off-by-one the
    review caught); checkpoints land on period crossings only."""
    rec = telemetry.FlightRecorder(capacity=8, directory=str(tmp_path),
                                   rank=0, flush_period=4)
    rec.record(iteration=0, wall_s=0.0)
    assert not os.path.exists(rec.path())     # first record: no flush
    for i in range(1, 4):
        rec.record(iteration=i, wall_s=0.0)
    assert not os.path.exists(rec.path())     # still inside period 0
    rec.record(iteration=4, wall_s=0.0)       # crossing -> checkpoint
    recs, errors = telemetry.validate_flight_jsonl(rec.path())
    assert errors == []
    assert [r["reason"] for r in recs if r["type"] == "flush"] \
        == ["periodic"]


def test_validate_rejects_bad_jsonl(tmp_path):
    p = str(tmp_path / "bad.jsonl")
    with open(p, "w") as fh:
        fh.write('{"type": "iter", "iteration": 0}\nnot json\n')
    recs, errors = telemetry.validate_flight_jsonl(p)
    assert errors   # missing fields + unparseable + no header/flush
    assert any("unparseable" in e for e in errors)
    assert any("run" in e for e in errors)


# ------------------------------------------------------ memory telemetry

def test_snapshot_has_memory_plane():
    snap = telemetry.snapshot()
    mem = snap["memory"]
    for key in ("hbm_bytes_in_use", "hbm_peak_bytes", "host_rss_bytes",
                "host_rss_peak_bytes"):
        assert key in mem
    # CPU backend: HBM fields are null (Device.memory_stats() returns
    # None), host fields are real — the None-tolerance contract
    assert mem["hbm_bytes_in_use"] is None
    assert isinstance(mem["host_rss_bytes"], int)
    assert mem["host_rss_peak_bytes"] >= mem["host_rss_bytes"] > 0


def test_recorder_records_memory_fields(tmp_path):
    """Every flight record carries the memory sample (HBM fields null
    on CPU, host RSS real), the always-on gauges mirror the latest
    sample, and health_snapshot()/prometheus_text() surface them — the
    checkpoint-manifest and /metrics embed points."""
    from lightgbm_tpu import distributed
    _train({"telemetry_dir": str(tmp_path / "t")}, rounds=3)
    rec = telemetry.recorder()
    iters = [r for r in rec.records() if r["type"] == "iter"]
    assert iters
    for r in iters:
        mem = r["mem"]
        assert mem["hbm_bytes_in_use"] is None      # CPU: null, no crash
        assert mem["hbm_peak_bytes"] is None
        assert mem["host_rss_bytes"] > 0
    health = distributed.health_snapshot()
    assert health["memory"]["host_rss_bytes"] > 0
    assert health["memory"]["host_rss_peak_bytes"] \
        >= health["memory"]["host_rss_bytes"]
    text = telemetry.prometheus_text()
    assert "lightgbm_tpu_host_rss_bytes" in text
    # the nulls stay out of the exposition (a gauge is only set from a
    # non-null sample)
    assert "lightgbm_tpu_hbm_bytes_in_use" not in text


def test_memory_off_by_param(tmp_path):
    """telemetry_memory=false: records carry no mem field at all."""
    _train({"telemetry_memory": False,
            "telemetry_dir": str(tmp_path / "t")}, rounds=3)
    rec = telemetry.recorder()
    iters = [r for r in rec.records() if r["type"] == "iter"]
    assert iters and all("mem" not in r for r in iters)


def test_memory_stats_failure_forces_none_path(monkeypatch, tmp_path):
    """The satellite contract, forced: a device whose memory_stats()
    RAISES (not just returns None) must record null fields and never
    crash training — and the failed probe is cached so it is not
    retried per record."""
    from lightgbm_tpu.utils import profiling

    class _Exploding:
        calls = 0

        def memory_stats(self):
            _Exploding.calls += 1
            raise RuntimeError("memory_stats unavailable on this backend")

    monkeypatch.setattr(profiling, "_mem_device", _Exploding())
    monkeypatch.setattr(profiling, "_mem_device_ok", None)
    sample = profiling.sample_memory()
    assert sample["hbm_bytes_in_use"] is None
    assert sample["hbm_peak_bytes"] is None
    assert sample["host_rss_bytes"] > 0        # host source is independent
    # a full recorder-on training run survives the exploding device
    _train({"telemetry_dir": str(tmp_path / "t")}, rounds=3)
    iters = [r for r in telemetry.recorder().records()
             if r["type"] == "iter"]
    assert all(r["mem"]["hbm_bytes_in_use"] is None for r in iters)
    assert _Exploding.calls == 1               # probe cached, not per-record


def test_degradation_event_enrichment():
    """OOM rung events carry the memory snapshot at failure, the
    traffic model's predicted per-pass bytes, wall + monotonic stamps
    and the active iteration (the satellite ordering contract)."""
    from lightgbm_tpu import distributed
    with pytest.raises(Exception):
        _train({"fault_oom_at_iter": 1, "fault_oom_count": 4}, rounds=4)
    degr = [d for d in distributed.degradations() if d["kind"] == "oom"]
    assert [d["level"] for d in degr] == [1, 2, 3]
    for d in degr:
        assert d["iteration"] == 1
        assert d["t"] > 0 and d["t_mono"] > 0
        assert d["memory"]["host_rss_bytes"] > 0
        assert d["memory"]["hbm_bytes_in_use"] is None    # CPU
        assert d["predicted_hist_bytes"] > 0
    assert degr[0]["t_mono"] <= degr[1]["t_mono"] <= degr[2]["t_mono"]


# -------------------------------------------------- overhead contract

def test_recorder_adds_zero_dispatches():
    """The acceptance bar: recorder-on training must not add a single
    compiled-program dispatch or device fetch per iteration (it reads
    only already-fetched host values). Measured with the dispatch hook
    over the same warm fused loop, recorder off vs on."""
    X, y = _data(n=4000)
    counts = {}
    for on in (False, True):
        ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
        booster = lgb.Booster(params={
            "objective": "binary", "num_leaves": 15, "verbosity": -1,
            "telemetry_flight_recorder": on}, train_set=ds)
        booster.update()
        booster.update()                      # warm (compile)
        _ = float(np.asarray(booster._boosting.train_score).ravel()[0])
        live = profiling.install_dispatch_hook()
        try:
            with profiling.dispatch_scope() as d:
                for _ in range(4):
                    booster.update()
            _ = float(np.asarray(
                booster._boosting.train_score).ravel()[0])
        finally:
            profiling.uninstall_dispatch_hook()
        if not live:
            pytest.skip("dispatch hook unavailable on this jax")
        # device_gets are deliberately NOT compared: the lazy host-mirror
        # drain (_flush_pending only_ready=True) fetches whichever
        # mirrors finished during the window, so their attribution
        # shifts with any per-iteration host timing — the same mirrors
        # get fetched either way. Dispatches are the budget.
        counts[on] = d["dispatches"]
    assert counts[True] == counts[False], (
        f"recorder-on dispatched {counts[True]} programs vs recorder-off "
        f"{counts[False]}: the recorder touched the device")
    # and the fused budget itself holds with the recorder on
    assert counts[True] <= 2 * 4


# ------------------------------------------------------- trace capture

@pytest.mark.slow
def test_trace_window_captures_on_cpu(tmp_path):
    """Slow: scripts/telemetry_smoke.py (tests/run_suite.sh) runs this
    exact capture end-to-end on every CI pass; tier-1 keeps the instant
    bad-dir tolerance case below."""
    d = str(tmp_path / "trace")
    booster = _train(rounds=2)
    with telemetry.trace_window(d, iters=2) as tw:
        booster.update()
        booster.update()
    # jax's CPU profiler works in this image; if a backend cannot trace,
    # the contract is a recorded error — never a raise
    if not tw.ok:
        assert tw.error
        pytest.skip(f"profiler unavailable: {tw.error}")
    assert tw.to_json()["iters"] == 2
    assert telemetry.trace_files(d), "no trace artifacts written"


def test_trace_window_tolerates_bad_dir():
    with telemetry.trace_window("/proc/definitely/not/writable") as tw:
        pass
    assert not tw.ok and tw.error


# -------------------------------------------------- profiling satellites

def test_profiling_counters_thread_safe():
    """The satellite fix: _counters/_gauges read-modify-writes are now
    lock-protected — hammering them from threads loses no updates."""
    import threading
    profiling.reset()
    profiling.enable(True)
    try:
        n_threads, n_iter = 8, 400

        def work():
            for _ in range(n_iter):
                profiling.counter("ts_test", 1.0)
                profiling.inc_gauge("ts_gauge", 1.0)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert profiling.counters()["ts_test"] == n_threads * n_iter
        assert profiling.gauges()["ts_gauge"] == n_threads * n_iter
    finally:
        profiling.enable(False)
        profiling.reset()


def test_reset_leaves_dispatch_counters():
    """reset() keeps the monotonic dispatch counters (documented
    contract); reset_dispatch() is the explicit test-only origin."""
    before = profiling.dispatch_stats()
    profiling.reset()
    assert profiling.dispatch_stats() == before
    profiling.reset_dispatch()
    assert all(v == 0 for v in profiling.dispatch_stats().values())


# ------------------------------------------------------- serve /metrics

def test_serve_metrics_endpoint():
    booster = _train(rounds=3)
    from lightgbm_tpu import ServeFrontend
    fe = ServeFrontend(booster, metrics=True, metrics_port=0)
    try:
        addr = fe.metrics_addr
        assert addr is not None
        _ = fe.predict(_data(n=8)[0])
        body = urllib.request.urlopen(
            f"http://{addr}/metrics", timeout=10).read().decode()
        assert "lightgbm_tpu_serve_p50_ms" in body
        assert "lightgbm_tpu_serve_requests 1" in body
        # unknown paths 404
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://{addr}/nope", timeout=10)
        # direct render equals the endpoint's source of truth
        assert "lightgbm_tpu_serve_requests" in fe.metrics_text()
    finally:
        fe.close()
    assert fe.metrics_addr is None      # listener shut down with close()
