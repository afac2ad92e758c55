"""Fault-injection harness for resilience testing.

Deterministic, opt-in failure points threaded through the training loop so
the fault-tolerance suite (tests/test_fault_tolerance.py) and the gang
supervisor suite (tests/test_supervisor.py) can exercise the
checkpoint/resume, watchdog and gang-restart machinery against REAL failure
shapes — a hard kill mid-run (preemptible TPU fleets), a rank that hangs
and stalls every collective, a writer killed mid-checkpoint, a checkpoint
truncated/corrupted on disk, and NaN gradients poisoning histograms —
instead of only happy paths.

Faults are driven by params (``fault_kill_at_iter`` etc. on Config) or
environment variables (which override params, so a test can arm a fault in
a child process without touching its config):

  LGBM_TPU_FAULT_KILL_AT_ITER=k       hard-exit (os._exit(137), no cleanup,
                                      like SIGKILL) at the START of 0-based
                                      boosting iteration k
  LGBM_TPU_FAULT_HANG_AT_ITER=k       hang (interruptible sleep loop,
                                      forever) at the start of iteration k
  LGBM_TPU_FAULT_KILL_RANK_AT_ITER=r:k   kill ONLY process rank r at
                                      iteration k (multi-process gangs)
  LGBM_TPU_FAULT_HANG_RANK_AT_ITER=r:k   hang ONLY process rank r at
                                      iteration k
  LGBM_TPU_FAULT_KILL_IN_CKPT_WRITE=k hard-exit in the MIDDLE of the
                                      checkpoint write for iteration k
                                      (payload files written, manifest not)
  LGBM_TPU_FAULT_NAN_GRAD_AT_ITER=k   overwrite the first
                                      LGBM_TPU_FAULT_NAN_GRAD_COUNT (default
                                      8) gradient values with NaN at
                                      iteration k
  LGBM_TPU_FAULT_CORRUPT_CHECKPOINT=1 flip bytes in every checkpoint's
                                      model text right after it is written
                                      (simulates on-disk corruption)
  LGBM_TPU_FAULT_KILL_IN_SHARD_WRITE=r:k  hard-exit rank r between writing
                                      its score-cache shard and the shard-
                                      metadata exchange of the SHARDED
                                      checkpoint write for iteration k
                                      (pre-partitioned gangs; the stale
                                      ckpt_N.tmp must stay harmless)
  LGBM_TPU_FAULT_CORRUPT_SHARD=r      flip bytes in rank r's shard file of
                                      every sharded checkpoint right after
                                      publication (manifest stays intact,
                                      so only checksum validation catches
                                      it)
  LGBM_TPU_FAULT_SPAWN_FAIL_RANK=r    make spawned child rank r exit with
                                      SPAWN_FAIL_EXIT_CODE (96) before any
                                      bootstrap — the "machine cannot
                                      start" shape the supervisor answers
                                      with a gang SHRINK (env-driven only:
                                      it fires before a config exists)
  LGBM_TPU_FAULT_FLIP_SCORE_RANK=r:k  flip ONE bit of rank r's train-score
                                      cache right after iteration k
                                      completes — the silent-corruption
                                      shape (cosmic ray / bad DIMM / kernel
                                      bug) the cross-rank divergence check
                                      (distributed.check_model_integrity)
                                      exists to catch
  LGBM_TPU_FAULT_NAN_HIST_AT_ITER=k   poison one gradient value with NaN
                                      INSIDE the compiled program at
                                      iteration k — unlike NAN_GRAD (which
                                      materializes gradients on host and
                                      so unfuses the iteration), this one
                                      is a traced injection the fused
                                      path's in-program numerics sentinels
                                      must catch
  LGBM_TPU_FAULT_OOM_AT_ITER=k        raise a simulated RESOURCE_EXHAUSTED
                                      from the boosting step at iteration
                                      k, LGBM_TPU_FAULT_OOM_COUNT times
                                      consecutively (default 1) — drives
                                      the OOM degradation ladder
                                      (models/gbdt.py _maybe_degrade_oom)
                                      one rung per raise
  LGBM_TPU_FAULT_SLOW_PREDICT_MS=ms   sleep ``ms`` milliseconds inside
                                      every predict dispatch (the slow-
                                      dispatch shape — a stalled device,
                                      a noisy neighbor — the serving layer's
                                      per-request deadlines and admission
                                      control must answer; serving.py's
                                      deadline/shed tests arm it)
  LGBM_TPU_FAULT_OOM_AT_PREDICT=c     raise a simulated RESOURCE_EXHAUSTED
                                      from the next ``c`` predict
                                      dispatches PROCESS-WIDE (the fired
                                      count persists across the fresh
                                      fault plans each predict call
                                      builds, so the ladder's retry loop
                                      terminates) — drives the serve-side
                                      predict-chunk degradation rung
                                      (models/gbdt.py
                                      _maybe_degrade_predict_oom) without
                                      touching the training rungs

The rank-targeted forms resolve the process rank lazily through
``jax.process_index()`` so the plan can be built before distributed init.
With no fault armed the plan is ``None`` and every hook is a single
attribute check — zero cost on the training path.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from dataclasses import dataclass
from typing import Optional, Tuple

_KILL_EXIT_CODE = 137   # 128 + SIGKILL: what a preemption/oom kill reports


@dataclass
class FaultPlan:
    kill_at_iter: int = -1
    hang_at_iter: int = -1
    kill_rank_at_iter: Optional[Tuple[int, int]] = None   # (rank, iter)
    hang_rank_at_iter: Optional[Tuple[int, int]] = None   # (rank, iter)
    kill_in_ckpt_write: int = -1
    kill_in_shard_write: Optional[Tuple[int, int]] = None  # (rank, iter)
    corrupt_shard: int = -1                               # rank
    nan_grad_at_iter: int = -1
    nan_grad_count: int = 8
    corrupt_checkpoint: bool = False
    flip_score_rank: Optional[Tuple[int, int]] = None     # (rank, iter)
    nan_hist_at_iter: int = -1
    oom_at_iter: int = -1
    oom_count: int = 1            # consecutive simulated OOM raises left
                                  # (mutated by maybe_oom as they fire)

    @property
    def wants_nan_grad(self) -> bool:
        return self.nan_grad_at_iter >= 0


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name, "")
    try:
        return int(v) if v != "" else default
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name, "")
    try:
        return float(v) if v != "" else default
    except ValueError:
        return default


def _env_rank_iter(name: str,
                   default: str = "") -> Optional[Tuple[int, int]]:
    """Parse an "r:k" rank-targeted fault env var (falling back to the
    config-param twin's string value); None when unset or malformed (a
    malformed value must not silently kill rank 0)."""
    v = os.environ.get(name, "") or str(default or "")
    if not v:
        return None
    try:
        r, _, k = v.partition(":")
        return (int(r), int(k))
    except ValueError:
        sys.stderr.write(f"[faults] ignoring malformed {name}={v!r} "
                         f"(want rank:iter)\n")
        return None


def plan_from(config=None) -> Optional[FaultPlan]:
    """Build the active fault plan from config fields overridden by the
    LGBM_TPU_FAULT_* environment; None when nothing is armed."""
    get = (lambda k, d: getattr(config, k, d)) if config is not None \
        else (lambda k, d: d)
    plan = FaultPlan(
        kill_at_iter=_env_int("LGBM_TPU_FAULT_KILL_AT_ITER",
                              int(get("fault_kill_at_iter", -1))),
        hang_at_iter=_env_int("LGBM_TPU_FAULT_HANG_AT_ITER",
                              int(get("fault_hang_at_iter", -1))),
        kill_rank_at_iter=_env_rank_iter(
            "LGBM_TPU_FAULT_KILL_RANK_AT_ITER",
            get("fault_kill_rank_at_iter", "")),
        hang_rank_at_iter=_env_rank_iter(
            "LGBM_TPU_FAULT_HANG_RANK_AT_ITER",
            get("fault_hang_rank_at_iter", "")),
        kill_in_ckpt_write=_env_int("LGBM_TPU_FAULT_KILL_IN_CKPT_WRITE",
                                    int(get("fault_kill_in_ckpt_write", -1))),
        kill_in_shard_write=_env_rank_iter(
            "LGBM_TPU_FAULT_KILL_IN_SHARD_WRITE",
            get("fault_kill_in_shard_write", "")),
        corrupt_shard=_env_int("LGBM_TPU_FAULT_CORRUPT_SHARD",
                               int(get("fault_corrupt_shard", -1))),
        nan_grad_at_iter=_env_int("LGBM_TPU_FAULT_NAN_GRAD_AT_ITER",
                                  int(get("fault_nan_grad_at_iter", -1))),
        nan_grad_count=_env_int("LGBM_TPU_FAULT_NAN_GRAD_COUNT", 8),
        flip_score_rank=_env_rank_iter(
            "LGBM_TPU_FAULT_FLIP_SCORE_RANK",
            get("fault_flip_score_rank", "")),
        nan_hist_at_iter=_env_int("LGBM_TPU_FAULT_NAN_HIST_AT_ITER",
                                  int(get("fault_nan_hist_at_iter", -1))),
        oom_at_iter=_env_int("LGBM_TPU_FAULT_OOM_AT_ITER",
                             int(get("fault_oom_at_iter", -1))),
        oom_count=_env_int("LGBM_TPU_FAULT_OOM_COUNT",
                           int(get("fault_oom_count", 1))),
        corrupt_checkpoint=(
            # env, when set, OVERRIDES the param (in both directions, like
            # the integer faults): "1" arms, anything else disarms
            os.environ["LGBM_TPU_FAULT_CORRUPT_CHECKPOINT"] == "1"
            if "LGBM_TPU_FAULT_CORRUPT_CHECKPOINT" in os.environ
            else bool(get("fault_corrupt_checkpoint", False))),
    )
    if (plan.kill_at_iter < 0 and plan.hang_at_iter < 0
            and plan.kill_rank_at_iter is None
            and plan.hang_rank_at_iter is None
            and plan.kill_in_ckpt_write < 0
            and plan.kill_in_shard_write is None
            and plan.corrupt_shard < 0
            and plan.nan_grad_at_iter < 0
            and plan.flip_score_rank is None
            and plan.nan_hist_at_iter < 0
            and plan.oom_at_iter < 0
            and not plan.corrupt_checkpoint):
        return None
    return plan


def _process_rank() -> int:
    from .. import distributed
    return distributed.jax_rank()


def _hard_exit(context: str) -> None:
    """``os._exit`` skips atexit/finally so nothing gets the chance to
    'finish' a write (the SIGKILL shape a preempted worker actually sees).

    One deliberate exception: the flight recorder flushes first. A real
    SIGKILL cannot flush anything — for that shape, durable-dir runs
    rely on the recorder's periodic flush — but the harness kill is the
    TESTABLE stand-in for preemption, and the whole point of the
    post-mortem ring is that a killed gang leaves one; the flush is a
    single atomic file write, so it cannot 'finish' any in-flight
    checkpoint the way skipping atexit is meant to prevent."""
    try:
        from .. import telemetry
        telemetry.flush_recorder(f"fault-kill {context}")
    except Exception:
        pass
    sys.stderr.write(f"[faults] killing process {context}\n")
    sys.stderr.flush()
    os._exit(_KILL_EXIT_CODE)


def maybe_kill(plan: Optional[FaultPlan], iteration: int) -> None:
    """Hard-exit at the armed iteration (optionally rank-targeted)."""
    if plan is None:
        return
    if plan.kill_at_iter == iteration:
        _hard_exit(f"at iteration {iteration}")
    if plan.kill_rank_at_iter is not None \
            and plan.kill_rank_at_iter[1] == iteration \
            and plan.kill_rank_at_iter[0] == _process_rank():
        _hard_exit(f"(rank {plan.kill_rank_at_iter[0]}) at iteration "
                   f"{iteration}")


def maybe_hang(plan: Optional[FaultPlan], iteration: int) -> None:
    """Hang forever at the armed iteration (optionally rank-targeted) in an
    INTERRUPTIBLE short-sleep loop: the loop re-enters Python bytecode
    every tick, so the watchdog's asynchronous DistributedTimeoutError can
    land, and a supervisor SIGTERM still kills the process."""
    if plan is None:
        return
    hang = plan.hang_at_iter == iteration
    if not hang and plan.hang_rank_at_iter is not None \
            and plan.hang_rank_at_iter[1] == iteration:
        hang = plan.hang_rank_at_iter[0] == _process_rank()
    if not hang:
        return
    sys.stderr.write(f"[faults] hanging rank {_process_rank()} at "
                     f"iteration {iteration}\n")
    sys.stderr.flush()
    while True:
        time.sleep(0.05)


def maybe_kill_in_ckpt_write(plan: Optional[FaultPlan],
                             iteration: int) -> None:
    """Kill the checkpoint WRITER between the payload writes and the
    manifest write — the mid-write crash the manifest-last protocol and the
    .tmp staging directory must make harmless."""
    if plan is not None and plan.kill_in_ckpt_write == iteration:
        _hard_exit(f"inside checkpoint write for iteration {iteration}")


def maybe_nan_grad(plan: Optional[FaultPlan], iteration: int, g, h):
    """Overwrite the first ``nan_grad_count`` gradient entries with NaN at
    the armed iteration (returns possibly-modified (g, h))."""
    if plan is None or plan.nan_grad_at_iter != iteration:
        return g, h
    import jax.numpy as jnp
    n = min(plan.nan_grad_count, g.shape[0])
    flat = g.reshape(-1)
    flat = flat.at[:n].set(jnp.nan)
    return flat.reshape(g.shape), h


def corrupt_file(path: str, offset: Optional[int] = None,
                 nbytes: int = 16, truncate: bool = False) -> None:
    """Damage a file in place: XOR-flip ``nbytes`` at ``offset`` (middle of
    the file by default), or truncate it there. Shared by the
    corrupt-checkpoint injection point and the tests."""
    size = os.path.getsize(path)
    if offset is None:
        offset = size // 2
    offset = max(0, min(offset, max(size - 1, 0)))
    if truncate:
        with open(path, "r+b") as fh:
            fh.truncate(offset)
        return
    with open(path, "r+b") as fh:
        fh.seek(offset)
        chunk = fh.read(nbytes)
        fh.seek(offset)
        fh.write(bytes(b ^ 0xA5 for b in chunk))


def maybe_corrupt_checkpoint(plan: Optional[FaultPlan], path: str) -> None:
    """Corruption injection point the checkpoint writer calls after a
    successful save (damages the payload but leaves the manifest intact,
    so only checksum validation can catch it)."""
    if plan is not None and plan.corrupt_checkpoint:
        corrupt_file(path)


def maybe_kill_in_shard_write(plan: Optional[FaultPlan],
                              iteration: int) -> None:
    """Kill rank r between writing its score-cache shard into the staging
    directory and the shard-metadata exchange — mid-protocol death of ONE
    participant in the sharded checkpoint write. The manifest never lands,
    so the stale ``ckpt_N.tmp`` must be ignored by readers and reclaimed
    by the next write."""
    if plan is None or plan.kill_in_shard_write is None:
        return
    if plan.kill_in_shard_write[1] == iteration \
            and plan.kill_in_shard_write[0] == _process_rank():
        _hard_exit(f"(rank {plan.kill_in_shard_write[0]}) inside sharded "
                   f"checkpoint write for iteration {iteration}")


def maybe_corrupt_shard(plan: Optional[FaultPlan], path: str,
                        rank: int) -> None:
    """Corrupt ONE rank's published shard file (manifest intact): only the
    per-shard sha256 in MANIFEST.json can catch it, and the checkpoint
    must then be treated as invalid by the prune/fallback logic."""
    if plan is not None and plan.corrupt_shard == rank:
        corrupt_file(path)


def maybe_flip_score(plan: Optional[FaultPlan], iteration: int, score):
    """Flip ONE bit (the lowest mantissa bit of element 0) of the armed
    rank's train-score cache after iteration ``iteration`` completes —
    the silent single-bit corruption the cross-rank divergence check must
    attribute to exactly this rank. Returns the corrupted score array, or
    None when the fault is not armed for (this rank, this iteration).
    Involutory: applying it twice restores the original bits (the tests
    use that to verify exactly one bit moved)."""
    if plan is None or plan.flip_score_rank is None:
        return None
    if plan.flip_score_rank[1] != iteration \
            or plan.flip_score_rank[0] != _process_rank():
        return None
    import jax.numpy as jnp
    import numpy as np
    arr = np.array(np.asarray(score, np.float32), copy=True)
    flat = arr.reshape(-1).view(np.uint32)
    flat[0] ^= np.uint32(1)
    sys.stderr.write(f"[faults] flipping one score-cache bit on rank "
                     f"{_process_rank()} after iteration {iteration}\n")
    sys.stderr.flush()
    return jnp.asarray(arr)


def nan_hist_iter(plan: Optional[FaultPlan]) -> int:
    """The iteration armed for the IN-PROGRAM NaN injection (-1 = off).
    The fused step closes over this as a STATIC so the disarmed program is
    byte-identical to a fault-free trace; the armed program compares the
    traced iteration operand against it (models/gbdt.py _fused_step_fn)."""
    return plan.nan_hist_at_iter if plan is not None else -1


def maybe_nan_hist(plan: Optional[FaultPlan], iteration: int, g, h):
    """Host-path twin of the in-program NaN injection: poison ONE gradient
    value at the armed iteration (the unfused spelling of what
    nan_hist_iter injects inside the fused program). Returns (g, h)."""
    if plan is None or plan.nan_hist_at_iter != iteration:
        return g, h
    import jax.numpy as jnp
    flat = g.reshape(-1).at[0].set(jnp.nan)
    return flat.reshape(g.shape), h


class SimulatedResourceExhausted(RuntimeError):
    """Stands in for the backend's RESOURCE_EXHAUSTED XlaRuntimeError so
    the OOM degradation ladder is exercisable on any host. The message
    carries the literal token ``is_resource_exhausted`` matches on."""


def maybe_oom(plan: Optional[FaultPlan], iteration: int) -> None:
    """Raise a simulated RESOURCE_EXHAUSTED from the boosting step at the
    armed iteration, ``oom_count`` consecutive times (the plan's counter
    decrements per raise) — each raise drives the degradation ladder
    down one rung before the step is retried."""
    if plan is None or plan.oom_at_iter != iteration or plan.oom_count <= 0:
        return
    plan.oom_count -= 1
    raise SimulatedResourceExhausted(
        f"RESOURCE_EXHAUSTED: simulated histogram allocation failure at "
        f"iteration {iteration} ({plan.oom_count} more armed)")


def is_resource_exhausted(exc: BaseException) -> bool:
    """Whether an exception is an out-of-device-memory failure: the
    backend's RESOURCE_EXHAUSTED XlaRuntimeError (compile-time VMEM/HBM
    exhaustion and runtime allocation failures both carry the token), or
    the fault harness's simulated stand-in. The classifier the OOM
    degradation ladder gates on — it must never match unrelated errors,
    so the match is on the specific allocator phrasings only."""
    if isinstance(exc, SimulatedResourceExhausted):
        return True
    text = f"{type(exc).__name__}: {exc}"
    return ("RESOURCE_EXHAUSTED" in text
            or "Out of memory" in text
            or "Resource exhausted" in text)


# ------------------------------------------------------------ serve faults
# Serve-side injection points (see lightgbm_tpu/serving.py). Unlike the
# training faults these are re-read on EVERY predict dispatch (a fresh
# tiny plan per call, two env lookups + two attribute reads when
# disarmed), because serve tests arm/disarm them around individual
# requests without rebuilding the booster.

@dataclass
class ServeFaults:
    slow_predict_ms: float = 0.0   # sleep inside every predict dispatch
    oom_predicts: int = 0          # simulated OOMs to raise, process-wide


# predict-OOM raises fired so far in this process: the budget lives HERE
# (module state) rather than on the plan, because a fresh plan is built
# per predict call — a per-plan counter would re-arm on every ladder
# retry and loop the rescue forever. Check-and-increment runs under a
# lock: concurrent serve dispatches must not both pass the budget check
# and burn two ladder rungs for a budget of one.
_predict_oom_fired = 0
_predict_oom_lock = threading.Lock()


def serve_faults(config=None) -> Optional[ServeFaults]:
    """Build the active serve-side fault plan from config fields
    overridden by the LGBM_TPU_FAULT_* environment; None when nothing is
    armed (the common case — kept to two env reads)."""
    get = (lambda k, d: getattr(config, k, d)) if config is not None \
        else (lambda k, d: d)
    slow = _env_float("LGBM_TPU_FAULT_SLOW_PREDICT_MS",
                      float(get("fault_slow_predict_ms", 0.0)))
    ooms = _env_int("LGBM_TPU_FAULT_OOM_AT_PREDICT",
                    int(get("fault_oom_at_predict", 0)))
    if slow <= 0 and ooms <= 0:
        return None
    return ServeFaults(slow_predict_ms=slow, oom_predicts=ooms)


def maybe_slow_predict(sf: Optional[ServeFaults]) -> None:
    """Delay inside the predict dispatch path — forces requests past
    their deadlines and backs the queue up into admission control."""
    if sf is not None and sf.slow_predict_ms > 0:
        time.sleep(sf.slow_predict_ms / 1e3)


def maybe_oom_predict(sf: Optional[ServeFaults]) -> None:
    """Raise a simulated RESOURCE_EXHAUSTED from the predict dispatch
    while the armed budget has raises left (process-wide fired counter,
    see _predict_oom_fired) — each raise drives the predict-chunk
    degradation rung once before the call is retried."""
    global _predict_oom_fired
    if sf is None or sf.oom_predicts <= 0:
        return
    with _predict_oom_lock:
        if _predict_oom_fired >= sf.oom_predicts:
            return
        _predict_oom_fired += 1
        left = sf.oom_predicts - _predict_oom_fired
    raise SimulatedResourceExhausted(
        f"RESOURCE_EXHAUSTED: simulated predict allocation failure "
        f"({left} more armed)")


def reset_predict_oom() -> None:
    """Re-arm the predict-OOM budget (tests call this between scenarios)."""
    global _predict_oom_fired
    _predict_oom_fired = 0


def next_predict_chunk(exc: BaseException, cur: int,
                       hist_oom_fallback: bool = True) -> Optional[int]:
    """Predict-OOM ladder arithmetic, shared by GBDT and LoadedGBDT
    (`_maybe_degrade_predict_oom` in models/gbdt.py and io/model_text.py
    — ONE place owns the start/floor/halving so the two rungs cannot
    drift): the halved chunk to retry with, or None when the rung must
    not fire (gate off, not RESOURCE_EXHAUSTED, or the 16k-row floor is
    already reached — the caller then re-raises)."""
    if not hist_oom_fallback or not is_resource_exhausted(exc):
        return None
    cur = cur or (1 << 22)
    if cur <= (1 << 14):
        return None
    return max(1 << 14, cur // 2)


def maybe_fail_spawn(rank: int) -> None:
    """Spawn-failure injection point, called at the very top of spawned
    children (before jax/distributed bootstrap, so it is env-driven only):
    exits with SPAWN_FAIL_EXIT_CODE so the supervisor classifies the rank
    as permanently lost and shrinks the gang."""
    v = os.environ.get("LGBM_TPU_FAULT_SPAWN_FAIL_RANK", "")
    if not v:
        return
    try:
        target = int(v)
    except ValueError:
        sys.stderr.write(f"[faults] ignoring malformed "
                         f"LGBM_TPU_FAULT_SPAWN_FAIL_RANK={v!r}\n")
        return
    if target == rank:
        from .. import distributed
        sys.stderr.write(f"[faults] failing spawn of rank {rank}\n")
        sys.stderr.flush()
        os._exit(distributed.SPAWN_FAIL_EXIT_CODE)
