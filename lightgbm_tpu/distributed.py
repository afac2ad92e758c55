"""Multi-host bootstrap: the analog of the reference's distributed init.

The reference bootstraps its socket/MPI mesh from ``machines`` +
``local_listen_port`` + ``num_machines`` (reference:
src/network/linkers_socket.cpp:24-63 parse machine list, identify own rank
by local-IP match :38, bind + full-mesh handshake;
src/application/application.cpp:167-178 CLI init; Dask injects the same
params per worker, python-package/lightgbm/dask.py:211-330).

On TPU the entire linker layer collapses into ``jax.distributed.initialize``:
after it, every process sees the GLOBAL device set, `jax.devices()` spans
all hosts, and the same shard_map programs the single-host learners run
scale over ICI/DCN with zero further changes — collectives are compiled
into the program, so there is no rank-tagged socket protocol to speak.

Usage (one call per process, before constructing any Booster):

    import lightgbm_tpu as lgb
    lgb.distributed.init()                       # env-based (TPU pods)
    # or explicitly, the reference's machine-list style:
    lgb.distributed.init(machines="10.0.0.1:12400,10.0.0.2:12400")
    # or from a config/params dict holding machines/num_machines:
    lgb.distributed.init(params={"machines": "...", "num_machines": 2})

Rank resolution mirrors linkers_socket.cpp:38: if ``process_id`` is not
given, the local host's addresses are matched against the machine list.
On managed TPU pods (GKE/Cloud TPU), call ``init()`` with no arguments —
JAX's cluster autodetection fills coordinator/rank from the environment.
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time
from typing import Dict, List, Optional

from .utils import log

_initialized = False

# exit code a supervised rank uses when its collective watchdog fires —
# distinct from the fault harness's 137 kill so the supervisor can tell
# "rank died" from "rank declared the gang stalled"
WATCHDOG_EXIT_CODE = 97

# exit code a spawned child uses when it could not even come up (spawn/
# bootstrap failure before distributed init) — the supervisor classifies
# the rank as PERMANENTLY lost and shrinks the gang instead of burning
# same-size restarts on a machine that cannot start
SPAWN_FAIL_EXIT_CODE = 96

# exit code a supervised rank uses when the cross-rank integrity check
# (check_model_integrity) identifies IT as the minority whose model state
# silently diverged from the gang: the supervisor charges the corrupt
# rank's restart budget (like a hard kill — the rank's state is bad by
# majority evidence) and restarts the gang from the last valid checkpoint,
# or shrinks the rank away once the budget is exhausted
DIVERGENCE_EXIT_CODE = 95


def is_initialized() -> bool:
    return _initialized or _jax_already_initialized()


def _jax_already_initialized() -> bool:
    """True when jax.distributed was initialized (by us or externally)."""
    import jax
    return bool(jax.distributed.is_initialized())


def _local_addresses() -> set:
    addrs = {"127.0.0.1", "::1", "localhost", "0.0.0.0"}
    try:
        hostname = socket.gethostname()
        addrs.add(hostname)
        for info in socket.getaddrinfo(hostname, None):
            addrs.add(info[4][0])
    except OSError:
        pass
    # primary interface IP: a connected UDP socket reveals the address the
    # kernel would route from (no packet is sent)
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.connect(("10.255.255.255", 1))
        addrs.add(s.getsockname()[0])
        s.close()
    except OSError:
        pass
    return addrs


def _split_host_port(entry: str):
    """host[:port] -> (host, port-str|None); handles [v6]:port and bare
    IPv6 (which must not be split at its last hextet)."""
    if entry.startswith("["):
        host, _, rest = entry[1:].partition("]")
        return host, (rest[1:] if rest.startswith(":") else None)
    if entry.count(":") > 1:
        return entry, None        # bare IPv6
    host, _, port = entry.partition(":")
    return host, (port or None)


def _entry_matches_local(host: str, local: set) -> bool:
    if host in local:
        return True
    # the reference compares RESOLVED addresses (linkers_socket.cpp:38):
    # a machines entry may be an interface IP or FQDN that plain hostname
    # probing never surfaces
    try:
        for info in socket.getaddrinfo(host, None):
            if info[4][0] in local:
                return True
    except OSError:
        pass
    return False


def _rank_from_machines(machines: list,
                        listen_port: Optional[int] = None) -> Optional[int]:
    """Identify this process's rank by local-IP match (the reference's
    protocol, linkers_socket.cpp:38). With several processes on one host,
    ``listen_port`` (the reference's local_listen_port) disambiguates by
    exact host:port match; an ambiguous match without it is fatal rather
    than silently rank 0."""
    local = _local_addresses()
    parsed = [_split_host_port(m) for m in machines]
    matches = [i for i, (host, _port) in enumerate(parsed)
               if _entry_matches_local(host, local)]
    if listen_port is not None:
        exact = [i for i in matches
                 if parsed[i][1] == str(listen_port)]
        if len(exact) == 1:
            return exact[0]
    if len(matches) > 1:
        log.fatal(f"multiple machines entries match this host "
                  f"({[machines[i] for i in matches]}); set "
                  f"local_listen_port or process_id to disambiguate")
    return matches[0] if matches else None


def init(machines: Optional[str] = None,
         num_machines: Optional[int] = None,
         process_id: Optional[int] = None,
         coordinator_address: Optional[str] = None,
         params: Optional[dict] = None,
         local_device_ids=None,
         connect_retries: int = 5,
         connect_backoff: float = 1.0,
         connect_timeout: Optional[float] = None) -> None:
    """Initialize multi-host training (idempotent).

    Args:
      machines: comma-separated "host:port,host:port,..." — the reference's
        ``machines`` parameter (config.h:989). The FIRST entry is the
        coordinator.
      num_machines: process count; defaults to len(machines).
      process_id: this process's rank; default: local-IP match against the
        machine list (linkers_socket.cpp:38) or the JAX env autodetection.
      coordinator_address: overrides the coordinator (host:port).
      params: a params/config mapping — ``machines``/``num_machines``/
        ``local_listen_port``/``time_out`` are read from it when the
        explicit args are absent (so CLI configs written for the reference
        work unchanged).
      local_device_ids: forwarded to ``jax.distributed.initialize``.
      connect_retries: attempts to reach the coordinator before giving up
        (a slow-starting rank 0 must not fail the whole cluster — the
        reference's socket linker retries its connect the same way,
        linkers_socket.cpp TryBind/Connect loops).
      connect_backoff: initial retry delay in seconds; doubles per attempt
        (capped at 30s).
      connect_timeout: overall deadline in seconds across retries
        (defaults to the ``time_out`` parameter when given via params).
    """
    global _initialized
    if _initialized:
        log.warning("distributed.init called twice; ignoring")
        return
    import jax
    if _jax_already_initialized():
        # standard JAX practice initializes jax.distributed once at process
        # startup; treat that as ours rather than crashing on re-init
        log.info("jax.distributed already initialized externally; adopting")
        _initialized = True
        return

    listen_port = None
    if params:
        get = params.get if hasattr(params, "get") else \
            lambda k, d=None: getattr(params, k, d)
        machines = machines or get("machines") or None
        num_machines = num_machines or int(get("num_machines") or 0) or None
        lp = get("local_listen_port")
        listen_port = int(lp) if lp else None
        if connect_timeout is None:
            to = get("time_out")
            connect_timeout = float(to) if to else None

    mlist = [m.strip() for m in machines.split(",") if m.strip()] \
        if machines else []
    if mlist:
        if num_machines is None:
            num_machines = len(mlist)
        if coordinator_address is None:
            coordinator_address = mlist[0]
        if process_id is None:
            process_id = _rank_from_machines(mlist, listen_port)
            if process_id is None:
                log.fatal(f"none of this host's addresses match the "
                          f"machines list {mlist} (set process_id "
                          f"explicitly)")

    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_machines is not None:
        kwargs["num_processes"] = num_machines
    if process_id is not None:
        kwargs["process_id"] = process_id
    if local_device_ids is not None:
        kwargs["local_device_ids"] = local_device_ids
    _initialize_with_backoff(kwargs, connect_retries, connect_backoff,
                             connect_timeout)
    _initialized = True
    log.info(f"distributed: process {jax.process_index()} of "
             f"{jax.process_count()}, {len(jax.devices())} global devices")


def _initialize_with_backoff(kwargs: dict, retries: int, backoff: float,
                             timeout: Optional[float]) -> None:
    """``jax.distributed.initialize`` under bounded exponential backoff: a
    coordinator (rank 0) that is still starting up must not fail the
    cluster; a coordinator that never comes up must fail with an error
    naming the address that was unreachable."""
    import time
    import jax
    attempts = max(1, int(retries))
    delay = max(0.0, float(backoff))
    deadline = (time.monotonic() + timeout) if timeout else None
    for attempt in range(1, attempts + 1):
        try:
            jax.distributed.initialize(**kwargs)
            return
        except (ValueError, TypeError):
            # configuration errors (malformed address, bad argument
            # combinations) are permanent: fail fast, don't sleep on them
            raise
        except Exception as e:  # jax raises backend-specific error types
            out_of_time = deadline is not None \
                and time.monotonic() + delay > deadline
            if attempt >= attempts or out_of_time:
                addr = kwargs.get("coordinator_address") \
                    or os.environ.get("JAX_COORDINATOR_ADDRESS") \
                    or "<env-autodetected coordinator>"
                log.fatal(
                    f"could not connect to the distributed coordinator at "
                    f"{addr} after {attempt} attempt(s)"
                    + (f" within {timeout:g}s" if out_of_time else "")
                    + f": {e}")
            log.warning(f"coordinator connect attempt {attempt}/{attempts} "
                        f"failed ({e}); retrying in {delay:.1f}s")
            time.sleep(delay)
            delay = min(max(delay, 0.1) * 2, 30.0)


def barrier(name: str = "barrier", timeout: Optional[float] = None) -> None:
    """Cross-process synchronization point (no-op single-process). Used by
    the checkpoint writer so no rank races past a checkpoint another rank
    may later resume from.

    Prefers the distributed COORDINATION-SERVICE barrier (pure gRPC — no
    XLA computation, so it works on every backend and takes a hard
    deadline, the analog of the reference's socket ``time_out``,
    linkers_socket.cpp TimeOut) over ``sync_global_devices`` (a
    device collective). With a ``collective_deadline`` watchdog armed, the
    barrier inherits its deadline: a peer that died or hung before
    reaching the barrier surfaces as a DistributedTimeoutError (or a
    supervised watchdog exit) naming the suspects instead of an
    indefinite wait."""
    import jax
    if jax.process_count() <= 1:
        return
    wd = _active_health.watchdog if _active_health is not None else None
    if timeout is None and wd is not None:
        timeout = wd.deadline
    client = None
    try:
        from jax._src import distributed as jax_dist
        client = jax_dist.global_state.client
    except Exception:
        pass
    with watchdog_phase(f"barrier:{name}"):
        if client is not None:
            try:
                client.wait_at_barrier(
                    f"lgbm_tpu_{name}",
                    int((timeout or 3600.0) * 1000))
                return
            except DistributedTimeoutError:
                raise
            except Exception as e:
                # the coordination client's error type varies by jax
                # version: classify timeouts by message
                msg = str(e)
                if "DEADLINE_EXCEEDED" in msg or "imed out" in msg \
                        or "BarrierTimedOut" in msg:
                    _barrier_timed_out(name, wd, e)
                raise
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)


def _coordination_client():
    """The jax distributed coordination-service client (pure gRPC — works
    on every backend, including this container's CPU backend that cannot
    run cross-process XLA computations); None single-process or when jax
    exposes no client."""
    import jax
    if jax.process_count() <= 1:
        return None
    try:
        from jax._src import distributed as jax_dist
        return jax_dist.global_state.client
    except Exception:
        return None


_exchange_seq = 0


def exchange_host(tag: str, payload: str,
                  timeout: Optional[float] = None) -> List[str]:
    """Allgather a SMALL host-side string across processes, returning the
    per-rank payloads in rank order. This is the swappable collective
    floor the sharded-checkpoint protocol stands on: it prefers the
    coordination-service key-value store (pure gRPC, like ``barrier``), so
    it works even where cross-process XLA collectives don't (this
    container's CPU backend), and falls back to
    ``multihost_utils.process_allgather`` on clusters without a
    coordination client. Single-process: returns ``[payload]``.

    Callers must invoke it in lockstep on every rank with the same
    ``tag`` (keys are sequence-numbered per process, so lockstep keeps
    them agreed). Payloads should stay small (shard metadata, row counts
    — not data)."""
    global _exchange_seq
    import jax
    nproc = jax.process_count()
    if nproc <= 1:
        return [payload]
    rank = jax.process_index()
    client = _coordination_client()
    wd = _active_health.watchdog if _active_health is not None else None
    if timeout is None:
        timeout = wd.deadline if wd is not None else 600.0
    with watchdog_phase(f"exchange:{tag}"):
        if client is not None:
            _exchange_seq += 1
            prefix = f"lgbm_tpu_xchg/{tag}/{_exchange_seq}"
            client.key_value_set(f"{prefix}/r{rank}", payload)
            out = []
            for r in range(nproc):
                out.append(client.blocking_key_value_get(
                    f"{prefix}/r{r}", int(timeout * 1000)))
            # NO cleanup: deleting a key here races peers that have not
            # read it yet (their blocking get would then wait out the full
            # timeout and fail a healthy gang). Keys are sequence-
            # namespaced and the KV store lives only as long as the gang's
            # coordination service, so the leak is bounded and harmless.
            return out
        # no coordination client: fall back to an XLA-level allgather of
        # the utf-8 bytes padded to the max length
        import numpy as np
        from jax.experimental import multihost_utils
        raw = payload.encode()
        ln = np.asarray([len(raw)], np.int32)
        lens = np.asarray(multihost_utils.process_allgather(ln)).reshape(-1)
        width = max(1, int(lens.max()))
        buf = np.zeros((width,), np.uint8)
        buf[:len(raw)] = np.frombuffer(raw, np.uint8)
        gathered = np.asarray(
            multihost_utils.process_allgather(buf)).reshape(nproc, width)
        return [bytes(gathered[r, :int(lens[r])].tobytes()).decode()
                for r in range(nproc)]


def repartition_rows(old_ranges, row_start: int, row_count: int,
                     fetch_shard):
    """Reassemble one rank's row slice ``[row_start, row_start+row_count)``
    of a globally row-partitioned array from shards written under a
    DIFFERENT (or the same) partition — the load half of resume-at-a-
    different-world-size.

    Args:
      old_ranges: per-old-rank ``(row_start, row_count)`` pairs in rank
        order, tiling ``[0, sum(counts))`` contiguously.
      row_start, row_count: the slice the calling rank needs under the NEW
        partition.
      fetch_shard: ``fetch_shard(old_rank) -> np.ndarray`` returning that
        old rank's shard array (rows first). Called ONLY for old shards
        that overlap the requested slice, so a same-partition resume
        touches exactly its own shard.

    Returns the concatenated rows (np.ndarray), bit-identical to the
    original global array's slice — re-partitioning is pure row movement,
    so resume at any world size starts from the exact same per-row state.
    Raises ValueError when the old ranges do not tile the requested slice.
    """
    import numpy as np
    lo, hi = int(row_start), int(row_start) + int(row_count)
    if row_count == 0:
        # preserve trailing dims + dtype (multiclass caches are [n, k]):
        # an empty slice must still merge cleanly with non-empty peers
        if old_ranges:
            return fetch_shard(0)[:0]
        return np.zeros((0,), np.float32)
    pieces = []
    covered = lo
    for old_rank, (s, c) in enumerate(old_ranges):
        s, e = int(s), int(s) + int(c)
        if e <= lo or s >= hi:
            continue
        a, b = max(s, lo), min(e, hi)
        if a != covered:
            raise ValueError(
                f"shard ranges do not tile rows [{lo}, {hi}): gap at row "
                f"{covered} (old rank {old_rank} covers [{s}, {e}))")
        shard = fetch_shard(old_rank)
        if shard.shape[0] != c:
            raise ValueError(
                f"shard for old rank {old_rank} has {shard.shape[0]} rows, "
                f"its recorded partition says {c}")
        pieces.append(shard[a - s:b - s])
        covered = b
    if covered != hi:
        raise ValueError(
            f"shard ranges do not tile rows [{lo}, {hi}): rows "
            f"[{covered}, {hi}) are not covered by any shard")
    return pieces[0] if len(pieces) == 1 else np.concatenate(pieces, axis=0)


def _barrier_timed_out(name: str, wd, cause) -> None:
    """A deadlined barrier expired: some peer never arrived. Route through
    the watchdog's diagnosis when one is armed (supervised ranks exit for
    the gang supervisor); otherwise raise a diagnosable error directly."""
    global _last_diagnosis
    snap = dict(_progress.snapshot(), phase=f"barrier:{name}")
    if wd is not None:
        if wd.supervised:
            wd._fire(snap)            # writes diagnosis, then os._exit
        _last_diagnosis = wd._diagnose(snap)
    else:
        _last_diagnosis = {"rank": 0, "iteration": snap["iter"],
                           "phase": snap["phase"], "suspects": None}
    raise DistributedTimeoutError() from cause


# ===================================================== training supervision
# Heartbeat + collective-deadline watchdog: the detection half of the gang
# supervisor (lightgbm_tpu/supervisor.py holds the restart half). The
# reference survives a dead worker through per-socket recv timeouts
# (linkers_socket.cpp TimeOut on every Recv); jax collectives have no such
# deadline — a killed or hung rank stalls every shard_map psum forever. The
# watchdog restores the reference's property: a bounded wait, then a
# DIAGNOSABLE error naming the suspect rank(s) and the last completed
# iteration.
#
#   - Every rank runs a heartbeat thread that reports
#     (rank, last-completed iteration, current in-step iteration) to rank 0
#     over a lightweight TCP side-channel (newline-JSON request/response;
#     the address comes from LGBM_TPU_HEARTBEAT_ADDR, set by the
#     supervisor, or an explicit start_health call). Rank 0's reply carries
#     the aggregated table, so EVERY rank can name suspects, not just 0.
#   - The watchdog thread checks the current phase (boosting step or
#     cross-process barrier) against ``collective_deadline``. On expiry it
#     writes a JSON diagnosis (LGBM_TPU_DIAG_DIR), then either hard-exits
#     with WATCHDOG_EXIT_CODE (supervised mode — the supervisor tears down
#     the gang and relaunches from the latest checkpoint) or raises
#     DistributedTimeoutError in the main thread.

_SUPERVISED_ENV = "LGBM_TPU_SUPERVISED"
_HEARTBEAT_ADDR_ENV = "LGBM_TPU_HEARTBEAT_ADDR"
_DIAG_DIR_ENV = "LGBM_TPU_DIAG_DIR"
_RESTART_COUNT_ENV = "LGBM_TPU_RESTART_COUNT"

_last_diagnosis: Optional[dict] = None


class DistributedTimeoutError(Exception):
    """A collective (boosting step or barrier) exceeded the configured
    ``collective_deadline``. Carries the diagnosing rank, the last
    completed iteration, and the suspect rank(s) the heartbeat table
    implicates. Constructed argument-free by the watchdog's asynchronous
    raise, in which case the message comes from the last diagnosis."""

    def __init__(self, *args, rank=None, iteration=None, suspects=None,
                 phase=None):
        diag = _last_diagnosis or {}
        self.rank = rank if rank is not None else diag.get("rank")
        self.iteration = iteration if iteration is not None \
            else diag.get("iteration")
        self.suspects = suspects if suspects is not None \
            else diag.get("suspects")
        self.phase = phase if phase is not None else diag.get("phase")
        if not args:
            args = (format_timeout_message(self.rank, self.iteration,
                                           self.suspects, self.phase,
                                           diag.get("deadline")),)
        super().__init__(*args)


def format_timeout_message(rank, iteration, suspects, phase,
                           deadline) -> str:
    if suspects:
        sus = "rank(s) " + ", ".join(str(s) for s in suspects)
    elif suspects is not None:
        sus = "none identified (heartbeat table shows all ranks current)"
    else:
        sus = "unknown rank (no heartbeat table)"
    return (f"collective deadline"
            + (f" ({deadline:g}s)" if deadline else "")
            + f" exceeded on rank {rank} in {phase or 'step'}: "
            f"last completed iteration {iteration}; suspect {sus}. "
            f"The gang is stalled — restart it from the latest checkpoint "
            f"(lightgbm_tpu.supervisor does this automatically).")


class _Progress:
    """Per-process training progress the heartbeat reports and the
    watchdog judges against: a stack of active phases (step / barrier)
    plus the last COMPLETED boosting iteration."""

    def __init__(self):
        self.lock = threading.Lock()
        self.last_iter = -1            # last completed boosting iteration
        self.step_iter = -1            # iteration currently inside a step
        self.steps_done = 0            # steps completed IN THIS PROCESS —
        #   the compile-exemption clock: last_iter is the GLOBAL iteration
        #   and starts at k on a resumed incarnation, which would strip
        #   the fresh process's first-step/first-eval compile exemptions
        self.phases = []               # [(label, start_monotonic)]
        self.last_transition = None    # monotonic time of last begin/end

    def reset(self) -> None:
        """Fresh training run: clear completed-iteration history so the
        first-step compile exemption applies again."""
        with self.lock:
            self.last_iter = -1
            self.step_iter = -1
            self.steps_done = 0
            self.phases = []
            self.last_transition = None

    def begin(self, label: str, iteration: Optional[int] = None) -> None:
        with self.lock:
            now = time.monotonic()
            self.phases.append((label, now))
            self.last_transition = now
            if iteration is not None:
                self.step_iter = iteration

    def end(self, iteration: Optional[int] = None) -> None:
        with self.lock:
            if self.phases:
                self.phases.pop()
            self.last_transition = time.monotonic()
            if iteration is not None:
                if iteration > self.last_iter:
                    self.steps_done += 1
                self.last_iter = iteration
                if not self.phases:
                    self.step_iter = -1

    def snapshot(self) -> dict:
        with self.lock:
            now = time.monotonic()
            top = self.phases[-1] if self.phases else None
            return {"iter": self.last_iter, "step": self.step_iter,
                    "steps_done": self.steps_done,
                    "phase": top[0] if top else None,
                    "phase_elapsed": (now - top[1]) if top else 0.0,
                    "idle_elapsed": (now - self.last_transition)
                    if self.last_transition is not None else 0.0}


_progress = _Progress()


def notify_step_begin(iteration: int, label: str = "step") -> None:
    """Mark entry into boosting iteration ``iteration`` (the watchdog's
    clock starts; the heartbeat starts reporting it as in-flight)."""
    _progress.begin(f"{label}:{iteration}", iteration)


def notify_step_end(iteration: int) -> None:
    """Mark completion of boosting iteration ``iteration``."""
    _progress.end(iteration)


def notify_step_retry(iteration: int) -> None:
    """Re-arm the step clock for a RETRIED iteration (the OOM degradation
    ladder): the failed attempt's elapsed time must not be charged to the
    retry, and the retry recompiles the degraded programs — so it gets the
    same compile exemption as a first step (the watchdog skips
    ``step-retry:`` phases; degradation is single-process only, so no peer
    is left waiting on an exempted collective). Counters are untouched:
    the iteration did not complete."""
    _progress.end()
    _progress.begin(f"step-retry:{iteration}", iteration)


class watchdog_phase:
    """Context manager marking a non-step collective phase (barriers,
    allgathers) so the watchdog times it too. Reentrant; no-op overhead
    when no watchdog is armed (the progress stack is a few list ops)."""

    def __init__(self, label: str):
        self.label = label

    def __enter__(self):
        _progress.begin(self.label)
        return self

    def __exit__(self, *exc):
        _progress.end()
        return False


class HeartbeatMonitor:
    """Rank liveness over a TCP side-channel.

    Rank 0 runs the aggregation server; every rank (0 included) feeds its
    progress in every ``interval`` seconds and receives the aggregated
    table back. The table maps rank -> {iter, step, age} where ``age`` is
    seconds since that rank's last report reached rank 0."""

    def __init__(self, rank: int, nproc: int, addr: str,
                 interval: float = 5.0):
        self.rank = int(rank)
        self.nproc = int(nproc)
        host, _, port = addr.rpartition(":")
        self.addr = (host or "127.0.0.1", int(port))
        self.interval = max(0.2, float(interval))
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._server_table: Dict[int, dict] = {}   # rank0: rank -> report
        self._table: Dict[int, dict] = {}          # last aggregated view
        self._threads = []
        self._server_sock = None

    # ------------------------------------------------------------- server
    def _serve(self) -> None:
        srv = self._server_sock
        srv.settimeout(0.5)
        while not self._stop.is_set():
            try:
                conn, _ = srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            t = threading.Thread(target=self._handle, args=(conn,),
                                 daemon=True, name="lgbm-hb-conn")
            t.start()
            self._threads.append(t)

    def _handle(self, conn) -> None:
        conn.settimeout(max(4 * self.interval, 10.0))
        try:
            fh = conn.makefile("rw", encoding="utf-8", newline="\n")
            for line in fh:
                try:
                    msg = json.loads(line)
                except ValueError:
                    continue
                now = time.monotonic()
                with self._lock:
                    self._server_table[int(msg.get("rank", -1))] = {
                        "iter": msg.get("iter", -1),
                        "step": msg.get("step", -1),
                        "recv": now}
                    reply = json.dumps({"table": self._aggregated()})
                fh.write(reply + "\n")
                fh.flush()
        except (OSError, ValueError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _aggregated(self) -> dict:
        # caller HOLDS self._lock (mutates and iterates the table)
        mine = _progress.snapshot()
        now = time.monotonic()
        self._server_table[self.rank] = {"iter": mine["iter"],
                                         "step": mine["step"], "recv": now}
        out = {str(r): {"iter": e["iter"], "step": e["step"],
                        "age": round(now - e["recv"], 3)}
               for r, e in self._server_table.items()}
        # mirror into the health gauges (telemetry snapshots / postmortems):
        # heartbeat age + last completed iteration per rank
        from .utils import profiling
        for r, e in out.items():
            profiling.set_gauge(f"heartbeat_age_rank{r}", e["age"])
            profiling.set_gauge(f"last_iter_rank{r}", e["iter"])
        return out

    # ------------------------------------------------------------- client
    def _beat(self) -> None:
        fh = None
        while not self._stop.is_set():
            if fh is None:
                try:
                    conn = socket.create_connection(self.addr, timeout=5.0)
                    conn.settimeout(max(4 * self.interval, 10.0))
                    fh = conn.makefile("rw", encoding="utf-8", newline="\n")
                except OSError:
                    self._stop.wait(self.interval)
                    continue
            mine = _progress.snapshot()
            try:
                fh.write(json.dumps({"rank": self.rank,
                                     "iter": mine["iter"],
                                     "step": mine["step"],
                                     "t": time.time()}) + "\n")
                fh.flush()
                reply = json.loads(fh.readline())
                with self._lock:
                    self._table = {int(r): dict(e) for r, e in
                                   reply.get("table", {}).items()}
            except (OSError, ValueError):
                try:
                    fh.close()
                except OSError:
                    pass
                fh = None
            self._stop.wait(self.interval)
        if fh is not None:
            try:
                fh.close()
            except OSError:
                pass

    # -------------------------------------------------------------- api
    def start(self) -> "HeartbeatMonitor":
        if self.rank == 0:
            srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            srv.bind(self.addr)
            srv.listen(max(self.nproc, 8))
            self._server_sock = srv
            t = threading.Thread(target=self._serve, daemon=True,
                                 name="lgbm-hb-server")
            t.start()
            self._threads.append(t)
        t = threading.Thread(target=self._beat, daemon=True,
                             name="lgbm-hb-client")
        t.start()
        self._threads.append(t)
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._server_sock is not None:
            try:
                self._server_sock.close()
            except OSError:
                pass

    def table(self) -> Dict[int, dict]:
        """Latest aggregated liveness table (rank -> iter/step/age)."""
        if self.rank == 0:
            with self._lock:
                return {int(r): dict(e)
                        for r, e in self._aggregated().items()}
        with self._lock:
            return {r: dict(e) for r, e in self._table.items()}

    def suspects(self, my_step: int, my_iter: int = -1) -> Optional[list]:
        """Ranks implicated in a stall: dead (stale heartbeat), missing
        (never reported), or lagging (their reported progress — completed
        iteration or in-flight step — is behind this rank's: the hung-rank
        signature, where the process is alive and its heartbeat fresh but
        it never dispatched the step everyone else is blocked in).
        Returns None (unknown) when the table is empty — an unreplied
        heartbeat must not masquerade as confident evidence implicating
        every rank including the caller."""
        table = self.table()
        if not table:
            return None
        out = set()
        stale_after = max(3 * self.interval, 5.0)
        my_progress = max(my_step, my_iter)
        for r in range(self.nproc):
            e = table.get(r)
            if e is None:
                out.add(r)
                continue
            progress = max(e.get("step", -1), e.get("iter", -1))
            if e.get("age", 0.0) > stale_after:
                out.add(r)
            elif my_progress >= 0 and progress < my_progress \
                    and r != self.rank:
                out.add(r)
        return sorted(out)


class CollectiveWatchdog:
    """Deadline monitor over the progress stack. ``deadline`` seconds after
    a phase (boosting step / barrier) begins without ending, the watchdog
    diagnoses the stall and terminates it — supervised ranks exit with
    WATCHDOG_EXIT_CODE for the gang supervisor to reap; unsupervised runs
    get a DistributedTimeoutError raised in the main thread."""

    def __init__(self, deadline: float, rank: int = 0,
                 heartbeat: Optional[HeartbeatMonitor] = None,
                 supervised: Optional[bool] = None,
                 diag_dir: Optional[str] = None):
        self.deadline = float(deadline)
        self.rank = int(rank)
        self.heartbeat = heartbeat
        self.supervised = (os.environ.get(_SUPERVISED_ENV) == "1"
                           if supervised is None else bool(supervised))
        self.diag_dir = diag_dir if diag_dir is not None \
            else os.environ.get(_DIAG_DIR_ENV)
        self._stop = threading.Event()
        self._fired = threading.Event()
        self._main_thread = threading.main_thread()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "CollectiveWatchdog":
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="lgbm-watchdog")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    def _run(self) -> None:
        tick = min(0.25, self.deadline / 4)
        while not self._stop.wait(tick):
            snap = _progress.snapshot()
            if snap["phase"] is None:
                # between steps: the training loop itself has gone quiet —
                # the HUNG rank's own signature (its peers see a stalled
                # step; it sees nothing moving). Judged only after TWO
                # steps completed IN THIS PROCESS: the first between-steps
                # interval holds the initial valid-set eval's jit compile,
                # which — like the first step's own compile — says nothing
                # about a stalled peer and must not kill a healthy gang
                # (in-process count, so resumed/relaunched incarnations
                # keep the exemption for THEIR first interval too).
                if snap["steps_done"] >= 2 \
                        and snap["idle_elapsed"] > self.deadline:
                    snap = dict(snap, phase="between-steps (host-side)")
                    self._fire(snap)
                    return
                continue
            # compile warm-up exemption: the FIRST boosting step THIS
            # PROCESS runs includes jit compilation, whose wall time has
            # nothing to do with a stalled collective — step phases are
            # judged only once one in-process step completed. Barriers and
            # other explicitly marked collective phases (no compile
            # inside) are always judged; a gang member dying before anyone
            # finishes its first step is caught by the supervisor's
            # incarnation timeout.
            if snap["phase"].startswith("step:") and snap["steps_done"] < 1:
                continue
            # an OOM-degraded retry recompiles the shrunk programs: same
            # rationale as the first-step exemption (and single-process by
            # construction — gangs fail-stop on OOM, so no stalled peer
            # hides behind this phase)
            if snap["phase"].startswith("step-retry:"):
                continue
            if snap["phase_elapsed"] > self.deadline:
                self._fire(snap)
                return

    def _diagnose(self, snap: dict) -> dict:
        suspects = None
        table = None
        if self.heartbeat is not None:
            try:
                suspects = self.heartbeat.suspects(snap["step"],
                                                   snap["iter"])
                table = {str(r): e for r, e in
                         self.heartbeat.table().items()}
            except Exception:
                pass
        return {"rank": self.rank, "iteration": snap["iter"],
                "stalled_iteration": snap["step"], "phase": snap["phase"],
                "elapsed": round(snap["phase_elapsed"], 3),
                "deadline": self.deadline, "suspects": suspects,
                "heartbeat_table": table,
                # wall + monotonic stamps: the post-mortem analyzer
                # orders this fire against OOM rungs and flight records
                "t": time.time(), "t_mono": time.monotonic(),
                "kind": "watchdog"}

    def _fire(self, snap: dict) -> None:
        global _last_diagnosis
        diag = self._diagnose(snap)
        _last_diagnosis = diag
        self._fired.set()
        msg = format_timeout_message(diag["rank"], diag["iteration"],
                                     diag["suspects"], diag["phase"],
                                     self.deadline)
        log.warning(f"watchdog: {msg}")
        # flush the flight recorder NOW (the training thread is stalled
        # inside the very collective being diagnosed) and embed its path
        # in the diagnosis: the supervisor report then references a
        # per-iteration post-mortem, not just the final stack state
        try:
            from . import telemetry
            diag["flight_recorder"] = telemetry.flush_recorder(
                f"watchdog: {msg}")
        except Exception:
            pass
        if self.diag_dir:
            try:
                os.makedirs(self.diag_dir, exist_ok=True)
                with open(os.path.join(
                        self.diag_dir,
                        f"watchdog_rank{self.rank}.json"), "w") as fh:
                    json.dump(diag, fh, indent=1)
            except OSError:
                pass
        if self.supervised:
            # a rank blocked inside a native collective cannot be unstuck
            # from Python: exit with the watchdog code and let the
            # supervisor tear down and relaunch the gang
            import sys
            sys.stderr.write(f"[watchdog] {msg}\n")
            sys.stderr.flush()
            os._exit(WATCHDOG_EXIT_CODE)
        # unsupervised: asynchronously raise in the main thread. This lands
        # as soon as the main thread runs Python bytecode again — it
        # un-sticks Python-level stalls (the fault harness's hang loop, a
        # slow host phase); a thread parked inside a native collective only
        # sees it on return, which is the best Python can do without a
        # supervisor process.
        import ctypes
        ctypes.pythonapi.PyThreadState_SetAsyncExc(
            ctypes.c_long(self._main_thread.ident),
            ctypes.py_object(DistributedTimeoutError))

    @property
    def fired(self) -> bool:
        return self._fired.is_set()


class _Health:
    """The per-training supervision bundle: optional heartbeat + optional
    watchdog, started together by engine.train and stopped in its
    finally."""

    def __init__(self, heartbeat, watchdog):
        self.heartbeat = heartbeat
        self.watchdog = watchdog

    def stop(self) -> None:
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.heartbeat is not None:
            self.heartbeat.stop()
        global _active_health
        if _active_health is self:
            _active_health = None


_active_health: Optional[_Health] = None


def start_health(config=None, heartbeat_addr: Optional[str] = None) -> _Health:
    """Start training supervision for this process from config:

    - a HeartbeatMonitor when ``heartbeat_interval`` > 0, this is a
      multi-process run, and a side-channel address is known (the
      LGBM_TPU_HEARTBEAT_ADDR env the supervisor sets, or
      ``heartbeat_addr``);
    - a CollectiveWatchdog when ``collective_deadline`` > 0.

    Idempotent per training run; returns a handle whose ``stop()`` the
    caller owns. With neither enabled the handle is inert."""
    global _active_health
    if _active_health is not None:
        return _Health(None, None)    # nested train(): inert handle
    import jax
    interval = float(getattr(config, "heartbeat_interval", 0.0) or 0.0)
    deadline = float(getattr(config, "collective_deadline", 0.0) or 0.0)
    addr = heartbeat_addr or os.environ.get(_HEARTBEAT_ADDR_ENV)
    try:
        rank, nproc = jax.process_index(), jax.process_count()
    except Exception:
        rank, nproc = 0, 1
    if interval > 0 or deadline > 0:
        _progress.reset()   # fresh run: first-step compile exemption anew
    heartbeat = None
    if interval > 0 and nproc > 1 and addr:
        try:
            heartbeat = HeartbeatMonitor(rank, nproc, addr,
                                         interval).start()
        except OSError as e:
            log.warning(f"heartbeat disabled: cannot reach side-channel "
                        f"{addr}: {e}")
    watchdog = None
    if deadline > 0:
        watchdog = CollectiveWatchdog(deadline, rank,
                                      heartbeat=heartbeat).start()
    health = _Health(heartbeat, watchdog)
    if heartbeat is not None or watchdog is not None:
        _active_health = health
    return health


def health_snapshot() -> dict:
    """Health telemetry for telemetry snapshots and checkpoint manifests:
    restart count (from the supervisor's env), this process's progress,
    the per-rank heartbeat table when a monitor is live, and every OOM
    degradation event this process stepped down (an operator reading a
    manifest can see a job is running DEGRADED rather than discovering it
    at the bill)."""
    snap = _progress.snapshot()
    out = {
        "restart_count": int(os.environ.get(_RESTART_COUNT_ENV, "0") or 0),
        "last_iteration": snap["iter"],
        "in_step_iteration": snap["step"],
    }
    h = _active_health
    if h is not None and h.heartbeat is not None:
        out["heartbeat"] = {str(r): {"iter": e.get("iter", -1),
                                     "step": e.get("step", -1),
                                     "age": e.get("age", -1.0)}
                            for r, e in h.heartbeat.table().items()}
        out["heartbeat_interval"] = h.heartbeat.interval
    if h is not None and h.watchdog is not None:
        out["collective_deadline"] = h.watchdog.deadline
    if _degradations:
        out["degradations"] = list(_degradations)
    # serving-layer gauges (queue depth, in-flight rows, shed/timeout
    # counts, latency percentiles — lightgbm_tpu/serving.py): surfaced
    # here so an operator reading a manifest or bench JSON sees the serve
    # plane's health next to the training plane's
    from .utils import profiling
    serve = {k: v for k, v in profiling.gauges().items()
             if k.startswith("serve_")}
    if serve:
        out["serve"] = serve
    # memory gauges (the flight recorder samples them per iteration —
    # telemetry_memory): HBM in-use/peak + host RSS watermarks, so a
    # checkpoint manifest or bench JSON shows what the run COST in
    # memory, not just what it did. Absent until the first sample (CPU
    # backends record only the host fields).
    mem = {k: int(v) for k, v in profiling.gauges().items()
           if k in ("hbm_bytes_in_use", "hbm_peak_bytes",
                    "hbm_reserved_bytes", "hbm_peak_reserved_bytes",
                    "host_rss_bytes", "host_rss_peak_bytes")}
    if mem:
        out["memory"] = mem
    # flight-recorder post-mortem path BY REFERENCE (telemetry.py): a
    # checkpoint manifest or bench JSON embedding this snapshot tells an
    # operator where the per-iteration ring flushes, without inlining it
    try:
        from . import telemetry
        fr = telemetry.recorder_path()
        if fr:
            out["flight_recorder"] = fr
    except Exception:
        pass
    return out


def heartbeat_ages() -> Optional[Dict[str, float]]:
    """Per-rank heartbeat ages (seconds since last report) when a
    heartbeat monitor is live in this process, else None. The cheap
    host-side accessor the flight recorder records each iteration."""
    h = _active_health
    if h is None or h.heartbeat is None:
        return None
    try:
        return {str(r): float(e.get("age", -1.0))
                for r, e in h.heartbeat.table().items()}
    except Exception:
        return None


# ====================================================== training integrity
# The verification half of the fail-silent story: the fail-stop machinery
# above (heartbeats, watchdog, supervisor) catches ranks that DIE or HANG;
# this layer catches ranks whose state silently diverged (bit flips, bad
# DIMMs, kernel nondeterminism) and jobs that keep running but degraded
# (OOM fallbacks). The reference's distributed learners stay correct only
# because every rank executes bit-identical reductions — here that
# invariant is CHECKED: every ``integrity_check_period`` iterations the
# ranks exchange a cheap fingerprint of the global model state over the
# coordination service and majority-vote any mismatch.

# OOM degradation events this process recorded (models/gbdt.py
# _maybe_degrade_oom): surfaced through health_snapshot() and therefore
# every later checkpoint manifest's health section
_degradations: List[dict] = []


def record_degradation(event: dict) -> dict:
    """Record one degradation event (kind/iteration/level/action/error).
    Returns the STORED dict (the caller's is copied), so episode-style
    callers (serve shedding) can update one recorded event in place
    instead of growing the log per occurrence.

    Every stored event gains a wall timestamp (``t``), a MONOTONIC
    timestamp (``t_mono`` — post-mortem timelines order OOM rungs
    against watchdog fires with it, immune to wall-clock steps) and,
    when the caller didn't supply one, the training loop's active
    iteration (from the progress tracker; -1 before any step)."""
    event = dict(event)
    event["seq"] = len(_degradations)
    event.setdefault("t", time.time())
    event["t_mono"] = time.monotonic()
    if "iteration" not in event:
        try:
            event["iteration"] = int(_progress.snapshot()["iter"])
        except Exception:
            event["iteration"] = -1
    _degradations.append(event)
    from .utils import profiling
    # the gauge is the OOM ladder's (PR 8 failure-mode table) — serve
    # shed/swap events share the log but must not inflate it
    profiling.set_gauge("oom_degradations",
                        float(sum(1 for d in _degradations
                                  if "oom" in d.get("kind", ""))))
    return event


def degradations() -> List[dict]:
    """Degradation events recorded so far (in order)."""
    return list(_degradations)


def reset_degradations() -> None:
    """Clear the process-level degradation log. Called when a NEW
    training run initializes (GBDT._init_train) so a later booster's
    health snapshots — and therefore its checkpoint manifests — don't
    report an earlier, unrelated booster's events as their own."""
    _degradations.clear()
    from .utils import profiling
    profiling.set_gauge("oom_degradations", 0.0)


class RankDivergenceError(Exception):
    """The cross-rank integrity check found ranks whose model state does
    not match the gang's majority. ``corrupt_ranks`` names the minority
    (the ranks whose state diverged); with ``indeterminate`` no majority
    exists (e.g. a 1:1 split at world size 2) and the listed ranks are
    merely the disagreeing parties — restart the whole gang from the last
    checkpoint."""

    def __init__(self, iteration: int, corrupt_ranks, table,
                 indeterminate: bool = False):
        self.iteration = int(iteration)
        self.corrupt_ranks = list(corrupt_ranks)
        self.table = table
        self.indeterminate = bool(indeterminate)
        if indeterminate:
            msg = (f"model-state divergence detected at iteration "
                   f"{iteration}: ranks {self.corrupt_ranks} disagree and "
                   f"no majority exists — cannot name the corrupt rank; "
                   f"restart the gang from the last valid checkpoint")
        else:
            msg = (f"model-state divergence detected at iteration "
                   f"{iteration}: rank(s) {self.corrupt_ranks} hold state "
                   f"that differs from the gang's majority (silent "
                   f"corruption — bit flip, bad memory, or "
                   f"nondeterministic kernel). Restart the corrupt "
                   f"rank(s) from the last valid checkpoint "
                   f"(lightgbm_tpu.supervisor does this automatically).")
        super().__init__(msg)


def model_fingerprint(boosting) -> dict:
    """Cheap fingerprint of one rank's view of the global model state:

    - ``trees``: sha256 over every tree's structure AND values (split
      feature/threshold-bin per node, leaf values) — rank-symmetric by the
      SPMD contract, so it is comparable across EVERY rank;
    - ``score``: sha256 of the exact f32 train-score-cache bytes over this
      rank's row range — comparable only between ranks holding the same
      rows (all of them when replicated; recorded with the row range so
      the vote groups pre-partitioned ranks correctly).

    Reading it flushes the async host-tree mirrors and fetches the score
    cache — a per-``integrity_check_period`` cost, not per-iteration."""
    import hashlib
    import numpy as np
    h = hashlib.sha256()
    for ht in boosting.host_trees:
        nl = int(ht.num_leaves)
        nn = max(nl - 1, 0)
        h.update(np.int32(nl).tobytes())
        h.update(np.ascontiguousarray(
            np.asarray(ht.split_feature[:nn], np.int32)).tobytes())
        h.update(np.ascontiguousarray(
            np.asarray(ht.threshold_bin[:nn], np.int64)).tobytes())
        h.update(np.ascontiguousarray(
            np.asarray(ht.leaf_value[:nl], np.float64)).tobytes())
    score = np.ascontiguousarray(
        np.asarray(boosting.train_score, np.float32))
    ts = boosting.train_set
    row_start = int(getattr(ts, "local_row_start", 0) or 0) \
        if ts is not None else 0
    return {
        "rank": jax_rank(),
        "trees": h.hexdigest(),
        "score": hashlib.sha256(score.tobytes()).hexdigest(),
        "row_start": row_start,
        "row_count": int(score.shape[0]),
    }


def jax_rank() -> int:
    import jax
    try:
        return int(jax.process_index())
    except Exception:
        return 0


def divergence_verdict(entries):
    """Majority vote over per-rank fingerprints. Returns
    ``(corrupt_ranks, indeterminate)``: the minority rank(s) whose
    fingerprints differ from a strict majority, or — when no strict
    majority exists for some disputed component — every disagreeing rank
    with ``indeterminate=True``. Tree hashes vote globally (they are
    rank-symmetric); score checksums vote only within groups of ranks
    holding the SAME row range (pre-partitioned ranks hold disjoint rows
    whose checksums differ by design)."""
    from collections import Counter
    suspects = set()
    indeterminate = False

    def vote(group, key):
        nonlocal indeterminate
        counts = Counter(key(e) for e in group)
        if len(counts) <= 1:
            return
        _, best_n = counts.most_common(1)[0]
        if best_n * 2 <= len(group):
            indeterminate = True
            suspects.update(int(e["rank"]) for e in group)
        else:
            best = counts.most_common(1)[0][0]
            suspects.update(int(e["rank"]) for e in group
                            if key(e) != best)

    vote(entries, lambda e: e["trees"])
    by_range: Dict[tuple, list] = {}
    for e in entries:
        by_range.setdefault(
            (int(e.get("row_start", 0)), int(e.get("row_count", -1))),
            []).append(e)
    for group in by_range.values():
        if len(group) > 1:
            vote(group, lambda e: e["score"])
    return sorted(suspects), indeterminate


def check_model_integrity(boosting, iteration: int,
                          timeout: Optional[float] = None) -> None:
    """Cross-rank divergence check, called in lockstep on every rank
    every ``integrity_check_period`` iterations (engine.train). Exchanges
    each rank's :func:`model_fingerprint` over the coordination service
    (pure gRPC — works on backends without cross-process XLA) and
    majority-votes mismatches.

    Clean gang: returns. Divergence, unsupervised: raises
    :class:`RankDivergenceError` on every rank, naming the minority.
    Divergence, supervised (LGBM_TPU_SUPERVISED=1): the CORRUPT rank
    writes a ``divergence_rank{r}.json`` diagnosis and exits with
    ``DIVERGENCE_EXIT_CODE`` so the supervisor restarts the gang from the
    last valid checkpoint charging that rank's restart budget (a rank
    that keeps diverging is shrunk away); honest ranks log and continue —
    the supervisor tears them down and relaunches. No-op single-process."""
    import jax
    if jax.process_count() <= 1:
        return
    from .utils import profiling
    mine = model_fingerprint(boosting)
    payloads = exchange_host(f"integrity_{iteration}", json.dumps(mine),
                             timeout=timeout)
    entries = [json.loads(p) for p in payloads]
    corrupt, indeterminate = divergence_verdict(entries)
    profiling.set_gauge("integrity_checks_run",
                        profiling.gauges().get("integrity_checks_run", 0.0)
                        + 1.0)
    profiling.set_gauge("integrity_last_iteration", float(iteration))
    # dedup marker: the checkpoint callback votes before every save but
    # must not re-vote an iteration engine.train already certified
    boosting._integrity_checked_iter = int(iteration)
    if not corrupt:
        return
    table = {str(e["rank"]): {"trees": e["trees"][:16],
                              "score": e["score"][:16]} for e in entries}
    err = RankDivergenceError(iteration, corrupt, table,
                              indeterminate=indeterminate)
    rank = mine["rank"]
    supervised = os.environ.get(_SUPERVISED_ENV) == "1"
    if supervised and not indeterminate:
        if rank in corrupt:
            # write the diagnosis the supervisor folds into its report,
            # then exit with the divergence code: by majority evidence
            # THIS rank's state is bad, and a checkpoint restore is the
            # only way back to the gang's truth
            diag_dir = os.environ.get(_DIAG_DIR_ENV)
            diag = {"rank": rank, "iteration": int(iteration),
                    "corrupt_ranks": corrupt, "fingerprints": table,
                    "kind": "divergence",
                    "t": time.time(), "t_mono": time.monotonic()}
            try:
                from . import telemetry
                diag["flight_recorder"] = telemetry.flush_recorder(
                    f"divergence: rank {rank} voted corrupt at iteration "
                    f"{iteration}")
            except Exception:
                pass
            if diag_dir:
                try:
                    os.makedirs(diag_dir, exist_ok=True)
                    with open(os.path.join(
                            diag_dir, f"divergence_rank{rank}.json"),
                            "w") as fh:
                        json.dump(diag, fh, indent=1)
                except OSError:
                    pass
            import sys
            sys.stderr.write(f"[integrity] {err}\n")
            sys.stderr.flush()
            os._exit(DIVERGENCE_EXIT_CODE)
        # honest majority rank: its state is good — log and keep going;
        # the supervisor reaps the corrupt rank's exit, tears this gang
        # down and relaunches it from the last valid checkpoint
        log.warning(f"integrity check: {err} (this rank is in the "
                    f"majority; awaiting supervisor restart)")
        return
    raise err


def shutdown() -> None:
    global _initialized
    if not _initialized:
        return
    import jax
    jax.distributed.shutdown()
    _initialized = False


def maybe_init_from_config(config) -> None:
    """Auto-init when a Booster is constructed with num_machines > 1 and
    distributed training was not explicitly initialized (the CLI flow,
    application.cpp:167-178: Network::Init happens before training)."""
    if _initialized:
        return
    if _jax_already_initialized():
        return
    nm = int(getattr(config, "num_machines", 1) or 1)
    if nm > 1:
        # params=config also carries local_listen_port for same-host rank
        # disambiguation
        init(num_machines=nm, params=config)


def spawn(fn, nproc: int = 2, args: tuple = (),
          per_rank_args: Optional[list] = None,
          devices_per_proc: Optional[int] = None,
          timeout: Optional[float] = 600.0):
    """Run ``fn(rank, *args)`` in ``nproc`` freshly spawned local processes
    wired into one jax.distributed cluster, and return rank 0's result —
    the single-host analog of the reference's Dask orchestration
    (python-package/lightgbm/dask.py:211-330 _train: find open ports,
    inject machines/num_machines/local_listen_port per worker, run local
    fits, return the rank-0 model; examples/parallel_learning's mlist
    flow). Co-location is the caller's: ``fn`` typically slices its rank's
    rows and calls ``load_partitioned`` + ``train``.

    ``fn`` must be picklable (a module-level function). Each child calls
    ``distributed.init`` before ``fn`` runs; ``devices_per_proc`` forces a
    virtual CPU device count (tests), otherwise children inherit the
    environment. ``timeout`` is the OVERALL deadline for all ranks; a
    child that dies without reporting fails fast with its exit code.
    With ``per_rank_args`` (length nproc), rank r is called
    ``fn(r, per_rank_args[r], *args)`` — each child ships ONLY its own
    payload (a worker's data partition must not be pickled to every other
    worker). Returns rank 0's return value (must be picklable); raises
    RuntimeError with the failing rank's traceback on error.
    """
    import multiprocessing as mp
    import queue as _queue
    import time as _time

    if per_rank_args is not None and len(per_rank_args) != nproc:
        raise ValueError(f"per_rank_args has {len(per_rank_args)} entries "
                         f"for {nproc} ranks")
    port = free_port()
    machines = ",".join(f"127.0.0.1:{port}" for _ in range(nproc))
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [ctx.Process(
        target=_spawn_child,
        args=(q, fn, r, nproc, machines, devices_per_proc,
              args if per_rank_args is None
              else (per_rank_args[r],) + tuple(args)))
        for r in range(nproc)]
    for p in procs:
        p.start()
    results = {}
    deadline = None if timeout is None else _time.monotonic() + timeout
    try:
        while len(results) < nproc:
            try:
                rank, ok, payload = q.get(timeout=1.0)
            except _queue.Empty:
                # a segfaulted/OOM-killed child never enqueues: fail fast
                # with the dead rank identified instead of waiting out the
                # full deadline
                for r, p in enumerate(procs):
                    if r not in results and not p.is_alive() \
                            and p.exitcode not in (0, None):
                        raise RuntimeError(
                            f"distributed.spawn rank {r} died with exit "
                            f"code {p.exitcode} before reporting")
                if deadline is not None and _time.monotonic() > deadline:
                    missing = [r for r in range(nproc) if r not in results]
                    raise RuntimeError(
                        f"distributed.spawn timed out after {timeout}s "
                        f"waiting for ranks {missing}")
                continue
            if not ok:
                raise RuntimeError(
                    f"distributed.spawn rank {rank} failed:\n{payload}")
            results[rank] = payload
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()
                p.join(timeout=10)
            if p.is_alive():          # SIGTERM swallowed in native code
                p.kill()
                p.join(timeout=10)
    return results.get(0)


def free_port() -> int:
    """Grab an ephemeral localhost port (bind-then-close; shared by
    ``spawn`` and the multi-host test harness so the idiom lives once)."""
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def prepare_cpu_device_env(env, devices_per_proc: int) -> None:
    """Force ``devices_per_proc`` virtual CPU devices in an environment
    mapping (child-process setup shared by ``spawn`` and the test
    harnesses): pins JAX_PLATFORMS=cpu, clears JAX_NUM_CPU_DEVICES (which
    would override the XLA flag), and rewrites
    --xla_force_host_platform_device_count."""
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_NUM_CPU_DEVICES", None)
    flags = [t for t in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in t]
    flags.append(
        f"--xla_force_host_platform_device_count={devices_per_proc}")
    env["XLA_FLAGS"] = " ".join(flags)


def _spawn_child(q, fn, rank, nproc, machines, devices_per_proc, args):
    import traceback
    from .utils import faults
    # spawn-fail injection point: the child dies BEFORE bootstrap (the
    # "machine cannot start" shape — bad image, dead host, lost quota) so
    # the supervisor's permanent-loss classification can be exercised
    faults.maybe_fail_spawn(rank)
    try:
        if devices_per_proc is not None:
            prepare_cpu_device_env(os.environ, devices_per_proc)
            import jax
            jax.config.update("jax_platforms", "cpu")
        init(machines=machines, num_machines=nproc, process_id=rank)
        result = fn(rank, *args)
        # pre-pickle INSIDE the try: Queue.put pickles later, in a feeder
        # thread, so an unpicklable return value would otherwise vanish
        # (child exits 0, parent waits out the full deadline)
        import pickle
        pickle.dumps(result)
        q.put((rank, True, result))
    except BaseException:
        q.put((rank, False, traceback.format_exc()))


def _train_part(rank, part, params, num_boost_round, train_kwargs):
    """Per-worker body of ``train_distributed`` (module-level so spawn can
    pickle it): build the local pre-partitioned Dataset, run the standard
    train loop (collectives ride the jitted programs), return the model
    text — the exact shape of the reference's dask ``_train_part``
    (python-package/lightgbm/dask.py:73-124)."""
    from .engine import train as _train
    ds = load_partitioned(part["data"], label=part.get("label"),
                          weight=part.get("weight"),
                          init_score=part.get("init_score"),
                          params=params)
    booster = _train(params, ds, num_boost_round, **train_kwargs)
    return booster.model_to_string()


def train_distributed(params, parts, num_boost_round: int = 100,
                      devices_per_proc: Optional[int] = None,
                      timeout: Optional[float] = 900.0,
                      **train_kwargs):
    """Distributed training over pre-partitioned data, orchestrated like
    the reference's Dask layer (python-package/lightgbm/dask.py:211-330
    ``_train``: co-locate partitions per worker, find an open port, inject
    machines/num_machines per worker, run local fits, return the rank-0
    model).

    Args:
      params: training params; ``tree_learner`` defaults to "data" and must
        be one of data/voting/feature (the same restriction the reference's
        dask layer enforces, dask.py:301-311).
      parts: one dict per worker — {"data": X, "label": y,
        "weight": optional, "init_score": optional}. Each worker sees ONLY
        its part (the reference's data_parallel pre-partitioned mode:
        data never leaves its machine, dataset_loader.cpp:182-258).
      num_boost_round: boosting rounds.
      devices_per_proc: force N virtual CPU devices per worker (tests).
      timeout: overall deadline handed to ``spawn``.
      **train_kwargs: forwarded to ``engine.train`` in each worker.

    Returns the trained Booster (rank 0's model, loaded locally).
    """
    params = dict(params or {})
    learner = str(params.get("tree_learner", "data") or "data")
    allowed = {"data", "voting", "feature"}
    if learner not in allowed:
        log.fatal(f"train_distributed requires tree_learner in {allowed} "
                  f"(got {learner!r}) — the reference's dask layer has the "
                  f"same restriction (dask.py:301-311)")
    params["tree_learner"] = learner
    if "num_machines" in params:
        nm = int(params["num_machines"])
        if nm != len(parts):
            log.fatal(f"num_machines={nm} but {len(parts)} parts given")
    model_str = spawn(_train_part, nproc=len(parts),
                      args=(params, num_boost_round, dict(train_kwargs)),
                      per_rank_args=list(parts),
                      devices_per_proc=devices_per_proc, timeout=timeout)
    from .booster import Booster
    return Booster(params=params, model_str=model_str)


def allgather_f64(arr):
    """``process_allgather`` that PRESERVES float64 bits by gathering the
    raw bytes: with jax x64 disabled, a plain allgather round-trips
    through f32 device arrays and truncates. Returns [nproc, *arr.shape].
    """
    import numpy as np
    from jax.experimental import multihost_utils
    a = np.ascontiguousarray(np.asarray(arr, np.float64))
    g = np.ascontiguousarray(np.asarray(
        multihost_utils.process_allgather(a.view(np.uint8))))
    return g.reshape((-1,) + a.shape[:-1]
                     + (a.shape[-1] * 8,)).view(np.float64)


# ------------------------------------------------ distributed data loading
def load_partitioned(data, label=None, weight=None, init_score=None,
                     params: Optional[dict] = None,
                     feature_name="auto", categorical_feature="auto"):
    """Pre-partitioned multi-host Dataset: each process passes ITS OWN row
    slice; bin mappers are fitted from an allgathered row sample so every
    process agrees, and the binned matrix becomes one GLOBAL row-sharded
    device array over the full mesh.

    The analog of the reference's distributed loading (reference:
    dataset_loader.cpp:1046-1128 feature-sharded bin finding merged by
    Network::Allgather, :843 pre-partitioned per-machine loading,
    Metadata::CheckOrPartition dataset.h:86). Here the SAMPLE is what
    crosses hosts (a few hundred KB) — each process samples
    bin_construct_sample_cnt / num_processes of its local rows, the
    samples allgather, and identical mappers are fitted everywhere; the
    full data never leaves its host.

    Returns a constructed ``Dataset`` whose ``bins`` is a global jax.Array
    sharded over processes; ``num_data`` is the GLOBAL row count while
    label/weight stay process-local. Pass it straight to ``lgb.train`` /
    ``Booster`` with ``tree_learner="data"`` (or voting): scores,
    gradients and the leaf-id vector all stay process-local / row-sharded
    through the whole boosting loop (the reference's per-machine score
    partition, score_updater.hpp — memory per machine FALLS as machines
    are added, docs/Experiments.rst:228-242), with EFB bundling and the
    feature-major fast path both active. Metrics evaluate on each
    process's local partition, like the reference's per-machine metric
    logs. Not supported: dart, linear_tree, rollback_one_iter.
    """
    import jax
    import numpy as np
    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding, PartitionSpec as P

    from . import binning
    from .basic import Dataset, _to_2d_float
    from .config import Config
    from .parallel.data_parallel import make_mesh

    config = Config.from_params(dict(params or {}))
    X = _to_2d_float(data)
    n_local, f = X.shape
    nproc = jax.process_count()

    # ---- distributed bin finding: allgather a per-process row sample
    per_proc = max(1, config.bin_construct_sample_cnt // max(nproc, 1))
    idx = binning.sample_indices(n_local, per_proc,
                                 config.data_random_seed + jax.process_index())
    sample_local = np.ascontiguousarray(X[idx]).astype(np.float64)
    # pad to a common row count so allgather shapes agree
    pad = per_proc - sample_local.shape[0]
    if pad > 0:
        sample_local = np.pad(sample_local, ((0, pad), (0, 0)),
                              constant_values=np.nan)
        valid_local = np.concatenate([np.ones(len(idx), bool),
                                      np.zeros(pad, bool)])
    else:
        valid_local = np.ones(per_proc, bool)
    if nproc > 1:
        # bit-exact f64 sample gather (a plain allgather truncates to f32
        # with x64 off, making bin bounds differ from a 1-process run)
        gathered = allgather_f64(sample_local)
        valid = np.asarray(
            multihost_utils.process_allgather(valid_local)).reshape(-1)
        sample = gathered.reshape(-1, f)[valid]
        local_counts = np.asarray(multihost_utils.process_allgather(
            np.asarray([n_local], np.int32)))
        n_global = int(local_counts.sum())
    else:
        sample = sample_local[valid_local]
        local_counts = np.asarray([[n_local]])
        n_global = n_local

    ds = Dataset(X, label=label, weight=weight, init_score=init_score,
                 params=dict(params or {}), feature_name=feature_name,
                 categorical_feature=categorical_feature)
    names = ([f"Column_{i}" for i in range(f)]
             if feature_name in ("auto", None) else list(feature_name))
    cats = ds._resolve_categorical(f, names)
    cat_set = set(int(c) for c in cats)
    from .basic import _load_forced_bins
    forced = _load_forced_bins(config, f, cats)
    filter_cnt = binning.filter_cnt_for_sample(config, len(sample), n_global)
    mappers = [binning.fit_mapper_for_column(
        j, np.asarray(sample[:, j]), len(sample), config, cat_set,
        filter_cnt, forced) for j in range(f)]

    # bin the LOCAL rows against the agreed mappers, then assemble the
    # global row-sharded device matrix (each process contributes only its
    # addressable shards)
    ds.mappers = mappers
    ds.used_features = np.array(
        [j for j, m in enumerate(mappers) if not m.is_trivial], np.int32)
    ds.num_data = n_global
    ds.num_total_features = f
    ds._feature_names = names
    # EFB over the agreed (allgathered) sample: identical inputs on every
    # process -> identical bundle assignment, so the bundled column layout
    # needs no further cross-host negotiation (the analog of the
    # reference's sample-driven FastFeatureBundling, dataset.cpp:239).
    # enable_bundle=false skips the bundling machinery ENTIRELY (plain
    # per-feature columns) rather than building singleton bundles — the
    # layout load_partitioned_chunks produces, so the chunked and
    # monolithic loaders are bit-comparable with bundling off
    if config.enable_bundle:
        ds._run_bundling(sample, len(sample), config)
    else:
        ds.bundles = None
    if ds.bundles is not None and len(ds.bundles):
        ds._build_feature_meta_bundled(config)
        local_bins = ds._bin_columns(X)
    else:
        ds.bundles = None
        ds._build_feature_meta(config)
        used = [mappers[j] for j in ds.used_features]
        local_bins = binning.bin_data(
            X[:, ds.used_features] if len(ds.used_features)
            else np.zeros((n_local, 0)), used)
    dtype = np.uint8 if ds.max_num_bins <= 256 else np.int32
    _shard_local_bins(ds, local_bins.astype(dtype), local_counts)
    g = ds.num_used_features()
    log.info(f"pre-partitioned dataset: {n_local} local rows of "
             f"{n_global} global, {len(ds.used_features)} used features"
             + (f" (bundled into {g} columns)" if ds.bundles else ""))
    return ds


def _shard_local_bins(ds, local_bins, local_counts) -> None:
    """Assemble a rank's LOCAL binned rows into the global row-sharded
    device matrix and finish the pre-partitioned Dataset bookkeeping —
    the shared tail of ``load_partitioned`` (monolithic local matrix) and
    ``load_partitioned_chunks`` (streamed local chunks). ``local_counts``
    is every rank's local row count in rank order (array or list)."""
    import jax
    import numpy as np
    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .parallel.data_parallel import make_mesh

    nproc = jax.process_count()
    n_local = int(local_bins.shape[0])
    counts = [int(c) for c in np.asarray(local_counts).reshape(-1)]
    # pad local rows to a common per-process count divisible by the local
    # device count so the global sharding has equal shards; padded rows are
    # excluded from histograms by the zero-padded sample mask the grower
    # applies
    n_loc_dev = jax.local_device_count()
    max_local = max(counts)
    target = -(-max_local // n_loc_dev) * n_loc_dev
    if target > n_local:
        local_bins = np.pad(local_bins, ((0, target - n_local), (0, 0)))
    mesh = make_mesh(axis="shard")
    sharding = NamedSharding(mesh, P("shard", None))
    if nproc > 1:
        ds.bins = multihost_utils.host_local_array_to_global_array(
            local_bins, mesh, P("shard", None))
    else:
        ds.bins = jax.device_put(jax.numpy.asarray(local_bins), sharding)
    # the feature-major copy (doubles the dominant array) is built LAZILY
    # by the prepart-aware Dataset.bins_T property, so histogram methods
    # that never read it (scatter/binloop) pay nothing
    ds.raw_data_np = None
    ds.is_pre_partitioned = True
    ds.num_local_data = n_local
    # global row partition bookkeeping for sharded checkpoints: this
    # rank's first global row and every rank's local row count (the
    # PARTITION.json the checkpoint writer records; see checkpoint.py)
    rank = jax.process_index()
    ds.partition_counts = counts
    ds.local_row_start = int(sum(counts[:rank]))
    ds._constructed = True
    if ds.free_raw_data:
        ds.data = None


def merge_feature_sketches(sketches, tag: str = "construct"):
    """Allgather per-feature construct sketches as JSON over
    ``exchange_host`` and fold them together IN RANK ORDER — the
    streaming twin of the reference's distributed bin finding
    (dataset_loader.cpp:1046-1128: per-machine FindBin merged by
    Network::Allgather). Deterministic: every rank receives the same
    payloads in the same order and merges identically, so the mappers
    fitted from the result agree bit-exactly everywhere (float values
    serialize via repr, which round-trips f64). Single process: returns
    the input unchanged. Payload size is bounded by
    ``num_features * sketch_max_size`` distinct values — the sketch, not
    the data, is what crosses hosts."""
    import jax

    from . import binning

    if jax.process_count() <= 1:
        return list(sketches)
    sketches = list(sketches)
    # agree on the feature count FIRST (one tiny exchange): a mismatch
    # must fail loudly here — discovered later it would desync the
    # batched exchange below into a lockstep hang
    nfs = [int(json.loads(p)) for p in
           exchange_host(f"sketch_{tag}_nf", json.dumps(len(sketches)))]
    if len(set(nfs)) != 1:
        log.fatal(f"pre-partitioned chunk sources disagree on feature "
                  f"count across ranks: {nfs}")
    # exchange_host's contract is SMALL payloads (its KV store has no
    # chunking): a saturated sketch is ~sketch_max_size repr'd f64s per
    # feature (~25 B each), so features are exchanged in batches bounded
    # to a few MB. Batch boundaries derive only from values every rank
    # agrees on (feature count + the config's sketch_max_size), keeping
    # the per-batch tags in lockstep.
    max_size = max((sk.max_size for sk in sketches), default=0)
    per_batch = (len(sketches) if not max_size
                 else max(1, (4 << 20) // max(1, max_size * 25)))
    merged: List = []
    for b0 in range(0, len(sketches), per_batch):
        batch = sketches[b0:b0 + per_batch]
        payload = json.dumps([sk.to_dict() for sk in batch])
        parts = exchange_host(f"sketch_{tag}_b{b0}", payload)
        batch_merged = [binning.FeatureSketch.from_dict(d)
                        for d in json.loads(parts[0])]
        for r, part in enumerate(parts[1:], start=1):
            dicts = json.loads(part)
            if len(dicts) != len(batch_merged):
                # a zip would silently truncate and fit subtly-wrong
                # mappers deterministically on every rank — fail loudly
                # instead, like sketch_chunks' mid-stream width check
                log.fatal(f"rank {r} sketched {len(dicts)} features in "
                          f"batch {b0}, rank 0 sketched "
                          f"{len(batch_merged)}: pre-partitioned chunk "
                          f"sources disagree on feature count")
            for sk, d in zip(batch_merged, dicts):
                sk.merge(binning.FeatureSketch.from_dict(d))
        merged.extend(batch_merged)
    return merged


def load_partitioned_chunks(chunks, label=None, weight=None, init_score=None,
                            params: Optional[dict] = None,
                            feature_name="auto",
                            categorical_feature="auto"):
    """Streaming pre-partitioned loader: each process folds ITS OWN row
    chunks into per-feature sketches (host memory O(chunk) — the raw
    local matrix never materializes), the sketches merge across ranks
    over ``exchange_host`` (:func:`merge_feature_sketches`), identical
    BinMappers are fitted everywhere from the merged summaries, and each
    rank bins its chunks straight into its shard of the global
    row-sharded bin matrix. The chunked twin of :func:`load_partitioned`
    for the 100M-row regime where even one host's row slice dwarfs RAM.

    ``chunks``: this rank's local chunk source (``binning.chunk_factory``
    forms: callable/sequence/2-D array), each chunk ``[rows, F]`` or an
    ``(X, y)`` pair whose label parts concatenate into the local label.
    EFB bundling does not apply (it needs sampled row patterns; dense
    chunk columns map 1:1 to device columns like the dense monolithic
    construct) — for parity against ``load_partitioned`` run that side
    with ``enable_bundle=false``. Same training contract as
    ``load_partitioned``: label/weight stay process-local,
    ``tree_learner="data"``/voting, no dart/linear_tree."""
    import time as _time

    import jax
    import numpy as np

    from . import binning
    from .basic import Dataset, _load_forced_bins
    from .config import Config
    from .utils import profiling

    config = Config.from_params(dict(params or {}))
    profiling.drop_gauges("construct_")   # this construction's gauges only
    factory = binning.chunk_factory(chunks, config.construct_chunk_rows)
    peak = [0]

    def track(nbytes, mult=1):
        peak[0] = max(peak[0], mult * int(nbytes))

    t0 = _time.time()
    with profiling.timer("sketch_pass"):
        sketches, n_local, sizes, chunk_labels = binning.sketch_chunks(
            factory, max_size=config.sketch_max_size, track_bytes=track)
        merged = merge_feature_sketches(sketches)
    sketch_s = _time.time() - t0
    del sketches
    f = len(merged)
    n_global = int(merged[0].total_cnt) if f else 0
    counts = [int(json.loads(p)) for p in
              exchange_host("prepart_chunk_rows", json.dumps(int(n_local)))]
    assert sum(counts) == n_global or f == 0, (counts, n_global)

    if chunk_labels is not None:
        if label is not None:
            log.fatal("labels were passed both to load_partitioned_chunks "
                      "and in the chunk stream; pass one or the other")
        label = chunk_labels
    ds = Dataset(None, label=label, weight=weight, init_score=init_score,
                 params=dict(params or {}), feature_name=feature_name,
                 categorical_feature=categorical_feature)
    names = ([f"Column_{i}" for i in range(f)]
             if feature_name in ("auto", None) else list(feature_name))
    ds._feature_names = names
    cats = ds._resolve_categorical(f, names)
    forced = _load_forced_bins(config, f, cats)
    mappers = binning.fit_mappers_from_sketches(merged, n_global, config,
                                                cats, forced_bounds=forced)
    ds.mappers = mappers
    ds.used_features = np.array(
        [j for j, m in enumerate(mappers) if not m.is_trivial], np.int32)
    ds.num_data = n_global
    ds.num_total_features = f
    ds.bundles = None
    ds._build_feature_meta(config)

    # second pass: bin each local chunk into its slot of the local shard
    # (host per-chunk bin_data: the shard crosses into the global array
    # as a host-local contribution, so the rows are needed host-side)
    used = [mappers[j] for j in ds.used_features]
    uf = ds.used_features
    dtype = np.uint8 if ds.max_num_bins <= 256 else np.int32
    local_bins = np.zeros((n_local, max(len(uf), 1)), dtype)
    t0 = _time.time()
    with profiling.timer("bin_pass"):
        # shared host bin-pass helper: ref-dropping iteration (<= the
        # current chunk + its f64 column copy resident) and a LOUD
        # failure when the source under-yields on re-iteration
        binning.bin_chunks_host(factory, used, uf, local_bins, track)
    bin_s = _time.time() - t0
    profiling.set_gauge("construct_sketch_s", sketch_s)
    profiling.set_gauge("construct_bin_s", bin_s)
    profiling.set_gauge("construct_peak_bytes", float(peak[0]))
    profiling.set_gauge("construct_rows", float(n_local))
    ds.construct_stats = {
        "sketch_pass": round(sketch_s, 6), "bin_pass": round(bin_s, 6),
        "peak_host_bytes": int(peak[0]), "rows": int(n_local),
    }
    _shard_local_bins(ds, local_bins, counts)
    g = ds.num_used_features()
    log.info(f"pre-partitioned streaming dataset: {n_local} local rows of "
             f"{n_global} global in {len(sizes)} chunks "
             f"(peak raw {peak[0]} bytes), {len(ds.used_features)} used "
             f"features across {g} columns")
    return ds
