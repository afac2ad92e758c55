"""Unified telemetry layer: flight recorder, trace capture, exposition.

Before this module the repo had five disconnected telemetry surfaces —
the TIMETAG scopes/counters/gauges in ``utils/profiling.py``, the
dispatch/transfer hook, ``distributed.health_snapshot()``, the
supervisor/divergence diagnosis JSONs, and ad-hoc snapshot spellings in
the benchmark scripts — with no shared schema, no time axis, and
nothing that survived a crash (two benchmark rounds published CPU
numbers under a chip's name, and nothing had recorded WHY the chip was
not used).
This module is the one subsystem every layer reports into:

- :func:`snapshot` — the ONE versioned schema over all of the above
  (scopes + counters + gauges + dispatch + health, which itself carries
  the degradation log and the serve gauges), consumed by the
  Prometheus-style ``ServeFrontend`` metrics endpoint
  (:func:`prometheus_text`), and rank-0 gang aggregation
  (:func:`gang_snapshot` over ``distributed.exchange_host``).

- :class:`FlightRecorder` — a bounded in-memory ring of per-iteration
  structured records (the iteration's interval from its ``update()`` to
  the next one's, the host's self seconds by span in it, what no span
  covered, collections, compile requests, and, filled in when the
  device's outputs are seen ready, the rows its histogram passes
  streamed; phase wall-time deltas, dispatch/transfer deltas,
  sentinel verdicts, OOM-degradation rungs, heartbeat ages) that flushes
  to JSONL atomically on watchdog fire / divergence verdict /
  OOM-ladder exhaustion / training error / fault-harness kill, so any
  dead gang or failed TPU round leaves a self-describing post-mortem.
  The recorder reads ONLY already-fetched host values — it rides the
  lazy sentinel drain and never forces a device sync, so recorder-on
  training keeps the fused path's 2-dispatches-per-iteration budget
  (asserted in tests/test_telemetry.py).

- :func:`timeline_report` — one reading of the process timeline
  (``profiling.timeline()``: every host span keeps its own start and
  end) and the iteration records together: the set-up, from process
  start to the end of the first completed iteration, partitioned into
  import / construct / plan / step build / loop / unspanned with every
  instant owned once; the iterations by passes a tree; the stalled ones
  with where their time sat. Written into every flush event, printed by
  ``python -m lightgbm_tpu.telemetry <flight file>``.

- :func:`trace_window` — windowed device-trace capture driving
  ``jax.profiler`` start/stop around N boosting iterations. The host
  plane of the capture holds the always-on ``lgbm:`` spans
  (``profiling.span``: fused_dispatch, score_dispatch, tree_fetch, ...);
  the device plane names each event by its HLO instruction
  (``fusion.10``), which changes with every edit to the step.

- :func:`scope_table` — the join between the two: for every program the
  hot path dispatches (the fused step, the score add, the predict
  traversal), which ``jax.named_scope`` of ``profiling.SCOPES`` each
  compiled instruction came from, read off the compiled program's own
  text. A device trace is read against it.

Crash-durability model: the injected kill faults (``utils/faults.py``
``_hard_exit``) flush the ring before ``os._exit`` — the testable
stand-in for preemption. A REAL ``SIGKILL`` cannot flush anything, so
runs with a durable telemetry directory configured (``telemetry_dir``
param, the supervisor's diag-dir env, or ``checkpoint_path``) also
flush periodically (``telemetry_flush_period``), bounding the loss to
one flush period. Watchdog and divergence diagnoses embed the flushed
path by reference (``"flight_recorder"``), as does
``health_snapshot()`` — and therefore every checkpoint manifest's
health section.
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional

from .utils import log

# Version of BOTH the snapshot schema and the flight-recorder JSONL
# schema. Bump on any breaking field change; consumers (the smoke
# script, the supervisor, offline tooling) match on it.
SCHEMA_VERSION = 1

# record types a flight-recorder JSONL may contain, with their required
# fields (the machine-checkable half of the schema;
# validate_flight_jsonl enforces it)
FLIGHT_RECORD_FIELDS: Dict[str, tuple] = {
    # one per flushed file, always the first line: run identity + the
    # resolved execution context (backend, hist_method, split_fusion...)
    "run": ("schema", "rank", "pid", "context"),
    # one per boosting update() (a K-block counts as one record covering
    # ``iters`` iterations starting at ``iteration``). Closed when the next
    # update() begins or training ends, it gains t0_ns, t1_ns, host,
    # unspanned_s, gc_s and compile_requests; when its outputs are seen
    # ready, rows_streamed and ready_seen_ns. All optional: an open or an
    # unfinished record lacks them
    "iter": ("t", "iteration", "iters", "completed", "wall_s", "phases",
             "dispatch", "sentinel", "oom_level"),
    # the set-up spans of the process timeline (profiling.timeline()
    # ["setup"]): what ran before the first completed iteration ended
    "span": ("id", "parent", "name", "t0_ns", "t1_ns", "thread", "attrs"),
    # one per flush event, appended in order (every later flush rewrites
    # the file with the full ring + ALL flush events so far, so an
    # oom-exhaustion flush survives into the final train-error flush)
    "flush": ("t", "reason", "health"),
}


def _utcnow() -> float:
    return time.time()


# ============================================================ snapshot

def snapshot() -> Dict[str, Any]:
    """The unified telemetry snapshot — every surface in one versioned
    document:

    - ``scopes``/``counters``: the TIMETAG wall-time table and work
      counters (empty unless profiling is enabled — measurement mode);
    - ``gauges``: the always-on health gauges (supervisor restarts,
      heartbeat ages, serve queue/latency, OOM rungs);
    - ``dispatch``: cumulative compiled-program dispatch / transfer
      counters (zero until ``profiling.install_dispatch_hook``);
    - ``memory``: :func:`memory_snapshot` — device HBM in-use/peak and
      host RSS (null fields on backends without ``memory_stats()``),
      plus the per-phase HBM watermarks TIMETAG mode accumulates;
    - ``health``: ``distributed.health_snapshot()`` — progress,
      heartbeat table, degradation log, serve gauges, and (when a
      flight recorder is live) the post-mortem JSONL path.

    Reads only host-side state — never forces a device sync — so it is
    safe to call from serving threads and the metrics endpoint."""
    from . import distributed
    from .utils import profiling
    return {
        "schema": SCHEMA_VERSION,
        "time": _utcnow(),
        "scopes": profiling.scopes(),
        "counters": profiling.counters(),
        "gauges": profiling.gauges(),
        "dispatch": profiling.dispatch_stats(),
        "memory": memory_snapshot(),
        "health": distributed.health_snapshot(),
    }


def memory_snapshot() -> Dict[str, Any]:
    """The memory plane in one dict: the current
    ``profiling.sample_memory()`` fields (``hbm_bytes_in_use`` /
    ``hbm_peak_bytes`` / ``hbm_reserved_bytes`` /
    ``hbm_peak_reserved_bytes`` / ``host_rss_bytes``, each null where the
    backend or /proc doesn't supply it — the None-tolerance contract) and
    the process host-RSS peak (VmHWM)."""
    from .utils import profiling
    out: Dict[str, Any] = dict(profiling.sample_memory())
    out["host_rss_peak_bytes"] = profiling.host_rss_peak_bytes()
    return out


def construct_snapshot() -> Dict[str, Any]:
    """Construct-phase telemetry in one dict — the single spelling the
    flight-recorder header and the smoke scripts read. Sources: the
    always-on gauges the streaming construct records
    (``construct_sketch_s`` / ``construct_bin_s`` /
    ``construct_h2d_overlap_s`` / ``construct_peak_bytes`` /
    ``construct_rows``, basic.py ``_construct_streaming`` and
    ``distributed.load_partitioned_chunks``). Process-level semantics:
    describes the LAST streaming construct in this process (each one
    drops the family first) — the smoke scripts read it right after
    constructing; per-DATASET attribution (what the flight-recorder
    header uses) lives on ``Dataset.construct_stats`` instead. Empty
    dict when no streaming construct ran in this process.
    ``rows_per_sec`` is rows / (sketch + bin) wall."""
    from .utils import profiling
    g = profiling.gauges()
    out: Dict[str, Any] = {}
    for gauge, key in (("construct_sketch_s", "sketch_pass"),
                       ("construct_bin_s", "bin_pass"),
                       ("construct_h2d_overlap_s", "h2d_overlap")):
        if gauge in g:
            out[key] = round(float(g[gauge]), 6)
    if "construct_peak_bytes" in g:
        out["peak_host_bytes"] = int(g["construct_peak_bytes"])
    if "construct_rows" in g:
        out["rows"] = int(g["construct_rows"])
        wall = float(g.get("construct_sketch_s", 0.0)
                     + g.get("construct_bin_s", 0.0))
        if wall > 0:
            out["rows_per_sec"] = round(out["rows"] / wall, 1)
    return out


_METRIC_NAME_RE = re.compile(r"[^a-zA-Z0-9_]")


def _metric_name(name: str) -> str:
    return "lightgbm_tpu_" + _METRIC_NAME_RE.sub("_", str(name))


def _metric_value(value) -> str:
    """Full-precision exposition value: '%g'-style 6-digit rounding
    would freeze monotonic counters past ~1e6 (rate()/increase() then
    read zero forever). Integral values print as integers; the rest use
    repr's shortest round-trip form."""
    v = float(value)
    if v.is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(v)


def prometheus_text(snap: Optional[Dict[str, Any]] = None) -> str:
    """Render a :func:`snapshot` in the Prometheus text exposition
    format (one metric per line, ``lightgbm_tpu_`` prefix): gauges
    become first-class metrics (``lightgbm_tpu_serve_p99_ms``), scopes
    and counters become labeled totals, the dispatch counters and the
    health scalars ride along. The ``ServeFrontend`` ``/metrics``
    endpoint serves exactly this."""
    if snap is None:
        snap = snapshot()
    lines: List[str] = [
        f"# lightgbm_tpu telemetry schema {snap.get('schema', '?')}"]
    for name, value in sorted((snap.get("gauges") or {}).items()):
        lines.append(f"{_metric_name(name)} {_metric_value(value)}")
    for name, sc in sorted((snap.get("scopes") or {}).items()):
        base = _metric_name("scope")
        lines.append(f'{base}_seconds_total{{scope="{name}"}} '
                     f'{_metric_value(sc["total_s"])}')
        lines.append(f'{base}_calls_total{{scope="{name}"}} '
                     f'{int(sc["calls"])}')
    for name, value in sorted((snap.get("counters") or {}).items()):
        lines.append(f'{_metric_name("counter_total")}{{name="{name}"}} '
                     f"{_metric_value(value)}")
    for name, value in sorted((snap.get("dispatch") or {}).items()):
        lines.append(f"{_metric_name(name + '_total')} {int(value)}")
    health = snap.get("health") or {}
    for key in ("restart_count", "last_iteration"):
        if key in health:
            lines.append(f"{_metric_name(key)} {int(health[key])}")
    lines.append(f"{_metric_name('degradations_total')} "
                 f"{len(health.get('degradations') or [])}")
    for rank, entry in sorted((health.get("heartbeat") or {}).items()):
        lines.append(f'{_metric_name("heartbeat_age_seconds")}'
                     f'{{rank="{rank}"}} '
                     f'{_metric_value(entry.get("age", -1))}')
    return "\n".join(lines) + "\n"


def gang_snapshot(tag: str = "telemetry") -> List[Dict[str, Any]]:
    """Allgather every rank's :func:`snapshot` over the coordination
    service (``distributed.exchange_host`` — pure gRPC, works where
    cross-process XLA collectives don't), returning them in rank order
    on EVERY rank. Must be called in lockstep on all ranks, like any
    exchange. Single-process: ``[snapshot()]``. Rank 0 typically embeds
    the result in its reports (bench JSON, supervisor smoke)."""
    from . import distributed
    mine = snapshot()
    payloads = distributed.exchange_host(tag, json.dumps(mine))
    out = []
    for p in payloads:
        try:
            out.append(json.loads(p))
        except ValueError:
            out.append({"schema": SCHEMA_VERSION, "error": "unparseable"})
    return out


# ====================================================== flight recorder

class FlightRecorder:
    """Bounded ring of per-iteration structured records + flush events.

    Training (``GBDT.train_one_iter``) appends one record per update()
    from values the host ALREADY holds — wall time, dispatch-counter
    deltas, TIMETAG scope deltas (empty unless profiling is enabled),
    the OOM-ladder rung, heartbeat ages — so recording costs a dict
    build, never a device sync or an extra dispatch. Sentinel verdicts
    arrive LATE by design: the fused path judges its in-program NaN/Inf
    flag words lazily (the sentinel drain), and ``note_sentinel``
    back-fills the covering record when the verdict lands.

    ``flush(reason)`` serializes header + ring + every flush event so
    far to ``flight_rank{r}.jsonl`` atomically (``utils/atomic_write``:
    a kill mid-flush leaves the previous complete file, never a
    truncated hybrid). Thread-safe: the watchdog thread flushes
    concurrently with the training thread recording."""

    def __init__(self, capacity: int = 256, directory: Optional[str] = None,
                 rank: int = 0, flush_period: int = 0,
                 incarnation: int = 0):
        self.capacity = max(1, int(capacity))
        self.directory = directory or None
        self.rank = int(rank)
        self.flush_period = max(0, int(flush_period))
        # supervised relaunches must not overwrite the DEAD incarnation's
        # post-mortem: incarnation > 0 gets its own file
        self.incarnation = int(incarnation)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)
        # retained flush EVENTS (watchdog/divergence/OOM/error/kill/end)
        # — bounded like the ring: rare by nature, but a pathological
        # repeat-flusher must not grow memory or the file without limit
        self._flushes: deque = deque(maxlen=64)
        self._context: Dict[str, Any] = {}
        self._last_path: Optional[str] = None
        self._last_periodic = 0
        # begin_update()'s (t0_ns, compile requests so far, training
        # thread) until record() takes them; then the record whose
        # interval is still running, with the last two
        self._begun: Optional[tuple] = None
        self._open: Optional[tuple] = None
        # late fields that arrived before their record (a step that
        # finished inside its own update()): iteration -> (rows, seen_ns)
        self._early: Dict[int, tuple] = {}
        self._rows_cum = 0.0     # cumulative rows streamed, last seen

    # ------------------------------------------------------- recording
    def set_context(self, **fields) -> None:
        """Merge resolved run context (backend, hist_method,
        split_fusion, rounds-per-dispatch...) into the header record."""
        with self._lock:
            self._context.update(fields)

    @property
    def has_context(self) -> bool:
        return bool(self._context)

    def record(self, iteration: int, iters: int = 1, completed: bool = True,
               wall_s: float = 0.0, phases: Optional[Dict[str, float]] = None,
               dispatch: Optional[Dict[str, int]] = None,
               sentinel: str = "off", oom_level: int = 0,
               **fields) -> None:
        """Append one per-iteration record (a K-block passes iters=K).
        Extra keyword fields ride along verbatim (coll_bytes, heartbeat
        ages...). Values must already be host-side. ``wall_s`` is the
        host's time inside ``update()`` (an asynchronous dispatch);
        after :meth:`begin_update` the record also carries ``t0_ns`` and
        stays open until :meth:`close_open` gives it its whole
        interval."""
        rec = {"type": "iter", "t": _utcnow(), "iteration": int(iteration),
               "iters": int(iters), "completed": bool(completed),
               "wall_s": round(float(wall_s), 6),
               "phases": dict(phases or {}),
               "dispatch": {k: int(v) for k, v in (dispatch or {}).items()},
               "sentinel": sentinel, "oom_level": int(oom_level)}
        for k, v in fields.items():
            if v is not None:
                rec[k] = v
        with self._lock:
            self._ring.append(rec)
            if self._begun is not None:
                rec["t0_ns"], requests0, thread = self._begun
                self._open, self._begun = (rec, requests0, thread), None
            if self._early:
                for it in range(rec["iteration"],
                                rec["iteration"] + max(rec["iters"], 1)):
                    if it in self._early:
                        self._fill_ready(rec, *self._early.pop(it))
        if (self.flush_period and self.directory
                and iteration // self.flush_period != self._last_periodic):
            # durable-dir runs flush every flush_period iterations so a
            # REAL SIGKILL (which cannot flush) loses at most one
            # period. Transient: a periodic event is just a checkpoint
            # of the same ring — retaining each one would grow the file
            # and the event list linearly with run length (quadratic
            # total I/O), so only EVENT flushes are kept permanently.
            self._last_periodic = iteration // self.flush_period
            self.flush("periodic", retain_event=False)

    def note_sentinel(self, iteration: int, flags: int) -> None:
        """Back-fill a lazily-judged sentinel verdict into the record
        covering ``iteration`` (the fused path judges its in-program
        flag words iterations after the step dispatched). ``flags`` is
        the judged word: 0 = clean."""
        verdict = "ok" if not flags else f"flags=0b{int(flags):05b}"
        with self._lock:
            for rec in reversed(self._ring):
                if rec["type"] != "iter":
                    continue
                if rec["iteration"] <= iteration \
                        < rec["iteration"] + max(rec["iters"], 1):
                    rec["sentinel"] = verdict
                    return

    def begin_update(self, t0_ns: int) -> None:
        """An ``update()`` begins at ``t0_ns``: the record before it ends
        there, and the one :meth:`record` appends next starts there."""
        from . import compile_cache
        self.close_open(t0_ns)
        self._begun = (int(t0_ns), compile_cache.totals()["requests"],
                       threading.get_ident())

    def close_open(self, t1_ns: Optional[int] = None) -> None:
        """Close the open record at ``t1_ns`` (the next ``update()``
        begins) or now (training ends). It gains ``t1_ns``; ``host``, the
        self seconds by name of the training thread's spans that began in
        the interval; ``unspanned_s``, the interval less the union of
        those spans; ``gc_s``, the full collections in it on any thread;
        and ``compile_requests``, the backend compile requests since its
        ``update()`` began. Reads the process timeline only: no device
        value, no dispatch. A completed first record closes the
        timeline's set-up list."""
        from . import compile_cache
        from .utils import profiling
        with self._lock:
            if self._open is None:
                return
            rec, requests0, thread = self._open
            self._open = None
        t0 = rec["t0_ns"]
        t1 = max(int(t1_ns if t1_ns is not None else time.time_ns()), t0)
        spans = [s for s in profiling.spans_since(t0) if s["t0_ns"] < t1]
        mine = [s for s in spans if s["thread"] == thread
                and s["t0_ns"] >= t0]
        late = {"t1_ns": t1,
                "host": {k: round(v, 6) for k, v in
                         self_seconds(mine).items()},
                "unspanned_s": round(
                    (t1 - t0 - union_ns(mine, t0, t1)) * 1e-9, 6),
                "gc_s": round(sum(s["t1_ns"] - s["t0_ns"] for s in spans
                                  if s["name"] == "gc") * 1e-9, 6),
                "compile_requests": compile_cache.totals()["requests"]
                - requests0}
        with self._lock:
            rec.update(late)
        if rec["completed"]:
            profiling.close_setup()

    def _fill_ready(self, rec: dict, rows: float, seen_ns: int) -> None:
        rec["rows_streamed"] = rec.get("rows_streamed", 0.0) + rows
        rec.setdefault("ready_seen_ns", int(seen_ns))

    def note_ready(self, iteration: int, rows_cum: float,
                   seen_ns: int) -> None:
        """Back-fill the record covering ``iteration`` once the host has
        seen that iteration's outputs ready (``GBDT._flush_pending``, or
        a synchronous tree fetch): ``rows_streamed``, the rows its
        histogram passes read (``rows_cum`` is the step's cumulative
        counter; the iterations arrive in order, so the delta to the one
        before), and ``ready_seen_ns``, when that was. An upper bound on
        when the device finished: the host looks at the end of an
        ``update()`` and wherever it fetches a tree."""
        with self._lock:
            rows = float(rows_cum) - self._rows_cum
            self._rows_cum = float(rows_cum)
            for rec in reversed(self._ring):
                if rec["type"] == "iter" and rec["iteration"] <= iteration \
                        < rec["iteration"] + max(rec["iters"], 1):
                    self._fill_ready(rec, rows, seen_ns)
                    return
            if len(self._early) < 64:
                self._early[int(iteration)] = (rows, seen_ns)

    def records(self) -> List[dict]:
        """Current ring contents (oldest first; copies)."""
        with self._lock:
            return [dict(r) for r in self._ring]

    # --------------------------------------------------------- flushing
    @property
    def _filename(self) -> str:
        if self.incarnation > 0:
            return f"flight_rank{self.rank}.r{self.incarnation}.jsonl"
        return f"flight_rank{self.rank}.jsonl"

    def _resolve_path(self) -> str:
        d = self.directory
        if not d:
            # event flushes must land SOMEWHERE even when no durable dir
            # was configured — a temp dir beats losing the post-mortem
            import tempfile
            d = tempfile.mkdtemp(prefix="lgbm_flight_")
            self.directory = d
        os.makedirs(d, exist_ok=True)
        return os.path.join(d, self._filename)

    def path(self) -> Optional[str]:
        """Where this recorder flushes (None until a directory is known
        — i.e. configured, or created by the first event flush)."""
        if self._last_path:
            return self._last_path
        if self.directory:
            return os.path.join(self.directory, self._filename)
        return None

    def flush(self, reason: str, retain_event: bool = True) -> Optional[str]:
        """Write header + ring + flush events to the JSONL atomically
        and return the path (best-effort: a flush must never turn a
        crash diagnosis into a crash of its own — on failure it warns
        and returns None). Each flush appends its own event record
        first, carrying the reason and the health/scope state at flush
        time, so the LAST line of the file names what killed the run
        and which iteration was in flight. ``retain_event=False``
        (periodic checkpoint flushes) writes the event into THIS file
        but does not keep it for later flushes — retained events are
        the rare diagnostic ones (bounded at 64, oldest dropped)."""
        from . import distributed
        from .utils import profiling
        from .utils.atomic_write import atomic_write_text
        try:
            health = distributed.health_snapshot()
        except Exception:
            health = {}
        tl = profiling.timeline()
        event = {"type": "flush", "t": _utcnow(), "reason": str(reason),
                 "health": health, "scopes": profiling.scopes(),
                 "gauges": profiling.gauges(),
                 "dispatch": profiling.dispatch_stats()}
        try:
            event["timeline"] = timeline_report(
                self.records(), tl, self._context.get("num_data"))
        except Exception as e:       # noqa: BLE001 — a flush never raises
            event["timeline"] = {"error": f"{type(e).__name__}: {e}"}
        try:
            # the WHOLE flush — event append, directory resolution (which
            # may create the fallback temp dir), write, _last_path — runs
            # under the lock: the watchdog thread and the training
            # thread's error flush fire together by design, and racing
            # _resolve_path would mint two temp dirs and split the
            # post-mortem across divergent files
            with self._lock:
                if retain_event:
                    self._flushes.append(event)
                header = {"type": "run", "schema": SCHEMA_VERSION,
                          "rank": self.rank, "pid": os.getpid(),
                          "capacity": self.capacity,
                          "process_start_ns": tl["process_start_ns"],
                          "context": dict(self._context)}
                lines = [header] \
                    + [{"type": "span", **sp} for sp in tl["setup"]] \
                    + [dict(r) for r in self._ring] \
                    + [dict(f) for f in self._flushes]
                if not retain_event:
                    lines.append(event)
                path = self._resolve_path()
                atomic_write_text(path, "\n".join(
                    json.dumps(r, sort_keys=True, default=str)
                    for r in lines) + "\n")
                self._last_path = path
            return path
        except Exception as e:       # noqa: BLE001 — see docstring
            try:
                log.warning(f"flight recorder flush failed ({reason}): {e}")
            except Exception:
                pass
            return None


# process-level recorder: ONE per process (the training plane is
# process-wide — heartbeats, watchdog, degradation log all are), rebuilt
# by configure() whenever a new training run initializes
_recorder: Optional[FlightRecorder] = None
_recorder_lock = threading.Lock()


def configure(config=None) -> Optional[FlightRecorder]:
    """(Re)build the process flight recorder from config — called by
    ``GBDT._init_train`` so every training run starts with a fresh ring
    (like ``distributed.reset_degradations``). Returns the recorder, or
    None (and clears any previous one) when
    ``telemetry_flight_recorder`` is off.

    Flush directory resolution: explicit ``telemetry_dir`` param > the
    supervisor's diag-dir env (supervised gang children inherit it, so
    their post-mortems land next to the watchdog/divergence diagnoses)
    > ``checkpoint_path``/telemetry > none (event flushes then fall
    back to a temp dir)."""
    global _recorder
    get = (lambda k, d: getattr(config, k, d)) if config is not None \
        else (lambda k, d: d)
    if not bool(get("telemetry_flight_recorder", True)):
        with _recorder_lock:
            _recorder = None
        return None
    from . import distributed
    directory = str(get("telemetry_dir", "") or "")
    if not directory:
        directory = os.environ.get(distributed._DIAG_DIR_ENV, "") or ""
    if not directory:
        ck = str(get("checkpoint_path", "") or "")
        if ck:
            directory = os.path.join(ck, "telemetry")
    rec = FlightRecorder(
        capacity=int(get("telemetry_ring_size", 256)),
        directory=directory or None,
        rank=distributed.jax_rank(),
        flush_period=int(get("telemetry_flush_period", 64)),
        incarnation=int(os.environ.get(distributed._RESTART_COUNT_ENV,
                                       "0") or 0))
    with _recorder_lock:
        _recorder = rec
    return rec


def recorder() -> Optional[FlightRecorder]:
    """The live process recorder (None when disabled/never configured)."""
    return _recorder


def recorder_path() -> Optional[str]:
    """The live recorder's JSONL path, for embedding BY REFERENCE in
    health snapshots, checkpoint manifests and watchdog/divergence
    diagnoses. None when no recorder is live or no directory is known
    yet."""
    rec = _recorder
    return rec.path() if rec is not None else None


def flush_recorder(reason: str) -> Optional[str]:
    """Flush the process recorder (no-op None when there isn't one).
    For CONTEXT-FREE event paths only — the watchdog thread, the
    divergence verdict, ``faults._hard_exit`` — which have no booster
    in hand; booster-scoped paths (engine train-error/train-end, the
    OOM ladder) flush ``GBDT._flight`` directly so a multi-booster
    process (cv folds, bench probes) never flushes the wrong ring."""
    rec = _recorder
    if rec is None:
        return None
    return rec.flush(reason)


# ------------------------------------------------- JSONL validation

def validate_flight_record(rec: Dict[str, Any]) -> List[str]:
    """Schema-check one flight-recorder record; returns the list of
    violations (empty = valid)."""
    errs = []
    rtype = rec.get("type")
    if rtype not in FLIGHT_RECORD_FIELDS:
        return [f"unknown record type {rtype!r}"]
    for f in FLIGHT_RECORD_FIELDS[rtype]:
        if f not in rec:
            errs.append(f"{rtype} record missing field {f!r}")
    if rtype == "run" and rec.get("schema") != SCHEMA_VERSION:
        errs.append(f"schema {rec.get('schema')!r} != {SCHEMA_VERSION}")
    if rtype == "span" and not errs and not rec["t0_ns"] <= rec["t1_ns"]:
        errs.append(f"span {rec['name']!r} ends before it begins")
    if rtype == "iter":
        # the fields a record gains late are optional, but not shapeless
        if "t1_ns" in rec and not rec.get("t0_ns", rec["t1_ns"] + 1) \
                <= rec["t1_ns"]:
            errs.append("iter record's interval has no t0_ns <= t1_ns")
        if not isinstance(rec.get("host", {}), dict):
            errs.append("iter record's host is not {span: seconds}")
        for f in ("unspanned_s", "gc_s", "compile_requests",
                  "rows_streamed"):
            if f in rec and not (isinstance(rec[f], (int, float))
                                 and rec[f] >= 0):
                errs.append(f"iter record's {f} is not a count or a time")
    return errs


def validate_flight_jsonl(path: str):
    """Parse + schema-validate a flushed flight-recorder JSONL. Returns
    ``(records, errors)``; a valid file has a ``run`` header first, at
    least one ``flush`` event, and no per-record violations."""
    records: List[dict] = []
    errors: List[str] = []
    with open(path) as fh:
        for i, line in enumerate(fh):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError as e:
                errors.append(f"line {i + 1}: unparseable JSON ({e})")
                continue
            errors.extend(f"line {i + 1}: {m}"
                          for m in validate_flight_record(rec))
            records.append(rec)
    if not records or records[0].get("type") != "run":
        errors.append("first record is not a 'run' header")
    if not any(r.get("type") == "flush" for r in records):
        errors.append("no 'flush' event record")
    return records, errors


# ====================================================== timeline report

# the owners of the set-up's partition, innermost first where two are open
_OWNERS = ("compile", "plan", "construct", "import")


def union_ns(spans, t0: int, t1: int) -> int:
    """Nanoseconds of ``[t0, t1]`` that at least one of ``spans`` covers."""
    total, end = 0, t0
    for a, b in sorted((max(s["t0_ns"], t0), min(s["t1_ns"], t1))
                       for s in spans):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def self_seconds(spans) -> Dict[str, float]:
    """``{name: seconds}`` of ``spans``, each span's duration less its
    children's (the spans of the list whose ``parent`` it is)."""
    inside: Dict[Any, int] = {}
    for s in spans:
        inside[s["parent"]] = inside.get(s["parent"], 0) \
            + s["t1_ns"] - s["t0_ns"]
    out: Dict[str, float] = {}
    for s in spans:
        own = s["t1_ns"] - s["t0_ns"] - inside.get(s["id"], 0)
        out[s["name"]] = out.get(s["name"], 0.0) + max(own, 0) * 1e-9
    return out


def owned_seconds(spans, t0: int, t1: int,
                  loop_from: Optional[int] = None) -> Dict[str, float]:
    """Partition ``[t0, t1]``: every instant has ONE owner, the innermost
    open span (the one that began last) among ``compile``, ``plan``,
    ``construct`` and ``import``; else ``loop`` from ``loop_from`` on;
    else ``unspanned``. So a program that compiles inside ``construct``
    is ``compile``'s, and a plan computed inside the first update is
    ``plan``'s and not ``loop``'s. ``{owner: seconds}``, summing to the
    interval."""
    own = [s for s in spans if s["name"] in _OWNERS
           and s["t1_ns"] > t0 and s["t0_ns"] < t1]
    cuts = {t0, t1}
    if loop_from is not None and t0 < loop_from < t1:
        cuts.add(loop_from)
    for s in own:
        cuts.update((max(s["t0_ns"], t0), min(s["t1_ns"], t1)))
    out = dict.fromkeys(_OWNERS + ("loop", "unspanned"), 0.0)
    cuts = sorted(cuts)
    for a, b in zip(cuts, cuts[1:]):
        open_ = [s for s in own if s["t0_ns"] <= a and s["t1_ns"] >= b]
        if open_:
            name = max(open_, key=lambda s: s["t0_ns"])["name"]
        else:
            name = "loop" if loop_from is not None and a >= loop_from \
                else "unspanned"
        out[name] += (b - a) * 1e-9
    return out


def timeline_report(records: Optional[List[dict]] = None,
                    timeline: Optional[Dict[str, Any]] = None,
                    num_data: Optional[int] = None) -> Dict[str, Any]:
    """Where a run's time went, from inside: the process timeline
    (``profiling.timeline()``) and the flight recorder's iteration
    records (``records``; the live recorder's by default, with its
    ``num_data``), or those of a flushed file.

    - ``setup``: ``[process start, end of the first completed
      iteration]`` partitioned by :func:`owned_seconds` into ``import``,
      ``construct``, ``plan``, ``step_build`` (every ``compile`` span:
      trace + lower + backend), ``loop`` (from the first
      ``fused_dispatch``'s start) and ``unspanned`` (what no library
      span covers: the interpreter, jax's import and backend start, the
      caller's own code), with ``interval_s`` their sum,
      ``construct_children`` (seconds by name of the spans under a
      ``construct``) and ``compile`` (by program: its stages' seconds
      and the persistent cache's outcome).
    - ``iterations``: the completed iterations after the first by PASSES
      (``rows_streamed`` over the rows held, rounded; ``"?"`` until the
      late field arrived): ``{n, median_s, max_s}`` of their intervals.
    - ``stalled``: every iteration whose interval exceeds 1.25 times its
      class's median: its interval, the median, the span with the most
      self seconds, ``unspanned_s``, ``gc_s`` and ``compile_requests``.

    A span never waits for the device, so device work a stage leaves
    running shows in the first later span that does wait (``callbacks``
    where a callback blocks on the score; ``tree_fetch`` in a first
    iteration)."""
    from .utils import profiling
    tl = timeline if timeline is not None else profiling.timeline()
    if records is None:
        rec = _recorder
        records = rec.records() if rec is not None else []
        if num_data is None and rec is not None:
            num_data = rec._context.get("num_data")
    spans = list(tl["setup"]) + list(tl["ring"])
    iters = [r for r in records if r.get("type") == "iter"
             and r.get("completed") and "t1_ns" in r]
    out: Dict[str, Any] = {"setup": None, "iterations": {}, "stalled": []}
    if spans or iters:
        t0 = tl.get("process_start_ns") or min(
            [s["t0_ns"] for s in spans] + [r["t0_ns"] for r in iters])
        t1 = iters[0]["t1_ns"] if iters else max(s["t1_ns"] for s in spans)
        first = [s["t0_ns"] for s in spans if s["name"] == "fused_dispatch"]
        loop_from = min(first) if first else (
            iters[0]["t0_ns"] if iters else None)
        parts = owned_seconds(spans, t0, t1, loop_from)
        parts["step_build"] = parts.pop("compile")
        by_id = {s["id"]: s for s in spans}
        children: Dict[str, list] = {}
        programs: Dict[str, dict] = {}
        for s in spans:
            if s["t0_ns"] >= t1:
                continue
            if s["name"] == "compile":
                at = programs.setdefault(
                    str(s["attrs"].get("program")), {"outcome": None})
                key = f"{s['attrs'].get('stage')}_s"
                at[key] = round(at.get(key, 0.0)
                                + (s["t1_ns"] - s["t0_ns"]) * 1e-9, 6)
                if s["attrs"].get("stage") == "backend":
                    at["outcome"] = s["attrs"].get("outcome") or "built"
                continue
            up = by_id.get(s["parent"])
            while up is not None and up["name"] != "construct":
                up = by_id.get(up["parent"])
            if up is not None and s["name"] not in ("construct", "gc"):
                children.setdefault(s["name"], []).append(s)
        out["setup"] = {
            "interval_s": round((t1 - t0) * 1e-9, 6),
            **{k: round(v, 6) for k, v in parts.items()},
            "construct_children": {
                k: round(union_ns(v, t0, t1) * 1e-9, 6)
                for k, v in sorted(children.items())},
            "compile": programs}
    classes: Dict[str, list] = {}
    for r in iters[1:]:
        rows = r.get("rows_streamed")
        key = str(round(rows / num_data / max(r["iters"], 1))) \
            if rows is not None and num_data else "?"
        classes.setdefault(key, []).append(r)
    for key, recs in sorted(classes.items()):
        secs = sorted((r["t1_ns"] - r["t0_ns"]) * 1e-9 / max(r["iters"], 1)
                      for r in recs)
        median = secs[len(secs) // 2] if len(secs) % 2 else \
            0.5 * (secs[len(secs) // 2 - 1] + secs[len(secs) // 2])
        out["iterations"][key] = {"n": len(secs),
                                  "median_s": round(median, 6),
                                  "max_s": round(secs[-1], 6)}
        for r in recs:
            dt = (r["t1_ns"] - r["t0_ns"]) * 1e-9 / max(r["iters"], 1)
            if dt > 1.25 * median:
                host = r.get("host") or {}
                out["stalled"].append({
                    "iteration": r["iteration"], "passes": key,
                    "interval_s": round(dt, 6),
                    "class_median_s": round(median, 6),
                    "largest_span": max(host, key=host.get) if host
                    else None,
                    "largest_span_s": max(host.values()) if host else None,
                    "unspanned_s": r.get("unspanned_s"),
                    "gc_s": r.get("gc_s"),
                    "compile_requests": r.get("compile_requests")})
    out["stalled"].sort(key=lambda e: e["iteration"])
    return out


def report_of_file(path: str) -> Dict[str, Any]:
    """:func:`timeline_report` of a flushed flight file: its ``span``
    records are the timeline's set-up list, its header has the process
    start and the rows held."""
    records, _errors = validate_flight_jsonl(path)
    header = records[0] if records else {}
    tl = {"process_start_ns": header.get("process_start_ns"),
          "setup": [r for r in records if r.get("type") == "span"],
          "ring": []}
    return timeline_report(
        records, tl, (header.get("context") or {}).get("num_data"))


# ====================================================== scope table

# Programs of the hot path, held weakly: (weakref to the owner, lower).
# ``lower(owner)`` returns the program's ``jitted.lower(...)`` with the
# arguments it is dispatched with (or None once it is gone). The owner (a
# GBDT, a PredictEngine) adds its programs when it builds them; nothing
# is lowered until scope_table() asks.
_programs: List[tuple] = []
_programs_lock = threading.Lock()
# id of a loaded executable -> (the executable, module, {instr: (scope,
# shape)}): the parse of one compiled program, kept with its executable
# so that the id cannot be reused
_scope_cache: Dict[int, tuple] = {}

_HLO_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)
_HLO_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_HLO_INSTR_RE = re.compile(r"^\s+(ROOT\s+)?%?([^\s=]+)\s*=\s*(.*)$")
_HLO_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS_RE = re.compile(r"\bcalls=%?([^\s,)}]+)")
_HLO_LAYOUT_RE = re.compile(r"\{[^{}]*\}")
_HLO_OPERAND_RE = re.compile(r"%([^\s,(){}]+)")
_HLO_PLUMBING = ("copy", "bitcast", "tuple", "get-tuple-element")


def register_program(owner, lower) -> None:
    """Add a program of the hot path to the process-level registry behind
    :func:`scope_table`. ``owner`` is held weakly (the entry dies with
    it); ``lower(owner)`` returns ``jitted.lower(*args)`` for the
    arguments the program is dispatched with, or None for a program that
    is gone."""
    import weakref
    with _programs_lock:
        _programs[:] = [p for p in _programs if p[0]() is not None]
        _programs.append((weakref.ref(owner), lower))


def scope_of(op_name: str) -> Optional[str]:
    """The device scope of an HLO ``op_name`` path: its last component
    that is in ``profiling.SCOPES`` (scopes nest under jax's own
    ``while/body``, ``cond/branch_1_fun`` components and under each
    other; the innermost wins). None where there is none."""
    from .utils import profiling
    for part in reversed(op_name.split("/")):
        if part in profiling.SCOPES:
            return part
    return None


def parse_hlo_scopes(text: str):
    """``(module name, {instruction: (scope or None, shape)})`` of a
    compiled program's text (``compiled.as_text()``): every instruction
    of every computation, fused computations' inner instructions
    included. ``shape`` is the result shape with layouts taken out, for
    telling two programs' instructions of one name apart. The rules:

    - an instruction's scope is :func:`scope_of` its own ``op_name``;
    - a fusion without one takes its root's scope, else the scope most
      of its fused instructions carry;
    - what the COMPILER made (no ``op_name`` at all, or pure plumbing:
      copy, bitcast, tuple, get-tuple-element) takes the scope most of
      its users carry, else most of its operands: a layout copy belongs
      to the phase that needed it. Code of the program that sits outside
      every scope keeps None."""
    m = _HLO_MODULE_RE.search(text)
    module = m.group(1) if m else "<unknown>"
    scope: Dict[str, Optional[str]] = {}
    shape: Dict[str, str] = {}
    fused: Dict[str, str] = {}          # fusion -> its computation
    operands: Dict[str, List[str]] = {}
    made: List[str] = []                # compiler-made or plumbing
    members: Dict[str, List[str]] = {}  # computation -> instruction names
    roots: Dict[str, str] = {}
    comp = None
    for line in text.splitlines():
        if comp is None:
            cm = _HLO_COMPUTATION_RE.match(line)
            if cm:
                comp = cm.group(1)
                members[comp] = []
            continue
        if line.startswith("}"):
            comp = None
            continue
        im = _HLO_INSTR_RE.match(line)
        if not im:
            continue
        is_root, name, rest = im.groups()
        om = _HLO_OP_NAME_RE.search(rest)
        body = _HLO_LAYOUT_RE.sub("", rest.split(", metadata=")[0])
        # the result shape ends where the opcode begins: at the first
        # space outside a tuple's parentheses
        depth, cut = 0, len(body)
        for i, ch in enumerate(body):
            depth += ch == "("
            depth -= ch == ")"
            if ch == " " and depth == 0:
                cut = i
                break
        opcode = body[cut + 1:].split("(", 1)[0]
        scope[name] = scope_of(om.group(1)) if om else None
        shape[name] = body[:cut]
        operands[name] = _HLO_OPERAND_RE.findall(body[cut:])
        if opcode == "fusion":
            calls = _HLO_CALLS_RE.search(rest)
            if calls:
                fused[name] = calls.group(1)
        if (om is None and opcode not in ("parameter", "constant")) \
                or opcode in _HLO_PLUMBING:
            made.append(name)
        members[comp].append(name)
        if is_root:
            roots[comp] = name

    def most(names):
        votes = [scope[n] for n in names if scope.get(n) is not None]
        return max(sorted(set(votes)), key=votes.count) if votes else None

    for name, comp in fused.items():
        if scope[name] is None and comp in members:
            scope[name] = scope.get(roots.get(comp)) or most(members[comp])
    users: Dict[str, List[str]] = {}
    for name, ops in operands.items():
        for op in ops:
            if op in scope:
                users.setdefault(op, []).append(name)
    changed = True
    while changed:          # plumbing chains: copy -> bitcast -> user
        changed = False
        for name in reversed(made):     # users follow in the text
            if scope[name] is None:
                scope[name] = most(users.get(name, ()))
                changed |= scope[name] is not None
    scope.update({name: most(operands[name]) for name in made
                  if scope[name] is None})
    return module, {n: (scope[n], shape[n]) for n in scope}


def _compiled_scopes(lowered):
    """Compile and parse one lowered program. With the executable in
    memory (the program has run) this is a lookup. The persistent
    cache's key leaves metadata out, so an entry compiled by a build
    without scopes (an older library sharing the cache directory) answers
    for this program too: a compiled text with no scope at all, under a
    lowering that has some, is reported once, with the directory to
    clear, and the table is given as it is."""
    import jax
    from .utils import profiling
    compiled = lowered.compile()
    exe = compiled.runtime_executable()
    hit = _scope_cache.get(id(exe))
    if hit is not None:
        return hit[1:]
    module, table = parse_hlo_scopes(compiled.as_text())
    if not any(scope for scope, _ in table.values()):
        debug = lowered.as_text(debug_info=True)
        if any(f"/{s}/" in debug for s in profiling.SCOPES):
            log.warning(
                f"scope_table: the executable of {module} carries no scope "
                f"of its lowering: it was loaded from a persistent-cache "
                f"entry that a build without scopes wrote. Clear "
                f"{jax.config.jax_compilation_cache_dir} for a table that "
                f"names its instructions")
    if len(_scope_cache) >= 8:      # oldest out: an entry pins its program
        _scope_cache.pop(next(iter(_scope_cache)))
    _scope_cache[id(exe)] = (exe, module, table)
    return module, table


def scope_table(shapes: bool = False) -> Dict[str, Dict[str, Any]]:
    """``{hlo_module: {instruction_name: scope_or_None}}`` for every
    program the hot path dispatches in this process: the fused step (or
    block) of each live booster, the score add, the predict engine's
    traversal. A device trace names an event by its HLO instruction
    (``%fusion.10 = ...``); this is the table that says which
    ``jax.named_scope`` (``profiling.SCOPES``) the instruction came from.
    Lowers and compiles on demand and caches per program; a program that
    cannot be lowered (its owner's arguments are gone) is left out with a
    warning. ``shapes=True`` gives ``(scope, result shape)`` instead, for
    a reader that has an event's text but not its module."""
    from . import compile_cache
    with _programs_lock:
        live = [(ref(), lower) for ref, lower in _programs]
    out: Dict[str, Dict[str, Any]] = {}
    for owner, lower in live:
        if owner is None:
            continue
        try:
            # the table's lowering is no part of what compile_stats()
            # counts as the program's set-up
            with compile_cache.uncounted():
                lowered = lower(owner)
                if lowered is None:
                    continue
                module, table = _compiled_scopes(lowered)
        except Exception as e:   # noqa: BLE001 — measurement never raises
            log.warning(f"scope_table: a program could not be lowered: {e}")
            continue
        out.setdefault(module, {}).update(
            table if shapes else {n: s for n, (s, _) in table.items()})
    return out


# ==================================================== trace capture

class TraceResult:
    """Outcome of a :func:`trace_window` capture."""

    def __init__(self, trace_dir: str, iters: Optional[int]):
        self.trace_dir = trace_dir
        self.iters = iters
        self.ok = False
        self.error: Optional[str] = None

    def to_json(self) -> Dict[str, Any]:
        return {"dir": self.trace_dir, "iters": self.iters,
                "ok": self.ok, "error": self.error}


@contextmanager
def trace_window(trace_dir: str,
                 iters: Optional[int] = None) -> Iterator[TraceResult]:
    """Capture a device trace around a window of boosting iterations::

        with telemetry.trace_window(d, iters=N) as tw:
            for _ in range(N):
                booster.update()

    Drives ``jax.profiler.start_trace``/``stop_trace``. The host plane
    of the capture carries the ``lgbm:`` spans (``profiling.span``,
    always on). The device plane names events by HLO instruction, not by
    grower phase: read it against :func:`scope_table`. ``iters`` is
    metadata recorded in the result.

    Tolerant by design: a backend whose profiler cannot start (or a
    wedged stop) records ``tw.error`` instead of raising — trace
    capture is measurement, and measurement must never kill the run
    being measured. ``tw.ok`` is True only when both start and stop
    succeeded."""
    tw = TraceResult(trace_dir, iters)
    import jax
    started = False
    try:
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
        started = True
    except Exception as e:       # noqa: BLE001 — tolerance contract above
        tw.error = f"start_trace failed: {e}"
        log.warning(f"trace_window: {tw.error}")
    try:
        yield tw
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
                tw.ok = True
            except Exception as e:   # noqa: BLE001
                tw.error = f"stop_trace failed: {e}"
                log.warning(f"trace_window: {tw.error}")


def trace_files(trace_dir: str) -> List[str]:
    """Trace artifacts under a capture directory (the ``.pb``/
    ``.json.gz`` event files jax's profiler writes) — what the smoke
    test asserts non-empty to call a capture loadable."""
    out = []
    for root, _dirs, files in os.walk(trace_dir):
        for f in files:
            if f.endswith((".pb", ".json.gz", ".trace.json.gz", ".xplane.pb")):
                out.append(os.path.join(root, f))
    return sorted(out)


if __name__ == "__main__":
    # python -m lightgbm_tpu.telemetry <flight file>: the file's report
    import sys
    if len(sys.argv) != 2:
        sys.exit("usage: python -m lightgbm_tpu.telemetry <flight file>")
    print(json.dumps(report_of_file(sys.argv[1]), indent=1))
