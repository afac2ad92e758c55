"""Tracing and measurement, host side and device side.

Three kinds of names, with different costs:

- **Device scopes** (:data:`SCOPES`): ``jax.named_scope`` around the phases
  inside the compiled programs (the fused step, the score add, the predict
  traversal). A scope is metadata on the HLO: it costs nothing at run time
  and cannot be switched off. A device trace names an event by its HLO
  instruction (``fusion.10``), not by its scope; ``telemetry.scope_table()``
  is the join between the two.
- **Host spans** (:func:`span`): ``jax.profiler.TraceAnnotation("lgbm:" +
  name)`` around what the host does, AND a record ``{id, parent, name,
  t0_ns, t1_ns, thread, attrs}`` in one process timeline
  (:func:`timeline`), so that a run without a profiler session can still
  say where its time went. Set-up: ``import`` (the package's own import),
  ``construct`` and its children (``to_float``, ``find_bins``,
  ``bin_rows``, ``shard_place``, the sparse construct's
  ``efb_fit_mappers``, ``efb_find_bundles``, ``efb_place``,
  ``sparse_extract``, the streaming construct's ``sketch_pass``,
  ``bin_pass``, ``h2d_overlap``), ``plan`` (a booster's set-up and the
  build of a fused step's operands), ``compile`` (one a stage of every
  program jax traces, lowers and compiles or loads: ``compile_cache``
  records it after the fact through :func:`record_span`) and ``gc`` (a
  generation-2 collection). The iteration: ``fused_dispatch``,
  ``score_dispatch``, ``tree_fetch``, ``sentinel_drain``,
  ``flight_record``, ``callbacks``, ``eval``, and the spans the TIMETAG
  scopes below open. Always on, never a sync, never a dispatch: a span
  costs two clock reads, an annotation (a flag test outside a profiler
  session) and an append. The clock is ``time.time_ns()``, which is the
  clock of the profiler's host plane (``profile_start_time`` of the
  trace's ``Task Environment`` plane plus an event's start;
  tests/test_timeline.py holds the two together), so a timeline and a
  device trace of one run lay over each other. A span never waits for
  the device: work a stage leaves running shows in the first later span
  that does wait. Storage is bounded: a ring of the newest
  ``RING_SPANS`` spans and a list of at most ``SETUP_SPANS`` spans that
  ended before the first completed iteration (:func:`close_setup`),
  kept for the post-mortem of a slow start. ``telemetry
  .timeline_report()`` is the reader. Predict and serve have no spans
  yet: they come with the benchmark cell that reads them.
- **TIMETAG scopes** (:func:`timer`, ``LIGHTGBM_TPU_TIMETAG`` or
  :func:`enable`): the analog of the reference's ``Common::Timer`` table
  under ``USE_TIMETAG`` (include/LightGBM/utils/common.h:953-1037). When
  enabled, scope exit BLOCKS on the values passed to ``sync`` and the wall
  time accumulates into :func:`scopes`. They time the unfused / phased
  path (``fused_iteration=false``, ``grow_tree_phased``) with one device
  sync per scope; they cannot see inside the fused step.
"""

from __future__ import annotations

import functools
import gc
import itertools
import os
import threading
import time
from collections import defaultdict, deque
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional

_enabled = os.environ.get("LIGHTGBM_TPU_TIMETAG", "") not in ("", "0")
# One lock over every aggregate table below. The scopes/counters used to
# be bare defaultdict read-modify-writes, which was fine while only the
# training thread touched them — but the serve dispatcher thread, the
# watchdog thread and the flight recorder all read/update these now, and
# a racing `_acc[k] += v` can lose an update (the read and the store are
# separate bytecodes). RLock because table()/scopes() may be called from
# a flush that already holds it via the recorder.
_lock = threading.RLock()
_acc: Dict[str, float] = defaultdict(float)
_cnt: Dict[str, int] = defaultdict(int)
# named value counters (work counts rather than wall time): the analog of
# the reference's global_timer also carrying histogram-construction counts;
# used for the compaction telemetry (rows streamed per histogram pass)
_counters: Dict[str, float] = defaultdict(float)
_counter_cnt: Dict[str, int] = defaultdict(int)


# every jax.named_scope in the library uses one of these names, and
# telemetry.scope_of reads an HLO op_name against them ("the last component
# that is a scope wins"); tests/test_trace_scopes.py holds the two together
SCOPES = ("gradients", "tile_select", "rung_gather", "hist_pass",
          "split_search", "apply_split", "finalize_tree", "score_update",
          "hist_allreduce", "split_sync", "predict_traverse",
          # the ranking objectives' stages, nested under "gradients"
          "rank_sort", "rank_pairs", "rank_scatter",
          # sparse device columns: their planes (grower.combine_sparse,
          # nested under "hist_pass") and the branch of a split on a
          # stream column, which rebuilds the column from its stream
          # (_apply_split.route, nested under "apply_split")
          "sparse_hist", "sparse_route")
SPAN_PREFIX = "lgbm:"


# ------------------------------------------------------------- timeline
# One process timeline of finished spans, on time.time_ns(). Appends are
# single deque/list operations (atomic under the interpreter lock) and the
# id counter is itertools.count: no lock on the span path.
RING_SPANS = 4096
SETUP_SPANS = 1024
_ring: deque = deque(maxlen=RING_SPANS)
_setup: List[dict] = []
_setup_open = True
_next_id = itertools.count(1).__next__
_open = threading.local()       # .stack: ids of the spans open on a thread
_annotation = None              # jax.profiler.TraceAnnotation, on first use


def _stack() -> list:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


def _keep(rec: dict) -> None:
    if _setup_open and len(_setup) < SETUP_SPANS:
        _setup.append(rec)
    else:
        _ring.append(rec)


class span:
    """Host span: a ``TraceAnnotation`` named ``lgbm:<name>`` that also
    keeps its own time. On exit the record ``{id, parent, name, t0_ns,
    t1_ns, thread, attrs}`` joins the process timeline; ``parent`` is the
    span that was open on this thread. Always on, never syncs.
    ``attrs``: a few small values that say which (``program``, ``stage``,
    ``outcome``, ``iteration``)."""

    __slots__ = ("name", "attrs", "id", "t0_ns", "t1_ns", "_ann")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.t0_ns = self.t1_ns = 0

    def __enter__(self):
        global _annotation
        if _annotation is None:
            import jax
            _annotation = jax.profiler.TraceAnnotation
        self._ann = _annotation(SPAN_PREFIX + self.name)
        self._ann.__enter__()
        self.id = _next_id()
        _stack().append(self.id)
        self.t0_ns = time.time_ns()
        return self

    def __exit__(self, *exc):
        self.t1_ns = time.time_ns()
        stack = _stack()
        # an exit out of order (a generator closed late) still leaves the
        # stack whole: drop this span and whatever it left open above it
        if self.id in stack:
            del stack[stack.index(self.id):]
        _keep({"id": self.id, "parent": stack[-1] if stack else None,
               "name": self.name, "t0_ns": self.t0_ns, "t1_ns": self.t1_ns,
               "thread": threading.get_ident(), "attrs": self.attrs})
        return self._ann.__exit__(*exc)

    @property
    def seconds(self) -> float:
        """The finished span's duration."""
        return (self.t1_ns - self.t0_ns) * 1e-9


def record_span(name: str, t0_ns: int, t1_ns: int, **attrs) -> None:
    """A span whose duration arrived after the fact (a compile stage's
    seconds, a collection's): its parent is the span open on this thread
    now, and the spans of this thread that ended inside it under that
    same parent become its children (a function traced inside another's
    trace reports first). No annotation: the profiler has no event in
    the past."""
    stack = _stack()
    rec = {"id": _next_id(), "parent": stack[-1] if stack else None,
           "name": name, "t0_ns": int(t0_ns), "t1_ns": int(t1_ns),
           "thread": threading.get_ident(), "attrs": attrs}
    for held in (_ring, _setup):
        try:
            for older in reversed(held):
                if older["t1_ns"] < rec["t0_ns"]:
                    break
                if (older["thread"] == rec["thread"]
                        and older["parent"] == rec["parent"]
                        and older["t0_ns"] >= rec["t0_ns"]):
                    older["parent"] = rec["id"]
        except RuntimeError:    # another thread appended meanwhile: the
            pass                # rest keep the parent they had
    _keep(rec)


def close_setup() -> None:
    """The first iteration has completed: later spans live in the ring
    only. The flight recorder calls it when it closes its first completed
    record; :func:`reset` opens the list again."""
    global _setup_open
    _setup_open = False


@functools.lru_cache(maxsize=1)
def process_start_ns() -> Optional[int]:
    """When this process started, on ``time.time_ns()``'s clock: field 22
    of ``/proc/self/stat`` (clock ticks after boot, so 10 ms resolution)
    against ``CLOCK_BOOTTIME``; read once. None without ``/proc``."""
    try:
        with open("/proc/self/stat") as fh:
            # the command name may hold spaces: fields count after ")"
            ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        since_boot = ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK")
        return (time.time_ns() - time.clock_gettime_ns(time.CLOCK_BOOTTIME)
                + since_boot)
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def timeline() -> Dict[str, Any]:
    """The process timeline: ``{"process_start_ns", "clock_offset_ns",
    "setup": [...], "ring": [...]}``, spans in the order they ended.
    ``clock_offset_ns`` is what to add to a span's time to land on the
    profiler's host plane: 0, both are ``CLOCK_REALTIME``."""
    return {"process_start_ns": process_start_ns(), "clock_offset_ns": 0,
            "setup": list(_setup), "ring": list(_ring)}


def spans_since(t0_ns: int) -> List[dict]:
    """The finished spans that ended at or after ``t0_ns``, oldest first:
    what the flight recorder reads when it closes an iteration's record.
    The lists are in order of ending, so the scan stops at the first
    older span. (The copies are one C call each: another thread may
    append meanwhile; a closed set-up list holds nothing that new.)"""
    out: List[dict] = []
    for held in (_ring, _setup) if _setup_open else (_ring,):
        for rec in reversed(list(held)):
            if rec["t1_ns"] < t0_ns:
                break
            out.append(rec)
    out.sort(key=lambda r: r["t1_ns"])
    return out


def _gc_callback(phase: str, info: dict) -> None:
    """A ``gc`` span a full collection (generation 2: the ones that take
    milliseconds to seconds with a large heap)."""
    if info.get("generation") != 2:
        return
    if phase == "start":
        _open.gc_t0 = time.time_ns()
    else:
        t0 = getattr(_open, "gc_t0", None)
        if t0 is not None:
            record_span("gc", t0, time.time_ns(),
                        collected=info.get("collected"))
            _open.gc_t0 = None


gc.callbacks.append(_gc_callback)


def enable(on: bool = True) -> None:
    global _enabled
    _enabled = on


def enabled() -> bool:
    return _enabled


def reset() -> None:
    """Clear the timer scopes, work counters, gauges and the timeline
    (its set-up list opens again).

    Deliberately does NOT touch the dispatch/transfer counters
    (``_disp``): those are MONOTONIC by contract — concurrent readers
    scope their measurements by diffing two ``dispatch_stats()``
    snapshots, and a reset between their snapshots would corrupt every
    in-flight delta. Tests that need a clean origin use
    :func:`reset_dispatch` (nothing else may)."""
    global _setup_open
    with _lock:
        _acc.clear()
        _cnt.clear()
        _counters.clear()
        _counter_cnt.clear()
        _gauges.clear()
        _mem_marks.clear()
        _ring.clear()
        del _setup[:]
        _setup_open = True


def counter(name: str, value: float) -> None:
    """Accumulate a named work counter (e.g. ``hist_rows_streamed``).
    Cheap no-op when profiling is disabled; callers should avoid forcing a
    device sync just to record one (fetch an already-synced value)."""
    if not _enabled:
        return
    with _lock:
        _counters[name] += float(value)
        _counter_cnt[name] += 1


def counters() -> Dict[str, float]:
    """Accumulated named counters (empty when profiling is disabled)."""
    with _lock:
        return dict(_counters)


def scopes() -> Dict[str, Dict[str, float]]:
    """Accumulated timer scopes as data: ``{name: {"total_s", "calls",
    "mean_ms"}}`` — what ``table()`` prints, machine-readable (bench.py's
    phase sub-scope probe and the flight recorder's per-iteration phase
    deltas both read hist_pass / split_search / apply_split out of
    this)."""
    with _lock:
        return {name: {"total_s": _acc[name], "calls": _cnt[name],
                       "mean_ms": 1e3 * _acc[name] / max(_cnt[name], 1)}
                for name in _acc}


# Health gauges: last-value-wins instruments (heartbeat age, supervisor
# restart count, per-rank last iteration) — unlike the timers/counters
# these are ALWAYS on (a restart count that only records under TIMETAG
# would be useless for postmortems) and cost one dict store.
_gauges: Dict[str, float] = {}


def set_gauge(name: str, value: float) -> None:
    """Record the current value of a named health gauge."""
    with _lock:
        _gauges[name] = float(value)


def inc_gauge(name: str, delta: float = 1.0) -> float:
    """Increment a counting gauge (serve shed/timeout counts) and return
    the new value. Runs under the module lock, so racing increments from
    serve caller threads no longer lose counts (the authoritative counts
    still live on the ServeFrontend, behind its own lock — these gauges
    mirror them into health snapshots)."""
    with _lock:
        v = _gauges.get(name, 0.0) + float(delta)
        _gauges[name] = v
        return v


def gauges() -> Dict[str, float]:
    """Current gauge values (supervisor restarts, heartbeat ages, ...)."""
    with _lock:
        return dict(_gauges)


def drop_gauges(prefix: str) -> None:
    """Remove every gauge whose name starts with ``prefix``. Gauges are
    last-value-wins and process-global, so a measurement family scoped
    to an EVENT (e.g. the ``construct_*`` gauges of one dataset
    construction) must be dropped when the next event starts — otherwise
    consumers (the flight-recorder header, ``telemetry
    .construct_snapshot``) attribute a previous event's values to the
    current one."""
    with _lock:
        for k in [k for k in _gauges if k.startswith(prefix)]:
            del _gauges[k]


# --------------------------------------------------------------- memory
# Host-side memory sampling: the device allocator's view (HBM bytes in
# use / peak, via ``Device.memory_stats()`` — a local runtime query, NOT
# a dispatch) and this process's resident set (``/proc/self/status``).
# Every reader is None-tolerant BY CONTRACT: the CPU backend returns no
# memory_stats, containers may lack /proc — a missing source records
# null, never a crash, and never disables the telemetry that carries it.

_mem_device = None              # cached default device (resolved lazily)
_mem_device_ok: Optional[bool] = None   # None = never probed
# per-scope HBM high-water marks, sampled at TIMETAG scope exits (the
# scope already synced, so the allocator state reflects the phase's work)
_mem_marks: Dict[str, int] = {}


def device_memory() -> Optional[Dict[str, int]]:
    """One sample of the default device's allocator stats:
    ``{"bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
    "peak_bytes_reserved"}`` (whichever keys the backend exposes). On the
    TPU a loaded program's temporary buffers are RESERVED, not "in use"
    (the Higgs step reserves 10.96 GB beside 2.06 GB in use, PERF.md
    section 4), and what is reserved is not free: a reader that wants
    what the job holds takes the larger of the two. None on backends
    without ``memory_stats()`` (CPU returns None) — the failed probe is
    cached so the per-iteration caller pays one attribute check, not a
    rebuild per record."""
    global _mem_device, _mem_device_ok
    if _mem_device_ok is False:
        return None
    try:
        if _mem_device is None:
            import jax
            _mem_device = jax.local_devices()[0]
        stats = _mem_device.memory_stats()
    except Exception:
        _mem_device_ok = False
        return None
    if not stats:
        _mem_device_ok = False
        return None
    _mem_device_ok = True
    out = {}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
                "peak_bytes_reserved"):
        if key in stats:
            try:
                out[key] = int(stats[key])
            except (TypeError, ValueError):
                pass
    return out or None


def _proc_status_kb(field: str) -> Optional[int]:
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith(field + ":"):
                    return int(line.split()[1])
    except (OSError, ValueError, IndexError):
        pass
    return None


def host_rss_bytes() -> Optional[int]:
    """This process's current resident set size (bytes), or None where
    /proc is unavailable."""
    kb = _proc_status_kb("VmRSS")
    return kb * 1024 if kb is not None else None


def host_rss_peak_bytes() -> Optional[int]:
    """This process's peak resident set size (VmHWM, bytes) — the
    process-lifetime host-memory watermark bench.py reports."""
    kb = _proc_status_kb("VmHWM")
    return kb * 1024 if kb is not None else None


def sample_memory() -> Dict[str, Optional[int]]:
    """The memory snapshot the flight recorder records per iteration and
    the OOM ladder attaches to every degradation event: device HBM in
    use / peak, HBM reserved / peak reserved (a loaded program's
    temporaries: see :func:`device_memory`) plus host RSS, each field
    null when its source is unavailable (CPU backend, no /proc). One
    cached-device call + one /proc read — no dispatch, no device sync."""
    dev = device_memory() or {}
    return {
        "hbm_bytes_in_use": dev.get("bytes_in_use"),
        "hbm_peak_bytes": dev.get("peak_bytes_in_use"),
        "hbm_reserved_bytes": dev.get("bytes_reserved"),
        "hbm_peak_reserved_bytes": dev.get("peak_bytes_reserved"),
        "host_rss_bytes": host_rss_bytes(),
    }


def _mark_scope_memory(name: str) -> None:
    """Record a TIMETAG scope's HBM high-water mark: sampled at scope
    exit (after the sync fetch, so the allocator reflects the phase's
    buffers). No-op on backends without memory_stats."""
    dev = device_memory()
    if not dev:
        return
    cur = dev.get("peak_bytes_in_use", dev.get("bytes_in_use"))
    if cur is None:
        return
    with _lock:
        if cur > _mem_marks.get(name, -1):
            _mem_marks[name] = cur


def memory_watermarks() -> Dict[str, int]:
    """Per-phase HBM high-water marks (scope name -> peak bytes seen at
    that scope's exits), accumulated only under TIMETAG measurement mode
    — empty on CPU and when profiling is off. Cleared by :func:`reset`
    with the scopes they annotate."""
    with _lock:
        return dict(_mem_marks)


def _sync_fetch(value) -> None:
    """Block on ``value`` (an array or pytree) — the scope-exit barrier
    both ``timer`` and ``timer_sync`` use so a measured scope covers the
    device work dispatched inside it. A failure of that work surfaces
    here, inside the scope that dispatched it."""
    if value is not None:
        import jax
        jax.block_until_ready(value)


@contextmanager
def timer(name: str, sync=None) -> Iterator[None]:
    """Named scope: always a host :func:`span`; under TIMETAG also the
    accumulating, syncing timer. ``sync``: optional array (or pytree)
    whose value is fetched at scope exit so the measured time covers the
    device work dispatched inside the scope."""
    sp = span(name)
    if not _enabled:
        with sp:
            yield
        return
    try:
        with sp:
            try:
                yield
            finally:
                _sync_fetch(sync)
    finally:
        # the seconds are the span's own: one clock for both tables
        with _lock:
            _acc[name] += sp.seconds
            _cnt[name] += 1
        # per-phase HBM watermark (measurement mode only — the scope
        # just synced, so the sample attributes to this phase)
        _mark_scope_memory(name)


class timer_sync:
    """Like ``timer`` but the sync value is produced inside the scope:
    ``with timer_sync("x") as t: ...; t.sync(arr)``."""

    def __init__(self, name: str):
        self.name = name
        self._sync = None

    def sync(self, value) -> None:
        self._sync = value

    def __enter__(self):
        self._cm = timer(self.name, None)
        self._cm.__enter__()
        return self

    def __exit__(self, *exc):
        # the fetch happens BEFORE the inner timer closes, so the scope's
        # recorded wall time covers the synced device work
        if _enabled:
            _sync_fetch(self._sync)
        return self._cm.__exit__(*exc)


# ------------------------------------------------- dispatch / host-sync
# Always-on (TIMETAG-independent) counters for compiled-program dispatches
# and explicit host<->device transfers — the telemetry behind bench.py's
# ``dispatches_per_iter`` / ``host_bytes_per_iter`` JSON fields and the
# fused-iteration regression tests. Each dispatch costs the host a launch
# and each device_get stalls it until the device has drained, so the
# per-iteration counts ARE the non-histogram overhead budget.
#
# jax has no public hook for either, so the counters wrap the funnels of
# the installed jax (0.9) that every dispatch/transfer goes through:
#   - ``pxla.ExecuteReplicated.__call__``: every compiled-program execution
#     (jitted calls AND eager op dispatches both end here);
#   - ``jax.device_get``: explicit device->host fetches (the tree-mirror
#     and score-cache reads in this codebase all use it);
#   - ``pxla.batched_device_put``: host->device array uploads (bytes are
#     counted only for host-resident inputs; device-to-device moves are
#     not transfers).
# jax's C++ pjit fastpath executes cached programs WITHOUT entering
# Python, so installing the hook also forces every call back through the
# Python dispatch path (``_get_fastpath_data -> None`` + a cache clear).
# That adds a small per-dispatch Python overhead (tens of µs — noise next
# to the ms-scale iterations this instrument measures, but NOT free):
# telemetry is a measurement MODE, installed explicitly by bench.py and
# the regression tests, never by library code.

_disp: Dict[str, int] = {"dispatches": 0, "device_gets": 0,
                         "d2h_bytes": 0, "h2d_bytes": 0}
_hook_state = False                  # hooks live
_hook_originals: Optional[tuple] = None


def install_dispatch_hook() -> bool:
    """Install the dispatch/transfer counting hooks (idempotent); returns
    True. ``uninstall_dispatch_hook`` restores the originals (tests use
    it so the fastpath bypass doesn't tax the rest of the suite)."""
    global _hook_state, _hook_originals
    if _hook_state:
        return True
    import jax
    import numpy as np
    from jax._src import pjit as pjit_mod
    from jax._src.interpreters import pxla

    orig_call = pxla.ExecuteReplicated.__call__
    orig_get = jax.device_get
    orig_bdp = pxla.batched_device_put

    def _counting_call(self, *args):
        # locked like the other aggregates: concurrent dispatches
        # (serve threads + training) must not lose increments — the
        # dispatch-budget assertions diff these counters
        with _lock:
            _disp["dispatches"] += 1
        return orig_call(self, *args)

    def _counting_get(x):
        bytes_ = sum(int(leaf.nbytes)
                     for leaf in jax.tree_util.tree_leaves(x)
                     if isinstance(leaf, jax.Array))
        with _lock:
            _disp["device_gets"] += 1
            _disp["d2h_bytes"] += bytes_
        return orig_get(x)

    def _counting_bdp(aval, sharding, xs, devices, *args, **kwargs):
        # the shards are numpy arrays or jax's typed-literal wrapper of
        # one (no ``nbytes`` there): size them from shape and dtype
        bytes_ = sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
                     for x in xs if not isinstance(x, jax.Array))
        with _lock:
            _disp["h2d_bytes"] += bytes_
        return orig_bdp(aval, sharding, xs, devices, *args, **kwargs)

    # disable the C++ pjit fastpath so cached executions re-enter Python
    # (and thus ExecuteReplicated); clear caches so fastpath entries
    # established before the hook don't bypass it
    _hook_originals = (orig_call, orig_get, orig_bdp,
                       pjit_mod._get_fastpath_data)
    pxla.ExecuteReplicated.__call__ = _counting_call
    jax.device_get = _counting_get
    pxla.batched_device_put = _counting_bdp
    pjit_mod._get_fastpath_data = lambda *args, **kwargs: None
    jax.clear_caches()
    _hook_state = True
    return True


def uninstall_dispatch_hook() -> None:
    """Restore the hooked jax internals (and clear the jit caches so
    entries established WITHOUT fastpath data don't keep paying the
    Python round trip). Counter values are preserved."""
    global _hook_state, _hook_originals
    if not _hook_state or _hook_originals is None:
        return
    import jax
    from jax._src.interpreters import pxla
    from jax._src import pjit as pjit_mod
    orig_call, orig_get, orig_bdp, orig_fp = _hook_originals
    pxla.ExecuteReplicated.__call__ = orig_call
    jax.device_get = orig_get
    pxla.batched_device_put = orig_bdp
    pjit_mod._get_fastpath_data = orig_fp
    jax.clear_caches()
    _hook_state = False
    _hook_originals = None


def dispatch_stats() -> Dict[str, int]:
    """Current cumulative counter values (all zero until
    ``install_dispatch_hook`` succeeds). Monotonic BY CONTRACT — diff two
    snapshots to scope a measurement; ``reset()`` deliberately leaves
    these alone so concurrent readers' deltas never get clobbered. Tests
    that need a clean origin use :func:`reset_dispatch`."""
    with _lock:
        return dict(_disp)


def reset_dispatch() -> None:
    """Zero the dispatch/transfer counters. FOR TESTS ONLY: library and
    measurement code must scope with ``dispatch_stats()`` deltas instead
    (``reset()`` keeps these monotonic by contract) — zeroing while any
    other reader holds a snapshot corrupts that reader's delta."""
    with _lock:
        for k in _disp:
            _disp[k] = 0


def dispatch_delta(before: Dict[str, int],
                   after: Optional[Dict[str, int]] = None) -> Dict[str, int]:
    """Counter deltas since a ``dispatch_stats()`` snapshot."""
    if after is None:
        after = dispatch_stats()
    return {k: after[k] - before.get(k, 0) for k in after}


@contextmanager
def dispatch_scope() -> Iterator[Dict[str, int]]:
    """Scoped dispatch/transfer deltas: ``with dispatch_scope() as d:
    ...`` — after the block ``d`` holds the counter deltas for the work
    dispatched inside it (all zero unless ``install_dispatch_hook`` is
    live). The one-liner bench.py and the predict-engine regression
    tests both wrap their measured region in."""
    before = dispatch_stats()
    d: Dict[str, int] = {}
    try:
        yield d
    finally:
        d.update(dispatch_delta(before))


def table() -> str:
    """Aggregated per-scope wall-time table (reference: the USE_TIMETAG
    summary printed by ~Timer, common.h:970-990), followed by the named
    work counters."""
    with _lock:
        return _table_locked()


def _table_locked() -> str:
    if not _acc and not _counters:
        return "(no timer scopes recorded)"
    lines = []
    if _acc:
        width = max(len(k) for k in _acc)
        lines.append(f"{'scope'.ljust(width)}  {'calls':>7}  "
                     f"{'total s':>10}  {'mean ms':>10}")
        for name in sorted(_acc, key=lambda k: -_acc[k]):
            n = _cnt[name]
            lines.append(f"{name.ljust(width)}  {n:>7}  "
                         f"{_acc[name]:>10.3f}  "
                         f"{1e3 * _acc[name] / max(n, 1):>10.2f}")
    if _counters:
        width = max(len(k) for k in _counters)
        lines.append(f"{'counter'.ljust(width)}  {'calls':>7}  "
                     f"{'total':>14}  {'mean':>14}")
        for name in sorted(_counters, key=lambda k: -_counters[k]):
            n = _counter_cnt[name]
            lines.append(f"{name.ljust(width)}  {n:>7}  "
                         f"{_counters[name]:>14.0f}  "
                         f"{_counters[name] / max(n, 1):>14.1f}")
    return "\n".join(lines)
