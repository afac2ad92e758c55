"""Persistent XLA compilation cache + AOT program warmup.

The compile wall: the first boosting iteration pays the XLA and Mosaic
compile of the whole fused step (minutes at the Higgs shape on a v5e,
CHANGES.md PR 21) against a steady state of seconds — and every short
job, every supervisor gang relaunch and every hot-swap candidate
validation pays it again, because compiled executables die with the
process. This module makes compiles pay ONCE PER SHAPE, EVER:

- :func:`configure` points jax's persistent compilation cache at a
  directory: every compiled program is keyed by (HLO, backend, compile
  flags) and serialized to disk, so a SECOND process with the same shapes
  deserializes instead of compiling. ``JAX_COMPILATION_CACHE_DIR``, when
  set, names that directory and nothing in code sets another (the
  ``compile_cache_dir`` param yields to it); the entry points
  (chip_smoke.py, bench.py, bench_serve.py) otherwise use
  :func:`default_dir`, one fixed directory inside the checkout — fixed
  because the path is part of the cache key.

- :func:`aot_compile` is the explicit ``jit(...).lower(...).compile()``
  warmup used by ``GBDT.warm_start`` (fused step/block + score add) and
  ``PredictEngine.warm_aot``. jax 0.9 keeps the lowering of a jitted
  callable, and the executable compiled from it, in memory: the first
  real call with the same argument shapes issues NO compile request at
  all. The warmup therefore moves the whole compile out of the measured
  first step, and with the persistent cache configured it is also where
  a later process's disk entry gets written (or, in that later process,
  read).

- :func:`install_compile_hook` counts compile requests and
  persistent-cache outcomes per program from ``jax.monitoring``'s public
  events, so tests and bench.py can assert per-program cache behavior:
  the supervisor warm-restart regression pins "a relaunched incarnation
  performs ZERO fused-step XLA recompiles" on exactly these counters.
  The same events carry SECONDS, kept per program and per stage
  (``trace_s`` / ``lower_s`` / ``backend_s``): where the time before the
  first iteration goes when every program is already in the cache. Each
  is also a span ``compile`` of the process timeline
  (``profiling.record_span``: ``program``, ``stage``, and for a backend
  request the cache's ``outcome``), placed where the stage ended, under
  whatever span its thread had open: the totals say how much, the spans
  when and inside what.
"""

from __future__ import annotations

import os
import threading
import time
from collections import defaultdict
from contextlib import contextmanager
from typing import Dict, Optional

from .utils import log, profiling

_lock = threading.RLock()   # configure() calls install_compile_hook()
_configured_dir: Optional[str] = None
_hook_installed = False
# jax's name for the program ("jit(<function name>)") -> count.
# "requests": every backend compile request (cache outcome or not);
# "hits"/"misses": the persistent cache's answer to a request — a miss
# is counted when its entry is written; "compiles": requests that were
# not hits, i.e. real XLA builds
_stats: Dict[str, Dict[str, int]] = {
    k: defaultdict(int) for k in ("requests", "hits", "misses", "compiles")}
# seconds per program and stage, from the same listeners: "trace_s"
# (Python tracing to a jaxpr; a program traced inside another counts in
# both), "lower_s" (jaxpr to MLIR module), "backend_s" (the backend
# compile request: an XLA build or a persistent-cache load)
_secs: Dict[str, Dict[str, float]] = {
    k: defaultdict(float) for k in ("trace_s", "lower_s", "backend_s")}

# jax.monitoring event names (jax/_src/dispatch.py, compiler.py,
# compilation_cache.py). The hit/miss events fire INSIDE the timed
# compile request and carry no program name; the request's duration
# event, which names the program, closes it on the same thread.
_EV_REQUEST = "/jax/core/compile/backend_compile_duration"
_EV_STAGE = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
             "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
             _EV_REQUEST: "backend_s"}
_EV_HIT = "/jax/compilation_cache/cache_hits"
_EV_MISS = "/jax/compilation_cache/cache_misses"
_SPAN_OUTCOME = {"hits": "hit", "misses": "miss"}
_MIN_TRACE_SPAN_S = 1e-3
_pending = threading.local()


def default_dir() -> str:
    """The entry points' cache directory when ``JAX_COMPILATION_CACHE_DIR``
    is not set: ``.jax_cache`` at the root of the checkout (git-ignored)."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


def configure(config=None, cache_dir: Optional[str] = None) -> Optional[str]:
    """Enable jax's persistent compilation cache for this process.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise ``cache_dir``
    (or ``config.compile_cache_dir``), otherwise a directory already in
    jax's config. Idempotent — the first configured directory sticks for
    the process (jax initializes the cache once). Returns the active
    directory or None when caching stays disabled.

    jax's minimum entry-size/compile-time thresholds are dropped so EVERY
    program is cached — the fused step at CPU test scale compiles in
    milliseconds but must still produce the warm-start disk hit the tests
    and the gang-restart path rely on."""
    global _configured_dir
    asked = cache_dir if cache_dir is not None else \
        (getattr(config, "compile_cache_dir", "") or "")
    with _lock:
        if _configured_dir is not None:
            if asked and asked != _configured_dir:
                log.warning(
                    f"compile_cache_dir={asked!r} ignored: the persistent "
                    f"compilation cache is already configured at "
                    f"{_configured_dir!r} for this process")
            return _configured_dir
        import jax
        from jax.experimental.compilation_cache import compilation_cache
        env = os.environ.get("JAX_COMPILATION_CACHE_DIR") or ""
        if env and asked and asked != env:
            log.info(f"compile_cache_dir={asked!r} yields to "
                     f"JAX_COMPILATION_CACHE_DIR={env!r}")
        d = env or asked or jax.config.jax_compilation_cache_dir or ""
        if not d:
            return None
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
        # cache everything: the thresholds exist to bound disk churn on
        # giant fleets; here a skipped small entry is a compile the next
        # incarnation pays again
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # jax initializes the cache object ONCE, on the first compile —
        # which may already have happened (dir-less) before this call;
        # reset so the next compile re-initializes against the directory
        # just configured
        compilation_cache.reset_cache()
        _configured_dir = d
        install_compile_hook()
        log.info(f"persistent XLA compilation cache at {d}")
        return d


def configured_dir() -> Optional[str]:
    return _configured_dir


@contextmanager
def uncounted():
    """Leave this thread's compiles out of the counters and the seconds:
    for a lowering that is measurement (``telemetry.scope_table``), not
    the program's own work before its first iteration."""
    _pending.muted = True
    try:
        yield
    finally:
        _pending.muted = False


def _on_event(event: str, **_kw) -> None:
    if getattr(_pending, "muted", False):
        return
    if event == _EV_HIT:
        _pending.outcome = "hits"
    elif event == _EV_MISS:
        _pending.outcome = "misses"


def _on_duration(event: str, secs: float, fun_name: str = "<unknown>",
                 **_kw) -> None:
    stage = _EV_STAGE.get(event)
    if stage is None or getattr(_pending, "muted", False):
        return
    if stage == "trace_s":
        # the trace event names the bare function, the later stages the
        # program ("jit(<function>)"): one key for all three
        fun_name = f"jit({fun_name})"
    with _lock:
        _secs[stage][fun_name] += secs
    request = event == _EV_REQUEST
    outcome = None
    if request:
        outcome = getattr(_pending, "outcome", None)
        _pending.outcome = None
    # jax reports every function traced inside a program's trace too, a
    # thousand sub-millisecond events a fused step: those stay in the
    # totals above and out of the timeline. A backend request's outcome
    # is "hit", "miss" (built, and the entry written) or None: built with
    # no answer from a persistent cache
    if stage != "trace_s" or secs >= _MIN_TRACE_SPAN_S:
        now = time.time_ns()
        profiling.record_span("compile", now - int(secs * 1e9), now,
                              program=fun_name, stage=stage[:-2],
                              outcome=_SPAN_OUTCOME.get(outcome))
    if not request:
        return
    with _lock:
        _stats["requests"][fun_name] += 1
        if outcome is not None:
            _stats[outcome][fun_name] += 1
        if outcome != "hits":
            _stats["compiles"][fun_name] += 1


def install_compile_hook() -> bool:
    """Count compile requests and persistent-cache hits/misses per program
    by listening to ``jax.monitoring``. Idempotent; returns True. The
    listeners only increment dicts, and jax offers no way to take a
    listener back, so the hook stays for the process lifetime."""
    global _hook_installed
    with _lock:
        if not _hook_installed:
            import jax
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _hook_installed = True
    return True


def compile_stats() -> Dict[str, dict]:
    """Snapshot of the per-program counters and stage seconds:
    ``{"requests": {name: n}, "hits": {...}, "misses": {...},
    "compiles": {...}, "trace_s": {name: seconds}, "lower_s": {...},
    "backend_s": {...}}`` (empty until :func:`install_compile_hook`).
    Monotonic — diff two snapshots to scope a measurement."""
    with _lock:
        out: Dict[str, dict] = {k: dict(v) for k, v in _stats.items()}
        out.update({k: dict(v) for k, v in _secs.items()})
        return out


def totals() -> Dict[str, int]:
    """Aggregate request/hit/miss/compile counts across programs."""
    with _lock:
        return {k: sum(v.values()) for k, v in _stats.items()}


def module_count(kind: str, prefix: str) -> int:
    """Sum a counter over program names starting with ``prefix`` (jax
    names a program after its jitted function: the fused per-iteration
    step is ``jit(_fused_step)``, the K-block ``jit(_fused_block)``)."""
    with _lock:
        return sum(n for name, n in _stats[kind].items()
                   if name.startswith(prefix))


def aot_compile(jitted, args, label: str = "program",
                static_kwargs: Optional[dict] = None) -> bool:
    """AOT-compile a jitted callable for the given argument pytree (any
    mix of concrete arrays/scalars and ``jax.ShapeDtypeStruct``s — the
    concrete leaves are abstracted in place, so callers can hand over
    live trainer state without uploading or mutating anything).
    ``static_kwargs`` are passed through to ``lower`` for jits with
    static keyword parameters. Failures are logged and swallowed:
    warmup is an optimization, never a correctness dependency."""
    import jax
    import jax.numpy as jnp

    def _abstract(x):
        if x is None or isinstance(x, jax.ShapeDtypeStruct):
            return x
        return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))

    try:
        sds = jax.tree.map(_abstract, args)
        jitted.lower(*sds, **(static_kwargs or {})).compile()
        return True
    except Exception as e:
        log.warning(f"AOT warmup of {label} failed (will compile lazily "
                    f"on first call): {e}")
        return False
