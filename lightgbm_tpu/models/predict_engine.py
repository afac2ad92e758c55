"""Device-resident batched inference engine.

TPU-native serving path for a trained ensemble (the batched analog of
GBDT::PredictRaw's per-tree loop, gbdt_prediction.cpp:13-53, and of the
on-accelerator accumulation in the GPU tree-boosting literature —
arxiv 1706.08359 §4, arxiv 1806.11248 §3.3): a full-ensemble predict is a
CONSTANT, tiny number of compiled-program dispatches with near-zero
device->host traffic.

What the engine does differently from the earlier stacked-predict path
(tree.py predict_values_stacked + host numpy accumulation):

- **On-device accumulation, in tree order.** The scan over stacked trees
  adds each tree's output to a float64 carry IN TREE ORDER, so only the
  final ``[N, K]`` result crosses to the host — not the ``[T, N]``
  per-tree value matrix (a ``T x N x 4``-byte transfer per call before).
  The addends and their order are unchanged from the host-f64 loop and no
  multiply feeds the adds (leaf values arrive pre-shrunk, biases are
  subtracted before the add), so there is no mul+add pair for XLA to
  FMA-contract: the result is BIT-IDENTICAL to the host path. Where the
  backend lacks float64, ``accum="compensated"`` falls back to two-float
  (Kahan) f32 accumulation — near-f64 error, not bit-identical.
- **Depth-bounded traversal.** Trees are walked with
  ``predict_leaf_bins_depth`` (a ``fori_loop`` whose static trip count is
  the stacked ensemble's true max leaf depth, measured once at engine
  build) instead of the data-dependent ``while_loop`` — XLA can pipeline
  and fuse across trees instead of stalling every batch on its slowest
  row.
- **Shape-bucketed compile cache.** Batch rows are padded up to
  power-of-two buckets (>= ``predict_bucket_min_rows``), so serving
  traffic with varying batch sizes hits a handful of compiled programs
  instead of recompiling per distinct N.
- **Chunked streaming.** Inputs larger than ``predict_chunk_rows`` are
  processed in row chunks with the carry fetched per chunk — the device
  never holds more than one chunk of the feature matrix.
- **Row-sharded multi-device predict.** With ``predict_sharded`` the same
  scan runs under ``shard_map`` over all visible devices (rows sharded,
  trees replicated) — per-row accumulation order is unchanged, so the
  result is bit-identical to the single-device path.

The engine is built per (booster, tree-range) by ``GBDT._predict_engine``
and also serves ``score_dataset`` (training-time eval over binned valid
matrices, with per-tree bias subtraction) and ``predict_leaf``.
"""

from __future__ import annotations

import functools
import threading
from contextlib import nullcontext
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .tree import TreeArrays, predict_leaf_bins_depth

ACCUM_MODES = ("float64", "compensated", "float32")

# Trace-time compile counters: the core functions' Python bodies run
# exactly once per jit-cache miss (a trace == an XLA compile of a new
# program), so these count real compiles. The observable behind the
# serving suite's regression tests: concurrent first-touch of one shape
# bucket compiles exactly once (the engine lock serializes it), and a
# hot-swapped model version with the same statics/bucket re-uses the
# already-compiled programs (delta == 0) — the jitted entries are
# MODULE-level, shared across every engine and model version.
TRACE_COUNTS: Dict[str, int] = {"accum": 0, "leaves": 0, "refill": 0}


def _x64_scope(accum: str):
    """Trace/execute scope for the f64 accumulation programs: a no-op when
    x64 is already enabled globally (or not needed)."""
    if accum != "float64" or jax.config.jax_enable_x64:
        return nullcontext()
    return jax.enable_x64()


def resolve_accum(mode: str) -> str:
    """Map the ``predict_accum`` param to an engine mode. ``auto`` means
    float64 — exact, bit-identical to the host-f64 accumulation (XLA
    emulates f64 adds where the hardware lacks them); ``compensated`` is
    the two-float f32 fallback for backends where even emulated f64 is
    unavailable or too slow."""
    mode = (mode or "auto").lower()
    if mode in ("auto", "float64", "f64", "double"):
        return "float64"
    if mode in ("compensated", "kahan", "twofloat"):
        return "compensated"
    if mode in ("float32", "f32", "single"):
        return "float32"
    raise ValueError(f"unknown predict_accum mode: {mode!r}")


def host_tree_depth(left_child: np.ndarray, right_child: np.ndarray,
                    num_leaves: int) -> int:
    """Max leaf depth (edge count from the root) of one tree, walked from
    the host child arrays — authoritative for the fori_loop trip count."""
    if num_leaves <= 1:
        return 0
    best = 1
    stack = [(0, 1)]
    while stack:
        node, d = stack.pop()
        for ch in (int(left_child[node]), int(right_child[node])):
            if ch >= 0:
                stack.append((ch, d + 1))
            elif d > best:
                best = d
    return best


# ----------------------------------------------------------- core programs
def _accum_core(stacked, class_of, biases, bins, missing_bin, carry, active,
                *, depth: int, k: int, use_bias: bool, use_active: bool,
                accum: str, init_zero: bool):
    """Scan over the stacked ensemble, accumulating tree outputs into the
    carry IN TREE ORDER (class ``t % k`` of tree ``t`` gets the add —
    exactly the host loop's ``out[:, t % k] += vals[t] - bias[t]``).

    No multiply feeds the accumulation adds (the active mask is applied
    with a select, not a 0/1 multiply), so XLA cannot FMA-contract a
    rounding away — see the PR 3 parity lesson in _apply_score_delta."""
    TRACE_COUNTS["accum"] += 1          # trace-time only: counts compiles
    n = bins.shape[0]
    if init_zero:
        if accum == "compensated":
            z = jnp.zeros((n,) if k == 1 else (n, k), jnp.float32)
            carry = (z, z)
        else:
            dt = jnp.float64 if accum == "float64" else jnp.float32
            carry = jnp.zeros((n,) if k == 1 else (n, k), dt)

    val_dtype = jnp.float32 if accum == "compensated" else (
        jnp.float64 if accum == "float64" else jnp.float32)

    def step(carry, xs):
        tree, c = xs[0], xs[1]
        leaf = predict_leaf_bins_depth(tree, bins, missing_bin, depth)
        v = tree.leaf_value[leaf].astype(val_dtype)
        if use_bias:
            v = v - xs[2].astype(val_dtype)
        if accum == "compensated":
            s, comp = carry
            sc = s if k == 1 else s[:, c]
            cc = comp if k == 1 else comp[:, c]
            y = v - cc
            t = sc + y
            nc = (t - sc) - y
            if use_active:
                t = jnp.where(active, t, sc)
                nc = jnp.where(active, nc, cc)
            if k == 1:
                return (t, nc), None
            return (s.at[:, c].set(t), comp.at[:, c].set(nc)), None
        col = carry if k == 1 else carry[:, c]
        new = col + v
        if use_active:
            new = jnp.where(active, new, col)
        if k == 1:
            return new, None
        return carry.at[:, c].set(new), None

    xs = (stacked, class_of) + ((biases,) if use_bias else ())
    with jax.named_scope("predict_traverse"):
        carry, _ = jax.lax.scan(step, carry, xs)
    return carry


_accum_jit = jax.jit(_accum_core, static_argnames=(
    "depth", "k", "use_bias", "use_active", "accum", "init_zero"))


def _leaves_core(stacked, bins, missing_bin, *, depth: int):
    TRACE_COUNTS["leaves"] += 1         # trace-time only: counts compiles

    def step(_, tree):
        return _, predict_leaf_bins_depth(tree, bins, missing_bin, depth)
    with jax.named_scope("predict_traverse"):
        _, leaves = jax.lax.scan(step, 0, stacked)
    return leaves


_leaves_jit = jax.jit(_leaves_core, static_argnames=("depth",))


# ------------------------------------------------- donated serve programs
# Steady-state serving re-uses two device buffers per shape bucket — the
# padded bin matrix and the accumulation carry — via buffer DONATION, so
# the serve loop never re-allocates its large operands: each flush writes
# the new rows into the donated bin buffer and the accumulation writes its
# output into the donated carry buffer (with ``init_zero`` the incoming
# carry VALUE is ignored — only its buffer is recycled). Donation is a
# no-op on backends without input-output aliasing (CPU), where passing
# donate_argnums would only emit per-program warnings — so the jits are
# built lazily, once the backend is known, with donation enabled only
# where it is implemented. Numerics are identical either way, which is
# what keeps the donated path CPU-testable.

_serve_jits: Dict[str, object] = {}
_serve_jit_lock = threading.Lock()

# first-dispatch serialization is MODULE-level to match the jitted
# programs it guards (_accum_jit is shared by every engine): two engines
# of the same ensemble shape first-touching one bucket concurrently must
# also compile it exactly once, which a per-engine lock cannot give
_first_dispatch_lock = threading.RLock()
_compiled_keys: set = set()


def _donation_ok() -> bool:
    return jax.default_backend() in ("tpu", "gpu", "cuda", "rocm")


def _refill_core(buf, rows):
    TRACE_COUNTS["refill"] += 1         # trace-time only: counts compiles
    # full-buffer overwrite that CONSUMES buf: XLA aliases the output to
    # the donated input buffer (a bare `return rows` would leave the
    # donated buffer unused — no reuse, and a warning per program)
    return jax.lax.dynamic_update_slice(buf, rows.astype(buf.dtype), (0, 0))


def _serve_refill_jit():
    with _serve_jit_lock:
        prog = _serve_jits.get("refill")
        if prog is None:
            prog = jax.jit(_refill_core,
                           donate_argnums=(0,) if _donation_ok() else ())
            _serve_jits["refill"] = prog
        return prog


def _serve_accum_jit():
    """The accumulation program with the carry operand (positional arg 5)
    donated — one jit entry shared by every engine and model version, so
    same-bucket traffic across hot swaps hits the same compiled programs."""
    with _serve_jit_lock:
        prog = _serve_jits.get("accum")
        if prog is None:
            prog = jax.jit(
                _accum_core,
                static_argnames=("depth", "k", "use_bias", "use_active",
                                 "accum", "init_zero"),
                donate_argnums=(5,) if _donation_ok() else ())
            _serve_jits["accum"] = prog
        return prog


class PredictEngine:
    """Compiled inference engine over one stacked ensemble.

    ``biases``: optional per-tree float64 bias (the boost-from-average
    fold recorded in GBDT.tree_bias) subtracted before accumulation —
    used by ``score_dataset``, off for raw prediction (the stored trees
    already carry the bias)."""

    def __init__(self, stacked: TreeArrays, k: int, num_trees: int,
                 max_depth: int, *, biases: Optional[np.ndarray] = None,
                 accum: str = "auto", bucket_min_rows: int = 1024,
                 chunk_rows: int = 0, sharded: bool = False):
        self.stacked = stacked
        self.k = int(k)
        self.T = int(num_trees)
        self.depth = int(max_depth)
        self.accum = resolve_accum(accum)
        self.bucket_min = max(int(bucket_min_rows), 16)
        self.chunk_rows = int(chunk_rows)
        self.sharded = bool(sharded) and len(jax.devices()) > 1
        self.class_of_np = (np.arange(self.T, dtype=np.int32)
                            % max(self.k, 1))
        self.biases_np = (None if biases is None
                          else np.asarray(biases, np.float64))
        self._mesh = None
        self._dev_cache: Dict[Tuple, jax.Array] = {}
        # shape-bucket program keys ever dispatched: the observable compile
        # cache the bucketing exists to keep small (same key => same arg
        # shapes + statics => guaranteed jit cache hit, no recompile)
        self._programs: Dict[Tuple, bool] = {}
        self._shard_programs: Dict[Tuple, object] = {}
        # guards every cache fill (device operands, program keys, serve
        # slots): concurrent FIRST calls from serve threads used to race
        # the fill and double-compile (or publish a half-built operand) —
        # the first dispatch of each new program key now runs under the
        # lock, warm traffic takes the lock-free fast path (reentrant:
        # accumulate -> _range_operands -> _dev nests)
        self._lock = threading.RLock()
        # serving mode (set by serving.ServeFrontend via
        # GBDT.enable_serve_mode): steady-state predicts of one chunk
        # re-use donated per-bucket device buffers instead of allocating
        # a padded bin matrix + carry per call (see _serve_chunk)
        self.serve_mode = False
        self._serve_slots: Dict[int, dict] = {}

    # ------------------------------------------------------------ shapes
    def bucket_rows(self, n: int) -> int:
        """Pad target: the smallest power-of-two bucket >= n (>= the
        configured floor), quarter-step refined above 4x the floor —
        4 buckets per octave keep the compile-cache size logarithmic in
        batch size while capping the padded-row waste at ~14% (pure
        pow2 wastes up to 2x minus one row). Rounded up to a
        device-count multiple when sharding so rows split evenly."""
        b = self.bucket_min
        while b < n:
            b <<= 1
        if b > n and b >= (self.bucket_min << 2):
            half = b >> 1
            for q in (5, 6, 7):              # 1.25x, 1.5x, 1.75x of b/2
                cand = (half * q) >> 2
                if cand >= n:
                    b = cand
                    break
        if self.sharded:
            d = len(jax.devices())
            b = -(-b // d) * d
        return b

    def _chunk_rows(self, n: int) -> int:
        if self.chunk_rows > 0:
            return self.chunk_rows
        return 1 << 22          # auto: ~4M-row chunks bound HBM residency

    # ------------------------------------------------------------ device
    def _dev(self, key, build):
        hit = self._dev_cache.get(key)
        if hit is None:
            with self._lock:
                hit = self._dev_cache.get(key)
                if hit is None:
                    hit = build()
                    self._dev_cache[key] = hit
        return hit

    def _range_operands(self, a: int, b: int, use_bias: bool):
        """(stacked, class_of, biases) device operands for tree range
        [a, b) — the full-range case reuses the engine's resident arrays
        (no per-call slicing dispatches)."""
        full = (a, b) == (0, self.T)
        stacked = self.stacked if full else jax.tree.map(
            lambda x: x[a:b], self.stacked)
        class_of = self._dev(("class_of", a, b),
                             lambda: jnp.asarray(self.class_of_np[a:b]))
        biases = None
        if use_bias and self.biases_np is not None:
            biases = self._dev(("biases", a, b, self.accum),
                               lambda: jnp.asarray(self.biases_np[a:b]))
        return stacked, class_of, biases

    def _mesh_axis(self):
        if self._mesh is None:
            from ..parallel.data_parallel import make_mesh
            self._mesh = make_mesh(axis="predict")
        return self._mesh, "predict"

    def _shard_program(self, key, statics):
        """shard_map-wrapped accumulation program (rows sharded, trees
        replicated) — bit-identical to the single-device scan because
        each row's accumulation order is unchanged."""
        prog = self._shard_programs.get(key)
        if prog is not None:
            return prog
        with self._lock:
            prog = self._shard_programs.get(key)
            if prog is not None:
                return prog
            from jax.sharding import PartitionSpec as P
            from ..parallel.learners import _shard_map
            mesh, axis = self._mesh_axis()
            row = P(axis)
            row2 = P(axis, None)
            carry_spec = row if self.k == 1 else row2
            use_bias = statics["use_bias"]
            use_active = statics["use_active"]
            init_zero = statics["init_zero"]
            in_specs = (P(), P(), P(), row2, P(),
                        P() if init_zero else carry_spec,
                        row if use_active else P())
            prog = jax.jit(_shard_map(
                functools.partial(_accum_core, **statics),
                mesh=mesh, in_specs=in_specs, out_specs=carry_spec))
            self._shard_programs[key] = prog
            return prog

    def _upload_rows(self, arr: np.ndarray, sharded: bool):
        """Host array -> device, placed row-sharded over the mesh when the
        sharded path is active."""
        if not sharded:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh, axis = self._mesh_axis()
        spec = P(axis) if arr.ndim == 1 else P(axis, *([None] * (arr.ndim - 1)))
        return jax.device_put(arr, NamedSharding(mesh, spec))

    # ----------------------------------------------------- operand prep
    def prepare_bins(self, bins, bucket: int):
        """Pad (host or device) bins to ``bucket`` rows and place them on
        device (sharded over the mesh when the sharded path is active) —
        the ONE definition of the row-pad/upload rule, shared by
        _predict_chunk, leaves() and the early-stop loop."""
        pad = bucket - bins.shape[0]
        if isinstance(bins, jax.Array):
            b = jnp.pad(bins, ((0, pad), (0, 0))) if pad else bins
            # device -> device reshard when sharding (no host round trip)
            return self._upload_rows(b, self.sharded) if self.sharded else b
        b = np.pad(bins, ((0, pad), (0, 0))) if pad else bins
        return self._upload_rows(np.ascontiguousarray(b), self.sharded)

    def make_carry(self, base: Optional[np.ndarray], bucket: int):
        """Device carry seeded from a host f64 base (None = let the
        program build zeros): row-padded, cast per the accumulation mode
        (compensated pairs the seed with a zero compensation term), and
        placed like the bins."""
        if base is None:
            return None
        with _x64_scope(self.accum):
            b = np.asarray(base, np.float64)
            pad = bucket - b.shape[0]
            if pad:
                b = np.pad(b, ((0, pad),) + ((0, 0),) * (b.ndim - 1))
            if self.accum == "compensated":
                s = self._upload_rows(b.astype(np.float32), self.sharded)
                return (s, jnp.zeros_like(s))
            dt = np.float64 if self.accum == "float64" else np.float32
            return self._upload_rows(b.astype(dt), self.sharded)

    # ------------------------------------------------------- accumulation
    def accumulate(self, bins_dev, missing_bin, carry=None, active=None,
                   tree_range: Optional[Tuple[int, int]] = None,
                   use_bias: bool = True):
        """One dispatch: scan trees [a, b) over ``bins_dev`` (already
        padded to a row bucket), adding into ``carry`` (None = zeros built
        in-program). Returns the device carry."""
        a, b = tree_range if tree_range is not None else (0, self.T)
        if b <= a:
            if carry is not None:
                return carry
            a = b = 0           # empty scan: the program just builds zeros
        with _x64_scope(self.accum):
            # operand prep INSIDE the scope: the f64 bias upload would
            # silently round to f32 outside it
            stacked, class_of, biases = self._range_operands(a, b, use_bias)
            use_bias = biases is not None
            statics = dict(depth=self.depth, k=self.k, use_bias=use_bias,
                           use_active=active is not None, accum=self.accum,
                           init_zero=carry is None)
            # the key carries the stacked operand's full shape, not just
            # the tree count: two ensembles with equal T but different
            # max leaf width are DIFFERENT jit entries, and the
            # first-dispatch serialization below must know it
            key = ("accum", bins_dev.shape, b - a,
                   tuple(np.shape(stacked.leaf_value)), self.sharded,
                   tuple(sorted(statics.items())))

            def dispatch():
                if self.sharded:
                    prog = self._shard_program(key, statics)
                    return prog(stacked, class_of, biases, bins_dev,
                                missing_bin, carry, active)
                return _accum_jit(stacked, class_of, biases, bins_dev,
                                  missing_bin, carry, active, **statics)

            self._note_program(
                key, None if self.sharded else _accum_jit,
                (stacked, class_of, biases, bins_dev, missing_bin, carry,
                 active), statics)
            if key not in _compiled_keys:
                # serialize the FIRST dispatch of each new program key:
                # jax's jit cache lookup-then-trace is not atomic, so two
                # threads first-touching one shape bucket — from the same
                # engine or from two same-shape engines — would both miss
                # and compile it twice. Warm traffic (key present =>
                # program compiled) stays lock-free.
                with _first_dispatch_lock:
                    if key not in _compiled_keys:
                        out = dispatch()
                        _compiled_keys.add(key)
                        return out
            return dispatch()

    def _note_program(self, key, jitted, args, statics: dict) -> None:
        """Count a program key of this engine and, the first time this
        engine meets it, add the program as its shapes (no arrays are
        kept) to the registry behind ``telemetry.scope_table``. The
        sharded program (``jitted`` None) is counted only."""
        if key in self._programs:
            return
        self._programs[key] = True
        if jitted is None:
            return
        from .. import telemetry
        with _x64_scope(self.accum):
            shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)

        def lower(engine):
            with _x64_scope(engine.accum):
                return jitted.lower(*shapes, **statics)

        telemetry.register_program(self, lower)

    def warm_aot(self, rows: int, n_features: int, bins_dtype,
                 missing_bin, serve: bool = False) -> bool:
        """AOT-compile the full-ensemble accumulation program for the
        row BUCKET ``rows`` pads to — the same shape-bucket key the
        predict compile cache builds on first touch, compiled via
        ``jit(...).lower(...).compile()`` without touching device data.

        ``serve``: warm the SERVE variant instead — ``_serve_accum_jit``
        with a concrete donated carry operand, the program the
        steady-state ``_serve_chunk`` loop actually dispatches (the plain
        variant builds its carry in-program from ``carry=None``; the two
        are different HLO modules, so warming one does not warm the
        other). ``ServeFrontend.register`` warms the
        ``serve_max_batch_rows`` bucket through this before traffic.

        With the persistent compilation cache configured
        (``compile_cache_dir``), a fresh process warms its buckets from
        DISK here instead of paying the XLA compile on the first
        full-size batch. Sharded engines skip (their shard_map wrappers
        are built per mesh at dispatch)."""
        if self.sharded:
            return False
        from .. import compile_cache
        with _x64_scope(self.accum):
            stacked, class_of, biases = self._range_operands(0, self.T,
                                                             True)
            statics = dict(depth=self.depth, k=self.k,
                           use_bias=biases is not None, use_active=False,
                           accum=self.accum, init_zero=True)
            bucket = self.bucket_rows(int(rows))
            bins_sds = jax.ShapeDtypeStruct((bucket, int(n_features)),
                                            np.dtype(bins_dtype))
            if serve:
                shape = (bucket,) if self.k == 1 else (bucket, self.k)
                if self.accum == "compensated":
                    s = jax.ShapeDtypeStruct(shape, jnp.float32)
                    carry_sds = (s, s)
                else:
                    dt = jnp.float64 if self.accum == "float64" \
                        else jnp.float32
                    carry_sds = jax.ShapeDtypeStruct(shape, dt)
                return compile_cache.aot_compile(
                    _serve_accum_jit(),
                    (stacked, class_of, biases, bins_sds, missing_bin,
                     carry_sds, None),
                    label="predict_engine serve accum",
                    static_kwargs=statics)
            return compile_cache.aot_compile(
                _accum_jit,
                (stacked, class_of, biases, bins_sds, missing_bin,
                 None, None),
                label="predict_engine accum", static_kwargs=statics)

    def fetch(self, carry, n: int) -> np.ndarray:
        """Slice off the row padding and fetch the result — the ONLY
        device->host transfer of a predict: ``n * K * itemsize`` bytes."""
        s = carry[0] if self.accum == "compensated" else carry
        with _x64_scope(self.accum):    # eager f64 slice needs the scope
            return np.asarray(jax.device_get(s[:n]), np.float64)

    # ------------------------------------------------------------ predict
    def predict(self, bins, missing_bin, *, base: Optional[np.ndarray] = None,
                use_bias: bool = True, postprocess=None,
                tree_range: Optional[Tuple[int, int]] = None) -> np.ndarray:
        """Full predict over a host (or device) bin matrix: row-chunked,
        bucket-padded, accumulated on device; returns the host ``[n, K]``
        (or ``[n]``) result. ``base``: optional f64 initial scores
        (score_dataset's init-score seed). ``postprocess``: an
        already-jitted device fn applied to the padded carry before the
        fetch (objective output conversion)."""
        n = bins.shape[0]
        chunk = self._chunk_rows(n)
        outs = []
        for a0 in range(0, max(n, 1), chunk):
            b0 = min(n, a0 + chunk)
            outs.append(self._predict_chunk(
                bins[a0:b0], missing_bin,
                None if base is None else base[a0:b0],
                postprocess, tree_range, use_bias))
        return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)

    def _predict_chunk(self, bins, missing_bin, base, postprocess,
                       tree_range, use_bias) -> np.ndarray:
        n = bins.shape[0]
        if (self.serve_mode and base is None and not self.sharded
                and self.T > 0 and not isinstance(bins, jax.Array)
                and (tree_range is None
                     or tuple(tree_range) == (0, self.T))):
            return self._serve_chunk(bins, missing_bin, postprocess,
                                     use_bias)
        bucket = self.bucket_rows(n)
        bins_dev = self.prepare_bins(bins, bucket)
        carry = self.make_carry(base, bucket)
        carry = self.accumulate(bins_dev, missing_bin, carry,
                                tree_range=tree_range, use_bias=use_bias)
        if postprocess is not None:
            with _x64_scope(self.accum):
                s = carry[0] if self.accum == "compensated" else carry
                # keep the conversion's own dtype (f32 unless x64 is on
                # globally — the dtype the legacy host conversion returned)
                return np.asarray(jax.device_get(postprocess(s)[:n]))
        return self.fetch(carry, n)

    # ----------------------------------------------------- serve (donated)
    def _fresh_carry(self, bucket: int):
        """Zero carry buffer in the accumulation dtype — the cold seed of
        a serve slot (its VALUE is ignored under ``init_zero``; only its
        buffer is donated and recycled). Caller holds the x64 scope."""
        shape = (bucket,) if self.k == 1 else (bucket, self.k)
        if self.accum == "compensated":
            return (jnp.zeros(shape, jnp.float32),
                    jnp.zeros(shape, jnp.float32))
        dt = jnp.float64 if self.accum == "float64" else jnp.float32
        return jnp.zeros(shape, dt)

    def _serve_chunk(self, bins, missing_bin, postprocess,
                     use_bias) -> np.ndarray:
        """Steady-state serving predict of one host-bin chunk: the padded
        bin matrix and the carry live in per-bucket slots whose device
        buffers are DONATED back to the next flush, so the serve loop's
        large allocations happen once per bucket, not once per call.
        Bit-identical to the ordinary chunk path — the host staging array
        keeps rows beyond the current batch at zero (exactly np.pad), and
        per-row accumulation never reads another row. Runs under the
        engine lock for its whole duration: a donated buffer is invalid
        the moment the next program consumes it, so two threads in one
        slot would read freed buffers — with the lock they serialize."""
        n = bins.shape[0]
        bucket = self.bucket_rows(n)
        with self._lock, _x64_scope(self.accum):
            stacked, class_of, biases = self._range_operands(
                0, self.T, use_bias)
            use_bias = biases is not None
            statics = dict(depth=self.depth, k=self.k, use_bias=use_bias,
                           use_active=False, accum=self.accum,
                           init_zero=True)
            slot = self._serve_slots.get(bucket)
            if slot is not None and (
                    slot["staging"].shape[1] != bins.shape[1]
                    or slot["staging"].dtype != bins.dtype):
                slot = None          # feature width/dtype changed: go cold
            skey = ("serve", (bucket, bins.shape[1]),
                    tuple(np.shape(stacked.leaf_value)),
                    bool(slot is None), tuple(sorted(statics.items())))
            # the serve programs are module-level jits too: their FIRST
            # dispatch per signature takes the same module lock as
            # accumulate's — two same-shape engines (two frontends) must
            # compile each serve program exactly once. Safe with the held
            # engine lock: serve engines are never sharded, so no path
            # acquires an engine lock while holding the module lock.
            guard = _first_dispatch_lock if skey not in _compiled_keys \
                else nullcontext()
            try:
                with guard:
                    if slot is None:
                        staging = np.zeros((bucket, bins.shape[1]),
                                           bins.dtype)
                        staging[:n] = bins
                        bins_dev = jnp.asarray(staging)
                        carry = self._fresh_carry(bucket)
                    else:
                        staging = slot["staging"]
                        staging[:n] = bins
                        if slot["rows"] > n:
                            # stale rows from the previous (larger) batch
                            # must read as padding zeros, exactly np.pad
                            staging[n:slot["rows"]] = 0
                        bins_dev = _serve_refill_jit()(slot["bins"],
                                                       staging)
                        carry = slot["carry"]
                    self._note_program(
                        skey, _serve_accum_jit(),
                        (stacked, class_of, biases, bins_dev, missing_bin,
                         carry, None), statics)
                    carry = _serve_accum_jit()(stacked, class_of, biases,
                                               bins_dev, missing_bin,
                                               carry, None, **statics)
                    _compiled_keys.add(skey)
                self._serve_slots[bucket] = {
                    "staging": staging, "bins": bins_dev, "carry": carry,
                    "rows": n}
            except BaseException:
                # donation may have invalidated the old buffers mid-call
                # (e.g. a RESOURCE_EXHAUSTED between the refill and the
                # accumulate): drop the slot so the next call goes cold
                self._serve_slots.pop(bucket, None)
                raise
            if postprocess is not None:
                s = carry[0] if self.accum == "compensated" else carry
                return np.asarray(jax.device_get(postprocess(s)[:n]))
            return self.fetch(carry, n)

    def release_serve_slots(self) -> None:
        """Drop the donated per-bucket serve buffers (the owning frontend
        closed): staging arrays and device bins/carry go back to the
        allocator; the next serve-mode predict simply goes cold."""
        with self._lock:
            self._serve_slots.clear()

    # ------------------------------------------------------------- leaves
    def leaves(self, bins, missing_bin,
               tree_range: Optional[Tuple[int, int]] = None,
               n_rows: Optional[int] = None) -> np.ndarray:
        """[t, n] int32 per-tree leaf indices over the range, via the same
        depth-bounded stacked scan (one dispatch; the [t, n] transfer is
        inherent to the predict_leaf API). Callers looping tree-range
        chunks should ``prepare_bins`` ONCE and pass the resident device
        array with ``n_rows`` = the true row count — the bin matrix is
        then uploaded once, not once per chunk."""
        a, b = tree_range if tree_range is not None else (0, self.T)
        n = bins.shape[0] if n_rows is None else n_rows
        bins_dev = bins if (isinstance(bins, jax.Array)
                            and bins.shape[0] == self.bucket_rows(n)) \
            else self.prepare_bins(bins, self.bucket_rows(n))
        stacked = self.stacked if (a, b) == (0, self.T) else jax.tree.map(
            lambda x: x[a:b], self.stacked)
        key = ("leaves", bins_dev.shape, b - a, self.depth)
        self._note_program(key, _leaves_jit,
                           (stacked, bins_dev, missing_bin),
                           dict(depth=self.depth))
        leaves = _leaves_jit(stacked, bins_dev, missing_bin,
                             depth=self.depth)
        return np.asarray(jax.device_get(leaves[:, :n]))
