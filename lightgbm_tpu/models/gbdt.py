"""GBDT boosting orchestrator.

TPU-native analog of the reference boosting layer (reference:
src/boosting/gbdt.cpp GBDT): per-iteration flow mirrors GBDT::TrainOneIter
(gbdt.cpp:369-452):

  boost_from_average (first iter, gbdt.cpp:344-367)
  -> objective gradients (Boosting(), gbdt.cpp:170-179)
  -> bagging (gbdt.cpp:228-262; mask-based here to keep shapes static)
  -> per-class tree growth (models/grower.py)
  -> RenewTreeOutput (objective leaf refresh, gbdt.cpp:433)
  -> Shrinkage (gbdt.cpp:411 tree->Shrinkage(lr))
  -> UpdateScore train + valid (gbdt.cpp:369-452; out-of-bag rows included,
     gbdt.cpp:434-452)

Trees are stored both as device arrays (stacked lazily for batched ensemble
prediction) and as host ``HostTree`` objects for model IO/SHAP.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..basic import Dataset
from ..config import Config
from ..metrics import Metric, create_metric, default_metric_for_objective
from ..objectives import ObjectiveFunction, create_objective
from ..ops.split import SplitParams
from ..utils import log, profiling
from .grower import GrowAux, grow_tree
from .tree import (HostTree, TreeArrays, predict_leaf_bins,
                   predict_leaf_bins_depth, predict_value_bins, stack_trees)


import functools


# profiling counters that mirror a tree's GrowAux counters (rows_streamed,
# coll_bytes, leaves_resolved, sync_calls), in that order
_AUX_COUNTERS = ("hist_rows_streamed", "hist_coll_bytes",
                 "hist_leaves_resolved", "split_sync_calls")
# the TIMETAG counters GBDT._fetch_tree keeps of the trees it fetches
TREE_COUNTERS = ("splits", "cat_splits", "nan_splits", "nan_left_splits",
                 "tree_leaves", "trees")

# bit -> source name of the fused step's in-program sentinel flag word
# (see _fused_step_fn: packed NaN/Inf bits computed inside the compiled
# program and fetched with the iteration's own results)
_SENTINEL_SOURCES = (
    (0, "gradients"),
    (1, "hessians"),
    (2, "histogram sums (in-program, Pallas/XLA histogram path)"),
    (3, "leaf outputs"),
    (4, "score delta"),
)


def _chunk_iters_cap(n: int, k: int, itemsize: int) -> int:
    """Iterations per stacked-predict dispatch so the [t, n, k] host buffer
    stays under ~256 MB."""
    return max(1, (256 << 20) // itemsize // max(n * k, 1))


def _chunked_tree_ranges(start_it: int, end_it: int, k: int, n: int,
                         itemsize: int):
    """Yield (a, b) TREE ranges covering [start_it, end_it) iterations in
    buffer-capped chunks (shared by the stacked value/leaf predict paths)."""
    cap = _chunk_iters_cap(n, k, itemsize)
    it = start_it
    while it < end_it:
        ce = min(end_it, it + cap)
        yield it * k, ce * k
        it = ce


@functools.partial(jax.jit, static_argnames=("n",))
def _bagging_mask(key: jax.Array, frac, n: int) -> jax.Array:
    """0/1 bagging mask drawn on device (gbdt.cpp:228-262 Bagging)."""
    u = jax.random.uniform(key, (n,))
    return (u < frac).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n",))
def _bagging_mask_rows(key: jax.Array, frac, row_start, n: int) -> jax.Array:
    """Bagging mask for pre-partitioned runs, keyed per GLOBAL row
    (fold_in(key, global_row) -> one uniform draw each): every row's
    keep/drop decision depends only on the period key and the row's global
    index, never on how rows are split across processes — so a gang
    resumed at a DIFFERENT world size re-derives the exact same sample
    the original partition drew (checkpoint.py's elastic resume)."""
    rows = row_start + jnp.arange(n)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(key, rows)
    u = jax.vmap(lambda k: jax.random.uniform(k, ()))(keys)
    return (u < frac).astype(jnp.float32)


@jax.jit
def _linear_valid_delta(leaf: jax.Array, leaf_value: jax.Array,
                        const: jax.Array, W: jax.Array, used: jax.Array,
                        raw: jax.Array) -> jax.Array:
    """Linear-leaf tree output for valid rows, on device (the device analog
    of ModelTree.predict's linear branch: const + coeff.x, rows with
    NaN/inf in any of their leaf's linear features fall back to the plain
    leaf value, linear_tree_learner.cpp:19-41)."""
    oh = jax.nn.one_hot(leaf, const.shape[0], dtype=jnp.float32)   # [N, L]
    finite = jnp.isfinite(raw)
    raw0 = jnp.where(finite, raw, 0.0)
    w_row = jax.lax.dot_general(oh, W, (((1,), (0,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST)
    contrib = jnp.sum(w_row * raw0, axis=1)
    used_row = jax.lax.dot_general(oh, used, (((1,), (0,)), ((), ())),
                                   precision=jax.lax.Precision.HIGHEST)
    bad = jnp.sum(used_row * (~finite).astype(jnp.float32), axis=1) > 0
    return jnp.where(bad, leaf_value[leaf], const[leaf] + contrib)


@functools.partial(jax.jit, static_argnames=("k",))
def _bagging_subset(key: jax.Array, bins: jax.Array, k: int):
    """Exact-k bagging selection + subset copy (gbdt.cpp:810-818 /
    Dataset::CopySubrow): the k rows with the smallest random draws are
    gathered into a compact [K, F] matrix so histogram passes scale with
    the bagging fraction instead of full N."""
    n = bins.shape[0]
    r = jax.random.bits(key, (n,), jnp.uint32)
    sub_idx = jnp.argsort(r)[:k].astype(jnp.int32)
    mask = jnp.zeros((n,), jnp.float32).at[sub_idx].set(1.0)
    sub_bins = jnp.take(bins, sub_idx, axis=0)
    return mask, sub_idx, sub_bins, sub_bins.T


def _fma_guard(x: jax.Array, salt_u32: jax.Array) -> jax.Array:
    """Value-preserving rounding fence: bitcast ``x`` to uint32, XOR with
    a RUNTIME-ZERO salt the compiler cannot prove zero, bitcast back.

    Why it exists: inside one compiled program XLA's CPU/TPU backends
    contract a multiply feeding an add into an FMA whose single rounding
    drifts 1 ulp from the two-rounding sequence — and they do it even
    across ``optimization_barrier`` and through a gather whose operand is
    the multiply (both verified here; the PR 3 lesson that forced the
    score add into its own program). The K-block scan cannot split the
    program (the score is its carry), so this fence breaks the FLOAT
    dataflow instead: the multiply's result must round to a concrete f32
    bit pattern to enter the integer domain, and no fmul-fadd pattern
    survives for the backend to contract. The salt (e.g. ``it0 < -1`` on
    a non-negative operand) is what stops the algebraic simplifier from
    cancelling the bitcast pair and re-exposing the multiply."""
    xi = jax.lax.bitcast_convert_type(x, jnp.uint32)
    xi = jnp.bitwise_xor(xi, salt_u32)
    return jax.lax.bitcast_convert_type(xi, jnp.float32)


@functools.partial(jax.jit, donate_argnums=(0,))
def _apply_score_delta(score: jax.Array, delta: jax.Array) -> jax.Array:
    """Score-cache update for the fused iteration, as its OWN tiny program
    with the score buffer DONATED: the add writes in place instead of
    allocating a fresh [N, K] cache every iteration. Kept separate from
    the fused grow program on purpose — inside one XLA loop fusion the
    backend contracts the leaf-value*lr multiply and this add into an FMA
    whose single rounding drifts 1 ulp from the unfused path (observed on
    CPU even across an optimization_barrier), breaking the fused-vs-
    unfused bit-parity the suite asserts. ``delta`` arrives [N] (one
    class) or [K, N] (the fused multiclass scan's stacked layout); the
    column-disjoint adds are bit-identical to the unfused per-class
    ``at[:, c].add`` sequence."""
    with jax.named_scope("score_update"):
        return score + (delta.T if delta.ndim == 2 else delta)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("depth", "kk"))
def _apply_valid_tree(score: jax.Array, tree: TreeArrays, bins: jax.Array,
                      missing_bin: jax.Array, class_idx, depth: int,
                      kk: int) -> jax.Array:
    """Per-iteration valid-score update as ONE compiled program with the
    score cache DONATED (in-place add): depth-bounded traversal + leaf
    gather + add — the training-time eval leg of the inference engine.
    Previously this was an eager predict_value_bins per tree per valid
    set (an op-by-op dispatch chain); now eval-on-valid costs one
    dispatch. No multiply feeds the add (leaf values arrive pre-shrunk),
    so there is no FMA-contraction parity hazard (see _apply_score_delta)
    and the result is bit-identical to the eager path."""
    with jax.named_scope("predict_traverse"):
        leaf = predict_leaf_bins_depth(tree, bins, missing_bin, depth)
    with jax.named_scope("score_update"):
        delta = tree.leaf_value[leaf]
        if kk > 1:
            return score.at[:, class_idx].add(delta)
        return score + delta


def _shrink_tree(tree: TreeArrays, lr: float) -> TreeArrays:
    """Apply the learning rate to a tree's value-bearing fields
    (Tree::Shrinkage, tree.h:187). Works on device or host-mirrored
    TreeArrays — the single definition both finalize paths share."""
    return tree._replace(leaf_value=tree.leaf_value * lr,
                         node_value=tree.node_value * lr,
                         shrinkage=tree.shrinkage * lr)


class GBDT:
    """Gradient Boosting Decision Tree (reference: gbdt.h:42, boosting.h:27)."""

    name = "gbdt"
    average_output = False

    def __init__(self, config: Config, train_set: Optional[Dataset] = None,
                 objective: Optional[ObjectiveFunction] = None):
        self.config = config
        self.train_set = train_set
        self.objective = objective
        self.trees: List[TreeArrays] = []       # device trees, leaf_value shrunk
        self._host_trees: List[HostTree] = []
        # host-mirror pipeline: device trees whose host fetch is in flight
        # (index into _host_trees, device TreeArrays). See host_trees below.
        # each with its iteration and the step's cumulative rows-streamed
        # scalar, for the flight record's late fields.
        self._pending_host: List[Tuple[int, TreeArrays, int, jax.Array]] = []
        # lagged no-split stop: count splitless flushed trees PER ITERATION
        # group (tree index // num_tree_per_iteration) — the reference stop
        # condition is one whole iteration without a split, so the count
        # must not straddle iteration boundaries
        self._splitless_group = -1
        self._splitless_in_group = 0
        self._lagged_stop = False    # a full splitless iteration was flushed
        self.num_class = max(config.num_class, 1)
        self.num_tree_per_iteration = 1
        self.init_scores: List[float] = []
        self.tree_bias: List[float] = []   # bias folded into each stored tree
        self.iter = 0
        # continued training: a LoadedGBDT whose trees precede ours
        # (reference: gbdt.h num_init_iteration_, engine.py:163-169)
        self.loaded = None
        self.loaded_iters = 0
        # fused-iteration compile cache: static-options tuple (see
        # _fused_step_fn's key) -> (jitted step, dataset-constant bind).
        # Bounded: parallel-learner binds pin padded full-dataset copies,
        # so stale entries from reset_parameter sweeps must be evicted
        self._fused_cache: Dict[tuple, tuple] = {}
        # (learner, forced-splits, padded dataset bind) per binsT flavor —
        # see _fused_parallel_bindings
        self._fused_bind_cache: Dict[bool, tuple] = {}
        self._mt_cache: Dict[int, object] = {}   # host-tree idx -> ModelTree
        self._valid_raw_cache: Dict[int, jax.Array] = {}
        self._stacked_cache: Optional[Tuple[int, TreeArrays]] = None
        # device inference engines keyed by tree count; each entry records
        # the stacked pytree it was built from, so a stacked-cache refresh
        # (new trees, shuffle, rollback, restore) invalidates it by identity
        self._engine_cache: Dict[int, Tuple[TreeArrays, object]] = {}
        # guards engine-cache fill/eviction: two serve threads first-
        # touching a booster used to both build an engine and race the
        # bounded eviction (reentrant — _predict_engine can re-enter via
        # the stacked-cache refresh)
        self._engine_lock = threading.RLock()
        self.valid_sets: List[Dataset] = []
        self.valid_names: List[str] = []
        self._valid_scores: List[jax.Array] = []
        self._valid_programs_registered: set = set()    # scope_table
        self.metric_names: List[str] = []
        self.best_score: Dict[str, Dict[str, float]] = {}
        # OOM degradation ladder state (see _maybe_degrade_oom): how many
        # rungs this booster has stepped down, and the resulting overrides.
        # Rides the trainer state so a resumed incarnation keeps the
        # degraded (numerics-relevant) configuration — the bit-identical-
        # restart contract.
        self._oom_level = 0
        self._oom_block = 0            # rung 1: forced smaller hist block
        self._oom_hm: Optional[str] = None   # rung 2: forced XLA fallback
        self._oom_predict_chunk = 0    # rung 3: forced predict chunk rows
        # deferred in-program sentinel words from the fused path: FIFO of
        # (iteration, device flag scalar), judged as their steps complete
        # (_drain_sentinels, non-blocking) so the fetch never stalls the
        # dispatch pipeline; flushed blockingly at every state-capture
        # point (_flush_sentinel)
        self._sentinel_pending: List[tuple] = []
        if train_set is not None:
            self._init_train(train_set)

    # ------------------------------------------------- host-tree pipeline
    @property
    def host_trees(self) -> List["HostTree"]:
        """Host mirrors of ``self.trees``. In the lazy fast path the mirror
        fetch is ASYNC (copy_to_host_async at dispatch time) and pending
        slots hold None until consumed here — every reader goes through
        this property, so no consumer can observe a placeholder. The point:
        a blocking ``jax.device_get`` per iteration makes the host wait
        for the device to drain and serializes the dispatch pipeline;
        deferring it lets XLA queue iterations
        back-to-back (the same reason the reference keeps its tree on the
        training thread and only serializes at save time)."""
        self._flush_sentinel()
        self._flush_pending()
        return self._host_trees

    def _flush_pending(self, only_ready: bool = False) -> None:
        """Materialize pending host mirrors in FIFO order. With
        ``only_ready`` stop at the first tree whose device computation has
        not finished (non-blocking progress check for the lagged no-split
        stop signal)."""
        while self._pending_host:
            idx, tree_dev, it, rows_dev = self._pending_host[0]
            if only_ready:
                try:
                    if not tree_dev.num_leaves.is_ready():
                        break
                except AttributeError:   # backend without is_ready()
                    break
            self._pending_host.pop(0)
            t_host = self._fetch_tree(tree_dev)
            self._note_ready(it, rows_dev)
            self._host_trees[idx] = self._make_host_tree(t_host)
            # the reference stops when an iteration can add no split
            # (gbdt.cpp:404-435); lagged detection: a full iteration of
            # flushed splitless trees arms the stop flag (group = the
            # iteration this tree belongs to; a whole iteration takes the
            # same lazy/sync path, so a flushed group is complete)
            group = idx // self.num_tree_per_iteration
            if group != self._splitless_group:
                self._splitless_group = group
                self._splitless_in_group = 0
            if int(t_host.num_leaves) <= 1:
                self._splitless_in_group += 1
                if self._splitless_in_group >= self.num_tree_per_iteration:
                    self._lagged_stop = True

    def _lazy_host_ok(self, sentinels: bool = False) -> bool:
        """Whether this iteration can defer the host tree fetch: nothing in
        the iteration itself needs host-side tree data. First iteration
        stays synchronous (boost-from-average bias fold + the TIMETAG
        first-iter sample); leaf-renewal objectives rewrite leaf values on
        host before the score update; linear trees fit on host.
        ``sentinels``: the fused path's in-program numerics sentinels
        already cover the leaf outputs, so check_numerics no longer forces
        the synchronous host-mirror fetch there (the unfused path keeps
        it: its leaf check reads the host mirror in _finalize_tree)."""
        return (self._supports_lazy_host
                and self.iter >= 1
                and not self.config.linear_tree
                # check_numerics inspects each tree's leaf outputs in
                # _finalize_tree, which the lazy path skips — unless the
                # in-program sentinels are doing that job
                and not (self.config.check_numerics and not sentinels)
                and not (self.objective is not None
                         and self.objective.need_renew_tree_output))

    _supports_lazy_host = True   # DART/RF override: they touch host trees
    _rows_streamed_dev = 0.0     # overwritten per-train; float for loaded
                                 # boosters that never trained here
    _coll_bytes_dev = 0.0        # ditto (collective-volume telemetry)
    _leaves_resolved_dev = 0.0   # ditto (tile-fill telemetry)
    _sync_calls_dev = 0.0        # ditto (best-split syncs, parallel learners)
    cat_splits_total = 0         # splits on a categorical column, and all
    splits_total = 0             # splits, of the trees fetched so far
                                 # (counted where the set has categorical
                                 # columns: _fetch_tree)
    _fault_plan = None           # set per-train (utils/faults injection)
    _flight = None               # per-train flight recorder (telemetry.py);
                                 # None for loaded boosters / when disabled
    _mem_telemetry = True        # per-iteration memory sampling gate
                                 # (telemetry_memory param)
    _bag_stale = False           # fused iterations draw bagging in-program;
                                 # the host mask re-derives on next use
    _score_program_registered = False   # telemetry.scope_table registry
    _serve_mode = False          # ServeFrontend registration flips it on:
                                 # engines built for this booster keep
                                 # donated per-bucket serve buffers

    def enable_serve_mode(self, on: bool = True) -> None:
        """Serving mode for this booster's inference engines: steady-state
        predicts re-use donated per-bucket device buffers (bin matrix +
        carry) instead of allocating per call — see
        predict_engine._serve_chunk. Applied to already-cached engines
        too (the frontend may register a booster that has predicted)."""
        self._serve_mode = bool(on)
        with self._engine_lock:
            for _, eng in self._engine_cache.values():
                eng.serve_mode = self._serve_mode
                if not self._serve_mode:
                    eng.release_serve_slots()

    # ------------------------------------------------------------ setup
    def _init_train(self, train_set: Dataset) -> None:
        train_set.construct()
        # everything a booster prepares before its first step, a ranking
        # objective's bucket plan and the O(N) score and label uploads
        # included, is the span "plan"
        with profiling.span("plan"):
            self._plan_train(train_set)

    def _plan_train(self, train_set: Dataset) -> None:
        from .. import distributed
        from ..utils import faults
        cfg = self.config
        self._fault_plan = faults.plan_from(cfg)
        # a fresh training run starts with a clean process-level
        # degradation log: this booster's health snapshots / checkpoint
        # manifests must not inherit an earlier booster's OOM events
        distributed.reset_degradations()
        # per-iteration flight recorder (telemetry.py): a fresh ring per
        # training run, fed from host-side values only in train_one_iter
        # (the resolved-context header fills lazily at the first record)
        from .. import telemetry
        self._flight = telemetry.configure(cfg)
        # per-iteration memory telemetry (profiling.sample_memory rides
        # the flight record): device HBM in-use/peak + host RSS, each
        # field null on backends without memory_stats (the None-tolerance
        # contract) — one cached-device call + one /proc read, never a
        # dispatch
        self._mem_telemetry = bool(getattr(cfg, "telemetry_memory", True))
        # persistent XLA compile cache (compile_cache_dir): pay each
        # program compile once per shape EVER, not once per process
        from .. import compile_cache
        compile_cache.configure(cfg)
        # with or without a cache directory, every program's trace, lower
        # and backend stage is a "compile" span of the process timeline
        compile_cache.install_compile_hook()
        # pre-partitioned mode (distributed.load_partitioned): bins are a
        # global row-sharded array; labels/weights/scores/gradients stay
        # PROCESS-LOCAL (the reference's per-machine score partition,
        # score_updater.hpp) and only the tree + histograms cross hosts
        self._pre_part = bool(getattr(train_set, "is_pre_partitioned",
                                      False))
        if self._pre_part:
            if cfg.tree_learner not in ("data", "voting"):
                log.fatal("pre-partitioned Datasets shard rows: set "
                          "tree_learner=data or voting")
            if cfg.linear_tree:
                log.fatal("linear_tree is not supported with "
                          "pre-partitioned Datasets (raw features are not "
                          "retained)")
        self._setup_learner_features(train_set)
        if cfg.linear_tree and self.name in ("dart", "rf"):
            log.fatal(f"linear_tree is not supported with boosting={self.name}")
        if cfg.linear_tree and train_set.raw_data_np is None:
            log.fatal("linear_tree requires the Dataset's raw data: construct "
                      "the Dataset with linear_tree in its params (a Dataset "
                      "constructed without it did not retain raw features)")
        if self.objective is None:
            self.objective = create_objective(cfg)
        label = train_set.get_label()
        weight = train_set.get_weight()
        if self.objective is not None:
            self.objective.init(label, weight, train_set.get_group())
            self.num_tree_per_iteration = self.objective.num_model_per_iteration
            if cfg.linear_tree and self.objective.need_renew_tree_output:
                log.fatal(f"objective {cfg.objective} is not supported with "
                          f"linear_tree")
        else:
            self.num_tree_per_iteration = max(cfg.num_class, 1)
        # scores cover the PROCESS-LOCAL rows in pre-partitioned mode
        n = (train_set.num_local_data if self._pre_part
             else train_set.num_data)
        self._n_score_rows = n
        k = self.num_tree_per_iteration
        self._score_shape = (n, k) if k > 1 else (n,)
        # boost_from_average init scores (gbdt.cpp:333-367)
        self.init_scores = [0.0] * k
        if self.objective is not None and cfg.boost_from_average:
            for c in range(k):
                self.init_scores[c] = float(self.objective.boost_from_score(c))
            if self._pre_part and jax.process_count() > 1:
                # mean of the per-machine local init scores, the
                # reference's GlobalSyncUpByMean (gbdt.cpp:338-341
                # ObtainAutomaticInitialScore), bit-exact in f64
                from ..distributed import allgather_f64
                all_scores = allgather_f64(np.asarray(self.init_scores))
                self.init_scores = [float(v)
                                    for v in all_scores.mean(axis=0)]
        if (self._pre_part and self.objective is not None
                and self.objective.need_renew_tree_output):
            log.warning("pre-partitioned training: L1-style leaf "
                        "renewal uses each process's local partition "
                        "(the reference syncs only mean-based renewals)")
        init = train_set.init_score
        # the auto init score is folded as a bias into the first tree of each
        # class (gbdt.cpp:414-416 AddBias) UNLESS a user init score is set
        # (gbdt.cpp:348 has_init_score check)
        self._fold_init_bias = (init is None and cfg.boost_from_average
                                and self.objective is not None)
        if init is not None:
            base = np.asarray(init, dtype=np.float32).reshape(self._score_shape)
        else:
            base = np.broadcast_to(
                np.asarray(self.init_scores, dtype=np.float32),
                (n, k)).reshape(self._score_shape) if k > 1 else \
                np.full((n,), self.init_scores[0], dtype=np.float32)
        self.train_score = jnp.asarray(np.ascontiguousarray(base))
        self.shrinkage_rate = cfg.learning_rate
        self.split_params = SplitParams.from_config(cfg)
        # metric setup: one instance per (metric, dataset), created lazily
        self.metric_names = [nm for nm in (cfg.metric or
                                           default_metric_for_objective(cfg.objective))]
        self._metric_cache: Dict[Tuple[str, int], Metric] = {}
        # feature-fraction rng (seed per config.h:307); bagging/GOSS draws
        # come from the device PRNG keyed on bagging_seed
        self._feat_rng = np.random.RandomState(cfg.feature_fraction_seed)
        self._bag_mask = jnp.ones((n,), dtype=jnp.float32)
        self._bag_sub = None
        # compaction / collective telemetry: rows read by histogram passes
        # and histogram-plane collective bytes, accumulated ON DEVICE so
        # the lazy dispatch pipeline never syncs for them (reading the
        # properties below does)
        self._rows_streamed_dev = jnp.float32(0.0)
        self._coll_bytes_dev = jnp.float32(0.0)
        self._leaves_resolved_dev = jnp.float32(0.0)
        self._sync_calls_dev = jnp.float32(0.0)
        self.cat_splits_total = self.splits_total = 0
        self._need_bagging = (cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0) or \
            (cfg.pos_bagging_fraction < 1.0 or cfg.neg_bagging_fraction < 1.0)

    def _setup_learner_features(self, train_set: Dataset) -> None:
        """Static learner-feature flags + arrays for the grower (monotone,
        interaction constraints, CEGB, extra-trees, per-node sampling)."""
        cfg = self.config
        f = train_set.num_used_features()
        used = train_set.used_features
        self._with_monotone = any(int(m) != 0
                                  for m in (cfg.monotone_constraints or []))
        # static used-space indices of monotone-constrained features (the
        # intermediate-mode pair masks are built only for these)
        if self._with_monotone:
            mono_np = np.asarray(train_set.feature_meta.monotone)
            self._mono_features = tuple(int(i)
                                        for i in np.nonzero(mono_np)[0])
        else:
            self._mono_features = ()
        self._mono_mode = "basic"
        if self._with_monotone:
            method = cfg.monotone_constraints_method
            if method in ("intermediate", "advanced"):
                self._mono_mode = method
                # exact output bounds are recomputed from all leaf outputs
                # each phase, which requires strict one-split-per-phase
                # growth (matching the reference's re-search-after-update,
                # monotone_constraints.hpp:565)
                log.warning(
                    f"monotone_constraints_method={self._mono_mode} forces "
                    "strict one-split-per-phase growth: one histogram round "
                    "per split, ~num_leaves/log2(num_leaves) x the batched "
                    "mode's data passes (use 'basic' for speed)")
            elif method not in ("basic",):
                log.warning(f"monotone_constraints_method={method} is not "
                            f"implemented; falling back to basic")
        self._with_interactions = bool(cfg.interaction_constraints)
        self._interaction_groups = None
        if self._with_interactions:
            orig_to_used = {int(j): i for i, j in enumerate(used)}
            groups = np.zeros((len(cfg.interaction_constraints), f), bool)
            for gi, grp in enumerate(cfg.interaction_constraints):
                for j in grp:
                    if int(j) in orig_to_used:
                        groups[gi, orig_to_used[int(j)]] = True
            self._interaction_groups = jnp.asarray(groups)
        # CEGB enable rule (cost_effective_gradient_boosting.hpp:26-33)
        cegb_enabled = (cfg.cegb_tradeoff < 1.0 or cfg.cegb_penalty_split > 0.0
                        or cfg.cegb_penalty_feature_coupled
                        or cfg.cegb_penalty_feature_lazy)
        self._cegb_mode = "off"
        self._cegb_coupled = None
        self._cegb_lazy = None
        # cross-iteration CEGB tracking survives reset_config (the reference
        # Init() keeps its state once init_ is true)
        self._cegb_aux = getattr(self, "_cegb_aux", None)
        if cegb_enabled:
            for name, lst in (("cegb_penalty_feature_coupled",
                               cfg.cegb_penalty_feature_coupled),
                              ("cegb_penalty_feature_lazy",
                               cfg.cegb_penalty_feature_lazy)):
                if lst and len(lst) != train_set.num_total_features:
                    log.fatal(f"{name} should be the same size as feature "
                              f"number ({train_set.num_total_features})")
            self._cegb_mode = "lazy" if cfg.cegb_penalty_feature_lazy else "feat"
            if cfg.cegb_penalty_feature_coupled:
                arr = np.zeros((f,), np.float32)
                for i, j in enumerate(used):
                    if j < len(cfg.cegb_penalty_feature_coupled):
                        arr[i] = cfg.cegb_penalty_feature_coupled[j]
                self._cegb_coupled = jnp.asarray(arr)
            if cfg.cegb_penalty_feature_lazy:
                arr = np.zeros((f,), np.float32)
                for i, j in enumerate(used):
                    if j < len(cfg.cegb_penalty_feature_lazy):
                        arr[i] = cfg.cegb_penalty_feature_lazy[j]
                self._cegb_lazy = jnp.asarray(arr)
        self._use_bynode = cfg.feature_fraction_bynode < 1.0
        self._extra_rng_key = jax.random.PRNGKey(cfg.extra_seed)
        # gpu_use_dp analog: float64 histogram accumulation (the reference
        # CPU's hist_t precision; bin.h:32) — requires jax x64
        self._hist_dp = bool(cfg.gpu_use_dp)
        if cfg.quantized_grad and self._hist_dp:
            # checked on the CONFIG flags, before the x64-availability
            # demotion below — the contradiction is in what was asked for
            raise ValueError(
                "quantized_grad and gpu_use_dp are exclusive: int8 "
                "histograms with stochastic rounding and f64 accumulation "
                "contradict each other — pick one precision model")
        if self._hist_dp and not jax.config.jax_enable_x64:
            log.warning("gpu_use_dp=true needs jax x64 (set JAX_ENABLE_X64=1 "
                        "or jax.config.update('jax_enable_x64', True)); "
                        "falling back to float32 histograms")
            self._hist_dp = False
        self._forced_splits = self._load_forced_splits(train_set)
        self._setup_tree_learner()

    def _load_forced_splits(self, ts: Dataset):
        """Parse forcedsplits_filename JSON into flat preorder arrays for the
        grower's forced phase (reference: serial_tree_learner.cpp:450
        ForceSplits; format {"feature": i, "threshold": v, "left": {...},
        "right": {...}})."""
        fn = self.config.forcedsplits_filename
        if not fn:
            return None
        import json
        from .. import binning
        try:
            with open(fn) as fh:
                data = json.load(fh)
        except OSError:
            log.warning(f"Could not open forced splits file {fn}. "
                        f"Will ignore.")
            return None
        if not data:
            return None
        ts.construct()
        if ts.bundles is not None:
            col_of = {}
            for gi, bd in enumerate(ts.bundles):
                if len(bd.members) == 1:
                    col_of[int(ts.used_features[bd.members[0]])] = gi
        else:
            col_of = {int(j): i for i, j in enumerate(ts.used_features)}
        nodes: List[List[int]] = []

        def rec(node) -> int:
            orig = int(node["feature"])
            col = col_of.get(orig)
            m = ts.mappers[orig] if orig < len(ts.mappers) else None
            if (col is None or m is None
                    or m.bin_type != binning.BIN_TYPE_NUMERICAL):
                log.warning(f"forced split on feature {orig} ignored "
                            f"(unused, bundled or categorical)")
                return -1
            idx = len(nodes)
            nodes.append([col, m.value_to_bin(float(node["threshold"])),
                          -1, -1])
            if node.get("left"):
                nodes[idx][2] = rec(node["left"])
            if node.get("right"):
                nodes[idx][3] = rec(node["right"])
            return idx

        if rec(data) != 0 or not nodes:
            return None
        arr = np.asarray(nodes, np.int32)
        return (jnp.asarray(arr[:, 0]), jnp.asarray(arr[:, 1]),
                jnp.asarray(arr[:, 2]), jnp.asarray(arr[:, 3]))

    def _setup_tree_learner(self) -> None:
        """tree_learner dispatch (reference: TreeLearner factory,
        tree_learner.h:104 + config.h:205). Non-serial learners run the same
        jitted grower under a shard_map over the visible device mesh."""
        cfg = self.config
        mode = cfg.tree_learner
        if mode in ("serial", None, ""):
            self._parallel_grower = None
            return
        from ..parallel.learners import PARALLEL_MODES, ParallelGrower
        if mode not in PARALLEL_MODES:
            log.fatal(f"Unknown tree learner type {mode}")
        unsupported = []
        if getattr(self.train_set, "has_sparse_cols", False):
            # construct() only extracts sparse columns when the params it
            # saw said tree_learner=serial; reaching here means the Booster
            # was configured differently from the Dataset
            unsupported.append("sparse device storage (construct the "
                               "Dataset with enable_sparse=false)")
        if self._cegb_mode != "off":
            unsupported.append("CEGB")
        if self._with_interactions:
            unsupported.append("interaction_constraints")
        if self._use_bynode:
            unsupported.append("feature_fraction_bynode")
        if cfg.linear_tree:
            unsupported.append("linear_tree")
        if mode == "voting" and \
                getattr(self, "_forced_splits", None) is not None:
            # voting keeps histograms local; a forced threshold's sums
            # would come from one shard only
            unsupported.append("forced splits (voting)")
        if unsupported:
            log.fatal(f"tree_learner={mode} does not support: "
                      f"{', '.join(unsupported)}")
        existing = getattr(self, "_parallel_grower", None)
        if existing is not None and existing.mode == mode:
            return  # keep the compiled cache across reset_config
        if len(jax.devices()) == 1:
            log.info(f"tree_learner={mode} with a single device: running the "
                     f"distributed program on a 1-device mesh")
        self._parallel_grower = ParallelGrower(mode)

    def reset_config(self, config: Config) -> None:
        """Apply updated parameters mid-training (reference: GBDT::ResetConfig,
        gbdt.cpp; used by the reset_parameter callback / learning_rates)."""
        self.config = config
        # NOTE: the fused-step cache is keyed on the static grow options
        # (see _fused_step_fn), so a reset that only touches dynamic
        # scalars (learning_rates schedules via reset_parameter — lr and
        # SplitParams are traced arguments) reuses the compiled program
        self.shrinkage_rate = config.learning_rate
        self.split_params = SplitParams.from_config(config)
        if self.train_set is not None:
            # _setup_learner_features ends by re-running _setup_tree_learner,
            # so a config change enabling an option the active parallel
            # learner rejects fails loudly here
            self._setup_learner_features(self.train_set)
        self._need_bagging = (config.bagging_freq > 0 and config.bagging_fraction < 1.0) or \
            (config.pos_bagging_fraction < 1.0 or config.neg_bagging_fraction < 1.0)
        self._bag_frac = None   # fractions may have changed
        if not self._need_bagging:
            # bagging switched off mid-training: drop the frozen subset/mask
            self._bag_sub = None
            self._bag_mask = jnp.ones((self._n_score_rows,),
                                      dtype=jnp.float32) \
                if self.train_set is not None else self._bag_mask

    def add_valid(self, valid_set: Dataset, name: str) -> None:
        valid_set.construct()
        self.valid_sets.append(valid_set)
        self.valid_names.append(name)
        n = valid_set.num_data
        k = self.num_tree_per_iteration
        shape = (n, k) if k > 1 else (n,)
        init = valid_set.init_score
        if init is not None:
            base = np.asarray(init, dtype=np.float32).reshape(shape)
        else:
            base = np.broadcast_to(np.asarray(self.init_scores, dtype=np.float32),
                                   (n, k)).reshape(shape) if k > 1 else \
                np.full((n,), self.init_scores[0], dtype=np.float32)
        self._valid_scores.append(jnp.asarray(np.ascontiguousarray(base)))

    # ---------------------------------------------------------- sampling
    def _bagging_mode(self) -> str:
        """STATIC bagging flavor for the current config: "off" | "mask" |
        "subset". The subset rule mirrors the reference's compact-copy
        heuristic (gbdt.cpp:810-818): small enough fraction that a compact
        row copy beats masked full-N histogram passes; serial learner and
        plain fraction only. The single definition the host refresh below
        and the fused in-program draw share."""
        cfg = self.config
        if not self._need_bagging or cfg.bagging_freq <= 0:
            return "off"
        use_subset = (cfg.bagging_fraction <= 0.5
                      and cfg.pos_bagging_fraction >= 1.0
                      and cfg.neg_bagging_fraction >= 1.0
                      and self._parallel_grower is None
                      and self._cegb_mode == "off"
                      and not cfg.linear_tree
                      # sparse streams index ORIGINAL row ids; the subset
                      # copy compacts rows, so it takes the mask path
                      and not getattr(self.train_set, "has_sparse_cols",
                                      False))
        return "subset" if use_subset else "mask"

    def _subset_rows(self) -> int:
        """Static row count of the bagging subset copy."""
        return max(1, int(round(self._n_score_rows
                                * self.config.bagging_fraction)))

    def _bagging_frac(self):
        """Per-row (pos/neg) or scalar keep-probability for the mask mode
        (config.h:268-280), built lazily and cached until reset_config."""
        cfg = self.config
        if getattr(self, "_bag_frac", None) is None:
            if cfg.pos_bagging_fraction < 1.0 or cfg.neg_bagging_fraction < 1.0:
                pos = self.objective.label_np > 0 \
                    if hasattr(self.objective, "label_np") \
                    else self.train_set.get_label() > 0
                self._bag_frac = jnp.asarray(np.where(
                    pos, cfg.pos_bagging_fraction,
                    cfg.neg_bagging_fraction).astype(np.float32))
            else:
                self._bag_frac = jnp.float32(cfg.bagging_fraction)
        return self._bag_frac

    def _update_bagging(self) -> None:
        """Bagging mask refresh (reference: gbdt.cpp:228-262 Bagging;
        pos/neg bagging per config.h:268-280). The mask comes from the
        device PRNG — no per-period host uniform draw + upload. The draw
        is keyed on the PERIOD-START iteration, so it is deterministic in
        the iteration alone: a mid-period resume, or an unfused iteration
        following fused ones (which draw the same key in-program and leave
        the host mask stale), re-derives the exact same mask."""
        cfg = self.config
        mode = self._bagging_mode()
        if mode == "off":
            return
        if self.iter % cfg.bagging_freq != 0 and not self._bag_stale:
            return
        period_start = (self.iter // cfg.bagging_freq) * cfg.bagging_freq
        key = jax.random.fold_in(jax.random.PRNGKey(cfg.bagging_seed),
                                 period_start)
        self._bag_stale = False
        if mode == "subset":
            self._bag_mask, sub_idx, sub_bins, sub_binsT = _bagging_subset(
                key, self.train_set.bins, self._subset_rows())
            self._bag_sub = (sub_idx, sub_bins, sub_binsT)
            return
        self._bag_sub = None
        if self._pre_part:
            # per-global-row draw: partition-invariant, so an elastic
            # resume at a different world size re-derives the same sample
            self._bag_mask = _bagging_mask_rows(
                key, self._bagging_frac(),
                jnp.int32(getattr(self.train_set, "local_row_start", 0) or 0),
                self._n_score_rows)
        else:
            self._bag_mask = _bagging_mask(key, self._bagging_frac(),
                                           self._n_score_rows)

    def _feature_mask(self) -> jax.Array:
        """Per-tree column sampling (reference: col_sampler.hpp:20-50
        feature_fraction by-tree)."""
        f = self.train_set.num_used_features()
        frac = self.config.feature_fraction
        if frac >= 1.0:
            return jnp.ones((f,), dtype=jnp.float32)
        k = max(1, int(round(f * frac)))
        chosen = self._feat_rng.choice(f, size=k, replace=False)
        mask = np.zeros((f,), dtype=np.float32)
        mask[chosen] = 1.0
        return jnp.asarray(mask)

    def _feature_mask_np(self) -> Optional[np.ndarray]:
        """Host-side per-class feature-fraction masks for the fused step
        ([K, F] float32), drawn from the SAME stateful rng in the same
        per-tree order as the unfused path's _feature_mask calls (bit-
        parity). None when column sampling is off — the fused step then
        builds a constant all-ones mask in-program, so a steady-state
        iteration uploads nothing."""
        f = self.train_set.num_used_features()
        frac = self.config.feature_fraction
        if frac >= 1.0:
            return None
        k = self.num_tree_per_iteration
        kk = max(1, int(round(f * frac)))
        masks = np.zeros((k, f), dtype=np.float32)
        for c in range(k):
            masks[c, self._feat_rng.choice(f, size=kk, replace=False)] = 1.0
        return masks

    # ------------------------------------------------------------ train
    def _gradients(self) -> Tuple[jax.Array, jax.Array]:
        return self.objective.get_grad_hess(self.train_score)

    def _fused_ok(self, grad_external) -> bool:
        """Whether this iteration can run gradients -> growth -> score
        update as ONE jitted program (see _fused_step_fn).

        The gate is wide: multiclass (all class trees grow inside the one
        program via a lax.scan over the class axis), the data/feature/
        voting parallel learners (the same shard_map'd grower the unfused
        path uses, embedded in the fused program), the bagging mask AND
        subset copy (drawn in-program from the period-start key), CEGB
        (its cross-iteration aux rides through as device-resident loop
        state), interaction constraints, per-node feature sampling and
        forced splits (constant device tables closed over).

        What remains excluded genuinely interleaves HOST work between the
        phases: externally supplied gradients (fobj), objectives with
        host-side leaf renewal, linear-leaf fitting (host lstsq per leaf),
        the NaN-GRADIENT injection fault (it materializes gradients on
        host by design; the in-program nan_hist fault does not unfuse),
        and multi-controller / pre-partitioned runs (per-process array
        globalization between phases). ``check_numerics`` is NOT excluded
        anymore: the fused step computes an in-program sentinel flag word
        (packed NaN/Inf bits for gradients, hessians, the histogram
        plane, leaf outputs and the score delta) that the host checks
        from the iteration's own results — the guard works WITH the fused
        path instead of gating it off (PR 3's limitation, lifted).
        Subclasses whose only deviation is an in-program-expressible
        sampling scheme (GOSS) opt in via ``_fused_sampling``; DART and
        RF stay host-interleaved."""
        cfg = self.config
        return ((type(self) is GBDT
                 or getattr(self, "_fused_sampling", False))
                and cfg.fused_iteration
                and grad_external is None
                # NaN-gradient injection needs the gradients materialized
                # outside the fused program (check_numerics does not: see
                # the sentinel note above)
                and (self._fault_plan is None
                     or not self._fault_plan.wants_nan_grad)
                and self.objective is not None
                and not self.objective.need_renew_tree_output
                and getattr(self.objective, "jit_safe_gradients", True)
                and not cfg.linear_tree
                and jax.process_count() == 1
                and not getattr(self, "_pre_part", False)
                # 0-feature datasets take _grow_one's constant-tree path
                and (self.train_set.num_dense_columns() > 0
                     or getattr(self.train_set, "has_sparse_cols", False)))

    def _serial_grow_statics(self, hm: str) -> dict:
        """STATIC grow_tree options for the serial learner — the single
        definition the unfused call site and the fused step share, so a
        new option cannot silently diverge between the two paths (the
        suite asserts their bit-parity). Nothing caches them: every call
        computes (the first imports the kernels' module), under the span
        "plan"."""
        with profiling.span("plan"):
            return self._serial_statics(hm)

    def _serial_statics(self, hm: str) -> dict:
        cfg = self.config
        ts = self.train_set
        has_sp = getattr(ts, "has_sparse_cols", False)
        fb = self._feature_block(hm)
        sf = self._split_fusion_on(hm, fb)
        tile, blk, _ = self._hist_plan(hm)
        return dict(
            max_leaves=cfg.num_leaves, num_bins=ts.max_num_bins,
            max_depth=cfg.max_depth, hist_method=hm,
            tile_leaves=tile, hist_block=blk,
            hist_interpret=self._hist_interpret(),
            numerics_sentinels=cfg.check_numerics,
            feature_block=fb,
            split_fusion=sf,
            exact=cfg.tree_growth_mode == "exact",
            with_categorical=ts.has_categorical,
            with_monotone=self._with_monotone,
            mono_mode=self._mono_mode,
            mono_features=self._mono_features,
            with_interactions=self._with_interactions,
            cegb_mode=self._cegb_mode,
            use_bynode=self._use_bynode,
            extra_trees=cfg.extra_trees,
            hist_dp=self._hist_dp,
            hist_subtraction=cfg.hist_subtraction and fb == 0,
            sp_cols=tuple(int(c) for c in ts.sp_cols) if has_sp else (),
            sp_offsets=(tuple(int(o) for o in ts.sp_offsets)
                        if has_sp else ()),
            compaction_ladder=() if fb else self._compaction_ladder(hm))

    def _parallel_grow_statics(self, hm: str) -> dict:
        """STATIC grow options for the configured parallel learner — like
        _serial_grow_statics, the single definition the unfused _grow_one
        call site and the fused step share (the two also share the
        compiled shard_map program through ParallelGrower.get_shard_fn)."""
        with profiling.span("plan"):
            return self._parallel_statics(hm)

    def _parallel_statics(self, hm: str) -> dict:
        cfg = self.config
        ts = self.train_set
        tile, blk, _ = self._hist_plan(hm)
        return dict(
            max_leaves=cfg.num_leaves, num_bins=ts.max_num_bins,
            max_depth=cfg.max_depth, hist_method=hm,
            tile_leaves=tile, hist_block=blk,
            hist_interpret=self._hist_interpret(),
            numerics_sentinels=cfg.check_numerics,
            exact=cfg.tree_growth_mode == "exact",
            with_categorical=ts.has_categorical,
            with_monotone=self._with_monotone,
            mono_mode=self._mono_mode,
            mono_features=self._mono_features,
            extra_trees=cfg.extra_trees,
            hist_subtraction=cfg.hist_subtraction,
            vote_top_k=cfg.top_k, hist_dp=self._hist_dp)

    def _compaction_ladder(self, hm: str) -> tuple:
        """Static row-buffer sizes for the grower's leaf-partitioned row
        compaction (see grow_tree's compaction_ladder docstring — the
        DataPartition analog). CANDIDATE rungs are
        ``hist_compaction_ladder`` fractions of the histogram row count
        (the bagging-subset copy's K rows when that path is active),
        rounded up to a 64-row boundary; candidates that don't undercut
        the full count are dropped — the full-N pass is always the
        fallback. Of the candidates a rung is KEPT only where a pass
        through it costs less than the full pass it replaces
        (ops/histogram.py prune_compaction_ladder: arithmetic on the
        device kind, the resolved histogram method ``hm`` and the shape).
        On a TPU v5 lite at the Higgs shape (10.5M x 28, 255 bins) that
        keeps none, and the step is traced without gather, count or
        ``cond``; on a backend the rule has no constants for (the CPU)
        every candidate stays."""
        from ..ops.histogram import (prune_compaction_ladder,
                                     rung_costs_source)
        cfg = self.config
        ts = self.train_set
        if not cfg.hist_compaction or ts is None:
            return ()
        base = (self._subset_rows() if self._bagging_mode() == "subset"
                else (ts.num_local_data if getattr(self, "_pre_part", False)
                      else ts.num_data))
        rungs = set()
        for fr in (cfg.hist_compaction_ladder or []):
            m = -(-max(int(round(base * float(fr))), 1) // 64) * 64
            if 0 < m < base:
                rungs.add(m)
        candidates = tuple(sorted(rungs))
        kind = jax.devices()[0].device_kind
        f_dense = ts.num_dense_columns()
        kept = prune_compaction_ladder(candidates, kind, hm, base, f_dense,
                                       ts.max_num_bins)
        said = (candidates, kept, kind, hm, base)
        if said != getattr(self, "_ladder_said", None):
            # once per booster and resolved shape
            self._ladder_said = said
            priced = rung_costs_source(kind)
            log.info(f"hist compaction: candidates {candidates} kept {kept} "
                     f"[{kind}, {hm}, N={base} F={f_dense} "
                     f"B={ts.max_num_bins}]"
                     + (f" (no rung costs measured on this TPU: priced "
                        f"with {priced}'s; scripts/calibrate_compaction.py "
                        "measures them)"
                        if priced not in (None, kind) else ""))
        return kept

    def _fused_cegb_state(self) -> Optional[GrowAux]:
        """CEGB's cross-iteration feature-used tracking as an explicit
        fused-step operand (cost_effective_gradient_boosting.hpp Init:
        !init_ reuse). A zero aux is materialized once at the first
        iteration so the step's operand structure stays trace-stable."""
        if self._cegb_mode == "off":
            return None
        if self._cegb_aux is None:
            ts = self.train_set
            f = ts.num_used_features()
            n = self._n_score_rows
            lazy = self._cegb_mode == "lazy"
            self._cegb_aux = GrowAux(
                used_split=jnp.zeros((f,), bool),
                row_used=jnp.zeros((n, f) if lazy else (1, 1), bool),
                rows_streamed=jnp.float32(0.0),
                coll_bytes=jnp.float32(0.0),
                sentinel=jnp.float32(0.0),
                leaves_resolved=jnp.float32(0.0))
        return self._cegb_aux

    def _fused_parallel_bindings(self, hm: str):
        """Padded dataset-constant arrays for the fused parallel step,
        through the SAME ParallelGrower padding/extras helpers the
        unfused ``__call__`` uses (single source of truth) — but built
        ONCE and cached per (learner, binsT-needed) instead of per call;
        the per-iteration grad/hess/mask pads move inside the jitted
        program. The sub-cache is keyed separately from the fused step
        cache so a reset_parameter sweep over step statics never
        duplicates the padded O(N*F) dataset copies."""
        pg = self._parallel_grower
        ts = self.train_set
        use_binsT = hm.startswith(("onehot", "pallas"))
        hit = self._fused_bind_cache.get(use_binsT)
        # identity-checked (not id-keyed): a reset_config can replace the
        # learner or the forced-split tables; the old objects stay alive
        # inside the stale entry, so an `is` match is exact
        if (hit is not None and hit[0] is pg
                and hit[1] is self._forced_splits):
            return hit[2]
        row_bins = getattr(ts, "row_bins", None)
        if pg.takes_row_shards(row_bins):
            # the construct left one shard of rows a device: pad and
            # transpose them where they lie, no whole copy anywhere
            (bins, binsT, meta, missing_bin, bundle_meta,
             n_pad, f_pad) = pg.pad_row_sharded_inputs(
                row_bins, ts.num_data, use_binsT, ts.feature_meta,
                ts.missing_bin, ts.bundle_meta)
        else:
            (bins, binsT, meta, missing_bin, bundle_meta,
             n_pad, f_pad) = pg.pad_replicated_inputs(
                ts.bins, ts.bins_T if use_binsT else None, ts.feature_meta,
                ts.missing_bin, ts.bundle_meta)
        extras, extras_spec = pg.build_extras(binsT, bundle_meta,
                                              self._forced_splits)
        bins, meta, missing_bin, extras = pg.place_constants(
            bins, meta, missing_bin, extras, extras_spec)
        pb = dict(bins=bins, extras=extras, extras_spec=extras_spec,
                  meta=meta, missing_bin=missing_bin, n=ts.num_data,
                  n_pad=n_pad, f_pad=f_pad)
        self._fused_bind_cache[use_binsT] = (pg, self._forced_splits, pb)
        return pb

    def _fused_step_fn(self, hm: str, fmask_on: bool, k_rounds: int = 1):
        """One jitted program per boosting iteration — or per K-iteration
        BLOCK (``k_rounds`` > 1, the ``boost_rounds_per_dispatch`` scan):
        objective gradients -> sampling draw -> per-class tree growth ->
        shrinkage -> score deltas, fused so the host dispatches the whole
        grow phase ONCE (three-plus dispatches otherwise, and per-class
        multiples for multiclass — each a launch the device idles before)
        and XLA fuses the elementwise gradient math into the
        grower's first histogram pass instead of materializing grad/hess
        through HBM. The reference's TrainOneIter phases
        (gbdt.cpp:369-452) collapse into one program:

        - multiclass grows all ``num_tree_per_iteration`` class trees via
          a ``lax.scan`` over the class axis — the grower (and its
          histogram workspace) is compiled ONCE and reused per class,
          mirroring the reference's single logical TrainOneIter;
        - the parallel learners run the SAME shard_map'd grower the
          unfused path uses (ParallelGrower.get_shard_fn), embedded in
          the fused program, so distributed iterations also collapse to
          one dispatch;
        - bagging (mask or subset copy) is drawn in-program from the
          period-start key, and GOSS's one-side sampling weights from the
          per-iteration key — bit-identical to the host refresh draws
          and never interleaved as separate dispatches;
        - CEGB's cross-iteration aux rides through as device-resident
          loop state (operand in, operand out).

        EVERY dataset-constant array — the bin matrices, the objective's
        label/weight (and derived label_sign/onehot/... tables), feature
        metadata, bundle/forced-split/interaction/CEGB tables — enters
        the program as an OPERAND through the cached ``bind`` dict, never
        as a closure constant: closure constants are embedded in the HLO
        and their label-derived subexpressions become dataset-sized
        constant folds at COMPILE time (XLA's slow-constant-folding alarm
        fired at >6 s on single instructions at 10.5M rows). The hoist
        test pins the
        traced jaxpr's constant footprint near zero.

        Per-iteration mode (``k_rounds`` == 1): the score update is the
        SECOND (and last) dispatch — ``_apply_score_delta``, a donated
        in-place add kept out of this program so the backend cannot
        FMA-contract it against the leaf-value shrinkage (see its
        docstring; bit-parity).

        Block mode (``k_rounds`` K > 1): a ``lax.scan`` over the K
        iterations carries the score cache IN-PROGRAM (the donated score
        operand is the carry seed), with each step re-keyed by the
        scanned absolute iteration index — the same fold_in(…, it)
        streams the per-iteration mode draws, so the block is
        bit-identical to K separate fused iterations. The carry update
        keeps the exact two-rounding sequence of the split programs:
        trees are shrunk FIRST (round(leaf_value*lr), the [L]-sized
        multiply), the per-row delta is a GATHER of the pre-shrunk leaf
        values, and the gathered delta passes the ``_fma_guard``
        rounding fence before the add — the backend contracts a multiply
        feeding an add even across ``optimization_barrier`` AND through
        the gather (both re-verified; the PR 3 lesson), so only the
        fence's integer round-trip actually pins the rounding. One
        dispatch grows K*C trees.

        Trees are returned SHRUNK either way. Cached by the STATIC grow
        options (+ objective/constant identities + k_rounds), so
        dynamic-parameter resets (learning_rates schedules) never
        retrace. Returns ``(step, bind)`` where ``bind`` holds the
        dataset-constant operands the caller passes each call."""
        ts = self.train_set
        obj = self.objective
        cfg = self.config
        k = self.num_tree_per_iteration
        kk = max(1, int(k_rounds))
        pg = self._parallel_grower
        bag_mode = self._bagging_mode()
        sub_k = self._subset_rows() if bag_mode == "subset" else 0
        frac_kind = "arr" if (bag_mode == "mask"
                              and (cfg.pos_bagging_fraction < 1.0
                                   or cfg.neg_bagging_fraction < 1.0)) \
            else bag_mode
        grow_kw = self._parallel_grow_statics(hm) if pg is not None \
            else self._serial_grow_statics(hm)
        # in-program numerics sentinels (check_numerics on the fused path)
        # and the traced NaN-injection fault are STATICS of the program:
        # the disarmed trace is byte-identical to a guard-free one
        from ..utils import faults as faults_mod
        sentinels = bool(cfg.check_numerics)
        nan_hist_it = faults_mod.nan_hist_iter(self._fault_plan)
        n = self._n_score_rows
        # GOSS one-side sampling as in-program statics (goss.hpp:105-150):
        # the subclass opts in via _fused_sampling; counts and the
        # 1/learning_rate warm-up gate are static per (n, rates, lr)
        goss_on = bool(getattr(self, "_fused_sampling", False))
        goss_top = max(1, int(n * cfg.top_rate)) if goss_on else 0
        goss_other = max(1, int(n * cfg.other_rate)) if goss_on else 0
        goss_warm = int(1.0 / cfg.learning_rate) if goss_on else 0
        key = (id(obj), k, kk, bag_mode, sub_k, frac_kind, fmask_on,
               pg.mode if pg is not None else "serial",
               sentinels, nan_hist_it,
               goss_on, goss_top, goss_other, goss_warm,
               cfg.bagging_freq, cfg.bagging_seed, cfg.extra_seed,
               # the by-node fraction is closed over below (a constant of
               # the program): key it so a reset_parameter change
               # retraces instead of silently keeping the old fraction
               cfg.feature_fraction_bynode if self._use_bynode else None,
               id(self._interaction_groups), id(self._cegb_coupled),
               id(self._cegb_lazy), id(self._forced_splits),
               ) + tuple(grow_kw[k2] for k2 in sorted(grow_kw))
        hit = self._fused_cache.get(key)
        if hit is not None:
            return hit
        from .tree import leaf_values_of_rows
        f_used = ts.num_used_features()
        freq = cfg.bagging_freq
        extra_key = self._extra_rng_key
        bag_key0 = jax.random.PRNGKey(cfg.bagging_seed)
        has_sp = getattr(ts, "has_sparse_cols", False)
        cegb_on = self._cegb_mode != "off"
        bynode_frac = (jnp.float32(cfg.feature_fraction_bynode)
                       if self._use_bynode else None)
        # dataset-constant OPERANDS (see docstring): one cached dict the
        # caller passes per dispatch — the host-side cost is a pointer
        # walk, the compile-time win is that nothing here can be folded.
        # Building them (the padded or transposed bins, the row placement
        # of a parallel learner) is the span "plan": computed inside the
        # first update, and not that iteration's own time
        with profiling.span("plan"):
            if pg is not None:
                pb = self._fused_parallel_bindings(hm)
                shard = pg.get_shard_fn(pb["extras_spec"],
                                        tuple(sorted(grow_kw.items())))
                # the O(N) operands where the step reads them, once
                self.train_score = pg.place_rows(self.train_score, n)
                bind = dict(bins=pb["bins"], binsT=None, sp_rows=None,
                            sp_cell=None, sp_default=None, extras=pb["extras"],
                            meta=pb["meta"], missing_bin=pb["missing_bin"],
                            bundle_meta=None, forced=None, igroups=None,
                            cegb_coupled=None, cegb_lazy=None,
                            obj_consts=pg.place_rows(obj.device_consts(), n))
            else:
                pb = shard = None
                bind = dict(bins=ts.bins,
                            binsT=ts.bins_T if self._use_binsT(hm) else None,
                            sp_rows=ts.sp_rows if has_sp else None,
                            sp_cell=ts.sp_cell if has_sp else None,
                            sp_default=ts.sp_default if has_sp else None,
                            extras=None,
                            meta=ts.feature_meta, missing_bin=ts.missing_bin,
                            bundle_meta=ts.bundle_meta,
                            forced=self._forced_splits,
                            igroups=self._interaction_groups,
                            cegb_coupled=self._cegb_coupled,
                            cegb_lazy=self._cegb_lazy,
                            obj_consts=obj.device_consts())

        def one_iter(score, it, lr, fmask_it, cegb_state, rows_acc,
                     coll_acc, leaves_acc, sync_acc, sparams, bag_frac, b):
            """One boosting iteration's traced body — shared verbatim by
            the per-iteration program and the K-block scan (re-keyed by
            the traced absolute iteration index ``it``)."""
            with jax.named_scope("gradients"):
                with obj.bound(b["obj_consts"]):
                    g, h = obj.get_grad_hess(score)
                if nan_hist_it >= 0:
                    # traced NaN injection (LGBM_TPU_FAULT_NAN_HIST_AT_ITER):
                    # poison one gradient value INSIDE the program at the
                    # armed iteration — the failure shape the in-program
                    # sentinels exist for (a host-side injection would unfuse)
                    gf = g.reshape(-1).at[0].set(jnp.nan).reshape(g.shape)
                    g = jnp.where(jnp.equal(it, nan_hist_it), gf, g)
                # ---- bagging, derived from the period-start key: the exact
                # draw _update_bagging performs on the host path
                mask = jnp.ones((n,), jnp.float32)
                sub = None
                if bag_mode != "off":
                    bkey = jax.random.fold_in(bag_key0, (it // freq) * freq)
                    if bag_mode == "mask":
                        u = jax.random.uniform(bkey, (n,))
                        mask = (u < bag_frac).astype(jnp.float32)
                    else:
                        r = jax.random.bits(bkey, (n,), jnp.uint32)
                        sub_idx = jnp.argsort(r)[:sub_k].astype(jnp.int32)
                        sub_bins = jnp.take(b["bins"], sub_idx, axis=0)
                        sub = (sub_idx, sub_bins, sub_bins.T)
                if goss_on:
                    # GOSS weights from the per-iteration key, exactly the
                    # host path's _sample_weights -> goss_weights sequence;
                    # the warm-up arm (< 1/learning_rate iterations) skips
                    # the draw like the host's early return
                    from .goss import goss_weights_impl

                    def _sampled(args):
                        g0, h0 = args
                        sc = jnp.sum(jnp.abs(g0 * h0), axis=1) if k > 1 \
                            else jnp.abs(g0 * h0)
                        w = goss_weights_impl(
                            sc, jax.random.fold_in(bag_key0, it),
                            goss_top, goss_other)
                        wk = w[:, None] if k > 1 else w
                        return g0 * wk, h0 * wk, (w > 0).astype(jnp.float32)

                    def _warm(args):
                        g0, h0 = args
                        return g0, h0, mask

                    g, h, mask = jax.lax.cond(it >= goss_warm, _sampled,
                                              _warm, (g, h))

            def grow_c(gc, hc, fmask_c, key_c, cegb_aux):
                if pg is None:
                    tree, leaf_id, aux = grow_tree(
                        b["bins"], gc, hc, mask, b["meta"], sparams,
                        fmask_c, b["missing_bin"], binsT=b["binsT"],
                        rng_key=key_c, bundle_meta=b["bundle_meta"],
                        forced_splits=b["forced"],
                        sub_idx=sub[0] if sub else None,
                        sub_bins=sub[1] if sub else None,
                        sub_binsT=sub[2] if sub else None,
                        interaction_groups=b["igroups"],
                        cegb_coupled=b["cegb_coupled"],
                        cegb_lazy_penalty=b["cegb_lazy"],
                        cegb_state=cegb_aux,
                        bynode_fraction=bynode_frac,
                        sp_rows=b["sp_rows"], sp_cell=b["sp_cell"],
                        sp_default=b["sp_default"], **grow_kw)
                else:
                    gp = jnp.pad(gc, (0, pb["n_pad"]))
                    hp = jnp.pad(hc, (0, pb["n_pad"]))
                    mp = jnp.pad(mask, (0, pb["n_pad"]))
                    fp = jnp.pad(fmask_c, (0, pb["f_pad"]))
                    tree, leaf_id, aux = shard(
                        b["bins"], gp, hp, mp, b["meta"], sparams, fp,
                        b["missing_bin"], b["extras"], key_c)
                    leaf_id = leaf_id[:n]
                # shrink FIRST, then GATHER the pre-shrunk leaf values:
                # identical bits to gather-then-multiply (gather commutes
                # with the elementwise mul), but the block mode's in-carry
                # score add then sees no multiply to FMA-contract
                with jax.named_scope("finalize_tree"):
                    tree = _shrink_tree(tree, lr)
                with jax.named_scope("score_update"):
                    delta = leaf_values_of_rows(tree.leaf_value, leaf_id)
                return tree, delta, aux

            fm = fmask_it if fmask_on else jnp.ones((k, f_used),
                                                    jnp.float32)
            if k == 1:
                key0 = jax.random.fold_in(extra_key, it * k)
                tree, delta, aux = grow_c(g, h, fm[0], key0, cegb_state)
                trees_st = tree
                rows, coll = aux.rows_streamed, aux.coll_bytes
                leaves, sync = aux.leaves_resolved, aux.sync_calls
                hist_sent = aux.sentinel
                cegb_out = aux if cegb_on else None
            else:
                keys = jax.vmap(
                    lambda c: jax.random.fold_in(extra_key, it * k + c))(
                        jnp.arange(k, dtype=jnp.int32))

                def body(carry, xs):
                    gc, hc, fmask_c, key_c = xs
                    tree, delta_c, aux = grow_c(gc, hc, fmask_c, key_c,
                                                carry if cegb_on else
                                                cegb_state)
                    return (aux if cegb_on else carry,
                            (tree, delta_c, aux.rows_streamed,
                             aux.coll_bytes, aux.sentinel,
                             aux.leaves_resolved, aux.sync_calls))

                carry0 = cegb_state if cegb_on else jnp.int32(0)
                carry, (trees_st, delta, rows_st, coll_st, sent_st,
                        leaves_st, sync_st) = \
                    jax.lax.scan(body, carry0, (g.T, h.T, fm, keys))
                rows, coll = jnp.sum(rows_st), jnp.sum(coll_st)
                leaves = jnp.sum(leaves_st)
                sync = None if sync_st is None else jnp.sum(sync_st)
                hist_sent = jnp.sum(sent_st)
                cegb_out = carry if cegb_on else None
            if sentinels:
                # the per-iteration sentinel flag word: packed NaN/Inf
                # bits per SOURCE (see _SENTINEL_SOURCES), computed as
                # tiny reductions fused into the step's epilogue and
                # fetched by the host with this iteration's results — no
                # extra dispatch, no host round trip of the arrays
                bad = lambda x: jnp.any(~jnp.isfinite(x))  # noqa: E731
                u32 = lambda bv: bv.astype(jnp.uint32)     # noqa: E731
                with jax.named_scope("finalize_tree"):
                    leaf_bad = bad(trees_st.leaf_value)
                    flags = (u32(bad(g)) | (u32(bad(h)) << 1)
                             | (u32(hist_sent > 0) << 2)
                             | (u32(leaf_bad) << 3)
                             | (u32(bad(delta)) << 4))
            else:
                flags = jnp.uint32(0)
            # the serial learner syncs nothing: no operand, and a None
            # for a LAST result, so that its program stays what it was
            return (trees_st, delta, rows_acc + rows, coll_acc + coll,
                    leaves_acc + leaves, cegb_out, flags,
                    None if sync is None else sync_acc + sync)

        def _unstack_classes(trees_st):
            if k == 1:
                return (trees_st,)
            return tuple(jax.tree.map(lambda x: x[c], trees_st)
                         for c in range(k))

        if kk == 1:
            def _fused_step(score, it, lr, fmask, sparams, bag_frac,
                            cegb_state, rows_acc, coll_acc, leaves_acc,
                            sync_acc, b):
                (trees_st, delta, rows, coll, leaves, cegb_out, flags,
                 sync) = one_iter(
                    score, it, lr, fmask, cegb_state, rows_acc, coll_acc,
                    leaves_acc, sync_acc, sparams, bag_frac, b)
                return (_unstack_classes(trees_st), delta, rows, coll,
                        leaves, cegb_out, flags, sync)

            step = jax.jit(_fused_step)
        else:
            def _fused_block(score, it0, lr, fmask, sparams, bag_frac,
                             cegb_state, rows_acc, coll_acc, leaves_acc,
                             sync_acc, b):
                """K boosting iterations per dispatch: scan the fused
                step over the absolute iteration indices, score cache in
                the carry (donated operand in, aliased result out). See
                the outer docstring and _fma_guard for the FMA-safety
                argument."""
                cegb0 = cegb_state if cegb_on else jnp.int32(0)
                # runtime-zero XOR salt (it0 is never negative): the
                # compiler cannot fold it, so the _fma_guard fence around
                # the carry add survives every optimization pass
                salt = (it0 < jnp.int32(-1)).astype(jnp.uint32)

                def body(carry, xs):
                    (score_c, cegb_c, rows_c, coll_c, leaves_c,
                     sync_c) = carry
                    if fmask_on:
                        j, fm_it = xs
                    else:
                        j, fm_it = xs, None
                    (trees_st, delta, rows_c, coll_c, leaves_c, cegb_out,
                     flags, sync_c) = one_iter(
                        score_c, it0 + j, lr, fm_it,
                        cegb_c if cegb_on else cegb_state,
                        rows_c, coll_c, leaves_c, sync_c, sparams,
                        bag_frac, b)
                    # the in-carry analog of _apply_score_delta: delta is
                    # a gather of PRE-SHRUNK leaf values, passed through
                    # the _fma_guard rounding fence — the backend cannot
                    # contract the shrinkage multiply into this add, so
                    # the two-rounding sequence (and bit-parity with the
                    # split per-iteration programs) is preserved
                    with jax.named_scope("score_update"):
                        d = delta.T if delta.ndim == 2 else delta
                        score_c = score_c + _fma_guard(d, salt)
                    return ((score_c, cegb_out if cegb_on else cegb_c,
                             rows_c, coll_c, leaves_c, sync_c),
                            (trees_st, flags))

                js = jnp.arange(kk, dtype=jnp.int32)
                xs = (js, fmask) if fmask_on else js
                (score_f, cegb_f, rows_f, coll_f, leaves_f, sync_f), \
                    (trees_all, flags) = jax.lax.scan(
                        body, (score, cegb0, rows_acc, coll_acc,
                               leaves_acc, sync_acc), xs)
                trees = tuple(
                    _unstack_classes(jax.tree.map(lambda x: x[j],
                                                  trees_all))
                    for j in range(kk))
                return (trees, score_f, rows_f, coll_f, leaves_f,
                        cegb_f if cegb_on else None, flags, sync_f)

            step = jax.jit(_fused_block, donate_argnums=(0,))
        if len(self._fused_cache) >= 8:
            # oldest-entry eviction: each parallel bind can pin a padded
            # O(N*F) dataset copy — a reset_parameter sweep over statics
            # must not accumulate one per swept value
            self._fused_cache.pop(next(iter(self._fused_cache)))
        self._fused_cache[key] = (step, bind)
        # for telemetry.scope_table(): the step as it is dispatched, found
        # again by its key (an evicted entry is simply left out; nothing
        # is lowered here)
        from .. import telemetry
        telemetry.register_program(
            self, functools.partial(GBDT._lower_fused, key=key,
                                    fmask_on=fmask_on, kk=kk))
        if kk == 1 and not self._score_program_registered:
            self._score_program_registered = True
            telemetry.register_program(
                self, lambda gb: _apply_score_delta.lower(
                    *gb._score_delta_shapes()))
        return step, bind

    def _register_valid_program(self, i: int, args, statics: dict) -> None:
        """The valid-score update of valid set ``i``, as its shapes, for
        ``telemetry.scope_table``: once per set and traversal depth."""
        key = (i, statics["depth"])
        if key in self._valid_programs_registered:
            return
        self._valid_programs_registered.add(key)
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
        from .. import telemetry
        telemetry.register_program(
            self, lambda _gb: _apply_valid_tree.lower(*shapes, **statics))

    def _lower_fused(self, key, fmask_on: bool, kk: int):
        """One cached fused step or block, lowered as it is dispatched,
        for ``telemetry.scope_table``; None once the entry is evicted."""
        hit = self._fused_cache.get(key)
        if hit is None:
            return None
        step, bind = hit
        fmask = self._fmask_shape(kk) if fmask_on else None
        return step.lower(*self._fused_call_args(fmask, bind))

    def _fmask_shape(self, kk: int):
        """The feature-mask operand of a fused step (``kk`` == 1) or
        K-block, as a shape."""
        shape = (self.num_tree_per_iteration,
                 self.train_set.num_used_features())
        return jax.ShapeDtypeStruct(((kk,) if kk > 1 else ()) + shape,
                                    jnp.float32)

    def _score_delta_shapes(self):
        """Argument shapes of the per-iteration mode's second dispatch
        (``_apply_score_delta``)."""
        k = self.num_tree_per_iteration
        d_shape = ((k, self._n_score_rows) if k > 1
                   else (self._n_score_rows,))
        return (jax.ShapeDtypeStruct(self._score_shape, jnp.float32),
                jax.ShapeDtypeStruct(d_shape, jnp.float32))

    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration (gbdt.cpp:369-452). Returns True when the
        iteration could not add any tree with a split (early stoppable).

        The body runs inside a watchdog phase: in multi-process training a
        dead or hung peer stalls this step's collectives forever, so the
        collective_deadline watchdog (distributed.CollectiveWatchdog) times
        the fused/unfused step and converts an over-deadline stall into a
        diagnosable DistributedTimeoutError / supervised gang restart.

        It also hosts the OOM degradation ladder: a RESOURCE_EXHAUSTED
        from the histogram programs (compile or execute) steps the booster
        down one documented rung (_maybe_degrade_oom) and RETRIES the
        iteration instead of killing the job — the retry is safe because a
        failed step mutates no trainer state (checked: the tree count must
        be unchanged)."""
        from .. import distributed
        from ..utils import faults
        it = self.iter
        # flight-recorder bookkeeping (host-side snapshots only — a dict
        # copy and a clock read; the record itself is built in the
        # finally so a failed step still leaves an in-flight record)
        flight = self._flight
        t_rec = time.time_ns()
        if flight is not None:
            # this update's begin is where the record before it ends
            flight.begin_update(t_rec)
        disp0 = profiling.dispatch_stats() if flight is not None else None
        sc0 = profiling.scopes() \
            if flight is not None and profiling.enabled() else None
        distributed.notify_step_begin(it)
        try:
            while True:
                ntrees_before = len(self.trees)
                try:
                    stop = self._train_one_iter_watched(grad, hess)
                    break
                except Exception as e:
                    if not self._maybe_degrade_oom(e, ntrees_before):
                        raise
                    # the retry recompiles the degraded programs under a
                    # fresh clock — without this the failed attempt +
                    # recompile could trip the collective-deadline
                    # watchdog on the very iteration the ladder rescues
                    distributed.notify_step_retry(it)
        finally:
            # on success self.iter advanced past ``it``: record completion;
            # on an exception the step did NOT complete and last_iter stays
            distributed.notify_step_end(it if self.iter > it else it - 1)
            if flight is not None:
                # telemetry must never kill the run it observes — and in
                # this finally an escaping record error would REPLACE a
                # real training exception. A failing recorder disarms
                # itself (one warning, not one per iteration).
                try:
                    with profiling.span("flight_record"):
                        self._record_flight(flight, it, t_rec, disp0, sc0)
                except Exception as e:
                    self._flight = None
                    log.warning(f"flight recorder disabled after record "
                                f"failure: {e}")
        if self._fault_plan is not None:
            # silent-corruption injection (FLIP_SCORE_RANK): one score-
            # cache bit flipped AFTER the iteration completes, on one rank
            # — the divergence check must attribute it to exactly that rank
            flipped = faults.maybe_flip_score(self._fault_plan, it,
                                              self.train_score)
            if flipped is not None:
                self.train_score = flipped
        return stop

    def _train_one_iter_watched(self, grad: Optional[np.ndarray] = None,
                                hess: Optional[np.ndarray] = None) -> bool:
        from ..utils import faults as faults_mod
        cfg = self.config
        ts = self.train_set
        k = self.num_tree_per_iteration
        # simulated-OOM injection point for the degradation ladder (raises
        # before any state mutates, so the retry in train_one_iter is safe)
        faults_mod.maybe_oom(self._fault_plan, self.iter)
        if self._fused_ok(grad):
            # the fused program draws its own bagging mask/subset from the
            # period-start key — no host refresh dispatch
            return self._train_one_iter_fused()
        self._update_bagging()
        mask = self._bag_mask
        with profiling.timer("gradients"):
            if grad is None:
                g, h = self._gradients()
            else:
                g = jnp.asarray(np.asarray(grad, dtype=np.float32).reshape(self._score_shape))
                h = jnp.asarray(np.asarray(hess, dtype=np.float32).reshape(self._score_shape))
        if self._fault_plan is not None:
            from ..utils import faults
            g, h = faults.maybe_nan_grad(self._fault_plan, self.iter, g, h)
            # host-path twin of the in-program NaN injection
            g, h = faults.maybe_nan_hist(self._fault_plan, self.iter, g, h)
        if cfg.check_numerics:
            self._check_numerics_grad(g, h)
        sample_weights = self._sample_weights(g, h)
        if sample_weights is not None:
            # GOSS-style reweighting: grad/hess amplified, the 0/1 mask keeps
            # the histogram count channel exact (reference: goss.hpp:103-150
            # multiplies gradients_/hessians_ of sampled small-grad rows).
            w = sample_weights
            g = g * (w[:, None] if k > 1 else w)
            h = h * (w[:, None] if k > 1 else w)
            mask = (w > 0).astype(jnp.float32)
        no_split = True
        hm = self._hist_method()
        for c in range(k):
            gc = g[:, c] if k > 1 else g
            hc = h[:, c] if k > 1 else h
            fmask = self._feature_mask()
            iter_key = jax.random.fold_in(self._extra_rng_key,
                                          self.iter * k + c)
            with profiling.timer_sync("grow_tree") as grow_scope:
                tree, leaf_id, aux = self._grow_one(gc, hc, mask, fmask,
                                                    iter_key, hm)
                grow_scope.sync(tree.num_leaves)
            if aux is not None:
                self._record_aux_counters(aux)
                if cfg.check_numerics and float(aux.sentinel):
                    # same judge as the fused path so the histogram-plane
                    # defect is reported with ONE message either way
                    self._check_sentinel_flags(1 << 2)
            # pre-partitioned: leaf_id comes back row-sharded; keep only
            # this process's rows for the local score update (the
            # reference's per-machine score partition, score_updater.hpp —
            # no O(N_global) array is ever materialized per host)
            leaf_id = self._localize_leaf_id(leaf_id)
            if self._cegb_mode != "off":
                # CEGB feature-used tracking persists across iterations
                # (cost_effective_gradient_boosting.hpp Init: !init_ reuse)
                self._cegb_aux = aux
            lin = None
            if cfg.linear_tree:
                # "first tree" counts loaded init-model trees too
                # (reference: models_.size() < num_tree_per_iteration_)
                first_tree = len(self.trees) < k and self.loaded_iters == 0
                lin = self._fit_linear_leaves(tree, leaf_id, gc, hc, mask,
                                              first_tree)
            lazy = lin is None and self._lazy_host_ok()
            with profiling.timer("finalize_tree"):
                if lazy:
                    # shrink on device only; the host mirror fetch is async
                    # (see host_trees) — no blocking round-trip this iter
                    tree = _shrink_tree(tree, self.shrinkage_rate)
                    t_host, had_split = None, True
                else:
                    tree, t_host, had_split = self._finalize_tree(
                        tree, leaf_id, c)
            no_split = no_split and not had_split
            with profiling.timer("score_update", sync=None):
                if lin is not None:
                    self._add_tree(tree, leaf_id, c, linear=lin, t_host=t_host)
                else:
                    self._add_tree(tree, leaf_id, c, t_host=t_host, lazy=lazy)
                self._bias_after_score(c, had_split)
        self.iter += 1
        # lagged no-split detection for lazy iterations: consume whatever
        # mirrors already finished (non-blocking) and report the stop one
        # or more iterations late — the extra trees are splitless zero
        # trees, prediction-identical to stopping on time
        self._flush_pending(only_ready=True)
        return no_split or self._lagged_stop

    def _block_rounds(self) -> int:
        """How many iterations the NEXT fused dispatch should grow — the
        ``boost_rounds_per_dispatch`` K, clipped so blocks (a) never run
        past the engine's round target and (b) always END on a multiple
        of K (the first block after an unaligned resume truncates to
        re-align), which is what lets a checkpoint callback whose period
        is a multiple of K fire on schedule. 1 unless engine.train has
        opted in for this run (``_block_target``): a manual
        ``Booster.update`` loop or cv() must keep one-iteration-per-call
        semantics, or its round counting would double-train."""
        cfg = self.config
        K = max(1, int(cfg.boost_rounds_per_dispatch))
        if K <= 1:
            return 1
        target = getattr(self, "_block_target", None)
        if target is None or getattr(self, "_block_disable", False):
            return 1
        remaining = int(target) - self.iter
        aligned = K - (self.iter % K)
        return max(1, min(aligned, remaining))

    def _fused_call_args(self, fmask, bind, it=None):
        """The fused step/block argument tuple — ONE definition shared by
        the training dispatch and the AOT warmup (warm_start), so the
        warmed program signature can never drift from the called one."""
        bag_mode = self._bagging_mode()
        bag_frac = self._bagging_frac() if bag_mode == "mask" else None
        cegb_state = self._fused_cegb_state()
        return (self.train_score,
                np.int32(self.iter if it is None else it),
                np.float32(self.shrinkage_rate), fmask, self.split_params,
                bag_frac, cegb_state, self._rows_streamed_dev,
                self._coll_bytes_dev, self._leaves_resolved_dev,
                None if self._parallel_grower is None
                else self._sync_calls_dev, bind)

    def _train_one_iter_fused(self) -> bool:
        """Fused iteration for every admitted configuration (see
        _fused_step_fn): TWO compiled-program dispatches — the fused grow
        step and the donated in-place score add — versus three-plus (and
        per-class multiples) on the unfused path; everything after
        mirrors the unfused finalize/add/bias flow per class. The step
        returns SHRUNK trees, so on the steady-state lazy path nothing
        else dispatches — the telemetry tests assert it stays that way.

        With ``boost_rounds_per_dispatch`` K > 1 under engine.train, the
        whole K-iteration BLOCK runs instead (_train_block_fused): ONE
        dispatch grows K*C trees with the score carried in-program."""
        K = self._block_rounds()
        if K > 1:
            return self._train_block_fused(K)
        hm = self._hist_method()
        fmask = self._feature_mask_np()
        step, bind = self._fused_step_fn(hm, fmask is not None)
        bag_mode = self._bagging_mode()
        if bag_mode != "off":
            self._bag_stale = True   # host mask not refreshed this iter
        prev = None
        if profiling.enabled():
            prev = self._aux_counter_values()
        with profiling.timer_sync("grow_tree") as grow_scope:
            with profiling.span("fused_dispatch"):
                (trees, delta, self._rows_streamed_dev,
                 self._coll_bytes_dev, self._leaves_resolved_dev,
                 cegb_aux, sent_flags, sync_calls) = step(
                    *self._fused_call_args(fmask, bind))
            if sync_calls is not None:
                self._sync_calls_dev = sync_calls
            grow_scope.sync(trees[0].num_leaves)
        if self.config.check_numerics:
            # the flag word is judged LAZILY (_drain_sentinels below): a
            # blocking scalar fetch here — or even a fixed one-iteration
            # lag — serializes the host against the dispatch queue, the
            # pipelining the fused path exists for (measured ~15-40% at
            # small CPU shapes). Instead the device scalar joins a FIFO
            # judged by non-blocking ready checks, the same lagged
            # pattern as the async host-tree mirrors; every state-capture
            # path (host_trees, get_trainer_state, training end) flushes
            # it blockingly first, so poisoned state can briefly exist in
            # memory but is never read out or written. Still 2
            # dispatches/iter.
            self._sentinel_pending.append((self.iter, sent_flags))
        if cegb_aux is not None:
            self._cegb_aux = cegb_aux
        if prev is not None:
            self._count_aux_since(prev)
        with profiling.span("score_dispatch"):
            self.train_score = _apply_score_delta(self.train_score, delta)
        lazy = self._lazy_host_ok(sentinels=True)
        no_split = True
        for c, tree in enumerate(trees):
            with profiling.timer("finalize_tree"):
                if lazy:
                    t_host, had_split = None, True
                else:
                    # trees arrive pre-shrunk; renew/linear are excluded
                    # by _fused_ok and check_numerics is covered by the
                    # in-program sentinels, so finalize reduces to the
                    # host-mirror fetch
                    t_host = self._fetch_tree(tree)
                    had_split = int(t_host.num_leaves) > 1
            no_split = no_split and not had_split
            with profiling.timer("score_update", sync=None):
                self._add_tree(tree, None, c, t_host=t_host, lazy=lazy,
                               score_updated=True)
                self._bias_after_score(c, had_split)
        self.iter += 1
        self._flush_pending(only_ready=True)
        with profiling.span("sentinel_drain"):
            self._drain_sentinels()
        return (not lazy and no_split) or self._lagged_stop

    def _train_block_fused(self, K: int) -> bool:
        """K boosting iterations in ONE compiled-program dispatch (the
        ``boost_rounds_per_dispatch`` block, _fused_step_fn's scan mode):
        the score cache is donated in and carried through the scan, K*C
        shrunk trees come back stacked, and the host-side finalize/add/
        bias flow then runs per iteration in order — so valid-set scores,
        the bias fold and the lagged-stop bookkeeping are identical to K
        separate fused iterations. Everything external (callbacks, eval,
        checkpoints) happens at block boundaries only; engine.train
        validates the checkpoint period against K and advances its round
        counter by the consumed count."""
        hm = self._hist_method()
        fmask_on = self.config.feature_fraction < 1.0
        fmask = None
        if fmask_on:
            # the SAME stateful host rng stream, drawn K iterations ahead
            # in the per-iteration order (bit-parity with K single steps)
            fmask = np.stack([self._feature_mask_np() for _ in range(K)])
        step, bind = self._fused_step_fn(hm, fmask_on, k_rounds=K)
        if self._bagging_mode() != "off":
            self._bag_stale = True   # host mask not refreshed this block
        it0 = self.iter
        prev = None
        if profiling.enabled():
            prev = self._aux_counter_values()
        with profiling.timer_sync("grow_tree") as grow_scope:
            with profiling.span("fused_dispatch"):
                (trees, self.train_score, self._rows_streamed_dev,
                 self._coll_bytes_dev, self._leaves_resolved_dev,
                 cegb_aux, sent_flags, sync_calls) = step(
                    *self._fused_call_args(fmask, bind))
            if sync_calls is not None:
                self._sync_calls_dev = sync_calls
            grow_scope.sync(trees[0][0].num_leaves)
        if self.config.check_numerics:
            # one [K] flag vector per block, judged lazily like the
            # per-iteration scalars (_drain_sentinels names it0 + j)
            self._sentinel_pending.append((it0, sent_flags))
        if cegb_aux is not None:
            self._cegb_aux = cegb_aux
        if prev is not None:
            self._count_aux_since(prev)
        lazy = self._lazy_host_ok(sentinels=True)
        stop = False
        for j in range(K):
            no_split = True
            for c, tree in enumerate(trees[j]):
                with profiling.timer("finalize_tree"):
                    if lazy:
                        t_host, had_split = None, True
                    else:
                        t_host = self._fetch_tree(tree)
                        had_split = int(t_host.num_leaves) > 1
                no_split = no_split and not had_split
                with profiling.timer("score_update", sync=None):
                    self._add_tree(tree, None, c, t_host=t_host, lazy=lazy,
                                   score_updated=True)
                    self._bias_after_score(c, had_split)
            self.iter += 1
            # a splitless iteration anywhere in the block arms the stop;
            # any later trees of the same block are splitless zero trees,
            # prediction-identical to stopping on time (the same argument
            # as the lazy path's lagged stop)
            stop = stop or (not lazy and no_split)
        self._flush_pending(only_ready=True)
        with profiling.span("sentinel_drain"):
            self._drain_sentinels()
        return stop or self._lagged_stop

    # --------------------------------------------------- AOT compile warm
    def warm_start(self, k_rounds: Optional[int] = None) -> bool:
        """AOT-compile the training programs for the current
        configuration — ``jax.jit(...).lower(...).compile()`` on the
        fused step/block (which embeds the grower) and the donated score
        add, with argument shapes taken from the live trainer state so
        the warmed signatures exactly match the first real dispatch.

        With the persistent compilation cache configured
        (``compile_cache_dir``), this is how a restarted supervisor
        incarnation, a resumed elastic gang or a second same-shape
        process starts HOT: the XLA compile the first boosting step would
        pay becomes a disk-cache deserialization here, before the
        training loop begins. Without the cache it still moves the
        compile wall out of the measured first iteration: jax keeps the
        executable for the call, so the first step then asks for no
        compile at all (tests/test_compile_wall.py pins it). Returns True
        when a program was AOT-compiled; False (with the reason logged at
        info) when the configuration is not fused-eligible."""
        from .. import compile_cache
        if self.train_set is None or not self._fused_ok(None):
            return False
        try:
            K = k_rounds if k_rounds is not None else self._block_rounds()
            # outside engine.train (_block_target unset) warm the
            # configured block size directly: the warmed program must be
            # the one the training loop will dispatch
            if k_rounds is None and K == 1:
                cfgK = max(1, int(self.config.boost_rounds_per_dispatch))
                if cfgK > 1:
                    K = cfgK - (self.iter % cfgK)
            hm = self._hist_method()
            fmask_on = self.config.feature_fraction < 1.0
            step, bind = self._fused_step_fn(hm, fmask_on,
                                             k_rounds=K)
            fmask = self._fmask_shape(K) if fmask_on else None
            args = self._fused_call_args(fmask, bind)
            ok = compile_cache.aot_compile(step, args, label="fused_step")
            if ok and K == 1:
                # the per-iteration mode's second dispatch: the donated
                # in-place score add (block mode carries it in-program)
                compile_cache.aot_compile(_apply_score_delta,
                                          self._score_delta_shapes(),
                                          label="score_delta")
            return ok
        except Exception as e:   # warmup must never break training
            log.warning(f"AOT compile warmup failed (training will "
                        f"compile lazily instead): {e}")
            return False

    def _grow_one(self, gc: jax.Array, hc: jax.Array, mask: jax.Array,
                  fmask: jax.Array, iter_key: jax.Array, hm: str):
        """Dispatch one tree's growth to the serial grower or the configured
        parallel learner (the analog of TreeLearner::Train through the
        factory-selected learner, tree_learner.h:104)."""
        cfg = self.config
        ts = self.train_set
        if ts.num_dense_columns() == 0 and not getattr(ts, "has_sparse_cols",
                                                 False):
            # every feature pre-filtered as trivial (e.g. min_data_in_leaf
            # too large for the data — the reference's feature_pre_filter,
            # dataset_loader.cpp:647-648): train a splitless constant tree
            # like the reference instead of dispatching a 0-feature grower
            from .tree import empty_tree
            n = (ts.num_local_data if getattr(self, "_pre_part", False)
                 else ts.num_data)
            return (empty_tree(cfg.num_leaves),
                    jnp.zeros((n,), dtype=jnp.int32), None)
        pg = self._parallel_grower
        if pg is not None and pg.takes_row_shards(
                getattr(ts, "row_bins", None)):
            # a row-sharded set: the fused step's build-once constants,
            # per-call pads of the O(N) vectors only
            pb = self._fused_parallel_bindings(hm)
            shard = pg.get_shard_fn(pb["extras_spec"], tuple(sorted(
                self._parallel_grow_statics(hm).items())))
            n_pad, n = pb["n_pad"], ts.num_data
            tree, leaf_id, aux = shard(
                pb["bins"], jnp.pad(gc, (0, n_pad)), jnp.pad(hc, (0, n_pad)),
                jnp.pad(mask, (0, n_pad)), pb["meta"], self.split_params,
                jnp.pad(fmask, (0, pb["f_pad"])), pb["missing_bin"],
                pb["extras"], iter_key)
            return tree, leaf_id[:n], aux
        if pg is not None:
            return pg(
                ts.bins, gc, hc, mask,
                ts.feature_meta, self.split_params, fmask, ts.missing_bin,
                binsT=ts.bins_T if hm.startswith(("onehot", "pallas")) else None,
                pre_part=getattr(self, "_pre_part", False),
                rng_key=iter_key,
                bundle_meta=ts.bundle_meta,
                forced_splits=self._forced_splits,
                **self._parallel_grow_statics(hm))
        sub = self._bag_sub
        has_sp = getattr(ts, "has_sparse_cols", False)
        return grow_tree(
            ts.bins, gc, hc, mask,
            ts.feature_meta, self.split_params, fmask, ts.missing_bin,
            binsT=ts.bins_T if self._use_binsT(hm) else None,
            sub_idx=sub[0] if sub else None,
            sub_bins=sub[1] if sub else None,
            sub_binsT=sub[2] if sub else None,
            interaction_groups=self._interaction_groups,
            cegb_coupled=self._cegb_coupled,
            cegb_lazy_penalty=self._cegb_lazy,
            cegb_state=self._cegb_aux,
            bynode_fraction=jnp.float32(cfg.feature_fraction_bynode)
            if self._use_bynode else None,
            rng_key=iter_key,
            bundle_meta=ts.bundle_meta,
            forced_splits=self._forced_splits,
            sp_rows=ts.sp_rows if has_sp else None,
            sp_cell=ts.sp_cell if has_sp else None,
            sp_default=ts.sp_default if has_sp else None,
            **self._serial_grow_statics(hm))

    def _use_binsT(self, hm: str) -> bool:
        """The feature-major bins copy doubles the dominant array; above
        ~2 GiB keep only the row-major matrix (pallas kernels then fall
        back to the XLA onehot formulation, with routing slicing rows)."""
        if not hm.startswith(("onehot", "pallas")):
            return False
        ts = self.train_set
        itemsize = 4 if ts.max_num_bins > 256 else 1   # int32 vs uint8 bins
        # per-HOST bytes: pre-partitioned data is row-sharded, so the copy
        # costs each host only its shard
        rows = (ts.num_local_data if getattr(self, "_pre_part", False)
                else ts.num_data)
        bins_bytes = int(rows) * int(ts.num_used_features()) * itemsize
        if bins_bytes <= 2 << 30:
            return True
        if not getattr(self, "_warned_binst", False):
            self._warned_binst = True
            log.warning(
                f"bins matrix is {bins_bytes / 2**30:.1f} GiB: skipping the "
                "feature-major copy (binsT) to halve memory; pallas "
                "histogram kernels fall back to the XLA path")
        return False

    def _feature_block(self, hm: str) -> int:
        """Column-block width for the grower's memory-bounded mode, or 0
        to keep the resident [L, F, B, 3] histogram state.

        Engages when that state would exceed ``histogram_pool_size``
        (the reference's pool cap, config.h histogram_pool_size in MB;
        <= 0 here means a 2 GiB auto cap rather than unlimited: the
        state, a second copy of it where the grow loop cannot alias it,
        and the tiles have to fit the chip beside the data). The analog
        of the reference's HistogramPool LRU
        (feature_histogram.hpp:1095-1290): over-cap leaves pay
        recomputation instead of residency. This is about MEMORY only.
        Width by itself needs no such mode: the histogram kernel walks
        any number of device columns in feature blocks
        (pallas_hist.feature_block), and a 400,000 x 2,000 job at 255
        leaves (1.56 GB of state) keeps the resident state, the fused
        epilogue, the ladder and histogram subtraction."""
        cfg = self.config
        ts = self.train_set
        f_cols = ts.num_used_features()
        B = ts.max_num_bins
        hist_bytes = cfg.num_leaves * f_cols * B * 3 * 4
        pool = cfg.histogram_pool_size
        cap = int(pool * 1024 * 1024) if pool and pool > 0 else 2 << 30
        if hist_bytes <= cap:
            return 0
        subset_possible = (cfg.bagging_freq > 0
                           and cfg.bagging_fraction <= 0.5
                           and cfg.pos_bagging_fraction >= 1.0
                           and cfg.neg_bagging_fraction >= 1.0
                           and self._cegb_mode == "off"
                           and not cfg.linear_tree)
        unsupported = (self._cegb_mode != "off"
                       or self._forced_splits is not None
                       or (self._with_monotone
                           and self._mono_mode != "basic")
                       or subset_possible or self._hist_dp
                       or hm.endswith("_q8")
                       or getattr(ts, "has_sparse_cols", False))
        if unsupported:
            if not getattr(self, "_warned_pool", False):
                self._warned_pool = True
                log.warning(
                    f"histogram state ({hist_bytes / 2**20:.0f} MB) exceeds "
                    f"the pool cap ({cap / 2**20:.0f} MB) but the "
                    "memory-bounded mode does not support "
                    "CEGB/forced-splits/box-monotone/subset-bagging/f64/q8 "
                    "here; keeping the resident state (may OOM)")
            return 0
        tile = self._hist_plan(hm)[0]
        P = (min(tile, cfg.num_leaves)
             if hm.startswith(("onehot", "pallas")) else cfg.num_leaves)
        # transient per feature column: the [P, B, 3] tile plus ~8
        # search-sized temporaries
        per_f = P * B * 4 * (3 + 8)
        fb = max(16, min(f_cols, cap // per_f))
        if not getattr(self, "_warned_pool", False):
            self._warned_pool = True
            log.warning(
                f"histogram state ({hist_bytes / 2**20:.0f} MB) exceeds the "
                f"pool cap ({cap / 2**20:.0f} MB): memory-bounded growth "
                f"engaged ({fb} feature columns per pass, no histogram "
                "subtraction — ~2x the histogram passes)")
        return fb

    def _localize_leaf_id(self, leaf_id: jax.Array) -> jax.Array:
        """Pre-partitioned mode: slice this process's rows out of the
        row-sharded global leaf-id vector (identity otherwise)."""
        if not getattr(self, "_pre_part", False):
            return leaf_id
        n_local = self.train_set.num_local_data
        if leaf_id.is_fully_addressable:
            return leaf_id[:n_local]
        shards = sorted(leaf_id.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        local = np.concatenate([np.asarray(s.data) for s in shards])
        return jnp.asarray(local[:n_local])

    def _hist_interpret(self) -> bool:
        """Run Pallas histogram kernels through the interpreter: only when
        asked (hist_pallas_interpret) and only off-TPU — on TPU the real
        kernel always wins and the flag is inert."""
        return (self.config.hist_pallas_interpret
                and jax.default_backend() != "tpu")

    def _split_fusion_on(self, hm: str, fb: int = 0) -> bool:
        """Resolve Config.split_fusion for this booster's configuration.

        "auto" engages the fused split-finding epilogue whenever the
        numerical non-bundled search is the whole story (the fused scan
        covers missing-direction both ways, min_data/min_hessian masks
        and basic monotone constraints; categorical / EFB / forced-split
        / CEGB / extra_trees / bynode / advanced-monotone semantics stay
        in find_best_splits, so those configurations keep the classic
        split phase). "on" raises on an unsupported configuration
        instead of silently degrading."""
        cfg = self.config
        mode = getattr(cfg, "split_fusion", "auto")
        if mode == "off" or self.train_set is None:
            return False
        ts = self.train_set
        reasons = []
        if self._parallel_grower is not None:
            reasons.append("parallel learner")
        if ts.has_categorical:
            reasons.append("categorical features")
        if ts.bundle_meta is not None:
            reasons.append("EFB bundles")
        if self._forced_splits is not None:
            reasons.append("forced splits")
        if self._cegb_mode != "off":
            reasons.append("CEGB")
        if cfg.extra_trees:
            reasons.append("extra_trees")
        if self._use_bynode:
            reasons.append("feature_fraction_bynode")
        if self._with_monotone and self._mono_mode != "basic":
            reasons.append(f"{self._mono_mode} monotone constraints")
        if cfg.feature_contri and min(cfg.feature_contri) <= 0:
            # the fused path applies the contri multiplier AFTER the
            # within-feature argmax (find_best_splits applies it per
            # bin); the two commute only for positive multipliers — a
            # zero/negative entry flips or flattens the within-feature
            # order, so those configs keep the classic phase
            reasons.append("non-positive feature_contri")
        if self._hist_dp:
            reasons.append("f64 histograms")
        if getattr(ts, "has_sparse_cols", False):
            reasons.append("sparse device columns")
        if fb:
            reasons.append("memory-bounded (feature-blocked) growth")
        if mode == "on" and reasons:
            raise ValueError(
                "split_fusion=on is unsupported with "
                + ", ".join(reasons)
                + " (these split semantics live in the classic search; "
                "use split_fusion=auto to fall back automatically)")
        return not reasons

    def _hist_plan(self, hm: str) -> tuple:
        """(tile_leaves, hist_block, feature_block) of the grow statics,
        the serial and the parallel learners' alike: a pure function of
        the configuration, the method and the shape. Explicit config
        values win; else the kernel's structural leaf batch and, for the
        Pallas methods, DEFAULT_BLOCK (ops/pallas_hist.py; 0 leaves an
        XLA formulation its own blocking); the OOM ladder's rung 1 then
        caps the block. ``feature_block`` is the width of the device
        columns one kernel body covers (pallas_hist.feature_block: the
        kernels compute it from their operands, nothing hands it to
        them; 0 for a method that is no kernel): the whole width up to
        ONE_BLOCK_FEATURES, so one block a launch at every width under
        it."""
        from ..ops.histogram import _KERNEL_MODE
        from ..ops.pallas_hist import (DEFAULT_BLOCK, feature_block,
                                       structural_tile_leaves)
        cfg = self.config
        ts = self.train_set
        pallas = hm.startswith("pallas")
        blk = cfg.hist_block or (DEFAULT_BLOCK if pallas else 0)
        fblk = 0
        if pallas and ts is not None:
            # a parallel learner's kernel sees the same columns: the data
            # learner shards rows, and a feature shard is narrower still
            fblk = feature_block(ts.num_dense_columns(),
                                 int(ts.max_num_bins), _KERNEL_MODE[hm])
        return (cfg.tile_leaves or structural_tile_leaves(),
                self._eff_hist_block(blk), fblk)

    def hist_plan(self) -> dict:
        """The histogram plan this booster trains with, as the library
        resolves it for the configuration, the platform and the training
        set's shape (nothing is timed: two boosters over one shape say
        the same): the method, the leaves a tile pass computes, the rows
        and the device columns one kernel body covers, the feature blocks
        a kernel launch walks, whether the split search runs in the
        kernel's epilogue, and the compaction rungs kept. The flight
        recorder's header carries the same fields."""
        from ..ops.pallas_hist import feature_blocks
        hm = self._hist_method()
        tile, blk, fblk = self._hist_plan(hm)
        # the parallel learners search in the classic phase, without rungs
        statics = ({"split_fusion": False, "compaction_ladder": ()}
                   if self._parallel_grower is not None
                   else self._serial_grow_statics(hm))
        cols = self.train_set.num_dense_columns()
        return {"hist_method": hm, "tile_leaves": int(tile),
                "hist_block": int(blk), "feature_block": int(fblk),
                "feature_blocks": feature_blocks(cols, fblk) if fblk else 0,
                "device_columns": int(cols),
                "split_fusion": bool(statics["split_fusion"]),
                "compaction_ladder": [int(m) for m in
                                      statics["compaction_ladder"]]}

    @property
    def hist_feature_blocks(self) -> int:
        """Feature blocks one histogram kernel launch walks (1 up to
        pallas_hist.ONE_BLOCK_FEATURES device columns; 0 where the method
        is no kernel): with ``rows_streamed_total`` over the rows held,
        the passes, it counts the kernel bodies' sweeps over the rows."""
        return self.hist_plan()["feature_blocks"]

    def _hist_method(self) -> str:
        """The histogram method this booster runs: ``resolve_method``'s
        answer for the configuration and the platform (computed on every
        call, under the span "plan"), unless the OOM ladder's rung 2
        forced the XLA fallback (the override rides the trainer
        state)."""
        cfg = self.config
        if self._oom_hm:
            return self._oom_hm
        with profiling.span("plan"):
            from ..ops.histogram import resolve_method
            return resolve_method(cfg.histogram_method,
                                  deterministic=cfg.deterministic,
                                  quantized=cfg.quantized_grad,
                                  interpret=self._hist_interpret())

    def _sample_weights(self, g, h) -> Optional[jax.Array]:
        """Hook for GOSS-style reweighted sampling; None = use bag mask."""
        return None

    # ---------------------------------------------------- numerics guard
    def _check_numerics_grad(self, g: jax.Array, h: jax.Array) -> None:
        """check_numerics fail-fast: NaN/Inf gradients or hessians poison
        every histogram they touch and surface much later as garbage
        splits — name the iteration and offending count NOW instead."""
        bad_g = int(jnp.sum(~jnp.isfinite(g)))
        bad_h = int(jnp.sum(~jnp.isfinite(h)))
        if bad_g or bad_h:
            log.fatal(
                f"check_numerics: iteration {self.iter}: {bad_g} non-finite "
                f"gradient and {bad_h} non-finite hessian values out of "
                f"{int(np.prod(g.shape))} — failing fast before they poison "
                f"the histograms (check the objective / custom fobj, "
                f"learning_rate, and input features)")

    def _check_numerics_leaves(self, t_host, num_leaves: int) -> None:
        """check_numerics on a finalized tree's leaf outputs."""
        lv = np.asarray(t_host.leaf_value[:max(num_leaves, 1)])
        bad = int(np.sum(~np.isfinite(lv)))
        if bad:
            log.fatal(
                f"check_numerics: iteration {self.iter}: {bad} of "
                f"{max(num_leaves, 1)} leaf outputs in the new tree are "
                f"non-finite — failing fast before the score caches are "
                f"poisoned")

    def _check_sentinel_flags(self, flags: int,
                              iteration: Optional[int] = None) -> None:
        """Judge the fused step's in-program sentinel flag word: nonzero
        bits name which sources carried NaN/Inf (see _SENTINEL_SOURCES) —
        fail fast with the iteration and sources spelled out."""
        if not flags:
            return
        it = self.iter if iteration is None else iteration
        sources = [name for bit, name in _SENTINEL_SOURCES
                   if flags & (1 << bit)]
        log.fatal(
            f"check_numerics: iteration {it}: in-program sentinels "
            f"flagged non-finite values in {', '.join(sources)} "
            f"(flag word 0b{flags:05b}) — failing fast before they poison "
            f"the model on disk (check the objective / custom fobj, "
            f"learning_rate, and input features)")

    def _drain_sentinels(self) -> None:
        """Judge every pending sentinel word whose step has already
        finished — non-blocking ready checks, oldest first (so the FIRST
        poisoned iteration is the one named), mirroring
        ``_flush_pending(only_ready=True)``. A backend without
        ``is_ready()`` judges everything (blocking) — the guard stays
        correct, just without the pipelined fetch. The FIFO is bounded:
        past 64 pending words the oldest is judged blockingly, which
        bounds both memory and detection lag."""
        q = self._sentinel_pending
        while q:
            it, flags = q[0]
            if len(q) <= 64:
                try:
                    if not flags.is_ready():
                        break
                except AttributeError:
                    pass
            q.pop(0)
            self._judge_sentinel(it, flags)

    def _flush_sentinel(self) -> None:
        """Blocking judge of EVERY deferred in-program sentinel word
        (fused path). The per-iteration fetch is lazy (_drain_sentinels)
        so it never stalls the dispatch pipeline; every state-capture
        path — ``host_trees``, ``get_trainer_state`` (the checkpoint
        capture), rollback, training end — flushes here first, so
        poisoned state is never read out or written."""
        q = self._sentinel_pending
        while q:
            it, flags = q.pop(0)
            self._judge_sentinel(it, flags)

    def _judge_sentinel(self, it: int, flags) -> None:
        """Judge one pending sentinel entry: a scalar word (per-iteration
        fused step) or a [K] vector (one word per iteration of a
        ``boost_rounds_per_dispatch`` block, oldest first so the FIRST
        poisoned iteration is the one named)."""
        arr = np.atleast_1d(np.asarray(flags))
        for j in range(arr.size):
            word = int(arr[j])
            if self._flight is not None:
                # back-fill the verdict into the covering flight record
                # BEFORE judging: a nonzero word raises, and the flushed
                # post-mortem must name the poisoned iteration
                self._flight.note_sentinel(it + j, word)
            self._check_sentinel_flags(word, it + j)

    # ------------------------------------------------ OOM degradation
    def _eff_hist_block(self, blk: int) -> int:
        """Histogram row-block size after the OOM ladder's rung-1 override
        (0 keeps the per-method auto default)."""
        if not self._oom_block:
            return blk
        return self._oom_block if not blk else min(blk, self._oom_block)

    def _predicted_hist_bytes(self) -> Optional[int]:
        """The histogram traffic model's predicted HBM bytes for ONE
        pass under the CURRENT configuration (ops/pallas_hist
        traffic_model — a static model, not a measurement): the number
        that makes an OOM rung step explainable next to the allocator
        snapshot ("the model said this pass moves N bytes; the device
        had M free"). Chooses the formulation the active hist method
        actually runs (fused kernel vs the XLA one-hot materialization);
        None when the shape is not yet known."""
        try:
            from ..ops.pallas_hist import _PAD, traffic_model
            ts = self.train_set
            n = int(ts.num_data)
            f = ts.num_dense_columns()
            b = int(ts.max_num_bins)
            s = 3
            mode = "q8" if getattr(self.config, "quantized_grad", False) \
                else "hilo"
            t = traffic_model(n, f, b, _PAD // s, s, mode)
            hm = self._hist_method()
            if "onehot" in hm or hm in ("scatter", "binloop"):
                key = "xla_onehot"
            else:
                # the kernel pass the booster actually dispatches: the
                # epilogue formulation only when split fusion resolved
                # ON for this configuration — the pre-fusion kernel
                # round-trips the RHS planes the epilogue keeps in VMEM
                key = ("fused" if self._split_fusion_on(
                    hm, self._feature_block(hm)) else "prefusion")
            return int(t[key])
        except Exception:
            return None

    def _oom_memory_evidence(self) -> Dict[str, Any]:
        """The explainability payload every OOM degradation event
        carries: the allocator/host snapshot AT failure plus the traffic
        model's predicted per-pass bytes (fields null where a source is
        unavailable — CPU backends have no allocator stats)."""
        return {"memory": profiling.sample_memory(),
                "predicted_hist_bytes": self._predicted_hist_bytes()}

    def _maybe_degrade_oom(self, exc: BaseException,
                           ntrees_before: int) -> bool:
        """Step the booster down ONE rung of the documented OOM degradation
        ladder and report whether the failed iteration may be retried:

          1. smaller histogram row block (less transient VMEM/HBM per
             pass, more passes),
          2. ``hist_method`` -> the XLA scatter formulation (no one-hot
             materialization, no Pallas VMEM tiles — the smallest-footprint
             backend; q8 keeps its integer form via onehot_q8),
          3. chunked predict buckets (bounds the eval/serving programs'
             resident rows).

        Every degradation is recorded in ``distributed.health_snapshot()``
        (and therefore every later checkpoint manifest's health section),
        the ``hist_oom_degrade_level`` gauge and a WARNING — the job keeps
        running, but visibly DEGRADED, instead of dying. The degraded
        configuration rides the trainer state (get_trainer_state) so a
        resumed incarnation reuses it (the bit-identical-restart
        contract). False (re-raise) when
        the guard is off, the error is not a RESOURCE_EXHAUSTED, an
        earlier class of this multiclass iteration already adopted a tree
        (retry would double-count), or the ladder is exhausted."""
        from .. import distributed
        from ..utils import faults
        if not self.config.hist_oom_fallback \
                or not faults.is_resource_exhausted(exc):
            return False
        try:
            score_gone = bool(self.train_score.is_deleted())
        except Exception:
            score_gone = False
        if score_gone:
            self._flush_flight(
                f"oom-exhausted: donated score cache consumed at "
                f"iteration {self.iter}")
            # the K-block step DONATES the score cache; an OOM during
            # EXECUTION (not compile — the common case — which fails
            # before any donation) may have consumed the buffer, so the
            # iteration cannot be retried in-process. Fail stop with the
            # real remedy named instead of crashing the retry on a
            # deleted array.
            log.warning(
                f"RESOURCE_EXHAUSTED in boosting iteration {self.iter}: "
                f"the failed K-block dispatch consumed the donated score "
                f"cache, so the degradation ladder cannot retry "
                f"in-process — resume from the last checkpoint (or set "
                f"boost_rounds_per_dispatch=1) with a smaller "
                f"hist_block/scatter fallback")
            return False
        if jax.process_count() > 1:
            # gangs FAIL-STOP on a training OOM instead of degrading: the
            # ladder's rungs change accumulation shape (numerics), so one
            # rank degrading alone would break the rank-symmetric
            # reduction contract — and be named corrupt by the very
            # divergence vote this layer adds. The supervisor's
            # restart/shrink path owns rank-local resource failures.
            self._flush_flight(
                f"oom-exhausted: multi-process fail-stop at iteration "
                f"{self.iter}")
            log.warning(
                f"RESOURCE_EXHAUSTED in boosting iteration {self.iter}: "
                f"per-rank degradation is disabled in multi-process gangs "
                f"(it would silently break the rank-symmetric reductions) "
                f"— failing stop for the supervisor to restart or shrink")
            return False
        if len(self.trees) != ntrees_before:
            return False
        if self._oom_level >= 3:
            # ladder exhausted: the exception re-raises and kills the run
            # — the flushed ring is the post-mortem naming every rung
            # this booster already stepped down
            self._flush_flight(
                f"oom-exhausted: ladder spent at iteration {self.iter} "
                f"(level {self._oom_level}/3)")
            return False
        self._oom_level += 1
        if self._oom_level == 1:
            from ..ops.pallas_hist import oom_shrink_block
            self._oom_block = oom_shrink_block(
                self._hist_plan(self._hist_method())[1])
            action = f"hist_block -> {self._oom_block}"
        elif self._oom_level == 2:
            from ..ops.histogram import oom_fallback_method
            self._oom_hm = oom_fallback_method(self._hist_method())
            action = f"hist_method -> {self._oom_hm} (XLA fallback)"
        else:
            base = self.config.predict_chunk_rows or (1 << 22)
            self._oom_predict_chunk = max(1 << 14, base // 4)
            action = f"predict_chunk_rows -> {self._oom_predict_chunk}"
        # degraded statics must recompile: drop every cached program that
        # baked the old histogram configuration in
        self._fused_cache.clear()
        self._engine_cache.clear()
        distributed.record_degradation({
            "kind": "oom", "iteration": int(self.iter),
            "level": int(self._oom_level), "action": action,
            "error": str(exc)[:200], **self._oom_memory_evidence()})
        profiling.set_gauge("hist_oom_degrade_level", self._oom_level)
        log.warning(
            f"RESOURCE_EXHAUSTED in boosting iteration {self.iter}: "
            f"degrading ({action}; ladder rung {self._oom_level}/3) and "
            f"retrying — the job continues DEGRADED (recorded in "
            f"health_snapshot()/gauges and checkpoint manifests)")
        return True

    def _maybe_degrade_predict_oom(self, exc: BaseException) -> bool:
        """Predict-path entry to the ladder's rung 3: halve the effective
        predict chunk (repeatably, floor 16k rows) so the serving program
        holds fewer resident rows, and retry. Deliberately does NOT touch
        ``_oom_level``: predict chunking is numerics-exact and independent
        of the training rungs — a serve-time OOM must not consume the
        hist-block/scatter rungs a later training OOM may still need."""
        from .. import distributed
        from ..utils import faults
        nxt = faults.next_predict_chunk(
            exc, self._oom_predict_chunk or self.config.predict_chunk_rows,
            self.config.hist_oom_fallback)
        if nxt is None:
            return False
        with self._engine_lock:
            # chunk update + cache clear under the engine lock: a
            # concurrent _predict_engine fill must not read the old chunk
            # and re-publish a stale engine after this clear (the retry
            # would OOM again and burn an extra ladder rung)
            self._oom_predict_chunk = nxt
            self._engine_cache.clear()
        action = f"predict_chunk_rows -> {self._oom_predict_chunk}"
        distributed.record_degradation({
            "kind": "oom_predict", "iteration": int(self.iter),
            "level": int(self._oom_level), "action": action,
            "error": str(exc)[:200], **self._oom_memory_evidence()})
        profiling.set_gauge("predict_oom_chunk_rows",
                            float(self._oom_predict_chunk))
        log.warning(f"RESOURCE_EXHAUSTED in predict: degrading ({action}) "
                    f"and retrying")
        return True

    def _flush_flight(self, reason: str) -> Optional[str]:
        """Flush THIS booster's flight recorder (not the process-global
        one): in multi-booster processes — lgb.cv folds, bench probes —
        the module slot holds the last-configured booster's ring, and a
        fold-0 OOM post-mortem carrying fold k-1's records would
        misattribute the failure. Context-free flush paths (watchdog,
        faults._hard_exit) still use the module recorder, the best
        available without a booster in hand."""
        if self._flight is None:
            return None
        return self._flight.flush(reason)

    def _note_ready(self, it: int, rows_dev) -> None:
        """Iteration ``it``'s outputs were seen ready just now: the late
        fields of its flight record, from the step's cumulative
        rows-streamed scalar (an output of the same program as the tree
        that was just fetched: reading it waits for nothing)."""
        if self._flight is not None:
            self._flight.note_ready(it, float(rows_dev), time.time_ns())

    def _close_flight(self) -> None:
        """Training ends: close the last iteration's flight record."""
        if self._flight is not None:
            self._flight.close_open()

    def _record_flight(self, flight, it: int, t0_ns: int,
                       disp0, sc0) -> None:
        """Append one flight-recorder record for the update() that began
        at iteration ``it`` (a K-block covers several iterations; a
        failed step records completed=False with the in-flight
        iteration). The record stays OPEN until the next update begins
        or training ends (``FlightRecorder.close_open``): its interval,
        its host stages and its compile requests cover the callbacks and
        the eval after the update too. Reads ONLY host-side state — phase deltas come from
        the TIMETAG scope table (empty when profiling is off), the
        cumulative coll_bytes/rows counters are the host mirrors TIMETAG
        mode already fetched, and the sentinel column is back-filled by
        the lazy drain (_judge_sentinel) when verdicts land — so the
        record never forces a device sync or an extra dispatch."""
        from .. import distributed
        consumed = self.iter - it
        phases = None
        if sc0 is not None:
            phases = {}
            for name, sc in profiling.scopes().items():
                d = sc["total_s"] - sc0.get(name, {}).get("total_s", 0.0)
                if d > 0:
                    phases[name] = round(d, 6)
        sentinel = "off"
        if self.config.check_numerics:
            sentinel = "pending" if self._sentinel_pending else "ok"
        counters = profiling.counters() if sc0 is not None else {}
        hb = distributed.heartbeat_ages()
        mem = None
        if self._mem_telemetry:
            # memory snapshot per record (allocator query + /proc read —
            # host-side, zero dispatches): fields stay null where the
            # backend has no memory_stats; the same values feed the
            # always-on gauges so health_snapshot()/manifests/metrics
            # see the latest watermark without touching the ring
            mem = profiling.sample_memory()
            for key, val in mem.items():
                if val is not None:
                    profiling.set_gauge(key, float(val))
            # the peak gauge is VmHWM — the kernel's own process-lifetime
            # watermark, exact across spikes BETWEEN iteration samples
            # (a running max of sampled VmRSS would miss them) and the
            # same source memory_snapshot() reports
            rss_peak = profiling.host_rss_peak_bytes()
            if rss_peak is not None:
                profiling.set_gauge("host_rss_peak_bytes",
                                    float(rss_peak))
        flight.record(
            iteration=it, iters=max(consumed, 1),
            completed=consumed > 0,
            wall_s=(time.time_ns() - t0_ns) * 1e-9, phases=phases,
            dispatch=profiling.dispatch_delta(disp0) if disp0 else None,
            sentinel=sentinel, oom_level=self._oom_level,
            coll_bytes=counters.get("hist_coll_bytes"),
            leaves_resolved=counters.get("hist_leaves_resolved"),
            route_splits=counters.get("sparse_route_splits"),
            route_stream_splits=counters.get("sparse_route_stream_splits"),
            cat_splits=counters.get("cat_splits"),
            splits=counters.get("splits"),
            heartbeat_age=(max(hb.values()) if hb else None),
            mem=mem)
        if not flight.has_context:
            # resolved execution context, filled at the first record; the
            # split_fusion flag resolves through the SAME feature-block
            # the grower statics used (fb nonzero — memory-bounded
            # growth — disables the fusion, and a post-mortem claiming
            # the fused path ran would misdirect exactly the
            # memory-pressure debugging it exists for)
            hm = self._hist_method()
            fb = self._feature_block(hm)
            flight.set_context(
                backend=jax.default_backend(), boosting=self.name,
                hist_method=hm,
                split_fusion=bool(self._split_fusion_on(hm, fb)),
                # the rungs the rule kept (serial learner; what the fused
                # step was traced with)
                compaction_ladder=(
                    [] if fb or self._parallel_grower is not None
                    else list(self._compaction_ladder(hm))),
                quantized_grad=bool(getattr(self.config, "quantized_grad",
                                            False)),
                rounds_per_dispatch=int(getattr(
                    self.config, "boost_rounds_per_dispatch", 1)),
                num_leaves=int(self.config.num_leaves),
                tree_learner=self.config.tree_learner,
                # the kernel's blocks: rows a grid step, device columns a
                # body, bodies a launch (hist_plan)
                **{k: v for k, v in self.hist_plan().items()
                   if k in ("hist_block", "feature_block",
                            "feature_blocks")},
                # rows held: an iteration's rows_streamed over it is its
                # passes (telemetry.timeline_report's classes)
                num_data=int(self.train_set.num_data),
                # a ranking objective's bucket plan: documents, padded
                # slots, pair slots, bucket count and shapes
                **getattr(self.objective, "counters", dict)())
            # streaming-construct phase telemetry (sketch/bin/h2d walls,
            # peak resident raw-chunk bytes) rides the header so a
            # post-mortem names how THIS training set was built — read
            # from the dataset's own construct_stats, not the process
            # gauges, so a valid set's (or any later) construct cannot
            # wipe or substitute it; absent when the training set was
            # constructed monolithically
            construct = getattr(self.train_set, "construct_stats", None)
            if construct:
                flight.set_context(construct=dict(construct))

    def _record_aux_counters(self, aux: GrowAux) -> None:
        """Accumulate a tree's histogram-pass row count and collective
        receive volume (device adds, no sync); mirror into the profiling
        counters when TIMETAG is on (the grow_tree scope already synced,
        so the fetch is cheap there)."""
        self._rows_streamed_dev = self._rows_streamed_dev + aux.rows_streamed
        self._coll_bytes_dev = self._coll_bytes_dev + aux.coll_bytes
        self._leaves_resolved_dev = (self._leaves_resolved_dev
                                     + aux.leaves_resolved)
        sync = 0.0 if aux.sync_calls is None else aux.sync_calls
        self._sync_calls_dev = self._sync_calls_dev + sync
        if profiling.enabled():
            for name, v in zip(_AUX_COUNTERS, (aux.rows_streamed,
                                               aux.coll_bytes,
                                               aux.leaves_resolved, sync)):
                profiling.counter(name, float(v))

    def _fetch_tree(self, tree: TreeArrays) -> TreeArrays:
        """A finished tree's host mirror, in ONE batched transfer. Where
        the training set has stream columns the tree's splits are counted
        on the way (TIMETAG mode): ``sparse_route_splits`` in all and
        ``sparse_route_stream_splits`` on a stream column, the splits
        that took ``_apply_split``'s stream branch and its N-row scatter.
        A data set without stream columns counts neither. Where the set
        has categorical columns the tree's splits are counted always, on
        the host mirror (``cat_splits_total`` / ``splits_total``), and
        in TIMETAG mode as the counters ``cat_splits`` and ``splits``:
        the splits that took ``_apply_split``'s bitset branch, and all.
        In TIMETAG mode also, where the set has a device column whose
        histogram carries a NaN bin: ``nan_splits``, the splits on such
        a column, ``nan_left_splits``, those that send NaN left, and
        ``splits`` (once a tree, categorical columns or not); and for
        every set ``tree_leaves`` and ``trees``, the leaves grown and
        the trees fetched."""
        with profiling.span("tree_fetch"):
            t_host = jax.device_get(tree)
        ts = self.train_set
        n = max(int(t_host.num_leaves) - 1, 0)
        feats = t_host.node_feature[:n]
        if profiling.enabled() and getattr(ts, "has_sparse_cols", False):
            profiling.counter("sparse_route_splits", len(feats))
            profiling.counter("sparse_route_stream_splits",
                              int(np.isin(feats, ts.sp_cols).sum()))
        has_cat = getattr(ts, "has_categorical", False)
        if has_cat:
            cats = int(np.asarray(t_host.node_cat[:n]).sum())
            self.cat_splits_total += cats
            self.splits_total += n
            profiling.counter("cat_splits", cats)
        if profiling.enabled():
            nan_cols = getattr(ts, "_nan_bin_cols", None)
            has_nan = nan_cols is not None and bool(nan_cols.any())
            if has_cat or has_nan:
                profiling.counter("splits", n)
            if has_nan:
                on_nan = nan_cols[feats]
                profiling.counter("nan_splits", int(on_nan.sum()))
                profiling.counter("nan_left_splits", int(
                    (on_nan & t_host.node_default_left[:n]).sum()))
            profiling.counter("tree_leaves", int(t_host.num_leaves))
            profiling.counter("trees", 1)
        return t_host

    def _aux_counter_values(self) -> tuple:
        """The cumulative device counters as host floats (a sync), in
        _AUX_COUNTERS' order: what _count_aux_since diffs around a fused
        dispatch."""
        return (float(self._rows_streamed_dev), float(self._coll_bytes_dev),
                float(self._leaves_resolved_dev),
                float(self._sync_calls_dev))

    def _count_aux_since(self, prev: tuple) -> None:
        """Mirror a fused dispatch's share of the cumulative device
        counters into the profiling counters (TIMETAG mode)."""
        for name, now, was in zip(_AUX_COUNTERS, self._aux_counter_values(),
                                  prev):
            profiling.counter(name, now - was)

    @property
    def rows_streamed_total(self) -> float:
        """Rows read by histogram passes across all trees so far — the
        compaction telemetry the benchmark reports per tree.
        Reading this syncs the device accumulator."""
        return float(self._rows_streamed_dev)

    @property
    def rows_streamed_per_tree(self) -> float:
        return self.rows_streamed_total / max(len(self.trees), 1)

    @property
    def coll_bytes_total(self) -> float:
        """Histogram-plane collective bytes received per device across all
        trees so far (see GrowAux.coll_bytes; 0 for the serial and
        feature learners). Reading this syncs the device accumulator."""
        return float(self._coll_bytes_dev)

    @property
    def split_sync_calls_total(self) -> float:
        """Best-split syncs (one collective round over all leaves' bests,
        or the voting learner's vote tally) across all trees so far; 0
        for the serial learner. Reading this syncs the device
        accumulator."""
        return float(self._sync_calls_dev)

    @property
    def coll_bytes_per_iter(self) -> float:
        return self.coll_bytes_total / max(self.iter, 1)

    def _finalize_tree(self, tree: TreeArrays, leaf_id: jax.Array,
                       class_idx: int) -> Tuple[TreeArrays, TreeArrays, bool]:
        """RenewTreeOutput + Shrinkage (gbdt.cpp:411-433). Returns the device
        tree, a host (numpy) mirror fetched in ONE batched transfer (per-array
        fetches would each stall the host on the device), and whether the
        tree has any split."""
        cfg = self.config
        t_host = self._fetch_tree(tree)
        num_leaves = int(t_host.num_leaves)
        had_split = num_leaves > 1
        if (had_split and self.objective is not None
                and self.objective.need_renew_tree_output):
            score = self._renew_score(class_idx)
            new_values = self.objective.renew_tree_output(
                np.asarray(leaf_id), score, num_leaves)
            if new_values is not None:
                lv = np.asarray(t_host.leaf_value).copy()
                lv[:num_leaves] = new_values
                t_host = t_host._replace(leaf_value=lv)
                tree = tree._replace(leaf_value=jnp.asarray(lv))
        lr = self.shrinkage_rate
        tree = _shrink_tree(tree, lr)
        t_host = _shrink_tree(t_host, lr)
        if cfg.check_numerics:
            self._check_numerics_leaves(t_host, num_leaves)
        return tree, t_host, had_split

    def _renew_score(self, class_idx: int) -> np.ndarray:
        """Score array used for objective leaf renewal (RF overrides with the
        constant init score, rf.hpp:133-136)."""
        return np.asarray(self.train_score if self.num_tree_per_iteration == 1
                          else self.train_score[:, class_idx], dtype=np.float64)

    def _bias_after_score(self, class_idx: int, had_split: bool) -> None:
        """Fold the boost-from-average init score into the just-stored tree
        AFTER the score update so scores are not double counted
        (reference: gbdt.cpp:404-435 — AddBias after UpdateScore for split
        trees; AsConstantTree(init) for a splitless first tree). RF overrides
        (it folds its bias per-tree in _finalize_tree, rf.hpp:135-137)."""
        first = len(self.trees) <= self.num_tree_per_iteration
        bias = self.init_scores[class_idx] if (first and self._fold_init_bias) else 0.0
        if abs(bias) <= 1e-15:
            self.tree_bias.append(0.0)
            return
        tree = self.trees[-1]
        if had_split:
            tree = tree._replace(leaf_value=tree.leaf_value + bias,
                                 node_value=tree.node_value + bias)
        else:
            tree = tree._replace(leaf_value=tree.leaf_value.at[0].set(bias))
        self.trees[-1] = tree
        old_ht = self.host_trees[-1]
        new_ht = self._make_host_tree(tree)
        if getattr(old_ht, "is_linear", False):
            # AddBias reaches leaf_const too for linear trees (tree.h:212-231)
            new_ht.is_linear = True
            new_ht.leaf_const = old_ht.leaf_const + bias
            new_ht.leaf_coeff = old_ht.leaf_coeff
            new_ht.leaf_features_raw = old_ht.leaf_features_raw
        self.host_trees[-1] = new_ht
        self._mt_cache.pop(len(self.host_trees) - 1, None)
        self._contrib_tree_cache = None      # in-place replacement
        self.tree_bias.append(bias)
        self._stacked_cache = None

    def _add_tree(self, tree: TreeArrays, leaf_id: jax.Array, class_idx: int,
                  linear: Optional[dict] = None,
                  t_host: Optional[TreeArrays] = None,
                  lazy: bool = False,
                  score_updated: bool = False) -> None:
        """Score updates for train (via leaf ids — no traversal needed) and
        valid sets (tree traversal on their binned matrices). ``linear``
        carries a fitted linear-leaf model: per-row train deltas plus the
        const/coeff tables (reference: Tree::AddPredictionToScore linear
        branch, tree.h). ``t_host`` is the already-fetched numpy mirror;
        with ``lazy`` the mirror is deferred (async copy, see host_trees);
        ``score_updated`` means the train-score update already happened
        inside the fused one-dispatch program (leaf_id may then be None)."""
        from .tree import leaf_values_of_rows
        lr = self.shrinkage_rate
        if not score_updated:
            if linear is not None:
                delta = jnp.asarray(linear["train_delta"] * lr)
            else:
                delta = leaf_values_of_rows(tree.leaf_value, leaf_id)
            if self.num_tree_per_iteration > 1:
                self.train_score = self.train_score.at[:, class_idx].add(
                    delta)
            else:
                self.train_score = self.train_score + delta
        self.trees.append(tree)
        rows_dev = self._rows_streamed_dev
        if lazy:
            for leaf in jax.tree_util.tree_leaves((tree, rows_dev)):
                try:
                    leaf.copy_to_host_async()
                except AttributeError:
                    pass
            self._host_trees.append(None)
            self._pending_host.append((len(self._host_trees) - 1, tree,
                                       self.iter, rows_dev))
        else:
            self._append_host_tree(t_host if t_host is not None else tree)
            if t_host is not None:
                # fetched already, and the counter is the same step's
                self._note_ready(self.iter, rows_dev)
        if linear is not None:
            ht = self.host_trees[-1]
            ht.is_linear = True
            ht.leaf_const = linear["const"] * lr
            ht.leaf_coeff = [[c * lr for c in cs] for cs in linear["coeff"]]
            ht.leaf_features_raw = linear["features"]
        lin_tables = None
        mt = None
        if linear is not None and self.valid_sets:
            ht = self.host_trees[-1]
            if all(getattr(vs, "raw_data_np", None) is not None
                   for vs in self.valid_sets):
                # device tables for linear-leaf valid scoring: dense
                # [L, F_total] coefficient matrix + used-feature mask so
                # per-iteration valid deltas stay on device (no host tree
                # walk per valid set per tree)
                # tables padded to the CONFIG leaf budget so the jitted
                # delta kernel compiles once, not per distinct tree size
                L = self.config.num_leaves
                nl = len(ht.leaf_value)
                ftot = self.train_set.num_total_features
                W = np.zeros((L, ftot), np.float32)
                used = np.zeros((L, ftot), np.float32)
                for li, (feats, coefs) in enumerate(
                        zip(ht.leaf_features_raw, ht.leaf_coeff)):
                    for fj, cj in zip(feats, coefs):
                        W[li, int(fj)] = np.float32(cj)
                        used[li, int(fj)] = 1.0
                lv = np.zeros((L,), np.float32)
                lv[:nl] = np.asarray(ht.leaf_value, np.float32)
                lc = np.zeros((L,), np.float32)
                lc[:nl] = np.asarray(ht.leaf_const, np.float32)
                lin_tables = (jnp.asarray(lv), jnp.asarray(lc),
                              jnp.asarray(W), jnp.asarray(used))
            else:
                from ..io.model_text import ModelTree
                mt = ModelTree.from_host(ht, self.train_set.mappers)
        for i, vs in enumerate(self.valid_sets):
            if lin_tables is not None:
                raw_dev = self._valid_raw_cache.get(i)
                if raw_dev is None:
                    raw_dev = jnp.asarray(
                        vs.raw_data_np.astype(np.float32, copy=False))
                    self._valid_raw_cache[i] = raw_dev
                leaf = predict_leaf_bins(tree, vs.bins, vs.missing_bin)
                vdelta = _linear_valid_delta(leaf, *lin_tables, raw_dev)
            elif mt is not None:
                vdelta = jnp.asarray(mt.predict(vs.raw_data_np).astype(np.float32))
            else:
                # inference-engine leg of training-time eval: traversal +
                # donated in-place add as ONE compiled program per valid
                # set (bit-identical to the eager per-op path it replaced)
                args = (self._valid_scores[i], tree, vs.bins,
                        vs.missing_bin, np.int32(class_idx))
                statics = dict(depth=self._traversal_depth(),
                               kk=self.num_tree_per_iteration)
                self._register_valid_program(i, args, statics)
                self._valid_scores[i] = _apply_valid_tree(*args, **statics)
                continue
            if self.num_tree_per_iteration > 1:
                self._valid_scores[i] = self._valid_scores[i].at[:, class_idx].add(vdelta)
            else:
                self._valid_scores[i] = self._valid_scores[i] + vdelta
        self._stacked_cache = None

    def _fit_linear_leaves(self, tree: TreeArrays, leaf_id: jax.Array,
                           grad: jax.Array, hess: jax.Array, mask: jax.Array,
                           first_tree: bool) -> dict:
        """Fit a linear model per leaf on the raw branch features
        (reference: linear_tree_learner.cpp:173-380 CalculateLinear —
        coefficients = -(X^T H X + lambda)^{-1} X^T g per Eq 3 of
        arXiv:1802.05640, with NaN rows excluded and near-zero coefficients
        dropped). Returns pre-shrinkage const/coeff tables and per-row
        train deltas."""
        ts = self.train_set
        raw = ts.raw_data_np
        ht = self._make_host_tree(tree)
        L = ht.num_leaves
        leaf_np = np.asarray(leaf_id)
        g = np.asarray(grad, np.float64)
        h = np.asarray(hess, np.float64)
        m = np.asarray(mask) > 0
        lam = self.config.linear_lambda
        from ..binning import BIN_TYPE_NUMERICAL, K_ZERO_THRESHOLD

        # branch features per leaf (sorted unique numerical ORIGINAL indices,
        # linear_tree_learner.cpp:195-225)
        leaf_feats: List[List[int]] = [[] for _ in range(L)]
        if L > 1:
            stack = [(0, [])]
            while stack:
                node, path = stack.pop()
                inner = int(ht.split_feature[node])
                orig = int(ht.feature_indices[inner])
                is_num = (ts.mappers[orig].bin_type == BIN_TYPE_NUMERICAL)
                npath = path + ([orig] if is_num else [])
                for child in (int(ht.left_child[node]), int(ht.right_child[node])):
                    if child >= 0:
                        stack.append((child, npath))
                    else:
                        leaf_feats[~child] = sorted(set(npath))

        leaf_value = np.asarray(ht.leaf_value[:L], np.float64)
        consts = leaf_value.copy()
        coeffs: List[List[float]] = [[] for _ in range(L)]
        features: List[List[int]] = [[] for _ in range(L)]
        train_delta = leaf_value[leaf_np]

        if not first_tree:
            for leaf in range(L):
                feats = leaf_feats[leaf]
                if not feats:
                    continue
                rows = (leaf_np == leaf) & m
                Xl = raw[rows][:, feats].astype(np.float64)
                okr = ~np.isnan(Xl).any(axis=1) & ~np.isinf(Xl).any(axis=1)
                if okr.sum() < len(feats) + 1:
                    continue    # keep the plain leaf output as const
                Xl = Xl[okr]
                gl = g[rows][okr]
                hl = h[rows][okr]
                X1 = np.concatenate([Xl, np.ones((len(Xl), 1))], axis=1)
                A = X1.T @ (X1 * hl[:, None])
                A[np.arange(len(feats)), np.arange(len(feats))] += lam
                b = X1.T @ gl
                try:
                    sol = -np.linalg.solve(A, b)
                except np.linalg.LinAlgError:
                    sol = -(np.linalg.pinv(A) @ b)
                keep = [i for i in range(len(feats))
                        if abs(sol[i]) > K_ZERO_THRESHOLD]
                features[leaf] = [feats[i] for i in keep]
                coeffs[leaf] = [float(sol[i]) for i in keep]
                consts[leaf] = float(sol[-1])
                # per-row deltas for rows of this leaf (NaN rows keep the
                # plain leaf output, linear_tree_learner.cpp:19-41 semantics)
                all_rows = leaf_np == leaf
                Xa = raw[all_rows][:, features[leaf]].astype(np.float64) \
                    if features[leaf] else np.zeros((int(all_rows.sum()), 0))
                bad = (np.isnan(Xa).any(axis=1) | np.isinf(Xa).any(axis=1)) \
                    if features[leaf] else np.zeros(int(all_rows.sum()), bool)
                pred = consts[leaf] + (Xa @ np.asarray(coeffs[leaf])
                                       if features[leaf] else 0.0)
                train_delta[all_rows] = np.where(bad, leaf_value[leaf], pred)

        return {"const": consts, "coeff": coeffs, "features": features,
                "train_delta": train_delta.astype(np.float32)}

    def _make_host_tree(self, tree: TreeArrays) -> HostTree:
        ds = self.train_set
        num_leaves = int(tree.num_leaves)
        n_nodes = max(num_leaves - 1, 0)
        feats = np.asarray(tree.node_feature[:n_nodes])
        bins_thr = np.asarray(tree.node_threshold_bin[:n_nodes])
        real_thr = np.zeros(n_nodes, dtype=np.float64)
        missing = np.zeros(n_nodes, dtype=np.int8)
        if ds.bundles is not None:
            # bundle columns: map (column, bundle bin) back to the owning
            # ORIGINAL feature + its bin; the host/model tree is bundle-free
            # (saved models reference original features, like the reference's)
            seg_lo = np.asarray(tree.node_seg_lo[:n_nodes])
            dleft = np.asarray(tree.node_default_left[:n_nodes])
            orig_feats = np.zeros(n_nodes, dtype=np.int32)
            for i in range(n_nodes):
                g, t = int(feats[i]), int(bins_thr[i])
                orig = int(ds._owner_orig[g, t])
                orig_feats[i] = orig
                mapper = ds.mappers[orig]
                missing[i] = mapper.missing_type
                if seg_lo[i] >= 0:      # bundle split: map back to the
                    # member's own bin space (direction-dependent)
                    thr_tab = ds._thr_rev if dleft[i] else ds._thr_fwd
                    real_thr[i] = mapper.bin_to_value(int(thr_tab[g, t]))
                else:
                    real_thr[i] = mapper.bin_to_value(t)
            full_thr = np.zeros(tree.node_threshold_bin.shape[0],
                                dtype=np.float64)
            full_thr[:n_nodes] = real_thr
            ht = HostTree(tree, full_thr,
                          np.arange(ds.num_total_features, dtype=np.int32),
                          missing)
            ht.split_feature = orig_feats
            return ht
        used = ds.used_features
        for i in range(n_nodes):
            mapper = ds.mappers[used[feats[i]]]
            real_thr[i] = mapper.bin_to_value(int(bins_thr[i]))
            missing[i] = mapper.missing_type
        full_thr = np.zeros(tree.node_threshold_bin.shape[0], dtype=np.float64)
        full_thr[:n_nodes] = real_thr
        return HostTree(tree, full_thr, used, missing)

    def _append_host_tree(self, tree: TreeArrays) -> None:
        self.host_trees.append(self._make_host_tree(tree))

    def rollback_one_iter(self) -> None:
        """reference: gbdt.cpp:454-470 RollbackOneIter."""
        if self.iter <= 0:
            return
        self._flush_sentinel()
        self._flush_pending()
        # the popped iteration must not leave a stale stop signal behind
        self._lagged_stop = False
        self._splitless_group = -1
        self._splitless_in_group = 0
        if getattr(self, "_pre_part", False):
            # the rollback delta re-traverses the train bins, which are
            # globally sharded here; per-shard traversal is not wired up
            log.fatal("rollback_one_iter is not supported with "
                      "pre-partitioned Datasets")
        if getattr(self.train_set, "has_sparse_cols", False):
            # same reason: the traversal needs the full-width bin matrix,
            # which sparse storage no longer materializes
            log.fatal("rollback_one_iter is not supported with sparse "
                      "device storage (construct with enable_sparse=false)")
        k = self.num_tree_per_iteration
        # tree count returns to a previously-seen value after retraining,
        # so the count-keyed contrib cache would serve the popped trees
        self._contrib_tree_cache = None
        for c in range(k):
            tree = self.trees.pop()
            self.host_trees.pop()
            self._mt_cache.pop(len(self.host_trees), None)
            bias = self.tree_bias.pop() if self.tree_bias else 0.0
            class_idx = k - 1 - c
            # recompute train deltas via traversal (leaf ids not stored);
            # subtract only the pre-bias contribution (the init-score bias was
            # folded AFTER the score update, see _bias_after_score)
            delta = predict_value_bins(tree, self.train_set.bins,
                                       self.train_set.missing_bin) - bias
            if k > 1:
                self.train_score = self.train_score.at[:, class_idx].add(-delta)
            else:
                self.train_score = self.train_score - delta
            for i, vs in enumerate(self.valid_sets):
                vdelta = predict_value_bins(tree, vs.bins, vs.missing_bin) - bias
                if k > 1:
                    self._valid_scores[i] = self._valid_scores[i].at[:, class_idx].add(-vdelta)
                else:
                    self._valid_scores[i] = self._valid_scores[i] - vdelta
        self.iter -= 1
        self._stacked_cache = None

    # ------------------------------------------------- checkpoint/resume
    def get_trainer_state(self) -> dict:
        """Complete trainer state for checkpointing (see
        lightgbm_tpu/checkpoint.py): everything a resume needs to continue
        BIT-IDENTICALLY — the exact float32 score caches, device tree
        arrays, host mirrors and the stateful RNGs. Device-PRNG draws
        (bagging, GOSS, extra_trees) are fold_in(seed, iter) and need no
        state; the numpy RNGs (feature fraction; DART's drop RNG in the
        subclass) are stateful and serialize their full state."""
        self._flush_sentinel()
        self._flush_pending()
        state = {
            "name": self.name,
            "iter": int(self.iter),
            "trees": jax.device_get(self.trees),
            "host_trees": list(self._host_trees),
            "tree_bias": list(self.tree_bias),
            "init_scores": list(self.init_scores),
            "train_score": (np.asarray(self.train_score)
                            if self.train_score is not None else None),
            "valid_scores": [np.asarray(s) for s in self._valid_scores],
            "feat_rng_state": self._feat_rng.get_state(),
            "splitless_group": self._splitless_group,
            "splitless_in_group": self._splitless_in_group,
            "lagged_stop": self._lagged_stop,
            "rows_streamed": float(self._rows_streamed_dev),
            "coll_bytes": float(self._coll_bytes_dev),
            "leaves_resolved": float(self._leaves_resolved_dev),
            "sync_calls": float(self._sync_calls_dev),
            "best_score": dict(self.best_score),
            # the OOM degradation ladder's position: a resumed incarnation
            # must train with the SAME degraded configuration (block size /
            # histogram method change the accumulation shape — numerics)
            # or the bit-identical-restart contract breaks
            "oom_degrade": ({"level": self._oom_level,
                             "block": self._oom_block,
                             "hm": self._oom_hm,
                             "predict_chunk": self._oom_predict_chunk}
                            if (self._oom_level
                                or self._oom_predict_chunk) else None),
            "cegb_aux": (jax.device_get(self._cegb_aux)
                         if self._cegb_aux is not None else None),
            "loaded_iters": self.loaded_iters,
            "loaded_model_text": None,
        }
        if self.loaded is not None:
            from ..io.model_text import dump_model_text
            state["loaded_model_text"] = dump_model_text(self.loaded)
        return state

    def set_trainer_state(self, state: dict) -> None:
        """Inverse of :meth:`get_trainer_state`, applied to a freshly
        constructed booster over the same dataset/params."""
        if state.get("name") != self.name:
            log.fatal(f"checkpoint was written by "
                      f"boosting={state.get('name')!r}; this booster is "
                      f"boosting={self.name!r}")
        if len(state["valid_scores"]) != len(self._valid_scores):
            log.fatal(f"checkpoint was written with "
                      f"{len(state['valid_scores'])} validation sets; this "
                      f"run has {len(self._valid_scores)} — pass the same "
                      f"valid_sets in the same order")
        self.iter = int(state["iter"])
        self.trees = [jax.tree.map(jnp.asarray, t) for t in state["trees"]]
        self._host_trees = list(state["host_trees"])
        self._pending_host = []
        self.tree_bias = list(state["tree_bias"])
        self.init_scores = list(state["init_scores"])
        if state["train_score"] is not None:
            self.train_score = jnp.asarray(state["train_score"])
        self._valid_scores = [jnp.asarray(s) for s in state["valid_scores"]]
        self._feat_rng.set_state(state["feat_rng_state"])
        self._splitless_group = state["splitless_group"]
        self._splitless_in_group = state["splitless_in_group"]
        self._lagged_stop = state["lagged_stop"]
        self._rows_streamed_dev = jnp.float32(state["rows_streamed"])
        self._coll_bytes_dev = jnp.float32(state.get("coll_bytes", 0.0))
        self._leaves_resolved_dev = jnp.float32(
            state.get("leaves_resolved", 0.0))
        self._sync_calls_dev = jnp.float32(state.get("sync_calls", 0.0))
        self.best_score = dict(state["best_score"])
        od = state.get("oom_degrade")
        if od:
            self._oom_level = int(od.get("level", 0))
            self._oom_block = int(od.get("block", 0))
            self._oom_hm = od.get("hm")
            self._oom_predict_chunk = int(od.get("predict_chunk", 0))
        if state.get("cegb_aux") is not None:
            self._cegb_aux = jax.tree.map(jnp.asarray, state["cegb_aux"])
            # a checkpoint from before a defaulted field existed pickled
            # no array for it; materialize the zero so the fused step's
            # operand structure stays trace-stable
            for field in ("sentinel", "leaves_resolved"):
                if getattr(self._cegb_aux, field, None) is None:
                    self._cegb_aux = self._cegb_aux._replace(
                        **{field: jnp.float32(0.0)})
        if state.get("loaded_model_text"):
            from ..io.model_text import load_model
            self.loaded = load_model(state["loaded_model_text"], self.config)
            self.loaded_iters = int(state["loaded_iters"])
        self._stacked_cache = None
        self._engine_cache.clear()
        self._mt_cache.clear()
        self._contrib_tree_cache = None
        self._bag_frac = None
        self._restore_bagging()

    def _restore_bagging(self) -> None:
        """Recreate the bagging mask/subset active at the restored
        iteration: the draw is keyed on the period-start iteration (see
        _update_bagging), so marking the host state stale makes the next
        iteration re-derive the exact mid-period mask — no RNG state to
        persist."""
        self._bag_stale = True

    # ------------------------------------------------------------- eval
    def eval_set(self, feval=None) -> List[Tuple[str, str, float, bool]]:
        """Evaluate all metrics on train (if configured) and valid sets.
        Returns (dataset_name, metric_name, value, bigger_is_better) tuples
        (analog of GBDT::OutputMetric, gbdt.cpp:517-575)."""
        out = []
        sets = []
        if self.config.is_provide_training_metric:
            sets.append(("training", self.train_set, self.train_score))
        for name, vs, score in zip(self.valid_names, self.valid_sets, self._valid_scores):
            sets.append((name, vs, score))
        for ds_name, ds, score in sets:
            score_np = np.asarray(score, dtype=np.float64)
            out.extend(self.eval_metrics(score_np, ds, ds_name, feval,
                                         cache=True))
        return out

    def eval_metrics(self, score_np, ds, ds_name, feval=None,
                     cache: bool = False):
        """Run every configured metric (+ optional feval) over raw scores
        for one dataset — the single metric-reporting loop eval_set and
        Booster.eval share. ``cache`` keeps the initialized Metric objects
        keyed by dataset identity (safe for the booster's own long-lived
        train/valid sets; arbitrary eval datasets skip it)."""
        out = []
        for name in self.metric_names:
            key = (name, id(ds))
            mm = self._metric_cache.get(key) if cache else None
            if mm is None:
                mm = create_metric(name, self.config)
                if mm is None:
                    continue
                mm.init(ds.get_label(), ds.get_weight(), ds.get_group())
                if cache:
                    self._metric_cache[key] = mm
            val = mm.eval(score_np, self.objective)
            if isinstance(val, (list, tuple)):
                # multi-position metrics (ndcg@k / map@k) report one
                # entry per position (reference: rank_metric.hpp name_)
                names = mm.name if isinstance(mm.name, (list, tuple)) \
                    else [mm.name] * len(val)
                for nm2, v2 in zip(names, val):
                    out.append((ds_name, nm2, float(v2),
                                mm.bigger_is_better))
            else:
                out.append((ds_name, mm.name, val, mm.bigger_is_better))
        if feval is not None:
            out.extend(_call_feval(feval, score_np, ds, self.objective,
                                   ds_name))
        return out

    # ---------------------------------------------------------- predict
    def _prep_predict_X(self, X) -> np.ndarray:
        """Predict-time feature matrix: pandas category columns are mapped
        through the train-time category lists BEFORE any array conversion
        (np.asarray on a category dtype would yield raw values, not codes).
        scipy sparse inputs pass through unchanged (binned column-wise
        without densifying).

        Input hardening: a wrong feature count, a non-numeric column, or a
        non-finite value the trained bin mappers cannot route (NaN in a
        feature trained without missing values; ±Inf in a feature whose
        value range never saw it) raises a ValueError NAMING the offending
        column/row — silently binning such values routes rows through
        arbitrary thresholds and serves garbage scores. NaN in features
        trained WITH missing handling (and in categorical features, whose
        unseen values go to the other-bin by design) stays valid.
        ``predict_disable_shape_check`` opts out of all of it (the
        reference's escape hatch for intentionally truncated inputs)."""
        from ..basic import _is_scipy_sparse, _to_2d_float
        validate = not self.config.predict_disable_shape_check
        if _is_scipy_sparse(X):
            if validate:
                self._validate_predict_matrix(X, sparse=True)
            return X
        raw = X
        X = self.train_set._pandas_to_codes(X)
        try:
            X = _to_2d_float(X)
        except (ValueError, TypeError) as e:
            self._raise_bad_dtype(raw, e)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        if validate:
            self._validate_predict_matrix(X, sparse=False)
        return X

    def _raise_bad_dtype(self, raw, cause) -> None:
        """Name the first non-numeric column of a failed conversion."""
        cols = None
        if hasattr(raw, "dtypes"):          # pandas: dtypes are explicit
            for ci, dt in enumerate(raw.dtypes):
                if dt == object or str(dt).startswith(("datetime", "str")):
                    cols = ci
                    break
        elif getattr(raw, "ndim", 0) == 2:
            for ci in range(raw.shape[1]):
                try:
                    np.asarray(raw[:, ci], dtype=np.float64)
                except (ValueError, TypeError):
                    cols = ci
                    break
        where = f"feature column {cols}" if cols is not None \
            else "the input"
        raise ValueError(
            f"predict input has non-numeric data in {where}: {cause}. "
            f"Convert categoricals to codes (or pandas category dtype) "
            f"before predicting.") from cause

    def _validate_predict_matrix(self, X, sparse: bool) -> None:
        """Shape + finiteness validation against the trained mappers."""
        expected = self.train_set.num_total_features
        if X.shape[1] != expected:
            raise ValueError(
                f"predict input has {X.shape[1]} feature columns but the "
                f"model was trained with {expected} (set "
                f"predict_disable_shape_check=true to bypass)")
        mappers = self.train_set.mappers
        if sparse:
            # csr/csc/coo expose a flat numeric .data — check it in place;
            # lil/dok hold object arrays of row lists that isfinite cannot
            # take, so those canonicalize through coo (one copy)
            data = getattr(X, "data", None)
            flat = (data is not None and hasattr(data, "dtype")
                    and data.dtype.kind in "fiu")
            if flat and (data.size == 0 or bool(np.isfinite(data).all())):
                return
            coo = X.tocoo()
            vals = np.asarray(coo.data, dtype=np.float64) \
                if coo.nnz else np.zeros(0)
            bad = ~np.isfinite(vals)          # walk only the offenders
            for r, c, v in zip(coo.row[bad], coo.col[bad], vals[bad]):
                self._check_nonfinite(float(v), int(r), int(c), mappers)
            return
        # fast path: one reduction — any NaN/Inf poisons the f64 sum (an
        # inf pair cancels to NaN, which still fails isfinite); only on
        # failure walk columns. A sum of large FINITE values can overflow
        # to inf — the column scan then finds nothing and the input
        # passes. Per-column work stays vectorized: legitimate
        # missing-heavy inputs (NaN routed to missing bins) cost one
        # isfinite pass, not a Python loop over every NaN.
        with np.errstate(over="ignore", invalid="ignore"):
            total = float(np.sum(X, dtype=np.float64))
        if np.isfinite(total):
            return
        for c in range(X.shape[1]):
            col = X[:, c]
            if np.isfinite(col).all():
                continue
            # one representative per kind (NaN / +inf / -inf route
            # differently) — each either raises or is valid for ALL
            # entries of that kind in this column
            nan_rows = np.flatnonzero(np.isnan(col))
            if nan_rows.size:
                self._check_nonfinite(np.nan, int(nan_rows[0]), c, mappers)
            for sign in (np.inf, -np.inf):
                rows = np.flatnonzero(col == sign)
                if rows.size:
                    self._check_nonfinite(sign, int(rows[0]), c, mappers)

    def _check_nonfinite(self, v: float, row: int, col: int,
                         mappers) -> None:
        """Raise unless the trained mapper can route this non-finite
        value (NaN -> missing bin / categorical other-bin / linear-leaf
        fallback; Inf -> only if the training data contained it)."""
        from .. import binning
        m = mappers[col] if mappers and col < len(mappers) else None
        if m is None:
            return
        if m.bin_type == binning.BIN_TYPE_CATEGORICAL:
            return        # unseen/NaN categoricals route to the other-bin
        if np.isnan(v):
            if self.config.linear_tree:
                # linear trees define NaN prediction: any NaN feature
                # falls back to the leaf's constant output (reference:
                # LeafOutputWithLinearModel's isnan check)
                return
            if m.missing_type == binning.MISSING_NONE and not m.is_trivial:
                raise ValueError(
                    f"predict input has NaN at row {row}, feature column "
                    f"{col}, but the model was trained without missing "
                    f"values in that feature — there is no bin to route "
                    f"it to (set predict_disable_shape_check=true to "
                    f"bin it arbitrarily)")
            return
        # +/-inf: valid only if the training data actually contained it;
        # trivial (constant, unused-by-every-tree) features route nowhere
        # and stay exempt like the NaN branch
        if m.is_trivial:
            return
        seen = m.max_val if v > 0 else m.min_val
        if not np.isinf(seen):
            raise ValueError(
                f"predict input has {v:+g} at row {row}, feature column "
                f"{col}; the training data for that feature was bounded "
                f"([{m.min_val:g}, {m.max_val:g}]) — an infinite value "
                f"would bin to an arbitrary edge bin (set "
                f"predict_disable_shape_check=true to allow)")

    def _stacked(self, num_iteration: Optional[int] = None) -> Optional[TreeArrays]:
        total_iters = len(self.trees) // self.num_tree_per_iteration
        use_iters = total_iters if num_iteration is None or num_iteration <= 0 \
            else min(num_iteration, total_iters)
        n_trees = use_iters * self.num_tree_per_iteration
        if n_trees == 0:
            return None
        if self._stacked_cache is not None and self._stacked_cache[0] == n_trees:
            return self._stacked_cache[1]
        stacked = stack_trees(self.trees[:n_trees])
        self._stacked_cache = (n_trees, stacked)
        return stacked

    # ------------------------------------------------- inference engine
    def _traversal_depth(self) -> int:
        """STATIC trip-count bound for depth-bounded traversal DURING
        training (no host sync to measure the freshly grown tree): a
        leaf's depth is bounded by max_depth when set, and by
        num_leaves - 1 always."""
        cfg = self.config
        if cfg.max_depth and cfg.max_depth > 0:
            return min(cfg.max_depth, cfg.num_leaves - 1)
        return cfg.num_leaves - 1

    def _ensemble_depth(self, n_trees: int) -> int:
        """True max leaf depth over the first n_trees host mirrors — the
        engine's static fori_loop trip count, measured ONCE at engine
        build (not per predict)."""
        from .predict_engine import host_tree_depth
        d = 0
        for ht in self.host_trees[:n_trees]:
            d = max(d, host_tree_depth(ht.left_child, ht.right_child,
                                       ht.num_leaves))
        return d

    def _predict_engine(self, num_iteration: Optional[int] = None):
        """Cached device inference engine over the stacked ensemble (see
        models/predict_engine.py): depth-bounded traversal + on-device
        f64 accumulation + shape-bucketed compile cache + chunked /
        sharded serving. Invalidated by identity against the stacked
        cache, so anything that refreshes the stack (new trees, shuffle,
        rollback, checkpoint restore) rebuilds the engine."""
        from .predict_engine import PredictEngine
        with self._engine_lock:
            stacked = self._stacked(num_iteration)
            if stacked is None:
                return None
            nt = int(stacked.leaf_value.shape[0])
            hit = self._engine_cache.get(nt)
            if hit is not None and hit[0] is stacked:
                return hit[1]
            cfg = self.config
            biases = None
            if len(self.tree_bias) >= nt:
                b = np.asarray(self.tree_bias[:nt], np.float64)
                if b.size and np.any(b):
                    biases = b
            chunk = cfg.predict_chunk_rows
            if self._oom_predict_chunk:
                # OOM ladder rung 3: bound the serving program's resident
                # rows
                chunk = self._oom_predict_chunk if not chunk \
                    else min(chunk, self._oom_predict_chunk)
            eng = PredictEngine(
                stacked, self.num_tree_per_iteration, nt,
                self._ensemble_depth(nt), biases=biases,
                accum=cfg.predict_accum,
                bucket_min_rows=cfg.predict_bucket_min_rows,
                chunk_rows=chunk,
                sharded=cfg.predict_sharded)
            eng.serve_mode = self._serve_mode
            if len(self._engine_cache) >= 2:
                self._engine_cache.pop(next(iter(self._engine_cache)))
            self._engine_cache[nt] = (stacked, eng)
            return eng

    def _convert_output_jit(self):
        """The objective's output conversion as ONE jitted program (the
        eager convert_output is an op-by-op dispatch chain). Input is
        cast to the dtype the legacy host path fed it (f32 unless x64 is
        on globally), so converted outputs keep their historical bits."""
        obj = self.objective
        x64 = bool(jax.config.jax_enable_x64)
        if getattr(self, "_convert_jit_key", None) == (id(obj), x64):
            return self._convert_jit
        dt = jnp.float64 if x64 else jnp.float32
        self._convert_jit = jax.jit(lambda r: obj.convert_output(
            r.astype(dt)))
        # keyed on the objective AND the x64 flag: a flag flip must not
        # serve a stale f32-casting program (obj retained via the closure)
        self._convert_jit_key = (id(obj), x64)
        return self._convert_jit

    def score_dataset(self, ds) -> np.ndarray:
        """Raw scores for a train-aligned Dataset via traversal of its
        BINNED matrix (the mechanism Booster.eval uses for a dataset whose
        raw features were freed — the reference scores added valid sets
        through the same binned representation, score_updater.hpp)."""
        ds.construct()
        ts = self.train_set
        if ts is not None and ds is not ts and ds.reference is not ts \
                and ds.mappers is not ts.mappers:
            # tree thresholds are TRAIN-bin indices; traversing a matrix
            # binned with different mappers silently computes wrong scores
            # (the reference rejects misaligned valid data the same way)
            log.fatal("eval dataset was not binned against the training "
                      "set; construct it with reference=<train Dataset>")
        k = self.num_tree_per_iteration
        n = ds.num_data
        if self.loaded_iters > 0 or self.config.linear_tree:
            # loaded host trees / linear leaves need raw features
            raw = getattr(ds, "raw_data_np", None)
            if raw is None and ds.data is not None:
                from ..basic import _is_scipy_sparse, _to_2d_float
                raw = ds.data if _is_scipy_sparse(ds.data) else \
                    _to_2d_float(ds._pandas_to_codes(ds.data))
            if raw is None:
                log.fatal("eval with a loaded init_model or linear trees "
                          "needs raw features (construct the Dataset with "
                          "free_raw_data=False)")
            return self.predict_raw(raw)
        out = np.broadcast_to(
            np.asarray(self.init_scores, np.float64), (n, k)).copy()
        init = ds.init_score
        if init is not None:
            out = np.asarray(init, np.float64).reshape(n, k).copy()
        stacked = self._stacked()
        if stacked is not None:
            # device-resident engine: traversal + per-tree bias subtraction
            # + f64 accumulation IN TREE ORDER all on device — only the
            # [n, K] result crosses to the host (the [T, n] per-tree value
            # matrix never does), bit-identical to the former host loop
            eng = self._predict_engine()
            base = out if k > 1 else out[:, 0]
            return eng.predict(self._traversal_bins(ds), ds.missing_bin,
                               base=base)
        return out if k > 1 else out[:, 0]

    def _traversal_bins(self, ds) -> jax.Array:
        """Full-width bin matrix for tree traversal. Tree feature ids are
        LOGICAL device-column positions, but a sparse-stored Dataset's
        ``bins`` holds only the dense columns — traversing it directly
        silently scores the wrong columns (ADVICE r5 high: binary_logloss
        0.85 vs the true 0.28). Reconstruct the sparse columns from their
        (row, bin) streams + default bin, the whole-column materialization
        of SparseBin::Split's stream walk (sparse_bin.hpp). Costs the O(N)
        dense matrix sparse storage elided — the price of eval-on-train;
        cached ON the dataset so repeated eval calls pay it once per
        dataset (not per alternation) and the matrix's lifetime follows
        the dataset's (free_dataset releases it with the other device
        storage)."""
        if not getattr(ds, "has_sparse_cols", False):
            return ds.bins
        cache = getattr(ds, "_traversal_bins_cache", None)
        if cache is not None:
            return cache
        n = ds.num_data
        sp = np.asarray(ds.sp_cols)
        f_dense = ds.bins.shape[1]
        fc = f_dense + len(sp)
        dtype = np.uint8 if ds.max_num_bins <= 256 else np.int32
        full = np.zeros((n, fc), dtype)
        dense_cols = np.setdiff1d(np.arange(fc), sp)
        if f_dense:
            full[:, dense_cols] = np.asarray(ds.bins)
        full[:, sp] = np.asarray(ds.sp_default).astype(dtype)[None, :]
        for i, c in enumerate(sp):
            rows, vals = ds.stream_column(i)
            full[rows, int(c)] = vals
        out = jnp.asarray(full)
        ds._traversal_bins_cache = out
        return out

    def predict_raw(self, X, num_iteration: Optional[int] = None,
                    start_iteration: int = 0,
                    pred_early_stop: bool = False,
                    pred_early_stop_freq: int = 10,
                    pred_early_stop_margin: float = 10.0,
                    _postprocess=None) -> np.ndarray:
        """``_predict_raw_impl`` under the OOM degradation ladder's
        predict rung: a RESOURCE_EXHAUSTED from the engine programs
        shrinks the chunk size (recorded in health_snapshot()) and
        retries instead of failing the serve call."""
        while True:
            try:
                return self._predict_raw_impl(
                    X, num_iteration, start_iteration, pred_early_stop,
                    pred_early_stop_freq, pred_early_stop_margin,
                    _postprocess)
            except Exception as e:
                if not self._maybe_degrade_predict_oom(e):
                    raise

    def _predict_raw_impl(self, X, num_iteration: Optional[int] = None,
                          start_iteration: int = 0,
                          pred_early_stop: bool = False,
                          pred_early_stop_freq: int = 10,
                          pred_early_stop_margin: float = 10.0,
                          _postprocess=None) -> np.ndarray:
        """Raw scores for new raw-feature data (binned via the train mappers;
        the analog of GBDT::PredictRaw, gbdt_prediction.cpp:13-53). The
        boost-from-average init score lives inside the first tree's leaves
        (see _bias_after_score), so prediction is a pure sum of tree outputs.
        Iterations from a loaded init model come first (gbdt.h
        num_init_iteration_). ``pred_early_stop``: margin-based per-row
        early exit — rows whose margin exceeds the threshold at a check
        round stop accumulating further trees (reference:
        prediction_early_stop.cpp:25-75, hook in gbdt_prediction.cpp)."""
        from ..utils import faults as faults_mod
        sf = faults_mod.serve_faults(self.config)
        if sf is not None:
            # serve-side injection points (deterministic, re-read per
            # dispatch): a traced delay forcing deadline/shed paths, and a
            # simulated RESOURCE_EXHAUSTED the predict-chunk degradation
            # rung (predict_raw's retry loop) must rescue
            faults_mod.maybe_slow_predict(sf)
            faults_mod.maybe_oom_predict(sf)
        X = self._prep_predict_X(X)
        if self.config.linear_tree or self.train_set.bundles is not None:
            # raw-feature prediction via the model-space trees: linear leaves
            # need raw features, and EFB-bundled datasets must not bin new
            # data through shared bundle columns (new rows may violate the
            # exclusivity the training rows satisfied — the reference also
            # predicts on raw features with real thresholds, predictor.hpp)
            from ..basic import _is_scipy_sparse
            from ..io.model_text import ModelTree
            k = self.num_tree_per_iteration
            total_iters = self.loaded_iters + len(self.trees) // k
            if num_iteration is None or num_iteration <= 0:
                end_iter = total_iters
            else:
                end_iter = min(start_iteration + num_iteration, total_iters)
            if _is_scipy_sparse(X):
                X = np.asarray(X.todense())
            out = np.zeros((X.shape[0], k), dtype=np.float64)
            active = np.ones(X.shape[0], dtype=bool)
            for it in range(start_iteration, end_iter):
                for c in range(k):
                    if it < self.loaded_iters:
                        delta = self.loaded.trees[it * k + c].predict(X)
                    else:
                        idx = (it - self.loaded_iters) * k + c
                        mt = self._mt_cache.get(idx)
                        if mt is None:
                            mt = ModelTree.from_host(self.host_trees[idx],
                                                     self.train_set.mappers)
                            self._mt_cache[idx] = mt
                        delta = mt.predict(X)
                    _accumulate_active(out, c, delta, active, pred_early_stop)
                if pred_early_stop and \
                        (it - start_iteration + 1) % pred_early_stop_freq == 0:
                    active &= ~_early_stop_mask(out, k,
                                                pred_early_stop_margin)
                    if not active.any():
                        break
            return out if k > 1 else out[:, 0]
        bins = self.train_set.bin_new_data(X)
        k = self.num_tree_per_iteration
        n = bins.shape[0]
        total_iters = self.loaded_iters + len(self.trees) // k
        # num_iteration counts iterations used FROM start_iteration
        # (reference: c_api predict semantics, gbdt.h num_iteration_for_pred_)
        if num_iteration is None or num_iteration <= 0:
            end_iter = total_iters
        else:
            end_iter = min(start_iteration + num_iteration, total_iters)
        out = np.zeros((n, k), dtype=np.float64)
        mb = self.train_set.missing_bin
        active = np.ones(n, dtype=bool)
        # iterations from a loaded init model walk host trees (their bin
        # thresholds belong to a different mapper space); the numpy walker
        # needs a dense matrix
        if start_iteration < min(end_iter, self.loaded_iters):
            from ..basic import _is_scipy_sparse
            if _is_scipy_sparse(X):
                X = np.asarray(X.todense())
        it = start_iteration
        while it < min(end_iter, self.loaded_iters):
            for c in range(k):
                delta = self.loaded.trees[it * k + c].predict(X)
                _accumulate_active(out, c, delta, active, pred_early_stop)
            it += 1
            if pred_early_stop and \
                    (it - start_iteration) % pred_early_stop_freq == 0:
                active &= ~_early_stop_mask(out, k, pred_early_stop_margin)
                if not active.any():
                    return out if k > 1 else out[:, 0]
        # own trees: the device-resident inference engine — depth-bounded
        # traversal + f64 accumulation IN TREE ORDER on device, so only
        # the [n, K] result crosses to the host (bit-identical to the
        # former host per-tree accumulation; the [T, n] per-tree value
        # matrix never leaves the device)
        if it < end_iter:
            own_end = end_iter - self.loaded_iters
            eng = self._predict_engine(own_end)
            rng = ((it - self.loaded_iters) * k, own_end * k)
            base = None
            if out.any():        # nonzero only after a loaded-model prefix
                base = out if k > 1 else out[:, 0]
            if not pred_early_stop:
                res = eng.predict(bins, mb, base=base, use_bias=False,
                                  tree_range=rng, postprocess=_postprocess)
                return np.asarray(res)
            out = self._predict_early_stop(
                eng, bins, mb, out, active, base, it, end_iter,
                start_iteration, pred_early_stop_freq,
                pred_early_stop_margin)
        res = out if k > 1 else out[:, 0]
        if _postprocess is not None:
            # degenerate window (no own trees in range): still honor the
            # requested device-side conversion
            res = np.asarray(jax.device_get(_postprocess(jnp.asarray(res))))
        return res

    def _predict_early_stop(self, eng, bins, mb, out, active, base, it,
                            end_iter, start_iteration, freq,
                            margin) -> np.ndarray:
        """Margin-based prediction early stop on the engine: the f64 carry
        stays ON DEVICE across check chunks (accumulation order unchanged
        — bit-identical to the legacy host loop), rows deactivate via a
        device select mask, and the host sees the [n, K] scores only at
        the freq-bounded check points. Rows beyond the streaming chunk
        size are processed in independent row chunks (early stop is
        per-row, so chunking is exact) — the device never holds more
        than one chunk of the feature matrix, like the plain path."""
        k = self.num_tree_per_iteration
        n = bins.shape[0]
        chunk = eng._chunk_rows(n)
        if n > chunk:
            outs = []
            for a0 in range(0, n, chunk):
                b0 = min(n, a0 + chunk)
                outs.append(self._predict_early_stop(
                    eng, bins[a0:b0], mb, out[a0:b0], active[a0:b0],
                    None if base is None else base[a0:b0], it, end_iter,
                    start_iteration, freq, margin))
            return np.concatenate(outs, axis=0)
        bucket = eng.bucket_rows(n)
        pad = bucket - n
        bins_dev = eng.prepare_bins(bins, bucket)
        carry = eng.make_carry(base, bucket)

        def upload_active(a_np):
            return eng._upload_rows(np.pad(a_np, (0, pad)) if pad
                                    else a_np, eng.sharded)

        active_dev = upload_active(active)
        while it < end_iter:
            nxt = start_iteration + ((it - start_iteration) // freq
                                     + 1) * freq
            ce = min(end_iter, nxt)
            a = (it - self.loaded_iters) * k
            b = (ce - self.loaded_iters) * k
            carry = eng.accumulate(bins_dev, mb, carry, active_dev,
                                   tree_range=(a, b), use_bias=False)
            it = ce
            if (it - start_iteration) % freq == 0 and it < end_iter:
                out = eng.fetch(carry, n).reshape(n, k)
                active &= ~_early_stop_mask(out, k, margin)
                if not active.any():
                    return out
                active_dev = upload_active(active)
        return eng.fetch(carry, n).reshape(n, k)

    def _engine_predict_ok(self) -> bool:
        """Whether predict_raw routes the WHOLE ensemble through the
        device engine with the conversion fused before the fetch (no
        host-walked prefix; RF's averaged output divides on host AFTER
        the engine sum, so its conversion cannot fuse)."""
        return (not self.config.linear_tree
                and self.train_set.bundles is None
                and self.loaded_iters == 0
                and not self.average_output
                and len(self.trees) > 0)

    def predict(self, X, raw_score: bool = False,
                num_iteration: Optional[int] = None,
                start_iteration: int = 0,
                pred_early_stop: bool = False,
                pred_early_stop_freq: int = 10,
                pred_early_stop_margin: float = 10.0) -> np.ndarray:
        if not (raw_score or self.objective is None) \
                and not pred_early_stop and self._engine_predict_ok():
            # conversion fused on device BEFORE the single [n, K] fetch:
            # a converted full-ensemble predict is <= 3 dispatches
            # (ensemble scan, jitted conversion, row-pad slice)
            return self.predict_raw(
                X, num_iteration, start_iteration,
                _postprocess=self._convert_output_jit())
        raw = self.predict_raw(X, num_iteration, start_iteration,
                               pred_early_stop=pred_early_stop,
                               pred_early_stop_freq=pred_early_stop_freq,
                               pred_early_stop_margin=pred_early_stop_margin)
        if raw_score or self.objective is None:
            return raw
        conv = np.asarray(self._convert_output_jit()(jnp.asarray(raw)))
        return conv

    def predict_leaf(self, X, num_iteration: Optional[int] = None,
                     start_iteration: int = 0) -> np.ndarray:
        """Per-tree leaf indices (reference: predict_leaf_index path)."""
        X = self._prep_predict_X(X)
        bundled = self.train_set.bundles is not None
        # bundled datasets traverse raw features via ModelTree (see
        # predict_raw) — don't bin the prediction matrix at all
        bins = None if bundled else self.train_set.bin_new_data(X)
        k = self.num_tree_per_iteration
        total_iters = self.loaded_iters + len(self.trees) // k
        if num_iteration is None or num_iteration <= 0:
            end_iter = total_iters
        else:
            end_iter = min(start_iteration + num_iteration, total_iters)
        mb = self.train_set.missing_bin
        if bundled:
            from ..basic import _is_scipy_sparse
            from ..io.model_text import ModelTree
            if _is_scipy_sparse(X):
                X = np.asarray(X.todense())
        cols = []
        it = start_iteration
        while it < min(end_iter, self.loaded_iters):
            for c in range(k):
                cols.append(self.loaded.trees[it * k + c].leaf_index(X))
            it += 1
        if bundled:
            while it < end_iter:
                for c in range(k):
                    idx = (it - self.loaded_iters) * k + c
                    mt = self._mt_cache.get(idx)
                    if mt is None:
                        mt = ModelTree.from_host(self.host_trees[idx],
                                                 self.train_set.mappers)
                        self._mt_cache[idx] = mt
                    cols.append(mt.leaf_index(X))
                it += 1
        elif it < end_iter:
            # own trees: the engine's depth-bounded stacked traversal
            # (like predict_raw — not one round trip per tree); the [t, n]
            # leaf transfer is inherent to this API, so only the tree-range
            # chunking bounds the host buffer
            own_end = end_iter - self.loaded_iters
            eng = self._predict_engine(own_end)
            n = bins.shape[0]
            # upload the padded bin matrix ONCE; the tree-range chunks
            # below reuse the resident device copy
            bins_dev = eng.prepare_bins(bins, eng.bucket_rows(n))
            for a, b in _chunked_tree_ranges(
                    it - self.loaded_iters, own_end, k, n, itemsize=4):
                leaves = eng.leaves(bins_dev, mb, tree_range=(a, b),
                                    n_rows=n)
                cols.extend(list(leaves))            # [t, n] -> t columns
        return np.stack(cols, axis=1) if cols else np.zeros((X.shape[0], 0),
                                                            np.int32)

    def predict_contrib(self, X, num_iteration: Optional[int] = None,
                        start_iteration: int = 0) -> np.ndarray:
        """SHAP feature contributions (reference: GBDT::PredictContrib via
        Tree::PredictContrib, tree.h:139; layout [N, (F+1)*k])."""
        from ..io.model_text import ModelTree
        from ..io.shap import predict_contrib_trees
        X = self._prep_predict_X(X)
        k = self.num_tree_per_iteration
        total_iters = self.loaded_iters + len(self.trees) // k
        if num_iteration is None or num_iteration <= 0:
            end_iter = total_iters
        else:
            end_iter = min(start_iteration + num_iteration, total_iters)
        mappers = self.train_set.mappers
        # reuse the converted ModelTree lists across calls, keyed by the
        # iteration window so alternating truncated/full pred_contrib calls
        # don't thrash (stable object identities also let the SHAP stack
        # cache skip its precompute)
        cache_key = (start_iteration, end_iter, len(self.trees),
                     self.loaded_iters)
        cache = getattr(self, "_contrib_tree_cache", None)
        if cache is None:
            cache = self._contrib_tree_cache = {}
        trees = cache.get(cache_key)
        if trees is None:
            trees = []
            for it in range(start_iteration, end_iter):
                for c in range(k):
                    if it < self.loaded_iters:
                        trees.append(self.loaded.trees[it * k + c])
                    else:
                        trees.append(ModelTree.from_host(
                            self.host_trees[(it - self.loaded_iters) * k + c],
                            mappers))
            if len(cache) >= 8:
                cache.pop(next(iter(cache)))
            cache[cache_key] = trees

        return predict_contrib_trees(trees, X,
                                     self.train_set.num_total_features, k,
                                     average=self.average_output)

    def feature_importance(self, importance_type: str = "split") -> np.ndarray:
        """Split-count or total-gain importance per original feature
        (reference: gbdt.cpp:838+ FeatureImportance)."""
        imp = np.zeros(self.train_set.num_total_features, dtype=np.float64)
        if self.loaded is not None:
            imp += self.loaded.feature_importance(importance_type)
        for ht in self.host_trees:
            for i in range(ht.num_leaves - 1):
                real_feat = int(ht.feature_indices[ht.split_feature[i]])
                if importance_type == "split":
                    imp[real_feat] += 1.0
                else:
                    imp[real_feat] += max(float(ht.split_gain[i]), 0.0)
        return imp

    @property
    def num_trees(self) -> int:
        return len(self.trees) + self.loaded_iters * self.num_tree_per_iteration

    def current_iteration(self) -> int:
        return self.iter + self.loaded_iters


def _accumulate_active(out: np.ndarray, c: int, delta: np.ndarray,
                       active: np.ndarray, early_stop: bool) -> None:
    """Add a tree's outputs to the active rows; plain add on the hot path
    when prediction early stop is off (boolean fancy-indexing costs two
    full-size copies per tree)."""
    if not early_stop or active.all():
        out[:, c] += delta
    else:
        out[active, c] += delta[active]


def _early_stop_mask(out: np.ndarray, k: int,
                     margin_threshold: float) -> np.ndarray:
    """Rows whose prediction margin already exceeds the early-stop threshold
    (reference: prediction_early_stop.cpp — binary margin = 2|pred| (:58-66),
    multiclass margin = top1 - top2 (:29-49))."""
    if k == 1:
        margin = 2.0 * np.abs(out[:, 0])
    else:
        srt = np.sort(out, axis=1)
        margin = srt[:, -1] - srt[:, -2]
    return margin > margin_threshold


def _call_feval(feval, score_np, ds, objective, ds_name="valid"):
    """Adapt a user eval function returning (name, value, is_higher_better)
    or a list of such tuples (reference: engine.py feval protocol)."""
    results = []
    fevals = feval if isinstance(feval, (list, tuple)) else [feval]
    for fe in fevals:
        ret = fe(score_np, ds)
        rets = ret if isinstance(ret, list) else [ret]
        for name, val, bigger in rets:
            results.append((ds_name, name, float(val), bool(bigger)))
    return results
