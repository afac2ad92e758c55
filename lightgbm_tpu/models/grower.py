"""Leaf-wise tree growth as one jitted XLA program.

TPU-native re-design of the reference's SerialTreeLearner::Train loop
(reference: src/treelearner/serial_tree_learner.cpp:158-209): leaf membership
is a per-row int32 vector instead of a permuted index partition
(data_partition.hpp:21-60), histograms are built for every
histogram-pending leaf in ONE full-data pass (ops/histogram.py), and split
search evaluates all (leaf, feature, threshold) candidates at once
(ops/split.py).

Growth proceeds in ROUNDS inside a ``lax.while_loop``; each round is either

  a TILE PASS — one data pass building histograms for a tile of up to
  ``tile_leaves`` histogram-pending leaves (ops/histogram.py); with
  ``hist_subtraction`` only the SMALLER child of each sibling pair is
  computed and the larger is derived as parent - smaller (the reference's
  subtraction trick, serial_tree_learner.cpp:311-320: the parent's histogram
  is still resident in the slot the left child inherited, tracked by
  ``parent_hist``). With a ``compaction_ladder`` the pass first gathers
  just the tile's rows into the smallest padded buffer that fits (the
  DataPartition analog — see the grow_tree docstring) so non-root passes
  stream O(pending rows), not O(N); the ladder holds only rungs that pay
  at the shape, which on a TPU at a narrow width is none. Or,

  a SPLIT PHASE (entered when nothing is pending) — vectorized best-split
  search over all leaves, then an inner while_loop splitting leaves in gain
  order (children become histogram-pending for the next tile rounds).

Equivalence to the reference's strict leaf-wise order: tree growth is
order-independent whenever every positive-gain split fits in the
``num_leaves`` budget (the set of splits is the gain>0 closure, regardless of
order). The batched order can differ from strict best-first only in WHICH
leaves receive the final few splits when the budget binds mid-round — the
per-leaf split decisions themselves are identical.

Guards mirror BeforeFindBestSplit (serial_tree_learner.cpp:282-322): a leaf
whose count < 2*min_data_in_leaf or hessian sum < 2*min_sum_hessian_in_leaf
is never histogrammed; max_depth masks at split-search level.

Optional learner features threaded through the same jitted program:

- monotone constraints, basic mode (monotone_constraints.hpp:463-512
  BasicLeafConstraints): per-leaf [min, max] output bounds, updated with the
  children's mid-point at every split on a monotone feature;
- interaction constraints (col_sampler.hpp:20-50): per-leaf allowed-feature
  masks derived from the features used along the path and the constraint
  groups — two boolean matmuls per round;
- CEGB (cost_effective_gradient_boosting.hpp): split/coupled/lazy penalties
  as a per-(leaf, feature) additive gain adjustment;
- extra_trees (feature_histogram.hpp USE_RAND): one random threshold per
  (leaf, feature) per round;
- feature_fraction_bynode (col_sampler.hpp GetByNode): per-leaf random
  feature subset resampled every round.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.histogram import histogram_tiles
from ..ops.split import (FeatureMeta, SplitInfo, SplitParams,
                         find_best_splits)
from .tree import TreeArrays, empty_tree

NEG_INF = -jnp.inf
F32_MAX = jnp.finfo(jnp.float32).max


def advanced_child_bounds(lo, hi, out, act, monotone, num_bins: int,
                          mono_features: tuple):
    """Per-threshold child output bounds for the ADVANCED monotone mode.

    For a split of leaf ``l`` on feature ``g`` at threshold bin ``t``, the
    left child occupies the slice ``[lo[l,g], t]`` of l's region box and
    the right child ``[t+1, hi[l,g]]``. A leaf ``l'`` bounds a child when
    it overlaps the child's region in every feature except exactly one
    monotone feature where it lies strictly on one side — the same
    contiguity relation the intermediate mode applies to whole boxes,
    refined to the child region. This is the vectorized re-derivation of
    the reference's threshold-sliced constraints
    (monotone_constraints.hpp:856-1171 AdvancedLeafConstraints:
    GoUp/GoDownToFindConstrainingLeaves build FeatureMinOrMaxConstraints
    over threshold slices whose CumulativeFeatureConstraint left/right
    extrema equal these arrays at each t).

    Every contribution is monotone in t (a leaf starts or stops
    constraining at one breakpoint bin), so bounds assemble as
    scatter-extremum at the breakpoints followed by prefix/suffix
    cumulative extrema over the bin axis.

    Args:
      lo, hi: [L, F] int32 inclusive leaf region boxes in bin space.
      out: [L] current leaf outputs.
      act: [L] bool active leaves.
      monotone: [F] int8 per-feature direction.
      num_bins: static B (threshold axis length).
      mono_features: static tuple of monotone feature indices.

    Returns:
      (lmin, lmax, rmin, rmax): [L, F, B] f32 output bounds for the
      left/right child as a function of threshold bin.
    """
    L, F = lo.shape
    B = num_bins
    NEG = jnp.float32(-F32_MAX)
    POS = jnp.float32(F32_MAX)
    outf = out.astype(jnp.float32)
    size = L * F * B
    li = jnp.arange(L, dtype=jnp.int32)

    ovl = ((lo[:, None, :] <= hi[None, :, :])
           & (lo[None, :, :] <= hi[:, None, :]))          # [L, L', F]
    cnt = jnp.sum(ovl, axis=2, dtype=jnp.int32)           # [L, L']
    pair = act[:, None] & act[None, :] & ~jnp.eye(L, dtype=bool)

    # scatter planes: pre_* activates for t >= tau (prefix extremum),
    # suf_* for t <= tau (suffix extremum)
    pre_lmin = jnp.full((size,), NEG)
    suf_lmin = jnp.full((size,), NEG)
    pre_lmax = jnp.full((size,), POS)
    suf_lmax = jnp.full((size,), POS)
    pre_rmin = jnp.full((size,), NEG)
    pre_rmax = jnp.full((size,), POS)
    suf_rmin = jnp.full((size,), NEG)
    suf_rmax = jnp.full((size,), POS)

    val2 = jnp.broadcast_to(outf[None, :], (L, L))

    # ---- case A: the separating monotone feature IS the split feature g.
    # l' must overlap l in every other feature; its position relative to
    # the child SLICE in g decides the bound and the breakpoint.
    for m in mono_features:
        caseA = pair & (cnt - ovl[:, :, m].astype(jnp.int32) == F - 1)
        mpos = monotone[m] > 0
        base_idx = (li[:, None] * F + m) * B
        # LEFT child, l' strictly above the slice (lo_g(l') > t):
        # active for t <= lo_g(l') - 1
        tau = jnp.broadcast_to(lo[None, :, m] - 1, (L, L))
        idx = jnp.where(caseA & (tau >= 0), base_idx + tau, size)
        suf_lmax = suf_lmax.at[jnp.where(mpos, idx, size)].min(
            val2, mode="drop")
        suf_lmin = suf_lmin.at[jnp.where(mpos, size, idx)].max(
            val2, mode="drop")
        # LEFT child, l' strictly below the slice (== below the box,
        # since the slice shares the box's lower edge): all t
        belowb = caseA & (hi[None, :, m] < lo[:, None, m])
        idx0 = jnp.where(belowb, base_idx, size)
        pre_lmin = pre_lmin.at[jnp.where(mpos, idx0, size)].max(
            val2, mode="drop")
        pre_lmax = pre_lmax.at[jnp.where(mpos, size, idx0)].min(
            val2, mode="drop")
        # RIGHT child, l' strictly below the slice (hi_g(l') <= t):
        # active for t >= hi_g(l')
        taur = jnp.broadcast_to(hi[None, :, m], (L, L))
        idxr = jnp.where(caseA, base_idx + taur, size)
        pre_rmin = pre_rmin.at[jnp.where(mpos, idxr, size)].max(
            val2, mode="drop")
        pre_rmax = pre_rmax.at[jnp.where(mpos, size, idxr)].min(
            val2, mode="drop")
        # RIGHT child, l' strictly above the slice (== above the box): all t
        aboveb = caseA & (lo[None, :, m] > hi[:, None, m])
        idx0r = jnp.where(aboveb, base_idx, size)
        pre_rmax = pre_rmax.at[jnp.where(mpos, idx0r, size)].min(
            val2, mode="drop")
        pre_rmin = pre_rmin.at[jnp.where(mpos, size, idx0r)].max(
            val2, mode="drop")

    # ---- case B: the separator is a monotone feature m* != g; the
    # t-dependence enters through l' overlapping the child's g-slice.
    Bmin = jnp.zeros((L, L, F), bool)
    Bmax = jnp.zeros((L, L, F), bool)
    for m in mono_features:
        above = lo[None, :, m] > hi[:, None, m]
        below = hi[None, :, m] < lo[:, None, m]
        okF = ((cnt[:, :, None] - ovl.astype(jnp.int32)
                - ovl[:, :, m].astype(jnp.int32)[:, :, None]) == F - 2)
        okF = okF & (pair & (above | below))[:, :, None]
        okF = okF.at[:, :, m].set(False)          # m* == g handled by case A
        mpos = monotone[m] > 0
        is_min = jnp.where(mpos, below, above)[:, :, None]
        Bmin = Bmin | (okF & is_min)
        Bmax = Bmax | (okF & ~is_min)

    gidx = jnp.arange(F, dtype=jnp.int32)
    base3 = (li[:, None, None] * F + gidx[None, None, :]) * B    # [L, 1, F]
    val3 = jnp.broadcast_to(outf[None, :, None], (L, L, F))
    # LEFT child: needs hi_g(l') >= lo_g(l); active for t >= lo_g(l')
    okL = hi[None, :, :] >= lo[:, None, :]
    tauL = jnp.clip(jnp.broadcast_to(lo[None, :, :], (L, L, F)), 0, B - 1)
    idxL_min = jnp.where(Bmin & okL, base3 + tauL, size)
    idxL_max = jnp.where(Bmax & okL, base3 + tauL, size)
    pre_lmin = pre_lmin.at[idxL_min].max(val3, mode="drop")
    pre_lmax = pre_lmax.at[idxL_max].min(val3, mode="drop")
    # RIGHT child: needs lo_g(l') <= hi_g(l); active for t <= hi_g(l') - 1
    okR = lo[None, :, :] <= hi[:, None, :]
    tauR = jnp.broadcast_to(hi[None, :, :] - 1, (L, L, F))
    okR = okR & (tauR >= 0)
    idxR_min = jnp.where(Bmin & okR, base3 + tauR, size)
    idxR_max = jnp.where(Bmax & okR, base3 + tauR, size)
    suf_rmin = suf_rmin.at[idxR_min].max(val3, mode="drop")
    suf_rmax = suf_rmax.at[idxR_max].min(val3, mode="drop")

    def shape(x):
        return x.reshape(L, F, B)

    cmax = functools.partial(jax.lax.cummax, axis=2)
    cmin = functools.partial(jax.lax.cummin, axis=2)
    lmin = jnp.maximum(cmax(shape(pre_lmin)),
                       cmax(shape(suf_lmin), reverse=True))
    lmax = jnp.minimum(cmin(shape(pre_lmax)),
                       cmin(shape(suf_lmax), reverse=True))
    rmin = jnp.maximum(cmax(shape(pre_rmin)),
                       cmax(shape(suf_rmin), reverse=True))
    rmax = jnp.minimum(cmin(shape(pre_rmax)),
                       cmin(shape(suf_rmax), reverse=True))
    return lmin, lmax, rmin, rmax


def tile_fill(rows_of: jax.Array, ladder: tuple) -> jax.Array:
    """[P] bool: how far a fused pass under a compaction ladder fills its
    tile. ``rows_of`` holds the row counts of the pass's P candidate
    leaves in tile order (0 for an empty slot). The first ``P // 2`` are
    always taken; the rest only while the running row count stays within
    the rung the first half fits — a fuller tile that misses that rung
    streams more rows than the pass it saves (two passes through N/8
    merged into one through N/2), while within the rung, or where the
    first half already needs the full pass, every further leaf is free.
    Only the fill is decided here, from the leaf counts the state holds;
    the rung itself is still picked by the exact row count of the tile."""
    p = rows_of.shape[0]
    half = max(p // 2, 1)
    run = jnp.cumsum(rows_of)
    cap = jnp.float32(jnp.inf)
    for m in sorted(ladder, reverse=True):
        cap = jnp.where(run[half - 1] <= m, jnp.float32(m), cap)
    return (jnp.arange(p) < half) | (run <= cap)


class GrowAux(NamedTuple):
    """Cross-iteration learner state returned alongside the tree (CEGB's
    feature-used tracking is global across the boosting run,
    cost_effective_gradient_boosting.hpp:90-101), plus per-tree counters."""
    used_split: jax.Array    # [F] bool: feature used in any split (CEGB coupled)
    row_used: jax.Array      # [N, F] bool or [1, 1] dummy (CEGB lazy)
    rows_streamed: jax.Array  # f32 scalar: rows read by this tree's
                              # histogram passes (compaction telemetry)
    coll_bytes: jax.Array    # f32 scalar: histogram-plane collective bytes
                             # RECEIVED per device for this tree (the
                             # psum_scatter'd tiles of the data learner /
                             # the vote + elected-histogram psums of the
                             # voting learner; best-split syncs are O(L)
                             # scalars and not counted). Row-count
                             # independent by construction — the volume
                             # the reference's ReduceScatter moves
                             # (data_parallel_tree_learner.cpp:184-186).
                             # 0 for the serial / feature learners.
    sentinel: jax.Array = None  # f32 scalar numerics sentinel for the
                             # HISTOGRAM PLANE: nonzero when the final
                             # histogram state / per-leaf grad-hess sums /
                             # leaf outputs contain NaN/Inf. Computed
                             # IN-PROGRAM (so it sees what the Pallas/XLA
                             # histogram kernels actually accumulated,
                             # which the host-side gradient check cannot)
                             # only when the ``numerics_sentinels`` static
                             # is on; a constant 0 otherwise — zero cost
                             # and a byte-identical program with the
                             # guard off. The default exists ONLY so
                             # 4-field GrowAux pickles from pre-sentinel
                             # checkpoints (CEGB aux in state.pkl) still
                             # unpickle; set_trainer_state normalizes the
                             # None to a real f32 zero.
    leaves_resolved: jax.Array = None  # f32 scalar: leaves whose histogram
                             # this tree's passes produced, computed from
                             # rows or derived from a sibling. Over the
                             # tree's pass count it says how full the tiles
                             # ran (at most 2 x tile_leaves a pass).
                             # Defaulted for the same pickles.
    sync_calls: jax.Array = None  # f32 scalar: best-split syncs this tree
                             # ran (``sync_best_splits`` over all leaves'
                             # bests under the data and feature learners,
                             # the vote tally under voting: one a search
                             # round). None under the serial learner, which
                             # has none: no operand, no result, the same
                             # program as before the counter.


def _one_more(sync_calls):
    """A search round's best-split sync, counted where there is one."""
    return None if sync_calls is None else sync_calls + 1.0


class GrowState(NamedTuple):
    leaf_id: jax.Array       # [N] int32
    leaf_id_sub: jax.Array   # [K] int32 (bagging subset) or [1]
    hist: jax.Array          # [L, F, B, 3]
    hist_valid: jax.Array    # [L] bool
    leaf_dead: jax.Array     # [L] bool (guard-failed, never splittable)
    leaf_sum_g: jax.Array    # [L]
    leaf_sum_h: jax.Array
    leaf_cnt: jax.Array
    leaf_output: jax.Array
    leaf_depth: jax.Array    # [L] int32
    leaf_min: jax.Array      # [L] monotone output lower bound
    leaf_max: jax.Array      # [L] monotone output upper bound
    leaf_lo: jax.Array       # [L, F] int32 region box lo (intermediate) or [1,1]
    leaf_hi: jax.Array       # [L, F] int32 region box hi (inclusive)
    used_path: jax.Array     # [L, F] bool (interaction constraints) or [1,1]
    used_split: jax.Array    # [F] bool (CEGB coupled)
    row_used: jax.Array      # [N, F] bool (CEGB lazy) or [1,1]
    sib: jax.Array           # [L] int32 sibling slot (-1 = none); the pair's
                             # parent histogram lives at slot min(l, sib[l])
    parent_hist: jax.Array   # [L] bool: slot's hist holds the PARENT's data
    done: jax.Array          # bool: a split phase found nothing to split
    forced_idx: jax.Array    # int32: next forced-split node to apply
    forced_slot: jax.Array   # [K] int32 leaf slot per forced node (-1 = dead)
    best: SplitInfo
    tree: TreeArrays
    num_leaves: jax.Array    # int32
    rounds: jax.Array        # int32
    rows_streamed: jax.Array  # f32: rows read by histogram passes so far
    coll_bytes: jax.Array    # f32: collective bytes received so far (see
                             # GrowAux.coll_bytes)
    leaves_resolved: jax.Array  # f32: leaves computed or derived so far
    sync_calls: jax.Array = None  # f32: best-split syncs so far (see
                             # GrowAux.sync_calls; None without a mesh axis)


class StreamPack(NamedTuple):
    """The stream columns as the row routing reads them
    (Dataset._maybe_extract_sparse has the layout)."""
    rows: jax.Array       # [E] int32 row ids, ascending inside a stream
    cell: jax.Array       # [E] int32 stream index * num_bins + bin
    default: jax.Array    # [F_sp] int32 the elided bin
    start: jax.Array      # [F_sp] int32 a stream's first entry
    length: jax.Array     # [F_sp] int32 its entry count
    width: int            # the widest (= last) stream's entry count
    num_bins: int
    col2dense: jax.Array  # [F] position of a dense column in ``bins``
    col2sp: jax.Array     # [F] stream index of a stream column
    is_stream: jax.Array  # [F] bool


@jax.named_scope("apply_split")
def _apply_split(state: GrowState, bins: jax.Array, binsT: jax.Array | None,
                 missing_bin: jax.Array,
                 gain_eff: jax.Array, meta: FeatureMeta, *,
                 with_monotone: bool, with_interactions: bool,
                 cegb_lazy: bool,
                 with_categorical: bool, with_bundle: bool,
                 mono_intermediate: bool = False,
                 sub_bins: jax.Array | None = None,
                 sub_binsT: jax.Array | None = None,
                 sp: StreamPack | None = None
                 ) -> Tuple[GrowState, jax.Array]:
    """Split the current best leaf (reference: SerialTreeLearner::Split,
    serial_tree_learner.cpp:564-682 + Tree::Split, tree.h:62).

    ``with_categorical`` / ``with_bundle``: whether the data set has a
    categorical feature / an EFB bundle at all. Where it has not,
    ``best.is_cat`` is never true and ``best.seg_lo`` never >= 0, and the
    row routing is traced without the bitset lookup and the segment test.
    The compiler cannot fold them away itself where ``state.best`` is a
    loop-carried table (the fused search scatters into it), and the
    8-word gather with the index relayout it forces then costs four more
    passes over the rows a split (0.49 against 0.18 s an iteration at
    10.5M rows, 254 splits).

    ``state.leaf_id`` / ``leaf_id_sub`` are [N] or, inside apply_splits'
    loop, [1, N]; they leave in the shape they came in.

    ``sp``: the ``StreamPack`` when some device columns live as streams.
    The routing is then a ``lax.cond`` on ``is_stream[feat]``, the split's
    own column: a split on a dense column reads ``binsT`` as it does
    without streams, and only a split on a stream column rebuilds the
    column from its stream (the analog of SparseBin::Split's stream walk,
    sparse_bin.hpp) by ONE N-row scatter into the default bin, under scope
    ``sparse_route``. The stream is a slice of the concatenated entries,
    ``width`` (the widest stream's length) from the stream's start: the
    widest is stored last, so the slice is never clamped, and what it
    holds past the stream's own length (the next streams' entries)
    becomes row ``n``, out of range and dropped. A stream's rows ascend
    (Dataset._maybe_extract_sparse), the masked tail included, so the
    scatter does not sort (a select over both columns paid the scatter
    and an 0.8M-index sort on every split: 1.44 of 5.36 s an iteration at
    11M rows, 254 splits, of which a tree in ten has one on a stream)."""
    l = jnp.argmax(gain_eff).astype(jnp.int32)
    best = state.best
    tree = state.tree
    new_leaf = state.num_leaves
    node = state.num_leaves - 1

    feat = best.feature[l]
    thr = best.threshold[l]
    dleft = best.default_left[l]
    is_cat = best.is_cat[l]
    bitset = best.cat_bitset[l]
    mb = missing_bin[feat]
    seg_lo = best.seg_lo[l]
    seg_hi = best.seg_hi[l]

    # --- rows of leaf l route left/right. A feature-major ``binsT`` makes
    # the column extraction a contiguous dynamic slice instead of a strided
    # read of the whole row-major matrix (matters at 10M+ rows).
    def route(bins_m, binsT_m, leaf_vec):
        def routed(colv, leaf_vec):
            gol = jnp.where((colv == mb) & (mb >= 0), dleft, colv <= thr)
            if with_bundle:
                # EFB bundle split: rows outside the owning member's
                # segment are its default mass and route by the default
                # direction
                in_seg = (colv >= seg_lo) & (colv <= seg_hi)
                gol = jnp.where(seg_lo >= 0,
                                jnp.where(in_seg, colv <= thr, dleft), gol)
            if with_categorical:
                # categorical: bitset membership
                # (Tree::CategoricalDecision, tree.h:349)
                word = jnp.take(bitset, colv >> 5)
                catl = ((word >> (colv & 31).astype(jnp.uint32)) & 1) == 1
                gol = jnp.where(is_cat, catl, gol)
            return jnp.where((leaf_vec == l) & ~gol, new_leaf, leaf_vec)

        def dense_route(leaf_vec):
            fidx = feat if sp is None else sp.col2dense[feat]
            if bins_m is None or bins_m.shape[1] == 0:
                colv = jnp.zeros(leaf_vec.shape, jnp.int32)
            elif binsT_m is not None:
                colv = jax.lax.dynamic_slice_in_dim(binsT_m, fidx, 1, 0)
            else:
                colv = jnp.take(bins_m, fidx, axis=1)
            return routed(colv.reshape(leaf_vec.shape).astype(jnp.int32),
                          leaf_vec)

        # a cond's branch opens name components of its own
        # (cond/branch_1_fun): the branch restates where it sits, so that
        # its instructions read apply_split/sparse_route/
        @jax.named_scope("apply_split")
        @jax.named_scope("sparse_route")
        def stream_route(leaf_vec):
            scol = sp.col2sp[feat]
            start = sp.start[scol]
            rowsv = jax.lax.dynamic_slice_in_dim(sp.rows, start, sp.width)
            cellv = jax.lax.dynamic_slice_in_dim(sp.cell, start, sp.width)
            # the slice runs on into the next streams: those positions
            # become row n, so the rows still ascend and the scatter does
            # not sort them; they index out of range and are dropped
            rowsv = jnp.where(jnp.arange(sp.width, dtype=jnp.int32)
                              < sp.length[scol], rowsv, leaf_vec.size)
            base = jnp.full((leaf_vec.size,), sp.default[scol], jnp.int32)
            colv = base.at[rowsv].set(cellv - scol * sp.num_bins,
                                      mode="drop", indices_are_sorted=True)
            return routed(colv.reshape(leaf_vec.shape), leaf_vec)

        if sp is None:
            return dense_route(leaf_vec)
        # control flow on the split's own column, not a select over two
        # computed columns: only a split on a stream column pays the N-row
        # scatter, and each branch is one fused pass over the rows
        return jax.lax.cond(sp.is_stream[feat], stream_route, dense_route,
                            leaf_vec)

    in_leaf = state.leaf_id.reshape(-1) == l
    leaf_id = route(bins, binsT, state.leaf_id)
    # bagging-subset mode: the compacted in-bag rows route in parallel so
    # histogram passes stay subset-sized (GBDT subset copy,
    # gbdt.cpp:810-818 / Dataset::CopySubrow)
    leaf_id_sub = state.leaf_id_sub
    if sub_bins is not None:
        leaf_id_sub = route(sub_bins, sub_binsT, state.leaf_id_sub)

    # --- tree arrays: fix the parent link that pointed at leaf l
    parent = tree.leaf_parent[l]
    psafe = jnp.maximum(parent, 0)
    left_match = (parent >= 0) & (tree.node_left[psafe] == ~l)
    right_match = (parent >= 0) & (tree.node_right[psafe] == ~l)
    node_left = tree.node_left.at[psafe].set(
        jnp.where(left_match, node, tree.node_left[psafe]))
    node_right = tree.node_right.at[psafe].set(
        jnp.where(right_match, node, tree.node_right[psafe]))

    tree = tree._replace(
        num_leaves=state.num_leaves + 1,
        node_feature=tree.node_feature.at[node].set(feat),
        node_threshold_bin=tree.node_threshold_bin.at[node].set(thr),
        node_default_left=tree.node_default_left.at[node].set(dleft),
        node_cat=tree.node_cat.at[node].set(is_cat),
        node_cat_bitset=tree.node_cat_bitset.at[node].set(bitset),
        node_seg_lo=tree.node_seg_lo.at[node].set(seg_lo),
        node_seg_hi=tree.node_seg_hi.at[node].set(seg_hi),
        node_left=node_left.at[node].set(~l),
        node_right=node_right.at[node].set(~new_leaf),
        node_gain=tree.node_gain.at[node].set(best.gain[l]),
        node_value=tree.node_value.at[node].set(state.leaf_output[l]),
        node_weight=tree.node_weight.at[node].set(state.leaf_sum_h[l]),
        node_count=tree.node_count.at[node].set(state.leaf_cnt[l]),
        leaf_value=tree.leaf_value.at[l].set(best.left_output[l])
                                   .at[new_leaf].set(best.right_output[l]),
        leaf_weight=tree.leaf_weight.at[l].set(best.left_sum_h[l])
                                    .at[new_leaf].set(best.right_sum_h[l]),
        leaf_count=tree.leaf_count.at[l].set(best.left_count[l])
                                  .at[new_leaf].set(best.right_count[l]),
        leaf_depth=tree.leaf_depth.at[l].set(state.leaf_depth[l] + 1)
                                  .at[new_leaf].set(state.leaf_depth[l] + 1),
        leaf_parent=tree.leaf_parent.at[l].set(node).at[new_leaf].set(node),
    )

    new_depth = state.leaf_depth[l] + 1

    # monotone basic-mode bound update (monotone_constraints.hpp:485-501):
    # children inherit the parent's bounds; a split on a monotone feature
    # tightens them around the children's mid-point
    leaf_min, leaf_max = state.leaf_min, state.leaf_max
    if with_monotone:
        mono = meta.monotone[feat].astype(jnp.int32)
        mono = jnp.where(is_cat, 0, mono)
        mid = (best.left_output[l] + best.right_output[l]) / 2.0
        pmin, pmax = leaf_min[l], leaf_max[l]
        # leaf keeps the LEFT child, new_leaf the RIGHT child
        lmax = jnp.where(mono > 0, jnp.minimum(pmax, mid), pmax)
        lmin = jnp.where(mono < 0, jnp.maximum(pmin, mid), pmin)
        rmin = jnp.where(mono > 0, jnp.maximum(pmin, mid), pmin)
        rmax = jnp.where(mono < 0, jnp.minimum(pmax, mid), pmax)
        leaf_min = leaf_min.at[l].set(lmin).at[new_leaf].set(rmin)
        leaf_max = leaf_max.at[l].set(lmax).at[new_leaf].set(rmax)

    # intermediate monotone mode tracks per-leaf bin-interval boxes: a
    # numerical split partitions the split feature's interval; categorical
    # splits leave both children's boxes unchanged (conservative overlap,
    # like the reference's always-go-down categorical handling,
    # monotone_constraints.hpp GoDownToFindLeavesToUpdate)
    leaf_lo, leaf_hi = state.leaf_lo, state.leaf_hi
    if mono_intermediate:
        parent_lo, parent_hi = leaf_lo[l], leaf_hi[l]
        num_split = ~is_cat
        lhi = jnp.where((jnp.arange(parent_hi.shape[0]) == feat) & num_split,
                        jnp.minimum(parent_hi, thr), parent_hi)
        rlo = jnp.where((jnp.arange(parent_lo.shape[0]) == feat) & num_split,
                        jnp.maximum(parent_lo, thr + 1), parent_lo)
        leaf_lo = leaf_lo.at[new_leaf].set(rlo)
        leaf_hi = leaf_hi.at[l].set(lhi).at[new_leaf].set(parent_hi)

    used_path = state.used_path
    if with_interactions:
        parent_used = state.used_path[l].at[feat].set(True)
        used_path = used_path.at[l].set(parent_used).at[new_leaf].set(parent_used)

    used_split = state.used_split.at[feat].set(True)

    row_used = state.row_used
    if cegb_lazy:
        row_used = row_used | (in_leaf[:, None]
                               & (jnp.arange(row_used.shape[1]) == feat)[None, :])

    state = state._replace(
        leaf_id=leaf_id,
        leaf_id_sub=leaf_id_sub,
        tree=tree,
        hist_valid=state.hist_valid.at[l].set(False).at[new_leaf].set(False),
        leaf_sum_g=state.leaf_sum_g.at[l].set(best.left_sum_g[l])
                                   .at[new_leaf].set(best.right_sum_g[l]),
        leaf_sum_h=state.leaf_sum_h.at[l].set(best.left_sum_h[l])
                                   .at[new_leaf].set(best.right_sum_h[l]),
        leaf_cnt=state.leaf_cnt.at[l].set(best.left_count[l])
                               .at[new_leaf].set(best.right_count[l]),
        leaf_output=state.leaf_output.at[l].set(best.left_output[l])
                                     .at[new_leaf].set(best.right_output[l]),
        leaf_depth=state.leaf_depth.at[l].set(new_depth)
                                   .at[new_leaf].set(new_depth),
        leaf_min=leaf_min, leaf_max=leaf_max,
        leaf_lo=leaf_lo, leaf_hi=leaf_hi,
        used_path=used_path, used_split=used_split, row_used=row_used,
        # slot l inherits the parent's histogram data (the basis of the
        # subtraction trick, serial_tree_learner.cpp:311-320)
        sib=state.sib.at[l].set(new_leaf).at[new_leaf].set(l),
        parent_hist=state.parent_hist.at[l].set(True).at[new_leaf].set(False),
        num_leaves=state.num_leaves + 1,
    )
    gain_eff = gain_eff.at[l].set(NEG_INF).at[new_leaf].set(NEG_INF)
    return state, gain_eff


# the static (compile-time) grow options — ONE definition shared by the
# monolithic grow_tree jit and the phased per-round programs
_GROW_STATICS = ("max_leaves", "num_bins", "max_depth", "hist_method",
                 "exact", "axis_name", "with_categorical", "with_monotone",
                 "mono_mode", "mono_features",
                 "with_interactions", "cegb_mode", "extra_trees",
                 "use_bynode", "tile_leaves", "hist_block",
                 "hist_subtraction", "feature_block",
                 "feature_axis_name", "feature_shards", "voting",
                 "vote_top_k", "hist_dp", "sp_cols", "sp_offsets",
                 "compaction_ladder", "hist_interpret",
                 "numerics_sentinels", "split_fusion")


def _grower_fns(bins: jax.Array, grad: jax.Array, hess: jax.Array,
              sample_mask: jax.Array, meta: FeatureMeta, params: SplitParams,
              feature_mask: jax.Array, missing_bin: jax.Array, *,
              max_leaves: int, num_bins: int, max_depth: int = -1,
              hist_method: str = "scatter",
              exact: bool = False,
              with_categorical: bool = False,
              with_monotone: bool = False,
              mono_mode: str = "basic",
              mono_features: tuple = (),
              with_interactions: bool = False,
              interaction_groups: jax.Array | None = None,
              cegb_mode: str = "off",
              cegb_coupled: jax.Array | None = None,
              cegb_lazy_penalty: jax.Array | None = None,
              cegb_state: GrowAux | None = None,
              extra_trees: bool = False,
              use_bynode: bool = False,
              bynode_fraction: jax.Array | None = None,
              rng_key: jax.Array | None = None,
              axis_name: str | None = None,
              binsT: jax.Array | None = None,
              sub_idx: jax.Array | None = None,
              sub_bins: jax.Array | None = None,
              sub_binsT: jax.Array | None = None,
              tile_leaves: int = 0,
              hist_block: int = 0,
              hist_subtraction: bool = True,
              feature_block: int = 0,
              feature_axis_name: str | None = None,
              feature_shards: int = 1,
              voting: bool = False,
              vote_top_k: int = 20,
              bundle_meta=None,
              forced_splits=None,
              hist_dp: bool = False,
              sp_cols: tuple = (),
              sp_offsets: tuple = (),
              sp_rows: jax.Array | None = None,
              sp_cell: jax.Array | None = None,
              sp_default: jax.Array | None = None,
              compaction_ladder: tuple = (),
              hist_interpret: bool = False,
              numerics_sentinels: bool = False,
              split_fusion: bool = False,
              ) -> dict:
    """Build the grow program's phase functions (closure factory).

    ``grow_tree`` runs them inside one jitted ``lax.while_loop``;
    ``grow_tree_phased`` runs the SAME functions as separate per-round
    jitted programs so each phase is host-timeable (the hist_pass /
    split_search / apply_split TIMETAG sub-scopes). Grow one tree;
    finalize returns (tree arrays, per-row leaf index, aux state).

    Args:
      bins: [N, F] binned features (device-resident, uint8/int32).
      grad, hess: [N] objective gradients/hessians (weights folded in,
        reference: ObjectiveFunction::GetGradients).
      sample_mask: [N] f32 0/1 bagging mask (mask-based bagging keeps shapes
        static; the analog of GBDT::Bagging's index subset, gbdt.cpp:228-262).
      feature_mask: [F] f32 0/1 from column sampling (col_sampler.hpp).
      missing_bin: [F] int32 default-routed bin per feature or -1.
      exact: strict best-first order (one split per histogram round) — the
        reference's exact leaf-wise semantics even when the num_leaves budget
        binds, at the cost of one histogram pass per split. The default
        batched mode performs all available splits per round (see module
        docstring for the equivalence argument).
      interaction_groups: [G, F] bool group membership when
        with_interactions.
      cegb_mode: "off" | "feat" (split+coupled penalties) | "lazy" (adds the
        per-row on-demand costs); cegb_state carries the cross-iteration
        used-feature tracking.
      rng_key: PRNG key, consumed when extra_trees or use_bynode.
      axis_name: when set, rows are sharded over this mesh axis (shard_map
        context): root sums and histogram tiles are psum'd over it — the SPMD
        analog of the reference data-parallel learner's root allreduce
        (data_parallel_tree_learner.cpp:125-152) and histogram ReduceScatter
        (:184-186). All devices then take identical split decisions with no
        further communication.
      binsT: optional [F, N] feature-major copy of ``bins`` for contiguous
        per-split column extraction during routing (recommended on TPU).
      tile_leaves: max pending leaves per histogram pass (the "onehot"
        backend's pass cost is flat in this up to ~42 at 256 bins x 3 stats;
        scatter/binloop backends use one pass for everything regardless).
      hist_subtraction: build only the smaller sibling's histogram and derive
        the larger by subtraction from the parent (the reference's trick,
        serial_tree_learner.cpp:311-320). Subtraction is exact for the count
        channel and float32-rounded for grad/hess (the reference subtracts in
        float64; its GPU path is float32 like ours).
      compaction_ladder: static ascending tuple of row-buffer sizes for the
        LEAF-PARTITIONED ROW COMPACTION path — the shape-static analog of
        the reference's permuted per-leaf row partition
        (data_partition.hpp:21-60; the optimization both GPU boosting
        papers build on: arXiv:1706.08359 §4, arXiv:1806.11248 §3.3).
        Before a tile pass the pending tile's rows are counted; the first
        rung that fits gets a prefix-sum gather of just those rows
        (ops/histogram.py compact_rows) and the histogram streams only the
        buffer — with ``hist_subtraction`` every non-root pass covers the
        SMALLER siblings, so <= N/2 rows fit from depth 1 and the covered
        row count shrinks geometrically with depth, restoring the
        reference's O(N * depth) histogram asymptotics. The full-N pass
        remains the fallback rung (chosen via lax.cond inside the jitted
        while_loop, so every rung is compiled once). Empty = always
        full-N: no count, no gather and no cond is traced. Serial learner
        only. Fewer rows streamed is not faster by itself: a rung costs a
        row count on EVERY pass plus an index build and XLA gathers on
        the passes that take it, and GBDT._compaction_ladder hands over
        only the rungs that pay at the shape
        (ops/histogram.py prune_compaction_ladder). On a TPU v5 lite at
        10.5M x 28, 255 bins, that is none — the half rung costs 774 ms
        against the 350 ms pass it replaces — and every pass is the
        full-size kernel; on the CPU's scatter backend every rung pays.
      split_fusion: the fused split-finding epilogue + frontier batching
        (ISSUE 12): every tile pass ALSO reduces each (leaf, feature) to
        its best numerical split candidate — in kernel on the Pallas
        methods (ops/pallas_hist.py epilogue kernels), via the identical
        XLA twin elsewhere. Every slot of the tile holds a leaf computed
        from rows (the smaller child of a pair, or a leaf with nothing
        to derive from); each brings its sibling along in a second lane
        group that only the epilogue sees, its plane derived in-pass as
        parent - smaller on the slot's own lanes, so a pass resolves up
        to 2 x tile_leaves leaves and the frontier's depth, not the
        tile, sets a tree's pass count. Under a compaction ladder a tile
        is filled past its first half only while its rows stay within
        the rung that half fits (tile_fill). state.best is maintained
        incrementally and the split phase consumes it directly: no
        [L, F, B, S] plane ever re-enters the search. Bit-identical
        trees to the classic phase (the parity suite pins it); serial
        learner, numerical non-bundled search only (see the gate asserts
        — the gbdt layer resolves Config.split_fusion="auto" off when
        unsupported).
      feature_block: > 0 engages the MEMORY-BOUNDED mode for wide datasets:
        no [L, F, B, 3] histogram state is kept at all — each pending leaf
        is histogrammed and searched immediately, ``feature_block`` columns
        at a time into a transient [P, Fb, B, 3] buffer, and only its best
        SplitInfo is retained (the analog of the reference's capped
        HistogramPool, feature_histogram.hpp:1095-1290: a full pool miss
        for every leaf). Costs ~2x the histogram passes (no parent
        subtraction) in exchange for O(P * Fb * B) transient memory.
        Serial learner only; CEGB, forced splits, box-mode monotone
        constraints, voting and the bagging subset copy are unsupported.
      feature_axis_name: feature-ownership mesh axis. Set WITHOUT axis_name
        (rows replicated) = the feature-parallel learner (reference:
        feature_parallel_tree_learner.cpp:59-78): each device histograms and
        searches only its own feature slice and the per-leaf best splits are
        merged with an allreduce-argmax (sync_best_splits). Set EQUAL to
        axis_name (rows sharded too) = the data-parallel learner with the
        reference's ReduceScatter communication pattern
        (data_parallel_tree_learner.cpp:184-186): histogram tiles are
        psum_scatter'd so each device receives only its owned features'
        global histograms, searches those, and syncs the best split —
        1/D the allreduce volume.
      feature_shards: number of feature slices (= size of feature_axis_name
        axis); the caller pads features so F divides evenly.
      voting: voting-parallel learner over ``axis_name`` (reference:
        voting_parallel_tree_learner.cpp PV-tree): histograms stay LOCAL to
        each row shard; each device votes its local top ``vote_top_k``
        features per leaf (local stats, min_data scaled by 1/D,
        voting_parallel_tree_learner.cpp:62-64), the vote elects 2*top_k
        features globally (GlobalVoting, :151-182), and only the elected
        features' histograms are summed across devices before the final
        search (CopyLocalHistogram, :184+).
    """
    n, f_dense = bins.shape
    f_sp = len(sp_cols)
    # f is the LOGICAL device-column count: meta/feature_mask/missing_bin
    # and the histogram planes span all columns; ``bins`` holds only the
    # dense ones. Sparse columns live as (row, bin) streams
    # (Dataset._maybe_extract_sparse): stream i is column sp_cols[i] and
    # the entries [sp_offsets[i], sp_offsets[i + 1]) of sp_rows / sp_cell,
    # the widest stream last; sp_cols and sp_offsets are static. Plane
    # placement and routing go through the static sp_cols positions.
    f = f_dense + f_sp
    if f_sp:
        assert (feature_axis_name is None and axis_name is None
                and not voting and feature_block == 0
                and sub_idx is None), (
            "sparse device storage is serial-only (construct with "
            "enable_sparse=false for parallel learners)")
        sp_np = np.asarray(sp_cols, dtype=np.int32)
        dense_np = np.asarray(
            [c for c in range(f) if c not in set(sp_cols)], dtype=np.int32)
        col2dense_np = np.zeros((f,), dtype=np.int32)
        col2dense_np[dense_np] = np.arange(len(dense_np), dtype=np.int32)
        col2sp_np = np.zeros((f,), dtype=np.int32)
        col2sp_np[sp_np] = np.arange(f_sp, dtype=np.int32)
        is_sp_np = np.zeros((f,), dtype=bool)
        is_sp_np[sp_np] = True
        assert (len(sp_offsets) == f_sp + 1 and sp_offsets[0] == 0
                and sp_offsets[-1] == sp_rows.shape[0] == sp_cell.shape[0]), (
            "sp_offsets bounds the f_sp streams inside sp_rows / sp_cell")
        sp_len_np = np.diff(np.asarray(sp_offsets, dtype=np.int64))
        assert (sp_len_np[:-1] <= sp_len_np[-1]).all(), (
            "the widest stream is stored last: a slice of its length from "
            "any stream's start stays inside the arrays")
        sp_pack = StreamPack(
            rows=sp_rows, cell=sp_cell, default=sp_default,
            start=jnp.asarray(sp_offsets[:-1], dtype=jnp.int32),
            length=jnp.asarray(sp_len_np, dtype=jnp.int32),
            width=int(sp_len_np[-1]), num_bins=num_bins,
            col2dense=jnp.asarray(col2dense_np),
            col2sp=jnp.asarray(col2sp_np), is_stream=jnp.asarray(is_sp_np))
    else:
        sp_np = dense_np = None
        sp_pack = None
    # the row routing's cases (_apply_split): constants of the data set
    route_kw = dict(with_categorical=with_categorical,
                    with_bundle=bundle_meta is not None)
    if compaction_ladder:
        assert (axis_name is None and feature_axis_name is None
                and not voting and feature_block == 0), (
            "hist compaction is serial-only; the caller must pass an empty "
            "ladder for parallel/blocked learners")
        assert tuple(sorted(compaction_ladder)) == tuple(compaction_ladder), (
            "compaction_ladder must be ascending")
    if split_fusion:
        assert (axis_name is None and feature_axis_name is None
                and not voting and feature_block == 0), (
            "split_fusion is serial-only; the caller resolves 'auto' off "
            "for parallel/blocked learners")
        assert (not with_categorical and bundle_meta is None
                and forced_splits is None and cegb_mode == "off"
                and not extra_trees and not use_bynode and not hist_dp
                and not f_sp), (
            "split_fusion covers the numerical non-bundled search only "
            "(no categorical/EFB/forced-splits/CEGB/extra_trees/bynode/"
            "f64/sparse) — those semantics stay in find_best_splits and "
            "the caller resolves 'auto' off when they apply")
        assert (not with_monotone) or mono_mode == "basic", (
            "split_fusion supports only basic monotone constraints")
    L = max_leaves
    if not tile_leaves:     # 0 = auto
        from ..ops.pallas_hist import structural_tile_leaves
        tile_leaves = structural_tile_leaves()
    P = min(tile_leaves, L) if hist_method.startswith(("onehot", "pallas")) \
        else L
    cat_words = max(1, -(-num_bins // 32))
    cegb_lazy = cegb_mode == "lazy"
    cegb_on = cegb_mode != "off"

    # --- feature-ownership slicing (FP learner, and DP's reduce-scatter)
    fp_mode = feature_axis_name is not None
    dp_scatter = fp_mode and (feature_axis_name == axis_name)
    if voting:
        assert axis_name is not None, "voting requires row sharding"
        assert not fp_mode, "voting and feature slicing are exclusive"
    if fp_mode:
        assert f % feature_shards == 0, (
            f"features {f} not divisible into {feature_shards} shards "
            f"(pad in the caller)")
        f_loc = f // feature_shards
        off = jax.lax.axis_index(feature_axis_name) * f_loc
        meta_s = FeatureMeta(*(jax.lax.dynamic_slice_in_dim(a, off, f_loc, 0)
                               for a in meta))
        missing_bin_s = jax.lax.dynamic_slice_in_dim(missing_bin, off, f_loc, 0)
        # FP replicates rows and histograms only the local slice; DP-scatter
        # histograms the full width locally, then psum_scatter assigns slices
        bins_h = (bins if dp_scatter
                  else jax.lax.dynamic_slice(bins, (jnp.int32(0), off),
                                             (n, f_loc)))
        binsT_h = None if binsT is None else (
            binsT if dp_scatter
            else jax.lax.dynamic_slice_in_dim(binsT, off, f_loc, 0))
    else:
        f_loc, off = f, None
        meta_s, missing_bin_s = meta, missing_bin
        bins_h = bins
        binsT_h = binsT

    def slice_f(arr):
        """Slice a per-feature trailing axis to the local feature shard."""
        if not fp_mode or arr is None:
            return arr
        return jax.lax.dynamic_slice_in_dim(arr, off, f_loc, arr.ndim - 1)

    # EFB bundle structure is per-feature on the LEADING axis; owner shards
    # search their own bundle columns (the reference's distributed learners
    # operate on the same bundled Dataset object on every machine)
    bundle_s = bundle_meta
    if fp_mode and bundle_meta is not None:
        bundle_s = type(bundle_meta)(
            *(jax.lax.dynamic_slice_in_dim(a, off, f_loc, 0)
              for a in bundle_meta))

    # hist_dp: float64 histogram accumulation, the reference CPU precision
    # model (hist_t, bin.h:32) / the gpu_use_dp flag's double mode; needs
    # jax x64 (the caller warns otherwise)
    hist_dtype = jnp.float64 if hist_dp else jnp.float32
    use_subset = sub_idx is not None
    if use_subset:
        # bagging subset copy (gbdt.cpp:810-818): histograms and root sums
        # run over the compacted in-bag rows only — pass cost scales with
        # the bagging fraction instead of full N. Full-row routing still
        # happens for the out-of-bag score update. Serial learner only.
        assert not fp_mode and not voting and axis_name is None, (
            "bagging subset copy is serial-only; distributed learners use "
            "the mask path")
        bins_h = sub_bins
        binsT_h = sub_binsT

    if rng_key is None:
        rng_key = jax.random.PRNGKey(0)

    # quantized-gradient mode (opt-in, histogram_method=*_q8): grad/hess
    # quantize to int8 with per-tree scales and stochastic rounding, so the
    # histogram contraction runs on the int8 MXU path (~2x bf16 rate) with
    # EXACT integer accumulation; counts stay exact 0/1. The re-design of
    # LightGBM 4.x quantized training for the MXU (not in the v3.2
    # reference — a forward-compatible fast path).
    quant8 = hist_method in ("pallas_q8", "onehot_q8")
    if quant8:
        assert not hist_dp, "q8 and f64 histograms are exclusive"
        # int32 accumulation bound: a cell summing |q| <= 127 per row wraps
        # past 2^31 only beyond ~16.9M rows per shard (static shape check)
        assert n <= (2 ** 31 - 1) // 127, (
            f"quantized histograms overflow int32 beyond "
            f"{(2**31 - 1) // 127} rows per shard (got {n}); use the "
            f"pallas_hilo method at this scale")

    @jax.named_scope("gradients")
    def row_stats():
        """The [rows, 3] statistics the passes stream (gradient, hessian,
        count, masked for bagging), their q8 scales, the root's sums and
        its output: the last step of the gradient phase."""
        if use_subset:
            g_sub = jnp.take(grad, sub_idx)
            h_sub = jnp.take(hess, sub_idx)
            stats = jnp.stack([g_sub, h_sub, jnp.ones_like(g_sub)],
                              axis=1).astype(hist_dtype)
        else:
            stats = jnp.stack(
                [grad * sample_mask, hess * sample_mask, sample_mask],
                axis=1).astype(hist_dtype)
        q_scale = None
        if quant8:
            sg = jnp.maximum(jnp.max(jnp.abs(stats[:, 0])), 1e-12)
            sh = jnp.maximum(jnp.max(jnp.abs(stats[:, 1])), 1e-12)
            if axis_name is not None:
                sg = jax.lax.pmax(sg, axis_name)
                sh = jax.lax.pmax(sh, axis_name)
            q_scale = jnp.stack([sg / 127.0, sh / 127.0,
                                 jnp.float32(1.0)]).astype(jnp.float32)
            u = jax.random.uniform(jax.random.fold_in(rng_key, 0x5138),
                                   stats.shape)
            stats = jnp.clip(jnp.floor(stats / q_scale[None, :] + u),
                             -127, 127).astype(jnp.int8)
            root = jnp.sum(stats.astype(jnp.float32), axis=0) * q_scale
        else:
            root = jnp.sum(stats, axis=0)
        if axis_name is not None:
            with jax.named_scope("hist_allreduce"):
                root = jax.lax.psum(root, axis_name)
        from ..ops.split import calculate_leaf_output
        root_out = calculate_leaf_output(root[0], root[1], params, root[2],
                                         jnp.float32(0.0))
        return stats, q_scale, root, root_out

    stats, q_scale, root, root_out = row_stats()
    if f_sp:
        # the statistics by stream entry are the same in every pass of a
        # tree (``stats`` is per tree, ``sp_rows`` per data set): gathered
        # once, here, and not in combine_sparse. Held entries-minor: a
        # float32 [E, 3] is tiled to 128 lanes on the chip, 0.62 GB where
        # [3, E] takes 20 MB at 1.2M entries
        with jax.named_scope("sparse_hist"):
            sp_stats = stats[sp_rows].T                           # [S, E]

    iota_l = jnp.arange(L, dtype=jnp.int32)
    # "intermediate" and "advanced" both maintain leaf region boxes and
    # recompute exact bounds each phase; "advanced" additionally derives
    # per-threshold child bounds for the numerical search
    mono_intermediate = with_monotone and mono_mode in ("intermediate",
                                                        "advanced")
    mono_advanced = with_monotone and mono_mode == "advanced"
    # intermediate-mode constraints are recomputed from ALL current leaf
    # outputs at the start of each split phase, so the strict one-split-per-
    # phase order is required for soundness (the reference re-searches the
    # leaves_to_update set after every split, monotone_constraints.hpp:565)
    exact = exact or mono_intermediate

    blocked = feature_block > 0
    if blocked:
        assert not fp_mode and not voting and axis_name is None, (
            "feature-blocked mode is serial-only")
        assert not cegb_on and forced_splits is None, (
            "feature-blocked mode does not support CEGB or forced splits")
        assert not mono_intermediate, (
            "feature-blocked mode supports only basic monotone constraints")
        assert not use_subset and not hist_dp and not quant8, (
            "feature-blocked mode: bagging subset copy / f64 / q8 "
            "histograms unsupported")
        hist_subtraction = False    # no resident parent histograms

    def _zero_best_direct() -> SplitInfo:
        """All -inf placeholder without materializing a [L, F, B, 3] zeros
        histogram (which is exactly what blocked mode must avoid). Sum and
        output fields carry ``hist_dtype`` so the while_loop state matches
        what split_phase's find_best_splits returns (f64 under hist_dp)."""
        zi = jnp.zeros((L,), jnp.int32)
        zs = jnp.zeros((L,), hist_dtype)
        return SplitInfo(
            gain=jnp.full((L,), NEG_INF, jnp.float32),
            feature=zi, threshold=zi,
            default_left=jnp.zeros((L,), bool),
            left_sum_g=zs, left_sum_h=zs, left_count=zs,
            right_sum_g=zs, right_sum_h=zs, right_count=zs,
            left_output=zs, right_output=zs,
            is_cat=jnp.zeros((L,), bool),
            cat_bitset=jnp.zeros((L, cat_words), jnp.uint32),
            seg_lo=jnp.full((L,), -1, jnp.int32),
            seg_hi=jnp.full((L,), -1, jnp.int32))

    def init_state() -> GrowState:
        zf = functools.partial(jnp.zeros, dtype=hist_dtype)
        # the placeholder best is never read before the first split phase
        # replaces it wholesale (gain_eff also masks on hist_valid, all
        # False here); building it directly instead of running
        # find_best_splits over a constant zero histogram avoids multi-
        # second XLA constant folds of the whole split search at compile
        # time (observed: 6+ s per folded reduce-window in the r4 logs)
        zero_best = _zero_best_direct()
        if cegb_state is not None:
            used_split = cegb_state.used_split
            row_used = cegb_state.row_used
        else:
            used_split = jnp.zeros((f,), bool)
            row_used = jnp.zeros((n, f) if cegb_lazy else (1, 1), bool)
        return GrowState(
            leaf_id=jnp.zeros((n,), jnp.int32),
            leaf_id_sub=jnp.zeros((sub_idx.shape[0],) if use_subset else (1,),
                                  jnp.int32),
            hist=jnp.zeros((1, 1, 1, 1) if blocked
                           else (L, f_loc, num_bins, 3), hist_dtype),
            hist_valid=jnp.zeros((L,), bool),
            leaf_dead=jnp.zeros((L,), bool),
            leaf_sum_g=zf((L,)).at[0].set(root[0]),
            leaf_sum_h=zf((L,)).at[0].set(root[1]),
            leaf_cnt=zf((L,)).at[0].set(root[2]),
            leaf_output=zf((L,)).at[0].set(root_out),
            leaf_depth=jnp.zeros((L,), jnp.int32),
            leaf_min=jnp.full((L,), -F32_MAX, hist_dtype),
            leaf_max=jnp.full((L,), F32_MAX, hist_dtype),
            leaf_lo=jnp.zeros((L, f) if mono_intermediate else (1, 1),
                              jnp.int32),
            leaf_hi=(jnp.broadcast_to(meta.num_bins[None, :] - 1, (L, f))
                     .astype(jnp.int32) if mono_intermediate
                     else jnp.zeros((1, 1), jnp.int32)),
            used_path=jnp.zeros((L, f) if with_interactions else (1, 1), bool),
            used_split=used_split,
            row_used=row_used,
            sib=jnp.full((L,), -1, jnp.int32),
            parent_hist=jnp.zeros((L,), bool),
            done=jnp.bool_(False),
            forced_idx=jnp.int32(0),
            forced_slot=(jnp.full((forced_splits[0].shape[0],), -1,
                                  jnp.int32).at[0].set(0)
                         if forced_splits is not None
                         else jnp.full((1,), -1, jnp.int32)),
            best=zero_best,
            tree=empty_tree(L, cat_words),
            num_leaves=jnp.int32(1),
            rounds=jnp.int32(0),
            rows_streamed=jnp.float32(0.0),
            coll_bytes=jnp.float32(0.0),
            leaves_resolved=jnp.float32(0.0),
            sync_calls=jnp.float32(0.0) if fp_mode or voting else None,
        )

    def active_mask(state: GrowState) -> jax.Array:
        return iota_l < state.num_leaves

    def pending_mask(state: GrowState) -> jax.Array:
        return (active_mask(state) & ~state.hist_valid & ~state.leaf_dead)

    # each forced node consumes one round even when its subtree is dead, so
    # the cap grows by the forced-node count (otherwise a forcedsplits file
    # with more nodes than ~3*L silently truncates growth)
    k_forced = forced_splits[0].shape[0] if forced_splits is not None else 0
    max_rounds = 3 * L + 8 + k_forced

    @jax.named_scope("tile_select")
    def outer_cond(state: GrowState) -> jax.Array:
        # keep looping while there is histogram work or more splits may come;
        # ``done`` is set by a split phase that split nothing
        more = jnp.any(pending_mask(state)) | ~state.done
        return (state.num_leaves < L) & more & (state.rounds < max_rounds)

    def leaf_feature_mask(state: GrowState, round_key) -> jax.Array:
        """Per-(leaf, feature) validity: global column sampling x interaction
        constraints x per-node sampling."""
        fmask = feature_mask
        if fmask.ndim == 1:
            fmask = jnp.broadcast_to(fmask[None, :], (L, f))
        out = fmask.astype(bool)
        if with_interactions:
            # allowed[l] = union of groups containing every used feature of l
            # (col_sampler.hpp interaction filtering): two boolean matmuls
            grp = interaction_groups.astype(jnp.float32)        # [G, F]
            used = state.used_path.astype(jnp.float32)          # [L, F]
            viol = used @ (1.0 - grp).T                          # [L, G] >0 bad
            ok = (viol < 0.5).astype(jnp.float32)
            allowed = (ok @ grp) > 0.5                           # [L, F]
            out = out & allowed
        if use_bynode:
            # per-leaf random subset of ceil(frac * F) features per round
            # (col_sampler.hpp GetByNode resamples per node)
            u = jax.random.uniform(jax.random.fold_in(round_key, 1), (L, f))
            k = jnp.maximum(
                jnp.ceil(bynode_fraction * f).astype(jnp.int32), 1)
            rank = jnp.argsort(jnp.argsort(u, axis=1), axis=1)
            out = out & (rank < k)
        return out

    def cegb_adjust(state: GrowState) -> jax.Array | None:
        """CEGB delta per (leaf, feature) subtracted from stored gains
        (cost_effective_gradient_boosting.hpp:66-84 DetlaGain)."""
        if not cegb_on:
            return None
        delta = (params.cegb_tradeoff * params.cegb_penalty_split
                 * state.leaf_cnt)[:, None]                      # [L, 1]
        delta = jnp.broadcast_to(delta, (L, f))
        if cegb_coupled is not None:
            delta = delta + jnp.where(state.used_split[None, :], 0.0,
                                      params.cegb_tradeoff
                                      * cegb_coupled[None, :])
        if cegb_lazy and cegb_lazy_penalty is not None:
            onehot = jax.nn.one_hot(state.leaf_id, L, dtype=jnp.float32)
            unused = 1.0 - state.row_used.astype(jnp.float32)    # [N, F]
            cnt_unused = onehot.T @ unused                       # [L, F]
            if axis_name is not None:
                cnt_unused = jax.lax.psum(cnt_unused, axis_name)
            delta = delta + (params.cegb_tradeoff
                             * cegb_lazy_penalty[None, :] * cnt_unused)
        return delta

    @jax.named_scope("sparse_hist")
    def combine_sparse(tile, sel, hist_leaf_ids, stats):
        """Histogram planes for the sparse columns: an O(nnz) scatter-add
        of the non-default (row, bin) stream entries plus reconstruction of
        the elided default bin from per-slot totals — the reference's
        most_freq elision + FixHistogram (reference: sparse_bin.hpp
        ConstructHistogram; FixHistogram decl dataset.h:506). Returns the
        full [P, f, B, S] tile with dense planes at their column ids.

        A pass does what depends on the pass, over entries that exist:
        the entries' leaf ids, their slots, and the scatter-add of
        ``sp_stats`` (gathered once a tree, above) into
        ``slot * F_sp * B + sp_cell`` (the cell inside a slot's block is
        the data set's, Dataset._maybe_extract_sparse)."""
        acc = jnp.int32 if quant8 else hist_dtype
        S = stats.shape[1]
        # the entries' leaf ids, gathered from a 16-bit copy of the rows'
        # where the leaf count allows one. Nothing in the source says where
        # the compiler keeps a gather's operand: at 11M rows it leaves the
        # 44 MB int32 vector in HBM (21.6 ms a pass at 1.2M entries), makes
        # the 22 MB copy in fast memory and gathers from it there (9.2 ms,
        # the copy included)
        lid = hist_leaf_ids.astype(jnp.int16 if L <= 2 ** 15 else jnp.int32)
        ent_leaf = lid[sp_rows].astype(jnp.int32)             # [E]
        # leaf -> tile slot; an entry of a leaf outside the tile adds into
        # the parked block P
        if P <= 64:
            # a tile of few slots: one compare a slot, summed in the same
            # fusion (no [E, P] tensor is built). A second gather by entry
            # costs more than the leaf ids' own, 256-entry table or not:
            # 9 of a pass's 37 ms at 1.2M entries and 42 slots. An
            # inactive sel entry (-1) matches no leaf
            hit = ent_leaf[:, None] == sel[None, :]
            slot = P + jnp.sum(
                jnp.where(hit, jnp.arange(P, dtype=jnp.int32) - P, 0), axis=1)
        else:
            # a tile as large as the tree (the untiled backends: P = L) is
            # an O(L) lookup table; inactive sel entries park their writes
            # at index L, which no ent_leaf value ever reads
            slot = jnp.full((L + 1,), P, jnp.int32).at[
                jnp.where(sel >= 0, sel, L)].set(
                    jnp.arange(P, dtype=jnp.int32))[ent_leaf]
        idx = slot * (f_sp * num_bins) + sp_cell
        flat = jnp.zeros(((P + 1) * f_sp * num_bins, S), acc)
        flat = flat.at[idx].add(sp_stats.T.astype(acc))
        sp_t = flat.reshape(P + 1, f_sp, num_bins, S)[:P]
        # per-slot totals: any dense column's plane partitions all rows;
        # without one, reduce the stats by slot directly
        if f_dense > 0:
            totals = tile[:, 0].sum(axis=1)                   # [P, S]
        else:
            eq_all = (hist_leaf_ids[:, None] == sel[None, :])
            totals = jnp.einsum("np,ns->ps", eq_all.astype(acc),
                                stats.astype(acc))
        others = sp_t.sum(axis=2)                             # [P, F_sp, S]
        defm = (jnp.arange(num_bins, dtype=jnp.int32)[None, :]
                == sp_default[:, None])                       # [F_sp, B]
        recon = (totals[:, None, :] - others)[:, :, None, :]
        sp_t = jnp.where(defm[None, :, :, None], recon, sp_t)
        full = jnp.zeros((P, f, num_bins, S), acc)
        full = full.at[:, dense_np].set(tile)
        return full.at[:, sp_np].set(sp_t)

    def tile_pass(state: GrowState) -> GrowState:
        """One histogram pass for a tile of up to P pending leaves, with the
        larger sibling of each computed pair derived by subtraction."""
        sel, chosen, chosen_ok, pending, sibc, has_sib, p_slot = \
            tile_choice(state)
        hist_leaf_ids = state.leaf_id_sub if use_subset else state.leaf_id
        tile, streamed, coll = tile_build(sel, hist_leaf_ids,
                                          hist_leaf_ids.shape[0])
        return tile_store(state, tile, streamed, coll, chosen, chosen_ok,
                          pending, sibc, has_sib, p_slot)

    @jax.named_scope("tile_select")
    def tile_choice(state: GrowState):
        """The pass's leaves: up to P pending slots, the smaller of each
        derivable sibling pair."""
        pending = pending_mask(state)
        sibc = jnp.maximum(state.sib, 0)
        has_sib = state.sib >= 0
        p_slot = jnp.minimum(iota_l, sibc)
        sib_pending = pending[sibc] & has_sib
        if hist_subtraction:
            # compute only the smaller of a derivable pair (reference picks
            # the smaller child, serial_tree_learner.cpp:311-320)
            derivable = (pending & sib_pending & state.parent_hist[p_slot])
            cnt_sib = state.leaf_cnt[sibc]
            is_smaller = ((state.leaf_cnt < cnt_sib)
                          | ((state.leaf_cnt == cnt_sib) & (iota_l < sibc)))
            cand = pending & (~derivable | is_smaller)
        else:
            cand = pending

        # first P candidate slots (ascending slot id)
        order = jnp.argsort(jnp.where(cand, iota_l, L + iota_l))
        chosen = order[:P].astype(jnp.int32)
        chosen_ok = cand[chosen]
        sel = jnp.where(chosen_ok, chosen, -1)
        return sel, chosen, chosen_ok, pending, sibc, has_sib, p_slot

    @jax.named_scope("hist_pass")
    def tile_build(sel, hist_leaf_ids, n_rows):
        """The tile's planes: (tile, rows streamed, collective bytes)."""
        def full_pass():
            t = histogram_tiles(bins_h, stats, hist_leaf_ids, sel,
                                num_bins, method=hist_method,
                                dtype=hist_dtype,
                                binsT=binsT_h, block=hist_block,
                                interpret=hist_interpret)
            return t, jnp.float32(n_rows)

        if f_dense > 0 and compaction_ladder:
            # leaf-partitioned row compaction (see the compaction_ladder
            # docstring): count the tile's rows via an O(L) slot lookup,
            # then dispatch to the smallest precompiled rung that fits
            with jax.named_scope("tile_select"):
                slot_map = jnp.full((L + 1,), P, jnp.int32).at[
                    jnp.where(sel >= 0, sel, L)].set(
                        jnp.arange(P, dtype=jnp.int32))
                in_tile = slot_map[hist_leaf_ids] < P
                n_pend = jnp.sum(in_tile, dtype=jnp.int32)

            # every rung hands histogram_tiles the row-INDEX buffer, which
            # expands it with compact_rows' semantics (same stable order,
            # clamp, -2 leaf fill) for every backend — one rung
            # definition, no branch pair to keep in sync
            def compact_pass(m):
                def fn():
                    from ..ops.histogram import compact_indices
                    idx = compact_indices(in_tile, m)
                    t = histogram_tiles(bins_h, stats, hist_leaf_ids,
                                        sel, num_bins,
                                        method=hist_method,
                                        dtype=hist_dtype,
                                        binsT=binsT_h, block=hist_block,
                                        gather_idx=idx,
                                        interpret=hist_interpret)
                    return t, jnp.float32(m)
                return fn

            # nest largest-first so the OUTERMOST cond tests the smallest
            # rung: if n_pend <= m_small take it, else fall through
            branch = full_pass
            for m in sorted(compaction_ladder, reverse=True):
                branch = (lambda m=m, nxt=branch:
                          jax.lax.cond(n_pend <= m, compact_pass(m),
                                       lambda: nxt()))
            tile, streamed = branch()
        elif f_dense > 0:
            tile, streamed = full_pass()
        else:
            tile = jnp.zeros((P, 0, num_bins, stats.shape[1]),
                             jnp.int32 if quant8 else hist_dtype)
            streamed = jnp.float32(n_rows)    # sparse streams still walk
                                              # the full leaf-id vector
        if f_sp:
            tile = combine_sparse(tile, sel, hist_leaf_ids, stats)
        # collective-volume accounting (GrowAux.coll_bytes): logical
        # histogram payload received per device per pass — a STATIC
        # quantity (tile shapes are static), so the counter costs one
        # scalar add and is independent of row count by construction
        hist_itemsize = 4 if quant8 else (8 if hist_dp else 4)
        tile_bytes = int(np.prod(tile.shape)) * hist_itemsize
        coll = 0.0
        if dp_scatter:
            # the reference DP learner reduce-scatters histograms so each
            # machine receives only its owned features' global sums
            # (data_parallel_tree_learner.cpp:184-186) — 1/D the volume of a
            # full allreduce
            with jax.named_scope("hist_allreduce"):
                tile = jax.lax.psum_scatter(tile, axis_name,
                                            scatter_dimension=1, tiled=True)
            coll = tile_bytes / feature_shards
        elif axis_name is not None and not voting:
            with jax.named_scope("hist_allreduce"):
                tile = jax.lax.psum(tile, axis_name)
            coll = tile_bytes
        if quant8:
            # collectives ran on exact int32 sums; dequantize once here.
            # The product passes the rounding fence so the sibling
            # subtraction below cannot FMA-contract it (ops/split.py
            # _round_fence — keeps q8 ladder-invariant and bit-matched
            # with the fused epilogue's identically-fenced dequant)
            from ..ops.split import _round_fence
            tile = _round_fence(
                tile.astype(hist_dtype) * q_scale[None, None, None, :],
                params)
        return tile, streamed, coll

    @jax.named_scope("hist_pass")
    def tile_store(state, tile, streamed, coll, chosen, chosen_ok, pending,
                   sibc, has_sib, p_slot):
        """Scatter the computed planes into the resident state and derive
        each larger sibling as parent - computed."""
        computed = jnp.zeros((L,), bool).at[chosen].set(chosen_ok)
        buf = jnp.zeros_like(state.hist).at[chosen].set(
            jnp.where(chosen_ok[:, None, None, None], tile, 0.0))
        hist = jnp.where(computed[:, None, None, None], buf, state.hist)
        if hist_subtraction:
            # sibling = parent - computed (parent hist still resident at
            # p_slot in state.hist, untouched by this round's writes)
            derived = (pending & ~computed & computed[sibc]
                       & state.parent_hist[p_slot] & has_sib)
            parent_vals = jnp.take(state.hist, p_slot, axis=0)
            sib_vals = jnp.take(buf, sibc, axis=0)
            hist = jnp.where(derived[:, None, None, None],
                             parent_vals - sib_vals, hist)
            resolved = computed | derived
        else:
            resolved = computed
        return state._replace(
            hist=hist,
            hist_valid=state.hist_valid | resolved,
            parent_hist=state.parent_hist & ~resolved,
            rounds=state.rounds + 1,
            rows_streamed=state.rows_streamed + streamed,
            coll_bytes=state.coll_bytes + jnp.float32(coll),
            leaves_resolved=state.leaves_resolved
            + jnp.sum(resolved, dtype=jnp.float32))

    def tile_pass_fused(state: GrowState) -> GrowState:
        """Frontier-batched histogram pass WITH the fused split epilogue
        (split_fusion): the tile's P slots all hold leaves COMPUTED from
        rows, and each brings its derivable sibling along in a second
        group that reads no rows — its plane is parent - computed, built
        in-pass — so a pass resolves up to 2P leaves. The per-(leaf,
        feature) best-split candidates of both groups come back alongside
        the planes (ops/histogram.py histogram_tiles_with_candidates).
        state.best is updated in place for every resolved leaf, so the
        split phase never re-reads the [L, F, B, S] planes."""
        from ..ops.histogram import histogram_tiles_with_candidates
        from ..ops.pallas_hist import (pack_feature_meta, pack_leaf_aux,
                                       pack_scan_params)
        from ..ops.split import candidates_to_splitinfo
        hist_leaf_ids = state.leaf_id_sub if use_subset else state.leaf_id
        n_rows = hist_leaf_ids.shape[0]
        with jax.named_scope("tile_select"):
            pending = pending_mask(state)
            sibc = jnp.maximum(state.sib, 0)
            p_slot = jnp.minimum(iota_l, sibc)
            if hist_subtraction:
                sib_pending = pending[sibc] & (state.sib >= 0)
                derivable = (pending & sib_pending & state.parent_hist[p_slot])
                cnt_sib = state.leaf_cnt[sibc]
                is_smaller = ((state.leaf_cnt < cnt_sib)
                              | ((state.leaf_cnt == cnt_sib) & (iota_l < sibc)))
                cand = pending & (~derivable | is_smaller)
            else:
                derivable = jnp.zeros((L,), bool)
                cand = pending
            order = jnp.argsort(jnp.where(cand, iota_l, L + iota_l))
            chosen = order[:P].astype(jnp.int32)
            chosen_ok = cand[chosen]
            if compaction_ladder:
                chosen_ok = chosen_ok & tile_fill(
                    jnp.where(chosen_ok, state.leaf_cnt[chosen], 0.0),
                    compaction_ladder)
            sel = jnp.where(chosen_ok, chosen, -1)
            sel_derived = jnp.where(chosen_ok & derivable[chosen],
                                    sibc[chosen].astype(jnp.int32), -1)
            # computed leaves, then derived: the order of the planes and
            # candidates that come back
            sel_all = jnp.concatenate([sel, sel_derived])
            selc = jnp.maximum(sel_all, 0)
            ok = sel_all >= 0

            # parent planes of the pairs: the one plane-sized read the
            # in-pass subtraction needs (the parent's histogram is still
            # resident at the slot the left child inherited)
            parent_planes = jnp.where(
                (sel_derived >= 0)[:, None, None, None],
                jnp.take(state.hist, p_slot[selc[:P]],
                         axis=0).astype(jnp.float32),
                0.0)

            la = pack_leaf_aux(
                state.leaf_sum_g[selc], state.leaf_sum_h[selc],
                state.leaf_cnt[selc], state.leaf_output[selc],
                state.leaf_min[selc].astype(jnp.float32) if with_monotone
                else None,
                state.leaf_max[selc].astype(jnp.float32) if with_monotone
                else None).reshape(2, P, -1)
            fm_pack = pack_feature_meta(meta.num_bins, meta.missing_type,
                                        meta.default_bin, meta.monotone)
            pvec = pack_scan_params(params)

        from ..ops.histogram import (derive_and_scan, epilogue_supported,
                                     histogram_tiles)
        in_kernel = epilogue_supported(hist_method, binsT_h, P,
                                       stats.shape[1], hist_dtype,
                                       hist_interpret)

        def fused_pass(gather_idx, streamed):
            def fn():
                if in_kernel:
                    # the whole epilogue runs IN KERNEL: the candidate
                    # table comes back with the planes, per rung branch
                    tile, tab = histogram_tiles_with_candidates(
                        bins_h, stats, hist_leaf_ids, sel, sel_derived,
                        parent_planes, la, fm_pack, pvec, num_bins,
                        method=hist_method, block=hist_block,
                        dtype=hist_dtype, binsT=binsT_h,
                        gather_idx=gather_idx, interpret=hist_interpret,
                        with_monotone=with_monotone, q_scale=q_scale)
                else:
                    # XLA twin: the rung branches return only the raw
                    # tile; the (identical) derive + scan runs ONCE
                    # after the cond, so it compiles once per grower,
                    # not once per rung
                    tile = histogram_tiles(
                        bins_h, stats, hist_leaf_ids, sel,
                        num_bins, method=hist_method, block=hist_block,
                        dtype=hist_dtype, binsT=binsT_h,
                        gather_idx=gather_idx, interpret=hist_interpret)
                    tab = None
                return tile, tab, jnp.float32(streamed)
            return fn

        if f_dense > 0 and compaction_ladder:
            with jax.named_scope("tile_select"):
                slot_map = jnp.full((L + 1,), P, jnp.int32).at[
                    jnp.where(sel >= 0, sel, L)].set(
                        jnp.arange(P, dtype=jnp.int32))
                in_tile = slot_map[hist_leaf_ids] < P
                n_pend = jnp.sum(in_tile, dtype=jnp.int32)

            def compact_pass(m):
                def fn():
                    from ..ops.histogram import compact_indices
                    idx = compact_indices(in_tile, m)
                    return fused_pass(idx, m)()
                return fn

            branch = fused_pass(None, n_rows)
            for m in sorted(compaction_ladder, reverse=True):
                branch = (lambda m=m, nxt=branch:
                          jax.lax.cond(n_pend <= m, compact_pass(m),
                                       lambda: nxt()))
            with jax.named_scope("hist_pass"):
                tile, tab, streamed = branch()
        else:
            tile, tab, streamed = fused_pass(None, n_rows)()
        if not in_kernel:
            tile, tab = derive_and_scan(
                tile, sel_derived, parent_planes, la, fm_pack, pvec,
                q8=quant8, q_scale=q_scale, with_monotone=with_monotone)

        with jax.named_scope("split_search"):
            # scatter planes (computed AND derived — both stay resident as
            # the next level's parents) and the per-leaf bests
            slots = jnp.where(ok, sel_all, L)
            buf = jnp.zeros_like(state.hist).at[slots].set(
                jnp.where(ok[:, None, None, None], tile.astype(hist_dtype),
                          0.0), mode="drop")
            resolved = jnp.zeros((L,), bool).at[slots].set(ok, mode="drop")
            hist = jnp.where(resolved[:, None, None, None], buf, state.hist)

            round_key = jax.random.fold_in(rng_key, state.rounds)
            fmask_sel = leaf_feature_mask(state, round_key)[selc]
            info = candidates_to_splitinfo(
                tab, state.leaf_sum_g[selc], state.leaf_sum_h[selc],
                state.leaf_cnt[selc], state.leaf_output[selc],
                state.leaf_depth[selc], meta, params, fmask_sel, max_depth,
                cat_words, with_monotone=with_monotone,
                leaf_min=(state.leaf_min[selc].astype(jnp.float32)
                          if with_monotone else None),
                leaf_max=(state.leaf_max[selc].astype(jnp.float32)
                          if with_monotone else None))

            def scat(cur, new):
                return cur.at[slots].set(new.astype(cur.dtype), mode="drop")

            new_best = SplitInfo(*(scat(c, nb)
                                   for c, nb in zip(state.best, info)))
            return state._replace(
                hist=hist, best=new_best,
                hist_valid=state.hist_valid | resolved,
                parent_hist=state.parent_hist & ~resolved,
                rounds=state.rounds + 1,
                rows_streamed=state.rows_streamed + streamed,
                leaves_resolved=state.leaves_resolved
                + jnp.sum(ok, dtype=jnp.float32))

    @jax.named_scope("apply_split")
    def intermediate_bounds(state: GrowState) -> GrowState:
        """Exact per-leaf output bounds from ALL current leaf outputs and
        the leaf region boxes — the vectorized re-derivation of the
        reference's intermediate-mode constraint maintenance
        (monotone_constraints.hpp:514-698 IntermediateLeafConstraints: its
        GoUp/GoDown contiguity walk incrementally maintains the same
        pairwise relations this computes from scratch each phase). A pair
        (l, l') constrains l when their boxes overlap in every feature
        except a monotone one where l' lies strictly on one side."""
        out = state.leaf_output.astype(jnp.float32)
        act = active_mask(state)
        lo, hi = state.leaf_lo, state.leaf_hi               # [L, F]
        # overlap COUNT over all features reduces without materializing the
        # [L, L, F] tensor; the per-feature pair masks are only needed for
        # the (static, usually few) monotone-constrained features
        cnt = jnp.sum((lo[:, None, :] <= hi[None, :, :])
                      & (lo[None, :, :] <= hi[:, None, :]),
                      axis=2, dtype=jnp.int32)               # [L, L']
        mf = jnp.asarray(mono_features, jnp.int32)           # [Fm] static
        lo_m, hi_m = lo[:, mf], hi[:, mf]                    # [L, Fm]
        ovl_m = ((lo_m[:, None, :] <= hi_m[None, :, :])
                 & (lo_m[None, :, :] <= hi_m[:, None, :]))
        except_f = (cnt[:, :, None] - ovl_m.astype(jnp.int32)) == (f - 1)
        below = hi_m[None, :, :] < lo_m[:, None, :]          # l' below l
        above = lo_m[None, :, :] > hi_m[:, None, :]
        mono = meta.monotone[mf].astype(jnp.int32)
        up = (mono > 0)[None, None, :]
        dn = (mono < 0)[None, None, :]
        pair_ok = (act[:, None, None] & act[None, :, None] & except_f)
        lb_mask = jnp.any(pair_ok & ((up & below) | (dn & above)), axis=2)
        ub_mask = jnp.any(pair_ok & ((up & above) | (dn & below)), axis=2)
        lb = jnp.max(jnp.where(lb_mask, out[None, :], -F32_MAX), axis=1)
        ub = jnp.min(jnp.where(ub_mask, out[None, :], F32_MAX), axis=1)
        return state._replace(leaf_min=lb.astype(state.leaf_min.dtype),
                              leaf_max=ub.astype(state.leaf_max.dtype))

    def adv_bounds_sliced(state: GrowState):
        """Advanced per-threshold child bounds, built over the GLOBAL
        feature axis (leaf boxes are global state) then sliced to this
        shard's owned feature window like every other per-feature input."""
        adv = advanced_child_bounds(
            state.leaf_lo, state.leaf_hi, state.leaf_output,
            active_mask(state), meta.monotone, num_bins, mono_features)
        if fp_mode:
            adv = tuple(jax.lax.dynamic_slice_in_dim(a, off, f_loc, 1)
                        for a in adv)
        return adv

    @jax.named_scope("split_search")
    def split_search(state: GrowState) -> GrowState:
        """Best-split search over all resident histograms -> state.best.
        Under ``split_fusion`` the search already happened in the tile
        passes' epilogues (state.best is incrementally maintained), so
        this reduces to the round bookkeeping."""
        if split_fusion:
            return state._replace(rounds=state.rounds + 1)
        adv = None
        if mono_intermediate:
            state = intermediate_bounds(state)
            if mono_advanced:
                adv = adv_bounds_sliced(state)
        round_key = jax.random.fold_in(rng_key, state.rounds)
        fmask = slice_f(leaf_feature_mask(state, round_key))
        rand_bin = None
        if extra_trees:
            # one random threshold per (leaf, feature) per search
            # (feature_histogram.hpp USE_RAND rand.NextInt); drawn over the
            # GLOBAL feature space so all shards agree, then sliced
            nbm = jnp.maximum(meta.num_bins - 2, 1)
            u = jax.random.uniform(jax.random.fold_in(round_key, 2), (L, f))
            rand_bin = slice_f((u * nbm[None, :]).astype(jnp.int32))

        search_hist = state.hist
        search_fmask = fmask
        coll = 0.0
        if voting:
            # PV-tree election (voting_parallel_tree_learner.cpp:137-182):
            # local per-feature gains from LOCAL histograms and local leaf
            # sums (min_data guards scaled by 1/D, :62-64) -> local top-k
            # vote -> global top-2k electorate -> psum only elected columns
            lsum = jnp.sum(state.hist[:, 0, :, :], axis=1)     # [L, 3] local
            ndev = jax.lax.psum(jnp.float32(1.0), axis_name)
            params_vote = params._replace(
                min_data_in_leaf=params.min_data_in_leaf / ndev,
                min_sum_hessian_in_leaf=params.min_sum_hessian_in_leaf / ndev)
            _, fgain = find_best_splits(
                state.hist, lsum[:, 0], lsum[:, 1], lsum[:, 2],
                state.leaf_output, state.leaf_depth, meta_s, params_vote,
                fmask, max_depth, with_categorical=with_categorical,
                cat_words=cat_words, rand_bin=rand_bin, bundle=bundle_s,
                return_feature_gains=True)
            kk = min(vote_top_k, f)
            k2 = min(2 * vote_top_k, f)
            rank_local = jnp.argsort(jnp.argsort(-fgain, axis=1), axis=1)
            local_top = (rank_local < kk) & jnp.isfinite(fgain)
            with jax.named_scope("split_sync"):
                votes = jax.lax.psum(local_top.astype(jnp.float32),
                                     axis_name)
            # elect top 2k by vote count, ties to the lower feature index
            key = votes * (f + 1) - jnp.arange(f, dtype=jnp.float32)[None, :]
            el_idx = jnp.argsort(-key, axis=1)[:, :k2].astype(jnp.int32)
            el_onehot = (el_idx[:, :, None]
                         == jnp.arange(f, dtype=jnp.int32)[None, None, :]
                         ).astype(jnp.float32)                  # [L, 2k, F]
            # HIGHEST precision: the selector is exact 0/1 but default TPU
            # matmul precision would bf16-round the histogram values
            hist_el = jnp.einsum("lkf,lfbs->lkbs", el_onehot, state.hist,
                                 precision=jax.lax.Precision.HIGHEST)
            with jax.named_scope("hist_allreduce"):
                hist_el = jax.lax.psum(hist_el, axis_name)      # [L, 2k, B, S]
            search_hist = jnp.einsum("lkf,lkbs->lfbs", el_onehot, hist_el,
                                     precision=jax.lax.Precision.HIGHEST)
            elected = jnp.sum(el_onehot, axis=1) > 0.5          # [L, F]
            fm2 = fmask if fmask.ndim == 2 else jnp.broadcast_to(
                fmask[None, :], (L, f))
            search_fmask = (fm2.astype(bool) & elected).astype(jnp.float32)
            # GlobalVoting communication: the vote tally allreduce plus the
            # elected columns' histogram sum (CopyLocalHistogram analog) —
            # the only histogram-plane collectives in the voting learner
            hist_itemsize = 8 if hist_dp else 4
            coll = (L * f * 4
                    + L * k2 * num_bins * int(state.hist.shape[3])
                    * hist_itemsize)

        best = find_best_splits(
            search_hist, state.leaf_sum_g, state.leaf_sum_h,
            state.leaf_cnt, state.leaf_output,
            state.leaf_depth, meta_s, params,
            search_fmask, max_depth,
            with_categorical=with_categorical, cat_words=cat_words,
            leaf_min=state.leaf_min if with_monotone else None,
            leaf_max=state.leaf_max if with_monotone else None,
            adv_bounds=adv,
            gain_adjust=slice_f(cegb_adjust(state)),
            rand_bin=rand_bin, bundle=bundle_s)
        if fp_mode:
            # local feature index -> global, then allreduce-argmax of the
            # per-leaf bests (reference: SyncUpGlobalBestSplit,
            # parallel_tree_learner.h:191-214)
            from ..ops.split import sync_best_splits
            best = best._replace(feature=best.feature + off)
            best = sync_best_splits(best, feature_axis_name)
        return state._replace(best=best, rounds=state.rounds + 1,
                              coll_bytes=state.coll_bytes
                              + jnp.float32(coll),
                              sync_calls=_one_more(state.sync_calls))

    @jax.named_scope("apply_split")
    def split_apply(state: GrowState) -> GrowState:
        """Apply every available split from state.best (gain order via the
        inner while_loop; one split under ``exact``)."""
        num_leaves_before = state.num_leaves
        gain_eff = jnp.where(active_mask(state) & state.hist_valid
                             & ~state.leaf_dead, state.best.gain, NEG_INF)
        state = apply_splits(state, gain_eff, dict(
            with_monotone=with_monotone,
            with_interactions=with_interactions,
            cegb_lazy=cegb_lazy,
            mono_intermediate=mono_intermediate,
            sub_bins=sub_bins, sub_binsT=sub_binsT, sp=sp_pack,
            **route_kw))
        return state._replace(done=state.num_leaves == num_leaves_before)

    def split_phase(state: GrowState) -> GrowState:
        return split_apply(split_search(state))

    @jax.named_scope("apply_split")
    def forced_phase(state: GrowState) -> GrowState:
        """Apply one forced split (reference: SerialTreeLearner::ForceSplits,
        serial_tree_learner.cpp:450-562): the node's (feature, threshold)
        goes through the regular split machinery with the candidate set
        restricted to the forced bin and min_gain disabled, so sums and
        missing/default semantics are exact; a forced split its constraints
        reject is skipped along with its whole subtree."""
        adv = None
        if mono_intermediate:
            state = intermediate_bounds(state)
            if mono_advanced:
                adv = adv_bounds_sliced(state)
        ff, ft, fl, fr = forced_splits
        k_idx = state.forced_idx
        l = state.forced_slot[k_idx]
        lsafe = jnp.maximum(l, 0)
        # ff holds GLOBAL feature indices; under feature slicing only the
        # owning shard's mask lights up and the result syncs below
        fidx = jnp.arange(f_loc, dtype=jnp.int32)
        if fp_mode:
            fidx = fidx + off
        fmask_forced = (fidx == ff[k_idx]).astype(jnp.float32)
        # forced means forced: the reference gathers the threshold's sums
        # directly (GatherInfoForThreshold) without min_gain/min_data
        # screening, aborting only on gain < 0
        params_forced = params._replace(
            min_gain_to_split=jnp.float32(-1e30),
            min_data_in_leaf=jnp.float32(0.0),
            min_sum_hessian_in_leaf=jnp.float32(0.0))
        best = find_best_splits(
            state.hist, state.leaf_sum_g, state.leaf_sum_h,
            state.leaf_cnt, state.leaf_output, state.leaf_depth,
            meta_s, params_forced, fmask_forced, max_depth,
            with_categorical=False, cat_words=cat_words,
            leaf_min=state.leaf_min if with_monotone else None,
            leaf_max=state.leaf_max if with_monotone else None,
            adv_bounds=adv,
            rand_bin=jnp.full((L, f_loc), ft[k_idx], jnp.int32),
            bundle=bundle_s)
        if fp_mode:
            from ..ops.split import sync_best_splits
            best = best._replace(feature=best.feature + off)
            best = sync_best_splits(best, feature_axis_name)
        ok = ((l >= 0) & (state.num_leaves < L)
              & state.hist_valid[lsafe] & ~state.leaf_dead[lsafe]
              & jnp.isfinite(best.gain[lsafe]))
        new_leaf = state.num_leaves
        state = state._replace(best=best, rounds=state.rounds + 1,
                               sync_calls=_one_more(state.sync_calls))

        def do_split(st):
            ge = jnp.where(iota_l == lsafe, 1.0, NEG_INF)
            st2, _ = _apply_split(st, bins, binsT, missing_bin, ge, meta,
                                  with_monotone=with_monotone,
                                  with_interactions=with_interactions,
                                  cegb_lazy=cegb_lazy,
                                  mono_intermediate=mono_intermediate,
                                  sub_bins=sub_bins, sub_binsT=sub_binsT,
                                  sp=sp_pack, **route_kw)
            return st2

        state = jax.lax.cond(ok, do_split, lambda s: s, state)
        # children inherit slots (left keeps the split slot, right takes the
        # new one); a skipped node kills its subtree (slot -1)
        slot = state.forced_slot
        flk, frk = fl[k_idx], fr[k_idx]
        slot = slot.at[jnp.maximum(flk, 0)].set(
            jnp.where(flk >= 0, jnp.where(ok, lsafe, -1),
                      slot[jnp.maximum(flk, 0)]))
        slot = slot.at[jnp.maximum(frk, 0)].set(
            jnp.where(frk >= 0, jnp.where(ok, new_leaf, -1),
                      slot[jnp.maximum(frk, 0)]))
        return state._replace(forced_idx=k_idx + 1, forced_slot=slot,
                              done=jnp.bool_(False))

    def merge_best(a: SplitInfo, b: SplitInfo) -> SplitInfo:
        """Cross-block best merge: strictly greater gain replaces, ties keep
        the earlier block = the lower feature index (the reference's
        cross-feature tie rule, serial_tree_learner.cpp:374-448)."""
        take = b.gain > a.gain

        def w(x, y):
            m = take if x.ndim == 1 else take[:, None]
            return jnp.where(m, y, x)

        return SplitInfo(*(w(x, y) for x, y in zip(a, b)))

    @jax.named_scope("split_search")
    def blocked_pass(state: GrowState) -> GrowState:
        """Histogram + search for a tile of pending leaves, one feature
        block at a time; only the winning SplitInfo survives the block."""
        with jax.named_scope("tile_select"):
            pending = pending_mask(state)
            order = jnp.argsort(jnp.where(pending, iota_l, L + iota_l))
            chosen = order[:P].astype(jnp.int32)
            chosen_ok = pending[chosen]
            sel = jnp.where(chosen_ok, chosen, -1)

        round_key = jax.random.fold_in(rng_key, state.rounds)
        fmask_sel = leaf_feature_mask(state, round_key)[chosen] \
            .astype(jnp.float32)                              # [P, f]
        rand_bin_sel = None
        if extra_trees:
            nbm = jnp.maximum(meta.num_bins - 2, 1)
            u = jax.random.uniform(jax.random.fold_in(round_key, 2), (L, f))
            rand_bin_sel = (u * nbm[None, :]).astype(jnp.int32)[chosen]

        sum_g = state.leaf_sum_g[chosen]
        sum_h = state.leaf_sum_h[chosen]
        cnt = state.leaf_cnt[chosen]
        outp = state.leaf_output[chosen]
        depth = state.leaf_depth[chosen]
        lmin = state.leaf_min[chosen] if with_monotone else None
        lmax = state.leaf_max[chosen] if with_monotone else None

        best_t = None
        for bi in range(-(-f // feature_block)):
            s_, e_ = bi * feature_block, min((bi + 1) * feature_block, f)
            tile = histogram_tiles(
                bins[:, s_:e_], stats, state.leaf_id, sel, num_bins,
                method=hist_method, dtype=hist_dtype,
                binsT=binsT[s_:e_] if binsT is not None else None,
                block=hist_block, interpret=hist_interpret)
            mb = FeatureMeta(*(a[s_:e_] for a in meta))
            bundle_b = (type(bundle_meta)(*(a[s_:e_] for a in bundle_meta))
                        if bundle_meta is not None else None)
            bb = find_best_splits(
                tile, sum_g, sum_h, cnt, outp, depth, mb, params,
                fmask_sel[:, s_:e_], max_depth,
                with_categorical=with_categorical, cat_words=cat_words,
                leaf_min=lmin, leaf_max=lmax,
                rand_bin=(rand_bin_sel[:, s_:e_]
                          if rand_bin_sel is not None else None),
                bundle=bundle_b)
            bb = bb._replace(feature=bb.feature + s_)
            best_t = bb if best_t is None else merge_best(best_t, bb)

        def scat(cur, new):
            m = chosen_ok if new.ndim == 1 else chosen_ok[:, None]
            return cur.at[chosen].set(jnp.where(m, new, cur[chosen]))

        new_best = SplitInfo(*(scat(c, nb)
                               for c, nb in zip(state.best, best_t)))
        return state._replace(
            best=new_best,
            hist_valid=state.hist_valid.at[chosen].set(
                state.hist_valid[chosen] | chosen_ok),
            rounds=state.rounds + 1,
            rows_streamed=state.rows_streamed
            + jnp.float32(n * (-(-f // feature_block))),
            leaves_resolved=state.leaves_resolved
            + jnp.sum(chosen_ok, dtype=jnp.float32))

    def apply_splits(state: GrowState, gain_eff: jax.Array,
                     apply_kw: dict) -> GrowState:
        """Shared split-application loop: strict best-first (one split per
        phase) under ``exact``, otherwise every positive-gain split this
        round via an inner while_loop."""
        if exact:
            def do_split(carry):
                st, ge = carry
                return _apply_split(st, bins, binsT, missing_bin, ge, meta,
                                    **apply_kw)

            state, _ = jax.lax.cond(
                (state.num_leaves < L) & (jnp.max(gain_eff) > 0.0),
                do_split, lambda c: c, (state, gain_eff))
        else:
            def inner_cond(carry):
                st, ge = carry
                return (st.num_leaves < L) & (jnp.max(ge) > 0.0)

            def inner_body(carry):
                st, ge = carry
                return _apply_split(st, bins, binsT, missing_bin, ge, meta,
                                    **apply_kw)

            # the leaf ids ride the loop as [1, N]: the shape, and on a TPU
            # the tiled layout, of the column a split slices out of binsT,
            # so a split is ONE fused pass over the rows. As [N] the sliced
            # column is first relaid out to it, a pass of its own that cost
            # twice the rest (0.58 of 0.70 ms a split at 10.5M rows)
            def leaf_ids_as(st, lead):
                return st._replace(
                    leaf_id=st.leaf_id.reshape(lead + (-1,)),
                    leaf_id_sub=st.leaf_id_sub.reshape(lead + (-1,)))

            state, _ = jax.lax.while_loop(
                inner_cond, inner_body,
                (leaf_ids_as(state, (1,)), gain_eff))
            state = leaf_ids_as(state, ())
        return state

    @jax.named_scope("apply_split")
    def split_phase_blocked(state: GrowState) -> GrowState:
        """Apply splits from the STORED per-leaf bests (no re-search — the
        histograms are gone). Valid because a leaf's best is invariant
        until it is split: basic-monotone bounds and interaction masks
        only change for the split leaf's children, which are re-searched
        with fresh histograms anyway."""
        num_leaves_before = state.num_leaves
        state = state._replace(rounds=state.rounds + 1)
        gain_eff = jnp.where(active_mask(state) & state.hist_valid
                             & ~state.leaf_dead, state.best.gain, NEG_INF)
        state = apply_splits(state, gain_eff, dict(
            with_monotone=with_monotone,
            with_interactions=with_interactions,
            cegb_lazy=False, mono_intermediate=False,
            sub_bins=None, sub_binsT=None, sp=sp_pack, **route_kw))
        return state._replace(done=state.num_leaves == num_leaves_before)

    hist_phase = tile_pass_fused if split_fusion else tile_pass

    @jax.named_scope("tile_select")
    def dead_guard(state: GrowState) -> GrowState:
        # BeforeFindBestSplit guards (serial_tree_learner.cpp:282-322): a
        # leaf failing the 2x min-data/min-hessian check is never
        # histogrammed and never splittable
        active = active_mask(state)
        guard = ((state.leaf_cnt >= 2.0 * params.min_data_in_leaf)
                 & (state.leaf_sum_h >= 2.0 * params.min_sum_hessian_in_leaf))
        newly_dead = active & ~state.hist_valid & ~state.leaf_dead & ~guard
        return state._replace(leaf_dead=state.leaf_dead | newly_dead)

    def outer_body(state: GrowState) -> GrowState:
        state = dead_guard(state)
        with jax.named_scope("tile_select"):
            any_pending = jnp.any(pending_mask(state))
        if blocked:
            return jax.lax.cond(any_pending, blocked_pass,
                                split_phase_blocked, state)
        if forced_splits is not None:
            k_total = forced_splits[0].shape[0]

            def no_pending(st):
                return jax.lax.cond(st.forced_idx < k_total,
                                    forced_phase, split_phase, st)

            return jax.lax.cond(any_pending, hist_phase, no_pending, state)
        return jax.lax.cond(any_pending, hist_phase, split_phase, state)

    @jax.named_scope("finalize_tree")
    def finalize(state: GrowState):
        rows_streamed = state.rows_streamed
        if axis_name is not None:
            # global rows per tree across the row shards (each shard
            # counted only its local rows)
            rows_streamed = jax.lax.psum(rows_streamed, axis_name)
        # histogram-plane numerics sentinel (see GrowAux.sentinel): judged
        # on the FINAL grow state, in-program — the per-leaf grad/hess
        # sums and outputs integrate every histogram the tree consumed (a
        # NaN entering any pass lands in some leaf's sums), and the
        # resident histogram state is checked directly where it exists
        # (the blocked mode holds only a dummy). A constant 0 when the
        # static is off, so the disarmed program is unchanged.
        if numerics_sentinels:
            bad = (jnp.any(~jnp.isfinite(state.leaf_sum_g))
                   | jnp.any(~jnp.isfinite(state.leaf_sum_h))
                   | jnp.any(~jnp.isfinite(state.leaf_output)))
            if not blocked:
                bad = bad | jnp.any(~jnp.isfinite(state.hist))
            sentinel = bad.astype(jnp.float32)
            if axis_name is not None:
                sentinel = jax.lax.psum(sentinel, axis_name)
        else:
            sentinel = jnp.float32(0.0)
        # coll_bytes is already the per-device receive volume and
        # identical on every shard — no psum (a psum would scale it by
        # the mesh size)
        return state.tree, state.leaf_id, GrowAux(
            state.used_split, state.row_used, rows_streamed,
            state.coll_bytes, sentinel, state.leaves_resolved,
            state.sync_calls)

    return {"init_state": init_state, "dead_guard": dead_guard,
            "outer_cond": outer_cond, "outer_body": outer_body,
            "hist_phase": hist_phase, "split_search": split_search,
            "split_apply": split_apply, "pending_mask": pending_mask,
            "finalize": finalize, "phased_ok": (not blocked
                                               and forced_splits is None)}


# dynamic (array) grow kwargs, in the canonical order the phased programs
# receive them as one tuple operand
_GROW_DYN = ("interaction_groups", "cegb_coupled", "cegb_lazy_penalty",
             "cegb_state", "bynode_fraction", "rng_key", "binsT", "sub_idx",
             "sub_bins", "sub_binsT", "bundle_meta", "forced_splits",
             "sp_rows", "sp_cell", "sp_default")


@functools.partial(jax.jit, static_argnames=_GROW_STATICS)
def grow_tree(bins: jax.Array, grad: jax.Array, hess: jax.Array,
              sample_mask: jax.Array, meta: FeatureMeta, params: SplitParams,
              feature_mask: jax.Array, missing_bin: jax.Array, *,
              max_leaves: int, num_bins: int, max_depth: int = -1,
              hist_method: str = "scatter",
              exact: bool = False,
              with_categorical: bool = False,
              with_monotone: bool = False,
              mono_mode: str = "basic",
              mono_features: tuple = (),
              with_interactions: bool = False,
              interaction_groups: jax.Array | None = None,
              cegb_mode: str = "off",
              cegb_coupled: jax.Array | None = None,
              cegb_lazy_penalty: jax.Array | None = None,
              cegb_state: GrowAux | None = None,
              extra_trees: bool = False,
              use_bynode: bool = False,
              bynode_fraction: jax.Array | None = None,
              rng_key: jax.Array | None = None,
              axis_name: str | None = None,
              binsT: jax.Array | None = None,
              sub_idx: jax.Array | None = None,
              sub_bins: jax.Array | None = None,
              sub_binsT: jax.Array | None = None,
              tile_leaves: int = 0,
              hist_block: int = 0,
              hist_subtraction: bool = True,
              feature_block: int = 0,
              feature_axis_name: str | None = None,
              feature_shards: int = 1,
              voting: bool = False,
              vote_top_k: int = 20,
              bundle_meta=None,
              forced_splits=None,
              hist_dp: bool = False,
              sp_cols: tuple = (),
              sp_offsets: tuple = (),
              sp_rows: jax.Array | None = None,
              sp_cell: jax.Array | None = None,
              sp_default: jax.Array | None = None,
              compaction_ladder: tuple = (),
              hist_interpret: bool = False,
              numerics_sentinels: bool = False,
              split_fusion: bool = False,
              ) -> Tuple[TreeArrays, jax.Array, GrowAux]:
    """Grow one tree as ONE jitted program (see _grower_fns for the full
    argument contract). Returns (tree arrays, per-row leaf index, aux)."""
    fns = _grower_fns(
        bins, grad, hess, sample_mask, meta, params, feature_mask,
        missing_bin, max_leaves=max_leaves, num_bins=num_bins,
        max_depth=max_depth, hist_method=hist_method, exact=exact,
        with_categorical=with_categorical, with_monotone=with_monotone,
        mono_mode=mono_mode, mono_features=mono_features,
        with_interactions=with_interactions,
        interaction_groups=interaction_groups, cegb_mode=cegb_mode,
        cegb_coupled=cegb_coupled, cegb_lazy_penalty=cegb_lazy_penalty,
        cegb_state=cegb_state, extra_trees=extra_trees,
        use_bynode=use_bynode, bynode_fraction=bynode_fraction,
        rng_key=rng_key, axis_name=axis_name, binsT=binsT, sub_idx=sub_idx,
        sub_bins=sub_bins, sub_binsT=sub_binsT, tile_leaves=tile_leaves,
        hist_block=hist_block, hist_subtraction=hist_subtraction,
        feature_block=feature_block, feature_axis_name=feature_axis_name,
        feature_shards=feature_shards, voting=voting, vote_top_k=vote_top_k,
        bundle_meta=bundle_meta, forced_splits=forced_splits,
        hist_dp=hist_dp, sp_cols=sp_cols, sp_offsets=sp_offsets,
        sp_rows=sp_rows, sp_cell=sp_cell, sp_default=sp_default,
        compaction_ladder=compaction_ladder,
        hist_interpret=hist_interpret,
        numerics_sentinels=numerics_sentinels, split_fusion=split_fusion)
    state = jax.lax.while_loop(fns["outer_cond"], fns["outer_body"],
                               fns["init_state"]())
    return fns["finalize"](state)


@functools.lru_cache(maxsize=8)
def _phased_programs(statics_items: tuple):
    """Per-config jitted phase programs for the host-driven grower (the
    hist_pass / split_search / apply_split TIMETAG sub-scopes). Statics
    fold in via this cache's key; arrays arrive as explicit operands, so
    no dataset-sized closure constants reach XLA (the PR 10 lesson).

    Each per-round program also returns (any-pending, continue) flags
    computed on the post-phase state with the next round's dead-guard
    already folded in (idempotent — the guard depends only on leaf
    aggregates), so the host's branch decisions reproduce the monolithic
    while_loop's guard-then-branch order bit-exactly."""
    skw = dict(statics_items)

    def _fns(arrs, dyn):
        bins, grad, hess, sample_mask, meta, params, fmask, missing_bin = \
            arrs
        return _grower_fns(bins, grad, hess, sample_mask, meta, params,
                           fmask, missing_bin,
                           **dict(zip(_GROW_DYN, dyn)), **skw)

    def init(arrs, dyn):
        fns = _fns(arrs, dyn)
        state = fns["dead_guard"](fns["init_state"]())
        return (state, jnp.any(fns["pending_mask"](state)),
                fns["outer_cond"](state))

    def mk(phase):
        def run(state, arrs, dyn):
            fns = _fns(arrs, dyn)
            if phase == "tile":
                state = fns["dead_guard"](fns["hist_phase"](state))
            elif phase == "search":
                state = fns["split_search"](state)
            else:
                state = fns["dead_guard"](fns["split_apply"](state))
            return (state, jnp.any(fns["pending_mask"](state)),
                    fns["outer_cond"](state))
        return jax.jit(run)

    def fin(state, arrs, dyn):
        return _fns(arrs, dyn)["finalize"](state)

    return {"init": jax.jit(init), "tile": mk("tile"),
            "search": mk("search"), "apply": mk("apply"),
            "finalize": jax.jit(fin)}


def grow_tree_phased(bins, grad, hess, sample_mask, meta, params,
                     feature_mask, missing_bin, **kw):
    """Host-driven grow loop with per-phase TIMETAG scopes.

    The SAME _grower_fns phases as grow_tree, but each round is its own
    compiled dispatch so ``hist_pass`` / ``split_search`` / ``apply_split``
    wall time is attributable per phase (bench.py's sub-scope probe; the
    reference's per-phase USE_TIMETAG table). The host fetches two
    booleans per ROUND — with frontier batching that is one histogram
    launch per frontier level, not per leaf (the dispatch-count
    regression pins it). Bit-identical trees to grow_tree; serial
    non-blocked non-forced configurations only (callers fall back to
    grow_tree otherwise).
    """
    from ..utils import profiling
    statics = tuple(sorted((k, v) for k, v in kw.items()
                           if k in _GROW_STATICS))
    dyn = tuple(kw.get(k) for k in _GROW_DYN)
    unknown = set(kw) - set(_GROW_STATICS) - set(_GROW_DYN)
    assert not unknown, f"grow_tree_phased: unsupported kwargs {unknown}"
    assert not kw.get("axis_name") and not kw.get("feature_axis_name"), (
        "grow_tree_phased is serial-only")
    assert kw.get("forced_splits") is None and not kw.get("feature_block"), (
        "grow_tree_phased: forced splits / blocked mode unsupported")
    arrs = (bins, grad, hess, sample_mask, meta, params, feature_mask,
            missing_bin)
    progs = _phased_programs(statics)
    state, pending, cont = progs["init"](arrs, dyn)
    pending, cont = bool(pending), bool(cont)
    while cont:
        if pending:
            with profiling.timer("hist_pass"):
                state, p2, c2 = progs["tile"](state, arrs, dyn)
                pending, cont = bool(p2), bool(c2)
        else:
            with profiling.timer("split_search"):
                state, _, _ = progs["search"](state, arrs, dyn)
                state.best.gain.block_until_ready()
            with profiling.timer("apply_split"):
                state, p2, c2 = progs["apply"](state, arrs, dyn)
                pending, cont = bool(p2), bool(c2)
    return progs["finalize"](state, arrs, dyn)
