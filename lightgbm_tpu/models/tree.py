"""Tree model object: fixed-capacity array representation + traversal.

TPU-native analog of the reference's flat-array binary tree
(reference: include/LightGBM/tree.h:62-231, src/io/tree.cpp). A tree with
leaf capacity L has L-1 internal-node slots and L leaf slots; child links
follow the reference's encoding: ``child >= 0`` is an internal node index,
``child < 0`` is ``~leaf_index`` (tree.h ``left_child_``/``right_child_``).

Thresholds are stored in BIN space for exact device traversal over the binned
matrix (the training-data path), plus real-valued thresholds filled from the
bin mappers for raw-feature traversal (reference: Tree::RealThreshold via
``BinMapper::BinToValue``). Missing-value routing mirrors
``Tree::NumericalDecision`` (tree.h:133+, decision_type missing flags).
"""

from __future__ import annotations

import copy
from typing import List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class TreeArrays(NamedTuple):
    """Single tree as device arrays. Internal-node arrays have shape
    [L-1], leaf arrays [L]; ``num_leaves`` is the used count."""
    num_leaves: jax.Array        # int32 scalar (actual leaves used)
    node_feature: jax.Array      # int32 [L-1] inner feature index
    node_threshold_bin: jax.Array  # int32 [L-1]
    node_default_left: jax.Array   # bool [L-1]
    node_left: jax.Array         # int32 [L-1]  (>=0 node, <0 = ~leaf)
    node_right: jax.Array        # int32 [L-1]
    node_gain: jax.Array         # f32 [L-1] split gain
    node_value: jax.Array        # f32 [L-1] internal output (pre-shrinkage)
    node_weight: jax.Array       # f32 [L-1] sum_hessian at node
    node_count: jax.Array        # f32 [L-1]
    node_cat: jax.Array          # bool [L-1] categorical split flag
    node_cat_bitset: jax.Array   # uint32 [L-1, CAT_WORDS] bin membership (left side)
    node_seg_lo: jax.Array       # int32 [L-1] EFB bundle segment start (-1 = regular)
    node_seg_hi: jax.Array       # int32 [L-1] EFB bundle segment end (inclusive)
    leaf_value: jax.Array        # f32 [L] (shrinkage already applied by booster)
    leaf_weight: jax.Array       # f32 [L] sum_hessian
    leaf_count: jax.Array        # f32 [L]
    leaf_depth: jax.Array        # int32 [L]
    leaf_parent: jax.Array       # int32 [L]
    shrinkage: jax.Array         # f32 scalar


def empty_tree(max_leaves: int, cat_words: int = 8) -> TreeArrays:
    li, lf = max_leaves - 1, max_leaves
    i32 = lambda n, v=0: jnp.full((n,), v, dtype=jnp.int32)
    f32 = lambda n: jnp.zeros((n,), dtype=jnp.float32)
    return TreeArrays(
        num_leaves=jnp.int32(1),
        node_feature=i32(li), node_threshold_bin=i32(li),
        node_default_left=jnp.zeros((li,), dtype=bool),
        node_left=i32(li, -1), node_right=i32(li, -1),
        node_gain=f32(li), node_value=f32(li), node_weight=f32(li),
        node_count=f32(li),
        node_cat=jnp.zeros((li,), dtype=bool),
        node_cat_bitset=jnp.zeros((li, cat_words), dtype=jnp.uint32),
        node_seg_lo=i32(li, -1), node_seg_hi=i32(li, -1),
        leaf_value=f32(lf), leaf_weight=f32(lf), leaf_count=f32(lf),
        leaf_depth=i32(lf), leaf_parent=i32(lf, -1),
        shrinkage=jnp.float32(1.0),
    )


def _decide_left_bins(bin_val, threshold_bin, default_left, missing_bin,
                      is_cat, cat_bitset, seg_lo=None, seg_hi=None):
    """Split decision in bin space.

    ``missing_bin``: per-feature bin routed by default direction (-1 when the
    feature has no missing routing; see ops/split.py mode analysis).
    Categorical: left iff the bin's bit is set in the membership bitset
    (reference: Tree::CategoricalDecision bitset FindInBitset, tree.h:133+).
    ``seg_lo/seg_hi``: EFB bundle segment for bundle-column splits — rows
    outside the owning member's bin range are that member's default mass and
    route by ``default_left`` (the model-file analog is a missing_type=Zero
    node, tree.h NumericalDecision).
    """
    num_default = (bin_val == missing_bin) & (missing_bin >= 0)
    num_left = jnp.where(num_default, default_left, bin_val <= threshold_bin)
    if seg_lo is not None:
        in_seg = (bin_val >= seg_lo) & (bin_val <= seg_hi)
        bundle_left = jnp.where(in_seg, bin_val <= threshold_bin, default_left)
        num_left = jnp.where(seg_lo >= 0, bundle_left, num_left)
    word = (bin_val >> 5).astype(jnp.int32)
    bit = (bin_val & 31).astype(jnp.int32)
    cat_words = jnp.take_along_axis(cat_bitset, word[:, None], axis=1)[:, 0]
    cat_left = ((cat_words >> bit.astype(jnp.uint32)) & 1) == 1
    return jnp.where(is_cat, cat_left, num_left)


def _traversal_setup(tree: TreeArrays, bins: jax.Array,
                     missing_bin: jax.Array):
    """Shared setup of the level-by-level traversal: the 0-feature guard,
    the step body (descend every active row one edge) and the initial
    (cur, leaf) state. Used by both the data-dependent while_loop
    traversal and the depth-bounded fori_loop traversal below."""
    n = bins.shape[0]
    if bins.shape[1] == 0:
        # 0-feature dataset (every feature pre-filtered as trivial): all
        # trees are splitless, every row lands in leaf 0; pad one dummy
        # column so the gathers below stay well-formed for the traversal
        # machinery (which never routes anywhere for a 1-leaf tree anyway)
        bins = jnp.zeros((n, 1), dtype=bins.dtype)
        missing_bin = jnp.full((1,), -1, dtype=jnp.int32)
    rows = jnp.arange(n, dtype=jnp.int32)

    def step(state):
        cur, leaf = state
        active = cur >= 0
        node = jnp.maximum(cur, 0)
        feat = tree.node_feature[node]
        b = bins[rows, feat].astype(jnp.int32)
        go_left = _decide_left_bins(
            b, tree.node_threshold_bin[node], tree.node_default_left[node],
            missing_bin[feat], tree.node_cat[node], tree.node_cat_bitset[node],
            tree.node_seg_lo[node], tree.node_seg_hi[node])
        nxt = jnp.where(go_left, tree.node_left[node], tree.node_right[node])
        nxt = jnp.where(active, nxt, cur)
        new_leaf = jnp.where(active & (nxt < 0), ~nxt, leaf)
        return nxt, new_leaf

    # single-leaf tree: no nodes to traverse
    init_cur = jnp.where(tree.num_leaves <= 1, -1, 0) * jnp.ones((n,), jnp.int32)
    return step, (init_cur, jnp.zeros((n,), dtype=jnp.int32))


def predict_leaf_bins(tree: TreeArrays, bins: jax.Array,
                      missing_bin: jax.Array) -> jax.Array:
    """Leaf index per row by traversing over the binned matrix.

    Args:
      bins: [N, F] int bins.
      missing_bin: [F] int32, per-feature default-routed bin or -1.
    Returns [N] int32 leaf indices.
    """
    step, init = _traversal_setup(tree, bins, missing_bin)

    def cond(state):
        return jnp.any(state[0] >= 0)

    _, leaf = jax.lax.while_loop(cond, lambda s: step(s), init)
    return leaf


def predict_leaf_bins_depth(tree: TreeArrays, bins: jax.Array,
                            missing_bin: jax.Array, depth: int) -> jax.Array:
    """Depth-bounded traversal: a ``fori_loop`` with a STATIC trip count
    instead of the data-dependent ``while_loop`` above. ``depth`` must be
    >= the deepest leaf's edge count in ``tree`` — rows whose leaf is
    reached earlier mask out (cur < 0) and the remaining steps are
    no-ops, so the leaf indices are IDENTICAL to predict_leaf_bins.

    The point: inside a stacked-ensemble scan the while_loop stalls every
    batch on its slowest row AND blocks XLA from pipelining/fusing across
    trees (a data-dependent trip count is a hard scheduling barrier); a
    fixed trip count turns the whole ensemble traversal into a statically
    schedulable loop nest (the batched analog of the reference's
    unconditional per-node descent, gbdt_prediction.cpp:13-53)."""
    step, init = _traversal_setup(tree, bins, missing_bin)
    _, leaf = jax.lax.fori_loop(0, depth, lambda _, s: step(s), init)
    return leaf


def predict_value_bins(tree: TreeArrays, bins: jax.Array,
                       missing_bin: jax.Array) -> jax.Array:
    """Tree output per row (leaf_value already includes shrinkage)."""
    leaf = predict_leaf_bins(tree, bins, missing_bin)
    return tree.leaf_value[leaf]


import functools


@functools.partial(jax.jit, static_argnames=("block",))
def _leaf_values_of_rows_tpu(leaf_value: jax.Array, leaf_id: jax.Array,
                             block: int) -> jax.Array:
    n = leaf_id.shape[0]
    l = leaf_value.shape[0]
    c = min(block, -(-n // 512) * 512)
    pad = -n % c
    lid = jnp.pad(leaf_id, (0, pad), constant_values=-1) if pad else leaf_id
    iota = jnp.arange(l, dtype=jnp.int32)

    def body(_, lid_blk):
        oh = (lid_blk[:, None] == iota[None, :]).astype(jnp.float32)
        # HIGHEST precision: the default TPU matmul would bf16-round
        # leaf_value (~0.4% rel) in every train-score update, biasing
        # gradients each iteration (the reference accumulates scores in
        # double, score_updater.hpp)
        vals = jax.lax.dot_general(
            oh, leaf_value[:, None], (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)[:, 0]
        return _, vals

    _, vals = jax.lax.scan(body, 0, lid.reshape(-1, c))
    return vals.reshape(-1)[:n]


def leaf_values_of_rows(leaf_value: jax.Array, leaf_id: jax.Array,
                        block: int = 65536) -> jax.Array:
    """Per-row tree output ``leaf_value[leaf_id]`` without a gather.

    XLA's gather from a small table costs ~90ms for 10M rows on a v5e (it
    serializes); a jitted blocked compare x matmul runs at memory bandwidth
    (unjitted, the scan dispatches eagerly step by step, one launch per
    block). Used for the training-score update (the analog of
    Tree::AddPredictionToScore, tree.h, which indexes the data partition
    instead)."""
    if jax.default_backend() != "tpu":
        return leaf_value[leaf_id]
    return _leaf_values_of_rows_tpu(leaf_value, leaf_id, block)


def stack_trees(trees: List[TreeArrays]) -> TreeArrays:
    """Stack per-tree arrays with a leading T axis for scan-based ensemble
    prediction (the analog of GBDT::PredictRaw's per-tree loop,
    gbdt_prediction.cpp:13-53, but batched on device)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def predict_value_ensemble(stacked: TreeArrays, bins: jax.Array,
                           missing_bin: jax.Array,
                           num_trees: int | None = None) -> jax.Array:
    """Sum of tree outputs over a stacked ensemble via lax.scan."""

    def step(carry, tree):
        return carry + predict_value_bins(tree, bins, missing_bin), None

    total, _ = jax.lax.scan(step, jnp.zeros((bins.shape[0],), jnp.float32), stacked)
    return total


@jax.jit
def predict_leaves_stacked(stacked: TreeArrays, bins: jax.Array,
                           missing_bin: jax.Array) -> jax.Array:
    """Per-tree leaf indices over a stacked ensemble in one device program
    (the batched analog of the per-tree predict_leaf loop). Returns
    [T, N] int32."""
    def step(_, tree):
        return _, predict_leaf_bins(tree, bins, missing_bin)

    _, leaves = jax.lax.scan(step, 0, stacked)
    return leaves


@jax.jit
def predict_values_stacked(stacked: TreeArrays, bins: jax.Array,
                           missing_bin: jax.Array) -> jax.Array:
    """Per-tree outputs over a stacked ensemble in ONE device program (the
    batched analog of GBDT::PredictRaw's per-tree loop,
    gbdt_prediction.cpp:13-53 — a 500-tree predict is a handful of
    dispatches, not 500 launches with a host fetch each). The per-tree
    values are
    returned (not summed on device) so the caller can accumulate in float64
    in tree order, bit-identical to the host per-tree path.

    Returns [T, N] float32.
    """
    def step(_, tree):
        return _, predict_value_bins(tree, bins, missing_bin)

    _, vals = jax.lax.scan(step, 0, stacked)
    return vals


# --------------------------------------------------------------------- host
class HostTree:
    """Host-side (numpy) view of a trained tree for model IO, SHAP and
    raw-feature prediction. Built once per tree after training."""

    def __init__(self, arrays: TreeArrays, real_thresholds: np.ndarray,
                 feature_indices: np.ndarray,
                 missing_types: np.ndarray | None = None):
        # one batched device_get: per-array fetches would each stall the
        # host on the device, ~18x per tree
        t = jax.device_get(arrays)
        self.num_leaves = int(t.num_leaves)
        n = max(self.num_leaves - 1, 0)
        self.split_feature = t.node_feature[:n].astype(np.int32)
        self.threshold_bin = t.node_threshold_bin[:n]
        self.threshold = real_thresholds[:n]
        self.default_left = t.node_default_left[:n]
        self.left_child = t.node_left[:n]
        self.right_child = t.node_right[:n]
        self.split_gain = t.node_gain[:n]
        self.internal_value = t.node_value[:n]
        self.internal_weight = t.node_weight[:n]
        self.internal_count = t.node_count[:n]
        self.is_cat = t.node_cat[:n]
        self.cat_bitset = t.node_cat_bitset[:n]
        self.leaf_value = t.leaf_value[:self.num_leaves]
        self.leaf_weight = t.leaf_weight[:self.num_leaves]
        self.leaf_count = t.leaf_count[:self.num_leaves]
        self.leaf_depth = t.leaf_depth[:self.num_leaves]
        self.leaf_parent = t.leaf_parent[:self.num_leaves]
        self.shrinkage = float(t.shrinkage)
        # map inner feature index -> original column index
        self.feature_indices = feature_indices
        # per-node missing type (binning.MISSING_*), for decision_type dumps
        # (reference: tree.h:269 GetMissingType packed in decision_type_)
        self.missing_type = (missing_types[:n].astype(np.int8)
                             if missing_types is not None
                             else np.zeros(n, dtype=np.int8))
        # linear-leaf model (reference: tree.h:194-204 leaf_coeff_/leaf_const_)
        self.is_linear = False
        self.leaf_const: np.ndarray | None = None
        self.leaf_coeff: list = []
        self.leaf_features_raw: list = []

    def scaled(self, factor: float) -> "HostTree":
        """Copy with outputs scaled (reference: Tree::Shrinkage, tree.h:187;
        used by DART normalization). Linear coefficients scale too."""
        out = copy.copy(self)
        out.leaf_value = self.leaf_value * factor
        out.internal_value = self.internal_value * factor
        out.shrinkage = self.shrinkage * factor
        if self.is_linear and self.leaf_const is not None:
            out.leaf_const = self.leaf_const * factor
            out.leaf_coeff = [[c * factor for c in cs] for cs in self.leaf_coeff]
        return out
