"""Vectorized best-split search over histograms.

TPU-native re-design of the reference's per-feature threshold scan
(reference: src/treelearner/feature_histogram.hpp:858-1050
``FindBestThresholdSequentially`` and the gain/output formulas at
feature_histogram.hpp:737-856). Where the reference runs a sequential
two-direction scan per feature inside OpenMP, here cumulative sums over the
bin axis evaluate EVERY (leaf, feature, direction, threshold) candidate at
once, then a masked lexicographic argmax reproduces the reference's
first-better-wins tie ordering.

Semantics carried over exactly:

- gain  = GetLeafGain(left) + GetLeafGain(right) compared against
  ``min_gain_shift = GetLeafGain(parent) + min_gain_to_split`` (strict ``>``),
  with stored gain = best_gain - min_gain_shift
  (feature_histogram.hpp:103-112, 934-944).
- leaf output = -ThresholdL1(sum_g, l1) / (sum_h + l2), clipped to
  ±max_delta_step, then path-smoothed toward the parent output
  (feature_histogram.hpp:737-764 CalculateSplittedLeafOutput).
- missing handling (feature_histogram.hpp:166-213 FuncForNumricalL3 dispatch):
  * num_bin > 2 and MissingType::Zero  -> two scans, default bin skipped from
    both accumulations and from the threshold candidates (SKIP_DEFAULT_BIN).
  * num_bin > 2 and MissingType::NaN   -> two scans, NaN bin (last bin)
    excluded from directional accumulation so its mass rides with the default
    direction (NA_AS_MISSING).
  * otherwise -> single reverse scan; default_left=False forced for NaN
    (feature_histogram.hpp:199-210).
  Reverse scan => missing goes left (default_left=True); forward scan =>
  missing goes right.
- the accumulated direction's hessian starts at kEpsilon
  (feature_histogram.hpp:882 ``sum_right_hessian = kEpsilon``).
- min_data_in_leaf / min_sum_hessian_in_leaf validity masks
  (feature_histogram.hpp:904-917).

Deviation from the reference: counts come from an exactly-accumulated count
channel instead of ``RoundInt(hess * num_data / sum_hessian)``
(feature_histogram.hpp:869, 898) — exact counts, same constraint semantics.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..binning import MISSING_NAN, MISSING_NONE, MISSING_ZERO

K_EPSILON = 1e-15          # reference: include/LightGBM/meta.h kEpsilon
K_MIN_SCORE = -jnp.inf     # reference: kMinScore


class FeatureMeta(NamedTuple):
    """Per-feature static metadata arrays, all shape [F]."""
    num_bins: jax.Array        # int32, total bins incl. NaN bin
    missing_type: jax.Array    # int32, MISSING_{NONE,ZERO,NAN}
    default_bin: jax.Array     # int32, bin of value 0.0
    is_categorical: jax.Array  # bool
    monotone: jax.Array        # int8, -1/0/+1 (0 = unconstrained)
    penalty: jax.Array         # float32 feature_contri gain multiplier


class SplitParams(NamedTuple):
    """Split hyperparameters (dynamic scalars so param changes don't recompile)."""
    lambda_l1: jax.Array
    lambda_l2: jax.Array
    max_delta_step: jax.Array
    path_smooth: jax.Array
    min_data_in_leaf: jax.Array
    min_sum_hessian_in_leaf: jax.Array
    min_gain_to_split: jax.Array
    cat_l2: jax.Array
    cat_smooth: jax.Array
    max_cat_threshold: jax.Array
    min_data_per_group: jax.Array
    max_cat_to_onehot: jax.Array
    monotone_penalty: jax.Array
    cegb_tradeoff: jax.Array
    cegb_penalty_split: jax.Array

    @classmethod
    def from_config(cls, config) -> "SplitParams":
        f32 = jnp.float32
        return cls(
            lambda_l1=f32(config.lambda_l1),
            lambda_l2=f32(config.lambda_l2),
            max_delta_step=f32(config.max_delta_step),
            path_smooth=f32(config.path_smooth),
            min_data_in_leaf=f32(config.min_data_in_leaf),
            min_sum_hessian_in_leaf=f32(config.min_sum_hessian_in_leaf),
            min_gain_to_split=f32(config.min_gain_to_split),
            cat_l2=f32(config.cat_l2),
            cat_smooth=f32(config.cat_smooth),
            max_cat_threshold=jnp.int32(config.max_cat_threshold),
            min_data_per_group=f32(config.min_data_per_group),
            max_cat_to_onehot=jnp.int32(config.max_cat_to_onehot),
            monotone_penalty=f32(config.monotone_penalty),
            cegb_tradeoff=f32(config.cegb_tradeoff),
            cegb_penalty_split=f32(config.cegb_penalty_split),
        )


class BundleMeta(NamedTuple):
    """Per-(column, bin) EFB segment structure (bundling.py layout). For a
    bundle column, bin ``b`` inside member ``f``'s range has ``seg_lo/seg_hi``
    = that range's first/last bin; bins outside any member range (bundle bin
    0) carry lo = hi = 0. Regular columns: lo = 0, hi = num_bin - 1 (which
    makes the generalized directional sums reduce to the plain ones).
    ``fwd_ok/rev_ok`` restrict threshold candidates per scan direction so
    the bundle scan evaluates exactly the member feature's unbundled
    candidate set (each original threshold once, with the member's
    most-frequent mass — reconstructed from the leaf totals — on the side
    its bin order dictates); built host-side in
    basic.py _build_feature_meta_bundled.

    ``pref_fwd/pref_rev`` are the per-(column, bin, direction) TIE-BREAK
    keys (higher wins among equal-gain candidates), built so the bundled
    argmax reproduces the UNBUNDLED lexicographic order exactly: ordered
    by the candidate's ORIGINAL owner feature (lowest index wins — a
    bundle column interleaves several features' bins, so the plain
    column-major preference would resolve a within-bundle tie to the
    highest-offset member instead of the lowest feature, silently growing
    a different tree than the unbundled run), then by the owner's own scan
    direction and threshold order.

    Every segment is a CONTIGUOUS range of bins that all carry the same
    (lo, hi), and the only bins past their own ``seg_hi`` are a column's
    trailing padding, all with one ``seg_hi`` and ``seg_lo`` 0
    (basic.py _build_feature_meta_bundled writes each table by ranges:
    ``seg_lo[gi, off:off + span] = off``); ``segment_prefix_sums`` relies
    on it to read a segment's bounds without a gather."""
    seg_lo: jax.Array        # int32 [F, B]
    seg_hi: jax.Array        # int32 [F, B]
    is_bundle: jax.Array     # bool [F]
    fwd_ok: jax.Array        # bool [F, B]
    rev_ok: jax.Array        # bool [F, B]
    pref_fwd: jax.Array      # int32 [F, B]
    pref_rev: jax.Array      # int32 [F, B]


class SplitInfo(NamedTuple):
    """Per-leaf best split, struct-of-arrays of shape [L]
    (reference: src/treelearner/split_info.hpp:22-90)."""
    gain: jax.Array          # f32; -inf when unsplittable
    feature: jax.Array       # int32 inner feature index
    threshold: jax.Array     # int32 bin threshold (left: bin <= threshold)
    default_left: jax.Array  # bool, direction for missing values
    left_sum_g: jax.Array
    left_sum_h: jax.Array
    left_count: jax.Array    # f32 (weighted count channel)
    right_sum_g: jax.Array
    right_sum_h: jax.Array
    right_count: jax.Array
    left_output: jax.Array
    right_output: jax.Array
    is_cat: jax.Array        # bool, categorical (bitset) split
    cat_bitset: jax.Array    # uint32[L, CAT_WORDS] categorical membership (0 when numerical)
    seg_lo: jax.Array        # int32 [L]; EFB bundle segment start (-1 regular)
    seg_hi: jax.Array        # int32 [L]; EFB bundle segment end (inclusive)


CAT_BITSET_WORDS = 8  # default width (256 bins); widened when max_bin > 256


def threshold_l1(s: jax.Array, l1: jax.Array) -> jax.Array:
    """reference: feature_histogram.hpp:737-741 ThresholdL1."""
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def _round_fence(x: jax.Array, p: "SplitParams") -> jax.Array:
    """Value-preserving rounding fence for the gain math (the
    models/gbdt.py _fma_guard idiom): bitcast to the matching integer
    width, XOR with a runtime-zero salt the compiler cannot fold, bitcast
    back. XLA contracts a multiply feeding an add into an FMA whose
    single rounding drifts 1 ulp — and WHICH adds it contracts depends on
    the surrounding program, so the same gain expression compiled in two
    places (the classic split phase vs the fused tile epilogue, or either
    side of a compaction-rung lax.cond) can disagree in the last bit.
    Fencing each product before it enters an add pins the two-rounding
    sequence everywhere, which is what makes the split_fusion bit-parity
    contract (and the classic path's own cross-context stability) hold.
    The salt ``l2 != l2`` is zero unless lambda_l2 is NaN — runtime data
    the simplifier cannot prove constant."""
    itype = jnp.uint64 if x.dtype == jnp.float64 else jnp.uint32
    salt = (p.lambda_l2 != p.lambda_l2).astype(itype)
    xi = jax.lax.bitcast_convert_type(x, itype)
    return jax.lax.bitcast_convert_type(jnp.bitwise_xor(xi, salt), x.dtype)


def calculate_leaf_output(sum_g, sum_h, p: SplitParams, num_data, parent_output,
                          lambda_l2=None):
    """reference: feature_histogram.hpp:743-764 CalculateSplittedLeafOutput."""
    l2 = p.lambda_l2 if lambda_l2 is None else lambda_l2
    ret = -threshold_l1(sum_g, p.lambda_l1) / (sum_h + l2)
    ret = jnp.where((p.max_delta_step > 0) & (jnp.abs(ret) > p.max_delta_step),
                    jnp.sign(ret) * p.max_delta_step, ret)
    use_smooth = p.path_smooth > K_EPSILON
    n_over_s = num_data / jnp.where(use_smooth, p.path_smooth, 1.0)
    # the product rounds concretely before the add (_round_fence): the
    # smoothing multiply-add is FMA-contraction-prone and must compute
    # the same bits in every compilation context (classic phase, fused
    # epilogue, compaction-rung branches); the division term cannot
    # contract and needs no fence
    smoothed = (_round_fence(ret * (n_over_s / (n_over_s + 1.0)), p)
                + parent_output / (n_over_s + 1.0))
    return jnp.where(use_smooth, smoothed, ret)


def leaf_gain_given_output(sum_g, sum_h, output, p: SplitParams, lambda_l2=None):
    """reference: feature_histogram.hpp:846-856 GetLeafGainGivenOutput.

    Both products pass the rounding fence before the add — see
    _round_fence: the gain must compute the same bits wherever this
    expression is compiled (classic split phase, fused tile epilogue,
    either side of a compaction-rung cond)."""
    l2 = p.lambda_l2 if lambda_l2 is None else lambda_l2
    sg = threshold_l1(sum_g, p.lambda_l1)
    return -(_round_fence(2.0 * sg * output, p)
             + _round_fence((sum_h + l2) * output * output, p))


def leaf_gain(sum_g, sum_h, p: SplitParams, num_data, parent_output, lambda_l2=None):
    """reference: feature_histogram.hpp:826-843 GetLeafGain. Always routed
    through the output (identical to the closed form when no clipping/smoothing)."""
    out = calculate_leaf_output(sum_g, sum_h, p, num_data, parent_output, lambda_l2)
    return leaf_gain_given_output(sum_g, sum_h, out, p, lambda_l2)


def prefix_sum(x: jax.Array, axis: int) -> jax.Array:
    """Inclusive prefix sum along ``axis`` as the SEQUENTIAL f32 sum
    ``cs[t] = cs[t-1] + x[t]`` — the order of the reference's threshold
    scan (feature_histogram.hpp:858-1050), and the one order in which an
    empty bin leaves the running sum bit-for-bit unchanged. That is what
    makes two thresholds with no row of the leaf between them tie
    EXACTLY (so the tie order decides, not rounding), and what keeps an
    EFB bundle column's segment sums equal to its members' own. A
    tree-shaped sum (``jnp.cumsum`` lowers to one) regroups the non-zero
    terms by their positions and loses both.

    The Mosaic TPU compiler has no ``cumsum`` lowering either; the
    in-kernel split epilogue (ops/pallas_hist.py _epilogue_feature) runs
    the same recurrence as a loop over a slab's rows, so kernel, XLA twin
    and classic search produce the same bits."""
    xs = jnp.moveaxis(x, axis, 0)

    def step(run, row):
        run = run + row
        return run, run

    _, cs = jax.lax.scan(step, jnp.zeros_like(xs[0]), xs)
    return jnp.moveaxis(cs, 0, axis)


def segment_prefix_sums(x: jax.Array, seg_lo: jax.Array, seg_hi: jax.Array):
    """``prefix_sum(x, 2)`` of a ``[L, F, B, C]`` plane of bundle columns
    with each bin's segment bounds beside it: ``(csum, csum_lo, upper)``
    where ``csum_lo[l, f, b] = csum[l, f, seg_lo[f, b] - 1]`` (0 where
    ``seg_lo`` is 0) and ``upper[l, f, b] = csum[l, f, seg_hi[f, b]]``.

    The bounds are COPIES of elements of ``csum``, never sums of their
    own (a sum restarted at a segment's first bin rounds differently and
    would grow other trees), and no gather moves them: the tables are the
    same for every leaf and channel and their segments are contiguous
    ranges (BundleMeta), so the scan that builds ``csum`` latches its
    running sum BEFORE the bin where a segment starts and carries it to
    the segment's end, and a second scan from the top bin down carries
    ``csum`` from where a segment ends to its start. That second scan
    starts from ``csum`` at the trailing padding's ``seg_hi`` (``top``,
    latched by the first), so the bins past the last segment read what a
    gather would. A ``take_along_axis`` of the ``[255, 11, 255, 3]``
    planes took 10.8 ns an element on the chip, twice a search round."""
    bins = jnp.arange(x.shape[2], dtype=seg_lo.dtype)

    def by_bin(mask):                                  # [F, B] -> [B, 1, F, 1]
        return mask.T[:, None, :, None]

    def forward(carry, step):
        run, lo, top = carry
        row, starts, is_top = step
        lo = jnp.where(starts, run, lo)
        run = run + row
        return (run, lo, jnp.where(is_top, run, top)), (run, lo)

    def backward(hi, step):
        cs_b, ends = step
        hi = jnp.where(ends, cs_b, hi)
        return hi, hi

    xs = jnp.moveaxis(x, 2, 0)
    zero = jnp.zeros_like(xs[0])
    (_, _, top), (cs, lo) = jax.lax.scan(
        forward, (zero, zero, zero),
        (xs, by_bin(seg_lo == bins), by_bin(seg_hi[:, -1:] == bins)))
    _, hi = jax.lax.scan(backward, top, (cs, by_bin(seg_hi == bins)),
                         reverse=True)
    csum, csum_lo, upper = (jnp.moveaxis(a, 0, 2) for a in (cs, lo, hi))
    return csum, jnp.where(seg_lo[None, :, :, None] > 0, csum_lo, 0.0), upper


def _sums_from_prefix(cs_g, cs_h, cs_c, tot_g, tot_h, tot_c,
                      leaf_sum_g, leaf_sum_h, leaf_cnt, lo=None):
    """Left/right sums for every threshold, both directions, from the
    per-channel prefix sums (accumulated-side eps added like the
    reference). ``tot_*`` is the reverse scan's upper prefix (the
    non-excluded total, or a bundle segment's end); ``lo`` an optional
    (g, h, c) prefix to subtract from the forward scan (bundle segment
    start). The complement side comes from the leaf's TRUE totals, which
    include the missing mass. Shapes only need to broadcast, so the same
    code serves [L, F, B] planes in XLA and [B, lanes] slabs in kernel."""
    fwd = (cs_g, cs_h, cs_c) if lo is None else (
        cs_g - lo[0], cs_h - lo[1], cs_c - lo[2])
    lt = dict(
        fwd_left_g=fwd[0], fwd_left_h=fwd[1] + K_EPSILON, fwd_left_c=fwd[2],
        rev_right_g=tot_g - cs_g, rev_right_h=tot_h - cs_h + K_EPSILON,
        rev_right_c=tot_c - cs_c)
    lt["fwd_right_g"] = leaf_sum_g - lt["fwd_left_g"]
    lt["fwd_right_h"] = leaf_sum_h - lt["fwd_left_h"]
    lt["fwd_right_c"] = leaf_cnt - lt["fwd_left_c"]
    lt["rev_left_g"] = leaf_sum_g - lt["rev_right_g"]
    lt["rev_left_h"] = leaf_sum_h - lt["rev_right_h"]
    lt["rev_left_c"] = leaf_cnt - lt["rev_right_c"]
    return lt


def _directional_sums(hist_excl, leaf_sum_g, leaf_sum_h, leaf_cnt,
                      bundle: BundleMeta | None = None):
    """Cumulative left/right sums for every threshold, both directions.

    hist_excl: [L, F, B, 3] histogram with excluded bins zeroed.
    Returns dict with fwd/rev (accumulated-side eps added like the reference).
    Threshold t means: left = bins <= t (accumulated side fwd), right = bins > t.

    With ``bundle``, the accumulated side is SEGMENT-relative: an EFB bundle
    column interleaves many features' bin ranges, so the left mass at
    threshold t inside member f's range is csum[t] - csum[seg_lo-1] and the
    reverse-scan right mass is csum[seg_hi] - csum[t]. The complement side
    comes from the leaf totals, which automatically assigns every
    out-of-segment row (the member's most-frequent/default mass and the
    other members' rows) to the scan's default direction — the same
    total-minus-accumulated reconstruction as the reference's FixHistogram
    (dataset.cpp) + SKIP_DEFAULT_BIN scans.
    """
    lo_sums = None
    if bundle is None:
        csum = prefix_sum(hist_excl, 2)                        # [L, F, B, 3]
        upper = csum[:, :, -1:, :]
    else:
        csum, csum_lo, upper = segment_prefix_sums(
            hist_excl, bundle.seg_lo, bundle.seg_hi)
        lo_sums = (csum_lo[..., 0], csum_lo[..., 1], csum_lo[..., 2])
    return _sums_from_prefix(
        csum[..., 0], csum[..., 1], csum[..., 2],
        upper[..., 0], upper[..., 1], upper[..., 2],
        leaf_sum_g[:, None, None], leaf_sum_h[:, None, None],
        leaf_cnt[:, None, None], lo=lo_sums)


def _leaf_gain_nosmooth(sum_g, sum_h, p: SplitParams, lambda_l2):
    """Leaf gain with NO path smoothing (the reference's categorical
    min_gain_shift when path_smooth is off, feature_histogram.hpp:296-302:
    GetLeafGain with parent_output=0)."""
    sg = threshold_l1(sum_g, p.lambda_l1)
    out = -sg / (sum_h + lambda_l2)
    out = jnp.where((p.max_delta_step > 0) & (jnp.abs(out) > p.max_delta_step),
                    jnp.sign(out) * p.max_delta_step, out)
    return -(_round_fence(2.0 * sg * out, p)
             + _round_fence((sum_h + lambda_l2) * out * out, p))


def sort_with_key(key: jax.Array, *planes: jax.Array):
    """``(order, *planes)`` of ``[L, F, B]`` arrays, each sorted along the
    bin axis by ``key``: ``order`` is ``jnp.argsort(key, axis=2)`` and a
    plane ``x`` comes back as ``take_along_axis(x, order, axis=2)``, but
    ONE stable sort carries them all beside their key (ties keep their bin
    order, as the stable argsort's), so every value is a copy and no
    gather moves it."""
    iota = jax.lax.broadcasted_iota(jnp.int32, key.shape, 2)
    _, order, *moved = jax.lax.sort((key, iota, *planes), dimension=2,
                                    is_stable=True, num_keys=1)
    return (order, *moved)


def read_reversed(x: jax.Array, start: jax.Array):
    """``x[l, f, start[l, f] - i]`` at bin ``i`` of an ``[L, F, B]`` plane,
    0 where that index is below 0 (``start`` in ``[-2, B - 1]``).

    No gather: the bin axis is reversed (a static copy) and then moved
    left by ``B - 1 - start`` lanes of its own (leaf, feature) row, in
    ``ceil(log2(B + 2))`` static shifts by a power of two, each kept where
    its bit of the distance is set, with zeros coming in from the top."""
    b = x.shape[2]
    x = jnp.flip(x, axis=2)
    dist = (b - 1 - start)[:, :, None]
    for k in range((b + 1).bit_length()):          # dist is at most B + 1
        step = 1 << k
        moved = jnp.concatenate(
            [x[:, :, step:], jnp.zeros_like(x[:, :, :step])], axis=2)
        x = jnp.where((dist >> k) & 1 == 1, moved, x)
    return x


def find_best_cat_splits(hist: jax.Array, leaf_sum_g, leaf_sum_h, leaf_cnt,
                         leaf_output, leaf_depth, meta: FeatureMeta,
                         p: SplitParams, feature_mask: jax.Array,
                         max_depth: int = -1,
                         cat_words: int = CAT_BITSET_WORDS,
                         gain_adjust=None):
    """Best categorical split per leaf over all categorical features.

    Vectorized re-design of the reference's categorical threshold search
    (reference: feature_histogram.hpp:277-515
    FindBestThresholdCategoricalInner). Two modes, chosen per feature:

    - one-hot (num_bin <= max_cat_to_onehot): every bin t in [1, nb) is a
      one-vs-rest candidate, gain with plain lambda_l2.
    - sorted many-vs-many: bins with count >= cat_smooth are sorted by
      grad/(hess + cat_smooth); candidates take the first i+1 sorted bins
      from either end (two directions), with l2 += cat_l2, the
      min_data_per_group group counter, and max_cat_threshold cap.

    Candidate axes are evaluated all at once as [L, F, 3, B] gains
    (mode-slots: one-hot / dir+1 / dir-1); a lexicographic argmax reproduces
    the reference's first-better-wins evaluation order.

    No gather moves the sorted sums or the backward direction's reversed
    reads: the sort carries g, h and count beside their key
    (``sort_with_key``) and the reads are a reversal and per-row static
    shifts (``read_reversed``), copies of the same elements, so the bits
    are those of ``take_along_axis``. Each of the seven such gathers of a
    ``[255, 8, 255]`` plane took 4.855 ms a search round on the chip at
    ``expo-cat.train``, 14% of its step.

    Returns (gain[L], feature[L], left sums..., bitset[L, CAT_WORDS]).
    """
    L, F, B, _ = hist.shape
    g = hist[..., 0]
    h = hist[..., 1]
    c = hist[..., 2]
    nb = meta.num_bins[None, :]                                 # [1, F]
    bins = jnp.arange(B, dtype=jnp.int32)[None, None, :]        # [1, 1, B]
    in_range = (bins >= 1) & (bins < nb[:, :, None])            # bin 0 = other/NaN
    G = leaf_sum_g[:, None]
    H = leaf_sum_h[:, None]
    C = leaf_cnt[:, None]
    parent_out = leaf_output[:, None, None]

    use_onehot = (meta.num_bins <= p.max_cat_to_onehot)[None, :]   # [1, F]
    l2_sorted = p.lambda_l2 + p.cat_l2

    # min_gain_shift (feature_histogram.hpp:291-305): smoothing uses the
    # parent's actual output; otherwise plain-l2 leaf gain with no smoothing
    use_smooth = p.path_smooth > K_EPSILON
    shift_smooth = leaf_gain_given_output(leaf_sum_g, leaf_sum_h, leaf_output, p)
    shift_plain = _leaf_gain_nosmooth(leaf_sum_g, leaf_sum_h, p, p.lambda_l2)
    min_gain_shift = (jnp.where(use_smooth, shift_smooth, shift_plain)
                      + p.min_gain_to_split)[:, None, None]       # [L, 1, 1]

    def split_gain(lg, lh, lc, l2):
        rg, rh, rc = G[:, :, None] - lg, H[:, :, None] - lh, C[:, :, None] - lc
        lo = calculate_leaf_output(lg, lh, p, lc, parent_out, l2)
        ro = calculate_leaf_output(rg, rh, p, rc, parent_out, l2)
        return (leaf_gain_given_output(lg, lh, lo, p, l2)
                + leaf_gain_given_output(rg, rh, ro, p, l2))

    # ---- one-hot candidates: left = single bin t (hess + eps)
    oh_lg, oh_lh, oh_lc = g, h + K_EPSILON, c
    oh_gain = split_gain(oh_lg, oh_lh, oh_lc, p.lambda_l2)
    oh_ok = (in_range
             & (c >= p.min_data_in_leaf) & (h >= p.min_sum_hessian_in_leaf)
             & (C[:, :, None] - c >= p.min_data_in_leaf)
             & (H[:, :, None] - h - K_EPSILON >= p.min_sum_hessian_in_leaf))

    # ---- sorted candidates
    valid = in_range & (c >= p.cat_smooth)                       # count filter
    ratio = jnp.where(valid, g / (h + p.cat_smooth), jnp.inf)
    order, sg, sh, sc = sort_with_key(                           # invalid last
        ratio, jnp.where(valid, g, 0.0), jnp.where(valid, h, 0.0),
        jnp.where(valid, c, 0.0))
    csum_g = jnp.cumsum(sg, axis=2)
    csum_h = jnp.cumsum(sh, axis=2)
    csum_c = jnp.cumsum(sc, axis=2)
    used_bin = valid.sum(axis=2).astype(jnp.int32)               # [L, F]
    max_num_cat = jnp.minimum(p.max_cat_threshold, (used_bin + 1) // 2)

    idx = jnp.arange(B, dtype=jnp.int32)[None, None, :]
    # dir +1: left = first i+1 sorted bins
    fw_lg, fw_lh, fw_lc = csum_g, csum_h + K_EPSILON, csum_c
    # dir -1: left = last i+1 valid sorted bins = total_valid - csum[ub-2-i]
    j = used_bin[:, :, None] - 2 - idx
    tot_g, tot_h, tot_c = csum_g[:, :, -1:], csum_h[:, :, -1:], csum_c[:, :, -1:]
    bw_lg = tot_g - jnp.where(j >= 0, read_reversed(csum_g, used_bin - 2), 0.0)
    bw_lh = tot_h - jnp.where(j >= 0, read_reversed(csum_h, used_bin - 2), 0.0) + K_EPSILON
    bw_lc = tot_c - jnp.where(j >= 0, read_reversed(csum_c, used_bin - 2), 0.0)

    cand_ok_base = (idx < used_bin[:, :, None]) & (idx < max_num_cat[:, :, None])

    def sorted_guards(lh_, lc_):
        rc = C[:, :, None] - lc_
        rh = H[:, :, None] - lh_
        return ((lc_ >= p.min_data_in_leaf) & (lh_ >= p.min_sum_hessian_in_leaf)
                & (rc >= p.min_data_in_leaf) & (rc >= p.min_data_per_group)
                & (rh >= p.min_sum_hessian_in_leaf))

    # group counter (feature_histogram.hpp:443-447): cnt accumulates along the
    # scan and resets when a candidate is emitted — a sequential recurrence,
    # run as a lax.scan over the (small) bin axis with [L, F] lanes
    def group_scan(per_bin_cnt, eligible):
        def step(carry, xs):
            cnt_i, elig_i = xs
            acc = carry + cnt_i
            emit = elig_i & (acc >= p.min_data_per_group)
            return jnp.where(emit, 0.0, acc), emit
        xs = (jnp.moveaxis(per_bin_cnt, 2, 0), jnp.moveaxis(eligible, 2, 0))
        _, emits = jax.lax.scan(step, jnp.zeros(per_bin_cnt.shape[:2]), xs)
        return jnp.moveaxis(emits, 0, 2)

    fw_elig = cand_ok_base & sorted_guards(fw_lh, fw_lc)
    bw_elig = cand_ok_base & sorted_guards(bw_lh, bw_lc)
    # per-candidate cnt along each direction's scan order
    bw_cnt = jnp.where(j + 1 >= 0, read_reversed(sc, used_bin - 1), 0.0)
    fw_ok = group_scan(sc, fw_elig)
    bw_ok = group_scan(bw_cnt, bw_elig)

    fw_gain = split_gain(fw_lg, fw_lh, fw_lc, l2_sorted)
    bw_gain = split_gain(bw_lg, bw_lh, bw_lc, l2_sorted)

    # ---- assemble [L, F, 3, B]: slot 0 one-hot, 1 dir+1, 2 dir-1
    fmask = feature_mask
    if fmask.ndim == 1:
        fmask = fmask[None, :]
    base_ok = (fmask.astype(bool) & meta.is_categorical)[:, :, None]  # [L|1, F, 1]
    if max_depth > 0:
        base_ok = base_ok & (leaf_depth < max_depth)[:, None, None]

    oh_val = oh_ok & base_ok & use_onehot[:, :, None]
    so_val = base_ok & ~use_onehot[:, :, None]

    # adjusted "key" gains: stored gain x feature contri - CEGB delta
    # (matches the numerical path; monotone never applies to categoricals)
    contri = meta.penalty[None, :, None]

    def keyed(gain, valid):
        key = (gain - min_gain_shift) * contri
        if gain_adjust is not None:
            key = key - gain_adjust[:, :, None]
        return jnp.where(valid, key, K_MIN_SCORE)

    gains = jnp.stack([
        keyed(oh_gain, oh_val & (oh_gain > min_gain_shift)),
        keyed(fw_gain, so_val & fw_ok & (fw_gain > min_gain_shift)),
        keyed(bw_gain, so_val & bw_ok & (bw_gain > min_gain_shift)),
    ], axis=2)                                                   # [L, F, 3, B]

    # lexicographic argmax: features in index order, then evaluation order
    # (one-hot t asc | dir+1 i asc | dir-1 i asc), strict-greater-wins
    farange = jnp.arange(F, dtype=jnp.int32)[None, :, None, None]
    slot_pref = jnp.asarray([3 * B, 2 * B, B], jnp.int32)[None, None, :, None]
    pref = ((F - 1) - farange) * (8 * B) + slot_pref - idx[:, :, None, :]
    flat_gains = gains.reshape(L, -1)
    best_gain = jnp.max(flat_gains, axis=1)
    is_best = flat_gains == best_gain[:, None]
    best_idx = jnp.argmax(jnp.where(
        is_best, jnp.broadcast_to(pref, gains.shape).reshape(L, -1), -1), axis=1)

    bf = (best_idx // (3 * B)).astype(jnp.int32)
    rem = best_idx % (3 * B)
    bmode = (rem // B).astype(jnp.int32)                         # 0/1/2
    bi = (rem % B).astype(jnp.int32)

    li = jnp.arange(L)

    def pick3(a0, a1, a2):
        v0 = a0[li, bf, bi]
        v1 = a1[li, bf, bi]
        v2 = a2[li, bf, bi]
        return jnp.where(bmode == 0, v0, jnp.where(bmode == 1, v1, v2))

    left_g = pick3(oh_lg, fw_lg, bw_lg)
    left_h = pick3(oh_lh, fw_lh, bw_lh)
    left_c = pick3(oh_lc, fw_lc, bw_lc)

    # ---- membership bitset over bins for the chosen candidate
    order_rows = order[li, bf]                                   # [L, B]
    rank = jnp.argsort(order_rows, axis=1).astype(jnp.int32)     # bin -> sort pos
    ub_rows = used_bin[li, bf][:, None]
    bins_row = jnp.arange(B, dtype=jnp.int32)[None, :]
    member_oh = bins_row == bi[:, None]
    member_fw = rank <= bi[:, None]
    member_bw = (rank >= ub_rows - 1 - bi[:, None]) & (rank < ub_rows)
    member = jnp.where((bmode == 0)[:, None], member_oh,
                       jnp.where((bmode == 1)[:, None], member_fw, member_bw))
    # restrict to in-range bins of the chosen feature
    nb_rows = meta.num_bins[bf][:, None]
    member = member & (bins_row >= 1) & (bins_row < nb_rows)
    pad = (-B) % 32
    if pad:
        member = jnp.pad(member, ((0, 0), (0, pad)))
    mw = member.reshape(L, -1, 32).astype(jnp.uint32)
    words = (mw << jnp.arange(32, dtype=jnp.uint32)[None, None, :]).sum(
        axis=2, dtype=jnp.uint32)
    nwords = words.shape[1]
    if nwords < cat_words:
        words = jnp.pad(words, ((0, 0), (0, cat_words - nwords)))
    else:
        assert nwords == cat_words, (
            f"bitset width {nwords} exceeds cat_words={cat_words}")

    l2_out = jnp.where(use_onehot[0, bf], p.lambda_l2, l2_sorted)
    return (best_gain.astype(jnp.float32), bf, left_g, left_h, left_c,
            words, l2_out)


# ------------------------------------------------- fused split epilogue
#
# The split-finding epilogue of the fused Pallas histogram pipeline
# (ops/pallas_hist.py): after the kernel's last grid step accumulates a
# tile's histogram planes in VMEM, the NUMERICAL threshold scan below runs
# in-kernel and reduces each (leaf, feature) to one best candidate — only
# the tiny [P, F, CAND_CHANNELS] table returns to the grower's split
# phase, never the [F, B, S] planes. The same function is the XLA twin
# for the non-Pallas backends (models/grower.py tile_pass under
# ``split_fusion``), so the two paths are the SAME jnp ops on the same
# plane values — bit-identical tables by construction.
#
# Division of labor with find_best_splits (which stays the one place for
# categorical / EFB-bundle / forced-split / CEGB / extra_trees semantics;
# the grower only enables the fused path when none of those apply):
#   in the scan (per-bin, must precede the per-feature reduction):
#     missing-type bin exclusion, both-direction cumulative sums, gains
#     with l1/l2/max_delta_step/path_smooth, basic-monotone clip +
#     violation zeroing, min_data/min_hessian masks, threshold-range
#     masks, strict gain > min_gain_shift, NaN rejection, and the
#     reference's within-feature tie order (reverse scan first, highest
#     threshold; forward strictly-greater, lowest threshold).
#   deferred to candidates_to_splitinfo (whole-feature/whole-leaf
#     multiplicative or masking transforms that cannot change the
#     within-feature argmax): feature_contri, the monotone depth penalty,
#     feature_mask/interaction masks, the max_depth gate, and the
#     cross-feature lowest-index-wins argmax — applied in exactly the
#     order find_best_splits applies them, so a fused and a classic run
#     pick the same candidate with the same stored gain bits.

# candidate-table channel layout ([..., CAND_CHANNELS] float32): gain is
# the SHIFTED raw gain (gain - min_gain_shift; K_MIN_SCORE = invalid),
# threshold/is_rev stored as exact small-integer floats. 12 channels (10
# used + 2 pad) keep the per-leaf table at exactly 1/(B/4) of the
# [F, B, 3] plane bytes the classic search streams — the ISSUE 12
# acceptance floor, asserted from the REAL returned buffers in
# kernel_bench and the fusion tests
CAND_CHANNELS = 12
CAND_GAIN, CAND_THR, CAND_REV = 0, 1, 2
CAND_LG, CAND_LH, CAND_LC = 3, 4, 5
CAND_RG, CAND_RH, CAND_RC = 6, 7, 8


def excluded_bins(pos, num_bins, missing_type, default_bin):
    """Mask of the bins the directional scans skip: the NaN bin
    (NA_AS_MISSING) or the default bin (SKIP_DEFAULT_BIN) of a two-scan
    feature. Integer selects only — no bool broadcasts — so the same
    code lowers inside the Pallas kernel, where the per-feature values
    are scalars."""
    mode_a = (num_bins > 2) & (missing_type != MISSING_NONE)
    nan_bin = jnp.where(mode_a & (missing_type == MISSING_NAN),
                        num_bins - 1, -1)
    zero_bin = jnp.where(mode_a & (missing_type == MISSING_ZERO),
                         default_bin, -1)
    return (pos == nan_bin) | (pos == zero_bin)


def scan_candidates(cs_g, cs_h, cs_c, tot_g, tot_h, tot_c, pos, axis: int,
                    B: int, leaf_sum_g, leaf_sum_h, leaf_cnt, leaf_output,
                    num_bins, missing_type, default_bin, monotone,
                    p: SplitParams, *, with_monotone: bool = False,
                    leaf_min=None, leaf_max=None):
    """Best numerical split candidate along the bin axis — the
    layout-agnostic core of the fused split epilogue. The XLA twin calls
    it on [P, F, B] planes (``axis=2``), the Pallas kernel on one
    feature's [B, lanes] slab (``axis=0``, per-feature values as
    scalars); every operand only has to broadcast, and every op is
    elementwise or a max along ``axis``, so both run the SAME arithmetic
    (the parity suite pins them bit for bit) and Mosaic can lower it.

    Args:
      cs_g/h/c: prefix sums (ops/split.py prefix_sum) of the three
        channels with the excluded bins zeroed; tot_*: their value at bin
        B-1.
      pos: int32 bin index along ``axis``; B: number of real bins (rows
        past B are kernel padding and never win).
      leaf_*: the leaf aggregates; num_bins/missing_type/default_bin/
        monotone: int32 per-feature metadata.

    Returns (gain, threshold, is_rev, left g/h/c, right g/h/c), each
    float32 with ``axis`` reduced to size 1. gain is the SHIFTED raw gain
    (K_MIN_SCORE = no valid candidate); the tie order is the reference's
    (reverse scan first keeping the highest threshold, forward replacing
    only on strictly greater gain, lowest threshold first).
    """
    mode_a = (num_bins > 2) & (missing_type != MISSING_NONE)
    s = _sums_from_prefix(cs_g, cs_h, cs_c, tot_g, tot_h, tot_c,
                          leaf_sum_g, leaf_sum_h, leaf_cnt)

    def clip_out(out):
        if not with_monotone:
            return out
        return jnp.clip(out, leaf_min, leaf_max)

    def split_gain_dir(prefix):
        lg, lh, lc = (s[f"{prefix}_left_g"], s[f"{prefix}_left_h"],
                      s[f"{prefix}_left_c"])
        rg, rh, rc = (s[f"{prefix}_right_g"], s[f"{prefix}_right_h"],
                      s[f"{prefix}_right_c"])
        lo = clip_out(calculate_leaf_output(lg, lh, p, lc, leaf_output))
        ro = clip_out(calculate_leaf_output(rg, rh, p, rc, leaf_output))
        gain = (leaf_gain_given_output(lg, lh, lo, p)
                + leaf_gain_given_output(rg, rh, ro, p))
        if with_monotone:
            viol = (((monotone > 0) & (lo > ro))
                    | ((monotone < 0) & (lo < ro)))
            gain = jnp.where(viol, 0.0, gain)
        return gain

    gain_fwd = split_gain_dir("fwd")
    gain_rev = split_gain_dir("rev")

    min_gain_shift = (leaf_gain(leaf_sum_g, leaf_sum_h, p, leaf_cnt,
                                leaf_output) + p.min_gain_to_split)

    def constraint_mask(prefix):
        lh, lc = s[f"{prefix}_left_h"], s[f"{prefix}_left_c"]
        rh, rc = s[f"{prefix}_right_h"], s[f"{prefix}_right_c"]
        return ((lc >= p.min_data_in_leaf) & (rc >= p.min_data_in_leaf)
                & (lh >= p.min_sum_hessian_in_leaf)
                & (rh >= p.min_sum_hessian_in_leaf))

    # threshold ranges (module docstring): forward candidates exist only
    # for two-scan features; the reverse scan stops one short of a NaN
    # bin; a skipped default bin is no threshold in either direction
    fwd_upper = jnp.where(mode_a, num_bins - 2, -1)
    rev_upper = num_bins - 2 - jnp.where(
        mode_a & (missing_type == MISSING_NAN), 1, 0)
    skip_bin = jnp.where(mode_a & (missing_type == MISSING_ZERO),
                         default_bin, -1)
    fwd_ok = (pos <= fwd_upper) & (pos != skip_bin)
    rev_ok = (pos <= rev_upper) & (pos != skip_bin)

    valid_fwd = (constraint_mask("fwd") & fwd_ok
                 & (gain_fwd > min_gain_shift) & ~jnp.isnan(gain_fwd))
    valid_rev = (constraint_mask("rev") & rev_ok
                 & (gain_rev > min_gain_shift) & ~jnp.isnan(gain_rev))
    key_fwd = jnp.where(valid_fwd, gain_fwd - min_gain_shift, K_MIN_SCORE)
    key_rev = jnp.where(valid_rev, gain_rev - min_gain_shift, K_MIN_SCORE)

    # lexicographic reduction as masked maxima (no argmax / gather, which
    # Mosaic cannot lower): preference values match find_best_splits'
    # tpref — reverse [2B, 3B) above forward [0, B) — and are exact in f32
    best = jnp.maximum(jnp.max(key_rev, axis=axis, keepdims=True),
                       jnp.max(key_fwd, axis=axis, keepdims=True))
    real = pos < B
    posf = pos.astype(jnp.float32)
    pref_rev = jnp.where((key_rev == best) & real, 2.0 * B + posf, -1.0)
    pref_fwd = jnp.where((key_fwd == best) & real, (B - 1.0) - posf, -1.0)
    bpref = jnp.maximum(jnp.max(pref_rev, axis=axis, keepdims=True),
                        jnp.max(pref_fwd, axis=axis, keepdims=True))
    is_rev = bpref >= 2.0 * B
    bt = jnp.where(is_rev, bpref - 2.0 * B, (B - 1.0) - bpref)
    at_bt = posf == bt

    def pick(name):
        v = jnp.where(is_rev, s[f"rev_{name}"], s[f"fwd_{name}"])
        return jnp.max(jnp.where(at_bt, v, K_MIN_SCORE), axis=axis,
                       keepdims=True)

    return (best.astype(jnp.float32), bt, is_rev.astype(jnp.float32),
            pick("left_g"), pick("left_h"), pick("left_c"),
            pick("right_g"), pick("right_h"), pick("right_c"))


def numerical_candidates(hist, leaf_sum_g, leaf_sum_h, leaf_cnt, leaf_output,
                         num_bins_f, missing_type_f, default_bin_f,
                         monotone_f, p: SplitParams, *,
                         with_monotone: bool = False,
                         leaf_min=None, leaf_max=None) -> jax.Array:
    """Per-(leaf, feature) best numerical split candidate — the XLA twin
    of the in-kernel epilogue: scan_candidates over [P, F, B] planes (the
    fused-vs-classic bit-parity suite pins the agreement with
    find_best_splits' numerical scan).

    Args:
      hist: [P, F, B, 3] float32 histogram planes (excluded bins NOT yet
        zeroed — done here, like find_best_splits).
      leaf_sum_g/h/cnt/output: [P] leaf aggregates for the tile's slots.
      num_bins_f/missing_type_f/default_bin_f/monotone_f: [F] int32 (the
        FeatureMeta columns).
      p: SplitParams (only the 7 numerical-scan fields are read).
      with_monotone: static; basic-mode [P] output bounds.

    Returns:
      [P, F, CAND_CHANNELS] float32 candidate table (see CAND_*).
    """
    P, F, B, _ = hist.shape
    pos = jnp.arange(B, dtype=jnp.int32)[None, None, :]
    per_f = [a[None, :, None] for a in (num_bins_f, missing_type_f,
                                        default_bin_f, monotone_f)]
    excl = excluded_bins(pos, *per_f[:3])
    cs = prefix_sum(jnp.where(excl[..., None], 0.0, hist), 2)
    tot = cs[:, :, -1:, :]

    def per_leaf(a):
        return None if a is None else a[:, None, None]

    chans = scan_candidates(
        cs[..., 0], cs[..., 1], cs[..., 2],
        tot[..., 0], tot[..., 1], tot[..., 2], pos, 2, B,
        per_leaf(leaf_sum_g), per_leaf(leaf_sum_h), per_leaf(leaf_cnt),
        per_leaf(leaf_output), *per_f, p, with_monotone=with_monotone,
        leaf_min=per_leaf(leaf_min), leaf_max=per_leaf(leaf_max))
    out = jnp.stack([c[:, :, 0] for c in chans], axis=2)
    return jnp.pad(out, ((0, 0), (0, 0), (0, CAND_CHANNELS - len(chans))))


def candidates_to_splitinfo(cand, leaf_sum_g, leaf_sum_h, leaf_cnt,
                            leaf_output, leaf_depth, meta: FeatureMeta,
                            p: SplitParams, feature_mask, max_depth: int = -1,
                            cat_words: int = CAT_BITSET_WORDS,
                            with_monotone: bool = False,
                            leaf_min=None, leaf_max=None) -> SplitInfo:
    """Cross-feature argmax over a candidate table -> per-leaf SplitInfo.

    Applies the transforms find_best_splits folds into its keyed gains —
    feature_contri, the monotone depth penalty, feature/depth masking —
    in the same order, then the cross-feature lowest-index-wins argmax
    (the reference's in-order feature loop with strict operator>). The
    candidates' within-feature selection already happened in the scan, so
    only whole-feature transforms that COMMUTE with it are legal here:
    the contri multiplier commutes only when positive (the reference
    itself applies penalty post-scan, feature_histogram.hpp:94, but
    find_best_splits applies it per bin — the gbdt resolver keeps
    non-positive feature_contri on the classic phase), and the monotone
    depth penalty is floored at K_EPSILON > 0. The fused-vs-classic
    bit-parity suite pins the equivalence.

    Args:
      cand: [P, F, CAND_CHANNELS] from numerical_candidates.
      feature_mask: [P, F] bool/float validity.
    """
    P, F, _ = cand.shape
    raw = cand[:, :, CAND_GAIN]
    valid = jnp.isfinite(raw)
    contri = meta.penalty[None, :]
    mono_pen = monotone_split_penalty(leaf_depth, p)[:, None]
    is_mono = (meta.monotone != 0)[None, :]
    key = raw * contri
    key = jnp.where(is_mono, key * mono_pen, key)

    fmask = feature_mask.astype(bool) & ~meta.is_categorical[None, :]
    depth_ok = (jnp.ones((P,), bool) if max_depth <= 0
                else (leaf_depth < max_depth))
    key = jnp.where(valid & fmask & depth_ok[:, None], key, K_MIN_SCORE)

    best_gain = jnp.max(key, axis=1)
    is_best = key == best_gain[:, None]
    fpref = (F - 1) - jnp.arange(F, dtype=jnp.int32)[None, :]
    bf = jnp.argmax(jnp.where(is_best, fpref, -1), axis=1).astype(jnp.int32)

    li = jnp.arange(P)
    row = cand[li, bf]                                       # [P, CAND]
    bt = row[:, CAND_THR].astype(jnp.int32)
    bdir_rev = row[:, CAND_REV] > 0.5
    left_g, left_h, left_c = row[:, CAND_LG], row[:, CAND_LH], row[:, CAND_LC]
    right_g, right_h, right_c = (row[:, CAND_RG], row[:, CAND_RH],
                                 row[:, CAND_RC])

    left_out = calculate_leaf_output(left_g, left_h, p, left_c, leaf_output)
    right_out = calculate_leaf_output(right_g, right_h, p, right_c,
                                      leaf_output)
    if with_monotone:
        left_out = jnp.clip(left_out, leaf_min, leaf_max)
        right_out = jnp.clip(right_out, leaf_min, leaf_max)

    mode_a = (meta.num_bins > 2) & (meta.missing_type != MISSING_NONE)
    nan_single = ((meta.missing_type == MISSING_NAN) & ~mode_a)[bf]
    default_left = bdir_rev & ~nan_single

    return SplitInfo(
        gain=best_gain.astype(jnp.float32),
        feature=bf,
        threshold=bt,
        default_left=default_left,
        left_sum_g=left_g, left_sum_h=left_h, left_count=left_c,
        right_sum_g=right_g, right_sum_h=right_h, right_count=right_c,
        left_output=left_out, right_output=right_out,
        is_cat=jnp.zeros((P,), dtype=bool),
        cat_bitset=jnp.zeros((P, cat_words), dtype=jnp.uint32),
        seg_lo=jnp.full((P,), -1, jnp.int32),
        seg_hi=jnp.full((P,), -1, jnp.int32),
    )


def monotone_split_penalty(leaf_depth, p: SplitParams):
    """Depth-decaying gain multiplier for splits on monotone features
    (reference: monotone_constraints.hpp:355-364)."""
    d = leaf_depth.astype(jnp.float32)
    pen = p.monotone_penalty
    small = 1.0 - pen / jnp.exp2(d) + K_EPSILON
    large = 1.0 - jnp.exp2(pen - 1.0 - d) + K_EPSILON
    out = jnp.where(pen <= 1.0, small, large)
    out = jnp.where(pen >= d + 1.0, K_EPSILON, out)
    return jnp.where(pen > 0.0, out, 1.0)


@jax.named_scope("split_sync")
def sync_best_splits(info: SplitInfo, axis_name: str) -> SplitInfo:
    """Allreduce-argmax of per-leaf best splits across a mesh axis — the SPMD
    analog of the reference's SyncUpGlobalBestSplit allreduce over serialized
    SplitInfo blobs (reference: parallel_tree_learner.h:191-214; reducer
    keeps the destination on ties, i.e. the lower rank wins). Used by the
    feature-parallel learner where each device searched its own feature
    slice."""
    gathered = jax.tree.map(
        lambda x: jax.lax.all_gather(x, axis_name), info)   # [D, L, ...]
    gains = gathered.gain                                   # [D, L]
    ndev = gains.shape[0]
    # winner = max gain; ties -> lowest device rank (strict-greater reducer)
    order = jnp.arange(ndev, dtype=jnp.int32)[:, None]
    best_gain = jnp.max(gains, axis=0)
    is_best = gains == best_gain[None, :]
    win = jnp.argmax(jnp.where(is_best, ndev - order, 0), axis=0)  # [L]
    li = jnp.arange(gains.shape[1])
    return jax.tree.map(lambda x: x[win, li], gathered)


def per_feature_best_gain_key(gains_rev: jax.Array, gains_fwd: jax.Array
                              ) -> jax.Array:
    """Best adjusted gain per (leaf, feature) over all numerical candidates
    — the quantity the voting-parallel learner votes on (reference:
    voting_parallel_tree_learner.cpp:137-150 local gains for GlobalVoting)."""
    return jnp.maximum(jnp.max(gains_rev, axis=2), jnp.max(gains_fwd, axis=2))


def find_best_splits(hist: jax.Array, leaf_sum_g, leaf_sum_h, leaf_cnt,
                     leaf_output, leaf_depth, meta: FeatureMeta, p: SplitParams,
                     feature_mask: jax.Array, max_depth: int = -1,
                     with_categorical: bool = False,
                     cat_words: int = CAT_BITSET_WORDS,
                     leaf_min=None, leaf_max=None,
                     adv_bounds=None,
                     gain_adjust=None, rand_bin=None,
                     bundle: BundleMeta | None = None,
                     return_feature_gains: bool = False):
    """Best split per leaf over all numerical features.

    Args:
      hist: [L, F, B, 3] (grad, hess, count).
      leaf_sum_g/h/cnt/output/depth: [L] current leaf aggregates.
      feature_mask: [F] or [L, F] float/bool validity (col sampling,
        per-node sampling, interaction constraints).
      max_depth: static; leaves at max_depth get gain -inf (reference:
        serial_tree_learner.cpp BeforeFindBestSplit depth guard).
      leaf_min/leaf_max: [L] monotone output bounds; when set (static),
        candidate outputs are clipped and monotone-violating candidates
        rejected (reference: feature_histogram.hpp:766-824 GetSplitGains
        with USE_MC + BasicConstraint clip).
      adv_bounds: optional (lmin, lmax, rmin, rmax) [L, F, B] per-threshold
        child output bounds for the ADVANCED monotone mode (reference:
        CumulativeFeatureConstraint Get{Left,Right}{Min,Max} per threshold,
        monotone_constraints.hpp:144-259); overrides the [L] clip for the
        numerical search.
      gain_adjust: [L, F] additive penalty subtracted from the stored gain
        (the CEGB delta, cost_effective_gradient_boosting.hpp:66-84).
      rand_bin: [L, F] int32 forced random threshold for extra_trees
      (feature_histogram.hpp USE_RAND): only that bin is a candidate.
    Returns SplitInfo with arrays of shape [L].
    """
    L, F, B, _ = hist.shape
    nb = meta.num_bins[None, :, None]                      # [1, F, 1]
    bins = jnp.arange(B, dtype=jnp.int32)[None, None, :]   # [1, 1, B]

    mode_a = (meta.num_bins > 2) & (meta.missing_type != MISSING_NONE)   # [F]
    is_nan = meta.missing_type == MISSING_NAN
    is_zero = meta.missing_type == MISSING_ZERO

    excl = jnp.zeros((1, F, B), dtype=bool)
    excl = excl | (mode_a & is_nan)[None, :, None] & (bins == nb - 1)
    excl = excl | (mode_a & is_zero)[None, :, None] & (bins == meta.default_bin[None, :, None])
    hist_excl = jnp.where(excl[:, :, :, None], 0.0, hist)

    s = _directional_sums(hist_excl, leaf_sum_g, leaf_sum_h, leaf_cnt, bundle)

    parent_out = leaf_output[:, None, None]

    use_mc = leaf_min is not None or adv_bounds is not None

    def clip_out(out, side):
        if adv_bounds is not None:
            lmin_a, lmax_a, rmin_a, rmax_a = adv_bounds
            mn, mx = ((lmin_a, lmax_a) if side == "left"
                      else (rmin_a, rmax_a))
            return jnp.clip(out, mn, mx)
        if leaf_min is None:
            return out
        return jnp.clip(out, leaf_min[:, None, None], leaf_max[:, None, None])

    def split_gain_dir(prefix):
        lg, lh, lc = s[f"{prefix}_left_g"], s[f"{prefix}_left_h"], s[f"{prefix}_left_c"]
        rg, rh, rc = s[f"{prefix}_right_g"], s[f"{prefix}_right_h"], s[f"{prefix}_right_c"]
        lo = clip_out(calculate_leaf_output(lg, lh, p, lc, parent_out), "left")
        ro = clip_out(calculate_leaf_output(rg, rh, p, rc, parent_out), "right")
        gain = (leaf_gain_given_output(lg, lh, lo, p)
                + leaf_gain_given_output(rg, rh, ro, p))
        if use_mc:
            mono = meta.monotone[None, :, None].astype(jnp.int32)
            viol = (((mono > 0) & (lo > ro)) | ((mono < 0) & (lo < ro)))
            gain = jnp.where(viol, 0.0, gain)   # GetSplitGains returns 0
        return gain

    gain_fwd = split_gain_dir("fwd")
    gain_rev = split_gain_dir("rev")

    min_gain_shift = (leaf_gain(leaf_sum_g, leaf_sum_h, p, leaf_cnt, leaf_output)
                      + p.min_gain_to_split)[:, None, None]

    def constraint_mask(prefix):
        lh, lc = s[f"{prefix}_left_h"], s[f"{prefix}_left_c"]
        rh, rc = s[f"{prefix}_right_h"], s[f"{prefix}_right_c"]
        return ((lc >= p.min_data_in_leaf) & (rc >= p.min_data_in_leaf)
                & (lh >= p.min_sum_hessian_in_leaf) & (rh >= p.min_sum_hessian_in_leaf))

    valid_fwd = constraint_mask("fwd")
    valid_rev = constraint_mask("rev")

    # threshold-range masks (see module docstring for the scan ranges)
    thr_ok_common = bins <= nb - 2
    fwd_ok = mode_a[None, :, None] & thr_ok_common
    rev_upper = nb - 2 - (mode_a & is_nan)[None, :, None].astype(jnp.int32)
    rev_ok = bins <= rev_upper
    zero_thr_skip = (mode_a & is_zero)[None, :, None] & (bins == meta.default_bin[None, :, None])
    fwd_ok = fwd_ok & ~zero_thr_skip
    rev_ok = rev_ok & ~zero_thr_skip
    if bundle is not None:
        # bundle columns: per-bin direction masks reproduce each member's
        # unbundled candidate set exactly (see BundleMeta docstring)
        isb = bundle.is_bundle[None, :, None]
        fwd_ok = jnp.where(isb, bundle.fwd_ok[None, :, :], fwd_ok)
        rev_ok = jnp.where(isb, bundle.rev_ok[None, :, :], rev_ok)
    if rand_bin is not None:   # extra_trees: only the random threshold
        rb = rand_bin[:, :, None]
        fwd_ok = fwd_ok & (bins == rb)
        rev_ok = rev_ok & (bins == rb)

    fmask = feature_mask
    if fmask.ndim == 1:
        fmask = fmask[None, :]
    fmask = (fmask.astype(bool) & ~meta.is_categorical)[..., None]   # [L|1, F, 1]

    depth_ok = jnp.ones((L,), dtype=bool) if max_depth <= 0 else (leaf_depth < max_depth)
    base_ok = fmask & depth_ok[:, None, None]

    valid_fwd = valid_fwd & fwd_ok & base_ok & (gain_fwd > min_gain_shift) & ~jnp.isnan(gain_fwd)
    valid_rev = valid_rev & rev_ok & base_ok & (gain_rev > min_gain_shift) & ~jnp.isnan(gain_rev)

    # ---- adjusted "key" gains: the stored gain after per-feature contri
    # multiplier (feature_histogram.hpp:94 output->gain *= meta->penalty),
    # minus the CEGB delta (serial_tree_learner.cpp:740-744), times the
    # monotone penalty (serial_tree_learner.cpp:745-749). Cross-feature and
    # cross-leaf comparisons all happen on these adjusted gains.
    contri = meta.penalty[None, :, None]
    mono_pen = monotone_split_penalty(leaf_depth, p)[:, None, None]
    is_mono = (meta.monotone != 0)[None, :, None]

    def keyed(gain, valid):
        key = (gain - min_gain_shift) * contri
        if gain_adjust is not None:
            key = key - gain_adjust[:, :, None]
        key = jnp.where(is_mono, key * mono_pen, key)
        return jnp.where(valid, key, K_MIN_SCORE)

    gain_fwd = keyed(gain_fwd, valid_fwd)
    gain_rev = keyed(gain_rev, valid_rev)

    # ---- lexicographic argmax reproducing the reference's scan tie order:
    # reverse scan runs first and keeps the first (=highest-threshold) maximum;
    # forward replaces only on strictly greater gain (lowest threshold first).
    # Across features: lowest feature index wins ties
    # (serial_tree_learner.cpp:374-448 feature loop with strict operator>).
    gains = jnp.stack([gain_rev, gain_fwd], axis=2)          # [L, F, 2, B]
    if bundle is not None:
        # bundled datasets: host-built preference tables ordered by each
        # candidate's ORIGINAL owner feature + its unbundled scan order,
        # so gain ties resolve exactly as the unbundled run's would (see
        # BundleMeta docstring)
        pref = jnp.stack([bundle.pref_rev, bundle.pref_fwd],
                         axis=1)[None]                       # [1, F, 2, B]
    else:
        farange = jnp.arange(F, dtype=jnp.int32)[None, :, None, None]
        tpref = jnp.stack([bins, (B - 1) - bins], axis=2)    # rev: high t; fwd: low t
        pref = ((F - 1) - farange) * (4 * B) + jnp.stack(
            [jnp.full_like(bins, 2 * B), jnp.zeros_like(bins)], axis=2) + tpref

    flat_gains = gains.reshape(L, -1)
    best_gain = jnp.max(flat_gains, axis=1)
    is_best = flat_gains == best_gain[:, None]
    flat_pref = jnp.broadcast_to(pref, gains.shape).reshape(L, -1)
    best_idx = jnp.argmax(jnp.where(is_best, flat_pref, -1), axis=1)

    bf = (best_idx // (2 * B)).astype(jnp.int32)             # feature
    rem = best_idx % (2 * B)
    bdir = (rem // B).astype(jnp.int32)                      # 0=rev, 1=fwd
    bt = (rem % B).astype(jnp.int32)                         # threshold bin

    li = jnp.arange(L)

    def pick(rev_name, fwd_name):
        rev_v = s[rev_name][li, bf, bt]
        fwd_v = s[fwd_name][li, bf, bt]
        return jnp.where(bdir == 0, rev_v, fwd_v)

    left_g = pick("rev_left_g", "fwd_left_g")
    left_h = pick("rev_left_h", "fwd_left_h")
    left_c = pick("rev_left_c", "fwd_left_c")
    right_g = pick("rev_right_g", "fwd_right_g")
    right_h = pick("rev_right_h", "fwd_right_h")
    right_c = pick("rev_right_c", "fwd_right_c")

    left_out = calculate_leaf_output(left_g, left_h, p, left_c, leaf_output)
    right_out = calculate_leaf_output(right_g, right_h, p, right_c, leaf_output)
    if adv_bounds is not None:
        lmin_a, lmax_a, rmin_a, rmax_a = adv_bounds
        left_out = jnp.clip(left_out, lmin_a[li, bf, bt], lmax_a[li, bf, bt])
        right_out = jnp.clip(right_out, rmin_a[li, bf, bt],
                             rmax_a[li, bf, bt])
    elif use_mc:
        left_out = jnp.clip(left_out, leaf_min, leaf_max)
        right_out = jnp.clip(right_out, leaf_min, leaf_max)

    # default_left: reverse scan => True; forced False for NaN single-scan mode
    # (feature_histogram.hpp:199-210)
    nan_single = (is_nan & ~mode_a)[bf]
    default_left = (bdir == 0) & ~nan_single

    if bundle is not None:
        chose_bundle = bundle.is_bundle[bf]
        seg_lo_out = jnp.where(chose_bundle, bundle.seg_lo[bf, bt], -1)
        seg_hi_out = jnp.where(chose_bundle, bundle.seg_hi[bf, bt], -1)
    else:
        seg_lo_out = jnp.full((L,), -1, jnp.int32)
        seg_hi_out = jnp.full((L,), -1, jnp.int32)

    num_info = SplitInfo(
        gain=best_gain.astype(jnp.float32),
        feature=bf,
        threshold=bt,
        default_left=default_left,
        left_sum_g=left_g, left_sum_h=left_h, left_count=left_c,
        right_sum_g=right_g, right_sum_h=right_h, right_count=right_c,
        left_output=left_out, right_output=right_out,
        is_cat=jnp.zeros((L,), dtype=bool),
        cat_bitset=jnp.zeros((L, cat_words), dtype=jnp.uint32),
        seg_lo=seg_lo_out.astype(jnp.int32),
        seg_hi=seg_hi_out.astype(jnp.int32),
    )
    if not with_categorical:
        if return_feature_gains:
            return num_info, per_feature_best_gain_key(gain_rev, gain_fwd)
        return num_info

    # the categorical search, and per leaf the better of its best and the
    # numerical one
    with jax.named_scope("cat_search"):
        (cgain, cfeat, clg, clh, clc, cbits, cl2) = find_best_cat_splits(
            hist, leaf_sum_g, leaf_sum_h, leaf_cnt, leaf_output, leaf_depth,
            meta, p, feature_mask, max_depth, cat_words,
            gain_adjust=gain_adjust)
        crg = leaf_sum_g - clg
        crh = leaf_sum_h - clh
        crc = leaf_cnt - clc
        clo = calculate_leaf_output(clg, clh, p, clc, leaf_output, cl2)
        cro = calculate_leaf_output(crg, crh, p, crc, leaf_output, cl2)
        if use_mc:
            clo = jnp.clip(clo, leaf_min, leaf_max)
            cro = jnp.clip(cro, leaf_min, leaf_max)
        # per-leaf choice between numerical and categorical bests; ties
        # resolve to the lower feature index (the reference's in-order
        # feature loop with strict operator>,
        # serial_tree_learner.cpp:374-448)
        take_cat = (cgain > num_info.gain) | (
            (cgain == num_info.gain) & jnp.isfinite(cgain) & (cfeat < num_info.feature))

        def sel(cv, nv):
            cond = take_cat
            while cond.ndim < cv.ndim:
                cond = cond[..., None]
            return jnp.where(cond, cv, nv)

        merged = SplitInfo(
            gain=sel(cgain, num_info.gain),
            feature=sel(cfeat, num_info.feature),
            threshold=sel(jnp.zeros((L,), jnp.int32), num_info.threshold),
            default_left=sel(jnp.zeros((L,), bool), num_info.default_left),
            left_sum_g=sel(clg, num_info.left_sum_g),
            left_sum_h=sel(clh, num_info.left_sum_h),
            left_count=sel(clc, num_info.left_count),
            right_sum_g=sel(crg, num_info.right_sum_g),
            right_sum_h=sel(crh, num_info.right_sum_h),
            right_count=sel(crc, num_info.right_count),
            left_output=sel(clo, num_info.left_output),
            right_output=sel(cro, num_info.right_output),
            is_cat=take_cat,
            cat_bitset=sel(cbits, num_info.cat_bitset),
            seg_lo=sel(jnp.full((L,), -1, jnp.int32), num_info.seg_lo),
            seg_hi=sel(jnp.full((L,), -1, jnp.int32), num_info.seg_hi),
        )
    if return_feature_gains:
        return merged, per_feature_best_gain_key(gain_rev, gain_fwd)
    return merged
