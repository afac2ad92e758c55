"""Feature quantization (binning).

Host-side re-implementation of the reference BinMapper semantics
(reference: src/io/bin.cpp:78-520, include/LightGBM/bin.h:61-225):

- ``greedy_find_bin``: equal-count greedy bin boundaries over sampled distinct
  values (reference ``GreedyFindBin``, bin.cpp:78-155).
- ``find_bin_with_zero_as_one_bin``: dedicated zero bin straddling
  ±kZeroThreshold (reference ``FindBinWithZeroAsOneBin``, bin.cpp:256-314).
- Missing handling ``MissingType {None, Zero, NaN}`` (reference bin.h:26): with
  NaN present and ``use_missing``, the LAST bin is the NaN bin
  (bin.cpp:398-402); with ``zero_as_missing`` the zero/default bin doubles as
  the missing bin.
- Categorical: categories sorted by count descending, bin 0 reserved for
  NaN/other (reference bin.cpp:424-490).

Unlike the reference we do NOT elide the most-frequent bin from histogram
storage (``most_freq_bin`` offset machinery, bin.cpp:497-516 + FixHistogram):
the TPU layout keeps dense ``[num_bins]`` histograms per feature, so
``FixHistogram`` reconstruction is unnecessary. ``most_freq_bin_`` is still
computed for sparsity bookkeeping.

Binning the full data matrix is vectorized with ``np.searchsorted`` per
feature (the analog of the per-value binary search ``BinMapper::ValueToBin``,
bin.h:464-502).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence

import numpy as np

from .utils import log

# reference: include/LightGBM/bin.h:30 (kZeroThreshold = 1e-35)
K_ZERO_THRESHOLD = 1e-35
# reference: include/LightGBM/bin.h:39 (kSparseThreshold = 0.7)
K_SPARSE_THRESHOLD = 0.7

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2

BIN_TYPE_NUMERICAL = 0
BIN_TYPE_CATEGORICAL = 1


def _get_double_upper_bound(a: float) -> float:
    """Smallest double strictly greater than a (reference: common.h:830)."""
    return float(np.nextafter(a, np.inf))


def _check_double_equal_ordered(a: float, b: float) -> bool:
    """reference: common.h:825 CheckDoubleEqualOrdered."""
    upper = _get_double_upper_bound(a)
    return a >= b or b <= upper


def need_filter(cnt_in_bin: np.ndarray, total_cnt: int, filter_cnt: int,
                bin_type: int) -> bool:
    """Pre-filter: no threshold leaves >= filter_cnt on both sides
    (reference: bin.cpp:54-76 NeedFilter)."""
    if bin_type == BIN_TYPE_NUMERICAL:
        sum_left = 0
        for i in range(len(cnt_in_bin) - 1):
            sum_left += int(cnt_in_bin[i])
            if sum_left >= filter_cnt and total_cnt - sum_left >= filter_cnt:
                return False
    else:
        if len(cnt_in_bin) <= 2:
            for i in range(len(cnt_in_bin) - 1):
                sum_left = int(cnt_in_bin[i])
                if sum_left >= filter_cnt and total_cnt - sum_left >= filter_cnt:
                    return False
        else:
            return False
    return True


def greedy_find_bin(distinct_values: np.ndarray, counts: np.ndarray, max_bin: int,
                    total_cnt: int, min_data_in_bin: int) -> List[float]:
    """Equal-count greedy bin upper bounds (reference: bin.cpp:78-155 GreedyFindBin)."""
    num_distinct = len(distinct_values)
    bin_upper_bound: List[float] = []
    assert max_bin > 0
    if num_distinct <= max_bin:
        cur_cnt_inbin = 0
        for i in range(num_distinct - 1):
            cur_cnt_inbin += counts[i]
            if cur_cnt_inbin >= min_data_in_bin:
                val = _get_double_upper_bound((distinct_values[i] + distinct_values[i + 1]) / 2.0)
                if not bin_upper_bound or not _check_double_equal_ordered(bin_upper_bound[-1], val):
                    bin_upper_bound.append(val)
                    cur_cnt_inbin = 0
        bin_upper_bound.append(math.inf)
        return bin_upper_bound

    if min_data_in_bin > 0:
        max_bin = min(max_bin, total_cnt // min_data_in_bin)
        max_bin = max(max_bin, 1)
    mean_bin_size = total_cnt / max_bin

    counts = np.asarray(counts, dtype=np.int64)
    rest_bin_cnt = max_bin
    rest_sample_cnt = int(total_cnt)
    is_big_count_value = counts >= mean_bin_size
    rest_bin_cnt -= int(is_big_count_value.sum())
    rest_sample_cnt -= int(counts[is_big_count_value].sum())
    mean_bin_size = rest_sample_cnt / max(rest_bin_cnt, 1)

    # The reference walks the distinct values one by one and closes a bin
    # at the first value i that is a big one, or fills the bin
    # (count so far >= mean_bin_size), or half fills it before a big one.
    # Between two closes nothing the three tests read changes but the
    # running count, so each close is found by binary search in the
    # cumulative counts: one step a BIN, not one a distinct value (a
    # continuous column of a 200,000-row sample has 200,000 of them).
    # Counts are integers, so ``count >= x`` is ``count >= ceil(x)`` and
    # every comparison below is exact.
    last = num_distinct - 1                  # values 0 .. last-1 are walked
    cum = np.cumsum(counts)                  # cum[i]: rows of values 0..i
    big = np.flatnonzero(is_big_count_value)
    cum_small = np.cumsum(np.where(is_big_count_value, 0, counts)) \
        if len(big) else cum
    before_big = big[big > 0] - 1            # values right before a big one
    upper_bounds = [math.inf] * max_bin
    lower_bounds = [math.inf] * max_bin
    bin_cnt = 0
    lower_bounds[0] = float(distinct_values[0])
    start = 0                                # first value of the open bin
    while start < last and max_bin > 1:
        base = int(cum[start - 1]) if start else 0
        # first value that fills the bin
        i = max(start, int(np.searchsorted(
            cum, base + math.ceil(mean_bin_size), side="left")))
        if len(big):
            # first big value
            k = int(np.searchsorted(big, start, side="left"))
            if k < len(big):
                i = min(i, int(big[k]))
            # first value before a big one with the bin half full
            half = int(np.searchsorted(
                cum, base + math.ceil(max(1.0, mean_bin_size * 0.5)),
                side="left"))
            k = int(np.searchsorted(before_big, max(half, start),
                                    side="left"))
            if k < len(before_big):
                i = min(i, int(before_big[k]))
        if i >= last:
            break
        small_before = int(cum_small[start - 1]) if start else 0
        rest_sample_cnt -= int(cum_small[i]) - small_before
        upper_bounds[bin_cnt] = float(distinct_values[i])
        bin_cnt += 1
        lower_bounds[bin_cnt] = float(distinct_values[i + 1])
        if bin_cnt >= max_bin - 1:
            break
        if not is_big_count_value[i]:
            rest_bin_cnt -= 1
            mean_bin_size = rest_sample_cnt / max(rest_bin_cnt, 1)
        start = i + 1
    bin_cnt += 1
    for i in range(bin_cnt - 1):
        val = _get_double_upper_bound((upper_bounds[i] + lower_bounds[i + 1]) / 2.0)
        if not bin_upper_bound or not _check_double_equal_ordered(bin_upper_bound[-1], val):
            bin_upper_bound.append(val)
    bin_upper_bound.append(math.inf)
    return bin_upper_bound


def find_bin_with_zero_as_one_bin(distinct_values: np.ndarray, counts: np.ndarray,
                                  max_bin: int, total_sample_cnt: int,
                                  min_data_in_bin: int,
                                  forced_bounds: Optional[Sequence[float]] = None) -> List[float]:
    """Bin bounds with a dedicated zero bin (reference: bin.cpp:256-314)."""
    if forced_bounds:
        return _find_bin_with_predefined(distinct_values, counts, max_bin,
                                         total_sample_cnt, min_data_in_bin,
                                         list(forced_bounds))
    left_mask = distinct_values <= -K_ZERO_THRESHOLD
    right_mask = distinct_values > K_ZERO_THRESHOLD
    left_cnt_data = int(counts[left_mask].sum())
    cnt_zero = int(counts[~left_mask & ~right_mask].sum())
    right_cnt_data = int(counts[right_mask].sum())

    nz = np.nonzero(distinct_values > -K_ZERO_THRESHOLD)[0]
    left_cnt = int(nz[0]) if len(nz) else len(distinct_values)

    bin_upper_bound: List[float] = []
    if left_cnt > 0 and max_bin > 1:
        denom = max(total_sample_cnt - cnt_zero, 1)
        left_max_bin = max(1, int(left_cnt_data / denom * (max_bin - 1)))
        bin_upper_bound = greedy_find_bin(distinct_values[:left_cnt], counts[:left_cnt],
                                          left_max_bin, left_cnt_data, min_data_in_bin)
        if bin_upper_bound:
            bin_upper_bound[-1] = -K_ZERO_THRESHOLD

    nz = np.nonzero(distinct_values[left_cnt:] > K_ZERO_THRESHOLD)[0]
    right_start = (left_cnt + int(nz[0])) if len(nz) else -1

    right_max_bin = max_bin - 1 - len(bin_upper_bound)
    if right_start >= 0 and right_max_bin > 0:
        right_bounds = greedy_find_bin(distinct_values[right_start:], counts[right_start:],
                                       right_max_bin, right_cnt_data, min_data_in_bin)
        bin_upper_bound.append(K_ZERO_THRESHOLD)
        bin_upper_bound.extend(right_bounds)
    else:
        bin_upper_bound.append(math.inf)
    assert len(bin_upper_bound) <= max_bin
    return bin_upper_bound


def _find_bin_with_predefined(distinct_values: np.ndarray, counts: np.ndarray,
                              max_bin: int, total_sample_cnt: int,
                              min_data_in_bin: int,
                              forced_bounds: List[float]) -> List[float]:
    """Forced bin bounds + proportional greedy fill of each forced segment
    (reference: bin.cpp:157-254 FindBinWithPredefinedBin: zero/inf bounds
    first, forced bounds inserted up to the budget, then the free bins are
    distributed across segments proportional to their sample counts and
    found greedily within each)."""
    nvals = len(distinct_values)
    left_cnt = nvals
    for i in range(nvals):
        if distinct_values[i] > -K_ZERO_THRESHOLD:
            left_cnt = i
            break
    right_start = -1
    for i in range(left_cnt, nvals):
        if distinct_values[i] > K_ZERO_THRESHOLD:
            right_start = i
            break

    bin_upper_bound: List[float] = []
    if max_bin == 2:
        bin_upper_bound.append(K_ZERO_THRESHOLD if left_cnt == 0
                               else -K_ZERO_THRESHOLD)
    elif max_bin >= 3:
        if left_cnt > 0:
            bin_upper_bound.append(-K_ZERO_THRESHOLD)
        if right_start >= 0:
            bin_upper_bound.append(K_ZERO_THRESHOLD)
    bin_upper_bound.append(math.inf)

    max_to_insert = max_bin - len(bin_upper_bound)
    num_inserted = 0
    for b in forced_bounds:
        if num_inserted >= max_to_insert:
            break
        if abs(b) > K_ZERO_THRESHOLD:
            bin_upper_bound.append(float(b))
            num_inserted += 1
    bin_upper_bound.sort()

    free_bins = max_bin - len(bin_upper_bound)
    bounds_to_add: List[float] = []
    value_ind = 0
    for i, ub in enumerate(bin_upper_bound):
        cnt_in_bin = 0
        bin_start = value_ind
        while value_ind < nvals and distinct_values[value_ind] < ub:
            cnt_in_bin += int(counts[value_ind])
            value_ind += 1
        bins_remaining = (max_bin - len(bin_upper_bound)
                          - len(bounds_to_add))
        num_sub_bins = int(round(cnt_in_bin * free_bins
                                 / max(total_sample_cnt, 1)))
        num_sub_bins = min(num_sub_bins, bins_remaining) + 1
        if i == len(bin_upper_bound) - 1:
            num_sub_bins = bins_remaining + 1
        new_ub = greedy_find_bin(distinct_values[bin_start:value_ind],
                                 counts[bin_start:value_ind], num_sub_bins,
                                 cnt_in_bin, min_data_in_bin)
        bounds_to_add.extend(new_ub[:-1])       # last bound is infinity
    out = sorted(bin_upper_bound + bounds_to_add)
    assert len(out) <= max_bin
    return out


def _distinct_sorted(values: np.ndarray):
    """``np.unique(values, return_counts=True)`` of a NaN-free float64
    vector."""
    v = np.sort(values)
    new = v[1:] != v[:-1]
    if new.all():
        return v, np.ones(len(v), dtype=np.int64)
    first = np.flatnonzero(np.concatenate([[True], new]))
    return v[first], np.diff(np.concatenate([first, [len(v)]]))


class BinMapper:
    """Per-feature value→bin mapping (reference: include/LightGBM/bin.h:61-225)."""

    def __init__(self):
        self.num_bin: int = 1
        self.missing_type: int = MISSING_NONE
        self.bin_type: int = BIN_TYPE_NUMERICAL
        self.is_trivial: bool = True
        self.sparse_rate: float = 1.0
        self.bin_upper_bound: np.ndarray = np.array([math.inf])
        self.bin_2_categorical: List[int] = []
        self.categorical_2_bin: Dict[int, int] = {}
        self.default_bin: int = 0       # bin of value 0 (bin.h GetDefaultBin)
        self.most_freq_bin: int = 0
        self.min_val: float = 0.0
        self.max_val: float = 0.0

    # ------------------------------------------------------------------ fit
    def find_bin(self, values: np.ndarray, total_sample_cnt: int, max_bin: int,
                 min_data_in_bin: int = 3, min_split_data: int = 0,
                 pre_filter: bool = False, bin_type: int = BIN_TYPE_NUMERICAL,
                 use_missing: bool = True, zero_as_missing: bool = False,
                 forced_bounds: Optional[Sequence[float]] = None) -> None:
        """Fit the mapper on sampled values (reference: bin.cpp:325-520 FindBin).

        ``values`` are the sampled non-zero entries; ``total_sample_cnt`` is the
        number of sampled rows (zeros implied by the difference, matching the
        reference's sparse sampling protocol, dataset_loader.cpp:953+).
        """
        values = np.asarray(values, dtype=np.float64)
        na_mask = np.isnan(values)
        na_cnt = int(na_mask.sum())
        values = values[~na_mask]
        if len(values):
            vals, counts = _distinct_sorted(values)
        else:
            vals, counts = np.array([]), np.array([], dtype=np.int64)
        self.find_bin_from_distinct(
            vals, counts, na_cnt, total_sample_cnt, max_bin,
            min_data_in_bin=min_data_in_bin, min_split_data=min_split_data,
            pre_filter=pre_filter, bin_type=bin_type, use_missing=use_missing,
            zero_as_missing=zero_as_missing, forced_bounds=forced_bounds)

    def find_bin_from_distinct(self, vals: np.ndarray, counts: np.ndarray,
                               na_cnt: int, total_sample_cnt: int,
                               max_bin: int, min_data_in_bin: int = 3,
                               min_split_data: int = 0,
                               pre_filter: bool = False,
                               bin_type: int = BIN_TYPE_NUMERICAL,
                               use_missing: bool = True,
                               zero_as_missing: bool = False,
                               forced_bounds: Optional[Sequence[float]] = None
                               ) -> None:
        """Fit from a pre-aggregated (sorted distinct values, counts, NaN
        count) summary — the form a streaming :class:`FeatureSketch` holds,
        and exactly what ``find_bin`` computes internally, so a sketch that
        never compacted fits BIT-IDENTICAL mappers to the sampled path.
        ``total_sample_cnt - counts.sum() - na_cnt`` rows are implied zeros
        (the sparse sampling protocol)."""
        vals = np.asarray(vals, dtype=np.float64)
        counts = np.asarray(counts, dtype=np.int64)
        na_cnt = int(na_cnt)

        if not use_missing:
            self.missing_type = MISSING_NONE
        elif zero_as_missing:
            self.missing_type = MISSING_ZERO
        else:
            self.missing_type = MISSING_NAN if na_cnt > 0 else MISSING_NONE

        self.bin_type = bin_type
        self.default_bin = 0
        zero_cnt = int(total_sample_cnt - counts.sum() - na_cnt)

        # distinct values with counts; zero slot positioned in sorted order
        # (reference: bin.cpp:355-395)
        if zero_cnt > 0 or len(vals) == 0:
            if 0.0 not in vals:
                insert_at = int(np.searchsorted(vals, 0.0))
                vals = np.insert(vals, insert_at, 0.0)
                counts = np.insert(counts, insert_at, zero_cnt)
            else:
                counts[np.searchsorted(vals, 0.0)] += zero_cnt
        self.min_val = float(vals[0]) if len(vals) else 0.0
        self.max_val = float(vals[-1]) if len(vals) else 0.0
        counts = counts.astype(np.int64)

        cnt_in_bin: np.ndarray
        if bin_type == BIN_TYPE_NUMERICAL:
            if self.missing_type in (MISSING_ZERO, MISSING_NONE):
                bounds = find_bin_with_zero_as_one_bin(vals, counts, max_bin,
                                                       total_sample_cnt, min_data_in_bin,
                                                       forced_bounds)
                if self.missing_type == MISSING_ZERO and len(bounds) == 2:
                    self.missing_type = MISSING_NONE
            else:  # NaN bin appended as the last bin (bin.cpp:398-402)
                bounds = find_bin_with_zero_as_one_bin(vals, counts, max_bin - 1,
                                                       total_sample_cnt - na_cnt,
                                                       min_data_in_bin, forced_bounds)
                bounds.append(math.nan)
            self.bin_upper_bound = np.asarray(bounds)
            self.num_bin = len(bounds)
            # count per bin (bin.cpp:404-421)
            n_real = self.num_bin - (1 if self.missing_type == MISSING_NAN else 0)
            finite_bounds = self.bin_upper_bound[:n_real]
            cnt_in_bin = np.zeros(self.num_bin, dtype=np.int64)
            if len(vals):
                # a value goes to the first bin whose upper bound >= it,
                # past the last bound to the last bin: the bounds' places
                # in the sorted values cut the cumulative counts
                ends = np.searchsorted(vals, finite_bounds[:n_real - 1],
                                       side="right")
                run = np.concatenate([[0], np.cumsum(counts)])
                cnt_in_bin[:n_real] = np.diff(
                    np.concatenate([[0], run[ends], run[-1:]]))
            if self.missing_type == MISSING_NAN:
                cnt_in_bin[self.num_bin - 1] = na_cnt
        else:
            # categorical (reference: bin.cpp:424-490)
            vals_int = vals.astype(np.int64)
            neg = vals_int < 0
            if neg.any():
                log.warning("Met negative value in categorical features, will convert it to NaN")
                na_cnt += int(counts[neg].sum())
                vals_int, counts = vals_int[~neg], counts[~neg]
            # merge duplicates after int cast
            if len(vals_int):
                vals_int_u, inv = np.unique(vals_int, return_inverse=True)
                counts_u = np.zeros(len(vals_int_u), dtype=np.int64)
                np.add.at(counts_u, inv, counts)
            else:
                vals_int_u, counts_u = vals_int, counts
            rest_cnt = total_sample_cnt - na_cnt
            self.bin_2_categorical = [-1]   # bin 0 = NaN/other bin
            self.categorical_2_bin = {-1: 0}
            cnt_list = [0]
            self.num_bin = 1
            if rest_cnt > 0 and len(vals_int_u):
                order = np.argsort(-counts_u, kind="stable")
                cut_cnt = int(round((total_sample_cnt - na_cnt) * 0.99))
                distinct_cnt = len(vals_int_u) + (1 if na_cnt > 0 else 0)
                eff_max_bin = min(distinct_cnt, max_bin)
                used_cnt = 0
                for rank, j in enumerate(order):
                    if not (used_cnt < cut_cnt or self.num_bin < eff_max_bin):
                        break
                    if counts_u[j] < min_data_in_bin and rank > 1:
                        break
                    cat = int(vals_int_u[j])
                    self.bin_2_categorical.append(cat)
                    self.categorical_2_bin[cat] = self.num_bin
                    used_cnt += int(counts_u[j])
                    cnt_list.append(int(counts_u[j]))
                    self.num_bin += 1
                all_used = (self.num_bin - 1) == len(vals_int_u)
                self.missing_type = MISSING_NONE if (all_used and na_cnt == 0) else MISSING_NAN
                cnt_list[0] = int(total_sample_cnt - used_cnt)
            cnt_in_bin = np.asarray(cnt_list, dtype=np.int64)

        # trivial / pre-filter (bin.cpp:494-503)
        self.is_trivial = self.num_bin <= 1
        if (not self.is_trivial and pre_filter
                and need_filter(cnt_in_bin, int(total_sample_cnt),
                                int(min_split_data), bin_type)):
            self.is_trivial = True
        if not self.is_trivial:
            self.default_bin = self.value_to_bin(0.0)
            self.most_freq_bin = int(np.argmax(cnt_in_bin))
            max_sparse_rate = float(cnt_in_bin[self.most_freq_bin]) / max(total_sample_cnt, 1)
            if self.most_freq_bin != self.default_bin and max_sparse_rate < K_SPARSE_THRESHOLD:
                self.most_freq_bin = self.default_bin
            self.sparse_rate = float(cnt_in_bin[self.most_freq_bin]) / max(total_sample_cnt, 1)
        else:
            self.sparse_rate = 1.0

    # ---------------------------------------------------------------- apply
    def value_to_bin(self, value: float) -> int:
        """Scalar value→bin (reference: bin.h:464-502 ValueToBin)."""
        return int(self.values_to_bins(np.array([value]))[0])

    def values_to_bins(self, values: np.ndarray) -> np.ndarray:
        """Vectorized value→bin for a whole column."""
        values = np.asarray(values, dtype=np.float64)
        if self.bin_type == BIN_TYPE_CATEGORICAL:
            out = np.zeros(len(values), dtype=np.int32)
            if self.categorical_2_bin:
                cats = np.array(self.bin_2_categorical[1:], dtype=np.int64)
                bins = np.arange(1, self.num_bin, dtype=np.int32)
                vals_int = np.where(np.isnan(values), -1, values).astype(np.int64)
                if len(cats):
                    sorter = np.argsort(cats)
                    pos = np.searchsorted(cats[sorter], vals_int)
                    pos = np.clip(pos, 0, len(cats) - 1)
                    matched = cats[sorter][pos] == vals_int
                    out = np.where(matched, bins[sorter][pos], 0).astype(np.int32)
            return out
        has_nan_bin = self.missing_type == MISSING_NAN
        n_real = self.num_bin - (1 if has_nan_bin else 0)
        finite_bounds = self.bin_upper_bound[:n_real - 1] if n_real > 0 else np.array([])
        vals = values
        if self.missing_type == MISSING_ZERO:
            # NaN treated as zero → default bin (bin.h:479-481)
            vals = np.where(np.isnan(vals), 0.0, vals)
        idx = np.searchsorted(finite_bounds, vals, side="left").astype(np.int32)
        # value == bound goes to that bin (upper bounds inclusive): searchsorted
        # 'left' puts v==bound at the bound's bin, matching `value <= upper`.
        if has_nan_bin:
            idx = np.where(np.isnan(values), self.num_bin - 1, idx).astype(np.int32)
        return idx

    def bin_to_value(self, bin_idx: int) -> float:
        """Representative threshold value for a bin boundary (used for real-valued
        tree thresholds, reference: tree.h RealThreshold)."""
        if self.bin_type == BIN_TYPE_CATEGORICAL:
            return float(self.bin_2_categorical[bin_idx]) if bin_idx < len(self.bin_2_categorical) else -1.0
        return float(self.bin_upper_bound[bin_idx])

    # ---------------------------------------------------------- serialization
    def to_dict(self) -> dict:
        return {
            "num_bin": self.num_bin,
            "missing_type": self.missing_type,
            "bin_type": self.bin_type,
            "is_trivial": self.is_trivial,
            "sparse_rate": self.sparse_rate,
            "bin_upper_bound": [float(x) for x in self.bin_upper_bound],
            "bin_2_categorical": list(self.bin_2_categorical),
            "default_bin": self.default_bin,
            "most_freq_bin": self.most_freq_bin,
            "min_val": self.min_val,
            "max_val": self.max_val,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "BinMapper":
        m = cls()
        m.num_bin = int(d["num_bin"])
        m.missing_type = int(d["missing_type"])
        m.bin_type = int(d["bin_type"])
        m.is_trivial = bool(d["is_trivial"])
        m.sparse_rate = float(d["sparse_rate"])
        m.bin_upper_bound = np.asarray(d["bin_upper_bound"], dtype=np.float64)
        m.bin_2_categorical = [int(x) for x in d["bin_2_categorical"]]
        m.categorical_2_bin = {c: i for i, c in enumerate(m.bin_2_categorical)}
        m.default_bin = int(d["default_bin"])
        m.most_freq_bin = int(d["most_freq_bin"])
        m.min_val = float(d.get("min_val", 0.0))
        m.max_val = float(d.get("max_val", 0.0))
        return m


def sample_indices(num_data: int, sample_cnt: int, seed: int) -> np.ndarray:
    """Row sample for bin finding (reference: dataset_loader.cpp sampling with
    Random::Sample; here a seeded choice without replacement)."""
    if num_data <= sample_cnt:
        return np.arange(num_data)
    rng = np.random.RandomState(seed)
    return np.sort(rng.choice(num_data, size=sample_cnt, replace=False))


def _find_bin_kwargs(j: int, config, cat_set, filter_cnt: int,
                     forced_bounds=None) -> dict:
    """Per-column binning parameters from the config — the ONE kwargs
    assembly both the sampled (``fit_mapper_for_column``) and the
    streaming (``fit_mappers_from_sketches``) fits use, so a new binning
    parameter cannot reach one construct path and miss the other (the
    bit-parity contract between them depends on it)."""
    return dict(
        max_bin=(config.max_bin_by_feature[j]
                 if j < len(config.max_bin_by_feature) else config.max_bin),
        min_data_in_bin=config.min_data_in_bin,
        min_split_data=filter_cnt,
        pre_filter=config.feature_pre_filter,
        bin_type=(BIN_TYPE_CATEGORICAL if j in cat_set
                  else BIN_TYPE_NUMERICAL),
        use_missing=config.use_missing,
        zero_as_missing=config.zero_as_missing,
        forced_bounds=(forced_bounds or {}).get(j),
    )


def fit_mapper_for_column(j: int, vals: np.ndarray, total_sample_cnt: int,
                          config, cat_set, filter_cnt: int,
                          forced_bounds=None) -> BinMapper:
    """Fit one column's BinMapper with the config's binning parameters —
    the single point both the dense and the sparse/EFB construct paths go
    through (reference: DatasetLoader::ConstructBinMappersFromTextData's
    per-feature FindBin call, dataset_loader.cpp:953-1140)."""
    m = BinMapper()
    m.find_bin(vals, total_sample_cnt=total_sample_cnt,
               **_find_bin_kwargs(j, config, cat_set, filter_cnt,
                                  forced_bounds))
    return m


def filter_cnt_for_sample(config, sample_cnt: int, num_data: int) -> int:
    """reference: dataset_loader.cpp:647-648 filter_cnt scaling."""
    return int(config.min_data_in_leaf * sample_cnt / max(num_data, 1))


# columns a fit_block of find_bin_mappers gathers at once
_FIT_BLOCK = 16


def find_bin_mappers(X: np.ndarray, config, categorical_features: Sequence[int] = (),
                     forced_bounds: Optional[Dict[int, List[float]]] = None) -> List[BinMapper]:
    """Fit one BinMapper per column (reference: DatasetLoader::
    ConstructBinMappersFromTextData, dataset_loader.cpp:953-1140)."""
    num_data, num_features = X.shape
    sample_idx = sample_indices(num_data, config.bin_construct_sample_cnt,
                                config.data_random_seed)
    cat_set = set(int(c) for c in categorical_features)
    filter_cnt = filter_cnt_for_sample(config, len(sample_idx), num_data)

    def fit_block(j0: int) -> List[BinMapper]:
        # the sample's rows of a block of columns, gathered once (a row
        # of the block is contiguous in a row-major X; one column of it
        # is a stride of the whole row) and turned feature-major
        blk = X[sample_idx, j0:j0 + _FIT_BLOCK]
        cols = np.ascontiguousarray(np.asarray(blk, dtype=np.float64).T)
        return [fit_mapper_for_column(j0 + k, col, len(sample_idx), config,
                                      cat_set, filter_cnt, forced_bounds)
                for k, col in enumerate(cols)]

    return [m for j0 in range(0, num_features, _FIT_BLOCK)
            for m in fit_block(j0)]


# ------------------------------------------------------- streaming sketch
class FeatureSketch:
    """Mergeable per-feature (distinct values, counts, NaN count) summary
    for streaming bin finding — the TPU analog of the reference's
    distributed bin-finding protocol (dataset_loader.cpp:1046-1128:
    feature-sharded FindBin merged by Network::Allgather) crossed with the
    sketch-based quantile binning of the scalable-GPU XGBoost paper
    (PAPERS.md): row chunks fold in one at a time, sketches merge
    associatively (across chunks AND across ranks over
    ``distributed.exchange_host``), and a mapper fitted from the merged
    sketch via :meth:`BinMapper.find_bin_from_distinct` equals the
    sampled-path mapper exactly while the sketch stays EXACT.

    ``max_size`` bounds the distinct-value budget: past it the sketch
    compacts to equal-mass representatives (each kept value is the upper
    edge of its mass group, so ``max_val`` is preserved and every group's
    count collapses onto its edge). Each compaction moves a value's
    cumulative rank by at most ``total/max_size``, so after ``L``
    compactions boundary ranks are within ~``L/max_size`` of exact —
    the documented rank error the parity tests assert. ``max_size=0``
    means unbounded (exact)."""

    __slots__ = ("max_size", "values", "counts", "na_cnt", "total_cnt",
                 "compactions")

    def __init__(self, max_size: int = 0):
        self.max_size = int(max_size)
        self.values = np.zeros((0,), np.float64)
        self.counts = np.zeros((0,), np.int64)
        self.na_cnt = 0
        self.total_cnt = 0
        self.compactions = 0

    def fold(self, column: np.ndarray) -> None:
        """Fold one chunk's raw column values (NaN included) into the
        sketch. Bit-path note: NaNs are stripped and the rest go through
        ``np.unique`` — the same normalization ``find_bin`` applies."""
        col = np.asarray(column, dtype=np.float64).reshape(-1)
        self.total_cnt += len(col)
        na = np.isnan(col)
        n_na = int(na.sum())
        if n_na:
            self.na_cnt += n_na
            col = col[~na]
        if len(col):
            v, c = np.unique(col, return_counts=True)
            self._merge_arrays(v, c.astype(np.int64))

    def merge(self, other: "FeatureSketch") -> "FeatureSketch":
        """Fold another sketch in (rank merge). Associative up to the
        compaction error; exact when neither side ever compacted."""
        self.na_cnt += other.na_cnt
        self.total_cnt += other.total_cnt
        self.compactions = max(self.compactions, other.compactions)
        self._merge_arrays(other.values, other.counts)
        return self

    def _merge_arrays(self, v: np.ndarray, c: np.ndarray) -> None:
        if len(v):
            if len(self.values):
                allv = np.concatenate([self.values, v])
                allc = np.concatenate([self.counts, c])
                uv, inv = np.unique(allv, return_inverse=True)
                uc = np.zeros(len(uv), np.int64)
                np.add.at(uc, inv.reshape(-1), allc)
                self.values, self.counts = uv, uc
            else:
                self.values = np.asarray(v, np.float64).copy()
                self.counts = np.asarray(c, np.int64).copy()
        if self.max_size and len(self.values) > self.max_size:
            self._compact()

    def _compact(self) -> None:
        """Equal-mass compaction to ``max_size`` representatives. The zero
        slot is force-retained when present (the dedicated zero bin of
        ``find_bin_with_zero_as_one_bin`` keys on it)."""
        n = len(self.values)
        m = self.max_size
        cum = np.cumsum(self.counts)
        total = int(cum[-1])
        edges = np.searchsorted(cum, total * (np.arange(1, m + 1) / m),
                                side="left")
        edges = np.clip(edges, 0, n - 1)
        zi = int(np.searchsorted(self.values, 0.0))
        if zi < n and self.values[zi] == 0.0:
            edges = np.append(edges, zi)
        edges = np.unique(edges)
        grp_cnt = np.diff(np.concatenate([[0], cum[edges]]))
        self.values = self.values[edges]
        self.counts = grp_cnt.astype(np.int64)
        self.compactions += 1

    @property
    def exact(self) -> bool:
        return self.compactions == 0

    # JSON payloads for the cross-rank exchange_host merge: repr-based
    # float serialization round-trips f64 bit-exactly, so a merged-then-
    # fitted mapper is identical on every rank
    def to_dict(self) -> dict:
        return {"max_size": self.max_size,
                "values": [float(x) for x in self.values],
                "counts": [int(x) for x in self.counts],
                "na_cnt": int(self.na_cnt),
                "total_cnt": int(self.total_cnt),
                "compactions": int(self.compactions)}

    @classmethod
    def from_dict(cls, d: dict) -> "FeatureSketch":
        sk = cls(int(d.get("max_size", 0)))
        sk.values = np.asarray(d["values"], np.float64)
        sk.counts = np.asarray(d["counts"], np.int64)
        sk.na_cnt = int(d["na_cnt"])
        sk.total_cnt = int(d["total_cnt"])
        sk.compactions = int(d.get("compactions", 0))
        return sk


def split_chunk(chunk):
    """Normalize one chunk to ``(X [rows, F] ndarray, labels-or-None)``.
    Chunk sources may yield bare feature arrays or ``(X, y)`` pairs."""
    y = None
    if isinstance(chunk, (tuple, list)) and len(chunk) == 2:
        chunk, y = chunk
    X = np.asarray(chunk)
    if X.ndim == 1:
        X = X.reshape(-1, 1)
    if y is not None:
        y = np.asarray(y, dtype=np.float64).reshape(-1)
    return X, y


def chunk_factory(source, chunk_rows: int = 0):
    """Normalize a chunk source into a re-iterable factory (the streaming
    construct runs TWO passes — sketch, then bin — so a one-shot
    generator cannot feed it):

    - a callable -> called per pass, must return a fresh iterator of
      chunks (each a ``[rows, F]`` array or an ``(X, y)`` pair);
    - a list/tuple of chunks -> iterated per pass;
    - a 2-D array (or anything array-like with ``.shape``) -> sliced into
      ``chunk_rows`` row views (no copies).
    """
    from .utils import log as _log
    default = int(chunk_rows) if chunk_rows else (1 << 20)
    if callable(source):
        return source
    if isinstance(source, (list, tuple)):
        return lambda: iter(source)
    if hasattr(source, "shape") and getattr(source, "ndim", 0) == 2:
        def _slices():
            n = source.shape[0]
            for s in range(0, max(n, 1), default):
                yield source[s:s + default]
        return _slices
    _log.fatal("chunk source must be re-iterable: a callable returning an "
               "iterator of chunks, a sequence of chunk arrays, or a 2-D "
               f"array (got {type(source).__name__}; a one-shot generator "
               "cannot feed the two construct passes)")


def sketch_chunks(factory, max_size: int = 0, track_bytes=None,
                  fold: bool = True):
    """Pass 1 of streaming construction: fold every chunk into per-feature
    :class:`FeatureSketch` es, holding at most ONE raw chunk at a time.

    Returns ``(sketches, num_data, chunk_sizes, labels)`` where ``labels``
    is the concatenation of per-chunk label parts (None when chunks carry
    no labels). ``track_bytes``: optional callback fed each chunk's raw
    byte size (the construct_peak_bytes gauge). ``fold=False`` skips the
    per-column fold (the dominant wall) but keeps the row/size/label
    accounting and the mid-stream width check — the light pass a
    reference-aligned valid set needs (its mappers come from the
    reference)."""
    sketches: Optional[List[FeatureSketch]] = None
    num_data = 0
    sizes: List[int] = []
    label_parts: List[np.ndarray] = []
    # explicit next() loop so the previous chunk's reference is DROPPED
    # before the source builds the next one — a plain for-loop keeps the
    # loop variable bound across next(), holding two chunks alive
    it = iter(factory())
    while True:
        chunk = next(it, None)
        if chunk is None:
            break
        X, y = split_chunk(chunk)
        chunk = None
        if track_bytes is not None:
            track_bytes(int(getattr(X, "nbytes", 0)))
        if sketches is None:
            sketches = [FeatureSketch(max_size) for _ in range(X.shape[1])]
        elif X.shape[1] != len(sketches):
            from .utils import log as _log
            _log.fatal(f"chunk feature count changed mid-stream: "
                       f"{X.shape[1]} vs {len(sketches)}")
        if fold:
            for j in range(X.shape[1]):
                sketches[j].fold(X[:, j])
        num_data += X.shape[0]
        sizes.append(X.shape[0])
        if y is not None:
            label_parts.append(y)
        X = None
    if sketches is None:
        from .utils import log as _log
        _log.fatal("chunk source yielded no chunks")
    labels = np.concatenate(label_parts) if label_parts else None
    return sketches, num_data, sizes, labels


def fit_mappers_from_sketches(sketches: Sequence[FeatureSketch],
                              num_data: int, config,
                              categorical_features: Sequence[int] = (),
                              forced_bounds: Optional[Dict[int, List[float]]]
                              = None) -> List[BinMapper]:
    """Fit one BinMapper per feature from (possibly rank-merged) sketches
    — the streaming twin of :func:`find_bin_mappers`. With exact sketches
    whose total covers every row, this IS the sampled path's fit (the
    sample being all rows), so mappers are bit-identical whenever
    ``num_data <= bin_construct_sample_cnt``."""
    cat_set = set(int(c) for c in categorical_features)
    total = int(sketches[0].total_cnt) if len(sketches) else 0
    filter_cnt = filter_cnt_for_sample(config, total, num_data)
    out = []
    for j, sk in enumerate(sketches):
        if j in cat_set and sk.compactions > 0:
            # equal-mass compaction merges distinct CODES into their
            # group's upper-edge code — meaningless for unordered
            # categories and silently different from the sampled path.
            # Fail loudly instead of fitting wrong category maps.
            from .utils import log as _log
            _log.fatal(
                f"categorical feature {j} exceeded sketch_max_size "
                f"({sk.max_size}) distinct codes during streaming "
                f"construction and was compacted; raise sketch_max_size "
                f"above the category count (rank-error compaction only "
                f"applies to numerical features)")
        m = BinMapper()
        m.find_bin_from_distinct(
            sk.values, sk.counts, sk.na_cnt, sk.total_cnt,
            **_find_bin_kwargs(j, config, cat_set, filter_cnt,
                               forced_bounds))
        out.append(m)
    return out


def bin_data(X: np.ndarray, mappers: Sequence[BinMapper]) -> np.ndarray:
    """Quantize the full matrix → int32 bin matrix [num_data, num_features]."""
    num_data, num_features = X.shape
    out = np.zeros((num_data, num_features), dtype=np.int32)
    for j, m in enumerate(mappers):
        if m.is_trivial:
            continue
        out[:, j] = m.values_to_bins(np.asarray(X[:, j], dtype=np.float64))
    return out


# ------------------------------------------------------------ device binning
def device_bin_tables(mappers: Sequence[BinMapper]):
    """Per-feature tables for on-device quantization of float32 data.

    The host path compares float64 values against float64 upper bounds
    (``values_to_bins``: idx = #{bounds < v}). For float32 inputs the same
    predicate is computed exactly in f32 by replacing each f64 bound b with
    the largest f32 <= b: for any f32 v, (v > b) == (v > b_dn). Returns
    (bounds_dn [F, Bpad] f32 (+inf padded), nan_to_zero [F] bool,
    nan_bin [F] int32).
    """
    fs = len(mappers)
    finite = []
    nan_to_zero = np.zeros((fs,), dtype=bool)
    nan_bin = np.zeros((fs,), dtype=np.int32)
    for i, m in enumerate(mappers):
        assert m.bin_type == BIN_TYPE_NUMERICAL
        has_nan_bin = m.missing_type == MISSING_NAN
        n_real = m.num_bin - (1 if has_nan_bin else 0)
        fb = np.asarray(m.bin_upper_bound[:n_real - 1], dtype=np.float64) \
            if n_real > 0 else np.zeros((0,), np.float64)
        finite.append(fb)
        nan_to_zero[i] = m.missing_type == MISSING_ZERO
        # NaN routing matches the host semantics: NaN-as-missing gets the
        # top bin; with no NaN handling, searchsorted lands NaN at the end
        # of the finite bounds (bin n_real-1)
        nan_bin[i] = m.num_bin - 1 if has_nan_bin else max(n_real - 1, 0)
    bpad = max(1, max((len(fb) for fb in finite), default=1))
    bounds = np.full((fs, bpad), np.inf, dtype=np.float32)
    for i, fb in enumerate(finite):
        if not len(fb):
            continue
        b32 = fb.astype(np.float32)
        over = b32.astype(np.float64) > fb
        bounds[i, :len(fb)] = np.where(
            over, np.nextafter(b32, np.float32(-np.inf)), b32)
    return bounds, nan_to_zero, nan_bin


def _quantize_block(xs, bd, nz, nb, odt):
    """The device quantize predicate ONE block of float32 rows goes
    through — shared by ``bin_data_device`` and ``StreamingBinWriter``
    so the streaming path's bit-exactness contract (same bins as the
    monolithic device pass, and via ``device_bin_tables`` the host pass)
    is enforced structurally, not by parallel copies staying in sync.
    ``xs [rows, F]`` f32, ``bd [F, Bpad]`` downshifted bounds, ``nz [F]``
    NaN-as-zero mask, ``nb [F]`` NaN routing bin; returns ``[rows, F]``
    of dtype ``odt``."""
    import jax.numpy as jnp
    v = jnp.where(jnp.isnan(xs) & nz[None, :], 0.0, xs)
    cnt = jnp.sum(v[:, :, None] > bd[None, :, :], axis=-1, dtype=jnp.int32)
    cnt = jnp.where(jnp.isnan(v), nb[None, :], cnt)
    return cnt.astype(odt)


def bin_chunks_host(factory, used: Sequence[BinMapper], uf, out: np.ndarray,
                    track=None) -> None:
    """Pass 2's HOST fallback: re-iterate the chunk source and write each
    chunk's per-column ``bin_data`` result into its row slot of ``out``
    — shared by ``Dataset._construct_streaming`` (non-f32/categorical
    streams) and ``distributed.load_partitioned_chunks``. Maintains the
    ref-dropping iteration discipline (<= the current chunk + its f64
    column copy resident, reported through ``track``) and VERIFIES the
    source yielded exactly ``len(out)`` rows — a source that under-yields
    on its second iteration must fail loudly, not train on the zero
    tail."""
    from .utils import log as _log
    row = 0
    it = iter(factory())
    while True:                            # ref-dropping next() loop
        chunk = next(it, None)
        if chunk is None:
            break
        X, _y = split_chunk(chunk)
        chunk = None
        n = X.shape[0]
        if len(uf):
            # subset FIRST, then widen: np.asarray(X, f64)[:, uf] would
            # materialize a full-width f64 temp (2x the chunk) before
            # the column select; f32->f64 is exact so this is
            # bit-equivalent with a smaller transient
            Xu = np.asarray(X[:, uf] if X.shape[1] != len(uf) else X,
                            np.float64)
            if track is not None:
                # resident: the source chunk + its f64 column copy
                track(X.nbytes + Xu.nbytes)
            X = None
            out[row:row + n] = bin_data(Xu, used)
            Xu = None
        else:
            if track is not None:
                track(X.nbytes)
            X = None
        row += n
    if row != len(out):
        _log.fatal(f"chunk source yielded {row} rows on the bin pass but "
                   f"{len(out)} on the sketch pass: the source must be "
                   f"re-iterable and deterministic (a one-shot iterator "
                   f"cannot feed the two construct passes)")


def row_shard_sharding(mesh):
    """Rows over the one axis of a 1-D device mesh, columns whole."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(mesh.axis_names[0], None))


def place_row_shards(bins_np: np.ndarray, mesh):
    """A host bin matrix ``[N, F]`` as a global ``[S * D, F]`` device array,
    rows over the mesh's ``D`` devices in ``S = ceil(N / D)``-row contiguous
    shards (zero rows pad the last): each shard goes from the host straight
    to the device that owns it, no device ever holds the whole."""
    import jax
    d = mesh.devices.size
    pad = -len(bins_np) % d
    if pad:
        bins_np = np.pad(bins_np, ((0, pad), (0, 0)))
    return jax.device_put(bins_np, row_shard_sharding(mesh))


def bin_data_device(X, mappers: Sequence[BinMapper], block: int = 1 << 17,
                    mesh=None):
    """Quantize a float32 matrix on device (the TPU replacement for the
    host ``bin_data`` loop — this box's single CPU core makes the host
    searchsorted pass the construct bottleneck at 10M+ rows; reference
    pushes rows through DenseBin with OpenMP, dense_bin.hpp).

    Bit-exact vs ``bin_data`` for float32 input (see device_bin_tables).
    Returns a DEVICE array [N, F] uint8/int32. With a 1-D ``mesh`` the
    rows are cut into one contiguous shard a device (``place_row_shards``'
    layout), each shard of the float matrix goes straight to the device
    that will own it and is quantized THERE by one program over the mesh;
    the result is the global row-sharded ``[S * D, F]`` array: the float
    matrix passes through no single device and none holds the whole.
    """
    import jax
    import jax.numpy as jnp

    assert X.dtype == np.float32
    n, fs = X.shape
    bounds, nan_to_zero, nan_bin = device_bin_tables(mappers)
    max_bin = max(m.num_bin for m in mappers) if fs else 2
    out_dtype = jnp.uint8 if max_bin <= 256 else jnp.int32

    def run(xd, bd, nz, nb, odt, c):
        def body(_, xb):
            return _, _quantize_block(xb, bd, nz, nb, odt)

        _, bins = jax.lax.scan(body, 0, xd.reshape(-1, c, fs))
        return bins.reshape(-1, fs)

    if mesh is None:
        c = min(block, n) if n else 1
        pad = -n % c
        bins = jax.jit(run, static_argnames=("odt", "c"))(
            jnp.asarray(np.pad(X, ((0, pad), (0, 0))) if pad else X),
            jnp.asarray(bounds), jnp.asarray(nan_to_zero),
            jnp.asarray(nan_bin), out_dtype, c)
        return bins[:n] if pad else bins
    # one shard of the float matrix straight to each device (the copies
    # run side by side), then ONE program over the mesh in which every
    # device quantizes its own rows
    from jax.sharding import PartitionSpec as P
    d, axis = mesh.devices.size, mesh.axis_names[0]
    s = -(-n // d)
    parts = []
    for i, dev in enumerate(mesh.devices.flat):
        Xi = X[i * s:(i + 1) * s]
        if len(Xi) < s:
            Xi = np.pad(Xi, ((0, s - len(Xi)), (0, 0)))
        parts.append(jax.device_put(Xi, dev))
    sharding = row_shard_sharding(mesh)
    xd = jax.make_array_from_single_device_arrays((s * d, fs), sharding,
                                                  parts)
    c = min(block, s)
    pad = -s % c

    def local(x, bd, nz, nb):
        if pad:
            x = jnp.pad(x, ((0, pad), (0, 0)))
        bins = run(x, bd, nz, nb, out_dtype, c)[:s]
        if s * d != n:          # the last shards' padding rows: bin 0
            row = jax.lax.axis_index(axis) * s + jnp.arange(s)
            bins = jnp.where((row < n)[:, None], bins, 0)
        return bins

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis, None), P(), P(), P()),
        out_specs=P(axis, None), check_vma=False))(
            xd, bounds, nan_to_zero, nan_bin)


class StreamingBinWriter:
    """Pass 2 of streaming construction: quantize float32 row chunks ON
    DEVICE and write each into its row slot of one preallocated (donated)
    ``[N_pad, F]`` bin matrix — the pre-sharded destination of the chunked
    pipeline (SNIPPETS.md [1] naive-sharding: the leading axis is the one
    a row-sharded mesh splits). Every ``write`` is one async jitted
    dispatch (pad to a fixed chunk shape -> one compiled program), so
    chunk k's H2D transfer + device quantize overlap chunk k+1's host
    parse: the double buffer is the dispatch queue itself, and host
    residency stays at the current chunk + its padded copy (<= 2 chunks
    of raw data). Quantization is the ``bin_data_device`` predicate —
    bit-exact vs the host ``bin_data`` path for float32 input (see
    ``device_bin_tables``).

    Residency is HARD-BOUNDED, not best-effort: each ``write`` first
    drains the previous dispatch (at most ONE write in flight — the
    caller's parse of chunk k+1 already overlapped chunk k's transfer
    and compute between the two calls, so the wait costs no overlap) and
    copies the chunk into a fresh staging buffer rather than handing the
    caller's array to jax (which may pin it for the dispatch lifetime).
    Peak host residency: one source chunk + one staged copy — the
    "<= 2 chunks of raw data" acceptance bound; an unbounded dispatch
    queue would instead retain O(queue-depth) chunks.

    Writes past a chunk's true row count spill pad garbage into the NEXT
    chunk's slot, which that chunk's later write overwrites (dispatches
    are ordered by the donated-buffer dependency); the allocation keeps
    ``max_chunk_rows`` spare rows so the LAST chunk's spill stays in
    bounds, and ``finalize`` slices the matrix back to ``total_rows``.
    """

    def __init__(self, mappers: Sequence[BinMapper], total_rows: int,
                 max_chunk_rows: int, sub_block: int = 1 << 15):
        import jax
        import jax.numpy as jnp

        assert all(m.bin_type == BIN_TYPE_NUMERICAL for m in mappers)
        self._num_mappers = len(mappers)
        self.f = max(len(mappers), 1)
        bounds, nan_to_zero, nan_bin = (
            device_bin_tables(mappers) if len(mappers)
            else (np.full((1, 1), np.inf, np.float32),
                  np.zeros((1,), bool), np.zeros((1,), np.int32)))
        max_bin = max((m.num_bin for m in mappers), default=2)
        self.dtype = jnp.uint8 if max_bin <= 256 else jnp.int32
        self.n = int(total_rows)
        c = min(int(sub_block), max(int(max_chunk_rows), 1))
        self.chunk_pad = -(-max(int(max_chunk_rows), 1) // c) * c
        self._sub = c
        self._bounds = jnp.asarray(bounds)
        self._nz = jnp.asarray(nan_to_zero)
        self._nb = jnp.asarray(nan_bin)
        self._out = jnp.zeros((self.n + self.chunk_pad, self.f), self.dtype)
        self._next = 0

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _write(out, xb, start, bd, nz, nb):
            def body(_, xs):
                return _, _quantize_block(xs, bd, nz, nb, out.dtype)

            _, bins = jax.lax.scan(body, 0, xb.reshape(-1, c, xb.shape[1]))
            return jax.lax.dynamic_update_slice(
                out, bins.reshape(-1, xb.shape[1]), (start, 0))

        self._write_fn = _write

    def write(self, chunk: np.ndarray) -> None:
        """Dispatch one chunk's quantize-and-place (async), after
        draining the PREVIOUS write — see the class docstring's
        residency bound."""
        import jax
        import jax.numpy as jnp
        chunk = np.asarray(chunk, dtype=np.float32)
        if chunk.ndim == 1:
            chunk = chunk.reshape(-1, 1)
        rows = chunk.shape[0]
        assert rows <= self.chunk_pad, (rows, self.chunk_pad)
        assert self._next + rows <= self.n, "writer overflow"
        if self._num_mappers != 0:
            assert chunk.shape[1] == self.f, (chunk.shape, self.f)
        if self._next:
            jax.block_until_ready(self._out)   # <= 1 write in flight
        staged = np.zeros((self.chunk_pad, self.f), np.float32)
        if self._num_mappers != 0:
            staged[:rows] = chunk
        del chunk                              # staging owns the only copy
        self._out = self._write_fn(self._out, jnp.asarray(staged),
                                   jnp.int32(self._next), self._bounds,
                                   self._nz, self._nb)
        self._next += rows

    def finalize(self):
        """Drain the dispatch queue and return the device ``[N, F]`` bin
        matrix. The blocking wait here is the NON-overlapped tail of the
        pipeline — callers time it as the ``h2d_overlap`` sub-scope."""
        import jax
        assert self._next == self.n, (self._next, self.n)
        out, self._out = self._out, None
        out = out[:self.n]
        jax.block_until_ready(out)
        return out
