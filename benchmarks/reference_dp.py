"""The plain reference for a data-parallel training job on dense columns
with missing values: numpy, float64, no jax, independent of the learners
and of how they shard the rows.

- ``host_bins``: the bin of every raw value from the quantiser's bin upper
  bounds (the algorithm is histogram-based: its candidates are bin
  boundaries), NaN in the column's last bin where the column has one.
- ``column_histograms``: per column and bin the row count and the label
  sum over a bin matrix. Taken over each chip's OWN rows as read back from
  that chip and summed over the chips, they have to equal the histograms of
  the whole host bin matrix (``partition_faults``): every row on exactly one
  chip, none dropped, none counted twice, padding rows carrying nothing.
- ``root_split``: the best root split of tree 0 from those histograms,
  missing values tried on both sides (the reference's two scans,
  feature_histogram.hpp: NaN goes left in one and right in the other);
  gain and minima as ``reference.root_split``. ``gain_of_raw_split`` scores
  a printed split straight from the raw column by the same arithmetic.
- ``leaf_index``: the leaf every row reaches in a parsed model-text tree,
  traversed over the RAW float values; a NaN takes the node's printed
  default direction (``decision_type``), as ``Tree::NumericalDecision``.
  ``reference_sparse.leaf_values`` then gives what the leaves must print.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

K_EPSILON = 1e-15
BLOCK = 1 << 20
# decision_type of a numerical node in the v3 model text: bit 1 the default
# direction, bits 2-3 the missing type (2: NaN)
DEFAULT_LEFT, MISSING_SHIFT, MISSING_NAN = 2, 2, 2


def _blocks(n: int, fn, threads: int):
    """``fn(a, b)`` over row blocks by a few threads (numpy's loops run
    without the GIL); the results in block order."""
    cuts = list(range(0, n, BLOCK)) + [n]
    with ThreadPoolExecutor(max(1, threads)) as ex:
        return list(ex.map(lambda i: fn(cuts[i], cuts[i + 1]),
                           range(len(cuts) - 1)))


def host_bins(X: np.ndarray, bounds: list, nan_bin: list,
              threads: int = 8) -> np.ndarray:
    """Bins ``[F, n]`` (feature-major) of the raw matrix ``X [n, F]``:
    column j's bin is the number of ``bounds[j]`` (its ascending finite
    upper bounds, the last bin's +inf left out) below the value, and
    ``nan_bin[j]`` (or -1) for a NaN."""
    n, f = X.shape
    out = np.empty((f, n), dtype=np.int16)

    def fill(a, b):
        cols = np.ascontiguousarray(X[a:b].T).astype(np.float64)
        for j in range(f):
            idx = np.searchsorted(bounds[j], cols[j], side="left")
            if nan_bin[j] >= 0:
                idx[np.isnan(cols[j])] = nan_bin[j]
            out[j, a:b] = idx

    _blocks(n, fill, threads)
    return out


def column_histograms(binsT: np.ndarray, y: np.ndarray, num_bins: int,
                      threads: int = 8):
    """(count int64 ``[F, num_bins]``, label sum float64 ``[F, num_bins]``)
    over a feature-major bin matrix ``[F, n]`` and the rows' labels."""
    f, n = binsT.shape
    y64 = np.asarray(y, dtype=np.float64)

    def part(a, b):
        cnt = np.zeros((f, num_bins), dtype=np.int64)
        ysum = np.zeros((f, num_bins), dtype=np.float64)
        for j in range(f):
            col = binsT[j, a:b].astype(np.int64)
            cnt[j] = np.bincount(col, minlength=num_bins)
            ysum[j] = np.bincount(col, weights=y64[a:b], minlength=num_bins)
        return cnt, ysum

    parts = _blocks(n, part, threads)
    return sum(p[0] for p in parts), sum(p[1] for p in parts)


def partition_faults(shard_counts: list, whole_counts: np.ndarray) -> int:
    """By how many rows the chips' own count histograms, summed, differ
    from the whole host matrix's: the sum of |differences| over one
    column's bins, the largest over the columns. A row dropped or counted
    twice shows once in every column, a row in a wrong bin twice; 0 where
    the rows are partitioned."""
    diff = np.abs(sum(shard_counts) - whole_counts).sum(axis=1)
    return int(diff.max()) if diff.size else 0


def binary_root_stats(y):
    """(p0, h0): at the root of tree 0 of the binary objective with
    boost_from_average every row has g = p0 - y and h = p0 (1 - p0)."""
    p0 = float(np.mean(y, dtype=np.float64))
    return p0, p0 * (1.0 - p0)


def split_gain(gl, hl, cl, g, h, c, min_data, min_hess):
    """Gain G_l^2/H_l + G_r^2/H_r of splits of a node with totals (g, h,
    c), -inf where a child breaks a minimum; vectorised over the left."""
    gr, hr, cr = g - gl, h - hl, c - cl
    ok = (cl >= min_data) & (cr >= min_data) & (hl >= min_hess) \
        & (hr >= min_hess)
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = gl * gl / hl + gr * gr / hr
    return np.where(ok, gain, -np.inf)


def root_split(cnt: np.ndarray, ysum: np.ndarray, y, real_bins: list,
               nan_bin: list, min_data: float, min_hess: float):
    """Best root split of tree 0 from the whole histograms: (gain, column,
    threshold bin, NaN goes left, left count). Column j has
    ``real_bins[j]`` bins of values and, at ``nan_bin[j]`` >= 0, one of
    NaNs; a row goes left when its bin <= the threshold bin, a NaN by the
    direction."""
    n = float(len(y))
    p0, h0 = binary_root_stats(y)
    y_tot = float(np.sum(y, dtype=np.float64))
    g_tot, h_tot = p0 * n - y_tot, h0 * n
    best = (-np.inf, -1, -1, False, -1)
    for j in range(cnt.shape[0]):
        r = int(real_bins[j])
        c_real = np.cumsum(cnt[j, :r].astype(np.float64))
        y_real = np.cumsum(ysum[j, :r])
        has_nan = nan_bin[j] >= 0 and cnt[j, nan_bin[j]] > 0
        # NaN right: thresholds 0 .. r-1 (the last cuts NaN from values)
        sides = [(False, c_real[:r if has_nan else r - 1],
                  y_real[:r if has_nan else r - 1])]
        if has_nan:
            sides.append((True, c_real[:r - 1] + cnt[j, nan_bin[j]],
                          y_real[:r - 1] + ysum[j, nan_bin[j]]))
        for nan_left, cl, yl in sides:
            if not len(cl):
                continue
            gain = split_gain(p0 * cl - yl, h0 * cl + K_EPSILON, cl, g_tot,
                              h_tot, n, min_data, min_hess)
            t = int(np.argmax(gain))
            if gain[t] > best[0]:
                best = (float(gain[t]), j, t, nan_left, int(cl[t]))
    return best


def go_left(x: np.ndarray, threshold: float, decision_type: int):
    """Which raw values a numerical node sends left: ``x <= threshold``,
    a NaN by the printed default direction where the node's missing type
    is NaN, as 0.0 otherwise."""
    nan = np.isnan(x)
    if (int(decision_type) >> MISSING_SHIFT) & 3 == MISSING_NAN:
        with np.errstate(invalid="ignore"):
            return np.where(nan, bool(int(decision_type) & DEFAULT_LEFT),
                            x <= threshold)
    return np.where(nan, 0.0, x) <= threshold


def gain_of_raw_split(x_col: np.ndarray, y, threshold: float,
                      decision_type: int, min_data: float, min_hess: float):
    """(gain, left count) of the printed root split by ``root_split``'s
    arithmetic, straight from the raw column."""
    n = float(len(y))
    p0, h0 = binary_root_stats(y)
    y64 = np.asarray(y, dtype=np.float64)
    left = go_left(np.asarray(x_col, np.float64), threshold, decision_type)
    cl = float(left.sum())
    gain = split_gain(np.float64(p0 * cl - y64[left].sum()),
                      np.float64(h0 * cl + K_EPSILON), np.float64(cl),
                      p0 * n - y64.sum(), h0 * n, n, min_data, min_hess)
    return float(gain), int(cl)


def leaf_index(tree: dict, decision_type: np.ndarray, X: np.ndarray,
               threads: int = 8) -> np.ndarray:
    """The leaf of every row of the raw matrix ``X [n, F]`` in one parsed
    tree (``reference.parse_model``; ``decision_type`` its line of that
    name), int32 [n]. A block's rows are kept as one index list a node, so
    a level costs one pass over them."""
    n = X.shape[0]
    leaf = np.zeros(n, dtype=np.int32)
    if tree["num_leaves"] == 1:
        return leaf

    def walk(a, b):
        pending = {0: np.arange(a, b, dtype=np.int64)}
        # a child's index is larger than its parent's, so ascending order
        # meets every node after the node that fills it
        for node in range(tree["num_leaves"] - 1):
            rows = pending.pop(node, None)
            if rows is None or not len(rows):
                continue
            left = go_left(
                X[rows, int(tree["split_feature"][node])].astype(np.float64),
                tree["threshold"][node], decision_type[node])
            for child, part in ((int(tree["left_child"][node]), rows[left]),
                                (int(tree["right_child"][node]),
                                 rows[~left])):
                if child < 0:
                    leaf[part] = ~child
                else:
                    pending[child] = part

    _blocks(n, walk, threads)
    return leaf
