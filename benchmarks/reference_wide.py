"""The plain reference for a training job on a WIDE dense matrix (thousands
of columns, no missing value): numpy, float64, no jax. It never sees a
kernel, a tile or a feature block: every number comes from the raw float
values, the quantiser's bin upper bounds (the algorithm is histogram-based:
its candidates are bin boundaries) and the printed model text.

- ``whole_histograms`` / ``root_split``: the bin of every raw value from
  the bounds, per column and bin the row count and the label sum, and the
  best root split of tree 0 over ALL columns (``reference_dp``'s arithmetic
  with no NaN bin: a dense column is a column whose NaN bin is absent).
- ``leaf_index``: the leaf every row reaches in a parsed tree, traversed
  over the raw values; ``reference_sparse.leaf_values`` then gives what the
  leaves must print.
- **The probe.** ``designated_columns`` names three columns of every
  feature block the library's plan reports (the block's first, an inner and
  its last), so column 0 and the last column are among them;
  ``decision_list`` plants a label that only those columns explain, one
  step a column: of the rows no earlier step took, those in the column's
  TOP bin (above the last finite bin bound) take a label mean far from
  one half, alternately high and low; the rest stay at one half. A step's
  rows are one bin of what is left (about 1/255 of it: over the 400 rows
  ``min_sum_hessian_in_leaf=100`` asks of a child at a hessian of 1/4 a
  row, under the three leaves such a node can still make, so the peeled
  nodes cannot use up the leaf budget) and its gain is some fifty times a
  noise column's best, so a tree grown on the label has to split every
  designated column at the planted bound, whatever order it takes them in.
  ``planted_found`` reads the tree's splits back, by bin.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference_dp

COLUMN_BLOCK = 16

# the probe label's means in a step's rows, alternately
PROBE_HIGH, PROBE_LOW = 0.95, 0.05


def bounds_of(mappers) -> list:
    """The ascending finite bin upper bounds of every column (the last
    bin's +inf left out), float64."""
    return [np.asarray(m.bin_upper_bound[:m.num_bin - 1], dtype=np.float64)
            for m in mappers]


def _by_columns(f: int, fn, threads: int):
    """``fn(a, b)`` over blocks of COLUMN_BLOCK columns by a few threads
    (numpy's loops run without the GIL). ``reference_dp`` shares its work
    out by blocks of a million ROWS: right for 24,000,000 x 67, one block
    and one thread for 400,000 x 2,000."""
    cuts = list(range(0, f, COLUMN_BLOCK)) + [f]
    with ThreadPoolExecutor(max(1, threads)) as ex:
        list(ex.map(lambda i: fn(cuts[i], cuts[i + 1]),
                    range(len(cuts) - 1)))


def host_bins(X: np.ndarray, bounds: list, threads: int = 8) -> np.ndarray:
    """Bins ``[F, n]`` (feature-major, int16) of the raw matrix ``X [n,
    F]``: column j's bin is the number of ``bounds[j]`` below the value."""
    n, f = X.shape
    out = np.empty((f, n), dtype=np.int16)

    def fill(a, b):
        cols = np.ascontiguousarray(X[:, a:b].T).astype(np.float64)
        for j in range(a, b):
            out[j] = np.searchsorted(bounds[j], cols[j - a], side="left")

    _by_columns(f, fill, threads)
    return out


def column_histograms(binsT: np.ndarray, y, num_bins: int,
                      threads: int = 8):
    """(count int64 ``[F, num_bins]``, label sum float64 ``[F,
    num_bins]``) over a feature-major bin matrix and the rows' labels."""
    f = binsT.shape[0]
    y64 = np.asarray(y, dtype=np.float64)
    cnt = np.zeros((f, num_bins), dtype=np.int64)
    ysum = np.zeros((f, num_bins), dtype=np.float64)

    def part(a, b):
        for j in range(a, b):
            col = binsT[j].astype(np.int64)
            cnt[j] = np.bincount(col, minlength=num_bins)
            ysum[j] = np.bincount(col, weights=y64, minlength=num_bins)

    _by_columns(f, part, threads)
    return cnt, ysum


def whole_histograms(X: np.ndarray, y, bounds: list, num_bins: int,
                     threads: int = 8):
    """(binsT int16 ``[F, n]``, count int64 ``[F, num_bins]``, label sum
    float64 ``[F, num_bins]``) of the raw matrix ``X [n, F]``."""
    binsT = host_bins(X, bounds, threads)
    cnt, ysum = column_histograms(binsT, y, num_bins, threads)
    return binsT, cnt, ysum


def root_split(cnt, ysum, y, bounds: list, min_data: float, min_hess: float):
    """Best root split of tree 0 over all columns: (gain, column,
    threshold bin, left count)."""
    gain, col, t, _, left = reference_dp.root_split(
        cnt, ysum, y, [len(b) + 1 for b in bounds], [-1] * len(bounds),
        min_data, min_hess)
    return gain, col, t, left


def gain_of_raw_split(x_col, y, threshold: float, min_data: float,
                      min_hess: float):
    """(gain, left count) of the printed root split ``x <= threshold``
    straight from the raw column."""
    return reference_dp.gain_of_raw_split(x_col, y, threshold, 0, min_data,
                                          min_hess)


def leaf_index(tree: dict, X: np.ndarray, threads: int = 8) -> np.ndarray:
    """The leaf of every row of the raw matrix in one parsed tree."""
    return reference_dp.leaf_index(
        tree, np.zeros(max(tree["num_leaves"] - 1, 1), dtype=np.int64), X,
        threads)


# ------------------------------------------------------------------ probe
def designated_columns(features: int, feature_block: int) -> np.ndarray:
    """Three columns of every feature block of ``feature_block`` columns
    (the first, the middle, the last; fewer where a block is narrower),
    ascending: column 0 and column ``features - 1`` are among them."""
    out = set()
    for a in range(0, features, max(int(feature_block), 1)):
        b = min(a + int(feature_block), features) - 1
        out |= {a, (a + b) // 2, b}
    return np.array(sorted(out), dtype=np.int64)


def decision_list(binsT: np.ndarray, columns, top_bins, seed: int):
    """(label float32 [n], steps): ``steps`` lists (column, rows taken,
    label mean) in the order the list takes the columns, drawn from
    ``seed``. Step k takes, of the rows no earlier step took, those in
    column k's bin ``top_bins[k]`` (its last one)."""
    n = binsT.shape[1]
    rng = np.random.default_rng([seed, 0xE951])
    order = rng.permutation(len(columns))
    p = np.full(n, 0.5)
    free = np.ones(n, dtype=bool)
    steps = []
    for k, i in enumerate(order):
        take = free & (binsT[columns[i]] == top_bins[i])
        mean = PROBE_HIGH if k % 2 == 0 else PROBE_LOW
        p[take] = mean
        free &= ~take
        steps.append((int(columns[i]), int(take.sum()), mean))
    return (rng.random(n) < p).astype(np.float32), steps


def planted_found(tree: dict, columns, top_bins, bounds: list) -> list:
    """For every designated column whether the tree splits it AT the
    planted bound, i.e. cuts its top bin off: a printed threshold lies in
    the bin whose upper bound it is, so its bin is the number of the
    column's bounds below it, and the planted split's is the last bin
    but one."""
    feat, thr = tree["split_feature"], tree["threshold"]
    out = []
    for c, top in zip(columns, top_bins):
        at = np.searchsorted(bounds[int(c)], thr[feat == c], side="left")
        out.append(bool(np.any(at == top - 1)))
    return out
