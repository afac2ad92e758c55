"""The plain reference for a training job on a WIDE dense matrix with
missing values (hundreds of columns, most of their entries NaN): numpy,
float64, no jax. It never sees a kernel, a tile, a feature block or the
kernel's scan metadata: every number comes from the raw float values, the
quantiser's bin upper bounds and missing type (the algorithm is
histogram-based: its candidates are bin boundaries) and the printed model
text.

- ``tables``: per column the finite bin upper bounds, the count of value
  bins and the NaN bin (the column's last) or -1.
- ``host_bins`` / ``whole_histograms``: the bin of every raw value, NaN in
  the column's NaN bin, and per column and bin the row count and the label
  sum, worked out by blocks of columns (``reference_wide``'s partition:
  right for 1,000,000 x 968, where ``reference_dp``'s blocks of a million
  rows would leave one thread).
- From those histograms ``reference_dp.root_split`` gives the best root
  split of tree 0 over ALL columns with NaN tried on both sides (the
  reference's two scans, feature_histogram.hpp, NaN left in one and right
  in the other); ``direction_gains`` scores one printed threshold both ways
  straight from the raw column.
- ``reference_dp.leaf_index`` gives the leaf every row reaches in a parsed
  tree over the RAW values, a NaN by the node's printed ``decision_type``
  default-left bit (as ``Tree::NumericalDecision``); ``node_rows`` the rows
  that reach one inner node.

Departures from the reference's ``FindBestThresholdSequentially``, none of
which moves a comparison the job makes: every candidate is scored by the
plain gain G_l^2/H_l + G_r^2/H_r at tree 0 (no l1, l2, max_delta_step or
path smoothing: the configuration sets none); within a column the two
scans' ties go to the lowest threshold, NaN right first, where the
reference keeps the reverse scan's highest threshold (the job compares
gains within a tolerance, never a tied threshold); and the NaN-left scan
is skipped where the column has no NaN row, as the reference finds nothing
there either.

**The probe.** ``reference_wide.designated_columns`` names three columns
of every feature block the library's plan reports (the block's first, its
middle and its last real column, so column 0 and the last column are
among them). ``planted_list`` plants a label that only those columns
explain, one step a column, in an order drawn from the seed: of the rows
no earlier step took, the step takes the PRESENT rows in the column's top
bins (on alternate columns its bottom bins), as few whole bins as hold
``slice_rows`` of them, at a label mean of 0.95 (0.05); the rest, the
column's NaN rows among them, stay at one half. A tree grown on the label
then has to cut each designated column at the slice's bound with the NaN
rows on the OTHER side, with the rest: left where the slice is the top
(the reverse scan's direction), right where it is the bottom (the forward
scan's). ``planted_found`` reads the threshold and the printed direction
back, at the node the most rows reach where several cut the column at the
bound: the tree may also cut it there inside another step's slice, where
the column's NaN rows carry that slice's label, or the child limits leave
NaN one side only, and the side is then the data's, not the scan's.
Why not a planted group of "NaN or the top bin": a station's
columns are present or NaN together, so every column of a
station offers the same NaN-against-present cut and a planted group that
holds the NaN rows ties, up to a bin's rows, with a cut on a neighbour;
and at 81% NaN a step that takes a column's NaN rows takes most of what
is left, so the later steps run out of rows long before the fifteenth.
"""

import numpy as np

import reference_dp
import reference_wide

# the probe label's means in a step's rows, alternately
PROBE_HIGH, PROBE_LOW = reference_wide.PROBE_HIGH, reference_wide.PROBE_LOW
NAN_LEFT = (reference_dp.MISSING_NAN << reference_dp.MISSING_SHIFT) \
    | reference_dp.DEFAULT_LEFT
NAN_RIGHT = reference_dp.MISSING_NAN << reference_dp.MISSING_SHIFT
# the NaN rows of a designated column a node needs before the probe holds
# its direction: with fewer, their labels' own draw can move them to the
# slice's side, and the direction is then the data's, not the scan's
NAN_ROWS_HELD = 100


def tables(mappers):
    """(bounds, value bins, NaN bin or -1) of every column of fitted bin
    mappers (``bin_upper_bound``, ``num_bin``, ``missing_type``; the
    missing type's NaN code is the one the model text prints)."""
    bounds, real, nan_bin = [], [], []
    for m in mappers:
        has_nan = int(m.missing_type) == reference_dp.MISSING_NAN
        r = int(m.num_bin) - (1 if has_nan else 0)
        bounds.append(np.asarray(m.bin_upper_bound[:r - 1], np.float64))
        real.append(r)
        nan_bin.append(r if has_nan else -1)
    return bounds, real, nan_bin


def host_bins(X: np.ndarray, bounds: list, nan_bin: list,
              threads: int = 8) -> np.ndarray:
    """Bins ``[F, n]`` (feature-major, int16) of the raw matrix ``X [n,
    F]``: the number of ``bounds[j]`` below the value, ``nan_bin[j]`` for
    a NaN."""
    n, f = X.shape
    out = np.empty((f, n), dtype=np.int16)

    def fill(a, b):
        cols = np.ascontiguousarray(X[:, a:b].T).astype(np.float64)
        for j in range(a, b):
            idx = np.searchsorted(bounds[j], cols[j - a], side="left")
            if nan_bin[j] >= 0:
                idx[np.isnan(cols[j - a])] = nan_bin[j]
            out[j] = idx

    reference_wide._by_columns(f, fill, threads)
    return out


def whole_histograms(X: np.ndarray, y, bounds: list, nan_bin: list,
                     num_bins: int, threads: int = 8):
    """(binsT int16 ``[F, n]``, count int64 ``[F, num_bins]``, label sum
    float64 ``[F, num_bins]``) of the raw matrix ``X [n, F]``."""
    binsT = host_bins(X, bounds, nan_bin, threads)
    cnt, ysum = reference_wide.column_histograms(binsT, y, num_bins,
                                                 threads)
    return binsT, cnt, ysum


def direction_gains(x_col, y, threshold: float, min_data: float,
                    min_hess: float):
    """((gain, left count) with NaN left, the same with NaN right) of the
    root split ``x <= threshold`` straight from the raw column."""
    return tuple(reference_dp.gain_of_raw_split(x_col, y, threshold, dt,
                                                min_data, min_hess)
                 for dt in (NAN_LEFT, NAN_RIGHT))


def node_rows(tree: dict, decision_type, X: np.ndarray,
              node: int) -> np.ndarray:
    """Indices of the rows of the raw matrix that reach inner node
    ``node``."""
    parent = {}
    for i in range(tree["num_leaves"] - 1):
        for side, child in ((True, tree["left_child"][i]),
                            (False, tree["right_child"][i])):
            if child >= 0:
                parent[int(child)] = (i, side)
    path, at = [], node
    while at in parent:
        at, side = parent[at]
        path.append((at, side))
    rows = np.arange(X.shape[0])
    for i, side in reversed(path):
        left = reference_dp.go_left(
            X[rows, int(tree["split_feature"][i])].astype(np.float64),
            tree["threshold"][i], decision_type[i])
        rows = rows[left == side]
    return rows


# ------------------------------------------------------------------ probe
def planted_list(binsT: np.ndarray, columns, real: list, slice_rows: int,
                 seed: int):
    """(label float32 [n], steps): ``steps`` lists (column, rows taken,
    label mean, planted threshold bin, NaN goes left) in the order the
    list takes the columns, drawn from ``seed``. Designated column k
    (ascending) is a TOP column where k is even, a BOTTOM column where it
    is odd."""
    n = binsT.shape[1]
    rng = np.random.default_rng([seed, 0xB05C])
    p = np.full(n, 0.5)
    free = np.ones(n, dtype=bool)
    steps = []
    for i in rng.permutation(len(columns)):
        c, top = int(columns[i]), bool(i % 2 == 0)
        r = int(real[c])
        b = binsT[c]
        # free present rows by bin, and the fewest extreme bins that hold
        # slice_rows of them (never all of the value bins)
        per_bin = np.bincount(b[free & (b < r)], minlength=r)[:r]
        run = np.cumsum(per_bin[::-1] if top else per_bin)
        k = min(int(np.searchsorted(run, slice_rows)), r - 2)
        t = r - 1 - k if top else k
        take = free & ((b >= t) & (b < r) if top else b <= t)
        mean = PROBE_HIGH if top else PROBE_LOW
        p[take] = mean
        free &= ~take
        steps.append((c, int(take.sum()), mean, t - 1 if top else t, top))
    return (rng.random(n) < p).astype(np.float32), steps


def planted_found(tree: dict, decision_type, steps, bounds: list,
                  X: np.ndarray):
    """For every step (in ``planted_list``'s order) the node that cuts
    its column at the planted bound, or -1, and whether its printed
    direction is the planted one: (node, direction right or None) a
    step. A printed threshold lies in the bin whose upper bound it is, so
    its bin is the number of the column's bounds below it. Where several
    nodes cut the column there, the one that the most rows reach is the
    planted cut: a smaller one sits inside another step's slice, where
    the column's NaN rows carry that slice's label, or the child limits
    leave NaN one side only, whatever the planted direction. The
    direction counts only at a node NAN_ROWS_HELD or more NaN rows of
    the column (of the raw matrix ``X``) reach."""
    feat, thr = tree["split_feature"], tree["threshold"]
    dl = (np.asarray(decision_type, dtype=np.int64)
          & reference_dp.DEFAULT_LEFT) != 0
    out = []
    for c, _rows, _mean, t, nan_left in steps:
        nodes = np.flatnonzero(feat == c)
        at = [int(i) for i in nodes
              if np.searchsorted(bounds[c], thr[i], side="left") == t]
        if not at:
            out.append((-1, None))
            continue
        reach = [node_rows(tree, decision_type, X, i) for i in at]
        k = int(np.argmax([len(r) for r in reach]))
        node, rows = at[k], reach[k]
        if np.isnan(X[rows, c]).sum() < NAN_ROWS_HELD:
            out.append((node, None))
        else:
            out.append((node, bool(dl[node]) == nan_left))
    return out
