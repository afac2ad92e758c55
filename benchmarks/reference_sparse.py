"""The plain reference for a job whose features arrive as a scipy sparse
matrix: numpy, float64, over the RAW columns, independent of the code under
test and of how it stores them (bundles, streams).

- ``root_split``: the best root split of tree 0 over every original column.
  A column's histogram is summed over its CSC non-zeros only; the rows that
  hold no entry are zeros, and their count and label sum are the totals
  less the non-zeros'. Thresholds are the quantiser's bin upper bounds (the
  algorithm is histogram-based: its candidates are bin boundaries), gain
  and minima as ``reference.root_split``.
- ``leaf_index`` / ``leaf_counts``: the leaf every row reaches in a parsed
  model-text tree, traversed over the raw values (``x <= threshold`` goes
  left; an absent entry is 0.0), and the rows a leaf.
- ``leaf_values``: what tree 0 of the binary objective has to print for
  those leaves, from float64 sums of the rows' gradients and hessians: the
  number that shows the precision of the histograms the leaves were cut
  from.
- ``tree_field``: a line of a tree's block that ``reference.parse_model``
  leaves out (``split_gain``, ``leaf_weight``).

tests/reference_sparse.py is a copy of this file.
"""

import numpy as np

K_EPSILON = 1e-15


def split_gain(gl, hl, cl, g, h, c, min_data, min_hess):
    """Gain G_l^2/H_l + G_r^2/H_r of splits of a node with totals (g, h,
    c), -inf where a child breaks a minimum; vectorised over the left."""
    gr, hr, cr = g - gl, h - hl, c - cl
    ok = (cl >= min_data) & (cr >= min_data) & (hl >= min_hess) \
        & (hr >= min_hess)
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = gl * gl / hl + gr * gr / hr
    return np.where(ok, gain, -np.inf)


def binary_root_stats(y):
    """(p0, h0): at the root of tree 0 of the binary objective with
    boost_from_average every row has g = p0 - y and h = p0 (1 - p0)."""
    p0 = float(np.mean(y, dtype=np.float64))
    return p0, p0 * (1.0 - p0)


def column(X_csc, j: int):
    """(rows, float64 values) of the stored entries of column ``j``."""
    a, b = X_csc.indptr[j], X_csc.indptr[j + 1]
    return X_csc.indices[a:b], X_csc.data[a:b].astype(np.float64)


def dense_column(X_csc, j: int) -> np.ndarray:
    """Column ``j`` as float64 [n]: absent entries are 0.0."""
    out = np.zeros(X_csc.shape[0], dtype=np.float64)
    rows, vals = column(X_csc, j)
    out[rows] = vals
    return out


def root_split(X_csc, y, bounds, min_data: float, min_hess: float):
    """Best root split of tree 0 over the raw columns of a CSC matrix:
    (gain, column, threshold value, left count). ``bounds[j]`` are column
    j's ascending bin upper bounds, the last one +inf (None, or a single
    bound: the column cannot split). A row goes left when its value <= the
    threshold."""
    n = X_csc.shape[0]
    p0, h0 = binary_root_stats(y)
    y64 = np.asarray(y, dtype=np.float64)
    y_tot = y64.sum()
    g_tot, h_tot = p0 * n - y_tot, h0 * n
    best = (-np.inf, -1, np.nan, -1)
    for j in range(X_csc.shape[1]):
        ub = None if bounds[j] is None else np.asarray(bounds[j], np.float64)
        if ub is None or len(ub) < 2:
            continue
        rows, vals = column(X_csc, j)
        b = np.searchsorted(ub[:-1], vals, side="left")
        cnt = np.bincount(b, minlength=len(ub)).astype(np.float64)
        ysum = np.bincount(b, weights=y64[rows], minlength=len(ub))
        zero = int(np.searchsorted(ub[:-1], 0.0, side="left"))
        cnt[zero] += n - len(rows)
        ysum[zero] += y_tot - ysum.sum()
        cl = np.cumsum(cnt)[:-1]
        gl = p0 * cl - np.cumsum(ysum)[:-1]
        gain = split_gain(gl, h0 * cl + K_EPSILON, cl, g_tot, h_tot,
                          float(n), min_data, min_hess)
        t = int(np.argmax(gain))
        if gain[t] > best[0]:
            best = (float(gain[t]), j, float(ub[t]), int(cl[t]))
    return best


def gain_of_raw_split(X_csc, j: int, y, threshold: float, min_data: float,
                      min_hess: float):
    """(gain, left count) of the root split ``x_j <= threshold`` by the
    same arithmetic, straight from the raw column."""
    n = X_csc.shape[0]
    p0, h0 = binary_root_stats(y)
    y64 = np.asarray(y, dtype=np.float64)
    left = dense_column(X_csc, j) <= threshold
    cl = float(left.sum())
    gl = p0 * cl - y64[left].sum()
    gain = split_gain(np.float64(gl), np.float64(h0 * cl + K_EPSILON),
                      np.float64(cl), p0 * n - y64.sum(), h0 * n, float(n),
                      min_data, min_hess)
    return float(gain), int(cl)


def _values_at(X_csc, j: int, rows: np.ndarray) -> np.ndarray:
    """Column ``j`` at the ascending row list ``rows``: the stored entries
    (their rows ascend too) matched by a binary search of the shorter list
    in the longer, 0.0 elsewhere."""
    r, v = column(X_csc, j)
    x = np.zeros(len(rows), dtype=np.float64)
    if len(r) == X_csc.shape[0]:
        return v[rows]
    if not len(r) or not len(rows):
        return x
    if len(r) <= len(rows):
        at = np.minimum(np.searchsorted(rows, r), len(rows) - 1)
        hit = rows[at] == r
        x[at[hit]] = v[hit]
    else:
        at = np.minimum(np.searchsorted(r, rows), len(r) - 1)
        hit = r[at] == rows
        x[hit] = v[at[hit]]
    return x


def leaf_index(tree: dict, X_csc) -> np.ndarray:
    """The leaf of every row of ``X_csc`` in one parsed tree
    (``reference.parse_model``), int32 [n]. The rows are kept as one index
    list a node, so a level costs one pass over the rows."""
    n = X_csc.shape[0]
    leaf = np.zeros(n, dtype=np.int32)
    if tree["num_leaves"] == 1:
        return leaf
    pending = {0: np.arange(n, dtype=np.int64)}
    # a child's index is larger than its parent's, so ascending order
    # meets every node after the node that fills it
    for node in range(tree["num_leaves"] - 1):
        rows = pending.pop(node)
        j = int(tree["split_feature"][node])
        left = _values_at(X_csc, j, rows) <= tree["threshold"][node]
        for child, part in ((int(tree["left_child"][node]), rows[left]),
                            (int(tree["right_child"][node]), rows[~left])):
            if child < 0:
                leaf[part] = ~child
            else:
                pending[child] = part
    return leaf


def leaf_counts(tree: dict, X_csc, leaf=None) -> np.ndarray:
    """Rows of ``X_csc`` in every leaf of one parsed tree, int64
    [num_leaves]; ``leaf`` is ``leaf_index``'s answer where the caller has
    it."""
    if leaf is None:
        leaf = leaf_index(tree, X_csc)
    return np.bincount(leaf, minlength=tree["num_leaves"]).astype(np.int64)


def leaf_values(tree: dict, leaf: np.ndarray, y, learning_rate: float,
                lambda_l2: float = 0.0) -> np.ndarray:
    """The leaf values of TREE 0 of the binary objective with
    boost_from_average, float64 [num_leaves]: the average's log-odds plus
    ``learning_rate`` times -G / (H + lambda_l2) over the rows ``leaf``
    (``leaf_index``) sends to each leaf, g = p0 - y and h = p0 (1 - p0) a
    row. NaN for a leaf no row reaches."""
    p0, h0 = binary_root_stats(y)
    k = tree["num_leaves"]
    cnt = np.bincount(leaf, minlength=k).astype(np.float64)
    ysum = np.bincount(leaf, weights=np.asarray(y, np.float64), minlength=k)
    g, h = p0 * cnt - ysum, h0 * cnt
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.log(p0 / (1.0 - p0)) \
            + learning_rate * -g / (h + lambda_l2)


def tree_field(model_text: str, k: int, key: str) -> np.ndarray:
    """The numbers of line ``key=`` in the block of tree ``k`` of a v3
    model text, float64."""
    block = model_text.split("\nTree=")[1 + k].split("\nend of trees")[0]
    for line in block.splitlines():
        if line.startswith(key + "="):
            return np.array(line.split("=", 1)[1].split(), dtype=np.float64)
    raise KeyError(f"tree {k} has no line {key!r}")
