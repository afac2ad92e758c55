"""The plain reference the benchmark holds the system to. numpy, float64,
independent of the code under test.

- ``root_split``: the reference's gain arithmetic (feature_histogram.hpp,
  as tests/reference_impl.py spells it: leaf_gain = G^2/H with l1 = l2 = 0,
  ``min_data_in_leaf`` / ``min_sum_hessian_in_leaf`` on both children,
  K_EPSILON on the scanned side) over float64 ``np.bincount`` histograms.
- ``parse_model`` / ``predict_raw``: an own reader of the v3 model text and
  a float64 traversal (numerical splits, missing values not supported: the
  benchmark's data has none).
"""

import numpy as np

K_EPSILON = 1e-15


def leaf_gain(g, h):
    return g * g / h


def binary_root_stats(y: np.ndarray):
    """Gradient and hessian of every row at the root of tree 0 of the binary
    objective with boost_from_average: score = logit(mean y), so
    g = p0 - y and h = p0 (1 - p0)."""
    p0 = float(np.mean(y, dtype=np.float64))
    return p0, p0 * (1.0 - p0)


def split_gain(gl, hl, cl, g, h, c, min_data, min_hess):
    """Gain of one split of a node with totals (g, h, c), or -inf where a
    child breaks a minimum. Vectorised over the left sums."""
    gr, hr, cr = g - gl, h - hl, c - cl
    ok = (cl >= min_data) & (cr >= min_data) & (hl >= min_hess) \
        & (hr >= min_hess)
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = leaf_gain(gl, hl) + leaf_gain(gr, hr)
    return np.where(ok, gain, -np.inf)


def root_split(bins: np.ndarray, y: np.ndarray, num_bins: int,
               min_data: float, min_hess: float):
    """Best root split of tree 0 over a bin matrix [n, F] (no missing
    values): returns (gain, feature, threshold_bin, left_count). A row goes
    left when its bin <= threshold_bin."""
    n, f = bins.shape
    p0, h0 = binary_root_stats(y)
    y64 = y.astype(np.float64)
    g_tot, h_tot = p0 * n - y64.sum(), h0 * n
    best = (-np.inf, -1, -1, -1)
    cols = np.ascontiguousarray(bins.T)
    for j in range(f):
        col = cols[j].astype(np.int64)
        cnt = np.bincount(col, minlength=num_bins).astype(np.float64)
        ysum = np.bincount(col, weights=y64, minlength=num_bins)
        cl = np.cumsum(cnt)[:-1]
        gl = p0 * cl - np.cumsum(ysum)[:-1]
        hl = h0 * cl + K_EPSILON
        gain = split_gain(gl, hl, cl, g_tot, h_tot, float(n),
                          min_data, min_hess)
        t = int(np.argmax(gain))
        if gain[t] > best[0]:
            best = (float(gain[t]), j, t, int(cl[t]))
    return best


def gain_of_raw_split(X_col: np.ndarray, y: np.ndarray, threshold: float,
                      min_data: float, min_hess: float):
    """(gain, left_count) of the root split ``x <= threshold`` scored by
    the same arithmetic straight from the raw column."""
    n = len(y)
    p0, h0 = binary_root_stats(y)
    y64 = y.astype(np.float64)
    left = X_col.astype(np.float64) <= threshold
    cl = float(left.sum())
    gl = p0 * cl - y64[left].sum()
    gain = split_gain(np.float64(gl), np.float64(h0 * cl + K_EPSILON),
                      np.float64(cl), p0 * n - y64.sum(), h0 * n, float(n),
                      min_data, min_hess)
    return float(gain), int(cl)


# ------------------------------------------------------------- model text
def parse_model(text: str) -> list:
    """Trees of a v3 model text as dicts of numpy arrays."""
    trees = []
    for block in text.split("\nTree=")[1:]:
        block = block.split("\nend of trees")[0]
        kv = dict(line.split("=", 1) for line in block.splitlines()[1:]
                  if "=" in line)
        if int(kv.get("num_cat", "0")) != 0:
            raise ValueError("categorical splits are not supported here")

        def arr(key, dtype):
            s = kv.get(key, "").split()
            return np.array(s, dtype=dtype)

        trees.append({
            "num_leaves": int(kv["num_leaves"]),
            "split_feature": arr("split_feature", np.int64),
            "threshold": arr("threshold", np.float64),
            "left_child": arr("left_child", np.int64),
            "right_child": arr("right_child", np.int64),
            "leaf_value": arr("leaf_value", np.float64),
            "leaf_count": arr("leaf_count", np.int64),
            "internal_count": arr("internal_count", np.int64),
        })
    return trees


def child_count(tree: dict, child: int) -> int:
    """Rows in a child of a node: children < 0 are leaves (~child)."""
    if child < 0:
        return int(tree["leaf_count"][~child])
    return int(tree["internal_count"][child])


def predict_raw(trees: list, X: np.ndarray) -> np.ndarray:
    """Sum of leaf values in tree order, float64; ``x <= threshold`` goes
    left."""
    X = np.asarray(X, dtype=np.float64)
    if np.isnan(X).any():
        raise ValueError("the reference traversal takes no missing values")
    out = np.zeros(len(X), dtype=np.float64)
    rows = np.arange(len(X))
    for t in trees:
        if t["num_leaves"] == 1:
            out += t["leaf_value"][0]
            continue
        node = np.zeros(len(X), dtype=np.int64)
        live = np.ones(len(X), dtype=bool)
        while live.any():
            nd = node[live]
            go_left = X[rows[live], t["split_feature"][nd]] \
                <= t["threshold"][nd]
            node[live] = np.where(go_left, t["left_child"][nd],
                                  t["right_child"][nd])
            live = node >= 0
        out += t["leaf_value"][~node]
    return out


def midrank_auc(y: np.ndarray, score: np.ndarray) -> float:
    """Mann-Whitney AUC with midranks (ties are common: raw scores are sums
    of discrete leaf values). Copied from bench.midrank_auc."""
    from scipy.stats import rankdata
    npos = float(y.sum())
    nneg = float(len(y) - npos)
    if npos <= 0 or nneg <= 0:
        raise ValueError("AUC needs both classes")
    ranks = rankdata(score, method="average")
    return float((ranks[y > 0].sum() - npos * (npos + 1) / 2) / (npos * nneg))
