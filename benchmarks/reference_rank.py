"""The plain ranking reference the benchmark holds the system to. numpy,
float64, independent of lightgbm_tpu/ranking.py.

- ``lambdarank``: lambdas and hessians per query straight from
  LambdarankNDCG::GetGradientsForOneQuery (rank_objective.hpp:142-227):
  stable descending sort, ``i`` over the first ``truncation_level`` sorted
  positions, ``j > i``, high / low by label, delta NDCG, the score-distance
  regularisation and the log2 normalisation. Vectorised over one query's
  pairs, one query at a time: no buckets, no padding.
- ``ndcg_at_k``: rank_metric.hpp / dcg_calculator.cpp (a query without a
  relevant document counts as 1).
- ``root_split`` / ``gain_of_raw_split``: the reference's gain arithmetic
  (reference.py) over float64 histograms of a GIVEN gradient and hessian.
"""

import numpy as np

from reference import K_EPSILON, split_gain


def label_gains(max_label: int = 31) -> np.ndarray:
    """dcg_calculator.cpp DefaultLabelGain: 2^l - 1."""
    return np.asarray([0.0] + [float((1 << i) - 1)
                               for i in range(1, max_label)])


def _bounds(sizes):
    return np.concatenate([[0], np.cumsum(np.asarray(sizes, np.int64))])


def _discount(k: int) -> np.ndarray:
    return 1.0 / np.log2(2.0 + np.arange(k))


def max_dcg_at_k(k: int, label: np.ndarray, gains: np.ndarray) -> float:
    """dcg_calculator.cpp CalMaxDCGAtK: the k largest gains in order."""
    top = np.sort(gains[label.astype(np.int64)])[::-1][:k]
    return float(np.sum(top * _discount(len(top))))


def lambdarank_query(label, score, gains, sigmoid, norm, truncation_level,
                     pair_round=None):
    """(lambdas, hessians) of one query, float64. ``pair_round`` rounds
    each pair's factors (score difference, delta NDCG, sigmoid) the way a
    lower-precision pair stage would: for the reading that shows the
    comparison's tolerance would catch it, never for the reference."""
    cnt = len(label)
    lam, hess = np.zeros(cnt), np.zeros(cnt)
    if cnt < 2:
        return lam, hess
    rnd = pair_round or (lambda x: x)
    mx = max_dcg_at_k(truncation_level, label, gains)
    inv_max_dcg = 1.0 / mx if mx > 0 else 0.0
    order = np.argsort(-score, kind="stable")
    s, lab = score[order], label[order]
    gain = gains[lab.astype(np.int64)]
    disc = _discount(cnt)
    t = min(truncation_level, cnt - 1)
    i = np.arange(t)[:, None]
    j = np.arange(cnt)[None, :]
    ok = (j > i) & (lab[:t, None] != lab[None, :])
    i_high = lab[:t, None] > lab[None, :]
    sign = np.where(i_high, 1.0, -1.0)
    delta_score = rnd(sign * (s[:t, None] - s[None, :]))
    delta_ndcg = (sign * (gain[:t, None] - gain[None, :])
                  * np.abs(disc[:t, None] - disc[None, :]) * inv_max_dcg)
    if norm and s[0] != s[-1]:
        delta_ndcg = delta_ndcg / (0.01 + np.abs(delta_score))
    delta_ndcg = rnd(delta_ndcg)
    p = rnd(1.0 / (1.0 + np.exp(sigmoid * delta_score)))
    p_lambda = np.where(ok, rnd(-sigmoid * delta_ndcg * p), 0.0)
    p_hess = np.where(ok, rnd(sigmoid * sigmoid * delta_ndcg * p
                              * (1.0 - p)), 0.0)
    # the high document takes +p_lambda, the low one -p_lambda
    to_i = sign * p_lambda
    lam_s = -to_i.sum(axis=0)
    lam_s[:t] += to_i.sum(axis=1)
    hess_s = p_hess.sum(axis=0)
    hess_s[:t] += p_hess.sum(axis=1)
    sum_lambdas = -2.0 * p_lambda.sum()
    if norm and sum_lambdas > 0:
        nf = np.log2(1.0 + sum_lambdas) / sum_lambdas
        lam_s, hess_s = lam_s * nf, hess_s * nf
    lam[order], hess[order] = lam_s, hess_s
    return lam, hess


def lambdarank(label, score, sizes, sigmoid=1.0, norm=True,
               truncation_level=30, gains=None, pair_round=None):
    """(lambdas [N], hessians [N]) over all queries, float64."""
    gains = label_gains() if gains is None else np.asarray(gains, np.float64)
    label = np.asarray(label, np.float64)
    score = np.asarray(score, np.float64)
    b = _bounds(sizes)
    lam, hess = np.zeros(len(label)), np.zeros(len(label))
    for q in range(len(b) - 1):
        lam[b[q]:b[q + 1]], hess[b[q]:b[q + 1]] = lambdarank_query(
            label[b[q]:b[q + 1]], score[b[q]:b[q + 1]], gains, sigmoid,
            norm, truncation_level, pair_round)
    return lam, hess


def per_query_error(got, want, sizes):
    """Per query: max |got - want| over max |want| ([Q]; where a query's
    ``want`` is all zero, max |got| itself)."""
    b = _bounds(sizes)[:-1]
    diff = np.maximum.reduceat(np.abs(np.asarray(got, np.float64) - want), b)
    scale = np.maximum.reduceat(np.abs(want), b)
    return np.where(scale > 0, diff / np.where(scale > 0, scale, 1.0), diff)


def ndcg_at_k(label, score, sizes, k, gains=None) -> float:
    """Mean NDCG@k over the queries (rank_metric.hpp NDCGMetric::Eval,
    unweighted)."""
    gains = label_gains() if gains is None else np.asarray(gains, np.float64)
    label = np.asarray(label, np.float64)
    score = np.asarray(score, np.float64)
    b = _bounds(sizes)
    total = 0.0
    for q in range(len(b) - 1):
        lab, sc = label[b[q]:b[q + 1]], score[b[q]:b[q + 1]]
        mx = max_dcg_at_k(k, lab, gains)
        if mx <= 0:
            total += 1.0
            continue
        top = np.argsort(-sc, kind="stable")[:k]
        total += float(np.sum(gains[lab[top].astype(np.int64)]
                              * _discount(len(top)))) / mx
    return total / max(len(b) - 1, 1)


def root_split(bins: np.ndarray, g: np.ndarray, h: np.ndarray, num_bins: int,
               min_data: float, min_hess: float):
    """Best root split over a bin matrix [n, F] (no missing values) for the
    given per-row gradient and hessian: (gain, feature, threshold_bin,
    left_count). A row goes left when its bin <= threshold_bin."""
    n, f = bins.shape
    g, h = np.asarray(g, np.float64), np.asarray(h, np.float64)
    g_tot, h_tot = g.sum(), h.sum()
    best = (-np.inf, -1, -1, -1)
    cols = np.ascontiguousarray(bins.T)
    for j in range(f):
        col = cols[j].astype(np.int64)
        cl = np.cumsum(np.bincount(col, minlength=num_bins))[:-1] \
            .astype(np.float64)
        gl = np.cumsum(np.bincount(col, weights=g, minlength=num_bins))[:-1]
        hl = np.cumsum(np.bincount(col, weights=h, minlength=num_bins))[:-1] \
            + K_EPSILON
        gain = split_gain(gl, hl, cl, g_tot, h_tot, float(n),
                          min_data, min_hess)
        t = int(np.argmax(gain))
        if gain[t] > best[0]:
            best = (float(gain[t]), j, t, int(cl[t]))
    return best


def gain_of_raw_split(X_col: np.ndarray, g: np.ndarray, h: np.ndarray,
                      threshold: float, min_data: float, min_hess: float):
    """(gain, left_count) of the root split ``x <= threshold`` by the same
    arithmetic straight from the raw column."""
    g, h = np.asarray(g, np.float64), np.asarray(h, np.float64)
    left = X_col.astype(np.float64) <= threshold
    cl = float(left.sum())
    gain = split_gain(np.float64(g[left].sum()),
                      np.float64(h[left].sum() + K_EPSILON), np.float64(cl),
                      g.sum(), h.sum(), float(len(g)), min_data, min_hess)
    return float(gain), int(cl)
