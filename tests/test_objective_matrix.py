"""Per-objective behavior matrix over the regression/xentropy families —
the analog of the reference's giant parametrized objective coverage
(reference: tests/python_package_test/test_engine.py: test_regression,
test_quantile, test_huber, test_poisson/gamma/tweedie, test_mape,
test_xentropy; semantics from src/objective/regression_objective.hpp and
xentropy_objective.hpp)."""

import numpy as np
import pytest

pytestmark = pytest.mark.slow

import lightgbm_tpu as lgb


def _positive_problem(seed, n=1200):
    rng = np.random.RandomState(seed)
    X = rng.uniform(-1, 1, size=(n, 4))
    mu = np.exp(0.8 * X[:, 0] - 0.5 * X[:, 1])
    return X, mu, rng


def _train(X, y, objective, extra=None, rounds=40):
    params = {"objective": objective, "num_leaves": 15,
              "min_data_in_leaf": 20, "learning_rate": 0.1,
              "verbosity": -1, **(extra or {})}
    evals = {}
    booster = lgb.train(params, lgb.Dataset(X, label=y), rounds,
                        valid_sets=[lgb.Dataset(X, label=y)],
                        valid_names=["t"], evals_result=evals)
    return booster, evals["t"]


@pytest.mark.parametrize("objective,metric", [
    ("regression", "l2"), ("regression_l1", "l1"), ("huber", "huber"),
    ("fair", "fair"), ("mape", "mape"),
])
def test_regression_family_metric_improves(objective, metric):
    rng = np.random.RandomState(11)
    n = 1200
    X = rng.uniform(-2, 2, size=(n, 4))
    y = 2 * X[:, 0] + np.sin(2 * X[:, 1]) + 0.2 * rng.normal(size=n)
    if objective == "mape":
        y = y + 6.0          # mape needs labels away from 0
    _, ev = _train(X, y, objective)
    hist = ev[metric]
    assert hist[-1] < hist[0] * 0.8, (objective, hist[0], hist[-1])


@pytest.mark.parametrize("objective", ["poisson", "gamma", "tweedie"])
def test_positive_objectives_log_link(objective):
    """Poisson/gamma/tweedie predict via exp(score): predictions must be
    positive and the deviance metric must improve
    (regression_objective.hpp:398,677,712)."""
    X, mu, rng = _positive_problem(13)
    if objective == "poisson":
        y = rng.poisson(mu).astype(np.float64)
    else:
        y = mu * rng.gamma(2.0, 0.5, size=len(mu))
    booster, ev = _train(X, y, objective)
    pred = booster.predict(X)
    assert np.all(pred > 0)
    hist = ev[objective]
    assert hist[-1] < hist[0], (hist[0], hist[-1])
    # predictions track the conditional mean scale
    assert 0.3 < np.mean(pred) / np.mean(y) < 3.0


@pytest.mark.parametrize("alpha", [0.1, 0.5, 0.9])
def test_quantile_coverage(alpha):
    """Quantile regression's empirical coverage must approximate alpha
    (regression_objective.hpp:478 + test_engine.py quantile tests)."""
    rng = np.random.RandomState(17)
    n = 4000
    X = rng.uniform(-1, 1, size=(n, 3))
    y = X[:, 0] + rng.normal(scale=0.5, size=n)
    booster, _ = _train(X, y, "quantile", extra={"alpha": alpha}, rounds=60)
    cover = float(np.mean(y <= booster.predict(X)))
    assert abs(cover - alpha) < 0.08, (alpha, cover)


def test_huber_less_outlier_sensitive_than_l2():
    rng = np.random.RandomState(19)
    n = 2000
    X = rng.uniform(-1, 1, size=(n, 3))
    y = X[:, 0].copy()
    out_rows = rng.choice(n, 40, replace=False)
    y[out_rows] += 60.0 * rng.choice([-1, 1], size=40)   # gross outliers
    clean = np.setdiff1d(np.arange(n), out_rows)

    def clean_mse(objective):
        b, _ = _train(X, y, objective)
        p = b.predict(X)
        return float(np.mean((p[clean] - X[clean, 0]) ** 2))

    assert clean_mse("huber") < clean_mse("regression") * 0.8


def test_cross_entropy_objectives():
    """xentropy/xentlambda accept soft labels in [0, 1]
    (xentropy_objective.hpp:44,152)."""
    rng = np.random.RandomState(23)
    n = 1500
    X = rng.uniform(-2, 2, size=(n, 4))
    p_true = 1.0 / (1.0 + np.exp(-(X[:, 0] + 0.5 * X[:, 1])))
    y = np.clip(p_true + 0.1 * rng.normal(size=n), 0, 1)   # soft labels
    for objective, metric in (("cross_entropy", "cross_entropy"),
                              ("cross_entropy_lambda",
                               "cross_entropy_lambda")):
        booster, ev = _train(X, y, objective)
        pred = booster.predict(X)
        if objective == "cross_entropy":
            # sigmoid output (xentropy_objective.hpp:102-104)
            assert np.all((pred >= 0) & (pred <= 1))
        else:
            # xentlambda converts via log1p(exp(.)) — positive, unbounded
            # (xentropy_objective.hpp:233-235)
            assert np.all(pred >= 0)
        hist = ev[metric]
        assert hist[-1] < hist[0], (objective, hist[0], hist[-1])
        # predictions correlate with the underlying probability
        assert np.corrcoef(pred, p_true)[0, 1] > 0.85


def test_reg_sqrt_label_transform():
    """reg_sqrt trains on sqrt(label) and squares predictions back
    (regression_objective.hpp reg_sqrt handling)."""
    rng = np.random.RandomState(29)
    n = 1500
    X = rng.uniform(0, 1, size=(n, 3))
    y = (3 * X[:, 0] + 0.1 * rng.normal(size=n)) ** 2
    b_sqrt, _ = _train(X, y, "regression", extra={"reg_sqrt": True})
    pred = b_sqrt.predict(X)
    r2 = 1 - np.mean((pred - y) ** 2) / np.var(y)
    assert r2 > 0.8, r2


def test_objective_alias_resolution():
    """Objective aliases map like the reference's ParseObjectiveAlias."""
    rng = np.random.RandomState(31)
    X = rng.normal(size=(400, 3))
    y = X[:, 0]
    for alias in ("mse", "l2", "mean_squared_error"):
        b = lgb.train({"objective": alias, "num_leaves": 7,
                       "verbosity": -1}, lgb.Dataset(X, label=y), 3)
        assert b._boosting.objective.name in ("regression", "l2"), alias


def test_metric_formulas_match_reference_pointwise():
    """Pointwise numeric audit of the regression metric formulas against
    the reference LossOnPoint definitions (regression_metric.hpp) — the
    gamma sign and gamma_deviance scale bugs were caught this way."""
    from lightgbm_tpu import metrics as M
    from lightgbm_tpu.config import Config
    rng = np.random.RandomState(0)
    label = np.abs(rng.normal(size=300)) + 0.5
    score = np.abs(rng.normal(size=300)) + 0.5
    cfg = Config.from_params({"alpha": 0.9, "fair_c": 1.0,
                              "tweedie_variance_power": 1.5})

    d = score - label
    x = np.abs(d)
    theta = -1.0 / score
    tmp = label / (score + 1e-9)
    rho = 1.5
    expect = {
        "l2": np.mean(d ** 2),
        "l1": np.mean(x),
        "huber": np.mean(np.where(x <= 0.9, 0.5 * d * d,
                                  0.9 * (x - 0.45))),
        "fair": np.mean(x - np.log1p(x)),
        "poisson": np.mean(score - label * np.log(score)),
        "mape": np.mean(x / np.maximum(1.0, np.abs(label))),
        "gamma": np.mean(-((label * theta + np.log(-theta)) / 1.0
                           + (np.log(label) - np.log(label)))),
        # AverageLoss override: sum_loss * 2, sum_weights IGNORED
        # (regression_metric.hpp:291-293) — 2x the SUM, not a mean
        "gamma_deviance": 2.0 * np.sum(tmp - np.log(tmp) - 1.0),
        "tweedie": np.mean(-label * score ** (1 - rho) / (1 - rho)
                           + score ** (2 - rho) / (2 - rho)),
    }
    for name, ref in expect.items():
        m = M.create_metric(name, cfg)
        m.init(label, None)
        got = float(m.eval(score, None))
        np.testing.assert_allclose(got, ref, rtol=1e-9, err_msg=name)

    # weighted gamma_deviance: loss is weighted per row, but the final
    # AverageLoss divides by nothing — 2 * sum(w * loss)
    w = np.abs(rng.normal(size=300)) + 0.1
    m = M.create_metric("gamma_deviance", cfg)
    m.init(label, w)
    got = float(m.eval(score, None))
    np.testing.assert_allclose(
        got, 2.0 * np.sum(w * (tmp - np.log(tmp) - 1.0)), rtol=1e-9)


def test_gradient_formulas_match_reference_pointwise():
    """Pointwise audit of regression-family gradients/hessians against the
    reference GetGradients formulas (regression_objective.hpp:127-751)."""
    import jax.numpy as jnp
    from lightgbm_tpu import objectives as O
    from lightgbm_tpu.config import Config
    rng = np.random.RandomState(0)
    n = 300
    label_pos = np.abs(rng.normal(size=n)) + 0.5
    label_any = rng.normal(size=n)
    score = rng.normal(size=n) * 0.8
    rho = 1.5
    d = score - label_any
    checks = {
        "regression": (label_any, d, np.ones(n)),
        "regression_l1": (label_any, np.sign(d), np.ones(n)),
        "huber": (label_any,
                  np.where(np.abs(d) <= 0.9, d, np.sign(d) * 0.9),
                  np.ones(n)),
        "fair": (label_any, d / (np.abs(d) + 1.0),
                 1.0 / (np.abs(d) + 1.0) ** 2),
        "poisson": (label_pos, np.exp(score) - label_pos,
                    np.exp(score + 0.7)),
        # delta = score - label (regression_objective.hpp:495-500)
        "quantile": (label_any,
                     np.where(d >= 0, 1 - 0.9, -0.9), np.ones(n)),
        "gamma": (label_pos, 1.0 - label_pos * np.exp(-score),
                  label_pos * np.exp(-score)),
        "tweedie": (label_pos,
                    -label_pos * np.exp((1 - rho) * score)
                    + np.exp((2 - rho) * score),
                    -label_pos * (1 - rho) * np.exp((1 - rho) * score)
                    + (2 - rho) * np.exp((2 - rho) * score)),
    }
    for name, (lab, g_ref, h_ref) in checks.items():
        cfg = Config.from_params({"objective": name, "alpha": 0.9,
                                  "fair_c": 1.0,
                                  "tweedie_variance_power": 1.5,
                                  "poisson_max_delta_step": 0.7})
        obj = O.create_objective(cfg)
        obj.init(lab, None)
        g, h = obj.get_grad_hess(jnp.asarray(score))
        np.testing.assert_allclose(np.asarray(g), g_ref, rtol=1e-5,
                                   atol=1e-6, err_msg=f"{name} grad")
        np.testing.assert_allclose(np.asarray(h), h_ref, rtol=1e-5,
                                   atol=1e-6, err_msg=f"{name} hess")


def test_binary_multiclass_gradients_match_reference():
    """Binary (sigmoid + scale_pos_weight) and multiclass softmax gradients
    pinned to the reference formulas (binary_objective.hpp:105-121,
    multiclass_objective.hpp softmax factor k/(k-1))."""
    import jax.numpy as jnp
    from lightgbm_tpu import objectives as O
    from lightgbm_tpu.config import Config
    rng = np.random.RandomState(0)
    n = 300
    y = (rng.uniform(size=n) > 0.6).astype(np.float64)
    score = rng.normal(size=n)
    cfg = Config.from_params({"objective": "binary", "sigmoid": 2.0,
                              "scale_pos_weight": 1.3, "verbosity": -1})
    obj = O.create_objective(cfg)
    obj.init(y, None)
    g, h = obj.get_grad_hess(jnp.asarray(score))
    lab = np.where(y > 0, 1.0, -1.0)
    lw = np.where(y > 0, 1.3, 1.0)
    sig = 2.0
    resp = -lab * sig / (1.0 + np.exp(lab * sig * score))
    np.testing.assert_allclose(np.asarray(g), resp * lw, rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(h), np.abs(resp) * (sig - np.abs(resp)) * lw,
        rtol=1e-4, atol=1e-6)

    K = 3
    yk = rng.randint(0, K, size=n).astype(np.float64)
    objm = O.create_objective(Config.from_params(
        {"objective": "multiclass", "num_class": K, "verbosity": -1}))
    objm.init(yk, None)
    S = rng.normal(size=(n, K))
    gm, hm = objm.get_grad_hess(jnp.asarray(S))
    P = np.exp(S - S.max(1, keepdims=True))
    P /= P.sum(1, keepdims=True)
    Y = np.eye(K)[yk.astype(int)]
    np.testing.assert_allclose(np.asarray(gm), P - Y, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(hm),
                               K / (K - 1.0) * P * (1 - P),
                               rtol=1e-4, atol=1e-6)


def test_cv_runs_and_improves():
    """lgb.cv: stratified folds, mean/stdv curves (engine.py:392-470)."""
    rng = np.random.RandomState(7)
    n = 1200
    X = rng.normal(size=(n, 5))
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.normal(size=n) > 0).astype(
        np.float64)
    res = lgb.cv({"objective": "binary", "num_leaves": 15, "metric": "auc",
                  "verbosity": -1},
                 lgb.Dataset(X, label=y, free_raw_data=False),
                 num_boost_round=15, nfold=3, stratified=True, seed=3)
    key = [k for k in res if k.endswith("auc-mean")][0]
    curve = res[key]
    assert len(curve) == 15
    assert curve[-1] > 0.85 and curve[-1] >= curve[0] - 1e-9
    sd_key = [k for k in res if k.endswith("auc-stdv")][0]
    assert len(res[sd_key]) == 15


def test_average_precision_matches_reference_sweep():
    """average_precision must follow the reference's threshold-group sweep
    exactly (tied scores form one group whose precision is taken AFTER the
    whole group, binary_metric.hpp:270+) — ours deviated on ties."""
    from lightgbm_tpu import metrics as M
    from lightgbm_tpu.config import Config

    def ref_ap(y, score, w=None):
        order = np.argsort(-score, kind="stable")
        wv = np.ones(len(y)) if w is None else w
        cur_pos = cur_neg = sum_pos = sum_pred = accum = 0.0
        thr = score[order[0]]
        for i in order:
            if score[i] != thr:
                thr = score[i]
                sum_pos += cur_pos
                sum_pred += cur_pos + cur_neg
                accum += cur_pos * (sum_pos / sum_pred)
                cur_pos = cur_neg = 0.0
            if y[i] > 0:
                cur_pos += wv[i]
            else:
                cur_neg += wv[i]
        sum_pos += cur_pos
        sum_pred += cur_pos + cur_neg
        accum += cur_pos * (sum_pos / sum_pred)
        sw = wv.sum()
        return accum / sum_pos if (sum_pos > 0 and sum_pos != sw) else 1.0

    rng = np.random.RandomState(0)
    for use_w in (False, True):
        y = (rng.uniform(size=400) > 0.5).astype(np.float64)
        score = np.round(rng.normal(size=400), 1)       # heavy ties
        w = rng.uniform(0.5, 2.0, size=400) if use_w else None
        m = M.create_metric("average_precision", Config.from_params({}))
        m.init(y, w)
        np.testing.assert_allclose(m.eval(score, None), ref_ap(y, score, w),
                                   rtol=1e-12)


def test_ranking_metrics_match_reference():
    """NDCG@k (2^l - 1 gains, log2 discounts, ideal from sorted labels,
    empty-gain queries = 1; dcg_calculator.cpp) and MAP@k
    (map_metric.hpp:74-104 CalMapAtK denominator min(npos, k)) pinned to
    literal reference transcriptions."""
    from lightgbm_tpu import metrics as M
    from lightgbm_tpu.config import Config
    rng = np.random.RandomState(0)
    groups = np.array([10, 7, 13, 10])
    n = groups.sum()
    y_rel = rng.randint(0, 4, size=n).astype(np.float64)
    y_bin = (rng.uniform(size=n) > 0.6).astype(np.float64)
    score = rng.normal(size=n)

    def ref_ndcg(k):
        out, s = [], 0
        for g in groups:
            yy, ss = y_rel[s:s+g], score[s:s+g]
            s += g
            kk = min(k, g)
            order = np.argsort(-ss, kind="stable")
            dcg = sum((2 ** yy[order[i]] - 1) / np.log2(2 + i)
                      for i in range(kk))
            ideal = np.sort(yy)[::-1]
            idcg = sum((2 ** ideal[i] - 1) / np.log2(2 + i)
                       for i in range(kk))
            out.append(1.0 if idcg <= 0 else dcg / idcg)
        return float(np.mean(out))

    def ref_map(k):
        out, s = [], 0
        for g in groups:
            yy, ss = y_bin[s:s+g], score[s:s+g]
            s += g
            order = np.argsort(-ss, kind="stable")
            kk = min(k, g)
            npos = int(np.sum(yy > 0.5))
            hit, sap = 0, 0.0
            for j in range(kk):
                if yy[order[j]] > 0.5:
                    hit += 1
                    sap += hit / (j + 1.0)
            out.append(sap / min(npos, kk) if npos > 0 else 1.0)
        return float(np.mean(out))

    for k in (1, 3, 5):
        m = M.create_metric("ndcg", Config.from_params({"eval_at": [k]}))
        m.init(y_rel, None, groups)
        got = m.eval(score, None)
        got = got[0] if isinstance(got, (list, tuple, np.ndarray)) else got
        np.testing.assert_allclose(got, ref_ndcg(k), rtol=1e-9)
        m2 = M.create_metric("map", Config.from_params({"eval_at": [k]}))
        m2.init(y_bin, None, groups)
        got2 = m2.eval(score, None)
        got2 = got2[0] if isinstance(got2, (list, tuple, np.ndarray)) \
            else got2
        np.testing.assert_allclose(got2, ref_map(k), rtol=1e-9)


def test_auc_mu_raw_scores_and_weight_matrix():
    """auc_mu ranks by raw-score hyperplane distances (no softmax) and
    honors auc_mu_weights (multiclass_metric.hpp:238-266: decision value
    (W_i - W_j) . score scaled by t1)."""
    from lightgbm_tpu import metrics as M
    from lightgbm_tpu.config import Config
    rng = np.random.RandomState(0)
    K, n = 3, 300
    y = rng.randint(0, K, size=n).astype(np.float64)
    S = rng.normal(size=(n, K))
    m = M.create_metric("auc_mu", Config.from_params({"num_class": K}))
    m.init(y, None)
    base = m.eval(S, None)
    # raw-score ranking is invariant to per-row shifts (softmax probs are
    # not order-equivalent across rows; the old implementation failed this)
    shifted = m.eval(S + rng.normal(size=(n, 1)), None)
    np.testing.assert_allclose(base, shifted, rtol=1e-12)
    # uniform default equals mean pairwise AUC of score differences
    from sklearn.metrics import roc_auc_score
    aucs = []
    for a in range(K):
        for b in range(a + 1, K):
            mask = (y == a) | (y == b)
            aucs.append(roc_auc_score((y[mask] == a).astype(float),
                                      S[mask, a] - S[mask, b]))
    np.testing.assert_allclose(base, np.mean(aucs), rtol=1e-9)
    # a custom weight matrix changes the decision values
    mw = M.create_metric("auc_mu", Config.from_params(
        {"num_class": K, "auc_mu_weights": [0, 1, 5, 1, 0, 1, 5, 1, 0]}))
    mw.init(y, None)
    assert abs(mw.eval(S, None) - base) > 1e-4


def test_treeshap_matches_bruteforce_shapley():
    """pred_contrib equals brute-force path-dependent Shapley values
    (exact subset enumeration with cover-weighted conditional expectations
    — the semantics of the reference's TreeSHAP, tree.cpp PredictContrib)."""
    import math
    from itertools import combinations
    rng = np.random.RandomState(0)
    n, F = 600, 4
    X = rng.normal(size=(n, F))
    y = X[:, 0] + 0.7 * X[:, 1] * X[:, 2] + 0.1 * rng.normal(size=n)
    b = lgb.train({"objective": "regression", "num_leaves": 8,
                   "min_data_in_leaf": 20, "verbosity": -1},
                  lgb.Dataset(X, label=y), 1)
    contrib = b.predict(X[:5], pred_contrib=True)
    tree = b._boosting.host_trees[0]
    sf = np.asarray(tree.split_feature)
    thr = np.asarray(tree.threshold)
    lc = np.asarray(tree.left_child)
    rc = np.asarray(tree.right_child)
    lv = np.asarray(tree.leaf_value)
    lcount = np.asarray(tree.leaf_count, float)
    icount = np.asarray(tree.internal_count, float)

    def cover(node):
        return icount[node] if node >= 0 else lcount[~node]

    def exp_f(x, S, node=0):
        if node < 0:
            return lv[~node]
        f = sf[node]
        if f in S:
            return exp_f(x, S, lc[node] if x[f] <= thr[node] else rc[node])
        wl, wr = cover(lc[node]), cover(rc[node])
        return (wl * exp_f(x, S, lc[node])
                + wr * exp_f(x, S, rc[node])) / (wl + wr)

    for r in range(5):
        phis = np.zeros(F + 1)
        for i in range(F):
            others = [f for f in range(F) if f != i]
            for k in range(F):
                for S in combinations(others, k):
                    w = (math.factorial(k) * math.factorial(F - k - 1)
                         / math.factorial(F))
                    phis[i] += w * (exp_f(X[r], set(S) | {i})
                                    - exp_f(X[r], set(S)))
        phis[F] = exp_f(X[r], set())
        np.testing.assert_allclose(contrib[r], phis, rtol=1e-5, atol=1e-7)


def test_percentile_functions_match_reference():
    """Literal transcriptions of PercentileFun / WeightedPercentileFun
    (regression_objective.hpp:18-88) pinned against our implementations on
    tie-heavy data, including the label_t (f32) result rounding of the
    BoostFromScore instantiation (regression_objective.hpp:241-246)."""
    from lightgbm_tpu.objectives import _percentile, _weighted_percentile

    def ref_percentile(data, alpha, T=np.float64):
        # PercentileFun: ArgMaxAtK partitions descending (array_args.h:128
        # "k=0 means get the max"); a full descending sort is the same
        # selection, and both branches of `pos > cnt/2` pick
        # v1=desc[pos-1], v2=desc[pos]
        data = np.asarray(data, T)
        cnt = len(data)
        if cnt <= 1:
            return T(data[0])
        desc = np.sort(data)[::-1]
        float_pos = (1.0 - alpha) * cnt
        pos = int(float_pos)
        if pos < 1:
            return desc[0]                       # ArgMax
        if pos >= cnt:
            return desc[-1]                      # ArgMin
        bias = float_pos - pos
        v1, v2 = desc[pos - 1], desc[pos]
        return T(v1 - (v1 - v2) * bias)

    def ref_weighted_percentile(data, weight, alpha, T=np.float64):
        data = np.asarray(data, T)
        cnt = len(data)
        if cnt <= 1:
            return T(data[0])
        sorted_idx = np.argsort(data, kind="stable")   # std::stable_sort
        weighted_cdf = np.cumsum(np.asarray(weight, np.float64)[sorted_idx])
        threshold = weighted_cdf[cnt - 1] * alpha
        pos = int(np.searchsorted(weighted_cdf, threshold, side="right"))
        pos = min(pos, cnt - 1)
        if pos == 0 or pos == cnt - 1:
            return T(data[sorted_idx[pos]])
        assert threshold >= weighted_cdf[pos - 1]      # CHECK_GE
        assert threshold < weighted_cdf[pos]           # CHECK_LT
        v1 = data[sorted_idx[pos - 1]]
        v2 = data[sorted_idx[pos]]
        if weighted_cdf[pos + 1] - weighted_cdf[pos] >= 1.0:
            return T((threshold - weighted_cdf[pos])
                     / (weighted_cdf[pos + 1] - weighted_cdf[pos])
                     * (v2 - v1) + v1)
        return T(v2)

    rng = np.random.RandomState(11)
    alphas = [0.05, 0.1, 0.5, 0.9, 0.95]
    for trial in range(40):
        n = int(rng.choice([1, 2, 3, 5, 10, 101, 500]))
        # heavy ties: values drawn from a tiny grid
        data = np.round(rng.normal(size=n) * 2.0, 1)
        # weights spanning tiny-to-large so the cdf-gap >= 1.0 branch and
        # the v2 branch are both exercised
        weight = np.exp(rng.uniform(-3, 2, size=n))
        for alpha in alphas:
            ours = _percentile(data, alpha)
            ref = ref_percentile(data, alpha)
            np.testing.assert_allclose(ours, ref, rtol=0, atol=0,
                                       err_msg=f"n={n} alpha={alpha}")
            ours_w = _weighted_percentile(data, weight, alpha)
            ref_w = ref_weighted_percentile(data, weight, alpha)
            np.testing.assert_allclose(ours_w, ref_w, rtol=0, atol=0,
                                       err_msg=f"weighted n={n} alpha={alpha}")
            # the BoostFromScore instantiation stores label_t (f32) data
            # and casts the result back to label_t; its C++ `v1 - v2` also
            # rounds to f32 BEFORE the double interpolation (float-float
            # arithmetic stays float), while our pipeline interpolates
            # fully in f64 — the rounding error scales with the data
            # SPREAD (ulp of v1-v2), not the result, so bound absolutely
            f32 = np.float32
            np.testing.assert_allclose(
                f32(_percentile(data.astype(f32), alpha)),
                ref_percentile(data, alpha, T=f32), rtol=0,
                atol=1.2e-7 * max(1.0, float(np.ptp(data))),
                err_msg=f"f32 n={n} alpha={alpha}")
