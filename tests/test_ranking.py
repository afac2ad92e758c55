"""Ranking objective/metric tests.

Behavior-level parity with the reference's lambdarank coverage
(tests/python_package_test/test_engine.py lambdarank tests): training
improves NDCG on a synthetic ranking problem, and the metric math matches a
straightforward reference implementation.
"""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.ranking import NDCGMetric, MapMetric, group_boundaries


def _ranking_problem(num_queries=40, docs_per_query=12, f=8, seed=3):
    rng = np.random.RandomState(seed)
    n = num_queries * docs_per_query
    X = rng.normal(size=(n, f))
    # relevance driven by two features + noise, discretized to 0..3
    raw = X[:, 0] * 1.2 + 0.8 * X[:, 1] + 0.3 * rng.normal(size=n)
    y = np.clip(np.digitize(raw, [-1.0, 0.2, 1.2]), 0, 3).astype(np.float64)
    group = np.full(num_queries, docs_per_query)
    return X, y, group


def _ndcg_at_k(y, score, group, k):
    cfg = Config.from_params({"eval_at": [k]})
    m = NDCGMetric(cfg)
    m.init(y, None, group)
    return m.eval(score)[0]


@pytest.mark.slow
def test_lambdarank_learns():
    """slow: a pure quality claim (30-round NDCG bar). The lambdarank
    gradient/group plumbing stays tier-1 via
    test_lambdarank_eval_during_training (trains with the ndcg metric)
    and test_group_boundaries; test_rank_xendcg_learns remains the
    tier-1 learns anchor for the ranking objective family."""
    X, y, group = _ranking_problem()
    ds = lgb.Dataset(X, label=y, group=group)
    params = {"objective": "lambdarank", "num_leaves": 15, "learning_rate": 0.1,
              "min_data_in_leaf": 3, "verbosity": -1, "eval_at": [3]}
    booster = lgb.train(params, ds, num_boost_round=30)
    pred = booster.predict(X)
    ndcg_trained = _ndcg_at_k(y, pred, group, 3)
    ndcg_random = _ndcg_at_k(y, np.random.RandomState(0).normal(size=len(y)),
                             group, 3)
    assert ndcg_trained > ndcg_random + 0.15
    assert ndcg_trained > 0.8


def test_rank_xendcg_learns():
    X, y, group = _ranking_problem(seed=5)
    ds = lgb.Dataset(X, label=y, group=group)
    params = {"objective": "rank_xendcg", "num_leaves": 15,
              "learning_rate": 0.1, "min_data_in_leaf": 3, "verbosity": -1}
    booster = lgb.train(params, ds, num_boost_round=30)
    pred = booster.predict(X)
    assert _ndcg_at_k(y, pred, group, 3) > 0.8


def test_ndcg_metric_perfect_and_inverse():
    y = np.array([3.0, 2.0, 1.0, 0.0, 2.0, 1.0, 1.0, 0.0])
    group = np.array([4, 4])
    perfect = -np.arange(8, dtype=np.float64)  # descending within each query
    assert _ndcg_at_k(y, perfect, group, 4) == pytest.approx(1.0)
    worst = np.arange(8, dtype=np.float64)
    assert _ndcg_at_k(y, worst, group, 4) < 1.0


def test_ndcg_all_negative_query_counts_as_one():
    y = np.zeros(6)
    group = np.array([3, 3])
    score = np.random.RandomState(0).normal(size=6)
    assert _ndcg_at_k(y, score, group, 3) == pytest.approx(1.0)


def test_map_metric_basic():
    cfg = Config.from_params({"eval_at": [2]})
    m = MapMetric(cfg)
    y = np.array([1.0, 0.0, 0.0, 1.0])
    group = np.array([2, 2])
    m.init(y, None, group)
    # query 1: relevant doc ranked first -> AP@2 = 1; query 2: relevant doc
    # ranked second -> precision@2 = 1/2 with 1 hit -> AP = 0.5
    score = np.array([1.0, 0.0, 1.0, 0.0])
    assert m.eval(score)[0] == pytest.approx(0.75)


def test_lambdarank_eval_during_training():
    X, y, group = _ranking_problem()
    ds = lgb.Dataset(X, label=y, group=group)
    results = {}
    booster = lgb.train(
        {"objective": "lambdarank", "num_leaves": 7, "verbosity": -1,
         "eval_at": [1, 3, 5], "min_data_in_leaf": 3},
        ds, num_boost_round=5, valid_sets=[ds],
        callbacks=[lgb.record_evaluation(results)])
    assert "training" in results
    assert "ndcg@3" in results["training"]
    assert len(results["training"]["ndcg@3"]) == 5


def test_group_boundaries():
    np.testing.assert_array_equal(group_boundaries([2, 3, 1]), [0, 2, 5, 6])


# ----------------------------------------------- the length-bucket plan
def _heavy_tailed_sizes(queries=300, seed=0):
    rng = np.random.default_rng(seed)
    sizes = np.minimum(1 + rng.geometric(1 / 60.0, size=queries), 700)
    sizes[:3] = (1, 2, 650)
    return sizes


@pytest.mark.parametrize("budget", (None, 4 * 32 * 32 * 16))
def test_bucket_plan_gather_and_scatter_are_a_permutation(budget,
                                                          monkeypatch):
    """Every document sits in exactly one slot of exactly one block; the
    padding slots carry indices of their own past N, so the document index
    over all blocks is a permutation of the padded slots and the scatter
    back returns every document's value and nothing of the padding."""
    import jax.numpy as jnp
    from lightgbm_tpu import ranking
    if budget:
        monkeypatch.setattr(ranking, "PAIR_BUDGET_BYTES", budget)
    sizes = _heavy_tailed_sizes()
    n = int(sizes.sum())
    plan = ranking.QueryBuckets(sizes, 30)
    assert len(plan.blocks) >= 3
    assert bool(budget) == any(b["n_chunks"] > 1 for b in plan.blocks)
    doc = np.concatenate([b["doc_index"].reshape(-1) for b in plan.blocks])
    assert len(doc) == plan.padded_slots
    np.testing.assert_array_equal(np.sort(doc), np.arange(plan.padded_slots))
    assert plan.padded_slots < 2 * n
    for b in plan.blocks:
        real = b["doc_index"] < n
        np.testing.assert_array_equal(real.sum(axis=1), b["count"])
        # a query's documents are its contiguous run, in order, first
        q = b["query"]
        np.testing.assert_array_equal(b["count"][:len(q)], sizes[q])
        np.testing.assert_array_equal(b["doc_index"][:len(q), 0],
                                      plan.bounds[q])
        assert (np.diff(b["doc_index"], axis=1)[real[:, 1:]] == 1).all()
        assert (b["count"][len(q):] == 0).all()
    # the device round trip: gather [N] values, scatter them back
    obj = ranking.LambdarankNDCG(Config.from_params(
        {"objective": "lambdarank"}))
    x = np.random.default_rng(1).standard_normal(n).astype(np.float32)
    obj.init(np.zeros(n), None, sizes)
    parts = []
    for bucket in obj.buckets:
        got = obj._gather_scores(jnp.asarray(x), bucket)
        poison = jnp.where(bucket["doc_index"] < n, got, jnp.nan)
        parts.append((bucket["doc_index"], poison, 2.0 * poison))
    a, b2 = obj._scatter_grads(parts, n, None)
    np.testing.assert_array_equal(np.asarray(a), x)
    np.testing.assert_array_equal(np.asarray(b2), 2.0 * x)


def test_bucket_ladder_follows_the_lengths():
    from lightgbm_tpu import ranking
    assert ranking.bucket_ladder(1) == [32]
    assert ranking.bucket_ladder(33) == [32, 64]
    assert ranking.bucket_ladder(128) == [32, 64, 128]
    # the top rung is cut to the longest query's multiple of 128
    assert ranking.bucket_ladder(1251) == [32, 64, 128, 256, 512, 1024, 1280]
    assert ranking.pair_rows(30, 1280) == 32
    assert ranking.pair_rows(30, 32) == 32
    assert ranking.pair_rows(5, 64) == 8


def _closure_const_bytes(jaxpr) -> int:
    """Bytes of closure constants of a ClosedJaxpr and of every program
    nested in it."""
    import jax
    total = sum(np.asarray(c).nbytes for c in jaxpr.consts)
    for eqn in jaxpr.jaxpr.eqns:
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    total += _closure_const_bytes(sub)
    return total


def test_lambdarank_fused_step_equals_unfused_and_holds_no_table():
    """The fused step traces get_grad_hess under obj.bound(...): the same
    model text as the phase-by-phase path, every bucket table an operand
    (none an HLO constant: PR 10's rule), and no [Q, M, M] value."""
    import jax
    import re
    sizes = _heavy_tailed_sizes(queries=80, seed=2)
    n = int(sizes.sum())
    rng = np.random.default_rng(4)
    X = rng.standard_normal((n, 6)).astype(np.float32)
    y = np.clip(np.round(X[:, 0] + 0.5 * X[:, 1] + 1.5), 0, 4)
    p = {"objective": "lambdarank", "num_leaves": 7, "verbosity": -1,
         "min_data_in_leaf": 5}
    texts = {}
    for fused in (True, False):
        q = {**p, "fused_iteration": fused}
        b = lgb.train(q, lgb.Dataset(X, label=y, group=sizes, params=q), 3,
                      keep_training_booster=fused)
        texts[fused] = b.model_to_string().split("\nparameters:")[0]
        if fused:
            bo = b._boosting
    assert texts[True] == texts[False]
    assert bo._fused_cache, "the lambdarank run did not take the fused step"
    (step, bind), = bo._fused_cache.values()
    tables = bind["obj_consts"]["buckets"]
    assert len(tables) >= 3
    assert {"doc_index", "count", "label", "gain", "inv_max_dcg",
            "discount"} == set(tables[0])
    args = bo._fused_call_args(None, bind)
    jaxpr = jax.make_jaxpr(step.__wrapped__)(*args)
    smallest = min(int(np.asarray(v).nbytes) for t in tables
                   for k, v in t.items() if v.ndim == 3)
    assert _closure_const_bytes(jaxpr) < min(1024, smallest)
    # no all-pairs tensor: the widest pair value is [Q_c, T, M]
    hlo = step.lower(*args).as_text()
    for t in tables:
        _c, qc, m = t["doc_index"].shape
        if m > 32:
            assert not re.search(rf"tensor<(\d+x)?{qc}x{m}x{m}x", hlo), m
            assert re.search(rf"tensor<{qc}x32x{m}x", hlo), m


# --------------------------- the objectives against the literal loops
# (moved here from the slow-marked test_objective_matrix.py: they take
# seconds, and tier-1 is where the bucketed objective is held to the
# reference's loop)
def _lambdarank_loop(y, score, groups, sigmoid=2.0, trunc=30, norm=True,
                     label_gain=None):
    """Literal transcription of the reference per-query loop
    (rank_objective.hpp:140-226: truncation, deltaNDCG with score-distance
    regularization, sigmoid-table-free exact sigmoid, log2 lambda
    normalization)."""
    if label_gain is None:
        label_gain = [2 ** i - 1 for i in range(32)]
    g_out = np.zeros_like(score)
    h_out = np.zeros_like(score)
    s = 0
    for g in groups:
        yy, ss = y[s:s+g], score[s:s+g]
        order = np.argsort(-ss, kind="stable")
        ideal = np.sort(yy)[::-1]
        maxdcg = sum(label_gain[int(ideal[i])] / np.log2(2.0 + i)
                     for i in range(min(trunc, g)))
        inv = 1.0 / maxdcg if maxdcg > 0 else 0.0
        lam, hes = np.zeros(g), np.zeros(g)
        best, worst = ss[order[0]], ss[order[g - 1]]
        sum_lam = 0.0
        for i in range(min(g - 1, trunc)):
            for j in range(i + 1, g):
                if yy[order[i]] == yy[order[j]]:
                    continue
                hi_r, lo_r = ((i, j) if yy[order[i]] > yy[order[j]]
                              else (j, i))
                hi, lo = order[hi_r], order[lo_r]
                d = ss[hi] - ss[lo]
                gap = label_gain[int(yy[hi])] - label_gain[int(yy[lo])]
                pdisc = abs(1 / np.log2(2.0 + hi_r)
                            - 1 / np.log2(2.0 + lo_r))
                dndcg = gap * pdisc * inv
                if norm and best != worst:
                    dndcg /= (0.01 + abs(d))
                p = 1.0 / (1.0 + np.exp(sigmoid * d))
                pl = -sigmoid * dndcg * p
                ph = sigmoid * sigmoid * dndcg * p * (1 - p)
                lam[lo] -= pl
                hes[lo] += ph
                lam[hi] += pl
                hes[hi] += ph
                sum_lam -= 2 * pl
        if norm and sum_lam > 0:
            nf = np.log2(1 + sum_lam) / sum_lam
            lam *= nf
            hes *= nf
        g_out[s:s+g], h_out[s:s+g] = lam, hes
        s += g
    return g_out, h_out


# name -> (query lengths, objective parameters, how the data is drawn)
_LAMBDARANK_CASES = {
    "three_queries": ([12, 8, 15], {}, {}),
    "length_1_and_2": ([1, 2, 1, 2, 7], {}, {}),
    "around_truncation_5": ([3, 4, 5, 6, 9, 31],
                            {"lambdarank_truncation_level": 5}, {}),
    "around_truncation_30": ([29, 30, 31, 45], {}, {}),
    "three_buckets_one_chunked": ([10] * 20 + [40] * 3 + [100, 5], {},
                                  {"pair_budget": 4 * 32 * 32 * 8}),
    "tied_scores": ([12, 8, 15, 40], {}, {"round_scores": 0}),
    "all_scores_equal": ([9, 33], {}, {"round_scores": -3}),
    "one_label_queries": ([6, 9, 14], {}, {"one_label": True}),
    "norm_off": ([12, 8, 15, 50], {"lambdarank_norm": False}, {}),
    "sigmoid_1": ([12, 8, 15], {"sigmoid": 1.0}, {}),
    "truncation_5_sigmoid_1_norm_off": (
        [12, 8, 40], {"sigmoid": 1.0, "lambdarank_norm": False,
                      "lambdarank_truncation_level": 5}, {}),
    "document_weights": ([12, 8, 15], {}, {"weights": True}),
    "custom_label_gain": ([12, 8, 15],
                          {"label_gain": [0.0, 1.0, 1.0, 5.0, 11.0]}, {}),
}


@pytest.mark.parametrize("case", sorted(_LAMBDARANK_CASES))
def test_lambdarank_lambdas_match_reference(case, monkeypatch):
    """The bucketed, truncated pair window pinned to the literal loop. Our
    get_grad_hess returns the reference's lambdas verbatim (the boosting
    loop consumes them with the same sign convention); document weights
    multiply after the scatter back to [N]."""
    import jax.numpy as jnp
    from lightgbm_tpu import objectives as O
    from lightgbm_tpu import ranking
    from lightgbm_tpu.config import Config
    lengths, params, how = _LAMBDARANK_CASES[case]
    if "pair_budget" in how:
        monkeypatch.setattr(ranking, "PAIR_BUDGET_BYTES", how["pair_budget"])
    rng = np.random.RandomState(0)
    groups = np.array(lengths)
    n = int(groups.sum())
    y = rng.randint(0, 4, size=n).astype(np.float64)
    if how.get("one_label"):
        y = np.repeat(rng.randint(0, 4, size=len(groups)), groups) \
            .astype(np.float64)
    score = rng.normal(size=n)
    if "round_scores" in how:
        score = np.round(score, how["round_scores"]) + 0.0
    weight = rng.uniform(0.5, 2.0, size=n) if how.get("weights") else None
    # float32 scores on both sides, so ties are the same ties
    score = score.astype(np.float32).astype(np.float64)
    obj = O.create_objective(Config.from_params(
        {"objective": "lambdarank", "sigmoid": 2.0, **params}))
    obj.init(y, weight, groups)
    if "pair_budget" in how:
        shapes = obj.counters()["rank_bucket_shapes"]
        assert len(shapes) == 3 and max(s[0] for s in shapes) > 1, shapes
    g, h = obj.get_grad_hess(jnp.asarray(score, jnp.float32))
    g_ref, h_ref = _lambdarank_loop(
        y, score, groups, sigmoid=params.get("sigmoid", 2.0),
        trunc=params.get("lambdarank_truncation_level", 30),
        norm=params.get("lambdarank_norm", True),
        label_gain=params.get("label_gain"))
    if weight is not None:
        g_ref, h_ref = g_ref * weight, h_ref * weight
    np.testing.assert_allclose(np.asarray(g), g_ref, rtol=2e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), h_ref, rtol=2e-3, atol=1e-5)


@pytest.mark.parametrize("lengths", ([1, 7, 12, 3, 2, 9],
                                     [1, 40, 12, 70, 2, 33, 120]),
                         ids=("one_bucket", "three_buckets"))
def test_rank_xendcg_matches_reference_pointwise(lengths):
    """Literal transcription of RankXENDCG::GetGradientsForOneQuery
    (rank_objective.hpp:301-358: softmax rho, Phi(l,g)=2^int(l)-g, the
    three cascaded correction sweeps) vs our vectorized bucket program,
    sharing the same per-doc gamma draws."""
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ranking import RankXENDCG

    rng = np.random.RandomState(5)
    groups = np.array(lengths)
    n = int(groups.sum())
    label = rng.randint(0, 4, size=n).astype(np.float64)
    score = np.round(rng.normal(size=n), 1)          # tie-heavy scores

    obj = RankXENDCG(Config.from_params({"objective": "rank_xendcg",
                                         "seed": 7}))
    obj.init(label, None, groups)
    gamma = rng.uniform(size=n).astype(np.float32)
    assert len(obj.buckets) == (1 if max(lengths) <= 32 else 3)
    lam, hess = obj.get_grad_hess(jnp.asarray(score, jnp.float32),
                                  gamma=gamma)
    lam, hess = np.asarray(lam), np.asarray(hess)

    def ref_one_query(cnt, lab, sc, gam):
        lambdas = np.zeros(cnt)
        hessians = np.zeros(cnt)
        if cnt <= 1:                       # rank_objective.hpp:305-311
            return lambdas, hessians
        rho = np.exp(sc - sc.max())        # Common::Softmax (common.h:567)
        rho = rho / rho.sum()
        params = np.empty(cnt)
        inv_denominator = 0.0
        for i in range(cnt):
            params[i] = 2.0 ** int(lab[i]) - gam[i]   # Phi, :356-358
            inv_denominator += params[i]
        inv_denominator = 1.0 / max(1e-15, inv_denominator)  # kEpsilon
        sum_l1 = 0.0
        for i in range(cnt):
            term = -params[i] * inv_denominator + rho[i]
            lambdas[i] = np.float32(term)
            params[i] = term / (1.0 - rho[i])
            sum_l1 += params[i]
        sum_l2 = 0.0
        for i in range(cnt):
            term = rho[i] * (sum_l1 - params[i])
            lambdas[i] += np.float32(term)
            params[i] = term / (1.0 - rho[i])
            sum_l2 += params[i]
        for i in range(cnt):
            lambdas[i] += np.float32(rho[i] * (sum_l2 - params[i]))
            hessians[i] = np.float32(rho[i] * (1.0 - rho[i]))
        return lambdas, hessians

    bounds = np.concatenate([[0], np.cumsum(groups)])
    for q in range(len(groups)):
        b0, b1 = bounds[q], bounds[q + 1]
        cnt = b1 - b0
        ref_lam, ref_hess = ref_one_query(
            cnt, label[b0:b1], score[b0:b1], gamma[b0:b1])
        np.testing.assert_allclose(lam[b0:b1], ref_lam,
                                   rtol=2e-4, atol=2e-6,
                                   err_msg=f"query {q} lambdas")
        np.testing.assert_allclose(hess[b0:b1], ref_hess,
                                   rtol=2e-4, atol=2e-6,
                                   err_msg=f"query {q} hessians")
