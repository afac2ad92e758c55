"""Learning-to-rank objectives and metrics.

TPU-native re-design of the reference ranking stack
(reference: src/objective/rank_objective.hpp, src/metric/rank_metric.hpp,
src/metric/map_metric.hpp, src/metric/dcg_calculator.cpp).

The reference iterates queries with OpenMP; per query it sorts the
documents by score and visits the pairs ``(i, j)`` with ``i`` among the top
``lambdarank_truncation_level`` sorted positions and ``j > i``
(rank_objective.hpp:142-227). Here the same pairs are dense tensor work:

- **length buckets** (:class:`QueryBuckets`): queries are grouped by
  padded length from a ladder derived from the lengths given (powers of two
  from 32, the top rung cut to the longest query's multiple of 128), each
  bucket a dense ``[Q_b, M_b]`` block with its own label, gain, count and
  document-index tables. A heavy-tailed length distribution pads to about
  1.4x the documents instead of ``Q x longest``;
- the **truncated pair window**: one stable descending sort per query that
  carries label, gain and document index along, then ``[Q_c, T, M_b]``
  pair tensors (``T`` = the truncation level rounded up to the sublane
  multiple) instead of all ``M x M`` pairs. A bucket whose window would
  pass :data:`PAIR_BUDGET_BYTES` is walked in query chunks (``lax.map``),
  so peak memory does not grow with the number of queries;
- the lambdas leave the sorted domain through ONE scatter on the sorted
  document indices: no inverse permutation is ever built.

Deviation from the reference, by design: the 1M-entry sigmoid lookup table
(rank_objective.hpp:235-260) is replaced by computing the sigmoid directly
(on TPU the transcendental is cheaper than a gather). Everything else per
pair follows rank_objective.hpp:142-227: delta-NDCG weighting with
|discount(rank_h) - discount(rank_l)| * gap * inv_max_dcg, the optional
score-distance regularization and the log2(1+S)/S lambda normalization
(``lambdarank_norm``).
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import Config
from .objectives import ObjectiveFunction
from .utils import log

K_EPSILON = 1e-15
MIN_BUCKET = 32                  # shortest padded query length
SUBLANE = 8
LANE = 128
# one float32 [Q_c, T, M_b] pair temporary of a chunk; the pair stage holds
# a handful of them at once, so its peak is a few times this whatever Q is
PAIR_BUDGET_BYTES = 32 << 20


def default_label_gain(max_label: int = 31) -> np.ndarray:
    """reference: dcg_calculator.cpp:33-41 DefaultLabelGain (2^i - 1)."""
    gains = [0.0]
    for i in range(1, max_label):
        gains.append(float((1 << i) - 1))
    return np.asarray(gains, dtype=np.float64)


def _resolve_label_gain(config: Config) -> np.ndarray:
    if config.label_gain:
        return np.asarray(config.label_gain, dtype=np.float64)
    return default_label_gain()


def group_boundaries(groups: np.ndarray) -> np.ndarray:
    """Query sizes -> boundary offsets [Q+1] (reference: Metadata::SetQuery)."""
    groups = np.asarray(groups, dtype=np.int64).reshape(-1)
    return np.concatenate([[0], np.cumsum(groups)])


def _max_dcg_at_k(k: int, labels: np.ndarray, gains: np.ndarray) -> float:
    """reference: dcg_calculator.cpp:55-78 CalMaxDCGAtK."""
    lab = np.sort(labels.astype(np.int64))[::-1][:k]
    disc = 1.0 / np.log2(2.0 + np.arange(len(lab)))
    return float(np.sum(gains[lab] * disc))


def _round_up(x: int, to: int) -> int:
    return -(-int(x) // to) * to


def bucket_ladder(longest: int) -> List[int]:
    """Padded query lengths: powers of two from :data:`MIN_BUCKET` up to
    the longest query, the top rung cut to the longest's multiple of the
    lane width where that is shorter."""
    ladder, m = [], MIN_BUCKET
    while m < longest:
        ladder.append(m)
        m *= 2
    ladder.append(min(m, _round_up(longest, LANE)) if m > LANE else m)
    return ladder


def pair_rows(truncation_level: int, m: int) -> int:
    """Rows ``T`` of a bucket's pair window: the sorted positions that can
    be the ``i`` of a pair, rounded up to the sublane multiple (0 for an
    objective without pairs)."""
    return min(m, _round_up(truncation_level, SUBLANE))


class QueryBuckets:
    """Host-side plan: which padded block each query lives in.

    ``blocks`` holds, per non-empty rung of the ladder, the padded length
    ``m``, the queries ``query`` [Q_b] it takes (in data order), its shape
    in chunks ``(n_chunks, q_chunk)`` and ``doc_index``
    ``[n_chunks * q_chunk, m]``: the document of every slot. Every
    document appears in exactly one slot; a padding slot (a query's tail,
    or a whole padding query that fills the last chunk) carries an index of
    its own in ``[N, padded_slots)``, so ``doc_index`` over all blocks is a
    permutation of ``range(padded_slots)`` and the scatter back needs no
    accumulation."""

    def __init__(self, groups: np.ndarray, truncation_level: int):
        self.bounds = bounds = group_boundaries(groups)
        sizes = np.diff(bounds)
        self.num_docs = n = int(bounds[-1])
        ladder = np.asarray(bucket_ladder(int(sizes.max(initial=1))))
        rung = np.searchsorted(ladder, sizes)       # first rung >= size
        self.blocks = []
        pad_at = n
        for b, m in enumerate(ladder.tolist()):
            query = np.flatnonzero(rung == b)
            if not len(query):
                continue
            t = pair_rows(truncation_level, m)
            fit = max(SUBLANE, PAIR_BUDGET_BYTES // (4 * max(t, 1) * m))
            n_chunks = -(-len(query) // fit)
            q_chunk = len(query) if n_chunks == 1 else \
                _round_up(-(-len(query) // n_chunks), SUBLANE)
            rows = n_chunks * q_chunk
            count = np.zeros(rows, np.int64)
            count[:len(query)] = sizes[query]
            start = np.zeros(rows, np.int64)
            start[:len(query)] = bounds[query]
            pos = np.arange(m)
            doc = start[:, None] + pos[None, :]
            pad = pos[None, :] >= count[:, None]
            doc[pad] = pad_at + np.arange(int(pad.sum()))
            pad_at += int(pad.sum())
            self.blocks.append(dict(
                m=m, t=t, query=query, n_chunks=n_chunks, q_chunk=q_chunk,
                count=count.astype(np.int32),
                doc_index=doc.astype(np.int32)))
        self.padded_slots = pad_at

    @property
    def pair_slots(self) -> int:
        return sum(len(b["count"]) * b["t"] * b["m"] for b in self.blocks)

    def counters(self) -> dict:
        """What the flight recorder's header and the benchmark read."""
        return {"rank_documents": self.num_docs,
                "rank_padded_slots": self.padded_slots,
                "rank_pair_slots": self.pair_slots,
                "rank_buckets": len(self.blocks),
                "rank_bucket_shapes": [
                    [b["n_chunks"], b["q_chunk"], b["m"]]
                    for b in self.blocks]}

    def gather(self, block: dict, x: np.ndarray, fill) -> np.ndarray:
        """[N] per-document values -> the block's [rows, m] table."""
        x = np.asarray(x)
        doc = block["doc_index"]
        return np.where(doc < self.num_docs,
                        x[np.minimum(doc, self.num_docs - 1)], fill)


def _chunked(block: dict, table: np.ndarray, dtype) -> jax.Array:
    """A block's [rows, ...] table as the [n_chunks, q_chunk, ...] device
    array ``lax.map`` walks."""
    shape = (block["n_chunks"], block["q_chunk"]) + table.shape[1:]
    return jnp.asarray(table.reshape(shape), dtype)


# ---------------------------------------------------------------- objectives
class RankingObjective(ObjectiveFunction):
    """reference: rank_objective.hpp:25 RankingObjective.

    ``self.buckets`` is a tuple of dicts of device tables, one per block
    of the plan, each ``[n_chunks, q_chunk, ...]`` (lambdarank's
    ``discount`` is ``[m]``); it is part of
    :meth:`device_consts`, so the fused step takes every table as an
    operand."""

    truncation_level = 0          # rows of the pair window; none here

    def init(self, label, weight, groups=None) -> None:
        super().init(label, weight, groups)
        if groups is None:
            log.fatal("Ranking tasks require query information "
                      "(set group on the Dataset)")
        self.plan = QueryBuckets(groups, self.truncation_level)
        if self.plan.num_docs != self.num_data:
            log.fatal(f"Sum of query counts ({self.plan.num_docs}) differs "
                      f"from the number of documents ({self.num_data})")
        self.buckets = tuple(self._block_tables(b) for b in self.plan.blocks)
        c = self.plan.counters()
        log.info(f"ranking buckets: {c['rank_buckets']} blocks "
                 f"{c['rank_bucket_shapes']} (chunks x queries x length), "
                 f"padded_slots / documents = {c['rank_padded_slots']} / "
                 f"{c['rank_documents']} = "
                 f"{c['rank_padded_slots'] / max(c['rank_documents'], 1):.3f}"
                 f", pair slots {c['rank_pair_slots']}")

    def _block_tables(self, block: dict) -> dict:
        label = self.plan.gather(block, self.label_np, 0.0)
        return {"doc_index": _chunked(block, block["doc_index"], jnp.int32),
                "count": _chunked(block, block["count"], jnp.int32),
                "label": _chunked(block, label, jnp.float32)}

    def device_consts(self) -> dict:
        consts = super().device_consts()
        consts["buckets"] = self.buckets
        return consts

    def counters(self) -> dict:
        return self.plan.counters()

    @staticmethod
    def _gather_scores(score: jax.Array, bucket: dict) -> jax.Array:
        """[N] -> the bucket's [n_chunks, q_chunk, m]; padding slots read
        some document's score and are masked by ``count``."""
        return jnp.take(score, bucket["doc_index"], mode="clip")

    @staticmethod
    def _scatter_grads(parts, n: int, weight):
        """``parts``: per bucket (document index, lambdas, hessians), each
        [n_chunks, q_chunk, m] in any slot order -> [N] each, then the
        document weights. The indices are a permutation of the padded
        slots (padding slots land past N and are cut off)."""
        with jax.named_scope("rank_scatter"):
            idx = jnp.concatenate([p[0].reshape(-1) for p in parts])

            def back(k):
                flat = jnp.concatenate([p[k].reshape(-1) for p in parts])
                out = jnp.zeros((idx.shape[0],), jnp.float32).at[idx].set(
                    flat, unique_indices=True, mode="drop")[:n]
                return out if weight is None else out * weight
            return back(1), back(2)


class LambdarankNDCG(RankingObjective):
    """reference: rank_objective.hpp:98 LambdarankNDCG."""

    name = "lambdarank"

    def __init__(self, config: Config):
        super().__init__(config)
        self.sigmoid = config.sigmoid
        if self.sigmoid <= 0.0:
            log.fatal(f"Sigmoid param {self.sigmoid} should be greater than zero")
        self.norm = config.lambdarank_norm
        self.truncation_level = config.lambdarank_truncation_level
        self.gains = _resolve_label_gain(config)

    def _block_tables(self, block: dict) -> dict:
        tables = super()._block_tables(block)
        gain = self.plan.gather(
            block, self.gains[self.label_np.astype(np.int64)], 0.0)
        # CalMaxDCGAtK per query: the k largest gains against the
        # discounts, padding (gain 0) last
        k = min(self.truncation_level, block["m"])
        top = -np.sort(-gain, axis=1)[:, :k]
        max_dcg = np.sum(top / np.log2(2.0 + np.arange(k)), axis=1)
        inv = np.where(max_dcg > 0, 1.0 / np.maximum(max_dcg, K_EPSILON), 0.0)
        tables["gain"] = _chunked(block, gain, jnp.float32)
        tables["inv_max_dcg"] = _chunked(block, inv, jnp.float32)
        # the discount by sorted position, rounded once from float64: the
        # pair term is a DIFFERENCE of two discounts, and the TPU's float32
        # log2 (5.7e-5 relative at worst, my chip run, PR 29) would lose
        # two more digits to the cancellation
        tables["discount"] = jnp.asarray(
            1.0 / np.log2(2.0 + np.arange(block["m"])), jnp.float32)
        return tables

    def _chunk_grads(self, discount, score, doc_index, count, label, gain,
                     inv_max_dcg):
        """One chunk: scores [Q_c, M] in slot order -> (document index,
        lambdas, hessians) [Q_c, M] in SORTED order
        (rank_objective.hpp:142-227)."""
        m = score.shape[1]
        t = pair_rows(self.truncation_level, m)
        sig = jnp.float32(self.sigmoid)
        pos = jnp.arange(m)[None, :]
        valid = pos < count[:, None]
        with jax.named_scope("rank_sort"):
            # descending and stable; +0.0 and -0.0 are one score, as for
            # the reference's comparison; padding sorts last, so the same
            # ``valid`` masks the sorted slots
            key = jnp.where(valid, jnp.where(score == 0, 0.0, -score),
                            jnp.inf)
            key, doc_index, label, gain = jax.lax.sort(
                (key, doc_index, label, gain), dimension=1, is_stable=True,
                num_keys=1)
            s = jnp.where(valid, -key, 0.0)
        with jax.named_scope("rank_pairs"):
            disc = discount[None, :]
            best = s[:, :1]
            worst = jnp.min(jnp.where(valid, s, jnp.inf), axis=1,
                            keepdims=True)
            row = jnp.arange(t)[None, :, None]
            col = pos[:, None, :]
            li, lj = label[:, :t, None], label[:, None, :]
            ok = ((row < self.truncation_level) & (col > row)
                  & (col < count[:, None, None]) & (li != lj))
            # high / low by label: the pair's terms as seen from i
            sign = jnp.where(li > lj, 1.0, -1.0).astype(jnp.float32)
            delta_score = sign * (s[:, :t, None] - s[:, None, :])
            delta_ndcg = (sign * (gain[:, :t, None] - gain[:, None, :])
                          * jnp.abs(disc[:, :t, None] - disc[:, None, :])
                          * inv_max_dcg[:, None, None])
            if self.norm:
                delta_ndcg = jnp.where(
                    (best == worst)[:, :, None], delta_ndcg,
                    delta_ndcg / (0.01 + jnp.abs(delta_score)))
            p = jax.nn.sigmoid(-sig * delta_score)    # 1/(1+e^{sig*ds})
            p_lambda = jnp.where(ok, -sig * delta_ndcg * p, 0.0)
            p_hess = jnp.where(ok, sig * sig * delta_ndcg * p * (1.0 - p),
                               0.0)
            # i takes +sign*p_lambda, j takes -sign*p_lambda; both the
            # hessian
            toward_i = sign * p_lambda
            tail = ((0, 0), (0, m - t))
            lam = jnp.pad(jnp.sum(toward_i, axis=2), tail) \
                - jnp.sum(toward_i, axis=1)
            hess = jnp.pad(jnp.sum(p_hess, axis=2), tail) \
                + jnp.sum(p_hess, axis=1)
            if self.norm:
                sum_lambdas = -2.0 * jnp.sum(p_lambda, axis=(1, 2))
                nf = jnp.where(
                    sum_lambdas > 0,
                    jnp.log2(1.0 + sum_lambdas)
                    / jnp.maximum(sum_lambdas, K_EPSILON), 1.0)
                lam = lam * nf[:, None]
                hess = hess * nf[:, None]
        return doc_index, lam, hess

    def init(self, label, weight, groups=None) -> None:
        super().init(label, weight, groups)
        self._grad_fn = jax.jit(self._grads)

    def _grads(self, score, buckets, weight):
        """Every table is an argument, so neither this program nor a step
        that traces it under :meth:`bound` holds one as a constant."""
        parts = []
        for bucket in buckets:
            with jax.named_scope("rank_sort"):
                q_score = self._gather_scores(score, bucket)
            parts.append(jax.lax.map(
                lambda x, d=bucket["discount"]: self._chunk_grads(d, *x),
                (q_score, bucket["doc_index"], bucket["count"],
                 bucket["label"], bucket["gain"], bucket["inv_max_dcg"])))
        return self._scatter_grads(parts, score.shape[0], weight)

    def get_grad_hess(self, score: jax.Array):
        return self._grad_fn(score, self.buckets, self.weight)


class RankXENDCG(RankingObjective):
    """reference: rank_objective.hpp:285 RankXENDCG (arxiv 1911.09798)."""

    name = "rank_xendcg"
    # gamma is re-drawn from a HOST numpy RNG every GetGradients call
    # (rank_objective.hpp re-samples per iteration); inside a jitted
    # training step the draw would freeze at trace time
    jit_safe_gradients = False

    def __init__(self, config: Config):
        super().__init__(config)
        self.seed = config.objective_seed if hasattr(config, "objective_seed") \
            else config.seed

    def init(self, label, weight, groups=None) -> None:
        super().init(label, weight, groups)
        self._rng = np.random.RandomState(self.seed)
        self._grad_fn = jax.jit(self._bucket_grads)

    @staticmethod
    def _bucket_grads(q_score: jax.Array, gamma: jax.Array,
                      count: jax.Array, label: jax.Array):
        """reference: rank_objective.hpp:306-355, vectorized over the
        queries of one bucket ([..., M] blocks in slot order)."""
        mask = jnp.arange(q_score.shape[-1]) < count[..., None]
        neg_inf = jnp.float32(-1e30)
        s = jnp.where(mask, q_score, neg_inf)
        rho = jax.nn.softmax(s, axis=-1)
        rho = jnp.where(mask, rho, 0.0)

        # Phi(l, g) = 2^int(l) - g (rank_objective.hpp:356-358); labels are
        # truncated toward zero like the reference's static_cast<int>
        phi = jnp.where(mask, jnp.exp2(jnp.trunc(label)) - gamma, 0.0)
        inv_den = 1.0 / jnp.maximum(jnp.sum(phi, axis=-1, keepdims=True),
                                    K_EPSILON)

        # first-order terms
        t1 = jnp.where(mask, -phi * inv_den + rho, 0.0)
        lam = t1
        params = jnp.where(mask, t1 / jnp.maximum(1.0 - rho, K_EPSILON), 0.0)
        sum_l1 = jnp.sum(params, axis=-1, keepdims=True)
        # second-order terms
        t2 = jnp.where(mask, rho * (sum_l1 - params), 0.0)
        lam = lam + t2
        params = jnp.where(mask, t2 / jnp.maximum(1.0 - rho, K_EPSILON), 0.0)
        sum_l2 = jnp.sum(params, axis=-1, keepdims=True)
        # third-order terms
        lam = lam + jnp.where(mask, rho * (sum_l2 - params), 0.0)
        hess = jnp.where(mask, rho * (1.0 - rho), 0.0)

        # queries with <= 1 doc get zero gradients (rank_objective.hpp:311)
        few = count[..., None] <= 1
        lam = jnp.where(few, 0.0, lam)
        hess = jnp.where(few, 0.0, hess)
        return lam, hess

    def get_grad_hess(self, score: jax.Array, gamma=None):
        """``gamma``: one uniform draw per DOCUMENT ([N], data order); drawn
        from the objective's host generator when not given."""
        if gamma is None:
            gamma = self._rng.uniform(size=self.num_data)
        gamma = jnp.asarray(gamma, jnp.float32)
        parts = []
        for bucket in self.buckets:
            lam, hess = self._grad_fn(
                self._gather_scores(score, bucket),
                self._gather_scores(gamma, bucket),
                bucket["count"], bucket["label"])
            parts.append((bucket["doc_index"], lam, hess))
        return self._scatter_grads(parts, self.num_data, self.weight)


def create_ranking_objective(config: Config) -> RankingObjective:
    if config.objective == "lambdarank":
        return LambdarankNDCG(config)
    if config.objective == "rank_xendcg":
        return RankXENDCG(config)
    log.fatal(f"Unknown ranking objective: {config.objective}")


# ------------------------------------------------------------------- metrics
def _query_weights(weight, bounds) -> Optional[np.ndarray]:
    """Per-query weight = MEAN of its doc weights (reference:
    src/io/metadata.cpp:467-471 query_weights_)."""
    if weight is None:
        return None
    w = np.asarray(weight, dtype=np.float64)
    nq = len(bounds) - 1
    return np.array([np.sum(w[bounds[i]:bounds[i + 1]]) /
                     max(bounds[i + 1] - bounds[i], 1) for i in range(nq)])


class NDCGMetric:
    """reference: rank_metric.hpp:19 NDCGMetric. Host-side (numpy)."""

    bigger_is_better = True

    def __init__(self, config: Config):
        self.eval_at = list(config.eval_at) if config.eval_at else [1, 2, 3, 4, 5]
        self.gains = _resolve_label_gain(config)
        self.name = [f"ndcg@{k}" for k in self.eval_at]

    def init(self, label, weight, groups=None) -> None:
        if groups is None:
            log.fatal("The NDCG metric requires query information")
        self.label = np.asarray(label, dtype=np.float64)
        self.bounds = group_boundaries(groups)
        self.num_queries = len(self.bounds) - 1
        self.query_weights = _query_weights(weight, self.bounds)
        self.inv_max = np.zeros((self.num_queries, len(self.eval_at)))
        for i in range(self.num_queries):
            lab = self.label[self.bounds[i]:self.bounds[i + 1]]
            for j, k in enumerate(self.eval_at):
                mx = _max_dcg_at_k(k, lab, self.gains)
                self.inv_max[i, j] = 1.0 / mx if mx > 0 else -1.0

    def eval(self, score: np.ndarray, objective=None) -> List[float]:
        score = np.asarray(score, dtype=np.float64).reshape(-1)
        res = np.zeros(len(self.eval_at))
        total_w = 0.0
        for i in range(self.num_queries):
            w = 1.0 if self.query_weights is None else self.query_weights[i]
            total_w += w
            lab = self.label[self.bounds[i]:self.bounds[i + 1]]
            sc = score[self.bounds[i]:self.bounds[i + 1]]
            if self.inv_max[i, 0] <= 0:
                res += w  # all-negative query counts as NDCG=1
                continue
            order = np.argsort(-sc, kind="stable")
            disc = 1.0 / np.log2(2.0 + np.arange(len(lab)))
            g = self.gains[lab[order].astype(np.int64)]
            for j, k in enumerate(self.eval_at):
                kk = min(k, len(lab))
                res[j] += w * np.sum(g[:kk] * disc[:kk]) * self.inv_max[i, j]
        return list(res / max(total_w, K_EPSILON))


class MapMetric:
    """reference: map_metric.hpp:20 MapMetric (mean average precision @ k)."""

    bigger_is_better = True

    def __init__(self, config: Config):
        self.eval_at = list(config.eval_at) if config.eval_at else [1, 2, 3, 4, 5]
        self.name = [f"map@{k}" for k in self.eval_at]

    def init(self, label, weight, groups=None) -> None:
        if groups is None:
            log.fatal("The MAP metric requires query information")
        self.label = np.asarray(label, dtype=np.float64)
        self.bounds = group_boundaries(groups)
        self.num_queries = len(self.bounds) - 1
        self.query_weights = _query_weights(weight, self.bounds)

    def eval(self, score: np.ndarray, objective=None) -> List[float]:
        """reference: map_metric.hpp:58-84 CalMapAtK per query."""
        score = np.asarray(score, dtype=np.float64).reshape(-1)
        res = np.zeros(len(self.eval_at))
        total_w = 0.0
        for i in range(self.num_queries):
            w = 1.0 if self.query_weights is None else self.query_weights[i]
            total_w += w
            lab = self.label[self.bounds[i]:self.bounds[i + 1]]
            sc = score[self.bounds[i]:self.bounds[i + 1]]
            order = np.argsort(-sc, kind="stable")
            rel = lab[order] > 0.5
            npos_total = int(np.count_nonzero(rel))
            hits = np.cumsum(rel)
            prec = hits / (1.0 + np.arange(len(rel)))
            for j, k in enumerate(self.eval_at):
                kk = min(k, len(rel))
                if npos_total > 0:
                    # reference: map_metric.hpp sum_ap / min(npos, k)
                    res[j] += w * np.sum(prec[:kk] * rel[:kk]) / min(npos_total, kk)
                else:
                    res[j] += w  # queries without positives count as 1
        return list(res / max(total_w, K_EPSILON))


def create_ranking_metric(name: str, config: Config):
    if name == "ndcg":
        return NDCGMetric(config)
    if name == "map":
        return MapMetric(config)
    return None
