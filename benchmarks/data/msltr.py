"""MS-LTR-shaped synthetic ranking data: query groups with heavy-tailed
lengths, graded labels 0-4 and float32 features of the kinds a web-search
feature file holds (heavy-tailed counts, scores in [0,1], a few booleans
and small integers).

The data SET is fixed, the way MSLR-WEB30K fold 1 is: query lengths,
features, labels and the label model come from the configuration's
``sample_seed``. ``--seed`` draws the ORDER of the training queries; the
documents of a query stay contiguous and in their order. Every seed then
gives the same histograms up to the order of summation. Queries after the
training ones (the held-out set) keep their place.

Labels come from a latent relevance that the features partly explain: a
fixed linear form of the features' underlying normals, one product term, a
per-query offset (some queries have nothing relevant) and per-document
noise. NDCG therefore rises with training and stays well below 1.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

TASK_SEED = 0
CHUNK = 250_000        # part of the data set's definition: do not change
THREADS = 4
INFORMATIVE = 24       # features whose normals enter the latent relevance


def query_sizes(spec: dict):
    """(training sizes [queries], held-out sizes [valid_queries]) from
    ``sample_seed``: lognormal lengths clipped to [shortest, longest], one
    query of each extreme, and the training sizes nudged by ones until they
    sum exactly to ``rows``."""
    rng = np.random.default_rng([int(spec["sample_seed"]), 1 << 20])
    q, vq = int(spec["queries"]), int(spec["valid_queries"])
    lo, hi = int(spec["shortest_query"]), int(spec["longest_query"])
    rows = int(spec["rows"])
    sigma = float(spec["query_length_sigma"])
    mean = rows / q
    draw = rng.lognormal(np.log(mean) - 0.5 * sigma ** 2, sigma, size=q + vq)
    sizes = np.clip(np.rint(draw), lo, hi).astype(np.int64)
    train, valid = sizes[:q].copy(), sizes[q:]
    if q >= 2:
        train[0], train[1] = lo, hi
    free = np.arange(2, q) if q > 2 else np.arange(q)
    while True:
        gap = rows - int(train.sum())
        if gap == 0:
            break
        step = 1 if gap > 0 else -1
        room = free[(train[free] < hi) if step > 0 else (train[free] > lo)]
        take = rng.choice(room, size=min(abs(gap), len(room)), replace=False)
        train[take] += step
    return train, valid


def _kinds(features: int):
    """Column ranges by kind: (counts, scores, booleans, small integers),
    in the shares of a 137-feature web-search file (96 / 31 / 5 / 5)."""
    n_bool = n_int = max(1, round(features * 5 / 137))
    n_score = round(features * 31 / 137)
    n_count = features - n_score - n_bool - n_int
    edges = np.cumsum([0, n_count, n_score, n_bool, n_int])
    return [slice(int(a), int(b)) for a, b in zip(edges, edges[1:])]


def _label_model(features: int):
    rng = np.random.RandomState(TASK_SEED)
    k = min(INFORMATIVE, features)
    cols = np.sort(rng.choice(features, size=k, replace=False))
    w = rng.normal(size=k)
    return cols, w / np.linalg.norm(w)


def _pool(fn, items):
    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(fn, items))


def make(spec: dict, seed: int, rows: int, shuffled_rows: int):
    """(X float32 [rows, features], y float32 [rows], sizes int64
    [queries + valid_queries]); ``rows`` is the training documents plus the
    held-out ones, the first ``shuffled_rows`` (the training documents) in
    the query order ``seed`` draws. Made in chunks, each from a generator
    of its own, by a few threads: the result does not depend on the number
    of threads."""
    f, sample_seed = int(spec["features"]), int(spec["sample_seed"])
    train, valid = query_sizes(spec)
    sizes = np.concatenate([train, valid])
    if int(train.sum()) != shuffled_rows or int(sizes.sum()) != rows:
        raise ValueError(f"query sizes sum to {int(train.sum())} + "
                         f"{int(valid.sum())}, asked for {shuffled_rows} "
                         f"of {rows} rows")
    counts, scores, bools, ints = _kinds(f)
    cols, w = _label_model(f)
    shares = np.cumsum(spec["label_shares"])[:-1]
    qrng = np.random.default_rng([sample_seed, 1 << 21])
    offset = np.repeat(qrng.normal(scale=0.6, size=len(sizes)), sizes) \
        .astype(np.float32)
    # a count feature's scale: some fill thousands of values, some are
    # mostly zero, as term counts of a title are
    scale = np.random.RandomState(TASK_SEED + 1).uniform(
        0.3, 4.0, size=counts.stop - counts.start).astype(np.float32)

    X = np.empty((rows, f), dtype=np.float32)
    latent = np.empty(rows, dtype=np.float32)
    bounds = list(range(0, rows, CHUNK)) + [rows]

    def fill(c):
        a, b = bounds[c], bounds[c + 1]
        rng = np.random.default_rng([sample_seed, c])
        z = rng.standard_normal(size=(b - a, f), dtype=np.float32)
        latent[a:b] = (z[:, cols] @ w.astype(np.float32)
                       + 0.4 * z[:, cols[0]] * z[:, cols[1]]
                       + offset[a:b]
                       + rng.standard_normal(b - a, dtype=np.float32))
        z[:, counts] = np.floor(np.exp(1.4 * z[:, counts] + scale))
        z[:, scores] = 1.0 / (1.0 + np.exp(-z[:, scores]))
        z[:, bools] = z[:, bools] > 0.5
        z[:, ints] = np.clip(np.floor(2.0 * z[:, ints] + 3.0), 0, 9)
        X[a:b] = z
    _pool(fill, range(len(bounds) - 1))
    # the label thresholds are the data set's: quantiles of its first chunk
    cuts = np.quantile(latent[:bounds[1]], shares)
    y = np.searchsorted(cuts, latent).astype(np.float32)

    # the seed's order of the training queries, as a gather of rows
    q = len(train)
    order = np.random.default_rng(seed).permutation(q)
    start = np.concatenate([[0], np.cumsum(train)])[:-1]
    new_sizes = train[order]
    new_start = np.concatenate([[0], np.cumsum(new_sizes)])[:-1]
    take = np.repeat(start[order] - new_start, new_sizes) \
        + np.arange(shuffled_rows)
    Xs, ys = np.empty_like(X), np.empty_like(y)
    Xs[shuffled_rows:], ys[shuffled_rows:] = X[shuffled_rows:], y[shuffled_rows:]
    cut = list(range(0, shuffled_rows, CHUNK)) + [shuffled_rows]

    def shuffle(c):
        a, b = cut[c], cut[c + 1]
        np.take(X, take[a:b], axis=0, out=Xs[a:b])
        np.take(y, take[a:b], out=ys[a:b])
    _pool(shuffle, range(len(cut) - 1))
    return Xs, ys, np.concatenate([new_sizes, valid])
