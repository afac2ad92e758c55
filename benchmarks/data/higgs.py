"""Higgs-shaped synthetic data: float32 standard-normal features and a
binary label from a fixed logit (copied from bench.py higgs_weights /
higgs_logits / chip_smoke.make_data so the yardstick cannot drift).

The data SET is fixed, the way the real Higgs file is: rows, label noise and
the label weight vector come from the configuration's ``sample_seed``.
``--seed`` draws the ORDER of the rows a job trains on: every seed then
gives the same histograms up to the order of summation, so the same trees
and the same work, and two runs differ by the machine and not by the
sample. Rows after the shuffled ones (held-out rows, a scoring pool) keep
their place.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

TASK_SEED = 0
CHUNK = 500_000        # part of the data set's definition: do not change
THREADS = 4


def weights(features: int) -> np.ndarray:
    return np.random.RandomState(TASK_SEED).normal(size=features)


def logits(X: np.ndarray, w: np.ndarray) -> np.ndarray:
    f = X.shape[1]
    return X[:, : f // 2] @ w[: f // 2] + 0.5 * np.sin(X[:, f // 2]) * X[:, 0]


def _pool(fn, n):
    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(fn, range(n)))


def make(spec: dict, seed: int, rows: int, shuffled_rows: int):
    """(X float32 [rows, features], y float32 [rows]); the first
    ``shuffled_rows`` rows in the order ``seed`` draws. Made in chunks, each
    from a generator of its own, by a few threads (numpy fills without the
    GIL): the result does not depend on the number of threads."""
    f, sample_seed = int(spec["features"]), int(spec["sample_seed"])
    w = weights(f)
    X = np.empty((rows, f), dtype=np.float32)
    y = np.empty(rows, dtype=np.float32)
    order = np.random.default_rng(seed).permutation(shuffled_rows)
    bounds = list(range(0, rows, CHUNK)) + [rows]

    def fill(c):
        a, b = bounds[c], bounds[c + 1]
        rng = np.random.default_rng([sample_seed, c])
        Xc = rng.standard_normal(size=(b - a, f), dtype=np.float32)
        yc = logits(Xc, w) + rng.logistic(size=b - a) > 0
        X[a:b], y[a:b] = Xc, yc
    _pool(fill, len(bounds) - 1)

    Xs, ys = np.empty_like(X), np.empty_like(y)
    Xs[shuffled_rows:], ys[shuffled_rows:] = X[shuffled_rows:], y[shuffled_rows:]
    cuts = list(range(0, shuffled_rows, CHUNK)) + [shuffled_rows]

    def shuffle(c):
        a, b = cuts[c], cuts[c + 1]
        np.take(X, order[a:b], axis=0, out=Xs[a:b])
        np.take(y, order[a:b], out=ys[a:b])
    _pool(shuffle, len(cuts) - 1)
    return Xs, ys
