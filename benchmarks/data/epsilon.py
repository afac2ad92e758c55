"""Epsilon-shaped synthetic data: 2,000 dense float32 columns with no
missing value, every column standardised and then every row scaled to unit
length (the form LIBSVM publishes as ``epsilon_normalized``), a balanced
binary label.

The columns are a standard normal part plus a few shared factors (the
PASCAL set's features are correlated), divided by the column's own
standard deviation. The label's signal is spread over MANY columns:
a weak linear part over every ``SIGNAL_EVERY``-th column, from column 0
to the last, with weights that fall off as 1 / sqrt(rank) and are dealt
to the positions in a fixed random order (so the strong columns lie all
through the matrix and a first tree splits on columns of many feature
blocks), plus a few products of two columns, under logistic noise. The
logit is symmetric about zero: half the labels are 1.

As ``data/higgs.py``: the data SET is fixed by the configuration's
``sample_seed`` (rows, factors, noise), made in row chunks by a few
threads, float32 throughout (a chunk's [rows, 2000] block is the largest
temporary; no float64 copy of the matrix exists); ``--seed`` draws the
ORDER of the first ``shuffled_rows`` rows, and the rows after them keep
their place.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

TASK_SEED = 0
CHUNK = 25_000         # part of the data set's definition: do not change
THREADS = 4
FACTORS = 8            # shared factors behind the columns
FACTOR_SCALE = 0.35    # a factor loading's standard deviation
SIGNAL_EVERY = 5       # every fifth column carries a linear weight
LINEAR_SD = 2.0        # standard deviation of the logit's linear part
PAIRS = 4              # products of two columns in the logit
PAIR_WEIGHT = 0.6


def task(features: int) -> dict:
    """What defines the label and the columns' correlation, from
    TASK_SEED: factor loadings [FACTORS, F], the columns' standard
    deviations, the signal columns with their weights, the pairs."""
    rng = np.random.RandomState(TASK_SEED)
    load = (rng.normal(size=(FACTORS, features)) * FACTOR_SCALE).astype(
        np.float32)
    sd = np.sqrt(1.0 + (load.astype(np.float64) ** 2).sum(axis=0)).astype(
        np.float32)
    cols = np.arange(0, features, SIGNAL_EVERY)
    if cols[-1] != features - 1:
        cols = np.append(cols, features - 1)
    w = (1.0 + np.arange(len(cols))) ** -0.5 * rng.choice([-1.0, 1.0],
                                                          len(cols))
    w = w[rng.permutation(len(cols))]
    w *= LINEAR_SD / np.sqrt((w ** 2).sum())
    pairs = rng.choice(features, size=(PAIRS, 2), replace=False)
    return {"load": load, "sd": sd, "cols": cols,
            "w": w.astype(np.float32), "pairs": pairs}


def logits(Z: np.ndarray, t: dict) -> np.ndarray:
    """The label's logit from the STANDARDISED columns ``Z`` (before the
    rows are scaled)."""
    out = Z[:, t["cols"]] @ t["w"]
    for a, b in t["pairs"]:
        out += PAIR_WEIGHT * Z[:, a] * Z[:, b]
    return out


def _pool(fn, n):
    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(fn, range(n)))


def make(spec: dict, seed: int, rows: int, shuffled_rows: int):
    """(X float32 [rows, features], y float32 [rows]); the first
    ``shuffled_rows`` rows in the order ``seed`` draws. The result does
    not depend on the number of threads."""
    f, sample_seed = int(spec["features"]), int(spec["sample_seed"])
    t = task(f)
    X = np.empty((rows, f), dtype=np.float32)
    y = np.empty(rows, dtype=np.float32)
    bounds = list(range(0, rows, CHUNK)) + [rows]

    def fill(c):
        a, b = bounds[c], bounds[c + 1]
        rng = np.random.default_rng([sample_seed, c])
        Z = rng.standard_normal(size=(b - a, f), dtype=np.float32)
        Z += rng.standard_normal(size=(b - a, FACTORS),
                                 dtype=np.float32) @ t["load"]
        Z /= t["sd"]
        y[a:b] = logits(Z, t) + rng.logistic(size=b - a).astype(
            np.float32) > 0
        Z /= np.sqrt(np.einsum("ij,ij->i", Z, Z))[:, None]
        X[a:b] = Z
    _pool(fill, len(bounds) - 1)

    order = np.random.default_rng(seed).permutation(shuffled_rows)
    Xs, ys = np.empty_like(X), np.empty_like(y)
    Xs[shuffled_rows:], ys[shuffled_rows:] = X[shuffled_rows:], \
        y[shuffled_rows:]
    del_at = list(range(0, shuffled_rows, CHUNK)) + [shuffled_rows]

    def shuffle(c):
        a, b = del_at[c], del_at[c + 1]
        np.take(X, order[a:b], axis=0, out=Xs[a:b])
        np.take(y, order[a:b], out=ys[a:b])
    _pool(shuffle, len(del_at) - 1)
    return Xs, ys
