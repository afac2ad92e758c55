"""Expo-shaped synthetic data: the one-hot coding of a flight record, as a
scipy CSR float32 matrix, and a binary label ("delayed") from a fixed logit.

A row is one flight: month, day of month, weekday, carrier, origin and
destination, each one-hot coded into its own group of columns, then the
scheduled departure time (minutes after midnight, never 0) and the distance
(miles). Every row therefore holds exactly ``len(groups) + 2`` non-zeros,
and the matrix is built from its three CSR arrays directly: no dense
``[rows, features]`` array exists at any point (30.8 GB as float32 at
11,000,000 x 700).

Inside a group the column index is the category's frequency rank (column 0
the commonest): months, days and weekdays are near uniform, carriers and
airports Zipf-Mandelbrot, ``p(k) ~ (k + offset) ** -exponent``. Groups are
drawn independently.

The data SET is fixed by the configuration's ``sample_seed`` (the draws,
the label's effects and its noise); ``--seed`` draws the ORDER of the first
``shuffled_rows`` rows and nothing else, as data/higgs.py does. Rows at the
positions ``keep`` stay where the unshuffled set has them, and the others
are permuted among themselves: the job passes the positions the library
samples for its bin bounds and bundles, so that the storage it chooses is
the data set's and not the row order's (jobs/sparse_train.py).
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp

CHUNK = 500_000        # part of the data set's definition: do not change
THREADS = 4


def layout(spec: dict):
    """(names, sizes, first column) of the one-hot groups, and the columns
    of the two numeric features after them."""
    names = [g["name"] for g in spec["groups"]]
    sizes = np.array([int(g["size"]) for g in spec["groups"]])
    first = np.concatenate([[0], np.cumsum(sizes)])
    if first[-1] + 2 != int(spec["features"]):
        raise ValueError("the groups and the two numeric columns do not "
                         "add up to the configuration's features")
    return names, sizes, first[:-1], (int(first[-1]), int(first[-1]) + 1)


def probabilities(group: dict) -> np.ndarray:
    """Category shares of one group, commonest first."""
    k = np.arange(1, int(group["size"]) + 1, dtype=np.float64)
    p = (k + float(group.get("offset", 0.0))) ** -float(
        group.get("exponent", 0.0))
    return p / p.sum()


def effects(spec: dict) -> list:
    """The label's per-category logit effects, one array a group."""
    rng = np.random.default_rng([int(spec["sample_seed"]), 1 << 20])
    return [rng.normal(scale=float(g["effect"]), size=int(g["size"]))
            for g in spec["groups"]]


def _pool(fn, n):
    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(fn, range(n)))


def make(spec: dict, seed: int, rows: int, shuffled_rows: int, keep=None):
    """(X scipy CSR float32 [rows, features], y float32 [rows]); the first
    ``shuffled_rows`` rows in the order ``seed`` draws, but for the
    positions ``keep`` (ascending, all under ``shuffled_rows``), whose rows
    stay in place. Made in chunks, each from a generator of its own, by a
    few threads: the result does not depend on the number of threads."""
    sample_seed = int(spec["sample_seed"])
    _names, sizes, first, (c_time, c_dist) = layout(spec)
    groups = spec["groups"]
    g = len(groups)
    nnz = g + 2
    cum = [np.cumsum(probabilities(grp)) for grp in groups]
    eff = effects(spec)
    idx = np.empty((rows, nnz), dtype=np.int32)
    val = np.ones((rows, nnz), dtype=np.float32)
    y = np.empty(rows, dtype=np.float32)
    bounds = list(range(0, rows, CHUNK)) + [rows]

    def fill(c):
        a, b = bounds[c], bounds[c + 1]
        rng = np.random.default_rng([sample_seed, c])
        cats = [np.minimum(np.searchsorted(cum[i], rng.random(b - a)),
                           sizes[i] - 1) for i in range(g)]
        logit = np.full(b - a, float(spec["label_bias"]))
        for i in range(g):
            idx[a:b, i] = first[i] + cats[i]
            logit += eff[i][cats[i]]
        # departures 05:00-23:59, later ones later in the day's build-up of
        # delays; distances lognormal, 31 to 4,983 miles
        minute = 300.0 + np.floor(1140.0 * rng.beta(1.6, 1.8, size=b - a))
        miles = np.clip(np.rint(np.exp(rng.normal(6.4, 0.75, size=b - a))),
                        31.0, 4983.0)
        idx[a:b, g], idx[a:b, g + 1] = c_time, c_dist
        val[a:b, g], val[a:b, g + 1] = minute, miles
        logit += float(spec["time_effect"]) * ((minute - 300.0) / 1140.0) ** 2
        logit += float(spec["distance_effect"]) * np.log(miles / 600.0)
        y[a:b] = logit + rng.logistic(size=b - a) > 0
    _pool(fill, len(bounds) - 1)

    order = np.random.default_rng(seed).permutation(shuffled_rows)
    if keep is not None and len(keep):
        free = np.setdiff1d(np.arange(shuffled_rows), keep)
        order, drawn = np.arange(shuffled_rows), order
        order[free] = free[drawn[drawn < len(free)]]
    idx_s, val_s, y_s = np.empty_like(idx), np.empty_like(val), \
        np.empty_like(y)
    idx_s[shuffled_rows:] = idx[shuffled_rows:]
    val_s[shuffled_rows:] = val[shuffled_rows:]
    y_s[shuffled_rows:] = y[shuffled_rows:]
    cuts = list(range(0, shuffled_rows, CHUNK)) + [shuffled_rows]

    def shuffle(c):
        a, b = cuts[c], cuts[c + 1]
        np.take(idx, order[a:b], axis=0, out=idx_s[a:b])
        np.take(val, order[a:b], axis=0, out=val_s[a:b])
        np.take(y, order[a:b], out=y_s[a:b])
    _pool(shuffle, len(cuts) - 1)

    indptr = np.arange(rows + 1, dtype=np.int64) * nnz
    if rows * nnz < 2 ** 31:
        indptr = indptr.astype(np.int32)
    X = sp.csr_matrix((val_s.reshape(-1), idx_s.reshape(-1), indptr),
                      shape=(rows, int(spec["features"])))
    X.has_sorted_indices = True
    return X, y_s
