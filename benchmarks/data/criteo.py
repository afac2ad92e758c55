"""Criteo-shaped synthetic click logs: 67 dense float32 columns and a
binary label, as the reference's "Parallel Experiment" prepares them
(docs/Experiments.rst: the 13 integer features, and the 26 categorical
features replaced by their CTR and their count). The source gives the
shape (1.7 billion records x 67 features) and no more; what the columns
hold is this file's, listed in the configuration under ``assumed``:

- columns 0-12, the integer features: heavy-tailed counts,
  ``floor(exp(N(mu, sigma)))``, each column with its own share of missing
  values between 1% and 45%, as NaN;
- columns 13-38, the categorical features' CTR: a row draws one of the
  column's few hundred to few thousand categories by a Zipf-Mandelbrot law
  and the column holds that category's click rate, in [0, 1], so popular
  categories share one value over many rows;
- columns 39-64, the same categories' counts, ``log1p`` of the rows the
  source's 1.7e9 records would hold of the category: column 39 + j is a
  function of the same draw as column 13 + j, so each pair is dependent;
- columns 65-66: hour of the day in minutes, and a standard normal.

The label is Bernoulli in a logit of the first ``label_ctr_columns``
categorical features' effects (the effect that also sets the CTR shown),
of ``log1p`` of two integer features and of whether those are missing:
about 3% positive, as click logs are.

The data SET is fixed by the configuration's ``sample_seed``; ``--seed``
draws the ORDER of the first ``shuffled_rows`` rows (the training rows),
and so which chip holds which row. Rows are made in blocks of ``CHUNK`` by
a few threads, each block from a generator of its own and written straight
to its rows' places in the order drawn: the host never holds more than the
float32 matrix itself, and the result does not depend on the threads.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK = 250_000        # part of the data set's definition: do not change
THREADS = max(1, min(16, (os.cpu_count() or 2) - 1))
N_INT, N_CAT, N_NUM = 13, 26, 2
FEATURES = N_INT + 2 * N_CAT + N_NUM
SOURCE_RECORDS = 1.7e9


def tables(spec: dict) -> dict:
    """What the configuration's ``sample_seed`` fixes: per integer column
    (mu, sigma, missing share), per categorical column the categories'
    cumulative shares, effects, CTR and count values."""
    rng = np.random.default_rng([int(spec["sample_seed"]), 0xC817E0])
    lo, hi = spec["missing_share"]
    t = {"mu": rng.uniform(0.5, 4.0, N_INT),
         "sigma": rng.uniform(0.8, 2.0, N_INT),
         "missing": rng.permutation(np.linspace(lo, hi, N_INT)),
         "cdf": [], "effect": [], "ctr": [], "count": []}
    klo, khi = spec["categories"]
    bias = float(spec["label_bias"])
    for j in range(N_CAT):
        k = int(np.exp(rng.uniform(np.log(klo), np.log(khi))))
        share = (np.arange(k) + rng.uniform(1.0, 4.0)) \
            ** -rng.uniform(0.8, 1.3)
        share /= share.sum()
        sd = float(spec["label_effect_sd"]) \
            if j < int(spec["label_ctr_columns"]) else 0.5
        effect = rng.normal(0.0, sd, k)
        t["cdf"].append(np.cumsum(share)[:-1].astype(np.float32))
        t["effect"].append(effect.astype(np.float32))
        t["ctr"].append((1.0 / (1.0 + np.exp(-(bias + effect))))
                        .astype(np.float32))
        t["count"].append(np.log1p(np.round(share * SOURCE_RECORDS))
                          .astype(np.float32))
    return t


def block(spec: dict, t: dict, c: int, rows: int):
    """Rows ``c * CHUNK ...`` of the fixed data set: (X [rows, 67], y)."""
    rng = np.random.default_rng([int(spec["sample_seed"]), 1, c])
    X = np.empty((rows, FEATURES), dtype=np.float32)
    logit = np.full(rows, float(spec["label_bias"]), dtype=np.float32)
    w_int, w_missing = spec["label_int_weight"], spec["label_missing_weight"]
    for j in range(N_INT):
        v = np.floor(np.exp(rng.standard_normal(rows, dtype=np.float32)
                            * np.float32(t["sigma"][j])
                            + np.float32(t["mu"][j])))
        gone = rng.random(rows, dtype=np.float32) < t["missing"][j]
        if j in spec["label_int_columns"]:
            logit += np.where(gone, np.float32(w_missing),
                              np.float32(w_int)
                              * (np.log1p(v) - np.float32(t["mu"][j])))
        v[gone] = np.nan
        X[:, j] = v
    for j in range(N_CAT):
        cat = np.searchsorted(t["cdf"][j],
                              rng.random(rows, dtype=np.float32))
        X[:, N_INT + j] = t["ctr"][j][cat]
        X[:, N_INT + N_CAT + j] = t["count"][j][cat]
        if j < int(spec["label_ctr_columns"]):
            logit += t["effect"][j][cat]
    X[:, -2] = np.floor(rng.random(rows, dtype=np.float32) * 1440.0)
    X[:, -1] = rng.standard_normal(rows, dtype=np.float32)
    y = rng.random(rows, dtype=np.float32) < 1.0 / (1.0 + np.exp(-logit))
    return X, y.astype(np.float32)


def make(spec: dict, seed: int, rows: int, shuffled_rows: int):
    """(X float32 [rows, 67], y float32 [rows]); the first
    ``shuffled_rows`` rows in the order ``seed`` draws, the rest in
    place."""
    if int(spec["features"]) != FEATURES:
        raise ValueError(f"the criteo generator makes {FEATURES} columns")
    t = tables(spec)
    X = np.empty((rows, FEATURES), dtype=np.float32)
    y = np.empty(rows, dtype=np.float32)
    # where each row of the fixed data set goes: the inverse of the order
    place = np.arange(rows)
    place[np.random.default_rng(seed).permutation(shuffled_rows)] = \
        np.arange(shuffled_rows)
    bounds = list(range(0, rows, CHUNK)) + [rows]

    def fill(c):
        a, b = bounds[c], bounds[c + 1]
        Xc, yc = block(spec, t, c, b - a)
        X[place[a:b]], y[place[a:b]] = Xc, yc

    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(fill, range(len(bounds) - 1)))
    return X, y
