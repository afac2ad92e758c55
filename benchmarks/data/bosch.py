"""Bosch-shaped synthetic data: a production line's sensor table, 968
float32 columns of which about 81% are NaN, and a rare binary label
("the part failed the quality test", 0.581% of parts).

A column is one feature of one station (the source file names it
``L<line>_S<station>_F<k>``): four lines of 24, 2, 3 and 23 stations (52
in all) hold 168, 513, 42 and 245 columns (at another width, as many in
proportion), and a line's columns are dealt to its stations by a fixed
draw. A part takes ONE route
of ``ROUTES``; a route goes through line 0 or line 1 (or neither),
sometimes through line 2, mostly through line 3, and inside a line
through the stations whose popularity clears the route's own jittered
bar: a station's visit probability on a route rises from ``OFF_ROUTE`` to
``ON_ROUTE`` across the bar, smoothly, so that the share of present
entries is a continuous function of the bar. A station's
columns are all present or all NaN together, so the NaN share is uneven:
some stations are visited by nearly every part, some by under 1%. The bar
is set once (bisection) so that 81% of all entries are NaN in
expectation.

A present value is ``round(loc + sd * z, 3)``, ``z`` standard normal, with
the column's ``sd`` from 0.001 to 0.04: most columns have tens of
distinct values, few reach 255 bins.

The label's logit is a route effect, the visits of a few stations (the
NaN pattern carries signal, as in the source), a few readings where they
are present, and a bias set once (bisection on a pilot sample) for 0.581%
positives; logistic noise; label = logit > 0.

As ``data/epsilon.py``: the data SET is fixed by the configuration's
``sample_seed``, made in row chunks by a few threads, float32 throughout
(a chunk's [rows, 968] block is the largest temporary; no float64 copy of
the matrix exists); ``--seed`` draws the ORDER of the first
``shuffled_rows`` rows, and the rows after them keep their place. As
``data/expo.py``: the rows at the positions ``keep`` stay where the
unshuffled set has them, so the library's bin sample is the same rows on
every seed (jobs/missing_train.py).
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

TASK_SEED = 0
CHUNK = 25_000         # part of the data set's definition: do not change
THREADS = 4
# (stations, columns) of each line
LINES = ((24, 168), (2, 513), (3, 42), (23, 245))
ROUTES = 16
NAN_SHARE = 0.81       # expected share of NaN entries
POSITIVE_SHARE = 0.00581
ON_ROUTE, OFF_ROUTE = 0.995, 0.004   # visit probability of a station
BAR_WIDTH = 0.08       # how sharply a station's visit rises at the bar
SD_RANGE = (0.001, 0.04)
LOC_SD = 0.05
STATION_EFFECTS = 6    # stations whose visit moves the logit
READINGS = 10          # columns whose reading moves the logit
PILOT_ROWS = 200_000


def _bisect(fn, lo, hi, want, steps=60):
    """x in [lo, hi] with fn(x) == want, fn increasing."""
    for _ in range(steps):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if fn(mid) < want else (lo, mid)
    return 0.5 * (lo + hi)


def _line_widths(features: int) -> list:
    """(stations, columns) of each line at ``features`` columns: LINES at
    968, else its columns scaled (largest remainders), a station a column
    at most."""
    full = np.array([f for _, f in LINES], dtype=np.float64)
    share = full / full.sum() * features
    cols = np.floor(share).astype(int)
    cols[np.argsort(cols - share)[:features - cols.sum()]] += 1
    return [(max(1, min(k, int(c))), int(c))
            for (k, _), c in zip(LINES, cols)]


def layout(features: int = 968) -> dict:
    """The fixed table, from TASK_SEED: each column's station, each
    station's line, the routes (weights, lines, visit probabilities), the
    columns' loc and sd, and the label's effects."""
    rng = np.random.RandomState(TASK_SEED)
    st_line, col_st = [], []
    for line, (k, f) in enumerate(_line_widths(features)):
        w = rng.gamma(1.5, size=k)
        extra = np.floor(w / w.sum() * (f - k)).astype(int)
        extra[np.argsort(-(w / w.sum() * (f - k) - extra))[
            :f - k - extra.sum()]] += 1
        for s in range(k):
            col_st += [len(st_line)] * int(extra[s] + 1)
            st_line.append(line)
    st_line, col_st = np.array(st_line), np.array(col_st)
    n_st, f = len(st_line), len(col_st)
    width = np.bincount(col_st, minlength=n_st)

    weight = rng.dirichlet(np.ones(ROUTES))
    front = rng.choice(3, size=ROUTES, p=[0.6, 0.32, 0.08])  # L0, L1, none
    lines = np.zeros((ROUTES, 4), bool)
    lines[np.arange(ROUTES), np.minimum(front, 1)] = front < 2
    lines[:, 2] = rng.random_sample(ROUTES) < 0.15
    lines[:, 3] = rng.random_sample(ROUTES) < 0.9
    popular = rng.random_sample(n_st)
    jitter = rng.normal(0.0, 0.35, size=(ROUTES, n_st))

    def visits(bar):
        rise = 1.0 / (1.0 + np.exp((bar - popular[None, :] - jitter)
                                   / BAR_WIDTH))
        on = rise * lines[:, st_line]
        return OFF_ROUTE + (ON_ROUTE - OFF_ROUTE) * on

    def present(bar):
        return float(weight @ visits(bar) @ width) / f

    # the bar falls as the present share rises
    bar = _bisect(lambda b: -present(b), -2.0, 3.0, -(1.0 - NAN_SHARE))
    sd = np.exp(rng.uniform(*np.log(SD_RANGE), size=f)).astype(np.float32)
    loc = rng.normal(0.0, LOC_SD, size=f).astype(np.float32)
    return {"station": col_st, "line": st_line, "route_weight": weight,
            "visit": visits(bar).astype(np.float32),
            "loc": loc, "sd": sd,
            "route_effect": rng.normal(0.0, 0.7, size=ROUTES),
            "station_effect": (rng.choice(n_st, STATION_EFFECTS, False),
                               rng.normal(0.0, 0.8, STATION_EFFECTS)),
            "reading_effect": (rng.choice(f, READINGS, False),
                               rng.choice([-1.0, 1.0], READINGS)
                               * rng.uniform(0.5, 1.0, READINGS))}


def _parts(t: dict, rng, n: int, columns: int):
    """(route [n], visited [n, stations], z float32 [n, columns]) of n
    parts."""
    route = np.searchsorted(np.cumsum(t["route_weight"]),
                            rng.random(n) * t["route_weight"].sum())
    route = np.minimum(route, ROUTES - 1)
    visited = rng.random((n, len(t["line"])), dtype=np.float32) \
        < t["visit"][route]
    z = rng.standard_normal((n, columns), dtype=np.float32)
    return route, visited, z


def _logit(t: dict, route, visited, z_read) -> np.ndarray:
    """The label's logit less its bias; ``z_read`` holds the readings'
    draws, in ``reading_effect``'s order."""
    out = t["route_effect"][route].copy()
    st, eff = t["station_effect"]
    out += visited[:, st] @ eff
    cols, w = t["reading_effect"]
    out += (visited[:, t["station"][cols]] * z_read) @ w
    return out


def bias(t: dict) -> float:
    """The logit's bias for POSITIVE_SHARE positives, on a pilot sample
    drawn from TASK_SEED."""
    rng = np.random.default_rng([TASK_SEED, 0xB05C])
    lg = _logit(t, *_parts(t, rng, PILOT_ROWS, READINGS))

    def share(b):
        return float(np.mean(1.0 / (1.0 + np.exp(-(lg + b)))))
    return _bisect(share, -20.0, 5.0, POSITIVE_SHARE)


def _pool(fn, n):
    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(fn, range(n)))


def make(spec: dict, seed: int, rows: int, shuffled_rows: int, keep=None):
    """(X float32 [rows, 968] with NaN, y float32 [rows]); the first
    ``shuffled_rows`` rows in the order ``seed`` draws, but for the
    positions ``keep`` (ascending, all under ``shuffled_rows``), whose rows
    stay in place. The result does not depend on the number of
    threads."""
    sample_seed = int(spec["sample_seed"])
    t = layout(int(spec["features"]))
    b = bias(t)
    X = np.empty((rows, len(t["station"])), dtype=np.float32)
    y = np.empty(rows, dtype=np.float32)
    bounds = list(range(0, rows, CHUNK)) + [rows]

    def fill(c):
        a, e = bounds[c], bounds[c + 1]
        rng = np.random.default_rng([sample_seed, c])
        route, visited, z = _parts(t, rng, e - a, len(t["station"]))
        y[a:e] = _logit(t, route, visited,
                        z[:, t["reading_effect"][0]]) + b \
            + rng.logistic(size=e - a) > 0
        np.copyto(X[a:e], np.float32(np.nan))
        np.copyto(X[a:e], np.round(t["loc"] + t["sd"] * z, 3),
                  where=visited[:, t["station"]])
    _pool(fill, len(bounds) - 1)

    order = np.random.default_rng(seed).permutation(shuffled_rows)
    if keep is not None and len(keep):
        free = np.setdiff1d(np.arange(shuffled_rows), keep)
        order, drawn = np.arange(shuffled_rows), order
        order[free] = free[drawn[drawn < len(free)]]
    Xs, ys = np.empty_like(X), np.empty_like(y)
    Xs[shuffled_rows:], ys[shuffled_rows:] = X[shuffled_rows:], \
        y[shuffled_rows:]
    cuts = list(range(0, shuffled_rows, CHUNK)) + [shuffled_rows]

    def shuffle(c):
        a, e = cuts[c], cuts[c + 1]
        np.take(X, order[a:e], axis=0, out=Xs[a:e])
        np.take(y, order[a:e], out=ys[a:e])
    _pool(shuffle, len(cuts) - 1)
    return Xs, ys
