"""What the categorical search's sorted and reversed reads cost: the
readings behind ``ops/split.sort_with_key`` and ``read_reversed``.

``find_best_cat_splits`` alone over ``[255, 8, 255, 3]`` planes with the
columns of ``expo-cat.train`` (benchmarks/README-expo-cat.md: six
categorical columns of 13 / 32 / 8 / 23 / 255 / 255 bins and two
numerical ones of 255), the sorted sums and the backward direction's
reads taken two ways in one call:

- ``gather``: ``argsort`` and seven ``take_along_axis`` of the whole
  plane a search, three for the sorted g, h and count and four for the
  backward reads (the library's form before the sort carried the sums,
  and tests/test_cat_search_gathers.py's oracle);
- ``sort``: the library's form, one stable sort that carries the sums
  beside their key, and the backward reads as a reversal and static
  shifts.

For each: seconds a search, the gathers of a whole plane left in the
compiled text, and how many elements of the returned split (gain,
feature, left sums, bitset words, l2) and of the two helpers' results
differ in their bits from the gather's. Needs a TPU; ``--cpu`` proves the
arguments at a tiny shape (its times mean nothing).

    python3 scripts/cat_search_bench.py
"""

import argparse
import contextlib
import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

EXPO_CAT_BINS = (13, 32, 8, 23, 255, 255, 255, 255)
EXPO_CAT_COLUMNS = 6          # the first six columns are categorical


def gather_sort(key, *planes):
    """``sort_with_key`` by ``argsort`` and a gather of each plane."""
    import jax.numpy as jnp
    order = jnp.argsort(key, axis=2)
    return (order, *(jnp.take_along_axis(x, order, axis=2) for x in planes))


def gather_read_reversed(x, start):
    """``read_reversed`` by a gather of the whole plane."""
    import jax.numpy as jnp
    j = start[:, :, None] - jnp.arange(x.shape[2], dtype=start.dtype)
    return jnp.where(j >= 0, jnp.take_along_axis(
        x, jnp.clip(j, 0, x.shape[2] - 1), axis=2), 0.0)


# the oracle first: the other's bits are compared with its
FORMS = {"gather": (gather_sort, gather_read_reversed), "sort": None}


@contextlib.contextmanager
def reads_by(form):
    """What is traced inside sorts and reads back by ``form`` (None: the
    library's own)."""
    from lightgbm_tpu.ops import split
    own = (split.sort_with_key, split.read_reversed)
    split.sort_with_key, split.read_reversed = form or own
    try:
        yield
    finally:
        split.sort_with_key, split.read_reversed = own


def feature_meta(num_bins, categorical):
    """A ``FeatureMeta`` of plain columns, the first ``categorical`` of
    them categorical."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import FeatureMeta
    f = len(num_bins)
    return FeatureMeta(
        num_bins=jnp.asarray(num_bins, jnp.int32),
        missing_type=jnp.zeros((f,), jnp.int32),
        default_bin=jnp.zeros((f,), jnp.int32),
        is_categorical=jnp.arange(f) < categorical,
        monotone=jnp.zeros((f,), jnp.int8),
        penalty=jnp.ones((f,), jnp.float32))


def planes(rng, leaves, num_bins, bins):
    """``[L, F, B, 3]`` histograms and the leaves' ``[L, 3]`` totals:
    counts drawn from a few values, so that many bins share a ratio
    (ties) and some hold fewer rows than ``cat_smooth`` (invalid),
    gradients of both signs from a few multiples of the count, nothing
    past a column's ``num_bins``; a leaf holds every column's rows and
    more (its bin 0)."""
    shape = (leaves, len(num_bins), bins)
    c = rng.choice(np.asarray([0, 0, 3, 100, 100, 250, 1000, 4000.]), shape)
    c *= np.arange(bins) < np.asarray(num_bins)[:, None]
    g = c * rng.choice(np.asarray([-0.5, -0.25, 0.0, 0.25, 0.5]), shape)
    hist = np.stack([g, 0.25 * c, c], axis=-1).astype(np.float32)
    cnt = hist[..., 2].sum(axis=2).max(axis=1) + rng.integers(0, 2000, leaves)
    tot = np.stack([hist[:, 0, :, 0].sum(axis=1) - 0.1 * cnt, 0.25 * cnt,
                    cnt], axis=1).astype(np.float32)
    return hist, tot


def search(hist, tot, meta, params, mask):
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import find_best_cat_splits
    leaves = hist.shape[0]
    return find_best_cat_splits(
        hist, tot[:, 0], tot[:, 1], tot[:, 2],
        jnp.zeros((leaves,), jnp.float32), jnp.zeros((leaves,), jnp.int32),
        meta, params, mask)


def reads(hist):
    """Both helpers over every bin: the sort of the count plane's ratios
    with the three planes, and the reversed reads of each sorted plane
    from every start a search can ask for."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops import split
    g, h, c = hist[..., 0], hist[..., 1], hist[..., 2]
    order, *moved = split.sort_with_key(g / (h + 10.0), g, h, c)
    leaves, f, b = c.shape
    start = jnp.arange(leaves * f, dtype=jnp.int32).reshape(leaves, f)
    start = start % (b + 2) - 2                          # -2 .. B - 1
    return order, moved, [split.read_reversed(x, start) for x in moved]


def bits_differ(a, b):
    """Elements of two pytrees whose bits differ."""
    import jax

    def raw(x):
        x = np.asarray(x)
        return x.view(np.uint32) if x.dtype == np.float32 else x

    return int(sum((raw(x) != raw(y)).sum() for x, y in
                   zip(jax.tree.leaves(a), jax.tree.leaves(b))))


def plane_gathers(text, whole):
    """Gathers in a compiled text whose result has ``whole`` elements or
    more."""
    return sum(int(np.prod([int(d) for d in m.group(1).split(",")])) >= whole
               for m in re.finditer(r"= \w+\[([\d,]+)\]\S* gather\(", text))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--leaves", type=int, default=255)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu", action="store_true",
                    help="a tiny shape on the CPU backend: the arguments "
                         "only")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ops.split import SplitParams
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu:
        sys.exit(f"no TPU here ({dev.platform}): --cpu rehearses the "
                 "arguments, a time comes from the chip only")
    num_bins, categorical, bins = EXPO_CAT_BINS, EXPO_CAT_COLUMNS, 255
    if args.cpu:
        num_bins, categorical, bins = (5, 3, 16, 9, 16), 4, 16
        args.leaves, args.reps = min(args.leaves, 7), 1
    meta = feature_meta(num_bins, categorical)
    params = SplitParams.from_config(Config(min_data_in_leaf=100,
                                            min_sum_hessian_in_leaf=100.0))
    hist, tot = (jnp.asarray(a) for a in planes(
        np.random.default_rng(args.seed), args.leaves, num_bins, bins))
    mask = jnp.ones((len(num_bins),), bool)
    print(f"device={dev.device_kind} planes={list(hist.shape)}", flush=True)

    whole = args.leaves * len(num_bins) * bins
    oracle = None
    for name, form in FORMS.items():
        with reads_by(form):
            # a function of its own a form: jit keys its traces by the
            # function, and the form is not an argument
            t0 = time.time()
            compiled = jax.jit(lambda *a: search(*a)).lower(
                hist, tot, meta, params, mask).compile()
            row = {"form": name, "compile_s": round(time.time() - t0, 2)}
            got_reads = jax.jit(lambda x: reads(x))(hist)
        row["plane_gathers"] = plane_gathers(compiled.as_text(), whole)
        best = jax.block_until_ready(compiled(hist, tot, meta, params, mask))
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(hist, tot, meta, params, mask))
            times.append(time.perf_counter() - t0)
        oracle = oracle or (got_reads, best)
        row.update(
            search_s=float(np.median(times)),
            reads_bits_differ_from_gather=bits_differ(got_reads, oracle[0]),
            bits_differ=bits_differ(best, oracle[1]),
            leaves_with_a_split=int(np.isfinite(np.asarray(best[0])).sum()))
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
