"""What reading a bundle segment's bounds costs: the readings behind
``ops/split.segment_prefix_sums``.

``find_best_splits`` alone over ``[255, 11, 255, 3]`` planes with a
``BundleMeta`` of ``expo.train``'s shape (benchmarks/README-expo.md: nine
bundle columns of two-bin members, 12 / 31 / 7 / 22 / 127 x 4 / 118 of
them, and two plain columns of 255 bins), with the segment bounds read
three ways in one call:

- ``latch``: the library's form, the bounds latched inside the scan;
- ``gather``: two ``take_along_axis`` of the whole plane (the library's
  form until PR 40, and tests/test_bundle_search_bounds.py's oracle);
- ``onehot``: a contraction with a ``[F, B, B]`` one-hot of the bounds'
  positions at ``Precision.HIGHEST``.

For each: seconds a search, the gathers of the whole plane left in the
compiled text, and how many elements of ``_directional_sums``' twelve
arrays and of the returned ``SplitInfo`` differ in their bits from the
gather's. Needs a TPU; ``--cpu`` proves the arguments at a tiny shape (its
times mean nothing).

    python3 scripts/bundle_search_bench.py
"""

import argparse
import contextlib
import json
import os
import re
import sys
import time
from types import SimpleNamespace

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

ONE_HOT = (2, 0, 0)      # a member: (num_bin, most_freq_bin, MISSING_NONE)
EXPO_COLUMNS = [[ONE_HOT] * m for m in (12, 31, 7, 22, 127, 127, 127, 127,
                                        118)] + [255, 255]


def bundle_tables(columns):
    """``(FeatureMeta, BundleMeta)`` as ``basic.Dataset`` itself writes
    them (``_build_feature_meta_bundled``, called on a stand-in that holds
    only what it reads) for device columns given as an int (a plain column
    of that many bins) or a list of members ``(num_bin, most_freq_bin,
    missing_type)`` laid out after the bundle's shared bin 0."""
    from lightgbm_tpu import basic, binning
    from lightgbm_tpu.bundling import Bundle
    from lightgbm_tpu.config import Config
    mappers, bundles = [], []
    for col in columns:
        plain = isinstance(col, int)
        ids, offsets, off = [], [], 1
        for nb, z, missing in ([(col, 0, binning.MISSING_NONE)] if plain
                               else col):
            ids.append(len(mappers))
            offsets.append(off)
            off += nb
            mappers.append(SimpleNamespace(
                num_bin=nb, most_freq_bin=z, default_bin=z,
                missing_type=missing, bin_type=binning.BIN_TYPE_NUMERICAL))
        bundles.append(Bundle(ids, [0], col) if plain
                       else Bundle(ids, offsets, off))
    ds = SimpleNamespace(mappers=mappers, bundles=bundles,
                         used_features=list(range(len(mappers))),
                         num_total_features=len(mappers))
    basic.Dataset._build_feature_meta_bundled(ds, Config())
    return ds._feature_meta, ds._bundle_meta


def gather_bounds(x, seg_lo, seg_hi):
    """``segment_prefix_sums`` by two gathers of the whole plane."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import prefix_sum
    csum = prefix_sum(x, 2)
    lo = seg_lo[None, :, :, None]
    lo_b = jnp.broadcast_to(jnp.maximum(lo - 1, 0), csum.shape)
    hi_b = jnp.broadcast_to(seg_hi[None, :, :, None], csum.shape)
    csum_lo = jnp.where(lo > 0, jnp.take_along_axis(csum, lo_b, axis=2), 0.0)
    return csum, csum_lo, jnp.take_along_axis(csum, hi_b, axis=2)


def onehot_bounds(x, seg_lo, seg_hi):
    """``segment_prefix_sums`` by a contraction with one-hots of the
    bounds' positions (a sum of one element and zeros)."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import prefix_sum
    csum = prefix_sum(x, 2)
    src = jnp.arange(x.shape[2], dtype=seg_lo.dtype)[None, :, None]

    def take(pos):                                  # pos [F, B] -> one-hot
        hot = (src == pos[:, None, :]).astype(csum.dtype)      # [F, src, b]
        return jnp.einsum("lfsc,fsb->lfbc", csum, hot,
                          precision=jax.lax.Precision.HIGHEST)

    return csum, take(seg_lo - 1), take(seg_hi)     # -1 matches no bin: 0


# the oracle first: the others' bits are compared with its
FORMS = {"gather": gather_bounds, "latch": None, "onehot": onehot_bounds}


@contextlib.contextmanager
def bounds_read_by(form):
    """What is traced inside reads its bounds by ``form`` (None: the
    library's own)."""
    from lightgbm_tpu.ops import split
    latch = split.segment_prefix_sums
    split.segment_prefix_sums = form or latch
    try:
        yield
    finally:
        split.segment_prefix_sums = latch


def planes(rng, leaves, meta, bins):
    """``[L, F, B, 3]`` histograms as a pass leaves them: every column a
    partition of its leaf's rows, gradients of both signs, hessians and
    counts positive, a third of the bins empty, nothing past a column's
    ``num_bins``; and the leaves' ``[L, 3]`` totals."""
    f = len(meta.num_bins)
    shape = (leaves, f, bins)
    c = rng.random(shape) * (rng.random(shape) > 1 / 3)
    c *= np.arange(bins) < np.asarray(meta.num_bins)[None, :, None]
    rows = rng.integers(1_000, 1_000_000, (leaves, 1, 1))
    c = np.round(c / c.sum(axis=2, keepdims=True) * rows)
    hist = np.stack([rng.standard_normal(shape) * c, 0.25 * c, c],
                    axis=-1).astype(np.float32)
    return hist, hist[:, -1].sum(axis=1, dtype=np.float32)


def search(hist, tot, meta, bundle, params):
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import find_best_splits
    leaves, f = hist.shape[:2]
    return find_best_splits(
        hist, tot[:, 0], tot[:, 1], tot[:, 2],
        jnp.zeros((leaves,), jnp.float32), jnp.zeros((leaves,), jnp.int32),
        meta, params, jnp.ones((f,), bool), bundle=bundle)


def sums(hist, tot, bundle):
    from lightgbm_tpu.ops.split import _directional_sums
    return _directional_sums(hist, tot[:, 0], tot[:, 1], tot[:, 2], bundle)


def bits_differ(a, b):
    """Elements of two pytrees whose bits differ."""
    import jax

    def raw(x):
        x = np.asarray(x)
        return x.view(np.uint32) if x.dtype == np.float32 else x

    return int(sum((raw(x) != raw(y)).sum() for x, y in
                   zip(jax.tree.leaves(a), jax.tree.leaves(b))))


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
    columns = EXPO_COLUMNS
    if args.cpu:
        columns = [[ONE_HOT] * 5, [ONE_HOT] * 3, 12]
        args.leaves, args.reps = min(args.leaves, 7), 1
    meta, bundle = bundle_tables(columns)
    bins = int(bundle.seg_lo.shape[1])
    params = SplitParams.from_config(Config(min_data_in_leaf=100,
                                            min_sum_hessian_in_leaf=100.0))
    hist, tot = (jnp.asarray(a) for a in planes(
        np.random.default_rng(args.seed), args.leaves, meta, bins))
    print(f"device={dev.device_kind} planes={list(hist.shape)} "
          f"bundle_columns={int(bundle.is_bundle.sum())}", flush=True)

    whole = args.leaves * len(columns) * bins
    oracle = None
    for name, form in FORMS.items():
        with bounds_read_by(form):
            # a function of its own a form: jit keys its traces by the
            # function, and the form is not an argument
            t0 = time.time()
            compiled = jax.jit(lambda *a: search(*a)).lower(
                hist, tot, meta, bundle, params).compile()
            row = {"form": name, "compile_s": round(time.time() - t0, 2)}
            got_sums = jax.jit(lambda *a: sums(*a))(hist, tot, bundle)
        row["plane_gathers"] = sum(
            int(np.prod([int(d) for d in m.group(1).split(",")])) >= whole
            for m in re.finditer(r"= \w+\[([\d,]+)\]\S* gather\(",
                                 compiled.as_text()))
        best = jax.block_until_ready(compiled(hist, tot, meta, bundle,
                                              params))
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(hist, tot, meta, bundle, params))
            times.append(time.perf_counter() - t0)
        oracle = oracle or (got_sums, best)
        row.update(
            search_s=float(np.median(times)),
            sums_bits_differ_from_gather=bits_differ(got_sums, oracle[0]),
            split_bits_differ_from_gather=bits_differ(best, oracle[1]),
            leaves_with_a_split=int(np.isfinite(np.asarray(best.gain)).sum()))
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
