"""Microbenchmark: one full-data histogram tile pass at Higgs scale.

Compares the histogram backends head-to-head on the real chip (the pass this
framework's sec/iter is made of — reference hot-loop analog:
src/io/dense_bin.hpp:98-141, src/treelearner/kernels/histogram_16_64_256.cu).

Usage: python scripts/microbench_hist.py [--rows 10500000] [--reps 5]
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def sync(x):
    return float(np.asarray(x).ravel()[0])


def timeit(fn, reps):
    fn()  # compile
    sync(fn())
    t0 = time.time()
    for _ in range(reps):
        out = fn()
    sync(out)
    return (time.time() - t0) / reps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=10_500_000)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--bins", type=int, default=255)
    ap.add_argument("--tile", type=int, default=42)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated subset of variant names")
    ap.add_argument("--pend-frac", type=float, default=0.25,
                    help="pending-row fraction for the compacted-pass "
                         "variants (gather + histogram over the rung)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import histogram_tiles

    n, f, b, p = args.rows, args.features, args.bins, args.tile
    print(f"# device={jax.devices()[0]} N={n} F={f} B={b} P={p}")

    rng = np.random.RandomState(0)
    bins_np = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    bins = jnp.asarray(bins_np)
    binsT = jnp.asarray(np.ascontiguousarray(bins_np.T))
    # channel-major, as the grower hands the statistics over
    stats = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32).T)
    leaf_ids = jnp.asarray(rng.randint(0, p, size=n).astype(np.int32))
    sel = jnp.arange(p, dtype=jnp.int32)

    results = {}

    def bench(name, fn):
        if args.only and name not in args.only.split(","):
            return
        try:
            dt = timeit(fn, args.reps)
            results[name] = dt
            print(f"{name:32s} {dt*1e3:9.1f} ms/pass")
        except Exception as e:
            print(f"{name:32s} FAILED: {type(e).__name__}: {e}")

    onehot = jax.jit(lambda: histogram_tiles(
        bins, stats, leaf_ids, sel, b, method="onehot"))
    bench("xla_onehot_highest", onehot)

    onehot_hilo = jax.jit(lambda: histogram_tiles(
        bins, stats, leaf_ids, sel, b, method="onehot_hilo"))
    bench("xla_onehot_hilo", onehot_hilo)

    from lightgbm_tpu.ops import pallas_hist

    for blk in (1024, 2048, 4096, 8192):
        bench(f"pallas_highest_blk{blk}", jax.jit(
            lambda blk=blk: pallas_hist.histogram_tiles_pallas_mode(
                binsT, pallas_hist.pad_stats(stats, n, blk), leaf_ids, sel, b,
                block=blk, mode="highest")))

    for blk in (1024, 2048, 4096, 8192):
        bench(f"pallas_hilo_blk{blk}", jax.jit(
            lambda blk=blk: pallas_hist.histogram_tiles_pallas_mode(
                binsT, pallas_hist.pad_stats(stats, n, blk), leaf_ids, sel, b,
                block=blk, mode="hilo")))

    if hasattr(pallas_hist, "histogram_tiles_pallas_mode"):
        stats_q = jnp.asarray(
            rng.randint(-127, 128, size=(n, 3)).astype(np.int8).T)
        for blk in (2048, 4096):
            bench(f"pallas_q8_blk{blk}", jax.jit(
                lambda blk=blk: pallas_hist.histogram_tiles_pallas_mode(
                    binsT, pallas_hist.pad_stats(stats_q, n, blk), leaf_ids,
                    sel, b, block=blk, mode="q8")))

    # compacted passes (grower ladder analog): leaf ids drawn over 1/frac
    # as many leaves as the tile selects, so ~frac of the rows are pending;
    # the variant times gather (compact_rows) + histogram over the rung —
    # the full end-to-end cost the ladder pays per tile round
    from lightgbm_tpu.ops.histogram import compact_rows

    frac = args.pend_frac
    spread = max(1, int(round(1.0 / max(frac, 1e-6))))
    leaf_wide = jnp.asarray(
        rng.randint(0, spread * p, size=n).astype(np.int32))
    in_tile = leaf_wide < p
    # size the rung from the ACTUAL pending count (the grower's lax.cond
    # guarantees n_pend <= rung before dispatching; the variant must honor
    # the same compact_rows contract or it silently drops pending rows)
    rung = -(-int(np.asarray(jnp.sum(in_tile))) // 512) * 512

    def compacted(method, use_binsT):
        def fn():
            bm, btm, st, lid = compact_rows(
                bins, binsT if use_binsT else None, stats, leaf_wide,
                in_tile, rung)
            from lightgbm_tpu.ops.histogram import histogram_tiles
            return histogram_tiles(bm, st, lid, sel, b, method=method,
                                   binsT=btm)
        return jax.jit(fn)

    bench(f"compact{frac:.2f}_scatter", compacted("scatter", False))
    bench(f"compact{frac:.2f}_onehot_hilo", compacted("onehot_hilo", True))
    bench(f"compact{frac:.2f}_pallas_hilo", compacted("pallas_hilo", True))

    if results:
        best = min(results, key=results.get)
        print(f"# best: {best} ({results[best]*1e3:.1f} ms)")


if __name__ == "__main__":
    main()
