"""Calibrate the compaction-rung cost rule on a chip.

Times, for each (features, bins, histogram method), ONE tile pass through
``ops/histogram.histogram_tiles`` as the serial grower issues it:

- ``full``: the full-size pass over N rows (the pass a rung replaces);
- ``count``: the ``slot_map[hist_leaf_ids] < P`` lookup and its sum that
  every pass of a grower with a ladder pays (models/grower.py tile_build);
- per rung size m in N/2, N/8, N/32: ``index`` (``compact_indices``, per
  row HELD), ``gather`` (``gather_rows``, per row gathered), ``rung``, the
  whole rung in one program (count + index + gather + kernel), and with
  ``--kernel-parts`` ``kernel``: the same kernel over m pre-gathered rows.

``rung < full`` is the sign ``ops/histogram.prune_compaction_ladder`` has
to reproduce; the four parts are where its per-row constants
(``RUNG_COSTS``) come from. Every line printed carries the rule's own
estimate beside the measurement, so a run on a chip whose row the table
lacks shows how far the fallback row is off.

The rows a rung keeps fill 90% of it and are spread over the whole row
range at random, as a pending leaf's rows are in a trained tree. A time
is the median of ``--reps`` calls, each ended by ``block_until_ready``;
host clock (a call is tens of milliseconds or more, a dispatch well under
one).

Usage (a TPU; the builder's chip tool):
  python scripts/calibrate_compaction.py --out chiprun_out/calibrate.jsonl
  python scripts/calibrate_compaction.py --shapes 28,255,pallas_hilo,1048576
  JAX_PLATFORMS=cpu python scripts/calibrate_compaction.py --interpret \
      --shapes 6,15,pallas_hilo,4096          # control flow only, no times
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# (features, bins, method, rows): the Higgs shape at its own row count,
# the same width at a fifth of the rows (are the costs per row?), then
# MS-LTR's and an Expo-like width with rows cut to fit one chip
SHAPES = [
    (28, 255, "pallas_hilo", 10_500_000),
    (28, 255, "pallas_q8", 10_500_000),
    (28, 63, "pallas_hilo", 10_500_000),
    (28, 63, "pallas_q8", 10_500_000),
    (28, 255, "pallas_hilo", 2_097_152),
    (137, 255, "pallas_hilo", 2_097_152),
    (137, 255, "pallas_q8", 2_097_152),
    (137, 63, "pallas_hilo", 2_097_152),
    (137, 63, "pallas_q8", 2_097_152),
]
RUNG_DIVISORS = (2, 8, 32)
TILE = 42            # leaf slots of one pass: 42 x 3 channels in 128 lanes
FILL = 0.9           # share of a rung's slots the kept rows take


def median_time(fn, reps):
    import jax
    jax.block_until_ready(fn())              # compile + first run
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def measure(f, b, method, n, *, reps, block, interpret, kernel_parts=False):
    """One record per rung size for the shape, as dicts."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import (
        compact_indices, gather_rows, histogram_tiles, rung_costs)

    q8 = method.endswith("_q8")
    kind = jax.devices()[0].device_kind
    key = jax.random.PRNGKey(f * 1000 + b)
    k_bins, k_stats, k_u, k_in, k_out = jax.random.split(key, 5)
    bins = (jax.random.bits(k_bins, (n, f), jnp.uint8)
            % jnp.uint8(b)).astype(jnp.uint8)
    binsT = bins.T
    # channel-major, as the grower hands the statistics over
    if q8:
        stats = jax.random.randint(k_stats, (3, n), -127, 128,
                                   jnp.int32).astype(jnp.int8)
    else:
        stats = jax.random.normal(k_stats, (3, n), jnp.float32)
    u = jax.random.uniform(k_u, (n,))
    leaf_in = jax.random.randint(k_in, (n,), 0, TILE, jnp.int32)
    leaf_out = TILE + jax.random.randint(k_out, (n,), 0, 200, jnp.int32)
    sel = jnp.arange(TILE, dtype=jnp.int32)
    L = 255
    kw = dict(num_bins=b, method=method, block=block, interpret=interpret)

    def count(leaf, sel):
        # models/grower.py tile_build, scope tile_select
        slot_map = jnp.full((L + 1,), TILE, jnp.int32).at[
            jnp.where(sel >= 0, sel, L)].set(
                jnp.arange(TILE, dtype=jnp.int32))
        in_tile = slot_map[leaf] < TILE
        return in_tile, jnp.sum(in_tile, dtype=jnp.int32)

    full_fn = jax.jit(lambda bins, binsT, stats, leaf, sel: histogram_tiles(
        bins, stats, leaf, sel, binsT=binsT, **kw))
    count_fn = jax.jit(count)

    leaf_half = jnp.where(u < FILL / 2, leaf_in, leaf_out)
    t_full = median_time(
        lambda: full_fn(bins, binsT, stats, leaf_half, sel), reps)
    t_count = median_time(lambda: count_fn(leaf_half, sel), reps)

    out = []
    for div in RUNG_DIVISORS:
        m = -(-(n // div) // 64) * 64
        leaf = jnp.where(u < FILL / div, leaf_in, leaf_out)
        in_tile, n_pend = count_fn(leaf, sel)
        assert int(n_pend) <= m, (int(n_pend), m)

        def rung(bins, binsT, stats, leaf, sel, m=m):
            in_tile, n_pend = count(leaf, sel)
            idx = compact_indices(in_tile, m)
            return histogram_tiles(bins, stats, leaf, sel, binsT=binsT,
                                   gather_idx=idx, **kw), n_pend

        rung_fn = jax.jit(rung)
        rec = dict(device_kind=kind, method=method, features=f, bins=b,
                   rows=n, rung_rows=m, kept_rows=int(n_pend), block=block,
                   full_s=t_full, count_s=t_count)
        rec["rung_s"] = median_time(
            lambda: rung_fn(bins, binsT, stats, leaf, sel), reps)
        index_fn = jax.jit(lambda keep, m=m: compact_indices(keep, m))
        gather_fn = jax.jit(
            lambda bins, binsT, stats, leaf, idx: gather_rows(
                bins, binsT, stats, leaf, idx)[1:])
        idx = index_fn(in_tile)
        rec["index_s"] = median_time(lambda: index_fn(in_tile), reps)
        rec["gather_s"] = median_time(
            lambda: gather_fn(bins, binsT, stats, leaf, idx), reps)
        if kernel_parts:
            binsT_c, stats_c, leaf_c = gather_fn(bins, binsT, stats, leaf,
                                                 idx)
            kernel_fn = jax.jit(
                lambda binsT, stats, leaf, sel: histogram_tiles(
                    None, stats, leaf, sel, binsT=binsT, **kw))
            rec["kernel_s"] = median_time(
                lambda: kernel_fn(binsT_c, stats_c, leaf_c, sel), reps)
            del binsT_c, stats_c, leaf_c
        del idx
        model = rung_costs(kind if not interpret else "TPU v5 lite",
                           method, n, f, b, m)
        if model is not None:
            rec["rule"] = model
        rec["pays"] = rec["rung_s"] < rec["full_s"]
        rec["margin"] = rec["rung_s"] / rec["full_s"] - 1.0
        out.append(rec)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=None,
                    help="features,bins,method,rows ... (default: the "
                         "calibration set)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--block", type=int, default=0,
                    help="rows a grid step (0: the library's "
                         "pallas_hist.DEFAULT_BLOCK, what a run takes)")
    ap.add_argument("--out", default="")
    ap.add_argument("--kernel-parts", action="store_true",
                    help="also time the kernel alone over each rung's "
                         "pre-gathered rows (seven kernel compiles a shape "
                         "instead of four: is the kernel linear in rows?)")
    ap.add_argument("--interpret", action="store_true",
                    help="Pallas interpreter (CPU rehearsal: times mean "
                         "nothing)")
    args = ap.parse_args()

    import jax
    from lightgbm_tpu.ops.pallas_hist import DEFAULT_BLOCK
    args.block = args.block or DEFAULT_BLOCK
    if jax.default_backend() != "tpu" and not args.interpret:
        sys.exit("calibrate_compaction: needs a TPU (or --interpret for a "
                 f"rehearsal); backend is {jax.default_backend()!r}")
    shapes = SHAPES
    if args.shapes:
        shapes = [(int(a), int(b), c, int(d)) for a, b, c, d in
                  (s.split(",") for s in args.shapes)]
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={jax.device_count()}", flush=True)
    sink = open(args.out, "a") if args.out else None
    try:
        for f, b, method, n in shapes:
            t0 = time.time()
            try:
                recs = measure(f, b, method, n, reps=args.reps,
                               block=args.block, interpret=args.interpret,
                               kernel_parts=args.kernel_parts)
            except Exception as e:  # a shape the compiler refuses is a
                # finding of the calibration, not a reason to lose the rest
                recs = [dict(method=method, features=f, bins=b, rows=n,
                             error=f"{type(e).__name__}: {str(e)[:400]}")]
            for rec in recs:
                rec["shape_wall_s"] = time.time() - t0
                line = json.dumps(rec)
                print(line, flush=True)
                if sink:
                    sink.write(line + "\n")
                    sink.flush()
    finally:
        if sink:
            sink.close()


if __name__ == "__main__":
    main()
