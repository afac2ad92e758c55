"""Per-kernel roofline microbench for the fused Pallas histogram pipeline.

For each mode (hilo / highest / q8) and pass form (full pass / compaction
rung: XLA row gather feeding the same kernel) at a Higgs-shaped tile
pass, reports:

- **bytes moved** (modeled HBM traffic, ops/pallas_hist.py traffic_model)
  and the achieved HBM bandwidth implied by the measured time;
- **MXU passes** (contraction input passes: hilo 2 bf16, highest 6, q8 1
  int8) and the achieved vs peak MXU rate on the mode's input path;
- the **XLA onehot** formulation of the same contraction as the baseline
  (the acceptance comparison: the fused kernel's modeled traffic is
  >= 5x below it, and on TPU the measured time should follow).

On a TPU the numbers are real, and are set against the published peaks
of that device (``benchmarks/peaks.json``, keyed by device_kind; an
unknown device is an error); on CPU hosts ``--interpret`` runs the kernels
through the Pallas interpreter — times are then meaningless
(interpretation overhead) and no achieved fraction is printed, but the
traffic MODEL columns still hold and every kernel variant actually
executes. The CI smoke
(`tests/run_suite.sh`, ``--fast --interpret``) runs all nine variants
through the interpreter at a tiny shape (~30-60 s) and asserts the
modeled >=5x traffic ratios; ``--model-only`` skips execution entirely
for an instant model-table print.

Usage:
  python scripts/kernel_bench.py                  # Higgs0.5M shape, TPU
  python scripts/kernel_bench.py --rows 10500000  # full Higgs
  python scripts/kernel_bench.py --fast --interpret   # the CI smoke
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, REPO)

# mode -> the peak (a key of a benchmarks/peaks.json row) its MXU input
# path runs against
MODE_PEAK = {"hilo": "bf16_flops", "highest": "bf16_flops",
             "q8": "int8_ops"}


def device_peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``: its row of
    ``benchmarks/peaks.json``, the table the benchmark's roofline readers
    use. A device that is not listed there is an error, not a default."""
    with open(os.path.join(REPO, "benchmarks", "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise SystemExit(f"no published peak for device kind "
                         f"{device_kind!r}: add it to benchmarks/peaks.json "
                         f"with its source")
    return table[device_kind]


def achieved_fracs(traffic, macs, passes, sec, mode, peaks) -> dict:
    """The shares of the device's peaks a kernel reached: modeled bytes
    over seconds against HBM bandwidth, and contraction operations
    (2 x MACs x input passes) over seconds against the peak of the
    mode's MXU input path."""
    return {"achieved_hbm_frac": round(
                traffic / sec / peaks["hbm_bytes_per_s"], 4),
            "achieved_mxu_frac": round(
                2.0 * macs * passes / sec / peaks[MODE_PEAK[mode]], 4)}


def timeit(fn, reps):
    import jax
    jax.block_until_ready(fn())     # compile + first run
    t0 = time.time()
    for _ in range(reps):
        r = fn()
    jax.block_until_ready(r)
    return (time.time() - t0) / reps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=500_000,
                    help="tile-pass rows (default: the Higgs0.5M shape)")
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--bins", type=int, default=255)
    ap.add_argument("--tile", type=int, default=42)
    ap.add_argument("--block", type=int, default=0,
                    help="rows a grid step (0: pallas_hist.DEFAULT_BLOCK)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--gather-frac", type=float, default=0.25,
                    help="pending-row fraction for the compaction-rung rows")
    ap.add_argument("--modes", type=str, default="hilo,highest,q8")
    ap.add_argument("--interpret", action="store_true",
                    help="run kernels through the Pallas interpreter "
                         "(CPU hosts; times are interpreter overhead)")
    ap.add_argument("--model-only", action="store_true",
                    help="print the traffic/roofline model without timing "
                         "(works anywhere, instantly)")
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke knobs: tiny shape, 1 rep")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops import pallas_hist
    args.block = args.block or pallas_hist.DEFAULT_BLOCK
    if args.fast:
        args.rows = min(args.rows, 8192)
        args.features = min(args.features, 6)
        args.bins = min(args.bins, 63)
        args.block = min(args.block, 512)
        args.reps = 1
    from lightgbm_tpu.ops.histogram import histogram_tiles

    n, f, b, p = args.rows, args.features, args.bins, args.tile
    s = 3
    m = -(-int(n * args.gather_frac) // 128) * 128
    backend = jax.default_backend()
    interpret = args.interpret and backend != "tpu"
    peaks = None
    if backend == "tpu":
        peaks = device_peaks(jax.devices()[0].device_kind)
    print(f"# device={jax.devices()[0]} N={n} F={f} B={b} P={p} "
          f"block={args.block} gather_rows={m} interpret={interpret}",
          file=sys.stderr)

    rng = np.random.RandomState(0)
    binsT_np = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    binsT = jnp.asarray(binsT_np)
    bins = jnp.asarray(np.ascontiguousarray(binsT_np.T))
    # the statistics as the grower hands them: channel-major, [S, N] for
    # the dispatcher, zero-padded to whole row blocks for the kernels
    stats_f = jnp.asarray(rng.normal(size=(n, s)).astype(np.float32).T)
    stats_i = jnp.asarray(rng.randint(-127, 128, (n, s)).astype(np.int8).T)
    leaf = jnp.asarray(rng.randint(0, p, size=n).astype(np.int32))
    sel = jnp.asarray(np.arange(p, dtype=np.int32))
    idx = jnp.asarray(np.sort(rng.choice(n, size=m, replace=False))
                      .astype(np.int32))

    # per-pass MAC count of the contraction: every row drives F one-hot
    # columns x 128 output lanes (feature packing keeps the tile full at
    # b <= 64, so lanes-per-row is 128 regardless of b)
    g = max(1, 128 // b) if b <= 128 else 1
    macs_full = n * (-(-f // g)) * max(b * g, 128) * 128
    macs_gather = m * (-(-f // g)) * max(b * g, 128) * 128

    rows = []

    def record(name, mode, kind, sec, traffic, macs):
        passes = pallas_hist.MXU_PASSES.get(mode, 1)
        entry = {
            "variant": name, "mode": mode, "kind": kind,
            "modeled_bytes": traffic,
            "mxu_passes": passes,
            "macs": macs,
            "sec": round(sec, 6) if sec is not None else None,
        }
        if sec is not None and peaks is not None:
            entry.update(achieved_fracs(traffic, macs, passes, sec, mode,
                                        peaks))
        rows.append(entry)
        print(json.dumps(entry), flush=True)

    # fused split-EPILOGUE variant inputs (ISSUE 12): every slot computed,
    # each deriving a sibling in the epilogue's second lane group;
    # dummy-but-valid scan metadata
    from lightgbm_tpu.ops.split import CAND_CHANNELS
    sel_pairs = jnp.asarray(np.arange(p, dtype=np.int32))
    sel_derived = sel_pairs + p
    parent = jnp.zeros((p, f, b, s), jnp.float32)
    la = jnp.stack([pallas_hist.pack_leaf_aux(
        jnp.zeros((p,)), jnp.ones((p,)), jnp.full((p,), float(n)),
        jnp.zeros((p,)))] * 2)
    fmeta = pallas_hist.pack_feature_meta(
        jnp.full((f,), b, jnp.int32), jnp.zeros((f,), jnp.int32),
        jnp.zeros((f,), jnp.int32), jnp.zeros((f,), jnp.int32))
    pvec = jnp.zeros((7,), jnp.float32)

    for mode in args.modes.split(","):
        st = stats_i if mode == "q8" else stats_f
        kst = pallas_hist.pad_stats(st, n, args.block)
        t = pallas_hist.traffic_model(n, f, b, p, s, mode)
        tg = pallas_hist.traffic_model(n, f, b, p, s, mode,
                                       gathered_rows=m)
        sec_full = sec_gather = sec_xla = sec_epi = None
        if not args.model_only:
            sec_full = timeit(lambda: pallas_hist.histogram_tiles_pallas_mode(
                binsT, kst, leaf, sel, b, block=args.block, mode=mode,
                interpret=interpret), args.reps)
            pallas_m = {"hilo": "pallas_hilo", "highest": "pallas",
                        "q8": "pallas_q8"}[mode]
            sec_gather = timeit(lambda: histogram_tiles(
                bins, st, leaf, sel, b, method=pallas_m, block=args.block,
                binsT=binsT, gather_idx=idx, interpret=interpret),
                args.reps)
            qsc = (jnp.ones((s,), jnp.float32) if mode == "q8" else None)
            epi_tile, epi_cand = pallas_hist.histogram_tiles_pallas_epilogue(
                binsT, kst, leaf, sel_pairs, sel_derived, parent, la, fmeta,
                pvec, b, block=args.block, mode=mode,
                interpret=interpret, q_scale=qsc)
            # acceptance floor from the REAL returned buffers (not the
            # traffic model): per-leaf plane bytes the classic search
            # would stream vs the candidate row the fused search reads
            plane_per_leaf = epi_tile.nbytes // epi_tile.shape[0]
            cand_per_leaf = epi_cand.nbytes // epi_cand.shape[0]
            sratio_real = plane_per_leaf / cand_per_leaf
            print(f"# {mode}: measured split-search bytes/leaf "
                  f"plane={plane_per_leaf} cand={cand_per_leaf} "
                  f"ratio={sratio_real:.1f}x (floor: B/4 = {b / 4:.1f}x)",
                  file=sys.stderr)
            assert sratio_real >= b / 4, (mode, sratio_real, b)
            sec_epi = timeit(
                lambda: pallas_hist.histogram_tiles_pallas_epilogue(
                    binsT, kst, leaf, sel_pairs, sel_derived, parent, la,
                    fmeta, pvec, b, block=args.block, mode=mode,
                    interpret=interpret, q_scale=qsc)[1], args.reps)
            xla_m = {"hilo": "onehot_hilo", "highest": "onehot",
                     "q8": "onehot_q8"}[mode]
            sec_xla = timeit(lambda: histogram_tiles(
                bins, st, leaf, sel, b, method=xla_m,
                block=args.block), args.reps)
        record(f"pallas_{mode}", mode, "full", sec_full, t["fused"],
               macs_full)
        record(f"pallas_{mode}_gather", mode, "gather", sec_gather,
               tg["fused"], macs_gather)
        record(f"pallas_{mode}_epilogue", mode, "epilogue", sec_epi,
               t["fused"], macs_full)
        record(f"xla_onehot_{mode}", mode, "xla-baseline", sec_xla,
               t["xla_onehot"], macs_full)
        ratio = t["xla_onehot"] / t["fused"]
        print(f"# {mode}: modeled traffic fused={t['fused']/1e6:.1f}MB "
              f"xla={t['xla_onehot']/1e6:.1f}MB ratio={ratio:.0f}x "
              f"(acceptance floor: 5x)", file=sys.stderr)
        assert ratio >= 5, (mode, ratio)
        # split-search consumer bytes: per-leaf [F, B, 4] planes vs the
        # epilogue's [F, CAND_CHANNELS] candidate row — the ISSUE 12
        # acceptance floor is a >= B/4x reduction
        sratio = t["search_in_planes"] / t["search_in_cand"]
        print(f"# {mode}: split-search bytes planes="
              f"{t['search_in_planes']} cand={t['search_in_cand']} "
              f"ratio={sratio:.1f}x (floor: B/4 = {b / 4:.1f}x, "
              f"CAND_CHANNELS={CAND_CHANNELS})", file=sys.stderr)
        assert sratio >= b / 4, (mode, sratio, b)
        if sec_full is not None and sec_xla is not None and not interpret:
            print(f"# {mode}: measured fused={sec_full*1e3:.2f}ms "
                  f"epilogue={sec_epi*1e3:.2f}ms "
                  f"xla={sec_xla*1e3:.2f}ms "
                  f"speedup={sec_xla/max(sec_full,1e-12):.2f}x",
                  file=sys.stderr)

    print(f"# OK: {len(rows)} variants", file=sys.stderr)


if __name__ == "__main__":
    main()
