"""What a feature block's width costs: the readings behind
``pallas_hist.MAX_FEATURE_BLOCK``.

For each width in ``--blocks`` the histogram kernel (the epilogue form the
fused step runs, and the plain one) at ``--rows`` x ``--features``, 255
bins, ``hilo``: seconds to compile the launch cold, seconds a pass, and
whether its planes and candidates equal, bit for bit, those of the first
width (the per-feature sums do not depend on how the features are
blocked). Needs a TPU; ``--interpret`` proves the arguments on the CPU at
a tiny shape (its times mean nothing).

    python3 scripts/feature_block_bench.py --blocks 80,128,200
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=400_000)
    ap.add_argument("--features", type=int, default=2000)
    ap.add_argument("--bins", type=int, default=255)
    ap.add_argument("--blocks", default="80,128,200")
    ap.add_argument("--mode", default="hilo")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops import pallas_hist as ph
    jax.config.update("jax_enable_compilation_cache", False)
    n, f, b, p = args.rows, args.features, args.bins, 42
    rng = np.random.default_rng(0)
    binsT = jnp.asarray(rng.integers(0, b, size=(f, n), dtype=np.uint8))
    stats = jnp.asarray(rng.standard_normal((n, 3)).astype(np.float32))
    # as the kernels read them: channel-major, padded to whole row blocks
    stats = ph.pad_stats(stats.T, n)
    leaf = jnp.asarray(rng.integers(0, 64, size=n, dtype=np.int32))
    sel = jnp.arange(p, dtype=jnp.int32)
    seld = jnp.where(sel % 2 == 0, sel + 42, -1).astype(jnp.int32)
    # built inside the program: as an argument a [P, F, B, 3] array takes
    # the default tiled layout, its 3 channels padded to 128 lanes
    parent = lambda: jnp.zeros((p, f, b, 3), jnp.float32)    # noqa: E731
    la = jnp.ones((2, p, 8), jnp.float32) * 1000.0
    fm = ph.pack_feature_meta(jnp.full((f,), b), jnp.zeros((f,)),
                              jnp.zeros((f,)), jnp.zeros((f,)))
    pv = jnp.asarray([0, 0, 0, 0, 1, 1e-3, 0], jnp.float32)
    dev = jax.devices()[0]
    print(f"device={dev.device_kind} rows={n} features={f} bins={b} "
          f"mode={args.mode}", flush=True)

    first = {}
    for fb in [int(x) for x in args.blocks.split(",")]:
        for epi in (True, False):
            if epi:
                fn = jax.jit(lambda: ph.histogram_tiles_pallas_epilogue(
                    binsT, stats, leaf, sel, seld, parent(), la, fm, pv, b,
                    mode=args.mode, interpret=args.interpret, fblock=fb))
            else:
                fn = jax.jit(lambda: ph.histogram_tiles_pallas_mode(
                    binsT, stats, leaf, sel, b, mode=args.mode,
                    interpret=args.interpret, fblock=fb))
            row = {"fblock": fb, "epilogue": epi,
                   "blocks": ph.feature_blocks(f, fb)}
            try:
                t0 = time.time()
                compiled = fn.lower().compile()
                row["compile_s"] = round(time.time() - t0, 2)
                out = jax.block_until_ready(compiled())
                t0 = time.time()
                for _ in range(args.reps):
                    out = compiled()
                jax.block_until_ready(out)
                row["pass_s"] = (time.time() - t0) / args.reps
                row["ns_per_row"] = row["pass_s"] / n * 1e9
                leaves = [np.asarray(x) for x in jax.tree.leaves(out)]
                if epi not in first:
                    first[epi] = leaves
                row["equal_to_first"] = all(
                    np.array_equal(x, y, equal_nan=True)
                    for x, y in zip(leaves, first[epi]))
            except Exception as e:                      # noqa: BLE001
                row["error"] = str(e)[:300]
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
