"""Benchmark: Higgs-class GBDT training throughput on one TPU chip.

Mirrors the reference's headline benchmark (docs/Experiments.rst:108-124 —
Higgs 10.5M train rows x 28 features, 255 leaves, lr 0.1, max_bin 255;
130.094 s / 500 iters = 0.260 s/iter on 2x Xeon E5-2690 v4). Data is
synthetic Higgs-shaped (the real HIGGS file isn't in the image); the cost of
a boosting iteration depends on (rows, features, bins, leaves), not label
values, so sec/iter is comparable.

One process, one backend, one shape: without ``--cpu`` the backend must be
a TPU or the run exits 2; whatever fails at the requested rows and method
fails the run (no retry on another method, at fewer rows or on the CPU).
``--cpu`` is an explicit functional run; every result line names the
device it ran on, and ``scripts/bench_compare.py`` refuses to compare a
CPU round with a TPU one. Prints a per-phase breakdown to stderr and
result JSON lines to stdout: {"metric", "value", "unit", "vs_baseline",
...} where vs_baseline = reference_sec_per_iter / ours, scaled to the rows
run (>1 means faster than the reference CPU baseline at that scale). The
headline line prints as soon as the main run completes and again,
enriched with the probe fields, at the end — parsers must take the LAST
JSON line.

The main run trains with the leaf-partitioned row-compaction ladder ON
(the default) and reports its ``rows_streamed_per_tree`` /
``compact_sec_per_iter``; a compaction-off probe at the same scale emits
``nocompact_sec_per_iter`` + ``nocompact_rows_streamed_per_tree`` so the
headroom (the DataPartition-analog row reduction) is on record on every
backend.

A chip belongs to one process, so the second-process warm-start
measurement is a second COMMAND, run after this one has exited and against
the same compile cache: ``python bench.py --warm-start [--rows ...]``.
"""

import argparse
import json
import os
import sys
import time
import traceback

BASELINE_SEC_PER_ITER = 130.094 / 500  # docs/Experiments.rst:108-124
FULL_ROWS = 10_500_000
# Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``:
# the ONE table for every utilization number (scripts/kernel_bench.py
# reads it too). A device that is not here is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "393 TOP/s int8, 16 GB HBM at 819 GB/s per chip",
        "bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9},
}
# histogram_method -> the MXU input rate its contraction runs at (the f32
# HIGHEST modes are bf16 passes; scatter/binloop never touch the MXU)
MODE_PEAK = {"pallas_hilo": "bf16_flops", "onehot_hilo": "bf16_flops",
             "pallas": "bf16_flops", "onehot": "bf16_flops",
             "pallas_q8": "int8_ops", "onehot_q8": "int8_ops"}


def device_peaks(device_kind: str) -> dict:
    if device_kind not in DEVICE_PEAKS:
        raise SystemExit(f"no published peak for device kind "
                         f"{device_kind!r}: add it to bench.DEVICE_PEAKS "
                         f"with its source")
    return DEVICE_PEAKS[device_kind]


def mfu_estimate(sec_per_iter, rows, features, max_bin, num_leaves,
                 hist_method, device_kind):
    """End-to-end utilization estimate — NOT a kernel's roofline share:
    the dense histogram pass's nominal 2*N*F*B*S operations,
    ~log2(num_leaves) passes per tree with subtraction, per second, over
    the published peak of the MXU path ``hist_method`` drives on this
    device. None for methods that never touch the MXU."""
    import math
    peak = MODE_PEAK.get(hist_method)
    if peak is None:
        return None
    nominal = (2.0 * rows * features * max_bin * 3
               * math.ceil(math.log2(max(num_leaves, 2))))
    return nominal / max(sec_per_iter, 1e-12) / device_peaks(device_kind)[peak]


def _compile_totals():
    """Persistent-compile-cache counters (zeros when the hook is off)."""
    from lightgbm_tpu import compile_cache
    t = compile_cache.totals()
    return {"hits": t["hits"], "misses": t["misses"]}


def warm_start(args):
    """``--warm-start``: the second-process side of the compile wall, run
    as its own command AFTER a main run has exited (a chip belongs to one
    process). Rebuilds the same-shape dataset and booster against the
    persistent compile cache the main run filled, times the first
    dispatch (datagen/construct excluded — the wall being measured is the
    compile) and reports this process's fused-step cache counters. Zero
    fused misses == the compile wall is gone."""
    import numpy as np
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import compile_cache
    rng = np.random.RandomState(0)
    n, f = args.rows, args.features
    X = rng.normal(size=(n, f)).astype(np.float32)
    w = rng.normal(size=f)
    y = (higgs_logits(X, w) + rng.logistic(size=n) > 0).astype(np.float32)
    ds = lgb.Dataset(X, label=y, params={"max_bin": args.max_bin,
                                         "verbosity": -1})
    ds.construct()
    K = args.rounds_per_dispatch
    booster = lgb.Booster(params={
        "objective": "binary", "num_leaves": args.num_leaves,
        "learning_rate": 0.1, "max_bin": args.max_bin,
        "min_data_in_leaf": 100, "min_sum_hessian_in_leaf": 100.0,
        "verbosity": -1, "boost_rounds_per_dispatch": K}, train_set=ds)
    if K > 1:
        booster._boosting._block_target = 1 << 30
    t0 = time.time()
    booster.update()
    jax.block_until_ready(booster._boosting.train_score)
    warm = time.time() - t0
    print(json.dumps({
        "metric": "warm_start_s", "value": round(warm, 3), "unit": "s",
        "rows": n, "device": device_json(),
        "warm_fused_misses": compile_cache.module_count("misses",
                                                        "jit(_fused"),
        "warm_fused_hits": compile_cache.module_count("hits",
                                                      "jit(_fused"),
        "warm_cache_hits": _compile_totals()["hits"],
        "warm_cache_misses": _compile_totals()["misses"]}))


def device_json():
    """The device every result names (platform, kind, count)."""
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def higgs_weights(features, seed=0):
    """The label weight vector every Higgs-shaped datagen site shares —
    ONE definition so the --streaming train stream, its held-out valid
    rows and the monolithic branch stay the same task (a drifted copy
    would silently turn the AUC anchor into a mismatched-distribution
    measurement)."""
    import numpy as np
    return np.random.RandomState(seed).normal(size=features)


def higgs_logits(X, w):
    """Higgs-shaped label logits for feature matrix ``X`` under weight
    vector ``w`` (see higgs_weights)."""
    import numpy as np
    f = X.shape[1]
    return (X[:, : f // 2] @ w[: f // 2]
            + 0.5 * np.sin(X[:, f // 2]) * X[:, 0])


def midrank_auc(y, score):
    """Mann-Whitney AUC with midranks (tied scores are common: raw scores
    are sums of discrete leaf values); None when a class is empty."""
    from scipy.stats import rankdata
    npos = float(y.sum())
    nneg = float(len(y) - npos)
    if npos <= 0 or nneg <= 0:
        return None
    ranks = rankdata(score, method="average")
    return float((ranks[y > 0].sum() - npos * (npos + 1) / 2)
                 / (npos * nneg))


def higgs_chunk_stream(rows, features, chunk_rows, seed=0):
    """Chunked Higgs-shaped datagen: a callable chunk factory yielding
    ``(X_chunk, y_chunk)`` pairs, each generated from its own per-chunk
    RandomState — so the 100M-shape round NEVER holds the raw ``[N, F]``
    matrix in host RAM (the monolithic datagen's 11.8 GB at 100M x 28 f32
    was the other half of the construct ceiling, next to construct
    itself). The label weight vector is seed-deterministic and shared
    across chunks, so the stream is re-iterable (the two construct
    passes) and reproducible."""
    import numpy as np
    w = higgs_weights(features, seed)

    def factory():
        for ci, s in enumerate(range(0, rows, chunk_rows)):
            n = min(chunk_rows, rows - s)
            rng = np.random.RandomState((seed + 1) * 100003 + ci)
            X = rng.normal(size=(n, features)).astype(np.float32)
            y = (higgs_logits(X, w) + rng.logistic(size=n) > 0) \
                .astype(np.float32)
            yield X, y

    return factory


def construct_probe(rows, args):
    """Streaming-vs-monolithic construct at CPU-diagnostic scale: the
    SAME float32 matrix constructed both ways, reporting wall seconds,
    rows/sec, the streaming path's peak resident raw-chunk bytes and its
    sketch/bin/h2d sub-phases (telemetry.construct_snapshot), plus a
    bit-parity verdict over the resulting bin matrices — the
    chunked-ingest acceptance numbers on every backend."""
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry

    n = min(rows, 500_000)
    f = args.features
    rng = np.random.RandomState(3)
    X = rng.normal(size=(n, f)).astype(np.float32)
    chunk_rows = max(1, n // 8)

    # bit-parity preconditions: the sampled monolithic fit equals the
    # all-rows sketch fit only when (a) the sample covers every row and
    # (b) the sketch never compacts — so the probe pins
    # bin_construct_sample_cnt >= n AND sketch_max_size=0 (exact mode) on
    # both sides; without them a >=200k-row probe reports a FALSE parity
    # failure (sampling) or a >=65k-distinct one does (compaction). The
    # compaction regime's quality is covered by the rank-error tests,
    # not this bit-parity probe.
    common = {"max_bin": args.max_bin, "verbosity": -1,
              "bin_construct_sample_cnt": n, "sketch_max_size": 0}
    t0 = time.time()
    ds_m = lgb.Dataset(X, params=dict(common)).construct()
    import jax
    jax.block_until_ready(ds_m.bins)
    mono_sec = time.time() - t0

    t0 = time.time()
    ds_s = lgb.Dataset(X, params={**common,
                                  "construct_chunk_rows": chunk_rows})
    ds_s.construct(streaming=True)
    stream_sec = time.time() - t0
    parity = bool(np.array_equal(np.asarray(ds_m.bins),
                                 np.asarray(ds_s.bins)))
    snap = telemetry.construct_snapshot()
    peak = snap.get("peak_host_bytes")
    return {
        "construct_probe_rows": n,
        "construct_monolithic_sec": round(mono_sec, 3),
        "construct_streaming_sec": round(stream_sec, 3),
        "construct_streaming_rows_per_sec": round(n / max(stream_sec, 1e-9),
                                                  1),
        # probe-scoped key: the MAIN run's construct_peak_host_bytes
        # (the 100M acceptance number on --streaming rounds) must not be
        # clobbered by this diagnostic-scale probe's result.update
        "construct_probe_peak_host_bytes": peak,
        # the acceptance ratio: peak resident raw bytes over ONE chunk's
        # bytes — must stay <= 2 (current chunk + in-flight padded copy),
        # vs the monolithic path's n/chunk_rows chunks resident
        "construct_peak_chunks": (round(peak / (chunk_rows * f * 4), 2)
                                  if peak else None),
        "construct_bins_bit_identical": parity,
        "construct_phases": {k: snap[k] for k in
                             ("sketch_pass", "bin_pass", "h2d_overlap")
                             if k in snap},
    }


def _telemetry_json():
    """The unified telemetry snapshot for the result JSON
    (telemetry.snapshot(): scopes + counters + gauges + dispatch +
    health in ONE versioned schema — replaces the hand-rolled
    health/gauges spellings this file used to assemble)."""
    try:
        from lightgbm_tpu import telemetry
        snap = telemetry.snapshot()
        snap["gauges"] = {k: round(v, 3)
                         for k, v in snap.get("gauges", {}).items()}
        return snap
    except Exception:
        return None


def run_at_scale(rows, args, hist_method="auto", hist_compaction=True,
                 extra_params=None, trace=False):
    import numpy as np
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils import profiling

    # K iterations per dispatch (the compile-wall PR's scan block):
    # booster.update() consumes K iterations per call once the block
    # target is set, so every per-iteration number below divides by K
    K = max(1, int(getattr(args, "rounds_per_dispatch", 1)))

    # TIMETAG scopes force a host sync per phase to attribute wall time —
    # exactly what the async-pipelined steady state must NOT do. Collect
    # the table from the two warmup iterations only, then run the timed
    # loop (and everything after) sync-free.
    profiling.reset()
    profiling.enable(True)
    # dispatch/host-sync telemetry for the timed loop (dispatches_per_iter
    # / host_bytes_per_iter JSON fields): counts compiled-program launches
    # and explicit host<->device transfer bytes — the non-histogram
    # overhead the fused iteration exists to kill
    profiling.install_dispatch_hook()

    def mark(name):
        # stream phase completions: a run that dies or is cut at its time
        # limit is then attributable to a phase
        print(f"# [{time.strftime('%H:%M:%S')}] phase done: {name}",
              file=sys.stderr, flush=True)

    phases = {}
    rng = np.random.RandomState(0)
    # train + held-out valid rows from the same synthetic distribution
    n_valid = min(args.valid_rows, rows // 10)
    n, f = rows, args.features
    streaming = bool(getattr(args, "streaming", False))
    t0 = time.time()
    if streaming:
        # chunked datagen + streaming construct: the raw [N, F] train
        # matrix NEVER materializes — each chunk is generated, sketched
        # and device-binned in O(chunk) host memory (the 100M-row shape's
        # only viable ingest). The held-out rows stay monolithic (small).
        chunk_rows = int(getattr(args, "construct_chunk_rows", 0) or 0) \
            or min(max(1 << 18, n // 8), 1 << 21)
        factory = higgs_chunk_stream(n, f, chunk_rows, seed=0)
        vr = np.random.RandomState(10**6)
        Xv = vr.normal(size=(n_valid, f)).astype(np.float32)
        yv = (higgs_logits(Xv, higgs_weights(f, 0))
              + vr.logistic(size=n_valid) > 0).astype(np.float32)
        phases["datagen"] = time.time() - t0
        mark("datagen (chunked stream)")
        t0 = time.time()
        ds = lgb.Dataset.from_chunks(
            factory, params={"max_bin": args.max_bin, "verbosity": -1,
                             "construct_chunk_rows": chunk_rows})
        ds.construct()
    else:
        # Higgs-shaped synthetic: continuous physics-like features,
        # binary label. NOTE: w here is drawn AFTER X on this rng's
        # stream (the historical monolithic task, kept for round-over-
        # round comparability), so it is a DIFFERENT weight realization
        # than the streaming branch's higgs_weights(f, 0) — compare AUC
        # within a mode across rounds, not across modes
        X = rng.normal(size=(n + n_valid, f)).astype(np.float32)
        w = rng.normal(size=f)
        y = (higgs_logits(X, w)
             + rng.logistic(size=n + n_valid) > 0).astype(np.float32)
        Xv, yv = X[n:], y[n:]
        X, y = X[:n], y[:n]
        phases["datagen"] = time.time() - t0
        mark("datagen")
        t0 = time.time()
        ds = lgb.Dataset(X, label=y, params={"max_bin": args.max_bin,
                                             "verbosity": -1})
        ds.construct()
    phases["construct"] = time.time() - t0
    if streaming:
        from lightgbm_tpu import telemetry as _telemetry
        for k, v in _telemetry.construct_snapshot().items():
            if k in ("sketch_pass", "bin_pass", "h2d_overlap"):
                phases[k] = v
    mark("construct")

    booster = lgb.Booster(params={
        "objective": "binary", "num_leaves": args.num_leaves,
        "learning_rate": 0.1, "max_bin": args.max_bin,
        "min_data_in_leaf": 100, "min_sum_hessian_in_leaf": 100.0,
        "histogram_method": hist_method,
        "hist_compaction": hist_compaction,
        "verbosity": -1,
        "boost_rounds_per_dispatch": K,
        **(extra_params or {}),
    }, train_set=ds)
    if K > 1:
        # opt the manual update loop into K-block consumption (normally
        # only engine.train sets the target)
        booster._boosting._block_target = 1 << 30

    # warmup (jit compile + first real block). With K > 1 the first
    # update grows K trees — first_iter_compile_s stays the whole wall
    # (that is the quantity the persistent cache kills), second_iter is
    # per-iteration steady state
    t0 = time.time()
    booster.update()
    phases["first_iter_incl_compile"] = time.time() - t0
    mark("first_iter_incl_compile")
    t0 = time.time()
    booster.update()
    phases["second_iter"] = (time.time() - t0) / K
    mark("second_iter")
    print(f"# ---- TIMETAG phase table ({hist_method}, warmup iters) ----",
          file=sys.stderr)
    for line in profiling.table().splitlines():
        print(f"# {line}", file=sys.stderr)
    profiling.enable(False)

    # drain outstanding async work so warmup doesn't leak into the timing
    jax.block_until_ready(booster._boosting.train_score)
    trees0 = len(booster._boosting.trees)
    disp0 = profiling.dispatch_stats()
    t0 = time.time()
    for _ in range(args.iters):
        booster.update()
    disp1 = profiling.dispatch_stats()
    jax.block_until_ready(booster._boosting.train_score)
    sec_per_iter = (time.time() - t0) / (args.iters * K)
    phases["sec_per_iter"] = sec_per_iter
    d = profiling.dispatch_delta(disp0, disp1)
    disp_per_iter = d["dispatches"] / (args.iters * K)
    host_bytes_per_iter = (d["d2h_bytes"] + d["h2d_bytes"]) \
        / (args.iters * K)
    trees_grown = len(booster._boosting.trees) - trees0
    trees_per_dispatch = trees_grown / max(d["dispatches"], 1)
    mark(f"dispatch telemetry: {disp_per_iter:.1f} dispatches/iter, "
         f"{host_bytes_per_iter:.0f} host bytes/iter, "
         f"{trees_per_dispatch:.1f} trees/dispatch")
    mark(f"timed_iters ({sec_per_iter:.3f} s/iter)")

    # quality anchor: continue to --rounds total iterations, then held-out
    # AUC (speed without a matched-accuracy number is unfalsifiable)
    auc = None
    done = (2 + args.iters) * K
    if args.rounds > done and n_valid > 0:
        t0 = time.time()
        for _ in range(-(-(args.rounds - done) // K)):
            booster.update()
        jax.block_until_ready(booster._boosting.train_score)
        phases["extra_rounds"] = time.time() - t0
        mark("extra_rounds")
    predict_rps = predict_host_bytes = None
    if n_valid > 0:
        t0 = time.time()
        auc = midrank_auc(yv, booster.predict(Xv, raw_score=True))
        phases["valid_auc_predict"] = time.time() - t0
        mark(f"valid_auc_predict (auc={auc})")
        # serving throughput: a SECOND (warm — the AUC predict above paid
        # the engine compile) full-ensemble predict at the same shape,
        # with dispatch/d2h telemetry: the inference-engine acceptance
        # numbers (constant dispatches, [N, K]-only device->host bytes)
        with profiling.dispatch_scope() as dd:
            t0 = time.time()
            _ = booster.predict(Xv, raw_score=True)
            warm_sec = time.time() - t0
        phases["warm_predict"] = warm_sec
        predict_rps = n_valid / max(warm_sec, 1e-9)
        predict_host_bytes = dd["d2h_bytes"]
        mark(f"warm_predict ({predict_rps:.0f} rows/s, "
             f"{dd['dispatches']} dispatches, "
             f"{predict_host_bytes} d2h bytes)")
    # compaction telemetry: rows read by histogram passes per tree (the
    # device-side accumulator syncs here, after the timed loop)
    rows_per_tree = booster._boosting.rows_streamed_per_tree
    mark(f"rows_streamed_per_tree={rows_per_tree:.0f} "
         f"(compaction={'on' if hist_compaction else 'off'})")

    # windowed device-trace capture (--trace-dir/--trace-iters): drive
    # jax.profiler start/stop around N WARM boosting iterations through
    # telemetry.trace_window. The capture holds the lgbm: host spans and
    # device events named by HLO instruction; telemetry.scope_table() maps
    # those to the grower's phases (benchmarks/readers/trace_scope.py is
    # the reader; nothing here reads the capture). Runs on the main
    # booster only (trace=True); a profiler that cannot start is named in
    # the JSON (tw.error).
    trace_info = None
    if trace and getattr(args, "trace_dir", None):
        from lightgbm_tpu import telemetry
        t_iters = max(1, int(getattr(args, "trace_iters", 3)))
        with telemetry.trace_window(args.trace_dir, iters=t_iters) as tw:
            for _ in range(t_iters):
                booster.update()
            jax.block_until_ready(booster._boosting.train_score)
        trace_info = tw.to_json()
        trace_info["files"] = len(telemetry.trace_files(args.trace_dir))
        mark(f"trace capture ({'ok' if tw.ok else tw.error}, "
             f"{trace_info['files']} artifact files)")

    return {"sec_per_iter": sec_per_iter, "phases": phases, "auc": auc,
            # what "auto" resolved to and ran, not what was asked for
            "hist_method": booster._boosting._hist_method(),
            "rounds_run": max(args.rounds, done),
            "rows_per_tree": rows_per_tree,
            "disp_per_iter": disp_per_iter,
            "host_bytes_per_iter": host_bytes_per_iter,
            "predict_rps": predict_rps,
            "predict_host_bytes": predict_host_bytes,
            "trees_per_dispatch": trees_per_dispatch,
            "trace": trace_info}


def phase_scope_probe(rows, args, hist_method="auto", iters=3):
    """Per-phase grow_tree breakdown: train a bounded-scale booster on the
    PHASE-BY-PHASE path (fused_iteration=false) with TIMETAG on, which
    routes growth through the host-phased grower (grow_tree_phased) —
    each round is its own dispatch, so ``hist_pass`` / ``split_search`` /
    ``apply_split`` wall time is attributable per phase on every backend
    (the epilogue's win shows as split_search collapsing). Returns the
    sub-scope dict for the BENCH JSON ``phases`` entry plus the
    dispatch-count frontier check (hist_pass launches per tree — one per
    frontier LEVEL, not per leaf)."""
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils import profiling
    rng = np.random.RandomState(1)
    n, f = min(rows, 200_000), args.features
    X = rng.normal(size=(n, f)).astype(np.float32)
    w = rng.normal(size=f)
    y = (X @ w + rng.logistic(size=n) > 0).astype(np.float32)
    ds = lgb.Dataset(X, label=y, params={"max_bin": args.max_bin,
                                         "verbosity": -1})
    booster = lgb.Booster(params={
        "objective": "binary", "num_leaves": args.num_leaves,
        "learning_rate": 0.1, "max_bin": args.max_bin,
        "min_data_in_leaf": 100, "min_sum_hessian_in_leaf": 100.0,
        "histogram_method": hist_method, "fused_iteration": False,
        "verbosity": -1}, train_set=ds)
    was = profiling.enabled()
    profiling.reset()
    profiling.enable(True)
    try:
        booster.update()          # compile-laden first iteration
        profiling.reset()         # keep only warm per-phase times
        for _ in range(iters):
            booster.update()
        sc = profiling.scopes()
    finally:
        profiling.enable(was)
        profiling.reset()
    out = {}
    for name in ("hist_pass", "split_search", "apply_split"):
        if name in sc:
            out[name] = round(sc[name]["total_s"] / iters, 4)
            out[f"{name}_calls"] = round(sc[name]["calls"] / iters, 1)
    return out


def overhead_probe(rows, args, param, iters=8, repeats=3):
    """Cost of one always-on guard on the fused iteration, measured as
    off-vs-on timed loops at the same scale; returns
    (sec_off, sec_on, overhead_pct). Two consumers:

    - ``param="check_numerics"`` — the in-program numerics sentinels
      (training-integrity layer); budget <= 2% (the flag word is a
      handful of reductions riding the step's epilogue, fetched by lazy
      non-blocking drains);
    - ``param="telemetry_flight_recorder"`` — the per-iteration flight
      recorder; budget <= 2% (host-side dict builds only — the record
      never forces a device sync or an extra dispatch).

    The two arms run as INTERLEAVED timed windows and each arm takes its
    MINIMUM: single-window timing noise on a shared host (±15% at probe
    scale) would otherwise swamp the budgets being measured."""
    import numpy as np
    import jax
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    n, f = rows, args.features
    X = rng.normal(size=(n, f)).astype(np.float32)
    w = rng.normal(size=f)
    y = (X @ w + rng.logistic(size=n) > 0).astype(np.float32)
    boosters = {}
    for guard in (False, True):
        ds = lgb.Dataset(X, label=y, params={"max_bin": args.max_bin,
                                             "verbosity": -1})
        booster = lgb.Booster(params={
            "objective": "binary", "num_leaves": args.num_leaves,
            "learning_rate": 0.1, "max_bin": args.max_bin,
            "min_data_in_leaf": 100, "min_sum_hessian_in_leaf": 100.0,
            "verbosity": -1, param: guard,
        }, train_set=ds)
        booster.update()
        booster.update()                        # warmup (compile)
        jax.block_until_ready(booster._boosting.train_score)
        boosters[guard] = booster
    times = {False: [], True: []}
    for _ in range(repeats):
        for guard in (False, True):
            booster = boosters[guard]
            t0 = time.time()
            for _ in range(iters):
                booster.update()
            jax.block_until_ready(booster._boosting.train_score)
            times[guard].append((time.time() - t0) / iters)
    t_off, t_on = min(times[False]), min(times[True])
    pct = (t_on - t_off) / max(t_off, 1e-12) * 100.0
    return t_off, t_on, pct


def main():
    t_main = time.time()
    # per-phase timer table (the reference's USE_TIMETAG analog) — enabled
    # before the library imports so every run prints the breakdown. Set
    # here and not at import: chip_smoke.py imports this module for the
    # data generator and must not inherit a measurement mode from it
    os.environ.setdefault("LIGHTGBM_TPU_TIMETAG", "1")
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=FULL_ROWS)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--num-leaves", type=int, default=255)
    ap.add_argument("--max-bin", type=int, default=255)
    ap.add_argument("--iters", type=int, default=10,
                    help="timed iterations (after 2 warmup)")
    ap.add_argument("--rounds", type=int, default=100,
                    help="total boosting rounds before the AUC readout")
    ap.add_argument("--valid-rows", type=int, default=500_000,
                    help="held-out rows for the AUC readout (0 disables)")
    ap.add_argument("--probe-deadline", type=int, default=2400,
                    help="stop starting secondary probes (q8/bin63) after "
                         "this many seconds of total wall time")
    ap.add_argument("--streaming", action="store_true",
                    help="chunked datagen + streaming two-pass construct "
                         "for the MAIN run: the raw [N, F] train matrix "
                         "never materializes in host RAM (required for "
                         "the 100M-row Higgs-shape round; host memory "
                         "stays O(chunk))")
    ap.add_argument("--construct-chunk-rows", type=int, default=0,
                    dest="construct_chunk_rows",
                    help="rows per construct chunk in --streaming mode "
                         "(0 = auto: n/8 clamped to [262144, 2M], so any "
                         "scale above ~262k rows streams multi-chunk)")
    ap.add_argument("--cpu", action="store_true",
                    help="explicit functional run on the CPU backend; "
                         "without it the backend must be a TPU")
    ap.add_argument("--rounds-per-dispatch", type=int, default=4,
                    dest="rounds_per_dispatch",
                    help="boost_rounds_per_dispatch K: iterations grown "
                         "per compiled dispatch (lax.scan block; 1 = the "
                         "pre-PR per-iteration dispatch)")
    ap.add_argument("--warm-start", action="store_true", dest="warm_start",
                    help="instead of the benchmark: time the first dispatch "
                         "of a same-shape booster against the compile cache "
                         "a previous run of this command filled (run it "
                         "AFTER that run has exited: one process per chip)")
    ap.add_argument("--trace-dir", default=None, dest="trace_dir",
                    help="capture a jax.profiler device trace of "
                         "--trace-iters warm boosting iterations into "
                         "this directory (telemetry.trace_window; read "
                         "its device events against "
                         "telemetry.scope_table()). The "
                         "outcome — including WHY a capture failed — "
                         "lands in the result JSON 'trace' field")
    ap.add_argument("--trace-iters", type=int, default=3,
                    dest="trace_iters",
                    help="boosting iterations the trace window covers")
    args = ap.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    dev = jax.devices()[0]
    print(f"# device: {dev}", file=sys.stderr)
    if not args.cpu and dev.platform != "tpu":
        # a device metric with no device is an error, never a CPU number
        # under a chip-looking name
        print(json.dumps({"metric": "higgs_sec_per_iter", "value": None,
                          "unit": "s/iter", "vs_baseline": None,
                          "device": device_json(),
                          "error": f"no TPU: jax found {dev.platform!r} "
                                   f"(--cpu for a functional CPU run)"}),
              flush=True)
        sys.exit(2)
    if not args.cpu:
        device_peaks(dev.device_kind)     # unknown device: refuse up front
    # one compile cache for this command and its --warm-start twin:
    # JAX_COMPILATION_CACHE_DIR if set, else a fixed dir in the checkout
    from lightgbm_tpu import compile_cache
    compile_cache.configure(cache_dir=compile_cache.default_dir())
    if args.warm_start:
        warm_start(args)
        return

    used_rows, used_method = args.rows, "auto"
    main_run = run_at_scale(used_rows, args, hist_method=used_method,
                            trace=True)

    sec_per_iter = main_run["sec_per_iter"]
    phases = main_run["phases"]
    auc = main_run["auc"]
    rounds_run = main_run["rounds_run"]
    rows_per_tree = main_run["rows_per_tree"]
    disp_per_iter = main_run["disp_per_iter"]
    host_bytes_per_iter = main_run["host_bytes_per_iter"]
    predict_rps = main_run["predict_rps"]
    predict_host_bytes = main_run["predict_host_bytes"]
    trees_per_dispatch = main_run["trees_per_dispatch"]
    executed_method = main_run["hist_method"]

    # baseline scaled to the rows actually benchmarked (reference cost is
    # ~linear in rows at fixed features/bins/leaves)
    scaled_baseline = BASELINE_SEC_PER_ITER * used_rows / FULL_ROWS
    # utilization estimate against the published peak of THIS device and
    # of the MXU path the executed method drives — a CPU run has no such
    # peak, and gets none
    mfu = None if args.cpu else mfu_estimate(
        sec_per_iter, used_rows, args.features, args.max_bin,
        args.num_leaves, executed_method, dev.device_kind)
    print(f"# executed histogram method: {executed_method}; utilization "
          f"estimate (nominal dense-hist work / published peak): {mfu}",
          file=sys.stderr)

    from lightgbm_tpu.utils import profiling as _profiling
    profiling_gauges = _profiling.gauges()
    result = {
        "metric": f"higgs{used_rows/1e6:.1f}M_sec_per_iter",
        "value": round(sec_per_iter, 4),
        "unit": f"s/iter ({used_rows} rows x {args.features} feat, "
                f"{args.num_leaves} leaves, {args.max_bin} bins, binary)",
        "vs_baseline": round(scaled_baseline / sec_per_iter, 4),
        "rows": used_rows,
        "mfu_mode_est": round(mfu, 4) if mfu is not None else None,
        "auc": round(auc, 6) if auc is not None else None,
        "auc_rounds": rounds_run,
        "hist_method": executed_method,
        # the device this line was measured on; tpu_required says the
        # run would have refused any other (everything but --cpu)
        "backend": jax.default_backend(),
        "device": device_json(),
        "tpu_required": not args.cpu,
        # dispatch/host-sync telemetry over the timed loop (see
        # utils/profiling.py install_dispatch_hook): compiled-program
        # launches and explicit host<->device transfer bytes per
        # iteration — the fused one-dispatch iteration holds the former
        # at 2 (grow step + donated score add)
        "dispatches_per_iter": round(disp_per_iter, 2),
        "host_bytes_per_iter": round(host_bytes_per_iter, 1),
        # serving-path telemetry: warm full-ensemble predict throughput at
        # the valid shape and its device->host bytes (the inference engine
        # holds the latter at ~N*K*8: only the result leaves the device)
        "predict_rows_per_sec": round(predict_rps, 1)
        if predict_rps is not None else None,
        "predict_host_bytes": int(predict_host_bytes)
        if predict_host_bytes is not None else None,
        # the main run has compaction ON (the default): these two fields
        # are the compacted numbers; the nocompact_* probe below supplies
        # the uncompacted side of the headroom comparison
        "compact_sec_per_iter": round(sec_per_iter, 4),
        "rows_streamed_per_tree": round(rows_per_tree, 1)
        if rows_per_tree is not None else None,
        # the compile wall (ISSUE 10): the first dispatch's full wall
        # (XLA compile + first block), the K-block shape, and this
        # process's persistent-cache counters; `bench.py --warm-start`,
        # run afterwards, supplies the second-process (cache-hit) side
        # construct-phase telemetry (the chunked-ingest tentpole): wall
        # seconds, throughput, and — on --streaming runs — the peak
        # resident raw-chunk bytes (O(chunk), vs O(N*F) monolithic); the
        # streaming-vs-monolithic probe below supplies the comparison
        # fields at diagnostic scale on every backend
        "construct_sec": round(phases.get("construct", 0.0), 3),
        "construct_rows_per_sec": round(
            used_rows / max(phases.get("construct", 0.0), 1e-9), 1),
        "construct_streaming": bool(getattr(args, "streaming", False)),
        "construct_peak_host_bytes": (
            int(profiling_gauges.get("construct_peak_bytes"))
            if profiling_gauges.get("construct_peak_bytes") is not None
            else None),
        # memory watermarks (profiling.sample_memory / VmHWM): the
        # device allocator's process-lifetime HBM peak and the host RSS
        # peak — the round's memory cost next to its speed, and the
        # regression axis scripts/bench_compare.py gates on. Null on
        # backends without Device.memory_stats() (the CPU)
        "hbm_peak_bytes": _profiling.sample_memory()["hbm_peak_bytes"],
        "host_rss_peak_bytes": _profiling.host_rss_peak_bytes(),
        "first_iter_compile_s": round(
            phases.get("first_iter_incl_compile", 0.0), 3),
        "trees_per_dispatch": round(trees_per_dispatch, 2),
        "boost_rounds_per_dispatch": args.rounds_per_dispatch,
        "compile_cache_hits": _compile_totals()["hits"],
        "compile_cache_misses": _compile_totals()["misses"],
        "phases": {k: round(v, 3) for k, v in phases.items()},
        # windowed device-trace capture outcome (--trace-dir): where the
        # perfetto trace landed, how many iterations it covers, and WHY
        # it failed when it did
        "trace": main_run.get("trace"),
        # the unified telemetry snapshot (telemetry.snapshot(), one
        # versioned schema): scopes, counters, gauges, dispatch counters
        # and distributed.health_snapshot() — the supervisor restart
        # count, heartbeat table, degradation log and flight-recorder
        # path all live under its "health" key
        "telemetry": _telemetry_json(),
    }
    # print the headline line NOW: a later probe that dies or runs into
    # the time limit must not cost the round its number. The final
    # enriched line is printed again below; parsers that take the last
    # JSON line get the probes too.
    print(json.dumps(result), flush=True)

    def probe_headroom(label):
        left = args.probe_deadline - (time.time() - t_main)
        if left < 0:
            print(f"# skipping {label} probe: past --probe-deadline "
                  f"({args.probe_deadline}s)", file=sys.stderr)
            return False
        return True

    # per-phase grow_tree sub-scopes (the phased grower's hist_pass /
    # split_search / apply_split TIMETAG scopes at a bounded scale): the
    # fused split epilogue's win is measurable per phase on every backend
    # — split_search collapses to bookkeeping and hist_pass_calls counts
    # ONE launch per frontier level, not per leaf
    if probe_headroom("phase-scopes"):
        try:
            ph = phase_scope_probe(used_rows, args, hist_method=used_method)
            result["phases"].update(ph)
            print(f"# grow_tree phase sub-scopes (per iter): {ph}",
                  file=sys.stderr)
        except Exception:
            traceback.print_exc(file=sys.stderr)
            print("# phase-scope probe failed; omitting", file=sys.stderr)
    print(json.dumps(result), flush=True)

    # streaming-vs-monolithic construct probe (runs on ANY backend at
    # CPU-diagnostic scale): the same matrix constructed both ways —
    # wall seconds, the streaming path's peak resident raw-chunk bytes
    # (acceptance: <= 2 chunks) and a bin-matrix bit-parity verdict
    if probe_headroom("construct"):
        try:
            cp = construct_probe(used_rows, args)
            result.update(cp)
            print(f"# construct probe: monolithic "
                  f"{cp['construct_monolithic_sec']}s vs streaming "
                  f"{cp['construct_streaming_sec']}s at "
                  f"{cp['construct_probe_rows']} rows, peak "
                  f"{cp['construct_peak_chunks']} chunks resident, "
                  f"bit-identical={cp['construct_bins_bit_identical']}",
                  file=sys.stderr)
        except Exception:
            traceback.print_exc(file=sys.stderr)
            print("# construct probe failed; omitting", file=sys.stderr)
    print(json.dumps(result), flush=True)

    # compaction on/off headroom probe (runs on ANY backend — the row
    # reduction shows on the CPU scatter path too): same scale with
    # hist_compaction=false supplies the uncompacted sec_per_iter and
    # rows_streamed_per_tree the acceptance comparison needs
    nc_sec = nc_rows = None
    if probe_headroom("nocompact"):
        try:
            nc = run_at_scale(used_rows, args, hist_method=used_method,
                              hist_compaction=False)
            nc_sec, nc_rows = nc["sec_per_iter"], nc["rows_per_tree"]
            print(f"# nocompact probe: {nc_sec:.3f} s/iter, "
                  f"rows/tree={nc_rows:.0f} (compacted run: "
                  f"{sec_per_iter:.3f} s/iter, {rows_per_tree:.0f})",
                  file=sys.stderr)
        except Exception:
            traceback.print_exc(file=sys.stderr)
            print("# nocompact probe failed; omitting", file=sys.stderr)
    result.update({
        "nocompact_sec_per_iter": round(nc_sec, 4)
        if nc_sec is not None else None,
        "nocompact_rows_streamed_per_tree": round(nc_rows, 1)
        if nc_rows is not None else None,
    })
    print(json.dumps(result), flush=True)

    # in-program numerics-sentinel overhead (the training-integrity
    # layer's guard word on the fused iteration): timed at a bounded
    # probe scale so the number exists on every backend; the acceptance
    # budget is <= 2%
    sent_pct = None
    if probe_headroom("sentinel"):
        try:
            s_off, s_on, sent_pct = overhead_probe(
                min(used_rows, 200_000), args, "check_numerics")
            print(f"# sentinel probe: off {s_off:.4f} s/iter, on "
                  f"{s_on:.4f} s/iter -> {sent_pct:+.2f}%",
                  file=sys.stderr)
        except Exception:
            traceback.print_exc(file=sys.stderr)
            print("# sentinel probe failed; omitting", file=sys.stderr)
    # flight-recorder overhead (the telemetry layer's always-on ring):
    # same interleaved-min off/on measurement, same <= 2% budget — the
    # record is host-side dict builds only, so the number should be
    # noise around zero on every backend
    rec_pct = None
    if probe_headroom("recorder"):
        try:
            r_off, r_on, rec_pct = overhead_probe(
                min(used_rows, 200_000), args, "telemetry_flight_recorder")
            print(f"# recorder probe: off {r_off:.4f} s/iter, on "
                  f"{r_on:.4f} s/iter -> {rec_pct:+.2f}%",
                  file=sys.stderr)
        except Exception:
            traceback.print_exc(file=sys.stderr)
            print("# recorder probe failed; omitting", file=sys.stderr)
    result.update({
        "sentinel_overhead_pct": round(sent_pct, 2)
        if sent_pct is not None else None,
        "recorder_overhead_pct": round(rec_pct, 2)
        if rec_pct is not None else None,
    })
    print(json.dumps(result), flush=True)

    # secondary probes: the quantized-gradient mode and the max_bin=63
    # configuration. These run on EVERY backend: on TPU they measure the
    # Pallas q8 kernel; under --cpu the same quantized_grad training
    # resolves to the XLA int8 contraction, so the functional run still
    # covers the path. CPU probes shrink so the run fits its budget.
    if jax.default_backend() == "tpu":
        probe_args = args
        probe_rows = used_rows
    else:
        probe_args = argparse.Namespace(**{
            **vars(args),
            "rounds": min(args.rounds, 15),
            "iters": min(args.iters, 5),
            "valid_rows": min(args.valid_rows, 50_000)})
        probe_rows = min(used_rows, 200_000)

    # quantized-gradient training (Config.quantized_grad): int8 grad/hess
    # with stochastic rounding, exact int32 histogram accumulation, f32
    # rescale at split-gain time — WITH its own held-out AUC so
    # quality-at-speed is on record (the promotion gate for folding q8
    # into "auto" is AUC within ~0.002 of the default path — the same
    # kind of tolerance the reference publishes for its GPU
    # float32-histogram mode, docs/GPU-Performance.rst:133-140)
    q8_sec = q8_auc = q8_mfu = q8_ref_auc = None
    if probe_headroom("q8"):
        try:
            q8 = run_at_scale(probe_rows, probe_args, hist_method="auto",
                              extra_params={"quantized_grad": True})
            q8_sec, q8_ph, q8_auc = (q8["sec_per_iter"], q8["phases"],
                                     q8["auc"])
            q8_mfu = None if args.cpu else mfu_estimate(
                q8_sec, probe_rows, probe_args.features, probe_args.max_bin,
                probe_args.num_leaves, q8["hist_method"], dev.device_kind)
            print(f"# q8 probe: {q8_sec:.3f} s/iter, auc={q8_auc}, "
                  f"int8-peak mfu={q8_mfu}", file=sys.stderr)
            for kk, vv in q8_ph.items():
                print(f"# q8 phase {kk}: {vv:.3f}s", file=sys.stderr)
            if (probe_rows, probe_args.rounds) == (used_rows, args.rounds):
                q8_ref_auc = auc    # main run IS the matched f32 reference
            elif probe_headroom("q8-f32-ref"):
                # reduced-scale probe (--cpu): the q8 AUC needs an f32
                # reference at the SAME scale to be a quality delta
                q8_ref_auc = run_at_scale(
                    probe_rows, probe_args, hist_method=used_method)["auc"]
                print(f"# q8 f32 reference auc={q8_ref_auc}",
                      file=sys.stderr)
        except Exception:
            traceback.print_exc(file=sys.stderr)
            print("# q8 probe failed; omitting", file=sys.stderr)

    # max_bin=63: the reference's RECOMMENDED GPU configuration with
    # published AUC parity (docs/GPU-Performance.rst:43-47: CPU-255
    # 0.845612 vs GPU-63 0.845209 on Higgs) — ~4x fewer one-hot MACs per
    # histogram pass (and full 128-row MXU tiles via the kernel's
    # feature packing). Timed at the probe scale with its own AUC readout
    # so speed-at-matched-quality is on the record.
    b63_sec = b63_auc = b63q8_sec = b63q8_auc = None
    if args.max_bin != 63 and probe_headroom("bin63"):
        b63_args = argparse.Namespace(**{**vars(probe_args), "max_bin": 63})
        try:
            b63 = run_at_scale(probe_rows, b63_args, hist_method="auto")
            b63_sec, b63_ph, b63_auc = (b63["sec_per_iter"], b63["phases"],
                                        b63["auc"])
            print(f"# max_bin=63: {b63_sec:.3f} s/iter, "
                  f"auc={b63_auc}", file=sys.stderr)
            for kk, vv in b63_ph.items():
                print(f"# b63 phase {kk}: {vv:.3f}s", file=sys.stderr)
        except Exception:
            traceback.print_exc(file=sys.stderr)
            print("# max_bin=63 probe failed; omitting", file=sys.stderr)
        # the two levers COMBINED (4x fewer MACs x 2x int8 MXU rate) —
        # the projected fastest configuration, with its own AUC readout
        if probe_headroom("bin63+q8"):
            try:
                b63q8 = run_at_scale(probe_rows, b63_args,
                                     hist_method="auto",
                                     extra_params={"quantized_grad": True})
                b63q8_sec, b63q8_auc = b63q8["sec_per_iter"], b63q8["auc"]
                print(f"# max_bin=63 + q8: {b63q8_sec:.3f} s/iter, "
                      f"auc={b63q8_auc}", file=sys.stderr)
            except Exception:
                traceback.print_exc(file=sys.stderr)
                print("# max_bin=63+q8 probe failed; omitting",
                      file=sys.stderr)

    result.update({
        # probe scale differs from the main run under --cpu — record it
        # so q8/bin63 numbers are compared against the right denominator
        "probe_rows": probe_rows,
        "q8_sec_per_iter": round(q8_sec, 4) if q8_sec is not None else None,
        "q8_auc": round(q8_auc, 6) if q8_auc is not None else None,
        # f32 AUC at the probe's own scale/rounds — the denominator of the
        # q8 quality delta (equals the headline auc when scales match)
        "q8_f32_ref_auc": round(q8_ref_auc, 6)
        if q8_ref_auc is not None else None,
        "q8_mfu_int8_est": round(q8_mfu, 4) if q8_mfu is not None else None,
        "bin63_sec_per_iter": round(b63_sec, 4) if b63_sec is not None
        else None,
        "bin63_auc": round(b63_auc, 6) if b63_auc is not None else None,
        "bin63_q8_sec_per_iter": round(b63q8_sec, 4)
        if b63q8_sec is not None else None,
        "bin63_q8_auc": round(b63q8_auc, 6) if b63q8_auc is not None
        else None,
    })
    print(json.dumps(result))


if __name__ == "__main__":
    main()
