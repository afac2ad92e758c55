"""chip_smoke.py — the quickest proof that the system starts on the chip.

Drives the main path once through the entry points a user calls —
``lgb.Dataset`` -> ``lgb.train`` -> ``Booster.predict`` -> ``ServeFrontend``
— on ONE TPU chip, with default parameters, at the full width of the Higgs
model (28 features, 255 bins, 255 leaves; docs/Experiments.rst:108-124 of
the reference, BASELINE.md). Data is synthetic Higgs-shaped float32 made
from ``--seed``; rows are cut from the published 10.5M (the cut is printed).

One process, no fallback: without a TPU the script exits non-zero and never
prints the result line. Each phase prints one line with its seconds; a
phase that finds something wrong raises. The last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--chips 4`` runs, instead of those phases, data-parallel training
(``tree_learner=data``) over a 4-device mesh and the serial one-chip run it
is compared with.

The phases are functions of their sizes and a parameter override, so that
tests/test_chip_smoke.py can call them at a tiny size on the CPU with the
kernels interpreted (``hist_pallas_interpret``).
"""

import argparse
import dataclasses
import json
import re
import sys
import time

import numpy as np

PUBLISHED_ROWS = 10_500_000
# the reference's Higgs parameters (docs/Experiments.rst:108-124);
# everything else stays at its default
HIGGS_PARAMS = {"objective": "binary", "num_leaves": 255,
                "learning_rate": 0.1, "max_bin": 255,
                "min_data_in_leaf": 100, "min_sum_hessian_in_leaf": 100.0,
                "verbosity": -1}
# the plain path every fast path is checked against: XLA scatter-add
# histograms, the classic split search, every pass over all rows
PLAIN_PATH = {"histogram_method": "scatter", "split_fusion": "off",
              "hist_compaction": False}
# held-out AUC agreement between two paths on the same data: the repo's own
# gate for a change of histogram precision
AUC_TOLERANCE = 0.002
# .claude/skills/verify/SKILL.md: on this synthetic data anything >= 0.80
# is healthy; ~0.5 means a sign bug
AUC_ANCHOR = 0.80


@dataclasses.dataclass(frozen=True)
class Sizes:
    rows: int = PUBLISHED_ROWS
    valid_rows: int = 200_000
    features: int = 28
    warmup_iters: int = 2
    timed_iters: int = 5
    compare_rows: int = 200_000
    compare_rounds: int = 5
    host_check_rows: int = 2048
    serve_requests: tuple = (1, 8, 256)


def log(msg: str) -> None:
    print(msg, flush=True)


def higgs_weights(features, seed=0):
    """The label weight vector of the Higgs-shaped task, drawn from
    ``seed``: the training rows and the held-out rows share it, so they
    are one task."""
    return np.random.RandomState(seed).normal(size=features)


def higgs_logits(X, w):
    """Higgs-shaped label logits for feature matrix ``X`` under weight
    vector ``w`` (see higgs_weights)."""
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


def make_data(sizes: Sizes, seed: int):
    """Seeded Higgs-shaped data: (X, y, X_valid, y_valid), float32."""
    t0 = time.time()
    n = sizes.rows + sizes.valid_rows
    rng = np.random.default_rng(seed)
    X = rng.standard_normal(size=(n, sizes.features), dtype=np.float32)
    y = (higgs_logits(X, higgs_weights(sizes.features, seed))
         + rng.logistic(size=n) > 0).astype(np.float32)
    log(f"datagen: {time.time() - t0:.1f} s  rows={sizes.rows} "
        f"(published {PUBLISHED_ROWS}, cut {PUBLISHED_ROWS / sizes.rows:.2f}x)"
        f" valid_rows={sizes.valid_rows} features={sizes.features} "
        f"seed={seed}")
    return X[:sizes.rows], y[:sizes.rows], X[sizes.rows:], y[sizes.rows:]


def _params(overrides: dict, **extra) -> dict:
    return {**HIGGS_PARAMS, **extra, **overrides}


def _fallbacks() -> set:
    """The Pallas-to-XLA fallbacks the library has warned of so far in this
    process (it warns once for each)."""
    from lightgbm_tpu.ops import histogram
    return set(histogram._pallas_fallback_warned)


def _no_degradation(where: str, fallbacks_before: set) -> None:
    """After a training: no OOM-ladder step (a new training clears that
    log, so look now) and no kernel that quietly became an XLA program."""
    from lightgbm_tpu import distributed
    events = distributed.degradations()
    if events:
        raise RuntimeError(f"{where}: degradation events recorded: {events}")
    new = _fallbacks() - fallbacks_before
    if new:
        raise RuntimeError(f"{where}: a Pallas method fell back to XLA: "
                           f"{sorted(new)}")


# ---------------------------------------------------------------- construct
def phase_construct(X, y, overrides: dict):
    """lgb.Dataset at max_bin=255. On the chip float32 input is quantised
    ON DEVICE (basic.py, binning.bin_data_device) — a branch no CPU test
    reaches — so a slice is checked against the host quantiser."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import binning
    t0 = time.time()
    params = _params(overrides)
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    jax.block_until_ready(ds.bins)
    secs = time.time() - t0
    k = min(len(X), 4096)
    used = [ds.mappers[j] for j in ds.used_features]
    host = binning.bin_data(X[:k][:, ds.used_features].astype(np.float64),
                            used)
    if not np.array_equal(np.asarray(ds.bins[:k]).astype(np.int32), host):
        raise RuntimeError("construct: device bins differ from the host "
                           "quantiser on the first rows")
    on_chip = jax.default_backend() == "tpu"
    if ds.binned_on_device != on_chip:
        raise RuntimeError(f"construct: on-device quantiser ran="
                           f"{ds.binned_on_device} on backend "
                           f"{jax.default_backend()!r}")
    cat_rows = _categorical_slice(X, y, params, on_chip)
    log(f"construct: {secs:.1f} s  rows={ds.num_data} "
        f"features={ds.num_used_features()} max_bin={params['max_bin']} "
        f"bins={ds.max_num_bins} on_device_quantiser={ds.binned_on_device} "
        f"host_slice_equal=True ({len(X) / secs:.0f} rows/s) "
        f"categorical_slice_equal=True ({cat_rows} rows)")
    return ds


def _categorical_slice(X, y, params: dict, on_chip: bool) -> int:
    """The device quantiser on a categorical column (binning.
    device_cat_table): integer codes made from the first column, with
    NaN, negative, fractional, folded and unseen codes, beside a numerical
    one; every row's device bins against the host quantiser's."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import binning
    k = min(len(X), 1 << 16)
    Z = np.empty((k, 2), np.float32)
    Z[:, 0] = np.floor(np.abs(X[:k, 0]) * 60.0)     # rarer codes further out
    Z[:, 1] = X[:k, 1]
    ds = lgb.Dataset(Z, label=y[:k], params=params,
                     categorical_feature=[0]).construct()
    cut = k // 8
    Z[:cut:7, 0] = np.nan
    Z[1:cut:7, 0] = -3.0
    Z[2:cut:7, 0] = -0.5
    Z[3:cut:7, 0] = 4.25
    Z[4:cut:7, 0] = 100000.0
    used = [ds.mappers[j] for j in ds.used_features]
    dev = np.asarray(binning.bin_data_device(Z, used)).astype(np.int32)
    host = binning.bin_data(Z.astype(np.float64), used)
    if not np.array_equal(dev, host):
        raise RuntimeError("construct: device bins of a categorical column "
                           "differ from the host quantiser")
    if ds.binned_on_device != on_chip:
        raise RuntimeError(f"construct: a categorical column took the "
                           f"on-device quantiser={ds.binned_on_device} on "
                           f"backend {jax.default_backend()!r}")
    return k


# -------------------------------------------------------------------- train
def _kernels_in_program(gb, hm: str) -> list:
    """Names of the Pallas kernels in the fused step's program, read from
    its lowering (a trace, no compile): a kernel that is interpreted, or a
    method that fell back to XLA, leaves no custom call behind."""
    step, bind = gb._fused_step_fn(hm, False)
    text = step.lower(*gb._fused_call_args(None, bind)).as_text()
    return re.findall(r'kernel_name = "([^"]+)"', text)


def phase_tune(ds, overrides: dict) -> dict:
    """The histogram plan the training that follows will run: method, row
    block and tile width, as the library's rule resolves them for this
    configuration and platform (no device program runs here)."""
    import lightgbm_tpu as lgb
    t0 = time.time()
    gb = lgb.Booster(params={**_params(overrides), "verbosity": 1},
                     train_set=ds)._boosting
    hm = gb._hist_method()
    statics = gb._serial_grow_statics(hm)
    tuned = {"histogram_method": hm, "hist_block": statics["hist_block"],
             "tile_leaves": statics["tile_leaves"]}
    log(f"tune: {time.time() - t0:.1f} s -> {hm} "
        f"block={statics['hist_block']} tile_leaves={statics['tile_leaves']}")
    return tuned


def phase_train(ds, sizes: Sizes, overrides: dict):
    """lgb.train with the Higgs parameters: warm-up iterations, then timed
    ones; every iteration ends in block_until_ready (a callback, so the
    loop is lgb.train's own)."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import compile_cache
    compile_cache.install_compile_hook()
    params = _params(overrides)
    stamps, requests = [], []

    def after_iteration(env):
        jax.block_until_ready(env.model._boosting.train_score)
        stamps.append(time.time())
        requests.append(compile_cache.totals()["requests"])

    rounds = sizes.warmup_iters + sizes.timed_iters
    before = _fallbacks()
    t0 = time.time()
    booster = lgb.train(params, ds, num_boost_round=rounds,
                        callbacks=[after_iteration],
                        keep_training_booster=True)
    _no_degradation("train", before)
    gb = booster._boosting
    w = sizes.warmup_iters
    first = stamps[0] - t0
    steady = (stamps[-1] - stamps[w - 1]) / sizes.timed_iters
    compiles_in_window = requests[-1] - requests[w - 1]

    hm = gb._hist_method()
    statics = gb._serial_grow_statics(hm)
    kernels = _kernels_in_program(gb, hm)
    on_chip = jax.default_backend() == "tpu"
    fusion = statics["split_fusion"]
    from lightgbm_tpu.ops import pallas_hist
    epilogue_kernels = [k for k in kernels
                        if k.startswith(pallas_hist.EPILOGUE_KERNEL_NAME)]
    log(f"train: {stamps[-1] - t0:.1f} s  first_iteration={first:.1f} s "
        f"steady={steady:.3f} s/iter over {sizes.timed_iters} iterations "
        f"(after {w} warm-up; iteration 2 took {stamps[1] - stamps[0]:.2f} s)"
        f"  rows={ds.num_data} leaves={params['num_leaves']} "
        f"executed_method={hm} kernels_compiled={on_chip and bool(kernels)} "
        f"kernels_in_program={sorted(set(kernels))} x{len(kernels)} "
        f"split_epilogue_in_program={fusion} "
        f"gather_rungs_in_program={list(statics['compaction_ladder'])} "
        f"block={statics['hist_block']} tile_leaves={statics['tile_leaves']} "
        f"compile_requests_in_timed_window={compiles_in_window} "
        f"rows_streamed_per_tree={gb.rows_streamed_per_tree:.0f}")
    if not hm.startswith("pallas"):
        raise RuntimeError(f"train: executed histogram method {hm!r} is not "
                           f"a Pallas kernel")
    if on_chip and not kernels:
        raise RuntimeError("train: no compiled Pallas kernel in the fused "
                           "step (interpreted, or fell back to XLA)")
    if not on_chip and not gb._hist_interpret():
        raise RuntimeError("train: off the chip the kernels must run "
                           "interpreted (hist_pallas_interpret)")
    if on_chip and (len(epilogue_kernels)
                    != (len(kernels) if fusion else 0)):
        raise RuntimeError(f"train: split_fusion={fusion} but the program "
                           f"holds {kernels}")
    if compiles_in_window:
        raise RuntimeError(f"train: {compiles_in_window} compile requests "
                           f"inside the timed window")
    return booster


# ------------------------------------------------------------------ compare
def phase_compare(X, y, Xv, yv, booster, tuned: dict, sizes: Sizes,
                  overrides: dict) -> None:
    """The default path against the plain path on a row subsample, same
    parameters and rounds: held-out AUC within AUC_TOLERANCE, and both —
    and the full-size model — above the anchor. The default-path side
    names the full run's method and kernel shape explicitly."""
    import lightgbm_tpu as lgb
    t0 = time.time()
    n = min(sizes.compare_rows, len(X))
    aucs = {}
    for name, extra in (("default", tuned), ("plain", PLAIN_PATH)):
        params = _params(overrides, **extra)
        ds = lgb.Dataset(X[:n], label=y[:n], params=params)
        before = _fallbacks()
        b = lgb.train(params, ds, num_boost_round=sizes.compare_rounds)
        _no_degradation(f"compare[{name}]", before)
        aucs[name] = midrank_auc(yv, b.predict(Xv, raw_score=True))
    full = midrank_auc(yv, booster.predict(Xv, raw_score=True))
    diff = abs(aucs["default"] - aucs["plain"])
    log(f"compare: {time.time() - t0:.1f} s  rows={n} "
        f"rounds={sizes.compare_rounds} auc_default={aucs['default']:.6f} "
        f"auc_plain={aucs['plain']:.6f} diff={diff:.6f} "
        f"(tolerance {AUC_TOLERANCE})  full_model_auc={full:.6f} after "
        f"{booster.current_iteration()} rounds (anchor {AUC_ANCHOR})")
    if not diff <= AUC_TOLERANCE:
        raise RuntimeError(f"compare: AUC differs by {diff:.6f}")
    low = min(full, *aucs.values())
    if not low >= AUC_ANCHOR:
        raise RuntimeError(f"compare: AUC {low:.6f} below the anchor")


# ------------------------------------------------------------------ predict
def phase_predict(booster, Xv, sizes: Sizes):
    """Booster.predict on held-out rows through PredictEngine (default
    predict_accum: float64 accumulation on the device), equal on a slice
    to a host traversal of the model text."""
    from lightgbm_tpu.io import model_text
    t0 = time.time()
    first = booster.predict(Xv)
    t_first = time.time() - t0
    t0 = time.time()
    pred = booster.predict(Xv)
    t_warm = time.time() - t0
    if pred.shape != (len(Xv),) or not np.isfinite(pred).all() \
            or pred.min() < 0.0 or pred.max() > 1.0 \
            or not np.array_equal(first, pred):
        raise RuntimeError("predict: not a vector of probabilities")
    k = min(sizes.host_check_rows, len(Xv))
    host = model_text.load_model(booster.model_to_string()).predict(
        Xv[:k].astype(np.float64), raw_score=True)
    dev = booster.predict(Xv[:k], raw_score=True)
    if not np.array_equal(dev, host):
        raise RuntimeError(f"predict: device raw scores differ from the "
                           f"host traversal (max abs "
                           f"{np.abs(dev - host).max():.3e})")
    engine = booster._boosting._predict_engine()
    log(f"predict: {t_first + t_warm:.1f} s  rows={len(Xv)} "
        f"first_call={t_first:.2f} s warm={t_warm:.3f} s "
        f"({len(Xv) / t_warm:.0f} rows/s)  engine_accum={engine.accum} "
        f"trees={booster.num_trees()} host_traversal_equal=True "
        f"({k} rows, bit for bit)")
    return pred


# -------------------------------------------------------------------- serve
def phase_serve(booster, Xv, sizes: Sizes) -> None:
    """A ServeFrontend answers a few requests, equal to the direct predict."""
    from lightgbm_tpu.serving import ServeFrontend
    t0 = time.time()
    fe = ServeFrontend(booster)
    lat = []
    try:
        for i, rows in enumerate(sizes.serve_requests):
            a = (i * 131) % max(len(Xv) - rows, 1)
            t1 = time.time()
            out = fe.predict(Xv[a:a + rows])
            lat.append((rows, (time.time() - t1) * 1e3))
            if not np.array_equal(out, booster.predict(Xv[a:a + rows])):
                raise RuntimeError(f"serve: {rows}-row answer differs from "
                                   f"Booster.predict")
        st = fe.stats()
    finally:
        fe.close()
    log(f"serve: {time.time() - t0:.1f} s  requests="
        + ", ".join(f"{r} rows in {ms:.1f} ms" for r, ms in lat)
        + f"  batches={st['batches']} equal_to_predict=True")


# --------------------------------------------------------------- four chips
_COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
                "collective-permute", "all-to-all")


def phase_four_chips(X, y, Xv, yv, sizes: Sizes, overrides: dict,
                     devices: int = 4) -> None:
    """tree_learner=data over a mesh of ``devices`` chips against the
    serial run on one, same rows and width: held-out AUC within
    AUC_TOLERANCE. Code that has never seen more than one real chip may
    put everything on the first, so the bin matrix's sharding is checked
    and the collectives of the compiled step are printed."""
    import jax
    import lightgbm_tpu as lgb
    rounds = sizes.warmup_iters + sizes.timed_iters
    out = {}
    for name, extra in (("data_parallel", {"tree_learner": "data"}),
                        ("serial", {})):
        params = _params(overrides, **extra)
        stamps = []

        def after_iteration(env, stamps=stamps):
            jax.block_until_ready(env.model._boosting.train_score)
            stamps.append(time.time())

        t0 = time.time()
        ds = lgb.Dataset(X, label=y, params=params)
        ds.construct()
        t_construct = time.time() - t0
        before = _fallbacks()
        t0 = time.time()
        b = lgb.train(params, ds, num_boost_round=rounds,
                      callbacks=[after_iteration],
                      keep_training_booster=True)
        _no_degradation(name, before)
        w = sizes.warmup_iters
        steady = (stamps[-1] - stamps[w - 1]) / sizes.timed_iters
        gb = b._boosting
        hm = gb._hist_method()
        line = (f"{name}: construct={t_construct:.1f} s "
                f"first_iteration={stamps[0] - t0:.1f} s "
                f"steady={steady:.3f} s/iter over {sizes.timed_iters} "
                f"iterations  rows={ds.num_data} executed_method={hm}")
        if name == "data_parallel":
            pg = gb._parallel_grower
            if pg is None or pg.ndev != devices:
                raise RuntimeError(f"four chips: mesh has "
                                   f"{getattr(pg, 'ndev', 0)} devices")
            bins = gb._fused_parallel_bindings(hm)["bins"]
            span = len(bins.sharding.device_set)
            shard = bins.addressable_shards[0].data.shape
            if span != devices:
                raise RuntimeError(f"four chips: the bin matrix is on "
                                   f"{span} devices, not {devices}")
            step, bind = gb._fused_step_fn(hm, False)
            text = step.lower(*gb._fused_call_args(None, bind)) \
                .compile().as_text()
            found = {c: len(re.findall(rf"= [^=\n]*\b{c}(?:-start)?\(",
                                       text)) for c in _COLLECTIVES}
            kernels = len(re.findall(r'custom_call_target="tpu_custom_call"',
                                     text))
            line += (f" mesh_devices={pg.ndev} bins_sharding_devices={span} "
                     f"bins_global={tuple(bins.shape)} per_device={shard} "
                     f"collectives_in_step="
                     f"{ {c: k for c, k in found.items() if k} } "
                     f"pallas_kernels_in_step={kernels}")
            if not any(found.values()):
                raise RuntimeError("four chips: no collective in the "
                                   "compiled step")
        out[name] = midrank_auc(yv, b.predict(Xv, raw_score=True))
        log(line + f" auc={out[name]:.6f}")
    diff = abs(out["data_parallel"] - out["serial"])
    log(f"four_chips: auc_data_parallel={out['data_parallel']:.6f} "
        f"auc_serial={out['serial']:.6f} diff={diff:.6f} "
        f"(tolerance {AUC_TOLERANCE}, anchor {AUC_ANCHOR})")
    if not diff <= AUC_TOLERANCE or min(out.values()) < AUC_ANCHOR:
        raise RuntimeError("four chips: AUC check failed")


# --------------------------------------------------------------------- main
def run_one_chip(sizes: Sizes, seed: int, overrides: dict) -> None:
    X, y, Xv, yv = make_data(sizes, seed)
    ds = phase_construct(X, y, overrides)
    tuned = phase_tune(ds, overrides)
    booster = phase_train(ds, sizes, overrides)
    phase_compare(X, y, Xv, yv, booster, tuned, sizes, overrides)
    phase_predict(booster, Xv, sizes)
    phase_serve(booster, Xv, sizes)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: data-parallel training over four chips and "
                         "the serial run it is compared with, nothing else")
    ap.add_argument("--rows", type=int, default=Sizes.rows,
                    help="training rows (published shape: 10.5M)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t_start = time.time()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform!r}); this "
              f"script only runs on the chip", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    from lightgbm_tpu import compile_cache
    cache = compile_cache.configure(cache_dir=compile_cache.default_dir())
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}  jax={jax.__version__}  compile_cache={cache}")

    sizes = Sizes(rows=args.rows)
    if args.chips == 1:
        run_one_chip(sizes, args.seed, {})
    else:
        X, y, Xv, yv = make_data(sizes, args.seed)
        phase_four_chips(X, y, Xv, yv, sizes, {})
    t = compile_cache.totals()
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"total: {time.time() - t_start:.1f} s  compile_requests="
        f"{t['requests']} persistent_cache_hits={t['hits']} "
        f"written={t['misses']} device_peak_bytes={peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
