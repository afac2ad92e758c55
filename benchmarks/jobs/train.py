"""Job ``train``: lgb.Dataset -> (tune) -> lgb.train, timed per iteration.

The phases are chip_smoke.py's (construct, tune, train with a
``block_until_ready`` callback, compile requests counted over the window,
kernel names read from the lowering, the no-degradation check), copied so
that a later change to chip_smoke.py cannot move the yardstick.

The loop is ``lgb.train``'s own: a callback stamps every iteration, opens
the window after the warm-up iterations and ends training through the
library's ``EarlyStopException`` at the first iteration boundary at or
after ``--seconds``. With ``--trace 1`` the window is ``trace_iterations``
whole iterations under the profiler instead.
"""

import re
import time

import numpy as np

import reference

COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
               "collective-permute", "all-to-all")


def _params(ctx) -> dict:
    return {**ctx.cfg["params"], "verbosity": -1, **ctx.overrides}


def _fallbacks() -> set:
    from lightgbm_tpu.ops import histogram
    return set(histogram._pallas_fallback_warned)


def _degradation(fallbacks_before: set) -> list:
    """Reasons the run was not the path it claims: an OOM-ladder step, or a
    Pallas method that quietly became an XLA program."""
    from lightgbm_tpu import distributed
    out = [f"degradation event: {e}" for e in distributed.degradations()]
    out += [f"Pallas method fell back to XLA: {k}"
            for k in sorted(_fallbacks() - fallbacks_before)]
    return out


def _kernels_in_program(gb, hm: str) -> list:
    """Names of the Pallas kernels in the fused step, from its lowering."""
    step, bind = gb._fused_step_fn(hm, False)
    text = step.lower(*gb._fused_call_args(None, bind)).as_text()
    return re.findall(r'kernel_name = "([^"]+)"', text)


def setup(ctx) -> dict:
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import binning
    cfg, params = ctx.cfg, _params(ctx)
    rows, valid_rows = int(cfg["data"]["rows"]), int(cfg["assumed"]["valid_rows"])
    with ctx.phase("datagen"):
        X, y = ctx.data.make(cfg["data"], ctx.seed, rows + valid_rows,
                               rows)
    st = {"X": X[:rows], "y": y[:rows], "Xv": X[rows:], "yv": y[rows:],
          "params": params, "parallel": params.get("tree_learner",
                                                   "serial") != "serial"}

    with ctx.phase("construct"):
        ds = lgb.Dataset(st["X"], label=st["y"], params=params)
        ds.construct()
        jax.block_until_ready(ds.bins)
    k = min(rows, 4096)
    used = [ds.mappers[j] for j in ds.used_features]
    host = binning.bin_data(
        st["X"][:k][:, ds.used_features].astype(np.float64), used)
    st["construct_ok"] = bool(np.array_equal(
        np.asarray(ds.bins[:k]).astype(np.int32), host))
    st["binned_on_device"] = bool(ds.binned_on_device)
    ctx.log(f"construct: {ctx.phases['construct']:.1f} s rows={ds.num_data} "
            f"bins={ds.max_num_bins} on_device_quantiser="
            f"{ds.binned_on_device} host_slice_equal={st['construct_ok']}")
    st["ds"] = ds

    if not st["parallel"]:
        # method measurement and kernel autotune, timed on their own; both
        # cache per shape, so lgb.train below reuses the answers
        with ctx.phase("tune"):
            gb = lgb.Booster(params=params, train_set=ds)._boosting
            hm = gb._hist_method()
            statics = gb._serial_grow_statics(hm)
        ctx.log(f"tune: {ctx.phases['tune']:.1f} s -> {hm} "
                f"block={statics['hist_block']} "
                f"tile_leaves={statics['tile_leaves']}")
    return st


def window(ctx, st: dict, seconds: float, trace) -> dict:
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import callback, compile_cache
    from lightgbm_tpu.utils import profiling
    warm = int(ctx.cell["warmup_iterations"])
    traced = int(ctx.cell["trace_iterations"]) if trace else None
    stamps, requests, marks = [], [], {}

    def before_iteration(env):
        if "open" in marks:
            marks["span"] = jax.profiler.TraceAnnotation("bench_iteration")
            marks["span"].__enter__()
    before_iteration.before_iteration = True

    def after_iteration(env):
        jax.block_until_ready(env.model._boosting.train_score)
        if "span" in marks:
            marks.pop("span").__exit__(None, None, None)
        now = time.time()
        stamps.append(now)
        requests.append(compile_cache.totals()["requests"])
        n = len(stamps)
        if n == warm:
            # reading the accumulator syncs: before the window opens
            marks["streamed"] = env.model._boosting.rows_streamed_total
            marks["dispatch"] = profiling.dispatch_stats()
            if trace:
                trace.start()
            marks["open"] = time.time()
        elif n > warm:
            done = (n - warm >= traced) if trace \
                else (now - marks["open"] >= seconds)
            if done:
                if trace:
                    trace.stop()
                marks["dispatch"] = profiling.dispatch_delta(
                    marks["dispatch"])
                raise callback.EarlyStopException(env.iteration, [])

    before = _fallbacks()
    t0 = time.time()
    booster = lgb.train(st["params"], st["ds"],
                        num_boost_round=int(ctx.cell["max_rounds"]),
                        callbacks=[before_iteration, after_iteration],
                        keep_training_booster=True)
    st["degraded"] = _degradation(before)
    st["booster"] = booster
    iters = len(stamps) - warm
    if iters < 1 or "open" not in marks:
        raise RuntimeError(f"training ended after {len(stamps)} iterations, "
                           f"inside the warm-up")
    ctx.phases["first_iter"] = stamps[0] - t0
    ctx.phases["warmup_iters"] = stamps[warm - 1] - stamps[0]
    closed = stamps[-1]
    st["compiles_in_window"] = requests[-1] - requests[warm - 1]

    gb = booster._boosting
    n_trees = booster.num_trees()
    # reading the accumulator syncs: after the window
    streamed_per_tree = (gb.rows_streamed_total - marks["streamed"]) / iters
    rows_dev = st["ds"].num_data
    if st["parallel"]:
        rows_dev = rows_dev / gb._parallel_grower.ndev
        # the data-parallel learner sums the devices' rows (psum)
        streamed_per_tree = streamed_per_tree / gb._parallel_grower.ndev
    hm = gb._hist_method()
    ctx.counters.update({k: float(v) for k, v in marks["dispatch"].items()})
    ctx.counters["passes"] = streamed_per_tree / rows_dev * iters
    ctx.units = iters
    ctx.work = {"histogram_method": hm,
                "rows_streamed": streamed_per_tree * iters,
                "features": st["ds"].num_used_features(),
                "bins": int(st["ds"].max_num_bins)}
    ctx.log(f"train: first_iteration={ctx.phases['first_iter']:.1f} s "
            f"window={closed - marks['open']:.3f} s iterations={iters} "
            f"trees={n_trees} method={hm} "
            f"rows_streamed_per_tree={streamed_per_tree:.0f} "
            f"({streamed_per_tree / rows_dev:.3f} passes a device) "
            f"compile_requests_in_window={st['compiles_in_window']} "
            f"iteration_seconds="
            f"{[round(b - a, 3) for a, b in zip(stamps, stamps[1:])]}")

    auc_rounds = int(ctx.cell["auc_rounds"])
    st["auc_rounds"] = min(auc_rounds, n_trees)
    st["auc"] = reference.midrank_auc(st["yv"], booster.predict(
        st["Xv"], num_iteration=st["auc_rounds"], raw_score=True))
    ctx.log(f"valid_auc={st['auc']:.6f} after {st['auc_rounds']} trees on "
            f"{len(st['yv'])} held-out rows")
    metrics = {"train_s_per_iter": (closed - marks["open"]) / iters}
    if n_trees >= auc_rounds:
        metrics["valid_auc"] = st["auc"]
    return {"t_open": marks["open"], "attempted": iters, "failed": 0,
            "metrics": metrics}


def check(ctx, st: dict) -> list:
    """Reasons why the run is not correct (empty: correct)."""
    import jax
    bad = list(st["degraded"])
    booster, ds, params = st["booster"], st["ds"], st["params"]
    gb = booster._boosting
    on_chip = jax.default_backend() == "tpu"
    if not st["construct_ok"]:
        bad.append("device bins differ from the host quantiser")
    if st["binned_on_device"] != on_chip:
        bad.append(f"on-device quantiser ran={st['binned_on_device']} on "
                   f"backend {jax.default_backend()!r}")

    # (a) the root of tree 0 against numpy, at full size
    t0 = time.time()
    bins = np.asarray(ds.bins)[:ds.num_data]
    min_data = float(params["min_data_in_leaf"])
    min_hess = float(params["min_sum_hessian_in_leaf"])
    gain, f_np, t_np, left_np = reference.root_split(
        bins, st["y"], int(ds.max_num_bins), min_data, min_hess)
    tree = reference.parse_model(booster.model_to_string(num_iteration=1))[0]
    f_sys = int(tree["split_feature"][0])
    thr = float(tree["threshold"][0])
    gain_sys, left_raw = reference.gain_of_raw_split(
        st["X"][:, f_sys], st["y"], thr, min_data, min_hess)
    left_sys = reference.child_count(tree, int(tree["left_child"][0]))
    right_sys = reference.child_count(tree, int(tree["right_child"][0]))
    short = (gain - gain_sys) / gain
    tol = float(ctx.cell["root_gain_rel_tolerance"])
    ctx.log(f"root_split: numpy best feature={f_np} bin<={t_np} "
            f"gain={gain:.6f} left={left_np}; system feature={f_sys} "
            f"x<={thr!r} gain(numpy)={gain_sys:.6f} shortfall={short:.3e} "
            f"(tolerance {tol}) counts system={left_sys}/{right_sys} "
            f"numpy={left_raw}/{len(st['y']) - left_raw} "
            f"({time.time() - t0:.1f} s)")
    if not short <= tol:
        bad.append(f"root split gain falls short of numpy's by {short:.3e}")
    if (left_sys, right_sys) != (left_raw, len(st["y"]) - left_raw):
        bad.append("root child counts differ from numpy's")

    # (b) quality anchor
    if not st["auc"] >= float(ctx.cell["auc_anchor"]):
        bad.append(f"valid AUC {st['auc']:.6f} below the anchor")
    # (c) nothing compiled inside the window
    if st["compiles_in_window"]:
        bad.append(f"{st['compiles_in_window']} compile requests inside "
                   f"the window")
    # (d) the kernels are in the step
    hm = gb._hist_method()
    kernels = _kernels_in_program(gb, hm)
    ctx.log(f"kernels_in_program={sorted(set(kernels))} x{len(kernels)} "
            f"method={hm}")
    if not hm.startswith("pallas"):
        bad.append(f"executed histogram method {hm!r} is not a Pallas kernel")
    if on_chip and not any(k.startswith("hist_tiles") for k in kernels):
        bad.append("no compiled hist_tiles kernel in the fused step")
    if not on_chip and not gb._hist_interpret():
        bad.append("off the chip the kernels must run interpreted")
    # (e) data-parallel: the bin matrix spans the mesh, the step holds
    # a collective
    if st["parallel"]:
        want = int(ctx.cell["chips"])
        pg = gb._parallel_grower
        dbins = gb._fused_parallel_bindings(hm)["bins"]
        span = len(dbins.sharding.device_set)
        step, bind = gb._fused_step_fn(hm, False)
        text = step.lower(*gb._fused_call_args(None, bind)) \
            .compile().as_text()
        found = {c: len(re.findall(rf"= [^=\n]*\b{c}(?:-start)?\(", text))
                 for c in COLLECTIVES}
        ctx.log(f"data_parallel: mesh_devices={pg.ndev} "
                f"bins_on_devices={span} per_device="
                f"{dbins.addressable_shards[0].data.shape} "
                f"collectives_in_step={ {c: k for c, k in found.items() if k} }")
        if pg.ndev != want or span != want:
            bad.append(f"mesh has {pg.ndev} devices, bins on {span}, "
                       f"want {want}")
        if not any(found.values()):
            bad.append("no collective in the compiled step")
    return bad
