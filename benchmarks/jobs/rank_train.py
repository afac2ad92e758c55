"""Job ``rank_train``: lgb.Dataset with query groups -> (tune) -> lgb.train
with a ranking objective, timed per iteration.

Shaped like job ``train`` (whose helpers it imports): the same phases
(``datagen``, ``construct``, ``tune``), the same callback window that closes
at the first iteration boundary at or after ``--seconds``, the same
``ctx.units`` / ``ctx.counters`` / ``ctx.work`` keys, so the train-layer
metrics can read this job once their ``jobs`` lists name it. What differs:
the data come with query sizes, the plain reference is
``reference_rank.py``, and ``check`` holds the objective itself to it, on
every query, at the scores the window ended with.

Before any device work ``setup`` asks the library for its bucket plan of
the job's query lengths: a library that pads every query to the longest
one has none, and at this shape its gradient program would be 119 GB a
tensor, so the run ends there instead of compiling it.
"""

import time

import numpy as np

import reference
import reference_rank
from jobs import train as train_job


def _bucket_plan(sizes, params: dict):
    from lightgbm_tpu import ranking
    plan = getattr(ranking, "QueryBuckets", None)
    if plan is None:
        raise RuntimeError(
            "lightgbm_tpu.ranking has no bucket plan (QueryBuckets): this "
            "library pads every query to the longest, which a job of "
            f"{len(sizes)} queries up to {int(np.max(sizes))} documents "
            "cannot run")
    return plan(sizes, int(params.get("lambdarank_truncation_level", 30)))


def _objective_params(params: dict) -> dict:
    """The lambdarank parameters the reference is given: the job's own,
    else the library's documented defaults."""
    return {"sigmoid": float(params.get("sigmoid", 1.0)),
            "norm": bool(params.get("lambdarank_norm", True)),
            "truncation_level": int(params.get(
                "lambdarank_truncation_level", 30))}


def setup(ctx) -> dict:
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import binning
    cfg, params = ctx.cfg, train_job._params(ctx)
    train_sizes, valid_sizes = ctx.data.query_sizes(cfg["data"])
    plan = _bucket_plan(train_sizes, params).counters()
    rows, valid_rows = int(train_sizes.sum()), int(valid_sizes.sum())
    ctx.log(f"bucket plan: {plan} pad_ratio="
            f"{plan['rank_padded_slots'] / plan['rank_documents']:.4f}; "
            f"{len(train_sizes)} training queries / {rows} documents, "
            f"{len(valid_sizes)} held-out queries / {valid_rows} documents")
    if not plan["rank_padded_slots"] < 2 * plan["rank_documents"]:
        raise RuntimeError("the bucket plan pads to 2x the documents or more")
    with ctx.phase("datagen"):
        X, y, sizes = ctx.data.make(cfg["data"], ctx.seed, rows + valid_rows,
                                    rows)
    q = len(train_sizes)
    st = {"X": X[:rows], "y": y[:rows], "sizes": sizes[:q],
          "Xv": X[rows:], "yv": y[rows:], "sizes_v": sizes[q:],
          "params": params, "plan": plan}

    with ctx.phase("construct"):
        ds = lgb.Dataset(st["X"], label=st["y"], group=st["sizes"],
                         params=params)
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
            f"features_used={ds.num_used_features()} bins={ds.max_num_bins} "
            f"on_device_quantiser={ds.binned_on_device} "
            f"host_slice_equal={st['construct_ok']}")
    st["ds"] = ds

    # method measurement and kernel autotune, timed on their own; both
    # cache per shape, so lgb.train below reuses the answers
    with ctx.phase("tune"):
        gb = lgb.Booster(params=params, train_set=ds)._boosting
        hm = gb._hist_method()
        statics = gb._serial_grow_statics(hm)
    ctx.log(f"tune: {ctx.phases['tune']:.1f} s -> {hm} "
            f"block={statics['hist_block']} "
            f"tile_leaves={statics['tile_leaves']} "
            f"split_fusion={statics['split_fusion']} "
            f"compaction_ladder={list(statics['compaction_ladder'])}")
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

    before = train_job._fallbacks()
    t0 = time.time()
    booster = lgb.train(st["params"], st["ds"],
                        num_boost_round=int(ctx.cell["max_rounds"]),
                        callbacks=[before_iteration, after_iteration],
                        keep_training_booster=True)
    st["degraded"] = train_job._degradation(before)
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
    hm = gb._hist_method()
    ctx.counters.update({k: float(v) for k, v in marks["dispatch"].items()})
    ctx.counters["passes"] = streamed_per_tree / rows_dev * iters
    # the plan the booster's own objective holds (not the one setup asked
    # for): what the step's operands were built from
    ctx.counters.update({k: float(v) for k, v in gb.objective.counters()
                         .items() if not isinstance(v, list)})
    ctx.units = iters
    ctx.work = {"histogram_method": hm,
                "rows_streamed": streamed_per_tree * iters,
                "features": st["ds"].num_used_features(),
                "bins": int(st["ds"].max_num_bins)}
    trees = reference.parse_model(booster.model_to_string())
    ctx.log(f"train: first_iteration={ctx.phases['first_iter']:.1f} s "
            f"window={closed - marks['open']:.3f} s iterations={iters} "
            f"trees={n_trees} method={hm} "
            f"leaves_per_tree={[t['num_leaves'] for t in trees]} "
            f"rows_streamed_per_tree={streamed_per_tree:.0f} "
            f"({streamed_per_tree / rows_dev:.3f} passes) "
            f"compile_requests_in_window={st['compiles_in_window']} "
            f"iteration_seconds="
            f"{[round(b - a, 3) for a, b in zip(stamps, stamps[1:])]}")

    k = int(ctx.cell["ndcg_at"])
    st["ndcg_rounds"] = min(int(ctx.cell["ndcg_rounds"]), n_trees)
    raw = booster.predict(st["Xv"], num_iteration=st["ndcg_rounds"],
                          raw_score=True)
    st["ndcg"] = reference_rank.ndcg_at_k(st["yv"], raw, st["sizes_v"], k)
    st["ndcg_untrained"] = reference_rank.ndcg_at_k(
        st["yv"], np.zeros(len(st["yv"])), st["sizes_v"], k)
    ctx.log(f"valid_ndcg@{k}={st['ndcg']:.6f} after {st['ndcg_rounds']} "
            f"trees on {len(st['sizes_v'])} held-out queries "
            f"({len(st['yv'])} documents); untrained (all scores equal) "
            f"{st['ndcg_untrained']:.6f}")
    return {"t_open": marks["open"], "attempted": iters, "failed": 0,
            "metrics": {"train_s_per_iter": (closed - marks["open"]) / iters}}


def _bf16(x):
    import ml_dtypes
    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float64)


def check(ctx, st: dict) -> list:
    """Reasons why the run is not correct (empty: correct)."""
    import jax
    bad = list(st["degraded"])
    booster, ds, params = st["booster"], st["ds"], st["params"]
    gb = booster._boosting
    on_chip = jax.default_backend() == "tpu"
    obj = _objective_params(params)
    if not st["construct_ok"]:
        bad.append("device bins differ from the host quantiser")
    if st["binned_on_device"] != on_chip:
        bad.append(f"on-device quantiser ran={st['binned_on_device']} on "
                   f"backend {jax.default_backend()!r}")

    # (a) the root of tree 0 against numpy at full size, over the
    # reference's lambdas at the scores training starts from (all zero)
    t0 = time.time()
    y, sizes = st["y"], st["sizes"]
    g0, h0 = reference_rank.lambdarank(y, np.zeros(len(y)), sizes, **obj)
    bins = np.asarray(ds.bins)[:ds.num_data]
    min_data = float(params["min_data_in_leaf"])
    min_hess = float(params["min_sum_hessian_in_leaf"])
    gain, f_np, t_np, left_np = reference_rank.root_split(
        bins, g0, h0, int(ds.max_num_bins), min_data, min_hess)
    tree = reference.parse_model(booster.model_to_string(num_iteration=1))[0]
    f_sys = int(tree["split_feature"][0])
    thr = float(tree["threshold"][0])
    gain_sys, left_raw = reference_rank.gain_of_raw_split(
        st["X"][:, f_sys], g0, h0, thr, min_data, min_hess)
    left_sys = reference.child_count(tree, int(tree["left_child"][0]))
    right_sys = reference.child_count(tree, int(tree["right_child"][0]))
    short = (gain - gain_sys) / gain
    tol = float(ctx.cell["root_gain_rel_tolerance"])
    ctx.log(f"root_split: sum_hessian={h0.sum():.3f} numpy best "
            f"feature={f_np} bin<={t_np} gain={gain:.6f} left={left_np}; "
            f"system feature={f_sys} x<={thr!r} gain(numpy)={gain_sys:.6f} "
            f"shortfall={short:.3e} (tolerance {tol}) counts system="
            f"{left_sys}/{right_sys} numpy={left_raw}/{len(y) - left_raw} "
            f"({time.time() - t0:.1f} s)")
    if not short <= tol:
        bad.append(f"root split gain falls short of numpy's by {short:.3e}")
    if (left_sys, right_sys) != (left_raw, len(y) - left_raw):
        bad.append("root child counts differ from numpy's")

    # (b) the objective on every query, at the scores the window ended
    # with (the all-equal start exercises neither the sort nor the
    # score-distance term)
    t0 = time.time()
    score = np.asarray(gb.train_score, dtype=np.float32).reshape(-1)
    g_sys, h_sys = (np.asarray(a) for a in
                    gb.objective.get_grad_hess(gb.train_score))
    g_ref, h_ref = reference_rank.lambdarank(y, score, sizes, **obj)
    err = {"lambda": reference_rank.per_query_error(g_sys, g_ref, sizes),
           "hessian": reference_rank.per_query_error(h_sys, h_ref, sizes)}
    g_low, h_low = reference_rank.lambdarank(y, score, sizes, **obj,
                                             pair_round=_bf16)
    low = {"lambda": reference_rank.per_query_error(g_low, g_ref, sizes),
           "hessian": reference_rank.per_query_error(h_low, h_ref, sizes)}
    tol = float(ctx.cell["lambda_rel_tolerance"])
    for name in ("lambda", "hessian"):
        worst = float(err[name].max())
        ctx.log(f"objective[{name}]: {len(sizes)} queries at the scores of "
                f"{booster.num_trees()} trees ({len(np.unique(score))} "
                f"distinct): per-query max|diff|/max|reference| worst="
                f"{worst:.3e} at query {int(err[name].argmax())} (length "
                f"{int(sizes[err[name].argmax()])}) p99.9="
                f"{np.percentile(err[name], 99.9):.3e} median="
                f"{np.median(err[name]):.3e} (tolerance {tol}); a bf16 pair "
                f"stage would read worst={low[name].max():.3e} median="
                f"{np.median(low[name]):.3e}, over the tolerance in "
                f"{int((low[name] > tol).sum())} queries")
        if not worst <= tol:
            bad.append(f"{name}s differ from the reference by {worst:.3e} "
                       f"of a query's largest")
    ctx.log(f"objective: compared in {time.time() - t0:.1f} s")

    # (c) quality anchor
    if not st["ndcg"] >= float(ctx.cell["ndcg_anchor"]):
        bad.append(f"held-out NDCG {st['ndcg']:.6f} below the anchor")
    margin = float(ctx.cell["ndcg_margin_over_untrained"])
    if not st["ndcg"] >= st["ndcg_untrained"] + margin:
        bad.append(f"held-out NDCG {st['ndcg']:.6f} is not {margin} above "
                   f"the untrained {st['ndcg_untrained']:.6f}")
    # (d) nothing compiled inside the window; the kernels are in the step;
    # the ladder is the one the cell states
    if st["compiles_in_window"]:
        bad.append(f"{st['compiles_in_window']} compile requests inside "
                   f"the window")
    hm = gb._hist_method()
    kernels = train_job._kernels_in_program(gb, hm)
    ladder = list(gb._serial_grow_statics(hm)["compaction_ladder"])
    ctx.log(f"kernels_in_program={sorted(set(kernels))} x{len(kernels)} "
            f"method={hm} compaction_ladder={ladder}")
    if not hm.startswith("pallas"):
        bad.append(f"executed histogram method {hm!r} is not a Pallas kernel")
    if on_chip and not any(k.startswith("hist_tiles") for k in kernels):
        bad.append("no compiled hist_tiles kernel in the fused step")
    if not on_chip and not gb._hist_interpret():
        bad.append("off the chip the kernels must run interpreted")
    if on_chip and ladder != list(ctx.cell["kept_ladder_rows"]):
        bad.append(f"the step's compaction ladder is {ladder}, the cell "
                   f"states {ctx.cell['kept_ladder_rows']}")
    if st["plan"] != gb.objective.counters():
        bad.append("the booster's bucket plan differs from the one the "
                   "query lengths give")
    return bad
