"""Job ``predict``: a scoring job, closed loop with one caller.

Set-up trains the ensemble (only a trained Booster reaches the device
predict engine: a model loaded from text predicts on the host) and makes
the first calls of the batch shape. The window calls ``Booster.predict`` on
host float32 batches at seeded offsets into a pool, one after the other,
each call waiting for its answer.
"""

import time

import numpy as np

import reference


def setup(ctx) -> dict:
    import jax
    import lightgbm_tpu as lgb
    cfg, cell = ctx.cfg, ctx.cell
    params = {**cfg["params"], "verbosity": -1, **cell["train_params"],
              **ctx.overrides}
    train_rows, pool_rows = int(cell["train_rows"]), int(cell["pool_rows"])
    with ctx.phase("datagen"):
        X, y = ctx.data.make(cfg["data"], ctx.seed, train_rows + pool_rows,
                               train_rows)
    with ctx.phase("construct"):
        ds = lgb.Dataset(X[:train_rows], label=y[:train_rows], params=params)
        ds.construct()
        jax.block_until_ready(ds.bins)
    with ctx.phase("train_model"):
        booster = lgb.train(params, ds, num_boost_round=int(cell["trees"]),
                            keep_training_booster=True)
        jax.block_until_ready(booster._boosting.train_score)
    pool = X[train_rows:]
    batch = int(cell["batch_rows"])
    rng = np.random.default_rng(ctx.seed)
    offsets = rng.integers(0, pool_rows - batch + 1,
                           size=int(cell["max_calls"]))
    with ctx.phase("first_calls"):
        for a in offsets[:int(cell["warmup_calls"])]:
            booster.predict(pool[a:a + batch])
    ctx.log(f"setup: train_rows={train_rows} trees={booster.num_trees()} "
            f"in {ctx.phases['train_model']:.1f} s; pool_rows={pool_rows} "
            f"batch_rows={batch} first_calls={ctx.phases['first_calls']:.2f} s")
    return {"booster": booster, "pool": pool, "offsets": offsets,
            "batch": batch, "params": params}


def window(ctx, st: dict, seconds: float, trace) -> dict:
    import jax
    from lightgbm_tpu import compile_cache
    from lightgbm_tpu.utils import profiling
    booster, pool, batch = st["booster"], st["pool"], st["batch"]
    if trace:
        seconds = float(ctx.cell["trace_seconds"])
        trace.start()
    requests = compile_cache.totals()["requests"]
    dispatch = profiling.dispatch_stats()
    lat, failed = [], 0
    t_open = now = time.time()
    for a in st["offsets"]:
        with jax.profiler.TraceAnnotation("bench_call"):
            out = booster.predict(pool[a:a + batch])
        t_sent, now = now, time.time()
        lat.append(now - t_sent)
        if out.shape != (batch,) or not np.isfinite(out).all():
            failed += 1
        if now - t_open >= seconds:
            break
        now = time.time()       # the caller's own check is not latency
    else:
        raise RuntimeError("the window outlasted max_calls: raise it in the "
                           "cell's file")
    if trace:
        trace.stop()
    st["compiles_in_window"] = compile_cache.totals()["requests"] - requests
    ctx.counters.update({k: float(v) for k, v in
                         profiling.dispatch_delta(dispatch).items()})
    ctx.units = len(lat)
    lat_ms = np.sort(np.array(lat)) * 1e3
    ctx.log(f"predict: calls={len(lat)} failed={failed} window="
            f"{now - t_open:.3f} s latency_ms median={np.median(lat_ms):.4f} "
            f"p95={np.percentile(lat_ms, 95):.4f} max={lat_ms[-1]:.4f} "
            f"min={lat_ms[0]:.4f} "
            f"compile_requests_in_window={st['compiles_in_window']}")
    return {"t_open": t_open, "attempted": len(lat), "failed": failed,
            "metrics": {
                "predict_rows_per_s": (len(lat) - failed) * batch
                / (now - t_open),
                "predict_p95_ms": float(np.percentile(lat_ms, 95))}}


def check(ctx, st: dict) -> list:
    from lightgbm_tpu.models import predict_engine
    bad = []
    booster, pool = st["booster"], st["pool"]
    k = int(ctx.cell["check_rows"])
    t0 = time.time()
    trees = reference.parse_model(booster.model_to_string())
    want = reference.predict_raw(trees, pool[:k])
    got = booster.predict(pool[:k], raw_score=True)
    err = float(np.abs(np.asarray(got, np.float64) - want).max())
    tol = float(ctx.cell["raw_score_abs_tolerance"])
    engine = booster._boosting._predict_engine()
    default = predict_engine.resolve_accum("auto")
    ctx.log(f"check: {k} rows x {len(trees)} trees against the numpy "
            f"traversal of the model text: max_abs_err={err:.3e} "
            f"(tolerance {tol}) engine_accum={engine.accum} "
            f"default={default} ({time.time() - t0:.1f} s)")
    if len(trees) != int(ctx.cell["trees"]):
        bad.append(f"model holds {len(trees)} trees")
    if not err <= tol:
        bad.append(f"raw scores differ from the reference by {err:.3e}")
    if engine.accum != default:
        bad.append(f"engine accumulates in {engine.accum}, the default "
                   f"is {default}")
    if st["compiles_in_window"]:
        bad.append(f"{st['compiles_in_window']} compile requests inside "
                   f"the window")
    return bad
