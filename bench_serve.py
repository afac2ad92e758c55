"""Serving-layer benchmark: latency percentiles and sustained rows/sec
through the ServeFrontend under concurrent mixed-size load.

bench.py measures the TRAINING plane; this is its serve-plane twin for
ROADMAP item 4 ("serve batched predictions to millions of users"). An
OPEN-LOOP arrival process (request start times are fixed up front at
``--rps``, independent of completions — the load a front end actually
faces, where a slow server does not slow the clients down) submits a
small/large request mix from many client threads; the frontend coalesces
them into bucketed engine dispatches with a ``serve_flush_ms`` deadline.

Prints result JSON lines to stdout in the bench.py shape ({"metric", ...};
parsers take the LAST line) with the serve fields alongside the existing
bench fields (backend, scale, health snapshot):

  serve_p50_ms / serve_p99_ms   end-to-end request latency percentiles
                                (queue wait + coalesced dispatch + split)
  serve_rows_per_sec            successfully answered rows / wall time
  serve_shed_count              admission-control rejections during the
                                measured load (ServeOverloadError)
  serve_timeout_count           deadline misses (ServeTimeoutError)
  serve_coalesce_ratio          requests per engine dispatch (>1 = the
                                micro-batcher is earning its flush delay)

One process (the load's client threads live in it), so it runs on the
chip as it is; every result line names the backend it ran on. A CPU run
(--cpu / --fast) is a functional number, not the benchmark, but the
MACHINERY measured (admission, coalescing, deadline accounting, donated
serve buffers) is backend-independent, which is what CI asserts via the
fast-knob stanza in tests/run_suite.sh. The persistent compile cache is
JAX_COMPILATION_CACHE_DIR if set, else `.jax_cache/` in the checkout.
"""

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def build_model(args):
    import numpy as np
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.normal(size=(args.train_rows, args.features))
    y = (X[:, 0] + 0.4 * X[:, 1] - 0.2 * X[:, 2] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": args.num_leaves,
              "min_data_in_leaf": 20, "verbosity": -1, "seed": 3,
              "serve_flush_ms": args.flush_ms,
              "serve_max_queue_rows": args.max_queue_rows}
    booster = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                        args.rounds)
    return booster, X


def request_mix(args, n_requests):
    """Deterministic small/large size mix: mostly single-digit-row
    point-lookups with a heavy tail of batch scorers — the shape that
    makes micro-batching matter (small requests ride along with big
    ones into one bucketed dispatch)."""
    import numpy as np
    rng = np.random.RandomState(11)
    small = rng.choice([1, 2, 4, 8], size=n_requests)
    large = rng.choice(args.large_sizes, size=n_requests)
    is_large = rng.uniform(size=n_requests) < args.large_frac
    return np.where(is_large, large, small)


def run_load(fe, X, sizes, args):
    """Open-loop load: request i starts at t0 + i/rps regardless of how
    the previous ones are doing. Client threads pull the next arrival,
    sleep until its slot, submit, record. If every client is busy when a
    slot comes due the submission is late — counted (late_starts) so a
    saturated client pool is visible instead of silently turning the
    measurement closed-loop."""
    import numpy as np
    from lightgbm_tpu.serving import ServeOverloadError, ServeTimeoutError

    lat_ms = []
    ok_rows = [0]
    sheds = [0]
    timeouts = [0]
    late = [0]
    errors = []
    lock = threading.Lock()
    next_i = [0]
    t0 = time.monotonic()

    def client():
        while True:
            with lock:
                i = next_i[0]
                if i >= len(sizes):
                    return
                next_i[0] += 1
            rows = int(sizes[i])
            slot = t0 + i / args.rps
            now = time.monotonic()
            if now < slot:
                time.sleep(slot - now)
            elif now - slot > 0.5 / args.rps:
                with lock:
                    late[0] += 1
            a = (i * 131) % max(len(X) - rows, 1)
            t_req = time.monotonic()
            try:
                fe.predict(X[a:a + rows],
                           deadline_ms=args.deadline_ms or None)
            except ServeOverloadError:
                with lock:
                    sheds[0] += 1
                continue
            except ServeTimeoutError:
                with lock:
                    timeouts[0] += 1
                continue
            except BaseException as e:     # noqa: BLE001 — reported
                with lock:
                    errors.append(repr(e))
                continue
            dt = (time.monotonic() - t_req) * 1e3
            with lock:
                lat_ms.append(dt)
                ok_rows[0] += rows
    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    lat = np.asarray(lat_ms) if lat_ms else np.asarray([float("nan")])
    return {
        "serve_p50_ms": round(float(np.percentile(lat, 50)), 3),
        "serve_p99_ms": round(float(np.percentile(lat, 99)), 3),
        "serve_rows_per_sec": round(ok_rows[0] / max(wall, 1e-9), 1),
        "serve_shed_count": sheds[0],
        "serve_timeout_count": timeouts[0],
        "serve_requests_ok": len(lat_ms),
        "serve_requests_total": int(len(sizes)),
        "serve_late_starts": late[0],
        "serve_wall_s": round(wall, 3),
        "errors": errors[:5],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=20.0,
                    help="seconds of open-loop load")
    ap.add_argument("--rps", type=float, default=200.0,
                    help="open-loop request arrival rate")
    ap.add_argument("--clients", type=int, default=16,
                    help="client threads submitting the arrival schedule")
    ap.add_argument("--train-rows", type=int, default=20_000)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--num-leaves", type=int, default=63)
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--flush-ms", type=float, default=2.0)
    ap.add_argument("--max-queue-rows", type=int, default=65536)
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline (0 = none)")
    ap.add_argument("--large-frac", type=float, default=0.2)
    ap.add_argument("--large-sizes", type=int, nargs="+",
                    default=[256, 512])
    ap.add_argument("--cpu", action="store_true", help="force CPU backend")
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke knobs: tiny model, ~3 s of load, CPU")
    args = ap.parse_args()
    if args.fast:
        args.cpu = True
        args.duration = min(args.duration, 3.0)
        args.rps = min(args.rps, 120.0)
        args.train_rows = min(args.train_rows, 3000)
        args.features = min(args.features, 10)
        args.num_leaves = min(args.num_leaves, 15)
        args.rounds = min(args.rounds, 8)
        args.clients = min(args.clients, 8)
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    backend = jax.devices()[0].platform
    print(f"# device: {jax.devices()[0]}", file=sys.stderr)
    from lightgbm_tpu import compile_cache
    compile_cache.configure(cache_dir=compile_cache.default_dir())

    t_build = time.time()
    booster, X = build_model(args)
    print(f"# model trained in {time.time() - t_build:.1f}s",
          file=sys.stderr)

    from lightgbm_tpu import distributed
    from lightgbm_tpu.serving import ServeFrontend
    fe = ServeFrontend(booster, flush_ms=args.flush_ms,
                       max_queue_rows=args.max_queue_rows)
    try:
        # warm every bucket the mix can hit OUTSIDE the measured window
        # (compiles are a cold-start cost, not a steady-state latency)
        for rows in sorted({1, 8, *args.large_sizes}):
            fe.predict(X[:rows])
        n_requests = max(int(args.duration * args.rps), 1)
        sizes = request_mix(args, n_requests)
        print(f"# open-loop load: {n_requests} requests @ {args.rps:g}/s "
              f"({args.clients} clients, flush {args.flush_ms:g} ms)",
              file=sys.stderr)
        result = run_load(fe, X, sizes, args)
        st = fe.stats()
    finally:
        fe.close()

    batches = max(st["batches"], 1)
    result.update({
        "metric": "serve_bench",
        "backend": backend,
        "train_rows": args.train_rows,
        "features": args.features,
        "num_leaves": args.num_leaves,
        "rounds": args.rounds,
        "rps_target": args.rps,
        "serve_flush_ms": args.flush_ms,
        "serve_deadline_ms": args.deadline_ms,
        "serve_batches": st["batches"],
        "serve_coalesce_ratio": round(st["requests"] / batches, 2),
        "health": distributed.health_snapshot().get("serve"),
    })
    print(json.dumps(result), flush=True)
    if result["errors"]:
        print(f"# FAIL: unexpected request errors: {result['errors']}",
              file=sys.stderr)
        return 1
    if result["serve_requests_ok"] == 0:
        print("# FAIL: no request completed", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
