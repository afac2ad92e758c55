"""Run ONE benchmark cell once, in this process, and print its result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything that belongs to one cell, configuration, job, data generator,
layer metric or reader is a file of its own under this directory, found by
name (see README.md); this file only wires them together:

    workloads/<cell>.json -> configs/<config>.json, jobs/<job>.py,
    data/<generator>.py; layer_metrics/<metric>.json -> readers/<kind>.py

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (from a short traced sub-window) and a ``breakdown``.
Without a TPU, or with another number of chips than the cell asks for, the
process exits non-zero and prints no result line. ``--rehearse`` is the one
exception: it holds jax to the CPU, interprets the kernels and names
``cpu`` as its device, to prove paths and arguments before a chip call.
"""

import time

T_PROCESS_START = time.time()

import argparse          # noqa: E402
import contextlib        # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

import trace_reduce      # noqa: E402


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmarks/<kind>/<name>.py, by name."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Tracer:
    """The profiler around a short sub-window. The Python tracer is off:
    it records every call of every frame, and the benchmark's own
    annotations name what the host was doing."""

    def __init__(self, directory: str):
        self.directory = directory

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()


class Context:
    """What a job and the readers share."""

    def __init__(self, cell, cfg, seed, overrides, peaks):
        self.cell, self.cfg = cell, cfg
        self.seed, self.overrides, self.peaks = seed, overrides, peaks
        self.data = load_module("data", cfg["data"]["generator"])
        self.phases = {}        # name -> seconds, host clock
        self.counters = {}      # name -> count over the window
        self.units = 0          # iterations or calls in the window
        self.work = {}          # what kernel_ops functions read
        self.view = None        # TraceView of a traced run
        self.memory = {}        # phase -> device peak bytes so far

    def log(self, msg: str) -> None:
        print(msg, flush=True)

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.time()
        yield
        self.phases[name] = time.time() - t0
        self.memory[name] = device_memory()["peak"]


def device_memory() -> dict:
    """memory_stats() of the fullest chip, with ``peak``: the most of its
    memory that was ever taken. On the TPU a loaded program's temporary
    buffers are RESERVED, not counted "in use" (the Higgs step reserves
    10 GB beside 0.9 GB of live arrays, and what is reserved is not free:
    ``bytes_reservable_limit - bytes_reserved == largest_free_block_bytes``),
    so the peak is the larger of the two high-water marks."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()]
    for s in stats:
        s["peak"] = max(s.get("peak_bytes_in_use", 0),
                        s.get("peak_bytes_reserved", 0))
    return max(stats, key=lambda s: s["peak"])


def expected_metrics(cell_name: str, section: str):
    """Names BENCHMARK.json lists for this cell in ``section``, or None for
    a cell it does not list (a rehearsal cell: report what there is)."""
    path = os.path.join(ROOT, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    if cell_name not in [w["name"] for w in bench["workloads"]]:
        return None
    return [m["name"] for m in bench[section]
            if cell_name in m.get("workloads", [cell_name])]


def layer_metrics(ctx) -> dict:
    """Every layer_metrics/<name>.json whose reader finds something."""
    out = {}
    for fn in sorted(os.listdir(os.path.join(HERE, "layer_metrics"))):
        if not fn.endswith(".json"):
            continue
        spec = load_json("layer_metrics", fn)
        if ctx.cell["job"] not in spec["jobs"]:
            continue
        reader = load_module("readers", spec["reader"]["kind"])
        value = reader.read(spec["reader"], ctx)
        if value is not None:
            out[fn[:-len(".json")]] = {"value": float(value),
                                       "unit": spec["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: kernels interpreted, device 'cpu'")
    ap.add_argument("--describe-trace", metavar="FILE",
                    help="with --trace 1: write what the trace holds here")
    args = ap.parse_args(argv)

    cell = load_json("workloads", args.workload + ".json")
    cfg = load_json("configs", cell["config"] + ".json")
    chips = int(cell["chips"])
    section = "per_layer" if args.trace else "end_to_end"
    expected = expected_metrics(args.workload, section)
    if expected is None and not args.rehearse:
        print(f"run.py: {args.workload!r} is not a cell of BENCHMARK.json "
              f"(rehearsal cells need --rehearse)", file=sys.stderr)
        return 1
    overrides = {}
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")
        overrides = {"hist_pallas_interpret": True}

    import jax
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        print(f"run.py: no TPU (jax found {dev.platform!r}); there is no "
              f"CPU fallback", file=sys.stderr)
        return 1
    if len(devices) != chips:
        print(f"run.py: cell {args.workload!r} needs {chips} chips, jax "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    peaks = None
    if not args.rehearse:
        table = load_json("peaks.json")
        if dev.device_kind not in table:
            print(f"run.py: no published peaks for device kind "
                  f"{dev.device_kind!r} in benchmarks/peaks.json",
                  file=sys.stderr)
            return 1
        peaks = table[dev.device_kind]

    from lightgbm_tpu import compile_cache
    from lightgbm_tpu.utils import profiling
    cache = compile_cache.configure(cache_dir=compile_cache.default_dir())
    if args.trace:
        # counting dispatches takes jax's C++ fast path away, so only a
        # traced run pays for it; before anything compiles (it clears the
        # jit caches)
        profiling.install_dispatch_hook()
    ctx = Context(cell, cfg, args.seed, overrides, peaks)
    ctx.log(f"device: platform={dev.platform} kind={dev.device_kind} "
            f"count={len(devices)} jax={jax.__version__} "
            f"compile_cache={cache} cell={args.workload} seed={args.seed} "
            f"seconds={args.seconds} trace={args.trace}")

    job = load_module("jobs", cell["job"])
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        state = job.setup(ctx)
        res = job.window(ctx, state, args.seconds,
                         Tracer(trace_dir) if args.trace else None)
        setup_s = res["t_open"] - T_PROCESS_START
        if args.trace:
            xplane = trace_reduce.find_xplane(trace_dir)
            if args.describe_trace:
                os.makedirs(os.path.dirname(os.path.abspath(
                    args.describe_trace)), exist_ok=True)
                with open(args.describe_trace, "w") as f:
                    f.write(trace_reduce.describe(xplane))
            trace = trace_reduce.load(xplane)
            if trace.devices:
                ctx.view = trace_reduce.TraceView(trace)
        reasons = job.check(ctx, state)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": device_memory()["peak"]}
    out = {"correct": not reasons, "attempted": res["attempted"],
           "failed": res["failed"]}
    if args.trace:
        if ctx.view is None:
            raise RuntimeError("the traced window holds no device operation")
        metrics = layer_metrics(ctx)
        device["busy_s"] = ctx.view.busy_s
        device["window_s"] = ctx.view.window_s
        out["breakdown"] = ctx.view.breakdown()
    else:
        units = cell["end_to_end_units"]
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        metrics.update({k: {"value": float(v), "unit": units[k]}
                        for k, v in res["metrics"].items()})
    if expected is not None:
        missing = [m for m in expected if m not in metrics]
        if missing and not args.trace:
            raise RuntimeError(f"cell reports no {missing}")
        metrics = {k: v for k, v in metrics.items() if k in expected}
    out["metrics"], out["device"] = metrics, device
    t = compile_cache.totals()
    ctx.log(f"phases: { {k: round(v, 3) for k, v in ctx.phases.items()} } "
            f"setup_s={setup_s:.3f} total={time.time() - T_PROCESS_START:.1f}"
            f" s compile_requests={t['requests']} cache_hits={t['hits']} "
            f"written={t['misses']}")
    ctx.log(f"device_peak_bytes_after_phase: {ctx.memory} "
            f"memory_stats: {device_memory()}")
    for r in reasons:
        ctx.log(f"NOT CORRECT: {r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
