"""Run one cell several times, one process after the other, and print each
metric's spread by the rule a bound is set from.

    python3 benchmarks/measure_bounds.py --workload higgs.train --sets 2 --runs 6

Two sets with the same seeds; per metric and set the spread is the distance
between the first and third quartile (``statistics.quantiles(v, n=4)``) as
a share of the median; a bound is about five times the widest spread, never
under 1%. This process never touches jax (a chip belongs to one process).
Every run's output goes to ``--out``/<cell>.<set>.<run>.txt.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values):
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--first-seed", type=int, default=2147483659)
    ap.add_argument("--out", default="chiprun_out")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    sets = []
    for s in range(args.sets):
        runs = []
        for r in range(args.runs):
            seed = args.first_seed + 7919 * r
            path = os.path.join(args.out, f"{args.workload}.{s}.{r}.txt")
            with open(path, "w") as f:
                rc = subprocess.run(
                    [sys.executable, os.path.join(HERE, "run.py"),
                     "--workload", args.workload, "--seed", str(seed),
                     "--seconds", str(args.seconds), "--trace", "0"],
                    stdout=f, stderr=subprocess.STDOUT).returncode
            with open(path) as f:
                last = f.read().strip().splitlines()[-1]
            if rc != 0:
                print(f"set {s} run {r} seed {seed}: rc={rc}: {last[:300]}")
                return 1
            res = json.loads(last)
            runs.append(res)
            print(f"set {s} run {r} seed {seed} correct={res['correct']} "
                  + " ".join(f"{k}={v['value']:.6g}"
                             for k, v in res["metrics"].items())
                  + f" peak={res['device']['memory_peak_bytes']}", flush=True)
        sets.append(runs)
    for name in sets[0][0]["metrics"]:
        line = []
        for s, runs in enumerate(sets):
            vals = [r["metrics"][name]["value"] for r in runs]
            if name == "setup_s":
                vals = vals[1:] if s == 0 else vals   # the first run compiles
            line.append(f"set{s}: median={statistics.median(vals):.6g} "
                        f"spread={100 * spread(vals):.3f}%")
        print(f"{name}: " + "  ".join(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
