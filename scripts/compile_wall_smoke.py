"""Compile-wall CI smoke: cold-then-warm two-process drill.

A CPU tool: it starts child processes, and a chip belongs to one process
at a time (on the chip the warm side is a second `benchmarks/run.py`
command in the same compile cache, after the first has exited).

Phase 1 (cold process): train a K=4-blocks-per-dispatch booster against
a fresh persistent compile cache + checkpoint dir — every fused program
is an XLA compile (cache miss) that lands on disk.

Phase 2 (warm process): a NEW process resumes the same training from the
checkpoint against the same cache — the restore-time AOT warmup and the
first K-block must be pure cache DESERIALIZATIONS: zero fused-step XLA
compiles, and the continued model must be bit-identical to an
uninterrupted single-process run.

This is the supervisor-relaunch / elastic-gang warm path reduced to its
smallest reproducible shape: the persistent cache works on the CPU
backend (where cross-process XLA collectives don't — the same reason
the gang tests run replicated-serial), so CI proves the cold -> warm
transition on every container.
"""

import json
import os
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ROUNDS_COLD = 4
ROUNDS_FULL = 8
K = 4

_CHILD = r"""
import json, os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, %(repo)r)
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu import callback as callback_mod
from lightgbm_tpu import compile_cache

cfg = json.loads(sys.argv[1])
rng = np.random.RandomState(3)
X = rng.normal(size=(2000, 8)).astype(np.float32)
y = (X[:, 0] + 0.4 * X[:, 1] + rng.normal(size=2000) * 0.3 > 0)
y = y.astype(np.float32)
p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 10,
     "verbosity": -1, "boost_rounds_per_dispatch": cfg["K"],
     "compile_cache_dir": cfg["cache_dir"]}
cbs = [callback_mod.checkpoint(cfg["ckpt_dir"], period=cfg["K"])] \
    if cfg["ckpt_dir"] else []
t0 = time.time()
b = lgb.train(p, lgb.Dataset(X, label=y, params=p), cfg["rounds"],
              callbacks=cbs,
              resume_from=cfg["ckpt_dir"] if cfg["resume"] else None)
json.dump({
    "wall_s": round(time.time() - t0, 3),
    "iter": b._boosting.iter,
    "model": b.model_to_string(),
    "fused_misses": compile_cache.module_count("misses", "jit(_fused"),
    "fused_hits": compile_cache.module_count("hits", "jit(_fused"),
}, open(cfg["out"], "w"))
""" % {"repo": REPO}


def run_child(cfg):
    r = subprocess.run([sys.executable, "-c", _CHILD, json.dumps(cfg)],
                       capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        print(r.stderr[-3000:], file=sys.stderr)
        raise SystemExit(f"child failed (rc={r.returncode})")
    with open(cfg["out"]) as fh:
        return json.load(fh)


def strip(model_text):
    drop = ("[boost_rounds_per_dispatch", "[compile_cache_dir")
    return "\n".join(l for l in model_text.splitlines()
                     if not l.startswith(drop))


def main():
    t0 = time.time()
    with tempfile.TemporaryDirectory() as tmp:
        cache = os.path.join(tmp, "cache")
        ckpt = os.path.join(tmp, "ckpt")
        print(f"# cold process: {ROUNDS_COLD} rounds, K={K}, fresh cache")
        cold = run_child({"K": K, "cache_dir": cache, "ckpt_dir": ckpt,
                          "rounds": ROUNDS_COLD, "resume": False,
                          "out": os.path.join(tmp, "cold.json")})
        assert cold["iter"] == ROUNDS_COLD, cold
        assert cold["fused_misses"] >= 1, \
            f"cold run should MISS (and fill) the cache: {cold}"
        print(f"#   wall {cold['wall_s']}s, fused misses "
              f"{cold['fused_misses']} (cache filled)")

        print(f"# warm process: resume -> {ROUNDS_FULL} rounds, same cache")
        warm = run_child({"K": K, "cache_dir": cache, "ckpt_dir": ckpt,
                          "rounds": ROUNDS_FULL, "resume": True,
                          "out": os.path.join(tmp, "warm.json")})
        assert warm["iter"] == ROUNDS_FULL, warm
        assert warm["fused_misses"] == 0, \
            f"warm incarnation recompiled the fused step: {warm}"
        assert warm["fused_hits"] >= 1, warm
        print(f"#   wall {warm['wall_s']}s, fused misses 0, "
              f"fused hits {warm['fused_hits']} (started hot)")

        print("# reference: uninterrupted single process, no cache")
        full = run_child({"K": K, "cache_dir": os.path.join(tmp, "c2"),
                          "ckpt_dir": "", "rounds": ROUNDS_FULL,
                          "resume": False,
                          "out": os.path.join(tmp, "full.json")})
        assert strip(warm["model"]) == strip(full["model"]), \
            "warm continuation diverged from the uninterrupted run"
        print("#   warm continuation BIT-IDENTICAL to uninterrupted run")
    print(f"compile_wall_smoke: PASS ({time.time() - t0:.0f}s)")


if __name__ == "__main__":
    main()
