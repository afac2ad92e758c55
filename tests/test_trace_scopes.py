"""The program's own tracing: device scopes (``jax.named_scope`` names of
``profiling.SCOPES`` inside the compiled programs), the scope table a
device trace is read against, host spans on the profiler's clock and the
compile-stage seconds. CPU, small shapes, kernels interpreted."""

import contextlib
import glob
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import compile_cache, telemetry
from lightgbm_tpu.utils import profiling

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERIAL_SCOPES = ("gradients", "tile_select", "rung_gather", "hist_pass",
                 "split_search", "apply_split", "finalize_tree",
                 "score_update")
# the ladder and the fused epilogue on, kernels interpreted: the default
# path of the chip at a size the CPU compiles in seconds
SERIAL = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "min_data_in_leaf": 5, "histogram_method": "pallas_hilo",
          "hist_pallas_interpret": True, "hist_compaction": True,
          "split_fusion": "on"}


def _data(n=4000, f=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1]
         + 0.3 * rng.standard_normal(n) > 0).astype(np.float32)
    return X, y


def _instructions(text):
    """[(name, opcode)] of a compiled program's text."""
    out = []
    for line in text.splitlines():
        m = telemetry._HLO_INSTR_RE.match(line)
        if m:
            body = telemetry._HLO_LAYOUT_RE.sub("", m.group(3))
            op = re.search(r"\s([a-z][a-z\-]*)\(", " " + body)
            out.append((m.group(2), op.group(1) if op else "?"))
    return out


@pytest.fixture(scope="module")
def serial():
    """(booster, compiled text of its fused step, its scope table)."""
    compile_cache.install_compile_hook()
    X, y = _data()
    b = lgb.train(SERIAL, lgb.Dataset(X, label=y, params=SERIAL), 2,
                  keep_training_booster=True)
    gb = b._boosting
    assert gb._serial_grow_statics(gb._hist_method())["compaction_ladder"]
    (step, bind), = gb._fused_cache.values()
    text = step.lower(*gb._fused_call_args(None, bind)).compile().as_text()
    return b, text, telemetry.scope_table()


# --------------------------------------------------------- device scopes
def test_every_named_scope_literal_is_in_SCOPES():
    used = set()
    for path in glob.glob(os.path.join(REPO, "lightgbm_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            src = f.read()
        names = re.findall(r"named_scope\(\s*([^)]*)\)", src)
        for arg in names:
            lit = re.fullmatch(r"[\"']([^\"']+)[\"']", arg.strip())
            assert lit, f"{path}: named_scope({arg}) is not a literal"
            used.add(lit.group(1))
    assert used <= set(profiling.SCOPES), used - set(profiling.SCOPES)
    assert used == set(profiling.SCOPES), set(profiling.SCOPES) - used
    assert len(set(profiling.SCOPES)) == len(profiling.SCOPES)


@pytest.mark.parametrize("scope", SERIAL_SCOPES)
def test_serial_step_names_scope(serial, scope):
    _b, text, table = serial
    assert re.search(rf'op_name="[^"]*/{scope}/', text), \
        f"no instruction of the compiled fused step sits in {scope!r}"
    assert scope in set(table["jit__fused_step"].values())


@pytest.mark.parametrize("scope", ("rank_sort", "rank_pairs",
                                   "rank_scatter"))
def test_lambdarank_step_names_scope(scope):
    """The ranking objective's three stages are in the compiled fused step
    and in the scope table, nested under ``gradients`` (the innermost
    scope names the instruction)."""
    rng = np.random.default_rng(3)
    sizes = rng.integers(2, 90, size=60)
    n = int(sizes.sum())
    X = rng.standard_normal((n, 6)).astype(np.float32)
    y = np.clip(np.round(X[:, 0] + 1.5), 0, 4).astype(np.float32)
    p = {"objective": "lambdarank", "num_leaves": 7, "verbosity": -1,
         "min_data_in_leaf": 5}
    b = lgb.train(p, lgb.Dataset(X, label=y, group=sizes, params=p), 1,
                  keep_training_booster=True)
    gb = b._boosting
    (step, bind), = gb._fused_cache.values()
    text = step.lower(*gb._fused_call_args(None, bind)).compile().as_text()
    assert re.search(rf'op_name="[^"]*/gradients/[^"]*{scope}/', text), \
        f"no instruction of the compiled fused step sits in {scope!r}"
    assert scope in set(telemetry.scope_table()["jit__fused_step"].values())


def test_score_add_program_is_one_scope(serial):
    table = serial[2]["jit__apply_score_delta"]
    assert set(table.values()) == {"score_update", None}
    assert "score_update" in table.values()


@pytest.mark.parametrize("scope", ("hist_allreduce", "split_sync"))
def test_data_parallel_step_names_collective(scope):
    import jax
    assert len(jax.devices()) >= 4
    X, y = _data(n=1600, f=8, seed=1)
    p = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
         "min_data_in_leaf": 5, "tree_learner": "data"}
    b = lgb.train(p, lgb.Dataset(X, label=y, params=p), 1,
                  keep_training_booster=True)
    gb = b._boosting
    assert gb._parallel_grower is not None and gb._parallel_grower.ndev >= 4
    (step, bind), = gb._fused_cache.values()
    text = step.lower(*gb._fused_call_args(None, bind)).compile().as_text()
    hits = [ln for ln in text.splitlines() if f"/{scope}/" in ln]
    assert hits, f"no instruction in {scope!r}"
    collective = re.compile(r"\b(all-reduce|reduce-scatter|all-gather|"
                            r"all-to-all|collective-permute)")
    assert any(collective.search(ln) for ln in hits), \
        f"{scope!r} holds no collective"


def test_scope_table_covers_the_step(serial):
    _b, text, table = serial
    step = table["jit__fused_step"]
    timed = [n for n, op in _instructions(text)
             if op not in ("parameter", "constant")]
    assert len(timed) > 1000
    assert set(timed) <= set(step)
    scoped = sum(step[n] is not None for n in timed)
    assert scoped / len(timed) >= 0.90, (scoped, len(timed))


def test_valid_score_update_is_in_the_table():
    X, y = _data(n=1200, seed=2)
    p = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
    train = lgb.Dataset(X[:900], label=y[:900], params=p)
    valid = lgb.Dataset(X[900:], label=y[900:], reference=train)
    b = lgb.train(p, train, 2, valid_sets=[valid],
                  keep_training_booster=True)
    table = telemetry.scope_table()["jit__apply_valid_tree"]
    assert {"predict_traverse", "score_update"} <= set(table.values())
    assert len(b._boosting._valid_programs_registered) == 1


def test_each_predict_engine_registers_its_programs():
    """Two boosters of one shape share the jit entry; the table holds the
    program for as long as either engine lives."""
    import gc
    X, y = _data(n=1000, seed=4)
    p = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
    first, second = (lgb.train(p, lgb.Dataset(X, label=y, params=p), 2)
                     for _ in range(2))
    first.predict(X)
    assert "predict_traverse" in set(
        telemetry.scope_table()["jit__accum_core"].values())
    del first
    gc.collect()
    assert "jit__accum_core" not in telemetry.scope_table()
    second.predict(X)
    second.predict(X, pred_leaf=True)
    table = telemetry.scope_table()
    for module in ("jit__accum_core", "jit__leaves_core"):
        assert "predict_traverse" in set(table[module].values()), module


def test_scope_of_takes_the_innermost():
    assert telemetry.scope_of(
        "jit(_fused_step)/jit(grow_tree)/while/body/hist_pass/cond/"
        "branch_1_fun/rung_gather/jit(_take)/gather") == "rung_gather"
    assert telemetry.scope_of("jit(f)/while/body/add") is None
    assert telemetry.scope_of("jit(f)/gradients_extra/mul") is None


HLO = """HloModule jit__demo, is_scheduled=true

%fused_a (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %m = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(demo)/gradients/mul"}
  ROOT %n = f32[8]{0} negate(%m)
}

%fused_b (p1: f32[8]) -> f32[8] {
  %p1 = f32[8]{0} parameter(0)
  %s = f32[8]{0} sine(%p1), metadata={op_name="jit(demo)/split_search/sin"}
  %c = f32[8]{0} cosine(%s), metadata={op_name="jit(demo)/split_search/cos"}
  ROOT %e = f32[8]{0} exponential(%c), metadata={op_name="jit(demo)/apply_split/exp"}
}

ENTRY %main (x: f32[8]) -> (f32[8], f32[4,2]) {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0:T(1024)} fusion(%x), kind=kLoop, calls=%fused_a
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_b
  %copy.3 = f32[8]{0} copy(%fusion.2)
  %gather.4 = f32[8]{0} gather(%copy.3), metadata={op_name="jit(demo)/while/body/rung_gather/gather"}
  %abs.5 = f32[8]{0} abs(%gather.4), metadata={op_name="jit(demo)/abs"}
  %reshape.6 = f32[4,2]{1,0} reshape(%abs.5)
  ROOT %tuple.7 = (f32[8]{0}, f32[4,2]{1,0}) tuple(%gather.4, %reshape.6)
}
"""


def test_parse_hlo_scopes_rules():
    module, table = telemetry.parse_hlo_scopes(HLO)
    assert module == "jit__demo"
    scope = {n: s for n, (s, _shape) in table.items()}
    # own op_name, inner instructions of fused computations included
    assert scope["m"] == "gradients" and scope["gather.4"] == "rung_gather"
    # a fusion without metadata: its root's scope, else the majority
    assert scope["fusion.2"] == "apply_split"
    assert scope["fusion.1"] == "gradients"
    # plumbing takes its users' scope
    assert scope["copy.3"] == "rung_gather"
    # the program's own code outside every scope stays None; what the
    # compiler made out of it has nothing to inherit
    assert scope["abs.5"] is None and scope["reshape.6"] is None
    # shapes: layouts out, tuples whole
    assert table["fusion.1"][1] == "f32[8]"
    assert table["tuple.7"][1] == "(f32[8], f32[4,2])"


# ------------------------------------------- same program, same cache key
_CHILD = r"""
import contextlib, json, os, sys
sys.path.insert(0, {repo!r})
import jax
if sys.argv[1] == "noscope":
    class _Null(contextlib.ContextDecorator):
        def __enter__(self):
            return self
        def __exit__(self, *exc):
            return False
    jax.named_scope = lambda name: _Null()
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu import compile_cache, telemetry
from lightgbm_tpu.utils import log
rng = np.random.default_rng(3)
X = rng.standard_normal((3000, 6)).astype(np.float32)
y = (X[:, 0] - X[:, 2] + 0.3 * rng.standard_normal(3000) > 0).astype(np.float32)
p = dict({params!r}, compile_cache_dir=sys.argv[2])
b = lgb.train(p, lgb.Dataset(X, label=y, params=p), 3,
              keep_training_booster=True)
log.set_verbosity(0)
table = telemetry.scope_table()
print(json.dumps({{
    "model": b.model_to_string(),
    "score": np.asarray(b._boosting.train_score).tobytes().hex(),
    "fused_hits": compile_cache.module_count("hits", "jit(_fused_step)"),
    "fused_misses": compile_cache.module_count("misses", "jit(_fused_step)"),
    "scopes": sorted({{s for t in table.values() for s in t.values() if s}}),
}}))
"""


@pytest.fixture(scope="module")
def children(tmp_path_factory):
    """The same training twice over one persistent cache directory: first
    with ``jax.named_scope`` patched to a null context before the library
    is imported, then as it is."""
    cache = str(tmp_path_factory.mktemp("scope_cache"))
    code = _CHILD.format(repo=REPO, params=SERIAL)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    out = {}
    for mode in ("noscope", "scoped"):
        res = subprocess.run([sys.executable, "-c", code, mode, cache],
                             env=env, capture_output=True, text=True,
                             timeout=600)
        assert res.returncode == 0, res.stderr[-3000:]
        out[mode] = json.loads(res.stdout.strip().splitlines()[-1])
        out[mode]["stderr"] = res.stderr + res.stdout
    out["cache"] = cache
    return out


def test_scopes_change_no_tree_and_no_score(children):
    assert children["noscope"]["scopes"] == []
    assert children["scoped"]["model"] == children["noscope"]["model"]
    assert children["scoped"]["score"] == children["noscope"]["score"]


def test_scopes_leave_the_cache_key_alone(children):
    """A named scope is metadata, and the persistent cache's key leaves
    metadata out: the scoped build is served the entry the scope-less
    build wrote."""
    assert children["noscope"]["fused_misses"] >= 1
    assert children["scoped"]["fused_hits"] >= 1
    assert children["scoped"]["fused_misses"] == 0


def test_scope_table_reports_a_scopeless_cache_entry(children):
    """... so the executable it runs carries no scope: the table is given
    as it is, with one warning that names the directory to clear."""
    assert children["scoped"]["scopes"] == []
    warned = [ln for ln in children["scoped"]["stderr"].splitlines()
              if "scope_table" in ln and "carries no scope" in ln]
    assert len(warned) >= 1 and children["cache"] in warned[0]


# ------------------------------------------------------------ host spans
def test_fused_iteration_spans_once_per_iteration(serial, tmp_path):
    from jax.profiler import ProfileData
    booster = serial[0]
    with telemetry.trace_window(str(tmp_path), iters=2) as tw:
        booster.update()
        booster.update()
    if not tw.ok:
        pytest.skip(f"profiler unavailable: {tw.error}")
    files = [f for f in telemetry.trace_files(str(tmp_path))
             if f.endswith(".xplane.pb")]
    assert files
    counts = {}
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(profiling.SPAN_PREFIX):
                    counts[ev.name] = counts.get(ev.name, 0) + 1
    assert counts.get("lgbm:fused_dispatch") == 2, counts
    assert counts.get("lgbm:score_dispatch") == 2, counts


def test_span_outside_a_session_and_timer_on_top_of_it():
    with profiling.span("nothing_listens"):
        pass
    profiling.reset()
    was = profiling.enabled()
    try:
        profiling.enable(False)
        with profiling.timer("off_scope"):
            pass
        assert "off_scope" not in profiling.scopes()
        profiling.enable(True)
        with profiling.timer("on_scope"):
            pass
        assert profiling.scopes()["on_scope"]["calls"] == 1
    finally:
        profiling.enable(was)
        profiling.reset()
    assert not hasattr(profiling, "print_table")


# -------------------------------------------------- compile-stage seconds
def test_compile_stats_keep_the_seconds(serial):
    stats = compile_cache.compile_stats()
    for key in ("trace_s", "lower_s", "backend_s"):
        assert stats[key]["jit(_fused_step)"] > 0.0, key
    # the counts keep their keys, and the table's own lowering of the
    # step counts in neither
    assert set(compile_cache.totals()) == {"requests", "hits", "misses",
                                           "compiles"}
    telemetry.scope_table()
    assert compile_cache.compile_stats() == stats


# --------------------------------------------------------- memory sample
@pytest.mark.parametrize("stats, reserved, peak_reserved", [
    ({"bytes_in_use": 5, "peak_bytes_in_use": 7, "bytes_reserved": 11,
      "peak_bytes_reserved": 13}, 11, 13),
    ({"bytes_in_use": 5, "peak_bytes_in_use": 7}, None, None),
    (None, None, None),
])
def test_memory_sample_reports_reserved(monkeypatch, stats, reserved,
                                        peak_reserved):
    class Dev:
        def memory_stats(self):
            return stats

    monkeypatch.setattr(profiling, "_mem_device", Dev())
    monkeypatch.setattr(profiling, "_mem_device_ok", None)
    sample = profiling.sample_memory()
    assert sample["hbm_reserved_bytes"] == reserved
    assert sample["hbm_peak_reserved_bytes"] == peak_reserved
    assert sample["hbm_bytes_in_use"] == (5 if stats else None)
    with contextlib.suppress(KeyError):
        assert telemetry.memory_snapshot()["hbm_reserved_bytes"] == reserved


# ------------------------------------------------------ the histogram plan
# last in the file: the dispatch hook clears the jit caches
@pytest.mark.parametrize("learner", ["serial", "data"])
def test_the_histogram_plan_runs_no_device_program(monkeypatch, learner):
    """Method, row block and tile width come from a rule: on what looks
    like a TPU, resolving them neither dispatches nor compiles nor
    transfers anything (a run used to time two kernels against each other
    and four row blocks here)."""
    import jax
    X, y = _data(n=600, f=5)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "tree_learner": learner}
    ds = lgb.Dataset(X, label=y, params=params)
    gb = lgb.Booster(params=params, train_set=ds)._boosting
    compile_cache.install_compile_hook()
    if not profiling.install_dispatch_hook():
        pytest.skip("dispatch hook unavailable on this jax")
    try:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        requests = compile_cache.totals()["requests"]
        with profiling.dispatch_scope() as d:
            hm = gb._hist_method()
            st = (gb._serial_grow_statics(hm) if learner == "serial"
                  else gb._parallel_grow_statics(hm))
        assert hm == st["hist_method"] == "pallas_hilo"
        assert st["hist_block"] > 0 and st["tile_leaves"] > 0
        assert all(v == 0 for v in d.values()), d
        assert compile_cache.totals()["requests"] == requests
    finally:
        profiling.uninstall_dispatch_hook()
