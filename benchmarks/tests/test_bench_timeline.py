"""The timeline reader: on a hand-made timeline for every ``stat``, without
a timeline, in the four CPU rehearsals, and under the parent commit's
library. Run by hand: JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)

TWELVE = ["setup_import_s", "setup_construct_s", "setup_find_bins_s",
          "setup_to_float_s", "setup_bin_rows_s", "setup_plan_s",
          "setup_step_build_s", "setup_compile_misses", "setup_loop_s",
          "setup_unspanned_s", "host_dispatch_s_per_iter",
          "host_unspanned_s_per_iter"]
PARTS = ["setup_import_s", "setup_construct_s", "setup_plan_s",
         "setup_step_build_s", "setup_loop_s", "setup_unspanned_s"]
S = 10**9


def _reader():
    spec = importlib.util.spec_from_file_location(
        "reader_timeline", os.path.join(HERE, "readers", "timeline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


tl = _reader()


def _sp(i, name, a, b, parent=None, thread=1, **attrs):
    return {"id": i, "parent": parent, "name": name, "t0_ns": a * S,
            "t1_ns": b * S, "thread": thread, "attrs": attrs}


# process start 100; import 101-103; construct 104-112 with to_float,
# find_bins, bin_rows around a shard_place, and the quantiser's compile;
# plan 113-114; three iterations at 120 / 130 / 134 (one warm-up, two in
# the window), the first with a lazy plan and the step's three stages;
# then a probe iteration of another thread's booster that must not count
SPANS = [
    _sp(1, "import", 101, 103),
    _sp(3, "to_float", 104, 106, parent=2),
    _sp(4, "find_bins", 106, 107, parent=2),
    _sp(7, "compile", 108, 109, parent=6, program="jit(bin)",
        stage="backend", outcome="hit"),
    _sp(6, "shard_place", 107, 111, parent=5),
    _sp(5, "bin_rows", 107, 112, parent=2),
    _sp(2, "construct", 104, 112),
    _sp(8, "plan", 113, 114),
    _sp(10, "plan", 120, 121, parent=9),
    _sp(11, "compile", 121, 122, parent=9, program="jit(_fused_step)",
        stage="trace", outcome=None),
    _sp(12, "compile", 122, 123, parent=9, program="jit(_fused_step)",
        stage="lower", outcome=None),
    _sp(13, "compile", 123, 126, parent=9, program="jit(_fused_step)",
        stage="backend", outcome="miss"),
    _sp(9, "fused_dispatch", 120, 127),
    _sp(14, "score_dispatch", 127, 128),
    _sp(15, "compile", 127, 128, parent=14, program="jit(add)",
        stage="backend", outcome=None),
    _sp(16, "callbacks", 128, 129),
    _sp(17, "fused_dispatch", 130, 131),
    _sp(18, "callbacks", 132, 133),
    _sp(19, "fused_dispatch", 134, 135),
    _sp(20, "score_dispatch", 135, 136),
    _sp(21, "callbacks", 137, 139),
    _sp(22, "fused_dispatch", 150, 160, thread=2),
]
TIMELINE = {"process_start_ns": 100 * S, "clock_offset_ns": 0,
            "setup": SPANS[:8], "ring": SPANS[8:]}


@pytest.fixture
def ctx(monkeypatch):
    monkeypatch.setattr(tl, "_timeline", lambda: TIMELINE)
    return types.SimpleNamespace(cell={"warmup_iterations": 1}, units=2)


def _read(ctx, scope, stat, spans, **more):
    return tl.read({"kind": "timeline", "scope": scope, "stat": stat,
                    "spans": spans, **more}, ctx)


def test_owned_seconds_partition_the_setup(ctx):
    # the scope ends with the callbacks after the warm-up iteration: 129
    own = {n: _read(ctx, "setup", "owned_s", [n])
           for n in ("import", "construct", "plan", "compile", "loop",
                     "unspanned")}
    assert own == {"import": 2.0,
                   "construct": 7.0,      # 8 less the quantiser's compile
                   "plan": 2.0,           # the lazy one is not the loop's
                   "compile": 7.0,        # 1 + 5 + 1 (the score add's)
                   "loop": 2.0,           # 120-129 less plan and compile
                   "unspanned": 9.0}      # 100-101, 103-104, 112-113, 114-120
    assert sum(own.values()) == 29.0
    assert _read(ctx, "setup", "owned_s", ["loop", "unspanned"]) == 11.0


def test_total_seconds_count_an_instant_once(ctx):
    assert _read(ctx, "setup", "total_s", ["to_float"]) == 2.0
    # shard_place lies inside bin_rows and adds nothing
    assert _read(ctx, "setup", "total_s",
                 ["bin_rows", "efb_place", "shard_place"]) == 5.0
    assert _read(ctx, "setup", "total_s", ["find_bins",
                                           "efb_fit_mappers"]) == 1.0
    assert _read(ctx, "setup", "total_s", ["efb_place"]) == 0.0


def test_count_and_where(ctx):
    misses = {"stage": "backend", "outcome": ["miss", None]}
    assert _read(ctx, "setup", "count", ["compile"], where=misses) == 2.0
    assert _read(ctx, "setup", "count", ["compile"],
                 where={"stage": "backend"}) == 3.0
    assert _read(ctx, "setup", "count", ["compile"],
                 where={"outcome": "hit"}) == 1.0
    assert _read(ctx, "window", "count", ["compile"]) == 0.0


def test_window_stats_are_an_iterations(ctx):
    # the window: 130 to the end of the last callbacks, 139; two iterations
    assert _read(ctx, "window", "self_s",
                 ["fused_dispatch", "score_dispatch"]) == (1 + 1 + 1) / 2
    assert _read(ctx, "window", "total_s", ["*"]) == (1 + 1 + 1 + 1 + 2) / 2
    assert _read(ctx, "window", "total_s", ["*"], invert=True) \
        == (9 - 6) / 2
    # self seconds leave the children out
    assert _read(ctx, "setup", "self_s", ["fused_dispatch"]) == 7 - 6
    with pytest.raises(ValueError):
        _read(ctx, "window", "median", ["plan"])


def test_nothing_without_a_timeline_or_enough_iterations(monkeypatch):
    cell = {"warmup_iterations": 1}
    spec = {"kind": "timeline", "scope": "setup", "stat": "owned_s",
            "spans": ["plan"]}
    monkeypatch.setattr(tl, "_timeline", lambda: None)
    assert tl.read(spec, types.SimpleNamespace(cell=cell, units=2)) is None
    monkeypatch.setattr(tl, "_timeline", lambda: TIMELINE)
    few = types.SimpleNamespace(cell={"warmup_iterations": 4}, units=2)
    assert tl.read(spec, few) is None
    assert tl.read(dict(spec, scope="window"),
                   types.SimpleNamespace(cell=cell, units=3)) is None
    monkeypatch.setattr(tl, "_timeline",
                        lambda: dict(TIMELINE, process_start_ns=None))
    assert tl.read(spec, types.SimpleNamespace(cell=cell, units=2)) is None
    assert tl.read(dict(spec, scope="window"),
                   types.SimpleNamespace(cell=cell, units=2)) == 0.0


def test_the_files_and_benchmark_json_list_the_twelve_for_all_four():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = [w["name"] for w in bench["workloads"]]
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-12:] == TWELVE
    for name in TWELVE:
        spec = json.load(open(os.path.join(HERE, "layer_metrics",
                                           name + ".json")))
        assert spec["jobs"] == ["train", "rank_train", "sparse_train",
                                "dp_train"]
        assert spec["reader"]["kind"] == "timeline"
        entry = listed[name]
        assert entry["workloads"] == cells
        assert {k: entry[k] for k in ("unit", "better", "source", "layer",
                                      "moves")} \
            == {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")}


def _rehearse(cell: str, root: str = ROOT):
    res = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "2147483659", "--seconds", "2",
         "--trace", "1", "--rehearse"], cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=1500)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "Traceback" not in res.stderr, res.stderr[-2000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    setup_s = float(res.stdout.split("setup_s=")[1].split()[0])
    return line, setup_s


@pytest.mark.parametrize("cell", ["tiny.train", "tiny-rank.train",
                                  "tiny-expo.train",
                                  "tiny-criteo-dp4.train"])
def test_a_traced_rehearsal_prints_the_twelve(cell):
    line, setup_s = _rehearse(cell)
    assert line["correct"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(TWELVE) <= set(m)
    assert all(m[k] >= 0 for k in TWELVE)
    # the partition is the run's own setup_s, plus the interpreter's
    # start before run.py's first line
    assert 0 <= sum(m[k] for k in PARTS) - setup_s < 0.3
    assert m["setup_step_build_s"] > 0 and m["setup_loop_s"] > 0
    assert 0 < m["host_dispatch_s_per_iter"]
    assert m["host_unspanned_s_per_iter"] < 0.05


def test_the_parent_under_these_files_prints_none_and_no_error(tmp_path):
    """The parent commit's library has no ``profiling.timeline``: with this
    PR's benchmark files laid over it, a traced run prints what it printed
    before. Needs the repo's git history."""
    parent = "abed2376b8de6c59165ccdeb351e89bf3eced8af"
    root = str(tmp_path / "parent")
    os.makedirs(root)
    tar = subprocess.run(["git", "-C", ROOT, "archive", parent],
                         capture_output=True)
    if tar.returncode != 0:
        pytest.skip("the parent commit is not in this checkout's history")
    subprocess.run(["tar", "-x", "-C", root], input=tar.stdout, check=True)
    subprocess.run(["cp", "-r", os.path.join(ROOT, "benchmarks") + "/.",
                    os.path.join(root, "benchmarks")], check=True)
    subprocess.run(["cp", os.path.join(ROOT, "BENCHMARK.json"), root],
                   check=True)
    line, _setup_s = _rehearse("tiny.train", root)
    assert line["correct"]
    assert not set(TWELVE) & set(line["metrics"])
    assert "construct_s" in line["metrics"]
