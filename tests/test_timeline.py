"""The process timeline (utils/profiling.py: every host span keeps its own
start and end), the flight recorder's whole-iteration records and
``telemetry.timeline_report()``: what a run without a profiler session
can say about where its time went."""

import gc
import glob
import json
import os
import statistics
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import compile_cache, telemetry
from lightgbm_tpu.utils import profiling

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARAMS = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
          "min_data_in_leaf": 20}


def _data(n=3000, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] + rng.normal(size=n) > 0).astype(np.float32)
    return X, y


def _spans(tl=None):
    tl = tl or profiling.timeline()
    return tl["setup"] + tl["ring"]


@pytest.fixture
def clean():
    profiling.reset()
    yield
    profiling.reset()


# ------------------------------------------------------------ the spans

def test_span_keeps_its_time_outside_a_session(clean):
    before = time.time_ns()
    with profiling.span("outer", iteration=3) as outer:
        with profiling.span("inner"):
            time.sleep(0.002)
    after = time.time_ns()
    inner_rec, outer_rec = _spans()
    assert (inner_rec["name"], outer_rec["name"]) == ("inner", "outer")
    assert outer_rec["parent"] is None
    assert inner_rec["parent"] == outer_rec["id"] == outer.id
    assert outer_rec["thread"] == inner_rec["thread"] \
        == threading.get_ident()
    assert outer_rec["attrs"] == {"iteration": 3}
    assert before <= outer_rec["t0_ns"] <= inner_rec["t0_ns"] \
        < inner_rec["t1_ns"] <= outer_rec["t1_ns"] <= after
    assert inner_rec["t1_ns"] - inner_rec["t0_ns"] >= 2_000_000
    assert outer.seconds == pytest.approx(
        (outer_rec["t1_ns"] - outer_rec["t0_ns"]) * 1e-9)


def test_spans_of_two_threads_do_not_nest(clean):
    def work():
        with profiling.span("other"):
            pass
    with profiling.span("mine"):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    other, mine = _spans()
    assert other["parent"] is None and other["thread"] != mine["thread"]


def test_self_time_is_the_duration_less_the_children(clean):
    with profiling.span("a"):
        with profiling.span("b"):
            time.sleep(0.01)
        with profiling.span("b"):
            time.sleep(0.01)
        time.sleep(0.005)
    spans = _spans()
    dur = {s["id"]: s["t1_ns"] - s["t0_ns"] for s in spans}
    a = next(s for s in spans if s["name"] == "a")
    own = telemetry.self_seconds(spans)
    assert own["b"] == pytest.approx(
        sum(dur[s["id"]] for s in spans if s["name"] == "b") * 1e-9)
    assert own["a"] == pytest.approx(dur[a["id"]] * 1e-9 - own["b"])
    assert 0.005 <= own["a"] < 0.02
    # and the union of the three is the outer span
    assert telemetry.union_ns(spans, a["t0_ns"], a["t1_ns"]) == dur[a["id"]]


def test_a_span_recorded_after_the_fact_adopts_what_it_encloses(clean):
    with profiling.span("dispatch") as d:
        t0 = time.time_ns()
        profiling.record_span("compile", t0, time.time_ns(), program="in")
        profiling.record_span("compile", t0 - 10, time.time_ns(),
                              program="out")
    inner, outer, disp = _spans()
    assert disp["id"] == d.id and outer["parent"] == d.id
    assert inner["parent"] == outer["id"]
    assert outer["attrs"] == {"program": "out"}
    own = telemetry.self_seconds(_spans())
    # the two compiles' self seconds are their union, not their sum
    assert own["compile"] == pytest.approx(
        (outer["t1_ns"] - outer["t0_ns"]) * 1e-9)


def test_the_ring_and_the_setup_list_are_bounded(clean):
    # a full collection would add a "gc" span the test does not open
    gc.disable()
    try:
        for _ in range(profiling.SETUP_SPANS + 10):
            with profiling.span("s"):
                pass
        tl = profiling.timeline()
        assert len(tl["setup"]) == profiling.SETUP_SPANS
        assert len(tl["ring"]) == 10          # the overflow is not lost
        profiling.close_setup()
        for _ in range(profiling.RING_SPANS + 5):
            with profiling.span("r"):
                pass
        tl = profiling.timeline()
    finally:
        gc.enable()
    assert len(tl["setup"]) == profiling.SETUP_SPANS
    assert len(tl["ring"]) == profiling.RING_SPANS
    assert {s["name"] for s in tl["ring"]} == {"r"}
    profiling.reset()
    assert profiling.timeline()["setup"] == profiling.timeline()["ring"] == []
    with profiling.span("again"):
        pass
    assert [s["name"] for s in profiling.timeline()["setup"]] == ["again"]


def test_process_start_is_before_now_and_on_the_spans_clock():
    start = profiling.process_start_ns()
    if start is None:
        pytest.skip("no /proc here")
    assert start == profiling.timeline()["process_start_ns"]
    now = time.time_ns()
    assert now - 3600 * 10**9 < start < now
    assert profiling.timeline()["clock_offset_ns"] == 0


def test_a_full_collection_is_a_gc_span(clean):
    with profiling.span("work") as w:
        gc.collect()
    found = [s for s in _spans() if s["name"] == "gc"]
    assert found and found[0]["parent"] == w.id
    assert "collected" in found[0]["attrs"]
    gc.collect(0)                           # a young collection is none
    assert len([s for s in _spans() if s["name"] == "gc"]) == len(found)


def test_timer_takes_its_seconds_from_its_span(clean):
    was = profiling.enabled()
    profiling.enable(True)
    try:
        with profiling.timer("phase"):
            time.sleep(0.003)
    finally:
        profiling.enable(was)
    rec = next(s for s in _spans() if s["name"] == "phase")
    sc = profiling.scopes()["phase"]
    assert sc["calls"] == 1
    assert sc["total_s"] == pytest.approx(
        (rec["t1_ns"] - rec["t0_ns"]) * 1e-9, abs=1e-12)


def test_a_span_is_on_the_trace_clock(clean, tmp_path):
    """The ``lgbm:`` event of a CPU trace starts where the same span's
    ``t0_ns`` says: the profiler's host plane is on ``time.time_ns()``
    (its events are relative to the ``profile_start_time`` stat of the
    ``Task Environment`` plane), so ``clock_offset_ns`` is 0."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            with profiling.span("clock_probe"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    data = jax.profiler.ProfileData.from_file(path)
    base, events = None, []
    for plane in data.planes:
        if plane.name == "Task Environment":
            base = dict(plane.stats)["profile_start_time"]
        for line in plane.lines:
            events += [e for e in line.events
                       if e.name == "lgbm:clock_probe"]
    recs = [s for s in _spans() if s["name"] == "clock_probe"]
    assert base is not None and len(events) == len(recs) == 3
    for ev, rec in zip(sorted(events, key=lambda e: e.start_ns), recs):
        # the annotation opens just before the span's own clock read
        assert abs(base + ev.start_ns - rec["t0_ns"]) < 1_000_000
        assert abs(ev.duration_ns - (rec["t1_ns"] - rec["t0_ns"])) \
            < 1_000_000


def test_a_span_costs_microseconds(clean):
    cost = []
    for _ in range(3000):
        t = time.perf_counter_ns()
        with profiling.span("cost"):
            pass
        cost.append(time.perf_counter_ns() - t)
    median_us = statistics.median(cost) / 1e3
    print(f"span cost: median {median_us:.2f} us, "
          f"p99 {sorted(cost)[int(0.99 * len(cost))] / 1e3:.2f} us")
    assert median_us < 50


def test_construct_stats_and_spans_share_one_clock(clean):
    sp = pytest.importorskip("scipy.sparse")
    rng = np.random.RandomState(1)
    X = sp.random(2000, 40, density=0.05, format="csr", random_state=rng,
                  dtype=np.float64)
    y = (rng.rand(2000) > 0.5).astype(np.float32)
    ds = lgb.Dataset(X, label=y, params={"verbosity": -1}).construct()
    spans = _spans()
    for stage in ("efb_fit_mappers", "efb_find_bundles", "efb_place",
                  "sparse_extract"):
        total = sum(s["t1_ns"] - s["t0_ns"] for s in spans
                    if s["name"] == stage) * 1e-9
        assert ds.construct_stats[stage + "_s"] == round(total, 6)
    top = next(s for s in spans if s["name"] == "construct")
    assert all(s["parent"] == top["id"] for s in spans
               if s["name"].startswith(("efb_", "sparse_extract")))


# ------------------------------------------- a training run's timeline

_CHILD = r"""
import json, sys
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu import compile_cache, telemetry
from lightgbm_tpu.utils import profiling
compile_cache.install_compile_hook()
rng = np.random.RandomState(0)
X = rng.normal(size=(3000, 6)).astype(np.float32)
y = (X[:, 0] + rng.normal(size=3000) > 0).astype(np.float32)
params = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
out = {}
lgb.train(params, lgb.Dataset(X, label=y), 3)
out["dense"] = {"timeline": profiling.timeline(),
                "report": telemetry.timeline_report()}
profiling.reset()
import scipy.sparse as sp
Xs = sp.csr_matrix(np.where(np.abs(X) > 1.0, X, 0.0))
lgb.train(params, lgb.Dataset(Xs, label=y), 3)
out["sparse"] = {"timeline": profiling.timeline(),
                 "report": telemetry.timeline_report()}
json.dump(out, open(sys.argv[1], "w"))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One new process trains a dense and a scipy CSR set, three rounds
    each, and hands back both timelines and reports: a process of its
    own, because ``import`` happens once."""
    pytest.importorskip("scipy.sparse")
    out = tmp_path_factory.mktemp("timeline") / "runs.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("LIGHTGBM_TPU_TIMETAG", None)
    res = subprocess.run([sys.executable, "-c", _CHILD, str(out)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(out.read_text())


def _children_of(spans, name):
    tops = {s["id"] for s in spans if s["name"] == name}
    return {s["name"] for s in spans if s["parent"] in tops}


def test_a_dense_train_leaves_its_setup_spans(runs):
    spans = _spans(runs["dense"]["timeline"])
    names = [s["name"] for s in spans]
    assert names.count("import") == 1 and names.count("construct") == 1
    assert {"to_float", "find_bins", "bin_rows"} \
        <= _children_of(spans, "construct")
    assert "plan" in names
    first = min((s for s in spans if s["name"] == "import"),
                key=lambda s: s["t0_ns"])
    assert runs["dense"]["timeline"]["process_start_ns"] < first["t0_ns"]


def test_a_sparse_train_leaves_its_setup_spans(runs):
    spans = _spans(runs["sparse"]["timeline"])
    assert {"efb_fit_mappers", "efb_find_bundles", "efb_place",
            "sparse_extract"} <= _children_of(spans, "construct")
    assert "plan" in {s["name"] for s in spans}
    # reset() cleared the first run's spans, "import" among them
    assert "import" not in {s["name"] for s in spans}


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_the_step_compiles_under_the_first_dispatch(runs, kind):
    spans = _spans(runs[kind]["timeline"])
    by_id = {s["id"]: s for s in spans}
    first = min((s for s in spans if s["name"] == "fused_dispatch"),
                key=lambda s: s["t0_ns"])

    def under(s, top):
        while s is not None and s["id"] != top["id"]:
            s = by_id.get(s["parent"])
        return s is not None

    step = [s for s in spans if s["name"] == "compile"
            and s["attrs"]["program"] == "jit(_fused_step)"]
    stages = sorted(s["attrs"]["stage"] for s in step)
    assert stages == ["backend", "lower", "trace"]     # each once
    assert all(under(s, first) for s in step)
    backend = next(s for s in step if s["attrs"]["stage"] == "backend")
    assert backend["attrs"]["outcome"] in ("hit", "miss", None)
    later = [s for s in spans if s["name"] == "fused_dispatch"
             and s["id"] != first["id"]]
    assert later and not any(
        under(s, d) for d in later for s in spans if s["name"] == "compile")


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_the_reports_setup_parts_sum_to_its_interval(runs, kind):
    setup = runs[kind]["report"]["setup"]
    parts = ("import", "construct", "plan", "step_build", "loop",
             "unspanned")
    assert sum(setup[p] for p in parts) == pytest.approx(
        setup["interval_s"], abs=1e-5)
    assert all(setup[p] >= 0 for p in parts)
    assert setup["step_build"] > 0 and setup["construct"] > 0
    if kind == "dense":
        assert setup["import"] > 0
        assert {"to_float", "find_bins", "bin_rows"} \
            <= set(setup["construct_children"])
    step = setup["compile"]["jit(_fused_step)"]
    assert step["backend_s"] > 0 and step["outcome"] in ("hit", "miss",
                                                          "built")


def test_owned_seconds_gives_every_instant_one_owner():
    def sp(i, name, a, b):
        return {"id": i, "parent": None, "name": name, "t0_ns": a,
                "t1_ns": b, "thread": 1, "attrs": {}}
    spans = [sp(1, "import", 10, 20), sp(2, "construct", 30, 60),
             sp(3, "compile", 40, 50),          # inside construct
             sp(4, "fused_dispatch", 70, 100),
             sp(5, "plan", 72, 75), sp(6, "compile", 80, 90),
             sp(7, "to_float", 31, 35)]         # no owner: construct's
    own = telemetry.owned_seconds(spans, 0, 100, loop_from=70)
    ns = {k: round(v * 1e9) for k, v in own.items()}
    assert ns == {"import": 10, "construct": 20, "compile": 20, "plan": 3,
                  "loop": 17, "unspanned": 30}
    assert sum(ns.values()) == 100


# ----------------------------------------------- the iteration records

def _train(rounds, callbacks=None, **params):
    X, y = _data()
    return lgb.train({**PARAMS, **params},
                     lgb.Dataset(X, label=y, params={"verbosity": -1}),
                     rounds, callbacks=callbacks)


def test_iteration_records_cover_the_iteration(clean):
    booster = _train(5)
    gb = booster._boosting
    recs = [r for r in gb._flight.records() if r["type"] == "iter"]
    assert [r["iteration"] for r in recs] == [0, 1, 2, 3, 4]
    for r, nxt in zip(recs, recs[1:] + [None]):
        assert r["t0_ns"] < r["t1_ns"]
        if nxt is not None:         # one ends where the next begins
            assert r["t1_ns"] == nxt["t0_ns"]
        assert r["unspanned_s"] >= 0 and r["gc_s"] >= 0
        assert {"fused_dispatch", "score_dispatch", "flight_record"} \
            <= set(r["host"])
        assert sum(r["host"].values()) + r["unspanned_s"] == pytest.approx(
            (r["t1_ns"] - r["t0_ns"]) * 1e-9, abs=1e-4)
        # wall_s means what it meant: the host's time inside update()
        assert r["wall_s"] <= (r["t1_ns"] - r["t0_ns"]) * 1e-9 + 1e-6
    assert recs[0]["compile_requests"] >= 1     # the step's own
    assert all(r["compile_requests"] == 0 for r in recs[2:])
    gb._flush_pending()                          # the lagged flush
    recs = [r for r in gb._flight.records() if r["type"] == "iter"]
    n = gb.train_set.num_data
    for r in recs:
        assert r["rows_streamed"] >= n and r["rows_streamed"] % 1 == 0
        assert r["ready_seen_ns"] >= r["t0_ns"]
    assert sum(r["rows_streamed"] for r in recs) == gb.rows_streamed_total
    # the timeline's set-up list closed with the first completed iteration
    assert not any(s["t0_ns"] > recs[0]["t1_ns"]
                   for s in profiling.timeline()["setup"])


def test_a_slow_iteration_is_the_stalled_one_and_names_its_span(clean):
    def slow(env):
        time.sleep(0.4 if env.iteration == 4 else 0.03)
    booster = _train(8, callbacks=[slow])
    booster._boosting._flush_pending()
    rep = telemetry.timeline_report()
    assert sum(c["n"] for c in rep["iterations"].values()) == 7
    assert "?" not in rep["iterations"]
    assert [e["iteration"] for e in rep["stalled"]] == [4]
    entry = rep["stalled"][0]
    assert entry["largest_span"] == "callbacks"
    assert entry["interval_s"] >= 0.4 > 1.25 * entry["class_median_s"]
    assert entry["compile_requests"] == 0 and entry["gc_s"] >= 0
    assert entry["unspanned_s"] < 0.05


def test_validate_accepts_the_flushed_file_and_the_cli_reads_it(
        clean, tmp_path):
    compile_cache.install_compile_hook()
    _train(4, telemetry_dir=str(tmp_path))
    path = str(tmp_path / "flight_rank0.jsonl")
    records, errors = telemetry.validate_flight_jsonl(path)
    assert errors == []
    kinds = [r["type"] for r in records]
    assert kinds[0] == "run" and "span" in kinds and kinds[-1] == "flush"
    assert {"construct", "plan", "fused_dispatch"} \
        <= {r["name"] for r in records if r["type"] == "span"}
    iters = [r for r in records if r["type"] == "iter"]
    # the train-end flush waited for the last trees
    assert all("rows_streamed" in r and "t1_ns" in r for r in iters)
    assert records[0]["context"]["num_data"] == 3000
    report = records[-1]["timeline"]
    assert sum(c["n"] for c in report["iterations"].values()) == 3
    assert telemetry.report_of_file(path)["iterations"] \
        == report["iterations"]
    res = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.telemetry", path],
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO),
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    assert json.loads(res.stdout)["iterations"] == report["iterations"]
    # a record that lost its shape is named
    bad = dict(iters[0], unspanned_s=-1.0)
    assert telemetry.validate_flight_record(bad)
    bad = dict(iters[0], t1_ns=iters[0]["t0_ns"] - 1)
    assert telemetry.validate_flight_record(bad)
    assert telemetry.validate_flight_record({"type": "span", "id": 1})
