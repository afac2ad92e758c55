"""The trace_scope and program_seconds readers: on a hand-made event list
and table, and end to end in the CPU rehearsal. Run by hand:
JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q"""

import importlib.util
import json
import os
import subprocess
import sys
import types

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import trace_reduce as tr  # noqa: E402
from trace_reduce import Event  # noqa: E402


def _reader(kind):
    spec = importlib.util.spec_from_file_location(
        f"reader_{kind}", os.path.join(HERE, "readers", kind + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ts = _reader("trace_scope")

# a while that encloses a scoped gather, a kernel, an unscoped op and a
# cond with one scoped child; then the score add, whose "fusion" shares its
# name with an instruction of the step
EVENTS = [
    Event("while.1", 0.0, 10.0, (), "(s32[8], f32[2]) while(...)"),
    Event("fusion.10", 1.0, 3.0, (), "u8[4,28] fusion(u8[8,28] %p)"),
    Event("hist_tiles_hilo.6", 4.0, 2.0, (), "(f32[8,128]) custom-call()"),
    Event("copy.9", 6.0, 0.5, (), "f32[8] copy(f32[8] %x)"),
    Event("cond.2", 7.0, 2.0, (), "(f32[2]) conditional(...)"),
    Event("fusion.11", 7.5, 1.0, (), "s32[4] fusion(s32[8] %q)"),
    Event("fusion", 10.0, 0.25, (), "f32[8] fusion(f32[8] %a, f32[8] %b)"),
    Event("fusion", 10.5, 0.5, (), "s32[256] fusion(s32[256] %c)"),
]
TABLE = {
    "jit__fused_step": {
        "while.1": (None, "(s32[8], f32[2])"),
        "fusion.10": ("rung_gather", "u8[4,28]"),
        "hist_tiles_hilo.6": ("hist_pass", "(f32[8,128])"),
        "cond.2": ("hist_pass", "(f32[2])"),
        "fusion.11": ("rung_gather", "s32[4]"),
        "fusion": ("tile_select", "s32[256]"),
    },
    "jit__apply_score_delta": {"fusion": ("score_update", "f32[8]")},
}


def test_owned_seconds_equal_self_times_where_events_nest():
    own = {}
    for ev, t in ts.owned_seconds(EVENTS):
        own[f"{ev.name} {ev.detail}".strip()] = own.get(
            f"{ev.name} {ev.detail}".strip(), 0.0) + t
    assert own == dict(tr.by_name(EVENTS, top=99))


def test_owned_seconds_stay_a_partition_where_events_overlap():
    """The chip's op line: a copy-done still open when the next op starts.
    self_times counts the overlap twice; each instant has one owner."""
    evs = [Event("cond.1", 0.0, 10.0), Event("copy-done.2", 1.0, 1.0),
           Event("cond.3", 1.5, 4.5), Event("fusion.4", 3.5, 1.0),
           Event("tail", 12.0, 1.0)]
    own = {ev.name: t for ev, t in ts.owned_seconds(evs)}
    assert own == {"cond.1": 5.0, "copy-done.2": 0.5, "cond.3": 3.5,
                   "fusion.4": 1.0, "tail": 1.0}
    busy = tr.union_seconds([(e.start, e.end) for e in evs])
    assert sum(own.values()) == busy == 11.0
    assert sum(t for _e, t in tr.self_times(evs)) > busy
    assert ts.owned_seconds([]) == []


def test_self_time_by_scope_partitions_the_busy_time():
    by = ts.seconds_by_scope(EVENTS, TABLE, ["hist_tiles"])
    assert by == {"rung_gather": 4.0,       # fusion.10 + fusion.11
                  "hist_pass": 1.0,         # cond.2 less its child
                  None: 2.5 + 0.5,          # while.1's self time + copy.9
                  "score_update": 0.25,     # told apart by the shape
                  "tile_select": 0.5}
    busy = tr.union_seconds([(e.start, e.end) for e in EVENTS])
    kernel = tr.sum_matching(EVENTS, ["hist_tiles"])
    assert abs(sum(by.values()) + kernel - busy) < 1e-12
    # without the prefix the kernel counts in its scope
    assert ts.seconds_by_scope(EVENTS, TABLE)["hist_pass"] == 3.0


def test_an_event_of_no_known_program_is_unscoped():
    ev = Event("fusion.99", 0.0, 1.0, (), "f32[3] fusion()")
    assert ts.scope_of_event(ev, TABLE) is None
    both = Event("fusion", 0.0, 1.0, (), "u8[7] fusion()")   # neither shape
    assert ts.scope_of_event(both, TABLE) is None
    # one program holds the name, with another shape: an unregistered
    # program's instruction, not the step's
    other = Event("fusion.10", 0.0, 1.0, (), "u8[4,280] fusion(u8[8] %p)")
    assert ts.scope_of_event(other, TABLE) is None
    # an event without its text (the CPU's) goes by the name alone, and a
    # text cut inside the shape by what there is of it
    assert ts.scope_of_event(Event("fusion.10", 0.0, 1.0), TABLE) \
        == "rung_gather"
    cut = Event("while.1", 0.0, 1.0, (), "(s32[8], f3")
    assert ts.same_shape("(s32[8], f32[2])", cut.detail)
    assert not ts.same_shape("u8[4,28]", "u8[4,280] fusion(")


def test_read_divides_by_the_units_and_needs_a_view(monkeypatch):
    view = types.SimpleNamespace(devices={"d": EVENTS}, busiest="d")
    ctx = types.SimpleNamespace(view=view, units=2, log=lambda _m: None)
    import lightgbm_tpu.telemetry as telemetry
    monkeypatch.setattr(telemetry, "scope_table",
                        lambda shapes=False: TABLE, raising=False)
    spec = {"kind": "trace_scope", "scopes": ["rung_gather"],
            "minus_prefixes": ["hist_tiles"]}
    assert ts.read(spec, ctx) == 2.0
    assert ts.read({"kind": "trace_scope", "unscoped": True,
                    "minus_prefixes": ["hist_tiles"]}, ctx) == 1.5
    assert ts.read({**spec, "scopes": ["gradients"]}, ctx) == 0.0
    assert ts.read(spec, types.SimpleNamespace(view=None, units=2)) is None
    # a program without the table (the parent): nothing, and no error
    monkeypatch.delattr(telemetry, "scope_table")
    fresh = types.SimpleNamespace(view=view, units=2, log=lambda _m: None)
    assert ts.read(spec, fresh) is None


def test_program_seconds_reads_by_key_and_prefix(monkeypatch):
    ps = _reader("program_seconds")
    from lightgbm_tpu import compile_cache
    stats = {"requests": {"jit(_fused_step)": 1},
             "trace_s": {"jit(_fused_step)": 8.0, "jit(grow_tree)": 5.0},
             "lower_s": {"jit(_fused_step)": 2.0, "jit(_fused_block)": 1.0},
             "backend_s": {"jit(_fused_step)": 0.5, "jit(f)": 0.25}}
    monkeypatch.setattr(compile_cache, "compile_stats", lambda: stats)
    ctx = types.SimpleNamespace()
    step = {"source": "compile_stats", "keys": ["trace_s", "lower_s"],
            "program_prefixes": ["jit(_fused_step)", "jit(_fused_block)"]}
    assert ps.read(step, ctx) == 11.0
    assert ps.read({"source": "compile_stats", "keys": ["backend_s"]},
                   ctx) == 0.75
    # the parent's compile_stats has the counts and no seconds
    monkeypatch.setattr(compile_cache, "compile_stats",
                        lambda: {"requests": {}})
    assert ps.read(step, ctx) is None


def test_rehearsal_prints_the_device_metrics_and_they_sum_to_busy():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "tiny.train", "--seed", "3", "--seconds", "2", "--trace", "1",
         "--rehearse"], env=env, capture_output=True, text=True,
        timeout=900)
    assert res.returncode == 0, res.stderr[-2000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    device = ["rung_gather_s_per_iter", "tile_select_s_per_iter",
              "split_search_s_per_iter", "apply_split_s_per_iter",
              "gradients_s_per_iter", "score_update_s_per_iter",
              "hist_pass_other_s_per_iter", "unscoped_device_s_per_iter"]
    for name in device + ["step_trace_lower_s", "compile_or_load_s",
                          "autotune_s"]:
        assert name in m, name
    # off the chip the kernels are interpreted: no hist_tiles event, so
    # the eight are the whole of the busy time. The CPU's pseudo device
    # is several threads, whose events overlap without nesting: 2%, where
    # the chip's one line gives equality
    busy = line["device"]["busy_s"] / line["attempted"]
    assert abs(sum(m[k] for k in device) - busy) <= 0.02 * busy
    assert m["step_trace_lower_s"] > 0 and m["compile_or_load_s"] > 0
    gaps = [g[0] for g in line["breakdown"]["idle_gaps"]]
    assert any("lgbm:" in g for g in gaps), gaps
