"""trace_reduce on a hand-made event list. Run by hand or in the CPU
rehearsal: JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import trace_reduce as tr  # noqa: E402
from trace_reduce import Event  # noqa: E402


def test_union_merges_overlapping_and_nested_intervals():
    assert tr.union_seconds([]) == 0.0
    assert tr.union_seconds([(0, 1), (0.5, 2), (3, 4), (3.2, 3.5)]) == 3.0
    assert tr.union_seconds([(3, 4), (0, 1)]) == 2.0       # unsorted input


def test_sum_matching_by_name_prefix_and_by_stats():
    evs = [Event("hist_tiles_hilo", 0, 1.0),
           Event("custom-call.7", 1, 0.5, ("hist_tiles_split_epilogue",)),
           Event("fusion.1", 2, 4.0, ("gather",), "f32[8] fusion(%hist_tiles)"),
           Event("all-reduce.3", 6, 0.25)]
    assert tr.sum_matching(evs, ["hist_tiles"]) == 1.5
    assert tr.sum_matching(evs, ["all-reduce", "all-gather"]) == 0.25
    assert tr.sum_matching(evs, ["nothing"]) == 0.0


def test_self_time_takes_nested_events_out_of_the_parent():
    evs = [Event("while.1", 0, 10.0), Event("fusion.2", 1, 3.0),
           Event("fusion.2", 5, 2.0), Event("copy.3", 5.5, 0.5),
           Event("tail", 10, 1.0)]
    got = dict(tr.by_name(evs))
    assert got == {"while.1": 5.0, "fusion.2": 4.5, "copy.3": 0.5,
                   "tail": 1.0}
    assert sum(got.values()) == tr.union_seconds([(e.start, e.end)
                                                   for e in evs])
    assert tr.by_name(evs, top=1) == [["while.1", 5.0]]


def test_idle_gaps_are_labelled_by_what_the_host_was_doing():
    busy = [(1.0, 2.0), (2.5, 4.0)]
    ann = [Event("bench_iteration", 0.0, 3.0), Event("bench_iteration", 3.0, 2.0)]
    host = ann + [Event("device_get", 2.1, 0.3), Event("dispatch", 0.0, 0.9)]
    got = dict(tr.idle_gaps(busy, (0.0, 5.0), host, ann))
    assert got["bench_iteration/dispatch"] == 1.0           # gap 0..1
    assert got["bench_iteration/device_get"] == 0.5         # gap 2..2.5
    assert got["bench_iteration"] == 1.0                    # gap 4..5
    assert abs(sum(got.values()) - (5.0 - tr.union_seconds(busy))) < 1e-12


def test_clip_cuts_events_to_the_window():
    evs = [Event("a", 0, 2.0), Event("b", 3, 1.0), Event("c", 9, 1.0)]
    got = tr.clip(evs, (1.0, 3.5))
    assert [(e.name, e.start, e.dur) for e in got] == [("a", 1.0, 1.0),
                                                       ("b", 3, 0.5)]


def test_split_name_takes_the_instruction_name_and_drops_layouts():
    raw = ("%fusion.10 = u8[5250048,28]{0,1:T(8,128)(4,1)} fusion(u8[10500000,"
           "28]{0,1:T(8,128)(4,1)} %get-tuple-element.488), kind=kCustom")
    name, detail = tr.split_name(raw)
    assert name == "fusion.10"
    assert detail.startswith("u8[5250048,28] fusion(u8[10500000,28] %get")
    assert tr.split_name("bench_iteration") == ("bench_iteration", "")
