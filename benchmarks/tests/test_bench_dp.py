"""The data-parallel cell's files: the ``trace_devices`` reader on a
hand-made view, and the CPU rehearsal of ``criteo.train-dp4`` end to end,
untraced and traced. Run by hand:
JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q"""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
# what only a chip can give: the CPU has no device plane with the kernel's
# events, no published peak and no allocator statistics
CHIP_ONLY = {"dp_hist_kernel_s_per_iter", "dp_hist_tiles_roofline",
             "dp_memory_spread"}


def _reader(kind):
    spec = importlib.util.spec_from_file_location(
        f"reader_{kind}", os.path.join(HERE, "readers", kind + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_devices_reads_the_slowest_chip_over_the_mean():
    td = _reader("trace_devices")
    spec = {"kind": "trace_devices", "stat": "busy_max_over_mean"}
    view = types.SimpleNamespace(busy={"/device:TPU:0": 4.0,
                                       "/device:TPU:1": 4.0,
                                       "/device:TPU:2": 6.0,
                                       "/device:TPU:3": 2.0})
    assert td.read(spec, types.SimpleNamespace(view=view)) == 1.5
    one = types.SimpleNamespace(busy={"host-xla": 3.0})
    assert td.read(spec, types.SimpleNamespace(view=one)) == 1.0
    assert td.read(spec, types.SimpleNamespace(view=None)) is None
    idle = types.SimpleNamespace(busy={"a": 0.0, "b": 0.0})
    assert td.read(spec, types.SimpleNamespace(view=idle)) is None
    with pytest.raises(ValueError):
        td.read({"stat": "median"}, types.SimpleNamespace(view=view))


def _rehearse(trace: int) -> dict:
    res = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "tiny-criteo-dp4.train", "--seed", "3000000019", "--seconds", "2",
         "--trace", str(trace), "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=1200)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "NOT CORRECT" not in res.stdout, res.stdout[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_rehearsal_prints_the_cells_end_to_end_metrics():
    line = _rehearse(0)
    assert line["correct"] and line["device"]["count"] == 4
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    want = [m["name"] for m in bench["end_to_end"]
            if "criteo.train-dp4" in m.get("workloads",
                                           ["criteo.train-dp4"])]
    assert sorted(want) == sorted(line["metrics"])


def test_rehearsal_prints_every_layer_metric_a_cpu_can():
    line = _rehearse(1)
    assert line["correct"]
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = {m["name"] for m in bench["per_layer"]
              if "criteo.train-dp4" in m.get("workloads", [])}
    assert len(listed) == 21
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert listed - CHIP_ONLY == set(m)
    assert m["dp_split_sync_calls_per_iter"] >= 1
    assert m["dp_coll_bytes_per_iter"] > 0
    assert m["dp_hist_allreduce_s_per_iter"] > 0
    assert m["dp_split_sync_s_per_iter"] > 0
    assert m["dp_chip_busy_ratio"] == 1.0
    assert m["dp_dispatches_per_iter"] == 2.0
