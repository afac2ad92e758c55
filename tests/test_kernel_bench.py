"""scripts/kernel_bench.py sets a kernel's time against the published
peaks of the benchmark's own table (benchmarks/peaks.json), and refuses a
device kind that table does not list."""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "kernel_bench", os.path.join(REPO, "scripts", "kernel_bench.py"))
kernel_bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(kernel_bench)


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v0 unlisted"])
def test_kernel_bench_reads_the_benchmark_peaks(kind):
    with open(os.path.join(REPO, "benchmarks", "peaks.json")) as fh:
        table = json.load(fh)
    if kind not in table:
        with pytest.raises(SystemExit, match="benchmarks/peaks.json"):
            kernel_bench.device_peaks(kind)
        return
    row = table[kind]
    peaks = kernel_bench.device_peaks(kind)
    assert peaks == row
    # one second moving the row's HBM bandwidth in bytes, and running
    # its bf16 / int8 peak in operations, reads as exactly 1.0
    macs = 1e12
    hilo = kernel_bench.achieved_fracs(
        row["hbm_bytes_per_s"], macs, 2, 1.0, "hilo", peaks)
    assert hilo["achieved_hbm_frac"] == 1.0
    assert hilo["achieved_mxu_frac"] == round(
        2.0 * macs * 2 / row["bf16_flops"], 4)
    q8 = kernel_bench.achieved_fracs(
        row["hbm_bytes_per_s"], row["int8_ops"] / 2, 1, 1.0, "q8", peaks)
    assert q8 == {"achieved_hbm_frac": 1.0, "achieved_mxu_frac": 1.0}
