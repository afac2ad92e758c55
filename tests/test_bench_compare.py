"""BENCH regression gate (scripts/bench_compare.py): per-metric
thresholds, backend/tpu_required sanity (a CPU-fallback round can never
be blessed against a TPU baseline), and the driver-wrapper/JSONL file
shapes. Pure host logic — no jax work."""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "bench_compare", os.path.join(REPO, "scripts", "bench_compare.py"))
bench_compare = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_compare)

BASE = {"metric": "higgs10.5M_sec_per_iter", "value": 1.0,
        "rows": 10_500_000, "backend": "tpu", "tpu_required": True,
        "auc": 0.94, "mfu_est": 0.05, "hbm_peak_bytes": 8_000_000_000}


def _write(tmp_path, name, doc):
    p = str(tmp_path / name)
    with open(p, "w") as fh:
        if isinstance(doc, str):
            fh.write(doc)
        else:
            json.dump(doc, fh)
    return p


def test_identical_round_passes(tmp_path):
    b = _write(tmp_path, "b.json", BASE)
    c = _write(tmp_path, "c.json", dict(BASE, value=1.01))
    assert bench_compare.run([b, c]) == 0


def test_synthetic_regression_exits_nonzero(tmp_path):
    """The acceptance criterion: a synthetic regression exits non-zero."""
    b = _write(tmp_path, "b.json", BASE)
    c = _write(tmp_path, "c.json", dict(BASE, value=1.5))
    assert bench_compare.run([b, c]) == 1


def test_cpu_fallback_vs_tpu_baseline_refused(tmp_path):
    """The acceptance criterion: a CPU-fallback round compared against
    a TPU baseline exits non-zero (sanity code 2), regardless of its
    numbers."""
    b = _write(tmp_path, "b.json", BASE)
    c = _write(tmp_path, "c.json",
               dict(BASE, backend="cpu", value=0.5, tpu_required=False))
    assert bench_compare.run([b, c]) == 2


def test_tpu_required_but_cpu_backend_refused(tmp_path):
    b = _write(tmp_path, "b.json", dict(BASE, backend="cpu",
                                        tpu_required=False))
    c = _write(tmp_path, "c.json", dict(BASE, backend="cpu",
                                        tpu_required=True))
    assert bench_compare.run([b, c]) == 2


def test_auc_uses_absolute_tolerance(tmp_path):
    b = _write(tmp_path, "b.json", BASE)
    ok = _write(tmp_path, "ok.json", dict(BASE, auc=0.938))    # -0.002
    bad = _write(tmp_path, "bad.json", dict(BASE, auc=0.93))   # -0.010
    assert bench_compare.run([b, ok]) == 0
    assert bench_compare.run([b, bad]) == 1


def test_memory_metrics_gate(tmp_path):
    b = _write(tmp_path, "b.json", BASE)
    c = _write(tmp_path, "c.json",
               dict(BASE, hbm_peak_bytes=10_000_000_000))
    assert bench_compare.run([b, c]) == 1
    assert bench_compare.run([b, c, "--threshold",
                              "hbm_peak_bytes=30"]) == 0


def test_rows_mismatch_refused_unless_ignored(tmp_path):
    b = _write(tmp_path, "b.json", BASE)
    c = _write(tmp_path, "c.json", dict(BASE, rows=500_000))
    assert bench_compare.run([b, c]) == 2
    assert bench_compare.run([b, c, "--ignore-rows"]) == 0


def test_null_value_refused(tmp_path):
    b = _write(tmp_path, "b.json", BASE)
    c = _write(tmp_path, "c.json", dict(BASE, value=None, error="died"))
    assert bench_compare.run([b, c]) == 2


def test_multiple_candidates_worst_exit_wins(tmp_path):
    b = _write(tmp_path, "b.json", BASE)
    ok = _write(tmp_path, "ok.json", dict(BASE, value=1.02))
    bad = _write(tmp_path, "bad.json", dict(BASE, value=2.0))
    assert bench_compare.run([b, ok, bad]) == 1


def test_wrapper_and_jsonl_shapes(tmp_path):
    """Driver wrappers (tail + parsed) and raw bench.py JSONL
    streams both load; the LAST enriched line wins over earlier ones."""
    wrapper = _write(tmp_path, "wrap.json", {
        "n": 3, "rc": 0,
        "tail": json.dumps(dict(BASE, value=5.0)) + "\n"
                + json.dumps(dict(BASE, value=1.0)) + "\n",
        "parsed": dict(BASE, value=99.0)})
    assert bench_compare.load_bench(wrapper)["value"] == 1.0
    jsonl = _write(tmp_path, "stream.json",
                   "# comment\n" + json.dumps(dict(BASE, value=3.0))
                   + "\n" + json.dumps(dict(BASE, value=2.0)) + "\n")
    assert bench_compare.load_bench(jsonl)["value"] == 2.0
    garbage = _write(tmp_path, "garbage.json", "not json at all\n")
    with pytest.raises(SystemExit):
        bench_compare.load_bench(garbage)


def test_self_check_passes():
    assert bench_compare.self_check() == 0
