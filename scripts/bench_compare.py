#!/usr/bin/env python
"""BENCH JSON regression gate: compare a candidate round against a
blessed baseline, with per-metric thresholds and backend sanity.

    python scripts/bench_compare.py BASELINE.json CANDIDATE.json [MORE...]
        [--pct 10] [--threshold metric=value] [--ignore-rows]

Every file may be a raw ``bench.py`` result line, a JSONL stream (the
LAST parseable line wins — bench.py prints enriched lines as probes
land), or a driver wrapper document holding the stream under ``tail`` /
the first line under ``parsed`` (the driver's wrapper shape). Each
candidate (2nd file onward) is compared against the FIRST file.

Sanity gates (exit 2 — the comparison itself is invalid):
  - a CPU round can NEVER be judged against a TPU baseline: two early
    rounds ran on the CPU and were filed under a chip's name; this gate
    makes that a hard failure, in both directions (backend mismatch
    either way is incomparable);
  - a round with ``tpu_required`` set but a non-TPU backend (bench.py
    exits 2 before writing such a round, but a hand-edited or truncated
    file must not pass);
  - a null headline ``value``, or a row-count mismatch (``--ignore-rows``
    downgrades the row check to a warning for cross-scale eyeballing).

Metric gates (exit 1 — a real regression): every metric present in BOTH
documents and listed in the direction tables is compared; lower-better
metrics fail when the candidate is more than the threshold above the
baseline, higher-better when more than the threshold below. Thresholds
are percent by default (``--pct``, default 10); AUC-family metrics use
ABSOLUTE tolerances (default 0.003) — percent noise on a 0.94 AUC would
hide a real quality loss. ``--threshold metric=value`` overrides one
metric (absolute for the AUC family, percent otherwise).

Exit codes: 0 = no regression; 1 = regression(s); 2 = sanity failure.
``--self-check`` runs the built-in synthetic scenarios (wired into
tests/run_suite.sh) and exits 0 only when every scenario gates
correctly.
"""

import argparse
import json
import os
import sys

# lower-is-better metrics (seconds, bytes, dispatch counts)
LOWER_BETTER = {
    "value", "sec_per_iter", "compact_sec_per_iter",
    "nocompact_sec_per_iter", "q8_sec_per_iter", "bin63_sec_per_iter",
    "bin63_q8_sec_per_iter", "first_iter_compile_s", "warm_start_s",
    "construct_sec", "dispatches_per_iter", "host_bytes_per_iter",
    "predict_host_bytes", "rows_streamed_per_tree",
    "hbm_peak_bytes", "host_rss_peak_bytes", "construct_peak_host_bytes",
    "sentinel_overhead_pct", "recorder_overhead_pct",
}
# higher-is-better metrics (throughput, utilization, quality)
HIGHER_BETTER = {
    "vs_baseline", "mfu_est", "mfu_bf16_est", "mfu_mode_est",
    "predict_rows_per_sec", "construct_rows_per_sec",
    "auc", "q8_auc", "q8_f32_ref_auc", "bin63_auc", "bin63_q8_auc",
    "trees_per_dispatch",
}
# AUC-family metrics compare on ABSOLUTE deltas (percent flatters them)
ABS_TOLERANCE = {"auc": 0.003, "q8_auc": 0.005, "q8_f32_ref_auc": 0.005,
                 "bin63_auc": 0.005, "bin63_q8_auc": 0.005}
DEFAULT_PCT = 10.0


def _last_json_line(text):
    out = None
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict) and "metric" in doc:
            out = doc
    return out


def load_bench(path):
    """Load one BENCH document: a result dict, a JSONL stream (last
    enriched line wins), or the driver wrapper ({"tail": ...,
    "parsed": ...}). Raises SystemExit(2) when nothing parseable is
    found — an unreadable round must not silently pass the gate."""
    with open(path) as fh:
        text = fh.read()
    doc = None
    try:
        doc = json.loads(text)
    except ValueError:
        pass
    if isinstance(doc, dict):
        if "metric" in doc:
            return doc
        # driver wrapper: prefer the LAST enriched line in tail over the
        # first-line "parsed" snapshot
        tail = doc.get("tail") or ""
        last = _last_json_line(tail)
        if last is not None:
            return last
        if isinstance(doc.get("parsed"), dict) and "metric" in doc["parsed"]:
            return doc["parsed"]
    last = _last_json_line(text)
    if last is not None:
        return last
    print(f"bench_compare: {path} holds no parseable BENCH result "
          f"(no JSON line with a 'metric' field)", file=sys.stderr)
    raise SystemExit(2)


def sanity(baseline, candidate, base_name, cand_name, ignore_rows=False):
    """Comparison-validity gates; returns a list of fatal messages."""
    fatal = []
    b_back = baseline.get("backend")
    c_back = candidate.get("backend")
    if b_back and c_back and b_back != c_back:
        fatal.append(
            f"backend mismatch: baseline {base_name} ran on "
            f"{b_back!r}, candidate {cand_name} on {c_back!r} — a CPU "
            f"round can never be judged against a TPU baseline; rerun "
            f"bench.py on the chip (without --cpu it refuses any other "
            f"backend)")
    # the same gates apply to BOTH sides: a null-headline error record
    # or a tpu_required round that ran on CPU must not be blessable as a
    # baseline either — compare() would silently skip the headline and
    # every candidate would pass ungated
    for doc, name, role in ((baseline, base_name, "baseline"),
                            (candidate, cand_name, "candidate")):
        back = doc.get("backend")
        if doc.get("tpu_required") and back != "tpu":
            fatal.append(
                f"{role} {name} demanded a TPU (tpu_required=true) "
                f"but ran on {back!r}")
        if doc.get("value") is None:
            fatal.append(f"{role} {name} has a null headline value"
                         + (f" (error: {doc.get('error')})"
                            if doc.get("error") else ""))
    b_rows, c_rows = baseline.get("rows"), candidate.get("rows")
    if b_rows and c_rows and b_rows != c_rows:
        msg = (f"row-count mismatch: baseline {b_rows} vs candidate "
               f"{c_rows} — per-iteration metrics scale with rows, the "
               f"comparison is apples-to-oranges")
        if ignore_rows:
            print(f"# WARNING (--ignore-rows): {msg}", file=sys.stderr)
        else:
            fatal.append(msg)
    return fatal


def _threshold_for(metric, pct, overrides):
    if metric in overrides:
        return overrides[metric], metric in ABS_TOLERANCE
    if metric in ABS_TOLERANCE:
        return ABS_TOLERANCE[metric], True
    return pct, False


def compare(baseline, candidate, pct=DEFAULT_PCT, overrides=None):
    """Per-metric comparison; returns (regressions, improvements, rows)
    where rows is the printable table and regressions the failing
    metric names."""
    overrides = overrides or {}
    regressions, improvements, rows = [], [], []
    for metric in sorted(LOWER_BETTER | HIGHER_BETTER):
        b, c = baseline.get(metric), candidate.get(metric)
        if not isinstance(b, (int, float)) or not isinstance(c, (int, float)) \
                or isinstance(b, bool) or isinstance(c, bool):
            continue
        thr, absolute = _threshold_for(metric, pct, overrides)
        lower = metric in LOWER_BETTER
        delta = c - b
        if absolute:
            worse = (delta > thr) if lower else (delta < -thr)
            better = (delta < -thr) if lower else (delta > thr)
            shown = f"{delta:+.6g} (tol {thr:g} abs)"
        else:
            rel = (delta / abs(b) * 100.0) if b else (0.0 if not c
                                                      else float("inf"))
            worse = (rel > thr) if lower else (rel < -thr)
            better = (rel < -thr) if lower else (rel > thr)
            shown = f"{rel:+.1f}% (tol {thr:g}%)"
        flag = "REGRESSION" if worse else ("improved" if better else "ok")
        rows.append((metric, b, c, shown, flag))
        if worse:
            regressions.append(metric)
        elif better:
            improvements.append(metric)
    return regressions, improvements, rows


def run(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="compare BENCH JSONs with per-metric thresholds")
    ap.add_argument("files", nargs="*",
                    help="BASELINE then one or more CANDIDATE files")
    ap.add_argument("--pct", type=float, default=DEFAULT_PCT,
                    help=f"default percent tolerance (default "
                         f"{DEFAULT_PCT}); AUC metrics use absolute "
                         f"tolerances instead")
    ap.add_argument("--threshold", action="append", default=[],
                    metavar="METRIC=VALUE",
                    help="per-metric override (absolute for the AUC "
                         "family, percent otherwise); repeatable")
    ap.add_argument("--ignore-rows", action="store_true",
                    help="downgrade the row-count sanity gate to a "
                         "warning (cross-scale eyeballing only)")
    ap.add_argument("--self-check", action="store_true",
                    help="run the built-in synthetic gate scenarios")
    args = ap.parse_args(argv)
    if args.self_check:
        return self_check()
    if len(args.files) < 2:
        ap.error("need a BASELINE and at least one CANDIDATE file")
    overrides = {}
    for spec in args.threshold:
        metric, _, val = spec.partition("=")
        try:
            overrides[metric.strip()] = float(val)
        except ValueError:
            ap.error(f"bad --threshold {spec!r} (want METRIC=NUMBER)")

    baseline = load_bench(args.files[0])
    exit_code = 0
    for cand_path in args.files[1:]:
        candidate = load_bench(cand_path)
        print(f"== {cand_path} vs baseline {args.files[0]} "
              f"(backend {candidate.get('backend')!r} vs "
              f"{baseline.get('backend')!r}, rows "
              f"{candidate.get('rows')} vs {baseline.get('rows')})")
        fatal = sanity(baseline, candidate, args.files[0], cand_path,
                       ignore_rows=args.ignore_rows)
        if fatal:
            for msg in fatal:
                print(f"SANITY FAILURE: {msg}")
            exit_code = max(exit_code, 2)
            continue
        regressions, improvements, rows = compare(
            baseline, candidate, pct=args.pct, overrides=overrides)
        width = max((len(r[0]) for r in rows), default=6)
        for metric, b, c, shown, flag in rows:
            print(f"  {metric.ljust(width)}  {b:>14.6g}  ->  "
                  f"{c:>14.6g}  {shown:>22}  {flag}")
        if regressions:
            print(f"RESULT: {len(regressions)} regression(s): "
                  f"{', '.join(regressions)}")
            exit_code = max(exit_code, 1)
        else:
            print(f"RESULT: ok ({len(improvements)} improved, "
                  f"{len(rows)} compared)")
    return exit_code


# ------------------------------------------------------------ self-check

def _write(tmp, name, doc):
    path = os.path.join(tmp, name)
    with open(path, "w") as fh:
        json.dump(doc, fh)
    return path


def self_check() -> int:
    """Synthetic gate scenarios (wired into tests/run_suite.sh): the
    gate must pass an identical round, fail a slowed/regressed round
    with exit 1, and refuse a CPU-fallback round against a TPU baseline
    with exit 2."""
    import tempfile
    base = {"metric": "higgs10.5M_sec_per_iter", "value": 1.0,
            "rows": 10_500_000, "backend": "tpu", "tpu_required": True,
            "auc": 0.94, "mfu_est": 0.05, "first_iter_compile_s": 30.0,
            "hbm_peak_bytes": 8_000_000_000,
            "host_rss_peak_bytes": 4_000_000_000}
    ok = True

    def expect(label, code, want):
        nonlocal ok
        good = code == want
        print(f"[self-check] {label}: exit {code} "
              f"({'ok' if good else f'WANT {want}'})")
        ok = ok and good

    with tempfile.TemporaryDirectory(prefix="bench_compare_") as tmp:
        b = _write(tmp, "base.json", base)
        same = _write(tmp, "same.json", dict(base, value=1.02))
        expect("identical round passes", run([b, same]), 0)
        slow = _write(tmp, "slow.json",
                      dict(base, value=1.5, auc=0.94))
        expect("25%-slower round fails", run([b, slow]), 1)
        worse_auc = _write(tmp, "auc.json", dict(base, auc=0.93))
        expect("AUC -0.01 fails (absolute tolerance)",
               run([b, worse_auc]), 1)
        cpu = _write(tmp, "cpu.json",
                     dict(base, backend="cpu", rows=500_000, value=4.8,
                          tpu_required=False))
        expect("CPU fallback vs TPU baseline refused",
               run([b, cpu]), 2)
        null = _write(tmp, "null.json",
                      dict(base, value=None,
                           error="all ladder scales failed"))
        expect("null headline refused", run([b, null]), 2)
        expect("null BASELINE refused too", run([null, b]), 2)
        cpu_req = _write(tmp, "cpu_req.json",
                         dict(base, backend="cpu"))
        expect("tpu_required baseline that ran on CPU refused",
               run([cpu_req, cpu_req]), 2)
        more_mem = _write(tmp, "mem.json",
                          dict(base, hbm_peak_bytes=10_000_000_000))
        expect("25% more HBM peak fails", run([b, more_mem]), 1)
        loose = _write(tmp, "loose.json",
                       dict(base, hbm_peak_bytes=10_000_000_000))
        expect("per-metric override loosens the gate",
               run([b, loose, "--threshold", "hbm_peak_bytes=30"]), 0)
        # the driver-wrapper shape parses (last tail line wins)
        wrapper = _write(tmp, "wrap.json", {
            "n": 3, "rc": 0,
            "tail": json.dumps(dict(base, value=1.01)) + "\n"
                    + json.dumps(dict(base, value=1.03)) + "\n",
            "parsed": dict(base, value=99.0)})
        expect("driver-wrapper shape parses (last line wins)",
               run([b, wrapper]), 0)
    print(f"[self-check] {'ALL SCENARIOS PASSED' if ok else 'FAILED'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(run())
