#!/bin/sh
# Run the test suite one pytest process per file. Isolates the XLA-CPU
# compiler's many-programs segfault (see conftest.py) and makes a crash
# attributable to a single file instead of killing the whole run.
set -u
fail=0
for f in "$(dirname "$0")"/test_*.py; do
  echo "=== $f"
  python -u -m pytest "$f" -q --no-header || fail=1
done
# supervisor gang-restart + elastic + integrity smoke (fast knobs,
# ~90 s): kill a rank mid-iter -> relaunch from checkpoint ->
# bit-identical final model; fail a rank's spawn permanently -> gang
# shrinks to world size 1 and completes (the shrink recorded in the
# SupervisorReport); flip one score-cache bit on rank 1 of a 3-rank gang
# -> the cross-rank divergence vote names exactly that rank (exit 95) ->
# the supervisor restores the gang from the last valid checkpoint ->
# training completes with model text bit-identical to the fault-free run
echo "=== scripts/supervisor_smoke.py"
python -u "$(dirname "$0")/../scripts/supervisor_smoke.py" || fail=1
# Pallas histogram-kernel roofline smoke (fast knobs, ~30 s on CPU): runs
# all three modes x {full, in-kernel gather} through the interpreter at a
# tiny shape and asserts the modeled fused-vs-XLA traffic ratio >= 5x
echo "=== scripts/kernel_bench.py"
python -u "$(dirname "$0")/../scripts/kernel_bench.py" --fast --interpret \
  || fail=1
# compile-wall smoke (~20 s, CPU backend): cold process trains K=4
# blocks-per-dispatch against a fresh persistent compile cache +
# checkpoint; a SECOND process resumes from the checkpoint against the
# same cache and must perform ZERO fused-step XLA compiles (disk hits
# only) while continuing bit-identically to an uninterrupted run — the
# supervisor-relaunch warm path at its smallest shape
echo "=== scripts/compile_wall_smoke.py"
python -u "$(dirname "$0")/../scripts/compile_wall_smoke.py" || fail=1
# serving-layer end-to-end smoke (fast knobs, ~10 s): concurrent mixed
# load coalesces bit-identically -> injected slow dispatch produces a
# phase-named timeout + a retriable shed in the health gauges -> corrupt
# hot-swap candidate rejected with the old model serving -> valid
# candidate swaps in bit-identical to a cold load
echo "=== scripts/serve_smoke.py"
python -u "$(dirname "$0")/../scripts/serve_smoke.py" || fail=1
# streaming-construct smoke (fast knobs, ~20 s on CPU): chunked
# two-pass construct -> 3 boosting rounds, bit-identical mappers/bins/
# model text vs monolithic; raw-chunk residency <= 2 chunks (weakref
# census + construct_peak_bytes gauge); sketch/bin/h2d telemetry on
# record; compacted-sketch rank error within the documented budget;
# free_dataset / construct re-entry audited on the chunked path
echo "=== scripts/construct_smoke.py"
python -u "$(dirname "$0")/../scripts/construct_smoke.py" || fail=1
# telemetry smoke (fast knobs, ~20 s on CPU): kill-at-iteration flushes
# a flight-recorder JSONL that schema-validates and names the in-flight
# iteration; a clean run flushes at train end with the health snapshot
# referencing the JSONL; a trace_window capture around two boosting
# iterations writes perfetto artifacts (or records the profiler error —
# jax.profiler no-op tolerance); the Prometheus exposition renders
echo "=== scripts/telemetry_smoke.py"
python -u "$(dirname "$0")/../scripts/telemetry_smoke.py" || fail=1
# post-mortem smoke (fast knobs, ~40 s on CPU): a 2-process supervised
# gang has rank 1 hard-killed with no restart budget -> GangFailedError
# carries an auto-generated post-mortem classifying the failure 'kill'
# and naming rank 1; rerunning scripts/postmortem.py offline over the
# diag dir reaches the same verdict (the operator workflow)
echo "=== scripts/postmortem_smoke.py"
python -u "$(dirname "$0")/../scripts/postmortem_smoke.py" || fail=1
# bench regression gate self-check (<5 s, no jax): identical round
# passes, a synthetic regression exits 1, a CPU-fallback round against
# a TPU baseline is refused with exit 2, AUC gates on absolute deltas,
# per-metric overrides work, the driver's wrapper shape parses
echo "=== scripts/bench_compare.py --self-check"
python -u "$(dirname "$0")/../scripts/bench_compare.py" --self-check \
  || fail=1
# serve bench smoke (fast knobs, ~15 s on CPU): open-loop mixed-size load
# through the micro-batching frontend; asserts it completes and reports
# serve_p50_ms / serve_p99_ms / serve_rows_per_sec / serve_shed_count JSON
echo "=== bench_serve.py --fast"
python -u "$(dirname "$0")/../bench_serve.py" --fast || fail=1
exit $fail
