#!/usr/bin/env sh
# Tier-1 verify (see ROADMAP.md). Runs on the CPU; the property tests need
# hypothesis.
set -e
cd "$(dirname "$0")/.."
# docs gate: every package documented, every link/module/CLI-flag
# reference resolves against the tree (fatal since PR 5)
python scripts/docs_check.py
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"
# kernel-routing gate: every paged serving path through the Pallas
# kernels (interpret mode, fp + int8) must match the jnp oracle engine;
# also runs the sharded-parity subprocess (8 forced devices): (2,2)-mesh
# and disaggregated engines must be token-identical to single-host
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python benchmarks/bench_serve.py --smoke
# fleet gate: deterministic elastic scenario — the re-scale arm must
# beat queue-only goodput on the same failure trace, the simulated
# checkpoint-interval optimum must match the closed-form search — plus
# the serve-scenario arm: the autoscale-beats-static and
# burst-SLO-violation scenario suites (benchmarks/scenarios/) must pass
# their expect checks, a double-run must be byte-identical, and
# serve_calibration_check must recover a synthetic service law
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python benchmarks/bench_fleet.py --smoke
# trace gate: serve a short arrivals trace with telemetry on, then
# validate the Chrome trace (balanced spans, non-negative durations),
# replay the measured steptrace through the fleet simulator, merge
# serve + train + fleet events into one validating timeline, and hold
# the serve calibration gate: a saturated one-replica serve sim
# calibrated from the measured steptrace must reproduce the engine's
# per-chunk decode time within tolerance
TRACE_TMP="$(mktemp -d)"
trap 'rm -rf "$TRACE_TMP"' EXIT
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.launch.serve --arch qwen2_0_5b --smoke --trace 6 \
    --max-batch 2 --chunk 4 \
    --trace-out "$TRACE_TMP/serve_trace.json" \
    --metrics-out "$TRACE_TMP/serve_metrics.jsonl" \
    --steptrace-out "$TRACE_TMP/serve_steptrace.json"
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python scripts/trace_gate.py "$TRACE_TMP/serve_trace.json" \
    "$TRACE_TMP/serve_steptrace.json"
