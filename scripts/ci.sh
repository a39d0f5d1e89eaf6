#!/usr/bin/env bash
# Tier-1 verification, exactly as ROADMAP.md specifies, with the bounded-
# runtime guarantee made checkable: the suite must collect cleanly, pass,
# and finish within TIMEOUT_S.
#
#   scripts/ci.sh            # full tier-1 (includes -m slow tests)
#   FAST=1 scripts/ci.sh     # quick signal: skip the slow marker
#   FLEET=1 scripts/ci.sh    # fleet tier only: sweep smoke, preemption
#                            # signal path, elastic virtual-device tests
#   LINT=0 scripts/ci.sh     # skip the repro-lint static-analysis stage
set -euo pipefail
cd "$(dirname "$0")/.."

TIMEOUT_S="${TIMEOUT_S:-1500}"
ARGS=(-x -q)
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [[ "${LINT:-1}" == "1" ]]; then
  # Static-analysis stage (every tier, including FAST): repro-lint fails
  # on any finding that is neither inline-suppressed nor justified in
  # .repro-lint-baseline.json — so a reintroduced donated-buffer reuse,
  # interpret=True, or hot-path host sync breaks CI before any test runs.
  python -m repro.analysis.lint src benchmarks
  # Telemetry schema stage: every committed BENCH_*.json baseline must
  # validate against the v1 bench schema (repro.telemetry.schema), so a
  # half-written or hand-edited artifact fails before any test runs.
  python -m repro.telemetry.schema benchmarks
fi

if [[ "${FLEET:-0}" == "1" ]]; then
  # Fleet tier: the elastic-training acceptance surface in one bounded
  # command — the sweep driver (incl. the crash-mid-sweep resume proof),
  # the SIGTERM→checkpoint→exit-75→elastic-resume protocol, the chaos
  # bitwise-recovery harness, and the multi-virtual-device elastic
  # restore subprocess tests.  All slow-marked tests here fit the same
  # TIMEOUT_S budget as the full tier.
  exec timeout "$TIMEOUT_S" python -m pytest tests/fleet \
      tests/run/test_profiler.py -q "$@"
fi

if [[ "${FAST:-0}" == "1" ]]; then
  # Fast tier leads with the contract guards: the Opt v2 zero-recompile-
  # under-hparam-schedule assertions (tests/core/test_api.py), the
  # Run API smoke (tests/run: RunSpec JSON round-trip, a short synthetic
  # run + checkpoint resume through run(), the packed-batch equivalence
  # + fault-recovery rewind proofs, and the jit cache-size proof that
  # the hook pipeline adds zero steady-state recompiles), the
  # segment-packing layout invariants (tests/data), the telemetry
  # schema / probe / golden-report checks (tests/telemetry), and the
  # training-sentinel guard/policy/injected-fault proofs (tests/sentinel)
  # — so an accidental retrace, run-layer, packing, or anomaly-guard
  # regression fails in seconds, before
  # the wider suite runs (which then skips those paths to stay within
  # the single TIMEOUT_S wall-clock bound).
  SECONDS=0
  timeout "$TIMEOUT_S" python -m pytest tests/core/test_api.py tests/run \
      tests/data tests/telemetry tests/sentinel -m "not slow" -q
  TIMEOUT_S=$((TIMEOUT_S - SECONDS))
  # `timeout 0` would DISABLE the bound entirely — clamp to >= 1s.
  if (( TIMEOUT_S < 1 )); then TIMEOUT_S=1; fi
  ARGS+=(-m "not slow" --ignore=tests/core/test_api.py --ignore=tests/run
         --ignore=tests/data --ignore=tests/telemetry
         --ignore=tests/sentinel)
fi

exec timeout "$TIMEOUT_S" python -m pytest "${ARGS[@]}" "$@"
