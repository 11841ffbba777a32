#!/usr/bin/env bash
# CI suite: first the static-analysis gate (python -m repro.analysis
# --strict: jit-contract checks traced over every program the pipeline
# family can build, plus the concurrency lint over serving/runtime — any
# error OR warning fails before a single test runs), then the ROADMAP
# tier-1 verify command with slow (VGG-sized) cases deselected, then — when
# no pytest args override the selection — the slow-marked alexnet/vgg16
# pallas pipeline parity geometries and fused-transition parity runs.
# Finally, under 8 emulated host devices
# (XLA_FLAGS=--xla_force_host_platform_device_count=8): the device-pool
# parity tests and the coded-LM device-pool decode parity test (both skip
# in the single-device main run above).
#
# CI asserts correctness only.  Speed is measured only by bench/run.py
# (BENCHMARK.json) on the chip; PERF.md holds its numbers.
#
# Extra args are passed through to the main pytest run.
#
# Tests run with a per-test watchdog (tests/conftest.py, REPRO_TEST_TIMEOUT
# seconds) so a hung scheduler/worker thread fails fast instead of wedging
# the suite; -x stops the run at the first failure.
#
#   scripts/ci.sh            # fast suite + slow parity + device pool
#   scripts/ci.sh -m ""      # include all slow cases in the main run
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export REPRO_TEST_TIMEOUT="${REPRO_TEST_TIMEOUT:-300}"
# first-stage gate: static analysis (jit contracts over the full pipeline
# family + concurrency lint of serving/runtime) — strict means warnings
# fail too; machine-readable findings land in results/analysis_findings.json
mkdir -p results
python -m repro.analysis --strict --json-out results/analysis_findings.json
python -m pytest -x -q -m "not slow" "$@"
# skip the extra block only when the caller overrides marker selection
# (e.g. `-m ""` already ran the slow cases in the main suite above)
if [[ "$*" != *"-m"* ]]; then
  python -m pytest -x -q -m "slow" tests/test_pipeline.py -k "pallas"
  # fused-transition parity on the big archs, both backends (the fast
  # lenet5 cases already ran in the main suite)
  python -m pytest -x -q -m "slow" tests/test_fused_transitions.py
fi
# device pool: multi-device parity tests
export XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}"
python -m pytest -x -q tests/test_device_pool.py
# coded LM decode: device-pool decode parity runs here (it skips on a
# single-device jax)
python -m pytest -x -q tests/test_coded_decoder.py -k "device_pool"
