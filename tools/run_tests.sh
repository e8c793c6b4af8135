#!/usr/bin/env bash
# Tier-1 test runner (referenced from ROADMAP.md).
#
#   tools/run_tests.sh               full tier-1 suite
#   tools/run_tests.sh --fast        inner-loop subset (skips the slow
#                                    model-zoo and perf-profile suites)
#   tools/run_tests.sh --bench-smoke fast subset, then the population-scaling,
#                                    wire-quantization, robustness and
#                                    serving benchmarks in --quick mode
#                                    (refreshing
#                                    BENCH_population_scaling.json /
#                                    BENCH_wire_quantization.json /
#                                    BENCH_robustness.json /
#                                    BENCH_serving.json), then
#                                    tools/check_bench_regression.py compares
#                                    the fresh rates of ALL four benches
#                                    against the committed BENCH_*.json
#                                    baselines — an engine perf regression
#                                    (or a broken cross-engine wire-codec /
#                                    fault-model / serving-snapshot parity
#                                    probe) fails loudly
#
# Every mode first runs tools/check_docs.py (a doc referencing a removed
# symbol fails tier 1) and tools/lint/run.py (repro-lint: the parity
# contracts in docs/CONTRACTS.md — RNG discipline, shard_map spec arity,
# merge-dtype purity, tracer leaks, codec literals — are machine-checked
# on every run). --bench-smoke additionally runs the retrace budget gate
# (tools/lint/retrace_guard.py): the engines must not compile more
# signatures than their pinned budgets, and a warm rerun must compile
# nothing.
#
# Installs the optional test extras (hypothesis) when an installer and
# network are available; the suite degrades gracefully without them
# (tests/test_merge_properties.py skips; tests/test_merge_equivalences.py
# keeps the Section V equivalences covered).
set -euo pipefail
cd "$(dirname "$0")/.."

python tools/check_docs.py
python tools/lint/run.py

if ! python -c "import hypothesis" >/dev/null 2>&1; then
    echo "run_tests: hypothesis not installed; trying to install (best-effort)"
    python -m pip install --quiet hypothesis >/dev/null 2>&1 \
        || echo "run_tests: pip install failed (offline?) — property tests will skip"
fi

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [[ "${1:-}" == "--fast" ]]; then
    shift
    exec python -m pytest -x -q -k "not models and not perf" "$@"
fi
if [[ "${1:-}" == "--bench-smoke" ]]; then
    shift
    python -m pytest -x -q -k "not models and not perf" "$@"
    python tools/lint/retrace_guard.py
    # snapshot the committed baselines BEFORE the quick benches overwrite
    # them, then fail loudly if the fresh rates regressed past the
    # tolerance band (or a wire-codec parity probe broke)
    baseline="$(mktemp /tmp/bench_baseline.XXXXXX.json)"
    wire_baseline="$(mktemp /tmp/wire_baseline.XXXXXX.json)"
    robust_baseline="$(mktemp /tmp/robust_baseline.XXXXXX.json)"
    serving_baseline="$(mktemp /tmp/serving_baseline.XXXXXX.json)"
    trap 'rm -f "$baseline" "$wire_baseline" "$robust_baseline" "$serving_baseline"' EXIT
    # mktemp pre-creates an EMPTY file: remove it so a tree without a
    # committed baseline takes the checker's "no baseline" skip path
    # instead of failing to parse zero bytes of JSON
    rm -f "$baseline" "$wire_baseline" "$robust_baseline" "$serving_baseline"
    cp BENCH_population_scaling.json "$baseline" 2>/dev/null || true
    cp BENCH_wire_quantization.json "$wire_baseline" 2>/dev/null || true
    cp BENCH_robustness.json "$robust_baseline" 2>/dev/null || true
    cp BENCH_serving.json "$serving_baseline" 2>/dev/null || true
    python -m benchmarks.run --quick \
        --only population_scaling,wire_quantization,robustness,serving
    python tools/check_bench_regression.py \
        --pair "$baseline" BENCH_population_scaling.json \
        --pair "$wire_baseline" BENCH_wire_quantization.json \
        --pair "$robust_baseline" BENCH_robustness.json \
        --pair "$serving_baseline" BENCH_serving.json
    exit 0
fi
exec python -m pytest -x -q "$@"
