#!/usr/bin/env bash
# CI entry point.
#
# Default = fast split: collection sanity check, then everything not marked
# `slow` (the 20k-point acceptance runs). Tier-1 verify (see ROADMAP.md)
# remains the FULL suite: run with CI_MARKERS="" or call pytest directly.
#
#   scripts/ci.sh                 # fast: -m "not slow" (graph/quant/serve
#                                 #   unit + property tests included)
#   CI_MARKERS="slow" scripts/ci.sh  # slow split only: the 20k acceptance
#                                 #   runs (api, quantized, graph)
#   CI_MARKERS="" scripts/ci.sh   # full suite (tier-1 equivalent)
#   CI_BENCH=1 scripts/ci.sh      # + bench regression gate: rerun the
#                                 #   serving bench, compare against the
#                                 #   committed results/BENCH_*.json via
#                                 #   scripts/check_bench.py
#   CI_CHURN=1 scripts/ci.sh      # + churn soak: live mutation under
#                                 #   load (benchmarks/table7_churn.py),
#                                 #   gated by check_bench's churn block
#                                 #   (tombstones, drops, recall ratio)
#   CI_AUTOTUNE=1 scripts/ci.sh   # + self-tuning gate: re-sweep the
#                                 #   operating curves and verify tuned
#                                 #   points hit their recall SLOs with
#                                 #   >= 30% fewer distance evals than
#                                 #   the hand-picked defaults
#                                 #   (benchmarks/table8_autotune.py)
#   CI_SKIP_TESTS=1 CI_BENCH=1 scripts/ci.sh   # bench gate only
#   CI_SKIP_LINT=1 scripts/ci.sh  # skip the static-analysis gate
#   scripts/ci.sh -k quant        # extra pytest args pass through
#
# Every invocation (unless CI_SKIP_LINT=1) starts with the static-analysis
# gate: scripts/lint.py runs the repro.analysis checkers (jit-purity,
# kernel-contract, fingerprint) over src/ and fails the build on any
# finding.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Static analysis first: pure-AST (no jax import), so it verdicts in
# ~a second — an impure jit function, broken kernel triple, or unhashed
# index attribute fails CI before a single test runs. CI_SKIP_LINT=1
# opts out (e.g. the bench-only invocation on a box without the repo's
# scripts on PATH).
if [ "${CI_SKIP_LINT:-0}" != "1" ]; then
    python scripts/lint.py
fi

# Import errors must fail loudly before any test runs — a module that
# doesn't collect is a broken build, not 0 skipped tests. pytest writes
# collection errors to stdout, so capture and replay them on failure
# (quiet on success).
if ! collect_out=$(python -m pytest --collect-only -q 2>&1); then
    echo "$collect_out"
    echo "FATAL: test collection failed (import error?)" >&2
    exit 1
fi

# Every suite that guards a subsystem contract must stay collected: a
# rename/deselection that silently drops one is a coverage regression,
# not a green build.
REQUIRED_SUITES=(api properties kernels quantized graph serve sharded
                 mutation autotune tpu_compile)
for suite in "${REQUIRED_SUITES[@]}"; do
    if ! grep -q "test_${suite}" <<<"$collect_out"; then
        echo "FATAL: tests/test_${suite}.py not collected" >&2
        exit 1
    fi
done

# Every Pallas kernel triple must keep its parity cases collected (the
# shared harness parametrizes test ids by kernel name) — dropping one
# silently un-gates that kernel's pad/edge paths.
REQUIRED_KERNELS=(l2_topk rae_encode flash_decode embedding_bag pq_adc
                  graph_beam graph_beam_q topk_merge ivf_scan)
for kern in "${REQUIRED_KERNELS[@]}"; do
    if ! grep -q "${kern}" <<<"$collect_out"; then
        echo "FATAL: kernel-parity cases for ${kern} not collected" >&2
        exit 1
    fi
done

if [ "${CI_SKIP_TESTS:-0}" != "1" ]; then
    MARKERS="${CI_MARKERS-not slow}"
    # The slow (nightly) split exercises the device-parallel sharded path:
    # force 8 host devices so mesh tests run on CPU-only runners. Exact
    # match on purpose — the default "not slow" must NOT trip this.
    if [ "${CI_MARKERS-}" = "slow" ]; then
        export XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}"
    fi
    if [ -n "$MARKERS" ]; then
        python -m pytest -x -q -m "$MARKERS" "$@"
    else
        python -m pytest -x -q "$@"
    fi
fi

# Bench regression gate: snapshot the committed baselines, rerun the
# selected benches (CPU-budget), and fail on recall/QPS regression.
# check_bench discovers BENCH_*.json by glob on both sides — benches not
# rerun here compare equal to their own snapshot, so no hardcoded list.
# CI_BENCH reruns the serving bench; CI_CHURN additionally soaks the
# mutable tiers under concurrent insert/delete/query load (its gates —
# zero tombstone violations, zero dropped queries, recall ratio vs the
# static twin — are correctness, not perf, so they hold on any box).
# The machine-readable verdict lands in results/check_bench_report.json
# for CI to upload alongside the fresh BENCH_*.json files.
if [ "${CI_BENCH:-0}" = "1" ] || [ "${CI_CHURN:-0}" = "1" ] \
        || [ "${CI_AUTOTUNE:-0}" = "1" ]; then
    baseline_dir=$(mktemp -d)
    trap 'rm -rf "$baseline_dir"' EXIT
    cp results/BENCH_*.json "$baseline_dir"/
    if [ "${CI_BENCH:-0}" = "1" ]; then
        python -m benchmarks.table5_serve --quick
    fi
    if [ "${CI_CHURN:-0}" = "1" ]; then
        python -m benchmarks.table7_churn --quick
    fi
    if [ "${CI_AUTOTUNE:-0}" = "1" ]; then
        python -m benchmarks.table8_autotune --quick
    fi
    python scripts/check_bench.py --baseline "$baseline_dir" \
        --candidate results --format json \
        | tee results/check_bench_report.json
fi
