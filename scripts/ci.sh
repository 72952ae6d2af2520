#!/usr/bin/env bash
# CI entry point: tier-1 tests + a fast benchmark smoke pass.
#
#   scripts/ci.sh             # full tier-1 + engine_perf smoke (~2 min)
#   SKIP_BENCH=1 scripts/ci.sh  # tests only
#
# Exits nonzero on any test failure or benchmark error. The smoke bench
# also writes machine-readable rows to results/BENCH_engine.json so the
# perf trajectory is comparable across PRs.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

python -m pytest -x -q

if [ "${SKIP_BENCH:-0}" != "1" ]; then
    mkdir -p results
    python -m benchmarks.run --json results/BENCH_engine.json engine_perf
    # ranking smoke: lexsort-vs-segmented + region-vs-segmented rows
    python -m benchmarks.run --json results/BENCH_ranking.json ranking
    # recovery smoke: crash -> restore -> catch-up replay must beat real time
    python -m benchmarks.run --json results/BENCH_recovery.json recovery
    # store smoke: region-vs-fused-vs-twopass insert rows (the PR 4 layout)
    python -m benchmarks.run --json results/BENCH_store.json store
    # overload smoke: 50x flash crowd -> spike throughput, ticks-to-SLO
    # recovery, shed fraction (the degradation-ladder contract)
    python -m benchmarks.run --json results/BENCH_overload.json overload
    # fleet chaos smoke: leader kill mid-segment + follower kill under a
    # 50x spike -- zero failed requests, epoch-fenced failover, healed log
    python -m benchmarks.run --json results/BENCH_fleet.json fleet
    # compaction smoke: 2000-tick run -- on-disk bytes bounded by the
    # working set (vs linear growth), base+tail replay bit-exact vs
    # replay-from-zero, fold pause p95
    python -m benchmarks.run --json results/BENCH_compaction.json compaction
    # autotune smoke: tuned-vs-untuned rows per hot path (tuned must be
    # >= 0.95x the best candidate) + the 16384-batch cliff (tuned chunking
    # must hold within 25% of the 4096 peak) -- both asserted in-bench
    python -m benchmarks.run --json results/BENCH_autotune.json autotune
fi
