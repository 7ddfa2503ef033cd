#!/usr/bin/env bash
# Tier-1 verification, exactly as the roadmap specifies, plus the offline
# guard: the suite must collect and pass with no network.
#
#   scripts/check.sh            # tier-1 + no-network guard + bench smoke
#   scripts/check.sh -k tet     # extra args forwarded to pytest
#                               # (bench smoke skipped when args are given)
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src:scripts${PYTHONPATH:+:$PYTHONPATH}"

# Static contract lint tier: envelope certification, schedule contract
# proofs, jaxpr/HLO structural rules (see src/repro/analysis/README.md).
# Gating and fully offline — nothing executes a kernel. Skipped when
# pytest args are forwarded so `scripts/check.sh -k foo` stays fast.
if [ "$#" -eq 0 ]; then
    echo "== contract lint =="
    python -m repro.analysis.lint -q --json artifacts/lint_report.json
fi

# -p _offline_guard turns any outbound connection attempt into a failure,
# so offline-collectability cannot regress silently.
python -m pytest -x -q -p _offline_guard "$@"

# Benchmark smoke tier: every benchmark script must still EXECUTE offline
# (tiny n, scan impls) so the scripts cannot silently rot between the
# occasions someone runs them at full scale.
if [ "$#" -eq 0 ]; then
    echo "== benchmarks --smoke =="
    python -m benchmarks.run --smoke
    # The packed mixed-position decode path also has its own CLI entry;
    # exercise it directly so the --decode argparse surface cannot rot
    # (benchmarks.run --smoke already covers the underlying run_decode).
    echo "== bench_packed --decode --smoke =="
    python -m benchmarks.bench_packed --decode --smoke
    # Same for the packed ragged-document training step (fwd + bwd through
    # the custom VJP): the --train surface and its packed < padded tile
    # assertion must keep executing offline.
    echo "== bench_packed --train --smoke =="
    python -m benchmarks.bench_packed --train --smoke
    # And the fused continuous-batching step: its own CLI surface plus the
    # 1-launch / fused == split tile assertions must keep executing
    # offline (benchmarks.run --smoke covers the underlying run()).
    echo "== bench_continuous --smoke =="
    python -m benchmarks.bench_continuous --smoke
    # Telemetry smoke tier: the benchmarks.run --smoke above wrote
    # artifacts/metrics.json, a trace JSONL, and appended a record to
    # BENCH_trajectory.json — all three must be schema-valid
    # (src/repro/obs/schema.py), so the metric/trace formats cannot
    # drift from their validators.
    echo "== obs validate (metrics.json / trajectory / trace) =="
    python -m repro.obs.validate artifacts/metrics.json \
        BENCH_trajectory.json artifacts/trace/*.jsonl
    # Fault-injection smoke tier: the serving engine under seeded
    # FaultPlans (launch errors, OOM admissions, poisoned tiles,
    # stragglers) must stay token-identical to fault-free, with every
    # degrade/quarantine schema-valid in the trace — run under the same
    # no-network guard as the test suite (PYTHONPATH includes scripts).
    echo "== resilience smoke (fault injection, offline) =="
    python -m repro.resilience.smoke
    # Fleet failover smoke tier: two replicas behind tile-cost routing
    # under an engine-killing plan, both step modes — migrated requests
    # must finish token-identically to a fault-free single engine, with
    # every failover/quarantine/rebalance event schema-valid.
    echo "== fleet resilience smoke (failover, offline) =="
    python -m repro.resilience.smoke --fleet
fi
