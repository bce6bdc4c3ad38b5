#!/usr/bin/env bash
# Tiered local validation — the full suite, split to fit ~10-minute
# execution windows on a single-core box (this dev box has ONE cpu; see
# README "Testing"). Each tier is independently green; together they are
# the whole suite.
#
#   scripts/ci.sh           # all three tiers, sequential
#   scripts/ci.sh fast      # just the fast tier (~4 min)
set -eu
cd "$(dirname "$0")/.."

tier="${1:-all}"

run_lint() {
    # tpu-lint: static collective-contract + lock-order analysis over the
    # library and examples. The shipped baseline is EMPTY — any finding
    # is either a new bug or needs an inline justified suppression.
    echo "=== lint (tpu-lint static analysis) ==="
    python -m torchmpi_tpu.analysis torchmpi_tpu examples --strict \
        --baseline scripts/tpu_lint_baseline.json
}

run_fast() {
    run_lint
    # tier-1 runs ONCE under the instrumented-lock runtime monitor: every
    # lock in the threaded modules records real acquisition orders and the
    # conftest session gate fails on any inversion — the dynamic check
    # validating tpu-lint's static lock graph.
    echo "=== fast tier (unit + interpret p<=3 + single-process; lock monitor armed) ==="
    TORCHMPI_TPU_LOCK_MONITOR=1 python -m pytest tests/ -q -m "not slow"
    run_sim_smoke
    run_proc_smoke
}

run_sim_smoke() {
    # sim-smoke: a 1024-rank simulated fleet (REAL elastic coordinator,
    # schedule compiler and reshard planner on a modeled network) must
    # survive a death wave and a partition, with telemetry.analyze
    # reaching the verdict each scenario file names (hang naming the
    # dead ranks; resize-incomplete naming the partitioned ones) —
    # deterministically per seed. Pure host path — no jax backend. (The
    # simulator's own gates — the coordinator curve, supervised
    # death-wave recovery, synthesized-plan pricing — run at test-sized
    # worlds in tests/test_sim.py.)
    echo "=== sim-smoke (1k-rank fault scenarios) ==="
    simdir="$(mktemp -d)"
    # the EXIT trap survives set -eu: a failing scenario must not
    # strand ~2k telemetry dumps per retry in /tmp on the CI box
    trap 'rm -rf "$simdir"' EXIT
    JAX_PLATFORMS=cpu python -m torchmpi_tpu.sim death_wave partition \
        read_storm --ranks 1024 --out "$simdir"
    rm -rf "$simdir"
    # partition SUPERVISED at 1024 ranks: the recovery ladder (verdict
    # -> evict the wave -> committed shrink -> training resumed) per
    # the scenario's expected.recovery contract. death_wave's
    # supervised coverage is tests/test_sim.py's
    # (check_supervised_recovery: bounded action count +
    # byte-identical journal replay), so it is not repeated here.
    JAX_PLATFORMS=cpu python -m torchmpi_tpu.sim --supervise \
        partition --ranks 1024 --out "$simdir"
    rm -rf "$simdir"
    # traffic_surge SUPERVISED at 1024 ranks: the serving-tier scenario
    # (diurnal open-loop surge against per-rank capacity) must drive the
    # load-verdict ladder end to end — overload -> scale-up through the
    # real coordinator join, brownout shedding with zero silent drops
    # while saturated, underload -> scale-down after the surge, with the
    # asymmetric hysteresis + shared cooldown bounding the resize count
    # (no flapping) — per expected.recovery, deterministically per seed.
    JAX_PLATFORMS=cpu python -m torchmpi_tpu.sim --supervise \
        traffic_surge --ranks 1024 --out "$simdir"
    rm -rf "$simdir"
}

run_proc_smoke() {
    # the multi-process smokes: each launches real worker processes on
    # the CPU and checks what they leave behind. (What one process can
    # assert — zero compiles after precompile(), bitwise twins,
    # exactly-once audits — is in tier-1, tests/.)
    # flight-recorder/analyzer smoke: a short 2-proc job with telemetry on
    # must yield a merged per-rank Perfetto trace and a clean
    # `desync: none` analyzer report.
    echo "=== telemetry smoke (2-proc flight recorder + analyzer) ==="
    python scripts/telemetry_smoke.py
    # causal-tracing smoke: the same 2-proc shape with a trace-stamped
    # step loop must yield >=1 CROSS-RANK flow arrow in the merged
    # Perfetto trace and a critical-path attribution whose bucket sums
    # cover >=95% of each rank's step wall time.
    echo "=== trace smoke (2-proc causal flows + critical path) ==="
    python scripts/trace_smoke.py
    # overlap smoke: the same 2-proc shape drives GradientBuckets
    # through the 'none' and 'reverse' flush schedules; the analyzer
    # must stay `desync: none` (scheduled flushes are rank-local
    # bookkeeping, not divergence) and every rank's reverse-order row
    # in the measured overlap ledger must strictly beat its
    # all-at-once baseline row, with bitwise-identical gradients.
    echo "=== overlap smoke (2-proc scheduled flush + measured ledger) ==="
    python scripts/overlap_smoke.py
    # live-plane smoke: a 2-proc job with --telemetry-live must serve
    # fleet Prometheus + JSON (per-rank seq high-waters) and a streaming
    # `desync: none` verdict WHILE still running, the top CLI must
    # render both ranks, and a clean shutdown must leave no exporter
    # threads behind.
    echo "=== live telemetry smoke (2-proc streaming aggregator) ==="
    python scripts/live_smoke.py
    # resize smoke: a 2-proc live-elastic run must survive an operator
    # grow (2->3) and shrink (3->2) through the launcher without any
    # relaunch, with `desync: none` and every live rank inside every
    # resize.* epoch barrier per telemetry.analyze.
    echo "=== resize smoke (2-proc live-elastic grow/shrink) ==="
    python scripts/elastic_smoke.py
    # recover smoke: a 2-proc --elastic --supervise run loses a worker
    # to a hard mid-train kill and must self-heal with no operator
    # input — the supervisor's evict-shrink on /actions mid-run, the
    # survivor finishing at world=1, and `desync: none` from the
    # analyzer.
    echo "=== recover smoke (2-proc supervised kill -> auto-shrink) ==="
    python scripts/recover_smoke.py
    # serve smoke: a 2-proc serving job — REQUEST traffic over a real
    # peer channel against an InferenceServer while a background
    # downpour trainer publishes — must observe >= 1 weight swap (and
    # the client >= 2 distinct reply versions ON the wire), answer or
    # shed-with-retry every request (zero drops), shut down cleanly,
    # and leave `desync: none` telemetry.
    echo "=== serve smoke (2-proc serving tier + background downpour) ==="
    python scripts/serve_smoke.py
}

run_slow_a() {
    echo "=== slow tier A (multi-process + e2e examples) ==="
    python -m pytest tests/test_multiprocess.py tests/test_examples.py -q
}

run_slow_b() {
    echo "=== slow tier B (wide interpret sweeps + heavy engine/models) ==="
    python -m pytest tests/test_ops.py tests/test_parallel.py \
        tests/test_lm.py tests/test_engine.py tests/test_native.py \
        tests/test_scale_breadth.py -q -m slow
}

case "$tier" in
    lint) run_lint ;;
    fast) run_fast ;;
    sim-smoke) run_sim_smoke ;;
    proc-smoke) run_proc_smoke ;;
    slow-a) run_slow_a ;;
    slow-b) run_slow_b ;;
    all) run_fast; run_slow_a; run_slow_b ;;
    *) echo "usage: scripts/ci.sh [lint|fast|sim-smoke|proc-smoke|slow-a|slow-b|all]" >&2; exit 2 ;;
esac
echo "Success"
