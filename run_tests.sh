#!/usr/bin/env bash
# Sharded test runner: one pytest process per test file.
#
# Rationale: the full suite compiles several hundred XLA programs; on this
# image the XLA:CPU backend segfaults once a single process has aged
# through roughly ~600 compiles. Root-caused in round 5 by two
# instrumented single-process runs (PYTHONFAULTHANDLER; their logs are
# in git history, deleted in PR 23): both died at the same ~59% point
# of tests/ (test_tpcds), once inside persistent-cache serialization
# (put_executable_and_time) and once — with cache writes disabled via DFTPU_TEST_CACHE_WRITES=0 — inside
# backend_compile_and_load itself. Crash site moves, trigger point does
# not: process-age heap corruption in this image's XLA:CPU, independent
# of the compile cache, not reachable from library code. Every file
# passes in isolation; process-per-file keeps each XLA instance young
# and makes a crash attributable.
set -u
# Deterministic fault-injection seed (tests/test_fault_tolerance.py +
# runtime/chaos.py): exported and echoed so a chaos-test failure is
# reproducible by re-running with the printed seed.
export DFTPU_CHAOS_SEED="${DFTPU_CHAOS_SEED:-20260803}"
echo "DFTPU_CHAOS_SEED=$DFTPU_CHAOS_SEED"
# Default to skipping @pytest.mark.slow (heavy multi-fault chaos sweeps):
# their extra XLA compiles age a process toward the crash this script
# exists to avoid. DFTPU_TEST_MARKERS="" runs everything.
MARKERS="${DFTPU_TEST_MARKERS-not slow}"
MARKER_ARGS=()
[ -n "$MARKERS" ] && MARKER_ARGS=(-m "$MARKERS")
FAILED=()
# Tracer-safety lint gate FIRST (tools/check_tracer_safety.py): pure-AST,
# no jax/device/network — fails in milliseconds on a tracer-coercion /
# determinism violation not covered by tools/tracer_safety_allowlist.txt,
# before any XLA compile is paid.
echo "=== tools/check_tracer_safety.py (tracer-safety lint gate)"
if ! python tools/check_tracer_safety.py; then
    echo "LINT FAILED: tracer-safety violations (see above; intentional"
    echo "exceptions go in tools/tracer_safety_allowlist.txt with a"
    echo "justification)"
    FAILED+=("tools/check_tracer_safety.py[lint-gate]")
fi
# Concurrency-safety lint gate (tools/check_concurrency.py): pure-AST,
# sub-second — guarded-by discipline (DFTPU201-205) and the static
# lock-ordering graph (DFTPU206/207) over the whole package, before any
# XLA compile is paid. Stale allowlist entries fail the gate too.
echo "=== tools/check_concurrency.py (concurrency-safety lint gate)"
if ! python tools/check_concurrency.py; then
    echo "LINT FAILED: concurrency-safety violations (see above;"
    echo "intentional exceptions go in tools/concurrency_allowlist.txt"
    echo "with a justification)"
    FAILED+=("tools/check_concurrency.py[lint-gate]")
fi
# Resource-lifecycle lint gate (tools/check_resource_lifecycle.py):
# pure-AST, sub-second — declared acquire/release discipline
# (DFTPU301-307) over the whole package, before any XLA compile is
# paid. Stale allowlist entries fail the gate too.
echo "=== tools/check_resource_lifecycle.py (resource-lifecycle lint gate)"
if ! python tools/check_resource_lifecycle.py; then
    echo "LINT FAILED: resource-lifecycle violations (see above;"
    echo "intentional exceptions go in tools/resource_allowlist.txt"
    echo "with a justification)"
    FAILED+=("tools/check_resource_lifecycle.py[lint-gate]")
fi
# Static-verifier gate SECOND (tests/test_plan_verify.py): the seeded
# malformed-plan classes must each be rejected with their DFTPU0xx code,
# and the snapshot-suite/inlined clean sweep must verify with zero errors
# (the rest of the suite re-checks this implicitly: conftest exports
# DFTPU_VERIFY_PLANS=strict, so every planned query is verified).
echo "=== tests/test_plan_verify.py (static plan-verifier gate)"
if ! python -m pytest tests/test_plan_verify.py -q --no-header \
        -p no:cacheprovider "${MARKER_ARGS[@]}" "$@"; then
    echo "VERIFY FAILED: static plan verifier gate (plan/verify.py)"
    FAILED+=("tests/test_plan_verify.py[verify-gate]")
fi
# Recompile-regression gate (tests/test_recompile_budget.py): three
# TPC-H templates re-submitted with varied literals must perform zero new
# XLA compiles (plan/fingerprint.py literal hoisting + fingerprint-keyed
# program caches). Runs in its own young process like every other file;
# ordering it ahead of the per-file loop makes a serving-hot-path compile
# regression the first EXECUTION failure an operator sees (the two static
# gates above it are sub-second).
echo "=== tests/test_recompile_budget.py (recompile-regression gate)"
if ! python -m pytest tests/test_recompile_budget.py -q --no-header \
        -p no:cacheprovider "${MARKER_ARGS[@]}" "$@"; then
    FAILED+=("tests/test_recompile_budget.py[gate]")
fi
# Stage-DAG scheduler gate (tests/test_stage_scheduler.py): concurrent
# vs sequential stage scheduling must stay byte-identical (incl. under a
# seeded chaos schedule), the overlap factor must exceed 1.0 on bushy
# plans, and a fatal error must cancel + release in-flight siblings.
# INSTRUMENTED (race-harness gate, runtime/lockcheck.py): this gate and
# the serving + data-plane gates below export DFTPU_LOCK_CHECK=1, so
# every seeded chaos/churn schedule doubles as a deadlock/race harness —
# per-thread acquisition stacks, observed-vs-static lock-order
# assertion (a cycle raises with both stacks instead of hanging), and
# same results byte-identical under instrumentation.
echo "=== tests/test_stage_scheduler.py (stage-DAG scheduler gate, DFTPU_LOCK_CHECK=1)"
if ! env DFTPU_LOCK_CHECK=1 python -m pytest tests/test_stage_scheduler.py \
        -q --no-header \
        -p no:cacheprovider "${MARKER_ARGS[@]}" "$@"; then
    FAILED+=("tests/test_stage_scheduler.py[gate+lockcheck]")
fi
# Serving gate (tests/test_serving.py): the multi-query tier —
# N concurrent clients over one cluster must produce byte-identical
# results vs sequential execution (incl. under seeded chaos + membership
# churn), admission control must queue instead of over-committing, the
# global cross-query scheduler must respect its slot bound and fair-share
# policy, and prepared-statement serving must perform zero new XLA
# traces across parameter variations (the recompile gate's serving arm).
# Runs under DFTPU_LOCK_CHECK=1 (see the race-harness note above): the
# 8-thread mixed run is the widest cross-thread schedule in the suite.
echo "=== tests/test_serving.py (multi-query serving gate, DFTPU_LOCK_CHECK=1 DFTPU_LEAK_CHECK=strict)"
if ! env DFTPU_LOCK_CHECK=1 DFTPU_LEAK_CHECK=strict python -m pytest tests/test_serving.py \
        -q --no-header \
        -p no:cacheprovider "${MARKER_ARGS[@]}" "$@"; then
    FAILED+=("tests/test_serving.py[gate+lockcheck]")
fi
# Hedging + query-recovery gate (tests/test_hedging_recovery.py):
# straggler hedging — hedge-fires-and-winner-wins byte-identity, loser
# slice release to zero, no breaker trip on hedge loss, in-flight hedge
# budget bound — and query checkpoint/resume: a query interrupted after
# N completed stages resumes on a fresh coordinator/session from its
# staged frontier byte-identically, falling back on fingerprint mismatch
# or staged-slice loss (departed worker), zero leaked slices either way.
# Deterministic under DFTPU_CHAOS_SEED; runs under DFTPU_LOCK_CHECK=1
# (hedge races + checkpoint saves are cross-thread schedules).
echo "=== tests/test_hedging_recovery.py (hedging + query-recovery gate, DFTPU_LOCK_CHECK=1 DFTPU_LEAK_CHECK=strict)"
if ! env DFTPU_LOCK_CHECK=1 DFTPU_LEAK_CHECK=strict python -m pytest tests/test_hedging_recovery.py \
        -q --no-header \
        -p no:cacheprovider "${MARKER_ARGS[@]}" "$@"; then
    FAILED+=("tests/test_hedging_recovery.py[gate+lockcheck]")
fi
# Memory-pressure gate (tests/test_memory_pressure.py): the enforced
# worker byte budget — spill-to-host + byte-exact refault, stream
# backpressure under store pressure, the serving pressure matrix
# (8-thread mixed TPC-H under a budget below the unconstrained peak:
# byte-identical, spill engaged, residency bounded), red-line load
# shedding (preempt -> recover() byte-identical), chaos kind="oom",
# checkpoint byte cap, zero leaked slices AND spill files. Runs under
# DFTPU_LOCK_CHECK=1: spill swaps, the red-line monitor, and producer
# backpressure are cross-thread schedules.
echo "=== tests/test_memory_pressure.py (memory-pressure gate, DFTPU_LOCK_CHECK=1 DFTPU_LEAK_CHECK=strict)"
if ! env DFTPU_LOCK_CHECK=1 DFTPU_LEAK_CHECK=strict python -m pytest tests/test_memory_pressure.py \
        -q --no-header \
        -p no:cacheprovider "${MARKER_ARGS[@]}" "$@"; then
    FAILED+=("tests/test_memory_pressure.py[gate+lockcheck]")
fi
# Telemetry gate (tests/test_telemetry.py): the cluster-wide telemetry
# pipeline — typed registry units, OpenMetrics exposition-format golden
# test, cross-transport get_metrics merge (in-process AND gRPC, with
# per-worker degradation), TelemetryHistory ring bounds, SLO attainment
# math, event-log/trace id correlation, console per-line degradation
# against empty/partial stores, and zero new XLA traces with telemetry +
# event logging active.
echo "=== tests/test_telemetry.py (telemetry gate)"
if ! python -m pytest tests/test_telemetry.py -q --no-header \
        -p no:cacheprovider "${MARKER_ARGS[@]}" "$@"; then
    FAILED+=("tests/test_telemetry.py[gate]")
fi
# Tracing gate (tests/test_tracing.py): the distributed-tracing
# subsystem — span-tree shape for distributed TPC-H (worker spans joined
# via cross-wire context propagation, in-process AND gRPC), retry/heal/
# cancel events under seeded chaos + membership churn, byte counters
# matching table nbytes, tracing=off adding zero spans AND zero new XLA
# traces, >= 95% query-wall coverage, serving-path isolation per query,
# and the DFTPU109 span-in-traced-code lint rule.
echo "=== tests/test_tracing.py (distributed-tracing gate)"
if ! python -m pytest tests/test_tracing.py -q --no-header \
        -p no:cacheprovider "${MARKER_ARGS[@]}" "$@"; then
    FAILED+=("tests/test_tracing.py[gate]")
fi
# Elasticity gate (tests/test_elasticity.py): dynamic membership —
# workers joining/leaving/draining MID-QUERY under seeded chaos schedules
# (DFTPU_CHAOS_SEED above) must keep TPC-H results byte-identical, leak
# zero TableStore slices, drain to zero in-flight before removal, and
# route tasks to mid-query joiners. The long churn+fault sweeps are
# @slow; DFTPU_TEST_MARKERS="" runs them.
echo "=== tests/test_elasticity.py (elastic-membership gate)"
if ! python -m pytest tests/test_elasticity.py -q --no-header \
        -p no:cacheprovider "${MARKER_ARGS[@]}" "$@"; then
    FAILED+=("tests/test_elasticity.py[gate]")
fi
# Zero-copy data-plane gate (tests/test_data_plane.py): buffer identity
# across put/get/view-slice on the in-process plane, refcounted release
# (partition drop + query-end sweep, incl. under chaos retries), TPC-H
# q5/q9 byte-identical between the view and copying planes, a peak-
# staged-bytes bound under the chaos retry schedule, and the >= 2x
# view-vs-copy chunk-plane rate bound.
# Runs under DFTPU_LOCK_CHECK=1: the 8-thread churn run exercises the
# TableStore/TaskRegistry lock pairs the static graph predicts.
echo "=== tests/test_data_plane.py (zero-copy data-plane gate, DFTPU_LOCK_CHECK=1 DFTPU_LEAK_CHECK=strict)"
if ! env DFTPU_LOCK_CHECK=1 DFTPU_LEAK_CHECK=strict python -m pytest tests/test_data_plane.py \
        -q --no-header \
        -p no:cacheprovider "${MARKER_ARGS[@]}" "$@"; then
    FAILED+=("tests/test_data_plane.py[gate+lockcheck]")
fi
# Pipelined-shuffle gate (tests/test_pipelined_shuffle.py): shuffle
# boundaries streaming partition slices into live feeds — byte-identical
# pipelined-vs-materialized across TPC-H shapes on peer AND peerless
# planes (incl. seeded chaos, membership churn, hedging), zero leaked
# slices, plane toggle = zero new XLA traces, StreamBudget cancel-wake,
# abandoned-puller accounting, and the statistics-driven partial-agg
# push-down (plan rewrite, eligibility guards, predicted-vs-measured
# exchange bytes). Runs under DFTPU_LOCK_CHECK=1: the feeder thread's
# cross-thread slice handoff (PartitionFeed/StreamScanExec) is exactly
# the schedule the PR 9 race harness exists for.
echo "=== tests/test_pipelined_shuffle.py (pipelined-shuffle gate, DFTPU_LOCK_CHECK=1 DFTPU_LEAK_CHECK=strict)"
if ! env DFTPU_LOCK_CHECK=1 DFTPU_LEAK_CHECK=strict python -m pytest tests/test_pipelined_shuffle.py \
        -q --no-header \
        -p no:cacheprovider "${MARKER_ARGS[@]}" "$@"; then
    FAILED+=("tests/test_pipelined_shuffle.py[gate+lockcheck]")
fi
# Runtime-adaptivity gate (tests/test_adaptivity.py): the closed-loop
# decision points (runtime/adaptivity.py) — skew-aware shuffle splitting
# under a seeded chaos kind="skew" schedule, the partial-aggregate
# bail-out probe (high-NDV mispredictions swap to PartialPassthroughExec
# within 10% of pushdown-off), and mid-query re-costing of unsubmitted
# stages — with TPC-H q3/q5/q18 byte-identical between every
# adaptation path forced ON and OFF under chaos + membership churn,
# replanned stages re-verified clean, and zero leaked slices. Runs
# under DFTPU_LOCK_CHECK=1: the probe/replan hooks sit inside the
# stage-DAG scheduler's cross-thread schedules.
echo "=== tests/test_adaptivity.py (runtime-adaptivity gate, DFTPU_LOCK_CHECK=1)"
if ! env DFTPU_LOCK_CHECK=1 python -m pytest tests/test_adaptivity.py \
        -q --no-header \
        -p no:cacheprovider "${MARKER_ARGS[@]}" "$@"; then
    FAILED+=("tests/test_adaptivity.py[gate+lockcheck]")
fi
# Shm + streaming-transfer data-plane gate (tests/test_shm_plane.py):
# the cross-process planes — segment refcount lifecycle (last release
# unlinks, zero leaked segments), spill-file -> segment hardlink
# composition, torn-segment SegmentError, per-connection wire-codec
# negotiation, adaptive per-column compression roundtrip, TPC-H
# q1/q3/q12/q18 byte-identical across data_plane in {unary,stream,shm}
# on a real gRPC cluster, zero new XLA traces on plane toggle, and the
# seeded chaos kind="segment_lost" degradation to the wire path. Runs
# under DFTPU_LOCK_CHECK=1: SegmentPool's decide-locked/do-unlocked
# publish/open discipline is exercised by concurrent partition pullers.
echo "=== tests/test_shm_plane.py (shm + streaming data-plane gate, DFTPU_LOCK_CHECK=1)"
if ! env DFTPU_LOCK_CHECK=1 python -m pytest tests/test_shm_plane.py \
        -q --no-header \
        -p no:cacheprovider "${MARKER_ARGS[@]}" "$@"; then
    FAILED+=("tests/test_shm_plane.py[gate+lockcheck]")
fi
# Multiway-join + global-hash-agg gate (tests/test_multiway_join.py):
# the fusion pass's two link forms (broadcast same-stage chains and
# identity re-shuffle deletion with dftpu_exchanges_deleted >= 2 on
# co-shuffled q21), cascaded-probe and global-hash-agg kernel parity vs
# the claim-loop oracles in interpret mode, MultiwayHashJoinExec
# byte-identity vs the binary chain it fused on both execution paths,
# TPC-H q5/q9/q21 fused-vs-unfused byte identity through the
# coordinator under seeded chaos + membership churn, exact
# global-agg-vs-merge aggregation, the measured-rows-only coordinator
# bailout, zero new XLA traces on resubmission, and the
# DFTPU011/012/023/025/034 verifier arms.
echo "=== tests/test_multiway_join.py (multiway-join + global-hash-agg gate)"
if ! python -m pytest tests/test_multiway_join.py \
        -q --no-header \
        -p no:cacheprovider "${MARKER_ARGS[@]}" "$@"; then
    FAILED+=("tests/test_multiway_join.py[gate]")
fi
# Result-cache gate (tests/test_result_cache.py): the fingerprint-keyed
# whole-result + sub-plan cache (runtime/result_cache.py) — hit/miss/
# LRU/spill-refault unit arms, literal-variant correctness, PlannerConfig
# and catalog-generation key misses, register_table invalidation (no
# stale reads), sub-plan prefix reuse across distinct queries, TPC-H
# byte-identity cache-on vs cache-off (incl. seeded chaos + membership
# churn), zero new XLA traces on a hit, and the 8-thread serving
# stampede (concurrent identical submissions execute once). Runs under
# DFTPU_LOCK_CHECK=1 + strict leak sweeps: the single-flight Condition
# and the cache's unattributed store entries are exactly what the two
# harnesses exist to police.
echo "=== tests/test_result_cache.py (result-cache gate, DFTPU_LOCK_CHECK=1 DFTPU_LEAK_CHECK=strict)"
if ! env DFTPU_LOCK_CHECK=1 DFTPU_LEAK_CHECK=strict python -m pytest tests/test_result_cache.py \
        -q --no-header \
        -p no:cacheprovider "${MARKER_ARGS[@]}" "$@"; then
    FAILED+=("tests/test_result_cache.py[gate+lockcheck]")
fi
for f in tests/test_*.py; do
    [ "$f" = "tests/test_memory_pressure.py" ] && continue  # ran above
    [ "$f" = "tests/test_multiway_join.py" ] && continue  # ran above (gate)
    [ "$f" = "tests/test_recompile_budget.py" ] && continue  # ran above
    [ "$f" = "tests/test_pipelined_shuffle.py" ] && continue  # ran above
    [ "$f" = "tests/test_plan_verify.py" ] && continue  # ran above (gate)
    [ "$f" = "tests/test_stage_scheduler.py" ] && continue  # ran above
    [ "$f" = "tests/test_serving.py" ] && continue  # ran above (gate)
    [ "$f" = "tests/test_hedging_recovery.py" ] && continue  # ran above
    [ "$f" = "tests/test_tracing.py" ] && continue  # ran above (gate)
    [ "$f" = "tests/test_telemetry.py" ] && continue  # ran above (gate)
    [ "$f" = "tests/test_elasticity.py" ] && continue  # ran above (gate)
    [ "$f" = "tests/test_data_plane.py" ] && continue  # ran above (gate)
    [ "$f" = "tests/test_shm_plane.py" ] && continue  # ran above (gate)
    [ "$f" = "tests/test_adaptivity.py" ] && continue  # ran above (gate)
    [ "$f" = "tests/test_result_cache.py" ] && continue  # ran above (gate)
    echo "=== $f"
    if ! python -m pytest "$f" -q --no-header -p no:cacheprovider \
            "${MARKER_ARGS[@]}" "$@"; then
        FAILED+=("$f")
    fi
done
if [ ${#FAILED[@]} -gt 0 ]; then
    echo "FAILED FILES: ${FAILED[*]}"
    exit 1
fi
echo "ALL FILES PASSED"
