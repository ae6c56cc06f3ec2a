#!/usr/bin/env bash
# Test harness (reference tests/run_tests.sh). The reference loops its test
# binaries over --sys.techniques / --sampling.scheme variants from the
# shell; here those variants are pytest parameterizations inside the suite
# (test_consistency.py: all/replication_only/relocation_only;
# test_sampling.py: naive/preloc/pool/local x with/without replacement),
# so one pytest run covers the same matrix.
set -euo pipefail
cd "$(dirname "$0")/.."

# adapm-lint invariant gate FIRST (ISSUE 11): the AST analyzer checks
# the concurrency disciplines mechanically — gate coverage, the
# lock-narrowing rule, skip-wrappers, the raw-thread ban, donation
# lifetimes, revalidate-under-lock, metric-catalog drift — in
# milliseconds, before anything compiles a program. Zero unsuppressed
# findings, zero unused suppressions (docs/INVARIANTS.md;
# ADAPM_LINT_BASELINE is the incremental-adoption escape hatch)
python scripts/invariant_lint_check.py
# fast prefetch-pipeline smoke next: a staged-pull/plan-cache regression
# should fail in seconds, not after the full matrix
python -m pytest tests/test_prefetch.py -q
# metrics-overhead guard + duplicate-metric-name check (ISSUE 2): the
# registry must stay under its hot-path budget and no two subsystems may
# register the same metric (docs/OBSERVABILITY.md)
python scripts/metrics_overhead_check.py
# management-plane ratio guard (ISSUE 3): the vectorized planner round
# must stay a small fraction of the per-key-Python shadow cost —
# reintroduced set/fromiter/listcomp hot loops cost a multiple
python scripts/mgmt_plane_check.py
# serving-plane guard (ISSUE 4): coalesced lookups at 32 concurrent
# clients must beat sequential per-request pulls, and an idle serve
# loop must dispatch zero device programs
python scripts/serve_latency_check.py
# tiered-storage guard (ISSUE 5): under a zipf workload at 25% hot
# capacity the promotion policy must reach >= 0.9 hot-hit rate, the
# all-cold configuration must read bit-identically to untiered, and
# the all-hot tiered pull path must stay near parity with untiered
python scripts/tier_residency_check.py
# unified-executor guard (ISSUE 6): an idle executor starts zero
# programs (workers park on the condvar), and the overlapped default
# must keep up with the serialized single-stream fallback on a tiered
# promotion-churn workload (median pairwise ratio; overlap_fraction > 0)
python scripts/exec_overlap_check.py
# episodic-execution guard (ISSUE 14): on a beyond-hot-capacity zipf
# fused-step workload, the double-buffered episode/episode_commit
# pipeline must keep up with plain sequential execution (median
# pairwise ratio), record exec.overlap_fraction > 0 (prep genuinely
# overlapped compute), and dispatch nothing while idle
python scripts/episode_overlap_check.py
# compression-plane guard (ISSUE 8): a randomized push/promote/demote/
# sync storm with both features OFF must stay bit-identical to an
# untiered fp32 shadow (the pre-PR pin), the fp16/int8 storms must keep
# every read under the docs/MEMORY.md contract bound (the EF residual
# loop bounding drift), and compressed sync rounds must ship <= 0.55x
# (fp16) / 0.30x (int8) of the shadow's full-width bytes
python scripts/compress_drift_check.py
# SLO-autopilot guard (ISSUE 7): with --sys.serve.slo_ms set against an
# oversized micro-batch window, the closed-loop controller must walk
# max_wait_us DOWN and land the observed serve P99 within the tolerance
# band of the target (median of trailing measurement windows)
python scripts/slo_convergence_check.py
# trace-replay guard (ISSUE 15): a captured multi-plane storm must
# replay bit-identically (same seed + knobs, across 1x/10x logical
# speed), and a two-candidate knob sweep's ranked artifact must pick
# the same winner as the live-measured ordering on the same workload
python scripts/trace_replay_check.py
# fault drill (ISSUE 10): a seeded push/serve/promote/sync storm under
# injected transient faults must stay bit-identical to an uninjected
# shadow; a server killed mid-storm must restore from the incremental
# checkpoint chain bit-exactly within the recovery bound; lookups
# during the degraded restore window shed with ServeDegradedError
# (never a torn or stale read); and a 1%-dirty trickle's delta link
# must cost <= 10% of a full checkpoint
python scripts/fault_drill_check.py
# port-differential + fused-bag guard (ISSUE 16): the same seeded
# 5-plane storm run against the jax DevicePort and the pure-NumPy
# reference port must read bit-identically (plus a deterministic
# fp16/int8 wire-program differential on standalone tiered stores);
# device/refport.py must stay jax-free with zero lint suppressions;
# and the fused gather_pool bag read must beat gather-then-host-pool
# (median pairwise, < 0.9 on accelerators; near-parity bar on CPU
# hosts where the wire-byte saving is a memcpy — ADAPM_BAG_RATIO_MAX)
python scripts/portdiff_check.py
# decision-telemetry guard (ISSUE 17): a captured zipf storm's decision
# trace must carry a complete feature vector on every event, close
# >= 90% of outcome-attribution windows, export a byte-deterministic
# labeled dataset, and fold a strictly higher tier regret rate under a
# thrashing (tiny) hot pool than under an ample one
python scripts/decision_quality_check.py
# learned-policy promotion gate (ISSUE 18): the same thrashing-pool
# storm must train a byte-deterministic policy artifact whose learned
# tier policy strictly beats the heuristic on replayed tier regret
# while folding a bit-identical reads digest (a policy changes
# what/when, never values)
python scripts/policy_gate_check.py
# NetPort transport drill (ISSUE 19): a seeded two-node loopback storm
# under injected frame drop/dup/delay/partition must read bit-identical
# to an uninjected single-process shadow after every quiesce (lock-order
# sentinel armed); killing one node mid-storm must promote its replicas
# to mains within the bounded, recorded net.failover_s and the survivor
# must keep serving the covered keys bit-exactly
python scripts/net_storm_check.py
# freshness-SLO guard (ISSUE 20): with --sys.stream.freshness_slo_ms
# set tight against lazy static knobs (250 ms replica refresh, 2/s
# sync), the closed-loop controller must walk the levers in the
# correct direction on its first move and land the trailing-window
# event-to-servable freshness P99 within the tolerance band of the
# target (median of trailing windows; ADAPM_FRESHNESS_BAND)
python scripts/freshness_slo_check.py
python -m pytest tests/ -q "$@"
echo "ALL TESTS PASSED"
