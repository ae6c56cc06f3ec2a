"""Unified-executor guard (ISSUE 6 satellite; run by
scripts/run_tests.sh).

Two assertions about adapm_tpu/exec that a regression would break
silently:

1. **Idle dispatches nothing.** An idle executor must start ZERO
   programs and dispatch ZERO device programs: its workers park on the
   executor condvar — no polling passes, no busy loop. Checked against
   `exec.programs_started` AND the stores' host-side gather/program
   counters over an idle second (same shape as serve_latency_check.py's
   idle guard).

2. **Overlap does not cost.** A tiered KGE-shaped workload with
   promotion churn (zipf pulls + pushes over a 25%-capacity hot pool,
   maintenance kicked throughout — promotion batch prep overlapping
   device scatters is exactly the GraphVite-style episodic overlap the
   executor exists for) must run at least as fast overlapped
   (multi-stream default) as serialized (--sys.exec.single_stream),
   within noise. Methodology: MEDIAN-pairwise-ratio per the
   mgmt_plane_check.py convention — (overlapped, serialized) timed back
   to back per repeat, guard on the median overlapped/serialized ratio.
   The real failure mode this catches is structural: an executor that
   serializes the training thread behind background streams (a lock
   held across dispatch, a gate held across device EXECUTION rather
   than enqueue) costs a MULTIPLE, pushing every pair well above 1. On
   this shared 2-core container individual pairs swing with scheduler
   noise (observed 0.57-1.70), so the guard is on the median and sized
   for that noise: median < 1.35 (override: ADAPM_EXEC_RATIO_MAX),
   recorded medians 1.00-1.17 — two cores leave little CPU for
   parallelism to win outright, so "within noise of serialized" is the
   honest pass bar here; the structural failure mode costs a multiple.
   The overlapped run must also record exec.overlap_fraction > 0 under
   churn (the acceptance criterion that >= 2 streams genuinely ran
   simultaneously at some point).
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    from xla_compat import mesh_flags
    os.environ["XLA_FLAGS"] = " ".join([_flags, mesh_flags(2)]).strip()

import numpy as np  # noqa: E402

NK = 4096
VLEN = 8
B = 64               # keys per batch
BATCHES = 60         # per timed repeat
REPEATS = 5
SKEW = 3             # zipf-ish: key = NK * u^SKEW


def build(single_stream: bool):
    import jax

    import adapm_tpu
    from adapm_tpu.config import SystemOptions

    jax.config.update("jax_platforms", "cpu")
    S = len(jax.devices())
    srv = adapm_tpu.setup(NK, VLEN, opts=SystemOptions(
        sync_max_per_sec=0, prefetch=False,
        tier=True, tier_hot_rows=max(8, NK // 4 // S),
        exec_single_stream=single_stream))
    w = srv.make_worker(0)
    rng = np.random.default_rng(0)
    w.wait(w.set(np.arange(NK),
                 rng.normal(size=(NK, VLEN)).astype(np.float32)))
    srv.block()
    return srv, w


def schedule(rng, n):
    return [(NK * rng.random(B) ** SKEW).astype(np.int64).clip(0, NK - 1)
            for _ in range(n)]


def run_workload(srv, w, batches, vals) -> float:
    """One timed pass: zipf pull + push per batch (cold misses kick the
    maintenance worker; promotion churn overlaps the training thread's
    dispatches on the overlapped executor), then settle — the drain is
    INSIDE the timing so a serialized executor pays its queued backlog
    where the overlapped one already retired it concurrently."""
    t0 = time.perf_counter()
    for i, b in enumerate(batches):
        w.pull_sync(b)
        w.push(b, vals)
        if i % 8 == 0:
            srv.tier.engine.kick()
    srv.exec.drain("tier", timeout=60)
    srv.exec.drain("tier_commit", timeout=60)
    srv.block()
    return time.perf_counter() - t0


def main() -> int:
    ratio_max = float(os.environ.get("ADAPM_EXEC_RATIO_MAX", "1.35"))
    rng = np.random.default_rng(7)
    vals = np.full((B, VLEN), 1e-4, dtype=np.float32)

    srv_o, w_o = build(False)      # overlapped default
    srv_s, w_s = build(True)       # serialized fallback

    # warm both (compiles every gather/scatter bucket + tier paths)
    warm = schedule(rng, 10)
    run_workload(srv_o, w_o, warm, vals)
    run_workload(srv_s, w_s, warm, vals)

    pairs = []
    for _ in range(REPEATS):
        batches = schedule(rng, BATCHES)
        t_over = run_workload(srv_o, w_o, batches, vals)
        t_ser = run_workload(srv_s, w_s, batches, vals)
        pairs.append(t_over / t_ser)
    overlap_frac = srv_o.exec.overlap_fraction()

    # -- idle guard: a parked executor starts nothing -------------------
    time.sleep(0.1)   # let the last maintenance pass park
    p0 = srv_o.exec.stats()["programs_started"]
    g0 = sum(s.gathers for s in srv_o.stores)
    time.sleep(1.0)
    p1 = srv_o.exec.stats()["programs_started"]
    g1 = sum(s.gathers for s in srv_o.stores)
    idle_ok = (p1 == p0) and (g1 == g0)

    srv_o.shutdown()
    srv_s.shutdown()
    pairs.sort()
    median = pairs[len(pairs) // 2]
    print(f"[exec-check] {BATCHES} batches x {REPEATS} pairs tiered "
          f"churn workload: overlapped/serialized ratios min "
          f"{pairs[0]:.3f} / median {median:.3f} / max {pairs[-1]:.3f} "
          f"(guard: median < {ratio_max:.2f}) | "
          f"overlap_fraction {overlap_frac:.3f} | "
          f"idle: programs {p1 - p0:+d}, gathers {g1 - g0:+d}")
    rc = 0
    if median >= ratio_max:
        print("[exec-check] FAILED: the overlapped executor no longer "
              "keeps up with the serialized fallback — check that the "
              "dispatch gate brackets only the ENQUEUE (never device "
              "execution) and that no stream holds the server lock "
              "across dispatch", file=sys.stderr)
        rc = 1
    if overlap_frac <= 0.0:
        print("[exec-check] FAILED: exec.overlap_fraction stayed 0 "
              "under promotion churn — streams never ran "
              "simultaneously; double-buffering is broken",
              file=sys.stderr)
        rc = 1
    if not idle_ok:
        print("[exec-check] FAILED: an idle executor started programs "
              "or dispatched gathers — workers must park on the "
              "executor condvar, never poll", file=sys.stderr)
        rc = 1
    if rc == 0:
        print("[exec-check] OK")
    return rc


if __name__ == "__main__":
    sys.exit(main())
