"""Tiered-storage residency guard (ISSUE 5 satellite; run by
scripts/run_tests.sh).

Three checks over the tiering plane (adapm_tpu/tier, docs/MEMORY.md):

1. ADAPTATION: under a zipf-skewed pull workload with device-hot
   capacity capped at 25% of the keys, the score-driven promotion
   worker must converge the hot set onto the head of the distribution —
   measured hot-hit rate over the post-adaptation window >= 0.9
   (ADAPM_TIER_HIT_MIN overrides). The workload's skew puts ~97% of
   accesses in the top quarter, so a broken replacement policy (random,
   FIFO, or thrashing) lands far below the bar while measurement noise
   moves it by fractions of a point.

2. CORRECTNESS FLOOR: the ALL-COLD configuration (tier on, minimal hot
   pool, promotion never driven) must return bit-identical reads to an
   untiered server initialized with the same values — the cold path
   serves slowly, never wrongly. Servers run SEQUENTIALLY (two live
   servers sharing one virtual device set can interleave sharded
   programs from different lock domains and deadlock XLA-CPU's
   collective rendezvous — same constraint as tests/test_tier.py).

3. TIMING GUARD: with the hot pool sized at 100% of the keys and
   everything promoted, the tiered pull path must stay within
   ADAPM_TIER_RATIO_MAX (default 2.5) of the untiered pull path —
   MEDIAN-pairwise-ratio over per-batch best-of-3 timings, per the
   check-script conventions (metrics_overhead_check.py). Guard sizing:
   the real failure mode — a hot-path residency resolve doing per-key
   Python, or a device sync per gather — costs 5-50x, while this
   shared 2-core box's scheduler noise moves the recorded medians
   between ~0.7 and ~1.6 across runs (the tiered pull is at parity
   with untiered; the smaller device pool even wins some runs).
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
    os.environ["XLA_FLAGS"] = " ".join([_flags, mesh_flags(8)]).strip()

import numpy as np  # noqa: E402

E = 8192
L = 16
B = 512
SKEW = 48.0  # key = E * u^SKEW; P(top 25%) = 0.25^(1/48) ~= 0.971


def _build(tier: bool, hot_rows: int, init: np.ndarray):
    import adapm_tpu
    import jax
    from adapm_tpu.config import SystemOptions

    jax.config.update("jax_platforms", "cpu")
    srv = adapm_tpu.setup(E, L, opts=SystemOptions(
        sync_max_per_sec=0, prefetch=False,
        tier=tier, tier_hot_rows=hot_rows))
    if tier:
        # deterministic adaptation: maintenance is driven explicitly
        srv.tier.engine.kick = lambda: None
    w = srv.make_worker(0)
    w.set(np.arange(E), init)
    srv.block()
    return srv, w


def _schedule(n_batches: int):
    rng = np.random.default_rng(7)
    return [(E * rng.random(B) ** SKEW).astype(np.int64).clip(0, E - 1)
            for _ in range(n_batches)]


def main() -> int:
    hit_min = float(os.environ.get("ADAPM_TIER_HIT_MIN", "0.9"))
    ratio_max = float(os.environ.get("ADAPM_TIER_RATIO_MAX", "2.5"))
    init = np.random.default_rng(1).normal(size=(E, L)).astype(np.float32)
    import jax
    S = len(jax.devices())

    # -- 1. adaptation: 25% hot capacity, zipf pulls -----------------------
    adapt, measure = 30, 30
    sched = _schedule(adapt + measure)
    srv, w = _build(True, max(8, E // 4 // S), init)
    for b in sched[:adapt]:
        w.pull_sync(b)
        srv.tier.maintain()
    st = srv.stores[0]
    h0, c0 = st.tier_hot_hits, st.tier_cold_hits
    for b in sched[adapt:]:
        w.pull_sync(b)
        srv.tier.maintain()
    dh = st.tier_hot_hits - h0
    dc = st.tier_cold_hits - c0
    hit = dh / max(1, dh + dc)
    rep = srv.tier.report()
    srv.shutdown()
    print(f"[tier-check] adaptation: hot-hit {hit:.4f} over {measure} "
          f"post-adaptation batches at 25% capacity (floor {hit_min}); "
          f"promotions={rep['promotions']} demotions={rep['demotions']}")
    if hit < hit_min:
        print("[tier-check] FAILED: the promotion policy did not "
              "converge the hot set onto the zipf head — check the "
              "score/eviction policy in tier/promote.py",
              file=sys.stderr)
        return 1

    # -- 2+3. untiered reference reads + timings (sequential servers) -----
    t_sched = _schedule(16)
    ref, wr = _build(False, 0, init)
    ref_out = [np.asarray(wr.pull_sync(b)) for b in t_sched]  # warm + ref

    def _time_batches(worker):
        """Per-batch BEST-of-3 pull wall: this shared 2-core box's
        scheduler spikes individual pulls by >10x; the min is the
        undisturbed cost (same rationale as serve_latency_check's
        min-pairwise guard)."""
        best = np.full(len(t_sched), np.inf)
        for _ in range(3):
            for i, b in enumerate(t_sched):
                t0 = time.perf_counter()
                worker.pull_sync(b)
                best[i] = min(best[i], time.perf_counter() - t0)
        return best

    t_ref = _time_batches(wr)
    ref.shutdown()

    # all-cold: minimal hot pool, promotion never driven -> every owner
    # read goes through the cold path; bit-identity is the floor
    cold_srv, wc = _build(True, 8, init)
    for i, b in enumerate(t_sched):
        got = np.asarray(wc.pull_sync(b))
        if not np.array_equal(got, ref_out[i]):
            print(f"[tier-check] FAILED: all-cold read of batch {i} "
                  f"diverged from the untiered reference "
                  f"({int((got != ref_out[i]).sum())} floats)",
                  file=sys.stderr)
            cold_srv.shutdown()
            return 1
    st = cold_srv.stores[0]
    assert st.tier_cold_hits > 0, \
        "all-cold config never exercised the cold path"
    cold_srv.shutdown()
    print(f"[tier-check] all-cold: {len(t_sched)} batches bit-identical "
          f"to the untiered reference (cold-served entries: "
          f"{st.tier_cold_hits})")

    # all-hot: full-capacity pool, everything promoted up front
    hot_srv, wh = _build(True, -(-E // S), init)
    hot_srv.tier.promote_keys(np.arange(E))
    for b in t_sched:
        wh.pull_sync(b)  # warm the tiered gather buckets
    t_hot = _time_batches(wh)
    st = hot_srv.stores[0]
    hot_srv.shutdown()
    pairs = sorted(h / r for h, r in zip(t_hot, t_ref))
    median = pairs[len(pairs) // 2]
    print(f"[tier-check] timing: all-hot/untiered per-batch ratios min "
          f"{pairs[0]:.3f} / median {median:.3f} / max {pairs[-1]:.3f} "
          f"(guard: median < {ratio_max:.2f})")
    if median >= ratio_max:
        print("[tier-check] FAILED: the all-hot tiered pull path costs "
              "a multiple of the untiered path — check the residency "
              "resolve in tier/coldpath.py split_owner for per-key "
              "Python or device syncs", file=sys.stderr)
        return 1
    print("[tier-check] OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
