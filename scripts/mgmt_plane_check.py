"""Management-plane microbench guard (ISSUE 3 satellite; run by
scripts/run_tests.sh).

Times the planner's per-round host cost — snapshot + keep/drop/cross
partition + dirty filter over a replicated table, via real
`sync.run_round()` calls on an idle (fully dirty-filtered, zero
device dispatch) population — against a SHADOW implementation of the
pre-PR-3 set-based classification (per-key Python: `list(set)`,
`np.fromiter`, keep/drop listcomps) over the same population.

Methodology: same MEDIAN-pairwise-ratio pattern as
scripts/metrics_overhead_check.py — (vectorized, shadow) timings back
to back per repeat, guard on the median ratio. The guard is sized for
the real failure mode: reintroducing per-key Python into
`drain_intents`/`sync_channel`/`quiesce` makes the vectorized round
cost what the shadow costs, pushing the ratio to ~1.0 — an order of
magnitude past the threshold — while host-speed noise moves it by
percents. Recorded baseline on the reference host (2-core container,
8192 replicas): ratio ~0.04 (vectorized round ~0.2 ms vs shadow
~4 ms); threshold = a wide multiple of that, overridable via
ADAPM_MGMT_RATIO_MAX, and 1.15x headroom on a re-recorded baseline is
the intended tightening procedure when this host's numbers move.
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

REPLICAS = 8192


def build():
    import jax

    from adapm_tpu import Server
    from adapm_tpu.base import CLOCK_MAX, MgmtTechniques
    from adapm_tpu.config import SystemOptions
    from adapm_tpu.parallel.mesh import Mesh, MeshContext

    jax.config.update("jax_platforms", "cpu")
    mesh = MeshContext(Mesh(np.asarray(jax.devices("cpu")), ("kv",)))
    S = mesh.num_shards
    num_keys = int(REPLICAS * S / max(S - 1, 1)) + 256
    srv = Server(num_keys, 8, ctx=mesh, opts=SystemOptions(
        techniques=MgmtTechniques.REPLICATION_ONLY, sync_max_per_sec=0,
        prefetch=False, cache_slots_per_shard=REPLICAS + 256))
    w = srv.make_worker(1)
    keys = np.arange(num_keys)
    cand = keys[srv.ab.owner[keys] != w.shard][:REPLICAS]
    w.intent(cand, 0, CLOCK_MAX)
    srv.sync.run_round(force_intents=True, all_channels=True)
    srv.block()
    return srv, w


def shadow_classify(sync, items, min_clocks):
    """The pre-PR-3 per-key classification shape (set walk + fromiter +
    listcomps) — what sync_channel cost per round before the
    ReplicaTable rewrite, and what it must never cost again."""
    keep_mask = np.fromiter(
        (sync.intent_end[s, k] >= min_clocks[s] for k, s in items),
        np.uint8, len(items))
    keep = [it for it, m in zip(items, keep_mask) if m]
    drop = [it for it, m in zip(items, keep_mask) if not m]
    karr = np.fromiter((k for k, _ in keep), np.int64, len(keep))
    sarr = np.fromiter((s for _, s in keep), np.int32, len(keep))
    return karr, sarr, drop


def main() -> int:
    ratio_max = float(os.environ.get("ADAPM_MGMT_RATIO_MAX", "0.5"))
    rounds, repeats = 20, 7
    srv, w = build()
    live = int(sum(len(t) for t in srv.sync.replicas))
    assert live >= REPLICAS, f"setup failed: {live} replicas live"
    # the shadow's input: the replica population as the old set-of-tuples
    reps = set()
    for t in srv.sync.replicas:
        k, s = t.snapshot()
        reps |= {(int(a), int(b)) for a, b in zip(k, s)}
    shipped_before = srv.sync.stats.keys_synced
    pairs = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(rounds):
            srv.sync.run_round()
            w.advance_clock()
        t_vec = time.perf_counter() - t0
        mc = srv.shard_min_clocks()
        t0 = time.perf_counter()
        for _ in range(rounds):
            shadow_classify(srv.sync, list(reps), mc)
        t_shadow = time.perf_counter() - t0
        pairs.append(t_vec / t_shadow)
    # sanity: idle rounds over a clean table ship nothing (the dirty
    # filter is what makes the vectorized round O(live)-cheap)
    assert srv.sync.stats.keys_synced == shipped_before, \
        "idle rounds re-shipped clean replicas (dirty filter broken?)"
    srv.shutdown()
    pairs.sort()
    median = pairs[len(pairs) // 2]
    print(f"[mgmt-check] {live} replicas, {rounds} rounds x {repeats} "
          f"pairs: vec/shadow ratios min {pairs[0]:.3f} / median "
          f"{median:.3f} / max {pairs[-1]:.3f} (guard: median < "
          f"{ratio_max:.2f}; per-key Python in the round => ~1.0+)")
    if median >= ratio_max:
        print("[mgmt-check] FAILED: vectorized planner round costs a "
              "per-key-Python multiple — check drain_intents/"
              "sync_channel/quiesce for reintroduced set/fromiter/"
              "listcomp hot loops", file=sys.stderr)
        return 1
    print("[mgmt-check] OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
