"""NetPort loopback storm + dead-peer drill (ISSUE 19 acceptance; run
by scripts/run_tests.sh).

Three checks over the transport plane (adapm_tpu/net, docs/NETWORK.md):

1. BIT-IDENTITY UNDER WIRE FAULTS: a seeded two-node loopback storm —
   integer-valued pushes under full replication pressure — runs with
   the fault plane injecting frame drops (net.send / net.recv),
   duplicate deliveries (net.dup), delivery delays (net.delay), and
   pairwise partitions (net.partition) into every cross-node frame,
   with the lock-order sentinel armed. After EVERY round's quiesce
   (WaitSync -> Barrier -> WaitSync) each rank's full-table read must
   be bit-identical to an UNINJECTED single-process shadow server fed
   the same logical writes: a dropped frame must be retransmitted, a
   duplicated frame must NOT double-apply (receiver-side at-most-once
   dedup), and a delayed frame must not reorder visible state. The
   drill asserts the faults actually FIRED (an inert spec would pass
   vacuously) and that zero frames failed integrity checks.

2. DEAD-PEER KILL MID-STORM: rank 1 is killed between rounds. The
   survivor's membership plane must detect the death by heartbeat
   staleness, promote its replicas of dead-owned keys to mains
   (GlobalPM.failover_dead_peer), and record a recovery wall time
   `net.failover_s` <= ADAPM_NET_FAILOVER_MAX_S (default 30 s). The
   survivor then keeps storming ALONE on the covered keys and its
   reads must still match the shadow bitwise — a promoted replica
   carries the pre-kill pushes (pending delta merged, not dropped).

3. LOST-KEY ACCOUNTING: dead-owned keys WITHOUT a live replica are
   counted in net.lost_keys and promoted+lost must cover every
   dead-homed key — nothing silently disappears.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

K = 96
L = 4
ROUNDS = int(os.environ.get("ADAPM_NET_STORM_ROUNDS", "6"))
SEED = int(os.environ.get("ADAPM_NET_STORM_SEED", "1234"))
FAULT_SPEC = ("net.send=0.08,net.recv=0.08,net.dup=0.10,"
              "net.delay=0.02,net.partition=0.02")


def _opts(**kw):
    from adapm_tpu.config import SystemOptions
    return SystemOptions(sync_max_per_sec=0, prefetch=False, **kw)


def main() -> int:
    from adapm_tpu.base import CLOCK_MAX
    from adapm_tpu.core.kv import Server
    from adapm_tpu.net import LoopbackCluster

    failover_max_s = float(os.environ.get(
        "ADAPM_NET_FAILOVER_MAX_S", "30"))

    # integer-valued float32 pushes: addition on the integer grid is
    # exact and order-independent, so ANY legal interleaving must land
    # bitwise on the shadow — a drop, dup, or reorder shows up as a
    # wrong integer, never as fp noise
    rng = np.random.default_rng(SEED)
    logs = [[(np.sort(rng.choice(K, size=12, replace=False))
              .astype(np.int64),
              rng.integers(-8, 9, size=(12, L)).astype(np.float32))
             for _ in range(ROUNDS)] for _ in range(2)]
    expect = np.zeros((K, L), np.float64)
    for rank_log in logs:
        for keys, vals in rank_log:
            expect[keys] += vals
    partial = np.zeros((K, L), np.float64)  # running shadow per round

    # the UNINJECTED single-process shadow: same writes, no net plane,
    # no faults — the bit-identity reference required by the drill
    shadow = Server(K, L, opts=_opts(), num_workers=1)
    sw = shadow.make_worker(0)
    sw.wait(sw.set(np.arange(K, dtype=np.int64),
                   np.zeros((K, L), np.float32)))

    cl = LoopbackCluster(
        2, num_keys=K, value_lengths=L,
        opts_factory=lambda r: _opts(fault_spec=FAULT_SPEC,
                                     lint_lockorder=True),
        heartbeat_ms=40.0)
    try:
        allk = np.arange(K, dtype=np.int64)

        def prep(rank, srv):
            w = srv.make_worker(0)
            if rank == 0:
                w.wait(w.set(allk, np.zeros((K, L), np.float32)))
            srv.barrier()
            # competing intents install replicas at rank 0 of rank-1-
            # homed keys (an uncontended intent would relocate instead)
            theirs = allk[srv.glob.home_proc(allk) == 1]
            if rank == 1:
                w.intent(theirs, 0, CLOCK_MAX)
                srv.wait_sync()
            srv.barrier()
            if rank == 0:
                w.intent(theirs, 0, CLOCK_MAX)
                srv.wait_sync()
            srv.barrier()

        cl.run(prep)

        def storm_round(r):
            def body(rank, srv):
                w = srv.make_worker(0)
                keys, vals = logs[rank][r]
                w.wait(w.push(keys, vals))
                srv.wait_sync()
                srv.barrier()
                srv.wait_sync()
                srv.barrier()
                return w.pull_sync(allk)

            return cl.run(body)

        t0 = time.monotonic()
        for r in range(ROUNDS):
            for keys, vals in (logs[0][r], logs[1][r]):
                partial[keys] += vals
                sw.wait(sw.push(keys, vals))
            outs = storm_round(r)
            ref = sw.pull_sync(allk)
            want = partial.astype(np.float32)
            assert np.array_equal(ref, want), \
                f"round {r}: shadow server diverged from numpy log"
            for rank, got in enumerate(outs):
                assert np.array_equal(got, ref), (
                    f"round {r} rank {rank}: read differs from the "
                    f"uninjected shadow (max abs diff "
                    f"{np.abs(got - ref).max()})")
        storm_s = time.monotonic() - t0

        s0 = cl.servers[0].net.stats()
        fired = sum(cl.servers[i].fault.counts(p)[1]
                    for i in range(2)
                    for p in ("net.send", "net.recv", "net.dup",
                              "net.delay", "net.partition"))
        assert fired > 0, \
            "no wire faults fired — the storm proved nothing"
        assert s0["decode_errors"] == 0, \
            f"frame integrity failures: {s0['decode_errors']}"
        print(f"[net-storm] {ROUNDS} rounds x 2 ranks bit-identical "
              f"to uninjected shadow in {storm_s:.1f}s; wire faults "
              f"fired={fired}, retransmits={s0['retransmits']}, "
              f"dups suppressed={s0['dup_suppressed']}")

        # ---- dead-peer kill mid-storm --------------------------------
        srv0 = cl.servers[0]
        theirs = allk[srv0.glob.home_proc(allk) == 1]
        covered = theirs[
            (srv0.ab.cache_slot[:, theirs] >= 0).any(axis=0)
            & (srv0.ab.owner[theirs] < 0)]
        assert len(covered) > 0, "prep installed no replicas"
        cl.kill(1)
        deadline = time.monotonic() + failover_max_s
        while time.monotonic() < deadline and \
                srv0.net.stats()["failovers"] == 0:
            time.sleep(0.02)
        s = srv0.net.stats()
        assert s["failovers"] == 1, \
            f"death not detected within {failover_max_s}s"
        assert 0.0 < s["failover_s"] <= failover_max_s, \
            f"failover_s={s['failover_s']:.3f}s out of bound"
        assert s["promoted_keys"] >= len(covered), \
            (f"promoted {s['promoted_keys']} < {len(covered)} "
             f"replica-covered keys")
        assert s["promoted_keys"] + s["lost_keys"] >= len(theirs), \
            "promoted+lost does not cover the dead rank's keys"

        # survivor keeps storming alone on the covered keys; reads must
        # still match the shadow (promoted replicas carry pre-kill
        # pushes — pending deltas merged by _adopt, not dropped)
        srng = np.random.default_rng(SEED + 99)
        for _ in range(2):
            idx = np.sort(srng.choice(len(covered),
                                      size=min(8, len(covered)),
                                      replace=False))
            keys = covered[idx]
            vals = srng.integers(-8, 9, size=(len(keys), L)).astype(
                np.float32)
            partial[keys] += vals
            sw.wait(sw.push(keys, vals))

            def body(rank, srv):
                w = srv.make_worker(0)
                w.wait(w.push(keys, vals))
                srv.wait_sync()
                srv.barrier()
                return w.pull_sync(keys)

            got = cl.run(body, ranks=[0])[0]
            ref = sw.pull_sync(keys)
            assert np.array_equal(got, ref), \
                "survivor read diverged from shadow after failover"
        print(f"[net-storm] kill mid-storm: failover in "
              f"{s['failover_s'] * 1e3:.0f}ms "
              f"(bound {failover_max_s:.0f}s), promoted="
              f"{s['promoted_keys']} lost={s['lost_keys']} of "
              f"{len(theirs)} dead-homed keys; survivor reads still "
              f"bit-identical")
        cl.shutdown(ranks=[0])
    finally:
        shadow.shutdown()
        from adapm_tpu.lint import lockorder
        lockorder.disable_sentinel()
    print("[net-storm] OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
