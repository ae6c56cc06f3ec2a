"""Serving-plane latency guard (ISSUE 4 satellite; run by
scripts/run_tests.sh).

Two assertions about adapm_tpu/serve that a regression would break
silently:

1. **Coalescing wins.** At 32 concurrent clients, the coalesced
   `ServeSession.lookup` path must beat sequential per-request
   `Worker.pull_sync` of the same request stream by a safe margin.
   Methodology: same MEDIAN-pairwise-ratio pattern as
   scripts/mgmt_plane_check.py / metrics_overhead_check.py —
   (coalesced, sequential) timings back to back per repeat, guard on
   the median ratio. The guard is sized for the real failure mode: if
   the batcher stops coalescing (one dispatch per request — e.g. the
   micro-batch window breaks, or the dispatcher serializes behind a
   lock it should not hold), the coalesced path costs what sequential
   costs PLUS queue/thread overhead, pushing EVERY pairwise ratio to
   ~1.0+. Unlike the single-threaded mgmt guard, the coalesced side
   runs 32 client threads on a (possibly loaded) 2-core container, so
   individual pairs can spike arbitrarily on scheduler noise — the
   guard is therefore on the MIN pairwise ratio: if even the best pair
   cannot beat sequential, coalescing is broken (the failure mode
   degrades all pairs together, so min loses no sensitivity). All
   gather bucket shapes are pre-compiled before timing (a mid-loop XLA
   compile of a new union bucket would otherwise dominate a pair).
   Recorded baseline on the reference host (2-core container,
   32 clients x 8 lookups of 64 skewed keys): min ratio ~0.15-0.45;
   threshold 0.8 (override: ADAPM_SERVE_RATIO_MAX), tighten per the
   1.15x-headroom procedure when this host's numbers move.

2. **Idle serves nothing.** An idle serving plane must dispatch ZERO
   device programs: the dispatcher parks on the admission queue's
   condition variable — no polling gathers, no busy loop. Checked
   against the stores' host-side gather-program counters AND the
   serve.batches_total counter over an idle second.

ISSUE 9 guards (the read fast path + tenancy):

3. **The replica path wins under write contention.** With
   `--sys.serve.replica_rows` set and a concurrent training pusher
   hammering the server lock, hot-row lookups served from the
   epoch-validated snapshot (no lock, no device dispatch) must beat
   the r13 locked path on the same load: MEDIAN pairwise wall ratio
   < 0.8 (override: ADAPM_SERVE_REPLICA_RATIO_MAX), with
   replica-path hits actually observed (hit counter floor) in every
   replica half — a snapshot that silently stops covering the hot set
   degrades every pair toward 1.0.

4. **Tenancy holds the high-priority tail under a flood.** A
   low-priority tenant flooding a small queue must SHED
   (shed+rejected > 0 — quota/pressure backpressure, never a hang)
   while the high-priority tenant's P99, served through priority
   claim (priority-pure batches) + the replica fast path, stays under
   ADAPM_SERVE_GOLD_P99_MS (default 400 ms — sized for a loaded
   2-core container where one in-flight bronze batch's locked gather
   bounds the gold wait; recorded ~230 ms on the reference host) with
   zero gold sheds.
"""
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    from xla_compat import mesh_flags
    os.environ["XLA_FLAGS"] = " ".join([_flags, mesh_flags(2)]).strip()

import numpy as np  # noqa: E402

CLIENTS = 32
LOOKUPS = 8          # per client per repeat
B = 64               # keys per lookup
NK = 4096
VLEN = 8
REPEATS = 5


def build():
    import jax

    import adapm_tpu
    from adapm_tpu.config import SystemOptions
    from adapm_tpu.serve import ServePlane

    jax.config.update("jax_platforms", "cpu")
    srv = adapm_tpu.setup(NK, VLEN, opts=SystemOptions(
        sync_max_per_sec=0, prefetch=False))
    w = srv.make_worker(0)
    rng = np.random.default_rng(0)
    w.wait(w.set(np.arange(NK),
                 rng.normal(size=(NK, VLEN)).astype(np.float32)))
    plane = ServePlane(srv)
    return srv, w, plane, rng


def run_coalesced(plane, batches) -> float:
    barrier = threading.Barrier(CLIENTS + 1)
    errs = []

    def client(ci):
        try:
            sess = plane.session()
            barrier.wait()
            for b in batches[ci]:
                sess.lookup(b)
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(CLIENTS)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    assert not errs, errs[:3]
    return dt


def run_sequential(w, batches) -> float:
    t0 = time.perf_counter()
    for cb in batches:
        for b in cb:
            w.pull_sync(b)
    return time.perf_counter() - t0


def run_replica_guard(srv, w, rng) -> tuple:
    """Guard 3: replica-path vs locked-path pairwise ratios under a
    concurrent training pusher (same plane, replica detached for the
    locked half — the r13 baseline path, byte for byte)."""
    import threading

    from adapm_tpu.serve import ServePlane

    clients, lookups, hot_n = 6, 48, 256
    srv.opts.serve_replica_rows = 512
    srv.opts.serve_replica_refresh_ms = 10.0
    plane = ServePlane(srv)
    hot = np.arange(hot_n, dtype=np.int64)
    batches = [[rng.choice(hot, B) for _ in range(lookups)]
               for _ in range(clients)]

    def run(attach_replica) -> float:
        plane.batcher.replica = plane.replica if attach_replica else None
        barrier = threading.Barrier(clients + 1)
        errs = []

        def client(ci):
            try:
                sess = plane.session()
                barrier.wait()
                for b in batches[ci]:
                    sess.lookup(b)
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(clients)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        assert not errs, errs[:3]
        return dt

    # warm scores + snapshot; pin that the fast path fires at all
    run(True)
    assert plane.replica.refresh_now() > 0, "empty replica snapshot"
    h0 = srv.obs.find("serve.replica_hits_total").value
    run(True)
    hits_ok = srv.obs.find("serve.replica_hits_total").value > h0

    # concurrent training pushes on DISJOINT keys: lock contention for
    # the locked half, epoch-silence for the snapshot's hot rows
    stop = threading.Event()
    push_keys = np.arange(1024, NK, dtype=np.int64)

    def pusher():
        prng = np.random.default_rng(5)
        while not stop.is_set():
            ks = np.unique(prng.choice(push_keys, 64))
            w.push(ks, np.ones((len(ks), VLEN), np.float32))

    pt = threading.Thread(target=pusher)
    pt.start()
    pairs = []
    try:
        for _ in range(9):
            h0 = srv.obs.find("serve.replica_hits_total").value
            t_rep = run(True)
            if srv.obs.find("serve.replica_hits_total").value <= h0:
                hits_ok = False
            t_lock = run(False)
            pairs.append(t_rep / t_lock)
    finally:
        stop.set()
        pt.join()
    plane.close()
    pairs.sort()
    return pairs, hits_ok


def run_tenant_guard(srv, w, rng) -> dict:
    """Guard 4: bronze flood sheds, gold P99 holds (see module doc)."""
    import threading

    from adapm_tpu.config import SystemOptions
    from adapm_tpu.serve import (DeadlineExceededError,
                                 ServeOverloadError, ServePlane)

    opts = SystemOptions(sync_max_per_sec=0, prefetch=False,
                         serve_queue=64, serve_max_batch=32,
                         serve_dispatchers=2, serve_replica_rows=512,
                         serve_replica_refresh_ms=10.0)
    plane = ServePlane(srv, opts=opts)
    plane.configure_tenant("gold", priority=2)
    plane.configure_tenant("bronze", priority=0)
    hot = np.arange(256, dtype=np.int64)
    # seed the snapshot with the gold working set
    sess0 = plane.session(tenant="gold")
    sess0.lookup(hot)    # score the whole gold working set
    plane.replica.refresh_now()
    h0 = srv.obs.find("serve.replica_hits_total").value
    b0 = srv.obs.find("serve.batches_total").value

    stop = threading.Event()
    errs = []
    gold_lat = []
    gold_sheds = [0]

    def pusher():
        prng = np.random.default_rng(6)
        ks_all = np.arange(1024, NK, dtype=np.int64)
        while not stop.is_set():
            ks = np.unique(prng.choice(ks_all, 64))
            w.push(ks, np.ones((len(ks), VLEN), np.float32))

    def bronze(ci):
        prng = np.random.default_rng(100 + ci)
        sess = plane.session(tenant="bronze")
        try:
            while not stop.is_set():
                try:
                    sess.lookup(prng.integers(0, NK, B),
                                deadline_ms=5.0)
                except (DeadlineExceededError, ServeOverloadError):
                    pass  # the expected backpressure under the flood
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    def gold():
        prng = np.random.default_rng(200)
        sess = plane.session(tenant="gold")
        try:
            for _ in range(60):
                t0 = time.perf_counter()
                try:
                    sess.lookup(prng.choice(hot, B), deadline_ms=1000.0)
                    gold_lat.append(time.perf_counter() - t0)
                except (DeadlineExceededError, ServeOverloadError):
                    gold_sheds[0] += 1
                time.sleep(0.01)   # paced open-loop arrivals
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=pusher)] + \
              [threading.Thread(target=bronze, args=(ci,))
               for ci in range(4)] + [threading.Thread(target=gold)]
    for t in threads:
        t.start()
    threads[-1].join(timeout=120)
    stop.set()
    for t in threads[:-1]:
        t.join(timeout=60)
    assert not errs, errs[:3]
    bz = plane.queue.tenant("bronze")
    hits_d = srv.obs.find("serve.replica_hits_total").value - h0
    batches_d = srv.obs.find("serve.batches_total").value - b0
    out = {"gold_p99_ms": 1e3 * sorted(gold_lat)[
               max(0, int(0.99 * len(gold_lat)) - 1)] if gold_lat
           else float("inf"),
           "gold_served": len(gold_lat),
           "gold_sheds": gold_sheds[0],
           "bronze_shed": bz.c_shed.value + bz.c_rejected.value,
           # segment-windowed (the cumulative gauge is diluted by the
           # coalesce segment's batches on this shared server)
           "replica_hit_rate": hits_d / max(1.0, batches_d)}
    plane.close()
    return out


def main() -> int:
    ratio_max = float(os.environ.get("ADAPM_SERVE_RATIO_MAX", "0.8"))
    rep_ratio_max = float(os.environ.get(
        "ADAPM_SERVE_REPLICA_RATIO_MAX", "0.8"))
    gold_p99_max_ms = float(os.environ.get(
        "ADAPM_SERVE_GOLD_P99_MS", "400"))
    srv, w, plane, rng = build()

    def make_batches():
        # power-law key skew (embedding serving is zipfian): concurrent
        # clients hit the same hot rows, which
        # is exactly the union-dedup case the coalescer exists for
        return [[(NK * rng.random(B) ** 3).astype(np.int64)
                 .clip(0, NK - 1) for _ in range(LOOKUPS)]
                for _ in range(CLIENTS)]

    # warm both paths. Every gather bucket shape a coalesced union can
    # hit is compiled HERE: union sizes vary per repeat, and a mid-loop
    # XLA compile of a fresh power-of-two bucket would dominate that
    # pair's timing.
    n = B
    while True:
        w.pull_sync(np.arange(min(n, NK), dtype=np.int64))
        if n >= min(CLIENTS * B, NK):
            break
        n *= 2
    warm = make_batches()
    run_sequential(w, warm[:2])
    run_coalesced(plane, warm)

    pairs = []
    for _ in range(REPEATS):
        batches = make_batches()
        t_coal = run_coalesced(plane, batches)
        t_seq = run_sequential(w, batches)
        pairs.append(t_coal / t_seq)

    # -- idle guard: a parked serving plane dispatches nothing ----------
    time.sleep(0.05)  # let the dispatcher park after the last batch
    g0 = sum(s.gathers for s in srv.stores)
    b0 = srv.obs.find("serve.batches_total").value
    time.sleep(1.0)
    g1 = sum(s.gathers for s in srv.stores)
    b1 = srv.obs.find("serve.batches_total").value
    idle_ok = (g1 == g0) and (b1 == b0)

    # -- ISSUE 9 guards: replica fast path + tenancy --------------------
    plane.close()   # one live plane per server
    rep_pairs, rep_hits_ok = run_replica_guard(srv, w, rng)
    tenant = run_tenant_guard(srv, w, rng)

    srv.shutdown()
    pairs.sort()
    best, median = pairs[0], pairs[len(pairs) // 2]
    print(f"[serve-check] {CLIENTS} clients x {LOOKUPS} lookups x "
          f"{REPEATS} pairs: coalesced/sequential ratios min "
          f"{best:.3f} / median {median:.3f} / max {pairs[-1]:.3f} "
          f"(guard: min < {ratio_max:.2f}; a non-coalescing batcher "
          f"degrades every pair to ~1.0+) | idle: gathers {g1 - g0:+d}, "
          f"batches {b1 - b0:+.0f}")
    rep_median = rep_pairs[len(rep_pairs) // 2]
    print(f"[serve-check] replica guard: replica/locked wall ratios "
          f"min {rep_pairs[0]:.3f} / median {rep_median:.3f} / max "
          f"{rep_pairs[-1]:.3f} under concurrent pushes (guard: "
          f"median < {rep_ratio_max:.2f}; hits observed: "
          f"{rep_hits_ok})")
    print(f"[serve-check] tenant guard: gold p99 "
          f"{tenant['gold_p99_ms']:.1f} ms over "
          f"{tenant['gold_served']} served / {tenant['gold_sheds']} "
          f"shed (guard: < {gold_p99_max_ms:.0f} ms, 0 shed) | bronze "
          f"shed+rejected {tenant['bronze_shed']:.0f} (floor: > 0) | "
          f"replica_hit_rate {tenant['replica_hit_rate']:.3f}")
    rc = 0
    if best >= ratio_max:
        print("[serve-check] FAILED: coalesced lookups no longer beat "
              "sequential per-request pulls — check the micro-batch "
              "window (take/max_wait), union dedup, and that the "
              "dispatcher is not serializing behind an extra lock",
              file=sys.stderr)
        rc = 1
    if not idle_ok:
        print("[serve-check] FAILED: an idle serving plane dispatched "
              "device programs — the dispatcher must park on the "
              "admission queue, never poll with gathers",
              file=sys.stderr)
        rc = 1
    if rep_median >= rep_ratio_max or not rep_hits_ok:
        print("[serve-check] FAILED: the replica read fast path no "
              "longer beats the locked path under write contention "
              "(or the snapshot stopped covering the hot set) — check "
              "epoch validation, the refresh selection, and that "
              "try_serve stays lock-free", file=sys.stderr)
        rc = 1
    if (tenant["gold_p99_ms"] >= gold_p99_max_ms
            or tenant["gold_sheds"] > 0 or tenant["bronze_shed"] <= 0
            or tenant["replica_hit_rate"] <= 0):
        print("[serve-check] FAILED: tenancy guard — a low-priority "
              "flood must shed while the high-priority tenant's tail "
              "holds through priority claim + the replica fast path",
              file=sys.stderr)
        rc = 1
    if rc == 0:
        print("[serve-check] OK")
    return rc


if __name__ == "__main__":
    sys.exit(main())
