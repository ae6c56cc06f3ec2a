"""Multi-process test scenarios, run as child processes by
test_multiprocess.py (one per rank, rendezvoused through the launcher env
contract). Each scenario is the multi-process twin of the reference's
self-checking test binaries (tests/test_many_key_operations.cc,
tests/test_locality_api.cc) launched by tracker/dmlc_local.py.

Usage: ADAPM_* env set by the launcher; argv[1] = scenario name.
"""
import faulthandler
import os
import sys

# hung-scenario diagnostics: dump all thread stacks and exit BEFORE the
# harness's subprocess timeout, so the test failure carries the stacks
# instead of a bare TimeoutExpired (run_mp sets the budget)
faulthandler.dump_traceback_later(
    int(os.environ.get("ADAPM_FAULT_T", "280")), exit=True)

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from xla_compat import mesh_flags  # noqa: E402

os.environ.setdefault("XLA_FLAGS", mesh_flags(2))
os.environ.pop("PYTHONPATH", None)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import adapm_tpu  # noqa: E402
from adapm_tpu.base import CLOCK_MAX, LOCAL, NO_SLOT, NOT_CACHED, REMOTE  # noqa: E402
from adapm_tpu.config import SystemOptions  # noqa: E402
from adapm_tpu.parallel import control  # noqa: E402


def owned_by_proc(srv, proc, n=None):
    """Keys whose INITIAL home process is `proc` (key % (S*P) // S)."""
    keys = np.arange(srv.num_keys, dtype=np.int64)
    mine = keys[srv.glob.home_proc(keys) == proc]
    return mine if n is None else mine[:n]


def scenario_pullpush():
    """Cross-process Pull/Push/Set with exact values (the reference's
    test_many_key_operations value checks, phases 1-2)."""
    srv = adapm_tpu.setup(64, 4, opts=SystemOptions(sync_max_per_sec=0))
    rank = control.process_id()
    P = control.num_processes()
    w = srv.make_worker(0)
    keys = np.arange(64, dtype=np.int64)
    base = np.arange(64, dtype=np.float32)[:, None] * np.ones(4, np.float32)
    if rank == 0:
        ts = w.set(keys, base)
        w.wait(ts)
    srv.barrier()
    vals = w.pull_sync(keys)
    assert np.allclose(vals, base), f"pull after set mismatch\n{vals[:4]}"
    # every rank pushes +1 to every key -> each key gains +P exactly
    ts = w.push(keys, np.ones((64, 4), np.float32))
    w.wait(ts)
    srv.barrier()
    vals = w.pull_sync(keys)
    assert np.allclose(vals, base + P), f"pull after pushes\n{vals[:4]}"
    rm = srv.read_main(keys).reshape(64, 4)
    assert np.allclose(rm, base + P), "read_main disagrees"
    # locality: this worker's own keys answered locally
    mine = owned_by_proc(srv, rank)
    mine = mine[srv.ab.owner[mine] == w.shard]
    assert w.pull(mine) == LOCAL, "own-shard keys should be LOCAL"
    srv.barrier()
    srv.shutdown()
    print(f"MP-OK pullpush rank={rank}")


def scenario_intent_locality():
    """Rank 1's intent MOVES rank-0-owned keys (exclusive -> relocation);
    rank 0's competing intent then REPLICATES them back (reference
    test_locality_api semantics, cross-process)."""
    srv = adapm_tpu.setup(64, 4, opts=SystemOptions(sync_max_per_sec=0))
    rank = control.process_id()
    w = srv.make_worker(0)
    keys = owned_by_proc(srv, 0, 8)
    if rank == 0:
        ts = w.set(keys, np.full((8, 4), 7.0, np.float32))
        w.wait(ts)
    srv.barrier()
    if rank == 1:
        w.intent(keys, 0, CLOCK_MAX)
        srv.wait_sync()
        assert (srv.ab.owner[keys] >= 0).all(), \
            "exclusive intent should relocate cross-process"
        assert srv.glob.stats["relocations_in"] >= 8
        v = w.pull_sync(keys)
        assert np.allclose(v, 7.0), f"value lost in relocation: {v}"
    srv.barrier()
    if rank == 0:
        assert (srv.ab.owner[keys] == REMOTE).all(), \
            "rank 0 should have released ownership"
        assert (srv.glob.owner_hint[keys] == 1).all(), \
            "manager/owner hint should track the transfer"
        # competing intent: rank 1 still holds intent -> replicate here
        w.intent(keys, 0, CLOCK_MAX)
        srv.wait_sync()
        assert (srv.ab.cache_slot[w.shard, keys] != NO_SLOT).all(), \
            "competing intent should replicate"
        assert w.pull(keys) == LOCAL, "replicated keys should be LOCAL"
    srv.barrier()
    # rank 1 pushes on its (now owned) keys; rank 0's replicas converge
    # after the quiesce protocol (WaitSync -> Barrier -> WaitSync)
    if rank == 1:
        ts = w.push(keys, np.ones((8, 4), np.float32))
        w.wait(ts)
    w.wait_all()
    srv.wait_sync()
    srv.barrier()
    srv.wait_sync()
    srv.barrier()
    v = w.pull_sync(keys)
    assert np.allclose(v, 8.0), f"rank {rank} sees {v[:2]} after quiesce"
    srv.shutdown()
    print(f"MP-OK intent_locality rank={rank}")


def scenario_monotonic():
    """Concurrent contended pushes under intent churn with the background
    sync thread running: a worker's own applied pushes are never lost
    (monotonicity), and after quiesce the value is exactly P * R
    (reference test_many_key_operations phases 2-3 +
    test_dynamic_allocation)."""
    srv = adapm_tpu.setup(32, 2, opts=SystemOptions(sync_max_per_sec=500))
    rank = control.process_id()
    P = control.num_processes()
    srv.start_sync_thread()
    w = srv.make_worker(0)
    contended = int(owned_by_proc(srv, 0, 1)[0])
    rng = np.random.default_rng(rank)
    R = 30
    applied = 0
    kk = np.array([contended], dtype=np.int64)
    for i in range(R):
        if rng.random() < 0.4:
            w.intent(kk, w.current_clock, w.current_clock + 3)
        ts = w.push(kk, np.ones((1, 2), np.float32))
        w.wait(ts)
        applied += 1
        v = float(w.pull_sync(kk)[0, 0])
        assert v + 1e-3 >= applied, \
            f"rank {rank}: pulled {v} < own applied {applied}"
        w.advance_clock()
    w.wait_all()
    srv.wait_sync()
    srv.barrier()
    srv.wait_sync()
    srv.barrier()
    final = float(srv.read_main(kk)[0])
    assert abs(final - P * R) < 1e-3, \
        f"rank {rank}: final {final} != {P * R} (lost/duplicated updates)"
    v = float(w.pull_sync(kk)[0, 0])
    assert abs(v - P * R) < 1e-3, f"rank {rank}: pull {v} != {P * R}"
    srv.barrier()
    srv.shutdown()
    print(f"MP-OK monotonic rank={rank}")


def scenario_eventual():
    """Eventual consistency: every rank pushes then reverts on a shared key
    set under replication; after the quiesce protocol all ranks read the
    exact base everywhere (reference test_many_key_operations phase 3).
    argv[2] selects --sys.techniques (the reference's run_tests.sh
    variants: all / replication_only / relocation_only); argv[3] == "coll"
    runs the BSP collective sync data plane (--sys.collective_sync,
    parallel/collective.py) with a small bucket so the exchange loop runs
    several padded iterations."""
    from adapm_tpu.base import MgmtTechniques
    tech = MgmtTechniques(sys.argv[2]) if len(sys.argv) > 2 \
        else MgmtTechniques.ALL
    coll = len(sys.argv) > 3 and sys.argv[3] == "coll"
    srv = adapm_tpu.setup(48, 4, opts=SystemOptions(
        sync_max_per_sec=0, techniques=tech,
        collective_sync=coll, collective_bucket=16))
    rank = control.process_id()
    w = srv.make_worker(0)
    keys = np.arange(48, dtype=np.int64)
    base = np.arange(48, dtype=np.float32)[:, None] * np.ones(4, np.float32)
    if rank == 0:
        w.wait(w.set(keys, base))
    srv.barrier()
    # everyone subscribes everywhere -> full replication pressure
    w.intent(keys, 0, CLOCK_MAX)
    srv.wait_sync()
    srv.barrier()
    x = np.full((48, 4), 2.5 + rank, np.float32)
    w.wait(w.push(keys, x))
    w.wait(w.push(keys, -x))
    w.wait_all()
    srv.wait_sync()
    srv.barrier()
    srv.wait_sync()
    srv.barrier()
    v = w.pull_sync(keys)
    assert np.allclose(v, base, atol=1e-4), \
        f"rank {rank}: not restored\n{(v - base)[:4]}"
    rm = srv.read_main(keys).reshape(48, 4)
    assert np.allclose(rm, base, atol=1e-4), f"rank {rank}: main differs"
    srv.barrier()
    srv.shutdown()
    print(f"MP-OK eventual rank={rank}")


def scenario_cadence():
    """Bounded staleness with --sys.collective_cadence K (VERDICT r4 item
    3): rank 1 holds a replica of a rank-0-owned key; rank 0 pushes and
    NOBODY calls WaitSync — the replica must still observe the push
    within ~K clock advances, because every process joins a BSP exchange
    at each K-clock boundary of its run_round loop. All ranks run the
    same fixed number of steps (no early exit: an exchange needs every
    process)."""
    K = 4
    srv = adapm_tpu.setup(16, 4, opts=SystemOptions(
        sync_max_per_sec=0, collective_sync=True, collective_bucket=8,
        collective_cadence=K))
    rank = control.process_id()
    w = srv.make_worker(0)
    k = owned_by_proc(srv, 0, 1)
    if rank == 0:
        w.wait(w.set(k, np.full((1, 4), 1.0, np.float32)))
    srv.barrier()
    # every rank subscribes: the owner-local interest forces REPLICATE
    # (not relocate) for rank 1 (sync_manager.h:624-644 decision)
    w.intent(k, 0, CLOCK_MAX)
    srv.wait_sync()
    srv.barrier()
    if rank == 1:
        ok, v = w.pull_if_local(k)
        assert ok and abs(float(np.ravel(v)[0]) - 1.0) < 1e-6, \
            f"rank 1: replica not installed ({ok}, {v})"
    if rank == 0:
        w.wait(w.push(k, np.full((1, 4), 1.0, np.float32)))
    srv.barrier()  # push applied at the owner before anyone counts clocks
    seen_at = None
    for step in range(4 * K):
        w.advance_clock()
        srv.sync.run_round()
        if rank == 1 and seen_at is None:
            ok, v = w.pull_if_local(k)
            if ok and abs(float(np.ravel(v)[0]) - 2.0) < 1e-6:
                seen_at = step
    if rank == 1:
        assert seen_at is not None, \
            f"replica never observed the push in {4 * K} clocks"
        assert seen_at <= K + 1, \
            f"staleness bound violated: observed at step {seen_at} > K={K}"
        print(f"[cadence] observed after {seen_at + 1} clocks (K={K})")
    # quiesce protocol still holds in cadence mode
    srv.quiesce()
    srv.barrier()
    srv.quiesce()
    final = 2.0
    v = srv.read_main(k) if rank == 0 else None
    if rank == 0:
        assert abs(float(np.asarray(v)[0]) - final) < 1e-6
    srv.barrier()
    srv.shutdown()
    print(f"MP-OK cadence rank={rank}")


def scenario_location_caches():
    """3 processes: after a relocation 0 -> 1, rank 2's first pull routes
    via the manager (redirect) and LEARNS the owner; the second goes one
    hop. With --sys.location_caches 0 the hint table stays cold and every
    access re-routes via the manager (reference addressbook.h:114-133)."""
    caches = bool(int(sys.argv[2])) if len(sys.argv) > 2 else True
    srv = adapm_tpu.setup(12, 4, opts=SystemOptions(
        sync_max_per_sec=0, location_caches=caches))
    rank = control.process_id()
    w = srv.make_worker(0)
    k = owned_by_proc(srv, 0, 1)  # managed (and initially owned) by rank 0
    if rank == 0:
        w.wait(w.set(k, np.full((1, 4), 5.0, np.float32)))
    srv.barrier()
    if rank == 1:
        w.intent(k, 0, CLOCK_MAX)
        srv.wait_sync()
        assert (srv.ab.owner[k] >= 0).all()
    srv.barrier()
    if rank == 2:
        assert float(w.pull_sync(k)[0, 0]) == 5.0
        if caches:
            assert srv.glob.owner_hint[k[0]] == 1, \
                "location cache should have learned the relocated owner"
        else:
            assert srv.glob.owner_hint[k[0]] == NOT_CACHED, \
                "caches off: hint table must stay cold"
        # second pull: with caches, one hop straight to the owner
        before = srv.glob.stats["redirects"]
        assert float(w.pull_sync(k)[0, 0]) == 5.0
        if caches:
            assert srv.glob.stats["redirects"] == before, \
                "cached owner should not redirect"
    srv.barrier()
    if rank == 0 and caches:
        # the manager redirected rank 2's first pull instead of serving it
        assert srv.glob.stats["pulls_in"] >= 1
    srv.barrier()
    srv.shutdown()
    print(f"MP-OK location_caches rank={rank}")


def scenario_ckpt_save():
    """Phase 1 of the crash-recovery test: adapt placement (cross-process
    relocation + replication), push values, checkpoint, then 'crash'
    (exit). Phase 2 (ckpt_restore) runs as a fresh launch."""
    from adapm_tpu.utils.checkpoint import save_server
    path = sys.argv[2]
    srv = adapm_tpu.setup(48, 4, opts=SystemOptions(sync_max_per_sec=0))
    rank = control.process_id()
    w = srv.make_worker(0)
    keys = np.arange(48, dtype=np.int64)
    if rank == 0:
        w.wait(w.set(keys, np.arange(48, dtype=np.float32)[:, None]
                     * np.ones(4, np.float32)))
    srv.barrier()
    # rank 1 takes exclusive ownership of some rank-0 keys; rank 0 then
    # subscribes to two of them -> cross-process replicas exist at save
    moved = owned_by_proc(srv, 0, 6)
    if rank == 1:
        w.intent(moved, 0, CLOCK_MAX)
        srv.wait_sync()
        assert (srv.ab.owner[moved] >= 0).all()
    srv.barrier()
    if rank == 0:
        w.intent(moved[:2], 0, CLOCK_MAX)
        srv.wait_sync()
    srv.barrier()
    w.wait(w.push(keys, np.ones((48, 4), np.float32)))
    w.wait_all()
    save_server(srv, path)  # runs the distributed quiesce internally
    srv.shutdown()
    print(f"MP-OK ckpt_save rank={rank}")


def scenario_ckpt_restore():
    """Phase 2: fresh launch restores the rank shards; values, adapted
    placement, and the consistency invariant must survive."""
    from adapm_tpu.utils.checkpoint import restore_server
    path = sys.argv[2]
    srv = adapm_tpu.setup(48, 4, opts=SystemOptions(sync_max_per_sec=0))
    rank = control.process_id()
    w = srv.make_worker(0)
    restore_server(srv, path)
    keys = np.arange(48, dtype=np.int64)
    # set(k) + one push(+1) from each of the two ranks before the save
    base = (np.arange(48, dtype=np.float32)[:, None]
            * np.ones(4, np.float32)) + 2.0
    v = w.pull_sync(keys)
    assert np.allclose(v, base), f"rank {rank}: restored values wrong"
    moved = owned_by_proc(srv, 0, 6)
    if rank == 1:
        assert (srv.ab.owner[moved] >= 0).all(), \
            "adapted ownership lost in restore"
    if rank == 0:
        assert (srv.ab.owner[moved] == REMOTE).all()
        assert (srv.glob.owner_hint[moved] == 1).all(), \
            "manager table lost in restore"
        assert (srv.ab.cache_slot[w.shard, moved[:2]] != NO_SLOT).any(), \
            "cross-process replicas lost in restore"
    srv.barrier()
    # the restored manager still satisfies eventual consistency
    w.wait(w.push(keys, np.ones((48, 4), np.float32)))
    w.wait(w.push(keys, -np.ones((48, 4), np.float32)))
    w.wait_all()
    srv.wait_sync()
    srv.barrier()
    srv.wait_sync()
    srv.barrier()
    v = w.pull_sync(keys)
    assert np.allclose(v, base, atol=1e-4), f"rank {rank}: not consistent"
    srv.shutdown()
    print(f"MP-OK ckpt_restore rank={rank}")


def scenario_kge_app():
    """Full KGE app, data-parallel across processes: global worker data
    partition, cross-process parameter traffic via intent/ensure_local,
    PS-key loss/eval allreduce, distributed eval. The whole stack,
    end to end (reference: the same binary runs on every node)."""
    from adapm_tpu.apps import knowledge_graph_embeddings as kge
    args = kge.build_parser().parse_args(
        ["--dim", "8", "--neg_ratio", "2", "--synthetic_entities", "60",
         "--synthetic_relations", "4", "--synthetic_triples", "400",
         "--epochs", "6", "--batch_size", "32", "--lr", "0.2",
         "--eval_every", "6", "--eval_triples", "60",
         "--sys.sync.max_per_sec", "0"])
    result = kge.run_app(args)
    rank = control.process_id()
    assert np.isfinite(result["loss"]), result
    assert result["mrr"] > 0.12, f"rank {rank}: no learning: {result}"
    print(f"MP-OK kge_app rank={rank}")


def scenario_coll_pullpush():
    """Pull/Push data plane over device collectives (VERDICT r4 item 4;
    SURVEY's remaining ICI mapping): request keys ride the all-to-all to
    their owners, values/deltas ride back — no DCN RPC for the data.
    Exact-value checks mirror scenario_pullpush; bucket 8 forces several
    packed exchange iterations."""
    srv = adapm_tpu.setup(64, 4, opts=SystemOptions(
        sync_max_per_sec=0, collective_sync=True, collective_bucket=8))
    rank = control.process_id()
    P = control.num_processes()
    w = srv.make_worker(0)
    keys = np.arange(64, dtype=np.int64)
    base = np.arange(64, dtype=np.float32)[:, None] * np.ones(4, np.float32)
    if rank == 0:
        w.wait(w.set(keys, base))
    srv.barrier()
    # collective pull: every rank reads the whole table via the exchange
    vals = srv.collective_pull(keys).reshape(64, 4)
    assert np.allclose(vals, base), f"rank {rank}: coll pull\n{vals[:4]}"
    # collective push: every rank adds +1 everywhere -> each key gains +P
    srv.collective_push(keys, np.ones((64, 4), np.float32))
    srv.barrier()
    vals = srv.collective_pull(keys).reshape(64, 4)
    assert np.allclose(vals, base + P), \
        f"rank {rank}: after coll push\n{vals[:4]}"
    # the RPC read path agrees (same owner state, different transport)
    rm = srv.read_main(keys).reshape(64, 4)
    assert np.allclose(rm, base + P), f"rank {rank}: read_main disagrees"
    # RPC ops and the NEXT exchange must be separated by a barrier: a
    # rank already waiting inside an exchange parks its devices there,
    # and serving a peer's read_main needs a device gather — without the
    # barrier that is a cross-program device-queue deadlock (the barrier
    # itself is device-free, so pending serves drain during it); see
    # GlobalPM.collective_pull docstring
    srv.barrier()
    # empty-keys join: a rank with nothing to pull still participates
    srv.collective_pull(keys if rank == 0 else keys[:0])
    srv.barrier()
    srv.shutdown()
    print(f"MP-OK coll_pullpush rank={rank}")


def scenario_kge_eval_chunk():
    """Candidate-partitioned chunked eval across processes (VERDICT r4
    item 5): every rank scores only its OWNED entities from its local
    pool and the merged counts must match the dense-matrix path (which
    reads the full entity matrix via read_main) on the same triples."""
    from adapm_tpu.apps import knowledge_graph_embeddings as kge
    from adapm_tpu.io import kge as kgeio
    args = kge.build_parser().parse_args(
        ["--dim", "8", "--synthetic_entities", "60",
         "--synthetic_relations", "4", "--synthetic_triples", "300",
         "--eval_chunk", "16", "--sys.sync.max_per_sec", "0"])
    ds = kgeio.generate_synthetic(60, 4, 300, seed=1)
    # KgeRun joins the distributed runtime; jax.process_index() before it
    # would initialize the backend and break jax.distributed.initialize
    run = kge.KgeRun(args, ds)
    rank = control.process_id()
    run.init_model()  # random model: rank equivalence needs no training
    trip = ds.test[:60]
    pool = kge.evaluate(run, trip)   # mp pool path: counts merge inside
    assert run._pool_eval_n > 0, \
        f"rank {rank}: expected to own some entities"
    assert run._pool_eval_n < run.E, \
        f"rank {rank}: candidate partition is not a partition"
    args.eval_chunk = 0
    dense = kge.evaluate(run, trip)  # dense path: full set, global stats
    assert np.allclose(pool, dense), f"rank {rank}:\n{pool}\n{dense}"
    run.srv.barrier()
    run.srv.shutdown()
    print(f"MP-OK kge_eval_chunk rank={rank}")


def scenario_stress():
    """True-concurrency cross-process stress: 2 worker THREADS per process
    push into overlapping skewed key sets under intent churn with the
    background sync thread running; after the quiesce protocol every key's
    main copy equals the exact global push count (reference
    test_dynamic_allocation's contended exactness, scaled to threads x
    processes)."""
    import threading
    K = 48
    srv = adapm_tpu.setup(K, 2, opts=SystemOptions(sync_max_per_sec=300))
    srv.start_sync_thread()
    rank = control.process_id()
    ws = [srv.make_worker(i) for i in range(2)]
    counts = np.zeros(K, dtype=np.float64)
    counts_lock = threading.Lock()
    errs = []

    def work(wi):
        w = ws[wi]
        rng = np.random.default_rng(1000 * rank + wi)
        try:
            for i in range(25):
                keys = np.unique((K * rng.random(6) ** 2).astype(np.int64))
                if rng.random() < 0.5:
                    w.intent(keys, w.current_clock, w.current_clock + 3)
                ts = w.push(keys, np.ones((len(keys), 2), np.float32))
                w.wait(ts)
                with counts_lock:
                    counts[keys] += 1
                if rng.random() < 0.3:
                    v = w.pull_sync(keys)
                    assert np.isfinite(v).all()
                w.advance_clock()
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=work, args=(wi,)) for wi in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    for w in ws:
        w.wait_all()
    srv.wait_sync()
    srv.barrier()
    srv.wait_sync()
    srv.barrier()
    total = control.allreduce(counts, "sum")
    final = srv.read_main(np.arange(K)).reshape(K, 2)
    assert np.allclose(final, total[:, None], atol=1e-3), \
        f"rank {rank}: lost/duplicated updates\n{final[:, 0] - total}"
    srv.barrier()
    srv.shutdown()
    print(f"MP-OK stress rank={rank}")


def scenario_sampling():
    """Sampling across processes (reference tests/test_sampling.cc run under
    dmlc_local with multiple nodes, run_tests.sh:21-42): every scheme draws
    keys whose main copies live on OTHER processes; sampled values must be
    exact (value[0] == key), WOR draws unique, and the Local scheme must
    only ever return process-locally-resident keys."""
    scheme = sys.argv[2]
    K = 48
    srv = adapm_tpu.setup(K, 4, opts=SystemOptions(
        sync_max_per_sec=0, sampling_scheme=scheme,
        sampling_with_replacement=False))
    rank = control.process_id()
    w = srv.make_worker(0)
    keys = np.arange(K, dtype=np.int64)
    if rank == 0:  # value[0] = key, recognizable everywhere
        vals = np.zeros((K, 4), np.float32)
        vals[:, 0] = keys
        w.wait(w.set(keys, vals))
    srv.barrier()
    srv.enable_sampling_support(
        lambda n, rng: rng.integers(0, K, n).astype(np.int64))
    h = w.prepare_sample(12)
    if scheme == "preloc":
        srv.wait_sync()  # act on the signalled intent (replicate/relocate)
    drawn, vals = w.pull_sample(h)
    assert len(drawn) == 12
    np.testing.assert_allclose(vals[:, 0], drawn.astype(np.float32))
    assert len(np.unique(drawn)) == len(drawn), "WOR produced duplicates"
    if scheme == "local":
        # Local draws only process-resident keys (reference
        # sampling.h:476-505 probes the local store)
        loc = (srv.ab.owner[drawn] >= 0) | \
            (srv.ab.cache_slot[:, drawn] >= 0).any(axis=0)
        assert loc.all(), f"local scheme drew non-resident keys {drawn[~loc]}"
    w.finish_sample(h)
    srv.barrier()
    srv.shutdown()
    print(f"MP-OK sampling rank={rank}")


def scenario_bindings():
    """The torch/numpy bindings surface works across launched processes
    (the reference's bindings example runs 4 simulated nodes —
    bindings/example.py): cross-process push/pull through the bindings
    Worker, intent-driven locality, exact sums after barrier."""
    import adapm_tpu.bindings as adapm
    adapm.setup(num_keys=32, num_threads=1)  # joins jax.distributed FIRST
    rank = control.process_id()
    P = control.num_processes()
    srv = adapm.Server(4, 32)
    w = adapm.Worker(0, srv)
    keys = np.arange(32, dtype=np.int64)
    vals = np.ones((32, 4), np.float32)
    ts = w.push(keys, vals, asynchronous=True)
    w.wait(ts)
    srv.barrier()
    out = np.zeros((32, 4), np.float32)
    w.pull(keys, out)
    assert np.allclose(out, P), out[:2]
    w.intent(keys[:4], w.current_clock, w.current_clock + 10)
    w.wait_sync()
    srv.barrier()
    w.finalize()
    srv.shutdown()
    print(f"MP-OK bindings rank={rank}")


def scenario_heartbeat():
    """Heartbeat + dead-node detection (reference van heartbeats +
    Postoffice::GetDeadNodes): rank 1 stops beating; rank 0 must report it
    dead within the age window, while a beating rank stays undetected."""
    import time
    srv = adapm_tpu.setup(16, 4, opts=SystemOptions(
        sync_max_per_sec=0, heartbeat_s=0.3))
    rank = control.process_id()
    time.sleep(1.0)  # everyone has beaten at least once
    assert srv.dead_nodes(max_age_s=5.0) == [], "live peers reported dead"
    srv.barrier()
    if rank == 1:
        control.stop_heartbeat()
    srv.barrier()
    if rank == 0:
        deadline = time.time() + 20
        while time.time() < deadline:
            dead = srv.dead_nodes(max_age_s=1.5)
            if dead == [1]:
                break
            time.sleep(0.3)
        assert dead == [1], f"rank 1 not detected dead: {dead}"
    srv.barrier()
    srv.shutdown()
    print(f"MP-OK heartbeat rank={rank}")


def scenario_elastic():
    """The documented recovery loop (docs/failure_handling.md) end to end,
    driven by the LAUNCHER KEEPALIVE rather than a scripted second launch:
    train -> checkpoint -> crash with exit code 254 mid-epoch (work after
    the checkpoint is lost) -> keepalive restarts the ranks with the same
    env -> restore_server -> values, adapted placement, and the
    consistency invariant hold (reference dmlc_local.py:15-25 restart
    contract + this repo's whole-manager checkpoints)."""
    from adapm_tpu.utils.checkpoint import restore_server, save_server
    path = sys.argv[2]
    srv = adapm_tpu.setup(48, 4, opts=SystemOptions(sync_max_per_sec=0))
    rank = control.process_id()
    P = control.num_processes()
    marker = f"{path}.attempt.rank{rank}"
    first_attempt = not os.path.exists(marker)
    w = srv.make_worker(0)
    keys = np.arange(48, dtype=np.int64)
    if first_attempt:
        open(marker, "w").write("1")
        if rank == 0:
            w.wait(w.set(keys, np.ones((48, 4), np.float32)))
        srv.barrier()
        # adapt placement so the restore must carry it: rank 1 takes
        # ownership of rank-0 keys before the checkpoint
        moved = owned_by_proc(srv, 0, 4)
        if rank == 1:
            w.intent(moved, 0, CLOCK_MAX)
            srv.wait_sync()
        srv.barrier()
        w.wait(w.push(keys, np.ones((48, 4), np.float32)))
        w.wait_all()
        save_server(srv, path)  # the per-epoch checkpoint
        # mid-epoch work after the checkpoint: lost in the crash
        w.wait(w.push(keys, np.full((48, 4), 7.0, np.float32)))
        w.wait_all()
        srv.barrier()  # both ranks reach the crash point
        # crash: no shutdown, no coordinator teardown — the keepalive
        # contract restarts this rank with the same rank/env
        sys.stdout.flush()
        os._exit(254)
    # restarted attempt: recover from the checkpoint
    restore_server(srv, path)
    base = np.full((48, 4), 1.0 + P, np.float32)  # set(1) + P pushes(+1)
    v = w.pull_sync(keys)
    assert np.allclose(v, base), \
        f"rank {rank}: restored values wrong (lost work resurrected?)\n{v[:2]}"
    moved = owned_by_proc(srv, 0, 4)
    if rank == 1:
        assert (srv.ab.owner[moved] >= 0).all(), "adapted ownership lost"
    if rank == 0:
        assert (srv.ab.owner[moved] == REMOTE).all(), "relocation lost"
    srv.barrier()
    # the restored manager still satisfies eventual consistency
    w.wait(w.push(keys, np.ones((48, 4), np.float32)))
    w.wait(w.push(keys, -np.ones((48, 4), np.float32)))
    w.wait_all()
    srv.wait_sync()
    srv.barrier()
    srv.wait_sync()
    srv.barrier()
    v = w.pull_sync(keys)
    assert np.allclose(v, base, atol=1e-4), f"rank {rank}: not consistent"
    srv.shutdown()
    open(f"{path}.done.rank{rank}", "w").write("1")
    print(f"MP-OK elastic rank={rank}")


SCENARIOS = {
    "pullpush": scenario_pullpush,
    "elastic": scenario_elastic,
    "intent_locality": scenario_intent_locality,
    "monotonic": scenario_monotonic,
    "eventual": scenario_eventual,
    "cadence": scenario_cadence,
    "kge_eval_chunk": scenario_kge_eval_chunk,
    "coll_pullpush": scenario_coll_pullpush,
    "location_caches": scenario_location_caches,
    "ckpt_save": scenario_ckpt_save,
    "ckpt_restore": scenario_ckpt_restore,
    "heartbeat": scenario_heartbeat,
    "sampling": scenario_sampling,
    "kge_app": scenario_kge_app,
    "bindings": scenario_bindings,
    "stress": scenario_stress,
}

if __name__ == "__main__":
    SCENARIOS[sys.argv[1]]()
