"""Multi-process parameter-manager integration tests.

The reference's core test strategy is N real server processes + a scheduler
on localhost (tracker/dmlc_local.py, SURVEY.md §4); here N real Python
processes rendezvous through the jax.distributed coordinator and exchange
parameter traffic over the DCN channel (parallel/pm.py). Scenarios live in
tests/mp_scenarios.py — the multi-process twins of
test_many_key_operations.cc / test_locality_api.cc phases.
"""
import os
import subprocess
import sys

import pytest

from adapm_tpu import launcher

HERE = os.path.dirname(os.path.abspath(__file__))
SCENARIOS = os.path.join(HERE, "mp_scenarios.py")
REPO = os.path.dirname(HERE)


def run_mp(n, scenario, devices=2, args=(), timeout=300):
    """Launch `n` ranks of a scenario; assert all exit 0."""
    env = dict(os.environ)
    # children need the repo importable
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    from xla_compat import mesh_flags
    env["XLA_FLAGS"] = mesh_flags(devices)
    # a hung scenario dumps its thread stacks + exits before our timeout
    env["ADAPM_FAULT_T"] = str(max(timeout - 20, 30))
    # oversubscribed CI host: a rank's coordination heartbeat can stall
    # past jax's 100 s default during concurrent XLA compiles and get
    # declared dead (PollForError flake); raise it for tests only
    env.setdefault("ADAPM_COORD_HEARTBEAT_S", "300")
    coordinator = f"localhost:{launcher.free_port()}"
    procs = [subprocess.Popen(
        [sys.executable, SCENARIOS, scenario, *map(str, args)],
        env=launcher.make_env(r, n, coordinator, env),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(n)]
    outs = []
    try:
        outs = [p.communicate(timeout=timeout)[0].decode() for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for r, (p, o) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{o[-4000:]}"
        assert f"MP-OK {scenario} rank={r}" in o
    return outs


# ---------------------------------------------------------------------------
# Collective-sync gating (ISSUE 19 satellite). The BSP collective data
# plane rides jaxlib's cross-process CPU collectives, which this image's
# jaxlib lacks (client init aborts on the watchdog flags — the r6 seed
# note in CHANGES.md; these were the 7 seed failures). The tests stay,
# gated on an explicit opt-in for images that have them; the SAME
# consistency/staleness invariants run in-container through the NetPort
# loopback backend (tests/test_netport.py and the reroute test below —
# docs/NETWORK.md).
# ---------------------------------------------------------------------------

requires_cpu_collectives = pytest.mark.skipif(
    os.environ.get("ADAPM_MP_COLLECTIVES", "") != "1",
    reason="needs jaxlib cross-process CPU collectives, absent from this "
           "image (set ADAPM_MP_COLLECTIVES=1 where available); the "
           "NetPort loopback reroute covers the same invariants "
           "in-container (tests/test_netport.py, docs/NETWORK.md)")


@pytest.mark.slow
@pytest.mark.parametrize("n,devices", [(2, 2), (4, 1)])
def test_mp_pull_push_set(n, devices):
    """Cross-process Pull/Push/Set land exactly (2 procs x 2 shards and
    4 procs x 1 shard — the reference tests run 3-4 nodes)."""
    run_mp(n, "pullpush", devices=devices)


@pytest.mark.slow
def test_mp_intent_relocation_replication():
    """Rank 1's intent moves rank-0-owned keys; a competing intent
    replicates them back; pushes converge after quiesce."""
    run_mp(2, "intent_locality")


@pytest.mark.slow
def test_mp_monotonic_contended_pushes():
    """Own pushes never lost under churn; final value exact (3 procs)."""
    run_mp(3, "monotonic")


@pytest.mark.slow
@pytest.mark.parametrize("tech", ["all", "replication_only",
                                  "relocation_only"])
def test_mp_eventual_consistency(tech):
    """Push+revert restores the exact base on every rank after
    WaitSync -> Barrier -> WaitSync (2 procs), under every management
    technique (reference run_tests.sh --sys.techniques variants)."""
    run_mp(2, "eventual", args=(tech,))


@pytest.mark.slow
@requires_cpu_collectives
@pytest.mark.parametrize("tech", ["all", "replication_only",
                                  "relocation_only"])
def test_mp_eventual_consistency_collective(tech):
    """The same invariant with the BSP COLLECTIVE sync data plane
    (--sys.collective_sync, parallel/collective.py — VERDICT r3 item 1):
    replica deltas and fresh values ride device all-to-all exchanges at
    the WaitSync points instead of DCN RPC; bucket 16 forces several
    padded exchange iterations."""
    run_mp(2, "eventual", args=(tech, "coll"), timeout=420)


@pytest.mark.slow
@requires_cpu_collectives
def test_mp_collective_cadence_staleness_bound():
    """--sys.collective_cadence K: a replica observes a remote push
    within ~K clock advances with NO WaitSync anywhere in between — the
    bounded-staleness contract of collective mode (VERDICT r4 item 3;
    reference: the continuously-running sync loop,
    sync_manager.h:452-520)."""
    run_mp(2, "cadence", timeout=420)


@pytest.mark.slow
@requires_cpu_collectives
@pytest.mark.parametrize("n", [2, 3])
def test_mp_collective_pull_push(n):
    """Pull/Push values ride the device-collective exchange instead of
    DCN RPC, exactly (VERDICT r4 item 4 — the SURVEY ICI mapping's
    remaining half, prototyped)."""
    run_mp(n, "coll_pullpush", devices=1 if n == 3 else 2, timeout=420)


@pytest.mark.slow
def test_mp_kge_eval_chunk_matches_dense():
    """Candidate-partitioned chunked pool eval across 2 processes equals
    the dense-matrix path on the same triples (VERDICT r4 item 5)."""
    run_mp(2, "kge_eval_chunk", timeout=420)


@pytest.mark.slow
@requires_cpu_collectives
def test_mp_eventual_collective_three_procs():
    """Collective sync with P=3: routing by owner, per-destination
    buckets, and the global-backlog loop all span more than one peer."""
    run_mp(3, "eventual", args=("all", "coll"), devices=1, timeout=420)


@pytest.mark.slow
def test_mp_location_caches_on():
    """Second pull of a relocated key takes one hop (3 procs x 1 device)."""
    run_mp(3, "location_caches", devices=1, args=(1,))


@pytest.mark.slow
def test_mp_checkpoint_crash_recovery(tmp_path):
    """Distributed checkpoint + whole-job restart: per-rank shards restore
    values, adapted placement (cross-process relocations/replicas), and
    the consistency invariant in a FRESH launch (VERDICT r2 item 8)."""
    path = str(tmp_path / "ck")
    run_mp(2, "ckpt_save", args=(path,))
    assert os.path.exists(path + ".manifest.npz")
    assert os.path.exists(path + ".rank0.npz")
    assert os.path.exists(path + ".rank1.npz")
    run_mp(2, "ckpt_restore", args=(path,))


@pytest.mark.slow
def test_mp_thread_process_stress():
    """2 worker threads x 2 processes hammer overlapping keys under intent
    churn + background sync; final main copies equal the exact global
    push counts."""
    run_mp(2, "stress", timeout=420)


@pytest.mark.slow
def test_mp_bindings():
    """The bindings surface (reference bindings/example.py's multi-node
    shape) works across 2 launched processes."""
    run_mp(2, "bindings")


@pytest.mark.slow
def test_mp_kge_app_data_parallel():
    """The full KGE app trains data-parallel across 2 processes and
    reaches the same quality bar as the single-process run."""
    run_mp(2, "kge_app", timeout=600)


@pytest.mark.slow
def test_mp_heartbeat_dead_node_detection():
    """--sys.heartbeat: a rank that stops beating is reported by
    dead_nodes() (reference GetDeadNodes, src/postoffice.cc:202-221)."""
    run_mp(2, "heartbeat")


@pytest.mark.slow
def test_mp_location_caches_off():
    """--sys.location_caches 0: hint table stays cold, routing still
    converges via the manager."""
    run_mp(3, "location_caches", devices=1, args=(0,))


@pytest.mark.slow
@pytest.mark.parametrize("scheme", ["naive", "preloc", "pool", "local"])
def test_mp_sampling_schemes(scheme):
    """All four sampling schemes draw remotely-owned keys correctly across
    processes (reference run_tests.sh sampling-scheme variants)."""
    run_mp(3, "sampling", devices=1, args=(scheme,))


@pytest.mark.slow
def test_mp_elastic_recovery_under_keepalive(tmp_path, monkeypatch):
    """The recovery loop of docs/failure_handling.md driven END TO END by
    the launcher keepalive (VERDICT r3 item 10): both ranks crash with
    exit code 254 mid-epoch after a checkpoint, launch_local restarts
    them with the same rank/env, the restarted job restores the manager
    and passes the value/placement/consistency checks."""
    path = str(tmp_path / "ck")
    # launch_local spawns with os.environ + the ADAPM contract; give the
    # children the same env run_mp does (CPU mesh, repo importable)
    monkeypatch.setenv("PYTHONPATH", REPO)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    from xla_compat import mesh_flags
    monkeypatch.setenv("XLA_FLAGS", mesh_flags(2))
    code = launcher.launch_local(
        2, [sys.executable, SCENARIOS, "elastic", path], keepalive=True)
    assert code == 0
    for r in range(2):
        assert os.path.exists(f"{path}.attempt.rank{r}"), \
            f"rank {r} never ran its first attempt"
        assert os.path.exists(f"{path}.done.rank{r}"), \
            f"rank {r} did not complete the restarted attempt"


@pytest.mark.parametrize("tech", ["all", "replication_only",
                                  "relocation_only"])
def test_mp_eventual_consistency_loopback_reroute(tech):
    """scenario_eventual rerouted through the NetPort loopback backend
    (ISSUE 19): the exact invariant the collective-gated tests pin —
    push+revert under full replication pressure restores the exact base
    on every rank after WaitSync -> Barrier -> WaitSync — runs fully
    in-container, two Servers in one process wired through
    adapm_tpu/net. Not slow-marked: this is the tier-1 stand-in for the
    gated runs above."""
    import numpy as np

    from adapm_tpu.base import CLOCK_MAX, MgmtTechniques
    from adapm_tpu.config import SystemOptions
    from adapm_tpu.net import LoopbackCluster

    cl = LoopbackCluster(
        2, num_keys=48, value_lengths=4,
        opts_factory=lambda r: SystemOptions(
            sync_max_per_sec=0, prefetch=False,
            techniques=MgmtTechniques(tech)))
    try:
        keys = np.arange(48, dtype=np.int64)
        base = np.arange(48, dtype=np.float32)[:, None] * \
            np.ones(4, np.float32)

        def scenario(rank, srv):
            w = srv.make_worker(0)
            if rank == 0:
                w.wait(w.set(keys, base))
            srv.barrier()
            w.intent(keys, 0, CLOCK_MAX)
            srv.wait_sync()
            srv.barrier()
            x = np.full((48, 4), 2.5 + rank, np.float32)
            w.wait(w.push(keys, x))
            w.wait(w.push(keys, -x))
            srv.wait_sync()
            srv.barrier()
            srv.wait_sync()
            srv.barrier()
            return w.pull_sync(keys)

        outs = cl.run(scenario)
        for rank, v in enumerate(outs):
            assert np.allclose(v, base, atol=1e-4), \
                f"rank {rank}: not restored"
    finally:
        cl.shutdown()
