"""Fault/crash-recovery drill (ISSUE 10 acceptance; run by
scripts/run_tests.sh).

Four checks over the robustness spine (adapm_tpu/fault,
docs/failure_handling.md):

1. STORM CORRECTNESS UNDER INJECTED FAULTS: a seeded
   push/set/serve/promote/sync storm runs against a server with the
   fault plane injecting transient failures into the background sync
   tick, the serve drains, tier promotion commits, executor dispatch,
   and checkpoint saves — while an UNINJECTED, untiered shadow server
   applies the identical write sequence. Every serve lookup must be
   bit-identical to the shadow's Worker.pull of the same keys (no torn
   or stale read, ever — a retried drain serves the same bits a
   healthy one would), and after quiesce the two servers' full main
   tables must match bitwise. The drill also asserts the faults
   actually FIRED and were RETRIED (an inert plane would vacuously
   pass).

2. KILL + RESTORE: mid-storm the injected server checkpoints to an
   incremental chain (base + dirty-slot deltas; saves themselves are
   injected and retried), keeps storming PAST the last save (writes
   that are deliberately lost), and is then killed under concurrent
   serve load. A fresh server restores from the chain and must read
   bit-exactly the state at the last checkpoint — mains AND replica
   reads — within ADAPM_RECOVERY_MAX_S (default 60 s) of recovery
   wall time.

3. DEGRADED-MODE SHEDDING: while the restore applies (the window is
   held open with restore_chain's hold_degraded_s so the pin is
   deterministic on any machine), concurrent lookups must shed with
   the DISTINCT ServeDegradedError — every hammer outcome is either a
   clean pre/post-window value or that error; nothing hangs, nothing
   returns a mixed read.

4. INCREMENTAL BYTES: on a second server, a ~1%-dirty trickle's delta
   link must cost <= ADAPM_CKPT_DELTA_RATIO_MAX (default 0.10) of the
   full base checkpoint — the whole point of shipping only dirty
   slots.
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
    os.environ["XLA_FLAGS"] = " ".join([_flags, mesh_flags(4)]).strip()

import numpy as np  # noqa: E402

E = 2048
L = 8
SEED = int(os.environ.get("ADAPM_FAULT_DRILL_SEED", "1234"))
FAULT_SPEC = ("sync.round=0.25,serve.drain=0.2,tier.promote=0.2,"
              "exec.dispatch=0.02,ckpt.save=0.3")


def log(msg):
    print(f"[fault-drill] {msg}", flush=True)


def _mk(fault: bool, tier: bool):
    import adapm_tpu
    from adapm_tpu.config import SystemOptions
    opts = SystemOptions(
        sync_max_per_sec=0, prefetch=False,
        cache_slots_per_shard=64,
        tier=tier, tier_hot_rows=256,
        serve_max_wait_us=100,
        fault_spec=FAULT_SPEC if fault else "",
        fault_seed=SEED, fault_retries=12, fault_backoff_ms=2.0)
    return adapm_tpu.setup(E, L, opts=opts, num_workers=4)


def _save_retrying(ck, tries: int = 20):
    """ckpt.save is itself an injection point (p=0.3): the operator
    loop retries — atomic tmp+rename writes make a failed save
    invisible, so retrying is always safe."""
    from adapm_tpu.fault import InjectedFault
    for _ in range(tries):
        try:
            return ck.save()
        except InjectedFault:
            continue
    raise RuntimeError("checkpoint save exhausted its retry budget")


def main() -> int:
    import adapm_tpu  # noqa: F401
    from adapm_tpu.base import CLOCK_MAX
    from adapm_tpu.fault import IncrementalCheckpointer, restore_chain
    from adapm_tpu.serve import (DeadlineExceededError,
                                 ServeDegradedError, ServePlane)

    recovery_max_s = float(os.environ.get("ADAPM_RECOVERY_MAX_S", "60"))
    delta_ratio_max = float(os.environ.get(
        "ADAPM_CKPT_DELTA_RATIO_MAX", "0.10"))
    chain_dir = os.path.join("/tmp", f"adapm_fault_drill_{os.getpid()}")

    rng = np.random.default_rng(SEED)
    log(f"building injected server (spec {FAULT_SPEC!r}, seed {SEED}) "
        f"+ uninjected untiered shadow")
    srv = _mk(fault=True, tier=True)
    ref = _mk(fault=False, tier=False)
    w, wr = srv.make_worker(0), ref.make_worker(0)
    init = rng.normal(size=(E, L)).astype(np.float32)
    w.set(np.arange(E), init)
    wr.set(np.arange(E), init)
    # adapted placement on the injected side: replicas via competing
    # intents (the chain must carry them through the kill)
    w1 = srv.make_worker(1)
    shared = np.arange(0, 48)
    w.intent(shared, 0, CLOCK_MAX)
    w1.intent(shared, 0, CLOCK_MAX)
    srv.wait_sync()

    plane = ServePlane(srv)
    sess = plane.session()
    ck = IncrementalCheckpointer(srv, chain_dir)
    _save_retrying(ck)  # base
    srv.start_sync_thread()
    ref.start_sync_thread()

    # ---- 1. storm under injected faults, lookups vs the shadow ----------
    lookups = sheds = 0
    for step in range(60):
        keys = np.unique(rng.integers(0, E, 96))
        vals = rng.normal(size=(len(keys), L)).astype(np.float32)
        if step % 11 == 3:
            w.set(keys, vals)
            wr.set(keys, vals)
        else:
            w.push(keys, vals)
            wr.push(keys, vals)
        if step % 3 == 0:
            qk = np.unique(rng.integers(0, E, 64))
            try:
                got = np.asarray(sess.lookup(qk, deadline_ms=5000))
            except DeadlineExceededError:
                sheds += 1
                continue
            exp = np.asarray(wr.pull_sync(qk))
            assert np.array_equal(got, exp), (
                f"step {step}: serve lookup diverged from the "
                f"uninjected shadow ({int((got != exp).sum())} floats)"
                f" — torn or stale read under injected faults")
            lookups += 1
        if step % 15 == 14:
            _save_retrying(ck)
    srv.stop_sync_thread()
    ref.stop_sync_thread()
    srv.quiesce()
    ref.quiesce()
    a = np.asarray(srv.read_main(np.arange(E)))
    b = np.asarray(ref.read_main(np.arange(E)))
    assert np.array_equal(a, b), (
        f"post-quiesce main tables diverged "
        f"({int((a != b).sum())} floats): injected transient faults "
        f"corrupted state despite retries")
    snap = srv.metrics_snapshot()
    fired = snap["fault"]["injections_fired"]
    retries = snap["fault"]["retries"]          # executor policy
    loop_retries = snap["fault"]["loop_retries"]  # self-healing loops
    assert fired >= 5, f"only {fired} injections fired — drill vacuous"
    assert retries >= 1, \
        f"executor retry policy never engaged ({retries} retries)"
    assert retries + loop_retries >= 3, (
        f"only {retries}+{loop_retries} retries — recovery machinery "
        f"not engaged")
    log(f"storm OK: {lookups} verified bit-identical lookups "
        f"({sheds} deadline-shed), {fired} injections fired, "
        f"{retries} executor retries + {loop_retries} loop retries, "
        f"post-quiesce tables bit-equal")

    # ---- 2. final checkpoint, storm past it, kill under load ------------
    final = _save_retrying(ck)
    expected_main = a.copy()
    expected_pull = np.asarray(w.pull_sync(np.arange(E))).copy()
    log(f"final checkpoint: chain of {ck.stats()['chain_len']} links, "
        f"last {final['kind']} = {final['bytes']}B / "
        f"{final['slots']} slots")
    srv.start_sync_thread()
    stop_storm = threading.Event()
    kill_outcomes = []

    def kill_hammer():
        s2 = plane.session()
        while not stop_storm.is_set():
            try:
                s2.lookup(np.arange(16), deadline_ms=500)
                kill_outcomes.append("ok")
            except Exception as e:  # noqa: BLE001 — the kill races
                # everything; the assertion is "no hang, no crash"
                kill_outcomes.append(type(e).__name__)
            time.sleep(0.002)

    hammers = [threading.Thread(target=kill_hammer, daemon=True)
               for _ in range(3)]
    for t in hammers:
        t.start()
    for _ in range(10):  # post-checkpoint writes: deliberately lost
        keys = np.unique(rng.integers(0, E, 96))
        w.push(keys, rng.normal(size=(len(keys), L)).astype(np.float32))
    t_kill = time.perf_counter()
    srv.shutdown()  # the kill, under concurrent serve load
    stop_storm.set()
    for t in hammers:
        t.join(10)
    log(f"killed mid-storm in {time.perf_counter() - t_kill:.2f}s "
        f"({len(kill_outcomes)} concurrent lookups rode the kill: "
        f"{sorted(set(kill_outcomes))})")

    # ---- 3. restore into a fresh server, degraded window pinned ---------
    srv2 = _mk(fault=False, tier=True)
    w2 = srv2.make_worker(0)
    plane2 = ServePlane(srv2)
    sess2 = plane2.session()
    outcomes = []
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                v = np.asarray(sess2.lookup(np.arange(12),
                                            deadline_ms=2000))
                outcomes.append(("ok", v.copy()))
            except ServeDegradedError:
                outcomes.append(("degraded", None))
            except Exception as e:  # noqa: BLE001
                outcomes.append((type(e).__name__, None))
            time.sleep(0.002)

    ham = [threading.Thread(target=hammer, daemon=True)
           for _ in range(3)]
    for t in ham:
        t.start()
    recovery_s = restore_chain(srv2, chain_dir, hold_degraded_s=0.5)
    time.sleep(0.1)
    stop.set()
    for t in ham:
        t.join(10)

    got_main = np.asarray(srv2.read_main(np.arange(E)))
    assert np.array_equal(got_main, expected_main), (
        f"post-restore read_main not bit-exact vs the last checkpoint "
        f"({int((got_main != expected_main).sum())} floats)")
    got_pull = np.asarray(w2.pull_sync(np.arange(E)))
    assert np.array_equal(got_pull.ravel(), expected_pull.ravel()), \
        "post-restore replica reads not bit-exact"
    assert recovery_s <= recovery_max_s, (
        f"recovery took {recovery_s:.2f}s > bound {recovery_max_s}s")
    kinds = {}
    for k, _ in outcomes:
        kinds[k] = kinds.get(k, 0) + 1
    assert kinds.get("degraded", 0) >= 1, (
        f"no lookup observed the degraded window: {kinds}")
    bad = set(kinds) - {"ok", "degraded", "DeadlineExceededError"}
    assert not bad, f"unexpected lookup outcomes during restore: {kinds}"
    # every successful hammer read is a CLEAN state: the fresh server's
    # zeros (pre-window) or the restored bits (post-window) — never a
    # mix (keys 0..11 are uniform-length, so the slices align)
    pre = np.zeros((12, L), np.float32)
    post = expected_main[: 12 * L].reshape(12, L)
    for k, v in outcomes:
        if k == "ok":
            assert (np.array_equal(v, pre)
                    or np.array_equal(v, post)), \
                "hammer lookup returned a torn/mixed read"
    # post-restore serving is live and bit-exact
    assert np.array_equal(np.asarray(sess2.lookup(np.arange(12))), post)
    assert plane2.health.readiness()["ready"]
    log(f"restore OK: recovery_s={recovery_s:.3f} "
        f"(bound {recovery_max_s}), hammer outcomes {kinds}, "
        f"degraded sheds carried ServeDegradedError, post-restore "
        f"reads bit-exact")
    srv2.shutdown()

    # ---- 4. incremental bytes: 1%-dirty trickle -------------------------
    import adapm_tpu as _a
    from adapm_tpu.config import SystemOptions
    srv3 = _a.setup(8192, 16,
                    opts=SystemOptions(sync_max_per_sec=0,
                                       prefetch=False),
                    num_workers=2)
    w3 = srv3.make_worker(0)
    w3.set(np.arange(8192),
           rng.normal(size=(8192, 16)).astype(np.float32))
    ck3 = IncrementalCheckpointer(
        srv3, os.path.join(chain_dir, "trickle"))
    base = ck3.save()
    dirty = rng.choice(8192, size=82, replace=False)  # ~1%
    w3.push(dirty, np.ones((82, 16), np.float32))
    delta = ck3.save()
    ratio = delta["bytes"] / base["bytes"]
    log(f"incremental bytes: base {base['bytes']}B, 1%-dirty delta "
        f"{delta['bytes']}B ({delta['slots']} slots) -> ratio "
        f"{ratio:.4f} (bound {delta_ratio_max})")
    assert ratio <= delta_ratio_max, (
        f"1%-dirty delta costs {ratio:.3f} of a full checkpoint "
        f"(bound {delta_ratio_max}) — the dirty-slot filter is broken")
    srv3.shutdown()
    ref.shutdown()

    log("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
