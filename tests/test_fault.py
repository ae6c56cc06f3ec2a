"""Fault-injection plane, executor error policy, incremental
checkpoint chains, and degraded-mode serving (ISSUE 10 tentpole).

The load-bearing pins:
  - the seeded injection plane is DETERMINISTIC per point and free
    when off (`Server.fault is None`, zero fault.* registry names —
    also guarded by scripts/metrics_overhead_check.py);
  - transient executor-program failures retry with bounded exponential
    backoff and the completion sees ONE final outcome; fatal failures
    surface unchanged; the watchdog names a wedged stream without
    blocking behind it;
  - an incremental chain (base + dirty-slot deltas) restores BIT-EXACT
    manager state — mains, dirty replica bases+deltas, placement
    tables, clocks — and a 1%-dirty trickle's delta is a small
    fraction of the base (the full end-to-end drill with a killed
    server lives in scripts/fault_drill_check.py);
  - during a degraded window (restore in progress) serve lookups shed
    loudly with ServeDegradedError — at the session door AND for
    already-queued requests — and readiness reports the reason.
"""
import os
import time

import numpy as np
import pytest

import adapm_tpu
from adapm_tpu.base import CLOCK_MAX
from adapm_tpu.config import SystemOptions
from adapm_tpu.fault import (CheckpointChainError, FatalInjectedFault,
                             FaultPlane, IncrementalCheckpointer,
                             InjectedFault, RetryPolicy,
                             TransientFaultError, parse_fault_spec,
                             restore_chain)

E = 128
L = 4


def _mk(**kw):
    opts = SystemOptions(sync_max_per_sec=0, prefetch=False, **kw)
    return adapm_tpu.setup(E, L, opts=opts, num_workers=2)


# ---------------------------------------------------------------------------
# injection plane
# ---------------------------------------------------------------------------


def test_fault_spec_parse_and_rejection():
    assert parse_fault_spec("a.b=0.5, c=1; d.e.f=0") == {
        "a.b": 0.5, "c": 1.0, "d.e.f": 0.0}
    for bad in ("nope", "x=2", "x=-0.1", "x=abc", "=0.5"):
        with pytest.raises(ValueError):
            parse_fault_spec(bad)
    # the same validation runs at options-validation time
    with pytest.raises(ValueError):
        SystemOptions(fault_spec="x=7").validate_serve()
    with pytest.raises(ValueError):
        SystemOptions(fault_watchdog_s=0).validate_serve()
    with pytest.raises(ValueError):
        SystemOptions(ckpt_every_s=1.0).validate_serve()  # no path


def test_fault_plane_deterministic_per_point_and_off_by_default():
    def fire_seq(plane, point, n):
        out = []
        for _ in range(n):
            try:
                plane.fire(point)
                out.append(False)
            except InjectedFault:
                out.append(True)
        return out

    a = FaultPlane("p.one=0.5,p.two=0.3", seed=42)
    b = FaultPlane("p.one=0.5,p.two=0.3", seed=42)
    # interleave differently on b: per-point RNG streams make the Nth
    # evaluation of a point identical regardless of other points
    seq_a = fire_seq(a, "p.one", 50)
    fire_seq(b, "p.two", 17)
    assert fire_seq(b, "p.one", 50) == seq_a
    assert any(seq_a) and not all(seq_a)
    # a different seed draws a different sequence
    c = FaultPlane("p.one=0.5", seed=43)
    assert fire_seq(c, "p.one", 50) != seq_a
    # unconfigured point: silent no-op
    a.fire("never.configured")
    # counts surface per point
    evals, fired = a.counts("p.one")
    assert evals == 50 and fired == sum(seq_a)
    # fatal variant raises the non-transient class
    d = FaultPlane("x=1.0", seed=0)
    with pytest.raises(FatalInjectedFault):
        d.fire("x", transient=False)
    assert not issubclass(FatalInjectedFault, TransientFaultError)


def test_fault_off_by_default_zero_cost_shape():
    """Default server: no plane, no fault.* registry names, fault/ckpt
    snapshot sections present but empty (schema v9)."""
    srv = _mk()
    try:
        assert srv.fault is None
        assert not [n for n in srv.obs.names()
                    if n.startswith("fault.")]
        snap = srv.metrics_snapshot()
        assert snap["schema_version"] == 17
        assert snap["fault"] == {} and snap["ckpt"] == {}
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# executor error policy: retry / backoff / watchdog
# ---------------------------------------------------------------------------


def test_executor_retries_transient_and_surfaces_fatal():
    srv = _mk(fault_backoff_ms=1.0)
    try:
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise TransientFaultError("flaky")
            return "ok"

        c = srv.exec.submit("t", flaky)
        assert c.result(10) == "ok"
        assert calls["n"] == 3
        st = srv.exec.fault_stats()
        assert st["retries"] >= 2 and st["backoff_s"] > 0

        # fatal errors surface unchanged, no retry
        fatal = {"n": 0}

        def boom():
            fatal["n"] += 1
            raise ValueError("fatal")

        c2 = srv.exec.submit("t", boom)
        with pytest.raises(ValueError):
            c2.result(10)
        assert fatal["n"] == 1
    finally:
        srv.shutdown()


def test_executor_retry_budget_exhausts_loudly():
    srv = _mk(fault_retries=2, fault_backoff_ms=1.0)
    try:
        calls = {"n": 0}

        def always():
            calls["n"] += 1
            raise TransientFaultError("always")

        c = srv.exec.submit("t", always)
        with pytest.raises(TransientFaultError):
            c.result(10)
        # initial attempt + exactly the retry budget
        assert calls["n"] == 3
    finally:
        srv.shutdown()


def test_executor_retry_preserves_stream_fifo():
    """A retrying head program still blocks its stream (ordered means
    ordered): the program queued behind it runs only after the final
    attempt."""
    srv = _mk(fault_backoff_ms=1.0)
    try:
        order = []

        def flaky():
            order.append("a")
            if order.count("a") < 2:
                raise TransientFaultError("once")

        srv.exec.submit("s", flaky)
        c2 = srv.exec.submit("s", lambda: order.append("b"))
        c2.result(10)
        assert order == ["a", "a", "b"]
    finally:
        srv.shutdown()


def test_executor_watchdog_marks_wedged_stream():
    srv = _mk()
    try:
        import threading
        release = threading.Event()
        started = threading.Event()

        def stuck():
            started.set()
            release.wait(10)

        c = srv.exec.submit("w", stuck)
        assert started.wait(5)
        time.sleep(0.1)
        wedged = srv.exec.wedged_streams(0.05)
        assert [w["stream"] for w in wedged] == ["w"]
        assert srv.exec.fault_stats()["wedge_flips"] == 1
        # excluded streams are skipped (the serve drains' contract)
        assert srv.exec.wedged_streams(0.05, exclude=("w",)) == []
        release.set()
        c.result(10)
        assert srv.exec.wedged_streams(0.05) == []
        # the flip counter counts EDGES, not probes
        assert srv.exec.fault_stats()["wedge_flips"] == 1
    finally:
        srv.shutdown()


def test_background_sync_survives_injected_faults():
    """The pre-PR failure mode: one transient tick failure silently
    killed the background sync loop. With the plane injecting and the
    policy retrying, rounds keep flowing and the injections are
    visible in the fault section."""
    srv = _mk(fault_spec="sync.round=0.4", fault_seed=3,
              fault_backoff_ms=1.0, fault_retries=10)
    try:
        w = srv.make_worker(0)
        w.set(np.arange(E), np.ones((E, L), np.float32))
        srv.start_sync_thread()
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if (srv.sync.stats.rounds >= 5
                    and srv.fault.counts("sync.round")[1] >= 2):
                break
            time.sleep(0.05)
        srv.stop_sync_thread()
        assert srv.sync.stats.rounds >= 5, "sync loop died under faults"
        assert srv.fault.counts("sync.round")[1] >= 2
        snap = srv.metrics_snapshot()
        assert snap["fault"]["injections_fired"] >= 2
        # the tick is a SELF-HEALING loop: it catches its own failures
        # and reschedules with backoff (fault.loop_retries_total) —
        # the executor policy's bounded budget must not be its lifeline
        assert snap["fault"]["loop_retries"] >= 2
    finally:
        srv.shutdown()


def test_background_sync_immortal_past_retry_budget():
    """The review-caught gap: a failure streak LONGER than the
    executor retry budget must still not kill the loop. With p=1.0
    every tick fails forever — the loop keeps rescheduling itself with
    backoff, and turning injection off (end of the streak, simulated
    by zeroing the point's probability) lets rounds flow again."""
    srv = _mk(fault_spec="sync.round=1.0", fault_seed=0,
              fault_retries=1, fault_backoff_ms=1.0)
    try:
        srv.start_sync_thread()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and \
                srv.fault.counts("sync.round")[1] < 5:
            time.sleep(0.02)
        assert srv.fault.counts("sync.round")[1] >= 5, \
            "loop died inside the failure streak"
        assert srv.sync.stats.rounds == 0
        # streak ends: the still-alive loop resumes real rounds
        srv.fault._points["sync.round"].prob = 0.0
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and srv.sync.stats.rounds < 3:
            time.sleep(0.02)
        srv.stop_sync_thread()
        assert srv.sync.stats.rounds >= 3, \
            "loop did not recover after the failure streak ended"
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# incremental checkpoint chain
# ---------------------------------------------------------------------------


def _chained_state(tmp_path, rng):
    """Server with an adapted placement + a 3-link chain; returns
    (path, expected read_main, expected pull, owner/cache tables)."""
    srv = _mk(cache_slots_per_shard=16)
    w0, w1 = srv.make_worker(0), srv.make_worker(1)
    w0.set(np.arange(E), rng.normal(size=(E, L)).astype(np.float32))
    path = str(tmp_path / "chain")
    ck = IncrementalCheckpointer(srv, path)
    base = ck.save()
    assert base["kind"] == "base"
    # delta 1: plain trickle
    w0.push(np.arange(7), np.ones((7, L), np.float32))
    d1 = ck.save()
    assert d1["kind"] == "delta" and d1["slots"] >= 7
    # delta 2: replica churn + a dirty (unshipped) replica delta
    shared = np.array([5, 9, 13])
    w0.intent(shared, 0, CLOCK_MAX)
    w1.intent(shared, 0, CLOCK_MAX)
    srv.wait_sync()
    w0.push(shared, np.full((3, L), 0.25, np.float32))
    srv.block()
    ck.save()
    expected_main = np.asarray(srv.read_main(np.arange(E)))
    expected_pull = np.asarray(w0.pull_sync(np.arange(E)))
    owner = srv.ab.owner.copy()
    cache_slot = srv.ab.cache_slot.copy()
    srv.shutdown()
    return path, expected_main, expected_pull, owner, cache_slot


def test_chain_roundtrip_bit_exact(tmp_path, rng):
    path, exp_main, exp_pull, owner, cache_slot = \
        _chained_state(tmp_path, rng)
    srv2 = _mk(cache_slots_per_shard=16)
    w0b = srv2.make_worker(0)
    recovery_s = restore_chain(srv2, path)
    assert recovery_s > 0
    assert not srv2.degraded  # cleared on success
    assert (srv2.ab.owner == owner).all()
    assert (srv2.ab.cache_slot == cache_slot).all()
    got_main = np.asarray(srv2.read_main(np.arange(E)))
    assert np.array_equal(got_main, exp_main), "read_main not bit-exact"
    # replica reads (base + pending delta) survive the chain bitwise
    got_pull = np.asarray(w0b.pull_sync(np.arange(E)))
    assert np.array_equal(got_pull, exp_pull), "pull not bit-exact"
    # recovery_s lands in the ckpt snapshot section
    assert srv2.metrics_snapshot()["ckpt"]["recovery_s"] == recovery_s
    # the restored manager keeps working: flush the restored deltas
    srv2.quiesce()
    assert np.isfinite(srv2.read_main(np.arange(E))).all()
    srv2.shutdown()


def test_chain_delta_bytes_small_for_sparse_trickle(tmp_path, rng):
    """A ~1%-dirty trickle's delta link must be a small fraction of
    the base (the incremental contract; the 10% acceptance bound at
    bench scale is enforced by scripts/fault_drill_check.py)."""
    opts = SystemOptions(sync_max_per_sec=0, prefetch=False)
    srv = adapm_tpu.setup(4096, 16, opts=opts, num_workers=2)
    try:
        w = srv.make_worker(0)
        w.set(np.arange(4096),
              rng.normal(size=(4096, 16)).astype(np.float32))
        ck = IncrementalCheckpointer(srv, str(tmp_path / "chain"))
        base = ck.save()
        dirty = rng.choice(4096, size=41, replace=False)
        w.push(dirty, np.ones((41, 16), np.float32))
        delta = ck.save()
        assert delta["slots"] == 41
        assert delta["bytes"] <= 0.10 * base["bytes"], (
            f"1%-dirty delta {delta['bytes']}B vs base "
            f"{base['bytes']}B")
    finally:
        srv.shutdown()


def test_periodic_checkpointer_runs_on_ckpt_stream(tmp_path):
    srv = _mk(ckpt_every_s=0.03, ckpt_path=str(tmp_path / "chain"))
    try:
        w = srv.make_worker(0)
        w.set(np.arange(E), np.ones((E, L), np.float32))
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and srv.ckpt.saves_total < 2:
            time.sleep(0.02)
        assert srv.ckpt.saves_total >= 2, "periodic ckpt never ran"
        snap = srv.metrics_snapshot()
        assert snap["ckpt"]["saves_total"] >= 2
        assert snap["ckpt"]["bases_total"] == 1
    finally:
        srv.shutdown()
    # shutdown drained the stream; the chain restores cleanly
    srv2 = _mk()
    restore_chain(srv2, str(tmp_path / "chain"))
    assert np.allclose(srv2.read_main(np.arange(E)), 1.0)
    srv2.shutdown()


def test_restore_rejects_geometry_mismatch_untouched(tmp_path, rng):
    path, exp_main, _, _, _ = _chained_state(tmp_path, rng)
    other = adapm_tpu.setup(
        64, L, opts=SystemOptions(sync_max_per_sec=0, prefetch=False))
    try:
        before = np.asarray(other.read_main(np.arange(64)))
        with pytest.raises(CheckpointChainError, match="mismatch"):
            restore_chain(other, path)
        # verification failed BEFORE mutation: live server untouched
        assert not other.degraded
        assert np.array_equal(
            np.asarray(other.read_main(np.arange(64))), before)
    finally:
        other.shutdown()


# ---------------------------------------------------------------------------
# degraded-mode serving
# ---------------------------------------------------------------------------


def test_degraded_window_sheds_with_distinct_error():
    from adapm_tpu.serve import ServeDegradedError, ServePlane
    srv = _mk()
    plane = ServePlane(srv)
    try:
        sess = plane.session()
        w = srv.make_worker(0)
        w.set(np.arange(E), np.ones((E, L), np.float32))
        assert np.array_equal(sess.lookup(np.arange(4)),
                              np.ones((4, L), np.float32))
        srv.begin_degraded("unit-test window")
        # session door: shed before touching the queue
        with pytest.raises(ServeDegradedError, match="unit-test"):
            sess.lookup(np.arange(4))
        # readiness reports the reason
        rd = plane.health.readiness()
        assert not rd["ready"]
        assert rd["degraded"] == "unit-test window"
        assert any("degraded" in x for x in rd["reasons"])
        # a request already queued when the window opens is shed by the
        # dispatcher with the same distinct error
        from adapm_tpu.serve.admission import LookupRequest
        req = LookupRequest(np.arange(4, dtype=np.int64))
        plane.queue.submit(req)
        assert req.wait(10)
        with pytest.raises(ServeDegradedError):
            req.take_result()
        assert plane.queue.c_degraded.value >= 2
        srv.end_degraded()
        # recovery: bit-exact serving resumes
        assert np.array_equal(sess.lookup(np.arange(4)),
                              np.ones((4, L), np.float32))
        assert plane.health.readiness()["ready"]
    finally:
        plane.close()
        srv.shutdown()


def test_restore_chain_brackets_degraded_and_holds(tmp_path, rng):
    """restore_chain flips the server degraded while applying (plus
    the operational hold), and lookups during the window shed with
    ServeDegradedError — the drill's deterministic pin."""
    import threading

    from adapm_tpu.serve import ServeDegradedError, ServePlane
    path, exp_main, _, _, _ = _chained_state(tmp_path, rng)
    srv = _mk(cache_slots_per_shard=16)
    plane = ServePlane(srv)
    sess = plane.session()
    try:
        outcomes = []
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    v = sess.lookup(np.arange(8))
                    outcomes.append(("ok", np.asarray(v).copy()))
                except ServeDegradedError:
                    outcomes.append(("degraded", None))
                except Exception as e:  # noqa: BLE001
                    outcomes.append((type(e).__name__, None))
                time.sleep(0.002)

        t = threading.Thread(target=hammer, daemon=True)
        t.start()
        restore_chain(srv, path, hold_degraded_s=0.3)
        stop.set()
        t.join(5)
        kinds = {k for k, _ in outcomes}
        assert "degraded" in kinds, (
            f"no lookup shed during the degraded window: {kinds}")
        assert kinds <= {"ok", "degraded"}, kinds
        # post-restore serving is bit-exact against the chain state
        lens = srv.value_lengths[np.arange(8)]
        exp8 = exp_main[: int(lens.sum())].reshape(8, L)
        got = np.asarray(sess.lookup(np.arange(8)))
        assert np.array_equal(got, exp8)
    finally:
        plane.close()
        srv.shutdown()
