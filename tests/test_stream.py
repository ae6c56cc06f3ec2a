"""Streaming plane (ISSUE 20; adapm_tpu/stream, docs/STREAMING.md):

  - default-off discipline: no --sys.stream.* knob -> no plane object,
    zero stream.* registry names, `stream` snapshot section `{}`;
  - EventLog determinism (event i is a pure function of (seed, i) —
    the property the kill/restore replay leans on) and memo bounds;
  - StreamTrainer exactly-once accounting (cursor/counter wiring,
    the plane requirement failing loudly);
  - THE DRILL: a seeded run killed mid-stream with its checkpoint
    chain lagging the live ack watermark, restored, and tail-replayed
    must hold every ACKED event exactly once — main-store values
    bitwise identical to an unkilled shadow of the same prefix;
  - the cursor riding the chain as aux state, including a restore
    into a plane-LESS server (surfaced, not dropped);
  - FreshnessSLO control law units: window extension below
    min_samples, tighten/relax direction, static-anchor bounds,
    tightest-class target;
  - per-priority-class serve SLO windows (obs/slo.py
    `_control_classes`): overridden classes walk their own lane
    window; the no-override path keeps every hook None and its
    report byte-identical.
"""
import os
import tempfile

import numpy as np
import pytest

import adapm_tpu
from adapm_tpu.config import SystemOptions

NK = 256
VLEN = 8


def _stream_opts(**kw):
    base = dict(sync_max_per_sec=0, prefetch=False, stream_batch=8)
    base.update(kw)
    return SystemOptions(**base)


def _init_vals(srv):
    w = srv.make_worker(0)
    rng = np.random.default_rng(11)
    w.wait(w.set(np.arange(NK),
                 rng.normal(size=(NK, VLEN)).astype(np.float32)))
    return w


# -- default-off ------------------------------------------------------------

def test_stream_default_off():
    srv = adapm_tpu.setup(NK, VLEN, opts=SystemOptions(
        sync_max_per_sec=0, prefetch=False))
    assert srv.stream is None
    assert not [n for n in srv.obs.names() if n.startswith("stream.")]
    snap = srv.metrics_snapshot()
    assert snap["schema_version"] == 17 and snap["stream"] == {}
    # no plane -> a trainer cannot exist (loud, not a silent no-op)
    from adapm_tpu.stream import EventLog, StreamTrainer
    with pytest.raises(RuntimeError):
        StreamTrainer(srv, EventLog(NK))
    srv.shutdown()


# -- EventLog ---------------------------------------------------------------

def test_event_log_deterministic_and_bounded():
    from adapm_tpu.stream import EventLog
    vlen = np.full(NK, VLEN, dtype=np.int64)
    a = EventLog(NK, seed=3, keys_per_event=8, bound=4)
    b = EventLog(NK, seed=3, keys_per_event=8, bound=4096)
    for i in (0, 1, 17, 1000):
        ka, va = a.event(i, vlen)
        kb, vb = b.event(i, vlen)
        assert np.array_equal(ka, kb) and np.array_equal(va, vb)
        assert len(np.unique(ka)) == len(ka)  # unique within one event
        assert ka.max() < NK and ka.min() >= 0
    # memo bound respected; evicted events regenerate bit-identically
    assert len(a._memo) <= 4
    k0, v0 = a.event(0, vlen)
    kb0, vb0 = b.event(0, vlen)
    assert np.array_equal(k0, kb0) and np.array_equal(v0, vb0)
    # different seed -> different stream
    c = EventLog(NK, seed=4, keys_per_event=8)
    kc, vc = c.event(0, vlen)
    assert not (np.array_equal(k0, kc) and np.array_equal(v0, vc))


# -- trainer accounting -----------------------------------------------------

def test_trainer_cursor_and_counters():
    from adapm_tpu.stream import EventLog, StreamTrainer
    srv = adapm_tpu.setup(NK, VLEN, opts=_stream_opts(), num_workers=2)
    _init_vals(srv)
    tr = StreamTrainer(srv, EventLog(NK, seed=5))
    assert tr.batch == 8 and tr.resumed_from == 0
    assert tr.step() == 8 and tr.cursor == 8
    assert tr.run_until(24) == 24
    st = srv.stream.stats()
    assert st["cursor"] == 24
    assert st["events_total"] == 24 and st["batches_total"] == 3
    assert st["acked_events_total"] == 24
    assert st["replayed_events_total"] == 0
    snap = srv.metrics_snapshot()
    assert snap["stream"]["cursor"] == 24
    assert snap["stream"]["trainer"]["batch"] == 8
    srv.shutdown()


# -- the kill/restore drill -------------------------------------------------

def test_kill_restore_drill_bitwise_vs_shadow():
    """Mid-stream kill with the chain LAGGING the ack watermark,
    restore, replay the acked tail: every acked event applied exactly
    once — bitwise vs an unkilled shadow of the same prefix."""
    from adapm_tpu.fault.ckpt import IncrementalCheckpointer, \
        restore_chain
    from adapm_tpu.stream import EventLog, StreamTrainer
    allk = np.arange(NK)
    with tempfile.TemporaryDirectory() as tmp:
        chain = os.path.join(tmp, "chain")
        # -- run A: ingest to 72, but the last chain link is at 40 ----
        srv = adapm_tpu.setup(NK, VLEN, opts=_stream_opts(),
                              num_workers=2)
        _init_vals(srv)
        tr = StreamTrainer(srv, EventLog(NK, seed=5))
        ck = IncrementalCheckpointer(srv, chain)
        ck.save()                       # base link (cursor 0)
        tr.run_until(40)
        ck.save()                       # delta link (cursor 40)
        tr.run_until(72)                # acked past the chain: 72
        acked = tr.cursor
        assert acked == 72
        srv.shutdown()                  # the kill
        # -- restore: chain lands BEHIND the watermark ----------------
        srv2 = adapm_tpu.setup(NK, VLEN, opts=_stream_opts(),
                               num_workers=2)
        srv2.make_worker(0)             # worker-id parity with run A
        restore_chain(srv2, chain)
        assert int(srv2.stream.cursor[0]) == 40
        tr2 = StreamTrainer(srv2, EventLog(NK, seed=5))
        assert tr2.resumed_from == 40
        replayed = tr2.replay_tail(acked)
        assert replayed == 32 and tr2.cursor == 72
        assert int(srv2.stream.c_replayed.value) == 32
        got = srv2.read_main(allk)
        srv2.shutdown()
        # -- unkilled shadow: same seed, same prefix, no kill ---------
        srv3 = adapm_tpu.setup(NK, VLEN, opts=_stream_opts(),
                               num_workers=2)
        _init_vals(srv3)
        tr3 = StreamTrainer(srv3, EventLog(NK, seed=5))
        tr3.run_until(72)
        want = srv3.read_main(allk)
        srv3.shutdown()
        # exactly once, bitwise: a lost acked event or a double-applied
        # replay both break float-add equality
        assert np.array_equal(got, want)


def test_cursor_restore_into_planeless_server():
    """A chain carrying the cursor restored into a server with NO
    stream plane surfaces the watermark instead of dropping it."""
    from adapm_tpu.fault.ckpt import IncrementalCheckpointer, \
        restore_chain
    from adapm_tpu.stream import EventLog, StreamTrainer
    with tempfile.TemporaryDirectory() as tmp:
        chain = os.path.join(tmp, "chain")
        srv = adapm_tpu.setup(NK, VLEN, opts=_stream_opts(),
                              num_workers=2)
        _init_vals(srv)
        StreamTrainer(srv, EventLog(NK, seed=5)).run_until(16)
        IncrementalCheckpointer(srv, chain).save()
        srv.shutdown()
        srv2 = adapm_tpu.setup(NK, VLEN, opts=SystemOptions(
            sync_max_per_sec=0, prefetch=False), num_workers=2)
        assert srv2.stream is None
        restore_chain(srv2, chain)
        assert srv2._restored_stream_cursor == 16
        srv2.shutdown()


# -- freshness controller law ----------------------------------------------

def _fresh_srv(tmp_path, slo_ms=50.0, **kw):
    # trace_flight exports at shutdown: into tmp_path, not the cwd
    return adapm_tpu.setup(NK, VLEN, opts=SystemOptions(
        sync_max_per_sec=2.0, prefetch=False, metrics=True,
        trace_flight=True,
        trace_flight_out=str(tmp_path / "flight.trace.json"),
        stream_freshness_slo_ms=slo_ms, **kw))


def test_freshness_law_direction_and_bounds(tmp_path):
    srv = _fresh_srv(tmp_path)
    ctl = srv.stream.freshness
    assert ctl is not None and ctl.target_s == 0.05
    h = srv.flight.freshness.h_freshness
    sm = srv.sync
    assert sm.effective_max_per_sec == 2.0
    # prime tick (no previous window mark): never moves
    ctl._control()
    assert int(ctl.c_adjust.value) == 0
    # window extension: 2 samples < min_samples leaves the mark put...
    h.observe(1.0), h.observe(1.0)
    ctl._control()
    assert int(ctl.c_adjust.value) == 0
    # ...two more complete the SAME window -> tighten (P99 1s >> 50ms)
    h.observe(1.0), h.observe(1.0)
    ctl._control()
    assert int(ctl.c_adjust.value) == 1
    assert sm.effective_max_per_sec > 2.0
    assert ctl.first_adjustment is not None
    (lever, old, new) = ctl.first_adjustment[2][0]
    assert lever == "sync_rate" and new > old
    # keep tightening: the rate caps at 64x static, never beyond
    for _ in range(30):
        for _ in range(4):
            h.observe(1.0)
        ctl._control()
    assert sm.effective_max_per_sec == pytest.approx(128.0)
    # relax on a far-below-target window: walks back, floored at the
    # operator's static knob
    for _ in range(40):
        for _ in range(4):
            h.observe(1e-4)
        ctl._control()
    assert sm.effective_max_per_sec == pytest.approx(2.0)
    rep = ctl.report()
    assert rep["active"] and rep["target_ms"] == 50.0
    assert rep["adjustments"] == int(ctl.c_adjust.value) >= 2
    srv.shutdown()


def test_freshness_steers_to_tightest_class_target(tmp_path):
    srv = _fresh_srv(tmp_path, slo_ms=400.0,
                     stream_freshness_slo_class="1=200")
    ctl = srv.stream.freshness
    # per-class freshness is a write-path property: the controller
    # honestly steers to the TIGHTEST class (docs/STREAMING.md)
    assert ctl.target_s == pytest.approx(0.2)
    rep = ctl.report()
    assert rep["base_target_ms"] == 400.0
    assert rep["target_ms"] == 200.0
    assert rep["class_targets"] == {"1": 200.0}
    srv.shutdown()


# -- per-priority-class serve windows (obs/slo.py) --------------------------

def test_serve_class_windows_walk_independently():
    import time

    from adapm_tpu.serve import ServePlane
    srv = adapm_tpu.setup(NK, VLEN, opts=SystemOptions(
        sync_max_per_sec=0, prefetch=False, serve_max_wait_us=200,
        serve_slo_ms=20.0, serve_slo_class="1=5"))
    plane = ServePlane(srv)
    ctl = plane.slo
    b = plane.batcher
    assert ctl is not None and b.class_wait_us == {1: 200}
    assert b._class_samples is not None
    ctl._control_classes()              # prime the window cut
    # class-1 latencies far above its 5 ms target -> its window
    # shrinks; the base window (class-0 traffic) is untouched
    now = time.perf_counter()
    for _ in range(8):
        b._class_samples.append((now, 0.050, 1))
    ctl._control_classes()
    assert b.class_wait_us[1] < 200
    rep = ctl.report()
    assert rep["class_targets_ms"] == {"1": 5.0}
    assert rep["class_adjustments"] and \
        rep["class_adjustments"][-1]["priority"] == 1
    assert rep["class_wait_us"] == {
        str(p): int(w) for p, w in b.class_wait_us.items()}
    srv.shutdown()


def test_serve_no_class_override_path_untouched():
    from adapm_tpu.serve import ServePlane
    srv = adapm_tpu.setup(NK, VLEN, opts=SystemOptions(
        sync_max_per_sec=0, prefetch=False, serve_slo_ms=20.0))
    plane = ServePlane(srv)
    b = plane.batcher
    # no overrides: every per-class hook stays None and the report
    # carries no class keys (byte-identical to the pre-class path)
    assert b.class_wait_us is None and b._class_samples is None
    rep = plane.slo.report()
    assert "class_targets_ms" not in rep
    assert "class_wait_us" not in rep and "class_adjustments" not in rep
    srv.shutdown()


# -- replay hygiene ---------------------------------------------------------

def test_replay_zeroes_stream_knobs():
    """Replay re-drives captured pushes from the op stream — a replay
    server must never ALSO ingest (double-training) nor demand the
    flight sensor the hygiene pass already zeroed."""
    from adapm_tpu.replay.engine import _build_opts

    class _Trace:
        meta = {"knobs": {"stream_batch": 32, "stream_rate": 2000.0,
                          "stream_freshness_slo_ms": 400.0,
                          "stream_freshness_slo_class": "1=200"}}

    opts, _ = _build_opts(_Trace(), overrides=None)
    assert opts.stream_batch == 0 and opts.stream_rate == 0.0
    assert opts.stream_freshness_slo_ms == 0.0
    assert opts.stream_freshness_slo_class == ""
