"""Request-flight tracing + SLO autopilot (ISSUE 7 tentpole).

Tier-1 coverage for adapm_tpu/obs/flight.py + obs/slo.py and their
threading through serve/session, serve/admission, serve/batcher,
exec/executor, and core/kv:

  - THE acceptance walk: one served lookup renders as a single
    connected Perfetto flow in the exported JSON — the test loads the
    export and walks the flow-event links mint -> queue -> batch ->
    program -> reply, anchoring every step inside its phase slice;
  - the trace-propagation storm: every served lookup's chain is
    complete (no orphaned spans) under concurrent pushes, relocations,
    and sync rounds;
  - the off pin: `--sys.trace.flight 0` (default) leaves the registry
    untouched (zero flight.* names) and the hot path pays one
    `is None` check (the r7 skip-wrapper discipline);
  - SLO autopilot: control-law unit tests (shrink / grow / deadband /
    bounds) against a synthetic latency histogram, the
    static-knob-path-untouched pin for `--sys.serve.slo_ms 0`, and an
    end-to-end convergence smoke (the full guard is
    scripts/slo_convergence_check.py);
  - flight recorder: the per-stream ring + ring FILE ride
    `--sys.crash_dumps` and surface in `metrics_snapshot()["flight"]`;
  - freshness probe: push wall-time -> first servable read;
  - satellites: `hist_percentile` edge cases (empty / overflow /
    single-bucket) and the reporter's stable line format.
"""
import json
import threading
import time

import numpy as np
import pytest

from adapm_tpu import Server, SystemOptions, make_mesh
from adapm_tpu.obs.flight import (FLIGHT_PHASES, FlightRecorder,
                                  FreshnessProbe)
from adapm_tpu.serve import DeadlineExceededError, ServePlane

NK = 96
VL = 4


@pytest.fixture(scope="module")
def ctx():
    return make_mesh(8)


def make_server(ctx, num_keys=NK, vlen=VL, **kw):
    opts = kw.pop("opts", None) or SystemOptions(sync_max_per_sec=0)
    return Server(num_keys, vlen, opts=opts, ctx=ctx, **kw)


def _seed(w, num_keys=NK, vlen=VL):
    keys = np.arange(num_keys)
    vals = (np.arange(num_keys * vlen, dtype=np.float32)
            .reshape(num_keys, vlen))
    w.wait(w.set(keys, vals))
    return vals


def _load_flight(srv, tmp_path):
    path = srv.write_flight_trace()
    assert path is not None
    return json.load(open(path))


def _flow_chains(doc):
    """{trace_id: [flow events in emission order]} from the export."""
    chains = {}
    for e in doc["traceEvents"]:
        if e.get("ph") in ("s", "t", "f") and e.get("cat") == "flight":
            chains.setdefault(e["id"], []).append(e)
    return chains


def _phase_slices(doc):
    """{phase_name: [X slices]} for the five causal phases."""
    out = {n: [] for n in FLIGHT_PHASES}
    for e in doc["traceEvents"]:
        if e.get("ph") == "X" and e["name"] in out:
            out[e["name"]].append(e)
    return out


# ---------------------------------------------------------------------------
# THE acceptance walk: one lookup = one connected flow
# ---------------------------------------------------------------------------


def test_flight_flow_export_walk(ctx, tmp_path):
    """Acceptance: a served lookup's trace renders as a single
    connected flow (mint -> admission -> batch -> executor program ->
    reply). The test walks the flow-event links: 5 steps per trace id
    (one `s` start, three `t` steps, one `f` finish), each anchored
    INSIDE an `X` slice of the matching causal phase that carries the
    trace id in its membership args, with non-decreasing timestamps."""
    opts = SystemOptions(sync_max_per_sec=0, trace_flight=True,
                         stats_out=str(tmp_path))
    s = make_server(ctx, opts=opts)
    w = s.make_worker(0)
    vals = _seed(w)
    with ServePlane(s) as plane:
        sess = plane.session()
        for batch in (np.array([1, 5, 9]), np.array([7, 7, 3]),
                      np.array([42])):
            assert np.array_equal(sess.lookup(batch),
                                  w.pull_sync(batch))
    doc = _load_flight(s, tmp_path)
    s.shutdown()

    assert doc["adapm_flight"]["complete_flows"] >= 3
    chains = _flow_chains(doc)
    slices = _phase_slices(doc)
    assert len(chains) >= 3
    walked = 0
    for trace_id, evs in chains.items():
        # one start, three steps, one finish — a single connected chain
        assert [e["ph"] for e in evs] == ["s", "t", "t", "t", "f"], \
            trace_id
        assert all(e["id"] == trace_id for e in evs)
        # causal order: the flow's timestamps never regress (tolerance
        # covers the 3-decimal µs rounding of the export)
        ts = [e["ts"] for e in evs]
        assert all(a <= b + 1e-3 for a, b in zip(ts, ts[1:])), \
            (trace_id, ts)
        # each step anchors inside an X slice of its causal phase that
        # lists this trace in its batch membership
        for phase, ev in zip(FLIGHT_PHASES, evs):
            hits = [
                sl for sl in slices[phase]
                if sl["tid"] == ev["tid"]
                and sl["ts"] - 1e-3 <= ev["ts"] <= sl["ts"] + sl["dur"]
                + 1e-3 and trace_id in sl["args"]["traces"]]
            assert hits, (trace_id, phase, ev)
        walked += 1
    assert walked == len(chains)
    # batch-membership attribution: the program slice says how many
    # requests rode it and how many unique keys were gathered
    progs = slices["flight.program"]
    assert progs and all("traces" in p["args"] for p in progs)
    batches = slices["flight.batch"]
    assert batches
    for b in batches:
        assert b["args"]["requests"] >= 1
        assert b["args"]["unique_keys"] <= b["args"]["keys"]


def test_flight_storm_every_chain_complete(ctx, tmp_path):
    """Trace-propagation storm: concurrent serve clients vs a pusher, a
    relocator, and a sync driver — every SERVED lookup's chain is
    complete (mint -> queue -> batch -> program -> reply) and no trace
    id dangles with a partial chain (no orphaned spans)."""
    opts = SystemOptions(sync_max_per_sec=0, trace_flight=True,
                         stats_out=str(tmp_path))
    s = make_server(ctx, opts=opts)
    w0, w1 = s.make_worker(0), s.make_worker(1)
    _seed(w0)
    plane = ServePlane(s)
    errs: list = []
    served = [0, 0]
    stop = threading.Event()

    def client(ci):
        try:
            sess = plane.session()
            rng = np.random.default_rng(100 + ci)
            for _ in range(20):
                batch = rng.integers(0, NK, 8)
                got = sess.lookup(batch)
                assert got.shape == (8, VL)
                served[ci] += 1
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    def pusher():
        try:
            rng = np.random.default_rng(5)
            while not stop.is_set():
                ks = np.unique(rng.integers(0, NK, 6))
                w1.push(ks, rng.normal(size=(len(ks), VL))
                        .astype(np.float32))
                time.sleep(0.001)
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    def relocator():
        try:
            rng = np.random.default_rng(11)
            while not stop.is_set():
                keys = np.unique(rng.integers(0, NK, 4))
                s._relocate_to(keys, int(rng.integers(0, s.num_shards)))
                time.sleep(0.002)
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    def syncer():
        try:
            while not stop.is_set():
                with s._round_lock:
                    s.sync.run_round(all_channels=True)
                time.sleep(0.002)
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    clients = [threading.Thread(target=client, args=(ci,))
               for ci in range(2)]
    churn = [threading.Thread(target=f)
             for f in (pusher, relocator, syncer)]
    for t in clients + churn:
        t.start()
    for t in clients:
        t.join(timeout=120)
        assert not t.is_alive(), "serve client hung"
    stop.set()
    for t in churn:
        t.join(timeout=60)
        assert not t.is_alive()
    assert not errs, errs[:3]
    n_served = sum(served)
    assert n_served == 40

    doc = _load_flight(s, tmp_path)
    # every served lookup completed its chain...
    assert doc["adapm_flight"]["complete_flows"] == n_served
    chains = _flow_chains(doc)
    assert len(chains) == n_served
    # ...and no id with any causal-phase slice has a partial chain:
    # ids on phase slices either completed or were terminal-marked
    phase_ids = set()
    shed_ids = set()
    for e in doc["traceEvents"]:
        if e.get("ph") != "X" or e["name"] not in FLIGHT_PHASES:
            continue
        ids = set(e["args"]["traces"])
        phase_ids |= ids
        if e["args"].get("status") == "shed":
            shed_ids |= ids
    orphans = phase_ids - set(chains) - shed_ids
    assert not orphans, f"orphaned trace ids: {sorted(orphans)[:8]}"
    # the per-request breakdown ladder (the always-on serve.*_s phase
    # histograms, read from the same stamps the flow export draws)
    # observed every served lookup
    snap = s.metrics_snapshot()
    for h in ("admit_s", "queue_s", "batch_wait_s", "dispatch_s",
              "copy_out_s", "deliver_s", "wake_s", "lookup_s"):
        assert snap["serve"][h]["count"] == n_served, h
    assert snap["flight"]["complete"] == n_served
    plane.close()
    s.shutdown()


def test_flight_shed_records_terminal_slice(ctx, tmp_path):
    """A shed request's trace does not dangle silently: the terminal
    lookup slice carries status=shed, and no flow chain is fabricated
    for the incomplete phases."""
    opts = SystemOptions(sync_max_per_sec=0, trace_flight=True,
                         stats_out=str(tmp_path))
    s = make_server(ctx, opts=opts)
    w = s.make_worker(0)
    _seed(w)
    plane = ServePlane(s, start=False)  # paused: nothing will serve
    sess = plane.session()
    with pytest.raises(DeadlineExceededError):
        sess.lookup(np.array([1]), deadline_ms=20)
    doc = _load_flight(s, tmp_path)
    assert doc["adapm_flight"]["complete_flows"] == 0
    assert _flow_chains(doc) == {}
    sheds = [e for e in doc["traceEvents"]
             if e.get("ph") == "X" and e["name"] == "flight.lookup"
             and e["args"].get("status") == "shed"]
    assert len(sheds) == 1
    plane.close()
    s.shutdown()


def test_flight_worker_ops_single_segment(ctx, tmp_path):
    """Plain Worker.pull/push/set mint single-segment flights: one
    slice on the caller's thread, counted in flight.traces_total."""
    opts = SystemOptions(sync_max_per_sec=0, trace_flight=True,
                         stats_out=str(tmp_path))
    s = make_server(ctx, opts=opts)
    w = s.make_worker(0)
    _seed(w)
    w.pull_sync(np.array([1, 2]))
    w.push(np.array([1, 2]), np.ones((2, VL), np.float32))
    doc = _load_flight(s, tmp_path)
    names = {e["name"] for e in doc["traceEvents"]
             if e.get("ph") == "X"}
    assert "flight.kv.pull" in names
    assert "flight.kv.push" in names and "flight.kv.set" in names
    assert s.flight.stats()["traces"] >= 3  # set + pull + push
    s.shutdown()


def test_flight_off_default_untouched(ctx):
    """The off pin (`--sys.trace.flight 0`, the default): no tracer on
    the server, ZERO flight.* metric names in the registry, requests
    carry trace=None, and the worker wrapper's flight branch is the one
    `is None` check (r7 skip-wrapper discipline — the overhead guard in
    scripts/metrics_overhead_check.py runs with this default)."""
    s = make_server(ctx)
    w = s.make_worker(0)
    _seed(w)
    assert s.flight is None
    assert s.write_flight_trace() is None
    with ServePlane(s) as plane:
        sess = plane.session()
        sess.lookup(np.array([1, 2, 3]))
    assert not [n for n in s.obs.names() if n.startswith("flight.")]
    snap = s.metrics_snapshot()
    # the section stays schema-present; only the crash-ride recorder
    # summary lives there until --sys.trace.flight
    assert set(snap["flight"]) <= {"recorder"}
    s.shutdown()
    # ...and with metrics AND spans AND flight all off, the wrapper
    # degrades to a plain call (h/sp/fl all None on the server/worker)
    s2 = make_server(ctx, opts=SystemOptions(sync_max_per_sec=0,
                                             metrics=False))
    w2 = s2.make_worker(0)
    assert w2._h_pull is None and s2.spans is None and s2.flight is None
    s2.shutdown()


def test_flight_tracer_bounded_drops():
    """Slice memory is bounded: past max_slices new slices are counted
    as dropped, never stored."""
    from adapm_tpu.obs.flight import FlightTracer
    tr = FlightTracer(registry=None, max_slices=4)
    for _ in range(10):
        tr.record_op("kv.pull", time.perf_counter())
    st = tr.stats()
    assert st["slices"] == 4 and st["dropped"] == 6
    assert st["traces"] == 10


# ---------------------------------------------------------------------------
# freshness probe (ROADMAP-5 pre-work)
# ---------------------------------------------------------------------------


def test_freshness_probe_unit():
    p = FreshnessProbe(registry=None, sample_every=1, bound=4)
    tok = p.note_push(np.array([5, 6]))
    assert tok == 5
    # a gather enqueued BEFORE the push became visible read old data:
    # it must not retire the probe (even though the key matches)
    t_before = time.perf_counter()
    p.push_visible(tok)
    p.note_read(np.array([5, 9]), t_before)
    assert p.h_freshness.snap()["count"] == 0
    p.note_read(np.array([7]))          # miss: nothing resolved
    assert p.h_freshness.snap()["count"] == 0
    p.note_read(np.array([5, 9]))       # first servable read of key 5
    assert p.h_freshness.snap()["count"] == 1
    p.note_read(np.array([5]))          # measured once per probe entry
    assert p.h_freshness.snap()["count"] == 1
    # a push never marked visible (scatter not enqueued) never observes
    p.note_push(np.array([6]))
    p.note_read(np.array([6]))
    assert p.h_freshness.snap()["count"] == 1
    # the probe table is bounded, and filling it with never-served
    # keys does NOT silence the gauge: the oldest probe is evicted so
    # new pushes keep getting probed
    for k in range(100):
        assert p.note_push(np.array([100 + k])) == 100 + k
    assert len(p._pending) <= 4
    assert p.evicted > 0
    tok = p.note_push(np.array([999]))
    assert tok == 999
    p.push_visible(tok)
    p.note_read(np.array([999]))
    assert p.h_freshness.snap()["count"] == 2


def test_freshness_probe_end_to_end(ctx, tmp_path):
    """Event-to-servable staleness: the Nth push of a key is probed and
    the first serve lookup reading it lands one flight.freshness_s
    observation."""
    opts = SystemOptions(sync_max_per_sec=0, trace_flight=True,
                         stats_out=str(tmp_path))
    s = make_server(ctx, opts=opts)
    w = s.make_worker(0)
    _seed(w)
    with ServePlane(s) as plane:
        sess = plane.session()
        # sample_every pushes of the same key guarantee it is probed
        for _ in range(s.flight.freshness._sample):
            w.push(np.array([7]), np.ones((1, VL), np.float32))
        sess.lookup(np.array([7, 8]))
        snap = s.metrics_snapshot()
        assert snap["flight"]["freshness_s"]["count"] >= 1
        assert snap["flight"]["freshness_samples"] >= 1
    s.shutdown()


# ---------------------------------------------------------------------------
# flight recorder (rides --sys.crash_dumps)
# ---------------------------------------------------------------------------


def test_flight_recorder_ring_and_crash_tail(ctx, tmp_path):
    """The executor flight-recorder ring rides --sys.crash_dumps
    (default on, flight tracing NOT required): per-stream tail in
    memory, fixed-width ring FILE next to the crash dump (the
    post-mortem of what was in flight), and the recorder summary in
    metrics_snapshot()["flight"]."""
    s = make_server(ctx, opts=SystemOptions(sync_max_per_sec=0,
                                            stats_out=str(tmp_path)))
    w = s.make_worker(0)
    _seed(w)
    assert s.flight is None and s.flight_recorder is not None
    with ServePlane(s) as plane:
        sess = plane.session()
        for _ in range(4):
            sess.lookup(np.array([1, 2, 3]))
    tail = s.flight_recorder.tail()
    assert tail, "no executor programs recorded"
    assert {e["stream"] for e in tail} >= {"serve"}
    for e in tail:
        assert e["run_s"] >= 0.0 and e["wait_s"] >= 0.0
    serve_tail = s.flight_recorder.tail("serve")
    assert serve_tail and all(e["stream"] == "serve" for e in serve_tail)
    snap = s.metrics_snapshot()
    rec = snap["flight"]["recorder"]
    assert rec["programs_recorded"] >= len(serve_tail)
    assert rec["per_stream"].get("serve", 0) >= 1
    # the ring FILE sits next to the crash dump and names the programs
    rings = sorted(tmp_path.glob("adapm_flightring.*.log"))
    assert rings, "flight ring file missing"
    content = rings[-1].read_text()
    assert "stream=serve" in content and "label=serve.drain" in content
    s.shutdown()
    assert rings[-1].exists()  # the post-mortem survives shutdown


# ---------------------------------------------------------------------------
# SLO autopilot (obs/slo.py)
# ---------------------------------------------------------------------------


class _FakeBatcher:
    def __init__(self, wait_us, h):
        self.max_wait_us = wait_us
        self.h_latency = h


class _FakeServer:
    def __init__(self):
        from adapm_tpu.obs.metrics import MetricsRegistry
        self.obs = MetricsRegistry()
        self.decisions = None  # decision telemetry off (ISSUE 17)


def _mk_controller(target_ms=10.0, wait_us=20_000):
    from adapm_tpu.obs.metrics import SERVE_LATENCY_BOUNDS_S, Histogram
    from adapm_tpu.obs.slo import SLOController
    h = Histogram("serve.latency_s", bounds=SERVE_LATENCY_BOUNDS_S)
    b = _FakeBatcher(wait_us, h)
    c = SLOController(_FakeServer(), b, target_ms=target_ms)
    c._control()  # first tick: baseline snapshot only, never adjusts
    return c, b, h


def test_slo_control_law_shrink_grow_deadband():
    c, b, h = _mk_controller(target_ms=10.0, wait_us=20_000)
    # P99 far above target -> the window SHRINKS (multiplicative)
    for _ in range(10):
        h.observe(0.050)
    c._control()
    assert b.max_wait_us < 20_000
    assert int(c.c_adjust.value) == 1
    first = b.max_wait_us
    # P99 far below target -> the window GROWS back toward the cap
    for _ in range(10):
        h.observe(0.001)
    c._control()
    assert b.max_wait_us > first
    # P99 inside the deadband -> hysteresis: no change
    cur = b.max_wait_us
    for _ in range(10):
        h.observe(0.010)
    adjusts = int(c.c_adjust.value)
    c._control()
    assert b.max_wait_us == cur and int(c.c_adjust.value) == adjusts
    # every adjustment landed in the bounded log with old/new/p99
    rep = c.report()
    assert rep["adjustments"] == adjusts == 2
    assert len(rep["recent_adjustments"]) == 2
    a0 = rep["recent_adjustments"][0]
    assert a0["old_us"] == 20_000 and a0["new_us"] == first
    assert rep["target_ms"] == 10.0


def test_slo_control_law_bounded():
    c, b, h = _mk_controller(target_ms=10.0, wait_us=20_000)
    # sustained overshoot walks the window to the floor... and stops
    for _ in range(60):
        for _ in range(10):
            h.observe(0.050)
        c._control()
    assert b.max_wait_us == 0
    ticks_at_floor = int(c.c_adjust.value)
    for _ in range(10):
        h.observe(0.050)
    c._control()
    assert b.max_wait_us == 0 and int(c.c_adjust.value) == ticks_at_floor
    # sustained undershoot grows back (escaping 0 via the minimum step)
    # and caps at hi_us = max(static knob, 75% of the SLO)
    for _ in range(60):
        for _ in range(10):
            h.observe(0.001)
        c._control()
    assert b.max_wait_us == c.hi_us == 20_000


def test_slo_too_few_samples_no_adjustment():
    """A control window with fewer than min_samples observations never
    adjusts — one straggler must not yank the knob."""
    c, b, h = _mk_controller(target_ms=10.0, wait_us=20_000)
    for _ in range(c.min_samples - 1):
        h.observe(0.050)
    c._control()
    assert b.max_wait_us == 20_000 and int(c.c_adjust.value) == 0


def test_slo_static_path_untouched(ctx):
    """--sys.serve.slo_ms unset (default): no controller exists, no
    slo.* metric names, no `slo` executor stream, and the effective
    window IS the static knob before and after load — the pre-PR
    behavior bit-identically."""
    s = make_server(ctx)
    w = s.make_worker(0)
    _seed(w)
    with ServePlane(s) as plane:
        assert plane.slo is None
        assert plane.batcher.max_wait_us == s.opts.serve_max_wait_us
        sess = plane.session()
        for _ in range(5):
            sess.lookup(np.array([1, 2, 3]))
        assert plane.batcher.max_wait_us == s.opts.serve_max_wait_us
    assert not [n for n in s.obs.names() if n.startswith("slo.")]
    assert "slo" not in s.exec._streams
    assert s.metrics_snapshot()["slo"] == {}
    s.shutdown()


def test_slo_autopilot_end_to_end_shrinks(ctx):
    """Convergence smoke (the sized guard is
    scripts/slo_convergence_check.py): with a coalescing window 25x the
    SLO target, the controller must walk the window DOWN under load and
    the slo section must carry the adjustments."""
    opts = SystemOptions(sync_max_per_sec=0, serve_max_wait_us=50_000,
                         serve_slo_ms=2.0)
    s = make_server(ctx, opts=opts)
    w = s.make_worker(0)
    _seed(w)
    plane = ServePlane(s)
    assert plane.slo is not None
    # concurrent clients: each 50 ms micro-batch then carries several
    # requests, so a 100 ms control tick sees >= min_samples and the
    # law can act (a single serial client would starve the window)
    stop = threading.Event()
    errs: list = []

    def client():
        try:
            sess = plane.session()
            while not stop.is_set():
                sess.lookup(np.arange(8))
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=client) for _ in range(4)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline \
            and int(plane.slo.c_adjust.value) < 1:
        time.sleep(0.05)
    stop.set()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "serve client hung"
    assert not errs, errs[:3]
    assert int(plane.slo.c_adjust.value) >= 1, \
        "controller never adjusted the window"
    assert plane.batcher.max_wait_us < 50_000
    snap = s.metrics_snapshot()
    assert snap["slo"]["active"] is True
    assert snap["slo"]["target_ms"] == 2.0
    assert snap["slo"]["adjustments"] >= 1
    assert snap["slo"]["recent_adjustments"]
    assert snap["slo"]["wait_us"] == plane.batcher.max_wait_us
    assert snap["slo"]["ticks_total"] >= 1
    plane.close()
    # close() stops the reschedule: the tick counter settles
    s.exec.drain("slo", timeout=10)
    s.shutdown()


def test_slo_controller_survives_plane_rebuild(ctx):
    """A ServePlane closed and rebuilt within one tick interval gets a
    LIVE controller: the new instance's first tick must not coalesce
    into the predecessor's still-queued tick (which sees its own
    _closed flag and exits without rescheduling — the rebuilt
    autopilot would silently never run)."""
    opts = SystemOptions(sync_max_per_sec=0, serve_slo_ms=2.0)
    s = make_server(ctx, opts=opts)
    w = s.make_worker(0)
    _seed(w)
    p1 = ServePlane(s)
    assert p1.slo is not None
    p1.close()          # a queued delayed tick exists at close time
    p2 = ServePlane(s)  # rebuilt immediately, well inside 100 ms
    assert p2.slo is not None and p2.slo is not p1.slo
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and p2.slo._prev_snap is None:
        time.sleep(0.05)
    assert p2.slo._prev_snap is not None, \
        "rebuilt controller never ticked (coalesced into stale tick?)"
    p2.close()
    s.exec.drain("slo", timeout=10)
    s.shutdown()


def test_slo_requires_metrics():
    with pytest.raises(ValueError, match="requires --sys.metrics"):
        SystemOptions(serve_slo_ms=5.0, metrics=False).validate_serve()
    with pytest.raises(ValueError, match="slo_ms"):
        SystemOptions(serve_slo_ms=-1.0).validate_serve()


# ---------------------------------------------------------------------------
# satellites: hist_percentile edges + reporter line format
# ---------------------------------------------------------------------------


def test_hist_percentile_edges():
    from adapm_tpu.obs.metrics import Histogram, hist_percentile
    # empty histogram -> 0
    h = Histogram("t.h", bounds=(1.0, 10.0))
    assert hist_percentile(h.snap(), 0.99) == 0.0
    # overflow bucket: clamp to the last finite bound, never
    # interpolate past the ladder
    for v in (0.5, 5.0, 100.0, 200.0):
        h.observe(v)
    assert hist_percentile(h.snap(), 0.99) == 10.0
    assert hist_percentile(h.snap(), 0.75) == 10.0  # lands in overflow
    # in-bucket interpolation stays inside the containing bucket
    p50 = hist_percentile(h.snap(), 0.50)
    assert 1.0 <= p50 <= 10.0
    # every observation in the overflow bucket -> still the last bound
    h2 = Histogram("t.h2", bounds=(1.0, 10.0))
    for _ in range(5):
        h2.observe(50.0)
    assert hist_percentile(h2.snap(), 0.50) == 10.0
    # single-bucket ladder: interpolation within, clamp above
    h3 = Histogram("t.h3", bounds=(8.0,))
    for v in (2.0, 4.0, 6.0, 8.0):
        h3.observe(v)
    assert 0.0 < hist_percentile(h3.snap(), 0.50) <= 8.0
    h3.observe(100.0)
    assert hist_percentile(h3.snap(), 0.99) == 8.0


def test_reporter_line_format():
    """The one-line summary's format is STABLE (reporter module
    docstring): field order and formatting are pinned here so
    log-scraping tooling can rely on them."""
    from adapm_tpu.obs.reporter import _fmt
    assert _fmt({}) == "no activity yet"
    snap = {
        "kv": {"pull_s": {"count": 2, "avg": 1.05e-3}},
        "serve": {"lookups_total": 4,
                  "latency_s": {"count": 4, "bounds": [0.001],
                                "buckets": [4, 0]}},
        "exec": {"programs_total": 3, "overlap_fraction": 0.25},
        "tier": {"hot_hits": 9, "cold_hits": 1, "hot_hit_rate": 0.9},
        "flight": {"freshness_s": {"count": 2, "bounds": [0.002],
                                   "buckets": [2, 0]}},
        "decision": {"events_total": 10, "regret_rate.tier": 0.25,
                     "regret_rate.sync": 0.10},
    }
    assert _fmt(snap) == ("pull=2 avg=1.05ms "
                          "serve=4 p50=0.50ms p99=0.99ms "
                          "overlap=0.25 hot_hit=0.90 "
                          "fresh=1.98ms regret=0.25")
    # net part (ISSUE 19): msgs/bytes + live/total peers, appended last
    snap["net"] = {"msgs_out": 12, "bytes_out": 3456,
                   "peers_live": 2, "peers_total": 3}
    assert _fmt(snap).endswith(" net=12/3456 peers=2/3")
    # a subsystem with no activity contributes nothing (no empty fields)
    assert _fmt({"serve": {"latency_s": {"count": 0}},
                 "exec": {"programs_total": 0},
                 "tier": {"hot_hits": 0, "cold_hits": 0},
                 "flight": {"freshness_s": {"count": 0}},
                 "decision": {"events_total": 0,
                              "regret_rate.tier": 0.0},
                 "net": {"msgs_out": 0, "msgs_in": 0,
                         "peers_live": 1, "peers_total": 1}}) \
        == "no activity yet"


def test_clock_domains_recorded_everywhere(tmp_path):
    """ISSUE 15 clock-domain satellite: the flight-recorder ring and
    the SLO move log each stamp BOTH wall time and a monotonic clock —
    merged timelines (and replay alignment) must not skew when NTP
    steps the wall clock. The tail merge orders by the MONOTONIC
    stamp, which cannot step backwards."""
    rec = FlightRecorder(path=str(tmp_path / "r.log"))
    m0, w0 = time.monotonic(), time.time()
    rec.record("sync", "a", None, 0.0, 0.001)
    rec.record("serve", "b", None, 0.0, 0.001)
    m1, w1 = time.monotonic(), time.time()
    tail = rec.tail()
    assert len(tail) == 2
    for e in tail:
        # both domains present, each bracketed by its own clock
        assert m0 <= e["t_mono"] <= m1
        assert w0 <= e["t"] <= w1 + 1.0
    # merged tail is mono-ordered (wall could lie under an NTP step)
    assert tail[0]["t_mono"] <= tail[1]["t_mono"]
    rec.close()
    # SLO move log: drive one adjustment and check the report entries
    c, b, h = _mk_controller(target_ms=10.0, wait_us=20_000)
    m0 = time.monotonic()
    for _ in range(10):
        h.observe(0.050)    # far over target -> shrink
    c._control()
    m1 = time.monotonic()
    rep = c.report()
    assert rep["adjustments"] == 1
    first = rep["first_adjustment"]
    last = rep["recent_adjustments"][-1]
    for entry in (first, last):
        assert m0 <= entry["t_mono"] <= m1
        assert entry["t"] > 1e9  # epoch wall seconds, not monotonic
    assert first == last


def test_flight_recorder_unit(tmp_path):
    """FlightRecorder mechanics: bounded per-stream rings, mono-merged
    tail, fixed-slot ring file overwrites (no unbounded growth)."""
    path = str(tmp_path / "ring.log")
    rec = FlightRecorder(path=path, per_stream=2, file_slots=4)
    for i in range(6):
        rec.record("sync", f"prog{i}", None, 0.001, 0.002)
    rec.record("serve", "drain", "serve.drain", 0.0, 0.001, failed=True)
    tail = rec.tail()
    # per-stream bound: only the last 2 sync programs survive
    assert [e["label"] for e in tail if e["stream"] == "sync"] \
        == ["prog4", "prog5"]
    assert tail[-1]["stream"] == "serve" and tail[-1]["failed"]
    assert rec.summary()["programs_recorded"] == 7
    assert rec.summary()["per_stream"] == {"serve": 1, "sync": 6}
    rec.close()
    data = open(path, "rb").read()
    # fixed-size ring: file_slots fixed-width slots, never more
    assert len(data) <= 4 * 192
    assert b"FAILED" in data
