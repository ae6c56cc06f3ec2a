"""Metrics-overhead guard (ISSUE 2 satellite; run by scripts/run_tests.sh).

Times the bench probe-phase shape — a pull/push loop through the full PM
dispatch path — with the hot-path instrumentation attached vs detached
and asserts the overhead stays under the budget.

Methodology: ONE server, the instrumentation toggled on its workers and
sync manager, (off, on) timings back to back, guard on the MEDIAN
pairwise ratio. Comparing two separately built servers swings >10% on
this shared 1-2-core container (different pool allocations / memory
layout), and individual pairs still swing ~0.5x-1.4x, so neither a
two-server ratio nor a min/max pair statistic can resolve the
documented <2% budget here. The median of interleaved pairs is robust
to that noise, and the failure mode this guard exists to catch — an
accidental lock, O(n) scan, or device sync on the pull/push path —
costs a MULTIPLE, not percents: it pushes every pair, hence the
median, far past the 1.15 default threshold
(ADAPM_METRICS_OVERHEAD_MAX). The 2% budget itself is established by
the micro-measurement in docs/OBSERVABILITY.md (~2 µs per op), not
re-measured per commit.

Also performs the duplicate-metric-name integrity check: constructing a
default Server registers every subsystem's metrics into one registry,
which raises on any name collision (obs/metrics.py).
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def build():
    import adapm_tpu
    from adapm_tpu.config import SystemOptions
    srv = adapm_tpu.setup(
        4096, 32, opts=SystemOptions(sync_max_per_sec=0, prefetch=False))
    w = srv.make_worker(0)
    rng = np.random.default_rng(0)
    w.set(np.arange(4096), rng.normal(
        size=(4096, 32)).astype(np.float32))
    batches = [np.unique(rng.integers(0, 4096, 128)) for _ in range(8)]
    vals = [np.ones((len(b), 32), np.float32) for b in batches]
    return srv, w, batches, vals


def probe(w, batches, vals, steps: int) -> None:
    for i in range(steps):
        j = i % len(batches)
        w.pull_sync(batches[j])
        w.wait(w.push(batches[j], vals[j]))


def set_instrumentation(srv, w, saved, on: bool) -> None:
    """Attach/detach the hot-path metrics hooks (exactly what
    --sys.metrics 0 removes from the pull/push path)."""
    from adapm_tpu.obs.metrics import _NULL
    if on:
        (w._h_pull, w._h_push, w._h_set, srv.sync._h_round) = saved
    else:
        w._h_pull = w._h_push = w._h_set = None
        srv.sync._h_round = _NULL


def main() -> int:
    budget = float(os.environ.get("ADAPM_METRICS_OVERHEAD_MAX", "1.15"))
    steps, repeats = 100, 9
    srv, w, batches, vals = build()
    names = srv.obs.names()
    print(f"[overhead-check] registry catalog: {len(names)} metrics, "
          f"duplicate-name check passed (enforced at registration)")
    # ISSUE 7: request-flight tracing is compiled in but DEFAULT OFF —
    # the probe loop below therefore times the hot path with the flight
    # branch present (one `is None` check in Worker._instrumented), and
    # the same budget guard proves its default-off cost is nil. Pin the
    # default-off state structurally too: no tracer, zero flight.*
    # metric names.
    assert srv.flight is None, \
        "flight tracing must be DEFAULT OFF (--sys.trace.flight 0)"
    flight_names = [n for n in names if n.startswith("flight.")]
    assert not flight_names, \
        f"default-off flight tracing registered metrics: {flight_names}"
    print("[overhead-check] flight tracing default-off: no tracer, "
          "zero flight.* names; probe times the hot path with the "
          "flight branch compiled in")
    # ISSUE 10: the fault-injection plane is compiled in but DEFAULT
    # OFF — no FaultPlane object, zero fault.* registry names, and the
    # instrumented sites (executor dispatch, sync tick, serve drain,
    # tier commit, checkpoint I/O) each pay one `is None` check. The
    # unchanged median-ratio guard below times the pull/push hot path
    # with those branches present.
    assert srv.fault is None, \
        "fault injection must be DEFAULT OFF (--sys.fault.spec empty)"
    fault_names = [n for n in names if n.startswith("fault.")]
    assert not fault_names, \
        f"default-off fault plane registered metrics: {fault_names}"
    print("[overhead-check] fault injection default-off: no plane, "
          "zero fault.* names; injection points are zero-cost skips")
    # ISSUE 15: workload trace capture is compiled in but DEFAULT OFF —
    # no recorder object, zero wtrace.* registry names, and every
    # capture hook (worker pull/push/set, intent, clock, serve submit,
    # sync round, relocation, promotion) pays one `is None` check. The
    # unchanged median-ratio guard below times the pull/push hot path
    # with those branches present.
    assert srv.wtrace is None, \
        "workload capture must be DEFAULT OFF (--sys.trace.workload " \
        "unset)"
    wtrace_names = [n for n in names if n.startswith("wtrace.")]
    assert not wtrace_names, \
        f"default-off workload capture registered metrics: " \
        f"{wtrace_names}"
    print("[overhead-check] workload capture default-off: no recorder, "
          "zero wtrace.* names; capture hooks are zero-cost skips")
    # ISSUE 17: decision telemetry is compiled in but DEFAULT OFF — no
    # DecisionRecorder, zero decision.* registry names, and every
    # decision site (relocate-vs-replicate classify, landed moves, tier
    # promote/demote, dirty-sync ship/hold, SLO moves, prefetch
    # stage/skip, cost overrides) pays one `is None` check. The
    # unchanged median-ratio guard below times the pull/push hot path
    # with those branches present.
    assert srv.decisions is None, \
        "decision telemetry must be DEFAULT OFF (--sys.trace.decisions " \
        "unset)"
    decision_names = [n for n in names if n.startswith("decision.")]
    assert not decision_names, \
        f"default-off decision telemetry registered metrics: " \
        f"{decision_names}"
    print("[overhead-check] decision telemetry default-off: no "
          "recorder, zero decision.* names; decision sites are "
          "zero-cost skips")
    # ISSUE 18: the learned-policy plane is compiled in but DEFAULT
    # OFF — no PolicyPlane object, zero policy.* registry names, and
    # every hook site (relocate batches, background tier promotion,
    # dirty-mask sync filtering, SLO window moves, batcher close
    # accounting) pays one `is None` check. The unchanged median-ratio
    # guard below times the pull/push hot path with those branches
    # present.
    assert srv.policy is None, \
        "learned policies must be DEFAULT OFF (--sys.policy.file unset)"
    policy_names = [n for n in names if n.startswith("policy.")]
    assert not policy_names, \
        f"default-off policy plane registered metrics: {policy_names}"
    print("[overhead-check] learned-policy plane default-off: no "
          "PolicyPlane, zero policy.* names; hook sites are zero-cost "
          "skips")
    # ISSUE 19: the NetPort transport plane is compiled in but DEFAULT
    # OFF — a single-process server attaches NO net node/membership
    # plane (srv.net is None), registers zero net.* names, and the
    # snapshot `net` section stays empty. The loopback/tcp backends
    # exist only when a NetNode is passed at construction.
    assert srv.net is None, \
        "NetPort membership plane must be DEFAULT OFF (no net_node)"
    net_names = [n for n in names if n.startswith("net.")]
    assert not net_names, \
        f"default-off net plane registered metrics: {net_names}"
    print("[overhead-check] net transport plane default-off: no "
          "membership plane, zero net.* names; the dcn/legacy path is "
          "byte-identical")
    # ISSUE 20: the streaming plane is compiled in but DEFAULT OFF —
    # with no --sys.stream.* knobs set no StreamPlane object exists,
    # zero stream.* registry names, and the snapshot `stream` section
    # stays empty. The checkpoint aux writer and Server.shutdown each
    # pay one `is None` check; the unchanged median-ratio guard below
    # times the pull/push hot path with those branches present.
    assert srv.stream is None, \
        "streaming plane must be DEFAULT OFF (--sys.stream.batch 0, " \
        "--sys.stream.freshness_slo_ms 0)"
    stream_names = [n for n in names if n.startswith("stream.")]
    assert not stream_names, \
        f"default-off streaming plane registered metrics: {stream_names}"
    print("[overhead-check] streaming plane default-off: no "
          "StreamPlane, zero stream.* names; the ingest/freshness "
          "hooks are zero-cost skips")
    saved = (w._h_pull, w._h_push, w._h_set, srv.sync._h_round)
    probe(w, batches, vals, 30)  # warm the jit caches
    # per-pair (off, on) timings back to back; the guard is the MEDIAN
    # pairwise ratio (see module docstring for why min/max/two-server
    # statistics cannot work at this box's noise level)
    pairs = []
    for _ in range(repeats):
        t = {}
        for on in (False, True):
            set_instrumentation(srv, w, saved, on)
            t0 = time.perf_counter()
            probe(w, batches, vals, steps)
            t[on] = time.perf_counter() - t0
        pairs.append(t)
    set_instrumentation(srv, w, saved, True)
    srv.shutdown()
    ratios = sorted(p[True] / p[False] for p in pairs)
    ratio = ratios[len(ratios) // 2]
    print(f"[overhead-check] probe {steps} steps x {repeats} pairs: "
          f"pairwise on/off ratios min {ratios[0]:.3f} / median "
          f"{ratio:.3f} / max {ratios[-1]:.3f} "
          f"(guard: median < {budget:.2f}, documented budget < 1.02)")
    if ratio >= budget:
        print("[overhead-check] FAILED: metrics registry overhead over "
              "budget", file=sys.stderr)
        return 1
    print("[overhead-check] OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
