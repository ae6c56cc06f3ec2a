"""SLO-autopilot convergence guard (ISSUE 7 satellite; run by
scripts/run_tests.sh).

Drives an open-loop serve load with `--sys.serve.slo_ms` set and an
ABSURDLY oversized static micro-batch window (the window itself is 4x
the SLO target, so the uncontrolled P99 sits far above target by
construction) and asserts the closed-loop controller (obs/slo.py):

1. **moves the knob in the correct direction** — at least one recorded
   `max_wait_us` adjustment, the FIRST adjustment is downward, and the
   effective window ends below the static knob it started from;
2. **lands the tail inside the tolerance band** — the observed serve
   P99, measured over trailing windows AFTER the controller has had
   time to act (cumulative `serve.latency_s` snapshots diffed per
   window, quantile via `hist_percentile` — the controller's own
   method), must come within `ADAPM_SLO_BAND` (default 3x) of the
   target. Guard on the MEDIAN of the trailing windows (the
   mgmt_plane_check.py / metrics_overhead_check.py pattern, sized for
   this shared 2-core box: single windows spike on scheduler noise,
   but the failure mode — a controller that never shrinks the window —
   leaves EVERY window's P99 pinned at the full static window, 4x
   target, well past any band).

The static-knob path needs no guard here: with `--sys.serve.slo_ms`
unset no controller object exists at all (tests/test_flight.py pins
that the registry, the executor streams, and the effective window are
untouched).
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

NK = 4096
VLEN = 8
B = 64               # keys per lookup
CLIENTS = 8
TARGET_MS = 25.0
WAIT_US = 100_000    # static window = 4x the SLO target
SETTLE_S = 2.0       # controller reaction time before measuring
WINDOW_S = 0.75      # one P99 measurement window
WINDOWS = 4          # trailing windows; guard on their median


def main() -> int:
    band = float(os.environ.get("ADAPM_SLO_BAND", "3.0"))
    import jax

    import adapm_tpu
    from adapm_tpu.config import SystemOptions
    from adapm_tpu.obs.metrics import hist_percentile
    from adapm_tpu.serve import ServePlane

    jax.config.update("jax_platforms", "cpu")
    srv = adapm_tpu.setup(NK, VLEN, opts=SystemOptions(
        sync_max_per_sec=0, prefetch=False,
        serve_max_wait_us=WAIT_US, serve_slo_ms=TARGET_MS))
    w = srv.make_worker(0)
    rng = np.random.default_rng(0)
    w.wait(w.set(np.arange(NK),
                 rng.normal(size=(NK, VLEN)).astype(np.float32)))
    # pre-compile the gather bucket shapes the unions can hit (a
    # mid-run XLA compile would pollute a measurement window)
    n = B
    while True:
        w.pull_sync(np.arange(min(n, NK), dtype=np.int64))
        if n >= min(CLIENTS * B, NK):
            break
        n *= 2

    plane = ServePlane(srv)
    assert plane.slo is not None, "no controller with slo_ms set"
    h_lat = srv.obs.find("serve.latency_s")
    stop = threading.Event()
    errs: list = []

    def client(ci):
        try:
            sess = plane.session()
            crng = np.random.default_rng(ci)
            while not stop.is_set():
                batch = (NK * crng.random(B) ** 3).astype(np.int64) \
                    .clip(0, NK - 1)
                sess.lookup(batch)
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(CLIENTS)]
    for t in threads:
        t.start()
    time.sleep(SETTLE_S)        # the controller walks the window down
    p99s = []
    for _ in range(WINDOWS):    # trailing measurement windows
        snap0 = h_lat.snap()
        time.sleep(WINDOW_S)
        snap1 = h_lat.snap()
        count = snap1["count"] - snap0["count"]
        buckets = [a - b for a, b in zip(snap1["buckets"],
                                         snap0["buckets"])]
        if count:
            p99s.append(hist_percentile(
                {"count": count, "bounds": snap1["bounds"],
                 "buckets": buckets}, 0.99) * 1e3)
    stop.set()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "serve client hung"
    assert not errs, errs[:3]

    rep = plane.slo.report()
    adjustments = rep["adjustments"]
    first = rep["first_adjustment"]
    final_us = rep["wait_us"]
    srv.shutdown()

    p99s.sort()
    median_p99 = p99s[len(p99s) // 2] if p99s else float("inf")
    print(f"[slo-check] target {TARGET_MS:.0f} ms, window "
          f"{WAIT_US} us -> {final_us} us in {adjustments} "
          f"adjustments; trailing-window P99s "
          f"{[round(p, 1) for p in p99s]} ms, median "
          f"{median_p99:.1f} (guard: median < {TARGET_MS * band:.0f} "
          f"= {band:.1f}x target)")
    rc = 0
    if adjustments < 1 or final_us >= WAIT_US:
        print("[slo-check] FAILED: the controller never moved "
              "max_wait_us below the oversized static knob — check "
              "obs/slo.py tick scheduling and the shrink branch",
              file=sys.stderr)
        rc = 1
    if first is not None and first["new_us"] >= first["old_us"]:
        print("[slo-check] FAILED: first adjustment moved the window "
              "UP with P99 far above target — control law direction "
              "inverted", file=sys.stderr)
        rc = 1
    if median_p99 >= TARGET_MS * band:
        print(f"[slo-check] FAILED: median trailing-window P99 "
              f"{median_p99:.1f} ms not within {band:.1f}x of the "
              f"{TARGET_MS:.0f} ms target — the tail is not tracking "
              f"the SLO (ADAPM_SLO_BAND to override on a saturated "
              f"box)", file=sys.stderr)
        rc = 1
    if rc == 0:
        print("[slo-check] OK")
    return rc


if __name__ == "__main__":
    sys.exit(main())
