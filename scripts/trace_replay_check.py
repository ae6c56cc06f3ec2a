"""Trace-replay guard (ISSUE 15; run by scripts/run_tests.sh).

Three acceptance properties of the workload-trace plane, end to end:

  1. **Determinism.** A seeded multi-plane storm (pull/push/set,
     intents, clocks, serve lookups, sync rounds, quiesce) is captured
     once; replaying the `.wtrace` twice with the same seed + knobs
     produces bit-identical reads (the sha256 digest over every
     pull/serve result), and replaying at 1x vs 10x logical speed
     produces the SAME digest — pacing is presentation, never data.

  2. **Ranked-artifact sanity.** A two-candidate knob sweep
     (`tier_hot_rows` at 25% vs 100% of the table) emits an artifact
     whose candidates both scored the objective and whose winner is
     ranked first.

  3. **Replay predicts live.** The same workload generator is run LIVE
     (no replay) under both candidates and the hot-hit-rate ordering
     is measured directly; the replay artifact's winner must match the
     live winner — the whole point of the offline policy lab is that
     its rankings transfer.

The storm is zipf-skewed (the DLRM embedding-bag shape the recorder
exists to capture faithfully) so the 25%-capacity candidate lands a
high-but-sub-1.0 hit rate and the orderings are non-degenerate.
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

E = 2048          # keys
VL = 8            # value length
STEPS = 80        # storm steps
SKEW = 8.0        # zipf-ish skew (key = E * u^SKEW)
SEED = 123

def candidates():
    """Whole-table hot fractions via the shared per_shard_hot_rows
    helper (--sys.tier.hot_rows is PER SHARD per length class; an
    undivided fraction on a multi-shard mesh would make both
    candidates effectively all-hot — a near-tie proving nothing)."""
    from adapm_tpu.replay import per_shard_hot_rows
    return {
        "hot_25pct": {"tier": True,
                      "tier_hot_rows": per_shard_hot_rows(E, 0.25)},
        "hot_100pct": {"tier": True,
                       "tier_hot_rows": per_shard_hot_rows(E, 1.0)},
    }


def _sched(rng, n):
    return (E * rng.random(n) ** SKEW).astype(np.int64).clip(0, E - 1)


def drive_storm(srv, with_serve=True):
    """The seeded workload, shared verbatim between the capture run and
    the live-measurement runs (one generator, three uses)."""
    from adapm_tpu.serve import ServePlane
    w = srv.make_worker(0)
    rng = np.random.default_rng(SEED)
    slab = np.ones((E, VL), np.float32)
    w.wait(w.set(np.arange(E), slab))
    plane = ServePlane(srv) if with_serve else None
    sess = plane.session() if plane is not None else None
    for i in range(STEPS):
        ks = np.unique(_sched(rng, 64))
        w.pull_sync(ks)
        w.wait(w.push(ks, np.ones((len(ks), VL), np.float32)))
        if sess is not None and i % 4 == 0:
            sess.lookup(_sched(rng, 32))
        if i % 10 == 9:
            w.advance_clock()
            srv.wait_sync()
    srv.quiesce()
    if plane is not None:
        plane.close()
    return w


def capture(tmp) -> str:
    import adapm_tpu
    from adapm_tpu.config import SystemOptions
    path = os.path.join(tmp, "storm.wtrace")
    opts = SystemOptions(sync_max_per_sec=0, prefetch=False,
                         trace_workload=path,
                         trace_workload_keys=256)
    srv = adapm_tpu.setup(E, VL, opts=opts, num_workers=1)
    drive_storm(srv)
    srv.shutdown()
    return path


def live_hit_rate(overrides) -> float:
    """The live (no-replay) measurement of one candidate: same
    generator, same knobs, hot-hit rate from the same gauge."""
    import adapm_tpu
    from adapm_tpu.config import SystemOptions
    opts = SystemOptions(sync_max_per_sec=0, prefetch=False)
    for k, v in overrides.items():
        setattr(opts, k, v)
    srv = adapm_tpu.setup(E, VL, opts=opts, num_workers=1)
    drive_storm(srv)
    rate = float(srv.obs.find("tier.hot_hit_rate").value)
    srv.shutdown()
    return rate


def main() -> int:
    from adapm_tpu.replay import ReplayEngine, load_wtrace, \
        rank_candidates

    with tempfile.TemporaryDirectory() as tmp:
        print(f"[replay-check] capturing storm ({E} keys x {VL}, "
              f"{STEPS} steps, zipf skew {SKEW})")
        path = capture(tmp)
        tr = load_wtrace(path)
        kinds = tr.kinds()
        print(f"[replay-check] trace: {len(tr.events)} events {kinds}")
        for k in ("pull", "push", "serve", "sync", "quiesce"):
            assert kinds.get(k, 0) >= 1, f"storm recorded no {k} events"

        # 1) determinism: same seed+knobs twice, and across speeds
        r_a = ReplayEngine(tr, seed=5, speed=10.0).run()
        r_b = ReplayEngine(tr, seed=5, speed=10.0).run()
        if r_a["reads_digest"] != r_b["reads_digest"]:
            print("[replay-check] FAILED: same-speed replays disagree "
                  f"({r_a['reads_digest'][:12]} vs "
                  f"{r_b['reads_digest'][:12]})", file=sys.stderr)
            return 1
        r_1x = ReplayEngine(tr, seed=5, speed=1.0).run()
        if r_1x["reads_digest"] != r_a["reads_digest"]:
            print("[replay-check] FAILED: 1x vs 10x logical speed "
                  "changed the replayed reads — pacing leaked into "
                  "data", file=sys.stderr)
            return 1
        print(f"[replay-check] determinism OK: digest "
              f"{r_a['reads_digest'][:16]} stable across runs and "
              f"1x/10x speeds ({r_a['reads']} reads, "
              f"{r_a['events_replayed']} events)")

        # 2) ranked two-candidate sweep on the replay engine
        cands = candidates()
        art = rank_candidates(tr, cands,
                              objective="hot_hit_rate", seed=5,
                              speed=10.0,
                              out_path=os.path.join(tmp, "cmp.json"))
        scores = {n: art["candidates"][n]["score"]["hot_hit_rate"]
                  for n in cands}
        print(f"[replay-check] replay hot_hit_rate: {scores}, "
              f"winner {art['winner']}")
        for n, s in scores.items():
            if s is None:
                print(f"[replay-check] FAILED: candidate {n} scored "
                      f"no hot_hit_rate", file=sys.stderr)
                return 1
        if art["ranking"][0] != art["winner"]:
            print("[replay-check] FAILED: artifact winner is not "
                  "ranked first", file=sys.stderr)
            return 1

        # 3) the replay ordering must match the LIVE-measured ordering
        live = {n: live_hit_rate(o) for n, o in cands.items()}
        live_winner = max(sorted(live), key=lambda n: live[n])
        print(f"[replay-check] live hot_hit_rate: "
              f"{ {n: round(v, 4) for n, v in live.items()} }, "
              f"winner {live_winner}")
        if art["winner"] != live_winner:
            print(f"[replay-check] FAILED: replay winner "
                  f"{art['winner']} != live winner {live_winner} — "
                  f"the offline ranking does not transfer",
                  file=sys.stderr)
            return 1
        print("[replay-check] OK: replay ranking matches the "
              "live-measured ordering")
    return 0


if __name__ == "__main__":
    sys.exit(main())
