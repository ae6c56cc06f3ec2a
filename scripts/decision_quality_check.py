"""Decision-telemetry quality gate (ISSUE 17; run by scripts/run_tests.sh).

Four acceptance properties of the decision plane, end to end, on a
seeded zipf storm (the DLRM embedding-bag shape) captured with BOTH
`--sys.trace.decisions` and `--sys.trace.workload`:

  1. **Complete feature vectors.** Every decision event in the
     `.dtrace` carries every CORE_FEATURES key (logical clock, live
     replicas, dirty fraction, hot free/total rows, batch size) — a
     policy cannot train on rows with holes.

  2. **Attribution closure.** >= 90% of decisions have a resolved
     outcome event (immediate or window; `close()` force-resolves
     stragglers with `truncated: true`, which counts — a truncated
     label is a label).

  3. **Deterministic export.** `replay/dataset.py` run twice over the
     same (.dtrace, .wtrace) pair writes byte-identical artifacts.

  4. **Regret discriminates policies.** The same storm against a tiny
     hot pool must fold a strictly higher `decision.regret_rate.tier`
     than an amply-sized pool: promotion under churn evicts rows
     before they are re-touched (promoted_never_hit), which is
     exactly the signal the regret counters exist to surface. A
     telemetry plane whose regret metric cannot tell a thrashing
     policy from a healthy one is decoration.
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    from xla_compat import mesh_flags
    os.environ["XLA_FLAGS"] = " ".join([_flags, mesh_flags(8)]).strip()

import numpy as np  # noqa: E402

E = 1024          # keys
VL = 8            # value length
STEPS = 80        # storm steps
SKEW = 6.0        # zipf-ish skew (key = E * u^SKEW)
SEED = 29


def _storm(tmp, tag: str, hot_rows: int):
    """One seeded capture storm at the given per-shard hot-pool size;
    returns (dtrace_path, wtrace_path, decision_snapshot_section)."""
    from adapm_tpu import Server, SystemOptions, make_mesh
    dpath = os.path.join(tmp, f"{tag}.dtrace")
    wpath = os.path.join(tmp, f"{tag}.wtrace")
    opts = SystemOptions(sync_max_per_sec=0, prefetch=False,
                         tier=True, tier_hot_rows=hot_rows,
                         trace_decisions=dpath,
                         trace_workload=wpath)
    srv = Server(E, VL, opts=opts, ctx=make_mesh(8), num_workers=2)
    w0, w1 = srv.make_worker(0), srv.make_worker(1)
    w0.wait(w0.set(np.arange(E),
                   np.ones((E, VL), np.float32)))
    rng = np.random.default_rng(SEED)
    for i in range(STEPS):
        w = w0 if i % 2 == 0 else w1
        ks = np.unique((E * rng.random(24) ** SKEW)
                       .astype(np.int64).clip(0, E - 1))
        w.pull_sync(ks)
        w.wait(w.push(ks, np.ones((len(ks), VL), np.float32)))
        if i % 4 == 0:
            w.intent(ks, w.current_clock, w.current_clock + 4)
            w.advance_clock()
        srv.wait_sync()
    snap = srv.metrics_snapshot()["decision"]
    srv.shutdown()
    return dpath, wpath, snap


def main() -> int:
    from adapm_tpu.obs.decisions import CORE_FEATURES, load_dtrace
    from adapm_tpu.replay import export_dataset, per_shard_hot_rows

    with tempfile.TemporaryDirectory(prefix="adapm-dqc-") as tmp:
        ample = per_shard_hot_rows(E, 1.0)
        dpath, wpath, snap_ok = _storm(tmp, "ample", ample)
        tiny_rows = max(8, per_shard_hot_rows(E, 0.05))
        _, _, snap_tiny = _storm(tmp, "tiny", tiny_rows)

        tr = load_dtrace(dpath)
        decisions = tr.decisions()
        outcomes = tr.outcomes()
        if not decisions:
            print("[decision-check] FAILED: storm produced zero "
                  "decision events", file=sys.stderr)
            return 1
        planes = tr.planes()
        for must in ("tier", "sync"):
            if not planes.get(must):
                print(f"[decision-check] FAILED: no {must!r}-plane "
                      f"decisions captured (got {planes})",
                      file=sys.stderr)
                return 1

        # 1. complete feature vectors
        holes = [(d["seq"], k) for d in decisions
                 for k in CORE_FEATURES
                 if k not in d.get("features", {})]
        if holes:
            print(f"[decision-check] FAILED: {len(holes)} feature "
                  f"holes, first {holes[:5]}", file=sys.stderr)
            return 1
        print(f"[decision-check] {len(decisions)} decisions across "
              f"planes {planes}: every event carries all "
              f"{len(CORE_FEATURES)} core features")

        # 2. attribution closure
        closed = sum(1 for d in decisions if d["seq"] in outcomes)
        closure = closed / len(decisions)
        print(f"[decision-check] attribution closure "
              f"{closed}/{len(decisions)} = {closure:.3f} "
              f"(gate: >= 0.90)")
        if closure < 0.90:
            print("[decision-check] FAILED: attribution closure under "
                  "0.90", file=sys.stderr)
            return 1

        # 3. deterministic dataset export
        p1, p2 = (os.path.join(tmp, n) for n in ("ds1.json",
                                                 "ds2.json"))
        art = export_dataset(dpath, wpath, out_path=p1)
        export_dataset(dpath, wpath, out_path=p2)
        with open(p1, "rb") as f1, open(p2, "rb") as f2:
            b1, b2 = f1.read(), f2.read()
        if b1 != b2:
            print("[decision-check] FAILED: dataset export is not "
                  "byte-deterministic", file=sys.stderr)
            return 1
        print(f"[decision-check] dataset export: {art['n_rows']} rows "
              f"x {len(art['columns'])} columns, two exports "
              f"byte-identical ({len(b1)} bytes)")

        # 4. regret discriminates a thrashing tier policy
        r_ok = snap_ok.get("regret_rate.tier", 0.0)
        r_tiny = snap_tiny.get("regret_rate.tier", 0.0)
        print(f"[decision-check] regret_rate.tier: ample "
              f"({ample} rows/shard) {r_ok:.3f} vs tiny "
              f"({tiny_rows} rows/shard) {r_tiny:.3f} "
              f"(gate: tiny > ample)")
        if not r_tiny > r_ok:
            print("[decision-check] FAILED: tiny hot pool did not "
                  "raise tier regret over the ample pool",
                  file=sys.stderr)
            return 1

    print("[decision-check] OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
