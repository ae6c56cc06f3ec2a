"""Learned-policy promotion gate (ISSUE 18; run by scripts/run_tests.sh).

The replay lab is the promotion gate: a policy ships only when the
deterministic replay ranks it at least as well as the heuristic it
replaces, and NOTHING about the values a client reads may change. On a
seeded zipf storm against a deliberately starved hot pool (the
decision_quality_check contrast that makes the tier heuristic thrash —
promotions under churn evict rows before they are re-touched):

  1. **Capture -> dataset -> train.** The storm's `.dtrace`/`.wtrace`
     pair exports the labeled dataset and trains the per-plane regret
     scorers (`adapm_tpu/policy/train.py`). The tier plane must get a
     real logistic fit (enough labeled promote rows), and re-training
     from the same traces must write a BYTE-IDENTICAL artifact — the
     fit consumes no RNG and mints no timestamp.

  2. **Replay A/B promotion gate.** `rank_candidates` replays the same
     workload under {heuristic, learned-tier} with the metrics-only
     decision recorder attached (`score_decisions=True`) and ranks by
     `regret_rate_tier`. The learned policy must WIN — strictly lower
     tier regret (ties rank the heuristic first by name, so a
     do-nothing model cannot pass).

  3. **Value preservation.** Both candidates must fold the SAME
     `reads_digest`: the learned tier veto only holds background
     promotions, which never changes what a read returns — a policy
     changes *what/when*, never *values* (docs/POLICY.md).
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


def _storm(tmp):
    """The decision_quality_check tiny-pool storm: captures both trace
    planes under a starved hot pool so tier regret has signal."""
    from adapm_tpu import Server, SystemOptions, make_mesh
    from adapm_tpu.replay import per_shard_hot_rows
    dpath = os.path.join(tmp, "storm.dtrace")
    wpath = os.path.join(tmp, "storm.wtrace")
    tiny = max(8, per_shard_hot_rows(E, 0.05))
    opts = SystemOptions(sync_max_per_sec=0, prefetch=False,
                         tier=True, tier_hot_rows=tiny,
                         trace_decisions=dpath,
                         trace_workload=wpath)
    srv = Server(E, VL, opts=opts, ctx=make_mesh(8), num_workers=2)
    w0, w1 = srv.make_worker(0), srv.make_worker(1)
    w0.wait(w0.set(np.arange(E), np.ones((E, VL), np.float32)))
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
    srv.quiesce()
    srv.shutdown()
    return dpath, wpath


def main() -> int:
    from adapm_tpu.policy import train_policy
    from adapm_tpu.replay import load_wtrace, rank_candidates

    with tempfile.TemporaryDirectory(prefix="adapm-pgc-") as tmp:
        dpath, wpath = _storm(tmp)

        # 1. capture -> dataset -> train; byte-deterministic re-train
        p1, p2 = (os.path.join(tmp, n) for n in ("pol1.json",
                                                 "pol2.json"))
        bundle = train_policy(dpath, wpath, out_path=p1)
        train_policy(dpath, wpath, out_path=p2)
        with open(p1, "rb") as f1, open(p2, "rb") as f2:
            b1, b2 = f1.read(), f2.read()
        if b1 != b2:
            print("[policy-check] FAILED: re-training from the same "
                  "traces is not byte-deterministic", file=sys.stderr)
            return 1
        tm = bundle.meta["train"]
        print(f"[policy-check] trained from "
              f"{bundle.meta['dataset_rows']} dataset rows "
              f"({bundle.meta['truncated_rows']} truncated excluded); "
              f"two trainings byte-identical ({len(b1)} bytes)")
        for plane in sorted(tm):
            m = tm[plane]
            print(f"[policy-check]   {plane}: {m['fit']} fit, "
                  f"{m['used']}/{m['rows']} rows, {m['pos']} regretted")
        if tm["tier"]["fit"] != "logistic":
            print("[policy-check] FAILED: the tier plane fell back to "
                  f"the constant model ({tm['tier']}) — the storm "
                  "produced too few labeled promote rows",
                  file=sys.stderr)
            return 1

        # 2. replay A/B promotion gate on tier regret
        tr = load_wtrace(wpath)
        art = rank_candidates(
            tr,
            {"heuristic": {},
             "learned": {"policy_tier": "learned",
                         "policy_file": p1}},
            objective="regret_rate_tier", seed=7, speed=10.0,
            score_decisions=True)
        heur = art["candidates"]["heuristic"]
        lrn = art["candidates"]["learned"]
        r_h = heur["score"]["regret_rate_tier"]
        r_l = lrn["score"]["regret_rate_tier"]
        print(f"[policy-check] replay A/B regret_rate.tier: heuristic "
              f"{r_h} vs learned {r_l} -> winner {art['winner']} "
              f"(gate: learned strictly better)")
        if art["winner"] != "learned":
            print("[policy-check] FAILED: the learned tier policy did "
                  "not beat the heuristic on replay tier regret — not "
                  "promotable", file=sys.stderr)
            return 1

        # 3. value preservation: identical reads digests
        if heur["reads_digest"] != lrn["reads_digest"]:
            print(f"[policy-check] FAILED: reads digests diverge "
                  f"(heuristic {heur['reads_digest'][:16]}.. vs "
                  f"learned {lrn['reads_digest'][:16]}..) — the "
                  f"policy changed VALUES, not just what/when",
                  file=sys.stderr)
            return 1
        print(f"[policy-check] value preservation: both candidates "
              f"fold reads_digest {heur['reads_digest'][:16]}.. over "
              f"{heur['reads']} reads")

    print("[policy-check] OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
