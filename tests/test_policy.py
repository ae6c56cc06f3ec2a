"""Learned adaptive-policy plane (ISSUE 18 tentpole).

Tier-1 coverage for adapm_tpu/policy/ + the replay promotion gate:

  - the off pin: no --sys.policy.file (default) => no PolicyPlane
    object, zero policy.* registry names, empty policy snapshot
    section (schema v14) — the r7 skip-wrapper shape
    (scripts/metrics_overhead_check.py pins the same thing in CI);
  - training: byte-deterministic re-train from the same traces, a
    real logistic fit on the tier plane, truncated rows excluded and
    counted loudly;
  - artifact hygiene: missing file, flipped byte, and wrong-format
    input each raise the NAMED PolicyError during verification; a
    feature-spec mismatch (stale artifact vs this build's
    PLANE_FEATURES contract) is rejected at load;
  - the OBSERVER-EFFECT pin: a shadow-mode replay folds
    agree/disagree verdicts yet reads bit-identically to the plain
    heuristic replay — shadow scores, never steers;
  - the VALUE-PRESERVATION pin: the learned tier policy applies real
    vetoes during replay and STILL reproduces the heuristic
    `reads_digest` bitwise, ranking no worse on tier regret — a
    policy changes what/when, never values (the full strict-win gate
    runs in scripts/policy_gate_check.py on a bigger storm);
  - live mechanics: a server built with --sys.policy.* consults the
    models on the real decision sites and carries the policy section
    in its snapshot.
"""
import numpy as np
import pytest

from adapm_tpu import Server, SystemOptions, make_mesh
from adapm_tpu.policy import (PLANE_FEATURES, PlaneModel, PolicyError,
                              load_policy, train_policy)
from adapm_tpu.replay import ReplayEngine, load_wtrace, rank_candidates

NK = 256
VL = 4


@pytest.fixture(scope="module")
def ctx():
    return make_mesh(8)


def _storm(ctx, out_dir, tag, steps=40, tier_rows=8):
    """Seeded zipf pull/push/intent storm against a starved hot pool
    (tier regret has signal); returns (dtrace, wtrace) paths after
    shutdown."""
    dpath = str(out_dir / f"{tag}.dtrace")
    wpath = str(out_dir / f"{tag}.wtrace")
    opts = SystemOptions(sync_max_per_sec=0, prefetch=False,
                         tier=True, tier_hot_rows=tier_rows,
                         trace_decisions=dpath, trace_workload=wpath)
    srv = Server(NK, VL, opts=opts, ctx=ctx, num_workers=2)
    w0, w1 = srv.make_worker(0), srv.make_worker(1)
    w0.wait(w0.set(np.arange(NK), np.ones((NK, VL), np.float32)))
    rng = np.random.default_rng(17)
    for i in range(steps):
        w = w0 if i % 2 == 0 else w1
        ks = np.unique((NK * rng.random(16) ** 6.0)
                       .astype(np.int64).clip(0, NK - 1))
        w.pull_sync(ks)
        w.wait(w.push(ks, np.ones((len(ks), VL), np.float32)))
        if i % 4 == 0:
            w.intent(ks, w.current_clock, w.current_clock + 4)
            w.advance_clock()
        srv.wait_sync()
    srv.shutdown()
    return dpath, wpath


@pytest.fixture(scope="module")
def trained(ctx, tmp_path_factory):
    """One storm + one training, shared by the replay/load tests:
    (dtrace, wtrace, policy_path, bundle)."""
    out = tmp_path_factory.mktemp("policy")
    dpath, wpath = _storm(ctx, out, "cap")
    ppath = str(out / "policy.json")
    bundle = train_policy(dpath, wpath, out_path=ppath)
    return dpath, wpath, ppath, bundle


# ---------------------------------------------------------------------------
# the off pin (metrics_overhead_check.py pins the same thing in CI)
# ---------------------------------------------------------------------------


def test_policy_off_pin(ctx):
    """Default server: no PolicyPlane, zero policy.* names, empty
    policy snapshot section — the r7 skip-wrapper shape."""
    srv = Server(NK, VL, opts=SystemOptions(sync_max_per_sec=0),
                 ctx=ctx)
    w = srv.make_worker(0)
    w.wait(w.set(np.arange(NK), np.ones((NK, VL), np.float32)))
    w.pull_sync(np.arange(8))
    assert srv.policy is None
    assert not [n for n in srv.obs.names() if n.startswith("policy.")]
    snap = srv.metrics_snapshot()
    assert snap["schema_version"] == 17
    assert snap["policy"] == {}
    srv.shutdown()


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def test_train_is_byte_deterministic(trained, tmp_path):
    """Re-training from the same traces writes a byte-identical
    artifact (no RNG, no timestamps), the thrashing-pool tier plane
    gets a real logistic fit, and truncated rows are excluded from the
    fit but counted loudly in the meta."""
    dpath, wpath, ppath, bundle = trained
    p2 = str(tmp_path / "again.json")
    train_policy(dpath, wpath, out_path=p2)
    with open(ppath, "rb") as f1, open(p2, "rb") as f2:
        assert f1.read() == f2.read()
    tm = bundle.meta["train"]
    assert set(tm) == set(PLANE_FEATURES)
    assert tm["tier"]["fit"] == "logistic", tm
    # default truncated_weight=0.0: forced-close rows never train
    assert bundle.meta["truncated_weight"] == 0.0
    for plane in tm:
        assert tm[plane]["truncated_rows"] >= 0
    assert bundle.meta["truncated_rows"] == sum(
        tm[p]["truncated_rows"] for p in tm)
    # up-weighting forced outcomes is rejected — they are not labels
    with pytest.raises(ValueError, match="truncated_weight"):
        train_policy(dpath, wpath, truncated_weight=1.5)


# ---------------------------------------------------------------------------
# artifact hygiene
# ---------------------------------------------------------------------------


def test_artifact_corruption_raises_named_error(trained, tmp_path):
    """Missing file, flipped body byte, and a wrong-format trace each
    raise PolicyError during verification — before anything consults
    a model."""
    dpath, _, ppath, _ = trained
    with pytest.raises(PolicyError):
        load_policy(str(tmp_path / "nope.json"))
    with open(ppath, "rb") as f:
        raw = bytearray(f.read())
    raw[-10] ^= 0x40  # flip one body byte: sha256 mismatch
    bad = tmp_path / "flipped.json"
    bad.write_bytes(bytes(raw))
    with pytest.raises(PolicyError):
        load_policy(str(bad))
    # a verified file of the WRONG format is rejected by name
    with pytest.raises(PolicyError):
        load_policy(dpath)


def test_feature_spec_mismatch_rejected(trained):
    """An artifact trained against a different PLANE_FEATURES contract
    (reordered columns, wrong width) must not load — silent skew
    between capture and inference is the failure mode features.py
    exists to prevent."""
    _, _, ppath, _ = trained
    d = load_policy(ppath).planes["tier"].to_dict()
    d["features"] = list(reversed(d["features"]))
    with pytest.raises(PolicyError, match="feature"):
        PlaneModel.from_dict(d)
    with pytest.raises(PolicyError):
        PlaneModel("tier", [0.0], [1.0], [0.0], 0.0)  # wrong width
    with pytest.raises(PolicyError, match="plane"):
        PlaneModel.constant("parking", 0.5)  # unknown plane


# ---------------------------------------------------------------------------
# observer-effect + value-preservation pins (deterministic replay)
# ---------------------------------------------------------------------------


def test_shadow_mode_scores_without_steering(trained):
    """Shadow replay folds agree/disagree verdicts, yet the reads
    digest is bit-identical to the plain heuristic replay — shadow
    scores the model, never applies it."""
    _, wpath, ppath, _ = trained
    tr = load_wtrace(wpath)
    base = ReplayEngine(tr, seed=3, speed=100.0).run()
    sh = ReplayEngine(tr, overrides={"policy_file": ppath,
                                     "policy_shadow": True},
                      seed=3, speed=100.0).run(include_snapshot=True)
    assert sh["reads_digest"] == base["reads_digest"]
    pol = sh["snapshot"]["policy"]
    assert pol["shadow"] is True
    consults = pol["shadow_agree"] + pol["shadow_disagree"]
    assert consults > 0 and pol["consults_total"] == consults
    # nothing applied, ever, in shadow mode
    assert pol["applied_total"] == 0


def test_learned_policy_preserves_reads_and_ranks_on_regret(trained):
    """The promotion-gate shape: heuristic vs learned-tier replay A/B
    with the metrics-only decision recorder attached. The learned
    candidate must apply real vetoes, fold a tier regret no worse than
    the heuristic's, and reproduce the heuristic reads digest BITWISE
    (the strict-win gate on a bigger storm is
    scripts/policy_gate_check.py)."""
    _, wpath, ppath, _ = trained
    tr = load_wtrace(wpath)
    art = rank_candidates(
        tr,
        {"heuristic": {},
         "learned": {"policy_tier": "learned", "policy_file": ppath}},
        objective="regret_rate_tier", seed=5, speed=100.0,
        score_decisions=True)
    heur = art["candidates"]["heuristic"]
    lrn = art["candidates"]["learned"]
    # value preservation: a policy changes what/when, never values
    assert lrn["reads_digest"] == heur["reads_digest"]
    r_h = heur["score"]["regret_rate_tier"]
    r_l = lrn["score"]["regret_rate_tier"]
    assert r_h is not None and r_l is not None
    assert r_l <= r_h, (r_l, r_h)
    # determinism: the same learned replay re-runs bit-identically
    redo = ReplayEngine(tr, overrides={"policy_tier": "learned",
                                       "policy_file": ppath},
                        seed=5, speed=100.0,
                        score_decisions=True).run(include_snapshot=True)
    assert redo["reads_digest"] == lrn["reads_digest"]
    pol = redo["snapshot"]["policy"]
    assert pol["mode.tier"] == "learned"
    assert pol["consults.tier"] > 0
    # the veto path genuinely ran (applied, or guard-refused)
    assert pol["applied_total"] + pol["guard_vetoes_total"] > 0


# ---------------------------------------------------------------------------
# live mechanics
# ---------------------------------------------------------------------------


def test_live_server_consults_policy_and_snapshots(ctx, trained,
                                                   tmp_path):
    """A live server with --sys.policy.file + learned tier consults
    the model at the real decision sites, registers the policy.*
    counters, and carries the plane detail in its snapshot."""
    _, _, ppath, bundle = trained
    opts = SystemOptions(sync_max_per_sec=0, prefetch=False,
                         tier=True, tier_hot_rows=8,
                         policy_file=ppath, policy_tier="learned")
    srv = Server(NK, VL, opts=opts, ctx=ctx, num_workers=1)
    assert srv.policy is not None
    assert srv.policy.active("tier")
    assert not srv.policy.active("serve")  # heuristic mode, no shadow
    w = srv.make_worker(0)
    w.wait(w.set(np.arange(NK), np.ones((NK, VL), np.float32)))
    rng = np.random.default_rng(23)
    for i in range(12):
        ks = np.unique((NK * rng.random(16) ** 6.0)
                       .astype(np.int64).clip(0, NK - 1))
        w.pull_sync(ks)
        w.wait(w.push(ks, np.ones((len(ks), VL), np.float32)))
        w.advance_clock()
        srv.wait_sync()
    assert [n for n in srv.obs.names() if n.startswith("policy.")]
    snap = srv.metrics_snapshot()
    pol = snap["policy"]
    assert pol["file"] == ppath
    assert pol["mode.tier"] == "learned"
    assert pol["planes_loaded"] == sorted(bundle.planes)
    assert pol["consults.tier"] > 0
    assert pol["consults_total"] >= pol["consults.tier"]
    srv.shutdown()
