"""Workload trace capture + deterministic replay (ISSUE 15 tentpole).

Tier-1 coverage for adapm_tpu/obs/wtrace.py + adapm_tpu/replay/:

  - THE determinism property test: a randomized 5-plane storm
    (pull/push/set, intents + relocations, clock advances, serve
    lookups, sync rounds, quiesce) is recorded once and replayed
    repeatedly — same trace + same seed + same knobs => bit-identical
    replayed reads (the sha256 reads digest), at different logical
    speeds, and EVEN ACROSS value-preserving knob candidates (the
    tiered store's bit-identity contract carries into replay);
  - corruption quartet: truncated body, flipped byte, wrong version,
    missing header each raise the NAMED WorkloadTraceError during
    verification — before any replay server exists;
  - the off pin: no --sys.trace.workload (default) => no recorder
    object, zero wtrace.* registry names, empty wtrace/replay snapshot
    sections, and the plain op path untouched;
  - capture mechanics: event kinds + clock domains (wall AND mono on
    every event), the lossless-or-loudly-sampled key budget, the
    bounded event buffer's loud drop counter, atomic flush/close;
  - ranked comparison artifact sanity (rank_candidates).
"""
import json

import numpy as np
import pytest

from adapm_tpu import Server, SystemOptions, make_mesh
from adapm_tpu.obs.wtrace import (WTRACE_VERSION, WorkloadTraceError,
                                  WorkloadTraceRecorder, event_keys,
                                  load_wtrace)
from adapm_tpu.replay import ReplayEngine, rank_candidates, replay_trace
from adapm_tpu.serve import ServePlane

NK = 128
VL = 4


@pytest.fixture(scope="module")
def ctx():
    return make_mesh(8)


def make_server(ctx, tmp_path=None, num_keys=NK, vlen=VL, **kw):
    opts = kw.pop("opts", None)
    if opts is None:
        opts = SystemOptions(sync_max_per_sec=0)
    if tmp_path is not None and not opts.trace_workload:
        opts.trace_workload = str(tmp_path / "capture.wtrace")
    return Server(num_keys, vlen, opts=opts, ctx=ctx, **kw)


def _seed(w, num_keys=NK, vlen=VL):
    w.wait(w.set(np.arange(num_keys),
                 np.ones((num_keys, vlen), np.float32)))


def _capture_storm(ctx, tmp_path, steps=40, key_budget=4096,
                   with_serve=True):
    """One seeded multi-plane storm under capture; returns the trace
    path after a clean shutdown (final flush)."""
    opts = SystemOptions(sync_max_per_sec=0, prefetch=False,
                         trace_workload=str(tmp_path / "storm.wtrace"),
                         trace_workload_keys=key_budget)
    srv = Server(NK, VL, opts=opts, ctx=ctx, num_workers=2)
    w0, w1 = srv.make_worker(0), srv.make_worker(1)
    _seed(w0)
    rng = np.random.default_rng(7)
    plane = ServePlane(srv) if with_serve else None
    sessions = {}
    n_serves = 0
    if plane is not None:
        plane.configure_tenant("gold", priority=1)
        sessions["gold"] = plane.session(tenant="gold")
        sessions[None] = plane.session()
    for i in range(steps):
        w = w0 if i % 2 == 0 else w1
        op = rng.integers(0, 6)
        ks = np.unique(rng.integers(0, NK, int(rng.integers(1, 24))))
        if op == 0:
            w.pull_sync(ks)
        elif op == 1:
            w.wait(w.push(ks, rng.normal(
                size=(len(ks), VL)).astype(np.float32)))
        elif op == 2:
            w.wait(w.set(ks, rng.normal(
                size=(len(ks), VL)).astype(np.float32)))
        elif op == 3:
            w.intent(ks, w.current_clock, w.current_clock + 4)
            w.advance_clock()
        elif op == 4 and plane is not None:
            # alternate tenanted / untenanted lookups so both admission
            # shapes land in the trace
            sess = sessions["gold" if n_serves % 2 else None]
            n_serves += 1
            sess.lookup(rng.integers(0, NK, 16))
        else:
            srv.wait_sync()
    srv.quiesce()
    path = srv.opts.trace_workload
    if plane is not None:
        plane.close()
    srv.shutdown()
    return path


# ---------------------------------------------------------------------------
# the off pin (metrics_overhead_check.py pins the same thing in CI)
# ---------------------------------------------------------------------------


def test_capture_off_pin(ctx):
    """Default server: no recorder, zero wtrace.* names, empty
    wtrace/replay snapshot sections — the r7 skip-wrapper shape."""
    srv = make_server(ctx)
    w = srv.make_worker(0)
    _seed(w)
    w.pull_sync(np.arange(8))
    assert srv.wtrace is None and srv.replay_stats is None
    assert not [n for n in srv.obs.names() if n.startswith("wtrace.")]
    snap = srv.metrics_snapshot()
    assert snap["schema_version"] == 17
    assert snap["wtrace"] == {} and snap["replay"] == {}
    srv.shutdown()


# ---------------------------------------------------------------------------
# capture mechanics
# ---------------------------------------------------------------------------


def test_capture_event_stream_and_clock_domains(ctx, tmp_path):
    """Every op kind lands in the trace with its logical clock AND both
    time domains (wall + mono — the ISSUE 15 clock-domain rule); the
    wtrace.* counters ride the registry; the file verifies."""
    path = _capture_storm(ctx, tmp_path)
    tr = load_wtrace(path)
    kinds = tr.kinds()
    for k in ("pull", "push", "set", "intent", "clock", "serve",
              "sync", "quiesce"):
        assert kinds.get(k, 0) >= 1, (k, kinds)
    monos = []
    for ev in tr.events:
        assert {"kind", "clock", "wall", "mono", "seq"} <= set(ev), ev
        monos.append(ev["mono"])
    assert monos == sorted(monos), \
        "recorded mono stamps must be non-decreasing in seq order"
    # serve events carry the admission attributes
    sv = [e for e in tr.events if e["kind"] == "serve"]
    assert {e["tenant"] for e in sv} >= {None, "gold"}
    assert any(e["priority"] == 1 for e in sv)
    # meta carries geometry + knobs for the replay server
    assert tr.meta["num_keys"] == NK
    assert tr.meta["value_lengths"] == VL
    assert tr.meta["knobs"]["prefetch"] is False
    assert tr.dropped == 0


def test_capture_registers_metrics_and_snapshot_section(ctx, tmp_path):
    srv = make_server(ctx, tmp_path)
    w = srv.make_worker(0)
    _seed(w)
    w.pull_sync(np.arange(4))
    names = srv.obs.names()
    for n in ("wtrace.events_total", "wtrace.dropped_total",
              "wtrace.sampled_batches_total", "wtrace.bytes_written"):
        assert n in names, n
    snap = srv.metrics_snapshot()
    assert snap["wtrace"]["events_total"] >= 2
    assert snap["wtrace"]["path"] == srv.opts.trace_workload
    assert snap["wtrace"]["closed"] is False
    srv.shutdown()
    snap2 = srv.metrics_snapshot()
    assert snap2["wtrace"]["closed"] is True


def test_key_budget_lossless_or_loudly_sampled(ctx, tmp_path):
    """Batches within the budget record exact keys; beyond it an
    evenly-strided sample + the true count, counted loudly — and
    event_keys reconstructs deterministically from a seeded rng."""
    opts = SystemOptions(sync_max_per_sec=0, prefetch=False,
                         trace_workload=str(tmp_path / "b.wtrace"),
                         trace_workload_keys=16)
    srv = Server(NK, VL, opts=opts, ctx=ctx)
    w = srv.make_worker(0)
    _seed(w)                      # set of 128 keys: sampled
    small = np.arange(10)
    w.pull_sync(small)            # exact
    big = np.arange(100)
    w.pull_sync(big)              # sampled
    assert int(srv.obs.find("wtrace.sampled_batches_total").value) == 2
    srv.shutdown()
    tr = load_wtrace(str(tmp_path / "b.wtrace"))
    pulls = [e for e in tr.events if e["kind"] == "pull"]
    exact = next(e for e in pulls if e["n"] == 10)
    assert exact["keys"] == [int(k) for k in small]
    assert "sampled" not in exact
    samp = next(e for e in pulls if e["n"] == 100)
    assert samp["sampled"] is True and "keys" not in samp
    assert 1 <= len(samp["sample"]) <= 16
    assert set(samp["sample"]) <= set(int(k) for k in big)
    # reconstruction: deterministic given the rng seed, loud without
    rng = np.random.default_rng(5)
    k1 = event_keys(samp, rng=np.random.default_rng(5))
    k2 = event_keys(samp, rng=np.random.default_rng(5))
    assert len(k1) == 100 and np.array_equal(k1, k2)
    with pytest.raises(ValueError, match="key-sampled"):
        event_keys(samp)
    assert np.array_equal(event_keys(exact), small)
    del rng


def test_event_buffer_bound_drops_loudly(ctx, tmp_path):
    opts = SystemOptions(sync_max_per_sec=0, prefetch=False,
                         trace_workload=str(tmp_path / "d.wtrace"))
    srv = Server(NK, VL, opts=opts, ctx=ctx)
    srv.wtrace.max_events = 4
    w = srv.make_worker(0)
    _seed(w)
    for _ in range(8):
        w.pull_sync(np.arange(4))
    assert int(srv.obs.find("wtrace.dropped_total").value) >= 4
    srv.shutdown()
    tr = load_wtrace(str(tmp_path / "d.wtrace"))
    assert len(tr.events) == 4 and tr.dropped >= 4


def test_flush_is_atomic_and_mid_run_readable(ctx, tmp_path):
    srv = make_server(ctx, tmp_path)
    w = srv.make_worker(0)
    _seed(w)
    w.pull_sync(np.arange(6))
    p = srv.wtrace.flush()
    mid = load_wtrace(p)            # verifies header + checksum
    assert mid.kinds().get("pull", 0) >= 1
    assert not list(tmp_path.glob("*.tmp")), "tmp file left behind"
    w.pull_sync(np.arange(6))
    srv.shutdown()                  # final flush supersedes
    assert len(load_wtrace(p).events) > len(mid.events)


# ---------------------------------------------------------------------------
# corruption: named error BEFORE any server mutation
# ---------------------------------------------------------------------------


def test_corrupt_trace_raises_named_error(ctx, tmp_path):
    path = _capture_storm(ctx, tmp_path, steps=10, with_serve=False)
    raw = open(path, "rb").read()
    # truncated body
    trunc = tmp_path / "trunc.wtrace"
    trunc.write_bytes(raw[:-20])
    with pytest.raises(WorkloadTraceError, match="bytes"):
        load_wtrace(str(trunc))
    # flipped byte in the checksummed body
    nl = raw.find(b"\n")
    flip = bytearray(raw)
    flip[nl + 30] ^= 0xFF
    bad = tmp_path / "flip.wtrace"
    bad.write_bytes(bytes(flip))
    with pytest.raises(WorkloadTraceError, match="sha256"):
        load_wtrace(str(bad))
    # wrong version in the header
    hdr = json.loads(raw[:nl])
    hdr["version"] = WTRACE_VERSION + 1
    vbad = tmp_path / "v.wtrace"
    vbad.write_bytes(json.dumps(hdr).encode() + raw[nl:])
    with pytest.raises(WorkloadTraceError, match="version"):
        load_wtrace(str(vbad))
    # not a wtrace at all / missing header line
    junk = tmp_path / "junk.wtrace"
    junk.write_bytes(b"{}")
    with pytest.raises(WorkloadTraceError):
        load_wtrace(str(junk))
    with pytest.raises(WorkloadTraceError, match="cannot read"):
        load_wtrace(str(tmp_path / "missing.wtrace"))
    # the engine verifies at CONSTRUCTION — before any replay server
    # exists, so a corrupt trace can never half-drive one
    with pytest.raises(WorkloadTraceError):
        ReplayEngine(str(bad))


# ---------------------------------------------------------------------------
# THE determinism property test
# ---------------------------------------------------------------------------


def test_capture_replay_determinism_property(ctx, tmp_path):
    """Randomized 5-plane storm recorded once; replayed repeatedly:
    same seed => bit-identical reads digest, across logical speeds,
    and across value-preserving knob candidates (the tiered store's
    bit-identity contract holds under replay). A different seed
    changes the synthesized values, hence the digest — the digest is
    a real function of the replayed data, not a constant."""
    path = _capture_storm(ctx, tmp_path, steps=48, key_budget=12)
    tr = load_wtrace(path)
    assert tr.kinds().get("serve", 0) >= 1
    r1 = ReplayEngine(tr, seed=11, speed=100).run()
    r2 = ReplayEngine(tr, seed=11, speed=100).run()
    assert r1["reads_digest"] == r2["reads_digest"]
    assert r1["reads"] == r2["reads"] > 0
    assert r1["events_replayed"] == r2["events_replayed"] > 0
    # speed changes pacing, never reads
    r_fast = ReplayEngine(tr, seed=11, speed=10.0).run()
    assert r_fast["reads_digest"] == r1["reads_digest"]
    # a value-preserving knob candidate (tiered residency) replays the
    # SAME bits — the r10 bit-identity contract carried into replay
    r_tier = ReplayEngine(tr, overrides={"tier": True,
                                         "tier_hot_rows": 16},
                          seed=11, speed=100).run()
    assert r_tier["reads_digest"] == r1["reads_digest"]
    assert r_tier["score"]["hot_hit_rate"] is not None
    # the digest is data: a different seed synthesizes different
    # pushed values and must move it
    r_other = ReplayEngine(tr, seed=12, speed=100).run()
    assert r_other["reads_digest"] != r1["reads_digest"]


def test_replay_rejects_bad_knobs_and_bad_speed(ctx, tmp_path):
    path = _capture_storm(ctx, tmp_path, steps=8, with_serve=False)
    with pytest.raises(ValueError, match="unknown replay knob"):
        ReplayEngine(path, overrides={"hot_rows": 8}).run()
    with pytest.raises(ValueError, match="speed"):
        ReplayEngine(path, speed=0)
    with pytest.raises(ValueError, match="metrics"):
        ReplayEngine(path, overrides={"metrics": False}).run()
    with pytest.raises(ValueError, match="capture itself"):
        ReplayEngine(path, overrides={
            "trace_workload": "/tmp/x.wtrace"}).run()
    # determinism pins are not candidate knobs: re-enabling deadlines
    # or the timer loops turns wall-clock races back into "behavior"
    for pin in ("serve_deadline_ms", "sync_max_per_sec", "prefetch"):
        with pytest.raises(ValueError, match="determinism pin"):
            ReplayEngine(path, overrides={pin: 1}).run()


def test_replay_snapshot_section_and_decisions_skipped(ctx, tmp_path):
    """The replay engine re-decides management decisions (reloc /
    promote observed events are skipped, counted) and stamps the
    `replay` snapshot section on the driven server (schema v11)."""
    path = _capture_storm(ctx, tmp_path, steps=32)
    tr = load_wtrace(path)
    assert tr.kinds().get("reloc", 0) >= 1, \
        "storm should have landed at least one relocation decision"
    res = replay_trace(tr, seed=1, speed=100)
    assert res["events_skipped"].get("reloc", 0) >= 1
    assert res["events_total"] == len(tr.events)
    # the engine folded its stats into the driven server's snapshot
    # before shutdown (include_snapshot exposes it)
    res2 = ReplayEngine(tr, seed=1).run(include_snapshot=True)
    rep = res2["snapshot"]["replay"]
    assert rep["reads_digest"] == res["reads_digest"]
    assert rep["events_replayed"] == res["events_replayed"]
    assert rep["trace"] == path


def test_rank_candidates_artifact(ctx, tmp_path):
    """Two-candidate knob sweep: ranked artifact carries per-candidate
    scores + a deterministic winner by the named objective (the full
    live-vs-replay ordering guard is scripts/trace_replay_check.py)."""
    path = _capture_storm(ctx, tmp_path, steps=24, with_serve=False)
    art = rank_candidates(
        path,
        {"hot_all": {"tier": True, "tier_hot_rows": NK},
         "hot_8": {"tier": True, "tier_hot_rows": 8}},
        objective="hot_hit_rate", seed=2, speed=100,
        out_path=str(tmp_path / "compare.json"))
    assert art["winner"] in ("hot_all", "hot_8")
    assert sorted(art["ranking"]) == ["hot_8", "hot_all"]
    assert art["objective"] == "hot_hit_rate"
    for name, cand in art["candidates"].items():
        assert cand["score"]["hot_hit_rate"] is not None, name
        assert cand["reads_digest"]
    # all-hot must not LOSE to a tiny hot pool on hit rate
    s_all = art["candidates"]["hot_all"]["score"]["hot_hit_rate"]
    s_8 = art["candidates"]["hot_8"]["score"]["hot_hit_rate"]
    assert s_all >= s_8
    assert art["winner"] == "hot_all" or s_all == s_8
    on_disk = json.loads((tmp_path / "compare.json").read_text())
    assert on_disk["winner"] == art["winner"]
    with pytest.raises(ValueError, match="objective"):
        rank_candidates(path, {"a": None}, objective="nope")


def test_replay_inherits_recorded_knobs(ctx, tmp_path):
    """The replay baseline is the RECORDED configuration, not library
    defaults — a candidate's overrides are a diff against the config
    that produced the workload — with the determinism/hygiene pins
    applied on top."""
    from adapm_tpu.replay.engine import _build_opts
    opts = SystemOptions(sync_max_per_sec=0, prefetch=False,
                         serve_max_batch=32, channels=2,
                         trace_workload=str(tmp_path / "k.wtrace"))
    srv = Server(NK, VL, opts=opts, ctx=ctx)
    w = srv.make_worker(0)
    _seed(w)
    srv.shutdown()
    tr = load_wtrace(str(tmp_path / "k.wtrace"))
    built, ns = _build_opts(tr, None)
    # recorded non-defaults carry over
    assert built.serve_max_batch == 32 and built.channels == 2
    assert ns == srv.ctx.num_shards
    # pins win over the recorded values
    assert built.sync_max_per_sec == 0 and built.prefetch is False
    assert built.trace_workload is None and built.metrics is True
    assert built.ckpt_every_s == 0.0 and built.stats_out is None
    # candidate overrides still land on top of the recorded base
    built2, _ = _build_opts(tr, {"serve_max_batch": 16})
    assert built2.serve_max_batch == 16


def test_recorder_knob_validation():
    """Hand-built options reject a zero key budget (the CLI round-trip
    lives in test_config_knobs); the recorder itself refuses an empty
    path."""
    with pytest.raises(ValueError, match="workload_keys"):
        SystemOptions(trace_workload_keys=0).validate_serve()
    with pytest.raises(ValueError, match="path"):
        WorkloadTraceRecorder(None, "")
