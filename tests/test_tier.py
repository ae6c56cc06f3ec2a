"""Tiered parameter storage (ISSUE 5 tentpole; adapm_tpu/tier).

The load-bearing test is THE acceptance storm: a randomized interleaving
of push / set / relocate / replica churn / sync rounds / promote /
demote against a tiered server, with an UNTIERED shadow server applying
the identical operation sequence — every read (read_main of the whole
table plus worker pulls of random batches) must be bit-identical at
every step and after quiesce. Residency moves values between the
device-hot pool and the host cold store; it must never change them.

Plus: capacity bounds (hot pool never exceeds --sys.tier.hot_rows),
intent pinning (pinned rows survive pressure demotion), checkpoint
save/restore with tiering (restored values bit-identical regardless of
pre-save residency; residency reset all-cold; dirty-delta sync tracking
consistent after restore), the tier metrics section (schema v4), and
the deterministic double-close shutdown contract.
"""
import numpy as np
import pytest

import adapm_tpu
from adapm_tpu.base import CLOCK_MAX
from adapm_tpu.config import SystemOptions

E = 384
L = 8


def _mk(tier: bool, hot_rows: int = 16, **kw):
    # Until PR 6, two-server tests had to null the tier worker's kick:
    # concurrent sharded-program dispatch from two lock domains could
    # deadlock XLA-CPU's collective rendezvous. The unified executor's
    # dispatch gate serializes every sharded enqueue process-wide
    # (docs/EXECUTOR.md), so the worker now runs EVERYWHERE — including
    # the two-servers-on-one-device storm below (the regression shape).
    opts = SystemOptions(sync_max_per_sec=0, prefetch=False,
                         tier=tier, tier_hot_rows=hot_rows, **kw)
    return adapm_tpu.setup(E, L, opts=opts)


def _read_all(srv):
    return np.asarray(srv.read_main(np.arange(E)))


def _assert_bitwise(srv, ref, tag):
    a, b = _read_all(srv), _read_all(ref)
    assert np.array_equal(a, b), (
        f"{tag}: tiered read diverged from untiered shadow "
        f"({int((a != b).sum())} floats differ)")


# ---------------------------------------------------------------------------
# THE acceptance storm
# ---------------------------------------------------------------------------


def test_tier_storm_bit_identical_to_untiered_shadow(rng):
    # runtime lock-order sentinel (ISSUE 11): the promote/demote/sync/
    # relocate churn takes server lock + gate + registry in every
    # combination this plane knows — a cycle raises here, named
    srv = _mk(True, hot_rows=16, lint_lockorder=True)
    ref = _mk(False)
    w, wr = srv.make_worker(0), ref.make_worker(0)
    vals = rng.normal(size=(E, L)).astype(np.float32)
    for ww in (w, wr):
        ww.set(np.arange(E), vals)
    keys = np.arange(E)
    for step in range(50):
        op = rng.integers(0, 7)
        if op == 0:      # additive push (with in-batch duplicates)
            ks = rng.integers(0, E, 24)
            v = rng.normal(size=(24, L)).astype(np.float32)
            w.push(ks, v)
            wr.push(ks, v)
        elif op == 1:    # set
            ks = rng.choice(E, 16, replace=False)
            v = rng.normal(size=(16, L)).astype(np.float32)
            w.set(ks, v)
            wr.set(ks, v)
        elif op == 2:    # relocation (identical on both servers)
            ks = rng.choice(E, 12, replace=False)
            dest = int(rng.integers(0, srv.num_shards))
            srv._relocate_to(ks, dest)
            ref._relocate_to(ks, dest)
        elif op == 3:    # replica churn: intent + forced round
            ks = rng.choice(keys[srv.ab.owner[keys] != w.shard], 16,
                            replace=False)
            end = int(w.current_clock + rng.integers(1, 4))
            w.intent(ks, w.current_clock, end)
            wr.intent(ks, wr.current_clock, end)
            srv.sync.run_round(force_intents=True, all_channels=True)
            ref.sync.run_round(force_intents=True, all_channels=True)
        elif op == 4:    # forced sync round (flush + expiry drops)
            srv.sync.run_round(force_intents=True, all_channels=True)
            ref.sync.run_round(force_intents=True, all_channels=True)
        elif op == 5:    # promotion (tiered only: must be value-invisible)
            srv.tier.promote_keys(rng.choice(E, 32, replace=False))
        else:            # demotion + a maintenance pass (tiered only)
            srv.tier.demote_keys(rng.choice(E, 32, replace=False))
            srv.tier.maintain()
        if rng.integers(0, 3) == 0:
            w.advance_clock()
            wr.advance_clock()
        # reads at every step: whole table + a duplicate-heavy pull
        _assert_bitwise(srv, ref, f"step {step} (op {op})")
        pk = rng.integers(0, E, 20)
        assert np.array_equal(np.asarray(w.pull_sync(pk)),
                              np.asarray(wr.pull_sync(pk))), \
            f"step {step}: pull diverged"
    srv.quiesce()
    ref.quiesce()
    _assert_bitwise(srv, ref, "after quiesce")
    srv.shutdown()
    ref.shutdown()
    # lock-order sentinel: non-vacuous graph, zero violations (the
    # dynamic half of the APM001/APM002 static claims; ISSUE 11)
    from adapm_tpu.lint import lockorder
    sen = lockorder.get_sentinel()
    assert sen is not None and sen.edges(), \
        "sentinel saw no lock edges: the storm exercised nothing"
    sen.assert_clean()
    lockorder.disable_sentinel()


# ---------------------------------------------------------------------------
# capacity + residency mechanics
# ---------------------------------------------------------------------------


def test_hot_pool_capacity_bounded(rng):
    srv = _mk(True, hot_rows=8)
    w = srv.make_worker(0)
    w.set(np.arange(E), rng.normal(size=(E, L)).astype(np.float32))
    # ask for far more than fits: promotion must truncate, never exceed
    srv.tier.promote_keys(np.arange(E))
    st = srv.stores[0]
    for s in range(st.res.num_shards):
        assert st.res.hot_count(s) <= st.res.hot_rows
    # reads still correct with a mostly-cold table
    assert np.array_equal(
        np.asarray(w.pull_sync(np.arange(E))).ravel(),
        _read_all(srv))
    assert st.tier_cold_hits > 0  # the cold path actually served
    srv.shutdown()


def test_intent_pins_survive_pressure_demotion(rng):
    from adapm_tpu.base import MgmtTechniques
    # REPLICATION_ONLY keeps owners in place, so the pinned owner rows
    # stay spread over the shards (4 per shard — within hot capacity);
    # with relocation on, the intent would pull all 32 owners onto one
    # shard, where they legitimately exceed a 16-row hot pool
    srv = _mk(True, hot_rows=16, tier_demote_batch=4,
              techniques=MgmtTechniques.REPLICATION_ONLY)
    w = srv.make_worker(0)
    w.set(np.arange(E), rng.normal(size=(E, L)).astype(np.float32))
    pinned = np.arange(0, 32)
    w.intent(pinned, 0, CLOCK_MAX)
    srv.sync.run_round(force_intents=True, all_channels=True)
    srv.tier.maintain()  # drains the intent promotion wants
    st = srv.stores[0]
    o_sh, o_sl = srv.ab.owner[pinned], srv.ab.slot[pinned]
    assert (st.res.dev_row[o_sh, o_sl] >= 0).all(), \
        "intent-pinned keys were not promoted"
    # pressure: promote lots of other keys; pinned rows must stay hot
    srv.tier.promote_keys(np.arange(64, E))
    srv.tier.maintain()
    assert (st.res.dev_row[srv.ab.owner[pinned],
                           srv.ab.slot[pinned]] >= 0).all(), \
        "pressure demotion evicted intent-pinned rows"
    srv.shutdown()


def test_residency_epoch_bumps_on_moves(rng):
    srv = _mk(True, hot_rows=16)
    w = srv.make_worker(0)
    w.set(np.arange(E), rng.normal(size=(E, L)).astype(np.float32))
    e0 = srv.tier.epoch
    srv.tier.promote_keys(np.arange(0, 16))
    e1 = srv.tier.epoch
    assert e1 > e0
    srv.tier.demote_keys(np.arange(0, 8))
    assert srv.tier.epoch > e1
    srv.shutdown()


def test_tier_metrics_section_schema_v4(rng):
    srv = _mk(True, hot_rows=16)
    w = srv.make_worker(0)
    w.set(np.arange(E), rng.normal(size=(E, L)).astype(np.float32))
    w.pull_sync(np.arange(0, 64))
    srv.tier.promote_keys(np.arange(0, 16))
    snap = srv.metrics_snapshot()
    assert snap["schema_version"] == 17
    t = snap["tier"]
    assert t["promotions"] >= 16
    assert 0.0 <= t["hot_hit_rate"] <= 1.0
    assert t["hot_rows_used"] <= t["hot_rows_capacity"]
    assert "cold_serve_s" in t  # the cold-serve latency histogram
    srv.shutdown()


def test_compose_slot_table_cold_is_oob(rng):
    """Cold rows in the composed device mirror must carry OOB, never
    -1: JAX `.at[]` drops/fills only LARGE positive out-of-bounds
    indices — a negative index WRAPS to the last row, so a -1 sentinel
    would silently read/corrupt whichever slot owns the last hot row."""
    from adapm_tpu.core.store import OOB
    srv = _mk(True, hot_rows=16)
    w = srv.make_worker(0)
    w.set(np.arange(E), rng.normal(size=(E, L)).astype(np.float32))
    srv.tier.promote_keys(np.arange(0, 32))
    res = srv.stores[0].res
    with srv._lock:     # one residency for both reads: the worker waits
        eff = srv.tier.compose_slot_table()
        rows = res.dev_row[srv.ab.owner[np.arange(E)],
                           srv.ab.slot[np.arange(E)]].copy()
    assert (eff >= 0).all()
    assert (eff[rows < 0] == OOB).all(), "cold rows must mirror as OOB"
    assert np.array_equal(eff[rows >= 0], rows[rows >= 0])
    srv.shutdown()


def test_device_routed_negatives_bit_identical(rng):
    """Device-routed fused steps WITH device-drawn negatives under tier
    vs the untiered shadow: with the negative population kept
    device-resident (intent-pinned before the runs), the hot-restricted
    draw equals the untiered local draw, so the whole training
    trajectory must stay bit-identical — this exercises the composed
    slot mirror and the in-program sampler the host-routed storm
    cannot reach."""
    import jax.numpy as jnp

    from adapm_tpu.ops import DeviceRoutedRunner

    d = L // 2

    def loss_fn(embs, aux):
        return jnp.mean(jnp.sum(embs["a"][:, None, :] * embs["n"],
                                axis=-1))

    pop = np.arange(0, 64)
    outs = []
    for tier in (True, False):
        srv = _mk(tier, hot_rows=32)
        w = srv.make_worker(0)
        vals = np.random.default_rng(5).normal(
            size=(E, L)).astype(np.float32)
        vals[:, d:] = np.abs(vals[:, d:])
        w.set(np.arange(E), vals)
        # make the neg population local (and, tiered, device-resident)
        w.intent(pop, 0, CLOCK_MAX)
        srv.sync.run_round(force_intents=True, all_channels=True)
        if tier:
            srv.tier.promote_keys(pop)
        run = DeviceRoutedRunner(
            srv, loss_fn, {"a": 0, "n": 0}, {"a": d, "n": d}, shard=0,
            neg_role="n", neg_shape=(8, 4), neg_population=pop, seed=11)
        kb = np.random.default_rng(6)
        for _ in range(5):
            run({"a": kb.choice(pop, 8, replace=False)}, None, lr=0.05)
        outs.append(_read_all(srv))
        srv.shutdown()
    assert np.array_equal(outs[0], outs[1]), \
        "device-drawn negatives diverged under tier"


def test_tiered_negative_fallback_promotes_all_cold(rng):
    """All-cold shard with zero resident population keys: the tiered
    negative-index fallback must PROMOTE a slice of the population and
    draw from the resident subset (never silently sample cold keys,
    whose mirror rows are OOB and would read zeros / drop scatters)."""
    import jax.numpy as jnp

    from adapm_tpu.ops import DeviceRoutedRunner

    d = L // 2

    def loss_fn(embs, aux):
        return jnp.mean(jnp.sum(embs["a"][:, None, :] * embs["n"],
                                axis=-1))

    srv = _mk(True, hot_rows=32)
    w = srv.make_worker(0)
    vals = np.random.default_rng(5).normal(size=(E, L)).astype(np.float32)
    vals[:, d:] = np.abs(vals[:, d:])
    w.set(np.arange(E), vals)
    # population owned by OTHER shards, everything cold, no replicas:
    # the untiered code would fall back to full-population draws
    pop = np.arange(E)[srv.ab.owner[np.arange(E)] != 0][:48]
    run = DeviceRoutedRunner(
        srv, loss_fn, {"a": 0, "n": 0}, {"a": d, "n": d}, shard=0,
        neg_role="n", neg_shape=(8, 4), neg_population=pop, seed=3)
    run({"a": np.arange(0, 8)}, None, lr=0.05)
    res = srv.stores[0].res
    o_sh, o_sl = srv.ab.owner[pop], srv.ab.slot[pop]
    assert (res.dev_row[o_sh, o_sl] >= 0).any(), \
        "fallback did not promote any population rows"
    srv.shutdown()


# ---------------------------------------------------------------------------
# r10 known-limit regression (retired by the PR 6 dispatch gate)
# ---------------------------------------------------------------------------


def test_two_servers_concurrent_sharded_dispatch_bounded(rng):
    """Two servers sharing this process's virtual device set dispatch
    sharded programs CONCURRENTLY — tier maintenance enabled on both
    (executor `tier` streams) plus a driving thread per server pushing,
    pulling, and churning residency — and every join is bounded. The
    old failure mode was an indefinite XLA-CPU collective-rendezvous
    stall whenever two lock domains interleaved per-device enqueue
    orders; the process-wide dispatch gate (adapm_tpu/exec) makes the
    orders identical by construction, so the former workaround (nulling
    the worker's kick and driving tier.maintain() synchronously) is
    gone for good."""
    import threading
    srv1 = _mk(True, hot_rows=16)
    srv2 = _mk(True, hot_rows=16)
    vals = rng.normal(size=(E, L)).astype(np.float32)
    w1, w2 = srv1.make_worker(0), srv2.make_worker(0)
    w1.set(np.arange(E), vals)
    w2.set(np.arange(E), vals)
    errs = []

    def churn(srv, w, seed):
        r = np.random.default_rng(seed)
        try:
            for _ in range(12):
                ks = r.integers(0, E, 16)
                w.push(ks, r.normal(size=(16, L)).astype(np.float32))
                srv.tier.promote_keys(r.choice(E, 24, replace=False))
                srv.tier.demote_keys(r.choice(E, 24, replace=False))
                srv.tier.engine.kick()  # async passes on the executor
                w.pull_sync(r.integers(0, E, 16))
        except BaseException as e:  # noqa: BLE001 — surface in-thread
            errs.append(e)

    ts = [threading.Thread(target=churn, args=(srv1, w1, 1)),
          threading.Thread(target=churn, args=(srv2, w2, 2))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in ts), \
        "concurrent sharded dispatch stalled — rendezvous deadlock?"
    assert not errs, errs
    # bounded shutdown too: both executors drain without a stall
    srv1.shutdown()
    srv2.shutdown()


# ---------------------------------------------------------------------------
# shutdown ordering satellite
# ---------------------------------------------------------------------------


def test_shutdown_deterministic_and_double_close(rng, tmp_path):
    from adapm_tpu.serve import ServePlane
    srv = _mk(True, hot_rows=16,
              ckpt_every_s=0.02, ckpt_path=str(tmp_path / "chain"))
    w = srv.make_worker(0)
    w.set(np.arange(E), rng.normal(size=(E, L)).astype(np.float32))
    plane = ServePlane(srv)
    plane.session().lookup(np.arange(8))
    srv.tier.engine.kick()   # queue real tier maintenance work
    srv.start_sync_thread()
    # race an in-flight checkpoint program against shutdown (ISSUE 10
    # satellite): a zero-delay save is queued on the `ckpt` stream
    # right as teardown begins; close must DRAIN it before pool
    # teardown, never cancel it into a half-written chain or read
    # through torn-down pools
    srv.exec.submit("ckpt", srv.ckpt.save, label="ckpt.save.race")
    srv.shutdown()
    # every background producer is down after the first shutdown, and
    # the unified executor closed LAST with nothing left on its streams
    assert srv._sync_thread is None
    assert not plane.batcher.is_alive()
    assert srv.exec.closed
    assert srv.exec.live_streams() == [], \
        "orphaned executor streams survived shutdown"
    # the raced save drained (not cancelled): the chain manifest
    # describes only durably-written, checksum-valid links
    from adapm_tpu.fault.ckpt import _load_verified_chain
    assert len(_load_verified_chain(str(tmp_path / "chain"))) >= 1
    srv.shutdown()  # double-close must be a no-op, not a crash
    # ... and the checkpointer's own close is idempotent too
    srv.ckpt.close()
    # a submit against the closed executor is a cancelled no-op, not a
    # crash (late kicks during teardown)
    c = srv.exec.submit("tier", lambda: 1)
    assert c.done() and c.cancelled
    # and a manually-closed plane before shutdown stays tolerated
    srv2 = _mk(True, hot_rows=16)
    p2 = ServePlane(srv2)
    p2.close()
    p2.close()
    srv2.shutdown()
    srv2.shutdown()


# ---------------------------------------------------------------------------
# checkpoint save/restore with tiering (satellite)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("restore_tier", [True, False])
def test_checkpoint_roundtrip_across_tiers(tmp_path, rng, restore_tier):
    from adapm_tpu.utils.checkpoint import restore_server, save_server
    srv = _mk(True, hot_rows=16)
    w = srv.make_worker(0)
    w.set(np.arange(E), rng.normal(size=(E, L)).astype(np.float32))
    # mixed residency before the save: some hot, some cold, plus live
    # replicas carrying unshipped deltas
    srv.tier.promote_keys(np.arange(0, 128))
    rem = np.arange(E)[srv.ab.owner[np.arange(E)] != w.shard][:32]
    w.intent(rem, 0, CLOCK_MAX)
    srv.sync.run_round(force_intents=True, all_channels=True)
    w.push(rem, rng.normal(size=(len(rem), L)).astype(np.float32))
    path = str(tmp_path / "ck.npz")
    save_server(srv, path)
    before = _read_all(srv)
    srv2 = _mk(restore_tier, hot_rows=16)
    restore_server(srv2, path)
    if restore_tier:
        # residency reset cleanly: everything cold. Checked BEFORE the
        # first read — a read's cold misses kick the (executor-run)
        # maintenance worker, which starts re-promoting immediately
        for st in srv2.stores:
            assert (st.res.dev_row < 0).all()
            assert (st.res.row_slot < 0).all()
            assert st.res.alloc.num_free(0) == st.res.hot_rows
    # bit-identical regardless of pre-save residency or restore tiering
    assert np.array_equal(_read_all(srv2), before)
    if restore_tier:
        # lazy re-promotion works and is value-invisible
        srv2.tier.promote_keys(np.arange(0, 64))
        assert np.array_equal(_read_all(srv2), before)
    # dirty-delta tracking consistent after restore: the checkpoint
    # carries unshipped replica deltas (restore marks everything dirty
    # once), and flushing them post-restore must land bit-identically
    # to flushing them on the original server
    w2 = srv2.make_worker(0)
    srv2.sync.run_round(force_intents=True, all_channels=True)
    srv.sync.run_round(force_intents=True, all_channels=True)
    before = _read_all(srv)  # post-flush authoritative state
    assert np.array_equal(_read_all(srv2), before)
    # and new writes flow through sync correctly post-restore
    ks = np.arange(0, 16)
    v = rng.normal(size=(16, L)).astype(np.float32)
    w2.push(ks, v)
    srv2.quiesce()
    expect = before.reshape(E, L).copy()
    expect[ks] += v
    assert np.array_equal(_read_all(srv2).reshape(E, L), expect)
    srv.shutdown()
    srv2.shutdown()


def test_untiered_checkpoint_restores_into_tiered(tmp_path, rng):
    """A checkpoint written by an untiered server restores into a tiered
    one (the saved main table is tier-independent geometry)."""
    from adapm_tpu.utils.checkpoint import restore_server, save_server
    src = _mk(False)
    w = src.make_worker(0)
    w.set(np.arange(E), rng.normal(size=(E, L)).astype(np.float32))
    path = str(tmp_path / "ck.npz")
    save_server(src, path)
    before = _read_all(src)
    dst = _mk(True, hot_rows=16)
    restore_server(dst, path)
    assert np.array_equal(_read_all(dst), before)
    src.shutdown()
    dst.shutdown()
