"""Episodic device plane (ISSUE 14; adapm_tpu/device).

The load-bearing test is THE episodic acceptance storm: a tiered server
driven by an EpisodicRunner (episode rotation: pin/promote + key
staging of window N+1 overlapping window N's fused-step commits on the
`episode`/`episode_commit` streams) under a randomized interleaving of
push / set / relocate / replica churn / sync rounds / serve lookups,
against an UNTIERED NON-EPISODIC shadow applying the identical
operation sequence — every read (whole-table read_main, worker pulls,
serve lookups) bit-identical at every step and after quiesce. Episodic
execution changes WHEN values move, never WHAT a read returns.

Plus: the DevicePort surface (programs counted, pool swap-out), the
partition helper, the serialized/inline degradation, and the v10
device/episode snapshot sections.
"""
import numpy as np
import jax.numpy as jnp
import pytest

import adapm_tpu
from adapm_tpu.config import SystemOptions
from adapm_tpu.device import EpisodicRunner
from adapm_tpu.device.episode import plan_episodes
from adapm_tpu.ops import DeviceRoutedRunner

E = 384
L = 8
D = L // 2


def _loss(embs, aux):
    return jnp.mean(jnp.sum(embs["a"] * embs["b"], axis=-1))


def _mk(tier: bool, hot_rows: int = 16, **kw):
    opts = SystemOptions(sync_max_per_sec=0, prefetch=False,
                         tier=tier, tier_hot_rows=hot_rows, **kw)
    return adapm_tpu.setup(E, L, opts=opts)


def _init_vals(rng):
    vals = rng.normal(size=(E, L)).astype(np.float32)
    # AdaGrad accumulator columns must be positive (rsqrt domain)
    vals[:, D:] = np.abs(vals[:, D:]) + 1e-3
    return vals


def _runner(srv, seed=7):
    return DeviceRoutedRunner(srv, _loss, {"a": 0, "b": 0},
                              {"a": D, "b": D}, shard=0, seed=seed)


def _read_all(srv):
    return np.asarray(srv.read_main(np.arange(E)))


def _batches(rng, n, bsz=16):
    return [{"a": rng.integers(0, E, bsz), "b": rng.integers(0, E, bsz)}
            for _ in range(n)]


# ---------------------------------------------------------------------------
# THE episodic acceptance storm
# ---------------------------------------------------------------------------


def test_episodic_storm_bit_identical_to_sequential_shadow(rng):
    from adapm_tpu.serve import ServePlane
    srv = _mk(True, hot_rows=16, lint_lockorder=True)
    ref = _mk(False)
    w, wr = srv.make_worker(0), ref.make_worker(0)
    vals = _init_vals(rng)
    for ww in (w, wr):
        ww.set(np.arange(E), vals)
    run_e = EpisodicRunner(_runner(srv), episode_batches=3)
    run_s = _runner(ref)
    plane, plane_r = ServePlane(srv), ServePlane(ref)
    sess, sess_r = plane.session(), plane_r.session()
    keys = np.arange(E)
    for step in range(14):
        # episode rotation: a window of fused-step batches runs
        # episodically on srv (prep of window k+1 overlapping commit of
        # window k) and strictly sequentially on the shadow
        bs = _batches(rng, int(rng.integers(3, 7)))
        le = run_e.run(bs, lr=0.05)
        ls = [run_s(b, None, lr=0.05) for b in bs]
        assert len(le) == len(bs)
        for a, b in zip(le, ls):
            assert float(a) == float(b), f"step {step}: loss diverged"
        op = rng.integers(0, 6)
        if op == 0:      # additive push with in-batch duplicates
            ks = rng.integers(0, E, 24)
            v = rng.normal(size=(24, L)).astype(np.float32) * 1e-3
            w.push(ks, v)
            wr.push(ks, v)
        elif op == 1:    # set (keep acc columns positive)
            ks = rng.choice(E, 16, replace=False)
            v = _init_vals(rng)[:16]
            w.set(ks, v)
            wr.set(ks, v)
        elif op == 2:    # relocation (identical on both servers)
            ks = rng.choice(E, 12, replace=False)
            dest = int(rng.integers(0, srv.num_shards))
            srv._relocate_to(ks, dest)
            ref._relocate_to(ks, dest)
        elif op == 3:    # replica churn: intent + forced round
            cand = keys[srv.ab.owner[keys] != w.shard]
            ks = rng.choice(cand, min(16, len(cand)), replace=False)
            end = int(w.current_clock + rng.integers(1, 4))
            w.intent(ks, w.current_clock, end)
            wr.intent(ks, wr.current_clock, end)
            srv.sync.run_round(force_intents=True, all_channels=True)
            ref.sync.run_round(force_intents=True, all_channels=True)
        elif op == 4:    # forced sync round (flush + expiry drops)
            srv.sync.run_round(force_intents=True, all_channels=True)
            ref.sync.run_round(force_intents=True, all_channels=True)
        else:            # serve lookups, compared bitwise
            ks = rng.integers(0, E, 20)
            assert np.array_equal(np.asarray(sess.lookup(ks)),
                                  np.asarray(sess_r.lookup(ks))), \
                f"step {step}: serve lookup diverged"
        if rng.integers(0, 3) == 0:
            w.advance_clock()
            wr.advance_clock()
        a, b = _read_all(srv), _read_all(ref)
        assert np.array_equal(a, b), (
            f"step {step} (op {op}): episodic read diverged from "
            f"sequential shadow ({int((a != b).sum())} floats differ)")
        pk = rng.integers(0, E, 20)
        assert np.array_equal(np.asarray(w.pull_sync(pk)),
                              np.asarray(wr.pull_sync(pk))), \
            f"step {step}: pull diverged"
    srv.quiesce()
    ref.quiesce()
    assert np.array_equal(_read_all(srv), _read_all(ref)), \
        "post-quiesce tables diverged"
    plane.close()
    plane_r.close()
    srv.shutdown()
    ref.shutdown()
    from adapm_tpu.lint import lockorder
    sen = lockorder.get_sentinel()
    assert sen is not None and sen.edges(), \
        "sentinel saw no lock edges: the storm exercised nothing"
    sen.assert_clean()
    lockorder.disable_sentinel()


# ---------------------------------------------------------------------------
# mechanics
# ---------------------------------------------------------------------------


def test_plan_episodes_partition_preserves_order():
    bs = [{"a": np.array([i])} for i in range(10)]
    eps = plan_episodes(bs, None, 4)
    assert [len(e.batches) for e in eps] == [4, 4, 2]
    flat = [int(b["a"][0]) for e in eps for b in e.batches]
    assert flat == list(range(10))
    aux = list(range(10))
    eps = plan_episodes(bs, aux, 3)
    assert [e.auxes for e in eps] == [[0, 1, 2], [3, 4, 5], [6, 7, 8],
                                      [9]]


def test_episodic_single_stream_degrades_inline(rng):
    """--sys.exec.single_stream: the runner degrades to inline
    prep+commit — same results, no pipelining machinery."""
    vals = _init_vals(rng)
    kb = np.random.default_rng(11)
    bs = [{"a": kb.integers(0, E, 16), "b": kb.integers(0, E, 16)}
          for _ in range(7)]
    outs = []
    for single in (True, False):
        srv = _mk(True, hot_rows=16, exec_single_stream=single)
        w = srv.make_worker(0)
        w.set(np.arange(E), vals)
        losses = EpisodicRunner(_runner(srv),
                                episode_batches=2).run(bs, lr=0.05)
        assert len(losses) == len(bs)
        outs.append(_read_all(srv))
        srv.shutdown()
    assert np.array_equal(outs[0], outs[1])


def test_device_and_episode_snapshot_sections_v10(rng):
    srv = _mk(True, hot_rows=16)
    w = srv.make_worker(0)
    w.set(np.arange(E), _init_vals(rng))
    kb = np.random.default_rng(17)
    bs = [{"a": kb.integers(0, E, 16), "b": kb.integers(0, E, 16)}
          for _ in range(4)]
    EpisodicRunner(_runner(srv), episode_batches=2).run(bs, lr=0.05)
    snap = srv.metrics_snapshot()
    assert snap["schema_version"] == 17
    dev = snap["device"]
    assert dev["backend"] == "jax"
    assert dev["programs_total"] > 0
    assert dev["wire_ingest_rows_total"] >= 0
    ep = snap["episode"]
    assert ep["episodes_total"] == 2
    assert ep["staged_batches_total"] == 4
    assert ep["prep_s"]["count"] == 2 and ep["commit_s"]["count"] == 2
    srv.shutdown()
    # metrics off: sections present but empty (the r7 contract)
    srv2 = _mk(False, metrics=False)
    snap2 = srv2.metrics_snapshot()
    assert snap2["device"] == {} and snap2["episode"] == {}
    srv2.shutdown()


def test_port_swap_is_the_backend_boundary(rng):
    """A wrapped port observes every store dispatch — the 'a new
    backend is one port implementation' claim, exercised: swap the
    default port for a counting delegator, run traffic, and assert the
    programs flowed through it."""
    from adapm_tpu.device import default_port, set_default_port

    class CountingPort:
        def __init__(self, inner):
            self._inner = inner
            self.calls = 0

        def __getattr__(self, name):
            attr = getattr(self._inner, name)
            if callable(attr) and not name.startswith("_"):
                def wrapped(*a, **kw):
                    self.calls += 1
                    return attr(*a, **kw)
                return wrapped
            return attr

    counting = CountingPort(default_port())
    set_default_port(counting)
    try:
        srv = _mk(True, hot_rows=16)
        w = srv.make_worker(0)
        w.set(np.arange(E), _init_vals(rng))
        w.pull_sync(np.arange(64))
        srv.tier.promote_keys(np.arange(32))
        assert counting.calls > 0, \
            "store traffic bypassed the installed port"
        assert srv.stores[0].port is counting
        srv.shutdown()
    finally:
        set_default_port(None)


def test_episode_batches_knob_validation():
    with pytest.raises(ValueError, match="episode.batches"):
        SystemOptions(episode_batches=0).validate_serve()
    SystemOptions(episode_batches=3).validate_serve()  # fine
