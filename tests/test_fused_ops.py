"""Fused step correctness: against a numpy re-implementation, and PM-semantics
preservation (updates through replicas flow back to main copies on sync).

Reference invariant source: the fused step is a batched Push, so the same
additive-merge guarantees as test_consistency apply (handle.h:404-415).
"""
import numpy as np
import pytest

import adapm_tpu
from adapm_tpu.base import MgmtTechniques
from adapm_tpu.config import SystemOptions
from adapm_tpu.models import (complex_score, make_kge_loss, make_mf_loss,
                              sgns_loss)
from adapm_tpu.ops import DeviceRoutedRunner


def _server(num_keys, val_len, num_shards=None, **opts):
    return adapm_tpu.setup(num_keys, val_len, num_shards=num_shards,
                           opts=SystemOptions(**opts))


def numpy_adagrad(table, dim, role_keys, grads, lr, eps=1e-10):
    """The numpy reference of a fused step's write-back, with the
    *batched* semantics the step defines, in place on `table` (rows
    [emb (dim) | acc (dim)] by key): every occurrence's update is
    computed against the pre-step accumulator, then all updates (and
    grad^2 increments) merge additively — duplicate keys accumulate,
    exactly like concurrent reference Pushes (handle.h:404-415)."""
    acc = table[:, dim:].copy()
    for r, keys in role_keys.items():
        k, g = keys.ravel(), grads[r].reshape(-1, dim)
        np.add.at(table[:, :dim], k,
                  -lr * g / np.sqrt(acc[k] + g ** 2 + eps))
        np.add.at(table[:, dim:], k, g ** 2)


def test_complex_score_matches_numpy(rng):
    d = 4
    s, r, o = (rng.normal(size=(5, 2 * d)).astype(np.float32)
               for _ in range(3))
    got = np.asarray(complex_score(s, r, o))
    sc = s[:, :d] + 1j * s[:, d:]
    rc = r[:, :d] + 1j * r[:, d:]
    oc = o[:, :d] + 1j * o[:, d:]
    want = np.real((sc * rc * np.conj(oc)).sum(-1))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_fused_mf_step_matches_numpy_adagrad(rng):
    rank, nrow, ncol = 4, 6, 5
    num_keys = nrow + ncol
    srv = _server(num_keys, 2 * rank)
    w = srv.make_worker(0)

    init = rng.normal(size=(num_keys, 2 * rank)).astype(np.float32) * 0.1
    init[:, rank:] = 0.01  # adagrad accumulators start small-positive
    w.set(np.arange(num_keys), init)
    srv.block()

    i = np.array([0, 1, 2, 3], dtype=np.int64)
    j = np.array([0, 1, 0, 4], dtype=np.int64) + nrow
    x = rng.normal(size=4).astype(np.float32)
    lr, eps = 0.1, 1e-10

    runner = DeviceRoutedRunner(srv, make_mf_loss(l2=0.01),
                                role_class={"w": 0, "h": 0},
                                role_dim={"w": rank, "h": rank},
                                shard=w.shard)
    runner({"w": i, "h": j}, x, lr, eps)
    srv.block()

    want = init.copy()
    W, H = want[i, :rank], want[j, :rank]
    B = len(i)
    pred = (W * H).sum(-1)
    gw = (2 * (pred - x)[:, None] * H + 2 * 0.01 * W) / B
    gh = (2 * (pred - x)[:, None] * W + 2 * 0.01 * H) / B
    numpy_adagrad(want, rank, {"w": i, "h": j}, {"w": gw, "h": gh}, lr, eps)

    got = srv.read_main(np.arange(num_keys)).reshape(num_keys, 2 * rank)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    srv.shutdown()


def _sgns_grads_np(c, x, n):
    """Gradients of models.sgns_loss (mean over the batch of
    softplus(-c.x) + sum_n softplus(c.n)) w.r.t. its three roles' rows."""
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))  # noqa: E731
    B = len(c)
    dpos = -sig(-(c * x).sum(-1)) / B
    dneg = sig((c[:, None, :] * n).sum(-1)) / B
    return {"center": dpos[:, None] * x + (dneg[..., None] * n).sum(1),
            "ctx": dpos[:, None] * c,
            "neg": dneg[..., None] * c[:, None, :]}


def test_caller_supplied_negatives_match_numpy_adagrad(rng):
    """A runner built without `neg_role` takes a (B, N) `neg` role from
    the caller like any other role (negatives the PM sampled, say): rows
    and AdaGrad columns follow the numpy reference over 3 steps, on 2
    shards with a replica of some of the batch's keys on the worker's
    shard (the worker reads its own writes through cache + delta; after
    quiesce the main copies hold them)."""
    d, V, B, N, lr = 4, 40, 8, 3, 0.2
    srv = _server(V, 2 * d, num_shards=2, sync_max_per_sec=0,
                  techniques=MgmtTechniques.REPLICATION_ONLY,
                  cache_slots_per_shard=16)
    w0 = srv.make_worker(0)
    init = rng.normal(size=(V, 2 * d)).astype(np.float32) * 0.3
    init[:, d:] = 0.01
    w0.set(np.arange(V), init)
    remote = np.array([k for k in range(V)
                       if srv.ab.owner[k] != w0.shard][:6], dtype=np.int64)
    w0.intent(remote, 0, 100)
    srv.sync.run_round(force_intents=True, all_channels=True)
    assert srv.ab.has_replica(remote, w0.shard).all()

    roles = ("center", "ctx", "neg")
    runner = DeviceRoutedRunner(srv, sgns_loss,
                                role_class={r: 0 for r in roles},
                                role_dim={r: d for r in roles},
                                shard=w0.shard, neg_role=None)
    want = init.copy()
    for _ in range(3):
        batch = {"center": rng.integers(0, V, B).astype(np.int64),
                 "ctx": rng.integers(0, V, B).astype(np.int64),
                 "neg": rng.integers(0, V, (B, N)).astype(np.int64)}
        batch["ctx"][:len(remote)] = remote  # replica-served every step
        runner(batch, None, lr)
        grads = _sgns_grads_np(*(want[batch[r], :d] for r in roles))
        numpy_adagrad(want, d, batch, grads, lr)
    assert runner.locality_counts()["params"] == 3 * B * (2 + N)
    srv.quiesce()
    got = srv.read_main(np.arange(V)).reshape(V, 2 * d)
    assert not np.allclose(got[remote], init[remote])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    srv.shutdown()


def test_fused_mf_training_decreases_loss(rng):
    rank, nrow, ncol = 8, 16, 12
    srv = _server(nrow + ncol, 2 * rank)
    w = srv.make_worker(0)
    init = rng.normal(size=(nrow + ncol, 2 * rank)).astype(np.float32) * 0.1
    init[:, rank:] = 1e-6
    w.set(np.arange(nrow + ncol), init)

    Wt = rng.normal(size=(nrow, rank))
    Ht = rng.normal(size=(ncol, rank))
    i = rng.integers(0, nrow, 64).astype(np.int64)
    j = rng.integers(0, ncol, 64).astype(np.int64)
    x = (Wt[i] * Ht[j]).sum(-1).astype(np.float32)

    runner = DeviceRoutedRunner(srv, make_mf_loss(),
                                role_class={"w": 0, "h": 0},
                                role_dim={"w": rank, "h": rank},
                                shard=w.shard)
    losses = [float(runner({"w": i, "h": j + nrow}, x, 0.5))
              for _ in range(30)]
    assert losses[-1] < 0.5 * losses[0]
    srv.shutdown()


def test_fused_updates_flow_through_replicas(rng):
    """A fused step whose routes hit replica rows must land in the delta pool
    and reach the main copy after a sync round (batched-Push semantics)."""
    rank = 4
    srv = _server(16, 2 * rank, techniques=MgmtTechniques.REPLICATION_ONLY,
                  cache_slots_per_shard=16)
    workers = [srv.make_worker(i) for i in range(srv.num_shards)]
    w0 = workers[0]
    init = np.full((16, 2 * rank), 1.0, dtype=np.float32)
    w0.set(np.arange(16), init)
    srv.block()

    # worker 0 declares intent on keys owned elsewhere -> replicas on shard 0
    remote = np.array([k for k in range(16)
                       if srv.ab.owner[k] != w0.shard][:4], dtype=np.int64)
    w0.intent(remote, 0, 100)
    srv.sync.run_round(force_intents=True, all_channels=True)
    assert srv.ab.has_replica(remote, w0.shard).all()

    keys = remote
    x = np.zeros(len(keys) // 2, dtype=np.float32)
    runner = DeviceRoutedRunner(srv, make_mf_loss(),
                                role_class={"w": 0, "h": 0},
                                role_dim={"w": rank, "h": rank},
                                shard=w0.shard)
    runner({"w": keys[: len(keys) // 2], "h": keys[len(keys) // 2:]},
           x, 0.1)
    counts = runner.locality_counts()  # all served from replicas
    assert counts["params_local"] == counts["params"] == len(keys)

    # local read-your-writes via replica (cache+delta)
    local_view = w0.pull_sync(keys)
    assert not np.allclose(local_view[:, :rank], 1.0)

    # after quiesce the main copies converge to the local view
    srv.quiesce()
    main_view = srv.read_main(keys).reshape(len(keys), 2 * rank)
    np.testing.assert_allclose(main_view, local_view, rtol=1e-5)
    srv.shutdown()


def test_kge_and_sgns_losses_train(rng):
    d = 4
    # entities+relations same class (2d emb + 2d acc)
    srv = _server(24, 4 * d)
    w = srv.make_worker(0)
    init = rng.normal(size=(24, 4 * d)).astype(np.float32) * 0.1
    init[:, 2 * d:] = 1e-6
    w.set(np.arange(24), init)

    runner = DeviceRoutedRunner(
        srv, make_kge_loss("complex"),
        role_class={"s": 0, "r": 0, "o": 0, "neg": 0},
        role_dim={r: 2 * d for r in ("s", "r", "o", "neg")},
        shard=w.shard)
    s = rng.integers(0, 16, 8).astype(np.int64)
    r = rng.integers(16, 24, 8).astype(np.int64)
    o = rng.integers(0, 16, 8).astype(np.int64)
    neg = rng.integers(0, 16, (8, 3)).astype(np.int64)
    losses = [float(runner({"s": s, "r": r, "o": o, "neg": neg}, None, 0.3))
              for _ in range(20)]
    assert losses[-1] < losses[0]
    srv.shutdown()

    srv2 = _server(32, 2 * d)
    w2 = srv2.make_worker(0)
    init2 = rng.normal(size=(32, 2 * d)).astype(np.float32) * 0.1
    init2[:, d:] = 1e-6
    w2.set(np.arange(32), init2)
    runner2 = DeviceRoutedRunner(
        srv2, sgns_loss,
        role_class={"center": 0, "ctx": 0, "neg": 0},
        role_dim={r: d for r in ("center", "ctx", "neg")},
        shard=w2.shard)
    c = rng.integers(0, 16, 8).astype(np.int64) * 2
    ctx = rng.integers(0, 16, 8).astype(np.int64) * 2 + 1
    neg2 = rng.integers(0, 16, (8, 3)).astype(np.int64) * 2 + 1
    losses2 = [float(runner2({"center": c, "ctx": ctx, "neg": neg2},
                             None, 0.3)) for _ in range(20)]
    assert losses2[-1] < losses2[0]
    srv2.shutdown()
