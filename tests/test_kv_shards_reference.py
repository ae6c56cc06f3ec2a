"""Several kv shards, several workers, against the plain reference
(`benchmarks/reference/`: numpy float32 ComplEx + AdaGrad, which imports
nothing of the program): the semantics the four-shard deployment
`kge-wikidata5m-kv4` is held to, at a few hundred entities on four
virtual devices.

With relocation alone a key has one copy and the app dispatches its
workers one after another, so the whole run is sequential and EVERY step
of EVERY worker has to follow the reference. With replication a worker
that runs alone still reads its own writes (cache + delta), so its steps
follow too; several workers' steps interleave with each other's syncs
and have no sequential reference: for them the store's additive contract
is checked exactly."""
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

from reference import adagrad_np, complex_np  # noqa: E402

from adapm_tpu.apps import knowledge_graph_embeddings as kge  # noqa: E402

E, R, D, B, N, LR = 320, 12, 8, 32, 4, 0.1
W = 2 * D          # embedding columns of a row [re | im | AdaGrad]
# the probe's float32 limits (benchmarks/traffic/train-app-zipf.json)
LOSS_GAP, NORM_GAP, DIFF_SHARE = 2.5e-6, 5e-6, 2e-5


def _open(techniques: str, cache_slots: int = 64, entities: int = E,
          extra=()):
    args = kge.build_parser().parse_args(
        ["--dim", str(D), "--batch_size", str(B), "--neg_ratio", str(N),
         "--lr", str(LR), "--num_shards", "4", "--num_workers", "4",
         "--eval_every", "0", "--epochs", "1", "--seed", "11",
         "--synthetic_entities", str(entities),
         "--synthetic_relations", str(R),
         "--synthetic_triples", str(8 * B),
         "--sys.techniques", techniques,
         "--sys.cache_slots_per_shard", str(cache_slots),
         "--sys.main_over_alloc", "2.0", *extra])
    return kge.open_run(args)


def _draw(rng, n):
    """n triples, Zipf-ish subjects and objects so that a head is shared
    by all workers."""
    ent = lambda: np.minimum(  # noqa: E731
        rng.zipf(1.3, n) - 1, E - 1).astype(np.int64)
    return np.stack([ent(), rng.integers(0, R, n), ent()], axis=1)


class _Recorder:
    """Every dispatched step of the given runners, in dispatch order:
    what went into the compiled step (keys, the worker's local index, the
    PRNG key) and the loss that came out."""

    def __init__(self, runners):
        self.steps, self._undo = [], []
        for runner in runners:
            for name in ("step_fn", "_step_fn_norep"):
                fn = getattr(runner, name)
                self._undo.append((runner, name, fn))
                setattr(runner, name, self._wrap(fn))

    def _wrap(self, fn):
        def recorded(pools, locstat, tables, keys, local_index, alias,
                     rng_key, *rest):
            out = fn(pools, locstat, tables, keys, local_index, alias,
                     rng_key, *rest)
            idx, count = local_index
            self.steps.append({
                "keys": {r: np.asarray(k).astype(np.int64)
                         for r, k in keys.items()},
                "local": np.asarray(idx)[:int(count)].astype(np.int64),
                "cache_row": np.asarray(tables[1]),
                "rng_key": rng_key, "loss": out[2]})
            return out
        return recorded

    def remove(self):
        for runner, name, fn in self._undo:
            setattr(runner, name, fn)


def _follow(table, steps):
    """The reference's steps over its own copy of the table, in dispatch
    order; returns its losses. A step draws its negatives as the
    compiled step does: uniform positions into the worker's local
    index."""
    losses = []
    for st in steps:
        pos = np.asarray(jax.random.randint(
            st["rng_key"], (B, N), 0, len(st["local"])))
        roles = dict(st["keys"], neg=st["local"][pos])
        rows = {r: table[k] for r, k in roles.items()}
        loss, grads = complex_np.loss_and_grads(
            **{r: v[..., :W] for r, v in rows.items()}, batch_size=B)
        for r, k in roles.items():
            upd = adagrad_np.position_updates(grads[r], rows[r][..., W:],
                                              LR)
            np.add.at(table, k.ravel(), upd.reshape(-1, 2 * W))
        losses.append(loss)
    return losses


def _gaps(got, want, init):
    """Per leaf (entities, relations): the gap of the norms of the
    change, and the norm of the changes' difference, over the
    reference's norm."""
    out = []
    for leaf in (slice(0, E), slice(E, E + R)):
        p = (got[leaf] - init[leaf]).astype(np.float64)[:, :W]
        q = (want[leaf] - init[leaf]).astype(np.float64)[:, :W]
        nq = np.linalg.norm(q)
        out.append((abs(np.linalg.norm(p) - nq) / nq,
                    np.linalg.norm(p - q) / nq))
    return out


def _compare(run, rec, init):
    keys = np.arange(E + R)
    want = init.copy()
    ref = _follow(want, rec.steps)
    got = [float(st["loss"]) for st in rec.steps]
    assert max(abs(p - q) / abs(q) for p, q in zip(got, ref)) <= LOSS_GAP
    table = np.asarray(run.srv.read_main(keys)).reshape(len(keys), -1)
    for norm_gap, diff_share in _gaps(table, want, init):
        assert norm_gap <= NORM_GAP and diff_share <= DIFF_SHARE


def test_relocation_only_four_workers_follow_the_reference():
    """Three passes of fresh draws, four workers: every step of every
    worker agrees with the reference in dispatch order, and so does the
    final table; relocations happened, so it did not pass by standing
    still."""
    run = _open("relocation_only")
    try:
        srv = run.srv
        keys = np.arange(E + R)
        init = np.asarray(srv.read_main(keys)).reshape(len(keys), -1).copy()
        rec = _Recorder([run.device_runner(w.shard) for w in run.workers])
        rng = np.random.default_rng(5)
        for _ in range(3):
            run.ds.train = _draw(rng, 8 * B)
            kge.train(run)
        rec.remove()
        assert len(rec.steps) == 3 * 8
        assert srv.sync.stats.relocations > 0
        assert srv.sync.stats.replicas_created == 0
        _compare(run, rec, init)
    finally:
        run.srv.shutdown()


def _replica_positions_and_chunks(steps, chunk):
    """The host's own count over recorded steps: positions (named and
    drawn) whose key the worker's shard held a replica of at the
    dispatch, and the chunks of `chunk` they take, role by role."""
    held = chunks = 0
    for st in steps:
        pos = np.asarray(jax.random.randint(
            st["rng_key"], (B, N), 0, len(st["local"])))
        for k in dict(st["keys"], neg=st["local"][pos]).values():
            n = int((st["cache_row"][k] >= 0).sum())
            held, chunks = held + n, chunks - (-n // min(k.size, chunk))
    return held, chunks


@pytest.mark.parametrize("side_rows", [4096, 8])
def test_one_worker_beside_replicas_follows_the_reference(side_rows,
                                                          monkeypatch):
    """Techniques all, replica pools small enough to overflow: worker 0
    alone, from a quiesced table in which the other workers hold
    intents on the head (so worker 0's own intents replicate those keys
    and relocate the rest), follows the reference step by step:
    read-your-writes through cache + delta. With a side-path chunk of 8
    positions the replica positions of a role take several chunks; the
    side path's two counters read the host's own count either way."""
    from adapm_tpu.ops import fused
    monkeypatch.setattr(fused, "SIDE_ROWS", side_rows)
    run = _open("all", cache_slots=16)
    try:
        srv, w0 = run.srv, run.workers[0]
        keys = np.arange(E + R)
        head = run.ekey(np.arange(24))
        for w in run.workers[1:]:
            w.intent(head, 0, 1 << 20)
        srv.wait_sync()
        srv.quiesce()
        init = np.asarray(srv.read_main(keys)).reshape(len(keys), -1).copy()
        owner0 = srv.ab.owner.copy()
        rec = _Recorder([run.device_runner(w0.shard)])
        workers, run.workers, run.num_workers = run.workers, [w0], 1
        rng = np.random.default_rng(6)
        for _ in range(3):
            run.ds.train = _draw(rng, 4 * B)
            kge.train(run)
        run.workers, run.num_workers = workers, len(workers)
        rec.remove()
        assert len(rec.steps) == 3 * 4
        assert len(np.unique(owner0[np.concatenate(
            [k for st in rec.steps for k in st["keys"].values()])])) == 4
        st = srv.sync.stats
        assert st.relocations > 0 and st.replicas_created > 0
        assert (srv.ab.owner[keys] != owner0[keys]).any()
        _compare(run, rec, init)
        run.device_runner(w0.shard).locality_counts()  # the drain
        held, chunks = _replica_positions_and_chunks(rec.steps, side_rows)
        assert held > 12 * 8 and (chunks > 12 * 4) is (side_rows == 8)
        assert srv.obs.find("fused.replica_positions").snap() == held
        assert srv.obs.find("fused.replica_chunks").snap() == chunks
    finally:
        run.srv.shutdown()


def _grid(rng, shape):
    """float32 multiples of 1/256: sums of a few are exact in any
    order."""
    return (rng.integers(-256, 256, shape) / 256.0).astype(np.float32)


def test_four_workers_additive_read_back_is_exact_from_every_holder():
    """After a four-worker run with replication on: every worker's
    pull_sync of every key equals read_main bitwise after quiesce(); and
    a known delta pushed by EACH worker, to keys that hold replicas on
    two shards or more and to keys relocated since set-up, reads back as
    seeded + the sum of the four, exactly, from all holders."""
    run = _open("all")
    try:
        srv, workers = run.srv, run.workers
        owner0 = srv.ab.owner.copy()
        rng = np.random.default_rng(7)
        for _ in range(2):
            run.ds.train = _draw(rng, 8 * B)
            kge.train(run)
        srv.quiesce()
        ents = np.arange(E + R)
        main = np.asarray(srv.read_main(ents))
        for w in workers:
            assert np.asarray(w.pull_sync(ents)).tobytes() == main.tobytes()
        moved = ents[srv.ab.owner[ents] != owner0[ents]]
        assert len(moved) > 0
        ks = np.unique(np.concatenate([moved[:16], run.ekey(np.arange(16))]))
        L = int(srv.value_lengths[ks[0]])
        base = _grid(rng, (len(ks), L))
        workers[0].wait(workers[0].set(ks, base))
        srv.quiesce()
        for w in workers:
            w.intent(ks, w.current_clock, w.current_clock + (1 << 20))
        srv.wait_sync()
        holders = (srv.ab.cache_slot[:, ks] >= 0).sum(axis=0)
        assert (holders >= 2).sum() >= len(ks) // 2
        want = base.copy()
        for w in workers:
            delta = _grid(rng, (len(ks), L))
            w.wait(w.push(ks, delta))
            want += delta
        srv.quiesce()
        assert np.array_equal(
            np.asarray(srv.read_main(ks)).reshape(len(ks), L), want)
        for w in workers:
            assert np.array_equal(
                np.asarray(w.pull_sync(ks)).reshape(len(ks), L), want)
    finally:
        run.srv.shutdown()


@pytest.mark.parametrize("no_replicas", [False, True])
def test_shared_step_equals_the_per_shard_programs(no_replicas):
    """The step with the worker's shard as an operand gives, for each of
    the four shard values, bitwise what a program compiled with that
    shard as a constant gives (the per-shard programs this replaced):
    as one program over the global pools. The per-chip step that pools
    of four shards get (`make_device_routed_step`), the shard its
    operand too, gives the same counts, and its loss and rows inside the
    probe's limits (another program: an ulp apart on this CPU). Every
    shard holds replicas here, which the replica-free variant is never
    run beside: its per-chip form is held to the global program in
    tests/test_kv_per_chip_step.py."""
    from adapm_tpu.models.kge import make_kge_loss
    from adapm_tpu.ops import DeviceRouter, fused
    run = _open("all")
    try:
        srv = run.srv
        # replicas on every shard: the first worker to ask gets the main
        # copies, the others replicas
        for hot, askers in ((np.arange(40), [run.workers]),
                            (np.arange(40, 80),
                             [run.workers[1:], run.workers[:1]])):
            for group in askers:
                for w in group:
                    w.intent(run.ekey(hot), 0, 1 << 20)
                srv.wait_sync()
        assert (srv.ab.cache_slot >= 0).any(axis=1).all()
        roles = {"s": run.ent_class, "r": run.rel_class,
                 "o": run.ent_class, "neg": run.ent_class}
        body = fused._build_device_routed_body(
            make_kge_loss("complex", 0.0, 0.0), roles,
            {r: W for r in roles}, (), "neg", (B, N), no_replicas, False)
        shared = jax.jit(body)
        per_chip = fused.make_device_routed_step(
            make_kge_loss("complex", 0.0, 0.0), roles,
            {r: W for r in roles}, (), "neg", (B, N), no_replicas)
        rng = np.random.default_rng(8)
        put = srv.ctx.put_replicated
        keys = {"s": put(run.ekey(rng.integers(0, E, B)).astype(np.int32)),
                "r": put(run.rkey(rng.integers(0, R, B)).astype(np.int32)),
                "o": put(run.ekey(rng.integers(0, E, B)).astype(np.int32))}
        pools = tuple((s.main, s.cache, s.delta) for s in srv.stores)
        locstat = put(np.zeros(4, np.int32))
        lr, eps = np.float32(LR), np.float32(1e-10)
        for shard in range(4):
            runner = run.device_runner(shard)
            tables = DeviceRouter(srv, shard).tables()
            local_index = runner._local_neg_index()
            rest = (keys, local_index, None, jax.random.PRNGKey(shard),
                    None, lr, eps)
            per_shard = jax.jit(
                lambda pools, locstat, tables, *rest, _s=shard: body(
                    pools, locstat, tables + (_s,), *rest))
            want = per_shard(pools, locstat, tables, *rest)
            got = shared(pools, locstat, tables + (put(np.int32(shard)),),
                         *rest)
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
                assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
            if no_replicas:
                continue
            # (not donated here: a copy of the pools, which it consumes)
            got = per_chip(jax.tree_util.tree_map(lambda x: x + 0, pools),
                           locstat,
                           tables + (put(np.int32(shard)),), *rest)
            assert list(per_chip._forms) == [srv.ctx.mesh]
            assert np.array_equal(got[1], want[1])
            assert abs(float(got[2]) - float(want[2])) <= \
                LOSS_GAP * abs(float(want[2]))
            for a, b in zip(jax.tree_util.tree_leaves(got[0]),
                            jax.tree_util.tree_leaves(want[0])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=0, atol=1e-6)
    finally:
        run.srv.shutdown()


def test_four_workers_share_one_compiled_step_a_variant():
    """The workers' runners hold the same two jitted functions, and a
    pass of all four compiles each once: two step programs where the
    per-shard static made eight."""
    from jax._src import monitoring
    compiled = []

    def note(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name", "?"))

    monitoring.register_event_duration_secs_listener(note)
    # 350 entities a shard (keys go by key % 4), of which the draws can
    # move 80 out and a worker's two steps 128 in: the padded local index
    # stays at 512, so no step meets a new shape
    run = _open("all", entities=1400)
    try:
        runners = [run.device_runner(w.shard) for w in run.workers]
        assert len(run._programs) == 2
        for name in ("step_fn", "_step_fn_norep"):
            assert len({id(getattr(r, name)) for r in runners}) == 1
        assert sum(r.steps for r in runners) == 0
        run.ds.train = _draw(np.random.default_rng(9), 8 * B)
        kge.train(run)
        assert sum(r.steps for r in runners) == 8
        # open_run compiled both (precompile); the pass compiled neither
        # again: jax's compile requests, by program name
        assert [e for e in compiled if "step" in e] == \
            ["jit(step)", "jit(step)"], compiled
    finally:
        monitoring.unregister_event_duration_listener(note)
        run.srv.shutdown()


def test_precompile_leaves_the_store_and_nothing_to_compile():
    """`KgeRun.precompile()` (open_run calls it) runs every planner
    bucket and both step variants on out-of-bounds coordinates: the
    pools come back bit for bit, the RNG sequence does not move, and the
    training passes after it compile nothing."""
    from adapm_tpu.device import jaxport
    run = _open("all")
    try:
        srv = run.srv
        runner = run.device_runner(0)
        before = [np.asarray(x).copy() for st in srv.stores
                  for x in (st.main, st.cache, st.delta)]
        rng_before = np.asarray(jax.random.key_data(runner._rng)).copy()
        assert run.precompile() > 0
        after = [np.asarray(x) for st in srv.stores
                 for x in (st.main, st.cache, st.delta)]
        assert all(a.tobytes() == b.tobytes()
                   for a, b in zip(after, before))
        assert np.array_equal(
            np.asarray(jax.random.key_data(runner._rng)), rng_before)
        assert runner.locality_counts()["params"] == 0
        programs = [jaxport._relocate, jaxport._replica_create,
                    jaxport._sync_replicas, jaxport._patch_routes]
        sizes = [fn._cache_size() for fn in programs]
        rng = np.random.default_rng(10)
        for _ in range(2):
            run.ds.train = _draw(rng, 8 * B)
            kge.train(run)
        st = srv.sync.stats
        assert st.relocations > 0 and st.replicas_created > 0
        assert srv.obs.find("fused.route_patch_s").snap()["count"] > 0
        assert [fn._cache_size() for fn in programs] == sizes
    finally:
        run.srv.shutdown()


def _counters(srv):
    return {n: srv.obs.find(n).snap() for n in (
        "fused.route_refresh_total", "fused.route_upload_bytes_total",
        "fused.route_patch_total", "fused.route_patch_keys_total",
        "fused.route_patch_calls_total",
        "fused.rows_total", "fused.rows_local_total",
        "fused.rows_sampled_total",
        "fused.replica_positions", "fused.replica_chunks",
        "sync.relocations_total", "sync.replicas_created_total",
        "sync.replicas_dropped_total", "sync.keys_shipped_total",
        "sync.bytes_shipped_total")}


def test_a_run_with_relocations_and_replicas_moves_every_counter():
    run = _open("all", cache_slots=16)
    try:
        srv = run.srv
        c0 = _counters(srv)
        h0 = {n: srv.obs.find(n).snap()["count"] for n in (
            "kv.relocate_s", "kv.sync_replicas_s", "fused.route_refresh_s")}
        rng = np.random.default_rng(12)
        for _ in range(3):
            run.ds.train = _draw(rng, 8 * B)
            kge.train(run)
        for w in run.workers:      # the drain moves the row counters
            run.device_runner(w.shard).locality_counts()
        c1 = _counters(srv)
        assert all(c1[n] > c0[n] for n in c0), (c0, c1)
        assert c1["fused.rows_total"] == 3 * 8 * B * (3 + N)
        assert 0 < c1["fused.rows_local_total"] <= c1["fused.rows_total"]
        # the negatives come from the worker's own local index: local by
        # construction, and counted apart so that a share can leave them
        # out (benchmarks/layer_metrics/local_row_share.json)
        assert c1["fused.rows_sampled_total"] == 3 * 8 * B * N
        assert c1["fused.rows_local_total"] >= c1["fused.rows_sampled_total"]
        st = srv.sync.stats
        assert c1["sync.relocations_total"] == st.relocations
        assert c1["sync.replicas_created_total"] == st.replicas_created
        assert c1["sync.replicas_dropped_total"] == st.replicas_dropped
        assert c1["sync.keys_shipped_total"] == st.keys_synced
        assert c1["sync.bytes_shipped_total"] == sum(
            s.sync_bytes_shipped for s in srv.stores)
        for n, c in h0.items():
            assert srv.obs.find(n).snap()["count"] > c, n
        # one observation for each refresh of a mirror or a local index
        assert srv.obs.find("fused.route_refresh_s").snap()["count"] == \
            c1["fused.route_refresh_total"]
        # and every one of them a patch by the journal's keys, but each
        # runner's first (its mirrors and its index, built from the
        # tables at set-up or at its first step)
        assert c1["fused.route_refresh_total"] \
            - c1["fused.route_patch_total"] == 2 * len(run.workers)
    finally:
        run.srv.shutdown()


def test_a_placement_change_uploads_entries_and_not_tables(monkeypatch):
    """At 30,000 entities a step's placement change ships the patch's
    operand and, where it changed, the local index: under a sixth of
    the three tables and the index that a rebuild uploads (to each of
    the four devices), which is what every step paid before the
    journal."""
    from adapm_tpu.ops import fused
    monkeypatch.setattr(fused, "PATCH_KEYS", 256)
    run = _open("all", cache_slots=16, entities=30000,
                extra=("--sys.prefetch", "0"))   # a round after each step
    try:
        srv = run.srv
        rng = np.random.default_rng(13)
        run.ds.train = _draw(rng, 8 * B)
        kge.train(run)           # every runner's first build is behind
        c0 = _counters(srv)
        steps0 = srv.obs.find("fused.dispatch_s").snap()["count"]
        for _ in range(2):
            run.ds.train = _draw(rng, 8 * B)
            kge.train(run)
        c1 = _counters(srv)
        steps = srv.obs.find("fused.dispatch_s").snap()["count"] - steps0
        grew = {n: c1[n] - c0[n] for n in c0}
        assert steps == 16 and grew["sync.relocations_total"] > 0
        # a step's worker finds placement changed: its mirrors and its
        # index are refreshed, and both by patches
        assert grew["fused.route_patch_total"] == \
            grew["fused.route_refresh_total"] == 2 * steps
        # and the mirrors' by ONE call of the program, whatever changed
        assert grew["fused.route_patch_calls_total"] == steps
        index, _ = run.device_runner(0)._local_neg_index()
        rebuilt = 4 * 4 * (3 * srv.num_keys + len(index))
        assert grew["fused.route_upload_bytes_total"] / steps < rebuilt / 6
    finally:
        run.srv.shutdown()


def test_patched_run_is_bitwise_the_run_with_every_refresh_rebuilt(
        monkeypatch):
    """The same seeded four-worker run twice, relocations and replicas
    in both: once as shipped, once with the journal answering nothing
    (every refresh the full rebuild): the same losses, local indexes
    and pools, bit for bit. The planner's rounds run on the training
    thread, one after every step, as in the four-chip cell (with the
    prefetch pipeline they run at their own pace, and no two runs are
    the same)."""
    from adapm_tpu.ops import fused

    def outcome(patched):
        if not patched:
            monkeypatch.setattr(fused, "_changed_keys", lambda *a: None)
        run = _open("all", cache_slots=16, extra=("--sys.prefetch", "0"))
        try:
            srv = run.srv
            rec = _Recorder([run.device_runner(w.shard)
                             for w in run.workers])
            rng = np.random.default_rng(14)
            for _ in range(3):
                run.ds.train = _draw(rng, 8 * B)
                kge.train(run)
            rec.remove()
            st = srv.sync.stats
            assert st.relocations > 0 and st.replicas_created > 0
            patches = srv.obs.find("fused.route_patch_total").snap()
            assert (patches > 3 * 8) if patched else patches == 0
            losses = np.array([np.asarray(s["loss"]) for s in rec.steps])
            pools = [np.asarray(p) for st in srv.stores
                     for p in (st.main, st.cache, st.delta)]
            return losses, pools, [s["local"] for s in rec.steps]
        finally:
            run.srv.shutdown()

    got, want = outcome(True), outcome(False)
    assert np.array_equal(got[0], want[0])
    for a, b in zip(got[1], want[1]):
        assert np.array_equal(a, b)
    for a, b in zip(got[2], want[2]):
        assert np.array_equal(a, b)


def test_one_shard_never_refreshes_its_routes_after_set_up():
    args = kge.build_parser().parse_args(
        ["--dim", str(D), "--batch_size", str(B), "--neg_ratio", str(N),
         "--num_shards", "1", "--num_workers", "1", "--eval_every", "0",
         "--epochs", "1", "--synthetic_entities", str(E),
         "--synthetic_relations", str(R), "--synthetic_triples", str(4 * B)])
    run = kge.open_run(args)
    try:
        srv = run.srv
        assert run.precompile() == 0     # one shard: no planner program
        kge.train(run)
        at_set_up = srv.obs.find("fused.route_refresh_total").snap()
        assert at_set_up == 2            # the mirrors and the local index
        kge.train(run)
        assert srv.obs.find("fused.route_refresh_total").snap() == at_set_up
        assert srv.obs.find("fused.route_patch_total").snap() == 0
        assert srv.obs.find("sync.relocations_total").snap() == 0
        assert len(run._programs) == 2
    finally:
        run.srv.shutdown()
