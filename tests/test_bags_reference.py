"""The serving side of the CTR app (`apps/ctr.py CtrServe` / `open_serve`:
the tables' rows alone behind a `ServePlane`, requests through
`ServeSession.lookup_bags`) against the plain reference
(`benchmarks/reference/bags_np.py`: numpy float32, a bag the sum of its
members' seeded rows in member order, imports nothing of the program), at
a few hundred keys and dim 8: on each of the three paths the batcher can
take, with coalesced batches of several requests of unequal sizes; the
shares of a row-wise sharded table adding up to the whole; the plane's
`precompile_bags`; and the batch bound."""
import os
import sys
import threading

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

from reference import bags_np  # noqa: E402

from adapm_tpu.apps import ctr  # noqa: E402

ROWS, HOT = [96, 64, 1, 160, 48], [3, 2, 1, 4, 1]
DIM, SCALE, SEED = 8, 0.0625, 2 ** 32 + 11
FAST = ["--sys.sync.max_per_sec", "0", "--sys.prefetch", "0",
        "--sys.cache_slots_per_shard", "1"]


def _serve(*extra, samples="2,9", max_batch=4):
    """A `CtrServe` whose rows are the reference's seeded rows, its plane
    open (the bag programs compiled)."""
    join = lambda xs: ",".join(map(str, xs))  # noqa: E731
    serve = ctr.CtrServe(ctr.build_parser().parse_args(
        ["--table_rows", join(ROWS), "--multi_hot_sizes", join(HOT),
         "--embedding_dim", str(DIM), "--serve_samples", samples,
         "--num_shards", "1", "--sys.serve.max_batch", str(max_batch),
         "--sys.serve.max_wait_us", "20000"] + FAST + list(extra)))
    keys = np.arange(serve.n_feat)
    w0 = serve.workers[0]
    w0.wait(w0.set(keys, bags_np.seeded_rows(keys, DIM, SCALE, SEED)))
    serve.open_plane()
    return serve


def _request(serve, rng, samples: int, repeat: bool = False):
    """`lookup_bags`' arguments of a request of `samples` samples: ids
    uniform over each table's rows; with `repeat` every bag holds ONE
    id as often as it has members."""
    members = np.concatenate(
        [rng.integers(0, r, (samples, 1 if repeat else h)).repeat(
            h if repeat else 1, axis=1)
         for r, h in zip(serve.table_rows, HOT)], axis=1)
    return serve.bag_args(serve.feat_keys(members))


def _differ(got, tables, bags) -> int:
    """Pooled vectors that are not the reference's, bit for bit."""
    ref = bags_np.reply(tables, bags, DIM, SCALE, SEED)
    assert [g.shape for g in got] == [w.shape for w, _ in ref]
    return int(sum((np.asarray(g) != w).any(axis=1).sum()
                   for g, (w, _) in zip(got, ref)))


def _together(serve, requests):
    """The requests' replies, issued by as many client threads at once,
    so that the batcher coalesces them (its window is 20 ms here)."""
    sessions = [serve.plane.session() for _ in requests]
    out = [None] * len(requests)
    gate = threading.Barrier(len(requests))

    def client(i):
        gate.wait(timeout=30)
        out[i] = sessions[i].lookup_bags(*requests[i], pooling="sum",
                                         deadline_ms=60_000)
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(len(requests))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    return out


def _count(serve, name: str) -> float:
    return serve.srv.obs.find(name).value


def test_bag_args_are_the_training_layout():
    """A request's `[members, S]` keys become 26-table arguments whose
    bag s of table t holds rows member_at[t]:member_at[t + 1] of column
    s, in member order."""
    serve = _serve()
    try:
        rng = np.random.default_rng(0)
        members = np.concatenate(
            [rng.integers(0, r, (5, h)) for r, h in zip(ROWS, HOT)], axis=1)
        keys = serve.feat_keys(members)
        assert keys.shape == (sum(HOT), 5)
        tables, bags = serve.bag_args(keys)
        at = np.concatenate([[0], np.cumsum(HOT)])
        for t, (ks, bg) in enumerate(zip(tables, bags)):
            assert bg.tolist() == [HOT[t] * s for s in range(6)]
            for s in range(5):
                assert ks[bg[s]:bg[s + 1]].tolist() == \
                    keys[at[t]:at[t + 1], s].tolist()
            first = serve.table_first
            assert ((ks >= first[t]) & (ks < first[t + 1])).all()
    finally:
        serve.close()


@pytest.mark.parametrize("path", ["fused", "hostpool", "replica"])
def test_lookup_bags_is_the_reference_on_every_path(path):
    """Coalesced batches of requests of unequal sizes (one with repeated
    members), bitwise, and the path counter says which path ran."""
    extra = {"fused": [], "hostpool": ["--sys.serve.bags", "0"],
             "replica": ["--sys.serve.replica_rows", "512",
                         "--sys.serve.replica_refresh_ms", "1.0"]}[path]
    serve = _serve(*extra)
    try:
        rng = np.random.default_rng(1)
        reqs = [_request(serve, rng, 9), _request(serve, rng, 2),
                _request(serve, rng, 5, repeat=True), _request(serve, rng, 7)]
        if path == "replica":
            # the snapshot covers what serve load has touched
            sess = serve.plane.session()
            sess.lookup(np.arange(serve.n_feat))
            assert serve.plane.replica.refresh_now() > 0
        b0 = _count(serve, "serve.bag_batches_total")
        for got, (tables, bags) in zip(_together(serve, reqs), reqs):
            assert _differ(got, tables, bags) == 0
        batches = _count(serve, "serve.bag_batches_total") - b0
        assert 1 <= batches < len(reqs)      # some were coalesced
        want = {"fused": "serve.bag_fused_total",
                "hostpool": "serve.bag_hostpool_total",
                "replica": "serve.bag_replica_hits_total"}[path]
        assert _count(serve, want) == batches
        if path != "fused":
            assert _count(serve, "serve.bag_fused_total") == 0
        # what the batches carried, as asked for
        members = serve.srv.obs.find("serve.bag_batch_members").snap()
        assert members["sum"] == sum(HOT) * (9 + 2 + 5 + 7)
        bags_h = serve.srv.obs.find("serve.bag_batch_bags").snap()
        assert bags_h["sum"] == len(HOT) * (9 + 2 + 5 + 7)
    finally:
        serve.close()


def test_a_dropped_member_and_a_shifted_offset_are_seen():
    """The comparison itself: a member left out of one bag, and offsets
    shifted by one, each change pooled vectors."""
    serve = _serve()
    try:
        tables, bags = _request(serve, np.random.default_rng(2), 6)
        sess = serve.plane.session()
        assert _differ(sess.lookup_bags(tables, bags), tables, bags) == 0
        t = int(np.argmax(HOT))
        short = [ks.copy() for ks in tables]
        short[t] = np.delete(short[t], 1)
        cut = [bg.copy() for bg in bags]
        cut[t][1:] -= 1
        got = sess.lookup_bags(short, cut)
        assert _differ(got, tables, bags) >= 1
        shifted = [bg.copy() for bg in bags]
        shifted[t][1:-1] += 1
        got = sess.lookup_bags(tables, shifted)
        assert _differ(got, tables, bags) >= 2
    finally:
        serve.close()


@pytest.mark.parametrize("shards, tier", [
    (8, []),        # dlrm-dcnv2-criteo1tb-serve: a v5e-8 host, all in HBM
    # dlrm-dcnv2-criteo1tb-serve-tier: a v5e-4 host, 40% of a share hot
    (4, ["--sys.tier", "1", "--sys.tier.hot_rows", "16"])])
def test_the_shares_of_a_row_wise_sharded_table_add_up(shards, tier):
    """The deployment's cut: `shards` shards hold ceil(rows / shards)
    rows of every table each (tiered: the most of them in the host cold
    store); a bag's members on shard k pooled there, the partial sums
    added, give what the reference gives over the whole table, within
    the bound a float32 sum in another order keeps (exactly for bags of
    one member)."""
    whole = [50, 17, 3, 80, 8]
    share = [-(-r // shards) for r in whole]
    rng = np.random.default_rng(3)
    S = 6
    ids = [rng.integers(0, r, (S, h)) for r, h in zip(whole, HOT)]
    first = np.concatenate([[0], np.cumsum(whole)])
    # the reference over the whole tables, keys in the uncut layout
    bags = [np.arange(S + 1) * h for h in HOT]
    ref = bags_np.reply([i.ravel() + first[t] for t, i in enumerate(ids)],
                        bags, DIM, SCALE, SEED)
    total = [np.zeros((S, DIM), np.float32) for _ in HOT]
    for k in range(shards):
        serve = ctr.CtrServe(ctr.build_parser().parse_args(
            ["--table_rows", ",".join(map(str, share)),
             "--multi_hot_sizes", ",".join(map(str, HOT)),
             "--embedding_dim", str(DIM), "--serve_samples", "1,8",
             "--num_shards", "1", "--sys.serve.max_batch", "1"] + FAST
            + tier))
        try:
            # shard k's row j of table t is the whole table's row
            # k * share + j (rows past the table's end hold nothing)
            w0 = serve.workers[0]
            for t, r in enumerate(whole):
                rows = np.arange(k * share[t], min((k + 1) * share[t], r))
                if len(rows):
                    w0.wait(w0.set(
                        serve.table_first[t] + rows - k * share[t],
                        bags_np.seeded_rows(first[t] + rows, DIM, SCALE,
                                            SEED)))
            serve.open_plane()
            tables, offs = [], []
            for t, i in enumerate(ids):
                here = (i // share[t]) == k
                tables.append(serve.table_first[t]
                              + (i[here] - k * share[t]))
                offs.append(np.concatenate(
                    [[0], np.cumsum(here.sum(axis=1))]))
            keep = [t for t in range(len(HOT)) if len(tables[t])]
            got = serve.plane.session().lookup_bags(
                [tables[t] for t in keep], [offs[t] for t in keep])
            for t, g in zip(keep, got):
                total[t] += g
        finally:
            serve.close()
    for t, (want, mag) in enumerate(ref):
        room = bags_np.order_bound(mag, bags[t])
        assert (np.abs(total[t] - want) <= room).all(), t
        if HOT[t] == 1:
            assert np.array_equal(total[t], want)


def test_precompile_bags_compiles_every_bucket_before_traffic():
    """`open_plane` runs one `_gather_pool` program a (members, bags)
    bucket pair that a batch of 1..max_batch requests can fall in, and
    traffic then compiles nothing."""
    import jax.monitoring as mon
    from adapm_tpu.core.store import bucket_size
    serve = _serve(samples="2,9", max_batch=4)
    try:
        M, T, least = sum(HOT), len(HOT), serve.srv.stores[0].bucket_min
        want = {(bucket_size(M * s, least), bucket_size(T * s, least))
                for s in range(2, 4 * 9 + 1)}
        assert len(want) >= 4
        # once more, counted: every pair runs, nothing compiles
        compiled = []
        mon.register_event_duration_secs_listener(
            lambda event, secs, **kw: compiled.append(kw.get("fun_name"))
            if event == "/jax/core/compile/backend_compile_duration"
            else None)
        ran = serve.plane.precompile_bags(
            (M * s, T * s) for s in range(2, 4 * 9 + 1))
        assert ran == len(want)
        rng = np.random.default_rng(4)
        reqs = [_request(serve, rng, s) for s in (9, 9, 9, 9)]
        for got, (tables, bags) in zip(_together(serve, reqs), reqs):
            assert _differ(got, tables, bags) == 0
        for s in (2, 3, 5, 8):
            tables, bags = _request(serve, rng, s)
            got = serve.plane.session().lookup_bags(tables, bags)
            assert _differ(got, tables, bags) == 0
        assert "_gather_pool" not in compiled, compiled
    finally:
        serve.close()


def test_a_bag_batch_stays_inside_serve_max_batch():
    """The deployment's batch bound: with `--sys.serve.max_batch 2`, six
    requests issued at once are served in batches of at most two
    requests and as many members as two of the largest hold."""
    serve = _serve(samples="2,9", max_batch=2)
    try:
        rng = np.random.default_rng(5)
        reqs = [_request(serve, rng, 9) for _ in range(6)]
        for got, (tables, bags) in zip(_together(serve, reqs), reqs):
            assert _differ(got, tables, bags) == 0
        assert serve.srv.obs.find("serve.batch_size").snap()["max"] <= 2
        members = serve.srv.obs.find("serve.bag_batch_members").snap()
        assert members["max"] <= 2 * 9 * sum(HOT)
        assert members["count"] >= 3
    finally:
        serve.close()


def test_open_serve_initialises_the_embedding_half_alone():
    """`open_serve`: rows of `--embedding_dim` floats, uniform in
    +-init_scale, the same draws `CtrRun.init_model` gives the embedding
    half of a training row; the plane is open and serves."""
    join = lambda xs: ",".join(map(str, xs))  # noqa: E731
    argv = ["--table_rows", join(ROWS), "--multi_hot_sizes", join(HOT),
            "--embedding_dim", str(DIM), "--serve_samples", "2,4",
            "--num_shards", "1", "--sys.serve.max_batch", "2",
            "--seed", "7"] + FAST
    serve = ctr.open_serve(ctr.build_parser().parse_args(argv))
    try:
        n = serve.n_feat
        rows = np.asarray(serve.srv.read_main(np.arange(n))).reshape(n, DIM)
        want = (np.random.default_rng(7).random((n, DIM), dtype=np.float32)
                - 0.5) * (2 * 0.0625)
        assert np.array_equal(rows, want)
        tables, bags = _request(serve, np.random.default_rng(6), 3)
        got = serve.plane.session().lookup_bags(tables, bags)
        for g, ks, bg in zip(got, tables, bags):
            assert np.array_equal(g, bags_np.pool(rows[ks], bg)[0])
    finally:
        serve.close()
