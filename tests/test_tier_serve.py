"""A TIERED embedding shard behind the serve plane (`apps/ctr.py CtrServe`
with `--sys.tier 1`: the hottest rows in the device pool, the rest in the
host cold store, the maintenance worker moving rows between requests)
against the plain reference (`benchmarks/reference/bags_np.py`), at a few
hundred keys and dim 8: every reply bitwise the untiered store's, on each
of the batcher's three paths, in five residency states; residency's
invariants after a storm; what a maintenance pass costs (rows examined
against rows moved: a count, not a stopwatch) and that a clean victim is
demoted without a readback."""
import threading

import numpy as np
import pytest

import adapm_tpu
from adapm_tpu.apps import ctr
from adapm_tpu.config import SystemOptions
from adapm_tpu.tier import promote

from test_bags_reference import (DIM, FAST, HOT, ROWS, SCALE, SEED,  # noqa
                                 _request, _together, bags_np)

N = sum(ROWS)
HOT_ROWS = 152          # 41% of the 369 keys (the store rounds to 8s)
PATHS = {"fused": [], "hostpool": ["--sys.serve.bags", "0"],
         "replica": ["--sys.serve.replica_rows", "512",
                     "--sys.serve.replica_refresh_ms", "1.0"]}


def _tiered(*extra, hot_rows=HOT_ROWS, worker=False):
    """A tiered `CtrServe` holding the reference's seeded rows, every
    row cold, its plane open; the maintenance worker is held still
    unless `worker` (tests/test_tier.py's way: `engine.kick` replaced on
    the instance), so a test decides the residency."""
    join = lambda xs: ",".join(map(str, xs))  # noqa: E731
    serve = ctr.CtrServe(ctr.build_parser().parse_args(
        ["--table_rows", join(ROWS), "--multi_hot_sizes", join(HOT),
         "--embedding_dim", str(DIM), "--serve_samples", "2,9",
         "--num_shards", "1", "--sys.serve.max_batch", "4",
         "--sys.serve.max_wait_us", "20000", "--sys.tier", "1",
         "--sys.tier.hot_rows", str(hot_rows)] + FAST + list(extra)))
    tier = serve.srv.tier
    kick = tier.engine.kick
    tier.engine.kick = lambda: None
    keys = np.arange(N)
    w0 = serve.workers[0]
    w0.wait(w0.set(keys, bags_np.seeded_rows(keys, DIM, SCALE, SEED)))
    for st in serve.srv.stores:
        st.res.want.clear()
    serve.open_plane()
    if worker:
        tier.engine.kick = kick
    return serve


def _hot_keys(serve) -> np.ndarray:
    srv = serve.srv
    keys = np.arange(N)
    return keys[srv.stores[0].res.dev_row[srv.ab.owner[keys],
                                          srv.ab.slot[keys]] >= 0]


def _differ(got, tables, bags, pushed=None) -> int:
    """Pooled vectors that are not the reference's over the seeded rows
    (plus `pushed`: key -> what was added to its row), bit for bit."""
    bad = 0
    for g, ks, bg in zip(got, tables, bags):
        rows = bags_np.seeded_rows(ks, DIM, SCALE, SEED)
        for k, d in (pushed or {}).items():
            rows[np.asarray(ks) == k] += d
        want = bags_np.pool(rows, bg)[0]
        assert np.asarray(g).shape == want.shape
        bad += int((np.asarray(g) != want).any(axis=1).sum())
    return bad


def _invariants(serve, pushed=None) -> None:
    """`dev_row` and `row_slot` inverse to each other, the hot pool
    within its bound, every key's main copy its seeded row plus its
    acknowledged pushes whichever tier holds it."""
    srv = serve.srv
    with srv._lock:     # a live worker moves rows under it
        for st in srv.stores:
            res = st.res
            for s in range(res.num_shards):
                slots = np.nonzero(res.dev_row[s] >= 0)[0]
                rows = np.nonzero(res.row_slot[s] >= 0)[0]
                assert len(slots) == len(rows) == res.hot_count(s)
                assert res.hot_count(s) <= res.hot_rows
                assert (res.row_slot[s, res.dev_row[s, slots]]
                        == slots).all()
                assert (res.dev_row[s, res.row_slot[s, rows]]
                        == rows).all()
    keys = np.arange(N)
    want = bags_np.seeded_rows(keys, DIM, SCALE, SEED)
    for k, d in (pushed or {}).items():
        want[k] += d
    got = np.asarray(srv.read_main(keys)).reshape(N, DIM)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("state", ["all_hot", "all_cold", "mixed",
                                   "worker_live", "pushed"])
@pytest.mark.parametrize("path", sorted(PATHS))
def test_tiered_lookup_bags_is_the_reference(path, state):
    """Coalesced batches of requests of unequal sizes (one with repeated
    members), bitwise, whichever tier holds each member and whatever
    the worker moves while the batches are read."""
    serve = _tiered(*PATHS[path],
                    hot_rows=N + 8 if state == "all_hot" else HOT_ROWS,
                    worker=state == "worker_live")
    try:
        srv, tier = serve.srv, serve.srv.tier
        rng = np.random.default_rng(11)
        keys = np.arange(N)
        pushed = {}
        if state == "all_hot":
            assert tier.promote_keys(keys) == N
        elif state != "all_cold":
            tier.promote_keys(rng.choice(N, HOT_ROWS - 16, replace=False))
        if state == "pushed":
            hot = _hot_keys(serve)
            cold = np.setdiff1d(keys, hot)
            for k in (int(hot[3]), int(cold[5])):
                pushed[k] = rng.uniform(-1, 1, DIM).astype(np.float32)
            w0 = serve.workers[0]
            ks = np.asarray(sorted(pushed))
            w0.wait(w0.push(ks, np.stack([pushed[k] for k in ks])))
        reqs = [_request(serve, rng, 9), _request(serve, rng, 2),
                _request(serve, rng, 5, repeat=True),
                _request(serve, rng, 7)]
        if pushed:      # one request names both pushed keys
            for k in pushed:
                t = int(np.searchsorted(serve.table_first, k, "right")) - 1
                reqs[0][0][t][0] = k
        if path == "replica":
            # the snapshot covers what serve load has touched
            serve.plane.session().lookup(keys)
            assert serve.plane.replica.refresh_now() > 0
        stop = threading.Event()

        def churn():
            r = np.random.default_rng(12)
            while not stop.is_set():
                tier.demote_keys(r.choice(N, 24, replace=False))
                tier.promote_keys(r.choice(N, 24, replace=False))
                tier.maintain()
        mover = threading.Thread(target=churn, daemon=True)
        if state == "worker_live":
            mover.start()
        try:
            for _ in range(3 if state == "worker_live" else 1):
                for got, (tables, bags) in zip(_together(serve, reqs),
                                               reqs):
                    assert _differ(got, tables, bags, pushed) == 0
        finally:
            stop.set()
            if mover.is_alive():
                mover.join(timeout=60)
        if state == "all_hot":
            assert len(_hot_keys(serve)) == N
        if state == "all_cold" and path != "replica":
            # nothing moved (the worker stands still) and every member
            # was answered from the cold store
            assert len(_hot_keys(serve)) == 0
            assert srv.stores[0].tier_cold_hits > 0
        _invariants(serve, pushed)
    finally:
        serve.close()


def test_init_model_fills_a_tiered_store():
    """`open_serve` with the two flags: the app's own initialisation
    lands every row (in whichever tier) and the plane serves them."""
    join = lambda xs: ",".join(map(str, xs))  # noqa: E731
    serve = ctr.open_serve(ctr.build_parser().parse_args(
        ["--table_rows", join(ROWS), "--multi_hot_sizes", join(HOT),
         "--embedding_dim", str(DIM), "--serve_samples", "2,4",
         "--num_shards", "1", "--sys.serve.max_batch", "2", "--seed", "7",
         "--sys.tier", "1", "--sys.tier.hot_rows", str(HOT_ROWS)] + FAST))
    try:
        rows = np.asarray(serve.srv.read_main(np.arange(N))).reshape(N, DIM)
        want = (np.random.default_rng(7).random((N, DIM), dtype=np.float32)
                - 0.5) * (2 * 0.0625)
        assert np.array_equal(rows, want)
        tables, bags = _request(serve, np.random.default_rng(6), 3)
        got = serve.plane.session().lookup_bags(tables, bags)
        for g, ks, bg in zip(got, tables, bags):
            assert np.array_equal(g, bags_np.pool(rows[ks], bg)[0])
        assert 0 < len(_hot_keys(serve)) <= HOT_ROWS
    finally:
        serve.close()


def test_residency_invariants_after_a_storm():
    """Requests, pushes, promotions, demotions and maintenance passes
    interleaved, the worker live: the maps stay inverse, the pool
    within its bound, and every key reads its seeded row plus its
    acknowledged pushes."""
    serve = _tiered(worker=True)
    try:
        tier, w0 = serve.srv.tier, serve.workers[0]
        rng = np.random.default_rng(21)
        sess = serve.plane.session()
        total = np.zeros((N, DIM), np.float32)
        for step in range(40):
            op = rng.integers(0, 5)
            if op == 0:
                tables, bags = _request(serve, rng, int(rng.integers(2, 9)))
                got = sess.lookup_bags(tables, bags)
                pushed = {int(k): total[k] for k in
                          np.nonzero(total.any(axis=1))[0]}
                assert _differ(got, tables, bags, pushed) == 0
            elif op == 1:
                # one push a key: total[k] is then the exact float32
                # sum the store holds (a second push would round twice)
                ks = rng.choice(np.nonzero(~total.any(axis=1))[0], 6,
                                replace=False)
                v = rng.uniform(-1, 1, (6, DIM)).astype(np.float32)
                w0.wait(w0.push(ks, v))
                total[ks] = v
            elif op == 2:
                tier.promote_keys(rng.choice(N, 40, replace=False))
            elif op == 3:
                tier.demote_keys(rng.choice(N, 40, replace=False))
            else:
                tier.maintain()
        _invariants(serve, {int(k): total[k] for k in
                            np.nonzero(total.any(axis=1))[0]})
    finally:
        serve.close()


# ---------------------------------------------------------------------------
# what a pass costs
# ---------------------------------------------------------------------------

E, L, POOL = 40_000, 8, 16_384


def _big(**kw):
    """One shard, 40,000 keys, a hot pool of 16,384 rows, FULL: the first
    16,384 keys hot, the worker held still."""
    srv = adapm_tpu.setup(E, L, opts=SystemOptions(
        sync_max_per_sec=0, prefetch=False, tier=True, tier_hot_rows=POOL,
        **kw), num_shards=1)
    srv.tier.engine.kick = lambda: None
    w = srv.make_worker(0)
    vals = np.random.default_rng(5).normal(size=(E, L)).astype(np.float32)
    w.wait(w.set(np.arange(E), vals))
    srv.stores[0].res.want.clear()
    srv.stores[0].res.score[:] = 0
    assert srv.tier.promote_keys(np.arange(POOL)) == POOL
    return srv, w, vals


def test_a_pass_examines_what_it_moves_not_the_pool():
    """Victim selection over a full pool of 16,384 rows: a pass examines
    at most `_VICTIM_FANOUT` resident rows a row it moves (promotions
    and demotions, the headroom's among them) plus one floor window a
    call, not the pool a call (the parent's scan: 16,384 rows for each
    commit chunk and once more for the headroom, whatever moved); the
    anti-thrash law stands (past free capacity a candidate enters only
    over a STRICTLY lower-scored resident)."""
    srv, w, vals = _big()
    try:
        st = srv.stores[0]
        res, ab, tier = st.res, srv.ab, srv.tier
        fan, floor = promote._VICTIM_FANOUT, promote._VICTIM_WINDOW_MIN
        chunk = 4 * srv.opts.tier_demote_batch

        def moved():
            return tier.c_promotions.snap() + tier.c_demotions.snap()
        for n in (10, 600, 5000):
            cold = np.arange(POOL, E)
            cold = cold[res.dev_row[0, ab.slot[cold]] < 0][:n]
            assert len(cold) == n
            # touched once: score 1 beats the residents' 0
            res.touch(ab.owner[cold], ab.slot[cold])
            res.request_promote(ab.owner[cold], ab.slot[cold])
            seen0, moved0, up0 = res.victim_rows_examined, moved(), \
                tier.c_promotions.snap()
            tier.maintain()
            seen = res.victim_rows_examined - seen0
            assert tier.c_promotions.snap() - up0 == n
            assert (res.dev_row[0, ab.slot[cold]] >= 0).all()
            calls = -(-n // chunk) + 1      # commit chunks + the headroom
            assert 0 < seen <= fan * (moved() - moved0) + floor * calls, \
                (n, seen, moved() - moved0)
            assert seen < POOL * calls
        # the headroom is spent: equal scores never churn (untouched
        # candidates, score 0, move nothing over residents of score 0)
        spend = np.arange(POOL, E)
        spend = spend[res.dev_row[0, ab.slot[spend]] < 0]
        with srv._lock:
            promote.promote_rows(st, 0, ab.slot[spend])
        assert res.alloc.num_free(0) == 0
        idle = spend[res.dev_row[0, ab.slot[spend]] < 0][:64]
        res.score[:] = 0
        with srv._lock:
            n = promote.ensure_hot_rows(srv, st, ab.owner[idle],
                                        ab.slot[idle])
        assert n == 0 and (res.dev_row[0, ab.slot[idle]] < 0).all()
        assert np.array_equal(
            np.asarray(srv.read_main(np.arange(E))).reshape(E, L), vals)
    finally:
        srv.shutdown()


def test_a_clean_victim_is_demoted_without_a_readback():
    """A row not written since its promotion is demoted by dropping its
    device row; a pushed-to row is read back. Values bitwise unchanged
    either way."""
    srv, w, vals = _big()
    try:
        st = srv.stores[0]
        res = st.res
        reads = []
        real = st.read_hot_rows_at
        st.read_hot_rows_at = lambda sh, row: (reads.append(len(row)),
                                               real(sh, row))[1]
        clean, dirty = np.arange(0, 300), np.arange(300, 340)
        delta = np.random.default_rng(6).normal(
            size=(len(dirty), L)).astype(np.float32)
        w.wait(w.push(dirty, delta))
        vals[dirty] += delta
        assert srv.tier.demote_keys(clean) == len(clean)
        assert reads == [] and res.clean_demotions == len(clean)
        assert srv.tier.demote_keys(dirty) == len(dirty)
        assert reads == [len(dirty)]
        assert res.clean_demotions == len(clean)
        # a push to a COLD row lands in the cold store: promoted after
        # it, the row is clean again
        w.wait(w.push(clean[:8], delta[:8]))
        vals[clean[:8]] += delta[:8]
        srv.tier.promote_keys(clean[:8])
        assert srv.tier.demote_keys(clean[:8]) == 8
        assert reads == [len(dirty)]
        # mixed victims: only the written ones cross
        srv.tier.promote_keys(np.concatenate([clean[:50], dirty[:5]]))
        w.wait(w.push(dirty[:5], delta[:5]))
        vals[dirty[:5]] += delta[:5]
        assert srv.tier.demote_keys(
            np.concatenate([clean[:50], dirty[:5]])) == 55
        assert reads == [len(dirty), 5]
        assert np.array_equal(
            np.asarray(srv.read_main(np.arange(E))).reshape(E, L), vals)
        st.read_hot_rows_at = real
        snap = srv.metrics_snapshot()["tier"]
        assert snap["clean_demotions"] == res.clean_demotions
        assert snap["victim_rows_examined"] == res.victim_rows_examined
    finally:
        srv.shutdown()


def test_precompile_leaves_the_tier_nothing_to_compile():
    """`open_plane` on a tiered store runs both twins of every bag
    bucket and the worker's promotion and demotion programs at every
    chunk bucket: cold reads, promotions and demotions of written rows
    then compile nothing."""
    import jax.monitoring as mon
    serve = _tiered()
    try:
        srv, tier = serve.srv, serve.srv.tier
        compiled = []
        mon.register_event_duration_secs_listener(
            lambda event, secs, **kw: compiled.append(kw.get("fun_name"))
            if event == "/jax/core/compile/backend_compile_duration"
            else None)
        rng = np.random.default_rng(4)
        sess = serve.plane.session()
        for s in (2, 3, 5, 9):              # all cold: the cold twin
            tables, bags = _request(serve, rng, s)
            assert _differ(sess.lookup_bags(tables, bags),
                           tables, bags) == 0
        w0 = serve.workers[0]
        for n in (3, 20, 100):
            ks = rng.choice(N, n, replace=False)
            tier.promote_keys(ks)
            srv.stores[0].main_epoch[srv.ab.owner[ks],
                                     srv.ab.slot[ks]] += 1   # "written"
            tier.demote_keys(ks)
        tier.promote_keys(np.arange(N)[:HOT_ROWS])
        hot = _hot_keys(serve)
        tables, bags = _request(serve, rng, 4)
        tables = [hot[ks % len(hot)] for ks in tables]   # the plain twin
        got = sess.lookup_bags(tables, bags)
        assert _differ(got, tables, bags) == 0
        assert w0 is not None
        bad = [f for f in compiled if f and (
            "gather_pool" in f or "write_main_rows" in f
            or "read_rows_at" in f)]
        assert not bad, bad
    finally:
        serve.close()


# ---------------------------------------------------------------------------
# the kept staging buffers
# ---------------------------------------------------------------------------


def _bag_read(srv, keys, bag_of, nbags):
    """`ShardedStore.gather_pool` of `keys` (member i in bag
    `bag_of[i]`), under the server lock as the dispatcher calls it."""
    from adapm_tpu.core.store import OOB
    st, ab = srv.stores[0], srv.ab
    n = len(keys)
    with srv._lock:
        return st.gather_pool(
            ab.owner[keys].astype(np.int32), ab.slot[keys].astype(np.int32),
            np.zeros(n, np.int32), np.full(n, OOB, np.int32),
            np.zeros(n, bool), np.asarray(bag_of, np.int32), nbags)


@pytest.mark.parametrize("cold_dtype", ["fp32", "fp16", "int8"])
def test_two_batches_in_flight_read_two_kept_buffers(cold_dtype,
                                                     monkeypatch):
    """Two cold batches of one bucket shape while the first's program
    has not finished: each stages into a buffer of its own, the ring
    grows by what is in flight and no further, a freed buffer is
    cleared where its last user wrote and nowhere else, and every
    pooled vector is the host's sum over the rows a plain read gives
    (the cold store's visible values: exact in fp32)."""
    from adapm_tpu.tier import coldpath
    srv, w, vals = _big(tier_cold_dtype=cold_dtype)
    try:
        st = srv.stores[0]
        ring = st.stage_ring
        rng = np.random.default_rng(8)
        cold = np.arange(POOL, E)
        nb = 5

        def batch():
            ks = rng.choice(cold, 40, replace=False)
            ks[::4] = rng.choice(POOL, 10, replace=False)   # some hot
            return ks, rng.integers(0, nb, len(ks))

        def want(ks, bag_of):
            rows = np.asarray(srv.read_main(ks)).reshape(len(ks), L)
            out = np.zeros((nb, L), np.float32)
            np.add.at(out, bag_of, rows)
            return out
        first = batch()
        a = _bag_read(srv, *first, nb)
        kept = {k: len(v) for k, v in ring._rings.items()}
        assert kept and all(n == 1 for n in kept.values())
        # nothing is reported finished: the first batch's buffers are
        # held, the second's are new ones, a third's new again
        monkeypatch.setattr(coldpath._Stage, "free",
                            lambda self: self.user is None)
        second = batch()
        b = _bag_read(srv, *second, nb)
        assert {k: len(v) for k, v in ring._rings.items()} == \
            {k: 2 for k in kept}
        held = [s for r in ring._rings.values() for s in r]
        assert all(s.user is not None for s in held)
        assert len({id(s.buf) for s in held}) == len(held)
        assert np.array_equal(np.asarray(a)[:nb], want(*first))
        assert np.array_equal(np.asarray(b)[:nb], want(*second))
        # finished programs free their buffers: no third buffer, and
        # what the buffer's last user wrote is gone
        monkeypatch.undo()
        for ks, bag_of in (batch(), batch(), batch()):
            got = _bag_read(srv, ks, bag_of, nb)
            assert np.array_equal(np.asarray(got)[:nb], want(ks, bag_of))
            assert {k: len(v) for k, v in ring._rings.items()} == \
                {k: 2 for k in kept}
        rows_ring = max(ring._rings.items(),
                        key=lambda kv: len(kv[0][0]))[1]
        for s in rows_ring:
            live = np.zeros(len(s.buf), bool)
            live[s.at] = True
            assert not np.asarray(s.buf[~live], np.float32).any()
    finally:
        srv.shutdown()


@pytest.mark.parametrize("fails", ["read", "fill"])
def test_a_batch_that_fails_before_its_program_holds_no_buffer(fails,
                                                              monkeypatch):
    """A cold batch whose cold read or whose staging raises (a bad
    index, no memory) dispatches no program, so nothing would ever free
    a buffer it took: the ring is as deep after it as before, every
    buffer free, and the next batch reads as ever."""
    from adapm_tpu.tier import coldpath
    srv, w, vals = _big()
    try:
        st = srv.stores[0]
        ring = st.stage_ring
        nb = 3
        ks = np.arange(POOL, POOL + 24)                 # all cold
        bag_of = np.arange(len(ks)) % nb
        first = np.asarray(_bag_read(srv, ks, bag_of, nb))[:nb].copy()
        kept = {k: len(v) for k, v in ring._rings.items()}

        def boom(*a, **k):
            raise MemoryError("planted")
        if fails == "read":
            monkeypatch.setattr(st.coldq, "read", boom)
        else:
            monkeypatch.setattr(coldpath._Stage, "fill", boom)
        for _ in range(3):
            with pytest.raises(MemoryError, match="planted"):
                _bag_read(srv, ks, bag_of, nb)
        monkeypatch.undo()
        assert {k: len(v) for k, v in ring._rings.items()} == kept
        assert all(s.user is not coldpath._HELD
                   for r in ring._rings.values() for s in r)
        again = np.asarray(_bag_read(srv, ks, bag_of, nb))[:nb]
        assert np.array_equal(again, first)
        assert {k: len(v) for k, v in ring._rings.items()} == kept
    finally:
        srv.shutdown()


def test_open_plane_touches_every_bucket_s_staging_buffers():
    """`precompile` leaves two touched staging buffers a bag bucket, and
    cold traffic of every size the plane admits makes no more."""
    serve = _tiered()
    try:
        ring = serve.srv.stores[0].stage_ring
        made = {k: len(v) for k, v in ring._rings.items()}
        assert made and all(n == 2 for n in made.values())
        sess = serve.plane.session()
        rng = np.random.default_rng(9)
        for s in (2, 4, 9, 3, 9):
            tables, bags = _request(serve, rng, s)
            assert _differ(sess.lookup_bags(tables, bags),
                           tables, bags) == 0
        assert {k: len(v) for k, v in ring._rings.items()} == made
        assert serve.srv.metrics_snapshot()["tier"][
            "cold_stage_bytes"] > 0
    finally:
        serve.close()
