"""State derived from placement is patched by the journal of changed
keys (core/addressbook.py), never rebuilt while those keys are known: a
`DeviceRouter`'s two device tables (a key's place as ONE word, and the
worker shard's cache row) and a `DeviceRoutedRunner`'s local sampling
index have to equal, bit for bit, what `_refresh` and
`_build_local_neg_index` build from the tables, after any sequence of
placement changes, on four shards. And the word itself: what it decodes
to is the `(owner[k], slot[k])` of the addressbook for every live pair,
and out of bounds for every other (`test_place_word_*`)."""
import numpy as np
import pytest

import adapm_tpu
from adapm_tpu.config import SystemOptions
from adapm_tpu.core.addressbook import Addressbook
from adapm_tpu.ops import DeviceRoutedRunner

K, L, S = 400, 8, 4


def _loss(embs, aux):
    return ((embs["a"] * embs["neg"]).sum(-1) ** 2).mean()


def _runner(srv, shard, population):
    return DeviceRoutedRunner(
        srv, _loss, role_class={"a": 0, "neg": 0},
        role_dim={"a": 4, "neg": 4}, shard=shard, neg_role="neg",
        neg_shape=(4, 2), neg_population=population, seed=shard)


def _state(runner):
    """What the next dispatch of `runner` would hand the step: the two
    tables, the padded local index, its count, the fallback flag."""
    with runner.server._lock:
        tables = runner._tables()[:2]
        index, count = runner._local_neg_index()
    return ([np.asarray(t) for t in tables], np.asarray(index),
            int(count), runner._li_fallback)


def _from_scratch(twin):
    """The same from the addressbook's tables alone: a twin runner that
    forgets what it held."""
    twin.router._version = twin.router._cursor = None
    twin._li_version = twin._li_host = None
    return _state(twin)


def _assert_same(got, want):
    for g, w in zip(got[0], want[0]):
        assert g.dtype == w.dtype and np.array_equal(g, w)
    assert got[1].dtype == want[1].dtype and got[1].shape == want[1].shape
    assert np.array_equal(got[1], want[1])
    assert got[2:] == want[2:]


def _patches(srv):
    return srv.obs.find("fused.route_patch_total").snap()


# the sampled population of each case, and which shard the moves favour
CASES = {
    "uniform": (None, None),
    "subset": (np.arange(3, K, 3), None),
    # keys that shard 0 owns at set-up alone: the other shards' runners
    # start with nothing local, and fall back again when all have left
    "fallback": (np.arange(0, K, S), None),
    # moves favour shard 0 until its index outgrows its padded capacity
    "doubling": (None, 0),
    "restore": (None, None),
}


def _patch_widths(monkeypatch):
    """The widths of the operands that the port's patch program is
    called with from here on, one entry a call."""
    from adapm_tpu.device import jaxport
    widths = []
    call = jaxport.JaxDevicePort.patch_routes

    def patch_routes(self, place, cache_row, patch):
        widths.append(patch.shape[1])
        return call(self, place, cache_row, patch)
    monkeypatch.setattr(jaxport.JaxDevicePort, "patch_routes", patch_routes)
    return widths


@pytest.mark.parametrize("patch_keys", [8, None])
@pytest.mark.parametrize("case", sorted(CASES))
def test_patched_tables_and_index_equal_the_full_rebuild(case, patch_keys,
                                                         tmp_path,
                                                         monkeypatch):
    """`patch_keys` 8: a first rung of 8, so the seeded bursts land on
    several rungs of the ladder; None: the shipped first rung, which
    holds them all. One call of the program a patch either way."""
    from adapm_tpu.ops import fused
    if patch_keys is not None:
        monkeypatch.setattr(fused, "PATCH_KEYS", patch_keys)
    widths = _patch_widths(monkeypatch)
    population, favoured = CASES[case]
    srv = adapm_tpu.setup(K, L, num_shards=S, opts=SystemOptions(
        sync_max_per_sec=0, cache_slots_per_shard=32, main_over_alloc=3.0))
    try:
        ab = srv.ab
        runners = [_runner(srv, s, population) for s in range(S)]
        twins = [_runner(srv, s, population) for s in range(S)]
        rng = np.random.default_rng(sorted(CASES).index(case))
        pop = np.arange(K) if population is None else population

        def check(expect_patch=None):
            before = _patches(srv)
            for r, t in zip(runners, twins):
                _assert_same(_state(r), _from_scratch(t))
            if expect_patch is not None:
                assert (_patches(srv) > before) is expect_patch

        check(expect_patch=False)       # set-up: the full build
        capacities = {len(_state(runners[0])[1])}
        fallbacks = {_state(r)[3] for r in runners}
        ckpt = str(tmp_path / "ck.npz")
        for it in range(40):
            op = rng.integers(0, 5)
            keys = np.unique(rng.choice(pop, rng.integers(1, 24)))
            dest = favoured if favoured is not None and it % 4 else \
                int(rng.integers(0, S))
            if op <= 1:
                srv._relocate_to(keys, dest)
            elif op == 2:
                srv._create_replicas(keys, dest)
            elif op == 3:
                held = np.flatnonzero(ab.cache_slot[dest] >= 0)
                held = held[: rng.integers(1, 12)]
                srv._drop_replicas(held, np.full(len(held), dest))
            else:   # ownership leaves the process and comes back
                keys = keys[ab.owner[keys] >= 0]
                if not len(keys):
                    continue
                srv._drop_replicas(
                    np.tile(keys, S), np.repeat(np.arange(S), len(keys)))
                with srv._topology_mutation():
                    ab.abandon_batch(keys)
                check(expect_patch=True)
                with srv._topology_mutation():
                    ab.adopt_batch(keys, dest)
            if it == 20:    # a burst the journal cannot hold: rebuilt
                limit, ab.journal_limit = ab.journal_limit, 8
                for s in range(S):
                    srv._relocate_to(rng.choice(pop, 16), s)
                ab.journal_limit = limit
                check(expect_patch=False)
            if case == "fallback" and it == 30:
                # everything the population has leaves shards 1..3
                srv._drop_replicas(
                    np.tile(pop, S - 1),
                    np.repeat(np.arange(1, S), len(pop)))
                srv._relocate_to(pop, 0)
            if case == "restore" and it == 10:
                from adapm_tpu.utils.checkpoint import save_server
                save_server(srv, ckpt)
            if case == "restore" and it == 25:
                from adapm_tpu.utils.checkpoint import restore_server
                restore_server(srv, ckpt)
                check(expect_patch=False)   # journal reset: rebuilt
            check()
            capacities.add(len(_state(runners[0])[1]))
            fallbacks |= {_state(r)[3] for r in runners}
        refreshes = srv.obs.find("fused.route_refresh_total").snap()
        assert _patches(srv) > 40 and refreshes > _patches(srv)
        assert srv.obs.find("fused.route_patch_keys_total").snap() > 0
        # every call at a rung, and on several where the first is small
        assert srv.obs.find("fused.route_patch_calls_total").snap() == \
            len(widths) > 40
        assert set(widths) <= set(fused.patch_rungs(ab.journal_limit))
        assert (len(set(widths)) > 2) is (patch_keys is not None)
        assert len(capacities) > 1 or case != "doubling"
        assert fallbacks == ({False, True} if case == "fallback"
                             else {False})
    finally:
        srv.shutdown()


# a table of 20,000 x 16 keys: the journal holds 20,000 entries, so the
# ladder is the shipped first rung and one more
BIG = 320_000


@pytest.mark.parametrize("changed", [1, 16_384, 16_385, BIG // 16,
                                     BIG // 16 + 1])
def test_a_refresh_is_one_call_at_the_rung_that_holds_its_keys(
        changed, monkeypatch):
    """However many keys changed placement since a router's last look,
    its refresh is ONE call of the port's program, at the smallest rung
    that holds them, and the mirrors are the addressbook's tables; one
    key more than the journal holds is a rebuild and no call."""
    from adapm_tpu.ops import fused
    widths = _patch_widths(monkeypatch)
    srv = adapm_tpu.setup(BIG, 2, num_shards=S, opts=SystemOptions(
        sync_max_per_sec=0, cache_slots_per_shard=32, main_over_alloc=1.5))
    try:
        ab = srv.ab
        assert ab.journal_limit == BIG // 16
        assert fused.patch_rungs(ab.journal_limit) == [16_384, 32_768]
        router = fused.DeviceRouter(srv, 1)

        def counters():
            return [srv.obs.find("fused.route_" + n).snap() for n in (
                "refresh_total", "patch_total", "patch_calls_total",
                "patch_keys_total")]

        def look():
            """The counters' growth over one look at the mirrors, which
            have to be the addressbook's tables."""
            before = counters()
            with srv._lock:
                place, cache_row = map(np.asarray, router.tables())
            assert place.dtype == cache_row.dtype == np.int32
            owner, slot = map(np.asarray, fused.decode_place(
                place, srv.stores[0].main.shape[1]))
            assert np.array_equal(owner, ab.owner)
            assert np.array_equal(slot, ab.slot)
            assert np.array_equal(cache_row, ab.cache_slot[1])
            return [b - a for a, b in zip(before, counters())]

        assert look() == [1, 0, 0, 0]               # set-up: the build
        # keys that shard 0 does not own move there, in ONE mutation
        keys = np.flatnonzero(np.arange(BIG) % S != 0)[:changed]
        srv._relocate_to(keys, 0)
        assert (ab.owner[keys] == 0).all()
        if changed <= ab.journal_limit:
            assert look() == [1, 1, 1, changed]
            assert widths == [16_384 if changed <= 16_384 else 32_768]
        else:
            assert look() == [1, 0, 0, 0] and widths == []
        assert look() == [0, 0, 0, 0]               # nothing changed since
        del widths[:]
        held = np.arange(BIG - 30, BIG, 4)          # eight of shard 2's
        srv._create_replicas(held, 1)               # `cache_row` moves too
        assert (ab.cache_slot[1, held] >= 0).all()
        assert look() == [1, 1, 1, 8] and widths == [16_384]
    finally:
        srv.shutdown()


@pytest.mark.parametrize("changed, width", [
    (0, 16_384), (1, 16_384), (16_384, 16_384), (16_385, 32_768),
    (BIG // 16, 32_768)])
def test_patch_operand_keeps_the_promise_made_to_the_scatter(changed,
                                                             width):
    """The patch program's scatter is told its keys ascend and none
    repeats (`jaxport._patch_routes`); a false promise is undefined
    behaviour, so the operand is held to it here: row 0 strictly
    ascending over the keys AND the padding, every padding key past the
    tables (dropped), its values `OOB`, the keys' values the
    addressbook's: their place words (which decode to its owner and
    slot) and their cache rows."""
    from adapm_tpu.ops import fused
    srv = adapm_tpu.setup(BIG, 2, num_shards=S, opts=SystemOptions(
        sync_max_per_sec=0, cache_slots_per_shard=32, main_over_alloc=1.5))
    try:
        ab = srv.ab
        router = fused.DeviceRouter(srv, 1)
        # the keys as `_changed_keys` hands them over, spread over the
        # table: from two keys on the last is the table's last
        keys = np.unique(np.linspace(0, BIG - 1, changed).astype(np.int64))
        assert len(keys) == changed
        assert fused.patch_rungs(changed)[-1] == width
        patch = router._patch_operand(keys, width)
        assert patch.dtype == np.int32 and patch.shape == (3, width)
        assert (np.diff(patch[0].astype(np.int64)) > 0).all()
        assert np.array_equal(patch[0, :changed], keys)
        assert (patch[0, changed:] >= ab.num_keys).all()
        assert (patch[1:, changed:] == fused.OOB).all()
        owner, slot = map(np.asarray, fused.decode_place(
            patch[1, :changed], srv.stores[0].main.shape[1]))
        assert np.array_equal(owner, ab.owner[keys])
        assert np.array_equal(slot, ab.slot[keys])
        assert np.array_equal(patch[2, :changed], ab.cache_slot[1, keys])
    finally:
        srv.shutdown()


def test_changed_keys_are_sorted_and_distinct_after_repeats():
    """The other half of the promise: a journal lists a key once for
    every mutation that touched it, in the order of the mutations, and
    `_changed_keys` returns each once and ascending."""
    from adapm_tpu.ops import fused
    srv = adapm_tpu.setup(K, L, num_shards=S, opts=SystemOptions(
        sync_max_per_sec=0, cache_slots_per_shard=32, main_over_alloc=3.0))
    try:
        ab = srv.ab
        cursor = ab.journal_cursor()
        srv._relocate_to(np.array([90, 13, 57]), 0)
        srv._relocate_to(np.array([57, 13, 201]), 2)
        srv._create_replicas(np.array([201, 90, 5]), 3)
        journal = ab.changed_since(cursor)
        assert len(journal) > len(set(journal.tolist()))     # repeats
        assert (np.diff(journal) < 0).any()                  # unsorted
        with srv._lock:
            keys = fused._changed_keys(srv, cursor)
        assert keys.tolist() == [5, 13, 57, 90, 201]
        with srv._lock:
            assert len(fused._changed_keys(srv, ab.journal_cursor())) == 0
            assert fused._changed_keys(srv, None) is None
    finally:
        srv.shutdown()


def _mutate(ab, mutator):
    """One call of `mutator` on keys that are ready for it."""
    keys = np.array([5, 9, 14])             # homes 1, 1, 2
    if mutator == "add_replicas":
        ab.add_replicas(keys, 3)
    elif mutator == "drop_replicas":
        ab.drop_replicas(np.array([21, 22]), 0)
    elif mutator == "relocate":
        ab.relocate(5, 3)
    elif mutator == "relocate_batch":
        ab.relocate_batch(keys, 0)
    elif mutator == "abandon_batch":
        ab.abandon_batch(keys)
    else:
        ab.adopt_batch(np.array([30, 31]), 2)


@pytest.mark.parametrize("mutator", [
    "add_replicas", "drop_replicas", "relocate", "relocate_batch",
    "abandon_batch", "adopt_batch"])
def test_every_mutator_journals_the_entries_it_changes(mutator):
    """Whatever a mutator changes in the three tables that the fused
    step mirrors, the journal lists: the keys after the cursor are
    exactly the keys whose owner, slot or cache slot differ from a
    snapshot, and the mutation is counted once."""
    ab = Addressbook(np.zeros(64, np.int32), 4, [64], [8])
    ab.add_replicas(np.array([21, 22, 23]), 0)   # something to drop
    ab.abandon_batch(np.array([30, 31]))         # something to adopt
    before = (ab.owner.copy(), ab.slot.copy(), ab.cache_slot.copy())
    cursor, counted = ab.journal_cursor(), ab.mutations
    _mutate(ab, mutator)
    moved = (ab.owner != before[0]) | (ab.slot != before[1]) \
        | (ab.cache_slot != before[2]).any(axis=0)
    assert moved.any()
    assert sorted(ab.changed_since(cursor)) == list(np.flatnonzero(moved))
    assert ab.mutations == counted + 1


def test_journal_answers_a_cursor_or_says_it_cannot():
    ab = Addressbook(np.zeros(64, np.int32), 4, [64], [8])
    assert ab.journal_limit == 4096   # the floor: 64 keys // 16 is less
    assert ab.changed_since(None) is None
    c0 = ab.journal_cursor()
    assert len(ab.changed_since(c0)) == 0
    ab.relocate(5, 2)
    ab.add_replicas(np.array([7, 9]), 1)
    c1 = ab.journal_cursor()
    ab.relocate_batch(np.array([9, 10, 11]), 0)
    ab.drop_replicas(np.array([7]), 1)
    assert sorted(ab.changed_since(c0)) == [5, 7, 7, 9, 9, 10, 11]
    assert sorted(ab.changed_since(c1)) == [7, 9, 10, 11]
    assert len(ab.changed_since(ab.journal_cursor())) == 0
    assert ab.mutations == 4
    # a cursor inside a chunk: the chunk's tail
    assert sorted(ab.changed_since(c1 + 1)) == [7, 10, 11]
    # bounded by entries: the oldest chunks go, and a cursor before the
    # first kept entry is not answered
    ab.journal_limit = 4
    ab.relocate_batch(np.array([20, 22, 24]), 1)
    assert ab.changed_since(c1) is None
    c2 = ab.journal_cursor()
    assert sorted(ab.changed_since(c2 - 3)) == [20, 22, 24]
    # one mutation larger than the limit leaves nothing to answer from
    ab.relocate_batch(np.arange(40, 60)[np.arange(40, 60) % 4 != 3], 3)
    assert ab.changed_since(c2) is None
    assert len(ab.changed_since(ab.journal_cursor())) == 0
    # a reset answers no cursor taken before it
    c3 = ab.journal_cursor()
    ab.reset_journal()
    assert ab.changed_since(c3) is None
    assert len(ab.changed_since(ab.journal_cursor())) == 0


# -- the place word ---------------------------------------------------------

def _two_classes(num_keys):
    """Value lengths of two classes of unlike sizes (the first eighth of
    the keys have rows of 16), so their pools' words split at unlike
    bits."""
    return np.where(np.arange(num_keys) < num_keys // 8, 16, 8)


@pytest.mark.parametrize("classes", [1, 2])
@pytest.mark.parametrize("shards", [1, 4, 8])
def test_place_word_decodes_to_the_addressbooks_route(shards, classes):
    """Every worker shard's place mirror, decoded with the slots of the
    key's own pool, is the addressbook's `(owner[k], slot[k])` on every
    live pair, after relocations, replicas and keys that left the
    process; a key another process owns (`REMOTE`, `NO_SLOT`) decodes
    out of bounds in both, where the two tables wrapped it to the last
    row of the last shard; and the patched mirror is the rebuilt one."""
    from adapm_tpu.ops import fused
    from adapm_tpu.base import NO_SLOT, REMOTE
    num_keys = 640
    srv = adapm_tpu.setup(
        num_keys, 8 if classes == 1 else _two_classes(num_keys),
        num_shards=shards, opts=SystemOptions(
            sync_max_per_sec=0, cache_slots_per_shard=32,
            main_over_alloc=3.0))
    try:
        ab = srv.ab
        slots = [st.main.shape[1] for st in srv.stores]
        assert len(slots) == classes
        assert len({fused.place_bits(n) for n in slots}) == classes
        routers = [fused.DeviceRouter(srv, s) for s in range(shards)]
        assert all(r.owner is None for r in routers)    # not built yet
        rng = np.random.default_rng(shards * 2 + classes)
        # (a mutation of ownership across processes takes one class)
        gone = rng.choice(np.flatnonzero(ab.key_class == classes - 1), 24,
                          replace=False)

        def check():
            for s, router in enumerate(routers):
                with srv._lock:
                    place, cache_row = map(np.asarray, router.tables())
                assert router.owner is router.place is not None
                assert place.dtype == np.int32 and (place >= 0).all()
                assert np.array_equal(cache_row, ab.cache_slot[s])
                for cid, n in enumerate(slots):
                    k = np.flatnonzero(ab.key_class == cid)
                    sh, sl = map(np.asarray,
                                 fused.decode_place(place[k], n))
                    live = ab.owner[k] != REMOTE
                    assert np.array_equal(sh[live], ab.owner[k][live])
                    assert np.array_equal(sl[live], ab.slot[k][live])
                    assert (ab.slot[k][~live] == NO_SLOT).all()
                    assert (place[k][~live] == fused.OOB).all()
                    assert (sh[~live] >= shards).all()
                    assert (sl[~live] == fused.OOB).all()

        check()                                         # the full build
        for it in range(6):
            keys = np.unique(rng.choice(num_keys, 40))
            keys = keys[ab.owner[keys] >= 0]
            if shards > 1:
                srv._relocate_to(keys[::2], int(rng.integers(0, shards)))
                srv._create_replicas(keys[1::2],
                                     int(rng.integers(0, shards)))
            if it == 2:     # ownership leaves the process
                srv._drop_replicas(np.tile(gone, shards), np.repeat(
                    np.arange(shards), len(gone)))
                with srv._topology_mutation():
                    ab.abandon_batch(gone)
                assert (ab.owner[gone] == REMOTE).all()
            if it == 4:     # and half of it comes back
                with srv._topology_mutation():
                    ab.adopt_batch(gone[::2], shards - 1)
            check()
        assert _patches(srv) > 0
        # a rebuilt mirror is the patched one, to the bit
        for router in routers:
            twin = fused.DeviceRouter(srv, router.shard)
            with srv._lock:
                for got, want in zip(router.tables(), twin.tables()):
                    assert np.asarray(got).tobytes() == \
                        np.asarray(want).tobytes()
    finally:
        srv.shutdown()


def _check_nowhere(srv, router, kind, no_replicas, rows, rng):
    """The body of the test below, on a store whose `rows` are known."""
    import jax
    import jax.numpy as jnp
    from adapm_tpu.ops import fused
    ab = srv.ab
    num_keys, L = rows.shape
    dim, B = L // 2, 16
    every = np.arange(num_keys)
    place, cache_row = router.tables()
    if kind == "cold":
        nowhere = srv.tier.compose_slot_table() == fused.OOB
        assert 0 < (~nowhere).sum() < nowhere.sum()
    elif kind == "remote":
        nowhere = ab.owner < 0
        assert 0 < nowhere.sum() < (~nowhere).sum()
    else:
        nowhere = np.ones(num_keys, bool)
        patch = router._patch_operand(np.empty(0, np.int64),
                                      fused.PATCH_KEYS)
        assert (patch[0] >= num_keys).all()
        # the program drops every padding key: the tables stand
        got = srv.stores[0].port.patch_routes(
            place, cache_row, srv.ctx.put_replicated(patch))
        for g, w in zip(got, (place, cache_row)):
            assert np.asarray(g).tobytes() == np.asarray(w).tobytes()
        place = srv.ctx.put_replicated(
            np.full(num_keys, patch[1, -1], np.int32))
    assert (np.asarray(place)[nowhere] == fused.OOB).all()
    assert (np.asarray(place)[~nowhere] != fused.OOB).all()
    tables = (place, cache_row, srv.ctx.put_replicated(np.int32(0)))
    pools = tuple((s.main, s.cache, s.delta) for s in srv.stores)
    n_slots = pools[0][0].shape[1]
    lost, live = every[nowhere], every[~nowhere]
    mixed = rng.choice(lost, B)
    if len(live):
        mixed[::2] = rng.choice(live, B // 2, replace=False)

    # the read half: zeros, and not local
    embs, _, _, _, counts = fused._route_and_gather(
        pools, tables, {"a": jnp.asarray(mixed.astype(np.int32))},
        ["a"], {"a": 0}, {"a": dim}, no_replicas)
    got = np.asarray(embs["a"])
    here = ~nowhere[mixed]
    assert not got[~here].any()
    assert got[here].tobytes() == rows[mixed[here], :dim].tobytes()
    assert rows[mixed[~here], :dim].all()   # the rows themselves are not
    assert int(counts[0]) == B
    assert int(counts[1]) == int((here & (ab.owner[mixed] == 0)).sum())

    # the write half: dropped
    def loss(embs, aux):   # a gradient of 1 where the row read zero
        return (embs["a"] ** 2).sum() + embs["a"].sum()

    step = jax.jit(fused._build_device_routed_body(
        loss, {"a": 0}, {"a": dim}, (), None, None, no_replicas, False))
    stat = srv.ctx.put_replicated(np.zeros(4, np.int32))
    start = [np.asarray(x) for x in pools[0]]
    for batch in (rng.choice(lost, B), mixed):
        keys = {"a": srv.ctx.put_replicated(batch.astype(np.int32))}
        out, _, _ = step(pools, stat, tables, keys, None, None,
                         jax.random.PRNGKey(0), None, jnp.float32(0.1),
                         jnp.float32(1e-10))
        moved = (np.asarray(out[0][0]) != start[0]).any(axis=2)
        sh, sl = map(np.asarray, fused.decode_place(
            np.asarray(place)[batch[~nowhere[batch]]], n_slots))
        want = np.zeros_like(moved)
        want[sh, sl] = True
        assert np.array_equal(moved, want)
        assert moved.any() == (batch is mixed and len(live) > 0)
        for i in (1, 2):    # no replica is held: cache and delta stand
            assert np.asarray(out[0][i]).tobytes() == start[i].tobytes()


@pytest.mark.parametrize("no_replicas", [True, False])
@pytest.mark.parametrize("kind", ["cold", "remote", "padding"])
def test_place_word_out_of_bounds_reads_zeros_and_writes_nothing(
        kind, no_replicas):
    """What is not a live pair decodes out of bounds and the step treats
    it so: a tiered store's cold row (`compose_slot_table` hands `OOB`
    for its slot), a key another process owns, and the padding value of
    the patch operand (whose padding KEYS, past `num_keys`, the patch
    program drops). Such a position reads a ZERO embedding, no shard
    counts it local, and its write-back is dropped: the pools come back
    to the bit where a batch names nothing else, and with live keys
    beside it only their rows move."""
    from adapm_tpu.ops import fused
    num_keys, L, shards = 384, 8, 4
    tiered = kind == "cold"
    srv = adapm_tpu.setup(num_keys, L, num_shards=shards,
                          opts=SystemOptions(
                              sync_max_per_sec=0, prefetch=False,
                              cache_slots_per_shard=32, tier=tiered,
                              tier_hot_rows=16 if tiered else 0))
    try:
        ab = srv.ab
        rng = np.random.default_rng(5)
        every = np.arange(num_keys)
        srv.make_worker(0).set(
            every, rng.normal(size=(num_keys, L)).astype(np.float32) + 3)
        rows = np.asarray(srv.read_main(every)).reshape(num_keys, L)
        router = fused.DeviceRouter(srv, 0)
        if kind == "cold":
            srv.tier.promote_keys(np.arange(0, 48))
        elif kind == "remote":
            with srv._topology_mutation():
                ab.abandon_batch(every[every % 5 == 1])
        # (under the lock to the end: a tier worker's pass waits)
        with srv._lock:
            _check_nowhere(srv, router, kind, no_replicas, rows, rng)
    finally:
        srv.shutdown()
