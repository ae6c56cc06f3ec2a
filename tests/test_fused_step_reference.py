"""The fused step's layout is not its semantics: the step body as it is
(the sampled role's rows sample-major, the gather clamped and the mask on
the embedding columns) against a plain `jax.numpy` step written here by
the formulas the step had before it (whole rows gathered with
`mode="fill"`, the sampled role batch-major, AdaGrad update rows and
`.at[].add(mode="drop")`), on tables that send positions out of bounds
and to shard -1. The step is handed each key's place as ONE word
(`fused.decode_place`); the plain step the shard and the slot that the
word stands for, as two tables. And the replica variant as it is (every
position gathered from main, the replica positions compacted and patched
in from cache + delta a chunk at a time, their update rows alone added
to delta) against
the plain step it was before: main, cache and delta gathered at every
position and selected, update rows of every position added to both
pools."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapm_tpu.core.store import OOB
from adapm_tpu.ops import fused

B = 8
LR, EPS = 0.1, 1e-10


def _loss(embs, aux):
    pos = (embs["a"] * embs["b"]).sum(-1)
    out = jax.nn.softplus(-pos)
    if "neg" in embs:
        neg = (embs["a"][:, None, :] * embs["neg"]).sum(-1)
        # a weight a position: a loss that is not symmetric in [b, k]
        w = 1.0 + jnp.arange(neg.size, dtype=jnp.float32).reshape(
            neg.shape) / neg.size
        out = out + (w * jax.nn.softplus(neg)).sum(-1)
    return out.mean()


def _tables(num_keys, rng):
    """Every key its own slot of a pool of `num_keys`; a seventh of the
    keys nowhere (`OOB`: a cold tier row), a fifth on shard -1 (jnp's
    wrap: shard 0; a word made by hand, the router's own are never
    negative). Returns the plain step's `owner` and `slot` and the
    step's `place` words: the pair is what the word decodes to, a live
    pair itself and nowhere out of bounds in both."""
    owner = np.zeros(num_keys, np.int32)
    slot = rng.permutation(num_keys).astype(np.int32)
    keys = np.arange(num_keys)
    owner[keys % 5 == 2] = -1
    nowhere = keys % 7 == 3
    bits = fused.place_bits(num_keys)
    place = np.where(nowhere, OOB, (owner << bits) | slot).astype(np.int32)
    assert ((place < 0) == (owner == -1) & ~nowhere).all()
    sh, sl = (np.asarray(x) for x in fused.decode_place(place, num_keys))
    assert sh.dtype == sl.dtype == np.int32
    assert np.array_equal(sh[~nowhere], owner[~nowhere])
    assert np.array_equal(sl[~nowhere], slot[~nowhere])
    assert (sl[nowhere] == OOB).all() and (sh[nowhere] >= 1).all()
    return sh, sl, place


def _pools(num_keys, L, rng):
    main = rng.normal(size=(1, num_keys, L)).astype(np.float32)
    main[..., L // 2:] = np.abs(main[..., L // 2:]) * 1e-3
    small = np.zeros((1, 8, L), np.float32)
    return ((jnp.asarray(main), jnp.asarray(small), jnp.asarray(small)),)


def _draw_as_before(rng_key, neg_shape, local_index, alias):
    """The sampled keys at [b, k], by the step's own calls."""
    if alias is not None:
        prob, alias_t, snap = alias
        k1, k2 = jax.random.split(rng_key)
        u = jax.random.randint(k1, neg_shape, 0, prob.shape[0])
        v = jax.random.uniform(k2, neg_shape)
        return snap[jnp.where(v < prob[u], u, alias_t[u])]
    idx, count = local_index
    return idx[jax.random.randint(rng_key, neg_shape, 0, count)]


def _step_as_before(main, locstat, owner, slot, shard, keys, dim):
    """One replica-free step by the formulas the step had before the
    sampled role went sample-major: `keys` holds every role's keys, the
    sampled role's as `[B, N]`."""
    roles = sorted(keys)
    routes = {r: (owner[keys[r]], slot[keys[r]]) for r in roles}
    rows = {r: main.at[routes[r]].get(mode="fill", fill_value=0)
            for r in roles}
    n_total = sum(keys[r].size for r in roles)
    n_local = sum(jnp.sum(routes[r][0] == shard, dtype=jnp.int32)
                  for r in roles)
    locstat = locstat + jnp.stack([
        jnp.int32(n_total), n_local, jnp.int32(1),
        (n_local == n_total).astype(jnp.int32)])
    loss, grads = jax.value_and_grad(lambda e: _loss(e, None))(
        {r: rows[r][..., :dim] for r in roles})
    for r in roles:
        g, acc = grads[r], rows[r][..., dim:]
        g2 = g * g
        upd = jnp.concatenate(
            [-LR * g * jax.lax.rsqrt(acc + g2 + EPS), g2], axis=-1)
        main = main.at[routes[r]].add(upd, mode="drop")
    return main, locstat, loss


# name -> (negatives a row or None, keys, the sampler, the variant)
CASES = {
    # 65,536 keys: the 40 or 64 draws of a step name no row twice
    "neg5-distinct": (5, 1 << 16, "uniform", "xla"),
    "neg8-distinct": (8, 1 << 16, "uniform", "xla"),
    "neg5-distinct-alias": (5, 1 << 16, "alias", "xla"),
    # 24 keys: every sampled key several times, in both orders
    "neg5-dups": (5, 24, "uniform", "xla"),
    "neg8-dups": (8, 24, "uniform", "xla"),
    "neg5-dups-alias": (5, 24, "alias", "xla"),
    # the variant with replica pools (none held): the side path idles
    "neg5-dups-replicas": (5, 24, "uniform", "replicas"),
    "neg8-distinct-replicas": (8, 1 << 16, "uniform", "replicas"),
    # the write-back kernel (its interpret build), rows of 256
    "neg5-dups-kernel": (5, 24, "uniform", "kernel"),
    "neg8-dups-kernel": (8, 24, "uniform", "kernel"),
    # no sampled role: host-named keys with duplicates
    "host-named": (None, 24, None, "xla"),
    "host-named-kernel": (None, 24, None, "kernel"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_step_equals_the_batch_major_fill_step(case, monkeypatch,
                                               kernel_cache):
    """Two steps: the pool, each loss and the locality counts, bitwise
    where no sampled row is named twice (the replica-free variant, XLA
    writing back), else to 1e-6 (the order of additions among a row's positions is `(k, b)`
    now). Every batch has out-of-bounds positions and positions on shard
    -1, in every role; the sampled keys sit at the `[b, k]` of the old
    draw from the same PRNG key, or nothing would agree."""
    N, num_keys, sampler, variant = CASES[case]
    L = 256 if variant == "kernel" else 8
    dim = L // 2
    if variant == "kernel":
        monkeypatch.setattr(fused, "writeback_uses_kernel",
                            functools.partial(fused.writeback_uses_kernel,
                                              backend="tpu"))
    rng = np.random.default_rng(7)
    owner, slot, place = _tables(num_keys, rng)
    pools = _pools(num_keys, L, rng)
    roles = {"a": 0, "b": 0}
    if N is not None:
        roles["neg"] = 0
    neg_shape = None if N is None else (B, N)
    body = jax.jit(fused._build_device_routed_body(
        _loss, roles, {r: dim for r in roles}, (),
        None if N is None else "neg", neg_shape,
        variant != "replicas", sampler == "alias"))
    local_index = alias = None
    if sampler == "uniform":
        local_index = (jnp.arange(num_keys, dtype=jnp.int32),
                       jnp.int32(num_keys))
    elif sampler == "alias":
        prob = rng.uniform(0.2, 1.0, num_keys).astype(np.float32)
        alias = (jnp.asarray(prob),
                 jnp.asarray(rng.integers(0, num_keys, num_keys,
                                          dtype=np.int32)),
                 jnp.asarray(rng.permutation(num_keys).astype(np.int32)))
    shard = jnp.int32(0)
    tables = (jnp.asarray(place), jnp.full(num_keys, -1, jnp.int32), shard)
    owner_dev, slot_dev = jnp.asarray(owner), jnp.asarray(slot)
    before = jax.jit(_step_as_before, static_argnums=6)

    start = np.asarray(pools[0][0])
    want_main, want_stat = pools[0][0], jnp.zeros(4, jnp.int32)
    got_stat = want_stat
    repeats = False
    for step_no, rng_key in enumerate(jax.random.split(
            jax.random.PRNGKey(11), 2)):
        # a: three hot keys, one nowhere and one on shard -1; b: a draw
        hot = np.array([3 + 7 * step_no, 2 + 5 * step_no,
                        rng.integers(0, num_keys)])
        keys = {"a": hot[np.arange(B) % 3].astype(np.int32),
                "b": rng.integers(0, num_keys, B).astype(np.int32)}
        keys["b"][:2] = 10, 12
        ref_keys = dict(keys)
        if N is not None:
            neg = np.asarray(_draw_as_before(rng_key, neg_shape,
                                             local_index, alias))
            assert neg.shape == neg_shape
            landed = neg[(slot[neg] != OOB)]
            repeats |= len(np.unique(landed)) < landed.size
            ref_keys["neg"] = neg
        for r, k in ref_keys.items():
            assert (slot[k] == OOB).any() and (owner[k] == -1).any(), r
        want_main, want_stat, want_loss = before(
            want_main, want_stat, owner_dev, slot_dev, shard,
            {r: jnp.asarray(k) for r, k in ref_keys.items()}, dim)
        pools, got_stat, got_loss = body(
            pools, got_stat, tables, keys, local_index, alias, rng_key,
            None, jnp.float32(LR), jnp.float32(EPS))
        # the replica variant is another program (the side path's loops
        # stand between the gather and the loss): equal to an ulp
        exact = variant == "xla" and not repeats
        if exact:
            assert float(got_loss) == float(want_loss), step_no
        else:
            assert np.isclose(float(got_loss), float(want_loss),
                              rtol=1e-6, atol=0), step_no
    assert repeats is (num_keys == 24 and N is not None)
    assert np.asarray(got_stat).tolist() == np.asarray(want_stat).tolist()
    got, want = np.asarray(pools[0][0]), np.asarray(want_main)
    assert (got != start).any()
    if exact:
        assert got.tobytes() == want.tobytes()
    else:
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    # the other pools come back as they went in
    assert not np.asarray(pools[0][1]).any()
    assert not np.asarray(pools[0][2]).any()


def _replica_step_as_before(pools, locstat, tables, keys, dim):
    """One step of the replica variant by the formulas it had before the
    side path: three row-wide gathers with `mode="fill"` and a select,
    update rows of EVERY position added to main and to delta (each drops
    the positions that are the other's). `keys` holds every role's keys,
    the sampled role's as `[B, N]`; `tables` are the four the step had
    (owner, slot, cache row, shard)."""
    main, cache, delta = pools
    owner, slot, cache_row, shard = tables
    roles = sorted(keys)
    routes, rows = {}, {}
    for r in roles:
        cs = cache_row[keys[r]]
        use_c = cs >= 0
        routes[r] = (owner[keys[r]], jnp.where(use_c, OOB, slot[keys[r]]),
                     jnp.full_like(cs, shard), jnp.where(use_c, cs, OOB),
                     use_c)
        o_sh, g_sl, c_sh, c_sl, _ = routes[r]
        rows[r] = jnp.where(
            use_c[..., None],
            cache.at[c_sh, c_sl].get(mode="fill", fill_value=0)
            + delta.at[c_sh, c_sl].get(mode="fill", fill_value=0),
            main.at[o_sh, g_sl].get(mode="fill", fill_value=0))
    n_total = sum(keys[r].size for r in roles)
    n_local = sum(jnp.sum(routes[r][4] | (routes[r][0] == shard),
                          dtype=jnp.int32) for r in roles)
    locstat = locstat + jnp.stack([
        jnp.int32(n_total), n_local, jnp.int32(1),
        (n_local == n_total).astype(jnp.int32)])
    loss, grads = jax.value_and_grad(lambda e: _loss(e, None))(
        {r: rows[r][..., :dim] for r in roles})
    for r in roles:
        g, acc = grads[r], rows[r][..., dim:]
        g2 = g * g
        upd = jnp.concatenate(
            [-LR * g * jax.lax.rsqrt(acc + g2 + EPS), g2], axis=-1)
        o_sh, g_sl, c_sh, c_sl, _ = routes[r]
        main = main.at[o_sh, g_sl].add(upd, mode="drop")
        delta = delta.at[c_sh, c_sl].add(upd, mode="drop")
    return (main, cache, delta), locstat, loss


K = 8       # `fused.SIDE_ROWS` here: the sampled role's chunk, and B
CACHE = 128  # slots of the replica pools

# name -> (negatives a row, keys, replica positions among the sampled
# role's B * N, the variant): 65,536 keys name no row twice, so the
# count is exact; 24 keys name every replica row from several positions
REPLICA_CASES = {
    "held-0": (5, 1 << 16, 0, "xla"),
    "held-1": (5, 1 << 16, 1, "xla"),
    "held-K-1": (5, 1 << 16, K - 1, "xla"),
    "held-K": (5, 1 << 16, K, "xla"),
    "held-K+1": (5, 1 << 16, K + 1, "xla"),
    "held-2K+3": (5, 1 << 16, 2 * K + 3, "xla"),
    "held-all": (5, 1 << 16, 5 * B, "xla"),
    "held-K+1-kernel": (5, 1 << 16, K + 1, "kernel"),
    "held-all-kernel": (8, 1 << 16, 8 * B, "kernel"),
    "held-dups": (5, 24, 9, "xla"),
    "held-dups-kernel": (8, 24, 9, "kernel"),
}


@pytest.mark.parametrize("case", sorted(REPLICA_CASES))
def test_replica_step_equals_the_three_gather_step(case, monkeypatch,
                                                   kernel_cache):
    """Two steps of the replica variant with a chunk of `K` positions:
    main, delta, each loss and the locality counts against the plain
    step, the cache pool untouched and no row of delta touched that the
    plain step leaves; the accumulator's last two entries are the
    host's own count of replica positions (every role's) and of the
    chunks they take. Replica positions number 0, 1, K - 1, K, K + 1,
    2K + 3 and all of the sampled role's, named roles hold some too (one
    replica key three times in `a`), and every batch has out-of-bounds
    positions and positions on shard -1."""
    N, num_keys, held, variant = REPLICA_CASES[case]
    L = 256 if variant == "kernel" else 8
    dim = L // 2
    monkeypatch.setattr(fused, "SIDE_ROWS", K)
    if variant == "kernel":
        monkeypatch.setattr(fused, "writeback_uses_kernel",
                            functools.partial(fused.writeback_uses_kernel,
                                              backend="tpu"))
    rng = np.random.default_rng(17)
    owner, slot, place = _tables(num_keys, rng)
    main = _pools(num_keys, L, rng)[0][0]
    side = rng.normal(size=(2, 1, CACHE, L)).astype(np.float32)
    side[..., dim:] = np.abs(side[..., dim:]) * 1e-3
    pools = ((main, jnp.asarray(side[0]), jnp.asarray(side[1] * 0.1)),)
    roles = {"a": 0, "b": 0, "neg": 0}
    body = jax.jit(fused._build_device_routed_body(
        _loss, roles, {r: dim for r in roles}, (), "neg", (B, N), False,
        False))
    before = jax.jit(_replica_step_as_before, static_argnums=4)
    local_index = (jnp.arange(num_keys, dtype=jnp.int32),
                   jnp.int32(num_keys))
    shard = jnp.int32(0)
    start = [np.asarray(x) for x in pools[0]]
    want_pools, want_stat = pools[0], jnp.zeros(4, jnp.int32)
    got_stat = jnp.zeros(6, jnp.int32)
    n_held = n_chunks = 0
    for step_no, rng_key in enumerate(jax.random.split(
            jax.random.PRNGKey(13), 2)):
        neg = np.asarray(_draw_as_before(rng_key, (B, N), local_index,
                                         None))
        hot = np.array([3 + 7 * step_no, 2 + 5 * step_no,
                        rng.integers(0, num_keys)])
        keys = {"a": hot[np.arange(B) % 3].astype(np.int32),
                "b": rng.integers(0, num_keys, B).astype(np.int32)}
        keys["b"][:2] = 10, 12
        # this step's replicas: the keys at `held` of the sampled
        # positions, and (where any) one hot key of `a` and one of `b`
        replicas = rng.permutation(np.unique(neg))[:held] \
            if num_keys == 24 else rng.permutation(neg.ravel())[:held]
        if held:
            replicas = np.union1d(replicas, [hot[1], keys["b"][3]])
        cache_row = np.full(num_keys, -1, np.int32)
        cache_row[replicas] = rng.permutation(CACHE)[:len(replicas)]
        ref_keys = dict(keys, neg=neg)
        counts = {r: int((cache_row[k] >= 0).sum())
                  for r, k in ref_keys.items()}
        if num_keys == 24:  # several positions name one replica row
            assert counts["neg"] > held + 2
        else:
            assert held <= counts["neg"] <= held + 2
        n_held += sum(counts.values())
        n_chunks += sum(-(-c // min(ref_keys[r].size, K))
                        for r, c in counts.items())
        tables = (jnp.asarray(place), jnp.asarray(cache_row), shard)
        want_pools, want_stat, want_loss = before(
            want_pools, want_stat,
            (jnp.asarray(owner), jnp.asarray(slot)) + tables[1:],
            {r: jnp.asarray(k) for r, k in ref_keys.items()}, dim)
        pools, got_stat, got_loss = body(
            pools, got_stat, tables, keys, local_index, None, rng_key,
            None, jnp.float32(LR), jnp.float32(EPS))
        assert np.isclose(float(got_loss), float(want_loss),
                          rtol=1e-6, atol=0), step_no
    assert np.asarray(got_stat).tolist() == \
        np.asarray(want_stat).tolist() + [n_held, n_chunks]
    got, want = [np.asarray(x) for x in pools[0]], \
        [np.asarray(x) for x in want_pools]
    assert (got[0] != start[0]).any()
    assert bool((got[2] != start[2]).any()) is bool(held)
    for i in (0, 2):  # main and delta
        np.testing.assert_allclose(got[i], want[i], rtol=0, atol=1e-6)
        untouched = (want[i] == start[i]).all(axis=2)
        assert ((got[i] == start[i]).all(axis=2) == untouched).all()
    assert got[1].tobytes() == start[1].tobytes() == want[1].tobytes()


@pytest.mark.parametrize("no_replicas, held", [
    (True, 0), (False, 0), (False, 3), (False, "all")])
def test_score_of_out_of_bounds_positions(no_replicas, held, monkeypatch):
    """The gather-only score program takes the step's read half: a
    position that is nowhere scores as a zero embedding, one on shard -1
    as shard 0's row, in either variant; and one whose key the worker's
    shard holds a replica of (`held` keys of the batch, or all: two
    chunks of 4 a role) as `cache + delta`."""
    num_keys, L = 24, 8
    dim = L // 2
    monkeypatch.setattr(fused, "SIDE_ROWS", 4)
    rng = np.random.default_rng(9)
    owner, slot, place = _tables(num_keys, rng)
    main = _pools(num_keys, L, rng)[0][0]
    cache, delta = (jnp.asarray(rng.normal(size=(1, num_keys, L)).astype(
        np.float32) * scale) for scale in (1.0, 0.1))
    pools = ((main, cache, delta),)

    def score_fn(embs, aux):
        return ((embs["a"] * embs["b"]).sum(-1) * aux).sum()

    score = fused.make_device_routed_score(
        score_fn, {"a": 0, "b": 0}, {"a": dim, "b": dim}, ["a", "b"],
        no_replicas=no_replicas)
    keys = {"a": np.arange(B, dtype=np.int32) * 3 % num_keys,
            "b": np.arange(B, dtype=np.int32) + 2}
    for k in keys.values():
        assert (slot[k] == OOB).any() and (owner[k] == -1).any()
    named = np.unique(np.concatenate(list(keys.values())))
    replicas = named if held == "all" else rng.permutation(named)[:held]
    cache_row = np.full(num_keys, -1, np.int32)
    cache_row[replicas] = rng.permutation(num_keys)[:len(replicas)]
    aux = jnp.asarray(rng.normal(size=B).astype(np.float32))
    tables = (jnp.asarray(place), jnp.asarray(cache_row), jnp.int32(0))
    got = score(pools, tables, keys, aux, jnp.float32(0.5))
    rows = {r: np.where(
        (cache_row[k] >= 0)[:, None],
        np.asarray(cache + delta)[0, cache_row[k]],
        np.asarray(main.at[owner[k], slot[k]].get(
            mode="fill", fill_value=0))) for r, k in keys.items()}
    want = 0.5 + score_fn({r: v[..., :dim] for r, v in rows.items()}, aux)
    assert np.isclose(float(got), float(want), rtol=1e-6, atol=0)
    # and the zero rows were read as zeros, not as the clamp's row
    zero = {r: v[(slot[keys[r]] == OOB) & (cache_row[keys[r]] < 0)]
            for r, v in rows.items()}
    if held != "all":
        assert all(len(z) and not z.any() for z in zero.values())
