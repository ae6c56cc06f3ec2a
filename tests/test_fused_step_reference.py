"""The fused step's layout is not its semantics: the step body as it is
(the sampled role's rows sample-major, the gather clamped and the mask on
the embedding columns) against a plain `jax.numpy` step written here by
the formulas the step had before it (whole rows gathered with
`mode="fill"`, the sampled role batch-major, AdaGrad update rows and
`.at[].add(mode="drop")`), on tables that send positions out of bounds
and to shard -1."""
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
    """Every key its own slot; a seventh of the keys nowhere (`OOB`: a
    cold tier row), a fifth on shard -1 (jnp's wrap: shard 0)."""
    owner = np.zeros(num_keys, np.int32)
    slot = rng.permutation(num_keys).astype(np.int32)
    keys = np.arange(num_keys)
    slot[keys % 7 == 3] = OOB
    owner[keys % 5 == 2] = -1
    return owner, slot


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
    # the variant with replica pools (none held): batch-major as ever
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
    owner, slot = _tables(num_keys, rng)
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
    tables = (jnp.asarray(owner), jnp.asarray(slot),
              jnp.full(num_keys, -1, jnp.int32), shard)
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
            want_main, want_stat, tables[0], tables[1], shard,
            {r: jnp.asarray(k) for r, k in ref_keys.items()}, dim)
        pools, got_stat, got_loss = body(
            pools, got_stat, tables, keys, local_index, alias, rng_key,
            None, jnp.float32(LR), jnp.float32(EPS))
        # the replica variant is another program (three gathers and a
        # select before the loss): equal to an ulp, as it always was
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


@pytest.mark.parametrize("no_replicas", [True, False])
def test_score_of_out_of_bounds_positions(no_replicas):
    """The gather-only score program takes the step's read half: a
    position that is nowhere scores as a zero embedding, one on shard -1
    as shard 0's row, in either variant."""
    num_keys, L = 24, 8
    dim = L // 2
    rng = np.random.default_rng(9)
    owner, slot = _tables(num_keys, rng)
    pools = _pools(num_keys, L, rng)
    main = pools[0][0]

    def score_fn(embs, aux):
        return ((embs["a"] * embs["b"]).sum(-1) * aux).sum()

    score = fused.make_device_routed_score(
        score_fn, {"a": 0, "b": 0}, {"a": dim, "b": dim}, ["a", "b"],
        no_replicas=no_replicas)
    keys = {"a": np.arange(B, dtype=np.int32) * 3 % num_keys,
            "b": np.arange(B, dtype=np.int32) + 2}
    for k in keys.values():
        assert (slot[k] == OOB).any() and (owner[k] == -1).any()
    aux = jnp.asarray(rng.normal(size=B).astype(np.float32))
    tables = (jnp.asarray(owner), jnp.asarray(slot),
              jnp.full(num_keys, -1, jnp.int32), jnp.int32(0))
    got = score(pools, tables, keys, aux, jnp.float32(0.5))
    rows = {r: main.at[owner[k], slot[k]].get(mode="fill", fill_value=0)
            for r, k in keys.items()}
    want = 0.5 + score_fn({r: v[..., :dim] for r, v in rows.items()}, aux)
    assert np.isclose(float(got), float(want), rtol=1e-6, atol=0)
    # and the zero rows were read as zeros, not as the clamp's row
    zero = {r: np.asarray(v)[slot[keys[r]] == OOB] for r, v in rows.items()}
    assert all(len(z) and not z.any() for z in zero.values())
