"""Pools of several shards: the fused step as a per-chip program
(`ops/fused.py _PoolProgram`: shard_map over the kv axis, of the roles
named by key the rows that lie OFF the worker's chip exchanged, a chunk
of `EXCHANGE_BYTES` at a time, the write-back kernel inside) against the
same step as ONE program over the global pools (GSPMD, what every step on
several shards was before, and what a step whose negatives may lie
anywhere still is), on four virtual devices and hand-made tables: keys
whose main copy lies on another shard, keys replicated on the worker's
shard, keys that are nowhere, duplicates in a batch and one key in two
roles; batches of several exchange chunks, of none and of nothing but;
and the runner's choice between the two forms."""
import itertools
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from adapm_tpu.core.store import OOB
from adapm_tpu.ops import fused

S, KEYS, SLOTS, CACHE, B, N = 4, 96, 32, 8, 8, 5
LR, EPS = 0.1, 1e-10
# the probe's float32 limits (benchmarks/traffic/train-app-zipf-fresh.json)
LOSS_GAP, NORM_GAP, DIFF_SHARE = 2.5e-6, 5e-6, 2e-5


def _loss(embs, aux):
    pos = (embs["a"] * embs["b"]).sum(-1)
    neg = (embs["a"][:, None, :] * embs["neg"]).sum(-1)
    # a weight a position: a loss that is not symmetric in [b, k]
    w = 1.0 + jnp.arange(neg.size, dtype=jnp.float32).reshape(
        neg.shape) / neg.size
    return (jax.nn.softplus(-pos) + (w * jax.nn.softplus(neg)).sum(-1)).mean()


@functools.lru_cache(maxsize=None)
def _mesh():
    return Mesh(np.asarray(jax.devices()[:S]), ("kv",))


def _placement(replicas: bool):
    """owner[key], slot[key] and cache_row[shard, key]: every key its own
    slot of its owner's shard, a ninth of the keys nowhere (`OOB`); with
    `replicas` each shard also holds replicas of a few keys that other
    shards own."""
    rng = np.random.default_rng(3)
    owner = rng.integers(0, S, KEYS).astype(np.int32)
    slot = np.full(KEYS, OOB, np.int32)
    for s in range(S):
        mine = np.flatnonzero(owner == s)
        slot[mine] = rng.permutation(SLOTS)[:len(mine)]
    slot[np.arange(KEYS) % 9 == 4] = OOB
    cache_row = np.full((S, KEYS), -1, np.int32)
    if replicas:
        for s in range(S):
            others = np.flatnonzero((owner != s) & (slot != OOB))
            held = rng.choice(others, CACHE - 2, replace=False)
            cache_row[s, held] = rng.permutation(CACHE)[:len(held)]
    return owner, slot, cache_row


def _pools(L):
    rng = np.random.default_rng(4)
    rows = NamedSharding(_mesh(), P("kv"))

    def pool(n, scale):
        x = rng.normal(size=(S, n, L)).astype(np.float32) * scale
        x[..., L // 2:] = np.abs(x[..., L // 2:]) * 1e-3
        return jax.device_put(x, rows)
    return ((pool(SLOTS, 1.0), pool(CACHE, 1.0), pool(CACHE, 0.1)),)


# positions one exchange chunk takes in the batches of several chunks
# (`fused.EXCHANGE_BYTES` set to as many rows' embedding columns)
CHUNK = 3


def _away(shard, owner, slot, cache_row):
    """Whether a key's row lies off worker `shard`'s chip: it is
    somewhere, another shard owns it and `shard` holds no replica."""
    return (slot != OOB) & (owner != shard) & (cache_row[shard] < 0)


def _batch(shard, owner, slot, cache_row, named="mixed"):
    """Keys of both named roles: `a` three hot keys (one owned by another
    shard, one by the worker's, one the worker's shard holds a replica
    of, where it holds any), each several times; `b` a draw that repeats
    keys of `a` (one key in two roles) and names a key that is nowhere.
    `named`: "chunks": 5 positions of `a` and all 8 of `b` lie off the
    worker's chip, two and three chunks of `CHUNK`, the last of each
    partly filled; "here": none does (owned, replicated, nowhere);
    "away": every one does."""
    rng = np.random.default_rng(5 + shard)
    live = slot != OOB
    away = np.flatnonzero(_away(shard, owner, slot, cache_row))
    here = np.flatnonzero(live & (owner == shard))
    held = np.flatnonzero(cache_row[shard] >= 0)
    if named != "mixed":
        stays = np.concatenate([here, held, np.flatnonzero(~live)[:1]])
        a, b = {"chunks": (np.concatenate([away[:4], away[:1], here[:3]]),
                           away[rng.integers(0, 6, B)]),
                "here": (stays[:B], stays[rng.integers(0, len(stays), B)]),
                "away": (away[:B], away[rng.integers(0, len(away), B)])
                }[named]
        a = rng.permutation(a)
        return {"a": a.astype(np.int32), "b": b.astype(np.int32)}
    hot = np.array([away[0], here[0], (held if len(held) else away)[-1]])
    a = hot[np.arange(B) % 3]
    b = rng.integers(0, KEYS, B)
    b[:3] = hot[1], hot[2], np.flatnonzero(~live)[0]
    assert len(np.unique(owner[np.concatenate([a, b])])) == S
    return {"a": a.astype(np.int32), "b": b.astype(np.int32)}


@functools.lru_cache(maxsize=None)
def _programs(no_replicas: bool, L: int):
    """(per-chip, one program over the global pools): the step's two
    forms on pools of several shards; the worker's shard is an operand,
    so its four values share them."""
    roles = {"a": 0, "b": 0, "neg": 0}
    make = functools.partial(
        fused.make_device_routed_step, _loss, roles,
        {r: L // 2 for r in roles}, (), "neg", (B, N), no_replicas)
    return make(), make(neg_local=False)


def _gaps(got, want, start):
    """The gap of the norms of the change, and the norm of the changes'
    difference, over the reference's norm (embedding columns)."""
    half = start.shape[-1] // 2
    p = (got - start).astype(np.float64)[..., :half]
    q = (want - start).astype(np.float64)[..., :half]
    nq = np.linalg.norm(q)
    return abs(np.linalg.norm(p) - nq) / nq, np.linalg.norm(p - q) / nq


VARIANTS = ["xla", "xla-replicas", "kernel", "kernel-replicas"]


@pytest.mark.parametrize(
    "variant,shard,named",
    [(v, s, "mixed") for v, s in itertools.product(VARIANTS, range(S))]
    + [(v, 1, named) for named in ("chunks", "here", "away")
       for v in ("xla", "xla-replicas")]
    + [("kernel-replicas", 2, "chunks")])
def test_per_chip_step_equals_the_step_over_global_pools(
        variant, shard, named, monkeypatch, kernel_cache):
    """Two steps of worker `shard`: the pools (main, cache, delta), each
    loss and the locality counts of the per-chip step against those of
    the one GSPMD program. The counts are equal; the losses and the
    pools stay inside the probe's limits (here, with this loss, all come
    out bitwise equal; they are two programs with fusions of their own,
    and the KGE step's rows came out an ulp apart). `kernel`: the
    write-back kernel's interpret build INSIDE the map (the rule forced
    as on a TPU: a chip's block is one shard), the global program
    writing back through XLA. `named` other than "mixed" (`_batch`):
    exchange chunks of `CHUNK` positions, and the step's own count of
    the positions off the worker's chip and of the blocks it summed
    against the hand-made placement's."""
    no_replicas = "replicas" not in variant
    L, make, traced = 8, _programs, []
    if named != "mixed":
        make = _programs.__wrapped__  # traced here, patched
    if variant.startswith("kernel"):
        L, make = 256, _programs.__wrapped__
        monkeypatch.setattr(fused, "writeback_uses_kernel",
                            functools.partial(fused.writeback_uses_kernel,
                                              backend="tpu"))
        kernel_writeback = fused._kernel_writeback
        monkeypatch.setattr(
            fused, "_kernel_writeback", lambda main, *a: traced.append(
                main.shape) or kernel_writeback(main, *a))
    if named != "mixed":
        monkeypatch.setattr(fused, "EXCHANGE_BYTES", CHUNK * (L // 2) * 4)
    owner, slot, cache_row = _placement(not no_replicas)
    mapped, whole = make(no_replicas, L)
    rep = NamedSharding(_mesh(), P())
    put = lambda x: jax.device_put(x, rep)  # noqa: E731
    tables = (put(fused.place_words(owner, slot, fused.place_bits(SLOTS))),
              put(cache_row[shard]), put(np.int32(shard)))
    # the worker's resident keys, as `_local_neg_index` builds them
    # (a key that is nowhere is no shard's: its place word says so)
    local = np.flatnonzero(((owner == shard) & (slot != OOB))
                           | (cache_row[shard] >= 0))
    padded = np.full(64, np.iinfo(np.int32).max, np.int32)
    padded[:len(local)] = local
    local_index = (put(padded), put(np.int32(len(local))))
    batch = _batch(shard, owner, slot, cache_row, named)
    keys = {r: put(k) for r, k in batch.items()}
    start = [np.asarray(x) for x in _pools(L)[0]]
    out = {}
    for name, fn in (("mapped", mapped), ("whole", whole)):
        # the whole accumulator of a runner on several shards: locality,
        # replica positions and chunks, then the exchange's counts
        pools, stat, losses = _pools(L), put(np.zeros(10, np.int32)), []
        for rng_key in jax.random.split(jax.random.PRNGKey(7 + shard), 2):
            pools, stat, loss = fn(pools, stat, tables, keys, local_index,
                                   None, rng_key, None, jnp.float32(LR),
                                   jnp.float32(EPS))
            losses.append(float(loss))
        out[name] = ([np.asarray(x) for x in pools[0]],
                     np.asarray(stat).tolist(), losses)
    # each form was compiled as what it says: over the mesh, or not
    assert list(mapped._forms) == [_mesh()] and list(whole._forms) == [None]
    # the kernel wrote the three roles' main rows, on a chip's block
    assert traced == [(1, SLOTS, L)] * 3 * variant.startswith("kernel")
    (got, got_stat, got_loss), (want, want_stat, want_loss) = \
        out["mapped"], out["whole"]
    assert got_stat[:6] == want_stat[:6]
    # 2 steps of 2 * B named rows and B * N sampled ones, which are local
    assert got_stat[0] == 2 * (2 * B + B * N) and got_stat[1] >= 2 * B * N
    # the exchange's counts: the positions off the worker's chip, and of
    # each role (a, b, the sampled one) the positions of its blocks; the
    # one program over the global pools exchanges nothing of its own
    off = {r: int(_away(shard, owner, slot, cache_row)[k].sum())
           for r, k in batch.items()}
    chunk = B if named == "mixed" else CHUNK
    assert got_stat[6:] == [2 * sum(off.values())] + [
        2 * -(-off[r] // chunk) * chunk for r in "ab"] + [0]
    assert want_stat[6:] == [0] * 4
    if named != "mixed":
        assert sum(off.values()) == {"chunks": 5 + B, "here": 0,
                                     "away": 2 * B}[named]
    for p, q in zip(got_loss, want_loss):
        assert abs(p - q) <= LOSS_GAP * abs(q)
    # main rows of the worker's shard (the sampled role's) and of every
    # shard that owns a named key the worker's holds no replica of:
    # every shard's ("mixed"), the worker's alone ("here")
    named_keys = np.concatenate(list(batch.values()))
    owners = set(owner[named_keys[(slot[named_keys] != OOB) & (
        cache_row[shard, named_keys] < 0)]].tolist()) | {shard}
    assert len(owners) == {"mixed": S, "here": 1}.get(named, len(owners))
    moved = (want[0] != start[0]).any(axis=(1, 2)).tolist()
    assert moved == [s in owners for s in range(S)]
    changed = [0] if no_replicas else [0, 2]  # main; and the delta pool
    for i in changed:
        norm_gap, diff_share = _gaps(got[i], want[i], start[i])
        assert norm_gap <= NORM_GAP and diff_share <= DIFF_SHARE, i
        untouched = (want[i] == start[i]).all(axis=2)
        assert ((got[i] == start[i]).all(axis=2) == untouched).all()
    for i in set(range(3)) - set(changed):
        assert got[i].tobytes() == start[i].tobytes() == want[i].tobytes()
    if not no_replicas:
        # the replicas that the OTHER shards hold (of their own workers)
        # are no business of this worker's step: only its own shard's
        # delta block moved
        moved = (got[2] != start[2]).any(axis=(1, 2))
        assert moved.tolist() == [s == shard for s in range(S)]


def _sums_over_the_axis(jaxpr, in_loop=False):
    """(shape summed, whether inside a loop) of every sum over a mesh
    axis in `jaxpr` and the jaxprs beneath it."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("psum"):
            found += [(v.aval.shape, in_loop) for v in eqn.invars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _sums_over_the_axis(
                sub, in_loop or eqn.primitive.name == "while")
    return found


def test_per_chip_step_exchanges_the_named_rows_only(monkeypatch):
    """The per-chip step sums over the axis no role's whole `[B, L/2]`
    array: of each named role a `[CHUNK, L/2]` block of embedding
    columns and one of gradients, inside the loops over the positions
    off the worker's chip, and the loss's scalar outside; nothing of
    the sampled role's shape."""
    monkeypatch.setattr(fused, "EXCHANGE_BYTES", CHUNK * 4 * 4)
    mapped, _ = _programs.__wrapped__(False, 8)
    shape = lambda dims, dtype, spec=P(): jax.ShapeDtypeStruct(  # noqa
        dims, dtype, sharding=NamedSharding(_mesh(), spec))
    i32 = lambda *dims: shape(dims, jnp.int32)  # noqa: E731
    pools = tuple(shape((S, n, 8), jnp.float32, P("kv"))
                  for n in (SLOTS, CACHE, CACHE))
    args = ((pools,), i32(4), (i32(KEYS), i32(KEYS), i32()),
            {"a": i32(B), "b": i32(B)}, (i32(64), i32()), None,
            shape((2,), jnp.uint32), None, shape((), jnp.float32),
            shape((), jnp.float32))
    text = mapped.lower(*args).as_text()
    assert "@jit_step" in text
    assert text.count("stablehlo.all_reduce") == 5
    sums = _sums_over_the_axis(
        mapped._form(args[0], args[1:]).trace(*args).jaxpr.jaxpr)
    assert sorted(sums) == sorted([((CHUNK, 4), True)] * 4 + [((), False)])


def test_fallback_draw_takes_the_global_program_and_follows_the_reference():
    """A runner whose negative population has no key resident on its
    shard draws from the whole population (`_li_fallback`), whose rows
    lie on other shards: it takes the step as one program over the global
    pools, its steps follow the numpy reference, and neither the kernel's
    rows nor the exchange's bytes are counted for it. The app's own
    runner of the same shard, whose negatives are local, takes the
    per-chip step and counts its exchange."""
    import test_kv_shards_reference as kv
    from adapm_tpu.models.kge import make_kge_loss
    from adapm_tpu.ops import DeviceRoutedRunner
    run = kv._open("relocation_only")
    try:
        srv = run.srv
        ents = np.arange(kv.E + kv.R)
        init = np.asarray(srv.read_main(ents)).reshape(len(ents), -1).copy()
        # entities that other shards own, and stay there: no intent
        elsewhere = run.ekey(np.flatnonzero(
            srv.ab.owner[run.ekey(np.arange(kv.E))] != 0)[:40])
        roles = {"s": run.ent_class, "r": run.rel_class,
                 "o": run.ent_class, "neg": run.ent_class}
        runner = DeviceRoutedRunner(
            srv, make_kge_loss("complex", 0.0, 0.0), roles,
            {r: kv.W for r in roles}, shard=0, neg_role="neg",
            neg_shape=(kv.B, kv.N), neg_population=elsewhere, seed=3)
        exchanged = srv.obs.find("fused.exchange_bytes_total")
        rows = srv.obs.find("fused.writeback_rows_total")
        rec = kv._Recorder([])
        rng = np.random.default_rng(13)
        for _ in range(2):
            t = kv._draw(rng, kv.B)
            batch = {"s": run.ekey(t[:, 0]), "r": run.rkey(t[:, 1]),
                     "o": run.ekey(t[:, 2])}
            fn = runner._step_program  # record at the chosen program

            def recording(no_replicas, _fn=fn):
                return rec._wrap(_fn(no_replicas))
            runner._step_program = recording
            runner(batch, None, kv.LR)
            runner._step_program = fn
        assert runner._li_fallback
        whole = [k for k in runner._programs if ("neg_local", False) in k]
        assert len(whole) == 1
        assert list(runner._programs[whole[0]]._forms) == [None]
        assert [len(st["local"]) for st in rec.steps] == [40, 40]
        assert rows.snap() == 2 * kv.B * (3 + kv.N)
        assert exchanged.snap() == 0
        kv._compare(run, rec, init)

        own = run.device_runner(0)
        t = kv._draw(rng, kv.B)
        batch = {"s": run.ekey(t[:, 0]), "r": run.rkey(t[:, 1]),
                 "o": run.ekey(t[:, 2])}
        # the positions whose row lies off worker 0's chip, by the
        # addressbook as the step will find it
        off = {r: int(((srv.ab.owner[k] != 0)
                       & (srv.ab.cache_slot[0, k] < 0)).sum())
               for r, k in batch.items()}
        own(batch, None, kv.LR)
        assert not own._li_fallback
        assert list(own._step_fn_norep._forms) == [srv.ctx.mesh]
        assert exchanged.snap() == 4  # the loss; the rest at the drain
        own.locality_counts()
        assert srv.obs.find("fused.exchange_positions").snap() \
            == sum(off.values()) > 0
        # what the device counted: of each named role that has a row off
        # the chip one chunk (a role's B positions fit one) of embedding
        # columns out and of gradients back, and the loss
        assert exchanged.snap() == 4 + 2 * sum(
            kv.B * kv.W * 4 for r in off if off[r])
    finally:
        run.srv.shutdown()
