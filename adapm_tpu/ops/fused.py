"""Fused embedding-update steps: the TPU-native hot path.

Every reference app's inner loop is the same triad: Pull a handful of rows,
run a small dense compute + AdaGrad, Push additive updates (mf/update.h:32-70,
word2vec.cc:718-743, kge.cc:415-530). Translating that per-key loop would
leave the MXU idle; instead the whole triad over a *batch* of data points is
ONE jitted program on the sharded pools:

    gather rows -> model loss -> grad -> AdaGrad transform -> scatter-add

Updates remain *additive deltas*, so the parameter-manager semantics
(concurrent pushes merge at the main copy; replica writes land in the delta
pool and flow back through sync rounds) are preserved exactly — the fused
step is a batched `Push` in PM terms, not a bypass.

Value-row layout follows the reference convention of carrying optimizer
state inside the value (`param_len = 2*rank = [factor | adagrad]`,
matrix_factorization.cc:695-697): row = [emb (D) | adagrad acc (D)].

Routing (which shard/slot serves each key) is resolved IN the program
(DeviceRouter): the Addressbook tables are mirrored into HBM as two
words a key (the main copy's PLACE, owner and slot in one int32:
`place_words`, `decode_place`; the worker shard's cache-slot row),
brought up to date lazily when the
planner changes placement (topology_version; patched by the keys the
addressbook's journal lists as changed, rebuilt from the tables where
it cannot say), and the jitted step resolves routes
itself by the policy of `Server._route` (prefer a local replica, else the
owner row) — per step the host ships only raw keys, and relocation and
replication decisions made by the planner between steps are picked up with
the next refresh. Table lookups are trivial device gathers, and a placement
change costs the host by the keys it moved, not by the tables' size.

Negative sampling can run on device too (the `neg_role`/`neg_shape`
parameters of DeviceRoutedRunner / make_device_routed_step): drawing uniform
positions into a device mirror of the locally-resident key index is exactly
the Local sampling scheme (core/sampling.py) executed in-program,
so no sample keys cross to the device either. A runner built
without `neg_role` takes its negatives from the caller like any role.
"""
from __future__ import annotations

from typing import (Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.store import OOB
from ..device import default_port
from ..exec import dispatch_gate
from ..parallel.mesh import KV_AXIS
from . import writeback

# sharded-dispatch serialization (adapm_tpu/exec, docs/EXECUTOR.md):
# the fused step is a sharded program like every store op — its
# dispatch funnels through the same process-wide gate so two servers
# on one device set can never interleave per-device enqueue orders
_GATE = dispatch_gate()


def _key_dtype(num_keys: int):
    """Key-upload dtype: int32 halves the transfer and is exact as long as
    every key fits; beyond 2^31 keys fall back to int64."""
    return np.int32 if num_keys <= 2**31 else np.int64


def _mark_fused_writes(server, shard: int, role_class, role_keys,
                       skip_roles=()) -> None:
    """Dirty-delta write tracking for a fused step's host-known roles
    (caller holds the server lock): resolve each role's keys through the
    addressbook — the same tables the step's routes come from, so the
    marking is exact — and record the scatter in the stores' write
    epochs (ShardedStore.mark_routed_writes). `skip_roles`: frozen roles
    whose rows the step never updates."""
    ab = server.ab
    for r, keys in role_keys.items():
        if r in skip_roles:
            continue
        k = np.asarray(keys, dtype=np.int64).ravel()
        server.stores[role_class[r]].mark_routed_writes(
            shard, ab.cache_slot[shard, k], ab.owner[k], ab.slot[k])


# Replica positions one chunk of the replica variant's side path takes
# (`_replica_side`): a role of no more positions than this is one chunk
# whatever it holds, the 131,072 negatives of a KGE step whose replicas
# number several hundred are one chunk of this many rows beside the
# row-wide gather from main. A chunk costs by its rows, replicas or
# padding alike, and one chunk more costs nothing measurable (on a v5e
# at 8 KB rows 1,024 beat 2,048 and 4,096: PERF.md section 6, PR 36).
SIDE_ROWS = 1024


def _chunk_rows(n: int) -> int:
    """Positions one chunk of the side path takes, of a role of `n`."""
    return min(n, SIDE_ROWS)


class _ReplicaSide(NamedTuple):
    """The replica positions of one role in one step, compacted: what
    the replica variant's side path walks (`_for_replica_chunks`), the
    only code of a step that indexes the cache and delta pools."""
    sh: jnp.ndarray     # [n] the replicas' shard, by flat position
    sl: jnp.ndarray     # [n] their slot; out of bounds: no replica here
    here: jnp.ndarray   # [n] whether the position reads its replica
    order: jnp.ndarray  # the replica positions ascending, then `n`s
    count: jnp.ndarray  # how many there are, an int32 scalar


def _compact(here, rows: int):
    """The flat positions where `here` holds, ascending, then `n`s to
    whole chunks of `rows` (a slice of the last chunk must not slide
    back), and their count, an int32 scalar."""
    n = here.shape[0]
    order = jnp.sort(jnp.where(here, jax.lax.iota(jnp.int32, n), n))
    order = jnp.pad(order, (0, -n % rows), constant_values=n)
    return order, jnp.sum(here, dtype=jnp.int32)


def _replica_side(cache, c_sh, c_sl) -> _ReplicaSide:
    """Compact a role's replica positions: those whose replica
    coordinates `(c_sh, c_sl)` lie inside the pool `cache` (and so
    inside `delta`, its twin). `_route_on_device` sends every other
    position's slot out of bounds, and `_on_this_chip` those of a
    replica that another chip holds."""
    sh, sl = c_sh.reshape(-1), c_sl.reshape(-1)
    here = _in_bounds(sh, cache.shape[0]) & _in_bounds(sl, cache.shape[1])
    return _ReplicaSide(sh, sl, here,
                        *_compact(here, _chunk_rows(sl.shape[0])))


def _replica_chunks(count, n: int):
    """Chunks of the side path that `count` replica positions among `n`
    take."""
    return (count + (_chunk_rows(n) - 1)) // _chunk_rows(n)


def _for_replica_chunks(side: _ReplicaSide, fn, carry):
    """`carry = fn(idx, sh, sl, carry)` for each chunk of the role's
    replica positions, in their order: `idx` are `_chunk_rows(n)`
    flat positions and `(sh, sl)` their replica coordinates; past the
    last replica position `idx` is `n` and `sl` out of bounds, for `fn`
    to drop. A loop whose count the program reads from its input: none
    where the role holds no replica here (every chip but the worker's),
    one while the replica positions fit a chunk, as many as it takes
    otherwise. The carry (the gathered rows, the delta pool) is updated
    in place."""
    n = side.sl.shape[0]
    k = _chunk_rows(n)

    def chunk(t, carry):
        idx = jax.lax.dynamic_slice(side.order, (t * k,), (k,))
        sh = side.sh.at[idx].get(mode="clip")
        sl = side.sl.at[idx].get(mode="fill", fill_value=OOB)
        return fn(idx, sh, sl, carry)

    return jax.lax.fori_loop(0, _replica_chunks(side.count, n), chunk,
                             carry)


def _patch_replica_rows(rows, cache, delta, side: _ReplicaSide):
    """The rows gathered from main with `cache + delta` at the role's
    replica positions: the replica's value as a Pull reads it."""
    L = rows.shape[-1]

    def patch(idx, sh, sl, flat):
        value = cache.at[sh, sl].get(mode="fill", fill_value=0) \
            + delta.at[sh, sl].get(mode="fill", fill_value=0)
        return flat.at[idx].set(value, mode="drop")

    return _for_replica_chunks(side, patch, rows.reshape(-1, L)) \
        .reshape(rows.shape)


def _replica_writeback(delta, side: _ReplicaSide, g, acc, lr, eps):
    """The additive AdaGrad updates of the role's replica positions into
    the delta pool (replica writes land there and flow back through sync
    rounds): update rows are formed for a chunk's positions only."""
    g, acc = (x.reshape(-1, x.shape[-1]) for x in (g, acc))

    def add(idx, sh, sl, delta):
        upd = _adagrad_update(g.at[idx].get(mode="clip"),
                              acc.at[idx].get(mode="clip"), lr, eps)
        return delta.at[sh, sl].add(upd, mode="drop")

    with jax.named_scope("adapm_scatter_add"):
        return _for_replica_chunks(side, add, delta)


# Bytes of one block of the per-chip step's exchange (`_exchange`): the
# positions of a chunk follow from the role's row length, a static
# property of the compiled variant: 2,048 positions at the 128 embedding
# columns of a 1 KB row, 256 at the 1,024 of an 8 KB row. A chunk costs
# by its positions, off the chip or padding alike, and most of it is
# the set, XLA's scatter, not the sum: on a v5e 2x2 blocks of 1 MB beat
# 2, 4, 8 and 16 MB at both row lengths by a tenth at most, and waste
# the least of the last chunk (`scripts/exchange_probe.py`; PERF.md
# section 6, PR 43).
EXCHANGE_BYTES = 1 << 20


class _Away(NamedTuple):
    """The positions of one role named by key whose row lies OFF the
    worker's chip, compacted: what the per-chip step's exchange sums
    over the axis (`_exchange`). Computed from the global route, so the
    same on every chip."""
    order: jnp.ndarray  # those flat positions ascending, then `n`s
    count: jnp.ndarray  # how many there are, an int32 scalar
    rows: int           # positions one chunk takes (static)

    @property
    def chunks(self):
        return (self.count + (self.rows - 1)) // self.rows


def _away(whole, main, shard, axis, dim: int) -> _Away:
    """Compact the positions whose row another chip than the worker's
    holds: the global route `whole` is in bounds (a key that is nowhere
    and a padding position read zeros on every chip, and a position
    that reads the worker shard's replica has main's slot out of
    bounds: `_route_on_device`) and names another shard than `shard`.
    `main` is a chip's block, `[1, slots, L]`."""
    sh, sl = whole[0].reshape(-1), whole[1].reshape(-1)
    n = sl.shape[0]
    width = jax.lax.axis_size(axis)
    off = _in_bounds(sh, width) & _in_bounds(sl, main.shape[1]) \
        & (sh != shard) & (sh != shard - width)
    rows = min(n, max(1, EXCHANGE_BYTES // (4 * dim)))
    return _Away(*_compact(off, rows), rows)


def _exchange(x, away: _Away, axis):
    """`x` (a role's `[..., dim]` values by position) with its rows at
    the positions `away` lists summed over the axis, a chunk at a time:
    each chip's own rows of the chunk (zeros past the last position),
    ONE `psum` of the `[rows, dim]` block, and the sum set back at
    those positions. Every other position keeps the chip's own value.
    A loop whose count the program reads from its input, the same on
    every chip: none where every row named lies on the worker's chip."""
    flat = x.reshape(-1, x.shape[-1])

    def chunk(t, flat):
        idx = jax.lax.dynamic_slice(away.order, (t * away.rows,),
                                    (away.rows,))
        block = jax.lax.psum(
            flat.at[idx].get(mode="fill", fill_value=0), axis)
        return flat.at[idx].set(block, mode="drop")

    with jax.named_scope("adapm_exchange"):
        return jax.lax.fori_loop(0, away.chunks, chunk, flat) \
            .reshape(x.shape)


def writeback_uses_kernel(main, backend: str = None) -> bool:
    """Which row-mover the write-back into this MAIN pool is compiled
    with, in either variant (the replica variant's delta rows, a
    side-path chunk at a time, are always XLA's: `_replica_writeback`):
    the Pallas kernel (pallas_kernels
    .scatter_adagrad_sorted_rows, which forms the AdaGrad update rows
    itself) where the backend (jax's default unless given) is a TPU and
    the pool is ONE float32 shard on the step's device (`[1, slots, L]`:
    a pool of one shard, or a chip's own block of a pool of several
    inside the per-chip step, `_PoolProgram`; a Pallas call does not
    partition under GSPMD, so a program over a global pool of several
    shards never takes it) whose rows the kernel can copy and form: the
    row's two halves, [emb | accumulator], each a whole number of the
    128 lanes (L a multiple of 256), slots of the 8 rows of a tile and
    no more of them than a code of the kernel names (`writeback
    .SLOT_MASK`: 2^24, at 1 KB a row the chip's whole HBM);
    `_adagrad_update` and XLA's scatter-add everywhere else. A static
    property of the compiled variant, read from the pool's shape and
    dtype."""
    backend = jax.default_backend() if backend is None else backend
    return (backend == "tpu" and main.ndim == 3
            and main.shape[0] == 1 and main.dtype == jnp.float32
            and main.shape[2] % 256 == 0 and main.shape[1] % 8 == 0
            and main.shape[1] <= writeback.SLOT_MASK + 1)


def _kernel_writeback(main, o_sh, o_sl, g, acc, lr, eps):
    """The replica-free write-back of one role through the kernel: the
    same rows as `main.at[o_sh, o_sl].add(_adagrad_update(g, acc, lr,
    eps), mode="drop")`, in the order of their slots. Gradients and
    accumulators (as gathered before any write-back of the step) are
    brought into that order and handed to the kernel, which forms each
    chunk's update rows in VMEM: the update rows are never an array.
    One kernel call for each `writeback.MAX_POSITIONS` positions. The
    sort and the permuting gathers are the write-back's cost and carry
    its scope."""
    n_slots, L = main.shape[1:]
    # one shard: a row lands iff its shard index is 0 (or wraps to it)
    o_sl = jnp.where((o_sh == 0) | (o_sh == -1), o_sl, OOB)
    rows = writeback.chunk_rows_for(L)
    g, acc = (x.reshape(-1, L // 2) for x in (g, acc))
    lr, eps = (jnp.asarray(x, jnp.float32) for x in (lr, eps))
    pool = main[0]
    with jax.named_scope("adapm_scatter_add"):
        for codes, perm in writeback.sorted_slices(o_sl.reshape(-1),
                                                   n_slots, rows):
            pool = writeback.kernel(n_slots, L, codes.shape[0], rows,
                                    adagrad=True)(
                pool, codes, g[perm], acc[perm], lr, eps)
    return pool[None]


# The parts of a fused step carry stable `jax.named_scope` names
# (adapm_route, adapm_sampler, adapm_gather, adapm_loss_grad,
# adapm_adagrad, adapm_scatter_add): compile-time metadata on the
# step's operations, so a device trace can be reduced by part whatever
# the compiler numbers its fusions (PERF.md section 3). Where the
# write-back kernel runs, AdaGrad is inside its custom call, under
# adapm_scatter_add, and no operation carries adapm_adagrad.


def _loss_and_grads(loss_fn, embs, trainable, aux):
    """Mean loss and its gradients w.r.t. the trainable roles' rows."""
    def objective(train_embs):
        merged = dict(embs)
        merged.update(train_embs)
        return loss_fn(merged, aux)

    with jax.named_scope("adapm_loss_grad"):
        return jax.value_and_grad(objective)(
            {r: embs[r] for r in trainable})


def _adagrad_update(g, acc, lr, eps):
    """The additive row update [d emb | d acc]: AdaGrad with the
    accumulator carried in the value row (reference
    UpdateNsqlL2Adagrad, apps/mf/update.h:23-79)."""
    with jax.named_scope("adapm_adagrad"):
        g2 = g * g
        upd_emb = -lr * g * jax.lax.rsqrt(acc + g2 + eps)
        return jnp.concatenate([upd_emb, g2], axis=-1)


# the first rung of the ladder of widths the port's patch program
# (`patch_routes`) is compiled at: a placement change takes ONE call, at
# the smallest rung that holds its changed keys
PATCH_KEYS = 16384


def patch_rungs(most: int) -> List[int]:
    """The ladder up to the first rung that holds `most` keys: from
    `PATCH_KEYS` by doubling, so the padding is under the keys' own
    count above the first rung. A refresh takes the last rung of
    `patch_rungs(its changed keys)`; `precompile` compiles
    `patch_rungs(ab.journal_limit)`, the most a journal answers with
    (eight rungs at 25.5 M keys)."""
    rungs = [PATCH_KEYS]
    while rungs[-1] < most:
        rungs.append(2 * rungs[-1])
    return rungs


def _changed_keys(server, cursor) -> Optional[np.ndarray]:
    """The keys whose placement changed since the journal position
    `cursor` (core/addressbook.py), each once and sorted: what state
    derived from placement is patched by. None where it has to be
    rebuilt from the tables: the journal does not reach back to the
    cursor, or the store is tiered (residency moves what the step reads
    and is not journalled). Caller holds the server lock."""
    if server.tier is not None:
        return None
    keys = server.ab.changed_since(cursor)
    return None if keys is None else np.unique(keys)


def place_bits(n_slots: int) -> int:
    """The low bits of a place word that hold the slot, for a pool of
    `n_slots` slots a shard."""
    return max(int(n_slots) - 1, 0).bit_length()


def place_words(owner, slot, bits) -> np.ndarray:
    """The device mirror's word for each (owner, slot) of the host's
    tables, int32: `owner << bits | slot` for a live pair (`bits`:
    `place_bits` of the key's pool, a number or one a key), and `OOB`
    for every other: a key that another process owns (`REMOTE`,
    `NO_SLOT`), a tiered store's cold row (`compose_slot_table` hands
    `OOB` for its slot), padding. A new array, never a view."""
    owner, slot = np.asarray(owner), np.asarray(slot)
    live = (owner >= 0) & (slot >= 0) & (slot >> bits == 0)
    return np.where(live, (owner << bits) | slot, OOB).astype(
        np.int32, copy=False)


def decode_place(word, n_slots: int):
    """(shard, slot) of place words (`place_words`) for a main pool of
    `n_slots` slots a shard, static in the program: a shift, a mask and
    a select, which fuse into whatever reads them. A live pair decodes
    to itself. `OOB` decodes OUT OF BOUNDS in both: its slot is `OOB`
    and its shard past the last (`DeviceRouter` asserts that every live
    word lies under it), so the position reads a zero embedding, its
    write-back is dropped and no shard counts it local."""
    bits = place_bits(n_slots)
    return word >> bits, jnp.where(word == OOB, OOB,
                                   word & ((1 << bits) - 1))


class DeviceRouter:
    """Device mirrors of the Addressbook tables for one worker shard:
    `place`, a key's main copy as ONE int32 word (`place_words`: owner
    and slot together, so a program finds both with one look-up), and
    `cache_row`, this shard's replica slots. Brought up to date lazily
    on placement changes (Server.topology_version): patched by the keys
    that changed since (`_patch`), rebuilt from the tables where those
    are not known (`_refresh`: set-up, a journal trimmed or reset, a
    tiered store). The host's addressbook keeps `owner` and `slot`
    apart; only the mirror packs them."""

    def __init__(self, server, shard: int):
        self.server = server
        self.shard = shard
        self._version = None   # (topology_version, residency epoch)
        self._cursor = None    # the journal position the mirrors are at
                               # (None: not built yet)
        self.place = None      # [num_keys] int32 place words
        self.cache_row = None  # [num_keys] int32 (this shard's replica slots)
        # the slot bits of each length class's word, from the pool a
        # step indexes (a tiered store's is its hot pool). Every live
        # word lies under `OOB`, whose own shard is then past the last
        bits = [place_bits(st.main.shape[1]) for st in server.stores]
        assert all(server.num_shards << b <= OOB for b in bits), \
            "a pool too large for int32 place words"
        self._bits = np.array(bits, np.int32)
        # what a placement change costs this worker on the host: every
        # re-upload of the mirrors (and of the runner's local sampling
        # index) is one observation; the bytes count every device a
        # replicated array is put to
        self._h_refresh = server.obs.histogram("fused.route_refresh_s",
                                               shared=True)
        self._c_refresh = server.obs.counter("fused.route_refresh_total",
                                             shared=True)
        self._c_upload = server.obs.counter(
            "fused.route_upload_bytes_total", unit="bytes", shared=True)
        # of a refresh, the uploads (the wait span `fused.route_upload`
        # around each `put_replicated`) and the rest: the host's own
        # snapshots and index building (`work=`, obs/spans.py)
        self._h_upload = server.obs.histogram("fused.route_upload_s",
                                              shared=True)
        self._h_refresh_work = server.obs.histogram(
            "fused.route_refresh_work_s", shared=True)
        # of the refreshes, those that patched what was there by the
        # journal's keys (the mirrors here, a runner's local index),
        # and the entries they shipped; the wait span `fused.route_patch`
        # holds each call of the patch program, and the calls are counted
        self._c_patch = server.obs.counter("fused.route_patch_total",
                                           shared=True)
        self._c_patch_keys = server.obs.counter(
            "fused.route_patch_keys_total", unit="keys", shared=True)
        self._c_patch_calls = server.obs.counter(
            "fused.route_patch_calls_total", shared=True)
        self._h_patch = server.obs.histogram("fused.route_patch_s",
                                             shared=True)

    @property
    def owner(self):
        """The place mirror under the name of the table it replaced:
        None exactly until the mirrors are built, which the
        benchmark's planted fault reads
        (benchmarks/tests/_broken_run_kv.py)."""
        return self.place

    def _put_counted(self, arr):
        """`put_replicated` of one mirror, its bytes counted once for
        each device it lands on."""
        srv = self.server
        self._c_upload.inc(arr.nbytes * srv.ctx.num_shards)
        with srv._span("fused.route_upload", self._h_upload, wait=True):
            return srv.ctx.put_replicated(arr)

    def refresh(self):
        srv = self.server
        ver = (srv.topology_version,
               srv.tier.epoch if srv.tier is not None else -1)
        if self._version == ver and self.place is not None:
            return
        with srv._span("fused.route_refresh", self._h_refresh,
                       work=self._h_refresh_work):
            changed = _changed_keys(srv, self._cursor)
            if changed is None:
                self._refresh()
            else:
                self._patch(changed)
            self._version = ver
            self._cursor = srv.ab.journal_cursor()
        self._c_refresh.inc()

    def _patch(self, keys: np.ndarray) -> None:
        """Set the mirrors' entries of `keys` (sorted, each once) to the
        addressbook's values of now, read here under the server lock, in
        ONE call of the port's program, at the smallest rung that holds
        the keys (`patch_rungs`). The program returns new tables, and
        every later dispatch is ordered after it as after an upload."""
        srv = self.server
        patch = self._put_counted(
            self._patch_operand(keys, patch_rungs(len(keys))[-1]))
        with srv._span("fused.route_patch", self._h_patch, wait=True):
            self.place, self.cache_row = default_port().patch_routes(
                self.place, self.cache_row, patch)
        self._c_patch_calls.inc()
        self._c_patch.inc()
        self._c_patch_keys.inc(len(keys))

    def _words(self, slot: np.ndarray, keys=slice(None)) -> np.ndarray:
        """The place words of `keys` (of every key unless given), whose
        slots in the pool a step indexes are `slot`."""
        ab = self.server.ab
        bits = self._bits if len(self._bits) == 1 else \
            self._bits[ab.key_class[keys]]
        return place_words(ab.owner[keys], slot, bits)

    def _patch_operand(self, keys: np.ndarray, width: int) -> np.ndarray:
        """The patch program's operand for `keys`, int32 [3, width]: the
        keys, their place words and their cache rows; the padding keys
        count up from `num_keys`: past the tables, so dropped, and
        ascending and distinct after the keys, as the program's scatter
        is promised."""
        ab = self.server.ab
        n = len(keys)
        patch = np.empty((3, width), np.int32)
        patch[:, :n] = (keys, self._words(ab.slot[keys], keys),
                        ab.cache_slot[self.shard, keys])
        patch[0, n:] = np.arange(ab.num_keys, ab.num_keys + width - n,
                                 dtype=np.int32)
        patch[1:, n:] = OOB
        return patch

    def _refresh(self):
        srv = self.server
        ab = srv.ab
        # what is uploaded is made HERE, under the server lock, and is
        # nobody else's: the planner changes the addressbook's arrays in
        # place, a device_put reads its host buffer until the transfer
        # is done, and with rounds on the prefetch thread the next
        # relocation can land before that. A step then routed by
        # placement it was not ordered after, and read a row's old (or
        # still empty) place. The words are a new array by construction;
        # the cache row is copied. Both are host work, done before
        # `_put_counted`'s wait span opens.
        # Tiered storage: the step indexes the DEVICE hot pool, so the
        # words carry hot-pool ROWS (composed against the residency
        # map, cached per epoch at the TierManager and shared by all
        # runners; `OOB` while cold, so the word is: fill zeros / drop,
        # never the negative-index WRAP — and runners pin their batches
        # hot so the step never actually touches a cold row)
        self.place = self._put_counted(self._words(
            ab.slot if srv.tier is None else srv.tier.compose_slot_table()))
        self.cache_row = self._put_counted(
            np.array(ab.cache_slot[self.shard]))

    def tables(self):
        self.refresh()
        return self.place, self.cache_row


def _route_on_device(tables, keys, n_slots: int):
    """In-jit route resolution: the device-side twin of Server._route
    (and native adapm_route), in TWO look-ups: the key's place word
    (`decode_place`, for a main pool of `n_slots` slots a shard) and the
    worker shard's cache row. keys int32/int64 device array; `tables`
    ends in the worker's shard, an int32 scalar operand."""
    place, cache_row, shard = tables
    o_sh, o_sl = decode_place(place[keys], n_slots)
    cs = cache_row[keys]
    use_c = cs >= 0
    g_sl = jnp.where(use_c, OOB, o_sl)
    c_sh = jnp.full_like(o_sh, shard)
    c_sl = jnp.where(use_c, cs, OOB)
    return (o_sh, g_sl, c_sh, c_sl, use_c)


class _PoolProgram:
    """A fused program (step, scan or score: `pools` is its first
    operand) compiled in the form its pools call for, read from their
    leading dimension when it is first called or lowered with them:

      - one shard: `build(None)` as ONE program (`port.compile`);
      - several shards, laid over the mesh's kv axis one a chip:
        `build(KV_AXIS)` as a PER-CHIP program (`port
        .compile_collective`: shard_map over that axis). Each chip is
        handed its own `[1, slots, L]` blocks of the pools, every other
        operand whole; it returns its blocks (where `pools_out`) and
        values that are the same on every chip. Its collectives are the
        sums the body asks for, and a chip's block is one shard, so the
        write-back kernel applies (`writeback_uses_kernel`).

    `per_chip=False` keeps several shards ONE program over the global
    pools too, partitioned by GSPMD: for a step whose rows may lie
    anywhere (`make_device_routed_step`, `neg_local`)."""

    def __init__(self, build, pools_out: bool, per_chip: bool = True,
                 **jit_kwargs):
        self._build = build
        self._pools_out = pools_out
        self._per_chip = per_chip
        self._jit_kwargs = jit_kwargs
        self._forms = {}  # mesh of the per-chip form (None: one program)

    def _form(self, pools, rest):
        main = pools[0][0]
        mesh = getattr(main.sharding, "mesh", None) \
            if self._per_chip and main.shape[0] > 1 else None
        if mesh not in self._forms:
            port = default_port()
            if mesh is None:
                form = port.compile(self._build(None), **self._jit_kwargs)
            else:
                whole = P()
                form = port.compile_collective(
                    self._build(KV_AXIS), mesh,
                    in_specs=(P(KV_AXIS),) + (whole,) * len(rest),
                    out_specs=(P(KV_AXIS), whole, whole)
                    if self._pools_out else whole,
                    # unchecked, as a body with a Pallas call (the
                    # write-back kernel) has to be. The body counts on
                    # it: a checked map's gradient w.r.t. a value it
                    # knows to be the same on every chip (the summed
                    # rows) is summed over the axis, and the body does
                    # that sum itself, of the worker chip's alone
                    check_vma=False, **self._jit_kwargs)
            self._forms[mesh] = form
        return self._forms[mesh]

    def __call__(self, pools, *rest):
        return self._form(pools, rest)(pools, *rest)

    def lower(self, pools, *rest):
        return self._form(pools, rest).lower(pools, *rest)


def make_device_routed_step(loss_fn: Callable[..., jnp.ndarray],
                            role_class: Dict[str, int],
                            role_dim: Dict[str, int],
                            frozen_roles: Sequence[str] = (),
                            neg_role: str = None,
                            neg_shape: Tuple[int, ...] = None,
                            no_replicas: bool = False,
                            neg_alias: bool = False,
                            neg_local: bool = True):
    """Fused step that resolves routing in-program from device table
    mirrors. Signature of the returned step:

        step(pools, locstat, tables, keys, local_index, alias, rng_key,
             aux, lr, eps)
          pools       tuple per class of (main, cache, delta)  [donated]
          tables      (place, cache_row, shard): the device mirrors
                      (key-indexed global arrays, shared by all length
                      classes: a key's main copy as one place word,
                      `decode_place`; cache_row is the worker shard's)
                      and the worker's shard as an int32 scalar. The
                      shard is an
                      OPERAND, so the workers of a server run one
                      compiled step (DeviceRoutedRunner shares it)
          keys        dict role -> device int array (raw PM keys)
          local_index (padded sorted index [capacity], valid count) of the
                      locally-resident keys for uniform on-device
                      negative sampling (None disables; None with
                      `neg_alias`, which reads `alias` instead)
          rng_key     jax PRNG key for the device-side sampler

    When `neg_role` is set and local_index is given, that role's keys
    are DRAWN in-program: uniform positions into local_index — the Local
    sampling scheme (core/sampling.py LocalSampling) executed on device.
    The draw has the shape `neg_shape` = `[B, N]` and the loss is handed
    `[B, N, dim]` rows; in between the step lays the role out
    SAMPLE-MAJOR (`[N, B, .]`: keys, routes, gathered rows,
    accumulators, gradients), so that no reshape of its rows is a copy
    whatever N is (`_build_device_routed_body`).

    `neg_alias=True` switches the draw to a NON-uniform app distribution:
    the step takes an extra `alias` argument (prob[V], alias[V], snap[V]
    device arrays — a Vose table, models/sgns.py build_alias_table, e.g.
    unigram^0.75 for word2vec), draws an alias position and reads its key
    out of `snap`: ONE gather. `snap[j]` is position j's key of the
    population already SNAPPED to the nearest locally-resident key (the
    Local scheme's LocalSampling._snap, sampling.h:476-505). The snap
    depends on placement only, not on the draw, so the host computes it
    where placement changes (DeviceRoutedRunner._snap_table) and the
    program searches nothing.

    `no_replicas=True` compiles the replica-free specialization: reads
    touch only the main pool and updates scatter only into main. Legal
    exactly while this shard holds zero replicas — the runner re-checks
    per step and switches variants. The replica variant
    (`no_replicas=False`) is the same data path over main for EVERY
    position (one clamped gather a role, the out-of-bounds mask on the
    embedding columns only: `_route_and_gather`) plus a side path that
    alone touches the cache and delta pools: the positions whose key the
    worker's shard holds a replica of are compacted and read from
    `cache + delta`, and written to `delta`, a chunk of `SIDE_ROWS` rows
    at a time (`_replica_side`), so it pays for replicas at the replica
    positions and costs the replica-free step plus those chunks.

    Pools of several shards get the step as a per-chip program
    (`_PoolProgram`, `_build_device_routed_body`), which counts on the
    drawn negatives lying on the worker's shard. `neg_local=False` says
    they may lie anywhere (the runner's local index found no resident
    key and holds the whole population): such a step stays one program
    over the global pools.
    """
    def build(axis):
        return _build_device_routed_body(
            loss_fn, role_class, role_dim, frozen_roles, neg_role,
            neg_shape, no_replicas, neg_alias, axis=axis)
    # donate the pools only: donating the few-scalar locstat accumulator
    # saves nothing and its aliased buffer has been observed returning
    # stale/garbage counts on the multi-device CPU backend (flaky
    # locality_counts mismatches in test_device_routed)
    return _PoolProgram(build, True, per_chip=neg_local,
                        donate_argnums=(0,))


def make_device_routed_scan(loss_fn: Callable[..., jnp.ndarray],
                            role_class: Dict[str, int],
                            role_dim: Dict[str, int],
                            frozen_roles: Sequence[str] = (),
                            neg_role: str = None,
                            neg_shape: Tuple[int, ...] = None,
                            no_replicas: bool = False,
                            neg_alias: bool = False,
                            has_aux: bool = True,
                            neg_local: bool = True):
    """K training steps in ONE dispatch: `lax.scan` over stacked batches
    (VERDICT r3 item 2 — the per-step host dispatch is the residual over
    the HBM row-rate floor; amortizing it over a K-step window reclaims
    it). Placement is frozen for the window: the routing tables are read
    once, so the planner's moves land between scans — exactly the
    lookahead contract (intents are signaled a window ahead anyway).

    Signature: scan(pools, locstat, tables, keys[K,...], local_index,
    alias, rng_keys[K], aux[K,...]|None, lr, eps)
    -> (pools, locstat, losses[K])."""
    def build(axis):
        body = _build_device_routed_body(
            loss_fn, role_class, role_dim, frozen_roles, neg_role,
            neg_shape, no_replicas, neg_alias, axis=axis)

        def scan(pools, locstat, tables, keys, local_index, alias,
                 rng_keys, aux, lr, eps):
            def f(carry, xs):
                pools, locstat = carry
                if has_aux:
                    k, rkey, a = xs
                else:
                    k, rkey = xs
                    a = None
                pools, locstat, loss = body(
                    pools, locstat, tables, k, local_index, alias, rkey, a,
                    lr, eps)
                return (pools, locstat), loss

            xs = (keys, rng_keys, aux) if has_aux else (keys, rng_keys)
            (pools, locstat), losses = jax.lax.scan(f, (pools, locstat),
                                                    xs)
            return pools, locstat, losses
        return scan

    # pools-only donation, and the per-chip form for pools of several
    # shards: same rationale as make_device_routed_step
    return _PoolProgram(build, True, per_chip=neg_local,
                        donate_argnums=(0,))


def _in_bounds(idx, n: int):
    """Whether jnp's indexing finds `idx` among `n` entries: a negative
    index wraps once (-1 is the last entry), anything further out is out
    of bounds, which `mode="fill"` fills and `mode="drop"` drops."""
    return (idx >= -n) & (idx < n)


def _here(sh, axis):
    """Whether the shard index `sh` names the chip this per-chip program
    runs on (a negative index wraps once, as jnp's indexing of the
    global pool does)."""
    me = jax.lax.axis_index(axis)
    return (sh == me) | (sh == me - jax.lax.axis_size(axis))


def _on_this_chip(route, axis):
    """A route into the global pools as a route into this chip's own
    blocks (`[1, slots, L]` each): shard 0, and the slot out of bounds
    where the row lies on another chip, so that the gather reads zeros
    there and the write-back drops the position. Every row of the
    global route lies on exactly one chip."""
    if len(route) == 2:  # the replica-free variant's (o_sh, o_sl)
        o_sh, o_sl = route
        return jnp.zeros_like(o_sh), jnp.where(_here(o_sh, axis), o_sl, OOB)
    o_sh, g_sl, c_sh, c_sl, use_c = route
    zero = jnp.zeros_like(o_sh)
    return (zero, jnp.where(_here(o_sh, axis), g_sl, OOB),
            zero, jnp.where(_here(c_sh, axis), c_sl, OOB), use_c)


def _classes_counted(role_class) -> list:
    """The length classes whose replica positions and side-path chunks
    a step counts one by one beside its totals: those of its roles where
    they span several (with one class the totals are that class's, and
    the step's accumulator, and so its program, is what it was)."""
    classes = sorted(set(role_class.values()))
    return classes if len(classes) > 1 else []


def _route_and_gather(pools, tables, keys, roles, role_class, role_dim,
                      no_replicas, axis=None, drawn=()):
    """Route every role's keys and gather their rows: the read half of a
    fused step, shared with the gather-only score program
    (`make_device_routed_score`). Returns (embs, accs, routes, away,
    counts): each role's embedding columns and accumulator columns in
    the shape of its keys (a sampled role's are sample-major, `[N, B]`:
    `_build_device_routed_body`), its route
    (main's shard and slot; in the replica variant the compacted replica
    positions too, a `_ReplicaSide`), with `axis` the positions of each
    role named by key that lie off the worker's chip (an `_Away`), and
    the step's counts (n_total,
    n_local, n_replica, n_chunks, the last two once more for each
    length class where the roles span several, `_classes_counted`, and
    the exchange's: the positions off the worker's chip, then each
    role's positions in the blocks summed for them, whole chunks): the
    device-side locality counts
    (reference coloc_kv_server.h:147-157 prints % accesses served
    locally; Pull/Push record this in Server._route, which a step never
    visits: a key access is local when this worker's shard owns the row
    or holds a replica), the positions that read a replica, and the
    side-path chunks those take.

    A position whose route is out of bounds (`OOB`: a cold tier row, a
    padding position) reads as a ZERO embedding. Either variant gathers
    main ONCE for all positions with `mode="clip"` and zeroes the
    embedding columns alone, where the loss reads them, so no row-wide
    mask crosses HBM; the accumulator columns of such a position are
    whatever row the clamp found, and nobody reads them: the write-back
    drops the position (its code is -1 in the kernel, `mode="drop"` in
    XLA's scatter-add). In the replica variant main's slot is out of
    bounds at the replica positions (`_route_on_device`); those are
    compacted (`_replica_side`) and their rows patched in from
    `cache + delta`, a chunk at a time (`_patch_replica_rows`): the only
    reads of the two replica pools, `SIDE_ROWS` rows each.

    With `axis` (the per-chip step, `_build_device_routed_body`) the
    pools are this chip's blocks and each route is brought onto the chip
    (`_on_this_chip`) before the same gather: the rows and the returned
    routes are the chip's own, zeros and out of bounds for what lies
    elsewhere. The counts come from the global routes, so every chip
    holds the same (the replica positions are the worker chip's: the
    other chips find none of them and run no chunk). Then the exchange:
    of the roles named by key (all but `drawn`) the rows that lie OFF
    the worker's chip are summed over the axis (`_away`, `_exchange`),
    after which the worker's chip holds every row its batch names. The
    other chips hold their own rows and those: what they compute from
    them the step masks."""
    embs, accs, routes, away = {}, {}, {}, {}
    n_total = 0
    n_local = n_replica = n_chunks = n_away = jnp.int32(0)
    away_rows = {r: jnp.int32(0) for r in roles}  # in whole chunks
    # class -> [replica positions, chunks], where the roles span several
    by_class = {cid: [jnp.int32(0), jnp.int32(0)]
                for cid in _classes_counted(role_class)}
    shard = tables[-1]  # the worker's, an int32 scalar operand
    for r in roles:
        cid = role_class[r]
        main, cache, delta = pools[cid]
        dim = role_dim[r]
        n_total += keys[r].size
        with jax.named_scope("adapm_route"):
            # ONE look-up a role for the main copy's shard and slot
            if no_replicas:
                route = whole = decode_place(tables[0][keys[r]],
                                             main.shape[1])
            else:
                route = whole = _route_on_device(tables, keys[r],
                                                 main.shape[1])
            if axis is not None:
                route = _on_this_chip(whole, axis)
            o_sh, o_sl = route[:2]
        with jax.named_scope("adapm_gather"):
            rows = main.at[o_sh, o_sl].get(mode="clip")
            valid = _in_bounds(o_sh, main.shape[0]) \
                & _in_bounds(o_sl, main.shape[1])
            routes[r] = (o_sh, o_sl)
            if not no_replicas:
                side = _replica_side(cache, *route[2:4])
                rows = _patch_replica_rows(rows, cache, delta, side)
                valid |= side.here.reshape(valid.shape)
                routes[r] += (side,)
            embs[r] = jnp.where(valid[..., None], rows[..., :dim], 0)
        if axis is not None and r not in drawn:
            away[r] = _away(whole, main, shard, axis, dim)
            embs[r] = _exchange(embs[r], away[r], axis)
            n_away += away[r].count
            away_rows[r] = away[r].chunks * away[r].rows
        local = whole[0] == shard
        accs[r] = rows[..., dim:]
        if not no_replicas:
            use_c = whole[4]
            local |= use_c
            # counted on the global route, like the locality counts: the
            # worker chip's side path, and the same number on every chip
            held = jnp.sum(use_c, dtype=jnp.int32)
            chunks = _replica_chunks(held, use_c.size)
            n_replica += held
            n_chunks += chunks
            if cid in by_class:
                by_class[cid][0] += held
                by_class[cid][1] += chunks
        n_local += jnp.sum(local, dtype=jnp.int32)
    return embs, accs, routes, away, (
        n_total, n_local, n_replica, n_chunks,
        [n for cid in sorted(by_class) for n in by_class[cid]],
        [n_away] + [away_rows[r] for r in roles])


def make_device_routed_score(score_fn: Callable[..., jnp.ndarray],
                             role_class: Dict[str, int],
                             role_dim: Dict[str, int],
                             roles: Sequence[str],
                             no_replicas: bool = False):
    """The read half of the fused step as a program of its own: route and
    gather `roles` exactly as the step does (`_route_and_gather`), hand
    the embedding columns to `score_fn(embs, aux) -> scalar`, and add the
    result to an accumulator. No gradient, no write-back, and the pools
    are not donated: they come back untouched. Signature of the returned
    program (named `jit_score` in a device trace):

        score(pools, tables, keys, aux, acc) -> acc + score_fn(embs, aux)

    A pass-end objective (apps/matrix_factorization.py: the squared error
    over the worker's own points) is then a walk of such dispatches whose
    sum stays on the device until one fetch."""
    roles = sorted(roles)

    def build(axis):
        def score(pools, tables, keys, aux, acc):
            # per chip: the worker's chip holds every row (the exchange)
            # and the score is its own, summed with zeros
            embs = _route_and_gather(
                pools, tables, dict(keys), roles, role_class, role_dim,
                no_replicas, axis)[0]
            with jax.named_scope("adapm_loss_grad"):
                got = score_fn(embs, aux)
                if axis is not None:
                    with jax.named_scope("adapm_exchange"):
                        got = jax.lax.psum(jnp.where(
                            _here(tables[-1], axis), got, 0), axis)
                return acc + got
        return score

    return _PoolProgram(build, False)


def _build_device_routed_body(loss_fn, role_class, role_dim,
                              frozen_roles, neg_role, neg_shape,
                              no_replicas, neg_alias, axis=None):
    """The un-jitted single-step body shared by make_device_routed_step
    (one dispatch per step) and make_device_routed_scan (K steps per
    dispatch).

    `axis` names the mesh axis the body is mapped over (`_PoolProgram`:
    pools of several shards): it is then the PER-CHIP step. `pools` are
    the chip's own `[1, slots, L]` blocks of main, cache and delta;
    everything else is the same on every chip. It is the one-shard step
    on routes brought onto the chip (`_on_this_chip`), with an exchange
    over the axis between its parts, of the rows that lie OFF the
    worker's chip and of nothing else:

      - the sampled role is local by construction (drawn from the
        worker shard's own resident keys: main rows it owns, or its
        replicas), so its rows are gathered, differentiated and written
        back on the worker's chip alone and cross no wire. The other
        chips run the same operations on routes that are out of bounds
        everywhere: clamped or zero-filled gathers, dropped write-backs;
      - the roles named by key are gathered where they lie (the main
        copy on its owner's chip, a replica of the worker's shard on the
        worker's chip, zeros elsewhere). The positions whose row
        another chip than the worker's holds are compacted from the
        global route, the same list on every chip (`_away`), and
        walked in chunks of `EXCHANGE_BYTES` by a loop whose count the
        program reads from its input: a chunk's rows, one sum of the
        `[chunk, dim]` block over the axis, set back (`_exchange`).
        The worker's chip then holds every row its batch names; the
        other chips hold their own and those, and what they compute
        from them is masked. The gradients, the worker chip's and
        zeros on the others, go back by the same walk: every chip then
        holds the worker's gradient at the positions it may own, and
        writes back the rows it holds, from the accumulators it
        gathered itself. A batch whose named rows all lie on the
        worker's chip (the CTR step's dense role, all replicas or its
        own) runs no chunk; one whose rows all lie elsewhere sums all
        its positions, as the whole-array sum that this replaced did
        for every batch;
      - the loss is the worker chip's, summed with zeros from the
        others. The locality counts and the exchange's own (the
        positions off the chip, the blocks summed) are computed from
        the global routes, alike on every chip.

    Inside the map a chip's main block is one shard, so the write-back
    kernel applies (`writeback_uses_kernel`) in both variants.

    The replica variant is the replica-free data path over main for
    every position, and a side path for the positions that read a
    replica (`_replica_side`: compacted, walked in chunks of `SIDE_ROWS`
    by a loop whose count the program reads from its input). Main's
    write-back is the same call in both variants (the kernel where it
    applies, else `_adagrad_update` and XLA's scatter-add of row-wide
    update rows): a replica position's slot in main is out of bounds,
    so it is skipped there. The delta pool receives the update rows of
    the replica positions alone, formed a chunk at a time
    (`_replica_writeback`); they are few, on the worker's chip, and
    XLA's scatter-add. So no variant that takes the kernel ever holds an
    `[n, L]` array of update rows, and the cache and delta pools are
    never indexed by all positions.

    In either variant no row-wide array is copied, padded or masked
    as a whole between the gather and its readers. The sampled
    role's keys are drawn in `neg_shape` = `[B, N]` (the draw is the
    caller's to mirror) and TRANSPOSED to `[N, B]` before they are
    routed: routes, gathered rows, accumulators and gradients of that
    role are sample-major, `[N, B, .]`, whose tiled dims `[B, .]` are
    whole tiles for any N, so every reshape to and from the flat
    `[N * B, .]` that the gather gives and the write-back takes is a
    bitcast (a `[B, 5, .]` array pads its 5 to the 8 rows of a tile and
    is copied each time). The loss is handed the `[B, N, dim]` view it
    was written for, and its gradient comes back through the same view.
    Among positions that name ONE row the write-back adds in the order
    of the flattened routes: `(k, b)` for the sampled role. The
    out-of-bounds mask sits on the embedding columns
    (`_route_and_gather`). The replica variant's side path walks the
    same flat positions, so a role's replica positions are patched and
    written in that order too."""
    roles = sorted(role_class)
    trainable = [r for r in roles if r not in frozen_roles]
    sample_major = neg_role is not None

    def batch_major_loss(embs, aux):
        if sample_major:
            embs = dict(embs)
            embs[neg_role] = jnp.moveaxis(embs[neg_role], 0, -2)
        return loss_fn(embs, aux)

    def step(pools, locstat, tables, keys, local_index, alias, rng_key,
             aux, lr, eps):
        keys = dict(keys)
        # the roles whose keys this program draws (from the worker
        # shard's resident keys: local by construction), the others are
        # named by key
        drawn = (neg_role,) if neg_role is not None and (
            neg_alias or local_index is not None) else ()
        with jax.named_scope("adapm_sampler"):
            if neg_role is not None and neg_alias:
                # snap_table[j]: alias position j's key, snapped to the
                # locally-resident population by the host
                prob, alias_t, snap_table = alias
                k1, k2 = jax.random.split(rng_key)
                u = jax.random.randint(k1, neg_shape, 0, prob.shape[0])
                v = jax.random.uniform(k2, neg_shape)
                keys[neg_role] = snap_table[
                    jnp.where(v < prob[u], u, alias_t[u])]
            elif neg_role is not None and local_index is not None:
                idx, count = local_index  # padded index + valid count
                pos = jax.random.randint(rng_key, neg_shape, 0, count)
                keys[neg_role] = idx[pos]
            if sample_major:
                # sample-major from here on; the values at [b, k] stay
                keys[neg_role] = jnp.moveaxis(keys[neg_role], -1, 0)
        embs, accs, routes, away, counts = _route_and_gather(
            pools, tables, keys, roles, role_class, role_dim, no_replicas,
            axis, drawn)
        # one step = one (batched) pull op + one push op of the same keys;
        # the op counts local iff every key it touched was local
        n_total, n_local, n_replica, n_chunks, by_class, exchanged = counts
        all_local = (n_local == n_total).astype(jnp.int32)
        # the accumulator takes as many of the step's counts as it has
        # entries (`DeviceRoutedRunner._locstat`)
        locstat = locstat + jnp.stack(
            [jnp.int32(n_total), n_local, jnp.int32(1), all_local,
             n_replica, n_chunks, *by_class, *exchanged]
            [:locstat.shape[0]])
        loss, grads = _loss_and_grads(batch_major_loss, embs, trainable,
                                      aux)
        if axis is not None:
            # the worker chip's loss, zeros from the others; its
            # gradients at the positions whose row another chip holds
            # (a chip keeps its own elsewhere: the worker's are the
            # step's, the others' zeros, which no route of theirs takes)
            worker = _here(tables[-1], axis)
            with jax.named_scope("adapm_exchange"):
                loss = jax.lax.psum(jnp.where(worker, loss, 0), axis)
            for r in trainable:
                if r in away:
                    grads[r] = _exchange(jnp.where(worker, grads[r], 0),
                                         away[r], axis)

        new_pools = list(pools)
        for r in trainable:
            cid = role_class[r]
            main, cache, delta = new_pools[cid]
            # main's coordinates in either variant (the replica
            # variant's slot is out of bounds at replica positions)
            o_sh, o_sl = routes[r][:2]
            if writeback_uses_kernel(main):
                main = _kernel_writeback(main, o_sh, o_sl, grads[r],
                                         accs[r], lr, eps)
            else:
                upd = _adagrad_update(grads[r], accs[r], lr, eps)
                with jax.named_scope("adapm_scatter_add"):
                    main = main.at[o_sh, o_sl].add(upd, mode="drop")
            if not no_replicas:
                delta = _replica_writeback(delta, routes[r][2], grads[r],
                                           accs[r], lr, eps)
            new_pools[cid] = (main, cache, delta)
        return tuple(new_pools), locstat, loss

    return step


class StagedKeys:
    """A step's key batch pre-staged on device (DeviceRoutedRunner
    .prefetch_keys): the host->device upload happened at prepare/intent
    time instead of inside the dispatch critical section. Valid across
    topology changes — these are raw keys, not routes."""

    __slots__ = ("host", "dev")

    def __init__(self, host: Dict[str, np.ndarray], dev: Dict[str, object]):
        self.host = host
        self.dev = dev

    def matches(self, role_keys: Dict[str, np.ndarray]) -> bool:
        if set(self.host) != set(role_keys):
            return False
        return all(np.array_equal(self.host[r],
                                  np.asarray(k, dtype=self.host[r].dtype))
                   for r, k in role_keys.items())


class DeviceRoutedRunner:
    """Binds the fused step to a Server: swaps the pools in and out of
    the ShardedStores, so the PM view (Pull/Push/sync rounds) and the
    fused hot loop always see the same buffers. Routing (and optionally
    negative sampling) happens on device: per step the host ships only
    the raw key batch, and the table mirrors refresh lazily when the
    planner moves parameters.
    With the prefetch pipeline on (SystemOptions.prefetch), the mirrors
    are instead re-staged by the pipeline's background thread right after
    planner rounds, and `prefetch_keys` lets the app upload a future
    step's key batch ahead of its dispatch.

    Locality is recorded by a small device accumulator folded into the
    step program (params seen / params local / steps / all-local steps;
    on several shards also replica positions / side-path chunks, and
    the exchange's positions and blocks) and
    drained to the host lazily — at `locality_counts()` (which
    Server.locality_summary calls) and often enough that the int32 counters
    cannot wrap. Per-KEY counters (--sys.stats.locality tsv dumps) are
    kept on the host, for the keys the host knows: with the option on,
    each dispatch records its batch through `Server._route`
    (`_record_key_locality`); negatives drawn in the program are local by
    construction and not recorded per key.
    """

    def __init__(self, server, loss_fn, role_class: Dict[str, int],
                 role_dim: Dict[str, int], shard: int = 0,
                 frozen_roles: Sequence[str] = (), neg_role: str = None,
                 neg_shape: Tuple[int, ...] = None,
                 neg_population=None, neg_alias=None, seed: int = 0,
                 programs: Optional[Dict] = None, score_fn=None):
        """`neg_alias=(prob, alias)` (models/sgns.py build_alias_table)
        switches on-device negative sampling to the app's non-uniform
        distribution over `neg_population` (position i of the population
        is drawn with prob ~ weight i), with a Local-scheme snap to
        locally-resident keys. The snap is a table over the alias
        positions that this runner rebuilds where placement changes
        (`_snap_table`); the compiled step reads it and searches nothing.

        `programs`: a dict the caller hands to every runner it builds
        ALIKE (same loss function, roles, shapes and sampler; only
        `shard` and `seed` may differ). The worker's shard is an operand
        of the compiled step, so such runners run the same programs: the
        first to need a variant compiles it into the dict and the others
        find it there, and a server with four workers compiles each
        variant once and not four times. None: this runner's own.

        `score_fn(embs, aux) -> scalar`: the read-only objective that
        `score` evaluates over the host-named roles (None: the runner
        has no score program)."""
        self.server = server
        self._programs = {} if programs is None else programs
        self.shard = shard
        self.role_class = role_class
        self.frozen_roles = frozenset(frozen_roles)
        self.router = DeviceRouter(server, shard)
        self.neg_role = neg_role
        self._li_fallback = False  # set by _local_neg_index
        self._neg_shape = neg_shape
        self._rng = jax.random.PRNGKey(seed)
        # population the device sampler may draw from (Local scheme: the
        # locally-resident slice of the allowed keys); None -> all keys
        self._neg_population = self._key_pos = None
        if neg_population is not None:
            pop = np.asarray(neg_population, dtype=np.int64)
            if neg_alias is None:
                self._neg_population = np.unique(pop)
            else:
                # _key_pos: where each entry of the population as given
                # (the key table, in any order) sits in the sorted one
                self._neg_population, self._key_pos = np.unique(
                    pop, return_inverse=True)
        # the step's `alias` operand: (prob, alias, snap table). The snap
        # table is the key table itself until _local_neg_index says that
        # some key of the population is not resident here
        self._alias = self._key_table = None
        if neg_alias is not None:
            assert neg_role is not None and neg_population is not None, \
                "neg_alias needs neg_role and neg_population"
            prob, alias = neg_alias
            assert len(prob) == len(self._key_pos), \
                "alias table must cover the population"
            put = server.ctx.put_replicated
            self._key_table = put(np.asarray(
                neg_population, dtype=_key_dtype(server.num_keys)))
            self._alias = (put(prob), put(alias), self._key_table)
            # snap tables built, and of the alias positions the share
            # whose snapped key is not their own: whether the snap does
            # anything in this deployment (0 and 0.0 on one shard)
            self._c_snap_rebuilds = server.obs.counter(
                "fused.neg_snap_rebuilds_total", shared=True)
            self._g_snap_moved = server.obs.gauge(
                "fused.neg_snap_moved_share", shared=True)
        if self._neg_population is not None and neg_role is not None:
            kc = server.ab.key_class[self._neg_population]
            assert (kc == role_class[neg_role]).all(), (
                "neg_population spans length classes "
                f"{np.unique(kc)} but role {neg_role} is class "
                f"{role_class[neg_role]}")
        self._local_index = None  # uniform path: (padded index, count)
        self._li_version = None
        # the uniform path's sorted index on the host (a view of the
        # padded array last uploaded, never written again) and the
        # journal position it is at: what `_patch_local_neg_index`
        # merges a placement change into. None: not built yet, or the
        # alias path
        self._li_host = self._li_cursor = None
        # per-step RNG keys come from a batched split (one tiny device
        # dispatch per 64 steps instead of per step) and device scalars
        # are cached per value
        self._rng_pool: list = []
        self._scalars: Dict[float, jnp.ndarray] = {}
        # device locality accumulator [params, params_local, ops, ops_local]
        # (int32; drained before it can wrap — see _drain_locstat). On
        # several shards two entries more: the replica variant's replica
        # positions and the chunks its side path took for them
        # (`_for_replica_chunks`). One shard holds no replica and
        # compiles the replica-free variant alone, on the accumulator
        # (and so to the program) it always had
        # Roles of several length classes: a pair more for each class,
        # its own replica positions and chunks (`_classes_counted`).
        # Then the per-chip step's exchange: the positions whose row lay
        # off the worker's chip, and each role's positions in the
        # blocks summed for them, whole chunks (`_exchange`)
        self._locstat_zero = np.zeros(
            4 if server.num_shards == 1
            else 6 + 2 * len(_classes_counted(role_class))
            + 1 + len(role_class), np.int32)
        self._locstat = server.ctx.put_replicated(self._locstat_zero)
        self._loc_host = np.zeros(4, dtype=np.int64)
        self._drain_every = None  # set on first step (needs params/step)
        server._locality_sources.append(self.locality_counts)
        # obs: drain cadence — how often the device accumulator is
        # folded to host (each drain is a device sync, so the count and
        # the computed interval belong in metrics_snapshot()['fused']).
        # `shared`: several runners per server feed the same counters.
        self._c_drains = server.obs.counter("fused.locstat_drains",
                                            shared=True)
        self._g_drain_every = server.obs.gauge(
            "fused.locstat_drain_every", unit="steps", shared=True)
        # host time of one dispatch (the whole __call__/run_scan) and of
        # the key upload inside it (or ahead of it, prefetch_keys)
        self._h_dispatch = server.obs.histogram("fused.dispatch_s",
                                                shared=True)
        self._h_key_upload = server.obs.histogram("fused.key_upload_s",
                                                  shared=True)
        # of a dispatch: the compiled program's call (the wait span
        # `fused.enqueue`: it returns once the program is queued, so
        # what it holds beyond that is the wait for a free dispatch
        # slot) and the dispatch less every wait beneath it (`work=`)
        self._h_enqueue = server.obs.histogram("fused.enqueue_s",
                                               shared=True)
        self._h_dispatch_work = server.obs.histogram(
            "fused.dispatch_work_s", shared=True)
        # the server's dispatched steps not yet done on the device, read
        # at each step or scan dispatch before its enqueue: near 0 with
        # a busy host, the host paces the run (a scan dispatch counts
        # once). The handles are the steps' losses, shared by the
        # server's runners (Server._steps_in_flight; None: registry off)
        self._h_inflight = server.obs.histogram(
            "fused.inflight_steps", unit="steps",
            bounds=(0, 1, 2, 4, 8, 16, 32, 64, 128), shared=True)
        self._inflight = server._steps_in_flight
        # rows the dispatched steps write back, and those of them in a
        # variant compiled with the Pallas write-back kernel
        # (writeback_uses_kernel): how often the kernel engages
        self._c_wb_rows = server.obs.counter(
            "fused.writeback_rows_total", unit="rows", shared=True)
        self._c_wb_kernel_rows = server.obs.counter(
            "fused.writeback_kernel_rows_total", unit="rows", shared=True)
        # bytes the per-chip steps summed over the mesh's kv axis: the
        # exchange's blocks as the step counted them (the rows named by
        # key that lay off the worker's chip out, their gradients back,
        # in whole chunks: the padding of a role's last chunk crosses
        # the wire too), moved at each drain, and the loss's scalar a
        # step. 0 on one shard, and GSPMD's own collectives are not
        # counted. And the positions those blocks were summed for
        self._c_exchange = server.obs.counter(
            "fused.exchange_bytes_total", unit="bytes", shared=True)
        self._c_exchange_positions = server.obs.counter(
            "fused.exchange_positions", unit="rows", shared=True)
        # a step's (rows written back, those of them in pools the kernel
        # takes); set on first step
        self._per_step = None
        # rows the gather-only score program read (`score`)
        self._c_score_rows = server.obs.counter(
            "fused.score_rows_total", unit="rows", shared=True)
        self._score_fn = score_fn
        # the locality accumulator's first two entries as counters (rows
        # the steps touched / found on the worker's shard), moved at each
        # drain: what a per-layer metric reads (PERF.md section 3)
        self._c_rows = server.obs.counter("fused.rows_total", unit="rows",
                                          shared=True)
        self._c_rows_local = server.obs.counter(
            "fused.rows_local_total", unit="rows", shared=True)
        # of both, the rows of the role the step samples from the
        # worker's own local index: local by construction, so the share
        # that placement earns is (local - sampled) / (total - sampled)
        self._c_rows_sampled = server.obs.counter(
            "fused.rows_sampled_total", unit="rows", shared=True)
        self._sampled_pending = 0  # since the last drain
        # the accumulator's fifth and sixth entries, moved at each drain too:
        # positions the steps read from (and wrote to) a replica, and
        # the side-path chunks that took; a chunk a role a step says
        # `SIDE_ROWS` always sufficed
        self._c_replica_positions = server.obs.counter(
            "fused.replica_positions", unit="rows", shared=True)
        self._c_replica_chunks = server.obs.counter(
            "fused.replica_chunks", shared=True)
        # the same two for each length class of the roles, named by the
        # class's row length (`.len2048`: rows of 2,048 values)
        self._c_replica_by_class = []
        for cid in sorted(set(role_class.values())):
            n = server.class_lengths[cid]
            self._c_replica_by_class += [
                server.obs.counter(f"fused.replica_positions.len{n}",
                                   unit="rows", shared=True),
                server.obs.counter(f"fused.replica_chunks.len{n}",
                                   shared=True)]
        # the worker's shard as the step's operand (the last of `tables`)
        self._shard_dev = server.ctx.put_replicated(np.int32(shard))
        self._mk_kwargs = dict(
            loss_fn=loss_fn, role_class=role_class, role_dim=role_dim,
            frozen_roles=frozen_roles, neg_role=neg_role,
            neg_shape=neg_shape, neg_alias=self._alias is not None)
        self.step_fn = self._program(make_device_routed_step,
                                     no_replicas=False)
        # replica-free specialization: no side path; selected per step
        # while this shard holds no replicas
        self._step_fn_norep = self._program(make_device_routed_step,
                                            no_replicas=True)
        self.steps = 0
        if getattr(server, "prefetch", None) is not None:
            server.prefetch.register_refresher(self._prefetch_refresh)

    def _program(self, make, **variant):
        """The compiled program `make(**variant, **self._mk_kwargs)`,
        kept in `programs` (see __init__) under the variant's name."""
        key = (make.__name__,) + tuple(sorted(variant.items()))
        if key not in self._programs:
            self._programs[key] = make(**variant, **self._mk_kwargs)
        return self._programs[key]

    def _tables(self):
        """The step's `tables` operand: the router's mirrors and this
        worker's shard."""
        return self.router.tables() + (self._shard_dev,)

    def precompile(self, role_keys: Dict[str, np.ndarray],
                   aux=None, score_aux=None) -> None:
        """Compile (or fetch from the compile cache) the step variants
        this runner can reach, before a timed loop meets them: the
        replica-free variant, and on a server of several shards the
        replica variant too (one shard never holds a replica). Each runs
        once, on a batch shaped like `role_keys` and an `aux` like the
        steps', against a place table that is out of bounds everywhere:
        every gather fills zeros and every write-back is dropped, so the
        pools come back bit for bit, and neither the RNG sequence nor
        the locality counts move. On several shards the program that
        patches the router's mirrors compiles here too, at every rung
        (`DeviceRouter._patch`, `patch_rungs`). A runner with a
        `score_fn` compiles its score program's variants the same way,
        on `score_aux`."""
        srv = self.server
        with srv._lock:
            _, cache_row, shard = self._tables()
            nowhere = srv.ctx.put_replicated(
                np.full(srv.num_keys, OOB, np.int32))
            no_cache = cache_row if srv.num_shards == 1 else \
                srv.ctx.put_replicated(np.full(srv.num_keys, -1, np.int32))
            local_index = self._local_neg_index() \
                if self.neg_role is not None else None
            keys = self._upload_keys(
                {r: np.zeros(np.shape(k), _key_dtype(srv.num_keys))
                 for r, k in role_keys.items()})
            fns = [self._step_fn_norep]
            if srv.num_shards > 1:
                fns.append(self.step_fn)
                # and the mirrors' patch program at every rung a
                # journal's answer can take (one shard never changes
                # placement), every key padding
                no_keys = np.empty(0, np.int64)
                for n in patch_rungs(srv.ab.journal_limit):
                    default_port().patch_routes(
                        nowhere, no_cache, srv.ctx.put_replicated(
                            self.router._patch_operand(no_keys, n)))
            for fn in fns:
                pools = tuple((s.main, s.cache, s.delta)
                              for s in srv.stores)
                with srv.exec.track("main"), _GATE:
                    pools, _, _ = fn(
                        pools, self._locstat,
                        (nowhere, no_cache, shard), keys,
                        local_index, self._alias, self._rng, aux,
                        self._scalar(0.0), self._scalar(1e-10))
                    for st, (m, c, d) in zip(srv.stores, pools):
                        st.main, st.cache, st.delta = m, c, d
            if self._score_fn is None:
                return
            pools = tuple((s.main, s.cache, s.delta) for s in srv.stores)
            variants = [True] if srv.num_shards == 1 else [True, False]
            for no_replicas in variants:
                with srv.exec.track("main"), _GATE:
                    self._score_program(no_replicas)(
                        pools, (nowhere, no_cache, shard), keys,
                        score_aux, self._scalar(0.0))

    def _prefetch_refresh(self) -> None:
        """Called by the prefetch pipeline (under the server lock) after
        planner rounds: bring the device table mirrors and the local
        sampling index up to date as soon as the topology settles, so
        the next dispatch finds them fresh instead of refreshing them
        inside its critical section."""
        self.router.refresh()
        if self.neg_role is not None:
            self._local_neg_index()

    def _note_step_writes(self, role_keys) -> None:
        """The fused step is a batched Push in PM terms: staged pull
        buffers covering trained keys must be invalidated like any other
        write (caller holds the server lock), and the stores' dirty-delta
        tracking must see the step's scatter (core/store.py) or the sync
        planner would skip shipping the trained replicas. Device-drawn
        negatives are not enumerable on the host, so runners with an
        in-program sampler conservatively invalidate every staged batch
        and mark the negative class's whole shard written."""
        srv = self.server
        _mark_fused_writes(srv, self.shard, self.role_class, role_keys,
                           skip_roles=self.frozen_roles)
        pre = srv.prefetch
        if pre is None or not pre._staged:
            return
        if self.neg_role is not None:
            pre.invalidate_all()
            return
        srv._prefetch_note(np.concatenate(
            [np.asarray(k, dtype=np.int64).ravel()
             for k in role_keys.values()]))

    def _record_key_locality(self, role_keys) -> None:
        """--sys.stats.locality: the per-key access counts of a step's
        host-known keys (caller holds the server lock), by `Server
        ._route`'s definition of local; its coordinates are dropped, the
        step routes for itself."""
        for k in role_keys.values():
            self.server._route(np.asarray(k, dtype=np.int64), self.shard)

    def _mark_neg_writes(self) -> None:
        """Write tracking for device-drawn negatives (caller holds the
        server lock, AFTER _local_neg_index refreshed for this step):
        their rows are not enumerable on the host, so the negative
        class's whole shard counts as written — every shard when the
        local-index fallback is live, because a full-population draw
        scatters into other shards' main rows too."""
        if self.neg_role is None:
            return
        st = self.server.stores[self.role_class[self.neg_role]]
        if self._li_fallback:
            for s in range(self.server.num_shards):
                st.mark_shard_written(s)
        else:
            st.mark_shard_written(self.shard)

    def prefetch_keys(self, role_keys: Dict[str, np.ndarray]) -> StagedKeys:
        """Pre-stage a future step's key batch on device (the staging
        rule, parallel/mesh.py put_replicated): the upload runs now — on
        the app's intent/prepare path — instead of inside the next
        dispatch.
        Returns the handle for __call__'s `staged` parameter."""
        self._check_batch(role_keys)
        host = {r: np.asarray(k, dtype=_key_dtype(self.server.num_keys))
                for r, k in role_keys.items()}
        return StagedKeys(host, self._upload_keys(host))

    def _upload_keys(self, host_keys: Dict[str, np.ndarray]):
        """Host -> device upload of one dispatch's key arrays (already
        in the key dtype), replicated: the staging rule, mesh.py."""
        srv = self.server
        put = srv.ctx.put_replicated
        with srv._span("fused.key_upload", self._h_key_upload, wait=True):
            return {r: put(k) for r, k in host_keys.items()}

    def _next_rng(self):
        if not self._rng_pool:
            with self.server._span("fused.rng_refill"):
                self._rng, *pool = jax.random.split(self._rng, 65)
                self._rng_pool = pool
        return self._rng_pool.pop()

    def _scalar(self, v: float):
        out = self._scalars.get(v)
        if out is None:
            out = self._scalars[v] = jnp.float32(v)
            if len(self._scalars) > 64:  # lr schedules: bound the cache
                self._scalars = {v: out}
        return out

    def _ensure_drain_every(self, role_keys: Dict[str, np.ndarray]) -> None:
        """Size the locstat drain interval so the int32 params counter
        stays below 2^30 between drains (computed from the first batch's
        params-per-step; key shapes are fixed per runner)."""
        if self._drain_every is None:
            pps = sum(np.asarray(k).size for k in role_keys.values())
            if self._neg_shape is not None:
                pps += int(np.prod(self._neg_shape))
            self._drain_every = max(1, 2**30 // max(1, pps))
            self._g_drain_every.set(self._drain_every)

    def _step_program(self, no_replicas: bool):
        """The compiled step of one variant. On several shards a runner
        whose local index fell back to the whole population
        (`_li_fallback`: its negatives may lie on any shard) takes the
        step as one program over the global pools, not the per-chip
        one (`make_device_routed_step`, `neg_local`)."""
        if self._one_program_over_shards():
            return self._program(make_device_routed_step,
                                 no_replicas=no_replicas, neg_local=False)
        return self._step_fn_norep if no_replicas else self.step_fn

    def _one_program_over_shards(self) -> bool:
        return self._li_fallback and self.server.num_shards > 1

    def _count_step(self, role_keys: Dict[str, np.ndarray],
                    steps: int) -> None:
        """Count the rows `steps` dispatched steps write back (from the
        first batch's key shapes, fixed per runner, like the drain
        interval above) and the scalar each per-chip step sums for its
        loss; the exchange's blocks are the step's own to count
        (`_drain_locstat`)."""
        if self._per_step is None:
            rows = {r: np.asarray(k).size for r, k in role_keys.items()}
            if self.neg_role is not None:
                rows[self.neg_role] = int(np.prod(self._neg_shape))
            rows = {r: n for r, n in rows.items()
                    if r not in self.frozen_roles}

            def takes_kernel(r):  # a pool's one shard, or a chip's block
                main = self.server.stores[self.role_class[r]].main
                return writeback_uses_kernel(jax.ShapeDtypeStruct(
                    (1,) + main.shape[1:], main.dtype))
            self._per_step = (
                sum(rows.values()),
                sum(n for r, n in rows.items() if takes_kernel(r)))
        rows, kernel_rows = self._per_step
        self._c_wb_rows.inc(rows * steps)
        if self._one_program_over_shards():
            return  # GSPMD: no kernel, and its collectives are its own
        self._c_wb_kernel_rows.inc(kernel_rows * steps)
        if self.server.num_shards > 1:
            self._c_exchange.inc(4 * steps)

    def _count_sampled(self, steps: int) -> None:
        """Count the rows `steps` dispatched steps drew from the local
        index. A fallback draw (nothing local) is over the whole
        population: its rows count like any other role's."""
        if self.neg_role is not None and not self._li_fallback:
            self._sampled_pending += int(np.prod(self._neg_shape)) * steps

    def _drain_locstat(self) -> None:
        """Fold the device accumulator into the host int64 totals and reset
        it. A fetch syncs the device, so this runs only at reporting
        time and every _drain_every steps —
        chosen so the int32 params counter stays below 2^30 between
        drains."""
        with self.server._span("fused.locstat_drain", wait=True):
            vals = np.asarray(self._locstat, dtype=np.int64)
        self._loc_host += vals[:4]
        self._c_rows.inc(int(vals[0]))
        self._c_rows_local.inc(int(vals[1]))
        self._c_rows_sampled.inc(self._sampled_pending)
        self._sampled_pending = 0
        # the totals, then each class's pair; roles of one class count
        # no pair apart: the totals are that class's
        roles = sorted(self.role_class)
        counts, away, blocks = np.split(
            vals[4:], [-len(roles) - 1, -len(roles)])  # one shard: empty
        if len(counts) == 2:
            counts = np.tile(counts, 2)
        for c, v in zip([self._c_replica_positions, self._c_replica_chunks]
                        + self._c_replica_by_class, counts):
            c.inc(int(v))
        # the exchange: the positions off the worker's chip, then each
        # role's positions in the blocks summed for them (whole chunks),
        # of its `dim` float32 columns, out and (a trained role's) back
        self._c_exchange_positions.inc(int(away.sum()))
        dim = self._mk_kwargs["role_dim"]
        for r, n in zip(roles, blocks):
            self._c_exchange.inc(
                int(n) * dim[r] * 4 * (1 + (r not in self.frozen_roles)))
        self._locstat = self.server.ctx.put_replicated(self._locstat_zero)
        self._c_drains.inc()

    def locality_counts(self) -> Dict[str, int]:
        """Cumulative step-program access counts, host-side (the device-
        routed analog of Worker.stats; Server.locality_summary merges these
        as both pull and push — the fused step is one batched gather + one
        batched scatter of the same keys)."""
        with self.server._lock:
            self._drain_locstat()
            p, pl, o, ol = (int(v) for v in self._loc_host)
        return {"params": p, "params_local": pl, "ops": o, "ops_local": ol}

    def _shard_has_replicas(self) -> bool:
        return self.server.ab.holds_replicas(self.shard)

    def _local_neg_index(self):
        """The step's `local_index` operand, brought up to date with
        placement (and tier residency). Uniform draws: (padded index
        [capacity], valid count) of the locally-resident keys, sorted,
        padded to a power-of-two capacity so placement changes don't
        change the jit shape (only a capacity doubling recompiles).
        Alias draws: None; the placement goes into the snap table, the
        last of `self._alias`, which callers read AFTER this call."""
        srv = self.server
        li_ver = (srv.topology_version,
                  srv.tier.epoch if srv.tier is not None else -1)
        if self._li_version != li_ver:
            # part of what a placement change costs: the same span and
            # counters as the table mirrors (DeviceRouter.refresh)
            router = self.router
            with srv._span("fused.route_refresh", router._h_refresh,
                           work=router._h_refresh_work):
                # (an index in its fallback is the population, not
                # the local keys: there is nothing to merge into)
                changed = None \
                    if self._li_host is None or self._li_fallback \
                    else _changed_keys(srv, self._li_cursor)
                if changed is None or \
                        not self._patch_local_neg_index(changed):
                    self._build_local_neg_index()
                self._li_cursor = srv.ab.journal_cursor()
            self._li_version = li_ver
            router._c_refresh.inc()
        return self._local_index

    def _build_local_neg_index(self) -> None:
        srv = self.server
        pop = self._neg_population if self._neg_population is not None \
            else np.arange(srv.num_keys, dtype=np.int64)
        local = self._local_neg_mask(pop)
        if self._alias is not None:
            self._alias = self._alias[:2] + (self._snap_table(pop, local),)
            return
        self._set_local_index(pop[local])

    def _set_local_index(self, idx: np.ndarray) -> None:
        """Upload the sorted local keys `idx` as the step's operand: a
        FRESH padded array every time, because the device reads an
        uploaded buffer until its transfer ends."""
        from ..core.store import bucket_size
        cap = bucket_size(len(idx), minimum=64)
        kdt = _key_dtype(self.server.num_keys)
        padded = np.full(cap, np.iinfo(kdt).max, dtype=kdt)
        padded[: len(idx)] = idx
        self._li_host = padded[: len(idx)]
        self._local_index = (self.router._put_counted(padded),
                             jnp.int32(len(idx)))

    def _patch_local_neg_index(self, changed: np.ndarray) -> bool:
        """Bring the uniform path's index up to date by the keys whose
        placement changed (sorted, each once): those of the sampled
        population that left the shard are taken out, those that came
        are merged in, and the index stays sorted: entry for entry what
        `_build_local_neg_index` builds from the tables, so the step
        draws the same negatives. False where nothing would be left
        (the fallback is the full build's to set up)."""
        idx = self._li_host
        changed = changed.astype(idx.dtype)
        pop = self._neg_population
        if pop is not None:
            at = np.minimum(np.searchsorted(pop, changed), len(pop) - 1)
            changed = changed[pop[at] == changed]
        now = self.server.ab.is_local(changed, self.shard)
        at = np.searchsorted(idx, changed)
        was = idx[np.minimum(at, len(idx) - 1)] == changed
        gone, came = at[was & ~now], changed[~was & now]
        if len(idx) - len(gone) + len(came) == 0:
            return False
        router = self.router
        router._c_patch.inc()
        router._c_patch_keys.inc(len(gone) + len(came))
        if len(gone) or len(came):
            kept = np.delete(idx, gone)
            self._set_local_index(
                np.insert(kept, np.searchsorted(kept, came), came))
        return True

    def _snap_table(self, pop: np.ndarray, local: np.ndarray):
        """The alias path's Local-scheme snap, for every alias position
        at once: entry j is the smallest key of `pop[local]` that is >=
        position j's key, wrapping to the smallest (sampling.h:494: what
        a searchsorted into the sorted local keys would give each draw).
        It depends on placement alone, so it is built here, where
        placement changes, and the compiled step reads `snap[j]`.
        `local` is a mask over the sorted population, so nothing is
        searched: a reverse running minimum and one take, O(V). Where
        every key is local (always so on one shard) the snap moves
        nothing and the table is the key table the device holds already."""
        if local.all():
            self._g_snap_moved.set(0.0)
            return self._key_table
        self._g_snap_moved.set(1.0 - float(local[self._key_pos].mean()))
        n = len(pop)
        nxt = np.where(local, np.arange(n), n)
        nxt = np.minimum.accumulate(nxt[::-1])[::-1]
        nxt[nxt == n] = nxt[0]  # past the largest local key: wrap
        self._c_snap_rebuilds.inc()
        return self.router._put_counted(pop[nxt][self._key_pos].astype(
            _key_dtype(self.server.num_keys)))

    def _local_neg_mask(self, pop: np.ndarray) -> np.ndarray:
        """Which keys of the sorted population `pop` the device sampler
        may draw: those resident on this shard, or a fallback where none
        is (never all False). Sets `_li_fallback`."""
        srv = self.server
        ab = srv.ab
        from ..base import NO_SLOT
        local = (ab.owner[pop] == self.shard) | (
            ab.cache_slot[self.shard, pop] != NO_SLOT)
        if srv.tier is not None:
            # tiered storage: device-drawn negatives read/scatter main
            # rows in-program, which only works for DEVICE-RESIDENT
            # rows — restrict the draw population to hot-owned or
            # replicated keys (a residency change invalidates the index
            # via the epoch in li_ver). Sampling from the hot slice is
            # a valid negative draw; cold keys rejoin the population as
            # the promotion worker brings them up.
            cid = self.role_class[self.neg_role]
            res = srv.stores[cid].res
            o_sh, o_sl = ab.owner[pop], ab.slot[pop]
            owner_hot = np.zeros(len(pop), dtype=bool)
            m = (o_sh == self.shard) & (o_sl >= 0)
            if m.any():
                owner_hot[m] = res.dev_row[o_sh[m], o_sl[m]] >= 0
            local = owner_hot | (
                ab.cache_slot[self.shard, pop] != NO_SLOT)
        # fallback flag feeds _mark_neg_writes: full-population draws can
        # scatter into OTHER shards' main rows, so write tracking must
        # widen beyond this shard
        self._li_fallback = not local.any()
        if not self._li_fallback:
            return local
        if srv.tier is None:
            # nothing local: draw from the full population
            return np.ones(len(pop), dtype=bool)
        # tiered: the untiered fallback (draw from the FULL
        # population) would sample cold keys, whose mirror rows
        # are OOB — reads would silently return zeros and
        # scatters drop. Promote a bounded slice of the
        # population (wherever its rows are owned) and draw
        # from the device-resident subset; fail loudly if even
        # that cannot produce one resident key.
        cid = self.role_class[self.neg_role]
        res = srv.stores[cid].res
        take = pop[: 4096]
        srv.tier.ensure_hot(cid, ab.owner[take], ab.slot[take])
        o_sh, o_sl = ab.owner[pop], ab.slot[pop]
        ok = o_sl >= 0
        resident = np.zeros(len(pop), dtype=bool)
        resident[ok] = res.dev_row[o_sh[ok], o_sl[ok]] >= 0
        if not resident.any():
            raise RuntimeError(
                "tiered negative sampling: no device-resident "
                "key in the population and promotion could not "
                "produce one (hot pool full of pinned rows?) — "
                "raise --sys.tier.hot_rows or signal intent on "
                "the sampling population")
        return resident

    def _check_batch(self, role_keys: Dict[str, np.ndarray]) -> None:
        srv = self.server
        if self.neg_role is not None and self.neg_role in role_keys:
            raise ValueError(
                f"role {self.neg_role!r} is sampled on device; caller-"
                "supplied keys for it would be silently discarded — drop "
                "them or build the runner without neg_role")
        from ..base import check_key_range
        for r, k in role_keys.items():
            k64 = np.asarray(k, dtype=np.int64)
            # on device, XLA clamps bad indices instead of raising — reject
            # out-of-range keys here, then fail fast on a wrong role->class
            # mapping (per-class slot indices gathered for the wrong pool
            # would corrupt rows)
            check_key_range(k64, srv.num_keys, f"role {r} key")
            kc = srv.ab.key_class[k64]
            assert (kc == self.role_class[r]).all(), (
                f"role {r}: keys span length classes {np.unique(kc)} but "
                f"role is mapped to class {self.role_class[r]}")
            # multi-process: device tables carry owner=-1 for keys owned by
            # another process — fetch them before routing on device
            srv.ensure_local(k64, self.shard)

    def __call__(self, role_keys: Dict[str, np.ndarray], aux, lr: float,
                 eps: float = 1e-10,
                 staged: Optional[StagedKeys] = None) -> jnp.ndarray:
        with self.server._span("fused.dispatch", self._h_dispatch,
                               work=self._h_dispatch_work):
            return self._dispatch_step(role_keys, aux, lr, eps, staged)

    def _dispatch_step(self, role_keys, aux, lr, eps, staged):
        srv = self.server
        self._check_batch(role_keys)
        if staged is not None and not staged.matches(role_keys):
            raise ValueError(
                "staged keys differ from the step's batch — pass the "
                "handle prefetch_keys returned for THIS batch")
        with srv._lock:
            if srv.tier is not None:
                # tiered storage: the step reads main rows through the
                # hot pool — promote + pin the batch before the route
                # mirror is composed (ensure_hot bumps the residency
                # epoch, which router.tables() below picks up)
                srv.tier.pin_step_keys(self.role_class, role_keys)
            self._note_step_writes(role_keys)
            if srv.locality is not None:
                self._record_key_locality(role_keys)
            tables = self._tables()
            local_index = self._local_neg_index() \
                if self.neg_role is not None else None
            self._mark_neg_writes()
            sub = self._next_rng()
            # keys validated above to be inside [0, num_keys)
            kdtype = _key_dtype(srv.num_keys)
            keys = staged.dev if staged is not None else \
                self._upload_keys({r: np.asarray(k, dtype=kdtype)
                                   for r, k in role_keys.items()})
            pools = tuple((s.main, s.cache, s.delta) for s in srv.stores)
            fn = self._step_program(not self._shard_has_replicas())
            # dispatch under the gate, tracked on the "main" stream for
            # the executor's overlap accounting (enqueue-only: the jit
            # call returns as soon as the program is queued)
            lr, eps = self._scalar(lr), self._scalar(eps)
            self._observe_in_flight()
            with srv.exec.track("main"), _GATE:
                with srv._span("fused.enqueue", self._h_enqueue, wait=True):
                    pools, self._locstat, loss = fn(
                        pools, self._locstat, tables, keys, local_index,
                        self._alias, sub, aux, lr, eps)
                for st, (m, c, d) in zip(srv.stores, pools):
                    st.main, st.cache, st.delta = m, c, d
            self._note_in_flight(loss)
            self.steps += 1
            self._count_step(role_keys, 1)
            self._count_sampled(1)
            self._ensure_drain_every(role_keys)
            if self.steps % self._drain_every == 0:
                self._drain_locstat()
        return loss

    def _observe_in_flight(self) -> None:
        """`fused.inflight_steps`: of the server's dispatched steps,
        those the device has not finished (caller holds the server
        lock, before its own enqueue). Steps finish in dispatch order,
        so the done ones are popped from the left; `is_ready` never
        blocks."""
        dq = self._inflight
        if dq is None:
            return
        while dq and dq[0].is_ready():
            dq.popleft()
        self._h_inflight.observe(len(dq))

    def _note_in_flight(self, loss) -> None:
        if self._inflight is not None:
            self._inflight.append(loss)

    def _score_program(self, no_replicas: bool):
        """The compiled score program of one variant, kept in `programs`
        beside the step's."""
        key = ("make_device_routed_score", no_replicas)
        if key not in self._programs:
            roles = [r for r in self.role_class if r != self.neg_role]
            self._programs[key] = make_device_routed_score(
                self._score_fn, self.role_class,
                self._mk_kwargs["role_dim"], roles,
                no_replicas=no_replicas)
        return self._programs[key]

    def score(self, role_keys: Dict[str, np.ndarray], aux, acc=None,
              staged: Optional[StagedKeys] = None) -> jnp.ndarray:
        """`acc + score_fn(embs, aux)` over the rows `role_keys` name,
        as a device scalar: the fused step's route and gather without
        its gradient and write-back (make_device_routed_score). A Pull
        in PM terms: nothing is written, no pool is donated, no clock
        moves, and neither the RNG sequence nor the locality counts are
        touched. `acc` (a device scalar from an earlier call, or None
        for zero) lets a walk over many batches keep its sum on the
        device and fetch it once. `staged`: the handle `prefetch_keys`
        returned for THIS batch (it was checked there); a walk that
        scores the same batches again and again uploads them once."""
        srv = self.server
        with srv._span("fused.score"):
            if staged is None or srv.glob is not None:
                self._check_batch(role_keys)  # several processes: fetch
            with srv._lock:
                if srv.tier is not None:
                    srv.tier.pin_step_keys(self.role_class, role_keys)
                tables = self._tables()
                kdtype = _key_dtype(srv.num_keys)
                keys = staged.dev if staged is not None else \
                    self._upload_keys({r: np.asarray(k, dtype=kdtype)
                                       for r, k in role_keys.items()})
                pools = tuple((s.main, s.cache, s.delta)
                              for s in srv.stores)
                fn = self._score_program(not self._shard_has_replicas())
                if acc is None:
                    acc = self._scalar(0.0)
                # no histogram: `fused.enqueue_s` counts step and scan
                # dispatches, as `fused.dispatch_s` does
                with srv.exec.track("main"), _GATE, \
                        srv._span("fused.enqueue", wait=True):
                    acc = fn(pools, tables, keys, aux, acc)
                self._c_score_rows.inc(
                    sum(np.asarray(k).size for k in role_keys.values()))
        return acc

    def _scan_fn(self, **variant):
        return self._program(make_device_routed_scan, **variant)

    def run_scan(self, batches: Sequence[Dict[str, np.ndarray]], auxes,
                 lr: float, eps: float = 1e-10) -> np.ndarray:
        """Train K steps in ONE device dispatch (lax.scan over the stacked
        batches; make_device_routed_scan). Returns the [K] per-step losses
        (device array). All batches must share roles and shapes (one
        compiled variant per K). Placement freezes for the window — the
        planner's changes apply between scans, matching the apps'
        lookahead contract. `auxes` is a list of per-step aux pytrees, or
        None when the loss takes no aux."""
        with self.server._span("fused.dispatch", self._h_dispatch,
                               work=self._h_dispatch_work):
            return self._dispatch_scan(batches, auxes, lr, eps)

    def _dispatch_scan(self, batches, auxes, lr, eps):
        srv = self.server
        K = len(batches)
        assert K >= 1, "empty scan window"
        for b in batches:
            self._check_batch(b)
        has_aux = auxes is not None
        if has_aux:
            assert len(auxes) == K, "one aux per batch"
        with srv._lock:
            if srv.tier is not None:
                # placement AND residency freeze for the scan window:
                # the route mirror is read ONCE for all K batches, so
                # the whole window's rows must be hot simultaneously —
                # pin the UNION (per-batch pinning would let a later
                # batch's forced eviction victimize an earlier one)
                union = {r: np.concatenate(
                    [np.asarray(b[r], dtype=np.int64).ravel()
                     for b in batches]) for r in batches[0]}
                srv.tier.pin_step_keys(self.role_class, union)
            for b in batches:
                self._note_step_writes(b)
                if srv.locality is not None:
                    self._record_key_locality(b)
            tables = self._tables()
            local_index = self._local_neg_index() \
                if self.neg_role is not None else None
            self._mark_neg_writes()
            # draw through _next_rng so the key sequence is IDENTICAL to K
            # sequential __call__ steps (refills included) — the scan-vs-
            # sequential equivalence depends on it when negatives are
            # drawn in-program
            rngs = jnp.stack([self._next_rng() for _ in range(K)])
            kdtype = _key_dtype(srv.num_keys)
            put = srv.ctx.put_replicated  # the staging rule, mesh.py
            keys = self._upload_keys(
                {r: np.stack([np.asarray(b[r], dtype=kdtype)
                              for b in batches]) for r in batches[0]})
            aux = None
            if has_aux:
                import jax.tree_util as jtu
                aux = jtu.tree_map(
                    lambda *xs: put(np.stack([np.asarray(x) for x in xs])),
                    *auxes)
            pools = tuple((s.main, s.cache, s.delta) for s in srv.stores)
            variant = dict(no_replicas=not self._shard_has_replicas(),
                           has_aux=has_aux)
            if self._one_program_over_shards():  # as _step_program
                variant["neg_local"] = False
            fn = self._scan_fn(**variant)
            lr, eps = self._scalar(lr), self._scalar(eps)
            self._observe_in_flight()
            with srv.exec.track("main"), _GATE:
                with srv._span("fused.enqueue", self._h_enqueue, wait=True):
                    pools, self._locstat, losses = fn(
                        pools, self._locstat, tables, keys, local_index,
                        self._alias, rngs, aux, lr, eps)
                for st, (m, c, d) in zip(srv.stores, pools):
                    st.main, st.cache, st.delta = m, c, d
            self._note_in_flight(losses)
            self.steps += K
            self._count_step(batches[0], K)
            self._count_sampled(K)
            self._ensure_drain_every(batches[0])
            if self.steps // self._drain_every != \
                    (self.steps - K) // self._drain_every:
                self._drain_locstat()
        return losses
