"""Device-resident sharded parameter pools + the jitted data-plane programs.

This replaces the reference's `DefaultColoServerHandle` (the node-local store
with a 16384-mutex lock array, coloc_kv_server_handle.h) with three pooled
`jax.Array`s sharded over the mesh "kv" axis:

    main  [S, slots, L]   main copies          (owner shard holds the row)
    cache [S, cslots, L]  replica base values  (value at last refresh)
    delta [S, cslots, L]  additive updates accumulated against replicas

No locks are needed: AdaPM's merge function is additive (reference
handle.h:404-415), so XLA scatter-add expresses concurrent pushes exactly, and
single-controller dispatch order serializes programs on the (donated) buffers.
The reference's `sync_state` copy + subtraction (`val - sync_state`,
handle.h:601-662) is replaced by *storing the delta directly*; a replica read
returns `cache + delta`, which preserves read-your-writes.

A `ShardedStore` is one uniform-value-length pool (a "length class"); routing
from keys to (shard, slot) indices lives in Server/Addressbook. All programs
take fixed-shape index buffers; batches are padded to power-of-two buckets and
padding entries carry out-of-range indices so JAX's mode="drop" (scatter) and
mode="fill" (gather) make them no-ops.

Since ISSUE 14 the store holds NO device programs of its own: every
dispatch goes through the narrow DevicePort (adapm_tpu/device — the
jitted programs moved verbatim into device/jaxport.py), so a
real-accelerator backend is one new port implementation rather than a
store rewrite. The port brackets each enqueue in the process-wide
sharded-dispatch gate internally (docs/EXECUTOR.md); this module is
device-API-free (adapm-lint APM008).
"""
from __future__ import annotations

import contextlib
import math

import jax
import numpy as np

from ..device import default_port
from ..device.jaxport import F16_MAX, OOB  # noqa: F401  (re-exported:
# OOB/F16_MAX are part of this module's historical API — routing, tier,
# serve, and quant layers import them from here)
from ..parallel.mesh import MeshContext


def bucket_size(n: int, minimum: int = 8) -> int:
    """Pad n up to a power of two (bounds the number of compiled variants)."""
    if n <= minimum:
        return minimum
    return 1 << math.ceil(math.log2(n))


def bucket_ladder(top: int, minimum: int = 8) -> list:
    """Every bucket a batch of 1..`top` rows can be padded to: the
    floor, then the powers of two above it (what a `precompile` runs)."""
    sizes, n = set(), 1
    while n < 2 * max(1, top):
        sizes.add(bucket_size(min(n, top), minimum))
        n *= 2
    return sorted(sizes)


def pad_to(arr: np.ndarray, size: int, fill) -> np.ndarray:
    out = np.full((size,) + arr.shape[1:], fill, dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def pad_bucket(n: int, *arrays_and_fills, minimum: int = 8):
    b = bucket_size(n, minimum)
    # numpy (uncommitted) on purpose: jit places numpy args directly with
    # each executable's expected sharding. A jnp.asarray here would commit
    # them to device 0, and every mesh-jitted op would then RESHARD them
    # host-side per call (measured: ~10x slowdown of planner device ops on
    # an 8-device mesh, profile dominated by Array._value readbacks).
    # Caveat (the staging rule, parallel/mesh.py): a bare numpy arg
    # uploads inside dispatch; fine for planner-frequency ops and the bindings'
    # per-op pull/push, but anything per-STEP hot must pre-stage via
    # MeshContext.put_replicated the way ops/fused.py _upload_keys does.
    return [pad_to(a, b, fill) for a, fill in arrays_and_fills]


# ---------------------------------------------------------------------------
# (the jitted data-plane programs formerly defined here live in
# adapm_tpu/device/jaxport.py since ISSUE 14 — same names, same bits)
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------


class StagingPool:
    """Row budget for device-resident staged gather buffers (one per
    length class; core/intent.py PrefetchScheduler).

    Not a preallocated arena: XLA's gather already materializes its
    output in a fresh device buffer, so copying that into a reserved
    pool would only add a device-to-device copy. What staging needs is a
    BOUND — prefetch must not be able to OOM HBM by racing ahead of the
    consumer — so the pool accounts rows (buffers stay owned by the
    staged entries) and `stage_gather` refuses to gather past the
    budget. Thread-safe: the prefetch thread acquires, any thread that
    drops/consumes an entry releases."""

    def __init__(self, max_rows: int):
        import threading
        self.max_rows = max_rows
        self._rows = 0
        self._hwm = 0  # occupancy high-water mark (obs: staging.rows_hwm)
        self._lock = threading.Lock()

    def try_acquire(self, rows: int) -> bool:
        with self._lock:
            if self._rows + rows > self.max_rows:
                return False
            self._rows += rows
            if self._rows > self._hwm:
                self._hwm = self._rows
            return True

    def release(self, rows: int) -> None:
        with self._lock:
            self._rows -= rows
            assert self._rows >= 0, "staging pool released more than held"

    @property
    def rows_in_use(self) -> int:
        return self._rows

    @property
    def rows_hwm(self) -> int:
        """Highest concurrent row occupancy seen (never resets)."""
        return self._hwm


class ShardedStore:
    """Pools for one length class. Index-level API; key routing lives above."""

    def __init__(self, num_keys_in_class: int, value_length: int,
                 ctx: MeshContext, dtype=np.float32, over_alloc: float = 1.25,
                 cache_slots_per_shard: int = 0, bucket_min: int = 8,
                 tier_hot_rows: int = 0, tier_cold_dtype: str = "fp32",
                 port=None, wait=contextlib.nullcontext):
        self.value_length = value_length
        self.ctx = ctx
        self.dtype = dtype
        # the device plane (ISSUE 14): every program dispatch below goes
        # through this narrow port — swap it to target a new backend
        self.port = port if port is not None else default_port()
        # the server's wait bracket (Server._store_wait: the span
        # `store.enqueue`), handed over like the port: around each call
        # of a planner program, which returns once the program is
        # queued and so holds the wait for a free dispatch slot. The
        # epoch bookkeeping around the calls stays outside it
        self._wait = wait
        # min padded batch size (--sys equivalent: remote_bucket_min) — a
        # larger floor means fewer distinct bucket shapes, i.e. fewer XLA
        # compilations, at the cost of padding work on tiny batches
        self.bucket_min = max(1, bucket_min)
        S = ctx.num_shards

        def _round8(n: int) -> int:
            # Slot counts are rounded to a multiple of 8: the TPU backend
            # picks the pool layout from the SHAPE, and an odd slot count
            # gets a (1,0,2):T(1,128) layout whose scatter operand then
            # needs a pool-sized layout-conversion copy inside every fused
            # step (observed +9.6 GiB peak HBM on a Wikidata5M-sized
            # table — the difference between fitting on a chip and OOM).
            # 8-aligned counts get the scatter-native T(8,128) layout.
            return -8 * (-n // 8)

        per_shard = max(1, math.ceil(num_keys_in_class / S))
        # floor at per_shard: an over_alloc < 1 (user squeezing HBM) must
        # not produce a pool smaller than the initial allocation
        self.main_slots = _round8(max(per_shard,
                                      math.ceil(per_shard * over_alloc)))
        # replica slots a shard: as many as asked for, and never more
        # than the class has keys (a shard holds at most one replica of
        # a key, so slots beyond that could not be filled: one
        # --sys.cache_slots_per_shard sizes every class, and a class of
        # a few thousand long rows beside one of millions of short ones
        # would otherwise get the millions); 0 = as many as main holds
        self.cache_slots = _round8(max(1, min(
            cache_slots_per_shard or per_shard, num_keys_in_class)))

        # -- tiered residency (ISSUE 5 tentpole; adapm_tpu/tier) -----------
        # tier_hot_rows > 0 caps the DEVICE main pool at that many rows
        # per shard; the authoritative table spans main_slots rows per
        # shard, with rows beyond the hot set living in the host cold
        # store (`self.cold`, layout mirroring the pool row format).
        # Replica cache/delta pools stay fully device-resident. All
        # index-level ops keep taking (shard, SLOT) coordinates — the
        # residency map translates slots to hot rows at dispatch time,
        # so routing plans and the addressbook never see the tier.
        self.res = None
        self.cold = None          # fp32 alias of coldq.q (back-compat)
        self.coldq = None         # QuantCold (tier/quant.py)
        self.tier_hot_hits = 0   # owner-served gather entries, hot
        self.tier_cold_hits = 0  # owner-served gather entries, cold
        self.tier_hist = None    # cold-serve latency hist (TierManager)
        # the bag read's cold staging: its bracket (`serve.cold_stage`)
        # and the bytes it uploads (TierManager hands both over)
        self.tier_stage = contextlib.nullcontext
        self.tier_stage_bytes = None
        self.stage_ring = None   # its kept staging buffers (coldpath.py)
        dev_main_slots = self.main_slots
        if tier_hot_rows > 0:
            from ..tier.coldpath import StageRing
            from ..tier.quant import QuantCold
            from ..tier.residency import Residency
            self.stage_ring = StageRing()
            dev_main_slots = _round8(
                min(self.main_slots, max(8, tier_hot_rows)))
            self.res = Residency(S, self.main_slots, dev_main_slots)
            # the cold tier, in --sys.tier.cold_dtype format (fp32 is a
            # bit-identical raw-array passthrough — the pre-PR pin);
            # residual capacity scales with the hot pool: the rows that
            # cycle promote/demote are the ones that park remainders
            self.coldq = QuantCold(
                S, self.main_slots, value_length, mode=tier_cold_dtype,
                resid_cap=min(65536, max(1024, 4 * dev_main_slots)))
            if tier_cold_dtype == "fp32":
                self.cold = self.coldq.q

        # donation-aware pool allocation through the port: the returned
        # buffers are the roots of the donated program chain
        sh = ctx.shard0()
        self.main = self.port.alloc_pool(
            (S, dev_main_slots, value_length), dtype, sh)
        self.cache = self.port.alloc_pool(
            (S, self.cache_slots, value_length), dtype, sh)
        self.delta = self.port.alloc_pool(
            (S, self.cache_slots, value_length), dtype, sh)

        # -- dirty-delta tracking (host-side, PR 3 tentpole) ---------------
        # NOTE (PR 5, tiering): the epochs below are indexed by SLOT,
        # not by device row, so the tracking extends to cold rows for
        # free — a write that lands in the cold store bumps the same
        # main_epoch[o, os] cell a hot write would, and the dirty-delta
        # sync filter keeps working across promotions/demotions (which
        # move values without changing them, hence without bumping).
        # A sync of replica (s, cs) against owner row (o, os) is a
        # bit-for-bit no-op iff its pending delta is zero AND its base
        # still equals the main row. Both facts are tracked on the host
        # so the planner can skip no-op syncs without a device readback:
        #   main_epoch[o, os]   — bumped (from one per-store counter) by
        #                         every program that can change a main
        #                         row's VALUE;
        #   repl_epoch[s, cs]   — the main row's epoch at the replica's
        #                         last base refresh;
        #   delta_dirty[s, cs]  — a delta write landed since that refresh.
        # dirty  :=  delta_dirty | (main_epoch != repl_epoch).
        # Conservative only toward syncing (a zero-valued push still
        # marks dirty); never toward skipping — the invariant the
        # dirty-vs-full consistency test pins (tests/test_replica_table).
        self._epoch = 1
        self.main_epoch = np.zeros((S, self.main_slots), dtype=np.int64)
        self.repl_epoch = np.zeros((S, self.cache_slots), dtype=np.int64)
        self.delta_dirty = np.zeros((S, self.cache_slots), dtype=bool)

        # -- sync wire accounting (ISSUE 8; --sys.sync.compress) -----------
        # bytes one sync round ships in the configured wire format vs
        # what full-width f32 would have cost for the same rows —
        # bumped by sync_replicas under the server lock; read by the
        # sync.bytes_* gauges (core/sync.py). With sync_threshold > 0
        # the ship/hold decision is on device, so these count the
        # CONSIDERED rows (an exact on-device count would cost a
        # readback per round) — same convention as keys_synced.
        self.sync_bytes_shipped = 0
        self.sync_bytes_full = 0
        # max-abs residual parked by the last compressed round: a jnp
        # scalar kept UNCONVERTED (float() would block the round);
        # sync.ef_residual_norm converts it lazily at snapshot time
        self._ef_resid_dev = None
        self._ef_resid_host = 0.0  # tiered cold-owner (host) rounds

        # host-side count of dispatched gather programs. Lock-free (a
        # racing increment may be lost): this is a LIVENESS probe — the
        # serve idle guard (scripts/serve_latency_check.py) asserts it
        # does not move while the serving plane is idle — not an exact
        # accounting surface.
        self.gathers = 0

    def _next_epoch(self) -> int:
        self._epoch += 1
        return self._epoch

    def reset_write_tracking(self) -> None:
        """Conservatively mark everything dirty (checkpoint restore
        replaces the pools wholesale): the first sync round after a
        reset re-ships every live replica once, then the filter
        reconverges."""
        self._epoch += 1
        self.main_epoch.fill(self._epoch)
        self.repl_epoch.fill(0)
        self.delta_dirty.fill(True)

    def mark_shard_written(self, shard: int) -> None:
        """Conservative write-tracking for in-program scatters whose row
        set the host cannot enumerate (device-drawn negatives in the
        device-routed fused step): every row `shard` holds counts as
        written. Two contiguous row fills — cheap relative to the step
        dispatch — at the cost of making the dirty filter inert for
        this shard's replicas until they resync (exactly the pre-filter
        behavior, never a missed sync)."""
        self.main_epoch[shard, :] = self._next_epoch()
        self.delta_dirty[shard, :] = True

    def mark_routed_writes(self, shard: int, cache_rows: np.ndarray,
                           owner_sh: np.ndarray,
                           owner_sl: np.ndarray) -> None:
        """Exact write-tracking for a fused-step scatter of host-known
        keys routed by the shared policy (replica delta row where
        `cache_rows` >= 0, else the owner main row). Caller resolves the
        coordinates from the addressbook under the server lock — the
        same tables the device program routes with."""
        repl = cache_rows >= 0
        if repl.any():
            self.delta_dirty[shard, cache_rows[repl]] = True
        # owner_sl < 0 (process-remote key not yet localized) would wrap
        # as a negative fancy index — skip; its write lands remotely
        m = ~repl & (owner_sl >= 0)
        if m.any():
            self.main_epoch[owner_sh[m], owner_sl[m]] = self._next_epoch()

    # -- write-epoch export (ISSUE 9; serve/replica.py) ----------------------

    def export_epochs(self, o_sh: np.ndarray,
                      o_sl: np.ndarray) -> np.ndarray:
        """Copy of the main-row write epochs at (shard, slot) coords —
        the serve replica records these under the server lock at
        snapshot time. A row whose epoch later differs has (or may
        have) a changed VALUE; promotions/demotions move rows without
        changing them and deliberately do not bump."""
        return self.main_epoch[o_sh, o_sl].copy()

    def epochs_unchanged(self, o_sh: np.ndarray, o_sl: np.ndarray,
                         epochs: np.ndarray) -> bool:
        """True iff every (shard, slot) row's main epoch still equals
        the exported value — the serve replica's read-your-writes /
        staleness guard. Pure host read, safe without the lock: every
        write path bumps the epoch cell BEFORE its device program is
        enqueued (under the server lock), so a write that completed
        before this check is always visible; a concurrent write that
        is not yet visible linearizes after the lock-free read."""
        return bool(np.array_equal(self.main_epoch[o_sh, o_sl], epochs))

    def _vals_bucket(self, vals, bucket: int):
        # numpy (uncommitted) for the same reason as pad_bucket: a device-0
        # committed array would be host-resharded by every mesh-jitted op
        v = np.zeros((bucket, self.value_length), dtype=self.dtype)
        n = vals.shape[0]
        v[:n] = np.asarray(vals)
        return v

    # index-level ops (all index arrays are np.int32, padded by caller or
    # padded here via pad_bucket)

    def gather(self, o_shard, o_slot, c_shard, c_slot, use_cache):
        n = len(o_shard)
        self.gathers += 1
        if self.res is not None:
            from ..tier import coldpath
            return coldpath.gather_tiered(self, o_shard, o_slot,
                                          c_shard, c_slot, use_cache)
        a = pad_bucket(n, (o_shard, 0), (o_slot, OOB), (c_shard, 0),
                       (c_slot, OOB), (use_cache, False),
                       minimum=self.bucket_min)
        return self.port.gather(self.main, self.cache, self.delta, *a)

    def gather_pool(self, o_shard, o_slot, c_shard, c_slot, use_cache,
                    seg, nbags: int, pooling: str = "sum"):
        """Fused embedding-bag read (ISSUE 16): gather member rows
        exactly as `gather` and reduce them into per-bag vectors in ONE
        port program. `seg` maps each member entry to its bag index
        (< nbags); the result's first `nbags` rows are the pooled
        vectors (the rest is bucket padding — slice `[:nbags]`).
        Bit-identical to host-pooling this batch's `gather` rows with
        `np.add.at` (the batch-order accumulation contract)."""
        n = len(o_shard)
        self.gathers += 1
        nb = bucket_size(max(int(nbags), 1), self.bucket_min)
        out = np.zeros((nb, self.value_length),
                       dtype=np.dtype(self.dtype))
        if self.res is not None:
            from ..tier import coldpath
            return coldpath.gather_pool_tiered(
                self, o_shard, o_slot, c_shard, c_slot, use_cache,
                seg, out, pooling)
        a = pad_bucket(n, (o_shard, 0), (o_slot, OOB), (c_shard, 0),
                       (c_slot, OOB), (use_cache, False),
                       (np.asarray(seg, dtype=np.int32), OOB),
                       minimum=self.bucket_min)
        return self.port.gather_pool(self.main, self.cache, self.delta,
                                     *a, out, pooling=pooling)

    def stage_gather(self, o_shard, o_slot, c_shard, c_slot, use_cache,
                     pool: "StagingPool"):
        """The gather-into-staging program (prefetch pipeline): identical
        program and result to `gather` — a staged pull must be
        bit-identical to the pull it replaces — but accounted against
        `pool`'s row budget. Returns (device rows, accounted row count),
        or None when the budget is exhausted (the caller skips staging;
        the consumer falls back to a plain pull — slower, never wrong).
        The caller must `pool.release(rows)` when the staged buffer is
        consumed or dropped."""
        rows = bucket_size(len(o_shard), self.bucket_min)
        if not pool.try_acquire(rows):
            return None
        return self.gather(o_shard, o_slot, c_shard, c_slot,
                           use_cache), rows

    def scatter_add(self, o_shard, o_slot, d_shard, d_slot, vals):
        n = len(o_shard)
        m = np.asarray(o_slot) != OOB
        if m.any():
            self.main_epoch[np.asarray(o_shard)[m],
                            np.asarray(o_slot)[m]] = self._next_epoch()
        md = np.asarray(d_slot) != OOB
        if md.any():
            self.delta_dirty[np.asarray(d_shard)[md],
                             np.asarray(d_slot)[md]] = True
        if self.res is not None:
            from ..tier import coldpath
            coldpath.scatter_add_tiered(self, o_shard, o_slot,
                                        d_shard, d_slot, vals)
            return
        a = pad_bucket(n, (o_shard, 0), (o_slot, OOB), (d_shard, 0),
                       (d_slot, OOB), minimum=self.bucket_min)
        v = self._vals_bucket(vals, a[0].shape[0])
        self.main, self.delta = self.port.scatter_add(
            self.main, self.delta, *a, v)

    def set_rows(self, o_shard, o_slot, vals, c_shard, c_slot):
        n = len(o_shard)
        e = self._next_epoch()
        m = np.asarray(o_slot) != OOB
        if m.any():
            self.main_epoch[np.asarray(o_shard)[m],
                            np.asarray(o_slot)[m]] = e
        # the writer's refreshed replica carries the set value with a
        # cleared delta: clean at the new epoch (rows are index-aligned
        # with the owner rows, so both sides stamp the same e)
        mc = np.asarray(c_slot) != OOB
        if mc.any():
            cs, cl = np.asarray(c_shard)[mc], np.asarray(c_slot)[mc]
            self.repl_epoch[cs, cl] = e
            self.delta_dirty[cs, cl] = False
        if self.res is not None:
            from ..tier import coldpath
            coldpath.set_rows_tiered(self, o_shard, o_slot, vals,
                                     c_shard, c_slot)
            return
        a = pad_bucket(n, (o_shard, 0), (o_slot, OOB), (c_shard, 0),
                       (c_slot, OOB), minimum=self.bucket_min)
        v = self._vals_bucket(vals, a[0].shape[0])
        self.main, self.cache, self.delta = self.port.set_rows(
            self.main, self.cache, self.delta, a[0], a[1], v,
            a[2], a[3])

    def replica_create(self, o_shard, o_slot, c_shard, c_slot):
        n = len(o_shard)
        # a fresh replica copies the CURRENT main row: clean at the main
        # row's epoch (no sync needed until someone writes)
        self.repl_epoch[c_shard, c_slot] = self.main_epoch[o_shard, o_slot]
        self.delta_dirty[c_shard, c_slot] = False
        if self.res is not None:
            from ..tier import coldpath
            coldpath.replica_create_tiered(self, o_shard, o_slot,
                                           c_shard, c_slot)
            return
        a = pad_bucket(n, (o_shard, 0), (o_slot, OOB), (c_shard, 0),
                       (c_slot, OOB), minimum=self.bucket_min)
        with self._wait():
            self.cache, self.delta = self.port.replica_create(
                self.main, self.cache, self.delta, *a)

    def sync_replicas(self, r_shard, r_cslot, o_shard, o_slot,
                      threshold: float = 0.0, compress: str = "off"):
        n = len(r_shard)
        if n:
            # wire accounting: what this batch ships in `compress`
            # format vs full-width f32 (tier/quant.py wire table)
            from ..tier.quant import wire_bytes_per_row
            self.sync_bytes_shipped += n * wire_bytes_per_row(
                compress, self.value_length)
            self.sync_bytes_full += n * 4 * self.value_length
        if threshold <= 0.0:
            r_sh, r_cs = np.asarray(r_shard), np.asarray(r_cslot)
            o_sh, o_sl = np.asarray(o_shard), np.asarray(o_slot)
            # only owner rows receiving a DIRTY delta advance the epoch:
            # a clean-but-stale replica's refresh merges a zero delta and
            # leaves main unchanged — bumping for it would re-stale every
            # sibling replica and the filter would ping-pong forever
            dd = self.delta_dirty[r_sh, r_cs]
            if dd.any():
                self.main_epoch[o_sh[dd], o_sl[dd]] = self._next_epoch()
            # refresh: every replica in the batch now equals its main row
            # (read AFTER the bump; duplicate owner rows agree by
            # construction — one fresh gather feeds them all)
            self.repl_epoch[r_sh, r_cs] = self.main_epoch[o_sh, o_sl]
            self.delta_dirty[r_sh, r_cs] = False
        # threshold > 0: the ship/hold decision is made ON DEVICE, so the
        # host cannot know which deltas merged or which bases refreshed —
        # leave the tracking untouched (replicas stay dirty and are
        # re-considered every round, the pre-filter behavior)
        if self.res is not None:
            from ..tier import coldpath
            coldpath.sync_replicas_tiered(self, r_shard, r_cslot,
                                          o_shard, o_slot,
                                          threshold=threshold,
                                          compress=compress)
            return
        a = pad_bucket(n, (r_shard, 0), (r_cslot, OOB), (o_shard, 0),
                       (o_slot, OOB), minimum=self.bucket_min)
        with self._wait():
            out = self.port.sync_replicas(self.main, self.cache,
                                          self.delta, *a,
                                          threshold=threshold,
                                          compress=compress)
        if compress != "off":
            (self.main, self.cache, self.delta,
             self._ef_resid_dev) = out
        else:
            self.main, self.cache, self.delta = out

    def ef_residual_norm(self) -> float:
        """Max-abs residual parked by the most recent compressed sync
        round (device + tiered host paths). Converting the device
        scalar synchronizes with the round's program — snapshot-time
        cost only, never on the round itself."""
        dev = 0.0
        if self._ef_resid_dev is not None:
            dev = float(np.asarray(self._ef_resid_dev))
        return max(dev, self._ef_resid_host)

    def relocate_rows(self, old_shard, old_slot, new_shard, new_slot,
                      rc_shard, rc_slot):
        n = len(old_shard)
        # the moved (possibly delta-merged) main rows get a fresh epoch:
        # conservative — surviving replicas of the key resync once
        m = np.asarray(new_slot) != OOB
        if m.any():
            self.main_epoch[np.asarray(new_shard)[m],
                            np.asarray(new_slot)[m]] = self._next_epoch()
        mr = np.asarray(rc_slot) != OOB
        if mr.any():  # upgraded replica slot is freed; leave it clean
            self.delta_dirty[np.asarray(rc_shard)[mr],
                             np.asarray(rc_slot)[mr]] = False
        if self.res is not None:
            from ..tier import coldpath
            coldpath.relocate_tiered(self, old_shard, old_slot,
                                     new_shard, new_slot,
                                     rc_shard, rc_slot)
            return
        a = pad_bucket(n, (old_shard, 0), (old_slot, OOB), (new_shard, 0),
                       (new_slot, OOB), (rc_shard, 0), (rc_slot, OOB),
                       minimum=self.bucket_min)
        with self._wait():
            self.main, self.delta = self.port.relocate(
                self.main, self.delta, *a)

    def precompile_planner(self, moved: int, synced: int,
                           sync_variants=((0.0, "off"),)) -> int:
        """Run the planner's three bucketed programs once at every bucket
        size they can be called with, so that none compiles later, inside
        a timed loop: `replica_create` and `relocate_rows` up to `moved`
        rows a call, `sync_replicas` (each (threshold, compress) variant
        given) up to `synced`. The ladder is `bucket_size`'s: the floor,
        then the powers of two above it. Every coordinate is out of
        bounds, as a padded tail's is, so each program reads fill and
        writes nothing: the pools come back bit for bit. Returns how
        many programs ran. A tiered store dispatches other programs
        (tier/coldpath.py) and is left alone."""
        if self.res is not None:
            return 0

        ran = 0
        for b in bucket_ladder(moved, self.bucket_min):
            sh, oob = np.zeros(b, np.int32), np.full(b, OOB, np.int32)
            self.cache, self.delta = self.port.replica_create(
                self.main, self.cache, self.delta, sh, oob, sh, oob)
            self.main, self.delta = self.port.relocate(
                self.main, self.delta, sh, oob, sh, oob, sh, oob)
            ran += 2
        for b in bucket_ladder(synced, self.bucket_min):
            sh, oob = np.zeros(b, np.int32), np.full(b, OOB, np.int32)
            for threshold, compress in sync_variants:
                out = self.port.sync_replicas(
                    self.main, self.cache, self.delta, sh, oob, sh, oob,
                    threshold=threshold, compress=compress)
                self.main, self.cache, self.delta = out[:3]
                ran += 1
        return ran

    # -- cross-process helpers (parallel/pm.py GlobalPM) ---------------------

    def read_rows(self, which: str, sh, sl) -> np.ndarray:
        """Host readback of pool rows (non-destructive). `which` selects the
        pool; padding rows are dropped from the result. Slot-indexed for
        "main" — tier-aware (hot rows via a device gather, cold rows
        from the host cold store)."""
        if which == "main" and self.res is not None:
            from ..tier import coldpath
            return coldpath.read_main_rows_tiered(self, sh, sl)
        n = len(sh)
        a = pad_bucket(n, (sh, 0), (sl, OOB), minimum=self.bucket_min)
        arr = {"main": self.main, "cache": self.cache,
               "delta": self.delta}[which]
        with self._wait():  # the program's call and the read-back
            rows = np.asarray(self.port.read_rows_at(arr, *a))
        return rows[:n]

    # -- tiered-residency helpers (adapm_tpu/tier; no-ops untiered) ----------

    def read_hot_rows_at(self, sh: np.ndarray, row: np.ndarray) -> np.ndarray:
        """Host readback of hot-pool rows by DEVICE ROW index (the
        demotion/relocation readback; non-destructive)."""
        n = len(sh)
        a = pad_bucket(n, (sh, 0), (row, OOB), minimum=self.bucket_min)
        rows = self.port.read_rows_at(self.main, *a)
        return np.asarray(rows)[:n]

    def main_host(self) -> np.ndarray:
        """The full authoritative main table [S, main_slots, L] on host
        (checkpoint save, bulk reads) — one whole-pool copy untiered,
        cold store overlaid with the hot pool's rows tiered."""
        if self.res is None:
            return np.asarray(self.main)
        from ..tier import coldpath
        return coldpath.main_full_host(self)

    @property
    def main_shape_full(self):
        """Shape of the authoritative main table (checkpoint geometry —
        identical whether or not the store is tiered, so checkpoints
        restore across tier configurations)."""
        S = self.ctx.num_shards
        return (S, self.main_slots, self.value_length)

    def install_replica_rows(self, c_shard, c_slot, vals) -> None:
        n = len(c_shard)
        # cross-process replica: its base comes from a remote owner, so
        # local epochs cannot track it (cross replicas are exempt from
        # the dirty filter — core/sync.py sync_channel)
        self.delta_dirty[c_shard, c_slot] = False
        a = pad_bucket(n, (c_shard, 0), (c_slot, OOB),
                       minimum=self.bucket_min)
        v = self._vals_bucket(vals, a[0].shape[0])
        self.cache, self.delta = self.port.install_rows(
            self.cache, self.delta, *a, v)

    def refresh_after_sync(self, c_shard, c_slot, fresh, shipped) -> None:
        n = len(c_shard)
        a = pad_bucket(n, (c_shard, 0), (c_slot, OOB),
                       minimum=self.bucket_min)
        b = a[0].shape[0]
        self.cache, self.delta = self.port.refresh_after_sync(
            self.cache, self.delta, *a,
            self._vals_bucket(fresh, b), self._vals_bucket(shipped, b))

    def block(self) -> None:
        jax.block_until_ready((self.main, self.cache, self.delta))
