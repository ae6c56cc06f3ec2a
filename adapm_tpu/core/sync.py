"""The adaptive management planner: SyncManager reborn.

Reference: one SyncManager thread per channel (sync_manager.h:452-520) drains
worker intent queues, materializes replicas, extracts/ships deltas, and — on
the owner side — decides per key whether to *relocate* the main copy to the
requesting node or *replicate* it there (sync_manager.h:553-739, decision at
:624-644: relocate iff no other node and no local worker has intent).

Here the planner is a host-side loop (optionally a background thread) driving
the jitted sync/relocate/replica-create programs of the ShardedStores. The
owner/requester message exchange collapses: the single controller holds the
authoritative tables, so a "sync round" for a channel is ONE fused device
program per length class (delta psum -> owner merge -> fresh-value refresh)
instead of per-destination ZeroMQ messages. Channels partition keys by the
same Knuth multiplicative hash (reference handle.h:1016-1029) and bound the
per-round payload.
"""
from __future__ import annotations

import time
from typing import List, Optional, Tuple

import numpy as np

from ..base import CLOCK_MAX, NO_SLOT, MgmtTechniques
from .intent import ActionTimer

KNUTH = np.uint64(2654435761)


def key_channel(keys: np.ndarray, num_channels: int) -> np.ndarray:
    """Key -> channel via Knuth multiplicative hash (handle.h:1016-1029).

    The HIGH half of the 32-bit product picks the channel: KNUTH is odd,
    so the product's low bits are just a permutation of the key's low
    bits — `h % 2^m` would degenerate to `key % 2^m`, perfectly
    correlated with the home-process layout (key % (S*P)), and one
    process's keys would all share a channel (observed in dcn_bench:
    chan_rounds == 1 at P = 4)."""
    h = (keys.astype(np.uint64) * KNUTH) & np.uint64(0xFFFFFFFF)
    return ((h >> np.uint64(16)) % np.uint64(num_channels)).astype(
        np.int32)


class ReplicaTable:
    """One channel's live-replica set as a numpy structure-of-arrays.

    Replaces the `set[(key, shard)]` the planner used to walk with
    per-key Python: parallel `keys` (int64) / `shards` (int32) columns,
    a `live` mask, and a LIFO free-list of dead rows — every operation
    (add / remove / contains / snapshot) is O(batch) vectorized.

    Membership is one fancy-indexed read of a `(num_shards, num_keys)`
    int32 row-lookup table. The lookup may be SHARED across the channel
    tables of one SyncManager: a (key, shard) pair lives in exactly one
    channel (channel = hash(key)), so one table serves all channels
    without collisions — and int32 at S x K matches the `intent_end`
    footprint decision above. Lookup entries are validated against the
    stored key/shard columns on every read, so a stale or foreign row
    id degrades to "absent", never to a wrong entry.

    Not internally locked: callers mutate under the server lock (the
    same discipline the replica sets had).
    """

    GROW_MIN = 1024

    def __init__(self, num_shards: int, num_keys: int,
                 row_lookup: Optional[np.ndarray] = None):
        self.num_shards = num_shards
        self.num_keys = num_keys
        self._row = row_lookup if row_lookup is not None else \
            np.full((num_shards, num_keys), -1, dtype=np.int32)
        cap = self.GROW_MIN
        self.keys = np.zeros(cap, dtype=np.int64)
        self.shards = np.zeros(cap, dtype=np.int32)
        self.live = np.zeros(cap, dtype=bool)
        self._free = np.empty(cap, dtype=np.int32)
        self._n_free = 0
        self._top = 0       # rows [0, _top) have been handed out
        self._size = 0

    def __len__(self) -> int:
        return self._size

    @staticmethod
    def _as_pair(keys, shards) -> Tuple[np.ndarray, np.ndarray]:
        keys = np.ascontiguousarray(keys, dtype=np.int64).ravel()
        if np.ndim(shards) == 0:
            shards = np.full(len(keys), int(shards), dtype=np.int32)
        else:
            shards = np.ascontiguousarray(shards, dtype=np.int32).ravel()
        return keys, shards

    def _valid_rows(self, rows: np.ndarray, keys: np.ndarray,
                    shards: np.ndarray) -> np.ndarray:
        """True where the lookup row really is (key, shard) in THIS
        table (bounds + column match — see class docstring)."""
        out = np.zeros(len(rows), dtype=bool)
        idx = np.nonzero((rows >= 0) & (rows < self._top))[0]
        if len(idx):
            r = rows[idx]
            out[idx] = (self.live[r] & (self.keys[r] == keys[idx])
                        & (self.shards[r] == shards[idx]))
        return out

    def _grow_cols(self, need: int) -> None:
        cap = len(self.keys)
        while cap < need:
            cap *= 2
        if cap == len(self.keys):
            return
        for name in ("keys", "shards", "live"):
            old = getattr(self, name)
            new = np.zeros(cap, dtype=old.dtype)
            new[: len(old)] = old
            setattr(self, name, new)

    def add(self, keys, shards) -> int:
        """Insert (key, shard) pairs; already-present and intra-batch
        duplicate pairs are ignored. Returns the number inserted."""
        keys, shards = self._as_pair(keys, shards)
        if len(keys) == 0:
            return 0
        fresh = ~self._valid_rows(self._row[shards, keys], keys, shards)
        k, s = keys[fresh], shards[fresh]
        if len(k) == 0:
            return 0
        # intra-batch dedup (first occurrence wins)
        _, first = np.unique(k * np.int64(self.num_shards) + s,
                             return_index=True)
        k, s = k[first], s[first]
        n = len(k)
        rows = np.empty(n, dtype=np.int64)
        take = min(n, self._n_free)
        if take:
            rows[:take] = self._free[self._n_free - take: self._n_free]
            self._n_free -= take
        if n - take:
            self._grow_cols(self._top + (n - take))
            rows[take:] = np.arange(self._top, self._top + (n - take))
            self._top += n - take
        self.keys[rows] = k
        self.shards[rows] = s
        self.live[rows] = True
        self._row[s, k] = rows
        self._size += n
        return n

    def remove(self, keys, shards) -> int:
        """Remove (key, shard) pairs; absent pairs are ignored. Returns
        the number removed."""
        keys, shards = self._as_pair(keys, shards)
        if len(keys) == 0 or self._size == 0:
            return 0
        rows = self._row[shards, keys]
        rows = np.unique(rows[self._valid_rows(rows, keys, shards)])
        n = len(rows)
        if n == 0:
            return 0
        self.live[rows] = False
        self._row[self.shards[rows], self.keys[rows]] = -1
        if self._n_free + n > len(self._free):
            cap = len(self._free)
            while cap < self._n_free + n:
                cap *= 2
            new = np.empty(cap, dtype=np.int32)
            new[: self._n_free] = self._free[: self._n_free]
            self._free = new
        self._free[self._n_free: self._n_free + n] = rows
        self._n_free += n
        self._size -= n
        return n

    def contains(self, keys, shards) -> np.ndarray:
        keys, shards = self._as_pair(keys, shards)
        return self._valid_rows(self._row[shards, keys], keys, shards)

    def snapshot(self) -> Tuple[np.ndarray, np.ndarray]:
        """Copies of the live (keys, shards) columns (safe to use after
        the caller releases whatever lock guarded the mutation)."""
        rows = np.nonzero(self.live[: self._top])[0]
        return self.keys[rows], self.shards[rows]


class SyncStats:
    """Planner counters. EVERY bump goes through the locked `add()`
    helper: rounds run concurrently (per-channel threads, the prefetch
    pipeline, DCN handlers) and `int +=` is not atomic — the pre-PR 3
    code locked some sites and not others."""

    FIELDS = ("rounds", "replicas_created", "replicas_dropped",
              "relocations", "keys_synced", "keys_considered",
              "intents_processed")

    def __init__(self, counters=None):
        """`counters`: field -> registry counter moved with the field (a
        gauge of a field is a level; a counter's growth over a window
        is a rate a metric can read)."""
        import threading
        self.lock = threading.Lock()
        self._counters = counters or {}
        # keys_considered: replicas examined by sync rounds (intent-live,
        # keep-partition); keys_synced: replicas actually SHIPPED to a
        # sync program after the dirty-delta filter. With sync_threshold
        # > 0 the final ship/hold decision is on device, so held-back
        # small-delta replicas still count as synced here (an exact
        # on-device count would cost a readback per round).
        for f in self.FIELDS:
            setattr(self, f, 0)

    def add(self, **deltas) -> None:
        with self.lock:
            for name, n in deltas.items():
                setattr(self, name, getattr(self, name) + n)
                if name in self._counters:
                    self._counters[name].inc(n)


class SyncManager:
    """Plans and executes replication/relocation/sync for one Server."""

    def __init__(self, server, opts):
        self.server = server
        self.opts = opts
        # the EFFECTIVE sync-rate bound _throttle honors (ISSUE 20):
        # initialized from the static --sys.sync.max_per_sec knob and —
        # only when a FreshnessSLO controller is live — walked ABOVE it
        # so sync rounds run more often than the static throttle
        # allows, then relaxed back toward it. With no controller
        # nothing ever writes this, so throttling is byte-identical to
        # the static-knob path. <= 0 keeps meaning unthrottled.
        self.effective_max_per_sec = float(opts.sync_max_per_sec)
        self.num_channels = opts.channels
        S = server.num_shards
        K = server.num_keys
        # per-shard registered intent horizon: max end clock of any active
        # intent by a worker on that shard (reference: Parameter.local_intents
        # per customer, handle.h:122-152, aggregated to the node level).
        # int32: clocks are bounded by CLOCK_MAX = 2^31-1 (base.py), and at
        # Wikidata5M scale this table is S x 5M — int64 would double its
        # footprint for no range benefit
        self.intent_end = np.full((S, K), -1, dtype=np.int32)
        # live replicas, partitioned by channel: one array-native
        # ReplicaTable per channel, sharing a single (S, K) row-lookup
        # (a key belongs to exactly one channel, so rows never collide;
        # same S x K int32 footprint call as intent_end above). Mutated
        # under the server lock via replica_add/replica_discard.
        self._replica_row = np.full((S, K), -1, dtype=np.int32)
        self.replicas: List[ReplicaTable] = [
            ReplicaTable(S, K, row_lookup=self._replica_row)
            for _ in range(self.num_channels)]
        self.timer = ActionTimer(
            server.max_workers, alpha=opts.timing_alpha,
            quantile=opts.timing_quantile,
            rounds_lookahead=opts.timing_rounds_lookahead,
            enabled=opts.time_intent_actions)
        reg = server.obs
        self.stats = SyncStats({
            "relocations": reg.counter("sync.relocations_total",
                                       unit="keys"),
            "replicas_created": reg.counter("sync.replicas_created_total",
                                            unit="replicas"),
            "replicas_dropped": reg.counter("sync.replicas_dropped_total",
                                            unit="replicas"),
            "keys_synced": reg.counter("sync.keys_shipped_total",
                                       unit="keys")})
        # obs wiring (docs/OBSERVABILITY.md): round latency, replica
        # staleness in clocks, and SyncStats mirrored as callable gauges
        # so metrics_snapshot()'s sync section is complete without
        # touching the counters the rest of this file maintains
        self._h_round = reg.histogram("sync.round_s")
        # the round less the waits for the device beneath it (the
        # stores' program calls): the planner's own host time
        self._h_round_work = reg.histogram("sync.round_work_s")
        # staleness = worker clocks elapsed since the channel's previous
        # sync round, observed once per round that refreshed replicas
        # (i.e. how stale those replicas had been allowed to grow)
        self._h_staleness = reg.histogram(
            "sync.replica_staleness_clocks", unit="clocks",
            bounds=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024))
        if reg.enabled:
            for name in SyncStats.FIELDS:
                reg.gauge(f"sync.{name}",
                          fn=lambda n=name: getattr(self.stats, n))
            # keys_shipped: the post-dirty-filter name for keys_synced
            # (docs/OBSERVABILITY.md); both gauges read the same counter
            reg.gauge("sync.keys_shipped",
                      fn=lambda: self.stats.keys_synced)
            # compression plane (ISSUE 8; schema v7): wire bytes the
            # most recent round shipped (--sys.sync.compress format),
            # cumulative shipped vs full-width-f32-equivalent bytes,
            # and the max-abs EF residual parked by the last
            # compressed round (0 until a compressed round runs; the
            # device scalar converts lazily here, at snapshot time)
            reg.gauge("sync.bytes_per_round",
                      fn=lambda: self._last_round_bytes)
            reg.gauge("sync.bytes_shipped",
                      fn=lambda: sum(st.sync_bytes_shipped
                                     for st in server.stores))
            reg.gauge("sync.bytes_full_equiv",
                      fn=lambda: sum(st.sync_bytes_full
                                     for st in server.stores))
            reg.gauge("sync.ef_residual_norm",
                      fn=lambda: max((st.ef_residual_norm()
                                      for st in server.stores),
                                     default=0.0))
            # table occupancy + dirty fraction, per channel and total —
            # host arrays only, no device readback. Best-effort reads
            # (evaluated without the server lock at snapshot time).
            reg.gauge("sync.replicas_live",
                      fn=lambda: sum(len(t) for t in self.replicas))
            reg.gauge("sync.dirty_fraction",
                      fn=lambda: self._dirty_fraction(None))
            # per length class, named by the class's row length: the
            # cache slots its allocator has handed out (no table scan)
            for cid, n in enumerate(server.class_lengths):
                reg.gauge(f"sync.replicas_live.len{n}",
                          fn=lambda cid=cid: server.ab.replicas_held(cid))
            for c in range(self.num_channels):
                reg.gauge(f"sync.replicas_live.c{c}",
                          fn=lambda c=c: len(self.replicas[c]))
                reg.gauge(f"sync.dirty_fraction.c{c}",
                          fn=lambda c=c: self._dirty_fraction(c))
        # per-channel min-active-clock at the channel's last sync round
        # (-1 = never synced yet); feeds _h_staleness
        self._chan_last_clock = np.full(self.num_channels, -1,
                                        dtype=np.int64)
        self._next_channel = 0
        self._last_round_t = 0.0
        # wire bytes shipped by the most recent sync_channel round
        # (sync.bytes_per_round gauge; ISSUE 8)
        self._last_round_bytes = 0
        # per-channel (monotonic, dirty, live) memo for the dirty_fraction
        # gauges — see _dirty_counts
        self._df_cache: dict = {}
        # collective cadence state (--sys.collective_cadence K): local
        # joins of the BSP exchange must be serialized (two local threads
        # entering the all-to-all concurrently would corrupt the global
        # exchange sequence); _cad_joined counts the clock boundaries
        # already serviced since the last global sync point
        import threading
        self._coll_lock = threading.Lock()
        self._cad_joined = 0
        self._chan_exec = None  # lazy: concurrent all-channel rounds (mp)

    # ------------------------------------------------------------------
    # intent registration + replicate-vs-relocate decision
    # ------------------------------------------------------------------

    def drain_intents(self, force: bool = False) -> None:
        """Drain worker intent queues for intents starting within the
        ActionTimer window (reference registerNewIntents,
        sync_manager.h:257-286); force=True drains everything (WaitSync)."""
        with self.server._span("sync.drain_intents"):
            self._drain_intents_impl(force)

    def _drain_intents_impl(self, force: bool) -> None:
        clocks = self.server.worker_clocks()
        self.timer.observe(clocks)
        window = self.timer.window()
        for w in self.server.workers():
            max_start = CLOCK_MAX if force else int(
                clocks[w.worker_id] + window[w.worker_id])
            for keys, start, end in w._intent_queue.pop_relevant(max_start):
                # actions are applied per intent entry: a later intent in the
                # same drain must observe placement changes made by earlier
                # ones, or locality decisions go stale
                relocate_keys, replicate_keys, remote_keys = self._register(
                    w.shard, keys, end)
                self.stats.add(intents_processed=len(keys))
                if len(remote_keys):
                    # keys owned by another process: the OWNER decides
                    # relocate-vs-replicate (reference owner branch,
                    # sync_manager.h:553-739) — ask it over the channel
                    self.server.glob.intent_remote(remote_keys, w.shard, end)
                if len(relocate_keys):
                    self.stats.add(relocations=self.server._relocate_to(
                        relocate_keys, w.shard))
                if len(replicate_keys):
                    created = self.server._create_replicas(
                        replicate_keys, w.shard)
                    with self.server._lock:
                        self.replica_add(created, w.shard)
                    self.stats.add(replicas_created=len(created))
                if self.server.tier is not None:
                    # tiered storage (adapm_tpu/tier): pin the intent
                    # batch's owner rows hot for the window and queue
                    # their promotion — the same just-in-time hook the
                    # prefetch pipeline rides, and AFTER the relocate/
                    # replicate actions above so the pins land on the
                    # keys' final placement
                    self.server.tier.note_intent(keys, end)

    # ------------------------------------------------------------------
    # replica registry (the channel tables; callers hold the server lock)
    # ------------------------------------------------------------------

    def _replica_op(self, keys: np.ndarray, shards, op: str) -> None:
        """One vectorized channel grouping (no per-key Python) applying
        ReplicaTable.`op` per channel; `shards` is a scalar or a per-key
        array. Caller holds the server lock."""
        if len(keys) == 0:
            return
        keys = np.ascontiguousarray(keys, dtype=np.int64).ravel()
        chans = key_channel(keys, self.num_channels)
        sarr = None if np.ndim(shards) == 0 else \
            np.asarray(shards, dtype=np.int32).ravel()
        for c in np.unique(chans):
            m = chans == c
            getattr(self.replicas[c], op)(
                keys[m], shards if sarr is None else sarr[m])

    def replica_add(self, keys: np.ndarray, shards) -> None:
        """Register live replicas into their channels' tables. Caller
        holds the server lock."""
        self._replica_op(keys, shards, "add")

    def replica_discard(self, keys: np.ndarray, shards) -> None:
        """Unregister replicas (absent pairs are ignored, matching the
        sets' discard semantics). Caller holds the server lock."""
        self._replica_op(keys, shards, "remove")

    def replica_clear(self) -> None:
        """Drop every registration (checkpoint restore rebuilds from the
        addressbook). Caller holds the server lock."""
        S, K = self.intent_end.shape
        self._replica_row.fill(-1)
        self.replicas = [ReplicaTable(S, K, row_lookup=self._replica_row)
                         for _ in range(self.num_channels)]

    def _dirty_counts(self, channel: int) -> Tuple[int, int]:
        """(dirty, live) for one channel, memoized briefly: one
        metrics_snapshot() evaluates the total gauge AND every
        per-channel gauge, and without the memo each full-table pass
        would run twice per snapshot (matters at ~1e5 live replicas)."""
        now = time.monotonic()
        ent = self._df_cache.get(channel)
        if ent is not None and now - ent[0] < 0.25:
            return ent[1], ent[2]
        t = self.replicas[channel]
        dirty = total = 0
        if len(t):
            keys, shards = t.snapshot()
            total = len(keys)
            if total:
                dirty = int(self.server._dirty_replica_mask(
                    keys, shards).sum())
        self._df_cache[channel] = (now, dirty, total)
        return dirty, total

    def _dirty_fraction(self, channel: Optional[int]) -> float:
        """Fraction of live replicas with unshipped writes (channel, or
        all channels for None). Best-effort lock-free gauge read."""
        chans = range(self.num_channels) if channel is None else (channel,)
        counts = [self._dirty_counts(c) for c in chans]
        total = sum(t for _, t in counts)
        return sum(d for d, _ in counts) / total if total else 0.0

    def _register(self, shard: int, keys: np.ndarray,
                  end: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Register an intent batch; returns (keys to relocate to `shard`,
        keys to replicate onto `shard`, remotely-owned keys to hand to the
        cross-process layer). Fully vectorized — no per-key Python (the
        reference is O(1)/key in C++, addressbook.h:110-151). Capacity
        degradation (full pools) is handled downstream: _relocate demotes
        to replication, _create_replicas truncates — slower for the surplus
        keys, never wrong."""
        ie = self.intent_end
        # validate up front so the native and numpy paths leave identical
        # intent_end state when the batch contains a bad key (the C helper
        # applies in-range updates before reporting the bad count)
        from ..base import check_key_range
        check_key_range(keys, self.server.num_keys, "intent key")
        if self.server._native is not None:
            self.server._native.adapm_intent_max(
                np.ascontiguousarray(keys, np.int64), len(keys),
                self.server.num_keys, int(end), ie[shard])
        else:
            np.maximum.at(ie[shard], keys, np.int32(min(end, 2**31 - 1)))
        if self.server.tracer is not None:
            from ..utils.stats import INTENT_START
            self.server.tracer.record(keys, INTENT_START, shard)
        # keys that are not yet available on `shard`
        cand = keys[~self.server.ab.is_local(keys, shard)]
        e = np.empty(0, dtype=np.int64)
        if len(cand) == 0:
            return e, e, e
        remote = e
        if self.server.glob is not None:
            rm = self.server.ab.owner[cand] < 0
            remote, cand = cand[rm], cand[~rm]
            if len(cand) == 0:
                return e, e, remote
        relocate = self._decide_batch(cand, shard)
        dc = self.server.decisions
        if dc is not None:
            # ISSUE 17: the relocate-vs-replicate split with its
            # feature vector; replications open an outcome window
            # probing whether the replicas were ever worth creating
            rep = cand[~relocate]
            dc.record_classify(int(shard), int(relocate.sum()),
                               len(rep), len(remote), rep)
        return cand[relocate], cand[~relocate], remote

    def _decide_batch(self, keys: np.ndarray, shard: int) -> np.ndarray:
        """Relocate vs replicate (reference sync_manager.h:624-644): relocate
        iff no *other* shard currently has interest in any of the keys (an
        active intent or a replica) — otherwise replicate. Returns a bool
        mask (True = relocate)."""
        t = self.opts.techniques
        if t == MgmtTechniques.REPLICATION_ONLY:
            return np.zeros(len(keys), dtype=bool)
        if t == MgmtTechniques.RELOCATION_ONLY:
            return np.ones(len(keys), dtype=bool)
        ab = self.server.ab
        clocks = self.server.shard_min_clocks()
        other_interest = np.zeros(len(keys), dtype=bool)
        for s in range(self.server.num_shards):
            if s == shard:
                continue
            # any other shard's active intent or replica blocks relocation;
            # the reference distinguishes owner-local and remote node intent
            # but blocks relocation on either (:624-644)
            other_interest |= (ab.cache_slot[s, keys] != NO_SLOT) | \
                (self.intent_end[s, keys] >= clocks[s])
        return ~other_interest

    # ------------------------------------------------------------------
    # sync rounds
    # ------------------------------------------------------------------

    def sync_channel(self, channel: int) -> None:
        """Refresh replicas with active intent; flush+drop expired ones
        (reference readAndPotentiallyDropReplica, handle.h:601-662).
        Replicas of remotely-owned keys sync/drop over the DCN channel.

        Lock discipline (PR 3 tentpole): the server lock brackets only
        the table snapshot here and the coordinate-revalidation +
        program-enqueue inside `_sync_replicas`/`_drop_replicas` — the
        keep/drop/cross partition, the dirty-delta filter, and the
        device execution itself all run outside it, so worker dispatch
        and the next channel's classification overlap this channel's
        device work instead of queueing behind the round."""
        srv = self.server
        table = self.replicas[channel]
        # staleness-in-clocks: replicas refreshed this round had gone
        # unrefreshed since the channel's previous round — observe the
        # min-active-clock delta across that gap
        mc = self._min_active_clock()
        if mc is not None:
            last = int(self._chan_last_clock[channel])
            self._chan_last_clock[channel] = mc
            # mc can REGRESS below last when a new worker registers at
            # clock 0 mid-run; that re-bases the channel (line above)
            # and must not feed a negative staleness into the histogram
            if 0 <= last <= mc and len(table):
                self._h_staleness.observe(float(mc - last))
        with srv._lock:  # snapshot only (DCN handlers mutate tables too)
            if len(table) == 0:
                return
            keys, shards = table.snapshot()
            cross = (srv.ab.owner[keys] < 0).astype(np.uint8) \
                if srv.glob is not None else None
        min_clocks = srv.shard_min_clocks()
        keep_l, keep_x, drop_l, drop_x = self._scan_partition(
            keys, shards, cross, min_clocks)
        self.stats.add(keys_considered=len(keep_l) + len(keep_x))
        if len(keep_l):
            kk, ks = keys[keep_l], shards[keep_l]
            n_considered, n_dirty = len(kk), -1
            if self.opts.sync_dirty_only:
                # dirty-delta filter: gather-and-ship only replicas with
                # an unshipped write or a stale base (store.py write
                # epochs). Exact, not heuristic — a clean replica's sync
                # program is a bit-for-bit no-op (delta == 0 and cache
                # == main), so skipping it cannot change any read.
                dirty = srv._dirty_replica_mask(kk, ks)
                n_dirty = int(dirty.sum())
                if dirty.any() and not dirty.all():
                    # sibling propagation: a dirty replica's merge
                    # advances the shared main row DURING this round, so
                    # its key's other replicas must ride the same fused
                    # program to pick up the post-merge value (a full
                    # round refreshes them in one program; judging them
                    # against the PRE-merge main would leave them one
                    # round stale). All replicas of a key hash to this
                    # channel, so the batch is self-contained.
                    dirty |= np.isin(kk, kk[dirty])
                kk, ks = kk[dirty], ks[dirty]
            else:
                pol = srv.policy
                if pol is not None and pol.active("sync"):
                    # ISSUE 18 learned sync law: with the static dirty
                    # filter OFF the heuristic ships every kept
                    # replica; a predicted wasted-wire verdict applies
                    # the EXACT per-batch dirty mask instead — the
                    # same value-preservation guard the filter-on
                    # branch above is built on (a clean replica's sync
                    # program is a bit-for-bit no-op, so holding it
                    # cannot change any read; sibling ride-alongs keep
                    # the post-merge refresh rule). A wrong prediction
                    # costs one mask pass — it never ships less than
                    # the dirty set.
                    if pol.consult("sync", {"n_dirty": -1},
                                   n_considered):
                        pol.applied("sync")
                        dirty = srv._dirty_replica_mask(kk, ks)
                        n_dirty = int(dirty.sum())
                        if dirty.any() and not dirty.all():
                            dirty |= np.isin(kk, kk[dirty])
                        kk, ks = kk[dirty], ks[dirty]
            dc = srv.decisions
            if dc is not None:
                # ISSUE 17: the ship/hold verdict for this channel's
                # batch — clean sibling ride-alongs (or a fully-clean
                # ship with the dirty filter off) fold into
                # decision.shipped_clean
                dc.record_sync(channel, n_considered, n_dirty, len(kk))
            if len(kk):
                # periodic rounds ship in the --sys.sync.compress wire
                # format (the EF residual parks in the delta row);
                # drop/quiesce flushes stay EXACT — kv.py _sync_replicas
                srv._sync_replicas(kk, ks,
                                   threshold=self.opts.sync_threshold,
                                   compress=True)
                self.stats.add(keys_synced=len(kk))
        if len(keep_x) and not self.opts.collective_sync:
            # collective mode: cross-process deltas accumulate and ship in
            # the BSP exchange at the next WaitSync/quiesce point. Cross
            # replicas are exempt from the dirty filter: their owner's
            # writes are invisible to local epochs, and the DCN round is
            # also how they OBSERVE remote pushes.
            srv.glob.sync_replicas(keys[keep_x], shards[keep_x])
            self.stats.add(keys_synced=len(keep_x))
        if (len(drop_l) or len(drop_x)) and srv.tracer is not None:
            from ..utils.stats import INTENT_STOP
            dk = np.concatenate([keys[drop_l], keys[drop_x]])
            ds = np.concatenate([shards[drop_l], shards[drop_x]])
            for s in np.unique(ds):
                srv.tracer.record(dk[ds == s], INTENT_STOP, int(s))
        if len(drop_l):
            dk, ds = keys[drop_l], shards[drop_l]
            srv._drop_replicas(dk, ds)
            with srv._lock:
                self.replica_discard(dk, ds)
            self.stats.add(replicas_dropped=len(dk))
        if len(drop_x):
            # discards from the channel tables itself
            srv.glob.drop_replicas(keys[drop_x], shards[drop_x])
            self.stats.add(replicas_dropped=len(drop_x))

    def _scan_partition(self, keys: np.ndarray, shards: np.ndarray,
                        cross: Optional[np.ndarray],
                        min_clocks: np.ndarray):
        """Partition one channel snapshot into (keep_local, keep_cross,
        drop_local, drop_cross) index arrays: keep iff the holder
        shard's intent horizon is still active. One native pass
        (adapm_replica_scan2) or its vectorized numpy equivalent —
        never per-key Python."""
        srv = self.server
        if srv._native is not None:
            from ..native import replica_scan_partition
            return replica_scan_partition(
                srv._native, keys, shards, self.intent_end,
                np.ascontiguousarray(min_clocks, np.int64),
                srv.num_keys, cross)
        keep = self.intent_end[shards, keys] >= min_clocks[shards]
        x = np.zeros(len(keys), dtype=bool) if cross is None \
            else cross.astype(bool)
        return (np.nonzero(keep & ~x)[0], np.nonzero(keep & x)[0],
                np.nonzero(~keep & ~x)[0], np.nonzero(~keep & x)[0])

    def run_round(self, force_intents: bool = False,
                  all_channels: bool = False) -> None:
        # self-serializing (the round lock is reentrant): rounds may now
        # be driven concurrently by the training thread, the background
        # sync thread, AND the prefetch pipeline — drain_intents pops
        # worker heaps and sync_channel walks replica sets, neither of
        # which tolerates interleaved rounds
        with self.server._round_lock:
            self._throttle()
            if self.server._in_setup and not force_intents:
                # BeginSetup/EndSetup bracket (reference
                # coloc_kv_worker.h): management is paused so bulk
                # Set/Push of initial values runs at full speed;
                # EndSetup's barrier resumes it. An explicit WaitSync
                # (force) still acts.
                return
            # round latency measured AFTER the throttle (sleep is policy,
            # not work): the "sync.round" span observes sync.round_s
            # wire bytes this ROUND ships (keep syncs in the
            # --sys.sync.compress format + drop flushes, which go
            # exact) — sync.bytes_per_round. Measured here, under the
            # round lock, across ALL of the round's channels: a
            # per-channel diff of the shared cumulative counter would
            # report only the last channel and cross-contaminate when
            # multi-process rounds issue channels concurrently.
            bytes_before = sum(st.sync_bytes_shipped
                               for st in self.server.stores)
            with self.server._span("sync.round", self._h_round,
                                   work=self._h_round_work):
                self.drain_intents(force=force_intents)
                if all_channels:
                    self._sync_all_channels()
                else:
                    self.sync_channel(self._next_channel)
                    self._next_channel = \
                        (self._next_channel + 1) % self.num_channels
                if force_intents and all_channels:
                    # the WaitSync shape: in collective mode this is the
                    # agreed point where every process joins the BSP delta
                    # exchange
                    self._collective_point()
                else:
                    self._maybe_cadence()
                self.stats.add(rounds=1)
            self._last_round_bytes = \
                sum(st.sync_bytes_shipped
                    for st in self.server.stores) - bytes_before
            wt = self.server.wtrace
            if wt is not None:
                # the round as it LANDED (ISSUE 15): replay re-drives
                # these events instead of running a timer-driven
                # background loop — rounds happen where the workload
                # put them, not where a wall clock did
                wt.record_sync(forced=force_intents,
                               all_channels=all_channels,
                               bytes_shipped=self._last_round_bytes)

    def _sync_all_channels(self) -> None:
        """All channels' rounds. Multi-process, >1 channel: issued
        CONCURRENTLY — channels partition keys (per-channel delta locks,
        pm.delta_window), local device work serializes briefly under the
        server lock, and the expensive part (per-channel DCN round-trips
        to owners) overlaps instead of stacking RTTs (VERDICT r4 item 9;
        reference: C parallel SyncManager threads,
        coloc_kv_server.h:100-105). Single-process: serial — there is no
        network latency to hide, only thread overhead to pay."""
        srv = self.server
        if srv.glob is None or self.num_channels == 1:
            for c in range(self.num_channels):
                self.sync_channel(c)
            return
        if self._chan_exec is None:
            from concurrent.futures import ThreadPoolExecutor
            self._chan_exec = ThreadPoolExecutor(
                max_workers=self.num_channels,
                thread_name_prefix="adapm-chan")
        futs = [self._chan_exec.submit(self.sync_channel, c)
                for c in range(self.num_channels)]
        errs = []
        for f in futs:
            try:
                f.result()
            except Exception as e:
                errs.append(e)
        if errs:
            # surface every channel's failure: log the others before
            # raising the first, so concurrent-round diagnostics are not
            # reduced to whichever channel happened to be joined first
            from ..utils.log import alog
            for e in errs[1:]:
                alog(f"[sync] concurrent channel round also failed: "
                     f"{type(e).__name__}: {e}")
            raise errs[0]

    def close(self) -> None:
        if self._chan_exec is not None:
            self._chan_exec.shutdown(wait=True)
            self._chan_exec = None

    def _collective_active(self) -> bool:
        srv = self.server
        return srv.glob is not None and self.opts.collective_sync

    def _collective_exchange(self, quiescing: bool) -> bool:
        """One BSP exchange of every cross-process replica delta (caller
        holds _coll_lock). Returns True iff all processes entered it
        quiescing."""
        srv = self.server
        with srv._lock:
            parts = [t.snapshot() for t in self.replicas]
            karr = np.concatenate([k for k, _ in parts])
            sarr = np.concatenate([s for _, s in parts])
            m = srv.ab.owner[karr] < 0
            karr, sarr = karr[m], sarr[m]
        all_q = srv.glob.collective_sync(karr, sarr, quiescing=quiescing)
        self.stats.add(keys_synced=len(karr), keys_considered=len(karr))
        return all_q

    def _min_active_clock(self):
        """Min clock over this process's registered, unfinished workers;
        None when no worker is active (cadence then never triggers)."""
        from ..base import WORKER_FINISHED
        srv = self.server
        clocks = [int(srv._clocks[wid]) for wid in list(srv._workers)
                  if srv._clocks[wid] != WORKER_FINISHED]
        return min(clocks) if clocks else None

    def _maybe_cadence(self) -> None:
        """--sys.collective_cadence K: join one BSP exchange per K-clock
        boundary this process's workers have crossed. Every process runs
        the same check in its run_round, so exchanges pair up globally in
        boundary order; a process that crosses fewer boundaries before
        its next WaitSync/quiesce is absorbed there by the flag loop
        (_collective_point). Bounded staleness: a replica observes any
        remote push within K clocks of the slowest process (plus one
        run_round), vs unbounded between wait points with cadence off."""
        K = self.opts.collective_cadence
        if K <= 0 or not self._collective_active():
            return
        while True:
            mc = self._min_active_clock()
            if mc is None or mc < (self._cad_joined + 1) * K:
                return
            with self._coll_lock:
                # re-check: another local thread may have serviced it (or
                # the last worker may have finalized mid-check)
                mc = self._min_active_clock()
                if mc is None or mc < (self._cad_joined + 1) * K:
                    continue
                self._cad_joined += 1
                self._collective_exchange(quiescing=False)

    def _collective_point(self) -> None:
        """Ship all cross-process replica deltas through the collective
        exchange (parallel/collective.py). Must be reached by every
        process together; runs (with possibly zero items) whenever
        collective mode is on. With a cadence configured this is a FLAG
        LOOP: the process keeps joining exchanges (quiescing=True) until
        every peer is also at its wait point — absorbing peers that cross
        more cadence boundaries than we did (skewed batch counts)."""
        if not self._collective_active():
            return
        with self._coll_lock:
            while True:
                all_q = self._collective_exchange(quiescing=True)
                if all_q or self.opts.collective_cadence <= 0:
                    break
            # quiesce is a global sync point: re-base the cadence so all
            # processes agree that past boundaries need no exchange
            K = self.opts.collective_cadence
            if K > 0:
                mc = self._min_active_clock()
                self._cad_joined = 0 if mc is None else mc // K

    def _throttle(self) -> None:
        """Bound sync frequency (reference sync_manager.h:384-411, 805-814:
        --sys.sync.max_per_sec / --sys.sync.pause)."""
        if self.opts.sync_pause_ms > 0:
            time.sleep(self.opts.sync_pause_ms / 1e3)
            return
        if self.effective_max_per_sec <= 0:
            return
        min_gap = 1.0 / self.effective_max_per_sec
        now = time.monotonic()
        wait = self._last_round_t + min_gap - now
        if wait > 0:
            time.sleep(wait)
        self._last_round_t = time.monotonic()

    # ------------------------------------------------------------------

    def quiesce(self) -> None:
        """Force-process all intents and flush every pending delta; after
        this — and in multi-process, after every process quiesces and a
        barrier (WaitSync -> Barrier -> WaitSync) — all reads observe
        identical values (reference test_many_key_operations.cc:375-385)."""
        srv = self.server
        # same self-serialization as run_round (reentrant under the
        # Server.quiesce wrapper)
        with srv._round_lock:
            self._quiesce_locked()

    def _quiesce_locked(self) -> None:
        srv = self.server
        self.drain_intents(force=True)
        for c in range(self.num_channels):
            with srv._lock:
                if len(self.replicas[c]) == 0:
                    continue
                keys, shards = self.replicas[c].snapshot()
                cross = (srv.ab.owner[keys] < 0) \
                    if srv.glob is not None else None
            if cross is not None:
                lk, ls = keys[~cross], shards[~cross]
                rk, rs = keys[cross], shards[cross]
            else:
                lk, ls = keys, shards
                rk = rs = np.empty(0, dtype=np.int64)
            if len(lk):
                # unconditional flush: quiesce bypasses the dirty filter
                # (and sync_threshold) so no pending delta is ever lost
                srv._sync_replicas(lk, ls)
                self.stats.add(keys_synced=len(lk),
                               keys_considered=len(lk))
            if len(rk) and not self.opts.collective_sync:
                srv.glob.sync_replicas(rk, rs)
                self.stats.add(keys_synced=len(rk),
                               keys_considered=len(rk))
        # collective mode: one BSP exchange covers every cross replica
        # (joined by all processes, items or not)
        self._collective_point()
        srv.block()

    def report(self) -> str:
        s = self.stats
        out = (f"sync: rounds={s.rounds} intents={s.intents_processed} "
               f"replicas+={s.replicas_created} -={s.replicas_dropped} "
               f"relocations={s.relocations} "
               f"keys_shipped={s.keys_synced}/"
               f"considered={s.keys_considered}")
        if self.server.glob is not None:
            out += " | " + self.server.glob.report()
        return out
