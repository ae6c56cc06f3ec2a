"""Batched promotion/demotion between the device-hot pool and the host
cold store.

Promotion is one fused scatter program per (class, shard) batch: the
cold rows' authoritative host values upload into freshly-allocated hot
rows (`_write_main_rows`, donated — the StagingPool-style bounded-
device-buffer discipline: the hot pool IS the bound). Demotion is the
reverse: a device gather readback lands the rows in the cold store and
frees the device rows. Both are BIT-EXACT moves — a float32 row is the
same bits on either side — so residency changes can never change what a
Pull/Push/serve lookup returns (the tentpole's bit-identity contract,
pinned by tests/test_tier.py's storm).

What a move costs (PR 46; PR 45 was its refused first attempt). A
CLEAN victim is demoted with no readback:
a promotion leaves the row's cold copy where it was and records the
slot's write epoch (`ShardedStore.main_epoch`, bumped by every program
that can change a main row's value) beside the hot row; a victim whose
epoch has not moved since still equals its cold copy bit for bit, so
its device row is simply dropped. Only a row WRITTEN while hot is read
back (float32 rows in a float32 cold store only: a quantized cold
store parks a remainder at every landing and a narrower pool rounds at
promotion, so their victims are read back as before). A served table
is read-only but for acknowledged pushes, so its demotions wait for no
program. Victim selection examines a WINDOW of the hot pool from the
shard's clock hand (`_VICTIM_FANOUT` rows a victim asked for, at least
`_VICTIM_WINDOW_MIN`), not the pool: a maintenance pass costs what it
moves, whatever the pool holds (20 M rows in the serving tier cell),
and nothing pool-wide runs under the server lock.
`tier.victim_rows_examined`, `tier.clean_demotions` and the
`adapm.tier.*` spans say what a pass did. The READ side's host cost, the
staged operand of a bag read, is `coldpath.StageRing`'s: kept buffers a
bucket shape, each held until the program that reads it has finished.

Discipline: mutations run under the server lock and bump the store's
residency epoch (see residency.py). The maintenance worker computes its
victim plans OUTSIDE the lock against an epoch snapshot and revalidates
under the lock before acting — stale plans are recomputed, never
dispatched (the topology_version discipline applied to residency).
"""
from __future__ import annotations

import contextlib
from functools import partial

import numpy as np

from ..core.store import OOB, pad_bucket

# the promotion upload programs (_write_main_rows and its wire twins)
# live on the DevicePort since ISSUE 14 (device/jaxport.py) — this
# module stays device-API-free (adapm-lint APM008)


def promote_rows(store, shard: int, slots: np.ndarray) -> int:
    """Promote cold `slots` of `shard` into the hot pool (caller holds
    the server lock). Capacity-bounded: only as many rows as the free
    list covers promote; the surplus stays cold — slower, never wrong.
    Returns the number promoted."""
    res = store.res
    slots = np.unique(np.asarray(slots, dtype=np.int64))
    slots = slots[res.dev_row[shard, slots] < 0]
    if len(slots) == 0:
        return 0
    rows = res.alloc.alloc_batch(shard, len(slots))
    take = slots[: len(rows)]
    if len(take) == 0:
        return 0
    a = pad_bucket(len(take),
                   (np.full(len(take), shard, np.int32), 0),
                   (rows.astype(np.int32), OOB),
                   minimum=store.bucket_min)
    b = a[0].shape[0]
    mode = store.coldq.mode
    if mode == "fp32":
        v = store._vals_bucket(store.coldq.read(
            np.full(len(take), shard), take), b)
        store.main = store.port.write_main_rows(store.main, a[0],
                                                a[1], v)
    else:
        # dequant-fused upload (the port's wire ingest): ship the WIRE
        # rows — half/quarter the host->device bytes — and invert the
        # format inside the donated scatter. Rows with a parked EF
        # residual (few) get their full-precision value re-set exactly
        # right after: the residual folds into the promote, so the hot
        # row carries the true long-run sum (docs/MEMORY.md contract).
        q, s, fix_pos, fix_vals = store.coldq.promote_wire(shard, take)
        qb = np.zeros((b, store.value_length), dtype=q.dtype)
        qb[: len(take)] = q
        sb = None
        if mode != "fp16":
            sb = np.zeros(b, dtype=np.float32)
            sb[: len(take)] = s
        store.main = store.port.write_main_rows_wire(
            mode, store.main, a[0], a[1], qb, sb)
        if len(fix_pos):
            f = pad_bucket(len(fix_pos),
                           (np.full(len(fix_pos), shard, np.int32), 0),
                           (rows[fix_pos].astype(np.int32), OOB),
                           minimum=store.bucket_min)
            fv = store._vals_bucket(fix_vals, f[0].shape[0])
            store.main = store.port.write_main_rows(store.main, f[0],
                                                    f[1], fv)
    res.dev_row[shard, take] = rows
    res.row_slot[shard, rows] = take
    # the cold copy stays where it is: while the slot's write epoch
    # stands, the hot row equals it and demotes without a readback
    res.promo_epoch[shard, rows] = store.main_epoch[shard, take]
    res.epoch += 1
    return len(take)


def demote_rows(store, shard: int, slots: np.ndarray,
                wait=contextlib.nullcontext) -> int:
    """Demote hot `slots` of `shard` back to the cold store (caller
    holds the server lock). A victim whose write epoch has not moved
    since its promotion still equals its cold copy: its device row is
    dropped and nothing is read. A victim written while hot is read
    back (inside the `wait` bracket): the readback synchronizes with
    every enqueued program on the pool (dispatch order), so the landed
    bits are the row's current authoritative value. Returns rows
    demoted."""
    res = store.res
    slots = np.unique(np.asarray(slots, dtype=np.int64))
    rows = res.dev_row[shard, slots]
    m = rows >= 0
    slots, rows = slots[m], rows[m]
    if len(slots) == 0:
        return 0
    dirty = np.ones(len(slots), dtype=bool)
    if store.coldq.mode == "fp32" and \
            np.dtype(store.dtype) == np.float32:
        # the hot row and its cold copy are the same float32 bits (a
        # narrower pool rounds at promotion, a quantized cold store at
        # every landing: both are read back)
        dirty = store.main_epoch[shard, slots] != \
            res.promo_epoch[shard, rows]
    if dirty.any():
        d_slots, d_rows = slots[dirty], rows[dirty]
        with wait():
            vals = store.read_hot_rows_at(
                np.full(len(d_rows), shard, dtype=np.int32),
                d_rows.astype(np.int32))
        # land the readback in the cold tier's at-rest format; quantized
        # modes park the sub-grid remainder as the demote's EF residual
        # (folded back in at the next promote — docs/MEMORY.md contract)
        store.coldq.set_at(np.full(len(d_slots), shard), d_slots, vals)
    res.clean_demotions += int(len(slots) - dirty.sum())
    res.dev_row[shard, slots] = -1
    res.row_slot[shard, rows] = -1
    res.alloc.free_batch(shard, rows)
    res.epoch += 1
    return len(slots)


def release_rows(store, shards: np.ndarray, slots: np.ndarray) -> None:
    """Free the residency of slots leaving the store entirely (slot
    free on relocation/abandonment): the hot rows are returned WITHOUT a
    copy-back — the caller has already read the authoritative value out.
    Caller holds the server lock."""
    res = store.res
    if res is None or len(slots) == 0:
        return
    shards = np.asarray(shards, dtype=np.int64).ravel()
    slots = np.asarray(slots, dtype=np.int64).ravel()
    changed = False
    for s in np.unique(shards):
        sl = slots[shards == s]
        rows = res.dev_row[s, sl]
        hot = rows >= 0
        if hot.any():
            res.row_slot[s, rows[hot]] = -1
            res.alloc.free_batch(int(s), rows[hot])
            res.dev_row[s, sl[hot]] = -1
            changed = True
        res.score[s, sl] = 0
        res.pin_until[s, sl] = -1
        # the slot's value has left the store: its parked EF residual
        # must not leak onto whatever key reuses the slot
        store.coldq.drop_resid(np.full(len(sl), int(s)), sl)
    if changed:
        res.epoch += 1


def _count_demotions(server, n: int) -> None:
    """Fold victim demotions into tier.demotions (the promotions/
    demotions pair must balance occupancy, so EVERY demote_rows path
    counts — eviction victims included, not just the pressure worker
    and the tooling surface)."""
    if n and getattr(server, "tier", None) is not None:
        server.tier.c_demotions.inc(n)


# Victim selection examines this many hot rows for each victim asked
# for, from the shard's clock hand on, and never fewer than the floor (a
# pool no larger than the floor is examined whole, as every pool was
# before PR 46). The window grows only while it has not found `need`
# evictable rows (a pool full of pinned rows is still examined whole).
_VICTIM_FANOUT = 8
_VICTIM_WINDOW_MIN = 1024


def _pick_victims(store, shard: int, need: int, min_clock: int,
                  protect: np.ndarray,
                  force: bool = False) -> np.ndarray:
    """Low-score, unpinned hot slots of `shard` (up to `need`), never
    from `protect` (the batch being made hot right now): the lowest
    scores of a WINDOW of the hot pool that starts at the shard's clock
    hand and holds `_VICTIM_FANOUT` rows a victim asked for, so the
    work follows `need` and not the pool (every row examined is counted
    in `res.victim_rows_examined`). `force=True` falls back to PINNED
    rows (still never `protect`) when unpinned victims alone cannot
    cover `need` — the fused-step path, where the current batch being
    hot is a correctness requirement and an older pin is only a
    performance hint."""
    res = store.res
    if need <= 0:
        return np.empty(0, dtype=np.int64)
    H = res.hot_rows
    hand = int(res.hand[shard])
    span = min(H, max(_VICTIM_WINDOW_MIN, _VICTIM_FANOUT * need))
    unpinned = pinned = np.empty(0, dtype=np.int64)
    seen = 0
    while seen < H:
        w = min(span, H - seen)
        rows = (hand + seen + np.arange(w)) % H
        seen += w
        slots = res.row_slot[shard, rows].astype(np.int64)
        slots = slots[slots >= 0]
        if len(protect) and len(slots):
            slots = slots[~np.isin(slots, protect)]
        pin = res.pinned_mask(shard, slots, min_clock)
        unpinned = np.concatenate([unpinned, slots[~pin]])
        if force:
            pinned = np.concatenate([pinned, slots[pin]])
        if len(unpinned) + len(pinned) >= need:
            break
    res.hand[shard] = (hand + seen) % H
    res.victim_rows_examined += seen
    if force and len(unpinned) < need:
        # prefer unpinned victims; overflow into pinned by score
        extra = need - len(unpinned)
        if extra < len(pinned):
            sc = res.score[shard, pinned]
            pinned = pinned[np.argpartition(sc, extra - 1)[:extra]]
        return np.concatenate([unpinned, pinned])
    if len(unpinned) <= need:
        return unpinned
    sc = res.score[shard, unpinned]
    return unpinned[np.argpartition(sc, need - 1)[:need]]


def _span(server, name: str, wait: bool = False):
    """The bracket `adapm.tier.<name>` with its `tier.<name>_s`
    histogram (obs/spans.py; TierManager registers the histograms)."""
    t = server.tier
    return server._span("tier." + name,
                        t.hists[name] if t is not None else None,
                        wait=wait)


def _victims(server, store, shard, need, min_clock, protect,
             force=False) -> np.ndarray:
    with _span(server, "pick_victims"):
        return _pick_victims(store, shard, need, min_clock, protect,
                             force=force)


def _demote(server, store, shard: int, slots: np.ndarray) -> int:
    """`demote_rows` in its bracket, the readback of the written rows a
    wait bracket inside it, the rows counted (`tier.demotions`)."""
    with _span(server, "demote"):
        n = demote_rows(store, shard, slots, wait=partial(
            _span, server, "demote_readback", wait=True))
    _count_demotions(server, n)
    return n


def _promote(server, store, shard: int, slots: np.ndarray) -> int:
    with _span(server, "promote"):
        return promote_rows(store, shard, slots)


def ensure_hot_rows(server, store, shards: np.ndarray, slots: np.ndarray,
                    min_clock: int = 0, force: bool = False) -> int:
    """Promote any cold rows among (shards, slots), demoting low-score
    unpinned victims when a shard's hot pool is full (caller holds the
    server lock). `force=True` (the fused-step path) additionally evicts
    PINNED victims — never the batch itself — and raises when even that
    cannot fit the batch (the batch's own unique rows exceed the hot
    pool: a configuration error, like a full cache pool). Returns rows
    promoted."""
    res = store.res
    n = 0
    for s in np.unique(shards):
        s = int(s)
        sl = np.unique(slots[shards == s]).astype(np.int64)
        cold = sl[res.dev_row[s, sl] < 0]
        if len(cold) == 0:
            continue
        if force:
            short = len(cold) - res.alloc.num_free(s)
            if short > 0:
                victims = _victims(server, store, s, short, min_clock,
                                   sl, force=True)
                if len(victims):
                    _demote(server, store, s, victims)
            got = _promote(server, store, s, cold)
            if got < len(cold):
                raise RuntimeError(
                    f"tier hot pool exhausted on shard {s}: a fused "
                    f"step needs {len(cold)} cold rows hot but only "
                    f"{got} fit (hot_rows={res.hot_rows}); raise "
                    f"--sys.tier.hot_rows above the step's per-shard "
                    f"unique-key working set")
            n += got
            continue
        # background (non-forced) policy — anti-thrash: PINNED cold
        # candidates (live intent windows) outrank unpinned residents
        # and may demote them; unpinned candidates fill free capacity
        # and beyond that evict only STRICTLY lower-scored unpinned
        # residents (equal scores never churn)
        is_pin = res.pinned_mask(s, cold, min_clock)
        pc, uc = cold[is_pin], cold[~is_pin]
        n_pinned, n_unpinned = len(pc), len(uc)
        n_victims = n_beat = 0
        if len(pc):
            short = len(pc) - res.alloc.num_free(s)
            if short > 0:
                victims = _victims(server, store, s, short, min_clock,
                                   sl)
                n_victims += len(victims)
                if len(victims):
                    _demote(server, store, s, victims)
            n += _promote(server, store, s, pc)
        if len(uc):
            pol = server.policy
            if pol is not None and pol.active("tier"):
                # ISSUE 18 learned tier law: predicted
                # promoted-never-hit regret HOLDS this shard's
                # UNPINNED background promotions (the rows stay cold —
                # served exactly from the cold pool, slower, never
                # wrong, so no value-preservation guard is needed).
                # Pinned candidates above and the force=True fused-step
                # path are NEVER policy-gated: those promotions are
                # intent/correctness driven, not speculative.
                if pol.consult("tier", {"n_pinned": n_pinned,
                                        "n_unpinned": n_unpinned},
                               n_pinned + n_unpinned):
                    pol.applied("tier")
                    uc = uc[:0]
        if len(uc):
            over = len(uc) - res.alloc.num_free(s)
            if over > 0:
                uc = uc[np.argsort(-res.score[s, uc], kind="stable")]
                victims = _victims(server, store, s, over, min_clock, sl)
                n_victims += len(victims)
                if len(victims):
                    victims = victims[np.argsort(
                        res.score[s, victims], kind="stable")]
                    k = min(len(victims), len(uc))
                    beat = res.score[s, victims[:k]] < \
                        res.score[s, uc[:k]]
                    n_beat = int(beat.sum())
                    if beat.any():
                        _demote(server, store, s, victims[:k][beat])
                uc = uc[: res.alloc.num_free(s)]
            if len(uc):
                n += _promote(server, store, s, uc)
        dc = server.decisions
        if dc is not None and (n_pinned or n_unpinned):
            # ISSUE 17: this shard's promotion batch with the
            # anti-thrash verdict (pin split, victims scanned, victims
            # strictly beaten); the promoted rows open an outcome
            # window probing re-touch-while-hot
            dc.record_tier(store, s, np.concatenate((pc, uc)),
                           n_pinned, n_unpinned, n_victims, n_beat,
                           min_clock)
    return n


class PromotionEngine:
    """The tier maintenance worker, as a self-rescheduling executor
    task on the `tier` stream (adapm_tpu/exec; the dedicated thread +
    condvar this class owned before PR 6 is subsumed by the executor's
    worker pool). Each pass:

      1. drains the residency `want` queues (cold-miss and intent
         promotion requests) into batched `ensure_hot_rows` calls —
         DOUBLE-BUFFERED: the host-side prep of chunk N+1 (dedup,
         coordinate split) runs on the `tier` stream while chunk N's
         device scatter — committed on the `tier_commit` stream — is
         still in flight (GraphVite's episodic transfer/compute
         overlap; the exec.overlap_fraction gauge measures it);
      2. pressure-demotes: keeps a bounded free-row headroom per shard
         so hot-path promotions rarely wait on a victim readback;
      3. decays the access scores periodically (the CLOCK sweep).

    Every mutating batch takes the server lock for revalidation +
    ENQUEUE only (dispatch never — the lock-narrowing rule,
    docs/EXECUTOR.md); candidate scans run outside it and revalidate
    via the residency epoch. What a pass costs follows what it moves
    (module docstring): a victim scan examines `_VICTIM_FANOUT` rows a
    victim from the clock hand, a clean victim is dropped without a
    readback, and the one pool-wide sweep, the score decay, runs every
    `_DECAY_EVERY` passes OUTSIDE the lock. `run_once()` exposes one
    synchronous pass
    for deterministic tests/tooling. A pass that moved rows reschedules
    itself; an idle pass parks (no queued task — the executor worker
    parks on its condvar, pinned by scripts/exec_overlap_check.py)."""

    _INTERVAL_S = 0.02
    _DECAY_EVERY = 64

    def __init__(self, server, opts, manager):
        self.server = server
        self.opts = opts
        self.manager = manager
        self._stop = False
        self._passes = 0

    # -- producer ------------------------------------------------------------

    def kick(self) -> None:
        """Queue one maintenance pass (coalesced: a pass already queued
        absorbs the kick; a running pass reschedules itself while it
        finds work)."""
        if self._stop:
            return
        self.server.exec.submit("tier", self._pass,
                                label="tier.maintain",
                                coalesce_key="tier.maintain")

    # -- worker --------------------------------------------------------------

    def _pass(self) -> None:
        from ..utils import alog
        if self._stop:
            return
        delay = self._INTERVAL_S
        try:
            moved = self.run_once()
        except Exception as e:  # noqa: BLE001 — keep the worker up
            # retry after a backoff (the pre-PR thread loop's behavior):
            # a transient failure must not strand queued wants, pressure
            # demotion, and the CLOCK decay until the next external kick
            moved = 1
            delay = self._INTERVAL_S * 5
            alog(f"[tier] maintenance pass failed: "
                 f"{type(e).__name__}: {e}")
        if moved and not self._stop:
            # work found (or a failed pass retrying): keep draining at
            # the maintenance cadence
            self.server.exec.submit("tier", self._pass,
                                    label="tier.maintain",
                                    coalesce_key="tier.maintain",
                                    delay=delay)

    def run_once(self) -> int:
        """One maintenance pass (see class doc) in its bracket
        (`adapm.tier.pass`, `tier.pass_s`). Safe to call from any
        thread; takes the server lock internally per batch. Returns the
        number of rows moved (0 = the pass was a no-op)."""
        with _span(self.server, "pass"):
            return self._run_once()

    @contextlib.contextmanager
    def _locked(self):
        """The server lock for one batch of moves, its two halves
        apart: the wait for it (`adapm.tier.lock_wait`, a wait bracket:
        the serve dispatcher holds the same lock for every batch) and
        the hold (`adapm.tier.commit`, `tier.commit_s`: what every
        lookup waits behind)."""
        srv = self.server
        with srv._locked("tier.lock_wait",
                         self.manager.hists["lock_wait"], wait=True):
            with _span(srv, "commit"):
                yield

    def _run_once(self) -> int:
        srv = self.server
        mgr = self.manager
        moved = 0
        min_clock = mgr._min_active_clock()
        batch = max(1, self.opts.tier_demote_batch)
        ex = srv.exec
        # double-buffering needs a second worker to run the commit
        # while this pass preps the next chunk; the serialized fallback
        # (--sys.exec.single_stream) and a closing executor commit
        # inline — same results, no overlap
        pipelined = (not ex.single_stream and not ex.closed
                     and ex.max_workers >= 2)
        for st in srv.stores:
            res = st.res
            # 1. drain promotion wants — deduplicated, then processed in
            # bounded chunks so no single lock hold scans an unbounded
            # batch (the whole drained set IS processed this pass; a
            # capped-and-dropped remainder would silently starve
            # intent-pinned promotions behind access-driven noise).
            # Capture the list OBJECT, then rebind: a lock-free
            # request_promote racing the swap lands its append either in
            # the captured list (processed now) or the fresh one
            # (processed next pass) — a copy-then-clear would drop it.
            wants = res.want
            res.want = []
            if wants:
                sh = np.concatenate([w[0] for w in wants]).astype(np.int64)
                sl = np.concatenate([w[1] for w in wants]).astype(np.int64)
                pair = np.unique(sh * np.int64(res.main_slots) + sl)
                # DOUBLE-BUFFERED drain: chunk N commits (server lock ->
                # revalidate -> cold-row copy -> device scatter enqueue)
                # on the `tier_commit` stream while this pass preps
                # chunk N+1's coordinates on the `tier` stream — at most
                # one commit in flight, so host prep of batch N+1
                # overlaps the device scatter of batch N and nothing
                # runs unboundedly ahead
                prev = None
                for lo in range(0, len(pair), 4 * batch):
                    p = pair[lo: lo + 4 * batch]
                    csh = (p // res.main_slots).astype(np.int32)
                    csl = (p % res.main_slots).astype(np.int32)
                    commit = partial(self._commit_chunk, st, csh, csl,
                                     min_clock)
                    if pipelined:
                        cur = ex.submit("tier_commit", commit,
                                        label="tier.promote_commit")
                    else:
                        cur = None
                        moved += commit()
                    if prev is not None:
                        moved += self._commit_result(prev)
                    prev = cur
                if prev is not None:
                    moved += self._commit_result(prev)
            # 2. pressure demotion: keep a MODEST free-row headroom per
            # shard so hot-path promotions rarely pay a victim readback
            # — bounded by a fraction of the pool, NOT the raw batch
            # knob (a target above the pool size would demote every
            # unpinned row every pass, a permanent demote/promote storm)
            target = min(batch, max(1, res.hot_rows // 8))
            for s in range(res.num_shards):
                free = res.alloc.num_free(s)
                if free >= target:
                    continue
                # plan outside the lock; revalidate epoch under it
                epoch = res.epoch
                none = np.empty(0, dtype=np.int64)
                victims = _victims(srv, st, s, target - free, min_clock,
                                   none)
                if len(victims) == 0:
                    continue
                with self._locked():
                    if res.epoch != epoch:
                        # residency moved underneath the scan: replan
                        victims = _victims(
                            srv, st, s, target - res.alloc.num_free(s),
                            min_clock, none)
                    n = _demote(srv, st, s, victims) \
                        if len(victims) else 0
                if n:
                    moved += n
                    dc = srv.decisions
                    if dc is not None:
                        # ISSUE 17: headroom-reclaim demotion (outcome
                        # immediate — its cost surfaces as later
                        # promotions' regret, not its own)
                        dc.record_tier_demote(s, n, free, target)
        # 3. score decay
        self._passes += 1
        if self._passes % self._DECAY_EVERY == 0:
            for st in srv.stores:
                st.res.decay()
        return moved

    def _commit_chunk(self, st, sh: np.ndarray, sl: np.ndarray,
                      min_clock: int) -> int:
        """Commit one promotion chunk: server lock -> coordinate
        revalidation -> program enqueue (the lock-narrowing rule —
        dispatch itself is async under the gate)."""
        srv = self.server
        if srv.fault is not None:
            # ISSUE 10 injection point: fires BEFORE the commit takes
            # the lock or moves any row, so a retried commit (executor
            # policy on `tier_commit`, or _pass's own backoff retry
            # when inline) re-runs cleanly; the wanted rows stay cold
            # until a commit succeeds — slower, never wrong
            srv.fault.fire("tier.promote")
        with self._locked():
            n = ensure_hot_rows(srv, st, sh, sl, min_clock=min_clock)
        if n:
            self.manager.c_promotions.inc(n)
            wt = srv.wtrace
            if wt is not None:
                # promotion decision as it landed (ISSUE 15):
                # observational — replay's candidate tier policy
                # re-decides; the recorded stream is the baseline
                wt.record_decision("promote", n)
        return n

    @staticmethod
    def _commit_result(completion) -> int:
        """Join one in-flight commit; a commit cancelled by executor
        close counts zero (teardown path)."""
        n = completion.result(timeout=60)
        return int(n or 0)

    def close(self) -> None:
        """Stop the worker (idempotent; drains the tier streams so no
        maintenance pass can outlive the server into pool teardown)."""
        self._stop = True
        ex = self.server.exec
        if not ex.closed:
            if not ex.drain("tier", timeout=30) or \
                    not ex.drain("tier_commit", timeout=30):
                from ..utils import alog
                alog("[tier] maintenance pass failed to drain within "
                     "30s of close")
