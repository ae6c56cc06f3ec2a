"""Micro-batching coalescer: many concurrent lookups -> one fused gather.

DLRM-style inference is dominated by the embedding lookup path, and a
dedicated request-coalescing layer in front of the parameter store is
the standard lever (GraphVite's batched sample/lookup pipeline,
PAPERS.md; "Dissecting Embedding Bag Performance in DLRM Inference").
The `LookupBatcher` dispatches as event-driven drain programs on the
unified executor (PR 6 — the dedicated dispatcher thread is subsumed by
the executor pool; every `AdmissionQueue.submit` kicks a coalesced
drain for the request's lane, and an idle plane owns no queued
program). ISSUE 9 shards the dispatch plane: `--sys.serve.dispatchers
N` runs N drains on DISTINCT executor streams (`serve`, `serve.1`,
...), one per admission lane, so a long-row length class's gather no
longer head-of-line-blocks short ones; the queue's claim/shed state
machine makes the N consumers exactly-once by construction. A drain

  1. takes up to `--sys.serve.max_batch` requests from its lane,
     lingering at most `--sys.serve.max_wait_us` after the first
     (the micro-batch window — while a batch's gather is in flight the
     queue refills, so sustained load coalesces without waiting);
  2. DEDUPLICATES the union key set (concurrent clients hit the same hot
     rows; the device gathers one row per unique key, not per request);
  3. serves the union from the READ-ONLY SERVE REPLICA when one is
     attached (`--sys.serve.replica_rows`; serve/replica.py) and its
     epoch-versioned snapshot fully covers the batch — no server lock,
     no device dispatch, bit-identical by the epoch/topology
     validation — otherwise dispatches ONE fused gather per length
     class through the exact Pull machinery the training path uses —
     the routing-plan cache, `Server._plan_pull`, and `Server._pull`
     under the server lock — and scatters the union result back to
     each request.

Consistency contract (docs/SERVING.md): the locked path's plan is
computed optimistically outside the lock against a `topology_version`
snapshot and REVALIDATED under the lock at take time, exactly like
`Worker.pull` (PR 1's staged-pull discipline); the per-class gathers
are single device programs enqueued under the lock, so every key in a
coalesced batch is read from the same pool state (no torn batches — a
concurrent push is a whole program ordered before or after the gather,
never interleaved). The replica path keeps the same contract through
its write-epoch validation (serve/replica.py module docstring): a
batch carrying `after` ordering futures, an uncovered key, a moved
topology, or any bumped epoch falls back to the locked path. A serve
lookup is therefore bit-identical to a plain `Worker.pull` of the same
keys at the same point in dispatch order, across concurrent
relocations and sync rounds (pinned by tests/test_serve.py's storm
tests, replica path included).
"""
from __future__ import annotations

import collections
import itertools
import time
from typing import Dict, List, Optional

import numpy as np

from ..exec.executor import dispatch_gate
from ..obs.metrics import BATCH_SIZE_BOUNDS, SERVE_LATENCY_BOUNDS_S
from .admission import AdmissionQueue, LookupRequest, ServeDegradedError
from .bags import BagLookupRequest, plan_bag_batch, pool_bags_host

# member positions (and bags) of one coalesced bag batch: powers of four
# from a single small request to millions of members
BAG_MEMBER_BOUNDS = tuple(float(4 ** i) for i in range(3, 12))


class LookupBatcher:
    """Owns the dispatch logic (drain programs on the per-lane executor
    streams); one per ServePlane."""

    def __init__(self, server, opts, queue: AdmissionQueue,
                 shard: int = 0):
        self.server = server
        self.opts = opts
        self.queue = queue
        # the shard serve lookups route from: a local replica there is
        # preferred, otherwise the owner row is gathered directly (the
        # pools are one global sharded array, so any shard's rows are
        # one gather away in a single process)
        self.shard = int(shard)
        # sharded dispatch (ISSUE 9): one drain stream per admission
        # lane. Stream 0 keeps the historical name `serve` so existing
        # drains/metrics/tooling see the single-dispatcher default
        # unchanged.
        self.dispatchers = max(1, int(getattr(opts, "serve_dispatchers",
                                              1)))
        self.streams = ["serve"] + [f"serve.{i}"
                                    for i in range(1, self.dispatchers)]
        # wall-clock start of the batch each dispatcher is currently
        # serving (None = parked/idle). Written only by the owning
        # drain; read lock-free by the health monitor's wedge probe.
        self._busy_since: List[Optional[float]] = \
            [None] * self.dispatchers
        # lane assignment policy (ServeSession.lookup): by length class
        # on multi-class servers (per-length-class program queues —
        # long-row gathers stay off the short rows' stream), else
        # round-robin so single-class load still spreads over N
        self._rr = itertools.count()
        # read-only serve replica (serve/replica.py); attached by
        # ServePlane when --sys.serve.replica_rows > 0, else None (the
        # fast path costs one attribute check)
        self.replica = None
        # the EFFECTIVE micro-batch window: initialized from the static
        # knob and — only when --sys.serve.slo_ms is set — adapted by
        # the SLO controller (obs/slo.py) so tails track the target.
        # With no SLO target nothing ever writes it, so the static-knob
        # path behaves exactly as before
        self.max_wait_us = int(opts.serve_max_wait_us)
        # per-priority-class effective windows (ISSUE 20 satellite;
        # --sys.serve.slo_ms class overrides). None — the default, and
        # the ONLY value without overrides — keeps the take() path
        # byte-identical; set by ServePlane to {prio: wait_us}, each
        # entry walked independently by the SLO controller. The
        # bounded sample ring feeds the controller's per-class
        # percentiles (plain (t_mono, latency_s, prio) tuples — no
        # dynamic per-class registry names, APM007 stays closed).
        self.class_wait_us: Optional[Dict[int, int]] = None
        self._class_samples: Optional[collections.deque] = None
        self._running = False
        reg = server.obs
        # shared=True: a plane rebuilt on the same server reuses the
        # metrics (single-registration discipline, docs/OBSERVABILITY.md)
        self.c_lookups = reg.counter("serve.lookups_total", shared=True)
        self.c_batches = reg.counter("serve.batches_total", shared=True)
        self.c_keys = reg.counter("serve.keys_total", shared=True)
        self.c_keys_unique = reg.counter("serve.keys_deduped_total",
                                         shared=True)
        # replica-path accounting (schema v8): batches served lock-free
        # from the snapshot, and the hit-rate gauge the bench/guard
        # quote (present-but-inert when no replica is attached)
        self.c_replica_hits = reg.counter("serve.replica_hits_total",
                                          shared=True)
        if reg.enabled:
            reg.gauge("serve.replica_hit_rate", shared=True,
                      fn=self.replica_hit_rate)
        self.h_latency = reg.histogram("serve.latency_s",
                                       bounds=SERVE_LATENCY_BOUNDS_S,
                                       shared=True)
        self.h_batch = reg.histogram("serve.batch_size", unit="requests",
                                     bounds=BATCH_SIZE_BOUNDS, shared=True)

        def _hist(name):
            return reg.histogram(name, bounds=SERVE_LATENCY_BOUNDS_S,
                                 shared=True)

        # where a served lookup's time went, always on: the seven
        # consecutive phases between the stamps every LookupRequest
        # carries, one observation each per DELIVERED request, so the
        # phases' sums add up to serve.lookup_s exactly (PERF.md
        # section 3). admit/wake/lookup are observed by the client
        # (ServeSession), the five between by the dispatcher.
        self.h_admit = _hist("serve.admit_s")
        self.h_queue = _hist("serve.queue_s")
        self.h_batch_wait = _hist("serve.batch_wait_s")
        self.h_dispatch = _hist("serve.dispatch_s")
        self.h_copy_out = _hist("serve.copy_out_s")
        self.h_deliver = _hist("serve.deliver_s")
        self.h_wake = _hist("serve.wake_s")
        self.h_lookup = _hist("serve.lookup_s")
        # bag-read accounting (ISSUE 16; schema v12): requests and
        # pooled vectors delivered, plus which path produced the bits —
        # fused device gather+pool batches vs host-pooled batches
        # (replica snapshot hit or flat-union fallback), the replica
        # subset counted separately for the hit-rate story
        self.c_bag_lookups = reg.counter("serve.bag_lookups_total",
                                         shared=True)
        self.c_bag_pooled = reg.counter("serve.bag_pooled_total",
                                        shared=True)
        self.c_bag_fused = reg.counter("serve.bag_fused_total",
                                       shared=True)
        self.c_bag_hostpool = reg.counter("serve.bag_hostpool_total",
                                          shared=True)
        self.c_bag_replica_hits = reg.counter(
            "serve.bag_replica_hits_total", shared=True)
        # what a coalesced bag batch carried and what its host path
        # cost (ISSUE 37): every bag batch (the denominator of the
        # fused share), its member positions and bags as ASKED for
        # (not the padded buckets), the pooled bytes copied back, and
        # the two host phases of the fused dispatch
        self.c_bag_batches = reg.counter("serve.bag_batches_total",
                                         shared=True)
        self.h_bag_members = reg.histogram(
            "serve.bag_batch_members", unit="keys",
            bounds=BAG_MEMBER_BOUNDS, shared=True)
        self.h_bag_bags = reg.histogram(
            "serve.bag_batch_bags", unit="bags",
            bounds=BAG_MEMBER_BOUNDS, shared=True)
        self.c_bag_reply_bytes = reg.counter(
            "serve.bag_reply_bytes_total", unit="bytes", shared=True)
        self.h_bag_plan = _hist("serve.bag_plan_s")
        self.h_bag_route = _hist("serve.bag_route_s")
        # the dispatcher's wait for the server lock (ISSUE 46): pushes,
        # planner rounds and the tier worker's commits hold the same
        # lock; inside serve.dispatch_s
        self.h_lock_wait = _hist("serve.lock_wait_s")

    def _server_lock(self):
        """The server lock for one dispatch, the wait for it in its own
        bracket (`adapm.serve.lock_wait`, `serve.lock_wait_s`)."""
        return self.server._locked("serve.lock_wait", self.h_lock_wait)

    def replica_hit_rate(self) -> float:
        """Fraction of coalesced batches served from the read-only
        replica snapshot (0 with no replica attached)."""
        b = float(self.c_batches.value)
        return float(self.c_replica_hits.value) / b if b else 0.0

    # -- lane assignment (called by ServeSession) ----------------------------

    def assign_lane(self, keys: np.ndarray) -> int:
        """Admission lane for a request: its length class on
        multi-class servers (so each class's gathers queue on their own
        stream), round-robin otherwise. With one dispatcher everything
        is lane 0 — the pre-PR path."""
        if self.dispatchers == 1:
            return 0
        srv = self.server
        if len(srv.stores) > 1 and len(keys):
            return int(srv.ab.key_class[keys[0]]) % self.dispatchers
        return next(self._rr) % self.dispatchers

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        if self._running:
            return
        self._running = True
        self.queue.set_kick(self._kick)
        for lane in range(self.dispatchers):
            self._kick(lane)  # drain anything admitted before start

    def stop(self) -> None:
        """Close the queue (failing queued requests loudly) and drain
        every dispatcher stream under ONE 30 s bound. A drain program
        that does not finish within the bound is WEDGED (e.g. blocked
        on a dead remote owner's pull future) and still reads through
        the server's pools — proceeding into pool teardown would be a
        use-after-teardown, so this fail-stops loudly instead
        (docs/failure_handling.md) and keeps `_running` set
        (is_alive()/readiness stay truthful about the live reader)."""
        self.queue.set_kick(None)
        self.queue.close()
        ex = self.server.exec
        if not ex.closed and not ex.drain_streams(self.streams,
                                                  timeout=30):
            from ..utils import alog
            alog("[serve] dispatcher(s) failed to exit within 30s — "
                 "wedged mid-dispatch (dead remote owner?)")
            raise RuntimeError(
                "serve dispatcher wedged: did not exit within 30s "
                "of queue close; refusing to proceed into pool "
                "teardown under a live reader")
        self._running = False

    def is_alive(self) -> bool:
        """Dispatch capability: started, not stopped, and the executor
        that runs the drain programs is still open."""
        return self._running and not self.server.exec.closed

    def wedged_dispatchers(self, bound_s: float) -> List[int]:
        """Dispatchers that have been serving ONE batch for longer than
        `bound_s` (ISSUE 9 satellite: per-dispatcher liveness). Reads
        the busy stamps lock-free — a wedged drain cannot be asked to
        report, so readiness must never block on it."""
        now = time.monotonic()
        return [i for i, t in enumerate(self._busy_since)
                if t is not None and now - t > bound_s]

    # -- dispatchers ---------------------------------------------------------

    def _kick(self, lane: int = 0) -> None:
        """Queue one drain for `lane` on its stream (coalesced: kicks
        landing while that drain is queued are absorbed; a kick during
        a RUNNING drain queues the next one, so no admitted request is
        ever left undrained)."""
        if self._running:
            self.server.exec.submit(
                self.streams[lane], lambda: self._drain(lane),
                label=f"serve.drain.{lane}",
                coalesce_key=f"serve.drain.{lane}")

    def _drain(self, lane: int) -> None:
        """Serve micro-batches until the lane is empty (one executor
        program; FIFO on the lane's stream). The non-blocking take
        still LINGERS up to the micro-batch window after claiming a
        first request — that linger is the coalescing lever and counts
        as genuine stream-busy time."""
        srv = self.server
        if srv.fault is not None:
            # ISSUE 10 injection point: fires BEFORE any request is
            # claimed, so a failed drain program sheds nobody — the
            # executor's retry policy re-runs the drain and every
            # admitted request is still served
            try:
                srv.fault.fire("serve.drain")
            except BaseException:
                # re-kick the lane FIRST (coalesced, short delay):
                # kicks that landed while this program was queued were
                # absorbed into it, so if the executor's retry budget
                # exhausts and this program dies, the follow-up drain
                # queued here still serves every admitted request — a
                # no-deadline lookup must never hang on a dead drain
                if self._running:
                    srv.exec.submit(
                        self.streams[lane],
                        lambda: self._drain(lane),
                        label=f"serve.drain.{lane}",
                        coalesce_key=f"serve.drain.{lane}",
                        delay=0.02)
                raise
        max_batch = self.opts.serve_max_batch
        while True:
            # re-read per batch: the SLO controller adapts max_wait_us
            # between batches and the next window must honor it
            max_wait_s = self.max_wait_us * 1e-6
            cw = self.class_wait_us
            with srv._span("serve.take"):
                reqs = self.queue.take(
                    max_batch, max_wait_s, block=False, lane=lane,
                    wait_s_by_prio=(
                        {p: w * 1e-6 for p, w in cw.items()}
                        if cw is not None else None))
            if not reqs:
                return  # empty (or closed): park until the next kick
            self._busy_since[lane] = time.monotonic()
            pol = srv.policy
            if pol is not None:
                # ISSUE 18: how this batch's coalescing window closed
                # — filled to max_batch (size-limited) or dispatched
                # with room left when the window expired
                # (window-limited). The live denominator the serve
                # batch-window policy's shadow A/B reads against
                # (docs/POLICY.md runbook); one `is None` check when
                # the plane is off (the r7 skip-wrapper discipline).
                pol.note_batch(len(reqs) < max_batch)
            try:
                self._serve_batch(reqs)
            except (KeyboardInterrupt, SystemExit):
                # interpreter/process teardown is NOT a request
                # failure: shed the claimed batch so no waiter hangs,
                # then PROPAGATE (ISSUE 9 satellite — recording these
                # as request errors used to swallow the interrupt and
                # keep the dispatcher serving)
                for r in reqs:
                    if not r._done.is_set():
                        if r.tenant is not None:
                            r.tenant.c_shed.inc()
                        self.queue.c_shed.inc()
                        r.fail(RuntimeError(
                            "serve dispatcher interrupted "
                            "(KeyboardInterrupt/SystemExit): claimed "
                            "batch shed"))
                raise
            except BaseException as e:  # noqa: BLE001 — the dispatcher
                # must outlive any one batch: fail the batch's waiters
                # loudly (never leave a claimed request undelivered) and
                # keep serving
                for r in reqs:
                    if not r._done.is_set():
                        r.fail(e)
            finally:
                self._busy_since[lane] = None

    def _serve_batch(self, reqs: List[LookupRequest]) -> None:
        srv = self.server
        # degraded window (ISSUE 10): requests admitted BEFORE the
        # window opened are shed here with the same distinct error the
        # session door uses — a degraded server never dispatches a
        # gather (no torn or stale read; the restore is mutating the
        # pools under the lock this batch would otherwise take)
        reason = srv._degraded_reason
        if reason is not None:
            for r in reqs:
                self.queue.c_degraded.inc()
                self.queue.c_shed.inc()
                if r.tenant is not None:
                    r.tenant.c_shed.inc()
                r.fail(ServeDegradedError(
                    f"serve degraded: {reason} — queued lookup shed"))
            return
        fl = srv.flight
        t_dispatch = time.perf_counter()  # batch window closes, the
        # coalesced lookup starts (serve.batch_wait_s -> serve.dispatch_s)
        self.c_batches.inc()
        self.h_batch.observe(float(len(reqs)))
        # bag reads (ISSUE 16) coalesce separately: their reply is
        # pooled vectors, not per-key rows, so they cannot share the
        # flat union scatter below. A failed bag batch fails only its
        # own waiters; the flat requests still get served.
        bag_reqs = [r for r in reqs if isinstance(r, BagLookupRequest)]
        if bag_reqs:
            try:
                self._serve_bag_batch(bag_reqs, fl, t_dispatch)
            except (KeyboardInterrupt, SystemExit):
                for r in reqs:
                    if not r._done.is_set():
                        r.fail(RuntimeError(
                            "serve dispatcher interrupted "
                            "(KeyboardInterrupt/SystemExit): claimed "
                            "batch shed"))
                raise
            except BaseException as e:  # noqa: BLE001 — see _drain
                for r in bag_reqs:
                    if not r._done.is_set():
                        r.fail(e)
            reqs = [r for r in reqs
                    if not isinstance(r, BagLookupRequest)]
            if not reqs:
                return
        if len(reqs) == 1:
            allk = reqs[0].keys
        else:
            allk = np.concatenate([r.keys for r in reqs])
        union = np.unique(allk)
        after = tuple(f for r in reqs for f in r.after)
        # read fast path (ISSUE 9): a batch with no cross-process write
        # ordering may be served lock-free from the replica snapshot;
        # any validation failure inside try_serve falls back here
        served = None
        rep = self.replica
        if rep is not None and not after:
            served = rep.try_serve(union)
        if served is not None:
            flat, t_cutoff = served
            self.c_replica_hits.inc()
            if srv.tier is not None:
                # tiered storage: the store never sees a batch the
                # snapshot answers, so its keys are scored (and its cold
                # ones queued for promotion) here; a gathered batch is
                # scored by the gather itself, once (tier/coldpath.py
                # `_note_access`): the hot set adapts to serve load
                # either way
                srv.tier.note_serve(union)
            # lock-free hit: the union's rows were in host memory when
            # the window closed, so dispatch and copy_out are 0 (their
            # stamps collapse onto the dispatch point) and the
            # selection from the snapshot counts as delivery; the
            # freshness probe keeps the SNAPSHOT's under-lock stamp as
            # its read-order cutoff (the served bits are exactly as
            # fresh as the snapshot's gather)
            t_enqueued = t_copied = t_dispatch
        else:
            try:
                flat, t_enqueued, t_copied = \
                    self._lookup_union(union, after)
                t_cutoff = t_enqueued
            except (KeyboardInterrupt, SystemExit):
                for r in reqs:
                    if not r._done.is_set():
                        r.fail(RuntimeError(
                            "serve dispatcher interrupted "
                            "(KeyboardInterrupt/SystemExit): claimed "
                            "batch shed"))
                raise  # _drain propagates (satellite fix)
            except BaseException as e:  # noqa: BLE001 — fail every waiter
                for r in reqs:
                    r.fail(e)
                return
        # scatter the deduplicated union back to each request's keys
        # (duplicates within a request fan out here, like Worker.pull)
        from ..parallel.pm import _offsets, _select_flat
        lens_u = srv.value_lengths[union]
        offs_u = _offsets(lens_u)
        self.c_keys_unique.inc(len(union))
        now = self._stamp_batch(reqs, fl, t_dispatch, t_enqueued, t_copied,
                                len(allk), union, t_cutoff)
        with srv._span("serve.deliver"):
            for r in reqs:
                pos = np.searchsorted(union, r.keys)
                r.deliver(_select_flat(flat, offs_u, lens_u, pos))
                self.c_lookups.inc()
                self.c_keys.inc(len(r.keys))
                self._note_delivered(r, now)

    def _stamp_batch(self, reqs, fl, t_dispatch, t_enqueued, t_copied,
                     n_keys, union, t_cutoff) -> float:
        """Put the micro-batch's stamps on every member BEFORE any is
        delivered: deliver wakes the client, which reads them (the
        phase histograms; the flight flow's close). Returns the
        instant the batch's values were in hand (serve.latency_s's
        end)."""
        now = time.perf_counter()
        for r in reqs:
            r.stamp_batch(t_dispatch, t_enqueued, t_copied)
        if fl is not None:
            fl.record_serve_batch(reqs, n_requests=len(reqs),
                                  n_keys=n_keys, n_unique=len(union))
            # freshness probe: this union is a servable read of any
            # probed key whose push was enqueued before this gather —
            # or, on the replica path, before the SNAPSHOT's gather
            # (obs/flight.py; t_cutoff orders the two either way)
            fl.freshness.note_read(union, t_cutoff)
        return now

    def _note_delivered(self, r: LookupRequest, now: float) -> None:
        """Per delivered request, after its deliver(): the dispatcher's
        five phase observations (consecutive stamps of the request;
        shed/failed requests observe none) and the latency samples."""
        self.h_queue.observe(r.t_claim - r.t0)
        self.h_batch_wait.observe(r.t_dispatch - r.t_claim)
        self.h_dispatch.observe(r.t_enqueued - r.t_dispatch)
        self.h_copy_out.observe(r.t_copied - r.t_enqueued)
        self.h_deliver.observe(r.t_deliver - r.t_copied)
        if r.tenant is not None:
            r.tenant.c_served.inc()
        self.h_latency.observe(now - r.t0)
        cs = self._class_samples
        if cs is not None:
            cs.append((now, now - r.t0, r.priority))

    def _lookup_union(self, keys: np.ndarray, after):
        """One coalesced pull of the (unique, sorted) union batch — the
        `Worker._pull_op` sequence minus per-worker staging: optimistic
        plan via the shared routing-plan cache, topology_version
        revalidation under the lock, `Server._pull` dispatch; then the
        copy to the host, which waits for the rows on the device in the
        same blocking call (a wait of its own first, to split the two,
        cost 0.3 ms a lookup: PERF.md section 6, PR 24). Returns
        `(flat, t_enqueued, t_copied)`: perf_counter stamps taken right
        after the device gather programs are ENQUEUED and when the
        union is assembled in host memory."""
        srv = self.server
        with srv._span("serve.dispatch"):
            plan, tv = None, -1
            if srv.opts.optimistic_routing:
                tv = srv.topology_version
                plan = srv._plan_cached(
                    "pull", self.shard, keys, tv,
                    lambda: srv._plan_pull(keys, self.shard))
            with self._server_lock():
                if plan is not None and srv.topology_version != tv:
                    plan = None  # topology moved underneath us: re-plan
                groups, _, remote = srv._pull(keys, self.shard,
                                              after=after, plan=plan)
                # stamped under the lock so it totally orders against
                # FreshnessProbe.push_visible stamps (same lock)
                t_enqueued = time.perf_counter()
        with srv._span("serve.copy_out"):
            flat = srv._assemble_flat(keys, groups, remote=remote)
            t_copied = time.perf_counter()
        return flat, t_enqueued, t_copied

    # -- bag reads (ISSUE 16) ------------------------------------------------

    def _serve_bag_batch(self, reqs: List[BagLookupRequest], fl,
                         t_dispatch: float) -> None:
        """Serve a coalesced batch of bag lookups. Path choice per
        batch (serve/bags.py module docstring — the returned bits are
        identical on every path):

          1. replica snapshot fully covers the member-key union and no
             `after` ordering → host-pool over the snapshot rows
             (lock-free, zero device dispatches);
          2. `--sys.serve.bags` on and single-process (every member is
             one gather away in the global pools) → ONE fused
             gather_pool program per (length class, pooling) under the
             server lock — only pooled vectors cross the device
             boundary;
          3. otherwise (multi-process — members may live off-process —
             or the knob is off) → the flat union gather
             (`_lookup_union`, which orders remote members through the
             DCN channel correctly) + host pool."""
        srv = self.server
        with srv._span("serve.bag_plan", self.h_bag_plan):
            allk = np.concatenate([r.keys for r in reqs]) \
                if len(reqs) > 1 else reqs[0].keys
            union = np.unique(allk)
            groups, slices = plan_bag_batch(reqs, srv.ab.key_class)
        after = tuple(f for r in reqs for f in r.after)
        self.c_bag_batches.inc()
        self.c_keys_unique.inc(len(union))
        self.h_bag_members.observe(float(len(allk)))
        self.h_bag_bags.observe(
            float(sum(g["nbags"] for g in groups.values())))
        pooled = None
        rep = self.replica
        served = rep.try_serve(union) \
            if rep is not None and not after else None
        if served is not None:
            flat, t_cutoff = served
            self.c_bag_replica_hits.inc()
            if srv.tier is not None:
                srv.tier.note_serve(union)   # as the flat path's hit
            self.c_bag_hostpool.inc()
            pooled = self._pool_from_flat(flat, union, groups)
            t_enqueued = t_copied = t_dispatch
        else:
            fused = (bool(getattr(self.opts, "serve_bags", True))
                     and srv.glob is None and not after)
            costs = getattr(srv, "costs", None)
            if fused and costs is not None:
                # measured-cost consult (ops/costs.py): host-pool this
                # batch only if the table measures the flat gather +
                # host pool cheaper for EVERY group's shape; a missing
                # entry (None) keeps the fused default for its group
                verdicts = [costs.prefer_fused(
                    int(srv.value_lengths[g["keys"][0]]),
                    len(g["keys"]),
                    np.dtype(srv.stores[gkey[0]].dtype).name,
                    gkey[1]) for gkey, g in groups.items()]
                if verdicts and all(v is False for v in verdicts):
                    fused = False
                    costs.c_overrides.inc()
                dc = srv.decisions
                if dc is not None and verdicts:
                    # ISSUE 17: the measured-cost dispatch verdict for
                    # this bag batch (outcome immediate — the table is
                    # already measured)
                    dc.record_costs(
                        fused, len(verdicts), len(union),
                        sum(1 for v in verdicts if v is False),
                        sum(1 for v in verdicts if v is None))
            if fused:
                dev, t_enqueued = self._lookup_bags_fused(groups)
                with srv._span("serve.copy_out"):
                    # the whole padded bucket crosses; the reply is
                    # its first nbags rows
                    host = {k: np.asarray(v) for k, v in dev.items()}
                    t_copied = time.perf_counter()
                pooled = {k: h[:groups[k]["nbags"]]
                          for k, h in host.items()}
                self.c_bag_fused.inc()
                self.c_bag_reply_bytes.inc(
                    sum(h.nbytes for h in host.values()))
            else:
                flat, t_enqueued, t_copied = \
                    self._lookup_union(union, after)
                self.c_bag_hostpool.inc()
                pooled = self._pool_from_flat(flat, union, groups)
            t_cutoff = t_enqueued
        now = self._stamp_batch(reqs, fl, t_dispatch, t_enqueued, t_copied,
                                len(allk), union, t_cutoff)
        with srv._span("serve.deliver"):
            for r, rs in zip(reqs, slices):
                parts = [np.ascontiguousarray(
                    pooled[g][s:s + nb]).ravel() for g, s, nb in rs]
                r.deliver(np.concatenate(parts)
                          if len(parts) > 1 else parts[0])
                self.c_bag_lookups.inc()
                self.c_bag_pooled.inc(sum(nb for _, _, nb in rs))
                self._note_delivered(r, now)

    def _lookup_bags_fused(self, groups):
        """Dispatch one fused gather_pool per (length class, pooling)
        group — route the member coordinates and enqueue every group's
        program back-to-back under ONE dispatch-gate hold inside the
        server lock (the same contiguous-enqueue discipline
        `Server._pull` applies to multi-class flat batches). Only
        called single-process (`srv.glob is None`), where every member
        row lives in the global pools. Returns `({gkey: device pooled
        matrix}, t_enqueued)` — readback happens on the caller, outside
        the lock."""
        srv = self.server
        from ..core.store import OOB
        with srv._span("serve.dispatch"):
            with self._server_lock():
                dev = {}
                with dispatch_gate():
                    for gkey, g in groups.items():
                        cid, pooling = gkey
                        with srv._span("serve.bag_route",
                                       self.h_bag_route):
                            o_sh, o_sl, c_sh, c_sl, use_c, _, _ = \
                                srv._route(g["keys"], self.shard,
                                           record=False)
                            o_sl = np.where(use_c, OOB,
                                            o_sl).astype(np.int32)
                        dev[gkey] = srv.stores[cid].gather_pool(
                            o_sh, o_sl, c_sh, c_sl, use_c, g["seg"],
                            g["nbags"], pooling=pooling)
                t_enqueued = time.perf_counter()
        return dev, t_enqueued

    def precompile_bags(self, sizes, cid: int = 0,
                        pooling: str = "sum") -> int:
        """Compile the fused bag programs of length class `cid` before
        traffic does. `sizes`: the (member positions, bags) a coalesced
        bag batch can carry; the store pads both to powers of two and
        compiles one `_gather_pool` program a pair of buckets, so every
        pair the sizes fall in runs once here, through the dispatch the
        batcher itself makes, on members of no bag (segment OOB: the
        pool drops them). A tiered store has a cold twin of each, run
        too. Returns how many programs ran."""
        from ..core.store import OOB, bucket_size
        srv = self.server
        least = srv.stores[cid].bucket_min
        pairs = sorted({(bucket_size(int(n), least),
                         bucket_size(max(int(b), 1), least))
                        for n, b in sizes})
        st = srv.stores[cid]
        if st.res is not None:
            # a tiered store dispatches one of TWO programs a pair of
            # buckets, by whether the batch names a cold member
            # (tier/coldpath.py): both run here, straight at the store
            # (through `_lookup_bags_fused` a repeated key would be
            # scored, and queued for promotion, n times)
            from ..tier.coldpath import precompile_gather_pool
            for n, nb in pairs:
                with srv._lock:
                    with dispatch_gate():
                        dev = precompile_gather_pool(st, n, nb, pooling)
                for v in dev:
                    getattr(v, "block_until_ready", lambda: None)()
            return 2 * len(pairs)
        key = int(np.argmax(srv.ab.key_class == cid))
        for n, nb in pairs:
            dev, _ = self._lookup_bags_fused({(cid, pooling): {
                "keys": np.full(n, key, dtype=np.int64),
                "seg": np.full(n, OOB, dtype=np.int32), "nbags": nb}})
            for v in dev.values():
                # one program's temporaries at a time (the numpy port
                # returns finished arrays)
                getattr(v, "block_until_ready", lambda: None)()
        return len(pairs)

    def _pool_from_flat(self, flat, union, groups):
        """Host-pool each group's bags out of a flat union value buffer
        (replica snapshot rows or a `_lookup_union` result) — the
        bit-identical twin of the fused device path (pool_bags_host)."""
        srv = self.server
        from ..parallel.pm import _offsets, _select_flat
        lens_u = srv.value_lengths[union]
        offs_u = _offsets(lens_u)
        out = {}
        for gkey, g in groups.items():
            ks = g["keys"]
            pos = np.searchsorted(union, ks)
            L = int(srv.value_lengths[ks[0]])
            rows = _select_flat(flat, offs_u, lens_u,
                                pos).reshape(len(ks), L)
            out[gkey] = pool_bags_host(rows, g["seg"], g["nbags"],
                                       gkey[1])
        return out
