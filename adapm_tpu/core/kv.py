"""The user-facing parameter-manager API: Server + Worker.

API parity with the reference's ColoKVServer / ColoKVWorker
(include/ps/coloc_kv_server.h, include/ps/coloc_kv_worker.h): Pull / Push /
Set / PullIfLocal / Intent / PrepareSample / PullSample / Wait / WaitAll /
WaitSync / IsFinished / advanceClock / Barrier / BeginSetup / EndSetup /
Finalize, with the reference's async contract: ops return a timestamp,
`Wait(ts)` blocks, and `-1` means "answered entirely locally, nothing to wait
for" (coloc_kv_worker.h:120-186).

Design notes (see ARCHITECTURE.md):
  - Workers are logical application threads mapped onto mesh devices
    (worker w -> shard w % S), mirroring the reference's co-located
    worker/server process model.
  - Values are flat float buffers with per-key lengths (reference per-key
    `value_lengths`, coloc_kv_server.h:76); uniform-length calls may pass/get
    2-D [B, L] arrays.
  - The async contract maps onto JAX's async dispatch: an op enqueues device
    programs and returns; Wait materializes results (device->host copy for
    pulls, block_until_ready for pushes).
  - A single coarse lock serializes table+pool mutation (the reference's
    16384-mutex array is unnecessary: ops are batched programs, not per-key
    critical sections).
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time as _time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from ..base import CLOCK_MAX, LOCAL, WORKER_FINISHED, MgmtTechniques
from ..config import SystemOptions
from ..exec.executor import dispatch_gate
from ..obs.spans import Span
from ..parallel.mesh import MeshContext, get_mesh_context
from .addressbook import Addressbook
from .store import OOB, ShardedStore
from .sync import SyncManager


class _WaitEntry:
    __slots__ = ("groups", "out", "is_write", "keys", "remote", "futures")

    def __init__(self, groups=None, out=None, is_write=False, keys=None,
                 remote=None, futures=None):
        # groups: list of (class_id, row_positions, key_lengths_slice,
        #                  device_vals, n)
        self.groups = groups or []
        self.out = out
        self.is_write = is_write  # push/set: wait = block on current pools
        self.keys = keys
        self.remote = remote      # (positions, Future) for cross-process keys
        self.futures = futures or []  # outstanding cross-process writes


class _TopoHandle:
    """Yielded by Server._topology_mutation; cancel() marks a section
    that mutated nothing (exit then skips the version bump)."""

    __slots__ = ("cancelled",)

    def __init__(self):
        self.cancelled = False

    def cancel(self):
        self.cancelled = True


def _class_counter(obs, name: str, unit: str, lengths):
    """`inc(cid, n)` that moves the counter `name` and, beside it, the
    counter of length class `cid` alone, named by the class's row length
    in values (`<name>.len2048`: rows of 2,048)."""
    total = obs.counter(name, unit=unit)
    by_class = [obs.counter(f"{name}.len{n}", unit=unit) for n in lengths]

    def inc(cid: int, n: int) -> None:
        total.inc(n)
        by_class[cid].inc(n)
    return inc


class Server:
    """Owns the sharded pools, addressbook, planner, and worker registry.

    Reference ColoKVServer (coloc_kv_server.h:58-354). `value_lengths` may be
    a scalar (uniform) or a per-key array; keys are grouped into length
    classes, each with its own pooled store.
    """

    def __init__(self, num_keys: int,
                 value_lengths: Union[int, Sequence[int]],
                 opts: Optional[SystemOptions] = None,
                 ctx: Optional[MeshContext] = None,
                 num_workers: Optional[int] = None,
                 dtype=None, net_node=None):
        import jax.numpy as jnp
        self.opts = opts or SystemOptions()
        self.ctx = ctx or get_mesh_context()
        self.num_keys = int(num_keys)
        self.dtype = dtype or jnp.float32

        lens = np.asarray(value_lengths)
        if lens.ndim == 0:
            lens = np.full(self.num_keys, int(lens), dtype=np.int64)
        assert len(lens) == self.num_keys
        self.value_lengths = lens.astype(np.int64)
        self.val_offsets = np.zeros(self.num_keys + 1, dtype=np.int64)
        np.cumsum(self.value_lengths, out=self.val_offsets[1:])

        # length classes (vectorized: uniq is sorted, so searchsorted is the
        # length -> class map)
        uniq = np.unique(self.value_lengths)
        self.class_lengths = [int(u) for u in uniq]
        key_class = np.searchsorted(uniq, self.value_lengths).astype(np.int32)
        class_counts = np.bincount(key_class, minlength=len(uniq))

        # identity comes from the net node when one is injected (a
        # LoopbackNode gives each in-process "node" its own rank; the
        # default None -> DcnNode inside GlobalPM = the jax.distributed
        # control plane, byte-identical to pre-NetPort behavior)
        self._net_node = net_node
        if net_node is not None:
            self.num_procs = int(net_node.num_procs)
            self.pid = int(net_node.pid)
        else:
            from ..parallel import control
            self.num_procs = control.num_processes()
            self.pid = control.process_id()

        # unified telemetry (adapm_tpu/obs; docs/OBSERVABILITY.md): the
        # metrics registry every subsystem below reports into, the
        # optional span tracer, and crash dumps. Built FIRST so
        # SyncManager / PlanCache / PrefetchScheduler / GlobalPM can
        # register their metrics at construction.
        from ..obs import metrics as _obs_metrics
        self.obs = _obs_metrics.MetricsRegistry(enabled=self.opts.metrics)
        _obs_metrics.set_global_registry(self.obs)
        self.spans = None
        self.crash_dump_path = None
        bc_path = ring_path = None
        if self.opts.crash_dumps:
            from ..obs.crash import enable_crash_dumps
            try:
                self.crash_dump_path, bc_path, ring_path = \
                    enable_crash_dumps(self.pid, self.opts.stats_out)
            except OSError:  # unwritable dump dir must not block startup
                bc_path = ring_path = None
        if self.opts.trace_spans:
            from ..obs.spans import SpanTracer
            self.spans = SpanTracer(
                rank=self.pid, breadcrumb_path=bc_path,
                max_events=self.opts.trace_spans_max_events,
                registry=self.obs)
        # request-flight tracing (ISSUE 7 tentpole; obs/flight.py):
        # per-request causal traces across admission -> batch ->
        # executor program -> reply, exported as Perfetto flow events.
        # Default off — when None every instrumented site pays one
        # `is None` check (the r7 skip-wrapper discipline) and the
        # registry holds zero flight.* names.
        self.flight = None
        if self.opts.trace_flight:
            from ..obs.flight import FlightTracer
            self.flight = FlightTracer(
                registry=self.obs, rank=self.pid,
                freshness_bound=self.opts.flight_freshness_samples)
        # workload trace capture (ISSUE 15 tentpole; obs/wtrace.py,
        # docs/REPLAY.md): the semantic op stream recorded to a
        # versioned, checksummed .wtrace file for the offline replay
        # engine (adapm_tpu/replay). Default off — when None every
        # instrumented site pays one `is None` check (the r7 skip-
        # wrapper discipline) and the registry holds zero wtrace.*
        # names.
        self.wtrace = None
        if self.opts.trace_workload:
            from ..obs.wtrace import WorkloadTraceRecorder
            self.wtrace = WorkloadTraceRecorder(
                self, self.opts.trace_workload,
                key_budget=self.opts.trace_workload_keys)
        # decision telemetry capture (ISSUE 17 tentpole;
        # obs/decisions.py): every adaptive decision with its
        # at-decision feature vector + bounded outcome attribution,
        # recorded to a versioned, checksummed .dtrace file. Default
        # off — when None every instrumented site pays one `is None`
        # check (the r7 skip-wrapper discipline) and the registry
        # holds zero decision.* names.
        self.decisions = None
        if self.opts.trace_decisions:
            from ..obs.decisions import DecisionRecorder
            self.decisions = DecisionRecorder(
                self, self.opts.trace_decisions,
                follow_events=self.opts.trace_decisions_window)
        # learned adaptive-policy plane (ISSUE 18 tentpole;
        # adapm_tpu/policy): per-plane trained regret scorers that may
        # VETO a heuristic decision (--sys.policy.<plane> learned) or
        # shadow-score it without applying (--sys.policy.shadow).
        # Default off — when None every hook site pays one `is None`
        # check (the r7 skip-wrapper discipline) and the registry
        # holds zero policy.* names. A corrupt/incompatible artifact
        # raises the named PolicyError HERE, before any plane consults
        # it.
        self.policy = None
        if self.opts.policy_file:
            from ..policy.runtime import PolicyPlane
            self.policy = PolicyPlane(self)
        # populated by a ReplayEngine that drove this server (the
        # snapshot's always-present `replay` section; schema v11)
        self.replay_stats: Optional[Dict] = None
        # executor flight-recorder ring (rides --sys.crash_dumps): the
        # last K executor programs per stream, mirrored into a ring
        # file, so a hard abort's post-mortem says what was in flight.
        # Per PROGRAM — independent of --sys.trace.flight, never on the
        # per-op hot path.
        self.flight_recorder = None
        if self.opts.crash_dumps:
            from ..obs.flight import FlightRecorder
            self.flight_recorder = FlightRecorder(path=ring_path)
        # fault-injection plane (ISSUE 10 tentpole; adapm_tpu/fault):
        # None unless --sys.fault.spec names points — the r7 skip-
        # wrapper discipline: off costs one `is None` check per
        # instrumented site and zero fault.* registry names (pinned by
        # scripts/metrics_overhead_check.py)
        self.fault = None
        if self.opts.fault_spec:
            from ..fault.inject import FaultPlane
            self.fault = FaultPlane(self.opts.fault_spec,
                                    seed=self.opts.fault_seed,
                                    registry=self.obs)
        # executor error policy (fault/policy.py): bounded retry +
        # exponential backoff for TRANSIENT program failures. Built
        # unconditionally — the default classifier matches only
        # TransientFaultError, so with nothing raising it the policy
        # is inert and executor behavior is byte-identical to pre-PR.
        from ..fault.policy import RetryPolicy
        self._retry_policy = RetryPolicy(
            max_retries=self.opts.fault_retries,
            backoff_base_s=self.opts.fault_backoff_ms * 1e-3,
            backoff_max_s=self.opts.fault_backoff_max_ms * 1e-3)
        # degraded readiness (ISSUE 10): set while a checkpoint-chain
        # restore applies (fault/ckpt.py restore_chain) — the serve
        # plane sheds loudly with ServeDegradedError instead of
        # risking a read that mixes pre- and post-restore bits
        self._degraded_reason: Optional[str] = None
        self._last_recovery_s: Optional[float] = None
        # unified async executor (ISSUE 6 tentpole; adapm_tpu/exec,
        # docs/EXECUTOR.md): THE ordered-stream dispatch plane under
        # sync rounds, prefetch staging, tier maintenance, serve
        # batching, and fused steps. Built right after the registry so
        # every subsystem below can submit from construction; closed
        # LAST in shutdown(), after every producer is stopped.
        from ..exec import AsyncExecutor
        self.exec = AsyncExecutor(registry=self.obs,
                                  workers=self.opts.exec_workers,
                                  single_stream=self.opts.exec_single_stream,
                                  recorder=self.flight_recorder,
                                  retry_policy=self._retry_policy,
                                  fault=self.fault)

        # kv-layer metrics: per-op latency histograms live on the
        # workers (kv.pull_s/push_s/set_s, shared); registry-side extras:
        self._c_topo_bumps = self.obs.counter("kv.topology_bumps")
        self.obs.gauge("kv.topology_version",
                       fn=lambda: self.topology_version)
        self.obs.gauge("kv.workers", fn=lambda: len(self._workers))
        # host time of a training step's server-side phases, one
        # observation per call (Server._span; PERF.md section 3)
        self._h_drive = self.obs.histogram("kv.drive_rounds_s")
        self._h_quiesce = self.obs.histogram("kv.quiesce_s")
        self._h_intent = self.obs.histogram("kv.intent_s")
        self._h_clock = self.obs.histogram("kv.advance_clock_s")
        # the same phases less the waits for the device beneath them
        # (obs/spans.py: `work=`): the host's own time
        self._h_drive_work = self.obs.histogram("kv.drive_rounds_work_s")
        self._h_intent_work = self.obs.histogram("kv.intent_work_s")
        self._h_clock_work = self.obs.histogram("kv.advance_clock_work_s")
        # the planner's two device-program phases (one observation per
        # call of _relocate_to / _sync_replicas), and the wire bytes the
        # sync programs shipped, as a counter
        self._h_relocate = self.obs.histogram("kv.relocate_s")
        self._h_sync_replicas = self.obs.histogram("kv.sync_replicas_s")
        self._h_relocate_work = self.obs.histogram("kv.relocate_work_s")
        self._h_sync_replicas_work = self.obs.histogram(
            "kv.sync_replicas_work_s")
        # the stores' calls of the planner's programs (the wait span
        # `store.enqueue`, core/store.py): what a call holds beyond its
        # enqueue is the wait for a free dispatch slot
        self._h_store_enqueue = self.obs.histogram("kv.store_enqueue_s")
        # the wire bytes and replica rows those programs shipped (a
        # periodic round, a drop's flush, quiesce); relocations demoted
        # to a replica because the destination's main pool was full, and
        # replica creations left out because its cache pool was
        # (`_relocate_to`, `_create_replicas`): a run that counts either
        # measures a pool size. Each `inc(class id, n)`: the total and
        # the length class's own (`_class_counter`)
        lens = self.class_lengths
        self._c_sync_bytes = _class_counter(
            self.obs, "sync.bytes_shipped_total", "bytes", lens)
        self._c_sync_rows = _class_counter(
            self.obs, "sync.rows_shipped_total", "rows", lens)
        self._c_demoted = _class_counter(
            self.obs, "sync.relocations_demoted_total", "keys", lens)
        self._c_truncated = _class_counter(
            self.obs, "sync.replicas_truncated_total", "keys", lens)
        # collective wait-time histograms, observed by the (server-less)
        # control plane via observe_global (parallel/control.py) and by
        # Server.barrier below
        self.obs.histogram("collective.barrier_wait_s")
        self.obs.histogram("collective.allreduce_wait_s")

        self.stores: List[ShardedStore] = []
        for cid, L in enumerate(self.class_lengths):
            cache_slots = self.opts.cache_slots_per_shard
            if cache_slots == 0 and self.num_procs > 1:
                # multi-process auto default: data-parallel workloads
                # contest keys across processes, so give each shard 2x the
                # per-shard fair share (bounded by the class size). At
                # memory-bound scale tune --sys.cache_slots explicitly —
                # ensure_local raises with that hint when the pool is the
                # limit; expired replicas are dropped to make room first.
                fair = -(-int(class_counts[cid]) // self.ctx.num_shards)
                cache_slots = min(2 * fair, int(class_counts[cid]))
            self.stores.append(ShardedStore(
                int(class_counts[cid]), L, self.ctx, dtype=self.dtype,
                over_alloc=self.opts.main_over_alloc,
                cache_slots_per_shard=cache_slots,
                bucket_min=self.opts.remote_bucket_min,
                tier_hot_rows=(self.opts.tier_hot_rows
                               if self.opts.tier else 0),
                tier_cold_dtype=(self.opts.tier_cold_dtype
                                 if self.opts.tier else "fp32"),
                wait=self._store_wait))
        # device-plane accounting (ISSUE 14; schema v10): the stores
        # share one process-wide DevicePort — surface its program /
        # wire-ingest counters. shared=True: several servers in one
        # process read the same port.
        if self.obs.enabled and self.stores:
            _port = self.stores[0].port
            self.obs.gauge("device.programs_total", shared=True,
                           fn=lambda p=_port: p.programs)
            self.obs.gauge("device.wire_ingest_rows_total", shared=True,
                           fn=lambda p=_port: p.wire_ingest_rows)

        self.ab = Addressbook(
            key_class, self.ctx.num_shards,
            [s.main_slots for s in self.stores],
            [s.cache_slots for s in self.stores],
            num_procs=self.num_procs, pid=self.pid)

        # addressbook-mutation discipline (ADVICE r5 #1): every counted
        # ab mutation must happen inside _topology_mutation(), which
        # bumps topology_version as the LAST step of the critical
        # section and acknowledges the count here
        self._ab_mut_acked = self.ab.mutations

        self.num_shards = self.ctx.num_shards
        # explicit num_workers DECLARES the worker set (reference
        # Setup(num_keys, num_threads)): worker barriers then rendezvous
        # over all declared ids, so an early barrier cannot slip past
        # workers whose threads have not registered yet
        self._wb_declared = num_workers is not None
        self.max_workers = num_workers or max(self.num_shards, 1)
        self._workers: Dict[int, "Worker"] = {}
        self._clocks = np.zeros(self.max_workers, dtype=np.int64)
        self._lock = threading.RLock()
        # serializes sync ROUNDS (planner) without holding _lock across DCN
        # round-trips — see parallel/pm.py locking discipline. Reentrant:
        # run_round acquires it itself (the prefetch pipeline drives
        # rounds from a background thread, so bare run_round calls from
        # tests/benches must self-serialize), and wait_sync/quiesce wrap
        # it around multi-call sequences.
        self._round_lock = threading.RLock()
        if self.opts.lint_lockorder:
            # runtime lock-order sentinel (ISSUE 11; lint/lockorder.py,
            # docs/INVARIANTS.md): record this server's lock
            # acquisitions in the process-wide graph — a cycle or a
            # lock taken under the dispatch gate raises LockOrderError
            # at the acquire, deterministically, instead of waiting for
            # a storm to actually deadlock. Off (the default) builds
            # the plain RLocks above: zero wrapper anywhere hot.
            from ..lint import lockorder
            lockorder.enable_sentinel()
            self._lock = lockorder.SentinelLock("server", self._lock)
            self._round_lock = lockorder.SentinelLock(
                "sync_round", self._round_lock)
            self.obs._lock = lockorder.SentinelLock(
                "metrics_registry", self.obs._lock)
        self._in_setup = False
        # worker-thread barrier state (reference ColoKVWorker::Barrier is a
        # barrier over ALL workers, threads included, via the scheduler's
        # BARRIER counting — src/postoffice.cc:149-174): generation counter
        # + the set of arrived worker ids; see worker_barrier()
        self._wb_cond = threading.Condition()
        self._wb_waiting: set = set()
        self._wb_gen = 0        # generation currently accepting arrivals
        self._wb_done = 0       # generations fully completed
        self._wb_leading = False
        self._wb_errs: Dict[int, BaseException] = {}  # gen -> leader error
        # bumped whenever placement changes (replica add/drop, relocation);
        # consumers (LocalSampling) use it to invalidate local-key caches
        self.topology_version = 0

        self.sync = SyncManager(self, self.opts)
        self._sync_thread: Optional[threading.Thread] = None
        self._sync_stop = threading.Event()

        # tiered parameter storage (ISSUE 5 tentpole; adapm_tpu/tier,
        # docs/MEMORY.md): device-hot / host-cold main-row residency
        # with intent-driven promotion. None when --sys.tier is off —
        # the stores are then plain device pools, zero tier overhead.
        self.tier = None
        if self.opts.tier:
            self.opts.validate_serve()  # tier knob ranges (parse-time
            # validation is skipped for hand-built SystemOptions)
            from ..tier.residency import TierManager
            self.tier = TierManager(self, self.opts)

        # measured kernel cost table (ISSUE 16; ops/costs.py): attached
        # when --sys.costs.table names a JSON table. calibrate=1
        # measures on THIS server's live stores and persists; otherwise
        # a missing/unreadable file just means no table (the built-in
        # dispatch preferences apply — a measured table can only ever
        # refine the choice, never be required). Consulted by the serve
        # batcher's bag dispatch and the episodic prep sizing.
        self.costs = None
        if self.opts.costs_table:
            from ..ops.costs import KernelCostTable, calibrate_server
            if self.opts.costs_calibrate:
                self.costs = calibrate_server(self)
                self.costs.save(self.opts.costs_table)
            else:
                try:
                    self.costs = KernelCostTable.load(
                        self.opts.costs_table)
                except OSError:
                    self.costs = None
            if self.costs is not None:
                self.costs.bind_metrics(self.obs)

        # routing-plan cache + intent-driven prefetch pipeline (the hot
        # Pull/Push path levers; core/intent.py). Both revalidate against
        # topology_version, i.e. they depend on the _topology_mutation
        # discipline above.
        from .intent import PlanCache, PrefetchScheduler
        self._plan_cache = PlanCache(self.opts.plan_cache_entries,
                                     registry=self.obs) \
            if self.opts.plan_cache_entries > 0 else None
        self.prefetch = PrefetchScheduler(self, self.opts) \
            if self.opts.prefetch else None

        # debug: per-key additive-apply counter (ADAPM_DEBUG_APPLIES=1);
        # diagnostics only — see tests/mp_bisect.py
        import os as _os
        self._dbg_applies = np.zeros(self.num_keys) \
            if _os.environ.get("ADAPM_DEBUG_APPLIES") else None

        # cross-process layer: N launched processes form one PM
        # (parallel/pm.py; reference van/postoffice data plane)
        self.glob = None
        # outstanding remote writes (future, keys): replication of a key
        # with an in-flight remote write is deferred — the owner's base
        # snapshot might miss the write, breaking read-your-own-pushes
        # (pm.py _install_replicas)
        self._rw_pending: List = []
        # transport-plane stats surface (net/membership.py): None on
        # single-process AND dcn servers — the snapshot `net` section
        # and net.* registry names exist only when a loopback/tcp node
        # is attached (metrics_overhead_check.py pins default-off)
        self.net = None
        if self.num_procs > 1:
            from ..parallel.pm import GlobalPM
            self.glob = GlobalPM(self, node=self._net_node)
            node = self.glob.node
            if hasattr(node, "bind"):
                # loopback: attach the executor + fault plane to the
                # port and start the membership beat thread
                node.bind(self)
            self.net = node.net_plane()
            if self.opts.heartbeat_s > 0:
                node.start_heartbeat(self.opts.heartbeat_s)

        self.sampling = None  # set by enable_sampling_support
        self._shutdown_done = False  # shutdown() is idempotent
        # online serving plane (adapm_tpu/serve): attached by
        # ServePlane.__init__ so metrics_snapshot can fold readiness in
        # and shutdown can close it; None until a plane is built
        self._serve_plane = None

        # streaming plane (ISSUE 20 tentpole; adapm_tpu/stream,
        # docs/STREAMING.md): the acked-event cursor + ingest
        # accounting + the FreshnessSLO controller closing the loop on
        # event-to-servable staleness. None unless a --sys.stream.*
        # knob is set — the r7 skip-wrapper discipline: off costs one
        # `is None` check per integration site and zero stream.*
        # registry names (scripts/metrics_overhead_check.py pins it).
        # Built AFTER the sync manager (the controller's first lever)
        # and the executor (the controller tick + trainer pump run on
        # it); started here so a freshness target begins steering
        # without any further wiring.
        self.stream = None
        # cursor recovered from a checkpoint chain that carried
        # aux_stream_cursor (fault/ckpt.py restore_chain); also applied
        # to self.stream.cursor when the plane exists — kept as a
        # separate field so a restore into a plane-less server still
        # surfaces the watermark loudly instead of dropping it
        self._restored_stream_cursor: Optional[int] = None
        if self.opts.stream_batch > 0 or \
                self.opts.stream_freshness_slo_ms > 0:
            from ..stream import StreamPlane
            self.stream = StreamPlane(self)
        if self.stream is not None:
            self.stream.start()

        # native host-routing core (C++ via ctypes; None -> numpy fallback)
        from ..native import get_lib
        self._native = get_lib()

        # observability (reference PS_TRACE_KEYS / PS_LOCALITY_STATS, §5)
        from ..utils.stats import (KeyTracer, LocalityStats, ALLOC,
                                   parse_trace_spec)
        traced = parse_trace_spec(self.opts.trace_keys or "", self.num_keys)
        self.tracer = KeyTracer(traced, self.num_keys) \
            if traced is not None else None
        self.locality = LocalityStats(self.num_keys, self._native) \
            if self.opts.locality_stats else None
        # device-routed runners register a counts callback here so the
        # production path feeds locality_summary too (ops/fused.py)
        self._locality_sources: List = []
        # ... and share this queue of their dispatched steps' losses,
        # oldest first: `fused.inflight_steps` reads its length at each
        # dispatch (ops/fused.py; not kept with the registry off)
        self._steps_in_flight = collections.deque() \
            if self.obs.enabled else None
        if self.tracer is not None:
            # initial allocation events, grouped by home shard (one record
            # call per shard, not per key)
            owners = self.ab.owner[traced]
            for s in np.unique(owners):
                self.tracer.record(traced[owners == s], ALLOC, int(s))

        # periodic incremental checkpoints (ISSUE 10; fault/ckpt.py):
        # with --sys.checkpoint.every N + --sys.checkpoint.path D, a
        # self-rescheduling `ckpt`-stream executor program appends a
        # dirty-slot delta (base first) every N seconds. None when off.
        self.ckpt = None
        if self.opts.ckpt_every_s > 0:
            if not self.opts.ckpt_path:
                raise ValueError(
                    "--sys.checkpoint.every requires "
                    "--sys.checkpoint.path (chain directory)")
            from ..fault.ckpt import IncrementalCheckpointer
            self.ckpt = IncrementalCheckpointer(self, self.opts.ckpt_path)
            self.ckpt.start_periodic(self.opts.ckpt_every_s)

        # periodic metrics reporter (--sys.metrics.report N). The import
        # is INSIDE the gate on purpose: with --sys.metrics 0 the
        # reporter module must never load (tests assert this).
        self._reporter = None
        if self.opts.metrics and self.opts.metrics_report_s > 0:
            from ..obs.reporter import Reporter
            self._reporter = Reporter(self.obs,
                                      self.opts.metrics_report_s,
                                      rank=self.pid)
            self._reporter.start()

    # -- topology-mutation discipline ----------------------------------------

    def _check_topology_discipline(self) -> None:
        """Debug assertion pairing addressbook mutations with a
        topology_version bump: every counted ab mutation must have gone
        through _topology_mutation(). Cheap (one int compare), so it
        runs on every entry to the context manager and on the optimistic
        revalidation path."""
        assert self.ab.mutations == self._ab_mut_acked, (
            "addressbook mutated outside Server._topology_mutation(): "
            "optimistic routing, the plan cache and staged prefetch "
            "buffers revalidate against topology_version, so an "
            "unpaired mutation lets stale plans dispatch into freed or "
            "reassigned pool slots")

    @contextlib.contextmanager
    def _topology_mutation(self):
        """THE addressbook-mutation discipline (ADVICE r5 #1). Every site
        that mutates placement tables must run inside this context: it
        holds the server lock and bumps `topology_version` as the LAST
        mutation of its critical section on exit — the invariant that
        makes optimistic routing's plan-then-revalidate sound (a stale
        plan can never pass revalidation, because the bump is visible
        before the lock is released). The yielded handle's `cancel()`
        marks a section that turned out to mutate nothing (e.g. a
        relocation whose whole batch demoted); exit then asserts nothing
        WAS mutated, so a cancelled-but-mutated section fails loudly
        instead of leaking an unbumped mutation."""
        with self._lock:
            self._check_topology_discipline()
            before = self.ab.mutations
            h = _TopoHandle()
            try:
                yield h
            finally:
                # bump even when the section raised: a PARTIAL mutation
                # must still fail every outstanding optimistic plan
                if h.cancelled:
                    assert self.ab.mutations == before, (
                        "topology mutation section cancelled after "
                        "mutating the addressbook")
                else:
                    self.topology_version += 1
                    self._c_topo_bumps.inc()
                    self._ab_mut_acked = self.ab.mutations

    def _span(self, name: str, hist=None, wait: bool = False,
              work=None) -> Span:
        """THE phase bracket (obs/spans.py): a host event
        `adapm.<name>` on the profiler's clock whenever a profiler
        session runs, the elapsed seconds into `hist` when given, and a
        SpanTracer record under --sys.trace.spans. `wait`: the span
        holds ONE call that can block on the device or a transfer, and
        its seconds join the thread's wait tally; `work`: a histogram
        for the span's seconds outside every wait beneath it. Neither
        is kept under --sys.metrics 0."""
        if (wait or work is not None) and not self.obs.enabled:
            wait, work = False, None
        return Span(name, hist, self.spans, wait, work)

    @contextlib.contextmanager
    def _locked(self, name: str, hist=None, wait: bool = False):
        """The server lock with the WAIT for it in a bracket of its own
        (`adapm.<name>`, `hist`; `wait`: a wait span): the serve
        dispatcher's and the tier worker's way in, each of which can
        wait behind the other."""
        with self._span(name, hist, wait=wait):
            self._lock.acquire()
        try:
            yield
        finally:
            self._lock.release()

    def _store_wait(self) -> Span:
        """The bracket the stores put around a planner program's call
        (core/store.py): the wait span `store.enqueue`."""
        return self._span("store.enqueue", self._h_store_enqueue,
                          wait=True)

    # -- worker management ---------------------------------------------------

    def make_worker(self, worker_id: Optional[int] = None) -> "Worker":
        with self._lock:
            if worker_id is None:
                worker_id = len(self._workers)
            assert worker_id < self.max_workers, (
                f"worker_id {worker_id} >= num_workers {self.max_workers}")
            w = Worker(self, worker_id)
            self._workers[worker_id] = w
            return w

    def workers(self):
        return list(self._workers.values())

    def worker_clocks(self) -> np.ndarray:
        return self._clocks.copy()

    def shard_min_clocks(self) -> np.ndarray:
        """Min clock over the workers mapped to each shard (used for intent
        expiry; reference compares per-customer clocks, handle.h:542-578)."""
        out = np.full(self.num_shards, np.iinfo(np.int64).max)
        for wid, w in self._workers.items():
            out[w.shard] = min(out[w.shard], self._clocks[wid])
        out[out == np.iinfo(np.int64).max] = 0
        return out

    # -- sampling ------------------------------------------------------------

    def enable_sampling_support(self, sample_key_fn, min_key: int = 0,
                                max_key: Optional[int] = None,
                                allowed_keys=None) -> None:
        """Install a sampling scheme (reference
        ColoKVServer::enable_sampling_support, coloc_kv_server.h;
        `sample_key_fn(n, rng) -> np.ndarray[int64]` draws app-distribution
        keys, like the reference's `Key sample_key()` callback).
        `allowed_keys` bounds the Local scheme's snap population when the
        sampled keys are not a contiguous range."""
        from .sampling import make_sampling
        self.sampling = make_sampling(self, sample_key_fn, min_key,
                                      max_key if max_key is not None
                                      else self.num_keys,
                                      allowed_keys=allowed_keys)

    # -- routing helpers (host) ---------------------------------------------

    def _route(self, keys: np.ndarray, shard: int,
               write_through: bool = False, record: bool = True):
        """Resolve keys (any shape) to pool coordinates for a worker on
        `shard`, preferring a local replica over the owner row (the single
        routing policy shared by Pull/Push and the fused step, ops/fused.py).
        Returns (o_sh, o_sl, c_sh, c_sl, use_c, n_remote, local): owner
        shard+slot, replica shard+slot (OOB where none), replica mask,
        remote-key count, and the per-key locality mask (THE definition of
        "local" — dispatch-time stats reuse it instead of restating the
        policy). Locality stats are recorded here unless `record=False`
        (optimistic planning: a plan that fails topology revalidation is
        recomputed, and must not count twice); `write_through` marks ops
        that must reach the owner regardless of replicas (Set), so a
        replica doesn't count as local. Uses the native router
        (adapm_tpu/native) when available."""
        ab = self.ab
        if self._native is not None:
            from ..native import route
            flat = np.ascontiguousarray(keys.ravel(), dtype=np.int64)
            o_sh, o_sl, c_sh, c_sl, use_c, n_remote, local = route(
                self._native, flat, ab.owner, ab.slot,
                ab.cache_slot[shard], shard, int(OOB), write_through)
            if record and self.locality is not None:
                self.locality.record(flat, local)
            sh = keys.shape
            o_sh, o_sl = o_sh.reshape(sh), o_sl.reshape(sh)
            c_sh, c_sl = c_sh.reshape(sh), c_sl.reshape(sh)
            use_c = use_c.reshape(sh)
            return o_sh, o_sl, c_sh, c_sl, use_c, n_remote, \
                local.reshape(sh)
        # numpy fallback: match the native path's bounds behavior
        from ..base import check_key_range
        check_key_range(keys, self.num_keys)
        o_sh = ab.owner[keys].astype(np.int32)
        o_sl = ab.slot[keys].astype(np.int32)
        cs = ab.cache_slot[shard, keys].astype(np.int32)
        use_c = cs >= 0
        on_owner = o_sh == shard
        local = on_owner if write_through else (use_c | on_owner)
        n_remote = int((~local).sum())
        if record and self.locality is not None:
            self.locality.record(keys.ravel(), local.ravel())
        c_sh = np.full_like(o_sh, shard)
        c_sl = np.where(use_c, cs, OOB).astype(np.int32)
        return o_sh, o_sl, c_sh, c_sl, use_c, n_remote, local

    def _group_by_class(self, keys: np.ndarray):
        """Split a key batch by length class; returns [(cid, positions)]."""
        kc = self.ab.key_class[keys]
        if len(self.stores) == 1:
            return [(0, np.arange(len(keys)))]
        return [(cid, np.nonzero(kc == cid)[0])
                for cid in np.unique(kc)]

    def _flat_parts(self, keys: np.ndarray, flat: np.ndarray, positions,
                    length: int) -> np.ndarray:
        """Extract [n, L] rows for `positions` of `keys` out of a flat
        concatenated value buffer (offsets are relative to this batch).
        Vectorized via the shared ragged-buffer helpers (parallel/pm.py) —
        never a per-key loop (a full-model push at Wikidata5M scale passes
        through here)."""
        from ..parallel.pm import _offsets, _select_flat
        lens = self.value_lengths[keys]
        return _select_flat(flat, _offsets(lens), lens,
                            np.asarray(positions)).reshape(-1, length)

    # -- core ops (called by Worker; all under the server lock) --------------

    def _plan_pull(self, keys: np.ndarray, shard: int):
        """Routing plan for `_pull`: no device dispatch, no side effects.
        Safe to call WITHOUT the server lock — it reads only the fixed-size
        in-place-mutated addressbook tables, and every table mutation bumps
        `topology_version` under the lock, so callers revalidate the
        version under the lock before dispatching and re-plan on a miss
        (optimistic routing; the reference instead shards per-key locks so
        N worker threads route concurrently, handle.h:1069-1083)."""
        with self._span("kv.plan_pull"):
            return self._plan_pull_impl(keys, shard)

    def _plan_pull_impl(self, keys: np.ndarray, shard: int):
        rem = None
        loc_map = None
        if self.glob is not None:
            proc_rem = (self.ab.owner[keys] < 0) & \
                (self.ab.cache_slot[shard, keys] < 0)
            if proc_rem.any():
                rem_pos = np.nonzero(proc_rem)[0]
                rem = (rem_pos, keys[rem_pos])
                loc_map = np.nonzero(~proc_rem)[0]
                keys = keys[loc_map]
        cls = []
        if len(keys):
            for cid, pos in self._group_by_class(keys):
                ks = keys[pos]
                cls.append((cid, pos, ks,
                            self._route(ks, shard, record=False)))
        return (rem, loc_map, cls)

    def _pull(self, keys: np.ndarray, shard: int, after=(), plan=None):
        """Returns (groups, n_remote, remote): one gather per length class.
        `remote` is (positions, Future) for process-remote keys served over
        the DCN channel (multi-process only); `after` futures are this
        worker's outstanding remote writes (read-your-writes ordering).
        `plan` is an optional pre-computed `_plan_pull` result (must have
        been revalidated against `topology_version` under the lock)."""
        if plan is None:
            plan = self._plan_pull(keys, shard)
        rem, loc_map, cls = plan
        groups = []
        remote = None
        n_remote = 0
        if rem is not None:
            rem_pos, rem_keys = rem
            fut = self.glob.pull_async(rem_keys, after=after)
            remote = (rem_pos, fut)
            n_remote = len(rem_pos)
        # Multi-class batches: issue every class's gather back-to-back
        # under ONE dispatch-gate hold (ISSUE 16 satellite). Each
        # store.gather re-acquires the (reentrant) gate per program, so
        # without the outer hold a concurrent serve/step dispatcher could
        # interleave between classes and the per-class enqueues would
        # serialize behind it; holding the gate across the loop keeps the
        # enqueue train contiguous. The gate is a leaf lock, so taking it
        # while holding the server lock is in-order (APM004).
        with dispatch_gate():
            for cid, pos, ks, (o_sh, o_sl, c_sh, c_sl, use_c, nr,
                               local) in cls:
                n_remote += nr
                if self.locality is not None:
                    self.locality.record(ks.ravel(), local.ravel())
                o_sl = np.where(use_c, OOB, o_sl).astype(np.int32)
                vals = self.stores[cid].gather(o_sh, o_sl, c_sh, c_sl,
                                               use_c)
                gpos = pos if loc_map is None else loc_map[pos]
                groups.append((cid, gpos, self.value_lengths[ks], vals,
                               len(ks)))
        return groups, n_remote, remote

    def _plan_push_routes(self, keys: np.ndarray, shard: int,
                          is_set: bool = False):
        """The cacheable routing part of `_plan_push`: everything derived
        from the key batch and the tables alone — the PlanCache entry for
        the 'push'/'set' kinds. Value staging is applied per call by
        `_plan_push` (values change every step; routes only change with
        the topology)."""
        rem_pos = loc_pos = None
        kloc = keys
        if self.glob is not None:
            # Set must reach the owner; Push may land in a local replica's
            # delta row (same split as the reference's local attempt)
            if is_set:
                proc_rem = self.ab.owner[keys] < 0
            else:
                proc_rem = (self.ab.owner[keys] < 0) & \
                    (self.ab.cache_slot[shard, keys] < 0)
            if proc_rem.any():
                rem_pos = np.nonzero(proc_rem)[0]
                loc_pos = np.nonzero(~proc_rem)[0]
                kloc = keys[loc_pos]
        cls = []
        if len(kloc):
            for cid, pos in self._group_by_class(kloc):
                ks = kloc[pos]
                cls.append((cid, pos, ks,
                            self._route(ks, shard, write_through=is_set,
                                        record=False)))
        return (rem_pos, loc_pos, cls)

    def _plan_push(self, keys: np.ndarray, vals: np.ndarray, shard: int,
                   is_set: bool = False, routes=None):
        """Routing + staging plan for `_push`: no device dispatch, no side
        effects; same lock-free contract as `_plan_pull`. `routes` is an
        optional pre-computed (possibly plan-cached) `_plan_push_routes`
        result for the same (keys, shard, is_set)."""
        with self._span("kv.plan_push"):
            return self._plan_push_impl(keys, vals, shard, is_set=is_set,
                                        routes=routes)

    def _plan_push_impl(self, keys, vals, shard, is_set=False,
                        routes=None):
        if routes is None:
            routes = self._plan_push_routes(keys, shard, is_set=is_set)
        rem_pos, loc_pos, cls_r = routes
        flat = vals.ndim == 1
        rem = None
        if rem_pos is not None:
            from ..parallel.pm import _offsets, _select_flat
            rem_keys = keys[rem_pos]
            if flat:
                lens = self.value_lengths[keys]
                offs = _offsets(lens)
                rem_flat = _select_flat(vals, offs, lens, rem_pos)
                vals = _select_flat(vals, offs, lens, loc_pos)
            else:
                rem_flat = np.ascontiguousarray(vals[rem_pos]).ravel()
                vals = vals[loc_pos]
            keys = keys[loc_pos]
            rem = (rem_pos, rem_keys, rem_flat)
        cls = []
        for cid, pos, ks, route in cls_r:
            L = self.class_lengths[cid]
            rows = self._flat_parts(keys, vals, pos, L) if flat \
                else vals[pos]
            cls.append((cid, ks, rows, route))
        return (rem, cls)

    def _push(self, keys: np.ndarray, vals: np.ndarray, shard: int,
              is_set: bool = False, after=(), plan=None):
        """Returns (n_remote, futures): futures are outstanding cross-process
        writes (multi-process only; `after` = the worker's earlier write
        futures, chained to preserve per-worker write order). `plan` is an
        optional `_plan_push` result revalidated under the lock."""
        self._prefetch_note(keys)
        if plan is None:
            plan = self._plan_push(keys, vals, shard, is_set=is_set)
        rem, cls = plan
        n_remote = 0
        futures = []
        if rem is not None:
            from ..parallel.pm import _fill_flat, _offsets
            rem_pos, rem_keys, rem_flat = rem
            chain = list(after)
            if is_set:
                # Set invalidates any local replicas of these keys: a
                # kept replica's pending delta would re-add on top of
                # the overwritten value. Flush the delta (ordered
                # BEFORE the set) and drop the replica; reads route to
                # the owner afterwards.
                cs = self.ab.cache_slot[shard, rem_keys]
                has = cs >= 0
                if has.any():
                    hk = np.unique(rem_keys[has])
                    lens_h = self.value_lengths[hk]
                    offs_h = _offsets(lens_h)
                    dflat = np.zeros(offs_h[-1], np.float32)
                    for cid, pos in self._group_by_class(hk):
                        rows = self.stores[cid].read_rows(
                            "delta",
                            np.full(len(pos), shard, np.int32),
                            self.ab.cache_slot[
                                shard, hk[pos]].astype(np.int32))
                        _fill_flat(dflat, offs_h, lens_h, pos,
                                   rows.ravel())
                    self._drop_cross_replicas(hk, shard)
                    chain = chain + [self.glob.write_async(
                        hk, dflat, is_set=False, after=chain)]
            fut = self.glob.write_async(
                rem_keys, rem_flat.astype(np.float32), is_set,
                after=chain)
            if is_set and len(chain) > len(after):
                # the owner keeps serving sync for our dropped replicas
                # until we unsubscribe; do it once the set has landed
                fut = self.glob.unsub_async(hk, after=[fut])
            futures.append(fut)
            if len(self._rw_pending) > 64:
                self._prune_rw_pending()
            self._rw_pending.append((fut, rem_keys))
            n_remote += len(rem_pos)
        for cid, ks, rows, (o_sh, o_sl, c_sh, c_sl, use_c, nr,
                            local) in cls:
            n_remote += nr
            if self.locality is not None:
                self.locality.record(ks.ravel(), local.ravel())
            if is_set:
                # Set writes through to the main copy and refreshes the
                # writer's local replica (store._set_rows docstring)
                self.stores[cid].set_rows(o_sh, o_sl, rows, c_sh, c_sl)
            else:
                if self._dbg_applies is not None:
                    np.add.at(self._dbg_applies, ks, rows[:, 0])
                o_sl = np.where(use_c, OOB, o_sl).astype(np.int32)
                self.stores[cid].scatter_add(o_sh, o_sl, c_sh, c_sl, rows)
        return n_remote, futures

    # -- cross-process service endpoints (called by GlobalPM under _lock) ----

    # full-model reads switch to one whole-pool device->host copy per class
    # instead of a padded device gather: at 5M keys the gather program (and
    # its compile) costs minutes, the pool copy seconds
    _BULK_READ_MIN = 65536

    def _read_owned_flat(self, keys: np.ndarray) -> np.ndarray:
        """Current main-copy values of locally-owned keys (flat concat)."""
        if len(keys) >= self._BULK_READ_MIN:
            return self._read_owned_bulk(keys)
        groups, _ = self._pull_main_only(keys)
        return self._assemble_flat(keys, groups)

    def _read_owned_bulk(self, keys: np.ndarray) -> np.ndarray:
        """Checkpoint/eval/export-scale read: copy each class pool to host
        once, then reorder rows with a vectorized fancy index. Under the
        server lock (re-entrant: `read_main` and the service endpoints
        hold it already): the tier's maintenance worker donates and
        replaces the pool this reads, and residency moves under it."""
        from ..parallel.pm import _fill_flat, _offsets
        lens = self.value_lengths[keys]
        offs = _offsets(lens)
        out = np.empty(offs[-1], dtype=np.float32)
        with self._lock:
            for cid, pos in self._group_by_class(keys):
                ks = keys[pos]
                st = self.stores[cid]
                if st.res is not None:
                    # tiered: read only the REQUESTED rows (cold store
                    # fancy index + one hot-pool-sized overlay readback)
                    # — a full main_host() copy would transiently double
                    # host RAM at the beyond-HBM sizes tiering exists for
                    from ..tier.coldpath import read_main_rows_bulk
                    rows = read_main_rows_bulk(
                        st, self.ab.owner[ks], self.ab.slot[ks])
                else:
                    host = np.asarray(st.main)         # [S, slots, L]
                    rows = host[self.ab.owner[ks], self.ab.slot[ks]]
                _fill_flat(out, offs, lens, pos, rows.ravel())
        return out

    def _plan_cached(self, kind: str, shard: int, keys: np.ndarray,
                     tv: int, compute):
        """The one plan-cache get-or-compute-then-put sequence (shared by
        Worker.pull/push/set and the prefetch staging path, so the
        caching contract lives in one place)."""
        cache = self._plan_cache
        plan = cache.get(kind, shard, keys, tv) \
            if cache is not None else None
        if plan is None:
            plan = compute()
            if cache is not None:
                cache.put(kind, shard, keys, tv, plan)
        return plan

    def _prefetch_note(self, keys: np.ndarray) -> None:
        """Invalidate staged prefetch buffers that intersect a value
        write (caller holds the lock; every write path must pass through
        here BEFORE a reader could miss the write — see
        PrefetchScheduler.note_writes)."""
        if self.prefetch is not None:
            self.prefetch.note_writes(keys)

    def _apply_remote_write(self, keys: np.ndarray, flat: np.ndarray,
                            is_set: bool) -> None:
        """Apply a cross-process push/set to locally-owned main rows."""
        self._prefetch_note(keys)
        flat = np.asarray(flat, dtype=np.float32)
        for cid, pos in self._group_by_class(keys):
            ks = keys[pos]
            L = self.class_lengths[cid]
            rows = self._flat_parts(keys, flat, pos, L)
            o_sh = self.ab.owner[ks].astype(np.int32)
            o_sl = self.ab.slot[ks].astype(np.int32)
            n = len(ks)
            zeros = np.zeros(n, np.int32)
            oob = np.full(n, OOB, np.int32)
            if is_set:
                self.stores[cid].set_rows(o_sh, o_sl, rows, zeros, oob)
            else:
                if self._dbg_applies is not None:
                    np.add.at(self._dbg_applies, ks, rows[:, 0])
                self.stores[cid].scatter_add(o_sh, o_sl, zeros, oob, rows)

    def ensure_local(self, keys: np.ndarray, shard: int) -> None:
        """Make process-remote `keys` locally servable (replicate or adopt
        via the owner's decision) — the fused runners' miss path: apps
        normally signal intent ahead so keys are local by step time; a
        cold miss blocks here once instead of computing on garbage rows.
        No-op in a single process."""
        if self.glob is None:
            return
        with self._lock:
            rem = keys[(self.ab.owner[keys] < 0)
                       & (self.ab.cache_slot[shard, keys] < 0)]
        if len(rem) == 0:
            return
        import time as _time
        rem = np.unique(rem)
        end = int(self._clocks.max()) + 2
        self.sync.intent_end[shard, rem] = np.maximum(
            self.sync.intent_end[shard, rem], end)
        for attempt in range(50):
            self.glob.intent_remote(rem, shard, end)
            # installs are deferred for keys with in-flight remote writes
            # (and capacity-truncated ones get unsubscribed) — retry until
            # everything is servable locally
            with self._lock:
                rem = rem[(self.ab.owner[rem] < 0)
                          & (self.ab.cache_slot[shard, rem] < 0)]
            if len(rem) == 0:
                return
            # a full cache pool frees up as expired replicas drop: drive a
            # full sync round (flush + drop) before retrying
            with self._round_lock:
                self.sync.run_round(all_channels=True)
            _time.sleep(0.005 * (attempt + 1))
        raise RuntimeError(
            f"{len(rem)} keys could not be made local on shard {shard} "
            f"(cache pool full?); first: {rem[:5].tolist()}")

    def _prune_rw_pending(self) -> None:
        """Drop completed remote-write records (caller holds the lock). A
        completed future means the write is applied at its owner, so any
        owner-side read AFTER the prune observes it."""
        self._rw_pending = [(f, k) for f, k in self._rw_pending
                            if not f.done()]

    def _rw_blocked_keys(self):
        """Keys with remote writes recorded since the last prune (caller
        holds the lock); replication installs must skip them."""
        if not self._rw_pending:
            return None
        return np.unique(np.concatenate([k for _, k in self._rw_pending]))

    def _drop_cross_replicas(self, keys: np.ndarray, shard: int) -> None:
        """Drop this shard's replicas of remotely-owned `keys` (metadata +
        channel registry only; the caller handles delta flushing and the
        owner unsubscription). Caller holds the lock."""
        keys = keys[self.ab.cache_slot[shard, keys] >= 0]
        if len(keys) == 0:
            return
        with self._topology_mutation():
            self.sync.replica_discard(keys, shard)
            for _, pos in self._group_by_class(keys):
                self.ab.drop_replicas(keys[pos], shard)
            self.sync.stats.add(replicas_dropped=len(keys))

    def _flush_drop_local_replicas(self, keys: np.ndarray) -> None:
        """Flush pending deltas of all local replicas of `keys` into their
        local main copies and drop the replicas (used before a forced
        cross-process relocation so no delta is lost)."""
        sh_idx, k_idx = np.nonzero(self.ab.cache_slot[:, keys] >= 0)
        if len(k_idx) == 0:
            return
        karr = keys[k_idx].astype(np.int64)
        sarr = sh_idx.astype(np.int32)
        self._sync_replicas(karr, sarr)
        with self._topology_mutation():
            self.sync.replica_discard(karr, sarr)
            for s in np.unique(sarr):
                sk = karr[sarr == s]
                for _, pos in self._group_by_class(sk):
                    self.ab.drop_replicas(sk[pos], int(s))
            self.sync.stats.add(replicas_dropped=len(karr))

    # -- planner ops (called by SyncManager) ---------------------------------

    def _create_replicas(self, keys: np.ndarray, shard: int) -> np.ndarray:
        """Allocate+materialize replicas on `shard`; returns created keys.
        Batched end to end (reference creates replica stubs per key under
        per-key locks, handle.h:484-532; here one allocator batch + one
        device program per length class). A full cache pool truncates the
        batch: surplus keys stay remote — slower, never wrong."""
        with self._lock:
            ab = self.ab
            mask = ~ab.is_local(keys, shard)
            todo = np.unique(keys[mask])
            # replica_create copies from LOCAL main rows; keys a DCN handler
            # relocated away concurrently must not be materialized from them
            todo = todo[ab.owner[todo] >= 0]
            if len(todo) == 0:
                return np.empty(0, dtype=np.int64)
            created = []
            with self._topology_mutation() as tm:
                for cid, pos in self._group_by_class(todo):
                    cs = ab.add_replicas(todo[pos], shard)
                    ks = todo[pos][: len(cs)]
                    if len(ks) < len(pos):  # the class's cache pool is full
                        self._c_truncated(cid, len(pos) - len(ks))
                    if len(ks) == 0:
                        continue
                    c_sl = cs.astype(np.int32)
                    o_sh = ab.owner[ks].astype(np.int32)
                    o_sl = ab.slot[ks].astype(np.int32)
                    c_sh = np.full_like(o_sh, shard)
                    self.stores[cid].replica_create(o_sh, o_sl, c_sh, c_sl)
                    created.append(ks)
                if not created:
                    tm.cancel()  # cache pool full: nothing materialized
            if not created:
                return np.empty(0, dtype=np.int64)
            out = np.concatenate(created)
            if self.tracer is not None:
                from ..utils.stats import REPLICA_SETUP
                self.tracer.record(out, REPLICA_SETUP, shard)
            return out

    def _dirty_replica_mask(self, keys: np.ndarray,
                            shards: np.ndarray) -> np.ndarray:
        """True per (key, holder-shard) replica iff a sync would change
        any bit: an unshipped delta write or a base older than the main
        row (the store-level write epochs; store.py). Cross-process
        replicas (owner remote, no local main row) report their
        delta-dirty flag alone — epochs cannot see the remote owner's
        writes, which is why sync_channel exempts them from the filter;
        here the flag keeps the dirty_fraction gauge honest in
        multi-process runs. Pure host reads — safe without the lock (a
        racing write flips an entry to dirty and is picked up next
        round; a dropped replica reads as clean and is skipped, which
        `_sync_replicas` would do anyway)."""
        out = np.zeros(len(keys), dtype=bool)
        ab = self.ab
        for cid, pos in self._group_by_class(keys):
            ks, ss = keys[pos], shards[pos]
            cs = ab.cache_slot[ss, ks]
            o_sh = ab.owner[ks]
            o_sl = ab.slot[ks]
            st = self.stores[cid]
            d = np.zeros(len(ks), dtype=bool)
            has = np.nonzero(cs >= 0)[0]
            if len(has) == 0:
                continue
            d[has] = st.delta_dirty[ss[has], cs[has]]
            loc = has[o_sl[has] >= 0]
            if len(loc):
                d[loc] |= (st.main_epoch[o_sh[loc], o_sl[loc]]
                           != st.repl_epoch[ss[loc], cs[loc]])
            out[pos] = d
        return out

    def _sync_replicas(self, keys: np.ndarray, shards: np.ndarray,
                       threshold: float = 0.0,
                       compress: bool = False) -> None:
        """Sync replicas given parallel (key, holder-shard) arrays.
        threshold > 0 leaves small-delta replicas out of the round
        (--sys.sync.threshold); drop/quiesce paths pass 0 so no pending
        delta is ever lost. compress=True applies the
        --sys.sync.compress wire format (quantized deltas, EF residual
        parked in the delta row — store._sync_replicas_compressed);
        ONLY the periodic sync_channel rounds pass it. Drop and
        quiesce flushes keep the default: a dropped replica's delta
        row is freed, so a compressed flush there would LOSE its
        parked residual — the exact flush is what bounds the
        compression contract (docs/MEMORY.md). Under the lock this
        does only coordinate revalidation and program ENQUEUE: the
        per-class device programs are dispatched back-to-back (JAX
        dispatch is asynchronous), so device execution overlaps the
        caller's classification of the next channel instead of
        serializing behind the lock."""
        mode = self.opts.sync_compress if compress else "off"
        with self._span("kv.sync_replicas", self._h_sync_replicas,
                        work=self._h_sync_replicas_work), self._lock:
            ab = self.ab
            karr = np.ascontiguousarray(keys, dtype=np.int64)
            sarr = np.ascontiguousarray(shards, dtype=np.int32)
            # a sync refreshes replica bases (and may advance owner rows):
            # staged pull buffers of these keys are no longer what a
            # fresh pull would return
            self._prefetch_note(karr)
            for cid, pos in self._group_by_class(karr):
                ks, ss = karr[pos], sarr[pos]
                r_cs = ab.cache_slot[ss, ks].astype(np.int32)
                o_sh = ab.owner[ks].astype(np.int32)
                o_sl = ab.slot[ks].astype(np.int32)
                # a DCN handler may have dropped a replica or relocated a
                # key away since the caller snapshotted its items; a -1
                # index would WRAP in the device gather/scatter and corrupt
                # unrelated rows, so re-validate under the lock
                ok = (r_cs >= 0) & (o_sl >= 0)
                if not ok.all():
                    ss, r_cs = ss[ok], r_cs[ok]
                    o_sh, o_sl = o_sh[ok], o_sl[ok]
                    if not ok.any():
                        continue
                st = self.stores[cid]
                shipped = st.sync_bytes_shipped
                st.sync_replicas(ss, r_cs, o_sh, o_sl,
                                 threshold=threshold, compress=mode)
                self._c_sync_bytes(cid, st.sync_bytes_shipped - shipped)
                self._c_sync_rows(cid, len(ss))

    def _drop_replicas(self, keys: np.ndarray,
                       shards: np.ndarray) -> None:
        with self._lock:
            # drop only replicas still on record (a DCN handler may have
            # upgraded/dropped some since the caller snapshotted)
            karr = np.ascontiguousarray(keys, dtype=np.int64)
            sarr = np.ascontiguousarray(shards, dtype=np.int32)
            ok = self.ab.cache_slot[sarr, karr] >= 0
            if not ok.any():
                return
            karr, sarr = karr[ok], sarr[ok]
            # flush pending deltas first (base refresh is harmless), then
            # free the slots (reference readAndPotentiallyDropReplica) —
            # grouped per (shard, class), not per key
            self._sync_replicas(karr, sarr)
            with self._topology_mutation():
                for s in np.unique(sarr):
                    sk = karr[sarr == s]
                    for _, pos in self._group_by_class(sk):
                        self.ab.drop_replicas(sk[pos], int(s))
                    if self.tracer is not None:
                        from ..utils.stats import REPLICA_DROP
                        self.tracer.record(sk, REPLICA_DROP, int(s))

    def _relocate(self, moves: List[Tuple[int, int]]) -> int:
        """Move main copies given (key, dest_shard) pairs. Returns the number
        of moves actually performed; see _relocate_to."""
        if not moves:
            return 0
        karr = np.fromiter((k for k, _ in moves), np.int64, len(moves))
        sarr = np.fromiter((s for _, s in moves), np.int32, len(moves))
        return sum(self._relocate_to(karr[sarr == dest], int(dest))
                   for dest in np.unique(sarr))

    def _relocate_to(self, keys: np.ndarray, dest: int) -> int:
        """Move the main copies of `keys` to shard `dest` (the drain path's
        shape: one destination per intent entry). Batched per class: one
        allocator batch + one device program. A move whose destination main
        pool is full is demoted to a replication attempt (the planner's
        graceful-degradation policy, sync.py _register) rather than
        silently dropped."""
        pol = self.policy
        if pol is not None and len(keys) and pol.active("reloc"):
            # ISSUE 18 learned reloc law: predicted move-thrash regret
            # (the plane's `move` outcome — locality 0 at window
            # close) may HOLD the whole batch in place; the keys stay
            # owned where they are and every pull/push reaches the
            # same main row immediately — slower, never wrong.
            # Value-preservation guard: a dest replica's pending delta
            # merges in-kernel AT relocate time, so holding the move
            # is only a bitwise no-op when every dest replica in the
            # batch is verifiably clean (the exact store-epoch mask,
            # never a heuristic); otherwise the heuristic's move
            # proceeds unvetoed.
            if pol.consult("reloc",
                           {"n_moved": len(keys), "n_demoted": 0},
                           len(keys)):
                rk = keys[self.ab.cache_slot[dest, keys] >= 0]
                if len(rk) == 0 or not self._dirty_replica_mask(
                        rk, np.full(len(rk), dest, np.int32)).any():
                    pol.applied("reloc")
                    return 0
                pol.guard_blocked("reloc")
        demoted = np.empty(0, dtype=np.int64)
        n_moved = 0
        with self._span("kv.relocate", self._h_relocate,
                        work=self._h_relocate_work), self._lock:
            ab = self.ab
            # dedup: a duplicate key would double-free its old main slot in
            # relocate_batch (the drain path dedups in Worker.intent, but
            # direct callers may not). Keys a DCN handler relocated to
            # another PROCESS since the caller's classification are skipped
            # (owner < 0): the planner re-requests them cross-process on a
            # later intent drain.
            keys = np.unique(keys)
            keys = keys[(ab.owner[keys] != dest) & (ab.owner[keys] >= 0)]
            if len(keys) == 0:
                return 0
            with self._topology_mutation() as tm:
                for cid, pos in self._group_by_class(keys):
                    ks = keys[pos]
                    moved, old_sh, old_sl, new_sl = \
                        ab.relocate_batch(ks, dest)
                    if len(moved) < len(ks):  # pool full: demote the rest
                        demoted = np.concatenate((demoted, ks[len(moved):]))
                        self._c_demoted(cid, len(ks) - len(moved))
                    if len(moved) == 0:
                        continue
                    # a replica at the destination upgrades to owner: its
                    # pending delta merges in-kernel (rc coords), and its
                    # cache slot is freed
                    cs = ab.cache_slot[dest, moved]
                    has_rep = cs >= 0
                    rc_sh = np.where(has_rep, dest, 0).astype(np.int32)
                    rc_sl = np.where(has_rep, cs, OOB).astype(np.int32)
                    rep_keys = moved[has_rep]
                    if len(rep_keys):
                        self.sync.replica_discard(rep_keys, dest)
                        ab.drop_replicas(rep_keys, dest)
                    self.stores[cid].relocate_rows(
                        old_sh.astype(np.int32), old_sl.astype(np.int32),
                        np.full(len(moved), dest, np.int32),
                        new_sl.astype(np.int32), rc_sh, rc_sl)
                    n_moved += len(moved)
                    if self.tracer is not None:
                        from ..utils.stats import RELOCATE
                        self.tracer.record(moved, RELOCATE, dest)
                if n_moved == 0:
                    tm.cancel()  # whole batch demoted: nothing moved
        if len(demoted):
            created = self._create_replicas(demoted, dest)
            with self._lock:
                self.sync.replica_add(created, dest)
            self.sync.stats.add(replicas_created=len(created))
        wt = self.wtrace
        if wt is not None and (n_moved or len(demoted)):
            # relocation decision as it landed (ISSUE 15): moves plus
            # the pool-full demotions-to-replication — observational,
            # replay lets the candidate policy re-decide
            wt.record_decision("reloc", n_moved, dest=int(dest),
                               demoted=int(len(demoted)))
        dc = self.decisions
        if dc is not None and (n_moved or len(demoted)):
            # ISSUE 17: the same landed move, with features + a
            # post-move-locality outcome window over the keys that
            # actually moved (the deduped batch minus the demotions)
            moved_keys = np.setdiff1d(keys, demoted) if len(demoted) \
                else keys
            dc.record_move(int(dest), n_moved, int(len(demoted)),
                           moved_keys)
        return n_moved

    def precompile(self, intent_keys: Dict[int, int], steps=()) -> int:
        """Compile every program a run can reach before its timed loop
        does: the ONE call an app makes once its server, workers and
        runners stand (the KGE app's `open_run`).

        The planner's bucketed programs (ShardedStore.precompile_planner):
        for each length class `cid` whose keys the application signals
        intent for, `intent_keys[cid]` is the most keys of it one intent
        names: that bounds a relocation and a replica creation; a sync
        ships at most the live replicas of the class, one for each
        shard, cache slot and key. One shard never relocates or
        replicates: none of them runs.

        `steps`: one `(runner, role_keys, aux)` for each KIND of
        fused-step runner the loop drives (runners built alike share
        their programs: one of them stands for all); each compiles its
        per-step variants (DeviceRoutedRunner.precompile). A fourth
        entry is the `score_aux` of a runner that has a score program.

        A tiered server's maintenance programs at the buckets its
        worker can dispatch (TierManager.precompile).

        Returns how many planner programs ran."""
        ran = 0
        if self.num_shards > 1:
            o = self.opts
            variants = sorted({(0.0, "off"),
                               (float(o.sync_threshold), o.sync_compress)})
            with self._lock:
                for cid, n in sorted(intent_keys.items()):
                    st = self.stores[cid]
                    in_class = int((self.ab.key_class == cid).sum())
                    ran += st.precompile_planner(
                        moved=min(n, in_class),
                        synced=self.num_shards * min(st.cache_slots,
                                                     in_class),
                        sync_variants=variants)
        for runner, role_keys, aux, *score_aux in steps:
            runner.precompile(role_keys, aux, *score_aux)
        if self.tier is not None:
            # the maintenance worker's promotion and demotion programs
            ran += self.tier.precompile()
        return ran

    # -- lifecycle -----------------------------------------------------------

    def start_sync_thread(self) -> None:
        """Run sync rounds in the background (reference SyncManager threads,
        coloc_kv_server.h:100-105). Optional: tests drive rounds manually.

        PR 6: the dedicated thread is subsumed by the executor — rounds
        run as a self-rescheduling program on the `sync` stream (one
        round per program, FIFO, resubmitted until stopped), so
        background sync shares the executor's worker pool and shows up
        in its queue/overlap accounting. `_sync_thread` remains the
        started/stopped token the old API exposed (None = stopped)."""
        if self._sync_thread is not None:
            return
        self._sync_stop.clear()
        state = {"last_report": _time.monotonic(), "last_rounds": 0,
                 "fail_streak": 0}
        token = object()
        self._sync_thread = token

        def tick():
            from ..utils import alog
            if self._sync_stop.is_set() or self._sync_thread is not token:
                return
            delay = 0.0
            try:
                if self.fault is not None:
                    # ISSUE 10 injection point: fires BEFORE the round
                    # does any work, so a retried tick re-runs cleanly
                    self.fault.fire("sync.round")
                with self._round_lock:
                    self.sync.run_round()
                state["fail_streak"] = 0
                # periodic report (reference SyncManager 10-second
                # reports, sync_manager.h:482-497)
                rs = self.opts.sync_report_s
                now = _time.monotonic()
                if rs > 0 and now - state["last_report"] >= rs:
                    dr = self.sync.stats.rounds - state["last_rounds"]
                    alog(f"[sync] "
                         f"{dr / (now - state['last_report']):.1f} "
                         f"rounds/s | " + self.sync.report())
                    state["last_report"] = now
                    state["last_rounds"] = self.sync.stats.rounds
            except Exception as e:  # noqa: BLE001 — the loop is
                # IMMORTAL (ISSUE 10): a failed round — injected or
                # real — reschedules with its own capped exponential
                # backoff instead of dying with an error nobody waits
                # on (the pre-PR failure mode: one transient tick
                # failure silently killed background sync forever).
                # Caught here rather than left to the executor's
                # retry policy: the policy's budget is bounded, and a
                # streak one longer than the budget must still not
                # kill the loop — the tier maintenance pass and the
                # periodic checkpointer follow the same pattern.
                state["fail_streak"] += 1
                delay = min(2.0, self.opts.fault_backoff_ms * 1e-3 *
                            (2.0 ** min(state["fail_streak"], 10)))
                if self.fault is not None:
                    self.fault.c_loop_retries.inc()
                alog(f"[sync] background round failed "
                     f"(streak {state['fail_streak']}): "
                     f"{type(e).__name__}: {e} — retrying in "
                     f"{delay * 1e3:.0f} ms")
            if not self._sync_stop.is_set() and \
                    self._sync_thread is token:
                self.exec.submit("sync", tick, label="sync.round",
                                 coalesce_key="sync.round", delay=delay)

        self.exec.submit("sync", tick, label="sync.round",
                         coalesce_key="sync.round")

    def stop_sync_thread(self) -> None:
        if self._sync_thread is None:
            return
        self._sync_stop.set()
        # drain, not join: at most one more queued round observes the
        # stop flag and returns immediately. A round that does NOT
        # drain is wedged (e.g. blocked on a dead remote peer) and
        # still reads through the pools — proceeding into executor
        # close and pool teardown would be a use-after-teardown, so
        # fail-stop loudly instead (the serve-dispatcher discipline,
        # docs/failure_handling.md)
        if not self.exec.drain("sync", timeout=60):
            from ..utils import alog
            alog("[sync] background round failed to drain within 60s "
                 "of stop — wedged mid-round (dead remote peer?)")
            raise RuntimeError(
                "sync round wedged: did not drain within 60s of stop; "
                "refusing to proceed into pool teardown under a live "
                "reader")
        self._sync_thread = None

    def _wb_active_ids(self) -> set:
        """Worker ids that participate in worker barriers: the declared set
        when the Server was built with an explicit num_workers (reference
        Setup(num_keys, num_threads) declares the thread count), else the
        workers registered so far; finalized workers (clock ==
        WORKER_FINISHED) are excluded either way."""
        ids = range(self.max_workers) if self._wb_declared \
            else list(self._workers)  # copy: registration mutates the dict
        return {wid for wid in ids
                if self._clocks[wid] != WORKER_FINISHED}

    def worker_barrier(self, worker_id: int) -> None:
        """Barrier across ALL active worker threads of all processes
        (reference ColoKVWorker::Barrier -> Postoffice::Barrier over the
        worker group): local threads rendezvous first, then one leader per
        process runs the cross-process barrier. A worker that finalizes
        while others wait is excluded (finalize() re-notifies).

        Cross-process contract (same as control.barrier): every process
        must run the same sequence of barrier generations — finalize
        exclusion is process-local, so an app whose ranks retire ALL their
        workers at different times while other ranks still barrier is
        misusing the API (it would equally hang the reference's
        scheduler-counted barriers)."""
        import time as _time

        from ..utils import alog
        with self._wb_cond:
            gen = self._wb_gen  # the generation this arrival joins: while
            # a leader is mid-flight the counter has already advanced, so
            # late arrivals rendezvous in the NEXT generation instead of
            # being absorbed into one they never synchronized with
            self._wb_waiting.add(worker_id)
            next_warn = _time.monotonic() + 30.0
            while True:
                if self._wb_done > gen:
                    err = self._wb_errs.get(gen)
                    if err is not None:  # leader's cross-process failure
                        raise RuntimeError(
                            f"worker barrier generation {gen} failed at "
                            f"the leader") from err
                    return
                if (not self._wb_leading and self._wb_gen == gen
                        and self._wb_waiting >= self._wb_active_ids()):
                    # freeze this generation's membership and open the next
                    self._wb_leading = True
                    self._wb_gen += 1
                    self._wb_waiting = set()
                    break  # this thread leads the global phase
                self._wb_cond.wait(timeout=5.0)
                # stall diagnostic: with declared num_workers, a declared-
                # but-never-created worker hangs the barrier silently —
                # name the absentees (one thread logs per window)
                if (_time.monotonic() >= next_warn
                        and self._wb_gen == gen
                        and worker_id == min(self._wb_waiting, default=-1)):
                    missing = sorted(
                        self._wb_active_ids() - self._wb_waiting)
                    if missing:
                        alog(f"[barrier] worker barrier gen {gen} stalled "
                             f">30s: waiting for worker ids {missing} "
                             f"(declared num_workers counts workers that "
                             f"must barrier or finalize)")
                    next_warn = _time.monotonic() + 30.0
        err = None
        try:
            self.barrier()
        except BaseException as e:  # noqa: BLE001 — followers must see it
            err = e
        with self._wb_cond:
            self._wb_leading = False
            self._wb_done = gen + 1
            if err is not None:
                self._wb_errs[gen] = err
                # prune: followers read their gen's error promptly; only a
                # bounded window is kept
                for g in [g for g in self._wb_errs if g < gen - 8]:
                    del self._wb_errs[g]
            self._wb_cond.notify_all()
        if err is not None:
            raise err

    def barrier(self) -> None:
        """Process barrier. Single-controller: flush dispatch. Multi-host:
        control-plane barrier (parallel/control.py replaces the reference's
        scheduler BARRIER protocol, src/postoffice.cc:149-174)."""
        from ..parallel import control
        # Pause the background sync thread across the cross-host barrier:
        # its rounds dispatch device programs, and the barrier collective
        # must not interleave with them. (Today each process owns its own
        # pools, so sync programs are process-local and the barrier is the
        # only cross-host collective; once pools span hosts, sync rounds
        # themselves must be driven at globally agreed points.)
        was_running = self._sync_thread is not None
        if was_running:
            self.stop_sync_thread()
        with self._span("collective.barrier"):
            self.block()
            if self.glob is not None:
                self.glob.node.barrier()
            else:
                control.barrier()
        if was_running:
            self.start_sync_thread()

    def block(self) -> None:
        # under the server lock: pool buffers are donated+replaced by ops
        # running in other threads, and blocking on a donated buffer raises
        with self._lock, self._span("kv.block", wait=True):
            for s in self.stores:
                # apm-lint: disable=APM002 quiesce point BY DESIGN: the
                # lock must be held across the device wait here, or a
                # racing op donates the very buffer being blocked on
                s.block()

    def dead_nodes(self, max_age_s: float = 10.0) -> list:
        """Peer processes whose heartbeat has gone stale (reference
        Postoffice::GetDeadNodes; requires --sys.heartbeat > 0). With a
        net node attached, its membership plane is the authority."""
        if self.glob is not None:
            return self.glob.node.dead_peers(max_age_s)
        from ..parallel import control
        return control.dead_processes(max_age_s)

    # -- degraded readiness (ISSUE 10; fault/ckpt.py restore_chain) ----------

    def begin_degraded(self, reason: str) -> None:
        """Flip the server into DEGRADED state: the serve plane sheds
        every lookup loudly with ServeDegradedError (session submit AND
        dispatcher batch-serve both check), and readiness reports the
        reason. Set by restore_chain around the chain apply; available
        to operators for any maintenance window where reads must not
        race a state mutation. A plain write — readers are lock-free:
        a lookup that read None just before the flag flips linearizes
        before the guarded mutation begins (nothing has changed yet),
        which is a valid pre-window read."""
        self._degraded_reason = str(reason)

    def end_degraded(self) -> None:
        self._degraded_reason = None

    @property
    def degraded(self) -> bool:
        return self._degraded_reason is not None

    @property
    def degraded_reason(self) -> Optional[str]:
        return self._degraded_reason

    def drive_rounds(self, n: int = 1) -> None:
        """One training step's planner-drive slot (the apps' per-step
        `sync.run_round` loop): inline when no prefetch pipeline, else
        delegated to the pipeline's background thread so planner work —
        relocations, replica churn, and the device-table re-uploads they
        trigger — overlaps the in-flight device step instead of
        serializing after it."""
        with self._span("kv.drive_rounds", self._h_drive,
                        work=self._h_drive_work):
            if self.prefetch is not None:
                self.prefetch.pump(n)
            else:
                for _ in range(n):
                    self.sync.run_round()

    def shutdown(self) -> None:
        """Deterministic teardown (ISSUE 5 satellite). Order matters —
        every closed plane reads through the pools the later steps block
        on, so readers go down strictly before their substrate:

          1. serve plane (stop admitting lookups; dispatcher drains),
             then the stream plane (ingest pump drains; freshness
             controller stops walking sync/replica state)
          2. metrics reporter
          3. prefetch pipeline (staged gathers + delegated rounds)
          4. tier maintenance worker (demotion readbacks)
          5. periodic checkpointer (an in-flight `ckpt` save reads
             through the pools: its stream drains BEFORE teardown —
             ISSUE 10 satellite)
          6. background sync rounds
          7. the unified executor (every producer above is stopped, so
             a well-ordered close cancels nothing; queued stragglers
             finish cancelled rather than dispatching into teardown)
          8. pool quiesce (block) + sync channel executor
          9. stats / trace / span export, registry unhook
         10. cross-process layer

        Idempotent: a second shutdown() is a no-op (each subordinate
        close is idempotent too, so a test that closed a plane manually
        and then shuts the server down stays clean)."""
        if getattr(self, "_shutdown_done", False):
            return
        self._shutdown_done = True
        if self._serve_plane is not None:
            # stop admitting lookups first: the serve dispatcher reads
            # through the same pools the teardown below blocks on
            self._serve_plane.close()
        if self.stream is not None:
            # stream plane next: the ingest pump pushes through the
            # live pools (its `stream` stream drains inside close) and
            # the freshness tick walks sync/replica state
            self.stream.close()
        if self._reporter is not None:
            self._reporter.stop()
            self._reporter = None
        if self.prefetch is not None:
            self.prefetch.close()
        if self.tier is not None:
            self.tier.close()
        if self.ckpt is not None:
            self.ckpt.close()
        self.stop_sync_thread()
        self.exec.close()
        self.block()
        self.sync.close()
        self.write_stats()
        self.write_trace()
        self.write_flight_trace()
        if self.wtrace is not None:
            # final flush + seal AFTER every producer is stopped: the
            # .wtrace on disk is the complete recorded stream
            self.wtrace.close()
        if self.decisions is not None:
            # same ordering rule, and additionally BEFORE store/pool
            # teardown below: close() force-resolves the open outcome
            # windows, whose probes read residency/addressbook state
            self.decisions.close()
        if self.spans is not None:
            self.spans.close()
        if self.flight_recorder is not None:
            self.flight_recorder.close()
        from ..obs import metrics as _obs_metrics
        _obs_metrics.clear_global_registry(self.obs)
        if self.glob is not None:
            self.glob.node.stop_heartbeat()
            self.glob.shutdown()

    def locality_summary(self) -> Dict[str, float]:
        """Aggregate worker op/param locality ratios (reference shutdown
        summary, coloc_kv_server.h:147-157). Device-routed runners count
        inside the step program; their fused gather+scatter contributes to
        both the pull and push aggregates."""
        agg: Dict[str, int] = {}
        for w in self._workers.values():
            for k, v in w.stats.items():
                agg[k] = agg.get(k, 0) + v
        for src in self._locality_sources:
            c = src()
            for kind in ("pull", "push"):
                for unit in ("ops", "params"):
                    agg[f"{kind}_{unit}"] = \
                        agg.get(f"{kind}_{unit}", 0) + c[unit]
                    agg[f"{kind}_{unit}_local"] = \
                        agg.get(f"{kind}_{unit}_local", 0) + \
                        c[f"{unit}_local"]
        out = {}
        for kind in ("pull", "push"):
            for unit in ("ops", "params"):
                tot = agg.get(f"{kind}_{unit}", 0)
                loc = agg.get(f"{kind}_{unit}_local", 0)
                out[f"{kind}_{unit}_local_frac"] = \
                    loc / tot if tot else float("nan")
        return out

    def write_stats(self) -> List[str]:
        """Dump trace/locality files into --sys.stats.out and log the final
        locality + sync summary."""
        from ..utils import alog, verbose_level
        enabled = bool(self.opts.stats_out or self.tracer is not None
                       or self.locality is not None or verbose_level() > 0)
        if enabled:
            summ = self.locality_summary()
            if any(v == v for v in summ.values()):  # any non-nan
                alog("[stats] " + " ".join(f"{k}={v:.3f}" for k, v in
                                           summ.items() if v == v))
            alog("[stats]", self.sync.report())
            if self.prefetch is not None:
                alog("[stats] prefetch: " + " ".join(
                    f"{k}={v}" for k, v in self.prefetch.report().items()))
            if self._plan_cache is not None:
                alog("[stats] plan_cache: " + " ".join(
                    f"{k}={v}" for k, v in self._plan_cache.stats().items()))
            if self.tier is not None:
                alog("[stats] tier: " + " ".join(
                    f"{k}={v}" for k, v in self.tier.report().items()))
        if not self.opts.stats_out:
            return []
        from ..parallel import control
        from ..utils.stats import write_stats
        written = write_stats(self.opts.stats_out, control.process_id(),
                              self.tracer, self.locality)
        if self.obs.enabled:
            # the full telemetry snapshot rides along (apps pass
            # --sys.stats.out; bench embeds the same dict in its JSON)
            import json
            import os
            p = os.path.join(self.opts.stats_out,
                             f"metrics.{control.process_id()}.json")
            with open(p, "w") as f:
                json.dump(self.metrics_snapshot(), f, indent=1,
                          default=float)
            written.append(p)
        return written

    # snapshot sections guaranteed present (possibly empty) in every
    # metrics_snapshot() — the schema-stability contract tests pin
    _SNAPSHOT_SECTIONS = ("kv", "prefetch", "plan_cache", "staging",
                          "sync", "pm", "collective", "fused", "spans",
                          "serve", "tier", "exec", "flight", "slo",
                          "fault", "ckpt", "device", "episode",
                          "wtrace", "replay", "decision", "policy",
                          "net", "stream", "app")

    def metrics_snapshot(self, drain_device: bool = True) -> Dict:
        """One structured, JSON-serializable telemetry dict for this
        process (docs/OBSERVABILITY.md has the metric catalog). Schema:
        `schema_version`, `metrics_enabled`, and the fixed sections in
        `_SNAPSHOT_SECTIONS` — always present, `{}`-valued where the
        subsystem is off or `--sys.metrics 0`. This is the single source
        of truth the pre-existing ad-hoc surfaces (prefetch stats, plan
        cache stats, fused locality counts) are folded into; their old
        accessors remain as views.

        `drain_device=False` skips the fused-runner locality drain (a
        device readback, which syncs the device) — for periodic
        callers; end-of-run callers keep the default.

        schema_version 2 (PR 3): `sync.keys_synced` now counts SHIPPED
        keys (post-dirty-filter; `sync.keys_shipped` is an alias), the
        new `sync.keys_considered` counts examined replicas, and the
        sync section gains `replicas_live`/`dirty_fraction` gauges
        (total + per channel).

        schema_version 3 (PR 4): new `serve` section — the online
        serving plane's qps/latency/queue/shed metrics plus the
        liveness/readiness surface (`serve.ready`, `serve.dead_peers`,
        and the embedded `readiness` detail dict when a ServePlane is
        attached); `{}` when no plane was ever built.

        schema_version 4 (PR 5): new `tier` section — the tiered-
        storage plane's hot-hit rate, promotions/demotions, hot-pool
        occupancy gauges, and the cold-serve latency histogram
        (`tier.cold_serve_s`); `{}` when --sys.tier is off.

        schema_version 5 (PR 6): new always-present `exec` section —
        the unified executor's per-stream queue-depth gauges
        (`exec.queue_depth.<stream>`), the enqueue->dispatch latency
        histogram (`exec.dispatch_wait_s`), program counters, and the
        `exec.overlap_fraction` gauge (fraction of busy executor wall
        time where >= 2 streams ran simultaneously — the
        transfer/compute-overlap measure).

        schema_version 6 (PR 7): new always-present `flight` and `slo`
        sections. `flight` — request-flight tracing (obs/flight.py):
        the per-request breakdown histograms (`queue_s` /
        `batch_wait_s` / `dispatch_s` / `device_s`), the freshness
        probe (`freshness_s`), trace/program counters, the tracer's
        minted/complete/dropped stats, and the executor
        flight-recorder summary (`recorder`, present whenever
        `--sys.crash_dumps` is on). `{}` when `--sys.trace.flight` is
        off and crash dumps are off too. `slo` — the closed-loop
        tail-latency controller (obs/slo.py, `--sys.serve.slo_ms`):
        target/effective-window/P99 gauges, tick/adjustment counters,
        and the bounded recent-adjustment log; `{}` when no SLO target
        is set.

        schema_version 7 (PR 8): the compression plane's gauges
        (ISSUE 8) — `sync.bytes_per_round` (wire bytes the most recent
        round shipped in the --sys.sync.compress format),
        `sync.bytes_shipped` / `sync.bytes_full_equiv` (cumulative
        wire vs full-width-f32-equivalent bytes — their ratio IS the
        compression factor), `sync.ef_residual_norm` (max-abs error-
        feedback residual parked by the last compressed round), and in
        the tier section `tier.cold_bytes_per_row` (actual host bytes
        per cold row: dense store + scale column + parked residuals)
        plus the `tier.ef_resid_rows` / `tier.ef_evicted` residual-map
        health pair.

        schema_version 8 (PR 9): the serve fast-path/tenancy surface
        (ISSUE 9) — `serve.replica_hit_rate` (fraction of coalesced
        batches served lock-free from the read-only replica snapshot),
        `serve.replica_hits_total` / `serve.replica_refreshes_total` /
        `serve.replica_stale_fallbacks_total` /  `serve.replica_rows`,
        per-dispatcher `serve.lane_depth.<i>` gauges, and — once
        tenants are configured — the per-tenant
        `serve.tenant.<name>.{served,shed,rejected}_total` counters.
        The readiness dict gains `dispatchers` /
        `wedged_dispatchers`. All present-but-inert at the default
        knobs (`--sys.serve.dispatchers 1`, no replica, no tenants).

        schema_version 9 (PR 10): always-present `fault` and `ckpt`
        sections (ISSUE 10). `fault` — the injection plane's seed,
        fired-injection totals and per-point eval/fire counts, plus
        the executor error policy's retries / cumulative backoff
        seconds and the watchdog's wedge-flip count; `{}` unless
        `--sys.fault.spec` names points. `ckpt` — the incremental
        checkpoint chain's save/base/delta counters, last link bytes
        and dirty-slot count, cumulative bytes, and — once a
        restore_chain ran on this server — `recovery_s`; `{}` unless a
        periodic checkpointer is attached or a restore ran. The
        readiness dict gains `degraded` (the restore-window shed
        reason, None when healthy) and `wedged_streams`.

        schema_version 10 (PR 12): always-present `device` and
        `episode` sections (ISSUE 14). `device` — the DevicePort's
        accounting: backend name, dispatched-program and quantized
        wire-ingest-row totals (adapm_tpu/device). `episode` —
        episodic-execution counters and prep/commit wall histograms
        (device/episode.py EpisodicRunner); `{}` until a runner is
        constructed.

        schema_version 11 (PR 13): always-present `wtrace` and
        `replay` sections (ISSUE 15). `wtrace` — workload trace
        capture (obs/wtrace.py, `--sys.trace.workload`): event /
        dropped / sampled-batch counters, bytes written, the trace
        path and buffered-event count; `{}` when capture is off (no
        recorder object, zero wtrace.* names). `replay` — populated
        on a server DRIVEN by the offline replay engine
        (adapm_tpu/replay): events replayed/skipped, the replay seed
        and logical speed, and the reads digest the determinism
        contract pins; `{}` everywhere else.

        schema_version 12 (PR 16): the serve section gains the fused
        bag-read counters (ISSUE 16; serve/bags.py) —
        `serve.bag_lookups_total` / `serve.bag_pooled_total` and the
        per-batch dispatch split `serve.bag_fused_total` /
        `serve.bag_hostpool_total` / `serve.bag_replica_hits_total` —
        and the device section gains the measured kernel-cost-table
        accounting (ops/costs.py, `--sys.costs.table`):
        `device.costs_consults_total` / `device.costs_overrides_total`
        / `device.costs_calibrations_total` and the
        `device.costs_entries` gauge, absent until a table is
        attached.

        schema_version 13 (PR 17): always-present `decision` section
        (ISSUE 17; obs/decisions.py, `--sys.trace.decisions`) — the
        decision telemetry plane's event/dropped counters, the
        per-plane regret counters and rates
        (`decision.promoted_never_hit`,
        `decision.replicated_never_read`, `decision.shipped_clean`,
        `decision.regret_rate.<plane>`), and the recorder's
        window-attribution stats (opened/resolved/forced + per-plane
        decided/resolved/regretted tallies); `{}` when capture is off
        (no recorder object, zero decision.* names). The spans section
        gains `spans.dropped` (registered while a SpanTracer exists):
        span-buffer overflow drops, counted loudly instead of silently
        capping at the old hardcoded 1M bound (now
        `--sys.trace.spans.max_events`).

        schema_version 14 (PR 18): always-present `policy` section
        (ISSUE 18; adapm_tpu/policy, `--sys.policy.*`) — the learned
        adaptive-policy plane's consult/veto counters
        (`policy.consults_total`, `policy.applied_total`,
        `policy.guard_vetoes_total`), the shadow A/B tallies
        (`policy.shadow_agree` / `policy.shadow_disagree`), and the
        plane's stats dict (per-plane mode/consults/vetoes/applied/
        guard-blocked/agree/disagree, the loaded artifact path, and
        the serve batch-window close-reason tallies); `{}` when no
        `--sys.policy.file` is set (no PolicyPlane object, zero
        policy.* names).

        schema_version 15 (PR 19): always-present `net` section
        (ISSUE 19; adapm_tpu/net) — the NetPort transport plane's
        frame accounting (`msgs_out/in`, `bytes_out/in`, per-family
        message counts, `retransmits`, `dup_suppressed`,
        `decode_errors`, `dropped_frames`) and the membership plane's
        peer states (`peers_live/dead/left/total`), beat/join/leave
        tallies, and failover record (`failovers`, `failover_s`,
        `promoted_keys`, `lost_keys`); `{}` on single-process and
        legacy-DCN servers (no plane object, zero net.* names —
        metrics_overhead_check.py pins default-off).

        schema_version 16 (PR 20): always-present `stream` section
        (ISSUE 20; adapm_tpu/stream) — the streaming plane's ingest
        accounting (acked-event `cursor`, `events_total` /
        `batches_total` / `acked_events_total` /
        `replayed_events_total`), the trainer's resume/batch/rate
        stats, and — with `--sys.stream.freshness_slo_ms` — the
        FreshnessSLO controller report (effective target, lever
        positions vs their static knobs, adjustment log); `{}` when no
        `--sys.stream.*` knob is set (no plane object, zero stream.*
        names — metrics_overhead_check.py pins default-off).

        schema_version 17 (PR 24): always-present `app` section — the
        apps' own phase histograms (`app.prepare_s` / `app.pass_end_s`,
        KGE `train()`); `{}` until an app loop ran on this server. The
        train-step and serve phase histograms of the same PR land in
        the existing `kv` / `fused` / `serve` sections, and the
        `flight` section loses its four breakdown histograms to
        `serve.*_s`."""
        out: Dict = {"schema_version": 17,
                     "metrics_enabled": bool(self.obs.enabled)}
        for s in self._SNAPSHOT_SECTIONS:
            out[s] = {}
        if not self.obs.enabled:
            return out
        serve_ready = None
        if self._serve_plane is not None:
            # probe readiness ONCE, BEFORE the registry snapshot: the
            # serve.ready/dead_peers gauges then read this result's
            # cache instead of each paying their own dead-peer probe
            # (multi-process, a probe is one coordinator KV read per
            # peer), and the gauges agree with the embedded dict below
            serve_ready = self._serve_plane.health.readiness()
        for sec, vals in self.obs.snapshot().items():
            out.setdefault(sec, {}).update(vals)
        # kv: worker-aggregated op/param counters + the ts=-1 rate
        agg: Dict[str, int] = {}
        with self._lock:
            workers = list(self._workers.values())
        for w in workers:
            for k, v in w.stats.items():
                agg[k] = agg.get(k, 0) + int(v)
        out["kv"].update(agg)
        po = agg.get("pull_ops", 0)
        out["kv"]["local_answer_frac"] = \
            (agg.get("pull_ops_local", 0) / po) if po else None
        if drain_device:
            out["kv"]["locality"] = self.locality_summary()
        if self.prefetch is not None:
            out["prefetch"].update(
                {k: int(v) for k, v in self.prefetch.report().items()})
        if self._plan_cache is not None:
            out["plan_cache"].update(self._plan_cache.stats())
        if self.glob is not None:
            with self.glob._stats_lock:
                out["pm"].update({k: int(v)
                                  for k, v in self.glob.stats.items()})
                out["pm"]["hops"] = [int(h) for h in self.glob.hops]
            if self.glob.coll is not None:
                out["collective"].update(
                    {f"bsp_{k}": int(v)
                     for k, v in self.glob.coll.stats.items()})
        if self.net is not None:
            out["net"].update(self.net.stats())
        if self.stream is not None:
            out["stream"].update(self.stream.stats())
        if self.spans is not None:
            out["spans"].update(self.spans.stats())
        # executor occupancy/overlap summary rides with the registry's
        # exec.* gauges (same numbers, one locked read)
        out["exec"].update(self.exec.stats())
        if self.stores:
            # device-plane accounting (ISSUE 14): the port's own stats
            # dict (incl. the backend name the gauges cannot carry)
            out["device"].update(self.stores[0].port.stats())
        if self.flight is not None:
            out["flight"].update(self.flight.stats())
        if self.flight_recorder is not None:
            out["flight"]["recorder"] = self.flight_recorder.summary()
        if self.wtrace is not None:
            out["wtrace"].update(self.wtrace.stats())
        if self.decisions is not None:
            out["decision"].update(self.decisions.stats())
        if self.policy is not None:
            out["policy"].update(self.policy.stats())
        if self.replay_stats is not None:
            out["replay"].update(self.replay_stats)
        if self._serve_plane is not None and \
                self._serve_plane.slo is not None:
            out["slo"].update(self._serve_plane.slo.report())
        # fault/ckpt (schema v9): populated only while the respective
        # plane exists — the sections stay {} (never absent) otherwise
        if self.fault is not None:
            out["fault"].update(self.fault.stats())
            out["fault"].update(self.exec.fault_stats())
        if self.ckpt is not None:
            out["ckpt"].update(self.ckpt.stats())
        if self._last_recovery_s is not None:
            out["ckpt"]["recovery_s"] = self._last_recovery_s
        if serve_ready is not None:
            # readiness detail rides with the serve.* gauges: dead peers
            # (Server.dead_nodes — detection-only), queue depth/bound,
            # and the human-readable not-ready reasons
            out["serve"]["readiness"] = serve_ready
        return out

    def write_trace(self) -> Optional[str]:
        """Export the span trace (Chrome trace-event JSON, Perfetto-
        loadable) when --sys.trace.spans is on; returns the path. Called
        by shutdown; callable earlier for a mid-run trace."""
        if self.spans is None:
            return None
        import os
        path = self.opts.trace_spans_out or os.path.join(
            self.opts.stats_out or ".",
            f"spans.{self.pid}.trace.json")
        return self.spans.export(path)

    def write_flight_trace(self) -> Optional[str]:
        """Export the request-flight trace (Perfetto flow-event JSON;
        docs/OBSERVABILITY.md "Follow one request") when
        --sys.trace.flight is on; returns the path. Called by shutdown;
        callable earlier for a mid-run export."""
        if self.flight is None:
            return None
        import os
        path = self.opts.trace_flight_out or os.path.join(
            self.opts.stats_out or ".",
            f"flight.{self.pid}.trace.json")
        return self.flight.export(path)

    def wait_sync(self) -> None:
        """Act on all signalled intents and complete a full sync round
        (reference WaitSync, coloc_kv_worker.h:517). Multi-process: the
        round ships cross-process deltas and intent requests; the full
        quiesce protocol is WaitSync -> Barrier -> WaitSync on every
        process (reference test_many_key_operations.cc:375-385)."""
        with self._round_lock:
            self.sync.run_round(force_intents=True, all_channels=True)
        self.block()

    def quiesce(self) -> None:
        wt = self.wtrace
        if wt is not None:
            # recorded at entry so replay re-drives the quiesce at the
            # same point in the op stream (docs/REPLAY.md)
            wt.record_quiesce()
        with self._span("kv.quiesce", self._h_quiesce), self._round_lock:
            self.sync.quiesce()

    def collective_pull(self, keys) -> np.ndarray:
        """BSP pull through the device-collective exchange — EVERY process
        must call this together (parallel/pm.py collective_pull;
        --sys.collective_sync). Returns owner values, flat."""
        assert self.glob is not None, "single process: use Worker.pull"
        return self.glob.collective_pull(keys)

    def collective_push(self, keys, vals) -> None:
        """BSP additive push through the device-collective exchange — same
        collective contract as collective_pull."""
        assert self.glob is not None, "single process: use Worker.push"
        self.glob.collective_push(keys, vals)

    def read_main(self, keys) -> np.ndarray:
        """Debug/test/checkpoint: read current authoritative main-copy
        values (flat concat). Multi-process: remotely-owned keys are read
        from their owner over the DCN channel."""
        keys = np.asarray(keys, dtype=np.int64)
        if self.glob is None:
            with self._lock:
                if len(keys) >= self._BULK_READ_MIN:
                    return self._read_owned_bulk(keys)
                groups, _ = self._pull_main_only(keys)
            return self._assemble_flat(keys, groups)
        from ..parallel.pm import _fill_flat, _offsets
        lens = self.value_lengths[keys]
        offs = _offsets(lens)
        out = np.empty(offs[-1], dtype=np.float32)
        with self._lock:
            owned = self.ab.owner[keys] >= 0
            pos = np.nonzero(owned)[0]
            if len(pos):
                _fill_flat(out, offs, lens, pos,
                           self._read_owned_flat(keys[pos]))
        rem = np.nonzero(~owned)[0]
        if len(rem):
            flat_r, _ = self.glob.request_pull(keys[rem])
            _fill_flat(out, offs, lens, rem, flat_r)
        return out

    def _pull_main_only(self, keys: np.ndarray):
        ab = self.ab
        groups = []
        for cid, pos in self._group_by_class(keys):
            ks = keys[pos]
            o_sh = ab.owner[ks].astype(np.int32)
            o_sl = ab.slot[ks].astype(np.int32)
            n = len(ks)
            vals = self.stores[cid].gather(
                o_sh, o_sl, np.zeros(n, np.int32),
                np.full(n, OOB, np.int32), np.zeros(n, bool))
            groups.append((cid, pos, self.value_lengths[ks], vals, n))
        return groups, 0

    def _assemble_flat(self, keys: np.ndarray, groups,
                       remote=None) -> np.ndarray:
        from ..parallel.pm import _fill_flat, _offsets
        lens = self.value_lengths[keys]
        offs = _offsets(lens)
        out = np.empty(offs[-1], dtype=np.float32)
        for cid, pos, klens, vals, n in groups:
            # one strided/fancy-indexed write per class, never per key
            _fill_flat(out, offs, lens, np.asarray(pos),
                       np.asarray(vals)[:n].ravel())
        if remote is not None:
            rem_pos, fut = remote
            _fill_flat(out, offs, lens, rem_pos, fut.result())
        return out


class Worker:
    """Reference ColoKVWorker (coloc_kv_worker.h). One per logical worker;
    mapped to mesh shard `worker_id % num_shards` (co-location)."""

    def __init__(self, server: Server, worker_id: int):
        self.server = server
        self.worker_id = worker_id
        self.shard = worker_id % server.num_shards
        # seed from the server's clock table so a worker registered after a
        # checkpoint restore resumes at the restored clock instead of
        # regressing it to 0 on its first advance
        self._clock = int(server._clocks[worker_id])
        self._ts = 0
        self._pending: Dict[int, _WaitEntry] = {}
        from .intent import IntentQueue
        self._intent_queue = IntentQueue()
        # outstanding cross-process write futures (read-your-writes: remote
        # pulls are ordered after them, see Server._pull's `after`)
        self._write_futs: List = []
        # locality stats (reference coloc_kv_server.h:147-157)
        self.stats = {"pull_ops": 0, "pull_ops_local": 0,
                      "pull_params": 0, "pull_params_local": 0,
                      "push_ops": 0, "push_ops_local": 0,
                      "push_params": 0, "push_params_local": 0}
        # kv op latency histograms (shared across workers; obs/metrics).
        # None with --sys.metrics 0 so the hot path skips even the
        # perf_counter bracketing.
        if server.obs.enabled:
            self._h_pull = server.obs.histogram("kv.pull_s", shared=True)
            self._h_push = server.obs.histogram("kv.push_s", shared=True)
            self._h_set = server.obs.histogram("kv.set_s", shared=True)
        else:
            self._h_pull = self._h_push = self._h_set = None

    # -- value plumbing ------------------------------------------------------

    def _keys(self, keys) -> np.ndarray:
        return np.ascontiguousarray(np.asarray(keys, dtype=np.int64).ravel())

    def _new_ts(self, entry: _WaitEntry) -> int:
        self._ts += 1
        self._pending[self._ts] = entry
        return self._ts

    # -- API: Pull / Push / Set ----------------------------------------------

    def _live_write_futs(self):
        self._write_futs = [f for f in self._write_futs if not f.done()]
        return list(self._write_futs)

    def _instrumented(self, name: str, h, impl, *args):
        """A worker op inside the phase bracket (Server._span: latency
        histogram `h`, None with --sys.metrics 0), plus its
        single-segment flight under --sys.trace.flight."""
        fl = self.server.flight
        t0 = _time.perf_counter() if fl is not None else 0.0
        try:
            with self.server._span(name, h):
                return impl(*args)
        finally:
            if fl is not None:
                # a plain Worker op is a single-segment flight: one
                # minted id, one slice on the caller's thread
                fl.record_op(name, t0)

    def _cached_push_routes(self, keys: np.ndarray, tv: int, is_set: bool):
        """Route skeleton for push/set through the plan cache (values are
        applied per call; routes only change with the topology)."""
        srv = self.server
        return srv._plan_cached(
            "set" if is_set else "push", self.shard, keys, tv,
            lambda: srv._plan_push_routes(keys, self.shard, is_set=is_set))

    def pull(self, keys, out: Optional[np.ndarray] = None) -> int:
        """Async pull. Returns ts (use wait) or LOCAL=-1 if every key was
        served from this worker's shard (owned or replicated) — in that case
        `out` is already filled when provided.

        Fast path: a batch this worker declared intent for may have been
        pre-gathered by the prefetch pipeline (core/intent.py); the pull
        then consumes the staged device buffers directly — no planning,
        no server lock, no dispatch. Validity (topology unchanged since
        the gather, no intersecting write) was enforced by the pipeline,
        so a staged hit is bit-identical to the pull it replaced."""
        return self._instrumented("kv.pull", self._h_pull,
                                  self._pull_op, keys, out)

    def _pull_op(self, keys, out: Optional[np.ndarray]) -> int:
        keys = self._keys(keys)
        srv = self.server
        wt = srv.wtrace  # bind-once, test-once (APM003 skip-wrapper)
        if wt is not None:
            wt.record_kv("pull", self.worker_id, self._clock, keys)
        if srv.prefetch is not None:
            st = srv.prefetch.take_staged(self, keys)
            if st is not None:
                self.stats["pull_ops"] += 1
                self.stats["pull_params"] += len(keys)
                self.stats["pull_params_local"] += len(keys) - st.n_remote
                entry = _WaitEntry(groups=st.groups, out=out, keys=keys)
                if st.n_remote == 0:
                    self.stats["pull_ops_local"] += 1
                    self._finish_pull(keys, entry)
                    return LOCAL
                return self._new_ts(entry)
        after = self._live_write_futs() if srv.glob is not None else ()
        plan, tv = None, -1
        if srv.opts.optimistic_routing:
            # route + stage outside the lock; revalidate the topology
            # below (reference: per-key lock array lets N worker threads
            # route concurrently, handle.h:1069-1083). Identical batches
            # skip planning entirely via the plan cache.
            tv = srv.topology_version
            plan = srv._plan_cached(
                "pull", self.shard, keys, tv,
                lambda: srv._plan_pull(keys, self.shard))
        with srv._lock:
            if plan is not None and srv.topology_version != tv:
                plan = None  # topology moved underneath us: re-plan
            groups, n_remote, remote = srv._pull(keys, self.shard,
                                                 after=after, plan=plan)
        self.stats["pull_ops"] += 1
        self.stats["pull_params"] += len(keys)
        self.stats["pull_params_local"] += len(keys) - n_remote
        entry = _WaitEntry(groups=groups, out=out, keys=keys, remote=remote)
        if n_remote == 0:
            self.stats["pull_ops_local"] += 1
            self._finish_pull(keys, entry)
            return LOCAL
        return self._new_ts(entry)

    def pull_sync(self, keys) -> np.ndarray:
        """Pull and materialize; returns flat values (or [B, L] when the
        batch is single-class and `reshape` fits)."""
        keys = self._keys(keys)
        ts = self.pull(keys)
        if ts == LOCAL:
            flat = self._last_result
        else:
            flat = self.wait(ts)
        lens = self.server.value_lengths[keys]
        if len(np.unique(lens)) == 1:
            return flat.reshape(len(keys), int(lens[0]))
        return flat

    def _finish_pull(self, keys, entry: _WaitEntry) -> np.ndarray:
        flat = self.server._assemble_flat(keys, entry.groups,
                                          remote=entry.remote)
        if entry.out is not None:
            np.copyto(entry.out.reshape(-1)[: len(flat)], flat)
        self._last_result = flat
        return flat

    def pull_if_local(self, keys, out: Optional[np.ndarray] = None):
        """Pull only if all keys are local (reference PullIfLocal,
        coloc_kv_worker.h:352). Returns (success, values|None)."""
        keys = self._keys(keys)
        srv = self.server
        with srv._lock:
            if not bool(srv.ab.is_local(keys, self.shard).all()):
                return False, None
            groups, _, _ = srv._pull(keys, self.shard)
        entry = _WaitEntry(groups=groups, out=out)
        return True, self._finish_pull(keys, entry)

    def push(self, keys, vals, asynchronous: bool = True) -> int:
        """Additive push (reference Push, coloc_kv_worker.h:120). vals is a
        flat buffer or [B, L]. Returns ts or LOCAL."""
        return self._instrumented("kv.push", self._h_push,
                                  self._push_op, keys, vals)

    def _push_op(self, keys, vals) -> int:
        keys = self._keys(keys)
        vals = np.asarray(vals, dtype=np.float32)
        srv = self.server
        wt = srv.wtrace
        if wt is not None:
            wt.record_kv("push", self.worker_id, self._clock, keys)
        probe = None
        fl = srv.flight  # bind-once, test-once (APM003 skip-wrapper)
        if fl is not None:
            # event-to-servable freshness probe (sampled): push wall
            # time -> first serve read of the key (obs/flight.py);
            # marked visible under the lock once the scatter enqueues
            probe = fl.freshness.note_push(keys)
        after = self._live_write_futs() if srv.glob is not None else ()
        plan, tv = None, -1
        if srv.opts.optimistic_routing:
            tv = srv.topology_version
            plan = srv._plan_push(
                keys, vals, self.shard, is_set=False,
                routes=self._cached_push_routes(keys, tv, is_set=False))
        with srv._lock:
            if plan is not None and srv.topology_version != tv:
                plan = None
            n_remote, futs = srv._push(keys, vals, self.shard,
                                       is_set=False, after=after,
                                       plan=plan)
            if probe is not None:
                fl.freshness.push_visible(probe)
        self.stats["push_ops"] += 1
        self.stats["push_params"] += len(keys)
        self.stats["push_params_local"] += len(keys) - n_remote
        self._write_futs.extend(futs)
        if n_remote == 0:
            self.stats["push_ops_local"] += 1
            return LOCAL
        return self._new_ts(_WaitEntry(is_write=True, futures=futs))

    def staggered_push(self, keys, vals, group_size: int = 100_000) -> int:
        """Push a large key set in groups (reference StaggeredPush,
        coloc_kv_worker.h:556-580: bounds per-request buffering when
        pushing e.g. a whole initial model). Returns the last group's ts."""
        keys = self._keys(keys)
        vals = np.asarray(vals, dtype=np.float32)
        flat = vals.ndim == 1
        if flat:
            cum = np.zeros(len(keys) + 1, dtype=np.int64)
            np.cumsum(self.server.value_lengths[keys], out=cum[1:])
        ts = LOCAL
        for lo in range(0, len(keys), group_size):
            hi = min(lo + group_size, len(keys))
            part = vals[cum[lo]:cum[hi]] if flat else vals[lo:hi]
            ts = self.push(keys[lo:hi], part)
        return ts

    def set(self, keys, vals) -> int:
        """Overwrite values (reference Set: non-additive write)."""
        return self._instrumented("kv.set", self._h_set,
                                  self._set_op, keys, vals)

    def _set_op(self, keys, vals) -> int:
        import contextlib
        keys = self._keys(keys)
        vals = np.asarray(vals, dtype=np.float32)
        srv = self.server
        wt = srv.wtrace
        if wt is not None:
            wt.record_kv("set", self.worker_id, self._clock, keys)
        after = self._live_write_futs() if srv.glob is not None else ()
        # Set may invalidate (consume the delta of) cross-process replicas;
        # that must not interleave with an in-flight sync round's extracted
        # delta (pm.py delta_window; taken BEFORE the server lock)
        dm = srv.glob.delta_window_for(keys) if srv.glob is not None \
            else contextlib.nullcontext()
        plan, tv = None, -1
        if srv.opts.optimistic_routing:
            tv = srv.topology_version
            plan = srv._plan_push(
                keys, vals, self.shard, is_set=True,
                routes=self._cached_push_routes(keys, tv, is_set=True))
        with dm:
            with srv._lock:
                if plan is not None and srv.topology_version != tv:
                    plan = None
                n_remote, futs = srv._push(keys, vals, self.shard,
                                           is_set=True, after=after,
                                           plan=plan)
        self._write_futs.extend(futs)
        if n_remote == 0:
            return LOCAL
        return self._new_ts(_WaitEntry(is_write=True, futures=futs))

    # -- API: waiting ---------------------------------------------------------

    def wait(self, ts: int):
        """Block until op `ts` is complete; for pulls returns/fills values."""
        if ts == LOCAL:
            return getattr(self, "_last_result", None)
        entry = self._pending.pop(ts, None)
        if entry is None:
            return None
        if entry.groups or entry.remote is not None:
            return self._finish_pull(entry.keys, entry)
        # write op: dispatch order serializes programs on the pool buffers,
        # so blocking on the current pools covers this op; cross-process
        # writes complete when their futures resolve
        for f in entry.futures:
            f.result()
        self.server.block()
        return None

    def wait_all(self) -> None:
        for ts in sorted(self._pending.keys()):
            self.wait(ts)

    def is_finished(self, ts: int) -> bool:
        """Non-blocking completion check (reference IsFinished)."""
        if ts == LOCAL or ts not in self._pending:
            return True
        entry = self._pending[ts]
        if not all(f.done() for f in entry.futures):
            return False
        if entry.remote is not None and not entry.remote[1].done():
            return False
        if entry.is_write:
            with self.server._lock:
                return all(s.main.is_ready() and s.delta.is_ready()
                           for s in self.server.stores)
        return all(g[3].is_ready() for g in entry.groups)

    def wait_sync(self) -> None:
        self.server.wait_sync()

    # -- API: intent + clock --------------------------------------------------

    def intent(self, keys, start: int, end: Optional[int] = None) -> None:
        """Declare future access to `keys` in clock window [start, end]
        (reference Intent, coloc_kv_worker.h:380-408; end defaults to
        start). With the prefetch pipeline on, the declaration also
        queues background staging: a later `pull` of exactly this
        (unique, sorted) key batch inside the window can be served from
        a pre-gathered staged buffer."""
        srv = self.server
        with srv._span("kv.intent", srv._h_intent,
                       work=srv._h_intent_work):
            keys = np.unique(self._keys(keys))
            end = start if end is None else end
            wt = srv.wtrace
            if wt is not None:
                wt.record_intent(self.worker_id, self._clock, keys,
                                 int(start), int(end))
            self._intent_queue.push(keys, int(start), int(end))
            if srv.prefetch is not None:
                srv.prefetch.on_intent(self, keys, int(start), int(end))

    def advance_clock(self) -> int:
        srv = self.server
        with srv._span("kv.advance_clock", srv._h_clock,
                       work=srv._h_clock_work):
            self._clock += 1
            srv._clocks[self.worker_id] = self._clock
            wt = srv.wtrace
            if wt is not None:
                wt.record_clock(self.worker_id, self._clock)
        return self._clock

    @property
    def current_clock(self) -> int:
        return self._clock

    # -- API: sampling --------------------------------------------------------

    def prepare_sample(self, n: int, start: Optional[int] = None,
                       end: Optional[int] = None) -> int:
        """Reference PrepareSample (coloc_kv_worker.h:418): announce that this
        worker will sample `n` keys around clock [start, end]."""
        start = self._clock if start is None else start
        end = start if end is None else end
        h = self.server.sampling.prepare(self, n, int(start), int(end))
        wt = self.server.wtrace
        if wt is not None:
            wt.record_sample("prep_sample", self.worker_id, self._clock,
                             h, n, int(start), int(end))
        return h

    def pull_sample(self, handle: int, n: Optional[int] = None):
        """Draw n keys (default: all prepared) from sampling handle; returns
        (keys, values[B, L])."""
        wt = self.server.wtrace
        if wt is not None:
            wt.record_sample("pull_sample", self.worker_id, self._clock,
                             handle, n)
        return self.server.sampling.pull(self, handle, n)

    def pull_sample_keys(self, handle: int, n: Optional[int] = None):
        """Draw n keys without fetching values (for fused steps that gather
        values themselves); locality behavior matches pull_sample."""
        return self.server.sampling.pull_keys(self, handle, n)

    def finish_sample(self, handle: int) -> None:
        wt = self.server.wtrace
        if wt is not None:
            wt.record_sample("finish_sample", self.worker_id,
                             self._clock, handle, None)
        self.server.sampling.finish(self, handle)

    # -- API: lifecycle -------------------------------------------------------

    def barrier(self) -> None:
        """Barrier with every other active worker (all threads, all
        processes) — reference ColoKVWorker::Barrier.

        Note this is an ALL-WORKER rendezvous, not a per-process barrier
        (changed from the pre-r3 semantics): with a declared num_workers,
        every declared worker must eventually barrier or finalize, or the
        barrier stalls (a periodic warning names the absent ids)."""
        self.server.worker_barrier(self.worker_id)

    def begin_setup(self) -> None:
        """Bracket initialization (reference BeginSetup/EndSetup): sync is
        paused so bulk Set/Push of initial values runs at full speed."""
        self.server._in_setup = True

    def end_setup(self) -> None:
        self.server._in_setup = False
        self.server.barrier()

    def finalize(self) -> None:
        """Mark worker finished (reference Finalize): clock to infinity so
        its intents expire and replicas can be dropped."""
        self.wait_all()
        self._clock = WORKER_FINISHED
        self.server._clocks[self.worker_id] = WORKER_FINISHED
        # workers blocked in a barrier must re-evaluate the participant set
        with self.server._wb_cond:
            self.server._wb_cond.notify_all()
