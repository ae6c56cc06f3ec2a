"""Online serving plane (ISSUE 4 tentpole; ISSUE 9 read fast path +
tenancy): the PM as a query-servable store.

Training built the store; this layer reads it under load. The pieces
(each in its own module, docs/SERVING.md has the user guide):

  - `admission` — bounded request lanes with backpressure + deadlines
    (reject loudly, never hang), per-tenant token-bucket quotas and
    priority classes (shed low-priority first under pressure,
    fair-share the batch budget across tenants);
  - `batcher`  — micro-batching coalescer: concurrent lookups merge
    into one deduplicated key batch dispatched as a single fused gather
    per length class through the routing-plan cache, on
    `--sys.serve.dispatchers` sharded dispatcher streams;
  - `replica`  — the read-only hot-row fast path: an epoch-versioned
    snapshot served WITHOUT the server lock, bit-identical by write-
    epoch validation (`--sys.serve.replica_rows`);
  - `session`  — the client API: `ServeSession.lookup(keys,
    deadline_ms)`, snapshot-consistent and bit-identical to a plain
    `Worker.pull`, including read-your-writes for clients that push;
  - `health`   — liveness/readiness folding `Server.dead_nodes`,
    per-dispatcher wedge detection, and queue depth into
    `metrics_snapshot()` (serve section).

Quickstart::

    from adapm_tpu.serve import ServePlane
    plane = ServePlane(server)            # knobs from server.opts
    plane.configure_tenant("gold", priority=1)          # optional QoS
    plane.configure_tenant("bronze", priority=0, qps=500)
    sess = plane.session(tenant="gold")   # one per client thread
    vals = sess.lookup(keys, deadline_ms=50)
    plane.close()                         # or rely on server.shutdown()
"""
from __future__ import annotations

from .admission import (AdmissionQueue, DeadlineExceededError,  # noqa: F401
                        LookupRequest, ServeDegradedError,
                        ServeOverloadError, TenantState)
from .batcher import LookupBatcher  # noqa: F401
from .health import HealthMonitor  # noqa: F401
from .replica import ServeReplica  # noqa: F401
from .session import ServeSession  # noqa: F401


class ServePlane:
    """Assembles lanes + batcher + replica + health over one Server and
    owns their lifecycle. One live plane per Server (the serve.* metrics
    namespace is single-registration; a plane closed and rebuilt on the
    same server reuses it — gauges rebind to the new plane)."""

    def __init__(self, server, opts=None, shard: int = 0,
                 start: bool = True, dead_nodes_fn=None,
                 dead_node_max_age_s: float = 10.0):
        opts = opts if opts is not None else server.opts
        opts.validate_serve()  # fail loudly on bad knobs, even when the
        # options object was hand-built rather than parsed
        if getattr(server, "_serve_plane", None) is not None:
            raise RuntimeError(
                "one live ServePlane per Server: close() the existing "
                "plane first")
        self.server = server
        self.opts = opts
        self.queue = AdmissionQueue(opts.serve_queue, registry=server.obs,
                                    lanes=max(1, opts.serve_dispatchers),
                                    lockorder=getattr(
                                        opts, "lint_lockorder", False))
        self.batcher = LookupBatcher(server, opts, self.queue, shard=shard)
        # read-only serve replica (ISSUE 9 tentpole a; serve/replica.py):
        # only with rows budgeted — unset, every lookup takes the exact
        # locked path and the replica metrics stay present-but-inert
        self.replica = None
        if opts.serve_replica_rows > 0:
            self.replica = ServeReplica(server, opts,
                                        registry=server.obs)
            self.batcher.replica = self.replica
        self.health = HealthMonitor(self, max_age_s=dead_node_max_age_s,
                                    dead_nodes_fn=dead_nodes_fn)
        # SLO autopilot (obs/slo.py, ISSUE 7): only with a target set —
        # unset, no controller exists and the static max_wait_us knob
        # path is untouched (the module is not even imported)
        self.slo = None
        if opts.serve_slo_ms > 0:
            from ..config import parse_class_targets
            from ..obs.slo import SLOController
            # per-priority-class overrides (ISSUE 20 satellite;
            # `--sys.serve.slo_ms 20,1=5`): validated at parse time,
            # re-parsed here into {priority: target_ms}
            cls = parse_class_targets(opts.serve_slo_ms,
                                      opts.serve_slo_class,
                                      flag="--sys.serve.slo_ms")
            self.slo = SLOController(server, self.batcher,
                                     target_ms=opts.serve_slo_ms,
                                     class_targets=cls)
        server._serve_plane = self
        if start:
            self.start()

    def start(self) -> None:
        self.batcher.start()
        if self.slo is not None:
            self.slo.start()

    def configure_tenant(self, name: str, priority: int = 0,
                         qps: float = 0.0, burst=None) -> TenantState:
        """Create or update a tenant's admission policy (token-bucket
        quota + priority class; serve/admission.py). Idempotent —
        reconfiguring a live tenant adjusts its policy in place."""
        return self.queue.configure_tenant(name, priority=priority,
                                           qps=qps, burst=burst)

    def session(self, worker=None, tenant=None,
                priority=None) -> ServeSession:
        """A client handle (one per client thread; cheap). Pass the
        client's `Worker` for cross-process read-your-writes ordering;
        `tenant`/`priority` bind the session to an admission class
        (docs/SERVING.md "Read fast path & tenancy")."""
        return ServeSession(self, worker=worker, tenant=tenant,
                            priority=priority)

    def precompile_bags(self, sizes, cid: int = 0,
                        pooling: str = "sum") -> int:
        """Compile every fused bag program a coalesced batch of `sizes`
        ((member positions, bags) pairs) can need, before traffic
        (`LookupBatcher.precompile_bags`; docs/SERVING.md "Bag
        reads"). Returns how many programs ran."""
        return self.batcher.precompile_bags(sizes, cid=cid,
                                            pooling=pooling)

    def close(self) -> None:
        """Stop the dispatchers and fail-stop queued requests.
        Idempotent; also called by `Server.shutdown()`."""
        if self.slo is not None:
            # stop the control loop before the dispatchers: a tick that
            # already sits queued on the `slo` stream sees _closed and
            # exits (executor close cancels it outright)
            self.slo.close()
        if self.replica is not None:
            # the refresh program reads through the pools like a
            # dispatcher drain: quiesce it before teardown proceeds
            self.replica.close()
        self.batcher.stop()
        if getattr(self.server, "_serve_plane", None) is self:
            self.server._serve_plane = None

    def __enter__(self) -> "ServePlane":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
