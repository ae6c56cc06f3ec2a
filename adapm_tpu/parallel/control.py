"""Multi-host control plane.

Replaces the reference's scheduler + Van control machinery (ADD_NODE
rendezvous, BARRIER counting, heartbeats — src/van.cc:40-210,
src/postoffice.cc:149-187) with JAX's distributed runtime: the coordinator
service (`jax.distributed.initialize`) plays the scheduler, process ranks
replace node ids, and barriers/aggregations ride the coordinator's gRPC
channel or device collectives. ZeroMQ is gone entirely; data-plane traffic
is XLA collectives over ICI/DCN (see ARCHITECTURE.md).

All primitives degrade to no-ops / local computation in a single-process
run, so the same app code runs on one host or many.

`allreduce` is the replacement for the reference's PS-based scalar/vector
allreduce (`ps_allreduce`, include/utils.h:163-197) used by the apps for
loss/eval aggregation.
"""
from __future__ import annotations

import os
from typing import Optional

import numpy as np

# env names follow the launcher contract (launcher.py), mirroring the
# reference's DMLC_* topology env vars (docs/env.md)
ENV_COORD = "ADAPM_COORDINATOR"       # host:port of process 0
ENV_NUM_PROCS = "ADAPM_NUM_PROCESSES"
ENV_PROC_ID = "ADAPM_PROCESS_ID"


def init_from_env() -> bool:
    """Initialize `jax.distributed` from launcher env vars; returns True if
    a multi-process runtime was set up (reference Postoffice::Start +
    Van ADD_NODE handshake, collapsed into one call). Idempotent: a second
    call (e.g. explicit init_from_env followed by adapm_tpu.setup) is a
    no-op, like the reference's Postoffice::Start start_stage_ guard."""
    coord = os.environ.get(ENV_COORD)
    if not coord:
        return False
    n = int(os.environ[ENV_NUM_PROCS])
    pid = int(os.environ[ENV_PROC_ID])
    if n <= 1:
        return False
    from jax._src import distributed
    if distributed.global_state.client is not None:
        return True  # already joined
    import jax
    # ADAPM_COORD_HEARTBEAT_S (docs/env.md): coordination-service
    # heartbeat timeout override. Unset = jax's own default (100 s in
    # jax 0.9) — production dead-rank detection latency is unchanged.
    # The mp TEST harness sets 300: on an oversubscribed CI host, N
    # ranks x XLA compiles on 1-2 cores can stall a rank's heartbeat
    # past 100 s, which surfaces as a CoordinationService PollForError
    # on the OTHER ranks (observed flake in the mp app tests).
    kw = {}
    hb = int(round(float(os.environ.get("ADAPM_COORD_HEARTBEAT_S", "0"))))
    if hb > 0:
        kw["heartbeat_timeout_seconds"] = hb
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=n, process_id=pid, **kw)
    return True


def num_processes() -> int:
    import jax
    return jax.process_count()


def process_id() -> int:
    import jax
    return jax.process_index()


_barrier_lock = __import__("threading").Lock()


def barrier(name: str = "adapm") -> None:
    """Global process barrier (reference Postoffice::Barrier via the
    scheduler, src/postoffice.cc:149-174). Rides the coordinator's gRPC
    barrier — no device collectives, so it is safe to call from planner /
    background threads while device programs are in flight.

    Ordering contract: barriers of the SAME `name` must be invoked in
    the same order on every process (sequence ids are per name, so
    differently-named barriers interleaved differently across ranks
    still pair correctly — the calling-site tag IS part of the id;
    ADVICE r5 #4). Same-name barriers from two local threads racing each
    other remain undefined — one caller thread per name.

    Wait time is observed into the `collective.barrier_wait_s`
    histogram of the process-default metrics registry (the Server
    registers it; no-op before a Server exists or with --sys.metrics
    0)."""
    import jax
    if jax.process_count() == 1:
        return
    from ..obs.metrics import timed
    with timed("collective.barrier_wait_s"):
        from jax._src import distributed
        client = distributed.global_state.client
        if client is not None:
            # id allocation is atomic; the wait happens outside the lock
            # so concurrent barriers from different threads both progress
            seq = _next_seq(f"barrier/{name}")
            # generous timeout: a peer may be inside a cold XLA compile
            client.wait_at_barrier(f"adapm/{name}/{seq}", 600_000)
            return
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)


_hb_stop = None


def start_heartbeat(interval_s: float = 2.0) -> None:
    """Publish a periodic liveness beat to the coordinator's KV store
    (reference Van heartbeats, src/van.cc:515-527; off by default there
    and opt-in here). No-op in a single process."""
    import threading
    import time as _time

    import jax
    if jax.process_count() == 1:
        return
    global _hb_stop
    if _hb_stop is not None:
        return
    from jax._src import distributed
    client = distributed.global_state.client
    pid = jax.process_index()
    _hb_stop = threading.Event()

    def loop():
        while True:
            client.key_value_set(f"adapm/hb/{pid}",
                                 str(_time.time()), allow_overwrite=True)
            if _hb_stop.wait(interval_s):
                return

    # apm-lint: disable=APM004 process-level heartbeat with no Server
    # (hence no executor) in scope: the control plane outlives and
    # predates any Server on this rank (launcher-adjacent, like dcn.py)
    threading.Thread(target=loop, daemon=True,
                     name="adapm-heartbeat").start()


def stop_heartbeat() -> None:
    global _hb_stop
    if _hb_stop is not None:
        _hb_stop.set()
        _hb_stop = None


def dead_processes(max_age_s: float = 10.0) -> list:
    """Process ids whose last heartbeat is older than `max_age_s` (the
    reference's Postoffice::GetDeadNodes, src/postoffice.cc:202-221).
    Processes that never published a beat are not reported (heartbeats
    are opt-in, as in the reference). Empty in a single process."""
    import time as _time

    import jax
    if jax.process_count() == 1:
        return []
    from jax._src import distributed
    client = distributed.global_state.client
    now = _time.time()
    dead = []
    for p in range(jax.process_count()):
        if p == jax.process_index():
            continue
        try:
            beat = client.key_value_try_get(f"adapm/hb/{p}")
        except Exception:  # noqa: BLE001 — no beat published yet
            continue
        if now - float(beat) > max_age_s:
            dead.append(p)
    return dead


_seqs: dict = {}
_inflight: set = set()


def _next_seq(counter: str) -> int:
    """Allocate the next sequence number for `counter`. PER-NAME
    counters (ADVICE r5 #4): the calling-site tag is part of every KV
    key and barrier id, so two DIFFERENT sites invoked in different
    orders on different ranks still pair correctly instead of
    cross-wiring each other's keys into a 600 s timeout. (The pre-r6
    shared allocator made ANY cross-rank reordering — even of unrelated
    primitives — a silent deadlock.)"""
    with _barrier_lock:
        _seqs[counter] = _seqs.get(counter, 0) + 1
        return _seqs[counter]


class _exclusive:
    """Immediate-error guard for the single-caller-thread contract: two
    local threads driving the same collective site concurrently (e.g. a
    sync-report thread racing an eval's allreduce) would interleave
    sequence allocation differently across ranks — an undebuggable
    cross-wire that used to surface as a 600 s timeout. Raise at the
    second local entry instead (ADVICE r5 #4)."""

    def __init__(self, site: str):
        self.site = site

    def __enter__(self):
        with _barrier_lock:
            if self.site in _inflight:
                raise RuntimeError(
                    f"concurrent collective call on site {self.site!r}: "
                    "allreduce/broadcast/_kv_gather are single-caller-"
                    "thread per site — give each calling site its own "
                    "`site` tag, or serialize the callers")
            _inflight.add(self.site)
        return self

    def __exit__(self, *exc):
        with _barrier_lock:
            _inflight.discard(self.site)


def _pack_array(arr: np.ndarray) -> bytes:
    """Frame an array payload with its dtype/shape so the receiver can
    verify instead of reinterpreting bytes (ADVICE r5 #2: a root/
    non-root template mismatch with coincidentally equal nbytes — e.g.
    int64 vs float64 — used to silently decode garbage). ':' separators
    on purpose: dtype.str itself BEGINS with '|' for byte-order-free
    dtypes (bool, uint8, bytes), so '|' cannot delimit it."""
    head = f"{arr.dtype.str}:{','.join(map(str, arr.shape))}:"
    return head.encode() + arr.tobytes()


def _unpack_array(raw: bytes, expect: np.ndarray,
                  what: str) -> np.ndarray:
    """Decode a _pack_array payload, failing loudly on any dtype/shape/
    size mismatch against the receiver's template."""
    sep1 = raw.index(b":")
    sep2 = raw.index(b":", sep1 + 1)
    dt = np.dtype(raw[:sep1].decode())
    shape_s = raw[sep1 + 1:sep2].decode()
    shape = tuple(int(x) for x in shape_s.split(",")) if shape_s else ()
    if dt != expect.dtype or shape != expect.shape:
        raise ValueError(
            f"{what}: payload is {dt}{list(shape)} but this rank's "
            f"template is {expect.dtype}{list(expect.shape)} — ranks "
            "disagree on the collective's array layout")
    body = raw[sep2 + 1:]
    if len(body) != expect.nbytes:
        raise ValueError(
            f"{what}: payload carries {len(body)} bytes for a "
            f"{expect.nbytes}-byte template")
    # .copy(): frombuffer over bytes is read-only; callers may mutate
    return np.frombuffer(body, dtype=dt).reshape(shape).copy()


def _kv_gather(tag: str, payload: bytes, timeout_ms: int = 600_000):
    """Publish this rank's payload under a fresh sequence id and collect
    every rank's, via the coordinator KV store. HOST-ONLY on purpose: a
    device collective here can deadlock the PM — a rank parked inside
    the collective holds its device queue, its DCN serve threads then
    cannot dispatch the gather a PEER's in-flight read needs, and that
    peer never reaches the collective (observed: guard.expired()'s
    allreduce vs a peer still inside the chunked eval's filter
    correction). The control plane must ride the control plane
    (reference: ps_allreduce goes through the PS/scheduler, never the
    data path — include/utils.h:163-197).

    Contract (ADVICE r5 #4): ONE caller thread per `tag`, invoking in
    the same order on every process. Sequence ids are per tag, so
    different tags may interleave freely across ranks; a second local
    thread entering the same tag concurrently raises immediately
    (_exclusive) instead of cross-wiring KV keys into a 600 s timeout.
    Keys are deleted after a trailing barrier so the store does not grow
    with call count. Requires the coordination client (callers fall back
    to multihost_utils without one — e.g. multi-host TPU auto-topology
    launched outside the ADAPM env)."""
    import base64
    import jax
    from jax._src import distributed
    client = distributed.global_state.client
    with _exclusive(f"kv/{tag}"):
        seq = _next_seq(f"kv/{tag}")
        pid = jax.process_index()
        key = f"adapm/{tag}/{seq}"
        client.key_value_set(f"{key}/{pid}",
                             base64.b64encode(payload).decode())
        parts = []
        for p in range(jax.process_count()):
            s = client.blocking_key_value_get(f"{key}/{p}", timeout_ms)
            parts.append(base64.b64decode(s))
        # all ranks have read everything once all have passed this
        # barrier; deleting one's own key is then race-free
        barrier(f"{tag}-gc")
        client.key_value_delete(f"{key}/{pid}")
        return parts


def _kv_client():
    from jax._src import distributed
    return distributed.global_state.client


def allreduce(values, op: str = "sum", site: str = "ar") -> np.ndarray:
    """Sum/mean/max a host scalar or vector across processes (reference
    ps_allreduce, include/utils.h:163-197: push to a shared PS key, barrier,
    pull). Single-process: returns the input unchanged (as float64 array).
    Rides the coordinator KV store — never a device collective (see
    _kv_gather for why that would deadlock).

    Contract: ONE caller thread per `site`, same per-site call order on
    every process (see _kv_gather). Callers that may run concurrently
    with other allreduces (e.g. a guard thread vs an eval merge) must
    pass their own `site` tag. Payloads are dtype/shape-framed, so ranks
    disagreeing on the array layout fail loudly instead of silently
    reinterpreting bytes (ADVICE r5 #2)."""
    import jax
    if op not in ("sum", "mean", "max"):
        raise ValueError(f"unknown allreduce op {op}")
    arr = np.atleast_1d(np.asarray(values, dtype=np.float64))
    if jax.process_count() == 1:
        return arr
    from ..obs.metrics import timed
    with timed("collective.allreduce_wait_s"):
        if _kv_client() is None:  # no coordination service: last resort
            from jax.experimental import multihost_utils
            gathered = np.asarray(multihost_utils.process_allgather(arr))
        else:
            parts = _kv_gather(site, _pack_array(arr))
            gathered = np.stack([
                _unpack_array(b, arr, f"allreduce[{site}] rank {p}")
                for p, b in enumerate(parts)])
    return {"sum": gathered.sum, "mean": gathered.mean,
            "max": gathered.max}[op](axis=0)


def broadcast(values, root: int = 0, site: str = "bc") -> np.ndarray:
    """Broadcast a host array from `root` to all processes (worker-0
    initialization across hosts). KV-store transport, same rationale and
    single-caller-thread-per-site contract as allreduce; one
    root-published key, O(P) coordinator messages. The payload carries
    the root's dtype/shape, so a root/non-root template mismatch — even
    with coincidentally equal nbytes (int64 vs float64) — raises instead
    of silently reinterpreting bytes (ADVICE r5 #2)."""
    import base64
    import jax
    arr = np.asarray(values)
    if jax.process_count() == 1:
        return arr
    client = _kv_client()
    if client is None:  # no coordination service: last resort only
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.broadcast_one_to_all(
            arr, is_source=jax.process_index() == root)).copy()
    with _exclusive(f"kv/{site}"):
        seq = _next_seq(f"kv/{site}")
        key = f"adapm/{site}/{seq}"
        if jax.process_index() == root:
            client.key_value_set(
                key, base64.b64encode(_pack_array(arr)).decode())
        raw = base64.b64decode(client.blocking_key_value_get(key, 600_000))
        barrier(f"{site}-gc")
        if jax.process_index() == root:
            client.key_value_delete(key)
    return _unpack_array(raw, arr, f"broadcast[{site}]")


# NOTE: an earlier draft exposed intent_summary_allgather here for a
# planner-side global interest exchange. The implemented design keeps the
# reference's shape instead: interest is tracked OWNER-side as per-key
# process bitmasks updated by intent/unsub traffic (parallel/pm.py
# GlobalPM.interest — the node_intent sets of sync_manager.h:182, 571,
# 644), so no allgather is needed on the decision path.
