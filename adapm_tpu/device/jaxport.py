"""JaxDevicePort: the shipping DevicePort over jax/XLA (ISSUE 14).

Every jitted data-plane program the parameter manager dispatches lives
HERE — moved from core/store.py, tier/coldpath.py, tier/promote.py and
ops/dequant.py, bit-for-bit unchanged — together with the donation-aware
pool allocation and the program constructors the fused-step and
collective layers use. Programs are module-level so the
jit cache is shared across stores and port instances; the port wraps
each dispatch in the process-wide sharded-dispatch gate
(docs/EXECUTOR.md) so per-device enqueue orders stay identical under
concurrent callers.

Padding convention (unchanged): index entries carrying `OOB` are
dropped by scatters (mode="drop") and zero-filled by gathers
(mode="fill"). A negative index would WRAP on device — only large
positive out-of-range values are safe sentinels (docs/MEMORY.md).
"""
from __future__ import annotations

from functools import lru_cache, partial, wraps

import jax
import jax.numpy as jnp
import numpy as np

from ..exec import dispatch_gate
from .port import DevicePort

# THE sharded-dispatch gate (adapm_tpu/exec, docs/EXECUTOR.md): every
# sharded program dispatched by the port funnels through this one
# process-wide mutex, so programs land on every device of the set in a
# single global order. Reentrant and held for the ENQUEUE only (JAX
# dispatch is asynchronous).
_GATE = dispatch_gate()

# Out-of-range slot index for padding / masked entries: dropped by
# scatters (mode="drop"), zero-filled by gathers (mode="fill").
OOB = np.int32(2**31 - 2)

# largest finite fp16 value: the compression wire formats clip to this
# before any f16 cast (values/scales beyond it would cast to inf and
# poison the EF loop with inf/NaN) — shared with tier/quant.py, whose
# host transforms must match the device programs bitwise
F16_MAX = 65504.0


# ---------------------------------------------------------------------------
# jitted data-plane programs (module level: jit cache shared process-wide)
# ---------------------------------------------------------------------------

def _scoped(name: str):
    """Trace the program's body under `jax.named_scope(name)`: a stable
    name on its operations in a device trace (compile-time metadata;
    nothing at run time). PERF.md section 3 lists the names."""
    def wrap(fn):
        @wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


@jax.jit
@_scoped("adapm_gather")
def _gather(main, cache, delta, o_shard, o_slot, c_shard, c_slot, use_cache):
    """Pull: main rows for owner-served keys, cache+delta for replica-served
    keys (o_slot is OOB for the latter to avoid pointless remote traffic)."""
    m = main.at[o_shard, o_slot].get(mode="fill", fill_value=0)
    c = (cache.at[c_shard, c_slot].get(mode="fill", fill_value=0)
         + delta.at[c_shard, c_slot].get(mode="fill", fill_value=0))
    return jnp.where(use_cache[:, None], c, m)


def _pool_rows(rows, seg, out, pooling):
    """Reduce gathered member rows into per-bag vectors (inlined by the
    _gather_pool* programs). Sum accumulates in BATCH ORDER — the same
    order `np.add.at` applies on host (core/tier/coldpath.py contract),
    so a fused pooled read is bit-identical to host-pooling the same
    gathered rows. Mean divides the batch-order sum by the member count
    once (single fp division; the host twin divides identically).
    Padding members carry seg=OOB and drop from both scatters."""
    summed = out.at[seg].add(rows, mode="drop")
    if pooling == "sum":
        return summed
    cnt = jnp.zeros(out.shape[0], rows.dtype).at[seg].add(
        jnp.ones(seg.shape[0], rows.dtype), mode="drop")
    return jnp.where(cnt[:, None] > 0, summed / cnt[:, None],
                     jnp.zeros_like(summed))


@partial(jax.jit, static_argnames=("nbags", "pooling"))
@_scoped("adapm_gather_pool")
def _gather_pool(main, cache, delta, o_shard, o_slot, c_shard, c_slot,
                 use_cache, seg, *, nbags, pooling):
    """Fused embedding-bag read (ISSUE 16): `_gather`'s member-row read
    followed by the in-program segment reduction — one dispatch per
    (length class, pooling) instead of gather + host pool. The zeroed
    `[nbags, L]` rows the members reduce into are made HERE: as an
    operand they were 4-67 MB of host zeros uploaded with every dispatch
    (0.7 ms of a mean DLRM request, 1.9 of the largest: PERF.md section
    6, PR 37). Nothing is donated, so the family contributes empty
    entries to APM005's auto-derived donation map by construction."""
    m = main.at[o_shard, o_slot].get(mode="fill", fill_value=0)
    c = (cache.at[c_shard, c_slot].get(mode="fill", fill_value=0)
         + delta.at[c_shard, c_slot].get(mode="fill", fill_value=0))
    rows = jnp.where(use_cache[:, None], c, m)
    out = jnp.zeros((nbags, rows.shape[1]), rows.dtype)
    return _pool_rows(rows, seg, out, pooling)


@partial(jax.jit, donate_argnums=(0, 1))
@_scoped("adapm_scatter_add")
def _scatter_add(main, delta, o_shard, o_slot, d_shard, d_slot, vals):
    """Push: each row routed either to main (owner path; d_slot=OOB) or to a
    local replica's delta row (o_slot=OOB). Duplicate keys accumulate."""
    main = main.at[o_shard, o_slot].add(vals, mode="drop")
    delta = delta.at[d_shard, d_slot].add(vals, mode="drop")
    return main, delta


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _set_rows(main, cache, delta, o_shard, o_slot, vals, c_shard, c_slot):
    """Set: overwrite the main copy; refresh the writer's local replica (if
    any) and clear its pending delta so a local read observes the set value."""
    main = main.at[o_shard, o_slot].set(vals, mode="drop")
    cache = cache.at[c_shard, c_slot].set(vals, mode="drop")
    delta = delta.at[c_shard, c_slot].set(jnp.zeros_like(vals), mode="drop")
    return main, cache, delta


@partial(jax.jit, donate_argnums=(1, 2))
def _replica_create(main, cache, delta, o_shard, o_slot, c_shard, c_slot):
    """Materialize replicas: copy current main rows into cache slots and zero
    their deltas (reference registerNewIntentsForKeyUnsafe + first refresh,
    handle.h:484-532, 776-840 — one program, since the single-controller
    planner creates replicas synchronously)."""
    rows = main.at[o_shard, o_slot].get(mode="fill", fill_value=0)
    cache = cache.at[c_shard, c_slot].set(rows, mode="drop")
    delta = delta.at[c_shard, c_slot].set(jnp.zeros_like(rows), mode="drop")
    return cache, delta


@partial(jax.jit, donate_argnums=(0, 1, 2))
@_scoped("adapm_sync_replicas")
def _sync_replicas(main, cache, delta, r_shard, r_cslot, o_shard, o_slot):
    """One sync round over a batch of replicas (reference SyncManager
    startSync/ProcessSyncMessage, sync_manager.h:291-382, 553-799): extract
    deltas -> merge into owners (scatter-add; multiple replicas of one key
    all land) -> gather fresh values -> refresh bases, clear deltas."""
    dvals = delta.at[r_shard, r_cslot].get(mode="fill", fill_value=0)
    main = main.at[o_shard, o_slot].add(dvals, mode="drop")
    fresh = main.at[o_shard, o_slot].get(mode="fill", fill_value=0)
    cache = cache.at[r_shard, r_cslot].set(fresh, mode="drop")
    delta = delta.at[r_shard, r_cslot].set(jnp.zeros_like(fresh), mode="drop")
    return main, cache, delta


@partial(jax.jit, donate_argnums=(0, 1, 2), static_argnames=("mode",))
@_scoped("adapm_sync_replicas")
def _sync_replicas_compressed(main, cache, delta, r_shard, r_cslot,
                              o_shard, o_slot, threshold, *, mode):
    """_sync_replicas shipping QUANTIZED deltas with per-key error
    feedback (--sys.sync.compress; ISSUE 8 tentpole, half b). The wire
    transform is applied in-program: the owner merges what a receiver
    would reconstruct from the fp16 / int8+fp16-scale payload — half /
    quarter the future-DCN bytes per round — and the quantization
    remainder is PARKED IN THE REPLICA'S DELTA ROW instead of zeroed
    (the EF-SGD residual loop): it rides into the next shipped round,
    so the main copy's long-run sum stays unbiased and a replica read
    (cache + delta = fresh + residual) keeps read-your-writes to
    within half a grid step. Sub-grid residuals of replicas that go
    CLEAN are flushed exactly by the drop/quiesce paths, which bypass
    compression (core/kv.py _sync_replicas). threshold composes like
    _sync_replicas_thresholded: held rows keep their full delta.
    Returns (main, cache, delta, max-abs parked residual) — the norm
    feeds the sync.ef_residual_norm gauge without a blocking readback
    (converted lazily at snapshot time)."""
    dvals = delta.at[r_shard, r_cslot].get(mode="fill", fill_value=0)
    ship = jnp.max(jnp.abs(dvals), axis=1) >= threshold
    # overflow guard (must match quant.py's host twins bitwise): a
    # delta beyond the fp16 range would cast to inf, merge an inf into
    # the owner row FOREVER and park a -inf residual — clip to the
    # format's max instead; the clipped excess rides the residual and
    # ships over subsequent rounds (the EF loop absorbs saturation the
    # same way it absorbs rounding)
    if mode == "fp16":
        shipped = jnp.clip(dvals, -F16_MAX, F16_MAX).astype(
            jnp.float16).astype(dvals.dtype)
    else:  # int8, symmetric per-row scale rounded through the f16 wire
        s = jnp.clip(jnp.max(jnp.abs(dvals), axis=1) / 127.0,
                     0.0, F16_MAX).astype(jnp.float16).astype(dvals.dtype)
        safe = jnp.where(s > 0, s, 1.0)
        q = jnp.clip(jnp.round(dvals / safe[:, None]), -127, 127)
        shipped = q.astype(jnp.int8).astype(dvals.dtype) * s[:, None]
    resid = dvals - shipped
    rs = jnp.where(ship, r_cslot, OOB)
    osl = jnp.where(ship, o_slot, OOB)
    main = main.at[o_shard, osl].add(shipped, mode="drop")
    fresh = main.at[o_shard, osl].get(mode="fill", fill_value=0)
    cache = cache.at[r_shard, rs].set(fresh, mode="drop")
    new_delta = jnp.where(ship[:, None], resid, dvals)
    delta = delta.at[r_shard, r_cslot].set(new_delta, mode="drop")
    resid_norm = jnp.max(jnp.where(ship[:, None], jnp.abs(resid), 0.0))
    return main, cache, delta, resid_norm


@partial(jax.jit, donate_argnums=(0, 1, 2))
@_scoped("adapm_sync_replicas")
def _sync_replicas_thresholded(main, cache, delta, r_shard, r_cslot,
                               o_shard, o_slot, threshold):
    """_sync_replicas with the reference's sync threshold
    (--sys.sync.threshold, handle.h:601-662, sync_manager.h:805-814): a
    replica whose pending delta is small (max-abs below threshold) is left
    out of the round entirely — no owner merge, no refresh — so tiny updates
    keep accumulating locally instead of paying sync traffic. The delta is
    never lost: it ships in a later round once it grows, or unconditionally
    on drop/quiesce."""
    dvals = delta.at[r_shard, r_cslot].get(mode="fill", fill_value=0)
    ship = jnp.max(jnp.abs(dvals), axis=1) >= threshold
    r_cslot = jnp.where(ship, r_cslot, OOB)
    o_slot = jnp.where(ship, o_slot, OOB)
    main = main.at[o_shard, o_slot].add(dvals, mode="drop")
    fresh = main.at[o_shard, o_slot].get(mode="fill", fill_value=0)
    cache = cache.at[r_shard, r_cslot].set(fresh, mode="drop")
    delta = delta.at[r_shard, r_cslot].set(jnp.zeros_like(fresh), mode="drop")
    return main, cache, delta


@jax.jit
def _read_rows_at(arr, sh, sl):
    return arr.at[sh, sl].get(mode="fill", fill_value=0)


@partial(jax.jit, donate_argnums=(0, 1))
def _install_rows(cache, delta, c_shard, c_slot, vals):
    """Install replica base rows received from a remote owner: set the base,
    zero the pending delta (cross-process replica creation; the local-owner
    twin is _replica_create)."""
    cache = cache.at[c_shard, c_slot].set(vals, mode="drop")
    delta = delta.at[c_shard, c_slot].set(jnp.zeros_like(vals), mode="drop")
    return cache, delta


@partial(jax.jit, donate_argnums=(0, 1))
def _refresh_after_sync(cache, delta, c_shard, c_slot, fresh, shipped):
    """Finish a cross-process sync round: install the owner's fresh value as
    the new base and subtract exactly the shipped delta (pushes that landed
    between extraction and refresh stay pending). Readers see base+delta
    throughout, so a local value never dips below what this worker already
    pushed — the moral equivalent of the reference keeping `val` intact and
    only advancing `sync_state` (handle.h:601-662)."""
    cache = cache.at[c_shard, c_slot].set(fresh, mode="drop")
    delta = delta.at[c_shard, c_slot].add(-shipped, mode="drop")
    return cache, delta


@partial(jax.jit, donate_argnums=(0, 1))
@_scoped("adapm_relocate")
def _relocate(main, delta, old_shard, old_slot, new_shard, new_slot,
              rc_shard, rc_slot):
    """Relocation: move rows old->new; if the destination shard held a
    replica, merge its pending delta (replica->owner upgrade, reference
    refreshUpgradeReplicaUnsafe handle.h:776-840). All gathers happen before
    all scatters, so intra-batch slot reuse is safe."""
    rows = main.at[old_shard, old_slot].get(mode="fill", fill_value=0)
    rows = rows + delta.at[rc_shard, rc_slot].get(mode="fill", fill_value=0)
    main = main.at[new_shard, new_slot].set(rows, mode="drop")
    delta = delta.at[rc_shard, rc_slot].set(jnp.zeros_like(rows), mode="drop")
    return main, delta


@jax.jit
@_scoped("adapm_route_patch")
def _patch_routes(place, cache_row, patch):
    """The fused step's two routing tables (ops/fused.py DeviceRouter:
    a key's place word and the worker shard's cache row) with the
    entries of `patch` set: int32 [3, n], its rows the keys and their
    place and cache-row values; a padding key is out of bounds and
    dropped. THE CALLER PROMISES that the keys ascend and
    none repeats, padding included, and the scatter is told so: on a
    v5e it then compiles in 0.3 s a width where it took 8 (25.5 M keys)
    and runs 22% faster an entry, to the same tables (PERF.md section
    6, PR 40). A false promise is undefined behaviour: the one caller,
    `DeviceRouter._patch_operand`, keeps it (the keys are
    `_changed_keys`' `np.unique`, the padding counts up from
    `num_keys`), and tests/test_route_patch.py holds both halves. The
    tables are not donated: a step in flight keeps the buffers it was
    dispatched with."""
    keys = patch[0]
    return tuple(t.at[keys].set(v, mode="drop", indices_are_sorted=True,
                                unique_indices=True)
                 for t, v in zip((place, cache_row), patch[1:]))


# ---------------------------------------------------------------------------
# tiered cold-path programs (host-supplied row overrides + refresh halves)
# ---------------------------------------------------------------------------


@jax.jit
def _gather_cold(main, cache, delta, o_shard, o_row, c_shard, c_slot,
                 use_cache, cold_vals, use_cold):
    """`_gather` with a host-supplied row override: entries whose owner
    row is cold read `cold_vals` (bit-exact select — `jnp.where`, never
    `+ 0`: addition maps -0.0 to +0.0)."""
    m = main.at[o_shard, o_row].get(mode="fill", fill_value=0)
    m = jnp.where(use_cold[:, None], cold_vals, m)
    c = (cache.at[c_shard, c_slot].get(mode="fill", fill_value=0)
         + delta.at[c_shard, c_slot].get(mode="fill", fill_value=0))
    return jnp.where(use_cache[:, None], c, m)


@partial(jax.jit, static_argnames=("nbags", "pooling"))
@_scoped("adapm_gather_pool_cold")
def _gather_pool_cold(main, cache, delta, o_shard, o_row, c_shard,
                      c_slot, use_cache, cold_vals, use_cold, seg,
                      *, nbags, pooling):
    """`_gather_pool` with `_gather_cold`'s host-supplied row override
    for cold owner members; the zeroed `[nbags, L]` rows are made here,
    as `_gather_pool` makes them."""
    m = main.at[o_shard, o_row].get(mode="fill", fill_value=0)
    m = jnp.where(use_cold[:, None], cold_vals, m)
    c = (cache.at[c_shard, c_slot].get(mode="fill", fill_value=0)
         + delta.at[c_shard, c_slot].get(mode="fill", fill_value=0))
    rows = jnp.where(use_cache[:, None], c, m)
    out = jnp.zeros((nbags, rows.shape[1]), rows.dtype)
    return _pool_rows(rows, seg, out, pooling)


@partial(jax.jit, donate_argnums=(0,))
def _clear_rows(arr, sh, sl):
    """Zero rows (relocation's replica-delta consume on the host path)."""
    return arr.at[sh, sl].set(
        jnp.zeros((sh.shape[0], arr.shape[-1]), arr.dtype), mode="drop")


@partial(jax.jit, donate_argnums=(0, 1))
def _install_cache_rows(cache, delta, c_shard, c_slot, vals):
    """Set replica bases to `vals` and zero their deltas (the cold
    sync's refresh half; same program shape as _install_rows but
    without the cross-process tracking semantics)."""
    cache = cache.at[c_shard, c_slot].set(vals, mode="drop")
    delta = delta.at[c_shard, c_slot].set(jnp.zeros_like(vals), mode="drop")
    return cache, delta


@partial(jax.jit, donate_argnums=(0, 1))
def _install_cache_rows_resid(cache, delta, c_shard, c_slot, vals, resid):
    """Compressed cold-owner sync refresh: install the fresh base and
    PARK the quantization residual in the delta row instead of zeroing
    it (the EF loop's host twin of _sync_replicas_compressed)."""
    cache = cache.at[c_shard, c_slot].set(vals, mode="drop")
    delta = delta.at[c_shard, c_slot].set(resid, mode="drop")
    return cache, delta


# ---------------------------------------------------------------------------
# wire-row ingest (Tensor Casting co-design; host twins in tier/quant.py)
# ---------------------------------------------------------------------------


@jax.jit
def _gather_cold_fp16(main, cache, delta, o_shard, o_row, c_shard,
                      c_slot, use_cache, cold_q, use_cold):
    """_gather with an fp16 wire override for cold owner rows
    (cold_q: [b, L] f16). The f16->f32 convert is exact — fp16 cold
    rows read the same bits everywhere."""
    m = main.at[o_shard, o_row].get(mode="fill", fill_value=0)
    m = jnp.where(use_cold[:, None], cold_q.astype(main.dtype), m)
    c = (cache.at[c_shard, c_slot].get(mode="fill", fill_value=0)
         + delta.at[c_shard, c_slot].get(mode="fill", fill_value=0))
    return jnp.where(use_cache[:, None], c, m)


@jax.jit
def _gather_cold_int8(main, cache, delta, o_shard, o_row, c_shard,
                      c_slot, use_cache, cold_q, cold_scale, use_cold):
    """_gather with an int8+per-row-scale wire override for cold
    owner rows (cold_q: [b, L] i8, cold_scale: [b] f32)."""
    m = main.at[o_shard, o_row].get(mode="fill", fill_value=0)
    deq = cold_q.astype(main.dtype) * cold_scale[:, None]
    m = jnp.where(use_cold[:, None], deq, m)
    c = (cache.at[c_shard, c_slot].get(mode="fill", fill_value=0)
         + delta.at[c_shard, c_slot].get(mode="fill", fill_value=0))
    return jnp.where(use_cache[:, None], c, m)


@partial(jax.jit, static_argnames=("pooling",))
def _gather_pool_cold_fp16(main, cache, delta, o_shard, o_row, c_shard,
                           c_slot, use_cache, cold_q, use_cold, seg,
                           out, *, pooling):
    """Bag read over fp16 wire cold rows: dequant + pooling fused."""
    m = main.at[o_shard, o_row].get(mode="fill", fill_value=0)
    m = jnp.where(use_cold[:, None], cold_q.astype(main.dtype), m)
    c = (cache.at[c_shard, c_slot].get(mode="fill", fill_value=0)
         + delta.at[c_shard, c_slot].get(mode="fill", fill_value=0))
    rows = jnp.where(use_cache[:, None], c, m)
    return _pool_rows(rows, seg, out, pooling)


@partial(jax.jit, static_argnames=("pooling",))
def _gather_pool_cold_int8(main, cache, delta, o_shard, o_row, c_shard,
                           c_slot, use_cache, cold_q, cold_scale,
                           use_cold, seg, out, *, pooling):
    """Bag read over int8+scale wire cold rows."""
    m = main.at[o_shard, o_row].get(mode="fill", fill_value=0)
    deq = cold_q.astype(main.dtype) * cold_scale[:, None]
    m = jnp.where(use_cold[:, None], deq, m)
    c = (cache.at[c_shard, c_slot].get(mode="fill", fill_value=0)
         + delta.at[c_shard, c_slot].get(mode="fill", fill_value=0))
    rows = jnp.where(use_cache[:, None], c, m)
    return _pool_rows(rows, seg, out, pooling)


@partial(jax.jit, donate_argnums=(0,))
def _write_main_rows(main, sh, row, vals):
    """Install host rows into the hot pool (promotion upload; padding
    rows carry OOB and are dropped)."""
    return main.at[sh, row].set(vals, mode="drop")


@partial(jax.jit, donate_argnums=(0,))
def _write_main_rows_fp16(main, sh, row, qvals):
    """Promotion upload, fp16 wire: dequantize fused into the donated
    hot-pool scatter (padding rows carry OOB and drop)."""
    return main.at[sh, row].set(qvals.astype(main.dtype), mode="drop")


@partial(jax.jit, donate_argnums=(0,))
def _write_main_rows_int8(main, sh, row, qvals, scales):
    """Promotion upload, int8 wire (scales: [b] f32 per-row)."""
    vals = qvals.astype(main.dtype) * scales[:, None]
    return main.at[sh, row].set(vals, mode="drop")


@lru_cache(maxsize=64)
def _zeros_program(shape, dtype, sharding):
    """Pool allocation: one zero-fill program per (shape, dtype,
    sharding), compiled with the pool's sharding as its OUTPUT sharding
    so no device ever holds more than its own shard."""
    return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sharding)


# ---------------------------------------------------------------------------


class JaxDevicePort(DevicePort):
    """The jax/XLA DevicePort (see port.py for the contract). Stateless
    beyond accounting: the jit caches are module-level, so any number of
    port instances share compiled programs."""

    name = "jax"

    def __init__(self):
        # lock-free liveness-grade counters (the store.gathers
        # convention): a racing increment may be lost; these feed the
        # `device` snapshot section + idle guards, not billing
        self.programs = 0
        self.wire_ingest_rows = 0

    def stats(self) -> dict:
        return {"backend": self.name,
                "programs_total": int(self.programs),
                "wire_ingest_rows_total": int(self.wire_ingest_rows)}

    # -- data-plane programs -------------------------------------------------

    def gather(self, main, cache, delta, o_shard, o_slot, c_shard,
               c_slot, use_cache):
        self.programs += 1
        with _GATE:
            return _gather(main, cache, delta, o_shard, o_slot,
                           c_shard, c_slot, use_cache)

    def gather_pool(self, main, cache, delta, o_shard, o_slot, c_shard,
                    c_slot, use_cache, seg, out, pooling="sum"):
        self.programs += 1
        with _GATE:
            # `out` fixes the result's shape; its zeros stay on the host
            return _gather_pool(main, cache, delta, o_shard, o_slot,
                                c_shard, c_slot, use_cache, seg,
                                nbags=out.shape[0], pooling=pooling)

    def scatter_add(self, main, delta, o_shard, o_slot, d_shard,
                    d_slot, vals):
        self.programs += 1
        with _GATE:
            return _scatter_add(main, delta, o_shard, o_slot, d_shard,
                                d_slot, vals)

    def set_rows(self, main, cache, delta, o_shard, o_slot, vals,
                 c_shard, c_slot):
        self.programs += 1
        with _GATE:
            return _set_rows(main, cache, delta, o_shard, o_slot, vals,
                             c_shard, c_slot)

    def replica_create(self, main, cache, delta, o_shard, o_slot,
                       c_shard, c_slot):
        self.programs += 1
        with _GATE:
            return _replica_create(main, cache, delta, o_shard, o_slot,
                                   c_shard, c_slot)

    def sync_replicas(self, main, cache, delta, r_shard, r_cslot,
                      o_shard, o_slot, threshold: float = 0.0,
                      compress: str = "off"):
        # one single-program helper per variant: the donated pool args
        # must not be mentioned after a donating call in the same
        # function scope (adapm-lint APM005 reasons lexically)
        self.programs += 1
        if compress != "off":
            return self._sync_compressed(main, cache, delta, r_shard,
                                         r_cslot, o_shard, o_slot,
                                         threshold, compress)
        if threshold > 0.0:
            return self._sync_thresholded(main, cache, delta, r_shard,
                                          r_cslot, o_shard, o_slot,
                                          threshold)
        return self._sync_plain(main, cache, delta, r_shard, r_cslot,
                                o_shard, o_slot)

    @staticmethod
    def _sync_compressed(main, cache, delta, r_shard, r_cslot, o_shard,
                         o_slot, threshold, compress):
        thr = jnp.asarray(threshold, main.dtype)
        with _GATE:
            return _sync_replicas_compressed(main, cache, delta,
                                             r_shard, r_cslot, o_shard,
                                             o_slot, thr, mode=compress)

    @staticmethod
    def _sync_thresholded(main, cache, delta, r_shard, r_cslot,
                          o_shard, o_slot, threshold):
        thr = jnp.asarray(threshold, main.dtype)
        with _GATE:
            return _sync_replicas_thresholded(main, cache, delta,
                                              r_shard, r_cslot,
                                              o_shard, o_slot, thr)

    @staticmethod
    def _sync_plain(main, cache, delta, r_shard, r_cslot, o_shard,
                    o_slot):
        with _GATE:
            return _sync_replicas(main, cache, delta, r_shard, r_cslot,
                                  o_shard, o_slot)

    def read_rows_at(self, arr, sh, sl):
        self.programs += 1
        with _GATE:
            return _read_rows_at(arr, sh, sl)

    def install_rows(self, cache, delta, c_shard, c_slot, vals):
        self.programs += 1
        with _GATE:
            return _install_rows(cache, delta, c_shard, c_slot, vals)

    def refresh_after_sync(self, cache, delta, c_shard, c_slot, fresh,
                           shipped):
        self.programs += 1
        with _GATE:
            return _refresh_after_sync(cache, delta, c_shard, c_slot,
                                       fresh, shipped)

    def relocate(self, main, delta, old_shard, old_slot, new_shard,
                 new_slot, rc_shard, rc_slot):
        self.programs += 1
        with _GATE:
            return _relocate(main, delta, old_shard, old_slot,
                             new_shard, new_slot, rc_shard, rc_slot)

    def patch_routes(self, place, cache_row, patch):
        self.programs += 1
        with _GATE:
            return _patch_routes(place, cache_row, patch)

    # -- tiered cold path + wire ingest --------------------------------------

    def gather_cold(self, main, cache, delta, o_shard, o_row, c_shard,
                    c_slot, use_cache, cold_vals, use_cold):
        self.programs += 1
        with _GATE:
            return _gather_cold(main, cache, delta, o_shard, o_row,
                                c_shard, c_slot, use_cache, cold_vals,
                                use_cold)

    def gather_cold_wire(self, mode: str, main, cache, delta, o_shard,
                         o_row, c_shard, c_slot, use_cache, cold_q,
                         cold_scale, use_cold):
        self.programs += 1
        # count REAL wire rows (use_cold marks them): the padded bucket
        # is mostly zeros and would inflate the gauge by the padding
        # factor
        self.wire_ingest_rows += int(np.count_nonzero(
            np.asarray(use_cold)))
        with _GATE:
            if mode == "fp16":
                return _gather_cold_fp16(main, cache, delta, o_shard,
                                         o_row, c_shard, c_slot,
                                         use_cache, cold_q, use_cold)
            return _gather_cold_int8(main, cache, delta, o_shard,
                                     o_row, c_shard, c_slot, use_cache,
                                     cold_q, cold_scale, use_cold)

    def gather_pool_cold(self, main, cache, delta, o_shard, o_row,
                         c_shard, c_slot, use_cache, cold_vals,
                         use_cold, seg, out, pooling="sum"):
        self.programs += 1
        with _GATE:
            # `out` fixes the result's shape; its zeros stay on the host
            return _gather_pool_cold(main, cache, delta, o_shard,
                                     o_row, c_shard, c_slot, use_cache,
                                     cold_vals, use_cold, seg,
                                     nbags=out.shape[0], pooling=pooling)

    def gather_pool_cold_wire(self, mode: str, main, cache, delta,
                              o_shard, o_row, c_shard, c_slot,
                              use_cache, cold_q, cold_scale, use_cold,
                              seg, out, pooling="sum"):
        self.programs += 1
        # real wire rows only, same convention as gather_cold_wire
        self.wire_ingest_rows += int(np.count_nonzero(
            np.asarray(use_cold)))
        with _GATE:
            if mode == "fp16":
                return _gather_pool_cold_fp16(
                    main, cache, delta, o_shard, o_row, c_shard,
                    c_slot, use_cache, cold_q, use_cold, seg, out,
                    pooling=pooling)
            return _gather_pool_cold_int8(
                main, cache, delta, o_shard, o_row, c_shard, c_slot,
                use_cache, cold_q, cold_scale, use_cold, seg, out,
                pooling=pooling)

    def write_main_rows(self, main, sh, row, vals):
        self.programs += 1
        with _GATE:
            return _write_main_rows(main, sh, row, vals)

    def write_main_rows_wire(self, mode: str, main, sh, row, qvals,
                             scales=None):
        self.programs += 1
        # real wire rows only (padding rows carry OOB and drop)
        self.wire_ingest_rows += int(np.count_nonzero(
            np.asarray(row) != OOB))
        if mode == "fp16":
            return self._write_wire_fp16(main, sh, row, qvals)
        return self._write_wire_int8(main, sh, row, qvals, scales)

    @staticmethod
    def _write_wire_fp16(main, sh, row, qvals):
        with _GATE:
            return _write_main_rows_fp16(main, sh, row, qvals)

    @staticmethod
    def _write_wire_int8(main, sh, row, qvals, scales):
        with _GATE:
            return _write_main_rows_int8(main, sh, row, qvals, scales)

    def clear_rows(self, arr, sh, sl):
        self.programs += 1
        with _GATE:
            return _clear_rows(arr, sh, sl)

    def install_cache_rows(self, cache, delta, c_shard, c_slot, vals,
                           resid=None):
        self.programs += 1
        if resid is None:
            return self._install_cache_plain(cache, delta, c_shard,
                                             c_slot, vals)
        return self._install_cache_resid(cache, delta, c_shard, c_slot,
                                         vals, resid)

    @staticmethod
    def _install_cache_plain(cache, delta, c_shard, c_slot, vals):
        with _GATE:
            return _install_cache_rows(cache, delta, c_shard, c_slot,
                                       vals)

    @staticmethod
    def _install_cache_resid(cache, delta, c_shard, c_slot, vals,
                             resid):
        with _GATE:
            return _install_cache_rows_resid(cache, delta, c_shard,
                                             c_slot, vals, resid)

    # -- buffer allocation / transfer ----------------------------------------

    def alloc_pool(self, shape, dtype, sharding):
        # allocated IN the sharding: every device zero-fills only its
        # own [1, slots, L] shard. A device_put of jnp.zeros(shape)
        # would materialize the whole pool on device 0 first.
        self.programs += 1
        with _GATE:
            return _zeros_program(tuple(shape), np.dtype(dtype),
                                  sharding)()

    def install_pool(self, arr, sharding):
        # a host array goes to the devices shard by shard (jax slices
        # it on the host), so no device stages the whole pool
        return jax.device_put(arr, sharding)

    def put_replicated(self, arr, sharding):
        # numpy in, asynchronous device_put out — the staging rule
        # (parallel/mesh.py put_replicated)
        return jax.device_put(np.asarray(arr), sharding)

    def put_single(self, arr, device):
        return jax.device_put(arr, device)

    # -- program construction ------------------------------------------------

    def compile(self, fn, **jit_kwargs):
        return jax.jit(fn, **jit_kwargs)

    def compile_collective(self, fn, mesh, in_specs, out_specs,
                           check_vma: bool = True, **jit_kwargs):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs,
                                     check_vma=check_vma), **jit_kwargs)
