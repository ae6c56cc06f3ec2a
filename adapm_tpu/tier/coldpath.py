"""The correct-but-slow cold path: serving main-row operations whose
rows live in the host cold store.

Every function here is the tiered twin of a `ShardedStore` device
program and preserves its BIT-EXACT semantics (the tentpole contract):

  - reads select the cold row's bits verbatim (`jnp.where` merge, never
    `+ 0` — addition maps -0.0 to +0.0);
  - additive writes are single f32 adds on either side (IEEE f32
    addition is deterministic; in-batch duplicates accumulate in batch
    order on both the XLA scatter and `np.add.at`);
  - a replica sync against a cold owner extracts the delta (device
    readback), merges on host, and installs the post-merge value as the
    new base with a zeroed delta — the same extract → merge-all →
    refresh-all ordering as the fused device program.

Callers hold the server lock (the residency discipline, residency.py);
the readbacks these paths pay ARE the cold tier's cost — misses are
served correctly and queued for promotion so repeated access turns hot.

Since ISSUE 8 the cold store may be QUANTIZED (--sys.tier.cold_dtype;
tier/quant.py): every access below goes through the `store.coldq`
surface, whose fp32 mode is a bit-identical raw-array passthrough (the
pre-PR pin) and whose fp16/int8 modes follow the error-compensated
contract in docs/MEMORY.md — the visible value of a cold row is its
dequantized stored value, identical through the dequant-fused device
gather (ops/dequant.py) and the host read paths here.

Since ISSUE 14 every device program below dispatches through the
store's DevicePort (adapm_tpu/device) — the cold-override gather, the
dequant-fused wire gathers, and the refresh installs are port methods;
this module is device-API-free (adapm-lint APM008) and pays only the
host-side residency work.
"""
from __future__ import annotations

import time

import numpy as np

from ..core.store import OOB, pad_bucket, pad_to

# ---------------------------------------------------------------------------
# kept staging buffers
# ---------------------------------------------------------------------------


_HELD = object()    # a taken buffer whose reader is not dispatched yet


class _Stage:
    """One kept staging buffer: `buf` is zero but for the rows `at`
    that its last user wrote; `user` is the result of the program that
    reads it (None: free)."""
    __slots__ = ("buf", "at", "user")

    def __init__(self, shape, dtype):
        # `np.full`: touched now, so a window pays no first-touch fault
        self.buf = np.full(shape, 0, dtype=dtype)
        self.at = np.empty(0, dtype=np.int64)
        self.user = None

    def free(self) -> bool:
        """No program is reading the buffer. The host->device copy of a
        numpy operand may still be under way when the call returns (and
        the CPU client reads the array in place), so a buffer is held
        until the program's RESULT is ready."""
        u = self.user
        return u is None or (u is not _HELD and
                             getattr(u, "is_ready", lambda: True)())

    def fill(self, at: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """Clear what the last user wrote, write `rows` at `at`."""
        self.buf[self.at] = 0
        self.buf[at] = rows
        self.at = at
        return self.buf


class StageRing:
    """The bag read's staged operands (`gather_pool_tiered`): a ring of
    kept buffers a (shape, dtype), so that a batch pays no `np.zeros`
    of its bucket (a first touch costs 1 ms a MB on the serving host and
    a 65,536-member batch stages 32 MB: PERF.md section 6, PR 46). A
    buffer is held from `take` until the program reading it has
    finished (`_Stage.user`); the ring grows by one where every buffer
    of a shape is held, so it is as deep as batches are in flight. The
    caller holds the server lock."""

    def __init__(self):
        self._rings = {}

    def take(self, shape, dtype) -> _Stage:
        ring = self._rings.setdefault((tuple(shape), np.dtype(dtype)), [])
        for st in ring:
            if st.free():
                break
        else:
            st = _Stage(shape, dtype)
            ring.append(st)
        st.user = _HELD
        return st


# ---------------------------------------------------------------------------
# residency resolution
# ---------------------------------------------------------------------------


def split_owner(store, o_sh: np.ndarray, o_sl: np.ndarray):
    """Resolve owner (shard, slot) coordinates against the residency
    map. Returns (g_row, cold, valid): the device hot-pool row per entry
    (OOB where the entry is padding/replica-served or cold), the cold
    mask, and the valid-entry mask."""
    o_sh = np.asarray(o_sh, dtype=np.int64).ravel()
    o_sl = np.asarray(o_sl, dtype=np.int64).ravel()
    valid = (o_sl >= 0) & (o_sl != OOB)
    g_row = np.full(o_sl.shape, OOB, dtype=np.int32)
    cold = np.zeros(o_sl.shape, dtype=bool)
    if valid.any():
        rows = store.res.dev_row[o_sh[valid], o_sl[valid]]
        g_row[valid] = np.where(rows >= 0, rows, OOB)
        cold[valid] = rows < 0
    return g_row, cold, valid


def _note_access(store, o_sh, o_sl, cold, valid) -> None:
    """Score the touched rows, count hot/cold serves, and queue cold
    rows for promotion (waking the maintenance worker — the miss path
    must drive adaptation even in workloads that never signal intent
    or serve lookups)."""
    res = store.res
    if valid.any():
        res.touch(o_sh[valid], o_sl[valid])
    nc = int(cold.sum())
    store.tier_hot_hits += int(valid.sum()) - nc
    store.tier_cold_hits += nc
    if nc:
        res.request_promote(o_sh[cold], o_sl[cold])
        res.kick()


# ---------------------------------------------------------------------------
# tiered store ops (called by ShardedStore when residency is enabled;
# caller holds the server lock)
# ---------------------------------------------------------------------------


def gather_tiered(store, o_shard, o_slot, c_shard, c_slot, use_cache):
    o_sh = np.asarray(o_shard, dtype=np.int64).ravel()
    o_sl = np.asarray(o_slot, dtype=np.int64).ravel()
    g_row, cold, valid = split_owner(store, o_sh, o_sl)
    _note_access(store, o_sh, o_sl, cold, valid)
    n = len(o_sh)
    a = pad_bucket(n, (o_sh.astype(np.int32), 0), (g_row, OOB),
                   (c_shard, 0), (c_slot, OOB), (use_cache, False),
                   minimum=store.bucket_min)
    if not cold.any():
        return store.port.gather(store.main, store.cache,
                                 store.delta, *a)
    t0 = time.perf_counter()
    b = a[0].shape[0]
    use_cold = np.zeros(b, dtype=bool)
    use_cold[:n] = cold
    mode = store.coldq.mode
    if mode == "fp32":
        cold_vals = np.zeros((b, store.value_length),
                             dtype=np.dtype(store.dtype))
        cold_vals[:n][cold] = store.coldq.read(o_sh[cold], o_sl[cold])
        out = store.port.gather_cold(store.main, store.cache,
                                     store.delta, *a, cold_vals,
                                     use_cold)
    else:
        # dequant-fused cold-miss gather (the port's wire ingest): ship
        # the WIRE rows — half/quarter the host->device bytes — and
        # invert the format inside the gather program itself
        q, s = store.coldq.wire(o_sh[cold], o_sl[cold])
        qbuf = np.zeros((b, store.value_length), dtype=q.dtype)
        qbuf[:n][cold] = q
        sbuf = None
        if mode != "fp16":
            sbuf = np.zeros(b, dtype=np.float32)
            sbuf[:n][cold] = s
        out = store.port.gather_cold_wire(
            mode, store.main, store.cache, store.delta, *a,
            qbuf, sbuf, use_cold)
    if store.tier_hist is not None:
        store.tier_hist.observe(time.perf_counter() - t0)
    return out


def gather_pool_tiered(store, o_shard, o_slot, c_shard, c_slot,
                       use_cache, seg, out, pooling):
    """`gather_tiered`'s fused-bag twin (ISSUE 16): identical residency
    resolution and cold-row staging, but the member rows reduce into
    `out` inside the port program (`gather_pool_cold[_wire]`) instead
    of coming back raw. The pooled result is bit-identical to host-
    pooling `gather_tiered`'s rows — the gather half is the same
    program body, and the segment sum accumulates in batch order on
    both sides. The host's share of it (the residency split, the cold
    rows' read and the staged operand: a DENSE `[bucket, L]` array
    whatever share of the members is cold, a kept buffer of the
    store's `StageRing`) is the bracket `adapm.serve.cold_stage`, and
    `tier.cold_stage_bytes` counts the staged bytes handed to the
    device."""
    with store.tier_stage():
        o_sh = np.asarray(o_shard, dtype=np.int64).ravel()
        o_sl = np.asarray(o_slot, dtype=np.int64).ravel()
        g_row, cold, valid = split_owner(store, o_sh, o_sl)
        _note_access(store, o_sh, o_sl, cold, valid)
        n = len(o_sh)
        a = pad_bucket(n, (o_sh.astype(np.int32), 0), (g_row, OOB),
                       (c_shard, 0), (c_slot, OOB), (use_cache, False),
                       minimum=store.bucket_min)
        b = a[0].shape[0]
        segb = pad_to(np.asarray(seg, dtype=np.int32), b, OOB)
        any_cold = bool(cold.any())
        if any_cold:
            t0 = time.perf_counter()
            use_cold = np.zeros(b, dtype=bool)
            use_cold[:n] = cold
            at = np.flatnonzero(cold)
            # the cold read BEFORE a buffer is taken, and a buffer whose
            # fill fails handed back: only `_pool_cold` frees one, and a
            # buffer left held makes the ring grow by a bucket a failure
            if store.coldq.mode == "fp32":
                vals = (store.coldq.read(o_sh[cold], o_sl[cold]),)
            else:
                vals = store.coldq.wire(o_sh[cold], o_sl[cold])
            stages = _stages(store, b)
            try:
                staged = sum(st.fill(at, v).nbytes
                             for st, v in zip(stages, vals))
            except BaseException:
                for st in stages:
                    st.user = None
                raise
            if store.tier_stage_bytes is not None:
                store.tier_stage_bytes.inc(staged)
    if not any_cold:
        return store.port.gather_pool(store.main, store.cache,
                                      store.delta, *a, segb, out,
                                      pooling=pooling)
    pooled = _pool_cold(store, a, stages, use_cold, segb, out, pooling)
    if store.tier_hist is not None:
        store.tier_hist.observe(time.perf_counter() - t0)
    return pooled


def _stages(store, b: int) -> list:
    """The ring's buffers for the staged operands of one cold batch of
    bucket `b`: the rows in the cold store's wire type and, in the int8
    mode, their scales."""
    ring, mode = store.stage_ring, store.coldq.mode
    rows = np.dtype(store.dtype) if mode == "fp32" else store.coldq.q.dtype
    stages = [ring.take((b, store.value_length), rows)]
    if mode == "int8":
        stages.append(ring.take((b,), np.float32))
    return stages


def _pool_cold(store, a, stages, use_cold, segb, out, pooling):
    """Dispatch the cold twin of the bag program on the staged operands
    `stages`, each held until the program has finished (it never
    started: free again)."""
    mode, pooled = store.coldq.mode, None
    pools = (store.main, store.cache, store.delta)
    try:
        if mode == "fp32":
            pooled = store.port.gather_pool_cold(
                *pools, *a, stages[0].buf, use_cold, segb, out,
                pooling=pooling)
        else:
            pooled = store.port.gather_pool_cold_wire(
                mode, *pools, *a, stages[0].buf,
                stages[1].buf if mode == "int8" else None, use_cold,
                segb, out, pooling=pooling)
    finally:
        for st in stages:
            st.user = pooled
    return pooled


def precompile_gather_pool(store, members: int, nbags: int,
                           pooling: str) -> tuple:
    """Dispatch both bag programs of a tiered store at one pair of
    buckets (`LookupBatcher.precompile_bags`): the plain one a batch of
    hot members runs and the cold twin a batch naming a cold member
    runs; every member out of bounds and of no bag, so both read fill
    and pool nothing. The cold twin reads the ring's buffers of this
    bucket, and one more set is made beside them: two batches in flight
    find theirs touched, and no window pays a first touch. Caller holds
    the server lock; returns the two results for it to wait on."""
    b = np.zeros(members, dtype=np.int32)
    oob = np.full(members, OOB, dtype=np.int32)
    no = np.zeros(members, dtype=bool)
    out = np.zeros((nbags, store.value_length),
                   dtype=np.dtype(store.dtype))
    plain = store.port.gather_pool(store.main, store.cache, store.delta,
                                   b, oob, b, oob, no, oob, out,
                                   pooling=pooling)
    stages, spare = _stages(store, members), _stages(store, members)
    for st in spare:
        st.user = None
    cold = _pool_cold(store, (b, oob, b, oob, no), stages, no, oob, out,
                      pooling)
    return plain, cold


def scatter_add_tiered(store, o_shard, o_slot, d_shard, d_slot, vals):
    o_sh = np.asarray(o_shard, dtype=np.int64).ravel()
    o_sl = np.asarray(o_slot, dtype=np.int64).ravel()
    g_row, cold, valid = split_owner(store, o_sh, o_sl)
    _note_access(store, o_sh, o_sl, cold, valid)
    rows = np.asarray(vals, dtype=np.dtype(store.dtype)).reshape(
        len(o_sh), store.value_length)
    if cold.any():
        # additive merge on the authoritative host row (in-batch
        # duplicates accumulate in batch order, like the device
        # scatter; quantized modes fold through the EF residual)
        store.coldq.add_at(o_sh[cold], o_sl[cold], rows[cold])
    n = len(o_sh)
    a = pad_bucket(n, (o_sh.astype(np.int32), 0), (g_row, OOB),
                   (d_shard, 0), (d_slot, OOB), minimum=store.bucket_min)
    v = store._vals_bucket(rows, a[0].shape[0])
    store.main, store.delta = store.port.scatter_add(
        store.main, store.delta, *a, v)


def set_rows_tiered(store, o_shard, o_slot, vals, c_shard, c_slot):
    o_sh = np.asarray(o_shard, dtype=np.int64).ravel()
    o_sl = np.asarray(o_slot, dtype=np.int64).ravel()
    g_row, cold, valid = split_owner(store, o_sh, o_sl)
    _note_access(store, o_sh, o_sl, cold, valid)
    rows = np.asarray(vals, dtype=np.dtype(store.dtype)).reshape(
        len(o_sh), store.value_length)
    if cold.any():
        store.coldq.set_at(o_sh[cold], o_sl[cold], rows[cold])
    n = len(o_sh)
    a = pad_bucket(n, (o_sh.astype(np.int32), 0), (g_row, OOB),
                   (c_shard, 0), (c_slot, OOB), minimum=store.bucket_min)
    v = store._vals_bucket(rows, a[0].shape[0])
    store.main, store.cache, store.delta = store.port.set_rows(
        store.main, store.cache, store.delta, a[0], a[1], v,
        a[2], a[3])


def replica_create_tiered(store, o_shard, o_slot, c_shard, c_slot):
    """Materialize replicas: hot owners through the device program (with
    remapped rows), cold owners via host read + base install."""
    o_sh = np.asarray(o_shard, dtype=np.int64).ravel()
    o_sl = np.asarray(o_slot, dtype=np.int64).ravel()
    c_sh = np.asarray(c_shard, dtype=np.int32).ravel()
    c_sl = np.asarray(c_slot, dtype=np.int32).ravel()
    g_row, cold, valid = split_owner(store, o_sh, o_sl)
    hot = valid & ~cold
    if hot.any():
        a = pad_bucket(int(hot.sum()),
                       (o_sh[hot].astype(np.int32), 0), (g_row[hot], OOB),
                       (c_sh[hot], 0), (c_sl[hot], OOB),
                       minimum=store.bucket_min)
        store.cache, store.delta = store.port.replica_create(
            store.main, store.cache, store.delta, *a)
    if cold.any():
        # a fresh replica copies the VISIBLE cold value (deq only —
        # the parked residual stays with the owner row)
        vals = store.coldq.read(o_sh[cold], o_sl[cold])
        a = pad_bucket(int(cold.sum()), (c_sh[cold], 0), (c_sl[cold], OOB),
                       minimum=store.bucket_min)
        v = store._vals_bucket(vals, a[0].shape[0])
        store.cache, store.delta = store.port.install_cache_rows(
            store.cache, store.delta, *a, v)


def sync_replicas_tiered(store, r_shard, r_cslot, o_shard, o_slot,
                         threshold: float = 0.0, compress: str = "off"):
    """One sync batch with tier-aware owners: replicas of hot owners
    ride the fused device program; replicas of cold owners sync through
    the cold path — delta readback → host merge → base install (the
    tentpole's "replicas of cold keys sync through the cold path").
    `compress` applies the --sys.sync.compress wire transform on both
    halves: the device program for hot owners, the host twin
    (quant.compress_delta) for cold owners — with the residual parked
    in the replica's delta row either way."""
    r_sh = np.asarray(r_shard, dtype=np.int32).ravel()
    r_cs = np.asarray(r_cslot, dtype=np.int32).ravel()
    o_sh = np.asarray(o_shard, dtype=np.int64).ravel()
    o_sl = np.asarray(o_slot, dtype=np.int64).ravel()
    g_row, cold, valid = split_owner(store, o_sh, o_sl)
    hot = ~cold  # invalid (padding) entries ride the device program: OOB
    if hot.any():
        a = pad_bucket(int(hot.sum()), (r_sh[hot], 0), (r_cs[hot], OOB),
                       (o_sh[hot].astype(np.int32), 0), (g_row[hot], OOB),
                       minimum=store.bucket_min)
        out = store.port.sync_replicas(
            store.main, store.cache, store.delta, *a,
            threshold=threshold, compress=compress)
        if compress != "off":
            (store.main, store.cache, store.delta,
             store._ef_resid_dev) = out
        else:
            store.main, store.cache, store.delta = out
        if threshold > 0.0:
            # the device decided which deltas merged, and the store
            # leaves the write epochs alone (core/store.py): none of
            # these owner rows may count as unwritten since its
            # promotion (tier/promote.py demotes a clean row unread)
            v = hot & valid
            store.res.promo_epoch[o_sh[v], g_row[v]] = -1
    if not cold.any():
        return
    t0 = time.perf_counter()
    ci = np.nonzero(cold)[0]
    # extract: the pending deltas of the cold-owner replicas (the
    # readback serializes behind every enqueued delta write — exact)
    dvals = store.read_rows("delta", r_sh[ci], r_cs[ci])
    ship = np.ones(len(ci), dtype=bool)
    if threshold > 0.0:
        # the reference's sync threshold, decided on host for cold rows
        # (the device program decides on device for hot rows)
        ship = np.max(np.abs(dvals), axis=1) >= threshold
    if ship.any():
        si = ci[ship]
        merged = dvals[ship]
        resid = None
        if compress != "off":
            # host twin of _sync_replicas_compressed: the owner merges
            # what the wire format reconstructs; the remainder parks in
            # the replica's delta row below
            from .quant import compress_delta
            merged, resid = compress_delta(compress, merged)
            if len(resid):
                store._ef_resid_host = float(np.max(np.abs(resid)))
        # merge-all THEN refresh-all, like the device program: all
        # shipped deltas land before any fresh value is read, so every
        # replica of a key sees the post-merge value
        store.coldq.add_at(o_sh[si], o_sl[si], merged)
        fresh = store.coldq.read(o_sh[si], o_sl[si])
        a = pad_bucket(len(si), (r_sh[si], 0), (r_cs[si], OOB),
                       minimum=store.bucket_min)
        v = store._vals_bucket(fresh, a[0].shape[0])
        rv = None if resid is None else \
            store._vals_bucket(resid, a[0].shape[0])
        store.cache, store.delta = store.port.install_cache_rows(
            store.cache, store.delta, *a, v, resid=rv)
    if store.tier_hist is not None:
        store.tier_hist.observe(time.perf_counter() - t0)


def relocate_tiered(store, old_shard, old_slot, new_shard, new_slot,
                    rc_shard, rc_slot):
    """Relocation on the tiered store runs through the host: read the
    authoritative old rows (device readback where hot, cold store
    otherwise), merge the destination replica's pending delta, land the
    moved rows COLD at the destination (relocation is intent-driven, so
    the pin/promote path makes them hot right after), and free the old
    residency. All reads happen before all writes — the device
    program's intra-batch slot-reuse discipline."""
    from .promote import release_rows
    old_sh = np.asarray(old_shard, dtype=np.int64).ravel()
    old_sl = np.asarray(old_slot, dtype=np.int64).ravel()
    new_sh = np.asarray(new_shard, dtype=np.int64).ravel()
    new_sl = np.asarray(new_slot, dtype=np.int64).ravel()
    rc_sh = np.asarray(rc_shard, dtype=np.int32).ravel()
    rc_sl = np.asarray(rc_slot, dtype=np.int32).ravel()
    n = len(old_sh)
    g_row, cold, valid = split_owner(store, old_sh, old_sl)
    rows = np.zeros((n, store.value_length), dtype=np.dtype(store.dtype))
    hot = valid & ~cold
    if hot.any():
        rows[hot] = store.read_hot_rows_at(old_sh[hot].astype(np.int32),
                                           g_row[hot])
    if cold.any():
        # a relocation MOVES the authoritative value: take the full-
        # precision row (deq + parked residual, consuming it) so the
        # error-feedback state travels with the key
        rows[cold] = store.coldq.take_true(old_sh[cold], old_sl[cold])
    has_rc = (rc_sl != OOB) & (rc_sl >= 0)
    if has_rc.any():
        d = store.read_rows("delta", rc_sh[has_rc], rc_sl[has_rc])
        rows[has_rc] += d
        a = pad_bucket(int(has_rc.sum()), (rc_sh[has_rc], 0),
                       (rc_sl[has_rc], OOB), minimum=store.bucket_min)
        store.delta = store.port.clear_rows(store.delta, *a)
    # free the old residency (value already extracted), land cold
    release_rows(store, old_sh[valid], old_sl[valid])
    dst_ok = (new_sl >= 0) & (new_sl != OOB)
    if dst_ok.any():
        store.coldq.set_at(new_sh[dst_ok], new_sl[dst_ok], rows[dst_ok])
        # defensively clear any stale mapping at the destination slot
        # (a correctly-released slot is already -1)
        store.res.dev_row[new_sh[dst_ok], new_sl[dst_ok]] = -1


def read_main_rows_tiered(store, sh, sl) -> np.ndarray:
    """Host readback of main rows on the tiered store (read_rows'
    "main" pool): hot rows via a device gather, cold rows from the cold
    store."""
    sh = np.asarray(sh, dtype=np.int64).ravel()
    sl = np.asarray(sl, dtype=np.int64).ravel()
    g_row, cold, valid = split_owner(store, sh, sl)
    out = np.zeros((len(sh), store.value_length),
                   dtype=np.dtype(store.dtype))
    hot = valid & ~cold
    if hot.any():
        out[hot] = store.read_hot_rows_at(sh[hot].astype(np.int32),
                                          g_row[hot])
    if cold.any():
        out[cold] = store.coldq.read(sh[cold], sl[cold])
    return out


def read_main_rows_bulk(store, sh: np.ndarray,
                        sl: np.ndarray) -> np.ndarray:
    """Bulk-scale host read of main rows (checkpoint/eval/export path):
    fancy-index the REQUESTED rows out of the cold store (no full-table
    copy — at beyond-HBM model sizes a whole-table copy would
    transiently double host RAM) and overlay the hot subset via one
    hot-pool-sized readback (bounded by hot_rows, not model size)."""
    sh = np.asarray(sh, dtype=np.int64).ravel()
    sl = np.asarray(sl, dtype=np.int64).ravel()
    # fancy index -> copy of the REQUESTED rows only; quantized modes
    # dequantize that same bounded slice (wire copy + f32 result), so
    # the dequant path keeps the no-second-full-table-copy contract
    out = store.coldq.read(sh, sl)
    rows = store.res.dev_row[sh, sl]
    m = rows >= 0
    if m.any():
        hot = np.asarray(store.main)  # [S, hot_rows, L]
        out[m] = hot[sh[m], rows[m]]
    return out


def main_full_host(store) -> np.ndarray:
    """Assemble the full authoritative main table [S, main_slots, L] on
    host (checkpoint save, bulk reads): the cold store overlaid with the
    hot pool's rows. One device readback of the whole hot pool."""
    full = store.coldq.full()
    res = store.res
    sh_idx, row_idx = np.nonzero(res.row_slot >= 0)
    if len(sh_idx):
        hot_host = np.asarray(store.main)
        full[sh_idx, res.row_slot[sh_idx, row_idx]] = \
            hot_host[sh_idx, row_idx]
    return full


def install_main_full(store, arr: np.ndarray) -> None:
    """Checkpoint restore into a tiered store: the full main table
    becomes the cold store and residency resets — everything cold,
    re-promoted lazily by access/intent (the restore contract,
    tests/test_tier.py)."""
    store.coldq.install_full(np.asarray(arr, dtype=np.dtype(store.dtype)))
    store.res.reset()
