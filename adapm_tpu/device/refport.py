"""NumpyRefPort: a pure-NumPy DevicePort (ISSUE 16, tentpole half c).

The existence proof that the r17 DevicePort seam is honest: a complete
second backend that never imports jax — no jit, no device_put, no
sharding — yet runs the same stores, tier engine, serve plane, sync
rounds and episodic prep BIT-IDENTICALLY to `JaxDevicePort`
(`scripts/portdiff_check.py` drives a randomized multi-plane storm
against both ports and compares every read and the post-quiesce tables
bitwise). If a data-plane change leaks a jax-ism past the port surface,
this module stops compiling against it and the port-differential storm
fails loudly.

Semantics mirror device/jaxport.py program for program:

  - gathers with `mode="fill"` read 0 for any out-of-range (shard, slot)
    entry — the OOB padding sentinel is a huge positive int32, never
    negative (a negative index would WRAP, docs/MEMORY.md);
  - scatters with `mode="drop"` skip out-of-range entries; duplicate
    in-batch indices accumulate in BATCH ORDER via `np.add.at` — the
    same order the XLA scatter applies, the accumulation-order contract
    tier/coldpath.py documents (this is what makes the fused
    `gather_pool` family bit-identical across backends);
  - the compressed-sync wire math (fp16 cast, int8 symmetric grid
    through the f16 scale wire) reuses numpy's IEEE round-to-nearest-
    even casts, which match the XLA converts bit for bit — the same
    equivalence tier/quant.py's host twins already rely on;
  - "donated" pools are simply mutated in place and returned: donation
    means the caller must rebind and never reread the old reference,
    which an in-place numpy update satisfies trivially.

`compile` / `compile_collective` raise: the reference port is a data-
plane backend (stores, tier, serve, sync), not a program compiler —
fused-step runners and device collectives stay jax-only, and nothing in
the port-differential storm needs them.
"""
from __future__ import annotations

import numpy as np

# duplicated from device/jaxport.py on purpose: importing it would pull
# jax into this module, and "imports no jax" is the point (asserted by
# scripts/portdiff_check.py)
OOB = np.int32(2**31 - 2)
F16_MAX = 65504.0

from .port import DevicePort  # noqa: E402


def _valid(arr, sh, sl):
    """In-range mask for (shard, slot) index pairs against pool `arr`
    ([S, R, L]). Matches jax's fill/drop modes: ANY out-of-range
    coordinate disqualifies the entry."""
    sh = np.asarray(sh)
    sl = np.asarray(sl)
    return ((sh >= 0) & (sh < arr.shape[0])
            & (sl >= 0) & (sl < arr.shape[1]))


def _fill_gather(arr, sh, sl):
    """`arr.at[sh, sl].get(mode="fill", fill_value=0)`."""
    sh = np.asarray(sh)
    sl = np.asarray(sl)
    m = _valid(arr, sh, sl)
    out = np.zeros((len(sh), arr.shape[-1]), arr.dtype)
    if m.any():
        out[m] = arr[sh[m], sl[m]]
    return out


def _drop_add(arr, sh, sl, vals):
    """`arr.at[sh, sl].add(vals, mode="drop")` in place — duplicates
    accumulate in batch order (np.add.at)."""
    sh = np.asarray(sh)
    sl = np.asarray(sl)
    m = _valid(arr, sh, sl)
    if m.any():
        np.add.at(arr, (sh[m], sl[m]), np.asarray(vals)[m])


def _drop_set(arr, sh, sl, vals):
    """`arr.at[sh, sl].set(vals, mode="drop")` in place."""
    sh = np.asarray(sh)
    sl = np.asarray(sl)
    m = _valid(arr, sh, sl)
    if m.any():
        arr[sh[m], sl[m]] = np.asarray(vals)[m]


def _pool_rows_host(rows, seg, out, pooling):
    """The host twin of jaxport._pool_rows: batch-order segment sum
    (np.add.at), one division for mean. `out` is consumed (mutated and
    returned) — callers pass a fresh zeroed buffer per dispatch."""
    seg = np.asarray(seg)
    m = (seg >= 0) & (seg < out.shape[0])
    np.add.at(out, seg[m], np.asarray(rows)[m])
    if pooling == "sum":
        return out
    cnt = np.zeros(out.shape[0], rows.dtype)
    np.add.at(cnt, seg[m], rows.dtype.type(1))
    denom = np.where(cnt > 0, cnt, rows.dtype.type(1))[:, None]
    return np.where(cnt[:, None] > 0, out / denom, np.zeros_like(out))


class NumpyRefPort(DevicePort):
    """The pure-NumPy reference DevicePort (module docstring). Install
    with `device.set_default_port(NumpyRefPort())` BEFORE any Server is
    built; every store then runs host-side."""

    name = "numpy-ref"

    def __init__(self):
        # same lock-free liveness-counter convention as JaxDevicePort
        self.programs = 0
        self.wire_ingest_rows = 0

    def stats(self) -> dict:
        return {"backend": self.name,
                "programs_total": int(self.programs),
                "wire_ingest_rows_total": int(self.wire_ingest_rows)}

    # -- data-plane programs -------------------------------------------------

    @staticmethod
    def _gather_rows(main, cache, delta, o_shard, o_slot, c_shard,
                     c_slot, use_cache):
        m = _fill_gather(main, o_shard, o_slot)
        c = (_fill_gather(cache, c_shard, c_slot)
             + _fill_gather(delta, c_shard, c_slot))
        return np.where(np.asarray(use_cache)[:, None], c, m)

    def gather(self, main, cache, delta, o_shard, o_slot, c_shard,
               c_slot, use_cache):
        self.programs += 1
        return self._gather_rows(main, cache, delta, o_shard, o_slot,
                                 c_shard, c_slot, use_cache)

    def gather_pool(self, main, cache, delta, o_shard, o_slot, c_shard,
                    c_slot, use_cache, seg, out, pooling="sum"):
        self.programs += 1
        rows = self._gather_rows(main, cache, delta, o_shard, o_slot,
                                 c_shard, c_slot, use_cache)
        return _pool_rows_host(rows, seg, np.array(out, copy=True),
                               pooling)

    def scatter_add(self, main, delta, o_shard, o_slot, d_shard,
                    d_slot, vals):
        self.programs += 1
        _drop_add(main, o_shard, o_slot, vals)
        _drop_add(delta, d_shard, d_slot, vals)
        return main, delta

    def set_rows(self, main, cache, delta, o_shard, o_slot, vals,
                 c_shard, c_slot):
        self.programs += 1
        _drop_set(main, o_shard, o_slot, vals)
        _drop_set(cache, c_shard, c_slot, vals)
        _drop_set(delta, c_shard, c_slot, np.zeros_like(vals))
        return main, cache, delta

    def replica_create(self, main, cache, delta, o_shard, o_slot,
                       c_shard, c_slot):
        self.programs += 1
        rows = _fill_gather(main, o_shard, o_slot)
        _drop_set(cache, c_shard, c_slot, rows)
        _drop_set(delta, c_shard, c_slot, np.zeros_like(rows))
        return cache, delta

    def sync_replicas(self, main, cache, delta, r_shard, r_cslot,
                      o_shard, o_slot, threshold: float = 0.0,
                      compress: str = "off"):
        self.programs += 1
        if compress != "off":
            return self._sync_compressed(main, cache, delta, r_shard,
                                         r_cslot, o_shard, o_slot,
                                         threshold, compress)
        dvals = _fill_gather(delta, r_shard, r_cslot)
        rs, osl = np.asarray(r_cslot), np.asarray(o_slot)
        if threshold > 0.0:
            ship = np.max(np.abs(dvals), axis=1) >= \
                main.dtype.type(threshold)
            rs = np.where(ship, rs, OOB)
            osl = np.where(ship, osl, OOB)
        _drop_add(main, o_shard, osl, dvals)
        fresh = _fill_gather(main, o_shard, osl)
        _drop_set(cache, r_shard, rs, fresh)
        _drop_set(delta, r_shard, rs, np.zeros_like(fresh))
        return main, cache, delta

    def _sync_compressed(self, main, cache, delta, r_shard, r_cslot,
                         o_shard, o_slot, threshold, mode):
        # the host twin of _sync_replicas_compressed, op for op: clip
        # before any f16 cast (inf guard), park the quantization
        # remainder in the delta row (EF loop), held rows keep their
        # full delta
        dvals = _fill_gather(delta, r_shard, r_cslot)
        thr = main.dtype.type(threshold)
        ship = np.max(np.abs(dvals), axis=1) >= thr
        if mode == "fp16":
            shipped = np.clip(dvals, -F16_MAX, F16_MAX).astype(
                np.float16).astype(dvals.dtype)
        else:  # int8, symmetric per-row scale through the f16 wire
            s = np.clip(np.max(np.abs(dvals), axis=1) / 127.0,
                        0.0, F16_MAX).astype(np.float16).astype(
                            dvals.dtype)
            safe = np.where(s > 0, s, dvals.dtype.type(1.0))
            q = np.clip(np.round(dvals / safe[:, None]), -127, 127)
            shipped = q.astype(np.int8).astype(dvals.dtype) * s[:, None]
        resid = dvals - shipped
        rs = np.where(ship, np.asarray(r_cslot), OOB)
        osl = np.where(ship, np.asarray(o_slot), OOB)
        _drop_add(main, o_shard, osl, shipped)
        fresh = _fill_gather(main, o_shard, osl)
        _drop_set(cache, r_shard, rs, fresh)
        new_delta = np.where(ship[:, None], resid, dvals)
        _drop_set(delta, r_shard, r_cslot, new_delta)
        resid_norm = np.max(np.where(ship[:, None], np.abs(resid),
                                     dvals.dtype.type(0.0)))
        return main, cache, delta, resid_norm

    def read_rows_at(self, arr, sh, sl):
        self.programs += 1
        return _fill_gather(arr, sh, sl)

    def install_rows(self, cache, delta, c_shard, c_slot, vals):
        self.programs += 1
        _drop_set(cache, c_shard, c_slot, vals)
        _drop_set(delta, c_shard, c_slot, np.zeros_like(vals))
        return cache, delta

    def refresh_after_sync(self, cache, delta, c_shard, c_slot, fresh,
                           shipped):
        self.programs += 1
        _drop_set(cache, c_shard, c_slot, fresh)
        _drop_add(delta, c_shard, c_slot, -np.asarray(shipped))
        return cache, delta

    def relocate(self, main, delta, old_shard, old_slot, new_shard,
                 new_slot, rc_shard, rc_slot):
        self.programs += 1
        # all gathers before all scatters (intra-batch slot reuse)
        rows = _fill_gather(main, old_shard, old_slot)
        rows = rows + _fill_gather(delta, rc_shard, rc_slot)
        _drop_set(main, new_shard, new_slot, rows)
        _drop_set(delta, rc_shard, rc_slot, np.zeros_like(rows))
        return main, delta

    # -- tiered cold path + wire ingest --------------------------------------

    def _gather_cold_rows(self, main, cache, delta, o_shard, o_row,
                          c_shard, c_slot, use_cache, cold_vals,
                          use_cold):
        m = _fill_gather(main, o_shard, o_row)
        m = np.where(np.asarray(use_cold)[:, None],
                     np.asarray(cold_vals), m)
        c = (_fill_gather(cache, c_shard, c_slot)
             + _fill_gather(delta, c_shard, c_slot))
        return np.where(np.asarray(use_cache)[:, None], c, m)

    def gather_cold(self, main, cache, delta, o_shard, o_row, c_shard,
                    c_slot, use_cache, cold_vals, use_cold):
        self.programs += 1
        return self._gather_cold_rows(main, cache, delta, o_shard,
                                      o_row, c_shard, c_slot,
                                      use_cache, cold_vals, use_cold)

    @staticmethod
    def _dequant_wire(mode, main, cold_q, cold_scale):
        if mode == "fp16":
            return np.asarray(cold_q).astype(main.dtype)
        return (np.asarray(cold_q).astype(main.dtype)
                * np.asarray(cold_scale)[:, None])

    def gather_cold_wire(self, mode: str, main, cache, delta, o_shard,
                         o_row, c_shard, c_slot, use_cache, cold_q,
                         cold_scale, use_cold):
        self.programs += 1
        self.wire_ingest_rows += int(np.count_nonzero(
            np.asarray(use_cold)))
        deq = self._dequant_wire(mode, main, cold_q, cold_scale)
        return self._gather_cold_rows(main, cache, delta, o_shard,
                                      o_row, c_shard, c_slot,
                                      use_cache, deq, use_cold)

    def gather_pool_cold(self, main, cache, delta, o_shard, o_row,
                         c_shard, c_slot, use_cache, cold_vals,
                         use_cold, seg, out, pooling="sum"):
        self.programs += 1
        rows = self._gather_cold_rows(main, cache, delta, o_shard,
                                      o_row, c_shard, c_slot,
                                      use_cache, cold_vals, use_cold)
        return _pool_rows_host(rows, seg, np.array(out, copy=True),
                               pooling)

    def gather_pool_cold_wire(self, mode: str, main, cache, delta,
                              o_shard, o_row, c_shard, c_slot,
                              use_cache, cold_q, cold_scale, use_cold,
                              seg, out, pooling="sum"):
        self.programs += 1
        self.wire_ingest_rows += int(np.count_nonzero(
            np.asarray(use_cold)))
        deq = self._dequant_wire(mode, main, cold_q, cold_scale)
        rows = self._gather_cold_rows(main, cache, delta, o_shard,
                                      o_row, c_shard, c_slot,
                                      use_cache, deq, use_cold)
        return _pool_rows_host(rows, seg, np.array(out, copy=True),
                               pooling)

    def write_main_rows(self, main, sh, row, vals):
        self.programs += 1
        _drop_set(main, sh, row, vals)
        return main

    def write_main_rows_wire(self, mode: str, main, sh, row, qvals,
                             scales=None):
        self.programs += 1
        self.wire_ingest_rows += int(np.count_nonzero(
            np.asarray(row) != OOB))
        _drop_set(main, sh, row,
                  self._dequant_wire(mode, main, qvals, scales))
        return main

    def clear_rows(self, arr, sh, sl):
        self.programs += 1
        sh = np.asarray(sh)
        _drop_set(arr, sh, sl,
                  np.zeros((len(sh), arr.shape[-1]), arr.dtype))
        return arr

    def install_cache_rows(self, cache, delta, c_shard, c_slot, vals,
                           resid=None):
        self.programs += 1
        _drop_set(cache, c_shard, c_slot, vals)
        _drop_set(delta, c_shard, c_slot,
                  np.zeros_like(np.asarray(vals))
                  if resid is None else resid)
        return cache, delta

    # -- buffer allocation / transfer ----------------------------------------

    def alloc_pool(self, shape, dtype, sharding):
        # host pool: the sharding argument is a placement hint this
        # backend has no devices to honor
        return np.zeros(shape, dtype)

    def install_pool(self, arr, sharding):
        return np.array(arr, copy=True)

    def put_replicated(self, arr, sharding):
        return np.asarray(arr)

    def put_single(self, arr, device):
        return np.asarray(arr)

    # -- program construction ------------------------------------------------

    def compile(self, fn, **jit_kwargs):
        raise NotImplementedError(
            "NumpyRefPort is a data-plane reference backend; fused-step "
            "program compilation is jax-only (use JaxDevicePort)")

    def compile_collective(self, fn, mesh, in_specs, out_specs,
                           check_vma=True, **jit_kwargs):
        raise NotImplementedError(
            "NumpyRefPort has no collective backend (single-process "
            "data plane only)")
