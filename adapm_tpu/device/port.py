"""The DevicePort protocol: the narrow device-plane surface (ISSUE 14).

Every accelerator interaction the parameter manager performs — data-plane
gathers/scatters, the sync/relocation programs, the tiered wire-row
ingest, donation-aware pool allocation, fused-step program construction,
and the collective exchange constructor — goes through ONE port object.
The rest of the tree never calls `jax.jit` / `jax.device_put` /
`shard_map` directly (mechanically enforced by adapm-lint APM008:
device-API confinement), so a real-accelerator backend is one new port
implementation, not a tree-wide edit.

The surface is deliberately narrow and index-shaped: port methods take
pool arrays plus padded (shard, slot/row) index buffers — exactly what
`ShardedStore` already computes — and return the replacement pool
arrays. Semantics every implementation must preserve:

  - **bit-exactness**: a port method's result is IEEE-f32 bit-identical
    to the reference `JaxDevicePort` programs (the storm tests compare
    tiered/episodic/compressed execution against shadows bitwise; a
    port that rounds differently fails them);
  - **padding**: index entries carrying `core.store.OOB` are no-ops —
    dropped by scatters, zero-filled by gathers;
  - **donation**: pool arguments documented as donated are CONSUMED by
    the call — the caller must rebind from the returned arrays and
    never read the old reference again (adapm-lint APM005);
  - **asynchrony**: methods ENQUEUE device work and return; callers
    hold the process-wide dispatch gate discipline inside the port
    (docs/EXECUTOR.md), never across device execution;
  - **wire ingest**: the `*_wire` methods accept still-quantized
    fp16/int8 payloads (tier/quant.py wire formats) and invert them
    in-program — the Tensor Casting co-design point; host twins in
    tier/quant.py must match bitwise.
"""
from __future__ import annotations

from typing import Optional


class DevicePort:
    """Abstract device-plane port (see module docstring). The shipping
    implementation is `JaxDevicePort` (device/jaxport.py); a GPU/TPU
    backend specializes by overriding program construction — the call
    sites in core/ops/tier never change."""

    # -- identity / health ---------------------------------------------------

    name = "abstract"

    def stats(self) -> dict:
        """Host-side accounting for the `device` snapshot section."""
        raise NotImplementedError

    # -- data-plane programs (core/store.py ShardedStore) --------------------

    def gather(self, main, cache, delta, o_shard, o_slot, c_shard,
               c_slot, use_cache):
        raise NotImplementedError

    def gather_pool(self, main, cache, delta, o_shard, o_slot, c_shard,
                    c_slot, use_cache, seg, out, pooling="sum"):
        """Fused embedding-bag read (ISSUE 16): gather member rows
        exactly as `gather` and reduce them into `out[seg[i]]` in ONE
        program — sum pooling accumulates in batch order (the same
        order `np.add.at` uses on host, so fused-vs-host-pooled results
        are bit-identical by construction); mean divides the batch-order
        sum by the per-bag member count once. `seg` carries OOB for
        padding members (dropped by the pooling scatter); `out` is a
        zeroed [n_bags_bucket, L] host buffer fixing the output shape."""
        raise NotImplementedError

    def scatter_add(self, main, delta, o_shard, o_slot, d_shard,
                    d_slot, vals):
        """Donates (main, delta); returns (main, delta)."""
        raise NotImplementedError

    def set_rows(self, main, cache, delta, o_shard, o_slot, vals,
                 c_shard, c_slot):
        """Donates (main, cache, delta); returns the triple."""
        raise NotImplementedError

    def replica_create(self, main, cache, delta, o_shard, o_slot,
                       c_shard, c_slot):
        """Donates (cache, delta); returns (cache, delta)."""
        raise NotImplementedError

    def sync_replicas(self, main, cache, delta, r_shard, r_cslot,
                      o_shard, o_slot, threshold: float = 0.0,
                      compress: str = "off"):
        """One sync round. Donates (main, cache, delta). Returns the
        triple, plus the max-abs parked residual when `compress` is a
        wire mode (the EF audit scalar) — i.e. a 3- or 4-tuple."""
        raise NotImplementedError

    def read_rows_at(self, arr, sh, sl):
        raise NotImplementedError

    def install_rows(self, cache, delta, c_shard, c_slot, vals):
        """Donates (cache, delta); returns (cache, delta)."""
        raise NotImplementedError

    def refresh_after_sync(self, cache, delta, c_shard, c_slot, fresh,
                           shipped):
        """Donates (cache, delta); returns (cache, delta)."""
        raise NotImplementedError

    def relocate(self, main, delta, old_shard, old_slot, new_shard,
                 new_slot, rc_shard, rc_slot):
        """Donates (main, delta); returns (main, delta)."""
        raise NotImplementedError

    def patch_routes(self, place, cache_row, patch):
        """The fused step's routing tables (ops/fused.py DeviceRouter:
        a key's place word and the worker shard's cache row) with the
        entries named by `patch` set: int32 [3, n], its rows the keys
        and their place and cache-row values; a key out of bounds is
        padding. The keys ascend and none repeats, padding included:
        the caller's promise (`DeviceRouter._patch_operand` keeps it),
        which a port may hand to its scatter. Donates nothing (a step
        in flight keeps the tables it was dispatched with); returns the
        two tables."""
        raise NotImplementedError

    # -- tiered cold path + wire-row ingest (tier/, ops/dequant twins) -------

    def gather_cold(self, main, cache, delta, o_shard, o_row, c_shard,
                    c_slot, use_cache, cold_vals, use_cold):
        raise NotImplementedError

    def gather_cold_wire(self, mode: str, main, cache, delta, o_shard,
                         o_row, c_shard, c_slot, use_cache, cold_q,
                         cold_scale, use_cold):
        """Cold-miss gather with still-quantized cold rows (`mode` in
        fp16/int8); dequant fuses into the program."""
        raise NotImplementedError

    def gather_pool_cold(self, main, cache, delta, o_shard, o_row,
                         c_shard, c_slot, use_cache, cold_vals,
                         use_cold, seg, out, pooling="sum"):
        """`gather_pool` with the host-supplied cold-row override
        (`gather_cold` semantics for the member gather half)."""
        raise NotImplementedError

    def gather_pool_cold_wire(self, mode: str, main, cache, delta,
                              o_shard, o_row, c_shard, c_slot,
                              use_cache, cold_q, cold_scale, use_cold,
                              seg, out, pooling="sum"):
        """`gather_pool` over still-quantized cold rows (`mode` in
        fp16/int8): dequant AND pooling both fuse into one program."""
        raise NotImplementedError

    def write_main_rows(self, main, sh, row, vals):
        """Promotion upload (donates main; returns main)."""
        raise NotImplementedError

    def write_main_rows_wire(self, mode: str, main, sh, row, qvals,
                             scales=None):
        """Promotion upload from wire rows (donates main; returns
        main)."""
        raise NotImplementedError

    def clear_rows(self, arr, sh, sl):
        """Zero rows (donates arr; returns arr)."""
        raise NotImplementedError

    def install_cache_rows(self, cache, delta, c_shard, c_slot, vals,
                           resid=None):
        """Cold-owner sync refresh: install bases; zero the deltas, or
        park `resid` in them (EF loop). Donates (cache, delta)."""
        raise NotImplementedError

    # -- buffer allocation / transfer (donation-aware) -----------------------

    def alloc_pool(self, shape, dtype, sharding):
        """A zeroed device pool in `sharding` — the donated-chain root.
        Allocated shard by shard: no device may hold more than its own
        part of the pool, even transiently."""
        raise NotImplementedError

    def install_pool(self, arr, sharding):
        """Host array -> device pool in `sharding` (checkpoint
        restore); the result enters the donated chain."""
        raise NotImplementedError

    def put_replicated(self, arr, sharding):
        """Stage a host array committed + replicated (the staging rule,
        parallel/mesh.py put_replicated)."""
        raise NotImplementedError

    def put_single(self, arr, device):
        """Host array -> one device (collective block staging)."""
        raise NotImplementedError

    # -- program construction ------------------------------------------------

    def compile(self, fn, **jit_kwargs):
        """Construct a device program from a traceable body (fused
        steps, app-scale fills). Accepts jax.jit keywords
        (donate_argnums, static_argnames, ...)."""
        raise NotImplementedError

    def compile_collective(self, fn, mesh, in_specs, out_specs,
                           check_vma: bool = True, **jit_kwargs):
        """Construct a per-shard collective program (shard_map + jit):
        `fn` runs per mesh shard with collective primitives available.
        `check_vma=False` leaves unchecked that a result given a
        replicated out_spec is the same on every shard (a body that
        calls a Pallas kernel cannot be checked: the fused step).
        Accepts jax.jit keywords like `compile` (the fused step donates
        its pools: `donate_argnums`)."""
        raise NotImplementedError


_default: Optional[DevicePort] = None


def default_port() -> DevicePort:
    """The process-wide port (one per process, like the dispatch gate:
    in-process device sets share one backend, so one port serves every
    server). Construction is lazy — importing the package never touches
    the device stack."""
    global _default
    if _default is None:
        from .jaxport import JaxDevicePort
        _default = JaxDevicePort()
    return _default


def set_default_port(port: Optional[DevicePort]) -> None:
    """Install a custom port (tests / alternative backends). None
    resets to lazy JaxDevicePort construction."""
    global _default
    _default = port
