"""Whole-manager checkpoint/restore.

The reference checkpoints only at the application level (pull full model ->
write; resume = push inside BeginSetup/EndSetup — kge.cc:327-401, SURVEY.md
§5 "Checkpoint / resume"); its adaptive state (ownership, replicas) is lost
on restart. Here the *entire* manager state is a handful of arrays, so a
checkpoint captures it exactly: pools (main/cache/delta per length class),
addressbook tables, registered intent horizons, and worker clocks. Restore
rebuilds the free-list allocators and the sync manager's replica registry
from the tables, so an adapted placement survives a restart.

Multi-process: each rank writes `<path>.rank<r>.npz` with its local pools,
tables, and cross-process metadata (owner hints, relocation counters,
interest bitmasks), bracketed by the quiesce protocol (WaitSync -> Barrier
-> WaitSync) so the shards are mutually consistent; rank 0 also writes a
`<path>.manifest.npz` pinning the topology. Restore loads each rank's shard
into a freshly-launched job of the same shape — the adapted placement
(including cross-process relocations and replicas) survives the restart.
"""
from __future__ import annotations

import os
from typing import Dict

import numpy as np


# v3: pool slot counts are 8-aligned (core/store.py _round8), changing the
# saved raw-pool geometry — v2 checkpoints written before the alignment
# change cannot be restored into current pools and are rejected by version,
# not by an opaque shape assert.
FORMAT_VERSION = 3


def rank_path(path: str, rank: int) -> str:
    return f"{path}.rank{rank}.npz"


def manifest_path(path: str) -> str:
    return f"{path}.manifest.npz"


def save_server(server, path: str) -> None:
    """Write the full manager state (single-controller: one .npz;
    multi-process: per-rank shards + manifest, globally quiesced)."""
    if server.fault is not None:
        # ISSUE 10 injection point (shared with the incremental chain):
        # fires before any I/O, so a failed save leaves the previous
        # checkpoint intact
        server.fault.fire("ckpt.save")
    if server.glob is not None:
        # quiesce so every delta is merged and every base is fresh
        server.wait_sync()
        server.barrier()
        server.wait_sync()
        server.barrier()
    server.block()
    with server._lock:
        arrs: Dict[str, np.ndarray] = {
            "format_version": np.int64(FORMAT_VERSION),
            "num_keys": np.int64(server.num_keys),
            "num_shards": np.int64(server.num_shards),
            "num_procs": np.int64(server.num_procs),
            "pid": np.int64(server.pid),
            "value_lengths": server.value_lengths,
            "owner": server.ab.owner,
            "slot": server.ab.slot,
            "cache_slot": server.ab.cache_slot,
            "relocation_counter": server.ab.relocation_counter,
            "intent_end": server.sync.intent_end,
            "clocks": server._clocks,
        }
        if server.glob is not None:
            arrs["owner_hint"] = server.glob.owner_hint
            arrs["reloc"] = server.glob.reloc
            arrs["interest"] = server.glob.interest
        for cid, st in enumerate(server.stores):
            # main_host() is the authoritative full-size main table
            # whether or not the store is tiered (cold store overlaid
            # with the hot pool), so checkpoints restore across tier
            # configurations — residency is transient state, not saved
            arrs[f"main_{cid}"] = st.main_host()
            arrs[f"cache_{cid}"] = np.asarray(st.cache)
            arrs[f"delta_{cid}"] = np.asarray(st.delta)
    if server.glob is None:
        np.savez_compressed(path, **arrs)
        return
    np.savez_compressed(rank_path(path, server.pid), **arrs)
    if server.pid == 0:
        np.savez_compressed(manifest_path(path),
                            format_version=np.int64(FORMAT_VERSION),
                            num_procs=np.int64(server.num_procs),
                            num_shards=np.int64(server.num_shards),
                            num_keys=np.int64(server.num_keys))
    server.barrier()  # checkpoint complete on every rank


def restore_server(server, path: str) -> None:
    """Restore state saved by save_server into a compatibly-constructed
    Server (same num_keys, value_lengths, shard count, pool geometry;
    multi-process: same process count — each rank reads its own shard)."""
    if server.fault is not None:
        # fires before any mutation: a failed restore leaves the live
        # server serving its current state (ISSUE 10)
        server.fault.fire("ckpt.restore")
    if server.glob is not None:
        mf = np.load(manifest_path(path))
        assert int(mf["num_procs"]) == server.num_procs, \
            "process count mismatch (elastic restore is not supported)"
        ck = np.load(rank_path(path, server.pid))
        assert int(ck["pid"]) == server.pid
    else:
        ck = np.load(path if os.path.exists(path) else rank_path(path, 0))
        assert int(ck["num_procs"]) == 1, (
            "this is one rank shard of a multi-process checkpoint; restore "
            "it under a launcher with the same process count")
    got = int(ck["format_version"])
    if got != FORMAT_VERSION:
        raise ValueError(
            f"checkpoint format v{got} is incompatible with this build "
            f"(expects v{FORMAT_VERSION}; v2->v3 changed pool geometry to "
            f"8-aligned slot counts) — re-export from the writing version")
    assert int(ck["num_keys"]) == server.num_keys, "key count mismatch"
    assert int(ck["num_shards"]) == server.num_shards, "shard mismatch"
    assert (ck["value_lengths"] == server.value_lengths).all(), \
        "value-length layout mismatch"
    # the whole addressbook is rewritten below (direct table writes, not
    # counted ab methods): run under the topology-mutation discipline so
    # the trailing version bump is the last mutation before the lock
    # releases, and keep the leading manual bump so any concurrently-
    # planned optimistic route (core/kv.py _plan_pull/_plan_push) fails
    # revalidation instead of dispatching pre-restore coordinates into
    # the restored pools
    with server._lock, server._topology_mutation():
        server.topology_version += 1
        ab = server.ab
        ab.owner[:] = ck["owner"]
        ab.slot[:] = ck["slot"]
        ab.cache_slot[:] = ck["cache_slot"]
        ab.reset_journal()  # tables rewritten: readers rebuild from them
        ab.relocation_counter[:] = ck["relocation_counter"]
        ab.replica_count[:] = (ab.cache_slot >= 0).sum(axis=0)
        server.sync.intent_end[:] = ck["intent_end"]
        server._clocks[:] = ck["clocks"]
        # Workers registered before the restore carry their own _clock and
        # write it back on advance_clock — re-seed them so the first advance
        # after a restore can't regress the restored clocks (intent windows
        # and replica expiry are computed from these).
        for wid, w in server._workers.items():
            w._clock = int(server._clocks[wid])

        # pools back onto the mesh with their original shardings
        for cid, st in enumerate(server.stores):
            sh = st.ctx.shard0()
            for name in ("main", "cache", "delta"):
                arr = ck[f"{name}_{cid}"]
                if name == "main":
                    # checkpoints carry the authoritative FULL main
                    # table (save_server main_host()); geometry is
                    # tier-independent
                    assert arr.shape == st.main_shape_full, (
                        f"pool main_{cid} geometry mismatch: checkpoint "
                        f"{arr.shape} vs server {st.main_shape_full}")
                    if st.res is not None:
                        # tiered restore: the table becomes the cold
                        # store and residency resets — everything cold,
                        # re-promoted lazily on access/intent (the
                        # device hot pool's stale rows are unmapped and
                        # never read)
                        from ..tier.coldpath import install_main_full
                        install_main_full(st, arr)
                        continue
                else:
                    cur = getattr(st, name)
                    assert arr.shape == cur.shape, (
                        f"pool {name}_{cid} geometry mismatch: "
                        f"checkpoint {arr.shape} vs server {cur.shape}")
                setattr(st, name, st.port.install_pool(arr, sh))

        # rebuild free lists from table occupancy
        for cid in range(len(server.stores)):
            class_keys = np.nonzero(ab.key_class == cid)[0]
            _rebuild_alloc(ab.main_alloc[cid],
                           ab.owner[class_keys], ab.slot[class_keys])
            used_by_shard = [
                ab.cache_slot[s, class_keys] for s in range(server.num_shards)]
            _rebuild_cache_alloc(ab.cache_alloc[cid], used_by_shard)

        # rebuild the sync manager's replica registry (one vectorized
        # channel-grouped insert, never per key), and reset the stores'
        # write-epoch tracking: the restored pools' replica bases may
        # predate their main rows, so everything starts dirty and the
        # first sync round re-ships every live replica once
        server.sync.replica_clear()
        shards, keys = np.nonzero(ab.cache_slot >= 0)
        server.sync.replica_add(keys.astype(np.int64),
                                shards.astype(np.int32))
        for st in server.stores:
            st.reset_write_tracking()
        if server.glob is not None:
            server.glob.owner_hint[:] = ck["owner_hint"]
            server.glob.reloc[:] = ck["reloc"]
            server.glob.interest[:] = ck["interest"]
    if server.prefetch is not None:
        # staged pull buffers predate the restore; the version bump
        # already invalidates them lazily — drop them now to release
        # their staging-pool rows promptly
        server.prefetch.invalidate_all()
    server.block()
    if server.glob is not None:
        server.barrier()  # all ranks restored before traffic resumes


def _rebuild_alloc(alloc, owners: np.ndarray, slots: np.ndarray) -> None:
    for s in range(alloc.num_shards):
        alloc.set_used(s, slots[owners == s])


def _rebuild_cache_alloc(alloc, used_by_shard) -> None:
    for s in range(alloc.num_shards):
        row = np.asarray(used_by_shard[s])
        alloc.set_used(s, row[row >= 0])
