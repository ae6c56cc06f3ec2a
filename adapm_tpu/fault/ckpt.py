"""Incremental dirty-slot checkpoints (ISSUE 10 tentpole, layer 1).

`utils/checkpoint.py` snapshots the WHOLE manager every time — at
NestPipe scale (PAPERS.md: recommendation models on 1,500+
accelerators) restart-from-full-checkpoint stops being viable, and the
r8/r10 write-epoch tracking already knows exactly which slots changed.
This module ships only those:

  - a **chain** lives in one directory: `base-000000.npz` (the full
    authoritative main tables + placement tables) followed by
    `delta-NNNNNN.npz` files, each holding only the main-row slots
    whose write epoch advanced since the previous link plus the
    currently-dirty replicas' (cache, delta) rows, plus any placement
    table that changed (ownership, replica map, clocks, intent
    horizons — skipped byte-identical, so a pure-push trickle's delta
    is rows + a few scalars);
  - every link is written **atomically** (tmp + fsync + rename) and
    carries a sha256 over its bytes; `chain.json` (also atomic) lists
    the links with their checksums AND each link's predecessor digest,
    so a truncated, bit-flipped, missing, or spliced link fails
    verification by name (`CheckpointCorruptError` /
    `CheckpointChainError`) — never a half-restore;
  - **restore** verifies and loads the ENTIRE chain into host memory
    first (the live server is untouched by any failure up to that
    point), then replays base + deltas under one topology-mutation
    critical section, rebuilds allocators/replica registries exactly
    like `utils.checkpoint.restore_server`, and resets write tracking.
    While the apply runs the server is DEGRADED (`Server.
    begin_degraded`): the serve plane sheds loudly with
    `ServeDegradedError` instead of risking a read that mixes pre- and
    post-restore bits (serve/batcher.py, serve/session.py).

Exactness argument (why replay == the state at the last save): every
path that can change a main row's VALUE bumps its `main_epoch` cell
under the server lock before the device program enqueues (core/
store.py), and the capture runs under that same lock with a device
readback that synchronizes with everything enqueued — so each link
captures exactly the cells changed since the previous link, with their
save-time bits, and cell-wise last-writer replay reconstructs the final
table. Replicas: a CLEAN replica (per `Server._dirty_replica_mask`) is
bitwise `cache == main row, delta == 0` — the dirty-filter invariant
tests/test_replica_table.py pins — so restore rebuilds clean replicas
from the replayed mains and overlays only the last link's captured
dirty (cache, delta) rows. Pinned by tests/test_fault.py and the
kill/restore drill (scripts/fault_drill_check.py).

Periodic operation: `--sys.checkpoint.every S --sys.checkpoint.path D`
runs `save()` as a self-rescheduling program on the executor's `ckpt`
stream (no thread; the executor-subsumption discipline of PR 6).
`Server.shutdown()` closes the checkpointer BEFORE pool teardown and
drains the `ckpt` stream, so an in-flight save never races the pools
out from under itself (ISSUE 10 satellite).

Multi-process is out of scope for the incremental chain (use
`utils.checkpoint.save_server`'s quiesced per-rank shards); save and
restore raise loudly under a GlobalPM.
"""
from __future__ import annotations

import hashlib
import io
import json
import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

MANIFEST_FORMAT = 1
FORMAT_VERSION = 1
MANIFEST_NAME = "chain.json"

# placement/meta tables captured per link iff changed since the
# previous link (byte-identical tables are skipped — a pure-push
# trickle's delta carries rows only)
_AUX_KEYS = ("owner", "slot", "cache_slot", "relocation_counter",
             "intent_end", "clocks")


class CheckpointCorruptError(RuntimeError):
    """A chain link's bytes do not match its recorded sha256 (truncated
    write, bit flip, unreadable archive). Raised during verification,
    BEFORE any server mutation."""


class CheckpointChainError(RuntimeError):
    """The chain itself is broken: missing manifest, missing/spliced
    link, non-contiguous sequence, predecessor-digest mismatch, or a
    geometry/format incompatibility with the restoring server. Raised
    during verification, BEFORE any server mutation."""


from ..utils import write_atomic as _write_atomic  # noqa: E402 — the
# shared tmp+fsync+rename discipline (adapm_tpu/utils; also used by the
# workload-trace recorder and the replay artifact writer)


def _npz_bytes(arrs: Dict[str, np.ndarray]) -> bytes:
    buf = io.BytesIO()
    np.savez_compressed(buf, **arrs)
    return buf.getvalue()


class IncrementalCheckpointer:
    """Owns one checkpoint chain for one (single-process) Server. The
    first `save()` writes the base; every later one a delta.
    Constructing a checkpointer on a directory STARTS A NEW CHAIN
    (existing links are superseded by the fresh manifest) — the resume
    workflow is restore_chain() first, then a new checkpointer."""

    def __init__(self, server, path: str):
        if server.glob is not None:
            raise NotImplementedError(
                "incremental checkpoint chains are single-process; "
                "multi-process jobs use utils.checkpoint.save_server's "
                "quiesced per-rank shards")
        if not path:
            raise ValueError("--sys.checkpoint.path is required for "
                             "incremental checkpoints")
        self.server = server
        self.path = path
        os.makedirs(path, exist_ok=True)
        self.chain_id = os.urandom(8).hex()
        self._entries: List[Dict] = []
        self._marks: List[int] = [0] * len(server.stores)
        self._aux_last: Dict[str, np.ndarray] = {}
        self._seq = 0
        import threading
        self._save_lock = threading.Lock()
        self._stop = False
        self._closed = False
        self._every_s = 0.0
        self._token = None
        # accounting (snapshot `ckpt` section; plain values — the
        # section is populated only when a checkpointer is attached)
        self.saves_total = 0
        self.bases_total = 0
        self.deltas_total = 0
        self.bytes_total = 0
        self.last_bytes = 0
        self.last_slots = 0
        self.last_kind = ""
        self.last_save_s = 0.0

    # -- capture -------------------------------------------------------------

    def _aux_arrays(self) -> Dict[str, np.ndarray]:
        srv = self.server
        ab = srv.ab
        out = {"owner": ab.owner, "slot": ab.slot,
               "cache_slot": ab.cache_slot,
               "relocation_counter": ab.relocation_counter,
               "intent_end": srv.sync.intent_end,
               "clocks": srv._clocks}
        # streaming plane (ISSUE 20): the acked-event cursor rides the
        # chain so a restore lands on "events [0, cursor) applied
        # exactly once". Captured under the SAME lock hold as the row
        # bits, and — like the trainer's cursor bump — never torn
        # against a push: both sides bracket with the server RLock.
        # Optional: pre-v16 chains (and stream-off servers) simply
        # never carry it, so it is NOT in _AUX_KEYS' mandatory set.
        if getattr(srv, "stream", None) is not None:
            out["stream_cursor"] = srv.stream.cursor
        return out

    def _capture_locked(self, kind: str):
        """Assemble one link's arrays (caller holds the server lock).
        Returns (arrs, new_marks, new_aux, slots_captured); the caller
        commits marks/aux only after the link is durably written."""
        srv = self.server
        ab = srv.ab
        arrs: Dict[str, np.ndarray] = {
            "format_version": np.int64(FORMAT_VERSION),
            "kind": np.frombuffer(kind.encode(), dtype=np.uint8).copy(),
            "num_keys": np.int64(srv.num_keys),
            "num_shards": np.int64(srv.num_shards),
        }
        if kind == "base":
            # compat metadata rides the base only: an O(num_keys)
            # array on every delta would put a floor under the very
            # bytes the incremental chain exists to shrink
            arrs["value_lengths"] = srv.value_lengths
        slots = 0
        new_marks = list(self._marks)
        for cid, st in enumerate(srv.stores):
            if kind == "base":
                arrs[f"main_{cid}"] = st.main_host()
                slots += int(st.main_shape_full[0] *
                             st.main_shape_full[1])
            else:
                sh, sl = np.nonzero(st.main_epoch > self._marks[cid])
                arrs[f"dsh_{cid}"] = sh.astype(np.int32)
                arrs[f"dsl_{cid}"] = sl.astype(np.int32)
                arrs[f"drows_{cid}"] = (
                    st.read_rows("main", sh.astype(np.int32),
                                 sl.astype(np.int32))
                    if len(sh) else
                    np.empty((0, st.value_length), dtype=np.float32))
                slots += len(sh)
            # the readback above synchronized with every enqueued
            # program; under the lock nothing new can land, so the
            # store's CURRENT epoch is the watermark this link covers
            new_marks[cid] = st._epoch
        # currently-dirty replicas: the restore rebuilds clean ones
        # from the replayed mains (clean == bitwise cache==main,
        # delta==0 — the dirty-filter invariant), so only these need
        # their (cache, delta) rows shipped
        shards, keys = np.nonzero(ab.cache_slot >= 0)
        if len(keys):
            keys = keys.astype(np.int64)
            shards = shards.astype(np.int32)
            dirty = srv._dirty_replica_mask(keys, shards)
            dk, ds = keys[dirty], shards[dirty]
        else:
            dk = np.empty(0, dtype=np.int64)
            ds = np.empty(0, dtype=np.int32)
        for cid, st in enumerate(srv.stores):
            if len(dk):
                in_cls = ab.key_class[dk] == cid
                ck, cs_sh = dk[in_cls], ds[in_cls]
            else:
                ck = np.empty(0, dtype=np.int64)
                cs_sh = np.empty(0, dtype=np.int32)
            cs = ab.cache_slot[cs_sh, ck].astype(np.int32) if len(ck) \
                else np.empty(0, dtype=np.int32)
            arrs[f"rsh_{cid}"] = cs_sh
            arrs[f"rcs_{cid}"] = cs
            if len(ck):
                arrs[f"rcache_{cid}"] = st.read_rows("cache", cs_sh, cs)
                arrs[f"rdelta_{cid}"] = st.read_rows("delta", cs_sh, cs)
            else:
                empty = np.empty((0, st.value_length), dtype=np.float32)
                arrs[f"rcache_{cid}"] = empty
                arrs[f"rdelta_{cid}"] = empty
        # placement/meta tables, skipped when byte-identical to the
        # previous link (aux churn, not row churn, would otherwise
        # dominate a small-model delta). Serialize the COPY taken
        # under the lock, never the live table: serialization happens
        # after the lock releases, and a concurrent relocation mutates
        # these arrays in place — a live reference would let the link
        # record placement from mid-mutation, inconsistent with the
        # row bits read back above
        new_aux: Dict[str, np.ndarray] = {}
        for name, arr in self._aux_arrays().items():
            prev = self._aux_last.get(name)
            if prev is None or not np.array_equal(prev, arr):
                snap = arr.copy()
                arrs[f"aux_{name}"] = snap
                new_aux[name] = snap
        return arrs, new_marks, new_aux, slots

    # -- save ----------------------------------------------------------------

    def save(self) -> Dict:
        """Write the next chain link (base first, deltas after):
        capture under the server lock, serialize, write atomically,
        then extend the manifest. Returns the manifest entry. A
        failure anywhere leaves the previous chain fully restorable
        (the manifest still describes only durably-written links)."""
        srv = self.server
        f = srv.fault
        if f is not None:
            f.fire("ckpt.save")
        with self._save_lock:
            t0 = time.perf_counter()
            kind = "base" if not self._entries else "delta"
            with srv._lock:
                arrs, new_marks, new_aux, slots = \
                    self._capture_locked(kind)
            blob = _npz_bytes(arrs)
            fname = f"{kind}-{self._seq:06d}.npz"
            _write_atomic(os.path.join(self.path, fname), blob)
            entry = {
                "seq": self._seq,
                "kind": kind,
                "file": fname,
                "bytes": len(blob),
                "slots": int(slots),
                "sha256": hashlib.sha256(blob).hexdigest(),
                "prev_sha256": (self._entries[-1]["sha256"]
                                if self._entries else ""),
                "wall_time": time.time(),
            }
            self._entries.append(entry)
            manifest = {"format": MANIFEST_FORMAT,
                        "chain_id": self.chain_id,
                        "entries": self._entries}
            _write_atomic(os.path.join(self.path, MANIFEST_NAME),
                          json.dumps(manifest, indent=1).encode())
            # commit the watermarks only now: had the write failed, the
            # next save would re-capture these slots (never lose them)
            self._marks = new_marks
            self._aux_last.update(new_aux)
            self._seq += 1
            self.saves_total += 1
            if kind == "base":
                self.bases_total += 1
            else:
                self.deltas_total += 1
            self.bytes_total += len(blob)
            self.last_bytes = len(blob)
            self.last_slots = int(slots)
            self.last_kind = kind
            self.last_save_s = time.perf_counter() - t0
            return entry

    # -- periodic operation (the `ckpt` executor stream) ---------------------

    def start_periodic(self, every_s: float) -> None:
        """Schedule `save()` every `every_s` seconds as a
        self-rescheduling delayed program on the `ckpt` stream (no
        sleeping thread). A failed save is logged and the cadence
        continues — the chain stays restorable to its last good link."""
        assert every_s > 0
        self._every_s = float(every_s)
        token = object()
        self._token = token

        def tick():
            from ..utils import alog
            if self._stop or self._token is not token:
                return
            try:
                self.save()
            except Exception as e:  # noqa: BLE001 — cadence survives
                # one failed save (injected or real I/O); the manifest
                # still describes only durable links
                f = self.server.fault
                if f is not None:
                    f.c_loop_retries.inc()
                alog(f"[ckpt] periodic save failed: "
                     f"{type(e).__name__}: {e}")
            if not self._stop and self._token is token:
                self.server.exec.submit("ckpt", tick, label="ckpt.save",
                                        coalesce_key="ckpt.save",
                                        delay=self._every_s)

        self.server.exec.submit("ckpt", tick, label="ckpt.save",
                                coalesce_key="ckpt.save",
                                delay=self._every_s)

    def close(self) -> None:
        """Stop the periodic program and drain the `ckpt` stream
        (idempotent). A save still in flight reads through the pools,
        so Server.shutdown() calls this BEFORE pool teardown; a save
        that cannot drain is wedged and fail-stops loudly instead of
        letting teardown pull the pools out from under it."""
        if self._closed:
            return
        self._closed = True
        self._stop = True
        ex = self.server.exec
        if not ex.closed and not ex.drain("ckpt", timeout=60):
            from ..utils import alog
            alog("[ckpt] checkpoint program failed to drain within 60s "
                 "of close — wedged mid-save")
            raise RuntimeError(
                "checkpoint program wedged: did not drain within 60s "
                "of close; refusing to proceed into pool teardown "
                "under a live reader")

    def stats(self) -> Dict:
        return {"saves_total": self.saves_total,
                "bases_total": self.bases_total,
                "deltas_total": self.deltas_total,
                "bytes_total": self.bytes_total,
                "last_bytes": self.last_bytes,
                "last_slots": self.last_slots,
                "last_kind": self.last_kind,
                "last_save_s": self.last_save_s,
                "chain_len": len(self._entries),
                "periodic_every_s": self._every_s}


# ---------------------------------------------------------------------------
# restore
# ---------------------------------------------------------------------------


def _load_manifest(path: str) -> Dict:
    mp = os.path.join(path, MANIFEST_NAME)
    if not os.path.exists(mp):
        raise CheckpointChainError(
            f"no checkpoint chain manifest at {mp}")
    try:
        with open(mp, "rb") as f:
            m = json.loads(f.read().decode())
    except (OSError, ValueError) as e:
        raise CheckpointCorruptError(
            f"chain manifest {mp} is unreadable: {e}") from e
    if m.get("format") != MANIFEST_FORMAT:
        raise CheckpointChainError(
            f"chain manifest format {m.get('format')!r} is "
            f"incompatible (expects {MANIFEST_FORMAT})")
    entries = m.get("entries") or []
    if not entries:
        raise CheckpointChainError(
            f"chain manifest {mp} lists no checkpoints")
    if entries[0].get("kind") != "base":
        raise CheckpointChainError(
            "chain does not start with a base checkpoint")
    for i, e in enumerate(entries):
        if e.get("seq") != i:
            raise CheckpointChainError(
                f"chain sequence broken at position {i}: manifest "
                f"lists seq {e.get('seq')!r} (a link is missing or "
                f"the manifest was edited)")
        if i > 0 and e.get("kind") != "delta":
            raise CheckpointChainError(
                f"unexpected {e.get('kind')!r} link at seq {i} "
                f"(only link 0 may be a base)")
    return m


def _load_verified_chain(path: str) -> List[Tuple[Dict, Dict]]:
    """Verify and load the whole chain into host memory. Every failure
    mode raises a NAMED error here, before the caller touches any
    server state."""
    m = _load_manifest(path)
    out: List[Tuple[Dict, Dict]] = []
    prev_sha = ""
    for e in m["entries"]:
        fp = os.path.join(path, e["file"])
        if not os.path.exists(fp):
            raise CheckpointChainError(
                f"missing chain link {e['file']} (seq {e['seq']}): "
                f"the manifest names it but the file is gone")
        with open(fp, "rb") as f:
            data = f.read()
        sha = hashlib.sha256(data).hexdigest()
        if sha != e.get("sha256"):
            raise CheckpointCorruptError(
                f"chain link {e['file']} (seq {e['seq']}) failed its "
                f"checksum ({len(data)} bytes on disk): truncated or "
                f"corrupt — refusing a half-restore")
        if e.get("prev_sha256", "") != prev_sha:
            raise CheckpointChainError(
                f"chain link {e['file']} (seq {e['seq']}) does not "
                f"chain to its predecessor (manifest edited or links "
                f"spliced from different chains)")
        prev_sha = sha
        try:
            with np.load(io.BytesIO(data), allow_pickle=False) as z:
                arrs = {k: z[k] for k in z.files}
        except Exception as e2:  # noqa: BLE001 — checksum passed but
            # the archive is unreadable: still a corrupt link
            raise CheckpointCorruptError(
                f"chain link {e['file']} is not a readable archive: "
                f"{e2}") from e2
        if int(arrs["format_version"]) != FORMAT_VERSION:
            raise CheckpointChainError(
                f"chain link {e['file']} has format "
                f"v{int(arrs['format_version'])} (expects "
                f"v{FORMAT_VERSION})")
        out.append((e, arrs))
    return out


def _check_compat(server, chain: List[Tuple[Dict, Dict]]) -> None:
    _, base = chain[0]
    if int(base["num_keys"]) != server.num_keys:
        raise CheckpointChainError(
            f"key count mismatch: chain has {int(base['num_keys'])}, "
            f"server has {server.num_keys}")
    if int(base["num_shards"]) != server.num_shards:
        raise CheckpointChainError(
            f"shard count mismatch: chain has "
            f"{int(base['num_shards'])}, server has "
            f"{server.num_shards}")
    if not (base["value_lengths"] == server.value_lengths).all():
        raise CheckpointChainError("value-length layout mismatch")
    for cid, st in enumerate(server.stores):
        got = base[f"main_{cid}"].shape
        if got != st.main_shape_full:
            raise CheckpointChainError(
                f"pool main_{cid} geometry mismatch: chain "
                f"{got} vs server {st.main_shape_full}")


def restore_chain(server, path: str,
                  hold_degraded_s: float = 0.0) -> float:
    """Verify + replay a checkpoint chain into a compatibly-constructed
    single-process Server. Returns the recovery wall time (seconds;
    also recorded as `ckpt.recovery_s` in metrics_snapshot).

    Failure contract: every verification error (`CheckpointChainError`
    / `CheckpointCorruptError` / geometry mismatch) raises BEFORE any
    server mutation — the live server keeps serving its current state.
    During the apply the server is DEGRADED: serve lookups shed loudly
    with `ServeDegradedError` (never a torn or mixed read); on apply
    success the flag clears, on an apply failure it stays set (the
    server's state is indeterminate — fail-stop, never quietly serve).

    `hold_degraded_s` keeps the degraded state up that much longer
    after a successful apply — an operational knob for drills and for
    deployments that gate traffic on an external health probe's
    observation window (scripts/fault_drill_check.py uses it to pin
    the shed-while-degraded contract deterministically)."""
    if server.glob is not None:
        raise NotImplementedError(
            "restore_chain is single-process; multi-process jobs use "
            "utils.checkpoint.restore_server")
    f = server.fault
    if f is not None:
        f.fire("ckpt.restore")
    t0 = time.perf_counter()
    chain = _load_verified_chain(path)
    _check_compat(server, chain)
    server.begin_degraded(
        f"checkpoint restore in progress ({path}, "
        f"{len(chain)} links)")
    _apply_chain(server, chain)
    recovery_s = time.perf_counter() - t0
    server._last_recovery_s = recovery_s
    if hold_degraded_s > 0:
        time.sleep(hold_degraded_s)
    server.end_degraded()
    return recovery_s


def _apply_chain(server, chain: List[Tuple[Dict, Dict]]) -> None:
    from ..utils.checkpoint import _rebuild_alloc, _rebuild_cache_alloc
    # latest version of each aux table across the chain (links skip
    # unchanged tables)
    aux: Dict[str, np.ndarray] = {}
    for _, arrs in chain:
        for name in _AUX_KEYS:
            k = f"aux_{name}"
            if k in arrs:
                aux[name] = arrs[k]
        # optional stream cursor (ISSUE 20): collected when present,
        # never required — pre-v16 chains and stream-off servers have
        # no aux_stream_cursor and must keep restoring cleanly
        if "aux_stream_cursor" in arrs:
            aux["stream_cursor"] = arrs["aux_stream_cursor"]
    missing = [n for n in _AUX_KEYS if n not in aux]
    if missing:
        raise CheckpointChainError(
            f"chain never captured table(s) {missing} (base link "
            f"incomplete)")
    _, final = chain[-1]
    with server._lock, server._topology_mutation():
        # leading bump: any concurrently-planned optimistic route fails
        # revalidation instead of dispatching pre-restore coordinates
        # (the restore_server discipline, utils/checkpoint.py)
        server.topology_version += 1
        ab = server.ab
        ab.owner[:] = aux["owner"]
        ab.slot[:] = aux["slot"]
        ab.cache_slot[:] = aux["cache_slot"]
        ab.reset_journal()  # tables rewritten: readers rebuild from them
        ab.relocation_counter[:] = aux["relocation_counter"]
        ab.replica_count[:] = (ab.cache_slot >= 0).sum(axis=0)
        server.sync.intent_end[:] = aux["intent_end"]
        server._clocks[:] = aux["clocks"]
        for wid, w in server._workers.items():
            w._clock = int(server._clocks[wid])
        if "stream_cursor" in aux:
            # acked-event horizon (ISSUE 20): recorded on the server
            # regardless of plane state, and written into the live
            # plane when one exists — a resumed StreamTrainer starts
            # from here and replay_tail() re-applies only the tail
            # between this and the pre-kill ack watermark
            cur = int(np.asarray(aux["stream_cursor"]).reshape(-1)[0])
            server._restored_stream_cursor = cur
            if getattr(server, "stream", None) is not None:
                server.stream.cursor[0] = cur

        rep_sh, rep_k = np.nonzero(ab.cache_slot >= 0)
        for cid, st in enumerate(server.stores):
            # replay: base table, then cell-wise last-writer deltas
            full = np.array(chain[0][1][f"main_{cid}"])
            for _, arrs in chain[1:]:
                dsh, dsl = arrs[f"dsh_{cid}"], arrs[f"dsl_{cid}"]
                if len(dsh):
                    full[dsh, dsl] = arrs[f"drows_{cid}"]
            if st.res is not None:
                from ..tier.coldpath import install_main_full
                install_main_full(st, full)
            else:
                st.main = st.port.install_pool(full, st.ctx.shard0())
            # replicas: clean ones are bitwise cache==main, delta==0;
            # the final link's captured dirty rows overlay that
            S = st.ctx.num_shards
            cache_host = np.zeros((S, st.cache_slots, st.value_length),
                                  dtype=full.dtype)
            delta_host = np.zeros_like(cache_host)
            if len(rep_k):
                in_cls = ab.key_class[rep_k] == cid
                ck, csh = rep_k[in_cls], rep_sh[in_cls]
                if len(ck):
                    cs = ab.cache_slot[csh, ck]
                    cache_host[csh, cs] = full[ab.owner[ck],
                                               ab.slot[ck]]
            rsh, rcs = final[f"rsh_{cid}"], final[f"rcs_{cid}"]
            if len(rsh):
                cache_host[rsh, rcs] = final[f"rcache_{cid}"]
                delta_host[rsh, rcs] = final[f"rdelta_{cid}"]
            sh0 = st.ctx.shard0()
            st.cache = st.port.install_pool(cache_host, sh0)
            st.delta = st.port.install_pool(delta_host, sh0)

        for cid in range(len(server.stores)):
            class_keys = np.nonzero(ab.key_class == cid)[0]
            _rebuild_alloc(ab.main_alloc[cid],
                           ab.owner[class_keys], ab.slot[class_keys])
            used_by_shard = [
                ab.cache_slot[s, class_keys]
                for s in range(server.num_shards)]
            _rebuild_cache_alloc(ab.cache_alloc[cid], used_by_shard)

        server.sync.replica_clear()
        shards, keys = np.nonzero(ab.cache_slot >= 0)
        server.sync.replica_add(keys.astype(np.int64),
                                shards.astype(np.int32))
        for st in server.stores:
            st.reset_write_tracking()
    if server.prefetch is not None:
        server.prefetch.invalidate_all()
    server.block()
