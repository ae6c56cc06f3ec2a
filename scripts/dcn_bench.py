"""Cross-process data-plane benchmark: what the DCN channel + GlobalPM
sustain between launched processes (the reference's ZMQ van numbers
analog — bytes and keys/s for remote Pull/Push and replica sync rounds).

Self-launches N processes through the launcher when run directly:

    python scripts/dcn_bench.py [n_procs]

Each rank times, against keys homed on the next rank:
  - remote pull  (keys/s, MiB/s)  — GlobalPM.request_pull round trips
  - remote push  (keys/s, MiB/s)  — GlobalPM.request_write round trips
  - sync rounds  (keys/s)         — replicate a working set via intent,
    then time planner rounds that extract deltas, ship them, and install
    fresh bases (pm.sync_replicas); reports the round's LIVE replica
    rows and raw-f32 vs --sys.sync.compress (fp16/int8) wire bytes per
    round (ISSUE 8 — the compressed program's future-DCN bytes)

Rank 0 prints one JSON line. CPU platform: this path is host+DCN-bound
by design; no TPU host has run it (PERF.md section 7, "Not measured").
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

K = 200_000
L = 64          # f32 per key -> 256 B values, the reference's mid-size rows
BATCH = 4096
ROUNDS = 20


def child() -> None:
    import adapm_tpu
    from adapm_tpu.config import SystemOptions
    from adapm_tpu.parallel import control

    srv = adapm_tpu.setup(K, L, opts=SystemOptions(
        sync_max_per_sec=0, collective_sync=True,
        collective_bucket=BATCH))
    rank = control.process_id()
    P = control.num_processes()
    assert P >= 2, "dcn_bench measures the CROSS-process data plane; " \
                   "launch with >= 2 processes"
    w = srv.make_worker(0)
    rng = np.random.default_rng(rank)
    pm = srv.glob

    keys = np.arange(K, dtype=np.int64)
    theirs = keys[pm.home_proc(keys) == (rank + 1) % P]
    srv.barrier()

    def timed(fn, n=ROUNDS):
        fn()  # warm (routing caches, lazy conns)
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        return (time.perf_counter() - t0) / n

    batch = rng.choice(theirs, BATCH, replace=False)
    vals = np.ones(BATCH * L, np.float32)

    t_pull = timed(lambda: pm.request_pull(batch))
    t_push = timed(lambda: pm.request_write(batch, vals, is_set=False))

    # single-peer concurrency: aggregate pull rate with C requests in
    # flight to the SAME peer (the channel demuxes by request id; pre-r4
    # a per-peer lock serialized these head-of-line)
    from concurrent.futures import ThreadPoolExecutor

    def pull_rate_inflight(c: int) -> float:
        batches = [rng.choice(theirs, BATCH, replace=False)
                   for _ in range(c)]
        with ThreadPoolExecutor(c) as ex:
            list(ex.map(pm.request_pull, batches))  # warm
            t0 = time.perf_counter()
            for _ in range(ROUNDS):
                list(ex.map(pm.request_pull, batches))
            dt = (time.perf_counter() - t0) / ROUNDS
        return c * BATCH / dt

    inflight = {c: round(pull_rate_inflight(c)) for c in (1, 2, 4)}

    # replicate the batch here: the OWNER rank must hold competing
    # interest first (exclusive intent would relocate instead —
    # sync_manager.h:624-644), so every rank intents its own keys, then
    # the cross intents are granted as replicas
    mine = keys[pm.home_proc(keys) == rank]
    w.intent(mine, w.current_clock, w.current_clock + 10_000)
    srv.wait_sync()
    srv.barrier()
    w.intent(batch, w.current_clock, w.current_clock + 10_000)
    srv.wait_sync()
    all_shards = np.full(len(batch), w.shard, np.int32)
    assert (srv.ab.cache_slot[w.shard, batch] >= 0).mean() > 0.9, \
        "expected the working set to be replicated"
    t_sync = timed(lambda: pm.sync_replicas(batch, all_shards))
    # wire bytes one sync round ships, counted from the round's LIVE
    # replica population (the r8 dirty filter and drop races can shrink
    # a round below BATCH — assuming full-width batch-sized deltas
    # overstates the plane). Raw = today's full-width f32 delta
    # direction; fp16/int8 = what the --sys.sync.compress wire formats
    # cost for the SAME rows (ISSUE 8; tier/quant.py wire table — the
    # future-DCN bytes/round the compressed sync program produces). The
    # fresh-base return direction stays full-width in every mode.
    from adapm_tpu.tier.quant import wire_bytes_per_row
    sync_rows = int((srv.ab.cache_slot[w.shard, batch] >= 0).sum())
    sync_wire = {m: sync_rows * wire_bytes_per_row(m, L)
                 for m in ("off", "fp16", "int8")}

    # channel overlap (VERDICT r4 item 9): the working set spans all sync
    # channels (Knuth-hash partition); per-channel rounds hold only their
    # channel's delta lock, so their DCN round-trips can overlap. Serial
    # baseline = the pre-r5 planner loop shape.
    from adapm_tpu.core.sync import key_channel
    nch = srv.sync.num_channels
    ch = key_channel(batch, nch)
    per_chan = [(batch[ch == cc], all_shards[ch == cc])
                for cc in range(nch)]
    per_chan = [p for p in per_chan if len(p[0])]

    def chan_serial():
        for k, s in per_chan:
            pm.sync_replicas(k, s)

    chan_pool = ThreadPoolExecutor(len(per_chan))

    def chan_overlap():
        list(chan_pool.map(lambda p: pm.sync_replicas(*p), per_chan))

    t_chan_serial = timed(chan_serial)
    t_chan_overlap = timed(chan_overlap)
    chan_pool.shutdown(wait=True)
    # the same replica-refresh traffic over the BSP collective data plane
    # (parallel/collective.py): both transports measured in one run so the
    # comparison answers "where each path wins" (VERDICT r3 item 1). All
    # ranks run `timed` with identical round counts, so every
    # collective_sync call is globally matched. The barrier separates the
    # RPC-timed loops above from the exchanges (collective_pull's
    # DEADLOCK RULE: a rank waiting in an exchange cannot serve RPCs)
    srv.barrier()
    t_coll = timed(lambda: pm.collective_sync(batch, all_shards))
    # pull/push over the exchange (VERDICT r4 item 4): the RPC rows above
    # are the baseline; on loopback RPC usually wins (no bucket padding,
    # no BSP join) — this records the protocol floor the way r4 did for
    # sync. All ranks run identical call counts (collective contract).
    t_cpull = timed(lambda: pm.collective_pull(batch))
    t_cpush = timed(lambda: pm.collective_push(batch, vals))

    srv.barrier()
    mib = BATCH * L * 4 / 2**20
    out = {
        "metric": "dcn_data_plane",
        "procs": P, "batch": BATCH, "value_bytes": L * 4,
        "pull_keys_per_s": round(BATCH / t_pull),
        "pull_MiB_per_s": round(mib / t_pull, 1),
        "push_keys_per_s": round(BATCH / t_push),
        "push_MiB_per_s": round(mib / t_push, 1),
        "pull_keys_per_s_inflight": inflight,
        "sync_round_ms": round(t_sync * 1e3, 2),
        "sync_keys_per_s": round(BATCH / t_sync),
        "sync_rows_per_round": sync_rows,
        "sync_delta_bytes_per_round": {
            "raw_fp32": sync_wire["off"],
            "fp16": sync_wire["fp16"],
            "int8": sync_wire["int8"]},
        "sync_compress_ratio": {
            "fp16": round(sync_wire["fp16"] / sync_wire["off"], 4),
            "int8": round(sync_wire["int8"] / sync_wire["off"], 4)},
        "sync_delta_MiB_per_s_raw": round(
            sync_wire["off"] / 2**20 / t_sync, 1),
        "chan_rounds": len(per_chan),
        "chan_serial_ms": round(t_chan_serial * 1e3, 2),
        "chan_overlap_ms": round(t_chan_overlap * 1e3, 2),
        "chan_overlap_speedup": round(t_chan_serial / t_chan_overlap, 2),
        "coll_sync_round_ms": round(t_coll * 1e3, 2),
        "coll_sync_keys_per_s": round(BATCH / t_coll),
        "coll_pull_keys_per_s": round(BATCH / t_cpull),
        "coll_push_keys_per_s": round(BATCH / t_cpush),
    }
    if rank == 0:
        print(json.dumps(out), flush=True)
    srv.barrier()
    srv.shutdown()


def main() -> None:
    if os.environ.get("ADAPM_PROCESS_ID") is not None:
        child()
        return
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 2
    from adapm_tpu import launcher
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    import subprocess
    coordinator = f"localhost:{launcher.free_port()}"
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env=launcher.make_env(r, n, coordinator, env))
        for r in range(n)]
    rc = []
    try:
        rc = [p.wait(timeout=420) for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    assert all(c == 0 for c in rc), rc


if __name__ == "__main__":
    main()
