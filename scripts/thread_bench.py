"""Worker-thread scaling microbench (VERDICT r4 item 7).

The reference expects N worker THREADS per process to scale pull/push
throughput, protected by a 16384-entry per-key lock array
(handle.h:1069-1083). This bench measures BOTH locking disciplines:
`locked_routing` (route + stage + dispatch all under the one server
RLock — the pre-r5 design) and `optimistic` (the r5 default,
--sys.optimistic_routing: route + stage outside the lock against a
topology_version snapshot, only device dispatch serialized). Aggregate
pull and push ops/s at 1/2/4/8 threads hammering disjoint key slices
(the best case for per-key locks, the worst case for one coarse lock).

    python scripts/thread_bench.py            # prints one JSON line

Interpretation caveats:
  - on a 1-2 core host NOTHING scales (no parallelism to expose); run on
    a multi-core host to see the lock's cost, not the core count's
  - numpy routing and XLA dispatch release the GIL, so the RLock is the
    binding constraint once cores are available
"""
from __future__ import annotations

import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    from xla_compat import mesh_flags
    os.environ["XLA_FLAGS"] = (flags + " " + mesh_flags(8)).strip()

import numpy as np  # noqa: E402

K = 100_000
L = 64
BATCH = 1024
OPS = 30  # batched ops per thread per timing


def main() -> None:
    import jax
    jax.config.update("jax_platforms", "cpu")
    import adapm_tpu
    from adapm_tpu.config import SystemOptions

    # declared worker budget covers the per-N thread teams (ids must be
    # < num_workers; finalize() retires each team after its run)
    srv = adapm_tpu.setup(K, L, num_workers=64,
                          opts=SystemOptions(sync_max_per_sec=0,
                                             cache_slots_per_shard=1))
    w0 = srv.make_worker(0)
    rng = np.random.default_rng(0)
    vals = rng.normal(size=(K, L)).astype(np.float32)
    slab = 50_000
    for lo in range(0, K, slab):
        w0.set(np.arange(lo, min(lo + slab, K)), vals[lo:lo + slab])
    srv.block()

    next_wid = [8]  # ids 0-7 reserved for the init worker's team

    def bench(n_threads: int) -> dict:
        base = next_wid[0]
        next_wid[0] += n_threads
        workers = [srv.make_worker(base + i) for i in range(n_threads)]
        # disjoint key slices per thread: per-key locks would make these
        # perfectly parallel; one server lock serializes them
        slices = np.array_split(np.arange(K, dtype=np.int64), n_threads)
        rngs = [np.random.default_rng(t) for t in range(n_threads)]
        batches = [[rngs[t].choice(sl, BATCH) for _ in range(4)]
                   for t, sl in enumerate(slices)]
        ones = np.ones((BATCH, L), np.float32)

        def puller(t):
            w = workers[t]
            for i in range(OPS):
                w.pull_sync(batches[t][i % 4])

        def pusher(t):
            w = workers[t]
            for i in range(OPS):
                w.wait(w.push(batches[t][i % 4], ones))

        out = {}
        with ThreadPoolExecutor(n_threads) as ex:
            for name, fn in (("pull", puller), ("push", pusher)):
                list(ex.map(fn, range(n_threads)))  # warm
                t0 = time.perf_counter()
                list(ex.map(fn, range(n_threads)))
                dt = time.perf_counter() - t0
                out[name] = round(n_threads * OPS * BATCH / dt)
        for w in workers:
            w.finalize()
        return out

    # both locking disciplines (r5: optimistic routing moves route+stage
    # out of the server lock; --sys.optimistic_routing 0 is the old
    # route-under-lock behavior). On a 1-core host expect parity; on a
    # multi-core host the optimistic mode is the one that can scale.
    out = {"metric": "worker_thread_scaling",
           "host_cores": os.cpu_count(),
           "batch": BATCH, "value_bytes": 4 * L}
    for mode, opt in (("locked_routing", False), ("optimistic", True)):
        srv.opts.optimistic_routing = opt
        results = {n: bench(n) for n in (1, 2, 4, 8)}
        out[mode] = {
            "keys_per_s": results,
            "pull_scaling_8v1": round(results[8]["pull"] /
                                      results[1]["pull"], 2),
            "push_scaling_8v1": round(results[8]["push"] /
                                      results[1]["push"], 2),
        }
    print(json.dumps(out))
    srv.shutdown()


if __name__ == "__main__":
    main()
