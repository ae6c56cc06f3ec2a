"""The worker chip's share of the four-shard step in isolation, on ONE
chip (ISSUE 36): the fused step's replica variant
(`ops/fused.py _build_device_routed_body`, `no_replicas=False`) on pools
of one shard at the `kge-wikidata5m-kv4` cell's sizes, with tables in
which `--rep-share` of the worker's resident keys and `--named-share` of
the named positions are replicas. No exchange and no planner: what it
times is what the worker's chip does in a step of the per-chip program.

    chiprun --chips 1 -- python scripts/replica_step_probe.py
    chiprun --chips 1 -- python scripts/replica_step_probe.py --side-rows 4096

Prints `probe <tag>: <ms> ms a step` by the host's clock and from a
trace, the step's largest device operations (an operation inside a
`while` is listed beside the `while`), and the locality accumulator
(its last two entries: replica positions, side-path chunks).
`--repo DIR` imports the package from another tree (a `git archive` of
the parent, to time both in one call, one process each);
`--rehearse-cpu` debugs the script here at tiny sizes and prints no
device number. TPU only otherwise."""
from __future__ import annotations

import argparse
import functools
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repo", default=ROOT)
    ap.add_argument("--tag", default="change")
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--rep-share", type=float, default=0.0065)
    ap.add_argument("--named-share", type=float, default=0.33)
    ap.add_argument("--side-rows", type=int, default=None,
                    help="time the side path at another chunk size")
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, args.repo)
    sys.path.insert(0, os.path.join(args.repo, "benchmarks"))

    import jax
    import jax.numpy as jnp
    import trace_reduce
    from adapm_tpu.core.store import OOB
    from adapm_tpu.models.kge import make_kge_loss
    from adapm_tpu.ops import fused
    cpu = args.rehearse_cpu
    if jax.devices()[0].platform != ("cpu" if cpu else "tpu"):
        print("replica_step_probe.py: no TPU", file=sys.stderr)
        return 2
    tag = ("platform=cpu | " if cpu else "") + f"probe {args.tag}"
    if args.side_rows is not None:
        fused.SIDE_ROWS = args.side_rows
    if cpu:
        L, slots, num_keys, B, N, cache, resident = \
            256, 4096, 8192, 64, 32, 256, 4000
    else:  # benchmarks/configs/kge-wikidata5m-kv4.json, one shard
        L, slots, num_keys, B, N, cache, resident = \
            2048, 1_194_784, 4_595_309, 4096, 32, 32_768, 1_150_000

    rng = np.random.default_rng(1)
    owner = np.full(num_keys, 1, np.int32)  # elsewhere, but for:
    slot = np.full(num_keys, OOB, np.int32)
    cache_row = np.full(num_keys, -1, np.int32)
    here = rng.choice(num_keys, resident, replace=False)
    n_rep = min(int(resident * args.rep_share), cache)
    reps, mains = here[:n_rep], here[n_rep:]
    owner[mains] = 0
    slot[mains] = rng.permutation(slots)[:len(mains)]
    slot[reps] = rng.integers(0, slots, n_rep)  # their main copy's
    cache_row[reps] = rng.permutation(cache)[:n_rep]
    idx = np.sort(here).astype(np.int32)
    padded = np.full(1 << int(np.ceil(np.log2(len(idx)))),
                     np.iinfo(np.int32).max, np.int32)
    padded[:len(idx)] = idx
    local_index = (jnp.asarray(padded), jnp.int32(len(idx)))

    def named():
        k = rng.choice(mains, B)
        m = rng.random(B) < args.named_share
        if n_rep:
            k[m] = rng.choice(reps, m.sum())
        return jnp.asarray(k.astype(np.int32))

    roles = {"s": 0, "r": 0, "o": 0, "neg": 0}
    step = jax.jit(fused._build_device_routed_body(
        make_kge_loss("complex", 0.0, 0.0), roles,
        {r: L // 2 for r in roles}, (), "neg", (B, N), False, False),
        donate_argnums=(0,))

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def pool(n, v):  # built in place: two of them do not fit a chip
        return jnp.concatenate(
            [jnp.full((1, n, L // 2), v, jnp.float32),
             jnp.full((1, n, L // 2), 1e-3, jnp.float32)], axis=-1)
    pools = ((pool(slots, 0.01), pool(cache, 0.01),
              jnp.zeros((1, cache, L), jnp.float32)),)
    tables = (jnp.asarray(fused.place_words(owner, slot,
                                            fused.place_bits(slots))),
              jnp.asarray(cache_row), jnp.int32(0))
    # the parent of PR 36 has the four-entry accumulator
    stat = jnp.zeros(6 if hasattr(fused, "SIDE_ROWS") else 4, jnp.int32)
    keys = [{r: named() for r in ("s", "r", "o")} for _ in range(4)]
    rngs = iter(jax.random.split(jax.random.PRNGKey(3),
                                 3 + 2 * args.steps))
    lr, eps = jnp.float32(0.1), jnp.float32(1e-10)

    def run(n):
        nonlocal pools, stat
        for i in range(n):
            pools, stat, loss = step(pools, stat, tables, keys[i % 4],
                                     local_index, None, next(rngs), None,
                                     lr, eps)
        return jax.block_until_ready(loss)

    t0 = time.time()
    loss = run(3)
    print(f"{tag}: warm-up (compile) {time.time() - t0:.1f} s; loss "
          f"{float(loss):.6f}", flush=True)
    t0 = time.time()
    run(args.steps)
    print(f"{tag}: {(time.time() - t0) / args.steps * 1e3:.3f} ms a step "
          f"over {args.steps} steps (host clock)", flush=True)
    if not cpu:  # a CPU trace has no device plane: nothing to report
        trace_dir = os.path.join(ROOT, ".bench_trace",
                                 f"replica_step_probe_{args.tag}")
        jax.profiler.start_trace(trace_dir)
        run(args.steps)
        jax.profiler.stop_trace()
        red = trace_reduce.reduce_file(
            trace_reduce.find_xplane(trace_dir), 1)
        per_step = 1e3 / args.steps
        print(f"{tag}: device busy {red['busy_s'] * per_step:.3f} ms a "
              "step")
        for name, seconds in red["device_ops"][:args.top]:
            print(f"{tag}:   {seconds * per_step:8.3f} ms  {name[:110]}")
    print(f"{tag}: accumulator {np.asarray(stat).tolist()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
