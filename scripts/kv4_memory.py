"""Memory of the four-shard deployment (`benchmarks/configs/kge-wikidata5m-
kv4.json`) on a v5e 2x2 that is described, not attached: the fused step's
two variants and the planner's largest programs, compiled at the cell's own
sizes. Nothing runs, so nothing here is a time. Run by hand here in the
sandbox (about three minutes; not a test: a four-device compile for the
chip beside the test suite's workers stalled them):

    JAX_PLATFORMS=cpu python scripts/kv4_memory.py [cache_slots_per_shard]
"""
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from adapm_tpu.device import jaxport  # noqa: E402
from adapm_tpu.models.kge import make_kge_loss  # noqa: E402
from adapm_tpu.ops import fused  # noqa: E402

L, SLOTS, NUM_KEYS, B, N = 2048, 1_194_784, 4_595_309, 4096, 32


def main(cache: int) -> None:
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices[:4]), ("kv",))
    rows, rep = NamedSharding(mesh, P("kv")), NamedSharding(mesh, P())

    def shape(dims, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    def report(name, compiled, t0):
        m = compiled.memory_analysis()
        live = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        print(f"{name}: compiled in {time.time() - t0:.0f} s; arguments "
              f"{m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB, live "
              f"{live / 1e9:.3f} GB = {live / 2**30:.2f} GiB of 15.75",
              flush=True)

    pool = (shape((4, SLOTS, L), jnp.float32, rows),
            shape((4, cache, L), jnp.float32, rows),
            shape((4, cache, L), jnp.float32, rows))
    roles = {"s": 0, "r": 0, "o": 0, "neg": 0}
    # the step as the runner builds it: pools of four shards make it the
    # per-chip program, built as on a TPU (the write-back kernel inside)
    default_backend, jax.default_backend = jax.default_backend, \
        lambda: "tpu"
    for no_replicas in (True, False):
        step = fused.make_device_routed_step(
            make_kge_loss("complex", 0.0, 0.0), roles,
            {r: L // 2 for r in roles}, (), "neg", (B, N), no_replicas)
        t0 = time.time()
        compiled = step.lower(
            (pool,), shape((11,), jnp.int32),
            tuple(shape((NUM_KEYS,), jnp.int32) for _ in range(2))
            + (shape((), jnp.int32),),
            {r: shape((B,), jnp.int32) for r in roles if r != "neg"},
            (shape((1 << 21,), jnp.int32), shape((), jnp.int32)), None,
            shape((2,), jnp.uint32), None, shape((), jnp.float32),
            shape((), jnp.float32)).compile()
        report(f"step, no_replicas={no_replicas}", compiled, t0)
        text = compiled.as_text()
        print("  write-back kernel calls:",
              text.count("custom_call_target=\"tpu_custom_call\""),
              "; all-reduces:", sorted(set(re.findall(
                  r"= (\(.*?\)|\S+) all-reduce(?:-start)?\(", text))), flush=True)
    jax.default_backend = default_backend

    def index(n):
        return jax.ShapeDtypeStruct((n,), jnp.int32)

    for name, fn, n_index, args, n in (
            ("sync_replicas", jaxport._sync_replicas, 4, pool, 4 * cache),
            ("relocate", jaxport._relocate, 6, (pool[0], pool[2]), 16384),
            ("replica_create", jaxport._replica_create, 4, pool, 16384)):
        t0 = time.time()
        report(f"{name} at {n} rows",
               fn.lower(*args, *[index(n)] * n_index).compile(), t0)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 32768)
