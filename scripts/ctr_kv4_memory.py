"""Memory of the four-shard CTR deployment (`benchmarks/configs/dlrm-dcnv2-
criteo1tb-kv4.json`) on a v5e 2x2 that is described, not attached: the
fused step's two variants over both length classes and the planner's
largest programs of each class, compiled at the cell's own sizes. Nothing
runs, so nothing here is a time. By hand, as `scripts/kv4_memory.py` and
for its reason (not a test):

    JAX_PLATFORMS=cpu python scripts/ctr_kv4_memory.py \
        [feature cache slots a shard] [main_over_alloc]
"""
import math
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
from adapm_tpu.models import dlrm  # noqa: E402
from adapm_tpu.ops import fused  # noqa: E402

N_FEAT, N_DENSE, B = 25_523_124, 15_676, 2048
HOT = [3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100, 27,
       10, 3, 1, 1]
L_FEAT, L_DENSE = 256, 2048


def _round8(n: int) -> int:
    return -8 * (-n // 8)


def main(cache: int, over_alloc: float) -> None:
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

    def pools_of(n_keys, L, cache_slots):
        per_shard = math.ceil(n_keys / 4)
        main = _round8(math.ceil(per_shard * over_alloc))
        c = _round8(min(cache_slots, n_keys))
        print(f"class of {n_keys} keys, rows of {L}: main {main} slots a "
              f"shard ({main * L * 4 / 1e9:.3f} GB), cache and delta {c} "
              f"each ({2 * c * L * 4 / 1e9:.3f} GB)", flush=True)
        return (shape((4, main, L), jnp.float32, rows),
                shape((4, c, L), jnp.float32, rows),
                shape((4, c, L), jnp.float32, rows))

    pools = (pools_of(N_FEAT, L_FEAT, cache),
             pools_of(N_DENSE, L_DENSE, cache))
    layout = dlrm.DenseLayout(dlrm.dense_tensors(
        13, 128, 26, [512, 256, 128], [1024, 1024, 512, 256, 1], 3, 512),
        1024)
    loss = dlrm.make_dlrm_loss(layout, HOT, 3, 3, 5)
    roles = {"feat": 0, "dense": 1}
    num_keys = N_FEAT + N_DENSE
    default_backend, jax.default_backend = jax.default_backend, \
        lambda: "tpu"
    for no_replicas in (True, False):
        step = fused.make_device_routed_step(
            loss, roles, {"feat": 128, "dense": 1024}, (), None, None,
            no_replicas)
        t0 = time.time()
        compiled = step.lower(
            pools, shape((13,), jnp.int32),
            tuple(shape((num_keys,), jnp.int32) for _ in range(2))
            + (shape((), jnp.int32),),
            {"feat": shape((sum(HOT), B), jnp.int32),
             "dense": shape((N_DENSE,), jnp.int32)},
            None, None, shape((2,), jnp.uint32),
            (shape((B, 13), jnp.float32), shape((B,), jnp.float32)),
            shape((), jnp.float32), shape((), jnp.float32)).compile()
        report(f"step, no_replicas={no_replicas}", compiled, t0)
        text = compiled.as_text()
        print("  write-back kernel calls:",
              text.count("custom_call_target=\"tpu_custom_call\""),
              "; all-reduces:", sorted(set(re.findall(
                  r"= (\(.*?\)|\S+) all-reduce(?:-start)?\(", text))),
              flush=True)
    jax.default_backend = default_backend

    def index(n):
        return jax.ShapeDtypeStruct((n,), jnp.int32)

    moved = 1 << 19     # the bucket of an intent's 438,272 feature keys
    for cls, pool, n_moved in (("feat", pools[0], moved),
                               ("dense", pools[1], 16384)):
        for name, fn, n_index, args, n in (
                ("sync_replicas", jaxport._sync_replicas, 4, pool,
                 4 * pool[1].shape[1]),
                ("relocate", jaxport._relocate, 6, (pool[0], pool[2]),
                 n_moved),
                ("replica_create", jaxport._replica_create, 4, pool,
                 n_moved)):
            t0 = time.time()
            report(f"{cls}: {name} at {n} rows",
                   fn.lower(*args, *[index(n)] * n_index).compile(), t0)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 262144,
         float(sys.argv[2]) if len(sys.argv) > 2 else 1.08)
