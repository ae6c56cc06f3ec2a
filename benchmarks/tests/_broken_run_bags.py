"""A run of the serving cell of the DLRM tables with its timed path broken
underneath, for test_bags_cell.py:

    member_dropped   the fused bag dispatch leaves the last member of the
                     batch out (its segment is set out of bounds, so the
                     pool drops it)
    offsets_shifted  every bag's inner offsets are shifted by one member
                     before the request is admitted: a bag loses its last
                     member to the next
    reply_altered    a served reply comes back with one element changed
    pooled_in_bf16   the fused program pools rows rounded to bfloat16
                     (the store stays float32): the precision below the
                     configuration's, inside the timed path

then everything else of a run, as `_broken_run.py`."""
import os
import sys

if "--rehearse-cpu" in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))


def member_dropped():
    import numpy as np
    from adapm_tpu.core.store import OOB
    from adapm_tpu.serve.batcher import LookupBatcher
    fused = LookupBatcher._lookup_bags_fused

    def broken(self, groups):
        for g in groups.values():
            g["seg"] = np.array(g["seg"])
            g["seg"][-1] = OOB
        return fused(self, groups)
    LookupBatcher._lookup_bags_fused = broken


def offsets_shifted():
    from adapm_tpu.serve import ServeSession
    lookup_bags = ServeSession.lookup_bags

    def broken(self, tables, bags, pooling="sum", deadline_ms=None):
        shifted = []
        for bg in bags:
            bg = bg.copy()
            bg[1:-1] -= 1
            shifted.append(bg)
        return lookup_bags(self, tables, shifted, pooling, deadline_ms)
    ServeSession.lookup_bags = broken


def reply_altered():
    from adapm_tpu.serve import ServeSession
    lookup_bags = ServeSession.lookup_bags

    def broken(self, tables, bags, pooling="sum", deadline_ms=None):
        out = [m.copy() for m in
               lookup_bags(self, tables, bags, pooling, deadline_ms)]
        out[-1].reshape(-1)[-1] += 1.0
        return out
    ServeSession.lookup_bags = broken


def pooled_in_bf16():
    import jax.numpy as jnp
    from adapm_tpu.device import jaxport
    pool = jaxport._pool_rows

    def broken(rows, seg, out, pooling):
        low = rows.astype(jnp.bfloat16).astype(rows.dtype)
        return pool(low, seg, out, pooling)
    jaxport._pool_rows = broken


if __name__ == "__main__":
    {"member_dropped": member_dropped, "offsets_shifted": offsets_shifted,
     "reply_altered": reply_altered,
     "pooled_in_bf16": pooled_in_bf16}[sys.argv[1]]()
    import run
    sys.exit(run.main(sys.argv[2:]))
