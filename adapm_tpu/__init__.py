"""adapm-tpu: a TPU-native adaptive parameter manager.

Capabilities of alexrenz/AdaPM (key→dense-vector store with intent-driven
relocation/replication and managed sampling), re-designed for JAX/XLA/Pallas
over TPU device meshes. See ARCHITECTURE.md and SURVEY.md.
"""
from .base import CLOCK_MAX, LOCAL, WORKER_FINISHED, MgmtTechniques  # noqa
from .config import SystemOptions  # noqa
from .core.kv import Server, Worker  # noqa
from .parallel.mesh import MeshContext, get_mesh_context, make_mesh  # noqa

__version__ = "0.1.0"


def setup(num_keys: int, value_lengths, opts=None, num_shards=None,
          num_workers=None):
    """Convenience: build a mesh + Server (reference `ps::Setup` +
    `ServerT server(...)`, apps/simple.cc:107-133). Under the launcher
    (ADAPM_COORDINATOR set), this also joins the multi-process runtime —
    the reference's Postoffice::Start + scheduler rendezvous. Compiled
    programs persist in the placeable compile cache
    (utils/compile_cache.py; `JAX_COMPILATION_CACHE_DIR`, docs/env.md)."""
    from .parallel import control
    from .utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    control.init_from_env()
    ctx = make_mesh(num_shards)
    return Server(num_keys, value_lengths, opts=opts, ctx=ctx,
                  num_workers=num_workers)
