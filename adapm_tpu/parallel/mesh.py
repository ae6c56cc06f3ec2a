"""Device mesh construction and sharding helpers.

Replaces the reference's process/topology bootstrap (Postoffice + Van ADD_NODE
rendezvous, src/van.cc:267-357): on TPU the "nodes" are mesh devices, rank
assignment is the mesh order, and the scheduler is `jax.distributed`'s
coordinator (multi-host) or nothing (single host).

The canonical mesh has one axis:
  - "kv": parameter shards (the reference's server dimension). Data-parallel
    workers are co-located with kv shards, mirroring the reference's co-located
    worker+server process model (README.md:161-165).

Model code may build richer meshes (e.g. ("data", "model")) on top; the KV
store only needs "kv".
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

KV_AXIS = "kv"


@dataclasses.dataclass
class MeshContext:
    mesh: Mesh

    @property
    def num_shards(self) -> int:
        return self.mesh.shape[KV_AXIS]

    @property
    def devices(self) -> Sequence[jax.Device]:
        return list(self.mesh.devices.flat)

    def shard0(self) -> NamedSharding:
        """Sharding for pool arrays [S, slots, L]: dim 0 over the kv axis."""
        return NamedSharding(self.mesh, P(KV_AXIS))

    def replicated(self) -> NamedSharding:
        if not hasattr(self, "_replicated"):
            self._replicated = NamedSharding(self.mesh, P())
        return self._replicated

    def put_replicated(self, arr):
        """Stage a host array for jitted programs: committed + replicated.
        This is THE staging rule: a
        device-0 `jnp.asarray` gets host-resharded by every mesh-compiled
        executable per call; a replicated device_put is asynchronous
        and already in the sharding executables expect. Routed through
        the DevicePort (ISSUE 14) — late import: the device plane sits
        above the mesh layer."""
        from ..device import default_port
        return default_port().put_replicated(arr, self.replicated())


def make_mesh(num_shards: Optional[int] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> MeshContext:
    if devices is None:
        # the platform is jax's own choice (JAX_PLATFORMS); multi-host:
        # each process's Server owns pools on ITS devices only (the
        # cross-process plane is the DCN channel + global sync rounds,
        # core/kv.py) — jax.devices() would include non-addressable peers
        devices = jax.local_devices() if jax.process_count() > 1 \
            else jax.devices()
    if num_shards is None:
        num_shards = len(devices)
    if num_shards > len(devices):
        raise ValueError(
            f"requested {num_shards} shards but only {len(devices)} devices")
    mesh = Mesh(np.asarray(devices[:num_shards]), (KV_AXIS,))
    return MeshContext(mesh=mesh)


_default_ctx: Optional[MeshContext] = None


def get_mesh_context() -> MeshContext:
    global _default_ctx
    if _default_ctx is None:
        _default_ctx = make_mesh()
    return _default_ctx


def set_mesh_context(ctx: MeshContext) -> None:
    global _default_ctx
    _default_ctx = ctx
