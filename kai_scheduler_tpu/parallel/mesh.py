"""Device mesh construction for the scheduling kernels.

One logical axis matters for a scheduler: ``nodes`` — the cluster-state
axis every per-node tensor (idle/releasing/labels/taints/room) shards over.
It is the data-parallel axis of this workload; queue and job tables are
small and replicate.  On a multi-slice deployment the same axis maps over
DCN with per-slice ICI sub-rings (the analog of the reference's
SchedulingShard partitioning, schedulingshard_types.go:66-95).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NODE_AXIS = "nodes"


def cluster_mesh(n_devices: int | None = None,
                 devices=None) -> Mesh:
    """1-D mesh over the node axis: the first ``n_devices`` devices JAX
    reports (all of them when None).  Asking for more than there are is
    an error — a smaller mesh would run the work on fewer chips than
    the caller sized it for without saying so."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if n_devices > len(devices):
                raise ValueError(
                    f"mesh of {n_devices} devices requested but JAX "
                    f"reports only {len(devices)}")
            devices = devices[:n_devices]
    return Mesh(np.array(devices), (NODE_AXIS,))


def node_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for [N, ...] per-node arrays: rows split across chips."""
    return NamedSharding(mesh, P(NODE_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_to_mesh(n: int, mesh: Mesh) -> int:
    """Round the node count up to a multiple of the mesh size."""
    d = mesh.devices.size
    return -(-n // d) * d
