"""Device-mesh topology for the framework's distribution axes.

The reference distributes work along two axes: **virtual shards** (4096-way
murmur3 hash of the series ID, `src/dbnode/sharding/shardset.go:148-163`)
mapped to instances by a placement (`src/cluster/placement/algo/sharded.go`),
and **replicas** (RF=3 fan-out with quorum consistency,
`src/dbnode/topology/consistency_level.go:36-46`).  The TPU-native design
maps both onto one `jax.sharding.Mesh`:

* ``shard`` axis — series-shard data parallelism.  Device arrays carry a
  leading logical-shard axis laid out over this mesh axis; a series lives on
  exactly one shard (slot allocation is per-shard, host-side).  Intra-shard
  traffic that the reference sends over TChannel becomes ICI collectives.
* ``replica`` axis — redundancy.  State is replicated across this axis;
  cross-replica checksum comparison (the repair path,
  `src/dbnode/storage/repair.go:115-246`) is a cheap `ppermute`/compare
  on device instead of a metadata RPC sweep.

Multi-host scaling keeps the same program: the mesh simply spans hosts, XLA
routes `psum`/`all_gather` over ICI within a slice and DCN across slices —
replacing the reference's NCCL/MPI-analogous TChannel+protobuf data plane
(SURVEY.md §5.8).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARD_AXIS = "shard"
REPLICA_AXIS = "replica"


def enable_cpu_core_devices(n: int | None = None) -> None:
    """One virtual CPU device per core (default: os.cpu_count()), so
    series-sharded programs (parallel/sharded_decode.py) can use every
    core — XLA-CPU runs their small per-op arrays single-threaded, and
    the bench's native C++ yardstick threads across cores.

    Must run BEFORE the backend initializes (first jnp/jit/devices()
    touch); afterwards the knob is inert.
    """
    import os

    n = n or max(1, os.cpu_count() or 1)
    jax.config.update("jax_num_cpu_devices", n)


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replica check disabled — the one
    seam every shard_map program in the tree goes through."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@dataclasses.dataclass(frozen=True)
class MeshTopology:
    """A (shard × replica) device mesh plus its canonical shardings."""

    mesh: Mesh

    @property
    def num_shards(self) -> int:
        return self.mesh.shape[SHARD_AXIS]

    @property
    def num_replicas(self) -> int:
        return self.mesh.shape[REPLICA_AXIS]

    def sharded(self, *trailing: None) -> NamedSharding:
        """Sharding for arrays with a leading logical-shard axis."""
        return NamedSharding(self.mesh, P(SHARD_AXIS, *trailing))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())


def make_mesh(
    num_shards: int | None = None,
    num_replicas: int = 1,
    devices=None,
) -> MeshTopology:
    """Build the (shard, replica) mesh over the available devices.

    Defaults to all devices on the shard axis, RF=1.  The reference's RF=3
    corresponds to ``num_replicas=3`` (each replica group holds a full copy
    of every shard, as an M3 placement does).
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if num_shards is None:
        if n % num_replicas != 0:
            raise ValueError(f"{n} devices not divisible by RF={num_replicas}")
        num_shards = n // num_replicas
    if num_shards * num_replicas != n:
        raise ValueError(
            f"mesh {num_shards}x{num_replicas} != {n} devices"
        )
    arr = np.asarray(devices).reshape(num_shards, num_replicas)
    return MeshTopology(Mesh(arr, (SHARD_AXIS, REPLICA_AXIS)))
