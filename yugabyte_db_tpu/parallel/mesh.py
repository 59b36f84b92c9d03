"""Device meshes mirroring tablet sharding.

The reference's intra-query parallelism clones one logical scan into
per-tablet requests and combines partial aggregates client-side
(reference: src/yb/yql/pggate/pg_doc_op.h:115-126
PopulateParallelSelectOps). The TPU-native equivalent maps tablet
shards onto a mesh axis ("tablets") so the combine is a `lax.psum`
riding ICI, and adds a second axis ("blocks") for splitting one huge
tablet's key-range across devices (the sequence-parallel analog of the
reference's GetTableKeyRanges chunking, src/yb/tablet/tablet.cc:5698).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


TABLETS_AXIS = "tablets"
BLOCKS_AXIS = "blocks"
ROW_AXES = (TABLETS_AXIS, BLOCKS_AXIS)


@dataclass(frozen=True)
class TabletMesh:
    mesh: Mesh

    @property
    def num_tablet_shards(self) -> int:
        return self.mesh.shape[TABLETS_AXIS]

    @property
    def num_block_shards(self) -> int:
        return self.mesh.shape.get(BLOCKS_AXIS, 1)

    def row_sharding(self) -> NamedSharding:
        """[S * N] row lanes cut over both axes, tablet-major: shard i
        holds rows [i * N, (i + 1) * N) as ONE-dimensional [N] — the
        shape a one-device lane has, so the chip lays a shard's lane
        out as it lays that one out (a [1, 1, N] shard pads each bool
        to four bytes and has to be relaid before the kernel reads it:
        PERF.md, PR 37)."""
        return NamedSharding(self.mesh, P(ROW_AXES))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())


def tablet_mesh(num_tablet_shards: Optional[int] = None,
                num_block_shards: int = 1,
                devices: Optional[Sequence] = None) -> TabletMesh:
    devices = list(devices if devices is not None else jax.devices())
    if num_tablet_shards is None:
        num_tablet_shards = len(devices) // num_block_shards
    need = num_tablet_shards * num_block_shards
    if need > len(devices):
        raise ValueError(
            f"mesh {num_tablet_shards}x{num_block_shards} needs {need} "
            f"devices, have {len(devices)}")
    arr = np.array(devices[:need]).reshape(num_tablet_shards,
                                           num_block_shards)
    return TabletMesh(Mesh(arr, (TABLETS_AXIS, BLOCKS_AXIS)))
