"""Per-shard SPMD execution — the mapPartitions replacement.

The reference's per-partition compute (``mapPartitions(WithIndex)``, e.g.
``/root/reference/optimization/ma.py:84-87``) maps onto ``jax.shard_map``:
the body function sees the local block of each sharded operand and may call
collectives. ``replica_index`` is the analogue of the partition index that
``mapPartitionsWithIndex`` passes in.
"""

from __future__ import annotations

import jax
from jax import lax
from jax.sharding import Mesh

from tpu_distalg.parallel.mesh import DATA_AXIS


def replica_index(axis_name: str = DATA_AXIS):
    """Index of this shard along the axis (≙ the mapPartitionsWithIndex idx)."""
    return lax.axis_index(axis_name)


def data_parallel(fn, mesh: Mesh, *, in_specs, out_specs,
                  check_vma: bool = False):
    """Wrap ``fn`` as a shard_map over the mesh.

    ``in_specs``/``out_specs`` are PartitionSpecs; pass ``P('data')`` for
    RDD-like row-sharded operands and ``P()`` for broadcast (replicated)
    operands — mirroring exactly which reference values travelled via
    ``parallelize`` vs ``broadcast``.
    """
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )
