"""Mesh/runtime core and the collectives/dataflow layer.

This package is the Spark replacement (SURVEY.md §2.2): everything the
reference scripts obtained from ``spark.sparkContext`` — RDD creation,
broadcast, tree aggregation, keyed reduction, per-partition compute — has a
TPU-native equivalent here, built on ``jax.sharding`` meshes, ``shard_map``
and XLA collectives.
"""

from tpu_distalg.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    MeshContext,
    NoAcceleratorError,
    get_mesh,
    local_device_count,
    mesh_on_tpu,
    multihost_initialize,
)
from tpu_distalg.parallel.sharding import (
    ShardedMatrix,
    build_sharded,
    data_sharding,
    pad_rows,
    parallelize,
    replicate,
    replicated_sharding,
)
from tpu_distalg.parallel.collectives import (
    all_gather,
    all_to_all,
    tree_allreduce_mean,
    tree_allreduce_sum,
    ring_shift,
)
from tpu_distalg.parallel.comms import (
    CommSpec,
    CommSync,
    make_sync,
)
from tpu_distalg.parallel import membership, partition, ssp
from tpu_distalg.parallel.partition import RuleTable
from tpu_distalg.parallel.ssp import SyncSpec
from tpu_distalg.parallel.spmd import data_parallel, replica_index
from tpu_distalg.parallel.ring import (
    alltoall_head_to_seq,
    alltoall_seq_to_head,
    ring_allgather_matmul,
    ring_attention,
    softmax_attention,
    ulysses_attention,
    zigzag_inverse,
    zigzag_order,
)

__all__ = [
    "CommSpec",
    "CommSync",
    "DATA_AXIS",
    "MODEL_AXIS",
    "MeshContext",
    "NoAcceleratorError",
    "RuleTable",
    "ShardedMatrix",
    "SyncSpec",
    "make_sync",
    "membership",
    "partition",
    "ssp",
    "all_gather",
    "all_to_all",
    "alltoall_head_to_seq",
    "alltoall_seq_to_head",
    "build_sharded",
    "data_parallel",
    "data_sharding",
    "get_mesh",
    "local_device_count",
    "mesh_on_tpu",
    "multihost_initialize",
    "pad_rows",
    "parallelize",
    "replica_index",
    "replicate",
    "replicated_sharding",
    "ring_allgather_matmul",
    "ring_attention",
    "ring_shift",
    "softmax_attention",
    "tree_allreduce_mean",
    "tree_allreduce_sum",
    "ulysses_attention",
    "zigzag_inverse",
    "zigzag_order",
]
