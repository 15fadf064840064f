"""Online serving layer — models answer requests, not just train.

Every workload used to end at a converged metric on disk; this package
is the half of the north star that answers a request. The shape is a
request-level micro-batching front end over the artifacts the training
workloads already checkpoint (``utils/checkpoint.py``):

  bounded queue → deadline-or-size dispatch → pad to a jit-stable
  batch shape → ONE batched predict (one host sync per BATCH, never
  per request) → scatter replies

Pieces:

  * :mod:`~tpu_distalg.serve.batcher` — the queue/dispatch loop
    (:class:`MicroBatcher`): bounded queue (full = shed, reply carries
    :class:`ServeOverloadError` — the server degrades instead of
    dying), every blocking wait carries a timeout (TDA060 polices
    both), per-batch telemetry spans and ``serve.*`` counters;
  * :mod:`~tpu_distalg.serve.artifacts` — checkpoint → servable model:
    LR scoring, k-means assignment, and ALS top-k recommendation
    through the fused Pallas matmul+top-k kernel
    (``ops/pallas_topk.py``) with item factors sharded over the mesh
    model axis and per-shard candidates merged via
    ``comms.ring_allgather`` (``8·B·k·(S−1)`` wire bytes per batch);
  * :mod:`~tpu_distalg.serve.server` — :class:`Server`: one batcher
    per served model, aggregate latency stats (p50/p99/QPS), the
    closed-loop load generator ``tda serve`` drives.

Padding is provably inert: a batch is always padded to exactly
``max_batch`` rows, so batched and unbatched requests run the SAME
compiled program and every reply is bitwise-equal to a single-request
submission (tests/test_serve.py pins it per served model).
"""

from tpu_distalg.serve.artifacts import (
    ServedModel,
    als_model,
    kmeans_model,
    load_artifact,
    lr_model,
)
from tpu_distalg.serve.batcher import (
    MicroBatcher,
    Reply,
    ServeClosedError,
    ServeOverloadError,
)
from tpu_distalg.serve.server import ServeConfig, Server

__all__ = [
    "MicroBatcher",
    "Reply",
    "ServeClosedError",
    "ServeConfig",
    "ServeOverloadError",
    "ServedModel",
    "Server",
    "als_model",
    "kmeans_model",
    "load_artifact",
    "lr_model",
]
