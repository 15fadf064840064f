"""Out-of-core power-law graph engine — streamed CSR PageRank.

Graph workloads were the last resident-only island: the fused SpMV
sweep (``ops/pallas_pagerank``) self-caps at 26M vertices on its VMEM
table budget and every resident path needs the full edge set in HBM,
while the SGD family has streamed >HBM datasets since the data
subsystem landed. This package closes that gap (ROADMAP open item 3):

  ``ingest``  edge lists → destination-sorted CSR edge-block caches in
              the versioned packed-cache disk format (``data/cache.py``
              atomic publish), native C++-accelerated with a
              byte-identical NumPy fallback; a chunked generator writes
              synthetic power-law graphs dst-sorted by construction so
              billion-edge caches never materialize the edge list.
  ``engine``  streamed frontier sweeps: blocks flow disk gather ∥ H2D ∥
              SpMV through the ``data/`` prefetch pipeline, per-shard
              partials accumulate in O(window) destination slices, and
              one ``comms.sparse_allreduce`` of each shard's distinct-
              destination (value, index) pairs combines them — sparse
              by construction on power-law graphs (arXiv:1312.3020),
              with ``comm.bytes_wire`` accounting proving the win over
              a dense O(V) psum. Only O(V) state lives on device.

Consumers: ``cli.py pagerank --data-backend streamed`` (and the
warn-and-degrade path when the resident VMEM guard trips),
``tda chaos --workload pagerank_stream``.
"""

from tpu_distalg.graphs.engine import (
    GraphDataset,
    StreamedPageRankConfig,
    StreamedPageRankResult,
    open_graph_dataset,
    resolve_combine,
    run_streamed_pagerank,
)
from tpu_distalg.graphs.ingest import (
    BLOCK_FORMAT_VERSION,
    DEFAULT_BLOCK_EDGES,
    LAYOUT,
    build_edge_block_cache,
    build_powerlaw_block_cache,
    powerlaw_in_degree_counts,
)

__all__ = [
    "BLOCK_FORMAT_VERSION",
    "DEFAULT_BLOCK_EDGES",
    "GraphDataset",
    "LAYOUT",
    "StreamedPageRankConfig",
    "StreamedPageRankResult",
    "build_edge_block_cache",
    "build_powerlaw_block_cache",
    "open_graph_dataset",
    "powerlaw_in_degree_counts",
    "resolve_combine",
    "run_streamed_pagerank",
]
