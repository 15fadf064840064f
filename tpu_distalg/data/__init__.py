"""Out-of-core sharded datasets — the RDD replacement, for every workload.

The reference leans on Spark's RDD to make datasets bigger than memory
a non-problem (``.cache()`` is a hint; partitions spill and stream —
SURVEY §2.2). This package owns that capability ONCE, as a subsystem,
instead of per-trainer:

  ``sharded``   :class:`ShardedDataset` — one block-addressable row
                matrix behind three interchangeable placements
                (``resident`` on-device / ``virtual`` host-RAM /
                ``streamed`` disk-memmap), staging bitwise-identical
                device batches from any of them.
  ``cache``     the versioned packed-cache disk format: atomic publish
                (tmp + rename, header LAST), layout/version/dtype
                header, shard-aware slicing, stale-tmp sweep.
  ``pipeline``  the prefetch engine: one-deep background host-gather +
                double-buffered ``device_put`` so gather ∥ H2D ∥
                compute, plus the host-side threefry block sampler that
                keeps streamed trajectories bitwise-equal to resident
                ones.
  ``builders``  deterministic dataset builders (k-means mixture points,
                ALS rank-k rating rows) that place the same bytes
                behind whichever backend the ``--data-backend`` CLI
                knob asks for.

Consumers: ``models/ssgd_stream`` (ported onto this package),
``models/kmeans.fit_minibatch`` and ``models/als.fit_streamed`` (the
>HBM paths this subsystem opened), ``cli.py``.
Every pipeline stage emits telemetry (``data:gather`` / ``data:h2d`` /
``data:cache_build`` spans, ``data.*`` counters) so ``tda report``
shows where a streamed run spends its time.
"""

from tpu_distalg.data.sharded import (
    BACKENDS,
    ShardedDataset,
    block_geometry,
)
from tpu_distalg.data.pipeline import (
    Prefetcher,
    make_host_block_sampler,
    stream_staged,
)
from tpu_distalg.data import builders, cache

__all__ = [
    "BACKENDS",
    "Prefetcher",
    "ShardedDataset",
    "block_geometry",
    "builders",
    "cache",
    "make_host_block_sampler",
    "stream_staged",
]
