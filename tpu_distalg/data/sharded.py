"""``ShardedDataset`` — one block-addressable dataset, three placements.

SURVEY §2.2's verdict on the reference is that its real framework is
the RDD itself: ``.cache()`` is a hint, and ANY dataset can spill past
memory. Until this subsystem, that capability lived only inside the
streamed SSGD trainer (``models/ssgd_stream.py``) — k-means and ALS
silently capped at one chip's HBM. ``ShardedDataset`` owns the layer
once, for every workload:

  * the dataset is a logical ``(n_rows, row_width)`` matrix, sharded
    CONTIGUOUSLY over the mesh data axis (shard s owns rows
    ``[s·n_local, (s+1)·n_local)``) and addressed at BLOCK granularity
    (``block_rows`` consecutive rows — whole-block DMA is the shape the
    hardware wants; row-granular random access serializes, see
    ``ops/pallas_kernels.fused_grad_sum_gathered``);
  * three interchangeable backends place the SAME bytes differently:

      ``resident``   a device ``jax.Array`` (row-sharded over HBM) —
                     block gathers run on device;
      ``virtual``    a host-RAM ``np.ndarray`` — block gathers are one
                     fancy-index memcpy + async ``device_put``;
      ``streamed``   a disk ``np.memmap`` (a packed cache,
                     ``data/cache.py``) — same gather path, the OS page
                     cache is the only RAM footprint;

  * :meth:`stage` produces the identical staged device batch
    ``(n_shards, n_sampled·block_rows, row_width)`` whichever backend
    holds the bytes, so a training step jitted over staged batches has
    a BITWISE-identical trajectory across backends (asserted in
    tests/test_data.py — the property that makes ``--data-backend`` a
    placement knob, not an algorithm knob);
  * :meth:`stream` runs the pipeline engine (``data/pipeline.py``):
    one-deep background host-gather prefetch + double-buffered
    ``device_put`` so gather ∥ H2D ∥ compute — the machinery
    ``ssgd_stream`` proved, promoted to the subsystem.

Telemetry: gathers and H2D dispatches are ``data:gather`` /
``data:h2d`` spans with ``data.*`` counters (bytes, batches), so
``tda report`` shows where a streamed run spends its time.
"""

from __future__ import annotations

import numpy as np

from tpu_distalg import faults
from tpu_distalg.telemetry import events as tevents

BACKENDS = ("resident", "virtual", "streamed")


def block_geometry(n_rows: int, block_rows: int, n_shards: int,
                   fraction: float | None = None):
    """The block grid every out-of-core path samples on: rows per shard
    padded up to whole blocks, blocks per shard, and (when ``fraction``
    is given) blocks sampled per shard per step (since PR 57 retired
    its one caller in the package only ``tests/test_data.py`` calls it:
    ROADMAP D14).
    Returns ``(rows_per_shard, n_blocks, n_sampled)`` (``n_sampled``
    None when ``fraction`` is)."""
    rows_per_shard = -(-n_rows // (n_shards * block_rows)) * block_rows
    n_blocks = rows_per_shard // block_rows
    n_sampled = (None if fraction is None
                 else max(1, round(fraction * n_blocks)))
    return rows_per_shard, n_blocks, n_sampled


def _infer_backend(storage) -> str:
    if isinstance(storage, np.memmap):
        return "streamed"
    if isinstance(storage, np.ndarray):
        return "virtual"
    return "resident"  # a jax.Array (checked in __init__)


class ShardedDataset:
    """See the module docstring. ``storage`` is the ``(n2, pd)`` row
    matrix (device array, host array, or memmap); ``block_rows`` is the
    gather granularity in STORAGE rows (for pack>1 layouts that is
    packed rows — ``gather_block_rows // pack``); ``meta`` carries the
    layout geometry (e.g. the packed-kernel dict) for consumers."""

    def __init__(self, storage, mesh, *, block_rows: int,
                 meta: dict | None = None, backend: str | None = None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from tpu_distalg.parallel import (
            DATA_AXIS, data_parallel, mesh_on_tpu)

        self.backend = backend or _infer_backend(storage)
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown data backend {self.backend!r}; choose from "
                f"{BACKENDS}")
        n2, pd = storage.shape
        n_shards = mesh.shape[DATA_AXIS]
        if n2 % n_shards:
            raise ValueError(
                f"{n2} storage rows not divisible by {n_shards} shards")
        n2_local = n2 // n_shards
        if block_rows <= 0 or n2_local % block_rows:
            raise ValueError(
                f"per-shard rows {n2_local} not divisible by "
                f"block_rows={block_rows}")
        self.storage = storage
        self.mesh = mesh
        self.meta = dict(meta) if meta else {}
        self.block_rows = int(block_rows)
        self.n_shards = int(n_shards)
        self.n2 = int(n2)
        self.pd = int(pd)
        self.n2_local = int(n2_local)
        self.n_blocks = int(n2_local // block_rows)
        self.itemsize = int(np.dtype(storage.dtype).itemsize)
        self.shard_spec = NamedSharding(mesh, P(DATA_AXIS, None, None))
        self._row_offsets = np.arange(n_shards)[:, None] * n2_local
        # full-array reduction, PER SHARD (axes 1,2 only): the touch
        # runs concurrently with the consumer's previous step, and two
        # in-flight collective programs can deadlock a rendezvous on
        # backends that may start them out of order (seen on the CPU
        # mesh) — so the touch must contain NO cross-device collective.
        self._touch = jax.jit(
            lambda a: jnp.sum(a.astype(jnp.float32), axis=(1, 2)))
        if self.backend == "resident":
            if isinstance(storage, np.ndarray):
                raise ValueError(
                    "resident backend needs a device array — build one "
                    "with ShardedDataset.from_array(backend='resident')")
            bp = self.block_rows

            def _take(Xl, ids_l):
                rows = (ids_l[0][:, None] * bp
                        + jnp.arange(bp)[None, :]).reshape(-1)
                return Xl[rows][None]

            self._device_take = jax.jit(data_parallel(
                _take, mesh,
                in_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None)),
                out_specs=P(DATA_AXIS, None, None)))
        else:
            self._device_take = None
        # CPU-mesh emulation on few host cores starves the rendezvous
        # when several multi-device programs are in flight — consumers
        # (trainers) read this to serialize steps there.
        self.on_tpu = mesh_on_tpu(mesh)

    # ---- constructors ------------------------------------------------

    @classmethod
    def from_array(cls, array, mesh, *, block_rows: int,
                   meta: dict | None = None, backend: str = "virtual"):
        """Wrap an in-memory ``(n2, pd)`` matrix. ``backend='virtual'``
        keeps it in host RAM; ``backend='resident'`` places it
        row-sharded in device memory (the same bytes — staged batches
        stay bitwise-equal across the two)."""
        if backend == "resident":
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P

            from tpu_distalg.parallel import DATA_AXIS

            sharding = NamedSharding(mesh, P(DATA_AXIS, None))
            dev = jax.device_put(jnp.asarray(array), sharding)
            return cls(dev, mesh, block_rows=block_rows, meta=meta,
                       backend="resident")
        if backend == "streamed":
            raise ValueError(
                "backend='streamed' opens a disk cache — use "
                "ShardedDataset.from_cache")
        return cls(np.asarray(array), mesh, block_rows=block_rows,
                   meta=meta, backend=backend)

    @classmethod
    def from_cache(cls, path: str, mesh, *, block_rows: int,
                   layout: str | None = None,
                   expect_geom: dict | None = None):
        """Open a complete packed cache (``data/cache.py``) as the
        streamed backend; header/layout/geometry are validated."""
        from tpu_distalg.data import cache as dcache

        mm, header = dcache.open_cache(path, layout=layout,
                                       expect_geom=expect_geom)
        return cls(mm, mesh, block_rows=block_rows,
                   meta=dict(header.get("geom") or {}),
                   backend="streamed")

    # ---- the gather/stage/stream surface -----------------------------

    def h2d_bytes_per_step(self, n_sampled: int) -> int:
        """Bytes one staged batch moves host→device (0 for resident —
        the gather is an HBM-to-HBM copy, so an H2D roofline over it
        would be bogus)."""
        if self.backend == "resident":
            return 0
        return int(self.n_shards * n_sampled * self.block_rows
                   * self.pd * self.itemsize)

    def gather(self, ids_step: np.ndarray) -> np.ndarray:
        """The HOST side of staging one step: the fancy-index gather of
        the sampled blocks out of the (possibly disk-memmap) matrix —
        for a >RAM dataset this is the dominant per-step cost, which is
        why :meth:`stream` runs it on the prefetch thread. Pure numpy:
        safe off the JAX dispatch thread. ``ids_step`` is
        ``(n_shards, n_sampled)`` LOCAL block ids; returns
        ``(n_shards, n_sampled·block_rows, pd)``."""
        if self.backend == "resident":
            raise ValueError("resident datasets gather on device — "
                             "use stage()")
        bp = self.block_rows
        with tevents.span("data:gather", backend=self.backend):
            # chaos seam: on the streamed path this runs on the
            # prefetch producer thread, so an injected kill here dies
            # silently and exercises the consumer's liveness guard;
            # corrupt (no payload) models checksum-detected bad reads
            faults.inject("data:gather")
            rows = (ids_step[:, :, None] * bp
                    + np.arange(bp)[None, None, :]).reshape(
                        self.n_shards, -1)
            rows = rows + self._row_offsets
            out = self.storage[rows]
        tevents.counter("data.gather_batches")
        tevents.counter("data.gather_bytes", int(out.nbytes))
        return out

    def put(self, gathered: np.ndarray):
        """The DEVICE side: async H2D of one gathered batch onto the
        mesh, TOUCHED with a tiny async per-shard reduction so the
        transfer actually starts now — a lazy backend can defer
        ``device_put``'s copy (even past ``block_until_ready`` on its
        result) until first use, which would serialize the H2D behind
        the next step instead of overlapping it."""
        import jax

        with tevents.span("data:h2d", backend=self.backend,
                          bytes=int(gathered.nbytes)):
            faults.inject("data:h2d")
            staged = jax.device_put(gathered, self.shard_spec)
            self._touch(staged)  # async; result dropped
        tevents.counter("data.h2d_batches")
        tevents.counter("data.h2d_bytes", int(gathered.nbytes))
        return staged

    def stage(self, ids_step: np.ndarray):
        """One step's staged batch, any backend: serial gather+put for
        host storage (no prefetch, on purpose: the shape an H2D
        roofline is read from), a device-side block take for
        resident storage. Bytes are identical across backends."""
        if self.backend == "resident":
            import jax.numpy as jnp

            return self._device_take(
                self.storage, jnp.asarray(ids_step, jnp.int32))
        return self.put(self.gather(ids_step))

    def stream(self, ids: np.ndarray):
        """Staged batches for every step of ``ids`` ``(T, S, ns)``, in
        order, through the pipeline engine: host backends get the
        prefetch thread + double-buffered puts (gather(t+2) ∥ H2D(t+1)
        ∥ compute(t)); resident storage stages directly (device gathers
        are already async). Use ``contextlib.closing`` (or iterate to
        exhaustion) so an early exit stops the producer thread."""
        from tpu_distalg.data import pipeline

        return pipeline.stream_staged(self, ids)
