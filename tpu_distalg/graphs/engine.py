"""Streamed frontier sweeps over edge-block caches — out-of-core PageRank.

The compute half of the graph engine: the edge set lives on disk
(``graphs/ingest.py`` block caches), is streamed through the data
subsystem's prefetch pipeline (disk gather ∥ H2D ∥ SpMV — the same
``Prefetcher`` machinery that feeds the >HBM SGD trainers), and only
the O(V) state — rank vector, out-degree mask, per-shard window
accumulators — ever resides in device memory. This lifts the vertex
ceiling from the resident SpMV path's 26M (its VMEM table budget,
``ops/pallas_pagerank.SPMV_VMEM_BUDGET``) to whatever the disk holds.

One power iteration = map over edge blocks, then one sparse reduce
(the DrJAX ``map_fn``/``reduce`` shape, arXiv:2403.07128):

    ranks (V,) replicated ──┐
                            ▼
    disk blocks ─ gather ─ H2D ─▶ per-shard window accumulate
      (prefetch thread)  (async)   acc[s] += segsum(ranks[src]·w)
                            │      (O(window) per shard, dst-local)
                            ▼
          sparse rank combine: each shard contributes its k distinct-
          destination (value, index) pairs → comms.sparse_allreduce
          → dense (V,) contribution sum, replicated bitwise-identically
                            ▼
          ranks' = q/V + (1−q)·(c + dangling/V)

Because a shard's blocks cover a contiguous destination window of a
globally dst-sorted edge list, its partial sums are sparse *by
construction*: ``k`` is the shard's distinct-destination count, which
on power-law graphs is a small fraction of V (most vertices have no
in-links) — the Sparse Allreduce observation (arXiv:1312.3020) applied
to rank vectors. The combine's wire bytes (``8k(n−1)`` pair bytes vs a
dense psum's ``4V·2(n−1)/n``) are accounted by
``comms.rank_combine_stats`` and emitted as ``comm.bytes_wire``
counters; ``combine='auto'`` picks whichever accounting is smaller for
the graph at hand (ER graphs are dense-favored; power-law sparse).

Bitwise contracts (tests/test_graphs.py):

  * streamed ≡ virtual ≡ resident backends — the ShardedDataset stages
    identical bytes, every jitted fn is shared, so ``--data-backend``
    is a placement knob here exactly as it is for SGD;
  * runs are deterministic and the combine's output replicated
    identically on every shard (origin-order accumulation in
    ``sparse_allreduce``);
  * segmented/checkpointed runs resume bitwise (iterations are
    time-invariant; PR 3's ``run_segmented`` machinery), and the
    streamed gather/H2D path passes through the ``data:gather`` /
    ``data:h2d`` fault seams — ``tda chaos --workload pagerank_stream``
    proves undisturbed ≡ chaos.
"""

from __future__ import annotations

import contextlib
import dataclasses

import numpy as np

from tpu_distalg.data import cache as dcache
from tpu_distalg.graphs import ingest
from tpu_distalg.telemetry import events as tevents

COMBINES = ("auto", "sparse", "dense")


@dataclasses.dataclass(frozen=True)
class StreamedPageRankConfig:
    """Standard-mode PageRank over a streamed edge-block cache (the
    reference-parity mode needs per-vertex receive masks — a resident-
    scale concern; at out-of-core scale you want textbook PageRank)."""

    n_iterations: int = 10
    q: float = 0.15
    redistribute_dangling: bool = True
    batch_blocks: int = 4       # blocks per shard per staged step
    combine: str = "auto"       # 'auto' | 'sparse' | 'dense'

    def __post_init__(self):
        if self.combine not in COMBINES:
            raise ValueError(
                f"unknown combine {self.combine!r}; choose from "
                f"{COMBINES}")


@dataclasses.dataclass
class StreamedPageRankResult:
    ranks: "object"             # (V,) f32 jax.Array
    n_iterations_run: int
    combine: str                # the resolved combine ('sparse'/'dense')
    comm_stats: dict            # per-sync rank_combine_stats accounting


@dataclasses.dataclass
class GraphDataset:
    """An opened edge-block cache plus its device-resident O(V)/O(k)
    side state — everything a sweep needs besides the streamed blocks."""

    ds: "object"                # ShardedDataset of packed edge rows
    header: dict
    lo: "object"                # (S,) int32, sharded: window base dst
    didx: "object"              # (S, k) int32, sharded: local offsets
    dmask: "object"             # (S, k) f32, sharded: pair validity
    has_out: "object"           # (V,) f32, replicated

    @property
    def geom(self) -> dict:
        return self.header["geom"]

    @property
    def n_vertices(self) -> int:
        return int(self.geom["n_vertices"])

    @property
    def n_edges(self) -> int:
        return int(self.geom["n_edges"])

    @property
    def window(self) -> int:
        return int(self.geom["window"])

    @property
    def k_sparse(self) -> int:
        return int(self.geom["k_sparse"])

    @property
    def n_shards(self) -> int:
        return int(self.geom["n_shards"])


def open_graph_dataset(path: str, mesh, *, backend: str = "streamed",
                       legacy_geom: dict | None = None) -> GraphDataset:
    """Open a COMPLETE edge-block cache behind any data backend.

    ``streamed`` memmaps the bin (the out-of-core mode this engine
    exists for); ``virtual``/``resident`` materialize the same bytes in
    host/device memory — small-scale placements whose sweeps are
    bitwise-equal to streamed (the golden-test contract). The cache's
    shard geometry must match the mesh: windows are baked at ingest.

    ``legacy_geom``: a cache whose meta.json is the bare flat geometry
    dict (the pre-versioned header style) reopens when it matches, with
    the memmap reconstructed from the geometry — the same courtesy
    ``data/cache.py`` extends PR 1 caches.
    """
    import jax
    import jax.numpy as jnp

    from tpu_distalg.data.sharded import ShardedDataset
    from tpu_distalg.parallel import DATA_AXIS
    from tpu_distalg.parallel.sharding import data_sharding

    mm, header = dcache.open_cache(path, layout=ingest.LAYOUT,
                                   legacy_geom=legacy_geom)
    geom = header["geom"]
    if int(geom.get("bv", -1)) != ingest.BLOCK_FORMAT_VERSION:
        raise ValueError(
            f"edge-block cache at {path!r} has block format "
            f"bv={geom.get('bv')!r}; this engine speaks "
            f"bv={ingest.BLOCK_FORMAT_VERSION} — re-ingest the edges")
    n_shards = int(mesh.shape[DATA_AXIS])
    if int(geom["n_shards"]) != n_shards:
        raise ValueError(
            f"edge-block cache at {path!r} was ingested for "
            f"{geom['n_shards']} shards; this mesh has {n_shards} — "
            f"shard windows are baked at ingest, re-ingest for this "
            f"mesh (or open on a matching one)")
    if mm is None:
        # legacy flat-meta reopen: the versioned header's dtype/shape
        # are reconstructible from the geometry alone
        gran = int(geom["n_shards"]) * int(geom["block_edges"])
        n_rows = -(-int(geom["n_edges"]) // gran) * gran
        mm = np.memmap(dcache.bin_path(path), dtype=np.int32, mode="r",
                       shape=(n_rows, ingest.ROW_WIDTH))
    deg, didx, dmask = ingest.read_aux(path, geom)
    block_edges = int(geom["block_edges"])
    if backend == "streamed":
        ds = ShardedDataset(mm, mesh, block_rows=block_edges,
                            meta=dict(geom), backend="streamed")
    elif backend in ("virtual", "resident"):
        ds = ShardedDataset.from_array(
            np.asarray(mm), mesh, block_rows=block_edges,
            meta=dict(geom), backend=backend)
    else:
        raise ValueError(
            f"unknown graph data backend {backend!r}; choose from "
            f"('resident', 'virtual', 'streamed')")
    s1 = data_sharding(mesh, 1)
    s2 = data_sharding(mesh, 2)
    return GraphDataset(
        ds=ds, header=header,
        lo=jax.device_put(jnp.asarray(geom["lo"], jnp.int32), s1),
        didx=jax.device_put(jnp.asarray(didx), s2),
        dmask=jax.device_put(jnp.asarray(dmask), s2),
        has_out=jnp.asarray((deg > 0).astype(np.float32)))


def resolve_combine(combine: str, k: int, length: int, n: int) -> str:
    """'auto' picks the schedule whose accounting moves fewer bytes for
    this graph: sparse pair exchange (``8k(n−1)``) vs dense ring psum
    (``4V·2(n−1)/n``) — power-law graphs go sparse, uniform-random
    (ER) graphs whose distinct-destination count approaches V/n go
    dense. Deterministic in the cache geometry, so backend A/B runs
    resolve identically."""
    from tpu_distalg.parallel import comms

    if combine != "auto":
        return combine
    st = comms.rank_combine_stats(k, length, n)
    return ("sparse" if st["bytes_wire"] <= st["bytes_dense_ring"]
            else "dense")


def _block_schedule(n_blocks: int, n_shards: int,
                    batch_blocks: int) -> np.ndarray:
    """Every shard's local blocks in order, batched ``bb`` per staged
    step with ``bb`` the largest divisor of ``n_blocks`` ≤
    ``batch_blocks`` (uniform staged shapes — one compile, no ragged
    tail retrace)."""
    bb = max(1, min(int(batch_blocks), n_blocks))
    while n_blocks % bb:
        bb -= 1
    local = np.arange(n_blocks, dtype=np.int64).reshape(-1, 1, bb)
    return np.broadcast_to(local, (n_blocks // bb, n_shards, bb))


def make_sweep_fns(gd: GraphDataset, config: StreamedPageRankConfig):
    """The three jitted pieces of one power iteration: a sharded zero
    accumulator, the per-staged-batch window accumulate, and the
    combine+update. Shared across backends/iterations/segments — the
    bitwise contract is that these are the ONLY compute."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_distalg.ops import graph as gops
    from tpu_distalg.parallel import comms, data_parallel
    from tpu_distalg.parallel.sharding import data_sharding

    mesh = gd.ds.mesh
    V, W, S = gd.n_vertices, gd.window, gd.n_shards
    combine = resolve_combine(config.combine, gd.k_sparse, V, S)
    q = config.q

    zeros_fn = jax.jit(lambda: jnp.zeros((S, W), jnp.float32),
                       out_shardings=data_sharding(mesh, 2))

    def accum_body(acc, blk, lo, ranks):
        return acc + gops.block_contribs(ranks, blk[0], lo[0], W)[None]

    accum_fn = jax.jit(data_parallel(
        accum_body, mesh,
        in_specs=(P("data", None), P("data", None, None), P("data"),
                  P()),
        out_specs=P("data", None)))

    if combine == "sparse":
        def combine_body(acc, didx, dmask, lo):
            vals = acc[0][didx[0]] * dmask[0]
            return comms.sparse_allreduce(vals, didx[0] + lo[0], V, n=S)

        inner = data_parallel(
            combine_body, mesh,
            in_specs=(P("data", None), P("data", None),
                      P("data", None), P("data")),
            out_specs=P())

        def combined(acc, gd_arrays):
            didx, dmask, lo = gd_arrays
            return inner(acc, didx, dmask, lo)
    else:
        def combine_body(acc, lo):
            dense = jnp.zeros((V,), jnp.float32)
            dense = dense.at[lo[0] + jnp.arange(W)].add(
                acc[0], mode="drop")
            return comms.psum(dense)

        inner = data_parallel(
            combine_body, mesh,
            in_specs=(P("data", None), P("data")), out_specs=P())

        def combined(acc, gd_arrays):
            _, _, lo = gd_arrays
            return inner(acc, lo)

    def update(acc, didx, dmask, lo, ranks, has_out):
        c = combined(acc, (didx, dmask, lo))
        if config.redistribute_dangling:
            c = c + jnp.sum(ranks * (1.0 - has_out)) / V
        return q / V + (1.0 - q) * c

    return zeros_fn, accum_fn, jax.jit(update), combine


def run_streamed_pagerank(gd: GraphDataset,
                          config: StreamedPageRankConfig =
                          StreamedPageRankConfig(), *,
                          checkpoint_dir: str | None = None,
                          checkpoint_every: int = 5
                          ) -> StreamedPageRankResult:
    """The out-of-core power iteration. With ``checkpoint_dir`` the run
    is segmented through PR 3's machinery — durable checkpoints of the
    (V,) rank carry at segment boundaries, SIGTERM-safe preemption, and
    bitwise resume (iterations are time-invariant). Wire-byte counters
    for the rank combine are bumped once per sweep actually executed,
    so ``tda report`` shows the sparse-vs-dense accounting for the run.
    """
    import jax
    import jax.numpy as jnp

    from tpu_distalg.parallel import comms

    V, S = gd.n_vertices, gd.n_shards
    zeros_fn, accum_fn, update_fn, combine = make_sweep_fns(gd, config)
    ids = _block_schedule(gd.ds.n_blocks, S, config.batch_blocks)
    serialize = not gd.ds.on_tpu
    executed = {"n": 0}

    def sweep(ranks):
        with tevents.span("graph:sweep", backend=gd.ds.backend,
                          n_edges=gd.n_edges, combine=combine):
            acc = zeros_fn()
            with contextlib.closing(gd.ds.stream(ids)) as batches:
                for staged in batches:
                    acc = accum_fn(acc, staged, gd.lo, ranks)
                    if serialize:
                        # CPU-mesh rendezvous starvation guard — the
                        # same serialization the minibatch consumers
                        # apply (data/sharded.py on_tpu note)
                        jax.block_until_ready(acc)
            ranks = update_fn(acc, gd.didx, gd.dmask, gd.lo, ranks,
                              gd.has_out)
        tevents.counter("graph.edges_streamed", gd.n_edges)
        executed["n"] += 1
        return ranks

    ranks0 = jnp.full((V,), 1.0 / V, jnp.float32)
    if checkpoint_dir is None:
        ranks = ranks0
        for _ in range(config.n_iterations):
            ranks = sweep(ranks)
    else:
        from tpu_distalg.utils import checkpoint as ckpt

        def make_seg_fn(seg):
            return seg  # the segment "program" is just its length

        def run_seg(seg, state, t0):
            ranks = state["ranks"]
            for _ in range(seg):
                ranks = sweep(ranks)
            return ({"ranks": ranks},
                    np.asarray(jnp.sum(ranks), np.float32)[None])

        state, _, _ = ckpt.run_segmented(
            checkpoint_dir, checkpoint_every, config.n_iterations,
            make_seg_fn, run_seg, {"ranks": ranks0},
            tag="pagerank_streamed")
        ranks = jnp.asarray(state["ranks"])
    st = comms.emit_rank_combine_counters(
        gd.k_sparse, V, S, n_syncs=executed["n"], combine=combine)
    return StreamedPageRankResult(
        ranks=ranks, n_iterations_run=config.n_iterations,
        combine=combine, comm_stats=st)
