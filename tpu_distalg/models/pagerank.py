"""PageRank power iteration.

Re-design of ``/root/reference/graph_computation/pagerank.py``: the
join+flatMap+reduceByKey shuffle pipeline (``:50-57``) becomes an
edge-parallel sweep — edges are sharded over the mesh data axis; each shard
gathers ``ranks[src]``, scatters contributions into a dense rank vector via
``segment_sum``, and one psum combines shards. Ten iterations compile into
a single ``lax.scan``; the reference executes them as one 10-join-deep lazy
lineage at collect time (SURVEY.md §3.4).

TPU layout decisions (random HBM access is the enemy — every random
gather/scatter element costs ~10-15 ns on a v5e through XLA, and that —
not bandwidth — bounds the sweep):

  * ``inv_deg[src]`` never changes across iterations, so it is gathered
    once at prep into a static per-edge weight array — one random gather
    per iteration (``ranks[src]``) instead of three, and standard mode
    skips the ``received`` scatter entirely (together ~2.9× per sweep,
    measured);
  * edges are sorted by ``dst`` ONCE at prep (native C++ counting sort),
    so the contribution scatter is a
    ``segment_sum(indices_are_sorted=True)``; shards are contiguous
    slices of the sorted list, so per-shard sortedness survives
    sharding, and padding uses dst=V-1 (order-preserving, masked out).
    Rejected alternatives, measured no faster: pull/ELL in-edge tables
    (doubles the random accesses) and prefix-sum segmented reduction
    (f32 prefix differences can't resolve 1e-6-scale ranks);
  * standard mode goes further: dst-sortedness means consecutive
    edges target a narrow band of a (V/128, 128) vertex table, so the
    scatter becomes a Pallas kernel (``ops/pallas_pagerank``) that
    keeps the table VMEM-resident and scatter-adds each 1024-edge
    chunk with ONE one-hot MXU matmul (the hybrid sweep: the
    ``ranks[src]`` gather stays in XLA); and with the edges sorted by
    (source group, destination row) the gather joins it in one kernel
    (the fused SpMV, ``scatter='spmv'``, what ``'auto'`` prefers): no
    random-access engine at all. Which sweep ranks a graph is
    :func:`sweep_form`'s to say and nobody else's: under ``'auto'``
    the fused sweep where its plan exists, the hybrid where only its
    own does (a sparse graph: 63 against XLA's 134 ms a sweep of 8.4M
    edges over 4.2M vertices, PERF.md, PR 43), XLA where neither. The
    fused plan is made on the device for
    every graph (:func:`prepare_device_spmv`), and a Graph500
    Kronecker graph is drawn and deduplicated there too
    (:func:`build_rmat_graph`): SCALE 24, 268M generated edges, drawn,
    deduplicated and planned on one chip in 8.3 s warm (PERF.md, PR 38);
  * on a mesh the fused sweep is sharded by DESTINATION RANGE, one
    form for every shard count: a shard holds the edges that point
    into its range (drawn a slice of the ids a shard and exchanged by
    one ``all_to_all`` at load, the shuffle the reference's
    ``reduceByKey`` pays every sweep), plans them itself, reads the
    whole ranks vector and writes the output table of its own range,
    which is what VMEM has to hold; the new ranges are all-gathered
    once a sweep and the dangling mass is a scalar psum. No chip holds
    the whole edge list or the whole output table: Graph500 SCALE 26
    (67M vertices, 1.07G edges) on four chips (PERF.md, PR 44). The
    ranges are cut where the edges are, a quarter of them a shard
    (``ops/pallas_pagerank.balanced_bounds``): a sweep ends when its
    fullest shard does. The
    XLA, hybrid and reference sweeps shard a destination-sorted list
    by position and all-reduce whole tables.

Two modes (SURVEY.md §7 hard part #6):
  * ``mode='reference'`` reproduces the reference's semantics exactly: n is
    the number of vertices WITH out-links (``:41-44``), sink vertices keep
    no rank and their mass vanishes (no dangling handling — ranks don't sum
    to 1, see the recorded outputs ``:66-68``), and a vertex only holds a
    rank in round t+1 if it received a contribution in round t.
  * ``mode='standard'`` is textbook PageRank over all vertices with optional
    dangling-mass redistribution — what you actually want at 1M nodes.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from tpu_distalg.ops import graph as gops
from tpu_distalg.parallel import (
    DATA_AXIS,
    all_gather,
    all_to_all,
    comms,
    data_parallel,
    mesh_on_tpu,
    partition,
    replica_index,
    tree_allreduce_sum,
)
from tpu_distalg.telemetry import events as tevents
from tpu_distalg.telemetry import names
from tpu_distalg.utils import metrics


@dataclasses.dataclass(frozen=True)
class PageRankConfig:
    """Knob names follow ``pagerank.py:17-19``."""

    n_iterations: int = 10
    q: float = 0.15
    mode: str = "reference"  # 'reference' | 'standard'
    redistribute_dangling: bool = True  # standard mode only
    scatter: str = "auto"  # 'auto' | 'spmv' | 'pallas' | 'xla' (standard)


@dataclasses.dataclass
class PageRankResult:
    ranks: jax.Array      # (V,) dense rank vector
    has_rank: jax.Array   # (V,) bool: vertex holds a rank (reference mode)


@dataclasses.dataclass
class DevicePlan:
    """Device-resident :class:`ops.pallas_pagerank.ScatterPlan` arrays."""

    base: jax.Array   # (NCH,) int32, sharded over data
    row: jax.Array    # (NCH, chunk) int32
    lane: jax.Array   # (NCH, chunk) int32
    w: int
    blk: int
    r8: int
    n_chunks: int


@dataclasses.dataclass
class DeviceSpMV:
    """A fused-SpMV plan's arrays on the device
    (:class:`ops.pallas_pagerank.SpMVPlan`; ``scatter='spmv'``)."""

    gbase: jax.Array      # (NCH,) int32, sharded over data
    sbase: jax.Array      # (NCH,) int32
    src_lane: jax.Array   # (NCH*8, 128) int32
    src_row: jax.Array    # (NCH*8, 128) int32
    dst_row: jax.Array    # (NCH*8, 128) int32
    dst_lane: jax.Array   # (NCH*8, 128) int32
    w_e: jax.Array        # (NCH*8, 128) f32
    rg: int
    ws: int
    r8: int
    blk: int
    n_chunks: int
    seg_steps: int = 0    # grid steps a kernel call (0: one call)
    n_groups: int = 1
    rows_out: int = 0     # rows of a shard's output table (0: r8)
    n_shards: int = 1
    bounds: jax.Array | None = None   # (n_shards + 1,) int32: where the
    # destination ranges are cut, in table rows; None: equal widths

    LEAVES = ("gbase", "sbase", "src_lane", "src_row", "dst_row",
              "dst_lane", "w_e")   # the arrays, by their rule-table names

    @classmethod
    def of(cls, arrays, geom, bounds=None) -> "DeviceSpMV":
        """The seven arrays (in ``LEAVES``' order) of a plan of
        ``geom`` (``ops.pallas_pagerank.SpMVGeometry``) whose ranges
        are cut at ``bounds``."""
        return cls(*arrays, rg=geom.rg, ws=geom.ws, r8=geom.r8,
                   blk=geom.blk, n_chunks=geom.n_chunks,
                   seg_steps=geom.seg_steps, n_groups=geom.n_groups,
                   rows_out=geom.rows_out, n_shards=geom.n_shards,
                   bounds=bounds)

    @property
    def ranks_form(self) -> str:
        """'windowed' past one gather group, else 'resident'."""
        return "windowed" if self.n_groups > 1 else "resident"

    @property
    def ranks_out_form(self) -> str:
        """'range' where a shard writes its own destination range of
        the table (``rows_out`` of ``r8`` rows), 'whole' on one."""
        return "range" if self.n_shards > 1 else "whole"

    @property
    def forms(self) -> dict:
        """What the spans say of the sweep (``tda report``'s ``ranks
        table`` line)."""
        from tpu_distalg.ops import pallas_pagerank as ppr

        return dict(ranks_form=self.ranks_form,
                    ranks_out_form=self.ranks_out_form, rg=self.rg,
                    ws=self.ws, scatter_passes=ppr.SCATTER_PASSES,
                    **ppr.overlap_fields(
                        self.rg, self.blk, self.n_chunks // self.n_shards,
                        self.seg_steps))

    @property
    def arrays(self) -> tuple:
        return tuple(getattr(self, n) for n in self.LEAVES)

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in self.arrays)


@dataclasses.dataclass
class DeviceGraph:
    """A graph's distinct edges on the device as the planner takes
    them: ``geom.shard_slots`` slots a shard, the edges whose
    destination lies in the shard's range (``bounds``) wherever ``src
    >= 0`` among its first ``geom.shard_cap``, spare slots behind.
    ``n_in`` counts the edges that came in, all shards together."""

    src: jax.Array        # (n_slots,) int32 over data, -1 where no edge
    dst: jax.Array        # (n_slots,) int32 over data
    inv_deg: jax.Array    # (V,) f32, 1 / distinct out-edges, 0 for none
    has_out: jax.Array    # (V,) f32
    n_in: int
    n_vertices: int
    n_edges: int          # distinct edges
    geom: object          # ops.pallas_pagerank.SpMVGeometry
    meta: dict = dataclasses.field(default_factory=dict)
    shard_edges: tuple = ()   # distinct edges a shard
    bounds: jax.Array | None = None   # as DeviceSpMV's


@dataclasses.dataclass
class DeviceEdges:
    """dst-sorted, mesh-sharded edge arrays + static per-edge weights."""

    src: jax.Array     # (E_pad,) int32, shards are dst-sorted slices
    dst: jax.Array     # (E_pad,) int32
    w_e: jax.Array     # (E_pad,) f32: inv_deg[src], 0 on padding
    emask: jax.Array   # (E_pad,) f32 edge validity
    inv_deg: jax.Array  # (V,) f32 (kept for parity introspection)
    has_out: jax.Array  # (V,) f32
    n_vertices: int
    n_ref: float        # reference's n = #vertices with out-links (:41-44)
    plan: DevicePlan | None = None  # Pallas scatter prep (standard mode)
    spmv: DeviceSpMV | None = None  # fused Path E prep (scatter='spmv')


def resident_guard_trips(n_vertices: int, n_shards: int = 1) -> bool:
    """True when the fused SpMV cannot hold this many vertices on this
    many shards: a shard's output table, 4 B a vertex of its
    destination range, has to stay in VMEM
    (``ops/pallas_pagerank.SPMV_VMEM_BUDGET``: 26M vertices a shard).
    The signal the CLI keys its warn-and-degrade-to-streamed on: past
    this line the resident paths either refuse (spmv) or fall back to
    sweeps that need the whole edge set HBM-resident anyway."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    return ppr.shards_needed(n_vertices) > n_shards


class ShardOverflow(ValueError):
    """A destination range drew more edges than a shard's capacity
    (``ops/pallas_pagerank.SPMV_SHARD_SIGMAS``): the load fails, no
    edge is dropped."""


def sweep_form(config: PageRankConfig, fused: bool, hybrid: bool) -> str:
    """Which sweep ranks a graph, ``'reference'``, ``'fused'``,
    ``'hybrid'`` or ``'xla'``: the one place that knows. ``fused`` and
    ``hybrid`` say whether that sweep's plan exists
    (:func:`prepare_device_spmv` refuses a graph whose chunks span more
    rows than its window, ``ops/pallas_pagerank.plan_scatter`` one
    whose 1024 destination-sorted edges span more than 32); ask with
    ``True`` whether a plan is worth making. ``mode='reference'`` is
    the reference sweep and takes no scatter but 'auto'. In standard
    mode 'auto' is the fused sweep where planned, else the hybrid
    where planned, else XLA; 'spmv' is fused and 'pallas' the hybrid,
    each raising the remedy without its plan; 'xla' is XLA."""
    if config.scatter not in ("auto", "pallas", "xla", "spmv"):
        raise ValueError(f"unknown scatter mode {config.scatter!r}")
    if config.mode != "standard" and config.scatter != "auto":
        raise ValueError(
            f"scatter={config.scatter!r} only applies to mode="
            "'standard' — the reference-parity mode always uses the "
            "XLA segment_sum path"
        )
    if config.mode == "reference":
        return "reference"
    if config.scatter == "pallas" and not hybrid:
        raise ValueError(
            "scatter='pallas' needs a scatter plan — the graph's dst "
            "distribution was too sparse/skewed for a bounded window "
            "(ops/pallas_pagerank.plan_scatter returned None). For "
            "graphs past the resident ceiling, use the streamed "
            "engine instead: --data-backend streamed "
            "(tpu_distalg/graphs/)"
        )
    if config.scatter == "spmv" and not fused:
        raise ValueError(
            "scatter='spmv' needs the fused-SpMV plan — build the "
            "DeviceSpMV via prepare_device_spmv (None means the "
            "graph's windows exceeded ops/pallas_pagerank caps, a "
            "destination range overflowed its shard, or a shard's "
            "output table blew SPMV_VMEM_BUDGET, 4 B a vertex of "
            "its range: ops/pallas_pagerank.shards_needed says how "
            "many data shards a graph of that size takes). Graphs "
            "past the mesh's ceiling belong on the out-of-core "
            "engine: --data-backend streamed (tpu_distalg/graphs/ "
            "streams edge blocks from disk; only O(V) state stays "
            "in HBM)"
        )
    if config.scatter in ("auto", "spmv") and fused:
        return "fused"
    if config.scatter in ("auto", "pallas") and hybrid:
        return "hybrid"
    return "xla"


def choose_data_backend(requested: str, n_vertices: int,
                        scatter: str = "auto", n_shards: int = 1
                        ) -> tuple[str, str | None]:
    """Resolve the pagerank ``--data-backend`` knob against the
    resident VMEM guard: a resident request past the mesh's ceiling
    degrades to streamed WITH a warning that says how many shards
    would hold the graph, instead of dying in the sweep prep. The
    ceiling is the fused sweep's table budget a shard, so it applies
    where a standard-mode sweep under this ``scatter`` would be fused
    (:func:`sweep_form`); an EXPLICIT ``--scatter xla``/``pallas``
    resident request is honored: those sweeps carry their own
    (HBM/plan) limits with remedy-naming errors.
    Returns ``(backend, warning-or-None)``."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    would_fuse = sweep_form(
        PageRankConfig(mode="standard", scatter=scatter),
        fused=True, hybrid=True) == "fused"
    if requested == "resident" and would_fuse \
            and resident_guard_trips(n_vertices, n_shards):
        return "streamed", (
            f"[pagerank] {n_vertices} vertices exceed the resident "
            f"sweep's VMEM guard on {n_shards} data shard(s) (the fused "
            f"SpMV keeps 4 B a vertex of a shard's destination range in "
            f"VMEM, ops/pallas_pagerank.SPMV_VMEM_BUDGET): a mesh of "
            f"{ppr.shards_needed(n_vertices)} data shards holds it "
            f"resident; degrading to --data-backend streamed "
            f"(tpu_distalg/graphs/: edge blocks stream from disk, only "
            f"O(V) state stays in HBM)")
    return requested, None


def _inv_out_degree(el: gops.EdgeList) -> np.ndarray:
    """Per-vertex 1/out_degree (0 for sinks) — THE per-edge weight
    definition, shared by every sweep path (the graph engine's ingest
    included: ``graphs/ingest.inv_out_degree`` is the one
    implementation) so they cannot diverge."""
    from tpu_distalg.graphs.ingest import inv_out_degree

    return inv_out_degree(el.out_degree)


_SLOTS = P(DATA_AXIS)    # a shard's edge slots: the ``pagerank`` rule
#                          table's ``slots``


def rmat_programs(mesh: Mesh, scale: int, abcd, geom, n_in: int):
    """:func:`build_rmat_graph`'s jitted programs ``generate(seed) ->
    (src, dst)`` and ``dedup(src, dst) -> (src, dst, inv_deg, has_out,
    distinct edges a shard)``, every edge array sharded over the data
    axis (the chipless compile check lowers them at the cell's
    shapes). A shard draws its slice of the ``n_in`` edge ids; on one
    shard ``generate`` appends the spare slots, on several
    :func:`exchange_program` comes between the two and does."""
    from tpu_distalg.utils import datasets

    V, n = 1 << scale, geom.n_shards
    per = -(-n_in // n)                   # ids a shard draws
    draw = datasets.kronecker_edges(scale, abcd)

    def generate(seed):
        ids = replica_index().astype(jnp.uint32) * np.uint32(per) \
            + jnp.arange(per, dtype=jnp.uint32)
        src, dst = draw(ids, seed)
        if per * n != n_in:               # ids past the last edge
            src = jnp.where(ids < n_in, src, V)
        if n > 1:
            return src, dst
        return _spare_behind(src, dst, V, geom.shard_slots - per)

    def dedup(src, dst):
        src, dst = jax.lax.sort((src, dst), num_keys=2, is_stable=False)
        again = (src[1:] == src[:-1]) & (dst[1:] == dst[:-1])
        real = (src < V) & ~jnp.concatenate([jnp.zeros((1,), bool), again])
        # a duplicate pair shares its destination, so it shares its
        # shard: the mask is local, the out-degrees add up over shards
        deg = comms.psum(jax.ops.segment_sum(
            real.astype(jnp.int32), src, num_segments=V + 1,
            indices_are_sorted=True)[:V])
        inv_deg = jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1), 0.0)
        return (jnp.where(real, src, -1), dst,
                inv_deg.astype(jnp.float32),
                (deg > 0).astype(jnp.float32),
                all_gather(jnp.sum(real, dtype=jnp.int32)[None]))

    return (jax.jit(data_parallel(generate, mesh, in_specs=P(),
                                  out_specs=(_SLOTS, _SLOTS))),
            jax.jit(data_parallel(
                dedup, mesh, in_specs=(_SLOTS, _SLOTS),
                out_specs=(_SLOTS, _SLOTS, P(), P(), P())),
                donate_argnums=(0, 1)))


def _spare_behind(src, dst, V: int, spare: int):
    """``spare`` slots that hold no edge, sorted behind every edge."""
    return (jnp.concatenate([src, jnp.full((spare,), V, jnp.int32)]),
            jnp.concatenate([dst, jnp.zeros((spare,), jnp.int32)]))


def exchange_program(mesh: Mesh, scale: int, geom):
    """``exchange(src, dst) -> (src, dst, bounds, overflow)``: every
    shard's draws sorted by destination, the destination ranges cut
    where a quarter (an n-th) of all shards' draws lies behind
    (``ops/pallas_pagerank.balanced_bounds`` over the psummed counts a
    tile of 8 table rows: ``bounds``, in rows), a shard's draws cut at
    the same places into one bucket of ``geom.bucket`` slots a range
    and exchanged by ``all_to_all`` (the shuffle the reference's
    ``reduceByKey`` pays a sweep, once, at load), the spare slots
    appended. A shard then holds exactly the drawn edges whose
    destination lies in its range. ``overflow`` counts the edges that
    fit no bucket and the rows of a range past a shard's table: the
    load fails on any."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    V, n, bucket = 1 << scale, geom.n_shards, geom.bucket
    tiles = np.arange(geom.r8 // 8 + 1, dtype=np.int32) * (8 * 128)

    def exchange(src, dst):
        dst, src = jax.lax.sort((jnp.where(src < V, dst, V), src),
                                num_keys=1, is_stable=False)
        behind = jnp.searchsorted(dst, tiles, side="left")
        bounds = ppr.balanced_bounds(
            jnp, comms.psum(behind[1:] - behind[:-1]), n)
        start = jnp.searchsorted(dst, bounds * 128, side="left")
        count = start[1:] - start[:-1]
        # a bucket is a slice from its range's first edge; room behind
        # the last edge so that no slice is clamped back into another's
        src, dst = _spare_behind(src, dst, V, bucket)
        held = jnp.arange(bucket, dtype=jnp.int32)

        def buckets(x, empty):
            return jnp.concatenate([
                jnp.where(held < count[k], jax.lax.dynamic_slice(
                    x, (start[k],), (bucket,)), empty)
                for k in range(n)])

        src, dst = all_to_all(buckets(src, V)), all_to_all(buckets(dst, 0))
        overflow = comms.psum(jnp.sum(jnp.maximum(count - bucket, 0))) \
            + jnp.sum(jnp.maximum(
                bounds[1:] - bounds[:-1] - geom.rows_out, 0))
        return _spare_behind(src, dst, V, geom.shard_slots - n * bucket) \
            + (bounds, overflow)

    return jax.jit(data_parallel(
        exchange, mesh, in_specs=(_SLOTS, _SLOTS),
        out_specs=(_SLOTS, _SLOTS, P(), P())), donate_argnums=(0, 1))


def even_bounds(geom) -> np.ndarray:
    """Ranges of equal width (the whole table on one shard): where a
    plan of ``geom`` (a geometry or a :class:`DeviceSpMV`: its ``r8``
    and ``n_shards``) is cut that nobody balanced."""
    wide = -(-geom.r8 // (8 * geom.n_shards)) * 8
    return np.minimum(np.arange(geom.n_shards + 1) * wide,
                      geom.r8).astype(np.int32)


def plan_programs(mesh: Mesh, geom, n_in: int):
    """:func:`prepare_device_spmv`'s two jitted programs, each a shard
    on its own slots: ``sort(src, dst, bounds) -> (src, dst)`` in the
    kernel's order and ``lay_out(src, dst, inv_deg, bounds) -> (the
    seven plan arrays, widest span of any shard)``; ``bounds`` as
    :class:`DeviceSpMV` holds them (left out: :func:`even_bounds`).
    ``n_in`` counts the edges that came in, all shards together; a
    shard's lie among its first ``geom.shard_cap`` slots."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    del n_in

    def sort_shard(src, dst, bounds):
        return ppr.sort_slots(src, dst, geom=geom, n_in=geom.shard_cap,
                              row0=bounds[replica_index()])

    def lay_out_shard(src, dst, inv_deg, bounds):
        w_e = inv_deg[jnp.maximum(src, 0)]
        arrays, span = ppr.slot_arrays(jnp, src, dst, w_e, geom,
                                       bounds[replica_index()])
        return arrays, comms.pmax(span)

    leaves = tuple(partition.table("pagerank").spec_for(n, (2, 2))
                   for n in DeviceSpMV.LEAVES)
    sort_mesh = data_parallel(sort_shard, mesh,
                              in_specs=(_SLOTS, _SLOTS, P()),
                              out_specs=(_SLOTS, _SLOTS))
    lay_out_mesh = data_parallel(lay_out_shard, mesh,
                                 in_specs=(_SLOTS, _SLOTS, P(), P()),
                                 out_specs=(leaves, P()))

    def cut(bounds):
        return even_bounds(geom) if bounds is None else bounds

    def sort(src, dst, bounds=None):
        return sort_mesh(src, dst, cut(bounds))

    def lay_out(src, dst, inv_deg, bounds=None):
        return lay_out_mesh(src, dst, inv_deg, cut(bounds))

    return (jax.jit(sort, donate_argnums=(0, 1)),
            jax.jit(lay_out, donate_argnums=(0, 1)))


def build_rmat_graph(mesh: Mesh, scale: int, edge_factor: int = 16,
                     abcd=None, seed: int = 0,
                     rg: int | None = None) -> DeviceGraph:
    """The program's own loader of a Graph500 Kronecker graph, on the
    device, sharded by destination range: every shard draws its slice
    of the ``edge_factor * 2**scale`` edge ids from the seed as an
    argument (``utils/datasets.kronecker_edges``), the draws are
    exchanged so that a shard holds the edges that point into its
    range (:func:`exchange_program`; nothing to exchange on one
    shard), and each shard sorts its own by (source, destination),
    marks a repeated edge and counts it once; the distinct out-degrees
    are added up over the shards. No shard ever holds the whole edge
    list, and nothing crosses to the host but the counts. Every shape
    follows from (scale, edge_factor, shards): one compile serves
    every seed. A range that draws more than a shard's capacity
    (``geom.shard_cap``) fails the load (:class:`ShardOverflow`,
    counted in ``pagerank_shard_overflow``)."""
    from tpu_distalg.ops import pallas_pagerank as ppr
    from tpu_distalg.utils import datasets

    V, n_in = 1 << scale, edge_factor << scale
    abcd = tuple(abcd or datasets.GRAPH500_ABCD)
    n = mesh.shape[DATA_AXIS]
    geom = ppr.spmv_geometry(V, n_in, n, rg)
    if geom is None:
        raise ValueError(
            f"2**{scale} vertices are past the resident fused SpMV on "
            f"{n} data shard(s) (4 B a vertex of a shard's destination "
            f"range in VMEM): a mesh of {ppr.shards_needed(V)} data "
            f"shards holds the graph")
    generate, dedup = rmat_programs(mesh, scale, abcd, geom, n_in)
    sharding = dict(shards=n, shard_capacity=geom.shard_cap)
    devices = mesh.local_devices
    with tevents.span("pagerank:generate", devices, scale=scale,
                      generated=n_in, rows=n_in, seed=int(seed),
                      shard_edges=[-(-n_in // n)] * n, **sharding):
        src, dst = jax.block_until_ready(
            generate(np.uint32(seed & 0xFFFFFFFF)))
        tevents.current().fields["bytes"] = metrics.nbytes(src, dst)
    overflow, bounds = 0, None
    if n > 1:
        with tevents.span("pagerank:exchange", devices,
                          bucket=geom.bucket, **sharding):
            src, dst, bounds, overflow = exchange_program(
                mesh, scale, geom)(src, dst)
            overflow = int(overflow)
            tevents.current().fields.update(
                overflow=overflow, bytes=metrics.nbytes(src, dst, bounds),
                bounds=[int(x) for x in np.asarray(bounds)])
    tevents.counter("pagerank_shard_overflow", overflow)
    if overflow:
        raise ShardOverflow(
            f"pagerank_shard_overflow: {overflow} edges of seed {seed} "
            f"fit no bucket of {geom.bucket} slots, or rows of a range "
            f"no table of {geom.rows_out} (a shard's capacity "
            f"{geom.shard_cap} at SCALE {scale} on {n} shards, "
            f"ops/pallas_pagerank.SPMV_SHARD_SIGMAS); no edge is "
            f"dropped: the load fails")
    with tevents.span("pagerank:dedup", devices, generated=n_in,
                      **sharding):
        src, dst, inv_deg, has_out, shard_edges = dedup(src, dst)
        shard_edges = [int(x) for x in
                       np.atleast_1d(np.asarray(shard_edges))]
        n_edges = sum(shard_edges)
        tevents.current().fields.update(
            distinct=n_edges, rows=n_edges, shard_edges=shard_edges,
            bytes=metrics.nbytes(src, dst, inv_deg, has_out))
    tevents.counter("pagerank_shard_edges_max", max(shard_edges))
    tevents.counter("pagerank_shard_edges_mean",
                    n_edges // len(shard_edges))
    return DeviceGraph(
        src=src, dst=dst, inv_deg=inv_deg, has_out=has_out, n_in=n_in,
        n_vertices=V, n_edges=n_edges, geom=geom,
        shard_edges=tuple(shard_edges), bounds=bounds,
        meta=dict(generator="kronecker", scale=scale,
                  edge_factor=edge_factor, abcd=abcd, seed=int(seed),
                  generated=n_in, distinct=n_edges))


def device_graph(el: gops.EdgeList, mesh: Mesh,
                 rg: int | None = None) -> DeviceGraph | None:
    """A host edge list (distinct already) copied up once in the form
    the device planner takes, the destination ranges cut to equal
    loads and every edge to the shard that owns its destination;
    ``None`` past the VMEM budget, or where a range holds more edges
    than a shard's capacity or more rows than its table (counted in
    ``pagerank_shard_overflow``)."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    n = mesh.shape[DATA_AXIS]
    geom = ppr.spmv_geometry(el.n_vertices, el.n_edges, n, rg) \
        if el.n_edges else None
    if geom is None:
        return None
    put = lambda a, n: partition.put(a, n, "pagerank", mesh)  # noqa: E731
    bounds, owner, over = ppr.host_ranges(el.dst, geom)
    held = np.bincount(owner, minlength=n)
    tevents.counter("pagerank_shard_overflow", over)
    if over:
        return None
    order = np.argsort(owner, kind="stable")
    at = np.arange(el.n_edges) + np.repeat(
        np.arange(n) * geom.shard_slots - (np.cumsum(held) - held), held)

    def slots(x, fill):
        out = np.full(geom.n_slots, fill, np.int32)
        out[at] = x[order]
        return put(out, "slots")

    inv_deg = _inv_out_degree(el)
    return DeviceGraph(
        src=slots(el.src, -1), dst=slots(el.dst, 0),
        inv_deg=put(inv_deg, "inv_deg"),
        has_out=put((inv_deg > 0).astype(np.float32), "has_out"),
        n_in=el.n_edges, n_vertices=el.n_vertices, n_edges=el.n_edges,
        geom=geom, shard_edges=tuple(int(x) for x in held),
        bounds=put(bounds, "bounds"))


def prepare_device_spmv(graph: gops.EdgeList | DeviceGraph, mesh: Mesh,
                        rg: int | None = None) -> DeviceSpMV | None:
    """The fused sweep's plan, made on the device for every graph: a
    host edge list is copied up once (:func:`device_graph`), a
    :class:`DeviceGraph` is there already. Every shard sorts its own
    slots, the edges that point into its destination range, by (source
    group over the whole table, destination row of the range) with the
    padding in the keys, which puts every slot where the kernel reads
    it, and array code lays the shard's seven plan arrays out
    (``ops/pallas_pagerank.sort_slots`` / ``slot_arrays``). The
    geometry (``rg``, ``ws``, the slot count) is a function of the
    sizes alone, so a second graph of a size compiles nothing.

    ``None`` when a shard's table passes the VMEM budget, a range
    holds more edges than a shard's capacity, or a chunk's
    destinations span more than the geometry's ``ws`` rows (a graph
    sparser or more skewed than the window was sized for): counted in
    ``spmv_plan_rejections`` and said in the ``pagerank:plan`` span, so
    ``scatter='auto'`` falls back knowingly and ``'spmv'`` raises.
    ``pagerank:prepare`` covers the copy and the plan; the graph's own
    spans (``pagerank:generate``, ``pagerank:dedup``) come before it
    where the program drew the graph. The graph's edge arrays are
    donated to the sort."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    devices = mesh.local_devices
    with tevents.span("pagerank:prepare", devices):
        sp = tevents.current().fields
        if isinstance(graph, gops.EdgeList):
            graph = device_graph(graph, mesh, rg)
            if graph is None:
                tevents.counter("spmv_plan_rejections")
                return None
        geom = graph.geom
        sp.update(vertices=graph.n_vertices, distinct=graph.n_edges,
                  generated=graph.n_in, rg=geom.rg, ws=geom.ws,
                  chunks=geom.n_chunks, ranks_form=geom.ranks_form,
                  ranks_out_form=geom.ranks_out_form,
                  shards=geom.n_shards, slots=geom.n_slots,
                  scatter_passes=ppr.SCATTER_PASSES,
                  **ppr.overlap_fields(
                      geom.rg, geom.blk, geom.n_steps * geom.blk,
                      geom.seg_steps),
                  padding_share=geom.n_slots / max(graph.n_edges, 1))
        sort, lay_out = plan_programs(mesh, geom, graph.n_in)
        with tevents.span("pagerank:plan", devices, rg=geom.rg,
                          ws=geom.ws, slots=geom.n_slots):
            src, dst = sort(graph.src, graph.dst, graph.bounds)
            graph.src = graph.dst = None
            arrays, span = lay_out(src, dst, graph.inv_deg, graph.bounds)
            span = int(span)
            tevents.current().fields.update(span=span,
                                            bytes=metrics.nbytes(arrays))
        if span > geom.ws:
            tevents.counter("spmv_plan_rejections")
            tevents.emit("spmv_span_rejected", span=span, ws=geom.ws,
                         rg=geom.rg, n_vertices=graph.n_vertices)
            return None
        plan = DeviceSpMV.of(arrays, geom, graph.bounds)
        sp["bytes"] = plan.nbytes
        tevents.counter("spmv_slots_padded",
                        geom.n_slots - graph.n_edges)
        return plan


def _plan_only_edges(mesh: Mesh, inv_deg, has_out,
                     n_vertices: int) -> DeviceEdges:
    """What the fused sweep's ``run`` takes beside its plan: the vertex
    tables; the per-edge arrays of the other sweeps are placeholders
    (the plan holds the edges)."""
    n_shards = mesh.shape[DATA_AXIS]
    put = lambda a, n: partition.put(a, n, "pagerank", mesh)  # noqa: E731
    z, zf = np.zeros(n_shards, np.int32), np.zeros(n_shards, np.float32)
    return DeviceEdges(
        src=put(z, "src"), dst=put(z, "dst"), w_e=put(zf, "w_e"),
        emask=put(zf, "emask"), inv_deg=jnp.asarray(inv_deg),
        has_out=jnp.asarray(has_out), n_vertices=n_vertices,
        n_ref=float(jnp.sum(has_out)))


def spmv_device_edges(graph: DeviceGraph, mesh: Mesh) -> DeviceEdges:
    """:func:`_plan_only_edges` of a graph on the device."""
    return _plan_only_edges(mesh, graph.inv_deg, graph.has_out,
                            graph.n_vertices)


def prepare_device_edges(el: gops.EdgeList, mesh: Mesh,
                         plan_chunk: int | None = None,
                         plan_blk: int | None = None,
                         build_plan: bool = True,
                         light: bool = False) -> DeviceEdges:
    """One-time host prep: dst-sort (native C++ counting sort), per-edge
    weight gather, pad, shard — plus the Pallas-scatter window plan
    (``ops/pallas_pagerank.plan_scatter``) when the graph admits one.

    When the plan succeeds, ALL edge arrays adopt its per-shard padding
    (tail replicates each shard's last dst with zero weight/mask), so
    the XLA fallback path and the Pallas path share the same arrays;
    otherwise the legacy dst=V-1 tail padding is used.
    """
    from tpu_distalg import native
    from tpu_distalg.ops import pallas_pagerank as ppr

    deg = el.out_degree.astype(np.float32)
    inv_deg = _inv_out_degree(el)
    V = el.n_vertices
    n_shards = mesh.shape[DATA_AXIS]
    put = lambda a, n: partition.put(a, n, "pagerank", mesh)  # noqa: E731
    has_out = (deg > 0).astype(np.float32)
    if light:
        # the spmv path deletes src/dst/w_e/emask on its first line —
        # skip the counting sort, per-edge gather, and the ~16 B/edge
        # of device uploads entirely; only has_out/n_ref are consumed
        return _plan_only_edges(mesh, inv_deg, has_out, V)

    order = native.counting_sort_perm(el.dst, el.n_vertices)
    src_o = el.src[order].astype(np.int32)
    dst_o = el.dst[order].astype(np.int32)
    w_e = inv_deg[src_o]
    E = len(src_o)

    kw = {}
    if plan_chunk is not None:
        kw["chunk"] = plan_chunk
    if plan_blk is not None:
        kw["blk"] = plan_blk
    plan = (ppr.plan_scatter(dst_o, V, n_shards, **kw)
            if E and build_plan else None)
    if plan is not None:
        # per-shard tail padding, driven by the plan's OWN shard
        # slicing (real_per_shard) so src/w/emask can never desync
        # from the dst encoding in plan.row/plan.lane
        sl = plan.shard_len
        src_p = np.zeros(n_shards * sl, np.int32)
        w_p = np.zeros(n_shards * sl, np.float32)
        emask = np.zeros(n_shards * sl, np.float32)
        lo = 0
        for s, n_real in enumerate(plan.real_per_shard):
            src_p[s * sl:s * sl + n_real] = src_o[lo:lo + n_real]
            w_p[s * sl:s * sl + n_real] = w_e[lo:lo + n_real]
            emask[s * sl:s * sl + n_real] = 1.0
            lo += n_real
        # the padded dst is exactly what the plan encoded
        dst_p = (plan.row.reshape(-1) * 128 + plan.lane.reshape(-1)
                 ).astype(np.int32)
        dplan = DevicePlan(
            base=put(plan.base, "base"),
            row=put(plan.row, "row"),
            lane=put(plan.lane, "lane"),
            w=plan.w, blk=plan.blk, r8=plan.r8, n_chunks=plan.n_chunks,
        )
    else:
        n_pad = (-E) % n_shards
        # padding keeps dst sorted (dst=V-1 ≥ every real id) and carries
        # zero weight/mask, so sorted-segment-sum sees an inert tail
        src_p = np.concatenate([src_o, np.zeros(n_pad, np.int32)])
        dst_p = np.concatenate([dst_o, np.full(n_pad, V - 1, np.int32)])
        w_p = np.concatenate([w_e, np.zeros(n_pad, np.float32)])
        emask = np.ones(E + n_pad, np.float32)
        emask[E:] = 0.0
        dplan = None
    return DeviceEdges(
        src=put(src_p, "src"), dst=put(dst_p, "dst"),
        w_e=put(w_p, "w_e"), emask=put(emask, "emask"),
        inv_deg=jnp.asarray(inv_deg), has_out=jnp.asarray(has_out),
        n_vertices=V, n_ref=float(has_out.sum()), plan=dplan,
    )


class _PlanBound:
    """The fused sweep's jitted run with its plan bound as ARGUMENTS
    (gigabytes at Graph500 SCALE 24: closed over, they would be
    constants of the program, copied to the host to be lowered), under
    the signature every sweep's ``run`` has."""

    def __init__(self, jitted, plan, bounds):
        self.jitted, self.plan, self.bounds = jitted, plan, bounds

    def _args(self, src, dst, w_e, emask, has_out, n_ref,
              ranks0=None, has_rank0=None):
        return self.plan, self.bounds, has_out, ranks0

    def __call__(self, *args):
        return self.jitted(*self._args(*args))

    def lower(self, *args):
        return self.jitted.lower(*self._args(*args))


def _dangling(config: PageRankConfig, ranks, has_out):
    """The mass of the vertices with no out-edge among ``ranks`` (the
    whole vector, or a shard's range of it), ``None`` where it is not
    spread."""
    if not config.redistribute_dangling:
        return None
    return jnp.sum(ranks * (1.0 - has_out))


def _teleport(config: PageRankConfig, V: int, c, dangling):
    """The standard update from a sweep's contributions ``c`` (of all
    vertices or of a range): the dangling vertices' mass spread over
    all ``V``, then the teleport."""
    if dangling is not None:
        c = c + dangling / V
    return config.q / V + (1 - config.q) * c


def _reference_run(mesh: Mesh, config: PageRankConfig, V: int):
    """The reference's semantics in XLA (the module docstring's
    ``mode='reference'``): a rank only where a contribution came."""
    q = config.q

    def body(src, dst, w_e, emask, ranks, has_rank):
        active = emask * has_rank[src]
        c = gops.contribs(ranks, src, dst, w_e * active,
                          V, indices_sorted=True)
        received = gops.scatter_add(active, dst, V,
                                    indices_sorted=True)
        return tree_allreduce_sum((c, received))

    sweep_fn = data_parallel(
        body, mesh,
        in_specs=(P("data"),) * 4 + (P(), P()),
        out_specs=(P(), P()),
    )

    def run(src, dst, w_e, emask, has_out, n_ref,
            ranks0=None, has_rank0=None):
        # optional carry-in: the checkpointed driver resumes the
        # power iteration mid-schedule (iterations are
        # time-invariant, so segmenting the scan is bitwise-exact)
        if ranks0 is None:
            ranks0 = jnp.where(has_out > 0, 1.0 / n_ref, 0.0)  # :47
        if has_rank0 is None:
            has_rank0 = has_out

        def step(carry, _):
            ranks, has_rank = carry
            c, received = sweep_fn(src, dst, w_e, emask, ranks,
                                   has_rank)
            new_has = (received > 0).astype(jnp.float32)
            ranks = jnp.where(
                received > 0, q / n_ref + (1 - q) * c, 0.0
            )  # :57
            return (ranks, new_has), None

        (ranks, has_rank), _ = jax.lax.scan(
            step, (ranks0, has_rank0), None,
            length=config.n_iterations,
        )
        return ranks, has_rank

    return jax.jit(run)


def _fit(x, n: int, fill=0.0):
    """``x`` cut or filled to ``n`` elements."""
    return x[:n] if x.shape[0] >= n else jnp.pad(
        x, (0, n - x.shape[0]), constant_values=fill)


def _fused_run(mesh: Mesh, config: PageRankConfig, V: int,
               spmv: DeviceSpMV):
    """The fully-fused tiled SpMV: gather AND scatter in one Pallas
    kernel, no XLA random-access op in the sweep, the plan's arrays
    its arguments. A shard reads the whole ranks vector and writes the
    table of its own destination range (``spmv.bounds``); the teleport
    runs on the range, the dangling mass is a scalar psum and the new
    ranges are all-gathered and laid into the next sweep's vector
    where they belong (``tda.pagerank.sync``). On one shard the range
    is the whole table and none of that exists."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    interpret = not mesh_on_tpu(mesh)
    n = mesh.shape[DATA_AXIS]
    rg, ws, blk = spmv.rg, spmv.ws, spmv.blk
    rows_out = spmv.rows_out or spmv.r8
    rows = spmv.n_groups * rg         # the ranks table, whole groups
    own = rows_out * 128              # vertices of a shard's table
    # room for the last range's table wherever it starts
    held = own if n == 1 else (spmv.r8 + rows_out) * 128
    bounds = spmv.bounds
    if bounds is None:
        bounds = even_bounds(spmv)

    def sweep(gb, sb, slane, srow, drow, dlane, we, bounds, has_out,
              ranks):
        with jax.named_scope(names.PAGERANK_SPMV):
            rt = _fit(ranks, rows * 128).reshape(rows, 128)
            acc = ppr.spmv_table(gb, sb, rt, slane, srow, drow,
                                 dlane, we, rg=rg, ws=ws, r8=rows_out,
                                 blk=blk,
                                 seg_steps=spmv.seg_steps or None,
                                 interpret=interpret)
        if n == 1:
            with jax.named_scope(names.PAGERANK_UPDATE):
                return _teleport(config, V, acc[:rows_out].reshape(-1),
                                 _dangling(config, ranks, has_out))
        mine = bounds[replica_index()] * 128
        with jax.named_scope(names.PAGERANK_SYNC):
            # a table's rows past its range are the next shard's
            wide = (bounds[replica_index() + 1] * 128 - mine)
            inside = jnp.arange(own, dtype=jnp.int32) < wide
            dangling = tree_allreduce_sum(_dangling(
                config,
                jnp.where(inside, jax.lax.dynamic_slice(
                    ranks, (mine,), (own,)), 0.0),
                jax.lax.dynamic_slice(has_out, (mine,), (own,))))
        with jax.named_scope(names.PAGERANK_UPDATE):
            c = acc[:rows_out].reshape(-1)
            new = _teleport(config, V, c, dangling)
        with jax.named_scope(names.PAGERANK_SYNC):
            tables = all_gather(new, tiled=False)
            # in the ranges' order: a table's first rows overwrite what
            # the one before left past its range
            for k in range(n):
                ranks = jax.lax.dynamic_update_slice(
                    ranks, tables[k], (bounds[k] * 128,))
            return ranks

    sweep_fn = data_parallel(
        sweep, mesh,
        in_specs=(P("data"), P("data"))
        + (P("data", None),) * 5 + (P(), P(), P()),
        out_specs=P(),
    )

    def run(plan, bounds, has_out, ranks0=None):
        if ranks0 is None:
            ranks0 = jnp.full((V,), 1.0 / V, dtype=jnp.float32)
        # a vertex past V (the table's filling) has no edge, and as one
        # with out-edges adds nothing to the dangling mass
        has_out = _fit(has_out, held, 1.0)
        ranks, _ = jax.lax.scan(
            lambda ranks, _: (sweep_fn(*plan, bounds, has_out, ranks),
                              None),
            _fit(ranks0, held), None, length=config.n_iterations)
        return ranks[:V], jnp.ones((V,), dtype=jnp.float32)

    return _PlanBound(jax.jit(run), spmv.arrays, bounds)


def _hybrid_run(mesh: Mesh, config: PageRankConfig, V: int,
                plan: DevicePlan):
    """XLA's ``ranks[src] * w`` gather, then the windowed one-hot-MXU
    scatter (``ops/pallas_pagerank.scatter_table``) over ``plan``."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    interpret = not mesh_on_tpu(mesh)
    w, r8, blk = plan.w, plan.r8, plan.blk
    nch_local = plan.n_chunks // mesh.shape[DATA_AXIS]
    chunk = plan.row.shape[1]

    def body(src, w_e, base, row, lane, ranks):
        g = (ranks[src] * w_e).reshape(nch_local, chunk)
        acc = ppr.scatter_table(base, g, row, lane, w=w, r8=r8,
                                blk=blk, interpret=interpret)
        return tree_allreduce_sum(acc)

    sweep_fn = data_parallel(
        body, mesh,
        in_specs=(P("data"), P("data"), P("data"),
                  P("data", None), P("data", None), P()),
        out_specs=P(),
    )

    def run(src, dst, w_e, emask, has_out, n_ref,
            ranks0=None, has_rank0=None):
        del dst, emask, n_ref, has_rank0  # plan encodes padded dst
        if ranks0 is None:
            ranks0 = jnp.full((V,), 1.0 / V, dtype=jnp.float32)

        def step(ranks, _):
            acc = sweep_fn(src, w_e, plan.base, plan.row,
                           plan.lane, ranks)
            c = acc[:r8].reshape(-1)[:V]
            return _teleport(config, V, c,
                             _dangling(config, ranks, has_out)), None

        ranks, _ = jax.lax.scan(
            step, ranks0, None, length=config.n_iterations
        )
        return ranks, jnp.ones((V,), dtype=jnp.float32)

    return jax.jit(run)


def _xla_run(mesh: Mesh, config: PageRankConfig, V: int):
    """Standard mode in XLA: every vertex ranked, Σranks preserved; one
    gather + one sorted scatter per iteration."""
    def body(src, dst, w_e, ranks):
        c = gops.contribs(ranks, src, dst, w_e, V, indices_sorted=True)
        return tree_allreduce_sum(c)

    sweep_fn = data_parallel(
        body, mesh,
        in_specs=(P("data"),) * 3 + (P(),),
        out_specs=P(),
    )

    def run(src, dst, w_e, emask, has_out, n_ref,
            ranks0=None, has_rank0=None):
        del emask, n_ref, has_rank0  # padding already carries 0 weight
        if ranks0 is None:
            ranks0 = jnp.full((V,), 1.0 / V, dtype=jnp.float32)

        def step(ranks, _):
            c = sweep_fn(src, dst, w_e, ranks)
            return _teleport(config, V, c,
                             _dangling(config, ranks, has_out)), None

        ranks, _ = jax.lax.scan(
            step, ranks0, None, length=config.n_iterations
        )
        return ranks, jnp.ones((V,), dtype=jnp.float32)

    return jax.jit(run)


def make_run_fn(mesh: Mesh, config: PageRankConfig, n_vertices: int,
                plan: DevicePlan | None = None,
                spmv: DeviceSpMV | None = None):
    """Build the jitted n-iteration sweep :func:`sweep_form` names for
    ``config`` and the plans that came: ``spmv`` the fused sweep's,
    ``plan`` the hybrid's.

    PRECONDITION of every sweep but the fused one: the edge arrays
    passed to the returned ``run`` MUST be dst-sorted per shard with
    order-preserving padding — exactly what
    :func:`prepare_device_edges` produces. The segment-sums inside promise
    ``indices_are_sorted=True`` to XLA, which is unchecked: unsorted
    ``dst`` yields silently wrong rank sums, not an error. Construct the
    inputs via :func:`prepare_device_edges` (or :func:`run`, which does).
    The fused sweep's ``run`` has the same signature and reads of it
    ``has_out`` and the carry alone: its plan holds the edges.
    """
    form = sweep_form(config, spmv is not None, plan is not None)
    if form == "reference":
        return _reference_run(mesh, config, n_vertices)
    if form == "fused":
        return _fused_run(mesh, config, n_vertices, spmv)
    if form == "hybrid":
        return _hybrid_run(mesh, config, n_vertices, plan)
    return _xla_run(mesh, config, n_vertices)


def run(edges: np.ndarray, mesh: Mesh,
        config: PageRankConfig = PageRankConfig(),
        n_vertices: int | None = None, *,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 5) -> PageRankResult:
    el = gops.prepare_edges(edges, n_vertices)
    # each plan is made only where it would run; a refused fused plan
    # is counted (spmv_plan_rejections) and said (spmv_span_rejected /
    # spmv_vmem_rejected) where it is refused
    spmv = (prepare_device_spmv(el, mesh)
            if sweep_form(config, fused=True, hybrid=True) == "fused"
            else None)
    fused = spmv is not None
    de = prepare_device_edges(
        el, mesh,
        build_plan=sweep_form(config, fused, hybrid=True) == "hybrid",
        # the fused sweep reads no edge array: skip the dst-sort prep
        # and the uploads
        light=fused)
    de.spmv = spmv
    return _run_prepared(de, mesh, config, checkpoint_dir,
                         checkpoint_every)


def run_rmat(mesh: Mesh, config: PageRankConfig, scale: int,
             edge_factor: int = 16, abcd=None, seed: int = 0, *,
             checkpoint_dir: str | None = None,
             checkpoint_every: int = 5) -> PageRankResult:
    """``tda pagerank --rmat-scale``: a Graph500 Kronecker graph drawn,
    deduplicated and planned on the device (:func:`build_rmat_graph`),
    ranked by the fused sweep. A span past the geometry's window is an
    error here: the loader has no host copy to fall back with."""
    if sweep_form(config, fused=True, hybrid=True) != "fused":
        raise ValueError(
            "a graph drawn on the device is ranked by the fused sweep: "
            "mode='standard', scatter 'auto' or 'spmv'")
    graph = build_rmat_graph(mesh, scale, edge_factor, abcd, seed)
    spmv = prepare_device_spmv(graph, mesh)
    if spmv is None:
        raise RuntimeError(
            f"the graph's chunks span more destination rows than the "
            f"window fixed for its size (ws {graph.geom.ws} at rg "
            f"{graph.geom.rg}; the pagerank:plan span says by how "
            f"much): ops/pallas_pagerank.SPMV_SPAN_ROOM")
    de = spmv_device_edges(graph, mesh)
    de.spmv = spmv
    return _run_prepared(de, mesh, config, checkpoint_dir,
                         checkpoint_every)


def _run_prepared(de: DeviceEdges, mesh: Mesh, config: PageRankConfig,
                  checkpoint_dir: str | None,
                  checkpoint_every: int) -> PageRankResult:
    if checkpoint_dir is not None:
        return _run_segmented(de, mesh, config, checkpoint_dir,
                              checkpoint_every)
    fn = make_run_fn(mesh, config, de.n_vertices, de.plan, de.spmv)
    ranks, has_rank = fn(
        de.src, de.dst, de.w_e, de.emask, de.has_out, de.n_ref
    )
    return PageRankResult(ranks=ranks, has_rank=has_rank)


def _run_segmented(de: DeviceEdges, mesh: Mesh, config: PageRankConfig,
                   checkpoint_dir: str,
                   checkpoint_every: int) -> PageRankResult:
    """Checkpointed power iteration (state is the (V,) rank vector plus
    the reference mode's has_rank mask). Iterations are time-invariant,
    so resuming a saved carry is bitwise-identical to an uninterrupted
    scan — replacing the Spark task-retry the reference's
    10-join-deep lineage gets for free
    (``graph_computation/pagerank.py:52-57``)."""
    import dataclasses as dc

    from tpu_distalg.utils import checkpoint as ckpt

    V = de.n_vertices
    if config.mode == "reference":
        ranks0 = jnp.where(de.has_out > 0, 1.0 / de.n_ref, 0.0)
        has_rank0 = de.has_out
    else:
        ranks0 = jnp.full((V,), 1.0 / V, dtype=jnp.float32)
        has_rank0 = jnp.ones((V,), dtype=jnp.float32)

    def make_seg_fn(seg):
        return make_run_fn(mesh, dc.replace(config, n_iterations=seg),
                           V, de.plan, de.spmv)

    def run_seg(fn, state, t0):
        ranks, has_rank = fn(de.src, de.dst, de.w_e, de.emask,
                             de.has_out, de.n_ref,
                             state["ranks"], state["has_rank"])
        return ({"ranks": ranks, "has_rank": has_rank},
                np.asarray(jnp.sum(ranks), np.float32)[None])

    state, _, _ = ckpt.run_segmented(
        checkpoint_dir, checkpoint_every, config.n_iterations,
        make_seg_fn, run_seg,
        {"ranks": ranks0, "has_rank": has_rank0},
        # both modes carry the same (V,) f32 pair, so the shape check
        # alone cannot catch a cross-mode resume — encode the mode
        tag=f"pagerank_{config.mode}",
        span_fields=(de.spmv.forms
                     if sweep_form(config, de.spmv is not None,
                                   de.plan is not None) == "fused"
                     else None))
    return PageRankResult(ranks=jnp.asarray(state["ranks"]),
                          has_rank=jnp.asarray(state["has_rank"]))
