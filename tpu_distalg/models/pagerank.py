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
    deduplicated and planned on one chip in 8.3 s warm (PERF.md, PR 38).

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
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from tpu_distalg.ops import graph as gops
from tpu_distalg.parallel import (
    DATA_AXIS,
    data_parallel,
    mesh_on_tpu,
    partition,
    tree_allreduce_sum,
)
from tpu_distalg.telemetry import events as tevents
from tpu_distalg.telemetry import names


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

    LEAVES = ("gbase", "sbase", "src_lane", "src_row", "dst_row",
              "dst_lane", "w_e")   # the arrays, by their rule-table names

    @classmethod
    def of(cls, arrays, geom) -> "DeviceSpMV":
        """The seven arrays (in ``LEAVES``' order) of a plan of
        ``geom`` (``ops.pallas_pagerank.SpMVGeometry``)."""
        return cls(*arrays, rg=geom.rg, ws=geom.ws, r8=geom.r8,
                   blk=geom.blk, n_chunks=geom.n_chunks,
                   seg_steps=geom.seg_steps, n_groups=geom.n_groups)

    @property
    def ranks_form(self) -> str:
        """'windowed' past one gather group, else 'resident'."""
        return "windowed" if self.n_groups > 1 else "resident"

    @property
    def arrays(self) -> tuple:
        return tuple(getattr(self, n) for n in self.LEAVES)

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in self.arrays)


@dataclasses.dataclass
class DeviceGraph:
    """A graph's distinct edges on the device as the planner takes
    them: ``geom.n_slots`` slots, an edge wherever ``src >= 0`` among
    the first ``n_in``, spare slots behind."""

    src: jax.Array        # (n_slots,) int32, -1 where no edge
    dst: jax.Array        # (n_slots,) int32
    inv_deg: jax.Array    # (V,) f32, 1 / distinct out-edges, 0 for none
    has_out: jax.Array    # (V,) f32
    n_in: int
    n_vertices: int
    n_edges: int          # distinct edges
    geom: object          # ops.pallas_pagerank.SpMVGeometry
    meta: dict = dataclasses.field(default_factory=dict)


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


def resident_guard_trips(n_vertices: int) -> bool:
    """True when the fused SpMV cannot hold this many vertices: its
    output table, 4 B a vertex, has to stay in VMEM
    (``ops/pallas_pagerank.SPMV_VMEM_BUDGET``: 26M vertices). The
    signal the CLI keys its warn-and-degrade-to-streamed on: past this
    line the resident paths either refuse (spmv) or fall back to
    sweeps that need the whole edge set HBM-resident anyway."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    return ppr.spmv_resident_bytes(n_vertices, ppr.SPMV_RGS[-1],
                                   ppr.SPMV_WS_CAP) > ppr.SPMV_VMEM_BUDGET


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
            "graph's windows exceeded ops/pallas_pagerank caps, or "
            "the kernel-resident VMEM footprint blew "
            "SPMV_VMEM_BUDGET, 4 B a vertex). Graphs "
            "past the resident ceiling belong on the out-of-core "
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
                        scatter: str = "auto"
                        ) -> tuple[str, str | None]:
    """Resolve the pagerank ``--data-backend`` knob against the
    resident VMEM guard: a resident request past the ceiling degrades
    to streamed WITH a warning instead of dying in the sweep prep. The
    ceiling is the fused sweep's table budget, so it applies where a
    standard-mode sweep under this ``scatter`` would be fused
    (:func:`sweep_form`); an EXPLICIT ``--scatter xla``/``pallas``
    resident request is honored: those sweeps carry their own
    (HBM/plan) limits with remedy-naming errors.
    Returns ``(backend, warning-or-None)``."""
    would_fuse = sweep_form(
        PageRankConfig(mode="standard", scatter=scatter),
        fused=True, hybrid=True) == "fused"
    if requested == "resident" and would_fuse \
            and resident_guard_trips(n_vertices):
        return "streamed", (
            f"[pagerank] {n_vertices} vertices exceed the resident "
            f"sweep's VMEM guard (the fused SpMV keeps 4 B a vertex in "
            f"VMEM, ops/pallas_pagerank.SPMV_VMEM_BUDGET) — degrading "
            f"to --data-backend streamed (tpu_distalg/graphs/: edge "
            f"blocks stream from disk, only O(V) state stays in HBM)")
    return requested, None


def _inv_out_degree(el: gops.EdgeList) -> np.ndarray:
    """Per-vertex 1/out_degree (0 for sinks) — THE per-edge weight
    definition, shared by every sweep path (the graph engine's ingest
    included: ``graphs/ingest.inv_out_degree`` is the one
    implementation) so they cannot diverge."""
    from tpu_distalg.graphs.ingest import inv_out_degree

    return inv_out_degree(el.out_degree)


def _replicated(mesh: Mesh):
    """The sharding of the edge slots before the plan: whole on every
    chip (the ``pagerank`` rule table's ``slots``)."""
    return partition.leaf_sharding("pagerank", "slots", mesh)


def rmat_programs(mesh: Mesh, scale: int, abcd, geom, n_in: int):
    """:func:`build_rmat_graph`'s two jitted programs, ``generate(seed)
    -> (src, dst)`` and ``dedup(src, dst) -> (src, dst, inv_deg,
    has_out, n_distinct)`` (the chipless compile check lowers them at
    the cell's shapes)."""
    from tpu_distalg.utils import datasets

    V = 1 << scale
    draw = datasets.kronecker_edges(scale, abcd)
    rep = _replicated(mesh)

    def generate(seed):
        src, dst = draw(jnp.arange(n_in, dtype=jnp.uint32), seed)
        spare = geom.n_slots - n_in      # sorted behind every edge
        return (jnp.concatenate([src, jnp.full((spare,), V, jnp.int32)]),
                jnp.concatenate([dst, jnp.zeros((spare,), jnp.int32)]))

    def dedup(src, dst):
        src, dst = jax.lax.sort((src, dst), num_keys=2, is_stable=False)
        again = (src[1:] == src[:-1]) & (dst[1:] == dst[:-1])
        real = (src < V) & ~jnp.concatenate([jnp.zeros((1,), bool), again])
        deg = jax.ops.segment_sum(real.astype(jnp.int32), src,
                                  num_segments=V + 1,
                                  indices_are_sorted=True)[:V]
        inv_deg = jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1), 0.0)
        return (jnp.where(real, src, -1), dst,
                inv_deg.astype(jnp.float32),
                (deg > 0).astype(jnp.float32), jnp.sum(deg))

    return (jax.jit(generate, out_shardings=rep),
            jax.jit(dedup, out_shardings=rep, donate_argnums=(0, 1)))


def plan_programs(mesh: Mesh, geom, n_in: int):
    """:func:`prepare_device_spmv`'s two jitted programs: ``sort(src,
    dst) -> (src, dst)`` in the kernel's order and ``lay_out(src, dst,
    inv_deg) -> (the seven plan arrays, widest span)``."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    def lay_out(src, dst, inv_deg):
        w_e = inv_deg[jnp.maximum(src, 0)]
        arrays, span = ppr.slot_arrays(jnp, src, dst, w_e, geom)
        return tuple(partition.constrain(a, n, "pagerank", mesh)
                     for a, n in zip(arrays, DeviceSpMV.LEAVES)), span

    return (jax.jit(functools.partial(ppr.sort_slots, geom=geom,
                                      n_in=n_in),
                    out_shardings=_replicated(mesh),
                    donate_argnums=(0, 1)),
            jax.jit(lay_out, donate_argnums=(0, 1)))


def build_rmat_graph(mesh: Mesh, scale: int, edge_factor: int = 16,
                     abcd=None, seed: int = 0,
                     rg: int | None = None) -> DeviceGraph:
    """The program's own loader of a Graph500 Kronecker graph, on the
    device: ``edge_factor * 2**scale`` edges drawn from the seed as an
    argument (``utils/datasets.kronecker_edges``), sorted by (source,
    destination), a repeated edge marked and counted once, the distinct
    out-degrees added up from the sorted sources. Nothing crosses to
    the host but the count of distinct edges. Every shape follows from
    (scale, edge_factor, shards): one compile serves every seed."""
    from tpu_distalg.ops import pallas_pagerank as ppr
    from tpu_distalg.utils import datasets

    V, n_in = 1 << scale, edge_factor << scale
    abcd = tuple(abcd or datasets.GRAPH500_ABCD)
    geom = ppr.spmv_geometry(V, n_in, mesh.shape[DATA_AXIS], rg)
    if geom is None:
        raise ValueError(
            f"2**{scale} vertices are past the resident fused SpMV "
            f"(4 B a vertex in VMEM): --data-backend streamed")
    generate, dedup = rmat_programs(mesh, scale, abcd, geom, n_in)
    with tevents.span("pagerank:generate", scale=scale,
                      generated=n_in, seed=int(seed)):
        src, dst = jax.block_until_ready(
            generate(np.uint32(seed & 0xFFFFFFFF)))
    with tevents.span("pagerank:dedup", generated=n_in):
        src, dst, inv_deg, has_out, n_edges = dedup(src, dst)
        n_edges = int(n_edges)
        tevents.current().fields["distinct"] = n_edges
    return DeviceGraph(
        src=src, dst=dst, inv_deg=inv_deg, has_out=has_out, n_in=n_in,
        n_vertices=V, n_edges=n_edges, geom=geom,
        meta=dict(generator="kronecker", scale=scale,
                  edge_factor=edge_factor, abcd=abcd, seed=int(seed),
                  generated=n_in, distinct=n_edges))


def device_graph(el: gops.EdgeList, mesh: Mesh,
                 rg: int | None = None) -> DeviceGraph | None:
    """A host edge list (distinct already) copied up once in the form
    the device planner takes; ``None`` past the VMEM budget."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    geom = ppr.spmv_geometry(el.n_vertices, el.n_edges,
                             mesh.shape[DATA_AXIS], rg) \
        if el.n_edges else None
    if geom is None:
        return None
    put = lambda a, n: partition.put(a, n, "pagerank", mesh)  # noqa: E731

    def slots(x, fill):
        out = np.full(geom.n_slots, fill, np.int32)
        out[:el.n_edges] = x
        return put(out, "slots")

    inv_deg = _inv_out_degree(el)
    return DeviceGraph(
        src=slots(el.src, -1), dst=slots(el.dst, 0),
        inv_deg=put(inv_deg, "inv_deg"),
        has_out=put((inv_deg > 0).astype(np.float32), "has_out"),
        n_in=el.n_edges, n_vertices=el.n_vertices, n_edges=el.n_edges,
        geom=geom)


def prepare_device_spmv(graph: gops.EdgeList | DeviceGraph, mesh: Mesh,
                        rg: int | None = None) -> DeviceSpMV | None:
    """The fused sweep's plan, made on the device for every graph: a
    host edge list is copied up once (:func:`device_graph`), a
    :class:`DeviceGraph` is there already. One sort by (source group,
    destination row) with the padding in its keys puts every slot
    where the kernel reads it, and array code lays the seven plan
    arrays out, sharded over the data axis by chunk
    (``ops/pallas_pagerank.sort_slots`` / ``slot_arrays``). The
    geometry (``rg``, ``ws``, the slot count) is a function of the
    sizes alone, so a second graph of a size compiles nothing.

    ``None`` when the table passes the VMEM budget or a chunk's
    destinations span more than the geometry's ``ws`` rows (a graph
    sparser or more skewed than the window was sized for): counted in
    ``spmv_plan_rejections`` and said in the ``pagerank:plan`` span, so
    ``scatter='auto'`` falls back knowingly and ``'spmv'`` raises.
    ``pagerank:prepare`` covers the copy and the plan; the graph's own
    spans (``pagerank:generate``, ``pagerank:dedup``) come before it
    where the program drew the graph. The graph's edge arrays are
    donated to the sort."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    with tevents.span("pagerank:prepare"):
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
                  scatter_passes=ppr.SCATTER_PASSES,
                  padding_share=geom.n_slots / max(graph.n_edges, 1))
        sort, lay_out = plan_programs(mesh, geom, graph.n_in)
        with tevents.span("pagerank:plan", rg=geom.rg, ws=geom.ws):
            src, dst = sort(graph.src, graph.dst)
            graph.src = graph.dst = None
            arrays, span = lay_out(src, dst, graph.inv_deg)
            span = int(span)
            tevents.current().fields["span"] = span
        if span > geom.ws:
            tevents.counter("spmv_plan_rejections")
            tevents.emit("spmv_span_rejected", span=span, ws=geom.ws,
                         rg=geom.rg, n_vertices=graph.n_vertices)
            return None
        plan = DeviceSpMV.of(arrays, geom)
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

    def __init__(self, jitted, plan):
        self.jitted, self.plan = jitted, plan

    def _args(self, src, dst, w_e, emask, has_out, n_ref,
              ranks0=None, has_rank0=None):
        return self.plan, has_out, ranks0

    def __call__(self, *args):
        return self.jitted(*self._args(*args))

    def lower(self, *args):
        return self.jitted.lower(*self._args(*args))


def _teleport(config: PageRankConfig, V: int, ranks, c, has_out):
    """The standard update from a sweep's contributions ``c``: the
    dangling vertices' mass spread over all, then the teleport."""
    if config.redistribute_dangling:
        dangling = jnp.sum(ranks * (1.0 - has_out))
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


def _fused_run(mesh: Mesh, config: PageRankConfig, V: int,
               spmv: DeviceSpMV):
    """The fully-fused tiled SpMV: gather AND scatter in one Pallas
    kernel, no XLA random-access op in the sweep, the plan's arrays
    its arguments."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    interpret = not mesh_on_tpu(mesh)
    rg, ws, r8, blk = spmv.rg, spmv.ws, spmv.r8, spmv.blk
    rows = spmv.n_groups * rg         # the ranks table, whole groups

    def body(gb, sb, slane, srow, drow, dlane, we, ranks):
        with jax.named_scope(names.PAGERANK_SPMV):
            rt = jnp.pad(ranks, (0, rows * 128 - V)).reshape(rows, 128)
            acc = ppr.spmv_table(gb, sb, rt, slane, srow, drow,
                                 dlane, we, rg=rg, ws=ws, r8=r8,
                                 blk=blk,
                                 seg_steps=spmv.seg_steps or None,
                                 interpret=interpret)
        return tree_allreduce_sum(acc)

    sweep_fn = data_parallel(
        body, mesh,
        in_specs=(P("data"), P("data"))
        + (P("data", None),) * 5 + (P(),),
        out_specs=P(),
    )

    def run(plan, has_out, ranks0=None):
        if ranks0 is None:
            ranks0 = jnp.full((V,), 1.0 / V, dtype=jnp.float32)

        def step(ranks, _):
            acc = sweep_fn(*plan, ranks)
            with jax.named_scope(names.PAGERANK_UPDATE):
                c = acc[:r8].reshape(-1)[:V]
                ranks = _teleport(config, V, ranks, c, has_out)
            return ranks, None

        ranks, _ = jax.lax.scan(
            step, ranks0, None, length=config.n_iterations
        )
        return ranks, jnp.ones((V,), dtype=jnp.float32)

    return _PlanBound(jax.jit(run), spmv.arrays)


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
            return _teleport(config, V, ranks, c, has_out), None

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
            return _teleport(config, V, ranks, c, has_out), None

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

    from tpu_distalg.ops import pallas_pagerank as ppr
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
        span_fields=(dict(ranks_form=de.spmv.ranks_form, rg=de.spmv.rg,
                          ws=de.spmv.ws,
                          scatter_passes=ppr.SCATTER_PASSES)
                     if sweep_form(config, de.spmv is not None,
                                   de.plan is not None) == "fused"
                     else None))
    return PageRankResult(ranks=jnp.asarray(state["ranks"]),
                          has_rank=jnp.asarray(state["has_rank"]))
