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
  * standard mode goes one further: dst-sortedness means consecutive
    edges target a narrow band of a (V/128, 128) vertex table, so the
    scatter becomes a Pallas kernel (``ops/pallas_pagerank``) that
    keeps the table VMEM-resident and scatter-adds each 1024-edge
    chunk with ONE one-hot MXU matmul — no random-access engine at
    all. Measured: sweep drops ~17 → ~9.2 ns/edge (13.5 iter/s at
    1M×8M on one v5e). The remaining random op, the ``ranks[src]``
    gather, stays in XLA: a Pallas windowed gather is 4× faster in
    isolation but needs src-sorted edges, and re-crossing the per-edge
    array between sort orders costs exactly the random access it
    saves (full analysis: ``ops/pallas_pagerank`` docstring).

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
    scatter: str = "auto"  # 'auto' | 'pallas' | 'xla' (standard mode)


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
    """Device-resident :class:`ops.pallas_pagerank.SpMVPlan` arrays —
    the fully-fused Path E sweep (``scatter='spmv'``)."""

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
    """True when the fused-SpMV VMEM guard would reject this vertex
    count even at the smallest scatter window — the documented ~12M
    resident ceiling (``ops/pallas_pagerank.SPMV_VMEM_BUDGET``). The
    signal the CLI keys its warn-and-degrade-to-streamed on: past this
    line the resident paths either refuse (spmv) or fall back to
    sweeps that need the whole edge set HBM-resident anyway."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    return ppr.spmv_resident_bytes(n_vertices, ppr.SPMV_RG, 8) \
        > ppr.SPMV_VMEM_BUDGET


def choose_data_backend(requested: str, n_vertices: int,
                        scatter: str = "auto"
                        ) -> tuple[str, str | None]:
    """Resolve the pagerank ``--data-backend`` knob against the
    resident VMEM guard: a resident request past the ceiling degrades
    to streamed WITH a warning instead of dying minutes later in the
    sweep prep (the guard used to just refuse). An EXPLICIT
    ``--scatter xla``/``pallas`` resident request is honored — the
    ceiling is the fused-SpMV kernel's table budget, and those sweeps
    carry their own (HBM/plan) limits with remedy-naming errors.
    Returns ``(backend, warning-or-None)``."""
    if requested == "resident" and scatter in ("auto", "spmv") \
            and resident_guard_trips(n_vertices):
        return "streamed", (
            f"[pagerank] {n_vertices} vertices exceed the resident "
            f"sweep's VMEM guard (~12M ceiling, "
            f"ops/pallas_pagerank.SPMV_VMEM_BUDGET) — degrading to "
            f"--data-backend streamed (tpu_distalg/graphs/: edge "
            f"blocks stream from disk, only O(V) state stays in HBM)")
    return requested, None


def _inv_out_degree(el: gops.EdgeList) -> np.ndarray:
    """Per-vertex 1/out_degree (0 for sinks) — THE per-edge weight
    definition, shared by every sweep path (the graph engine's ingest
    included: ``graphs/ingest.inv_out_degree`` is the one
    implementation) so they cannot diverge."""
    from tpu_distalg.graphs.ingest import inv_out_degree

    return inv_out_degree(el.out_degree)


def prepare_device_spmv(el: gops.EdgeList, mesh: Mesh,
                        rg: int | None = None) -> DeviceSpMV | None:
    """Host prep for the fused Path E sweep: two-key edge sort +
    per-chunk window metadata (``ops/pallas_pagerank.plan_spmv``),
    device_put sharded over the data axis by chunk blocks. ``None``
    when the graph's structure exceeds the window caps — callers fall
    back to the hybrid/XLA sweep.

    With ``rg=None`` the gather window ESCALATES (128 → 256 → 512
    rows) until the within-group scatter span fits: the span grows as
    R²/(rg·E), so larger vertex counts need taller windows — 10M
    vertices / 80M edges plans at rg=512 (ws=184) where rg=128
    overflows. Taller windows cost proportionally more unrolled gather
    rows (and Mosaic compile time: ~3 min at rg=512 vs ~10 s at 128);
    each escalation re-sorts, so the 512 attempt on an 80M-edge graph
    spends ~2-3 minutes of host prep. VMEM bounds the table:
    (r8 + ws + rg) · 512 B must stay under the ~100 MB budget, which
    holds to ~12M vertices — ``plan_spmv`` now enforces that budget
    itself (``spmv_resident_bytes``) BEFORE paying the sorts, so
    oversized graphs degrade here instead of failing the Mosaic
    compile minutes later. Each plan attempt runs in a telemetry span
    (``pagerank:plan_spmv:rgN``, child of ``pagerank:prepare``, which
    also covers the puts) — the sorts are exactly the kind of
    multi-minute host phase a stall report must be able to name."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    with tevents.span("pagerank:prepare", edges=int(el.n_edges)):
        inv_deg = _inv_out_degree(el)
        n_shards = mesh.shape[DATA_AXIS]
        plan = None
        for r in ((rg,) if rg is not None else (ppr.SPMV_RG, 256, 512)):
            with tevents.span(f"pagerank:plan_spmv:rg{r}",
                              n_edges=int(el.n_edges),
                              n_vertices=int(el.n_vertices)):
                plan = ppr.plan_spmv(el.src, el.dst, inv_deg[el.src],
                                     el.n_vertices, n_shards=n_shards,
                                     rg=r)
            if plan is not None:
                break
            tevents.counter("spmv_plan_rejections")
        if plan is None:
            return None
        put = lambda a, n: partition.put(  # noqa: E731
            a, n, "pagerank", mesh)
        return DeviceSpMV(
            gbase=put(plan.gbase, "gbase"), sbase=put(plan.sbase, "sbase"),
            src_lane=put(plan.src_lane, "src_lane"),
            src_row=put(plan.src_row, "src_row"),
            dst_row=put(plan.dst_row, "dst_row"),
            dst_lane=put(plan.dst_lane, "dst_lane"),
            w_e=put(plan.w_e, "w_e"), rg=plan.rg, ws=plan.ws, r8=plan.r8,
            blk=plan.blk, n_chunks=plan.n_chunks)


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
        z = np.zeros(n_shards, np.int32)
        zf = np.zeros(n_shards, np.float32)
        return DeviceEdges(
            src=put(z, "src"), dst=put(z, "dst"), w_e=put(zf, "w_e"),
            emask=put(zf, "emask"),
            inv_deg=jnp.asarray(inv_deg), has_out=jnp.asarray(has_out),
            n_vertices=V, n_ref=float(has_out.sum()), plan=None)

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


def make_run_fn(mesh: Mesh, config: PageRankConfig, n_vertices: int,
                plan: DevicePlan | None = None,
                spmv: DeviceSpMV | None = None):
    """Build the jitted n-iteration sweep.

    PRECONDITION: the edge arrays passed to the returned ``run`` MUST be
    dst-sorted per shard with order-preserving padding — exactly what
    :func:`prepare_device_edges` produces. The segment-sums inside promise
    ``indices_are_sorted=True`` to XLA, which is unchecked: unsorted
    ``dst`` yields silently wrong rank sums, not an error. Construct the
    inputs via :func:`prepare_device_edges` (or :func:`run`, which does).

    Standard-mode path choice: with an ``spmv`` plan (and scatter
    'auto'/'spmv') the fully-fused tiled SpMV runs — gather AND
    scatter in one Pallas kernel, measured ~2.9 ns/edge full-iteration
    at 1M×8M on one v5e. 'auto' PREFERS it; the hybrid sweep (XLA
    ``ranks[src]·w`` gather + the windowed one-hot-MXU scatter
    ``plan``, ~9.2 ns/edge) is the fallback when the spmv windows
    exceed their caps, and the XLA-only sweep (~17 ns/edge) the final
    fallback. ``scatter='pallas'``/'spmv' without their plan raise;
    'xla' forces the legacy path (benchmark A/B).
    """
    V = n_vertices
    q = config.q

    if config.scatter not in ("auto", "pallas", "xla", "spmv"):
        raise ValueError(f"unknown scatter mode {config.scatter!r}")
    if config.mode != "standard" and config.scatter != "auto":
        raise ValueError(
            f"scatter={config.scatter!r} only applies to mode="
            "'standard' — the reference-parity mode always uses the "
            "XLA segment_sum path"
        )
    use_pallas = (config.mode == "standard"
                  and config.scatter in ("auto", "pallas")
                  and plan is not None)
    if config.mode == "standard" and config.scatter == "pallas" \
            and plan is None:
        raise ValueError(
            "scatter='pallas' needs a scatter plan — the graph's dst "
            "distribution was too sparse/skewed for a bounded window "
            "(ops/pallas_pagerank.plan_scatter returned None). For "
            "graphs past the resident ceiling, use the streamed "
            "engine instead: --data-backend streamed "
            "(tpu_distalg/graphs/)"
        )
    if config.mode == "standard" and config.scatter == "spmv" \
            and spmv is None:
        raise ValueError(
            "scatter='spmv' needs the fused-SpMV plan — build the "
            "DeviceSpMV via prepare_device_spmv (None means the "
            "graph's windows exceeded ops/pallas_pagerank caps, or "
            "the kernel-resident VMEM footprint blew "
            "SPMV_VMEM_BUDGET — the ~12M-vertex ceiling). Graphs "
            "past the resident ceiling belong on the out-of-core "
            "engine: --data-backend streamed (tpu_distalg/graphs/ "
            "streams edge blocks from disk; only O(V) state stays "
            "in HBM)"
        )

    if config.mode == "reference":
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

    if (config.mode == "standard"
            and config.scatter in ("auto", "spmv")
            and spmv is not None):
        # Path E: the fully-fused tiled SpMV — gather AND scatter in
        # one Pallas kernel, no XLA random-access op in the sweep.
        # 'auto' prefers it (measured 3.7x the hybrid sweep at 1Mx8M)
        from tpu_distalg.ops import pallas_pagerank as ppr

        interpret = not mesh_on_tpu(mesh)
        rg, ws, r8, blk = spmv.rg, spmv.ws, spmv.r8, spmv.blk
        pad = (r8 + rg) * 128 - V

        def body(gb, sb, slane, srow, drow, dlane, we, ranks):
            with jax.named_scope(names.PAGERANK_SPMV):
                rt = jnp.pad(ranks, (0, pad)).reshape(r8 + rg, 128)
                acc = ppr.spmv_table(gb, sb, rt, slane, srow, drow,
                                     dlane, we, rg=rg, ws=ws, r8=r8,
                                     blk=blk, interpret=interpret)
            return tree_allreduce_sum(acc)

        sweep_fn = data_parallel(
            body, mesh,
            in_specs=(P("data"), P("data"))
            + (P("data", None),) * 5 + (P(),),
            out_specs=P(),
        )

        def run(src, dst, w_e, emask, has_out, n_ref,
                ranks0=None, has_rank0=None):
            del src, dst, w_e, emask, n_ref, has_rank0  # plan-encoded
            if ranks0 is None:
                ranks0 = jnp.full((V,), 1.0 / V, dtype=jnp.float32)

            def step(ranks, _):
                acc = sweep_fn(spmv.gbase, spmv.sbase, spmv.src_lane,
                               spmv.src_row, spmv.dst_row,
                               spmv.dst_lane, spmv.w_e, ranks)
                c = acc[:r8].reshape(-1)[:V]
                if config.redistribute_dangling:
                    dangling = jnp.sum(ranks * (1.0 - has_out))
                    c = c + dangling / V
                ranks = q / V + (1 - q) * c
                return ranks, None

            ranks, _ = jax.lax.scan(
                step, ranks0, None, length=config.n_iterations
            )
            return ranks, jnp.ones((V,), dtype=jnp.float32)

        return jax.jit(run)

    if use_pallas:
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
                if config.redistribute_dangling:
                    dangling = jnp.sum(ranks * (1.0 - has_out))
                    c = c + dangling / V
                ranks = q / V + (1 - q) * c
                return ranks, None

            ranks, _ = jax.lax.scan(
                step, ranks0, None, length=config.n_iterations
            )
            return ranks, jnp.ones((V,), dtype=jnp.float32)

        return jax.jit(run)

    # standard mode, XLA path: every vertex ranked, Σranks preserved;
    # one gather + one sorted scatter per iteration
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
            if config.redistribute_dangling:
                dangling = jnp.sum(ranks * (1.0 - has_out))
                c = c + dangling / V
            ranks = q / V + (1 - q) * c
            return ranks, None

        ranks, _ = jax.lax.scan(
            step, ranks0, None, length=config.n_iterations
        )
        return ranks, jnp.ones((V,), dtype=jnp.float32)

    return jax.jit(run)


def run(edges: np.ndarray, mesh: Mesh,
        config: PageRankConfig = PageRankConfig(),
        n_vertices: int | None = None, *,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 5) -> PageRankResult:
    el = gops.prepare_edges(edges, n_vertices)
    if config.mode == "standard" and config.scatter in ("auto", "spmv"):
        spmv = prepare_device_spmv(el, mesh)
    else:
        spmv = None
    de = prepare_device_edges(
        el, mesh,
        # the hybrid plan is only needed when it will actually run:
        # explicit 'pallas', or 'auto' falling back from a failed spmv
        build_plan=(config.mode == "standard"
                    and (config.scatter == "pallas"
                         or (config.scatter == "auto"
                             and spmv is None))),
        # when the spmv path will run, skip the dst-sort prep + edge
        # uploads it deletes anyway
        light=spmv is not None)
    de.spmv = spmv
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
        tag=f"pagerank_{config.mode}")
    return PageRankResult(ranks=jnp.asarray(state["ranks"]),
                          has_rank=jnp.asarray(state["has_rank"]))
