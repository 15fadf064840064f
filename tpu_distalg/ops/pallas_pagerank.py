"""Windowed one-hot-MXU scatter: the PageRank sweep's Pallas half.

The reference pays a full shuffle per PageRank iteration
(``/root/reference/graph_computation/pagerank.py:52-57`` — join +
flatMap + reduceByKey). The XLA re-design (``ops/graph.py``) reduced
that to one random gather (``ranks[src]``) plus one sorted
``segment_sum`` per edge per sweep, measured ~16-17 ns/edge on one
v5e — bound by the ~8 ns/element issue rate of EACH random-access XLA
op, not by bandwidth (the sweep streams ~12 B/edge, <1% of HBM).

This module replaces the scatter half with a Pallas kernel measured
~2.1 ns/edge, taking the full sweep to ~9.2 ns/edge (13.5 iter/s at
1M vertices / 8M edges, ~1.8× the XLA sweep), exact to f32.

How the scatter dodges the random-access engine
-----------------------------------------------
Vertex ``v`` lives at (row ``v//128``, lane ``v%128``) of an
(R, 128) f32 table that stays VMEM-resident across the whole pass
(4 MB at 1M vertices). Because edges are dst-sorted (graph prep,
``models/pagerank.py``), any chunk of 1024 consecutive edges lands in
a narrow band of table rows — the prep computes each chunk's base row
and verifies the worst-case span (``plan_scatter``). Per chunk the
kernel builds two small masks from lane-major loads (no relayouts):

  * ``m[ρ, e]   = contrib[e] · (row[e] == base + ρ)``   (8W, 1024)
  * ``onehotᵀ[λ, e] = (lane[e] == λ)``                  (128, 1024)

and one MXU matmul ``m @ onehotᵀ.T`` scatter-adds the whole chunk into
the resident window ``acc[base : base+8W]``. The matmul runs
``precision=HIGHEST`` (6-pass) because one operand carries real f32
contributions — DEFAULT truncates to bf16 and costs ~1e-3 relative
error in rank sums; measured, HIGHEST is within noise of DEFAULT here
because the kernel is mask-build/VPU-bound, not MXU-bound.

What was tried and rejected for the gather half (recorded so the next
round doesn't re-walk it):

  * Mosaic's ``tpu.dynamic_gather`` is vreg-local: it gathers along
    sublanes ONLY within one (8, 128) vreg ("Multiple source vregs
    along gather dimension" otherwise) — there is no primitive gather
    from a tall VMEM table.
  * A windowed Pallas gather (edges src-sorted, per-chunk vreg window,
    selector over ≤32 vregs) measures ~2.2 ns/edge — 4× under XLA's
    ~8.8. BUT it requires src-sorted edges while this scatter requires
    dst-sorted edges, and crossing a per-edge array from one order to
    the other is itself a random permutation at the same ~8 ns/element
    XLA cost — the crossing eats the entire gather win. One side must
    stay in XLA; the scatter is the better Pallas half because its
    XLA form (segment_sum over 1M segments) measures 15-20 ns/edge
    in isolation vs the gather's 8.8.
  * 1D dynamic slices inside a kernel (``ref[pl.ds(i*1024, 1024)]``)
    scalarise: a loads-only ablation measured ~13 ns/edge. Everything
    here is therefore 2D lane-major blocks. An (E, 1) column layout is
    equally fatal: TPU pads the lane dim to 128 (128× HBM traffic).

The fully-fused tiled SpMV (Path E) was costed in round 4 and BUILT in
round 5 (:func:`plan_spmv` / :func:`spmv_table`): measured
**1.5-1.75 ns/edge** at 1M×8M on one v5e — ~6x the hybrid sweep above
and beyond the 3-4 ns/edge pencil, because the scatter got cheaper than
priced (ws=80 windows at rg=128) while the unrolled gather row-loop
hits the VPU issue rate. The round-4 pencil, kept for the record:

  * the missing primitive EXISTS: Mosaic also lowers a LANE-direction
    ``dynamic_gather`` (``take_along_axis(x, idx, axis=1)`` with
    same-shape operands, verified working including multi-vreg row
    batches), so a full (8, 128)-vreg gather is 8 lane-gathers + 8
    selects — no lane constraint on edge placement;
  * sort edges by (src-block of V/n vertices, dst); per 1024-edge
    chunk the gather windows over 1024/n vregs of the rank table
    (selector ≈ 24·W ops) and the scatter windows over ≈n/8+1 vregs
    (dst-sorted within group). With a bf16 hi+lo split for the
    scatter matmul (2-pass, ~1.5e-5 relative — near-f32) the optimum
    near n=32 pencils to ~1.4 VPU-cycles/edge + builds ≈ 3 ns/edge,
    ~2× this hybrid;
  * the costs NOT in the pencil: the gather chunk must be (8, 128)
    (lane-gather needs a 128-lane axis) while the scatter matmul
    wants the edge dim as one 1024-lane axis — bridging them means 8
    per-sublane (rows_w, 128)@(128, 128) matmuls and sublane
    extraction glue; plus per-group chunk padding and a two-key host
    sort. Every windowed-kernel estimate this round landed ~2× under
    the measured result once loop overhead was counted, which prices
    the fused kernel at ~5-7 ns/edge end-to-end — a 1.3-1.8× for
    ~300 lines of delicate kernel; deferred, not disproven.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 128
DEF_CHUNK = 1024  # edges per in-kernel chunk (one matmul each)
DEF_BLK = 32      # chunks per grid step (keeps per-shard padding small)
MAX_W = 4         # widest row window: 8*W rows; beyond -> fall back

# ---- Path E (the fully-fused tiled SpMV) geometry ----
# rg=128 measured 1.5-1.75 ns/edge at 1M×8M on one v5e vs 2.1-2.4 for
# rg=64 (ws shrinks 168 -> 80: the 8 per-sublane scatter builds cost
# more than the extra 64 unrolled gather rows save).
# Scale law: the within-group scatter span grows as R²/(rg·E) rows, so
# bigger graphs need taller gather windows — 10M×80M plans at rg=512
# (ws=184; numerics verified on hardware, 1.5e-7) where rg=128
# overflows; models/pagerank.prepare_device_spmv escalates rg
# automatically. Costs at rg=512: ~50 s host sort per attempt and
# ~3 min Mosaic compile (the gather row-loop unrolls rg iterations).
# VMEM bounds the whole path at ~11M vertices (table + acc ≈ 81 MB).
SPMV_RG = 128      # gather window rows (vertices / window = rg*128)
SPMV_WS_CAP = 192  # max scatter window rows before falling back
SPMV_BLK = 8       # chunks per grid step
# plan-time VMEM budget: spmv_table compiles with vmem_limit_bytes =
# 128 MB, but Mosaic also needs scratch for the per-chunk temporaries
# (the (ws,128) upd accumulator, (128,128) one-hots, select masks), so
# plans whose RESIDENT footprint passes ~100 MB fail at compile time —
# after the multi-minute host sorts. plan_spmv rejects them up front
# (spmv_resident_bytes), so scatter='auto' degrades to the hybrid/XLA
# sweep instead. ~100 MB ≈ 8 bytes/vertex → the path self-caps at
# ~12-13M vertices, matching the module docstring's measured bound.
SPMV_VMEM_BUDGET = 100 * 1024 * 1024


def _emit_vmem_rejection(n_vertices: int, rg: int) -> None:
    """Record a VMEM-budget plan rejection AND its remedy: the guard
    used to just refuse, leaving the caller to discover the ~12M
    resident ceiling from a docstring. The event (and the CLI's
    warn-and-degrade built on ``models/pagerank.choose_data_backend``)
    names the out-of-core engine instead."""
    from tpu_distalg.telemetry import events as tevents

    tevents.emit(
        "spmv_vmem_rejected", n_vertices=int(n_vertices), rg=int(rg),
        budget_bytes=SPMV_VMEM_BUDGET,
        remedy="--data-backend streamed (tpu_distalg/graphs/: edge "
               "blocks stream from disk, only O(V) state in HBM)")


def spmv_resident_bytes(n_vertices: int, rg: int, ws: int,
                        blk: int = SPMV_BLK) -> int:
    """Kernel-resident VMEM bytes of an SpMV plan geometry: the ranks
    table (r8+rg, 128) f32 + the output table (r8+ws, 128) f32 + the 5
    per-grid-step edge-block operands (blk·8, 128) i32/f32, double-
    buffered by the grid pipeline."""
    r8 = ((n_vertices + LANES - 1) // LANES + 7) // 8 * 8
    tables = (r8 + rg + r8 + ws) * LANES * 4
    edge_blocks = 2 * 5 * blk * 8 * LANES * 4
    return tables + edge_blocks


@dataclasses.dataclass(frozen=True)
class ScatterPlan:
    """Host-side prep for :func:`scatter_table` over dst-sorted edges.

    Arrays are per-chunk lane-major layouts of the (padded) edge list;
    on a sharded mesh each shard holds ``n_chunks / n_shards`` chunk
    rows and the plan arrays shard along axis 0 exactly like the edge
    arrays they were derived from.
    """

    base: np.ndarray      # (NCH,) int32 sublane-aligned window base row
    row: np.ndarray       # (NCH, CHUNK) int32 dst // 128
    lane: np.ndarray      # (NCH, CHUNK) int32 dst % 128
    w: int                # window vregs: window is 8*w rows
    chunk: int
    blk: int
    n_chunks: int
    r8: int               # table rows, padded to a sublane multiple
    n_pad_edges: int      # edges added to reach the chunk grid
    shard_len: int        # padded edges per shard slice
    real_per_shard: tuple[int, ...]  # real (unpadded) edges per shard —
    # the ONE place the shard slicing is encoded; consumers building
    # aligned per-edge arrays (src/w/mask) must use these counts


def plan_scatter(dst_sorted: np.ndarray, n_vertices: int,
                 n_shards: int = 1, chunk: int = DEF_CHUNK,
                 blk: int = DEF_BLK) -> ScatterPlan | None:
    """Build the chunk/window plan, or ``None`` if the graph's dst
    distribution is too skewed for a ≤``MAX_W``-vreg window (the
    caller then keeps the XLA segment_sum path — correctness never
    depends on the plan succeeding; very sparse graphs, where 1024
    consecutive dst-sorted edges span many table rows, fall back too).

    Padding edges replicate the LAST real dst of their shard slice with
    zero contribution, so windows stay tight and the padded tail is a
    no-op in the sum.
    """
    dst_sorted = np.asarray(dst_sorted, np.int32)
    e = len(dst_sorted)
    if e == 0:
        return None
    gran = chunk * blk * n_shards
    e_pad = (e + gran - 1) // gran * gran
    if e_pad > 2 * e:
        # grid-granularity padding would dominate (tiny graph for this
        # chunk geometry) — the XLA path is fine at these sizes
        return None
    shard_len = e_pad // n_shards
    # shard boundaries first (contiguous dst-sorted slices), THEN pad
    # each shard's tail with its own last dst — a shard must never
    # window across another shard's dst range
    cols = []
    real = []
    for s in range(n_shards):
        lo = min(e, s * shard_len)
        hi = min(e, lo + shard_len)
        part = dst_sorted[lo:hi]
        real.append(hi - lo)
        if len(part) < shard_len:
            fill = part[-1] if len(part) else dst_sorted[-1]
            part = np.concatenate(
                [part, np.full(shard_len - len(part), fill, np.int32)])
        cols.append(part)
    dst_p = np.concatenate(cols)
    rows = (dst_p // LANES).astype(np.int32).reshape(-1, chunk)
    lanes = (dst_p % LANES).astype(np.int32).reshape(-1, chunk)
    base = (rows.min(axis=1) // 8 * 8).astype(np.int32)
    span = int((rows.max(axis=1) - base).max())
    w = span // 8 + 1
    if w > MAX_W:
        return None
    r8 = ((n_vertices + LANES - 1) // LANES + 7) // 8 * 8
    return ScatterPlan(base=base, row=rows, lane=lanes, w=w,
                       chunk=chunk, blk=blk, n_chunks=rows.shape[0],
                       r8=r8, n_pad_edges=e_pad - e,
                       shard_len=shard_len, real_per_shard=tuple(real))


def _kernel(base_ref, c_ref, row_ref, lane_ref, acc_ref, *,
            w: int, chunk: int, blk: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sub_iota = jax.lax.broadcasted_iota(jnp.int32, (8 * w, chunk), 0)
    lane_sub_iota = jax.lax.broadcasted_iota(jnp.int32, (LANES, chunk), 0)
    pid = pl.program_id(0)  # hoisted: not interpretable inside fori_loop

    def body(i, _):
        gi = pid * blk + i
        b = base_ref[gi]
        c = c_ref[pl.ds(i, 1), :]                       # (1, chunk)
        r = row_ref[pl.ds(i, 1), :]
        ln = lane_ref[pl.ds(i, 1), :]
        m = jnp.where((r - b) == sub_iota, c, 0.0)      # (8w, chunk)
        onehot_t = (ln == lane_sub_iota).astype(jnp.float32)
        upd = jax.lax.dot_general(                      # (8w, LANES)
            m, onehot_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
        acc_ref[pl.ds(b, 8 * w), :] += upd
        return 0

    jax.lax.fori_loop(0, blk, body, 0)


@dataclasses.dataclass(frozen=True)
class SpMVPlan:
    """Host prep for :func:`spmv_table` — Path E, the fully-fused tiled
    SpMV (gather AND scatter in one kernel, costed in the module
    docstring and built in round 5).

    Edges are two-key sorted by (gather group, dst) where a gather
    group is a ``SPMV_RG``-row window of the rank table (``rg·128``
    vertices): every 1024-edge chunk then reads ranks from ONE window
    (lane-direction ``dynamic_gather`` + sublane selects — no random
    access engine) and, because dst is sorted within the group, writes
    into a narrow scatter window (the same one-hot-MXU scatter as
    :func:`scatter_table`, built per gather sublane). All per-edge
    arrays are (NCH·8, 128) lane-major — the (8, 128) chunk layout the
    lane-gather requires.
    """

    gbase: np.ndarray     # (NCH,) int32 gather window base row
    sbase: np.ndarray     # (NCH,) int32 scatter window base row (8-mult)
    src_lane: np.ndarray  # (NCH*8, 128) int32  src % 128
    src_row: np.ndarray   # (NCH*8, 128) int32  src//128 - gbase
    dst_row: np.ndarray   # (NCH*8, 128) int32  dst//128 - sbase
    dst_lane: np.ndarray  # (NCH*8, 128) int32  dst % 128
    w_e: np.ndarray       # (NCH*8, 128) f32    inv_deg[src], 0 on pad
    rg: int               # gather window rows
    ws: int               # scatter window rows (8-mult)
    r8: int
    n_chunks: int
    chunk: int
    blk: int
    n_pad_edges: int


def plan_spmv(src: np.ndarray, dst: np.ndarray, w_e: np.ndarray,
              n_vertices: int, n_shards: int = 1, chunk: int = DEF_CHUNK,
              blk: int = SPMV_BLK, rg: int = SPMV_RG) -> SpMVPlan | None:
    """Two-key sort + per-group chunk padding + window metadata, or
    ``None`` when a group's within-chunk dst span exceeds
    ``SPMV_WS_CAP`` rows (very sparse/skewed graphs) or the kernel's
    resident VMEM footprint would exceed ``SPMV_VMEM_BUDGET`` (vertex
    tables at V≳12M — checked BEFORE the multi-minute host sorts) —
    callers fall back to the hybrid or XLA path; correctness never
    depends on the plan.

    Padding edges replicate a chunk's last (src, dst) with zero weight
    — inert in both the gather (reads a real window row) and the
    scatter (adds 0)."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w_e = np.asarray(w_e, np.float32)
    e = len(src)
    if e == 0:
        return None
    # VMEM guard BEFORE the expensive host work: when even the smallest
    # possible scatter window (ws=8) cannot fit the budget, the Mosaic
    # compile is guaranteed to fail AFTER the multi-minute sorts — bail
    # now so scatter='auto' degrades to the hybrid/XLA sweep instead
    # (ADVICE r5: the tables alone blow the budget at V≳12M).
    if spmv_resident_bytes(n_vertices, rg, 8, blk) > SPMV_VMEM_BUDGET:
        _emit_vmem_rejection(n_vertices, rg)
        return None
    # groups = EVEN partitions of the table rows (a fixed rg-row stride
    # would leave a skinny remainder group whose few edges span the
    # whole dst range — measured 1791-row chunks vs a 137-row p99).
    # Sizes are capped at rg-7 so the 8-aligned window base still
    # covers the whole group within rg rows.
    R = (n_vertices + LANES - 1) // LANES
    n_groups = max(1, -(-R // max(rg - 7, 1)))
    sizes = np.full(n_groups, R // n_groups, np.int64)
    sizes[: R % n_groups] += 1
    row_group = np.repeat(np.arange(n_groups), sizes)      # (R,)
    group_start = (np.concatenate([[0], np.cumsum(sizes)])[:-1]
                   // 8 * 8).astype(np.int32)
    group = row_group[src // LANES]
    # two-key sort as two stable LSD counting-sort passes (native C++,
    # O(E)): ~6x np.lexsort's comparison sort at 8M edges on this host
    from tpu_distalg import native

    p1 = native.counting_sort_perm(dst, n_vertices)
    p2 = native.counting_sort_perm(group[p1], n_groups)
    order = p1[p2]
    src, dst, w_e, group = (src[order], dst[order], w_e[order],
                            group[order])
    # per-group padding to whole chunks (replicated last edge, w=0)
    parts = []
    bounds = np.flatnonzero(np.diff(group)) + 1
    lo = 0
    for hi in list(bounds) + [e]:
        n_g = hi - lo
        pad = (-n_g) % chunk
        parts.append((lo, hi, pad))
        lo = hi
    sp, dp, wp = [], [], []
    for lo, hi, pad in parts:
        sp.append(src[lo:hi])
        dp.append(dst[lo:hi])
        wp.append(w_e[lo:hi])
        if pad:
            sp.append(np.full(pad, src[hi - 1]))
            dp.append(np.full(pad, dst[hi - 1]))
            wp.append(np.zeros(pad, np.float32))
    # inert whole chunks to reach the (blk × shards) grid granularity
    n_ch = sum(len(x) for x in sp) // chunk
    gran = blk * n_shards
    extra = (-n_ch) % gran
    if extra:
        sp.append(np.full(extra * chunk, src[e - 1]))
        dp.append(np.full(extra * chunk, dst[e - 1]))
        wp.append(np.zeros(extra * chunk, np.float32))
    src_p = np.concatenate(sp).astype(np.int64)
    dst_p = np.concatenate(dp).astype(np.int64)
    w_p = np.concatenate(wp)
    n_ch += extra
    if n_ch * chunk > 2 * e + gran * chunk:
        return None  # padding would dominate — tiny graph
    srows = (src_p // LANES).astype(np.int32).reshape(n_ch, chunk)
    drows = (dst_p // LANES).astype(np.int32).reshape(n_ch, chunk)
    gbase = group_start[row_group[srows[:, 0]]].astype(np.int32)
    if int((srows.max(axis=1) - gbase).max()) >= rg:
        return None  # group sizing guarantees this; belt&braces
    sbase = (drows.min(axis=1) // 8 * 8).astype(np.int32)
    span = int((drows.max(axis=1) - sbase).max()) + 1
    ws = (span + 7) // 8 * 8
    if ws > SPMV_WS_CAP:
        return None
    if spmv_resident_bytes(n_vertices, rg, ws, blk) > SPMV_VMEM_BUDGET:
        _emit_vmem_rejection(n_vertices, rg)
        return None  # actual ws confirmed the footprint overflow
    r8 = ((n_vertices + LANES - 1) // LANES + 7) // 8 * 8
    shape8 = (n_ch * 8, LANES)
    return SpMVPlan(
        gbase=gbase, sbase=sbase,
        src_lane=(src_p % LANES).astype(np.int32).reshape(shape8),
        src_row=(srows - gbase[:, None]).reshape(shape8),
        dst_row=(drows - sbase[:, None]).reshape(shape8),
        dst_lane=(dst_p % LANES).astype(np.int32).reshape(shape8),
        w_e=w_p.reshape(shape8), rg=rg, ws=ws, r8=r8, n_chunks=n_ch,
        chunk=chunk, blk=blk, n_pad_edges=n_ch * chunk - e)


def _spmv_kernel(gbase_ref, sbase_ref, ranks_ref, slane_ref, srow_ref,
                 drow_ref, dlane_ref, we_ref, out_ref, *, rg: int,
                 ws: int, blk: int):
    """Per chunk: unrolled window-row gather (broadcast row ρ →
    lane-gather by src_lane → select src_row==ρ), then the one-hot-MXU
    scatter built per gather sublane (8 small matmuls instead of one
    wide one — the price of bridging the (8,128) gather layout to the
    scatter, see the module docstring's Path E costing)."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    sub_iota_ws = jax.lax.broadcasted_iota(jnp.int32, (ws, LANES), 0)
    sub_iota128 = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    pid = pl.program_id(0)

    def body(i, _):
        gi = pid * blk + i
        gb = gbase_ref[gi]
        sb = sbase_ref[gi]
        slane = slane_ref[pl.ds(8 * i, 8), :]
        srow = srow_ref[pl.ds(8 * i, 8), :]
        drow = drow_ref[pl.ds(8 * i, 8), :]
        dlane = dlane_ref[pl.ds(8 * i, 8), :]
        we = we_ref[pl.ds(8 * i, 8), :]
        win = ranks_ref[pl.ds(gb, rg), :]               # (rg, 128)
        g = jnp.zeros((8, LANES), jnp.float32)
        for rho in range(rg):                           # static unroll
            rowv = jnp.broadcast_to(win[rho:rho + 1, :], (8, LANES))
            picked = jnp.take_along_axis(rowv, slane, axis=1)
            g = g + jnp.where(srow == rho, picked, 0.0)
        g = g * we
        upd = jnp.zeros((ws, LANES), jnp.float32)
        for s in range(8):                              # static unroll
            cb = jnp.broadcast_to(g[s:s + 1, :], (ws, LANES))
            m = jnp.where(
                jnp.broadcast_to(drow[s:s + 1, :], (ws, LANES))
                == sub_iota_ws, cb, 0.0)
            onehot_t = (jnp.broadcast_to(dlane[s:s + 1, :],
                                         (LANES, LANES))
                        == sub_iota128).astype(jnp.float32)
            upd += jax.lax.dot_general(
                m, onehot_t, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
        out_ref[pl.ds(sb, ws), :] += upd
        return 0

    jax.lax.fori_loop(0, blk, body, 0)


@functools.partial(jax.jit,
                   static_argnames=("rg", "ws", "r8", "blk", "interpret"))
def spmv_table(gbase, sbase, ranks_padded, src_lane, src_row, dst_row,
               dst_lane, w_e, *, rg: int, ws: int, r8: int,
               blk: int = SPMV_BLK, interpret: bool = False):
    """Per-shard fused SpMV: contributions ``ranks[src]·w_e``
    scatter-added into a dense (r8 + ws, 128) vertex table in ONE
    kernel — no XLA random-access op anywhere in the sweep.

    ``ranks_padded`` must be (r8 + rg, 128) (``rg`` zero guard rows so
    the last gather window slices in-bounds). Callers slice the result
    ``[:r8]`` and psum across shards."""
    nch8 = src_lane.shape[0]
    nch = nch8 // 8
    if nch % blk:
        raise ValueError(f"n_chunks {nch} must be a multiple of {blk}")
    if ranks_padded.shape != (r8 + rg, LANES):
        raise ValueError(
            f"ranks_padded must be ({r8 + rg}, {LANES}), got "
            f"{ranks_padded.shape}")
    return pl.pallas_call(
        functools.partial(_spmv_kernel, rg=rg, ws=ws, blk=blk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nch // blk,),
            in_specs=[
                pl.BlockSpec((r8 + rg, LANES), lambda i, s1, s2: (0, 0)),
            ] + [pl.BlockSpec((blk * 8, LANES),
                              lambda i, s1, s2: (i, 0))] * 5,
            out_specs=pl.BlockSpec((r8 + ws, LANES),
                                   lambda i, s1, s2: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((r8 + ws, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=128 * 1024 * 1024),
        interpret=interpret,
        name="_spmv_kernel",
    )(gbase, sbase, ranks_padded, src_lane, src_row, dst_row, dst_lane,
      w_e)


@functools.partial(jax.jit,
                   static_argnames=("w", "r8", "blk", "interpret"))
def scatter_table(base, contribs, row, lane, *, w: int, r8: int,
                  blk: int = DEF_BLK, interpret: bool = False):
    """Per-shard scatter-add of per-edge contributions into a dense
    (r8 + 8w, 128) vertex table (vertex v at row v//128, lane v%128).

    ``contribs/row/lane``: this shard's (NCH_local, chunk) lane-major
    chunk arrays; ``base``: (NCH_local,) window bases (scalar-prefetch).
    The trailing ``8w`` guard rows absorb windows that straddle the
    table end; callers slice ``[:r8]`` (they hold only padding targets'
    spill, which is zero-contribution anyway). Sum across shards (psum)
    completes ``reduceByKey(add)``.
    """
    nch, chunk = contribs.shape
    if nch % blk:
        raise ValueError(f"n_chunks {nch} must be a multiple of {blk}")
    return pl.pallas_call(
        functools.partial(_kernel, w=w, chunk=chunk, blk=blk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nch // blk,),
            in_specs=[pl.BlockSpec((blk, chunk), lambda i, s: (i, 0))] * 3,
            out_specs=pl.BlockSpec((r8 + 8 * w, LANES),
                                   lambda i, s: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((r8 + 8 * w, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )(base, contribs, row, lane)
