"""PageRank's sweep as Pallas kernels: the windowed one-hot-MXU scatter
and the fused tiled SpMV built on it.

The reference pays a full shuffle per PageRank iteration
(``/root/reference/graph_computation/pagerank.py:52-57``: join +
flatMap + reduceByKey). The XLA re-design (``ops/graph.py``) reduced
that to one random gather (``ranks[src]``) plus one sorted
``segment_sum`` per edge per sweep, each bound by the issue rate of a
random-access XLA op and not by bandwidth (the benchmark's reference,
which is those two ops, takes 4.8 s a sweep of 263M edges on one v5e
where a fused sweep takes 0.27 s: PERF.md, PRs 38, 39 and 45). The kernels
here
touch no random-access engine.

The scatter (:func:`scatter_table`, the hybrid sweep's half)
------------------------------------------------------------
Vertex ``v`` lives at (row ``v//128``, lane ``v%128``) of an
(R, 128) f32 table that stays VMEM-resident across the whole pass.
Because edges are dst-sorted (graph prep, ``models/pagerank.py``), any
chunk of 1024 consecutive edges lands in a narrow band of table rows:
the prep computes each chunk's base row and verifies the worst-case
span (``plan_scatter``). Per chunk the kernel builds two small masks
from lane-major loads (no relayouts):

  * ``m[rho, e]   = contrib[e] * (row[e] == base + rho)``   (8W, 1024)
  * ``onehot_t[l, e] = (lane[e] == l)``                     (128, 1024)

and the MXU product ``m @ onehot_t.T`` scatter-adds the whole chunk
into the resident window ``acc[base : base+8W]``. One operand carries
real f32 contributions, which a single pass at the default precision
truncates to bf16 (~1e-3 relative error in rank sums); the other is 0
or 1, which bf16 holds exactly. So the product is three single passes
(:func:`scatter_window`): the contributions split once into three
pieces that bf16 holds exactly and that add back bit for bit
(``ops/bf16_pieces.split3``: all 24 bits), each piece masked and
multiplied by the one-hot, the three summed in f32. Every product is a
piece times 0 or 1, so nothing is rounded before the f32 sum.
``precision=HIGHEST``, the form until PR 39, splits both operands and
takes six passes, three of them against the one-hot's zero pieces.

What does not work, kept so that nobody walks it again: Mosaic's
sublane ``dynamic_gather`` is vreg-local (it gathers only within one
(8, 128) vreg: there is no primitive gather from a tall VMEM table);
1-D dynamic slices inside a kernel scalarise, and an (E, 1) column
layout pads its lane dimension to 128: everything here is 2-D
lane-major blocks; a gather in source order and a scatter in
destination order cannot share one edge order, and crossing a per-edge
array from one to the other is itself a random permutation.

The fused tiled SpMV (:func:`spmv_table`)
-----------------------------------------
Mosaic does lower a LANE-direction ``dynamic_gather``
(``take_along_axis(x, idx, axis=1)`` on same-shape operands), so a
table row broadcast over a vreg and gathered by ``src % 128`` serves
every edge of a chunk whose source is in that row. Edges are sorted by
(source group, destination row), a group ``rg`` rows of the ranks table
(``rg * 128`` vertices), and a group is padded to whole grid steps. A
chunk then reads ranks from ONE window of ``rg`` rows (a lane-gather
and a three-level select tree a tile of 8 rows) and, the destinations
sorted inside the group, writes a scatter window of ``ws`` rows: the
one-hot product above, built per gather sublane (8 sublanes x 3 pieces
of (ws, 128) x (128, 128): the gather chunk is (8, 128), the matmul
wants the edge dimension along lanes).

Since PR 38 the ranks table stays in HBM and a group's window is the
block the pipeline copies in when the group changes; only the output
table (4 B a vertex) is kept in VMEM, which is what bounds the path
(``SPMV_VMEM_BUDGET``: 26M vertices). The plan is made on the device
(:func:`sort_slots`, :func:`slot_arrays`) with every static shape a
function of the sizes (:func:`spmv_geometry`), and a sweep is a few
kernel calls so that each call's per-chunk scalars fit SMEM.

On a mesh a shard owns a range of destinations (``rows_out`` rows of
the table) and the edges that point into it (PR 44): its gather groups
run over the whole ranks table, its scatter rows are counted from its
range's first row, and the table it keeps in VMEM is its range's, so
the budget bounds the vertices a shard, not the graph. A range of a
larger graph is a sparser block: a chunk's span grows with the whole
table's groups over the shard's edges, and :func:`spmv_geometry`
weighs a taller group against a wider window by the schedule law
below (``SPMV_GATHER_ROW``, ``SPMV_SCATTER_ROW``).

Measured on one v5e (PERF.md section 6 has the tables). PR 38,
``scripts/step0_pagerank_resident.py``, the scatter still ``HIGHEST``:
at Graph500 SCALE 24 (rg 512, ws 224, 270.6M slots) a sweep took 752.9
ms with the gather loop whole in one turn, and 828.7 / 920.8 / 1095.2 /
2131.7 ms at 16 / 8 / 4 / 1 tiles a turn (the selects of a tile are a
chain, and tiles overlap only inside a turn). The static schedule of a
chipless compile said why: a chunk was 4333 bundles (at 1.5 GHz,
264 240 chunks: 0.76 s), 2895 of them the scatter's eight ``HIGHEST``
matmuls and 1438 the 512 gathered rows. PR 39 (PERF.md section 6):
with three passes a chunk was 3062 bundles, 1641 the scatter and 1421
the gather, one after the other, and a sweep took 521.9 ms, 1.93 ns a
slot (754.4 the six passes in the same run); at SCALE 20 (rg 128, ws
72) 12.2 ms, 0.70 ns a slot (16.9, 0.98). Each of the four MXUs
streams a row a cycle and pops a row a cycle, so 224 rows x 3 pieces x
8 sublanes are 1344 cycles a chunk however the pieces are stacked
(along the rows, along the contraction, as bf16: 521.6 to 523.5 ms,
the v5e's MXU does not add across a contraction). PR 45: the two
loops share no unit (the gather is XLU lane gathers and selects, the
scatter MXU streaming), so the chunk loop is pipelined by one chunk:
a turn scatters the chunk before and gathers its own, in ONE basic
block, and the scheduler issues the gather under the products whatever
their order in the source. A turn is 1544 bundles at rg 512 / ws 224
where the sequential body of the same source reads 3000 (200 over the
MXUs' 1344) and 2839 at ws 440 where it reads 4296 (the MXUs' 2640):
what bounds a chunk now is the MXUs' streaming of the window's rows.
On the chip a sweep of SCALE 24 takes 274.7 ms where the sequential
loop takes 520.9 in the same run (1.02 ns a slot for 1.93), and a
shard of four's block of SCALE 25 252.7 for 374.2, the tables bit for
bit the same (``scripts/step0_pagerank_overlap.py``; the schedule read
2.5% low both times). With ``SPMV_UNROLL`` 128 a group of 1024 rows is
one turn too: a shard of four's block of SCALE 26 (rg 1024, ws 440)
takes 507.9 ms where two turns of 64 tiles after the scatter take
1014.7 (2870 bundles a chunk for 5845).
The widest span a chunk writes was 201 to 206 rows on three seeds,
1.54 to 1.58 x the uniform mean.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from tpu_distalg.ops.bf16_pieces import split3
from tpu_distalg.ops.pallas_api import pl, pltpu


LANES = 128
DEF_CHUNK = 1024  # edges per in-kernel chunk
SCATTER_PASSES = 3  # bf16 MXU passes of the one-hot scatter product:
# one a piece of split3 (the spans' scatter_passes; HIGHEST took six;
# tests/test_pallas_pagerank.py holds it to what the kernels pass)
DEF_BLK = 32      # chunks per grid step (keeps per-shard padding small)
MAX_W = 4         # widest row window: 8*W rows; beyond -> fall back

# ---- the fused tiled SpMV's geometry ----
# A gather group is ``rg`` rows of the ranks table (rg * 128 vertices);
# a chunk of 1024 edges reads one group's window and, its destinations
# sorted by row inside the group, writes a window of ``ws`` rows. The
# span a chunk writes is R^2 * 1024 / (rg * E) rows where destinations
# are uniform (R table rows, E edges), so a sparser graph needs taller
# groups: spmv_geometry picks rg and fixes ws from the sizes alone.
SPMV_RGS = (128, 256, 512, 1024, 2048)  # gather window heights tried
SPMV_RG = SPMV_RGS[0]
# A chunk's bundles by its two loops' rows (PR 39's schedule laws, from
# chipless compiles: gather 53 + 2.67 a window row, scatter 297 + 6.0 a
# window row; 3062 at rg 512 / ws 224): what spmv_geometry weighs a
# taller group against a wider window by. The law is a SUM, of loops
# that ran one after the other; since PR 45 they overlap at heights up
# to 1024 and the chip is nearer their MAX (1544, 2839 and 2870 bundles
# where the law says 3062, 4357 and 5724), so the heights want
# re-reading, with the cells' `geometry` (the benchmark's): PERF.md
# section 7
SPMV_GATHER_ROW = 2.67
SPMV_SCATTER_ROW = 6.0
SPMV_BLK = 8       # chunks per grid step; a step is one group's
SPMV_UNROLL = 128  # most tiles of 8 window rows a turn of the gather
# loop: the whole loop up to rg 1024. A turn's tiles overlap in the
# schedule; a tile alone is a chain of gathers and selects. A gather
# of ONE turn is straight-line code in the chunk's body, and overlaps
# the scatter of the chunk before (spmv_overlap): 64 until PR 45,
# when rg 1024 (a shard of four of SCALE 26) ran two turns after its
# scatter, 5830 bundles a chunk where one turn under it is 2870
SPMV_SEG_STEPS = 4096   # grid steps a kernel call: its scalars (a
# group a step, a base a chunk: 144 KB) have to fit SMEM's 1 MB, which
# 278k chunks' bases at SCALE 24 do not (chipless compile, PR 38)
# A chunk's span over the uniform mean: room for the skew of a
# Kronecker graph's destinations (SPAN_ROOM x mean + the slack: 16
# rows, for the base's rounding down to a sublane, or a tenth of the
# mean where that is more). The widest span of a seed is 1.57 to 1.61
# x the mean at SCALE 24 (mean 128) and 1.59 x at a quarter of SCALE
# 26 (mean 256: 407 rows, PR 44's Step 0), so the slack is all the
# margin there is, and 16 rows of a taller window are less of it
SPMV_SPAN_ROOM = 1.6
SPMV_SPAN_SLACK = 16
SPMV_SPAN_SLACK_SHARE = 0.1
# What the kernel keeps in VMEM is the output table, 4 B a vertex (the
# ranks table stays in HBM and is read a group's window at a time), of
# the chip's 128 MiB; the rest of SPMV_VMEM_LIMIT is Mosaic's own
# temporaries. 100 MB is 26M vertices.
SPMV_VMEM_BUDGET = 100 * 1024 * 1024
SPMV_VMEM_LIMIT = 120 * 1024 * 1024
# Most scatter window rows: what VMEM leaves beside the table for the
# scatter's temporaries, a piece's (ws, 1024) row mask and its masked
# operand (12 KB a window row)
SPMV_WS_CAP = (SPMV_VMEM_LIMIT - SPMV_VMEM_BUDGET) // (12 * 1024) // 8 * 8
# A shard owns a range of destination rows and holds the edges that
# point into it. The ranges are cut where the edges are (whole tiles of
# 8 rows, every shard as near the mean load as a tile allows:
# :func:`balanced_bounds`), because a sweep ends when its fullest shard
# does: equal-width ranges of Graph500's permuted labels carry their
# share of the edges to a relative sqrt((n - 1) x 0.6352^scale) (the
# share of edges a vertex receives has the second moment ((A + C)^2 +
# (B + D)^2)^scale; 0.47% at SCALE 26 on 4), and three seeds of that
# form read sweeps 0.7% apart (PR 44's Step 0). What varies instead is
# a range's width, by the same law: a shard's table has room for the
# mean width and SPMV_SHARD_SIGMAS deviations. A shard's capacity in
# edges is the mean load and the tiles its two cuts may fall beside (a
# mean tile, and twice the heaviest vertex, which receives (A +
# C)^scale of the edges: 0.42% of a shard's at SCALE 25 on 4) and six
# deviations of a bucket's count. A graph more skewed than that
# overflows a shard and is refused by name, never trimmed.
SPMV_SHARD_SIGMAS = 6.0
SPMV_SHARD_SKEW = (0.57 + 0.19) ** 2 + (0.19 + 0.05) ** 2
SPMV_SHARD_HUB = 0.57 + 0.19


def _emit_vmem_rejection(n_vertices: int, rg: int, n_shards: int) -> None:
    """Record a VMEM-budget plan rejection AND its remedy: the shards
    whose destination ranges would fit (:func:`shards_needed`)."""
    from tpu_distalg.telemetry import events as tevents

    need = shards_needed(n_vertices)
    tevents.emit(
        "spmv_vmem_rejected", n_vertices=int(n_vertices), rg=int(rg),
        n_shards=int(n_shards), budget_bytes=SPMV_VMEM_BUDGET,
        shards_needed=need,
        remedy=f"a mesh of {need} data shards: a shard keeps its own "
               f"destination range's table in VMEM, 4 B a vertex of "
               f"the range")


def _table_rows(n_vertices: int) -> int:
    return ((n_vertices + LANES - 1) // LANES + 7) // 8 * 8


def shard_rows(n_vertices: int, n_shards: int) -> int:
    """Most rows of the vertex table a shard may own (the rows of its
    output table): the mean width of a range and ``SPMV_SHARD_SIGMAS``
    deviations of it, in whole sublane tiles; the whole table on one
    shard."""
    r8 = _table_rows(n_vertices)
    if n_shards == 1:
        return r8
    sigma = math.sqrt((n_shards - 1) * SPMV_SHARD_SKEW
                      ** math.log2(max(n_vertices, 2)))
    wide = r8 / n_shards * (1 + SPMV_SHARD_SIGMAS * sigma)
    return min(r8, (int(wide) // 8 + 2) * 8)


def balanced_bounds(xp, tile_edges, n_shards: int):
    """Where the destination ranges are cut: ``n_shards + 1`` rows, 0
    first and the table's last, shard ``k`` owning rows ``[b[k], b[k +
    1])``, from the edges that point into each tile of 8 rows
    (``tile_edges``, all shards' together). A cut is the tile edge
    with the nearest count of edges behind it to a ``k / n_shards``
    share, so a shard's load is the mean to within the tiles its two
    cuts fall beside; where a run of empty tiles leaves the choice
    open, the edge of them nearest an equal width. ``xp`` is NumPy on
    the host, ``jax.numpy`` under jit."""
    n_tiles = tile_edges.shape[0]
    behind = xp.concatenate([xp.zeros((1,), tile_edges.dtype),
                             xp.cumsum(tile_edges)])
    k = xp.arange(n_shards, dtype=tile_edges.dtype)
    share = (behind[-1] // n_shards) * k
    before = xp.searchsorted(behind, share, side="right") - 1
    past = xp.minimum(before + 1, n_tiles)
    near = behind[xp.where(
        share - behind[before] <= behind[past] - share, before, past)]
    cuts = xp.clip(k * n_tiles // n_shards,
                   xp.searchsorted(behind, near, side="left"),
                   xp.searchsorted(behind, near, side="right") - 1)
    last = xp.full((1,), n_tiles, cuts.dtype)
    return (8 * xp.concatenate([cuts, last])).astype(xp.int32)


def shards_needed(n_vertices: int) -> int:
    """The fewest data shards (a power of two) whose destination range
    fits the fused sweep's VMEM budget."""
    n = 1
    while spmv_resident_bytes(n_vertices, SPMV_RGS[0], 8,
                              n_shards=n) > SPMV_VMEM_BUDGET:
        n *= 2
    return n


def spmv_resident_bytes(n_vertices: int, rg: int, ws: int,
                        blk: int = SPMV_BLK, n_shards: int = 1) -> int:
    """Kernel-resident VMEM bytes of an SpMV geometry: the shard's
    output table (rows + ws, 128) f32, and double-buffered by the grid
    pipeline a group's window of the ranks table (rg, 128) f32 and the
    5 edge-block operands (blk * 8, 128) a grid step."""
    table = (shard_rows(n_vertices, n_shards) + ws) * LANES * 4
    return table + 2 * (rg + 5 * blk * 8) * LANES * 4


@dataclasses.dataclass(frozen=True)
class SpMVGeometry:
    """Every static shape of a fused-SpMV plan, from the sizes alone
    (vertices, edges held, shards): no seed and no edge moves it, so
    one executable serves every graph of a size. A shard owns the
    destinations of ``rows_out`` table rows and plans the edges that
    point into them; the gather groups run over the whole table."""

    rg: int          # rows of a gather group (a multiple of 8)
    n_groups: int    # the ranks table is (n_groups * rg, 128)
    ws: int          # scatter window rows (a multiple of 8)
    r8: int          # rows of the vertex table
    blk: int
    chunk: int
    seg_steps: int   # grid steps a kernel call
    n_steps: int     # grid steps a shard, whole segments
    n_shards: int
    rows_out: int    # rows of a shard's output table (r8 on one shard)
    bucket: int      # most edges one shard draws for another's range

    @property
    def step_slots(self) -> int:
        return self.blk * self.chunk

    @property
    def n_chunks(self) -> int:
        return self.n_steps * self.n_shards * self.blk

    @property
    def n_slots(self) -> int:
        return self.n_chunks * self.chunk

    @property
    def shard_slots(self) -> int:
        return self.n_steps * self.step_slots

    @property
    def shard_cap(self) -> int:
        """Most edges a shard holds (a bucket from every shard); they
        lie among its first ``shard_cap`` slots."""
        return self.bucket * self.n_shards

    @property
    def ranks_form(self) -> str:
        return "windowed" if self.n_groups > 1 else "resident"

    @property
    def ranks_out_form(self) -> str:
        """``'range'``: a shard writes its own destination range and
        the ranges are gathered; ``'whole'`` on one shard."""
        return "range" if self.n_shards > 1 else "whole"


def spmv_geometry(n_vertices: int, n_edges: int, n_shards: int = 1,
                  rg: int | None = None, blk: int = SPMV_BLK,
                  chunk: int = DEF_CHUNK) -> SpMVGeometry | None:
    """The plan's geometry for a graph of at most ``n_edges`` edges
    sharded by destination range over ``n_shards``, or ``None`` past
    the VMEM budget. A chunk of a shard spans ``rows a shard * n_groups
    * chunk / edges a shard`` rows where destinations are uniform (the
    span law: a range of a larger graph is a sparser block), and its
    window is ``SPMV_SPAN_ROOM`` x that and the slack. ``rg`` is the height of
    ``SPMV_RGS`` whose chunk costs the fewest bundles by the schedule
    law (``SPMV_GATHER_ROW`` a group row + ``SPMV_SCATTER_ROW`` a
    window row) among those whose window fits ``SPMV_WS_CAP``, the
    tallest where none does, each raised until the table's last group
    is nearly full. The ranges are cut to equal loads
    (:func:`balanced_bounds`), so a shard's capacity is the mean load,
    room for the heaviest tile beside a cut and six deviations of a
    bucket's count (all the edges on one shard), and its table has
    :func:`shard_rows` rows. Slots a shard: its capacity, a grid step
    of padding a group, rounded up to whole segments."""
    r8 = _table_rows(n_vertices)
    rows_out = shard_rows(n_vertices, n_shards)
    owners = min(n_shards, r8 // 8)    # shards a tile of rows can go to
    n_edges = max(n_edges, 1)

    def groups(rg_cap):
        # tiles of 8 rows a group: the first height from the cap up
        # that leaves the last group at least 0.85 full (a skinny last
        # group spreads its few edges over every destination row)
        tiles, cap = r8 // 8, max(rg_cap // 8, 1)
        if tiles <= cap:
            return r8, 1

        def fill(k):
            return (tiles - (-(-tiles // k) - 1) * k) / k

        heights = range(cap, 2 * cap)
        k = next((k for k in heights if fill(k) >= 0.85),
                 max(heights, key=fill))
        return 8 * k, -(-tiles // k)

    def window(n_groups):
        mean = r8 * chunk * n_groups / n_edges
        slack = max(SPMV_SPAN_SLACK, int(SPMV_SPAN_SLACK_SHARE * mean))
        return (int(SPMV_SPAN_ROOM * mean) + slack + 7) // 8 * 8

    def bundles(r):
        height, n_groups = groups(r)
        return (SPMV_GATHER_ROW * height
                + SPMV_SCATTER_ROW * window(n_groups))

    if rg is None:
        fits = [r for r in SPMV_RGS
                if window(groups(r)[1]) <= SPMV_WS_CAP]
        rg = min(fits, key=bundles) if fits else SPMV_RGS[-1]
    rg, n_groups = groups(rg)
    ws = min(window(n_groups), SPMV_WS_CAP)
    if spmv_resident_bytes(n_vertices, rg, ws, blk,
                           n_shards) > SPMV_VMEM_BUDGET:
        _emit_vmem_rejection(n_vertices, rg, n_shards)
        return None
    mean = n_edges / (n_shards * owners)       # a bucket's edges
    slack = 0.0 if n_shards == 1 else (
        2 * owners * SPMV_SHARD_HUB ** math.log2(max(n_vertices, 2))
        + 8 * LANES * owners / n_vertices
        + SPMV_SHARD_SIGMAS / math.sqrt(mean))
    bucket = -(-int(n_edges * (1 + slack)) // (n_shards * owners))
    steps = -(-bucket * n_shards // (blk * chunk)) + n_groups
    n_segs = -(-steps // SPMV_SEG_STEPS)
    seg_steps = -(-steps // n_segs)
    return SpMVGeometry(rg=rg, n_groups=n_groups, ws=ws, r8=r8, blk=blk,
                        chunk=chunk, seg_steps=seg_steps,
                        n_steps=n_segs * seg_steps, n_shards=n_shards,
                        rows_out=rows_out, bucket=bucket)


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
    r8 = _table_rows(n_vertices)
    return ScatterPlan(base=base, row=rows, lane=lanes, w=w,
                       chunk=chunk, blk=blk, n_chunks=rows.shape[0],
                       r8=r8, n_pad_edges=e_pad - e,
                       shard_len=shard_len, real_per_shard=tuple(real))


def scatter_window(pieces, row, lane, n_rows: int):
    """The one-hot scatter of a chunk's slots, on the MXU: the
    ``(n_rows, 128)`` float32 window in which slot ``(s, e)`` adds the
    sum of its ``pieces`` at row ``row[s, e]``, lane ``lane[s, e]`` (a
    slot whose row is outside the window adds nothing).

    ``pieces`` are ``(S, E)`` float32 arrays that bfloat16 holds exactly
    (:func:`split3` of the contributions: all 24 bits of each), ``row``
    and ``lane`` ``(S, E)`` int32. Per sublane ``s`` the slots' rows
    make a 0/1 mask under each piece and their lanes a 0/1 one-hot,
    which bfloat16 holds exactly too, so ONE bfloat16 pass a piece
    (``SCATTER_PASSES``) multiplies every piece by 0 or 1 without
    rounding and adds in float32: a window cell that one slot writes
    holds that slot's contribution bit for bit, one that several write
    their float32 sum. ``Precision.HIGHEST`` (the form until PR 39)
    splits BOTH operands in three and takes six passes, three of them
    against the one-hot's zero pieces."""
    n_sub, n_slots = row.shape
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (n_rows, n_slots), 0)
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (LANES, n_slots), 0)
    upd = jnp.zeros((n_rows, LANES), jnp.float32)
    for s in range(n_sub):                              # static unroll
        hit = jnp.broadcast_to(row[s:s + 1, :], row_iota.shape) == row_iota
        onehot_t = (jnp.broadcast_to(lane[s:s + 1, :], lane_iota.shape)
                    == lane_iota).astype(jnp.float32)
        for piece in pieces:
            m = jnp.where(hit, jnp.broadcast_to(piece[s:s + 1, :],
                                                row_iota.shape), 0.0)
            # DEFAULT by name: one pass whatever the process's
            # jax_default_matmul_precision (the words' low halves are 0)
            upd += jax.lax.dot_general(
                m, onehot_t, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
    return upd


def _kernel(base_ref, c_ref, row_ref, lane_ref, acc_ref, *,
            w: int, chunk: int, blk: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pid = pl.program_id(0)  # hoisted: not interpretable inside fori_loop

    def body(i, _):
        gi = pid * blk + i
        b = base_ref[gi]
        c = c_ref[pl.ds(i, 1), :]                       # (1, chunk)
        r = row_ref[pl.ds(i, 1), :]
        ln = lane_ref[pl.ds(i, 1), :]
        acc_ref[pl.ds(b, 8 * w), :] += scatter_window(
            split3(c), r - b, ln, 8 * w)
        return 0

    jax.lax.fori_loop(0, blk, body, 0)


@dataclasses.dataclass(frozen=True)
class SpMVPlan:
    """A fused-SpMV plan's arrays on the host (:func:`plan_spmv`; the
    program plans on the device, :func:`sort_slots` and
    :func:`slot_arrays`, and holds the same seven arrays there).

    Edges are sorted by (gather group, destination row), a group
    ``geom.rg`` rows of the ranks table, and every group is padded to
    whole grid steps: a step's chunks read ranks from ONE window
    (lane-direction ``dynamic_gather`` + sublane selects, no random
    access engine) and, the destinations sorted inside the group,
    each chunk writes a narrow scatter window (the one-hot-MXU scatter
    of :func:`scatter_table`, built per gather sublane). The per-slot
    arrays are (NCH * 8, 128) lane-major: the (8, 128) chunk layout
    the lane-gather needs. A slot with no edge has weight 0 and zero
    indices."""

    gbase: np.ndarray     # (NCH,) int32 gather window base row
    sbase: np.ndarray     # (NCH,) int32 scatter base row (8-mult), or -1
    src_lane: np.ndarray  # (NCH*8, 128) int32  src % 128
    src_row: np.ndarray   # (NCH*8, 128) int32  src//128 - gbase
    dst_row: np.ndarray   # (NCH*8, 128) int32  dst//128 - sbase
    dst_lane: np.ndarray  # (NCH*8, 128) int32  dst % 128
    w_e: np.ndarray       # (NCH*8, 128) f32    inv_deg[src], 0 on pad
    geom: SpMVGeometry
    n_pad_edges: int
    bounds: np.ndarray    # (n_shards + 1,) int32: the ranges' cuts, rows

    rg = property(lambda self: self.geom.rg)
    ws = property(lambda self: self.geom.ws)
    r8 = property(lambda self: self.geom.r8)
    blk = property(lambda self: self.geom.blk)
    n_chunks = property(lambda self: self.geom.n_chunks)


def slot_arrays(xp, src, dst, w_e, geom: SpMVGeometry, row0=0):
    """One shard's plan arrays from its slots in their final order
    (``src`` -1 where a slot holds no edge), as ``xp`` (NumPy on the
    host, ``jax.numpy`` under jit) array code; ``row0`` is the first
    table row of the shard's destination range (``shard *
    geom.rows_out``), which its scatter rows are counted from. Returns
    the seven arrays of :class:`SpMVPlan` in its order and the widest
    scatter span of a chunk, which has to fit ``geom.ws``."""
    n_chunks = src.shape[0] // geom.chunk
    shape8 = (n_chunks * 8, LANES)
    real = (src >= 0).reshape(n_chunks, geom.chunk)
    srow = (src >> 7).reshape(real.shape)
    drow = ((dst >> 7) - row0).reshape(real.shape)
    # a step's first slot holds an edge unless the whole step is tail
    first = src[::geom.step_slots]
    group = xp.where(first >= 0, (first >> 7) // geom.rg,
                     geom.n_groups - 1)
    gbase = xp.repeat(group * geom.rg, geom.blk).astype(xp.int32)
    low = xp.where(real, drow, geom.rows_out).min(axis=1)
    live = low < geom.rows_out
    sbase = xp.where(live, low // 8 * 8, -1).astype(xp.int32)
    span = xp.where(real, drow, -1).max(axis=1) - sbase + 1
    zero = xp.zeros((), xp.int32)

    def held(x):
        return xp.where(real, x, zero).astype(xp.int32).reshape(shape8)

    return ((gbase, sbase,
             held((src & 127).reshape(real.shape)),
             held(srow - gbase[:, None]), held(drow - sbase[:, None]),
             held((dst & 127).reshape(real.shape)),
             xp.where(real.reshape(shape8), w_e.reshape(shape8),
                      xp.zeros((), xp.float32))),
            xp.where(live, span, 0).max())


def plan_spmv(src: np.ndarray, dst: np.ndarray, w_e: np.ndarray,
              n_vertices: int, n_shards: int = 1, chunk: int = DEF_CHUNK,
              blk: int = SPMV_BLK, rg: int | None = None
              ) -> SpMVPlan | None:
    """The plan on the host in NumPy, for per-edge weights of any kind:
    the tests' way to the kernel (the program plans on the device). A
    shard plans the edges whose destination lies in its range (the
    ranges cut to equal loads, :func:`balanced_bounds`), its arrays
    one after another's. ``None`` where a chunk's destinations span
    more than the geometry's ``ws`` rows, a range holds more edges
    than a shard's capacity or more rows than its table, or the
    output table passes ``SPMV_VMEM_BUDGET``."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w_e = np.asarray(w_e)
    e = len(src)
    geom = spmv_geometry(n_vertices, e, n_shards, rg, blk, chunk) \
        if e else None
    if geom is None:
        return None
    bounds, owner, overflow = host_ranges(dst, geom)
    if overflow:
        return None
    parts, spans = [], []
    for k in range(n_shards):
        mine = owner == k
        arrays, span = _plan_shard(src[mine], dst[mine], w_e[mine], geom,
                                   int(bounds[k]))
        parts.append(arrays)
        spans.append(span)
    if max(spans) > geom.ws:
        return None
    return SpMVPlan(*(np.concatenate(a) for a in zip(*parts)), geom=geom,
                    n_pad_edges=geom.n_slots - e, bounds=bounds)


def host_ranges(dst: np.ndarray, geom: SpMVGeometry):
    """A host edge list's destination ranges: ``(bounds, the shard that
    owns each edge, overflow)``, the ranges cut to equal loads
    (:func:`balanced_bounds`); ``overflow`` counts the edges past a
    shard's capacity and the rows of a range past its table."""
    bounds = balanced_bounds(
        np, np.bincount(dst >> 10, minlength=geom.r8 // 8), geom.n_shards)
    owner = np.searchsorted(bounds, dst >> 7, side="right") - 1
    held = np.bincount(owner, minlength=geom.n_shards)
    return bounds, owner, int(
        np.maximum(held - geom.shard_cap, 0).sum()
        + np.maximum(np.diff(bounds) - geom.rows_out, 0).sum())


def _plan_shard(src, dst, w_e, geom: SpMVGeometry, row0: int):
    """:func:`plan_spmv` of one shard's edges."""
    e = len(src)
    group = (src // LANES) // geom.rg
    order = np.lexsort((dst // LANES, group))
    n_g = np.bincount(group, minlength=geom.n_groups)
    p_g = -(-n_g // geom.step_slots) * geom.step_slots
    shift = np.cumsum(p_g) - p_g - (np.cumsum(n_g) - n_g)
    at = shift[group[order]] + np.arange(e)

    def slots(x, fill, dtype):
        out = np.full(geom.shard_slots, fill, dtype)
        out[at] = x[order]
        return out

    return slot_arrays(
        np, slots(src, -1, np.int32), slots(dst, 0, np.int32),
        slots(w_e, 0, np.float32), geom, row0)


def slot_keys(src, dst, *, geom: SpMVGeometry, n_in: int, row0=0):
    """One int32 sort key a slot of a shard, ``group * (rows_out + 1)
    + row`` (:func:`sort_slots` sorts by it): the edges by (source
    group, destination row of the shard's range); as many spare slots
    after each group as pad it to whole grid steps (row ``rows_out``:
    past its last edge); everything else behind the last group."""
    if (geom.n_groups + 1) * (geom.rows_out + 1) >= 2 ** 31:
        raise ValueError("the plan's sort key does not fit int32")
    stride = geom.rows_out + 1
    real = src[:n_in] >= 0
    group = jnp.where(real, (src[:n_in] >> 7) // geom.rg, geom.n_groups)
    n_g = jnp.sum(group[None, :]
                  == jnp.arange(geom.n_groups, dtype=jnp.int32)[:, None],
                  axis=1, dtype=jnp.int32)
    spare = jnp.searchsorted(
        jnp.cumsum((-n_g) % geom.step_slots),
        jnp.arange(geom.shard_slots - n_in, dtype=jnp.int32),
        side="right")
    return jnp.concatenate([
        group * stride + jnp.where(real, (dst[:n_in] >> 7) - row0, 0),
        spare.astype(jnp.int32) * stride + geom.rows_out])


def sort_slots(src, dst, *, geom: SpMVGeometry, n_in: int, row0=0):
    """The device's half of a shard's plan before the layout: ``src``
    and ``dst`` int32 of ``geom.shard_slots``, ``src`` -1 wherever a
    slot holds no edge: a duplicate among the first ``n_in``, and
    every spare slot past them; ``row0`` as :func:`slot_arrays` takes
    it. One sort by :func:`slot_keys` and every slot is where the
    kernel reads it: nothing is gathered into place. jit it with
    ``geom`` and ``n_in`` static."""
    key = slot_keys(src, dst, geom=geom, n_in=n_in, row0=row0)
    _, src, dst = jax.lax.sort((key, src, dst), num_keys=1,
                               is_stable=False)
    return src, dst


def spmv_overlap(rg: int) -> str:
    """How the fused kernel's chunk loop runs at a group height, the
    spans' ``spmv_overlap`` tag: ``'step'`` where the gather loop is
    one turn (at most ``SPMV_UNROLL`` tiles of 8 window rows: every
    height up to 1024), so a chunk's gather and the chunk before's
    scatter are one straight-line body that the scheduler overlaps;
    ``'none'`` at a taller group, whose gather is a rolled loop of
    several turns and a block of its own, after the scatter."""
    return "step" if rg // 8 <= SPMV_UNROLL else "none"


def overlap_fields(rg: int, blk: int, shard_chunks: int,
                   seg_steps: int) -> dict:
    """The spans' two tags of the pipelined chunk loop:
    ``spmv_overlap`` (:func:`spmv_overlap`) and
    ``overlapped_chunk_share``, the chunks of a shard's sweep whose
    gather runs in one body with a scatter, of all its chunks: all but
    the first of each kernel call (``seg_steps`` grid steps, 0 for one
    call; nothing is held across calls), or none."""
    form = spmv_overlap(rg)
    calls = shard_chunks // blk // seg_steps if seg_steps else 1
    share = 1.0 - calls / shard_chunks if form == "step" else 0.0
    return dict(spmv_overlap=form, overlapped_chunk_share=round(share, 6))


def _spmv_kernel(seg_ref, grp_ref, sbase_ref, win_ref, slane_ref,
                 srow_ref, drow_ref, dlane_ref, we_ref, acc_in, acc_out,
                 acc, held_c, held_row, held_lane, held_base, sem, *,
                 rg: int, ws: int, blk: int, unroll: int):
    """One grid step is ``blk`` chunks of one source group, and the
    chunk loop is pipelined by one chunk: a turn scatters the chunk
    BEFORE (its contributions, destination rows and lanes and its base
    row, held in ``held_*``) and gathers its own, which it then holds.
    The two halves share no value and no unit: the gather over the
    group's window of the ranks table is lane gathers and selects (a
    tile of 8 rows: broadcast row rho, lane-gather by ``src_lane``,
    keep where ``src_row == rho``), the one-hot scatter built per
    gather sublane is MXU streaming (:func:`scatter_window`: 8 sublanes
    x 3 exact pieces, a single bf16 pass each, the price of bridging
    the (8, 128) gather layout to the scatter). Where the gather is
    one turn (:func:`spmv_overlap`) the body is one basic block, with
    no branch between the halves, and the scheduler issues one under
    the other. Chunks still add into ``acc`` in chunk order, each the
    same float32 sum of the same 24 products.

    ``win_ref`` is the group's ``(rg, 128)`` window of the ranks table,
    which stays in HBM: its block index is the step's group
    (``grp_ref``), so the pipeline copies a window in when the group
    changes and not otherwise. ``acc`` is the whole output table in
    VMEM, copied in from ``acc_in`` at the first step and out to
    ``acc_out`` at the last: a sweep is several calls (segments) that
    hand the table on, each with its own slice of the scalars, so a
    call starts with nothing held (zeros, which add an exact zero) and
    its last step scatters what is held before the table leaves. A
    chunk with no edge has ``sbase`` -1, weights 0 and indices 0: it
    runs the body and adds an exact zero at row 0; a step with no edge
    at all (the plan's tail) is skipped whole."""
    del seg_ref, grp_ref                      # the index maps read them
    pid = pl.program_id(0)

    def copy(src, dst):
        dma = pltpu.make_async_copy(src, dst, sem.at[0])
        dma.start()
        dma.wait()

    @pl.when(pid == 0)
    def _load():
        copy(acc_in, acc)
        held_c[...] = jnp.zeros_like(held_c)
        held_row[...] = jnp.zeros_like(held_row)
        held_lane[...] = jnp.zeros_like(held_lane)
        held_base[0] = 0

    def scatter_held():
        upd = scatter_window(split3(held_c[...]), held_row[...],
                             held_lane[...], ws)
        rows = pl.ds(pl.multiple_of(held_base[0], 8), ws)
        acc[rows, :] += upd

    def chunk(i, _):
        scatter_held()
        at = pl.ds(pl.multiple_of(8 * i, 8), 8)
        slane = slane_ref[at, :]
        srow = srow_ref[at, :]
        # a slot's row inside a tile of 8, bit by bit: the three
        # levels of the select tree below (seven selects three deep,
        # where a chain over the eight rows is eight deep and the
        # loop's critical path)
        bits = [(srow & (1 << b)) != 0 for b in range(3)]
        tile_of = srow >> 3

        def gather_tiles(turn, g):
            for u in range(unroll):
                t = turn * unroll + u
                tile = win_ref[pl.ds(pl.multiple_of(8 * t, 8), 8), :]
                picked = [jnp.take_along_axis(
                    jnp.broadcast_to(tile[r:r + 1, :], (8, LANES)),
                    slane, axis=1) for r in range(8)]
                for bit in bits:
                    picked = [jnp.where(bit, hi, lo) for lo, hi
                              in zip(picked[::2], picked[1::2])]
                g = jnp.where(tile_of == t, picked[0], g)
            return g

        g = jnp.zeros((8, LANES), jnp.float32)
        turns = rg // (8 * unroll)
        # one turn is straight-line code: a loop, even of one trip, is
        # a block of its own that nothing of the scatter is issued in
        g = gather_tiles(0, g) if turns == 1 else jax.lax.fori_loop(
            0, turns, gather_tiles, g)
        held_c[...] = g * we_ref[at, :]
        held_row[...] = drow_ref[at, :]
        held_lane[...] = dlane_ref[at, :]
        held_base[0] = jnp.maximum(sbase_ref[pid * blk + i], 0)
        return 0

    live = sbase_ref[pid * blk]
    for j in range(1, blk):
        live = jnp.maximum(live, sbase_ref[pid * blk + j])

    @pl.when(live >= 0)
    def _step():
        jax.lax.fori_loop(0, blk, chunk, 0)

    @pl.when(pid == pl.num_programs(0) - 1)
    def _store():
        scatter_held()
        copy(acc, acc_out)


@functools.partial(jax.jit,
                   static_argnames=("rg", "ws", "r8", "blk", "seg_steps",
                                    "unroll", "interpret"))
def spmv_table(gbase, sbase, ranks_table, src_lane, src_row, dst_row,
               dst_lane, w_e, *, rg: int, ws: int, r8: int,
               blk: int = SPMV_BLK, seg_steps: int | None = None,
               unroll: int = SPMV_UNROLL, interpret: bool = False):
    """Per-shard fused SpMV: contributions ``ranks[src] * w_e``
    scatter-added into a dense ``(r8 + ws, 128)`` vertex table, no XLA
    random-access op anywhere in the sweep.

    ``ranks_table`` is ``(n_groups * rg, 128)`` (:func:`spmv_geometry`:
    the vertex table padded with zero rows to whole groups); a grid
    step's chunks read one group's window of it. ``gbase`` holds each
    chunk's window base row (a multiple of ``rg``), ``sbase`` its
    scatter base row or -1 for a chunk with no edge. The sweep runs as
    ``n_steps / seg_steps`` calls of the kernel so that a call's
    scalars fit SMEM; the output table is handed from call to call in
    HBM and lives in VMEM inside one. ``r8`` is the rows of the table
    written: a shard's own destination range (``geom.rows_out``; the
    whole table on one shard), which ``dst_row`` and ``sbase`` count
    from. Callers slice the result ``[:r8]``."""
    nch = src_lane.shape[0] // 8
    if nch % blk:
        raise ValueError(f"n_chunks {nch} must be a multiple of {blk}")
    n_steps = nch // blk
    seg_steps = seg_steps or n_steps
    if n_steps % seg_steps:
        raise ValueError(f"{n_steps} grid steps are not whole segments "
                         f"of {seg_steps}")
    if ranks_table.shape[0] % rg or ranks_table.shape[1] != LANES:
        raise ValueError(
            f"ranks_table must be (n_groups * {rg}, {LANES}), got "
            f"{ranks_table.shape}")
    grp = gbase[::blk] // rg                       # a step's group
    edge_block = pl.BlockSpec(
        (blk * 8, LANES),
        lambda i, seg, grp, sb: (seg[0] * seg_steps + i, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    call = pl.pallas_call(
        functools.partial(
            _spmv_kernel, rg=rg, ws=ws, blk=blk,
            # the largest whole divisor of the window's tiles; the
            # interpreter gains nothing from a longer body
            unroll=1 if interpret else max(
                d for d in range(1, min(unroll, rg // 8) + 1)
                if (rg // 8) % d == 0)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(seg_steps,),
            in_specs=[pl.BlockSpec((rg, LANES),
                                   lambda i, seg, grp, sb: (grp[i], 0))]
            + [edge_block] * 5 + [hbm],
            out_specs=hbm,
            scratch_shapes=[pltpu.VMEM((r8 + ws, LANES), jnp.float32),
                            # the chunk the scatter lags by
                            pltpu.VMEM((8, LANES), jnp.float32),
                            pltpu.VMEM((8, LANES), jnp.int32),
                            pltpu.VMEM((8, LANES), jnp.int32),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.SemaphoreType.DMA((1,))],
        ),
        out_shape=jax.ShapeDtypeStruct((r8 + ws, LANES), jnp.float32),
        input_output_aliases={9: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=SPMV_VMEM_LIMIT),
        interpret=interpret,
        name="_spmv_kernel",
    )

    def segment(s, acc):
        seg = jnp.full((1,), s, jnp.int32)
        return call(
            seg, jax.lax.dynamic_slice(grp, (s * seg_steps,), (seg_steps,)),
            jax.lax.dynamic_slice(sbase, (s * seg_steps * blk,),
                                  (seg_steps * blk,)),
            ranks_table, src_lane, src_row, dst_row, dst_lane, w_e, acc)

    return jax.lax.fori_loop(
        0, n_steps // seg_steps, segment,
        jnp.zeros((r8 + ws, LANES), jnp.float32))


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
