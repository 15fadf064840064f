"""Sparse ALS: a Gramian and a solve per owner, from a ratings list.

The dense path (``models/als.py``, ``ops/linalg.py``) amortises one
``k x k`` Gram and one Cholesky over every row of a half-sweep, because
a dense ``R`` gives every row the same system. A ratings list does not:
owner ``u`` (a user in the user half, an item in the item half) has

    A_u = sum_{v in Omega_u} theta_v theta_v^T + lam * n_u * I
    b_u = sum_{v in Omega_u} r_uv theta_v

so a half-sweep is a gather of ``theta`` rows by index, one small
Gramian an owner, and one small solve an owner. This file holds the
three pieces (in XLA forms; the gather and the solve also as Mosaic
kernels, ``ops/pallas_als.py``) and the pack that gives them static
shapes.

**The pack** (:func:`plan_side`, host, from the degrees alone). An
owner's ratings are cut into *segments* of ``seg_slots`` (32) slots.
Owners are grouped by how many segments they need, rounded up to the
next of ``classes`` (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48). A *block* is
always ``batch`` segments (``batch * seg_slots`` rating slots, one
static shape): in class ``K`` it holds ``batch / K`` owners of ``K``
segments each, so an owner's Gramian is ONE contraction ``K *
seg_slots`` deep on the MXU and nothing is added up afterwards. An owner
with more segments than the largest class is cut into *pieces* of
``piece_segs`` (64) segments; a block holds ``batch / piece_segs`` pieces, an
owner larger than a block is carried across blocks, and the pieces'
Gramians are added into that owner's row of a buffer (a scatter-add of a
few tens of thousands of tiles a half, where a segment at a time would
be millions). The skew pays for itself: half of a power-law set's
ratings sit in owners of thousands, which contract deep; the price is
the padding of the small (slots held / ratings, stated by the plan).

Factor rows live in *processing order* (class by class, shard-major), so
a solved batch is one contiguous slab of the table and the mesh's
all-gather returns the table as it is read; ``row_of_owner`` maps back.
Row ``zero_row`` (past every shard's rows) is zero and is what a padding
slot points at. Lane ``k`` of a gathered row takes the rating and lane
``k + 1`` the slot's validity before the product, so ``b_u``, ``sum
r^2`` and ``n_u`` come out of the same MXU pass as ``A_u`` (rows ``k``
and ``k + 1`` of the product) and the training error needs no second
gather.

**The gather** has two forms that hand the product the same block bit
for bit (a slot's row of the other side's table, the rating in lane
``k``, the validity in lane ``k + 1``), and :func:`gather_plan` picks
one from what the code can observe, with no flag:

``mosaic``  ``ops/pallas_als.py``: the table stays in HBM, the *resident
            range* of it is copied into VMEM once a call, a slot that
            points there is a dynamic-row vector load and a slot that
            does not a row DMA, and the kernel writes the rating's lane
            itself. What of a slot is the same all run long the loader
            makes once (:func:`gather_lists`: the slot's row of the
            resident range, beside the pack's index, which is its row
            of the table: the kernel computes neither; a chunk's cold
            slots listed; the ratings turned a group of slots down the
            sublanes: 6 bytes a slot held more than the pack's 8), and
            the validity's lane
            is the table's own, set once a half (:func:`gather_table`:
            a slot is valid where its row is not ``zero_row``). On a TPU,
            where a factor row is one vector of 128 lanes, a block's
            slots are whole vectors, the table is one shard's (on a
            mesh it is shard-major and the heavy rows are ``n_shards``
            ranges) and the range fits VMEM; and where what the loader
            makes for it fits the chip (``models/als._ratings_meta``).
``xla``     :func:`gather_rows`, ``other.at[idx].get(...)``: a DMA a
            512 B row, 9 to 13.6 ns whatever the block, and a ``where``
            for the two lanes that XLA fuses into it; everywhere else,
            and the kernel's reference in the tests.

The resident range (:func:`resident_row0`) is the table's tail from a
class boundary of the side that is read to ``table_rows``: the heavy
class, the owners without a rating and the zero rows at least, then the
classes before the heavy one, largest first, while the range stays under
``GATHER_VMEM_BYTES``. Rows are in class order, so the owners that most
slots point at are that tail, "hot" is one compare, and padding is hot by
construction. At the published shape the heavy class is 18 432 rows,
9.4 MB a side, and holds 74.4% (items) and 62.3% (users) of the stubs;
with the padding 73.6% of all slots held are resident
(:func:`resident_slots` counts them from the degrees alone). The budget
is 12 MiB because a resident row costs every call 2.9 ns to copy in and
a cold slot 4 ns more than a hot one (one v5e, PR 37's Step 0,
``scripts/step0_als_gather.py``): a block of 196 608 slots has to read
a row about once for its place to pay, which the heavy class's rows do
(six times) and the next classes' do not (a row of the four classes
before it is read by a block in three; with them resident, 31.5 / 40.9
MB, a block reads 7 and 4% slower than with the heavy class alone).

**The solve** (:func:`solve_batch`) is a right-looking Cholesky
factorisation of ``A_u + lam n_u I`` with the batch along the lanes, in
panels of 8 columns, the right-hand side carried as one more row, then
the backward substitution: every step an elementwise operation over the
batch's systems at once. The Gramians do not travel in that layout: a
batch is owner-major ``(batch, width, width)`` as :func:`block_gramians`'
product makes it, through a class's staging (whole rows of it), the
heavy class's accumulator (an owner a row) and the ``switch`` that picks
a step's class, and the error's sums read it as it lies; only the solve
wants a system a lane, and each form turns the batch where it is
cheapest for it. The two forms solve the same systems by the same steps
in float32 (a true square root and a true division), and
:func:`solve_plan` picks one from what the code can observe, with no
flag:

``mosaic``  ``pallas_als.solve_lanes``: a tile of 128 owners' rows is
            read out of the batch once, turned along the lanes in VMEM
            (a strided load and a transpose a column) and stays there
            from the ridge to the solved row (21 MB at rank 100); the
            batch is never copied in HBM (until PR 50 it was, 806 MB a
            batch each way at the rate of the memory: more than the
            solve it served) and the staged matrices of XLA's form
            never exist there. On a TPU, where a batch is whole tiles
            and a tile fits ``SOLVE_VMEM_BYTES`` (to rank 126: from 127
            an owner's row is two vectors); on a mesh every shard runs
            it on its own batches.
``xla``     :func:`cholesky_solve_lanes` on the batch turned by
            :func:`to_lanes` (a copy in HBM): each panel's update
            streams the stage's whole trailing matrix through HBM (286
            MB each way a first-stage panel at the published shape, at
            640 of the chip's 819 GB/s: 2.07 us a system on one v5e,
            where XLA's own ``cho_factor``, the matrix on the minor
            dimensions, took 13.9); everywhere else (the CPU, a batch
            of ``BATCH_UNIT``), and the kernel's reference in the tests.

Precision: the Gramians are ``Precision.HIGHEST`` products of float32
rows (six bfloat16 passes: every factor enters with its 24 bits),
float32 accumulation; the solve is float32 on the VPU. Nothing here is
imported before the sparse trainer's first call.
"""

from __future__ import annotations

import dataclasses

import numpy as np

LANES = 128
# the light classes at the published shape: steps of a half and a third,
# so that an owner is padded by a third at most (the powers of two alone
# pad by a half) and every size divides a batch of 192 x 2^n
CLASSES = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48)
BATCH_UNIT = 192            # the least batch those sizes and 64 divide
PANEL = 8                   # columns a Cholesky panel: a vector's
#                             sublanes in the Mosaic form, where a
#                             vector holds 8 rows of a column; in XLA's
#                             16 compiled for five minutes unrolled
# what the gather's resident range may take of VMEM: the heavy class of
# the published shape and not the class before it (the module docstring
# says where the number comes from)
GATHER_VMEM_BYTES = 12 << 20
# what a tile of the Mosaic solve may take of VMEM (128 owners' rows of
# the Gramians twice and the matrix it factors: 20.6 MB at rank 100, 26.9
# at rank 126, the widest that fits: from rank 127 an owner's row is two
# vectors wide and the rows alone 35.7 MB)
SOLVE_VMEM_BYTES = 40 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclasses.dataclass(frozen=True)
class SparseGeometry:
    """The static numbers of the pack and of a block's programs."""

    k: int                  # rank
    seg_slots: int = 32     # rating slots a segment
    piece_segs: int = 64    # segments a piece (the top class)
    batch: int = 6144       # owners a solve; segments a block
    # segments an owner of each light class is padded to, ascending
    classes: tuple[int, ...] = CLASSES

    def __post_init__(self):
        object.__setattr__(self, "classes", tuple(self.classes))
        sizes = (*self.classes, self.piece_segs)
        if self.piece_segs < 2 or list(sizes) != sorted(set(sizes)) \
                or any(self.batch % c for c in sizes):
            raise ValueError(
                f"piece_segs {self.piece_segs} and the classes "
                f"{self.classes} must ascend to it and each divide "
                f"batch {self.batch}")

    @property
    def width(self) -> int:
        """Lanes a factor row is held in: ``k`` columns, the rating's
        lane, the validity's lane, rounded up to whole vectors."""
        return _round_up(self.k + 2, LANES)

    @property
    def solve_n(self) -> int:
        return _round_up(self.k, PANEL)

    @property
    def block_slots(self) -> int:
        return self.batch * self.seg_slots

    @property
    def block_shape(self) -> tuple[int, int]:
        """How a block's slots are held: whole vectors of 128 lanes
        (a last dimension of 32 would be padded to 128 in HBM, four
        times the bytes), segment after segment."""
        n = self.block_slots
        return (n // LANES, LANES) if n % LANES == 0 else (1, n)


def geometry_for(k: int, n_users: int, n_items: int) -> SparseGeometry:
    """The geometry a loader takes where none is stated: the published
    shape's segments, classes and pieces, the batch scaled down with
    the larger side (a batch of 6144 owners a class is all padding at a
    thousand owners) in whole ``BATCH_UNIT``s."""
    want = -(-max(n_users, n_items) // (16 * BATCH_UNIT)) * BATCH_UNIT
    return SparseGeometry(k=k, batch=max(BATCH_UNIT, min(6144, want)))


@dataclasses.dataclass(frozen=True)
class SideStatic:
    """What of a side's plan the compiled half-sweep is built from."""

    n_shards: int
    rows_local: int                       # factor rows a shard
    n_blocks: int                         # blocks a shard
    # (class K, first block, superblocks, first row), one a light class
    light: tuple[tuple[int, int, int, int], ...]
    # (first block, blocks, first row, heavy rows) of the piece class
    heavy: tuple[int, int, int, int]

    @property
    def zero_row(self) -> int:
        return self.n_shards * self.rows_local

    @property
    def table_rows(self) -> int:
        return self.zero_row + 8


@dataclasses.dataclass
class SidePlan:
    """One side's pack: the static part and the host arrays that place
    every segment."""

    static: SideStatic
    degrees: np.ndarray        # int64 (owners,)
    row_of_owner: np.ndarray   # int32 (owners,) global factor row
    owner_of_row: np.ndarray   # int32 (table rows,), -1 where none
    seg_owner: np.ndarray      # int32 (shards * blocks, batch), -1 none
    seg_pos: np.ndarray        # int32 the same: segment within its owner
    piece_slot: np.ndarray     # int32 (shards * heavy blocks, pieces a
    #                            block): heavy row of the piece's owner,
    #                            ``heavy rows`` (a dump row) where none
    slots_held: int
    padding_share: float       # slots held / ratings


@dataclasses.dataclass(frozen=True)
class GatherPlan:
    """How one half gathers the other side's rows."""

    form: str               # 'mosaic' or 'xla'
    hot_row0: int           # first resident row; the table's rows where
    #                         none is resident
    resident_rows: int
    interpret: bool = False  # the kernel interpreted (tests, on the CPU)


@dataclasses.dataclass(frozen=True)
class SolvePlan:
    """How a half solves a batch of systems."""

    form: str               # 'mosaic' or 'xla'
    tile_systems: int       # systems held in VMEM at once; 0 in XLA's form
    interpret: bool = False  # the kernel interpreted (tests, on the CPU)


def solve_plan(geom: SparseGeometry, on_tpu: bool) -> SolvePlan:
    """The form of the per-owner solve from what the code can observe:
    the Mosaic kernel on a TPU where a batch is whole tiles of systems
    (a lane each, once turned) and a tile at this rank fits
    ``SOLVE_VMEM_BYTES``;
    XLA's :func:`cholesky_solve_lanes` elsewhere. The solve reads no
    table and no index, so every shard of a mesh runs the same form."""
    from tpu_distalg.ops import pallas_als

    tile = pallas_als.SOLVE_TILE
    if (on_tpu and geom.batch % tile == 0
            and pallas_als.solve_tile_bytes(geom.k) <= SOLVE_VMEM_BYTES):
        return SolvePlan("mosaic", tile)
    return SolvePlan("xla", 0)


def resident_row0(other: SideStatic, geom: SparseGeometry,
                  budget: int = GATHER_VMEM_BYTES) -> int | None:
    """First row of the resident range of one shard's table: the tail
    from a class boundary of ``other`` to ``table_rows`` (the heavy
    class, the owners without a rating and the zero rows at least, and
    as many of the classes before the heavy one, largest first, as
    ``budget`` bytes hold), or None where not even that fits."""
    starts = [row0 for _, _, n_super, row0 in other.light if n_super]
    fits = [r for r in (*starts, other.heavy[2])
            if r <= other.heavy[2]
            and (other.table_rows - r) * geom.width * 4 <= budget]
    return min(fits, default=None)


def gather_plan(other: SideStatic, geom: SparseGeometry,
                on_tpu: bool) -> GatherPlan:
    """The form of a half's gather from what the code can observe: the
    Mosaic kernel on a TPU where a factor row is one vector of 128
    lanes, a block's slots are whole vectors in whole chunks, the table
    is one shard's (on a mesh it is shard-major: ``n_shards`` heavy
    ranges, not one) and the resident range fits; XLA's gather
    elsewhere."""
    from tpu_distalg.ops import pallas_als

    row0 = resident_row0(other, geom)
    rows, lanes = geom.block_shape
    if (on_tpu and geom.width == LANES and lanes == LANES
            and pallas_als.chunk_rows(rows) and other.n_shards == 1
            and row0 is not None):
        return GatherPlan("mosaic", row0, other.table_rows - row0)
    return GatherPlan("xla", other.table_rows, 0)


def resident_slots(plan: SidePlan, other: SidePlan, hot_row0: int) -> int:
    """Slots of ``plan``'s pack that point at the other side's rows from
    ``hot_row0`` on, from the degrees alone: every rating of an owner of
    the other side that lives there, and every padding slot (the zero
    row is behind every owner's)."""
    hot = other.degrees[other.row_of_owner >= hot_row0].sum()
    return int(hot + plan.slots_held - plan.degrees.sum())


def plan_side(degrees, geom: SparseGeometry, n_shards: int = 1) -> SidePlan:
    """Place every owner of one side: its class, its shard, its factor
    row and its segments, from the degrees alone."""
    deg = np.asarray(degrees, np.int64)
    n = deg.shape[0]
    L, P, B, S = geom.seg_slots, geom.piece_segs, geom.batch, n_shards
    segs = -(-deg // L)
    per_piece_block = B // P

    # who goes where: class lists, dealt round-robin to the shards
    lists: list[list[np.ndarray]] = []
    lo = 0
    for K in geom.classes:
        own = np.flatnonzero((segs > lo) & (segs <= K))
        lists.append([own[s::S] for s in range(S)])
        lo = K
    heavy_all = np.flatnonzero(segs > lo)
    pieces = -(-segs // P)
    heavy_all = heavy_all[np.argsort(-pieces[heavy_all], kind="stable")]
    heavy_lists = [heavy_all[s::S] for s in range(S)]
    empty_all = np.flatnonzero(deg == 0)
    empty_lists = [empty_all[s::S] for s in range(S)]

    light, block0, row0 = [], 0, 0
    for K, per_shard in zip(geom.classes, lists):
        most = max(len(x) for x in per_shard)
        n_super = -(-most // B)
        light.append((K, block0, n_super, row0))
        block0 += n_super * K
        row0 += n_super * B
    heavy_rows = _round_up(max(len(x) for x in heavy_lists), B)
    heavy_pieces = max(int(pieces[x].sum()) for x in heavy_lists)
    heavy_blocks = -(-heavy_pieces // per_piece_block)
    heavy = (block0, heavy_blocks, row0, heavy_rows)
    block0 += heavy_blocks
    row0 += heavy_rows
    empty_row0 = row0
    row0 += max(len(x) for x in empty_lists)
    R = _round_up(max(row0, 8), 8)
    n_blocks = max(block0, 1)
    static = SideStatic(S, R, n_blocks, tuple(light), heavy)

    row_of_owner = np.full(n, -1, np.int32)
    owner_of_row = np.full(static.table_rows, -1, np.int32)
    seg_owner = np.full((S, n_blocks * B), -1, np.int32)
    seg_pos = np.zeros((S, n_blocks * B), np.int32)
    piece_slot = np.full((S, max(heavy_blocks, 1) * per_piece_block),
                         heavy_rows, np.int32)
    for s in range(S):
        for (K, b0, _, r0), per_shard in zip(light, lists):
            own = per_shard[s]
            row_of_owner[own] = s * R + r0 + np.arange(len(own))
            at = b0 * B + np.arange(len(own) * K)
            seg_owner[s, at] = np.repeat(own, K)
            seg_pos[s, at] = np.tile(np.arange(K), len(own))
        own = heavy_lists[s]
        row_of_owner[own] = s * R + heavy[2] + np.arange(len(own))
        pc = pieces[own]
        n_pc = int(pc.sum())
        piece_slot[s, :n_pc] = np.repeat(np.arange(len(own)), pc)
        at = heavy[0] * B + np.arange(n_pc * P)
        seg_owner[s, at] = np.repeat(own, pc * P)
        first = np.repeat(np.cumsum(pc) - pc, pc * P) * P
        seg_pos[s, at] = np.arange(n_pc * P) - first
        own = empty_lists[s]
        row_of_owner[own] = s * R + empty_row0 + np.arange(len(own))
    owner_of_row[row_of_owner] = np.arange(n, dtype=np.int32)
    held = S * n_blocks * B * L
    return SidePlan(
        static=static, degrees=deg, row_of_owner=row_of_owner,
        owner_of_row=owner_of_row,
        seg_owner=seg_owner.reshape(S * n_blocks, B),
        seg_pos=seg_pos.reshape(S * n_blocks, B),
        piece_slot=piece_slot.reshape(
            S * max(heavy_blocks, 1), per_piece_block),
        slots_held=held,
        padding_share=held / max(int(deg.sum()), 1))


def segment_stubs(plan: SidePlan, geom: SparseGeometry):
    """``(k0, n_valid)`` int32 ``(shards * blocks, batch)``: the place
    of each segment's first rating in the owner-ordered list of the
    side's ratings, and how many of its slots hold one."""
    deg = plan.degrees
    off = np.cumsum(deg) - deg
    own = plan.seg_owner
    at = np.where(own >= 0, own, 0)
    start = plan.seg_pos.astype(np.int64) * geom.seg_slots
    n_valid = np.clip(deg[at] - start, 0, geom.seg_slots)
    n_valid = np.where(own >= 0, n_valid, 0)
    k0 = np.where(n_valid > 0, off[at] + start, 0)
    return k0.astype(np.int32), n_valid.astype(np.int32)


def pack_coo(plan: SidePlan, geom: SparseGeometry, owners, others,
             ratings, other_row_of_owner, other_zero_row: int):
    """Host pack of an explicit ratings list for one side: ``idx`` int32
    and ``val`` float32 ``(shards * blocks, *geom.block_shape)``, a
    block's ``batch`` segments one after another. A pair listed twice is
    held twice."""
    owners = np.asarray(owners, np.int64)
    order = np.argsort(owners, kind="stable")
    got = np.bincount(owners, minlength=plan.degrees.shape[0])
    if not np.array_equal(got, plan.degrees):
        raise ValueError("the plan's degrees are not this list's")
    rows = np.asarray(other_row_of_owner)[np.asarray(others)[order]]
    vals = np.asarray(ratings, np.float32)[order]
    k0, n_valid = segment_stubs(plan, geom)
    lane = np.arange(geom.seg_slots)
    ok = lane < n_valid[..., None]
    at = np.where(ok, k0[..., None] + lane, 0)
    if rows.size == 0:
        rows = np.zeros(1, np.int32)
        vals = np.zeros(1, np.float32)
    shape = (k0.shape[0], *geom.block_shape)
    idx = np.where(ok, rows[at], other_zero_row).astype(np.int32)
    val = np.where(ok, vals[at], 0.0).astype(np.float32)
    return idx.reshape(shape), val.reshape(shape)


# ---------------------------------------------------------------- device


def gather_rows(other, idx_b):
    """``other[idx_b.reshape(-1)]``, XLA's gather: what the Mosaic
    kernel's rows are held to, bit for bit."""
    return other.at[idx_b.reshape(-1)].get(mode="promise_in_bounds")


def gather_lists(idx, val, gather: GatherPlan):
    """What of a side's packed blocks the Mosaic gather wants made once,
    on the device, beside the pack's own ``idx``: from ``idx`` int32 and
    ``val`` float32 ``(blocks, rows, 128)`` as the pack holds them,

    ``val_t``   float32, ``val`` turned tile by tile of 1024 slots: a
                tile's row ``j`` holds slot ``8 m + j`` at lane ``m``, a
                group of eight slots down the sublanes;
    ``row``     int32, a slot's row of the resident range, ``idx`` less
                ``hot_row0``, and the range's last row where that is
                negative, the slot *cold* (what pass 1 loads: a cold
                slot's row of the output is written again by pass 2,
                from row ``idx`` of the table);
    ``cold``    int32 ``(blocks, slots / 2)``: for every chunk (a grid
                step of the kernel) the positions in it of its cold
                slots, in slot order, filled to the chunk's end with
                the last one again (0 where none is cold), two 16-bit
                positions a word, the earlier one low;
    ``n_cold``  int32 ``(blocks, chunks)``: how many of them are slots.

    None of it changes through a run: ``idx`` is the loader's and the
    range is static. Six bytes a slot held beside the pack's eight: 3.65
    GB of 8.51 at the published shape, made in 0.47 s on one v5e."""
    import jax
    import jax.numpy as jnp

    from tpu_distalg.ops import pallas_als

    blocks, rows, lanes = idx.shape
    slots = pallas_als.chunk_rows(rows) * lanes
    hot_row0 = jnp.int32(gather.hot_row0)
    # (as unsigned a negative index lies past every row of the range)
    row = jnp.minimum((idx - hot_row0).astype(jnp.uint32),
                      jnp.uint32(gather.resident_rows - 1)).astype(jnp.int32)
    val_t = val.reshape(blocks, -1, lanes, pallas_als.SUBLANES) \
        .swapaxes(-1, -2).reshape(idx.shape)
    pos = jnp.arange(slots, dtype=jnp.int32)

    def lists(idx_b):
        cold = idx_b.reshape(-1, slots) < hot_row0
        order = jnp.sort(jnp.where(cold, pos, pos + slots), axis=-1)
        n = jnp.sum(cold, axis=-1, dtype=jnp.int32)[:, None]
        again = jnp.take_along_axis(order, jnp.maximum(n - 1, 0), axis=-1)
        order = jnp.where(pos < n, order, jnp.where(n > 0, again, 0))
        words = order[:, 0::2] | (order[:, 1::2] << 16)
        return words.reshape(-1), n[:, 0]

    cold, n_cold = jax.lax.map(lists, idx)
    return val_t, row, cold, n_cold


def gather_table(other, geom: SparseGeometry, zero_row: int,
                 gather: GatherPlan | None = None):
    """The other side's table as a half's gather reads it. The Mosaic
    form finds a slot's validity where it finds the slot's row: lane
    ``k + 1`` is 1.0 in every row but ``zero_row`` (a slot is valid
    where it does not point there, as XLA's form has it), made once a
    half. XLA's form reads the table as it is."""
    import jax
    import jax.numpy as jnp

    if gather is None or gather.form != "mosaic":
        return other
    row = jax.lax.broadcasted_iota(jnp.int32, (other.shape[0], 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, geom.width), 1)
    return jnp.where((lane == geom.k + 1) & (row != zero_row),
                     jnp.float32(1.0), other)


def block_gramians(other, idx_b, val_b, K: int, geom: SparseGeometry,
                   zero_row: int, gather: GatherPlan | None = None,
                   cold=None):
    """One block's ``batch / K`` extended Gramians as the product makes
    them, owner-major ``(batch / K, width, width)``: the gather of the
    other side's rows with the rating and the validity in lanes ``k``
    and ``k + 1``, one float32-accurate product ``K * seg_slots`` deep
    an owner. In the Mosaic form ``other`` is :func:`gather_table`'s,
    ``val_b`` is the block's ``val_t`` and ``cold`` its resident rows,
    cold list and counts (:func:`gather_lists`), and the kernel hands
    the block over whole; in XLA's form (no plan, or the plan's) a
    ``where`` writes the two lanes, which XLA fuses into its own
    gather."""
    import jax
    import jax.numpy as jnp

    from tpu_distalg.telemetry import names

    k, W = geom.k, geom.width
    if gather is not None and gather.form == "mosaic":
        from tpu_distalg.ops import pallas_als

        with jax.named_scope(names.ALS_GATHER):
            G = pallas_als.gather_rows_resident(
                other, idx_b, val_b, *cold, gather.hot_row0, k,
                interpret=gather.interpret)
    else:
        flat = idx_b.reshape(-1)
        with jax.named_scope(names.ALS_GATHER):
            G = gather_rows(other, idx_b)
        with jax.named_scope(names.ALS_GRAM):
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
            r = val_b.reshape(-1, 1)
            ok = (flat != zero_row).astype(jnp.float32)[:, None]
            G = jnp.where(lane == k, r, jnp.where(lane == k + 1, ok, G))
    with jax.named_scope(names.ALS_GRAM):
        G = G.reshape(geom.batch // K, K * geom.seg_slots, W)
        return jnp.einsum(
            "osd,ose->ode", G, G, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)


def to_lanes(Ap):
    """``(owners, width, width)`` to ``(width, width, owners)``: the
    layout XLA's form of the solve works in (the Mosaic kernel turns its
    tile itself, in VMEM)."""
    import jax.numpy as jnp

    return jnp.transpose(Ap, (1, 2, 0))


def cholesky_solve_lanes(M, rhs, panel: int):
    """Solve ``A x = rhs`` for a batch of symmetric positive definite
    systems held with the batch along the lanes: ``M`` is ``(n, n,
    batch)``, ``rhs`` ``(n, batch)``, ``n`` a multiple of ``panel``.
    Right-looking blocked Cholesky of the matrix with the right-hand
    side as one more row (which does the forward substitution), then the
    backward substitution, every step elementwise over the batch. The loops over panels are
    rolled: the factorisation in about four stages, between which the
    trailing matrix shrinks, a panel's update inside a stage taken over
    the stage's whole matrix with the finished rows zero (1.4 times the
    arithmetic of the form that shrinks every panel, a fortieth of its
    code: unrolled at every call site it compiled for twelve minutes).
    The matrix is held a column panel to a leading index, ``(panels,
    panel, n, batch)``: a panel taken by a dynamic slice of the columns
    of ``(n, n, batch)`` made XLA copy the whole batch into another
    layout and back every panel (a third of the solve's time)."""
    import jax.numpy as jnp
    from jax import lax

    n, _, batch = M.shape
    w = panel
    n_panels = n // w
    row = lax.broadcasted_iota(jnp.int32, (n, 1), 0)

    def diag_block(D):
        """Unblocked Cholesky of a diagonal block given as ``D[j, i]``
        (column, row): ``Lb[i][j]``."""
        Lb = [[None] * w for _ in range(w)]
        for j in range(w):
            s = D[j, j]
            for t in range(j):
                s = s - Lb[j][t] * Lb[j][t]
            Lb[j][j] = jnp.sqrt(s)
            for i in range(j + 1, w):
                s = D[j, i]
                for t in range(j):
                    s = s - Lb[i][t] * Lb[j][t]
                Lb[i][j] = s / Lb[j][j]
        return Lb

    def stage(Ms, count):
        """``count`` panels of the trailing matrix ``Ms`` ``(panels,
        w, m + w, batch)``: its first ``count`` panels of L and what is
        left of it."""
        m = Ms.shape[2] - w
        rows = lax.broadcasted_iota(jnp.int32, (m + w, 1), 0)

        def factor(p, carry):
            Ms, Ls = carry
            q = p * w
            pan = lax.dynamic_index_in_dim(Ms, p, 0, keepdims=False)
            Lb = diag_block(
                lax.dynamic_slice(pan, (0, q, 0), (w, w, batch)))
            xc = []
            for j in range(w):
                s = pan[j]
                for t in range(j):
                    s = s - xc[t] * Lb[j][t][None, :]
                # column j of L: nothing above its diagonal entry
                xc.append(jnp.where(rows >= q + j,
                                    s / Lb[j][j][None, :], 0.0))
            upd = None
            for t in range(w):
                term = xc[t][None, None, :, :] \
                    * xc[t][:m].reshape(m // w, w, 1, batch)
                upd = term if upd is None else upd + term
            Ls = lax.dynamic_update_index_in_dim(
                Ls, jnp.stack(xc, axis=0), p, 0)
            return Ms - upd, Ls

        Ms, Ls = lax.fori_loop(
            0, count, factor,
            (Ms, jnp.zeros((count, w, m + w, batch), Ms.dtype)))
        return Ms[count:, :, count * w:, :], Ls

    # the right-hand side rides along as one more row of the matrix
    # (under w - 1 rows of zeros): its row of L is L^-1 rhs, so the
    # forward substitution costs no loop of its own. The trailing matrix
    # shrinks between stages and not inside one: about four stages, each
    # one rolled loop
    extra = jnp.pad(rhs.reshape(n_panels, w, 1, batch),
                    ((0, 0), (0, 0), (0, w - 1), (0, 0)))
    Ms = jnp.concatenate([M.reshape(n_panels, w, n, batch), extra], axis=2)
    per = -(-n_panels // 4)
    parts, done = [], 0
    while done < n_panels:
        count = min(per, n_panels - done)
        Ms, Ls = stage(Ms, count)
        parts.append(jnp.pad(Ls, ((0, 0), (0, 0), (done * w, 0), (0, 0))))
        done += count
    Lm = jnp.concatenate(parts, axis=0)       # [panel, column, row, b]
    y = Lm[:, :, n, :].reshape(n, batch)
    Lm = Lm[:, :, :n, :]

    def backward(i, x):
        p = n_panels - 1 - i
        q = p * w
        pan = lax.dynamic_index_in_dim(Lm, p, 0, keepdims=False)
        Lb = lax.dynamic_slice(pan, (0, q, 0), (w, w, batch))
        v = lax.dynamic_slice(y, (q, 0), (w, batch))
        below = row >= q + w          # x is still zero elsewhere
        vp = [v[j] - jnp.sum(jnp.where(below, pan[j] * x, 0.0), axis=0)
              for j in range(w)]
        xp = [None] * w
        for j in reversed(range(w)):
            s = vp[j]
            for t in range(j + 1, w):
                s = s - Lb[j, t] * xp[t]
            xp[j] = s / Lb[j, j]
        return lax.dynamic_update_slice(x, jnp.stack(xp), (q, 0))

    return lax.fori_loop(0, n_panels, backward, jnp.zeros_like(rhs))


def solve_batch(Ap, lam: float, geom: SparseGeometry,
                solve: SolvePlan | None = None):
    """From a batch of extended Gramians as :func:`block_gramians` makes
    them, owner-major ``(batch, width, width)``: the new factor rows
    ``(batch, width)``, which owners have a rating, the squared training
    error of those that have, and the ratings counted. ``A_u + lam n_u
    I`` is solved exactly (Cholesky) in the plan's form (XLA's where
    none is given); an owner with no rating solves the identity and is
    flagged. Only XLA's solve wants the batch along the lanes and turns
    it (a copy of the batch in HBM); the kernel turns a tile in VMEM,
    and everything else reads the batch as it lies."""
    import jax
    import jax.numpy as jnp

    from tpu_distalg.telemetry import names

    k, W, w, n8 = geom.k, geom.width, PANEL, geom.solve_n
    with jax.named_scope(names.ALS_SOLVE):
        cnt = Ap[:, k + 1, k + 1]
        has = cnt > 0
        if solve is not None and solve.form == "mosaic":
            from tpu_distalg.ops import pallas_als

            x, b = (a[:k] for a in pallas_als.solve_lanes(
                Ap, k, float(lam), interpret=solve.interpret))
        else:
            lanes = to_lanes(Ap[:, :n8, :n8])
            b = Ap[:, :k, k].T                        # (k, batch)
            ridge = jnp.where(has, jnp.float32(lam) * cnt, 1.0)
            ri = jax.lax.broadcasted_iota(jnp.int32, (n8, n8, 1), 0)
            ci = jax.lax.broadcasted_iota(jnp.int32, (n8, n8, 1), 1)
            M = jnp.where((ri < k) & (ci < k), lanes, 0.0) \
                + jnp.where(ri == ci,
                            jnp.where(ri < k, ridge[None, None, :], 1.0),
                            0.0)
            x = cholesky_solve_lanes(
                M, jnp.pad(b, ((0, n8 - k), (0, 0))), w)[:k]  # (k, batch)
    with jax.named_scope(names.ALS_UPDATE):
        rows = x.T                                    # (batch, k)
        # x^T A x with the sum over A's rows: the batch is read as it
        # lies (summed over its minor dimension, or sliced for b_u
        # beside a lanes-major x, XLA copies all of it owners-last)
        Ax = jnp.sum(Ap[:, :k, :k] * rows[:, :, None], axis=1)
        err = Ap[:, k, k] - 2.0 * jnp.sum(x * b, axis=0) \
            + jnp.sum(rows * Ax, axis=1)
        sse = jnp.sum(jnp.where(has, err, 0.0))
        seen = jnp.sum(cnt.astype(jnp.int32))
        rows = jnp.pad(rows, ((0, 0), (0, W - k)))
    return rows, has, sse, seen


def half_sweep(idx, val, piece_slot, other, own, *, static: SideStatic,
               other_zero_row: int, geom: SparseGeometry, lam: float,
               axis: str, gather: GatherPlan | None = None,
               solve: SolvePlan | None = None, cold=()):
    """One shard's half of an iteration: every owner of this shard from
    the other side's table ``other`` (whole, constant through the half),
    written into the shard's rows of ``own``; the shards' rows gathered
    once at the end. In the Mosaic form of the gather ``val`` and
    ``cold`` are :func:`gather_lists`' four. Returns ``(table, sse,
    seen)``, the two sums over all shards. Runs inside ``shard_map``
    over ``axis``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from tpu_distalg.telemetry import names

    B, P, W, R = geom.batch, geom.piece_segs, geom.width, static.rows_local
    s = lax.axis_index(axis)
    with jax.named_scope(names.ALS_UPDATE):
        local = lax.dynamic_slice_in_dim(own, s * R, R, axis=0)
    with jax.named_scope(names.ALS_GATHER):
        other = gather_table(other, geom, other_zero_row, gather)
    sse = jnp.float32(0.0)
    seen = jnp.int32(0)

    def grams(block, K):
        idx_b, val_b, *cold_b = (
            lax.dynamic_index_in_dim(a, block, keepdims=False)
            for a in (idx, val, *cold))
        # (XLA's form is ``block_gramians``' own: named only where it
        # is not)
        how = {"gather": gather, "cold": cold_b} if cold else {}
        return block_gramians(other, idx_b, val_b, K, geom,
                              other_zero_row, **how)

    # (XLA's form is ``solve_batch``'s own: named only where it is not)
    how = {"solve": solve} if solve and solve.form == "mosaic" else {}

    def solve_into(carry, Ap, row):
        local, sse, seen = carry
        rows, has, e, c = solve_batch(Ap, lam, geom, **how)
        with jax.named_scope(names.ALS_UPDATE):
            old = lax.dynamic_slice_in_dim(local, row, B, axis=0)
            new = jnp.where(has[:, None], rows, old)
            local = lax.dynamic_update_slice_in_dim(local, new, row, 0)
        return local, sse + e, seen + c

    block0, n_heavy_blocks, heavy_row0, heavy_rows = static.heavy
    acc = None
    if heavy_rows:
        def piece_block(acc, i):
            got = grams(block0 + i, P)
            with jax.named_scope(names.ALS_GRAM):
                slot = lax.dynamic_index_in_dim(piece_slot, i,
                                                keepdims=False)
                return acc.at[slot].add(got), None

        acc, _ = lax.scan(
            piece_block, jnp.zeros((heavy_rows + 1, W, W), jnp.float32),
            jnp.arange(n_heavy_blocks))

    # every batch of owners that is solved, of whatever class, is one
    # step of ONE loop: a step's class picks how its Gramians are made,
    # and the solve that follows is compiled once (a loop and a solve a
    # class compiled for 190 s at eleven classes)
    branches, kind, local_steps, rows = [], [], [], []

    def light_branch(K, block0):
        def make(i):
            if K == 1:
                return grams(block0 + i, 1)

            def part(j, staging):
                got = grams(block0 + i * K + j, K)
                with jax.named_scope(names.ALS_GRAM):
                    return lax.dynamic_update_slice_in_dim(
                        staging, got, j * (B // K), 0)

            with jax.named_scope(names.ALS_GRAM):
                empty = jnp.zeros((B, W, W), jnp.float32)
            return lax.fori_loop(0, K, part, empty)

        return make

    for K, block0, n_super, row0 in static.light:
        if n_super:
            kind += [len(branches)] * n_super
            local_steps += list(range(n_super))
            rows += [row0 + i * B for i in range(n_super)]
            branches.append(light_branch(K, block0))
    if heavy_rows:
        n = heavy_rows // B
        kind += [len(branches)] * n
        local_steps += list(range(n))
        rows += [heavy_row0 + i * B for i in range(n)]
        branches.append(
            lambda i: lax.dynamic_slice_in_dim(acc, i * B, B, axis=0))

    if kind:
        def step(carry, at):
            which, i, row = at
            Ap = branches[0](i) if len(branches) == 1 else \
                lax.switch(which, branches, i)
            return solve_into(carry, Ap, row), None

        (local, sse, seen), _ = lax.scan(
            step, (local, sse, seen),
            tuple(jnp.asarray(np.asarray(a, np.int32))
                  for a in (kind, local_steps, rows)))

    with jax.named_scope(names.ALS_SYNC):
        table = lax.all_gather(local, axis, axis=0, tiled=True)
        sse = lax.psum(sse, axis)
        seen = lax.psum(seen, axis)
    with jax.named_scope(names.ALS_UPDATE):
        table = jnp.concatenate(
            [table, jnp.zeros((8, W), jnp.float32)], axis=0)
    return table, sse, seen


def heldout_rmse(X, Theta, hu, hv, hr, chunk: int = 1 << 16):
    """Root mean squared error of ``x_u . theta_v`` on pairs given as
    factor rows, float32 on the VPU, ``chunk`` pairs at a time (four
    million pairs' rows at once are 4 GB)."""
    import jax
    import jax.numpy as jnp

    n = hr.shape[0]
    pad = (-n) % chunk if n > chunk else 0
    step = chunk if n > chunk else n
    ok = jnp.pad(jnp.ones((n,), jnp.float32), (0, pad))
    parts = [jnp.pad(a, (0, pad)).reshape(-1, step) for a in (hu, hv, hr)]

    def some(args):
        u, v, r, ok = args
        xu = X.at[u].get(mode="promise_in_bounds")
        tv = Theta.at[v].get(mode="promise_in_bounds")
        d = (jnp.sum(xu * tv, axis=1) - r) * ok
        return jnp.sum(d * d)

    total = jnp.sum(jax.lax.map(some, (*parts, ok.reshape(-1, step))))
    return jnp.sqrt(total / n)
