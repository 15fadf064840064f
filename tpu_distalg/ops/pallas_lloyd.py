"""One Lloyd pass over points held feature-major, in row blocks.

Why a second k-means path. At a chip-filling shape (HiBench ``huge``:
100M points x 20 dimensions, k = 10, float32) the row layout of
``ops/kmeans.py`` holds 96 B a point and a 4 B mask (XLA lays
``f32[n, 20]`` out column-major in ``(8, 128)`` tiles, 24 sublanes for
20 columns: 10.0 GB with the mask, 11.0 GB with the pass's
intermediates). PERF.md §6 (PR 26, PR 29) has what each does on the chip.

Layout (``LanesGeometry``): ``f32[n_blocks, dim, R, 128]``. Point ``p``
of a shard sits in block ``p // (R * 128)``, sublane row ``(p // 128) %
R``, lane ``p % 128``; its ``dim`` features are ``dim`` separate
``(R, 128)`` tiles of that block. The last two dimensions are whole
tiles, so nothing is padded: 4 * dim bytes a point (80 B at dim 20, 8.0
GB at 100M), a block is one contiguous DMA, and a generator that draws
block after block (``parallel.build_sharded`` with ``pack=``) writes the
layout without a transpose of the whole table. Validity follows from
the id: padding points (ids past the valid count) join no cluster, and
hold any finite value. The table has to be finite, padding included:
the matmul of the sums multiplies every point by every cluster's 0 or
1, and 0 x NaN or 0 x infinity is NaN in all k sums of that feature
(``models/kmeans.build_scaled`` checks the table once, where it is
drawn, and raises).

Kernel (``lloyd_pass``): a block at a time, a group of 8 sublane rows
(1024 points, one register a feature) at a time.

  score_c = |c|^2 - 2 x . c      float32 on the VPU, k * dim multiply-
                                 adds a point: the argmin over c of
                                 |x - c|^2 without the |x|^2 every c
                                 shares. No product is rounded to
                                 bfloat16, as the MXU's default
                                 precision would (its exact mode costs
                                 six passes of a 20 x 10 matrix that
                                 fills 1% of the array). The centres'
                                 coordinates are read as registers from
                                 a table in VMEM that the first grid
                                 step broadcasts: a ``vstv`` from a
                                 scalar costs a vector slot each, a load
                                 does not (a tenth of the kernel at
                                 dim 20, k 10)
  assign  = first minimum        a strict ``<`` scan over c, the
                                 reference's ``closest_center``
  sums, counts                   on the MXU, exactly, from ``k * dim``
                                 144 up (``sums_on_mxu``: one v5e's
                                 threshold); else ``sums[c, d] +=
                                 where(assign == c, x_d, 0)`` on the
                                 VPU, counts int32 (float32 holds no
                                 odd count past 2**24)

The sums on the MXU. The mask of a cluster is 0 or 1, exact in bfloat16,
and a float32 is exactly the sum of three bfloat16 pieces (24
significand bits = 3 x 8: ``hi`` the top 16 bits of x, ``mid`` the top
16 of ``x - hi``, ``lo`` the rest; every step exact). So ``sum of the
cluster's x = mask . hi + mask . mid + mask . lo``: three bfloat16
passes in which every product is 1 x piece or 0 x piece and the
accumulation is float32. One matmul a group: the masks ``(16 * ceil(k /
2), 128)`` streamed against the planes ``(16 * n_plane_registers, 128)``
latched, contraction over the lanes. A bfloat16 register holds 16 rows,
two to a 32-bit word, so two planes (or two clusters' masks) of the
same 8 sublane rows share one: ``high | low >> 16`` and a bitcast, 2
vector operations where ``astype(bfloat16)`` of ``(8, 128)`` tiles costs
5. Entry ``((c, s), (plane, s'))`` of the product is wanted where ``s
== s'``; the whole tile is accumulated (float32, per block, then across
the grid), the last grid step keeps the diagonal and sums it over s,
a row a cluster, and ``fold_stats`` sums over s' and adds the three
pieces. A plane of ones beside the features gives the counts: exact
integers in float32 within a block (65 536 points), converted to int32
once a block.

What the compiler's schedule and the chip say (PERF.md §6, PR 29; one
v5e, dim 20, k 10): Mosaic spreads the product's four 128-column tiles
over the four MXUs; a register costs 8 cycles to latch, 16 to stream, a
result register 8 to pop, and the MXU does not accumulate across the
contraction, so the pieces sit side by side as columns. The matmul of a
group reads masks and planes that the step before left in VMEM: its
chain (latch, stream, 56 cycles, pop) then starts with the step and
runs under the next groups' scores. 635 vector operations a 1024 points
(970 before), 13.7 ms a pass over 100M points where the VPU form took
17.1 and the copies alone take 10.7; two groups a step, because the
chain of one step (64 + 80 a group + 136 cycles) is longer than the
vector work of one group (160), and no more, because the compiler's
scheduler does not interleave four groups' chains and scores (14.5 ms).
These cycle counts, and ``MXU_MIN_WORK``, are one v5e's. Past dim 20 the
planes are more than four column tiles and each MXU's chain takes them
in rounds; the MXU form still was not the slower one at (16, 24),
(9, 33), (5, 32) and (32, 32).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from tpu_distalg.ops.bf16_pieces import pieces as _pieces
from tpu_distalg.ops.pallas_api import pl, pltpu

LANES = 128
SUBLANES = 8
# the kernel unrolls k * dim multiply-adds, and holds as many (8, 128)
# registers of broadcast centre coordinates in VMEM (4 KB each)
MAX_UNROLL = 1024
BLOCK_BYTES = 6 << 20      # a block's share of VMEM; two are in flight
MXU_MIN_WORK = 144         # k * dim from which the sums take the MXU
_ONE_HI = 0x3F800000       # 1.0 as that half
_ONE_LO = 0x00003F80       # 1.0 as the other


@dataclasses.dataclass(frozen=True)
class LanesGeometry:
    dim: int
    block_rows: int        # R: sublane rows of 128 points a block

    @property
    def block_points(self) -> int:
        return self.block_rows * LANES

    def pack(self, rows):
        """``(block_points, dim)`` rows -> one ``(dim, R, 128)`` block."""
        return rows.T.reshape(self.dim, self.block_rows, LANES)

    def unpack(self, x4):
        """``(n_blocks, dim, R, 128)`` -> ``(n_blocks * R * 128, dim)``
        rows in id order (tests and small tables only: the result is a
        plain 2-D array, padded to 128 lanes on a TPU)."""
        return x4.transpose(0, 2, 3, 1).reshape(-1, self.dim)


def lanes_geometry(dim: int, k: int,
                   block_rows: int | None = None) -> LanesGeometry | None:
    """The layout for ``dim`` features and ``k`` centres, or ``None``
    where the kernel's unrolled form does not fit (the caller keeps the
    row layout and ``ops/kmeans.py`` then)."""
    if k * dim > MAX_UNROLL:
        return None
    if block_rows is None:
        block_rows = SUBLANES
        while (block_rows < 512
               and 2 * block_rows * dim * LANES * 4 <= BLOCK_BYTES):
            block_rows *= 2
    if block_rows % SUBLANES:
        raise ValueError(f"block_rows {block_rows} is not a multiple of "
                         f"{SUBLANES}")
    return LanesGeometry(dim, block_rows)


def _tile_shape(k: int, dim: int) -> tuple[int, int]:
    """The product tile: rows ``(cluster pair, s, half)``, columns
    ``(plane pair, s', half)``. Planes: the last feature's lo where
    ``dim`` is odd (else zeros) and ones, then mid and hi of each
    feature, then the other lo's two by two."""
    return 16 * ((k + 1) // 2), 16 * ((3 * dim + 2) // 2)


def sums_on_mxu(k: int, dim: int) -> bool:
    """Whether a pass adds up the per-cluster sums on the MXU (else on
    the VPU, as masked adds). The matmul takes ``2 * k * dim`` selects
    and adds a 1024 points off the vector slots and puts a serial chain
    (latch, stream, pop) on each MXU in their place. The threshold is
    one v5e's, read off 19 shapes (PERF.md §6, PR 29): from ``k * dim``
    144 up the MXU form was never the slower one (0.73 to 0.85 of the
    VPU form's time at dim 20 with k 8 to 50, 0.92 at (12, 12), 0.93
    at (32, 32), level elsewhere); below it never the faster one (1.05
    at (8, 16), 1.7 at (7, 5), 3.3 at (3, 2))."""
    return k * dim >= MXU_MIN_WORK


def sums_form(k: int, dim: int) -> str:
    """Where a pass adds up the per-cluster sums at this geometry: a
    tag for the spans of the scale path (``tda report`` prints it)."""
    return "mxu" if sums_on_mxu(k, dim) else "vpu"


def _pair(low, high):
    """Two planes as words whose low halves are zero -> one word a
    pair; as bfloat16, row 2s is ``low[s]`` and row 2s + 1 ``high[s]``."""
    return high | (low >> 16)


def _lloyd_kernel(nv_ref, cs_ref, x_ref, *refs, k: int, dim: int,
                  rows: int, groups: int, stats: bool, assign: bool,
                  mxu: bool, interpret: bool):
    """One block: ``rows`` sublane rows of 128 points, ``groups`` groups
    of 8 a step."""
    refs = list(refs)
    sums_ref = cnt_ref = asg_ref = None
    if stats:
        # mxu: a row a cluster of the product tile, and of its counts
        sums_ref, cnt_ref = refs.pop(0), refs.pop(0)
    if assign:
        asg_ref = refs.pop(0)
    tab_ref = refs.pop(0)
    tile_ref, cnt16_ref, acc_ref, a_ref, b_ref = \
        refs if mxu else (None,) * 5
    i = pl.program_id(0)
    unroll = not interpret

    @pl.when(i == 0)
    def _init():
        # (with a matmul the outputs are written whole by the last step)
        for ref in ((tile_ref, cnt16_ref, a_ref, b_ref) if mxu
                    else (sums_ref, cnt_ref) if stats else ()):
            ref[...] = jnp.zeros_like(ref)

        def fill(e, carry):
            tab_ref[e] = jnp.full((SUBLANES, LANES), cs_ref[e],
                                  jnp.float32)
            return carry

        jax.lax.fori_loop(0, k * dim + k, fill, 0)

    if mxu:
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n_valid = nv_ref[0]
    within = (jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
              * LANES
              + jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1))

    def score(r0):
        xs = [x_ref[d, pl.ds(r0, SUBLANES), :] for d in range(dim)]

        def nearer(c, carry):
            best, idx = carry
            # (lax, not operators: a fifth of the tracing time)
            s = jax.lax.mul(xs[0], tab_ref[c * dim])
            for d in range(1, dim):
                s = jax.lax.add(s, jax.lax.mul(xs[d], tab_ref[c * dim + d]))
            s = jax.lax.add(s, tab_ref[k * dim + c])
            better = s < best              # strict: the first minimum
            return jnp.where(better, s, best), jnp.where(better, c, idx)

        _, idx = jax.lax.fori_loop(
            0, k, nearer,
            (jnp.full((SUBLANES, LANES), jnp.inf, jnp.float32),
             jnp.zeros((SUBLANES, LANES), jnp.int32)), unroll=unroll)
        if assign:
            asg_ref[pl.ds(r0, SUBLANES), :] = idx
        if stats:
            pid = (i * rows + r0) * LANES + within
            idx = jnp.where(pid < n_valid, idx, k)   # padding: no cluster
        return idx

    def stage(g, r0, idx):
        """A group's masks and planes into the scratch that the next
        step's matmul reads."""
        # the clusters two to a register; with k odd the last one's
        # pair is cluster k, the padding's: its row is dropped
        pair = 2 * jax.lax.broadcasted_iota(
            jnp.int32, ((k + 1) // 2, SUBLANES, LANES), 0)
        a_ref[g] = jnp.where(
            idx == pair, jnp.uint32(_ONE_LO),
            jnp.where(idx == pair + 1, jnp.uint32(_ONE_HI),
                      jnp.uint32(0))).reshape(-1, LANES)
        # all the features one array operation: the compiler sees the
        # registers' operations all the same, the tracer a twentieth
        # of the equations (set-up: PERF.md §6, PR 29)
        hi, r, los = _pieces(x_ref[:, pl.ds(r0, SUBLANES), :])
        b_ref[g, pl.ds(SUBLANES, SUBLANES * dim), :] = (
            hi | (r >> 16)).reshape(-1, LANES)               # (mid, hi)
        ones = jnp.full((SUBLANES, LANES), _ONE_HI, jnp.uint32)
        b_ref[g, pl.ds(0, SUBLANES), :] = \
            _pair(los[dim - 1], ones) if dim % 2 else ones
        if dim > 1:
            pairs = los[:dim - dim % 2].reshape(-1, 2, SUBLANES, LANES)
            b_ref[g, pl.ds(SUBLANES * (1 + dim),
                           SUBLANES * (dim // 2)), :] = _pair(
                pairs[:, 0], pairs[:, 1]).reshape(-1, LANES)

    def add(r0s, idxs):
        """A step's points into the sums and counts on the VPU: the
        groups are added to each other first, one store an entry (the
        store slot is one a cycle)."""
        xs = [[x_ref[d, pl.ds(r0, SUBLANES), :] for d in range(dim)]
              for r0 in r0s]

        def cluster(c, carry):
            ms = [idx == c for idx in idxs]
            cnt_ref[c] += functools.reduce(
                jax.lax.add, [m.astype(jnp.int32) for m in ms])
            for d in range(dim):
                sums_ref[c, d] += functools.reduce(
                    jax.lax.add, [jnp.where(m, x[d], 0.0)
                                  for m, x in zip(ms, xs)])
            return carry

        jax.lax.fori_loop(0, k, cluster, 0, unroll=unroll)

    def drain():
        """The matmuls of the groups staged a step ago."""
        for g in range(groups):
            masks = pltpu.bitcast(a_ref[g], jnp.bfloat16)
            planes = pltpu.bitcast(b_ref[g], jnp.bfloat16)
            acc_ref[...] += jax.lax.dot_general(
                masks, planes, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

    def step(j, carry):
        if mxu:
            drain()
        r0s, idxs = [], []
        for g in range(groups):
            r0s.append(
                pl.multiple_of((j * groups + g) * SUBLANES, SUBLANES))
            idxs.append(score(r0s[g]))
            if mxu:
                stage(g, r0s[g], idxs[g])
        if stats and not mxu:
            add(r0s, idxs)
        return carry

    jax.lax.fori_loop(0, rows // (groups * SUBLANES), step, 0)

    if mxu:
        last = i == pl.num_programs(0) - 1
        pl.when(last)(drain)       # the last groups are still staged
        tile_ref[...] += acc_ref[...]
        # the plane of ones sits in the first 16 columns; a block's
        # counts are exact in float32
        cnt16_ref[...] += acc_ref[:, :16].astype(jnp.int32)

        @pl.when(last)
        def _fold_rows():
            """Rows ``(cluster pair, s, half)`` -> a row a cluster: of
            the tile the entries with ``s == s'``, summed over s; of
            the counts all (every s' holds the same)."""
            for src, dst in ((tile_ref, sums_ref), (cnt16_ref, cnt_ref)):
                row, col = (jax.lax.broadcasted_iota(
                    jnp.int32, src.shape, axis) for axis in (0, 1))
                kept = src[...]
                if src is tile_ref:
                    kept = jnp.where((row >> 1) & 7 == (col >> 1) & 7,
                                     kept, 0.0)
                for h in range(2):
                    pairs = jnp.where(row & 1 == h, kept, 0).reshape(
                        -1, 16, src.shape[1]).sum(axis=1)
                    for j in range(pairs.shape[0]):
                        dst[pl.ds(2 * j + h, 1), :] = pairs[j:j + 1]


@functools.partial(
    jax.jit, static_argnames=("stats", "assign", "interpret"))
def lloyd_pass(x4, centers, n_valid, *, stats: bool = True,
               assign: bool = False, interpret: bool = False):
    """One pass over one shard's points under ``centers``.

    ``x4`` ``f32[n_blocks, dim, R, 128]``; ``centers`` ``f32[k, dim]``;
    ``n_valid`` int32 scalar, the count of this shard's points that are
    valid (ids below it). Returns, in this order and as asked for:
    ``stats`` the partial sums and counts over the valid points, a
    pair whose form follows ``sums_on_mxu(k, dim)`` and that
    ``fold_stats`` makes ``(k, dim)`` float32 and ``(k,)`` int32;
    ``assign`` the nearest centre of every point, padding included,
    ``int32[n_blocks * R, 128]`` in id order.

    A step of the kernel's inner loop takes two groups of 8 sublane
    rows where the block has them (one: 18.5 ms a pass over 100M x 20
    points at k = 10 on a v5e, two: 13.7 as shipped, four: 14.5; my
    chip runs, PR 29); interpreted, the loops over centres stay rolled
    (XLA:CPU compiles the unrolled body for half a minute)."""
    nb, dim, rows, lanes = x4.shape
    k = centers.shape[0]
    if lanes != LANES or rows % SUBLANES:
        raise ValueError(f"lloyd_pass: blocks {x4.shape[1:]} need 128 "
                         f"lanes and rows in multiples of {SUBLANES}")
    groups = 1 if rows % (2 * SUBLANES) else 2
    mxu = stats and sums_on_mxu(k, dim)
    c32 = centers.astype(jnp.float32)
    scalars = jnp.concatenate(
        [(-2.0 * c32).reshape(-1), jnp.sum(c32 * c32, axis=1)])
    nv = jnp.asarray(n_valid, jnp.int32).reshape(1)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_specs, out_shape = [], []

    def held(shape, dtype):
        out_specs.append(pl.BlockSpec(shape, lambda b: (0,) * len(shape)))
        out_shape.append(jax.ShapeDtypeStruct(shape, dtype))

    scratch = [pltpu.VMEM((k * dim + k, SUBLANES, LANES), jnp.float32)]
    if mxu:
        tile = _tile_shape(k, dim)
        held((tile[0] // 8, tile[1]), jnp.float32)
        held((tile[0] // 8, 16), jnp.int32)
        scratch += [
            pltpu.VMEM(tile, jnp.float32),
            pltpu.VMEM((tile[0], 16), jnp.int32),
            pltpu.VMEM(tile, jnp.float32),
            pltpu.VMEM((groups, tile[0] // 2, LANES), jnp.uint32),
            pltpu.VMEM((groups, tile[1] // 2, LANES), jnp.uint32)]
    elif stats:
        held((k, dim, SUBLANES, LANES), jnp.float32)
        held((k, SUBLANES, LANES), jnp.int32)
    if assign:
        out_specs.append(pl.BlockSpec((rows, LANES), lambda b: (b, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((nb * rows, LANES), jnp.int32))
    block = dim * rows * LANES * 4
    resident = 3 * k * (dim + 1) * SUBLANES * LANES * 4
    kernel = functools.partial(
        _lloyd_kernel, k=k, dim=dim, rows=rows, groups=groups, stats=stats,
        assign=assign, mxu=mxu, interpret=interpret)
    out = pl.pallas_call(
        kernel,
        name="_lloyd_kernel",
        grid=(nb,),
        in_specs=[smem, smem,
                  pl.BlockSpec((None, dim, rows, LANES),
                               lambda b: (b, 0, 0, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            # the accumulators live across the grid
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * block + resident + (16 << 20)),
        interpret=interpret,
    )(nv, scalars, x4)
    if not stats:
        return tuple(out)
    return (tuple(out[:2]), *out[2:])


def fold_stats(partial, k: int, dim: int):
    """The kernel's partial sums and counts -> ``(k, dim)`` float32 and
    ``(k,)`` int32."""
    sums, counts = partial
    if not sums_on_mxu(k, dim):
        return sums.sum(axis=(2, 3)), counts.sum(axis=(1, 2))
    # a row a cluster, a column ``(plane pair, s', half)``, the entries
    # off the diagonal already zero: the sum over s'
    planes = sums[:k].reshape(k, -1, 8, 2).sum(axis=2).reshape(k, -1)
    mid_hi = planes[:, 2:2 + 2 * dim].reshape(-1, dim, 2)
    lo = planes[:, 2 + 2 * dim:2 + 3 * dim - dim % 2]
    if dim % 2:
        lo = jnp.concatenate([lo, planes[:, :1]], axis=1)
    # the ones' column holds the counts, the same at every s'
    return (lo + mid_hi[:, :, 0]) + mid_hi[:, :, 1], counts[:k, 1]
