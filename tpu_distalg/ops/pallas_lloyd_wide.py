"""One Lloyd pass where the distance product is MXU work.

The third geometry of the k-means scale path, beside the lanes kernel
(``ops/pallas_lloyd.py``: k * dim up to 1024, scores unrolled on the
VPU) and plain rows (``ops/kmeans.py``). At a codebook shape (FAISS's
MNIST8m k-means: 784 dimensions, 4096 centres, float32) a pass is 2 * n
* k * dim flop of distances beside one read of the points, and the
``(n, k)`` matrix of distances is five times the table: it is never
held.

Layout (``WideGeometry``): ``f32[n_blocks, dim_held, P]``. Point ``p``
of a shard sits in block ``p // P``, lane ``p % P``; its features are
the block's rows. ``dim_held`` is ``dim`` rounded up to 16 (a bfloat16
register's rows), the rows past ``dim`` zero: 4 * dim bytes a point
wherever ``dim`` is a multiple of 16 (3136 B at 784: ``(n, 784)`` rows
are held padded to 896 lanes), no mask: validity follows from the id,
as on the lanes layout, and padding points hold any finite value. The
table has to be finite, padding included (0 x NaN in both products).

Two kernels a pass. The first is a flash-attention forward pass in
shape, with a minimum where the softmax is:

``_wide_assign_kernel``, grid ``(point blocks, centre tiles)``:

  score_c = |c|^2 - 2 x . c      the product on the MXU at float32
                                 accuracy: x and -2c each as three
                                 bfloat16 pieces that add back to the
                                 float32 bit for bit (``split3``), the
                                 six products XLA's
                                 ``Precision.HIGHEST`` keeps (hi.hi,
                                 hi.mid, mid.hi, mid.mid, hi.lo, lo.hi),
                                 each exact in float32, as ONE
                                 contraction: the six products' operands
                                 laid end to end as bands of ``dim_held``
                                 rows, ``[x_hi; x_lo; x_mid; x_hi; x_mid;
                                 x_hi]`` under ``[c_lo | c_hi | c_mid |
                                 c_mid | c_hi | c_hi]`` (smallest terms
                                 first), so that band b of one side
                                 meets band b of the other and one
                                 float32 accumulator takes all six.
                                 Centres down the sublanes, points along
                                 the lanes: a tile of the centres' stack
                                 ``(TN, depth)`` times the block's
                                 ``(depth, P)``, no transpose. The block
                                 is split and stacked once, when its
                                 first tile of centres comes; the tiles
                                 of centres stream past it (38.8 MB a
                                 block at k = 4096, 370 GB/s beside the
                                 product: hidden, a pass reads the same
                                 415 ms with the tile pinned)
  assign  = first minimum        a running ``(8, P)`` minimum and its
                                 centre a sublane, strict ``<`` from
                                 tile to tile, the smallest index among
                                 equals within a tile; the last tile
                                 folds the 8 sublanes, smallest index
                                 among equals again

The per-cluster sums and counts take one of two forms, chosen from
``(k, dim)`` alone (:func:`sums_form`; ``kmeans:prepare`` and
``train:segment`` say which, ``scatter`` or ``mxu``). Both add unrounded
float32 points in float32, in an order the ids fix (a pass repeats bit
for bit), and count in int32:

``_wide_scatter_kernel``, grid ``(tiles of centres, point blocks)``,
where a product against k centres would cost more than the adds:

  sums[id] += x                  the block transposed in VMEM (points
                                 down the rows, the features padded to
                                 ``dim_mxu`` lanes), then a point at a
                                 time: its centre read from SMEM, its
                                 row added to that centre's row of an
                                 accumulator ``(TK + 8, dim_mxu)`` that
                                 stays in VMEM while all points stream
                                 past (TK = k = 4096 at dim 784: 14.7
                                 MB, one read of the table). Two
                                 accumulators taken in turn, added once
                                 a pass: a load of a row waits for the
                                 last store to its accumulator. Padding
                                 points, and another tile's, go to a
                                 dump row nobody reads. What the
                                 algorithm needs: n x dim adds, 18 ms a
                                 pass at 784 x 4096 on one v5e where the
                                 product below took 202
  counts[id] += 1                int32 in SMEM, one array an accumulator

``_wide_stats_kernel``, grid ``(tiles of TK centres, chunks of 256
points)``, where k x dim is small:

  sums   += x . onehot^T         ``(TK, 256)`` 0 or 1, exact in
                                 bfloat16, under the chunk's three
                                 pieces: every product is piece x 1 or
                                 piece x 0, the accumulation float32,
                                 so the sums are float32 sums of
                                 unrounded points. The accumulators
                                 ``(dim_held, TK)``, features down the
                                 rows so that no column is padding, stay
                                 in VMEM while all points stream past;
                                 XLA transposes them once a pass. Its
                                 cost grows with k (every point times
                                 every centre's 0 or 1)
  counts += onehot               int32, lane by lane; XLA folds the 128
                                 lanes once a pass

In VMEM the distance product's contraction is padded to whole
128-deep slabs once, past all six bands (``dist_depth``: 4736 for 6 x
784, 37 slabs a tile, 0.7% of them zeros; as six products of ``dim_mxu``
= 896 each, until PR 35, 42 slabs, an eighth zeros: 479.7 -> 415.1 ms a
pass at 784 x 4096 on one v5e, and no width read slower: PERF.md §6);
in HBM nothing is. The scatter pads a point's features to ``dim_mxu``
lanes.
Interpreted on the CPU the kernels run the same bfloat16
``dot_general``s and the same loop over the points.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from tpu_distalg.ops.pallas_api import pl, pltpu

LANES = 128
SUBLANES = 8
PIECE_ROWS = 16            # rows of one bfloat16 register
BLOCK_POINTS = 512         # P
CENTRE_TILE = 512          # TN at most: centres scored a grid step
STATS_TILE = 4096          # TK at most: the one-hot is (TK, 256)
STATS_POINTS = 256         # points a grid step of the stats kernel
ACC_BYTES = 16 << 20       # one accumulator of the sums a tile of centres,
#                            one tile of the centres' stack
SCATTER_POINTS = 8         # points written out a trip of the scatter's loop
SCATTER_ACCS = 2           # accumulators the scatter takes in turn
SCATTER_LOOP = 96 << 10    # products a point the scatter's loop is worth
SCATTER_LANE = 160         # ... and its transpose, a lane of ``dim_mxu``
MAX_DIM = 4096             # a block and its stack stay under 34 MB
DIST_FORM = "mxu6"         # six bfloat16 passes: float32 accuracy
# the six products, smallest terms first: band b of either stack holds
# (the piece of -2c, the piece of x) of split3's (hi, mid, lo)
BANDS = ((2, 0), (0, 2), (1, 1), (1, 0), (0, 1), (0, 0))
_TOP = 0xFFFF0000          # the half of a float32 that is a bfloat16
_BIG = 3.0e38              # over any centre's index, as a float32
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _stack_depth(held: int) -> int:
    """Rows of either stack: the bands end to end, padded to whole
    128-deep slabs once."""
    return _round_up(len(BANDS) * held, LANES)


@dataclasses.dataclass(frozen=True)
class WideGeometry:
    dim: int
    k: int
    block_points: int      # P: points a block, along the lanes
    centre_tile: int       # TN
    stats_tile: int        # TK, a multiple of TN

    @property
    def dim_held(self) -> int:
        """Feature rows a block holds in HBM."""
        return _round_up(self.dim, PIECE_ROWS)

    @property
    def dim_mxu(self) -> int:
        """Depth of a contraction over the features in VMEM."""
        return _round_up(self.dim, LANES)

    @property
    def k_padded(self) -> int:
        return _round_up(self.k, self.stats_tile)

    @property
    def point_bytes(self) -> int:
        return 4 * self.dim_held

    layout = "wide"        # what the spans call it
    dist_form = DIST_FORM  # how a pass scores the distances

    @property
    def dist_depth(self) -> int:
        """Depth of the distance product's one contraction in VMEM: six
        bands of ``dim_held`` rows."""
        return _stack_depth(self.dim_held)

    @property
    def sums_form(self) -> str:
        """How a pass adds up the per-cluster sums: :func:`sums_form`."""
        return sums_form(self.k, self.dim)

    @property
    def scatter_tile(self) -> int:
        """Centres a tile of the scatter: one accumulator, its dump rows
        included, stays under ``ACC_BYTES``."""
        room = ACC_BYTES // (4 * self.dim_mxu) - SUBLANES
        return min(_round_up(self.k, SUBLANES), room // SUBLANES * SUBLANES)

    def pack(self, rows):
        """``(block_points, dim)`` rows -> one ``(dim_held, P)`` block."""
        return jnp.pad(rows.T, ((0, self.dim_held - self.dim), (0, 0)))

    def unpack(self, x3):
        """``(n_blocks, dim_held, P)`` -> ``(n_blocks * P, dim)`` rows in
        id order (tests and small tables only)."""
        return x3[:, :self.dim].transpose(0, 2, 1).reshape(-1, self.dim)


def wide_geometry(dim: int, k: int) -> WideGeometry | None:
    """The layout and tiles for ``dim`` features and ``k`` centres, from
    these two alone; ``None`` past ``MAX_DIM`` features (plain rows and
    ``ops/kmeans.py`` then)."""
    if dim > MAX_DIM:
        return None
    held = _round_up(dim, PIECE_ROWS)
    tn = min(CENTRE_TILE, _round_up(k, LANES))
    # a tile of the centres' stack stays under the budget too (TN 256
    # past dim 2720: two tiles in flight beside the block's own stack)
    while tn * _stack_depth(held) * 2 > ACC_BYTES:
        tn //= 2
    room = ACC_BYTES // (4 * held)
    cap = tn
    while 2 * cap <= min(room, STATS_TILE):
        cap *= 2
    return WideGeometry(dim, k, BLOCK_POINTS, tn,
                        min(_round_up(k, tn), cap))


def sums_form(k: int, dim: int) -> str:
    """``"scatter"`` or ``"mxu"``: how :func:`wide_stats` adds up the
    per-cluster sums of ``k`` centres in ``dim`` dimensions, from these
    two alone. A one-hot product costs k * dim products a point whatever
    the point's cluster; a scatter-add costs its loop (a point's row into
    its centre's, whatever k) and the block's transpose (in proportion to
    the padded width). On one v5e, 400 000 points a pass (PERF.md §6, PR
    31; ms, one-hot / scatter): the forms cross between k 1024 and 2048
    at dim 96 (2.71 / 2.98, 4.11 / 2.96), 512 and 1024 at 128 (2.41 /
    2.92, 3.24 / 2.81), 256 and 512 at 784 (3.99 / 4.57, 6.39 / 4.70),
    at 256 at 1024 (4.63 / 4.56); at 784 x 4096 201.7 / 18.2 a 2 025 000
    points, at 128 x 16384 28.6 / 3.2."""
    held, deep = _round_up(dim, PIECE_ROWS), _round_up(dim, LANES)
    if k * held >= SCATTER_LOOP + SCATTER_LANE * deep:
        return "scatter"
    return "mxu"


def _u32(x):
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def _f32(u):
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def split3(x):
    """``x`` (float32) as three bfloat16 pieces ``hi, mid, lo`` that add
    back to it bit for bit: ``hi`` the top 16 bits of x, ``mid`` the top
    16 of ``x - hi``, ``lo`` the rest (24 significand bits = 3 x 8;
    every step exact, so is each conversion: the low halves are zero).
    Bit masks and subtractions only: XLA may drop ``astype(bfloat16)
    .astype(float32)`` as excess precision, never these."""
    top = jnp.uint32(_TOP)
    hi = _f32(_u32(x) & top)
    r = x - hi
    mid = _f32(_u32(r) & top)
    return tuple(p.astype(jnp.bfloat16) for p in (hi, mid, r - mid))


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _wide_assign_kernel(c_ref, c2_ref, x_ref, out_ref,
                        stack_ref, best_ref, arg_ref, *, tn: int):
    """One block of points against one tile of centres. ``c_ref`` holds
    the stacked pieces of ``-2 c`` ``(TN, depth)``, ``c2_ref`` ``|c|^2``
    (infinite for the padding past k)."""
    i, j = pl.program_id(0), pl.program_id(1)
    held, p = x_ref.shape

    used, deep = len(BANDS) * held, stack_ref.shape[0]
    if deep > used:
        @pl.when((i == 0) & (j == 0))
        def _zero():
            # the contraction's padding: written once, never again
            stack_ref[pl.ds(used, deep - used), :] = jnp.zeros(
                (deep - used, p), jnp.bfloat16)

    @pl.when(j == 0)
    def _new_block():
        pieces = split3(x_ref[...])
        for b, (_, q) in enumerate(BANDS):
            stack_ref[pl.ds(b * held, held), :] = pieces[q]
        best_ref[...] = jnp.full(best_ref.shape, jnp.inf, jnp.float32)
        arg_ref[...] = jnp.zeros(arg_ref.shape, jnp.float32)

    s = _dot(c_ref[...], stack_ref[...], _NN) + c2_ref[...]

    # per sublane first: elementwise over the tile's TN / 8 registers
    s3 = s.reshape(tn // SUBLANES, SUBLANES, p)
    m8 = jnp.min(s3, axis=0)
    shape = (tn // SUBLANES, SUBLANES, p)
    cidx = (j * tn
            + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * SUBLANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            ).astype(jnp.float32)
    i8 = jnp.min(jnp.where(s3 == m8[None], cidx, _BIG), axis=0)
    better = m8 < best_ref[...]            # strict: the first minimum
    best_ref[...] = jnp.where(better, m8, best_ref[...])
    arg_ref[...] = jnp.where(better, i8, arg_ref[...])

    @pl.when(j == pl.num_programs(1) - 1)
    def _fold():
        b, a = best_ref[...], arg_ref[...]
        m = jnp.min(b, axis=0, keepdims=True)
        out_ref[...] = jnp.min(jnp.where(b == m, a, _BIG), axis=0,
                               keepdims=True).astype(jnp.int32)


def _wide_stats_kernel(nv_ref, x_ref, a_ref, sums_ref, cnt_ref, *,
                       tk: int):
    """One chunk of ``STATS_POINTS`` points into one tile of TK
    centres' sums ``(dim_held, TK)`` and counts."""
    j, i = pl.program_id(0), pl.program_id(1)
    p = x_ref.shape[1]

    @pl.when(i == 0)
    def _new_tile():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    hi, mid, lo = split3(x_ref[...])
    pid = i * p + jax.lax.broadcasted_iota(jnp.int32, (1, p), 1)
    # padding points join no cluster
    rel = jnp.where(pid < nv_ref[0], a_ref[...], -1) - j * tk
    hot = jax.lax.broadcasted_iota(jnp.int32, (tk, p), 0) == rel
    ones = jnp.where(hot, 1.0, 0.0).astype(jnp.bfloat16)
    sums_ref[...] += (_dot(lo, ones, _NT) + _dot(mid, ones, _NT)) \
        + _dot(hi, ones, _NT)
    n = hot.astype(jnp.int32)
    cnt_ref[...] += functools.reduce(
        jax.lax.add, [n[:, c * LANES:(c + 1) * LANES]
                      for c in range(p // LANES)])


def _wide_scatter_kernel(ids_ref, x_ref, *refs, n_acc: int, points: int):
    """One block of points into one tile of centres' sums, a point at a
    time: ``acc[id] += x``. ``ids_ref`` (SMEM) holds the block's centres
    relative to the tile, the tile's dump row for a point that is padding
    or another tile's. ``refs``: ``n_acc`` outputs of sums (HBM) and of
    counts (SMEM), ``n_acc`` accumulators ``(TK + 8, dim_mxu)``, the
    transposed block, the copies' semaphores.

    Neighbouring points go to different accumulators, and each is its
    own allocation: a point's load of its centre's row has to wait for
    the last store to that accumulator (the compiler cannot tell two
    rows apart), 7 bundles on a v5e, and two accumulators halve the
    chain (18.2 ms a pass at 784 x 4096 against 22.2 with one, 18.9 with
    four; as one array with a leading index: no gain)."""
    outs, cnts = refs[:n_acc], refs[n_acc:2 * n_acc]
    accs, (xt_ref, sem) = refs[2 * n_acc:3 * n_acc], refs[3 * n_acc:]
    j, i = pl.program_id(0), pl.program_id(1)
    held, p = x_ref.shape

    @pl.when((j == 0) & (i == 0))
    def _pad():
        # the columns past dim_held: written once, never again
        xt_ref[...] = jnp.zeros_like(xt_ref)

    @pl.when(i == 0)
    def _new_tile():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

        def zero(c, carry):
            for cnt in cnts:
                cnt[0, c] = 0
            return carry

        jax.lax.fori_loop(0, cnts[0].shape[1], zero, 0)

    # points down the rows: a point's features are one row
    xt_ref[:, pl.ds(0, held)] = x_ref[...].T

    def some(t, carry):
        first = pl.multiple_of(t * points, points)
        for u in range(points):
            c = ids_ref[0, first + u]
            accs[u % n_acc][pl.ds(c, 1), :] += xt_ref[pl.ds(first + u, 1), :]
            cnts[u % n_acc][0, c] += 1
        return carry

    jax.lax.fori_loop(0, p // points, some, 0)

    @pl.when(i == pl.num_programs(1) - 1)
    def _out():
        copies = [pltpu.make_async_copy(acc, out.at[j], sem.at[n])
                  for n, (acc, out) in enumerate(zip(accs, outs))]
        for copy in copies:
            copy.start()
        for copy in copies:
            copy.wait()


def _check(x3, geom: WideGeometry):
    if x3.shape[1:] != (geom.dim_held, geom.block_points):
        raise ValueError(
            f"wide pass: blocks {x3.shape[1:]} are not the geometry's "
            f"{(geom.dim_held, geom.block_points)}")


@functools.partial(jax.jit, static_argnames=("geom", "interpret"))
def wide_assign(x3, centers, *, geom: WideGeometry,
                interpret: bool = False):
    """The nearest centre of every point of ``x3`` ``f32[n_blocks,
    dim_held, P]``, padding included: ``int32[n_blocks, 1, P]`` in id
    order."""
    _check(x3, geom)
    nb, held, p = x3.shape
    k, dim, tn, deep = geom.k, geom.dim, geom.centre_tile, geom.dist_depth
    c32 = centers.astype(jnp.float32)
    pieces = split3(jnp.pad(-2.0 * c32, ((0, geom.k_padded - k),
                                         (0, held - dim))))
    # the zeros by a pad of their own: as one more operand of the
    # concatenate the kernel reads its tiles 0.4% slower (PERF.md §6, PR 35)
    stack = jnp.pad(jnp.concatenate([pieces[q] for q, _ in BANDS], axis=1),
                    ((0, 0), (0, deep - len(BANDS) * held)))
    c2 = jnp.pad(jnp.sum(c32 * c32, axis=1), (0, geom.k_padded - k),
                 constant_values=jnp.inf)[:, None]
    kernel = functools.partial(_wide_assign_kernel, tn=tn)
    return pl.pallas_call(
        kernel,
        name="_wide_assign_kernel",
        grid=(nb, geom.k_padded // tn),
        in_specs=[pl.BlockSpec((tn, deep), lambda i, j: (j, 0)),
                  pl.BlockSpec((tn, 1), lambda i, j: (j, 0)),
                  pl.BlockSpec((None, held, p), lambda i, j: (i, 0, 0))],
        out_specs=pl.BlockSpec((None, 1, p), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, p), jnp.int32),
        scratch_shapes=[pltpu.VMEM((deep, p), jnp.bfloat16),
                        pltpu.VMEM((SUBLANES, p), jnp.float32),
                        pltpu.VMEM((SUBLANES, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # the running minimum and the stack live across the grid
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem(geom, 8 * tn * p * 4)),
        interpret=interpret,
    )(stack, c2, x3)


@functools.partial(jax.jit, static_argnames=("geom", "interpret"))
def wide_stats(x3, assign, n_valid, *, geom: WideGeometry,
               interpret: bool = False):
    """``(k, dim)`` float32 sums and ``(k,)`` int32 counts of this
    shard's valid points (ids below ``n_valid``) under ``assign`` as
    :func:`wide_assign` returns it, in the form ``geom.sums_form`` names:
    a scatter-add of each point into its centre's row where k x dim makes
    a one-hot product the dearer (:func:`sums_form`), else the one-hot
    product. Either way float32 sums of unrounded points in an order the
    ids fix, and exact counts."""
    _check(x3, geom)
    form = scatter_stats if geom.sums_form == "scatter" else onehot_stats
    return form(x3, assign, n_valid, geom=geom, interpret=interpret)


def onehot_stats(x3, assign, n_valid, *, geom: WideGeometry,
                 interpret: bool = False):
    """:func:`wide_stats` as a one-hot product on the MXU. A grid step
    takes ``STATS_POINTS`` lanes of a block (on one v5e at 784 x 4096 a
    pass took 202.6 ms in chunks of 256 points, 240.7 in 512, 222.4 in
    1024; the sums as ``(TK, dim)`` with the features padded to 896
    columns 30 ms more each: PERF.md §6, PR 30)."""
    nb, held, p = x3.shape
    tk = geom.stats_tile
    q = min(p, STATS_POINTS)
    per = p // q                           # chunks a block
    kernel = functools.partial(_wide_stats_kernel, tk=tk)
    sums, counts = pl.pallas_call(
        kernel,
        name="_wide_stats_kernel",
        grid=(geom.k_padded // tk, nb * per),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((None, held, q),
                               lambda j, i: (i // per, 0, i % per)),
                  pl.BlockSpec((None, 1, q),
                               lambda j, i: (i // per, 0, i % per))],
        out_specs=[pl.BlockSpec((held, tk), lambda j, i: (0, j)),
                   pl.BlockSpec((tk, LANES), lambda j, i: (j, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((held, geom.k_padded), jnp.float32),
            jax.ShapeDtypeStruct((geom.k_padded, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            # the accumulators live across the grid
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem(
                geom, 3 * tk * held * 4 + 3 * tk * q * 4)),
        interpret=interpret,
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), x3, assign)
    return (sums.T[:geom.k, :geom.dim], counts.sum(axis=1)[:geom.k])


def scatter_stats(x3, assign, n_valid, *, geom: WideGeometry,
                  interpret: bool = False, tile: int | None = None):
    """:func:`wide_stats` as a scatter-add: every point's row added to
    its centre's row of a float32 accumulator that stays in VMEM while
    all blocks stream past, counts in SMEM. Grid ``(tiles of centres,
    blocks)``; ``tile`` (``geom.scatter_tile``) centres an accumulator
    holds, so past ``ACC_BYTES`` the centres are tiled and a tile costs
    a whole pass of its own (the table's read and the loop over every
    point: 4.6 ms a 400 000 points at dim 784 on one v5e), the points of
    other tiles going to the dump row with the padding."""
    nb, held, p = x3.shape
    tk = geom.scatter_tile if tile is None else tile
    tiles, rows, deep = -(-geom.k // tk), tk + SUBLANES, geom.dim_mxu
    n_acc = SCATTER_ACCS
    pid = jnp.arange(nb * p, dtype=jnp.int32).reshape(nb, 1, p)
    # padding points join no cluster: the tile's dump row takes them
    rel = jnp.where(pid < n_valid, assign, -1)[None] - tk * jnp.arange(
        tiles, dtype=jnp.int32)[:, None, None, None]
    ids = jnp.where((rel >= 0) & (rel < tk), rel, tk)
    kernel = functools.partial(_wide_scatter_kernel, n_acc=n_acc,
                               points=SCATTER_POINTS)
    got = pl.pallas_call(
        kernel,
        name="_wide_scatter_kernel",
        grid=(tiles, nb),
        in_specs=[pl.BlockSpec((None, None, 1, p),
                               lambda j, i: (j, i, 0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((None, held, p), lambda j, i: (i, 0, 0))],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_acc
        + [pl.BlockSpec((None, 1, rows), lambda j, i: (j, 0, 0),
                        memory_space=pltpu.SMEM)] * n_acc,
        out_shape=[jax.ShapeDtypeStruct((tiles, rows, deep),
                                        jnp.float32)] * n_acc
        + [jax.ShapeDtypeStruct((tiles, 1, rows), jnp.int32)] * n_acc,
        scratch_shapes=[pltpu.VMEM((rows, deep), jnp.float32)] * n_acc
        + [pltpu.VMEM((p, deep), jnp.float32),
           pltpu.SemaphoreType.DMA((n_acc,))],
        compiler_params=pltpu.CompilerParams(
            # the accumulators live across the grid
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem(
                geom, n_acc * rows * deep * 4 + p * deep * 4)),
        interpret=interpret,
    )(ids, x3)
    sums = functools.reduce(jax.lax.add, got[:n_acc])[:, :tk]
    counts = functools.reduce(jax.lax.add, got[n_acc:])[:, 0, :tk]
    return (sums.reshape(tiles * tk, deep)[:geom.k, :geom.dim],
            counts.reshape(tiles * tk)[:geom.k])


def _vmem(geom: WideGeometry, working: int) -> int:
    """A kernel's VMEM limit: two blocks in flight and one being split,
    the block's stack, two tiles of the centres' stack, and the kernel's
    own working set."""
    p, deep = geom.block_points, geom.dist_depth
    return (3 * geom.dim_held * p * 4 + deep * p * 2
            + 2 * geom.centre_tile * deep * 2 + working + (16 << 20))
