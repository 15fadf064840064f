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

Two kernels a pass, both a flash-attention forward pass in shape, with
a minimum where the softmax is:

``_wide_assign_kernel``, grid ``(point blocks, centre tiles)``:

  score_c = |c|^2 - 2 x . c      the product on the MXU at float32
                                 accuracy: x and -2c each as three
                                 bfloat16 pieces that add back to the
                                 float32 bit for bit (``split3``), the
                                 six products XLA's
                                 ``Precision.HIGHEST`` keeps (hi.hi,
                                 hi.mid, mid.hi, mid.mid, hi.lo, lo.hi),
                                 accumulated in float32, smallest terms
                                 first. Centres down the sublanes,
                                 points along the lanes: a tile of
                                 centres ``(TN, dim)`` times the block
                                 ``(dim, P)``, no transpose. The block
                                 is split once, when its first tile of
                                 centres comes; the tiles of centres
                                 stream past it (19.3 MB a block at
                                 k = 4096, a quarter of the product's
                                 own time at P = 512)
  assign  = first minimum        a running ``(8, P)`` minimum and its
                                 centre a sublane, strict ``<`` from
                                 tile to tile, the smallest index among
                                 equals within a tile; the last tile
                                 folds the 8 sublanes, smallest index
                                 among equals again

``_wide_stats_kernel``, grid ``(tiles of TK centres, chunks of 256
points)``:

  sums   += x . onehot^T         ``(TK, 256)`` 0 or 1, exact in
                                 bfloat16, under the chunk's three
                                 pieces: every product is piece x 1 or
                                 piece x 0, the accumulation float32,
                                 so the sums are float32 sums of
                                 unrounded points. The accumulators
                                 ``(dim_held, TK)``, features down the
                                 rows so that no column is padding, stay
                                 in VMEM while all points stream past
                                 (TK = k = 4096 at dim 784: one read of
                                 the table); XLA transposes them once a
                                 pass
  counts += onehot               int32, lane by lane; XLA folds the 128
                                 lanes once a pass

No scatter-add anywhere. In VMEM the distance product's contraction is
padded to whole 128-deep slabs (``dim_mxu``: 896 for 784, so an eighth
of its MXU passes multiplies zeros: PERF.md §7); in HBM nothing is.
Interpreted on the CPU the kernels run the same bfloat16
``dot_general``s.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
PIECE_ROWS = 16            # rows of one bfloat16 register
BLOCK_POINTS = 512         # P
CENTRE_TILE = 512          # TN at most: centres scored a grid step
STATS_TILE = 4096          # TK at most: the one-hot is (TK, 256)
STATS_POINTS = 256         # points a grid step of the stats kernel
ACC_BYTES = 16 << 20       # the sums' accumulators a tile of TK centres
MAX_DIM = 4096             # a block and its pieces stay under 32 MB
DIST_FORM = "mxu6"         # six bfloat16 passes: float32 accuracy
_TOP = 0xFFFF0000          # the half of a float32 that is a bfloat16
_BIG = 3.0e38              # over any centre's index, as a float32
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


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
    tn = min(CENTRE_TILE, _round_up(k, LANES))
    room = ACC_BYTES // (4 * _round_up(dim, PIECE_ROWS))
    cap = tn
    while 2 * cap <= min(room, STATS_TILE):
        cap *= 2
    return WideGeometry(dim, k, BLOCK_POINTS, tn,
                        min(_round_up(k, tn), cap))


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
                        pieces_ref, best_ref, arg_ref, *, tn: int):
    """One block of points against one tile of centres. ``c_ref`` holds
    the pieces of ``-2 c``, ``c2_ref`` ``|c|^2`` (infinite for the
    padding past k)."""
    i, j = pl.program_id(0), pl.program_id(1)
    p = x_ref.shape[1]

    held, deep = x_ref.shape[0], pieces_ref.shape[1]
    if deep > held:
        @pl.when((i == 0) & (j == 0))
        def _zero():
            # the contraction's padding: written once, never again
            pieces_ref[:, pl.ds(held, deep - held), :] = jnp.zeros(
                (3, deep - held, p), jnp.bfloat16)

    @pl.when(j == 0)
    def _new_block():
        for q, piece in enumerate(split3(x_ref[...])):
            pieces_ref[q, pl.ds(0, held), :] = piece
        best_ref[...] = jnp.full(best_ref.shape, jnp.inf, jnp.float32)
        arg_ref[...] = jnp.zeros(arg_ref.shape, jnp.float32)

    xh, xm, xl = pieces_ref[0], pieces_ref[1], pieces_ref[2]
    ch, cm, cl = c_ref[0], c_ref[1], c_ref[2]
    s = _dot(cl, xh, _NN) + _dot(ch, xl, _NN)
    s = s + _dot(cm, xm, _NN)
    s = s + (_dot(cm, xh, _NN) + _dot(ch, xm, _NN))
    s = (s + _dot(ch, xh, _NN)) + c2_ref[...]

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
    k, dim, tn, deep = geom.k, geom.dim, geom.centre_tile, geom.dim_mxu
    c32 = centers.astype(jnp.float32)
    cm2 = jnp.pad(-2.0 * c32, ((0, geom.k_padded - k), (0, deep - dim)))
    c2 = jnp.pad(jnp.sum(c32 * c32, axis=1), (0, geom.k_padded - k),
                 constant_values=jnp.inf)[:, None]
    kernel = functools.partial(_wide_assign_kernel, tn=tn)
    return pl.pallas_call(
        kernel,
        name="_wide_assign_kernel",
        grid=(nb, geom.k_padded // tn),
        in_specs=[pl.BlockSpec((3, tn, deep), lambda i, j: (0, j, 0)),
                  pl.BlockSpec((tn, 1), lambda i, j: (j, 0)),
                  pl.BlockSpec((None, held, p), lambda i, j: (i, 0, 0))],
        out_specs=pl.BlockSpec((None, 1, p), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, p), jnp.int32),
        scratch_shapes=[pltpu.VMEM((3, deep, p), jnp.bfloat16),
                        pltpu.VMEM((SUBLANES, p), jnp.float32),
                        pltpu.VMEM((SUBLANES, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # the running minimum and the pieces live across the grid
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem(geom, 8 * tn * p * 4)),
        interpret=interpret,
    )(jnp.stack(split3(cm2)), c2, x3)


@functools.partial(jax.jit, static_argnames=("geom", "interpret"))
def wide_stats(x3, assign, n_valid, *, geom: WideGeometry,
               interpret: bool = False):
    """``(k, dim)`` float32 sums and ``(k,)`` int32 counts of this
    shard's valid points (ids below ``n_valid``) under ``assign`` as
    :func:`wide_assign` returns it. A grid step takes ``STATS_POINTS``
    lanes of a block (on one v5e at 784 x 4096 a pass took 202.6 ms in
    chunks of 256 points, 240.7 in 512, 222.4 in 1024; the sums as
    ``(TK, dim)`` with the features padded to 896 columns 30 ms more
    each: PERF.md §6, PR 30)."""
    _check(x3, geom)
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


def _vmem(geom: WideGeometry, working: int) -> int:
    """A kernel's VMEM limit: two blocks in flight, their pieces, two
    tiles of centres' pieces, and the kernel's own working set."""
    p, deep = geom.block_points, geom.dim_mxu
    return (2 * geom.dim_held * p * 4 + 3 * deep * p * 2
            + 2 * 3 * geom.centre_tile * deep * 2 + working + (16 << 20))
