"""One Lloyd pass over points held feature-major, in row blocks.

Why a second k-means path. At a chip-filling shape (HiBench ``huge``:
100M points x 20 dimensions, k = 10, float32) the row layout of
``ops/kmeans.py`` holds 96 B a point and a 4 B mask (XLA lays
``f32[n, 20]`` out column-major in ``(8, 128)`` tiles, 24 sublanes for
20 columns: 10.0 GB with the mask, 11.0 GB with the pass's
intermediates). PERF.md §6 (PR 26) has what each does on the chip.

Layout (``LanesGeometry``): ``f32[n_blocks, dim, R, 128]``. Point ``p``
of a shard sits in block ``p // (R * 128)``, sublane row ``(p // 128) %
R``, lane ``p % 128``; its ``dim`` features are ``dim`` separate
``(R, 128)`` tiles of that block. The last two dimensions are whole
tiles, so nothing is padded: 4 * dim bytes a point (80 B at dim 20, 8.0
GB at 100M), a block is one contiguous DMA, and a generator that draws
block after block (``parallel.build_sharded`` with ``pack=``) writes the
layout without a transpose of the whole table. Padding points (ids past
the valid count) hold anything; validity follows from the id.

Kernel (``lloyd_pass``): a block at a time, ``sub`` sublane rows at a
time, all on the VPU in float32, centres as scalars from SMEM:

  score_c = |c|^2 - 2 x . c      k * dim multiply-adds a point; the
                                 argmin over c of |x - c|^2 without the
                                 |x|^2 every c shares; no product is
                                 rounded to bfloat16, as the MXU's
                                 default precision would (and its exact
                                 mode costs six passes of a 20 x 10
                                 matrix that fills 1% of the array)
  assign  = first minimum        a strict ``<`` scan over c, the
                                 reference's ``closest_center``
  sums[c, d] += where(assign == c, x_d, 0)
  counts[c]  += (assign == c)    int32: float32 holds no odd count past
                                 2**24, and a cluster of 100M points
                                 has more

``sums`` and ``counts`` accumulate per (sublane, lane) position in the
output block, which stays in VMEM across the grid; the caller folds
the 1024 partial sums a cell (``fold_stats``). About 860 vector
operations a 1024 points: at dim 20, k 10 the pass is bound by the VPU
about as much as by HBM (PERF.md has the chip readings).
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
# the kernel unrolls k * dim multiply-adds and as many masked adds, and
# keeps k * dim (8, 128) accumulators in VMEM (4 KB each)
MAX_UNROLL = 1024
BLOCK_BYTES = 6 << 20      # a block's share of VMEM; two are in flight


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


def _fold8(x):
    """(sub, 128) -> (8, 128): whole-tile adds, no cross-lane work."""
    return x if x.shape[0] == SUBLANES else x.reshape(
        -1, SUBLANES, LANES).sum(axis=0)


def _lloyd_kernel(nv_ref, cs_ref, x_ref, *out_refs, k: int, dim: int,
                  rows: int, sub: int, stats: bool, assign: bool,
                  unroll: bool):
    """One block: ``rows`` sublane rows of 128 points, ``sub`` at a time."""
    out_refs = list(out_refs)
    sums_ref, cnt_ref = (out_refs.pop(0), out_refs.pop(0)) if stats \
        else (None, None)
    asg_ref = out_refs.pop(0) if assign else None
    i = pl.program_id(0)

    if stats:
        @pl.when(i == 0)
        def _init():
            sums_ref[...] = jnp.zeros_like(sums_ref)
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

    n_valid = nv_ref[0]
    within = (jax.lax.broadcasted_iota(jnp.int32, (sub, LANES), 0) * LANES
              + jax.lax.broadcasted_iota(jnp.int32, (sub, LANES), 1))

    def step(j, carry):
        r0 = pl.multiple_of(j * sub, sub)
        xs = [x_ref[d, pl.ds(r0, sub), :] for d in range(dim)]

        def nearer(c, carry):
            best, idx = carry
            s = xs[0] * cs_ref[c * dim]
            for d in range(1, dim):
                s = s + xs[d] * cs_ref[c * dim + d]
            s = s + cs_ref[k * dim + c]
            better = s < best              # strict: the first minimum
            return jnp.where(better, s, best), jnp.where(better, c, idx)

        _, idx = jax.lax.fori_loop(
            0, k, nearer, (jnp.full((sub, LANES), jnp.inf, jnp.float32),
                           jnp.zeros((sub, LANES), jnp.int32)),
            unroll=unroll)
        if assign:
            asg_ref[pl.ds(r0, sub), :] = idx
        if stats:
            pid = (i * rows + r0) * LANES + within
            idx = jnp.where(pid < n_valid, idx, k)   # padding: no cluster

            def add(c, carry):
                m = idx == c
                cnt_ref[c] += _fold8(m.astype(jnp.int32))
                for d in range(dim):
                    sums_ref[c, d] += _fold8(jnp.where(m, xs[d], 0.0))
                return carry

            jax.lax.fori_loop(0, k, add, 0, unroll=unroll)
        return carry

    jax.lax.fori_loop(0, rows // sub, step, 0)


@functools.partial(
    jax.jit, static_argnames=("stats", "assign", "sub", "interpret"))
def lloyd_pass(x4, centers, n_valid, *, stats: bool = True,
               assign: bool = False, sub: int | None = None,
               interpret: bool = False):
    """One pass over one shard's points under ``centers``.

    ``x4`` ``f32[n_blocks, dim, R, 128]``; ``centers`` ``f32[k, dim]``;
    ``n_valid`` int32 scalar, the count of this shard's points that are
    valid (ids below it). Returns, in this order and as asked for:
    ``stats`` the partial sums ``f32[k, dim, 8, 128]`` and counts
    ``int32[k, 8, 128]`` over the valid points (``fold_stats`` makes
    them ``(k, dim)`` and ``(k,)``); ``assign`` the nearest centre of
    every point, padding included, ``int32[n_blocks * R, 128]`` in id
    order. ``sub``: sublane rows a step of the kernel's inner loop.
    Compiled, 16 (a pass over 100M x 20 points at k = 10 on a v5e:
    36.9 ms at 8, 17.9 ms at 16, 19.5 ms at 32; my chip run, PR 26);
    interpreted, the whole block is one step and the loops over centres
    stay rolled (XLA:CPU compiles the unrolled body for half a
    minute)."""
    nb, dim, rows, lanes = x4.shape
    k = centers.shape[0]
    if sub is None:
        sub = rows if interpret else min(rows, 2 * SUBLANES)
    if lanes != LANES or rows % sub or sub % SUBLANES:
        raise ValueError(f"lloyd_pass: blocks {x4.shape[1:]} need 128 "
                         f"lanes and rows in multiples of sub={sub}")
    c32 = centers.astype(jnp.float32)
    scalars = jnp.concatenate(
        [(-2.0 * c32).reshape(-1), jnp.sum(c32 * c32, axis=1)])
    nv = jnp.asarray(n_valid, jnp.int32).reshape(1)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_specs, out_shape = [], []
    if stats:
        out_specs += [
            pl.BlockSpec((k, dim, SUBLANES, LANES), lambda b: (0, 0, 0, 0)),
            pl.BlockSpec((k, SUBLANES, LANES), lambda b: (0, 0, 0))]
        out_shape += [
            jax.ShapeDtypeStruct((k, dim, SUBLANES, LANES), jnp.float32),
            jax.ShapeDtypeStruct((k, SUBLANES, LANES), jnp.int32)]
    if assign:
        out_specs.append(pl.BlockSpec((rows, LANES), lambda b: (b, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((nb * rows, LANES), jnp.int32))
    block = dim * rows * LANES * 4
    held = k * (dim + 1) * SUBLANES * LANES * 4
    kernel = functools.partial(
        _lloyd_kernel, k=k, dim=dim, rows=rows, sub=sub, stats=stats,
        assign=assign, unroll=not interpret)
    return pl.pallas_call(
        kernel,
        name="_lloyd_kernel",
        grid=(nb,),
        in_specs=[smem, smem,
                  pl.BlockSpec((None, dim, rows, LANES),
                               lambda b: (b, 0, 0, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            # the accumulators live across the grid
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * block + 2 * held + (16 << 20)),
        interpret=interpret,
    )(nv, scalars, x4)


def fold_stats(sums8, counts8):
    """The kernel's per-position partial sums -> ``(k, dim)`` float32
    and ``(k,)`` int32."""
    return sums8.sum(axis=(2, 3)), counts8.sum(axis=(1, 2))
