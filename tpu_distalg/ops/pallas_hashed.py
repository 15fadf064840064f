"""The two passes of logistic regression over hashed rows.

A hashed row is ``nnz`` int32 slots of a weight table of ``2 **
hash_bits`` float32 and a 0/1 label; every value is 1 (a hashed one-hot:
click logs, ``models/ssgd.py``'s second row format). A block of
``block_rows`` rows is held field-major, ``int32[fields_held,
block_rows]``: field ``j`` of the block's rows is one dense run of lanes,
the label is row ``nnz``, the rows up to ``fields_held`` (``nnz + 1``
rounded up to a sublane tile) are zero. 160 B a row at 39 fields, 157
of them needed.

The model is one vector ``w`` of ``n_slots + 128`` float32: the table,
the bias at ``[n_slots]``, zeros behind it. A step over the sampled
blocks ``ids`` is

  margins:    m_i = b + sum_j w[h_ij]            (two fields of a row in
                                                 one slot count twice)
  slot sums:  g[s] = sum_i r_i * #{j: h_ij = s}  g[n_slots] = sum_i r_i

with ``r`` whatever the caller made of ``m`` (residual times validity).
Both are bound by addresses and not by bytes: 35.8M dependent accesses
a step at the benchmark's shape beside 73 MB read. Each has two forms
that give the same numbers up to the order of float32 additions, and
:func:`pass_form` picks one from the geometry alone:

``vmem``  Mosaic: the table (or the accumulators) ``(n_slots / 128,
          128)`` stays in VMEM, a chunk of the block's indices comes
          through SMEM, and a row at a time each index loads the
          table's 128-wide row ``h >> 7`` and keeps lane ``h & 127`` by
          a mask. The gather adds a row's masked loads into one vector
          that XLA folds; the scatter adds the row's residual, masked,
          into the row of an accumulator, the accumulators taken in
          turn (a load waits for the last store to its allocation,
          ``pallas_lloyd_wide``'s finding: on one v5e at the
          benchmark's shape a step's scatter took 100.1 ms with one
          accumulator, 59.9 with two, 39.6 with four; its gather 54.4).
``xla``   ``w[idx]`` and ``zeros.at[idx].add``: what XLA makes of them;
          the only form where the table is past VMEM or a block is not
          whole lanes.

Interpreted on the CPU the kernels run the same loads, masks and adds
in the same order.
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
CHUNK_ROWS = 256       # rows a grid step takes: 40 x 256 indices in SMEM
LOOP_ROWS = 2          # rows written out a trip of the loop
GATHER_SUMS = 4        # partial sums a row's loads are added into
SCATTER_ACCS = 4       # accumulators the scatter takes in turn, at most
ACC_VMEM_BYTES = 64 << 20   # ... and what they and their second buffers
#                             may take of VMEM: 4 up to 2**21 slots, 2 at
#                             2**22
VMEM_BITS = 22         # a table of 16 MB and its accumulators fit VMEM
MIN_BITS = 10          # one (8, 128) tile of slots


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def pass_form(hash_bits: int, block_rows: int) -> str:
    """``'vmem'`` where the table and the scatter's accumulators stay in
    VMEM and a block's rows are whole lanes, else ``'xla'``."""
    if MIN_BITS <= hash_bits <= VMEM_BITS and block_rows % LANES == 0:
        return "vmem"
    return "xla"


@dataclasses.dataclass(frozen=True)
class HashedGeometry:
    nnz: int               # fields a row
    hash_bits: int
    block_rows: int

    def __post_init__(self):
        if self.nnz < 1 or not 1 <= self.hash_bits <= 30:
            raise ValueError(
                f"hashed rows: nnz {self.nnz} must be >= 1 and hash_bits "
                f"{self.hash_bits} in [1, 30] (int32 slots)")

    @property
    def n_slots(self) -> int:
        return 1 << self.hash_bits

    @property
    def w_len(self) -> int:
        """The model vector: the table, the bias, zeros to a lane row."""
        return self.n_slots + LANES

    @property
    def fields_held(self) -> int:
        return _round_up(self.nnz + 1, SUBLANES)

    @property
    def row_bytes(self) -> int:
        return 4 * self.fields_held

    @property
    def pass_form(self) -> str:
        return pass_form(self.hash_bits, self.block_rows)

    @property
    def chunk_rows(self) -> int:
        return min(CHUNK_ROWS, self.block_rows)

    @property
    def scatter_accs(self) -> int:
        """Accumulators of the scatter: ``SCATTER_ACCS``, fewer where
        so many tables and their second buffers would not fit."""
        return max(1, min(SCATTER_ACCS,
                          ACC_VMEM_BYTES // (2 * 4 * self.n_slots)))


def _check(X, geom: HashedGeometry):
    if X.ndim != 3 or X.shape[1:] != (geom.fields_held, geom.block_rows) \
            or X.dtype != jnp.int32:
        raise ValueError(
            f"hashed table {X.shape} {X.dtype} is not int32 blocks of "
            f"{(geom.fields_held, geom.block_rows)}")


def labels(X, ids, geom: HashedGeometry):
    """``f32[n_sampled, block_rows]``: the 0/1 labels of blocks ``ids``."""
    return X[ids, geom.nnz, :].astype(jnp.float32)


# ---- XLA forms ---------------------------------------------------------

def margins_xla(X, w, ids, geom: HashedGeometry):
    idx = X[ids][:, :geom.nnz, :]
    return jnp.sum(w[:geom.n_slots][idx], axis=1) + w[geom.n_slots]


def slot_sums_xla(X, r, ids, geom: HashedGeometry):
    idx = X[ids][:, :geom.nnz, :]
    g = jnp.zeros((geom.n_slots,), jnp.float32).at[idx].add(
        jnp.broadcast_to(r[:, None, :], idx.shape))
    tail = jnp.zeros((LANES,), jnp.float32).at[0].set(jnp.sum(r))
    return jnp.concatenate([g, tail])


# ---- Mosaic forms ------------------------------------------------------

def _hashed_gather_kernel(ids_ref, idx_ref, w_ref, out_ref, *, nnz: int,
                          rows: int):
    """One chunk of one sampled block: ``out[i, :]`` holds row ``i``'s
    ``nnz`` weights in the lanes their slots have in the table, slots of
    one lane added up; the sum over lanes is the margin less the bias.
    ``GATHER_SUMS`` partial vectors keep the adds of one row off one
    chain."""
    del ids_ref                         # the index maps read it
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def some(t, carry):
        first = pl.multiple_of(t * rows, rows)
        for u in range(rows):
            sums = [None] * min(GATHER_SUMS, nnz)
            for j in range(nnz):
                h = idx_ref[j, first + u]
                got = jnp.where(lane == (h & (LANES - 1)),
                                w_ref[pl.ds(h >> 7, 1), :], 0.0)
                k = j % len(sums)
                sums[k] = got if sums[k] is None else sums[k] + got
            while len(sums) > 1:        # pairwise, a fixed order
                sums = [a + b for a, b in zip(sums[::2], sums[1::2])] \
                    + sums[len(sums) & ~1:]
            out_ref[pl.ds(first + u, 1), :] = sums[0]
        return carry

    jax.lax.fori_loop(0, idx_ref.shape[1] // rows, some, 0)


def _hashed_scatter_kernel(ids_ref, idx_ref, rb_ref, *accs, nnz: int,
                           rows: int):
    """One chunk of one sampled block into the accumulators ``(n_slots /
    128, 128)``, which stay in VMEM over the whole grid: row ``i``'s
    residual (``rb[i, :]``, the same in every lane) is added at each of
    its ``nnz`` slots, in lane ``h & 127`` of row ``h >> 7``.
    Neighbouring accesses go to different accumulators, each its own
    allocation."""
    del ids_ref
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _zero():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    def some(t, carry):
        first = pl.multiple_of(t * rows, rows)
        for u in range(rows):
            r = rb_ref[pl.ds(first + u, 1), :]
            for j in range(nnz):
                h = idx_ref[j, first + u]
                acc = accs[(u * nnz + j) % len(accs)]
                acc[pl.ds(h >> 7, 1), :] += jnp.where(
                    lane == (h & (LANES - 1)), r, 0.0)
        return carry

    jax.lax.fori_loop(0, idx_ref.shape[1] // rows, some, 0)


def _grid_spec(ids, geom: HashedGeometry, more_in, out_specs):
    cr = geom.chunk_rows
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ids.shape[0], geom.block_rows // cr),
        in_specs=[pl.BlockSpec((None, geom.fields_held, cr),
                               lambda s, c, ids: (ids[s], 0, c),
                               memory_space=pltpu.SMEM)] + more_in,
        out_specs=out_specs)


def _loop_rows(geom: HashedGeometry, rows: int | None) -> int:
    rows = LOOP_ROWS if rows is None else rows
    return rows if geom.chunk_rows % rows == 0 else 1


def _vmem_limit(geom: HashedGeometry, tables: int) -> int:
    return (tables * 4 * geom.n_slots
            + 8 * geom.chunk_rows * LANES * 4 + (8 << 20))


def margins_vmem(X, w, ids, geom: HashedGeometry, *,
                 interpret: bool = False, rows: int | None = None):
    cr = geom.chunk_rows
    table = w[:geom.n_slots].reshape(geom.n_slots // LANES, LANES)
    kernel = functools.partial(_hashed_gather_kernel, nnz=geom.nnz,
                               rows=_loop_rows(geom, rows))
    parts = pl.pallas_call(
        kernel,
        name="_hashed_gather_kernel",
        grid_spec=_grid_spec(
            ids, geom,
            [pl.BlockSpec(table.shape, lambda s, c, ids: (0, 0))],
            pl.BlockSpec((None, cr, LANES), lambda s, c, ids: (s, c, 0))),
        out_shape=jax.ShapeDtypeStruct(
            (ids.shape[0], geom.block_rows, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(geom, 2)),
        interpret=interpret,
    )(ids, X, table)
    return jnp.sum(parts, axis=-1) + w[geom.n_slots]


def slot_sums_vmem(X, r, ids, geom: HashedGeometry, *,
                   interpret: bool = False, n_acc: int | None = None,
                   rows: int | None = None):
    cr = geom.chunk_rows
    n_acc = geom.scatter_accs if n_acc is None else n_acc
    shape = (geom.n_slots // LANES, LANES)
    rb = jnp.broadcast_to(r[:, :, None], r.shape + (LANES,))
    kernel = functools.partial(_hashed_scatter_kernel, nnz=geom.nnz,
                               rows=_loop_rows(geom, rows))
    accs = pl.pallas_call(
        kernel,
        name="_hashed_scatter_kernel",
        grid_spec=_grid_spec(
            ids, geom,
            [pl.BlockSpec((None, cr, LANES),
                          lambda s, c, ids: (s, c, 0))],
            [pl.BlockSpec(shape, lambda s, c, ids: (0, 0))] * n_acc),
        out_shape=[jax.ShapeDtypeStruct(shape, jnp.float32)] * n_acc,
        compiler_params=pltpu.CompilerParams(
            # the accumulators live across the whole grid
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(geom, 2 * n_acc)),
        interpret=interpret,
    )(ids, X, rb)
    g = functools.reduce(jnp.add, accs).reshape(geom.n_slots)
    tail = jnp.zeros((LANES,), jnp.float32).at[0].set(jnp.sum(r))
    return jnp.concatenate([g, tail])


# ---- what the trainer calls ---------------------------------------------

def margins(X, w, ids, geom: HashedGeometry, *, interpret: bool = False):
    """``f32[n_sampled, block_rows]``: every row's margin under ``w``,
    the rows of the blocks ``ids`` of ``X int32[n_blocks, fields_held,
    block_rows]``, padding rows included."""
    _check(X, geom)
    if geom.pass_form == "vmem":
        return margins_vmem(X, w, ids, geom, interpret=interpret)
    return margins_xla(X, w, ids, geom)


def slot_sums(X, r, ids, geom: HashedGeometry, *, interpret: bool = False):
    """``f32[w_len]``: ``r f32[n_sampled, block_rows]`` added at every
    (row, field) occurrence's slot, once each; the sum of ``r`` where
    the bias is."""
    _check(X, geom)
    if geom.pass_form == "vmem":
        return slot_sums_vmem(X, r, ids, geom, interpret=interpret)
    return slot_sums_xla(X, r, ids, geom)
