"""The Mosaic form of sparse ALS' row gather (``ops/als_sparse.py``).

``gather_rows_resident(table, idx_b, hot_row0)`` returns ``table[idx_b
.reshape(-1)]``, bit for bit, for a table of 128-lane float32 rows whose
tail ``[hot_row0, table rows)`` (the *resident range*: the rows most
slots point at, ``als_sparse.gather_plan`` picks it) fits VMEM.

The table stays in HBM. Grid step 0 copies the resident range into a
VMEM scratch with one DMA; the scratch is held once and lives over the
whole grid. A grid step then takes a chunk of the block's slots: their
indices come through SMEM, the gathered rows leave through the
pipelined output block.

  pass 1  every slot, no branch: the slot's row of the scratch (the
          last row of it where the slot is cold) is loaded by a
          dynamic-row vector load and stored to the slot's row of the
          output, and the slot's position is written at the cursor of
          a list in SMEM, which moves on only where the slot is cold;
  pass 2  every cold slot of the list: a row DMA from the HBM table
          straight over the slot's row of the output block, all on one
          semaphore, ``FETCH`` a trip and many in flight (the list is
          filled to whole trips with its last slot again: a row copied
          twice is the same row);
  pass 3  one wait a trip of pass 2, before the chunk leaves.

"Hot" is one compare, ``row >= hot_row0``, and a branch a slot would
cost more than the cursor does: pass 1 is bound by the scalar slots of
a bundle, two of them for ten operations a slot (the index's load, the
difference, its sign, the cursor, the list's address and store, the
clamp, three addresses), 5 cycles a slot where a taken branch alone
has 4 delay slots. One v5e at the benchmark's block (196 608 slots, a
table of 663 560 rows, ``scripts/step0_als_gather.py``, PR 37): XLA's
gather 9.0 ns a slot whatever the rows; this kernel 3.8 ns a hot or
padding slot and 3.9 to 4.2 more a cold one; copying the resident range
in costs 2.9 ns a row a call (one DMA or sixteen: 175 GB/s), so a row
pays for its place only if a call reads it about once: the heavy class
does, the classes before it do not.

Interpreted on the CPU the kernel runs the same loads, stores and
copies in the same order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
CHUNK_ROWS = 64        # rows of 128 slots a grid step takes (32 KB of
#                        indices in SMEM, a 4 MB output block; 32, 16
#                        and 8 read 1, 3 and 7% slower)
UNROLL = 16            # slots a trip of pass 1 (8: 7% slower)
FETCH = 16             # row copies a trip of pass 2, and rows a wait
#                        (8: 5% slower on a cold block)
VMEM_SLACK = 8 << 20   # beside the resident range and the output blocks


def chunk_rows(block_rows: int, most: int = CHUNK_ROWS) -> int:
    """Largest multiple of a sublane tile that divides ``block_rows``
    and is at most ``most``; 0 where there is none."""
    fits = [r for r in range(SUBLANES, min(block_rows, most) + 1, SUBLANES)
            if block_rows % r == 0]
    return max(fits, default=0)


def _als_gather_kernel(idx_ref, tab_ref, out_ref, res_ref, cold_ref,
                       res_sem, row_sem, *, hot_row0: int):
    slots = idx_ref.shape[0]
    last = res_ref.shape[0] - 1

    @pl.when(pl.program_id(0) == 0)
    def _load():
        cp = pltpu.make_async_copy(
            tab_ref.at[pl.ds(hot_row0, res_ref.shape[0]), :], res_ref,
            res_sem)
        cp.start()
        cp.wait()

    def some(t, n_cold):
        first = pl.multiple_of(t * UNROLL, UNROLL)
        for u in range(UNROLL):
            h = idx_ref[first + u] - hot_row0
            cold_ref[n_cold] = first + u
            n_cold = n_cold - (h >> 31)       # one more where h < 0
            # a cold slot reads the range's last row: as unsigned it
            # lies past every row of it
            row = jnp.minimum(h.astype(jnp.uint32), jnp.uint32(last))
            out_ref[pl.ds(first + u, 1), :] = \
                res_ref[pl.ds(row.astype(jnp.int32), 1), :]
        return n_cold

    n_cold = jax.lax.fori_loop(0, slots // UNROLL, some, jnp.int32(0))
    again = cold_ref[jnp.maximum(n_cold - 1, 0)]
    for u in range(FETCH - 1):
        cold_ref[n_cold + u] = again
    trips = (n_cold + FETCH - 1) // FETCH

    def fetch(g, carry):
        first = pl.multiple_of(g * FETCH, FETCH)
        at = [cold_ref[first + u] for u in range(FETCH)]
        rows = [idx_ref[a] for a in at]
        for a, h in zip(at, rows):
            pltpu.make_async_copy(
                tab_ref.at[pl.ds(h, 1), :], out_ref.at[pl.ds(a, 1), :],
                row_sem).start()
        return carry

    jax.lax.fori_loop(0, trips, fetch, 0)

    def land(g, carry):
        # the semaphore counts what has arrived: a trip's rows a wait
        pltpu.make_async_copy(
            tab_ref.at[pl.ds(0, FETCH), :], out_ref.at[pl.ds(0, FETCH), :],
            row_sem).wait()
        return carry

    jax.lax.fori_loop(0, trips, land, 0)


# jitted so that a fit's two dozen call sites (a class each, a half
# each) trace and lower the kernel once a table, not once a site: 7 s of
# a run's set-up at the benchmark's shape
@functools.partial(jax.jit,
                   static_argnames=("hot_row0", "interpret", "chunk"))
def gather_rows_resident(table, idx_b, hot_row0: int, *,
                         interpret: bool = False,
                         chunk: int | None = None):
    """``table[idx_b.reshape(-1)]`` for ``table`` float32 ``(rows,
    128)`` and ``idx_b`` int32 ``(block rows, 128)``, every index in
    bounds, with the rows from ``hot_row0`` on read out of VMEM."""
    n_rows, width = table.shape
    if width != LANES or idx_b.ndim != 2 or idx_b.shape[1] != LANES:
        raise ValueError(f"table {table.shape} and indices {idx_b.shape} "
                         f"are not rows of {LANES} lanes")
    cr = chunk_rows(idx_b.shape[0]) if chunk is None else chunk
    if cr < SUBLANES or idx_b.shape[0] % cr or not 0 <= hot_row0 < n_rows:
        raise ValueError(f"no chunk of {cr} rows in a block of "
                         f"{idx_b.shape[0]}, or no resident row from "
                         f"{hot_row0} of {n_rows}")
    n_res = n_rows - hot_row0
    slots = cr * LANES
    return pl.pallas_call(
        functools.partial(_als_gather_kernel, hot_row0=hot_row0),
        name="_als_gather_kernel",
        grid=(idx_b.shape[0] // cr,),
        in_specs=[pl.BlockSpec((slots,), lambda c: (c,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((slots, LANES), lambda c: (c, 0)),
        out_shape=jax.ShapeDtypeStruct((idx_b.size, LANES), table.dtype),
        scratch_shapes=[pltpu.VMEM((n_res, LANES), table.dtype),
                        pltpu.SMEM((slots + FETCH,), jnp.int32),
                        pltpu.SemaphoreType.DMA(()),
                        pltpu.SemaphoreType.DMA(())],
        compiler_params=pltpu.CompilerParams(
            # the resident range lives across the whole grid
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * (n_res + 2 * slots) * LANES + VMEM_SLACK,
            # every index is in bounds, as XLA's form is promised
            disable_bounds_checks=True),
        interpret=interpret,
    )(idx_b.reshape(-1), table)
