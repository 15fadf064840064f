"""The two passes over rows of (feature, value) pairs by address, the
whole model vector resident in VMEM: ``ops/pairs.py``'s ``vmem`` form.

``ops/pairs.py`` says what a block holds and what the passes compute;
``pairs.pass_form`` says where this form runs (a TPU whose VMEM holds
the vector: 66.4 MB at webspam's 16.6M features, one copy, single
buffered). Nothing of ``ops/pallas_hashed.py`` is shared: a field-major
block of one-hot rows and a block of valued vectors conflict in layout.

A call of either kernel runs over every sampled block it is given (grid:
sampled block x chunk of ``CHUNK_VECTORS`` vectors). A chunk's ids and values
come through SMEM straight from the table (rows ``[0, V)`` and ``[V,
2 V)`` of the block the scalar-prefetched ``ids`` name), so no sampled
block is copied first; ``used`` (a scalar a block, prefetched too) says
how many of a block's vectors belong to a row, and the rest are not
read (3.5% of the slots at webspam's shape).

gather   ``w`` (brought to whole ``(8, 128)`` tiles, ``f32[129760, 128]``
         at webspam's width, and left in HBM by its ``BlockSpec``) is
         copied once, at the first grid step, into a VMEM scratch that
         stays for the whole call: every DMA is a whole array's. A pair loads row
         ``h >> 7``, keeps lane ``h & 127`` by a mask and multiplies by
         its value (the table's int32 word splat over a vector and
         bitcast there: Mosaic has no scalar bitcast); a trip's pairs
         add into ``GATHER_SUMS`` partial vectors, and one ``f32[128]``
         a vector goes out. Its lanes' sum is the vector's share of its
         row's margin: ``pairs.margins`` adds them up.
scatter  the same loop the other way into ONE accumulator scratch of
         the vector's shape, zeroed at the first grid step and copied
         to HBM at the last: a pair adds ``v * r_row`` (its value as in
         the gather, its row's residual a float32 a vector through
         SMEM) at lane ``h & 127`` of row ``h >> 7``. A load waits 7
         bundles for the last store to its allocation (a
         read-modify-write a pair is a chain of 10 bundles a link), so
         a piece's four rows are loaded before any of them is stored; a
         later pair of the piece that lands in an earlier one's row
         takes that one's addend along, so the last store to a row
         holds every addend once (on the chip at the cell's width and
         skew: sums of small whole numbers equal a float64 CSR sum's to
         the bit, ``tests_tpu``). 5.2 bundles a pair by the static
         schedule, 3.75 ns a pair on the chip (PR 56). A second
         accumulator would halve the chain and does not fit beside the
         first at webspam's width; none is built until a cell's vector
         leaves room for it.

A slot's addends arrive one after another in float32, so a call's share
of a slot that most rows hold is a serial sum: the trainer splits a
step's blocks over a few calls and adds their sums
(``ssgd_pairs.STEP_BLOCKS``).

The scalar slots bound the gather (two SMEM loads, a shift, an address
and two splats a pair), so a pair's lane is a constant: a chunk is
walked a segment of ``TRIP_PAIRS`` lanes at a time over all its
vectors (``_each_pair``), a trip of the loop one vector's pairs of the
segment and one basic block; what is traced is ``GATHER_SUMS`` pairs,
written out again at lowering (``pallas_hashed._each_row``'s device).
``scripts/step0_pairs.py`` reads each form on the chip and, with
``--bundles``, its static schedule without one. A padding slot inside a
row's last vector (id 0, value 0.0) reads ``w[0]`` and adds nothing, as
in the ``xla`` form. Interpreted on the CPU the kernels run the same
loads, masks, products and adds in the same order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpu_distalg.ops import pairs
from tpu_distalg.ops.pallas_api import pl, pltpu
from tpu_distalg.telemetry import events as tevents
from tpu_distalg.telemetry import names

LANES = pairs.LANES
CHUNK_VECTORS = 32     # vectors a grid step brings through SMEM: 16 KB of
#                        ids and as much of values
TRIP_PAIRS = 32        # pairs a trip of the inner loop, one basic block
GATHER_SUMS = 4        # partial sums a vector's products are added into,
#                        and the pairs of a piece: what of a trip is
#                        traced, and what the scatter loads before it
#                        stores
ZERO_ROWS = 32         # rows of an accumulator a trip of the loop that
#                        zeroes or folds it covers


def _table_rows(geom: pairs.PairsGeometry) -> int:
    """Rows of 128 lanes of the model vector in VMEM: whole tiles, and
    whole trips of the loop that zeroes an accumulator (129 760 at
    webspam's 129 759)."""
    return pairs._round_up(geom.w_len // LANES, ZERO_ROWS)


def _chunk(geom: pairs.PairsGeometry) -> int:
    cv = min(CHUNK_VECTORS, geom.vectors)
    if geom.vectors % cv:
        raise ValueError(f"{geom.vectors} vectors a block are not whole "
                         f"chunks of {cv}")
    return cv


def _each_pair(n_vectors, trip: int, pairs_of, start, done) -> None:
    """``carry = pairs_of(j, lanes, carry)`` for the pairs at ``lanes``
    (``GATHER_SUMS`` neighbouring ones, a *piece*) of each of the first
    ``n_vectors`` vectors ``j`` of a chunk (a number the kernel reads:
    a block's tail holds no row). The chunk is walked a *segment* of
    ``trip`` lanes at a time, every vector's pairs of that segment
    before the next segment's, so that a pair's lane is a constant of
    its trip (a lane the loop computed cost five scalar operations a
    pair for its SMEM address alone; the scalar slots are the bound:
    ``scripts/step0_pairs.py --bundles``). A trip is one basic block:
    from ``start(j)``, a piece traced and written out again at
    lowering, then ``done(j, segment, carry)``."""
    per = GATHER_SUMS
    for q in range(LANES // trip):
        def vector(j, _):
            def piece(v, carry):
                return pairs_of(
                    j, [q * trip + (v * per + u) for u in range(per)], carry)

            done(j, q, jax.lax.fori_loop(0, trip // per, piece, start(j),
                                         unroll=True))
            return 0

        jax.lax.fori_loop(0, n_vectors, vector, 0)


def _lane_of(h):
    """Where a vector's lanes are slot ``h``'s: the mask that keeps lane
    ``h & 127`` (the ``and`` on the vector side: a VALU slot is free
    where a scalar slot is not)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    return lane == (jnp.full((1, LANES), h, jnp.int32) & (LANES - 1))


def _first_step():
    return (pl.program_id(0) == 0) & (pl.program_id(1) == 0)


def _used_here(used_ref, chunk: int):
    """The vectors of this grid step's chunk that belong to a row: the
    block's first ``used[s]`` do, the rest hold no pair."""
    return jnp.clip(used_ref[pl.program_id(0)] - pl.program_id(1) * chunk,
                    0, chunk)


def _value(val_ref, j, p):
    """A pair's float32 value over a vector: the table's int32 word
    splat and bitcast there (Mosaic has no scalar bitcast)."""
    return jax.lax.bitcast_convert_type(
        jnp.full((1, LANES), val_ref[j, p], jnp.int32), jnp.float32)


def _pairs_gather_kernel(ids_ref, used_ref, idx_ref, val_ref, w_hbm, out_ref,
                         w_ref, sem, *, trip: int):
    """One chunk of one sampled block: ``out[j, :]`` holds vector
    ``j``'s products ``v_p * w[h_p]`` in the lanes their slots have in
    the table, slots of one lane added up; zeros for a vector past the
    block's last row."""
    del ids_ref                         # the index maps read it
    n = _used_here(used_ref, idx_ref.shape[0])

    @pl.when(_first_step())
    def _load():
        copy = pltpu.make_async_copy(w_hbm, w_ref, sem)
        copy.start()
        copy.wait()

    zero = jnp.zeros((1, LANES), jnp.float32)

    def pairs_of(j, lanes, sums):
        out = []
        for p, acc in zip(lanes, sums):
            h = idx_ref[j, p]
            out.append(acc + jnp.where(
                _lane_of(h), w_ref[pl.ds(h >> 7, 1), :], 0.0)
                * _value(val_ref, j, p))
        return tuple(out)

    def done(j, segment, sums):
        total = (sums[0] + sums[1]) + (sums[2] + sums[3])
        if segment:
            total = out_ref[pl.ds(j, 1), :] + total
        out_ref[pl.ds(j, 1), :] = total

    @pl.when(n < idx_ref.shape[0])
    def _rest():
        out_ref[...] = jnp.zeros_like(out_ref)

    _each_pair(n, trip, pairs_of, lambda j: (zero,) * GATHER_SUMS, done)


def _pairs_scatter_kernel(ids_ref, used_ref, idx_ref, val_ref, back_ref,
                          out_hbm, acc_ref, sem, *, trip: int):
    """One chunk of one sampled block into the accumulator, which stays
    in VMEM over the whole grid: a pair's value times its row's residual
    (``back``: a float32 a vector of the block) at lane ``h & 127`` of
    row ``h >> 7``. The sums go out at the last grid step."""
    del ids_ref

    @pl.when(_first_step())
    def _zero():
        def some(i, _):
            at = pl.ds(pl.multiple_of(i * ZERO_ROWS, ZERO_ROWS), ZERO_ROWS)
            acc_ref[at, :] = jnp.zeros((ZERO_ROWS, LANES), jnp.float32)
            return 0

        jax.lax.fori_loop(0, acc_ref.shape[0] // ZERO_ROWS, some, 0)

    first = pl.program_id(1) * idx_ref.shape[0]

    def residual(j):
        at = first + j                  # the vector's number in its block
        return jnp.full((1, LANES), back_ref[at >> 7, at & (LANES - 1)],
                        jnp.float32)

    def pairs_of(j, lanes, r):
        # a piece's rows are loaded before any of them is stored (the
        # compiler holds a load 7 bundles behind the last store to its
        # allocation), so a later pair of the piece that lands in an
        # earlier one's row takes that one's addend with it: the last
        # store to a row then holds every addend of the piece once
        rows, adds = [], []
        for p in lanes:
            h = idx_ref[j, p]
            rows.append(h >> 7)
            adds.append(jnp.where(_lane_of(h),
                                  _value(val_ref, j, p) * r, 0.0))
        olds = [acc_ref[pl.ds(row, 1), :] for row in rows]
        for n, row in enumerate(rows):
            new = olds[n] + adds[n]
            for m in range(n):
                new = new + jnp.where(rows[m] == row, adds[m], 0.0)
            acc_ref[pl.ds(row, 1), :] = new
        return r

    _each_pair(_used_here(used_ref, idx_ref.shape[0]), trip, pairs_of,
               residual, lambda j, segment, r: None)

    @pl.when((pl.program_id(0) == pl.num_programs(0) - 1)
             & (pl.program_id(1) == pl.num_programs(1) - 1))
    def _store():
        copy = pltpu.make_async_copy(acc_ref, out_hbm, sem)
        copy.start()
        copy.wait()


def _call(kernel, geom: pairs.PairsGeometry, ids):
    """A kernel bound to its trip; what the call runs is said once,
    when it is traced (``tda report``: ``pairs pass``)."""
    trip = TRIP_PAIRS
    if LANES % trip or trip % GATHER_SUMS:
        raise ValueError(f"a trip of {trip} pairs does not divide a "
                         f"vector of {LANES} into pieces of {GATHER_SUMS}")
    tevents.emit("ssgd:pairs_pass", kernel=kernel.__name__, form="vmem",
                 vmem_bytes=pairs.vmem_bytes(geom.w_len), trip_pairs=trip,
                 blocks=int(ids.shape[0]), chunk_vectors=_chunk(geom))
    return functools.partial(kernel, trip=trip)


def _grid_spec(ids, geom: pairs.PairsGeometry, more_in, out_specs):
    """The grid (sampled block, chunk of vectors): the chunk's ids from
    rows ``[0, V)`` of the table's block ``ids[s]`` and its values, bit
    for bit, from rows ``[V, 2 V)``, both through SMEM; then
    ``more_in``. The scratch is the model vector's one copy in VMEM
    (whole tiles) and its DMA's semaphore."""
    cv = _chunk(geom)
    per_block = geom.vectors // cv

    def smem(first):
        return pl.BlockSpec((None, cv, LANES),
                            lambda s, c, ids, used: (ids[s], first + c, 0),
                            memory_space=pltpu.SMEM)

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # ids, used
        grid=(ids.shape[0], per_block),
        in_specs=[smem(0), smem(per_block)] + more_in,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((_table_rows(geom), LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA(())])


def _params(geom: pairs.PairsGeometry) -> dict:
    """What both calls share. Off the TPU (the tests steer the CPU
    here) the kernels are interpreted."""
    return dict(
        compiler_params=pltpu.CompilerParams(
            # the scratch lives across the whole grid
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=pairs.vmem_bytes(geom.w_len)),
        interpret=not geom.on_tpu)


def vector_products(X, w, ids, used, geom: pairs.PairsGeometry):
    """``f32[n_sampled, V, 128]``: for each vector of the sampled blocks
    its pairs' products ``v * w[h]``, in the lanes of their slots.
    ``used`` (``int32[n_sampled]``, ``pairs.used_vectors``): a block's
    vectors from there on hold no pair, are not read and give zeros."""
    rows = _table_rows(geom)
    with jax.named_scope(names.SSGD_TABLE_HBM):
        # whole (8, 128) tiles: every copy in or out is a whole array's
        table = jnp.pad(w, (0, rows * LANES - geom.w_len)).reshape(
            rows, LANES)
    return pl.pallas_call(
        _call(_pairs_gather_kernel, geom, ids),
        name="_pairs_gather_kernel",
        grid_spec=_grid_spec(
            ids, geom, [pl.BlockSpec(memory_space=pl.ANY)],
            pl.BlockSpec((None, _chunk(geom), LANES),
                         lambda s, c, ids, used: (s, c, 0))),
        out_shape=jax.ShapeDtypeStruct(
            (ids.shape[0], geom.vectors, LANES), jnp.float32),
        **_params(geom),
    )(ids, used, X, X, table)


def slot_sums(X, back, ids, used, geom: pairs.PairsGeometry):
    """``f32[w_len]``: every pair's value times ``back`` (``f32[n_sampled,
    V]``, its vector's row's residual) added up by the pairs' ids;
    ``used`` as in :func:`vector_products`."""
    held = geom.vector_rows * LANES
    back = jnp.pad(back, ((0, 0), (0, held - geom.vectors))).reshape(
        ids.shape[0], geom.vector_rows, LANES)
    sums = pl.pallas_call(
        _call(_pairs_scatter_kernel, geom, ids),
        name="_pairs_scatter_kernel",
        grid_spec=_grid_spec(
            ids, geom,
            [pl.BlockSpec((None, geom.vector_rows, LANES),
                          lambda s, c, ids, used: (s, 0, 0),
                          memory_space=pltpu.SMEM)],
            pl.BlockSpec(memory_space=pl.ANY)),
        out_shape=jax.ShapeDtypeStruct((_table_rows(geom), LANES),
                                       jnp.float32),
        **_params(geom),
    )(ids, used, X, X, back)
    with jax.named_scope(names.SSGD_TABLE_HBM):
        return sums.reshape(-1)[:geom.w_len]
