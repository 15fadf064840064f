"""The Mosaic forms of sparse ALS' row gather and of its per-owner
solve (``ops/als_sparse.py``); the solve's is the file's second half.

``gather_rows_resident(table, idx_b, val_t, row_b, cold_b, n_cold,
hot_row0, lane)`` returns ``table[idx_b.reshape(-1)]``, bit for bit,
with lane ``lane`` of every row the slot's value (its rating), for a
table of 128-lane float32 rows whose tail ``[hot_row0, table rows)``
(the *resident range*: the rows most slots point at,
``als_sparse.gather_plan`` picks it) fits VMEM.

What of a slot never changes through a run is made once, by the loader
(``als_sparse.gather_lists``), and handed in beside the pack's indices:
the slot's row of the resident range (``row``: the index re-based on
the range, the range's last row where the slot is *cold*, which as
unsigned lies past every row of it), a chunk's cold slots' positions in
slot order, two 16-bit positions a word and filled to the chunk's end
with the last one again, the chunk's count of them, and the values a
group of eight slots down the sublanes.

The table stays in HBM. Grid step 0 copies the resident range into a
VMEM scratch with one DMA; the scratch is held once and lives over the
whole grid. A grid step then takes a chunk of the block's slots: their
indices, resident rows and cold list come through SMEM, the counts by
scalar prefetch, the values as a VMEM block, and the gathered rows
leave through the pipelined output block.

  pass 1  every slot, no branch: the slot's resident row is loaded from
          SMEM, that row of the scratch by a dynamic-row vector load,
          and stored to the slot's row of the output;
  pass 2  every cold slot of the list: its index, and a row DMA from
          that row of the HBM table straight over the slot's row of the
          output block, all on one semaphore, ``FETCH`` a trip and many
          in flight (a row copied twice is the same row);
  pass 3  one wait a trip of pass 2;
  pass 4  the values' lane, 1024 slots a trip: a tile of the values is
          128 groups of eight slots, a group down the sublanes at its
          own lane; a lane rotation brings a group to lane ``lane`` and
          a masked store writes that lane of the group's eight rows.

Neither of the first two passes computes a row: a slot's two row
addresses are loads. Pass 1 is bound by the scalar slots of a bundle,
two of them for three operations a slot (the resident row's address and
load, the row's address; the store's address is the trip's plus a
constant): 51 bundles for 32 slots by the static schedule, where the
kernel that clamped the re-based index itself took 69 (until PR 53) and
the one that kept the list too 84 for 16 (until PR 46;
``scripts/step0_als_gather.py --bundles``). Pass 2 is bound the same
way, seven operations and the DMA's own bundle a cold slot (75 bundles
for 16; 81 while it added ``hot_row0`` back to a re-based index), and
pass 4 by the three units that rotate, eight cycles a rotation (451
bundles for 1024 slots). ``PERF.md`` section 6, PRs 46 and 53, has the
chip's readings beside the schedule's and the forms that were dropped
(the cold slots' table rows packed at offsets and copied into SMEM by
hand, which holds 1.8 GB less and whose pass 2 takes 83 bundles; the
lane written in pass 1 and again over the list; a transpose in the
kernel). One v5e at the benchmark's block (196 608 slots, a table of
663 560 rows): 0.600 ms a block at the cell's mix of slots (0.688 from
the users' table) where the kernel that clamped read 0.672 (0.768), all
hot 0.436, all cold 1.189 (PR 53); XLA's gather 9.0 ns a slot whatever
the rows; copying
the resident range in costs 2.9 ns a row a call (one DMA or sixteen:
175 GB/s), so a row pays for its place only if a call reads it about
once: the heavy class does, the classes before it do not (PR 37).

Interpreted on the CPU the kernel runs the same loads, stores and
copies in the same order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpu_distalg.ops.pallas_api import pl, pltpu

LANES = 128
SUBLANES = 8
CHUNK_ROWS = 64        # rows of 128 slots a grid step takes (32 KB of
#                        indices in SMEM, a 4 MB output block; 32, 16
#                        and 8 read 1, 3 and 7% slower, PR 37)
UNROLL = 32            # slots a trip of pass 1 (16: 1.6% slower, PR 46; 8:
#                        7% more, PR 37)
FETCH = 16             # row copies a trip of pass 2, and rows a wait
#                        (8: 5% slower on a cold block, PR 37)
TILE = SUBLANES * LANES  # slots a trip of pass 4: a tile of the values
VMEM_SLACK = 8 << 20   # beside the resident range and the output blocks


def chunk_rows(block_rows: int, most: int = CHUNK_ROWS) -> int:
    """Largest multiple of a sublane tile that divides ``block_rows``
    and is at most ``most``; 0 where there is none."""
    fits = [r for r in range(SUBLANES, min(block_rows, most) + 1, SUBLANES)
            if block_rows % r == 0]
    return max(fits, default=0)


def _als_gather_kernel(n_cold_ref, idx_ref, row_ref, cold_ref, val_ref,
                       tab_ref, out_ref, res_ref, res_sem, row_sem, *,
                       hot_row0: int, lane: int):
    slots = row_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _load():
        cp = pltpu.make_async_copy(
            tab_ref.at[pl.ds(hot_row0, res_ref.shape[0]), :], res_ref,
            res_sem)
        cp.start()
        cp.wait()

    def some(t, carry):
        first = pl.multiple_of(t * UNROLL, UNROLL)
        rows = out_ref.at[pl.ds(first, UNROLL), :]
        for u in range(UNROLL):
            # (a cold slot's is the range's last row: the loader's clamp)
            rows[pl.ds(u, 1), :] = res_ref[pl.ds(row_ref[first + u], 1), :]
        return carry

    jax.lax.fori_loop(0, slots // UNROLL, some, 0)
    trips = (n_cold_ref[pl.program_id(0)] + FETCH - 1) // FETCH

    def fetch(g, carry):
        first = pl.multiple_of(g * (FETCH // 2), FETCH // 2)
        words = [cold_ref[first + u] for u in range(FETCH // 2)]
        at = [a for w in words for a in (w & 0xFFFF, w >> 16)]
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
    at_lane = jax.lax.broadcasted_iota(jnp.int32, (TILE, LANES), 1) == lane

    def values(q, carry):
        vals = val_ref[pl.ds(pl.multiple_of(q * SUBLANES, SUBLANES),
                             SUBLANES), :]
        # (one store a tile as it is written, a masked store a group as
        # it is compiled: a group is a vector of the tile's)
        groups = [pltpu.roll(vals, (lane - m) % LANES, axis=1)
                  if (lane - m) % LANES else vals for m in range(LANES)]
        pltpu.store(out_ref.at[pl.ds(pl.multiple_of(q * TILE, TILE), TILE), :],
                    jnp.concatenate(groups, axis=0), mask=at_lane)
        return carry

    jax.lax.fori_loop(0, slots // TILE, values, 0)


# jitted so that a fit's two dozen call sites (a class each, a half
# each) trace and lower the kernel once a table, not once a site: 7 s of
# a run's set-up at the benchmark's shape
@functools.partial(jax.jit,
                   static_argnames=("hot_row0", "lane", "interpret"))
def gather_rows_resident(table, idx_b, val_t, row_b, cold_b, n_cold,
                         hot_row0: int, lane: int, *,
                         interpret: bool = False):
    """``table[idx_b.reshape(-1)]`` with lane ``lane`` of every row the
    slot's value, for ``table`` float32 ``(rows, 128)``, a block of the
    pack's indices ``idx_b`` int32 ``(block rows, 128)`` (every index in
    bounds) and what ``als_sparse.gather_lists`` made of it: ``val_t``
    float32 the same shape (the values in tiles of 1024 slots, eight
    slots down the sublanes), ``row_b`` int32 the same shape (a slot's
    row of the resident range, its last row where the slot is cold),
    ``cold_b`` int32 ``(slots / 2,)`` and ``n_cold`` int32 ``(chunks,)``
    the chunks' cold lists and counts. The rows from ``hot_row0`` on are
    read out of VMEM."""
    n_rows, width = table.shape
    if width != LANES or idx_b.ndim != 2 or idx_b.shape[1] != LANES \
            or row_b.shape != idx_b.shape:
        raise ValueError(f"table {table.shape}, indices {idx_b.shape} and "
                         f"resident rows {row_b.shape} are not rows of "
                         f"{LANES} lanes")
    cr = chunk_rows(idx_b.shape[0])    # as the lists were made
    if cr < SUBLANES or not 0 <= hot_row0 < n_rows:
        raise ValueError(f"no chunk of {cr} rows in a block of "
                         f"{idx_b.shape[0]}, or no resident row from "
                         f"{hot_row0} of {n_rows}")
    n_res = n_rows - hot_row0
    slots = cr * LANES

    def smem(n):
        return pl.BlockSpec((n,), lambda c, n_cold: (c,),
                            memory_space=pltpu.SMEM)

    return pl.pallas_call(
        functools.partial(_als_gather_kernel, hot_row0=hot_row0, lane=lane),
        name="_als_gather_kernel",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(idx_b.shape[0] // cr,),
            in_specs=[smem(slots), smem(slots), smem(slots // 2),
                      pl.BlockSpec((cr, LANES), lambda c, n_cold: (c, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((slots, LANES),
                                   lambda c, n_cold: (c, 0)),
            scratch_shapes=[pltpu.VMEM((n_res, LANES), table.dtype),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((idx_b.size, LANES), table.dtype),
        compiler_params=pltpu.CompilerParams(
            # the resident range lives across the whole grid
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * (n_res + 2 * slots) * LANES + VMEM_SLACK,
            # every index is in bounds, as XLA's form is promised
            disable_bounds_checks=True),
        interpret=interpret,
    )(n_cold, idx_b.reshape(-1), row_b.reshape(-1), cold_b, val_t, table)


# ------------------------------------------------------------ the solve
#
# ``solve_lanes(Ap, k, lam)`` solves ``(A_u + lam n_u I) x = b_u`` for
# every owner of a batch of extended Gramians as the product makes them,
# owner-major ``Ap`` ``(batch, width, width)`` (``A_u`` rows and columns
# under ``k``, ``b_u`` column ``k``, ``n_u`` entry ``(k + 1, k + 1)``; an
# owner of no rating solves the identity), by the steps of
# ``als_sparse.cholesky_solve_lanes`` in the same order: right-looking
# Cholesky in panels of 8 columns, the right-hand side as one more row,
# the backward substitution. It hands back the right-hand sides too, a
# system a lane as it read them (XLA, asked for that column beside
# ``x``, copies the whole batch into another layout to slice it).
#
# A grid step takes a tile of 128 owners: the pipeline copies their
# first ``ext`` rows ``Ap[tile, :ext, :]`` in (``ext`` = ``k + 2`` in
# whole sublane tiles; 6.8 MB at rank 100, 53 KB an owner in one piece,
# read once) and the kernel turns them along the lanes itself, a column
# at a time: row ``c`` of the 128 owners is one sublane-strided load
# ``(128 owners, 128 entries)``, its transpose ``(128 entries, 128
# owners)`` is column ``c`` of 128 systems, a system a lane (entry for
# entry what a block of the batch held along the lanes gave: the
# unknowns are that form's bit for bit). The ridge and the ``k`` mask
# are made on the way into ``m_ref``, column ``c`` a leading index and
# rows down the sublanes, so a vector is 8 rows of one column of 128
# systems. Every row tile of a column is stored (13 stores, half of
# them above the diagonal, where nothing reads: cheaper than a loop of
# its own); eight columns a trip keep the three transpose units busy
# (540 bundles a trip, 67 a column, where one column alone waits 290).
# A panel's eight columns are finished a row tile at a time (the
# diagonal block first, which gives the 28 multipliers and 8 pivots the
# tiles below reuse) and go back to ``m_ref`` and into ``p_ref``, an
# allocation of its own at a static index: the trailing update reads the
# panel only there, so no load of it waits on a store to the matrix.
# That update takes a tile of eight columns by two row tiles a trip, a
# column's multiplier spread down the sublanes once for both. The loops
# over panels, column tiles and row tiles are rolled with dynamic
# bounds; what a trip does is unrolled.
#
# One v5e at rank 100 (``scripts/step0_als_solve.py``; PR 50, PR 41):
# 363.3 bundles a system by the static schedule, 15% of them the turn
# (7020 a tile where a block that came along the lanes took 2444) and
# half the trailing update; **1.62 ms a batch of 6144** (0.264 us a
# system, 0.14 ms over the 1.48 of the form that was handed the batch
# along the lanes, which with the 806 MB copy in HBM it needed read
# 2.68) where XLA's form takes 14.5, the tile's copy (0.54 ms a batch
# alone) hidden behind the arithmetic; tiles of 256 and 512 systems read
# the same, and a tile of 1024 with a matrix entry a whole vector the
# same again before its view of ``Ap`` is paid for (1.2 ms). The
# error's ``x^T A x`` taken here too, from the block while it is still
# in VMEM, is a second turn of every column: +0.23 ms on every batch,
# where XLA's sum over the owner-major batch costs 0.54 ms on the item
# half's batches alone (the user half drops its error): not shipped.

SOLVE_TILE = LANES     # systems a grid step: one a lane


def _solve_sizes(k: int) -> tuple[int, int]:
    """``(n, ext)`` in whole sublane tiles: the rows and columns that
    are factored (``k`` and the identity's padding), and an owner's rows
    read of ``Ap`` (``b_u`` is their entries ``k``, ``n_u`` entry ``k +
    1`` of row ``k + 1``)."""
    return -(-k // SUBLANES) * SUBLANES, -(-(k + 2) // SUBLANES) * SUBLANES


def solve_tile_bytes(k: int) -> int:
    """VMEM a grid step of :func:`solve_lanes` holds at rank ``k``: the
    tile's owners' rows of ``Ap`` (``ext`` of them, whole vectors wide)
    and the result's twice (the pipeline's two buffers), the matrix that
    is factored in place, right-hand side and all, and its panel."""
    n, ext = _solve_sizes(k)
    width = -(-ext // LANES) * LANES
    return 4 * SOLVE_TILE * (2 * (ext * width + 2 * n)
                             + (n + SUBLANES) * (n + 2 * SUBLANES))


def _rows8(x):
    return jnp.broadcast_to(x, (SUBLANES, LANES))


def _tile(i):
    return pl.ds(pl.multiple_of(i * SUBLANES, SUBLANES), SUBLANES)


def _column_of(a_ref):
    """``column(c)``: row ``c`` of every owner of the block ``a_ref``
    ``(owners, ext, width)`` as ``(width, owners)``, a sublane-strided
    load and one transpose."""
    systems, ext, width = a_ref.shape
    rows_ref = a_ref.reshape(systems * ext, width)
    return lambda c: rows_ref[pl.ds(c, systems, stride=ext), :].T


def _solve_build(a_ref, b_ref, m_ref, *, k: int, lam: float):
    """The matrix of a tile's systems out of their owners' rows of
    ``Ap``, ``a_ref`` ``(128 owners, ext, width)``: column ``c`` a
    leading index of ``m_ref``, rows down the sublanes, a system a lane,
    the right-hand side as row ``n``, the ridge on the diagonal and
    zeros (a one on the diagonal) from ``k`` on. Row ``c`` of the 128
    owners is one sublane-strided load ``(owners, width)``, and its
    transpose ``(width, owners)`` is what the lanes form read as
    ``Ap[c, :, tile]``: entry for entry the same floats. Every row tile
    is stored (what lies above the diagonal block is never read: no
    step mixes sublanes but by a row it names)."""
    n = m_ref.shape[0]
    w = SUBLANES
    f32 = jnp.float32
    sub = jax.lax.broadcasted_iota(jnp.int32, (w, LANES), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (n, LANES), 0)
    column = _column_of(a_ref)
    cnt = column(k + 1)[k + 1:k + 2, :]
    ridge = jnp.where(cnt > 0, f32(lam) * cnt, f32(1.0))

    def build(ct, carry):
        c0 = pl.multiple_of(ct * w, w)
        for u in range(w):
            c = c0 + u
            live = c < k
            col = column(c)
            v = jnp.where((row < k) & live, col[:n, :], f32(0.0))
            diag = jnp.where(row == c, jnp.where(live, ridge, f32(1.0)),
                             f32(0.0))
            # (the sum over the diagonal block alone, as the lanes form
            # made it: elsewhere a zero keeps its sign)
            m_ref[c, pl.ds(0, n), :] = jnp.where(
                (row >= c0) & (row < c0 + w), v + diag, v)
            b = jnp.where(live, _rows8(col[k:k + 1, :]), f32(0.0))
            m_ref[c, pl.ds(n, w), :] = jnp.where(sub == 0, b, f32(0.0))
            rhs = b if u == 0 else jnp.where(sub == u, b, rhs)
        b_ref[_tile(ct), :] = rhs
        return carry

    jax.lax.fori_loop(0, n // w, build, 0)


def _solve_factor(x_ref, m_ref, p_ref):
    """``m_ref`` as :func:`_solve_build` leaves it, factored in place
    (``p_ref`` the finished panel), and the systems' unknowns into
    ``x_ref`` ``(n, 128)``."""
    n = x_ref.shape[0]
    w = SUBLANES
    n_tiles = n // w            # column tiles, and the matrix's row tiles
    row_tiles = n_tiles + 1     # the right-hand side rides in one more
    f32 = jnp.float32
    sub = jax.lax.broadcasted_iota(jnp.int32, (w, LANES), 0)

    def factor(p, carry):
        q = pl.multiple_of(p * w, w)

        # a panel's eight columns, a row tile at a time from the
        # diagonal block down (the right-hand side's row with them,
        # which is the forward substitution): finished, a column goes
        # back to the matrix for the backward substitution and into
        # ``p_ref``, an allocation of its own at a static index, for
        # the trailing update
        def columns(tiles, mult, piv):
            Xs = [[m_ref[q + t, _tile(i), :] for t in range(w)]
                  for i in tiles]
            for X in Xs:
                for j in range(w):
                    s = X[j]
                    for t in range(j):
                        s = s - X[t] * mult[t][j]
                    if piv[j] is None:      # the diagonal block: row j
                        piv[j] = _rows8(jnp.sqrt(s[j:j + 1, :]))
                    X[j] = s / piv[j]
                    for u in range(j + 1, w):
                        if mult[j][u] is None:
                            mult[j][u] = _rows8(X[j][u:u + 1, :])
            for i, X in zip(tiles, Xs):
                for t in range(w):
                    m_ref[q + t, _tile(i), :] = X[t]
                    p_ref[t, _tile(i), :] = X[t]

        mult = [[None] * w for _ in range(w)]
        piv = [None] * w
        columns([p], mult, piv)

        # (two row tiles a trip, each a chain of eight columns; an odd
        # count's last trip runs into the spare tile under the
        # right-hand side's, which nothing reads)
        def below(g, carry):
            columns([p + 1 + 2 * g, p + 2 + 2 * g], mult, piv)
            return carry

        jax.lax.fori_loop(0, (row_tiles - p) // 2, below, 0)

        # the trailing matrix less the panel's outer product: a tile of
        # eight columns at a time, two row tiles a trip from the bottom
        # up (a row of a column is a sublane of its vector, so a
        # column's multiplier is spread down the sublanes once for both
        # tiles); an odd count's first trip also takes the tile above
        # the diagonal block, which nothing reads
        def trailing(jt, carry):
            c0 = pl.multiple_of(jt * w, w)
            first = jt - (row_tiles - jt) % 2

            def two(g, carry):
                i0 = first + 2 * g
                upd = [[None] * w, [None] * w]
                for t in range(w):
                    C = p_ref[t, _tile(jt), :]
                    X = [p_ref[t, _tile(i0 + h), :] for h in range(2)]
                    for u in range(w):
                        c = _rows8(C[u:u + 1, :])
                        for h in range(2):
                            term = X[h] * c
                            upd[h][u] = term if t == 0 \
                                else upd[h][u] + term
                M = [[m_ref[c0 + u, _tile(i0 + h), :] - upd[h][u]
                      for u in range(w)] for h in range(2)]
                for h in range(2):
                    for u in range(w):
                        m_ref[c0 + u, _tile(i0 + h), :] = M[h][u]
                return carry

            jax.lax.fori_loop(0, (row_tiles - first) // 2, two, 0)
            return carry

        jax.lax.fori_loop(p + 1, n_tiles, trailing, 0)
        return carry

    jax.lax.fori_loop(0, n_tiles, factor, 0)

    # backward: a panel's eight unknowns from those below it
    def backward(ip, carry):
        p = n_tiles - 1 - ip
        q = pl.multiple_of(p * w, w)

        def dots(i, acc):
            x = x_ref[_tile(i), :]
            return tuple(a + m_ref[q + j, _tile(i), :] * x
                         for j, a in enumerate(acc))

        acc = jax.lax.fori_loop(
            p + 1, n_tiles, dots,
            tuple(jnp.zeros((w, LANES), f32) for _ in range(w)))
        xp = [None] * w
        out = jnp.zeros((w, LANES), f32)
        for j in reversed(range(w)):
            s = m_ref[q + j, pl.ds(n, 1), :] \
                - jnp.sum(acc[j], axis=0, keepdims=True)
            for t in range(j + 1, w):
                s = s - m_ref[q + j, pl.ds(q + t, 1), :] * xp[t]
            xp[j] = s / m_ref[q + j, pl.ds(q + j, 1), :]
            out = jnp.where(sub == j, _rows8(xp[j]), out)
        x_ref[_tile(p), :] = out
        return carry

    jax.lax.fori_loop(0, n_tiles, backward, 0)


def _als_solve_kernel(a_ref, x_ref, b_ref, m_ref, p_ref, *, k: int,
                      lam: float):
    _solve_build(a_ref, b_ref, m_ref, k=k, lam=lam)
    _solve_factor(x_ref, m_ref, p_ref)


# jitted like the gather: a fit's two halves trace and lower the kernel
# once
@functools.partial(jax.jit, static_argnames=("k", "lam", "interpret"))
def solve_lanes(Ap, k: int, lam: float, *, interpret: bool = False):
    """The ``k`` unknowns of every system of a batch of extended
    Gramians as the product makes them, owner-major ``Ap`` ``(batch,
    width, width)`` float32, and the right-hand sides as they were read
    (``Ap[:, :k, k]``): two of ``(round_up(k, 8), batch)``, a system a
    lane (rows from ``k`` on are zero)."""
    batch, width, _ = Ap.shape
    n, ext = _solve_sizes(k)
    if batch % SOLVE_TILE or ext > width:
        raise ValueError(f"Gramians {Ap.shape} are not whole tiles of "
                         f"{SOLVE_TILE} systems of rank {k}")
    rows = n + 2 * SUBLANES     # the right-hand side's tile and a spare
    return pl.pallas_call(
        functools.partial(_als_solve_kernel, k=k, lam=lam),
        name="_als_solve_kernel",
        grid=(batch // SOLVE_TILE,),
        in_specs=[pl.BlockSpec((SOLVE_TILE, ext, width),
                               lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((n, SOLVE_TILE), lambda i: (0, i))] * 2,
        out_shape=[jax.ShapeDtypeStruct((n, batch), Ap.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((n, rows, SOLVE_TILE), Ap.dtype),
                        pltpu.VMEM((SUBLANES, rows, SOLVE_TILE), Ap.dtype)],
        compiler_params=pltpu.CompilerParams(
            # a tile's systems are its own: no step reads another's
            dimension_semantics=("parallel",),
            vmem_limit_bytes=solve_tile_bytes(k) + VMEM_SLACK),
        interpret=interpret,
    )(Ap)
