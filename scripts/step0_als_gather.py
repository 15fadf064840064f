#!/usr/bin/env python3
"""Step 0 of sparse ALS' resident gather: what one block's row gather
costs on the chip at the ``als100_253m_sweep1`` cell's shape (a block of
6144 segments x 32 slots = 196 608 rows of 128 float32 lanes, from the
items' table of 663 560 rows and the users' of 1 032 200), ms a block,
least of three. Kept as the way to re-read ``GATHER_VMEM_BYTES`` in
``tpu_distalg/ops/als_sparse.py`` (the ``heavy`` / ``five`` rows) and
the forms PRs 46 and 53 chose among (the ``form.*`` rows):

    chiprun -- python3 scripts/step0_als_gather.py
    JAX_PLATFORMS=cpu python3 scripts/step0_als_gather.py --rehearse
    JAX_PLATFORMS=cpu python3 scripts/step0_als_gather.py --bundles

``--q2`` beside any of the three keeps PR 53's rows alone (what ships,
what shipped before it, the packed form; the mix, all hot, all cold;
both tables).

Rows of the output, one a line as ``[step0] <name> <ms> [<ns a slot>]``:

  <table>.xla.<block>            XLA's ``other[idx]`` and the ``where``
                                 that writes the two lanes
  <table>.<range>.<block>        the Mosaic kernel as it ships
                                 (``pallas_als.gather_rows_resident``
                                 on ``als_sparse.gather_lists``' arrays)
                                 with that resident range
  <table>.form.<form>.<block>    a form of PR 46's or PR 53's Step 0
                                 (below), the heavy range resident
  <table>.gram.<form>.<block>    ``block_gramians`` whole at the heavy
                                 class's depth (gather, the two lanes,
                                 the product): ``xla``, ``i`` (the
                                 parent: its kernel, then XLA's
                                 ``where``) or ``ships``
  <table>.heavy.mix.u16.x8       as it ships but 16 slots a trip of
                                 pass 1 (32 ships: PR 46 read 1.6%)
  <...>.x8                       eight blocks in one program (a scan
                                 that keeps a column sum of each), ms a
                                 block: no dispatch in it

``<table>`` is ``items`` (what the user half reads) or ``users``;
``<range>`` is ``heavy`` (the heavy class and the zero rows, 9.4 MB) or
``five`` (the last five classes, 31.5 / 40.9 MB); ``<block>`` is ``hot``
(every slot in the heavy class), ``pad`` (every slot the zero row),
``cold`` (every slot below every range) or ``mix`` (the cell's shares
for that half: the plan's count of slots in the heavy class, in the
classes between and below). Every result is compared with XLA's, bit
for bit. A summary lands in ``chiprun_out/step0_als_gather.json``.

The forms (``_form_kernel``, ``_q2_kernel``; none but ``ships`` is in
``ops/pallas_als.py``): a name is the cold list's maker, what pass 1
reads, and who writes the rating's lane.

  i        the parent (PR 37): pass 1 lists the cold slots itself, a
           cursor in SMEM, and turns the index into a resident row
           (difference, clamp)
  i.v      the same, pass 1's store at the trip's address plus a
           constant (a view of the trip's rows)
  ii       the list loaded (int32, a chunk's slots + 1024 words, the
           count its last word); difference and clamp in pass 1
  ii.h     the list loaded, the index re-based by the loader: the clamp
           is left
  iii      the list and the resident row loaded, ready made; pass 2
           reads the index
  <f>.t    the rating's lane after pass 3: a 128 x 128 transpose of a
           row of ratings spread down the sublanes, a select a vector
  <f>.q    the same from ratings the loader turned a group of eight
           slots down the sublanes: a lane rotation and a masked store
           a group
  <f>.p    the lane in pass 1 (the rating through SMEM), then again
           over the cold list once the rows have landed
  ii.h.q2  ``ii.h.q`` with two 16-bit positions a word of the list, the
           counts by scalar prefetch and 32 slots a trip of pass 1:
           ``pallas_als``' own from PR 46 to PR 53 (``_q2_kernel``)
  ships    ``iii.q2``, form (a) of PR 53: pass 1 loads the resident row
           ready made (the loader's clamp), pass 2 a cold slot's table
           row from the pack's own indices, both through SMEM, 4 B a
           slot each: ``pallas_als``' own
  iii.b.q2 form (b) of PR 53, not shipped: pass 1 as ``ships``; the cold
           slots' table rows alone, in list order, a chunk's at an
           offset of whole 1024-word tiles of one array in HBM
           (``q2_arrays``: by NumPy here; a loader would pack it by a
           scan), copied into SMEM by hand, started before pass 1 and
           waited after it. 1.8 GB less resident at the cell's shape; its
           pass 2 pays an address a row (``base + u``) where ``idx[a]``
           takes the position it holds

One v5e, PR 53, call 1 (``--q2``; ms a block inside one program, items'
table / users'): at the mix ``ii.h.q2`` 0.6718 / 0.7684, ``ships``
0.5998 / 0.6883, ``iii.b.q2`` 0.6102 / 0.7072; all hot 0.5106 / 0.5141,
0.4363 / 0.4462, 0.4433 / 0.4453; all cold 1.2882 / 1.2962, 1.1888 /
1.1996, 1.2308 / 1.2381. By ``--bundles`` (pass 1 a 32 slots / pass 2 a
16 copies / the lane): 69 / 81 / 456, 51 / 75 / 451, 51 / 83 / 451.

``--bundles`` compiles every form for a described v5e with libtpu's
dump in a temporary directory and prints the bundles of each loop of the
static schedule, post-RA, in the kernel's order (pass 1 a trip of 16
slots, 32 in the three above; pass 2 a trip of 16 copies, pass 3 a
wait, then the lane's).

PR 37's readings (``PERF.md`` section 6) also name forms that are not
in any code any more, each timed once on the ``.x8`` rows: 8 slots a
trip of pass 1 (7% slower than 16), 8 copies a trip of pass 2 (5%
slower on a cold block than 16), the resident range copied in by 4 or
16 DMAs (the same as one), a chunk's slots in parts of 2048 with a
part's copies started before the next part's pass 1 (nothing over
chunks of 2048), chunks of 8, 16 and 32 rows (7, 3 and 1% slower than
64).
"""

from __future__ import annotations

import functools
import glob
import json
import os
import re
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# the cell's shape (benchmarks/configs/als-yahoomusic-f100.json)
N_RATINGS, N_USERS, N_ITEMS, K = 252_800_275, 1_000_990, 624_961, 100
GEOMETRY = dict(seg_slots=32, piece_segs=64, batch=6144)
FIVE = 4           # light classes before the heavy one in ``five``
FORMS = ("i", "i.v", "ii", "ii.h", "iii", "ii.h.t", "ii.h.q", "ii.h.p",
         "iii.q")
FEW = ("i", "ii.h", "iii", "ii.h.q")    # the forms the users' table times
# what shipped until PR 53, and PR 53's form that did not ship
# (``_q2_kernel``); ``ships`` is timed beside them
Q2 = ("ii.h.q2", "iii.b.q2")
LIST_PAD = 1024    # a 1-D block in HBM is whole tiles of 1024 words
UNROLL = 16        # slots a trip of the forms' pass 1 (what ships: 32)


def say(msg):
    print(msg, flush=True)


# ---- the forms ------------------------------------------------------------

def _parse(form: str):
    parts = form.split(".")
    lanes = parts.pop() if parts[-1] in "tqp" else None
    view = parts[-1] == "v" or parts[0] != "i"
    row = {"i": "idx", "ii": "idx", "iii": "rel"}[parts[0]]
    if parts[1:] == ["h"]:
        row = "rebased"
    return parts[0] != "i", row, lanes, view


def _form_kernel(*refs, hot_row0, k, loaded, row, lanes, view, n_res):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from tpu_distalg.ops.pallas_als import FETCH, LANES

    refs = list(refs)
    idx_ref = refs.pop(0)                 # idx, re-based or resident row
    cold_ref = refs.pop(0) if loaded else None
    pidx_ref = refs.pop(0) if row == "rel" else idx_ref   # pass 2's rows
    val_ref = refs.pop(0) if lanes else None
    tab_ref, out_ref, res_ref = refs[:3]
    if not loaded:
        cold_ref = refs[3]
    res_sem, row_sem = refs[-2:]
    slots = out_ref.shape[0]
    last = n_res - 1

    @pl.when(pl.program_id(0) == 0)
    def _load():
        cp = pltpu.make_async_copy(
            tab_ref.at[pl.ds(hot_row0, n_res), :], res_ref, res_sem)
        cp.start()
        cp.wait()

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    lane8 = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 1)

    def some(t, n_cold):
        first = pl.multiple_of(t * UNROLL, UNROLL)
        rows = out_ref.at[pl.ds(first, UNROLL), :]
        for u in range(UNROLL):
            h = idx_ref[first + u]
            if row == "idx":
                h = h - hot_row0
            if not loaded:
                cold_ref[n_cold] = first + u
                n_cold = n_cold - (h >> 31)
            if row != "rel":
                h = jnp.minimum(h.astype(jnp.uint32),
                                jnp.uint32(last)).astype(jnp.int32)
            v = res_ref[pl.ds(h, 1), :]
            if lanes == "p":
                v = jnp.where(lane == k, val_ref[first + u], v)
            if view:
                rows[pl.ds(u, 1), :] = v
            else:
                out_ref[pl.ds(first + u, 1), :] = v
        return n_cold

    n_cold = jax.lax.fori_loop(0, slots // UNROLL, some, jnp.int32(0))
    if loaded:
        n_cold = cold_ref[slots + LIST_PAD - 1]
    else:
        again = cold_ref[jnp.maximum(n_cold - 1, 0)]
        for u in range(FETCH - 1):
            cold_ref[n_cold + u] = again
    trips = (n_cold + FETCH - 1) // FETCH

    def fetch(g, carry):
        first = pl.multiple_of(g * FETCH, FETCH)
        at = [cold_ref[first + u] for u in range(FETCH)]
        rows = [pidx_ref[a] for a in at]
        if row == "rebased":
            rows = [h + hot_row0 for h in rows]
        for a, h in zip(at, rows):
            pltpu.make_async_copy(
                tab_ref.at[pl.ds(h, 1), :], out_ref.at[pl.ds(a, 1), :],
                row_sem).start()
        return carry

    jax.lax.fori_loop(0, trips, fetch, 0)

    def land(g, carry):
        pltpu.make_async_copy(
            tab_ref.at[pl.ds(0, FETCH), :], out_ref.at[pl.ds(0, FETCH), :],
            row_sem).wait()
        return carry

    jax.lax.fori_loop(0, trips, land, 0)

    if lanes == "p":
        def fix(g, carry):
            first = pl.multiple_of(g * FETCH, FETCH)
            at = [cold_ref[first + u] for u in range(FETCH)]
            got = [jnp.where(lane == k, val_ref[a],
                             out_ref[pl.ds(a, 1), :]) for a in at]
            for a, v in zip(at, got):
                out_ref[pl.ds(a, 1), :] = v
            return carry

        jax.lax.fori_loop(0, trips, fix, 0)
    elif lanes == "q":
        def tile(q, carry):
            first = pl.multiple_of(q * 1024, 1024)
            vals = val_ref[pl.ds(pl.multiple_of(q * 8, 8), 8), :]
            for m in range(LANES):
                rot = pltpu.roll(vals, (k - m) % LANES, axis=1) \
                    if (k - m) % LANES else vals
                pltpu.store(out_ref.at[pl.ds(first + 8 * m, 8), :], rot,
                            mask=lane8 == k)
            return carry

        jax.lax.fori_loop(0, slots // 1024, tile, 0)
    elif lanes == "t":
        def row_of(q, carry):
            first = pl.multiple_of(q * LANES, LANES)
            spread = jnp.broadcast_to(val_ref[pl.ds(q, 1), :],
                                      (LANES, LANES)).T
            for m in range(LANES // 8):
                at = pl.ds(first + 8 * m, 8)
                out_ref[at, :] = jnp.where(
                    lane8 == k, spread[8 * m:8 * m + 8, :], out_ref[at, :])
            return carry

        jax.lax.fori_loop(0, slots // LANES, row_of, 0)


def form_gather(form: str, table, hot_row0: int, arrays: dict, *,
                k: int = K, interpret: bool = False):
    """One block through a form: ``arrays`` holds ``idx`` and what the
    form's loader would have made (``block_arrays``)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from tpu_distalg.ops.pallas_als import (
        CHUNK_ROWS, FETCH, LANES, VMEM_SLACK, chunk_rows)

    loaded, row, lanes, view = _parse(form)
    idx_b = arrays["idx"]
    cr = chunk_rows(idx_b.shape[0], CHUNK_ROWS)
    n_res = table.shape[0] - hot_row0
    slots = cr * LANES

    def smem(n):
        return pl.BlockSpec((n,), lambda c: (c,), memory_space=pltpu.SMEM)

    args, specs = [arrays[row].reshape(-1)], [smem(slots)]
    if loaded:
        args.append(arrays["cold"].reshape(-1))
        specs.append(smem(slots + LIST_PAD))
    if row == "rel":
        args.append(idx_b.reshape(-1))
        specs.append(smem(slots))
    if lanes == "p":
        args.append(arrays["val"].reshape(-1))
        specs.append(smem(slots))
    elif lanes:
        args.append(arrays["val_t" if lanes == "q" else "val"])
        specs.append(pl.BlockSpec((cr, LANES), lambda c: (c, 0)))
    args.append(table)
    specs.append(pl.BlockSpec(memory_space=pl.ANY))
    scratch = [pltpu.VMEM((n_res, LANES), table.dtype)]
    if not loaded:
        scratch.append(pltpu.SMEM((slots + FETCH,), jnp.int32))
    scratch += [pltpu.SemaphoreType.DMA(()), pltpu.SemaphoreType.DMA(())]
    return pl.pallas_call(
        functools.partial(_form_kernel, hot_row0=hot_row0, k=k,
                          loaded=loaded, row=row, lanes=lanes, view=view,
                          n_res=n_res),
        name="_als_gather_kernel",
        grid=(idx_b.shape[0] // cr,),
        in_specs=specs,
        out_specs=pl.BlockSpec((slots, LANES), lambda c: (c, 0)),
        out_shape=jax.ShapeDtypeStruct((idx_b.size, LANES), table.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * (n_res + 2 * slots) * LANES + VMEM_SLACK,
            disable_bounds_checks=True),
        interpret=interpret,
    )(*args)


def _q2_kernel(*refs, hot_row0, lane, packed):
    """``pallas_als._als_gather_kernel`` as it stood until PR 53
    (``ii.h.q2``: pass 1 clamps the re-based index, pass 2 adds
    ``hot_row0`` back to it) and the packed form of PR 53 (``iii.b.q2``:
    pass 1 loads the resident row ready made, pass 2 the cold slots'
    table rows in list order, a chunk's at its offset of an array in
    HBM, copied into SMEM by hand while pass 1 runs)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from tpu_distalg.ops.pallas_als import FETCH, LANES, TILE, UNROLL

    refs = list(refs)
    n_cold_ref = refs.pop(0)
    off_ref = refs.pop(0) if packed else None
    row_ref, cold_ref, val_ref, tab_ref = refs[:4]
    refs = refs[4:]
    list_hbm = refs.pop(0) if packed else None
    out_ref, res_ref = refs[:2]
    refs = refs[2:]
    list_ref = refs.pop(0) if packed else None
    res_sem, row_sem = refs[:2]
    slots = row_ref.shape[0]
    last = res_ref.shape[0] - 1
    c = pl.program_id(0)

    @pl.when(c == 0)
    def _load():
        cp = pltpu.make_async_copy(
            tab_ref.at[pl.ds(hot_row0, res_ref.shape[0]), :], res_ref,
            res_sem)
        cp.start()
        cp.wait()

    if packed:
        mine = pltpu.make_async_copy(
            list_hbm.at[pl.ds(pl.multiple_of(off_ref[c] * LIST_PAD,
                                             LIST_PAD), slots)],
            list_ref, refs[2])
        mine.start()

    def some(t, carry):
        first = pl.multiple_of(t * UNROLL, UNROLL)
        rows = out_ref.at[pl.ds(first, UNROLL), :]
        for u in range(UNROLL):
            row = row_ref[first + u]
            if not packed:
                row = jnp.minimum(row.astype(jnp.uint32),
                                  jnp.uint32(last)).astype(jnp.int32)
            rows[pl.ds(u, 1), :] = res_ref[pl.ds(row, 1), :]
        return carry

    jax.lax.fori_loop(0, slots // UNROLL, some, 0)
    if packed:
        mine.wait()
    trips = (n_cold_ref[c] + FETCH - 1) // FETCH

    def fetch(g, carry):
        first = pl.multiple_of(g * (FETCH // 2), FETCH // 2)
        words = [cold_ref[first + u] for u in range(FETCH // 2)]
        at = [a for w in words for a in (w & 0xFFFF, w >> 16)]
        if packed:
            base = pl.multiple_of(g * FETCH, FETCH)
            rows = [list_ref[base + u] for u in range(FETCH)]
        else:
            rows = [row_ref[a] + hot_row0 for a in at]
        for a, h in zip(at, rows):
            pltpu.make_async_copy(
                tab_ref.at[pl.ds(h, 1), :], out_ref.at[pl.ds(a, 1), :],
                row_sem).start()
        return carry

    jax.lax.fori_loop(0, trips, fetch, 0)

    def land(g, carry):
        pltpu.make_async_copy(
            tab_ref.at[pl.ds(0, FETCH), :], out_ref.at[pl.ds(0, FETCH), :],
            row_sem).wait()
        return carry

    jax.lax.fori_loop(0, trips, land, 0)
    at_lane = jax.lax.broadcasted_iota(jnp.int32, (TILE, LANES), 1) == lane

    def values(q, carry):
        vals = val_ref[pl.ds(pl.multiple_of(q * 8, 8), 8), :]
        groups = [pltpu.roll(vals, (lane - m) % LANES, axis=1)
                  if (lane - m) % LANES else vals for m in range(LANES)]
        pltpu.store(out_ref.at[pl.ds(pl.multiple_of(q * TILE, TILE), TILE), :],
                    jnp.concatenate(groups, axis=0), mask=at_lane)
        return carry

    jax.lax.fori_loop(0, slots // TILE, values, 0)


def q2_gather(form: str, table, a: dict, *, hot_row0: int, k: int = K,
              interpret: bool = False):
    """One block through ``ii.h.q2`` or ``iii.b.q2``: ``a`` is a block
    of :func:`q2_arrays`'."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from tpu_distalg.ops.pallas_als import LANES, VMEM_SLACK, chunk_rows

    packed = form == "iii.b.q2"
    row_b = a["row" if packed else "rebased"]
    cr = chunk_rows(row_b.shape[0])
    n_res = table.shape[0] - hot_row0
    slots = cr * LANES
    n_pre = 2 if packed else 1

    def smem(n):
        return pl.BlockSpec((n,), lambda c, *pre: (c,),
                            memory_space=pltpu.SMEM)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_q2_kernel, hot_row0=hot_row0, lane=k,
                          packed=packed),
        name="_als_gather_kernel",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pre,
            grid=(row_b.shape[0] // cr,),
            in_specs=[smem(slots), smem(slots // 2),
                      pl.BlockSpec((cr, LANES), lambda c, *pre: (c, 0)),
                      hbm, *([hbm] if packed else [])],
            out_specs=pl.BlockSpec((slots, LANES), lambda c, *pre: (c, 0)),
            scratch_shapes=[
                pltpu.VMEM((n_res, LANES), table.dtype),
                *([pltpu.SMEM((slots,), jnp.int32)] if packed else []),
                pltpu.SemaphoreType.DMA(()), pltpu.SemaphoreType.DMA(()),
                *([pltpu.SemaphoreType.DMA(())] if packed else [])]),
        out_shape=jax.ShapeDtypeStruct((row_b.size, LANES), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * (n_res + 2 * slots) * LANES + VMEM_SLACK,
            disable_bounds_checks=True),
        interpret=interpret,
    )(a["n_cold"], *([a["off"]] if packed else []), row_b.reshape(-1),
      a["cold"], a["val_t"], table, *([a["packed"]] if packed else []))


def q2_arrays(idx, made: dict, hot_row0: int):
    """What ``ships`` and the two ``q2`` forms are handed for blocks
    ``idx`` ``(blocks, rows, 128)``: ``made`` (``gather_lists``' four
    beside ``idx``), the re-based index, and by NumPy the packed form's
    list of the cold slots' table rows: a chunk's in list order, filled
    to a whole trip of pass 2 with the last one again, from an offset
    of whole tiles of 1024 words (``off``, in tiles, a chunk), and room
    behind the last for a copy of a chunk's slots."""
    import numpy as np

    from tpu_distalg.ops.pallas_als import FETCH, chunk_rows

    blocks, rows, lanes = idx.shape
    slots = chunk_rows(rows) * lanes
    chunks = idx.reshape(blocks, -1, slots)
    n_chunks = chunks.shape[1]
    packed = np.zeros((blocks, rows * lanes + n_chunks * LIST_PAD + slots),
                      np.int32)
    off = np.zeros((blocks, n_chunks), np.int32)
    for b in range(blocks):
        at = 0
        for c in range(n_chunks):
            mine = chunks[b, c][chunks[b, c] < hot_row0]
            fill = -mine.size % FETCH
            off[b, c] = at // LIST_PAD
            packed[b, at:at + mine.size] = mine
            packed[b, at + mine.size:at + mine.size + fill] = \
                mine[-1] if mine.size else 0
            at += -(-(mine.size + fill) // LIST_PAD) * LIST_PAD
    return dict(made, idx=idx, rebased=idx - np.int32(hot_row0),
                packed=packed, off=off)


def block_arrays(idx, val, hot_row0: int, n_res: int):
    """What the forms' loaders hand over for blocks ``idx``, ``val``
    ``(blocks, rows, 128)``, by NumPy: the re-based index, the resident
    row, the int32 cold lists (the count a chunk's last word) and the
    turned ratings."""
    import numpy as np

    from tpu_distalg.ops.pallas_als import CHUNK_ROWS, chunk_rows

    blocks, rows, lanes = idx.shape
    slots = chunk_rows(rows, CHUNK_ROWS) * lanes
    rebased = idx - np.int32(hot_row0)
    rel = np.minimum(rebased.astype(np.uint32), np.uint32(n_res - 1)) \
        .astype(np.int32)
    chunks = rebased.reshape(blocks, -1, slots)
    cold = np.zeros((*chunks.shape[:2], slots + LIST_PAD), np.int32)
    for b, c in np.ndindex(*chunks.shape[:2]):
        at = np.flatnonzero(chunks[b, c] < 0)
        cold[b, c, :at.size] = at
        cold[b, c, at.size:slots] = at[-1] if at.size else 0
        cold[b, c, -1] = at.size
    val_t = val.reshape(blocks, -1, lanes, 8).swapaxes(-1, -2) \
        .reshape(idx.shape)
    return dict(idx=idx, val=val, rebased=rebased, rel=rel,
                cold=cold.reshape(blocks, -1), val_t=val_t)


# ---- the static schedule --------------------------------------------------

def compile_one(form: str):
    """In a child with the dump on: compile one form for a v5e at the
    cell's block and the items' table."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from tpu_distalg.models import als
    from tpu_distalg.ops import pallas_als

    meta = als.plan_ratings(N_RATINGS, N_USERS, N_ITEMS, K,
                            geometry=GEOMETRY, on_tpu=True)
    plan = meta["gather"][0]
    rows_b = meta["geometry"].block_shape[0]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    table = arr((meta["item"].static.table_rows, 128), jnp.float32)
    block_i = arr((rows_b, 128), jnp.int32)
    block_f = arr((rows_b, 128), jnp.float32)
    chunks = rows_b // pallas_als.chunk_rows(rows_b)
    t0 = time.perf_counter()
    cold = arr((rows_b * 64,), jnp.int32)
    counts = arr((chunks,), jnp.int32)
    if form == "ships":
        jax.jit(lambda t, i, v, r, c, n: pallas_als.gather_rows_resident(
            t, i, v, r, c, n, plan.hot_row0, K)).lower(
                table, block_i, block_f, block_i, cold, counts).compile()
    elif form in Q2:
        names = ("rebased", "row", "val_t", "cold", "n_cold", "off",
                 "packed")
        slots = rows_b * 128 // chunks
        shapes = (block_i, block_i, block_f, cold, counts, counts, arr(
            (rows_b * 128 + chunks * LIST_PAD + slots,), jnp.int32))
        jax.jit(lambda t, *a: q2_gather(
            form, t, dict(zip(names, a)), hot_row0=plan.hot_row0)).lower(
                table, *shapes).compile()
    else:
        names = ("idx", "val", "rebased", "rel", "cold", "val_t")
        shapes = dict(idx=block_i, val=block_f, rebased=block_i,
                      rel=block_i, val_t=block_f, cold=arr(
                          (rows_b * 128 + chunks * LIST_PAD,), jnp.int32))
        jax.jit(lambda t, *a: form_gather(
            form, t, plan.hot_row0, dict(zip(names, a)))).lower(
                table, *(shapes[n] for n in names)).compile()
    say(f"[compiled] {time.perf_counter() - t0:.2f}")


def read_loops(dump: str):
    """``(first bundle, last)`` of every backward branch of the gather
    kernel's post-RA schedule, in the file's order."""
    files = [f for f in sorted(glob.glob(os.path.join(
        dump, "*_als_gather_kernel*packed-bundles-post-ra.txt")))
        if "schedule-analysis" not in f]
    if not files:
        return None
    spans = []
    for line in open(files[-1]):
        m = re.match(r"\s*(0x[0-9a-f]+)\s+\w*\s*:", line)
        if not m:
            continue
        no = int(m.group(1), 16)
        for b in re.finditer(r"sbr\.rel \(!?%\w+\) target bundleno = (\d+)",
                             line):
            if int(b.group(1)) <= no:
                spans.append((int(b.group(1)), no))
    return sorted(spans)


def bundles(forms):
    out = {}
    for form in forms:
        # (the dump may abort the child at its very end, its files
        # written by then: read them whatever the exit code)
        with tempfile.TemporaryDirectory(prefix="llo_step0_") as dump:
            done = subprocess.run(
                [sys.executable, __file__, "--compile-one", form],
                capture_output=True, text=True, env=dict(
                    os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
                    LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} "
                    "--xla_jf_dump_llo_text=true"))
            loops = read_loops(dump)
        if not loops:
            say(f"[bundles] {form}: no schedule (exit {done.returncode}): "
                + (done.stderr.strip().splitlines() or ["?"])[-1][:300])
            continue
        inner = [hi - lo + 1 for lo, hi in loops[1:]]   # [0]: the grid's
        from tpu_distalg.ops import pallas_als

        trip = pallas_als.UNROLL if form in ("ships", *Q2) else UNROLL
        names = [f"pass1 a {trip} slots", "pass2 a 16 copies",
                 "pass3 a wait", "lane"]
        out[form] = dict(zip(names, inner))
        say(f"[bundles] {form}: " + ", ".join(
            f"{n} {b}" for n, b in zip(names, inner))
            + f" (pass 1: {inner[0] * 8 / trip:.1f} for 8 slots)")
    return out


# ---- on the chip (or interpreted) ----------------------------------------

def least_ms(fn, *args, n: int = 3) -> float:
    import jax

    jax.block_until_ready(fn(*args))          # compile, warm
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def main(argv) -> int:
    if "--compile-one" in argv:
        compile_one(argv[argv.index("--compile-one") + 1])
        return 0
    if "--bundles" in argv:
        bundles([*(() if "--q2" in argv else FORMS), *Q2, "ships"])
        return 0

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_distalg.models import als
    from tpu_distalg.ops import als_sparse

    interp = "--rehearse" in argv
    q2_only = "--q2" in argv      # PR 53's rows and no others
    if jax.devices()[0].platform != "tpu" and not interp:
        print("step0_als_gather: no chip", file=sys.stderr)
        return 2
    if interp:
        meta = als.plan_ratings(60_000, 900, 500, 12, geometry=dict(
            seg_slots=32, piece_segs=64, batch=192))
    else:
        meta = als.plan_ratings(N_RATINGS, N_USERS, N_ITEMS, K,
                                geometry=GEOMETRY)
    geom = meta["geometry"]
    k = geom.k
    rows_b = geom.block_shape[0]
    slots = geom.block_slots
    rng = np.random.default_rng(37)
    out: dict = {"slots": slots}

    def row(name, ms):
        out[name] = ms
        say(f"[step0] {name} {ms:.4f} ms  {ms * 1e6 / slots:.2f} ns/slot")

    def eight(fn):
        def run(T, a8):
            def one(c, a):
                return c, jnp.sum(fn(T, a), axis=0)

            return jax.lax.scan(one, 0, a8)[1]

        return jax.jit(run)

    # the half that reads a table is the OTHER side's
    for table, own, other in (("items", meta["user"], meta["item"]),
                              ("users", meta["item"], meta["user"])):
        st = other.static
        heavy0 = st.heavy[2]
        starts = [r0 for _, _, n, r0 in st.light if n]
        ranges = {
            "heavy": heavy0,
            "five": starts[-FIVE] if len(starts) >= FIVE else starts[0]}
        T = rng.standard_normal((st.table_rows, geom.width), np.float32)
        T[st.zero_row:] = 0
        T[:, k:] = 0
        T = jnp.asarray(T)
        # the cell's shares of this half's slots: heavy class and
        # padding, the classes of ``five`` below it, the rest
        held = own.slots_held
        share = {name: als_sparse.resident_slots(own, other, r0) / held
                 for name, r0 in ranges.items()}
        out[f"{table}.share"] = share
        out[f"{table}.rows"] = {n: st.table_rows - r for n, r in
                                ranges.items()}
        say(f"[step0] {table}: table rows {st.table_rows}, resident "
            f"rows {out[table + '.rows']}, shares {share}")
        lo = min(ranges.values())
        shape = (8, rows_b, 128)
        u = rng.random(shape)
        blocks = {
            "hot": rng.integers(heavy0, st.zero_row, shape),
            "pad": np.full(shape, st.zero_row),
            "cold": rng.integers(0, max(lo, 1), shape),
            "mix": np.where(
                u < share["heavy"],
                rng.integers(heavy0, st.zero_row + 1, shape),
                np.where(u < share["five"],
                         rng.integers(ranges["five"], max(
                             heavy0, ranges["five"] + 1), shape),
                         rng.integers(0, max(ranges["five"], 1), shape)))}
        blocks = {b: v.astype(np.int32) for b, v in blocks.items()}
        val = rng.integers(0, 101, shape).astype(np.float32)

        def xla(T, a):
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, geom.width), 1)
            flat = a["idx"].reshape(-1)
            ok = (flat != st.zero_row).astype(jnp.float32)[:, None]
            return jnp.where(lane == k, a["val"].reshape(-1, 1), jnp.where(
                lane == k + 1, ok, als_sparse.gather_rows(T, a["idx"])))

        def _ships(M, a, plan):
            from tpu_distalg.ops import pallas_als

            return pallas_als.gather_rows_resident(
                M, a["idx"], a["val_t"], a["row"], a["cold"], a["n_cold"],
                plan.hot_row0, k, interpret=interp)

        def form(name):
            # (a form that writes no lane returns the rows alone)
            return lambda T, a: form_gather(name, T, heavy0, a, k=k,
                                            interpret=interp)

        def shipped_arrays(idx, plan):
            made = jax.jit(lambda i, v: als_sparse.gather_lists(
                i, v, plan))(idx, val)
            return dict(zip(("val_t", "row", "cold", "n_cold"), made),
                        idx=idx)

        def first(a8):
            return jax.tree_util.tree_map(lambda x: x[0], a8)

        # the table as the Mosaic form reads it, made once a half
        n_res = st.table_rows - heavy0
        marked = als_sparse.gather_table(
            T, geom, st.zero_row, als_sparse.GatherPlan("mosaic", heavy0,
                                                        n_res))
        want = {}
        for b, idx in blocks.items():
            a8 = dict(idx=jnp.asarray(idx), val=jnp.asarray(val))
            want[b] = jax.jit(xla)(T, first(a8))
            if q2_only:
                continue
            row(f"{table}.xla.{b}", least_ms(jax.jit(xla), T, first(a8)))
            row(f"{table}.xla.{b}.x8", least_ms(eight(xla), T, a8) / 8)
        # PR 53's three: what shipped until then, what ships, and the
        # packed form that did not
        heavy = als_sparse.GatherPlan("mosaic", heavy0, n_res,
                                      interpret=interp)
        for b in ("mix", "hot", "cold"):
            a8 = {n: jnp.asarray(v) for n, v in q2_arrays(
                blocks[b], shipped_arrays(jnp.asarray(blocks[b]), heavy),
                heavy0).items()}
            for name in (Q2[0], "ships", Q2[1]):
                fn = functools.partial(_ships, plan=heavy) \
                    if name == "ships" else functools.partial(
                        q2_gather, name, hot_row0=heavy0, k=k,
                        interpret=interp)
                if not bool(jnp.array_equal(jax.jit(fn)(marked, first(a8)),
                                            want[b])):
                    say(f"[step0] {table}.form.{name}.{b}: NOT the block "
                        f"XLA makes")
                    return 1
                row(f"{table}.form.{name}.{b}.x8",
                    least_ms(eight(fn), marked, a8, n=5) / 8)
        if q2_only:
            del T, want, marked
            continue
        for name, r0 in ranges.items():
            plan = als_sparse.GatherPlan("mosaic", r0, st.table_rows - r0,
                                         interpret=interp)
            for b, idx in blocks.items():
                if name != "heavy" and b != "mix":
                    continue
                a8 = shipped_arrays(jnp.asarray(idx), plan)
                fn = jax.jit(functools.partial(_ships, plan=plan))
                if not bool(jnp.array_equal(fn(marked, first(a8)),
                                            want[b])):
                    say(f"[step0] {table}.{name}.{b}: NOT the block XLA "
                        f"makes")
                    return 1
                row(f"{table}.{name}.{b}", least_ms(fn, marked, first(a8)))
                row(f"{table}.{name}.{b}.x8", least_ms(eight(
                    functools.partial(_ships, plan=plan)), marked, a8) / 8)
        if not interp:
            # the shipped kernel with 16 slots a trip of pass 1
            from tpu_distalg.ops import pallas_als

            plan = als_sparse.GatherPlan("mosaic", heavy0,
                                         st.table_rows - heavy0)
            a8 = shipped_arrays(jnp.asarray(blocks["mix"]), plan)

            def wide(M, a):
                return pallas_als.gather_rows_resident.__wrapped__(
                    M, a["idx"], a["val_t"], a["row"], a["cold"],
                    a["n_cold"], heavy0, k)

            ships = pallas_als.UNROLL
            pallas_als.UNROLL = 16
            try:
                row(f"{table}.heavy.mix.u16.x8",
                    least_ms(eight(wide), marked, a8) / 8)
            finally:
                pallas_als.UNROLL = ships
        for b in (("mix",) if table == "users" else ("mix", "hot", "cold")):
            a8 = {n: jnp.asarray(v) for n, v in block_arrays(
                blocks[b], val, heavy0, n_res).items()}
            for name in (FORMS if table == "items" else FEW):
                lanes = _parse(name)[2]
                if b != "mix" and lanes:
                    continue
                fn = jax.jit(form(name))
                got = fn(marked if lanes else T, first(a8))
                ref = want[b] if lanes else als_sparse.gather_rows(
                    T, blocks[b][0])
                if not bool(jnp.array_equal(got, ref)):
                    say(f"[step0] {table}.form.{name}.{b}: NOT the block "
                        f"XLA makes")
                    return 1
                row(f"{table}.form.{name}.{b}.x8", least_ms(
                    eight(form(name)), marked if lanes else T, a8) / 8)
        # block_gramians whole, at the heavy class's depth
        P = geom.piece_segs
        plan = als_sparse.GatherPlan("mosaic", heavy0, n_res,
                                     interpret=interp)

        def product(G):
            G = G.reshape(geom.batch // P, P * geom.seg_slots, geom.width)
            return jnp.einsum("osd,ose->ode", G, G,
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)

        def gram_xla(T, a):
            return als_sparse.block_gramians(
                T, a["idx"], a["val"], P, geom, st.zero_row)

        def gram_parent(T, a):
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, geom.width), 1)
            ok = (a["idx"].reshape(-1) != st.zero_row).astype(
                jnp.float32)[:, None]
            G = form_gather("i", T, heavy0, a, k=k, interpret=interp)
            return product(jnp.where(
                lane == k, a["val"].reshape(-1, 1),
                jnp.where(lane == k + 1, ok, G)))

        def gram_ships(M, a):
            return als_sparse.block_gramians(
                M, a["idx"], a["val_t"], P, geom, st.zero_row, plan,
                (a["row"], a["cold"], a["n_cold"]))

        a8 = {n: jnp.asarray(v) for n, v in block_arrays(
            blocks["mix"], val, heavy0, n_res).items()}
        a8.update(shipped_arrays(a8["idx"], plan))
        ref = jax.jit(gram_xla)(T, first(a8))
        for name, fn, tab in (("xla", gram_xla, T), ("i", gram_parent, T),
                              ("ships", gram_ships, marked)):
            if not bool(jnp.array_equal(jax.jit(fn)(tab, first(a8)), ref)):
                say(f"[step0] {table}.gram.{name}.mix: NOT XLA's Gramians")
                return 1
            row(f"{table}.gram.{name}.mix.x8",
                least_ms(eight(fn), tab, a8) / 8)
        del T, want, marked
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "step0_als_gather.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
