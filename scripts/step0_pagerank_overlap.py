#!/usr/bin/env python3
"""Step 0 of PR 45: the fused SpMV's chunk loop as the parent had it
(a chunk's gather, then its scatter, one after the other inside a
``pl.when``) against the pipelined loop that ships (a chunk's gather in
one basic block with the chunk before's scatter), at the two cells'
geometries and at a shard of four of SCALE 26 (``rg`` 1024: two turns
of the gather in the parent, one since ``SPMV_UNROLL`` is 128;
readings in PERF.md section 6). Kept as the way to re-read whether
the scheduler still overlaps the two halves, and ``SPMV_UNROLL``: the
parent's form lives here alone.

    JAX_PLATFORMS=cpu python3 scripts/step0_pagerank_overlap.py --bundles
    chiprun -- python3 scripts/step0_pagerank_overlap.py [--geoms sharded26]
    JAX_PLATFORMS=cpu python3 scripts/step0_pagerank_overlap.py --rehearse

``--bundles`` compiles both forms for a described ``v5e:2x2`` with the
schedule dumped and counts the bundles of a turn of the chunk loop,
without the chip. On the chip: (resident) Graph500 SCALE 24 drawn and
planned by the program, (sharded, sharded26) range 0 of 4 of SCALE 25
and 26 drawn and planned as ``step0_pagerank_sharded.py`` does; ms a
sweep of each
form, and the shipped form's table against the parent's bit for bit
(random ranks) and, on the sharded block, against XLA's
``segment_sum`` of equal contributions (a power of two: every sum
exact)."""

from __future__ import annotations

import functools
import gc
import os
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "benchmarks"), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

# SCALE, shards: the two cells, and SCALE 26 on four (no cell; rg 1024)
GEOMS = {"resident": (24, 1), "sharded": (25, 4), "sharded26": (26, 4)}
FORMS = ("parent", "shipped")
PARENT_KERNEL = "_spmv_parent_kernel"
PARENT_UNROLL = 64     # the parent's SPMV_UNROLL: two turns at rg 1024


def say(msg):
    print(msg, flush=True)


# ---- the parent's form (PR 44's tree), for the comparison only ------------

def _parent_kernel(seg_ref, grp_ref, sbase_ref, win_ref, slane_ref,
                   srow_ref, drow_ref, dlane_ref, we_ref, acc_in, acc_out,
                   acc, sem, *, rg, ws, blk, unroll):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from tpu_distalg.ops import pallas_pagerank as ppr

    del seg_ref, grp_ref
    pid = pl.program_id(0)

    def copy(src, dst):
        dma = pltpu.make_async_copy(src, dst, sem.at[0])
        dma.start()
        dma.wait()

    @pl.when(pid == 0)
    def _load():
        copy(acc_in, acc)

    def chunk(i, _):
        sb = sbase_ref[pid * blk + i]

        @pl.when(sb >= 0)
        def _live():
            at = pl.ds(pl.multiple_of(8 * i, 8), 8)
            slane = slane_ref[at, :]
            srow = srow_ref[at, :]
            bits = [(srow & (1 << b)) != 0 for b in range(3)]
            tile_of = srow >> 3

            def gather_tiles(turn, g):
                for u in range(unroll):
                    t = turn * unroll + u
                    tile = win_ref[pl.ds(pl.multiple_of(8 * t, 8), 8), :]
                    picked = [jnp.take_along_axis(
                        jnp.broadcast_to(tile[r:r + 1, :], (8, ppr.LANES)),
                        slane, axis=1) for r in range(8)]
                    for bit in bits:
                        picked = [jnp.where(bit, hi, lo) for lo, hi
                                  in zip(picked[::2], picked[1::2])]
                    g = jnp.where(tile_of == t, picked[0], g)
                return g

            g = jax.lax.fori_loop(0, rg // (8 * unroll), gather_tiles,
                                  jnp.zeros((8, ppr.LANES), jnp.float32))
            upd = ppr.scatter_window(ppr.split3(g * we_ref[at, :]),
                                     drow_ref[at, :], dlane_ref[at, :], ws)
            acc[pl.ds(pl.multiple_of(sb, 8), ws), :] += upd

        return 0

    jax.lax.fori_loop(0, blk, chunk, 0)

    @pl.when(pid == pl.num_programs(0) - 1)
    def _store():
        copy(acc, acc_out)


def parent_table(gbase, sbase, ranks_table, src_lane, src_row, dst_row,
                 dst_lane, w_e, *, rg, ws, r8, blk, seg_steps,
                 interpret=False):
    """``spmv_table`` as the parent had it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from tpu_distalg.ops import pallas_pagerank as ppr

    n_steps = src_lane.shape[0] // 8 // blk
    edge_block = pl.BlockSpec(
        (blk * 8, ppr.LANES),
        lambda i, seg, grp, sb: (seg[0] * seg_steps + i, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    call = pl.pallas_call(
        functools.partial(
            _parent_kernel, rg=rg, ws=ws, blk=blk,
            unroll=1 if interpret else max(
                d for d in range(1, min(PARENT_UNROLL, rg // 8) + 1)
                if (rg // 8) % d == 0)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(seg_steps,),
            in_specs=[pl.BlockSpec((rg, ppr.LANES),
                                   lambda i, seg, grp, sb: (grp[i], 0))]
            + [edge_block] * 5 + [hbm],
            out_specs=hbm,
            scratch_shapes=[pltpu.VMEM((r8 + ws, ppr.LANES), jnp.float32),
                            pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct((r8 + ws, ppr.LANES), jnp.float32),
        input_output_aliases={9: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=ppr.SPMV_VMEM_LIMIT),
        interpret=interpret, name=PARENT_KERNEL)
    grp = gbase[::blk] // rg

    def segment(s, acc):
        return call(
            jnp.full((1,), s, jnp.int32),
            jax.lax.dynamic_slice(grp, (s * seg_steps,), (seg_steps,)),
            jax.lax.dynamic_slice(sbase, (s * seg_steps * blk,),
                                  (seg_steps * blk,)),
            ranks_table, src_lane, src_row, dst_row, dst_lane, w_e, acc)

    return jax.lax.fori_loop(
        0, n_steps // seg_steps, segment,
        jnp.zeros((r8 + ws, ppr.LANES), jnp.float32))


def table_fn(form: str, geom, interpret=False):
    """``f(gbase, sbase, ranks_table, *the five slot arrays)`` of a
    form at a geometry, jitted."""
    import jax

    from tpu_distalg.ops import pallas_pagerank as ppr

    fn = parent_table if form == "parent" else ppr.spmv_table
    return jax.jit(functools.partial(
        fn, rg=geom.rg, ws=geom.ws, r8=geom.rows_out, blk=geom.blk,
        seg_steps=geom.seg_steps, interpret=interpret))


# ---- the static schedule, without the chip --------------------------------

def compile_one(form: str, which: str):
    """In a child with the dump on: compile one form for a v5e."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from tpu_distalg.ops import pallas_pagerank as ppr

    scale, shards = GEOMS[which]
    geom = ppr.spmv_geometry(1 << scale, 16 << scale, shards)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    n_chunks = geom.n_chunks // geom.n_shards
    per_slot = (n_chunks * 8, 128)
    t0 = time.perf_counter()
    table_fn(form, geom).lower(
        arr((n_chunks,), jnp.int32), arr((n_chunks,), jnp.int32),
        arr((geom.n_groups * geom.rg, 128), jnp.float32),
        *[arr(per_slot, jnp.int32)] * 4,
        arr(per_slot, jnp.float32)).compile()
    say(f"[compiled] {time.perf_counter() - t0:.2f}")


def bundles(which: str):
    """Bundles of the grid step and of a turn of the chunk loop, by
    the post-RA schedule of each form at a geometry."""
    from step0_als_solve import read_loops
    from tpu_distalg.ops import pallas_pagerank as ppr

    scale, shards = GEOMS[which]
    geom = ppr.spmv_geometry(1 << scale, 16 << scale, shards)
    out = {}
    for form in FORMS:
        kernel = PARENT_KERNEL if form == "parent" else "_spmv_kernel"
        env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
        with tempfile.TemporaryDirectory(prefix="llo_step0_") as dump:
            t0 = time.perf_counter()
            done = subprocess.run(
                [sys.executable, __file__, "--compile-one", form, which],
                capture_output=True, text=True, env=dict(
                    env, LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} "
                    "--xla_jf_dump_llo_text=true"))
            took = time.perf_counter() - t0
            nest = read_loops(dump, kernel)
        if not nest:
            say(f"[bundles] {which} {form}: no schedule (exit "
                f"{done.returncode}): "
                + (done.stderr.strip().splitlines() or ["?"])[-1][:300])
            continue
        (lo, hi, inner), = nest[-1:]         # the grid's loop is the last
        (clo, chi, turns), = inner           # the chunk loop, its only one
        # a gather of several turns is a loop inside the chunk's
        trips = max(geom.rg // 8 // PARENT_UNROLL, 1)
        out[form] = chi - clo + 1 + (trips - 1) * sum(
            h - l + 1 for l, h, _ in turns)
        say(f"[bundles] {which} (rg {geom.rg}, ws {geom.ws}) {form}: "
            f"{out[form]} bundles a chunk; the grid step's own "
            f"{hi - lo - chi + clo}; compile and dump {took:.1f} s")
    if len(out) == 2:
        say(f"[bundles] {which}: parent / shipped = "
            f"{out['parent'] / out['shipped']:.3f}; the MXUs' streaming "
            f"floor is {geom.ws * 24 // 4} (ws x 24 products / 4 MXUs)")
    return out


# ---- on the chip (or interpreted) -----------------------------------------

def time_forms(which, arrays, geom, interpret, reps):
    """ms a sweep of each form over one plan, and the two tables."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rows = geom.n_groups * geom.rg
    rt = jax.random.uniform(jax.random.PRNGKey(45), (rows, 128),
                            jnp.float32, 0.5, 1.5) / (geom.r8 * 128)
    tables = {}
    for form in FORMS:
        fn = table_fn(form, geom, interpret)
        t0 = time.perf_counter()
        out = fn(arrays[0], arrays[1], rt, *arrays[2:]).block_until_ready()
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(arrays[0], arrays[1], rt, *arrays[2:])
        out.block_until_ready()
        each = (time.perf_counter() - t0) / reps
        slots = geom.shard_slots
        say(f"[kernel] {which} rg {geom.rg} ws {geom.ws} {form}: "
            f"{each * 1e3:.2f} ms a sweep, {each / slots * 1e9:.3f} ns a "
            f"slot ({slots // 1024} chunks in "
            f"{geom.n_steps // geom.seg_steps} calls); first call "
            f"{first:.2f} s")
        tables[form] = np.asarray(out)[:geom.rows_out]
        del out
    differ = tables["parent"].view(np.uint32) != \
        tables["shipped"].view(np.uint32)
    say(f"[bits] {which}: {int(differ.sum())} of {differ.size} cells "
        f"differ between the parent's table and the shipped form's "
        f"({int((tables['parent'] != 0).sum())} cells written); largest "
        f"difference {np.abs(tables['parent'] - tables['shipped']).max()}")
    return int(differ.sum())


def main(argv) -> int:
    if "--compile-one" in argv:
        at = argv.index("--compile-one")
        compile_one(argv[at + 1], argv[at + 2])
        return 0
    geoms = argv[argv.index("--geoms") + 1].split(",") \
        if "--geoms" in argv else list(GEOMS)
    if "--bundles" in argv:
        for which in geoms:
            bundles(which)
        return 0
    rehearse = "--rehearse" in argv
    if rehearse:
        geoms = geoms[:2]           # one block of SCALE 14 says it all
    import jax

    import step0_pagerank_resident as resident
    import step0_pagerank_sharded as sharded
    from tpu_distalg.ops import pallas_pagerank as ppr
    from tpu_distalg.parallel import get_mesh
    from tpu_distalg.utils import compile_cache

    compile_cache.configure()
    dev = jax.devices()[0]
    interpret = dev.platform != "tpu"
    if interpret and not rehearse:
        print("step0_pagerank_overlap: no chip", file=sys.stderr)
        return 2
    say(f"[step0] {dev.platform} {dev.device_kind!r}")
    reps = 1 if rehearse else 3
    bad = 0

    # the resident cell's geometry: the program's own loader and planner
    if "resident" in geoms:
        scale = 12 if rehearse else GEOMS["resident"][0]
        graph, spmv = resident.plan_once(get_mesh(data=1, model=1), scale,
                                         45)
        geom = graph.geom
        del graph
        bad += time_forms("resident", spmv.arrays, geom, interpret, reps)
        del spmv
        gc.collect()

    # a shard of four: range 0 of 4, planned on one chip
    sharded.BY_KEY = jax.jit(sharded._by_key, donate_argnums=(0, 1, 2))
    differ = 0
    for which in (g for g in geoms if g != "resident"):
        scale = 14 if rehearse else GEOMS[which][0]
        V, n_in = 1 << scale, 16 << scale
        geom = ppr.spmv_geometry(V, n_in, sharded.SHARDS)
        src, dst, _ = sharded.block_edges(
            scale, 3_000_000_019 & 0xFFFFFFFF, 2 if rehearse else 8)
        arrays, span = sharded.plan_block(src, dst, geom)
        say(f"[plan] {which}: span {span} of ws {geom.ws} at rg {geom.rg}")
        bad += time_forms(which, arrays, geom, interpret, reps)
        differ += against_segment_sum(which, arrays, geom, src, dst, V,
                                      interpret)
        del arrays, src, dst
        gc.collect()
    return 1 if bad or differ else 0


def against_segment_sum(which, arrays, geom, src, dst, V, interpret):
    """Cells of the shipped form's table that differ from XLA's
    ``segment_sum`` of equal contributions (a power of two)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rt = jnp.full((geom.n_groups * geom.rg, 128), 1.0 / V, jnp.float32)
    got = np.asarray(table_fn("shipped", geom, interpret)(
        arrays[0], arrays[1], rt, *arrays[2:]))[:geom.rows_out].reshape(-1)
    want = np.asarray(jax.jit(lambda s, d: jax.ops.segment_sum(
        jnp.where(s >= 0, 1.0 / V / 16, 0.0), d,
        num_segments=geom.rows_out * 128))(src, dst))
    differ = int((got.view(np.uint32) != want.view(np.uint32)).sum())
    say(f"[bits] {which}: {differ} of {got.size} cells differ between "
        f"the shipped form's table and segment_sum over the block's "
        f"{int((src >= 0).sum())} edges")
    return differ


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
