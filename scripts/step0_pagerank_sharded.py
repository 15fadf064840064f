#!/usr/bin/env python3
"""Step 0 (b) of PR 44 on ONE chip: the fused kernel on the block a
shard of the four-chip cell sweeps, a quarter of Graph500 SCALE 26 by
destination range, at each gather-group height the span law weighs
(readings in PERF.md section 6). Kept as the way to re-read
``SPMV_RGS`` and the schedule law's two constants in
``tpu_distalg/ops/pallas_pagerank.py``.

    chiprun -- python3 scripts/step0_pagerank_sharded.py [--scale 26]
    JAX_PLATFORMS=cpu python3 scripts/step0_pagerank_sharded.py --rehearse

The chip draws every edge id in pieces and keeps the edges that point
into the first quarter of the table (what the exchange hands shard 0
of four, to within the balance of its cut), deduplicates them, and for each
height plans the block by the program's own ``sort_slots`` /
``slot_arrays`` with the window the law gives and times ``spmv_table``
on it: ms a sweep, ns a slot, the widest span against the window, and
the bundles the law predicts beside. At the height the geometry picks
the kernel's table is compared with XLA's ``segment_sum`` on the same
block."""

from __future__ import annotations

import gc
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SHARDS = 4
BY_KEY = None     # the plan's sort, jitted once (main)


def say(msg):
    print(msg, flush=True)


def block_edges(scale: int, seed: int, pieces: int):
    """Range 0's distinct edges of the SCALE ``scale`` graph on the
    host: (src, dst) int32, -1 where a slot holds a repeated edge, the
    geometry's spare slots NOT appended."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_distalg.ops import pallas_pagerank as ppr
    from tpu_distalg.utils import datasets

    V, n_in = 1 << scale, 16 << scale
    geom = ppr.spmv_geometry(V, n_in, SHARDS)
    piece = n_in // pieces
    keep = geom.shard_cap // pieces
    draw = datasets.kronecker_edges(scale)

    @jax.jit
    def one_piece(p, seed):
        ids = p * np.uint32(piece) + jnp.arange(piece, dtype=jnp.uint32)
        src, dst = draw(ids, seed)
        mine = (dst >> 7) < geom.r8 // SHARDS     # an equal quarter
        src, dst = jax.lax.sort((jnp.where(mine, src, V), dst),
                                num_keys=1, is_stable=False)
        return src[:keep], dst[:keep], jnp.sum(mine)

    @jax.jit
    def dedup(src, dst):
        src, dst = jax.lax.sort((src, dst), num_keys=2, is_stable=False)
        again = (src[1:] == src[:-1]) & (dst[1:] == dst[:-1])
        real = (src < V) & ~jnp.concatenate([jnp.zeros((1,), bool), again])
        return jnp.where(real, src, -1), dst, jnp.sum(real)

    t0 = time.perf_counter()
    parts = [one_piece(np.uint32(p), np.uint32(seed))
             for p in range(pieces)]
    held = max(int(n) for _, _, n in parts)
    if held > keep:
        raise RuntimeError(f"a piece held {held} edges of range 0, the "
                           f"bucket {keep}")
    src, dst, n = dedup(jnp.concatenate([s for s, _, _ in parts]),
                        jnp.concatenate([d for _, d, _ in parts]))
    del parts
    out = np.asarray(src), np.asarray(dst), int(n)
    say(f"[block] SCALE {scale} range 0 of {SHARDS}: {out[2]} distinct "
        f"edges in {len(out[0])} slots (a shard's capacity "
        f"{geom.shard_cap}), drawn and deduplicated in "
        f"{time.perf_counter() - t0:.1f} s")
    return out


def same_slots(geoms):
    """The geometries with one slot count a shard (the most any of
    them needs, in ten calls a sweep), so that the plan's sort compiles
    once for all of them: a height's extra steps hold no edge and are
    skipped."""
    import dataclasses

    seg = -(-max(g.n_steps for g in geoms) // 10)
    return [dataclasses.replace(g, seg_steps=seg, n_steps=10 * seg)
            for g in geoms]


def plan_programs(geom, n_held: int):
    """The program's plan of a block of ``n_held`` slots at ``geom``
    in three programs, ``keys(src, dst) -> (key, src, dst)``,
    ``by_key(key, src, dst) -> (src, dst)`` (the same for every
    geometry of :func:`same_slots`) and ``lay_out(src, dst) -> (the
    seven arrays, the widest span)``."""
    import jax
    import jax.numpy as jnp

    from tpu_distalg.ops import pallas_pagerank as ppr

    spare = geom.shard_slots - n_held

    def keys(src, dst):
        src = jnp.concatenate([src, jnp.full((spare,), -1, jnp.int32)])
        dst = jnp.concatenate([dst, jnp.zeros((spare,), jnp.int32)])
        return ppr.slot_keys(src, dst, geom=geom,
                             n_in=geom.shard_cap), src, dst

    def lay_out(src, dst):
        w_e = jnp.full(src.shape, 1.0 / 16, jnp.float32)
        return ppr.slot_arrays(jnp, src, dst, w_e, geom)

    return (jax.jit(keys), BY_KEY, jax.jit(lay_out, donate_argnums=(0, 1)))


def _by_key(key, src, dst):
    import jax

    return jax.lax.sort((key, src, dst), num_keys=1, is_stable=False)[1:]


def plan_block(src, dst, geom):
    keys, by_key, lay_out = plan_programs(geom, len(src))
    arrays, span = lay_out(*by_key(*keys(src, dst)))
    return arrays, int(span)


def time_kernel(arrays, geom, reps: int):
    import jax
    import jax.numpy as jnp

    from tpu_distalg.ops import pallas_pagerank as ppr

    V = geom.r8 * 128
    rt = jnp.full((geom.n_groups * geom.rg, 128), 1.0 / V, jnp.float32)

    def call():
        return ppr.spmv_table(
            arrays[0], arrays[1], rt, *arrays[2:], rg=geom.rg,
            ws=geom.ws, r8=geom.rows_out, blk=geom.blk,
            seg_steps=geom.seg_steps,
            interpret=jax.devices()[0].platform != "tpu")

    t0 = time.perf_counter()
    out = call().block_until_ready()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        out = call()
    out.block_until_ready()
    return (time.perf_counter() - t0) / reps, first, out


def main(argv) -> int:
    rehearse = "--rehearse" in argv
    scale = int(argv[argv.index("--scale") + 1]) if "--scale" in argv \
        else (14 if rehearse else 26)
    heights = [int(x) for x in argv[argv.index("--rgs") + 1].split(",")] \
        if "--rgs" in argv else (
            [32, 64] if rehearse else [512, 768, 1024, 1536, 2048])
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_distalg.ops import pallas_pagerank as ppr
    from tpu_distalg.utils import compile_cache

    compile_cache.configure()
    dev = jax.devices()[0]
    V, n_in = 1 << scale, 16 << scale
    chosen = ppr.spmv_geometry(V, n_in, SHARDS)
    say(f"[step0] {dev.platform} {dev.device_kind!r}; SCALE {scale} on "
        f"{SHARDS} shards; the geometry picks rg {chosen.rg} ws "
        f"{chosen.ws}")
    src, dst, n_edges = block_edges(scale, 3_000_000_019 & 0xFFFFFFFF,
                                    2 if rehearse else 8)
    global BY_KEY
    BY_KEY = jax.jit(_by_key, donate_argnums=(0, 1, 2))
    for geom in same_slots([ppr.spmv_geometry(V, n_in, SHARDS, rg)
                            for rg in heights]):
        law = (350 + ppr.SPMV_GATHER_ROW * geom.rg
               + ppr.SPMV_SCATTER_ROW * geom.ws)
        t0 = time.perf_counter()
        arrays, span = plan_block(src, dst, geom)
        t_plan = time.perf_counter() - t0
        each, first, table = time_kernel(arrays, geom,
                                         1 if rehearse else 3)
        slots = geom.shard_slots
        say(f"[kernel] rg {geom.rg} ws {geom.ws} groups {geom.n_groups}:"
            f" {each * 1e3:.1f} ms a sweep, {each / slots * 1e9:.3f} ns "
            f"a slot ({slots} slots, {slots // 1024} chunks); span "
            f"{span} of {geom.ws}; the law says {law:.0f} bundles a "
            f"chunk = {slots / 1024 * law / 1.5e6:.1f} ms at 1.5 GHz; "
            f"plan {t_plan:.1f} s, first call {first:.1f} s")
        if geom.rg == chosen.rg:
            real = src >= 0
            want = jax.jit(lambda s, d: jax.ops.segment_sum(
                jnp.where(s >= 0, 1.0 / V / 16, 0.0), d,
                num_segments=geom.rows_out * 128))(src, dst)
            got = np.asarray(table)[:geom.rows_out].reshape(-1)
            err = np.abs(got - np.asarray(want)).max() \
                / np.asarray(want).max()
            say(f"[kernel] rg {geom.rg}: table against segment_sum on "
                f"the block's {int(real.sum())} edges: largest "
                f"difference {err:.2e} of the largest cell")
            if not err < 1e-5:
                return 1
        del arrays, table
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
