#!/usr/bin/env python3
"""Step 0 of PR 38 on the chip: what each piece of the resident
PageRank path costs alone at Graph500 SCALE 24, before the design is
fixed (readings in PERF.md section 6). Kept as the way to re-read
``SPMV_UNROLL`` in ``tpu_distalg/ops/pallas_pagerank.py`` (iii).

    chiprun -- python3 scripts/step0_pagerank_resident.py [--scale 24]
    JAX_PLATFORMS=cpu python3 scripts/step0_pagerank_resident.py --rehearse

(i) the generator, the dedup sort and the plan's sort and layout on the
device, seconds each warm and cold, and the peak bytes; (ii) the widest
destination span of a chunk over three seeds, against the window the
geometry fixes from the sizes; (iii) the kernel alone, ns an edge slot,
with the gather loop rolled (1 tile a turn) and partly unrolled, at the
cell's geometry and at rg 128 (SCALE 20) where the whole loop unrolls;
(iv) Mosaic's compile seconds; (v) a 10-sweep call, the reference's
wall, and the ranks against the reference.
"""

from __future__ import annotations

import gc
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "benchmarks")):
    if p not in sys.path:
        sys.path.insert(0, p)


def say(msg):
    print(msg, flush=True)


def spans_since(mark: int):
    from tpu_distalg.telemetry import events

    return [f for f in events.finished() if f.id > mark]


def plan_once(mesh, scale, seed):
    from tpu_distalg.models import pagerank
    from tpu_distalg.telemetry import events

    done = events.finished()
    mark = done[-1].id if done else 0
    t0 = time.perf_counter()
    graph = pagerank.build_rmat_graph(mesh, scale, 16, None, seed)
    spmv = pagerank.prepare_device_spmv(graph, mesh)
    wall = time.perf_counter() - t0
    own = {f.name: f for f in spans_since(mark)
           if f.name.startswith("pagerank:")}
    compiles = sum(f.seconds for f in spans_since(mark)
                   if f.name == "jit:compile")
    say(f"[plan] scale {scale} seed {seed}: wall {wall:.2f} s (compile "
        f"{compiles:.2f}); " + ", ".join(
            f"{n.split(':')[1]} {f.seconds:.2f}" for n, f in own.items())
        + f"; distinct {graph.n_edges} of {graph.n_in}; span "
        f"{own['pagerank:plan'].fields.get('span')} of ws "
        f"{graph.geom.ws} at rg {graph.geom.rg}; slots "
        f"{graph.geom.n_slots} ({graph.geom.n_slots / graph.n_edges:.4f}"
        f" a distinct edge); plan ok {spmv is not None}")
    return graph, spmv


def time_kernel(spmv, V, unroll, reps=3):
    import jax
    import jax.numpy as jnp

    from tpu_distalg.ops import pallas_pagerank as ppr

    rows = spmv.n_groups * spmv.rg
    rt = jnp.full((rows, 128), 1.0 / V, jnp.float32)

    def call():
        return ppr.spmv_table(
            spmv.gbase, spmv.sbase, rt, spmv.src_lane, spmv.src_row,
            spmv.dst_row, spmv.dst_lane, spmv.w_e, rg=spmv.rg,
            ws=spmv.ws, r8=spmv.r8, blk=spmv.blk,
            seg_steps=spmv.seg_steps or None, unroll=unroll,
            interpret=jax.devices()[0].platform != "tpu")

    t0 = time.perf_counter()
    call().block_until_ready()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        out = call()
    out.block_until_ready()
    each = (time.perf_counter() - t0) / reps
    slots = spmv.n_chunks * 1024
    say(f"[kernel] rg {spmv.rg} ws {spmv.ws} unroll {unroll}: "
        f"{each * 1e3:.1f} ms a sweep, {each / slots * 1e9:.3f} ns a "
        f"slot ({slots} slots); first call {first:.2f} s")
    return each


def main(argv) -> int:
    rehearse = "--rehearse" in argv
    scale = int(argv[argv.index("--scale") + 1]) if "--scale" in argv \
        else (12 if rehearse else 24)
    small = 10 if rehearse else 20
    import jax
    import jax.numpy as jnp
    import numpy as np

    from reference import pagerank_resident_ref as ref
    from tpu_distalg.models import pagerank
    from tpu_distalg.parallel import get_mesh
    from tpu_distalg.utils import compile_cache

    compile_cache.configure()
    dev = jax.devices()[0]
    say(f"[step0] {dev.platform} {dev.device_kind!r}; scale {scale}")
    mesh = get_mesh(data=1, model=1)
    cfg = pagerank.PageRankConfig(n_iterations=10, mode="standard",
                                  scatter="spmv")
    abcd = (0.57, 0.19, 0.19, 0.05)

    # (iii) at rg 128: the loop rolled, partly and wholly unrolled
    graph, spmv = plan_once(mesh, small, 5)
    for unroll in (1, 4, spmv.rg // 8):
        time_kernel(spmv, 1 << small, unroll)
    de = pagerank.spmv_device_edges(graph, mesh)
    fn = pagerank.make_run_fn(mesh, cfg, 1 << small, None, spmv)
    r = np.asarray(fn(de.src, de.dst, de.w_e, de.emask, de.has_out,
                      de.n_ref)[0])
    r_ref, n_ref = ref.ranks(small, 16, abcd, 5, 0.15, 10)
    say(f"[check] scale {small}: distinct {graph.n_edges} / reference "
        f"{n_ref}; l1 {ref.l1_err(r, r_ref):.3g} max "
        f"{ref.max_rel_err(r, r_ref):.3g} sum-1 {abs(r.sum() - 1):.3g}")
    del graph, spmv, de, fn
    gc.collect()

    # (i), (ii): three seeds at the cell's scale (the first cold)
    seeds = (77,) if "--one-seed" in argv else (1, 2_999_999_929, 77)
    for seed in seeds:
        graph, spmv = plan_once(mesh, scale, seed)
        stats = dev.memory_stats() or {}
        say(f"[memory] peak {stats.get('peak_bytes_in_use', 0) / 1e9:.3f}"
            f" GB, in use {stats.get('bytes_in_use', 0) / 1e9:.3f} GB; "
            f"plan {spmv.nbytes / 1e9:.3f} GB")
        if seed != seeds[-1]:
            del graph, spmv
            gc.collect()

    # (iii), (iv) at the cell's geometry
    for unroll in (1, 4, 8, 16, spmv.rg // 8):
        time_kernel(spmv, 1 << scale, unroll, reps=2)

    # (v) a call of 10 sweeps, then the reference
    de = pagerank.spmv_device_edges(graph, mesh)
    fn = pagerank.make_run_fn(mesh, cfg, 1 << scale, None, spmv)
    for i in range(2):
        t0 = time.perf_counter()
        ranks = fn(de.src, de.dst, de.w_e, de.emask, de.has_out,
                   de.n_ref)[0].block_until_ready()
        say(f"[run] call {i}: {time.perf_counter() - t0:.3f} s for 10 "
            f"sweeps over {graph.n_edges} distinct edges")
    r = np.asarray(ranks)
    del spmv, fn, ranks, de
    gc.collect()
    t0 = time.perf_counter()
    r_ref, n_ref = ref.ranks(scale, 16, abcd, seeds[-1], 0.15, 10)
    say(f"[reference] {time.perf_counter() - t0:.2f} s; distinct "
        f"{n_ref} / program {graph.n_edges}; l1 "
        f"{ref.l1_err(r, r_ref):.3g} max {ref.max_rel_err(r, r_ref):.3g}"
        f" sum-1 {abs(r.sum() - 1):.3g}")
    t0 = time.perf_counter()
    r_low, _ = ref.ranks(scale, 16, abcd, seeds[-1], 0.15, 10,
                         jnp.bfloat16)
    say(f"[control] bfloat16 reference {time.perf_counter() - t0:.2f} s:"
        f" l1 {ref.l1_err(r_low, r_ref):.3g} max "
        f"{ref.max_rel_err(r_low, r_ref):.3g} sum-1 "
        f"{abs(float(r_low.astype(np.float64).sum()) - 1):.3g}")
    stats = dev.memory_stats() or {}
    say(f"[memory] peak {stats.get('peak_bytes_in_use', 0) / 1e9:.3f} GB")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
