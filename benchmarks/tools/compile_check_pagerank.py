#!/usr/bin/env python3
"""Compile the resident PageRank cell's programs at their real shapes
for a described ``v5e:2x2`` topology, with no chip attached: the
program's draw, its dedup sort, the plan's sort and layout, and the
jitted run of ``n_iterations`` fused sweeps (the sibling of
``tools/compile_check_als.py`` for the ``pagerank_resident`` family).

Run by hand before the first chip call of a cell (``JAX_PLATFORMS=cpu
python3 benchmarks/tools/compile_check_pagerank.py [--scale N]
[--shards N]``); it costs no chip time and raises what the chip's
compiler would raise (HBM, VMEM, SMEM, tiling). Nothing runs, so it
gives no time and no result: a compile that passes is not a chip run.
It prints the per-device bytes XLA plans for each program."""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import manifest as mf  # noqa: E402


def compile_all(c: dict, topo, shards: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_distalg.models import pagerank
    from tpu_distalg.ops import pallas_pagerank as ppr

    mesh = Mesh(np.array(topo.devices[:shards]).reshape(shards, 1),
                ("data", "model"))
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))

    def arr(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    V, n_in = 1 << c["scale"], c["edge_factor"] << c["scale"]
    geom = ppr.spmv_geometry(V, n_in, shards)
    slots = arr((geom.n_slots,), jnp.int32)
    out = {"geom": geom}

    def timed(name, fn, *args):
        t0 = time.perf_counter()
        out[name] = fn.lower(*args).compile().memory_analysis()
        out[name + "_s"] = time.perf_counter() - t0
        print(f"  {name:9s} ({out[name + '_s']:.1f} s) {out[name]}",
              flush=True)

    generate, dedup = pagerank.rmat_programs(mesh, c["scale"],
                                             c["abcd"], geom, n_in)
    sort, lay_out = pagerank.plan_programs(mesh, geom, n_in)
    timed("generate", generate, arr((), jnp.uint32))
    timed("dedup", dedup, slots, slots)
    timed("sort", sort, slots, slots)
    timed("lay_out", lay_out, slots, slots, arr((V,), jnp.float32))
    per_slot = (geom.n_chunks * 8, 128)
    config = pagerank.PageRankConfig(
        n_iterations=c["n_iterations"], q=c["q"], mode=c["mode"],
        redistribute_dangling=c["redistribute_dangling"],
        scatter=c["scatter"])

    def run(has_out, *plan):
        # the program's run function closes over its plan: here the
        # plan's arrays are arguments, shapes alone
        fn = pagerank.make_run_fn(mesh, config, V, None,
                                  pagerank.DeviceSpMV.of(plan, geom))
        return fn(None, None, None, None, has_out, None)

    timed("run", jax.jit(run), arr((V,), jnp.float32),
          arr((geom.n_chunks,), jnp.int32, row),
          arr((geom.n_chunks,), jnp.int32, row),
          *[arr(per_slot, jnp.int32, row)] * 4,
          arr(per_slot, jnp.float32, row))
    return out


def main(argv) -> int:
    from jax.experimental import topologies

    opts = {"--shards": 1, "--scale": 0}
    for name in opts:
        if name in argv:
            at = argv.index(name)
            opts[name] = int(argv[at + 1])
            argv = argv[:at] + argv[at + 2:]
    c = mf.load_json(os.path.join(
        BENCH, "configs", "pagerank-graph500-24.json"))
    if opts["--scale"]:
        c = dict(c, scale=opts["--scale"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    got = compile_all(c, topo, opts["--shards"])
    print(f"[compile] pagerank SCALE {c['scale']} on {opts['--shards']} "
          f"shard(s): {got['geom']}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
