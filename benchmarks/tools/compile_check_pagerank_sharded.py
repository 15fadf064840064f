#!/usr/bin/env python3
"""Compile the sharded PageRank cell's programs at their real shapes
for a described ``v5e:2x2`` topology, with no chip attached: the
program's draw, the exchange by destination range, the dedup sort, the
plan's sort and layout and the jitted run of ``n_iterations`` fused
sweeps, every edge array sharded over the four chips (the sibling of
``tools/compile_check_pagerank.py`` for the ``pagerank_sharded``
family).

Run by hand before a four-chip call (``JAX_PLATFORMS=cpu python3
benchmarks/tools/compile_check_pagerank_sharded.py [--scale N]
[--rg N]``); it costs no chip time and raises what the chip's compiler
would raise (HBM, VMEM, SMEM, tiling). Nothing runs, so it gives no
time and no result: a compile that passes is not a chip run. It prints
the per-device bytes XLA plans for each program and the collectives
the run holds."""

from __future__ import annotations

import os
import re
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


def compile_all(c: dict, topo, rg: int | None = None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_distalg.models import pagerank
    from tpu_distalg.ops import pallas_pagerank as ppr

    shards = c["data_shards"]
    mesh = Mesh(np.array(topo.devices[:shards]).reshape(shards, 1),
                ("data", "model"))
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))

    def arr(shape, dtype, sharding=row):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    V, n_in = 1 << c["scale"], c["edge_factor"] << c["scale"]
    geom = ppr.spmv_geometry(V, n_in, shards, rg)
    out = {"geom": geom}

    def timed(name, fn, *args):
        t0 = time.perf_counter()
        out[name] = fn.lower(*args).compile()
        out[name + "_s"] = time.perf_counter() - t0
        print(f"  {name:9s} ({out[name + '_s']:.1f} s) "
              f"{out[name].memory_analysis()}", flush=True)

    generate, dedup = pagerank.rmat_programs(mesh, c["scale"],
                                             c["abcd"], geom, n_in)
    sort, lay_out = pagerank.plan_programs(mesh, geom, n_in)
    drawn = arr((n_in,), jnp.int32)
    slots = arr((geom.n_slots,), jnp.int32)
    timed("generate", generate, arr((), jnp.uint32, rep))
    timed("exchange", pagerank.exchange_program(mesh, c["scale"], geom),
          drawn, drawn)
    timed("dedup", dedup, slots, slots)
    timed("sort", sort, slots, slots)
    timed("lay_out", lay_out, slots, slots, arr((V,), jnp.float32, rep))
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

    timed("run", jax.jit(run), arr((V,), jnp.float32, rep),
          arr((geom.n_chunks,), jnp.int32),
          arr((geom.n_chunks,), jnp.int32),
          *[arr(per_slot, jnp.int32)] * 4, arr(per_slot, jnp.float32))
    text = out["run"].as_text()
    out["collectives"] = sorted(set(re.findall(
        r"\b(all-gather|all-reduce|all-to-all|collective-permute)"
        r"(?:-start)?\b", text)))
    out["kernels"] = text.count("tpu_custom_call")
    return out


def main(argv) -> int:
    from jax.experimental import topologies

    opts = {"--scale": 0, "--rg": 0}
    for name in opts:
        if name in argv:
            at = argv.index(name)
            opts[name] = int(argv[at + 1])
            argv = argv[:at] + argv[at + 2:]
    c = mf.load_json(os.path.join(
        BENCH, "configs", "pagerank-graph500-sharded4.json"))
    if opts["--scale"]:
        c = dict(c, scale=opts["--scale"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    got = compile_all(c, topo, opts["--rg"] or None)
    print(f"[compile] pagerank SCALE {c['scale']} on "
          f"{c['data_shards']} shards: {got['geom']}; the run holds "
          f"{got['kernels']} kernel call(s) and {got['collectives']}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
