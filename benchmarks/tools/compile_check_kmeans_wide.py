#!/usr/bin/env python3
"""Compile the wide k-means cells' programs at their real shapes for a
described ``v5e:2x2`` topology, with no chip attached: the benchmark's
table generator and the program's Lloyd segment (the sibling of
``tools/compile_check_kmeans.py`` for the ``kmeans_wide`` family).

Run by hand before the first chip call of a cell (``JAX_PLATFORMS=cpu
python3 benchmarks/tools/compile_check_kmeans_wide.py [cell ...]``); it
costs no chip time and raises what the chip's compiler would raise
(VMEM, tiling, HBM). Nothing runs, so it gives no time and no result: a
compile that passes is not a chip run. It prints the per-device bytes
XLA plans and how many Mosaic kernels (``tpu_custom_call``) the segment
holds: two, the assign and the stats kernel.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import manifest as mf  # noqa: E402


def compile_kmeans_wide(cell: mf.Cell, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from families import kmeans_wide as fam
    from tpu_distalg.models import kmeans

    c, t = cell.config, cell.traffic
    sh = fam.shapes(c, t)
    shards = c["data_shards"]
    mesh = Mesh(np.array(topo.devices[:shards]).reshape(shards, 1),
                ("data", "model"))
    geom, config = fam.program_parts(c, t)
    rep = NamedSharding(mesh, P())

    def scalar(dtype):
        return jax.ShapeDtypeStruct((), dtype, sharding=rep)

    table = fam.table_fn(c, sh, geom, mesh).lower(
        scalar(jnp.int32)).compile()
    X3 = jax.ShapeDtypeStruct(
        (sh["n_blocks"], geom.dim_held, geom.block_points), jnp.float32,
        sharding=NamedSharding(mesh, P("data", None, None)))
    centers = jax.ShapeDtypeStruct((c["k"], c["dim"]), jnp.float32,
                                   sharding=rep)
    seg = kmeans.make_fit_seg_fn(
        mesh, config, t["iterations_per_call"], geom).lower(
            X3, scalar(jnp.int32), centers, scalar(jnp.float32),
            scalar(jnp.int32)).compile()
    return {"table": table.memory_analysis(),
            "segment": seg.memory_analysis(),
            "tpu_custom_call": seg.as_text().count("tpu_custom_call"),
            "x3_bytes_per_device": sh["resident_bytes"] // shards}


def main(argv) -> int:
    from jax.experimental import topologies

    manifest = os.path.join(ROOT, "BENCHMARK.json")
    cells = [mf.Cell(manifest, w["name"])
             for w in mf.load_json(manifest)["workloads"]
             if not argv or w["name"] in argv]
    cells = [c for c in cells if c.config["family"] == "kmeans_wide"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bad = 0
    for cell in cells:
        got = compile_kmeans_wide(cell, topo)
        ok = got["tpu_custom_call"] >= 2
        bad += not ok
        print(f"[compile] {cell.name}: {'ok' if ok else 'MISSING'} "
              f"tpu_custom_call x{got['tpu_custom_call']} X3 "
              f"{got['x3_bytes_per_device'] / 1e9:.3f} GB/device\n"
              f"  table   {got['table']}\n  segment {got['segment']}",
              flush=True)
    return 1 if bad or not cells else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
