#!/usr/bin/env python3
"""Compile the k-means cells' programs at their real shapes for a
described ``v5e:2x2`` topology, with no chip attached: the benchmark's
table generator and the program's Lloyd segment
(``tools/compile_check.py`` keys its families in a dict of its own; this
is its sibling for the ``kmeans`` family).

Run by hand before the first chip call of a cell (``JAX_PLATFORMS=cpu
python3 benchmarks/tools/compile_check_kmeans.py [cell ...]``); it costs
no chip time and raises what the chip's compiler would raise (VMEM,
tiling, HBM). Nothing runs, so it gives no time and no result: a compile
that passes is not a chip run. It prints the per-device bytes XLA plans
and whether the Mosaic kernel (``tpu_custom_call``) is in the segment.
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


def compile_kmeans(cell: mf.Cell, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from families import kmeans as fam
    from tpu_distalg.models import kmeans

    c, t = cell.config, cell.traffic
    sh = fam.shapes(c, t)
    shards = c["data_shards"]
    mesh = Mesh(np.array(topo.devices[:shards]).reshape(shards, 1),
                ("data", "model"))
    lanes, config = fam.program_parts(c, t)
    rep = NamedSharding(mesh, P())

    def scalar(dtype):
        return jax.ShapeDtypeStruct((), dtype, sharding=rep)

    table = fam.table_fn(c, sh, lanes, mesh).lower(
        scalar(jnp.int32)).compile()
    X4 = jax.ShapeDtypeStruct(
        (sh["n_blocks"], c["dim"], lanes.block_rows, 128), jnp.float32,
        sharding=NamedSharding(mesh, P("data", None, None, None)))
    centers = jax.ShapeDtypeStruct((c["k"], c["dim"]), jnp.float32,
                                   sharding=rep)
    seg = kmeans.make_fit_seg_fn(
        mesh, config, t["iterations_per_call"], lanes).lower(
            X4, scalar(jnp.int32), centers, scalar(jnp.float32),
            scalar(jnp.int32)).compile()
    text = seg.as_text()
    return {"table": table.memory_analysis(),
            "segment": seg.memory_analysis(),
            "tpu_custom_call": text.count("tpu_custom_call"),
            "all_reduce": text.count("all-reduce("),
            "x4_bytes_per_device": sh["resident_bytes"] // shards}


def main(argv) -> int:
    from jax.experimental import topologies

    manifest = os.path.join(ROOT, "BENCHMARK.json")
    cells = [mf.Cell(manifest, w["name"])
             for w in mf.load_json(manifest)["workloads"]
             if not argv or w["name"] in argv]
    cells = [c for c in cells if c.config["family"] == "kmeans"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bad = 0
    for cell in cells:
        got = compile_kmeans(cell, topo)
        ok = got["tpu_custom_call"] > 0 and (
            cell.chips == 1 or got["all_reduce"] > 0)
        bad += not ok
        print(f"[compile] {cell.name}: {'ok' if ok else 'MISSING'} "
              f"tpu_custom_call x{got['tpu_custom_call']} all-reduce "
              f"x{got['all_reduce']} X4 "
              f"{got['x4_bytes_per_device'] / 1e9:.3f} GB/device\n"
              f"  table   {got['table']}\n  segment {got['segment']}",
              flush=True)
    return 1 if bad or not cells else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
