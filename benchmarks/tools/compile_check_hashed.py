#!/usr/bin/env python3
"""Compile the hashed-row SSGD cell's programs at their real shapes for
a described ``v5e:2x2`` topology, with no chip attached: the program's
loader of the table and its segment function (the sibling of
``tools/compile_check.py`` for the ``ssgd_hashed`` family).

Run by hand before the first chip call of a cell (``JAX_PLATFORMS=cpu
python3 benchmarks/tools/compile_check_hashed.py [cell ...]``); it costs
no chip time and raises what the chip's compiler would raise (VMEM,
SMEM, tiling, HBM). Nothing runs, so it gives no time and no result: a
compile that passes is not a chip run. It prints the per-device bytes
XLA plans and how many Mosaic kernels (``tpu_custom_call``) a step
holds: two where the passes' form is ``vmem``, none where it is ``xla``.
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


def compile_hashed(cell: mf.Cell, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from families import ssgd_hashed as fam
    from tpu_distalg.models import ssgd

    c, t = cell.config, cell.traffic
    sh = fam.shapes(c, t)
    shards = c["data_shards"]
    mesh = Mesh(np.array(topo.devices[:shards]).reshape(shards, 1),
                ("data", "model"))
    config = fam.program_config(c, t)
    args = fam.loader_args(c)
    meta = dict(row_format="hashed", nnz=c["nnz"],
                hash_bits=c["hash_bits"], pack=1, n_rows=c["n_rows"],
                n_padded=sh["n_padded"], d_total=sh["d_total"])
    geom = ssgd.hashed_geometry(config, meta)
    rep = NamedSharding(mesh, P())

    def scalar(dtype):
        return jax.ShapeDtypeStruct((), dtype, sharding=rep)

    kw = tuple(sorted((k, v) for k, v in args.items()
                      if k != "cardinalities"))
    table = ssgd.hashed_table_fn(
        mesh, c["n_rows"], sh["n_padded"], geom, args["cardinalities"],
        kw).lower(scalar(jnp.int32)).compile()
    X = jax.ShapeDtypeStruct(
        (sh["n_blocks"] * shards, geom.fields_held, geom.block_rows),
        jnp.int32, sharding=NamedSharding(mesh, P("data", None, None)))
    d = jax.ShapeDtypeStruct((1,), jnp.float32, sharding=rep)
    w = jax.ShapeDtypeStruct((sh["d_total"],), jnp.float32, sharding=rep)
    # the segment function reads the mesh's platform: a described chip
    # is one, so the passes compile and are not interpreted
    seg = ssgd.make_train_fn_fused(mesh, config, meta).lower(
        X, d, d, d, d, w, t0=0).compile()
    return {"table": table.memory_analysis(),
            "segment": seg.memory_analysis(), "form": geom.pass_form,
            "tpu_custom_call": seg.as_text().count("tpu_custom_call"),
            "x_bytes_per_device": sh["n_padded"] * geom.row_bytes // shards}


def main(argv) -> int:
    from jax.experimental import topologies

    manifest = os.path.join(ROOT, "BENCHMARK.json")
    cells = [mf.Cell(manifest, w["name"])
             for w in mf.load_json(manifest)["workloads"]
             if not argv or w["name"] in argv]
    cells = [c for c in cells if c.config["family"] == "ssgd_hashed"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bad = 0
    for cell in cells:
        got = compile_hashed(cell, topo)
        want = 2 if got["form"] == "vmem" else 0
        ok = got["tpu_custom_call"] >= want
        bad += not ok
        print(f"[compile] {cell.name}: {'ok' if ok else 'MISSING'} passes "
              f"{got['form']} tpu_custom_call x{got['tpu_custom_call']} "
              f"table {got['x_bytes_per_device'] / 1e9:.3f} GB/device\n"
              f"  table   {got['table']}\n  segment {got['segment']}",
              flush=True)
    return 1 if bad or not cells else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
