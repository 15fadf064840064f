#!/usr/bin/env python3
"""Compile the indexed-row SSGD cell's programs at their real shapes
for a described ``v5e:2x2`` topology, with no chip attached: the
program's loader of the table and its segment function (the sibling of
``tools/compile_check_hashed.py`` for the ``ssgd_indexed`` family).

Run by hand before the first chip call of a cell (``JAX_PLATFORMS=cpu
python3 benchmarks/tools/compile_check_indexed.py [cell ...]``); it costs
no chip time and raises what the chip's compiler would raise (VMEM,
SMEM, tiling, HBM). Nothing runs, so it gives no time and no result: a
compile that passes is not a chip run. It prints the per-device bytes
XLA plans, which form each field takes and how many Mosaic kernels
(``tpu_custom_call``) a step holds: three where a field is read by
value, two for every group of by-address fields and one for the
gather of the fields left in HBM (their scatter is XLA's), none where
the form is ``xla``.
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


def compile_indexed(cell: mf.Cell, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from families import ssgd_indexed as fam
    from tpu_distalg.models import ssgd

    c, t = cell.config, cell.traffic
    sh = fam.shapes(c, t)
    shards = c["data_shards"]
    mesh = Mesh(np.array(topo.devices[:shards]).reshape(shards, 1),
                ("data", "model"))
    config = fam.program_config(c, t)
    args = fam.loader_args(c)
    from tpu_distalg.utils import datasets

    cards = args["cardinalities"]
    meta = dict(row_format=c["row_format"], nnz=c["nnz"], hash_bits=0,
                pack=1, n_rows=c["n_rows"], n_padded=sh["n_padded"],
                d_total=sh["d_total"], cardinalities=cards,
                dictionaries=datasets.indexed_field_dictionaries(cards))
    geom = ssgd.hashed_geometry(config, meta)
    plan = ssgd.hashed_field_plan(config, meta)
    rep = NamedSharding(mesh, P())

    def scalar(dtype):
        return jax.ShapeDtypeStruct((), dtype, sharding=rep)

    kw = tuple(sorted((k, v) for k, v in args.items()
                      if k not in ("cardinalities", "row_format")))
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
            "want": 0 if plan is None else 3 * bool(plan.dict_fields)
            + 2 * len(plan.addr_groups) + bool(plan.hbm_fields),
            "fields": None if plan is None else (
                plan.dict_fields, [g.fields for g in plan.addr_groups],
                plan.hbm_fields),
            "tpu_custom_call": seg.as_text().count("tpu_custom_call"),
            "x_bytes_per_device": sh["n_padded"] * geom.row_bytes // shards}


def main(argv) -> int:
    from jax.experimental import topologies

    manifest = os.path.join(ROOT, "BENCHMARK.json")
    cells = [mf.Cell(manifest, w["name"])
             for w in mf.load_json(manifest)["workloads"]
             if not argv or w["name"] in argv]
    cells = [c for c in cells if c.config["family"] == "ssgd_indexed"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bad = 0
    for cell in cells:
        got = compile_indexed(cell, topo)
        ok = got["tpu_custom_call"] >= got["want"]
        bad += not ok
        print(f"[compile] {cell.name}: {'ok' if ok else 'MISSING'} passes "
              f"{got['form']} {got['fields']} tpu_custom_call "
              f"x{got['tpu_custom_call']} (at least {got['want']}) "
              f"table {got['x_bytes_per_device'] / 1e9:.3f} GB/device\n"
              f"  table   {got['table']}\n  segment {got['segment']}",
              flush=True)
    return 1 if bad or not cells else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
