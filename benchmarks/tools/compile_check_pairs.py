#!/usr/bin/env python3
"""Compile the pairs SSGD cell's programs at their real shapes for a
described ``v5e:2x2`` topology, with no chip attached: the program's
loader of the table of ragged rows and its segment function (the
sibling of ``tools/compile_check_indexed.py`` for the ``ssgd_pairs``
family).

Run by hand before the first chip call of a cell (``JAX_PLATFORMS=cpu
python3 benchmarks/tools/compile_check_pairs.py [cell ...] [--shards
4]``); it costs no chip time and raises what the chip's compiler would
raise (tiling, HBM). Nothing runs, so it gives no time and no result: a
compile that passes is not a chip run. It prints the per-device bytes
XLA plans: the table's, and the segment's temporaries, which is where a
gather of whole 2 MB blocks (``X[ids]``) once showed as 3.8 GB of
copies of the table a trip.
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

TEMP_LIMIT = 1 << 30      # a segment whose temporaries pass 1 GiB copies
#                           more than a trip's blocks


def compile_pairs(cell: mf.Cell, topo, shards: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from families import ssgd_pairs as fam
    from tpu_distalg.models import ssgd, ssgd_pairs
    from tpu_distalg.ops import pairs

    c, t = cell.config, cell.traffic
    mesh = Mesh(np.array(topo.devices[:shards]).reshape(shards, 1),
                ("data", "model"))
    spec = fam.loader_spec(c)
    geom = pairs.PairsGeometry(spec.n_features, spec.block_slots,
                               spec.block_rows, spec.n_blocks)
    config = fam.program_config(c, t)
    rep = NamedSharding(mesh, P())
    by_block = NamedSharding(mesh, P("data"))

    def shape(dims, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    table = ssgd_pairs.table_fn(mesh, spec, geom).lower(
        shape((), jnp.int32), shape((), jnp.float32),
        shape((spec.n_rows + spec.block_rows,), jnp.int32),
        shape((geom.n_blocks,), jnp.int32, by_block),
        shape((geom.n_blocks,), jnp.int32, by_block)).compile()
    meta = dict(row_format="pairs", pack=1, n_rows=spec.n_rows,
                n_features=spec.n_features, n_blocks=geom.n_blocks,
                block_slots=spec.block_slots, block_rows=spec.block_rows,
                d_total=geom.w_len)
    X = shape((geom.n_blocks, geom.held_rows, pairs.LANES), jnp.int32,
              NamedSharding(mesh, P("data", None, None)))
    d = shape((1,), jnp.float32)
    w = shape((geom.w_len,), jnp.float32)
    seg = ssgd.make_train_fn_fused(mesh, config, meta).lower(
        X, d, d, d, d, w, t0=0).compile()
    return {"table": table.memory_analysis(),
            "segment": seg.memory_analysis(),
            "all_reduce": seg.as_text().count("all-reduce"),
            "x_bytes_per_device": geom.n_blocks * geom.block_bytes
            // shards}


def main(argv) -> int:
    from jax.experimental import topologies

    shards = 1
    if "--shards" in argv:
        at = argv.index("--shards")
        shards = int(argv[at + 1])
        argv = argv[:at] + argv[at + 2:]
    manifest = os.path.join(ROOT, "BENCHMARK.json")
    cells = [mf.Cell(manifest, w["name"])
             for w in mf.load_json(manifest)["workloads"]
             if not argv or w["name"] in argv]
    cells = [c for c in cells if c.config["family"] == "ssgd_pairs"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bad = 0
    for cell in cells:
        got = compile_pairs(cell, topo, shards)
        temp = got["segment"].temp_size_in_bytes
        ok = temp < TEMP_LIMIT and (shards == 1 or got["all_reduce"] > 0)
        bad += not ok
        print(f"[compile] {cell.name} on {shards} shard(s): "
              f"{'ok' if ok else 'REFUSED'} table "
              f"{got['x_bytes_per_device'] / 1e9:.3f} GB/device, segment "
              f"temporaries {temp / 1e6:.1f} MB, all-reduce "
              f"x{got['all_reduce']}\n"
              f"  table   {got['table']}\n  segment {got['segment']}",
              flush=True)
    return 1 if bad or not cells else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
