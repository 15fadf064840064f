#!/usr/bin/env python3
"""Compile the sparse ALS cell's programs at their real shapes for a
described ``v5e:2x2`` topology, with no chip attached: the program's
draw of one side's packed ratings and its fit function (the sibling of
``tools/compile_check_hashed.py`` for the ``als_sparse`` family).

Run by hand before the first chip call of a cell (``JAX_PLATFORMS=cpu
python3 benchmarks/tools/compile_check_als.py [cell ...] [--shards N]``);
it costs no chip time and raises what the chip's compiler would raise
(HBM, tiling). Nothing runs, so it gives no time and no result: a
compile that passes is not a chip run. It prints the per-device bytes
XLA plans. ``--shards 4`` compiles the fit over a 4 x 1 mesh with its
all-gather (the table the cell holds, cut in four)."""

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


def compile_als(cell: mf.Cell, topo, shards: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from families import als_sparse as fam
    from tpu_distalg.models import als
    from tpu_distalg.utils import datasets as dsets

    c, t = cell.config, cell.traffic
    mesh = Mesh(np.array(topo.devices[:shards]).reshape(shards, 1),
                ("data", "model"))
    args = fam.loader_args(c)
    meta = als.plan_ratings(c["n_ratings"], c["n_users"], c["n_items"],
                            c["k"], shards, **args)
    fam.check_meta(c, meta)
    geom, pu, pi = meta["geometry"], meta["user"], meta["item"]
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))

    def arr(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    B, W = geom.batch, geom.width
    out = {"padding": meta["padding_share"], "blocks": meta["blocks"]}
    par = dict(meta["generator"])
    gen = dsets.seeded_ratings(c["n_ratings"], c["k"], mean=par["mean"],
                               scale=par["scale"], noise=par["noise"])
    nb = pu.static.n_blocks * shards
    t0 = time.perf_counter()
    side = als.side_generator(mesh, geom, gen, 0, pi.static.zero_row)
    out["generator"] = side.lower(
        arr((nb, B), jnp.int32, row), arr((nb, B), jnp.int32, row),
        arr((nb, B), jnp.int32, row), arr((c["n_ratings"],), jnp.int32),
        arr((pi.static.table_rows, W), jnp.float32),
        arr((), jnp.int32)).compile().memory_analysis()
    out["generator_s"] = time.perf_counter() - t0

    def side_arrays(p):
        n = p.static.n_blocks * shards
        return (arr((n, *geom.block_shape), jnp.int32, row),
                arr((n, *geom.block_shape), jnp.float32, row),
                arr(p.piece_slot.shape, jnp.int32, row))

    h = max(c["n_heldout"], 1)
    t0 = time.perf_counter()
    fit = als.make_fit_fn(mesh, fam.program_config(c, t), meta)
    out["fit"] = fit.lower(
        *side_arrays(pu), *side_arrays(pi), arr((h,), jnp.int32),
        arr((h,), jnp.int32), arr((h,), jnp.float32),
        arr((pu.static.table_rows, W), jnp.float32),
        arr((pi.static.table_rows, W), jnp.float32)
    ).compile().memory_analysis()
    out["fit_s"] = time.perf_counter() - t0
    out["resident"] = meta["ratings_bytes"] / shards + meta["factor_bytes"]
    return out


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
    cells = [c for c in cells if c.config["family"] == "als_sparse"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for cell in cells:
        got = compile_als(cell, topo, shards)
        print(f"[compile] {cell.name}: ok on {shards} shard(s); blocks a "
              f"side {got['blocks']}, slots held / ratings "
              f"{got['padding']:.4f}, resident "
              f"{got['resident'] / 1e9:.3f} GB/device\n"
              f"  generator ({got['generator_s']:.1f} s) "
              f"{got['generator']}\n"
              f"  fit       ({got['fit_s']:.1f} s) {got['fit']}",
              flush=True)
    return 0 if cells else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
