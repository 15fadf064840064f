#!/usr/bin/env python3
"""Compile each cell's hot program at its real shapes for a described
``v5e:2x2`` topology, with no chip attached.

Run by hand before the first chip call of a cell (``JAX_PLATFORMS=cpu
python3 benchmarks/tools/compile_check.py [cell ...]``); it costs no
chip time and raises what the chip's compiler would raise (VMEM,
tiling, HBM). Nothing runs, so it gives no time and no result: a
compile that passes is not a chip run. It prints the per-device bytes
XLA plans, whether the Mosaic kernel (``tpu_custom_call``) is in the
program, and, for a cell on several chips, the all-reduce.
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


def compile_ssgd(cell: mf.Cell, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from families import ssgd as fam
    from tpu_distalg.models import ssgd

    c, t = cell.config, cell.traffic
    sh = fam.shapes(c, t)
    devs = np.array(topo.devices[:c["data_shards"]]).reshape(
        c["data_shards"], 1)
    mesh = Mesh(devs, ("data", "model"))
    config = fam.program_config(c, t)
    meta = fam.packed_meta(c, sh)
    fn = ssgd.make_train_fn_fused(mesh, config, meta)
    rep = NamedSharding(mesh, P())
    X2 = jax.ShapeDtypeStruct(
        (sh["n_padded"] // c["fused_pack"],
         c["fused_pack"] * sh["d_total"]), jnp.dtype(c["x_dtype"]),
        sharding=NamedSharding(mesh, P("data", None)))
    dummy = jax.ShapeDtypeStruct((1,), jnp.float32, sharding=rep)
    w = jax.ShapeDtypeStruct((sh["d_total"],), jnp.float32, sharding=rep)
    t0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    compiled = fn.lower(X2, dummy, dummy, dummy, dummy, w, t0=t0).compile()
    text = compiled.as_text()
    return {"memory": compiled.memory_analysis(),
            "tpu_custom_call": text.count("tpu_custom_call"),
            "all_reduce": text.count("all-reduce("),
            "x2_bytes_per_device": int(
                np.prod(X2.shape) * 2 // c["data_shards"])}


FAMILIES = {"ssgd": compile_ssgd}


def main(argv) -> int:
    from jax.experimental import topologies

    manifest = os.path.join(ROOT, "BENCHMARK.json")
    names = argv or [w["name"] for w in mf.load_json(manifest)["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bad = 0
    for name in names:
        cell = mf.Cell(manifest, name)
        got = FAMILIES[cell.config["family"]](cell, topo)
        ok = got["tpu_custom_call"] > 0 and (
            cell.chips == 1 or got["all_reduce"] > 0)
        bad += not ok
        print(f"[compile] {name}: {'ok' if ok else 'MISSING'} "
              f"tpu_custom_call x{got['tpu_custom_call']} all-reduce "
              f"x{got['all_reduce']} X2 {got['x2_bytes_per_device'] / 1e9:.3f}"
              f" GB/device\n  {got['memory']}", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
