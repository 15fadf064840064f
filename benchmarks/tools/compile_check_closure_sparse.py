#!/usr/bin/env python3
"""Compile the pair-set closure's programs at ``closure-tree17``'s real
shapes for a described ``v5e:2x2`` topology, with no chip attached: the
program's semi-naive round (``transitive_closure.make_sparse_round_fn``),
the small programs that make a job's start round it
(``make_sparse_start_fns``) and the family's selection of the sampled
sources' pairs, what ``tda closure --tree-height 17`` and the
``closure_sparse`` family run.

Run by hand before the first chip call of the cell (``JAX_PLATFORMS=cpu
python3 benchmarks/tools/compile_check_closure_sparse.py``); it costs no
chip time and raises what the chip's compiler would raise (HBM). Nothing
runs, so it gives no time and no result: a compile that passes is not a
chip run. It prints the bytes XLA plans for each program (arguments,
results, temporaries) beside the state the geometry says is carried, and
the seconds each compile took here.
"""

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


def compile_sparse(config: dict, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, SingleDeviceSharding

    from families import closure_sparse as fam
    from tpu_distalg.models import transitive_closure as tc

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    one = SingleDeviceSharding(topo.devices[0])
    geom = fam.program_parts(config)[1]

    def arr(n, dtype=jnp.int32):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one)

    def scalar(dtype=jnp.int32):
        return jax.ShapeDtypeStruct((), dtype, sharding=one)

    out = {"geom": geom}

    def timed(name, fn, *args):
        t0 = time.perf_counter()
        got = fn.lower(*args).compile()
        out[name + "_s"] = time.perf_counter() - t0
        out[name] = got.memory_analysis()
        return got

    state = tc.SparseState(
        arr(geom.capacity), arr(geom.capacity), arr(geom.delta_capacity),
        arr(geom.delta_capacity), scalar(), scalar(), scalar(jnp.bool_))
    arcs = tc.Arcs(arr(geom.n_vertices + 1), arr(geom.n_vertices + 1),
                   arr(geom.n_edges + 1))
    rnd = timed("round", tc.make_sparse_round_fn(mesh, geom), state, arcs)
    out["sorts"] = rnd.as_text().count(" sort(")
    seed, arcs_of, start = tc.make_sparse_start_fns(mesh, geom)
    timed("seed", seed, arr(geom.n_edges), arr(geom.n_edges))
    timed("arcs_of", arcs_of, state)
    timed("start", start, arr(geom.n_edges), arr(geom.n_edges), scalar())
    timed("take", fam.make_take(config), arr(geom.capacity),
          arr(geom.capacity), arr(config["sample_rows"]))
    return out


def main(argv) -> int:
    from jax.experimental import topologies

    path = os.path.join(BENCH, "configs", (argv[0] if argv
                                           else "closure-tree17") + ".json")
    config = mf.load_json(path)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    got = compile_sparse(config, topo)
    g = got["geom"]
    print(f"[compile] {os.path.basename(path)}: capacity {g.capacity} "
          f"delta {g.delta_capacity} join {g.join_capacity}; carried "
          f"{g.resident_bytes / 1e9:.3f} GB, planned working set "
          f"{g.working_bytes / 1e9:.3f} GB; sorts in a round "
          f"{got['sorts']}", flush=True)
    for name in ("round", "seed", "arcs_of", "start", "take"):
        m = got[name]
        print(f"  {name:8s} compiled in {got[name + '_s']:5.1f} s: "
              f"arguments {m.argument_size_in_bytes / 1e9:.3f} GB, "
              f"results {m.output_size_in_bytes / 1e9:.3f} GB (aliased "
              f"{m.alias_size_in_bytes / 1e9:.3f}), temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB, code "
              f"{m.generated_code_size_in_bytes / 1e6:.1f} MB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
