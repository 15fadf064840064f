#!/usr/bin/env python3
"""Compile the hashed-row segment at the cell's shape for a described
``v5e:2x2`` with the loader's dictionaries in ``meta``, no chip attached
(``benchmarks/tools/compile_check_hashed.py`` builds a ``meta`` without
them and so compiles the by-address passes alone):

    JAX_PLATFORMS=cpu python3 scripts/compile_hashed_fields.py [shards]

Prints the plan (fields by value, by address, dictionary entries), the
bytes XLA plans a device and the Mosaic kernels a step holds: four
where a plan is there. Nothing runs: a compile that passes is not a
chip run."""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

N_ROWS, NNZ, HASH_BITS, BLOCK_ROWS, FRACTION = 45_840_617, 39, 20, 8192, 0.01


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_distalg.models import ssgd
    from tpu_distalg.utils import datasets

    shards = int(argv[0]) if argv else 1
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:shards]).reshape(shards, 1),
                ("data", "model"))
    config = ssgd.SSGDConfig(
        n_iterations=4, eval_test=False, sampler="fused_gather",
        gather_block_rows=BLOCK_ROWS, mini_batch_fraction=FRACTION)
    mult = BLOCK_ROWS * shards
    n_padded = N_ROWS + (-N_ROWS) % mult
    cards = datasets.click_field_cardinalities(NNZ)
    meta = dict(row_format="hashed", nnz=NNZ, hash_bits=HASH_BITS, pack=1,
                n_rows=N_ROWS, n_padded=n_padded,
                d_total=(1 << HASH_BITS) + 128,
                dictionaries=datasets.click_field_dictionaries(
                    cards, HASH_BITS))
    plan = ssgd.hashed_field_plan(config, meta)
    print(f"[compile] by value {len(plan.dict_fields)} fields "
          f"{plan.dict_fields}, {plan.n_values} values in "
          f"{len(plan.group_field)} groups; by address "
          f"{len(plan.addr_fields)}", flush=True)
    rep = NamedSharding(mesh, P())
    X = jax.ShapeDtypeStruct(
        (n_padded // BLOCK_ROWS, 40, BLOCK_ROWS), jnp.int32,
        sharding=NamedSharding(mesh, P("data", None, None)))
    d = jax.ShapeDtypeStruct((1,), jnp.float32, sharding=rep)
    w = jax.ShapeDtypeStruct((meta["d_total"],), jnp.float32, sharding=rep)
    seg = ssgd.make_train_fn_fused(mesh, config, meta).lower(
        X, d, d, d, d, w, t0=0).compile()
    n = seg.as_text().count("tpu_custom_call")
    print(f"[compile] ok tpu_custom_call x{n}\n  {seg.memory_analysis()}",
          flush=True)
    return 0 if n >= 4 else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
