"""Perf probe: compare SSGD step-path variants on the attached device.

Prints steps/sec for each (sampler, dtype, kernel) combination at bench
scale (same workload as bench.py: 1M rows, 125 features + bias → 128-wide
packed matrix) so we can pick the fastest faithful path for bench.py.
"""

import jax.numpy as jnp

from tpu_distalg.models import ssgd
from tpu_distalg.ops import logistic
from tpu_distalg.parallel import get_mesh, mesh_on_tpu, parallelize
from tpu_distalg.utils import datasets, prng, profiling

N_ROWS = 1 << 20
N_FEATURES = 125  # +bias = 126; packed layout pads to 128 (bench.py)
N_STEPS = 200


def _data():
    X, y = datasets.synthetic_two_class(N_ROWS, N_FEATURES, seed=0)
    return datasets.add_bias_column(X), y


def _time(run, w0):
    return profiling.steps_per_sec(run, w0, steps=N_STEPS)


def probe(name, config):
    mesh = get_mesh()
    X, y = _data()
    Xs = parallelize(X, mesh, dtype=jnp.dtype(config.x_dtype))
    ys = parallelize(y, mesh)
    w0 = logistic.init_weights(prng.root_key(7), X.shape[1])
    fn = ssgd.make_train_fn(mesh, config, Xs.n_padded)
    X_ev = jnp.zeros((1, X.shape[1]), jnp.float32)
    y_ev = jnp.zeros((1,), jnp.float32)
    best = _time(lambda w: fn(Xs.data, ys.data, Xs.mask, X_ev, y_ev, w)[0],
                 w0)
    print(f"{name:30s} {best:10.1f} steps/s", flush=True)


def probe_fused(name, config):
    """Fused-sampler probe via ssgd.prepare_fused (the bench.py path)."""
    mesh = get_mesh()
    if not mesh_on_tpu(mesh):
        print(f"{name:30s}       skip (needs TPU)", flush=True)
        return
    X, y = _data()
    try:
        fn, X2, w0, meta = ssgd.prepare_fused(X, y, mesh, config)
    except ValueError as e:
        # e.g. fused_train on a multi-data-shard mesh
        print(f"{name:30s}       skip ({e})", flush=True)
        return
    dummy = jnp.zeros((1,), jnp.float32)
    ev = (jnp.zeros((1, meta["d_total"]), jnp.float32),
          jnp.zeros((1,), jnp.float32))
    best = _time(lambda w: fn(X2, dummy, dummy, ev[0], ev[1], w)[0], w0)
    print(f"{name:30s} {best:10.1f} steps/s", flush=True)


if __name__ == "__main__":
    C = ssgd.SSGDConfig
    probe("bernoulli f32", C(n_iterations=N_STEPS, eval_test=False))
    probe("bernoulli bf16",
          C(n_iterations=N_STEPS, eval_test=False, x_dtype="bfloat16"))
    probe("pallas f32",
          C(n_iterations=N_STEPS, eval_test=False, use_pallas=True))
    probe("fixed f32",
          C(n_iterations=N_STEPS, eval_test=False, sampler="fixed"))
    probe("fixed bf16",
          C(n_iterations=N_STEPS, eval_test=False, sampler="fixed",
            x_dtype="bfloat16"))
    probe_fused("fused bf16",
                C(n_iterations=N_STEPS, eval_test=False, sampler="fused",
                  x_dtype="bfloat16", init_seed=7))
    probe_fused("fused_gather bf16",
                C(n_iterations=N_STEPS, eval_test=False,
                  sampler="fused_gather", gather_block_rows=8192,
                  x_dtype="bfloat16", shuffle_seed=0, init_seed=7))
    probe_fused("fused_train bf16 (megakernel)",
                C(n_iterations=N_STEPS, eval_test=False,
                  sampler="fused_train", gather_block_rows=8192,
                  mega_steps=100, x_dtype="bfloat16", shuffle_seed=0,
                  init_seed=7))
