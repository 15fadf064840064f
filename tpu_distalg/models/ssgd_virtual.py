"""SSGD over a VIRTUAL dataset — logical size unbounded by HBM.

The reference leans on Spark to make datasets bigger than memory a
non-problem: RDD partitions spill to executor disk and lineage
recomputes lost blocks (`/root/reference/optimization/ssgd.py:86`'s
``.cache()`` is a hint, not a requirement). The resident-``X2`` fused
samplers (``models/ssgd.py``) cap the dataset at HBM — 100M rows is
8 GB of a 16 GB v5e chip, so the 1B-row north star would need chips.

This module removes the cap the TPU-native way: rows are never stored.
The counter-based generators (``utils/datasets.synthetic_two_class_rows``)
define row content purely by global row id, so each step REGENERATES
exactly the sampled blocks on device — sampling identical to
'fused_gather' (same ``sampling.sample_block_ids`` draw keyed on the
absolute step id, so runs are deterministic and resumable), gradient
identical to the 'bernoulli' XLA path (``ops/logistic.grad_sum``), and
HBM holds only the current step's minibatch. Dataset "size" becomes a
pure integer: 400M rows (≈2× HBM if materialised bf16-packed), 1B, any
n — same program, same convergence, host RAM O(1).

Cost model: a regenerated row costs threefry bits + the normal/logistic
transforms instead of an HBM DMA — compute-bound where 'fused_gather'
is bandwidth-bound, so steps/s is lower per sampled row, but unbounded
in n_rows. The resident-HBM path remains the one for datasets that
fit; this is the >HBM story (not measured: PERF.md §7).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tpu_distalg.models.ssgd import SSGDConfig, TrainResult, \
    _build_scan, warn_quantized_fraction
from tpu_distalg.ops import logistic, sampling
from tpu_distalg.parallel import DATA_AXIS, data_parallel, \
    tree_allreduce_sum
from tpu_distalg.utils import prng


@dataclasses.dataclass(frozen=True)
class VirtualData:
    """Geometry of a generated-on-the-fly two-class LR dataset."""

    n_rows: int                 # logical rows (any size)
    n_features: int = 30        # generated features (bias appended)
    data_seed: int = 0
    separation: float = 2.0

    @property
    def d(self) -> int:
        return self.n_features + 1


def _geometry(config: SSGDConfig, data: VirtualData, n_shards: int):
    """Blocks per shard and blocks sampled per shard per step — the
    'fused_gather' block-cluster sampling on a virtual row space padded
    up to a whole number of blocks per shard (padding rows carry zero
    mask via ``row_id >= n_rows``). The grid itself is the data
    subsystem's shared ``block_geometry`` (every out-of-core path —
    virtual, streamed, minibatch k-means — samples the same grid)."""
    from tpu_distalg.data import block_geometry

    rows_per_shard, n_blocks, n_sampled = block_geometry(
        data.n_rows, config.gather_block_rows, n_shards,
        config.mini_batch_fraction)
    warn_quantized_fraction(
        "virtual", n_blocks, n_sampled, config.mini_batch_fraction,
        "lower gather_block_rows for a finer grid")
    return rows_per_shard, n_blocks, n_sampled


def make_train_fn(mesh: Mesh, config: SSGDConfig, data: VirtualData):
    """Scan builder, same contract as the other SSGD builders: the
    returned ``train(X, y, valid, X_test, y_test, w0, t0=0, acc0=0.0)``
    ignores X/y/valid (pass dummies — there is no resident dataset) and
    evaluates on the given test matrix (generate one with
    :func:`heldout_set`)."""
    if config.sampler != "virtual":
        raise ValueError(
            f"make_train_fn(virtual) got sampler={config.sampler!r}")
    n_shards = mesh.shape[DATA_AXIS]
    rows_per_shard, n_blocks, n_sampled = _geometry(
        config, data, n_shards)
    # row ids are int32 on device (jax_enable_x64 is off): past 2^31-1
    # they would wrap NEGATIVE, pass the (ids < n_rows) mask, and train
    # on rows from outside the logical dataset with no error — refuse
    # instead (the held-out anchor at 2^31-1 reserves the top ids too)
    if n_shards * rows_per_shard >= 2 ** 31 - 1 - 2 ** 20:
        raise ValueError(
            f"virtual dataset of {n_shards * rows_per_shard} padded "
            "rows exceeds the int32 row-id space (~2.1B); shard over "
            "more hosts or split the id space into epochs"
        )
    br = config.gather_block_rows
    make_rows = _make_rows(data)
    key = prng.root_key(config.seed)

    def prep_xs(ts):
        # all (step, shard) block draws in one batched threefry —
        # identical to 'fused_gather' (models/ssgd.py)
        return jax.vmap(
            lambda t: sampling.sample_block_ids(
                jax.random.fold_in(key, t), n_shards, n_blocks,
                n_sampled,
            )
        )(ts)                                           # (T, S, ns)

    def _local_grad(w, idx_shards):
        shard = lax.axis_index(DATA_AXIS)
        idx = lax.dynamic_index_in_dim(idx_shards, shard, keepdims=False)
        ids = (shard * rows_per_shard + idx[:, None] * br
               + jnp.arange(br)[None, :]).reshape(-1)   # (ns*br,)
        X, y = make_rows(ids)
        Xb = jnp.concatenate(
            [X, jnp.ones((X.shape[0], 1), X.dtype)], axis=1)
        mask = (ids < data.n_rows).astype(jnp.float32)
        g, cnt = logistic.grad_sum(Xb, y, w, mask)
        return tree_allreduce_sum((g, cnt))

    grad_fn = data_parallel(
        _local_grad, mesh, in_specs=(P(), P()), out_specs=(P(), P()))

    def sample_and_grad(X, y, valid, w, idx_shards):
        del X, y, valid  # virtual: nothing resident
        return grad_fn(w, idx_shards)

    return _build_scan(config, sample_and_grad, prep_xs=prep_xs)


def _make_rows(data: VirtualData):
    from tpu_distalg.utils import datasets

    return datasets.synthetic_two_class_rows(
        data.n_features, seed=data.data_seed,
        separation=data.separation)


def heldout_set(data: VirtualData, n_test: int = 4096):
    """Fresh rows from the same generator, ids beyond every shard's
    padded training range — the convergence check's test matrix (with
    bias column), never seen by any sampled block."""
    make_rows = _make_rows(data)
    # any id >= n_rows is outside the trained (masked) set; use ids
    # far past the padding for clarity
    ids = jnp.arange(n_test, dtype=jnp.int32) + jnp.int32(
        2 ** 31 - 1 - n_test)
    X, y = jax.jit(make_rows)(ids)
    return jnp.concatenate(
        [X, jnp.ones((n_test, 1), X.dtype)], axis=1), y


def train(mesh: Mesh, config: SSGDConfig, data: VirtualData,
          n_test: int = 4096) -> TrainResult:
    """End-to-end: build, init (reference ``2·ranf−1``), run, evaluate
    on a held-out generated set."""
    fn = make_train_fn(mesh, config, data)
    X_test, y_test = heldout_set(data, n_test)
    w0 = logistic.init_weights(prng.root_key(config.init_seed), data.d)
    dummy = jnp.zeros((1,), jnp.float32)
    w, accs = fn(dummy, dummy, dummy, X_test, y_test, w0)
    return TrainResult(w=w, accs=accs)
