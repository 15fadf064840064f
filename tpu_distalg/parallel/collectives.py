"""Collective operations over the mesh — the treeAggregate/shuffle replacement.

The reference pulls per-point values back to the driver through Spark's
tree reduction (``treeAggregate``, e.g. ``/root/reference/optimization/
ssgd.py:99-103``) and exchanges keyed data through TCP shuffles. Here the
same patterns are XLA collectives riding the ICI links, invoked from inside
``shard_map`` bodies:

  * ``tree_allreduce_sum``  ≙  ``treeAggregate(zero, add, add)`` — but the
    result lands replicated on every chip (no driver), as a single fused
    AllReduce over the pytree.
  * ``ring_shift``  ≙  a neighbour exchange (``ppermute``), the building
    block for ring pipelines (ring attention / ring all-reduce style
    algorithms) — exposed so long-sequence workloads can ride ICI.
  * keyed reductions (``reduceByKey``) are ``jax.ops.segment_sum`` inside the
    shard + a psum across shards; see ``tpu_distalg.ops.graph``.
"""

from __future__ import annotations

import jax
from jax import lax

from tpu_distalg.parallel.mesh import DATA_AXIS


def tree_allreduce_sum(tree, axis_name: str = DATA_AXIS):
    """psum every leaf of a pytree across ``axis_name``.

    Matches the tuple aggregation idiom of the reference — e.g. SSGD's
    ``(grad_sum, count)`` pair (``ssgd.py:99-103``) becomes a pytree of two
    leaves reduced in one collective.
    """
    return jax.tree.map(lambda x: lax.psum(x, axis_name), tree)


def tree_allreduce_mean(tree, axis_name: str = DATA_AXIS):
    """pmean every leaf across ``axis_name`` (MA's model average,
    ``ma.py:104-106``)."""
    return jax.tree.map(lambda x: lax.pmean(x, axis_name), tree)


def ring_shift(x: jax.Array, axis_name: str = DATA_AXIS, shift: int = 1):
    """Rotate shards around the ring: shard i receives shard (i - shift).

    A ``ppermute`` over the mesh axis — the ICI-native neighbour exchange
    used by ring algorithms (ring all-reduce, ring attention).
    """
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x: jax.Array, axis_name: str = DATA_AXIS, *, split_axis=0,
               concat_axis=0):
    """Transpose shard <-> local-axis ownership (Ulysses-style exchange)."""
    return lax.all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis,
        tiled=True,
    )


def all_gather(x: jax.Array, axis_name: str = DATA_AXIS, *, axis=0,
               tiled: bool = True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)
