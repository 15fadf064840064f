"""Sharded-array constructors — the RDD/broadcast replacement.

Maps the reference's data-distribution primitives onto ``jax.sharding``:

  * ``parallelize(rows, mesh)``  ≙  ``sc.parallelize(matrix, n_slices).cache()``
    (``/root/reference/optimization/ssgd.py:86``): rows are padded to a
    multiple of the data-axis size and placed as a row-sharded ``jax.Array``
    resident in HBM. A validity mask stands in for the exact partition sizes.
  * ``replicate(tree, mesh)``  ≙  ``sc.broadcast(w)`` (``ssgd.py:95``):
    fully-replicated sharding. Under ``jit`` the compiler keeps replicated
    operands resident on every chip, so the per-iteration re-broadcast of the
    reference costs nothing here.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_distalg.parallel.mesh import DATA_AXIS


def data_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Row-sharded over the data axis; remaining dims replicated."""
    spec = P(DATA_AXIS, *([None] * (ndim - 1)))
    return NamedSharding(mesh, spec)


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_rows(x: np.ndarray | jax.Array, multiple: int):
    """Pad axis 0 up to a multiple; return (padded, valid_mask).

    Spark partitions may be ragged; XLA shards must be equal-sized and
    static. The mask carries the 'true length' through reductions.
    """
    n = x.shape[0]
    n_pad = (-n) % multiple
    mask = np.ones((n + n_pad,), dtype=np.float32)
    if n_pad:
        pad_width = [(0, n_pad)] + [(0, 0)] * (x.ndim - 1)
        x = np.pad(np.asarray(x), pad_width)
        mask[n:] = 0.0
    return x, mask


@dataclasses.dataclass
class ShardedMatrix:
    """A row-sharded dataset: the framework's stand-in for a cached RDD.

    ``data`` is ``(n_padded, ...)`` sharded over the mesh data axis — a
    single array from :func:`parallelize`, possibly a pytree of aligned
    arrays from :func:`build_sharded`; ``mask`` is 1.0 for real rows,
    0.0 for padding; ``n_valid`` is the original row count.
    """

    data: jax.Array
    mask: jax.Array
    n_valid: int

    @property
    def n_padded(self) -> int:
        return self.mask.shape[0]


def parallelize(
    rows: np.ndarray,
    mesh: Mesh,
    *,
    dtype=jnp.float32,
) -> ShardedMatrix:
    """Shard ``rows`` row-wise across the mesh data axis (HBM-resident).

    Equivalent of ``parallelize(matrix, n_slices).cache()`` — but the shard
    placement is declarative (NamedSharding) and permanent; there is no lazy
    lineage to recompute because the array physically lives on the devices.
    """
    n_shards = mesh.shape[DATA_AXIS]
    padded, mask = pad_rows(np.asarray(rows), n_shards)
    sharding = data_sharding(mesh, ndim=padded.ndim)
    data = jax.device_put(jnp.asarray(padded, dtype=dtype), sharding)
    mask_arr = jax.device_put(jnp.asarray(mask), data_sharding(mesh, ndim=1))
    return ShardedMatrix(data=data, mask=mask_arr, n_valid=int(rows.shape[0]))


def replicate(tree, mesh: Mesh):
    """Place every leaf fully-replicated on the mesh (the broadcast op)."""
    sharding = replicated_sharding(mesh)
    return jax.tree.map(
        lambda x: jax.device_put(jnp.asarray(x), sharding), tree
    )


def build_sharded(
    mesh: Mesh,
    n_rows: int,
    make_rows,
    *,
    row_multiple: int = 1,
) -> ShardedMatrix:
    """Construct a row-sharded dataset ON DEVICE — the scale-out sibling
    of :func:`parallelize`.

    ``parallelize`` materializes the full array on the host first
    (``np.pad`` + ``device_put``) — at the 1B-row north-star scale
    (BASELINE.json) that is ~100s of GB of host RAM for data that is
    synthesized anyway (the reference builds its matrix host-side too,
    ``/root/reference/optimization/ssgd.py:86``, which is exactly the
    pattern that cannot scale). Here each shard's rows are generated
    inside a ``shard_map`` body on the device that owns them: host
    memory use is O(1) in ``n_rows`` and every host in a multi-host mesh
    only ever touches its own addressable shards.

    ``make_rows(row_ids)`` must be jittable: given the shard's global row
    ids ``(n_local,)`` it returns a pytree of ``(n_local, ...)`` row
    blocks (e.g. ``(X_rows, y_rows)``). Content should depend only on
    ``row_ids`` (e.g. fold them into a PRNG key), making the dataset
    topology-independent. Rows are padded to a multiple of
    ``row_multiple × n_shards``; padded rows carry mask 0.
    """
    from jax import lax

    n_shards = mesh.shape[DATA_AXIS]
    mult = n_shards * row_multiple
    n_padded = -(-n_rows // mult) * mult
    n_local = n_padded // n_shards

    def body():
        s = lax.axis_index(DATA_AXIS)
        ids = s * n_local + jnp.arange(n_local)
        rows = make_rows(ids)
        mask = (ids < n_rows).astype(jnp.float32)
        return rows, mask

    # trace abstractly to learn each row block's rank for out_specs
    shapes = jax.eval_shape(
        make_rows, jax.ShapeDtypeStruct((n_local,), jnp.int32)
    )
    specs = jax.tree.map(
        lambda sh: P(DATA_AXIS, *([None] * (sh.ndim - 1))), shapes
    )
    f = jax.shard_map(
        body, mesh=mesh, in_specs=(), out_specs=(specs, P(DATA_AXIS)),
    )
    shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs)
    data, mask = jax.jit(f, out_shardings=(
        shardings, data_sharding(mesh, 1)
    ))()
    return ShardedMatrix(data=data, mask=mask, n_valid=n_rows)
