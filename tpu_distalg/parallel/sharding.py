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
    0.0 for padding; ``n_valid`` is the original row count. A table
    that :func:`build_sharded` packed chunk by chunk (``pack=``) has no
    mask: its leaves are not one row to an index, and a row is valid
    when its id is below ``n_valid`` (``padded_rows`` says how many the
    table holds).
    """

    data: jax.Array
    mask: jax.Array | None
    n_valid: int
    padded_rows: int | None = None

    @property
    def n_padded(self) -> int:
        return (self.mask.shape[0] if self.mask is not None
                else self.padded_rows)


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
    seed=None,
    chunk_rows: int | None = None,
    pack=None,
) -> ShardedMatrix:
    """Construct a row-sharded dataset ON DEVICE — the scale-out sibling
    of :func:`parallelize`.

    ``parallelize`` materializes the full array on the host first
    (``np.pad`` + ``device_put``) — at a 1B-row scale that is ~100s
    of GB of host RAM for data that is
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

    ``seed``: where given, the generator is called ``make_rows(row_ids,
    seed)`` and the seed is an int32 *argument* of the compiled program:
    every seed is the same program, so a new one costs no compile (a
    generator that closes over a key made from a Python int compiles
    anew for each: 14.7 s a seed on the chip for the SSGD loader,
    PERF.md §6).

    ``chunk_rows``: draw each shard's rows that many at a time, in a
    loop on the device, instead of in one ``vmap`` over all of them,
    whose per-row keys and intermediates alone outgrow a chip at 100M
    rows; rows are padded to a multiple of it. ``pack(rows) -> tree``, with
    ``chunk_rows``, re-lays each chunk as it is drawn; the leaves are
    then ``(n_chunks, *packed shape)``, sharded over chunks, and no
    mask comes back (see :class:`ShardedMatrix`).
    """
    from jax import lax

    if pack is not None and chunk_rows is None:
        raise ValueError("build_sharded: pack= re-lays chunks; give "
                         "chunk_rows")
    n_shards = mesh.shape[DATA_AXIS]
    mult = n_shards * row_multiple * (chunk_rows or 1)
    n_padded = -(-n_rows // mult) * mult
    n_local = n_padded // n_shards
    seed_arg = () if seed is None else (jnp.int32(seed),)

    def local_rows(first, *s):
        """This shard's rows from id ``first`` on, whole or in chunks."""
        if chunk_rows is None:
            return make_rows(first + jnp.arange(n_local), *s)

        def one(c):
            rows = make_rows(
                first + c * chunk_rows + jnp.arange(chunk_rows), *s)
            return rows if pack is None else pack(rows)

        n_chunks = n_local // chunk_rows
        if pack is not None:
            return lax.map(one, jnp.arange(n_chunks))

        # written in place, chunk after chunk: a lax.map would stack
        # (n_chunks, chunk_rows, d) and a TPU pads that array's minor
        # dimension to 128 lanes (51 GB at 100M x 20) before any
        # reshape to (n_local, d), which XLA holds column-major
        def put(c, bufs):
            return jax.tree.map(
                lambda buf, rows: lax.dynamic_update_slice_in_dim(
                    buf, rows, c * chunk_rows, 0), bufs, one(c))

        return lax.fori_loop(0, n_chunks, put, jax.tree.map(
            lambda sh: jnp.zeros((n_local, *sh.shape[1:]), sh.dtype),
            jax.eval_shape(one, 0)))

    def body(*s):
        first = lax.axis_index(DATA_AXIS) * n_local
        rows = local_rows(first, *s)
        if pack is not None:
            return rows
        ids = first + jnp.arange(n_local)
        return rows, (ids < n_rows).astype(jnp.float32)

    # trace abstractly to learn each leaf's rank for out_specs
    shapes = jax.eval_shape(
        local_rows, jax.ShapeDtypeStruct((), jnp.int32), *seed_arg)
    specs = jax.tree.map(
        lambda sh: P(DATA_AXIS, *([None] * (sh.ndim - 1))), shapes
    )
    shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs)
    if pack is None:
        specs = (specs, P(DATA_AXIS))
        shardings = (shardings, data_sharding(mesh, 1))
    f = jax.shard_map(
        body, mesh=mesh, in_specs=(P(),) * len(seed_arg), out_specs=specs,
        check_vma=False,    # the zeros the chunks are written into
    )
    out = jax.jit(f, out_shardings=shardings)(*seed_arg)
    if pack is not None:
        return ShardedMatrix(data=out, mask=None, n_valid=n_rows,
                             padded_rows=n_padded)
    return ShardedMatrix(data=out[0], mask=out[1], n_valid=n_rows)
