"""Lloyd's-algorithm kernels.

Replaces the reference's per-point Python loop ``closest_center``
(``/root/reference/machine_learning/k-means.py:20-28``) and its
``reduceByKey`` cluster statistics (``k-means.py:62-63``) with a batched
distance argmin and a ``segment_sum`` scatter-reduction — the keyed shuffle
becomes an XLA scatter-add plus (cross-shard) psum.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def assign_clusters(points: jax.Array, centers: jax.Array) -> jax.Array:
    """Index of the nearest center per point (squared-Euclidean argmin;
    first-minimum tie-break matches the reference's strict ``<`` scan)."""
    # (n, k) scores |c|^2 - 2 x.c: the |x|^2 every centre shares cannot
    # change an argmin. The product is pinned to float32 accuracy: a
    # TPU's default rounds both operands to bfloat16, and a point
    # between two near centres then changes sides (the pin
    # cluster_stats and ops/linalg.py need too).
    score = jnp.sum(centers * centers, axis=1)[None, :] - 2.0 * jnp.dot(
        points, centers.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.argmin(score, axis=1)


def cluster_stats(
    points: jax.Array, mask: jax.Array, assign: jax.Array, k: int
):
    """(Σ points, count) per cluster — the reference's reduceByKey pair
    ``(p1+p2, cnt1+cnt2)`` (``k-means.py:60-63``).

    For small k the keyed reduction is a masked one-hot matmul on the
    MXU: ``sums = (onehot ⊙ mask)ᵀ · points``. XLA lowers
    ``segment_sum`` to a scatter-add, which serializes on TPU (the row
    path's chip readings: PERF.md §6, PR 26). Above the one-lane-tile cutoff the (n, k)
    one-hot stops being cheap and the scatter path takes over."""
    if k <= 128:
        om = (assign[:, None] == jnp.arange(k)[None, :]).astype(
            points.dtype) * mask[:, None]
        # precision pinned: the TPU default matmul rounds f32 operands
        # to bf16, which visibly shifts cluster means (the same pin ALS
        # needs, ops/linalg.py) — the keyed reduction must be exact
        sums = jax.lax.dot_general(
            om, points, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        # counts reduce in f32 regardless of points.dtype: a bf16 sum
        # loses integer exactness past 2^8, and f32 past 2^24 rows is
        # still exact for any realistic shard
        return sums, jnp.sum(om.astype(jnp.float32), axis=0)
    weighted = points * mask[:, None]
    sums = jax.ops.segment_sum(weighted, assign, num_segments=k)
    counts = jax.ops.segment_sum(mask, assign, num_segments=k)
    return sums, counts


def update_centers(
    sums: jax.Array, counts: jax.Array, old_centers: jax.Array
) -> jax.Array:
    """Mean per cluster; empty clusters keep their old center (the reference
    only overwrites ``k_centers[c_id]`` for ids present in the collect,
    ``k-means.py:66-71``)."""
    safe = jnp.maximum(counts, 1.0)[:, None]
    means = sums / safe
    return jnp.where(counts[:, None] > 0, means, old_centers)
