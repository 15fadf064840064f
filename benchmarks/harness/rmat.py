"""Seeded R-MAT / Graph500 Kronecker edge generator, the benchmark's
own (``datasets.erdos_renyi_edges`` draws uniform destinations, which
flatters a planner that windows by destination).

Graph500's generator: an edge picks, for each of SCALE bit levels, one
quadrant of the adjacency matrix with probabilities (A, B, C, D); the
quadrant gives one bit of the source and one of the destination. Vertex
labels are then permuted. Edges stay directed as generated, duplicates
and self-loops included (the program and the reference each dedupe).
The bits are drawn on the device with ``jax.random`` from the seed and
pulled to the host once, for the program's planner runs there.
"""

from __future__ import annotations

import numpy as np


def edges(scale: int, edge_factor: int, abcd, seed: int) -> np.ndarray:
    """(edge_factor * 2**scale, 2) int32 (src, dst)."""
    import jax
    import jax.numpy as jnp

    a, b, c, _ = (float(x) for x in abcd)
    n_vertices, n_edges = 1 << scale, edge_factor << scale

    def generate(seed):
        key = jax.random.key(seed)     # an argument: one compile, any seed

        def level(carry, k):
            src, dst = carry
            u = jax.random.uniform(k, (n_edges,))
            src_bit = (u >= a + b).astype(jnp.int32)
            dst_bit = (((u >= a) & (u < a + b))
                       | (u >= a + b + c)).astype(jnp.int32)
            return (src * 2 + src_bit, dst * 2 + dst_bit), None

        zero = jnp.zeros((n_edges,), jnp.int32)
        (src, dst), _ = jax.lax.scan(
            level, (zero, zero),
            jax.random.split(jax.random.fold_in(key, 0), scale))
        perm = jax.random.permutation(
            jax.random.fold_in(key, 1), n_vertices).astype(jnp.int32)
        return jnp.stack([perm[src], perm[dst]], axis=1)

    return np.asarray(jax.jit(generate)(jnp.int32(int(seed) & 0x7FFFFFFF)))
