"""Plain reference for PageRank by power iteration over all vertices
(the program's ``mode="standard"``): uniform start, damping ``1 - q``,
the mass of vertices with no out-link spread evenly, a fixed number of
sweeps. float32 ``segment_sum`` over the deduplicated edge list; it
takes the raw edges and dedupes them itself.

``dtype=bfloat16`` keeps ranks and contributions in bfloat16: the
control, which has to come out as not correct.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def distinct(edges: np.ndarray, n_vertices: int):
    code = np.unique(edges[:, 0].astype(np.int64) * n_vertices
                     + edges[:, 1].astype(np.int64))
    return ((code // n_vertices).astype(np.int32),
            (code % n_vertices).astype(np.int32))


def ranks(edges: np.ndarray, n_vertices: int, q: float, n_sweeps: int,
          dtype=jnp.float32) -> np.ndarray:
    src, dst = distinct(edges, n_vertices)
    out_deg = np.bincount(src, minlength=n_vertices).astype(np.float32)
    inv = np.where(out_deg > 0, 1.0 / np.maximum(out_deg, 1.0), 0.0)
    V = n_vertices

    def run(src, dst, inv, sink):
        def sweep(r, _):
            per_edge = (r[src] * inv[src].astype(dtype)).astype(dtype)
            c = jax.ops.segment_sum(per_edge, dst, num_segments=V)
            c = c + (jnp.sum(r * sink.astype(dtype)) / V).astype(dtype)
            return (q / V + (1 - q) * c).astype(dtype), None

        r0 = jnp.full((V,), 1.0 / V, dtype=dtype)
        return jax.lax.scan(sweep, r0, None, length=n_sweeps)[0]

    out = jax.jit(run)(jnp.asarray(src), jnp.asarray(dst),
                       jnp.asarray(inv, jnp.float32),
                       jnp.asarray(out_deg == 0, jnp.float32))
    return np.asarray(out.astype(jnp.float32)), len(src)


def l1_err(r, r_ref) -> float:
    """Sum of absolute differences over the reference's total mass."""
    r, r_ref = np.asarray(r, np.float64), np.asarray(r_ref, np.float64)
    return float(np.abs(r - r_ref).sum() / r_ref.sum())


def max_rel_err(r, r_ref) -> float:
    """Largest difference over the reference's largest rank."""
    r, r_ref = np.asarray(r, np.float64), np.asarray(r_ref, np.float64)
    return float(np.abs(r - r_ref).max() / r_ref.max())
