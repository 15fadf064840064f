"""Plain reference for Lloyd's k-means at a codebook's widths (FAISS's
MNIST8m k-means: 784 dimensions, 4096 centres), where
``kmeans_ref.Reference`` cannot go: a block of 32 768 rows against 4096
centres by the definition is 1e11 elements, and ``(n, 784)`` rows are
held padded to 896 lanes.

Nothing of the program is imported. The rows, the start and an
iteration are ``kmeans_ref``'s, restated only where the widths force it:

* the distance is its definition, ``sum((x - c) ** 2)``, in float32,
  formed a centre at a time against a block of rows (a block against
  all 4096 at once is 6.6e9 differences), the first minimum over all
  centres;
* a cluster's new centre is the sum of its rows (a one-hot product
  pinned to full float32 precision, float32 accumulation) over their
  count (int32); an empty cluster keeps its centre;
* the table is the reference's own: ``f32[blocks, dim, block_rows]``,
  a block's rows along the minor dimension (whole tiles: nothing
  padded), the features a reduction over the second, which the VPU does
  register by register.

``dtype=bfloat16`` is the control: rows and centres rounded to bfloat16
and the distance arithmetic done in it (sums still accumulate in
float32). It has to come out as not correct.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import kmeans_ref
from reference.kmeans_ref import HIGHEST, make_rows


def sq_dists(rows_t, centers):
    """``(k, n)`` squared distances of each column of ``rows_t`` ``(dim,
    n)`` to each of ``centers`` ``(k, dim)``, by the definition, a
    centre at a time; the arithmetic runs in the rows' dtype."""
    return jax.lax.map(
        lambda c: jnp.sum((rows_t - c[:, None]) ** 2, axis=0), centers)


def nearest(rows_t, centers):
    """Index of the nearest centre of each column, first minimum."""
    return jnp.argmin(sq_dists(rows_t, centers), axis=0)


def sums_err(centers, centers_ref, counts_ref, spread: float) -> float:
    """What the worst cluster's sum is off by, in spreads: the largest
    over centres of (largest coordinate difference x the cluster's
    count in the reference) over the data's spread; not a number where
    the shapes differ or a value is not finite.

    Why not ``kmeans_ref.centers_err``, the largest coordinate
    difference alone. Two float32 evaluations of a distance, one by
    ``|c|^2 - 2 x.c`` and one by the definition, send a point whose two
    nearest centres lie within rounding of each other (0.02 on scores
    of 1e5) to different sides: 335 to 362 of 2 025 000 points an
    iteration at 784 x 4096 (my chip runs, PR 30). Thousands of centres
    started from sampled rows have clusters of every size (median 105
    points, one in eleven of 5 or fewer), and one such point moves a
    centre by its distance over the count: 0.02 to 0.08 of the spread
    on two seeds, 0.3 where it lands in a cluster of two, which is
    where the bfloat16 control reads (0.36, 0.37). Times the count it
    is the point's own distance, whatever the cluster: a sound pass is
    off by two or three points' worth in its worst cluster (1.3, 1.5),
    the control by dozens in every one (30, 39)."""
    a = np.asarray(centers, np.float64)
    b = np.asarray(centers_ref, np.float64)
    if a.shape != b.shape or not np.isfinite(a).all():
        return float("nan")
    worth = np.abs(a - b).max(axis=1) * np.maximum(
        np.asarray(counts_ref, np.float64), 1.0)
    return float(worth.max() / spread)


class Reference(kmeans_ref.Reference):
    """One cell's data set on one device, and Lloyd iterations over it,
    blocked over rows, a centre at a time."""

    def __init__(self, *, block_rows: int = 2048, **kw):
        super().__init__(block_rows=block_rows, **kw)
        self._mean_least = jax.jit(lambda rows, c: jnp.mean(jax.lax.map(
            lambda rows_t: jnp.min(sq_dists(rows_t, c), axis=0), rows)))

    # ---- data ------------------------------------------------------
    def build(self):
        B, dim = self.B, self.dim

        def gen(seed):
            return jax.lax.map(
                lambda b: make_rows(b * B + jnp.arange(B), dim,
                                    self.clusters, seed, self.spread).T,
                jnp.arange(self.n_blocks))

        self.table = jax.jit(gen)(
            jax.device_put(jnp.int32(self.data_seed), self.device))
        self.table.block_until_ready()

    # ---- one iteration ----------------------------------------------
    def _iteration_fn(self, dtype):
        B, dim, k, n_rows = self.B, self.dim, self.k, self.n_rows

        def iteration(table, centers):
            c = centers.astype(dtype)

            def block(carry, b):
                sums, counts = carry
                rows_t = table[b].astype(dtype)
                a = nearest(rows_t, c)
                valid = (b * B + jnp.arange(B)) < n_rows
                onehot = (a[None, :] == jnp.arange(k)[:, None]) \
                    & valid[None, :]
                sums = sums + jnp.einsum(
                    "cn,dn->cd", onehot.astype(jnp.float32),
                    rows_t.astype(jnp.float32), precision=HIGHEST)
                return (sums, counts + jnp.sum(
                    onehot.astype(jnp.int32), axis=1)), None

            (sums, counts), _ = jax.lax.scan(
                block, (jnp.zeros((k, dim), jnp.float32),
                        jnp.zeros((k,), jnp.int32)),
                jnp.arange(self.n_blocks))
            means = sums / jnp.maximum(counts, 1).astype(
                jnp.float32)[:, None]
            return jnp.where(counts[:, None] > 0, means, centers), counts

        return jax.jit(iteration)

    def follow(self, n_calls: int, iterations: int, dtype=jnp.float32):
        """As ``kmeans_ref.Reference.follow``; the counts of each
        call's last iteration are kept in ``call_counts`` too."""
        step = self._iteration_fn(dtype)
        centers = jax.device_put(
            jnp.asarray(self.init_centers()), self.device)
        out, self.call_counts = [], []
        with jax.default_matmul_precision("highest"):
            for _ in range(n_calls):
                for _ in range(iterations):
                    centers, counts = step(self.table, centers)
                out.append(np.asarray(centers, np.float32))
                self.call_counts.append(np.asarray(counts))
        return out, self.call_counts[-1]

    # ---- held-out rows ----------------------------------------------
    def heldout(self, n: int = 1 << 15):
        """Rows of the same mixture that the data set does not hold, as
        ``(blocks, dim, block_rows)``."""
        n = -(-n // self.B) * self.B
        rows = super().heldout(n)
        return rows.reshape(-1, self.B, self.dim).transpose(0, 2, 1)

    def inertia(self, rows, centers) -> float:
        """Mean squared distance of the held-out ``rows`` to their
        nearest centre."""
        c = jax.device_put(jnp.asarray(centers, jnp.float32), self.device)
        return float(self._mean_least(rows, c))
