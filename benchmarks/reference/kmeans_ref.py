"""Plain reference for Lloyd's k-means on a seeded Gaussian mixture
(HiBench ``ml/kmeans``: ``num_of_clusters`` generating centres, ``k``
fitted ones, squared Euclidean distance).

Nothing of the program is imported. What is shared is restated here:

* rows: row ``i`` is a function of ``(data_seed, i)`` only, the form of
  the program's ``datasets.gaussian_mixture_rows``: the generating
  centres are ``normal(fold_in(key, 0), (clusters, dim)) * spread``,
  the row's centre ``randint(fold_in(fold_in(key, 1), i))`` and its
  noise ``normal(fold_in(that, 1), (dim,))``. ``make_rows`` is the one
  definition; the family's adapter fills the program's table from it,
  this reference its own copy;
* the start: ``k`` distinct row ids drawn from the init seed, those
  rows regenerated (the source script's ``takeSample(False, k, seed)``);
* an iteration: every valid row goes to its nearest centre by the
  squared distance written as its definition, ``sum((x - c) ** 2)``, in
  float32, first minimum on ties; a cluster's new centre is the sum of
  its rows (a one-hot product pinned to full float32 precision, float32
  accumulation) over their count (int32); an empty cluster keeps its
  centre.

The rows are plain ``(n, dim)`` rows taken a block at a time. The table
holds them flat, ``f32[blocks, block_rows * dim]``, because the TPU pads
the minor dimension of a 2-D float32 array to 128 lanes: ``(n, 20)``
itself would be 51 GB at the real size.

``dtype=bfloat16`` is the control: rows and centres rounded to bfloat16
and the distance arithmetic done in it (sums still accumulate in
float32). It has to come out as not correct.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def make_rows(ids, dim: int, clusters: int, data_seed, spread: float):
    """Rows ``ids`` of the mixture, ``(n, dim)`` float32. ``data_seed``
    may be traced: one compiled generator serves every seed."""
    key = jax.random.key(data_seed)
    k_c, k_rows = jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)
    centers = jax.random.normal(k_c, (clusters, dim)) * spread
    row_keys = jax.vmap(lambda i: jax.random.fold_in(k_rows, i))(ids)
    assign = jax.vmap(
        lambda rk: jax.random.randint(rk, (), 0, clusters))(row_keys)
    noise = jax.vmap(lambda rk: jax.random.normal(
        jax.random.fold_in(rk, 1), (dim,)))(row_keys)
    return centers[assign] + noise


def init_ids(init_seed: int, n_rows: int, k: int) -> np.ndarray:
    """``k`` distinct row ids."""
    return np.sort(np.random.default_rng(int(init_seed)).choice(
        n_rows, size=k, replace=False)).astype(np.int32)


def nearest(rows, centers):
    """Index of the nearest centre a row, first minimum; the arithmetic
    runs in the rows' dtype."""
    d2 = jnp.sum((rows[:, None, :] - centers[None, :, :]) ** 2, axis=-1)
    return jnp.argmin(d2, axis=1)


class Reference:
    """One cell's data set on one device, and Lloyd iterations over it."""

    def __init__(self, *, n_rows: int, dim: int, k: int, clusters: int,
                 spread: float, data_seed: int, init_seed: int, device,
                 block_rows: int = 1 << 15):
        self.n_rows, self.dim, self.k = n_rows, dim, k
        self.clusters, self.spread = clusters, spread
        self.data_seed, self.init_seed = data_seed, init_seed
        self.device = device
        self.B = block_rows
        self.n_blocks = -(-n_rows // block_rows)
        self.table = None

    def _rows(self, ids):
        f = jax.jit(lambda i, s: make_rows(
            i, self.dim, self.clusters, s, self.spread))
        return f(jax.device_put(jnp.asarray(ids, jnp.int32), self.device),
                 jax.device_put(jnp.int32(self.data_seed), self.device))

    def init_centers(self) -> np.ndarray:
        return np.asarray(self._rows(
            init_ids(self.init_seed, self.n_rows, self.k)))

    # ---- data ------------------------------------------------------
    def build(self):
        B, dim = self.B, self.dim

        def gen(seed):
            def one(b):
                rows = make_rows(b * B + jnp.arange(B), dim,
                                 self.clusters, seed, self.spread)
                return rows.reshape(B * dim)

            return jax.lax.map(one, jnp.arange(self.n_blocks))

        self.table = jax.jit(gen)(
            jax.device_put(jnp.int32(self.data_seed), self.device))
        self.table.block_until_ready()

    def free(self):
        if self.table is not None:
            self.table.delete()
        self.table = None

    # ---- one iteration ----------------------------------------------
    def _iteration_fn(self, dtype):
        B, dim, k, n_rows = self.B, self.dim, self.k, self.n_rows

        def iteration(table, centers):
            c = centers.astype(dtype)

            def block(carry, b):
                sums, counts = carry
                rows = table[b].reshape(B, dim).astype(dtype)
                a = nearest(rows, c)
                valid = (b * B + jnp.arange(B)) < n_rows
                onehot = (a[:, None] == jnp.arange(k)[None, :]) \
                    & valid[:, None]
                sums = sums + jnp.einsum(
                    "nc,nd->cd", onehot.astype(jnp.float32),
                    rows.astype(jnp.float32), precision=HIGHEST)
                return (sums, counts + jnp.sum(
                    onehot.astype(jnp.int32), axis=0)), None

            (sums, counts), _ = jax.lax.scan(
                block, (jnp.zeros((k, dim), jnp.float32),
                        jnp.zeros((k,), jnp.int32)),
                jnp.arange(self.n_blocks))
            means = sums / jnp.maximum(counts, 1).astype(
                jnp.float32)[:, None]
            return jnp.where(counts[:, None] > 0, means, centers), counts

        return jax.jit(iteration)

    def follow(self, n_calls: int, iterations: int, dtype=jnp.float32):
        """``(centres after each of the first n_calls calls of
        `iterations` iterations, the last iteration's counts)`` from the
        seeded start."""
        step = self._iteration_fn(dtype)
        centers = jax.device_put(
            jnp.asarray(self.init_centers()), self.device)
        out, counts = [], None
        with jax.default_matmul_precision("highest"):
            for _ in range(n_calls):
                for _ in range(iterations):
                    centers, counts = step(self.table, centers)
                out.append(np.asarray(centers, np.float32))
        return out, np.asarray(counts)

    # ---- held-out rows ----------------------------------------------
    def heldout(self, n: int = 1 << 18):
        """Rows of the same mixture that the data set does not hold
        (ids from 2**30 on; the largest table is under 2**29 rows)."""
        return self._rows((1 << 30) + np.arange(n))

    def inertia(self, rows, centers) -> float:
        """Mean squared distance of ``rows`` to their nearest centre."""
        c = jax.device_put(jnp.asarray(centers, jnp.float32), self.device)
        d2 = jnp.sum((rows[:, None, :] - c[None, :, :]) ** 2, axis=-1)
        return float(jnp.mean(jnp.min(d2, axis=1)))


def centers_err(centers, centers_ref, spread: float) -> float:
    """Largest difference of a centre's coordinate over the data's
    spread; not a number where the shapes differ or a value is not
    finite."""
    a = np.asarray(centers, np.float64)
    b = np.asarray(centers_ref, np.float64)
    if a.shape != b.shape or not np.isfinite(a).all():
        return float("nan")
    return float(np.abs(a - b).max() / spread)
