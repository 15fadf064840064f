"""Plain reference for block-sampled synchronous SGD on logistic
regression (the reference repo's ``optimization/ssgd.py``: eta 0.1,
lam 0, the gradient of one step summed over the sampled rows and
divided by their count).

What it shares with the program is the *definition of the draws*,
restated here so that nothing is imported, and the benchmark's own
definition of the data:

* rows: row ``i`` of the table is a function of ``(data_seed, i)``
  only (a counter PRNG, the shape of the program's own
  ``datasets.synthetic_two_class_rows``): 30 standard-normal features,
  label ``x . w_true * sep / sqrt(d) + logistic noise > 0``.
  ``make_rows`` below is the one definition: the family's adapter makes
  the program's table from it, this reference its own copy;
* the step with id ``t`` samples, on each data shard, ``n_sampled`` of
  the shard's ``n_blocks`` blocks of ``block_rows`` consecutive rows
  without replacement: the ``n_sampled`` smallest of ``n_blocks``
  random words drawn from ``fold_in(fold_in(key(sample_seed), t),
  shard)``;
* rows are rounded to bfloat16 once (the configuration serves X in
  bfloat16); weights, logits, residuals and the gradient sum are
  float32.

``dtype=bfloat16`` computes the same steps with weights, products and
sums in bfloat16: the control, which has to come out as not correct.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def geometry(n_rows: int, n_shards: int, block_rows: int, pack: int,
             fraction: float) -> dict:
    """Padded row count, blocks a shard, blocks sampled a step."""
    mult = max(block_rows, pack) * n_shards
    n_padded = n_rows + (-n_rows) % mult
    n_local = n_padded // n_shards
    n_blocks = n_local // block_rows
    n_sampled = max(1, round(fraction * n_blocks))
    return {"n_padded": n_padded, "n_local": n_local,
            "n_blocks": n_blocks, "n_sampled": n_sampled,
            "rows_per_step": n_sampled * block_rows * n_shards}


def make_rows(ids, n_features: int, data_seed, separation: float):
    """Features (n, d) and labels (n,) of the rows ``ids``, float32.
    ``data_seed`` may be traced: the seed is an argument of the
    compiled generator, so one compile serves every seed."""
    key = jax.random.key(data_seed)
    k_w, k_rows = jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)
    w_true = jax.random.normal(k_w, (n_features,))
    row_keys = jax.vmap(lambda i: jax.random.fold_in(k_rows, i))(ids)
    X = jax.vmap(lambda k: jax.random.normal(k, (n_features,)))(row_keys)
    logits = X @ w_true * (separation / jnp.sqrt(n_features))
    noise = jax.vmap(
        lambda k: jax.random.logistic(jax.random.fold_in(k, 7)))(row_keys)
    return X, (logits + noise > 0).astype(jnp.float32)


def init_weights(init_seed: int, d: int):
    """Uniform in [-1, 1): the source's ``2 * ranf(d) - 1``."""
    return jax.random.uniform(jax.random.key(jnp.int32(init_seed)), (d,),
                              minval=-1.0, maxval=1.0)


def block_draws(sample_seed: int, t0: int, n_steps: int, n_shards: int,
                n_blocks: int, n_sampled: int) -> np.ndarray:
    """(n_steps, n_shards, n_sampled) block ids of steps ``t0 ...``."""

    def one(seed, t, s):
        k = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(seed), t), s)
        bits = jax.random.bits(k, (n_blocks,))
        return jnp.argsort(bits)[:n_sampled].astype(jnp.int32)

    f = jax.jit(jax.vmap(jax.vmap(one, (None, None, 0)), (None, 0, None)))
    return np.asarray(f(jnp.int32(sample_seed), jnp.arange(n_steps) + t0,
                        jnp.arange(n_shards)))


class Reference:
    """The data set of one cell, a shard to a device, and the steps."""

    def __init__(self, *, n_rows: int, n_features: int, n_shards: int,
                 block_rows: int, pack: int, fraction: float, eta: float,
                 separation: float, data_seed: int, init_seed: int,
                 sample_seed: int, devices):
        self.g = geometry(n_rows, n_shards, block_rows, pack, fraction)
        self.n_rows, self.nf, self.S = n_rows, n_features, n_shards
        self.B, self.eta, self.sep = block_rows, eta, separation
        self.data_seed, self.init_seed = data_seed, init_seed
        self.sample_seed = sample_seed
        self.devices = list(devices)[:n_shards]
        self.d = n_features + 1                       # + bias column
        self.shards = None

    # ---- data ------------------------------------------------------
    def build(self):
        """Each shard's rows in bfloat16, block by block and
        feature-major: (blocks, d + 1, rows a block) with the features,
        the bias column of ones and the label along the middle axis,
        so that a block of rows is a dense run of lanes."""
        n_local, B = self.g["n_local"], self.B
        per = math.gcd(self.g["n_blocks"], 16)       # blocks a chunk
        chunk, n_chunks = B * per, self.g["n_blocks"] // per
        nf, sep, d = self.nf, self.sep, self.d

        def gen(seed, offset):
            def one(c):
                ids = offset + c * chunk + jnp.arange(chunk)
                X, y = make_rows(ids, nf, seed, sep)
                cols = jnp.concatenate(
                    [X.T, jnp.ones((1, chunk)), y[None, :]], axis=0)
                return cols.reshape(d + 1, per, B).transpose(
                    1, 0, 2).astype(jnp.bfloat16)

            return jax.lax.map(one, jnp.arange(n_chunks)).reshape(
                n_chunks * per, d + 1, B)

        gen = jax.jit(gen)
        self.shards = [
            gen(jax.device_put(jnp.int32(self.data_seed), dev),
                jax.device_put(jnp.int32(s * n_local), dev))
            for s, dev in enumerate(self.devices)]
        for x in self.shards:
            x.block_until_ready()

    def free(self):
        for x in self.shards or []:
            x.delete()
        self.shards = None

    # ---- one step's share of one shard ------------------------------
    def _partial_fn(self, dtype):
        B, d, n_rows = self.B, self.d, self.n_rows
        low = dtype != jnp.float32

        def partial(Xs, idx, w, offset):
            Xb = Xs[idx]                                 # (ns, d + 1, B)
            feats = Xb[:, :d].astype(dtype)
            y = Xb[:, d].astype(dtype)
            ids = offset + idx[:, None] * B + jnp.arange(B)[None, :]
            valid = (ids < n_rows).astype(dtype)
            kw = (dict(preferred_element_type=dtype) if low
                  else dict(precision=HIGHEST))
            z = jnp.einsum("njb,j->nb", feats, w.astype(dtype), **kw)
            r = (jax.nn.sigmoid(z) - y) * valid
            g = jnp.einsum("njb,nb->j", feats, r, **kw)
            return g, jnp.sum(valid.astype(jnp.float32))

        return jax.jit(partial)

    def follow(self, n_calls: int, steps_per_call: int,
               dtype=jnp.float32, t0: int = 0) -> list[np.ndarray]:
        """Weights after each of the first ``n_calls`` calls of
        ``steps_per_call`` steps, from the seeded start; the steps
        carry the ids ``t0, t0 + 1, ...``."""
        partial = self._partial_fn(dtype)
        n_steps = n_calls * steps_per_call
        draws = block_draws(self.sample_seed, t0, n_steps, self.S,
                            self.g["n_blocks"], self.g["n_sampled"])
        offs = [jax.device_put(jnp.int32(s * self.g["n_local"]), dev)
                for s, dev in enumerate(self.devices)]
        np_dtype = jnp.dtype(dtype)
        w = np.asarray(init_weights(self.init_seed, self.d)).astype(np_dtype)
        out = []
        for t in range(n_steps):
            parts = [partial(self.shards[s],
                             jax.device_put(draws[t, s], dev),
                             jax.device_put(w, dev), offs[s])
                     for s, dev in enumerate(self.devices)]
            g = sum(np.asarray(p[0]).astype(np_dtype) for p in parts)
            cnt = sum(float(p[1]) for p in parts)
            w = (w - (np_dtype.type(self.eta) * g
                      / np_dtype.type(max(cnt, 1.0)))).astype(np_dtype)
            if (t + 1) % steps_per_call == 0:
                out.append(np.asarray(w, np.float32))
        return out

    # ---- held-out rows ----------------------------------------------
    def heldout(self, n: int = 1 << 18):
        """Rows the data set does not hold (ids past its padded end)."""
        ids = self.g["n_padded"] + jnp.arange(n)
        X, y = jax.jit(lambda i, seed: make_rows(
            i, self.nf, seed, self.sep))(ids, jnp.int32(self.data_seed))
        X = jnp.concatenate([X, jnp.ones((n, 1))], axis=1)
        return X.astype(jnp.bfloat16).astype(jnp.float32), y

    @staticmethod
    def accuracy(X, y, w) -> float:
        z = jnp.einsum("nj,j->n", X, jnp.asarray(w, jnp.float32),
                       precision=HIGHEST)
        return float(jnp.mean(((z > 0).astype(jnp.float32) == y)))


def rel_err(w, w_ref, w0) -> float:
    """Norm of the difference over the norm of the reference's change."""
    w, w_ref, w0 = (np.asarray(a, np.float64) for a in (w, w_ref, w0))
    return float(np.linalg.norm(w - w_ref)
                 / max(np.linalg.norm(w_ref - w0), 1e-30))
