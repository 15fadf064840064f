"""Plain reference for block-sampled synchronous SGD on logistic
regression over hashed click-log rows (the configuration
``lr-criteo-hash20``): the reference repo's ``optimization/ssgd.py``
update on the rows of tinrtgu's hashed one-hot baseline.

The model, float32 throughout: weights ``w`` in R^D, D = 2 **
hash_bits, and a bias ``b``. A row is ``nnz`` slots ``h_1 .. h_nnz`` in
``[0, D)`` and a label ``y`` in {0, 1}; every value is 1.

    m_i  = b + sum_j w[h_ij]           two fields in one slot count twice
    p_i  = 1 / (1 + exp(-m_i))
    g[s] = (1/|B|) sum_{i in B} (p_i - y_i) * #{j : h_ij = s}
    g_b  = (1/|B|) sum_{i in B} (p_i - y_i)
    w   <- w - eta g,   b <- b - eta g_b

with ``B`` the valid rows of the step's sampled blocks. Written as
``w[idx].sum(-1)`` and ``zeros(D).at[idx].add(...)``.

Nothing of the program is imported. What is shared is restated here:

* the rows: row ``i`` is a function of ``(data_seed, i)`` alone.
  Field ``f`` draws a value from a bounded power law over its
  cardinality, the slot is a fixed integer mix of ``(f, value)`` modulo
  D, the label a Bernoulli draw of a planted logistic model whose
  bias is set for the configuration's click rate (``make_rows``);
* the draw: ``ssgd_ref.block_draws``, unchanged (blocks of
  ``block_rows`` consecutive rows, ``n_sampled`` of a shard's
  ``n_blocks`` a step, without replacement).

No table is built: a step regenerates the rows of its sampled blocks
from their ids, so the reference holds 73 MB where the program holds
7.3 GB, and runs after the window has freed the program's.

``dtype=bfloat16`` keeps ``w``, the gathered weights and the per-slot
sums in bfloat16: the control, which has to come out as not correct.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import ssgd_ref

LANES = 128


def cardinalities(config: dict) -> tuple[int, ...]:
    """The fields' value counts in row order: integer fields first."""
    cards = tuple(config["int_field_cardinalities_assumed"]) \
        + tuple(config["field_cardinalities"])
    if len(cards) != config["nnz"]:
        raise ValueError(f"{len(cards)} cardinalities, nnz "
                         f"{config['nnz']}")
    return cards


def _mix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


class Rows:
    """The generator of one configuration."""

    def __init__(self, cards, hash_bits: int, zipf_exponent: float,
                 planted_scale: float, click_rate: float):
        self.cards = jnp.asarray(cards, jnp.float32)
        self.nnz = len(cards)
        self.a1 = 1.0 - float(zipf_exponent)
        self.span = (self.cards + 1.0) ** self.a1 - 1.0
        self.field_salt = (jnp.arange(self.nnz, dtype=jnp.uint32) + 1) \
            * jnp.uint32(0x85EBCA6B)
        self.mask = jnp.uint32((1 << hash_bits) - 1)
        self.scale, self.rate = planted_scale, click_rate

    def _slots_scores(self, row_keys, w_salt):
        u = jax.vmap(
            lambda k: jax.random.uniform(k, (self.nnz,)))(row_keys)
        v = jnp.floor((1.0 + u * self.span) ** (1.0 / self.a1)) - 1.0
        v = jnp.clip(v, 0.0, self.cards - 1.0).astype(jnp.uint32)
        slots = _mix32((v + 1) * jnp.uint32(0x9E3779B1)
                       + self.field_salt) & self.mask
        bits = _mix32(slots ^ w_salt) >> 8
        planted = (bits.astype(jnp.float32) * (2.0 ** -23) - 1.0) \
            * (3.0 ** 0.5)
        return slots.astype(jnp.int32), \
            self.scale * jnp.sum(planted, axis=1)

    @staticmethod
    def _streams(seed):
        key = jax.random.key(seed)
        w_salt = jax.random.bits(jax.random.fold_in(key, 0), (),
                                 jnp.uint32)
        return w_salt, jax.random.fold_in(key, 1), \
            jax.random.fold_in(key, 2)

    def bias(self, seed):
        w_salt, _, k_cal = self._streams(seed)
        keys = jax.vmap(lambda i: jax.random.fold_in(k_cal, i))(
            jnp.arange(1 << 16))
        _, z = self._slots_scores(keys, w_salt)

        def halve(_, lo_hi):
            lo, hi = lo_hi
            mid = 0.5 * (lo + hi)
            over = jnp.mean(jax.nn.sigmoid(mid + z)) > self.rate
            return jnp.where(over, lo, mid), jnp.where(over, mid, hi)

        lo, hi = jax.lax.fori_loop(
            0, 40, halve, (jnp.float32(-30.0), jnp.float32(30.0)))
        return 0.5 * (lo + hi)

    def make(self, ids, seed, bias):
        """Slots ``int32 (n, nnz)`` and labels ``float32 (n,)``."""
        w_salt, k_rows, _ = self._streams(seed)
        keys = jax.vmap(lambda i: jax.random.fold_in(k_rows, i))(ids)
        slots, z = self._slots_scores(keys, w_salt)
        coin = jax.vmap(lambda k: jax.random.uniform(
            jax.random.fold_in(k, 7)))(keys)
        return slots, (coin < jax.nn.sigmoid(bias + z)).astype(
            jnp.float32)


class Reference:
    """The steps of one cell from its seeds."""

    def __init__(self, *, config: dict, fraction: float, data_seed: int,
                 sample_seed: int, n_shards: int = 1):
        c = config
        self.g = ssgd_ref.geometry(c["n_rows"], n_shards,
                                   c["gather_block_rows"], 1, fraction)
        self.n_rows, self.S = c["n_rows"], n_shards
        self.B, self.eta = c["gather_block_rows"], c["eta"]
        self.D = 1 << c["hash_bits"]
        self.rows = Rows(cardinalities(c), c["hash_bits"],
                         c["zipf_exponent"], c["planted_scale"],
                         c["click_rate"])
        self.data_seed = jnp.int32(data_seed)
        self.sample_seed = sample_seed
        self.bias = jax.jit(self.rows.bias)(self.data_seed)

    def _partial_fn(self, dtype):
        """One shard's share of one step: the sums over its sampled
        blocks' valid rows and their count."""
        B, D, n_rows = self.B, self.D, self.n_rows
        low = dtype != jnp.float32

        def partial(blocks, w, b, offset, seed, bias):
            ids = (offset + blocks[:, None] * B
                   + jnp.arange(B)[None, :]).reshape(-1)
            idx, y = self.rows.make(ids, seed, bias)
            valid = (ids < n_rows).astype(jnp.float32)
            got = w[idx]                              # (rows, nnz)
            m = b + jnp.sum(got.astype(jnp.float32), axis=-1)
            r = (jax.nn.sigmoid(m) - y) * valid
            add = jnp.broadcast_to(r[:, None], idx.shape).astype(dtype)
            g = jnp.zeros((D,), dtype).at[idx].add(add)
            if low:                # XLA may not drop the rounding
                g = jax.lax.reduce_precision(
                    g.astype(jnp.float32), 8, 7)
            return g.astype(jnp.float32), jnp.sum(r), jnp.sum(valid)

        return jax.jit(partial)

    def follow(self, n_calls: int, steps_per_call: int,
               dtype=jnp.float32, t0: int = 0) -> list[np.ndarray]:
        """The model vector (table then bias) after each of the first
        ``n_calls`` calls, from zero weights; the steps carry the ids
        ``t0, t0 + 1, ...``."""
        partial = self._partial_fn(dtype)
        n_steps = n_calls * steps_per_call
        draws = ssgd_ref.block_draws(
            self.sample_seed, t0, n_steps, self.S, self.g["n_blocks"],
            self.g["n_sampled"])
        w = jnp.zeros((self.D,), dtype)
        b = jnp.float32(0.0)
        eta = jnp.float32(self.eta)
        out = []
        with jax.default_matmul_precision("highest"):
            for t in range(n_steps):
                parts = [partial(jnp.asarray(draws[t, s]), w, b,
                                 jnp.int32(s * self.g["n_local"]),
                                 self.data_seed, self.bias)
                         for s in range(self.S)]
                g = sum(p[0] for p in parts)
                gb = sum(p[1] for p in parts)
                cnt = jnp.maximum(sum(p[2] for p in parts), 1.0)
                w = (w.astype(jnp.float32) - eta * (g / cnt)).astype(dtype)
                b = b - eta * (gb / cnt)
                if (t + 1) % steps_per_call == 0:
                    out.append(np.concatenate(
                        [np.asarray(w, np.float32),
                         np.asarray(b, np.float32)[None]]))
        return out

    # ---- held-out rows ----------------------------------------------
    def heldout(self, n: int = 1 << 18):
        """Rows the table does not hold (ids past its padded end)."""
        ids = self.g["n_padded"] + jnp.arange(n)
        return jax.jit(self.rows.make)(ids, self.data_seed, self.bias)

    def log_loss(self, idx, y, wb) -> float:
        wb = jnp.asarray(wb, jnp.float32)
        m = wb[self.D] + jnp.sum(wb[:self.D][idx], axis=-1)
        return float(jnp.mean(jax.nn.softplus(m) - y * m))


def model_vector(w, n_slots: int) -> np.ndarray:
    """The program's ``f32[n_slots + 128]`` (table, bias, zeros) as the
    reference's ``n_slots + 1``; the zeros must be zeros."""
    w = np.asarray(w, np.float32)
    if w.shape != (n_slots + LANES,) or np.any(w[n_slots + 1:] != 0):
        raise ValueError("model vector: not (table, bias, zeros)")
    return w[:n_slots + 1]


rel_err = ssgd_ref.rel_err
