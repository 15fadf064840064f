"""Plain reference for block-sampled synchronous SGD on logistic
regression over *indexed* click-log rows (the configuration
``lr-kdd12-wide55m``): the reference repo's ``optimization/ssgd.py``
update on one-hot rows whose every feature is its own weight, as
LIBSVM's ``kdd12`` holds KDD Cup 2012 track 2.

The model, float32 throughout: weights ``w`` in R^D, D =
``n_features`` = the fields' cardinalities added up, and a bias ``b``.
A row is ``nnz`` feature indices and a label ``y`` in {0, 1}; every
value is 1. Field ``f``'s value ``v`` is feature ``offset_f + v``,
``offset_f`` the cardinalities before it added up: nothing is hashed,
so no two features share a weight.

    m_i  = b + sum_j w[h_ij]
    p_i  = 1 / (1 + exp(-m_i))
    g[s] = (1/|B|) sum_{i in B} (p_i - y_i) * #{j : h_ij = s}
    g_b  = (1/|B|) sum_{i in B} (p_i - y_i)
    w   <- w - eta g,   b <- b - eta g_b

with ``B`` the valid rows of the step's sampled blocks: ``w[idx]
.sum(-1) + b`` and ``zeros(D).at[idx].add(...)`` over one flat vector.
There is no table here, no sub-table, no form of a field, and no
offset beyond the configuration file's cardinalities.

Nothing of the program is imported. The steps, the draw of blocks, the
seed's streams, the planted bias and the labels are
``ssgd_hashed_ref``'s (the benchmark's own, restated there); what this
file restates is what the indexed generator does differently:

* the value: a bounded power law over the field's cardinality whose
  draw is exact however large the field. A float32 uniform ``u`` gives
  ``x = (1 + u span) ** (1 / a1)`` on ``[1, N + 1)`` at 2**23 levels;
  a second uniform ``j`` places the value inside the stratum that one
  level of ``u`` spans, ``|dx/du| 2**-23 = |span / a1| x**zipf 2**-23``
  wide below ``x``, and the value is taken in int32 (a float32 past
  2**24 holds no odd integer);
* the planted weight of a feature: hashed from ``(seed, field,
  value)``, not from a slot.

A step is followed **a sampled block at a time**: the block's rows
regenerated, their margins and residuals, ``zeros(D).at[idx].add`` of
that block alone, and the blocks' vectors added up. The one flat
scatter over all of a step's 16.5M pairs, which the hashed reference
makes, is not accurate enough here. XLA's scatter-add on the chip adds
a slot's addends up one after another in float32, and this
configuration has features that most of a step's rows hold (depth and
position take 3 values: 775 000 of 1 499 136 rows hold the first) with
residuals that are all near 0.49 while the click rate is 3.5%: a
running sum of 4e5 takes each 0.49 rounded to 1/32, and the sum comes
out 4.4e-3 off (my chip run, PR 47: against float64 on the host, the
flat scatter read 4.44e-3, 2.95e-3 and 5.2e-4 on those three values,
2.2e-3 on the profile's first, 1e-5 to 7e-5 on a feature of 130 000
rows; the program's by-value sums read 1e-8 to 1e-7 there). A block's
8192 rows give a feature at most 8192 addends, whose running sum stays
under 4200 and rounds without a lean, and 183 such sums add up to
5e-7 (an ``optimization_barrier`` keeps XLA from folding a block's
scatter into the running vector, which is the flat scatter again). The equations are the same; only the order of float32 additions
is chosen, as for the blocks of any reference that has to fit.

``dtype=bfloat16`` keeps ``w``, the gathered weights and the per-slot
sums (a block's and the running one) in bfloat16: the control, which
has to come out as not correct.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import ssgd_hashed_ref as hashed_ref
from reference import ssgd_ref

LANES = 128


def cardinalities(config: dict) -> tuple[int, ...]:
    cards = tuple(config["field_cardinalities"])
    if len(cards) != config["nnz"] \
            or sum(cards) != config["n_features"]:
        raise ValueError(
            f"{len(cards)} cardinalities that add up to {sum(cards)}; "
            f"nnz {config['nnz']}, n_features {config['n_features']}")
    return cards


class Rows(hashed_ref.Rows):
    """The generator of one configuration: the hashed one's streams,
    bias and labels round this file's draw."""

    def __init__(self, cards, zipf_exponent: float, planted_scale: float,
                 click_rate: float):
        super().__init__(cards, 32, zipf_exponent, planted_scale,
                         click_rate)
        self.zipf = float(zipf_exponent)
        self.top = jnp.asarray(cards, jnp.int32) - 1
        self.offsets = jnp.asarray(
            np.concatenate([[0], np.cumsum(cards)[:-1]]), jnp.int32)
        self.stratum = jnp.abs(self.span / self.a1) * (2.0 ** -23)

    def _slots_scores(self, row_keys, w_salt):
        u = jax.vmap(
            lambda k: jax.random.uniform(k, (self.nnz,)))(row_keys)
        j = jax.vmap(lambda k: jax.random.uniform(
            jax.random.fold_in(k, 11), (self.nnz,)))(row_keys)
        x = (1.0 + u * self.span) ** (1.0 / self.a1)
        whole = jnp.floor(x)
        down = jnp.ceil(j * self.stratum * x ** self.zipf - (x - whole))
        v = whole.astype(jnp.int32) \
            - jnp.maximum(down, 0.0).astype(jnp.int32) - 1
        v = jnp.clip(v, 0, self.top)
        named = hashed_ref._mix32(
            (v.astype(jnp.uint32) + 1) * jnp.uint32(0x9E3779B1)
            + self.field_salt)
        bits = hashed_ref._mix32(named ^ w_salt) >> 8
        planted = (bits.astype(jnp.float32) * (2.0 ** -23) - 1.0) \
            * (3.0 ** 0.5)
        return self.offsets + v, self.scale * jnp.sum(planted, axis=1)


class Reference(hashed_ref.Reference):
    """The steps of one cell from its seeds (``follow``, ``heldout``,
    ``log_loss`` are the hashed reference's over ``D`` weights)."""

    def __init__(self, *, config: dict, fraction: float, data_seed: int,
                 sample_seed: int, n_shards: int = 1):
        c = config
        self.g = ssgd_ref.geometry(c["n_rows"], n_shards,
                                   c["gather_block_rows"], 1, fraction)
        self.n_rows, self.S = c["n_rows"], n_shards
        self.B, self.eta = c["gather_block_rows"], c["eta"]
        self.D = c["n_features"]
        self.rows = Rows(cardinalities(c), c["zipf_exponent"],
                         c["planted_scale"], c["click_rate"])
        self.data_seed = jnp.int32(data_seed)
        self.sample_seed = sample_seed
        self.bias = jax.jit(self.rows.bias)(self.data_seed)


    def _partial_fn(self, dtype):
        """One shard's share of one step, a sampled block at a time:
        the sums over the blocks' valid rows and their count."""
        B, D, n_rows = self.B, self.D, self.n_rows
        low = dtype != jnp.float32

        def rounded(g):                # XLA may not drop the rounding
            return jax.lax.reduce_precision(g, 8, 7) if low else g

        def partial(blocks, w, b, offset, seed, bias):
            def one(carry, block):
                g, rs, vs = carry
                ids = offset + block * B + jnp.arange(B)
                idx, y = self.rows.make(ids, seed, bias)
                valid = (ids < n_rows).astype(jnp.float32)
                got = w[idx]                          # (rows, nnz)
                m = b + jnp.sum(got.astype(jnp.float32), axis=-1)
                r = (jax.nn.sigmoid(m) - y) * valid
                add = jnp.broadcast_to(r[:, None], idx.shape).astype(dtype)
                gb = jnp.zeros((D,), dtype).at[idx].add(add)
                # the block's vector is finished before it is added:
                # without the barrier XLA rewrites ``g + scatter(zeros)``
                # as a scatter into ``g``, the flat scatter's running
                # sum again (my chip run, PR 47: 4.445e-3 either way)
                gb = jax.lax.optimization_barrier(gb)
                g = rounded(g + rounded(gb.astype(jnp.float32)))
                return (g, rs + jnp.sum(r), vs + jnp.sum(valid)), None

            zero = jnp.float32(0.0)
            (g, rs, vs), _ = jax.lax.scan(
                one, (jnp.zeros((D,), jnp.float32), zero, zero), blocks)
            return g, rs, vs

        return jax.jit(partial)


def vector_len(n_features: int) -> int:
    """What the program holds a model of ``n_features`` weights in: the
    weights, the bias, zeros to whole rows of 128 lanes."""
    return -(-(n_features + 1) // LANES) * LANES


def model_vector(w, n_features: int) -> np.ndarray:
    """The program's vector (weights, bias, zeros) as the reference's
    ``n_features + 1``; the zeros must be zeros."""
    w = np.asarray(w, np.float32)
    if w.shape != (vector_len(n_features),) \
            or np.any(w[n_features + 1:] != 0):
        raise ValueError("model vector: not (weights, bias, zeros)")
    return w[:n_features + 1]


rel_err = ssgd_ref.rel_err
