"""Plain reference for block-sampled synchronous SGD on logistic
regression over rows of (feature, value) pairs (the configuration
``lr-webspam-tri16m``): the reference repo's ``optimization/ssgd.py``
update on the rows a LIBSVM file holds, each a list of pairs of its own
length with float32 values.

The model, float32 throughout: weights ``w`` in R^D, D = ``n_features``,
and a bias ``b``. A row is its pairs ``(h_p, v_p)`` and a label ``y`` in
{0, 1}; a feature that occurs twice in a row counts twice.

    m_i  = b + sum_{p in row i} v_p w[h_p]
    p_i  = 1 / (1 + exp(-m_i))
    g[s] = (1/|B|) sum_{i in B} (p_i - y_i) sum_{p in i, h_p = s} v_p
    g_b  = (1/|B|) sum_{i in B} (p_i - y_i)
    w   <- w - eta g,   b <- b - eta g_b

with ``B`` the rows of the step's sampled blocks: ``w[idx] * val``, a
``segment_sum`` a row, ``zeros(D).at[idx].add(...)`` over one flat
vector. Nothing of the program is imported: no layout, no vector of 128
slots held, no table. What is restated here is the benchmark's own
definition of the data and of the blocks:

* **which rows a block holds**: the configuration's rule. A block has
  ``pair_block_slots`` pair slots and ``pair_block_rows`` row slots, a
  row takes its pairs rounded up to ``pair_row_granule``, and a block is
  the longest run of consecutive rows, from where the last block ended,
  that fits both. :func:`pack` is a prefix sum over the rows' lengths
  and a search; the table has ``pair_blocks`` blocks, the last ones
  empty;
* **the rows**: a row's length, a pair's feature (a rank of a bounded
  power law, scattered by ``id = (a rank + c) mod n_features``), its raw
  value (1 + a geometric draw) and the row's scaling to unit length,
  the planted label; every draw a 32-bit hash of the seed, the row's id
  and the pair's place (:class:`Rows`). A row is regenerated whenever a
  step samples its block: the reference holds no copy of the 10.4 GB;
* **the draw of blocks** is ``ssgd_ref.block_draws`` (the benchmark's
  own, shared by every SSGD reference).

A step is followed **a sampled block at a time**: the block's rows
regenerated, their margins and residuals, ``zeros(D).at[idx].add`` of
that block alone finished behind an ``optimization_barrier``, and the
blocks' vectors added up (``ssgd_indexed_ref.py`` says what one flat
scatter-add a step loses on a slot that most rows hold; here the first
feature of the power law takes a twelfth of all pairs).

``dtype=bfloat16`` keeps the values, ``w``, the gathered products and
the per-slot sums (a block's and the running one) in bfloat16: the
control, which has to come out as not correct.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import ndtri

from reference import ssgd_ref

LANES = 128
U = np.uint32


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


# ---- which rows a block holds ---------------------------------------------

def pack(lengths, slots: int, rows: int, granule: int) -> np.ndarray:
    """Block ``b`` holds rows ``cuts[b] .. cuts[b + 1]``."""
    took = -(-np.asarray(lengths, np.int64) // granule) * granule
    if took.size and took.max() > slots:
        raise ValueError("a row longer than a block")
    ends = np.concatenate([[0], np.cumsum(took)])
    cuts, at = [0], 0
    while at < took.size:
        fit = int(np.searchsorted(ends, ends[at] + slots, side="right")) - 1
        at = min(fit, at + rows)
        cuts.append(at)
    return np.asarray(cuts, np.int64)


# ---- the rows ---------------------------------------------------------------

def _mix32(x):
    """Wellons' lowbias32, uint32 in and out."""
    x = x ^ (x >> U(16))
    x = x * U(0x7FEB352D)
    x = x ^ (x >> U(15))
    x = x * U(0x846CA68B)
    return x ^ (x >> U(16))


def _feistel(n: int):
    """A keyed permutation of ``[0, n)``: four rounds of a balanced
    Feistel network over the least even number of bits that hold
    ``n``, walked until the value is under ``n``."""
    bits = max(2, int(n - 1).bit_length())
    bits += bits % 2
    half = bits // 2
    mask = U((1 << half) - 1)

    def once(x, key):
        left, right = x >> U(half), x & mask
        for r in range(4):
            k = _mix32(key + U((0x9E3779B1 * (r + 1)) & 0xFFFFFFFF))
            left, right = right, left ^ (
                _mix32(right * U(0x85EBCA6B) + k) & mask)
        return (left << U(half)) | right

    def walk(x, key):
        return jax.lax.while_loop(
            lambda y: jnp.any(y >= U(n)),
            lambda y: jnp.where(y >= U(n), once(y, key), y),
            once(x, key))

    return walk


class Rows:
    """The generator of one configuration."""

    PLANTED_SD = math.sqrt((256 ** 2 - 1) / 3.0)

    def __init__(self, c: dict):
        self.n_rows, self.D = c["n_rows"], c["n_features"]
        self.mu, self.sigma = c["length_mu"], c["length_sigma"]
        self.lo, self.hi = c["length_min"], c["length_max"]
        self.zipf = float(c["zipf_exponent"])
        self.a, self.c = c["scatter_a"], c["scatter_c"]
        if math.gcd(self.a, self.D) != 1 \
                or self.a * (self.D - 1) + self.c >= 1 << 32:
            raise ValueError("scatter_a, scatter_c: not a bijection in "
                             "32 bits")
        self.scale = c["planted_scale"]
        self.rate = c["positive_rate"]
        self.a1 = 1.0 - self.zipf
        self.span = np.float32((np.float32(self.D) + 1.0) ** self.a1 - 1.0)
        self.stratum = np.float32(abs(self.span / self.a1) * 2.0 ** -24)
        self.deal = _feistel(max(self.n_rows, 2))

    @staticmethod
    def key(seed, k: int):
        return _mix32(jnp.asarray(seed).astype(jnp.uint32) * U(0x9E3779B1)
                      + U((0x85EBCA6B * (k + 1)) & 0xFFFFFFFF))

    @staticmethod
    def word(key, ids):
        return _mix32(jnp.asarray(ids).astype(jnp.uint32) * U(0x9E3779B1)
                      + key)

    @staticmethod
    def unit(bits):
        return ((bits >> U(8)).astype(jnp.float32) + 0.5) * (2.0 ** -24)

    def lengths(self, row_ids, seed):
        """A row's count of pairs: a quantile of the clipped
        log-normal; inside the table the quantiles ``(k + 1/2) /
        n_rows`` dealt to the rows by a keyed permutation, past it a
        hash."""
        ids = jnp.asarray(row_ids, jnp.int32)
        inside = ids < self.n_rows
        dealt = self.deal(jnp.where(inside, ids, 0).astype(jnp.uint32),
                          self.key(seed, 0))
        q = jnp.where(
            inside,
            (dealt.astype(jnp.float32) + 0.5) / np.float32(self.n_rows),
            self.unit(self.word(self.key(seed, 1), ids)))
        x = jnp.exp(np.float32(self.mu) + np.float32(self.sigma) * ndtri(q))
        return jnp.clip(jnp.round(x), self.lo, self.hi).astype(jnp.int32)

    def pairs(self, rows, places, seed):
        """``(feature ids int32, raw values int32)``."""
        row_key = self.word(self.key(seed, 2), rows)
        j = jnp.asarray(places).astype(jnp.uint32) * U(0x85EBCA6B)
        h_rank = _mix32(row_key + j)
        h_place = _mix32((row_key ^ U(0x68E31DA4)) + j)
        h_value = _mix32((row_key ^ U(0xB5297A4D)) + j)
        x = (1.0 + (h_rank >> U(8)).astype(jnp.float32) * (2.0 ** -24)
             * self.span) ** np.float32(1.0 / self.a1)
        whole = jnp.floor(x)
        down = jnp.ceil(
            (h_place >> U(8)).astype(jnp.float32) * (2.0 ** -24)
            * self.stratum * x ** np.float32(self.zipf) - (x - whole))
        rank = whole.astype(jnp.int32) \
            - jnp.maximum(down, 0.0).astype(jnp.int32) - 1
        rank = jnp.clip(rank, 0, self.D - 1).astype(jnp.uint32)
        ids = (rank * U(self.a) + U(self.c)) % U(self.D)
        return ids.astype(jnp.int32), \
            1 + jax.lax.clz(h_value).astype(jnp.int32)

    def planted(self, ids, seed):
        named = _mix32((jnp.asarray(ids).astype(jnp.uint32) + U(1))
                       * U(0x9E3779B1))
        return 2 * (_mix32(named ^ self.key(seed, 3)) >> U(24)).astype(
            jnp.int32) - 255

    def block(self, seed, bias, lengths, row0, start, count, slots: int,
              rows: int):
        """The rows ``start .. start + count`` of a stream whose
        lengths (from row ``row0``, padded by ``rows``) are given, as
        flat arrays of ``slots`` pairs: ``(idx, val, row_of_pair,
        live, y, valid, z, coin)``, a row's pairs one after another."""
        k = jnp.arange(rows, dtype=jnp.int32)
        lens = jax.lax.dynamic_slice(lengths, (start - row0,), (rows,))
        lens = jnp.where(k < count, lens, 0)
        row = jnp.repeat(k, lens, total_repeat_length=slots)
        p = jnp.arange(slots, dtype=jnp.int32)
        live = p < jnp.sum(lens)
        row = jnp.where(live, row, 0)
        place = p - (jnp.cumsum(lens) - lens)[row]
        idx, raw = self.pairs(start + row, place, seed)
        idx = jnp.where(live, idx, 0)
        raw = jnp.where(live, raw, 0)
        squares = jax.ops.segment_sum(raw * raw, row, num_segments=rows)
        weighted = jax.ops.segment_sum(self.planted(idx, seed) * raw, row,
                                       num_segments=rows)
        norm = jnp.sqrt(jnp.maximum(squares, 1).astype(jnp.float32))
        val = jnp.where(live, raw.astype(jnp.float32) / norm[row], 0.0)
        z = np.float32(self.scale / self.PLANTED_SD) \
            * weighted.astype(jnp.float32) / norm
        coin = self.unit(self.word(self.key(seed, 4), start + k))
        valid = k < count
        y = (coin < jax.nn.sigmoid(bias + z)) & valid
        return idx, val, row, live, y.astype(jnp.float32), \
            valid.astype(jnp.float32), z, coin

    def set_bias(self, z, coin, live):
        """The bias under which ``positive_rate`` of the rows are
        positive, by bisection on their count."""
        want = jnp.floor(np.float32(self.rate) * jnp.sum(
            live.astype(jnp.int32)).astype(jnp.float32)).astype(jnp.int32)

        def halve(_, lo_hi):
            lo, hi = lo_hi
            mid = 0.5 * (lo + hi)
            over = jnp.sum(((coin < jax.nn.sigmoid(mid + z)) & live)
                           .astype(jnp.int32)) > want
            return jnp.where(over, lo, mid), jnp.where(over, mid, hi)

        lo, hi = jax.lax.fori_loop(
            0, 40, halve, (jnp.float32(-30.0), jnp.float32(30.0)))
        return 0.5 * (lo + hi)


class Reference:
    """The steps of one cell from its seeds."""

    def __init__(self, *, config: dict, fraction: float, data_seed: int,
                 sample_seed: int, n_shards: int = 1):
        c = self.c = config
        if c["pair_blocks"] % n_shards:
            raise ValueError("pair_blocks over the shards")
        self.g = ssgd_ref.geometry(c["pair_blocks"], n_shards, 1, 1,
                                   fraction)
        self.S, self.eta, self.D = n_shards, c["eta"], c["n_features"]
        self.slots, self.R = c["pair_block_slots"], c["pair_block_rows"]
        self.rows = Rows(c)
        self.seed = jnp.int32(data_seed)
        self.sample_seed = sample_seed
        n = c["n_rows"]
        self.lengths = jax.jit(self.rows.lengths)(
            jnp.arange(n + self.R), self.seed)
        host = np.asarray(self.lengths)[:n]
        self.starts, self.counts = self._blocks(host, c["pair_blocks"], 0)
        if self.counts.sum() != n:
            raise ValueError(
                f"{c['pair_blocks']} blocks do not hold the table's rows")
        ends = np.concatenate([[0], np.cumsum(host, dtype=np.int64)])
        self.block_pairs = ends[self.starts + self.counts] \
            - ends[self.starts]
        self.n_pairs = int(ends[-1])
        self._bias_set = None

    def _blocks(self, lengths, n_blocks: int, row0: int):
        cuts = pack(lengths, self.slots, self.R, self.c["pair_row_granule"])
        used = min(len(cuts) - 1, n_blocks)
        starts = np.full((n_blocks,), cuts[used], np.int64)
        counts = np.zeros((n_blocks,), np.int64)
        starts[:used], counts[:used] = cuts[:used], np.diff(cuts)[:used]
        return starts + row0, counts

    def _stream(self, row0: int, n_blocks: int):
        """The first ``n_blocks`` blocks of the rows from ``row0``:
        their lengths (padded), starts and counts."""
        n = n_blocks * self.R
        lengths = jax.jit(self.rows.lengths)(
            row0 + jnp.arange(n + self.R), self.seed)
        starts, counts = self._blocks(np.asarray(lengths)[:n], n_blocks,
                                      row0)
        return lengths, jnp.asarray(starts, jnp.int32), \
            jnp.asarray(counts, jnp.int32)

    @property
    def bias(self):
        """The planted model's bias (made when a label is first
        needed: the packing alone needs none)."""
        if self._bias_set is None:
            self._bias_set = self._bias()
        return self._bias_set

    def _bias(self):
        c = self.c
        row0 = c["n_rows"]
        lengths, starts, counts = self._stream(row0, c["bias_blocks"])

        def scores(seed):
            def one(sc):
                got = self.rows.block(seed, 0.0, lengths, row0, sc[0],
                                      sc[1], self.slots, self.R)
                return got[6], got[7], got[5] > 0
            z, coin, live = jax.lax.map(one, (starts, counts))
            return self.rows.set_bias(z.reshape(-1), coin.reshape(-1),
                                      live.reshape(-1))

        return jax.jit(scores)(self.seed)

    DRAW_STEPS = 256      # steps a call of the counting draw covers

    def _draw_fn(self):
        """``ssgd_ref.block_draws`` over a fixed number of steps, one
        compiled function an object (the family counts a window's work
        with it after the window, where nothing may compile)."""
        if getattr(self, "_draws", None) is None:
            n_blocks, n_sampled = self.g["n_blocks"], self.g["n_sampled"]

            def one(seed, t, s):
                k = jax.random.fold_in(
                    jax.random.fold_in(jax.random.key(seed), t), s)
                bits = jax.random.bits(k, (n_blocks,))
                return jnp.argsort(bits)[:n_sampled].astype(jnp.int32)

            self._draws = jax.jit(jax.vmap(
                jax.vmap(one, (None, None, 0)), (None, 0, None)))
        return self._draws

    def rows_and_pairs(self, t0: int, n_steps: int):
        """The valid rows and the pairs that steps ``t0 ...`` sample:
        two int arrays, an entry a step (the family's count of work)."""
        draw, got = self._draw_fn(), []
        for at in range(0, n_steps, self.DRAW_STEPS):
            got.append(np.asarray(draw(
                jnp.int32(self.sample_seed),
                jnp.arange(self.DRAW_STEPS) + (t0 + at),
                jnp.arange(self.S))))
        draws = np.concatenate(got)[:n_steps]
        blocks = draws + (np.arange(self.S) * self.g["n_blocks"])[
            None, :, None]
        return self.counts[blocks].sum(axis=(1, 2)), \
            self.block_pairs[blocks].sum(axis=(1, 2))

    # ---- one step --------------------------------------------------------
    def _partial_fn(self, dtype):
        D, slots, R = self.D, self.slots, self.R
        low = dtype != jnp.float32

        def rounded(g):                # XLA may not drop the rounding
            return jax.lax.reduce_precision(g, 8, 7) if low else g

        def partial(starts, counts, w, b, seed, bias, lengths):
            def one(carry, sc):
                g, rs, vs = carry
                idx, val, row, _, y, valid, _, _ = self.rows.block(
                    seed, bias, lengths, 0, sc[0], sc[1], slots, R)
                prod = w[idx] * val.astype(dtype)
                m = b + jax.ops.segment_sum(
                    prod.astype(jnp.float32), row, num_segments=R)
                r = (jax.nn.sigmoid(m) - y) * valid
                add = (val.astype(dtype) * r[row].astype(dtype))
                gb = jnp.zeros((D,), dtype).at[idx].add(add)
                # the block's vector is finished before it is added
                gb = jax.lax.optimization_barrier(gb)
                g = rounded(g + rounded(gb.astype(jnp.float32)))
                return (g, rs + jnp.sum(r), vs + jnp.sum(valid)), None

            zero = jnp.float32(0.0)
            (g, rs, vs), _ = jax.lax.scan(
                one, (jnp.zeros((D,), jnp.float32), zero, zero),
                (starts, counts))
            return g, rs, vs

        return jax.jit(partial)

    def follow(self, n_calls: int, steps_per_call: int,
               dtype=jnp.float32, t0: int = 0) -> list[np.ndarray]:
        """The ``n_features + 1`` weights (the bias last) after each of
        the first ``n_calls`` calls of ``steps_per_call`` steps from
        zero weights; the steps carry the ids ``t0, t0 + 1, ...``."""
        with jax.default_matmul_precision("highest"):
            return self._follow(n_calls, steps_per_call, dtype, t0)

    def _follow(self, n_calls, steps_per_call, dtype, t0):
        partial = self._partial_fn(dtype)
        n_steps = n_calls * steps_per_call
        draws = ssgd_ref.block_draws(
            self.sample_seed, t0, n_steps, self.S, self.g["n_blocks"],
            self.g["n_sampled"])
        np_dtype = jnp.dtype(dtype)
        w = np.zeros((self.D + 1,), np_dtype)
        out = []
        for t in range(n_steps):
            g = np.zeros((self.D + 1,), np.float32)
            cnt = 0.0
            for s in range(self.S):
                blocks = draws[t, s] + s * self.g["n_blocks"]
                gs, rs, vs = partial(
                    jnp.asarray(self.starts[blocks], jnp.int32),
                    jnp.asarray(self.counts[blocks], jnp.int32),
                    jnp.asarray(w[:self.D]), jnp.float32(w[self.D]),
                    self.seed, self.bias, self.lengths)
                g[:self.D] += np.asarray(gs)
                g[self.D] += float(rs)
                cnt += float(vs)
            w = (w - (np_dtype.type(self.eta) * g.astype(np_dtype)
                      / np_dtype.type(max(cnt, 1.0)))).astype(np_dtype)
            if (t + 1) % steps_per_call == 0:
                out.append(np.asarray(w, np.float32))
        return out

    # ---- held-out rows ---------------------------------------------------
    def heldout_log_loss(self, w) -> float:
        """Log-loss of the ``n_features + 1`` weights ``w`` on the rows
        of the first ``heldout_blocks`` blocks of a stream the table
        does not hold (row ids from ``n_rows + heldout_offset``)."""
        c = self.c
        row0 = c["n_rows"] + c["heldout_offset"]
        lengths, starts, counts = self._stream(row0, c["heldout_blocks"])

        def loss(w, b, seed, bias):
            def one(sc):
                idx, val, row, _, y, valid, _, _ = self.rows.block(
                    seed, bias, lengths, row0, sc[0], sc[1], self.slots,
                    self.R)
                m = b + jax.ops.segment_sum(w[idx] * val, row,
                                            num_segments=self.R)
                return jnp.sum((jax.nn.softplus(m) - y * m) * valid), \
                    jnp.sum(valid)
            tot, n = jax.lax.map(one, (starts, counts))
            return jnp.sum(tot) / jnp.maximum(jnp.sum(n), 1.0)

        w = np.asarray(w, np.float32)
        return float(jax.jit(loss)(jnp.asarray(w[:self.D]),
                                   jnp.float32(w[self.D]), self.seed,
                                   self.bias))
