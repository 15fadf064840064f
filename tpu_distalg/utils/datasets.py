"""Dataset loading and synthesis.

The reference's optimizers all train on sklearn breast-cancer with a fixed
70/30 split (``/root/reference/optimization/ssgd.py:71-76``); benchmarks
need synthetic data at scale (two-class LR rows by the billion, 1M-node
Erdős–Rényi graphs). Bias handling follows the reference: a ones
column is appended to X (``ssgd.py:83-84``), so the model has D+1 weights.
"""

from __future__ import annotations

import itertools
from typing import Tuple

import numpy as np


def breast_cancer_split(test_size: float = 0.3, random_state: int = 0):
    """Breast-cancer 70/30 split, bias column appended — the reference task.

    Returns (X_train1, y_train, X_test1, y_test) with the ones column already
    concatenated (matching ``ssgd.py:83-84``; test side ``ssgd.py:108-109``).
    """
    from sklearn.datasets import load_breast_cancer
    from sklearn.model_selection import train_test_split

    X, y = load_breast_cancer(return_X_y=True)
    X_train, X_test, y_train, y_test = train_test_split(
        X, y, test_size=test_size, random_state=random_state, shuffle=True
    )
    return (
        add_bias_column(X_train),
        y_train.astype(np.float32),
        add_bias_column(X_test),
        y_test.astype(np.float32),
    )


def add_bias_column(X: np.ndarray) -> np.ndarray:
    return np.concatenate(
        [X, np.ones((X.shape[0], 1))], axis=1
    ).astype(np.float32)


def synthetic_two_class(
    n_rows: int, n_features: int = 30, seed: int = 0, separation: float = 2.0
) -> Tuple[np.ndarray, np.ndarray]:
    """Linearly-separable-ish two-class Gaussian data for LR benchmarks."""
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(n_features,))
    X = rng.normal(size=(n_rows, n_features)).astype(np.float32)
    logits = X @ w_true * separation / np.sqrt(n_features)
    y = (logits + rng.logistic(size=n_rows) > 0).astype(np.float32)
    return X, y


def synthetic_two_class_rows(n_features: int, seed: int = 0,
                             separation: float = 2.0):
    """Jittable per-row generator for ``parallel.build_sharded`` — the
    host-memory-free sibling of :func:`synthetic_two_class` (same
    distribution, counter-based per-row PRNG so content depends only on
    the global row id, not the shard topology). Returns
    ``make_rows(row_ids) -> (X_rows, y_rows)``; the bias column is NOT
    appended (compose with a column of ones like ``add_bias_column``).
    """
    import jax
    import jax.numpy as jnp

    from tpu_distalg.utils import prng

    key = prng.root_key(seed)
    k_w, k_rows = jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)

    def make_rows(ids):
        w_true = jax.random.normal(k_w, (n_features,))
        row_keys = jax.vmap(lambda i: jax.random.fold_in(k_rows, i))(ids)
        X = jax.vmap(
            lambda k: jax.random.normal(k, (n_features,))
        )(row_keys)
        logits = X @ w_true * (separation / jnp.sqrt(n_features))
        noise = jax.vmap(
            lambda k: jax.random.logistic(jax.random.fold_in(k, 7))
        )(row_keys)
        y = (logits + noise > 0).astype(jnp.float32)
        return X, y

    return make_rows


# Values a field of a click log takes. The 26 categorical fields are the
# table sizes the public DLRM scripts pass for the Criteo Kaggle set; the
# 13 integer fields' counts of distinct values are ASSUMED (the set
# cannot be fetched here). Integer fields first, as the set's columns.
CLICK_INTEGER_CARDINALITIES = (
    649, 9364, 14746, 490, 476707, 11618, 4142, 1373, 7275, 13, 169,
    407, 1376)
CLICK_CATEGORICAL_CARDINALITIES = (
    1460, 583, 10131227, 2202608, 305, 24, 12517, 633, 3, 93145, 5683,
    8351593, 3194, 27, 14992, 5461306, 10, 5652, 2173, 4, 7046547, 18,
    15, 286181, 105, 142572)


def click_field_cardinalities(nnz: int) -> tuple[int, ...]:
    """Distinct values of each of a click-log row's ``nnz`` fields: the
    39 above, taken round again past them."""
    both = CLICK_INTEGER_CARDINALITIES + CLICK_CATEGORICAL_CARDINALITIES
    return tuple(both[f % len(both)] for f in range(nnz))


def _mix32(x):
    """A fixed 32-bit integer mix (Wellons' lowbias32), uint32 in and
    out (a NumPy or a jax array: the host's dictionaries and the
    device's rows are one function)."""
    u = np.uint32
    x = x ^ (x >> u(16))
    x = x * u(0x7FEB352D)
    x = x ^ (x >> u(15))
    x = x * u(0x846CA68B)
    return x ^ (x >> u(16))


def click_slots(fields, values, hash_bits: int):
    """The slot of value ``values`` of field ``fields`` in a table of
    ``2 ** hash_bits``: a fixed mix of the pair, no seed in it. uint32
    arrays in (NumPy or jax, broadcast against each other), uint32 out.
    The generator and :func:`click_field_dictionary` both call it."""
    u = np.uint32
    salt = (fields + u(1)) * u(0x85EBCA6B)
    return _mix32((values + u(1)) * u(0x9E3779B1) + salt) \
        & u((1 << hash_bits) - 1)


# A loader states a field's dictionary up to this many values, as a
# columnar file keeps a dictionary page for its low-cardinality columns
# and falls back to plain values past it.
DICTIONARY_MAX_VALUES = 1 << 16


def click_field_dictionary(field: int, cardinality: int,
                           hash_bits: int) -> np.ndarray:
    """Every slot field ``field`` can hold: the slots of its values ``0
    .. cardinality - 1``, unique and ascending, a host ``int32`` array
    (two values that fold to one slot are one entry)."""
    values = np.arange(cardinality, dtype=np.uint32)
    slots = click_slots(np.full((1,), field, np.uint32), values, hash_bits)
    return np.unique(slots).astype(np.int32)


def click_field_dictionaries(cardinalities, hash_bits: int) -> tuple:
    """For each field its dictionary, or ``None`` past
    ``DICTIONARY_MAX_VALUES`` values."""
    return tuple(
        click_field_dictionary(f, c, hash_bits)
        if c <= DICTIONARY_MAX_VALUES else None
        for f, c in enumerate(cardinalities))


def hashed_click_rows(cardinalities, hash_bits: int, *,
                      zipf_exponent: float = 1.1,
                      planted_scale: float = 0.25,
                      click_rate: float = 0.256):
    """Jittable per-row generator of hashed click-log rows, counter
    based like :func:`synthetic_two_class_rows` (a row is a function of
    the seed and its global id alone). Returns ``make_rows(row_ids,
    seed) -> (slots int32 (n, nnz), labels float32 (n,))``; the seed may
    be traced, so one compiled loader serves every seed.

    Field ``f`` takes value ``v`` in ``[0, cardinalities[f])`` by a
    bounded power law (the inverse of the continuous distribution with
    density ~ x ** -zipf_exponent on [1, N + 1)), so a few values of
    every field fill most rows and a field of millions has a long tail.
    Its slot is a fixed integer mix of ``(f, v)`` modulo ``2 **
    hash_bits``: the hashing trick, collisions and all. The label is a
    Bernoulli draw of a planted logistic model: slot ``s`` weighs
    ``planted_scale`` times a unit-variance uniform hashed from ``(seed,
    s)`` (no table to look up), and the bias is set on 65 536 rows of a
    stream of their own so that ``click_rate`` of the rows are clicks.
    """
    import jax
    import jax.numpy as jnp

    cards = jnp.asarray(cardinalities, jnp.float32)
    nnz = len(cardinalities)
    a1, span = _power_law_span(cards, zipf_exponent)
    fields = jnp.arange(nnz, dtype=jnp.uint32)

    def draw(row_keys):
        u = jax.vmap(lambda k: jax.random.uniform(k, (nnz,)))(row_keys)
        v = jnp.floor((1.0 + u * span) ** (1.0 / a1)) - 1.0
        v = jnp.clip(v, 0.0, cards - 1.0).astype(jnp.uint32)
        slots = click_slots(fields, v, hash_bits)
        return slots, slots          # a slot's planted weight is its own

    return _click_rows(draw, planted_scale, click_rate)


def click_field_offsets(cardinalities) -> tuple[int, ...]:
    """Where each field's range starts in an *indexed* table, the
    fields' ranges laid end to end, and (last) where the table ends:
    value ``v`` of field ``f`` is feature ``offsets[f] + v``, its own
    weight, no hash."""
    out = tuple(itertools.accumulate(map(int, cardinalities), initial=0))
    if out[-1] >= 1 << 31:
        raise ValueError(f"{out[-1]} features do not fit int32 indices")
    return out


def indexed_field_dictionaries(cardinalities) -> tuple:
    """:func:`click_field_dictionaries` for an indexed table: a field
    of at most ``DICTIONARY_MAX_VALUES`` values holds exactly its own
    range."""
    off = click_field_offsets(cardinalities)
    return tuple(
        np.arange(off[f], off[f + 1], dtype=np.int32)
        if c <= DICTIONARY_MAX_VALUES else None
        for f, c in enumerate(cardinalities))


def indexed_click_rows(cardinalities, *, zipf_exponent: float = 1.1,
                       planted_scale: float = 0.25,
                       click_rate: float = 0.256):
    """:func:`hashed_click_rows` without the hash: a row's slot for
    field ``f`` is ``click_field_offsets(cardinalities)[f] + v``, the
    one-hot index a LIBSVM file would hold (every feature its own
    weight). Two things differ beside that.

    The draw is exact however many values a field has. A float32
    uniform has 2**23 levels and a float32 ``x`` past 2**24 holds no odd
    integer, so the inverse above reaches one value in a hundred of a
    field of 24 million. Here the uniform picks a stratum of the
    distribution (``x`` down to ``x - |dx/du| 2**-23``), a second
    uniform a point inside it, and the value is taken in int32: every
    value can be drawn, odd ones past 2**24 among them, and the
    density inside a stratum is flat where the law's falls by a part in
    2**23.

    The planted weight of a feature is hashed from ``(seed, field,
    value)`` and so does not depend on how a table lays the fields out.
    """
    import jax
    import jax.numpy as jnp

    cards = jnp.asarray(cardinalities, jnp.float32)
    top = jnp.asarray(cardinalities, jnp.int32) - 1
    offsets = jnp.asarray(click_field_offsets(cardinalities)[:-1],
                          jnp.int32)
    nnz = len(cardinalities)
    a1, span = _power_law_span(cards, zipf_exponent)
    stratum = jnp.abs(span / a1) * (2.0 ** -23)
    fields = jnp.arange(nnz, dtype=jnp.uint32)

    def draw(row_keys):
        u = jax.vmap(lambda k: jax.random.uniform(k, (nnz,)))(row_keys)
        j = jax.vmap(lambda k: jax.random.uniform(
            jax.random.fold_in(k, 11), (nnz,)))(row_keys)
        x = (1.0 + u * span) ** (1.0 / a1)
        whole = jnp.floor(x)
        # how far below ``x`` the point lies, less what ``floor`` took
        down = jnp.ceil(j * stratum * x ** float(zipf_exponent)
                        - (x - whole))
        v = whole.astype(jnp.int32) - jnp.maximum(down, 0.0).astype(
            jnp.int32) - 1
        v = jnp.clip(v, 0, top)
        return offsets + v, click_slots(fields, v.astype(jnp.uint32), 32)

    return _click_rows(draw, planted_scale, click_rate)


def _power_law_span(cards, zipf_exponent: float):
    """``(a1, span)`` of the bounded power law's inverse: a uniform
    ``u`` gives ``x = (1 + u span) ** (1 / a1)`` on ``[1, N + 1)``."""
    a1 = 1.0 - float(zipf_exponent)
    if abs(a1) < 1e-3:
        raise ValueError("zipf_exponent 1 has its own inverse; use "
                         "another")
    return a1, (cards + 1.0) ** a1 - 1.0


def _click_rows(draw, planted_scale: float, click_rate: float):
    """What the hashed and the indexed generator share: the seed's
    streams, the planted model, its bias, the labels. ``draw(row_keys)
    -> (slots (n, nnz), uint32 (n, nnz) that name each slot's planted
    weight)``."""
    import jax
    import jax.numpy as jnp

    def slots_and_scores(row_keys, w_salt):
        slots, named = draw(row_keys)
        # the planted weight of a slot: uniform on [-sqrt 3, sqrt 3)
        bits = _mix32(named ^ w_salt) >> 8
        planted = (bits.astype(jnp.float32) * (2.0 ** -23) - 1.0) \
            * (3.0 ** 0.5)
        z = planted_scale * jnp.sum(planted, axis=1)
        return slots.astype(jnp.int32), z

    def streams(seed):
        key = jax.random.key(seed)
        w_salt = jax.random.bits(jax.random.fold_in(key, 0), (),
                                 jnp.uint32)
        return w_salt, jax.random.fold_in(key, 1), \
            jax.random.fold_in(key, 2)

    def row_keys_of(stream, ids):
        return jax.vmap(lambda i: jax.random.fold_in(stream, i))(ids)

    def planted_bias(seed):
        """The bias under which ``click_rate`` of the rows click."""
        w_salt, _, k_cal = streams(seed)
        _, z = slots_and_scores(
            row_keys_of(k_cal, jnp.arange(1 << 16)), w_salt)

        def halve(_, lo_hi):
            lo, hi = lo_hi
            mid = 0.5 * (lo + hi)
            over = jnp.mean(jax.nn.sigmoid(mid + z)) > click_rate
            return jnp.where(over, lo, mid), jnp.where(over, mid, hi)

        lo, hi = jax.lax.fori_loop(
            0, 40, halve, (jnp.float32(-30.0), jnp.float32(30.0)))
        return 0.5 * (lo + hi)

    def make_rows(ids, seed, bias=None):
        w_salt, k_rows, _ = streams(seed)
        bias = planted_bias(seed) if bias is None else bias
        row_keys = row_keys_of(k_rows, ids)
        slots, z = slots_and_scores(row_keys, w_salt)
        coin = jax.vmap(lambda k: jax.random.uniform(
            jax.random.fold_in(k, 7)))(row_keys)
        return slots, (coin < jax.nn.sigmoid(bias + z)).astype(
            jnp.float32)

    make_rows.planted_bias = planted_bias
    return make_rows


def ragged_pair_rows(n_rows: int, n_features: int, *, length_mu: float,
                     length_sigma: float = 1.0, length_min: int = 8,
                     length_max: int = 1 << 16,
                     zipf_exponent: float = 1.1, scatter_a: int = 251,
                     scatter_c: int = 0, planted_scale: float = 0.25,
                     positive_rate: float = 0.6):
    """Jittable generator of *ragged* rows of (feature, value) pairs: a
    LIBSVM file's ``SparseVector``s (an n-gram or text set: webspam's
    trigrams), counter based like the click-log generators (a row is a
    function of the seed and its id alone, a pair of the seed, its
    row's id and its place in the row), every draw a 32-bit hash
    (:func:`_mix32`). Nothing here knows a block or a layout.

    * **A row's length** is a quantile of a log-normal, ``round(exp(
      length_mu + length_sigma z))`` clipped to ``[length_min,
      length_max]``. Row ``i`` of the table's ``n_rows`` takes the
      quantile ``(pi(i) + 1/2) / n_rows``, ``pi`` a permutation of the
      rows keyed by the seed (:func:`feistel_permutation`): every seed
      deals the same lengths to other rows, so the pairs add up to the
      same total whatever the seed. A row past the table (held-out
      rows, the rows the label's bias is set on) draws its quantile
      from a hash of its id.
    * **A pair's feature** is a rank drawn from a bounded power law of
      ``zipf_exponent`` over ``n_features`` (the indexed generator's
      exact draw: a stratum by one hash, a place in it by a second)
      and then scattered over the id space by the fixed bijection ``id
      = (scatter_a * rank + scatter_c) mod n_features``: no range of
      ids is hot by construction. Two pairs of a row may name one
      feature; both are kept.
    * **A pair's raw value** is ``1 +`` a geometric draw (the leading
      zeros of a hash: p = 1/2), an integer; :func:`unit_values` scales a
      row to unit Euclidean length from the exact integer sum of its
      squares.
    * **The label** is a Bernoulli draw of a planted logistic model: a
      feature weighs an odd integer in [-255, 255] hashed from ``(seed,
      id)``, a row's score is the exact int32 sum of weight x raw value
      over its pairs, scaled to unit variance a pair, by the row's
      length and by ``planted_scale``; the bias is set by counting on a
      calibration set so that ``positive_rate`` of its rows are
      positive (:func:`set_bias`). Every sum a label rests on is an
      integer, so two programs that add in different orders draw the
      same labels.

    Returns a namespace of jittable functions that take the seed
    (traced or not) last."""
    import math
    import types

    import jax
    import jax.numpy as jnp
    from jax.scipy.special import ndtri

    u = np.uint32
    if math.gcd(scatter_a, n_features) != 1:
        raise ValueError(f"scatter_a {scatter_a} shares a factor with "
                         f"n_features {n_features}: not a bijection")
    if not 0 <= scatter_c < n_features \
            or scatter_a * (n_features - 1) + scatter_c >= 1 << 32:
        raise ValueError(
            f"scatter_a {scatter_a} x rank + scatter_c {scatter_c} must "
            f"stay under 2**32 over {n_features} ranks")
    if not 0 <= length_min <= length_max:
        raise ValueError(f"lengths {length_min} .. {length_max}")
    a1, span = _power_law_span(np.float32(n_features), zipf_exponent)
    span = np.float32(span)
    stratum = np.float32(abs(span / a1) * 2.0 ** -24)
    deal, _ = feistel_permutation(max(n_rows, 2))
    planted_sd = math.sqrt((256 ** 2 - 1) / 3.0)   # of the odd integers

    def key_of(seed, k: int):
        return _mix32(jnp.asarray(seed).astype(jnp.uint32) * u(0x9E3779B1)
                      + u((0x85EBCA6B * (k + 1)) & 0xFFFFFFFF))

    def word(key, ids):
        return _mix32(jnp.asarray(ids).astype(jnp.uint32) * u(0x9E3779B1)
                      + key)

    def unit(bits):
        """24 bits of a word as a float32 in (0, 1): a level's middle."""
        return ((bits >> u(8)).astype(jnp.float32) + 0.5) * (2.0 ** -24)

    def lengths(row_ids, seed):
        ids = jnp.asarray(row_ids, jnp.int32)
        inside = ids < n_rows
        dealt = deal(jnp.where(inside, ids, 0).astype(jnp.uint32),
                     key_of(seed, 0))
        q = jnp.where(
            inside,
            (dealt.astype(jnp.float32) + 0.5) / np.float32(n_rows),
            unit(word(key_of(seed, 1), ids)))
        x = jnp.exp(np.float32(length_mu)
                    + np.float32(length_sigma) * ndtri(q))
        return jnp.clip(jnp.round(x), length_min,
                        length_max).astype(jnp.int32)

    def pairs(row_ids, places, seed):
        """``(ids int32, raw int32)`` of pair ``places`` of rows
        ``row_ids`` (broadcast against each other)."""
        row_key = word(key_of(seed, 2), row_ids)
        j = jnp.asarray(places).astype(jnp.uint32) * u(0x85EBCA6B)
        h_rank = _mix32(row_key + j)
        h_place = _mix32((row_key ^ u(0x68E31DA4)) + j)
        h_value = _mix32((row_key ^ u(0xB5297A4D)) + j)
        x = (1.0 + (h_rank >> u(8)).astype(jnp.float32) * (2.0 ** -24)
             * span) ** np.float32(1.0 / a1)
        whole = jnp.floor(x)
        # how far below ``x`` the point lies, less what ``floor`` took
        down = jnp.ceil(
            (h_place >> u(8)).astype(jnp.float32) * (2.0 ** -24)
            * stratum * x ** np.float32(zipf_exponent) - (x - whole))
        rank = whole.astype(jnp.int32) \
            - jnp.maximum(down, 0.0).astype(jnp.int32) - 1
        rank = jnp.clip(rank, 0, n_features - 1)
        raw = 1 + jax.lax.clz(h_value).astype(jnp.int32)
        return scatter(rank), raw

    def scatter(rank):
        """The feature id of power-law rank ``rank``: the bijection."""
        return ((jnp.asarray(rank).astype(jnp.uint32) * u(scatter_a)
                 + u(scatter_c)) % u(n_features)).astype(jnp.int32)

    def planted(ids, seed):
        """The planted weight of feature ``ids``: an odd int32 in
        [-255, 255]."""
        named = _mix32((jnp.asarray(ids).astype(jnp.uint32) + u(1))
                       * u(0x9E3779B1))
        return 2 * (_mix32(named ^ key_of(seed, 3)) >> u(24)).astype(
            jnp.int32) - 255

    def unit_values(raw, sum_squares):
        """A pair's float32 value: its raw value over its row's
        Euclidean length (``sum_squares``: the row's exact integer sum,
        broadcast to the pair; 0 where there is no row)."""
        norm = jnp.sqrt(jnp.maximum(sum_squares, 1).astype(jnp.float32))
        return raw.astype(jnp.float32) / norm

    def scores(weighted_sum, sum_squares):
        """A row's planted score from its two integer sums."""
        norm = jnp.sqrt(jnp.maximum(sum_squares, 1).astype(jnp.float32))
        return np.float32(planted_scale / planted_sd) \
            * weighted_sum.astype(jnp.float32) / norm

    def coins(row_ids, seed):
        return unit(word(key_of(seed, 4), row_ids))

    def set_bias(z, coin, live):
        """The bias under which ``positive_rate`` of the rows ``live``
        marks are positive, by bisection on a count."""
        n_live = jnp.sum(live.astype(jnp.int32))
        want = jnp.floor(np.float32(positive_rate)
                         * n_live.astype(jnp.float32)).astype(jnp.int32)

        def halve(_, lo_hi):
            lo, hi = lo_hi
            mid = 0.5 * (lo + hi)
            got = jnp.sum(((coin < jax.nn.sigmoid(mid + z)) & live)
                          .astype(jnp.int32))
            over = got > want
            return jnp.where(over, lo, mid), jnp.where(over, mid, hi)

        lo, hi = jax.lax.fori_loop(
            0, 40, halve, (jnp.float32(-30.0), jnp.float32(30.0)))
        return 0.5 * (lo + hi)

    def labels(z, coin, bias):
        return (coin < jax.nn.sigmoid(bias + z)).astype(jnp.int32)

    return types.SimpleNamespace(
        lengths=lengths, pairs=pairs, scatter=scatter, planted=planted,
        unit_values=unit_values, scores=scores, coins=coins,
        set_bias=set_bias, labels=labels, n_rows=n_rows,
        n_features=n_features)


def power_law_degrees(n_owners: int, total: int, d_min: int, d_max: int,
                      salt: int) -> np.ndarray:
    """How many ratings each of ``n_owners`` owners has: a bounded power
    law on ``[d_min, d_max]`` (the quantiles of a density ~ d ** -a, the
    exponent found by bisection so that the degrees add up to ``total``,
    the last few units given one each to the first owners), dealt to the
    owner ids by a fixed integer mix of ``salt``. A function of its
    arguments alone: no seed reaches it, so every seed's table has the
    same sizes. int64 ``(n_owners,)``."""
    if not n_owners * d_min <= total <= n_owners * d_max:
        raise ValueError(
            f"{total} ratings do not fit {n_owners} owners of {d_min} "
            f"to {d_max}")
    q = (np.arange(n_owners, dtype=np.float64) + 0.5) / n_owners

    def seq(a):
        a1 = 1.0 - a
        lo, hi = float(d_min) ** a1, (d_max + 1.0) ** a1
        return np.clip(np.floor((lo + q * (hi - lo)) ** (1.0 / a1)),
                       d_min, d_max)

    lo, hi = 1.0 + 1e-6, 16.0        # a larger exponent: a smaller sum
    if seq(hi).sum() > total:
        d = np.full(n_owners, float(d_min))
    else:
        for _ in range(80):
            mid = 0.5 * (lo + hi)
            if seq(mid).sum() > total:
                lo = mid
            else:
                hi = mid
        d = seq(hi)
    d = d.astype(np.int64)
    # what is left goes a unit at a time to the largest that have room
    left = int(total - d.sum())
    at = n_owners - 1
    while left > 0:
        take = min(left, int(d_max - d[at]))
        d[at] += take
        left -= take
        at -= 1
    order = np.argsort(
        _mix32(np.arange(n_owners, dtype=np.uint32) * np.uint32(0x9E3779B1)
               + np.uint32(salt)), kind="stable")
    out = np.empty(n_owners, np.int64)
    out[order] = d
    return out


def feistel_permutation(n: int):
    """A seeded permutation of ``[0, n)`` that needs no table and no
    sort: four rounds of a balanced Feistel network over the least even
    number of bits that hold ``n``, walked until the value is under
    ``n``. Returns ``(forward, inverse)``, each ``f(x uint32 array, key
    uint32) -> uint32 array`` (jax); ``inverse(forward(x)) == x``."""
    import jax
    import jax.numpy as jnp

    bits = max(2, int(n - 1).bit_length())
    bits += bits % 2
    half = bits // 2
    mask = np.uint32((1 << half) - 1)
    u = np.uint32

    def keys(key):
        return [_mix32(key + u((0x9E3779B1 * (r + 1)) & 0xFFFFFFFF))
                for r in range(4)]

    def rnd(v, k):
        return _mix32(v * u(0x85EBCA6B) + k) & mask

    def once(x, ks, backwards):
        left, right = x >> u(half), x & mask
        if backwards:
            for k in reversed(ks):
                left, right = right ^ rnd(left, k), left
        else:
            for k in ks:
                left, right = right, left ^ rnd(right, k)
        return (left << u(half)) | right

    def walk(x, key, backwards):
        ks = keys(jnp.asarray(key, jnp.uint32))
        y = once(jnp.asarray(x, jnp.uint32), ks, backwards)
        return jax.lax.while_loop(
            lambda y: jnp.any(y >= u(n)),
            lambda y: jnp.where(y >= u(n), once(y, ks, backwards), y), y)

    return (lambda x, key: walk(x, key, False),
            lambda x, key: walk(x, key, True))


def seeded_ratings(n_ratings: int, k: int, *, mean: float = 50.0,
                   scale: float = 6.0, noise: float = 15.0,
                   low: float = 0.0, high: float = 100.0):
    """The pieces of a seeded explicit-ratings set drawn by the
    configuration model: the stubs of the two sides (owner ``u``
    repeated ``degree[u]`` times, owner-ordered) are paired by a seeded
    permutation, so both degree sequences are kept exactly and a pair
    may come twice. Counter-based throughout: rating ``p`` (the place of
    its user stub) is a function of the seed and ``p`` alone.

    Returns a namespace of jax functions: ``item_stub(p, seed)`` /
    ``user_stub(j, seed)`` (the permutation and its inverse),
    ``heldout_stubs(i, seed)`` (a held-out pair's two stubs),
    ``planted(owner_ids, seed, side)`` (float32 ``(n, k)`` rows of a
    planted rank-``k`` model, multiples of 1/8 in [-1, 7/8], so that a
    dot of two rows is exact in float32 in any order and on the MXU),
    ``rating(dot, p, seed, stream)``: ``mean + scale * dot`` plus a
    triangular noise of half-width ``2 * noise``, rounded to a whole
    number and clipped to ``[low, high]``."""
    import jax.numpy as jnp

    u = np.uint32
    fwd, inv = feistel_permutation(n_ratings)

    def key_of(seed, stream):
        return _mix32(jnp.asarray(seed, jnp.uint32) * u(0x9E3779B1)
                      + u((stream * 0x85EBCA6B + 1) & 0xFFFFFFFF))

    def planted(owner_ids, seed, side: int):
        ids = jnp.asarray(owner_ids, jnp.uint32)[:, None] * u(k) \
            + jnp.arange(k, dtype=jnp.uint32)[None, :]
        bits = _mix32(ids ^ key_of(seed, 8 + side)) >> u(28)
        return (bits.astype(jnp.float32) - 8.0) * 0.125

    def unit(p, seed, stream):
        bits = _mix32(jnp.asarray(p, jnp.uint32) ^ key_of(seed, stream))
        return (bits >> u(8)).astype(jnp.float32) * (2.0 ** -23) - 1.0

    def rating(dot, p, seed, stream: int = 0):
        eps = unit(p, seed, 2 + 2 * stream) + unit(p, seed, 3 + 2 * stream)
        r = jnp.float32(mean) + jnp.float32(scale) * dot \
            + jnp.float32(noise) * eps
        return jnp.clip(jnp.round(r), low, high)

    import types

    return types.SimpleNamespace(
        item_stub=lambda p, seed: fwd(p, key_of(seed, 0)),
        user_stub=lambda j, seed: inv(j, key_of(seed, 0)),
        planted=planted, rating=rating,
        heldout_stubs=lambda i, seed: (
            _mix32(jnp.asarray(i, jnp.uint32) ^ key_of(seed, 16))
            % u(n_ratings),
            _mix32(jnp.asarray(i, jnp.uint32) ^ key_of(seed, 17))
            % u(n_ratings)))


def gaussian_mixture(
    n_rows: int, k: int = 4, dim: int = 2, seed: int = 0, spread: float = 8.0
) -> np.ndarray:
    """Gaussian-mixture points for k-means benchmarks."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, dim)) * spread
    assign = rng.integers(0, k, size=n_rows)
    return (centers[assign] + rng.normal(size=(n_rows, dim))).astype(np.float32)


def gaussian_mixture_rows(k: int = 4, dim: int = 2, seed: int = 0,
                          spread: float = 8.0):
    """Jittable per-row Gaussian-mixture generator for
    ``parallel.build_sharded`` — the host-memory-free sibling of
    :func:`gaussian_mixture` (counter-based per-row PRNG: content
    depends only on the global row id, not the shard topology).
    Returns ``(make_rows, true_centers_fn)``: ``make_rows(row_ids) ->
    (n, dim) points``; ``true_centers_fn()`` the mixture means, for
    recovery checks. Both take the seed as an optional last argument,
    which may be traced (``build_sharded(..., seed=)`` passes it into
    the compiled generator, so one compile serves every seed); left
    out, it is the ``seed`` given here."""
    import jax

    default_seed = seed

    def keys(seed):
        key = jax.random.key(default_seed if seed is None else seed)
        return jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)

    def true_centers(seed=None):
        return jax.random.normal(keys(seed)[0], (k, dim)) * spread

    def make_rows(ids, seed=None):
        k_rows = keys(seed)[1]
        centers = true_centers(seed)
        row_keys = jax.vmap(lambda i: jax.random.fold_in(k_rows, i))(ids)
        assign = jax.vmap(
            lambda rk: jax.random.randint(rk, (), 0, k)
        )(row_keys)
        noise = jax.vmap(
            lambda rk: jax.random.normal(
                jax.random.fold_in(rk, 1), (dim,))
        )(row_keys)
        return centers[assign] + noise

    return make_rows, true_centers


def erdos_renyi_edges(
    n_vertices: int, avg_degree: float = 8.0, seed: int = 0
) -> np.ndarray:
    """Uniform-random directed edge list (src, dst), shape (E, 2), no
    self-loops — the 1M-node PageRank benchmark graph."""
    rng = np.random.default_rng(seed)
    n_edges = int(n_vertices * avg_degree)
    src = rng.integers(0, n_vertices, size=n_edges, dtype=np.int64)
    dst = rng.integers(0, n_vertices - 1, size=n_edges, dtype=np.int64)
    dst = np.where(dst >= src, dst + 1, dst)  # avoid self-loops
    return np.stack([src, dst], axis=1)


GRAPH500_ABCD = (0.57, 0.19, 0.19, 0.05)


def kronecker_edges(scale: int, abcd=GRAPH500_ABCD):
    """Graph500's Kronecker (R-MAT) generator as a function of the edge
    id: ``f(ids uint32 array, seed uint32) -> (src, dst)`` int32 (jax),
    so that any slice of the list is drawn on the device with the seed
    as an argument and nothing crosses to the host. An edge picks one
    quadrant of the adjacency matrix at each of ``scale`` bit levels
    with probabilities (A, B, C, D), each level from its own 32-bit
    hash of (seed, level, id) (:func:`_mix32`: the thresholds are the
    probabilities times 2^32); the quadrant gives one bit of the source
    and one of the destination. The vertex labels are then permuted
    (:func:`feistel_permutation`, keyed by the seed). Edges are
    directed as drawn, duplicates and self-loops included."""
    import jax
    import jax.numpy as jnp

    u = np.uint32
    a, b, c, _ = (float(x) for x in abcd)
    t_a, t_ab, t_abc = (u(min(int(p * 2 ** 32), 2 ** 32 - 1))
                        for p in (a, a + b, a + b + c))
    relabel, _ = feistel_permutation(1 << scale)

    def draw(ids, seed):
        ids = jnp.asarray(ids, jnp.uint32) * u(0x9E3779B1)
        seed = jnp.asarray(seed, jnp.uint32)

        def level(lv, bits):
            src, dst = bits
            key = _mix32(seed + (lv.astype(jnp.uint32) + u(1))
                         * u(0x85EBCA6B))
            h = _mix32(ids + key)
            down = h >= t_ab                      # quadrants C and D
            right = ((h >= t_a) & ~down) | (h >= t_abc)      # B and D
            return (src * u(2) + down.astype(jnp.uint32),
                    dst * u(2) + right.astype(jnp.uint32))

        zero = jnp.zeros(ids.shape, jnp.uint32)
        src, dst = jax.lax.fori_loop(0, scale, level, (zero, zero))
        key = _mix32(seed ^ u(0x68E31DA4))
        return (relabel(src, key).astype(jnp.int32),
                relabel(dst, key).astype(jnp.int32))

    return draw


def chain_forest_edges(n_vertices: int, chain_len: int = 8) -> np.ndarray:
    """Disjoint directed chains — a bounded-closure benchmark graph (the
    closure of an ER graph in the supercritical regime is Θ(V²) pairs, an
    inherently quadratic OUTPUT no sparse representation can avoid; chains
    give closure = (V/L)·C(L,2), linear in V)."""
    chain_len = max(2, min(chain_len, n_vertices))
    if n_vertices < 2:
        return np.zeros((0, 2), dtype=np.int64)
    starts = np.arange(0, n_vertices - chain_len + 1, chain_len)
    src = np.concatenate([s + np.arange(chain_len - 1) for s in starts])
    return np.stack([src, src + 1], axis=1).astype(np.int64)


def grid_edges(side: int, seed: int | None = None) -> np.ndarray:
    """BigDatalog's ``Grid<side>`` (Shkapsky et al., SIGMOD'16, Table 2):
    a (side + 1) × (side + 1) grid, each vertex with an arc to its right
    and to its lower neighbour, ``2 · side · (side + 1)`` arcs, the
    longest path ``2 · side`` arcs (Grid250: 63 001 vertices, 125 500
    arcs, 500 across). ``seed`` permutes the vertex labels (a Datalog
    engine's ids carry no order; row-major labels would leave every arc
    pointing up and half the path matrix empty by construction);
    ``None`` keeps them row-major. Shape (E, 2)."""
    n = side + 1
    ids = np.arange(n * n, dtype=np.int64).reshape(n, n)
    src = np.concatenate([ids[:, :-1].ravel(), ids[:-1, :].ravel()])
    dst = np.concatenate([ids[:, 1:].ravel(), ids[1:, :].ravel()])
    if seed is not None:
        perm = np.random.default_rng(int(seed)).permutation(n * n)
        src, dst = perm[src], perm[dst]
    return np.stack([src, dst], axis=1)


def grid_closure_pairs(side: int) -> int:
    """The pairs of :func:`grid_edges`' transitive closure: a vertex
    reaches every vertex to its right and below, itself excepted, so
    ``(n (n + 1) / 2)^2 - n^2`` at ``n = side + 1`` (Grid250: the
    source's published 1 000 140 875; Grid150: 131 675 775)."""
    n = side + 1
    return (n * (n + 1) // 2) ** 2 - n * n


#: the ratio of one level's vertices to the level above's in
#: :func:`tree_level_sizes`, and the two deepest levels of Tree17, which
#: make the vertices and the pairs total the published row
TREE_LEVEL_RATIO = 2.422
TREE17_DEEPEST = (3_370_020, 8_008_694)
TREE_CHILDREN = (2, 6)


def tree_level_sizes(height: int) -> list[int]:
    """The vertices of each level of BigDatalog's ``Tree<height>``
    (Shkapsky et al., SIGMOD'16, Table 2: "a tree of height ``height``
    whose non-leaf vertices have a random number of children"), the
    root's level first. The paper gives no generator; the published
    Tree17 row (13 766 856 vertices, closure 237 977 708 pairs, 17.29 a
    vertex: the mean depth) binds it: the deepest level lies 18 arcs
    under the root, so ``height + 2`` levels (depths 0 .. height + 1),
    and a geometric profile, level l holding ``round(2.422^l)``
    vertices, comes within half a percent of both totals; at height 17
    the two deepest levels are set so that both are met exactly."""
    if height < 0:
        raise ValueError(f"tree height {height} < 0")
    sizes = [round(TREE_LEVEL_RATIO ** lvl) for lvl in range(height + 2)]
    if height == 17:
        sizes[-2:] = TREE17_DEEPEST
    return sizes


def tree_nonleaves(n_level: int, n_below: int) -> int:
    """How many of a level's ``n_level`` vertices have children, given
    the ``n_below`` of the level under it: a quarter of the children
    (four a parent, the middle of 2 .. 6), held to what 2 .. 6 children
    a parent allow."""
    lo, hi = TREE_CHILDREN
    return max(-(-n_below // hi), min(round(n_below / 4), n_level,
                                      n_below // lo))


def tree_edges(height: int, seed: int | None = None) -> np.ndarray:
    """``Tree<height>`` as arcs from parent to child, shape (V - 1, 2):
    :func:`tree_level_sizes`' levels on every seed; which vertices of a
    level have children, how many each has (2 .. 6: two each, the rest
    dealt to four more places a parent by a permutation) and the labels
    (permuted over all vertices, as :func:`grid_edges`' are) drawn from
    ``seed``. ``None``: the first vertices of a level are the parents,
    the counts as even as they come, labels level by level."""
    sizes = tree_level_sizes(height)
    starts = np.concatenate([[0], np.cumsum(sizes)])
    rng = None if seed is None else np.random.default_rng(int(seed))
    lo, hi = TREE_CHILDREN
    src = []
    for lvl in range(len(sizes) - 1):
        n, below = sizes[lvl], sizes[lvl + 1]
        p = tree_nonleaves(n, below)
        extra, places = below - lo * p, (hi - lo) * p
        if not 0 <= extra <= places:
            raise ValueError(f"level {lvl + 1}: {below} children do not "
                             f"go to {p} parents at {lo} .. {hi} each")
        if rng is None:
            parents, taken = np.arange(p), np.arange(extra) % p
        else:
            parents = rng.permutation(n)[:p]
            taken = rng.permutation(places)[:extra] // (hi - lo)
        counts = lo + np.bincount(taken, minlength=p)
        src.append(starts[lvl] + np.repeat(parents, counts))
    src = np.concatenate(src) if src else np.zeros((0,), np.int64)
    if rng is None:
        return np.stack([src, np.arange(1, starts[-1])], axis=1)
    labels = rng.permutation(int(starts[-1]))
    return np.stack([labels[src], labels[1:]], axis=1)


def tree_closure_pairs(height: int, max_arcs: int | None = None) -> int:
    """The pairs of :func:`tree_edges`' transitive closure, or of its
    paths of at most ``max_arcs`` arcs: a vertex is reached from each of
    its ancestors, so the sum of the depths (each cut at ``max_arcs``);
    Tree17: the source's published 237 977 708."""
    return sum(n * (lvl if max_arcs is None else min(lvl, max_arcs))
               for lvl, n in enumerate(tree_level_sizes(height)))


def toy_graph_edges() -> np.ndarray:
    """The reference's 4-edge toy graph (``pagerank.py:35-38``,
    ``transitive_closure.py:18``), 0-indexed."""
    return np.array([[0, 1], [0, 2], [1, 2], [2, 0]], dtype=np.int64)


def toy_kmeans_matrix() -> np.ndarray:
    """The reference's hard-coded 6x2 k-means input (``k-means.py:49-50``)."""
    return np.array(
        [[1, 2], [1, 4], [1, 0], [10, 2], [10, 4], [10, 0]], dtype=np.float32
    )


def streamed_packed_cache(path: str, n_rows: int, n_features: int, *,
                          n_shards: int, pack: int = 16,
                          gather_block_rows: int = 8192, seed: int = 0,
                          x_dtype="bfloat16", chunk_rows: int = 1 << 21,
                          n_test: int = 8192):
    """Create-or-open a DISK-backed packed two-class dataset for the
    streamed >HBM trainer (``models/ssgd_stream``): ``<path>.bin`` is a
    memmap in the exact ``pack_augmented`` layout, ``<path>.meta.json``
    its geometry, ``<path>.test.npz`` a held-out split from the same
    teacher. Rows are a noisy linear-teacher task (uniform features,
    Bernoulli labels at the teacher's sigmoid) generated ONCE in
    streaming chunks — after that the bytes on disk are opaque data the
    trainer must move, exactly the situation Spark's spill/stream
    handles for the reference (``ssgd.py:86``). Returns
    ``(memmap X2, meta, (X_test, y_test))``; an existing cache with
    matching geometry is reopened read-only at O(ms).

    The disk format and publish protocol are the data subsystem's
    generalized packed cache (``tpu_distalg/data/cache.py`` — the
    engine was lifted OUT of this function in PR 2): versioned header,
    atomic aux→bin→meta publish, PID/uuid tmp names with a stale-orphan
    sweep. Caches written before the versioned header (flat geometry
    dict as the whole meta.json) reopen unchanged via the legacy path —
    a rig's multi-GB cache survives the format promotion."""
    import jax.numpy as jnp

    from tpu_distalg.data import cache as dcache
    from tpu_distalg.ops import pallas_kernels

    d = n_features + 1  # + bias, like the resident flagship task
    d_t, y_col, v_col = pallas_kernels.packed_dims(d, pack)
    mult = pack * gather_block_rows * n_shards
    if n_rows % mult:
        raise ValueError(
            f"n_rows={n_rows} must be a multiple of pack×block×shards="
            f"{mult} (no padding rows in a memmap dataset)")
    n2 = n_rows // pack
    pd = pack * d_t
    np_dtype = np.dtype(jnp.dtype(x_dtype))
    geom = dict(n_rows=n_rows, n_features=n_features, pack=pack,
                d_total=d_t, y_col=y_col, v_col=v_col, seed=seed,
                x_dtype=str(x_dtype), n_test=n_test)
    meta = dict(pack=pack, d_total=d_t, y_col=y_col, v_col=v_col,
                n_padded=n_rows)
    test_path = path + ".test.npz"

    if np_dtype.itemsize != 2:
        raise ValueError(
            f"streamed cache generates bf16 bit-packed rows; "
            f"x_dtype={x_dtype} is not 2-byte")
    rng = np.random.default_rng(seed)
    # features are EXACT bf16 values 1 + m/128, m ~ uniform{0..127}:
    # generated as raw bf16 BIT patterns (exponent fixed at 127, the 7
    # mantissa bits random) so the 32 GB is produced at integer-RNG +
    # bit-op speed — the f32-uniform + astype(bf16) formulation
    # measured ~25 min on this 1-core host, this one ~3 min. The value
    # is affine in m, so a linear teacher on m stays a linear-logit
    # task on the stored features. Var(m/128) = 1/12; teacher scaled
    # for logit std ≈ 2 → its own held-out accuracy ≈ 0.76 (saved in
    # .test.npz as the ceiling).
    wf = rng.standard_normal(d - 1).astype(np.float32)
    # features are ±(1 + m/128): sign-symmetric (mean 0 — uncentered
    # [1,2) features condition the logistic Hessian ~1000:1 worse and
    # SGD crawls), per-feature variance E[(1+u)²] ≈ 2.32. Teacher
    # scaled for logit std ≈ 2; its value-space vector is exactly
    # [wf…, 0] (no intercept needed), saved as the accuracy ceiling.
    VAR_X = 1.0 + 2 * (63.5 / 128.0) + float(
        np.mean((np.arange(128) / 128.0) ** 2))
    wf *= 2.0 / np.sqrt(np.sum(wf ** 2) * VAR_X)
    w_true = np.concatenate([wf, [0.0]]).astype(np.float32)
    EXP0 = np.uint16(127 << 7)   # exponent field for [1, 2)
    ONE = np.uint16(0x3F80)      # bf16 bit pattern of 1.0

    def _values(m, sgn):
        return ((1.0 + m.astype(np.float32) / 128.0)
                * (1.0 - 2.0 * sgn.astype(np.float32)))

    def gen_bits(n, g):
        """(n, d) bf16 bit patterns + labels; column d-1 is the bias."""
        m = g.integers(0, 128, size=(n, d), dtype=np.uint16)
        sgn = g.integers(0, 2, size=(n, d), dtype=np.uint16)
        m[:, -1] = 0
        sgn[:, -1] = 0                    # bias column = exactly +1.0
        logits = _values(m[:, :-1], sgn[:, :-1]) @ wf
        p = 1.0 / (1.0 + np.exp(-logits))
        y = (g.random(n, dtype=np.float32) < p)
        return (EXP0 | m | (sgn << np.uint16(15))), y

    def write_bin(mm):
        # bf16 memmap viewed as its uint16 bit patterns — the generator
        # works in raw bits (the f32 + astype path measured ~8x slower).
        # NOTE: `rng` is the OUTER stream, continued after the teacher
        # draw above — recreating it here would change the bytes vs
        # every cache generated before the engine extraction.
        X2u = mm.view(np.uint16)
        chunk = chunk_rows - (chunk_rows % pack)
        out = np.zeros((chunk, d_t), np.uint16)
        from tpu_distalg.telemetry import events as tevents

        for lo in range(0, n_rows, chunk):
            # per-chunk progress mark: a cold 32 GB generation runs
            # ~15 min and must read as progress, not as a stall, to
            # the heartbeat
            tevents.mark(f"streamed_cache:gen@{lo}/{n_rows}",
                         emit_event=False)
            n_c = min(chunk, n_rows - lo)
            bits, yc = gen_bits(n_c, rng)
            out[:n_c, :d] = bits
            out[:n_c, y_col] = np.where(yc, ONE, np.uint16(0))
            out[:n_c, v_col] = ONE
            X2u[lo // pack:(lo + n_c) // pack] = out[:n_c].reshape(
                n_c // pack, pd)

    def write_test(tmp_path):
        g2 = np.random.default_rng(seed + 1)
        bits_t, y_test = gen_bits(n_test, g2)
        # feature VALUES as the device sees them: ±(1 + m/128)
        X_test = _values(bits_t & np.uint16(0x7F),
                         bits_t >> np.uint16(15))
        # a FILE handle: np.savez on a path appends '.npz', which would
        # break the engine's tmp→final rename
        # tda: ignore[TDA030] -- aux writer invoked INSIDE
        # cache.build_cache's cache:write seam (tmp→rename publish and
        # injection both happen there); single-file analysis cannot
        # see the callback edge
        with open(tmp_path, "wb") as f:
            np.savez(f, X=X_test, y=y_test.astype(np.float32),
                     w_true=w_true)

    header = dcache.make_header(layout="packed_augmented",
                                dtype=str(x_dtype), shape=(n2, pd),
                                geom=geom)
    X2, _hdr = dcache.open_or_build(
        path, header=header, write_bin=write_bin,
        aux=[("test.npz", write_test)], legacy_geom=geom)
    if X2 is None:  # pre-versioned cache (flat geom meta.json)
        X2 = np.memmap(dcache.bin_path(path), dtype=np_dtype, mode="r",
                       shape=(n2, pd))
    t = np.load(test_path)
    return X2, meta, (t["X"], t["y"])
