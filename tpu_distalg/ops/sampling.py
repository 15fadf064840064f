"""Sampling kernels: Bernoulli minibatch masks and Monte-Carlo acceptance.

Replaces ``RDD.sample(False, frac, 42+t)`` (``/root/reference/optimization/
ssgd.py:97``) with a static-shape Bernoulli *mask* — SURVEY.md §7 hard part
#2: the sampled count is dynamic, so instead of a variable-size batch we keep
every row and weight it 0/1, dividing by the masked count. Bits come from the
partitionable threefry PRNG, so the mask for row i is independent of the
device topology.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpu_distalg.telemetry import names


def bernoulli_mask(
    key: jax.Array, t, n: int, fraction: float, valid: jax.Array
) -> jax.Array:
    """0/1 float mask of shape (n,): row kept iff u_i < fraction and valid.

    ``key`` folded with the iteration index replaces ``seed=42+t``.
    """
    from tpu_distalg.utils import prng

    u = jax.random.uniform(prng.step_key(key, t), (n,))
    return jnp.where(u < fraction, 1.0, 0.0) * valid


# The boundary between the two forms of the draw, a count of sampled
# blocks: up to FEW_MAX of them are taken by that many min-reduce
# rounds, more by the sort. From chip readings on one v5e with the rows
# in the sublanes (my chip runs, PR 25; PERF.md §6), us a row, rounds
# against sort, by blocks a row and blocks sampled:
#   12 208 blocks (the benchmark's): 12: 1.2 / 8.8; 64: 2.9 / 10.6;
#     128: 5.4 / 10.6; 192: 8.0 / 10.6; 256: 10.5 / 10.6 (the sort
#     7.3 at 250 rows a call, 8.6 to 10.6 at 1000 to 2500)
#   131 072 blocks: 128: 94 / 198; 256: 184 / 198
#   1024 blocks: 32: 0.17 / 0.26; 64: 0.30 / 0.26; 128: 0.50 / 0.26
# A round costs 0.04 us a row at 12 208 blocks and scales with the
# blocks; the sort does not care what is kept. The rounds lead up to
# 175 to 256 sampled where a draw costs anything, and at 1024 blocks a
# wrong choice costs a quarter of a microsecond. The benchmark's cells
# sit far to either side (12 and 1221).
FEW_MAX = 128

_ALL_ONES = np.uint32(0xFFFFFFFF)
_NO_ID = np.int32(np.iinfo(np.int32).max)


def draw_form(n_blocks: int, n_sampled: int) -> str:
    """Which exact selection ``sample_block_ids`` takes for this static
    pair: ``'few'`` (``n_sampled`` rounds of a min-reduce) or ``'sort'``
    (a full stable sort, cut to its first ``n_sampled``). Both return
    the same array; only the cost differs."""
    if not 0 < n_sampled <= n_blocks:
        raise ValueError(
            f"cannot draw {n_sampled} of {n_blocks} blocks without "
            "replacement")
    return "few" if n_sampled <= FEW_MAX else "sort"


def _lexmin(a, b):
    """The smaller of two (word, id) pairs, the word first."""
    (aw, ai), (bw, bi) = a, b
    a_first = (aw < bw) | ((aw == bw) & (ai < bi))
    return jnp.where(a_first, aw, bw), jnp.where(a_first, ai, bi)


def _select_few(bits: jax.Array, n_sampled: int) -> jax.Array:
    """(rows, n_blocks) words -> (rows, n_sampled) ids: round r keeps
    the smallest (word, id) pair above round r-1's, so the order and
    the ties are the stable sort's by construction. What is already
    taken is left out by a mask on the pair, not by a word's value: a
    taken slot reads (all ones, no id) and loses to a real all-ones
    word. Each round is one fused compare-and-reduce pass over the
    words; nothing is written back."""
    rows = bits.shape[0]
    ids = lax.broadcasted_iota(jnp.int32, bits.shape, 1)

    def one_round(r, carry):
        last_w, last_i, out = carry
        taken = (bits < last_w[:, None]) | (
            (bits == last_w[:, None]) & (ids <= last_i[:, None]))
        last_w, last_i = lax.reduce(
            (jnp.where(taken, _ALL_ONES, bits),
             jnp.where(taken, _NO_ID, ids)),
            (_ALL_ONES, _NO_ID), _lexmin, (1,))
        return last_w, last_i, lax.dynamic_update_index_in_dim(
            out, last_i, r, 1)

    # (0, -1) is below every pair: the first round takes nothing out
    start = (jnp.zeros((rows,), jnp.uint32),
             jnp.full((rows,), -1, jnp.int32),
             jnp.zeros((rows, n_sampled), jnp.int32))
    return lax.fori_loop(0, n_sampled, one_round, start)[2]


def _select_sort(bits: jax.Array, n_sampled: int) -> jax.Array:
    return jnp.argsort(bits, axis=-1)[:, :n_sampled].astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def _selector(form: str, n_sampled: int):
    """The selection over a 2-D (rows, n_blocks) array of words, with
    its own batching rule: a ``vmap`` over steps (every trainer's) adds
    its axis to the ROWS instead of leaving (steps, n_shards, n_blocks)
    with a short n_shards axis in the sublanes. On the chip the sort
    and the reduce take eight rows for the price of one: the sort of
    u32[250,1,12208] costs 67 us a row, of u32[250,12208] 7.2 us
    (PERF.md §6, PR 25)."""
    select = {"few": _select_few, "sort": _select_sort}[form]

    @jax.custom_batching.custom_vmap
    def rows_select(bits):
        return select(bits, n_sampled)

    @rows_select.def_vmap
    def _(axis_size, in_batched, bits):
        del in_batched                   # one argument, so it is batched
        flat = bits.reshape(axis_size * bits.shape[1], bits.shape[2])
        return rows_select(flat).reshape(
            axis_size, bits.shape[1], n_sampled), True

    return rows_select


def sample_block_ids(
    base_key: jax.Array, n_shards: int, n_blocks: int, n_sampled: int
) -> jax.Array:
    """Per-shard without-replacement block draw shared by the fused
    gather samplers (SSGD's flagship path and the local-update family):
    for each shard s, ``fold_in(base_key, s)`` seeds one threefry draw
    and the ``n_sampled`` smallest of ``n_blocks`` random words are the
    sampled block ids, smallest word first, equal words by id — a
    uniform without-replacement sample, deterministic in ``base_key``
    and independent of device topology. Returns (n_shards, n_sampled)
    int32: element for element ``argsort(words)[:, :n_sampled]`` with a
    stable sort, which is how it was first written and what older
    checkpoints, the golden trajectories and the benchmark's reference
    replay. HOW the smallest are found is ``draw_form``'s choice from
    the two static counts (rounds of a min-reduce for a few, the sort
    for many); the result does not depend on it. Callers build
    ``base_key`` from the absolute step id (and local-step index where
    applicable), so segmented checkpoint/resume replays identical
    draws.
    """
    form = draw_form(n_blocks, n_sampled)
    with jax.named_scope(names.SSGD_DRAW):
        ks = jax.vmap(
            lambda s: jax.random.fold_in(base_key, s)
        )(jnp.arange(n_shards))
        bits = jax.vmap(lambda k: jax.random.bits(k, (n_blocks,)))(ks)
        return _selector(form, n_sampled)(bits)


def mc_circle_hits(key: jax.Array, n: int) -> jax.Array:
    """Count darts landing in the unit circle out of ``n`` thrown.

    The reference's ``is_accept`` (``randomized_algorithm/monte_carlo.py:
    17-20``) draws x,y ~ U[-1,1) per element with *unseeded* ``random()``;
    here the draw is a deterministic counter-based batch and the count is a
    single fused reduction.
    """
    xy = jax.random.uniform(key, (n, 2), minval=-1.0, maxval=1.0)
    return jnp.sum(
        (jnp.sum(xy * xy, axis=1) <= 1.0).astype(jnp.int32)
    )


def mc_chunk_plan(n: int, chunk: int):
    """Static chunking plan: (n_chunks, darts_per_chunk); draws ≥ n darts."""
    n_chunks = max(1, -(-n // chunk))
    per = -(-n // n_chunks)
    return n_chunks, per


def mc_circle_hits_chunked(key: jax.Array, n: int, chunk: int = 1 << 20):
    """Memory-bounded variant: scan over chunks of at most ``chunk`` darts.

    Draws exactly ``n_chunks * per`` darts (≥ n; use ``mc_chunk_plan`` for
    the true count). Returns the (n_chunks,) int32 vector of per-chunk hit
    counts rather than a running total — each entry is ≤ chunk ≤ 2^20, so
    int32 never overflows regardless of total dart count; callers sum in
    int64 on the host (or psum the vector, which stays ≤ 2^20·n_shards).
    """
    n_chunks, per = mc_chunk_plan(n, chunk)

    def body(carry, i):
        hits = mc_circle_hits(jax.random.fold_in(key, i), per)
        return carry, hits

    _, per_chunk = jax.lax.scan(
        body, jnp.int32(0), jnp.arange(n_chunks)
    )
    return per_chunk
