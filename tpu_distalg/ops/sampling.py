"""Sampling kernels: Bernoulli minibatch masks and Monte-Carlo acceptance.

Replaces ``RDD.sample(False, frac, 42+t)`` (``/root/reference/optimization/
ssgd.py:97``) with a static-shape Bernoulli *mask* — SURVEY.md §7 hard part
#2: the sampled count is dynamic, so instead of a variable-size batch we keep
every row and weight it 0/1, dividing by the masked count. Bits come from the
partitionable threefry PRNG, so the mask for row i is independent of the
device topology.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

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


def sample_block_ids(
    base_key: jax.Array, n_shards: int, n_blocks: int, n_sampled: int
) -> jax.Array:
    """Per-shard without-replacement block draw shared by the fused
    gather samplers (SSGD's flagship path and the local-update family):
    for each shard s, ``fold_in(base_key, s)`` seeds one threefry draw
    and the ``n_sampled`` smallest of ``n_blocks`` random words are the
    sampled block ids — a uniform without-replacement sample,
    deterministic in ``base_key`` and independent of device topology.
    Returns (n_shards, n_sampled) int32. Callers build ``base_key`` from
    the absolute step id (and local-step index where applicable), so
    segmented checkpoint/resume replays identical draws.
    """
    with jax.named_scope(names.SSGD_DRAW):
        ks = jax.vmap(
            lambda s: jax.random.fold_in(base_key, s)
        )(jnp.arange(n_shards))
        bits = jax.vmap(lambda k: jax.random.bits(k, (n_blocks,)))(ks)
        return jnp.argsort(bits, axis=-1)[:, :n_sampled].astype(jnp.int32)


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
