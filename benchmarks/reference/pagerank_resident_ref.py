"""Plain reference for ``pagerank-graph500-24``: PageRank by power
iteration over all vertices of a Graph500 Kronecker graph that it draws
itself. It imports nothing of the program: the generator is restated
here from the configuration's ``assumed`` (one lowbias32 hash of (seed,
level, edge id) a bit level, a four-round Feistel relabelling), the
distinct edges are found by a sort of (destination, source) pairs, and a
sweep is ``segment_sum`` of ``ranks[src] / outdeg[src]`` by destination
in float32, the mass of vertices with no out-edge spread evenly, from
the uniform start. Everything stays on the device at full size. (The
sort is by destination first so that the sweep's ``segment_sum`` runs
over sorted indices: sorted by source the whole reference took 119 s
of a run's 360 on one v5e at SCALE 24, and 179 s with its programs
compiling.)

``dtype=bfloat16`` keeps ranks and contributions in bfloat16: the
control, which has to come out as not correct.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

U = np.uint32


def mix32(x):
    x = x ^ (x >> U(16))
    x = x * U(0x7FEB352D)
    x = x ^ (x >> U(15))
    x = x * U(0x846CA68B)
    return x ^ (x >> U(16))


def relabel(x, key, n: int):
    """Four Feistel rounds over the least even number of bits that hold
    ``n``, walked until the value is under ``n``."""
    bits = max(2, int(n - 1).bit_length())
    half = (bits + bits % 2) // 2
    mask = U((1 << half) - 1)
    keys = [mix32(key + U((0x9E3779B1 * (r + 1)) & 0xFFFFFFFF))
            for r in range(4)]

    def once(x):
        left, right = x >> U(half), x & mask
        for k in keys:
            left, right = right, left ^ (
                mix32(right * U(0x85EBCA6B) + k) & mask)
        return (left << U(half)) | right

    return jax.lax.while_loop(
        lambda y: jnp.any(y >= U(n)),
        lambda y: jnp.where(y >= U(n), once(y), y), once(x))


def edges(scale: int, edge_factor: int, abcd, seed):
    """(src, dst) int32 of ``edge_factor * 2**scale`` directed edges,
    duplicates and self-loops included; ``seed`` a uint32 scalar."""
    a, b, c, _ = (float(x) for x in abcd)
    t_a, t_ab, t_abc = (U(min(int(p * 2 ** 32), 2 ** 32 - 1))
                        for p in (a, a + b, a + b + c))
    ids = jnp.arange(edge_factor << scale, dtype=jnp.uint32) \
        * U(0x9E3779B1)
    src = dst = jnp.zeros(ids.shape, jnp.uint32)
    for level in range(scale):
        h = mix32(ids + mix32(seed + U(((level + 1) * 0x85EBCA6B)
                                       & 0xFFFFFFFF)))
        down = h >= t_ab
        right = ((h >= t_a) & ~down) | (h >= t_abc)
        src = src * U(2) + down.astype(jnp.uint32)
        dst = dst * U(2) + right.astype(jnp.uint32)
    key = mix32(seed ^ U(0x68E31DA4))
    n = 1 << scale
    return (relabel(src, key, n).astype(jnp.int32),
            relabel(dst, key, n).astype(jnp.int32))


def ranks(scale: int, edge_factor: int, abcd, seed: int, q: float,
          n_sweeps: int, dtype=jnp.float32):
    """(ranks as float32 on the host, the count of distinct edges)."""
    V = 1 << scale

    def graph(seed):
        src, dst = edges(scale, edge_factor, abcd, seed)
        # both operands are keys, so stability buys nothing, and XLA
        # compiles the stable sort in 88 s against 36 (chipless, v5e)
        dst, src = jax.lax.sort((dst, src), num_keys=2, is_stable=False)
        first = jnp.concatenate([
            jnp.ones((1,), bool),
            (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])])
        out_deg = jax.ops.segment_sum(first.astype(jnp.float32), src,
                                      num_segments=V)
        # a repeated edge weighs nothing: counted once
        inv = jnp.where(out_deg > 0, 1.0 / jnp.maximum(out_deg, 1.0), 0.0)
        return src, dst, first, inv, out_deg == 0

    def sweeps(src, dst, first, inv, sink):
        weight = (inv[src] * first).astype(dtype)

        def sweep(r, _):
            per_edge = (r[src] * weight).astype(dtype)
            c = jax.ops.segment_sum(per_edge, dst, num_segments=V,
                                    indices_are_sorted=True)
            c = c + (jnp.sum(r * sink.astype(dtype)) / V).astype(dtype)
            return (q / V + (1 - q) * c).astype(dtype), None

        r0 = jnp.full((V,), 1.0 / V, dtype=dtype)
        return jax.lax.scan(sweep, r0, None, length=n_sweeps)[0]

    src, dst, first, inv, sink = jax.jit(graph)(
        np.uint32(int(seed) & 0xFFFFFFFF))
    n_edges = int(jnp.sum(first))
    out = jax.jit(sweeps)(src, dst, first, inv, sink)
    return np.asarray(out.astype(jnp.float32)), n_edges


def l1_err(r, r_ref) -> float:
    """Sum of absolute differences over the reference's total mass."""
    r, r_ref = np.asarray(r, np.float64), np.asarray(r_ref, np.float64)
    return float(np.abs(r - r_ref).sum() / r_ref.sum())


def max_rel_err(r, r_ref) -> float:
    """Largest difference over the reference's largest rank."""
    r, r_ref = np.asarray(r, np.float64), np.asarray(r_ref, np.float64)
    return float(np.abs(r - r_ref).max() / r_ref.max())
