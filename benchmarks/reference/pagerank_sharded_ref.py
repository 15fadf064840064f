"""Plain reference for ``pagerank-graph500-sharded4``: PageRank by
power iteration over all vertices of a Graph500 Kronecker graph too
large for one device, which it draws itself, in blocks so that it
fits: one destination range a device. It imports nothing of the
program; of ``pagerank_resident_ref.py`` it takes what that file
restates of the generator (the hash, the Feistel relabelling) and the
two error measures.

A block draws every edge id, a piece at a time, keeps the edges whose
destination lies in its range (a sort that puts them first, cut at a
fixed room over the mean; a piece that holds more fails the run), and
finds its distinct edges by one more sort of (destination, source):
pieces and the kept edges are filled to one length, so that one
compiled sort serves all of them (a sort of this size takes the
compiler most of a minute). The out-degrees are added over the ranges.
A sweep is ``segment_sum`` of ``ranks[src] / outdeg[src]`` by
destination inside every range, in float32, the ranges concatenated,
the mass of vertices with no out-edge spread evenly, from the uniform
start. The blocks are a leading axis under ``vmap``, laid over the
devices by an array sharding alone: no ``shard_map``, no plan, no
kernel.

``dtype=bfloat16`` keeps ranks and contributions in bfloat16: the
control, which has to come out as not correct.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from reference.pagerank_resident_ref import (  # noqa: F401
    U, l1_err, max_rel_err, mix32, relabel)


def edges_of(ids, scale: int, abcd, seed):
    """(src, dst) uint32 of the edges ``ids`` (uint32), duplicates and
    self-loops included; ``seed`` a uint32 scalar."""
    a, b, c, _ = (float(x) for x in abcd)
    t_a, t_ab, t_abc = (U(min(int(p * 2 ** 32), 2 ** 32 - 1))
                        for p in (a, a + b, a + b + c))
    ids = ids * U(0x9E3779B1)
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
    return relabel(src, key, n), relabel(dst, key, n)


def ranks(scale: int, edge_factor: int, abcd, seed: int, q: float,
          n_sweeps: int, n_blocks: int, *, pieces: int = 4,
          room: float = 1.25, dtype=jnp.float32, devices=None):
    """(ranks as float32 on the host, the count of distinct edges).
    ``n_blocks`` destination ranges, each drawn in ``pieces`` pieces of
    the edge ids with ``room`` x the mean share kept a piece, on
    ``devices`` (the first ``n_blocks`` of ``jax.devices()``, or as
    many as there are)."""
    V, n_in = 1 << scale, edge_factor << scale
    if V % n_blocks or n_in % pieces:
        raise ValueError("ranges and pieces have to be whole")
    own, piece = V // n_blocks, n_in // pieces
    keep = min(piece, int(piece / n_blocks * room) + 1)
    length = max(piece, pieces * keep)     # of everything that is sorted
    devices = list(devices or jax.devices())[:n_blocks]
    while n_blocks % len(devices):
        devices.pop()
    mesh = Mesh(np.array(devices), ("blocks",))
    by_block = NamedSharding(mesh, P("blocks"))
    whole = NamedSharding(mesh, P())
    blocks = jnp.arange(n_blocks, dtype=jnp.uint32)

    def filled(x, fill):
        return jnp.pad(x, ((0, 0), (0, length - x.shape[1])),
                       constant_values=fill)

    def one_piece(p, seed):
        """A piece's edges a block: (destination, or V where it is not
        the block's; source; how many are the block's)."""
        ids = p * U(piece) + jnp.arange(piece, dtype=jnp.uint32)
        src, dst = edges_of(ids, scale, abcd, seed)
        mine = (dst // U(own))[None, :] == blocks[:, None]
        return (filled(jnp.where(mine, dst[None, :], U(V)), V),
                filled(jnp.broadcast_to(src, mine.shape), 0),
                jnp.sum(mine, axis=1))

    def sort_pairs(dst, src):
        # both operands are keys, so stability buys nothing
        return jax.vmap(lambda d, s: jax.lax.sort(
            (d, s), num_keys=2, is_stable=False))(dst, src)

    def graph(dst, src):
        """From a block's edges sorted by (destination, source), the
        slots past them behind: a repeated edge weighs nothing."""
        first = (dst < U(V)) & jnp.concatenate([
            jnp.ones((n_blocks, 1), bool),
            (src[:, 1:] != src[:, :-1]) | (dst[:, 1:] != dst[:, :-1])],
            axis=1)
        src = src.astype(jnp.int32)
        # a slot past the edges adds nothing, to the range's last vertex
        at = jnp.where(dst < U(V), dst - blocks[:, None] * U(own),
                       U(own - 1)).astype(jnp.int32)
        out_deg = jnp.sum(jax.vmap(lambda f, s: jax.ops.segment_sum(
            f.astype(jnp.float32), s, num_segments=V))(first, src), axis=0)
        inv = jnp.where(out_deg > 0, 1.0 / jnp.maximum(out_deg, 1.0), 0.0)
        return src, at, first, inv, out_deg == 0, jnp.sum(first)

    def sweeps(src, at, first, inv, sink):
        weight = (inv[src] * first).astype(dtype)

        def sweep(r, _):
            per_edge = (r[src] * weight).astype(dtype)
            c = jax.vmap(lambda x, d: jax.ops.segment_sum(
                x, d, num_segments=own, indices_are_sorted=True))(
                    per_edge, at).reshape(V)
            c = c + (jnp.sum(r * sink.astype(dtype)) / V).astype(dtype)
            return (q / V + (1 - q) * c).astype(dtype), None

        r0 = jnp.full((V,), 1.0 / V, dtype=dtype)
        return jax.lax.scan(sweep, r0, None, length=n_sweeps)[0]

    pair = (by_block, by_block)
    one_piece = jax.jit(one_piece, out_shardings=pair + (whole,))
    sort_pairs = jax.jit(sort_pairs, in_shardings=pair, out_shardings=pair,
                         donate_argnums=(0, 1))
    seed = np.uint32(int(seed) & 0xFFFFFFFF)
    kept = []
    for p in range(pieces):
        dst, src, held = one_piece(np.uint32(p), seed)
        if int(jnp.max(held)) > keep:
            raise RuntimeError(
                f"the reference keeps {keep} edges of a piece a range "
                f"({room} x the mean) and piece {p} of seed {seed} held "
                f"{int(jnp.max(held))}: more room, not fewer edges")
        dst, src = sort_pairs(dst, src)
        kept.append((dst[:, :keep], src[:, :keep]))
    dst, src = sort_pairs(
        *(jax.device_put(filled(jnp.concatenate(x, axis=1), fill), by_block)
          for x, fill in zip(zip(*kept), (V, 0))))
    del kept
    edge = (by_block,) * 3
    src, at, first, inv, sink, n_edges = jax.jit(
        graph, in_shardings=pair, out_shardings=edge + (whole,) * 3)(
            dst, src)
    del dst
    out = jax.jit(sweeps, in_shardings=edge + (whole,) * 2,
                  out_shardings=whole)(src, at, first, inv, sink)
    return np.asarray(out.astype(jnp.float32)), int(n_edges)
