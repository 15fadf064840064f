"""Plain reference of transitive closure on BigDatalog's grid, for the
``closure_dense`` family. It imports nothing of the program: it restates
the grid and the label permutation from the configuration's file and
``--seed``, and follows the reference script's own *linear* join
(``graph_computation/transitive_closure.py:27-40``: the paths joined
with the edges, united with what was known) for a sample of source
vertices, in plain ``jax.numpy``.

``reach[v, s]`` says that sampled source ``s`` reaches ``v``. One
relaxation is the script's join grouped by destination, as its
``reduceByKey`` after the join groups it: ``reach[v] |= reach[u]`` for
every arc ``u -> v``, read through a table of each vertex's
in-neighbours (padded with a vertex nothing reaches). ``L - 1``
relaxations from the sources' own arcs give every path of at most ``L``
arcs; a relaxation that adds nothing ends the loop early (the next
would add nothing either). The program's round ``r`` doubles, so it is
held against ``L = min(2^r, V)``.

What is cheap to count whole is counted whole: the pairs joined by at
most two arcs, from the edge list alone (:func:`pairs_within_two`); the
fixpoint's count is the source's published number (the configuration's
``closure_pairs``, the closed form :func:`closure_pairs` restates).
"""

from __future__ import annotations

import numpy as np


def grid_edges(side: int, seed: int) -> np.ndarray:
    """The arcs of the (side + 1) x (side + 1) grid, right and down,
    row-major vertices relabelled by the seed's permutation; (E, 2)."""
    n = side + 1
    ids = np.arange(n * n, dtype=np.int64).reshape(n, n)
    src = np.concatenate([ids[:, :-1].ravel(), ids[:-1, :].ravel()])
    dst = np.concatenate([ids[:, 1:].ravel(), ids[1:, :].ravel()])
    perm = np.random.default_rng(int(seed)).permutation(n * n)
    return np.stack([perm[src], perm[dst]], axis=1)


def closure_pairs(side: int) -> int:
    """A grid vertex reaches what lies right of it and below: ``(n (n +
    1) / 2)^2 - n^2`` pairs at ``n = side + 1``."""
    n = side + 1
    return (n * (n + 1) // 2) ** 2 - n * n


def sample_sources(n_vertices: int, n: int, seed: int) -> np.ndarray:
    """``n`` distinct source vertices, sorted, from ``--seed``."""
    rng = np.random.default_rng([int(seed), 1])
    return np.sort(rng.choice(n_vertices, size=min(n, n_vertices),
                              replace=False))


def pairs_within_two(edges: np.ndarray, n_vertices: int) -> int:
    """Distinct pairs joined by a path of one or two arcs."""
    e = np.unique(edges[:, 0] * n_vertices + edges[:, 1])
    src, dst = e // n_vertices, e % n_vertices
    order = np.argsort(src, kind="stable")
    src_s, dst_s = src[order], dst[order]
    start = np.searchsorted(src_s, np.arange(n_vertices + 1))
    deg = start[1:] - start[:-1]
    # every arc (x, y) joined with y's out-arcs (y, z)
    fan = deg[dst]
    x = np.repeat(src, fan)
    first = np.repeat(start[dst], fan)
    within = np.arange(fan.sum()) - np.repeat(np.cumsum(fan) - fan, fan)
    z = dst_s[first + within]
    return len(np.unique(np.concatenate([e, x * n_vertices + z])))


class Reference:
    def __init__(self, side: int, seed: int, n_sources: int, device=None):
        self.side, self.seed = side, int(seed)
        self.n_vertices = (side + 1) ** 2
        self.edges = grid_edges(side, seed)
        self.sources = sample_sources(self.n_vertices, n_sources, seed)
        self.device = device
        self._made = None

    def in_neighbours(self) -> np.ndarray:
        """``int32[V, widest in-degree]``: each vertex's in-neighbours,
        padded with vertex ``V`` (a row nothing reaches)."""
        v = self.n_vertices
        e = np.unique(self.edges[:, 0] * v + self.edges[:, 1])
        src, dst = e // v, e % v
        order = np.argsort(dst, kind="stable")
        src, dst = src[order], dst[order]
        start = np.searchsorted(dst, np.arange(v))
        slot = np.arange(len(dst)) - start[dst]
        table = np.full((v, int(slot.max()) + 1), v, np.int32)
        table[dst, slot] = src
        return table

    def _follow(self):
        """The jitted relaxation loop and the table it reads, made once:
        ``follow(reach f32[V + 1, sources], n_relax)``; row ``V`` stays
        zero."""
        if self._made is None:
            import jax
            import jax.numpy as jnp

            v = self.n_vertices
            put = (lambda x: jax.device_put(x, self.device)) \
                if self.device is not None else jnp.asarray
            table = put(self.in_neighbours())

            @jax.jit
            def follow(reach, n_relax):
                def relax(state):
                    reach, _, i = state
                    got = reach[:v]
                    for k in range(table.shape[1]):
                        got = jnp.maximum(got, reach[table[:, k]])
                    new = jnp.concatenate([got, reach[v:]])
                    return new, jnp.any(new != reach), i + 1

                def more(state):
                    return state[1] & (state[2] < n_relax)

                return jax.lax.while_loop(
                    more, relax, (reach, jnp.bool_(True), jnp.int32(0)))[0]

            self._made = (follow, put)
        return self._made

    def rows(self, max_arcs: int) -> np.ndarray:
        """``bool[sources, V]``: what each sampled source reaches by a
        path of at least one and at most ``max_arcs`` arcs."""
        v, s = self.n_vertices, len(self.sources)
        col = np.minimum(np.searchsorted(self.sources, self.edges[:, 0]),
                         s - 1)
        mine = self.sources[col] == self.edges[:, 0]
        first = np.zeros((v + 1, s), np.float32)
        first[self.edges[mine, 1], col[mine]] = 1.0
        follow, put = self._follow()
        got = follow(put(first), np.int32(max_arcs - 1))
        return np.asarray(got)[:v].T > 0
