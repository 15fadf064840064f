"""Plain reference of transitive closure as a set of pairs, for the
``closure_sparse`` family (BigDatalog's Tree17). It imports nothing of
the program: it restates the tree's generator from the configuration's
file (``level_sizes``, ``children``) and ``--seed``, and follows the
reference script's own *naive* linear join
(``graph_computation/transitive_closure.py:27-40``: every path joined
with the edges, united with what was known, made distinct, until the
count stands) over the edge list in NumPy, for a sample of source
vertices, a block of sources at a time so that it fits.

A pair is held as one int64 key, ``source's index in the sample * V +
target``. ``L - 1`` joins from the sources' own arcs give every path of
at most ``L`` arcs; the program's round ``r`` adds one arc, so it is
held against ``L = r + 1``.

What is cheap to count whole is counted whole: on the tree a vertex is
reached from each of its ancestors, so the pairs within ``L`` arcs are
the sum over the levels of ``size * min(depth, L)``
(:func:`tree_pairs_within`), the fixpoint's the source's published
number; on a graph small enough (the tests' grid) the same join runs
from every vertex (:func:`count_within`).
"""

from __future__ import annotations

import numpy as np

from reference import closure_ref


def tree_nonleaves(n_level: int, n_below: int, children) -> int:
    """How many vertices of a level have children: a quarter of the
    level below (four a parent), held to ``children`` = [least, most]
    a parent."""
    lo, hi = children
    return max(-(-n_below // hi),
               min(round(n_below / 4), n_level, n_below // lo))


def tree_edges(level_sizes, children, seed: int) -> np.ndarray:
    """The tree's arcs, parent to child, (V - 1, 2): level ``l`` holds
    ``level_sizes[l]`` vertices on every seed; the seed draws which of
    them have children (a permutation's first), how many each has (the
    least each, the rest dealt by a second permutation to ``most -
    least`` more places a parent) and, last, the labels (a permutation
    of all vertices)."""
    lo, hi = children
    sizes = [int(n) for n in level_sizes]
    starts = np.concatenate([[0], np.cumsum(sizes)])
    rng = np.random.default_rng(int(seed))
    src = []
    for lvl in range(len(sizes) - 1):
        n, below = sizes[lvl], sizes[lvl + 1]
        p = tree_nonleaves(n, below, children)
        extra = below - lo * p
        parents = rng.permutation(n)[:p]
        taken = rng.permutation((hi - lo) * p)[:extra] // (hi - lo)
        counts = lo + np.bincount(taken, minlength=p)
        assert counts.sum() == below and counts.max() <= hi
        src.append(starts[lvl] + np.repeat(parents, counts))
    src = np.concatenate(src)
    labels = rng.permutation(int(starts[-1]))
    return np.stack([labels[src], labels[1:]], axis=1)


def tree_pairs_within(level_sizes, max_arcs: int | None = None) -> int:
    """Pairs joined by a path of 1 .. ``max_arcs`` arcs (all: None)."""
    return sum(int(n) * (lvl if max_arcs is None else min(lvl, max_arcs))
               for lvl, n in enumerate(level_sizes))


def graph_edges(config: dict, seed: int) -> np.ndarray:
    """The configuration's graph from ``--seed``: the tree, or (the
    tests' and the control's) BigDatalog's grid."""
    if "grid_side" in config:
        return closure_ref.grid_edges(config["grid_side"], seed)
    return tree_edges(config["level_sizes"], config["children"], seed)


def by_source(edges: np.ndarray, n_vertices: int):
    """The distinct arcs in source order: (offset a vertex, targets)."""
    e = np.unique(edges[:, 0] * n_vertices + edges[:, 1])
    return (np.searchsorted(e // n_vertices, np.arange(n_vertices + 1)),
            e % n_vertices)


def join(keys: np.ndarray, start, dst, n_vertices: int) -> np.ndarray:
    """``paths ⋈ edges``: every pair (s, y) with every arc (y, z)."""
    s, y = keys // n_vertices, keys % n_vertices
    fan = start[y + 1] - start[y]
    first = np.repeat(start[y] - (np.cumsum(fan) - fan), fan)
    z = dst[first + np.arange(int(fan.sum()))]
    return np.repeat(s, fan) * n_vertices + z


def reach_within(arcs, n_vertices: int, sources: np.ndarray,
                 max_arcs: int, block: int = 64) -> np.ndarray:
    """The sorted keys ``index of the source * V + target`` of every
    pair joined by a path of 1 .. ``max_arcs`` arcs from one of
    ``sources``: the script's loop over ``arcs`` (:func:`by_source`'s),
    ``block`` sources at a time."""
    start, dst = arcs
    out = []
    for b in range(0, len(sources), block):
        idx = np.arange(b, min(b + block, len(sources)))
        paths = join(idx * n_vertices + sources[idx], start, dst, n_vertices)
        for _ in range(max_arcs - 1):
            new = np.unique(np.concatenate(
                [paths, join(paths, start, dst, n_vertices)]))
            if len(new) == len(paths):
                break
            paths = new
        out.append(np.unique(paths))
    return np.concatenate(out) if out else np.zeros((0,), np.int64)


def count_within(edges: np.ndarray, n_vertices: int, max_arcs: int) -> int:
    """Every pair within ``max_arcs`` arcs, counted whole from every
    vertex (small graphs only)."""
    return len(reach_within(by_source(edges, n_vertices), n_vertices,
                            np.arange(n_vertices), max_arcs))


def set_errors(got: np.ndarray, want: np.ndarray) -> int:
    """Disagreements of a held multiset of keys with the exact set:
    pairs held and not reached, reached and not held, and every copy
    past the first of a pair held twice."""
    once = np.unique(got)
    return len(np.setxor1d(once, want)) + len(got) - len(once)


class Reference:
    def __init__(self, config: dict, seed: int):
        self.config, self.seed = config, int(seed)
        self.n_vertices = config["n_vertices"]
        self.edges = graph_edges(config, seed)
        self.sources = closure_ref.sample_sources(
            self.n_vertices, config["sample_rows"], seed)
        self.arcs = by_source(self.edges, self.n_vertices)
        self._counts: dict[int, int] = {}

    def keys(self, x: np.ndarray, z: np.ndarray) -> np.ndarray:
        """Held pairs (x, z) of sampled sources as the reference's
        keys."""
        return (np.searchsorted(self.sources, x).astype(np.int64)
                * self.n_vertices + z)

    def reached(self, max_arcs: int) -> np.ndarray:
        return reach_within(self.arcs, self.n_vertices, self.sources,
                            max_arcs)

    def pairs(self, max_arcs: int) -> int:
        """Every pair within ``max_arcs`` arcs: the tree's closed form,
        the grid's counted whole."""
        if "level_sizes" in self.config:
            return tree_pairs_within(self.config["level_sizes"], max_arcs)
        if max_arcs not in self._counts:
            self._counts[max_arcs] = count_within(
                self.edges, self.n_vertices, max_arcs)
        return self._counts[max_arcs]
