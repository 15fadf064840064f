"""Bytes a pair-set closure round needs to move, from shapes and the
round's own counts alone (kept with the benchmark, like
``harness/bytes_pagerank.py``, so that no PR which claims a gain can
change what ``closure_sparse_roofline`` is a share of)."""

from __future__ import annotations

PAIR = 8        # a pair is two int32 vertex ids


def round_bytes_needed(shapes: dict, set_pairs: float, new_pairs: float,
                       candidates: float) -> float:
    """Per round, whatever layout implements it: the set is read once
    and written once (``set_pairs`` is the mean of what a round finds
    and what it leaves); the pairs the round before found new are read
    twice (their degree looked up, then joined) and the round's own new
    pairs written once; a candidate is written by the join and read by
    whatever makes the set distinct; the arcs' rows are gathered: a
    degree and an offset (8 B) a joined pair, a target (4 B) a
    candidate. Not counted, because a form of the work and not the
    work: the empty slots of the three buffers (``capacity``,
    ``delta_capacity``, ``join_capacity`` in ``shapes``), the passes of
    a sort over its operands, a compaction's passes, the tag a
    candidate carries through the sort."""
    del shapes      # nothing of the layout is needed work
    return (2 * PAIR * set_pairs + 3 * PAIR * new_pairs
            + 2 * PAIR * candidates + 8 * new_pairs + 4 * candidates)
