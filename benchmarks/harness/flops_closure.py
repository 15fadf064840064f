"""Operations a dense closure round needs, from shapes alone (kept with
the benchmark, like ``harness/flops_kmeans.py``, so that no PR which
claims a gain can change what ``closure_mxu_roofline`` is a share of)."""

from __future__ import annotations


def round_flops_needed(shapes: dict) -> int:
    """A round composes the path matrix with itself as a dense boolean
    product: for every pair (x, z) of the graph's V vertices, a multiply
    and an add for every vertex y between them: ``2 V^3``, 5.001e14 at
    Grid250's 63 001. Not counted, because a form of the work and not
    the work: the 487 isolated vertices V is padded with to whole tiles
    (2 x 63 488^3 is 2.3% more), the byte-to-bfloat16 turn of every
    operand tile, the or with the old tile, the count. A kernel that
    proves blocks of an operand empty and skips them does less than
    this count; the count is then a ``benchmark`` PR's to restate
    before that kernel's share is read."""
    v = shapes["n_vertices"]
    return 2 * v * v * v
