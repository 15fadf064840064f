"""Operations a Lloyd iteration needs, from shapes alone (kept with the
benchmark, like ``harness/bytes_kmeans.py``, so that no PR which claims
a gain can change what ``kmeans_mxu_roofline`` is a share of)."""

from __future__ import annotations


def lloyd_point_flops_needed(dim: int, k: int) -> int:
    """What one point costs the algorithm an iteration: its product
    with every centre, a multiply and an add a feature (the ``x . c``
    of ``|c|^2 - 2 x . c``). 6 422 528 at 784 dimensions and 4096
    centres. Not counted, because a form of the work and not the work:
    the passes a float32-accurate product takes on a bfloat16 MXU (six),
    the per-cluster sums where they go through a one-hot product
    (``2 * k * dim`` again, three times in exact pieces; a scatter needs
    ``dim`` adds), the argmin's ``k`` compares, the padding of 784 to
    whole 128-deep slabs."""
    return 2 * dim * k


def lloyd_iteration_flops_needed(shapes: dict) -> int:
    """Per chip and iteration: every valid point of the chip against
    every centre."""
    rows = -(-shapes["n_rows"] // shapes["n_shards"])
    return rows * lloyd_point_flops_needed(shapes["dim"], shapes["k"])
