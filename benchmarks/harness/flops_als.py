"""Operations an ALS iteration over a ratings list needs, from shapes
alone (kept with the benchmark, like ``harness/flops_kmeans.py``, so
that no PR which claims a gain can change what ``als_gram_mxu_roofline``
is a share of)."""

from __future__ import annotations


def rating_flops_needed(k: int) -> int:
    """What one rating costs the normal equations of one side: the
    outer product of the other side's factor row with itself, a
    multiply and an add an entry (``theta theta^T`` into ``A_u``):
    20 000 at rank 100. Not counted, because a form of the work and not
    the work: the passes a float32-accurate product takes on a bfloat16
    MXU (six), the 128 lanes that 100 columns are held in, the slots a
    pack pads an owner to, the symmetric half, ``b_u`` (``2 k``), the
    solves (``k^3 / 3`` an owner: VPU work)."""
    return 2 * k * k


def iteration_flops_needed(shapes: dict) -> int:
    """Per chip and iteration: every rating of the chip once in the
    user half and once in the item half. 1.01e13 at 252.8M ratings."""
    ratings = -(-shapes["n_ratings"] // shapes["n_shards"])
    return 2 * ratings * rating_flops_needed(shapes["k"])
