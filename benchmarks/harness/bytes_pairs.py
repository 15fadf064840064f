"""Bytes a step over rows of (feature, value) pairs needs, from shapes
alone (kept with the benchmark, like ``harness/bytes_hashed.py``, so
that no PR which claims a gain can change what ``pairs_pass_roofline``
is a share of)."""

from __future__ import annotations

PAIR_BYTES_GATHER = 12   # the gather pass: a pair's int32 id and float32
#                          value read once, and the float32 weight the id
#                          names
PAIR_BYTES_SCATTER = 16  # the scatter pass: the id and the value again,
#                          and the per-slot sum's float32 read and written
#                          (a read-modify-write of the slot)


def pairs_step_bytes_needed(shapes: dict) -> float:
    """Per chip and step: the pairs that a step's sampled blocks hold on
    average (``pairs_per_step_mean``: the pairs of the blocks that the
    window's steps drew over those steps, as the family's ``check``
    counted them from the configuration's rule; before it has, the
    table's pairs times the share of the blocks a step draws), in both
    passes, whatever implements them. The 512 B row of the table that a copy moves to fetch a
    weight, the slots of a block that hold no pair, the rows' labels
    and sums (a four-thousandth of the pairs) and the 66 MB vector of
    sums that a step zeroes and adds are what an implementation costs,
    not what the two passes need."""
    return shapes["pairs_per_step_mean"] * (
        PAIR_BYTES_GATHER + PAIR_BYTES_SCATTER)
