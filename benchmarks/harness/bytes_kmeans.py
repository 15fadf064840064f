"""Bytes a Lloyd iteration needs, from shapes alone (kept with the
benchmark, like ``harness/bytes.py``, so that no PR which claims a gain
can change what ``kmeans_pass_roofline`` is a share of)."""

from __future__ import annotations


def lloyd_point_bytes_needed(dim: int, itemsize: int = 4) -> int:
    """What one point costs the algorithm an iteration: its features,
    read once. 80 B at 20 float32 dimensions. The centres, the sums
    and the counts are k * (dim + 1) numbers a chip: nothing."""
    return dim * itemsize


def lloyd_iteration_bytes_needed(shapes: dict) -> int:
    """Per chip and iteration: one read of the chip's valid points. A
    program that reads them twice (assign, then sum) reads 50% at best."""
    rows = -(-shapes["n_rows"] // shapes["n_shards"])
    return rows * lloyd_point_bytes_needed(shapes["dim"])
