"""Bytes a kernel's work needs, from shapes alone.

Kept with the benchmark so that no PR which claims a gain can change
what a roofline share is a share of.
"""

from __future__ import annotations


def ssgd_row_bytes_needed(n_features: int, itemsize: int = 2) -> int:
    """What one sampled row costs the algorithm: its features, the
    bias column and the label, read once. 64 B at 30 features."""
    return (n_features + 2) * itemsize


def ssgd_row_bytes_moved(d_total: int, itemsize: int = 2) -> int:
    """What the packed layout moves for it: every packed column, the
    valid flag and the lane padding included. 80 B at 40 columns."""
    return d_total * itemsize


def ssgd_step_bytes_needed(shapes: dict) -> int:
    """Per chip and step: the sampled rows of one shard."""
    rows = shapes["n_sampled"] * shapes["block_rows"]
    return rows * ssgd_row_bytes_needed(shapes["n_features"])


def ssgd_step_bytes_moved(shapes: dict) -> int:
    rows = shapes["n_sampled"] * shapes["block_rows"]
    return rows * ssgd_row_bytes_moved(shapes["d_total"])


def spmv_sweep_bytes(n_chunks: int, chunk: int, r8: int, rg: int,
                     ws: int, lanes: int = 128) -> int:
    """One sweep of the fused SpMV: five 4-byte plan arrays an edge
    slot (source lane and row, destination row and lane, weight), the
    rank table in and the accumulator table out."""
    slots = n_chunks * chunk
    tables = (r8 + rg + r8 + ws) * lanes * 4
    return slots * 5 * 4 + tables
