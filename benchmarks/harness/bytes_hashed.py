"""Bytes a step over hashed rows needs, from shapes alone (kept with the
benchmark, like ``harness/bytes.py``, so that no PR which claims a gain
can change what ``hashed_pass_roofline`` is a share of)."""

from __future__ import annotations


def hashed_row_bytes_needed(nnz: int, index_bytes: int = 4) -> int:
    """What one sampled row costs the algorithm: its slots as int32 and
    its label in a byte, read once. 157 B at 39 fields. The weights and
    the per-slot sums are 2 ** hash_bits floats a chip that fit its fast
    memory: nothing beside a step's rows."""
    return nnz * index_bytes + 1


def hashed_step_bytes_needed(shapes: dict) -> int:
    """Per chip and step: the sampled rows of one shard, once. A
    program that reads them twice (gather, then scatter) reads 50% at
    best; one bound by its 2 x nnz dependent addresses a row reads far
    less, which is what the share is there to say."""
    rows = shapes["n_sampled"] * shapes["block_rows"]
    need = hashed_row_bytes_needed(shapes["nnz"])
    if need != shapes["row_bytes_needed"]:
        raise ValueError(f"{need} B a row from nnz, the configuration "
                         f"states {shapes['row_bytes_needed']}")
    return rows * need
