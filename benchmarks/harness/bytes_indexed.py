"""Bytes the gather of an indexed table's fields past VMEM needs, from
shapes alone (kept with the benchmark, like ``harness/bytes_hashed.py``,
so that no PR which claims a gain can change what
``hashed_hbm_gather_roofline`` is a share of)."""

from __future__ import annotations

PAIR_BYTES_GATHERED = 8    # a pair's int32 index and the float32 weight
#                            it names, each once; the 512 B row of the
#                            table that a copy moves to fetch the weight
#                            is what an implementation costs, not what
#                            the algorithm needs


def hbm_gather_bytes_needed(shapes: dict) -> int:
    """Per chip and step: every sampled row's pair for each of the
    fields whose weights stay in HBM (``shapes["hbm_fields"]``: how many
    the program's plan leaves there, as the family read it)."""
    rows = shapes["n_sampled"] * shapes["block_rows"]
    return rows * shapes["hbm_fields"] * PAIR_BYTES_GATHERED
