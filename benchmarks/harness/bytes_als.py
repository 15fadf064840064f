"""Bytes an ALS iteration over a ratings list needs to fetch, from
shapes alone (kept with the benchmark, like ``harness/bytes_hashed.py``,
so that no PR which claims a gain can change what ``als_gather_roofline``
is a share of)."""

from __future__ import annotations


def rating_bytes_needed(k: int, factor_bytes: int = 4) -> int:
    """What one rating costs the gather of one side: the other side's
    factor row, ``k`` float32 columns, fetched by index: 400 B at rank
    100. Not counted: the 128 lanes a row is held in (512 B moved), the
    padding slots of the pack, the index and the rating themselves
    (8 B), the write of the gathered rows where a program holds them."""
    return k * factor_bytes


def iteration_bytes_needed(shapes: dict) -> int:
    """Per chip and iteration: a factor row a rating a half. 2.02e11
    at 252.8M ratings and rank 100."""
    need = rating_bytes_needed(shapes["k"])
    if need != shapes["row_bytes_needed"]:
        raise ValueError(f"{need} B a rating from k, the configuration "
                         f"states {shapes['row_bytes_needed']}")
    ratings = -(-shapes["n_ratings"] // shapes["n_shards"])
    return 2 * ratings * need
