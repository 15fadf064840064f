"""The share of the slots held whose row the Mosaic gather fetches from
HBM by its loader-made list: ``als:prepare``'s ``gather_cold_share`` x
100."""

from harness import spans


def read(ctx):
    share = spans.field(ctx, "als:prepare", "gather_cold_share")
    return None if share is None else share * 100
