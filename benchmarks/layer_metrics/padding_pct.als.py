"""The share of the slots sparse ALS holds that hold no rating:
``als:prepare``'s ``padding_share`` (slots held over ratings, >= 1) as
(1 - 1 / share) x 100."""

from harness import spans


def read(ctx):
    return spans.wasted_pct(spans.field(ctx, "als:prepare", "padding_share"))
