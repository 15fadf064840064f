"""The share of the fused sweep's slots that hold no edge:
``pagerank:prepare``'s ``padding_share`` (slots over distinct edges, >=
1) as (1 - 1 / share) x 100."""

from harness import spans


def read(ctx):
    return spans.wasted_pct(
        spans.field(ctx, "pagerank:prepare", "padding_share"))
