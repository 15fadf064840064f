"""The share of the pair slots a step reads that hold no pair: the
program's ``ssgd:prepare`` span's ``padding_share`` (slots held over
pairs, >= 1: a row's tail to its last whole vector, a block's tail past
its last row, the blocks past the table's last row; a step draws its
blocks uniformly, so the table's share is a step's on average) as (1 -
1 / share) x 100. Nothing where the span has no such field."""

from harness import spans


def read(ctx):
    return spans.wasted_pct(spans.field(ctx, "ssgd:prepare",
                                        "padding_share"))
