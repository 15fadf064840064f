"""Valid (feature, value) pairs of one call over the median call time:
``median_call_rows_per_s.lr`` in the unit the two passes work in, since
rows differ in length a hundredfold (8 to 65 536 pairs). The pairs are
counted, not expected: the family's ``check`` adds up, after the window,
the pairs of the blocks that the window's steps drew (the
configuration's packing rule, the benchmark's own draws) and divides by
the window's calls (``pairs_per_call``); nothing where the family
counted none."""

import statistics


def read(ctx):
    pairs = ctx.counters.get("pairs_per_call")
    if not pairs or not ctx.readings_s:
        return None
    return pairs / statistics.median(ctx.readings_s)
