"""Seconds of ``data_build`` under no span of the program's
(harness/spans.unspanned_seconds): the data layer's ``scoped_busy_pct``,
read the other way round. With the self times of every program span
inside ``data_build`` it adds up to ``data_build_s.*``."""

from harness import spans


def read(ctx):
    return spans.unspanned_seconds(ctx)
