"""Exposed collective time a sweep: the time an op under the program's
``tda.pagerank.sync`` scope runs while no op outside that scope runs
on the same chip, mean over chips (``harness/scopes.exposed_seconds``,
the arithmetic of ``sync_exposed_ms_per_step.lr``).
``sync_ms_per_sweep.graph`` beside it is the scope's whole self time;
a sweep that overlaps its all-gather with the next sweep's first
groups would move this one and not that."""

from harness import scopes


def read(ctx):
    return scopes.per_step_ms(
        ctx, lambda events: scopes.exposed_seconds(events,
                                                   "tda.pagerank.sync"))
