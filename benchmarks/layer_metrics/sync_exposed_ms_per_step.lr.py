"""Exposed collective time a step: the time an op under the program's
``tda.ssgd.sync`` scope runs while no op outside that scope runs on
the same chip, mean over chips. ``collective_ms_per_step.lr`` beside
it is the all-reduce ops' total duration, hidden or not; a comm
schedule that overlaps its exchange moves this one and not that."""

from harness import scopes


def read(ctx):
    return scopes.per_step_ms(
        ctx, lambda events: scopes.exposed_seconds(events, "tda.ssgd.sync"))
