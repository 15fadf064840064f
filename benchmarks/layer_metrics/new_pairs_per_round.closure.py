"""Pairs a round of the window found new, mean over its calls: the
program's own count (the round's ``stats``, what ``tda closure`` adds to
its ``closure.sparse.new_pairs`` counter), read by the family after the
window. Nothing where the program hands no such count."""


def read(ctx):
    return ctx.counters.get("new_pairs_per_round")
