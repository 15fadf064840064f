"""Share of the HBM roofline that a pair-set closure round reaches: the
bytes the round's algorithm needs moved (the set read and written once,
the new pairs and the candidates twice, the arcs' rows gathered:
``harness/bytes_closure.py``, from the window's own mean counts) over the
device time a round under the program's three scopes
(``tda.closure.join``, ``.distinct``, ``.count``), over the chip's peak
bandwidth. By scope and not by an operation's name, and the same
whatever keeps the set distinct. What caps it today: XLA's sort passes
over all 2.85e8 slots some hundreds of times and each gather moves one
word an access, so the share is a fraction of a per cent and is the room
there is. It cannot read over 100. Nothing where the trace names no such
scope or the program hands no counts."""

from harness import bytes_closure, scopes


def read(ctx):
    parts = [scopes.scope_ms_per_step(ctx, "tda.closure." + s)
             for s in ("join", "distinct", "count")]
    counts = [ctx.counters.get(k) for k in (
        "set_pairs_per_round", "new_pairs_per_round",
        "candidates_per_round")]
    if not all(parts[:2]) or None in counts or not ctx.peaks:
        return None
    ms = sum(p or 0.0 for p in parts)
    need = bytes_closure.round_bytes_needed(ctx.shapes, *counts)
    return need / (ms / 1e3) / ctx.peaks["hbm_bytes_per_sec"] * 100
