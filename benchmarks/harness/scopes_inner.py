"""A scope set *inside* another of the program's scopes.

``harness/scopes.py`` keys a device event by the FIRST ``tda.``
component of its ``op_name`` path, so that the outer scopes partition
the busy time. A scope that the program sets inside one of those
(``tda.ssgd.table_hbm`` inside ``tda.ssgd.gather`` and
``tda.ssgd.scatter``) never comes first; this file keys the same
events by whether the path holds the inner scope anywhere, and leaves
the outer readers as they are. Where the trace names no scope at all,
or a program from before the inner scope ran, there is nothing to read:
``None``, and the result line leaves the metric out.
"""

from __future__ import annotations

import os

from harness import scopes, trace


def _names(ctx) -> dict:
    if not hasattr(ctx, "_hlo_op_names"):
        path = trace.find_xplane(os.path.join(
            ctx.out_dir, "trace", ctx.cell.name))
        with open(path, "rb") as f:
            ctx._hlo_op_names = scopes.hlo_op_names(f.read())
    return ctx._hlo_op_names


def inner_scope_ms_per_step(ctx, scope: str) -> float | None:
    """Device self time a step of the ops whose ``op_name`` path holds
    ``scope`` at any depth, mean over chips (a nested op's time taken
    out of the op that holds it, as ``scopes.scope_ms_per_step``);
    ``None`` in an untraced run or where no op of the trace is under
    ``scope``."""
    if not ctx.reduced:
        return None
    names = _names(ctx)
    part = scope + "/"
    if not any(part in v + "/" for v in names.values()):
        return None
    per_chip = []
    for d in ctx.reduced["per_device"].values():
        events = [
            (part in names.get(scopes.instruction_of(n), "") + "/", s, dur)
            for n, s, dur in d["events"]]
        per_chip.append(trace.self_seconds(events).get(True, 0.0))
    steps = ctx.counters["window_calls"] * ctx.counters["steps_per_call"]
    return sum(per_chip) / len(per_chip) / steps * 1e3
