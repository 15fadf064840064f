"""Seconds of jaxpr tracing in set-up: the Python the program runs to
describe its step to JAX. The program's own record: its ``jit:trace``
spans (``tpu_distalg/utils/compile_cache.py`` opens and closes one from
``jax.monitoring``'s events for every function JAX traces, and
``telemetry/events.py`` keeps every finished span in memory), summed
over those that ended by the end of ``warm_up``, the family's last
set-up span: the reference's come after it and stay out. A function
jitted inside another is traced inside it and has no span of its own,
but an eager operation on a constant compiles inside a trace: a span
that lies inside a ``jit:trace`` or a ``jit:lower`` is its ancestor's
time and is left out, so that ``trace_s + lower_s + cache_load_s``
counts no second twice.
``lower_s``, ``cache_load_s`` and ``jit_traces`` load this file."""

NESTING = ("jit:trace", "jit:lower")


def setup_spans(ctx):
    """The program's ``jit:*`` spans that ended inside set-up, with
    whether each lies inside another's trace or lowering: ``[(span,
    nested)]``. ``None`` where the program keeps no such record (a
    commit before the ring, or one that never listened)."""
    try:
        from tpu_distalg.telemetry import events

        done = events.finished()
    except (ImportError, AttributeError):
        return None
    if not ctx.spans:
        return None
    end = ctx.spans[-1][2]
    by_id = {s.id: s for s in done}

    def nested(s):
        up = by_id.get(s.parent)
        while up is not None:
            if up.name in NESTING:
                return True
            up = by_id.get(up.parent)
        return False

    got = [(s, nested(s)) for s in done
           if s.name.startswith("jit:") and s.t0 + s.seconds <= end]
    return got or None


def seconds_of(ctx, name):
    got = setup_spans(ctx)
    if got is None:
        return None
    return sum(s.seconds for s, nested in got
               if s.name == name and not nested)


def read(ctx):
    return seconds_of(ctx, "jit:trace")
