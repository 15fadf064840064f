"""What the data layer's readers share: the program's own finished
spans (``tpu_distalg/telemetry/events.finished()``, the ring every span
lands in, sink or no sink) that lie inside the harness's ``data_build``
span. Both clocks are ``time.perf_counter()`` (``layer_metrics/
trace_s.py`` relies on the same).

A phase's metric is its span's **self time**: its seconds less the
seconds of every span directly beneath it, the ``jit:*`` spans of
``utils/compile_cache`` included, so a loader's phases, its ``jit:*``
spans and :func:`unspanned_seconds` add up to ``data_build_s.*`` and no
second is counted twice. The memory readers take the fields a span
that was handed its devices ends with (``hbm_in_use``, ``hbm_peak``:
bytes, one entry a device; the fullest device is reported). Every
function returns ``None`` where the program keeps no ring, the harness
has no such span, or no span has the field (a commit before it, the
CPU): the result line then leaves the metric out."""

from __future__ import annotations

WINDOW = "data_build"


def inside(ctx, window: str = WINDOW):
    """``(spans, t0, t1)``: the program's finished spans that began and
    ended inside the harness's ``window`` span, in the order they
    ended, and the interval itself."""
    try:
        from tpu_distalg.telemetry import events

        done = events.finished()
    except (ImportError, AttributeError):
        return None
    edges = [(a, b) for name, a, b in ctx.spans if name == window]
    if not edges:
        return None
    t0, t1 = edges[0][0], edges[-1][1]
    return [s for s in done
            if t0 <= s.t0 and s.t0 + s.seconds <= t1], t0, t1


def self_seconds(ctx, name: str):
    """Seconds of the spans called ``name`` inside ``data_build`` less
    the seconds of the spans directly beneath each."""
    got = inside(ctx)
    if got is None:
        return None
    spans = got[0]
    beneath: dict = {}
    for s in spans:
        beneath[s.parent] = beneath.get(s.parent, 0.0) + s.seconds
    mine = [s for s in spans if s.name == name]
    if not mine:
        return None
    return sum(max(s.seconds - beneath.get(s.id, 0.0), 0.0) for s in mine)


def unspanned_seconds(ctx):
    """The ``data_build`` interval less the union of the program's
    spans inside it: what the loader's callers did under no span."""
    got = inside(ctx)
    if got is None or not got[0]:
        return None
    spans, t0, t1 = got
    covered, reach = 0.0, t0
    for a, b in sorted((s.t0, s.t0 + s.seconds) for s in spans):
        if b > reach:
            covered += b - max(a, reach)
            reach = b
    return (t1 - t0) - covered


def field(ctx, name: str, key: str):
    """``key`` of the last span called ``name`` inside ``data_build``."""
    got = inside(ctx)
    for s in reversed(got[0] if got else []):
        if s.name == name:
            return s.fields.get(key)
    return None


def hbm_gb(ctx, key: str, last: bool):
    """``key`` (``hbm_in_use`` / ``hbm_peak``) of the fullest device in
    GB: of the last span inside ``data_build`` that has it, or the
    largest over all of them."""
    got = inside(ctx)
    held = [max(s.fields[key]) for s in (got[0] if got else [])
            if s.fields.get(key)]
    if not held:
        return None
    return (held[-1] if last else max(held)) / 1e9


def wasted_pct(share):
    """Slots held over useful entries (``padding_share``, >= 1) as the
    share of the slots that hold nothing."""
    return None if not share else (1 - 1 / share) * 100
