"""The program's own names in a profiler trace: which ``tda.`` scope
each device op ran under, and the host spans named ``tda:*``.

The program sets ``jax.named_scope("tda.<family>.<part>")`` round the
parts of a step (``tpu_distalg/telemetry/names.py``); XLA keeps the
scope path in every instruction's ``op_name`` metadata. A TPU trace's
``XLA Ops`` events do not carry it: an event's name is the HLO text
of its instruction and its only stats are device offsets (looked at by
hand with ``tools/dump_event_stats.py``, PERF.md). The profiler stores
the HLO module of every program it saw in the ``/host:metadata`` plane
of the same ``.xplane.pb``; ``jax.profiler.ProfileData`` does not open
that plane's metadata, so this file reads the protobuf wire format
itself (field numbers of ``xplane.proto`` and ``hlo.proto``, below) and
joins instruction name -> ``op_name`` -> first ``tda.`` component.

Events are ``harness/trace.py``'s ``(name, start_ns, duration_ns)``, the
ones its reduction kept for the window; a
scoped event is ``(scope, start_ns, duration_ns)`` with ``""`` for an
op under no scope. A trace whose programs name no ``tda.`` scope at all
(a program from before the scopes) loads as ``None``: a reader then
reports nothing, never a guess from op names.
"""

from __future__ import annotations

import os
import re

from harness import trace

SCOPE = re.compile(r"(?:^|/)(tda\.[A-Za-z0-9_.]+)")
HOST_PREFIX = "tda:"
INSTRUCTION = re.compile(r"^%?([A-Za-z0-9_.\-]+) = ")

# xplane.proto
_XSPACE_PLANES = 1
_XPLANE_NAME, _XPLANE_EVENT_METADATA = 2, 4
_MAP_VALUE = 2
_XEVENTMETADATA_STATS = 5
_XSTAT_BYTES = 6
# hlo.proto
_HLOPROTO_MODULE = 1
_MODULE_COMPUTATIONS = 3
_COMPUTATION_INSTRUCTIONS = 2
_INSTRUCTION_NAME, _INSTRUCTION_METADATA = 1, 7
_OPMETADATA_OP_NAME = 2


def fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint or a fixed-width field, a ``memoryview`` for a
    length-delimited one."""
    buf = memoryview(buf)
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        kind = key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            value = int.from_bytes(buf[at:at + width], "little")
            at += width
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {at}")
        yield key >> 3, value


def _varint(buf, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[at]
        at += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, at
        shift += 7


def _sub(buf, number: int):
    return [v for n, v in fields(buf) if n == number]


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def scope_of(op_name: str) -> str:
    """The first ``tda.`` component of an ``op_name`` path, or ``""``."""
    m = SCOPE.search(op_name)
    return m.group(1) if m else ""


def instruction_of(event_name: str) -> str:
    """The instruction an ``XLA Ops`` event ran: its name is the HLO
    text ``%sort.3 = (u32[...]) sort(...)``."""
    m = INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


def op_names(hlo_proto) -> dict[str, str]:
    """instruction name -> ``op_name`` of one serialized ``HloProto``."""
    out = {}
    for module in _sub(hlo_proto, _HLOPROTO_MODULE):
        for comp in _sub(module, _MODULE_COMPUTATIONS):
            for ins in _sub(comp, _COMPUTATION_INSTRUCTIONS):
                name, op_name = "", ""
                for n, v in fields(ins):
                    if n == _INSTRUCTION_NAME:
                        name = _text(v)
                    elif n == _INSTRUCTION_METADATA:
                        op_name = "".join(
                            _text(x) for x in
                            _sub(v, _OPMETADATA_OP_NAME))
                if name:
                    out[name] = op_name
    return out


def hlo_op_names(xplane_bytes) -> dict[str, str]:
    """instruction name -> ``op_name`` over every HLO module stored in
    the trace's ``/host:metadata`` plane. Programs of one trace that
    name an instruction alike agree on its scope or lose it (``""``):
    a scope is never lent from one program to another's op."""
    merged: dict[str, str] = {}
    clash = set()
    for plane in _sub(xplane_bytes, _XSPACE_PLANES):
        if _text(b"".join(_sub(plane, _XPLANE_NAME))) != "/host:metadata":
            continue
        for entry in _sub(plane, _XPLANE_EVENT_METADATA):
            for meta in _sub(entry, _MAP_VALUE):
                for stat in _sub(meta, _XEVENTMETADATA_STATS):
                    for blob in _sub(stat, _XSTAT_BYTES):
                        for name, op in op_names(blob).items():
                            if name in merged and \
                                    scope_of(merged[name]) != scope_of(op):
                                clash.add(name)
                            merged.setdefault(name, op)
    for name in clash:
        merged[name] = ""
    return merged


def attach(devices: dict, names: dict[str, str]) -> dict | None:
    """``{ordinal: [scoped event...]}`` from ``trace.load_xplane``'s
    device events and the instruction -> ``op_name`` map; ``None``
    where no program names a scope."""
    if not any(scope_of(v) for v in names.values()):
        return None
    return {dev: [(scope_of(names.get(instruction_of(n), "")), s, d)
                  for n, s, d in events]
            for dev, events in devices.items()}


def load_host(path: str) -> list:
    """The program's spans (``tda:*``) on the profiler's clock (for the
    tools; no reader of a cell needs them yet)."""
    from jax.profiler import ProfileData

    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(HOST_PREFIX)]


def of(ctx) -> dict | None:
    """``{ordinal: [scoped event...]}`` of the traced window: the
    events ``trace.reduce`` kept, joined to the HLO modules of the same
    ``.xplane.pb``; read once a run and kept on ``ctx``. ``None`` in
    an untraced run or without scopes."""
    if not ctx.reduced:
        return None
    if not hasattr(ctx, "_scoped"):
        path = trace.find_xplane(os.path.join(
            ctx.out_dir, "trace", ctx.cell.name))
        with open(path, "rb") as f:
            names = hlo_op_names(f.read())
        ctx._scoped = attach(
            {dev: d["events"]
             for dev, d in ctx.reduced["per_device"].items()}, names)
    return ctx._scoped


def leaves(events) -> list:
    """The events that hold no other event: what really ran. A
    ``while`` that spans its body is a container, not an op that
    overlaps its own children; an op that merely overlaps the next one
    (it ends later than that one starts, and first) is a leaf."""
    out = []
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    for k, (name, s, d) in enumerate(evs):
        nxt = evs[k + 1] if k + 1 < len(evs) else None
        if nxt is None or nxt[1] + nxt[2] > s + d:
            out.append((name, s, d))
    return out


def exposed_seconds(events, scope: str) -> float:
    """Seconds during which an op of ``scope`` runs and no op outside
    it runs on the same chip. One pass over the two sorted interval
    lists: a step a scan brings one interval to each, and a window
    has thousands of steps."""
    ops = leaves(events)
    mine = trace.union((s, s + d) for n, s, d in ops if n == scope)
    rest = trace.union((s, s + d) for n, s, d in ops if n != scope)
    total = sum(hi - lo for lo, hi in mine)
    k = 0
    for lo, hi in mine:
        while k < len(rest) and rest[k][1] <= lo:
            k += 1
        j = k
        while j < len(rest) and rest[j][0] < hi:
            total -= min(rest[j][1], hi) - max(rest[j][0], lo)
            j += 1
    return total / 1e9


def per_chip(ctx, of_events) -> list | None:
    """``of_events(scoped events of the window)`` for each chip;
    ``None`` without scopes."""
    scoped = of(ctx)
    if not scoped:
        return None
    return [of_events(events) for events in scoped.values()]


def per_step_ms(ctx, seconds_of_events) -> float | None:
    """Mean over chips of ``seconds_of_events``, over the window's
    steps, in ms; ``None`` without scopes."""
    got = per_chip(ctx, seconds_of_events)
    if got is None:
        return None
    steps = ctx.counters["window_calls"] * ctx.counters["steps_per_call"]
    return sum(got) / len(got) / steps * 1e3


def scope_ms_per_step(ctx, scope: str) -> float | None:
    """Device self time in ``scope`` a step: a nested op's time is
    taken out of the op that holds it (``trace.self_seconds`` keys by
    an event's first element, here its scope), so the scopes and the
    unscoped rest sum to the busy time. Where the trace names scopes
    and this one never ran, that is a reading: 0."""
    return per_step_ms(
        ctx, lambda events: trace.self_seconds(events).get(scope, 0.0))
