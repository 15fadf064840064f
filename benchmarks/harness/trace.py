"""Reduction from a profiler trace to numbers.

``load_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into
plain lists; everything else works on those lists, so the arithmetic
is tested on a small recorded trace without a chip
(``tests/test_trace.py``).

Events are ``(name, start_ns, duration_ns)``. Device events come from
the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane; host events are
the harness's own ``bench:*`` annotations, which the profiler writes on
the same clock.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    """``{"devices": {ordinal: [event...]}, "host": [event...],
    "lines": {plane: [line names]}}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[int, list] = {}
    host: list = []
    lines: dict[str, list] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        names = lines.setdefault(plane.name, [])
        for line in plane.lines:
            names.append(line.name)
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                host.extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                    if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host, "lines": lines}


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def window_of(trace: dict) -> tuple[float, float]:
    """The traced window: the harness's ``bench:window`` annotation, or
    where it is missing the extent of the device events."""
    spans = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW_SPAN]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    evs = [ev for d in trace["devices"].values() for ev in d]
    if not evs:
        raise ValueError("trace holds no device event and no window span")
    return (min(s for _, s, _ in evs), max(s + d for _, s, d in evs))


def busy_intervals(events, window):
    return clip(union((s, s + d) for _, s, d in events), *window)


def gaps(busy, window) -> list[tuple[float, float]]:
    """Idle intervals inside ``window`` given merged busy intervals."""
    out, at = [], window[0]
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def self_seconds(events) -> dict[str, float]:
    """Seconds per op name with the time of nested ops taken out of
    their parent (a ``while`` that wraps a scan keeps only its own
    share), so the sum over names is the busy time."""
    out: dict[str, float] = {}
    stack: list[list] = []   # [name, start, end, nested_ns]

    def close(item):
        name, start, end, child = item
        out[name] = out.get(name, 0.0) + max(end - start - child, 0.0)

    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(d, stack[-1][2] - s)
        stack.append([name, s, s + d, 0.0])
    while stack:
        close(stack.pop())
    return {k: v / 1e9 for k, v in out.items()}


def matching_seconds(events, pattern: str) -> tuple[float, int]:
    """Total seconds and count of the events whose name matches."""
    rx = re.compile(pattern)
    hit = [d for n, _, d in events if rx.search(n)]
    return sum(hit) / 1e9, len(hit)


def label(name: str, width: int = 64) -> str:
    """An op's HLO text cut to a name the ledger can hold."""
    return re.sub(r"[^A-Za-z0-9_.\-]+", "_", name).strip("_")[:width]


def host_activity(host, at: float) -> str:
    """The innermost harness annotation that covers instant ``at``."""
    best, best_d = "bench:unattributed", None
    for n, s, d in host:
        if n != WINDOW_SPAN and s <= at <= s + d:
            if best_d is None or d < best_d:
                best, best_d = n, d
    return best


def reduce(trace: dict) -> dict:
    """Everything the per-layer readers and the result line need."""
    window = window_of(trace)
    window_s = (window[1] - window[0]) / 1e9
    per_dev = {}
    for dev, events in sorted(trace["devices"].items()):
        inside = [ev for ev in events
                  if ev[1] + ev[2] > window[0] and ev[1] < window[1]]
        busy = busy_intervals(inside, window)
        per_dev[dev] = {
            "events": inside, "busy": busy,
            "busy_s": sum(e - s for s, e in busy) / 1e9,
            "gaps": gaps(busy, window)}
    n = max(len(per_dev), 1)
    busy_s = sum(d["busy_s"] for d in per_dev.values()) / n
    # breakdown from the first device: chips of one SPMD program agree
    first = per_dev[min(per_dev)] if per_dev else {"events": [], "gaps": []}
    ops = sorted(self_seconds(first["events"]).items(),
                 key=lambda kv: -kv[1])[:10]
    longest = sorted(first["gaps"], key=lambda g: g[0] - g[1])[:10]
    return {
        "window": window, "window_s": window_s, "busy_s": busy_s,
        "per_device": per_dev,
        "device_ops": [[label(k), v] for k, v in ops],
        "idle_gaps": [[host_activity(trace["host"], (s + e) / 2),
                       (e - s) / 1e9] for s, e in longest]}


def kernel_seconds(reduced: dict, pattern: str) -> tuple[float, int]:
    """Mean over devices of the seconds (and count) of matching ops."""
    devs = reduced["per_device"].values()
    if not devs:
        return 0.0, 0
    got = [matching_seconds(d["events"], pattern) for d in devs]
    return (sum(g[0] for g in got) / len(got),
            round(sum(g[1] for g in got) / len(got)))


def inner_gap_ms(reduced: dict) -> float | None:
    """Mean idle gap between consecutive busy stretches, first and
    last edge of the window left out; mean over devices."""
    vals = []
    for d in reduced["per_device"].values():
        w0, w1 = reduced["window"]
        inner = [(s, e) for s, e in d["gaps"] if s > w0 and e < w1]
        if inner:
            vals.append(sum(e - s for s, e in inner) / len(inner) / 1e6)
    return sum(vals) / len(vals) if vals else None
