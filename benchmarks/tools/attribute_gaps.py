#!/usr/bin/env python3
"""Where the device sat idle in a profiled run of the program, by the
phase the host was in: each idle stretch of the first chip is cut at
the edges of the program's ``tda:*`` spans and given to the innermost
span that covers it (``tda:unattributed`` where none does).

    python3 benchmarks/tools/attribute_gaps.py <profile_dir>

``<profile_dir>`` is what ``tda <cmd> --profile DIR`` wrote. The
window is the ``tda:cli:*`` span when the trace has one, else the
extent of the device's events. Prints one JSON object: the window, the
busy time, idle seconds a span (self: what no child span covers) and
the ten longest single stretches.
"""

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import scopes, trace  # noqa: E402

NONE = "tda:unattributed"


def innermost(host, at: float) -> str:
    """The shortest span that covers instant ``at``."""
    covering = [(d, n) for n, s, d in host if s <= at <= s + d]
    return min(covering)[1] if covering else NONE


def attribute(gaps, host) -> tuple[dict, list]:
    """(idle ns per innermost span, [(span, start, end)] pieces)."""
    edges = sorted({t for _, s, d in host for t in (s, s + d)})
    by: dict[str, float] = {}
    pieces = []
    for lo, hi in gaps:
        cuts = [lo] + [t for t in edges if lo < t < hi] + [hi]
        for a, b in zip(cuts, cuts[1:]):
            name = innermost(host, (a + b) / 2)
            by[name] = by.get(name, 0.0) + (b - a)
            pieces.append((name, a, b))
    return by, pieces


def main(profile_dir: str) -> dict:
    path = trace.find_xplane(profile_dir)
    devices = trace.load_xplane(path)["devices"]
    host = scopes.load_host(path)
    events = devices[min(devices)] if devices else []
    roots = [(s, s + d) for n, s, d in host if n.startswith("tda:cli:")]
    if roots:
        window = (min(s for s, _ in roots), max(e for _, e in roots))
    else:
        window = (min(s for _, s, _ in events),
                  max(s + d for _, s, d in events))
    busy = trace.busy_intervals(events, window)
    by, pieces = attribute(trace.gaps(busy, window), host)
    longest = sorted(pieces, key=lambda p: p[1] - p[2])[:10]
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(e - s for s, e in busy) / 1e9,
        "spans_s": {n: sum(d for m, _, d in host if m == n) / 1e9
                    for n in sorted({n for n, _, _ in host})},
        "idle_s_by_span": {n: v / 1e9 for n, v in
                           sorted(by.items(), key=lambda kv: -kv[1])},
        "longest_idle": [[n, (b - a) / 1e9] for n, a, b in longest]}


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1]), indent=1))
