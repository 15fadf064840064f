#!/usr/bin/env python3
"""Print every stat of the first device event whose name matches each
pattern, and what planes and lines the trace holds: the look by hand
that comes before a reader is written against a trace (it showed that
a TPU event carries no ``op_name``, so ``harness/scopes.py`` goes
through the trace's HLO modules).

    python3 benchmarks/tools/dump_event_stats.py <trace_dir> <regex>...
"""

import os
import re
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import trace  # noqa: E402


def main(trace_dir: str, *patterns: str) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(trace.find_xplane(trace_dir))
    for plane in data.planes:
        print(f"plane {plane.name!r} stats "
              f"{[k for k, _ in plane.stats]} lines "
              f"{[(ln.name, len(list(ln.events))) for ln in plane.lines]}")
    for plane in data.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            left = [re.compile(p) for p in patterns]
            for e in line.events:
                for rx in [r for r in left if r.search(e.name)]:
                    left.remove(rx)
                    print(f"\n{plane.name} | {line.name} | /{rx.pattern}/ "
                          f"{e.name[:200]!r} {e.duration_ns} ns")
                    for k, v in e.stats:
                        print(f"    {k} = {str(v)[:400]!r}")
        break       # chips of one SPMD program agree


if __name__ == "__main__":
    main(sys.argv[1], *sys.argv[2:])
