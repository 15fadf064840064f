#!/usr/bin/env python3
"""Cut a recorded trace down to a sample the tests can hold: the first
``n`` device events of each chip and the harness's annotations over
the same stretch, as JSON (``tests/data/trace_sample.json`` was made
so from a chip run of ``lr30_400m_dp4``)."""

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import trace  # noqa: E402


def main(trace_dir: str, out: str, n: int = 400) -> None:
    raw = trace.load_xplane(trace.find_xplane(trace_dir))
    devices = {str(k): v[:n] for k, v in raw["devices"].items()}
    end = max((s + d for evs in devices.values() for _, s, d in evs),
              default=0.0)
    host = [ev for ev in raw["host"] if ev[1] <= end]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"devices": devices, "host": host,
                   "lines": raw["lines"]}, f)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *(int(x) for x in sys.argv[3:]))
