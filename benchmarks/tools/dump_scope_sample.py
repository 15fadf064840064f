#!/usr/bin/env python3
"""Cut a recorded trace of a program with ``tda.`` scopes down to a
sample the tests can hold: the first ``n`` device events of each chip
(an event's name, the HLO text of its instruction, cut to 160
characters), the ``op_name`` of every instruction they name (from the
HLO modules in the trace's metadata plane) and the host annotations
over the same stretch, as JSON (``tests/data/scope_sample.json`` was
made so from a chip run of ``lr30_400m_dp4``)."""

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import scopes, trace  # noqa: E402


def main(trace_dir: str, out: str, n: int = 400) -> None:
    path = trace.find_xplane(trace_dir)
    raw = trace.load_xplane(path)
    with open(path, "rb") as f:
        names = scopes.hlo_op_names(f.read())
    devices = {}
    for k, v in raw["devices"].items():
        # where the n-th event ends; a ``while`` that runs on past it
        # is cut there, so that it holds only the ops the sample keeps
        cut = v[n - 1][1] + v[n - 1][2] if len(v) >= n else float("inf")
        devices[str(k)] = [(name[:160], s, min(d, cut - s))
                           for name, s, d in v[:n]]
    end = max((s + d for evs in devices.values() for _, s, d in evs),
              default=0.0)
    used = {scopes.instruction_of(name)
            for evs in devices.values() for name, _, _ in evs}
    host = [ev for ev in raw["host"] + scopes.load_host(path)
            if ev[1] <= end]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"devices": devices, "host": host,
                   "op_names": {k: names[k] for k in sorted(used)
                                if k in names}}, f)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *(int(x) for x in sys.argv[3:]))
