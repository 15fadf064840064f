#!/usr/bin/env python3
"""Run a cell that is proposed but not in ``BENCHMARK.json``: the
entries of ``proposed/<name>.json`` are merged into a copy of the
manifest under ``.bench_out/`` and the cell runs through the same
``run.run_cell``, on the chip.

    python3 benchmarks/tools/run_proposed.py --proposed pagerank_g500_21 \
        --workload pagerank_g500_21 --seed 7 --seconds 20 --trace 0
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench  # noqa: E402
from harness import manifest as mf  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--proposed", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also read the control's numbers")
    a = ap.parse_args(argv)
    manifest = mf.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    extra = mf.load_json(os.path.join(BENCH, "proposed",
                                      a.proposed + ".json"))
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        manifest[group] += extra.get(group, [])
    out = os.path.join(ROOT, ".bench_out", "proposed")
    os.makedirs(out, exist_ok=True)
    path = os.path.join(out, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(manifest, f)
    try:
        rc, result = bench.run_cell(a.workload, a.seed, a.seconds,
                                    bool(a.trace), manifest_path=path,
                                    root=ROOT, control=a.control)
    except Exception:
        traceback.print_exc()
        return 1
    if result is not None:
        print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
