#!/usr/bin/env python3
"""Read, on the chip, the two numbers every limit is set from: the
largest each compared number gets over sound runs of the program on
many seeds, and the smallest the control gives (the reference in the
nearest lower precision in the program's place).

    python3 benchmarks/tools/check_limits.py --workload <cell> \
        --seeds 11,12,... [--control 3] [--seconds 2]

One process: per seed it makes the cell's set-up (data, compiled
program, the first calls), a short window at the cell's own load,
frees the program's state and has the reference follow. The first
``--control`` seeds also run the control. Limits in force are printed
beside each number but decide nothing here.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench  # noqa: E402
from harness import manifest as mf  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    a = ap.parse_args(argv)
    import jax

    from tpu_distalg.utils import compile_cache

    compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cell = mf.Cell(os.path.join(ROOT, "BENCHMARK.json"), a.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print("check_limits: no chip", file=sys.stderr)
        return 2
    family = cell.family()
    sound: dict[str, list] = {}
    control: dict[str, list] = {}
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        ctx = bench.Context(cell, seed, os.path.join(ROOT, ".bench_out"))
        ctx.limits = dict(cell.limits, _control=i < a.control)
        ctx.devices = list(devs[:cell.chips])
        state = family.setup(ctx)
        bench.timed_window(ctx, state, a.seconds, None)
        family.check(ctx, state.finish())
        for c in ctx.compared:
            sound.setdefault(c["name"], []).append(c["value"])
        for name, v in ctx.controls:
            control.setdefault(name, []).append(v)
    print(json.dumps({"workload": a.workload, "sound": sound,
                      "sound_max": {k: max(v) for k, v in sound.items()},
                      "control": control,
                      "control_min": {k: min(v)
                                      for k, v in control.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
