#!/usr/bin/env python3
"""Medians and spreads of sets of runs, as the contract reads them: a
spread is the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median; the
bound to set is about five times the widest spread over the cells, and
never under 1%.

    python3 benchmarks/tools/spread.py runs.jsonl [...]

Each line of a file is ``{"set": 1, "seed": 7, "result": <last line of
a run>}`` (``tools/proof.sh`` writes them).
"""

import json
import statistics
import sys


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(paths) -> int:
    for path in paths:
        with open(path) as f:
            rows = [json.loads(x) for x in f if x.strip()]
        sets = sorted({r["set"] for r in rows})
        names = sorted({m for r in rows for m in r["result"]["metrics"]})
        print(f"{path}: {len(rows)} runs, correct "
              f"{sum(r['result']['correct'] for r in rows)}/{len(rows)}")
        for name in names:
            per, meds = [], []
            for s in sets:
                v = [r["result"]["metrics"][name]["value"]
                     for r in rows if r["set"] == s]
                if name == "setup_s":
                    v = v[1:] if s == sets[0] else v   # the compiling run
                meds.append(statistics.median(v))
                per.append(spread(v) if len(v) >= 2 else float("nan"))
                print(f"  {name} set {s}: n {len(v)} median {meds[-1]:.6g} "
                      f"spread {per[-1]:.5%} min {min(v):.6g} "
                      f"max {max(v):.6g}")
            if len(meds) == 2:
                print(f"  {name}: second median / first - 1 = "
                      f"{meds[1] / meds[0] - 1:+.5%}; widest spread "
                      f"{max(per):.5%} -> five times = {5 * max(per):.4%}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
