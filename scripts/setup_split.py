#!/usr/bin/env python3
"""Where a benchmark cell's ``setup_s`` goes, from the program's own
record: one run of a cell through ``benchmarks/run.py`` (unchanged: its
``run_cell``, in this process), then the harness's three set-up spans
(``import_program``, ``data_build``, ``warm_up``) and what set-up does
between them, each with the ``jit:*`` spans of ``utils/compile_cache``
that lie in it (re-reads no constant; its standing reader is
``ROADMAP.md`` S3 (2), the set-up that stands under its ``best``):

    chiprun -- python3 scripts/setup_split.py --workload <cell> \
        --seed <n> [--seconds 10] [--trace 1]
    JAX_PLATFORMS=cpu python3 scripts/setup_split.py --rehearse

Prints the result line the benchmark prints, then ``[split]`` lines:
a row a harness span (seconds; beneath it trace / lower / compile, of
the compile seconds the cache's load; spans inside another's trace or
lowering are their ancestor's and not added), the two warm-up calls'
run time (the window's median reading x ``check_calls``) and what is
left of ``warm_up`` with no span; then the functions by name, largest
first, with the program span each was under. The same as JSON in
``chiprun_out/setup_split_<cell>.json``. ``PERF.md`` section 5's set-up
table is this output.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BETWEEN = "between_spans"
COLUMN = {"jit:trace": "trace_s", "jit:lower": "lower_s",
          "jit:compile": "compile_s", "jit:cache_load": "load_s"}


def split(ctx, setup_s: float) -> dict:
    """``{harness span: {seconds, trace_s, lower_s, compile_s, load_s,
    traces}}`` and the functions' rows. The spans and the rule for
    what may be added up are the readers' own
    (``benchmarks/layer_metrics/trace_s.py``)."""
    from harness import manifest as mf
    from tpu_distalg.telemetry import events

    reader = mf.load_module(os.path.join(
        BENCH, "layer_metrics", "trace_s.py"), "bench_reader_trace_s")
    by_id = {s.id: s for s in events.finished()}

    def under(s):
        up = by_id.get(s.parent)
        while up is not None and up.name.startswith("jit:"):
            up = by_id.get(up.parent)
        return f"{up.name}#{up.id}" if up is not None else "-"

    jit = [s for s, nested in reader.setup_spans(ctx) or () if not nested]
    phases, funs = {}, {}
    # what set-up does between the harness's spans (the start state,
    # the mesh, the reference's start) is the last row
    for name, t0, t1 in list(ctx.spans) + [(BETWEEN, None, None)]:
        seconds = (t1 - t0 if t0 is not None else
                   setup_s - sum(r["seconds"] for r in phases.values()))
        row = phases[name] = {"seconds": seconds, "traces": 0,
                              **dict.fromkeys(COLUMN.values(), 0.0)}
        for s in [s for s in jit if t0 is None or (
                t0 <= s.t0 and s.t0 + s.seconds <= t1)]:
            jit.remove(s)
            row[COLUMN[s.name]] += s.seconds
            f = funs.setdefault(s.fields.get("fun", "?"), {
                "traces": 0, **dict.fromkeys(COLUMN.values(), 0.0),
                "hit": [], "in": [], "under": []})
            f[COLUMN[s.name]] += s.seconds
            if s.name == "jit:trace":
                row["traces"] += 1
                f["traces"] += 1
                f["in"].append(name)
                f["under"].append(under(s))
            elif s.name == "jit:compile" and "hit" in s.fields:
                f["hit"].append(s.fields["hit"])
    return {"phases": phases, "functions": funs}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=2**31 + 34)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="a test-only cell on the CPU (benchmarks/tests)")
    a = ap.parse_args(argv)

    import run as bench

    kept = []

    class Context(bench.Context):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            kept.append(self)

    bench.Context = Context
    extra = {}
    if a.rehearse:
        import tempfile

        sys.path.insert(0, os.path.join(BENCH, "tests"))
        import helpers

        tmp = tempfile.mkdtemp(prefix="setup_split_")
        path, bench_dir = helpers.copy_with_test_cells(tmp)
        extra = {"manifest_path": path, "bench_dir": bench_dir,
                 "out_dir": os.path.join(tmp, "out"), "require_tpu": False}
        a.workload, a.seconds, a.trace = a.workload or "lr_tiny", 0.3, 0
    elif not a.workload:
        ap.error("--workload is required")
    rc, result = bench.run_cell(a.workload, a.seed, a.seconds,
                                bool(a.trace), **extra)
    if result is None:
        return rc
    print(json.dumps(result), flush=True)

    ctx = kept[0]
    # a traced run reports no setup_s: from the first span's start then
    got = split(ctx, result["metrics"]["setup_s"]["value"]
                if "setup_s" in result["metrics"]
                else ctx.spans[-1][2] - ctx.spans[0][1])
    calls = ctx.traffic.get("check_calls", 2) * statistics.median(
        ctx.readings_s)
    got["warm_up_calls_s"] = calls
    for name, row in got["phases"].items():
        jit = row["trace_s"] + row["lower_s"] + row["compile_s"]
        line = (f"[split] {name} {row['seconds']:.3f} s: trace "
                f"{row['trace_s']:.3f} ({row['traces']} functions) lower "
                f"{row['lower_s']:.3f} compile {row['compile_s']:.3f} (of "
                f"it load {row['load_s']:.3f})")
        if name == "warm_up":
            line += (f"; the calls running {calls:.3f}; with no span "
                     f"{row['seconds'] - jit - calls:.3f}")
        else:
            line += f"; with no span {row['seconds'] - jit:.3f}"
        print(line)
    rows = sorted(got["functions"].items(), key=lambda kv: -(
        kv[1]["trace_s"] + kv[1]["lower_s"] + kv[1]["compile_s"]))
    for fun, f in rows[:12]:
        print(f"[split]   {fun}: traced {f['traces']} trace "
              f"{f['trace_s']:.3f} lower {f['lower_s']:.3f} compile "
              f"{f['compile_s']:.3f} (load {f['load_s']:.3f}) hit "
              f"{f['hit']} in {f['in']} under {f['under']}")
    rest = rows[12:]
    print(f"[split]   {len(rest)} more functions: "
          f"{sum(f['trace_s'] + f['lower_s'] + f['compile_s'] for _, f in rest):.3f} s")
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"setup_split_{a.workload}.json"),
              "w") as f:
        json.dump({"workload": a.workload, "seed": a.seed,
                   "setup_s": result["metrics"].get("setup_s"), **got,
                   "metrics": result["metrics"]}, f, indent=1)
    return rc


if __name__ == "__main__":
    sys.exit(main())
