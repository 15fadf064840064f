"""Shared by the tests: a temporary copy of the benchmark with the
test-only cells of ``data/cells.json`` added as a later PR would add
a cell: new files, new entries, nothing edited."""

from __future__ import annotations

import json
import os
import shutil
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def copy_with_test_cells(tmp: str) -> tuple[str, str]:
    """Returns (manifest path, benchmark directory) of the copy."""
    bench = os.path.join(tmp, "benchmarks")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    with open(os.path.join(TESTS, "data", "cells.json")) as f:
        extra = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    before = {os.path.join(d, n) for d, _, ns in os.walk(bench) for n in ns}

    def add(rel: str, obj) -> None:
        path = os.path.join(bench, rel)
        assert path not in before, f"{rel}: a new cell may edit no file"
        with open(path, "w") as f:
            json.dump(obj, f)

    for name, cfg in extra["configs"].items():
        add(f"configs/{name}.json", cfg)
        manifest["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "test",
            "file": f"benchmarks/configs/{name}.json"})
    for name, t in extra["traffic"].items():
        add(f"traffic/{name}.json", t)
    for name, lim in extra["limits"].items():
        add(f"limits/{name}.json", lim)
    # the graph family's entries, which this PR keeps out of the manifest
    with open(os.path.join(BENCH, "proposed",
                           "pagerank_g500_21.json")) as f:
        proposed = json.load(f)
    for m in proposed["end_to_end"] + proposed["per_layer"]:
        m["workloads"] = []       # the proposed cell itself stays out
    manifest["end_to_end"] += proposed["end_to_end"]
    manifest["per_layer"] += proposed["per_layer"]
    manifest["workloads"] += extra["workloads"]
    suffix = extra["families"]               # cell -> ".lr" / ".graph"
    rate = {"lr": "rows_per_s", "graph": "edges_per_s"}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" not in m or m["name"] == "collective_ms_per_step.lr":
            continue
        for cell, fam in suffix.items():
            if m["name"] == rate[fam] or m.get("moves") == rate[fam] \
                    or m["name"].endswith("." + fam):
                m["workloads"] = m["workloads"] + [cell]
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return path, bench
