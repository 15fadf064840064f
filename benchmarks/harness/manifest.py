"""Everything a cell needs, found by name from ``BENCHMARK.json``.

A cell's entry names a configuration and a traffic mix; the
configuration's ``file`` is in the manifest, the rest sits at fixed
places under the benchmark's directory:

    traffic/<traffic>.json         parameters of the load
    limits/<cell>.json             the limits ``correct`` is held to
    families/<family>.py           adapter that builds and calls the program
    layer_metrics/<metric>.py      one reader per per-layer metric

so a later PR adds a cell with files and one entry, and edits nothing.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _of(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"{what} {name!r} is not in the manifest "
                   f"(known: {[e['name'] for e in entries]})")


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    """One entry of ``workloads`` with its files resolved."""

    def __init__(self, manifest_path: str, workload: str,
                 bench_dir: str = BENCH_DIR, root: str | None = None):
        self.manifest = load_json(manifest_path)
        root = root or os.path.dirname(os.path.abspath(manifest_path))
        self.bench_dir = bench_dir
        self.entry = _of(self.manifest["workloads"], workload, "workload")
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg = _of(self.manifest["configs"], self.entry["config"], "config")
        self.config = load_json(os.path.join(root, cfg["file"]))
        self.traffic = load_json(os.path.join(
            bench_dir, "traffic", self.entry["traffic"] + ".json"))
        self.limits = load_json(os.path.join(
            bench_dir, "limits", workload + ".json"))
        self.end_to_end = [m for m in self.manifest["end_to_end"]
                           if reports(m, workload)]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in self.manifest["per_layer"]
                          if reports(m, workload) and m["moves"] in e2e]

    def family(self):
        fam = self.config["family"]
        return load_module(os.path.join(
            self.bench_dir, "families", fam + ".py"), f"bench_family_{fam}")

    def reader(self, metric: str):
        return load_module(os.path.join(
            self.bench_dir, "layer_metrics", metric + ".py"),
            "bench_reader_" + metric.replace(".", "_").replace("-", "_"))


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    table = load_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"peaks.json: add its row with the source; a share of "
            f"another chip's peak is not a measurement")
    return table[device_kind]
