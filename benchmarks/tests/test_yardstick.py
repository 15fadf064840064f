"""The yardstick's own arithmetic, with no chip and no program: the
trace reduction on a recorded trace, the byte functions against
shapes, the manifest's rules, the graph generator's determinism."""

import json
import os
import re

import numpy as np
import pytest

import helpers
from harness import bytes as nbytes
from harness import manifest as mf
from harness import rmat, trace

DATA = os.path.join(helpers.TESTS, "data")


# ---- trace reduction -------------------------------------------------

def _toy():
    # one device: a while wrapping two kernels, a gap, a second program
    dev = [("while.1", 100.0, 400.0), ("kern.a", 110.0, 100.0),
           ("kern.a", 300.0, 150.0), ("fusion.2", 700.0, 100.0)]
    host = [("bench:window", 0.0, 1000.0), ("bench:dispatch", 480.0, 40.0),
            ("bench:sync", 520.0, 470.0)]
    return {"devices": {0: dev}, "host": host, "lines": {}}


def test_union_gaps_and_busy():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    r = trace.reduce(_toy())
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(500e-9)       # 400 + 100, nested once
    d = r["per_device"][0]
    assert d["gaps"] == [(0.0, 100.0), (500.0, 700.0), (800.0, 1000.0)]
    assert trace.inner_gap_ms(r) == pytest.approx(200e-6)


def test_self_time_and_kernel_sums():
    r = trace.reduce(_toy())
    ops = dict(map(tuple, r["device_ops"]))
    assert ops["kern.a"] == pytest.approx(250e-9)
    assert ops["while.1"] == pytest.approx(150e-9)    # its own share only
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    assert trace.kernel_seconds(r, r"kern\.a") == (pytest.approx(250e-9), 2)
    # the longest gap is attributed to what the host was doing in it
    assert r["idle_gaps"][0] == ["bench:sync", pytest.approx(200e-9)]


def test_reduction_on_the_recorded_chip_trace():
    with open(os.path.join(DATA, "trace_sample.json")) as f:
        raw = json.load(f)
    raw["devices"] = {int(k): [tuple(e) for e in v]
                      for k, v in raw["devices"].items()}
    raw["host"] = [tuple(e) for e in raw["host"]]
    r = trace.reduce(raw)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert len(r["device_ops"]) <= 10 and r["device_ops"][0][1] > 0
    for name, _ in r["device_ops"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-]{1,64}", name)
    pattern = mf.load_module(os.path.join(
        helpers.BENCH, "layer_metrics", "ssgd_kernel_roofline.py"),
        "roof").PATTERN
    seconds, n = trace.kernel_seconds(r, pattern)
    assert n > 0 and 0 < seconds <= r["busy_s"]
    # four chips, and the per-step psum is in the trace
    assert sorted(r["per_device"]) == [0, 1, 2, 3]
    assert trace.kernel_seconds(r, r"all-reduce")[1] > 0
    assert {g[0] for g in r["idle_gaps"]} <= {
        "bench:dispatch", "bench:sync", "bench:unattributed"}


# ---- byte functions ----------------------------------------------------

def test_ssgd_bytes_against_shapes():
    assert nbytes.ssgd_row_bytes_needed(30) == 64
    assert nbytes.ssgd_row_bytes_moved(40) == 80
    sh = {"n_sampled": 1221, "block_rows": 8192, "n_features": 30,
          "d_total": 40}
    assert nbytes.ssgd_step_bytes_needed(sh) == 1221 * 8192 * 64
    assert nbytes.ssgd_step_bytes_moved(sh) == 1221 * 8192 * 80
    # the packed matrix of the 100M-row configuration is what is moved
    cfg = mf.load_json(os.path.join(helpers.BENCH, "configs",
                                    "lr-bc30-100m.json"))
    from reference import ssgd_ref
    g = ssgd_ref.geometry(cfg["n_rows"], 1, cfg["gather_block_rows"],
                          cfg["fused_pack"], 0.1)
    assert g["n_padded"] * nbytes.ssgd_row_bytes_moved(
        cfg["packed_columns"]) == 8_000_634_880
    assert (g["n_blocks"], g["n_sampled"]) == (12208, 1221)


def test_spmv_bytes_against_shapes():
    got = nbytes.spmv_sweep_bytes(n_chunks=32, chunk=1024, r8=16, rg=128,
                                  ws=16)
    assert got == 32 * 1024 * 20 + (16 + 128 + 16 + 16) * 128 * 4


# ---- manifest ----------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _lint(manifest: dict, bench: str, root: str):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    cells = [w["name"] for w in manifest["workloads"]]
    configs = {c["name"]: c for c in manifest["configs"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in manifest[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
        for sub, name in (("traffic", w["traffic"]), ("limits", w["name"])):
            assert os.path.isfile(os.path.join(bench, sub, name + ".json"))
        reported = [m for m in manifest["end_to_end"]
                    if mf.reports(m, w["name"])]
        assert len(reported) >= 2, f"{w['name']}: setup_s and one more"
    assert {w["config"] for w in manifest["workloads"]} == set(configs)
    for c in manifest["configs"]:
        cfg = mf.load_json(os.path.join(root, c["file"]))
        assert os.path.isfile(os.path.join(
            bench, "families", cfg["family"] + ".py"))
        assert cfg["rate_metric"] in e2e
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert os.path.isfile(os.path.join(
            bench, "layer_metrics", m["name"] + ".py")), m["name"]
        # every cell that reports it also reports the metric it moves
        for cell in (m.get("workloads") or cells):
            assert cell in cells
            assert mf.reports(e2e[m["moves"]], cell), (m["name"], cell)
        if "workloads" not in m:
            assert "workloads" not in e2e[m["moves"]], m["name"]
    for w in cells:
        assert any(mf.reports(m, w) for m in manifest["per_layer"])


def test_manifest_lint():
    path = os.path.join(helpers.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    manifest = mf.load_json(path)
    _lint(manifest, helpers.BENCH, helpers.ROOT)
    assert manifest["paths"] == ["benchmarks"]
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    assert 1 <= manifest["run_seconds"] <= 51
    assert {m["name"] for m in manifest["end_to_end"]} >= {
        "setup_s", "rows_per_s"}


def test_a_new_cell_is_files_and_entries(tmp_path):
    """A fifth cell of an existing family, a new family's cell and a
    new end-to-end metric come in as new files and new entries; the
    copy keeps every file that was there unchanged."""
    path, bench = helpers.copy_with_test_cells(str(tmp_path))
    manifest = mf.load_json(path)
    _lint(manifest, bench, str(tmp_path))
    for d, _, names in os.walk(helpers.BENCH):
        if "__pycache__" in d or os.sep + "tests" in d:
            continue
        for n in names:
            src = os.path.join(d, n)
            dst = os.path.join(bench, os.path.relpath(src, helpers.BENCH))
            with open(src, "rb") as a, open(dst, "rb") as b:
                assert a.read() == b.read(), src
    cell = mf.Cell(path, "lr_tiny", bench)
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "rows_per_s"]
    assert "collective_ms_per_step.lr" not in {
        m["name"] for m in cell.per_layer}
    graph = mf.Cell(path, "pagerank_tiny", bench)
    assert {m["name"] for m in graph.end_to_end} == {"setup_s",
                                                     "edges_per_s"}
    assert all(m["moves"] in ("setup_s", "edges_per_s")
               for m in graph.per_layer)


def test_peaks_are_keyed_by_device_kind():
    assert mf.peaks("TPU v5 lite")["hbm_bytes_per_sec"] == 819e9
    with pytest.raises(KeyError):
        mf.peaks("TPU v9")
    with pytest.raises(KeyError):
        mf.peaks("_source")


# ---- graph generator ---------------------------------------------------

def test_rmat_is_a_function_of_the_seed():
    a = rmat.edges(10, 16, (0.57, 0.19, 0.19, 0.05), 2**31 + 5)
    b = rmat.edges(10, 16, (0.57, 0.19, 0.19, 0.05), 2**31 + 5)
    c = rmat.edges(10, 16, (0.57, 0.19, 0.19, 0.05), 7)
    assert a.shape == (16 << 10, 2) and a.dtype == np.int32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 1 << 10
    # skewed, not uniform: the busiest 1% of vertices draw far more than
    # 1% of the destinations
    deg = np.sort(np.bincount(a[:, 1], minlength=1 << 10))[::-1]
    assert deg[:10].sum() > 0.08 * len(a)
