"""Rehearsal of the k-means family on the CPU: a tiny cell added to a
temporary copy of the benchmark (new files, new entries, nothing
edited) and run end to end through ``run.run_cell``; the control (the
reference in bfloat16 in the program's place), which has to come out as
not correct; and a run whose iteration hands its centres back, which
has to report ``correct`` false."""

import contextlib
import io
import json
import os
import shutil

import numpy as np
import pytest

import helpers
import run as bench
from harness import manifest as mf

CELLS = mf.load_json(os.path.join(helpers.TESTS, "data",
                                  "cells_kmeans.json"))


def copy_with_kmeans_cells(tmp: str) -> tuple[str, str]:
    """(manifest path, benchmark directory) of a copy that holds the
    cells of ``data/cells_kmeans.json``; each reports what the real
    cell it is ``like`` reports."""
    bench_dir = os.path.join(tmp, "benchmarks")
    shutil.copytree(helpers.BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    manifest = mf.load_json(os.path.join(helpers.ROOT, "BENCHMARK.json"))

    def add(rel: str, obj) -> None:
        path = os.path.join(bench_dir, rel)
        assert not os.path.exists(path), \
            f"{rel}: a new cell may edit no file"
        with open(path, "w") as f:
            json.dump(obj, f)

    for name, cfg in CELLS["configs"].items():
        add(f"configs/{name}.json", cfg)
        manifest["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "test",
            "file": f"benchmarks/configs/{name}.json"})
    for name, t in CELLS["traffic"].items():
        add(f"traffic/{name}.json", t)
    for name, lim in CELLS["limits"].items():
        add(f"limits/{name}.json", lim)
    manifest["workloads"] += CELLS["workloads"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        for cell, like in CELLS["like"].items():
            if like in m.get("workloads", []):
                m["workloads"] = m["workloads"] + [cell]
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return path, bench_dir


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench_kmeans"))
    path, bench_dir = copy_with_kmeans_cells(tmp)
    return {"manifest_path": path, "bench_dir": bench_dir,
            "out_dir": os.path.join(tmp, "out"), "require_tpu": False}


def _run(cell, copy, seed=2**31 + 11, seconds=0.3, **kw):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc, res = bench.run_cell(cell, seed, seconds, False, **copy, **kw)
    return rc, res, out.getvalue()


def _line(log, word):
    return [ln for ln in log.splitlines() if word in ln][0]


def test_family_rehearsal(copy):
    rc, res, log = _run("kmeans_tiny", copy)
    assert rc == 0
    json.dumps(res)
    assert res["device"]["platform"] == "cpu"
    assert set(res["metrics"]) == {"setup_s", "rows_per_s"}
    assert res["metrics"]["rows_per_s"]["value"] > 0
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["correct"] is True, log
    assert "[kmeans] layout lanes blocks (3, 20, 512, 128)" in log
    for name in ("window_compiles", "centers_rel_err.call1",
                 "centers_rel_err.call2", "count_total_err",
                 "inertia_rise"):
        assert f"[check] {name} = " in log, name
    assert "[check] count_total_err = 0 " in log


def test_same_seed_same_inputs(copy):
    a, b, c = (_run("kmeans_tiny", copy, seed=s)[2] for s in (5, 5, 6))
    assert _line(a, "held-out inertia") == _line(b, "held-out inertia") \
        != _line(c, "held-out inertia")
    assert _line(a, "[kmeans] layout") != _line(c, "[kmeans] layout")


def test_every_reader_of_the_cell_loads_and_reports_untraced(copy):
    """The per-layer metrics of the real cell exist as files; without a
    trace the host-clock and counter readers report and the trace
    readers report nothing (they do not raise)."""
    cell = mf.Cell(copy["manifest_path"], "kmeans_tiny",
                   copy["bench_dir"])
    real = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"),
                   "kmeans20_100m_k10")
    assert [m["name"] for m in cell.per_layer] == \
        [m["name"] for m in real.per_layer]
    ctx = bench.Context(cell, 1, copy["out_dir"])
    ctx.spans.append(("data_build", 1.0, 3.5))
    ctx.readings_s[:] = [0.5, 0.25, 0.25]
    ctx.counters.update(work_per_call=1000, compile_s=0.2, cache_misses=0)
    ctx.memory_peak_bytes = int(8.1e9)
    got = {m["name"]: cell.reader(m["name"]).read(ctx)
           for m in cell.per_layer}
    assert got["data_build_s.kmeans"] == 2.5
    assert got["median_call_rows_per_s.kmeans"] == 4000
    assert got["hbm_peak_gb.kmeans"] == 8.1
    traced = {m["name"] for m in cell.per_layer
              if m["source"] == "device_trace"}
    assert len(traced) == 7 and all(got[n] is None for n in traced)


def test_roofline_bytes_are_one_read_of_the_points():
    from harness import bytes_kmeans

    sh = {"n_rows": 100_000_000, "dim": 20, "n_shards": 1}
    assert bytes_kmeans.lloyd_iteration_bytes_needed(sh) == 8_000_000_000
    assert bytes_kmeans.lloyd_iteration_bytes_needed(
        dict(sh, n_shards=4)) == 2_000_000_000


def test_kmeans_control_is_not_correct():
    """bfloat16 rows, centres and distance arithmetic in the
    reference's place land outside the test cell's limit by five times
    it; float32 lands on itself."""
    import jax
    import jax.numpy as jnp

    from reference import kmeans_ref

    c = CELLS["configs"]["kmeans-tiny"]
    limit = CELLS["limits"]["kmeans_tiny"]["centers_rel_err"]
    for seed in (3, 4, 5):
        ref = kmeans_ref.Reference(
            n_rows=c["n_rows"], dim=c["dim"], k=c["k"],
            clusters=c["generating_clusters"], spread=c["spread"],
            data_seed=seed, init_seed=seed + 1,
            device=jax.devices()[0])
        ref.build()
        good, counts = ref.follow(2, 3)
        low, _ = ref.follow(2, 3, dtype=jnp.bfloat16)
        assert int(counts.sum()) == c["n_rows"]
        assert kmeans_ref.centers_err(good[-1], good[-1], c["spread"]) == 0
        assert kmeans_ref.centers_err(
            low[-1], good[-1], c["spread"]) > 5 * limit
        ref.free()


def test_an_iteration_that_returns_its_centres_unchanged(copy, monkeypatch):
    """The program's segment function replaced by one that runs the
    iterations and hands its centres back: ``correct`` comes out
    false, by the centres and by the inertia."""
    from tpu_distalg.models import kmeans

    real = kmeans.make_fit_seg_fn

    def broken(mesh, config, seg, lanes=None):
        fn = real(mesh, config, seg, lanes)

        def unchanged(points, valid, centers, shift, n_run):
            _, shift, n_run, counts = fn(points, valid, centers, shift,
                                         n_run)
            return centers, shift, n_run, counts

        return unchanged

    monkeypatch.setattr(kmeans, "make_fit_seg_fn", broken)
    rc, res, log = _run("kmeans_tiny", copy)
    assert rc == 0 and res["correct"] is False
    assert "FAILED" in _line(log, "centers_rel_err.call1")
    assert "FAILED" in _line(log, "inertia_rise")
    assert "FAILED" not in _line(log, "count_total_err")


def test_a_pass_that_drops_points_fails_the_count(copy, monkeypatch):
    """A segment that is told of fewer valid points than the table
    holds: the counts of its last iteration do not add up."""
    from tpu_distalg.models import kmeans

    real = kmeans.make_fit_seg_fn

    def broken(mesh, config, seg, lanes=None):
        fn = real(mesh, config, seg, lanes)
        return lambda points, valid, *rest: fn(points, valid - 3, *rest)

    monkeypatch.setattr(kmeans, "make_fit_seg_fn", broken)
    rc, res, log = _run("kmeans_tiny", copy)
    assert rc == 0 and res["correct"] is False
    assert "[check] count_total_err = 3 " in log
    assert "FAILED" in _line(log, "count_total_err")


def test_a_layout_the_file_does_not_state_is_refused(copy):
    """The adapter raises where the program would lay the points out
    otherwise than the configuration states."""
    from families import kmeans as fam

    c = dict(CELLS["configs"]["kmeans-tiny"], block_points=32768)
    with pytest.raises(RuntimeError, match="is not the one"):
        fam.program_parts(c, CELLS["traffic"]["lloyd3"])
    sh = fam.shapes(CELLS["configs"]["kmeans-tiny"],
                    CELLS["traffic"]["lloyd3"])
    assert (sh["n_padded"], sh["n_blocks"]) == (196608, 3)
    assert sh["resident_bytes"] == 196608 * 80
    assert np.array_equal(fam.kmeans_ref.init_ids(7, 1000, 10),
                          fam.kmeans_ref.init_ids(7, 1000, 10))
