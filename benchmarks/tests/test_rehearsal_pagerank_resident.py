"""Rehearsal of the resident PageRank family on the CPU, as
``test_rehearsal_als.py`` rehearses sparse ALS: a tiny cell (Graph500's
generator at SCALE 12) added to a temporary copy of the benchmark (new
files, new entries, nothing edited) and run end to end through
``run.run_cell``; the control (the reference with bfloat16 ranks and
contributions) and a ranks vector left at the uniform start, which both
have to come out as not correct; a plan the program refuses, a program
without the device loader and a plan of another geometry, which fail the
run; the real cell's lists, work function, readers and geometry."""

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
                                  "cells_pagerank_resident.json"))
REAL = "pagerank_g500_24_resident"
TINY = "pagerank_tiny"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench_pagerank"))
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
    for kind in ("traffic", "limits"):
        for name, obj in CELLS[kind].items():
            add(f"{kind}/{name}.json", obj)
    manifest["workloads"] += CELLS["workloads"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        for cell, like in CELLS["like"].items():
            if like in m.get("workloads", []):
                m["workloads"] = m["workloads"] + [cell]
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return {"manifest_path": path, "bench_dir": bench_dir,
            "out_dir": os.path.join(tmp, "out"), "require_tpu": False}


def _run(copy, seed=2**31 + 11, seconds=0.2, **kw):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc, res = bench.run_cell(TINY, seed, seconds, False, **copy, **kw)
    return rc, res, out.getvalue()


def _line(log, word):
    return [ln for ln in log.splitlines() if word in ln][0]


CHECKS = ("window_compiles", "rank_l1_err.first", "rank_max_err.first",
          "rank_l1_err.last", "rank_max_err.last", "rank_sum_err")


def test_family_rehearsal_and_its_control(copy):
    rc, res, log = _run(copy, control=True)
    assert rc == 0
    json.dumps(res)
    assert set(res["metrics"]) == {"setup_s", "rows_per_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["correct"] is True, log
    assert "[pagerank] path spmv ranks resident vertices 4096 " \
        "generated 65536 distinct 53" in log
    assert "rejections 0" in log
    for name in CHECKS:
        assert f"[check] {name} = " in log, name
    distinct = int(_line(log, "[pagerank] path").split("distinct ")[1]
                   .split()[0])
    assert f"{10 * distinct} rows a call" in log
    # the control stands outside every limit it has a reading of
    limits = CELLS["limits"][TINY]
    controls = [ln for ln in log.splitlines()
                if ln.startswith("[control] ")]
    assert len(controls) == 3
    for ln in controls:
        name, value = ln.split()[1], float(ln.split("= ")[1])
        assert value > limits[name], ln


def test_same_seed_same_inputs(copy):
    a, b, c = (_run(copy, seed=s)[2] for s in (5, 5, 6))
    for word in ("distinct", "rank_l1_err.last"):
        assert _line(a, word) == _line(b, word) != _line(c, word)


def test_ranks_left_at_the_uniform_start(copy, monkeypatch):
    import jax.numpy as jnp

    from tpu_distalg.models import pagerank

    def broken(mesh, config, n_vertices, plan=None, spmv=None):
        def unchanged(*args):
            ranks = jnp.full((n_vertices,), 1.0 / n_vertices)
            return ranks, jnp.ones((n_vertices,))

        return unchanged

    monkeypatch.setattr(pagerank, "make_run_fn", broken)
    rc, res, log = _run(copy)
    assert rc == 0 and res["correct"] is False
    assert "FAILED" in _line(log, "[check] rank_l1_err.first")
    assert "FAILED" in _line(log, "[check] rank_max_err.last")
    assert "ok" in _line(log, "[check] rank_sum_err")


def test_duplicates_counted_twice(copy, monkeypatch):
    """A repeated edge weighs once: a loader that keeps the repeats
    (and counts them in the degrees) is a different result."""
    from tpu_distalg.models import pagerank

    real = pagerank.rmat_programs

    def keeps(mesh, scale, abcd, geom, n_in):
        generate, dedup = real(mesh, scale, abcd, geom, n_in)

        def no_dedup(src, dst):
            import jax
            import jax.numpy as jnp

            V = 1 << scale
            keep = src < V
            deg = jax.ops.segment_sum(keep.astype(jnp.int32), src,
                                      num_segments=V + 1)[:V]
            inv = jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1), 0.0)
            return (jnp.where(keep, src, -1), dst,
                    inv.astype(jnp.float32),
                    (deg > 0).astype(jnp.float32), jnp.sum(deg))

        return generate, no_dedup

    monkeypatch.setattr(pagerank, "rmat_programs", keeps)
    with pytest.raises(RuntimeError, match="distinct edges"):
        _run(copy)


def test_a_refused_plan_fails_the_run(copy, monkeypatch):
    from tpu_distalg.models import pagerank

    monkeypatch.setattr(pagerank, "prepare_device_spmv",
                        lambda graph, mesh, rg=None: None)
    with pytest.raises(RuntimeError, match="refused its plan"):
        _run(copy)


def test_a_program_without_the_loader_is_refused_at_once(copy, monkeypatch):
    from tpu_distalg.models import pagerank

    monkeypatch.delattr(pagerank, "build_rmat_graph")
    with pytest.raises(RuntimeError, match="no loader of a graph"):
        _run(copy)


def test_a_plan_of_another_geometry_is_refused(copy, monkeypatch):
    from tpu_distalg.ops import pallas_pagerank as ppr

    monkeypatch.setattr(ppr, "SPMV_SPAN_SLACK", 32)
    with pytest.raises(RuntimeError, match="not the one the configuration"):
        _run(copy)


def test_the_real_cell_reports_what_it_lists_and_the_new_metrics():
    """The seven lists the cell joined and its eleven metrics, none of
    another family's; the work function counts needed bytes only; a
    reader finds nothing without a trace."""
    from harness import bytes_pagerank

    real = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"), REAL)
    names = {m["name"] for m in real.per_layer}
    new = {"data_build_s.graph", "plan_s.graph", "plan_rejections.graph",
           "sweep_ms.graph", "scoped_busy_pct.graph",
           "spmv_ms_per_sweep.graph", "pagerank_spmv_roofline",
           "dispatch_gap_ms.graph", "median_call_rows_per_s.graph",
           "device_idle_pct.graph", "hbm_peak_gb.graph"}
    assert names == new | {"compile_s", "cache_misses", "trace_s",
                           "lower_s", "cache_load_s", "jit_traces"}
    assert {m["name"] for m in real.end_to_end} == {"setup_s",
                                                    "rows_per_s"}
    assert real.chips == 1
    assert real.config["family"] == "pagerank_resident"
    assert real.entry["traffic"] == "job10"
    manifest = mf.load_json(os.path.join(helpers.ROOT, "BENCHMARK.json"))
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    cfg = [c for c in manifest["configs"]
           if c["name"] == "pagerank-graph500-24"][0]
    assert cfg["reduced"] == ["scale"] and len(cfg["source"]) <= 200

    sh = dict(n_edges=260_000_000, n_vertices=1 << 24, n_shards=1,
              edge_bytes_needed=8, vertex_bytes_needed=12)
    assert bytes_pagerank.sweep_bytes_needed(sh) == \
        260_000_000 * 8 + (1 << 24) * 12

    class Ctx:
        reduced = None
        shapes = sh
        peaks = {"hbm_bytes_per_sec": 819e9}
        counters = {}
        readings_s = []
        memory_peak_bytes = 0

        @staticmethod
        def span_seconds(name):
            return None

    # plan_s.graph reads the program's ring of spans, not the trace
    for name in new - {"plan_s.graph"}:
        assert real.reader(name).read(Ctx()) is None, name


def test_the_real_cells_geometry_from_its_files():
    """The sizes every seed gets, from the configuration alone (no
    device): the gather groups, the windows, the slots and the bytes
    resident."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    c = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"), REAL).config
    geom = ppr.spmv_geometry(1 << c["scale"],
                             c["edge_factor"] << c["scale"],
                             c["data_shards"])
    assert dict(rg=geom.rg, ws=geom.ws, blk=geom.blk,
                chunk=geom.chunk) == c["geometry"]
    assert geom.n_groups == 256 and geom.ranks_form == "windowed"
    assert geom.r8 * 128 == 1 << 24
    spare = geom.n_slots - (c["edge_factor"] << c["scale"])
    assert geom.n_groups * geom.step_slots <= spare < 2.2e6
    resident = geom.n_slots * 20 + geom.n_chunks * 8
    assert 5.4e9 < resident < 5.45e9          # 34% of a chip's 16 GB
    assert ppr.spmv_resident_bytes(1 << 24, geom.rg, geom.ws) \
        < ppr.SPMV_VMEM_BUDGET
    assert geom.seg_steps * geom.blk * 4 < 256 * 1024     # SMEM a call
    assert np.prod([geom.n_steps, geom.blk, geom.chunk]) == geom.n_slots
