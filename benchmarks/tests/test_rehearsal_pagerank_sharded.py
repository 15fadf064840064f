"""Rehearsal of the sharded PageRank family on the CPU, as
``test_rehearsal_pagerank_resident.py`` rehearses the resident one: a
tiny cell (Graph500's generator at SCALE 12 on four virtual devices)
added to a temporary copy of the benchmark (new files, new entries,
nothing edited) and run end to end through ``run.run_cell``; the
control (the reference with bfloat16 ranks and contributions) and a
ranks vector left at the uniform start, which both have to come out as
not correct; a shard past its capacity, a capacity other than the
configuration's and a program that cannot shard the graph, which fail
the run; the real cell's lists, readers and geometry; the plain
reference in blocks against the resident one."""

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
                                  "cells_pagerank_sharded.json"))
REAL = "pagerank_g500_sharded4_job10"
TINY = "pagerank_tiny_sharded"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench_pagerank_sharded"))
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
    assert res["device"]["count"] == 4
    assert "[pagerank] path spmv ranks in resident out range on 4 " \
        "shards; vertices 4096 generated 65536 distinct 53" in log
    assert "rejections 0" in log
    for name in CHECKS:
        assert f"[check] {name} = " in log, name
    line = _line(log, "[pagerank] path")
    distinct = int(line.split("distinct ")[1].split()[0])
    shards = json.loads(line.split("a shard ")[1].split(" of ")[0])
    assert len(shards) == 4 and sum(shards) == distinct
    assert max(shards) <= 39172
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


def test_a_shard_past_its_capacity_fails_the_run(copy, monkeypatch):
    """No edge is dropped where a range draws more than a shard holds:
    the program's load fails by name."""
    import dataclasses

    from tpu_distalg.ops import pallas_pagerank as ppr

    real = ppr.spmv_geometry
    monkeypatch.setattr(ppr, "spmv_geometry", lambda *a: (
        lambda g: dataclasses.replace(g, bucket=g.bucket // 4))(real(*a)))
    with pytest.raises(ValueError, match="pagerank_shard_overflow"):
        _run(copy)


def test_another_capacity_is_refused(copy, monkeypatch):
    from tpu_distalg.ops import pallas_pagerank as ppr

    monkeypatch.setattr(ppr, "SPMV_SHARD_SIGMAS", 12.0)
    with pytest.raises(RuntimeError, match="a shard of the program holds"):
        _run(copy)


def test_a_program_that_cannot_shard_fails_at_once(copy, monkeypatch):
    """This cell's parent: its geometry holds every vertex's output
    row on every chip and refuses the size; the loader raises before
    anything is drawn."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    monkeypatch.setattr(ppr, "spmv_geometry", lambda *a, **kw: None)
    with pytest.raises(ValueError, match="past the resident fused SpMV"):
        _run(copy)


def test_a_refused_plan_fails_the_run(copy, monkeypatch):
    from tpu_distalg.models import pagerank

    monkeypatch.setattr(pagerank, "prepare_device_spmv",
                        lambda graph, mesh, rg=None: None)
    with pytest.raises(RuntimeError, match="refused its plan"):
        _run(copy)


def test_the_real_cell_reports_what_it_lists_and_the_new_metrics():
    """The eighteen lists the cell joined and its four new metrics; a
    reader finds nothing without a trace, a counter or a span."""
    real = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"), REAL)
    names = {m["name"] for m in real.per_layer}
    new = {"sync_ms_per_sweep.graph", "sync_exposed_ms_per_sweep.graph",
           "shard_imbalance_pct.graph", "load_exchange_s.graph"}
    graph = {"data_build_s.graph", "plan_s.graph", "plan_rejections.graph",
             "sweep_ms.graph", "scoped_busy_pct.graph",
             "spmv_ms_per_sweep.graph", "pagerank_spmv_roofline",
             "dispatch_gap_ms.graph", "median_call_rows_per_s.graph",
             "device_idle_pct.graph", "hbm_peak_gb.graph"}
    assert names == new | graph | {"compile_s", "cache_misses", "trace_s",
                                   "lower_s", "cache_load_s", "jit_traces"}
    assert {m["name"] for m in real.end_to_end} == {"setup_s",
                                                    "rows_per_s"}
    assert real.chips == 4
    assert real.config["family"] == "pagerank_sharded"
    assert real.entry["traffic"] == "job10"
    manifest = mf.load_json(os.path.join(helpers.ROOT, "BENCHMARK.json"))
    assert len(manifest["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 2
    cfg = [c for c in manifest["configs"]
           if c["name"] == "pagerank-graph500-sharded4"][0]
    # the source's class is SCALE 26; the cell runs 25 and says so
    assert cfg["reduced"] == ["scale"] and len(cfg["source"]) <= 200
    assert "SCALE 26" in cfg["source"] and real.config["scale"] == 25
    # only this cell reports the new metrics
    for m in manifest["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [REAL]

    class Ctx:
        reduced = None
        shapes = {}
        peaks = {}
        counters = {}
        readings_s = []
        memory_peak_bytes = 0

    # load_exchange_s.graph reads the program's ring of spans
    for name in new - {"load_exchange_s.graph"}:
        assert real.reader(name).read(Ctx()) is None, name
    Ctx.counters = {"shard_edges_max": 103, "shard_edges_mean": 100}
    assert real.reader("shard_imbalance_pct.graph").read(Ctx()) == \
        pytest.approx(3.0)


def test_the_real_cells_geometry_from_its_files():
    """The sizes every seed gets, from the configuration alone (no
    device): a shard's rows, the gather groups over the whole table,
    the windows, the capacity, the slots and the bytes resident."""
    from tpu_distalg.ops import pallas_pagerank as ppr

    c = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"), REAL).config
    V, n_in = 1 << c["scale"], c["edge_factor"] << c["scale"]
    geom = ppr.spmv_geometry(V, n_in, c["data_shards"])
    assert dict(rg=geom.rg, ws=geom.ws, blk=geom.blk,
                chunk=geom.chunk) == c["geometry"]
    assert geom.shard_cap == c["shard_capacity"]
    assert 1.005 < geom.shard_cap * 4 / n_in < 1.012
    assert geom.n_groups == 512 and geom.ranks_form == "windowed"
    assert geom.ranks_out_form == "range"
    assert geom.r8 * 128 == V
    assert geom.r8 / 4 < geom.rows_out < 1.04 * geom.r8 / 4
    spare = geom.shard_slots - geom.shard_cap
    assert geom.n_groups * geom.step_slots <= spare < 4.3e6
    resident = geom.shard_slots * 20 + geom.n_steps * geom.blk * 8
    assert 2.75e9 < resident < 2.85e9         # 17% of a chip's 16 GB
    assert ppr.spmv_resident_bytes(V, geom.rg, geom.ws,
                                   n_shards=4) < ppr.SPMV_VMEM_BUDGET
    assert ppr.spmv_geometry(V, n_in, 1) is None         # no one chip
    assert ppr.shards_needed(1 << 26) == 4      # the source's own class
    assert geom.seg_steps * geom.blk * 4 < 256 * 1024     # SMEM a call
    assert (geom.rows_out + 1) * (geom.n_groups + 1) < 2 ** 31


@pytest.mark.parametrize("blocks", [2, 4])
def test_the_reference_in_blocks_is_the_resident_reference(blocks):
    """One destination range a block or the whole graph on one device:
    the same distinct edges and the same ranks; a piece that holds more
    than its room fails the run."""
    from reference import pagerank_resident_ref as whole
    from reference import pagerank_sharded_ref as ranges

    args = (12, 16, [0.57, 0.19, 0.19, 0.05], 2**31 + 11, 0.15, 10)
    r_whole, n_whole = whole.ranks(*args)
    r, n = ranges.ranks(*args, blocks, pieces=4, room=2.0)
    assert n == n_whole
    assert whole.max_rel_err(r, r_whole) < 1e-6
    np.testing.assert_allclose(r.sum(), 1.0, rtol=1e-5)
    with pytest.raises(RuntimeError, match="more room"):
        ranges.ranks(*args, blocks, pieces=4, room=1.0)
