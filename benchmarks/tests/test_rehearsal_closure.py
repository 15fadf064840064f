"""Rehearsal of the dense closure family on the CPU, as
``test_rehearsal_pagerank_resident.py`` rehearses resident PageRank: a
tiny cell (BigDatalog's grid at side 12) added to a temporary copy of
the benchmark (new files, new entries, nothing edited) and run end to
end through ``run.run_cell``; call ``rounds_per_job + 1`` equal to call
1; the control (a round with the contraction's last block left out) and
a round that leaves its matrix unchanged, which both have to come out
as not correct; a program without the entry points and a program of
another geometry, which fail the run; the reference against the
program's own generator and the closed form; the real cell's lists,
work function, readers and sizes."""

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
from reference import closure_ref

CELLS = mf.load_json(os.path.join(helpers.TESTS, "data",
                                  "cells_closure.json"))
REAL = "closure_grid250_round1"
TINY = "closure_tiny"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench_closure"))
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


CHECKS = ("window_compiles", "row_bit_errors.first", "pair_count_err.first",
          "row_bit_errors.last", "fixpoint_flag_errors")


def test_family_rehearsal_and_its_control(copy):
    rc, res, log = _run(copy, control=True)
    assert rc == 0
    json.dumps(res)
    assert set(res["metrics"]) == {"setup_s", "rows_per_s"}
    assert res["attempted"] >= 8 and res["failed"] == 0
    assert res["correct"] is True, log
    assert "[closure] grid side 12 vertices 169 arcs 312 matrix 169 x " \
        "169 int8" in log
    assert "compose xla rounds/job 6 sampled rows 64" in log
    assert "169 rows a call" in log
    for name in CHECKS:
        assert f"[check] {name} = " in log, name
    # the jobs repeat: every job's rounds count the same pairs, the
    # sixth the closed form and the same as the fifth
    counts = json.loads(_line(log, "pairs a call").split("pairs a call ")[1]
                        .split(" (the first")[0])
    assert len(counts) == 6
    assert counts[4] == counts[5] == 8112 == closure_ref.closure_pairs(12)
    assert counts[0] < counts[1] < counts[2] < counts[3] < counts[4]
    # the control stands outside both limits
    controls = [ln for ln in log.splitlines()
                if ln.startswith("[control] ")]
    assert len(controls) == 2
    for ln in controls:
        assert float(ln.split("= ")[1]) > 0, ln


def test_the_tiny_cell_came_as_files_and_entries(copy):
    """What ``test_yardstick.test_a_new_cell_is_files_and_entries`` holds
    for its cells, for this one: the copy's manifest passes the lint and
    every file that was there is unchanged (the fixture's ``add`` refuses
    to write over one)."""
    from test_yardstick import _lint

    manifest = mf.load_json(copy["manifest_path"])
    _lint(manifest, copy["bench_dir"],
          os.path.dirname(copy["manifest_path"]))
    for d, _, names in os.walk(helpers.BENCH):
        if "__pycache__" in d or os.sep + "tests" in d:
            continue
        for n in names:
            src = os.path.join(d, n)
            dst = os.path.join(copy["bench_dir"],
                               os.path.relpath(src, helpers.BENCH))
            with open(src, "rb") as a, open(dst, "rb") as b:
                assert a.read() == b.read(), src
    cell = mf.Cell(copy["manifest_path"], TINY, copy["bench_dir"])
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "rows_per_s"]
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in mf.Cell(copy["manifest_path"], REAL,
                                   copy["bench_dir"]).per_layer}


def test_call_rounds_per_job_plus_one_is_call_one(copy):
    """The seventh call starts the next job from the edge list: its
    matrix and count are the first call's, bit for bit."""
    import jax

    cell = mf.Cell(copy["manifest_path"], TINY, copy["bench_dir"])
    ctx = bench.Context(cell, 5, copy["out_dir"])
    ctx.devices = jax.devices()[:1]
    family = cell.family()
    with contextlib.redirect_stdout(io.StringIO()):
        state = family.setup(ctx)            # calls 1 and 2
    seen = {}
    for call in range(3, 9):
        state.sync(state.dispatch())
        seen[call] = (np.asarray(state.paths), np.asarray(state.count))
    assert state.round_of(7) == 1 and state.round_of(6) == 6
    # call 7 is a first round again: the matrix of call 1, whose sampled
    # rows set-up kept, and call 8 is call 2
    np.testing.assert_array_equal(
        seen[7][0][np.asarray(state.sources), :169] != 0, state.first_rows)
    np.testing.assert_array_equal(seen[7][1], np.asarray(state.flags[0][1]))
    np.testing.assert_array_equal(seen[8][1], np.asarray(state.flags[1][1]))
    assert [bool(s) for s, _ in state.flags] == [
        False] * 5 + [True] + [False] * 2
    out = state.finish()
    assert out["calls"] == 8 and out["last_rows"].shape == (64, 169)


def test_same_seed_same_inputs(copy):
    """The labels and the sampled sources follow ``--seed`` and nothing
    else; the counts follow neither."""
    first = "pairs a call"
    a, b, c = (_run(copy, seed=s)[2] for s in (5, 5, 6))
    job = [_line(x, first).split("calls; ")[1].split("...")[0]
           for x in (a, b, c)]
    assert job[0] == job[1] == job[2]
    assert not np.array_equal(closure_ref.grid_edges(12, 5),
                              closure_ref.grid_edges(12, 6))
    np.testing.assert_array_equal(closure_ref.sample_sources(169, 64, 5),
                                  closure_ref.sample_sources(169, 64, 5))
    assert not np.array_equal(closure_ref.sample_sources(169, 64, 5),
                              closure_ref.sample_sources(169, 64, 6))


def test_a_round_that_changes_nothing_is_not_correct(copy, monkeypatch):
    from tpu_distalg.models import transitive_closure as tc

    def broken(mesh, geom):
        import jax.numpy as jnp

        return lambda spare, paths, count: (paths, spare, count,
                                            jnp.bool_(True))

    monkeypatch.setattr(tc, "make_round_fn", broken)
    rc, res, log = _run(copy)
    assert rc == 0 and res["correct"] is False
    assert "FAILED" in _line(log, "[check] row_bit_errors.first")
    assert "FAILED" in _line(log, "[check] pair_count_err.first")
    assert "FAILED" in _line(log, "[check] fixpoint_flag_errors")


def test_a_program_without_the_entry_points_is_refused_at_once(
        copy, monkeypatch):
    from tpu_distalg.models import transitive_closure as tc

    monkeypatch.delattr(tc, "make_round_fn")
    monkeypatch.delattr(tc, "make_start_fn")
    with pytest.raises(RuntimeError, match="no make_round_fn, make_start"):
        _run(copy)


def test_a_program_of_another_geometry_is_refused(copy, monkeypatch):
    from tpu_distalg.ops import pallas_closure

    monkeypatch.setattr(pallas_closure, "padded_vertices",
                        lambda v, form, shards: v + 7)
    with pytest.raises(RuntimeError, match="not the one the configuration"):
        _run(copy)


def test_the_reference_restates_the_programs_grid():
    from tpu_distalg.utils import datasets

    for side, seed in ((3, 0), (12, 2**31 + 11), (40, 7)):
        np.testing.assert_array_equal(closure_ref.grid_edges(side, seed),
                                      datasets.grid_edges(side, seed))
        assert closure_ref.closure_pairs(side) == \
            datasets.grid_closure_pairs(side)
    assert closure_ref.closure_pairs(250) == 1000140875


def test_the_reference_follows_the_linear_join():
    """Paths of at most L arcs from the sampled sources, against a plain
    NumPy product of the adjacency matrix, and the pairs within two arcs
    counted whole."""
    side, seed, v = 9, 3, 100
    ref = closure_ref.Reference(side, seed, 16)
    adj = np.zeros((v, v), bool)
    adj[ref.edges[:, 0], ref.edges[:, 1]] = True
    reach, by_arcs = adj.copy(), {1: adj.copy()}
    for arcs in range(2, 20):
        reach = reach | ((adj.astype(np.float32)
                          @ reach.astype(np.float32)) > 0)
        by_arcs[arcs] = reach.copy()
    for arcs in (1, 2, 4, 8, 16, 100):
        np.testing.assert_array_equal(
            ref.rows(arcs), by_arcs[min(arcs, 19)][ref.sources])
    assert closure_ref.pairs_within_two(ref.edges, v) == by_arcs[2].sum()
    assert by_arcs[19].sum() == closure_ref.closure_pairs(side)
    assert len(set(ref.sources)) == 16


def test_the_real_cell_reports_what_it_lists_and_the_new_metrics():
    from harness import flops_closure

    real = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"), REAL)
    names = {m["name"] for m in real.per_layer}
    new = {"data_build_s.closure", "round_ms.closure",
           "compose_ms_per_round.closure", "count_ms_per_round.closure",
           "closure_mxu_roofline", "scoped_busy_pct.closure",
           "device_idle_pct.closure", "hbm_peak_gb.closure",
           "dispatch_gap_ms.closure", "median_call_rows_per_s.closure"}
    assert names == new | {"compile_s", "cache_misses", "trace_s",
                           "lower_s", "cache_load_s", "jit_traces",
                           "data_unspanned_s", "hbm_loader_peak_gb",
                           "hbm_resident_gb"}
    assert {m["name"] for m in real.end_to_end} == {"setup_s",
                                                    "rows_per_s"}
    assert real.chips == 1 and real.entry["traffic"] == "round1"
    assert real.config["family"] == "closure_dense"
    manifest = mf.load_json(os.path.join(helpers.ROOT, "BENCHMARK.json"))
    assert len(manifest["workloads"]) == 11
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 2
    cfg = [c for c in manifest["configs"]
           if c["name"] == "closure-grid250"][0]
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    assert real.config["source"] == cfg["source"]

    c = real.config
    sh = {"n_vertices": c["n_vertices"]}
    assert flops_closure.round_flops_needed(sh) == 2 * 63001 ** 3
    assert 2.5 < flops_closure.round_flops_needed(sh) / 197e12 < 2.6

    class Ctx:
        reduced = None
        shapes = sh
        peaks = {"bf16_flops_per_sec": 197e12}
        counters = {}
        readings_s = []
        spans = []
        memory_peak_bytes = 0

        @staticmethod
        def span_seconds(name):
            return None

    for name in new:
        assert real.reader(name).read(Ctx()) is None, name


def test_the_real_cells_sizes_from_its_files():
    """What every seed gets, from the configuration alone (no device):
    the grid, the padded side, the rounds a job, the bytes resident."""
    import math

    from tpu_distalg.ops import pallas_closure

    c = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"), REAL).config
    n = c["grid_side"] + 1
    assert c["n_vertices"] == n * n == 63001
    assert c["n_edges"] == 2 * n * c["grid_side"] == 125500
    assert c["closure_pairs"] == closure_ref.closure_pairs(c["grid_side"])
    assert c["longest_path_arcs"] == 2 * c["grid_side"]
    assert c["rounds_per_job"] == math.ceil(
        math.log2(c["longest_path_arcs"])) + 1 == 10
    assert pallas_closure.compose_form(c["n_vertices"], True, 1) \
        == c["compose_form"]
    assert pallas_closure.padded_vertices(
        c["n_vertices"], "mosaic", 1) == c["v_padded"] == 63488
    assert list(c["tile"]) == [pallas_closure.TILE_M, pallas_closure.TILE_N,
                               pallas_closure.TILE_K]
    resident = 2 * c["v_padded"] ** 2
    assert 4.29e9 < resident == 8061452288       # 47% of a chip's 16 GiB
    edges = closure_ref.grid_edges(c["grid_side"], 2**31 + 5)
    assert edges.shape == (c["n_edges"], 2)
    assert int(edges.max()) == c["n_vertices"] - 1
    assert len(closure_ref.sample_sources(c["n_vertices"], c["sample_rows"],
                                          2**31 + 5)) == 256
