"""Rehearsal of the pair-set closure family on the CPU, as
``test_rehearsal_closure.py`` rehearses the dense one: two tiny cells
(BigDatalog's grid at side 5, where a pair has many derivations, and a
tree of height 5) added to a temporary copy of the benchmark (new files,
new entries, nothing edited) and run end to end through
``run.run_cell``; call ``rounds_per_job + 1`` equal to call 1; the
control and three planted faults (a round that does not make its
candidates distinct, a truncated buffer, a state handed back unchanged),
which each have to come out as not correct on the grid; a program
without the entry points and a program of other capacities, which fail
the run; the reference against the program's own generator, the closed
form and a plain product of the adjacency matrix; the real cell's lists,
byte function, readers and sizes."""

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
from reference import closure_ref, closure_tree_ref

CELLS = mf.load_json(os.path.join(helpers.TESTS, "data",
                                  "cells_closure_sparse.json"))
REAL = "closure_tree17_round1"
GRID = "closure_sparse_tiny"
TREE = "closure_tree_tiny"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench_closure_sparse"))
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


def _run(copy, cell=GRID, seed=2**31 + 11, seconds=0.2, **kw):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc, res = bench.run_cell(cell, seed, seconds, False, **copy, **kw)
    return rc, res, out.getvalue()


def _line(log, word):
    return [ln for ln in log.splitlines() if word in ln][0]


CHECKS = ("window_compiles", "set_errors.first", "set_errors.last",
          "pair_count_err", "pair_count_err.whole", "fixpoint_flag_errors",
          "overflow_flags")


def test_family_rehearsal_and_its_control(copy):
    rc, res, log = _run(copy, control=True)
    assert rc == 0
    json.dumps(res)
    assert set(res["metrics"]) == {"setup_s", "rows_per_s"}
    assert res["attempted"] >= 8 and res["failed"] == 0
    assert res["correct"] is True, log
    assert "[closure] vertices 36 arcs 60 set 512 pairs, new 256, " \
        "candidates 512" in log
    assert "rounds/job 10 sampled sources 12" in log
    assert "36 rows a call" in log
    for name in CHECKS:
        assert f"[check] {name} = " in log, name
    # the job's rounds: the ninth leaves the closed form, the tenth the
    # same; a grid pair has many derivations, so a round joins more
    # candidates than it finds new pairs
    ln = _line(log, "pairs a call")
    counts = json.loads(ln.split("pairs a call ")[1].split(" (the")[0])
    new = json.loads(ln.split("new pairs a call ")[1].split(";")[0])
    cand = json.loads(ln.split("candidates a call ")[1].split(";")[0])
    assert len(counts) == 10
    assert counts[8] == counts[9] == 405 == closure_ref.closure_pairs(5)
    assert all(a < b for a, b in zip(counts[:8], counts[1:9]))
    assert new[9] == 0 and sum(new) == 405 - 60
    assert sum(cand) > sum(new)
    # the control stands outside both limits
    controls = [x for x in log.splitlines() if x.startswith("[control] ")]
    assert len(controls) == 2
    for x in controls:
        assert float(x.split("= ")[1]) > 0, x


def test_the_tree_cell_rehearses_too(copy):
    rc, res, log = _run(copy, cell=TREE, seed=5)
    assert rc == 0 and res["correct"] is True, log
    ln = _line(log, "pairs a call")
    counts = json.loads(ln.split("pairs a call ")[1].split(" (the")[0])
    new = json.loads(ln.split("new pairs a call ")[1].split(";")[0])
    cand = json.loads(ln.split("candidates a call ")[1].split(";")[0])
    sizes = CELLS["configs"]["closure-tree-tiny"]["level_sizes"]
    assert counts == [closure_tree_ref.tree_pairs_within(sizes, k + 2)
                      for k in range(5)] + [1819]
    # on a tree no candidate is a duplicate
    assert new == cand


def test_the_tiny_cells_came_as_files_and_entries(copy):
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
    real = mf.Cell(copy["manifest_path"], REAL, copy["bench_dir"])
    for name in (GRID, TREE):
        cell = mf.Cell(copy["manifest_path"], name, copy["bench_dir"])
        assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                        "rows_per_s"]
        assert {m["name"] for m in cell.per_layer} == {
            m["name"] for m in real.per_layer}


def test_call_rounds_per_job_plus_one_is_call_one(copy):
    """The eleventh call starts the next job from the edge list: its
    set and count are the first call's, pair for pair."""
    import jax

    cell = mf.Cell(copy["manifest_path"], GRID, copy["bench_dir"])
    ctx = bench.Context(cell, 5, copy["out_dir"])
    ctx.devices = jax.devices()[:1]
    family = cell.family()
    with contextlib.redirect_stdout(io.StringIO()):
        state = family.setup(ctx)            # calls 1 and 2
    seen = {}
    for call in range(3, 13):
        state.sync(state.dispatch())
        seen[call] = state.sample()
    assert state.round_of(11) == 1 and state.round_of(10) == 10
    for got, want in zip(seen[11], state.first_pairs):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(state.flags[10][1]),
                                  np.asarray(state.flags[0][1]))
    np.testing.assert_array_equal(np.asarray(state.flags[11][1]),
                                  np.asarray(state.flags[1][1]))
    assert [bool(s) for s, _, _ in state.flags] == [
        False] * 9 + [True] + [False] * 2
    out = state.finish()
    assert out["calls"] == 12 and out["overflow"] == [0] * 12


def test_same_seed_same_inputs(copy):
    a, b, c = (_run(copy, seed=s)[2] for s in (5, 5, 6))
    job = [_line(x, "pairs a call").split("calls; ")[1].split("; the last")[0]
           for x in (a, b, c)]
    assert job[0] == job[1] == job[2]
    sizes = CELLS["configs"]["closure-tree-tiny"]["level_sizes"]
    np.testing.assert_array_equal(
        closure_tree_ref.tree_edges(sizes, [2, 6], 5),
        closure_tree_ref.tree_edges(sizes, [2, 6], 5))
    assert not np.array_equal(closure_tree_ref.tree_edges(sizes, [2, 6], 5),
                              closure_tree_ref.tree_edges(sizes, [2, 6], 6))


@pytest.fixture
def planted(monkeypatch):
    """Puts a fault in the program's round: the compiled round is kept
    by its geometry (``make_sparse_round_fn``'s cache), so the cache is
    emptied before the fault goes in and after it comes out."""
    from tpu_distalg.models import transitive_closure as tc

    def plant(name, fn):
        monkeypatch.setattr(tc, name, fn)
        tc.make_sparse_round_fn.cache_clear()

    tc.make_sparse_round_fn.cache_clear()
    yield plant
    monkeypatch.undo()
    tc.make_sparse_round_fn.cache_clear()


def test_a_round_without_its_set_difference_is_not_correct(copy, planted):
    """The candidates merged in as they come, neither made distinct nor
    held against the set: on the grid a pair arrives several times."""
    import jax
    import jax.numpy as jnp

    from tpu_distalg.models import transitive_closure as tc

    def undistinct(state, cx, cz, g):
        v = g.n_vertices
        ux, uz = jax.lax.sort((jnp.concatenate([state.sx, cx]),
                               jnp.concatenate([state.sz, cz])),
                              num_keys=2)
        cx, cz = jax.lax.sort((cx, cz), num_keys=2)
        return (ux[:g.capacity], uz[:g.capacity], cx[:g.delta_capacity],
                cz[:g.delta_capacity], ux < v, cx < v, jnp.bool_(False))

    planted("sparse_distinct", undistinct)
    rc, res, log = _run(copy)
    assert rc == 0 and res["correct"] is False
    assert "FAILED" in _line(log, "[check] pair_count_err =")
    assert "FAILED" in _line(log, "[check] set_errors.last")
    del tc


def test_a_truncated_buffer_is_not_correct(copy, planted):
    """The set cut to its first 300 pairs with no overflow said."""
    import jax.numpy as jnp

    from tpu_distalg.models import transitive_closure as tc

    sound = tc.sparse_distinct

    def truncated(state, cx, cz, g):
        sx, sz, dx, dz, held, new, _ = sound(state, cx, cz, g)
        cut = jnp.arange(g.capacity) < 300
        v = g.n_vertices
        return (jnp.where(cut, sx, v), jnp.where(cut, sz, v), dx, dz,
                held & (jnp.arange(len(held)) < 300), new,
                jnp.bool_(False))

    planted("sparse_distinct", truncated)
    rc, res, log = _run(copy)
    assert rc == 0 and res["correct"] is False
    assert "FAILED" in _line(log, "[check] pair_count_err =")
    assert "FAILED" in _line(log, "[check] pair_count_err.whole")


def test_a_state_handed_back_unchanged_is_not_correct(copy, planted):
    from tpu_distalg.models import transitive_closure as tc
    from tpu_distalg.ops import graph as gops

    def broken(mesh, geom):
        import jax.numpy as jnp

        return lambda state, arcs: (
            state, gops.path_count(state.n[None]), jnp.bool_(True),
            jnp.zeros((3,), jnp.int32))

    broken.cache_clear = lambda: None
    planted("make_sparse_round_fn", broken)
    rc, res, log = _run(copy)
    assert rc == 0 and res["correct"] is False
    assert "FAILED" in _line(log, "[check] set_errors.first")
    assert "FAILED" in _line(log, "[check] pair_count_err =")
    assert "FAILED" in _line(log, "[check] fixpoint_flag_errors")


def test_an_overflow_is_not_correct(copy, planted):
    """A buffer too small for a round's candidates: the flag is set and
    the run is not correct, whatever the counts say."""
    from tpu_distalg.models import transitive_closure as tc

    sound = tc.sparse_join

    def flagged(state, arcs, g):
        cx, cz, joined, over = sound(state, arcs, g)
        return cx, cz, joined, over | (joined > 100)

    planted("sparse_join", flagged)
    rc, res, log = _run(copy)
    assert rc == 0 and res["correct"] is False
    assert "FAILED" in _line(log, "[check] overflow_flags")


def test_a_program_without_the_entry_points_is_refused_at_once(
        copy, monkeypatch):
    from tpu_distalg.models import transitive_closure as tc

    monkeypatch.delattr(tc, "make_sparse_round_fn")
    monkeypatch.delattr(tc, "prepare_sparse")
    with pytest.raises(RuntimeError,
                       match="no prepare_sparse, make_sparse_round_fn"):
        _run(copy)


def test_a_program_of_other_capacities_is_refused(copy, monkeypatch):
    import dataclasses

    from tpu_distalg.models import transitive_closure as tc

    sound = tc.sparse_geometry
    monkeypatch.setattr(
        tc, "sparse_geometry", lambda *a: dataclasses.replace(
            sound(*a), join_capacity=sound(*a).join_capacity * 2))
    with pytest.raises(RuntimeError, match="the configuration states"):
        _run(copy)


def test_the_reference_restates_the_programs_tree():
    from tpu_distalg.utils import datasets

    for height, seed in ((3, 0), (5, 2**31 + 11), (9, 7)):
        sizes = datasets.tree_level_sizes(height)
        np.testing.assert_array_equal(
            closure_tree_ref.tree_edges(sizes, datasets.TREE_CHILDREN, seed),
            datasets.tree_edges(height, seed))
        for arcs in (None, 1, 2, height):
            assert closure_tree_ref.tree_pairs_within(sizes, arcs) == \
                datasets.tree_closure_pairs(height, arcs)
    c = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"), REAL).config
    assert c["level_sizes"] == datasets.tree_level_sizes(c["tree_height"])
    assert list(c["children"]) == list(datasets.TREE_CHILDREN)
    assert c["level_ratio"] == datasets.TREE_LEVEL_RATIO


@pytest.mark.parametrize("config", [
    {"grid_side": 6, "n_vertices": 49, "sample_rows": 16},
    {"level_sizes": [1, 2, 6, 14, 34, 83], "children": [2, 6],
     "n_vertices": 140, "sample_rows": 16}], ids=["grid", "tree"])
def test_the_reference_follows_the_linear_join(config):
    """Paths of at most L arcs from the sampled sources, against a plain
    NumPy product of the adjacency matrix, and the pairs counted
    whole."""
    v = config["n_vertices"]
    ref = closure_tree_ref.Reference(config, 3)
    adj = np.zeros((v, v), bool)
    adj[ref.edges[:, 0], ref.edges[:, 1]] = True
    reach, by_arcs = adj.copy(), {1: adj.copy()}
    for arcs in range(2, 14):
        reach = reach | ((reach.astype(np.float32)
                          @ adj.astype(np.float32)) > 0)
        by_arcs[arcs] = reach.copy()
    for arcs in (1, 2, 3, 5, 13):
        s, z = np.nonzero(by_arcs[arcs][ref.sources])
        np.testing.assert_array_equal(ref.reached(arcs), s * v + z)
        assert ref.pairs(arcs) == by_arcs[arcs].sum()
    x, z = np.nonzero(by_arcs[2])
    mine = np.isin(x, ref.sources)
    keys = ref.keys(x[mine], z[mine])
    assert closure_tree_ref.set_errors(keys, ref.reached(2)) == 0
    # a pair held twice, one missing and one too many each count
    assert closure_tree_ref.set_errors(
        np.concatenate([keys, keys[:1]]), ref.reached(2)) == 1
    assert closure_tree_ref.set_errors(keys[1:], ref.reached(2)) == 1
    assert closure_tree_ref.set_errors(
        np.concatenate([keys, [v * v * 99]]), ref.reached(2)) == 1


def test_the_real_cell_reports_what_it_lists_and_the_new_metrics():
    from harness import bytes_closure

    real = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"), REAL)
    names = {m["name"] for m in real.per_layer}
    new = {"join_ms_per_round.closure", "distinct_ms_per_round.closure",
           "new_pairs_per_round.closure", "closure_sparse_roofline"}
    shared = {"data_build_s.closure", "round_ms.closure",
              "count_ms_per_round.closure", "scoped_busy_pct.closure",
              "device_idle_pct.closure", "hbm_peak_gb.closure",
              "dispatch_gap_ms.closure", "median_call_rows_per_s.closure"}
    assert names == new | shared | {
        "compile_s", "cache_misses", "trace_s", "lower_s", "cache_load_s",
        "jit_traces", "data_unspanned_s", "hbm_loader_peak_gb",
        "hbm_resident_gb"}
    assert {m["name"] for m in real.end_to_end} == {"setup_s",
                                                    "rows_per_s"}
    assert real.chips == 1 and real.entry["traffic"] == "round1"
    assert real.config["family"] == "closure_sparse"
    manifest = mf.load_json(os.path.join(helpers.ROOT, "BENCHMARK.json"))
    assert len(manifest["workloads"]) == 13
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 2
    cfg = [c for c in manifest["configs"]
           if c["name"] == "closure-tree17"][0]
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    assert real.config["source"] == cfg["source"]

    # the set once in and once out, the new pairs three times, a
    # candidate twice, the arcs' rows: Tree17's round 10
    need = bytes_closure.round_bytes_needed({}, 1.3e8, 1.37e7, 1.37e7)
    assert need == 16 * 1.3e8 + 32 * 1.37e7 + 20 * 1.37e7
    assert 2e-3 < need / 819e9 < 4e-3

    class Ctx:
        reduced = None
        shapes = {}
        peaks = {"hbm_bytes_per_sec": 819e9}
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
    the levels, the totals, the rounds a job, the bytes carried."""
    c = mf.Cell(os.path.join(helpers.ROOT, "BENCHMARK.json"), REAL).config
    sizes = c["level_sizes"]
    assert len(sizes) == c["tree_height"] + 2 == 19
    assert sum(sizes) == c["n_vertices"] == 13766856
    assert c["n_edges"] == c["n_vertices"] - 1
    assert closure_tree_ref.tree_pairs_within(sizes) == c["closure_pairs"] \
        == 237977708
    assert c["longest_path_arcs"] == len(sizes) - 1 == c["rounds_per_job"]
    for n, below in zip(sizes, sizes[1:]):
        p = closure_tree_ref.tree_nonleaves(n, below, c["children"])
        assert p <= n and 2 * p <= below <= 6 * p
    assert c["capacity"] >= c["closure_pairs"]
    # no round of a tree joins or finds more pairs than it has arcs
    assert min(c["delta_capacity"], c["join_capacity"]) >= c["n_edges"]
    carried = 8 * (c["capacity"] + c["delta_capacity"]) \
        + 8 * (c["n_vertices"] + 1) + 4 * (c["n_edges"] + 1)
    assert 2.15e9 < carried == 2446903656       # 14% of a chip's 16 GiB
    assert len(closure_ref.sample_sources(
        c["n_vertices"], c["sample_rows"], 2**31 + 5)) == 256
