"""PageRank's graph drawn, deduplicated and planned on the device
(``models/pagerank.build_rmat_graph``, ``prepare_device_spmv``;
``ops/pallas_pagerank.sort_slots`` / ``slot_arrays``;
``utils/datasets.kronecker_edges``), against NumPy on the same draw,
the XLA sweep and the benchmark's plain reference; the fused kernel
interpreted on the CPU mesh."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_distalg.models import pagerank
from tpu_distalg.ops import graph as gops
from tpu_distalg.ops import pallas_pagerank as ppr
from tpu_distalg.parallel import get_mesh
from tpu_distalg.telemetry import events as tevents
from tpu_distalg.telemetry import report
from tpu_distalg.utils import datasets

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def mesh1():
    return get_mesh(data=1, model=1, devices=jax.devices()[:1])


def _draw(scale, seed, edge_factor=16):
    src, dst = jax.jit(datasets.kronecker_edges(scale))(
        jnp.arange(edge_factor << scale, dtype=jnp.uint32),
        np.uint32(seed))
    return np.asarray(src, np.int64), np.asarray(dst, np.int64)


@pytest.mark.parametrize("scale", [10, 11, 12])
def test_device_loader_equals_numpy_on_the_same_draw(mesh1, scale):
    """Distinct edges, out-degrees and ``has_out`` of the device's
    sort-and-mask against ``np.unique`` / ``np.bincount``."""
    V, seed = 1 << scale, 3_000_000_019
    src, dst = _draw(scale, seed & 0xFFFFFFFF)
    assert src.min() >= 0 and src.max() < V and dst.max() < V
    code = np.unique(src * V + dst)
    graph = pagerank.build_rmat_graph(mesh1, scale, 16, None, seed)
    assert graph.n_edges == len(code) < graph.n_in == 16 << scale
    got_src, got_dst = np.asarray(graph.src), np.asarray(graph.dst)
    real = got_src >= 0
    assert not real[graph.n_in:].any()      # the spare slots behind
    np.testing.assert_array_equal(
        got_src[real].astype(np.int64) * V + got_dst[real], code)
    deg = np.bincount(code // V, minlength=V)
    np.testing.assert_array_equal(np.asarray(graph.has_out), deg > 0)
    np.testing.assert_allclose(
        np.asarray(graph.inv_deg),
        np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0), rtol=1e-7)


def test_kronecker_draw_follows_the_initiator():
    """The share of edges whose first bit pair falls in each quadrant
    is (A, B, C, D) before the relabelling; after it the degrees are
    skewed as a Kronecker graph's are."""
    scale, n = 12, 16 << 12
    src, dst = _draw(scale, 5)
    assert np.bincount(src).max() > 20 * 16
    assert len(np.unique(src)) < (1 << scale)        # isolated vertices
    a = _draw(scale, 5)
    b = _draw(scale, 6)
    assert (a[0] == src).all() and (a[0] != b[0]).any()
    assert len(src) == n


def _xla_ranks(edges, V, mesh, n_iterations=10):
    return np.asarray(pagerank.run(
        edges, mesh, pagerank.PageRankConfig(
            n_iterations=n_iterations, mode="standard", scatter="xla"),
        V).ranks)


def _reference():
    bench = os.path.join(ROOT, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from reference import pagerank_resident_ref

    return pagerank_resident_ref


@pytest.mark.parametrize("shards", [1, 4])
def test_device_plan_sweep_equals_xla_and_the_reference(mesh8, shards):
    """``run_rmat`` (drawn, deduplicated, planned on the device, the
    fused kernel interpreted) against the XLA sweep on the same edges
    pulled to the host, and against the benchmark's plain reference,
    which draws the graph itself; on one shard and on four (the
    ``pagerank`` rule table's sharded chunks, the psum)."""
    scale, seed = 12, 2**31 + 11
    V = 1 << scale
    mesh = get_mesh(data=shards, model=1, devices=jax.devices()[:shards])
    cfg = pagerank.PageRankConfig(n_iterations=10, mode="standard")
    got = np.asarray(pagerank.run_rmat(mesh, cfg, scale, 16, None,
                                       seed).ranks)
    src, dst = _draw(scale, seed & 0xFFFFFFFF)
    want = _xla_ranks(np.stack([src, dst], axis=1), V, mesh)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-9)
    ref = _reference()
    r_ref, n_edges = ref.ranks(scale, 16, datasets.GRAPH500_ABCD, seed,
                               cfg.q, 10)
    assert n_edges == len(np.unique(src * V + dst))
    assert ref.l1_err(got, r_ref) < 1e-6
    assert ref.max_rel_err(got, r_ref) < 1e-5
    np.testing.assert_allclose(got.sum(), 1.0, rtol=1e-5)
    r_low, _ = ref.ranks(scale, 16, datasets.GRAPH500_ABCD, seed, cfg.q,
                         10, dtype=jnp.bfloat16)
    assert ref.l1_err(r_low, r_ref) > 1e-3       # the control is far


def test_device_plan_of_a_host_graph_equals_xla(mesh8):
    """One planner: ``pagerank.run`` copies a host edge list up and
    plans it by the same device code (``erdos_renyi_edges``: uniform
    destinations, several gather groups)."""
    V = 40_000
    edges = datasets.erdos_renyi_edges(V, 8.0, seed=3)
    el = gops.prepare_edges(edges, V)
    spmv = pagerank.prepare_device_spmv(el, mesh8, rg=64)
    assert spmv is not None and spmv.ranks_form == "windowed"
    de = pagerank.prepare_device_edges(el, mesh8, light=True)
    cfg = pagerank.PageRankConfig(n_iterations=6, mode="standard",
                                  scatter="spmv")
    fn = pagerank.make_run_fn(mesh8, cfg, V, None, spmv)
    got = np.asarray(fn(de.src, de.dst, de.w_e, de.emask, de.has_out,
                        de.n_ref)[0])
    want = _xla_ranks(edges, V, mesh8, 6)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-9)


def test_device_plan_equals_the_host_plan(mesh1):
    """The device's sort and layout against ``plan_spmv``'s NumPy on
    the same edges: the same windows chunk by chunk and the same edges
    in all (the order inside a destination row is free, so which of
    two chunks holds an edge of the row they share is too)."""
    V, e = 30_000, 200_000
    rng = np.random.default_rng(11)
    code = np.unique(rng.integers(0, V, e) * V + rng.integers(0, V, e))
    src, dst = code // V, code % V
    el = gops.EdgeList(src.astype(np.int32), dst.astype(np.int32), V,
                       np.bincount(src, minlength=V).astype(np.int32))
    inv = pagerank._inv_out_degree(el)
    host = ppr.plan_spmv(src, dst, inv[src], V, rg=64)
    dev = pagerank.prepare_device_spmv(el, mesh1, rg=64)
    assert (dev.rg, dev.ws, dev.r8, dev.n_chunks) == (
        host.rg, host.ws, host.r8, host.n_chunks)
    np.testing.assert_array_equal(np.asarray(dev.gbase), host.gbase)
    np.testing.assert_array_equal(np.asarray(dev.sbase), host.sbase)

    def edges_of(p):
        """(src, dst, weight) of every slot that holds an edge."""
        w = np.asarray(p.w_e).reshape(p.n_chunks, -1)
        at = np.nonzero(w)
        gb = np.asarray(p.gbase)[at[0]]
        sb = np.asarray(p.sbase)[at[0]]

        def slot(a):
            return np.asarray(a).reshape(p.n_chunks, -1)[at]

        s = (gb + slot(p.src_row)) * 128 + slot(p.src_lane)
        d = (sb + slot(p.dst_row)) * 128 + slot(p.dst_lane)
        order = np.lexsort((s, d))
        return s[order], d[order], w[at][order]

    for a, b in zip(edges_of(dev), edges_of(host)):
        np.testing.assert_array_equal(a, b)


def test_two_seeds_one_geometry_and_one_trace(mesh1):
    """Every static shape is a function of the sizes: a second seed
    gives the same geometry and arrays of the same shapes, and the run
    function built for the first plan runs the second without a new
    trace."""
    scale = 11
    cfg = pagerank.PageRankConfig(n_iterations=2, mode="standard",
                                  scatter="spmv")
    plans, ranks = [], []
    for seed in (1, 2):
        graph = pagerank.build_rmat_graph(mesh1, scale, 16, None, seed)
        plans.append((graph.geom, pagerank.prepare_device_spmv(
            graph, mesh1), pagerank.spmv_device_edges(graph, mesh1)))
    (g1, p1, d1), (g2, p2, d2) = plans
    assert g1 == g2 == ppr.spmv_geometry(1 << scale, 16 << scale, 1)
    for a, b in zip(p1.arrays, p2.arrays):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert not np.array_equal(np.asarray(p1.src_lane),
                              np.asarray(p2.src_lane))

    # the program's sweep with the plan as an argument: one trace
    def sweeps(plan_arrays, has_out):
        fn = pagerank.make_run_fn(mesh1, cfg, 1 << scale, None,
                                  pagerank.DeviceSpMV.of(plan_arrays, g1))
        return fn(None, None, None, None, has_out, None)[0]

    traced = jax.jit(sweeps)
    for p, d in ((p1, d1), (p2, d2)):
        ranks.append(np.asarray(traced(p.arrays, d.has_out)))
    assert traced._cache_size() == 1
    assert not np.allclose(ranks[0], ranks[1])


def test_a_span_past_the_fixed_window_is_reported(mesh1, tmp_path):
    """A graph more skewed than its geometry's window allows: the plan
    is refused, counted and named in the log; ``run_rmat`` raises, and
    ``scatter='spmv'`` on a host graph does too."""
    V = 1 << 14
    rng = np.random.default_rng(2)
    # destinations in two far bands: every chunk spans the whole table
    e = 40_000
    src = rng.integers(0, V, e)
    dst = np.where(rng.random(e) < 0.5, rng.integers(0, 256, e),
                   V - 1 - rng.integers(0, 256, e))
    code = np.unique(src * V + dst)
    el = gops.prepare_edges(np.stack([code // V, code % V], 1), V)
    sink = str(tmp_path / "tele")
    tevents.configure(sink)
    try:
        assert pagerank.prepare_device_spmv(el, mesh1) is None
    finally:
        tevents.configure(False)
    evts = report.load_events(sink)
    rejected = [x for x in evts if x.get("ev") == "spmv_span_rejected"]
    assert len(rejected) == 1 and rejected[0]["span"] > rejected[0]["ws"]
    assert report.summarize(evts)["counters"]["spmv_plan_rejections"] == 1
    plan_end = [x for x in evts if x.get("ev") == "span_end"
                and x["name"] == "pagerank:plan"][0]
    assert plan_end["span"] == rejected[0]["span"]
    with pytest.raises(ValueError, match="spmv"):
        pagerank.run(np.stack([code // V, code % V], 1), mesh1,
                     pagerank.PageRankConfig(mode="standard",
                                             scatter="spmv"), V)


def test_geometry_is_a_function_of_the_sizes():
    g = ppr.spmv_geometry(1 << 24, 16 << 24)
    assert (g.rg, g.n_groups, g.ws, g.r8) == (512, 256, 224, 131072)
    assert g.ranks_form == "windowed" and g.n_steps % g.seg_steps == 0
    assert g.seg_steps <= ppr.SPMV_SEG_STEPS
    assert g.n_slots >= (16 << 24) + g.n_groups * g.step_slots
    # a sparser graph of as many vertices: the tallest groups, the cap
    sparse = ppr.spmv_geometry(1 << 24, 4 << 24)
    assert sparse.rg == 512 and sparse.ws == ppr.SPMV_WS_CAP
    # the last group is never skinny: 49 tiles are 7 groups of 7
    g = ppr.spmv_geometry(50_000, 300_000, rg=32)
    assert (g.rg, g.n_groups) == (56, 7)
    # shards: whole segments each
    g4 = ppr.spmv_geometry(1 << 20, 16 << 20, n_shards=4)
    assert g4.n_chunks % (4 * g4.blk) == 0
    assert ppr.spmv_geometry(40_000_000, 1 << 20) is None


def test_cli_rmat_end_to_end_with_its_report(tmp_path, capsys):
    from tpu_distalg import cli

    tel = str(tmp_path / "tel")
    rc = cli.main(["--emulate", "1", "pagerank", "--telemetry-dir", tel,
                   "--rmat-scale", "10", "--n-iterations", "10",
                   "--seed", "7", "--checkpoint-dir",
                   str(tmp_path / "ck"), "--checkpoint-every", "5"])
    out = capsys.readouterr().out
    assert not rc
    assert "drawn on the device" in out and "has rank" in out
    cli.main(["report", tel])
    text = capsys.readouterr().out
    for name in ("pagerank:generate", "pagerank:dedup",
                 "pagerank:prepare", "pagerank:plan", "train:segment"):
        assert name in text, name
    assert "ranks table: resident (rg 8, ws 16), scatter passes 3" in text
    evts = report.load_events(tel)
    prepare = [e for e in evts if e.get("ev") == "span_end"
               and e["name"] == "pagerank:prepare"][0]
    assert prepare["vertices"] == 1024 and prepare["generated"] == 16384
    assert 0 < prepare["distinct"] < 16384 and prepare["bytes"] > 0
    assert prepare["padding_share"] > 1
    seg = [e for e in evts if e.get("ev") == "span_start"
           and e["name"] == "train:segment"]
    assert seg and all(e["ranks_form"] == "resident" and e["rg"] == 8
                       and e["scatter_passes"] == 3 for e in seg)
