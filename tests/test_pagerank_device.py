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


def _reference(name="pagerank_resident_ref"):
    bench = os.path.join(ROOT, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import importlib

    return importlib.import_module("reference." + name)


def _mesh(shards):
    return get_mesh(data=shards, model=1, devices=jax.devices()[:shards])


@pytest.mark.parametrize("shards,scale", [(1, 12), (4, 12), (8, 13),
                                          (4, 14)])
def test_device_plan_sweep_equals_xla_and_the_reference(mesh8, shards,
                                                        scale):
    """``run_rmat`` (drawn, exchanged by destination range,
    deduplicated and planned on the device, the fused kernel
    interpreted) against the XLA sweep on the same edges pulled to the
    host, and against the benchmark's plain references, which draw the
    graph themselves: the resident one (the whole graph on a device)
    and the one in blocks (a destination range a block); on one shard
    and on four and eight (a shard's own range of the output table,
    the scalar psum and the all-gather)."""
    seed = 2**31 + 11
    V = 1 << scale
    mesh = _mesh(shards)
    cfg = pagerank.PageRankConfig(n_iterations=10, mode="standard")
    got = np.asarray(pagerank.run_rmat(mesh, cfg, scale, 16, None,
                                       seed).ranks)
    src, dst = _draw(scale, seed & 0xFFFFFFFF)
    want = _xla_ranks(np.stack([src, dst], axis=1), V, mesh)
    assert np.abs(got - want).max() < 1e-5 * want.max()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-9)
    ref = _reference()
    args = (scale, 16, datasets.GRAPH500_ABCD, seed, cfg.q, 10)
    r_ref, n_edges = ref.ranks(*args)
    assert n_edges == len(np.unique(src * V + dst))
    assert ref.l1_err(got, r_ref) < 1e-6
    assert ref.max_rel_err(got, r_ref) < 1e-5
    np.testing.assert_allclose(got.sum(), 1.0, rtol=1e-5)
    blocks = _reference("pagerank_sharded_ref")
    r_blocks, n_blocks = blocks.ranks(*args, max(shards, 2), pieces=4,
                                      room=3.0)
    assert n_blocks == n_edges
    assert ref.max_rel_err(got, r_blocks) < 1e-5
    r_low, _ = blocks.ranks(*args, max(shards, 2), pieces=4, room=3.0,
                            dtype=jnp.bfloat16)
    assert ref.l1_err(r_low, r_ref) > 1e-3       # the control is far


@pytest.mark.parametrize("shards,scale", [(4, 12), (8, 12), (4, 13),
                                          (8, 14)])
def test_sharded_loader_holds_every_edge_on_its_owner(mesh1, shards,
                                                      scale):
    """The shards' distinct edges together are the one-device loader's
    set exactly, every edge on the shard that owns its destination
    (among the shard's first ``shard_cap`` slots, the spare behind;
    the ranges whole tiles of 8 rows, cut where the drawn edges are:
    no range holds more than the mean and the tiles beside its cuts),
    and the out-degrees added over the shards are the one-device
    ones."""
    seed, V = 3_000_000_019, 1 << scale
    one = pagerank.build_rmat_graph(mesh1, scale, 16, None, seed)
    many = pagerank.build_rmat_graph(_mesh(shards), scale, 16, None, seed)
    geom = many.geom
    assert geom.n_shards == shards and many.n_edges == one.n_edges
    assert sum(many.shard_edges) == one.n_edges
    assert max(many.shard_edges) <= geom.shard_cap
    # cut to equal loads: within two mean tiles and twice the heaviest
    # vertex of the mean (a tile is 1024 vertices)
    owners = [x for x in many.shard_edges if x]
    room = (2 * 1024 * len(owners) / V
            + 2 * len(owners) * ppr.SPMV_SHARD_HUB ** scale)
    assert max(owners) < (1 + room) * one.n_edges / len(owners)
    src = np.asarray(many.src).reshape(shards, geom.shard_slots)
    dst = np.asarray(many.dst).reshape(shards, geom.shard_slots)
    assert not (src[:, geom.shard_cap:] >= 0).any()
    bounds = np.asarray(many.bounds)
    assert bounds[0] == 0 and bounds[-1] == geom.r8
    assert (np.diff(bounds) >= 0).all() and not (bounds % 8).any()
    assert np.diff(bounds).max() <= geom.rows_out
    codes = []
    for k in range(shards):
        real = src[k] >= 0
        assert real.sum() == many.shard_edges[k]
        rows = dst[k][real] >> 7
        assert ((rows >= bounds[k]) & (rows < bounds[k + 1])).all()
        codes.append(src[k][real].astype(np.int64) * V + dst[k][real])
    s1, d1 = np.asarray(one.src), np.asarray(one.dst)
    want = s1[s1 >= 0].astype(np.int64) * V + d1[s1 >= 0]
    np.testing.assert_array_equal(np.sort(np.concatenate(codes)), want)
    np.testing.assert_array_equal(np.asarray(many.inv_deg),
                                  np.asarray(one.inv_deg))
    np.testing.assert_array_equal(np.asarray(many.has_out),
                                  np.asarray(one.has_out))


@pytest.mark.parametrize("tiles,want", [
    ([5, 1, 1, 1, 0, 0, 8, 2], [0, 8, 32, 48, 64]),
    # empty tiles leave a cut open: the edge nearest an equal width
    ([9, 0, 0, 0, 0, 9, 0, 0, 0, 9, 0, 0, 9, 0, 0, 0],
     [0, 32, 64, 96, 128]),
    ([1] * 16, [0, 32, 64, 96, 128]),
    ([7], [0, 0, 0, 0, 8]),                  # one tile: one shard has it
])
def test_ranges_are_cut_where_the_edges_are(tiles, want):
    """``balanced_bounds``: whole tiles, every shard as near a quarter
    of the edges as a tile allows; NumPy and ``jax.numpy`` agree."""
    got = ppr.balanced_bounds(np, np.array(tiles), 4)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(ppr.balanced_bounds(
        jnp, jnp.asarray(tiles, jnp.int32), 4)), want)
    load = np.add.reduceat(np.array(tiles + [0]), got[:-1] // 8)
    if len(tiles) > 1:
        assert load.max() <= sum(tiles) / 4 + max(tiles)


def test_a_skewed_graph_sweeps_equal_loads(mesh4):
    """Destinations that crowd the head of the table (a quarter of the
    vertices receives 44% of the edges): equal-width ranges would hand
    one shard more than twice another's edges; the ranges are cut so
    that every shard holds a quarter to within a tile, unequal in
    width (as far as a shard's table has rows), and the sweep over
    them equals the XLA sweep."""
    V, e = 1 << 15, 400_000
    rng = np.random.default_rng(5)
    dst = np.where(rng.random(e) < 0.25, rng.integers(0, V // 4, e),
                   rng.integers(0, V, e))
    code = np.unique(rng.integers(0, V, e) * V + dst)
    edges = np.stack([code // V, code % V], 1)
    el = gops.prepare_edges(edges, V)
    graph = pagerank.device_graph(el, mesh4)
    cuts = np.asarray(graph.bounds)
    assert np.diff(cuts).min() < 0.5 * np.diff(cuts).max()
    assert max(graph.shard_edges) <= el.n_edges / 4 \
        + np.bincount(el.dst >> 10).max()          # a tile of 8 rows
    spmv = pagerank.prepare_device_spmv(graph, mesh4)
    assert spmv is not None and np.diff(cuts).max() <= spmv.rows_out
    de = pagerank.prepare_device_edges(el, mesh4, light=True)
    cfg = pagerank.PageRankConfig(n_iterations=6, mode="standard",
                                  scatter="spmv")
    fn = pagerank.make_run_fn(mesh4, cfg, V, None, spmv)
    got = np.asarray(fn(de.src, de.dst, de.w_e, de.emask, de.has_out,
                        de.n_ref)[0])
    want = _xla_ranks(edges, V, mesh4, 6)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-9)
    np.testing.assert_allclose(got.sum(), 1.0, rtol=1e-5)


def test_a_shard_past_its_capacity_fails_by_name(mesh4, tmp_path,
                                                 monkeypatch):
    """A range that draws more edges than a shard holds fails the load
    (``ShardOverflow``, ``pagerank_shard_overflow`` counts the edges
    that fit nowhere); a host graph with every destination in one
    range is refused its fused plan and counted the same way. No edge
    is dropped in silence."""
    import dataclasses

    real = ppr.spmv_geometry
    monkeypatch.setattr(ppr, "spmv_geometry", lambda *a: (
        lambda g: dataclasses.replace(g, bucket=g.bucket // 4))(real(*a)))
    sink = str(tmp_path / "tele")
    tevents.configure(sink)
    try:
        with pytest.raises(pagerank.ShardOverflow,
                           match="pagerank_shard_overflow"):
            pagerank.build_rmat_graph(mesh4, 12, 16, None, 7)
        over = tevents.get_sink().counters()["pagerank_shard_overflow"]
        assert over > 0
        monkeypatch.undo()
        sound = pagerank.build_rmat_graph(mesh4, 12, 16, None, 7)
        counters = tevents.get_sink().counters()
        assert counters["pagerank_shard_overflow"] == over   # + 0
        assert counters["pagerank_shard_edges_max"] == max(
            sound.shard_edges)
        assert counters["pagerank_shard_edges_mean"] == sound.n_edges // 4
        rng = np.random.default_rng(0)
        V = 1 << 14
        code = np.unique(rng.integers(0, V, 60_000) * V
                         + rng.integers(0, 1024, 60_000))
        el = gops.prepare_edges(np.stack([code // V, code % V], 1), V)
        assert pagerank.prepare_device_spmv(el, mesh4) is None
        counters = tevents.get_sink().counters()
        assert counters["pagerank_shard_overflow"] > over
        assert counters["spmv_plan_rejections"] == 1
    finally:
        tevents.configure(False)
    evts = report.load_events(sink)
    spans = [e["name"] for e in evts if e.get("ev") == "span_end"]
    assert spans.count("pagerank:exchange") == 2
    assert spans.count("pagerank:dedup") == 1    # not of the failed load


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


# (vertices, edges) -> (rg, groups, ws, slots) as PR 43 fixed them on
# one shard: what every test, chip_smoke and the one-chip cell run at
PINNED = {
    (1 << 24, 16 << 24): (512, 256, 224, 270581760),
    (1 << 21, 16 << 21): (128, 128, 120, 34603008),
    (1 << 20, 16 << 20): (128, 64, 72, 17301504),
    (1 << 12, 16 << 12): (32, 1, 16, 73728),
    (1 << 11, 16 << 11): (16, 1, 16, 40960),
    (1 << 10, 16 << 10): (8, 1, 16, 24576),
    (4096, 65536): (32, 1, 16, 73728),
    (50_000, 300_000): (136, 3, 24, 327680),
    (1_000_000, 1_000_000): (560, 14, 200, 1122304),
    (1 << 14, 40_000): (128, 1, 24, 49152),
}


@pytest.mark.parametrize("sizes", sorted(PINNED))
def test_one_shard_keeps_the_geometry_it_had(sizes):
    g = ppr.spmv_geometry(*sizes)
    assert (g.rg, g.n_groups, g.ws, g.n_slots) == PINNED[sizes]
    assert g.rows_out == g.r8 and g.shard_cap == sizes[1]
    assert g.ranks_out_form == "whole"


@pytest.mark.parametrize("scale,rg,groups,ws", [(26, 1024, 512, 440),
                                                (25, 512, 512, 440)])
def test_a_destination_range_follows_the_span_law(scale, rg, groups, ws):
    """A range of a larger graph is a sparser block: a chunk of a
    shard spans ``rows_out x groups x 1024 / edges a shard`` rows, the
    window is 1.6 x that + a tenth of it (16 rows where that is
    more), and the height is the one whose chunk costs least by the
    schedule law; the shard's table, not the whole
    one, has to fit VMEM."""
    V, E = 1 << scale, 16 << scale
    g = ppr.spmv_geometry(V, E, 4)
    assert (g.rg, g.n_groups, g.ws) == (rg, groups, ws)
    # a range cut where the edges are may be wider than a quarter
    assert g.r8 == V // 128 and g.r8 / 4 < g.rows_out < 1.04 * g.r8 / 4
    mean = g.r8 / 4 * g.chunk * g.n_groups / (E / 4)
    assert mean == 256 and g.ws == (int(1.6 * mean) + 25 + 7) // 8 * 8

    def bundles(x):
        return (ppr.SPMV_GATHER_ROW * x.rg + ppr.SPMV_SCATTER_ROW * x.ws)

    others = [ppr.spmv_geometry(V, E, 4, r) for r in ppr.SPMV_RGS]
    assert bundles(g) == min(bundles(x) for x in others)
    assert g.ws <= ppr.SPMV_WS_CAP < 2048
    # the mean load and the tiles beside a range's cuts, whole buckets
    assert 1.005 < g.shard_cap * 4 / E < 1.012 and g.shard_cap % 4 == 0
    assert g.shard_slots - g.shard_cap >= g.n_groups * g.step_slots
    if scale == 26:
        assert ppr.spmv_geometry(V, E, 1) is None
        assert ppr.shards_needed(V) == 4
        assert pagerank.resident_guard_trips(V, 2)
        assert not pagerank.resident_guard_trips(V, 4)


def test_geometry_is_a_function_of_the_sizes():
    g = ppr.spmv_geometry(1 << 24, 16 << 24)
    assert (g.rg, g.n_groups, g.ws, g.r8) == (512, 256, 224, 131072)
    assert g.ranks_form == "windowed" and g.n_steps % g.seg_steps == 0
    assert g.seg_steps <= ppr.SPMV_SEG_STEPS
    assert g.n_slots >= (16 << 24) + g.n_groups * g.step_slots
    # a sparser graph of as many vertices: taller groups, a wider
    # window (the block a quarter of SCALE 26 is)
    sparse = ppr.spmv_geometry(1 << 24, 4 << 24)
    assert (sparse.rg, sparse.ws) == (1024, 440)
    # the last group is never skinny: 49 tiles are 7 groups of 7
    g = ppr.spmv_geometry(50_000, 300_000, rg=32)
    assert (g.rg, g.n_groups) == (56, 7)
    # shards: whole segments each, a range's rows each
    g4 = ppr.spmv_geometry(1 << 20, 16 << 20, n_shards=4)
    assert g4.n_chunks % (4 * g4.blk) == 0
    assert g4.r8 // 4 < g4.rows_out < g4.r8 // 3
    assert g4.ranks_out_form == "range"
    assert ppr.spmv_geometry(40_000_000, 1 << 20) is None
    assert ppr.spmv_geometry(40_000_000, 1 << 20, 2) is not None


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
    # (the test process's backend is up with 8 devices, so --emulate 1
    # leaves the mesh at 8 data shards: the sweep is sharded by range)
    # (a shard's 48 chunks in one kernel call: all but the first overlap)
    assert ("ranks table: resident (rg 8, ws 16), written a shard's "
            "range, scatter passes 3, overlap step on 0.9792 of the "
            "chunks") in text
    evts = report.load_events(tel)
    prepare = [e for e in evts if e.get("ev") == "span_end"
               and e["name"] == "pagerank:prepare"][0]
    assert prepare["vertices"] == 1024 and prepare["generated"] == 16384
    assert 0 < prepare["distinct"] < 16384 and prepare["bytes"] > 0
    assert prepare["padding_share"] > 1
    seg = [e for e in evts if e.get("ev") == "span_start"
           and e["name"] == "train:segment"]
    assert seg and all(e["ranks_form"] == "resident" and e["rg"] == 8
                       and e["ranks_out_form"] == "range"
                       and e["scatter_passes"] == 3
                       and e["spmv_overlap"] == "step"
                       and e["overlapped_chunk_share"] == round(47 / 48, 6)
                       for e in seg)
    assert prepare["spmv_overlap"] == "step" \
        and prepare["overlapped_chunk_share"] == round(47 / 48, 6)
    assert prepare["ranks_out_form"] == "range" and prepare["shards"] == 8
    dedup = [e for e in evts if e.get("ev") == "span_end"
             and e["name"] == "pagerank:dedup"][0]
    assert len(dedup["shard_edges"]) == 8 and dedup["shard_capacity"] > 0
    assert sum(dedup["shard_edges"]) == prepare["distinct"]

