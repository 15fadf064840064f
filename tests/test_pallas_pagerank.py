"""Pallas windowed one-hot-MXU scatter (ops/pallas_pagerank): the
standard-mode PageRank sweep's scatter half. Interpret mode on the CPU
mesh; the kernel path proper compiles on the chip in chip_smoke.py."""

import numpy as np
import pytest

import jax.numpy as jnp

from tpu_distalg.models import pagerank
from tpu_distalg.ops import graph as gops
from tpu_distalg.ops import pallas_pagerank as ppr


def _random_graph(v, e, seed):
    rng = np.random.default_rng(seed)
    return np.stack(
        [rng.integers(0, v, size=e), rng.integers(0, v, size=e)],
        axis=1).astype(np.int64)


def test_plan_and_scatter_match_numpy():
    """Single-shard plan + kernel (interpret) equals np.add.at."""
    v, e = 2048, 16384
    rng = np.random.default_rng(0)
    dst = np.sort(rng.integers(0, v, size=e).astype(np.int32))
    contrib = rng.random(e).astype(np.float32)
    plan = ppr.plan_scatter(dst, v, n_shards=1, chunk=128, blk=4)
    assert plan is not None
    c_pad = np.zeros(plan.n_chunks * 128, np.float32)
    c_pad[:e] = contrib
    out = ppr.scatter_table(
        jnp.asarray(plan.base), jnp.asarray(c_pad.reshape(-1, 128)),
        jnp.asarray(plan.row), jnp.asarray(plan.lane),
        w=plan.w, r8=plan.r8, blk=plan.blk, interpret=True)
    want = np.zeros(v, np.float64)
    np.add.at(want, dst, contrib.astype(np.float64))
    got = np.asarray(out)[:plan.r8].reshape(-1)[:v]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


def test_plan_rejects_sparse_and_tiny_graphs():
    """Very sparse graphs (chunk spans too many table rows) and graphs
    smaller than the grid granularity fall back to the XLA path."""
    rng = np.random.default_rng(1)
    # 1024 edges spread over 2^20 vertices: one 128-chunk spans far
    # beyond MAX_W vregs
    dst = np.sort(rng.integers(0, 1 << 20, size=4096).astype(np.int32))
    assert ppr.plan_scatter(dst, 1 << 20, chunk=128, blk=4) is None
    # tiny graph: padding would exceed 2x the real edges
    dst = np.sort(rng.integers(0, 64, size=100).astype(np.int32))
    assert ppr.plan_scatter(dst, 64, chunk=1024, blk=32) is None


def test_standard_mode_pallas_matches_xla(mesh8):
    """The hybrid sweep (XLA gather + Pallas scatter) and the XLA-only
    sweep agree on the final ranks across 8 shards."""
    v, e = 1024, 16384
    edges = _random_graph(v, e, seed=2)
    el = gops.prepare_edges(edges, v)
    de = pagerank.prepare_device_edges(el, mesh8, plan_chunk=128,
                                       plan_blk=2)
    assert de.plan is not None, "test graph should admit a plan"
    outs = {}
    for scatter in ("pallas", "xla"):
        cfg = pagerank.PageRankConfig(n_iterations=8, mode="standard",
                                      scatter=scatter)
        fn = pagerank.make_run_fn(mesh8, cfg, de.n_vertices,
                                  de.plan if scatter == "pallas" else None)
        ranks, _ = fn(de.src, de.dst, de.w_e, de.emask, de.has_out,
                      de.n_ref)
        outs[scatter] = np.asarray(ranks)
    assert np.isfinite(outs["pallas"]).all()
    np.testing.assert_allclose(outs["pallas"], outs["xla"],
                               rtol=1e-5, atol=1e-8)
    # mass is conserved in standard mode
    np.testing.assert_allclose(outs["pallas"].sum(), 1.0, rtol=1e-4)


def test_spmv_plan_and_kernel_match_numpy():
    """Single-shard fused-SpMV plan + kernel (interpret) equals the
    dense numpy SpMV ranks[src]·w scatter-added by dst."""
    v, e = 50000, 300000
    rng = np.random.default_rng(4)
    src = rng.integers(0, v, size=e)
    dst = rng.integers(0, v, size=e)
    w_e = rng.random(e).astype(np.float32)
    ranks = rng.random(v).astype(np.float32)
    plan = ppr.plan_spmv(src, dst, w_e, v)
    assert plan is not None and plan.geom.n_groups > 1
    got = _spmv(plan, ranks, v)
    want = np.zeros(v, np.float64)
    np.add.at(want, dst, ranks[src].astype(np.float64) * w_e)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


def _spmv(plan, ranks, v, seg_steps=None):
    """One interpreted sweep of a host plan over a ranks vector."""
    g = plan.geom
    rt = np.zeros((g.n_groups * g.rg, 128), np.float32)
    rt.reshape(-1)[:v] = ranks
    out = ppr.spmv_table(
        *(jnp.asarray(a) for a in (
            plan.gbase, plan.sbase, rt, plan.src_lane, plan.src_row,
            plan.dst_row, plan.dst_lane, plan.w_e)),
        rg=g.rg, ws=g.ws, r8=g.r8, blk=g.blk,
        seg_steps=seg_steps or g.seg_steps, interpret=True)
    return np.asarray(out)[:g.r8].reshape(-1)[:v]


def test_windowed_ranks_equal_the_resident_form_bit_for_bit():
    """The ranks table read a source group's window at a time (a group
    height small enough to force several at test size) returns what the
    one-group form, the whole table one window, returns, bit for bit:
    small whole numbers, so that no order of the sums rounds. Several
    kernel calls a sweep (the scalars' segments) change nothing."""
    v, e = 20000, 120000
    rng = np.random.default_rng(8)
    src, dst = rng.integers(0, v, size=e), rng.integers(0, v, size=e)
    ranks = rng.integers(1, 8, size=v).astype(np.float32)
    w_e = np.ones(e, np.float32)
    resident = ppr.plan_spmv(src, dst, w_e, v, rg=1024)
    windowed = ppr.plan_spmv(src, dst, w_e, v, rg=32)
    assert resident.geom.ranks_form == "resident"
    assert windowed.geom.ranks_form == "windowed"
    assert windowed.geom.n_groups >= 4
    want = np.zeros(v, np.float64)
    np.add.at(want, dst, ranks[src])
    got = _spmv(resident, ranks, v)
    np.testing.assert_array_equal(got, want.astype(np.float32))
    np.testing.assert_array_equal(_spmv(windowed, ranks, v), got)
    steps = windowed.geom.n_steps
    seg = next(d for d in range(2, steps) if steps % d == 0)
    np.testing.assert_array_equal(
        _spmv(windowed, ranks, v, seg_steps=seg), got)


def test_standard_mode_spmv_matches_xla(mesh8):
    """The fused Path E sweep and the XLA-only sweep agree on final
    ranks across 8 shards (sharded chunk blocks + psum)."""
    v, e = 4096, 65536
    edges = _random_graph(v, e, seed=5)
    el = gops.prepare_edges(edges, v)
    de = pagerank.prepare_device_edges(el, mesh8, build_plan=False)
    spmv = pagerank.prepare_device_spmv(el, mesh8)
    assert spmv is not None, "test graph should admit a spmv plan"
    cfg = pagerank.PageRankConfig(n_iterations=8, mode="standard",
                                  scatter="spmv")
    fn = pagerank.make_run_fn(mesh8, cfg, de.n_vertices, None, spmv)
    ranks, _ = fn(de.src, de.dst, de.w_e, de.emask, de.has_out,
                  de.n_ref)
    fn_x = pagerank.make_run_fn(
        mesh8, pagerank.PageRankConfig(n_iterations=8, mode="standard",
                                       scatter="xla"), de.n_vertices)
    ranks_x, _ = fn_x(de.src, de.dst, de.w_e, de.emask, de.has_out,
                      de.n_ref)
    np.testing.assert_allclose(np.asarray(ranks), np.asarray(ranks_x),
                               rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(np.asarray(ranks).sum(), 1.0, rtol=1e-4)


def test_run_auto_prefers_spmv_and_matches_xla(mesh8):
    """'auto' on a spmv-capable graph takes Path E end-to-end and
    agrees with the forced-XLA sweep."""
    v, e = 4096, 65536
    edges = _random_graph(v, e, seed=6)
    # guard against vacuous passing: the graph must actually admit the
    # spmv plan, else 'auto' silently falls back and this compares the
    # fallback against itself
    assert pagerank.prepare_device_spmv(
        gops.prepare_edges(edges, v), mesh8) is not None
    auto = pagerank.run(edges, mesh8,
                        pagerank.PageRankConfig(n_iterations=6,
                                                mode="standard"))
    xla = pagerank.run(edges, mesh8,
                       pagerank.PageRankConfig(n_iterations=6,
                                               mode="standard",
                                               scatter="xla"))
    np.testing.assert_allclose(np.asarray(auto.ranks),
                               np.asarray(xla.ranks),
                               rtol=1e-5, atol=1e-8)


def test_spmv_sparse_graph_takes_a_taller_gather_window(mesh8):
    """A graph whose within-group dst span overflows at rg=128 (the
    span grows as R²/(rg·E)) is given a taller gather window from its
    sizes alone, with no attempt at 128 — the 10M-vertex regime in
    miniature. A span past the window fixed for a forced rg=128 is
    reported (``None``, counted), not hidden. Plan invariants are
    checked; the tall kernel's numerics are verified on hardware."""
    v, e = 1_000_000, 1_000_000
    edges = _random_graph(v, e, seed=7)
    el = gops.prepare_edges(edges, v)
    # rg=128 must fail on this sparsity...
    assert pagerank.prepare_device_spmv(el, mesh8, rg=128) is None
    # ...and the geometry the sizes give must land a valid taller plan
    spmv = pagerank.prepare_device_spmv(el, mesh8)
    assert spmv is not None
    assert spmv.rg > 128
    assert spmv.ws <= ppr.SPMV_WS_CAP
    # window-relative indices must honor the planned windows
    assert int(np.asarray(spmv.src_row).max()) < spmv.rg
    assert int(np.asarray(spmv.dst_row).max()) < spmv.ws


def test_spmv_without_plan_raises(mesh8):
    cfg = pagerank.PageRankConfig(mode="standard", scatter="spmv")
    with pytest.raises(ValueError, match="spmv"):
        pagerank.make_run_fn(mesh8, cfg, 64, None, None)


def test_scatter_pallas_without_plan_raises(mesh8):
    cfg = pagerank.PageRankConfig(mode="standard", scatter="pallas")
    with pytest.raises(ValueError, match="scatter plan"):
        pagerank.make_run_fn(mesh8, cfg, 64, None)


def test_run_auto_falls_back_when_no_plan(mesh8):
    """run() on a graph too small for any plan still works (XLA path)."""
    edges = _random_graph(64, 256, seed=3)
    res = pagerank.run(edges, mesh8,
                       pagerank.PageRankConfig(n_iterations=4,
                                               mode="standard"))
    r = np.asarray(res.ranks)
    assert np.isfinite(r).all()
    np.testing.assert_allclose(r.sum(), 1.0, rtol=1e-4)
