"""Pallas windowed one-hot-MXU scatter (ops/pallas_pagerank): the
standard-mode PageRank sweep's scatter half. Interpret mode on the CPU
mesh; the kernel path proper compiles on the chip in chip_smoke.py."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_distalg.models import pagerank
from tpu_distalg.ops import bf16_pieces
from tpu_distalg.ops import graph as gops
from tpu_distalg.ops import pallas_pagerank as ppr
from tpu_distalg.telemetry import events as tevents
from tpu_distalg.telemetry import report


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


def _spmv(plan, ranks, v, seg_steps=None, geom=None):
    """One interpreted sweep of a host plan over a ranks vector: a call
    a shard on its own slots (as ``shard_map`` hands them out), each
    writing the table of its destination range, the tables laid in
    the ranges' order where their ranges start, as the sweep lays
    them. ``geom`` stands in for the plan's own (a wider scatter
    window over the same slots)."""
    g = geom or plan.geom
    rt = np.zeros((g.n_groups * g.rg, 128), np.float32)
    rt.reshape(-1)[:v] = ranks
    per = g.n_steps * g.blk                     # chunks a shard
    out = np.zeros((g.r8 + g.rows_out, 128), np.float32)
    for k in range(g.n_shards):
        chunks = slice(k * per, (k + 1) * per)
        slots = slice(8 * k * per, 8 * (k + 1) * per)
        out[plan.bounds[k]:plan.bounds[k] + g.rows_out] = np.asarray(
            ppr.spmv_table(
            jnp.asarray(plan.gbase[chunks]), jnp.asarray(plan.sbase[chunks]),
            jnp.asarray(rt), *(jnp.asarray(a[slots]) for a in (
                plan.src_lane, plan.src_row, plan.dst_row, plan.dst_lane,
                plan.w_e)),
            rg=g.rg, ws=g.ws, r8=g.rows_out, blk=g.blk,
            seg_steps=seg_steps or g.seg_steps,
            interpret=True))[:g.rows_out]
    return out.reshape(-1)[:v]


# a scatter window's rows -> the gather group's rows and the edges that
# give a chunk about half that span over V vertices, every one with at
# most one in-edge (the span is rows x 1024 x groups / edges)
V = 32768
WINDOWS = {24: (256, V), 72: (128, V // 2), 224: (64, V // 4)}


@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("ws", sorted(WINDOWS))
def test_scatter_product_keeps_every_bit(ws, n_shards, monkeypatch):
    """The product that ships, interpreted: three single-pass products
    of the contribution's three exact pieces against the one-hot.
    Ranks a third and 2^-20 apart, weights one over odd degrees: every
    contribution has a full 24-bit significand. (a) Where every vertex
    has at most one in-edge the table IS the contributions, bit for
    bit; (b) with all edges on eight vertices (a star) a cell is
    NumPy's float32 sum of its thousand terms within 2^-22 of their sum
    (read: up to 1.3 x 2^-23, NumPy's own sum 0.75 x 2^-23 off the
    float64 one; two pieces would miss by 2^-17); (c) the control, the
    same helper fed two pieces (hi, mid: the hi/lo split the benchmark's
    configuration forbids), fails (a)."""
    rg, e = WINDOWS[ws]
    rng = np.random.default_rng(ws)
    src = rng.integers(0, V, size=e)
    w_e = (1.0 / (2 * rng.integers(1, 2000, size=e) + 1)).astype(np.float32)
    ranks = (1 / 3 + np.arange(V) * 2.0 ** -20).astype(np.float32)
    terms = ranks[src] * w_e
    assert (terms.view(np.uint32) & 0xFF).astype(bool).mean() > 0.98

    def sweep(dst):
        plan = ppr.plan_spmv(src, dst, w_e, V, n_shards=n_shards, rg=rg)
        assert plan is not None and int(plan.dst_row.max()) < ws
        assert plan.geom.n_groups == 256 // rg
        geom = dataclasses.replace(plan.geom, ws=ws)
        return _spmv(plan, ranks, V, geom=geom)

    once = rng.permutation(V)[:e]
    want = np.zeros(V, np.float32)
    want[once] = terms
    np.testing.assert_array_equal(sweep(once).view(np.uint32),
                                  want.view(np.uint32))

    # two hubs a destination range (a star on one range would pass
    # its shard's capacity, as it should)
    hubs = 128 * rng.integers(0, 2, size=8) + rng.permutation(128)[:8] \
        + np.arange(8) % n_shards * (V // n_shards)
    star = hubs[rng.integers(0, 8, size=e)]
    got = sweep(star)
    assert not got[np.setdiff1d(np.arange(V), hubs)].any()
    for hub in hubs:
        mine = terms[star == hub]
        assert len(mine) > 500
        assert abs(got[hub] - np.sum(mine, dtype=np.float32)) \
            <= 2.0 ** -22 * mine.sum(dtype=np.float64)

    real = ppr.split3
    monkeypatch.setattr(ppr, "split3", lambda x: real(x)[:2])
    jax.clear_caches()
    try:
        short = sweep(once)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    lost = short.view(np.uint32) != want.view(np.uint32)
    assert lost.sum() > 0.9 * e
    # what two pieces drop: up to 2^-16 of a contribution, never more
    assert np.abs(short - want).max() <= 2.0 ** -16 * want.max()


def _without_chunks(plan, dead):
    """``plan`` with the chunks ``dead`` emptied as the planner empties
    a chunk with no edge: base -1, weights and indices 0."""
    arrays = {n: getattr(plan, n).copy() for n in (
        "sbase", "src_lane", "src_row", "dst_row", "dst_lane", "w_e")}
    for c in dead:
        arrays["sbase"][c] = -1
        for n in ("src_lane", "src_row", "dst_row", "dst_lane", "w_e"):
            arrays[n][8 * c:8 * c + 8] = 0
    return dataclasses.replace(plan, **arrays)


def _plan_edges(plan):
    """The edges a plan holds, read back from its arrays: ``(src, dst,
    w_e)`` of every slot with a weight, in slot order."""
    g = plan.geom
    per = g.n_steps * g.blk
    row0 = np.repeat(np.asarray(plan.bounds[:-1]), per)
    held = plan.w_e.reshape(-1, 1024) != 0

    def slots(x):
        return x.reshape(-1, 1024).astype(np.int64)

    src = (plan.gbase[:, None] + slots(plan.src_row)) * 128 \
        + slots(plan.src_lane)
    dst = ((row0 + plan.sbase)[:, None] + slots(plan.dst_row)) * 128 \
        + slots(plan.dst_lane)
    return src[held], dst[held], plan.w_e.reshape(-1, 1024)[held]


# what the scatter's lag of one chunk can get wrong: (rg, vertices,
# edges, shards, kernel calls a sweep (0: the geometry's own, -1: a
# call a step), the chunks emptied: (which of a shard's steps that
# hold an edge, chunk of the step) pairs)
CARRIES = {
    "rg128": (128, 1 << 16, 60000, 1, 0, ()),
    "rg512": (512, 1 << 17, 40000, 1, 0, ()),
    "rg1024": (1024, 1 << 18, 60000, 1, 0, ()),
    "dead-chunk-first-of-a-step": (128, 1 << 16, 60000, 1, 0, ((2, 0),)),
    "dead-chunk-in-the-middle": (128, 1 << 16, 60000, 1, 0, ((2, 3),)),
    "dead-chunk-last-of-a-step": (128, 1 << 16, 60000, 1, 0, ((2, 7),)),
    "dead-step-between-live-ones": (
        128, 1 << 16, 60000, 1, 0, tuple((2, c) for c in range(8))),
    "a-call-a-step": (128, 1 << 16, 60000, 1, -1, ()),
    "three-calls-and-a-dead-first-chunk": (
        128, 1 << 16, 60000, 1, 3, ((4, 0),)),
    "two-shards": (128, 1 << 16, 60000, 2, 0, ((1, 7),)),
    "four-shards-a-call-a-step": (128, 1 << 16, 60000, 4, -1, ((1, 0),)),
}


@pytest.mark.parametrize("case", sorted(CARRIES))
def test_pipelined_chunk_loop_is_the_segment_sum(case):
    """The kernel scatters a chunk one turn after it gathered it, and
    holds it across grid steps; a call starts with nothing held and
    its last step scatters what is held. With every vertex given at
    most one in-edge the table IS ``ranks[src] * w_e`` at ``dst``, bit
    for bit (contributions of full 24-bit significands); with random
    destinations it is their sum to float32's noise. Over: three group
    heights (the interpreter rolls the gather a tile a turn at every
    one: the several-turn order); a chunk with no edge first, last and in the
    middle of a step and a step with none between two with some (the
    planner leaves such chunks only behind a group's edges: emptied
    here by hand); groups changing between steps; the plan's tail
    (steps with no edge, skipped whole); several calls a sweep, down
    to a call a step; two and four shards."""
    rg, v, e, n_shards, calls, dead = CARRIES[case]
    rng = np.random.default_rng(len(case) + e)
    src = rng.integers(0, v, size=e)
    w_e = (1.0 / (2 * rng.integers(1, 2000, size=e) + 1)).astype(np.float32)
    ranks = (1 / 3 + np.arange(v) * 2.0 ** -20).astype(np.float32)
    for dst in (rng.permutation(v)[:e], rng.integers(0, v, size=e)):
        plan = ppr.plan_spmv(src, dst, w_e, v, n_shards=n_shards, rg=rg)
        g = plan.geom
        assert g.rg == rg and g.n_groups >= 2
        per = g.n_steps * g.blk
        live = (plan.sbase.reshape(-1, g.blk) >= 0)
        assert live[:, 0].any() and not live.all(axis=1).all()  # a tail
        kill = [k * per + np.flatnonzero(mine[:, 0])[step] * g.blk + c
                for k, mine in enumerate(np.split(live, n_shards))
                for step, c in dead]
        assert all(plan.sbase[c] >= 0 for c in kill)
        lost = sum(np.count_nonzero(plan.w_e[8 * c:8 * c + 8])
                   for c in kill)
        assert lost >= len(kill)
        plan = _without_chunks(plan, kill)
        seg = {0: g.seg_steps, -1: 1}.get(calls, g.n_steps // max(calls, 1))
        assert g.n_steps % seg == 0 and (calls <= 0 or seg > 1)
        got = _spmv(plan, ranks, v, seg_steps=seg)
        s, d, w = _plan_edges(plan)
        assert len(s) == e - lost
        if len(np.unique(dst)) == e:
            want = np.zeros(v, np.float32)
            want[d] = ranks[s] * w
            np.testing.assert_array_equal(got.view(np.uint32),
                                          want.view(np.uint32))
        else:
            want = np.zeros(v, np.float64)
            np.add.at(want, d, ranks[s].astype(np.float64) * w)
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


def test_a_table_built_by_two_calls_is_the_table_built_by_one():
    """A sweep cut into kernel calls hands the table on through HBM
    and carries nothing else: the same chunks add in the same order,
    so one call, two and a call a step build one table, bit for bit
    (random destinations: cells of many terms)."""
    v, e = 1 << 16, 64000
    rng = np.random.default_rng(45)
    src, dst = rng.integers(0, v, size=e), rng.integers(0, v, size=e)
    plan = ppr.plan_spmv(src, dst, rng.random(e).astype(np.float32), v)
    steps = plan.geom.n_steps
    assert steps % 2 == 0 and plan.geom.n_groups >= 2
    ranks = rng.random(v).astype(np.float32)
    one = _spmv(plan, ranks, v, seg_steps=steps)
    assert np.count_nonzero(one) > 0.6 * v
    for seg in (steps // 2, 1):
        np.testing.assert_array_equal(
            _spmv(plan, ranks, v, seg_steps=seg).view(np.uint32),
            one.view(np.uint32))


@pytest.mark.parametrize("rg,blk,chunks,seg_steps,form,share", [
    (512, 8, 264240, 3670, "step", 1 - 9 / 264240),    # the resident cell
    (512, 8, 136440, 3411, "step", 1 - 5 / 136440),    # a shard of four
    (128, 8, 16, 0, "step", 1 - 1 / 16),               # one call
    (1024, 8, 268128, 3724, "step", 1 - 9 / 268128),   # 128 tiles a turn
    (2048, 8, 800, 50, "none", 0.0),                   # two turns
    (1040, 8, 800, 50, "none", 0.0),                   # 130 tiles: two turns
])
def test_overlap_fields_say_where_the_loop_is_pipelined(
        rg, blk, chunks, seg_steps, form, share):
    """The spans' ``spmv_overlap`` / ``overlapped_chunk_share``: the
    chunk loop overlaps a gather with a scatter where the gather is
    one turn (up to ``SPMV_UNROLL`` tiles: 1024 rows), on every chunk
    but the first of a kernel call."""
    got = ppr.overlap_fields(rg, blk, chunks, seg_steps)
    assert got["spmv_overlap"] == form == ppr.spmv_overlap(rg)
    assert got["overlapped_chunk_share"] == pytest.approx(share, abs=1e-6)


@pytest.mark.parametrize("kernel", ["spmv", "hybrid"])
def test_scatter_passes_is_what_the_kernels_pass(kernel, monkeypatch):
    """The spans' ``scatter_passes`` tag (``SCATTER_PASSES``) is the
    number of pieces each kernel hands ``scatter_window``: one bf16 pass
    a piece."""
    seen = []
    real = ppr.scatter_window

    def counting(pieces, *args):
        seen.append(len(pieces))
        return real(pieces, *args)

    monkeypatch.setattr(ppr, "scatter_window", counting)
    jax.clear_caches()
    rng = np.random.default_rng(5)
    v, e = 2048, 8192
    dst = np.sort(rng.integers(0, v, size=e).astype(np.int32))
    try:
        if kernel == "spmv":
            plan = ppr.plan_spmv(rng.integers(0, v, size=e), dst,
                                 np.ones(e, np.float32), v)
            _spmv(plan, np.ones(v, np.float32), v)
        else:
            plan = ppr.plan_scatter(dst, v, chunk=128, blk=4)
            ppr.scatter_table(
                jnp.asarray(plan.base), jnp.ones(plan.row.shape),
                jnp.asarray(plan.row), jnp.asarray(plan.lane),
                w=plan.w, r8=plan.r8, blk=plan.blk, interpret=True)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert seen and set(seen) == {ppr.SCATTER_PASSES}


@pytest.mark.parametrize("what,lo,hi", [
    ("denormals and the smallest normals", -149, -102),
    ("one over a degree", -17, 0),        # w_e: 1 to 1 / 97 455
    ("rank x weight", -38, -10)])         # the Graph500 cell's range
def test_split3_adds_back_bit_for_bit(what, lo, hi):
    """Every piece is a bfloat16 (low half zero), and hi + mid + lo is
    the float32 again, bit for bit, in any order of float32 additions
    (the MXU's is its own): over ``w_e``'s range and the contributions'
    (the largest rank x weight of SCALE 24 is 12 050 / 2^24 x 1). Under
    2^-102 a piece can be a denormal, which XLA:CPU and the TPU flush:
    the pieces then fall short of x by less than 2^-126 and never pass
    it (``Precision.HIGHEST`` splits the same way)."""
    rng = np.random.default_rng(hi - lo)
    mant = rng.integers(1, 1 << 24, size=(8, 128)) | 1
    x = np.ldexp(mant.astype(np.float64) / (1 << 23),
                 rng.integers(lo, hi, size=(8, 128))).astype(np.float32)
    x[0, :4] = 0.0, 2.0 ** lo, 2.0 ** (hi - 1), 12050 / 2.0 ** 24
    pieces = [np.asarray(p) for p in
              jax.jit(bf16_pieces.split3)(jnp.asarray(x))]
    for piece in pieces:
        assert not (piece.view(np.uint32) & 0xFFFF).any()
    back = sum(p.astype(np.float64) for p in pieces)   # exact in float64
    if hi <= -102:
        assert ((0 <= x - back) & (x - back < 2.0 ** -126)).all()
        return
    np.testing.assert_array_equal(
        back.astype(np.float32).view(np.uint32), x.view(np.uint32))
    first, mid, last = pieces
    for a, b, c in ((first, mid, last), (last, mid, first),
                    (first, last, mid)):
        np.testing.assert_array_equal(
            ((a + b) + c).view(np.uint32), x.view(np.uint32))


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
    """A graph whose within-group dst span is wide at rg=128 (the span
    grows as R²/(rg·E)) is given a taller gather window from its sizes
    alone, the height whose chunk costs least by the schedule law: the
    10M-vertex regime in miniature. A forced rg=128 pays for its short
    groups with a window four times as wide. Plan invariants are
    checked; the tall kernel's numerics are verified on hardware."""
    v, e = 1_000_000, 1_000_000
    edges = _random_graph(v, e, seed=7)
    el = gops.prepare_edges(edges, v)
    spmv = pagerank.prepare_device_spmv(el, mesh8)
    assert spmv is not None
    assert spmv.rg > 128 and spmv.ranks_out_form == "range"
    assert spmv.ws <= ppr.SPMV_WS_CAP
    short = pagerank.prepare_device_spmv(el, mesh8, rg=128)
    assert short is not None and short.ws > 3 * spmv.ws
    # window-relative indices must honor the planned windows
    assert int(np.asarray(spmv.src_row).max()) < spmv.rg
    assert int(np.asarray(spmv.dst_row).max()) < spmv.ws
    assert int(np.asarray(spmv.sbase).max()) < spmv.rows_out


def test_spmv_without_plan_raises(mesh8):
    cfg = pagerank.PageRankConfig(mode="standard", scatter="spmv")
    with pytest.raises(ValueError, match="spmv"):
        pagerank.make_run_fn(mesh8, cfg, 64, None, None)


def test_scatter_pallas_without_plan_raises(mesh8):
    cfg = pagerank.PageRankConfig(mode="standard", scatter="pallas")
    with pytest.raises(ValueError, match="scatter plan"):
        pagerank.make_run_fn(mesh8, cfg, 64, None)


@pytest.mark.parametrize("mode,scatter,fused,hybrid,want", [
    ("reference", "auto", False, False, "reference"),
    ("reference", "auto", True, True, "reference"),
    # 'auto': fused, else the hybrid, else XLA
    ("standard", "auto", True, True, "fused"),
    ("standard", "auto", True, False, "fused"),
    ("standard", "auto", False, True, "hybrid"),
    ("standard", "auto", False, False, "xla"),
    # a named sweep is that sweep, whatever other plan came
    ("standard", "spmv", True, True, "fused"),
    ("standard", "pallas", True, True, "hybrid"),
    ("standard", "xla", True, True, "xla"),
    ("standard", "xla", False, False, "xla"),
    # and without its plan names the remedy
    ("standard", "spmv", False, True, "prepare_device_spmv"),
    ("standard", "pallas", True, False, "scatter plan"),
    # the reference sweep takes no scatter but 'auto'
    ("reference", "spmv", True, True, "only applies to mode='standard'"),
    ("reference", "xla", False, False, "only applies to mode='standard'"),
    ("standard", "mxu", True, True, "unknown scatter mode 'mxu'"),
])
def test_sweep_form_is_the_whole_rule(mode, scatter, fused, hybrid, want):
    """``sweep_form`` names the sweep for every (mode, scatter, which
    plans exist), and raises where no sweep answers."""
    cfg = pagerank.PageRankConfig(mode=mode, scatter=scatter)
    if want in ("reference", "fused", "hybrid", "xla"):
        assert pagerank.sweep_form(cfg, fused, hybrid) == want
    else:
        with pytest.raises(ValueError, match=want):
            pagerank.sweep_form(cfg, fused, hybrid)


def test_auto_on_a_sparse_graph_is_the_hybrid_sweep_and_says_so(
        mesh8, tmp_path, monkeypatch):
    """A graph too sparse for the fused window whose 1024
    destination-sorted edges still span under 32 rows: ``'auto'``
    ranks it by the hybrid sweep, bit for bit as ``scatter='pallas'``
    does and as the XLA sweep does to float32 noise, and the fused
    plan's refusal is counted and named (one v5e reads this regime
    2.1x ahead of XLA: PERF.md, PR 43). With the heights and the
    window cap that ship (PR 44) the regime starts past 14M vertices;
    the caps of PR 43 bring it to a size the interpreter runs: 2^20
    vertices, 400k edges, sixteen groups at rg 512, a chunk's mean
    span 335 rows."""
    monkeypatch.setattr(ppr, "SPMV_RGS", (128, 256, 512))
    monkeypatch.setattr(ppr, "SPMV_WS_CAP", 256)
    v, e = 1 << 20, 400_000
    edges = _random_graph(v, e, seed=3)
    cfg = pagerank.PageRankConfig(n_iterations=4, mode="standard")
    sink = str(tmp_path / "tele")
    tevents.configure(sink)
    try:
        auto = pagerank.run(edges, mesh8, cfg, v)
    finally:
        tevents.configure(False)
    evts = report.load_events(sink)
    assert report.summarize(evts)["counters"]["spmv_plan_rejections"] >= 1
    rejected = [x for x in evts if x.get("ev") == "spmv_span_rejected"]
    assert rejected and rejected[0]["span"] > rejected[0]["ws"]
    el = gops.prepare_edges(edges, v)
    assert pagerank.prepare_device_edges(el, mesh8).plan is not None
    hybrid, xla = (np.asarray(pagerank.run(
        edges, mesh8, dataclasses.replace(cfg, scatter=sc), v).ranks)
        for sc in ("pallas", "xla"))
    np.testing.assert_array_equal(
        np.asarray(auto.ranks).view(np.uint32), hybrid.view(np.uint32))
    np.testing.assert_allclose(hybrid, xla, rtol=1e-5, atol=1e-10)


@pytest.mark.parametrize("n_shards", [1, 4, 8])
def test_device_edges_without_a_plan_are_sorted_with_inert_padding(
        n_shards):
    """``prepare_device_edges``' form where no plan is made: every
    shard's slice sorted by destination, the padding ``dst = V-1`` with
    zero weight and mask behind every edge."""
    from tpu_distalg.parallel import get_mesh

    mesh = get_mesh(data=n_shards, devices=jax.devices()[:n_shards])
    v = 1000
    el = gops.prepare_edges(_random_graph(v, 8003, seed=n_shards), v)
    de = pagerank.prepare_device_edges(el, mesh, build_plan=False)
    assert de.plan is None and de.spmv is None
    dst, w_e, emask = (np.asarray(a) for a in (de.dst, de.w_e, de.emask))
    assert len(dst) % n_shards == 0 and len(dst) - el.n_edges < n_shards
    for part in np.split(dst, n_shards):
        assert (np.diff(part) >= 0).all()
    assert (emask[:el.n_edges] == 1).all() and (w_e[:el.n_edges] > 0).all()
    assert (dst[el.n_edges:] == v - 1).all()
    assert not w_e[el.n_edges:].any() and not emask[el.n_edges:].any()
    np.testing.assert_array_equal(np.sort(el.dst), dst[:el.n_edges])


def test_run_auto_falls_back_when_no_plan(mesh8):
    """run() on a graph too small for any plan still works (XLA path)."""
    edges = _random_graph(64, 256, seed=3)
    res = pagerank.run(edges, mesh8,
                       pagerank.PageRankConfig(n_iterations=4,
                                               mode="standard"))
    r = np.asarray(res.ranks)
    assert np.isfinite(r).all()
    np.testing.assert_allclose(r.sum(), 1.0, rtol=1e-4)


def test_pagerank_reference_mode_rejects_scatter_flag(mesh8):
    from tpu_distalg.models import pagerank

    cfg = pagerank.PageRankConfig(mode="reference", scatter="pallas")
    with pytest.raises(ValueError, match="standard"):
        pagerank.make_run_fn(mesh8, cfg, 64, None)
