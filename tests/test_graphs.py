"""The out-of-core graph engine (tpu_distalg/graphs/): the CSR
edge-block cache format (header/version round-trip, legacy flat-meta
reopen, dst-sortedness + inert padding, native-vs-NumPy byte
identity), the streamed frontier sweep (streamed == virtual ==
resident placement bitwise equality, agreement with the resident
models/pagerank path, segmented bitwise resume), the sparse rank
combine (determinism, replicated-identical output across shards,
wire-byte accounting + telemetry rendering), fault-seam coverage via
the pagerank_stream chaos workload, and the capability handling for a
stale/absent libtda_ingest.so."""

import json
import os

import numpy as np
import pytest

from tpu_distalg import graphs, native
from tpu_distalg.data import cache as dcache
from tpu_distalg.graphs import engine, ingest

N_SHARDS = 4


def _powerlaw(tmp_path, name="pl", n_vertices=512, block_edges=64,
              **kw):
    path = str(tmp_path / name)
    kw.setdefault("avg_in_degree", 8.0)
    kw.setdefault("alpha", 1.6)
    kw.setdefault("seed", 3)
    mm, header = graphs.build_powerlaw_block_cache(
        path, n_vertices=n_vertices, n_shards=N_SHARDS,
        block_edges=block_edges, **kw)
    return path, mm, header


# ------------------------------------------------------- cache format

def test_powerlaw_cache_roundtrip_and_reopen(tmp_path):
    path, mm, header = _powerlaw(tmp_path)
    geom = header["geom"]
    assert geom["bv"] == ingest.BLOCK_FORMAT_VERSION
    assert header["layout"] == ingest.LAYOUT
    # reopen with the same generation parameters is O(ms), identical
    mm2, header2 = graphs.build_powerlaw_block_cache(
        str(tmp_path / "pl"), n_vertices=512, n_shards=N_SHARDS,
        block_edges=64, avg_in_degree=8.0, alpha=1.6, seed=3)
    assert header2 == header
    np.testing.assert_array_equal(np.asarray(mm), np.asarray(mm2))
    # different generation parameters at the same path fail loudly
    with pytest.raises(ValueError, match="built with"):
        graphs.build_powerlaw_block_cache(
            str(tmp_path / "pl"), n_vertices=512, n_shards=N_SHARDS,
            block_edges=64, avg_in_degree=8.0, alpha=1.6, seed=4)


def test_cache_rows_dst_sorted_padding_inert(tmp_path):
    path, mm, header = _powerlaw(tmp_path)
    geom = header["geom"]
    rows = np.asarray(mm)
    E = int(geom["n_edges"])
    dst = rows[:, 1]
    assert np.all(np.diff(dst) >= 0), "rows must be globally dst-sorted"
    # padding rows: zero-weight (inert in the sweep), replicating the
    # last REAL destination so the final shard window stays tight
    assert np.all(rows[E:, 2] == 0)
    assert np.all(rows[E:, 1] == dst[E - 1])
    w = rows[:E, 2].view(np.float32)
    assert np.all(w > 0)
    # per-shard destination windows cover each shard's rows
    L = rows.shape[0] // N_SHARDS
    for s, lo in enumerate(geom["lo"]):
        d = rows[s * L:(s + 1) * L, 1]
        assert d.min() >= lo
        assert d.max() - lo < geom["window"]


def test_block_format_version_rejected(tmp_path, mesh4):
    path, _, header = _powerlaw(tmp_path)
    hdr = dcache.read_header(path)
    hdr["geom"]["bv"] = 99
    with open(dcache.meta_path(path), "w") as f:
        json.dump(hdr, f)
    with pytest.raises(ValueError, match="re-ingest"):
        graphs.open_graph_dataset(path, mesh4)


def test_shard_count_mismatch_rejected(tmp_path, mesh8):
    path, _, _ = _powerlaw(tmp_path)  # ingested for 4 shards
    with pytest.raises(ValueError, match="re-ingest"):
        graphs.open_graph_dataset(path, mesh8)


def test_legacy_flat_meta_reopen_sweeps_identically(tmp_path, mesh4):
    path, _, header = _powerlaw(tmp_path)
    cfg = graphs.StreamedPageRankConfig(n_iterations=3)
    gd = graphs.open_graph_dataset(path, mesh4)
    ref = np.asarray(graphs.run_streamed_pagerank(gd, cfg).ranks)
    # rewrite the header as the pre-versioned flat geometry dict — the
    # legacy style open_cache extends the same courtesy to
    geom = header["geom"]
    with open(dcache.meta_path(path), "w") as f:
        json.dump(geom, f)
    gd2 = graphs.open_graph_dataset(path, mesh4, legacy_geom=geom)
    out = np.asarray(graphs.run_streamed_pagerank(gd2, cfg).ranks)
    np.testing.assert_array_equal(out, ref)


def test_missing_aux_payload_names_remedy(tmp_path, mesh4):
    path, _, _ = _powerlaw(tmp_path)
    os.remove(dcache.aux_path(path, ingest.AUX_DIDX))
    with pytest.raises(FileNotFoundError, match="re-ingest"):
        graphs.open_graph_dataset(path, mesh4)


def test_edge_cache_matches_prepared_edges(tmp_path):
    rng = np.random.default_rng(7)
    edges = np.stack([rng.integers(0, 100, 500),
                      rng.integers(0, 100, 500)], 1).astype(np.int64)
    path = str(tmp_path / "e")
    mm, header = graphs.build_edge_block_cache(
        edges, path, n_shards=N_SHARDS, block_edges=16, n_vertices=100)
    geom = header["geom"]
    from tpu_distalg.ops import graph as gops

    el = gops.prepare_edges(edges, 100)
    assert geom["n_edges"] == el.n_edges  # deduped count
    rows = np.asarray(mm)[:el.n_edges]
    # every (src, dst) pair present exactly once, weight 1/out_deg[src]
    got = set(zip(rows[:, 0].tolist(), rows[:, 1].tolist()))
    want = set(zip(el.src.tolist(), el.dst.tolist()))
    assert got == want
    w = rows[:, 2].view(np.float32)
    np.testing.assert_array_equal(
        w, (1.0 / el.out_degree[rows[:, 0]]).astype(np.float32))


# --------------------------------------- native capability / fallback

def test_ingest_native_and_numpy_byte_identical(tmp_path, monkeypatch):
    if not native.available():
        pytest.skip("native library unavailable — only the fallback "
                    "path exists here")
    rng = np.random.default_rng(5)
    edges = np.stack([rng.integers(0, 200, 800),
                      rng.integers(0, 200, 800)], 1).astype(np.int64)
    mm_n, h_n = graphs.build_edge_block_cache(
        edges, str(tmp_path / "native"), n_shards=N_SHARDS,
        block_edges=32, n_vertices=200)
    bytes_native = np.asarray(mm_n).tobytes()
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_attempted", True)
    assert not native.available()
    mm_p, h_p = graphs.build_edge_block_cache(
        edges, str(tmp_path / "numpy"), n_shards=N_SHARDS,
        block_edges=32, n_vertices=200)
    assert h_p["geom"] == h_n["geom"]
    assert np.asarray(mm_p).tobytes() == bytes_native
    for name in (ingest.AUX_DEG, ingest.AUX_DIDX, ingest.AUX_DMASK):
        with open(dcache.aux_path(str(tmp_path / "native"), name),
                  "rb") as f:
            a = f.read()
        with open(dcache.aux_path(str(tmp_path / "numpy"), name),
                  "rb") as f:
            b = f.read()
        assert a == b, name


def test_pack_edge_rows_native_matches_numpy():
    if not native.available():
        pytest.skip("native library unavailable — native path not "
                    "present")
    rng = np.random.default_rng(11)
    src = rng.integers(0, 1 << 20, 4097).astype(np.int64)
    dst = rng.integers(0, 1 << 20, 4097).astype(np.int64)
    w = rng.random(4097).astype(np.float32)
    nat = native.pack_edge_rows(src, dst, w)
    ref = np.empty((4097, 3), np.int32)
    ref[:, 0] = src.astype(np.int32)
    ref[:, 1] = dst.astype(np.int32)
    ref[:, 2] = w.view(np.int32)
    np.testing.assert_array_equal(nat, ref)


# ------------------------------------------------------- sweep engine

def test_streamed_virtual_resident_bitwise_equal(tmp_path, mesh4):
    path, _, _ = _powerlaw(tmp_path)
    cfg = graphs.StreamedPageRankConfig(n_iterations=5)
    ranks = {}
    for backend in ("streamed", "virtual", "resident"):
        gd = graphs.open_graph_dataset(path, mesh4, backend=backend)
        ranks[backend] = np.asarray(
            graphs.run_streamed_pagerank(gd, cfg).ranks)
    np.testing.assert_array_equal(ranks["streamed"], ranks["virtual"])
    np.testing.assert_array_equal(ranks["streamed"], ranks["resident"])
    np.testing.assert_allclose(ranks["streamed"].sum(), 1.0, atol=1e-5)


def test_streamed_agrees_with_resident_model(tmp_path, mesh4):
    """The engine vs models/pagerank.py standard mode on the SAME
    (deduped) graph: the resident path accumulates each destination in
    one segment_sum pass while the engine sums blocked partials through
    the sparse combine, so exact bits differ by float association; the
    trajectories must still agree to f32 round-off."""
    rng = np.random.default_rng(0)
    E, V = 2000, 300
    edges = np.stack([rng.integers(0, V, E),
                      rng.integers(0, V, E)], 1).astype(np.int64)
    path = str(tmp_path / "e")
    graphs.build_edge_block_cache(edges, path, n_shards=N_SHARDS,
                                  block_edges=64, n_vertices=V)
    gd = graphs.open_graph_dataset(path, mesh4, backend="streamed")
    got = np.asarray(graphs.run_streamed_pagerank(
        gd, graphs.StreamedPageRankConfig(n_iterations=10)).ranks)

    from tpu_distalg.models import pagerank as m

    ref = m.run(edges, mesh4,
                m.PageRankConfig(n_iterations=10, mode="standard"))
    np.testing.assert_allclose(got, np.asarray(ref.ranks), atol=1e-6)


def test_sparse_and_dense_combine_agree(tmp_path, mesh4):
    path, _, _ = _powerlaw(tmp_path)
    outs = {}
    for combine in ("sparse", "dense"):
        gd = graphs.open_graph_dataset(path, mesh4)
        res = graphs.run_streamed_pagerank(
            gd, graphs.StreamedPageRankConfig(n_iterations=4,
                                              combine=combine))
        assert res.combine == combine
        outs[combine] = np.asarray(res.ranks)
    np.testing.assert_allclose(outs["sparse"], outs["dense"],
                               atol=1e-6)


def test_sparse_combine_deterministic_and_replicated(tmp_path, mesh4):
    path, _, _ = _powerlaw(tmp_path)
    cfg = graphs.StreamedPageRankConfig(n_iterations=4,
                                        combine="sparse")
    gd = graphs.open_graph_dataset(path, mesh4)
    a = np.asarray(graphs.run_streamed_pagerank(gd, cfg).ranks)
    b = np.asarray(graphs.run_streamed_pagerank(gd, cfg).ranks)
    np.testing.assert_array_equal(a, b)

    # per-shard outputs of the combine itself are bitwise-identical
    # (origin-order accumulation — the replicated contract psum gives
    # for free, earned without psum)
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_distalg.parallel import comms, data_parallel

    V = gd.n_vertices
    vals = jnp.arange(N_SHARDS * 7, dtype=jnp.float32).reshape(
        N_SHARDS, 7) * 0.37
    idx = jnp.stack([(jnp.arange(7) * (s + 3)) % V
                     for s in range(N_SHARDS)]).astype(jnp.int32)
    per_shard = data_parallel(
        lambda v, i: comms.sparse_allreduce(
            v[0], i[0], V, n=N_SHARDS)[None],
        mesh4, in_specs=(P("data", None), P("data", None)),
        out_specs=P("data", None))(vals, idx)
    per_shard = np.asarray(per_shard)
    for s in range(1, N_SHARDS):
        np.testing.assert_array_equal(per_shard[s], per_shard[0])


def test_segmented_resume_bitwise(tmp_path, mesh4):
    path, _, _ = _powerlaw(tmp_path)
    gd = graphs.open_graph_dataset(path, mesh4)
    cfg = graphs.StreamedPageRankConfig(n_iterations=6)
    straight = np.asarray(graphs.run_streamed_pagerank(gd, cfg).ranks)
    ck = str(tmp_path / "ck")
    seg = graphs.run_streamed_pagerank(gd, cfg, checkpoint_dir=ck,
                                       checkpoint_every=2)
    np.testing.assert_array_equal(np.asarray(seg.ranks), straight)
    # interrupted-then-resumed: 4 of 6 sweeps, then the full run picks
    # the checkpoint up and finishes bitwise-identically
    ck2 = str(tmp_path / "ck2")
    graphs.run_streamed_pagerank(
        gd, graphs.StreamedPageRankConfig(n_iterations=4),
        checkpoint_dir=ck2, checkpoint_every=2)
    resumed = graphs.run_streamed_pagerank(gd, cfg, checkpoint_dir=ck2,
                                           checkpoint_every=2)
    np.testing.assert_array_equal(np.asarray(resumed.ranks), straight)


def test_block_schedule_batches_divisors():
    ids = engine._block_schedule(n_blocks=12, n_shards=2,
                                 batch_blocks=5)
    # 5 does not divide 12 — largest divisor <= 5 is 4
    assert ids.shape == (3, 2, 4)
    flat = ids[:, 0, :].reshape(-1)
    np.testing.assert_array_equal(flat, np.arange(12))
    ids1 = engine._block_schedule(n_blocks=7, n_shards=4,
                                  batch_blocks=1)
    assert ids1.shape == (7, 4, 1)


# --------------------------------------- combine accounting/telemetry

def test_powerlaw_sparse_accounting_beats_dense(tmp_path, mesh4):
    """The acceptance property: on a power-law graph the sparse pair
    exchange accounts fewer wire bytes than the dense O(V) ring psum,
    and combine='auto' therefore resolves to sparse."""
    path, _, header = _powerlaw(tmp_path, name="big",
                                n_vertices=4096, block_edges=256)
    geom = header["geom"]
    from tpu_distalg.parallel import comms

    st = comms.rank_combine_stats(int(geom["k_sparse"]),
                                  int(geom["n_vertices"]), N_SHARDS)
    assert st["bytes_wire"] < st["bytes_dense_ring"]
    assert engine.resolve_combine(
        "auto", int(geom["k_sparse"]), int(geom["n_vertices"]),
        N_SHARDS) == "sparse"
    # power-law means MOST vertices have no in-links at all
    assert int(geom["k_sparse"]) < int(geom["n_vertices"]) // N_SHARDS


def test_combine_counters_rendered_by_report(tmp_path, mesh4):
    from tpu_distalg.telemetry import events, report

    path, _, _ = _powerlaw(tmp_path, name="big", n_vertices=4096,
                           block_edges=256)
    sink = str(tmp_path / "tele")
    events.configure(sink)
    try:
        gd = graphs.open_graph_dataset(path, mesh4)
        res = graphs.run_streamed_pagerank(
            gd, graphs.StreamedPageRankConfig(n_iterations=3))
        assert res.combine == "sparse"
    finally:
        events.configure(False)
    evts = report.load_events(sink)
    s = report.summarize(evts)
    wire = s["counters"]["comm.bytes_wire"]
    dense = s["counters"]["graph.combine_bytes_dense_ring"]
    assert wire == res.comm_stats["bytes_wire"] * 3
    assert wire < dense
    txt = report.render(s)
    assert "graph rank combine" in txt
    assert "sparser" in txt


# ------------------------------------------------- faults / VMEM guard

def test_chaos_pagerank_stream_bitwise(tmp_path, mesh4):
    """The streamed gather/H2D path runs through the data:gather /
    data:h2d inject seams, and recovery is bitwise."""
    from tpu_distalg.faults import chaos

    res = chaos.run_chaos(
        "pagerank_stream", mesh4,
        plan="seed=5;data:gather@1=oserror;data:h2d@2=oserror",
        workdir=str(tmp_path / "chaos"), n_iterations=4)
    assert res.equal, res.mismatched
    assert ("data:gather", 1, "oserror") in res.fired
    assert ("data:h2d", 2, "oserror") in res.fired


def test_resident_guard_degrades_to_streamed():
    from tpu_distalg.models import pagerank as m

    assert not m.resident_guard_trips(1_000_000)
    assert m.resident_guard_trips(50_000_000)
    backend, warn = m.choose_data_backend("resident", 1_000_000)
    assert backend == "resident" and warn is None
    backend, warn = m.choose_data_backend("resident", 50_000_000)
    assert backend == "streamed"
    assert "--data-backend streamed" in warn
    # the ceiling is a shard's: the warning says how many hold the
    # graph, and a mesh of that many keeps it resident
    assert "a mesh of 2 data shards" in warn
    assert not m.resident_guard_trips(50_000_000, 2)
    backend, warn = m.choose_data_backend("resident", 50_000_000,
                                          n_shards=2)
    assert backend == "resident" and warn is None
    # an explicit streamed request never degrades or warns
    backend, warn = m.choose_data_backend("streamed", 50_000_000)
    assert backend == "streamed" and warn is None
    # the ceiling is the fused-SpMV kernel's — an explicit xla/pallas
    # resident request is honored (those paths carry their own errors)
    backend, warn = m.choose_data_backend("resident", 50_000_000,
                                          scatter="xla")
    assert backend == "resident" and warn is None
    backend, _ = m.choose_data_backend("resident", 50_000_000,
                                       scatter="spmv")
    assert backend == "streamed"


def test_vmem_rejection_event_names_the_shards_needed(tmp_path):
    from tpu_distalg.ops import pallas_pagerank as ppr
    from tpu_distalg.telemetry import events, report

    sink = str(tmp_path / "tele")
    events.configure(sink)
    try:
        assert ppr.spmv_geometry(1 << 26, 16 << 26, 2) is None
    finally:
        events.configure(False)
    evts = [e for e in report.load_events(sink)
            if e.get("ev") == "spmv_vmem_rejected"]
    assert len(evts) == 1
    assert evts[0]["n_shards"] == 2 and evts[0]["shards_needed"] == 4
    assert "a mesh of 4 data shards" in evts[0]["remedy"]


# ------------------------------------------------- review-round pins

def test_powerlaw_chunking_is_by_edges_not_vertices(tmp_path):
    """A power-law profile concentrates ~all edges on the first hub
    vertices, so generation must chunk by EDGE rows (a hub's edges
    spanning many chunks) to keep the O(V + chunk) host-RAM bound —
    and the bytes must not depend on where inside a hub the chunk
    boundaries land relative to the block/shard grid."""
    a = str(tmp_path / "a")
    b = str(tmp_path / "b")
    kw = dict(n_vertices=512, n_shards=N_SHARDS, avg_in_degree=8.0,
              alpha=1.6, seed=9, block_edges=64)
    # 97 rows/chunk: prime, so boundaries fall mid-hub and mid-block
    mm_a, h_a = graphs.build_powerlaw_block_cache(a, chunk_edges=97,
                                                  **kw)
    mm_b, h_b = graphs.build_powerlaw_block_cache(b, chunk_edges=97,
                                                  **kw)
    np.testing.assert_array_equal(np.asarray(mm_a), np.asarray(mm_b))
    geom = h_a["geom"]
    E = geom["n_edges"]
    rows = np.asarray(mm_a)
    dst = rows[:E, 1]
    assert (np.diff(dst) >= 0).all()
    counts = ingest.powerlaw_in_degree_counts(512, 8.0, 1.6)
    np.testing.assert_array_equal(np.bincount(dst, minlength=512),
                                  counts)
    deg, _, _ = ingest.read_aux(a, geom)
    np.testing.assert_array_equal(
        rows[:E, 2].view(np.float32),
        ingest.inv_out_degree(deg)[rows[:E, 0]])
    # the chunk size is part of the cache identity (rng keying)
    with pytest.raises(ValueError, match="built with"):
        graphs.build_powerlaw_block_cache(a, chunk_edges=101, **kw)


def test_edge_cache_reopen_skips_pipeline_and_checks_content(tmp_path):
    """A cache hit must not re-run the O(E) dedupe/sort pipeline —
    and must still reject different edges / parameters at the path."""
    from unittest import mock

    from tpu_distalg.ops import graph as gops

    rng = np.random.default_rng(11)
    edges = np.stack([rng.integers(0, 64, 300),
                      rng.integers(0, 64, 300)], 1).astype(np.int64)
    path = str(tmp_path / "e")
    mm, header = graphs.build_edge_block_cache(
        edges, path, n_shards=N_SHARDS, block_edges=16)
    with mock.patch.object(gops, "prepare_edges",
                           side_effect=AssertionError(
                               "reopen ran the ingest pipeline")):
        mm2, header2 = graphs.build_edge_block_cache(
            edges, path, n_shards=N_SHARDS, block_edges=16)
    assert header2 == header
    np.testing.assert_array_equal(np.asarray(mm), np.asarray(mm2))
    with pytest.raises(ValueError, match="delete the cache"):
        graphs.build_edge_block_cache(edges, path, n_shards=N_SHARDS,
                                      block_edges=32)
    with pytest.raises(ValueError, match="delete the cache"):
        graphs.build_edge_block_cache(edges[:-1], path,
                                      n_shards=N_SHARDS,
                                      block_edges=16)


def test_prepare_edges_rejects_undersized_vertex_count():
    """An undersized n_vertices used to flow into the native degree
    histogram's unchecked ``degree[src[i]]++`` — a heap write. It must
    be a ValueError at the boundary instead."""
    from tpu_distalg.ops import graph as gops

    edges = np.array([[0, 1], [5, 2]], np.int64)
    with pytest.raises(ValueError, match="n_vertices"):
        gops.prepare_edges(edges, 3)
    el = gops.prepare_edges(edges, 6)
    assert el.n_vertices == 6
