"""The out-of-core dataset subsystem (tpu_distalg/data/): backend
equivalence (resident == virtual == streamed staged bytes and
trajectories), the versioned packed-cache format (header round-trip,
version/geometry rejection, legacy reopen, concurrent two-process
build), and prefetch-thread error propagation."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from tpu_distalg.data import ShardedDataset, builders, cache as dcache
from tpu_distalg.data import block_geometry


# ---------------------------------------------------------------- cache

def _tiny_header(n=32, pd=4):
    return dcache.make_header(layout="rows_test", dtype=np.float32,
                              shape=(n, pd), geom={"n": n, "pd": pd,
                                                   "seed": 3})


def _write_rows(mm):
    mm[:] = np.arange(mm.size, dtype=np.float32).reshape(mm.shape)


def test_cache_header_roundtrip(tmp_path):
    path = str(tmp_path / "c")
    mm, hdr = dcache.build_cache(path, header=_tiny_header(),
                                 write_bin=_write_rows)
    assert hdr == _tiny_header()
    mm2, hdr2 = dcache.open_cache(path, layout="rows_test",
                                  expect_geom=_tiny_header()["geom"])
    assert hdr2 == hdr
    np.testing.assert_array_equal(np.asarray(mm), np.asarray(mm2))
    # the reopened memmap is read-only
    with pytest.raises(ValueError):
        mm2[0, 0] = 1.0


def test_cache_version_rejected(tmp_path):
    path = str(tmp_path / "c")
    dcache.build_cache(path, header=_tiny_header(),
                       write_bin=_write_rows)
    hdr = dcache.read_header(path)
    hdr["version"] = 99
    with open(dcache.meta_path(path), "w") as f:
        json.dump(hdr, f)
    with pytest.raises(ValueError, match="version"):
        dcache.open_cache(path)


def test_cache_layout_and_geom_rejected(tmp_path):
    path = str(tmp_path / "c")
    dcache.build_cache(path, header=_tiny_header(),
                       write_bin=_write_rows)
    with pytest.raises(ValueError, match="layout"):
        dcache.open_cache(path, layout="something_else")
    with pytest.raises(ValueError, match="built with"):
        dcache.open_cache(path, expect_geom={"n": 64})


def test_cache_legacy_flat_meta_accepted(tmp_path):
    """Pre-subsystem caches wrote the flat geometry dict as the whole
    meta.json; they must reopen (not regenerate) after the header
    format promotion."""
    path = str(tmp_path / "c")
    geom = {"n_rows": 8, "seed": 0}
    arr = np.arange(16, dtype=np.float32).reshape(8, 2)
    arr.tofile(dcache.bin_path(path))
    with open(dcache.meta_path(path), "w") as f:
        json.dump(geom, f)
    mm, hdr = dcache.open_cache(path, legacy_geom=geom)
    assert mm is None and hdr["version"] == 1 and hdr["geom"] == geom
    with pytest.raises(ValueError, match="legacy"):
        dcache.open_cache(path, legacy_geom={"n_rows": 9})


def test_cache_bin_without_meta_is_incomplete(tmp_path):
    path = str(tmp_path / "c")
    np.zeros(4, np.float32).tofile(dcache.bin_path(path))
    assert not dcache.exists(path)
    with pytest.raises(FileNotFoundError, match="complete"):
        dcache.open_cache(path)


def test_cache_shard_slicing():
    lo, hi = dcache.shard_rows(32, 4, 2)
    assert (lo, hi) == (16, 24)
    with pytest.raises(ValueError, match="divide"):
        dcache.shard_rows(33, 4, 0)
    mm = np.arange(32)[:, None] * np.ones((1, 2))
    np.testing.assert_array_equal(
        dcache.shard_view(mm, 4, 1), mm[8:16])


def test_cache_concurrent_two_process_build(tmp_path):
    """Two real processes race the SAME cache path: both must succeed
    (PID/uuid tmp names + last-atomic-rename-wins), and the survivor's
    bytes must be the deterministic content either would write."""
    path = str(tmp_path / "race")
    prog = (
        "import numpy as np\n"
        "from tpu_distalg.data import cache as dcache\n"
        "hdr = dcache.make_header(layout='rows_test', dtype=np.float32,"
        " shape=(64, 8), geom={'seed': 5})\n"
        "def wb(mm):\n"
        "    mm[:] = np.random.default_rng(5).random(mm.shape,"
        " dtype=np.float32)\n"
        f"mm, _ = dcache.build_cache({path!r}, header=hdr, write_bin=wb)\n"
        "print(float(np.asarray(mm).sum()))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen([sys.executable, "-c", prog], env=env,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE)
             for _ in range(2)]
    outs = [p.communicate(timeout=300) for p in procs]
    assert all(p.returncode == 0 for p in procs), outs
    mm, hdr = dcache.open_cache(path, layout="rows_test")
    want = np.random.default_rng(5).random((64, 8), dtype=np.float32)
    np.testing.assert_array_equal(np.asarray(mm), want)
    # no tmp orphans survive a clean double-publish
    leftovers = [p for p in os.listdir(tmp_path) if ".tmp." in p]
    assert leftovers == []


# ------------------------------------------------- ShardedDataset core

def _packed_matrix(n2=64, pd=8, seed=0):
    return np.random.default_rng(seed).random((n2, pd)).astype(
        np.float32)


def _three_backends(mesh4, tmp_path, arr, block_rows):
    hdr = dcache.make_header(layout="rows_test", dtype=np.float32,
                             shape=arr.shape, geom={"seed": 0})
    path = str(tmp_path / "ds")

    def wb(mm):
        mm[:] = arr

    dcache.build_cache(path, header=hdr, write_bin=wb)
    return {
        "resident": ShardedDataset.from_array(
            arr, mesh4, block_rows=block_rows, backend="resident"),
        "virtual": ShardedDataset.from_array(
            arr, mesh4, block_rows=block_rows, backend="virtual"),
        "streamed": ShardedDataset.from_cache(
            path, mesh4, block_rows=block_rows, layout="rows_test"),
    }


def test_staged_batches_bitwise_equal_across_backends(mesh4, tmp_path):
    """The subsystem contract: whichever backend holds the bytes, the
    staged device batch is identical — the property that makes
    --data-backend a placement knob, not an algorithm knob."""
    arr = _packed_matrix()
    dss = _three_backends(mesh4, tmp_path, arr, block_rows=4)
    ids = np.array([[0, 3], [1, 1], [2, 0], [3, 2]])
    staged = {k: np.asarray(ds.stage(ids)) for k, ds in dss.items()}
    assert dss["streamed"].backend == "streamed"
    np.testing.assert_array_equal(staged["resident"], staged["virtual"])
    np.testing.assert_array_equal(staged["virtual"], staged["streamed"])
    # and against the hand gather: shard s block b = storage rows
    # [s*16 + b*4, ...+4)
    want = arr[1 * 16 + 1 * 4:1 * 16 + 2 * 4]
    np.testing.assert_array_equal(staged["virtual"][1, :4], want)


def test_stream_order_matches_serial_stage(mesh4, tmp_path):
    arr = _packed_matrix()
    ds = _three_backends(mesh4, tmp_path, arr, block_rows=4)["virtual"]
    ids = np.array([[[0], [1], [2], [3]], [[3], [2], [1], [0]]])
    got = [np.asarray(b) for b in ds.stream(ids)]
    want = [np.asarray(ds.stage(ids[0])), np.asarray(ds.stage(ids[1]))]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_dataset_shape_validation(mesh4):
    arr = _packed_matrix(n2=62)  # not divisible by 4 shards
    with pytest.raises(ValueError, match="divisible"):
        ShardedDataset.from_array(arr, mesh4, block_rows=4)
    with pytest.raises(ValueError, match="block_rows"):
        ShardedDataset.from_array(_packed_matrix(), mesh4, block_rows=5)
    with pytest.raises(ValueError, match="backend"):
        ShardedDataset.from_array(_packed_matrix(), mesh4,
                                  block_rows=4, backend="cloud")


def test_block_geometry_shared_grid():
    rows, blocks, sampled = block_geometry(10_001, 256, 8, 0.05)
    assert rows % 256 == 0 and rows * 8 >= 10_001
    assert blocks == rows // 256
    assert sampled == max(1, round(0.05 * blocks))
    assert block_geometry(1024, 64, 4, None)[2] is None


def test_prefetch_error_propagates(mesh4, tmp_path):
    """A producer-thread exception must surface in the consumer, not
    hang the queue."""
    arr = _packed_matrix()
    ds = _three_backends(mesh4, tmp_path, arr, block_rows=4)["virtual"]
    boom = RuntimeError("gather exploded")
    real_gather = ds.gather
    calls = {"n": 0}

    def bad_gather(ids_step):
        calls["n"] += 1
        if calls["n"] >= 3:
            raise boom
        return real_gather(ids_step)

    ds.gather = bad_gather
    ids = np.tile(np.array([[[0]], [[1]], [[2]], [[3]]]).reshape(
        1, 4, 1), (6, 1, 1))
    seen = 0
    with pytest.raises(RuntimeError, match="gather exploded"):
        for _ in ds.stream(ids):
            seen += 1
    assert seen <= 3  # the error arrives within the prefetch depth


def test_prefetcher_early_close_joins():
    from tpu_distalg.data import Prefetcher

    with Prefetcher(lambda i: i, 100) as pf:
        assert pf.get() == 0
    assert not pf._thread.is_alive()


# ------------------------------------- workload backend equivalence

def test_kmeans_minibatch_backend_equivalence(mesh4, tmp_path):
    """resident == virtual == streamed center trajectories, bit for
    bit, on toy shapes — same staged bytes, same jitted step."""
    from tpu_distalg.models import kmeans

    res = {}
    for be in ("resident", "virtual", "streamed"):
        ds, truth = builders.gaussian_points_dataset(
            mesh4, 4096, dim=4, k=3, seed=7, block_rows=64, backend=be,
            path=str(tmp_path / "pts") if be == "streamed" else None)
        r = kmeans.fit_minibatch(ds, kmeans.KMeansConfig(k=3, seed=1),
                                 n_steps=20, mini_batch_blocks=2)
        res[be] = np.asarray(r.centers)
    np.testing.assert_array_equal(res["resident"], res["virtual"])
    np.testing.assert_array_equal(res["virtual"], res["streamed"])
    # and the minibatch run actually clusters: every true mean found
    d = np.linalg.norm(res["streamed"][:, None] - truth[None],
                       axis=-1)
    assert sorted(d.argmin(axis=1).tolist()) == [0, 1, 2]
    assert float(d.min(axis=1).max()) < 1.0


def test_als_streamed_backend_equivalence_and_matches_resident(
        mesh4, tmp_path):
    """virtual == streamed bitwise; both match the resident
    make_fit_fn sweep to float tolerance (the blocked UᵀR contraction
    reorders additions, nothing else). m deliberately NOT a multiple
    of the block grid: builder zero-padding must be inert."""
    from tpu_distalg.models import als

    cfg = als.ALSConfig(m=90, n=40, k=5, lam=0.01, n_iterations=4,
                        seed=0)
    R = als.synthesize_rank_k(cfg)
    resident = als.fit(mesh4, cfg, R)
    outs = {}
    for be in ("resident", "virtual", "streamed"):
        ds, _ = builders.rank_k_rows_dataset(
            mesh4, cfg.m, cfg.n, cfg.k, seed=cfg.seed, block_rows=8,
            backend=be,
            path=str(tmp_path / "als") if be == "streamed" else None)
        assert ds.n2 == 96  # padded: 90 -> 96 (4 shards x 8-row blocks)
        outs[be] = als.fit_streamed(ds, cfg)
    np.testing.assert_array_equal(np.asarray(outs["virtual"].U),
                                  np.asarray(outs["streamed"].U))
    np.testing.assert_array_equal(
        np.asarray(outs["virtual"].rmse_history),
        np.asarray(outs["streamed"].rmse_history))
    assert outs["streamed"].U.shape == (cfg.m, cfg.k)  # truncated
    np.testing.assert_allclose(
        np.asarray(outs["streamed"].rmse_history),
        np.asarray(resident.rmse_history), rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(np.asarray(outs["streamed"].U),
                               np.asarray(resident.U), rtol=2e-3,
                               atol=2e-4)


def test_als_rmse_every_zero_evaluates_once(mesh4):
    from tpu_distalg.models import als

    cfg = als.ALSConfig(m=32, n=16, k=3, lam=0.0, n_iterations=3)
    ds, _ = builders.rank_k_rows_dataset(mesh4, cfg.m, cfg.n, cfg.k,
                                         seed=0, block_rows=8,
                                         backend="virtual")
    res = als.fit_streamed(ds, cfg, rmse_every=0)
    assert res.rmse_history.shape == (1,)


def test_streamed_cache_v2_header_written(mesh4, tmp_path):
    """streamed_packed_cache now publishes through the engine: the
    meta.json is a versioned header whose geom is the old flat dict."""
    from tpu_distalg.utils import datasets

    path = str(tmp_path / "ds")
    datasets.streamed_packed_cache(
        path, n_rows=4 * 32 * 4 * 2, n_features=15, n_shards=4, pack=4,
        gather_block_rows=32, seed=3, chunk_rows=4096, n_test=64)
    hdr = dcache.read_header(path)
    assert hdr["format"] == dcache.FORMAT
    assert hdr["version"] == dcache.FORMAT_VERSION
    assert hdr["layout"] == "packed_augmented"
    assert hdr["geom"]["n_rows"] == 4 * 32 * 4 * 2


# ------------------------------------------ satellites riding along

def test_als_model_axis_pads_and_engages(mesh_2x4):
    """VERDICT weak #4: n not divisible by the model axis used to
    silently replicate V; now fit() pads R's columns (inert zeros) and
    the result still matches the data-parallel reference run."""
    from tpu_distalg.models import als

    cfg = als.ALSConfig(m=24, n=30, k=3, lam=0.01, n_iterations=4,
                        seed=2)  # 30 % 4 != 0 -> pads to 32
    R = als.synthesize_rank_k(cfg)
    res = als.fit(mesh_2x4, cfg, R)
    assert res.V.shape == (30, 3)
    assert np.isfinite(res.final_rmse)
    import jax

    mesh1d = jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(4, 1), ("data", "model"))
    base = als.fit(mesh1d, cfg, R)
    np.testing.assert_allclose(res.final_rmse, base.final_rmse,
                               rtol=1e-3, atol=1e-5)


def test_als_model_axis_disengage_warns(mesh_2x4):
    """Direct make_fit_fn callers handing in an UNPADDED R get a logged
    disengage instead of the old silent replication."""
    import warnings

    import jax

    from tpu_distalg.models import als

    cfg = als.ALSConfig(m=8, n=30, k=3, n_iterations=1)
    fn = als.make_fit_fn(mesh_2x4, cfg)
    R = jnp.asarray(als.synthesize_rank_k(cfg))
    U0 = jnp.zeros((8, 3))
    V0 = jnp.asarray(
        np.random.default_rng(0).random((30, 3), dtype=np.float32))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        jax.block_until_ready(fn(R, U0, V0))
    assert any("DISENGAGED" in str(w.message) for w in caught)
