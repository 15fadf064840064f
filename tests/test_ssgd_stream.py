"""Streamed host→device SSGD (models/ssgd_stream.py): real bytes
bigger than HBM, double-buffered H2D — the Spark spill/stream
replacement for data that is NOT a function of the row id
(reference optimization/ssgd.py:86)."""

import numpy as np
import pytest

from tpu_distalg.models import ssgd, ssgd_stream


@pytest.fixture(scope="module")
def data(cancer_data):
    return cancer_data


def _cfg(**kw):
    base = dict(n_iterations=60, sampler="fused_gather",
                gather_block_rows=32, fused_pack=4, shuffle_seed=0,
                eval_every=10)
    base.update(kw)
    return ssgd.SSGDConfig(**base)


def test_stream_bitwise_equals_resident_fused_gather(mesh4, data):
    """The whole design contract: same packing, same threefry block
    draws (host CPU == device), same kernel over the staged blocks →
    the weight trajectory equals the resident 'fused_gather' path BIT
    FOR BIT."""
    X_train, y_train, X_test, y_test = data
    cfg = _cfg()
    resident = ssgd.train(X_train, y_train, X_test, y_test, mesh4, cfg)

    X2h, meta = ssgd_stream.pack_host(X_train, y_train, mesh4, cfg)
    assert isinstance(X2h, np.ndarray)  # never device-resident
    streamed = ssgd_stream.train(X2h, meta, mesh4, cfg, X_test, y_test)
    np.testing.assert_array_equal(np.asarray(resident.w),
                                  np.asarray(streamed.w))


def test_stream_memmap_source(mesh4, data, tmp_path):
    """A disk-mapped dataset trains identically to the in-RAM array —
    the >RAM story composes with >HBM."""
    X_train, y_train, X_test, y_test = data
    cfg = _cfg(n_iterations=30)
    X2h, meta = ssgd_stream.pack_host(X_train, y_train, mesh4, cfg)
    path = tmp_path / "packed.bin"
    mm = np.memmap(path, dtype=X2h.dtype, mode="w+", shape=X2h.shape)
    mm[:] = X2h
    mm.flush()
    ro = np.memmap(path, dtype=X2h.dtype, mode="r", shape=X2h.shape)
    a = ssgd_stream.train(X2h, meta, mesh4, cfg, X_test, y_test)
    b = ssgd_stream.train(ro, meta, mesh4, cfg, X_test, y_test)
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))


def test_stream_segmented_equals_straight(mesh4, data, tmp_path):
    X_train, y_train, X_test, y_test = data
    cfg = _cfg()
    X2h, meta = ssgd_stream.pack_host(X_train, y_train, mesh4, cfg)
    straight = ssgd_stream.train(X2h, meta, mesh4, cfg, X_test, y_test)
    seg = ssgd_stream.train(X2h, meta, mesh4, cfg, X_test, y_test,
                            checkpoint_dir=str(tmp_path / "ck"),
                            checkpoint_every=25)
    np.testing.assert_array_equal(np.asarray(straight.w),
                                  np.asarray(seg.w))
    np.testing.assert_array_equal(np.asarray(straight.accs),
                                  np.asarray(seg.accs))


def test_stream_resume_from_checkpoint(mesh4, data, tmp_path):
    X_train, y_train, X_test, y_test = data
    d = str(tmp_path / "ck")
    X2h, meta = ssgd_stream.pack_host(
        X_train, y_train, mesh4, _cfg())
    ssgd_stream.train(X2h, meta, mesh4, _cfg(n_iterations=30),
                      X_test, y_test, checkpoint_dir=d,
                      checkpoint_every=30)
    resumed = ssgd_stream.train(X2h, meta, mesh4, _cfg(), X_test,
                                y_test, checkpoint_dir=d,
                                checkpoint_every=30)
    straight = ssgd_stream.train(X2h, meta, mesh4, _cfg(), X_test,
                                 y_test)
    np.testing.assert_array_equal(np.asarray(straight.w),
                                  np.asarray(resumed.w))


def test_stream_converges(mesh4, data):
    X_train, y_train, X_test, y_test = data
    cfg = _cfg(n_iterations=1500, eval_every=250)
    X2h, meta = ssgd_stream.pack_host(X_train, y_train, mesh4, cfg)
    res = ssgd_stream.train(X2h, meta, mesh4, cfg, X_test, y_test)
    # platform-spread band: the original rig converges this schedule to
    # 0.9415, this container's BLAS to 0.9006 (chaotic 1500-step
    # trajectory); the reference-golden-band claim (0.9298) is asserted
    # where the trajectory is the chip's own (tests_tpu/, chip_smoke.py)
    assert res.final_acc > 0.88, res.final_acc


def test_streamed_packed_cache_roundtrip(mesh4, tmp_path):
    """The disk cache generates once, reopens instantly with identical
    bytes, rejects mismatched geometry, and its dataset trains to the
    teacher's accuracy band."""
    from tpu_distalg.utils import datasets

    path = str(tmp_path / "ds")
    kw = dict(n_shards=4, pack=4, gather_block_rows=32, seed=3,
              x_dtype="bfloat16", chunk_rows=4096, n_test=512)
    X2, meta, (X_test, y_test) = datasets.streamed_packed_cache(
        path, n_rows=4 * 32 * 4 * 8, n_features=15, **kw)
    X2b, meta_b, _ = datasets.streamed_packed_cache(
        path, n_rows=4 * 32 * 4 * 8, n_features=15, **kw)
    assert meta == meta_b
    np.testing.assert_array_equal(np.asarray(X2), np.asarray(X2b))
    with pytest.raises(ValueError, match="cache"):
        datasets.streamed_packed_cache(
            path, n_rows=4 * 32 * 4 * 8, n_features=14,
            **{**kw, "n_test": 512})

    cfg = _cfg(n_iterations=500, eta=0.5, gather_block_rows=32,
               fused_pack=4, shuffle_seed=None,
               mini_batch_fraction=0.2, eval_every=50,
               x_dtype="float32")
    res = ssgd_stream.train(X2, meta, mesh4, cfg, X_test, y_test)
    # the TEACHER scores ~0.76 on this noisy task (saved in the cache);
    # the trained model must land within a point of that ceiling
    t = np.load(str(tmp_path / "ds.test.npz"))
    teacher_acc = np.mean(
        (X_test @ t["w_true"] > 0) == (y_test > 0.5))
    assert res.final_acc > teacher_acc - 0.02


def test_stream_shard_mismatch_rejected(mesh4, data):
    X_train, y_train, X_test, y_test = data
    cfg = _cfg()
    X2h, meta = ssgd_stream.pack_host(X_train, y_train, mesh4, cfg)
    with pytest.raises(ValueError, match="divisible"):
        ssgd_stream.StreamTrainer(X2h[:-1], meta,
                                  mesh4, cfg, X_test, y_test)
