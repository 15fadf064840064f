"""Checkpoint/resume: segmented training must equal straight-through
training bitwise, and resume must continue from the saved step."""

import numpy as np
import pytest

from tpu_distalg.models import ssgd


@pytest.fixture(scope="module")
def data(cancer_data):
    return cancer_data


def test_segmented_equals_straight(mesh8, data, tmp_path):
    X_train, y_train, X_test, y_test = data
    cfg = ssgd.SSGDConfig(n_iterations=120)
    straight = ssgd.train(X_train, y_train, X_test, y_test, mesh8, cfg)
    seg = ssgd.train(
        X_train, y_train, X_test, y_test, mesh8, cfg,
        checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=50,
    )
    np.testing.assert_array_equal(np.asarray(straight.w), np.asarray(seg.w))
    np.testing.assert_array_equal(
        np.asarray(straight.accs), np.asarray(seg.accs)
    )


def test_resume_from_checkpoint(mesh8, data, tmp_path):
    """Kill after 60 steps (checkpointed), rerun: must complete to 120 and
    match the straight run."""
    X_train, y_train, X_test, y_test = data
    d = str(tmp_path / "ck")
    cfg60 = ssgd.SSGDConfig(n_iterations=60)
    ssgd.train(X_train, y_train, X_test, y_test, mesh8, cfg60,
               checkpoint_dir=d, checkpoint_every=60)

    cfg120 = ssgd.SSGDConfig(n_iterations=120)
    resumed = ssgd.train(X_train, y_train, X_test, y_test, mesh8, cfg120,
                         checkpoint_dir=d, checkpoint_every=60)
    straight = ssgd.train(X_train, y_train, X_test, y_test, mesh8, cfg120)
    np.testing.assert_array_equal(
        np.asarray(straight.w), np.asarray(resumed.w)
    )
    assert resumed.accs.shape == (120,)


def test_nan_guard_trips(mesh8, data, tmp_path):
    X_train, y_train, X_test, y_test = data
    X_bad = X_train.copy()
    X_bad[0, 0] = np.nan
    with pytest.raises(FloatingPointError, match="non-finite"):
        ssgd.train(X_bad, y_train, X_test, y_test, mesh8,
                   ssgd.SSGDConfig(n_iterations=20),
                   checkpoint_dir=str(tmp_path / "ck"),
                   checkpoint_every=10)


def test_stale_checkpoint_past_n_iterations_rejected(mesh8, data, tmp_path):
    X_train, y_train, X_test, y_test = data
    d = str(tmp_path / "ck")
    ssgd.train(X_train, y_train, X_test, y_test, mesh8,
               ssgd.SSGDConfig(n_iterations=100), checkpoint_dir=d,
               checkpoint_every=100)
    with pytest.raises(ValueError, match="past"):
        ssgd.train(X_train, y_train, X_test, y_test, mesh8,
                   ssgd.SSGDConfig(n_iterations=50), checkpoint_dir=d)


def test_checkpoints_pruned(mesh8, data, tmp_path):
    import os
    X_train, y_train, X_test, y_test = data
    d = str(tmp_path / "ck")
    ssgd.train(X_train, y_train, X_test, y_test, mesh8,
               ssgd.SSGDConfig(n_iterations=200), checkpoint_dir=d,
               checkpoint_every=40)
    files = [f for f in os.listdir(d) if f.endswith(".msgpack")]
    assert len(files) <= 3


# ---- local-update family (MA / BMUF / EASGD) ----

@pytest.mark.parametrize("mod_name", ["ma", "bmuf", "easgd"])
def test_local_sgd_segmented_equals_straight(mesh4, data, tmp_path,
                                             mod_name):
    """The full (w, ws, delta) carry checkpoints and resumes bitwise for
    every periodic-averaging optimizer."""
    import importlib

    m = importlib.import_module(f"tpu_distalg.models.{mod_name}")
    cfg_cls = {"ma": "MAConfig", "bmuf": "BMUFConfig",
               "easgd": "EASGDConfig"}[mod_name]
    cfg = getattr(m, cfg_cls)(n_iterations=60)
    X_train, y_train, X_test, y_test = data
    straight = m.train(X_train, y_train, X_test, y_test, mesh4, cfg)
    seg = m.train(X_train, y_train, X_test, y_test, mesh4, cfg,
                  checkpoint_dir=str(tmp_path / mod_name),
                  checkpoint_every=25)
    np.testing.assert_array_equal(np.asarray(straight.w), np.asarray(seg.w))
    np.testing.assert_array_equal(np.asarray(straight.ws),
                                  np.asarray(seg.ws))
    np.testing.assert_array_equal(np.asarray(straight.accs),
                                  np.asarray(seg.accs))


def test_local_sgd_resume_from_checkpoint(mesh4, data, tmp_path):
    from tpu_distalg.models import bmuf

    X_train, y_train, X_test, y_test = data
    d = str(tmp_path / "ck")
    bmuf.train(X_train, y_train, X_test, y_test, mesh4,
               bmuf.BMUFConfig(n_iterations=30), checkpoint_dir=d,
               checkpoint_every=30)
    resumed = bmuf.train(X_train, y_train, X_test, y_test, mesh4,
                         bmuf.BMUFConfig(n_iterations=60),
                         checkpoint_dir=d, checkpoint_every=30)
    straight = bmuf.train(X_train, y_train, X_test, y_test, mesh4,
                          bmuf.BMUFConfig(n_iterations=60))
    np.testing.assert_array_equal(np.asarray(straight.w),
                                  np.asarray(resumed.w))
    assert resumed.accs.shape == (60,)


# ---- fused-sampler SSGD ----

def test_fused_gather_segmented_equals_straight(mesh4, data, tmp_path):
    """The NotImplementedError is gone: the packed samplers checkpoint
    through the same segment machinery (augmented-w carry, absolute-step
    PRNG)."""
    X_train, y_train, X_test, y_test = data
    cfg = ssgd.SSGDConfig(n_iterations=60, sampler="fused_gather",
                          fused_pack=4, gather_block_rows=32,
                          shuffle_seed=0)
    straight = ssgd.train(X_train, y_train, X_test, y_test, mesh4, cfg)
    seg = ssgd.train(X_train, y_train, X_test, y_test, mesh4, cfg,
                     checkpoint_dir=str(tmp_path / "fg"),
                     checkpoint_every=25)
    np.testing.assert_array_equal(np.asarray(straight.w), np.asarray(seg.w))
    np.testing.assert_array_equal(np.asarray(straight.accs),
                                  np.asarray(seg.accs))


def test_local_sgd_fused_segmented_equals_straight(mesh4, data, tmp_path):
    """The fused local-update path checkpoints bitwise too: the
    augmented (w, ws, delta) carry and absolute-round block draws make
    segmented ≡ straight for the packed kernel family."""
    from tpu_distalg.models import bmuf

    X_train, y_train, X_test, y_test = data
    cfg = bmuf.BMUFConfig(n_iterations=60, sampler="fused_gather",
                          fused_pack=4, gather_block_rows=32,
                          shuffle_seed=0)
    straight = bmuf.train(X_train, y_train, X_test, y_test, mesh4, cfg)
    seg = bmuf.train(X_train, y_train, X_test, y_test, mesh4, cfg,
                     checkpoint_dir=str(tmp_path / "lsf"),
                     checkpoint_every=25)
    np.testing.assert_array_equal(np.asarray(straight.w), np.asarray(seg.w))
    np.testing.assert_array_equal(np.asarray(straight.ws),
                                  np.asarray(seg.ws))
    np.testing.assert_array_equal(np.asarray(straight.accs),
                                  np.asarray(seg.accs))


# ---- ALS ----

def test_als_segmented_equals_straight(mesh8, tmp_path):
    from tpu_distalg.models import als

    cfg = als.ALSConfig(n_iterations=6)
    straight = als.fit(mesh8, cfg)
    seg = als.fit(mesh8, cfg, checkpoint_dir=str(tmp_path / "als"),
                  checkpoint_every=2)
    np.testing.assert_array_equal(np.asarray(straight.U), np.asarray(seg.U))
    np.testing.assert_array_equal(np.asarray(straight.V), np.asarray(seg.V))
    np.testing.assert_array_equal(np.asarray(straight.rmse_history),
                                  np.asarray(seg.rmse_history))


def test_lr_segmented_equals_straight(mesh8, data, tmp_path):
    from tpu_distalg.models import logistic_regression as lr

    X_train, y_train, X_test, y_test = data
    cfg = lr.LRConfig(n_iterations=80)
    straight = lr.train(X_train, y_train, X_test, y_test, mesh8, cfg)
    seg = lr.train(X_train, y_train, X_test, y_test, mesh8, cfg,
                   checkpoint_dir=str(tmp_path / "lr"),
                   checkpoint_every=30)
    np.testing.assert_array_equal(np.asarray(straight.w), np.asarray(seg.w))


def test_incompatible_checkpoint_rejected(mesh8, data, tmp_path):
    """A checkpoint written by another workload (different state shape)
    fails with a clear message, not a KeyError."""
    from tpu_distalg.models import bmuf

    X_train, y_train, X_test, y_test = data
    d = str(tmp_path / "ck")
    ssgd.train(X_train, y_train, X_test, y_test, mesh8,
               ssgd.SSGDConfig(n_iterations=20), checkpoint_dir=d,
               checkpoint_every=20)
    with pytest.raises(ValueError, match="incompatible"):
        bmuf.train(X_train, y_train, X_test, y_test, mesh8,
                   bmuf.BMUFConfig(n_iterations=40), checkpoint_dir=d)


def test_checkpoint_every_validated(mesh8, data, tmp_path):
    X_train, y_train, X_test, y_test = data
    with pytest.raises(ValueError, match="checkpoint_every"):
        ssgd.train(X_train, y_train, X_test, y_test, mesh8,
                   ssgd.SSGDConfig(n_iterations=20),
                   checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=0)


def test_segmented_with_eval_every(mesh8, data, tmp_path):
    """eval_every>1 across segment boundaries: the carried last-acc is
    checkpointed, so segmented == straight including the held values."""
    X_train, y_train, X_test, y_test = data
    cfg = ssgd.SSGDConfig(n_iterations=100, eval_every=7)
    straight = ssgd.train(X_train, y_train, X_test, y_test, mesh8, cfg)
    seg = ssgd.train(X_train, y_train, X_test, y_test, mesh8, cfg,
                     checkpoint_dir=str(tmp_path / "ee"),
                     checkpoint_every=40)
    np.testing.assert_array_equal(np.asarray(straight.w), np.asarray(seg.w))
    np.testing.assert_array_equal(
        np.asarray(straight.accs), np.asarray(seg.accs))


def test_run_with_restarts_retries_then_succeeds():
    """The watchdog core: transient failures re-run; the retry budget
    is respected; success stops the loop."""
    from tpu_distalg.utils import checkpoint as ckpt

    calls = {"n": 0}
    logs = []

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise RuntimeError("injected transient crash")
        return "done"

    assert ckpt.run_with_restarts(flaky, max_restarts=2,
                                  logger=logs.append) == "done"
    assert calls["n"] == 3 and len(logs) == 2

    calls["n"] = 0
    with pytest.raises(RuntimeError, match="injected"):
        ckpt.run_with_restarts(flaky, max_restarts=1)

    with pytest.raises(ValueError, match="max_restarts"):
        ckpt.run_with_restarts(flaky, max_restarts=-1)


def test_watchdog_recovers_bitwise_from_guard_trip(mesh8, data, tmp_path,
                                                   monkeypatch):
    """The verdict's failure-recovery scenario end-to-end: a NaN-guard
    trip mid-run kills the job after segment 1 is checkpointed; the
    auto-restart re-runs, resumes from step 40, and the recovered
    weights and accuracy history are BITWISE equal to an uninterrupted
    run (sampling keys on absolute step ids)."""
    from tpu_distalg.utils import checkpoint as ckpt
    from tpu_distalg.utils import metrics

    X_train, y_train, X_test, y_test = data
    cfg = ssgd.SSGDConfig(n_iterations=120)
    straight = ssgd.train(X_train, y_train, X_test, y_test, mesh8, cfg)

    real_guard = metrics.guard_finite
    trips = {"armed": True}

    def tripping_guard(tree, what):
        real_guard(tree, what)
        # simulate a non-finite state detected after the SECOND segment
        # (step 80) of the first attempt — exactly once
        if trips["armed"] and "step 80" in what:
            trips["armed"] = False
            raise FloatingPointError(f"injected NaN in {what}")

    monkeypatch.setattr(metrics, "guard_finite", tripping_guard)

    def run_once():
        return ssgd.train(
            X_train, y_train, X_test, y_test, mesh8, cfg,
            checkpoint_dir=str(tmp_path / "wd"), checkpoint_every=40)

    res = ckpt.run_with_restarts(run_once, max_restarts=1,
                                 logger=lambda m: None)
    assert not trips["armed"], "the injected guard trip never fired"
    np.testing.assert_array_equal(np.asarray(straight.w),
                                  np.asarray(res.w))
    np.testing.assert_array_equal(np.asarray(straight.accs),
                                  np.asarray(res.accs))


# ---- non-optimizer workloads (r4 verdict ask #5): Spark gives the
# reference task retry on every script, so every workload here must
# checkpoint/resume, not just the SGD family ----


def test_kmeans_segmented_equals_straight(mesh4, tmp_path):
    from tpu_distalg.models import kmeans
    from tpu_distalg.utils import datasets

    pts = datasets.gaussian_mixture(4000, k=3, seed=1)
    cfg = kmeans.KMeansConfig(k=3, n_iterations=10)
    straight = kmeans.fit(pts, mesh4, cfg)
    seg = kmeans.fit(pts, mesh4, cfg,
                     checkpoint_dir=str(tmp_path / "km"),
                     checkpoint_every=4)
    np.testing.assert_array_equal(np.asarray(straight.centers),
                                  np.asarray(seg.centers))
    assert seg.n_iterations_run == 10


def test_kmeans_resume_from_checkpoint(mesh4, tmp_path):
    from tpu_distalg.models import kmeans
    from tpu_distalg.utils import datasets

    pts = datasets.gaussian_mixture(4000, k=3, seed=1)
    d = str(tmp_path / "km")
    kmeans.fit(pts, mesh4, kmeans.KMeansConfig(k=3, n_iterations=4),
               checkpoint_dir=d, checkpoint_every=4)
    resumed = kmeans.fit(pts, mesh4,
                         kmeans.KMeansConfig(k=3, n_iterations=10),
                         checkpoint_dir=d, checkpoint_every=4)
    straight = kmeans.fit(pts, mesh4,
                          kmeans.KMeansConfig(k=3, n_iterations=10))
    np.testing.assert_array_equal(np.asarray(straight.centers),
                                  np.asarray(resumed.centers))


def test_kmeans_converge_mode_segmented(mesh4, tmp_path):
    """Converge mode carries (shift, n_run) across segments: same
    centers and same iteration count as the straight while_loop, and
    convergence stops the segment loop early (stop_when)."""
    from tpu_distalg.models import kmeans
    from tpu_distalg.utils import datasets

    pts = datasets.gaussian_mixture(4000, k=3, seed=1)
    cfg = kmeans.KMeansConfig(k=3, converge_dist=1e-4,
                              max_iterations=200)
    straight = kmeans.fit(pts, mesh4, cfg)
    seg = kmeans.fit(pts, mesh4, cfg,
                     checkpoint_dir=str(tmp_path / "km"),
                     checkpoint_every=5)
    assert straight.n_iterations_run < 200  # actually converged
    assert seg.n_iterations_run == straight.n_iterations_run
    np.testing.assert_array_equal(np.asarray(straight.centers),
                                  np.asarray(seg.centers))
    # far fewer checkpoints than max_iterations/5 segments were written
    from tpu_distalg.utils import checkpoint as ckpt

    assert ckpt.latest_step(str(tmp_path / "km")) <= \
        straight.n_iterations_run + 5


def test_pagerank_segmented_equals_straight(mesh4, tmp_path):
    from tpu_distalg.models import pagerank
    from tpu_distalg.utils import datasets

    edges = datasets.erdos_renyi_edges(400, 4.0, seed=2)
    for mode in ("reference", "standard"):
        cfg = pagerank.PageRankConfig(n_iterations=10, mode=mode)
        straight = pagerank.run(edges, mesh4, cfg)
        seg = pagerank.run(edges, mesh4, cfg,
                           checkpoint_dir=str(tmp_path / f"pr_{mode}"),
                           checkpoint_every=4)
        np.testing.assert_array_equal(np.asarray(straight.ranks),
                                      np.asarray(seg.ranks))
        np.testing.assert_array_equal(np.asarray(straight.has_rank),
                                      np.asarray(seg.has_rank))


def test_pagerank_resume_from_checkpoint(mesh4, tmp_path):
    from tpu_distalg.models import pagerank
    from tpu_distalg.utils import datasets

    edges = datasets.erdos_renyi_edges(400, 4.0, seed=2)
    d = str(tmp_path / "pr")
    pagerank.run(edges, mesh4,
                 pagerank.PageRankConfig(n_iterations=4,
                                         mode="standard"),
                 checkpoint_dir=d, checkpoint_every=4)
    resumed = pagerank.run(
        edges, mesh4,
        pagerank.PageRankConfig(n_iterations=10, mode="standard"),
        checkpoint_dir=d, checkpoint_every=4)
    straight = pagerank.run(
        edges, mesh4,
        pagerank.PageRankConfig(n_iterations=10, mode="standard"))
    np.testing.assert_array_equal(np.asarray(straight.ranks),
                                  np.asarray(resumed.ranks))


def test_closure_dense_segmented_and_resume(mesh4, tmp_path):
    from tpu_distalg.models import transitive_closure as tc
    from tpu_distalg.utils import datasets

    edges = datasets.chain_forest_edges(48)
    straight = tc.run(edges, mesh4)
    d = str(tmp_path / "cl")
    seg = tc.run(edges, mesh4, checkpoint_dir=d, checkpoint_every=2)
    assert seg.n_paths == straight.n_paths
    assert seg.n_rounds == straight.n_rounds
    np.testing.assert_array_equal(np.asarray(straight.paths),
                                  np.asarray(seg.paths))

    # resume: cap the fixpoint at 3 rounds (simulated interruption),
    # then rerun uncapped from the same directory
    d2 = str(tmp_path / "cl2")
    tc.run(edges, mesh4, tc.ClosureConfig(max_iterations=3),
           checkpoint_dir=d2, checkpoint_every=2)
    resumed = tc.run(edges, mesh4, checkpoint_dir=d2,
                     checkpoint_every=2)
    assert resumed.n_paths == straight.n_paths
    np.testing.assert_array_equal(np.asarray(straight.paths),
                                  np.asarray(resumed.paths))


def test_closure_sparse_segmented_and_resume(mesh4, tmp_path):
    from tpu_distalg.models import transitive_closure as tc
    from tpu_distalg.utils import datasets

    edges = datasets.chain_forest_edges(48)
    straight = tc.run_sparse(edges, mesh4)
    seg = tc.run_sparse(edges, mesh4,
                        checkpoint_dir=str(tmp_path / "cls"),
                        checkpoint_every=2)
    assert seg.n_paths == straight.n_paths
    assert seg.n_rounds == straight.n_rounds
    np.testing.assert_array_equal(straight.paths, seg.paths)

    d2 = str(tmp_path / "cls2")
    tc.run_sparse(edges, mesh4,
                  tc.SparseClosureConfig(max_iterations=3),
                  checkpoint_dir=d2, checkpoint_every=2)
    resumed = tc.run_sparse(edges, mesh4, checkpoint_dir=d2,
                            checkpoint_every=2)
    assert resumed.n_paths == straight.n_paths
    np.testing.assert_array_equal(straight.paths, resumed.paths)


def test_workload_checkpoint_dirs_not_interchangeable(mesh4, tmp_path):
    """A k-means directory must not resume a PageRank run: the tag check
    fails loudly (the same contract the optimizer family has)."""
    from tpu_distalg.models import kmeans, pagerank
    from tpu_distalg.utils import datasets

    pts = datasets.gaussian_mixture(4000, k=3, seed=1)
    d = str(tmp_path / "mix")
    kmeans.fit(pts, mesh4, kmeans.KMeansConfig(k=3, n_iterations=4),
               checkpoint_dir=d, checkpoint_every=4)
    edges = datasets.erdos_renyi_edges(400, 4.0, seed=2)
    with pytest.raises(ValueError, match="incompatible"):
        pagerank.run(edges, mesh4,
                     pagerank.PageRankConfig(n_iterations=10),
                     checkpoint_dir=d, checkpoint_every=4)

    # cross-MODE resumes must also fail: the state signatures alias
    # ((V,) f32 pair for pagerank; fixed-mode kmeans saves shift=0.0,
    # which converge mode would read as "already converged")
    d2 = str(tmp_path / "pr_ref")
    pagerank.run(edges, mesh4,
                 pagerank.PageRankConfig(n_iterations=4,
                                         mode="reference"),
                 checkpoint_dir=d2, checkpoint_every=4)
    with pytest.raises(ValueError, match="incompatible"):
        pagerank.run(edges, mesh4,
                     pagerank.PageRankConfig(n_iterations=10,
                                             mode="standard"),
                     checkpoint_dir=d2, checkpoint_every=4)
    with pytest.raises(ValueError, match="incompatible"):
        kmeans.fit(pts, mesh4,
                   kmeans.KMeansConfig(k=3, converge_dist=1e-4),
                   checkpoint_dir=d, checkpoint_every=4)


def test_corrupt_checkpoint_falls_back_in_process(mesh8, data, tmp_path):
    """Advisor r4's quarantine scenario, upgraded by PR 3: a corrupt
    NEWEST checkpoint no longer even costs a ``run_with_restarts``
    cycle — the resume path quarantines it and falls back to the
    next-older step IN-PROCESS, bitwise-equal to a straight run."""
    import os

    from tpu_distalg.utils import checkpoint as ckpt

    X_train, y_train, X_test, y_test = data
    d = str(tmp_path / "ck")
    ssgd.train(X_train, y_train, X_test, y_test, mesh8,
               ssgd.SSGDConfig(n_iterations=60),
               checkpoint_dir=d, checkpoint_every=30)  # steps 30, 60
    newest = os.path.join(d, "step_60.msgpack")
    with open(newest, "wb") as f:
        f.write(b"\xff\xfe not msgpack")

    # direct resume — no watchdog wrapper anywhere in sight
    resumed = ssgd.train(X_train, y_train, X_test, y_test, mesh8,
                         ssgd.SSGDConfig(n_iterations=120),
                         checkpoint_dir=d, checkpoint_every=30)
    assert os.path.exists(newest + ".corrupt")
    straight = ssgd.train(X_train, y_train, X_test, y_test, mesh8,
                          ssgd.SSGDConfig(n_iterations=120))
    np.testing.assert_array_equal(np.asarray(straight.w),
                                  np.asarray(resumed.w))
    np.testing.assert_array_equal(np.asarray(straight.accs),
                                  np.asarray(resumed.accs))


def test_all_checkpoints_corrupt_means_fresh_start(mesh8, data, tmp_path):
    """When EVERY checkpoint is corrupt the fallback walks the whole
    chain, quarantines each, and restarts from step 0 — still
    bitwise-equal to a straight run, never an unhandled error."""
    import os

    from tpu_distalg.utils import checkpoint as ckpt

    X_train, y_train, X_test, y_test = data
    d = str(tmp_path / "ck")
    ssgd.train(X_train, y_train, X_test, y_test, mesh8,
               ssgd.SSGDConfig(n_iterations=60),
               checkpoint_dir=d, checkpoint_every=30)
    for name in list(os.listdir(d)):
        if name.endswith(".msgpack"):
            with open(os.path.join(d, name), "wb") as f:
                f.write(b"junk")
    resumed = ssgd.train(X_train, y_train, X_test, y_test, mesh8,
                         ssgd.SSGDConfig(n_iterations=60),
                         checkpoint_dir=d, checkpoint_every=30)
    assert ckpt.latest_step(d) == 60  # re-ran and re-checkpointed
    straight = ssgd.train(X_train, y_train, X_test, y_test, mesh8,
                          ssgd.SSGDConfig(n_iterations=60))
    np.testing.assert_array_equal(np.asarray(straight.w),
                                  np.asarray(resumed.w))


def test_run_with_restarts_still_quarantines_direct_corruption(tmp_path):
    """The watchdog-level quarantine path survives for DIRECT restore
    callers (explicit-step loads, non-segmented users): budget-free
    quarantine, then success."""
    import os

    from tpu_distalg.utils import checkpoint as ckpt

    path = str(tmp_path / "step_5.msgpack")
    with open(path, "wb") as f:
        f.write(b"junk")
    msgs = []

    def run_once():
        if os.path.exists(path):
            raise ckpt.CorruptCheckpointError(path, "boom")
        return "ok"

    assert ckpt.run_with_restarts(run_once, max_restarts=1,
                                  logger=msgs.append) == "ok"
    assert os.path.exists(path + ".corrupt")
    assert any("0/1 used" in m for m in msgs)

    # max_restarts=0 still means "no recovery of any kind"
    with open(path, "wb") as f:
        f.write(b"junk")
    with pytest.raises(ckpt.CorruptCheckpointError):
        ckpt.run_with_restarts(run_once, max_restarts=0)


# ---- durability: CRC32 footer + fsync + write retry (PR 3) ----


def test_crc_footer_detects_torn_write(tmp_path):
    """A flipped byte ANYWHERE in the payload — even one that still
    msgpack-parses — is a CorruptCheckpointError, not a silent resume
    from garbage."""
    import os

    from tpu_distalg.utils import checkpoint as ckpt

    d = str(tmp_path / "ck")
    p = ckpt.save(d, {"w": np.arange(64, dtype=np.float32)}, step=1)
    raw = bytearray(open(p, "rb").read())
    raw[len(raw) // 2] ^= 0xFF  # well inside the payload
    with open(p, "wb") as f:
        f.write(raw)
    with pytest.raises(ckpt.CorruptCheckpointError, match="CRC32") as ei:
        ckpt.restore(d)
    assert ei.value.path == p  # carried for the quarantine fallback
    assert os.path.exists(p)   # detection does not quarantine by itself


def test_crc_footer_roundtrip_and_legacy_footerless(tmp_path):
    from flax import serialization

    from tpu_distalg.utils import checkpoint as ckpt

    d = str(tmp_path / "ck")
    tree = {"w": np.arange(8, dtype=np.float32),
            "step": np.int32(7)}
    ckpt.save(d, tree, step=2)
    got, step = ckpt.restore(d)
    assert step == 2
    np.testing.assert_array_equal(got["w"], tree["w"])

    # a pre-PR-3 checkpoint has no footer: still restorable (its only
    # guard is msgpack parseability, as before)
    legacy = serialization.msgpack_serialize(
        {"w": np.ones(3, np.float32)})
    import os

    with open(os.path.join(d, "step_9.msgpack"), "wb") as f:
        f.write(legacy)
    got9, step9 = ckpt.restore(d)
    assert step9 == 9
    np.testing.assert_array_equal(got9["w"], np.ones(3, np.float32))


def test_save_retries_transient_oserror(tmp_path):
    from tpu_distalg import faults
    from tpu_distalg.utils import checkpoint as ckpt

    try:
        faults.configure("seed=1;ckpt:write@0=oserror")
        ckpt.save(str(tmp_path), {"w": np.zeros(4, np.float32)}, step=3)
        assert faults.active().fired == [("ckpt:write", 0, "oserror")]
    finally:
        faults.configure(False)
    got, step = ckpt.restore(str(tmp_path))
    assert step == 3
    np.testing.assert_array_equal(got["w"], np.zeros(4, np.float32))


def test_injected_disk_corruption_is_caught_by_crc(tmp_path):
    """The fault registry's ``corrupt`` at ckpt:write REALLY flips the
    bytes that hit disk; the CRC (computed over the true payload)
    catches it on restore."""
    from tpu_distalg import faults
    from tpu_distalg.utils import checkpoint as ckpt

    try:
        faults.configure("seed=2;ckpt:write@0=corrupt")
        ckpt.save(str(tmp_path), {"w": np.arange(32, dtype=np.float32)},
                  step=1)
    finally:
        faults.configure(False)
    with pytest.raises(ckpt.CorruptCheckpointError, match="CRC32"):
        ckpt.restore(str(tmp_path))


def test_quarantine_and_prune_tolerate_concurrent_races(tmp_path,
                                                        monkeypatch):
    """A concurrent restart's quarantine/prune racing ours: the file
    being already gone is the DESIRED state, not an error."""
    import os

    from tpu_distalg.utils import checkpoint as ckpt

    assert ckpt.quarantine(str(tmp_path / "never_existed.msgpack"))

    # prune sees a listing with a file another process just removed
    real_listdir = os.listdir
    ghost = ["step_1.msgpack", "step_2.msgpack", "step_3.msgpack",
             "step_4.msgpack"]
    monkeypatch.setattr(os, "listdir",
                        lambda d: ghost if str(d) == str(tmp_path)
                        else real_listdir(d))
    ckpt.prune(str(tmp_path), keep=1)  # must not raise


# ---- preemption: SIGTERM mid-run, distinct rc, bitwise resume ----


def test_sigterm_preempts_at_boundary_and_resume_is_bitwise(tmp_path):
    """The acceptance scenario end-to-end in real subprocesses: SIGTERM
    delivered mid-run exits with the distinct preemption rc having
    saved a boundary checkpoint, and the resumed run's weights equal an
    uninterrupted run's bitwise. The per-segment hang fault keeps the
    run slow enough to signal deterministically — and doubles as proof
    that an injected-hang run's trajectory is untouched."""
    import glob
    import os
    import signal
    import subprocess
    import sys
    import time

    from tpu_distalg import faults
    from tpu_distalg.utils import checkpoint as ckpt

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               TDA_TELEMETRY_DIR="", TDA_FAULT_PLAN="")

    def cmd(d, plan=None):
        c = [sys.executable, "-m", "tpu_distalg.cli", "lr",
             "--n-slices", "2", "--n-iterations", "300",
             "--checkpoint-dir", d, "--checkpoint-every", "20",
             "--quiet"]
        return c + (["--fault-plan", plan] if plan else [])

    d_pre = str(tmp_path / "pre")
    d_ref = str(tmp_path / "ref")

    p = subprocess.Popen(
        cmd(d_pre, "seed=1;segment:run@*=hang:0.15"), env=env, cwd=repo,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    deadline = time.time() + 180
    while time.time() < deadline:
        if len(glob.glob(os.path.join(d_pre, "step_*.msgpack"))) >= 2:
            break
        if p.poll() is not None:
            break
        time.sleep(0.02)
    assert p.poll() is None, \
        f"run finished before SIGTERM landed: {p.communicate()}"
    p.send_signal(signal.SIGTERM)
    out, err = p.communicate(timeout=180)
    assert p.returncode == faults.PREEMPTED_RC, (p.returncode, out, err)
    step_pre = ckpt.latest_step(d_pre)
    assert step_pre is not None and 0 < step_pre < 300
    assert step_pre % 20 == 0  # a BOUNDARY checkpoint, not a torn one

    # resume (no fault plan: hangs only delayed the preempted run, so
    # the trajectory is identical) and an uninterrupted reference
    r = subprocess.run(cmd(d_pre), env=env, cwd=repo,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
    r2 = subprocess.run(cmd(d_ref), env=env, cwd=repo,
                        capture_output=True, text=True, timeout=300)
    assert r2.returncode == 0, (r2.returncode, r2.stdout, r2.stderr)

    tree_a, step_a = ckpt.restore(d_pre)
    tree_b, step_b = ckpt.restore(d_ref)
    assert step_a == step_b == 300
    for a, b in zip(tree_a["state"], tree_b["state"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(tree_a["accs"]),
                                  np.asarray(tree_b["accs"]))


def test_fused_train_segment_guard_catches_all_segment_lengths(data, tmp_path):
    """Advisor r3: eval_test=True with checkpoint_every not a multiple
    of mega_steps used to raise the builder's 'segment boundaries'
    error MID-RUN; the guard must fire up front — including for the
    remainder segment. (fused_train is dp=1-only, so a 1-shard mesh.)"""
    from tpu_distalg.parallel import get_mesh

    mesh1 = get_mesh(data=1)
    X_train, y_train, X_test, y_test = data
    cfg = ssgd.SSGDConfig(n_iterations=500, sampler="fused_train",
                          mega_steps=125, eval_every=125,
                          fused_pack=4, gather_block_rows=32,
                          shuffle_seed=0)
    # checkpoint_every < mega_steps with eval_test: segment mega=100
    # != eval_every=125 -> up-front error
    with pytest.raises(ValueError, match="launch boundary"):
        ssgd.train(X_train, y_train, X_test, y_test, mesh1, cfg,
                   checkpoint_dir=str(tmp_path / "guard_a"),
                   checkpoint_every=100)
    # full length is valid (500 % 125 == 0) but the segment is not:
    # checkpoint_every=300 -> segment mega=125 doesn't divide 300 —
    # must fail up front, not at the second segment build mid-run
    with pytest.raises(ValueError, match="not divisible by mega_steps"):
        ssgd.train(X_train, y_train, X_test, y_test, mesh1, cfg,
                   checkpoint_dir=str(tmp_path / "guard_b"),
                   checkpoint_every=300)
