"""'fused_train' megakernel: whole-schedule-in-one-launch SSGD must be
the same algorithm as the per-step 'fused_gather' path — same sampling,
same update — differing only in float reduction order."""

import dataclasses
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg.models import ssgd

CFG = ssgd.SSGDConfig(
    n_iterations=60, eval_test=False, sampler="fused_train",
    mega_steps=20, fused_pack=4, gather_block_rows=32, shuffle_seed=0,
)


def _train_w(data, mesh, config, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # coarse-fraction geometry warn
        return ssgd.train(*data, mesh, config, **kw)


def test_fused_train_matches_fused_gather(mesh1, cancer_data):
    w_mega = _train_w(cancer_data, mesh1, CFG).w
    w_step = _train_w(
        cancer_data, mesh1,
        dataclasses.replace(CFG, sampler="fused_gather"),
    ).w
    np.testing.assert_allclose(
        np.asarray(w_mega), np.asarray(w_step), rtol=1e-5, atol=1e-5)


def test_fused_train_eval_at_segment_boundaries(mesh1, cancer_data):
    res = _train_w(
        cancer_data, mesh1,
        dataclasses.replace(CFG, eval_test=True, eval_every=20),
    )
    accs = np.asarray(res.accs)
    assert accs.shape == (60,)
    # positions within a segment carry the PREVIOUS boundary's acc
    assert accs[0] == accs[10] == 0.0  # seeded acc0
    assert accs[19] > 0.0              # first boundary eval
    assert accs[20] == accs[19]
    assert res.final_acc == accs[59] > 0.0


def test_fused_train_checkpoint_resume_bitwise(mesh1, cancer_data,
                                               tmp_path):
    straight = _train_w(cancer_data, mesh1, CFG).w
    segmented = _train_w(
        cancer_data, mesh1, CFG,
        checkpoint_dir=str(tmp_path), checkpoint_every=20,
    ).w
    np.testing.assert_array_equal(
        np.asarray(straight), np.asarray(segmented))


def test_fused_train_validation(mesh8, mesh1, cancer_data):
    with pytest.raises(ValueError, match="single-data-shard"):
        _train_w(cancer_data, mesh8,
                 dataclasses.replace(CFG, gather_block_rows=32))
    with pytest.raises(ValueError, match="lam=0"):
        _train_w(cancer_data, mesh1,
                 dataclasses.replace(CFG, lam=0.01))
    with pytest.raises(ValueError, match="divisible"):
        _train_w(cancer_data, mesh1,
                 dataclasses.replace(CFG, n_iterations=61))
    with pytest.raises(ValueError, match="segment boundaries"):
        _train_w(cancer_data, mesh1,
                 dataclasses.replace(CFG, eval_test=True, eval_every=1))
    with pytest.raises(ValueError, match="checkpoint_every"):
        _train_w(cancer_data, mesh1, CFG,
                 checkpoint_dir="/tmp/mega_ckpt_invalid",
                 checkpoint_every=30)  # > mega_steps=20, not a multiple


def test_fused_train_bf16_matches_fused_gather_bf16(mesh1, cancer_data):
    """bf16 X path: both samplers quantize the f32 weight master to a
    bf16 selector per step, so their trajectories track each other (the
    right oracle — bf16 vs f32 training legitimately diverges)."""
    w_mega = _train_w(
        cancer_data, mesh1,
        dataclasses.replace(CFG, x_dtype="bfloat16"),
    ).w
    w_step = _train_w(
        cancer_data, mesh1,
        dataclasses.replace(CFG, x_dtype="bfloat16",
                            sampler="fused_gather"),
    ).w
    assert np.isfinite(np.asarray(w_mega)).all()
    np.testing.assert_allclose(
        np.asarray(w_mega), np.asarray(w_step), rtol=2e-2, atol=2e-2)


def test_fused_train_t0_offset_continuity(mesh1, cancer_data):
    """Two 30-step runs chained via t0 equal one 60-step run: the
    absolute-step-keyed sampling survives segmentation by hand too."""
    X_train, y_train, X_test, y_test = cancer_data
    fn, X2, w0, meta = ssgd.prepare_fused(
        X_train, y_train, mesh1,
        dataclasses.replace(CFG, n_iterations=60, mega_steps=10))
    dummy = jnp.zeros((1,), jnp.float32)
    te = (jnp.zeros((1, meta["d_total"]), jnp.float32),
          jnp.zeros((1,), jnp.float32))
    w_full, _ = fn(X2, dummy, dummy, te[0], te[1], w0)

    fn30 = ssgd.make_train_fn_fused(
        mesh1,
        dataclasses.replace(CFG, n_iterations=30, mega_steps=10), meta)
    w_half, _ = fn30(X2, dummy, dummy, te[0], te[1], w0, t0=0)
    w_both, _ = fn30(X2, dummy, dummy, te[0], te[1], w_half, t0=30)
    np.testing.assert_array_equal(np.asarray(w_full), np.asarray(w_both))


def test_local_sgd_fused_train_matches_fused_gather(mesh4, cancer_data):
    """The local-update family's megakernel: each round's n_local steps
    run as ONE launch per replica. Must match the per-step fused path on
    a 4-replica mesh for all three combine rules (MA/BMUF/EASGD) — this
    is the dp>1 composition SSGD's megakernel cannot do, plus the
    in-kernel elastic pull."""
    from tpu_distalg.models import bmuf, easgd, ma

    for mod, cfg_cls in ((ma, ma.MAConfig), (bmuf, bmuf.BMUFConfig),
                         (easgd, easgd.EASGDConfig)):
        # 5 rounds: the paths differ only in f32 reduction order, and
        # SGD on the unnormalized cancer features amplifies ~1.9x per
        # round (measured: 2e-7 after 1 round, 4e-5 after 5) — tight
        # equality is only meaningful over a short horizon
        base = dict(n_iterations=5, fused_pack=4, gather_block_rows=32,
                    shuffle_seed=0, eval_test=False)
        r_mega = mod.train(*cancer_data, mesh4,
                           cfg_cls(sampler="fused_train", **base))
        r_step = mod.train(*cancer_data, mesh4,
                           cfg_cls(sampler="fused_gather", **base))
        np.testing.assert_allclose(
            np.asarray(r_mega.w), np.asarray(r_step.w), atol=1e-3,
            err_msg=f"{mod.__name__} megakernel != per-step")
        np.testing.assert_allclose(
            np.asarray(r_mega.ws), np.asarray(r_step.ws), atol=1e-3)


def test_local_sgd_fused_train_converges(mesh4, cancer_data):
    """Full-horizon run: the chaotic divergence from the per-step path
    stays inside the reference convergence band (ma.py golden 0.8538;
    the deterministic fused_gather run measures 0.9415)."""
    from tpu_distalg.models import ma

    res = ma.train(*cancer_data, mesh4, ma.MAConfig(
        n_iterations=300, sampler="fused_train", fused_pack=4,
        gather_block_rows=32, shuffle_seed=0))
    # band anchored to MA's reference golden 0.8538 (ma.py:131): the
    # original rig measures 0.9415 here, this container 0.8889 —
    # both converge above the reference
    assert res.final_acc > 0.85, res.final_acc


def test_local_sgd_fused_train_checkpoint_bitwise(mesh4, cancer_data,
                                                  tmp_path):
    from tpu_distalg.models import ma

    cfg = ma.MAConfig(n_iterations=30, sampler="fused_train",
                      fused_pack=4, gather_block_rows=32, shuffle_seed=0)
    straight = ma.train(*cancer_data, mesh4, cfg).w
    seg = ma.train(*cancer_data, mesh4, cfg,
                   checkpoint_dir=str(tmp_path), checkpoint_every=10).w
    np.testing.assert_array_equal(np.asarray(straight), np.asarray(seg))


# ---- the megakernel's block loop over the shapes it branches on (PR 27;
# the cases and their tables are tests/test_pallas.py's)

from test_pallas import SCHEDULE_CASES, schedule_case  # noqa: E402


@pytest.mark.parametrize("name", sorted(SCHEDULE_CASES))
def test_fused_train_kernel_over_schedule_shapes(name):
    """``fused_train_gathered`` over (T, n_sampled) block ids equals the
    per-step 'fused_gather' trajectory: one ``fused_grad_sum_gathered``
    launch and the same update (and elastic pull) a step."""
    import jax

    from tpu_distalg.ops import pallas_kernels as pk

    X2, meta, w_aug, ids, alpha = schedule_case(name)
    P, D, eta = meta["pack"], meta["d_total"], 0.1
    kw = dict(pack=P, d_total=D, y_col=meta["y_col"], v_col=meta["v_col"],
              gather_block_rows=meta["gather_block_rows"], interpret=True)
    keep = (np.arange(D) < meta["y_col"]).astype(np.float32)
    centre = w_aug + 0.05 * keep
    with jax.default_matmul_precision("highest"):
        wt = pk.fused_train_gathered(
            X2, jnp.tile(jnp.asarray(w_aug), P)[:, None], jnp.asarray(ids),
            eta=eta, alpha=alpha,
            center_tile=jnp.tile(jnp.asarray(centre), P)[:, None], **kw)
        w = jnp.asarray(w_aug)
        for step_ids in ids:
            g, cnt = pk.fused_grad_sum_gathered(
                X2, w, jnp.asarray(step_ids), **kw)
            w = (w - eta * g * keep / jnp.maximum(cnt, 1.0)
                 - alpha * (w - centre))
    wt = np.asarray(wt).reshape(P, D)
    assert np.abs(wt - wt[0]).max() == 0.0      # every slot the same w
    assert np.abs(wt[0] - w_aug).max() > 1e-4   # and it moved
    np.testing.assert_allclose(wt[0], np.asarray(w), rtol=1e-5, atol=1e-6)
