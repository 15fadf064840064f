"""The packed layout and the gathered kernels vs the XLA path
(interpret mode on CPU; the same kernels compile to Mosaic on TPU —
exercised by tests_tpu/ and chip_smoke.py)."""

import jax.numpy as jnp
import numpy as np

from tpu_distalg.ops import logistic


# ---- the packed layout: packing and selector algebra are pure XLA ----

from tpu_distalg.ops.pallas_kernels import build_selector, pack_augmented


def test_pack_augmented_layout():
    rng = np.random.default_rng(4)
    n, d = 300, 13
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, 2, n).astype(np.float32)
    X2, meta = pack_augmented(X, y, np.ones(n, np.float32),
                              dtype=jnp.float32, pack=16, block_rows=128)
    P, D = meta["pack"], meta["d_total"]
    assert (P * D) % 128 == 0
    assert meta["n_padded"] % 128 == 0
    flat = np.asarray(X2).reshape(meta["n_padded"], D)
    np.testing.assert_array_equal(flat[:n, :d], X)
    np.testing.assert_array_equal(flat[:n, meta["y_col"]], y)
    np.testing.assert_array_equal(flat[:n, meta["v_col"]], 1.0)
    # padded rows are invalid
    np.testing.assert_array_equal(flat[n:, meta["v_col"]], 0.0)


def test_build_selector_algebra():
    """x2 @ [Wbig|Ey|Ev] must reproduce (z, y, v) for every packed slot."""
    rng = np.random.default_rng(5)
    n, d = 64, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, 2, n).astype(np.float32)
    X2, meta = pack_augmented(X, y, np.ones(n, np.float32),
                              dtype=jnp.float32, pack=16, block_rows=64)
    P, D = meta["pack"], meta["d_total"]
    w = rng.normal(size=(d,)).astype(np.float32)
    w_aug = np.zeros(D, np.float32)
    w_aug[:d] = w
    C = np.asarray(build_selector(
        jnp.asarray(w_aug), pack=P, d_total=D, y_col=meta["y_col"],
        v_col=meta["v_col"], dtype=jnp.float32))
    zyv = np.asarray(X2) @ C                       # (n/P, 3P)
    flat = np.asarray(X2).reshape(meta["n_padded"], D)
    z_expect = flat @ w_aug
    for r in range(zyv.shape[0]):
        for c in range(P):
            i = r * P + c
            np.testing.assert_allclose(zyv[r, c], z_expect[i], rtol=1e-5)
            assert zyv[r, P + c] == flat[i, meta["y_col"]]
            assert zyv[r, 2 * P + c] == flat[i, meta["v_col"]]


# ---- gathered one-pass kernel (v4): fully CPU-testable (no on-core
# PRNG — sampling happens in the scalar-prefetch block index map) ----

import jax

from tpu_distalg.ops.pallas_kernels import fused_grad_sum_gathered


def _packed_case(n=400, d=30, seed=6, pack=16, gbr=128):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, 2, n).astype(np.float32)
    X2, meta = pack_augmented(X, y, np.ones(n, np.float32),
                              dtype=jnp.float32, pack=pack, block_rows=gbr)
    w_aug = np.zeros(meta["d_total"], np.float32)
    w_aug[:d] = rng.normal(size=(d,)).astype(np.float32) * 0.1
    return X, y, X2, meta, w_aug


def test_gathered_kernel_matches_flat_grad_sum():
    """End-to-end algebra of the v4 kernel — forward selector matmul,
    backward (P, P·D) accumulation AND the einsum('ccj->j') diagonal-band
    fold — against ``logistic.grad_sum`` on the flat layout restricted to
    the gathered rows.  ``precision='highest'`` pins the default-matmul
    bf16 passes that would otherwise dominate the comparison."""
    X, y, X2, meta, w_aug = _packed_case()
    gbr = 128
    blocks = [0, 2, 3]
    with jax.default_matmul_precision("highest"):
        g, cnt = fused_grad_sum_gathered(
            X2, jnp.asarray(w_aug), jnp.asarray(blocks, jnp.int32),
            pack=meta["pack"], d_total=meta["d_total"],
            y_col=meta["y_col"], v_col=meta["v_col"],
            gather_block_rows=gbr, interpret=True)
        rows = np.concatenate(
            [np.arange(b * gbr, (b + 1) * gbr) for b in blocks])
        flat = np.asarray(X2).reshape(meta["n_padded"], meta["d_total"])
        valid = flat[rows, meta["v_col"]]
        g_ref, cnt_ref = logistic.grad_sum(
            jnp.asarray(flat[rows, :X.shape[1]]),
            jnp.asarray(flat[rows, meta["y_col"]]),
            jnp.asarray(w_aug[:X.shape[1]]), jnp.asarray(valid))
    assert float(cnt) == float(cnt_ref)
    np.testing.assert_allclose(
        np.asarray(g)[:X.shape[1]], np.asarray(g_ref),
        rtol=1e-4, atol=1e-4)
    # y/v/pad gradient columns are declared garbage; the wrapper's
    # col_keep mask in ssgd zeroes them — nothing to assert there


def test_packed_backward_band_fold_emulation():
    """The v3 kernel's backward path (masked resid → (P, P·D) MXU
    accumulator → diagonal-band fold) emulated in XLA with a FIXED mask,
    against ``logistic.grad_sum`` on the flat layout — the layout-error-
    prone algebra the TPU-only kernel relies on."""
    X, y, X2, meta, w_aug = _packed_case(seed=7)
    P, D = meta["pack"], meta["d_total"]
    rng = np.random.default_rng(8)
    mask_flat = (rng.random(meta["n_padded"]) < 0.3).astype(np.float32)
    flat = np.asarray(X2).reshape(meta["n_padded"], D)
    mask_flat *= flat[:, meta["v_col"]]  # padding rows never sampled
    with jax.default_matmul_precision("highest"):
        x2 = jnp.asarray(X2)
        C = build_selector(jnp.asarray(w_aug), pack=P, d_total=D,
                           y_col=meta["y_col"], v_col=meta["v_col"],
                           dtype=jnp.float32)
        zyv = x2 @ C
        z, yv = zyv[:, :P], zyv[:, P:2 * P]
        m = jnp.asarray(mask_flat.reshape(-1, P))
        resid = (jax.nn.sigmoid(z) - yv) * m
        gacc = jax.lax.dot_general(
            resid, x2, (((0,), (0,)), ((), ())))      # (P, P·D)
        g = jnp.einsum("ccj->j", gacc.reshape(P, P, D))
        g_ref, cnt_ref = logistic.grad_sum(
            jnp.asarray(flat[:, :X.shape[1]]),
            jnp.asarray(flat[:, meta["y_col"]]),
            jnp.asarray(w_aug[:X.shape[1]]), jnp.asarray(mask_flat))
    np.testing.assert_allclose(
        np.asarray(g)[:X.shape[1]], np.asarray(g_ref),
        rtol=1e-4, atol=1e-4)
    assert float(jnp.sum(m)) == float(cnt_ref)


def test_gathered_kernel_validation():
    import pytest

    _, _, X2, meta, w_aug = _packed_case()
    with pytest.raises(ValueError, match="multiple of 8"):
        fused_grad_sum_gathered(
            X2, jnp.asarray(w_aug), jnp.zeros((1,), jnp.int32),
            pack=meta["pack"], d_total=meta["d_total"],
            y_col=meta["y_col"], v_col=meta["v_col"],
            gather_block_rows=32, interpret=True)
    with pytest.raises(ValueError, match="incompatible"):
        fused_grad_sum_gathered(
            X2, jnp.asarray(w_aug), jnp.zeros((1,), jnp.int32),
            pack=meta["pack"], d_total=meta["d_total"] + 8,
            y_col=meta["y_col"], v_col=meta["v_col"],
            gather_block_rows=128, interpret=True)


def test_pack_augmented_shuffle_seed():
    """Row shuffle keeps (x, y) pairs together and is deterministic."""
    rng = np.random.default_rng(9)
    n, d = 96, 5
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = np.arange(n, dtype=np.float32)  # label = original row id
    X2a, meta = pack_augmented(X, y, np.ones(n, np.float32),
                               dtype=jnp.float32, pack=16, block_rows=32,
                               shuffle_seed=3)
    X2b, _ = pack_augmented(X, y, np.ones(n, np.float32),
                            dtype=jnp.float32, pack=16, block_rows=32,
                            shuffle_seed=3)
    np.testing.assert_array_equal(np.asarray(X2a), np.asarray(X2b))
    flat = np.asarray(X2a).reshape(meta["n_padded"], meta["d_total"])
    for i in range(n):
        orig = int(flat[i, meta["y_col"]])
        np.testing.assert_array_equal(flat[i, :d], X[orig])


# ---- the gathered kernels' block loop (PR 27): a ring of block copies
# the kernel keeps in flight itself, and a body that takes the block in
# chunks of packed rows. The cases are the shapes that loop branches on.

import pytest

# id -> (pack, gather_block_rows, block ids (T, n_sampled), alpha)
SCHEDULE_CASES = {
    "bp8_one_chunk": (4, 32, [[0, 12, 3, 7], [5, 1, 9, 2], [8, 4, 6, 11]],
                      0.0),
    "bp64_one_chunk": (16, 1024, [[4, 0], [2, 3]], 0.0),
    "bp1024_two_chunks": (4, 4096, [[2, 0], [1, 2]], 0.0),
    "n_sampled_1": (4, 32, [[3], [0], [12], [7]], 0.0),
    "n_sampled_5_ring_of_3": (4, 32, [[0, 1, 2, 3, 4], [9, 8, 7, 6, 5]],
                              0.0),
    "one_step": (4, 32, [[6, 2, 10, 11, 0]], 0.0),
    "one_cell": (4, 32, [[5]], 0.0),
    "same_block_in_a_row": (4, 32, [[2, 2, 5], [5, 5, 1]], 0.0),
    "easgd_pull_with_centre": (4, 32, [[0, 4, 8], [1, 5, 9]], 0.05),
}


def schedule_case(name):
    """(X2, meta, w_aug, block ids (T, n), alpha) of a schedule case: a
    float32 table just large enough for the ids, some rows invalid."""
    pack, gbr, ids, alpha = SCHEDULE_CASES[name]
    ids = np.asarray(ids, np.int32)
    n_blocks = int(ids.max()) + 1
    n = n_blocks * gbr - gbr // 4          # the last block part padding
    rng = np.random.default_rng(len(name))
    X = rng.normal(size=(n, 30)).astype(np.float32)
    y = rng.integers(0, 2, n).astype(np.float32)
    valid = (rng.random(n) < 0.9).astype(np.float32)
    X2, meta = pack_augmented(X, y, valid, dtype=jnp.float32, pack=pack,
                              block_rows=gbr)
    w_aug = np.zeros(meta["d_total"], np.float32)
    w_aug[:30] = rng.normal(size=(30,)).astype(np.float32) * 0.1
    return X2, dict(meta, gather_block_rows=gbr), w_aug, ids, alpha


@pytest.mark.parametrize("name", sorted(SCHEDULE_CASES))
def test_gathered_kernel_over_schedule_shapes(name):
    """The v4 kernel over every grid cell of the case (a block drawn
    twice counts twice) against the flat float32 XLA gradient."""
    X2, meta, w_aug, ids, _ = schedule_case(name)
    gbr, d = meta["gather_block_rows"], 30
    blocks = ids.reshape(-1)
    with jax.default_matmul_precision("highest"):
        g, cnt = fused_grad_sum_gathered(
            X2, jnp.asarray(w_aug), jnp.asarray(blocks),
            pack=meta["pack"], d_total=meta["d_total"],
            y_col=meta["y_col"], v_col=meta["v_col"],
            gather_block_rows=gbr, interpret=True)
        rows = np.concatenate(
            [np.arange(b * gbr, (b + 1) * gbr) for b in blocks])
        flat = np.asarray(X2).reshape(meta["n_padded"], meta["d_total"])
        g_ref, cnt_ref = logistic.grad_sum(
            jnp.asarray(flat[rows, :d]),
            jnp.asarray(flat[rows, meta["y_col"]]),
            jnp.asarray(w_aug[:d]), jnp.asarray(flat[rows, meta["v_col"]]))
    assert float(cnt) == float(cnt_ref) > 0
    np.testing.assert_allclose(np.asarray(g)[:d], np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)
