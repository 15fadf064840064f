"""Kernel-level tests: gradient vs autodiff, regularizers, sampling."""

import jax
import jax.numpy as jnp
import numpy as np

from tpu_distalg.ops import logistic, sampling
from tpu_distalg.utils import prng


def _np_reference_grad_sum(X, y, w, mask):
    """The reference's per-point gradient -(y - σ(x·w))·x summed
    (ssgd.py:27-33), in float64 NumPy."""
    z = X @ w
    p = 1.0 / (1.0 + np.exp(-z))
    g = -( (y - p)[:, None] * X ) * mask[:, None]
    return g.sum(axis=0)


def test_grad_sum_matches_reference_formula():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(50, 7))
    y = rng.integers(0, 2, size=50).astype(np.float64)
    w = rng.normal(size=7) * 0.1
    mask = (rng.random(50) < 0.5).astype(np.float64)

    expect = _np_reference_grad_sum(X, y, w, mask)
    got, cnt = logistic.grad_sum(
        jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32),
        jnp.asarray(w, jnp.float32), jnp.asarray(mask, jnp.float32),
    )
    np.testing.assert_allclose(np.asarray(got), expect, rtol=2e-4, atol=2e-4)
    assert float(cnt) == mask.sum()


def test_grad_sum_matches_autodiff():
    """Σ grad over masked rows == ∇ of the masked log-loss sum."""
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.normal(size=(40, 5)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, size=40), jnp.float32)
    w = jnp.asarray(rng.normal(size=5) * 0.3, jnp.float32)
    mask = jnp.asarray((rng.random(40) < 0.7), jnp.float32)

    def loss(w):
        z = X @ w
        # log-loss whose gradient is (σ(z) - y)·x
        return jnp.sum(mask * (jnp.logaddexp(0.0, z) - y * z))

    expect = jax.grad(loss)(w)
    got, _ = logistic.grad_sum(X, y, w, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), rtol=1e-4,
                               atol=1e-5)


def _grad_data(n, d, seed):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    w = jnp.asarray(rng.normal(size=d) * 0.1, jnp.float32)
    mask = jnp.asarray(rng.random(n) < 0.3, jnp.float32)
    return X, y, w, mask


def test_grad_sum_zero_mask_is_zero_sum_and_zero_count():
    """An empty sample: the pair the step's ``max(count, 1)`` guards."""
    X, y, w, mask = _grad_data(256, 32, seed=3)
    g, c = logistic.grad_sum(X, y, w, jnp.zeros_like(mask))
    assert float(c) == 0.0
    assert g.shape == (32,)
    np.testing.assert_array_equal(np.asarray(g), 0.0)


def test_grad_sum_bf16_storage_keeps_f32_sums():
    """X served in bfloat16 (``x_dtype='bfloat16'``): the sums stay
    float32 and within bf16's 2-3 decimal digits of the f32 table's."""
    X, y, w, mask = _grad_data(512, 128, seed=2)
    g0, c0 = logistic.grad_sum(X, y, w, mask)
    g1, c1 = logistic.grad_sum(X.astype(jnp.bfloat16), y, w, mask)
    assert g1.dtype == jnp.float32 and float(c1) == float(c0)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1),
                               rtol=0.05, atol=0.5)
    assert np.abs(np.asarray(g0) - np.asarray(g1)).max() > 0


def test_sigmoid_stable_at_extremes():
    """The reference's 1/(exp(-z)+1) overflows at z=-1000; ours must not
    (SURVEY.md §5 NaN hazard)."""
    z = jnp.asarray([-1e4, -100.0, 0.0, 100.0, 1e4])
    X = z[:, None]
    p = logistic.predict_proba(X, jnp.ones((1,)))
    assert bool(jnp.all(jnp.isfinite(p)))
    np.testing.assert_allclose(np.asarray(p), [0, 0, 0.5, 1, 1], atol=1e-6)


def test_reg_gradient_variants():
    w = jnp.asarray([-2.0, 0.0, 3.0])
    np.testing.assert_array_equal(
        np.asarray(logistic.reg_gradient(w, "none")), [0, 0, 0]
    )
    np.testing.assert_array_equal(np.asarray(logistic.reg_gradient(w, "l2")),
                                  np.asarray(w))
    np.testing.assert_array_equal(np.asarray(logistic.reg_gradient(w, "l1")),
                                  [-1, 0, 1])
    en = logistic.reg_gradient(w, "elastic_net", alpha=0.25)
    np.testing.assert_allclose(
        np.asarray(en), 0.25 * np.sign([-2, 0, 3]) + 0.75 * np.array([-2, 0, 3])
    )


def test_bernoulli_mask_fraction_and_determinism():
    key = prng.root_key(42)
    valid = jnp.ones((100_000,))
    m1 = sampling.bernoulli_mask(key, 3, 100_000, 0.1, valid)
    m2 = sampling.bernoulli_mask(key, 3, 100_000, 0.1, valid)
    m3 = sampling.bernoulli_mask(key, 4, 100_000, 0.1, valid)
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
    assert not np.array_equal(np.asarray(m1), np.asarray(m3))
    assert abs(float(jnp.mean(m1)) - 0.1) < 0.01
    # padding rows never sampled
    valid0 = valid.at[50_000:].set(0.0)
    m4 = sampling.bernoulli_mask(key, 3, 100_000, 0.1, valid0)
    assert float(jnp.sum(m4[50_000:])) == 0.0
