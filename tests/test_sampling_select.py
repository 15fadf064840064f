"""The block draw is a selection, and the selection is the sort's prefix.

``sampling.sample_block_ids`` has to return, element for element, what
``jnp.argsort(bits, axis=-1)[:, :n_sampled]`` returns over the same
threefry words: the same ids, in the same order, a tie going to the
lower id. The draw is restated here (keys, ``fold_in``, ``bits``) and
not imported, so that a change to the function cannot move both sides.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg.ops import sampling

# (n_shards, n_blocks, n_sampled): both forms, the boundary between
# them, the benchmark's cells (12 and 1221 of 12 208, one and four
# shards) and the degenerate ends
SHAPES = [
    (1, 16, 4), (2, 16, 16), (1, 12208, 12), (1, 12208, 1221),
    (4, 12208, 1221), (1, 130, 1), (3, 1000, 999), (2, 12208, 12),
    (1, 4096, sampling.FEW_MAX), (2, 4096, sampling.FEW_MAX + 1),
    (1, 24, 12), (5, 40, 40),
]
N_KEYS = 64


def _restated(base_key, n_shards, n_blocks, n_sampled, bits=None):
    bits = bits or jax.random.bits
    ks = jnp.stack([jax.random.fold_in(base_key, s)
                    for s in range(n_shards)])
    words = jnp.stack([bits(ks[s], (n_blocks,))
                       for s in range(n_shards)])
    return jnp.argsort(words, axis=-1)[:, :n_sampled].astype(jnp.int32)


def _keys(seed):
    root = jax.random.key(seed)
    return jax.vmap(lambda i: jax.random.fold_in(root, i))(
        jnp.arange(N_KEYS))


@pytest.mark.parametrize("mode", ["jit", "vmap_steps"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(
    str(v) for v in s))
def test_selection_is_the_argsort_prefix(shape, mode):
    n_shards, n_blocks, n_sampled = shape
    if mode == "jit":
        # one key a call, 64 calls of one compiled function
        f = jax.jit(lambda k: sampling.sample_block_ids(
            k, n_shards, n_blocks, n_sampled))
        keys = _keys(3)
        got = np.stack([np.asarray(f(keys[i])) for i in range(N_KEYS)])
        want = jax.vmap(lambda k: _restated(
            k, n_shards, n_blocks, n_sampled))(keys)
    else:
        # the trainers' form: fold the step id in, vmap over the steps
        key = jax.random.key(11)
        ts = jnp.arange(N_KEYS) + 1_000_003
        got = jax.jit(jax.vmap(lambda t: sampling.sample_block_ids(
            jax.random.fold_in(key, t), n_shards, n_blocks,
            n_sampled)))(ts)
        want = jax.vmap(lambda t: _restated(
            jax.random.fold_in(key, t), n_shards, n_blocks,
            n_sampled))(ts)
    got = np.asarray(got)
    assert got.dtype == np.int32
    assert got.shape == (N_KEYS, n_shards, n_sampled)
    np.testing.assert_array_equal(got, np.asarray(want))


def test_nested_vmap_as_the_local_update_rounds_draw():
    # local_sgd: vmap over rounds of vmap over local steps
    key = jax.random.key(5)
    shape = (2, 512, 6)

    def rounds(draw):
        return jax.vmap(lambda t: jax.vmap(lambda l: draw(
            jax.random.fold_in(jax.random.fold_in(key, t), l), *shape)
        )(jnp.arange(3)))(jnp.arange(5))

    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda: rounds(sampling.sample_block_ids))()),
        np.asarray(rounds(_restated)))


def _tied_bits(alphabet):
    real = jax.random.bits
    table = jnp.asarray(alphabet, jnp.uint32)

    def bits(key, shape):
        return table[real(key, shape) % np.uint32(len(alphabet))]

    return bits


@pytest.mark.parametrize("alphabet", [
    (0, 7, 0xFFFFFFFF), (0xFFFFFFFF,), (0xFFFFFFFE, 0xFFFFFFFF, 3)],
    ids=["three_values", "all_ones_row", "ones_and_neighbours"])
@pytest.mark.parametrize("shape", [
    (1, 16, 4), (2, 64, 64), (1, 1000, 12), (3, 1000, 400),
    (1, 12208, 12), (1, 12208, 1221)],
    ids=lambda s: "x".join(str(v) for v in s))
def test_ties_go_to_the_lower_id(monkeypatch, shape, alphabet):
    """Words from a tiny alphabet: nearly every comparison is a tie,
    and the all-ones word (the value a masked selection would like to
    use for "taken") has to stay selectable."""
    bits = _tied_bits(alphabet)
    monkeypatch.setattr(sampling.jax.random, "bits", bits)
    key = jax.random.key(2)
    ts = jnp.arange(8)
    got = np.asarray(jax.vmap(lambda t: sampling.sample_block_ids(
        jax.random.fold_in(key, t), *shape))(ts))
    want = np.asarray(jax.vmap(lambda t: _restated(
        jax.random.fold_in(key, t), *shape, bits=bits))(ts))
    np.testing.assert_array_equal(got, want)
    for row in got.reshape(-1, shape[2]):       # no id twice
        assert len(set(row.tolist())) == shape[2]
    if len(alphabet) == 1:                      # all equal: ids 0..k-1
        np.testing.assert_array_equal(
            got[0, 0], np.arange(shape[2], dtype=np.int32))


def test_draw_form_is_a_function_of_the_two_static_counts():
    assert sampling.draw_form(12208, 12) == "few"
    assert sampling.draw_form(12208, 1221) == "sort"
    assert sampling.draw_form(16, 16) == "few"
    assert sampling.draw_form(4096, sampling.FEW_MAX) == "few"
    assert sampling.draw_form(4096, sampling.FEW_MAX + 1) == "sort"
    assert sampling.draw_form(1 << 20, 1 << 19) == "sort"
    with pytest.raises(ValueError):
        sampling.draw_form(8, 9)
