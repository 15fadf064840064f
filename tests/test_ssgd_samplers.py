"""The samplers SSGD keeps (``ssgd.SAMPLERS``): a retired or misspelt
name is refused by name at every public entry, and what the seeded
loader + block-sampled trainer (``prepare_fused_synthetic`` +
``make_train_fn_fused``) promise: a table and a run that follow their
seeds, padding rows that never count, a run in two halves that is the
straight one, a warning where the block grid quantizes the fraction."""

import dataclasses
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg.models import ma, ssgd
from tpu_distalg.ops import pallas_kernels

# the names PR 57 retired, and two that never were
REFUSED = ("fixed", "fused", "virtual", "fused_gathr", "")

DENSE_META = dict(pack=16, d_total=8, y_col=5, v_col=6, n_padded=64)
HASHED_META = dict(row_format="hashed", nnz=5, hash_bits=10, pack=1,
                   n_rows=1000, n_padded=1024, d_total=1024 + 128)
PAIRS_META = dict(row_format="pairs", pack=1, n_rows=600, n_features=5000,
                  n_blocks=96, block_slots=2048, block_rows=16,
                  d_total=5120)


def _rows():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(64, 4)).astype(np.float32)
    y = (rng.random(64) > 0.5).astype(np.float32)
    return X, y, X[:8], y[:8]


def _by_train(mesh, cfg):
    ssgd.train(*_rows(), mesh, cfg)


def _by_local_sgd(mesh, cfg):
    ma.train(*_rows(), mesh,
             ma.MAConfig(n_iterations=2, sampler=cfg.sampler))


ENTRIES = {
    "train": _by_train,
    "make_train_fn": lambda mesh, cfg: ssgd.make_train_fn(mesh, cfg, 64),
    "make_train_fn_fused:dense":
        lambda mesh, cfg: ssgd.make_train_fn_fused(mesh, cfg, DENSE_META),
    "make_train_fn_fused:hashed":
        lambda mesh, cfg: ssgd.make_train_fn_fused(mesh, cfg, HASHED_META),
    "make_train_fn_fused:pairs":
        lambda mesh, cfg: ssgd.make_train_fn_fused(mesh, cfg, PAIRS_META),
    "local_sgd.train": _by_local_sgd,
}


def test_the_kept_set_is_stated_once():
    assert ssgd.SAMPLERS == ("bernoulli", "fused_gather", "fused_train")
    assert ssgd.SSGDConfig().sampler in ssgd.SAMPLERS
    # the retired forms' three options went with them: one block size
    # is left, and no switch between kernels
    fields = {f.name for f in dataclasses.fields(ssgd.SSGDConfig)}
    assert {f for f in fields if "block" in f} == {"gather_block_rows"}
    assert not {f for f in fields if "pallas" in f}


@pytest.mark.parametrize("entry", sorted(ENTRIES))
@pytest.mark.parametrize("name", REFUSED)
def test_a_retired_or_misspelt_sampler_is_refused_by_name(mesh4, name,
                                                          entry):
    """One refusal, the same at every entry, before any check of a
    format, a mesh or a schedule: it names the sampler and the set."""
    cfg = ssgd.SSGDConfig(n_iterations=2, sampler=name, comm="int8",
                          sync="ssp:2", feature_sharded=True)
    with pytest.raises(ValueError, match="unknown sampler") as err:
        ENTRIES[entry](mesh4, cfg)
    assert repr(name) in str(err.value)
    assert str(ssgd.SAMPLERS) in str(err.value)


@pytest.mark.parametrize("cmd", ["ssgd", "ma"])
@pytest.mark.parametrize("name", REFUSED)
def test_the_cli_offers_the_kept_set_and_exits_2_on_any_other(
        name, cmd, capsys):
    from tpu_distalg import cli

    with pytest.raises(SystemExit) as err:
        cli.main(["--emulate", "1", cmd, "--sampler", name])
    assert err.value.code == 2
    said = capsys.readouterr().err
    assert "--sampler" in said
    assert all(s in said for s in ssgd.SAMPLERS)


@pytest.mark.parametrize("sampler,builder", [
    ("fused_gather", "make_train_fn"), ("fused_train", "make_train_fn"),
    ("bernoulli", "make_train_fn_fused")])
def test_a_kept_sampler_at_the_other_builder_is_sent_on(mesh1, sampler,
                                                        builder):
    cfg = ssgd.SSGDConfig(n_iterations=2, sampler=sampler)
    other = ("make_train_fn_fused" if builder == "make_train_fn"
             else r"make_train_fn\(")
    with pytest.raises(ValueError, match=other) as err:
        if builder == "make_train_fn":
            ssgd.make_train_fn(mesh1, cfg, 64)
        else:
            ssgd.make_train_fn_fused(mesh1, cfg, DENSE_META)
    assert repr(sampler) in str(err.value)


# ---- the seeded loader and the block-sampled trainer ---------------------

def _cfg(sampler, steps=8, **kw):
    base = dict(n_iterations=steps, sampler=sampler, fused_pack=4,
                gather_block_rows=32, x_dtype="float32", eval_test=False,
                mini_batch_fraction=0.25, mega_steps=4)
    base.update(kw)
    return ssgd.SSGDConfig(**base)


def _run(fn, X2, w0, t0=0):
    d = jnp.zeros((1,), jnp.float32)
    return np.asarray(fn(X2, d, d, d, d, w0, t0=t0)[0])


def test_the_table_and_the_run_follow_their_seeds(mesh4):
    cfg = _cfg("fused_gather")
    fn_a, X_a, w0, meta = ssgd.prepare_fused_synthetic(
        1000, 6, mesh4, cfg, data_seed=3)
    fn_b, X_b, _, _ = ssgd.prepare_fused_synthetic(
        1000, 6, mesh4, cfg, data_seed=3)
    _, X_c, _, _ = ssgd.prepare_fused_synthetic(
        1000, 6, mesh4, cfg, data_seed=4)
    np.testing.assert_array_equal(np.asarray(X_a), np.asarray(X_b))
    assert not np.array_equal(np.asarray(X_a), np.asarray(X_c))
    w_a, w_b = _run(fn_a, X_a, w0), _run(fn_b, X_b, w0)
    assert np.isfinite(w_a).all() and np.abs(w_a - np.asarray(w0)).max() > 0
    np.testing.assert_array_equal(w_a, w_b)
    assert not np.array_equal(w_a, _run(fn_a, X_c, w0))


@pytest.mark.parametrize("n_rows", [10_001, 999])
def test_padding_rows_never_count(mesh4, n_rows):
    """An odd row count: the counts the gathered kernel returns over
    every block of every shard sum to the real rows, not the padded."""
    cfg = _cfg("fused_gather")
    _, X2, w0, meta = ssgd.prepare_fused_synthetic(
        n_rows, 6, mesh4, cfg, data_seed=2)
    assert meta["n_padded"] > n_rows
    n_blocks = meta["n_padded"] // cfg.gather_block_rows
    g, cnt = pallas_kernels.fused_grad_sum_gathered(
        jnp.asarray(np.asarray(X2)), w0, jnp.arange(n_blocks),
        pack=meta["pack"], d_total=meta["d_total"], y_col=meta["y_col"],
        v_col=meta["v_col"], gather_block_rows=cfg.gather_block_rows,
        interpret=True)
    assert float(cnt) == n_rows
    assert np.isfinite(np.asarray(g)).all()


@pytest.mark.parametrize("sampler,shards", [("fused_gather", 4),
                                            ("fused_train", 1)])
def test_a_run_in_two_halves_is_the_straight_one(mesh1, mesh4, sampler,
                                                 shards):
    """The draw is keyed on the ABSOLUTE step id (``t0``): 8 + 8 steps
    with the carried weights are 16 straight steps, bit for bit."""
    mesh = mesh1 if shards == 1 else mesh4
    fn, X2, w0, meta = ssgd.prepare_fused_synthetic(
        4000, 6, mesh, _cfg(sampler, steps=16), data_seed=1)
    half = ssgd.make_train_fn_fused(mesh, _cfg(sampler, steps=8), meta)
    w_a = _run(half, X2, w0, t0=40)
    w_b = _run(half, X2, jnp.asarray(w_a), t0=48)
    np.testing.assert_array_equal(_run(fn, X2, w0, t0=40), w_b)
    assert not np.array_equal(w_a, w_b)


@pytest.mark.parametrize("fraction,blocks,warns", [
    (0.01, 50, True),        # 1 of 50 blocks is 2%: twice what was asked
    (0.1, 50, False),        # 5 of 50: exact
    (0.3, 7, False),         # 2 of 7 is 28.6%: inside the 25% tolerance
    (0.3, 2, True),          # 1 of 2 is 50%
])
def test_a_block_grid_that_quantizes_the_fraction_warns_once(
        fraction, blocks, warns):
    cfg = _cfg("fused_gather", mini_batch_fraction=fraction)
    meta = dict(pack=4, n_padded=2 * blocks * cfg.gather_block_rows)
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        n_blocks, n_sampled = ssgd.fused_gather_geometry(cfg, meta, 2)
    assert n_blocks == blocks
    assert n_sampled == max(1, round(fraction * blocks))
    said = [w for w in seen if "quantizes the minibatch" in str(w.message)]
    assert len(said) == (1 if warns else 0)
    if warns:
        assert f"{blocks} blocks/shard" in str(said[0].message)
        assert said[0].filename == __file__   # the caller's line, not ours
