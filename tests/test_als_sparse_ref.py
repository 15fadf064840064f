"""ALS on a ratings list, the trainer against the plain reference
(``benchmarks/reference/als_sparse_ref.py``: ``A_u``, ``b_u`` and
``jnp.linalg.solve`` owner by owner, its own restatement of the
generator) on seeded ratings in five geometries of the pack (an owner
carried across three blocks, a block of minimum-degree owners, an owner
with no rating, a padded last batch, every class at once), with the
reference's bfloat16 control outside the same limit, and a state left
unchanged against the held-out check. The helpers and the rest of the
sparse trainer's tests are ``tests/test_als_sparse.py``'s."""

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg.models import als

from test_als_sparse import (K, LIMIT, _cfg, _coo, _degrees, _ref, _table,
                             ref_mod)

# ---- the trainer against the plain reference -------------------------

@pytest.mark.parametrize("kind", ["three_blocks", "minimum_block",
                                  "empty_owner", "padded_last", "mixed"])
def test_trainer_follows_the_reference(mesh1, kind):
    du, di = _degrees(kind)
    seed, start = 11, 3
    arrays, meta = _table(mesh1, du, di, seed)
    pu, pi, geom = meta["user"], meta["item"], meta["geometry"]
    if kind == "three_blocks":
        assert pu.static.heavy[1] >= 3          # its pieces' blocks
    if kind == "minimum_block":
        assert pu.static.light[0][2] == 2 and pu.static.heavy[3] == 0
    fn = als.make_fit_fn(mesh1, _cfg(du, di), meta)
    X, Theta = als.start_factors(meta, mesh1, start)
    ref = _ref(du, di, seed, start)
    V_before = ref.start_items()
    np.testing.assert_array_equal(
        np.asarray(als.owners_from_rows(Theta, pi, K)), V_before)
    rng = np.random.default_rng(1)
    U_before = rng.random((len(du), K)).astype(np.float32)
    X = als.rows_from_owners(U_before, pu, geom.width)
    worst, control = 0.0, np.inf
    for _ in range(2):
        X, Theta, errs, seen = fn(*arrays, X, Theta)
        U = np.asarray(als.owners_from_rows(X, pu, K))
        V = np.asarray(als.owners_from_rows(Theta, pi, K))
        assert np.asarray(seen).tolist() == [[du.sum(), du.sum()]]
        for side, other, got, before in ((0, V_before, U, U_before),
                                         (1, U, V, V_before)):
            own, want = ref.half(side, other)
            assert own.tolist() == np.flatnonzero(
                (du, di)[side] > 0).tolist()
            worst = max(worst, ref_mod.rel_err(got[own], want, before[own]))
            _, low = ref.half(side, other, dtype=jnp.bfloat16)
            control = min(control,
                          ref_mod.rel_err(low, want, before[own]))
        # an owner with no rating keeps its row
        np.testing.assert_array_equal(U[du == 0], U_before[du == 0])
        U_before, V_before = U, V
    assert worst < LIMIT < control, (worst, control)
    # the program's own errors: the training one from the Gramians'
    # spare lanes, the held-out one on the reference's pairs
    pairs = ref.heldout()
    assert abs(float(errs[-1, 1]) - ref.rmse(pairs, U, V)) < 1e-3
    coo = _coo(arrays, meta)
    train = np.sqrt(np.mean(
        (np.sum(U[coo[0]] * V[coo[1]], axis=1) - coo[2]) ** 2))
    assert abs(float(errs[-1, 0]) - train) < 2e-3 * train


def test_unchanged_factors_fail_the_heldout_check(mesh1):
    du, di = _degrees("mixed")
    arrays, meta = _table(mesh1, du, di, 11)
    res = als.fit_ratings(mesh1, _cfg(du, di, 2), arrays, meta)
    ref = _ref(du, di, 11, 3)
    pairs = ref.heldout()
    fitted = ref.rmse(pairs, np.asarray(res.U), np.asarray(res.V))
    start = ref.rmse(pairs, np.zeros_like(res.U), ref.start_items())
    assert start / fitted - 1 > 0.5
