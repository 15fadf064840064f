"""The two forms of sparse ALS' per-owner solve (``ops/als_sparse
.solve_batch``): the Mosaic kernel of ``ops/pallas_als.py``, interpreted,
against a float64 NumPy solve and against XLA's ``cholesky_solve_lanes``
on systems conditioned like the benchmark cell's; the kernel's turn of
an owner-major tile in VMEM against the form that was handed the batch
along the lanes (``scripts/step0_als_solve.py`` keeps it), bit for bit;
an owner with no rating; a control without the ridge; a batch the
kernel refuses; the choice of form from what the code can observe; what
the spans and ``tda report`` say of it."""

import dataclasses
import inspect
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg.models import als
from tpu_distalg.ops import als_sparse as ops
from tpu_distalg.ops import pallas_als

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")
if SCRIPTS not in sys.path:
    sys.path.insert(0, SCRIPTS)

import step0_als_solve as step0  # noqa: E402

LAM = 1.4
MOSAIC = ops.SolvePlan("mosaic", pallas_als.SOLVE_TILE, interpret=True)


def _systems(k: int, batch: int, seed: int = 0):
    """Extended Gramians as a half-sweep makes them, ``(batch, width,
    width)``: an owner's rows are planted factors of eighths, ratings 0
    to 100 in lane ``k``, a one in lane ``k + 1``; owners of 0 (the
    first three) to 300 ratings, so some have fewer than ``k``."""
    rng = np.random.default_rng(seed)
    W = ops.SparseGeometry(k=k).width
    cnt = rng.integers(1, 300, batch)
    cnt[:3] = 0
    Ap = np.zeros((batch, W, W), np.float32)
    for b in range(batch):
        G = np.zeros((cnt[b], W), np.float32)
        G[:, :k] = rng.integers(-8, 9, (cnt[b], k)) / 8
        G[:, k] = rng.integers(0, 101, cnt[b])
        G[:, k + 1] = 1.0
        Ap[b] = G.T @ G
    return Ap, cnt


def _float64(Ap, cnt, k: int, lam: float = LAM):
    A = Ap[:, :k, :k].astype(np.float64)
    A = A + np.where(cnt > 0, lam * cnt, 1.0)[:, None, None] * np.eye(k)
    b = Ap[:, :k, k].astype(np.float64)
    return np.linalg.solve(A, b[..., None])[..., 0].T       # (k, batch)


def _rel(x, want):
    return np.linalg.norm(x - want) / np.linalg.norm(want)


def _solve(Ap, k: int, batch: int, plan=None, lam: float = LAM):
    geom = ops.SparseGeometry(k=k, batch=batch, classes=(1,),
                              piece_segs=batch)
    return jax.jit(lambda a: ops.solve_batch(a, lam, geom, plan))(Ap)


def _xla(Ap, k: int, batch: int):
    return _solve(Ap, k, batch)


def _mosaic(Ap, k: int, batch: int, lam: float = LAM):
    return _solve(Ap, k, batch, MOSAIC, lam)


# rank 100 in 13 panels, two tiles; a rank of one panel; a rank of two
# whose second holds padding columns; a rank whose count of ratings
# lies past the last panel (row 104 of 112 read)
@pytest.mark.parametrize("k,batch", [(100, 256), (5, 128), (12, 128),
                                     (103, 128)])
def test_the_kernel_solves_what_xla_solves(k, batch):
    Ap, cnt = _systems(k, batch, seed=k)
    want = _float64(Ap, cnt, k)
    # the tile turned in VMEM is the tile handed over along the lanes:
    # the same unknowns bit for bit, and the right-hand side it read
    x, b = pallas_als.solve_lanes(jnp.asarray(Ap), k, LAM, interpret=True)
    lanes = step0.solve_from_lanes(ops.to_lanes(jnp.asarray(Ap)), k, LAM,
                                   interpret=True)
    assert x.shape == b.shape == lanes.shape == (-(-k // 8) * 8, batch)
    assert np.array_equal(np.asarray(x), np.asarray(lanes))
    assert np.array_equal(np.asarray(b)[:k], Ap[:, :k, k].T)
    assert np.abs(np.asarray(b)[k:]).max(initial=0) == 0
    rows_x, has_x, sse_x, seen_x = _xla(jnp.asarray(Ap), k, batch)
    rows_m, has_m, sse_m, seen_m = _mosaic(jnp.asarray(Ap), k, batch)
    got_x, got_m = (np.asarray(r)[:, :k].T for r in (rows_x, rows_m))
    err_x, err_m = _rel(got_x, want), _rel(got_m, want)
    assert err_x < 2e-6 and err_m <= 2 * err_x
    assert np.abs(got_m - got_x).max() <= 1e-5 * np.abs(want).max()
    assert np.abs(np.asarray(rows_m)[:, k:]).max() == 0
    assert np.array_equal(np.asarray(has_m), np.asarray(has_x))
    assert int(seen_m) == int(seen_x) == int(cnt.sum())
    assert abs(float(sse_m) - float(sse_x)) <= 1e-5 * float(sse_x)


def test_an_owner_without_a_rating_solves_the_identity():
    k, batch = 12, 128
    Ap, cnt = _systems(k, batch)
    rows, has, _, _ = _mosaic(jnp.asarray(Ap), k, batch)
    assert np.asarray(has).tolist() == (cnt > 0).tolist()
    assert not np.asarray(has)[:3].any()
    assert np.abs(np.asarray(rows)[:3]).max() == 0      # I x = 0
    assert np.abs(np.asarray(rows)[3:, :k]).min(axis=1).max() > 0


@pytest.mark.parametrize("k,shape,word", [
    (12, (192, 128, 128), "whole tiles"),       # a tile and a half
    (127, (128, 128, 128), "whole tiles")])     # row 128 of 136 is not there
def test_a_batch_the_kernel_cannot_tile_is_refused(k, shape, word):
    with pytest.raises(ValueError, match=word):
        pallas_als.solve_lanes(jnp.zeros(shape, jnp.float32), k, LAM,
                               interpret=True)


def test_a_solve_without_the_ridge_is_another_answer():
    """The control: the same kernel told ``lam`` 0 leaves the bound the
    sound one keeps by five orders (an owner of fewer ratings than the
    rank has no unregularised solution at all)."""
    k, batch = 12, 128
    Ap, cnt = _systems(k, batch)
    want = _float64(Ap, cnt, k)
    some = cnt > 0
    sound = np.asarray(_mosaic(jnp.asarray(Ap), k, batch)[0])[:, :k].T
    bare = np.asarray(_mosaic(jnp.asarray(Ap), k, batch, 0.0)[0])[:, :k].T
    assert _rel(sound[:, some], want[:, some]) < 2e-6
    assert not _rel(bare[:, some], want[:, some]) < 1e-1


def test_the_form_follows_what_the_code_can_observe():
    cell = ops.SparseGeometry(k=100)                    # batch 6144
    mosaic = ops.SolvePlan("mosaic", 128)
    xla = ops.SolvePlan("xla", 0)
    assert ops.solve_plan(cell, True) == mosaic
    assert ops.solve_plan(cell, False) == xla           # not a TPU
    unit = dataclasses.replace(cell, batch=ops.BATCH_UNIT)
    assert ops.solve_plan(unit, True) == xla            # 192: 1.5 tiles
    smoke = dataclasses.replace(cell, batch=768)
    assert ops.solve_plan(smoke, True) == mosaic
    # a tile at rank 126 is 27 MB; from rank 127 an owner's row is two
    # vectors wide and the tile's block of them past the budget
    assert ops.solve_plan(ops.SparseGeometry(k=126), True) == mosaic
    assert pallas_als.solve_tile_bytes(126) <= ops.SOLVE_VMEM_BYTES \
        < pallas_als.solve_tile_bytes(127)
    assert ops.solve_plan(ops.SparseGeometry(k=127), True) == xla
    assert ops.solve_plan(ops.SparseGeometry(
        k=5, batch=128, classes=(1, 2, 4), piece_segs=8), True) == mosaic
    # nothing names a form: the geometry and the platform decide
    assert list(inspect.signature(ops.solve_plan).parameters) == [
        "geom", "on_tpu"]


def test_the_kernels_call_sits_under_the_solves_scope():
    """What ``solve_ms_per_sweep.als`` reads: the custom call is named
    after the kernel's body and traced under ``tda.als.solve``."""
    from tpu_distalg.telemetry import names

    geom = ops.SparseGeometry(k=100, batch=256, classes=(1,),
                              piece_segs=256)
    found = []

    def walk(jaxpr, prefix):
        for eqn in jaxpr.eqns:
            stack = f"{prefix}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"], stack))
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", p)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    walk(inner, stack)

    walk(jax.make_jaxpr(lambda a: ops.solve_batch(
        a, LAM, geom, ops.SolvePlan("mosaic", 128)))(
        jax.ShapeDtypeStruct((256, 128, 128), jnp.float32)).jaxpr, "")
    assert [n for n, _ in found] == ["_als_solve_kernel"]
    assert names.ALS_SOLVE in found[0][1]


def _toy(mesh, k=5, batch=128):
    rng = np.random.default_rng(5)
    du = np.concatenate([[700, 300], rng.integers(1, 120, 90)])
    di = np.full(60, du.sum() // 60)
    di[:du.sum() - di.sum()] += 1
    geometry = dict(seg_slots=32, piece_segs=8, batch=batch,
                    classes=(1, 2, 4))
    arrays, meta = als.build_ratings_table(
        int(du.sum()), len(du), len(di), k, mesh, data_seed=4,
        n_heldout=64, degrees=(du, di), geometry=geometry)
    return du, di, arrays, meta


def test_a_fit_is_the_same_in_both_forms(mesh1):
    du, di, arrays, meta = _toy(mesh1)
    assert meta["solve"] == ops.SolvePlan("xla", 0)     # on the CPU
    # the users' half holds every kind of step: a class of one segment
    # an owner (no staging), classes staged part by part, the heavy
    # class's accumulator
    st = meta["user"].static
    assert {K for K, _, n_super, _ in st.light if n_super} == {1, 2, 4}
    assert st.heavy[3] == 128
    assert meta["forms"]["als_solve_form"] == "xla"
    cfg = als.ALSConfig(lam=LAM, m=len(du), n=len(di), k=5,
                        n_iterations=2, seed=3)
    out = []
    for solve in (meta["solve"], MOSAIC):
        fn = als.make_fit_fn(mesh1, cfg, dict(meta, solve=solve))
        X, Theta = als.start_factors(meta, mesh1, cfg.seed)
        out.append([np.asarray(a) for a in fn(*arrays, X, Theta)])
    for a, b in zip(*out):       # X, Theta, (training, held-out RMSE),
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    assert out[1][3].tolist() == [[int(du.sum())] * 2] * 2


def test_the_spans_and_the_report_say_which_form(mesh1, tmp_path):
    from tpu_distalg.telemetry import report

    # the cell's shape planned for a TPU, chiplessly (no device touched)
    meta = als.plan_ratings(252_800_275, 1_000_990, 624_961, 100,
                            on_tpu=True, geometry=dict(
                                seg_slots=32, piece_segs=64, batch=6144))
    fields = als.segment_fields(meta)
    assert (fields["als_solve_form"], fields["solve_tile_systems"],
            fields["als_gram_layout"]) == ("mosaic", 128, "owners")
    off = als.segment_fields(als.plan_ratings(
        60_000, 900, 500, 12, on_tpu=False))
    assert (off["als_solve_form"], off["solve_tile_systems"],
            off["als_gram_layout"]) == ("xla", 0, "lanes")

    def line(fields):
        path = tmp_path / f"{fields['als_solve_form']}.jsonl"
        path.write_text(
            json.dumps(dict(ev="span_start", name="train:segment", id=1,
                            **fields)) + "\n"
            + json.dumps(dict(ev="span_end", name="train:segment", id=1,
                              seconds=1.0)) + "\n")
        text = report.render(report.summarize(
            report.load_events(str(path))))
        return next(ln for ln in text.splitlines() if "R layout" in ln)

    assert "gramians: xla by owners, solve: mosaic in tiles of 128)" \
        in line(fields)
    assert line(off).endswith("gramians: xla by lanes, solve: xla)")
