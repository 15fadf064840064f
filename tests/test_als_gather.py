"""The two forms of sparse ALS' row gather (``ops/als_sparse.py``,
``ops/pallas_als.py``): the loader's lists against what a pass over the
slots would list; the Mosaic kernel, interpreted, against ``other[idx]``
and XLA's ``where`` bit for bit on blocks of every kind; a block's
Gramians and a fit whose halves gather in either form; the choice of
form from what the code can observe; the resident share against a count
over packed indices."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg.models import als
from tpu_distalg.ops import als_sparse as ops
from tpu_distalg.ops import pallas_als

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# a block of 64 segments x 32 slots = (16, 128): one chunk
GEOM = dict(seg_slots=32, piece_segs=8, batch=64, classes=(1, 2, 4))
K, LAM = 5, 1.4
ROWS, HOT0 = 1000, 808          # a table of 1000 rows and 8 zero rows


def _block(case: str, rng) -> np.ndarray:
    n = 96 * 128                # two chunks of 48 rows of 128 slots
    if case == "all_hot":
        idx = rng.integers(HOT0, ROWS, n)
    elif case == "all_cold":
        idx = rng.integers(0, HOT0, n)
    elif case == "all_padding":
        idx = np.full(n, ROWS)
    elif case == "mixed":       # the cell's shares: 57% heavy, 17%
        u = rng.random(n)       # padding, 26% cold
        idx = np.where(u < 0.568, rng.integers(HOT0, ROWS, n),
                       np.where(u < 0.736, ROWS,
                                rng.integers(0, HOT0, n)))
    elif case == "edges":
        idx = rng.integers(0, ROWS + 1, n)
        idx[:4] = [HOT0 - 1, HOT0, ROWS - 1, ROWS]
        idx[4:6] = [ROWS + 7, ROWS + 1]    # zero rows no padding names
        idx[-4:] = [ROWS, ROWS - 1, HOT0, HOT0 - 1]
    elif case == "split":       # a chunk with no cold slot, then a
        idx = np.concatenate([  # chunk whose every slot is cold
            rng.integers(HOT0, ROWS + 1, n // 2),
            rng.integers(0, HOT0, n // 2)])
    else:                       # one cold slot, and it is the last
        idx = np.full(n, ROWS - 1)
        idx[-1] = 3
    return idx.astype(np.int32).reshape(96, 128)


CASES = ["all_hot", "all_cold", "all_padding", "mixed", "edges",
         "last_cold", "split"]
GEOM96 = ops.SparseGeometry(k=K, seg_slots=32, piece_segs=8, batch=384,
                            classes=(1, 2, 4))
PLAN = ops.GatherPlan("mosaic", HOT0, ROWS + 8 - HOT0, interpret=True)


def _table(rng) -> np.ndarray:
    """A factor table as the trainer holds it: ``K`` columns, zero
    lanes behind them, zero rows at the end."""
    T = rng.standard_normal((ROWS + 8, 128)).astype(np.float32)
    T[ROWS:] = 0.0
    T[:, K:] = 0.0
    return T


def _lists(idx, val):
    """What the kernel is handed for one block: the pack's indices and
    the loader's four."""
    return (idx, *(a[0] for a in jax.jit(
        lambda i, v: ops.gather_lists(i, v, PLAN))(idx[None], val[None])))


def _pass1_lists(idx_b: np.ndarray, slots: int, fetch: int):
    """What the kernel that kept the list itself (PR 37) wrote a chunk:
    a cursor that moves on where a slot is cold, then the last one
    again to a whole trip of ``fetch``."""
    out = []
    for chunk in idx_b.reshape(-1, slots):
        cold, n = np.zeros(slots + fetch, np.int64), 0
        for at, row in enumerate(chunk):
            cold[n] = at
            n += int(row < HOT0)
        cold[n:n + fetch - 1] = cold[max(n - 1, 0)]
        out.append((cold[:-(-n // fetch) * fetch], n))
    return out


@pytest.mark.parametrize("case", CASES)
def test_the_loaders_lists_are_what_pass_1_listed(case):
    rng = np.random.default_rng(11)
    idx = _block(case, rng)
    val = rng.standard_normal(idx.shape).astype(np.float32)
    _, val_t, _, cold, n_cold = (np.asarray(a) for a in _lists(idx, val))
    slots = pallas_als.chunk_rows(96) * 128
    assert slots == 48 * 128 and cold.shape == (96 * 128 // 2,)
    # a tile of 1024 slots: slot 8 m + j at row j, lane m
    assert np.array_equal(
        val_t.reshape(-1, 8, 128).transpose(0, 2, 1).reshape(-1),
        val.reshape(-1))
    want = _pass1_lists(idx, slots, pallas_als.FETCH)
    assert n_cold.tolist() == [n for _, n in want]
    assert int(n_cold.sum()) == int(np.count_nonzero(idx < HOT0))
    got = np.stack([cold & 0xFFFF, cold >> 16], axis=-1).reshape(-1, slots)
    for mine, (theirs, n) in zip(got, want):
        assert np.array_equal(mine[:theirs.size], theirs)
        assert np.all(mine[n:] == (mine[n - 1] if n else 0))


@pytest.mark.parametrize("case", CASES)
def test_the_loaders_rows_are_what_passes_1_and_2_computed(case):
    """Until PR 53 the kernel made a slot's two row addresses itself,
    every call, from the index re-based on the range (``rel``): pass 1
    ``min(uint32(rel), n_res - 1)``, pass 2 ``rel + hot_row0`` at every
    listed position. The loader's ``row`` is the first at every slot,
    and the pack's own indices are the second."""
    rng = np.random.default_rng(11)
    idx = _block(case, rng)
    val = rng.standard_normal(idx.shape).astype(np.float32)
    idx_b, _, row, cold, n_cold = (np.asarray(a) for a in _lists(idx, val))
    n_res = PLAN.resident_rows
    rel = idx - np.int32(HOT0)
    want = np.minimum(rel.astype(np.uint32), np.uint32(n_res - 1))
    assert row.dtype == np.int32 and row.shape == idx.shape
    assert np.array_equal(row, want.astype(np.int32))
    assert row.min() >= 0 and row.max() <= n_res - 1
    # hot: its own row of the range; cold: the range's last row
    assert np.array_equal(row[rel >= 0], rel[rel >= 0])
    assert np.all(row[rel < 0] == n_res - 1)
    slots = pallas_als.chunk_rows(96) * 128
    listed = np.stack([cold & 0xFFFF, cold >> 16], axis=-1) \
        .reshape(-1, slots)
    assert np.array_equal(idx_b, idx)
    for chunk, rel_c, at, n in zip(idx_b.reshape(-1, slots),
                                   rel.reshape(-1, slots), listed, n_cold):
        trips = -(-int(n) // pallas_als.FETCH) * pallas_als.FETCH
        assert np.array_equal(chunk[at[:trips]], rel_c[at[:trips]] + HOT0)
        # every row pass 2 copies is a cold slot's own row of the table
        assert np.all(chunk[at[:trips]] < HOT0)
        assert sorted(set(at[:n].tolist())) == np.flatnonzero(
            chunk < HOT0).tolist()
    if case == "split":
        assert n_cold.tolist() == [0, slots]


@pytest.mark.parametrize("case", CASES)
def test_mosaic_gather_returns_the_rows_bitwise(case):
    rng = np.random.default_rng(11)
    T = _table(rng)
    idx = _block(case, rng)
    val = rng.standard_normal(idx.shape).astype(np.float32)
    assert pallas_als.chunk_rows(96) == 48 and pallas_als.chunk_rows(4) == 0
    table = ops.gather_table(jnp.asarray(T), GEOM96, ROWS, PLAN)
    assert np.array_equal(np.asarray(ops.gather_table(
        jnp.asarray(T), GEOM96, ROWS)), T)      # XLA's form: as it is
    got = np.asarray(jax.jit(
        lambda t, *a: pallas_als.gather_rows_resident(
            t, *a, HOT0, K, interpret=True))(table, *_lists(idx, val)))
    assert got.shape == (96 * 128, 128)
    flat = idx.reshape(-1)
    # the rows bit for bit, the rating's and the validity's lanes the
    # values XLA's ``where`` writes
    lanes = [K, K + 1]
    assert np.array_equal(np.delete(got, lanes, 1),
                          np.delete(T[flat], lanes, 1))
    lane = np.arange(128)[None, :]
    assert np.array_equal(got, np.where(
        lane == K, val.reshape(-1, 1), np.where(
            lane == K + 1, (flat != ROWS)[:, None].astype(np.float32),
            T[flat])))
    # and XLA's gather, which is what the rows are held to
    assert np.array_equal(np.asarray(ops.gather_rows(
        jnp.asarray(T), jnp.asarray(idx))), T[flat])


@pytest.mark.parametrize("case", CASES)
def test_a_blocks_gramians_are_the_same_in_both_forms_bit_for_bit(case):
    rng = np.random.default_rng(13)
    T = jnp.asarray(_table(rng))
    idx = _block(case, rng)
    val = rng.integers(0, 101, idx.shape).astype(np.float32)
    rel, val_t, *cold = _lists(idx, val)
    for depth in (1, 8):
        xla = jax.jit(lambda t, i, v: ops.block_gramians(
            t, i, v, depth, GEOM96, ROWS))(T, idx, val)
        mosaic = jax.jit(lambda t, i, v, *c: ops.block_gramians(
            ops.gather_table(t, GEOM96, ROWS, PLAN), i, v, depth, GEOM96,
            ROWS, PLAN, c))(T, rel, val_t, *cold)
        assert xla.shape == (384 // depth, 128, 128)
        assert np.array_equal(np.asarray(xla), np.asarray(mosaic))


def _toy(mesh, seed=4):
    rng = np.random.default_rng(5)
    du = np.concatenate([[700, 300], rng.integers(1, 120, 90)])
    di = np.full(60, du.sum() // 60)
    di[:du.sum() - di.sum()] += 1
    arrays, meta = als.build_ratings_table(
        int(du.sum()), len(du), len(di), K, mesh, data_seed=seed,
        n_heldout=64, degrees=(du, di), geometry=GEOM)
    return du, di, arrays, meta


def test_a_fit_is_the_same_in_both_forms_bit_for_bit(mesh1):
    du, di, arrays, meta = _toy(mesh1)
    geom = meta["geometry"]
    assert geom.block_shape == (16, 128) and geom.width == 128
    assert meta["forms"]["als_gather_form"] == "xla"      # on the CPU
    cfg = als.ALSConfig(lam=LAM, m=len(du), n=len(di), k=K,
                        n_iterations=2, seed=3)
    mosaic = tuple(
        dataclasses.replace(ops.gather_plan(o.static, geom, True),
                            interpret=True)
        for o in (meta["item"], meta["user"]))
    assert [g.form for g in mosaic] == ["mosaic", "mosaic"]
    # the whole of a toy table fits the budget: cut the range at the
    # heavy class, so that both kinds of slot are there
    mosaic = tuple(
        dataclasses.replace(g, hot_row0=o.static.heavy[2],
                            resident_rows=o.static.table_rows
                            - o.static.heavy[2])
        for g, o in zip(mosaic, (meta["item"], meta["user"])))
    assert all(0 < g.hot_row0 for g in mosaic)
    # the arrays as a loader on a TPU would hold them: the lists made
    # of the pack's own two, a half each, the turned ratings in their
    # place
    assert len(arrays) == 9
    made = [jax.jit(lambda i, v, g=g: ops.gather_lists(i, v, g))(
        arrays[at], arrays[at + 1]) for g, at in zip(mosaic, (0, 3))]
    held = (arrays[0], made[0][0], arrays[2], arrays[3], made[1][0],
            *arrays[5:], *made[0][1:], *made[1][1:])
    fields = als.segment_fields(dict(meta, gather=mosaic))
    assert [int(jnp.sum(m[3])) for m in made] == [
        own.slots_held - ops.resident_slots(own, other, g.hot_row0)
        for own, other, g in zip((meta["user"], meta["item"]),
                                 (meta["item"], meta["user"]), mosaic)]
    assert (fields["gather_cold_list"], fields["gather_slot_rows"],
            fields["gather_lanes"]) == ("loader", "loader", "kernel")
    out = []
    for gather, args in ((meta["gather"], arrays), (mosaic, held)):
        fn = als.make_fit_fn(mesh1, cfg, dict(meta, gather=gather))
        X, Theta = als.start_factors(meta, mesh1, cfg.seed)
        out.append([np.asarray(a) for a in fn(*args, X, Theta)])
    for a, b in zip(*out):       # X, Theta, (training, held-out RMSE),
        assert np.array_equal(a, b)              # ratings seen
    assert out[0][3].tolist() == [[int(du.sum())] * 2] * 2
    # a form's function takes its own arrays and no others
    with pytest.raises(TypeError, match="cold lists"):
        als.make_fit_fn(mesh1, cfg, dict(meta, gather=mosaic))(
            *arrays, *als.start_factors(meta, mesh1, cfg.seed))


def test_no_list_is_built_on_a_mesh_or_off_the_chip(mesh4, mesh1):
    for mesh in (mesh4, mesh1):
        _, _, arrays, meta = _toy(mesh)
        assert meta["forms"]["als_gather_form"] == "xla"
        assert len(arrays) == 9          # as long as it has always been
        fields = als.segment_fields(meta)
        assert (fields["gather_cold_list"], fields["gather_cold_slots"],
                fields["gather_cold_share"], fields["gather_list_bytes"],
                fields["gather_slot_rows"], fields["gather_lanes"]) == (
            "none", [0, 0], 0.0, 0, "none", "xla")
        # the pack's own indices and ratings, as the pack made them
        assert arrays[0].dtype == arrays[3].dtype == jnp.int32
        assert meta["ratings_bytes"] == 8 * (
            meta["user"].slots_held + meta["item"].slots_held)


def _static(n_shards=1, heavy_rows=64, light_rows=128):
    rows = light_rows + heavy_rows
    return ops.SideStatic(n_shards, rows, 4, ((1, 0, 2, 0),),
                          (2, 2, light_rows, heavy_rows))


def test_the_form_follows_what_the_code_can_observe():
    geom = ops.SparseGeometry(k=K, **GEOM)
    st = _static()
    got = ops.gather_plan(st, geom, True)
    assert (got.form, got.hot_row0, got.resident_rows) == (
        "mosaic", 0, st.table_rows)      # all of it under the budget
    xla = ops.GatherPlan("xla", st.table_rows, 0)
    assert ops.gather_plan(st, geom, False) == xla          # not a TPU
    two = _static(n_shards=2)
    assert ops.gather_plan(two, geom, True) == ops.GatherPlan(
        "xla", two.table_rows, 0)                           # a mesh
    wide = ops.SparseGeometry(k=127, **GEOM)
    assert wide.width == 256
    assert ops.gather_plan(st, wide, True) == xla           # two vectors
    thin = ops.SparseGeometry(k=K, seg_slots=8, piece_segs=4, batch=8,
                              classes=(1, 2))
    assert ops.gather_plan(st, thin, True) == xla    # a block of 64 slots
    big = _static(heavy_rows=ops.GATHER_VMEM_BYTES // 512)
    assert ops.resident_row0(big, geom) is None
    assert ops.gather_plan(big, geom, True) == ops.GatherPlan(
        "xla", big.table_rows, 0)                    # past the budget
    # a class at a time, largest first, while the budget holds
    assert ops.resident_row0(st, geom, (64 + 8) * 512) == 128
    assert ops.resident_row0(st, geom, (64 + 8) * 512 - 1) is None


@pytest.fixture(scope="module")
def cell_meta():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "als-yahoomusic-f100.json")) as f:
        c = json.load(f)
    return als.plan_ratings(
        c["n_ratings"], c["n_users"], c["n_items"], c["k"], 1,
        n_heldout=c["n_heldout"], geometry=dict(c["geometry"]),
        on_tpu=True, **c["generator"])


def test_the_cells_statics_take_the_kernel(cell_meta):
    meta = cell_meta
    assert meta["forms"]["als_gather_form"] == "mosaic"
    user_half, item_half = meta["gather"]
    # the heavy class's 18 432 rows and the 8 zero rows, 9.4 MB a side
    assert (user_half.hot_row0, user_half.resident_rows) == (645120, 18440)
    assert (item_half.hot_row0, item_half.resident_rows) == (1013760, 18440)
    assert meta["gather_resident_rows"] == (18440, 18440)
    np.testing.assert_allclose(meta["gather_resident_shares"],
                               (0.79089, 0.67984), atol=1e-5)
    assert abs(meta["gather_resident_share"] - 0.73643) < 1e-5
    fields = als.segment_fields(meta)
    assert (fields["als_gather_form"], fields["gather_resident_rows"],
            fields["gather_resident_share"]) == (
        "mosaic", [18440, 18440], 0.7364)
    # the lists are the loader's: 160.2M live entries over both halves,
    # half a word a slot held; so is a slot's resident row, a word a
    # slot held (3.65 GB with the lists); and the kernel writes the lanes
    assert (fields["gather_cold_list"], fields["gather_slot_rows"],
            fields["gather_lanes"]) == ("loader", "loader", "kernel")
    assert fields["gather_cold_slots"] == [64753249, 95425029]
    assert fields["gather_cold_share"] == 0.2636
    assert fields["gather_list_bytes"] == 6 * (
        meta["user"].slots_held + meta["item"].slots_held) == 3646291968
    assert meta["ratings_bytes"] == 14 * (
        meta["user"].slots_held + meta["item"].slots_held) == 8508014592
    assert als._prepare_fields(meta)["gather_cold_slots"] == [
        64753249, 95425029]
    # the same sizes where the fit will not run on a TPU: XLA's form,
    # nothing resident
    off = als._ratings_meta(meta["geometry"], (meta["user"], meta["item"]),
                            meta["n_ratings"], 0, 1, False)
    assert off["forms"]["als_gather_form"] == "xla"
    assert off["gather_resident_rows"] == (0, 0)
    assert off["gather_resident_share"] == 0.0


def test_the_lists_have_to_fit_the_chip(cell_meta):
    """The kernel is taken where what the loader makes for it fits a
    device's memory beside the table, the tables and a half's
    accumulator (8.51 + 0.87 + 1.61 GB at the cell's shape); XLA's
    gather, which wants no list, where it does not; and the plan as it
    always was where nobody says how much there is."""
    from tpu_distalg.telemetry import report

    meta = cell_meta
    plans = (meta["user"], meta["item"])

    def plan(hbm_bytes):
        return als._ratings_meta(meta["geometry"], plans, meta["n_ratings"],
                                 0, 1, True, hbm_bytes)

    need = meta["ratings_bytes"] + meta["factor_bytes"] \
        + (18432 + 1 + 6144) * 128 * 128 * 4
    assert 10.9e9 < need < 11.1e9
    for room in (None, 16_911_433_728, need):        # a v5e: 15.75 GiB
        fits = plan(room)
        assert fits["forms"]["als_gather_form"] == "mosaic"
        assert fits["gather_list_bytes"] == meta["gather_list_bytes"]
        assert als.segment_fields(fits)["gather_cold_list"] == "loader"
    short = plan(need - 1)
    assert short["forms"]["als_gather_form"] == "xla"
    assert short["forms"]["als_solve_form"] == "mosaic"     # as it was
    assert short["gather_list_bytes"] == 0
    assert short["ratings_bytes"] == 8 * (
        plans[0].slots_held + plans[1].slots_held)
    fields = als.segment_fields(short)
    assert (fields["gather_cold_list"], fields["gather_slot_rows"],
            fields["gather_cold_slots"], fields["gather_lanes"]) == (
        "no room", "none", [0, 0], "xla")
    start = dict(ev="span_start", name="train:segment", id=1, parent=None,
                 t=0.0, **fields)
    line, = [ln for ln in report.render(report.summarize([start]))
             .splitlines() if ln.startswith("R layout")]
    assert "gather: xla (no room for the kernel's lists)" in line
    # off the chip there is no list to fit
    off = als._ratings_meta(meta["geometry"], plans, meta["n_ratings"],
                            0, 1, False, 1)
    assert als.segment_fields(off)["gather_cold_list"] == "none"


def test_the_report_prints_what_the_kernel_is_handed(cell_meta):
    from tpu_distalg.telemetry import report

    start = dict(ev="span_start", name="train:segment", id=1, parent=None,
                 t=0.0, **als.segment_fields(cell_meta))
    line, = [ln for ln in report.render(report.summarize([start]))
             .splitlines() if ln.startswith("R layout")]
    assert line == (
        "R layout: ratings (gather: mosaic with 0.7364 of the slots "
        "resident, 0.2636 cold and listed by the loader (160178278 slots, "
        "3646291968 B), a slot's rows by the loader, lanes by the kernel, "
        "gramians: xla by owners, solve: mosaic in tiles of 128)")


def test_resident_share_is_a_count_over_the_packed_indices(mesh1):
    _, _, arrays, meta = _toy(mesh1, seed=8)
    pu, pi = meta["user"], meta["item"]
    for idx, own, other in ((arrays[0], pu, pi), (arrays[3], pi, pu)):
        idx = np.asarray(idx)
        assert idx.size == own.slots_held
        for row0 in (other.static.heavy[2], other.static.light[1][3], 0,
                     other.static.zero_row):
            assert ops.resident_slots(own, other, row0) == int(
                np.count_nonzero(idx >= row0)), row0
