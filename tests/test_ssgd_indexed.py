"""SSGD over indexed rows (``meta["row_format"] == "indexed"``: the
fields' ranges end to end in the weight table, every value its own
weight, a table that may be wider than VMEM): the trainer against the
plain reference (``benchmarks/reference/ssgd_indexed_ref.py``: one flat
``w[idx].sum(-1)`` and ``zeros(D).at[idx].add``, its own restatement of
the generator) on seeded tables with the VMEM bound shrunk so that every
form a field can take runs (by value, by address in groups, in HBM),
the reference's bfloat16 control and an unchanged state outside the
same limit; the hashed trainer under the identity map, slot for slot;
the draw past 2**24 values; the forms at the benchmark's eleven sizes;
the fields' forms against XLA's over the whole table; the invalid
tail; shards; the refusals, the scopes, the spans and the CLI."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg.models import ssgd
from tpu_distalg.ops import pallas_hashed as ph
from tpu_distalg.telemetry import events, names, report
from tpu_distalg.utils import datasets

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import ssgd_indexed_ref as ref_mod  # noqa: E402

W_LIMIT = 1e-5      # float32 rounding over a few steps reads 1e-7
# the benchmark's eleven fields in their order, at a CPU's size: under a
# VMEM bound of 2**12 slots fields 5 and 9 (the query and user ids) are
# past it
CARDS = (300, 2000, 150, 3, 3, 9000, 1500, 3000, 2500, 7000, 21)
KDD12 = (24323, 594098, 13745, 3, 3, 24296581, 1157062, 3750862, 2936510,
         21913244, 21)


@pytest.fixture
def small_vmem(monkeypatch):
    monkeypatch.setattr(ph, "VMEM_BITS", 12)


def _cfg(block_rows, fraction, steps, **kw):
    return ssgd.SSGDConfig(
        n_iterations=steps, sampler="fused_gather", eval_test=False,
        gather_block_rows=block_rows, mini_batch_fraction=fraction,
        seed=42, **kw)


def _ref_config(n_rows, cards, block_rows):
    return {"n_rows": n_rows, "nnz": len(cards), "n_features": sum(cards),
            "gather_block_rows": block_rows, "eta": 0.1,
            "field_cardinalities": list(cards), "zipf_exponent": 1.1,
            "planted_scale": 0.25, "click_rate": 0.256}


def _prepare(mesh, n_rows, cards, cfg, seed):
    return ssgd.prepare_hashed_synthetic(
        n_rows, len(cards), 0, mesh, cfg, data_seed=seed,
        cardinalities=cards, row_format="indexed")


def _train(mesh, n_rows, cards, cfg, seed):
    fn, X, w0, meta = _prepare(mesh, n_rows, cards, cfg, seed)
    d = jnp.zeros((1,), jnp.float32)
    w, _ = fn(X, d, d, d, d, w0)
    return np.asarray(w), X, meta


# ---- the trainer against the plain reference -------------------------

@pytest.mark.parametrize("cards,block_rows,n_rows,fraction,forms", [
    # every form: 5 by value, 4 by address in 3 groups, 2 in HBM
    (CARDS, 256, 5000, 0.5, (5, 3, 2)),
    (CARDS, 128, 3000, 0.25, (5, 3, 2)),        # a short last block
    # no field by value, none in HBM; all blocks
    ((2000, 1500, 3000), 128, 1500, 1.0, (0, 2, 0)),
    # nothing by address: two ranges past the bound, one by value
    ((5000, 40, 6000), 256, 4000, 0.5, (1, 0, 2)),
    (CARDS, 64, 2000, 0.25, None),      # xla: a block is not whole lanes
])
def test_trainer_follows_the_reference(mesh1, small_vmem, cards,
                                       block_rows, n_rows, fraction, forms):
    steps, seed = 4, 11
    cfg = _cfg(block_rows, fraction, steps)
    w, _, meta = _train(mesh1, n_rows, cards, cfg, seed)
    plan = ssgd.hashed_field_plan(cfg, meta)
    if forms is None:
        assert plan is None
        assert ssgd.hashed_geometry(cfg, meta).pass_form == "xla"
    else:
        assert (len(plan.dict_fields), len(plan.addr_groups),
                len(plan.hbm_fields)) == forms
    ref = ref_mod.Reference(
        config=_ref_config(n_rows, cards, block_rows), fraction=fraction,
        data_seed=seed, sample_seed=cfg.seed)
    D = sum(cards)
    w0 = np.zeros((D + 1,), np.float32)
    (good,) = ref.follow(1, steps)
    mine = ref_mod.model_vector(w, D)
    assert np.linalg.norm(good) > 0.01          # it moved
    assert ref_mod.rel_err(mine, good, w0) < W_LIMIT
    # the control: weights, gathered weights, per-slot sums in bfloat16
    (low,) = ref.follow(1, steps, dtype=jnp.bfloat16)
    assert ref_mod.rel_err(low, good, w0) > 30 * W_LIMIT
    # a step that hands its state back
    assert ref_mod.rel_err(w0, good, w0) == 1.0 > W_LIMIT


def test_the_hashed_trainer_under_the_identity_map(mesh1, small_vmem):
    """An indexed table's slots never collide, so the hashed trainer,
    told that the same rows index a table of ``2 ** 15`` slots (the
    identity for a hash), has to give the same weights slot for slot:
    nothing of the fields' forms, groups, bases or ranges may show."""
    cfg = _cfg(256, 0.5, 4)
    fn, X, w0, meta = _prepare(mesh1, 5000, CARDS, cfg, seed=7)
    d = jnp.zeros((1,), jnp.float32)
    w, _ = fn(X, d, d, d, d, w0)
    D, bits = sum(CARDS), 15
    assert D <= 1 << bits
    as_hashed = dict(row_format="hashed", nnz=len(CARDS), hash_bits=bits,
                     pack=1, n_rows=meta["n_rows"],
                     n_padded=meta["n_padded"], d_total=(1 << bits) + 128)
    assert ssgd.hashed_geometry(cfg, as_hashed).pass_form == "xla"
    w_h, _ = ssgd.make_train_fn_fused(mesh1, cfg, as_hashed)(
        X, d, d, d, d, jnp.zeros(((1 << bits) + 128,), jnp.float32))
    w, w_h = np.asarray(w), np.asarray(w_h)
    assert np.count_nonzero(w_h[:D]) > 1000
    assert not w_h[D:1 << bits].any()           # no slot past the features
    np.testing.assert_allclose(w[:D], w_h[:D], rtol=2e-6, atol=1e-9)
    np.testing.assert_allclose(w[D], w_h[1 << bits], rtol=2e-6)
    assert np.array_equal(w[:D] != 0, w_h[:D] != 0)


# ---- the generator ----------------------------------------------------

def test_the_draw_reaches_odd_values_past_2_24():
    """A field of 30M values: the hashed generator's float32 value holds
    no odd integer past 16 777 216; the indexed one's is taken in int32
    and reaches them, stays inside the field and keeps the law's head."""
    n, card = 1 << 19, 30_000_000
    ids = jnp.arange(n)
    slots, _ = jax.jit(datasets.indexed_click_rows((card, 5)))(
        ids, jnp.int32(3))
    v = np.asarray(slots)[:, 0]
    far = v[v > 1 << 24]
    assert v.min() >= 0 and v.max() < card
    assert len(far) > 500 and 0.3 < np.mean(far % 2 == 1) < 0.7
    # the head: (1 - 2 ** -0.1) / (1 - (card + 1) ** -0.1) = 0.0816
    assert 0.078 < np.mean(v == 0) < 0.085
    second = np.asarray(slots)[:, 1] - card
    assert second.min() == 0 and second.max() == 4
    # the float32 inverse, for contrast: every value past 2**24 is even
    u = np.random.default_rng(0).random(n, dtype=np.float32)
    span = np.float32((card + 1.0) ** -0.1 - 1.0)
    x = np.floor((np.float32(1) + u * span) ** np.float32(-10.0))
    assert not np.any(x[x > 1 << 24] % 2)


def test_rows_are_a_function_of_seed_and_id():
    make = jax.jit(datasets.indexed_click_rows(CARDS))
    off = np.asarray(datasets.click_field_offsets(CARDS))
    a, ya = make(jnp.arange(100, 400), jnp.int32(1))
    b, yb = make(jnp.arange(0, 400), jnp.int32(1))
    c, _ = make(jnp.arange(100, 400), jnp.int32(2))
    assert np.array_equal(a, b[100:]) and np.array_equal(ya, yb[100:])
    assert not np.array_equal(a, c)
    a = np.asarray(a)
    assert (a >= off[:-1]).all() and (a < off[1:]).all()
    assert off[-1] == sum(CARDS)
    dicts = datasets.indexed_field_dictionaries((3, 70000, 5))
    assert dicts[1] is None
    assert dicts[0].tolist() == [0, 1, 2]
    assert dicts[2].tolist() == [70003 + k for k in range(5)]
    with pytest.raises(ValueError, match="int32"):
        datasets.click_field_offsets((1 << 30, 1 << 30))


# ---- the forms, from sizes alone ---------------------------------------

def test_forms_at_the_benchmarks_eleven_sizes():
    """``lr-kdd12-wide55m`` under the real bound, blocks of 8192 rows."""
    assert ph.VMEM_BITS == 22
    assert ph.pass_form(0, 8192, KDD12) == "fields"
    assert ph.pass_form(0, 8192 + 64, KDD12) == "xla"
    dicts = datasets.indexed_field_dictionaries(KDD12)
    forms = [ph.field_form(0 if d is None else len(d), 8192, n)
             for d, n in zip(dicts, KDD12)]
    assert forms == ["addr", "addr", "addr", "dict", "dict", "hbm", "addr",
                     "addr", "addr", "hbm", "dict"]
    # a field of 24 323 values states its range; 24 323 > 3800: by address
    assert dicts[0] is not None and dicts[1] is None
    geom = ph.HashedGeometry(11, 0, 8192, field_sizes=KDD12)
    # the weights, the bias, zeros to whole rows of 128 lanes
    assert (geom.n_slots, geom.w_len) == (54686452, 427238 * 128)
    assert (geom.fields_held, geom.row_bytes) == (16, 64)
    plan = ph.field_plan(geom, dicts)
    assert plan.dict_fields == (3, 4, 10) and plan.n_values == 27
    assert plan.hbm_fields == (5, 9)
    assert plan.addr_fields == (0, 1, 2, 6, 7, 8)
    groups = plan.addr_groups
    assert [g.fields for g in groups] == [(0, 1, 2, 6), (7,), (8,)]
    assert [g.n_slots for g in groups] == [1789952, 3750912, 2936832]
    off = geom.offsets
    assert groups[0].spans[3] == (off[6], off[7])
    # a field's slot h lies at h - base in its group's table
    assert groups[0].bases == (0, 0, 0, off[6] - (24323 + 594098 + 13745))
    assert groups[1].bases == (off[7],)
    # a hashed table's fields are addressed as before
    assert ph.field_form(0, 8192) == "addr"
    assert ph.field_form(3800, 8192) == "dict"
    assert ph.field_form(3801, 8192, 3801) == "addr"
    assert ph.field_form(0, 8192, (1 << 22) + 1) == "hbm"
    assert ph.field_form(0, 8192, 1 << 22) == "addr"


def test_groups_never_pass_the_bound(small_vmem):
    off = datasets.click_field_offsets((3000, 1000, 97, 4000, 100, 4096))
    groups = ph.addr_groups((0, 1, 2, 3, 4, 5), off)
    assert [g.fields for g in groups] == [(0, 1), (2,), (3,), (4,), (5,)]
    assert sorted(f for g in groups for f in g.fields) == list(range(6))
    for g in groups:
        assert sum(hi - lo for lo, hi in g.spans) <= 4096
        assert g.n_slots % 1024 == 0 and g.n_slots <= 4096


def test_a_geometry_is_hashed_or_indexed():
    with pytest.raises(ValueError, match="indexed rows"):
        ph.HashedGeometry(3, 10, 128, field_sizes=(5, 5, 5))
    with pytest.raises(ValueError, match="indexed rows"):
        ph.HashedGeometry(3, 0, 128, field_sizes=(5, 5))
    with pytest.raises(ValueError, match="hashed rows"):
        ph.HashedGeometry(3, 0, 128)
    geom = ph.HashedGeometry(2, 0, 128, field_sizes=(5, 7))
    assert (geom.row_format, geom.n_slots, geom.offsets) == (
        "indexed", 12, (0, 5, 12))


# ---- the fields' forms against XLA's over the whole table ---------------

@pytest.mark.parametrize("cards,block_rows", [
    (CARDS, 256), ((5000, 40, 6000), 128), ((2000, 1500, 3000), 1024)])
def test_every_form_gives_xlas_numbers(mesh1, small_vmem, cards,
                                       block_rows):
    cfg = _cfg(block_rows, 0.5, 1)
    X, meta = ssgd.build_hashed_table(
        6 * block_rows - 17, len(cards), 0, mesh1, cfg, data_seed=4,
        cardinalities=cards, row_format="indexed")
    geom = ssgd.hashed_geometry(cfg, meta)
    plan = ssgd.hashed_field_plan(cfg, meta)
    assert geom.pass_form == "fields"
    ids = jnp.array([5, 0, 3], jnp.int32)
    key = jax.random.key(1)
    w = jax.random.normal(key, (geom.w_len,)).at[geom.n_slots + 1:].set(0)
    r = jax.random.normal(jax.random.fold_in(key, 1), (3, block_rows))
    m = ph.margins(X, w, ids, geom, plan=plan, interpret=True)
    g = ph.slot_sums(X, r, ids, geom, plan=plan, interpret=True)
    np.testing.assert_allclose(m, ph.margins_xla(X, w, ids, geom),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(g, ph.slot_sums_xla(X, r, ids, geom),
                               rtol=1e-5, atol=1e-5)
    ones = ph.slot_sums(X, jnp.ones_like(r), ids, geom, plan=plan,
                        interpret=True)
    counts = np.zeros((geom.w_len,), np.int64)
    np.add.at(counts, np.asarray(X)[np.asarray(ids)][:, :geom.nnz, :], 1)
    counts[geom.n_slots] = r.size
    np.testing.assert_array_equal(np.asarray(ones), counts)


def test_the_invalid_tail_adds_nothing(mesh1, small_vmem):
    """All blocks sampled, one step from zero weights: every residual is
    0.5 - y, so the bias moves by eta times the mean over exactly the
    valid rows, and a feature by its valid occurrences."""
    n_rows = 1000
    w, X, meta = _train(mesh1, n_rows, CARDS, _cfg(256, 1.0, 1), seed=2)
    assert meta["n_padded"] == 1024
    D = sum(CARDS)
    Xn = np.asarray(X)
    flat = Xn.transpose(0, 2, 1).reshape(-1, Xn.shape[1])
    resid = 0.5 - flat[:n_rows, 11].astype(np.float64)
    g = np.zeros((D,), np.float64)
    np.add.at(g, flat[:n_rows, :11], resid[:, None] * np.ones((1, 11)))
    np.testing.assert_allclose(w[D], -0.1 * resid.mean(), rtol=1e-5)
    np.testing.assert_allclose(w[:D], -0.1 * g / n_rows, rtol=1e-5,
                               atol=1e-9)
    assert not w[D + 1:].any()


def test_one_shard_and_four_agree(mesh1, mesh4, small_vmem):
    """The same rows over four shards (a psum of the whole gradient a
    step) and one: the block grid differs, so compare a full batch."""
    cfg = _cfg(128, 1.0, 2)
    w1, _, _ = _train(mesh1, 2048, CARDS, cfg, seed=3)
    w4, _, _ = _train(mesh4, 2048, CARDS, cfg, seed=3)
    np.testing.assert_allclose(w1, w4, rtol=2e-5, atol=1e-8)


# ---- refusals, names, spans, the CLI ------------------------------------

@pytest.mark.parametrize("change,word", [
    (dict(sampler="fused_train"), "megakernel"),
    (dict(comm="int8"), "comm='int8'"),
    (dict(sync="ssp:4"), "BSP"),
    (dict(feature_sharded=True), "sharded over chips"),
])
def test_what_cannot_take_indexed_rows_refuses_by_name(mesh1, change, word):
    cfg = dataclasses.replace(_cfg(128, 0.25, 1), **change)
    meta = dict(row_format="indexed", nnz=3, hash_bits=0, pack=1,
                n_rows=1000, n_padded=1024, cardinalities=(5, 6, 7),
                d_total=128)
    with pytest.raises(ValueError, match="indexed rows") as err:
        ssgd.make_train_fn_fused(mesh1, cfg, meta)
    assert word in str(err.value) and "2**hash_bits weights" not in str(
        err.value)


def test_an_unknown_row_format_is_refused(mesh1):
    with pytest.raises(ValueError, match="row_format 'sorted'"):
        ssgd.build_hashed_table(100, 2, 0, mesh1, _cfg(128, 1.0, 1),
                                cardinalities=(3, 4), row_format="sorted")


def test_lowered_trainer_names_the_table_in_hbm(mesh1, small_vmem):
    cfg = _cfg(128, 0.25, 2)
    fn, X, w0, _ = _prepare(mesh1, 3000, CARDS, cfg, seed=1)
    d = jnp.zeros((1,), jnp.float32)
    text = fn.lower(X, d, d, d, d, w0).as_text(debug_info=True)
    for scope in (names.SSGD_DRAW, names.SSGD_GATHER, names.SSGD_SCATTER,
                  names.SSGD_UPDATE, names.SSGD_SYNC):
        assert scope + "/" in text, scope
    # the scope of the ranges past VMEM lies inside either pass's own
    for outer in (names.SSGD_GATHER, names.SSGD_SCATTER):
        assert f"{outer}/{names.SSGD_TABLE_HBM}/" in text, outer
    for kernel in ("_hashed_gather_kernel", "_hashed_scatter_kernel",
                   "_hashed_rows_kernel", "_hashed_value_gather_kernel",
                   "_hashed_value_sums_kernel",
                   "_hashed_hbm_gather_kernel"):
        assert kernel in text, kernel


@pytest.mark.parametrize("block_rows,fields", [
    (256, (5, 9)), (128, (9,)), (1024, (0, 5, 7)), (256, (5,)),
    (128, (1, 5, 8, 9))])
def test_the_gather_from_hbm_is_xlas_bit_for_bit(mesh1, block_rows, fields):
    """``_hashed_hbm_gather_kernel`` interpreted (a DMA a pair from the
    model vector as rows of 128 lanes, the lane kept) against ``w[idx]``:
    the same float32 weights, one field's alone bit for bit, several
    fields' sums to the order of their additions; its third pass at the
    rule's rows a trip and at 2, bit for bit."""
    cfg = _cfg(block_rows, 0.5, 1)
    X, meta = ssgd.build_hashed_table(
        5 * block_rows - 9, 11, 0, mesh1, cfg, data_seed=6,
        cardinalities=CARDS, row_format="indexed")
    geom = ssgd.hashed_geometry(cfg, meta)
    assert geom.w_len % 128 == 0 and geom.w_len >= geom.n_slots + 1
    ids = jnp.array([4, 1, 2], jnp.int32)
    w = jax.random.normal(jax.random.key(3), (geom.w_len,))
    got = ph.margins_hbm(X, w, ids, geom, fields, interpret=True)
    assert ph._loop_rows(geom, len(fields)) > ph.LOOP_ROWS
    np.testing.assert_array_equal(np.asarray(got), np.asarray(
        ph.margins_hbm(X, w, ids, geom, fields, interpret=True, rows=2)))
    want = ph.margins_hbm_xla(X, w, ids, fields)
    if len(fields) == 1:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


# ---- a call's trip follows the fields it serves --------------------------

# thirteen fields: a block holds 16 rows of indices, as the benchmark's
CARDS13 = (300, 200, 150, 3, 3, 900, 500, 700, 400, 600, 21, 50, 350)


def _crafted13(block_rows, nb):
    geom = ph.HashedGeometry(13, 0, block_rows, field_sizes=CARDS13)
    rng = np.random.default_rng(7)
    X = np.zeros((nb, geom.fields_held, block_rows), np.int32)
    for f, (lo, hi) in enumerate(zip(geom.offsets, geom.offsets[1:])):
        X[:, f, :] = rng.integers(lo, hi, (nb, block_rows))
    X[:, 13, :] = rng.integers(0, 2, (nb, block_rows))
    return geom, jnp.asarray(X)


@pytest.mark.parametrize("n_acc", [1, 2, 4])
@pytest.mark.parametrize("fields,rows", [
    ((7,), 32), ((8,), 32), ((0, 1, 2, 6), 8), ((3, 12), 16)])
def test_a_calls_trip_follows_its_fields(fields, rows, n_acc, tmp_path):
    """One call of each by-address kernel over a group with bases, at
    the rule's rows a trip against 2 rows a trip: the same float32
    numbers bit for bit (a row's sums fold in one order, a pair goes to
    the accumulator its row's parity gives it), XLA's to rounding; and
    the call says once what it runs."""
    geom, X = _crafted13(256, 5)
    group, = ph.addr_groups(fields, geom.offsets)
    assert any(group.bases) and group.fields == fields
    assert ph._loop_rows(geom, len(fields)) == rows
    ids = jnp.array([3, 0, 4], jnp.int32)
    key = jax.random.key(5)
    w = jax.random.normal(key, (geom.w_len,))
    r = jax.random.normal(jax.random.fold_in(key, 1), (3, 256))

    def both(**kw):
        return (np.asarray(ph.margins_vmem(
                    X, w, ids, geom, interpret=True, group=group, **kw)),
                np.asarray(ph.slot_sums_vmem(
                    X, r, ids, geom, interpret=True, group=group,
                    n_acc=n_acc, **kw)))

    events.configure(str(tmp_path))
    try:
        m, g = both()
    finally:
        events.configure(False)
    said = [e for e in report.load_events(str(tmp_path))
            if e["ev"] == "ssgd:addr_call"]
    assert [(e["kernel"], e["fields"], e["rows"], e["pairs"],
             e["smem_rows"]) for e in said] == [
        (k, list(fields), rows, rows * len(fields), 16)
        for k in ("_hashed_gather_kernel", "_hashed_scatter_kernel")]
    assert (f"by-address call: _hashed_gather_kernel over fields "
            f"{list(fields)}: {rows} rows a trip ({rows * len(fields)} "
            f"pairs), 16 index rows a chunk in SMEM") in \
        report.render(report.summarize(said))
    m2, g2 = both(rows=2)
    np.testing.assert_array_equal(m, m2)
    np.testing.assert_array_equal(g, g2)
    np.testing.assert_allclose(
        m, ph.margins_hbm_xla(X, w, ids, fields), rtol=1e-6, atol=1e-6)
    idx = np.asarray(X)[np.asarray(ids)][:, list(fields), :]
    want = np.zeros((geom.w_len,), np.float64)
    np.add.at(want, idx, np.broadcast_to(
        np.asarray(r, np.float64)[:, None, :], idx.shape))
    for (lo, hi), base in zip(group.spans, group.bases):
        np.testing.assert_allclose(g[lo - base:hi - base], want[lo:hi],
                                   rtol=1e-5, atol=1e-5)


def test_spans_report_and_result_say_the_forms(mesh1, small_vmem, tmp_path):
    tel = str(tmp_path / "tel")
    events.configure(tel)
    try:
        res = ssgd.train_hashed(
            2000, 11, 0, mesh1, _cfg(128, 0.25, 4), cardinalities=CARDS,
            row_format="indexed", checkpoint_dir=str(tmp_path / "ck"),
            checkpoint_every=2)
    finally:
        events.configure(False)
    assert res.heldout_log_loss < 0.6931
    assert res.forms == (
        "row format indexed: 25477 weights (0.1 MB), passes fields: "
        "fields by value [0, 2, 3, 4, 10], by address in VMEM [[1, 6], "
        "[7], [8]] (a group a table of at most 2**12 slots), in HBM "
        "[5, 9]")
    evts = report.load_events(tel)
    ends = {e["name"]: e for e in evts if e["ev"] == "span_end"}
    prep = ends["ssgd:prepare"]
    assert (prep["row_format"], prep["nnz"], prep["table_bytes"],
            prep["rows"], prep["bytes"]) == ("indexed", 11, 4 * 25477,
                                             2000, 2048 * 64)
    assert (prep["fields_dict"], prep["fields_vmem"],
            prep["fields_hbm"]) == (5, 4, 2)
    # the CPU: both fields in HBM keep XLA's scatter-add
    assert (prep["fields_hbm_scatter_vmem"],
            prep["fields_hbm_scatter_xla"]) == (0, 2)
    seg = ends["train:segment"]
    assert (seg["row_format"], seg["gather_form"], seg["scatter_form"],
            seg["fields_hbm"], seg["fields_hbm_scatter_vmem"],
            seg["fields_hbm_scatter_xla"]) == (
                "indexed", "fields", "fields", 2, 0, 2)
    said = [(e["kernel"], e["form"], e["field"], e["range_slots"])
            for e in evts if e["ev"] == "ssgd:field_scatter"]
    assert set(said) == {("xla scatter-add", "xla", 5, 9000),
                         ("xla scatter-add", "xla", 9, 7000)}
    lines = report.render(report.summarize(evts)).splitlines()
    for line in ("row format: indexed", "gather pass: fields",
                 "scatter pass: fields",
                 "fields by value: 5 (477 values), by address: 4, in "
                 "HBM: 2 (a table of 0.1 MB; the sums of 0 in VMEM a "
                 "call, of 2 through XLA)",
                 "field scatter: xla scatter-add (xla) over field 5, a "
                 "range of 9000 slots",
                 "by-address call: _hashed_gather_kernel over fields "
                 "[1, 6]: 16 rows a trip (32 pairs), 16 index rows a "
                 "chunk in SMEM",
                 "by-address call: _hashed_scatter_kernel over fields "
                 "[8]: 32 rows a trip (32 pairs), 16 index rows a chunk "
                 "in SMEM",
                 "by-address call: _hashed_hbm_gather_kernel over fields "
                 "[5, 9]: 16 rows a trip (32 pairs), 16 index rows a "
                 "chunk in SMEM"):
        assert line in lines, line


def test_cli_trains_indexed_rows_and_prints_the_forms(capsys):
    from tpu_distalg import cli

    rc = cli.main(["--emulate", "1", "ssgd", "--indexed-rows", "3000",
                   "--field-values", "300,2000,150,3,3,5000,21",
                   "--gather-block-rows", "128",
                   "--mini-batch-fraction", "0.25", "--n-iterations", "6"])
    out = capsys.readouterr().out
    assert rc in (0, None)
    assert ("row format indexed: 7477 weights (0.0 MB), passes fields: "
            "fields by value [0, 2, 3, 4, 6], by address in VMEM "
            "[[1, 5]]") in out
    assert "Held-out accuracy:" in out


@pytest.mark.parametrize("argv,word", [
    (["--indexed-rows", "100", "--hashed-rows", "100"], "two tables"),
    (["--hashed-rows", "100", "--field-values", "3,4"], "--indexed-rows"),
])
def test_cli_refuses_what_names_no_table(argv, word):
    from tpu_distalg import cli

    with pytest.raises(SystemExit) as err:
        cli.main(["--emulate", "1", "ssgd"] + argv)
    assert word in str(err.value)
