"""SSGD over hashed rows (``models/ssgd.py``'s second row format,
``ops/pallas_hashed.py``): the trainer against the plain reference
(``benchmarks/reference/ssgd_hashed_ref.py``: the equations as
``w[idx].sum(-1)`` and ``zeros(D).at[idx].add``, its own restatement of
the generator and the draw) on seeded data over several steps, with the
reference's bfloat16 control outside the same limit; the two passes on
crafted rows (two fields of a row in one slot, a slot every row hits);
the invalid tail of the last block; the Mosaic kernels interpreted
against their XLA form; one shard against four; resume through
``run_segmented``; the loader; the samplers that refuse the format; the
names in the lowered program and the spans."""

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

from reference import ssgd_hashed_ref as ref_mod  # noqa: E402

W_LIMIT = 1e-5      # float32 rounding over a few steps reads 1e-7


def _cfg(block_rows, fraction, steps, **kw):
    return ssgd.SSGDConfig(
        n_iterations=steps, sampler="fused_gather", eval_test=False,
        gather_block_rows=block_rows, mini_batch_fraction=fraction,
        seed=42, **kw)


def _ref_config(n_rows, nnz, hash_bits, block_rows):
    cards = datasets.click_field_cardinalities(nnz)
    return {"n_rows": n_rows, "nnz": nnz, "hash_bits": hash_bits,
            "gather_block_rows": block_rows, "eta": 0.1,
            "int_field_cardinalities_assumed": list(cards[:13]),
            "field_cardinalities": list(cards[13:]),
            "zipf_exponent": 1.1, "planted_scale": 0.25,
            "click_rate": 0.256}


def _train(mesh, n_rows, nnz, hash_bits, cfg, seed, w0=None):
    fn, X, w_zero, meta = ssgd.prepare_hashed_synthetic(
        n_rows, nnz, hash_bits, mesh, cfg, data_seed=seed)
    d = jnp.zeros((1,), jnp.float32)
    w, _ = fn(X, d, d, d, d, w_zero if w0 is None else w0)
    return np.asarray(w), X, meta


# ---- the trainer against the plain reference -------------------------

@pytest.mark.parametrize("nnz,hash_bits,block_rows,n_rows,fraction", [
    (5, 10, 128, 3000, 0.25),       # vmem, a short last block
    (9, 12, 256, 5000, 0.5),        # vmem
    (39, 11, 128, 1500, 1.0),       # vmem, the cell's fields, all blocks
    (7, 10, 64, 2000, 0.25),        # xla: a block is not whole lanes
    (6, 9, 128, 2000, 0.25),        # xla: under one tile of slots
])
def test_trainer_follows_the_reference(mesh1, nnz, hash_bits, block_rows,
                                       n_rows, fraction):
    steps, seed = 4, 11
    cfg = _cfg(block_rows, fraction, steps)
    w, _, meta = _train(mesh1, n_rows, nnz, hash_bits, cfg, seed)
    ref = ref_mod.Reference(
        config=_ref_config(n_rows, nnz, hash_bits, block_rows),
        fraction=fraction, data_seed=seed, sample_seed=cfg.seed)
    n_slots = 1 << hash_bits
    w0 = np.zeros((n_slots + 1,), np.float32)
    (good,) = ref.follow(1, steps)
    mine = ref_mod.model_vector(w, n_slots)
    assert np.linalg.norm(good) > 0.01          # it moved
    assert ref_mod.rel_err(mine, good, w0) < W_LIMIT
    # the control: weights, gathered weights, per-slot sums in bfloat16
    (low,) = ref.follow(1, steps, dtype=jnp.bfloat16)
    assert ref_mod.rel_err(low, good, w0) > 100 * W_LIMIT
    assert ssgd.hashed_geometry(cfg, meta).pass_form == ph.pass_form(
        hash_bits, block_rows)


@pytest.mark.parametrize("hash_bits,block_rows,form", [
    (20, 8192, "vmem"), (10, 128, "vmem"), (22, 1024, "vmem"),
    (23, 8192, "xla"), (9, 128, "xla"), (20, 64, "xla"),
    (20, 8192 + 64, "xla")])
def test_pass_form_follows_from_the_geometry(hash_bits, block_rows, form):
    assert ph.pass_form(hash_bits, block_rows) == form
    geom = ph.HashedGeometry(39, hash_bits, block_rows)
    assert geom.pass_form == form
    assert (geom.fields_held, geom.row_bytes) == (40, 160)
    assert geom.w_len == (1 << hash_bits) + 128


# ---- the two passes on crafted rows -----------------------------------

def _crafted(kind, geom, nb):
    """A table whose slots are chosen and not drawn."""
    B, F, nnz, D = geom.block_rows, geom.fields_held, geom.nnz, geom.n_slots
    rng = np.random.default_rng(0)
    X = rng.integers(0, D, (nb, F, B)).astype(np.int32)
    if kind == "twice":                 # two fields of a row, one slot
        X[:, 1, :] = X[:, 0, :]
        X[:, nnz - 1, :] = X[:, 0, :]
    elif kind == "hot":                 # one slot in every row
        X[:, 2, :] = 777 % D
    elif kind == "edges":               # the table's first and last slot
        X[:, 0, :] = 0
        X[:, 1, :] = D - 1
    X[:, nnz, :] = rng.integers(0, 2, (nb, B))
    X[:, nnz + 1:, :] = 0
    return X


@pytest.mark.parametrize("form", ["xla", "vmem"])
@pytest.mark.parametrize("kind", ["twice", "hot", "edges", "random"])
def test_passes_count_every_occurrence_once(kind, form):
    geom = ph.HashedGeometry(nnz=6, hash_bits=10, block_rows=128)
    nb = 5
    X = _crafted(kind, geom, nb)
    ids = np.array([3, 0, 4], np.int32)
    rng = np.random.default_rng(1)
    w = np.zeros((geom.w_len,), np.float32)
    w[:geom.n_slots + 1] = rng.normal(size=geom.n_slots + 1)
    r = rng.normal(size=(len(ids), geom.block_rows)).astype(np.float32)
    # the definition, a pair at a time, in float64
    idx = X[ids][:, :geom.nnz, :]                       # (ns, nnz, B)
    m_def = w[geom.n_slots] + w[idx].astype(np.float64).sum(axis=1)
    g_def = np.zeros((geom.w_len,), np.float64)
    np.add.at(g_def, idx, np.broadcast_to(
        r[:, None, :], idx.shape).astype(np.float64))
    g_def[geom.n_slots] = r.astype(np.float64).sum()
    if form == "xla":
        m = ph.margins_xla(jnp.asarray(X), jnp.asarray(w),
                           jnp.asarray(ids), geom)
        g = ph.slot_sums_xla(jnp.asarray(X), jnp.asarray(r),
                             jnp.asarray(ids), geom)
    else:
        m = ph.margins_vmem(jnp.asarray(X), jnp.asarray(w),
                            jnp.asarray(ids), geom, interpret=True)
        g = ph.slot_sums_vmem(jnp.asarray(X), jnp.asarray(r),
                              jnp.asarray(ids), geom, interpret=True)
    np.testing.assert_allclose(np.asarray(m), m_def, atol=2e-5)
    np.testing.assert_allclose(np.asarray(g), g_def, atol=2e-4)
    assert g.shape == (geom.w_len,)
    assert not np.any(np.asarray(g)[geom.n_slots + 1:])
    if kind == "twice":
        # a row's weight at the shared slot counts three times
        row0 = X[ids[0], :geom.nnz, 0]
        assert list(row0).count(row0[0]) >= 3
    if kind == "hot":
        # the slot every row hits holds every residual (and what the
        # random fields put there)
        others = (idx == 777 % geom.n_slots).sum() - r.size
        assert others >= 0
        assert abs(g_def[777 % geom.n_slots] - r.sum()) < 1e-3 + 5 * others


@pytest.mark.parametrize("n_acc,rows", [(1, 1), (2, 2), (4, 2), (4, 4)])
def test_shipped_kernels_interpreted_against_their_xla_form(
        n_acc, rows, mesh1):
    """Any split of the accesses over accumulators and any rows a trip
    give the XLA form's numbers up to the order of float32 additions."""
    geom = ph.HashedGeometry(nnz=9, hash_bits=11, block_rows=256)
    cfg = _cfg(256, 0.5, 1)
    X, meta = ssgd.build_hashed_table(
        2000, 9, 11, mesh1, cfg, data_seed=5)
    ids = jnp.array([6, 1, 2], jnp.int32)
    key = jax.random.key(3)
    w = jax.random.normal(key, (geom.w_len,)).at[geom.n_slots + 1:].set(0)
    r = jax.random.normal(jax.random.fold_in(key, 1), (3, 256))
    m = ph.margins_vmem(X, w, ids, geom, interpret=True, rows=rows)
    g = ph.slot_sums_vmem(X, r, ids, geom, interpret=True, n_acc=n_acc,
                          rows=rows)
    np.testing.assert_allclose(
        np.asarray(m), np.asarray(ph.margins_xla(X, w, ids, geom)),
        atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(ph.slot_sums_xla(X, r, ids, geom)),
        atol=2e-4)
    # what the trainer calls takes the geometry's form
    assert geom.pass_form == "vmem"
    np.testing.assert_array_equal(
        np.asarray(ph.margins(X, w, ids, geom, interpret=True)),
        np.asarray(ph.margins_vmem(X, w, ids, geom, interpret=True)))


@pytest.mark.parametrize("block_rows", [128, 256, 8192])
@pytest.mark.parametrize("n_fields,rows", [
    (1, 32), (2, 16), (3, 8), (4, 8), (8, 4), (16, 2), (18, 2), (39, 2)])
def test_rows_a_trip_follow_the_fields_a_call_serves(n_fields, rows,
                                                     block_rows):
    """A trip of a by-address loop is a number of (row, field) pairs:
    the most rows, a power of two times ``LOOP_ROWS``, that keep it at
    ``TRIP_PAIRS`` or fewer; the benchmark's hashed calls (18 of 39
    fields, or all 39) run the 2 rows they always did."""
    geom = ph.HashedGeometry(nnz=39, hash_bits=20, block_rows=block_rows)
    assert geom.chunk_rows == min(block_rows, ph.CHUNK_ROWS)
    assert (ph.LOOP_ROWS, ph.TRIP_PAIRS) == (2, 32)
    assert ph._loop_rows(geom, n_fields) == rows
    assert rows * n_fields <= max(ph.TRIP_PAIRS, ph.LOOP_ROWS * n_fields)
    # rows handed in are taken where they divide the chunk
    assert ph._loop_rows(geom, n_fields, 4) == 4
    assert ph._loop_rows(geom, n_fields, 3) == 1


def test_a_table_of_another_shape_is_refused():
    geom = ph.HashedGeometry(nnz=6, hash_bits=10, block_rows=128)
    ids = jnp.zeros((1,), jnp.int32)
    w = jnp.zeros((geom.w_len,))
    for X in (jnp.zeros((2, 8, 64), jnp.int32),
              jnp.zeros((2, 8, 128), jnp.float32)):
        with pytest.raises(ValueError, match="hashed table"):
            ph.margins(X, w, ids, geom)


# ---- the invalid tail, shards, resume ----------------------------------

@pytest.mark.parametrize("block_rows", [128, 64], ids=["vmem", "xla"])
def test_the_invalid_tail_adds_nothing(mesh1, block_rows):
    """All blocks sampled, one step from zero weights: every residual is
    0.5 - y, so the bias moves by eta times the mean over exactly the
    valid rows, and a slot by its valid occurrences."""
    n_rows, nnz, bits = 1000, 5, 10
    cfg = _cfg(block_rows, 1.0, 1)
    w, X, meta = _train(mesh1, n_rows, nnz, bits, cfg, seed=2)
    assert meta["n_padded"] > n_rows and meta["n_padded"] % block_rows == 0
    Xn = np.asarray(X)
    flat = Xn.transpose(0, 2, 1).reshape(-1, Xn.shape[1])   # rows in order
    y = flat[:n_rows, nnz].astype(np.float64)
    slots = flat[:n_rows, :nnz]
    resid = 0.5 - y
    g = np.zeros((1 << bits,), np.float64)
    np.add.at(g, slots, resid[:, None] * np.ones((1, nnz)))
    np.testing.assert_allclose(w[1 << bits], -0.1 * resid.mean(),
                               rtol=1e-5)
    np.testing.assert_allclose(w[:1 << bits], -0.1 * g / n_rows,
                               atol=1e-7)
    # the padding rows have slots and labels like any row: had they
    # counted, the count would be n_padded
    assert abs(w[1 << bits] + 0.1 * resid.sum() / meta["n_padded"]) > 1e-4


def test_one_shard_against_four(mesh1, mesh4):
    """All blocks sampled on both meshes: the same batch, the full
    gradient ``tree_allreduce_sum``ed over four shards."""
    cfg = _cfg(128, 1.0, 3)
    one, _, _ = _train(mesh1, 2000, 7, 10, cfg, seed=9)
    four, _, meta = _train(mesh4, 2000, 7, 10, cfg, seed=9)
    assert meta["n_padded"] == 2048
    n_slots = 1 << 10
    assert np.linalg.norm(one) > 0.01
    assert ref_mod.rel_err(four[:n_slots + 1], one[:n_slots + 1],
                           np.zeros(n_slots + 1)) < W_LIMIT


def test_resume_through_run_segmented_is_bitwise(mesh1, tmp_path):
    cfg = _cfg(128, 0.25, 6)
    straight = ssgd.train_hashed(3000, 5, 10, mesh1, cfg, data_seed=4)
    d = str(tmp_path / "ck")
    ssgd.train_hashed(3000, 5, 10, mesh1,
                      dataclasses.replace(cfg, n_iterations=4),
                      data_seed=4, checkpoint_dir=d, checkpoint_every=2)
    resumed = ssgd.train_hashed(3000, 5, 10, mesh1, cfg, data_seed=4,
                                checkpoint_dir=d, checkpoint_every=2)
    np.testing.assert_array_equal(np.asarray(resumed.w),
                                  np.asarray(straight.w))
    assert resumed.heldout_log_loss == straight.heldout_log_loss
    assert 0 < straight.heldout_log_loss < np.log(2)     # it learned
    assert 0.5 < straight.heldout_acc <= 1.0
    assert straight.final_acc == straight.heldout_acc


def test_segments_after_the_first_run_the_first_ones_program(
        mesh1, tmp_path, monkeypatch):
    """The state a segment hands on is placed as the first one's was, so
    three segments of one length trace and compile once (on the chip a
    second trace of the two kernels' bodies costs seconds)."""
    built = []
    real = ssgd.make_train_fn_fused

    def recording(mesh, config, meta):
        built.append(real(mesh, config, meta))
        return built[-1]

    monkeypatch.setattr(ssgd, "make_train_fn_fused", recording)
    ssgd.train_hashed(3000, 5, 10, mesh1, _cfg(128, 0.25, 6), data_seed=4,
                      checkpoint_dir=str(tmp_path / "ck"),
                      checkpoint_every=2)
    # prepare's own (never called) and the segments' one
    assert [fn._cache_size() for fn in built] == [0, 1]


# ---- the loader --------------------------------------------------------

def test_loader_same_seed_same_table_and_one_compile(mesh1):
    cfg = _cfg(128, 0.25, 1)
    ssgd.hashed_table_fn.cache_clear()
    a, meta = ssgd.build_hashed_table(1500, 6, 10, mesh1, cfg, data_seed=3)
    b, _ = ssgd.build_hashed_table(1500, 6, 10, mesh1, cfg, data_seed=3)
    c, _ = ssgd.build_hashed_table(1500, 6, 10, mesh1, cfg, data_seed=4)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.any(np.asarray(a) != np.asarray(c))
    info = ssgd.hashed_table_fn.cache_info()
    assert (info.misses, info.hits) == (1, 2)
    fn = ssgd.hashed_table_fn(
        mesh1, 1500, meta["n_padded"], ssgd.hashed_geometry(cfg, meta),
        meta["cardinalities"], meta["rows_kw"])
    assert fn._cache_size() == 1          # the seed is an argument
    assert meta["row_format"] == "hashed" and meta["pack"] == 1
    assert (meta["nnz"], meta["hash_bits"], meta["n_rows"]) == (6, 10, 1500)
    assert a.shape == (12, 8, 128) and a.dtype == jnp.int32
    An = np.asarray(a)
    assert An[:, :6].min() >= 0 and An[:, :6].max() < 1 << 10
    assert set(np.unique(An[:, 6])) <= {0, 1} and not An[:, 7:].any()


def test_generated_rows_are_skewed_and_click_at_the_rate():
    make_rows = datasets.hashed_click_rows(
        datasets.click_field_cardinalities(39), 20)
    slots, y = jax.jit(make_rows)(jnp.arange(60000), jnp.int32(8))
    slots, y = np.asarray(slots), np.asarray(y)
    assert slots.shape == (60000, 39) and 0.22 < y.mean() < 0.29
    _, counts = np.unique(slots, return_counts=True)
    top = np.sort(counts)[::-1]
    assert top[0] > 0.4 * len(slots)         # a slot in near half the rows
    assert top[:300].sum() > 0.25 * slots.size   # a quarter in 300 slots
    assert len(counts) > 100000              # and a tail of millions
    again, _ = jax.jit(make_rows)(jnp.arange(100), jnp.int32(8))
    np.testing.assert_array_equal(np.asarray(again), slots[:100])
    assert len(datasets.click_field_cardinalities(39)) == 39
    assert datasets.click_field_cardinalities(41)[39:] == \
        datasets.click_field_cardinalities(2)


# ---- what refuses the format, what the program names -------------------

@pytest.mark.parametrize("change,word", [
    (dict(sampler="bernoulli"), "bernoulli"),
    (dict(sampler="fused_train"), "fused_train"),
    (dict(feature_sharded=True), "feature_sharded"),
    (dict(comm="int8"), "int8"),
    (dict(sync="ssp:4"), "ssp:4"),
])
def test_what_cannot_take_hashed_rows_refuses_by_name(mesh1, change, word):
    cfg = dataclasses.replace(_cfg(128, 0.25, 1), **change)
    meta = dict(row_format="hashed", nnz=5, hash_bits=10, pack=1,
                n_rows=1000, n_padded=1024, d_total=1024 + 128)
    with pytest.raises(ValueError, match="hashed rows") as err:
        ssgd.make_train_fn_fused(mesh1, cfg, meta)
    assert word in str(err.value)


@pytest.mark.parametrize("shards", [1, 4])
def test_lowered_hashed_trainer_names_its_scopes(shards, mesh1, mesh4):
    mesh = mesh1 if shards == 1 else mesh4
    cfg = _cfg(128, 0.25, 2)
    fn, X, w0, _ = ssgd.prepare_hashed_synthetic(
        3000, 5, 10, mesh, cfg, data_seed=1)
    d = jnp.zeros((1,), jnp.float32)
    text = fn.lower(X, d, d, d, d, w0).as_text(debug_info=True)
    for scope in (names.SSGD_DRAW, names.SSGD_GATHER, names.SSGD_SCATTER,
                  names.SSGD_UPDATE, names.SSGD_SYNC):
        assert scope + "/" in text, scope
    assert names.SSGD_KERNEL not in text       # that is the dense rows'
    # 490 values of field 3 fold into 385 slots: read by value
    for kernel in ("_hashed_gather_kernel", "_hashed_scatter_kernel",
                   "_hashed_rows_kernel", "_hashed_value_gather_kernel",
                   "_hashed_value_sums_kernel"):
        assert kernel in text, kernel


def test_spans_and_report_say_the_row_format(mesh1, tmp_path):
    tel = str(tmp_path / "tel")
    events.configure(tel)
    try:
        ssgd.train_hashed(2000, 5, 10, mesh1, _cfg(128, 0.25, 4),
                          checkpoint_dir=str(tmp_path / "ck"),
                          checkpoint_every=2)
    finally:
        events.configure(False)
    evts = report.load_events(tel)
    ends = {e["name"]: e for e in evts if e["ev"] == "span_end"}
    prep = ends["ssgd:prepare"]
    assert (prep["row_format"], prep["nnz"], prep["hash_bits"],
            prep["rows"], prep["bytes"]) == ("hashed", 5, 10, 2000,
                                             2048 * 32)
    assert (prep["dict_fields"], prep["addr_fields"],
            prep["dict_values"]) == (1, 4, 385)
    assert "ssgd:generate" in ends and "ssgd:heldout" in ends
    seg = ends["train:segment"]
    assert (seg["row_format"], seg["gather_form"], seg["scatter_form"],
            seg["draw_form"]) == ("hashed", "vmem", "vmem", "few")
    assert (seg["dict_fields"], seg["addr_fields"],
            seg["dict_values"]) == (1, 4, 385)
    lines = report.render(report.summarize(evts)).splitlines()
    for line in ("row format: hashed", "block draw: few",
                 "gather pass: vmem", "scatter pass: vmem",
                 "fields by value: 1 (385 values), by address: 4"):
        assert line in lines, line


# ---- fields read by value (PR 33) ---------------------------------------

def _by_value_table(kind, geom, nb):
    """A table and the dictionaries a loader would state for it: fields
    1 and 3 (and 4 in 'twice') take few slots, the rest any."""
    B, F, nnz, D = geom.block_rows, geom.fields_held, geom.nnz, geom.n_slots
    rng = np.random.default_rng(3)
    X = rng.integers(0, D, (nb, F, B)).astype(np.int32)
    dicts = [None] * nnz
    for f, c in ((1, 5), (3, 40)):
        dicts[f] = rng.choice(D, c, replace=False).astype(np.int32)
    if kind == "folded":
        # two values of field 3 fold to one slot: the loader's list has
        # the slot twice, the plan once, and rows of both values hold it
        dicts[3] = np.concatenate([dicts[3], dicts[3][:7]])
    elif kind == "meets_addr":
        # field 0 is read by address and holds, on every other row, a
        # slot of field 1's dictionary
        X[:, 0, ::2] = dicts[1][2]
    elif kind == "twice":
        # fields 3 and 4 state one dictionary and agree on every row
        dicts[4] = dicts[3].copy()
    for f, d in enumerate(dicts):
        if d is not None:
            X[:, f, :] = d[rng.integers(0, len(d), (nb, B))]
    if kind == "twice":
        X[:, 4, :] = X[:, 3, :]
    X[:, nnz, :] = rng.integers(0, 2, (nb, B))
    X[:, nnz + 1:, :] = 0
    return X, dicts


@pytest.mark.parametrize("block_rows", [128, 1024])
@pytest.mark.parametrize("kind", ["plain", "folded", "meets_addr", "twice",
                                  "tiles"])
def test_fields_by_value_and_by_address_against_xla(kind, block_rows,
                                                    monkeypatch):
    geom = ph.HashedGeometry(nnz=6, hash_bits=10, block_rows=block_rows)
    nb = 5
    X, dicts = _by_value_table(kind, geom, nb)
    if kind == "tiles":
        # three sampled blocks in tiles of two: the last tile's second
        # block is padding
        monkeypatch.setattr(ph, "VALUE_TILE_ROWS", 2 * block_rows)
    plan = ph.field_plan(geom, dicts)
    want = (1, 3, 4) if kind == "twice" else (1, 3)
    assert plan.dict_fields == want
    assert plan.addr_fields == tuple(f for f in range(6) if f not in want)
    assert plan.n_values == sum(len(np.unique(dicts[f])) for f in want)
    ids = jnp.array([3, 0, 4], jnp.int32)
    rng = np.random.default_rng(1)
    w = np.zeros((geom.w_len,), np.float32)
    w[:geom.n_slots + 1] = rng.normal(size=geom.n_slots + 1)
    r = rng.normal(size=(3, block_rows)).astype(np.float32)
    Xj, wj, rj = jnp.asarray(X), jnp.asarray(w), jnp.asarray(r)
    m = ph.margins(Xj, wj, ids, geom, plan=plan, interpret=True)
    g = ph.slot_sums(Xj, rj, ids, geom, plan=plan, interpret=True)
    np.testing.assert_allclose(
        np.asarray(m), np.asarray(ph.margins_xla(Xj, wj, ids, geom)),
        atol=1e-5)
    g_xla = np.asarray(ph.slot_sums_xla(Xj, rj, ids, geom))
    np.testing.assert_allclose(np.asarray(g), g_xla, atol=2e-4)
    assert g.shape == (geom.w_len,)
    assert not np.any(np.asarray(g)[geom.n_slots + 1:])
    if kind == "twice":
        # a residual of one counts a shared slot twice a row
        ones = jnp.ones_like(rj)
        n1 = np.asarray(ph.slot_sums(Xj, ones, ids, geom, plan=plan,
                                     interpret=True))
        idx = X[np.asarray(ids)][:, :6, :]
        counts = np.zeros((geom.n_slots,), np.int64)
        np.add.at(counts, idx, 1)
        np.testing.assert_array_equal(n1[:geom.n_slots], counts)
        assert counts[dicts[3]].sum() >= 2 * 3 * block_rows


def test_every_field_by_value_needs_no_address_pass():
    geom = ph.HashedGeometry(nnz=2, hash_bits=10, block_rows=128)
    rng = np.random.default_rng(5)
    dicts = [rng.choice(1024, 9, replace=False).astype(np.int32),
             rng.choice(1024, 3, replace=False).astype(np.int32)]
    X = np.zeros((3, 8, 128), np.int32)
    for f, d in enumerate(dicts):
        X[:, f, :] = d[rng.integers(0, len(d), (3, 128))]
    X[:, 2, :] = rng.integers(0, 2, (3, 128))
    plan = ph.field_plan(geom, dicts)
    assert plan.addr_fields == () and plan.dict_fields == (0, 1)
    ids = jnp.array([2, 0], jnp.int32)
    w = jnp.asarray(rng.normal(size=geom.w_len).astype(np.float32)
                    ).at[geom.n_slots + 1:].set(0)
    r = jnp.asarray(rng.normal(size=(2, 128)).astype(np.float32))
    Xj = jnp.asarray(X)
    np.testing.assert_allclose(
        np.asarray(ph.margins(Xj, w, ids, geom, plan=plan, interpret=True)),
        np.asarray(ph.margins_xla(Xj, w, ids, geom)), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ph.slot_sums(Xj, r, ids, geom, plan=plan,
                                interpret=True)),
        np.asarray(ph.slot_sums_xla(Xj, r, ids, geom)), atol=2e-4)


@pytest.mark.parametrize("n_values,block_rows,form", [
    (1, 8192, "dict"), (ph.DICT_MAX_VALUES, 8192, "dict"),
    (ph.DICT_MAX_VALUES + 1, 8192, "addr"), (0, 8192, "addr"),
    (ph.DICT_MAX_VALUES, 1024, "dict"),
    # a block of 128 rows fills an eighth of a vector
    (ph.DICT_MAX_VALUES // 8, 128, "dict"),
    (ph.DICT_MAX_VALUES // 8 + 1, 128, "addr"),
    (ph.DICT_MAX_VALUES * 3 // 4, 1536, "dict"),
    (ph.DICT_MAX_VALUES * 3 // 4 + 1, 1536, "addr"),
    (5, 64, "addr"),                        # not whole lanes
])
def test_field_form_follows_from_the_dictionary_and_the_block(
        n_values, block_rows, form):
    assert ph.field_form(n_values, block_rows) == form


@pytest.mark.parametrize("hash_bits,block_rows", [
    (23, 8192), (9, 128), (20, 64)])
def test_no_field_is_read_by_value_where_the_passes_are_xla(hash_bits,
                                                            block_rows):
    geom = ph.HashedGeometry(3, hash_bits, block_rows)
    assert geom.pass_form == "xla"
    dicts = [np.arange(4, dtype=np.int32)] * 3
    assert ph.field_plan(geom, dicts) is None


def test_field_plan_lays_the_dictionaries_out_in_whole_groups():
    geom = ph.HashedGeometry(nnz=4, hash_bits=12, block_rows=1024)
    long = np.arange(ph.DICT_MAX_VALUES + 1, dtype=np.int32)
    dicts = [np.array([9, 3, 3, 7], np.int32), long, None,
             np.arange(100, 100 + ph.VALUE_GROUP + 1, dtype=np.int32)]
    plan = ph.field_plan(geom, dicts)
    assert (plan.dict_fields, plan.addr_fields) == ((0, 3), (1, 2))
    G = ph.VALUE_GROUP
    assert list(plan.group_field) == [0, 1, 1]
    assert list(plan.entries[:G]) == [3, 7, 9] + [ph.NO_SLOT] * (G - 3)
    assert list(plan.entries[G:2 * G]) == list(range(100, 100 + G))
    assert plan.entries[2 * G] == 100 + G
    assert (plan.entries[2 * G + 1:] == ph.NO_SLOT).all()
    assert plan.n_values == 3 + G + 1
    # nothing stated, nothing short enough: every field by address
    assert ph.field_plan(geom, None) is None
    assert ph.field_plan(geom, [None, long, None, None]) is None
    with pytest.raises(ValueError, match="leaves the table"):
        ph.field_plan(geom, [np.array([1 << 12], np.int32)] + [None] * 3)
    with pytest.raises(ValueError, match="dictionaries for"):
        ph.field_plan(geom, [None] * 3)


def test_a_meta_without_dictionaries_runs_the_passes_as_they_were(mesh1):
    """The loader's ``meta`` less its dictionaries has no plan, and the
    passes are the by-address kernels over every field, bit for bit;
    with the dictionaries the weights agree to float32 rounding."""
    cfg = _cfg(128, 0.5, 3)
    fn, X, w0, meta = ssgd.prepare_hashed_synthetic(
        3000, 5, 10, mesh1, cfg, data_seed=6)
    plan = ssgd.hashed_field_plan(cfg, meta)
    assert plan is not None and plan.dict_fields
    bare = {k: v for k, v in meta.items() if k != "dictionaries"}
    assert ssgd.hashed_field_plan(cfg, bare) is None
    geom = ssgd.hashed_geometry(cfg, bare)
    ids = jnp.array([5, 2, 9], jnp.int32)
    key = jax.random.key(4)
    w = jax.random.normal(key, (geom.w_len,)).at[geom.n_slots + 1:].set(0)
    r = jax.random.normal(jax.random.fold_in(key, 1), (3, 128))
    every = tuple(range(geom.nnz))
    np.testing.assert_array_equal(
        np.asarray(ph.margins(X, w, ids, geom, interpret=True)),
        np.asarray(ph.margins_vmem(X, w, ids, geom, interpret=True,
                                   fields=every)))
    np.testing.assert_array_equal(
        np.asarray(ph.slot_sums(X, r, ids, geom, interpret=True)),
        np.asarray(ph.slot_sums_vmem(X, r, ids, geom, interpret=True,
                                     fields=every)))
    text = ssgd.make_train_fn_fused(mesh1, cfg, bare).lower(
        X, *[jnp.zeros((1,), jnp.float32)] * 4, w0).as_text(
        debug_info=True)
    assert "_hashed_gather_kernel" in text
    for kernel in ("_hashed_value_gather_kernel",
                   "_hashed_value_sums_kernel", "_hashed_rows_kernel"):
        assert kernel not in text, kernel
    d = jnp.zeros((1,), jnp.float32)
    w_bare, _ = ssgd.make_train_fn_fused(mesh1, cfg, bare)(
        X, d, d, d, d, w0)
    w_dict, _ = fn(X, d, d, d, d, w0)
    n = (1 << 10) + 1
    assert np.linalg.norm(np.asarray(w_bare)) > 0.01
    assert ref_mod.rel_err(np.asarray(w_dict)[:n], np.asarray(w_bare)[:n],
                           np.zeros(n)) < W_LIMIT


def test_padding_rows_of_a_field_read_by_value_add_nothing(mesh1):
    """The invalid tail again, with a field known to be read by value:
    all blocks sampled, one step from zero weights."""
    n_rows, nnz, bits = 1000, 5, 10
    cfg = _cfg(128, 1.0, 1)
    w, X, meta = _train(mesh1, n_rows, nnz, bits, cfg, seed=2)
    plan = ssgd.hashed_field_plan(cfg, meta)
    assert plan.dict_fields and plan.addr_fields
    Xn = np.asarray(X)
    flat = Xn.transpose(0, 2, 1).reshape(-1, Xn.shape[1])
    resid = 0.5 - flat[:n_rows, nnz].astype(np.float64)
    f = plan.dict_fields[0]
    own = np.zeros((1 << bits,), np.float64)
    np.add.at(own, flat[:n_rows, f], resid)
    every = np.zeros((1 << bits,), np.float64)
    np.add.at(every, flat[:n_rows, :nnz], resid[:, None] * np.ones((1, nnz)))
    np.testing.assert_allclose(w[:1 << bits], -0.1 * every / n_rows,
                               atol=1e-7)
    # the padding rows hold slots of the field's dictionary like any row
    pad_slots = flat[n_rows:, f]
    assert len(pad_slots) and np.isin(pad_slots,
                                      meta["dictionaries"][f]).all()
    assert np.abs(own).max() > 1


@pytest.mark.parametrize("hash_bits", [10, 20, 22])
def test_every_drawn_slot_is_in_its_fields_dictionary(hash_bits):
    cards = datasets.click_field_cardinalities(39)
    dicts = datasets.click_field_dictionaries(cards, hash_bits)
    make_rows = datasets.hashed_click_rows(cards, hash_bits)
    slots, _ = jax.jit(make_rows)(jnp.arange(30000) + 45_000_000,
                                  jnp.int32(3))
    slots = np.asarray(slots)
    stated = 0
    for f, (c, d) in enumerate(zip(cards, dicts)):
        if c > datasets.DICTIONARY_MAX_VALUES:
            assert d is None
            continue
        stated += 1
        assert d.dtype == np.int32 and len(d) <= min(c, 1 << hash_bits)
        assert (np.diff(d) > 0).all() and d[0] >= 0 \
            and d[-1] < 1 << hash_bits
        assert np.isin(slots[:, f], d).all(), f
        # the dictionary is the slots of the field's values, one by one
        v = np.arange(min(c, 50), dtype=np.uint32)
        one = datasets.click_slots(np.full((1,), f, np.uint32), v,
                                   hash_bits)
        assert np.isin(one.astype(np.int32), d).all()
    assert stated == 30
    if hash_bits == 20:
        # the cell: 21 fields by value, 18 by address
        geom = ph.HashedGeometry(39, 20, 8192)
        plan = ph.field_plan(geom, dicts)
        assert (len(plan.dict_fields), len(plan.addr_fields),
                plan.n_values) == (21, 18, 13027)
        assert max(len(dicts[f]) for f in plan.dict_fields) == 3193
        assert min(len(dicts[f]) for f in plan.addr_fields
                   if dicts[f] is not None) == 4130
