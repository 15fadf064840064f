"""Rows of (feature, value) pairs, ``models/ssgd.py``'s third row
format (``ops/pairs.py``, ``models/ssgd_pairs.py``): the two passes
against a float64 NumPy step over CSR arrays, the packing rule, the
trainer against the benchmark's plain reference
(``benchmarks/reference/ssgd_pairs_ref.py``, which shares no code with
the program) on one device and on four, a one-hot table written as
pairs of value 1 against the ``indexed`` format, the control that has
to fail, the refusals, the generator's invariants, the loader's spans
and counters, the CLI. Each guarantee of the configuration's file
(exact ids, float32 values, every pair once, no maximum length under
the block's capacity, the divisor, BSP) is pinned by a test here."""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg.models import ssgd, ssgd_pairs
from tpu_distalg.ops import pairs
from tpu_distalg.telemetry import events, names, report
from tpu_distalg.utils import datasets

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import ssgd_pairs_ref as ref_mod  # noqa: E402

D = 1000                  # features of the hand-made tables
GEOM = pairs.PairsGeometry(n_features=D, block_slots=256, block_rows=4,
                           n_blocks=8)
# the rows every hand-made table starts with: an empty row, a row of one
# pair, a row that fills a block (and so a block of a single row), a
# feature twice in one row (and once more in the next)
SHAPES = {
    "empty_row": [[], [(3, 0.5)], [(4, 1.0), (5, -2.0)]],
    "one_pair": [[(7, 0.25)]],
    "fills_a_block": [[(i % D, 1.0 / (1 + i)) for i in range(256)],
                      [(1, 1.0)]],
    "feature_twice": [[(9, 0.5), (11, 0.25), (9, 0.125)], [(9, 2.0)]],
    "single_row_block": [[(i, 1.0) for i in range(130)],
                         [(i, 0.5) for i in range(129)],
                         [(2, 1.0)]],
    "ragged": [[(int(i * 37 % D), float(i % 7 - 3)) for i in range(n)]
               for n in (0, 1, 127, 128, 129, 5, 0, 200, 64, 3)],
}


def _csr(rows, labels=None):
    indptr = np.concatenate([[0], np.cumsum([len(r) for r in rows])])
    ids = np.asarray([p[0] for r in rows for p in r], np.int32)
    vals = np.asarray([p[1] for r in rows for p in r], np.float32)
    y = np.asarray(labels if labels is not None
                   else [i % 2 for i in range(len(rows))], np.int32)
    return indptr, ids, vals, y


def _step64(indptr, ids, vals, y, rows, w, eta=0.1):
    """One step of the source's update over ``rows`` in float64: the
    independent yardstick (CSR arrays, no block, no vector)."""
    w = np.asarray(w, np.float64)
    g = np.zeros_like(w)
    n_w = g.shape[0]
    m_all = {}
    for i in rows:
        sl = slice(indptr[i], indptr[i + 1])
        m = w[-1] + np.sum(w[ids[sl]] * vals[sl].astype(np.float64))
        r = 1.0 / (1.0 + math.exp(-m)) - float(y[i])
        np.add.at(g, ids[sl], r * vals[sl].astype(np.float64))
        g[n_w - 1] += r
        m_all[i] = m
    return w - eta * g / max(len(rows), 1), g, m_all


def _w(seed=0):
    w = np.random.default_rng(seed).normal(size=GEOM.w_len).astype(
        np.float32)
    w[D + 1:] = 0
    return w


# ---- the two passes against float64 ----------------------------------------

@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_two_passes_against_float64(shape):
    indptr, ids, vals, y = _csr(SHAPES[shape])
    X = pairs.blocks_from_csr(indptr, ids, vals, y, GEOM)
    starts = pairs.pack_rows(np.diff(indptr), GEOM.block_slots,
                             GEOM.block_rows)
    w = _w()
    sel = jnp.arange(GEOM.n_blocks)
    m = np.asarray(pairs.margins(jnp.asarray(X), jnp.asarray(w), sel, GEOM))
    yy, valid = pairs.labels(jnp.asarray(X), sel, GEOM)
    assert int(np.asarray(valid).sum()) == len(y)
    r = (jax.nn.sigmoid(jnp.asarray(m)) - yy) * valid
    g = np.asarray(pairs.slot_sums(jnp.asarray(X), r, sel, GEOM))
    w64 = np.concatenate([w[:D + 1].astype(np.float64)])
    _, g64, m64 = _step64(indptr, ids, vals, y, range(len(y)), w64)
    for b in range(len(starts) - 1):
        for k, i in enumerate(range(starts[b], starts[b + 1])):
            assert abs(m[b, k] - m64[i]) < 1e-5 * (1 + abs(m64[i])), (b, k)
    np.testing.assert_allclose(g[:D + 1], g64, rtol=1e-5, atol=1e-6)
    assert not g[D + 1:].any()
    # every pair once: the table gives its CSR arrays back
    back = pairs.csr_from_blocks(X, GEOM)
    for got, want in zip(back, (indptr, ids, vals, y)):
        assert np.array_equal(got, want)
    counts = np.asarray(pairs.pair_counts(jnp.asarray(X), sel, GEOM))
    assert counts.sum() == indptr[-1]


def test_a_feature_twice_in_a_row_counts_twice():
    indptr, ids, vals, y = _csr([[(9, 0.5), (9, 0.25)]], labels=[1])
    X = jnp.asarray(pairs.blocks_from_csr(indptr, ids, vals, y, GEOM))
    w = np.zeros(GEOM.w_len, np.float32)
    w[9] = 2.0
    m = pairs.margins(X, jnp.asarray(w), jnp.arange(1), GEOM)
    assert float(m[0, 0]) == 1.5
    r = jnp.zeros((1, 4)).at[0, 0].set(1.0)
    g = pairs.slot_sums(X, r, jnp.arange(1), GEOM)
    assert float(g[9]) == 0.75 and float(g[D]) == 1.0


def test_values_and_ids_are_held_exactly():
    """float32 values no bfloat16 holds and ids up to the last feature
    come back bit for bit (no rounding, no hashing, no remap)."""
    vals = [1.0 + 2.0 ** -20, 3.1415927, -1e-30, 65504.125]
    rows = [[(D - 1, vals[0]), (0, vals[1])], [(D - 1, vals[2])],
            [(D // 2, vals[3])]]
    indptr, ids, v, y = _csr(rows)
    X = pairs.blocks_from_csr(indptr, ids, v, y, GEOM)
    _, ids2, v2, _, _ = pairs.csr_from_blocks(X, GEOM)
    assert ids2.tolist() == [D - 1, 0, D - 1, D // 2]
    assert v2.tobytes() == np.asarray(vals, np.float32).tobytes()
    assert X.dtype == np.int32
    with pytest.raises(ValueError, match="outside"):
        pairs.blocks_from_csr(*_csr([[(D, 1.0)]]), GEOM)


# ---- which rows a block holds ------------------------------------------------

@pytest.mark.parametrize("lengths,slots,rows,want", [
    ([100, 100, 100], 256, 4, [0, 2, 3]),          # 128 + 128 fill it
    ([0, 0, 0, 0, 0], 256, 4, [0, 4, 5]),          # the row slots run out
    ([256, 1], 256, 4, [0, 1, 2]),                 # a row fills a block
    ([129, 127, 1], 384, 8, [0, 2, 3]),            # 256 + 128, then 128
    ([], 256, 4, [0]),
], ids=["vectors", "row_slots", "full_row", "rounding", "no_rows"])
def test_the_packing_rule(lengths, slots, rows, want):
    assert pairs.pack_rows(lengths, slots, rows).tolist() == want
    assert ref_mod.pack(lengths, slots, rows, 128).tolist() == want


def test_no_row_is_cut_and_none_is_dropped():
    with pytest.raises(ValueError, match="no row is split or cut"):
        pairs.pack_rows([257], 256, 4)
    # a row as long as the block's capacity is taken whole
    assert pairs.pack_rows([256], 256, 4).tolist() == [0, 1]
    with pytest.raises(ValueError, match="blocks needed"):
        pairs.blocks_from_csr(
            *_csr([[(1, 1.0)] * 200] * 9), GEOM)


@pytest.mark.parametrize("slots,rows", [(100, 4), (64, 4), (256, 0)])
def test_a_geometry_is_whole_vectors(slots, rows):
    with pytest.raises(ValueError):
        pairs.PairsGeometry(D, slots, rows, 2)


def test_the_layout_of_a_block():
    g = pairs.PairsGeometry(16609143, 1 << 18, 512, 5248)
    assert (g.vectors, g.vector_rows, g.label_rows, g.held_rows) == (
        2048, 16, 4, 4120)
    assert g.block_bytes * g.n_blocks == 11070341120
    assert g.w_len == 16609152 and g.w_len % 128 == 0


# ---- the generator -------------------------------------------------------------

SPEC = ssgd_pairs.PairsSpec(
    n_rows=600, n_features=5000, length_mu=4.848185062408447,
    block_slots=2048, block_rows=16, n_blocks=96, length_min=0,
    length_max=1024, scatter_c=77)


def _table(mesh, spec=SPEC, seed=3):
    X, meta = ssgd_pairs.build_table(spec, mesh, data_seed=seed)
    return X, meta, pairs.csr_from_blocks(np.asarray(X),
                                          ssgd_pairs.geometry(meta))


def test_the_loaders_rows(mesh1):
    X, meta, (indptr, ids, vals, y, _) = _table(mesh1)
    lens = np.diff(indptr)
    assert len(y) == 600 and indptr[-1] == meta["n_pairs"] == lens.sum()
    assert abs(meta["n_pairs"] / (600 * 200) - 1) < 1e-3
    assert lens.max() == 1024 == meta["longest_row"] and lens.min() < 8
    assert ids.min() >= 0 and ids.max() < 5000
    assert (vals > 0).all() and X.dtype == jnp.int32
    norms = np.sqrt(np.add.reduceat(
        vals.astype(np.float64) ** 2, indptr[:-1][lens > 0]))
    assert np.abs(norms - 1).max() < 1e-6          # unit-length rows
    assert 0.5 < y.mean() < 0.7                    # positive rate 0.6
    # the same seed, the same table; another seed, another table with
    # the same lengths dealt to other rows
    X2, _, (indptr2, ids2, _, _, _) = _table(mesh1)
    assert np.array_equal(np.asarray(X), np.asarray(X2))
    _, meta3, (indptr3, ids3, _, _, _) = _table(mesh1, seed=4)
    assert meta3["n_pairs"] == meta["n_pairs"]
    assert sorted(np.diff(indptr3)) == sorted(lens)
    assert not np.array_equal(np.diff(indptr3), lens)
    assert not np.array_equal(ids3[:1000], ids[:1000])


def test_the_bijection_is_a_bijection():
    gen = SPEC.generator()
    ids = np.asarray(gen.scatter(jnp.arange(5000)))
    assert sorted(ids.tolist()) == list(range(5000))
    assert ids[0] == 77 and ids[1] == 77 + 251
    # no range of ids is hot: the 50 first ranks lie all over the space
    assert np.ptp(ids[:50]) > 4000
    with pytest.raises(ValueError, match="not a bijection"):
        datasets.ragged_pair_rows(10, 251 * 4, length_mu=1.0)
    with pytest.raises(ValueError, match="2\\*\\*32"):
        datasets.ragged_pair_rows(10, (1 << 31) - 300, length_mu=1.0)


def test_the_cells_lengths_add_up_to_the_sources_pairs():
    """The configuration's 350 000 lengths: within 0.1% of LIBSVM's
    1 304 697 446 non-zeros, clipped to 65 536, and packed by the rule
    into fewer blocks than the file's ``pair_blocks``."""
    import json

    with open(os.path.join(BENCH, "configs",
                           "lr-webspam-tri16m.json")) as f:
        c = json.load(f)
    assert c["reduced"] if "reduced" in c else True
    gen = datasets.ragged_pair_rows(
        c["n_rows"], c["n_features"], length_mu=c["length_mu"],
        length_sigma=c["length_sigma"], length_min=c["length_min"],
        length_max=c["length_max"], scatter_a=c["scatter_a"],
        scatter_c=c["scatter_c"])
    lens = np.asarray(jax.jit(gen.lengths)(jnp.arange(c["n_rows"]),
                                           jnp.int32(11)))
    total = int(lens.sum(dtype=np.int64))
    assert abs(total / c["nnz_total"] - 1) < 1e-3
    assert lens.min() >= c["length_min"] and lens.max() == 65536
    cuts = pairs.pack_rows(lens, c["pair_block_slots"],
                           c["pair_block_rows"])
    assert 5100 < len(cuts) - 1 <= c["pair_blocks"] == 5248
    assert math.gcd(c["scatter_a"], c["n_features"]) == 1
    assert c["n_features"] == 16609143 and c["n_rows"] == 350000


def test_the_reference_restates_the_programs_rows(mesh1):
    """Row for row and block for block: the program's table and the
    reference's regenerated blocks, which share no code."""
    _, meta, (indptr, ids, vals, y, block_of) = _table(mesh1, seed=9)
    ref = ref_mod.Reference(config=_config(), fraction=0.1, data_seed=9,
                            sample_seed=42)
    assert np.array_equal(ref.starts, meta["block_starts"])
    assert np.array_equal(ref.counts, meta["block_counts"])
    assert ref.n_pairs == meta["n_pairs"]
    assert float(ref.bias) == meta["bias"]
    for b in (0, 17, meta["blocks_used"] - 1):
        idx, val, row, live, yb, valid, _, _ = jax.jit(
            lambda s, c: ref.rows.block(
                ref.seed, ref.bias, ref.lengths, 0, s, c, ref.slots,
                ref.R))(jnp.int32(ref.starts[b]), jnp.int32(ref.counts[b]))
        mine = np.flatnonzero(block_of == b)
        lo, hi = indptr[mine[0]], indptr[mine[-1] + 1]
        n = hi - lo
        assert int(np.asarray(live).sum()) == n
        assert np.array_equal(np.asarray(idx)[:n], ids[lo:hi])
        assert np.array_equal(np.asarray(val)[:n], vals[lo:hi])
        assert np.array_equal(np.asarray(yb)[:len(mine)], y[mine])


# ---- the trainer ------------------------------------------------------------------

def _config(**over):
    c = dict(
        n_rows=SPEC.n_rows, n_features=SPEC.n_features,
        pair_block_slots=SPEC.block_slots,
        pair_block_rows=SPEC.block_rows, pair_row_granule=128,
        pair_blocks=SPEC.n_blocks, length_mu=SPEC.length_mu,
        length_sigma=1.0, length_min=0, length_max=1024,
        zipf_exponent=1.1, scatter_a=251, scatter_c=77,
        planted_scale=0.25, positive_rate=0.6, eta=0.1,
        bias_blocks=64, heldout_blocks=64, heldout_offset=1 << 20)
    c.update(over)
    return c


def _cfg(frac, steps, **over):
    return ssgd.SSGDConfig(
        n_iterations=steps, eta=0.1, lam=0.0, mini_batch_fraction=frac,
        seed=42, eval_test=False, sampler="fused_gather", **over)


def _train(mesh, cfg, seed=5, t0=0):
    fn, X, w0, meta = ssgd_pairs.prepare_synthetic(SPEC, mesh, cfg,
                                                   data_seed=seed)
    d = jnp.zeros((1,), jnp.float32)
    w, _ = fn(X, d, d, d, d, w0, t0=t0)
    return np.asarray(w), X, meta


@pytest.mark.parametrize("shards", [1, 4])
def test_eight_steps_follow_the_reference(mesh1, mesh4, shards):
    mesh = mesh1 if shards == 1 else mesh4
    w, _, meta = _train(mesh, _cfg(0.1, 8), t0=640)
    ref = ref_mod.Reference(config=_config(), fraction=0.1, data_seed=5,
                            sample_seed=42, n_shards=shards)
    w_ref = ref.follow(1, 8, t0=640)[0]
    w0 = np.zeros_like(w_ref)
    err = ref_mod.rel_err(ref_mod.model_vector(w, 5000), w_ref, w0)
    assert err < 2e-5, err
    assert np.count_nonzero(w_ref) > 3000


def test_one_shard_and_four_agree(mesh1, mesh4):
    """The same blocks drawn a shard at a time give the same weights
    up to the order of the float32 sums: 96 blocks over 4 shards."""
    cfg = _cfg(1.0, 2)                  # every block, both meshes
    w1, _, _ = _train(mesh1, cfg)
    w4, _, _ = _train(mesh4, cfg)
    np.testing.assert_allclose(w1, w4, rtol=2e-5, atol=1e-7)


def test_the_divisor_is_the_sampled_blocks_valid_rows(mesh1):
    """One step from zero weights: ``-eta g / |S|`` with ``|S|`` the
    rows of the blocks drawn (blocks hold 3 to 16 rows, some none)."""
    w, X, meta = _train(mesh1, _cfg(0.1, 1), t0=77)
    indptr, ids, vals, y, block_of = pairs.csr_from_blocks(
        np.asarray(X), ssgd_pairs.geometry(meta))
    ref = ref_mod.Reference(config=_config(), fraction=0.1, data_seed=5,
                            sample_seed=42)
    n_rows, _ = ref.rows_and_pairs(77, 1)
    from reference import ssgd_ref

    drawn = ssgd_ref.block_draws(42, 77, 1, 1, 96, 10)[0, 0]
    rows = np.flatnonzero(np.isin(block_of, drawn))
    assert len(rows) == n_rows[0] == meta["block_counts"][drawn].sum()
    assert len(set(meta["block_counts"][drawn])) > 1
    w64, _, _ = _step64(indptr, ids, vals, y, rows,
                        np.zeros(5001, np.float64))
    np.testing.assert_allclose(w[:5001], w64, rtol=1e-5, atol=1e-9)
    # divided by the nominal rows of a step instead, it is another result
    nominal = 600 * 10 / 96
    assert abs(len(rows) / nominal - 1) > 0.01


def test_one_hot_rows_as_pairs_train_like_the_indexed_format(mesh1):
    """An indexed table's rows written as pairs of value 1, a block of
    rows to a block: the same draws, the same rows, the same weights
    as the ``indexed`` format's trainer (the cells' code)."""
    cards, B, n = (30, 200, 7, 3), 64, 1000
    cfg = dataclasses.replace(_cfg(0.25, 8), gather_block_rows=B)
    Xi, mi = ssgd.build_hashed_table(n, 4, 0, mesh1, cfg, data_seed=2,
                                     cardinalities=cards,
                                     row_format="indexed")
    fi = ssgd.make_train_fn_fused(mesh1, cfg, mi)
    d = jnp.zeros((1,), jnp.float32)
    wi, _ = fi(Xi, d, d, d, d, jnp.zeros((mi["d_total"],), jnp.float32))
    host = np.asarray(Xi)                       # (blocks, 8, B)
    idx = host[:, :4, :].transpose(0, 2, 1).reshape(-1, 4)[:n]
    y = host[:, 4, :].reshape(-1)[:n]
    geom = pairs.PairsGeometry(sum(cards), 128 * B, B, host.shape[0])
    Xp = pairs.blocks_from_csr(np.arange(n + 1) * 4, idx.reshape(-1),
                               np.ones(4 * n, np.float32), y, geom)
    mp = dict(row_format="pairs", pack=1, n_rows=n,
              n_features=sum(cards), n_blocks=geom.n_blocks,
              block_slots=geom.block_slots, block_rows=B,
              d_total=geom.w_len)
    assert ssgd.fused_gather_geometry(cfg, mp, 1) == \
        ssgd.fused_gather_geometry(cfg, mi, 1)
    fp = ssgd.make_train_fn_fused(mesh1, cfg, mp)
    wp, _ = fp(jnp.asarray(Xp), d, d, d, d,
               jnp.zeros((geom.w_len,), jnp.float32))
    assert mi["d_total"] == geom.w_len
    np.testing.assert_allclose(np.asarray(wp), np.asarray(wi), rtol=1e-5,
                               atol=1e-8)
    assert np.count_nonzero(np.asarray(wi)) > 100


def test_the_bfloat16_control_fails_where_float32_passes():
    """Values, weights, gathered products and per-slot sums in bfloat16
    land outside the tolerance the sound passes keep."""
    rows = SHAPES["ragged"] + SHAPES["fills_a_block"]
    indptr, ids, vals, y = _csr(rows)
    X = jnp.asarray(pairs.blocks_from_csr(indptr, ids, vals, y, GEOM))
    w, sel = _w(3), jnp.arange(GEOM.n_blocks)
    _, g64, _ = _step64(indptr, ids, vals, y, range(len(y)), w[:D + 1])
    errs = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        m = pairs.margins(X, jnp.asarray(w), sel, GEOM, dtype=dtype)
        yy, valid = pairs.labels(X, sel, GEOM)
        g = np.asarray(pairs.slot_sums(
            X, (jax.nn.sigmoid(m) - yy) * valid, sel, GEOM, dtype=dtype))
        errs[dtype] = np.linalg.norm(g[:D + 1] - g64) / np.linalg.norm(g64)
    assert errs[jnp.float32] < 1e-6 < 1e-4 < errs[jnp.bfloat16], errs
    ref = ref_mod.Reference(config=_config(), fraction=0.1, data_seed=5,
                            sample_seed=42)
    good = ref.follow(1, 4)[0]
    low = ref.follow(1, 4, dtype=jnp.bfloat16)[0]
    assert ref_mod.rel_err(low, good, np.zeros_like(good)) > 1e-3


# ---- refusals, names, spans, the CLI -------------------------------------------------

META = dict(row_format="pairs", pack=1, n_rows=600, n_features=5000,
            n_blocks=96, block_slots=2048, block_rows=16, d_total=5120)


@pytest.mark.parametrize("change,word", [
    (dict(sampler="bernoulli"), "masks every row of a dense matrix"),
    (dict(sampler="fused_train"), "megakernel"),
    (dict(comm="int8"), "comm='int8'"),
    (dict(comm="bf16"), "comm='bf16'"),
    (dict(comm="topk:0.1"), "comm='topk:0.1'"),
    (dict(comm="bucketed"), "comm='bucketed'"),
    (dict(sync="ssp:4"), "the guarantee is BSP"),
    (dict(feature_sharded=True), "sharded over chips"),
])
def test_what_cannot_take_pairs_rows_refuses_by_name(mesh1, change, word):
    cfg = dataclasses.replace(_cfg(0.1, 1), **change)
    with pytest.raises(ValueError, match="pairs rows") as err:
        ssgd.make_train_fn_fused(mesh1, cfg, META)
    assert word in str(err.value)
    with pytest.raises(ValueError, match="pairs rows"):
        ssgd_pairs.prepare_synthetic(SPEC, mesh1, cfg)


def test_the_other_loader_and_a_short_table_refuse(mesh1, mesh4):
    with pytest.raises(ValueError, match="their own loader"):
        ssgd.build_hashed_table(100, 2, 0, mesh1, _cfg(1.0, 1),
                                row_format="pairs")
    short = dataclasses.replace(SPEC, n_blocks=80)
    with pytest.raises(ValueError, match="no row is dropped or cut"):
        ssgd_pairs.build_table(short, mesh1)
    with pytest.raises(ValueError, match="4 shard"):
        ssgd_pairs.build_table(dataclasses.replace(SPEC, n_blocks=94),
                               mesh4)
    assert "pairs" in ssgd.INDEX_ROW_FORMATS
    # no number given: the blocks the rows need, whole over the shards
    _, meta = ssgd_pairs.build_table(
        dataclasses.replace(SPEC, n_blocks=None), mesh4)
    assert meta["n_blocks"] % 4 == 0
    assert 0 <= meta["n_blocks"] - meta["blocks_used"] < 4


def test_lowered_trainer_names_its_parts(mesh1):
    fn = ssgd.make_train_fn_fused(mesh1, _cfg(0.1, 2), META)
    X = jax.ShapeDtypeStruct((96, 40, 128), jnp.int32)
    d = jnp.zeros((1,), jnp.float32)
    w = jnp.zeros((5120,), jnp.float32)
    text = fn.lower(X, d, d, d, d, w).as_text(debug_info=True)
    for scope in (names.SSGD_DRAW, names.SSGD_GATHER, names.SSGD_SCATTER,
                  names.SSGD_UPDATE, names.SSGD_SYNC):
        assert scope + "/" in text, scope
    for outer in (names.SSGD_GATHER, names.SSGD_SCATTER):
        for inner in (names.SSGD_TABLE_HBM, names.SSGD_ROWSUM):
            assert f"{outer}/{inner}/" in text, (outer, inner)
    assert names.SSGD_ROWSUM == "tda.ssgd.rowsum"


def test_spans_counters_report_and_result(mesh1, tmp_path):
    tel = str(tmp_path / "tel")
    events.configure(tel)
    try:
        res = ssgd_pairs.train(SPEC, mesh1, _cfg(0.1, 6), data_seed=3,
                               checkpoint_dir=str(tmp_path / "ck"),
                               checkpoint_every=3)
        counted = events.counters()
    finally:
        events.configure(False)
    assert res.heldout_log_loss < 0.6931
    assert res.forms.startswith(
        "row format pairs: 5000 weights (0.0 MB) in HBM, 600 rows of "
        "120001 pairs (longest 1024) in 85 of 96 blocks of 2048 slots")
    assert "gather pass xla" in res.forms
    assert counted["ssgd.pairs_rows"] == 600
    assert counted["ssgd.pairs_pairs"] == 120001
    assert counted["ssgd.pairs_slots"] == 96 * 2048
    assert counted["ssgd.pairs_padding_slots"] == 96 * 2048 - 120001
    assert counted["ssgd.pairs_longest_row"] == 1024
    assert (counted["ssgd.pairs_blocks"],
            counted["ssgd.pairs_blocks_used"]) == (96, 85)
    evts = report.load_events(tel)
    ends = {e["name"]: e for e in evts if e["ev"] == "span_end"}
    prep, pack, gen = (ends[n] for n in ("ssgd:prepare", "ssgd:pack_pairs",
                                         "ssgd:generate"))
    assert pack["parent"] == gen["parent"] == prep["id"]
    assert (prep["row_format"], prep["pairs"], prep["pair_slots"],
            prep["rows"], prep["bytes"]) == (
                "pairs", 120001, 96 * 2048, 600, 96 * 40 * 128 * 4)
    assert prep["padding_share"] == round(96 * 2048 / 120001, 6)
    assert (pack["blocks"], pack["blocks_used"]) == (96, 85)
    assert gen["bytes"] == 96 * 40 * 128 * 4
    seg = ends["train:segment"]
    assert (seg["row_format"], seg["gather_form"], seg["scatter_form"],
            seg["rowsum_form"]) == ("pairs", "xla", "xla", "vectors")
    lines = report.render(report.summarize(evts)).splitlines()
    for line in ("row format: pairs", "gather pass: xla",
                 "scatter pass: xla",
                 "pairs: 600 rows of 120001 (feature, value) pairs, "
                 "longest 1024, in 85 of 96 blocks of 2048 slots (76607 "
                 "of 196608 slots hold no pair: 38.96%); 0.0 MB of "
                 "weights in HBM, row sums by vectors"):
        assert line in lines, line


def test_the_benchmarks_readers_of_the_loader(mesh1):
    """``pair_padding_pct.lr`` and ``generate_s.lr`` read the loader's
    spans as the harness loads them."""
    import importlib.util
    import time

    def reader(name):
        spec = importlib.util.spec_from_file_location(
            "r_" + name.replace(".", "_"),
            os.path.join(BENCH, "layer_metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    class Ctx:
        spans = []

    events.configure(False)
    t0 = time.perf_counter()
    ssgd_pairs.build_table(SPEC, mesh1, data_seed=3)
    Ctx.spans = [("data_build", t0, time.perf_counter())]
    pad = reader("pair_padding_pct.lr").read(Ctx)
    assert abs(pad - (1 - 120001 / (96 * 2048)) * 100) < 1e-3
    assert reader("generate_s.lr").read(Ctx) > 0
    Ctx.spans = []
    assert reader("pair_padding_pct.lr").read(Ctx) is None


def test_cli_trains_pairs_and_prints_the_forms(capsys):
    from tpu_distalg import cli

    rc = cli.main(["--emulate", "1", "ssgd", "--row-format", "pairs",
                   "--pair-rows", "400", "--features", "3000",
                   "--length-mu", "4.2", "--max-pairs", "512",
                   "--pair-block-slots", "1024",
                   "--mini-batch-fraction", "0.2", "--n-iterations", "6"])
    out = capsys.readouterr().out
    assert rc in (0, None)
    assert "row format pairs: 3000 weights (0.0 MB) in HBM, 400 rows of" \
        in out
    assert "Held-out accuracy:" in out


@pytest.mark.parametrize("argv,word", [
    (["--row-format", "pairs", "--hashed-rows", "10"], "two tables"),
    (["--row-format", "pairs", "--indexed-rows", "10"], "two tables"),
    (["--row-format", "pairs", "--stream-cache", "x"], "--stream-cache"),
    (["--row-format", "pairs", "--pair-rows", "10", "--max-pairs", "512",
      "--pair-block-slots", "256"], "no row is split or cut"),
    (["--row-format", "pairs", "--sampler", "fused_train"], "pairs rows"),
])
def test_cli_refuses_what_names_no_pairs_table(argv, word):
    from tpu_distalg import cli

    with pytest.raises((SystemExit, ValueError)) as err:
        cli.main(["--emulate", "1", "ssgd"] + argv)
    assert word in str(err.value)
