"""The pairs passes by address (``ops/pallas_pairs.py``, the ``vmem``
form of ``ops/pairs.py``), interpreted: both kernels against the ``xla``
form and against a float64 sum over CSR arrays on ragged tables (a row
of 0 pairs, a row that fills a block, a feature many times in a row,
ids 0 and ``n_features - 1``, empty blocks, padding slots, a block drawn
twice), ``pass_form`` from the platform and the size, a few trained
steps against the ``xla`` form's on one device and on four, the event
that says which form engaged, the report's lines and the lowered
trainer's scopes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg.models import ssgd, ssgd_pairs
from tpu_distalg.ops import pairs, pallas_pairs
from tpu_distalg.telemetry import events, names, report

D = 3000
# 64 vectors a block: two chunks of CHUNK_VECTORS a grid step
GEOM = pairs.PairsGeometry(n_features=D, block_slots=8192, block_rows=8,
                           n_blocks=6)


def _row(n, step=37, at=0):
    return [(int((at + i * step) % D), float((i % 7) - 3) / 4)
            for i in range(n)]


SHAPES = {
    # rows 0 and 3 hold no pair
    "empty_rows": [[], _row(5), _row(300, at=9), [], _row(129)],
    # a row of a whole block, then a block of short rows
    "fills_a_block": [_row(8192, step=1), _row(3), _row(128)],
    # one feature 200 times in a row (neighbouring pairs of one vector
    # into one slot), its row mate 128 times, then both in another row
    "duplicates": [[(700, 0.5)] * 200 + [(701, 0.25)] * 128,
                   [(700, -1.0), (701, 2.0), (700, 0.125)]],
    # every pair of a row in ONE row of the table, lane after lane, and
    # a second row that alternates between two table rows
    "one_table_row": [[(256 + i % 128, 0.5 + i % 3) for i in range(700)],
                      [(128 * (i % 2) + i % 5, 1.0) for i in range(300)]],
    "first_and_last_id": [[(0, 1.0), (D - 1, -2.0)] * 70,
                          [(D - 1, 0.5)], [(0, 0.25)]],
    # two rows: five of the six blocks hold nothing
    "empty_blocks": [_row(40), _row(7)],
    "ragged": [_row(n, at=n) for n in (0, 1, 127, 128, 129, 5, 0, 2000,
                                       64, 3, 4097, 900)],
}
# the sampled blocks: every block, out of order, one of them twice
SEL = np.asarray([4, 0, 5, 1, 1, 3, 2], np.int32)


def _csr(rows):
    indptr = np.concatenate([[0], np.cumsum([len(r) for r in rows])])
    ids = np.asarray([p[0] for r in rows for p in r], np.int32)
    vals = np.asarray([p[1] for r in rows for p in r], np.float32)
    y = np.asarray([i % 2 for i in range(len(rows))], np.int32)
    return indptr, ids, vals, y


def _float64(indptr, ids, vals, starts, w, r):
    """Margins and per-slot sums over the sampled blocks in float64,
    from the CSR arrays and the packing's cuts alone."""
    m = np.full(r.shape, float(w[D]))
    g = np.zeros(D + 1)
    for n, b in enumerate(SEL):
        if b >= len(starts) - 1:
            continue
        for k, i in enumerate(range(starts[b], starts[b + 1])):
            sl = slice(indptr[i], indptr[i + 1])
            v = vals[sl].astype(np.float64)
            m[n, k] += np.sum(w[ids[sl]].astype(np.float64) * v)
            np.add.at(g, ids[sl], float(r[n, k]) * v)
    g[D] = r.astype(np.float64).sum()
    return m, g


@pytest.fixture
def onto_kernels(monkeypatch):
    """The CPU takes the ``xla`` form; a test steers the passes onto
    the kernels here (no option does), which a geometry that lies on no
    TPU interprets. The held-out scorer is cached by geometry, which
    does not say who steered."""
    ssgd_pairs._score_fn.cache_clear()
    monkeypatch.setattr(pairs, "pass_form", lambda w_len, on_tpu: "vmem")
    yield
    ssgd_pairs._score_fn.cache_clear()


def _both_forms(X, w, r, sel, monkeypatch):
    """``{form: (margins, slot sums)}`` of the same operands."""
    got = {}
    for form in ("xla", "vmem"):
        monkeypatch.setattr(pairs, "pass_form",
                            lambda w_len, on_tpu, form=form: form)
        got[form] = (np.asarray(pairs.margins(X, jnp.asarray(w), sel, GEOM)),
                     np.asarray(pairs.slot_sums(X, jnp.asarray(r), sel,
                                                GEOM)))
    return got


@pytest.mark.parametrize("trip", [32, 128])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_both_kernels_against_xla_and_float64(shape, trip, monkeypatch):
    monkeypatch.setattr(pallas_pairs, "TRIP_PAIRS", trip)
    indptr, ids, vals, y = _csr(SHAPES[shape])
    X = jnp.asarray(pairs.blocks_from_csr(indptr, ids, vals, y, GEOM))
    starts = pairs.pack_rows(np.diff(indptr), GEOM.block_slots,
                             GEOM.block_rows)
    rng = np.random.default_rng(len(shape))
    w = rng.normal(size=GEOM.w_len).astype(np.float32)
    w[D + 1:] = 0
    r = rng.normal(size=(len(SEL), GEOM.block_rows)).astype(np.float32)
    r *= np.asarray(pairs.labels(X, jnp.asarray(SEL), GEOM)[1])
    sel = jnp.asarray(SEL)
    got = _both_forms(X, w, r, sel, monkeypatch)
    m64, g64 = _float64(indptr, ids, vals, starts, w, r)
    for form, (m, g) in got.items():
        np.testing.assert_allclose(m, m64, rtol=2e-5, atol=2e-5,
                                   err_msg=form)
        np.testing.assert_allclose(g[:D + 1], g64, rtol=2e-5, atol=2e-5,
                                   err_msg=form)
        assert not g[D + 1:].any(), form
    np.testing.assert_allclose(got["vmem"][0], got["xla"][0], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(got["vmem"][1], got["xla"][1], rtol=1e-5,
                               atol=1e-5)


def test_a_padding_slot_adds_nothing_whatever_slot_0_holds(onto_kernels):
    """Id 0, value 0.0: the gather reads ``w[0]`` and multiplies it by
    nothing, the scatter adds 0.0 to slot 0."""
    indptr, ids, vals, y = _csr([[(5, 1.0)], [(6, 2.0)]])
    X = jnp.asarray(pairs.blocks_from_csr(indptr, ids, vals, y, GEOM))
    w = np.zeros(GEOM.w_len, np.float32)
    w[0], w[5], w[6] = 1e30, 3.0, 0.5
    sel = jnp.arange(2, dtype=jnp.int32)
    m = pairs.margins(X, jnp.asarray(w), sel, GEOM)
    assert (float(m[0, 0]), float(m[0, 1])) == (3.0, 1.0)
    r = jnp.zeros((2, GEOM.block_rows)).at[0, 0].set(1.0).at[0, 1].set(4.0)
    g = np.asarray(pairs.slot_sums(X, r, sel, GEOM))
    assert (g[0], g[5], g[6], g[D]) == (0.0, 1.0, 8.0, 5.0)
    assert np.count_nonzero(g) == 3


def test_vectors_past_a_blocks_last_row_are_not_read():
    """``used_vectors`` counts a block's rows' pairs in whole vectors;
    the kernels leave the rest alone: zeros out of the gather whatever
    those slots hold, nothing added by the scatter."""
    rows = [_row(130), _row(1), [], _row(128)]
    indptr, ids, vals, y = _csr(rows)
    X = pairs.blocks_from_csr(indptr, ids, vals, y, GEOM)
    sel = jnp.asarray([0, 1], jnp.int32)
    used = pairs.used_vectors(jnp.asarray(X), sel, GEOM)
    assert used.tolist() == [2 + 1 + 0 + 1, 0]
    # poison what lies past the last row: ids in range, values not 0
    X[:, 4:GEOM.vectors] = 7
    X[:, GEOM.vectors + 4:2 * GEOM.vectors] = np.float32(3.0).view(np.int32)
    w = jnp.ones((GEOM.w_len,), jnp.float32)
    prod = pallas_pairs.vector_products(jnp.asarray(X), w, sel, used, GEOM)
    assert not np.asarray(prod[0, 4:]).any() and not np.asarray(prod[1]).any()
    assert np.asarray(prod[0, :4]).any()
    back = jnp.ones((2, GEOM.vectors), jnp.float32)
    g = np.asarray(pallas_pairs.slot_sums(jnp.asarray(X), back, sel, used,
                                          GEOM))
    want = np.zeros_like(g)
    np.add.at(want, ids, vals)
    np.testing.assert_allclose(g, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("shape", ["duplicates", "one_table_row"])
def test_a_pieces_pairs_land_in_one_row(shape):
    """Neighbouring pairs that land in one row of the table (one slot,
    or lane after lane): a piece's rows are loaded before any is stored,
    and the last store to a row must hold every addend once."""
    indptr, ids, vals, y = _csr(SHAPES[shape])
    X = jnp.asarray(pairs.blocks_from_csr(indptr, ids, vals, y, GEOM))
    sel = jnp.arange(GEOM.n_blocks, dtype=jnp.int32)
    back = jnp.asarray(np.random.default_rng(1).normal(
        size=(GEOM.n_blocks, GEOM.vectors)).astype(np.float32))
    g = np.asarray(pallas_pairs.slot_sums(
        X, back, sel, pairs.used_vectors(X, sel, GEOM), GEOM))
    vrow = np.asarray(pairs._Tails(X, sel, GEOM).vrow)
    want = np.zeros(GEOM.w_len)
    starts = pairs.pack_rows(np.diff(indptr), GEOM.block_slots,
                             GEOM.block_rows)
    for b in range(len(starts) - 1):
        at = 0
        for k, i in enumerate(range(starts[b], starts[b + 1])):
            n = indptr[i + 1] - indptr[i]
            for q in range(n):
                r = float(back[b, (at + q) // 128])
                want[ids[indptr[i] + q]] += r * float(vals[indptr[i] + q])
            assert (vrow[b, at // 128:(at + n + 127) // 128] == k).all()
            at += -(-n // 128) * 128
    np.testing.assert_allclose(g, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("trip", [48, 6, 256])
def test_a_trip_is_whole_pieces_of_a_vector(trip, monkeypatch):
    monkeypatch.setattr(pallas_pairs, "TRIP_PAIRS", trip)
    X = jnp.zeros((GEOM.n_blocks, GEOM.held_rows, 128), jnp.int32)
    w = jnp.zeros((GEOM.w_len,), jnp.float32)
    with pytest.raises(ValueError, match="does not divide a vector"):
        pallas_pairs.vector_products(
            X, w, jnp.arange(2), jnp.full((2,), 64, jnp.int32), GEOM)


@pytest.mark.parametrize("which", ["margins", "slot_sums"])
def test_the_kernels_are_float32s(which, onto_kernels):
    """``dtype`` is the ``xla`` form's control: the ``vmem`` form
    refuses another than float32 and does not quietly run its own."""
    X = jnp.zeros((GEOM.n_blocks, GEOM.held_rows, 128), jnp.int32)
    second = (jnp.zeros((GEOM.w_len,), jnp.float32) if which == "margins"
              else jnp.zeros((2, GEOM.block_rows), jnp.float32))
    with pytest.raises(ValueError, match="float32's, not bfloat16's"):
        getattr(pairs, which)(X, second, jnp.arange(2), GEOM,
                              dtype=jnp.bfloat16)


WEBSPAM, KDDB = 16_609_143, 29_890_095


@pytest.mark.parametrize("n_features,on_tpu,form", [
    (WEBSPAM, True, "vmem"),      # 66.4 MB and 8 of room: under 80 MiB
    (WEBSPAM, False, "xla"),      # the CPU, whatever the size
    (KDDB, True, "xla"),          # 119.6 MB: past the budget
    (D, True, "vmem"),
    (D, False, "xla"),
])
def test_pass_form_from_platform_and_size(n_features, on_tpu, form):
    geom = pairs.PairsGeometry(n_features, 1 << 18, 512, 5248, on_tpu)
    assert geom.pass_form == form == pairs.pass_form(geom.w_len, on_tpu)
    meta = dict(n_features=n_features, block_slots=1 << 18, block_rows=512,
                n_blocks=5248)
    assert ssgd_pairs.geometry(meta).pass_form == "xla"
    assert ssgd_pairs.geometry(dict(meta, on_tpu=on_tpu)) == geom


def test_the_budget_is_one_constant_and_holds_one_copy():
    """The largest vector the budget admits, and the first it does not:
    ``4 * w_len`` and the room beside it against ``VMEM_BUDGET_BYTES``,
    which is also all either kernel is allowed of VMEM."""
    edge = (pairs.VMEM_BUDGET_BYTES - pairs.VMEM_ROOM_BYTES) // 4
    assert pairs.pass_form(edge, True) == "vmem"
    assert pairs.pass_form(edge + 128, True) == "xla"
    assert pairs.vmem_bytes(16609152) == 66436608 + (8 << 20)
    assert pairs.vmem_bytes(edge) == pairs.VMEM_BUDGET_BYTES
    # a double-buffered copy of webspam's vector is past the budget
    assert 2 * 66436608 > pairs.VMEM_BUDGET_BYTES
    # Step 0's second width (scripts/step0_pairs.py) is the budget's
    assert pairs.pass_form(18_800_000 + 128, True) == "vmem"
    geom = dataclasses.replace(GEOM, n_features=WEBSPAM)
    for params in (pallas_pairs._params(geom),
                   pallas_pairs._params(dataclasses.replace(geom,
                                                            on_tpu=True))):
        assert params["compiler_params"].vmem_limit_bytes == 74825216 \
            <= pairs.VMEM_BUDGET_BYTES
    assert pallas_pairs._params(geom)["interpret"]
    assert not pallas_pairs._params(
        dataclasses.replace(geom, on_tpu=True))["interpret"]


@pytest.mark.parametrize("n_sampled,form,per", [
    (52, "xla", 4), (52, "vmem", 13),       # webspam's step: 13 and 4 trips
    (10, "vmem", 10), (10, "xla", 2),
    (7, "xla", 1), (34, "vmem", 2), (64, "vmem", 16),
])
def test_a_trips_blocks_divide_the_steps(n_sampled, form, per):
    assert ssgd_pairs.trip_blocks(n_sampled, form) == per


# ---- the trainer ---------------------------------------------------------

SPEC = ssgd_pairs.PairsSpec(
    n_rows=600, n_features=5000, length_mu=4.848185062408447,
    block_slots=2048, block_rows=16, n_blocks=96, length_min=0,
    length_max=1024, scatter_c=77)


def _cfg(frac, steps):
    return ssgd.SSGDConfig(
        n_iterations=steps, eta=0.1, lam=0.0, mini_batch_fraction=frac,
        seed=42, eval_test=False, sampler="fused_gather")


def _train(mesh, cfg, t0=0):
    fn, X, w0, meta = ssgd_pairs.prepare_synthetic(SPEC, mesh, cfg,
                                                   data_seed=5)
    d = jnp.zeros((1,), jnp.float32)
    w, _ = fn(X, d, d, d, d, w0, t0=t0)
    return np.asarray(w), meta


@pytest.mark.parametrize("shards,frac,trips", [
    (1, 0.1, 1), (4, 0.1, 1), (1, 0.25, 2)])
def test_trained_weights_equal_the_xla_forms(mesh1, mesh4, shards, frac,
                                             trips, monkeypatch):
    """Three steps of 10 or 24 (one shard) or 4 x 2 (four) sampled
    blocks, the ``vmem`` form's trips of up to 16 against the ``xla``
    form's of up to 4: the same weights up to the order of the float32
    sums."""
    mesh = mesh1 if shards == 1 else mesh4
    want, meta = _train(mesh, _cfg(frac, 3), t0=640)
    assert ssgd_pairs.geometry(meta).pass_form == "xla"
    n_sampled = ssgd_pairs.blocks_geometry(_cfg(frac, 3), meta, shards)[1]
    assert n_sampled // ssgd_pairs.trip_blocks(n_sampled, "vmem") == trips
    monkeypatch.setattr(pairs, "pass_form", lambda w_len, on_tpu: "vmem")
    got, meta = _train(mesh, _cfg(frac, 3), t0=640)
    assert ssgd_pairs.geometry(meta).pass_form == "vmem"
    assert np.count_nonzero(want) > 1000
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-7)


def test_the_event_the_forms_line_and_the_report(mesh1, tmp_path,
                                                 onto_kernels):
    tel = str(tmp_path / "tel")
    events.configure(tel)
    try:
        res = ssgd_pairs.train(SPEC, mesh1, _cfg(0.1, 4), data_seed=3,
                               checkpoint_dir=str(tmp_path / "ck"),
                               checkpoint_every=2)
    finally:
        events.configure(False)
    assert res.heldout_log_loss < 0.6931
    assert res.forms.startswith(
        "row format pairs: 5000 weights (0.0 MB) in VMEM for a pass, 600 "
        "rows of 120001 pairs")
    assert "gather pass vmem" in res.forms \
        and "scatter pass vmem" in res.forms
    evts = report.load_events(tel)
    said = [e for e in evts if e["ev"] == "ssgd:pairs_pass"]
    # the trainer's two passes over 10 blocks, the held-out score's
    # gather over 64
    assert {(e["kernel"], e["blocks"]) for e in said} == {
        ("_pairs_gather_kernel", 10), ("_pairs_scatter_kernel", 10),
        ("_pairs_gather_kernel", 64)}
    for e in said:
        # one copy of the vector a pass: the gather's table, the
        # scatter's sums
        assert (e["form"], e["trip_pairs"], e["vmem_bytes"]) == (
            "vmem", pallas_pairs.TRIP_PAIRS, 4 * 5120 + (8 << 20))
    ends = {e["name"]: e for e in evts if e["ev"] == "span_end"}
    seg = ends["train:segment"]
    assert (seg["gather_form"], seg["scatter_form"]) == ("vmem", "vmem")
    lines = report.render(report.summarize(evts)).splitlines()
    for line in ("gather pass: vmem", "scatter pass: vmem",
                 "pairs pass: _pairs_gather_kernel (vmem) over 10 blocks a "
                 "call, 8.4 MB of VMEM asked, 32 pairs a trip",
                 "pairs pass: _pairs_scatter_kernel (vmem) over 10 blocks "
                 "a call, 8.4 MB of VMEM asked, 32 pairs a trip"):
        assert line in lines, line
    assert any("0.0 MB of weights resident in VMEM a pass" in x
               for x in lines)


def test_the_heldout_score_is_one_devices_program(mesh4, monkeypatch):
    """A mesh's trained weights are replicated and the held-out stream
    lies on one device: the scorer gets both there (four chips refused
    to partition its Mosaic kernel under a plain jit, PR 56)."""
    from tpu_distalg.parallel import partition

    seen = {}
    monkeypatch.setattr(
        ssgd_pairs, "_score_fn", lambda geom, n_blocks: lambda X, w: (
            seen.update(X=X.devices(), w=w.devices()) or (0.0, 0.0)))
    _, meta = ssgd_pairs.build_table(SPEC, mesh4, data_seed=1)
    w = partition.put(jnp.zeros((meta["d_total"],), jnp.float32), "w",
                      "ssgd", mesh4)
    assert len(w.devices()) == 4
    ssgd_pairs.evaluate(w, meta, data_seed=1)
    assert seen["w"] == seen["X"] and len(seen["X"]) == 1


def test_the_xla_form_says_so_too(mesh1, tmp_path):
    tel = str(tmp_path / "tel")
    events.configure(tel)
    try:
        ssgd_pairs.train(SPEC, mesh1, _cfg(0.1, 2), data_seed=3)
    finally:
        events.configure(False)
    evts = report.load_events(tel)
    said = {(e["kernel"], e["form"], e["blocks"]) for e in evts
            if e["ev"] == "ssgd:pairs_pass"}
    assert said == {("xla gather", "xla", 2), ("xla scatter", "xla", 2),
                    ("xla gather", "xla", 64)}
    lines = report.render(report.summarize(evts)).splitlines()
    assert "pairs pass: xla scatter (xla) over 2 blocks a call" in lines


META = dict(row_format="pairs", pack=1, n_rows=600, n_features=5000,
            n_slots=5000, d_total=5120, n_blocks=96, block_slots=2048,
            block_rows=16)


def _pallas_call_names(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
            continue
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", p)
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                _pallas_call_names(inner, found)
    return found


def test_lowered_vmem_trainer_names_its_parts(mesh1, onto_kernels):
    """A call a pass a trip under the pass's own scope, XLA's scatter
    and sort gone; ``tda.ssgd.table_hbm`` round what XLA still does on
    the vectors in HBM (``w`` brought to whole tiles, the sums cut back
    to ``w_len``, the bias's slot)."""
    fn = ssgd.make_train_fn_fused(mesh1, _cfg(0.1, 2), META)
    X = jax.ShapeDtypeStruct((96, 40, 128), jnp.int32)
    d = jnp.zeros((1,), jnp.float32)
    w = jnp.zeros((5120,), jnp.float32)
    text = fn.lower(X, d, d, d, d, w).as_text(debug_info=True)
    for scope in (names.SSGD_DRAW, names.SSGD_GATHER, names.SSGD_SCATTER,
                  names.SSGD_UPDATE, names.SSGD_SYNC):
        assert scope + "/" in text, scope
    for outer in (names.SSGD_GATHER, names.SSGD_SCATTER):
        assert f"{outer}/{names.SSGD_ROWSUM}/" in text, outer
    # ... and round the vector brought to whole tiles before its copy in
    for outer in (names.SSGD_GATHER, names.SSGD_SCATTER):
        assert f"{outer}/{names.SSGD_TABLE_HBM}/" in text, outer
    # (the row sums' segment sum is a scatter-add still, over V numbers)
    assert "stablehlo.sort" not in text
    # a trip of the step's loop is one call of each kernel
    calls = _pallas_call_names(
        jax.make_jaxpr(fn)(X, d, d, d, d, w).jaxpr, [])
    assert calls == ["_pairs_gather_kernel", "_pairs_scatter_kernel"]


def test_a_meta_that_does_not_say_lies_on_no_tpu(mesh1):
    """The form's one source is the loader's mesh, through ``meta``: the
    CPU's loader says no TPU, and so does a ``meta`` that says nothing
    (a host table's, ``blocks_from_csr``)."""
    _, meta = ssgd_pairs.build_table(SPEC, mesh1, data_seed=1)
    assert meta["on_tpu"] is False
    assert not ssgd_pairs.geometry(META).on_tpu
    assert ssgd_pairs.geometry(dict(META, on_tpu=True)).pass_form == "vmem"
    fn = ssgd.make_train_fn_fused(mesh1, _cfg(0.1, 2), META)
    X = jax.ShapeDtypeStruct((96, 40, 128), jnp.int32)
    d = jnp.zeros((1,), jnp.float32)
    text = fn.lower(X, d, d, d, d, jnp.zeros((5120,), jnp.float32)) \
        .as_text()
    assert "stablehlo.scatter" in text and "_pairs_gather_kernel" not in text
