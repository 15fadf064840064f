"""The sums of the indexed fields whose ranges are past ``2 **
VMEM_BITS`` by address in ONE accumulator in VMEM, a piece of a field's
range at a time (``pallas_hashed._hashed_field_scatter_kernel``, one
call), interpreted, against XLA's scatter-add (``slot_sums_hbm``, which
the CPU and a range of too many pieces keep) and against float64 on the
host: a range that is no multiple of 128 from a base that is no whole
row, in one piece and in two; the pairs of a run of four in one lane,
in one row, in four rows and in one place of two pieces; rows that add
nothing, one and several sampled blocks, a flat and a heavily skewed
draw; the chooser (``field_scatter_form``: a size and a platform); and
the whole step's sums with the kernel on, every other form beside
it."""

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_distalg.ops import pallas_hashed as ph
from tpu_distalg.telemetry import events, report
from tpu_distalg.utils import datasets

# field 1: base 300 (lane 44 of row 2), 5000 slots; field 3: base 5337
# (lane 89 of row 41), 7001 slots: neither a whole row nor whole rows
CARDS = (300, 5000, 37, 7001)
KDD12_QUERY, KDD12_USER, KDDB = 24296581, 21913244, 29890095


def _geom(block_rows):
    return ph.HashedGeometry(nnz=len(CARDS), hash_bits=0,
                             block_rows=block_rows, field_sizes=CARDS)


def _slots(draw, rng, lo, hi, shape):
    """One field's slots of ``shape`` (blocks, rows) under a draw. The
    kernel loads a run of ``FIELD_RUN`` neighbouring rows before it
    stores any: the crafted draws plant what that must not lose."""
    n, per = int(np.prod(shape)), ph.FIELD_RUN
    runs = -(-n // per)
    # a row of the model vector that lies whole inside the range
    row = rng.integers((lo >> 7) + 1, (hi >> 7) - 4, (runs, 1))
    if draw == "flat":
        h = rng.integers(lo, hi, n)
    elif draw == "skewed":        # 0.7 of the pairs in three slots
        h = np.where(rng.random(n) < 0.7,
                     lo + rng.integers(0, 3, n) * 131,
                     rng.integers(lo, hi, n))
    elif draw == "one_lane":      # a run's pairs in ONE slot
        h = np.repeat(rng.integers(lo, hi, runs), per)[:n]
    elif draw == "one_row":       # ... in one row, each its own lane
        lanes = np.stack([rng.permutation(128)[:per]
                          for _ in range(runs)])
        h = ((row << 7) + lanes).reshape(-1)[:n]
    elif draw == "four_rows":     # ... in one lane of four rows
        h = (((row + np.arange(per)) << 7)
             + rng.integers(0, 128, (runs, 1))).reshape(-1)[:n]
    elif draw == "two_rows":      # rows A B A B, lanes a b b a
        assert per == 4
        lanes = rng.integers(0, 128, (runs, 2))
        h = (((row + np.array([0, 1, 0, 1])) << 7)
             + lanes[:, [0, 1, 1, 0]]).reshape(-1)[:n]
    else:                         # under pieces of 32 rows: one place of
        assert draw == "two_pieces" and per == 4    # the two pieces
        row = (lo >> 7) + rng.integers(1, 7, (runs, 1))
        lanes = rng.integers(0, 128, (runs, 2))
        h = (((row + np.array([0, 32, 32, 0])) << 7)
             + lanes[:, [0, 0, 1, 1]]).reshape(-1)[:n]
    assert h.min() >= lo and h.max() < hi
    return h.reshape(shape).astype(np.int32)


DRAWS = ("flat", "skewed", "one_lane", "one_row", "four_rows", "two_rows",
         "two_pieces")


@pytest.mark.parametrize("draw,field,block_rows,ids,piece_rows", [
    # every draw over several blocks of two chunks' runs, the range in
    # two pieces ...
    *[(d, 3, 256, (4, 0, 3), 32) for d in DRAWS],
    # ... and over one block of a chunk shorter than CHUNK_ROWS, the
    # other field, the range in one piece
    *[(d, 1, 128, (2,), None) for d in DRAWS],
    ("flat", 1, 256, (4, 0, 3), 32), ("skewed", 1, 256, (0,), 32),
    ("one_lane", 3, 128, (1, 2), None), ("two_rows", 3, 128, (3,), 32),
    ("flat", 3, 512, (0, 1), None), ("one_row", 1, 512, (4,), 32),
    ("two_pieces", 1, 256, (1, 3), 32),
])
def test_the_kernel_gives_xlas_sums(monkeypatch, draw, field, block_rows,
                                    ids, piece_rows):
    geom = _geom(block_rows)
    lo, hi = geom.offsets[field], geom.offsets[field + 1]
    assert lo % 128 and (hi - lo) % 128
    if piece_rows:
        monkeypatch.setattr(ph, "FIELD_PIECE_ROWS", piece_rows)
    rows, phases = ph.field_phases(geom, (field,))
    assert (rows, len(phases)) == ((32, 2) if piece_rows else (64, 1))
    rng = np.random.default_rng([DRAWS.index(draw), field, block_rows])
    nb = 5
    X = np.zeros((nb, geom.fields_held, block_rows), np.int32)
    for f in range(geom.nnz):     # the other fields: any slot of theirs
        X[:, f, :] = rng.integers(geom.offsets[f], geom.offsets[f + 1],
                                  (nb, block_rows))
    X[:, field, :] = _slots(draw, rng, lo, hi, (nb, block_rows))
    r = rng.standard_normal((len(ids), block_rows)).astype(np.float32)
    # an invalid tail and rows that hold no row: their residual is 0,
    # their slot any slot of the field
    r[-1, block_rows - 37:] = 0.0
    r[0, 5:9] = 0.0
    ids = jnp.asarray(ids, jnp.int32)
    got = np.asarray(ph.slot_sums_fields(
        jnp.asarray(X), jnp.asarray(r), ids, geom, (field,),
        interpret=True)[0])
    assert got.shape == (hi - lo,) and got.dtype == np.float32
    want = np.bincount(X[np.asarray(ids), field, :].reshape(-1) - lo,
                       weights=r.astype(np.float64).reshape(-1),
                       minlength=hi - lo)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=2e-6)
    xla = np.asarray(ph.slot_sums_hbm(
        jnp.asarray(X), jnp.asarray(r), ids, geom, (field,)))
    np.testing.assert_allclose(got, xla[lo:hi], rtol=1e-6, atol=2e-6)
    assert not xla[:lo].any() and not xla[hi:].any()


@pytest.mark.parametrize("piece_rows", [32, 1 << 17])
def test_whole_number_residuals_sum_to_the_bit(monkeypatch, piece_rows):
    """No addend lost, doubled or carried to another row or another
    piece: with small whole numbers every order of float32 additions is
    exact. Both fields in one call, as the step makes it."""
    monkeypatch.setattr(ph, "FIELD_PIECE_ROWS", piece_rows)
    geom = _geom(256)
    rng = np.random.default_rng(7)
    draws = ("one_lane", "one_row", "two_rows", "two_pieces")
    X = np.zeros((len(draws), geom.fields_held, 256), np.int32)
    for f in (1, 3):
        X[:, f, :] = np.concatenate(
            [_slots(d, rng, geom.offsets[f], geom.offsets[f + 1], (1, 256))
             for d in draws])
    r = rng.integers(-8, 9, (len(draws), 256)).astype(np.float32)
    ids = jnp.arange(len(draws), dtype=jnp.int32)
    got = ph.slot_sums_fields(jnp.asarray(X), jnp.asarray(r), ids, geom,
                              (1, 3), interpret=True)
    for f, g in zip((1, 3), got):
        lo, hi = geom.offsets[f], geom.offsets[f + 1]
        want = np.bincount(X[:, f, :].reshape(-1) - lo,
                           weights=r.reshape(-1).astype(np.float64),
                           minlength=hi - lo)
        np.testing.assert_array_equal(np.asarray(g),
                                      want.astype(np.float32))


@pytest.mark.parametrize("slots,on_tpu,form", [
    (KDD12_QUERY, True, "vmem"), (KDD12_USER, True, "vmem"),
    (KDD12_QUERY, False, "xla"), (KDD12_USER, False, "xla"),
    # a field of kddb's 29.9M values: two pieces still
    (KDDB, True, "vmem"), (KDDB, False, "xla"),
    # three pieces would visit every pair three times: XLA's
    (40_000_000, True, "xla"), (40_000_000, False, "xla"),
    ((1 << 22) + 1, True, "vmem"), ((1 << 22) + 1, False, "xla"),
])
def test_the_chooser_reads_a_size_and_a_platform(slots, on_tpu, form):
    assert ph.field_scatter_form(slots, on_tpu) == form
    # the gather's form does not follow: these fields stay 'hbm'
    assert ph.field_form(0, 8192, slots) == "hbm"


def test_a_piece_is_a_size_that_has_run():
    """A piece of 2^17 rows of 128 lanes is 67.1 MB and asks 76.5 MB
    with a chunk's buffers and 8 MB of room: under the 83.6 MB that
    PR 56's Step 0 asked and ran; accumulators of whole ranges (87.7
    and 97.2 MB) did not come back (PR 59's Step 0). The widest range
    of two pieces ends a row short of 2^18 rows."""
    assert ph.FIELD_PIECE_ROWS == 1 << 17
    assert ph._vmem_limit(_geom(256), 1, ph.FIELD_PIECE_ROWS * 128) == (
        (64 << 20) + 8 * 256 * 128 * 4 + (8 << 20)) < 83_600_000
    assert [ph.field_pieces(n) for n in (
        KDD12_QUERY, KDD12_USER, KDDB, (1 << 22) + 1)] == [2, 2, 2, 1]
    edge = ((2 << 17) - 1) * 128
    assert (ph.field_pieces(edge), ph.field_pieces(edge + 1)) == (2, 3)
    assert ph.field_scatter_form(edge, True) == "vmem"
    assert ph.field_scatter_form(edge + 1, True) == "xla"
    # the cell's call: four phases over one accumulator of 2^17 rows
    geom = ph.HashedGeometry(11, 0, 8192, field_sizes=(
        24323, 594098, 13745, 3, 3, KDD12_QUERY, 1157062, 3750862, 2936510,
        KDD12_USER, 21))
    off = geom.offsets
    assert ph.field_phases(geom, (5, 9)) == (1 << 17, (
        (5, off[5] >> 7, 0), (5, off[5] >> 7, 1),
        (9, off[9] >> 7, 0), (9, off[9] >> 7, 1)))
    # small fields share an accumulator of the widest's rows
    assert ph.field_phases(_geom(256), (1, 3)) == (
        64, ((1, 2, 0), (3, 41, 0)))


def _step_inputs():
    cards = (300, 5000, 37, 7001, 3)
    geom = ph.HashedGeometry(nnz=5, hash_bits=0, block_rows=256,
                             field_sizes=cards)
    rng = np.random.default_rng(3)
    X = np.zeros((6, geom.fields_held, 256), np.int32)
    for f, c in enumerate(cards):
        X[:, f, :] = geom.offsets[f] + rng.integers(0, c, (6, 256))
    r = jnp.asarray(rng.standard_normal((3, 256)), jnp.float32)
    return geom, cards, jnp.asarray(X), r, jnp.asarray([1, 3, 4], jnp.int32)


@pytest.mark.parametrize("piece_rows", [32, 1 << 17])
@pytest.mark.parametrize("on_vmem", [(1, 3), (1,), (3,), ()])
def test_a_steps_sums_with_the_kernel_on(monkeypatch, tmp_path, on_vmem,
                                         piece_rows):
    """``slot_sums`` over an indexed table with every form in it, the
    chooser steered (the CPU's own answer is ``xla`` for both fields):
    the fields of ``on_vmem`` through the kernel, the others through
    XLA's scatter-add, one vector either way; each field's form said
    once."""
    monkeypatch.setattr(ph, "VMEM_BITS", 12)
    monkeypatch.setattr(ph, "FIELD_PIECE_ROWS", piece_rows)
    geom, cards, X, r, ids = _step_inputs()
    plan = ph.field_plan(geom, datasets.indexed_field_dictionaries(cards))
    assert plan.hbm_fields == (1, 3) and plan.dict_fields
    monkeypatch.setattr(
        ph, "field_scatter_form",
        lambda n, on_tpu: "vmem" if cards.index(n) in on_vmem else "xla")
    events.configure(str(tmp_path))
    try:
        got = ph.slot_sums(X, r, ids, geom, plan=plan, interpret=True)
    finally:
        events.configure(False)
    np.testing.assert_allclose(got, ph.slot_sums_xla(X, r, ids, geom),
                               rtol=1e-5, atol=1e-5)
    evts = [e for e in report.load_events(str(tmp_path))
            if e["ev"] == "ssgd:field_scatter"]
    rows, phases = ph.field_phases(geom, on_vmem) if on_vmem else (0, ())
    pieces = {f: sum(p[0] == f for p in phases) for f in (1, 3)}
    asked = ph._vmem_limit(geom, 1, rows * 128)
    assert sorted((e["field"], e["form"], e["range_slots"], e["pieces"])
                  for e in evts) == [
        (f, "vmem" if f in on_vmem else "xla", cards[f], pieces[f])
        for f in (1, 3)]
    if piece_rows == 32:
        assert [pieces[f] for f in on_vmem] == [2] * len(on_vmem)
    for e in evts:
        assert (e["kernel"], e["vmem_bytes"]) == (
            ("_hashed_field_scatter_kernel", asked)
            if e["form"] == "vmem" else ("xla scatter-add", 0))
    lines = report.render(report.summarize(evts)).splitlines()
    for f in (1, 3):
        want = (f"field scatter: _hashed_field_scatter_kernel (vmem) over "
                f"field {f}, a range of {cards[f]} slots in {pieces[f]} "
                f"piece(s) of an accumulator in VMEM, "
                f"{asked / 1e6:.1f} MB asked"
                ) if f in on_vmem else (
            f"field scatter: xla scatter-add (xla) over field {f}, a "
            f"range of {cards[f]} slots")
        assert want in lines, want


def test_off_a_tpu_the_step_keeps_xlas_scatter(monkeypatch):
    """``interpret`` is how a mesh's platform reaches the passes: the
    CPU's step lowers no call of the new kernel."""
    import jax

    monkeypatch.setattr(ph, "VMEM_BITS", 12)
    geom, cards, X, r, ids = _step_inputs()
    plan = ph.field_plan(geom, datasets.indexed_field_dictionaries(cards))
    text = jax.jit(lambda X, r, ids: ph.slot_sums(
        X, r, ids, geom, plan=plan, interpret=True)).lower(
            X, r, ids).as_text()
    assert "_hashed_field_scatter_kernel" not in text
    assert "scatter" in text
