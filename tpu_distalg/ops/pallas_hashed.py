"""The two passes of logistic regression over rows of indices.

A row is ``nnz`` int32 slots of a weight table of float32 and a 0/1
label; every value is 1 (a one-hot: click logs, ``models/ssgd.py``'s
second row format). The table is either *hashed*, ``2 ** hash_bits``
slots that every field's values are mixed into, or *indexed*, the
fields' ranges laid end to end so that every value of every field is
its own slot (``HashedGeometry.field_sizes``; 54.7M slots at KDD Cup
2012's shape, which no VMEM holds). A block of
``block_rows`` rows is held field-major, ``int32[fields_held,
block_rows]``: field ``j`` of the block's rows is one dense run of lanes,
the label is row ``nnz``, the rows up to ``fields_held`` (``nnz + 1``
rounded up to a sublane tile) are zero. 160 B a row at 39 fields, 157
of them needed.

The model is one vector ``w`` of float32: the table, the bias at
``[n_slots]``, zeros behind it to whole rows of 128 lanes (``n_slots +
128`` for a hashed table; an indexed table is as long as its fields,
``n_slots`` no multiple of anything, and ``w_len`` the next multiple
of 128 past the bias). A step over the sampled
blocks ``ids`` is

  margins:    m_i = b + sum_j w[h_ij]            (two fields of a row in
                                                 one slot count twice)
  slot sums:  g[s] = sum_i r_i * #{j: h_ij = s}  g[n_slots] = sum_i r_i

with ``r`` whatever the caller made of ``m`` (residual times validity).
Both are bound by addresses and not by bytes: 35.8M dependent accesses
a step at the benchmark's shape beside 73 MB read. Each pass has two
forms that give the same numbers up to the order of float32 additions,
and :func:`pass_form` picks one from the geometry alone:

``vmem``  Mosaic: the table (or the accumulators) ``(n_slots / 128,
          128)`` stays in VMEM, a chunk of the block's indices comes
          through SMEM, and a row at a time each index loads the
          table's 128-wide row ``h >> 7`` and keeps lane ``h & 127`` by
          a mask. The gather adds a row's masked loads into one vector
          that XLA folds; the scatter adds the row's residual, masked,
          into the row of an accumulator, the accumulators taken in
          turn (a load waits for the last store to its allocation,
          ``pallas_lloyd_wide``'s finding: on one v5e at the
          benchmark's shape a step's scatter took 100.1 ms with one
          accumulator, 59.9 with two, 39.6 with four; its gather 54.4).
``xla``   ``w[idx]`` and ``zeros.at[idx].add``: what XLA makes of them;
          the only form where a hashed table is past VMEM or a block is
          not whole lanes.
``fields`` an indexed table in whole-lane blocks: no form for the
          table, one for each field, since a field's slots lie in its
          own range and residency can be decided a range at a time.

Inside the ``vmem`` and ``fields`` forms a field is read in one of
three ways, and :func:`field_form` picks one from sizes alone: the
field's dictionary (every slot the field can hold, which the loader
states in its ``meta`` where a field takes few values: a click log's
device type or weekday, not its user id) and, in an indexed table, the
field's range. :func:`field_plan` makes the choice for a table.

``addr``  by address, as above: the loops of the two kernels run over
          these fields only. In an indexed table the by-address fields
          are taken in *groups* of at most ``2 ** VMEM_BITS`` slots
          (:class:`AddrGroup`): a group's ranges are copied end to end
          into one table that a call of the same two kernels keeps in
          VMEM, each field's slots re-based by a constant. A trip of
          either kernel's loop is a number of (row, field) pairs and
          one basic block (the scheduler overlaps independent chains
          only inside one): ``_loop_rows`` gives the most rows, 2, 4, 8
          ..., that keep it at ``TRIP_PAIRS`` pairs, so 2 rows of 18 or
          of 39 fields, 8 of four, 16 of two, 32 of one. A pair of a
          one-field call costs 12 scalar operations on two scalar slots
          (the row, the SMEM tile address, the load, base, shift, mask,
          two addresses), seven of which a row's other fields would
          share; at 2 pairs a trip the loop's own overhead came on top:
          8.5 bundles a pair, 6.2 at 32, where four fields take 4.4 and
          3.8 (the post-RA schedule), and every trip's taken branch 3.8
          ns that no bundle shows (a call's time is 0.667 ns a bundle +
          3.8 ns a trip + 0.06 us a grid step to 1%, in both cells). On
          one v5e at KDD Cup 2012's shape
          (PR 48's Step 0, ``scripts/step0_indexed.py``, ms a call over
          1.5M rows) a one-field gather read 13.18 / 9.56 / 8.84 / 8.62 /
          8.37 at 2 / 8 / 16 / 32 / 64 rows a trip and its scatter 13.67
          / 10.07 / 9.37 / 9.16 / 9.15, the four-field group's 22.31 /
          19.18 / 17.99 / 17.31 and 23.05 / 21.73 / 19.40 / 18.35 at 2 /
          4 / 8 / 16, every trip the same float32 numbers bit for bit;
          tracing and lowering a trip's pairs is paid by every run
          (``_each_row``), which is what holds ``TRIP_PAIRS`` at 32. A
          chunk brings the block's every field row through SMEM
          whatever the call serves (16 x 256 indices at 11 fields, 40 x
          256 at 39): the tile of 8 rows that holds a call's fields
          alone read the same (8.58 against 8.62 ms), the copy is hidden
          behind the chunk before.
``hbm``   an indexed field whose range alone is past ``2 ** VMEM_BITS``
          slots (a user id, a query id): its weights stay in HBM, under
          the scope ``tda.ssgd.table_hbm`` inside the pass's own. The
          gather is ``_hashed_hbm_gather_kernel``: the model vector as
          rows of 128 lanes in HBM, a chunk's indices through SMEM, one
          DMA a (row, field) pair of the table's row ``h >> 7`` into a
          landing row in VMEM, a wait a trip of copies, then lane ``h &
          127`` kept and a row's fields added up as the by-address
          kernel does, at the by-address rule's rows a trip (16 for two
          fields: 27.43 ms where 2 rows read 32.20, PR 48). No resident
          head: a pair costs the same whatever the skew (on one v5e at
          KDD Cup 2012's shape 32.6 ms a step for two fields of 1.5M
          rows where XLA's ``w[idx]`` takes 52.6, the same float32
          weights bit for bit: PR 47's Step 0). A read-modify-write
          of HBM rows by DMA has no order between two copies to one
          row, so the scatter does not mirror it. Its form is a field's
          own, :func:`field_scatter_form` from the field's range and the
          platform alone:
          ``vmem``  on a TPU, a range that goes in ``FIELD_MAX_PIECES``
                    pieces of ``FIELD_PIECE_ROWS`` rows of 128 lanes or
                    fewer: ``_hashed_field_scatter_kernel``, ONE call
                    for all such fields, whose grid's first axis runs
                    *phases*, a (field, piece) each, over the sampled
                    blocks. The ONE float32 accumulator is a VMEM
                    scratch with no ``BlockSpec`` (no second buffer) of
                    a power of two of rows (the widest field's, at most
                    ``FIELD_PIECE_ROWS``: 67.1 MB), zeroed at a phase's
                    first grid step and copied to HBM once at its last;
                    with ``rel = (h >> 7) - first`` (``first`` the row
                    of the model vector the range starts in) a pair
                    adds its row's residual at lane ``h & 127`` of row
                    ``rel & (rows - 1)`` where ``rel >> log2(rows)`` is
                    the phase's piece and 0.0 where it is not, so a
                    phase visits all of its field's pairs and a range
                    of two pieces costs two visits a pair. The ranges
                    are cut from the pieces' copies and laid into the
                    sums. No head, no count, no sort: a pair costs the
                    same whatever the skew, and every addend of a step
                    is in the sums. An accumulator of a field's WHOLE
                    range was built first and does not run: at 87.7 and
                    97.2 MB (KDD Cup 2012's user and query ids; 97.1 and
                    106.6 MB asked of a core's 128 MiB, which the
                    chip's compiler grants) the call never came back on
                    one v5e (PR 59, call 1), where 75.2 MB had run
                    (PR 56); a piece is a size that runs. On one v5e at
                    KDD Cup 2012's shape (PR 59's Step 0,
                    ``scripts/step0_indexed.py --field-scatter``, ms a
                    call over 1.5M pairs with the residuals' 768 MB of
                    lanes made in it) a field of two pieces read 11.4 /
                    11.5 ms seeded and 11.0 / 11.1 flat where XLA's
                    form takes 16.2 to 16.3, a field of one piece 6.3
                    to 6.7 (3.4 ns a visit; 4.13 bundles by the static
                    schedule); in the cell the two id fields' sums fell
                    from 29.9 to 19.7 ms a step, the same float32 sums
                    to 4e-6 of float64 in norm (XLA's 3e-6).
          ``xla``   ``zeros.at[idx].add`` over the pairs of the fields
                    that are left (:func:`slot_sums_hbm`; 9.9 ns a pair
                    with XLA's sort, ledger PR 57): the CPU, where it is
                    also the tests' oracle, and a range of more pieces
                    (past 33.5M slots), whose every piece would visit
                    every pair.
``dict``  by value: ``_hashed_rows_kernel`` copies the field's rows of
          the sampled blocks from one sublane of a block's tiles to
          whole vectors of 1024 rows, and for every entry ``d`` of the
          dictionary ``_hashed_value_gather_kernel`` keeps ``w[d]``
          where a row's slot equals ``d``, ``_hashed_value_sums_kernel``
          the row's residual, summed into one float32 vector an entry
          that XLA folds and adds at ``d``. Two vector operations an
          entry and 1024 rows for the margins, three for the sums, no
          address and no chain; every (row, field) occurrence still
          counts once, in float32. A table whose ``meta`` states no
          dictionary, or none short enough, runs the ``addr`` loops
          over every field, operation for operation as before there
          was a choice.

``DICT_MAX_VALUES`` is where the two cross on one v5e at the
benchmark's shape (PR 33's Step 0, ``scripts/step0_hashed_fields.py``):
by address a field costs both passes 2.31 ms a step of 458 752 rows
(5.0 ns a (row, field) pair; 39 fields against 18), by value 0.583 us an
entry (1.30 ns an entry and 1024 rows; a field of 1024, 2048, 4096
entries), equal at 3960 entries; the constant stands 4% under that. A
block shorter than 1024 rows fills part of a vector, and the bound
falls in proportion.

Interpreted on the CPU the kernels run the same loads, masks, compares
and adds in the same order.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

from tpu_distalg.ops.pallas_api import pl, pltpu
from tpu_distalg.telemetry import events as tevents
from tpu_distalg.telemetry import names

LANES = 128
SUBLANES = 8
CHUNK_ROWS = 256       # rows a grid step takes: fields_held x 256 indices
#                        in SMEM (40 x 256 at 39 fields, 16 x 256 at 11)
LOOP_ROWS = 2          # rows a trip of a by-address loop writes out at
#                        least, and the rows of it that are traced
TRIP_PAIRS = 32        # (row, field) pairs a trip where fewer fields leave
#                        room for more rows: 64 read 2 to 5% under 32 on
#                        the chip and cost every run twice the lowering
GATHER_SUMS = 4        # partial sums a row's loads are added into
SCATTER_ACCS = 4       # accumulators the scatter takes in turn, at most
ACC_VMEM_BYTES = 64 << 20   # ... and what they and their second buffers
#                             may take of VMEM: 4 up to 2**21 slots, 2 at
#                             2**22
VMEM_BITS = 22         # a table of 16 MB and its accumulators fit VMEM
FIELD_PIECE_ROWS = 1 << 17   # rows of 128 lanes of the ONE accumulator the
#                              scatter of the fields past that keeps in
#                              VMEM: 67.1 MB, a size that runs on a v5e
#                              core (the module docstring says what did
#                              not); a power of two, so that a row's place
#                              in its piece is a mask away
FIELD_MAX_PIECES = 2   # pieces a field's range may go in: every piece
#                        visits all of the field's pairs
FIELD_RUN = 4          # rows of that accumulator loaded before any of them
#                        is stored
ZERO_ROWS = 32         # rows of it a trip of the loop that zeroes it covers
HBM_TRIP = 16          # rows whose copies from a table in HBM a loop trip
#                        starts, and a wait lands
MIN_BITS = 10          # one (8, 128) tile of slots
DICT_MAX_VALUES = 3800  # a field of so many slots or fewer is read by
#                         value where its rows fill whole vectors (the
#                         module docstring says where it comes from)
VALUE_GROUP = 8        # dictionary entries a grid step compares
VALUE_ROWS = SUBLANES * LANES   # rows one vector of a field holds
VALUE_TILE_ROWS = 1 << 19   # rows of one field a grid step keeps in VMEM
NO_SLOT = -1           # pads a dictionary to whole groups: no row holds it
NO_ROW = -2            # pads the sampled blocks to whole tiles


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def pass_form(hash_bits: int, block_rows: int,
              field_sizes: tuple = ()) -> str:
    """How a table's two passes run. Hashed (no ``field_sizes``):
    ``'vmem'`` where the table and the scatter's accumulators stay in
    VMEM and a block's rows are whole lanes, else ``'xla'``. Indexed:
    ``'fields'`` (each field the form :func:`field_form` gives its
    range) where a block's rows are whole lanes, else ``'xla'``."""
    if block_rows % LANES:
        return "xla"
    if field_sizes:
        return "fields"
    return "vmem" if MIN_BITS <= hash_bits <= VMEM_BITS else "xla"


def field_form(n_values: int, block_rows: int,
               range_slots: int | None = None) -> str:
    """How one field of a block is read: ``'dict'`` (every row compared
    with each of the ``n_values`` slots the field can hold: the length
    of its stated dictionary, 0 where none is stated), ``'addr'`` (each
    row's slot chased by address in a table in VMEM) or ``'hbm'`` (the
    same in a table that stays in HBM). By value costs ``n_values``
    times the vectors a block's rows fill, by address the block's rows:
    they cross at ``DICT_MAX_VALUES`` values where the rows fill whole
    vectors, and lower in proportion where a block is shorter than a
    vector. ``range_slots`` is the range an indexed table gives the
    field alone (a hashed table's fields share one table, whose
    residency is :func:`pass_form`'s): past ``2 ** VMEM_BITS`` slots no
    VMEM holds it with the scatter's accumulators."""
    if block_rows % LANES == 0 and n_values >= 1:
        fill = block_rows / _round_up(block_rows, VALUE_ROWS)
        if n_values <= DICT_MAX_VALUES * fill:
            return "dict"
    if range_slots is not None and range_slots > 1 << VMEM_BITS:
        return "hbm"
    return "addr"


def field_scatter_form(range_slots: int, on_tpu: bool) -> str:
    """How the sums of one ``'hbm'`` field (:func:`field_form`) are
    added up: ``'vmem'`` (``_hashed_field_scatter_kernel``: an
    accumulator in VMEM that holds the field's range a piece of
    ``FIELD_PIECE_ROWS`` rows at a time) on a TPU where the range goes
    in ``FIELD_MAX_PIECES`` pieces or fewer, else ``'xla'``
    (:func:`slot_sums_hbm`: the CPU, where it is also the tests'
    oracle; a range of more pieces, whose every piece would visit
    every pair). The field's gather is ``_hashed_hbm_gather_kernel``
    either way."""
    return "vmem" if on_tpu and field_pieces(range_slots) \
        <= FIELD_MAX_PIECES else "xla"


def _field_rows(range_slots: int) -> int:
    """Rows of 128 lanes of the model vector that a field's range
    touches, wherever in a row it starts."""
    return -(-range_slots // LANES) + 1


def field_pieces(range_slots: int) -> int:
    """Pieces of ``FIELD_PIECE_ROWS`` rows a field's range goes in."""
    return -(-_field_rows(range_slots) // FIELD_PIECE_ROWS)


@dataclasses.dataclass(frozen=True)
class AddrGroup:
    """The by-address fields of an indexed table that one call of the
    two kernels serves: their ranges ``spans`` of the model vector,
    copied end to end into one table of ``n_slots`` (whole ``(8, 128)``
    tiles); field ``fields[k]``'s slot ``h`` lies at ``h - bases[k]``
    there."""

    fields: tuple
    spans: tuple
    bases: tuple
    n_slots: int


def addr_groups(fields, offsets) -> tuple:
    """The by-address ``fields`` in order, a new group wherever the
    next range would take the group's table past ``2 ** VMEM_BITS``."""
    groups, cur, held = [], [], 0
    for f in list(fields) + [None]:
        size = 0 if f is None else offsets[f + 1] - offsets[f]
        if cur and (f is None or held + size > 1 << VMEM_BITS):
            at, bases = 0, []
            for g in cur:
                bases.append(offsets[g] - at)
                at += offsets[g + 1] - offsets[g]
            groups.append(AddrGroup(
                fields=tuple(cur), bases=tuple(bases),
                spans=tuple((offsets[g], offsets[g + 1]) for g in cur),
                n_slots=_round_up(at, SUBLANES * LANES)))
            cur, held = [], 0
        if f is not None:
            cur.append(f)
            held += size
    return tuple(groups)


@dataclasses.dataclass(frozen=True, eq=False)
class FieldPlan:
    """Which fields are read by value, and their dictionaries laid out
    for the by-value passes: one after another, each padded with
    ``NO_SLOT`` to whole groups of ``VALUE_GROUP`` entries."""

    addr_fields: tuple
    dict_fields: tuple
    entries: np.ndarray        # int32[n_groups * VALUE_GROUP]
    group_field: np.ndarray    # int32[n_groups]: index into dict_fields
    # an indexed table's: the by-address fields' groups (``None``: one
    # hashed table serves ``addr_fields``) and the fields left in HBM
    addr_groups: tuple | None = None
    hbm_fields: tuple = ()

    @property
    def n_values(self) -> int:
        return int(np.count_nonzero(self.entries != NO_SLOT))


def field_plan(geom: "HashedGeometry", dictionaries) -> FieldPlan | None:
    """The plan for a table whose loader states ``dictionaries`` (for
    each of the ``nnz`` fields every slot it can hold, or ``None``).
    A hashed table: ``None`` where every field is read by address (no
    dictionary stated, none short enough, or the passes in their
    ``xla`` form). An indexed table in its ``fields`` form always has
    a plan: every field's form from its range and its dictionary."""
    form = geom.pass_form
    if form == "xla" or (dictionaries is None and form == "vmem"):
        return None
    if dictionaries is None:
        dictionaries = (None,) * geom.nnz
    if len(dictionaries) != geom.nnz:
        raise ValueError(f"{len(dictionaries)} dictionaries for "
                         f"{geom.nnz} fields")
    sizes = geom.field_sizes or (None,) * geom.nnz
    forms = [field_form(0 if d is None else len(d), geom.block_rows, n)
             for d, n in zip(dictionaries, sizes)]
    dict_fields = tuple(f for f, v in enumerate(forms) if v == "dict")
    addr_fields = tuple(f for f, v in enumerate(forms) if v == "addr")
    if form == "fields":
        more = dict(addr_groups=addr_groups(addr_fields, geom.offsets),
                    hbm_fields=tuple(f for f, v in enumerate(forms)
                                     if v == "hbm"))
    elif not dict_fields:
        return None
    else:
        more = {}
    entries, group_field = [], []
    for n, f in enumerate(dict_fields):
        d = np.unique(np.asarray(dictionaries[f], np.int32))
        if d[0] < 0 or d[-1] >= geom.n_slots:
            raise ValueError(f"field {f}'s dictionary leaves the table")
        groups = -(-len(d) // VALUE_GROUP)
        entries.append(np.full((groups * VALUE_GROUP,), NO_SLOT, np.int32))
        entries[-1][:len(d)] = d
        group_field += [n] * groups
    return FieldPlan(
        addr_fields=addr_fields, dict_fields=dict_fields,
        entries=np.concatenate(entries + [np.zeros((0,), np.int32)]),
        group_field=np.asarray(group_field, np.int32), **more)


@dataclasses.dataclass(frozen=True)
class HashedGeometry:
    nnz: int               # fields a row
    hash_bits: int         # 0: an indexed table
    block_rows: int
    field_sizes: tuple = ()   # indexed: the values of each field, whose
    #                           ranges lie end to end in the table

    def __post_init__(self):
        if self.field_sizes:
            if self.hash_bits or len(self.field_sizes) != self.nnz \
                    or min(self.field_sizes) < 1 \
                    or sum(self.field_sizes) >= 1 << 31:
                raise ValueError(
                    f"indexed rows: {len(self.field_sizes)} field sizes "
                    f"for nnz {self.nnz} (each >= 1, {sum(self.field_sizes)}"
                    f" slots in all must fit int32) and no hash_bits "
                    f"({self.hash_bits})")
        elif self.nnz < 1 or not 1 <= self.hash_bits <= 30:
            raise ValueError(
                f"hashed rows: nnz {self.nnz} must be >= 1 and hash_bits "
                f"{self.hash_bits} in [1, 30] (int32 slots)")

    @property
    def row_format(self) -> str:
        return "indexed" if self.field_sizes else "hashed"

    @property
    def offsets(self) -> tuple:
        """Indexed: where each field's range starts, and the table's
        end."""
        return tuple(itertools.accumulate(self.field_sizes, initial=0))

    @property
    def n_slots(self) -> int:
        return sum(self.field_sizes) if self.field_sizes \
            else 1 << self.hash_bits

    @property
    def w_len(self) -> int:
        """The model vector: the table, the bias, zeros; whole rows of
        128 lanes, so that a table in HBM is the vector itself."""
        return _round_up(self.n_slots + 1, LANES) if self.field_sizes \
            else self.n_slots + LANES

    @property
    def fields_held(self) -> int:
        return _round_up(self.nnz + 1, SUBLANES)

    @property
    def row_bytes(self) -> int:
        return 4 * self.fields_held

    @property
    def pass_form(self) -> str:
        return pass_form(self.hash_bits, self.block_rows,
                         self.field_sizes)

    @property
    def chunk_rows(self) -> int:
        return min(CHUNK_ROWS, self.block_rows)

    @property
    def scatter_accs(self) -> int:
        """Accumulators of the scatter: ``SCATTER_ACCS``, fewer where
        so many tables and their second buffers would not fit."""
        return _scatter_accs(self.n_slots)


def _scatter_accs(n_slots: int) -> int:
    return max(1, min(SCATTER_ACCS, ACC_VMEM_BYTES // (2 * 4 * n_slots)))


def _check(X, geom: HashedGeometry):
    if X.ndim != 3 or X.shape[1:] != (geom.fields_held, geom.block_rows) \
            or X.dtype != jnp.int32:
        raise ValueError(
            f"hashed table {X.shape} {X.dtype} is not int32 blocks of "
            f"{(geom.fields_held, geom.block_rows)}")


def labels(X, ids, geom: HashedGeometry):
    """``f32[n_sampled, block_rows]``: the 0/1 labels of blocks ``ids``."""
    return X[ids, geom.nnz, :].astype(jnp.float32)


# ---- XLA forms ---------------------------------------------------------

def margins_xla(X, w, ids, geom: HashedGeometry):
    idx = X[ids][:, :geom.nnz, :]
    return jnp.sum(w[:geom.n_slots][idx], axis=1) + w[geom.n_slots]


def slot_sums_xla(X, r, ids, geom: HashedGeometry):
    idx = X[ids][:, :geom.nnz, :]
    g = jnp.zeros((geom.n_slots,), jnp.float32).at[idx].add(
        jnp.broadcast_to(r[:, None, :], idx.shape))
    tail = jnp.zeros((geom.w_len - geom.n_slots,), jnp.float32).at[0].set(
        jnp.sum(r))
    return jnp.concatenate([g, tail])


def _field_slots(X, ids, fields):
    """``int32[n_sampled, len(fields), block_rows]``: static slices of
    the sampled blocks (a gather over (block, field) pairs is the form
    XLA once placed in VMEM and halted the core with: PR 33)."""
    blocks = X[ids]
    return jnp.stack([blocks[:, f, :] for f in fields], axis=1)


def margins_hbm_xla(X, w, ids, fields):
    """:func:`margins_hbm` as XLA's gather over the model vector (Step
    0's yardstick and the tests'; the ``xla`` pass form gathers every
    field so)."""
    with jax.named_scope(names.SSGD_TABLE_HBM):
        return jnp.sum(w[_field_slots(X, ids, fields)], axis=1)


def _hashed_hbm_gather_kernel(ids_ref, idx_ref, tab_ref, out_ref, land_ref,
                              sem, *, fields: tuple, trip: int, rows: int):
    """One chunk of one sampled block with the table in HBM: pass 1
    starts a DMA a (row, field) pair, the table's row ``h >> 7`` into
    landing row ``n * chunk + i``; pass 2 waits a trip's copies at a
    time (the semaphore counts what has arrived); pass 3 is the
    by-address gather's: lane ``h & 127`` of each landed row kept, a
    row's fields added up."""
    del ids_ref                         # the index maps read it
    cr = idx_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def fetch(t, carry):
        first = pl.multiple_of(t * trip, trip)
        for u in range(trip):
            for n, j in enumerate(fields):
                h = idx_ref[j, first + u]
                pltpu.make_async_copy(
                    tab_ref.at[pl.ds(h >> 7, 1), :],
                    land_ref.at[pl.ds(n * cr + first + u, 1), :],
                    sem).start()
        return carry

    jax.lax.fori_loop(0, cr // trip, fetch, 0)

    def land(t, carry):
        pltpu.make_async_copy(
            tab_ref.at[pl.ds(0, trip * len(fields)), :],
            land_ref.at[pl.ds(0, trip * len(fields)), :], sem).wait()
        return carry

    jax.lax.fori_loop(0, cr // trip, land, 0)

    def one(i, u):
        acc = None
        for n, j in enumerate(fields):
            h = idx_ref[j, i]
            got = jnp.where(lane == (h & (LANES - 1)),
                            land_ref[pl.ds(n * cr + i, 1), :], 0.0)
            acc = got if acc is None else acc + got
        out_ref[pl.ds(i, 1), :] = acc

    _each_row(cr, rows, one)


def margins_hbm(X, w, ids, geom: HashedGeometry, fields, *,
                interpret: bool = False, rows: int | None = None):
    """The share of the margins of an indexed table's ``fields`` whose
    ranges stay in HBM (no bias): the model vector read where it lies,
    as rows of 128 lanes, a row a DMA. ``rows``: the third pass's rows
    a trip, the by-address rule's unless given."""
    cr = geom.chunk_rows
    trip = HBM_TRIP if cr % HBM_TRIP == 0 else 1
    fields = tuple(fields)
    kernel = _addr_call(_hashed_hbm_gather_kernel, geom, fields, rows,
                        trip=trip)
    with jax.named_scope(names.SSGD_TABLE_HBM):
        parts = pl.pallas_call(
            kernel,
            name="_hashed_hbm_gather_kernel",
            grid_spec=_grid_spec(
                ids, geom, [pl.BlockSpec(memory_space=pl.ANY)],
                pl.BlockSpec((None, cr, LANES),
                             lambda s, c, ids: (s, c, 0)),
                scratch_shapes=[
                    pltpu.VMEM((len(fields) * cr, LANES), jnp.float32),
                    pltpu.SemaphoreType.DMA(())]),
            out_shape=jax.ShapeDtypeStruct(
                (ids.shape[0], geom.block_rows, LANES), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                # every slot is a row of the table, as XLA's form is
                # promised
                disable_bounds_checks=True),
            interpret=interpret,
        )(ids, X, w.reshape(geom.w_len // LANES, LANES))
        return jnp.sum(parts, axis=-1)


def slot_sums_hbm(X, r, ids, geom: HashedGeometry, fields):
    """``f32[w_len]`` with those fields' per-slot sums: XLA's
    scatter-add into a zeroed model vector."""
    with jax.named_scope(names.SSGD_TABLE_HBM):
        idx = _field_slots(X, ids, fields)
        return jnp.zeros((geom.w_len,), jnp.float32).at[idx].add(
            jnp.broadcast_to(r[:, None, :], idx.shape))


# ---- Mosaic forms ------------------------------------------------------

def _each_row(n_rows: int, rows: int, one) -> None:
    """``one(i, u)`` for every row ``i`` of a chunk of ``n_rows``,
    ``rows`` of them a trip of the loop and one basic block. What is
    traced is ``LOOP_ROWS`` rows (``u`` counts them: the scatter's
    accumulators go by it); a longer trip is that piece written out again
    at lowering (a loop unrolled whole, its index a constant in every
    copy), so it costs the lowering of its pairs and no tracing."""
    per = math.gcd(rows, LOOP_ROWS)

    def trip(t, carry):
        first = pl.multiple_of(t * rows, rows)

        def piece(v, carry):
            for u in range(per):
                one(first + (v * per + u), u)
            return carry

        if rows == per:
            return piece(0, carry)
        return jax.lax.fori_loop(0, rows // per, piece, carry, unroll=True)

    jax.lax.fori_loop(0, n_rows // rows, trip, 0)


def _hashed_gather_kernel(ids_ref, idx_ref, w_ref, out_ref, *,
                          fields: tuple, bases: tuple, rows: int):
    """One chunk of one sampled block: ``out[i, :]`` holds the weights
    of row ``i``'s ``fields`` in the lanes their slots have in the
    table, slots of one lane added up; the sum over lanes is their
    share of the margin. ``GATHER_SUMS`` partial vectors keep the adds
    of one row off one chain. ``bases`` re-base each field's slots on
    the table handed in (zeros: the table is the whole of it)."""
    del ids_ref                         # the index maps read it
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def one(i, u):
        sums = [None] * min(GATHER_SUMS, len(fields))
        for n, j in enumerate(fields):
            h = idx_ref[j, i] - bases[n] if bases[n] else idx_ref[j, i]
            got = jnp.where(lane == (h & (LANES - 1)),
                            w_ref[pl.ds(h >> 7, 1), :], 0.0)
            k = n % len(sums)
            sums[k] = got if sums[k] is None else sums[k] + got
        while len(sums) > 1:            # pairwise, a fixed order
            sums = [a + b for a, b in zip(sums[::2], sums[1::2])] \
                + sums[len(sums) & ~1:]
        out_ref[pl.ds(i, 1), :] = sums[0]

    _each_row(idx_ref.shape[1], rows, one)


def _hashed_scatter_kernel(ids_ref, idx_ref, rb_ref, *accs,
                           fields: tuple, bases: tuple, rows: int):
    """One chunk of one sampled block into the accumulators ``(n_slots /
    128, 128)``, which stay in VMEM over the whole grid: row ``i``'s
    residual (``rb[i, :]``, the same in every lane) is added at the
    slot of each of its ``fields``, in lane ``h & 127`` of row ``h >> 7``.
    Neighbouring accesses go to different accumulators, each its own
    allocation."""
    del ids_ref
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _zero():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    def one(i, u):
        r = rb_ref[pl.ds(i, 1), :]
        for n, j in enumerate(fields):
            h = idx_ref[j, i] - bases[n] if bases[n] else idx_ref[j, i]
            acc = accs[(u * len(fields) + n) % len(accs)]
            acc[pl.ds(h >> 7, 1), :] += jnp.where(
                lane == (h & (LANES - 1)), r, 0.0)

    _each_row(idx_ref.shape[1], rows, one)


def _grid_spec(ids, geom: HashedGeometry, more_in, out_specs,
               scratch_shapes=()):
    cr = geom.chunk_rows
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ids.shape[0], geom.block_rows // cr),
        in_specs=[pl.BlockSpec((None, geom.fields_held, cr),
                               lambda s, c, ids: (ids[s], 0, c),
                               memory_space=pltpu.SMEM)] + more_in,
        out_specs=out_specs, scratch_shapes=scratch_shapes)


def _loop_rows(geom: HashedGeometry, n_fields: int,
               rows: int | None = None) -> int:
    """Rows a trip of a by-address loop writes out over ``n_fields``
    fields: the most, doubling from ``LOOP_ROWS`` while the chunk
    divides, that keep a trip at ``TRIP_PAIRS`` (row, field) pairs or
    fewer."""
    if rows is None:
        rows = LOOP_ROWS
        while 2 * rows * n_fields <= TRIP_PAIRS \
                and geom.chunk_rows % (2 * rows) == 0:
            rows *= 2
    return rows if geom.chunk_rows % rows == 0 else 1


def _addr_call(kernel, geom: HashedGeometry, fields: tuple,
               rows: int | None, **more):
    """A by-address kernel bound to its call's fields and the rows a
    trip they give; what the call runs is said once, when it is traced
    (``tda report``: ``by-address call``)."""
    rows = _loop_rows(geom, len(fields), rows)
    tevents.emit("ssgd:addr_call", kernel=kernel.__name__,
                 fields=list(fields), rows=rows, pairs=rows * len(fields),
                 smem_rows=geom.fields_held)
    return functools.partial(kernel, fields=fields, rows=rows, **more)


def _vmem_limit(geom: HashedGeometry, tables: int, n_slots: int) -> int:
    return (tables * 4 * n_slots
            + 8 * geom.chunk_rows * LANES * 4 + (8 << 20))


def _fields(geom: HashedGeometry, fields) -> tuple:
    return tuple(range(geom.nnz)) if fields is None else tuple(fields)


def _served(geom: HashedGeometry, fields, group: AddrGroup | None):
    """``(fields, bases, slots)`` of one call of a by-address kernel:
    a hashed table's ``fields`` in the table itself, or an indexed
    table's ``group`` in the group's."""
    if group is not None:
        return group.fields, group.bases, group.n_slots
    fields = _fields(geom, fields)
    return fields, (0,) * len(fields), geom.n_slots


def _group_table(w, group: AddrGroup):
    """The group's ranges of the model vector end to end, zeros to
    whole tiles: a copy of at most 16 MB a step."""
    at = sum(hi - lo for lo, hi in group.spans)
    return jnp.concatenate(
        [w[lo:hi] for lo, hi in group.spans]
        + [jnp.zeros((group.n_slots - at,), w.dtype)])


def margins_vmem(X, w, ids, geom: HashedGeometry, *,
                 interpret: bool = False, rows: int | None = None,
                 fields=None, group: AddrGroup | None = None):
    """The margins by address over ``fields`` (all of them unless
    given), the bias added; or a ``group``'s share of them, no bias."""
    cr = geom.chunk_rows
    fields, bases, n_slots = _served(geom, fields, group)
    table = (w[:n_slots] if group is None else _group_table(w, group)
             ).reshape(n_slots // LANES, LANES)
    kernel = _addr_call(_hashed_gather_kernel, geom, fields, rows,
                        bases=bases)
    parts = pl.pallas_call(
        kernel,
        name="_hashed_gather_kernel",
        grid_spec=_grid_spec(
            ids, geom,
            [pl.BlockSpec(table.shape, lambda s, c, ids: (0, 0))],
            pl.BlockSpec((None, cr, LANES), lambda s, c, ids: (s, c, 0))),
        out_shape=jax.ShapeDtypeStruct(
            (ids.shape[0], geom.block_rows, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(geom, 2, n_slots)),
        interpret=interpret,
    )(ids, X, table)
    m = jnp.sum(parts, axis=-1)
    return m + w[geom.n_slots] if group is None else m


def slot_sums_vmem(X, r, ids, geom: HashedGeometry, *,
                   interpret: bool = False, n_acc: int | None = None,
                   rows: int | None = None, fields=None,
                   group: AddrGroup | None = None):
    """The per-slot sums by address over ``fields`` (all of them unless
    given), the residuals' sum where the bias is; or ``f32[group.
    n_slots]``, a ``group``'s sums in its own table's order."""
    cr = geom.chunk_rows
    fields, bases, n_slots = _served(geom, fields, group)
    n_acc = _scatter_accs(n_slots) if n_acc is None else n_acc
    shape = (n_slots // LANES, LANES)
    rb = jnp.broadcast_to(r[:, :, None], r.shape + (LANES,))
    kernel = _addr_call(_hashed_scatter_kernel, geom, fields, rows,
                        bases=bases)
    accs = pl.pallas_call(
        kernel,
        name="_hashed_scatter_kernel",
        grid_spec=_grid_spec(
            ids, geom,
            [pl.BlockSpec((None, cr, LANES),
                          lambda s, c, ids: (s, c, 0))],
            [pl.BlockSpec(shape, lambda s, c, ids: (0, 0))] * n_acc),
        out_shape=[jax.ShapeDtypeStruct(shape, jnp.float32)] * n_acc,
        compiler_params=pltpu.CompilerParams(
            # the accumulators live across the whole grid
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(geom, 2 * n_acc, n_slots)),
        interpret=interpret,
    )(ids, X, rb)
    g = functools.reduce(jnp.add, accs).reshape(n_slots)
    if group is not None:
        return g
    tail = jnp.zeros((LANES,), jnp.float32).at[0].set(jnp.sum(r))
    return jnp.concatenate([g, tail])


def _hashed_field_scatter_kernel(ids_ref, phase_ref, idx_ref, rb_ref,
                                 out_hbm, acc_ref, sem):
    """One chunk of one sampled block in one *phase* (the grid's first
    axis; ``phase_ref`` holds a field, the model vector's row its range
    starts in, and a piece for each): the field's pairs whose row lies
    in the piece add their row's residual into the ONE accumulator,
    which stays in VMEM over the phase: at lane ``h & 127`` of row
    ``rel & (rows - 1)`` where ``rel = (h >> 7) - first`` and the piece
    is ``rel >> log2(rows)``; a pair of another piece adds 0.0 there.
    Zeroed at a phase's first step, copied to ``out[phase]`` at its last.

    A load waits 7 bundles for the last store to its allocation, and
    there is one allocation: a run of ``FIELD_RUN`` rows is loaded
    before any of it is stored, so a later pair of the run that lands
    in an earlier one's row takes that one's addend with it and the
    last store to a row holds every addend of the run once
    (``pallas_pairs._pairs_scatter_kernel``'s device). The addends are
    summed before the loads land, so a link of the chain is four
    loads, one add, four stores and the wait. The chunk is one basic
    block (the run traced once and written out again at lowering, its
    index a constant in every copy): a pair's place in SMEM and its
    residual's in VMEM are constants, where a loop over the chunk's
    rows paid five scalar operations a pair for the SMEM tile address
    alone (3.9 bundles a pair by the static schedule against 5.4; the
    two scalar slots were the bound)."""
    del ids_ref
    rows = acc_ref.shape[0]              # a power of two
    k = pl.program_id(0)
    field, first, piece = (phase_ref[3 * k], phase_ref[3 * k + 1],
                           phase_ref[3 * k + 2])
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _zero():
        def some(i, _):
            at = pl.ds(pl.multiple_of(i * ZERO_ROWS, ZERO_ROWS), ZERO_ROWS)
            acc_ref[at, :] = jnp.zeros((ZERO_ROWS, LANES), jnp.float32)
            return 0

        jax.lax.fori_loop(0, rows // ZERO_ROWS, some, 0)

    def splat(x):
        return jax.lax.broadcast_in_dim(jnp.int32(x), (1, LANES), ())

    zero = jnp.zeros((1, LANES), jnp.float32)
    low, seven, last = splat(LANES - 1), splat(7), splat(rows - 1)
    bits, vfirst, vpiece = splat(rows.bit_length() - 1), splat(first), \
        splat(piece)

    def run(v, carry):
        at = [v * FIELD_RUN + u for u in range(FIELD_RUN)]
        hs = [idx_ref[field, i] for i in at]
        # the lane, the piece and the compares of the run's rows on the
        # vector side: a VALU slot is free where a scalar slot is not
        # (``jax.lax`` by name: the run is lowered a chunk's 64 times
        # on every run of the program, and a ``jnp.where`` is a nested
        # call each time)
        vhs = [splat(h) for h in hs]
        rels = [jax.lax.shift_right_arithmetic(vh, seven) - vfirst
                for vh in vhs]
        adds = [jax.lax.select(
            (lane == jax.lax.bitwise_and(vh, low))
            & (jax.lax.shift_right_arithmetic(rel, bits) == vpiece),
            rb_ref[pl.ds(i, 1), :], zero)
            for vh, rel, i in zip(vhs, rels, at)]
        vrows = [jax.lax.bitwise_and(rel, last) for rel in rels]
        sums = list(adds)
        for n in range(1, FIELD_RUN):
            for m in range(n):
                sums[n] = sums[n] + jax.lax.select(
                    vrows[m] == vrows[n], adds[m], zero)
        where = [((h >> 7) - first) & (rows - 1) for h in hs]
        olds = [acc_ref[pl.ds(row, 1), :] for row in where]
        for row, old, add in zip(where, olds, sums):
            acc_ref[pl.ds(row, 1), :] = old + add
        return carry

    jax.lax.fori_loop(0, idx_ref.shape[1] // FIELD_RUN, run, 0,
                      unroll=True)

    @pl.when((pl.program_id(1) == pl.num_programs(1) - 1)
             & (pl.program_id(2) == pl.num_programs(2) - 1))
    def _store():
        copy = pltpu.make_async_copy(acc_ref, out_hbm.at[k], sem)
        copy.start()
        copy.wait()


def field_phases(geom: HashedGeometry, fields) -> tuple:
    """``(acc_rows, phases)`` of one call of the field scatter over
    ``fields``: the accumulator's rows (a power of two, the widest
    field's or ``FIELD_PIECE_ROWS``) and a ``(field, first row, piece)``
    for every piece of every field's range."""
    off = geom.offsets
    widest = max(_field_rows(off[f + 1] - off[f]) for f in fields)
    rows = min(FIELD_PIECE_ROWS,
               max(ZERO_ROWS, 1 << (widest - 1).bit_length()))
    return rows, tuple(
        (f, off[f] >> 7, p) for f in fields
        for p in range(-(-_field_rows(off[f + 1] - off[f]) // rows)))


def slot_sums_fields(X, r, ids, geom: HashedGeometry, fields, *,
                     interpret: bool = False) -> list:
    """``[f32[range] for each of fields]``: the per-slot sums of
    indexed fields whose ranges are past ``2 ** VMEM_BITS``, each in
    its range's own order: ONE call whose grid runs every piece of
    every field's range over the sampled blocks in turn, one
    accumulator in VMEM (a scratch with no ``BlockSpec``, hence no
    second buffer; a copy out a piece). No head, no count, no sort: a
    pair costs the same whatever the skew."""
    cr = geom.chunk_rows
    fields = tuple(fields)
    if cr % FIELD_RUN:
        raise ValueError(f"a chunk of {cr} rows is not whole runs of "
                         f"{FIELD_RUN}")
    rows, phases = field_phases(geom, fields)
    off = geom.offsets
    pieces = {f: sum(ph[0] == f for ph in phases) for f in fields}
    # the accumulator, a chunk's residuals and room
    vmem_bytes = _vmem_limit(geom, 1, rows * LANES)
    for f in fields:
        tevents.emit("ssgd:field_scatter",
                     kernel="_hashed_field_scatter_kernel", form="vmem",
                     field=f, range_slots=off[f + 1] - off[f],
                     pieces=pieces[f], vmem_bytes=vmem_bytes)
    rb = jnp.broadcast_to(r[:, :, None], r.shape + (LANES,))
    with jax.named_scope(names.SSGD_TABLE_HBM):
        acc = pl.pallas_call(
            _hashed_field_scatter_kernel,
            name="_hashed_field_scatter_kernel",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,          # ids, phases
                grid=(len(phases), ids.shape[0], geom.block_rows // cr),
                in_specs=[
                    pl.BlockSpec((None, geom.fields_held, cr),
                                 lambda k, s, c, ids, ph: (ids[s], 0, c),
                                 memory_space=pltpu.SMEM),
                    pl.BlockSpec((None, cr, LANES),
                                 lambda k, s, c, ids, ph: (s, c, 0))],
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.float32),
                                pltpu.SemaphoreType.DMA(())]),
            out_shape=jax.ShapeDtypeStruct((len(phases), rows, LANES),
                                           jnp.float32),
            compiler_params=pltpu.CompilerParams(
                # the accumulator lives across a phase
                dimension_semantics=("arbitrary",) * 3,
                vmem_limit_bytes=vmem_bytes),
            interpret=interpret,
        )(ids, jnp.asarray(phases, jnp.int32).reshape(-1), X, rb)
        out, at = [], 0
        for f in fields:
            lo, hi = off[f], off[f + 1]
            out.append(acc[at:at + pieces[f]].reshape(-1)[
                lo % LANES:lo % LANES + hi - lo])
            at += pieces[f]
        return out


# ---- by value: the fields whose dictionaries the loader states -----------

def _hashed_value_gather_kernel(gf_ref, d_ref, wd_ref, x_ref, out_ref, *,
                                group: int):
    """One group of one field's dictionary against a tile of sampled
    blocks: ``x[b]`` is the field's slots of block ``b``'s rows as whole
    vectors ``(block_rows / 128, 128)``; a row takes the weight of the
    entry its slot equals (at most one of a group: a dictionary's
    entries differ), added into ``out``, which stays in VMEM over the
    tile's groups."""
    del gf_ref                          # the index maps read it
    g = pl.program_id(1)
    ds = [d_ref[g * group + k] for k in range(group)]
    ws = [wd_ref[g * group + k] for k in range(group)]

    @pl.when(g == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    def one(b, carry):
        x = x_ref[b]
        got = jnp.zeros(x.shape, jnp.float32)
        for d, wv in zip(ds, ws):
            got = jnp.where(x == d, wv, got)
        out_ref[b] += got
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0], one, 0)


def _hashed_value_sums_kernel(gf_ref, d_ref, r_ref, x_ref, out_ref, *,
                              group: int, fold: int):
    """The same group and tile for the sums: ``out[k]`` is one float32
    vector of entry ``k``'s residuals, a row's residual kept where its
    slot equals the entry, the tile's blocks and a block's vectors added
    up; XLA folds the vector."""
    del gf_ref
    g = pl.program_id(1)
    ds = [d_ref[g * group + k] for k in range(group)]

    def one(b, accs):
        x, r = x_ref[b], r_ref[b]
        out = []
        for acc, d in zip(accs, ds):
            kept = jnp.where(x == d, r, 0.0)
            if kept.shape[0] != fold:
                kept = jnp.sum(kept.reshape(-1, fold, LANES), axis=0)
            out.append(acc + kept)
        return tuple(out)

    accs = jax.lax.fori_loop(
        0, x_ref.shape[0], one,
        tuple(jnp.zeros((fold, LANES), jnp.float32) for _ in ds))
    for k, acc in enumerate(accs):
        out_ref[k] = acc


def _value_tiles(n_sampled: int, geom: HashedGeometry):
    """``(blocks a tile, tiles)``: a grid step keeps one field's slots
    of a tile of sampled blocks in VMEM."""
    tb = max(1, min(n_sampled, VALUE_TILE_ROWS // geom.block_rows))
    return tb, -(-n_sampled // tb)


def _hashed_rows_kernel(ids_ref, x_ref, out_ref, *, fields: tuple):
    """One sampled block: the rows of each of ``fields``, one sublane
    of the block's tiles, written out as whole vectors."""
    del ids_ref
    for n, f in enumerate(fields):
        out_ref[n] = x_ref[pl.ds(f, 1), :].reshape(out_ref.shape[1:])


def dict_rows(X, ids, geom: HashedGeometry, plan: FieldPlan, *,
              interpret: bool = False):
    """``int32[n_tiles * tb, dict fields, block_rows / 128, 128]``: the
    sampled blocks' slots of the by-value fields, a field's rows of a
    block brought from one sublane of the block's tiles to whole
    vectors (a copy; ``X`` stays as it is). Blocks past the sampled
    ones hold ``NO_ROW``."""
    ns, rows = ids.shape[0], geom.block_rows // LANES
    tb, n_tiles = _value_tiles(ns, geom)
    n_dict = len(plan.dict_fields)
    x = pl.pallas_call(
        functools.partial(_hashed_rows_kernel, fields=plan.dict_fields),
        name="_hashed_rows_kernel",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(ns,),
            in_specs=[pl.BlockSpec(
                (None, geom.fields_held, geom.block_rows),
                lambda s, ids: (ids[s], 0, 0))],
            out_specs=pl.BlockSpec((None, n_dict, rows, LANES),
                                   lambda s, ids: (s, 0, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((ns, n_dict, rows, LANES),
                                       jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * (geom.fields_held + n_dict)
            * geom.block_rows * 4 + (8 << 20)),
        interpret=interpret,
    )(ids, X)
    pad = tb * n_tiles - ns
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0), (0, 0)),
                    constant_values=NO_ROW)
    return x


def _value_grid_spec(n_sampled, geom: HashedGeometry, plan: FieldPlan,
                     other_in, out_specs):
    """The grid (tiles of sampled blocks, groups of entries); ``x``,
    the tile's slots of the group's field, is the last input."""
    tb, n_tiles = _value_tiles(n_sampled, geom)
    rows = geom.block_rows // LANES
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, len(plan.group_field)),
        in_specs=other_in + [
            pl.BlockSpec((tb, None, rows, LANES),
                         lambda t, g, gf, d: (t, gf[g], 0, 0))],
        out_specs=out_specs)


def _value_params(n_sampled, geom: HashedGeometry):
    tb, _ = _value_tiles(n_sampled, geom)
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        # a field's slots, the residuals or margins beside them, each
        # with its second buffer
        vmem_limit_bytes=6 * tb * geom.block_rows * 4 + (8 << 20))


def margins_dict(X, w, ids, geom: HashedGeometry, plan: FieldPlan, *,
                 interpret: bool = False):
    """``f32[n_sampled, block_rows]``: the by-value fields' share of
    every row's margin."""
    ns, rows = ids.shape[0], geom.block_rows // LANES
    tb, n_tiles = _value_tiles(ns, geom)
    # an entry's weight, once a step (a padding entry's is never kept)
    wd = w[jnp.maximum(jnp.asarray(plan.entries), 0)]
    out = pl.pallas_call(
        functools.partial(_hashed_value_gather_kernel, group=VALUE_GROUP),
        name="_hashed_value_gather_kernel",
        grid_spec=_value_grid_spec(
            ns, geom, plan, [pl.BlockSpec(memory_space=pltpu.SMEM)],
            pl.BlockSpec((tb, rows, LANES),
                         lambda t, g, gf, d: (t, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((tb * n_tiles, rows, LANES),
                                       jnp.float32),
        compiler_params=_value_params(ns, geom),
        interpret=interpret,
    )(jnp.asarray(plan.group_field), jnp.asarray(plan.entries), wd,
      dict_rows(X, ids, geom, plan, interpret=interpret))
    return out[:ns].reshape(ns, geom.block_rows)


def slot_sums_dict(X, r, ids, geom: HashedGeometry, plan: FieldPlan, *,
                   interpret: bool = False):
    """``(slots int32[n_values], sums f32[n_values])``: for every entry
    of every by-value field's dictionary the residuals of the rows that
    hold it; two fields' entries of one slot are two elements."""
    ns, rows = ids.shape[0], geom.block_rows // LANES
    tb, n_tiles = _value_tiles(ns, geom)
    fold = SUBLANES if rows % SUBLANES == 0 else rows
    rv = r.reshape(ns, rows, LANES)
    if tb * n_tiles > ns:
        rv = jnp.pad(rv, ((0, tb * n_tiles - ns), (0, 0), (0, 0)))
    n_entries = len(plan.entries)
    parts = pl.pallas_call(
        functools.partial(_hashed_value_sums_kernel, group=VALUE_GROUP,
                          fold=fold),
        name="_hashed_value_sums_kernel",
        grid_spec=_value_grid_spec(
            ns, geom, plan,
            [pl.BlockSpec((tb, rows, LANES),
                          lambda t, g, gf, d: (t, 0, 0))],
            pl.BlockSpec((None, VALUE_GROUP, fold, LANES),
                         lambda t, g, gf, d: (t, g, 0, 0))),
        out_shape=jax.ShapeDtypeStruct(
            (n_tiles, n_entries, fold, LANES), jnp.float32),
        compiler_params=_value_params(ns, geom),
        interpret=interpret,
    )(jnp.asarray(plan.group_field), jnp.asarray(plan.entries), rv,
      dict_rows(X, ids, geom, plan, interpret=interpret))
    real = np.flatnonzero(plan.entries != NO_SLOT)
    return plan.entries[real], jnp.sum(parts, axis=(0, 2, 3))[real]


# ---- what the trainer calls ---------------------------------------------

def margins(X, w, ids, geom: HashedGeometry, *,
            plan: FieldPlan | None = None, interpret: bool = False):
    """``f32[n_sampled, block_rows]``: every row's margin under ``w``,
    the rows of the blocks ``ids`` of ``X int32[n_blocks, fields_held,
    block_rows]``, padding rows included. ``plan`` (:func:`field_plan`)
    says which fields are read by value."""
    _check(X, geom)
    if geom.pass_form == "xla":
        return margins_xla(X, w, ids, geom)
    if plan is None:
        return margins_vmem(X, w, ids, geom, interpret=interpret)
    if plan.addr_groups is not None:
        return _margins_fields(X, w, ids, geom, plan, interpret)
    m = margins_dict(X, w, ids, geom, plan, interpret=interpret)
    if not plan.addr_fields:
        return m + w[geom.n_slots]
    return m + margins_vmem(X, w, ids, geom, interpret=interpret,
                            fields=plan.addr_fields)


def _margins_fields(X, w, ids, geom, plan, interpret):
    """An indexed table's margins: the bias and each form's share."""
    m = w[geom.n_slots]
    if plan.dict_fields:
        m = m + margins_dict(X, w, ids, geom, plan, interpret=interpret)
    for group in plan.addr_groups:
        m = m + margins_vmem(X, w, ids, geom, interpret=interpret,
                             group=group)
    if plan.hbm_fields:
        m = m + margins_hbm(X, w, ids, geom, plan.hbm_fields,
                            interpret=interpret)
    return jnp.broadcast_to(m, (ids.shape[0], geom.block_rows))


def slot_sums(X, r, ids, geom: HashedGeometry, *,
              plan: FieldPlan | None = None, interpret: bool = False):
    """``f32[w_len]``: ``r f32[n_sampled, block_rows]`` added at every
    (row, field) occurrence's slot, once each; the sum of ``r`` where
    the bias is."""
    _check(X, geom)
    if geom.pass_form == "xla":
        return slot_sums_xla(X, r, ids, geom)
    if plan is None:
        return slot_sums_vmem(X, r, ids, geom, interpret=interpret)
    if plan.addr_groups is not None:
        return _slot_sums_fields(X, r, ids, geom, plan, interpret)
    slots, sums = slot_sums_dict(X, r, ids, geom, plan,
                                 interpret=interpret)
    if plan.addr_fields:
        g = slot_sums_vmem(X, r, ids, geom, interpret=interpret,
                           fields=plan.addr_fields)
    else:
        g = jnp.zeros((geom.w_len,), jnp.float32).at[geom.n_slots].set(
            jnp.sum(r))
    return g.at[slots].add(sums)


def _slot_sums_fields(X, r, ids, geom, plan, interpret):
    """An indexed table's sums: the fields in HBM whose ranges go in
    too many pieces scattered into the zeroed vector by XLA, the
    others' sums (``_hashed_field_scatter_kernel``, one call) laid at
    their ranges, each group's table added back range by range, the
    by-value entries added at their slots, the residuals' sum where the
    bias is."""
    off = geom.offsets
    # a mesh's platform reaches here as the trainer's ``interpret``
    forms = {f: field_scatter_form(off[f + 1] - off[f], not interpret)
             for f in plan.hbm_fields}
    by_xla = tuple(f for f in plan.hbm_fields if forms[f] == "xla")
    in_vmem = tuple(f for f in plan.hbm_fields if forms[f] == "vmem")
    for f in by_xla:
        tevents.emit("ssgd:field_scatter", kernel="xla scatter-add",
                     form="xla", field=f, range_slots=off[f + 1] - off[f],
                     pieces=0, vmem_bytes=0)
    if by_xla:
        g = slot_sums_hbm(X, r, ids, geom, by_xla)
    else:
        g = jnp.zeros((geom.w_len,), jnp.float32)
    if in_vmem:
        sums = slot_sums_fields(X, r, ids, geom, in_vmem,
                                interpret=interpret)
        with jax.named_scope(names.SSGD_TABLE_HBM):
            for f, at in zip(in_vmem, sums):
                g = g.at[off[f]:off[f + 1]].set(at)
    for group in plan.addr_groups:
        acc = slot_sums_vmem(X, r, ids, geom, interpret=interpret,
                             group=group)
        for (lo, hi), base in zip(group.spans, group.bases):
            g = g.at[lo:hi].add(acc[lo - base:hi - base])
    if plan.dict_fields:
        slots, sums = slot_sums_dict(X, r, ids, geom, plan,
                                     interpret=interpret)
        g = g.at[slots].add(sums)
    return g.at[geom.n_slots].set(jnp.sum(r))
