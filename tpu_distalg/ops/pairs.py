"""The two passes of logistic regression over rows of (feature, value)
pairs: ``models/ssgd.py``'s third row format, ``pairs``.

A row is a list of pairs of its own length, 0 pairs or tens of
thousands: an int32 feature id (exact: the model's own index, nothing
hashed) and a float32 value. It is what a LIBSVM file or a Spark
``SparseVector`` holds, and the general case of the two formats of
``ops/pallas_hashed.py`` (a fixed number of ids a row, every value 1).

**Which rows a block holds** is a rule over the rows' lengths alone
(:func:`pack_rows`), so that a reference can name a block's rows
without this module: a block has ``block_slots`` pair slots and
``block_rows`` row slots; a row takes its pairs rounded up to whole
vectors of ``LANES`` = 128 slots; a block is the longest run of
consecutive rows, from where the last block ended, of at most
``block_rows`` rows whose vectors fit. No row is split and none is
cut; a row longer than a block is refused.

**How a block is held**: ``int32[held_rows, 128]``, rows of 128 lanes,

  rows ``[0, V)``            the pairs' feature ids, a row of the
                             table after another, each from a lane 0
                             (``V = block_slots / 128`` vectors)
  rows ``[V, 2 V)``          the pairs' float32 values, bit for bit
                             (``lax.bitcast_convert_type``)
  rows ``[2 V, 2 V + V/128)``  for each vector the block-local number
                             of the row it belongs to
  the next ``block_rows/128``  for each row slot its label: 1, 0, or
                             -1 where the slot holds no row
  the next ``block_rows/128``  for each row slot its count of pairs

and zeros up to a whole sublane tile. A slot past a row's end holds id
0 and value 0.0: it reads a weight, multiplies it by nothing and adds
nothing. Because a vector belongs to one row, the sum over a row's
pairs is 128 lanes added up (dense) and then a sum over the row's
vectors by the vector's row number: a segmented sum over ``V`` numbers
a block and not over ``block_slots``. The way back is the same: a
row's residual is looked up a vector and multiplies 128 values. 8 B a
slot, 4 B a vector and 8 B a row slot: 8.05 B a slot held at 2^18
slots and 512 row slots a block.

The model is one float32 vector ``w``: ``n_features`` weights, the
bias at ``[n_features]``, zeros to whole rows of 128 lanes (the indexed
format's vector, the same indices as a plain reference's). A step over
the sampled blocks ``ids``:

  margins:    m_i  = b + sum_{p in row i} v_p * w[h_p]
  slot sums:  g[s] = sum_i r_i * sum_{p in row i, h_p = s} v_p
              g[n_features] = sum_i r_i

with ``r`` the residual times validity; a feature that occurs twice in
a row counts twice. Each pass has two forms that give the same numbers
up to the order of float32 additions, and :func:`pass_form` picks one
from what it can observe, where the loader laid the table (``on_tpu``,
from its mesh) and the vector's size (no option, no environment
variable):

``vmem``  on a TPU whose VMEM holds the model vector whole beside a
          chunk's buffers (``vmem_bytes`` against ``VMEM_BUDGET_BYTES``:
          webspam's 16.6M weights are 66.4 MB of a v5e core's 128 MiB):
          ``ops/pallas_pairs.py``'s two Mosaic kernels, a call a pass
          over the sampled blocks it is given. The gather copies ``w``
          into VMEM once and serves every pair by address (row
          ``h >> 7``, lane ``h & 127`` by a mask, times the value); the
          scatter adds ``v * r_row`` the same way into ONE accumulator
          of the vector's shape and copies the sums out once. No head,
          no count, no sort: every pair costs the same whatever the
          skew. float32 only.
``xla``   everywhere else (the CPU, where it is also the tests' oracle;
          a vector past the budget, such as kddb's 29.9M features):
          the table stays in HBM, ``w[idx]`` and ``zeros.at[idx].add``
          over every pair under ``tda.ssgd.table_hbm``.

A slot's addends are added one after another in float32 in either form,
so the trainer takes a step's blocks a few at a time and adds the trips'
finished sums (``ssgd_pairs.STEP_BLOCKS``: 4 blocks a trip in the
``xla`` form, whose temporaries are a trip's too, up to 16 in the
``vmem`` form).

The segmented sum and the way back are XLA's in both forms, under
``tda.ssgd.rowsum``; in the ``vmem`` form ``tda.ssgd.table_hbm`` holds
only what XLA still does on the 66 MB vectors in HBM (``w`` brought to
whole tiles, the sums cut back, the bias's slot set; the copies in and
out are the kernels' own DMAs). On one v5e at webspam's shape (52
blocks, 13.63M pair slots of which 12.9M hold a pair, a step):

  ``xla``   the gather 98.3 ms (7.6 ns a pair), the scatter 135.1 (10.4
            ns a pair, XLA's sort of a trip's ids in it), the row sums
            2.2 (ledger, PR 54)
  ``vmem``  the gather 31.7 ms (2.45 ns a pair), the scatter 50.0 (3.86
            ns a pair), the row sums 2.1, in 4 trips of 13 blocks
            (builder's chip run, PR 56; one call a pass a step read
            30.8 and 48.5 and three times the error in the weights);
            the same at a flat draw (``scripts/step0_pairs.py``: 2.31 /
            2.30 ns a slot the gather, 3.62 / 3.65 the scatter, seeded /
            flat). By the static schedule 3.3 and 5.2 bundles a pair.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from tpu_distalg.telemetry import events as tevents
from tpu_distalg.telemetry import names

LANES = 128
SUBLANES = 8
BLOCK_ROWS = 512      # row slots a block, unless a spec states another
NO_ROW = -1            # the label of a row slot that holds no row
VMEM_BUDGET_BYTES = 80 << 20   # what a by-address pass may ask of a v5e
#                                core's VMEM (128 MiB by its compiler's own
#                                refusal): the largest asked that has run on
#                                the chip is 83.6 MB (PR 56, Step 0)
VMEM_ROOM_BYTES = 8 << 20      # ... of which beside the one copy of the
#                                model vector: a chunk's buffers and what
#                                Mosaic keeps for itself


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def vmem_bytes(w_len: int) -> int:
    """What a by-address pass over a model vector of ``w_len`` float32
    asks of VMEM: one copy of the vector (the gather's table, the
    scatter's sums) and the room beside it."""
    return 4 * w_len + VMEM_ROOM_BYTES


def pass_form(w_len: int, on_tpu: bool) -> str:
    """How a table's two passes run: ``'vmem'`` (``ops/pallas_pairs.py``)
    on a TPU whose VMEM holds the model vector whole, else ``'xla'``
    (the CPU; a vector past the budget, such as kddb's 29.9M features'
    119.6 MB)."""
    fits = vmem_bytes(w_len) <= VMEM_BUDGET_BYTES
    return "vmem" if on_tpu and fits else "xla"


@dataclasses.dataclass(frozen=True)
class PairsGeometry:
    """A table of ragged rows from its sizes alone."""

    n_features: int
    block_slots: int       # pair slots a block: whole vectors of 128
    block_rows: int        # row slots a block
    n_blocks: int          # blocks of the whole table, every shard's
    on_tpu: bool = False   # where the table lies: the loader's mesh

    row_format = "pairs"

    def __post_init__(self):
        if self.block_slots < LANES or self.block_slots % LANES:
            raise ValueError(
                f"block_slots={self.block_slots}: whole vectors of "
                f"{LANES} pair slots")
        if self.block_rows < 1:
            raise ValueError(f"block_rows={self.block_rows}")
        if not 1 <= self.n_features < (1 << 31) - LANES:
            raise ValueError(
                f"{self.n_features} features do not fit int32 indices")

    @property
    def vectors(self) -> int:
        return self.block_slots // LANES

    @property
    def vector_rows(self) -> int:
        return -(-self.vectors // LANES)

    @property
    def label_rows(self) -> int:
        return -(-self.block_rows // LANES)

    @property
    def held_rows(self) -> int:
        return _round_up(self.at_lengths + self.label_rows, SUBLANES)

    @property
    def at_vrow(self) -> int:
        return 2 * self.vectors

    @property
    def at_labels(self) -> int:
        return self.at_vrow + self.vector_rows

    @property
    def at_lengths(self) -> int:
        return self.at_labels + self.label_rows

    @property
    def block_bytes(self) -> int:
        return 4 * LANES * self.held_rows

    @property
    def n_slots(self) -> int:
        return self.n_features

    @property
    def w_len(self) -> int:
        return _round_up(self.n_features + 1, LANES)

    @property
    def pass_form(self) -> str:
        return pass_form(self.w_len, self.on_tpu)


# ---- which rows a block holds (host) ------------------------------------

def pack_rows(lengths, block_slots: int, block_rows: int) -> np.ndarray:
    """The blocks of a run of rows from their lengths: ``starts``,
    ``int64[n_blocks + 1]``, block ``b`` holding rows ``starts[b] ..
    starts[b + 1]``. The rule is the module docstring's."""
    lengths = np.asarray(lengths, np.int64)
    vectors = -(-lengths // LANES)
    room = block_slots // LANES
    if lengths.size and int(vectors.max()) > room:
        raise ValueError(
            f"a row of {int(lengths.max())} pairs does not fit a block "
            f"of {block_slots} slots: no row is split or cut")
    ends = np.concatenate([[0], np.cumsum(vectors)])
    starts, at, n = [0], 0, lengths.size
    while at < n:
        fit = int(np.searchsorted(ends, ends[at] + room, side="right")) - 1
        at = min(fit, at + block_rows)
        starts.append(at)
    return np.asarray(starts, np.int64)


def blocks_from_csr(indptr, ids, values, labels, geom: PairsGeometry
                    ) -> np.ndarray:
    """A host table from CSR arrays (a LIBSVM file's rows as SciPy
    would hold them): ``int32[geom.n_blocks, held_rows, 128]``, blocks
    past the last row empty. The device loader writes the same layout;
    this is the tests' way in, and a file loader's."""
    indptr = np.asarray(indptr, np.int64)
    ids = np.asarray(ids, np.int32)
    values = np.asarray(values, np.float32)
    labels = np.asarray(labels)
    if ids.size and (ids.min() < 0 or ids.max() >= geom.n_features):
        raise ValueError("a feature id outside [0, n_features)")
    lengths = np.diff(indptr)
    starts = pack_rows(lengths, geom.block_slots, geom.block_rows)
    if len(starts) - 1 > geom.n_blocks:
        raise ValueError(f"{len(starts) - 1} blocks needed, "
                         f"{geom.n_blocks} held")
    V, nb = geom.vectors, geom.n_blocks
    idx = np.zeros((nb, V * LANES), np.int32)
    val = np.zeros((nb, V * LANES), np.float32)
    vrow = np.zeros((nb, geom.vector_rows * LANES), np.int32)
    lab = np.full((nb, geom.label_rows * LANES), NO_ROW, np.int32)
    cnt = np.zeros((nb, geom.label_rows * LANES), np.int32)
    for b in range(len(starts) - 1):
        at = 0
        for k, i in enumerate(range(starts[b], starts[b + 1])):
            n = int(lengths[i])
            idx[b, at:at + n] = ids[indptr[i]:indptr[i + 1]]
            val[b, at:at + n] = values[indptr[i]:indptr[i + 1]]
            took = -(-n // LANES)
            vrow[b, at // LANES:at // LANES + took] = k
            lab[b, k] = int(labels[i] > 0)
            cnt[b, k] = n
            at += took * LANES
    X = np.zeros((nb, geom.held_rows, LANES), np.int32)
    X[:, :V] = idx.reshape(nb, V, LANES)
    X[:, V:2 * V] = val.view(np.int32).reshape(nb, V, LANES)
    X[:, geom.at_vrow:geom.at_labels] = vrow.reshape(nb, -1, LANES)
    X[:, geom.at_labels:geom.at_lengths] = lab.reshape(nb, -1, LANES)
    X[:, geom.at_lengths:geom.at_lengths + geom.label_rows] = \
        cnt.reshape(nb, -1, LANES)
    return X


def csr_from_blocks(X, geom: PairsGeometry):
    """The way back (tests, a float64 check): ``(indptr, ids, values,
    labels, block_of_row)`` of the rows a host copy of ``X`` holds, in
    block order."""
    X = np.asarray(X)
    V, R = geom.vectors, geom.block_rows
    indptr, ids, values, labels, block_of = [0], [], [], [], []
    for b in range(X.shape[0]):
        lab = X[b, geom.at_labels:geom.at_lengths].reshape(-1)[:R]
        cnt = X[b, geom.at_lengths:
                geom.at_lengths + geom.label_rows].reshape(-1)[:R]
        idx = X[b, :V].reshape(-1)
        val = X[b, V:2 * V].view(np.float32).reshape(-1)
        at = 0
        for k in np.flatnonzero(lab != NO_ROW):
            n = int(cnt[k])
            ids.append(idx[at:at + n])
            values.append(val[at:at + n])
            indptr.append(indptr[-1] + n)
            labels.append(int(lab[k]))
            block_of.append(b)
            at += -(-n // LANES) * LANES
    cat = (lambda xs, dt: np.concatenate(xs).astype(dt) if xs
           else np.zeros((0,), dt))
    return (np.asarray(indptr, np.int64), cat(ids, np.int32),
            cat(values, np.float32), np.asarray(labels, np.int32),
            np.asarray(block_of, np.int32))


# ---- a sampled block's parts (device) ------------------------------------

def _check(X, geom: PairsGeometry):
    if X.ndim != 3 or X.shape[1:] != (geom.held_rows, LANES) \
            or X.dtype != jnp.int32:
        raise ValueError(
            f"pairs table {X.shape} {X.dtype} is not int32 blocks of "
            f"{(geom.held_rows, LANES)}")


def _rows(X, ids, lo: int, hi: int):
    """Rows ``[lo, hi)`` of the sampled blocks, a dynamic slice each
    (the ``xla`` form's ids and values, a trip's few blocks): a block is
    one run of 2 MB, and XLA's gather of such rows (``X[ids]``) copies
    the whole table in three slices first (seen in a chipless compile
    at webspam's shape: 3.8 GB of temporaries a trip)."""
    return jnp.concatenate([
        jax.lax.dynamic_slice(X, (ids[i], lo, 0), (1, hi - lo, LANES))
        for i in range(ids.shape[0])])


def _values(X, ids, geom: PairsGeometry):
    V = geom.vectors
    return jax.lax.bitcast_convert_type(_rows(X, ids, V, 2 * V),
                                        jnp.float32)


class _Tails:
    """What the sampled blocks hold behind their pairs (12 KB of a 2 MB
    block at webspam's shape): the vectors' row numbers, the row slots'
    labels and pair counts. One loop of dynamic slices, a trip a block:
    traced once however many blocks a call is given."""

    def __init__(self, X, ids, geom: PairsGeometry):
        _check(X, geom)
        lo, n = geom.at_vrow, geom.held_rows - geom.at_vrow
        held = jax.lax.map(
            lambda i: jax.lax.dynamic_slice(
                X, (i, lo, 0), (1, n, LANES))[0], ids)
        self._geom, self._held = geom, held

    def _part(self, at: int, rows: int, n: int):
        """The first ``n`` words of ``rows`` held rows from ``at``."""
        lo = at - self._geom.at_vrow
        return self._held[:, lo:lo + rows].reshape(
            self._held.shape[0], -1)[:, :n]

    @property
    def vrow(self):
        g = self._geom
        return self._part(g.at_vrow, g.vector_rows, g.vectors)

    @property
    def labels(self):
        g = self._geom
        return self._part(g.at_labels, g.label_rows, g.block_rows)

    @property
    def counts(self):
        g = self._geom
        return self._part(g.at_lengths, g.label_rows, g.block_rows)

    @property
    def used(self):
        """``int32[n_sampled]``: the vectors of each block that belong
        to a row (its rows' pairs in whole vectors); the block's vectors
        from there on hold no pair."""
        return jnp.sum(-(-self.counts // LANES), axis=1).astype(jnp.int32)


def pair_counts(X, ids, geom: PairsGeometry):
    """``int32[n_sampled, block_rows]``: the pairs of each row slot of
    the sampled blocks (0 where a slot holds no row)."""
    return _Tails(X, ids, geom).counts


def used_vectors(X, ids, geom: PairsGeometry):
    """``int32[n_sampled]``: :attr:`_Tails.used` of the sampled blocks."""
    return _Tails(X, ids, geom).used


def labels(X, ids, geom: PairsGeometry):
    """``(y float32 (n, block_rows), valid float32 (n, block_rows))``:
    the 0/1 labels of the sampled blocks' row slots and which of them
    hold a row."""
    lab = _Tails(X, ids, geom).labels
    valid = lab != NO_ROW
    return (jnp.where(valid, lab, 0).astype(jnp.float32),
            valid.astype(jnp.float32))


def _row_sums(vec, vrow, geom: PairsGeometry):
    """A row slot's sum over its vectors' ``vec`` (``(n, V)``)."""
    n = vrow.shape[0]
    seg = (jnp.arange(n, dtype=jnp.int32)[:, None] * geom.block_rows
           + vrow).reshape(-1)
    return jax.ops.segment_sum(
        vec.reshape(-1), seg, num_segments=n * geom.block_rows
    ).reshape(n, geom.block_rows)


def _say_xla(which: str, ids) -> None:
    """The ``xla`` form's word for ``ssgd:pairs_pass``, said when the
    pass is traced (``pallas_pairs._call`` says the ``vmem`` form's)."""
    tevents.emit("ssgd:pairs_pass", kernel=f"xla {which}", form="xla",
                 vmem_bytes=0, trip_pairs=0, blocks=int(ids.shape[0]))


def _vmem(geom: PairsGeometry, dtype) -> bool:
    """Whether a pass takes its ``vmem`` form, which is float32's."""
    if geom.pass_form != "vmem":
        return False
    if dtype != jnp.float32:
        raise ValueError(f"the vmem form of a pairs pass is float32's, "
                         f"not {jnp.dtype(dtype).name}'s")
    return True


def margins(X, w, ids, geom: PairsGeometry, *, dtype=jnp.float32):
    """``f32[n_sampled, block_rows]``: the margins of the sampled
    blocks' row slots (a slot without a row reads the bias). ``dtype``
    other than float32 is the tests' control of the ``xla`` form:
    values, weights and the gathered products in it."""
    tails = _Tails(X, ids, geom)
    if _vmem(geom, dtype):
        from tpu_distalg.ops import pallas_pairs

        prod = pallas_pairs.vector_products(X, w, ids, tails.used, geom)
    else:
        _say_xla("gather", ids)
        with jax.named_scope(names.SSGD_TABLE_HBM):
            got = w.astype(dtype)[_rows(X, ids, 0, geom.vectors)]
        prod = (got * _values(X, ids, geom).astype(dtype)).astype(
            jnp.float32)
    with jax.named_scope(names.SSGD_ROWSUM):
        m = _row_sums(jnp.sum(prod, axis=-1), tails.vrow, geom)
    return m + w[geom.n_slots]


def slot_sums(X, r, ids, geom: PairsGeometry, *, dtype=jnp.float32):
    """``f32[w_len]``: the per-slot sums of ``r`` (``(n_sampled,
    block_rows)``, zero where a slot holds no row) times the pairs'
    values, and ``sum(r)`` at the bias."""
    tails = _Tails(X, ids, geom)
    with jax.named_scope(names.SSGD_ROWSUM):
        back = jnp.take_along_axis(r, tails.vrow, axis=1)
    if _vmem(geom, dtype):
        from tpu_distalg.ops import pallas_pairs

        g = pallas_pairs.slot_sums(X, back, ids, tails.used, geom)
    else:
        _say_xla("scatter", ids)
        add = _values(X, ids, geom).astype(dtype) \
            * back[..., None].astype(dtype)
        with jax.named_scope(names.SSGD_TABLE_HBM):
            g = jnp.zeros((geom.w_len,), dtype).at[
                _rows(X, ids, 0, geom.vectors)].add(add)
    bias = jnp.sum(r)
    # the vector of sums where it lies in HBM: the bias's slot
    with jax.named_scope(names.SSGD_TABLE_HBM):
        return g.astype(jnp.float32).at[geom.n_slots].set(bias)
