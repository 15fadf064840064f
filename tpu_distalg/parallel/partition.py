"""Partition-rule engine — one rule table for every model's placement,
and device-side resharding between layouts.

Two halves (ROADMAP item 5):

  * **Rule engine.** Sharding decisions used to be hand-rolled per
    model (the ssgd tp matvec, ALS model-axis padding, the
    feature-sharded variants, every SSP carry re-put). Here a model's
    placement is a :class:`RuleTable` — an ordered list of
    ``(regex, PartitionSpec)`` rules matched against *named* pytree
    leaves (paths joined with ``/``) — from which the engine generates
    the shard/place/gather functions. Scalars are always replicated;
    a leaf no rule matches is a HARD error (a silently-replicated new
    leaf is exactly the drift this engine exists to kill). Every
    model registers its table here, so a 2-D ``data × model`` mesh is
    a ``--mesh-shape`` config, not a code path, and lint rule TDA080
    (``analysis/partition.py``) keeps raw ``NamedSharding``/
    ``device_put`` placement out of ``models/`` and ``serve/``.

  * **Device-side resharding.** ``reshard(tree, src, dst, mesh)``
    lowers a src→dst layout change to a device-side collective
    program in the spirit of "Memory-efficient array redistribution
    through portable collective communication" (arXiv:2112.01075):
    the (src, dst) spec pair is classified into the collective class
    it requires (all-gather / slice / all-to-all / gather+slice
    decomposition), the wire bytes are accounted per the comms
    layer's ring model (``CommSync.stats`` convention), and the
    transfer itself runs as one compiled identity program with
    ``out_shardings`` — the XLA partitioner emits exactly those
    collectives, ON DEVICE. The host gather + re-put round trip this
    replaces (``np.asarray`` every leaf, ``device_put`` it back —
    what checkpoint-restore placement, SSP resume-renegotiation and
    ``tda serve`` artifact load all paid) moves ``2·B`` bytes per
    leaf over PCIe and serializes on the host; the device program
    moves only the accounted wire bytes over the interconnect.
    ``reshard.*`` telemetry counters feed a ``tda report`` line.

Rule-table grammar::

    RuleTable("als_train", (
        (r"^R$", P(DATA_AXIS, None)),   # ratings: row-sharded
        (r"^U$", P(DATA_AXIS, None)),   # user factors: row-sharded
        (r"^V$", P(MODEL_AXIS, None)),  # item factors: model axis
    ))

Leaves are named by their pytree path (dict keys / dataclass fields /
sequence indices, ``/``-joined — Optax-style nested state matches with
rules like ``r"inner/.*/mu$"``); the FIRST matching rule wins; scalars
(0-d or size-1 leaves) replicate without consulting the table.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any

import numpy as np

from tpu_distalg.parallel.mesh import DATA_AXIS, MODEL_AXIS


class PartitionRuleError(ValueError):
    """A leaf no rule matches, an unknown table name, or a reshard
    between tables that do not cover the same leaves."""


def _spec_tuple(spec) -> tuple:
    """PartitionSpec → a comparable tuple (PartitionSpec equality is
    fine, but a canonical tuple also strips trailing Nones so
    ``P('data')`` and ``P('data', None)`` compare equal on the same
    array rank — they place identically)."""
    t = tuple(spec)
    while t and t[-1] is None:
        t = t[:-1]
    return t


def specs_equal(a, b) -> bool:
    return _spec_tuple(a) == _spec_tuple(b)


@dataclasses.dataclass(frozen=True)
class RuleTable:
    """An ordered ``(regex, PartitionSpec)`` rule list naming one
    model's placement. ``spec_for`` is the whole matching contract:
    scalars replicate, first ``re.search`` match wins, no match is a
    hard :class:`PartitionRuleError`."""

    name: str
    rules: tuple  # ((pattern_str, PartitionSpec), ...)

    def spec_for(self, leaf_name: str, shape: tuple):
        from jax.sharding import PartitionSpec as P

        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return P()  # never partition scalar values
        for pat, spec in self.rules:
            if re.search(pat, leaf_name) is not None:
                return spec
        raise PartitionRuleError(
            f"no partition rule in table {self.name!r} matches leaf "
            f"{leaf_name!r} (shape {tuple(shape)}) — every non-scalar "
            f"leaf must be named by a rule; add one to the table in "
            f"parallel/partition.py (rules: "
            f"{[p for p, _ in self.rules]})")


# --------------------------------------------------------------- registry

_REGISTRY: dict[str, RuleTable] = {}


def register(table: RuleTable, *, replace: bool = False) -> RuleTable:
    if not replace and table.name in _REGISTRY:
        raise PartitionRuleError(
            f"rule table {table.name!r} is already registered")
    _REGISTRY[table.name] = table
    return table


def table(name: str | RuleTable) -> RuleTable:
    if isinstance(name, RuleTable):
        return name
    try:
        return _REGISTRY[name]
    except KeyError:
        raise PartitionRuleError(
            f"unknown rule table {name!r} (registered: "
            f"{sorted(_REGISTRY)})") from None


def registered() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ----------------------------------------------------- row ownership


def row_bounds(n_rows: int, n_shards: int) -> np.ndarray:
    """The ``(n_shards + 1,)`` int64 cut points of ``np.array_split``'s
    contract over ``n_rows`` leading-dim rows: the first ``n_rows %
    n_shards`` shards own ``n_rows // n_shards + 1`` rows, the rest
    ``n_rows // n_shards`` — uneven splits are first-class (a shard
    count that does not divide the model axis is the NORMAL case).
    Shard ``i`` owns ``[bounds[i], bounds[i + 1])``."""
    if n_shards < 1:
        raise PartitionRuleError(
            f"n_shards must be >= 1, got {n_shards}")
    base, extra = divmod(int(n_rows), int(n_shards))
    sizes = np.full((int(n_shards),), base, np.int64)
    sizes[:extra] += 1
    return np.concatenate(
        [np.zeros((1,), np.int64), np.cumsum(sizes, dtype=np.int64)])


@dataclasses.dataclass(frozen=True)
class LeafOwnership:
    """One leaf's placement across row shards: either row-partitioned
    (``bounds`` holds the cut points) or whole on shard ``owner``."""

    name: str
    shape: tuple
    sharded: bool
    bounds: np.ndarray | None = None
    owner: int = 0

    def range_of(self, shard: int) -> tuple[int, int]:
        """The ``[lo, hi)`` leading-dim row range ``shard`` owns (an
        empty range for a non-owner of a whole leaf)."""
        if self.sharded:
            return int(self.bounds[shard]), int(self.bounds[shard + 1])
        n = int(self.shape[0]) if len(self.shape) else 1
        return (0, n) if shard == self.owner else (0, 0)

    def owner_of(self, rows: np.ndarray) -> np.ndarray:
        """Per-row owning shard ids (int64), vectorized."""
        rows = np.asarray(rows, np.int64)
        if not self.sharded:
            return np.full(rows.shape, self.owner, np.int64)
        return np.searchsorted(self.bounds, rows, side="right") - 1


class RowOwnershipMap:
    """The partition-table-driven row-ownership map — ONE derivation of
    "which shard owns which leading-dim rows of which leaf", shared by
    the PS tier's center sharding (``cluster/ps.split_center``), the
    sharded row store (``cluster/rowstore.py``), and the cluster graph/
    ALS engines that partition their work by it. A leaf whose spec in
    the model's rule table shards ANY dim row-splits on axis 0 with
    ``np.array_split`` arithmetic (:func:`row_bounds` — the historical
    ``ps.split_center`` slicing, now first-class); a replicated-spec or
    scalar leaf lives whole on shard 0. Derived from the SAME
    :class:`RuleTable` that drives the device-side ``shardings()`` —
    one table per model names both placements."""

    def __init__(self, shapes: dict, table_name, n_shards: int):
        if n_shards < 1:
            raise PartitionRuleError(
                f"n_shards must be >= 1, got {n_shards}")
        tbl = table(table_name)
        self.table_name = tbl.name
        self.n_shards = int(n_shards)
        self.leaves: dict[str, LeafOwnership] = {}
        for name, shape in shapes.items():
            shape = tuple(int(d) for d in shape)
            spec = tbl.spec_for(name, shape)
            sharded = any(e is not None for e in tuple(spec))
            if sharded and len(shape) >= 1 and shape[0] >= 1:
                self.leaves[name] = LeafOwnership(
                    name, shape, True,
                    bounds=row_bounds(shape[0], self.n_shards))
            else:
                self.leaves[name] = LeafOwnership(
                    name, shape, False, owner=0)

    @classmethod
    def for_center(cls, center: dict, table_name,
                   n_shards: int) -> "RowOwnershipMap":
        return cls({k: np.asarray(v).shape for k, v in center.items()},
                   table_name, n_shards)

    def __getitem__(self, name: str) -> LeafOwnership:
        try:
            return self.leaves[name]
        except KeyError:
            raise PartitionRuleError(
                f"leaf {name!r} is not in the {self.table_name!r} "
                f"ownership map (known: {sorted(self.leaves)})"
            ) from None

    def split(self, center: dict) -> list[dict]:
        """Per-shard sub-dicts of ``center`` (row slices copied) — the
        exact byte-level output of the historical
        ``ps.split_center``."""
        shards: list[dict] = [{} for _ in range(self.n_shards)]
        for name, leaf in center.items():
            leaf = np.asarray(leaf)
            own = self[name]
            if own.sharded:
                for i in range(self.n_shards):
                    lo, hi = own.range_of(i)
                    shards[i][name] = leaf[lo:hi].copy()
            else:
                shards[own.owner][name] = leaf.copy()
        return shards

    def join(self, shards: list[dict]) -> dict:
        """Inverse of :meth:`split` — concatenate row slices in shard
        order, pass whole leaves through."""
        out: dict = {}
        for name, own in self.leaves.items():
            pieces = [sh[name] for sh in shards if name in sh]
            if not pieces:
                continue
            out[name] = (pieces[0].copy() if len(pieces) == 1
                         else np.concatenate(pieces, axis=0))
        return out


# ---------------------------------------------------------- leaf naming


def _key_str(k) -> str:
    from jax import tree_util as jtu

    if isinstance(k, jtu.DictKey):
        return str(k.key)
    if isinstance(k, jtu.SequenceKey):
        return str(k.idx)
    if isinstance(k, jtu.GetAttrKey):
        return str(k.name)
    if isinstance(k, jtu.FlattenedIndexKey):
        return str(k.key)
    return str(k)


def named_leaves(tree) -> list[tuple[str, Any]]:
    """``[(path_name, leaf), ...]`` — dict keys / attr names / indices
    joined with ``/`` (the name the rule regexes match)."""
    from jax.tree_util import tree_flatten_with_path

    leaves, _ = tree_flatten_with_path(tree)
    return [("/".join(_key_str(k) for k in path) or "leaf", v)
            for path, v in leaves]


def _tree_map_named(fn, tree):
    """Map ``fn(name, leaf)`` over the tree, preserving structure."""
    import jax
    from jax.tree_util import tree_flatten_with_path

    leaves, treedef = tree_flatten_with_path(tree)
    out = [fn("/".join(_key_str(k) for k in path) or "leaf", v)
           for path, v in leaves]
    return jax.tree.unflatten(treedef, out)


# ----------------------------------------------------- generated fns


def match_partition_rules(tbl, tree):
    """Pytree of ``PartitionSpec`` for ``tree`` under table ``tbl`` —
    the SNIPPETS.md [2] shape; supports Flax/Optax-style nested state
    via the path-joined names."""
    t = table(tbl)
    return _tree_map_named(
        lambda name, leaf: t.spec_for(name, np.shape(leaf)), tree)


def shardings(tbl, tree, mesh):
    """Pytree of ``NamedSharding`` for ``tree`` under ``tbl``."""
    from jax.sharding import NamedSharding

    t = table(tbl)
    return _tree_map_named(
        lambda name, leaf: NamedSharding(
            mesh, t.spec_for(name, np.shape(leaf))), tree)


def leaf_sharding(tbl, leaf_name: str, mesh, *, shape=(2, 2)):
    """The ``NamedSharding`` table ``tbl`` assigns leaf ``leaf_name``
    — for call sites that place one bare array (``shape`` only
    matters for the scalar short-circuit; the default is non-scalar).
    """
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, table(tbl).spec_for(leaf_name, shape))


def _stage(x):
    """A ``device_put``-ready leaf WITHOUT committing it anywhere: a
    device array passes through (device_put reshards it), anything
    else becomes a host ndarray. A ``jnp.asarray`` here would eagerly
    commit the FULL leaf to the default device before the re-layout —
    a whole-array device-0 copy the 'one H2D direct to the final
    layout' contract exists to avoid (device_put canonicalizes dtypes
    the same way, so values land identically)."""
    import jax

    return x if isinstance(x, jax.Array) else np.asarray(x)


def put(x, leaf_name: str, tbl, mesh):
    """Place ONE array per its table rule (host→device or device
    re-layout; ``jax.device_put`` resolves either)."""
    import jax

    return jax.device_put(
        _stage(x), leaf_sharding(tbl, leaf_name, mesh,
                                 shape=np.shape(x)))


def place(tree, tbl, mesh):
    """Place every leaf of ``tree`` per its table rule. Host leaves
    take one H2D directly to their FINAL layout (each device receives
    only its shard) — the checkpoint-restore-placement seam."""
    import jax

    shs = shardings(tbl, tree, mesh)
    return jax.tree.map(
        lambda x, s: jax.device_put(_stage(x), s), tree, shs)


def constrain(x, leaf_name: str, tbl, mesh):
    """``lax.with_sharding_constraint`` per the table rule — the
    inside-jit spelling of :func:`put`."""
    from jax import lax

    return lax.with_sharding_constraint(
        x, leaf_sharding(tbl, leaf_name, mesh, shape=np.shape(x)))


def gather(tree):
    """Host copies of every leaf (the np.asarray gather the device
    reshard path exists to avoid — kept for checkpoint WRITES, which
    are host-bound by nature, and as the A/B baseline)."""
    import jax

    return jax.tree.map(lambda x: np.asarray(x), tree)


def ensure(tree, tbl, mesh):
    """Idempotent placement — the hot-seam helper. Per leaf:

      * already a committed device array in the table's layout → passed
        through untouched (zero copies);
      * a device array in ANOTHER layout → device-side re-layout
        (``device_put`` to the target sharding — no host round trip);
      * a host array (a restored checkpoint leaf) → one H2D direct to
        the final layout.

    Replaces the ``np.asarray(x)`` + ``device_put`` round trip the
    segmented runners used to pay EVERY segment on state that was
    already resident and correctly placed."""
    import jax

    shs = shardings(tbl, tree, mesh)

    def one(x, s):
        if isinstance(x, jax.Array) and getattr(x, "sharding", None) \
                is not None and x.sharding == s:
            return x
        return jax.device_put(_stage(x), s)

    return jax.tree.map(one, tree, shs)


# ------------------------------------------------------------- reshard


def _spec_dim_degrees(spec, mesh) -> list[int]:
    """Per-dimension shard degree the spec imposes (1 = that dim is
    not cut)."""
    out = []
    for entry in tuple(spec):
        if entry is None:
            out.append(1)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for ax in axes:
            n *= int(mesh.shape[ax])
        out.append(n)
    return out


def pad_amounts(shape, spec, mesh) -> tuple[int, ...]:
    """Per-dimension tail padding that makes ``shape`` divisible by
    the spec's shard degrees — all zeros when the layout is already
    even (the historical fast path). The uneven case is exactly what
    an elastic cluster shrinking to a worker count that does not
    divide the model axis produces; the padding is inert zeros, the
    ALS model-axis convention."""
    degs = _spec_dim_degrees(spec, mesh)
    return tuple(
        ((-int(dim)) % degs[i]) if i < len(degs) and degs[i] > 1
        else 0
        for i, dim in enumerate(shape))


def spec_shards(spec, mesh) -> int:
    """Number of distinct shards the spec cuts the array into on this
    mesh (product of the named axes' sizes; 1 == replicated)."""
    n = 1
    for entry in tuple(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for ax in axes:
            n *= int(mesh.shape[ax])
    return n


def _canonical_spec(spec, mesh) -> tuple:
    """The spec with size-1 mesh axes dropped — ``P('data','model')``
    on a 4×1 mesh PLACES identically to ``P('data')``, so the plan
    must classify the pair as a no-op, not an all-to-all (review-
    caught: spelling-only differences were accounted as real
    collectives with nonzero wire bytes on model=1 meshes)."""
    out = []
    for entry in tuple(spec):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        axes = tuple(a for a in axes if int(mesh.shape[a]) > 1)
        out.append(None if not axes
                   else (axes if len(axes) > 1 else axes[0]))
    while out and out[-1] is None:
        out.pop()
    return tuple(out)


def _leaf_plan(shape, dtype, src_spec, dst_spec, mesh,
               true_shape=None) -> dict:
    """Classify ONE leaf's src→dst transition into the collective
    class the pair requires and account its per-shard wire bytes
    under the comms layer's ring model (``CommSync.stats``):

      ==============  =======================  ======================
      transition      collective               bytes_wire (per shard)
      ==============  =======================  ======================
      same spec       none                     0
      repl → shard    local slice              0
      shard → repl    ring all-gather          ``B·(n_s−1)/n_s``
      shard → shard,  all-to-all               ``(B/n_s)·(n_s−1)/n_s``
      equal degree
      shard → shard,  all-gather + slice       ``B·(n_s−1)/n_s``
      degree change   (decomposition)
      ==============  =======================  ======================

    ``B`` = the leaf's full byte size. The decomposed degree-change
    row is an upper bound (arXiv:2112.01075 §4 shows tighter programs
    exist for some factorizations); the program actually emitted is
    the XLA partitioner's lowering of the (src, dst) sharding pair —
    always device-side. ``bytes_host_roundtrip`` is what the gather +
    re-put alternative moves over PCIe (full D2H + full H2D).

    UNEVEN dst layouts (a sharded dim the dst degree does not divide)
    go pad-reshard-slice: the leaf is zero-padded up to divisibility
    INSIDE the compiled program, moves at the padded size — which is
    what ``bytes_wire``/``bytes_logical`` account, with the overhead
    itemized as ``bytes_padding`` and the per-dim amounts as ``pad``
    — and a later reshard back (``true_shapes``) slices the padding
    off again. ``true_shape`` (when given) is the logical shape a
    previously-padded input is first sliced back to."""
    true = tuple(true_shape) if true_shape is not None else tuple(shape)
    pads = pad_amounts(true, dst_spec, mesh)
    moved = tuple(t + p for t, p in zip(true, pads))
    itemsize = np.dtype(dtype).itemsize
    nbytes = int(np.prod(moved)) if moved else 1
    nbytes = int(nbytes * itemsize)
    true_bytes = int((int(np.prod(true)) if true else 1) * itemsize)
    n_s = spec_shards(src_spec, mesh)
    n_d = spec_shards(dst_spec, mesh)
    reshaped = tuple(true) != tuple(shape) or any(pads)
    if not reshaped and _canonical_spec(src_spec, mesh) == \
            _canonical_spec(dst_spec, mesh):
        op, wire = "noop", 0.0
    elif n_s == 1:
        op, wire = "slice", 0.0
    elif n_d == 1:
        op, wire = "all_gather", nbytes * (n_s - 1) / n_s
    elif n_s == n_d:
        op, wire = "all_to_all", (nbytes / n_s) * (n_s - 1) / n_s
    else:
        op, wire = "gather_slice", nbytes * (n_s - 1) / n_s
    plan = {"op": op, "bytes_wire": int(round(wire)),
            "bytes_logical": nbytes,
            "bytes_host_roundtrip": 0 if op == "noop" else 2 * nbytes}
    if any(pads):
        plan["pad"] = pads
        plan["bytes_padding"] = nbytes - true_bytes
        plan["padded_shape"] = moved
    if tuple(true) != tuple(shape):
        plan["true_shape"] = tuple(true)
    return plan


def reshard_stats(tree, src_tbl, dst_tbl, mesh, *,
                  true_shapes: dict | None = None) -> dict:
    """The whole tree's reshard plan + byte accounting (host-side,
    static — no device work): per-leaf plans plus totals, including
    ``bytes_padding`` — the inert-zero overhead uneven dst layouts
    pay for divisibility (pad-reshard-slice). ``true_shapes`` maps
    leaf name → pre-pad logical shape for inputs a PREVIOUS uneven
    reshard padded (the slice half of the round trip). Raises
    :class:`PartitionRuleError` when either table fails to name a
    leaf (the tables must COVER the tree to reshard it)."""
    src_t, dst_t = table(src_tbl), table(dst_tbl)
    leaves: dict[str, dict] = {}
    tot_wire = tot_logical = tot_host = tot_pad = n_moved = 0
    for name, leaf in named_leaves(tree):
        shape = np.shape(leaf)
        dtype = getattr(leaf, "dtype", np.float32)
        plan = _leaf_plan(
            shape, dtype,
            src_t.spec_for(name, shape),
            dst_t.spec_for(name, shape), mesh,
            true_shape=(true_shapes or {}).get(name))
        leaves[name] = plan
        tot_wire += plan["bytes_wire"]
        tot_logical += plan["bytes_logical"]
        tot_host += plan["bytes_host_roundtrip"]
        tot_pad += plan.get("bytes_padding", 0)
        n_moved += plan["op"] != "noop"
    return {"leaves": leaves, "bytes_wire": tot_wire,
            "bytes_logical": tot_logical,
            "bytes_host_roundtrip": tot_host,
            "bytes_padding": tot_pad,
            "n_leaves": len(leaves), "n_moved": n_moved,
            "src": src_t.name, "dst": dst_t.name}


def row_block_stats(n_rows: int, block_rows: int, *,
                    n_shards: int = 1, row_bytes: int = 4) -> dict:
    """Out-of-core row-block accounting (pure arithmetic, no mesh):
    how many gathered blocks a ``block_rows`` granularity yields per
    shard, the pad rows divisibility costs, and the per-block wire
    bytes. The autotuner's block-rows chooser joins this against the
    measured copy bandwidth; it is the block-granularity sibling of
    :func:`reshard_stats`'s ``bytes_padding`` accounting."""
    n_rows = max(1, int(n_rows))
    block_rows = max(1, int(block_rows))
    n_shards = max(1, int(n_shards))
    per_shard = -(-n_rows // n_shards)             # ceil
    n_blocks = -(-per_shard // block_rows)
    padded = n_blocks * block_rows * n_shards
    pad_rows = padded - n_rows
    return {"n_blocks": int(n_blocks),
            "rows_per_shard": int(per_shard),
            "padded_rows": int(padded),
            "pad_rows": int(pad_rows),
            "waste_fraction": float(pad_rows) / float(padded),
            "block_bytes": int(block_rows) * int(row_bytes)}


def reshard(tree, src_tbl, dst_tbl, mesh, *, emit: bool = True,
            true_shapes: dict | None = None):
    """Re-lay ``tree`` out from ``src_tbl``'s placement to
    ``dst_tbl``'s, DEVICE-SIDE: one compiled identity program whose
    ``out_shardings`` are the destination table's — the XLA
    partitioner lowers the (src, dst) pair to the all-gather /
    slice / all-to-all program :func:`reshard_stats` accounts, and no
    device-resident leaf byte touches the host.

    The input's ACTUAL layout is not forced into ``src_tbl`` first —
    the compiled program reshards from whatever sharding each leaf
    carries; ``src_tbl`` declares the layout the plan/accounting
    describes, and at every registered seam the caller's tree IS in
    that layout. A host-resident leaf is handed to the program as a
    host ndarray (no src placement) — for such leaves the
    ``bytes_host_roundtrip``-avoided figure describes the device-
    resident seam this function exists for, not that call. Destination
    dims must divide the dst spec's axis sizes — the tables' own
    padding conventions (ALS model-axis padding, parallelize row
    padding) guarantee that at the registered seams.

    UNEVEN dst layouts are first-class via pad-reshard-slice: a leaf
    whose sharded dim the dst degree does not divide is zero-padded
    to divisibility INSIDE the same compiled program (one launch, no
    extra host trip), lands in dst layout at the padded shape, and
    the padding is itemized in :func:`reshard_stats`
    (``bytes_padding`` / per-leaf ``pad``). Passing ``true_shapes``
    (leaf name → logical shape) on a LATER reshard slices the padding
    off on the way back out — the round trip is bitwise the original
    (pinned by tests). Padded leaves are inert zeros past the true
    extent, the ALS model-axis convention.

    Emits ``reshard.bytes_wire`` / ``bytes_logical`` / ``leaves`` /
    ``syncs`` counters plus a ``reshard`` event (rendered by
    ``tda report``); ``emit=False`` for accounting-free use in inner
    loops that batch their own telemetry."""
    import jax

    st = reshard_stats(tree, src_tbl, dst_tbl, mesh,
                       true_shapes=true_shapes)
    src = jax.tree.map(_stage, tree)
    # destination shardings are computed at the FINAL (possibly
    # padded/sliced) shapes — the scalar short-circuit and the rule
    # match only consult shape via spec_for, which is shape-stable
    # under tail padding for every registered table
    final = _tree_map_named(
        lambda name, leaf: jax.ShapeDtypeStruct(
            tuple(st["leaves"][name].get(
                "padded_shape",
                st["leaves"][name].get("true_shape",
                                       np.shape(leaf)))),
            getattr(leaf, "dtype", np.float32)),
        tree)
    dst_sh = shardings(dst_tbl, final, mesh)
    transforms = tuple(
        (st["leaves"][name].get("true_shape"),
         st["leaves"][name].get("pad"))
        for name, _ in named_leaves(tree))
    out = _reshard_program(dst_sh, transforms)(src)
    if emit:
        emit_reshard_counters(st)
    return out


#: compiled reshard programs keyed by (destination-sharding tree,
#: per-leaf shape transforms) — ``jax.jit`` caches on FUNCTION
#: IDENTITY, so a fresh ``jit(lambda t: t, ...)`` per call would
#: re-trace+compile every reshard (review-caught: ~8 ms/call forever
#: vs ~10 µs cached); the hot seams (serve model builds, bench
#: repeats) hit this cache
_RESHARD_PROGRAMS: dict = {}


def _reshard_program(dst_sh, transforms=None):
    import jax

    leaves, treedef = jax.tree.flatten(dst_sh)
    transforms = transforms or tuple((None, None) for _ in leaves)
    key = (treedef, tuple(leaves), transforms)
    fn = _RESHARD_PROGRAMS.get(key)
    if fn is None:
        def _apply(t):
            import jax.numpy as jnp

            flat, td = jax.tree.flatten(t)
            out = []
            for x, (true_shape, pads) in zip(flat, transforms):
                # slice first (a previously-padded input's tail zeros
                # come off), then pad for the dst degrees — both fuse
                # into the ONE compiled relayout program
                if true_shape is not None and \
                        tuple(x.shape) != tuple(true_shape):
                    x = x[tuple(slice(0, s) for s in true_shape)]
                if pads is not None and any(pads):
                    x = jnp.pad(x, [(0, int(p)) for p in pads])
                out.append(x)
            return jax.tree.unflatten(td, out)

        fn = _RESHARD_PROGRAMS[key] = jax.jit(
            _apply, out_shardings=dst_sh)
    return fn


def host_gather_reshard(tree, dst_tbl, mesh,
                        true_shapes: dict | None = None):
    """The A/B baseline :func:`reshard` replaces: gather every leaf to
    THIS host (full D2H), then ``device_put`` back in the destination
    layout (full H2D) — ``2·B`` PCIe bytes per leaf and a host-RAM
    copy of the whole tree. Bitwise-identical output (both paths move
    the same values, including the uneven-layout pad/slice, applied
    here on host; tests pin it); kept for the bench A/B and as the
    fallback spelling on meshes the compiled path cannot address."""
    dst_t = table(dst_tbl)
    host = gather(tree)

    def one(name, x):
        true = (true_shapes or {}).get(name)
        if true is not None and tuple(x.shape) != tuple(true):
            x = x[tuple(slice(0, s) for s in true)]
        pads = pad_amounts(np.shape(x),
                           dst_t.spec_for(name, np.shape(x)), mesh)
        if any(pads):
            x = np.pad(x, [(0, int(p)) for p in pads])
        return x

    return place(_tree_map_named(one, host), dst_tbl, mesh)


def emit_reshard_counters(st: dict) -> dict:
    """Bump the ``reshard.*`` telemetry counters for one reshard and
    record the event (no-op when telemetry is disabled)."""
    from tpu_distalg.telemetry import events as tevents

    tevents.counter("reshard.bytes_wire", st["bytes_wire"])
    tevents.counter("reshard.bytes_logical", st["bytes_logical"])
    tevents.counter("reshard.bytes_host_avoided",
                    st["bytes_host_roundtrip"])
    tevents.counter("reshard.leaves", st["n_moved"])
    tevents.counter("reshard.syncs", 1)
    tevents.emit("reshard", src=st["src"], dst=st["dst"],
                 n_leaves=st["n_leaves"], n_moved=st["n_moved"],
                 bytes_wire=st["bytes_wire"])
    return st


# ------------------------------------------------- registered tables
#
# Every model's placement, as data. The leaf names are the ones the
# trainers use for their state/data pytrees; DATA_AXIS/MODEL_AXIS are
# the mesh axes from parallel/mesh.py. P is imported lazily at module
# import (jax.sharding is cheap and jax is a hard dep of this package).

from jax.sharding import PartitionSpec as _P  # noqa: E402

#: LR / plain SSGD / the SGD family's replicated-center layout:
#: weights and eval data replicated, per-shard state row-sharded.
TABLE_LR = register(RuleTable("lr", (
    (r"^(w|weights|delta)$", _P()),
    (r"^(res|residual)$", _P(DATA_AXIS, None)),
    (r"^(X2?|X_data)$", _P(DATA_AXIS, None)),
    (r"^(y|mask|valid)$", _P(DATA_AXIS)),
    (r"^(X_test|y_test|accs?|acc0?|clocks?|pend|basegen|stale)$",
     _P()),
)))

#: plain SSGD shares LR's layout wholesale (same leaf vocabulary:
#: replicated center w, row-sharded residual/packed data, replicated
#: SSP clock vector) plus the per-shard SSP window carries.
TABLE_SSGD = register(RuleTable("ssgd", (
    (r"^(wl|accd|ws)$", _P(DATA_AXIS, None)),
) + TABLE_LR.rules))

#: the tp split (sampler='fused_gather' + feature_sharded): packed
#: design matrix sharded data × model, augmented weights model-sharded.
TABLE_SSGD_TP = register(RuleTable("ssgd_tp", (
    (r"^(X2?|X_data)$", _P(DATA_AXIS, MODEL_AXIS)),
    (r"^(w|weights)$", _P(MODEL_AXIS)),
    (r"^(res|residual)$", _P(DATA_AXIS, None)),
    (r"^(y|mask|valid)$", _P(DATA_AXIS)),
    (r"^(X_test|y_test|accs?|acc0?)$", _P()),
)))

#: feature-sharded bernoulli SSGD: same 2-D placement as the tp split
#: (the table IS the code path — both spell P(data, model) / P(model)).
TABLE_SSGD_FEATURE_SHARDED = register(
    RuleTable("ssgd_feature_sharded", TABLE_SSGD_TP.rules))

#: the local-update family (local_sgd driving ma/bmuf/easgd): one
#: replicated center + per-replica row-sharded models/residuals.
TABLE_LOCAL_SGD = register(RuleTable("local_sgd", (
    (r"^(ws|res|residual)$", _P(DATA_AXIS, None)),
    (r"^(w|weights|delta)$", _P()),
    (r"^(X2?|X_data)$", _P(DATA_AXIS, None)),
    (r"^(y|mask|valid)$", _P(DATA_AXIS)),
    (r"^(X_test|y_test|accs?|acc0?|clocks?|stale)$", _P()),
)))
for _alias in ("ma", "bmuf", "easgd"):
    register(RuleTable(_alias, TABLE_LOCAL_SGD.rules))

#: k-means: points row-sharded (parallelize), centers replicated.
TABLE_KMEANS = register(RuleTable("kmeans", (
    (r"^(points|X2|m2)$", _P(DATA_AXIS, None)),
    (r"^(mask|valid)$", _P(DATA_AXIS)),
    (r"^(centers|n_seen)$", _P()),
)))

#: ALS training layout: ratings + user factors row-sharded over data,
#: item factors sharded over the MODEL axis (fit() pads n so this
#: always engages; the warned disengage path places V replicated).
#: ``V0`` — V at a sweep/segment ENTRY — is replicated: the engaged
#: layout is applied by constraint INSIDE the compiled sweep, and an
#: entry-sharded V would change the Gram matmul's reduction order
#: (the golden-hash pins hold the refactor to bitwise identity).
TABLE_ALS_TRAIN = register(RuleTable("als_train", (
    (r"^(R|U)$", _P(DATA_AXIS, None)),
    (r"^V0$", _P()),
    (r"^V$", _P(MODEL_AXIS, None)),
)))

#: ALS on a ratings list (models/als.py's sparse trainer): each side's
#: packed blocks (rows, ratings, pieces, the loader's stubs) sharded
#: over data along the blocks, both factor tables and the held-out
#: pairs replicated (a half-sweep reads the other side's table whole
#: and all-gathers its own).
TABLE_ALS_SPARSE = register(RuleTable("als_sparse", (
    (r"^ratings$", _P(DATA_AXIS)),
    (r"^(factors|heldout)$", _P()),
)))

#: ALS serving layout (serve/artifacts.py): user factors replicated
#: (any shard may score any user), item factors model-sharded for the
#: fused per-shard top-k. reshard('als_train' → 'als_serve') is the
#: train→serve seam: U all-gathers, V stays put — device-side.
TABLE_ALS_SERVE = register(RuleTable("als_serve", (
    (r"^U$", _P()),
    (r"^V$", _P(MODEL_AXIS, None)),
)))

#: dense transitive closure: the V×V boolean path matrix row-sharded
#: over data (the boolean-matmul fixpoint's only placed operand; the
#: sparse path's pair buffer stays replicated by design — see
#: models/transitive_closure.py).
TABLE_CLOSURE = register(RuleTable("closure_dense", (
    (r"^(paths|edges)$", _P(DATA_AXIS, None)),
)))

#: PageRank: edge/plan arrays contiguously sharded over data. The
#: fused sweep shards by destination range: a shard's edge slots and
#: plan arrays hold the edges that point into its range (no chip ever
#: holds the whole edge list), the rank vector it reads and the degree
#: tables are whole on every chip, and the ranks it writes are its
#: own range (``ranks_out``; the ranges are cut at ``bounds``, where
#: the loads are equal), all-gathered once a sweep. The XLA and
#: hybrid sweeps shard a destination-sorted list by position and
#: all-reduce whole tables.
TABLE_PAGERANK = register(RuleTable("pagerank", (
    (r"^(src|dst|w_e|emask|gbase|sbase|base|slots|ranks_out)$",
     _P(DATA_AXIS)),
    (r"^(src_lane|src_row|dst_row|dst_lane|row|lane)$",
     _P(DATA_AXIS, None)),
    (r"^(ranks|inv_deg|has_out|bounds)$", _P()),
)))

#: cluster-sharded PageRank: the rank vector ROW-PARTITIONED across
#: the PS tier (the rowstore twin of TABLE_PAGERANK, whose in-process
#: sweep replicates ranks and lets the all-reduce own combination);
#: the static degree tables stay whole on shard 0.
TABLE_PAGERANK_CLUSTER = register(RuleTable("pagerank_cluster", (
    (r"^ranks$", _P(DATA_AXIS)),
    (r"^(deg|inv_deg|has_out)$", _P()),
)))

#: streamed-SSGD eval operands: replicated (pinned to local compute
#: via shard_map in the trainer — see ssgd_stream.py).
TABLE_SSGD_STREAM = register(RuleTable("ssgd_stream", (
    (r"^(X_test|y_test)$", _P()),
) + TABLE_LR.rules))

#: the reshard pairs the system actually exercises (train→serve
#: artifact load; the 2-D ssgd layouts to/from pure-dp) — the
#: equivalence tests iterate this registry, so a new pair added here
#: is automatically held to the reshard ≡ gather+re-put contract.
RESHARD_PAIRS = (
    ("als_train", "als_serve"),
    ("als_serve", "als_train"),
    ("ssgd_feature_sharded", "ssgd"),
    ("ssgd", "ssgd_feature_sharded"),
)
