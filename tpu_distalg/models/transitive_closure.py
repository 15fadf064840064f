"""Transitive closure by fixpoint iteration.

Re-design of ``/root/reference/graph_computation/transitive_closure.py``:
the reference joins the full path set against reversed edges, unions, dedups
and counts every round until the count stops growing (``:27-40``) — a
shuffle-heavy O(rounds) Spark pipeline with dynamic-size sets. Dynamic set
semantics don't exist under XLA's static shapes (SURVEY.md §7 hard part #3),
so the dense form holds the path set as a V×V matrix of bytes: one round is
a boolean product on the MXU and a logical-or union, the ``distinct`` is
free (idempotent |), and the fixpoint test compares the round's pair count
with the one before — the reference's count-based convergence (``:38-40``).

The dense round *doubles* (path ∘ path) where the reference's is *linear*
(edge ∘ path, one more arc a round): the same fixpoint, set and count in
⌈log2 longest path⌉ + 1 rounds instead of longest path + 1 (a 251 × 251
grid: 10 against 501, each a product of the whole matrix). The sparse form
below keeps the reference's linear join.

Two entry points make everything the dense form runs:
:func:`make_round_fn` the compiled round, :func:`make_start_fn` the start
state scattered on the device from an edge list. :func:`run`, its
checkpointed segments and the benchmark's adapter all use those two.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from tpu_distalg.ops import graph as gops
from tpu_distalg.parallel import DATA_AXIS, mesh_on_tpu, partition
from tpu_distalg.telemetry import events as tevents
from tpu_distalg.telemetry import names as tnames


@dataclasses.dataclass(frozen=True)
class ClosureConfig:
    max_iterations: int | None = None  # None → V (always enough)


@dataclasses.dataclass
class ClosureResult:
    paths: jax.Array  # (V, V) bool reachability
    n_paths: int      # the reference's final paths.count() (:42)
    n_rounds: int     # doublings, the one that saw the count stand included


@dataclasses.dataclass(frozen=True)
class DenseGeometry:
    """What the dense form's two entry points are built from: the
    graph's vertices, the matrix's padded side, which product composes
    (``ops/pallas_closure.compose_form``) and whether the kernel is
    interpreted (no TPU under the mesh)."""

    n_vertices: int
    v_padded: int
    form: str
    interpret: bool

    @property
    def matrix_bytes(self) -> int:
        return self.v_padded * self.v_padded


def dense_geometry(n_vertices: int, mesh: Mesh) -> DenseGeometry:
    """The geometry for a graph on a mesh, from the platform, the shards
    and the size alone: the padded vertices are isolated (no edges) and
    add no paths."""
    from tpu_distalg.ops import pallas_closure

    n_shards = mesh.shape[DATA_AXIS]
    on_tpu = mesh_on_tpu(mesh)
    form = pallas_closure.compose_form(n_vertices, on_tpu, n_shards)
    return DenseGeometry(
        n_vertices=n_vertices,
        v_padded=pallas_closure.padded_vertices(n_vertices, form, n_shards),
        form=form, interpret=not on_tpu)


def make_round_fn(mesh: Mesh, geom: DenseGeometry):
    """The compiled round: ``(spare, paths, count) -> (paths', spare',
    count', still)``. ``spare`` and ``paths`` are ``int8[V, V]`` and both
    donated: the new matrix is written into the spare's buffer (by the
    kernel; XLA's form is given it as room) and the matrix that was read
    is handed back as the next round's spare, so a chain of rounds holds
    two matrices, allocates nothing and copies nothing. The spare comes
    first because XLA pairs a donated argument with the result of the
    same position: crossed, it keeps its promise with a copy of each.
    ``count`` is ``ops/graph.path_count``'s two words and ``still`` says
    that the round added no pair (the fixpoint test), all on the
    device."""

    def one_round(spare, paths, count):
        with jax.named_scope(tnames.CLOSURE_COMPOSE):
            new, partials = gops.closure_step(
                paths, into=spare, form=geom.form,
                interpret=geom.interpret)
            new = partition.constrain(new, "paths", "closure_dense", mesh)
        with jax.named_scope(tnames.CLOSURE_COUNT):
            new_count = gops.path_count(partials)
            still = jnp.all(new_count == count)
        return new, paths, new_count, still

    return jax.jit(one_round, donate_argnums=(0, 1))


#: the cells of one scatter of the start state: XLA:TPU compiles a
#: scatter into 2^31 cells or more for 33 to 43 s (Grid250's 4.03e9: my
#: compiles, PR 52) and into fewer in about a second
START_SCATTER_CELLS = 1 << 29


def start_blocks(v_padded: int) -> int:
    """In how many blocks of rows the start state is scattered: one
    while the matrix is under 2^31 cells, else the fewest equal blocks of
    at most ``START_SCATTER_CELLS`` (Grid250's 63 488: 8 of 7936 rows)."""
    if v_padded * v_padded < 1 << 31:
        return 1
    least = -(-v_padded * v_padded // START_SCATTER_CELLS)
    return next(n for n in range(least, v_padded + 1) if v_padded % n == 0)


def make_start_fn(mesh: Mesh, geom: DenseGeometry):
    """The compiled start state: ``(src, dst) -> (paths0, count0)``, the
    edge list scattered into a zero matrix on the device (no V × V array
    on the host) and its pairs counted there; an arc given twice counts
    once."""
    v = geom.v_padded
    n_blocks = start_blocks(v)
    rows = v // n_blocks

    def block(src, dst, b):
        # the arcs whose source lies in block b; the others fall past
        # the block's last row and are dropped
        r = src - b * rows
        r = jnp.where((r >= 0) & (r < rows), r, rows)
        return jnp.zeros((rows, v), jnp.int8).at[r, dst].set(1, mode="drop")

    def start(src, dst):
        if n_blocks == 1:
            paths = block(src, dst, 0)
        else:
            paths = jax.lax.fori_loop(
                0, n_blocks,
                lambda b, p: jax.lax.dynamic_update_slice(
                    p, block(src, dst, b), (b * rows, 0)),
                jnp.zeros((v, v), jnp.int8))
        paths = partition.constrain(paths, "paths", "closure_dense", mesh)
        return paths, gops.path_count(
            jnp.sum(paths, axis=1, dtype=jnp.int32))

    return jax.jit(start)


@dataclasses.dataclass
class DenseJob:
    """A dense closure job as ``closure:prepare`` leaves it: the
    geometry, the edge list on the device, the compiled start, the start
    state it made and the spare matrix the first round writes to
    (``paths`` and ``spare`` are donated to that round)."""

    geom: DenseGeometry
    n_edges: int
    src: jax.Array
    dst: jax.Array
    start_fn: object
    paths: jax.Array
    spare: jax.Array
    count: jax.Array

    def start(self):
        """``(paths0, count0)`` again, from the edge list on the device:
        the same compiled scatter, nothing from the host."""
        return self.start_fn(self.src, self.dst)


def prepare_dense(edges: np.ndarray, mesh: Mesh,
                  n_vertices: int | None = None) -> DenseJob:
    """``closure:prepare``: the edge list made (``prepare_edges``: arcs
    given twice once), laid on the device, the start matrix scattered
    there."""
    devices = list(mesh.local_devices)
    with tevents.span("closure:prepare", devices):
        el = gops.prepare_edges(edges, n_vertices)
        geom = dense_geometry(el.n_vertices, mesh)
        start_fn = make_start_fn(mesh, geom)
        src, dst = jnp.asarray(el.src), jnp.asarray(el.dst)
        paths, count = start_fn(src, dst)
        spare = jnp.zeros_like(paths)
        jax.block_until_ready((count, spare))
    return DenseJob(geom, el.n_edges, src, dst, start_fn, paths, spare,
                    count)


def run(edges: np.ndarray, mesh: Mesh,
        config: ClosureConfig = ClosureConfig(),
        n_vertices: int | None = None, *,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 8) -> ClosureResult:
    job = prepare_dense(edges, mesh, n_vertices)
    geom, paths, count, spare = job.geom, job.paths, job.count, job.spare
    job.paths = job.spare = None        # the first round donates both
    cap = (config.max_iterations if config.max_iterations is not None
           else geom.v_padded + 1)
    round_fn = make_round_fn(mesh, geom)

    def rounds(paths, count, still, it, seg):
        # up to ``seg`` more rounds from the carried state; the host
        # reads one flag a round (a round is a product of the whole
        # matrix: the read costs nothing beside it). A round past the
        # fixpoint is never run, so segments of any length run the same
        # sequence of rounds, bit for bit. The spare is handed from
        # round to round and never saved.
        nonlocal spare
        it_hi = min(it + seg, cap)
        while it < it_hi and not still:
            paths, spare, count, flag = round_fn(spare, paths, count)
            still, it = bool(flag), it + 1
        return paths, count, still, it

    def run_seg(seg, state, t0):
        paths, count, still, it = rounds(
            state["paths"], state["cnt"], bool(state["still"]),
            int(state["it"]), seg)
        new = {"paths": paths, "cnt": count,
               "still": np.bool_(still), "it": np.int32(it)}
        return new, np.float32([gops.count_of(count)])

    # the fields are what ``tda report``'s closure line says of the fit
    with tevents.span("closure:fit", list(mesh.local_devices),
                      closure_form="dense", compose_form=geom.form,
                      vertices=geom.n_vertices, v_padded=geom.v_padded,
                      matrix_bytes=geom.matrix_bytes):
        if checkpoint_dir is None:
            paths, count, _, n_rounds = rounds(paths, count, False, 0, cap)
        else:
            from tpu_distalg.utils import checkpoint as ckpt

            state, _, _ = ckpt.run_segmented(
                checkpoint_dir, checkpoint_every, cap, lambda seg: seg,
                run_seg,
                {"paths": paths, "cnt": count, "still": np.bool_(False),
                 "it": np.int32(0)},
                tag="closure_dense",
                stop_when=lambda s: bool(s["still"]))
            paths, count = jnp.asarray(state["paths"]), state["cnt"]
            n_rounds = int(state["it"])
    n_paths = gops.count_of(count)
    tevents.counter("closure.rounds", n_rounds)
    tevents.counter("closure.pairs", n_paths)
    return ClosureResult(paths=paths != 0, n_paths=n_paths,
                         n_rounds=n_rounds)


@dataclasses.dataclass(frozen=True)
class SparseClosureConfig:
    """Config for the pair-set form (:func:`run_sparse`).

    ``capacity`` bounds the distinct pairs the set can hold (static
    shape; auto = 8×edges). ``delta_capacity`` bounds the pairs one
    round may find new (auto = ``capacity``: nothing smaller is known
    without the graph's answer) and ``join_capacity`` the (δ ⋈ arc)
    candidates one round may produce, duplicates included (auto =
    max(2×capacity, 8×edges)); both are bounds on TRUE sizes, so skewed
    degrees cost nothing extra. A generator that knows its answer says
    all three (a tree: no round finds more than ``edges`` pairs).
    ``max_iterations`` caps the fixpoint (auto = longest possible path,
    V)."""

    capacity: int | None = None
    join_capacity: int | None = None
    max_iterations: int | None = None
    delta_capacity: int | None = None


@dataclasses.dataclass
class SparseClosureResult:
    paths: np.ndarray | None  # (n_paths, 2) distinct (x, z) pairs, sorted
    n_paths: int
    n_rounds: int


@dataclasses.dataclass(frozen=True)
class SparseGeometry:
    """What the pair-set form's entry points are built from: the graph's
    vertices and arcs and the three static capacities (pairs of the set,
    pairs a round finds new, candidates a round joins)."""

    n_vertices: int
    n_edges: int
    capacity: int
    delta_capacity: int
    join_capacity: int

    @property
    def resident_bytes(self) -> int:
        """The bytes carried from call to call: the set and δ as two
        int32 columns each, the arcs by source (degree and offset a
        vertex, target an arc)."""
        return (8 * (self.capacity + self.delta_capacity)
                + 8 * (self.n_vertices + 1) + 4 * (self.n_edges + 1))

    @property
    def working_bytes(self) -> int:
        """``SPARSE_BYTES_PER_CAPACITY_SLOT`` over the slots one round
        sorts (the set and the candidates)."""
        return SPARSE_BYTES_PER_CAPACITY_SLOT * (
            self.capacity + self.join_capacity)


def sparse_geometry(n_vertices: int, n_edges: int,
                    config: SparseClosureConfig = SparseClosureConfig()
                    ) -> SparseGeometry:
    """The capacities for a graph: the caller's where it says them, else
    the defaults :class:`SparseClosureConfig` documents."""
    if n_vertices >= 1 << 30:
        raise ValueError(f"{n_vertices} vertices: the pair-set form sorts "
                         f"2 z + tag in an int32 and holds under 2^30")
    cap = (config.capacity if config.capacity is not None
           else max(8 * n_edges, 1024))
    if n_edges > cap:
        raise ValueError(f"capacity {cap} < edge count {n_edges}")
    delta = (config.delta_capacity if config.delta_capacity is not None
             else cap)
    join = (config.join_capacity if config.join_capacity is not None
            else max(2 * cap, 8 * n_edges, 1024))
    if cap + join >= 1 << 31:
        raise ValueError(f"capacity {cap} + join_capacity {join} slots "
                         f"are past int32")
    return SparseGeometry(n_vertices, n_edges, cap, max(delta, n_edges, 1),
                          join)


class SparseState(NamedTuple):
    """The pair-set form's carried state, all on the device. The set's
    ``n`` pairs are the slots of ``(sx, sz)`` that do not hold the
    sentinel vertex V, in (x, z) order (a round leaves a sentinel where
    a candidate repeated a pair; its next sort moves them to the end);
    δ's (the pairs the last round found new, the arcs at the start) are
    ``(dx, dz)[:nd]``, sentinels after them. ``overflow`` says that some
    round's candidates, new pairs or set did not fit: the state is then
    worthless and :func:`check_sparse` raises."""

    sx: jax.Array
    sz: jax.Array
    dx: jax.Array
    dz: jax.Array
    n: jax.Array
    nd: jax.Array
    overflow: jax.Array


class Arcs(NamedTuple):
    """The arcs by source on the device: ``deg`` and ``off`` a vertex
    (the sentinel V included, degree 0), ``dst`` an arc in source order
    and one sentinel past the last."""

    deg: jax.Array
    off: jax.Array
    dst: jax.Array


def _repeats(x, z):
    """Which pairs of a sorted run equal the pair before them."""
    return jnp.concatenate([
        jnp.zeros((min(1, x.shape[0]),), bool),
        (x[1:] == x[:-1]) & (z[1:] == z[:-1])])


def sparse_join(state: SparseState, arcs: Arcs, g: SparseGeometry):
    """δ ⋈ arc by a segmented expand: δ's pair p = (x, y) owns the
    candidate slots ``[start_p, start_p + deg(y))``, one an arc (y, z),
    and each holds (x, z). The work follows the TRUE join size: no
    padding to a largest degree. Returns the candidates (sentinels past
    the ``K`` joined), ``K`` and whether they overflowed."""
    V, D, J = g.n_vertices, g.delta_capacity, g.join_capacity
    k = arcs.deg[state.dz]                       # 0 at a sentinel
    ends = jnp.cumsum(k)
    start = ends - k
    K = ends[-1]
    # K is int32 and wraps past 2^31: K < 0 catches true sizes in
    # (2^31, 2^32), the float sum those beyond (compared with 2^31, not
    # J: its rounding could trip a sound round with K near J)
    overflow = ((K > J) | (K < 0)
                | (jnp.sum(k.astype(jnp.float32)) > jnp.float32(2**31)))
    # slot start_p is marked p + 1 (pairs with arcs only), a running
    # maximum fills the segment: the owning pair of every slot
    marks = jnp.zeros((J,), jnp.int32).at[
        jnp.where(k > 0, start, J)].max(
            jnp.arange(D, dtype=jnp.int32) + 1, mode="drop")
    pid = jnp.maximum(jax.lax.cummax(marks) - 1, 0)
    slot = jnp.arange(J, dtype=jnp.int32)
    valid = slot < K
    # the arc of slot s is off[y_p] + (s - start_p): one gather of the
    # difference
    eidx = jnp.clip((arcs.off[state.dz] - start)[pid] + slot,
                    0, g.n_edges)
    cx = jnp.where(valid, state.dx[pid], V)
    cz = jnp.where(valid, arcs.dst[eidx], V)
    return cx, cz, K, overflow


def sparse_distinct(state: SparseState, cx, cz, g: SparseGeometry):
    """The set and the candidates sorted as one run by (x, z, whose):
    a candidate equal to the pair before it (the set's, which sorts
    first, or an earlier copy of itself) is a duplicate, every other is
    new. The one sort of ``capacity + join_capacity`` slots is the
    round's only one: the merged set is the sorted run with a sentinel
    in every duplicate's place, cut to ``capacity`` (the sentinels the
    round before left have sorted to the end), and the new pairs alone
    are brought to the front in order (``gops.compact_front``). Returns
    the set, the new pairs, which slots of the run are held and which
    are new, and whether a held pair fell past ``capacity`` (the set and
    its candidates' copies together did not fit)."""
    V, C, D = g.n_vertices, g.capacity, g.delta_capacity
    ux = jnp.concatenate([state.sx, cx])
    uk = jnp.concatenate([state.sz * 2, cz * 2 + 1])
    ux, uk = jax.lax.sort((ux, uk), num_keys=2)
    uz = uk >> 1
    held = (ux < V) & ~_repeats(ux, uz)
    new = held & ((uk & 1) == 1)
    sx = jnp.where(held, ux, V)[:C]
    sz = jnp.where(held, uz, V)[:C]
    dx, dz = gops.compact_front(new, (ux, uz), (V, V), D)
    return sx, sz, dx, dz, held, new, jnp.any(held[C:])


@functools.lru_cache(maxsize=8)
def make_sparse_round_fn(mesh: Mesh, geom: SparseGeometry):
    """The compiled semi-naive round of the pair-set form: ``(state,
    arcs) -> (state', count', still, stats)``, the state donated.

    Round k joins only the pairs round k - 1 found new with the arcs
    (δ ⋈ arc) and takes out what the set holds already, which is how a
    Datalog engine evaluates ``tc(X,Y) <- tc(X,Z), arc(Z,Y).``. The
    reference script (``transitive_closure.py:27-40``) is naive, it
    re-joins every path every round; the set after every round (every
    pair joined by a path of 1 .. k + 1 arcs, each once), its count and
    the fixpoint are the same, because a path of k + 1 arcs extends one
    of k arcs whose pair was new in round k - 1 or reached earlier by a
    shorter path that has been extended already. ``count'`` is
    ``ops/graph.path_count``'s two words; ``still`` says the round
    found nothing new: the count stands, the fixpoint. ``stats`` are the
    round's three counters as ``int32[3]`` (in this order: the
    candidates joined, the pairs found new, whether a buffer has
    overflowed), a value of their own that outlives the donated state.
    An overflow of any of the three buffers sets ``state'.overflow`` for
    good and never truncates quietly (:func:`check_sparse`)."""
    D = geom.delta_capacity

    def one_round(state: SparseState, arcs: Arcs):
        with jax.named_scope(tnames.CLOSURE_JOIN):
            cx, cz, K, over_join = sparse_join(state, arcs, geom)
        with jax.named_scope(tnames.CLOSURE_DISTINCT):
            sx, sz, dx, dz, held, new, over_set = sparse_distinct(
                state, cx, cz, geom)
        with jax.named_scope(tnames.CLOSURE_COUNT):
            n = jnp.sum(held, dtype=jnp.int32)
            nd = jnp.sum(new, dtype=jnp.int32)
            overflow = state.overflow | over_join | over_set | (nd > D)
            still = nd == 0
            count = gops.path_count(n[None])
            stats = jnp.stack([K, nd, overflow.astype(jnp.int32)])
        return (SparseState(sx, sz, dx, dz, n, nd, overflow), count, still,
                stats)

    return jax.jit(one_round, donate_argnums=(0,))


def make_sparse_start_fns(mesh: Mesh, geom: SparseGeometry):
    """The three small compiled programs round the round that make a
    job's start on the device, ``(seed, arcs_of, start)``:

    ``seed(src, dst)`` lays an edge list of ``geom.n_edges`` arcs, in
    any order, an arc perhaps given twice, into an otherwise empty set
    with nothing new. One call of the compiled round on it (nothing to
    join) sorts the arcs by source and holds each once: the program's
    one sort serves the loader too, and no second one is compiled (XLA
    compiles a sort of any length for a minute).
    ``arcs_of(state)`` reads that set as the arcs by source: ``(Arcs,
    src, dst, n)``, the degrees counted and the offsets summed there.
    ``start(src, dst, n)`` is a job's start state from those sorted
    arcs: the set and δ both hold them, paths of one arc."""
    V, E = geom.n_vertices, geom.n_edges
    C, D = geom.capacity, geom.delta_capacity

    def pad(a, m):
        return jnp.concatenate(
            [a, jnp.full((m - a.shape[0],), V, jnp.int32)])

    def seed(src, dst):
        none = jnp.full((D,), V, jnp.int32)
        empty = Arcs(jnp.zeros((V + 1,), jnp.int32),
                     jnp.zeros((V + 1,), jnp.int32),
                     jnp.full((E + 1,), V, jnp.int32))
        return SparseState(pad(src, C), pad(dst, C), none, none,
                           jnp.int32(E), jnp.int32(0),
                           jnp.bool_(False)), empty

    def arcs_of(state):
        # every arc lies in the first E slots, a sentinel where one was
        # given twice
        src, dst = state.sx[:E], state.sz[:E]
        src, dst = gops.compact_front(src < V, (src, dst), (V, V))
        deg = jnp.zeros((V + 1,), jnp.int32).at[src].add(
            (src < V).astype(jnp.int32), mode="drop",
            indices_are_sorted=True)
        return (Arcs(deg, jnp.cumsum(deg) - deg, pad(dst, E + 1)),
                src, dst, state.n)

    def start(src, dst, n):
        return SparseState(pad(src, C), pad(dst, C), pad(src, D),
                           pad(dst, D), n, n, jnp.bool_(False))

    return jax.jit(seed), jax.jit(arcs_of), jax.jit(start)


@dataclasses.dataclass
class SparseJob:
    """A pair-set closure job as ``closure:prepare`` leaves it: the
    geometry, the compiled round, the arcs by source (as the round
    reads them and as a sorted edge list), the compiled start and the
    start state (donated to the first round)."""

    geom: SparseGeometry
    round_fn: object
    arcs: Arcs
    src: jax.Array
    dst: jax.Array
    n_arcs: jax.Array
    start_fn: object
    state: SparseState

    def start(self) -> SparseState:
        """The start state again, from the sorted arcs on the device."""
        return self.start_fn(self.src, self.dst, self.n_arcs)


def prepare_sparse(edges: np.ndarray, mesh: Mesh,
                   n_vertices: int | None = None,
                   config: SparseClosureConfig = SparseClosureConfig()
                   ) -> SparseJob:
    """``closure:prepare`` of the pair-set form: the edge list laid on
    the device as it is given, sorted by source by the round's own sort
    and counted there (:func:`make_sparse_start_fns`)."""
    devices = list(mesh.local_devices)
    with tevents.span("closure:prepare", devices):
        edges = np.asarray(edges).reshape(-1, 2)
        if n_vertices is None:
            n_vertices = int(edges.max()) + 1 if len(edges) else 0
        elif len(edges) and int(edges.max()) >= n_vertices:
            raise ValueError(
                f"n_vertices={n_vertices} but the edge list references "
                f"vertex id {int(edges.max())}")
        geom = sparse_geometry(n_vertices, len(edges), config)
        tevents.emit("closure:sparse_plan", **dataclasses.asdict(geom),
                     resident_bytes=geom.resident_bytes,
                     working_bytes=geom.working_bytes)
        round_fn = make_sparse_round_fn(mesh, geom)
        seed, arcs_of, start_fn = make_sparse_start_fns(mesh, geom)
        sorted_state = round_fn(*seed(
            jnp.asarray(edges[:, 0], jnp.int32),
            jnp.asarray(edges[:, 1], jnp.int32)))[0]
        arcs, src, dst, n_arcs = arcs_of(sorted_state)
        del sorted_state
        state = start_fn(src, dst, n_arcs)
        jax.block_until_ready(state)
    return SparseJob(geom, round_fn, arcs, src, dst, n_arcs, start_fn,
                     state)


def check_sparse(state: SparseState, geom: SparseGeometry) -> None:
    """Raises where a round overflowed a buffer (one flag read)."""
    if bool(state.overflow):
        raise ValueError(
            f"closure overflowed its buffers (capacity {geom.capacity}, "
            f"delta_capacity {geom.delta_capacity}, join_capacity "
            f"{geom.join_capacity}); rerun with a larger "
            f"SparseClosureConfig.capacity/delta_capacity/join_capacity")


def run_sparse(edges: np.ndarray, mesh: Mesh,
               config: SparseClosureConfig = SparseClosureConfig(),
               n_vertices: int | None = None, *,
               checkpoint_dir: str | None = None,
               checkpoint_every: int = 8,
               keep_paths: bool = True) -> SparseClosureResult:
    """Transitive closure without the V×V matrix — O(closure size) memory.

    The dense fixpoint (:func:`run`) is the right shape for small/dense
    graphs (boolean matmul rides the MXU) but its V×V path matrix is dead
    at ~100k+ vertices. Here the path set is what Spark's RDD was — a set
    of (x, z) pairs — in static shapes: a capacity-capped pair buffer
    kept sorted, and the loop over :func:`make_sparse_round_fn`'s round
    (δ ⋈ arc by a segmented expand, one sort of set and candidates, the
    new pairs and the merged set brought to the front) until a round
    finds nothing new: the reference's count-based convergence
    (``:38-40``). The host reads the round's three counters, one small
    array a round.

    The pair buffer stays on one device: the sort is global, as Spark's
    shuffle was, and memory is O(closure), not O(V²).

    Raises if a buffer overflows (closure, one round's new pairs or its
    join bigger than its capacity). ``keep_paths=False`` leaves the
    pairs on the device and returns their count alone (a closure of
    2.4e8 pairs is 1.9 GB on the host).
    """
    job = prepare_sparse(edges, mesh, n_vertices, config)
    geom, arcs, state = job.geom, job.arcs, job.state
    job.state = None                    # the first round donates it
    cap = (config.max_iterations if config.max_iterations is not None
           else geom.n_vertices + 1)
    round_fn = job.round_fn

    def rounds(state, still, it, seg):
        # up to ``seg`` more rounds from the carried state; a round past
        # the fixpoint (or an overflow) is never run, so segments of any
        # length run the same sequence of rounds, bit for bit
        it_hi = min(it + seg, cap)
        while it < it_hi and not still:
            state, _, _, stats = round_fn(state, arcs)
            joined, found, overflowed = (int(x) for x in np.asarray(stats))
            still, it = found == 0, it + 1
            tevents.counter("closure.sparse.candidates", joined)
            tevents.counter("closure.sparse.new_pairs", found)
            if overflowed:
                tevents.counter("closure.sparse.overflow")
                break
        return state, still, it

    with tevents.span("closure:fit", list(mesh.local_devices),
                      closure_form="sparse", vertices=geom.n_vertices,
                      capacity=geom.capacity,
                      delta_capacity=geom.delta_capacity,
                      join_capacity=geom.join_capacity,
                      resident_bytes=geom.resident_bytes):
        if checkpoint_dir is None:
            state, _, n_rounds = rounds(state, False, 0, cap)
        else:
            from tpu_distalg.utils import checkpoint as ckpt

            def state_of(saved):
                return SparseState(**{k: jnp.asarray(saved[k])
                                      for k in SparseState._fields})

            def run_seg(seg, saved, t0):
                state, still, it = rounds(
                    state_of(saved), bool(saved["still"]),
                    int(saved["it"]), seg)
                new = dict(state._asdict(), still=np.bool_(still),
                           it=np.int32(it))
                return new, np.asarray(state.n, np.float32)[None]

            saved, _, _ = ckpt.run_segmented(
                checkpoint_dir, checkpoint_every, cap, lambda seg: seg,
                run_seg,
                dict(state._asdict(), still=np.bool_(False),
                     it=np.int32(0)),
                tag="closure_sparse",
                stop_when=lambda s: bool(s["still"]) or bool(s["overflow"]))
            state, n_rounds = state_of(saved), int(saved["it"])
    check_sparse(state, geom)
    n_paths = int(state.n)
    tevents.counter("closure.rounds", n_rounds)
    tevents.counter("closure.pairs", n_paths)
    pairs = None
    if keep_paths:
        sx, sz = np.asarray(state.sx), np.asarray(state.sz)
        pairs = np.stack([sx, sz], axis=1)[sx < geom.n_vertices]
    return SparseClosureResult(paths=pairs, n_paths=n_paths,
                               n_rounds=n_rounds)


#: the bytes a closure may plan with where nobody says otherwise:
#: :func:`run_sparse_auto`'s budget, and :func:`choose_form`'s where the
#: devices keep no memory statistics (the CPU)
DEFAULT_BUDGET_BYTES = 4 << 30

#: bytes a slot that the pair-set round sorts (``capacity +
#: join_capacity`` of them): the carried set and δ, the sorted run of
#: two int32 a slot beside its unsorted copy, the compaction's distance
#: word and its shifted copy. XLA plans 7.31 GB for Tree17's round (2.45
#: GB of arguments, 4.87 GB of temporaries over 2^28 + 2^24 slots: 25.6 B
#: a slot, ``benchmarks/tools/compile_check_closure_sparse.py``); 32
#: leaves a quarter over. :func:`choose_form` counts it once a pair of
#: the answer (a caller who knows the answer sizes the candidates small
#: beside it), :func:`run_sparse_auto` a slot of the default geometry,
#: so both name real bytes.
SPARSE_BYTES_PER_CAPACITY_SLOT = 32

def choose_form(n_vertices: int, n_edges: int, mesh: Mesh, *,
                pairs_bound: int | None = None,
                budget_bytes: int | None = None) -> dict:
    """``dense`` or ``sparse`` from the bytes each form would hold, and
    the numbers that decided it (``closure:fit``'s fields; the counter
    ``closure.form`` counts the decisions).

    The dense form holds two V × V byte matrices (the one a round reads,
    the one it writes) whatever the answer; the sparse form's set must
    hold every pair of the answer at ``SPARSE_BYTES_PER_CAPACITY_SLOT``
    (the round's sort beside it), which nobody knows beforehand:
    ``pairs_bound`` is what the caller can say (a generator's closed
    form), V^2 otherwise. The smaller of the two that fits the budget
    (three quarters of the mesh's device memory) runs; neither: raises.
    BigDatalog's Grid250: 8.06 GB dense against 32.0 GB sparse; its
    Tree17: 379 TB dense against 7.6 GB sparse."""
    geom = dense_geometry(n_vertices, mesh)
    dense = 2 * geom.matrix_bytes
    pairs = n_vertices * n_vertices
    if pairs_bound is not None:
        pairs = min(pairs, int(pairs_bound))
    sparse = max(pairs, 8 * n_edges, 1024) * SPARSE_BYTES_PER_CAPACITY_SLOT
    if budget_bytes is None:
        limit = tevents.memory_limit(mesh.local_devices)
        budget_bytes = limit * 3 // 4 if limit else DEFAULT_BUDGET_BYTES
    fits = [(b, f) for b, f in ((dense, "dense"), (sparse, "sparse"))
            if b <= budget_bytes]
    if not fits:
        raise ValueError(
            f"closure refused: {n_vertices} vertices need {dense / 1e9:.2f} "
            f"GB as two byte matrices and up to {sparse / 1e9:.2f} GB as a "
            f"pair buffer of {pairs} pairs, over the "
            f"{budget_bytes / 1e9:.2f} GB budget")
    picked = {"closure_form": min(fits)[1], "dense_bytes": dense,
              "sparse_bytes": sparse, "budget_bytes": int(budget_bytes),
              "compose_form": geom.form, "v_padded": geom.v_padded}
    tevents.counter("closure.form")
    tevents.emit("closure_form", **picked)
    return picked


def run_sparse_auto(edges: np.ndarray, mesh: Mesh, *,
                    n_vertices: int | None = None,
                    start_capacity: int | None = None,
                    budget_bytes: int = DEFAULT_BUDGET_BYTES,
                    max_iterations: int | None = None,
                    checkpoint_dir: str | None = None,
                    checkpoint_every: int = 8) -> SparseClosureResult:
    """:func:`run_sparse` with CAPACITY AUTO-SIZING — the scale story
    (VERDICT advice #8): the closure size is unknown until computed
    (the reference's ``paths.count()`` loop has the same property), so
    the buffer is grown geometrically on overflow — start at
    ``start_capacity`` (default: ``run_sparse``'s 8×edges heuristic),
    DOUBLE on the overflow error, re-run the fixpoint. Each retry pays
    the full fixpoint again (the overflow poisons the buffer, there is
    nothing to resume), which is the honest cost of static shapes;
    the doubling schedule bounds total work at ≤ 2× the final run.

    The DOCUMENTED REFUSAL: a capacity whose working set
    (``SPARSE_BYTES_PER_CAPACITY_SLOT`` a slot of the set and of the
    candidates, twice as many) would exceed
    ``budget_bytes`` raises ``ValueError`` naming the budget, the
    capacity it refused, and the remedy (a bigger ``budget_bytes`` or
    the dense path) — it never silently truncates a closure.

    With ``checkpoint_dir``, each capacity attempt owns the directory:
    an overflowed attempt's checkpoints hold the OLD ``(C,)``-shaped
    buffers (and a poisoned fixpoint), so they are pruned before the
    doubled retry — without that, ``run_segmented``'s state-signature
    check would reject the regrown shapes as a foreign workload and
    auto-sizing could never complete a checkpointed run.
    """
    from tpu_distalg.telemetry import events as tevents

    E = int(np.asarray(edges).shape[0]) if len(edges) else 0
    cap = (int(start_capacity) if start_capacity is not None
           else max(8 * E, 1024))
    # the buffer must at least hold the edge set (run_sparse's own
    # precondition) — an undersized explicit start_capacity is a
    # growth starting point, not a hard error
    cap = max(cap, E)
    while True:
        # the default geometry: the set and twice as many candidates
        working = sparse_geometry(
            0, E, SparseClosureConfig(capacity=cap)).working_bytes
        if working > budget_bytes:
            raise ValueError(
                f"sparse closure refused: capacity {cap} needs "
                f"~{working / 1e9:.1f} GB "
                f"working set, over the {budget_bytes / 1e9:.1f} GB "
                f"budget — the closure is larger than the budget "
                f"allows; raise budget_bytes, or use the dense path "
                f"(run) if V×V bits fit")
        try:
            return run_sparse(
                edges, mesh,
                SparseClosureConfig(capacity=cap,
                                    max_iterations=max_iterations),
                n_vertices,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every)
        except ValueError as e:
            if "overflowed its buffers" not in str(e):
                raise
            if checkpoint_dir is not None:
                from tpu_distalg.utils import checkpoint as ckpt

                ckpt.prune(checkpoint_dir, keep=0)
            tevents.emit("closure_capacity_grow", capacity=cap,
                         next_capacity=cap * 2)
            tevents.counter("closure.capacity_regrows")
            cap *= 2
