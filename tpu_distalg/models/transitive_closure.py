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
    """Config for :func:`run_sparse` — the O(closure-size) formulation.

    ``capacity`` bounds the number of distinct paths the buffer can hold
    (static shape; auto = 8×edges). ``join_capacity`` bounds the number
    of (path ⋈ edge) candidates one round may produce (auto =
    max(2×capacity, 8×edges)); unlike a per-vertex-degree pad this is a
    bound on the TRUE join size, so skewed degree distributions cost
    nothing extra. ``max_iterations`` caps the fixpoint (auto = longest
    possible path, V)."""

    capacity: int | None = None
    join_capacity: int | None = None
    max_iterations: int | None = None


@dataclasses.dataclass
class SparseClosureResult:
    paths: np.ndarray  # (n_paths, 2) distinct (x, z) pairs
    n_paths: int
    n_rounds: int


def run_sparse(edges: np.ndarray, mesh: Mesh,
               config: SparseClosureConfig = SparseClosureConfig(),
               n_vertices: int | None = None, *,
               checkpoint_dir: str | None = None,
               checkpoint_every: int = 8) -> SparseClosureResult:
    """Transitive closure without the V×V matrix — O(closure size) memory.

    The dense fixpoint (:func:`run`) is the right shape for small/dense
    graphs (boolean matmul rides the MXU) but its V×V path matrix is dead
    at ~100k+ vertices (SURVEY.md §2.2 names the alternative: "sort-based
    dedup for sparse"). Here the path set is what Spark's RDD was — a set
    of (x, z) pairs — mapped to static shapes:

      * a capacity-capped ``(C,)`` pair buffer, valid entries sorted
        first, sentinel (V, V) padding sorting last;
      * one round ≙ the reference's ``join`` + ``union().distinct()``
        (``transitive_closure.py:33-37``): a CSR segmented-expand joins
        every path (x, y) with y's out-edges — per-path counts →
        prefix-sum → scatter-max path markers → ``cummax`` recovers the
        owning path of each candidate slot, so the round's work is
        proportional to the TRUE join size (no per-vertex degree
        padding; skewed graphs cost nothing extra) — then concatenate
        with the known set (union), two-key ``lax.sort`` +
        neighbor-diff mask (distinct), and one more sort to compact
        uniques back into the buffer;
      * fixpoint when ``count`` stops growing — the reference's
        count-based convergence (``:38-40``), inside ``lax.while_loop``.

    Like the reference it re-joins the FULL path set each round (naïve,
    not frontier/semi-naïve — same asymptotics as the original). The
    sort-dedup is the shuffle equivalent and runs as one global XLA sort.

    Raises if ``capacity`` or ``join_capacity`` overflow (closure or
    one round's join bigger than its buffer).
    """
    el = gops.prepare_edges(edges, n_vertices)
    V = el.n_vertices
    E = el.n_edges
    n_shards = mesh.shape[DATA_AXIS]
    C = (config.capacity if config.capacity is not None
         else max(8 * E, 1024))
    C = -(-C // n_shards) * n_shards
    J = (config.join_capacity if config.join_capacity is not None
         else max(2 * C, 8 * E, 1024))
    cap = (config.max_iterations if config.max_iterations is not None
           else V + 1)

    from tpu_distalg import native

    if E > C:
        raise ValueError(f"capacity {C} < edge count {E}")
    # CSR over src (prepare_edges sorts by src); sentinel vertex V has
    # degree 0 so expanding an invalid path yields nothing
    offsets = np.zeros(V + 2, dtype=np.int64)
    if E:
        offsets[: V + 1] = native.csr_offsets(el.src.astype(np.int64), V)
        offsets[V + 1] = offsets[V]
    deg = np.diff(offsets).astype(np.int32)          # (V+1,)
    px0 = np.full(C, V, dtype=np.int32)
    pz0 = np.full(C, V, dtype=np.int32)
    px0[:E] = el.src
    pz0[:E] = el.dst

    # the path buffer stays REPLICATED: the sort-dedup is inherently
    # global, and XLA's partitioned sort on a row-sharded buffer (tested
    # on the 8-device CPU mesh) is orders of magnitude slower than one
    # local sort — the shuffle this replaces was Spark's global shuffle
    # too. Memory is O(closure), not O(V²), so replication is cheap.
    px0 = jnp.asarray(px0)
    pz0 = jnp.asarray(pz0)
    deg_d = jnp.asarray(deg)
    off_d = jnp.asarray(offsets[: V + 1].astype(np.int32))
    dst_d = jnp.asarray(el.dst)                      # src-sorted

    def make_seg_fn(seg):
        # one compiled segment of up to ``seg`` more rounds from the
        # carried fixpoint state; seg=cap is the straight run, smaller
        # seg adds checkpoint boundaries (bitwise-identical rounds)
        @jax.jit
        def fixpoint(px, pz, old_cnt0, cnt0, it0, overflow0,
                     deg, off, dst):
            it_hi = jnp.minimum(it0 + seg, cap)

            def count_valid(x):
                return jnp.sum((x < V).astype(jnp.int32))

            def cond(state):
                _, _, old_cnt, cnt, it, overflow = state
                # ~overflow: fail fast — once a round overflows its
                # buffers the result can never be trusted, so don't pay
                # the remaining rounds
                return (cnt != old_cnt) & (it < it_hi) & ~overflow

            def body(state):
                px, pz, _, cnt, it, overflow = state
                # join (x,y) ⋈ edges(y,·) via segmented expand: path p owns
                # candidate slots [start_p, start_p + deg(pz_p))
                k = deg[pz]                              # (C,)
                start = jnp.cumsum(k) - k                # exclusive prefix
                K = start[-1] + k[-1]                    # true join size
                # K is int32 and can wrap when the true join exceeds 2^31. The
                # exact K > J test catches every non-wrapping overflow; K < 0
                # catches true sizes in (2^31, 2^32); the f32 sum catches
                # >= 2^32 wrap-to-positive. Kf is compared against 2^31 (not J)
                # because the tree-reduction rounding of the f32 sum could
                # otherwise spuriously trip on a valid round with K ~ J.
                Kf = jnp.sum(k.astype(jnp.float32))
                overflow = (overflow | (K > J) | (K < 0)
                            | (Kf > jnp.float32(2**31)))
                # mark slot start_p with p+1 (k>0 paths only), cummax fills
                # the segment; -1 → owning path id
                marks = jnp.zeros((J,), jnp.int32).at[
                    jnp.where(k > 0, start, J)
                ].max(jnp.arange(C, dtype=jnp.int32) + 1, mode="drop")
                pid = jax.lax.cummax(marks) - 1          # (J,)
                slot = jnp.arange(J, dtype=jnp.int32)
                valid = (slot < K) & (pid >= 0)
                pid = jnp.where(valid, pid, 0)
                rank = slot - start[pid]
                eidx = jnp.clip(off[pz[pid]] + rank, 0, max(E - 1, 0))
                cx = jnp.where(valid, px[pid], V)
                cz = jnp.where(valid, dst[eidx], V) if E else jnp.full(
                    (J,), V, jnp.int32)
                ax = jnp.concatenate([px, cx])           # union
                az = jnp.concatenate([pz, cz])
                ax, az = jax.lax.sort((ax, az), num_keys=2)
                dup = jnp.concatenate([
                    jnp.zeros((1,), bool),
                    (ax[1:] == ax[:-1]) & (az[1:] == az[:-1]),
                ])
                uniq = (ax < V) & ~dup                   # distinct
                ax = jnp.where(uniq, ax, V)
                az = jnp.where(uniq, az, V)
                ax, az = jax.lax.sort((ax, az), num_keys=2)  # compact
                new_cnt = count_valid(ax)
                overflow = overflow | (new_cnt > C)
                return (ax[:C], az[:C], cnt, jnp.minimum(new_cnt, C),
                        it + 1, overflow)

            return jax.lax.while_loop(
                cond, body,
                (px, pz, old_cnt0, cnt0, it0, overflow0),
            )

        return fixpoint

    cnt0 = jnp.int32(E)  # every buffer entry < V is a real edge
    state0 = (px0, pz0, jnp.int32(-1), cnt0, jnp.int32(0),
              jnp.bool_(False))

    if checkpoint_dir is None:
        px, pz, _, cnt, rounds, overflow = make_seg_fn(cap)(
            *state0, deg_d, off_d, dst_d)
    else:
        from tpu_distalg.utils import checkpoint as ckpt

        def run_seg(fn, state, t0):
            px, pz, old, cnt, it, ov = fn(
                state["px"], state["pz"], state["old"], state["cnt"],
                state["it"], state["ov"], deg_d, off_d, dst_d)
            new = {"px": px, "pz": pz, "old": old, "cnt": cnt,
                   "it": it, "ov": ov}
            return new, np.asarray(cnt, np.float32)[None]

        state, _, _ = ckpt.run_segmented(
            checkpoint_dir, checkpoint_every, cap, make_seg_fn,
            run_seg,
            {"px": state0[0], "pz": state0[1], "old": state0[2],
             "cnt": state0[3], "it": state0[4], "ov": state0[5]},
            tag="closure_sparse",
            stop_when=lambda s: (bool(s["ov"])
                                 or int(s["cnt"]) == int(s["old"])))
        px, pz = jnp.asarray(state["px"]), jnp.asarray(state["pz"])
        cnt, rounds, overflow = state["cnt"], state["it"], state["ov"]

    n_paths = int(cnt)
    if bool(overflow):
        raise ValueError(
            f"closure overflowed its buffers (capacity {C}, "
            f"join_capacity {J}); rerun with a larger "
            f"SparseClosureConfig.capacity/join_capacity"
        )
    pairs = np.stack(
        [np.asarray(px[:n_paths]), np.asarray(pz[:n_paths])], axis=1
    )
    return SparseClosureResult(
        paths=pairs, n_paths=n_paths, n_rounds=int(rounds)
    )


#: the bytes a closure may plan with where nobody says otherwise:
#: :func:`run_sparse_auto`'s budget, and :func:`choose_form`'s where the
#: devices keep no memory statistics (the CPU)
DEFAULT_BUDGET_BYTES = 4 << 30

#: per-path buffer cost of one :func:`run_sparse` fixpoint round:
#: px/pz (2 int32) plus the two-key sort's union copy at C + J slots
#: (J defaults to 2C) — ~8 B/slot across ~4C live slots. The auto-
#: sizer budgets against THIS figure, so its refusal names real bytes.
SPARSE_BYTES_PER_CAPACITY_SLOT = 32

def choose_form(n_vertices: int, n_edges: int, mesh: Mesh, *,
                pairs_bound: int | None = None,
                budget_bytes: int | None = None) -> dict:
    """``dense`` or ``sparse`` from the bytes each form would hold, and
    the numbers that decided it (``closure:fit``'s fields; the counter
    ``closure.form`` counts the decisions).

    The dense form holds two V × V byte matrices (the one a round reads,
    the one it writes) whatever the answer; the sparse form's buffer must
    hold every pair of the answer at ``SPARSE_BYTES_PER_CAPACITY_SLOT``
    (``run_sparse_auto``'s rule), which nobody knows beforehand:
    ``pairs_bound`` is what the caller can say (a generator's closed
    form), V^2 otherwise. The smaller of the two that fits the budget
    (three quarters of the mesh's device memory) runs; neither: raises.
    BigDatalog's Grid250: 8.06 GB dense against 32.0 GB sparse."""
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
    (``capacity × SPARSE_BYTES_PER_CAPACITY_SLOT``) would exceed
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
        if cap * SPARSE_BYTES_PER_CAPACITY_SLOT > budget_bytes:
            raise ValueError(
                f"sparse closure refused: capacity {cap} needs "
                f"~{cap * SPARSE_BYTES_PER_CAPACITY_SLOT / 1e9:.1f} GB "
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
