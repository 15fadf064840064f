"""K-means (Lloyd's algorithm).

Re-design of ``/root/reference/machine_learning/k-means.py``: the per-point
``closest_center`` Python loop (``:20-28``) becomes a batched distance
argmin on the MXU; the ``reduceByKey`` cluster statistics (``:62-63``)
become a local ``segment_sum`` plus one psum of the (k, dim)+ (k,) stats
across shards; the driver center update (``:70-71``) happens replicated
on-device. The reference runs 5 fixed iterations and never uses its
``convergeDist`` constant (``:16``, SURVEY.md §2.1 row 6) — we default to
fixed iterations for parity and offer a real convergence check behind
``converge_dist``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from tpu_distalg.ops import kmeans as kops
from tpu_distalg.telemetry import events as tevents
from tpu_distalg.telemetry import names
from tpu_distalg.parallel import (
    data_parallel,
    mesh_on_tpu,
    parallelize,
    tree_allreduce_sum,
)


@dataclasses.dataclass(frozen=True)
class KMeansConfig:
    """Knob names follow ``k-means.py:14-17``."""

    k: int = 2
    n_iterations: int = 5
    converge_dist: float | None = None  # None → fixed iters (parity)
    max_iterations: int = 1000          # safety cap in converge mode
    seed: int = 42
    # scale-path init: 'sample' = k random rows (takeSample parity,
    # k-means.py:53); 'farthest' = greedy max-min over an oversample
    # (immune to the merged-cluster local optimum at larger k)
    init: str = "sample"


@dataclasses.dataclass
class KMeansResult:
    centers: jax.Array            # (k, dim)
    assignments: jax.Array        # (n_padded,) final cluster per point
    n_iterations_run: int


def _local_stats(points, mask, centers):
    with jax.named_scope(names.KMEANS_ASSIGN):
        assign = kops.assign_clusters(points, centers)
    with jax.named_scope(names.KMEANS_STATS):
        sums, counts = kops.cluster_stats(
            points, mask, assign, centers.shape[0])
    with jax.named_scope(names.KMEANS_SYNC):
        sums, counts = tree_allreduce_sum((sums, counts))
    return sums, counts, assign


def _mesh_fns(mesh: Mesh, lanes):
    """``(stats, assign)`` over the mesh for either layout of the
    points: ``stats(data, valid, centers) -> (sums, counts)`` summed
    over the shards, ``assign(data, valid, centers)`` the nearest centre
    of every point held, padding included, in id order.

    Row layout (``lanes`` None): ``data`` ``(n, dim)``, ``valid`` the
    ``(n,)`` mask, ``ops/kmeans.py``; counts are float32. Lanes layout:
    ``data`` ``(n_blocks, dim, R, 128)``, ``valid`` the count of valid
    points (validity follows from the id), one ``pallas_lloyd`` kernel
    a pass (the per-cluster sums on the MXU or the VPU as
    ``pallas_lloyd.sums_form`` says for the geometry); counts are
    int32. Wide layout (k * dim past the lanes kernel's reach):
    ``data`` ``(n_blocks, dim_held, P)``, ``valid`` the count again,
    two ``pallas_lloyd_wide`` kernels a pass (the distance product on
    the MXU at float32 accuracy under ``assign``; under ``stats`` the
    per-cluster sums as a scatter-add of each point into its centre's
    row, or as a one-hot product where k x dim is small enough that the
    product is the cheaper: ``pallas_lloyd_wide.sums_form``); counts
    int32. ``lanes`` is the geometry
    :func:`scale_geometry` gave."""
    if lanes is None:
        both = data_parallel(
            _local_stats, mesh,
            in_specs=(P("data", None), P("data"), P()),
            out_specs=(P(), P(), P("data")),
        )
        return (lambda p, m, c: both(p, m, c)[:2],
                lambda p, m, c: both(p, m, c)[2])

    interpret = not mesh_on_tpu(mesh)

    def mine(x, n_valid):
        """How many of this shard's points are valid."""
        n_local = x.shape[0] * lanes.block_points
        first = jax.lax.axis_index("data") * n_local
        return jnp.clip(n_valid - first, 0, n_local)

    if layout_of(lanes) == "wide":
        from tpu_distalg.ops import pallas_lloyd_wide as wide

        x_spec = P("data", None, None)

        def nearest(x3, centers):
            with jax.named_scope(names.KMEANS_ASSIGN):
                return wide.wide_assign(x3, centers, geom=lanes,
                                        interpret=interpret)

        def wide_stats(x3, n_valid, centers):
            assign = nearest(x3, centers)
            with jax.named_scope(names.KMEANS_STATS):
                stats = wide.wide_stats(
                    x3, assign, mine(x3, n_valid), geom=lanes,
                    interpret=interpret)
            with jax.named_scope(names.KMEANS_SYNC):
                return tree_allreduce_sum(stats)

        return (data_parallel(wide_stats, mesh,
                              in_specs=(x_spec, P(), P()),
                              out_specs=(P(), P())),
                data_parallel(
                    lambda x3, n_valid, c: nearest(x3, c).reshape(-1),
                    mesh, in_specs=(x_spec, P(), P()),
                    out_specs=P("data")))

    # Pallas costs a second to import: only where a kernel is built
    from tpu_distalg.ops import pallas_lloyd as lloyd

    x_spec = P("data", None, None, None)

    def local_stats(x4, n_valid, centers):
        with jax.named_scope(names.KMEANS_ASSIGN):
            partial, = lloyd.lloyd_pass(
                x4, centers, mine(x4, n_valid), interpret=interpret)
        with jax.named_scope(names.KMEANS_STATS):
            stats = lloyd.fold_stats(partial, *centers.shape)
        with jax.named_scope(names.KMEANS_SYNC):
            return tree_allreduce_sum(stats)

    def local_assign(x4, n_valid, centers):
        with jax.named_scope(names.KMEANS_ASSIGN):
            return lloyd.lloyd_pass(
                x4, centers, mine(x4, n_valid), stats=False, assign=True,
                interpret=interpret)[0].reshape(-1)

    return (data_parallel(local_stats, mesh, in_specs=(x_spec, P(), P()),
                          out_specs=(P(), P())),
            data_parallel(local_assign, mesh, in_specs=(x_spec, P(), P()),
                          out_specs=P("data")))


def scale_geometry(dim: int, k: int):
    """The resident layout of the scale path, from ``(dim, k)`` alone:
    the lanes kernel's where it covers the shape (k * dim up to 1024:
    scores unrolled on the VPU), else the wide pass's (the distance
    product on the MXU), else ``None``: plain rows."""
    from tpu_distalg.ops import pallas_lloyd as lloyd

    lanes = lloyd.lanes_geometry(dim, k)
    if lanes is not None:
        return lanes
    from tpu_distalg.ops import pallas_lloyd_wide as wide

    return wide.wide_geometry(dim, k)


def layout_of(geometry) -> str:
    """``rows``, ``lanes`` or ``wide``: what the spans call a layout."""
    if geometry is None:
        return "rows"
    return getattr(geometry, "layout", "lanes")


def _span_fields(k: int, lanes) -> dict:
    """What the scale path's spans say beyond their sizes: which layout
    took the table, where a pass scores the distances (on the wide
    layout also how deep a tile's contraction is, padding included) and
    where it adds up the per-cluster sums at this geometry."""
    layout = layout_of(lanes)
    if layout == "rows":
        return {"layout": layout}
    if layout == "wide":
        return {"layout": layout, "dist_form": lanes.dist_form,
                "dist_depth": lanes.dist_depth,
                "sums_form": lanes.sums_form}
    from tpu_distalg.ops import pallas_lloyd as lloyd

    return {"layout": layout, "dist_form": "vpu",
            "sums_form": lloyd.sums_form(k, lanes.dim)}


def _counts0(k: int, lanes) -> jax.Array:
    return jnp.zeros((k,), jnp.float32 if lanes is None else jnp.int32)


def _update(sums, counts, centers):
    with jax.named_scope(names.KMEANS_UPDATE):
        return kops.update_centers(
            sums, counts.astype(jnp.float32), centers)


def init_centers(points: np.ndarray, k: int, seed: int) -> np.ndarray:
    """Seeded k-point sample without replacement — ``takeSample(False, k,
    42)`` (``k-means.py:53``)."""
    rng = np.random.default_rng(seed)
    idx = rng.choice(points.shape[0], size=k, replace=False)
    return np.asarray(points)[idx].astype(np.float32)


def _seg_loop(one_iter, config: KMeansConfig, seg: int,
              centers0, shift0, n_run0, counts0):
    """THE Lloyd loop — both the straight driver (one full-length
    segment) and every checkpoint segment run this exact code, so the
    segmented==straight bitwise contract cannot drift. Fixed-iteration
    mode runs exactly ``seg``; converge mode caps the while_loop at
    ``seg`` more iterations, and because the carried ``shift``
    re-enters the loop condition, post-convergence segments are
    no-ops. ``one_iter(centers) -> (centers, counts)``; returns
    ``(centers, shift, n_run, counts)`` with the counts of the last
    iteration run (``counts0`` where none ran)."""
    if config.converge_dist is None:
        (centers, counts), _ = jax.lax.scan(
            lambda c, _: (one_iter(c[0]), None), (centers0, counts0),
            None, length=seg,
        )
        return centers, shift0, n_run0 + seg, counts

    def cond(state):
        _, shift, it, _ = state
        return (shift > config.converge_dist) & (it < seg)

    def body(state):
        centers, _, it, _ = state
        new, counts = one_iter(centers)
        with jax.named_scope(names.KMEANS_UPDATE):
            shift = jnp.sum(
                jnp.sqrt(jnp.sum((new - centers) ** 2, axis=1)))
        return new, shift, it + 1, counts

    centers, shift, it, counts = jax.lax.while_loop(
        cond, body, (centers0, shift0, jnp.int32(0), counts0)
    )
    return centers, shift, n_run0 + it, counts


def _lloyd_loop(one_iter, config: KMeansConfig, centers0, counts0):
    """Straight Lloyd driver = one full-length segment of
    :func:`_seg_loop`. Returns (final centers, iterations run)."""
    n_total = (config.n_iterations if config.converge_dist is None
               else config.max_iterations)
    centers, _, n_run, _ = _seg_loop(
        one_iter, config, n_total, centers0,
        jnp.float32(jnp.inf), jnp.int32(0), counts0)
    return centers, n_run


def make_fit_fn(mesh: Mesh, config: KMeansConfig,
                lanes=None):
    """The straight fit, for either layout (:func:`_mesh_fns` says what
    ``points`` and ``valid`` are in each)."""
    stats_fn, assign_fn = _mesh_fns(mesh, lanes)

    def fit(points, valid, centers0):
        def one_iter(centers):
            sums, counts = stats_fn(points, valid, centers)
            return _update(sums, counts, centers), counts

        centers, n_run = _lloyd_loop(
            one_iter, config, centers0, _counts0(config.k, lanes))
        # final assignment under the final centers (the reference's closing
        # display re-evaluates with updated centers, k-means.py:57-58,76)
        return centers, assign_fn(points, valid, centers), n_run

    return jax.jit(fit)


def _rows_of(make_rows, ids, data_seed) -> jax.Array:
    """The rows ``ids``, regenerated; ``data_seed`` as
    :func:`fit_scaled` takes it."""
    extra = () if data_seed is None else (jnp.int32(data_seed),)
    return jax.jit(make_rows)(jnp.asarray(ids, jnp.int32), *extra)


def init_centers_from_rows(make_rows, n_rows: int, k: int, seed: int,
                           data_seed: int | None = None) -> jax.Array:
    """Device-side seeded init for the scale path: draw k DISTINCT
    global row ids host-side (O(k) memory — the ids, never the data)
    and REGENERATE exactly those rows with the counter-based generator.
    Because row content depends only on the row id, this equals
    ``takeSample(False, k, seed)`` over the materialized dataset
    (``k-means.py:53``) without a host copy or a cross-shard gather."""
    if k > n_rows:
        raise ValueError(
            f"cannot sample k={k} distinct rows from n_rows={n_rows}"
        )
    rng = np.random.default_rng(seed)
    chosen: list[int] = []
    seen: set[int] = set()
    while len(chosen) < k:
        for i in rng.integers(0, n_rows, size=k).tolist():
            if i not in seen and len(chosen) < k:
                seen.add(i)
                chosen.append(i)
    return jnp.asarray(
        _rows_of(make_rows, np.array(chosen), data_seed), jnp.float32)


def init_centers_farthest(make_rows, n_rows: int, k: int, seed: int,
                          oversample: int = 32,
                          data_seed: int | None = None) -> jax.Array:
    """Farthest-point init for the scale path: regenerate ``oversample·k``
    candidate rows (still O(k) in ``n_rows``) and greedily pick k by
    max-min distance. Random-row init (``init_centers_from_rows``, the
    reference's ``takeSample`` parity) merges clusters with probability
    ≈1−k!/kᵏ on a balanced mixture; farthest-point avoids that Lloyd
    local optimum while staying a one-shot init, no extra data pass."""
    rng = np.random.default_rng(seed)
    m = oversample * k
    ids = rng.integers(0, n_rows, size=m, dtype=np.int64)
    cand = jnp.asarray(_rows_of(make_rows, ids, data_seed),
                       jnp.float32)                         # (m, dim)
    # on the device: k passes over the candidates (131 072 x 784 at
    # k = 4096: 20 minutes of NumPy on the host, seconds here)
    return _farthest(cand, jnp.int32(rng.integers(0, m)), k)


@functools.partial(jax.jit, static_argnums=2)
def _farthest(cand, first, k: int):
    """``k`` of the candidates, greedily: each next one the farthest
    from those chosen so far."""
    def dist(i):
        return jnp.sum((cand - cand[i]) ** 2, axis=1)

    def pick(j, state):
        chosen, d = state
        nxt = jnp.argmax(d).astype(jnp.int32)
        return chosen.at[j].set(nxt), jnp.minimum(d, dist(nxt))

    chosen, _ = jax.lax.fori_loop(
        1, k, pick,
        (jnp.zeros((k,), jnp.int32).at[0].set(first), dist(first)))
    return cand[chosen]


def make_fit_seg_fn(mesh: Mesh, config: KMeansConfig, seg: int,
                    lanes=None):
    """One compiled checkpoint segment: up to ``seg`` Lloyd iterations
    continuing from ``(centers, shift, n_run)`` — the same
    :func:`_seg_loop` the straight driver runs (the checkpoint/resume
    bitwise contract every optimizer workload has). ``seg_run(points,
    valid, centers, shift, n_run) -> (centers, shift, n_run, counts)``
    for either layout (:func:`_mesh_fns`); ``counts`` are those of the
    last iteration run. Nothing but the arguments varies from call to
    call: one compile serves every data seed and every start."""
    stats_fn, _ = _mesh_fns(mesh, lanes)

    def seg_run(points, valid, centers0, shift0, n_run0):
        def one_iter(centers):
            sums, counts = stats_fn(points, valid, centers)
            return _update(sums, counts, centers), counts

        return _seg_loop(one_iter, config, seg, centers0, shift0,
                         n_run0, _counts0(config.k, lanes))

    return jax.jit(seg_run)


def _fit_segmented(data, valid, mesh, config: KMeansConfig, centers0,
                   checkpoint_dir: str, checkpoint_every: int,
                   lanes=None):
    """Checkpointed Lloyd driver (state is tiny: the (k, dim) centers
    plus the convergence carry) — the task-retry capability Spark gives
    the reference's k-means for free (SURVEY.md §5)."""
    from tpu_distalg.utils import checkpoint as ckpt

    converge = config.converge_dist is not None
    n_total = config.max_iterations if converge else config.n_iterations
    stop_when = (
        (lambda s: float(s["shift"]) <= config.converge_dist)
        if converge else None)

    def run_seg(fn, state, t0):
        centers, shift, n_run, _ = fn(
            data, valid, state["centers"], state["shift"],
            state["n_run"])
        new = {"centers": centers, "shift": shift, "n_run": n_run}
        return new, np.asarray(shift, np.float32)[None]

    state0 = {
        "centers": jnp.asarray(centers0),
        # fixed mode never updates shift — keep it finite for the
        # segment-boundary non-finite guard; converge mode starts at
        # inf exactly like the straight while_loop
        "shift": jnp.float32(np.inf if converge else 0.0),
        "n_run": jnp.int32(0),
    }
    state, _, _ = ckpt.run_segmented(
        checkpoint_dir, checkpoint_every, n_total,
        lambda seg: make_fit_seg_fn(mesh, config, seg, lanes),
        run_seg, state0, span_fields=_span_fields(config.k, lanes),
        # the two modes share the state signature but fixed mode's
        # shift=0.0 sentinel would alias "converged" on a cross-mode
        # resume — encode the mode in the tag
        tag="kmeans_converge" if converge else "kmeans_fixed",
        stop_when=stop_when)

    centers = state["centers"]
    return KMeansResult(
        centers=centers,
        assignments=jax.jit(_mesh_fns(mesh, lanes)[1])(
            data, valid, centers),
        n_iterations_run=int(state["n_run"]),
    )


def fit(points: np.ndarray, mesh: Mesh,
        config: KMeansConfig = KMeansConfig(), *,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 100) -> KMeansResult:
    ps = parallelize(points, mesh)
    centers0 = init_centers(points, config.k, config.seed)
    if checkpoint_dir is not None:
        return _fit_segmented(ps.data, ps.mask, mesh, config, centers0,
                              checkpoint_dir, checkpoint_every)
    fn = make_fit_fn(mesh, config)
    centers, assign, n_run = fn(ps.data, ps.mask, jnp.asarray(centers0))
    return KMeansResult(
        centers=centers, assignments=assign, n_iterations_run=int(n_run)
    )


def make_minibatch_step_fn(mesh: Mesh, k: int, dim: int):
    """Jitted minibatch-k-means step over one STAGED batch from a
    ``ShardedDataset`` in the ``points_valid_f32`` layout
    (``data/builders.py``): per shard, assign + masked cluster stats
    over the staged rows, one psum, then the Sculley (2010) web-scale
    update — per-center learning rate ``count_c / n_seen_c`` so each
    center converges as the harmonic mean of its minibatch means.
    ``step(staged, centers, n_seen) -> (centers, n_seen)``; arithmetic
    is identical whichever backend staged the batch, so trajectories
    are bitwise-equal across resident/virtual/streamed
    (tests/test_data.py)."""
    from jax.sharding import PartitionSpec as P

    from tpu_distalg.ops import kmeans as kops

    def _local(staged, centers):
        rows = staged[0]
        pts, m = rows[:, :dim], rows[:, dim]
        assign = kops.assign_clusters(pts, centers)
        sums, counts = kops.cluster_stats(pts, m, assign, k)
        return tree_allreduce_sum((sums, counts))

    stats_fn = data_parallel(
        _local, mesh,
        in_specs=(P("data", None, None), P()),
        out_specs=(P(), P()),
    )

    def step(staged, centers, n_seen):
        sums, counts = stats_fn(staged, centers)
        n_seen = n_seen + counts
        eta = jnp.where(n_seen > 0, counts / jnp.maximum(n_seen, 1.0),
                        0.0)
        means = sums / jnp.maximum(counts, 1.0)[:, None]
        centers = jnp.where(counts[:, None] > 0,
                            centers + eta[:, None] * (means - centers),
                            centers)
        return centers, n_seen

    return jax.jit(step)


def init_centers_from_dataset(dataset, k: int, seed: int) -> jax.Array:
    """Greedy farthest-point init over the dataset's FIRST block
    (shard 0) — O(block) host cost, identical whichever backend holds
    the bytes (the staged block is bitwise-equal across backends).
    Farthest-point, not a random k-sample: random init merges clusters
    with probability ≈1−k!/kᵏ (98.5% at k=6) and the minibatch update
    cannot split a merged pair — the same Lloyd local optimum
    :func:`init_centers_farthest` documents for the resident scale
    path."""
    block0 = np.asarray(
        dataset.stage(np.zeros((dataset.n_shards, 1), np.int64)))[0]
    dim = block0.shape[1] - 1
    valid = block0[:, dim] > 0
    pts = block0[valid][:, :dim]
    if k > pts.shape[0]:
        raise ValueError(
            f"cannot sample k={k} centers from a {pts.shape[0]}-row "
            "first block; raise block_rows")
    rng = np.random.default_rng(seed)
    chosen = [int(rng.integers(0, pts.shape[0]))]
    d = np.linalg.norm(pts - pts[chosen[0]], axis=1)
    while len(chosen) < k:
        nxt = int(d.argmax())
        chosen.append(nxt)
        d = np.minimum(d, np.linalg.norm(pts - pts[nxt], axis=1))
    return jnp.asarray(pts[chosen], jnp.float32)


def fit_minibatch(dataset, config: KMeansConfig, *, n_steps: int,
                  mini_batch_blocks: int = 4,
                  centers0=None) -> KMeansResult:
    """Minibatch k-means over a :class:`~tpu_distalg.data.ShardedDataset`
    — the >HBM Lloyd replacement this repo previously had only for SSGD
    (VERDICT "what's missing" #3): per step, ``mini_batch_blocks``
    blocks per shard are drawn with the SAME host-side threefry sampler
    the streamed SSGD trainer uses (keyed on the absolute step id, so
    runs are deterministic), staged through the prefetch pipeline
    (gather ∥ H2D ∥ compute for host backends), and folded into the
    centers with the Sculley update. The dataset must be in the
    ``points_valid_f32`` layout (``data/builders.py``); padding rows
    carry valid 0 and are inert."""
    from tpu_distalg.data import make_host_block_sampler

    import contextlib

    dim = int(dataset.meta.get("dim", dataset.pd - 1))
    ns = min(mini_batch_blocks, dataset.n_blocks)
    draw = make_host_block_sampler(
        config.seed, dataset.n_shards, dataset.n_blocks, ns)
    ids = draw(np.arange(n_steps))
    if centers0 is None:
        centers0 = init_centers_from_dataset(
            dataset, config.k, config.seed)
    step = make_minibatch_step_fn(dataset.mesh, config.k, dim)
    centers = jnp.asarray(centers0, jnp.float32)
    n_seen = jnp.zeros((config.k,), jnp.float32)
    serialize = not dataset.on_tpu
    with contextlib.closing(dataset.stream(ids)) as batches:
        for staged in batches:
            centers, n_seen = step(staged, centers, n_seen)
            if serialize:
                jax.block_until_ready(centers)
    from tpu_distalg.utils import metrics

    metrics.guard_finite(centers, "minibatch k-means centers")
    return KMeansResult(centers=centers,
                        assignments=jnp.zeros((0,), jnp.int32),
                        n_iterations_run=n_steps)


def init_centers_scaled(make_rows, n_rows: int, config: KMeansConfig,
                        data_seed: int | None = None) -> jax.Array:
    """The scale path's ``config.init`` dispatch — one place, shared by
    :func:`fit_scaled` and callers that time the fit separately."""
    if config.init == "farthest":
        return init_centers_farthest(
            make_rows, n_rows, config.k, config.seed,
            data_seed=data_seed)
    if config.init == "sample":
        return init_centers_from_rows(
            make_rows, n_rows, config.k, config.seed, data_seed)
    raise ValueError(f"unknown init {config.init!r}")


@jax.jit
def _all_finite(x):
    return jnp.isfinite(x).all()


def build_scaled(mesh: Mesh, n_rows: int, make_rows, k: int, *,
                 data_seed: int | None = None):
    """The scale path's resident table: ``(points, valid, lanes)`` as
    :func:`make_fit_fn` and :func:`make_fit_seg_fn` take them.

    The layout follows from what can be seen (:func:`scale_geometry`):
    where the lanes kernel covers the geometry (k * dim up to 1024) the
    points are drawn block by block into its feature-major layout,
    4 * dim bytes a point and no mask; past it into the wide pass's
    blocks (``pallas_lloyd_wide``: features down a block's rows, 4 * dim
    bytes a point where dim is a multiple of 16, no mask); plain rows,
    chunk by chunk, with their mask, for ``ops/kmeans.py`` only where
    neither takes the shape. On one v5e at 100M x 20, k = 10 the lanes
    path holds 8.0 GB and takes 13.5 ms an iteration (16.9 before its
    sums took the MXU), the row path 10.0 GB and 36.9 ms (PERF.md §6,
    PR 26, PR 29); at 2 025 000 x 784, k = 4096 the row path cannot
    compile (18.7 GB of a 15.75 GB chip: a relaid and a masked copy of
    the table) and the wide one holds 6.35 GB (PR 30).

    A lanes or wide table has to be finite, padding included (the
    kernels' matmuls multiply every point by every cluster's 0 or 1,
    and 0 x NaN is NaN): one read of the table checks it, and a
    ``make_rows`` that yields a NaN or an infinity raises ``ValueError``
    here."""
    from tpu_distalg.parallel import build_sharded

    args = (jax.ShapeDtypeStruct((1,), jnp.int32),)
    if data_seed is not None:
        args += (jax.ShapeDtypeStruct((), jnp.int32),)
    dim = jax.eval_shape(make_rows, *args).shape[1]
    lanes = scale_geometry(dim, k)
    layout = layout_of(lanes)
    chunk = (1 << 16) if lanes is None else lanes.block_points
    per = chunk * mesh.shape["data"]
    held = lanes.dim_held if layout == "wide" else dim
    with tevents.span("kmeans:prepare", mesh.local_devices, rows=n_rows,
                      bytes=-(-n_rows // per) * per * held * 4,
                      **_span_fields(k, lanes)):
        ps = build_sharded(
            mesh, n_rows, make_rows, seed=data_seed, chunk_rows=chunk,
            pack=None if lanes is None else lanes.pack)
        if lanes is not None and not bool(_all_finite(ps.data)):
            raise ValueError(
                "build_scaled: make_rows gave a NaN or an infinity; the "
                f"{layout} table has to be finite, padding rows included")
        jax.block_until_ready(ps.data)
    valid = ps.mask if lanes is None else jnp.int32(n_rows)
    return ps.data, valid, lanes


def fit_scaled(mesh: Mesh, n_rows: int, make_rows,
               config: KMeansConfig = KMeansConfig(), *,
               checkpoint_dir: str | None = None,
               checkpoint_every: int = 100,
               data_seed: int | None = None) -> KMeansResult:
    """Scale-out fit: the dataset is synthesized ON DEVICE, shard by
    shard and chunk by chunk (``parallel.build_sharded``), and the init
    centers are regenerated from k row ids — host memory is O(k) in
    ``n_rows``, unlike :func:`fit`, which (like the reference's
    driver-side ``np.concatenate`` + ``parallelize``,
    ``k-means.py:49-53``) tops out at host RAM. ``make_rows(row_ids) ->
    (n, dim)`` must be jittable and counter-based (e.g.
    ``datasets.gaussian_mixture_rows``); with ``data_seed`` it is
    called ``make_rows(row_ids, seed)`` and the seed is an argument of
    the compiled generator, not a constant in it."""
    data, valid, lanes = build_scaled(
        mesh, n_rows, make_rows, config.k, data_seed=data_seed)
    with tevents.span("kmeans:init", mesh.local_devices,
                      init=config.init, k=config.k):
        centers0 = jax.block_until_ready(
            init_centers_scaled(make_rows, n_rows, config, data_seed))
    if checkpoint_dir is not None:
        return _fit_segmented(data, valid, mesh, config, centers0,
                              checkpoint_dir, checkpoint_every, lanes)
    fn = make_fit_fn(mesh, config, lanes)
    centers, assign, n_run = fn(data, valid, centers0)
    return KMeansResult(
        centers=centers, assignments=assign, n_iterations_run=int(n_run)
    )
