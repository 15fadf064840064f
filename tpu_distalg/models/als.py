"""ALS matrix factorization (the reference's "matrix decomposition").

Re-design of ``/root/reference/matrix_computation/matrix_decomposition.py``:
the reference broadcasts the FULL dense R, U, V to every task and solves one
row per Spark task (``:46-48,52-62``) — SURVEY.md §2.3 calls this the one
place the broadcast-everything design visibly fails to scale. Here R stays
row-sharded over the mesh ``data`` axis permanently; each half-sweep is a
batched normal-equation solve under GSPMD: the k×k Gram is computed once
(the reference recomputes it in every task), the cross-shard contraction
``Uᵀ·R`` is an XLA-inserted AllReduce over ICI, and factors carry sharding
constraints so nothing dense is ever replicated needlessly.

R's rows are zero-padded to the shard count; padded rows solve to exactly
zero factor rows (zero RHS against a PD Gram), so they contribute nothing to
Grams, RMSE numerator, or the V-update — the RMSE denominator uses the true
m·n (``matrix_decomposition.py:19-21``).

**R as a ratings list** (PR 36) is another trainer in this file
(:func:`fit_ratings`, :func:`build_ratings_table`,
``ops/als_sparse.py``): one normal-equation system an owner, chosen by
what the loader's ``meta`` states (``layout: ratings``); the benchmark's
cell ``als100_253m_sweep1`` and ``PERF.md`` hold its chip readings.

Cost attribution of the DENSE path from before the ledger (a claim of
its day, not a record: no benchmark cell runs the dense fit and
``PERF_LEDGER.jsonl`` has no line of it; 4096×16384 rank-64, one v5e,
``scripts/als_profile.py`` — scan-wrapped component benchmarks):
~2.16 ms/sweep total = solves ~1.5-1.7 ms + per-sweep RMSE ~1.4 ms
(overlapped by XLA). The sweep is bound by full passes over the 268 MB
R (two solve right-hand sides + the RMSE diff) with the HIGHEST-
precision multi-pass matmuls adding ~30-40% — and those pins are
load-bearing: DEFAULT-precision right-hand sides or a HIGH (bf16x3)
RMSE save ~0.4 ms each but cost the exact rank-k recovery this module
asserts (final rmse 2e-5). Rejected, measured: a blocked RMSE that
avoids materialising the (m, n) diff runs SLOWER (1.67 vs 1.40 ms —
the scan serialises and the narrow matmuls under-fill the MXU), and
an algebraic RMSE via ‖R‖² − 2·tr((UᵀR)V) + tr((UᵀU)(VᵀV)) dies on
f32 cancellation (resolving rmse 2e-5 against ‖R‖²~1e8 needs ~10
significant digits). The design is at its traffic floor given the
precision contract.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from tpu_distalg.ops import linalg
from tpu_distalg.parallel import (
    DATA_AXIS,
    data_parallel,
    mesh_on_tpu,
    pad_rows,
    tree_allreduce_sum,
)
from tpu_distalg.utils import metrics


@dataclasses.dataclass(frozen=True)
class ALSConfig:
    """Knob names follow ``matrix_decomposition.py:12-17``."""

    lam: float = 0.01
    m: int = 100
    n: int = 500
    k: int = 10
    n_iterations: int = 5
    seed: int = 0


@dataclasses.dataclass
class ALSResult:
    U: jax.Array
    V: jax.Array
    rmse_history: jax.Array  # per-sweep RMSE
    # the sparse trainer's held-out RMSE per sweep (None on dense R)
    heldout_history: jax.Array | None = None

    @property
    def final_rmse(self) -> float:
        return float(self.rmse_history[-1])


def synthesize_rank_k(config: ALSConfig) -> np.ndarray:
    """R = U₀·V₀ᵀ with U₀, V₀ ~ U[0,1) — the reference's synthetic
    exactly-rank-k target (``matrix_decomposition.py:42``)."""
    rng = np.random.default_rng(config.seed)
    U0 = rng.random((config.m, config.k), dtype=np.float32)
    V0 = rng.random((config.n, config.k), dtype=np.float32)
    return U0 @ V0.T


def model_padded_n(config: ALSConfig, mesh: Mesh) -> int:
    """Columns of R (= rows of V) after padding ``n`` up to a multiple
    of the model-axis size, so the model-parallel V sharding ALWAYS
    engages (it used to silently replicate V whenever
    ``n % n_model != 0`` — VERDICT weak #4). Padded columns are zero →
    their V rows solve to exactly zero (zero RHS against a PD Gram) and
    touch neither the U-update Gram nor the RMSE; the RMSE denominator
    and the Gram regularisation keep using the TRUE ``config.n``."""
    from tpu_distalg.parallel import MODEL_AXIS

    n_model = mesh.shape[MODEL_AXIS]
    return -(-config.n // n_model) * n_model


def make_fit_fn(mesh: Mesh, config: ALSConfig, meta: dict | None = None):
    """The jitted fit. ``meta`` is what the loader states of R: a
    ratings list (``layout: ratings``) takes the sparse trainer
    (:func:`_make_fit_fn_sparse`, another signature: the packed
    ratings, then the two factor tables), anything else the dense one."""
    if meta is not None and meta.get("layout") == RATINGS_LAYOUT:
        return _make_fit_fn_sparse(mesh, config, meta)
    import warnings

    from tpu_distalg.parallel import MODEL_AXIS, partition

    denom = config.m * config.n  # true element count, not padded
    # shard the item factor over the model axis — the model-parallel
    # einsum SURVEY.md §2.3 calls for, replacing the reference's
    # broadcast of full V to every task (:46-48). fit() pads R's
    # columns to model_padded_n, so with R padded the sharding ALWAYS
    # engages; a caller handing this closure an unpadded R gets a
    # LOGGED disengage instead of the old silent replication.
    n_model = mesh.shape[MODEL_AXIS]
    n_pad = model_padded_n(config, mesh)

    def _v_engaged(n_cols: int) -> bool:
        if n_model <= 1:
            return False
        if n_cols % n_model:
            warnings.warn(
                f"ALS model axis DISENGAGED: R has {n_cols} columns, "
                f"not a multiple of the model-axis size {n_model} — V "
                f"will be replicated. Pad R's columns to {n_pad} "
                "(als.fit does) to engage the model-parallel sharding.",
                stacklevel=3)
            return False
        return True

    def fit(R, U0, V0):
        v_engaged = _v_engaged(R.shape[1])
        def sweep(carry, _):
            U, V = carry
            # U-update: (VᵀV + λ·n·I) uᵢ = Vᵀ R[i,:]  (:52-54, :24-33)
            G_v = linalg.gram(V, config.lam, config.n)
            U = linalg.solve_factor_block(G_v, V, R)
            U = partition.constrain(U, "U", "als_train", mesh)
            # V-update against Rᵀ: (UᵀU + λ·m·I) vⱼ = Uᵀ R[:,j]  (:60-62)
            G_u = linalg.gram(U, config.lam, config.m)
            V = linalg.solve_factor_block(G_u, U, R.T)
            if v_engaged:
                V = partition.constrain(V, "V", "als_train", mesh)
            # padded rows are exactly zero on both sides; 'highest'
            # precision keeps the reconstruction error measurement from
            # being floored by TPU bf16 matmul passes
            diff = R - jnp.matmul(U, V.T, precision=lax.Precision.HIGHEST)
            err = jnp.sqrt(jnp.sum(diff * diff) / denom)  # :19-21
            return (U, V), err

        (U, V), errs = jax.lax.scan(
            sweep, (U0, V0), None, length=config.n_iterations
        )
        return U, V, errs

    return jax.jit(fit)


def fit(mesh: Mesh, config: ALSConfig = ALSConfig(),
        R: np.ndarray | None = None,
        *,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 5) -> ALSResult:
    """Fit U·Vᵀ ≈ R; optionally checkpointed per ``checkpoint_every``
    sweeps (carry = the (U, V) factor pair; ALS sweeps are
    deterministic functions of the factors, so segmented and straight
    runs are bitwise-identical)."""
    if R is None:
        R = synthesize_rank_k(config)
    elif R.shape != (config.m, config.n):
        # caller-supplied R wins: m/n drive the RMSE denominator, the
        # Gram regularisation scale, and the U truncation
        config = dataclasses.replace(config, m=R.shape[0], n=R.shape[1])
    n_shards = mesh.shape[DATA_AXIS]
    R_padded, _mask = pad_rows(np.asarray(R, dtype=np.float32), n_shards)
    # column padding engages the model-axis V sharding for ANY n (the
    # padded columns are zero → zero V rows, algebraically inert)
    n_pad = model_padded_n(config, mesh)
    if n_pad != config.n:
        R_padded = np.pad(R_padded, ((0, 0), (0, n_pad - config.n)))

    rng = np.random.default_rng(config.seed + 1)
    # U0 is never read: the first half-sweep recomputes U from (V, R)
    # exactly as the reference's first parallelize(range(m)) pass does.
    # V0's RANDOM entries cover only the true n rows (the padded tail
    # is zero and never read either — the first sweep's U-update uses
    # V0, whose padded rows multiply R's zero columns).
    U0 = np.zeros((R_padded.shape[0], config.k), dtype=np.float32)
    V0 = np.zeros((n_pad, config.k), dtype=np.float32)
    V0[: config.n] = rng.random((config.n, config.k), dtype=np.float32)

    from tpu_distalg.parallel import partition

    R_dev = partition.put(R_padded, "R", "als_train", mesh)
    U_dev = partition.put(U0, "U", "als_train", mesh)
    V_dev = partition.put(V0, "V0", "als_train", mesh)

    if checkpoint_dir is None:
        fn = make_fit_fn(mesh, config)
        U, V, errs = fn(R_dev, U_dev, V_dev)
        metrics.guard_finite(errs, "ALS rmse history")
        return ALSResult(U=U[: config.m], V=V[: config.n],
                         rmse_history=errs)

    from tpu_distalg.utils import checkpoint as ckpt

    def run_seg(fn, state, t0):
        del t0  # sweeps carry no PRNG; the factors are the whole state
        U, V = state
        U = partition.put(U, "U", "als_train", mesh)
        V = partition.put(V, "V0", "als_train", mesh)
        U, V, errs = fn(R_dev, U, V)
        return (U, V), errs

    (U, V), errs, _ = ckpt.run_segmented(
        checkpoint_dir, checkpoint_every, config.n_iterations,
        make_seg_fn=lambda seg: make_fit_fn(
            mesh, dataclasses.replace(config, n_iterations=seg)),
        run_seg=run_seg,
        state0=(U_dev, V_dev),
        tag="als",
    )
    return ALSResult(
        U=jnp.asarray(U)[: config.m], V=jnp.asarray(V)[: config.n],
        rmse_history=jnp.asarray(errs),
    )


# ------------------------------------------------------------ sparse R
#
# R as a ratings list: one normal-equation system an owner
# (``ops/als_sparse.py``). The loader's ``meta`` states the layout
# (``layout: ratings``) and :func:`make_fit_fn` picks the trainer from
# it, as ``row_format`` picks SSGD's hashed trainer; no flag names it.

RATINGS_LAYOUT = "ratings"
# the planted model and the degree sequences of the seeded table
RATINGS_DEFAULTS = dict(d_min=20, user_d_max=200_000, item_d_max=500_000,
                        mean=50.0, scale=6.0, noise=15.0)


def _sparse_geometry(k: int, n_users: int, n_items: int,
                     stated: dict | None):
    """The pack's geometry: what the caller states, else the published
    shape's scaled to the sides (``als_sparse.geometry_for``)."""
    from tpu_distalg.ops import als_sparse

    if stated:
        return als_sparse.SparseGeometry(k=k, **stated)
    return als_sparse.geometry_for(k, n_users, n_items)


def _ratings_meta(geom, plans, n_ratings: int, n_heldout: int,
                  n_shards: int, on_tpu: bool = False,
                  hbm_bytes: int | None = None, **extra) -> dict:
    from tpu_distalg.ops import als_sparse

    pu, pi = plans
    tables = (pu.static.table_rows + pi.static.table_rows) \
        * geom.width * 4
    # what a half holds beside the table and the tables: the heavy
    # class's accumulator and a batch of Gramians
    fit = max(p.static.heavy[3] + 1 + geom.batch for p in plans) \
        * geom.width ** 2 * 4

    def gathers_on(on_tpu):
        # a half gathers from the OTHER side's table: the user half first
        return tuple(als_sparse.gather_plan(o.static, geom, on_tpu)
                     for o in (pi, pu))

    gathers = gathers_on(on_tpu)
    # what the Mosaic gather is handed ready made
    # (``als_sparse.gather_lists``: the loader's, made once): a word a
    # slot held for its resident row and half a word for the cold list
    lists = sum(6 * p.slots_held for p, g in zip(plans, gathers)
                if g.form == "mosaic")
    packed = (pu.slots_held + pi.slots_held) * 8
    # they have to fit the chip beside the table: XLA's gather, which
    # wants none, where they do not
    no_room = hbm_bytes is not None and lists > 0 \
        and packed + lists + tables + fit > hbm_bytes
    if no_room:
        gathers, lists = gathers_on(False), 0
    solve = als_sparse.solve_plan(geom, on_tpu)
    resident = tuple(
        als_sparse.resident_slots(p, o, g.hot_row0) if g.resident_rows
        else 0 for p, o, g in zip(plans, (pi, pu), gathers))
    # a half's cold slots: the lists' live entries
    cold = tuple(p.slots_held - r if g.form == "mosaic" else 0
                 for p, r, g in zip(plans, resident, gathers))
    held = packed + lists
    return dict(
        layout=RATINGS_LAYOUT, n_users=int(pu.degrees.shape[0]),
        n_items=int(pi.degrees.shape[0]), n_ratings=int(n_ratings),
        k=geom.k, geometry=geom, user=pu, item=pi, n_shards=n_shards,
        n_heldout=int(n_heldout), ratings_bytes=held, factor_bytes=tables,
        blocks=(pu.static.n_blocks, pi.static.n_blocks),
        padding_share=(pu.slots_held + pi.slots_held)
        / max(2 * n_ratings, 1),
        gather_resident_rows=tuple(g.resident_rows for g in gathers),
        gather_resident_shares=tuple(
            r / max(p.slots_held, 1) for r, p in zip(resident, plans)),
        gather_resident_share=sum(resident)
        / max(pu.slots_held + pi.slots_held, 1),
        gather_cold_slots=cold, gather_list_bytes=lists,
        gather_lists_fit=not no_room, gather=gathers, solve=solve,
        forms=dict(als_gather_form="/".join(
            dict.fromkeys(g.form for g in gathers)),
            als_gram_form="xla", als_solve_form=solve.form,
            # a batch of Gramians is made owner-major; the Mosaic solve
            # reads it so, XLA's turns it to lanes first
            als_gram_layout="owners" if solve.form == "mosaic"
            else "lanes"),
        **extra)


def _prepare_fields(meta: dict) -> dict:
    return dict(ratings=meta["n_ratings"], users=meta["n_users"],
                items=meta["n_items"], k=meta["k"],
                user_blocks=meta["blocks"][0],
                item_blocks=meta["blocks"][1],
                padding_share=round(meta["padding_share"], 4),
                **_gather_fields(meta))


def _gather_fields(meta: dict) -> dict:
    """The gather's form and how often its resident range engages: the
    range's rows in the items' and the users' table (what the user and
    the item half read) and the share of the slots held that point into
    it. Then what the Mosaic form is handed ready made: who lists a
    chunk's cold slots (``loader``, once; ``none`` where no half takes
    the kernel, ``no room`` where it would but the lists do not fit the
    chip beside the table), the lists' live entries a half with their
    share of the slots held, who makes a slot's two row addresses (its
    row of the resident range and its row of the table: ``loader``,
    once), the bytes of all the loader makes for the kernel, and who
    writes the rating's and the validity's lanes."""
    mosaic = any(g.form == "mosaic" for g in meta["gather"])
    held = meta["user"].slots_held + meta["item"].slots_held
    made = "loader" if mosaic else "none"
    return dict(
        als_gather_form=meta["forms"]["als_gather_form"],
        gather_resident_rows=list(meta["gather_resident_rows"]),
        gather_resident_share=round(meta["gather_resident_share"], 4),
        gather_cold_list=made if meta["gather_lists_fit"] else "no room",
        gather_cold_slots=list(meta["gather_cold_slots"]),
        gather_cold_share=round(
            sum(meta["gather_cold_slots"]) / max(held, 1), 4),
        gather_slot_rows=made,
        gather_list_bytes=meta["gather_list_bytes"],
        gather_lanes="kernel" if mosaic else "xla")


def segment_fields(meta: dict) -> dict:
    """What a ``train:segment`` span says of the sparse trainer: each
    piece's form, how often the gather's resident range engages and how
    many systems a tile of the solve holds (0 in XLA's form; the form
    is one for every batch of a run, so a size says all a share would)."""
    return {"layout": meta["layout"], **meta["forms"],
            **_gather_fields(meta),
            "solve_tile_systems": meta["solve"].tile_systems}


def ratings_from_coo(users, items, ratings, n_users: int, n_items: int,
                     k: int, mesh: Mesh, *, heldout=None, **geom_kw):
    """The loader of an explicit ratings list held on the host: packs
    both sides (``als_sparse.pack_coo``) and states the layout. A pair
    listed twice is held twice. ``heldout`` is ``(users, items,
    ratings)`` never trained on, or None. Returns ``(arrays, meta)``."""
    from tpu_distalg.ops import als_sparse
    from tpu_distalg.telemetry import events as tevents

    geom = _sparse_geometry(k, n_users, n_items, geom_kw)
    S = mesh.shape[DATA_AXIS]
    users = np.asarray(users, np.int64)
    items = np.asarray(items, np.int64)
    with tevents.span("als:pack", mesh.local_devices,
                      ratings=int(users.shape[0])):
        pu = als_sparse.plan_side(
            np.bincount(users, minlength=n_users), geom, S)
        pi = als_sparse.plan_side(
            np.bincount(items, minlength=n_items), geom, S)
        ui, uv = als_sparse.pack_coo(pu, geom, users, items, ratings,
                                     pi.row_of_owner, pi.static.zero_row)
        ii, iv = als_sparse.pack_coo(pi, geom, items, users, ratings,
                                     pu.row_of_owner, pu.static.zero_row)
    if heldout is None:
        heldout = (np.zeros(1, np.int64), np.zeros(1, np.int64),
                   np.zeros(1, np.float32))
        n_heldout = 0
    else:
        n_heldout = len(heldout[0])
    hu = pu.row_of_owner[np.asarray(heldout[0], np.int64)]
    hv = pi.row_of_owner[np.asarray(heldout[1], np.int64)]
    meta = _ratings_meta(geom, (pu, pi), users.shape[0], n_heldout, S,
                         mesh_on_tpu(mesh),
                         tevents.memory_limit(mesh.local_devices[:1]))
    sides = [tuple(_put(a, "ratings", mesh) for a in side)
             for side in ((ui, uv), (ii, iv))]
    pieces = [_put(p.piece_slot, "ratings", mesh) for p in (pu, pi)]
    held = tuple(_put(a, "heldout", mesh) for a in
                 (hu.astype(np.int32), hv.astype(np.int32),
                  np.asarray(heldout[2], np.float32)))
    return _ratings_arrays(sides, pieces, held, meta, mesh), meta


def _ratings_arrays(sides, pieces, held, meta: dict, mesh: Mesh):
    """What the trainer's function takes before the two factor tables:
    a side's packed indices, ratings and pieces' slots, the users' then
    the items', and the held-out pairs. A half whose gather takes the
    Mosaic form holds its ratings as the kernel reads them and three
    arrays more at the end, its slots' resident rows, its cold lists
    and their counts (``als_sparse.gather_lists``, made here, once, on
    the device; the pack's ratings are given up to it, its indices stay:
    they are the cold slots' rows of the table)."""
    from tpu_distalg.ops import als_sparse
    from tpu_distalg.parallel import partition
    from tpu_distalg.telemetry import events as tevents

    lists = []
    rows = partition.leaf_sharding("als_sparse", "ratings", mesh)
    for s, gather in enumerate(meta["gather"]):
        if gather.form != "mosaic":
            continue
        with tevents.span("als:lists", mesh.local_devices, side=s,
                          cold_slots=meta["gather_cold_slots"][s],
                          slots=(meta["user"], meta["item"])[s].slots_held):
            idx, val = sides[s]
            made = jax.jit(
                functools.partial(als_sparse.gather_lists, gather=gather),
                donate_argnums=1, out_shardings=(rows,) * 4)(idx, val)
            jax.block_until_ready(made)
            # beside the pack's indices, in place of its ratings, which
            # were donated
            tevents.current().fields["bytes"] = metrics.nbytes(idx, made)
        sides[s] = (idx, made[0])
        lists += made[1:]
    return (*sides[0], pieces[0], *sides[1], pieces[1], *held, *lists)


def _put(x, leaf: str, mesh: Mesh):
    """One array placed as the ``als_sparse`` rule table says."""
    from tpu_distalg.parallel import partition

    return partition.put(x, leaf, "als_sparse", mesh)


def _stub_rows(plan, n_ratings: int):
    """``int32 (n_ratings,)`` on the device: the factor row of the owner
    of each place of a side's owner-ordered stub list. The rows' steps
    scattered at the owners' offsets, then one running sum (exact in
    int32); owners with no rating share an offset and their steps add
    up."""
    deg, rows = plan.degrees, plan.row_of_owner.astype(np.int64)
    off = np.cumsum(deg) - deg
    step = np.diff(rows, prepend=0)
    return _running_rows(jnp.asarray(off, jnp.int32),
                         jnp.asarray(step, jnp.int32), n_ratings)


def _running_rows(off, step, n_ratings: int):
    marks = jnp.zeros((n_ratings,), jnp.int32).at[off].add(step, mode="drop")
    return jnp.cumsum(marks)


_running_rows = jax.jit(_running_rows, static_argnames="n_ratings")


def _planted_table(plan, gen, seed, side: int, width: int):
    """The planted factor of every row of a side's table, zero where a
    row has no owner."""
    own = jnp.asarray(plan.owner_of_row)

    @jax.jit
    def build(own, seed):
        rows = gen.planted(jnp.maximum(own, 0), seed, side)
        rows = jnp.where((own >= 0)[:, None], rows, 0.0)
        return jnp.pad(rows, ((0, 0), (0, width - rows.shape[1])))

    return build(own, seed)


def side_generator(mesh: Mesh, geom, gen, side: int, zero_row: int):
    """The jitted draw of one side's packed ratings, a shard's blocks to
    a chip: ``f(k0, n_valid, seg_owner, stub_row_other, planted_other,
    seed) -> (idx, val)``."""
    from jax.sharding import PartitionSpec as P

    from tpu_distalg.parallel import partition

    L, k = geom.seg_slots, geom.k

    def block(args, stub_row_other, planted_other, seed):
        k0, n_valid, owner = args
        lane = jnp.arange(L, dtype=jnp.int32)[None, :]
        ok = lane < n_valid[:, None]
        mine = (k0[:, None] + lane).astype(jnp.uint32)
        mine = jnp.where(ok, mine, jnp.uint32(0))
        if side == 0:        # a user's stub: its item stub, pair id p
            pair, theirs = mine, gen.item_stub(mine, seed)
        else:                # an item's stub: its user stub is the pair
            theirs = gen.user_stub(mine, seed)
            pair = theirs
        row = stub_row_other.at[theirs.astype(jnp.int32).reshape(-1)].get(
            mode="promise_in_bounds")
        other = planted_other.at[row].get(mode="promise_in_bounds")
        own = gen.planted(jnp.maximum(owner, 0), seed, side)
        dot = jnp.sum(own[:, None, :]
                      * other.reshape(-1, L, other.shape[-1])[..., :k],
                      axis=-1)
        r = gen.rating(dot, pair, seed)
        idx = jnp.where(ok, row.reshape(-1, L), zero_row)
        return idx.astype(jnp.int32).reshape(geom.block_shape), \
            jnp.where(ok, r, 0.0).reshape(geom.block_shape)

    def body(k0, n_valid, owner, stub_row_other, planted_other, seed):
        return jax.lax.map(
            lambda a: block(a, stub_row_other, planted_other, seed),
            (k0, n_valid, owner))

    spec = P(DATA_AXIS)
    return jax.jit(
        data_parallel(body, mesh,
                      in_specs=(spec, spec, spec, P(), P(), P()),
                      out_specs=(spec, spec)),
        out_shardings=(partition.leaf_sharding(
            "als_sparse", "ratings", mesh),) * 2)


def plan_ratings(n_ratings: int, n_users: int, n_items: int, k: int,
                 n_shards: int = 1, *, n_heldout: int = 0, degrees=None,
                 geometry: dict | None = None, on_tpu: bool = False,
                 hbm_bytes: int | None = None, **gen_kw) -> dict:
    """The host's half of the seeded loader: both degree sequences
    (functions of the sizes alone, ``datasets.power_law_degrees``; or
    ``degrees=(users', items')``), both sides' pack, and the ``meta``
    that states the layout. No device is touched and no seed is read:
    every seed's table has these sizes. ``on_tpu`` says where the fit
    will run (the loaders pass their mesh's answer): the gather takes
    its Mosaic form only there (``als_sparse.gather_plan``), and only
    where what the loader makes for it fits ``hbm_bytes``, a device's
    memory, beside the table (None: not known, and not asked)."""
    from tpu_distalg.ops import als_sparse
    from tpu_distalg.utils import datasets as dsets

    par = dict(RATINGS_DEFAULTS, **gen_kw)
    geom = _sparse_geometry(k, n_users, n_items, geometry)
    if degrees is None:
        degrees = (
            dsets.power_law_degrees(n_users, n_ratings, par["d_min"],
                                    par["user_d_max"], 1),
            dsets.power_law_degrees(n_items, n_ratings, par["d_min"],
                                    par["item_d_max"], 2))
    du, di = (np.asarray(d, np.int64) for d in degrees)
    if du.sum() != n_ratings or di.sum() != n_ratings:
        raise ValueError(
            f"the two degree sequences add up to {du.sum()} and "
            f"{di.sum()}, not to {n_ratings} ratings")
    if (du.shape[0], di.shape[0]) != (n_users, n_items):
        raise ValueError(
            f"degrees of {du.shape[0]} users and {di.shape[0]} items "
            f"for a table of {n_users} by {n_items}")
    plans = (als_sparse.plan_side(du, geom, n_shards),
             als_sparse.plan_side(di, geom, n_shards))
    return _ratings_meta(geom, plans, n_ratings, n_heldout, n_shards,
                         on_tpu, hbm_bytes,
                         generator=tuple(sorted(par.items())))


def build_ratings_table(n_ratings: int, n_users: int, n_items: int,
                        k: int, mesh: Mesh, *, data_seed: int = 0,
                        **plan_kw):
    """The loader of a seeded ratings table: ``n_ratings`` explicit
    ratings of ``n_users`` by ``n_items`` made ON DEVICE, packed for the
    sparse trainer, and the ``meta`` that states the layout
    (:func:`plan_ratings`, which ``plan_kw`` reach: the sizes never
    depend on the seed, so the pack and every compiled program are the
    same for every seed). The seed pairs the two sides' stubs, plants
    the model and draws the noise (``datasets.seeded_ratings``);
    ``n_heldout`` more pairs are drawn the same way and never trained
    on. Returns ``(arrays, meta)``: ``arrays`` is what the trainer's
    function takes before the two factor tables
    (:func:`_ratings_arrays`)."""
    from tpu_distalg.ops import als_sparse
    from tpu_distalg.telemetry import events as tevents
    from tpu_distalg.utils import datasets as dsets

    S = mesh.shape[DATA_AXIS]
    seed = jnp.int32(data_seed)
    devices = mesh.local_devices
    with tevents.span("als:prepare", devices, ratings=n_ratings,
                      users=n_users, items=n_items, k=k,
                      layout=RATINGS_LAYOUT):
        prepare = tevents.current().fields
        with tevents.span("als:pack", devices, ratings=n_ratings):
            meta = plan_ratings(
                n_ratings, n_users, n_items, k, S,
                on_tpu=mesh_on_tpu(mesh),
                hbm_bytes=tevents.memory_limit(devices[:1]), **plan_kw)
            meta["data_seed"] = int(data_seed)
            geom, plans = meta["geometry"], (meta["user"], meta["item"])
            stubs = [tuple(_put(a, "ratings", mesh) for a in (
                *als_sparse.segment_stubs(p, geom), p.seg_owner))
                for p in plans]
            tevents.current().fields["bytes"] = metrics.nbytes(stubs)
        prepare.update(_prepare_fields(meta))
        par = dict(meta["generator"])
        gen = dsets.seeded_ratings(
            n_ratings, k, mean=par["mean"], scale=par["scale"],
            noise=par["noise"])
        with tevents.span("als:generate", devices,
                          slots=plans[0].slots_held + plans[1].slots_held):
            stub_rows = [_stub_rows(p, n_ratings) for p in plans]
            planted = [_planted_table(p, gen, seed, s, geom.width)
                       for s, p in enumerate(plans)]
            sides = []
            for s in (0, 1):
                o = 1 - s
                fn = side_generator(mesh, geom, gen, s,
                                    plans[o].static.zero_row)
                sides.append(fn(*stubs[s], stub_rows[o], planted[o], seed))
            jax.block_until_ready(sides)
            # the sides stay; the stubs' rows and the planted tables go
            # once the held-out pairs are drawn
            tevents.current().fields["bytes"] = metrics.nbytes(
                sides, stub_rows, planted)
        with tevents.span("als:heldout", devices,
                          pairs=meta["n_heldout"]):
            held = _heldout_pairs(gen, stub_rows, planted, seed,
                                  max(meta["n_heldout"], 1), geom.k)
            jax.block_until_ready(held)
            tevents.current().fields["bytes"] = metrics.nbytes(held)
        del stub_rows, planted, stubs
        pieces = [_put(p.piece_slot, "ratings", mesh) for p in plans]
        arrays = _ratings_arrays(sides, pieces, held, meta, mesh)
        # what the loader leaves on the chip (the plan's count, with the
        # two factor tables no loader makes, is ``meta``'s and the CLI's)
        prepare["bytes"] = metrics.nbytes(arrays)
    return arrays, meta


def _heldout_pairs(gen, stub_rows, planted, seed, n: int, k: int):
    """``n`` held-out pairs as factor rows and their ratings: each side
    picked by a random stub (so by degree), rated by the planted model
    with a noise stream of its own."""
    @jax.jit
    def draw(rows_u, rows_i, pl_u, pl_i, seed):
        i = jnp.arange(n, dtype=jnp.uint32)
        ju, jv = gen.heldout_stubs(i, seed)
        hu = rows_u.at[ju.astype(jnp.int32)].get(mode="promise_in_bounds")
        hv = rows_i.at[jv.astype(jnp.int32)].get(mode="promise_in_bounds")
        dot = jnp.sum(pl_u.at[hu].get(mode="promise_in_bounds")[:, :k]
                      * pl_i.at[hv].get(mode="promise_in_bounds")[:, :k],
                      axis=1)
        return hu, hv, gen.rating(dot, i, seed, 1)

    return draw(stub_rows[0], stub_rows[1], planted[0], planted[1], seed)


def start_factors(meta: dict, mesh: Mesh, seed: int):
    """The two factor tables a fit starts from: the item side a uniform
    draw on [0, 1) hashed from the seed and the item's id (the same for
    every layout), the user side zero (the first half never reads it),
    placed as the trainer returns them."""
    from tpu_distalg.utils import datasets as dsets

    geom = meta["geometry"]
    k, W = geom.k, geom.width
    own = jnp.asarray(meta["item"].owner_of_row)

    @jax.jit
    def draw(own, seed):
        ids = jnp.maximum(own, 0).astype(jnp.uint32)[:, None] \
            * np.uint32(k) + jnp.arange(k, dtype=jnp.uint32)[None, :]
        key = dsets._mix32(seed.astype(jnp.uint32) * np.uint32(0x9E3779B1)
                           + np.uint32(0x51ED270B))
        bits = dsets._mix32(ids ^ key) >> np.uint32(8)
        rows = bits.astype(jnp.float32) * (2.0 ** -24)
        rows = jnp.where((own >= 0)[:, None], rows, 0.0)
        return jnp.pad(rows, ((0, 0), (0, W - k)))

    Theta = _put(draw(own, jnp.int32(seed)), "factors", mesh)
    X = _put(jnp.zeros((meta["user"].static.table_rows, W), jnp.float32),
             "factors", mesh)
    return X, Theta


def rows_from_owners(F, plan, width: int):
    """A side's factor table from its factors in owner order."""
    return _place_rows(jnp.asarray(F, jnp.float32),
                       jnp.asarray(plan.owner_of_row), width)


def owners_from_rows(table, plan, k: int):
    """A side's factors in owner order from its table."""
    return _take_rows(table, jnp.asarray(plan.row_of_owner), k)


def _place_rows(F, own, width: int):
    rows = jnp.where((own >= 0)[:, None], F[jnp.maximum(own, 0)], 0.0)
    return jnp.pad(rows, ((0, 0), (0, width - F.shape[1])))


def _take_rows(table, rows, k: int):
    return table[rows][:, :k]


# one trace a shape, not one a call
_place_rows = jax.jit(_place_rows, static_argnames="width")
_take_rows = jax.jit(_take_rows, static_argnames="k")


def _make_fit_fn_sparse(mesh: Mesh, config: ALSConfig, meta: dict):
    """``fit(user idx, val, pieces, item idx, val, pieces, held-out
    users', items' rows, ratings[, a Mosaic half's resident rows, cold
    lists and counts], X, Theta) -> (X, Theta, errs, seen)`` (the loaders'
    ``arrays``, :func:`_ratings_arrays`, then the two tables):
    ``config.n_iterations`` ALS iterations (the user half from Theta,
    then the item half from the new X: one function over (owners'
    ratings, the other side's table)), ``errs`` float32 ``(iterations,
    2)`` the training and the held-out RMSE after each, ``seen`` int32
    ``(iterations, 2)`` the ratings that entered each half."""
    from jax.sharding import PartitionSpec as P

    from tpu_distalg.ops import als_sparse
    from tpu_distalg.parallel import partition

    geom = meta["geometry"]
    if config.k != geom.k:
        raise ValueError(f"the table was packed for rank {geom.k}, the "
                         f"configuration asks for {config.k}")
    su, si = meta["user"].static, meta["item"].static
    n_ratings = max(meta["n_ratings"], 1)

    def half(static, other_zero_row, gather):
        n_cold = 3 if gather.form == "mosaic" else 0

        def run(idx, val, pieces, other, own, *cold):
            return als_sparse.half_sweep(
                idx, val, pieces, other, own, static=static,
                other_zero_row=other_zero_row, geom=geom,
                lam=config.lam, axis=DATA_AXIS, gather=gather,
                solve=meta["solve"], cold=cold)

        return n_cold, data_parallel(
            run, mesh, in_specs=(*(P(DATA_AXIS),) * 3, P(), P(),
                                 *(P(DATA_AXIS),) * n_cold),
            out_specs=(P(), P(), P()))

    gather_u, gather_i = meta["gather"]
    cold_u, user_half = half(su, si.zero_row, gather_u)
    cold_i, item_half = half(si, su.zero_row, gather_i)

    def fit(ui, uv, up, ii, iv, ip, hu, hv, hr, *rest):
        *cold, X, Theta = rest
        if len(cold) != cold_u + cold_i:
            raise TypeError(
                f"the gathers' forms {meta['forms']['als_gather_form']} "
                f"want {cold_u + cold_i} arrays of rows and cold lists, "
                f"{len(cold)} were handed in")

        def iteration(carry, _):
            X, Theta = carry
            X, _, seen_u = user_half(ui, uv, up, Theta, X, *cold[:cold_u])
            Theta, sse, seen_i = item_half(ii, iv, ip, X, Theta,
                                           *cold[cold_u:])
            from tpu_distalg.telemetry import names

            with jax.named_scope(names.ALS_UPDATE):
                errs = jnp.stack([
                    jnp.sqrt(jnp.maximum(sse, 0.0) / n_ratings),
                    als_sparse.heldout_rmse(X, Theta, hu, hv, hr)])
            return (X, Theta), (errs, jnp.stack([seen_u, seen_i]))

        (X, Theta), (errs, seen) = jax.lax.scan(
            iteration, (X, Theta), None, length=config.n_iterations)
        return X, Theta, errs, seen

    rep = partition.leaf_sharding("als_sparse", "factors", mesh)
    # the tables in are the tables out: no second pair is held
    return jax.jit(fit, out_shardings=(rep, rep, rep, rep),
                   donate_argnums=(9 + cold_u + cold_i,
                                   10 + cold_u + cold_i))


def fit_ratings(mesh: Mesh, config: ALSConfig, arrays, meta: dict, *,
                init=None, checkpoint_dir: str | None = None,
                checkpoint_every: int = 5) -> ALSResult:
    """Fit ``X Theta^T`` to a ratings table (:func:`build_ratings_table`
    or :func:`ratings_from_coo`). ``init`` is ``(U0, V0)`` in owner
    order, or None for the seed's draw (:func:`start_factors`). The
    checkpointed state is the factor pair in owner order, which is what
    ``tda serve`` reads; segmented and straight runs are bitwise equal
    (an iteration is a function of the factors alone)."""
    geom = meta["geometry"]
    pu, pi = meta["user"], meta["item"]
    if init is None:
        X, Theta = start_factors(meta, mesh, config.seed)
    else:
        X = _put(rows_from_owners(init[0], pu, geom.width), "factors", mesh)
        Theta = _put(rows_from_owners(init[1], pi, geom.width), "factors",
                     mesh)

    def result(X, Theta, errs):
        errs = jnp.asarray(errs).reshape(-1, 2)
        metrics.guard_finite(errs, "sparse ALS rmse history")
        return ALSResult(U=owners_from_rows(X, pu, geom.k),
                         V=owners_from_rows(Theta, pi, geom.k),
                         rmse_history=errs[:, 0],
                         heldout_history=errs[:, 1])

    if checkpoint_dir is None:
        fn = make_fit_fn(mesh, config, meta)
        X, Theta, errs, _ = fn(*arrays, X, Theta)
        return result(X, Theta, errs)

    from tpu_distalg.utils import checkpoint as ckpt

    def run_seg(fn, state, t0):
        del t0        # an iteration is a function of the factors alone
        X = _put(rows_from_owners(state[0], pu, geom.width), "factors", mesh)
        Theta = _put(rows_from_owners(state[1], pi, geom.width), "factors",
                     mesh)
        X, Theta, errs, _ = fn(*arrays, X, Theta)
        return (owners_from_rows(X, pu, geom.k),
                owners_from_rows(Theta, pi, geom.k)), errs

    (U, V), errs, _ = ckpt.run_segmented(
        checkpoint_dir, checkpoint_every, config.n_iterations,
        make_seg_fn=lambda seg: make_fit_fn(
            mesh, dataclasses.replace(config, n_iterations=seg), meta),
        run_seg=run_seg,
        state0=(owners_from_rows(X, pu, geom.k),
                owners_from_rows(Theta, pi, geom.k)),
        tag="als", span_fields=segment_fields(meta))
    errs = jnp.asarray(errs).reshape(-1, 2)
    return ALSResult(U=jnp.asarray(U), V=jnp.asarray(V),
                     rmse_history=errs[:, 0], heldout_history=errs[:, 1])


def _make_streamed_block_fns(mesh: Mesh, config: ALSConfig, n: int):
    """The three jitted pieces of one streamed sweep: the per-R-block
    U-solve + partial-contraction, the V-update from the accumulated
    contractions, and the per-block RMSE accumulation. All matmuls pin
    HIGHEST precision — the same contract the resident path carries
    (module docstring: default-precision right-hand sides cost the
    exact rank-k recovery)."""
    from jax.sharding import PartitionSpec as P

    _HI = lax.Precision.HIGHEST
    k = config.k

    def _solve_block(Rb, V, G_v):
        R = Rb[0]                                       # (bp, n)
        U_b = linalg.solve_factor_block(G_v, V, R)      # (bp, k)
        C_inc = jnp.matmul(U_b.T, R, precision=_HI)     # (k, n)
        UtU_inc = jnp.matmul(U_b.T, U_b, precision=_HI)
        return (U_b[None],) + tree_allreduce_sum((C_inc, UtU_inc))

    solve_fn = jax.jit(data_parallel(
        _solve_block, mesh,
        in_specs=(P(DATA_AXIS, None, None), P(), P()),
        out_specs=(P(DATA_AXIS, None, None), P(), P())))

    def _v_update(UtU, C):
        # (UᵀU + λ·m·I) vⱼ = (UᵀR)[:, j] — reg_rows = the factor ROW
        # count, the reference's X_dim quirk (ops/linalg.gram)
        G_u = UtU + config.lam * config.m * jnp.eye(k, dtype=UtU.dtype)
        cho = jax.scipy.linalg.cho_factor(G_u)
        return jax.scipy.linalg.cho_solve(cho, C).T     # (n, k)

    v_update_fn = jax.jit(_v_update)

    def _rmse_block(Rb, U_b, V):
        diff = Rb[0] - jnp.matmul(U_b[0], V.T, precision=_HI)
        return tree_allreduce_sum(jnp.sum(diff * diff))

    rmse_fn = jax.jit(data_parallel(
        _rmse_block, mesh,
        in_specs=(P(DATA_AXIS, None, None), P(DATA_AXIS, None, None),
                  P()),
        out_specs=P()))

    gram_fn = jax.jit(
        lambda V: linalg.gram(V, config.lam, n))
    return solve_fn, v_update_fn, rmse_fn, gram_fn


def fit_streamed(dataset, config: ALSConfig | None = None, *,
                 rmse_every: int = 1) -> ALSResult:
    """ALS over a :class:`~tpu_distalg.data.ShardedDataset` of R rows
    (``dense_rows_f32`` layout) — R never resident: each half-sweep
    STREAMS the row blocks through the prefetch pipeline (gather ∥ H2D
    ∥ solve), so R is bounded by DISK, not HBM — the scale SURVEY §2.3
    says the reference's broadcast-everything design visibly fails at,
    and the cap VERDICT "what's missing" #3 flagged for this repo.

    Per sweep: one streaming pass solves the U row-blocks against the
    current V while accumulating the cross-shard contractions
    ``UᵀR (k, n)`` and ``UᵀU (k, k)`` block by block (the only state
    that persists between blocks is O(k·n) — never R); the V-update
    then solves against the accumulated normal equations, exactly the
    resident sweep's algebra with the n-column contraction distributed
    over blocks. ``rmse_every=r`` streams ONE extra evaluation pass
    every r-th sweep (``0``: once, after the final sweep) — the honest
    cost of measuring ‖R − UVᵀ‖ when R lives on disk. Zero padding
    rows (the builder's) solve to zero U rows and touch nothing.

    Trajectories are bitwise-identical across dataset backends (same
    staged bytes, same jitted block fns — tests/test_data.py); vs the
    resident :func:`fit` they agree to float tolerance (the blocked
    contraction changes the summation order, not the algebra)."""
    import contextlib

    mesh = dataset.mesh
    meta = dataset.meta
    m_true = int(meta.get("m", dataset.n2))
    n = dataset.pd
    if config is None:
        config = ALSConfig(m=m_true, n=n, k=int(meta.get("k", 10)))
    if (config.m, config.n) != (m_true, n):
        config = dataclasses.replace(config, m=m_true, n=n)
    k = config.k
    nb, S = dataset.n_blocks, dataset.n_shards
    solve_fn, v_update_fn, rmse_fn, gram_fn = _make_streamed_block_fns(
        mesh, config, n)

    from tpu_distalg.parallel import partition

    rng = np.random.default_rng(config.seed + 1)
    V = partition.put(rng.random((n, k), dtype=np.float32),
                      "V0", "als_train", mesh)
    # every sweep streams the blocks in order: one block per shard per
    # step, the same LOCAL block id on every shard
    ids = np.tile(np.arange(nb, dtype=np.int64)[:, None, None],
                  (1, S, 1))
    serialize = not dataset.on_tpu
    denom = config.m * config.n
    errs = []
    from tpu_distalg.telemetry import events as tevents

    for sweep in range(config.n_iterations):
        tevents.mark(f"als_stream:sweep@{sweep}", emit_event=False)
        G_v = gram_fn(V)
        C = jnp.zeros((k, n), jnp.float32)
        UtU = jnp.zeros((k, k), jnp.float32)
        us = []
        with contextlib.closing(dataset.stream(ids)) as batches:
            for staged in batches:
                U_b, C_inc, UtU_inc = solve_fn(staged, V, G_v)
                C, UtU = C + C_inc, UtU + UtU_inc
                us.append(U_b)
                if serialize:
                    # tda: ignore[TDA011] -- deliberate: on host
                    # (CPU-mesh) backends this bounds the stream's
                    # in-flight blocks; never taken on TPU
                    jax.block_until_ready(UtU)
        V = v_update_fn(UtU, C)
        want_rmse = (rmse_every and (sweep + 1) % rmse_every == 0) or \
            (sweep + 1 == config.n_iterations)
        if want_rmse:
            acc = jnp.float32(0.0)
            with contextlib.closing(dataset.stream(ids)) as batches:
                for b, staged in enumerate(batches):
                    acc = acc + rmse_fn(staged, us[b], V)
                    if serialize:
                        # tda: ignore[TDA011] -- deliberate: see the
                        # solve loop above (host-backend stream bound)
                        jax.block_until_ready(acc)
            errs.append(jnp.sqrt(acc / denom))
    U = jnp.stack(us, axis=1).reshape(dataset.n2, k)
    errs = jnp.stack(errs) if errs else jnp.zeros((0,))
    metrics.guard_finite(errs, "streamed ALS rmse history")
    return ALSResult(U=U[: config.m], V=V, rmse_history=errs)


def fit_rowstore(config: ALSConfig = ALSConfig(), *,
                 density: float = 0.08, ps_shards: int = 2,
                 user_block: int = 32,
                 model_budget_rows: int | None = None) -> dict:
    """Observed-entry ALS with the item factor V living in the
    SHARDED ROW STORE (``cluster/rowstore.py``, table ``als_train`` —
    the same rule table the in-process trainer places V under): the
    worker holds U and the ratings locally but NEVER materializes V
    whole. Each user-block U-solve pulls only the V rows that block's
    observed items reference, each V-update pushes per-row deltas
    (one contribution at the store's own version → age 0, weight 1 —
    an exact row replacement through the weighted-merge arithmetic),
    and items nobody rated are never pulled, pushed, or versioned.

    ``model_budget_rows`` is the >1-host-RAM contract: the peak V rows
    any single pull materializes must stay under it or the fit RAISES
    (the row store's streaming claim fails loudly, never silently
    densifies). numpy-only — a host fleet worker, no mesh.

    Returns ``{U, V, rmse_history, peak_pull_rows,
    sparse_pull_fraction, rows_pulled, rows_pushed}`` where the
    fraction is measured pulls over the dense pull-everything
    baseline and V is a final snapshot (test/report surface, outside
    the budget)."""
    from tpu_distalg.cluster import rowstore as _rowstore

    rng = np.random.default_rng(config.seed)
    m, n, k, lam = config.m, config.n, config.k, config.lam
    R = synthesize_rank_k(config)
    observed = rng.random((m, n)) < density
    user_cols = [np.flatnonzero(observed[i]) for i in range(m)]
    item_users = [np.flatnonzero(observed[:, j]) for j in range(n)]
    touched_items = np.flatnonzero(observed.any(axis=0))
    n_obs = int(observed.sum())
    if not n_obs:
        raise ValueError("no observed entries at this density/seed")

    store = _rowstore.RowStore(
        {"V": rng.random((n, k), dtype=np.float32)},
        table="als_train", n_shards=ps_shards)
    U = rng.random((m, k), dtype=np.float32)

    peak_pull = 0
    rows_pulled = 0
    rows_pushed = 0
    n_pulls = 0

    def pull(rows: np.ndarray) -> np.ndarray:
        nonlocal peak_pull, rows_pulled, n_pulls
        if model_budget_rows is not None \
                and rows.shape[0] > model_budget_rows:
            raise RuntimeError(
                f"a pull needs {rows.shape[0]} V rows at once but the "
                f"model budget is {model_budget_rows} — shrink the "
                f"user blocks, not the honesty of the claim")
        peak_pull = max(peak_pull, int(rows.shape[0]))
        rows_pulled += int(rows.shape[0])
        n_pulls += 1
        vals, _vers = store.pull_rows("V", rows)
        return vals

    def solve(F: np.ndarray, r: np.ndarray) -> np.ndarray:
        # (FᵀF + λ·|obs|·I) x = Fᵀ r — the reference's per-row normal
        # equations, restricted to the OBSERVED entries
        G = F.T @ F + lam * F.shape[0] * np.eye(k, dtype=np.float64)
        return np.linalg.solve(G, F.T @ r)

    errs = []
    for _sweep in range(config.n_iterations):
        # U half-sweep: per user block, pull the union of the block's
        # observed item rows once
        for b0 in range(0, m, user_block):
            users = range(b0, min(b0 + user_block, m))
            need = np.unique(np.concatenate(
                [user_cols[i] for i in users
                 if user_cols[i].size] or [np.empty(0, np.int64)]))
            if not need.size:
                continue
            Vblk = pull(need).astype(np.float64)
            for i in users:
                cols = user_cols[i]
                if not cols.size:
                    continue
                sel = np.searchsorted(need, cols)
                U[i] = solve(Vblk[sel],
                             R[i, cols].astype(np.float64)
                             ).astype(np.float32)
        # V half-sweep: per item block, solve the touched rows from
        # local U and push the per-row deltas (pull old values first —
        # the delta is the wire object, same as every rowstore push);
        # blocked like the U pulls so the budget holds on BOTH halves
        U64 = U.astype(np.float64)
        sq_err = 0.0
        item_blk = (min(user_block * 4, model_budget_rows)
                    if model_budget_rows else user_block * 4)
        for t0 in range(0, touched_items.shape[0], item_blk):
            items = touched_items[t0:t0 + item_blk]
            old = pull(items)
            new = np.empty_like(old)
            for t, j in enumerate(items):
                users = item_users[j]
                new[t] = solve(U64[users],
                               R[users, j].astype(np.float64)
                               ).astype(np.float32)
            store.merge_rows(store.version, [
                (0, {"V": (items, new - old, store.version)})])
            rows_pushed += int(items.shape[0])
            # observed-entry RMSE from the rows already in hand
            mask = observed[:, items]
            pred = np.einsum("ik,tk->it", U, new)[mask]
            sq_err += np.sum((pred - R[:, items][mask]) ** 2)
        errs.append(np.sqrt(sq_err / n_obs))

    dense_rows = n_pulls * n
    return {
        "U": U,
        "V": store.snapshot()["V"],
        "rmse_history": np.asarray(errs, np.float32),
        "peak_pull_rows": peak_pull,
        "sparse_pull_fraction": (rows_pulled / dense_rows
                                 if dense_rows else 0.0),
        "rows_pulled": rows_pulled,
        "rows_pushed": rows_pushed,
        "row_versions": store.row_versions("V"),
    }
