"""Adapter for wide k-means (a codebook: hundreds of dimensions,
thousands of centres): the program's resident Lloyd segment on a seeded
Gaussian mixture, called as ``tda kmeans --scale-points`` calls it under
a checkpoint directory, and the plain reference after its first calls.

What ``families/kmeans.py`` says of a call, of the seeds and of the
chained centres holds here, and its ``State``, ``sub_seeds`` and
``shapes`` serve unchanged. What differs is the table and who checks
it: ``table_fn`` draws the benchmark's rows (``kmeans_ref.make_rows``)
into the layout the program's wide pass reads
(``ops/pallas_lloyd_wide.WideGeometry``: blocks of ``block_points``
points, features down a block's rows, ``point_bytes`` a point), and the
reference is ``reference/kmeans_wide_ref.py``, blocked over rows and
centres. ``program_parts`` raises where the program lays the points out
otherwise than the configuration states, or scores the distances in
another form: the bytes and the accuracy stated would not be the ones
run. A program without the wide pass (this cell's parent) fails there,
at once.
"""

from __future__ import annotations

import numpy as np

from families import kmeans as base
from reference import kmeans_ref, kmeans_wide_ref

sub_seeds, shapes, State = base.sub_seeds, base.shapes, base.State


def program_parts(c: dict, t: dict):
    """The program's geometry and fit configuration for a configuration
    file and a traffic file (set-up and
    ``tools/compile_check_kmeans_wide.py`` build the same ones)."""
    from tpu_distalg.models import kmeans

    try:
        geom = kmeans.scale_geometry(c["dim"], c["k"])
        mine = (kmeans.layout_of(geom), geom.block_points,
                geom.point_bytes, geom.dist_form)
    except AttributeError as e:
        raise RuntimeError(
            f"the program has no wide k-means layout at dim {c['dim']}, "
            f"k {c['k']} ({type(e).__name__}: {e})") from e
    theirs = (c["layout"], c["block_points"], c["point_bytes"],
              c["dist_form"])
    if mine != theirs:
        raise RuntimeError(
            f"the program's layout at dim {c['dim']}, k {c['k']} "
            f"(layout, points a block, bytes a point, distance form: "
            f"{mine}) is not the one the configuration states {theirs}")
    config = kmeans.KMeansConfig(
        k=c["k"], n_iterations=t["iterations_per_call"])
    return geom, config


def table_fn(c: dict, sh: dict, geom, mesh):
    """The jitted generator of the resident points, ``f(data seed)``, a
    shard to a chip: block ``b`` of shard ``s`` holds the rows ``(s *
    blocks a shard + b) * block_points ...`` as the program packs a
    block; a few blocks a step of the loop."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    bp = c["block_points"]
    per_shard = sh["n_blocks"] // sh["n_shards"]
    group = next(g for g in (8, 4, 2, 1) if per_shard % g == 0)

    def body(seed):
        s = jax.lax.axis_index("data")

        def some(j):
            ids = (s * per_shard + j * group) * bp + jnp.arange(group * bp)
            rows = kmeans_ref.make_rows(
                ids, c["dim"], c["generating_clusters"], seed, c["spread"])
            return jax.vmap(geom.pack)(rows.reshape(group, bp, c["dim"]))

        blocks = jax.lax.map(some, jnp.arange(per_shard // group))
        return blocks.reshape(per_shard, *blocks.shape[2:])

    spec = P("data", None, None)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(),
                                 out_specs=spec),
                   out_shardings=NamedSharding(mesh, spec))


def reference_of(ctx) -> kmeans_wide_ref.Reference:
    c, seeds = ctx.config, sub_seeds(ctx.seed)
    return kmeans_wide_ref.Reference(
        n_rows=c["n_rows"], dim=c["dim"], k=c["k"],
        clusters=c["generating_clusters"], spread=c["spread"],
        data_seed=seeds["data"], init_seed=seeds["init"],
        device=ctx.devices[0])


def setup(ctx) -> State:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    with ctx.span("import_program"):
        from tpu_distalg.models import kmeans
        from tpu_distalg.parallel import get_mesh

    c, t = ctx.config, ctx.traffic
    sh = ctx.shapes = shapes(c, t)
    seeds = sub_seeds(ctx.seed)
    geom, config = program_parts(c, t)
    whole = len(ctx.devices) == len(jax.devices())
    mesh = get_mesh(data=c["data_shards"], model=1,
                    devices=None if whole else ctx.devices)
    with ctx.span("data_build"):
        X3 = table_fn(c, sh, geom, mesh)(jnp.int32(seeds["data"]))
        X3.block_until_ready()
    if X3.nbytes != sh["resident_bytes"]:
        raise RuntimeError(
            f"the table holds {X3.nbytes} bytes, the configuration "
            f"states {sh['resident_bytes']}")
    fn = kmeans.make_fit_seg_fn(mesh, config, t["iterations_per_call"],
                                geom)
    # placed as the segment returns them, so that the first call and
    # every later one are one compiled program
    rep = NamedSharding(mesh, P())
    centers0, n_valid, shift0, n_run0 = jax.device_put(
        (jnp.asarray(reference_of(ctx).init_centers()),
         jnp.int32(c["n_rows"]), jnp.float32(0.0), jnp.int32(0)), rep)
    ctx.say(f"[kmeans] layout {c['layout']} blocks {tuple(X3.shape)} "
            f"{X3.dtype} ({X3.nbytes / 1e9:.3f} GB) rows {c['n_rows']} of "
            f"{sh['n_padded']} k {c['k']} distances {c['dist_form']} "
            f"iterations/call {t['iterations_per_call']} seeds {seeds}")
    state = State(fn, X3, n_valid, centers0, shift0, n_run0,
                  t["iterations_per_call"], c["n_rows"])
    with ctx.span("warm_up"):
        for _ in range(t["check_calls"]):
            state.sync(state.dispatch())
            state.first.append(np.asarray(state.centers))
    return state


def check(ctx, out: dict) -> None:
    """As ``families/kmeans.check``: the reference follows the first
    calls from the same seeds; the window's last iteration assigned
    every point once; its last centres may not lie worse on held-out
    rows than the reference's. ``centers_rel_err`` is the centres'
    difference weighted by the clusters' counts
    (``kmeans_wide_ref.sums_err`` says why, with its readings)."""
    import jax.numpy as jnp

    c, t = ctx.config, ctx.traffic
    ref = reference_of(ctx)
    ref.build()
    n_calls, iters = len(out["first"]), t["iterations_per_call"]
    c_ref, _ = ref.follow(n_calls, iters)
    n_ref = ref.call_counts
    for k, (got, want, n) in enumerate(zip(out["first"], c_ref, n_ref), 1):
        ctx.compare(f"centers_rel_err.call{k}",
                    kmeans_wide_ref.sums_err(got, want, n, c["spread"]),
                    ctx.limits["centers_rel_err"])
    total = int(np.asarray(out["counts_final"], np.int64).sum())
    ctx.compare("count_total_err", abs(total - c["n_rows"]),
                ctx.limits["count_total_err"])
    X = ref.heldout()
    inertia_ref = ref.inertia(X, c_ref[-1])
    inertia_win = ref.inertia(X, out["centers_final"])
    ctx.say(f"[check] held-out inertia: window's last centres "
            f"{inertia_win:.6f} after {out['iterations_done']} "
            f"iterations, reference {inertia_ref:.6f} after "
            f"{n_calls * iters}; the start's "
            f"{ref.inertia(X, out['centers0']):.6f}")
    # one-sided: a fit that goes on past the reference's iterations may
    # only hold or better what it reached
    ctx.compare("inertia_rise", max(inertia_win / inertia_ref - 1, 0.0),
                ctx.limits["inertia_rise"])
    if ctx.limits.get("_control"):
        # limit-setting runs only (tools/check_limits.py): the control
        c_low, _ = ref.follow(n_calls, iters, dtype=jnp.bfloat16)
        for k, (low, want, n) in enumerate(zip(c_low, c_ref, n_ref), 1):
            ctx.control(f"centers_rel_err.call{k}", kmeans_wide_ref.sums_err(
                low, want, n, c["spread"]))
        ctx.control("inertia_rise", max(
            ref.inertia(X, c_low[-1]) / inertia_ref - 1, 0.0))
    ref.free()
