"""Adapter for the k-means family: the program's resident Lloyd segment
on a seeded Gaussian mixture, called as ``tda kmeans --scale-points``
calls it under a checkpoint directory, and the plain reference after
its first calls.

The table is the benchmark's: ``table_fn`` draws it on the device,
block by block, in one jitted call with the seed as an argument, from
the reference's own definition of a row (``kmeans_ref.make_rows``),
into the layout the program's scale path reads
(``ops/pallas_lloyd.LanesGeometry``: feature-major blocks, 4 * dim bytes
a point). The iteration is the program's:

A call is one invocation of the compiled segment function
``kmeans.make_fit_seg_fn`` returns (what ``kmeans._fit_segmented`` hands
``checkpoint.run_segmented``): ``iterations_per_call`` Lloyd iterations
in fixed-iteration mode over every point. The calls chain, centres out
to centres in, so the window continues the fit that set-up began; the
same compiled object serves set-up's first calls, which the reference
follows, and the window. The start is an argument too: ``k`` rows drawn
from ``--seed`` (the source script's ``takeSample``). Nothing of
``--seed`` is compiled in.
"""

from __future__ import annotations

import numpy as np

from reference import kmeans_ref


def sub_seeds(seed: int) -> dict:
    """What ``--seed`` decides: the data and the rows the fit starts
    from."""
    got = np.random.SeedSequence(int(seed)).generate_state(2)
    return {"data": int(got[0]) & 0x7FFFFFFF,
            "init": int(got[1]) & 0x7FFFFFFF}


def shapes(config: dict, traffic: dict) -> dict:
    """What the byte functions and the readers need, from the files."""
    per = config["block_points"] * config["data_shards"]
    n_padded = -(-config["n_rows"] // per) * per
    return {"n_rows": config["n_rows"], "dim": config["dim"],
            "k": config["k"], "n_padded": n_padded,
            "n_blocks": n_padded // config["block_points"],
            "n_shards": config["data_shards"],
            "resident_bytes": n_padded * config["point_bytes"],
            "steps_per_call": traffic["iterations_per_call"]}


def program_parts(c: dict, t: dict):
    """The program's layout and fit configuration for a configuration
    file and a traffic file (set-up and ``tools/compile_check_kmeans.py``
    build the same ones). Raises where the program would not lay the
    points out as the file states: the bytes counted would not be the
    bytes held."""
    from tpu_distalg.models import kmeans
    from tpu_distalg.ops import pallas_lloyd

    lanes = pallas_lloyd.lanes_geometry(c["dim"], c["k"])
    mine = None if lanes is None else (
        lanes.block_points, 4 * lanes.dim * lanes.block_points)
    theirs = (c["block_points"], c["point_bytes"] * c["block_points"])
    if mine != theirs:
        raise RuntimeError(
            f"the program's layout at dim {c['dim']}, k {c['k']} "
            f"(points, bytes a block: {mine}) is not the one the "
            f"configuration states {theirs}")
    config = kmeans.KMeansConfig(
        k=c["k"], n_iterations=t["iterations_per_call"])
    return lanes, config


class State:
    work_unit = "rows"

    def __init__(self, fn, X4, n_valid, centers0, shift0, n_run0,
                 iterations: int, n_rows: int):
        self.fn, self.X4, self.n_valid = fn, X4, n_valid
        self.centers, self.shift, self.n_run = centers0, shift0, n_run0
        self.counts = None
        self.steps_per_call = iterations
        self.work_per_call = iterations * n_rows
        self.first: list[np.ndarray] = []
        self.centers0 = np.asarray(centers0)

    def dispatch(self):
        self.centers, self.shift, self.n_run, self.counts = self.fn(
            self.X4, self.n_valid, self.centers, self.shift, self.n_run)
        return self.centers

    def sync(self, handle):
        handle.block_until_ready()

    def finish(self) -> dict:
        out = {"centers0": self.centers0, "first": self.first,
               "centers_final": np.asarray(self.centers),
               "counts_final": np.asarray(self.counts),
               "iterations_done": int(self.n_run)}
        self.X4.delete()
        self.X4 = self.fn = None
        return out


def table_fn(c: dict, sh: dict, lanes, mesh):
    """The jitted generator of the resident points, ``f(data seed)``, a
    shard to a chip: block ``b`` of shard ``s`` holds the rows ``(s *
    blocks a shard + b) * block_points ...`` as the program packs a
    block."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    bp = c["block_points"]
    per_shard = sh["n_blocks"] // sh["n_shards"]

    def body(seed):
        s = jax.lax.axis_index("data")

        def one(b):
            ids = (s * per_shard + b) * bp + jnp.arange(bp)
            return lanes.pack(kmeans_ref.make_rows(
                ids, c["dim"], c["generating_clusters"], seed,
                c["spread"]))

        return jax.lax.map(one, jnp.arange(per_shard))

    spec = P("data", None, None, None)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(),
                                 out_specs=spec),
                   out_shardings=NamedSharding(mesh, spec))


def reference_of(ctx) -> kmeans_ref.Reference:
    c, seeds = ctx.config, sub_seeds(ctx.seed)
    return kmeans_ref.Reference(
        n_rows=c["n_rows"], dim=c["dim"], k=c["k"],
        clusters=c["generating_clusters"], spread=c["spread"],
        data_seed=seeds["data"], init_seed=seeds["init"],
        device=ctx.devices[0],
        block_rows=min(1 << 15, c["block_points"]))


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
    whole = len(ctx.devices) == len(jax.devices())
    mesh = get_mesh(data=c["data_shards"], model=1,
                    devices=None if whole else ctx.devices)
    lanes, config = program_parts(c, t)
    with ctx.span("data_build"):
        X4 = table_fn(c, sh, lanes, mesh)(jnp.int32(seeds["data"]))
        X4.block_until_ready()
    if X4.nbytes != sh["resident_bytes"]:
        raise RuntimeError(
            f"the table holds {X4.nbytes} bytes, the configuration "
            f"states {sh['resident_bytes']}")
    fn = kmeans.make_fit_seg_fn(mesh, config, t["iterations_per_call"],
                                lanes)
    # placed as the segment returns them, so that the first call and
    # every later one are one compiled program
    rep = NamedSharding(mesh, P())
    centers0, n_valid, shift0, n_run0 = jax.device_put(
        (jnp.asarray(reference_of(ctx).init_centers()),
         jnp.int32(c["n_rows"]), jnp.float32(0.0), jnp.int32(0)), rep)
    ctx.say(f"[kmeans] layout lanes blocks {tuple(X4.shape)} {X4.dtype} "
            f"({X4.nbytes / 1e9:.3f} GB) rows {c['n_rows']} of "
            f"{sh['n_padded']} k {c['k']} iterations/call "
            f"{t['iterations_per_call']} seeds {seeds}")
    state = State(fn, X4, n_valid, centers0, shift0, n_run0,
                  t["iterations_per_call"], c["n_rows"])
    with ctx.span("warm_up"):
        for _ in range(t["check_calls"]):
            state.sync(state.dispatch())
            state.first.append(np.asarray(state.centers))
    return state


def check(ctx, out: dict) -> None:
    """The reference follows the first calls from the same seeds; the
    window's last iteration assigned every point once; its last centres
    may not lie worse on held-out rows than the reference's."""
    import jax.numpy as jnp

    c, t = ctx.config, ctx.traffic
    ref = reference_of(ctx)
    ref.build()
    n_calls, iters = len(out["first"]), t["iterations_per_call"]
    c_ref, _ = ref.follow(n_calls, iters)
    for k, (got, want) in enumerate(zip(out["first"], c_ref), 1):
        ctx.compare(f"centers_rel_err.call{k}",
                    kmeans_ref.centers_err(got, want, c["spread"]),
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
        for k, (low, want) in enumerate(zip(c_low, c_ref), 1):
            ctx.control(f"centers_rel_err.call{k}",
                        kmeans_ref.centers_err(low, want, c["spread"]))
        ctx.control("inertia_rise", max(
            ref.inertia(X, c_low[-1]) / inertia_ref - 1, 0.0))
    ref.free()
