"""Adapter for the dense transitive closure (``closure-grid250``): the
grid of the source drawn from ``--seed`` (the benchmark's copy of the
data definition, ``reference/closure_ref.grid_edges``), handed to the
program's loader (``transitive_closure.prepare_dense``: the edge list on
the device, the start matrix scattered there), and the program's
compiled round (``transitive_closure.make_round_fn``), what ``tda
closure`` runs.

**A call is one round of a job**: the compose over the whole resident
path matrix with its count, the paths chained call to call, written to
a spare matrix that is handed on with them, both donated.
Call ``rounds_per_job + 1`` starts the next job from the edge list on
the device (the adapter counts calls on the host, with no sync; the
start is the program's own compiled scatter), so every round the window
times is a round some job runs. Set-up's ``check_calls`` are a job's
first rounds. ``check`` holds the first call's and the window's last
call's sampled rows against the reference bit for bit
(``closure_ref.Reference.rows`` at ``L = min(2^round, V)`` arcs), the
first call's count against the pairs within two arcs counted whole from
the edge list, a complete closure's count against the source's published
number, and every job's last round against its own fixpoint test. A
program without the two entry points (this cell's parent) fails in
``setup``, at once and before anything is allocated.
"""

from __future__ import annotations

import numpy as np

from reference import closure_ref


def shapes(config: dict, traffic: dict) -> dict:
    """What the work function and the readers need, from the files."""
    return {"n_vertices": config["n_vertices"],
            "v_padded": config["v_padded"],
            "n_shards": config["data_shards"],
            "steps_per_call": traffic["rounds_per_call"]}


def program_parts(c: dict, mesh):
    """The program's geometry for the configuration's graph. Raises
    where the program lacks the dense closure's entry points, or would
    compose otherwise than the file states (another padded side or
    another form: the operations and bytes counted would not be the ones
    run)."""
    from tpu_distalg.models import transitive_closure as tc

    missing = [n for n in ("prepare_dense", "make_round_fn",
                           "make_start_fn", "dense_geometry")
               if not hasattr(tc, n)]
    if missing:
        raise RuntimeError(
            f"the program's models/transitive_closure.py has no "
            f"{', '.join(missing)}: it cannot run a dense closure round "
            f"as a call")
    geom = tc.dense_geometry(c["n_vertices"], mesh)
    mine = (geom.v_padded, geom.form)
    theirs = (c["v_padded"], c["compose_form"])
    if mine != theirs:
        raise RuntimeError(
            f"the program's dense closure at {c['n_vertices']} vertices "
            f"(padded side, compose form: {mine}) is not the one the "
            f"configuration states {theirs}")
    return tc, geom


class State:
    work_unit = "rows"

    def __init__(self, job, round_fn, take, sources, c: dict, t: dict):
        self.job, self.round_fn, self.take = job, round_fn, take
        self.sources = sources
        self.rounds_per_job = c["rounds_per_job"]
        self.steps_per_call = t["rounds_per_call"]
        self.work_per_call = t["rounds_per_call"] * c["n_vertices"]
        self.paths, self.spare, self.count = job.paths, job.spare, job.count
        job.paths = job.spare = None     # the first round donates both
        self.calls = 0
        self.flags: list = []        # (still, count) of every call
        self.first_rows = None

    def round_of(self, call: int) -> int:
        """Which round of its job call ``call`` (from 1) was."""
        return (call - 1) % self.rounds_per_job + 1

    def dispatch(self):
        if self.calls and self.calls % self.rounds_per_job == 0:
            self.paths = None
            self.paths, self.count = self.job.start()     # the next job
        self.paths, self.spare, self.count, still = self.round_fn(
            self.spare, self.paths, self.count)
        self.calls += 1
        self.flags.append((still, self.count))
        return still

    def sync(self, handle):
        handle.block_until_ready()

    def sample(self) -> np.ndarray:
        return np.asarray(self.take(self.paths, self.sources)) != 0

    def finish(self) -> dict:
        from tpu_distalg.ops import graph as gops

        out = {"first_rows": self.first_rows, "last_rows": self.sample(),
               "calls": self.calls,
               "still": [bool(s) for s, _ in self.flags],
               "counts": [gops.count_of(c) for _, c in self.flags]}
        self.paths.delete()
        self.spare.delete()
        self.paths = self.spare = self.job = self.round_fn = None
        return out


def setup(ctx) -> State:
    import jax
    import jax.numpy as jnp

    with ctx.span("import_program"):
        from tpu_distalg.parallel import get_mesh

    c, t = ctx.config, ctx.traffic
    ctx.shapes = shapes(c, t)
    whole = len(ctx.devices) == len(jax.devices())
    mesh = get_mesh(data=c["data_shards"], model=1,
                    devices=None if whole else ctx.devices)
    tc, geom = program_parts(c, mesh)
    with ctx.span("data_build"):
        edges = closure_ref.grid_edges(c["grid_side"], ctx.seed)
        job = tc.prepare_dense(edges, mesh, c["n_vertices"])
    if job.n_edges != c["n_edges"]:
        raise RuntimeError(f"the loader holds {job.n_edges} arcs, the "
                           f"configuration states {c['n_edges']}")
    round_fn = tc.make_round_fn(mesh, geom)
    take = jax.jit(lambda paths, rows: paths[rows, :c["n_vertices"]])
    sources = jnp.asarray(closure_ref.sample_sources(
        c["n_vertices"], c["sample_rows"], ctx.seed), jnp.int32)
    ctx.say(f"[closure] grid side {c['grid_side']} vertices "
            f"{c['n_vertices']} arcs {job.n_edges} matrix "
            f"{geom.v_padded} x {geom.v_padded} int8 "
            f"({geom.matrix_bytes / 1e9:.3f} GB, two resident) compose "
            f"{geom.form} rounds/job {c['rounds_per_job']} sampled rows "
            f"{len(sources)} seed {ctx.seed}")
    state = State(job, round_fn, take, sources, c, t)
    with ctx.span("warm_up"):
        for k in range(t["check_calls"]):
            state.sync(state.dispatch())
            if k == 0:
                state.first_rows = state.sample()
    return state


def check(ctx, out: dict) -> None:
    c, counts = ctx.config, out["counts"]
    v, per_job = c["n_vertices"], c["rounds_per_job"]
    ref = closure_ref.Reference(c["grid_side"], ctx.seed, c["sample_rows"],
                                device=ctx.devices[0])
    last_round = (out["calls"] - 1) % per_job + 1
    arcs = min(2 ** last_round, v)
    ctx.say(f"[check] {out['calls']} calls; pairs a call "
            f"{counts[:per_job]} (the first job's) ... {counts[-3:]}; the "
            f"last was round {last_round} of its job (paths of at most "
            f"{arcs} arcs)")
    want_first = ref.rows(2)
    ctx.compare("row_bit_errors.first",
                int((out["first_rows"] != want_first).sum()),
                ctx.limits["row_bit_errors"])
    ctx.compare("pair_count_err.first",
                abs(counts[0] - closure_ref.pairs_within_two(ref.edges, v)),
                ctx.limits["pair_count_err"])
    ctx.compare("row_bit_errors.last",
                int((out["last_rows"] != ref.rows(arcs)).sum()),
                ctx.limits["row_bit_errors"])
    if arcs >= c["longest_path_arcs"]:
        # the closure is whole: the source's published count
        ctx.compare("pair_count_err.last",
                    abs(counts[-1] - c["closure_pairs"]),
                    ctx.limits["pair_count_err"])
    # a job's last round sees the count stand still, and no earlier one
    wrong = sum(still != ((k % per_job) + 1 == per_job)
                for k, still in enumerate(out["still"]))
    ctx.compare("fixpoint_flag_errors", wrong,
                ctx.limits["fixpoint_flag_errors"])
    if ctx.limits.get("_control"):
        # limit-setting runs only (tools/check_limits.py): a round from
        # the start state with the contraction's last block left out
        # (the second operand's last tile_k rows zeroed)
        import jax
        import jax.numpy as jnp

        from tpu_distalg.models import transitive_closure as tc
        from tpu_distalg.ops import graph as gops
        from tpu_distalg.parallel import get_mesh

        mesh = get_mesh(data=1, model=1, devices=ctx.devices[:1])
        job = tc.prepare_dense(ref.edges, mesh, v)
        geom, cut = job.geom, geom_cut(c, job.geom)
        short = jax.jit(lambda p: gops.closure_step(
            p, p.at[geom.v_padded - cut:].set(0), form=geom.form,
            interpret=geom.interpret))
        new, partials = short(job.paths)
        low = np.asarray(new[jnp.asarray(ref.sources), :v]) != 0
        ctx.control("row_bit_errors.first", int((low != want_first).sum()))
        ctx.control("pair_count_err.first", abs(
            gops.count_of(gops.path_count(partials))
            - closure_ref.pairs_within_two(ref.edges, v)))


def geom_cut(c: dict, geom) -> int:
    """Rows of the contraction the control leaves out: the kernel's
    last block, or as large a share of a matrix XLA's form composes."""
    block = c["tile"][2]
    return block if geom.v_padded > block else max(geom.v_padded // 8, 1)
