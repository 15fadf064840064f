"""Adapter for the pair-set transitive closure (``closure-tree17``): the
tree of the source drawn from ``--seed`` (the benchmark's copy of the
data definition, ``reference/closure_tree_ref.graph_edges``), handed to
the program's loader (``transitive_closure.prepare_sparse``: the edge
list on the device, sorted by source and counted there), and the
program's compiled semi-naive round (``make_sparse_round_fn``), what
``tda closure --tree-height`` runs.

**A call is one round of a job**: the pairs the round before found new
joined with the arcs, the one sort of set and candidates, the new pairs
merged in, the count; the state chained call to call and donated. Call
``rounds_per_job + 1`` starts the next job from the edge list on the
device (the adapter counts calls on the host, with no sync; the start is
the program's own compiled program), so every round the window times is
a round some job runs. Set-up's ``check_calls`` are a job's first
rounds. ``check`` holds the first call's and the window's last call's
pairs of the sampled sources against the reference's naive join at that
round (round ``r`` leaves the paths of at most ``r + 1`` arcs) as sets,
every call's count against the pairs within that many arcs counted
whole, a complete closure's count against the source's published number,
every call's own fixpoint flag against the job's schedule and every
call's overflow flag. A program without the round's entry points (this
cell's parent) fails in ``setup``, at once and before anything is
allocated.
"""

from __future__ import annotations

import numpy as np

from reference import closure_tree_ref


def shapes(config: dict, traffic: dict) -> dict:
    """What the byte function and the readers need, from the files."""
    return {k: config[k] for k in (
        "n_vertices", "n_edges", "capacity", "delta_capacity",
        "join_capacity")} | {"n_shards": config["data_shards"],
                             "steps_per_call": traffic["rounds_per_call"]}


def program_parts(c: dict):
    """The program's module and its geometry for the configuration's
    graph. Raises where the program lacks the pair-set round's entry
    points, or would size its buffers otherwise than the file states."""
    from tpu_distalg.models import transitive_closure as tc

    missing = [n for n in ("prepare_sparse", "make_sparse_round_fn",
                           "make_sparse_start_fns", "sparse_geometry")
               if not hasattr(tc, n)]
    if missing:
        raise RuntimeError(
            f"the program's models/transitive_closure.py has no "
            f"{', '.join(missing)}: it cannot run a pair-set closure "
            f"round as a call")
    geom = tc.sparse_geometry(c["n_vertices"], c["n_edges"], sparse_config(
        tc, c))
    mine = (geom.capacity, geom.delta_capacity, geom.join_capacity)
    theirs = (c["capacity"], c["delta_capacity"], c["join_capacity"])
    if mine != theirs:
        raise RuntimeError(
            f"the program's pair-set closure would hold (set, new pairs, "
            f"candidates) {mine}, the configuration states {theirs}")
    return tc, geom


def sparse_config(tc, c: dict):
    return tc.SparseClosureConfig(
        capacity=c["capacity"], delta_capacity=c["delta_capacity"],
        join_capacity=c["join_capacity"])


def make_take(c: dict):
    """``(sx, sz, sources) -> (x, z, n)``: the set's pairs whose source
    is sampled, brought to the front of ``sample_capacity`` slots in the
    set's order, and how many there are."""
    import jax
    import jax.numpy as jnp

    from tpu_distalg.ops import graph as gops

    v, room = c["n_vertices"], c["sample_capacity"]

    def take(sx, sz, sources):
        chunk = next(k for k in (1 << 16, 1 << 10, 1) if len(sx) % k == 0)
        mine = jax.lax.map(
            lambda x: jnp.any(x[:, None] == sources[None, :], axis=1),
            sx.reshape(-1, chunk)).reshape(-1)
        x, z = gops.compact_front(mine, (sx, sz), (v, v), room)
        return x, z, jnp.sum(mine, dtype=jnp.int32)

    return jax.jit(take)


class State:
    work_unit = "rows"

    def __init__(self, job, take, sources, c: dict, t: dict):
        # the round is the loader's own compiled object (job.round_fn)
        self.job, self.round_fn, self.take = job, job.round_fn, take
        self.sources = sources
        self.room = c["sample_capacity"]
        self.rounds_per_job = c["rounds_per_job"]
        self.steps_per_call = t["rounds_per_call"]
        self.work_per_call = t["rounds_per_call"] * c["n_vertices"]
        self.state = job.state
        job.state = None             # the first round donates it
        self.calls = 0
        self.flags: list = []        # (still, count, stats) of every call
        self.first_pairs = None

    def round_of(self, call: int) -> int:
        """Which round of its job call ``call`` (from 1) was."""
        return (call - 1) % self.rounds_per_job + 1

    def dispatch(self):
        if self.calls and self.calls % self.rounds_per_job == 0:
            self.state = None
            self.state = self.job.start()            # the next job
        self.state, count, still, stats = self.round_fn(
            self.state, self.job.arcs)
        self.calls += 1
        self.flags.append((still, count, stats))
        return still

    def sync(self, handle):
        handle.block_until_ready()

    def sample(self):
        """The sampled sources' pairs now, on the host: ``(x, z)``."""
        x, z, n = self.take(self.state.sx, self.state.sz, self.sources)
        n = int(n)
        if n > self.room:
            raise RuntimeError(f"the sampled sources hold {n} pairs, "
                               f"sample_capacity is {self.room}")
        # cut on the host: a slice on the device compiles for every n
        return np.asarray(x)[:n], np.asarray(z)[:n]

    def finish(self) -> dict:
        from tpu_distalg.ops import graph as gops

        stats = np.asarray([np.asarray(s) for _, _, s in self.flags])
        out = {"first_pairs": self.first_pairs, "last_pairs": self.sample(),
               "calls": self.calls,
               "still": [bool(s) for s, _, _ in self.flags],
               "counts": [gops.count_of(c) for _, c, _ in self.flags],
               "candidates": stats[:, 0].tolist(),
               "new_pairs": stats[:, 1].tolist(),
               "overflow": stats[:, 2].tolist()}
        for a in self.state[:4]:
            a.delete()
        self.state = self.job = self.round_fn = None
        return out


def setup(ctx) -> State:
    import jax
    import jax.numpy as jnp

    with ctx.span("import_program"):
        from tpu_distalg.parallel import get_mesh

    c, t = ctx.config, ctx.traffic
    ctx.shapes = shapes(c, t)
    tc, geom = program_parts(c)
    whole = len(ctx.devices) == len(jax.devices())
    mesh = get_mesh(data=c["data_shards"], model=1,
                    devices=None if whole else ctx.devices)
    with ctx.span("data_build"):
        edges = closure_tree_ref.graph_edges(c, ctx.seed)
        job = tc.prepare_sparse(edges, mesh, c["n_vertices"],
                                sparse_config(tc, c))
    if int(job.state.n) != c["n_edges"]:
        raise RuntimeError(f"the loader holds {int(job.state.n)} arcs, the "
                           f"configuration states {c['n_edges']}")
    sources = jnp.asarray(closure_tree_ref.closure_ref.sample_sources(
        c["n_vertices"], c["sample_rows"], ctx.seed), jnp.int32)
    ctx.say(f"[closure] vertices {c['n_vertices']} arcs {c['n_edges']} "
            f"set {geom.capacity} pairs, new {geom.delta_capacity}, "
            f"candidates {geom.join_capacity} "
            f"({geom.resident_bytes / 1e9:.3f} GB carried) rounds/job "
            f"{c['rounds_per_job']} sampled sources {len(sources)} seed "
            f"{ctx.seed}")
    state = State(job, make_take(c), sources, c, t)
    with ctx.span("warm_up"):
        for k in range(t["check_calls"]):
            state.sync(state.dispatch())
            if k == 0:
                state.first_pairs = state.sample()
    return state


def check(ctx, out: dict) -> None:
    c, counts = ctx.config, out["counts"]
    per_job = c["rounds_per_job"]
    ref = closure_tree_ref.Reference(c, ctx.seed)
    rounds = [k % per_job + 1 for k in range(out["calls"])]
    ctx.say(f"[check] {out['calls']} calls; pairs a call {counts[:per_job]} "
            f"(the first job's); new pairs a call "
            f"{out['new_pairs'][:per_job]}; candidates a call "
            f"{out['candidates'][:per_job]}; the last was round "
            f"{rounds[-1]} of its job (paths of at most {rounds[-1] + 1} "
            f"arcs)")
    # the window's calls (set-up's are the first check_calls)
    win = slice(ctx.traffic["check_calls"], None)
    ctx.counters.update(
        new_pairs_per_round=float(np.mean(out["new_pairs"][win])),
        candidates_per_round=float(np.mean(out["candidates"][win])),
        set_pairs_per_round=float(
            np.mean(counts[win]) - np.mean(out["new_pairs"][win]) / 2))
    for tag, pairs, arcs in (("first", out["first_pairs"], 2),
                             ("last", out["last_pairs"], rounds[-1] + 1)):
        ctx.compare(f"set_errors.{tag}", closure_tree_ref.set_errors(
            ref.keys(*pairs), ref.reached(arcs)), ctx.limits["set_errors"])
    # every call's count against the pairs within its round's arcs
    ctx.compare("pair_count_err", sum(
        abs(n - ref.pairs(r + 1)) for n, r in zip(counts, rounds)),
        ctx.limits["pair_count_err"])
    whole = [n for n, r in zip(counts, rounds)
             if r + 1 >= c["longest_path_arcs"]]
    if whole:
        # the closure is whole: the source's published count
        ctx.compare("pair_count_err.whole",
                    max(abs(n - c["closure_pairs"]) for n in whole),
                    ctx.limits["pair_count_err"])
    # a job's last round sees the count stand still, and no earlier one
    ctx.compare("fixpoint_flag_errors", sum(
        still != (r == per_job) for still, r in zip(out["still"], rounds)),
        ctx.limits["fixpoint_flag_errors"])
    ctx.compare("overflow_flags", sum(out["overflow"]),
                ctx.limits["overflow_flags"])
    if ctx.limits.get("_control"):
        control(ctx)


def control(ctx) -> None:
    """Limit-setting runs only (tools/check_limits.py): the first round
    with its candidates not made distinct (every candidate counted and
    held, the program's own join), on BigDatalog's grid at
    ``control_grid_side``, where a pair has many derivations: on a tree
    no candidate is ever a duplicate and the fault would pass."""
    import jax
    import jax.numpy as jnp

    from tpu_distalg.models import transitive_closure as tc
    from tpu_distalg.parallel import get_mesh

    side = ctx.config["control_grid_side"]
    grid = {"grid_side": side, "n_vertices": (side + 1) ** 2,
            "sample_rows": ctx.config["sample_rows"]}
    ref = closure_tree_ref.Reference(grid, ctx.seed)
    mesh = get_mesh(data=1, model=1, devices=ctx.devices[:1])
    job = tc.prepare_sparse(ref.edges, mesh, grid["n_vertices"])
    geom, state = job.geom, job.state

    @jax.jit
    def faulty(state, arcs):
        cx, cz, joined, _ = tc.sparse_join(state, arcs, geom)
        return (jnp.concatenate([state.sx, cx]),
                jnp.concatenate([state.sz, cz]), state.n + joined)

    x, z, n = (np.asarray(a) for a in faulty(state, job.arcs))
    mine = np.isin(x, ref.sources)
    ctx.control("set_errors.first", closure_tree_ref.set_errors(
        ref.keys(x[mine], z[mine]), ref.reached(2)))
    ctx.control("pair_count_err", abs(int(n) - ref.pairs(2)))
