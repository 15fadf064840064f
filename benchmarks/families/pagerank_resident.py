"""Adapter for resident PageRank (``pagerank-graph500-24``): the
program's own loader draws the Kronecker graph on the device from
``--seed`` (``pagerank.build_rmat_graph``), the program's planner lays
the fused sweep's plan out there (``prepare_device_spmv``), and the
program's ``make_run_fn`` returns what ``tda pagerank --rmat-scale``
runs. The configuration's generator is passed to the loader, so the
file and not the program's defaults says what is drawn, and
``reference/pagerank_resident_ref.py`` restates the generator from the
same file.

A call is one job: ``n_iterations`` sweeps from the uniform start over
every distinct edge, unchained. The same compiled object serves
set-up's first calls and the window; the reference's ranks, computed
once after the window, are compared with the first call's and with the
window's last. A plan the program refuses (a chunk's span past the
window its geometry fixed) fails the run: there is no other path. A
program without the device loader (this cell's parent) fails in
``setup``, at once.
"""

from __future__ import annotations

import os

import numpy as np

from reference import pagerank_resident_ref as ref_mod


def shapes(config: dict, n_edges: int) -> dict:
    """What the work function and the readers need."""
    return dict(n_vertices=1 << config["scale"], n_edges=n_edges,
                n_shards=config["data_shards"],
                edge_bytes_needed=config["edge_bytes_needed"],
                vertex_bytes_needed=config["vertex_bytes_needed"])


def program_config(c: dict):
    from tpu_distalg.models import pagerank

    return pagerank.PageRankConfig(
        n_iterations=c["n_iterations"], q=c["q"], mode=c["mode"],
        redistribute_dangling=c["redistribute_dangling"],
        scatter=c["scatter"])


class State:
    work_unit = "rows"

    def __init__(self, fn, de, sweeps: int, n_edges: int):
        self.fn, self.de = fn, de
        self.steps_per_call = sweeps
        self.work_per_call = sweeps * n_edges
        self.first = None
        self.last = None

    def dispatch(self):
        de = self.de
        self.last, _ = self.fn(de.src, de.dst, de.w_e, de.emask,
                               de.has_out, de.n_ref)
        return self.last

    def sync(self, handle):
        handle.block_until_ready()

    def finish(self) -> dict:
        out = {"first": self.first, "last": np.asarray(self.last)}
        for a in self.de.spmv.arrays:
            a.delete()
        self.fn = self.de = self.last = None
        return out


def setup(ctx) -> State:
    import jax

    with ctx.span("import_program"):
        from tpu_distalg.models import pagerank
        from tpu_distalg.parallel import get_mesh
        from tpu_distalg.telemetry import events as tevents

    c, t = ctx.config, ctx.traffic
    if not hasattr(pagerank, "build_rmat_graph"):
        raise RuntimeError(
            "the program has no loader of a graph on the device "
            "(models/pagerank.build_rmat_graph): a host edge list only")
    whole = len(ctx.devices) == len(jax.devices())
    mesh = get_mesh(data=c["data_shards"], model=1,
                    devices=None if whole else ctx.devices)
    sink = tevents.configure(
        os.path.join(ctx.out_dir, "telemetry", ctx.cell.name))
    try:
        with ctx.span("data_build"):
            graph = pagerank.build_rmat_graph(
                mesh, c["scale"], c["edge_factor"], c["abcd"], ctx.seed)
            plan = pagerank.prepare_device_spmv(graph, mesh)
        ctx.counters["plan_rejections"] = sink.counters().get(
            "spmv_plan_rejections", 0)
        ctx.counters["slots_padded"] = sink.counters().get(
            "spmv_slots_padded", 0)
    finally:
        tevents.configure(False)
    if plan is None:
        raise RuntimeError(
            f"the program refused its plan for seed {ctx.seed}: a "
            f"chunk's destinations span more rows than the window the "
            f"geometry fixed (ws {graph.geom.ws}); the cell has no "
            f"other path")
    mine = dict(rg=plan.rg, ws=plan.ws, blk=plan.blk, chunk=1024)
    if mine != c["geometry"]:
        raise RuntimeError(
            f"the program's geometry {mine} is not the one the "
            f"configuration states {c['geometry']}")
    ctx.shapes = shapes(c, graph.n_edges)
    slots = plan.n_chunks * 1024
    ctx.say(f"[pagerank] path spmv ranks {plan.ranks_form} vertices "
            f"{graph.n_vertices} generated {graph.n_in} distinct "
            f"{graph.n_edges} slots {slots} "
            f"({slots / graph.n_edges:.4f} a distinct edge) rg "
            f"{plan.rg} ws {plan.ws} groups {plan.n_groups} calls of "
            f"{plan.seg_steps} steps; resident {plan.nbytes / 1e9:.3f} "
            f"GB; rejections {ctx.counters['plan_rejections']}")
    de = pagerank.spmv_device_edges(graph, mesh)
    de.spmv = plan
    fn = pagerank.make_run_fn(mesh, program_config(c), graph.n_vertices,
                              None, plan)
    state = State(fn, de, c["n_iterations"], graph.n_edges)
    with ctx.span("warm_up"):
        for call in range(t["check_calls"]):
            state.sync(state.dispatch())
            if call == 0:
                state.first = np.asarray(state.last)
    return state


def check(ctx, out: dict) -> None:
    """Ranks of the first call and of the window's last against the
    reference's float32 power iteration on the graph it draws from the
    same seed, computed once; the program and the reference count the
    same distinct edges; ranks sum to 1."""
    import jax.numpy as jnp

    c = ctx.config
    args = (c["scale"], c["edge_factor"], c["abcd"], ctx.seed, c["q"],
            c["n_iterations"])
    r_ref, n_edges = ref_mod.ranks(*args)
    if n_edges != ctx.shapes["n_edges"]:
        raise RuntimeError(
            f"the program counts {ctx.shapes['n_edges']} distinct edges, "
            f"the reference {n_edges}: the work counted is not the "
            f"work done")
    for name in ("first", "last"):
        ctx.compare(f"rank_l1_err.{name}",
                    ref_mod.l1_err(out[name], r_ref),
                    ctx.limits["rank_l1_err"])
        ctx.compare(f"rank_max_err.{name}",
                    ref_mod.max_rel_err(out[name], r_ref),
                    ctx.limits["rank_max_err"])
    ctx.compare("rank_sum_err", abs(float(
        np.asarray(out["last"], np.float64).sum()) - 1.0),
        ctx.limits["rank_sum_err"])
    if ctx.limits.get("_control"):
        r_low, _ = ref_mod.ranks(*args, dtype=jnp.bfloat16)
        ctx.control("rank_l1_err", ref_mod.l1_err(r_low, r_ref))
        ctx.control("rank_max_err", ref_mod.max_rel_err(r_low, r_ref))
        ctx.control("rank_sum_err", abs(float(
            np.asarray(r_low, np.float64).sum()) - 1.0))
