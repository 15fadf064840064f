"""Adapter for PageRank sharded by destination range
(``pagerank-graph500-sharded4``): the program's own loader draws the
Kronecker graph on the mesh from ``--seed``, every shard a slice of
the edge ids, cuts the destination ranges to equal loads, exchanges
the draws by range and deduplicates each range where it lives
(``pagerank.build_rmat_graph``);
the program's planner lays every shard's fused-sweep plan out there
(``prepare_device_spmv``), and the program's ``make_run_fn`` returns
what ``tda pagerank --rmat-scale`` runs on a mesh of ``data_shards``.
What a job is, what is compared and what fails the run are
``families/pagerank_resident.py``'s, whose ``State`` this file uses;
the reference is ``reference/pagerank_sharded_ref.py``, which holds
one destination range a device where the resident one holds the whole
graph on one.

A program that cannot shard the graph (this cell's parent: one chip's
VMEM does not hold 2^26 vertices' output table, and its loader says
so) fails in ``setup``, at once.
"""

from __future__ import annotations

import os

import numpy as np

from families import pagerank_resident as resident
from reference import pagerank_sharded_ref as ref_mod

State = resident.State


def setup(ctx) -> State:
    import jax

    with ctx.span("import_program"):
        from tpu_distalg.models import pagerank
        from tpu_distalg.parallel import get_mesh
        from tpu_distalg.telemetry import events as tevents

    c, t = ctx.config, ctx.traffic
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
        counted = sink.counters()
    finally:
        tevents.configure(False)
    for mine, theirs in (("plan_rejections", "spmv_plan_rejections"),
                         ("slots_padded", "spmv_slots_padded"),
                         ("shard_overflow", "pagerank_shard_overflow"),
                         ("shard_edges_max", "pagerank_shard_edges_max"),
                         ("shard_edges_mean", "pagerank_shard_edges_mean")):
        if theirs in counted or mine == "plan_rejections":
            ctx.counters[mine] = counted.get(theirs, 0)
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
    held = getattr(graph.geom, "shard_cap", None)
    if held != c["shard_capacity"]:
        raise RuntimeError(
            f"a shard of the program holds {held} edges, the "
            f"configuration states {c['shard_capacity']}")
    ctx.shapes = resident.shapes(c, graph.n_edges)
    slots = plan.n_chunks * 1024
    ctx.say(f"[pagerank] path spmv ranks in {plan.ranks_form} out "
            f"{plan.ranks_out_form} on {c['data_shards']} shards; "
            f"vertices {graph.n_vertices} generated {graph.n_in} "
            f"distinct {graph.n_edges} a shard "
            f"{list(graph.shard_edges)} of {held}, ranges cut at rows "
            f"{np.asarray(graph.bounds).tolist()}; slots {slots} "
            f"({slots / graph.n_edges:.4f} a distinct edge) rg "
            f"{plan.rg} ws {plan.ws} groups {plan.n_groups} calls of "
            f"{plan.seg_steps} steps; resident "
            f"{plan.nbytes / 1e9:.3f} GB; rejections "
            f"{ctx.counters['plan_rejections']}")
    de = pagerank.spmv_device_edges(graph, mesh)
    de.spmv = plan
    fn = pagerank.make_run_fn(mesh, resident.program_config(c),
                              graph.n_vertices, None, plan)
    state = State(fn, de, c["n_iterations"], graph.n_edges)
    with ctx.span("warm_up"):
        for call in range(t["check_calls"]):
            state.sync(state.dispatch())
            if call == 0:
                state.first = np.asarray(state.last)
    return state


def reference_ranks(ctx, **kw):
    c = ctx.config
    return ref_mod.ranks(
        c["scale"], c["edge_factor"], c["abcd"], ctx.seed, c["q"],
        c["n_iterations"], c["data_shards"],
        pieces=c["reference_pieces"], room=c["reference_room"],
        devices=ctx.devices, **kw)


def check(ctx, out: dict) -> None:
    """Ranks of the first call and of the window's last against the
    reference's float32 power iteration on the graph it draws from the
    same seed, one destination range a device, computed once; the
    program and the reference count the same distinct edges; ranks sum
    to 1."""
    import jax.numpy as jnp

    r_ref, n_edges = reference_ranks(ctx)
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
        r_low, _ = reference_ranks(ctx, dtype=jnp.bfloat16)
        ctx.control("rank_l1_err", ref_mod.l1_err(r_low, r_ref))
        ctx.control("rank_max_err", ref_mod.max_rel_err(r_low, r_ref))
        ctx.control("rank_sum_err", abs(float(
            np.asarray(r_low, np.float64).sum()) - 1.0))
