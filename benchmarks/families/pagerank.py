"""Adapter for the PageRank family: a seeded R-MAT graph through the
program's resident path, as ``pagerank.run`` takes it (``prepare_edges``,
``prepare_device_spmv`` with its window escalation, the light edge prep,
``make_run_fn``), built once; a call is one jitted run of
``n_iterations`` sweeps from the uniform start, as ``tda pagerank``
makes one. ``scatter="auto"``: the program picks the path, and the
adapter says on an earlier line which one it picked and with what
geometry. A fallback shows as a rate several times lower.
"""

from __future__ import annotations

import json
import os

import numpy as np

from harness import rmat
from reference import pagerank_ref


class State:
    work_unit = "edges"

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
        self.fn = self.de = self.last = None
        return out


def _graph(ctx) -> np.ndarray:
    c = ctx.config
    return rmat.edges(c["scale"], c["edge_factor"], c["abcd"], ctx.seed)


def setup(ctx) -> State:
    import jax

    from tpu_distalg.models import pagerank
    from tpu_distalg.ops import graph as gops
    from tpu_distalg.parallel import get_mesh
    from tpu_distalg.telemetry import events as tevents

    c = ctx.config
    n_vertices = 1 << c["scale"]
    tel_dir = os.path.join(ctx.out_dir, "telemetry", ctx.cell.name)
    sink = tevents.configure(tel_dir)
    with ctx.span("graph_gen"):
        edges = _graph(ctx)
    whole = len(ctx.devices) == len(jax.devices())
    mesh = get_mesh(data=len(ctx.devices), model=1,
                    devices=None if whole else ctx.devices)
    config = pagerank.PageRankConfig(
        n_iterations=c["n_iterations"], q=c["q"], mode=c["mode"],
        redistribute_dangling=c["redistribute_dangling"],
        scatter=c["scatter"])
    with ctx.span("host_prep"):
        el = gops.prepare_edges(edges, n_vertices)
        del edges
        spmv = pagerank.prepare_device_spmv(el, mesh)
        de = pagerank.prepare_device_edges(
            el, mesh, build_plan=spmv is None, light=spmv is not None)
        de.spmv = spmv
        fn = pagerank.make_run_fn(mesh, config, de.n_vertices, de.plan,
                                  de.spmv)
    ctx.counters["plan_rejections"] = sink.counters().get(
        "spmv_plan_rejections", 0)
    with open(sink.path) as f:
        for line in f:
            ev = json.loads(line)
            if ev.get("ev") == "span_end" and \
                    ev["name"].startswith("pagerank:plan_spmv"):
                ctx.say(f"[pagerank] span {ev['name']} "
                        f"{ev['seconds']:.2f} s ok={ev['ok']}")
                ctx.count("plan_sort_s", ev["seconds"])
    tevents.configure(False)
    path = "spmv" if spmv is not None else \
        "pallas" if de.plan is not None else "xla"
    geo = (dict(rg=spmv.rg, ws=spmv.ws, r8=spmv.r8, blk=spmv.blk,
                chunks=spmv.n_chunks,
                pad_edges=spmv.n_chunks * 1024 - el.n_edges)
           if spmv is not None else {})
    ctx.shapes = dict(geo, path=path, n_vertices=n_vertices,
                      n_edges=el.n_edges, chunk=1024)
    ctx.say(f"[pagerank] path {path} vertices {n_vertices} distinct "
            f"edges {el.n_edges} geometry {geo} rejections "
            f"{ctx.counters['plan_rejections']}")
    state = State(fn, de, c["n_iterations"], el.n_edges)
    with ctx.span("warm_up"):
        state.sync(state.dispatch())
        state.first = np.asarray(state.last)
        state.sync(state.dispatch())
    return state


def check(ctx, out: dict) -> None:
    """Ranks of the first call and of the window's last against the
    float32 power iteration on the same raw edges; ranks sum to 1."""
    import jax.numpy as jnp

    c = ctx.config
    n_vertices = 1 << c["scale"]
    edges = _graph(ctx)
    r_ref, n_edges = pagerank_ref.ranks(
        edges, n_vertices, c["q"], c["n_iterations"])
    if n_edges != ctx.shapes["n_edges"]:
        raise RuntimeError(
            f"the program counts {ctx.shapes['n_edges']} distinct edges, "
            f"the reference {n_edges}: the work counted is not the "
            f"work done")
    for name in ("first", "last"):
        ctx.compare(f"rank_l1_err.{name}",
                    pagerank_ref.l1_err(out[name], r_ref),
                    ctx.limits["rank_l1_err"])
        ctx.compare(f"rank_max_err.{name}",
                    pagerank_ref.max_rel_err(out[name], r_ref),
                    ctx.limits["rank_max_err"])
    ctx.compare("rank_sum_err", abs(float(
        np.asarray(out["last"], np.float64).sum()) - 1.0),
        ctx.limits["rank_sum_err"])
    if ctx.limits.get("_control"):
        r_low, _ = pagerank_ref.ranks(edges, n_vertices, c["q"],
                                      c["n_iterations"], jnp.bfloat16)
        ctx.control("rank_l1_err", pagerank_ref.l1_err(r_low, r_ref))
        ctx.control("rank_max_err", pagerank_ref.max_rel_err(r_low, r_ref))
        ctx.control("rank_sum_err", abs(float(
            np.asarray(r_low, np.float64).sum()) - 1.0))
