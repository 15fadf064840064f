"""Adapter for sparse ALS (``als-yahoomusic-f100``): the program's
loader builds the resident ratings table, the program's
``als.make_fit_fn`` returns the trainer for the ``meta`` that loader
states, and the plain reference follows its first calls.

The table is the program's: ``als.build_ratings_table`` draws it on the
device with the seed as an argument (one compile serves every seed; the
degree sequences and so the pack are functions of the sizes alone). The
configuration's generator and geometry are passed to it, so the file
and not the program's defaults says what is drawn and how it is held,
and ``reference/als_sparse_ref.py`` restates the generator from the
same file.

A call is one invocation of the compiled fit function:
``iterations_per_call`` ALS iterations, each the user half from the item
factors and then the item half from the new user factors, over every
rating. The calls chain: the factor tables out are the tables in. The
same compiled object serves set-up's first calls, which the reference
follows, and the window. A program without the sparse loader (this
cell's parent) fails in ``setup``, at once.
"""

from __future__ import annotations

import numpy as np

from reference import als_sparse_ref as ref_mod


def sub_seeds(seed: int) -> dict:
    """What ``--seed`` decides: the pairing, the planted model and the
    noise (``data``), and the factors' start (``start``)."""
    got = np.random.SeedSequence(int(seed)).generate_state(2)
    return {"data": int(got[0]) & 0x7FFFFFFF,
            "start": int(got[1]) & 0x7FFFFFFF}


def shapes(config: dict, traffic: dict) -> dict:
    """What the work functions and the readers need, from the files."""
    return dict(n_ratings=config["n_ratings"], n_users=config["n_users"],
                n_items=config["n_items"], k=config["k"],
                n_shards=config["data_shards"],
                row_bytes_needed=config["row_bytes_needed"],
                iterations_per_call=traffic["iterations_per_call"])


def program_config(c: dict, t: dict, start_seed: int = 0):
    """The program's trainer configuration for a configuration file and
    a traffic file (set-up and ``tools/compile_check_als.py`` build the
    same one)."""
    from tpu_distalg.models import als

    return als.ALSConfig(lam=c["lam"], m=c["n_users"], n=c["n_items"],
                         k=c["k"], n_iterations=t["iterations_per_call"],
                         seed=start_seed)


def loader_args(c: dict) -> dict:
    """What of the configuration reaches the program's loader."""
    return dict(n_heldout=c["n_heldout"], geometry=dict(c["geometry"]),
                **c["generator"])


def check_meta(c: dict, meta: dict) -> None:
    """The layout the program states against the configuration's."""
    geom = meta["geometry"]
    mine = (meta["layout"], meta["n_ratings"], meta["n_users"],
            meta["n_items"], meta["k"], geom.width, geom.seg_slots,
            geom.piece_segs, geom.batch, list(geom.classes))
    g = c["geometry"]
    theirs = (c["layout"], c["n_ratings"], c["n_users"], c["n_items"],
              c["k"], c["width"], g["seg_slots"], g["piece_segs"],
              g["batch"], list(g["classes"]))
    if mine != theirs:
        raise RuntimeError(
            f"the program's table {mine} is not the one the "
            f"configuration states {theirs}: the work counted would not "
            f"be the work done")


class State:
    work_unit = "rows"

    def __init__(self, fn, arrays, X, Theta, views, iterations: int,
                 n_ratings: int):
        self.fn, self.arrays, self.X, self.Theta = fn, arrays, X, Theta
        self.views = views            # tables -> factors in owner order
        self.steps_per_call = iterations
        self.work_per_call = 2 * n_ratings * iterations
        self.errs = self.seen = None
        self.calls = 0
        self.first: list[tuple[np.ndarray, np.ndarray]] = []

    def dispatch(self):
        self.X, self.Theta, self.errs, self.seen = self.fn(
            *self.arrays, self.X, self.Theta)
        self.calls += 1
        # the tables are donated to the next call: the handle the loop
        # waits on is the call's own small output
        return self.errs

    def sync(self, handle):
        handle.block_until_ready()

    def owners(self) -> tuple[np.ndarray, np.ndarray]:
        U, V = self.views(self.X, self.Theta)
        return np.asarray(U), np.asarray(V)

    def finish(self) -> dict:
        U, V = self.owners()
        out = {"first": self.first, "U_final": U, "V_final": V,
               "seen_final": np.asarray(self.seen),
               "errs_final": np.asarray(self.errs),
               "iterations_done": self.calls * self.steps_per_call}
        for a in (*self.arrays, self.X, self.Theta):
            a.delete()
        self.arrays = self.fn = self.X = self.Theta = self.views = None
        return out


def setup(ctx) -> State:
    import jax

    with ctx.span("import_program"):
        from tpu_distalg.models import als
        from tpu_distalg.parallel import get_mesh

    c, t = ctx.config, ctx.traffic
    ctx.shapes = shapes(c, t)
    seeds = sub_seeds(ctx.seed)
    if not hasattr(als, "build_ratings_table"):
        raise RuntimeError(
            "the program has no loader of a ratings list "
            "(models/als.build_ratings_table): ALS on a dense R only")
    whole = len(ctx.devices) == len(jax.devices())
    mesh = get_mesh(data=c["data_shards"], model=1,
                    devices=None if whole else ctx.devices)
    config = program_config(c, t, seeds["start"])
    with ctx.span("data_build"):
        arrays, meta = als.build_ratings_table(
            c["n_ratings"], c["n_users"], c["n_items"], c["k"], mesh,
            data_seed=seeds["data"], **loader_args(c))
    check_meta(c, meta)
    fn = als.make_fit_fn(mesh, config, meta)
    X, Theta = als.start_factors(meta, mesh, seeds["start"])
    geom, pu, pi = meta["geometry"], meta["user"], meta["item"]
    views = jax.jit(lambda X, Theta: (
        als.owners_from_rows(X, pu, geom.k),
        als.owners_from_rows(Theta, pi, geom.k)))
    held = sum(a.nbytes for a in arrays) + X.nbytes + Theta.nbytes
    ctx.say(f"[als] layout {meta['layout']} ratings {meta['n_ratings']} "
            f"users {meta['n_users']} items {meta['n_items']} k "
            f"{meta['k']} in {geom.width} lanes; blocks a side "
            f"{meta['blocks']} of {geom.batch} x {geom.seg_slots} slots, "
            f"slots held / ratings {meta['padding_share']:.4f}; resident "
            f"{held / 1e9:.3f} GB; forms {meta['forms']}; "
            f"iterations/call {t['iterations_per_call']} seeds {seeds}")
    state = State(fn, arrays, X, Theta, views, t["iterations_per_call"],
                  c["n_ratings"])
    with ctx.span("warm_up"):
        for _ in range(t["check_calls"]):
            state.sync(state.dispatch())
            state.first.append(state.owners())
            ctx.say(f"[als] call {state.calls}: training RMSE, held-out "
                    f"RMSE {np.asarray(state.errs).tolist()} ratings "
                    f"entered {np.asarray(state.seen).tolist()}")
    return state


def check(ctx, out: dict) -> None:
    """The reference follows the first calls owner by owner: every
    owner over ``reference_heavy_over`` ratings and a seeded sample a
    side, each solved from its regenerated ratings against the other
    side's factors as the program had them when the half began. Every
    rating entered each half of the window's last iteration once. The
    window's last factors may not score a higher held-out RMSE than the
    reference's rows of the last followed call do: on the held-out pairs
    of the followed users, those users' rows as the reference solved
    them against the item factors the program ended that call with (a
    program that hands its factors back leaves the start's item factors
    there, which the reference's rows fit and the program's do not)."""
    import jax.numpy as jnp

    c = ctx.config
    seeds = sub_seeds(ctx.seed)
    ref = ref_mod.Reference(config=c, data_seed=seeds["data"],
                            start_seed=seeds["start"])
    V0 = ref.start_items()
    U_before = np.zeros((c["n_users"], c["k"]), np.float32)
    V_before = V0
    control = bool(ctx.limits.get("_control"))
    for call, (U, V) in enumerate(out["first"], 1):
        for side, name, other, got, before in (
                (0, "x", V_before, U, U_before), (1, "theta", U, V,
                                                  V_before)):
            own, want = ref.half(side, other)
            if side == 0:
                users, users_rows = own, want
            ctx.compare(f"factor_rel_err.{name}.call{call}",
                        ref_mod.rel_err(got[own], want, before[own]),
                        ctx.limits["factor_rel_err"])
            if control:
                _, low = ref.half(side, other, dtype=jnp.bfloat16)
                ctx.control(f"factor_rel_err.{name}.call{call}",
                            ref_mod.rel_err(low, want, before[own]))
        U_before, V_before = U, V
    seen = np.asarray(out["seen_final"], np.int64).reshape(-1, 2)[-1]
    ctx.compare("visited_total_err",
                float(np.abs(seen - c["n_ratings"]).sum()),
                ctx.limits["visited_total_err"])
    hu, hv, hr = (np.asarray(a) for a in ref.heldout())
    keep = np.isin(hu, users)
    pairs = tuple(jnp.asarray(a[keep]) for a in (hu, hv, hr))
    U_ref = np.array(out["first"][-1][0])
    U_ref[users] = users_rows
    rmse_ref = ref.rmse(pairs, U_ref, out["first"][-1][1])
    rmse_win = ref.rmse(pairs, out["U_final"], out["V_final"])
    rmse_start = ref.rmse(pairs, np.zeros_like(out["U_final"]), V0)
    ctx.say(f"[check] held-out RMSE on the {int(keep.sum())} pairs of "
            f"{len(users)} followed users: window's last factors "
            f"{rmse_win:.6f} after {out['iterations_done']} iterations "
            f"(the program's own on all pairs "
            f"{float(out['errs_final'][-1][1]):.6f}), the reference's "
            f"rows {rmse_ref:.6f} after "
            f"{len(out['first']) * ctx.traffic['iterations_per_call']}; "
            f"the start's {rmse_start:.6f}")
    # one-sided: a fit that goes on past the followed iterations may
    # only hold or better what it reached
    ctx.compare("heldout_rmse_rise", max(rmse_win / rmse_ref - 1, 0.0),
                ctx.limits["heldout_rmse_rise"])
    if control:
        ctx.control("heldout_rmse_rise",
                    max(rmse_start / rmse_ref - 1, 0.0))
    ref.free()
