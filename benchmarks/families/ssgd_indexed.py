"""Adapter for SSGD over indexed rows (``lr-kdd12-wide55m``): the
sibling of ``families/ssgd_hashed.py`` for a table in which every value
of every field is its own weight and the model (54.7M float32) is wider
than VMEM. The program's loader builds the resident table for the
``row_format`` the configuration states, the program's
``make_train_fn_fused`` returns the segment function for the ``meta``
that loader states, and the plain reference
(``reference/ssgd_indexed_ref.py``) follows its first calls.

What a call is, how calls chain, what ``--seed`` decides and the state
handed to the window are the hashed family's, imported from it.
"""

from __future__ import annotations

import numpy as np

from families import ssgd_hashed
from reference import ssgd_indexed_ref as ref_mod
from reference import ssgd_ref

ROW_FORMAT = "indexed"

sub_seeds = ssgd_hashed.sub_seeds
program_config = ssgd_hashed.program_config
State = ssgd_hashed.State


def shapes(config: dict, traffic: dict) -> dict:
    """What the byte function and the readers need, from the files."""
    g = ssgd_ref.geometry(config["n_rows"], config["data_shards"],
                          config["gather_block_rows"], 1,
                          traffic["mini_batch_fraction"])
    return dict(g, nnz=config["nnz"], n_features=config["n_features"],
                block_rows=config["gather_block_rows"],
                n_shards=config["data_shards"],
                row_bytes_needed=config["row_bytes_needed"],
                d_total=ref_mod.vector_len(config["n_features"]),
                steps_per_call=traffic["steps_per_call"])


def loader_args(c: dict) -> dict:
    """What of the configuration reaches the program's loader."""
    return dict(cardinalities=ref_mod.cardinalities(c),
                row_format=c["row_format"],
                zipf_exponent=c["zipf_exponent"],
                planted_scale=c["planted_scale"],
                click_rate=c["click_rate"])


def require_format(ssgd) -> None:
    """A program from before the format refuses here, at once and by
    name, and not somewhere inside its hashed loader."""
    known = getattr(ssgd, "INDEX_ROW_FORMATS", ("hashed",))
    if ROW_FORMAT not in known:
        raise RuntimeError(
            f"this program's tda ssgd has no row_format {ROW_FORMAT!r} "
            f"(it knows {known}): a weight table with a slot a feature, "
            f"wider than VMEM, is what the cell measures; nothing was "
            f"built")


def setup(ctx) -> State:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    with ctx.span("import_program"):
        from tpu_distalg.models import ssgd
        from tpu_distalg.parallel import get_mesh

    require_format(ssgd)
    c, t = ctx.config, ctx.traffic
    if c["row_format"] != ROW_FORMAT:
        raise RuntimeError(f"family ssgd_indexed, configuration "
                           f"row_format {c['row_format']!r}")
    sh = ctx.shapes = shapes(c, t)
    seeds = sub_seeds(ctx.seed)
    whole = len(ctx.devices) == len(jax.devices())
    mesh = get_mesh(data=c["data_shards"], model=1,
                    devices=None if whole else ctx.devices)
    config = program_config(c, t)
    with ctx.span("data_build"):
        X, meta = ssgd.build_hashed_table(
            c["n_rows"], c["nnz"], 0, mesh, config,
            data_seed=seeds["data"], **loader_args(c))
    fn = ssgd.make_train_fn_fused(mesh, config, meta)
    n_blocks, n_sampled = ssgd.fused_gather_geometry(
        config, meta, c["data_shards"])
    mine = (meta["row_format"], meta["d_total"], meta["n_padded"],
            n_blocks, n_sampled)
    theirs = (ROW_FORMAT, sh["d_total"], sh["n_padded"], sh["n_blocks"],
              sh["n_sampled"])
    if mine != theirs:
        raise RuntimeError(
            f"the program's geometry {mine} is not the one the "
            f"configuration states {theirs}: the work counted would "
            f"not be the work done")
    held = X.nbytes / meta["n_padded"]
    if X.dtype != jnp.dtype(c["index_dtype"]) \
            or held < c["row_bytes_needed"]:
        raise RuntimeError(
            f"the program holds {held:.1f} B a row as {X.dtype}; "
            f"{c['nnz']} {c['index_dtype']} slots and a label need "
            f"{c['row_bytes_needed']}")
    geom = ssgd.hashed_geometry(config, meta)
    plan = ssgd.hashed_field_plan(config, meta)
    sh["hbm_fields"] = 0 if plan is None else len(plan.hbm_fields)
    forms = "every field xla" if plan is None else (
        f"by value {list(plan.dict_fields)} by address in VMEM "
        f"{[list(g.fields) for g in plan.addr_groups]} in HBM "
        f"{list(plan.hbm_fields)}")
    ctx.say(f"[ssgd] row format {meta['row_format']} nnz {meta['nnz']} "
            f"features {geom.n_slots} ({4 * geom.n_slots / 1e6:.1f} MB of "
            f"weights) passes {geom.pass_form}: {forms}; "
            f"shards {c['data_shards']} rows {meta['n_padded']} table "
            f"{tuple(X.shape)} {X.dtype} ({X.nbytes / 1e9:.3f} GB) "
            f"blocks/shard {n_blocks} sampled/step {n_sampled} "
            f"rows/step {sh['rows_per_step']} steps/call "
            f"{t['steps_per_call']} seeds {seeds}")
    # placed as the trainer returns it, so that the first call and
    # every later one are one compiled program
    w0 = jax.device_put(jnp.zeros((meta["d_total"],), jnp.float32),
                        NamedSharding(mesh, P()))
    state = State(fn, X, w0, jnp.zeros((1,), jnp.float32),
                  t["steps_per_call"], sh["rows_per_step"], seeds["t0"])
    with ctx.span("warm_up"):
        for _ in range(t["check_calls"]):
            state.sync(state.dispatch())
            state.first.append(np.asarray(state.w))
    return state


def check(ctx, out: dict) -> None:
    """The reference follows the first calls from the same seeds, over
    all ``n_features`` weights and the bias; the window's last weights
    may not score a higher held-out log-loss than the reference's."""
    import jax.numpy as jnp

    c, t = ctx.config, ctx.traffic
    seeds = sub_seeds(ctx.seed)
    D = c["n_features"]
    ref = ref_mod.Reference(
        config=c, fraction=t["mini_batch_fraction"],
        data_seed=seeds["data"], sample_seed=c["sample_seed"],
        n_shards=c["data_shards"])
    w0 = np.zeros((D + 1,), np.float32)
    w_ref = ref.follow(len(out["first"]), t["steps_per_call"],
                       t0=seeds["t0"])
    for k, (w, wr) in enumerate(zip(out["first"], w_ref), 1):
        ctx.compare(f"w_rel_err.call{k}",
                    ref_mod.rel_err(ref_mod.model_vector(w, D), wr, w0),
                    ctx.limits["w_rel_err"])
    idx, y = ref.heldout()
    ll_ref = ref.log_loss(idx, y, w_ref[-1])
    ll_win = ref.log_loss(idx, y, ref_mod.model_vector(out["w_final"], D))
    ctx.say(f"[check] held-out log-loss: window's last weights "
            f"{ll_win:.6f} after {out['steps_done']} steps, reference "
            f"{ll_ref:.6f} after {len(w_ref) * t['steps_per_call']} "
            f"(zero weights {ref.log_loss(idx, y, w0):.6f}); weights "
            f"that moved in the reference "
            f"{int(np.count_nonzero(w_ref[-1]))} of {D + 1}")
    # one-sided: training that goes on past the reference's steps may
    # only hold or lower its held-out log-loss
    ctx.compare("heldout_logloss_rise", max(ll_win - ll_ref, 0.0),
                ctx.limits["heldout_logloss_rise"])
    if ctx.limits.get("_control"):
        # limit-setting runs only (tools/check_limits.py): the control
        w_low = ref.follow(len(out["first"]), t["steps_per_call"],
                           dtype=jnp.bfloat16, t0=seeds["t0"])
        for k, (w, wr) in enumerate(zip(w_low, w_ref), 1):
            ctx.control(f"w_rel_err.call{k}", ref_mod.rel_err(w, wr, w0))
        ctx.control("heldout_logloss_rise",
                    max(ref.log_loss(idx, y, w_low[-1]) - ll_ref, 0.0))
