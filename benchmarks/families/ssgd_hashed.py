"""Adapter for SSGD over hashed rows (``lr-criteo-hash20``): the
program's loader builds the resident table, the program's
``make_train_fn_fused`` returns the segment function for the ``meta``
that loader states, and the plain reference follows its first calls.

The table is the program's: ``ssgd.build_hashed_table`` draws it on the
device in one jitted call with the seed as an argument (one compile
serves every seed). The configuration's generator parameters are passed
to it, so the file and not the program's defaults says what is drawn,
and ``reference/ssgd_hashed_ref.py`` restates the generator from the
same file.

A call is one invocation of the compiled segment function:
``steps_per_call`` SGD steps, each a draw of blocks, the gather pass,
the scatter pass, a psum and the update. The calls chain: weights out
are weights in, the step counter runs on. The same compiled object
serves set-up's first calls, which the reference follows, and the
window.
"""

from __future__ import annotations

import numpy as np

from reference import ssgd_hashed_ref as ref_mod
from reference import ssgd_ref

STEP_STRIDE = 1 << 14
LANES = 128


def sub_seeds(seed: int) -> dict:
    """What ``--seed`` decides: the table and its planted model, and
    which stretch of step ids, and so which block draws, the run uses
    (``families/ssgd.py`` says why the sampling seed stays the
    configuration's). The initial weights are zero, as the source's."""
    got = np.random.SeedSequence(int(seed)).generate_state(2)
    return {"data": int(got[0]) & 0x7FFFFFFF,
            "t0": (int(got[1]) & 0xFFFF) * STEP_STRIDE}


def shapes(config: dict, traffic: dict) -> dict:
    """What the byte function and the readers need, from the files."""
    g = ssgd_ref.geometry(config["n_rows"], config["data_shards"],
                          config["gather_block_rows"], 1,
                          traffic["mini_batch_fraction"])
    return dict(g, nnz=config["nnz"], hash_bits=config["hash_bits"],
                block_rows=config["gather_block_rows"],
                n_shards=config["data_shards"],
                row_bytes_needed=config["row_bytes_needed"],
                d_total=(1 << config["hash_bits"]) + LANES,
                steps_per_call=traffic["steps_per_call"])


def program_config(c: dict, t: dict):
    """The program's trainer configuration for a configuration file and
    a traffic file (the set-up and ``tools/compile_check_hashed.py``
    build the same one)."""
    from tpu_distalg.models import ssgd

    return ssgd.SSGDConfig(
        n_iterations=t["steps_per_call"], eta=c["eta"], lam=c["lam"],
        mini_batch_fraction=t["mini_batch_fraction"],
        seed=c["sample_seed"], eval_test=False, sampler=c["sampler"],
        gather_block_rows=c["gather_block_rows"], comm=c["comm"],
        sync=c["sync"])


def loader_args(c: dict) -> dict:
    """What of the configuration reaches the program's loader."""
    return dict(cardinalities=ref_mod.cardinalities(c),
                zipf_exponent=c["zipf_exponent"],
                planted_scale=c["planted_scale"],
                click_rate=c["click_rate"])


class State:
    work_unit = "rows"

    def __init__(self, fn, X, w0, dummy, steps: int, rows_per_step: int,
                 t0: int):
        self.fn, self.X, self.w, self.dummy = fn, X, w0, dummy
        self.t = self.t0 = t0
        self.steps_per_call = steps
        self.work_per_call = steps * rows_per_step
        self.first: list[np.ndarray] = []

    def dispatch(self):
        d = self.dummy
        self.w, _ = self.fn(self.X, d, d, d, d, self.w, t0=self.t)
        self.t += self.steps_per_call
        return self.w

    def sync(self, handle):
        handle.block_until_ready()

    def finish(self) -> dict:
        out = {"first": self.first, "w_final": np.asarray(self.w),
               "steps_done": self.t - self.t0}
        self.X.delete()
        self.X = self.fn = None
        return out


def setup(ctx) -> State:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    with ctx.span("import_program"):
        from tpu_distalg.models import ssgd
        from tpu_distalg.parallel import get_mesh

    c, t = ctx.config, ctx.traffic
    sh = ctx.shapes = shapes(c, t)
    seeds = sub_seeds(ctx.seed)
    whole = len(ctx.devices) == len(jax.devices())
    mesh = get_mesh(data=c["data_shards"], model=1,
                    devices=None if whole else ctx.devices)
    config = program_config(c, t)
    with ctx.span("data_build"):
        X, meta = ssgd.build_hashed_table(
            c["n_rows"], c["nnz"], c["hash_bits"], mesh, config,
            data_seed=seeds["data"], **loader_args(c))
    fn = ssgd.make_train_fn_fused(mesh, config, meta)
    n_blocks, n_sampled = ssgd.fused_gather_geometry(
        config, meta, c["data_shards"])
    mine = (meta["row_format"], meta["d_total"], meta["n_padded"],
            n_blocks, n_sampled)
    theirs = ("hashed", sh["d_total"], sh["n_padded"], sh["n_blocks"],
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
    ctx.say(f"[ssgd] row format {meta['row_format']} nnz {meta['nnz']} "
            f"hash_bits {meta['hash_bits']} passes {geom.pass_form} "
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
    """The reference follows the first calls from the same seeds; the
    window's last weights may not score a higher held-out log-loss
    than the reference's."""
    import jax.numpy as jnp

    c, t = ctx.config, ctx.traffic
    seeds = sub_seeds(ctx.seed)
    n_slots = 1 << c["hash_bits"]
    ref = ref_mod.Reference(
        config=c, fraction=t["mini_batch_fraction"],
        data_seed=seeds["data"], sample_seed=c["sample_seed"],
        n_shards=c["data_shards"])
    w0 = np.zeros((n_slots + 1,), np.float32)
    w_ref = ref.follow(len(out["first"]), t["steps_per_call"],
                       t0=seeds["t0"])
    for k, (w, wr) in enumerate(zip(out["first"], w_ref), 1):
        ctx.compare(f"w_rel_err.call{k}",
                    ref_mod.rel_err(ref_mod.model_vector(w, n_slots),
                                    wr, w0),
                    ctx.limits["w_rel_err"])
    idx, y = ref.heldout()
    ll_ref = ref.log_loss(idx, y, w_ref[-1])
    ll_win = ref.log_loss(
        idx, y, ref_mod.model_vector(out["w_final"], n_slots))
    ctx.say(f"[check] held-out log-loss: window's last weights "
            f"{ll_win:.6f} after {out['steps_done']} steps, reference "
            f"{ll_ref:.6f} after {len(w_ref) * t['steps_per_call']} "
            f"(zero weights {ref.log_loss(idx, y, w0):.6f})")
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
