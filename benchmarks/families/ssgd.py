"""Adapter for the SSGD family: builds the program's resident
block-sampled trainer from a configuration and a traffic file, calls
it, and has the plain reference follow its first calls.

The table is the benchmark's: ``make_table`` draws it on the device in
one jitted call, from the seed as an argument, in the packed bfloat16
layout the program's kernels read (``pallas_kernels.pack_augmented``
documents it; ``ssgd.prepare_fused_synthetic`` makes the same layout
but builds its seed into the compiled loader: 14.7 s of compile for
every new seed on the chip, PERF.md). The trainer is the program's:

A call is one invocation of the compiled segment function
``ssgd.make_train_fn_fused`` returns: ``steps_per_call`` SGD
steps, whole megakernel launches on one data shard (``fused_train``),
a scan of per-step kernels with a psum each on several
(``fused_gather``). The calls chain: weights out are weights in, the
step counter runs on, so the window continues the training that
set-up began. The same compiled object serves set-up's first calls,
which the reference follows, and the window.
"""

from __future__ import annotations

import math

import numpy as np

from reference import ssgd_ref


STEP_STRIDE = 1 << 14


def sub_seeds(seed: int) -> dict:
    """What ``--seed`` decides: the data, the initial weights, and
    which stretch of step ids, and so which block draws, the run uses.

    The program builds its sampling key into the compiled trainer, so
    a new sampling seed would compile anew in every run. Its step
    counter is an argument (``t0``, what a resumed run passes), and the
    draws are a function of (sampling seed, step id): the sampling seed
    stays the configuration's and the run starts at step ``t0``, up to
    2**30, drawn from ``--seed``. The weights are an argument too."""
    got = np.random.SeedSequence(int(seed)).generate_state(3)
    return {"data": int(got[0]) & 0x7FFFFFFF,
            "init": int(got[1]) & 0x7FFFFFFF,
            "t0": (int(got[2]) & 0xFFFF) * STEP_STRIDE}


def shapes(config: dict, traffic: dict) -> dict:
    """What the byte functions and the readers need, from the files."""
    g = ssgd_ref.geometry(config["n_rows"], config["data_shards"],
                          config["gather_block_rows"], config["fused_pack"],
                          traffic["mini_batch_fraction"])
    return dict(g, n_features=config["n_features"],
                block_rows=config["gather_block_rows"],
                n_shards=config["data_shards"],
                d_total=config["packed_columns"],
                steps_per_call=traffic["steps_per_call"])


def program_config(c: dict, t: dict):
    """The program's trainer configuration for a configuration file and
    a traffic file (the set-up and ``tools/compile_check.py`` build the
    same one)."""
    from tpu_distalg.models import ssgd

    return ssgd.SSGDConfig(
        n_iterations=t["steps_per_call"], eta=c["eta"], lam=c["lam"],
        mini_batch_fraction=t["mini_batch_fraction"],
        seed=c["sample_seed"], eval_test=False, x_dtype=c["x_dtype"],
        sampler=c["sampler"], fused_pack=c["fused_pack"],
        gather_block_rows=c["gather_block_rows"],
        mega_steps=c["mega_steps"], comm=c["comm"], sync=c["sync"])


def packed_meta(c: dict, sh: dict) -> dict:
    """The packed layout's static facts, as the program's packer
    states them."""
    from tpu_distalg.ops import pallas_kernels

    d_t, y_col, v_col = pallas_kernels.packed_dims(
        c["n_features"] + 1, c["fused_pack"])
    return dict(pack=c["fused_pack"], d_total=d_t, y_col=y_col,
                v_col=v_col, n_padded=sh["n_padded"])


class State:
    work_unit = "rows"

    def __init__(self, fn, X2, w0, dummy, steps: int, rows_per_step: int,
                 t0: int):
        self.fn, self.X2, self.w, self.dummy = fn, X2, w0, dummy
        self.t = self.t0 = t0
        self.steps_per_call = steps
        self.work_per_call = steps * rows_per_step
        self.first: list[np.ndarray] = []
        self.w0 = np.asarray(w0)

    def dispatch(self):
        d = self.dummy
        self.w, _ = self.fn(self.X2, d, d, d, d, self.w, t0=self.t)
        self.t += self.steps_per_call
        return self.w

    def sync(self, handle):
        handle.block_until_ready()

    def finish(self) -> dict:
        out = {"w0": self.w0, "first": self.first,
               "w_final": np.asarray(self.w),
               "steps_done": self.t - self.t0}
        self.X2.delete()
        self.X2 = self.fn = None
        return out


def make_table(c: dict, sh: dict, meta: dict, mesh, data_seed: int):
    """The packed table, a shard to a chip: row ``i`` is ``[features |
    1 | label | valid | 0...]`` in ``d_total`` bfloat16 columns, ``pack``
    rows to a packed row."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    pk, d_t, nf = meta["pack"], meta["d_total"], c["n_features"]
    n_local, B = sh["n_local"], c["gather_block_rows"]
    per = math.gcd(sh["n_blocks"], 16)
    chunk, n_chunks = B * per, sh["n_blocks"] // per
    n_rows, sep = c["n_rows"], c["separation"]
    if (meta["y_col"], meta["v_col"]) != (nf + 1, nf + 2):
        raise RuntimeError(f"packed layout {meta} is not the one "
                           f"make_table writes")

    def body(seed):
        s = jax.lax.axis_index("data")

        def one(k):
            ids = s * n_local + k * chunk + jnp.arange(chunk)
            X, y = ssgd_ref.make_rows(ids, nf, seed, sep)
            valid = (ids < n_rows).astype(jnp.float32)
            rows = jnp.concatenate(
                [X, jnp.ones((chunk, 1)), y[:, None], valid[:, None],
                 jnp.zeros((chunk, d_t - nf - 3))],
                axis=1).astype(jnp.dtype(c["x_dtype"]))
            return rows.reshape(chunk // pk, pk * d_t)

        return jax.lax.map(one, jnp.arange(n_chunks)).reshape(
            n_local // pk, pk * d_t)

    spec = P("data", None)
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(),
                              out_specs=spec),
                out_shardings=NamedSharding(mesh, spec))
    return f(jnp.int32(data_seed))


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
    # all the machine's devices: the mesh the CLI would build (ICI
    # order); fewer: the first ones, as a row
    whole = len(ctx.devices) == len(jax.devices())
    mesh = get_mesh(data=c["data_shards"], model=1,
                    devices=None if whole else ctx.devices)
    config = program_config(c, t)
    meta = packed_meta(c, sh)
    with ctx.span("data_build"):
        X2 = make_table(c, sh, meta, mesh, seeds["data"])
        X2.block_until_ready()
    fn = ssgd.make_train_fn_fused(mesh, config, meta)
    n_blocks, n_sampled = ssgd.fused_gather_geometry(
        config, meta, c["data_shards"])
    mine = (meta["d_total"], meta["n_padded"], n_blocks, n_sampled)
    theirs = (sh["d_total"], sh["n_padded"], sh["n_blocks"],
              sh["n_sampled"])
    if mine != theirs:
        raise RuntimeError(
            f"the program's geometry {mine} is not the one the "
            f"configuration states {theirs}: the work counted would "
            f"not be the work done")
    ctx.say(f"[ssgd] sampler {c['sampler']} shards {c['data_shards']} "
            f"rows {meta['n_padded']} packed {tuple(X2.shape)} "
            f"{X2.dtype} ({X2.nbytes / 1e9:.3f} GB) blocks/shard "
            f"{n_blocks} sampled/step {n_sampled} rows/step "
            f"{sh['rows_per_step']} steps/call {t['steps_per_call']} "
            f"seeds {seeds}")
    d = c["n_features"] + 1
    # placed as the trainer returns it, so that the first call and
    # every later one are one compiled program
    w0 = jax.device_put(
        jnp.zeros((meta["d_total"],), jnp.float32).at[:d].set(
            ssgd_ref.init_weights(seeds["init"], d)),
        NamedSharding(mesh, P()))
    state = State(fn, X2, w0, jnp.zeros((1,), jnp.float32),
                  t["steps_per_call"], sh["rows_per_step"], seeds["t0"])
    with ctx.span("warm_up"):
        for _ in range(t["check_calls"]):
            state.sync(state.dispatch())
            state.first.append(np.asarray(state.w))
    return state


def check(ctx, out: dict) -> None:
    """The reference follows the first calls from the same seeds; the
    window's last weights may not fall under its held-out accuracy."""
    import jax.numpy as jnp

    c, t = ctx.config, ctx.traffic
    seeds = sub_seeds(ctx.seed)
    ref = ssgd_ref.Reference(
        n_rows=c["n_rows"], n_features=c["n_features"],
        n_shards=c["data_shards"], block_rows=c["gather_block_rows"],
        pack=c["fused_pack"], fraction=t["mini_batch_fraction"],
        eta=c["eta"], separation=c["separation"],
        data_seed=seeds["data"], init_seed=seeds["init"],
        sample_seed=c["sample_seed"], devices=ctx.devices)
    ref.build()
    d = ref.d
    w0 = out["w0"][:d]
    w_ref = ref.follow(len(out["first"]), t["steps_per_call"],
                       t0=seeds["t0"])
    for k, (w, wr) in enumerate(zip(out["first"], w_ref), 1):
        ctx.compare(f"w_rel_err.call{k}",
                    ssgd_ref.rel_err(w[:d], wr, w0),
                    ctx.limits["w_rel_err"])
    X, y = ref.heldout()
    acc_ref = ref.accuracy(X, y, w_ref[-1])
    acc_win = ref.accuracy(X, y, out["w_final"][:d])
    ctx.say(f"[check] held-out accuracy: window's last weights "
            f"{acc_win:.5f} after {out['steps_done']} steps, reference "
            f"{acc_ref:.5f} after {len(w_ref) * t['steps_per_call']}")
    # one-sided: training that goes on past the reference's steps may
    # only hold or better its held-out accuracy
    ctx.compare("heldout_acc_drop", max(acc_ref - acc_win, 0.0),
                ctx.limits["heldout_acc_drop"])
    if ctx.limits.get("_control"):
        # limit-setting runs only (tools/check_limits.py): the control
        w_low = ref.follow(len(out["first"]), t["steps_per_call"],
                           dtype=jnp.bfloat16, t0=seeds["t0"])
        for k, (w, wr) in enumerate(zip(w_low, w_ref), 1):
            ctx.control(f"w_rel_err.call{k}", ssgd_ref.rel_err(w, wr, w0))
        ctx.control("heldout_acc_drop",
                    max(acc_ref - ref.accuracy(X, y, w_low[-1]), 0.0))
    ref.free()
