#!/usr/bin/env python3
"""Step 0 of the indexed-row cell (``lrwide11_150m_frac01``): what each
form of each pass costs alone on the chip at the cell's shape (183
sampled blocks of 8192 rows, 11 slots a row, 54 686 452 weights), ms a
call, least of three.

    chiprun -- python3 scripts/step0_indexed.py [--quick]
    JAX_PLATFORMS=cpu python3 scripts/step0_indexed.py --rehearse

The table is the program's (``ssgd.build_hashed_table`` with the
cell's generator parameters). Rows of the output, one a line as
``[step0] <name> <ms>``:

  xla.whole.gather/.scatter   ``w[idx]`` summed over all 11 fields and
                              ``.at[idx].add`` over the whole table: the
                              form the parent's ``pass_form`` falls to
  fields.gather/.scatter      what ships: every field its own form
  dict.*, group<k>.*          the shipped form's parts alone
  group<k>.xla.*              a group's fields through XLA instead
  hbm.[<field>].xla.*         one field past VMEM through XLA alone
  update                      ``w - eta * g / n`` over the model vector
  hbm.gather / hbm.scatter    the fields past VMEM as they ship: the
                              gather a DMA a pair from the table in HBM
                              (``_hashed_hbm_gather_kernel``, no resident
                              head), the scatter XLA's
  hbm.xla.gather              those fields' gather through XLA's
                              ``w[idx]`` instead (what shipped first)
  step.fields / step.xla      a call of 4 steps of the trainer, ms a step
A summary lands in ``chiprun_out/step0_indexed.json``.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "lrwide11_150m_frac01"


def least_ms(fn, *args, n: int = 3) -> float:
    import jax

    jax.block_until_ready(fn(*args))          # compile, warm
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    from harness import manifest as mf
    from families import ssgd_indexed as fam
    from tpu_distalg.models import ssgd
    from tpu_distalg.ops import pallas_hashed as ph
    from tpu_distalg.parallel import get_mesh

    rehearse, quick = "--rehearse" in argv, "--quick" in argv
    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu and not rehearse:
        print("step0_indexed: no chip (--rehearse interprets a tiny "
              "shape here)", file=sys.stderr)
        return 2
    cell = mf.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    c, t = dict(cell.config), cell.traffic
    if rehearse:
        ph.VMEM_BITS = 12
        c.update(n_rows=20034, gather_block_rows=256, n_features=25477,
                 field_cardinalities=[300, 2000, 150, 3, 3, 9000, 1500,
                                      3000, 2500, 7000, 21])
        t = dict(t, mini_batch_fraction=0.25)
    sh = fam.shapes(c, t)
    mesh = get_mesh(data=1, model=1, devices=jax.devices()[:1])
    config = fam.program_config(c, t)
    interpret = not on_tpu
    t0 = time.perf_counter()
    X, meta = ssgd.build_hashed_table(
        c["n_rows"], c["nnz"], 0, mesh, config, data_seed=1234567,
        **fam.loader_args(c))
    out = {"loader_s": time.perf_counter() - t0}
    print(f"[step0] loader {out['loader_s']:.2f} s (compile in it) table "
          f"{X.shape} {X.nbytes / 1e9:.3f} GB", flush=True)
    geom = ssgd.hashed_geometry(config, meta)
    plan = ssgd.hashed_field_plan(config, meta)
    ns, B = sh["n_sampled"], geom.block_rows
    key = jax.random.key(0)
    ids = jnp.sort(jax.random.permutation(key, sh["n_blocks"])[:ns]
                   ).astype(jnp.int32)
    w = 0.05 * jax.random.normal(jax.random.fold_in(key, 1),
                                 (geom.w_len,)
                                 ).at[geom.n_slots + 1:].set(0)
    r = jax.random.normal(jax.random.fold_in(key, 2), (ns, B))

    def say(name, ms):
        out[name] = ms
        print(f"[step0] {name} {ms:.3f}", flush=True)

    def gather_of(fields):
        return jax.jit(
            lambda X, w, ids: ph.margins_hbm_xla(X, w, ids, fields))

    def scatter_of(fields):
        return jax.jit(lambda X, r, ids: ph.slot_sums_hbm(
            X, r, ids, geom, fields))

    # the form the parent's pass_form falls to: XLA over the whole table
    m_x = jax.jit(lambda X, w, ids: ph.margins_xla(X, w, ids, geom))
    g_x = jax.jit(lambda X, r, ids: ph.slot_sums_xla(X, r, ids, geom))
    say("xla.whole.gather", least_ms(m_x, X, w, ids))
    say("xla.whole.scatter", least_ms(g_x, X, r, ids))
    # what ships
    m_f = jax.jit(lambda X, w, ids: ph.margins(
        X, w, ids, geom, plan=plan, interpret=interpret))
    g_f = jax.jit(lambda X, r, ids: ph.slot_sums(
        X, r, ids, geom, plan=plan, interpret=interpret))
    say("fields.gather", least_ms(m_f, X, w, ids))
    say("fields.scatter", least_ms(g_f, X, r, ids))
    err_m = float(jnp.abs(m_f(X, w, ids) - m_x(X, w, ids)).max())
    g1, g2 = g_f(X, r, ids), g_x(X, r, ids)
    err_g = float(jnp.abs(g1 - g2).max() / jnp.abs(g2).max())
    print(f"[step0] fields against xla.whole: margins max diff "
          f"{err_m:.3g}, sums max diff over max {err_g:.3g}", flush=True)
    out.update(margins_diff=err_m, sums_diff=err_g)
    del g1, g2
    # the parts
    if plan.dict_fields:
        say("dict.gather", least_ms(jax.jit(
            lambda X, w, ids: ph.margins_dict(
                X, w, ids, geom, plan, interpret=interpret)), X, w, ids))
        say("dict.scatter", least_ms(jax.jit(
            lambda X, r, ids: ph.slot_sums_dict(
                X, r, ids, geom, plan, interpret=interpret)[1]), X, r, ids))
    for k, group in enumerate(plan.addr_groups):
        tag = f"group{k}{list(group.fields)}"
        say(f"{tag}.gather", least_ms(jax.jit(
            lambda X, w, ids, group=group: ph.margins_vmem(
                X, w, ids, geom, interpret=interpret, group=group)),
            X, w, ids))
        say(f"{tag}.scatter", least_ms(jax.jit(
            lambda X, r, ids, group=group: ph.slot_sums_vmem(
                X, r, ids, geom, interpret=interpret, group=group)),
            X, r, ids))
        if not quick:
            say(f"{tag}.xla.gather",
                least_ms(gather_of(group.fields), X, w, ids))
            say(f"{tag}.xla.scatter",
                least_ms(scatter_of(group.fields), X, r, ids))
    if plan.hbm_fields:
        by_dma = jax.jit(lambda X, w, ids: ph.margins_hbm(
            X, w, ids, geom, plan.hbm_fields, interpret=interpret))
        say("hbm.gather", least_ms(by_dma, X, w, ids))
        say("hbm.xla.gather",
            least_ms(gather_of(plan.hbm_fields), X, w, ids))
        diff = float(jnp.abs(by_dma(X, w, ids) - gather_of(
            plan.hbm_fields)(X, w, ids)).max())
        print(f"[step0] hbm.gather against XLA's: max diff {diff:.3g}",
              flush=True)
        out["hbm_gather_diff"] = diff
        say("hbm.scatter", least_ms(scatter_of(plan.hbm_fields), X, r, ids))
        if not quick:
            for f in plan.hbm_fields:
                say(f"hbm.[{f}].xla.gather",
                    least_ms(gather_of((f,)), X, w, ids))
                say(f"hbm.[{f}].xla.scatter",
                    least_ms(scatter_of((f,)), X, r, ids))
    say("update", least_ms(jax.jit(
        lambda w, g: w - 0.1 * (g / 1499136.0)), w, w))
    # a call of the trainer, both forms
    d = jnp.zeros((1,), jnp.float32)
    w0 = jnp.zeros((geom.w_len,), jnp.float32)
    steps = config.n_iterations
    fn = ssgd.make_train_fn_fused(mesh, config, meta)
    say("step.fields", least_ms(
        lambda: fn(X, d, d, d, d, w0, t0=0)[0]) / steps)
    real = ph.pass_form
    ph.pass_form = lambda *a: "xla"
    try:
        fn_x = ssgd.make_train_fn_fused(mesh, config, meta)
        say("step.xla", least_ms(
            lambda: fn_x(X, d, d, d, d, w0, t0=0)[0]) / steps)
    finally:
        ph.pass_form = real
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes_in_use"] = int(stats.get("peak_bytes_in_use", 0))
    print(f"[step0] peak bytes in use {out['peak_bytes_in_use']}")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "step0_indexed.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
