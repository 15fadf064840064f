#!/usr/bin/env python3
"""Step 0 of reading a hashed row's short fields by value: what each
pass costs alone on the chip at the ``lrhash39_46m_frac01`` cell's shape
(56 sampled blocks of 8192 rows, 39 fields, 2^20 weights; the program's
loader and its dictionaries), ms a step, least of three, each with its
dispatch. Kept as the way to re-read ``DICT_MAX_VALUES`` in
``tpu_distalg/ops/pallas_hashed.py`` (the ``value.*.cN`` rows):

    chiprun -- python3 scripts/step0_hashed_fields.py
    JAX_PLATFORMS=cpu python3 scripts/step0_hashed_fields.py --rehearse

Rows of the output, one a line as ``[step0] <name> <ms>``:

  addr.gather.fN / addr.scatter.fN   the by-address kernels over N
                         fields: all 39, the 20 and the 18 left when
                         fields up to 2048 / 4096 values go
  dict_rows              the by-value fields' slots brought to whole
                         vectors (XLA's copy), alone
  value.gather / value.sums          the by-value passes over the 19
                         dictionaries, Mosaic (copy included)
  xla.value.gather / xla.value.sums  the same as XLA's fused
                         ``where(x[..., None] == D, wd, 0).sum(-1)``
  value.*.cN             one field of N entries by value (the crossing)
  value.*.group16        16 entries a grid step and not 8
  pass.gather / pass.scatter         what the trainer calls, with and
                         without the plan
A summary lands in ``chiprun_out/step0_hashed_fields.json``.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

N_ROWS, NNZ, HASH_BITS, BLOCK_ROWS, FRACTION = 45_840_617, 39, 20, 8192, 0.01


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
    import numpy as np

    from tpu_distalg.models import ssgd
    from tpu_distalg.ops import pallas_hashed as ph
    from tpu_distalg.parallel import get_mesh

    # --rehearse: the same calls interpreted at a CPU size (times mean
    # nothing there)
    interp = "--rehearse" in argv
    if jax.devices()[0].platform != "tpu" and not interp:
        print("step0_hashed_fields: no chip", file=sys.stderr)
        return 2
    n_rows = 600_000 if interp else N_ROWS
    mesh = get_mesh(data=1, model=1, devices=jax.devices()[:1])
    config = ssgd.SSGDConfig(
        n_iterations=4, eval_test=False, sampler="fused_gather",
        gather_block_rows=BLOCK_ROWS, mini_batch_fraction=FRACTION)
    t0 = time.perf_counter()
    X, meta = ssgd.build_hashed_table(n_rows, NNZ, HASH_BITS, mesh, config,
                                      data_seed=1234567)
    out = {"loader_s": time.perf_counter() - t0}
    geom = ssgd.hashed_geometry(config, meta)
    plan = ssgd.hashed_field_plan(config, meta)
    n_blocks, ns = ssgd.fused_gather_geometry(config, meta, 1)
    print(f"[step0] loader {out['loader_s']:.2f} s table {X.shape}; "
          f"{ns} of {n_blocks} blocks a step; by value "
          f"{len(plan.dict_fields)} fields, {plan.n_values} values",
          flush=True)
    key = jax.random.key(0)
    ids = jnp.sort(jax.random.choice(key, n_blocks, (ns,),
                                     replace=False)).astype(jnp.int32)
    w = (jax.random.normal(key, (geom.w_len,)) * 0.1).at[
        geom.n_slots + 1:].set(0)
    r = jax.random.normal(jax.random.fold_in(key, 1), (ns, BLOCK_ROWS))

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)

    def row(name, fn, *args):
        out[name] = least_ms(fn, *args)
        print(f"[step0] {name} {out[name]:.3f}", flush=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               "step0_hashed_fields.json"), "w") as f:
            json.dump(out, f, indent=1)

    # (i) by address over fewer fields: is a pair's cost flat?
    dicts = meta["dictionaries"]

    def upto(c):
        return tuple(f for f in range(NNZ)
                     if dicts[f] is None or len(dicts[f]) > c)

    for fields in (tuple(range(NNZ)), upto(2048), upto(4096)):
        tag = f"f{len(fields)}"
        row("addr.gather." + tag, jax.jit(
            lambda X, w, ids, f=fields: ph.margins_vmem(
                X, w, ids, geom, fields=f, interpret=interp)), X, w, ids)
        row("addr.scatter." + tag, jax.jit(
            lambda X, r, ids, f=fields: ph.slot_sums_vmem(
                X, r, ids, geom, fields=f, interpret=interp)), X, r, ids)
    # (ii) by value over the plan's dictionaries, two forms
    row("dict_rows", jax.jit(
        lambda X, ids: ph.dict_rows(X, ids, geom, plan, interpret=interp)),
        X, ids)
    row("value.gather", jax.jit(
        lambda X, w, ids: ph.margins_dict(X, w, ids, geom, plan,
                                         interpret=interp)),
        X, w, ids)
    row("value.sums", jax.jit(
        lambda X, r, ids: ph.slot_sums_dict(X, r, ids, geom, plan,
                                           interpret=interp)[1]),
        X, r, ids)
    firsts = np.flatnonzero(np.diff(plan.group_field, prepend=-1))
    per_field = [plan.entries[a * ph.VALUE_GROUP:b * ph.VALUE_GROUP]
                 for a, b in zip(firsts, list(firsts[1:])
                                 + [len(plan.group_field)])]

    def xla_gather(X, w, ids):
        Xs, m = X[ids], 0.0
        for f, d in zip(plan.dict_fields, per_field):
            d = jnp.asarray(d[d >= 0])
            m = m + jnp.sum(jnp.where(Xs[:, f, :][..., None] == d,
                                      w[d], 0.0), axis=-1)
        return m

    def xla_sums(X, r, ids):
        Xs = X[ids]
        return [jnp.sum(jnp.where(
            Xs[:, f, :][..., None] == jnp.asarray(d[d >= 0]),
            r[..., None], 0.0), axis=(0, 1))
            for f, d in zip(plan.dict_fields, per_field)]

    # (iii) the crossing: one field of C entries by value (a compare
    # costs the same whatever it finds) against a field by address
    for c in (1024, 2048, 4096):
        fake = [None] * NNZ
        fake[4] = np.arange(c, dtype=np.int32) * 7
        real = ph.DICT_MAX_VALUES
        ph.DICT_MAX_VALUES = 1 << 20
        one = ph.field_plan(geom, fake)
        ph.DICT_MAX_VALUES = real
        row(f"value.gather.c{c}", jax.jit(
            lambda X, w, ids, p=one: ph.margins_dict(
                X, w, ids, geom, p, interpret=interp)),
            X, w, ids)
        row(f"value.sums.c{c}", jax.jit(
            lambda X, r, ids, p=one: ph.slot_sums_dict(
                X, r, ids, geom, p, interpret=interp)[1]), X, r, ids)
    # twice the entries a grid step
    ph.VALUE_GROUP = 16
    plan16 = ssgd.hashed_field_plan(config, meta)
    row("value.gather.group16", jax.jit(
        lambda X, w, ids: ph.margins_dict(
            X, w, ids, geom, plan16, interpret=interp)), X, w, ids)
    row("value.sums.group16", jax.jit(
        lambda X, r, ids: ph.slot_sums_dict(
            X, r, ids, geom, plan16, interpret=interp)[1]), X, r, ids)
    ph.VALUE_GROUP = 8
    # the passes as the trainer calls them
    for tag, p in (("", plan), (".noplan", None)):
        row("pass.gather" + tag, jax.jit(
            lambda X, w, ids, p=p: ph.margins(
                X, w, ids, geom, plan=p, interpret=interp)),
            X, w, ids)
        row("pass.scatter" + tag, jax.jit(
            lambda X, r, ids, p=p: ph.slot_sums(
                X, r, ids, geom, plan=p, interpret=interp)),
            X, r, ids)
    m0 = ph.margins_xla(X, w, ids, geom)
    m1 = ph.margins(X, w, ids, geom, plan=plan, interpret=interp)
    g0 = ph.slot_sums_xla(X, r, ids, geom)
    g1 = ph.slot_sums(X, r, ids, geom, plan=plan, interpret=interp)
    out["gather_max_abs_diff"] = float(jnp.max(jnp.abs(m0 - m1)))
    out["scatter_rel_diff"] = float(
        jnp.linalg.norm(g0 - g1) / jnp.linalg.norm(g0))
    print(f"[step0] with the plan against XLA's forms: margins max abs "
          f"diff {out['gather_max_abs_diff']:.3g}, slot sums rel diff "
          f"{out['scatter_rel_diff']:.3g}", flush=True)
    row("xla.value.gather", jax.jit(xla_gather), X, w, ids)
    row("xla.value.sums", jax.jit(xla_sums), X, r, ids)
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    print(f"[step0] peak_bytes_in_use {out['peak_bytes_in_use']}")
    with open(os.path.join(ROOT, "chiprun_out",
                           "step0_hashed_fields.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
