#!/usr/bin/env python3
"""What each part of a step over hashed rows costs alone, on the chip,
at the cell's shape (``lrhash39_46m_frac01``: 56 sampled blocks of 8192
rows, 39 slots a row, 2^20 weights): ms a step, least of three.

    chiprun -- python3 benchmarks/tools/step0_hashed.py [--quick]

The skewed table is the program's (``ssgd.build_hashed_table``, the
cell's generator parameters), the uniform one is drawn here. Rows of
the output, one a line as ``[step0] <name> <ms>``:

  read                    the sampled blocks' read alone (a sum)
  xla.gather/.scatter     ``w[idx]`` summed over fields, ``.at[idx].add``
  xla.segment_sum.sorted  the pairs sorted by slot beforehand (what a
                          pack-time sort of a block would buy), with
                          and without the gather of the residuals
  vmem.gather.rowsN       the Mosaic loop, N rows written out a trip
  vmem.scatter.accN       the same for the scatter, N accumulators
  *.uniform               the form on uniformly drawn slots
A summary lands in ``chiprun_out/step0_hashed.json``.
"""

from __future__ import annotations

import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import manifest as mf  # noqa: E402

CELL = "lrhash39_46m_frac01"


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

    from families import ssgd_hashed as fam
    from tpu_distalg.models import ssgd
    from tpu_distalg.ops import pallas_hashed as ph
    from tpu_distalg.parallel import get_mesh

    quick = "--quick" in argv
    if jax.devices()[0].platform != "tpu":
        print("step0_hashed: no chip", file=sys.stderr)
        return 2
    cell = mf.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    c, t = cell.config, cell.traffic
    sh = fam.shapes(c, t)
    mesh = get_mesh(data=1, model=1, devices=jax.devices()[:1])
    config = fam.program_config(c, t)
    t0 = time.perf_counter()
    X, meta = ssgd.build_hashed_table(
        c["n_rows"], c["nnz"], c["hash_bits"], mesh, config,
        data_seed=1234567, **fam.loader_args(c))
    out = {"loader_s": time.perf_counter() - t0}
    print(f"[step0] loader {out['loader_s']:.2f} s (compile in it) table "
          f"{X.shape} {X.nbytes / 1e9:.3f} GB", flush=True)
    geom = ssgd.hashed_geometry(config, meta)
    ns, B, D = sh["n_sampled"], geom.block_rows, geom.n_slots
    key = jax.random.key(0)
    ids = jnp.sort(jax.random.choice(key, sh["n_blocks"], (ns,),
                                     replace=False)).astype(jnp.int32)
    n_uni = 4 * ns
    X_uni = jax.jit(lambda k: jax.random.randint(
        k, (n_uni, geom.fields_held, B), 0, D, jnp.int32))(key)
    ids_uni = jnp.arange(ns, dtype=jnp.int32) * 4
    w = jax.random.normal(key, (geom.w_len,)) * 0.1
    r = jax.random.normal(jax.random.fold_in(key, 1), (ns, B))

    def row(name, fn, *args):
        out[name] = least_ms(fn, *args)
        print(f"[step0] {name} {out[name]:.3f}", flush=True)

    row("read", jax.jit(lambda X, ids: jnp.sum(X[ids], dtype=jnp.int32)),
        X, ids)
    tables = [("", X, ids)] + ([] if quick else [(".uniform", X_uni,
                                                  ids_uni)])
    for tag, Xt, it in tables:
        row("xla.gather" + tag,
            jax.jit(lambda X, w, ids: ph.margins_xla(X, w, ids, geom)),
            Xt, w, it)
        row("xla.scatter" + tag,
            jax.jit(lambda X, r, ids: ph.slot_sums_xla(X, r, ids, geom)),
            Xt, r, it)
        row("vmem.gather.rows2" + tag, jax.jit(
            lambda X, w, ids: ph.margins_vmem(X, w, ids, geom)), Xt, w, it)
        row("vmem.scatter.acc2" + tag, jax.jit(
            lambda X, r, ids: ph.slot_sums_vmem(X, r, ids, geom)),
            Xt, r, it)
    # pairs sorted by slot, as a pack-time sort of the sampled rows
    idx = X[ids][:, :geom.nnz, :].reshape(-1)
    order = jnp.argsort(idx)
    idx_sorted = idx[order]
    pair_row = (order // (geom.nnz * B)) * B + order % B   # pair -> row
    seg = jax.jit(lambda v, s: jax.ops.segment_sum(
        v, s, num_segments=D, indices_are_sorted=True))
    row("xla.segment_sum.sorted", seg, r.reshape(-1)[pair_row],
        idx_sorted)
    row("xla.segment_sum.sorted+r_gather",
        jax.jit(lambda r, p, s: jax.ops.segment_sum(
            r.reshape(-1)[p], s, num_segments=D,
            indices_are_sorted=True)), r, pair_row, idx_sorted)
    row("xla.scatter.sorted_unflagged", jax.jit(
        lambda v, s: jnp.zeros((D,), jnp.float32).at[s].add(v)),
        r.reshape(-1)[pair_row], idx_sorted)
    if not quick:
        for rows in (1, 4):
            row(f"vmem.gather.rows{rows}", jax.jit(
                lambda X, w, ids: ph.margins_vmem(
                    X, w, ids, geom, rows=rows)), X, w, ids)
        for n_acc in (1, 4):
            row(f"vmem.scatter.acc{n_acc}", jax.jit(
                lambda X, r, ids: ph.slot_sums_vmem(
                    X, r, ids, geom, n_acc=n_acc)), X, r, ids)
        row("vmem.scatter.acc4.rows4", jax.jit(
            lambda X, r, ids: ph.slot_sums_vmem(
                X, r, ids, geom, n_acc=4, rows=4)), X, r, ids)
    # the two forms agree (float32, another order of additions)
    m0 = ph.margins_xla(X, w, ids, geom)
    m1 = ph.margins_vmem(X, w, ids, geom)
    g0 = ph.slot_sums_xla(X, r, ids, geom)
    g1 = ph.slot_sums_vmem(X, r, ids, geom)
    out["gather_max_abs_diff"] = float(jnp.max(jnp.abs(m0 - m1)))
    out["scatter_rel_diff"] = float(
        jnp.linalg.norm(g0 - g1) / jnp.linalg.norm(g0))
    print(f"[step0] forms agree: margins max abs diff "
          f"{out['gather_max_abs_diff']:.3g}, slot sums rel diff "
          f"{out['scatter_rel_diff']:.3g}", flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "step0_hashed.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
