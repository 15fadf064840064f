#!/usr/bin/env python3
"""Step 0 of the dense closure's compose kernel
(``tpu_distalg/ops/pallas_closure.py``): what a round costs by tile
size, whether the byte-to-bfloat16 turn hides under the MXU, and what a
chain of donated rounds holds on the chip. Kept as the way to re-read
``pallas_closure.TILE`` / ``TILE_K``:

    chiprun -- python3 scripts/step0_closure.py
    JAX_PLATFORMS=cpu python3 scripts/step0_closure.py --rehearse

Rows of the output, one a line as ``[step0] <what> ...``:

  turn V tiles   at ``--small`` vertices (a matrix that fits twice as
                 bfloat16 too): the kernel on byte operands against the
                 same body handed bfloat16 operands (no turn: the MXU
                 and the accumulator alone) and XLA's own bfloat16
                 product of the whole operands (the chip's practical
                 peak at the shape); ms a product, best of ``--reps``
  round V tiles  at Grid250's padded side: ms a round of the shipped
                 ``compose`` between two donated matrices (its partials
                 summed on the host), and the share of the bfloat16
                 peak 2 V^3 makes
  chain          eight rounds queued at once between the two matrices, as
                 the benchmark's window queues them: ms a round and the
                 chip's ``peak_bytes_in_use`` after

The small product is held bit for bit against XLA's. A summary lands in
``chiprun_out/step0_closure.json``. PR 52's readings (``PERF.md`` §6)
were taken with one donated argument and no spare: XLA's copy of the
temporary, 12.8 ms a round, is in every one of them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SIDE = 250                # the source's grid: (SIDE + 1)^2 vertices
PEAK = 197e12             # bfloat16, one v5e (benchmarks/peaks.json)
TILES = ((1024, 1024, 1024), (2048, 2048, 512), (2048, 2048, 1024),
         (2048, 2048, 2048), (1024, 2048, 1024))


def say(msg):
    print(f"[step0] {msg}", flush=True)


def grid_matrix(side: int, v_padded: int, seed: int = 0):
    """The start matrix of the n x n grid (labels permuted), scattered
    on the device."""
    import jax.numpy as jnp
    import numpy as np

    n = side + 1
    ids = np.arange(n * n).reshape(n, n)
    src = np.concatenate([ids[:, :-1].ravel(), ids[:-1, :].ravel()])
    dst = np.concatenate([ids[:, 1:].ravel(), ids[1:, :].ravel()])
    perm = np.random.default_rng(seed).permutation(n * n)
    return jnp.zeros((v_padded, v_padded), jnp.int8).at[
        jnp.asarray(perm[src]), jnp.asarray(perm[dst])].set(1)


def best_ms(fn, reps: int) -> float:
    import jax

    got = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        got.append((time.perf_counter() - t0) * 1e3)
    return min(got)


def bf16_operands(p16, q16, old, tiles, interpret):
    """The kernel's body on bfloat16 operands: no turn."""
    import jax
    import jax.numpy as jnp

    from tpu_distalg.ops import pallas_closure as pc
    from tpu_distalg.ops.pallas_api import pl, pltpu

    tm, tn, tk = tiles
    side = old.shape[0]
    gi, gj, gk = side // tm, side // tn, side // tk
    return pl.pallas_call(
        pc._compose_kernel, name="_closure_compose_bf16",
        grid=(gi, gj, gk),
        in_specs=[pl.BlockSpec((tm, tk), lambda i, j, k: (i, k)),
                  pl.BlockSpec((tk, tn), lambda i, j, k: (k, j)),
                  pl.BlockSpec((tm, tn), lambda i, j, k: (i, j))],
        out_specs=[pl.BlockSpec((tm, tn), lambda i, j, k: (i, j)),
                   pl.BlockSpec(pc._CNT_BLOCK, lambda i, j, k: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((side, side), jnp.int8),
                   jax.ShapeDtypeStruct((gi * 8, gj * 128), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=pc._vmem_bytes(tm, tn, tk) + 4 * tk * (tm + tn)),
        interpret=interpret)(p16, q16, old)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, kernels interpreted (CPU)")
    ap.add_argument("--small", type=int, default=16384)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--skip-chain", action="store_true")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_distalg.ops import pallas_closure as pc

    dev = jax.devices()[0]
    interp = dev.platform != "tpu"
    if interp and not a.rehearse:
        print("step0_closure: no TPU; --rehearse runs it tiny on the CPU",
              file=sys.stderr)
        return 2
    side, small, tiles = SIDE, a.small, TILES
    if a.rehearse:
        side, small = 15, 512
        tiles = ((128, 128, 128), (256, 256, 128))
    out: dict = {"device": dev.device_kind, "turn": [], "round": []}

    # --- the turn, at a size that fits as bfloat16 too
    unit = max(max(t) for t in tiles)
    small = -(-small // unit) * unit
    p = grid_matrix(int(small ** 0.5) - 1, small)
    p16 = p.astype(jnp.bfloat16)
    xla = jax.jit(lambda a16, old: ((jnp.dot(
        a16, a16, preferred_element_type=jnp.float32) > 0) | (old != 0)
    ).astype(jnp.int8))
    want = np.asarray(xla(p16, p))
    ms_xla = best_ms(lambda: xla(p16, p), a.reps)
    flops = 2.0 * small ** 3
    say(f"turn {small} xla-bf16 {ms_xla:.3f} ms "
        f"({flops / ms_xla / 1e9 / PEAK * 1e12 * 100:.1f}% of peak)")
    for t in tiles:
        k8 = jax.jit(lambda x, t=t: pc.compose(x, x, tiles=t,
                                               interpret=interp))
        k16 = jax.jit(lambda x16, x, t=t: bf16_operands(x16, x16, x, t,
                                                        interp))
        new, part = k8(p)
        same = bool(np.array_equal(np.asarray(new), want))
        cnt = int(np.asarray(part, np.int64).sum())
        ms8 = best_ms(lambda: k8(p), a.reps)
        ms16 = best_ms(lambda: k16(p16, p), a.reps)
        say(f"turn {small} tiles {t} bytes {ms8:.3f} ms "
            f"({flops / ms8 / 1e9 / PEAK * 1e12 * 100:.1f}%) bf16-operands "
            f"{ms16:.3f} ms ({flops / ms16 / 1e9 / PEAK * 1e12 * 100:.1f}%) "
            f"equal_to_xla {same} pairs {cnt} of {int(want.sum())}")
        out["turn"].append({"tiles": t, "ms_bytes": ms8, "ms_bf16": ms16,
                            "ms_xla": ms_xla, "equal": same})
    del p16, want, new

    # --- a round at the source's size
    vp = -(-(side + 1) ** 2 // unit) * unit
    flops = 2.0 * vp ** 3

    def chained(t):
        # the program's round: the result written into a donated spare,
        # the matrix read handed back as the next spare (spare first:
        # models/transitive_closure.make_round_fn says why)
        return jax.jit(lambda spare, x: pc.compose(
            x, x, spare, tiles=t, interpret=interp) + (x,),
            donate_argnums=(0, 1))

    for t in tiles:
        f = chained(t)
        x = grid_matrix(side, vp)
        spare = jnp.zeros_like(x)
        got = []
        for _ in range(2 + (not a.rehearse)):
            t0 = time.perf_counter()
            x, part, spare = f(spare, x)
            cnt = int(np.asarray(part, np.int64).sum())
            got.append((time.perf_counter() - t0) * 1e3)
        stats = dev.memory_stats() or {}
        say(f"round {vp} tiles {t} ms {[round(g, 1) for g in got]} "
            f"({flops / min(got) / 1e9 / PEAK * 1e12 * 100:.1f}% of peak) "
            f"pairs after {len(got)} rounds {cnt} peak_bytes "
            f"{stats.get('peak_bytes_in_use', 0) / 1e9:.3f} GB")
        out["round"].append({"tiles": t, "ms": got, "pairs": cnt})
        del x, part, spare

    # --- eight queued, as the window queues them
    if not a.skip_chain:
        f = chained(None if not a.rehearse else tiles[0])
        x = grid_matrix(side, vp)
        spare = jnp.zeros_like(x)
        x, part, spare = f(spare, x)
        jax.block_until_ready(part)
        t0 = time.perf_counter()
        parts = []
        for _ in range(8):
            x, part, spare = f(spare, x)
            parts.append(part)
        t_disp = time.perf_counter() - t0
        jax.block_until_ready(parts)
        dt = time.perf_counter() - t0
        stats = dev.memory_stats() or {}
        cnts = [int(np.asarray(q, np.int64).sum()) for q in parts]
        say(f"chain 8 queued in {t_disp * 1e3:.1f} ms, {dt / 8 * 1e3:.1f} ms "
            f"a round, pairs {cnts}, peak_bytes "
            f"{stats.get('peak_bytes_in_use', 0) / 1e9:.3f} GB")
        out["chain"] = {"ms": dt / 8 * 1e3, "pairs": cnts,
                        "peak": stats.get("peak_bytes_in_use", 0)}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "step0_closure.json"),
              "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
