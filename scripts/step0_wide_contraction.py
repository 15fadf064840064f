#!/usr/bin/env python3
"""Step 0 of the wide pass's distance product as one contraction: what
the assign kernel costs alone on the chip, ms a pass, least of three,
each with its dispatch, in two forms: ``six``, the kernel as it was
until PR 35 (six bfloat16 products of ``dim_mxu`` each; kept here, in
:func:`six_assign`, as what the reading is against), and ``stacked``,
what ``ops/pallas_lloyd_wide.py`` ships (their bands end to end, ``6 *
dim_held`` deep, padded to 128 once):

    chiprun -- python3 scripts/step0_wide_contraction.py
    JAX_PLATFORMS=cpu python3 scripts/step0_wide_contraction.py --rehearse

Rows of the output, one a line as ``[step0] <name> <ms>``:

  cell.six / cell.stacked      (i), (ii): the ``kmeans784_2m_k4096``
                         cell's shape (2 025 000 x 784 against 4096
                         centres, P 512, TN 512); ``differ`` is how many
                         points the two forms send to different centres
  cell.stacked.pinned    (ii) with every grid step reading the first
                         tile of centres, so that no tile is copied
                         after the first (the result is wrong, the
                         products are the same): what the centres'
                         stream past a block costs is (ii) less this
  cell.<form>.P<p>.TN<tn>      (iii): other tiles of the same kernel
  dim<d>.six / dim<d>.stacked  (v): k 4096, 400 000 points, P 512,
                         TN as the geometry gives it (512; 256 past
                         dim 2720) at other widths; ``slabs`` are the
                         128-deep slabs a tile of either form
A summary lands in ``chiprun_out/step0_wide_contraction.json``.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

N, DIM, K = 2_025_000, 784, 4096
N_OTHER = 400_000
OTHER_DIMS = (64, 96, 100, 128, 200, 256, 1024, 2000, 2736, 4096)


def _six_kernel(c_ref, c2_ref, x_ref, out_ref, pieces_ref, best_ref,
                arg_ref, *, tn: int):
    """``_wide_assign_kernel`` as PR 30 wrote it: three pieces a side,
    each padded to ``dim_mxu`` rows, six ``dot_general``s a tile."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from tpu_distalg.ops import pallas_lloyd_wide as wide

    dot, nn = wide._dot, wide._NN
    i, j = pl.program_id(0), pl.program_id(1)
    p = x_ref.shape[1]
    held, deep = x_ref.shape[0], pieces_ref.shape[1]
    if deep > held:
        @pl.when((i == 0) & (j == 0))
        def _zero():
            pieces_ref[:, pl.ds(held, deep - held), :] = jnp.zeros(
                (3, deep - held, p), jnp.bfloat16)

    @pl.when(j == 0)
    def _new_block():
        for q, piece in enumerate(wide.split3(x_ref[...])):
            pieces_ref[q, pl.ds(0, held), :] = piece
        best_ref[...] = jnp.full(best_ref.shape, jnp.inf, jnp.float32)
        arg_ref[...] = jnp.zeros(arg_ref.shape, jnp.float32)

    xh, xm, xl = pieces_ref[0], pieces_ref[1], pieces_ref[2]
    ch, cm, cl = c_ref[0], c_ref[1], c_ref[2]
    s = dot(cl, xh, nn) + dot(ch, xl, nn)
    s = s + dot(cm, xm, nn)
    s = s + (dot(cm, xh, nn) + dot(ch, xm, nn))
    s = (s + dot(ch, xh, nn)) + c2_ref[...]

    shape = (tn // wide.SUBLANES, wide.SUBLANES, p)
    s3 = s.reshape(shape)
    m8 = jnp.min(s3, axis=0)
    cidx = (j * tn
            + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * wide.SUBLANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            ).astype(jnp.float32)
    i8 = jnp.min(jnp.where(s3 == m8[None], cidx, wide._BIG), axis=0)
    better = m8 < best_ref[...]
    best_ref[...] = jnp.where(better, m8, best_ref[...])
    arg_ref[...] = jnp.where(better, i8, arg_ref[...])

    @pl.when(j == pl.num_programs(1) - 1)
    def _fold():
        b, a = best_ref[...], arg_ref[...]
        m = jnp.min(b, axis=0, keepdims=True)
        out_ref[...] = jnp.min(jnp.where(b == m, a, wide._BIG), axis=0,
                               keepdims=True).astype(jnp.int32)


def six_assign(x3, centers, *, geom, interpret: bool = False):
    """``wide_assign`` as it was until PR 35, its VMEM limit included."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from tpu_distalg.ops import pallas_lloyd_wide as wide

    nb, held, p = x3.shape
    k, dim, tn, deep = geom.k, geom.dim, geom.centre_tile, geom.dim_mxu
    c32 = centers.astype(jnp.float32)
    cm2 = jnp.pad(-2.0 * c32, ((0, geom.k_padded - k), (0, deep - dim)))
    c2 = jnp.pad(jnp.sum(c32 * c32, axis=1), (0, geom.k_padded - k),
                 constant_values=jnp.inf)[:, None]
    vmem = (2 * held * p * 4 + 3 * deep * p * 2 + 2 * 3 * tn * deep * 2
            + 8 * tn * p * 4 + (16 << 20))
    return pl.pallas_call(
        functools.partial(_six_kernel, tn=tn),
        name="_wide_assign_kernel_six",
        grid=(nb, geom.k_padded // tn),
        in_specs=[pl.BlockSpec((3, tn, deep), lambda i, j: (0, j, 0)),
                  pl.BlockSpec((tn, 1), lambda i, j: (j, 0)),
                  pl.BlockSpec((None, held, p), lambda i, j: (i, 0, 0))],
        out_specs=pl.BlockSpec((None, 1, p), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, p), jnp.int32),
        scratch_shapes=[pltpu.VMEM((3, deep, p), jnp.bfloat16),
                        pltpu.VMEM((wide.SUBLANES, p), jnp.float32),
                        pltpu.VMEM((wide.SUBLANES, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(jnp.stack(wide.split3(cm2)), c2, x3)


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
    from jax.experimental import pallas as pl

    from tpu_distalg.ops import pallas_lloyd_wide as wide
    from tpu_distalg.utils import compile_cache

    # --rehearse: the same calls interpreted at a CPU size (times mean
    # nothing there)
    interp = "--rehearse" in argv
    if jax.devices()[0].platform != "tpu" and not interp:
        print("step0_wide_contraction: no chip", file=sys.stderr)
        return 2
    compile_cache.configure()
    n, n_other, k = (2048, 1024, 256) if interp else (N, N_OTHER, K)
    out: dict[str, float | int | None] = {}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)

    def row(name, fn, *args):
        try:
            out[name] = least_ms(fn, *args)
            print(f"[step0] {name} {out[name]:.3f}", flush=True)
        except Exception as e:     # a form the compiler refuses is a reading
            out[name] = None
            print(f"[step0] {name} FAILED {type(e).__name__}: "
                  f"{str(e)[:300]}", flush=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               "step0_wide_contraction.json"), "w") as f:
            json.dump(out, f, indent=1)

    def table(dim, n, p):
        """Normal noise in the geometry's blocks (a dense pass does not
        see the values), the held rows past ``dim`` zero."""
        held = -(-dim // wide.PIECE_ROWS) * wide.PIECE_ROWS

        @jax.jit
        def gen(key):
            return jax.lax.map(
                lambda b: jnp.pad(jax.random.normal(
                    jax.random.fold_in(key, b), (dim, p), jnp.float32),
                    ((0, held - dim), (0, 0))),
                jnp.arange(-(-n // p)))

        return jax.block_until_ready(gen(jax.random.key(1)))

    def geometry(dim, p=wide.BLOCK_POINTS, tn=wide.CENTRE_TILE):
        g = wide.wide_geometry(dim, k)
        return wide.WideGeometry(dim, k, p, min(tn, g.centre_tile),
                                 g.stats_tile)

    def assign(geom, stacked):
        form = wide.wide_assign if stacked else jax.jit(
            six_assign, static_argnames=("geom", "interpret"))
        return functools.partial(form, geom=geom, interpret=interp)

    def slabs(geom, stacked):
        return (geom.dist_depth if stacked else 6 * geom.dim_mxu) \
            // wide.LANES

    centers = jax.random.normal(jax.random.key(2), (k, DIM), jnp.float32)

    # (i), (ii): the cell's shape, both forms, and where they differ
    x3 = table(DIM, n, wide.BLOCK_POINTS)
    g = geometry(DIM)
    row("cell.six", assign(g, False), x3, centers)
    row("cell.stacked", assign(g, True), x3, centers)
    a6 = assign(g, False)(x3, centers)
    a1 = assign(g, True)(x3, centers)
    out["cell.differ"] = int(jnp.sum(a6 != a1))
    out["cell.points"] = int(a6.size)
    print(f"[step0] cell.differ {out['cell.differ']} of {a6.size}",
          flush=True)
    del a6, a1

    # (ii) again with the centres' tile pinned: the same kernel through a
    # BlockSpec that maps every grid step to tile 0
    real = pl.BlockSpec

    def pinned_spec(shape=None, index_map=None, **kw):
        if shape is not None and shape[0] == g.centre_tile \
                and len(shape) == 2 and shape[1] > 1:
            return real(shape, lambda i, j: (0, 0), **kw)
        return real(shape, index_map, **kw)

    wide.pl.BlockSpec = pinned_spec
    jax.clear_caches()
    try:
        row("cell.stacked.pinned", assign(g, True), x3, centers)
    finally:
        wide.pl.BlockSpec = real
        jax.clear_caches()

    # (iii) other tiles of the same kernel
    for p, tn in ((512, 256), (1024, 512), (1024, 256)):
        if p != x3.shape[2]:
            x3.delete()
            x3 = table(DIM, n, p)
        gt = geometry(DIM, p, tn)
        for stacked in (True, False):
            form = "stacked" if stacked else "six"
            row(f"cell.{form}.P{p}.TN{tn}", assign(gt, stacked), x3,
                centers)
    x3.delete()

    # (v) other widths, both forms: where they cross
    for dim in OTHER_DIMS:
        gd = geometry(dim)
        xd = table(dim, n_other, gd.block_points)
        cd = jax.random.normal(jax.random.key(3), (k, dim), jnp.float32)
        for stacked in (False, True):
            form = "stacked" if stacked else "six"
            out[f"dim{dim}.{form}.slabs"] = slabs(gd, stacked)
            row(f"dim{dim}.{form}", assign(gd, stacked), xd, cd)
        out[f"dim{dim}.TN"] = gd.centre_tile
        xd.delete()
    with open(os.path.join(ROOT, "chiprun_out",
                           "step0_wide_contraction.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
