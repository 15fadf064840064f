"""Single-pass Lloyd-iteration Pallas kernel (4 points to a 128-lane row).

**Nothing in the program takes this kernel; it lost both times it was
measured, and it cannot hold a chip-filling table.** Two chip readings,
each with its shape and its run:

* 10M x 16 float32, k = 8, one v5e, an interleaved A/B by this kernel's
  author (round 5, before the driver's ledger; ``bench.py``'s k-means
  phase): XLA ``ops/kmeans.py`` 330 iterations/s against this kernel's
  212 (0.64x).
* 10M x 20 float32, k = 10, one v5e, jax 0.9.0 (my chip run, PR 26, five
  iterations a call, best of three): XLA row path 3.91 ms an iteration
  (2.56e9 rows/s), this kernel 11.60 ms (8.6e8 rows/s, 0.34x), the
  lanes kernel of ``ops/pallas_lloyd.py`` 2.6 ms a pass. At 100M x 20
  (HiBench ``huge``) this path does not compile to fit: its closing
  assignment reshapes the packed rows to ``f32[100007936, 32]``, which
  the TPU pads to 128 lanes (51.2 GB), and before that the packed rows
  hold 128 B a point and a mask padded to as much.

Why it loses: the XLA iteration streams each of its passes near peak
HBM bandwidth; this kernel reads each point once, yet its 128-lane-wide
block pipeline moves 110 to 250 GB/s, and its distance and shift
products run on an MXU that a 128 x 64 selector matrix fills to a
fraction. The kernel is kept as a correct, tested alternative
(``kmeans.make_fit_fn_fused``) and as the recorded negative result:
single-pass fusion is NOT automatically a win when the fused layout
narrows the stream; the same packed-selector algebra wins for SSGD
(``pallas_kernels``) where rows are 2048 lanes wide. ROADMAP D4 leaves
its removal to a ``simplicity`` PR.

Design (one HBM pass; distances, argmin, one-hot and the stats matmul
all happen on the block while it is VMEM-resident):

Layout: ``pp = 128 // dpad`` points are packed per 128-lane row
(``dpad`` = dim padded to a power-of-two lane divisor), mirroring the
SSGD packed layout (``pallas_kernels.pack_augmented``). All per-point
work is expressed as matmuls/elementwise against constant selector
operands — the same no-cross-lane-relayout algebra as the SSGD
megakernel:

  z    (B, pp·k)  = X2 · Csel          — per-slot point·center dots
  sq   (B, pp·k)  = (X2 ⊙ X2) · Esel   — per-slot |p|², k-broadcast
  d2              = sq − 2z + |c|²     — squared distances, lane-major
  argmin          — a log₂(k)-round butterfly of in-group cyclic lane
                    shifts (two full-lane rolls + a class-position
                    select — exact f32 VPU ops), with strict
                    first-minimum tie-break (reference ``closest_center``
                    scans with ``<``, k-means.py:20-28)
  band (pp·k,128) += onehotᵀ · X2      — accumulated stats, folded to
                    (k, dim) by the wrapper's diagonal-band einsum

The k axis is padded to a power of two with phantom centers at a huge
finite distance (never selected). Distances are compared on the bf16
grid (documented contract — near-boundary points may assign to either
of two near-equidistant centers; Lloyd's is insensitive and the XLA
path's default-precision distance matmul rounds the same way); the
stats accumulation runs at HIGHEST precision — cluster SUMS must be
exact, bf16 passes visibly shift the means.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_PREC = jax.lax.Precision.HIGHEST


def packed_geometry(dim: int, k: int):
    """(dpad, pp, k_pad): lane padding for dim, points per packed row,
    power-of-two-padded cluster count."""
    dpad = 8
    while dpad < dim:
        dpad *= 2
    if dpad > 128:
        raise ValueError(f"pallas k-means supports dim <= 128, got {dim}")
    pp = 128 // dpad
    if k > 256:
        # class ids travel through bf16 permutation matmuls in the
        # butterfly argmin; integers above 256 are not bf16-exact, which
        # would silently corrupt the tie-break and the one-hot
        raise ValueError(f"pallas k-means supports k <= 256, got {k}")
    k_pad = 1
    while k_pad < k:
        k_pad *= 2
    # the butterfly's shift permutations are (log2 k_pad, L, L) f32
    # constants resident in VMEM — at k=256 with dim<=8 (L=4096) that
    # is ~512 MB, far over the ~100 MB VMEM budget, and would die
    # inside Mosaic with an opaque allocation error; refuse up front
    lanes = pp * k_pad
    n_shifts = max(1, k_pad.bit_length() - 1)
    perm_bytes = n_shifts * lanes * lanes * 4
    if perm_bytes > 64 * 1024 * 1024:
        raise ValueError(
            f"pallas k-means geometry k={k}, dim={dim} needs "
            f"{perm_bytes >> 20} MB of butterfly permutations "
            f"({n_shifts}×{lanes}×{lanes} f32) — over the VMEM budget; "
            "use the XLA path (ops.kmeans.cluster_stats)"
        )
    return dpad, pp, k_pad


def pack_points(points, mask, *, dim: int, k: int,
                block_rows: int = 4096):
    """(n, dim) f32 + (n,) mask → (n2, 128) packed rows + (n2, pp)
    packed mask (rows padded to a block multiple with mask 0)."""
    import numpy as np

    dpad, pp, _ = packed_geometry(dim, k)
    n = points.shape[0]
    n_t = n + ((-n) % (pp * block_rows))
    out = np.zeros((n_t, dpad), np.float32)
    out[:n, :dim] = np.asarray(points, np.float32)
    m = np.zeros((n_t,), np.float32)
    m[:n] = np.asarray(mask, np.float32)
    return (jnp.asarray(out.reshape(n_t // pp, pp * dpad)),
            jnp.asarray(m.reshape(n_t // pp, pp)))


def _kernel(x_ref, xm_ref, csel_ref, cn2_ref, esel_ref, vsel_ref,
            shs_ref, iota_ref, band_ref, cnt_ref, accb_ref, accc_ref,
            *, k_pad: int, n_shifts: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        accb_ref[:] = jnp.zeros_like(accb_ref)
        accc_ref[:] = jnp.zeros_like(accc_ref)

    # distance dots run at DEFAULT precision — the XLA path's distance
    # matmul (ops.kmeans.assign_clusters) is default too, and distances
    # only feed the argmin
    x = x_ref[:]                                       # (B, 128)
    z = jnp.dot(x, csel_ref[:],
                preferred_element_type=jnp.float32)    # (B, pp·k_pad)
    sq = jnp.dot(x * x, esel_ref[:],
                 preferred_element_type=jnp.float32)
    # distances pre-rounded to the bf16 grid: the butterfly's shift
    # matmuls round their operand to bf16 at default precision, so
    # comparing unrounded-vs-shifted values would be order-dependent.
    # Rounding ONCE keeps every comparison consistent (and matches the
    # rounding class the XLA path's default-precision matmul already
    # applies to its operands). Lane rolls would be exact but measured
    # ~4 us/block vs ~0.2 us for the permutation dots.
    d = (sq - 2.0 * z + cn2_ref[:]).astype(jnp.bfloat16).astype(
        jnp.float32)
    c = jnp.broadcast_to(iota_ref[:], d.shape)         # class id per lane

    # in-group butterfly min: after log2(k_pad) cyclic-shift rounds
    # (shift = permutation matmul — bf16-grid values and class ids
    # < 256 pass through exactly: bf16's 8 mantissa bits represent
    # every integer up to 2^8, matching the k <= 256 guard) every lane
    # of a slot holds (min d, first-min class)
    for s in range(n_shifts):
        sh = shs_ref[s]                                # (L, L)
        ds = jnp.dot(d, sh, preferred_element_type=jnp.float32)
        cs = jnp.dot(c, sh, preferred_element_type=jnp.float32)
        better = (ds < d) | ((ds == d) & (cs < c))
        d = jnp.where(better, ds, d)
        c = jnp.where(better, cs, c)

    onehot = (c == iota_ref[:]).astype(jnp.float32)
    # per-point validity, broadcast over the slot's k_pad lanes (matmul
    # against the 0/1 selector — 0/1 values are exact at any precision)
    valid = jnp.dot(xm_ref[:], vsel_ref[:],
                    preferred_element_type=jnp.float32)
    oh = onehot * valid
    accb_ref[:] += jax.lax.dot_general(
        oh, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_PREC,
    )                                                  # (pp·k_pad, 128)
    accc_ref[:] += jnp.sum(oh, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _done():
        band_ref[:] = accb_ref[:]
        cnt_ref[:] = accc_ref[:]


@functools.partial(
    jax.jit,
    static_argnames=("dim", "k", "block_rows", "interpret"),
)
def fused_cluster_stats(X2, mask2, centers, *, dim: int, k: int,
                        block_rows: int = 4096,
                        interpret: bool = False):
    """One HBM pass → (Σ points, count) per cluster under the CURRENT
    centers. ``X2``/``mask2`` from :func:`pack_points`; ``centers``
    (k, dim) f32. Returns ``(sums (k, dim), counts (k,))`` — same
    contract as ``ops.kmeans.cluster_stats`` after assignment, psum
    across shards exactly like the XLA path."""
    dpad, pp, k_pad = packed_geometry(dim, k)
    L = pp * k_pad
    n2 = X2.shape[0]
    if X2.shape[1] != 128 or n2 % block_rows:
        raise ValueError(
            f"fused_cluster_stats: X2 {X2.shape} needs 128 lanes and a "
            f"row count divisible by block_rows={block_rows}"
        )

    eyep = jnp.eye(pp, dtype=jnp.float32)
    cpad = jnp.zeros((k_pad, dpad), jnp.float32).at[:k, :dim].set(
        centers.astype(jnp.float32))
    # Csel (128, L): Csel[i·dpad+j, i'·k_pad+c] = eye[i,i']·centers[c,j]
    csel = (eyep[:, None, :, None]
            * cpad.T[None, :, None, :]).reshape(128, L)
    # Esel (128, L): Esel[i·dpad+j, i·k_pad+c] = 1  (j < dpad)
    esel = (eyep[:, None, :, None]
            * jnp.ones((1, dpad, 1, k_pad), jnp.float32)).reshape(128, L)
    # |c|² per lane; phantom centers (c >= k) at a huge FINITE distance
    # so the argmin never selects them — inf would turn the shift
    # permutation matmuls into 0·inf = NaN
    cn2_row = jnp.where(
        jnp.arange(k_pad) < k,
        jnp.sum(cpad * cpad, axis=1),
        jnp.float32(1e30),
    )
    cn2 = jnp.tile(cn2_row, (pp,))[None, :]            # (1, L)
    iota = jnp.tile(
        jnp.arange(k_pad, dtype=jnp.float32), (pp,))[None, :]
    # vsel (pp, L): vsel[i, i·k_pad+c] = 1 — mask broadcast per slot
    vsel = (eyep[:, :, None]
            * jnp.ones((1, 1, k_pad), jnp.float32)).reshape(pp, L)
    # cyclic in-group shift permutations, strides 1, 2, 4, ...
    n_shifts = max(1, k_pad.bit_length() - 1)
    lanes = jnp.arange(L)
    grp, cls = lanes // k_pad, lanes % k_pad
    shs = jnp.stack([
        jax.nn.one_hot(grp * k_pad + (cls + (1 << s)) % k_pad, L,
                       dtype=jnp.float32).T
        for s in range(n_shifts)
    ])                                                 # (S, L, L)

    kernel = functools.partial(_kernel, k_pad=k_pad, n_shifts=n_shifts)
    whole = lambda b: (0, 0)  # noqa: E731 — resident constants
    band, cnt = pl.pallas_call(
        kernel,
        grid=(n2 // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, 128), lambda b: (b, 0)),
            pl.BlockSpec((block_rows, pp), lambda b: (b, 0)),
            pl.BlockSpec((128, L), whole),
            pl.BlockSpec((1, L), whole),
            pl.BlockSpec((128, L), whole),
            pl.BlockSpec((pp, L), whole),
            pl.BlockSpec((n_shifts, L, L), lambda b: (0, 0, 0)),
            pl.BlockSpec((1, L), whole),
        ],
        out_specs=[
            pl.BlockSpec((L, 128), whole),
            pl.BlockSpec((1, L), whole),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, 128), jnp.float32),
            jax.ShapeDtypeStruct((1, L), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((L, 128), jnp.float32),
            pltpu.VMEM((1, L), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(X2, mask2, csel, cn2, esel, vsel, shs, iota)

    # fold the diagonal band: sums[c, j] = Σ_i band[i·k_pad+c, i·dpad+j]
    sums = jnp.einsum(
        "icij->cj", band.reshape(pp, k_pad, pp, dpad))[:k, :dim]
    counts = jnp.sum(cnt.reshape(pp, k_pad), axis=0)[:k]
    return sums, counts
