"""The dense closure's round as one Mosaic kernel: a boolean product of
two byte matrices on the MXU, or-ed into the left one.

``compose(p, q)`` is ``p | (p ∘ q > 0)`` over ``int8`` 0/1 operands that
stay in HBM one byte a cell (``models/transitive_closure.py`` hands it
the path matrix twice: the doubling round). A tile of each operand is
turned to bfloat16 in VMEM (0 and 1 are exact there), the products are
added up in float32 over the contraction (a sum of at most V ones is
exact past any V a chip holds), the old tile is or-ed in at the
contraction's last block and bytes go back. The round's pair count
leaves the same pass as one int32 partial a tile, so the new matrix is
not read again for it. One bfloat16 pass and nothing else: an int8
product would be a different peak (``PERF.md`` §7).

The grid is (row tiles, column tiles, contraction blocks), the
contraction innermost; the accumulator lives across it. The tile sizes
are Step 0's (``PERF.md`` §6, PR 52): the byte-to-bfloat16 turn is
vector work that hides under the MXU at every size tried (byte operands
and bfloat16 operands read the same to 0.5%), and a tile's operand
traffic falls with its side. ``V`` is a multiple of ``TILE``: the model
pads the graph with isolated vertices, :func:`compose` pads any other
caller's operands and cuts the result back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpu_distalg.ops.pallas_api import pl, pltpu

#: an output tile's rows and columns and the depth of a contraction
#: block. Step 0 on one v5e at V 63 488 (``PERF.md`` §6, PR 52; ms a
#: round): (1024, 2048, 1024) 2653.1 and (2048, 2048, 512) 2652.9, 97.9%
#: of the bfloat16 peak; (1024, 1024, 1024) 2674.2; (2048, 2048, 2048)
#: 2963.6; (2048, 2048, 1024) 3275.5. The first compiles a second
#: faster than the second. The model pads V to ``TILE``, the larger side.
TILE_M = 1024
TILE = TILE_N = 2048
TILE_K = 1024
#: from this many vertices on a TPU chip runs the kernel; under it XLA's
#: own product of the whole operands (64 MB as float32 at 4096) is small
MOSAIC_MIN_VERTICES = 2048
#: the count's lane-dense home: a tile's partial fills one (8, 128) block
_CNT_BLOCK = (8, 128)


def compose_form(n_vertices: int, on_tpu: bool, n_shards: int) -> str:
    """``mosaic`` or ``xla``, from what the caller can see: the kernel
    where one TPU chip holds the whole matrix and it is large enough to
    fill a tile; XLA's product on the CPU, on small graphs and on a mesh
    (row-sharded paths need the whole matrix a round: XLA's
    all-gather)."""
    if on_tpu and n_shards == 1 and n_vertices >= MOSAIC_MIN_VERTICES:
        return "mosaic"
    return "xla"


def padded_vertices(n_vertices: int, form: str, n_shards: int) -> int:
    """The matrix's side: whole tiles for the kernel, whole shards for
    XLA's form. Padding vertices are isolated and add no path."""
    unit = TILE if form == "mosaic" else n_shards
    return -(-n_vertices // unit) * unit


def _compose_kernel(p_ref, q_ref, old_ref, *rest):
    # with ``into`` the call has a fourth operand, left in HBM and never
    # read: the buffer the output is written to
    out_ref, cnt_ref, acc_ref = rest[-3:]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(p_ref[...].astype(jnp.bfloat16),
                            q_ref[...].astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _out():
        new = (acc_ref[...] > 0.0) | (old_ref[...] != 0)
        out_ref[...] = new.astype(jnp.int8)
        cnt_ref[...] = jnp.full(_CNT_BLOCK, jnp.sum(new.astype(jnp.int32)),
                                jnp.int32)


def _vmem_bytes(tm: int, tn: int, tk: int) -> int:
    """Two buffers of each byte tile, the accumulator, the bfloat16
    turns of both operand tiles with their int32 and float32 steps, the
    last block's masks, and room."""
    tiles = 2 * (tm * tk + tk * tn + 2 * tm * tn)
    turns = 10 * (tm * tk + tk * tn)
    return tiles + turns + 3 * 4 * tm * tn + (8 << 20)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def compose(p, q, into=None, *, tiles: tuple[int, int, int] | None = None,
            interpret: bool = False):
    """``(p | (p ∘ q > 0), partials)``: ``p`` ``int8[m, c]`` and ``q``
    ``int8[c, n]`` hold 0 and 1, ``m == c == n`` in the closure (the
    product is or-ed into ``p``, so ``p`` is square wherever ``n ==
    c``); the result is ``int8[m, n]`` and ``partials`` ``int32[m /
    tm, n / tn]``, the number of ones of each output tile (a tile holds
    under 2^31 cells; their sum is the caller's: it can pass 2^31).
    ``into`` is a matrix of the result's shape whose buffer the result
    is written to (its contents are not read): a caller that donates it
    chains rounds between two matrices, with no third and no copy.
    ``tiles`` is ``(tm, tn, tk)``, by default the chip's; sides that are
    no whole tiles are padded with zeros here and the result cut back
    (the model never takes that copy: it pads the graph)."""
    tm, tn, tk = tiles or (TILE_M, TILE_N, TILE_K)
    m, c = p.shape
    c2, n = q.shape
    if c != c2 or m != c or n != c:
        raise ValueError(
            f"compose: p {p.shape} and q {q.shape} are not two square "
            f"matrices of one side")
    if p.dtype != jnp.int8 or q.dtype != jnp.int8:
        raise ValueError(f"compose: int8 operands, got {p.dtype}, {q.dtype}")
    unit = max(tm, tn, tk)
    if unit % tm or unit % tn or unit % tk:
        raise ValueError(f"compose: tiles {(tm, tn, tk)} do not nest")
    side = -(-m // unit) * unit
    if side != m:
        p = jnp.pad(p, ((0, side - m), (0, side - m)))
        q = jnp.pad(q, ((0, side - m), (0, side - m)))
        into = None
    elif into is not None and (into.shape, into.dtype) != (p.shape, p.dtype):
        raise ValueError(f"compose: into {into.shape} {into.dtype} is not "
                         f"the result's {p.shape} int8")
    gi, gj, gk = side // tm, side // tn, side // tk
    new, cnt = pl.pallas_call(
        _compose_kernel,
        name="_closure_compose_kernel",
        grid=(gi, gj, gk),
        in_specs=[pl.BlockSpec((tm, tk), lambda i, j, k: (i, k)),
                  pl.BlockSpec((tk, tn), lambda i, j, k: (k, j)),
                  pl.BlockSpec((tm, tn), lambda i, j, k: (i, j))]
        + ([] if into is None else [pl.BlockSpec(memory_space=pl.ANY)]),
        input_output_aliases={} if into is None else {3: 0},
        out_specs=[pl.BlockSpec((tm, tn), lambda i, j, k: (i, j)),
                   pl.BlockSpec(_CNT_BLOCK, lambda i, j, k: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((side, side), jnp.int8),
                   jax.ShapeDtypeStruct((gi * _CNT_BLOCK[0],
                                         gj * _CNT_BLOCK[1]), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # the accumulator lives across the contraction; rows and
            # columns are one core's on a v5e either way
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(tm, tn, tk)),
        interpret=interpret,
    )(p, q, p, *(() if into is None else (into,)))
    partials = cnt[::_CNT_BLOCK[0], ::_CNT_BLOCK[1]]
    if side != m:
        # the padding's cells are zero in both operands, so in the
        # result too: the partials already count the real cells alone
        new = new[:m, :m]
    return new, partials
