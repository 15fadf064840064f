"""A float32 as three pieces that bfloat16 holds exactly.

A float32 significand has 24 bits, a bfloat16 one 8: ``hi`` is the top
16 bits of ``x``, ``mid`` the top 16 of ``x - hi``, ``lo`` the rest, and
every step is exact. A product on the MXU of such pieces against a
factor that bfloat16 also holds exactly (a 0/1 mask, a one-hot) loses
nothing in one pass a piece: the per-cluster sums of
``ops/pallas_lloyd.py`` (PR 29) and the one-hot scatter of
``ops/pallas_pagerank.py`` (PR 39) are built on it.
(``ops/pallas_lloyd_wide.py`` still holds a copy of its own, which casts
the pieces to bfloat16: ROADMAP D16.)

Bit masks and exact subtractions only, inside a Pallas kernel or out:
XLA may drop ``astype(bfloat16).astype(float32)`` as excess precision,
and that conversion rounds where a mask truncates."""

from __future__ import annotations

import jax.numpy as jnp

from tpu_distalg.ops.pallas_api import pltpu

TOP = 0xFFFF0000           # the half of a float32 that is a bfloat16


def bits(x):
    return pltpu.bitcast(x, jnp.uint32)


def f32(u):
    return pltpu.bitcast(u, jnp.float32)


def pieces(x):
    """The bits of ``hi``, of ``r = x - hi`` (whose top half is ``mid``)
    and of ``lo = r - mid``: every step exact."""
    hi = bits(x) & jnp.uint32(TOP)
    r = bits(x - f32(hi))
    return hi, r, bits(f32(r) - f32(r & jnp.uint32(TOP)))


def split3(x):
    """``x`` as three float32 pieces, each exact in bfloat16 (its low
    16 bits are zero), that add back to ``x`` bit for bit."""
    hi, r, lo = pieces(x)
    return f32(hi), f32(r & jnp.uint32(TOP)), f32(lo)
