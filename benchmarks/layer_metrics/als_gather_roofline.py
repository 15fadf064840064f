"""Share of the HBM roofline that an ALS iteration's gather reaches: the
bytes the normal equations need fetched (2 x ratings x 400 B:
``harness/bytes_als.py``) over the device time an iteration under the
program's ``tda.als.gather`` scope, over the chip's peak bandwidth. What
caps it: a row is held in 128 lanes (512 B moved for 400 needed: 78),
the gathered rows are written before the product reads them (39), and
the pack's padding slots fetch a zero row (about 32). It reads 3.6 on
one v5e (PR 36): XLA's gather is bound by rows, a DMA each, and not by
their bytes. It cannot read over 100. Nothing where the trace names no such scope."""

from harness import bytes_als, scopes


def read(ctx):
    ms = scopes.scope_ms_per_step(ctx, "tda.als.gather")
    if not ms or ms <= 0 or not ctx.peaks:
        return None
    need = bytes_als.iteration_bytes_needed(ctx.shapes)
    return need / (ms / 1e3) / ctx.peaks["hbm_bytes_per_sec"] * 100
