"""Share of the MXU's bfloat16 peak that a dense closure round's boolean
product reaches: the operations the round needs (2 V^3 at the graph's V
= 63 001 vertices: ``harness/flops_closure.py``) over the device time a
round under the program's ``tda.closure.compose`` scope, over
``peaks.json``'s ``bf16_flops_per_sec``. Bound by arithmetic: 5.0e14
operations beside 0.25 TB of operand tiles a round (2.5 s of the peak
beside 0.3 s of HBM). What caps it: one bfloat16 pass is all the
configuration's exactness costs (0 and 1 are exact), so nothing is
multiplied away as in the float32 families; V is padded to whole tiles
(63 488: 97.7 at best), and the MXU's own fill and drain between tiles
take the rest. It cannot read over 100: the count is the dense
product's, and a kernel that proves blocks empty and skips them does
less than this count, which is then a ``benchmark`` PR's to restate
first. Nothing where the trace names no such scope."""

from harness import flops_closure, scopes


def read(ctx):
    ms = scopes.scope_ms_per_step(ctx, "tda.closure.compose")
    if not ms or ms <= 0 or not ctx.peaks:
        return None
    need = flops_closure.round_flops_needed(ctx.shapes)
    return need / (ms / 1e3) / ctx.peaks["bf16_flops_per_sec"] * 100
