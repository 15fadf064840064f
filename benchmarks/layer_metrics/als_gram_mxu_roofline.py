"""Share of the MXU's bfloat16 peak that an ALS iteration's per-owner
Gramians reach: the operations the normal equations need (2 x ratings x
2 k^2: ``harness/flops_als.py``) over the device time an iteration under
the program's ``tda.als.gram`` scope, over ``peaks.json``'s
``bf16_flops_per_sec``. What caps it: the configuration's float32
accuracy costs six bfloat16 passes (16.7), 100 columns are held in 128
lanes on both sides of the product (10.2), and the pack pads an owner to
whole segments and classes (slots held / ratings 1.20: about 8.5). It
reads 4.6 on one v5e (PR 36). It cannot read over 100. Nothing where the trace names no such scope."""

from harness import flops_als, scopes


def read(ctx):
    ms = scopes.scope_ms_per_step(ctx, "tda.als.gram")
    if not ms or ms <= 0 or not ctx.peaks:
        return None
    need = flops_als.iteration_flops_needed(ctx.shapes)
    return need / (ms / 1e3) / ctx.peaks["bf16_flops_per_sec"] * 100
