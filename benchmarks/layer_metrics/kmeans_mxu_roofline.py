"""Share of the MXU's bfloat16 peak that a Lloyd iteration's distance
product reaches: the operations the algorithm needs (2 x rows a chip x k
x dim: ``harness/flops_kmeans.py``) over the device time an iteration
under the program's ``tda.kmeans.assign`` and ``tda.kmeans.stats``
scopes, over ``peaks.json``'s ``bf16_flops_per_sec``. Bound by
arithmetic where the work is wide (1.30e13 flop beside a 6.35 GB read at
784 dimensions and 4096 centres: 66 ms of the peak beside 7.8 ms of
HBM). What caps it: the configuration's float32 accuracy costs six
bfloat16 passes, so the distances alone read 16.7 at best; per-cluster
sums through a one-hot product cost three more, 11.1 for both; a
128-deep MXU pads 784 to 896, 9.7. It cannot read over 100. Nothing
where the trace names no scope."""

from harness import flops_kmeans, scopes


def read(ctx):
    parts = [scopes.scope_ms_per_step(ctx, "tda.kmeans." + p)
             for p in ("assign", "stats")]
    if None in parts or sum(parts) <= 0 or not ctx.peaks:
        return None
    need = flops_kmeans.lloyd_iteration_flops_needed(ctx.shapes)
    return need / (sum(parts) / 1e3) / ctx.peaks["bf16_flops_per_sec"] * 100
