"""Share of the HBM roofline that the SSGD kernel reaches: the bytes
the algorithm needs for a step's sampled rows on one chip (64 B a row:
30 features, bias, label in bfloat16; ``harness/bytes.py``) over the
kernel's device time a step, over the chip's peak bandwidth. Bound by
bytes: a row's ~130 multiply-adds are nothing beside 197 TFLOP/s. The
layout moves 80 B a row (valid flag and lane padding), so 100% of the
bytes moved is 80% here.

The Mosaic calls of ``_train_kernel_gathered`` (one data shard) and
``_grad_kernel_gathered`` (several) are found by the names below.
"""

from harness import bytes as nbytes
from harness import readers

PATTERN = (r"(fused_train_gathered|fused_grad_sum_gathered|"
           r"_train_kernel_gathered|_grad_kernel_gathered)")


def read(ctx):
    ms = readers.kernel_ms_per_step(ctx, PATTERN)
    if ms is None or not ctx.peaks:
        return None
    need = nbytes.ssgd_step_bytes_needed(ctx.shapes)
    return need / (ms / 1e3) / ctx.peaks["hbm_bytes_per_sec"] * 100
