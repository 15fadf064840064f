"""Share of the HBM roofline that the fused SpMV kernel reaches: 20 B
an edge slot of the plan (five 4-byte arrays) plus the rank table in
and the accumulator out, a sweep (``harness/bytes.spmv_sweep_bytes``),
over the kernel's device time a sweep, over peak bandwidth. The kernel
is bound by its one-hot matmuls and selects, not by bytes: the share is
small by design and is the room there is. Reports nothing where the
program fell back from the spmv path."""

from harness import bytes as nbytes
from harness import readers

PATTERN = r"(spmv_table|_spmv_kernel)"


def read(ctx):
    sh = ctx.shapes
    if sh.get("path") != "spmv" or not ctx.peaks:
        return None
    ms = readers.kernel_ms_per_step(ctx, PATTERN)
    if ms is None:
        return None
    need = nbytes.spmv_sweep_bytes(sh["chunks"], sh["chunk"], sh["r8"],
                                   sh["rg"], sh["ws"])
    return need / (ms / 1e3) / ctx.peaks["hbm_bytes_per_sec"] * 100
