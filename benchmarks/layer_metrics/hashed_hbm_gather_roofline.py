"""Share of the HBM roofline that the gather of an indexed table's
fields past VMEM reaches: the bytes the algorithm needs (a pair's index
and the weight it names, 8 B; ``harness/bytes_indexed.py``) over the
device time a step of the program's ``_hashed_hbm_gather_kernel``
(found by name: the one Mosaic call under ``tda.ssgd.table_hbm``; the
scatter beside it there is XLA's), over the chip's peak bandwidth. The
kernel moves a 512 B row of the table a pair and is bound by the rate at
which copies are issued, so the share reads near 0.1: it says how far a
gather of single weights stands from its bytes. Nothing where the trace
holds no such kernel (a parent from before it, an untraced run)."""

from harness import bytes_indexed, readers

PATTERN = r"hashed_hbm_gather_kernel"


def read(ctx):
    ms = readers.kernel_ms_per_step(ctx, PATTERN)
    if ms is None or not ctx.peaks or not ctx.shapes.get("hbm_fields"):
        return None
    need = bytes_indexed.hbm_gather_bytes_needed(ctx.shapes)
    return need / (ms / 1e3) / ctx.peaks["hbm_bytes_per_sec"] * 100
