"""Share of the HBM roofline that a sweep's SpMV reaches: the bytes a
sweep needs moved (8 B a distinct edge + 12 B a vertex:
``harness/bytes_pagerank.py``) over the device time a sweep under the
program's ``tda.pagerank.spmv`` scope, over the chip's peak bandwidth.
By scope and not by a kernel's name, and the same whatever layout
implements the sweep. What caps it today: the fused plan holds 20 B a
slot and a few per cent of padding slots (about 40), and the kernel is
bound by its gather loop's selects and its one-hot products, not by
bytes: the share is small and is the room there is. It cannot read over
100. Nothing where the trace names no such scope."""

from harness import bytes_pagerank, scopes


def read(ctx):
    ms = scopes.scope_ms_per_step(ctx, "tda.pagerank.spmv")
    if not ms or ms <= 0 or not ctx.peaks:
        return None
    need = bytes_pagerank.sweep_bytes_needed(ctx.shapes)
    return need / (ms / 1e3) / ctx.peaks["hbm_bytes_per_sec"] * 100
