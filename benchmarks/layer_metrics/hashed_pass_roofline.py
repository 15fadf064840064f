"""Share of the HBM roofline that a step over hashed rows reaches: the
bytes the algorithm needs (the sampled rows once, 157 B each at 39
int32 slots and a label; ``harness/bytes_hashed.py``) over the device
time a step under the program's ``tda.ssgd.gather`` and
``tda.ssgd.scatter`` scopes, over the chip's peak bandwidth. The passes
are bound by addresses (2 x 39 dependent accesses a row into a table
that sits in fast memory), so the share reads far under 1: it says how
far such a pass stands from its bytes. Nothing where the trace names
neither scope."""

from harness import bytes_hashed, scopes


def read(ctx):
    parts = [scopes.scope_ms_per_step(ctx, "tda.ssgd." + p)
             for p in ("gather", "scatter")]
    if None in parts or sum(parts) <= 0 or not ctx.peaks:
        return None
    need = bytes_hashed.hashed_step_bytes_needed(ctx.shapes)
    return need / (sum(parts) / 1e3) / ctx.peaks["hbm_bytes_per_sec"] * 100
