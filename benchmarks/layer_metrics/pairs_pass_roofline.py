"""Share of the HBM roofline that a step over rows of (feature, value)
pairs reaches: the bytes the two passes need (a pair's id and value
read in each pass, the weight read, the slot's read-modify-write: 28 B
a pair; ``harness/bytes_pairs.py``) over the device time a step under
the program's ``tda.ssgd.gather`` and ``tda.ssgd.scatter`` scopes, over
the chip's peak bandwidth. The passes are bound by addresses (one
dependent access into 66 MB of weights a pair, each way), so the share
reads far under 1: it says how far, not why. Nothing where the trace
names neither scope or the family counted no pairs."""

from harness import bytes_pairs, scopes


def read(ctx):
    if not ctx.peaks or "pairs_per_step_mean" not in ctx.shapes:
        return None
    parts = [scopes.scope_ms_per_step(ctx, "tda.ssgd." + p)
             for p in ("gather", "scatter")]
    if None in parts or sum(parts) <= 0:
        return None
    need = bytes_pairs.pairs_step_bytes_needed(ctx.shapes)
    return need / (sum(parts) / 1e3) / ctx.peaks["hbm_bytes_per_sec"] * 100
