"""Share of the HBM roofline that a Lloyd iteration's pass over the
points reaches: the bytes the algorithm needs (one read of the chip's
points, 80 B each at 20 float32 dimensions; ``harness/bytes_kmeans.py``)
over the device time an iteration under the program's
``tda.kmeans.assign`` and ``tda.kmeans.stats`` scopes, over the chip's
peak bandwidth. Bound by bytes at the roofline: 600 flop a point at
k = 10 are 6e10 an iteration, 0.3 ms of the MXU's peak beside 9.8 ms of
HBM (on the VPU, where float32-exact distances have to run, they cost
about as much as the read: PERF.md). Nothing where the trace names no
scope."""

from harness import bytes_kmeans, scopes


def read(ctx):
    parts = [scopes.scope_ms_per_step(ctx, "tda.kmeans." + p)
             for p in ("assign", "stats")]
    if None in parts or sum(parts) <= 0 or not ctx.peaks:
        return None
    need = bytes_kmeans.lloyd_iteration_bytes_needed(ctx.shapes)
    return need / (sum(parts) / 1e3) / ctx.peaks["hbm_bytes_per_sec"] * 100
