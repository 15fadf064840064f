"""The largest ``hbm_peak`` (``memory_stats()["peak_bytes_in_use"]`` as
the program's spans sampled it, the fullest device) over the spans
that ended inside ``data_build``: the peak the loader set, to hold
against ``hbm_peak_gb.*``, the harness's reading at the window's end."""

from harness import spans


def read(ctx):
    return spans.hbm_gb(ctx, "hbm_peak", last=False)
