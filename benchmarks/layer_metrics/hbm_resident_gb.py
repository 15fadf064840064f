"""``hbm_in_use`` of the fullest device at the end of the last program
span inside ``data_build``: table, plan and lists as the first call
finds them."""

from harness import spans


def read(ctx):
    return spans.hbm_gb(ctx, "hbm_in_use", last=True)
