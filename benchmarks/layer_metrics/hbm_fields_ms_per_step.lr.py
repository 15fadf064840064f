"""Device self time a step under the program's ``tda.ssgd.table_hbm``
scope: whatever serves the fields of an indexed table whose ranges are
past VMEM (``pallas_hashed.field_form`` ``hbm``), in the gather pass
and in the scatter pass together. The scope lies inside
``tda.ssgd.gather`` / ``tda.ssgd.scatter``, whose own readers go on
counting it (harness/scopes_inner.py); nothing where no op is under
it."""

from harness import scopes_inner


def read(ctx):
    return scopes_inner.inner_scope_ms_per_step(ctx, "tda.ssgd.table_hbm")
