"""Seconds of the program's ``closure:prepare`` spans inside the
harness's ``data_build``: the edge list made on the host, laid on the
device, the start matrix scattered there and its pairs counted
(``transitive_closure.prepare_dense``; whole spans, their ``jit:*``
children included). Nothing where the program keeps no such span."""

from harness import spans


def read(ctx):
    got = spans.inside(ctx)
    mine = [s.seconds for s in (got[0] if got else [])
            if s.name == "closure:prepare"]
    return sum(mine) if mine else None
