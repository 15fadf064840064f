"""Gather windows the planner sorted for and then refused (the
program's ``spmv_plan_rejections`` counter); each costs a whole sort."""


def read(ctx):
    return ctx.counters.get("plan_rejections")
