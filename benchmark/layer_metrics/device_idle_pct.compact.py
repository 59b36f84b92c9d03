"""Device: the share of the traced window in which no operation ran."""
from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.idle_pct(ctx.trace)
