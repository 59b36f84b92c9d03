"""Device: the share of the device's idle seconds in the traced window
that falls inside a program span below `sql.execute` (the innermost span
open at that instant), on the profiler's clock."""
from benchmark import span_reduce


def read(ctx):
    return span_reduce.idle_attributed_pct(ctx)
