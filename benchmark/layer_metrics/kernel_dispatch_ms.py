"""Scan kernel: per statement, the sum of its `device.scan` spans — the
host's side of a launch: jit cache lookup, argument flattening, enqueue
(and the compile, where one lands in the window)."""
from benchmark import span_reduce


def read(ctx):
    return span_reduce.per_statement_ms(
        ctx, lambda t: span_reduce.total_ns(t, "device.scan"))
