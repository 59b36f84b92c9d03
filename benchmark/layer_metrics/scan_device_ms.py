"""Scan kernel: device busy time (union of device operations) inside the
benchmark's statement spans, per statement, from the profiler's trace."""
from benchmark import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    n = trace_reduce.span_count(ctx.trace, "bench:stmt.")
    busy = trace_reduce.busy_in_spans(ctx.trace, "bench:stmt.")
    return busy / n * 1e3 if n and busy > 0 else None
