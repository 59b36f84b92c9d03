"""Scan kernel: the collective that adds the chips' partials — the
`all-reduce` operations of the traced window (`ops_by_name`: seconds a
chip over the cell's chips), in milliseconds a statement a chip.  None
where the trace holds no such operation (a program with no mesh scan)."""
from benchmark import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    n = trace_reduce.span_count(ctx.trace, "bench:stmt.")
    secs = [s for name, s in (ctx.trace.get("ops_by_name") or {}).items()
            if "all-reduce" in name]
    return sum(secs) / n * 1e3 if n and secs else None
