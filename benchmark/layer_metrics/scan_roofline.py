"""Scan kernel: the least time the chip could take for the traced
statements — one pass over the lanes each reads (`bytes_model.scan_bytes`)
at the peak HBM rate of every chip the cell has, the bytes spread over
them — as a share of the device busy time a chip inside the statement
spans.  Bound: memory."""
from benchmark import bytes_model, trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    busy = trace_reduce.busy_in_spans(ctx.trace, "bench:stmt.")
    least = sum(bytes_model.least_seconds(
        bytes_model.scan_bytes(ctx.data.table_rows, name.split(".", 1)[1]),
        ctx.peak, ctx.cell.chips) for name, _, _ in ctx.trace["spans"]
        if name.startswith("bench:stmt."))
    return least / busy * 100 if busy > 0 and least > 0 else None
