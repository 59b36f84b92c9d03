"""Merge kernel: the least time the chip could take for the traced
compactions — every input SST byte read once, every output byte written
once (`bytes_model.merge_bytes`) at the peak HBM rate of every chip the
cell has — as a share of the device busy time a chip inside the compact
spans.  Bound: memory."""
from benchmark import bytes_model, trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    busy = trace_reduce.busy_in_spans(ctx.trace, "bench:compact")
    least = sum(bytes_model.least_seconds(
        bytes_model.merge_bytes(s["input_bytes"], s["output_bytes"]),
        ctx.peak, ctx.cell.chips) for s in ctx.rec.of("compact"))
    return least / busy * 100 if busy > 0 and least > 0 else None
