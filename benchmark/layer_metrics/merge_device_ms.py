"""Merge kernel: device busy time of the merge programs (`busy_by_program`
entries whose name holds `chunk_merge_kernel` or `merge_gc`: seconds a
chip) per traced tablet compaction, in milliseconds.  Beside
`compact_tablet_ms` it says how much of a compaction is the chip and how
much the host.  None where the trace holds no compaction or no merge
program."""
from benchmark import trace_reduce

PROGRAMS = ("chunk_merge_kernel", "merge_gc")


def read(ctx):
    if ctx.trace is None:
        return None
    n = trace_reduce.span_count(ctx.trace, "bench:compact")
    secs = [s for name, s in (ctx.trace.get("busy_by_program") or {}).items()
            if any(p in name for p in PROGRAMS)]
    return sum(secs) / n * 1e3 if n and secs else None
