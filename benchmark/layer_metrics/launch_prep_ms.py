"""Scan kernel: per statement, the sum of its `launch.prepare` spans — what
a launch does on its thread before the dispatch: `prepare_launch` (the
request's constants, scales and signature) and the program lookup under
the kernel's lock.  Part of `read_prep_ms`.  None where no statement of
the window has the span (a program from before it)."""
from benchmark import span_reduce

SPAN = "launch.prepare"


def read(ctx):
    trees = span_reduce.trees_of(ctx)
    if not trees or not any(s.name == SPAN for t in trees for s in t):
        return None
    return sum(span_reduce.total_ns(t, SPAN) for t in trees) \
        / len(trees) / 1e6
