"""Batch formation: per statement, the time of its batches' transfers to
the device — the `batch.h2d` spans of its cold reads (a traced run waits
for each transfer inside its span).  None where no statement of the window
built a batch."""
from benchmark import span_reduce

SPAN = "batch.h2d"


def read(ctx):
    trees = span_reduce.trees_of(ctx)
    if not trees or not any(s.name == SPAN for t in trees for s in t):
        return None
    return sum(span_reduce.total_ns(t, SPAN) for t in trees) \
        / len(trees) / 1e6
