"""tserver + scheduler: per statement, the sum of its
`tserver.read_resume` spans — from a tablet read's launch returning on its
pool thread to the event loop resuming the read: the loop busy with other
reads' steps, other tasks, or the interpreter's lock.  Part of
`read_prep_ms`.  None where no statement of the window has the span (a
program from before it)."""
from benchmark import span_reduce

SPAN = "tserver.read_resume"


def read(ctx):
    trees = span_reduce.trees_of(ctx)
    if not trees or not any(s.name == SPAN for t in trees for s in t):
        return None
    return sum(span_reduce.total_ns(t, SPAN) for t in trees) \
        / len(trees) / 1e6
