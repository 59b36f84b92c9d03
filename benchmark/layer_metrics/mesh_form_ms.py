"""tserver + scheduler: per statement, the time of its `tserver.mesh_gather`
spans — a statement's tablet reads gathered into one launch: every
tablet's leader and lease gates, one read time for all of them, every
tablet's safe-time wait.  None where no statement of the window has such
a span (a program that serves a launch a tablet)."""
from benchmark import span_reduce

SPAN = "tserver.mesh_gather"


def read(ctx):
    trees = span_reduce.trees_of(ctx)
    if not trees or not any(s.name == SPAN for t in trees for s in t):
        return None
    return sum(span_reduce.total_ns(t, SPAN) for t in trees) \
        / len(trees) / 1e6
