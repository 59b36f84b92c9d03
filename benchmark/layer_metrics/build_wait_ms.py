"""tserver + scheduler: per statement, the sum of `queue_ms` of its
`tserver.build_offload` spans — from a cold read handing its batch build
to the pool beside the event loop to a thread taking it, the wait for one
of the server's build slots included.  None on a program that has no such
span (it builds on the loop) or a window with no build."""
from benchmark import span_reduce

SPAN = "tserver.build_offload"


def read(ctx):
    trees = span_reduce.trees_of(ctx)
    if not trees or not any(s.name == SPAN for t in trees for s in t):
        return None
    return sum(span_reduce.tag_sum(t, SPAN, "queue_ms")
               for t in trees) / len(trees)
