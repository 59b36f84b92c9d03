"""tserver + scheduler: per statement, the sum of `queue_ms` of its
`tserver.read_offload` spans — from a tablet read handing its launch to
the pool beside the event loop to a thread taking it: what the hop costs.
None on a program that has no such span (it makes its launches on the
loop)."""
from benchmark import span_reduce

SPAN = "tserver.read_offload"


def read(ctx):
    trees = span_reduce.trees_of(ctx)
    if not trees or not any(s.name == SPAN for t in trees for s in t):
        return None
    return sum(span_reduce.tag_sum(t, SPAN, "queue_ms")
               for t in trees) / len(trees)
