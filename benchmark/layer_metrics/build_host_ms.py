"""Batch formation: per statement, the host time of its cold reads' batch
builds — `batch.collect` (the blocks of the tablet's SSTs, projected to the
batch's columns) and `batch.build` (gather and pad, and inside it
`batch.version_link`, the `next_ht` link, where it is made), summed over
the builds, which run on pool threads beside the event loop.  None where
no statement of the window built a batch."""
from benchmark import span_reduce

SPANS = ("batch.collect", "batch.build")


def read(ctx):
    trees = span_reduce.trees_of(ctx)
    if not trees or not any(s.name in SPANS for t in trees for s in t):
        return None
    return sum(span_reduce.total_ns(t, n) for t in trees for n in SPANS) \
        / len(trees) / 1e6
